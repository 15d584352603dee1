"""Device trace, device 0: time inside all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute operations during which
no other operation runs there, over the traced window."""


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "train" or not t or rec["device"]["platform"] != "tpu":
        return None
    return 100.0 * t["exposed_collective_s"] / t["window_s"]
