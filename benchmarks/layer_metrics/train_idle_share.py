"""1 - union of device-op intervals over the traced window, averaged over
the chips.  Device trace."""


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "train" or not t or rec["device"]["platform"] != "tpu":
        return None
    return 100.0 * t["idle_share"]
