"""``memory_stats()["peak_bytes_in_use"]``: weights, KV pool, step."""


def read(rec):
    d = rec["device"]
    if rec["kind"] != "serve" or d["platform"] != "tpu":
        return None
    return d["memory_peak_bytes"] / 1e9
