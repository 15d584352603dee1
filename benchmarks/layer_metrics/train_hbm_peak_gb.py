"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip."""


def read(rec):
    d = rec["device"]
    if rec["kind"] != "train" or d["platform"] != "tpu":
        return None
    return d["memory_peak_bytes"] / 1e9
