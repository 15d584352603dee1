"""1 - union of device-op intervals over the traced window.  Device
trace."""


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or rec["device"]["platform"] != "tpu":
        return None
    return 100.0 * t["idle_share"]
