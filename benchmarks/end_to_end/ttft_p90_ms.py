"""Time from when a request was DUE to be sent to its first token on the
wire, 90th percentile over the requests due inside the window.  A
request that failed or got no token counts with the time until the run
gave up on it.  Client's clock."""

from benchmarks.lib.common import quantile


def read(rec):
    if rec["kind"] != "serve":
        return None
    w = rec["window"]
    vals = []
    for r in rec["requests"]:
        if not (w["t_open"] <= r["due"] < w["t_close"]):
            continue
        first = r["token_t"][0] if r["token_t"] else rec["t_end"]
        vals.append((first - r["due"]) * 1e3)
    return quantile(vals, 0.90) if vals else None
