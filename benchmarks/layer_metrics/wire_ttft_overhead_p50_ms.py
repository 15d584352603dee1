"""Wire layer (gateway/server.py): the client's time from SENDING a
request to its first token, minus the engine's own first-token time for
the same uid (``request_metrics()``: arrival at ``put`` to first token).
Median over the requests due in the window."""

from benchmarks.lib.common import quantile


def read(rec):
    if rec["kind"] != "serve":
        return None
    vals = []
    for r in rec["requests_in_window"]:
        e = rec["engine_requests"].get(r["uid"])
        if e and e.get("ttft_ms") is not None and r["token_t"]:
            vals.append((r["token_t"][0] - r["sent"]) * 1e3 - e["ttft_ms"])
    return quantile(vals, 0.5) if vals else None
