"""Gap between consecutive tokens of one stream on the wire, 95th
percentile over ALL gaps whose later token arrived inside the window.
Client's clock."""

from benchmarks.lib.common import quantile, window_token_gaps_ms


def read(rec):
    if rec["kind"] != "serve":
        return None
    gaps = window_token_gaps_ms(rec)
    return quantile(gaps, 0.95) if gaps else None
