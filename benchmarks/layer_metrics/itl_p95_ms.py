"""Serving step, seen from the wire: gap between consecutive tokens of one
stream, 95th percentile over all gaps in the window (client's clock).
In the saturated closed loop (my chip runs, PR 23, six runs) the gaps level
off at 153-157 ms from the 90th to the 93rd percentile, the steps filled to
the token budget, and then climb 7-13 ms per point of rank (94th 158-163,
95th 164-171, 96th 181-183): this percentile sits on that slope and follows
how many steps in the window were full, so it is no end-to-end metric there.
``itl_p99_ms`` beside it repeats to 0.5%."""

from benchmarks.lib.common import quantile, window_token_gaps_ms


def read(rec):
    if rec["kind"] != "serve":
        return None
    gaps = window_token_gaps_ms(rec)
    return quantile(gaps, 0.95) if gaps else None
