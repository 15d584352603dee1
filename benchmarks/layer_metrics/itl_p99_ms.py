"""Serving step, seen from the wire: gap between consecutive tokens of one
stream, 99th percentile over all gaps in the window (client's clock).
In the saturated closed loop: the gap behind the slowest steps, those that
carry the chunk of a long prompt at long context (212-214 ms in six runs, my
chip runs, PR 23): what a new prompt costs the other 63 streams."""

from benchmarks.lib.common import quantile, window_token_gaps_ms


def read(rec):
    if rec["kind"] != "serve":
        return None
    gaps = window_token_gaps_ms(rec)
    return quantile(gaps, 0.99) if gaps else None
