"""Parallelism: in how many of forward, recomputation and backward an
operation under ``zero_gather`` runs (ZeRO-3's gather of a parameter at
its use).  The gauge ``training_zero3_gather_bytes_per_step`` times this
is a step's gathered bytes.  None where nothing is gathered."""

from benchmarks.lib import train_scopes


def read(rec):
    booked = train_scopes.of(rec)
    return booked and booked.passes_under("zero_gather")
