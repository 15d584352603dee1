"""Train step: device time of the operations under
``rematted_computation`` (what ``jax.checkpoint`` runs again in the
backward) over device-busy time, traced window, device 0."""

from benchmarks.lib import train_scopes


def read(rec):
    booked = train_scopes.of(rec)
    return booked and booked.share("by_pass", "recompute")
