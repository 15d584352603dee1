"""Kernels: device time of the operations under none of the training
scopes over device-busy time: the check on the instrumentation itself
(``train_scopes``' ``unscoped_top`` names them)."""

from benchmarks.lib import train_scopes


def read(rec):
    booked = train_scopes.of(rec)
    return booked and booked.share("by_scope", "none")
