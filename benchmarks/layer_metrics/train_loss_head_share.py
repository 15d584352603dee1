"""Kernels: device time under ``unembed`` (final norm, head) and
``loss`` (the cross entropy over the vocabulary), all passes, over
device-busy time."""

from benchmarks.lib import train_scopes


def read(rec):
    booked = train_scopes.of(rec)
    return booked and booked.share("by_scope", "unembed", "loss")
