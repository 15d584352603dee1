"""Kernels: device time under the training forward's ``attn`` scope
(the call of ``attention_fn``, whatever implements it), forward,
recomputation and backward, over device-busy time."""

from benchmarks.lib import train_scopes


def read(rec):
    booked = train_scopes.of(rec)
    return booked and booked.share("by_scope", "attn")
