"""Train step: device time of the step's own parts (``cast_params``,
``grad_accumulate``, ``grad_epilogue``, ``optimizer``) over device-busy
time."""

from benchmarks.lib import train_scopes


def read(rec):
    booked = train_scopes.of(rec)
    return booked and booked.share("by_pass", "update")
