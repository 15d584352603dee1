"""Train step: device-busy time of the traced window, device 0, over the
program's own count of steps (``ds.train.dispatch`` spans begun in the
window).  The step's length by the device; ``train_step_p50_ms`` is the
driver's clock, from outside."""

from benchmarks.lib import train_scopes


def read(rec):
    booked = train_scopes.of(rec)
    return booked and booked.device_step_ms()
