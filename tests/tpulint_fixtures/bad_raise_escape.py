"""Known-bad: device-ish exceptions escaping serving loops (tpulint:
raise-escape).

Three escape shapes: a raise two calls deep with no handler between,
a direct raise in the loop body, and the watchdog dispatch seam
(``.failures.run``) called bare — a virtual DispatchTimeoutError
source even though no raise is visible here.
"""


class DispatchTimeoutError(RuntimeError):
    pass


class InjectedFault(RuntimeError):
    pass


class Engine:
    def __init__(self, failures):
        self.failures = failures

    def step(self, fn):  # tpulint: serving-loop  # BAD: _dispatch raises through
        return self._dispatch(fn)

    def _dispatch(self, fn):
        if fn is None:
            raise DispatchTimeoutError("device stalled")
        return fn()

    def generate(self, fn):  # tpulint: serving-loop  # BAD: direct raise
        if fn is None:
            raise InjectedFault("chaos tier fault")
        return fn()

    def flush(self, fn):  # tpulint: serving-loop  # BAD: bare dispatch seam
        return self.failures.run(fn)
