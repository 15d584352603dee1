"""Known-good twin of bad_raise_escape: every device-ish raise
reachable from a serving loop is caught between the raise and the loop
and routed through the failure classifier seam.
"""


class DispatchTimeoutError(RuntimeError):
    pass


class InjectedFault(RuntimeError):
    pass


class Engine:
    def __init__(self, failures):
        self.failures = failures

    def step(self, fn):  # tpulint: serving-loop
        try:
            return self._dispatch(fn)
        except DispatchTimeoutError as e:
            return self.failures.classify_failure(e)

    def _dispatch(self, fn):
        if fn is None:
            raise DispatchTimeoutError("device stalled")
        return fn()

    def generate(self, fn):  # tpulint: serving-loop
        try:
            return self._inject(fn)
        except Exception as e:
            return self.failures.classify_failure(e)

    def _inject(self, fn):
        # caught INSIDE the callee: never reaches the serving loop
        try:
            if fn is None:
                raise InjectedFault("chaos tier fault")
        except InjectedFault as e:
            return self.failures.classify_failure(e)
        return fn()

    def flush(self, fn):  # tpulint: serving-loop
        try:
            return self.failures.run(fn)
        except DispatchTimeoutError as e:
            return self.failures.classify_failure(e)
