"""Known-good twin of bad_serving_except (no serving-except findings):
broad excepts on the serving loop route through the failure classifier,
re-raise, or catch narrowly."""
import logging

logger = logging.getLogger(__name__)


class Engine:
    def _dispatch(self, fn, uids):  # tpulint: serving-loop
        try:
            return fn()
        except Exception as e:
            # the sanctioned shape: the classifier seam decides
            self._handle_step_failure(e, uids, "dispatch")
            return None

    def _collect(self, st):  # tpulint: serving-loop
        try:
            return st.result()
        except Exception as e:
            verdict = classify_failure(e)
            if verdict is None:
                raise
            return {}

    def generate(self, fn):  # tpulint: serving-loop
        try:
            return fn()
        except Exception:
            raise                  # a bare re-raise defers the decision

    def _step(self, fn, uids):  # tpulint: serving-loop
        try:
            return fn()
        except Exception as e:
            # a call on the FailurePolicy receiver also routes
            return self.failures.recover(e, uids)

    def _probe(self, fn):  # tpulint: serving-loop
        try:
            return fn()
        except ValueError as e:    # narrow catches pick their own policy
            logger.warning("probe rejected: %s", e)
            return None

    def _handle_step_failure(self, e, uids, phase):
        logger.warning("%s failed: %s", phase, e)


def classify_failure(e):
    return None
