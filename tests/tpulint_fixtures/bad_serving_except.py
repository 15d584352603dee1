"""Known-bad: broad excepts in serving-loop methods that bypass the
failure classifier (tpulint: serving-except).  Each handler logs (so
silent-except stays quiet — this fixture isolates its own rule) but
invents a local failure policy instead of routing through the ONE
classifier seam."""
import logging

logger = logging.getLogger(__name__)


class Engine:
    def _dispatch(self, fn):  # tpulint: serving-loop
        try:
            return fn()
        except Exception as e:                       # BAD: ad-hoc policy
            logger.warning("step failed: %s", e)
            return None

    def _collect(self, st):  # tpulint: serving-loop
        try:
            return st.result()
        except:                                      # BAD: bare except  # noqa: E722
            logger.warning("collect failed; dropping step")
            return {}

    def generate(self, fn):  # tpulint: serving-loop
        try:
            return fn()
        except BaseException as e:                   # BAD: swallows all
            logger.error("generate failed: %s", e)
            self._retry = True
            return {}

    def _step(self, fn):  # tpulint: serving-loop
        try:
            return fn()
        except Exception as e:                       # BAD: near-miss name
            # counting/logging a "failure" is not ROUTING it — only the
            # exact classifier seam (or a .failures receiver) passes
            logger.warning("step failed: %s", e)
            self.metrics.count_failures(e)
            return None
