from .logging import logger, log_dist, warning_once
from .timer import ThroughputTimer

__all__ = ["logger", "log_dist", "warning_once", "ThroughputTimer"]
