"""Throughput over steps.

TPU-native analog of the reference's ``ThroughputTimer``
(``deepspeed/utils/timer.py:199``).  It reads no clock of its own: a
jitted step is dispatched asynchronously, so a timer around the call
measures the enqueue.  The caller hands in each step's wall time (the
engine: the interval between consecutive ``train_batch`` entries).
"""

from __future__ import annotations


class ThroughputTimer:
    """Samples/sec across steps (reference: utils/timer.py:199); the first
    ``start_step`` steps (compile, warm-up) are left out."""

    def __init__(self, batch_size: int, start_step: int = 2):
        self.batch_size = max(batch_size, 1)
        self.start_step = start_step
        self.global_step_count = 0
        self.total_elapsed_time = 0.0

    def record(self, seconds: float) -> None:
        """One more step, which took ``seconds`` of wall time."""
        self.global_step_count += 1
        if self.global_step_count > self.start_step:
            self.total_elapsed_time += seconds

    def avg_samples_per_sec(self) -> float:
        counted = self.global_step_count - self.start_step
        if counted > 0 and self.total_elapsed_time > 0:
            return self.batch_size * counted / self.total_elapsed_time
        return 0.0
