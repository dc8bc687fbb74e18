"""Timing utilities.

Net-new vs the reference, which has no profiler hooks at all (SURVEY.md
§5.1 — ad-hoc time.time() in a notebook is all it offers). Step time IS
the benchmark metric (BASELINE.json), so the timer is first-class:

- `percentile`: the one interpolating percentile everything reports
  through (StepTimer, serve.ServeMetrics, bench) — one stats path, no
  two subtly-different p99 definitions;
- `StepTimer`: wall-clock accumulator with mean/p50/p90/p99/min stats,
  used by `train.fit(step_timer=...)` and bench.py.

The door to the profiler is `alphafold2_tpu.obs.device` (device time by
kernel, and a capture's idle gaps booked to the worker's intervals).
"""

from __future__ import annotations

import contextlib
import time
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile (numpy's default method) on a
    possibly-unsorted sequence or array; 0.0 for empty input."""
    import numpy as np

    return float(np.percentile(values, q)) if len(values) else 0.0


class StepTimer:
    """Accumulates wall-clock step durations (seconds).

    `histogram`: optional obs.registry.Histogram every stop() also
    observes into, so step timings land in the process-wide metrics
    registry (Prometheus-exportable) without a second timing path. The
    p50/p90/p99 properties and Histogram.percentile share ONE quantile
    implementation — `percentile` above — so the two views can never
    disagree on what a p99 means."""

    def __init__(self, histogram=None):
        self.durations: List[float] = []
        self.histogram = histogram
        self._start: Optional[float] = None

    def start(self):
        self._start = time.perf_counter()

    def stop(self):
        if self._start is None:
            raise RuntimeError("StepTimer.stop() without start()")
        dur = time.perf_counter() - self._start
        self.durations.append(dur)
        self._start = None
        if self.histogram is not None:
            self.histogram.observe(dur)

    @contextlib.contextmanager
    def measure(self):
        self.start()
        try:
            yield
        finally:
            self.stop()

    @property
    def count(self) -> int:
        return len(self.durations)

    @property
    def mean(self) -> float:
        return sum(self.durations) / max(len(self.durations), 1)

    @property
    def p50(self) -> float:
        return percentile(self.durations, 50)

    @property
    def p90(self) -> float:
        return percentile(self.durations, 90)

    @property
    def p99(self) -> float:
        return percentile(self.durations, 99)

    @property
    def best(self) -> float:
        return min(self.durations) if self.durations else 0.0

    def summary(self) -> dict:
        return {"count": self.count, "mean_s": self.mean,
                "p50_s": self.p50, "p90_s": self.p90, "p99_s": self.p99,
                "best_s": self.best}
