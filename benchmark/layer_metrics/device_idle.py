"""Device: share of the traced window in which no operation ran on the chip
(1 - busy / window, from the profiler's trace, `trace_reduce.py`)."""


def read(spans, snapshot, trace, cell):
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
