"""alphafold2_tpu.obs — unified observability: tracing + metrics.

Three uncoordinated telemetry surfaces grew up with the serving stack
(`StepTimer`, `ServeMetrics`' per-batch JSONL, `MetricsLogger`); this
package replaces their private bookkeeping with these primitives:

- trace:    request-scoped spans with stable trace IDs, created at
            `Scheduler.submit` and propagated through coalescing
            (followers link to the leader's trace), batching, the
            executor (compile vs run), and the result cache — emitted
            as JSONL, slowest-K exposed via `serve_stats()["traces"]`.
            `NULL_TRACER` makes instrumentation zero-cost when off.
- registry: process-wide `MetricsRegistry` (counter / gauge /
            histogram with fixed exponential latency buckets, labels,
            thread-safe) that serve, cache, and train report into.
- device:   the same clock's other half: device time by kernel from the
            program's own executable (`device.profile`, the kernel
            vocabulary `device.KERNELS`), and a profiler capture's idle
            gaps booked to the scheduler worker's intervals, which the
            tracer enters as `jax.profiler.TraceAnnotation`s
            (`device.reduce`).
- builds:   set-up on the same clock: every program's trace, lowering,
            compile or cache read, and first run as ordered build records
            (`builds.records()`, tagged with the program: a
            `FoldExecutor` key, the training step), and each collection
            of Python's collector (`builds.collections()`); installed on
            import, always on.
- export:   Prometheus text exposition + JSONL sharing one versioned
            `"schema": 1` record convention; `flatten()` for
            arbitrary-depth dict keys.

`tools/obs_report.py` renders the per-stage latency waterfall and the
top-K slowest traces from a trace JSONL file (README "Observability").
"""

from alphafold2_tpu.obs import builds, device  # noqa: F401
from alphafold2_tpu.obs.export import (JsonlExporter, SCHEMA_VERSION,  # noqa: F401
                                       flatten, prometheus_text,
                                       registry_json, write_prometheus)
from alphafold2_tpu.obs.registry import (DEFAULT_LATENCY_BUCKETS,  # noqa: F401
                                         Counter, Gauge, Histogram,
                                         MetricsRegistry, get_registry,
                                         set_registry)
from alphafold2_tpu.obs.slo import (SLOClass, SLOEngine,  # noqa: F401
                                    SLOPolicy)
from alphafold2_tpu.obs.trace import (NULL_TRACE, NULL_TRACER,  # noqa: F401
                                      MultiTrace, Trace, TraceContext,
                                      Tracer)
