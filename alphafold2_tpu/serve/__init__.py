"""alphafold2_tpu.serve — length-bucketed batching inference server.

The serving stack, bottom-up:

- request:   FoldRequest/FoldResponse/FoldTicket — ragged in, exact out
- features:  FeaturePool/PipelineScheduler — the two-stage pipeline
             front: RAW jobs (strings + raw MSA) featurize on a CPU
             worker pool with their own cache tier (cache.FeatureCache,
             feature_key upstream of fold_key) + in-flight coalescing,
             then feed the fold queue (README "Feature pipeline")
- bucketing: BucketPolicy — ragged lengths onto a closed shape set
- executor:  FoldExecutor — LRU cache of compiled fold executables
- scheduler: Scheduler — dynamic batching, deadlines, backpressure,
             optional result cache + in-flight coalescing
- metrics:   ServeMetrics — counters, padding waste, latency tails, JSONL
             KeyFrequencyLog — served-key frequencies in the
             cache_warm profile format (`Scheduler(key_log=)`)
             (all mirrored into the process-wide obs.MetricsRegistry;
             pass `Scheduler(..., tracer=obs.Tracer(...))` for
             request-scoped traces — README "Observability")
- meshpolicy: MeshPolicy/FoldMemoryModel/DeviceSliceAllocator — pass
             `Scheduler(..., mesh_policy=MeshPolicy.from_model(...))`
             for multi-chip serving: per-bucket device slices (short
             folds single-chip, long folds pair-sharded over a
             `parallel.mesh`), concurrent disjoint-slice execution, and
             the analytic-HBM admission guard (README "Multi-chip
             serving")
- recycle:   RecyclePolicy — pass `Scheduler(recycle_policy=
             RecyclePolicy(converge_tol=...))` and the scheduler owns
             the recycle loop: early-exit converged folds, preempt
             between recycles for deadline traffic, stream per-recycle
             progressive results, and — with `continuous=True` —
             refill freed rows mid-loop with pending requests via the
             row-masked init program, so a hot bucket's slice never
             idles a row; `cross_bucket=True` additionally lets a
             freed row serve a SHORTER bucket's pending fold at the
             host shape (priced per admit by meshpolicy's
             AdmissionPricer) and `eager_form=True` launches thin
             queues' batches immediately, counting on admission to
             top them up (README "Iteration-level scheduling" /
             "Continuous batching")
- cascade:   CascadePolicy/build_draft_scheduler + confidence:
             ConfidenceGate/score_response — pass `Scheduler(cascade=
             CascadePolicy(draft=build_draft_scheduler(...)))` and
             interactive submits fold on a small draft tier first; a
             confidence gate (mean pLDDT, optional distogram entropy)
             accepts the draft or escalates to the flagship through
             the ordinary submit seam. `qos="express"` +
             `FeaturePool(express=StubEmbedder())` adds the MSA-free
             express lane with its own metric/SLO class (README
             "Model cascade & express lane")
- resilience: RetryPolicy/CircuitBreaker/Quarantine — pass
             `Scheduler(..., retry=RetryPolicy(...))` for transient-
             batch retry, poison isolation by bisection + quarantine,
             non-finite output validation, the executor watchdog, and
             degraded mode (README "Failure handling & degraded mode")
- preemption: PreemptionWatcher + notice sources (metadata/signal/
             file) — spot reclaim as a scheduled migration: the notice
             flips the scheduler into reclaim mode, `drain(grace_s=)`
             spills every in-flight loop it cannot finish, and the
             checkpoint store publishes an orphan manifest the fleet
             controller adopts onto survivors (README "Spot &
             preemptible serving")
- xla_errors: classify/attributed_rows — pure-function XLA/TPU error
             payload parser: transient-vs-deterministic verdicts plus
             best-effort per-row attribution feeding the row-isolation
             path; consulted by RetryPolicy only where the legacy
             marker list has no opinion
- faults:    FaultPlan — seeded chaos injection threaded through
             FoldExecutor / FoldCache / fleet.PeerCacheClient behind
             no-op defaults (tools/serve_loadtest.py --chaos)

`FoldCache` (re-exported from alphafold2_tpu.cache) makes the server
content-addressed: pass `Scheduler(..., cache=FoldCache(...),
model_tag=...)` and duplicate requests are served from the store or
coalesced onto the in-flight fold instead of re-folding (README
"Result cache & deduplication"). Off by default.

Minimal use (see README "Serving"):

    from alphafold2_tpu import serve
    executor = serve.FoldExecutor(model, params)
    sched = serve.Scheduler(executor, serve.BucketPolicy((64, 128, 256)),
                            serve.SchedulerConfig(msa_depth=5),
                            cache=serve.FoldCache(),
                            model_tag="demo@params-v1")
    with sched:
        sched.warmup()
        ticket = sched.submit(serve.FoldRequest(seq_tokens, msa=msa_tokens))
        response = ticket.result(timeout=120)
"""

from alphafold2_tpu.cache import (FeatureCache, FoldCache,  # noqa: F401
                                  feature_key, fold_key)
from alphafold2_tpu.obs import (MetricsRegistry, Tracer,  # noqa: F401
                                get_registry, prometheus_text)
from alphafold2_tpu.serve.bucketing import BucketPolicy, default_policy  # noqa: F401
from alphafold2_tpu.serve.bulk import BulkPolicy, BulkQueue  # noqa: F401
from alphafold2_tpu.serve.cascade import (CascadePolicy,  # noqa: F401
                                          build_draft_scheduler)
from alphafold2_tpu.serve.confidence import (ConfidenceGate,  # noqa: F401
                                             ConfidenceScore,
                                             distogram_entropy,
                                             plddt_score, score_response)
from alphafold2_tpu.serve.executor import FoldExecutor  # noqa: F401
from alphafold2_tpu.serve.faults import FaultInjected, FaultPlan  # noqa: F401
from alphafold2_tpu.serve.features import (FeaturePool,  # noqa: F401
                                           PipelineScheduler,
                                           RawFoldRequest, StubEmbedder,
                                           express_featurize,
                                           featurize_raw,
                                           featurizer_config_digest)
from alphafold2_tpu.serve.meshpolicy import (AdmissionDecision,  # noqa: F401
                                             AdmissionPricer,
                                             DeviceSliceAllocator,
                                             FoldMemoryModel, MeshPolicy,
                                             SliceLease)
from alphafold2_tpu.serve.metrics import (KeyFrequencyLog,  # noqa: F401
                                          ServeMetrics)
from alphafold2_tpu.serve.preemption import (FileNoticeSource,  # noqa: F401
                                             MetadataNoticeSource,
                                             PreemptionNotice,
                                             PreemptionWatcher,
                                             SignalNoticeSource)
from alphafold2_tpu.serve.recycle import RecyclePolicy  # noqa: F401
from alphafold2_tpu.serve.request import (FoldProgress, FoldRequest,  # noqa: F401
                                          FoldResponse, FoldTicket)
from alphafold2_tpu.serve.resilience import (CircuitBreaker,  # noqa: F401
                                             Quarantine, RetryPolicy,
                                             TransientExecutorError,
                                             WatchdogTimeout)
from alphafold2_tpu.serve.scheduler import (DrainingError,  # noqa: F401
                                            QueueFullError, Scheduler,
                                            SchedulerConfig)
from alphafold2_tpu.serve.xla_errors import (XlaErrorClass,  # noqa: F401
                                             attributed_rows,
                                             classify)
