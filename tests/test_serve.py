"""Serving subsystem tests (ISSUE 1): bucketing determinism, executor
cache accounting, scheduler batch formation / deadline shedding /
backpressure, and the end-to-end mixed-length acceptance demo on CPU.

Also covers the satellite stats plumbing the server reports through:
profiling.percentile / StepTimer p90/p99 and MetricsLogger flush().
"""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu import Alphafold2, obs
from alphafold2_tpu.data.synthetic import synthetic_requests
from alphafold2_tpu.serve import (BucketPolicy, FoldExecutor, FoldRequest,
                                  QueueFullError, Scheduler,
                                  SchedulerConfig, ServeMetrics)
from alphafold2_tpu.utils.logging import MetricsLogger
from alphafold2_tpu.utils.profiling import StepTimer, percentile

MSA_DEPTH = 3


@pytest.fixture(scope="module")
def model_and_params():
    model = Alphafold2(dim=32, depth=1, heads=2, dim_head=16,
                       predict_coords=True, structure_module_depth=1)
    n = 16
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, n), jnp.int32),
        msa=jnp.zeros((1, MSA_DEPTH, n), jnp.int32),
        mask=jnp.ones((1, n), bool),
        msa_mask=jnp.ones((1, MSA_DEPTH, n), bool))
    return model, params


def requests_of(lengths, key=1, msa_depth=MSA_DEPTH, **kwargs):
    reqs = synthetic_requests(jax.random.PRNGKey(key), num=len(lengths),
                              lengths=lengths, msa_depth=msa_depth)
    for r in reqs:
        for k, v in kwargs.items():
            setattr(r, k, v)
    return reqs


@pytest.mark.quick
class TestBucketPolicy:
    def test_powers_of_two_edges(self):
        p = BucketPolicy.powers_of_two(32, 512)
        assert p.edges == (32, 64, 128, 256, 512)
        assert BucketPolicy.powers_of_two(32, 96).edges == (32, 64, 96)

    def test_mapping_deterministic_and_minimal(self):
        p = BucketPolicy((16, 32, 48))
        for n in range(1, 49):
            b = p.bucket_for(n)
            assert b == p.bucket_for(n)          # same length, same shape
            assert b >= n
            assert b == min(e for e in p.edges if e >= n)

    def test_too_long_rejected(self):
        p = BucketPolicy((16, 32))
        with pytest.raises(ValueError, match="exceeds max bucket"):
            p.bucket_for(33)
        with pytest.raises(ValueError):
            BucketPolicy(())
        with pytest.raises(ValueError):
            BucketPolicy((0, 16))

    def test_assemble_shapes_masks_waste(self):
        p = BucketPolicy((16,))
        reqs = requests_of((8, 12))
        batch, waste = p.assemble(reqs, 16, 4)
        assert batch["seq"].shape == (4, 16)
        assert batch["mask"].shape == (4, 16)
        assert batch["msa"].shape == (4, MSA_DEPTH, 16)
        assert batch["msa_mask"].shape == (4, MSA_DEPTH, 16)
        # masks cover exactly the real tokens, rows 2-3 are batch fill
        assert np.asarray(batch["mask"]).sum(axis=1).tolist() == \
            [8, 12, 0, 0]
        assert np.allclose(waste, 1.0 - (8 + 12) / (4 * 16))
        # padded token slots are zero
        seq = np.asarray(batch["seq"])
        assert (seq[0, 8:] == 0).all() and (seq[2:] == 0).all()

    def test_assemble_pinned_msa_depth(self):
        """Ragged MSA depths under a pinned msa_depth still present ONE
        shape: shallow rows padded+masked, deep ones truncated to the
        first rows (query-first convention)."""
        p = BucketPolicy((16,))
        rng = np.random.default_rng(0)
        shallow = FoldRequest(seq=rng.integers(0, 20, 8),
                              msa=rng.integers(0, 20, (2, 8)))
        deep = FoldRequest(seq=rng.integers(0, 20, 8),
                           msa=rng.integers(0, 20, (6, 8)))
        bare = FoldRequest(seq=rng.integers(0, 20, 8))
        batch, _ = p.assemble([shallow, deep, bare], 16, 4, msa_depth=4)
        assert batch["msa"].shape == (4, 4, 16)
        mm = np.asarray(batch["msa_mask"])
        assert mm[0].sum() == 2 * 8 and mm[1].sum() == 4 * 8
        assert mm[2].sum() == 0                      # msa-free row masked
        # deep MSA keeps its FIRST rows
        assert np.array_equal(np.asarray(batch["msa"])[1, :, :8],
                              deep.msa[:4])
        # msa_depth=0 forces the MSA-free signature even with MSAs
        batch0, _ = p.assemble([shallow, deep], 16, 2, msa_depth=0)
        assert batch0["msa"] is None and batch0["msa_mask"] is None

    def test_assemble_msa_free(self):
        p = BucketPolicy((16,))
        reqs = requests_of((8,), msa_depth=0)
        batch, _ = p.assemble(reqs, 16, 2)
        assert batch["msa"] is None and batch["msa_mask"] is None

    def test_assemble_rejects_overflow(self):
        p = BucketPolicy((16,))
        reqs = requests_of((8, 8, 8))
        with pytest.raises(ValueError, match="> batch_size"):
            p.assemble(reqs, 16, 2)
        with pytest.raises(ValueError, match="> bucket_len"):
            p.assemble(requests_of((24,)), 16, 2)


@pytest.mark.quick
class TestStatsSatellites:
    def test_percentile_interpolates(self):
        vals = list(range(1, 11))  # 1..10
        assert percentile(vals, 50) == pytest.approx(5.5)
        assert percentile(vals, 90) == pytest.approx(9.1)
        assert percentile(vals, 99) == pytest.approx(9.91)
        assert percentile([], 99) == 0.0
        assert percentile([7.0], 90) == 7.0

    def test_steptimer_p90_p99_summary(self):
        t = StepTimer()
        t.durations = [float(i) for i in range(1, 101)]
        assert t.p90 == pytest.approx(percentile(t.durations, 90))
        assert t.p99 == pytest.approx(percentile(t.durations, 99))
        s = t.summary()
        for key in ("count", "mean_s", "p50_s", "p90_s", "p99_s",
                    "best_s"):
            assert key in s
        assert s["p90_s"] <= s["p99_s"]

    def test_metrics_logger_flush_close_context(self, tmp_path):
        path = tmp_path / "m.jsonl"
        with MetricsLogger(str(path), stdout=False) as logger:
            logger.log(step=1, loss=0.5)
            logger.flush()
            rec = json.loads(path.read_text().splitlines()[0])
            assert rec["step"] == 1 and rec["loss"] == 0.5
        assert logger._fh is None          # context exit closed it
        logger.flush()                     # no-op after close, no crash
        logger.close()

    def test_serve_metrics_snapshot(self, tmp_path):
        m = ServeMetrics(str(tmp_path / "s.jsonl"))
        m.record_enqueued(queue_depth=2)
        m.record_served(16, 0.5)
        m.record_batch(bucket_len=16, batch_size=2, n_real=1,
                       real_tokens=8, padding_waste=0.75,
                       batch_latency_s=0.5, queue_depth=1)
        m.record_shed()
        m.record_cache_hit()
        m.record_cache_miss()
        m.record_coalesced()
        snap = m.snapshot()
        assert snap["enqueued"] == 1 and snap["served"] == 1
        assert snap["shed"] == 1 and snap["batches"] == 1
        # cache section always present (zeros when caching is off)
        assert snap["cache"] == {"hits": 1, "misses": 1, "coalesced": 1,
                                 "hit_ratio": 0.5}
        assert snap["padding_waste"] == pytest.approx(1 - 8 / 32)
        assert snap["latency_by_bucket"]["16"]["p99_s"] == \
            pytest.approx(0.5)
        m.close()
        rec = json.loads((tmp_path / "s.jsonl").read_text().splitlines()[0])
        assert "queue_depth" in rec and "p99_latency_s" in rec


class TestExecutor:
    @pytest.mark.parametrize("differs", ["num_recycles", "batch_size",
                                         "msa_depth"])
    def test_cache_hit_miss_counts(self, model_and_params, differs):
        """A signature compiles once and then hits; one that differs in
        any one element of the key is a different executable: it compiles
        fresh, then hits, and leaves the first resident."""
        ex = FoldExecutor(*model_and_params, max_entries=4)
        policy = BucketPolicy((16,))
        batch, _ = policy.assemble(requests_of((8, 12)), 16, 2)
        r1 = ex.run(batch, num_recycles=0)
        assert ex.stats() == dict(ex.stats(), hits=0, misses=1)
        r2 = ex.run(batch, num_recycles=0)
        stats = ex.stats()
        assert stats["hits"] == 1 and stats["misses"] == 1
        assert r1.coords.shape == r2.coords.shape == (2, 16, 3)
        other, recycles = batch, 0
        if differs == "num_recycles":
            recycles = 1
        elif differs == "batch_size":
            other, _ = policy.assemble(requests_of((8, 12)), 16, 4)
        else:
            other, _ = policy.assemble(requests_of((8, 12)), 16, 2,
                                       msa_depth=MSA_DEPTH - 1)
        assert ex.key_for(other, recycles) != ex.key_for(batch, 0)
        for hits, misses in ((1, 2), (2, 2)):
            ex.run(other, num_recycles=recycles)
            assert ex.stats() == dict(ex.stats(), hits=hits, misses=misses)
        ex.run(batch, num_recycles=0)
        assert ex.stats() == dict(ex.stats(), hits=3, misses=2, resident=2)

    def test_lru_eviction_bounds_resident_set(self, model_and_params):
        ex = FoldExecutor(*model_and_params, max_entries=1)
        policy = BucketPolicy((16, 32))
        b16, _ = policy.assemble(requests_of((8,)), 16, 1)
        b32, _ = policy.assemble(requests_of((24,)), 32, 1)
        ex.run(b16, 0)
        ex.run(b32, 0)                       # evicts the 16-bucket entry
        stats = ex.stats()
        assert stats["evictions"] == 1 and stats["resident"] == 1
        # ExecKey grew (mesh_shape, model_tag) in ISSUE 7 and the variant
        # element in ISSUE 9 (see MIGRATING): single-chip untagged
        # opaque-fold executors key as (1,1)/""/"fold"
        assert stats["keys"] == [(32, 1, MSA_DEPTH, 0, (1, 1), "",
                                  "fold")]
        ex.run(b16, 0)                       # cold again after eviction
        assert ex.stats()["misses"] == 3

    @pytest.mark.parametrize("length", [4, 5, 6, 7, 8])
    def test_normalize_key(self, model_and_params, length):
        """`warmup()` and `profile()` callers pass the key at the length
        it had when they were written: the elements they leave out are
        this executor's defaults. An eight-element key (it carried the
        serving block-sparse route's kernel label) is refused, not cut."""
        ex = FoldExecutor(*model_and_params, model_tag="v1")
        full = (16, 2, MSA_DEPTH, 1, [2, 2], "v0", "step", "dense")
        defaults = (16, 2, MSA_DEPTH, 1, (1, 1), "v1", "fold")
        if length == 8:
            with pytest.raises(ValueError, match="4 to 7 elements"):
                ex._normalize_key(full)
            return
        got = ex._normalize_key(full[:length])
        want = (16, 2, MSA_DEPTH, 1, (2, 2), "v0", "step")
        assert got == want[:length] + defaults[length:]
        assert isinstance(got[4], tuple)

    def test_warmup_precompiles(self, model_and_params):
        ex = FoldExecutor(*model_and_params, max_entries=4)
        since = len(obs.builds.records())
        fresh = ex.warmup([(16, 1, MSA_DEPTH, 0)])
        booked = [(r["stage"], r["tagged"]) for r in
                  obs.builds.records()[since:]
                  if r["program"] == f"fold/16x1/m{MSA_DEPTH}/r0"]
        # the key's build, stage by stage, then its first run
        assert fresh == 1 and booked == [
            ("trace", True), ("lower", True), ("compile", True),
            ("first_run", True)]
        policy = BucketPolicy((16,))
        batch, _ = policy.assemble(requests_of((8,)), 16, 1)
        ex.run(batch, 0)
        stats = ex.stats()
        assert stats["misses"] == 1 and stats["hits"] == 1

    def test_compile_vs_fold_spans(self, model_and_params):
        """Cold key: the trace attributes XLA compile separately from
        the device run; warm key: fold span only."""
        ex = FoldExecutor(*model_and_params, max_entries=4)
        policy = BucketPolicy((16,))
        batch, _ = policy.assemble(requests_of((8,)), 16, 1)
        tracer = obs.Tracer(slow_k=4)
        cold = tracer.start_trace("cold")
        ex.run(batch, 0, trace=cold)
        cold.finish("ok")
        names = [s["name"] for s in cold.record()["spans"]]
        # `compile` and `fold` close after the stages they are split in
        assert names == ["trace", "lower", "backend_compile", "compile",
                         "dispatch", "device_wait", "fold"]
        warm = tracer.start_trace("warm")
        ex.run(batch, 0, trace=warm)
        warm.finish("ok")
        dispatch, device_wait, span = warm.record()["spans"]
        assert span["name"] == "fold" and span["dur_s"] > 0
        assert (dispatch["name"], device_wait["name"]) == (
            "dispatch", "device_wait")
        assert span["start_s"] <= dispatch["start_s"] \
            <= device_wait["start_s"]
        assert dispatch["dur_s"] + device_wait["dur_s"] <= span["dur_s"] \
            + 2e-6


class TestScheduler:
    def test_batch_formation_under_max_wait(self, model_and_params):
        """Two requests < max_batch_size coalesce into ONE batch once the
        oldest has waited max_wait_ms."""
        ex = FoldExecutor(*model_and_params)
        metrics = ServeMetrics()
        config = SchedulerConfig(max_batch_size=4, max_wait_ms=200.0,
                                 num_recycles=0)
        with Scheduler(ex, BucketPolicy((16,)), config, metrics) as sched:
            t1, t2 = [sched.submit(r) for r in requests_of((8, 12))]
            r1, r2 = t1.result(timeout=600), t2.result(timeout=600)
        assert r1.ok and r2.ok
        assert r1.coords.shape == (8, 3) and r2.coords.shape == (12, 3)
        snap = metrics.snapshot()
        assert snap["batches"] == 1        # coalesced, not two singles
        assert snap["served"] == 2

    def test_deadline_shedding(self, model_and_params):
        ex = FoldExecutor(*model_and_params)
        metrics = ServeMetrics()
        config = SchedulerConfig(num_recycles=0)
        with Scheduler(ex, BucketPolicy((16,)), config, metrics) as sched:
            req = requests_of((8,), deadline_s=0.0)[0]
            resp = sched.submit(req).result(timeout=60)
        assert resp.status == "shed" and not resp.ok
        assert resp.coords is None
        assert "deadline" in resp.error
        assert metrics.snapshot()["shed"] == 1
        assert ex.stats()["misses"] == 0   # never touched the executor

    def test_bounded_queue_backpressure(self, model_and_params):
        ex = FoldExecutor(*model_and_params)
        metrics = ServeMetrics()
        # worker can't form a batch (huge max_wait, huge max_batch), so
        # the first request parks in pending and holds queue depth at 1
        config = SchedulerConfig(max_batch_size=8, max_wait_ms=60_000.0,
                                 queue_limit=1, full_policy="reject",
                                 num_recycles=0)
        sched = Scheduler(ex, BucketPolicy((16,)), config, metrics)
        sched.start()
        reqs = requests_of((8, 8))
        ticket = sched.submit(reqs[0])
        with pytest.raises(QueueFullError):
            sched.submit(reqs[1])
        sched.stop(drain=False)
        assert ticket.result(timeout=60).status == "cancelled"
        snap = metrics.snapshot()
        assert snap["rejected"] == 1 and snap["cancelled"] == 1
        assert ex.stats()["misses"] == 0

    def test_metrics_sink_failure_does_not_kill_scheduler(
            self, model_and_params):
        """A failing JSONL sink (disk full) is an observability problem,
        not a serving outage: requests keep resolving ok."""
        class BoomMetrics(ServeMetrics):
            def record_batch(self, *a, **kw):
                raise OSError("disk full")

        ex = FoldExecutor(*model_and_params)
        config = SchedulerConfig(max_batch_size=2, max_wait_ms=10.0,
                                 num_recycles=0)
        with Scheduler(ex, BucketPolicy((16,)), config,
                       BoomMetrics()) as sched:
            r1 = sched.submit(requests_of((8,))[0]).result(timeout=600)
            r2 = sched.submit(requests_of((12,))[0]).result(timeout=600)
        assert r1.ok and r2.ok

    def test_submit_before_start_rejected(self, model_and_params):
        sched = Scheduler(FoldExecutor(*model_and_params),
                          BucketPolicy((16,)))
        with pytest.raises(RuntimeError, match="before start"):
            sched.submit(requests_of((8,))[0])

    def test_end_to_end_mixed_lengths(self, model_and_params, tmp_path):
        """ISSUE 1 acceptance demo (+ ISSUE 3 obs enabled): >= 32
        concurrent synthetic requests of >= 3 distinct lengths all
        complete with per-request shapes, distinct compilations <=
        buckets used, the JSONL carries queue-depth and p99-latency
        records, and EVERY request yields exactly one complete trace
        whose span tree covers submit -> terminal with a non-zero fold
        span."""
        jsonl = str(tmp_path / "serve.jsonl")
        trace_jsonl = str(tmp_path / "traces.jsonl")
        tracer = obs.Tracer(jsonl_path=trace_jsonl, slow_k=8)
        ex = FoldExecutor(*model_and_params, max_entries=4)
        metrics = ServeMetrics(jsonl)
        config = SchedulerConfig(max_batch_size=4, max_wait_ms=20.0,
                                 num_recycles=0)
        policy = BucketPolicy((16, 32, 48))
        lengths = (12, 24, 40)
        reqs = synthetic_requests(jax.random.PRNGKey(7), num=32,
                                  lengths=lengths, msa_depth=MSA_DEPTH)
        by_id = {r.request_id: r for r in reqs}
        tickets = []
        tickets_lock = threading.Lock()

        with Scheduler(ex, policy, config, metrics,
                       tracer=tracer) as sched:
            def submit_slice(i):
                for r in reqs[i::4]:
                    t = sched.submit(r)
                    with tickets_lock:
                        tickets.append(t)

            threads = [threading.Thread(target=submit_slice, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            responses = [t.result(timeout=600) for t in tickets]

        assert len(responses) == 32
        for resp in responses:
            req = by_id[resp.request_id]
            assert resp.ok, resp.error
            assert resp.coords.shape == (req.length, 3)
            assert resp.confidence.shape == (req.length,)
            assert np.isfinite(resp.coords).all()
            assert resp.bucket_len == policy.bucket_for(req.length)

        stats = ex.stats()
        assert stats["misses"] <= policy.num_buckets    # compile bound
        snap = metrics.snapshot()
        assert snap["served"] == 32 and snap["shed"] == 0
        assert 0.0 < snap["padding_waste"] < 1.0
        metrics.close()

        records = [json.loads(line) for line in open(jsonl)]
        assert records, "no JSONL metrics emitted"
        for rec in records:
            assert "queue_depth" in rec
            assert "p99_latency_s" in rec and rec["p99_latency_s"] > 0

        # ISSUE 3 acceptance: exactly one complete trace per request,
        # span tree covering submit -> terminal with non-zero fold time
        tracer.close()
        traces = [json.loads(line) for line in open(trace_jsonl)]
        trace_by_id = {}
        for tr in traces:
            assert tr["schema"] == 1
            assert tr["request_id"] not in trace_by_id, "duplicate trace"
            trace_by_id[tr["request_id"]] = tr
        assert set(trace_by_id) == set(by_id)
        for tr in traces:
            assert tr["status"] == "ok" and tr["source"] == "fold"
            names = [s["name"] for s in tr["spans"]]
            assert names[0] == "submit" and "queue" in names
            fold_s = sum(s["dur_s"] for s in tr["spans"]
                         if s["name"] in ("fold", "compile"))
            assert fold_s > 0, tr
        assert stats["misses"] <= policy.num_buckets  # tracing minted
        # no extra executables; the slow-trace ring is populated
        assert sched.serve_stats()["traces"]
