"""Cross-bucket continuous batching tests (ISSUE 13): the
AdmissionPricer's priced trade (pad-frac guard, deadline tiebreak,
native-imminent refusal, extension pricing), cross-bucket admitted-row
numerics byte-equal to folding the same request alone at the HOST shape
(single-chip and on a 1x2 mesh lease), the HBM host-shape re-price
falling back to native-bucket formation, admission-aware eager batch
formation, the cross_bucket=False scrubbed-stats identity pin,
padding-as-dead-blocks contact planning, and the loadtest
--cross-bucket/--eager-form flag surface."""

import json
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu import Alphafold2
from alphafold2_tpu.data.synthetic import synthetic_requests
from alphafold2_tpu.obs.registry import MetricsRegistry
from alphafold2_tpu.serve import (AdmissionPricer, BucketPolicy,
                                  FoldExecutor, FoldMemoryModel,
                                  FoldRequest, MeshPolicy, RecyclePolicy,
                                  Scheduler, SchedulerConfig,
                                  ServeMetrics)

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


def requests_of(lengths, key=1):
    return synthetic_requests(jax.random.PRNGKey(key),
                              num=len(lengths), lengths=lengths,
                              msa_depth=MSA_DEPTH)


class TestAdmissionPricer:
    def price(self, pricer, **kw):
        base = dict(native_len=16, host_len=32, length=12,
                    batch_size=4, msa_depth=3, candidate_steps=3,
                    remaining_host_steps=3, native_delay_s=1.0,
                    deadline_slack_s=None, host_step_s=0.1)
        base.update(kw)
        return pricer.price(**base)

    def test_step_cost_monotone_in_length(self):
        p = AdmissionPricer()
        assert p.step_cost(32, 4, 3) > p.step_cost(16, 4, 3) \
            > p.step_cost(8, 4, 3)

    def test_pad_frac_guard_refuses(self):
        p = AdmissionPricer(max_pad_frac=0.5)
        d = self.price(p, length=12)            # 1 - 12/32 = 0.625
        assert not d.admit and d.reason == "pad_frac"
        assert d.pad_frac == pytest.approx(0.625)
        # even a deadline about to die cannot override the hard guard
        d = self.price(p, length=12, deadline_slack_s=0.0)
        assert not d.admit and d.reason == "pad_frac"

    def test_deadline_tiebreak_admits_despite_cost(self):
        p = AdmissionPricer(max_pad_frac=0.75)
        # extension 3 at a huge step time would normally refuse...
        d = self.price(p, remaining_host_steps=0, host_step_s=100.0,
                       native_delay_s=0.5)
        assert not d.admit and d.reason == "padded_cost"
        # ...but a candidate that would MISS its deadline waiting for
        # the native bucket admits regardless
        d = self.price(p, remaining_host_steps=0, host_step_s=100.0,
                       native_delay_s=0.5, deadline_slack_s=0.1)
        assert d.admit and d.reason == "deadline"

    def test_native_imminent_refuses(self):
        p = AdmissionPricer()
        d = self.price(p, native_delay_s=0.0)
        assert not d.admit and d.reason == "native_imminent"

    def test_free_ride_admits_and_extension_prices(self):
        p = AdmissionPricer()
        # candidate fits inside the remaining host steps: zero excess
        d = self.price(p, candidate_steps=3, remaining_host_steps=3,
                       native_delay_s=0.01, host_step_s=10.0)
        assert d.admit and d.reason == "priced"
        assert d.excess_s == 0.0
        # extension beyond the loop is priced against the delay
        d = self.price(p, candidate_steps=3, remaining_host_steps=0,
                       native_delay_s=0.01, host_step_s=10.0)
        assert not d.admit and d.reason == "padded_cost"
        assert d.excess_s > d.native_delay_s

    def test_unmeasured_step_time_leans_toward_admitting(self):
        # before the first EWMA sample host_step_s is 0: extension is
        # priced free, so a cold loop admits whenever the native
        # bucket is not imminent
        p = AdmissionPricer()
        d = self.price(p, remaining_host_steps=0, host_step_s=0.0,
                       native_delay_s=0.001)
        assert d.admit and d.reason == "priced"


class GatedInitExecutor(FoldExecutor):
    """Real executor whose FIRST armed run_init blocks until released:
    the deterministic window for submitting work that must be admitted
    MID-LOOP rather than riding the founder batch."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.reached = threading.Event()
        self.release = threading.Event()
        self.armed = False

    def run_init(self, *a, **k):
        out = super().run_init(*a, **k)
        if self.armed:
            self.armed = False
            self.reached.set()
            assert self.release.wait(timeout=120)
        return out


def _scheduler(model_and_params, policy=None, num_recycles=3,
               buckets=(8, 16), max_batch=2, ex_cls=FoldExecutor, **kw):
    kw.setdefault("metrics", ServeMetrics(registry=MetricsRegistry()))
    kw.setdefault("registry", MetricsRegistry())
    ex = ex_cls(*model_and_params, max_entries=16)
    sched = Scheduler(
        ex, BucketPolicy(buckets),
        SchedulerConfig(max_batch_size=max_batch, max_wait_ms=5.0,
                        num_recycles=num_recycles, msa_depth=MSA_DEPTH),
        recycle_policy=policy, **kw)
    return ex, sched


XB = dict(converge_tol=0.0, continuous=True, cross_bucket=True,
          preempt=False)


class TestCrossBucketByteEqual:
    def test_admitted_short_byte_equal_alone_at_host_shape(
            self, model_and_params):
        """ISSUE 13 acceptance, single chip: a SHORT request admitted
        into a longer host batch's freed row mid-loop serves final
        coords BYTE-equal to the same request folded alone at the HOST
        shape, retires against its own age (full depth), and reports
        its NATIVE bucket."""
        founder = requests_of((12,), key=5)[0]     # bucket 16 (host)
        short = requests_of((7,), key=6)[0]        # bucket 8 (native)
        ex, sched = _scheduler(model_and_params, RecyclePolicy(**XB),
                               ex_cls=GatedInitExecutor)
        sched.warmup()
        ex.armed = True
        sched.start()
        try:
            tf = sched.submit(FoldRequest(seq=founder.seq,
                                          msa=founder.msa))
            assert ex.reached.wait(timeout=300)
            ts = sched.submit(FoldRequest(seq=short.seq, msa=short.msa))
            time.sleep(0.1)       # let the short reach pending
            ex.release.set()
            rf = tf.result(timeout=300)
            rs = ts.result(timeout=300)
        finally:
            sched.stop()
        assert rf.ok and rs.ok, (rf.error, rs.error)
        assert rs.recycles == 3            # its OWN age, full depth
        assert rs.bucket_len == 8          # native-bucket attribution
        rec = sched.serve_stats()["recycle"]
        assert rec["cross_bucket_admissions"] == 1
        assert rec["row_admissions"] == 1
        # pad-fraction observability: one admit at 1 - 7/16
        snap = sched.metrics.snapshot()
        assert snap["row_admits"] == 1
        assert snap["admit_pad_fraction"]["count"] == 1
        assert snap["admit_pad_fraction"]["p50"] == \
            pytest.approx(1.0 - 7.0 / 16.0)
        assert snap["padding_waste_admitted"] > 0.0
        # byte-equality against the same request folded ALONE AT THE
        # HOST SHAPE: a bucket policy with only the host edge maps the
        # short request onto it
        _, alone = _scheduler(model_and_params,
                              RecyclePolicy(converge_tol=0.0),
                              buckets=(16,))
        with alone:
            rs2 = alone.submit(FoldRequest(seq=short.seq,
                                           msa=short.msa)).result(
                                               timeout=300)
        np.testing.assert_array_equal(rs.coords, rs2.coords)
        np.testing.assert_array_equal(rs.confidence, rs2.confidence)

    @pytest.mark.skipif(len(jax.devices()) < 2,
                        reason="needs >= 2 devices")
    def test_cross_admission_on_mesh_lease_byte_equal(
            self, model_and_params):
        """Cross-bucket admission from a dispatch-pool thread on a
        1x2 mesh lease: the short rides the leased host loop in place
        (no repack of the mesh-sharded carry) and its result is
        byte-equal to folding it alone at the host shape on the same
        mesh."""
        founder = requests_of((12,), key=5)[0]
        short = requests_of((7,), key=6)[0]

        def mk(gated, buckets, shapes):
            ex, sched = _scheduler(
                model_and_params,
                RecyclePolicy(**XB), buckets=buckets,
                ex_cls=GatedInitExecutor if gated else FoldExecutor,
                mesh_policy=MeshPolicy(shapes,
                                       devices=jax.devices()[:2]))
            return ex, sched

        # ONE 2-chip slice shared by both buckets: while the host loop
        # leases it, the short's native bucket has no free slice —
        # exactly the starved-slice regime cross-bucket serves
        ex, sched = mk(True, (8, 16), {8: 2, 16: 2})
        sched.warmup()
        ex.armed = True
        sched.start()
        try:
            tf = sched.submit(FoldRequest(seq=founder.seq,
                                          msa=founder.msa))
            assert ex.reached.wait(timeout=300)
            ts = sched.submit(FoldRequest(seq=short.seq, msa=short.msa))
            time.sleep(0.1)
            ex.release.set()
            rf = tf.result(timeout=300)
            rs = ts.result(timeout=300)
        finally:
            sched.stop()
        assert rf.ok and rs.ok, (rf.error, rs.error)
        stats = sched.serve_stats()
        assert stats["recycle"]["cross_bucket_admissions"] == 1
        assert "1x2" in stats["mesh"]["folds"]       # ran sharded
        _, alone = mk(False, (16,), {16: 2})
        alone.warmup()
        with alone:
            rs2 = alone.submit(FoldRequest(seq=short.seq,
                                           msa=short.msa)).result(
                                               timeout=300)
        np.testing.assert_array_equal(rs.coords, rs2.coords)


class _ContStub:
    """Step/admission-capable executor stub with deterministic per-row
    convergence keyed by the seq's first token (see
    tests/test_continuous.py, whose stub this mirrors + span_attrs on
    run_init_rows for the cross-bucket native_bucket tagging)."""

    def __init__(self, plan):
        self.plan = plan
        self.calls = []
        self.reached = threading.Event()
        self.release = threading.Event()
        self.gate_at = None
        self._lock = threading.Lock()

    def _mk_state(self, ids, counts, b, n):
        coords = np.zeros((b, n, 3), np.float32)
        for i, c in enumerate(counts):
            coords[i] = float(c)
        return SimpleNamespace(coords=coords,
                               confidence=np.zeros((b, n), np.float32),
                               recyclables=None,
                               ids=np.array(ids), counts=np.array(counts))

    def run_init(self, batch, trace=None, devices=None,
                 mesh_shape=None):
        seq = np.asarray(batch["seq"])
        b, n = seq.shape
        ids = seq[:, 0]
        with self._lock:
            self.calls.append(("init", [int(i) for i in ids]))
        return self._mk_state(ids, [0] * b, b, n)

    def run_init_rows(self, batch, state, row_mask, trace=None,
                      devices=None, mesh_shape=None, span_attrs=None):
        seq = np.asarray(batch["seq"])
        b, n = seq.shape
        mask = np.asarray(row_mask)
        ids = state.ids.copy()
        counts = state.counts.copy()
        ids[mask] = seq[:, 0][mask]
        counts[mask] = 0
        with self._lock:
            self.calls.append(
                ("init_rows", [int(i) for i in seq[:, 0][mask]]))
        return self._mk_state(ids, counts, b, n)

    def run_step(self, batch, state, recycle_index, trace=None,
                 devices=None, mesh_shape=None, span_attrs=None):
        b, n = np.asarray(batch["seq"]).shape
        with self._lock:
            self.calls.append(("step", int(recycle_index)))
            gated = self.gate_at is not None \
                and recycle_index == self.gate_at
            if gated:
                self.gate_at = None
        if gated:
            self.reached.set()
            assert self.release.wait(timeout=60)
        counts = [min(int(c) + 1,
                      self.plan.get(int(t), 10 ** 9))
                  for t, c in zip(state.ids, state.counts)]
        time.sleep(0.01)
        return self._mk_state(state.ids, counts, b, n)

    def run(self, batch, num_recycles, **kw):
        st = self.run_init(batch)
        return SimpleNamespace(coords=st.coords,
                               confidence=st.confidence)

    def stats(self):
        return {"calls": len(self.calls)}


def _stub_sched(stub, num_recycles, policy, max_batch=2,
                buckets=(16, 32), **kw):
    kw.setdefault("metrics", ServeMetrics(registry=MetricsRegistry()))
    kw.setdefault("registry", MetricsRegistry())
    return Scheduler(
        stub, BucketPolicy(buckets),
        SchedulerConfig(max_batch_size=max_batch, max_wait_ms=5.0,
                        num_recycles=num_recycles, msa_depth=0),
        recycle_policy=policy, **kw)


def _req(token, length=28, **kw):
    return FoldRequest(seq=np.full(length, token, np.int32), **kw)


class TestCrossBucketScheduling:
    def test_hbm_refusal_falls_back_to_native_wait(self):
        """A cross-bucket candidate the (tightened) HBM guard refuses
        AT THE HOST SHAPE is not admitted — it returns to its NATIVE
        pending queue and folds through normal batch formation at its
        own bucket once the loop drains."""
        mem = FoldMemoryModel(param_bytes=0, dim=64, heads=4)
        mem.hbm_bytes_per_device = 1 << 60       # admits everything
        pol = MeshPolicy({16: 1, 32: 1}, devices=jax.devices()[:1],
                         memory=mem)
        stub = _ContStub({1: 10 ** 9})           # founder never converges
        stub.gate_at = 1
        sched = _stub_sched(
            stub, 3,
            RecyclePolicy(converge_tol=0.5, **{k: v for k, v in
                          XB.items() if k != "converge_tol"}),
            mesh_policy=pol)
        sched.start()
        try:
            t1 = sched.submit(_req(1, length=28))    # host bucket 32
            assert stub.reached.wait(timeout=60)
            t2 = sched.submit(_req(2, length=12))    # native bucket 16
            time.sleep(0.05)
            mem.hbm_bytes_per_device = 1             # guard tightens
            stub.release.set()
            r1 = t1.result(timeout=60)
            r2 = t2.result(timeout=60)
        finally:
            sched.stop()
        assert r1.ok and r2.ok
        rec = sched.serve_stats()["recycle"]
        assert rec["cross_bucket_admissions"] == 0
        assert rec["row_admissions"] == 0
        # the candidate folded in its own native batch afterwards
        assert r2.bucket_len == 16 and r2.recycles == 3
        assert ("init", [2, 2]) in stub.calls or \
            ("init", [2]) in [(c[0], c[1][:1]) for c in stub.calls
                              if c[0] == "init"]

    def test_refused_candidate_reenables_worker_yield(self):
        """A pricer refusal marks the entry cross_refused, so the
        inline admission gate yields the worker on its next gap and
        the refusal's fallback — drain + native formation — actually
        happens instead of the entry starving behind a refilled
        loop."""
        stub = _ContStub({1: 10 ** 9})
        stub.gate_at = 1
        # max_pad_frac too tight for a 12-residue fold at host 32:
        # the pricer refuses on pad_frac every time
        policy = RecyclePolicy(converge_tol=0.5, continuous=True,
                               cross_bucket=True,
                               cross_bucket_max_pad_frac=0.5,
                               preempt=False)
        sched = _stub_sched(stub, 6, policy)
        sched.start()
        try:
            t1 = sched.submit(_req(1, length=28))
            assert stub.reached.wait(timeout=60)
            t2 = sched.submit(_req(2, length=12))    # pad 0.625 > 0.5
            time.sleep(0.05)
            stub.release.set()
            r1 = t1.result(timeout=60)
            r2 = t2.result(timeout=60)
        finally:
            sched.stop()
        assert r1.ok and r2.ok
        rec = sched.serve_stats()["recycle"]
        assert rec["cross_bucket_admissions"] == 0
        assert r2.bucket_len == 16 and r2.recycles == 6

    def test_cross_bucket_false_scrubbed_stats_identity(
            self, model_and_params):
        """The off switch: RecyclePolicy(cross_bucket=False) leaves
        scrubbed serve_stats() byte-identical to a policy that never
        mentioned the field (the same scrub discipline as the
        continuous=False pin in test_continuous.py)."""
        def scrub(obj):
            if isinstance(obj, dict):
                return {k: scrub(v) for k, v in sorted(obj.items())
                        if k != "traces" and not k.endswith("_s")}
            if isinstance(obj, list):
                return [scrub(v) for v in obj]
            return obj

        def run_one(policy):
            _, sched = _scheduler(model_and_params, policy,
                                  num_recycles=1, buckets=(16,))
            reqs = requests_of((12, 8), key=9)
            with sched:
                for r in reqs:
                    assert sched.submit(
                        FoldRequest(seq=r.seq, msa=r.msa)).result(
                            timeout=300).ok
            return scrub(sched.serve_stats())

        explicit_off = run_one(RecyclePolicy(converge_tol=0.0,
                                             continuous=True,
                                             cross_bucket=False))
        never_heard = run_one(RecyclePolicy(converge_tol=0.0,
                                            continuous=True))
        assert json.dumps(explicit_off, sort_keys=True, default=str) \
            == json.dumps(never_heard, sort_keys=True, default=str)
        assert explicit_off["recycle"]["cross_bucket_admissions"] == 0
        assert explicit_off["recycle"]["cross_bucket"] is False

    def test_policy_validation(self):
        with pytest.raises(ValueError):
            RecyclePolicy(cross_bucket=True)        # needs continuous
        with pytest.raises(ValueError):
            RecyclePolicy(eager_form=True)          # needs continuous
        with pytest.raises(ValueError):
            RecyclePolicy(continuous=True, cross_bucket=True,
                          cross_bucket_max_pad_frac=1.5)


class TestEagerForm:
    def test_thin_queue_forms_before_max_wait(self):
        """Admission-aware formation: with eager_form a single pending
        request launches its (under-filled) batch immediately instead
        of waiting out a long max_wait — max_wait is a fallback, not a
        latency floor."""
        stub = _ContStub({1: 1})
        sched = Scheduler(
            stub, BucketPolicy((32,)),
            SchedulerConfig(max_batch_size=4, max_wait_ms=10_000.0,
                            num_recycles=2, msa_depth=0),
            recycle_policy=RecyclePolicy(converge_tol=0.0,
                                         continuous=True,
                                         eager_form=True,
                                         preempt=False),
            metrics=ServeMetrics(registry=MetricsRegistry()),
            registry=MetricsRegistry())
        sched.start()
        try:
            t0 = time.monotonic()
            r = sched.submit(_req(1)).result(timeout=60)
            elapsed = time.monotonic() - t0
        finally:
            sched.stop()
        assert r.ok
        # served far below the 10s max_wait window
        assert elapsed < 5.0, elapsed

    def test_admission_tops_up_eager_batch(self):
        """The thin-queue batch that formed eagerly is topped up by
        mid-loop admission: a request arriving while the loop runs
        rides a free row instead of waiting for the next formation."""
        stub = _ContStub({1: 10 ** 9, 2: 10 ** 9})
        stub.gate_at = 1
        sched = Scheduler(
            stub, BucketPolicy((32,)),
            SchedulerConfig(max_batch_size=2, max_wait_ms=10_000.0,
                            num_recycles=4, msa_depth=0),
            recycle_policy=RecyclePolicy(converge_tol=0.5,
                                         continuous=True,
                                         eager_form=True,
                                         preempt=False),
            metrics=ServeMetrics(registry=MetricsRegistry()),
            registry=MetricsRegistry())
        sched.start()
        try:
            t1 = sched.submit(_req(1))
            assert stub.reached.wait(timeout=60)
            t2 = sched.submit(_req(2))
            time.sleep(0.05)
            stub.release.set()
            r1 = t1.result(timeout=60)
            r2 = t2.result(timeout=60)
        finally:
            sched.stop()
        assert r1.ok and r2.ok
        rec = sched.serve_stats()["recycle"]
        assert rec["row_admissions"] == 1
        assert ("init_rows", [2]) in stub.calls


class TestLoadtestFlags:
    def test_cross_bucket_flags_fast(self, tmp_path, capsys):
        """Tier-1 flag-rot tripwire: the --cross-bucket /
        --cross-bucket-max-pad-frac / --eager-form surface drives a
        real (tiny) run and reports the cross-bucket fields."""
        import sys
        sys.path.insert(0, "tools")
        try:
            import serve_loadtest
        finally:
            sys.path.pop(0)
        rc = serve_loadtest.main([
            "--requests", "6", "--concurrency", "3",
            "--lengths", "7,12", "--buckets", "8,16",
            "--msa-depth", str(MSA_DEPTH), "--max-batch", "2",
            "--max-wait-ms", "5", "--num-recycles", "1",
            "--cross-bucket", "--cross-bucket-max-pad-frac", "0.9",
            "--eager-form",
            "--dim", "32", "--depth", "1",
            "--metrics-path", str(tmp_path / "m.jsonl")])
        assert rc == 0
        report = json.loads(capsys.readouterr().out.strip()
                            .splitlines()[-1])
        assert report["continuous"] is True        # implied
        assert report["cross_bucket"] is True
        assert report["served"] == 6
        assert "cross_bucket_admissions" in report
        assert "cross_bucket_refusals" in report
        assert "padding_waste_admitted" in report
        assert "admit_pad_fraction" in report
        assert report["recycle"]["cross_bucket"] is True
        assert report["recycle"]["eager_form"] is True
        assert report["recycle"]["cross_bucket_max_pad_frac"] == 0.9
