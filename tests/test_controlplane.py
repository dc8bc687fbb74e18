"""Control-plane actuation tests (ISSUE 16): the pure scaling brain
(`fleet/scaling.py` — burn scale-up, idle scale-down, the hysteresis
dead band, cooldown, quorum, min/max bounds, least-loaded drain
target), the registry heartbeat-TTL sweep (a wedged-but-listening
replica stops owning ring keys), the FeaturePool in-place resize, the
new front-door admin surface (/admin/stats identity block,
/admin/resize, /admin/peers, the fleet_replica_identity single-series
pin), the controller's telemetry helpers (parse_identity,
content_digest, merge_key_profiles, KeyFrequencyLog roundtrip), the
FleetController reconcile cycle against real front doors (join /
leave / sweep / quorum restore / rollout convergence / late-joiner
re-roll / telemetry-driven warming / stale-scrape discard), the
controller-off byte-identity pins, and the obs_fleet decision-log /
identity-check rendering.

Stub-executor + localhost HTTP, no model, no processes — the
test_frontdoor.py convention; serve_smoke.sh phase 15 is the
3-process chaos version of the same story.
"""

import http.server
import importlib.util
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from alphafold2_tpu import fleet
from alphafold2_tpu.fleet.controlplane import (FleetController,
                                               content_digest,
                                               merge_key_profiles,
                                               parse_identity)
from alphafold2_tpu.fleet.frontdoor import FrontDoorServer
from alphafold2_tpu.fleet.registry import ReplicaRegistry
from alphafold2_tpu.fleet.router import ConsistentHashRouter
from alphafold2_tpu.fleet.scaling import (HOLD, SCALE_DOWN, SCALE_UP,
                                          ReplicaSignals, ScalingPolicy,
                                          decide_feature_workers,
                                          decide_scale, drain_target)
from alphafold2_tpu.obs.registry import MetricsRegistry
from alphafold2_tpu.obs.trace import Tracer
from alphafold2_tpu.serve import (BucketPolicy, FeaturePool, FoldRequest,
                                  Scheduler, SchedulerConfig)
from alphafold2_tpu.serve.metrics import KeyFrequencyLog

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MSA_DEPTH = 3


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


obs_fleet = _load_tool("obs_fleet")


class _OkExecutor:
    def __init__(self):
        self.calls = 0

    def run(self, batch, num_recycles, trace=None):
        self.calls += 1
        b, n = batch["seq"].shape

        class R:
            coords = np.zeros((b, n, 3), np.float32)
            confidence = np.full((b, n), 0.5, np.float32)

        return R()

    def stats(self):
        return {"calls": self.calls}


def _scheduler(model_tag="cp", **kwargs):
    return Scheduler(_OkExecutor(), BucketPolicy((16,)),
                     SchedulerConfig(max_batch_size=2, max_wait_ms=10.0,
                                     poll_ms=2.0, msa_depth=MSA_DEPTH),
                     model_tag=model_tag,
                     registry=MetricsRegistry(), **kwargs)


def _request(seed=0, n=12, **kwargs):
    rng = np.random.default_rng(seed)
    return FoldRequest(
        seq=rng.integers(0, 20, size=n).astype(np.int32),
        msa=rng.integers(0, 20, size=(MSA_DEPTH, n)).astype(np.int32),
        **kwargs)


def _post(url, payload):
    """(status, decoded body) for an admin POST — keeps the 4xx bodies
    that urllib raises as exceptions."""
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read().decode("utf-8"))
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read().decode("utf-8"))


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.read().decode("utf-8")


def _signals(*specs):
    return [ReplicaSignals(**s) for s in specs]


POLICY = ScalingPolicy(min_replicas=1, max_replicas=4,
                       up_burn_rate=1.0, down_burn_rate=0.5,
                       down_idle_fraction=0.80, cooldown_s=30.0)


# -- scaling policy validation -------------------------------------------

@pytest.mark.quick
class TestScalingPolicyValidation:
    def test_defaults_are_valid(self):
        ScalingPolicy()

    def test_min_below_one_rejected(self):
        with pytest.raises(ValueError):
            ScalingPolicy(min_replicas=0)

    def test_max_below_min_rejected(self):
        with pytest.raises(ValueError):
            ScalingPolicy(min_replicas=3, max_replicas=2)

    def test_inverted_hysteresis_band_rejected(self):
        with pytest.raises(ValueError):
            ScalingPolicy(up_burn_rate=0.5, down_burn_rate=1.0)

    def test_inverted_feature_band_rejected(self):
        with pytest.raises(ValueError):
            ScalingPolicy(feature_workers_min=4, feature_workers_max=2)


# -- decide_scale units ---------------------------------------------------

@pytest.mark.quick
class TestDecideScale:
    def test_burn_scale_up(self):
        sigs = _signals({"replica_id": "a", "burn_rate": 0.2},
                        {"replica_id": "b", "burn_rate": 1.5})
        d = decide_scale(POLICY, sigs, now=100.0)
        assert d.action == SCALE_UP
        assert "up_burn_rate" in d.reason
        assert d.fleet_burn == pytest.approx(1.5)

    def test_burn_scale_up_capped_at_max(self):
        sigs = _signals(*({"replica_id": f"r{i}", "burn_rate": 2.0}
                          for i in range(4)))
        d = decide_scale(POLICY, sigs, now=100.0)
        assert d.action == HOLD and "max_replicas" in d.reason

    def test_infinite_burn_reads_as_way_over(self):
        sigs = _signals({"replica_id": "a",
                         "burn_rate": float("inf")})
        d = decide_scale(POLICY, sigs, now=100.0)
        assert d.action == SCALE_UP
        assert d.fleet_burn == pytest.approx(POLICY.up_burn_rate + 1.0)

    def test_featurize_queue_pressure_scale_up(self):
        sigs = _signals({"replica_id": "a", "burn_rate": 0.1,
                         "featurize_queue_depth": 10,
                         "featurize_workers": 2})
        d = decide_scale(POLICY, sigs, now=100.0)
        assert d.action == SCALE_UP and "featurize queue" in d.reason

    def test_idle_scale_down_needs_both_conditions(self):
        # idle enough but burn inside the dead band: HOLD
        sigs = _signals({"replica_id": "a", "burn_rate": 0.6,
                         "idle_fraction": 0.95},
                        {"replica_id": "b", "burn_rate": 0.1,
                         "idle_fraction": 0.95})
        d = decide_scale(POLICY, sigs, now=100.0)
        assert d.action == HOLD and "in band" in d.reason
        # burn low enough but not idle: HOLD
        sigs = _signals({"replica_id": "a", "burn_rate": 0.1,
                         "idle_fraction": 0.5},
                        {"replica_id": "b", "burn_rate": 0.1,
                         "idle_fraction": 0.5})
        assert decide_scale(POLICY, sigs, now=100.0).action == HOLD
        # both: SCALE_DOWN with a drain target
        sigs = _signals({"replica_id": "a", "burn_rate": 0.1,
                         "idle_fraction": 0.95, "queue_depth": 3},
                        {"replica_id": "b", "burn_rate": 0.1,
                         "idle_fraction": 0.95, "queue_depth": 1})
        d = decide_scale(POLICY, sigs, now=100.0)
        assert d.action == SCALE_DOWN
        assert d.drain_target == "b"         # least loaded

    def test_idle_scale_down_refused_at_min(self):
        sigs = _signals({"replica_id": "a", "burn_rate": 0.0,
                         "idle_fraction": 1.0})
        d = decide_scale(POLICY, sigs, now=100.0)
        assert d.action == HOLD and "min_replicas" in d.reason

    def test_hysteresis_band_holds_under_oscillation(self):
        """Burn oscillating anywhere inside (down_burn, up_burn] with
        an idle fleet never actuates in either direction: the dead
        band between the two thresholds absorbs the flapping."""
        for burn in (0.51, 0.6, 0.75, 0.9, 1.0, 0.55, 0.99):
            sigs = _signals({"replica_id": "a", "burn_rate": burn,
                             "idle_fraction": 0.95},
                            {"replica_id": "b", "burn_rate": burn,
                             "idle_fraction": 0.95})
            d = decide_scale(POLICY, sigs, now=100.0)
            assert d.action == HOLD, (burn, d.reason)

    def test_cooldown_suppresses_flapping(self):
        sigs = _signals({"replica_id": "a", "burn_rate": 2.0},
                        {"replica_id": "b", "burn_rate": 2.0})
        d = decide_scale(POLICY, sigs, now=110.0, last_action_s=100.0)
        assert d.action == HOLD and d.reason.startswith("cooldown (")
        # once the cooldown has elapsed, the same signals act
        d = decide_scale(POLICY, sigs, now=131.0, last_action_s=100.0)
        assert d.action == SCALE_UP

    def test_quorum_restore_beats_cooldown(self):
        policy = ScalingPolicy(min_replicas=2, max_replicas=4)
        sigs = _signals({"replica_id": "a"})
        d = decide_scale(policy, sigs, now=100.5, last_action_s=100.0)
        assert d.action == SCALE_UP and "quorum restore" in d.reason

    def test_pending_spawn_counts_toward_quorum(self):
        """The runaway-restore regression: a spawn whose boot spans
        many reconcile intervals satisfies the quorum deficit while it
        warms up — the controller must not spawn again every cycle."""
        policy = ScalingPolicy(min_replicas=3, max_replicas=5)
        sigs = _signals({"replica_id": "a"}, {"replica_id": "b"})
        assert decide_scale(policy, sigs, now=100.0).action == SCALE_UP
        d = decide_scale(policy, sigs, now=100.5, pending=1)
        assert d.action == HOLD and d.pending == 1
        assert "pending" in d.reason
        # two short: one spawn in flight still leaves a deficit
        d = decide_scale(policy, _signals({"replica_id": "a"}),
                         now=100.5, pending=1)
        assert d.action == SCALE_UP

    def test_pending_spawn_holds_tuning_actions(self):
        sigs = _signals({"replica_id": "a", "burn_rate": 5.0})
        d = decide_scale(POLICY, sigs, now=100.0, pending=1)
        assert d.action == HOLD and "pending" in d.reason
        idle = _signals({"replica_id": "a", "idle_fraction": 1.0},
                        {"replica_id": "b", "idle_fraction": 1.0})
        d = decide_scale(POLICY, idle, now=100.0, pending=1)
        assert d.action == HOLD and "pending" in d.reason

    def test_draining_and_unhealthy_do_not_count_toward_quorum(self):
        policy = ScalingPolicy(min_replicas=2, max_replicas=4)
        sigs = _signals({"replica_id": "a"},
                        {"replica_id": "b", "draining": True},
                        {"replica_id": "c", "healthy": False})
        d = decide_scale(policy, sigs, now=100.0)
        assert d.action == SCALE_UP and d.healthy == 1

    def test_drain_target_least_loaded_ordering(self):
        sigs = _signals(
            {"replica_id": "a", "queue_depth": 2},
            {"replica_id": "b", "queue_depth": 1,
             "featurize_queue_depth": 5},
            {"replica_id": "c", "queue_depth": 1,
             "featurize_queue_depth": 2, "served": 9},
            {"replica_id": "d", "queue_depth": 1,
             "featurize_queue_depth": 2, "served": 3})
        assert drain_target(sigs) == "d"     # queue, then featurize,
        #                                      then served tiebreak
        sigs = _signals({"replica_id": "a", "draining": True},
                        {"replica_id": "b", "healthy": False})
        assert drain_target(sigs) is None
        assert drain_target([]) is None


@pytest.mark.quick
class TestDecideFeatureWorkers:
    POLICY = ScalingPolicy(feature_workers_min=1, feature_workers_max=8,
                           feature_queue_per_worker=2.0)

    def test_grow_is_immediate(self):
        s = ReplicaSignals("a", featurize_queue_depth=10,
                           featurize_workers=2)
        assert decide_feature_workers(self.POLICY, s) == 5

    def test_shrink_has_one_worker_hysteresis(self):
        # want = cur - 1: inside the margin, leave it alone
        s = ReplicaSignals("a", featurize_queue_depth=4,
                           featurize_workers=3)
        assert decide_feature_workers(self.POLICY, s) is None
        # want well below: shrink
        s = ReplicaSignals("a", featurize_queue_depth=2,
                           featurize_workers=5)
        assert decide_feature_workers(self.POLICY, s) == 1

    def test_clamped_to_policy_max(self):
        s = ReplicaSignals("a", featurize_queue_depth=100,
                           featurize_workers=2)
        assert decide_feature_workers(self.POLICY, s) == 8

    def test_empty_queue_wants_the_floor(self):
        s = ReplicaSignals("a", featurize_queue_depth=0,
                           featurize_workers=1)
        assert decide_feature_workers(self.POLICY, s) is None


# -- registry heartbeat TTL -----------------------------------------------

@pytest.mark.quick
class TestRegistryTTL:
    def _reg(self, ttl=5.0):
        clk = [100.0]
        reg = ReplicaRegistry(heartbeat_timeout_s=ttl,
                              clock=lambda: clk[0],
                              registry=MetricsRegistry())
        return reg, clk

    def test_sweep_auto_downs_stale_members(self):
        reg, clk = self._reg()
        reg.register("a")
        reg.register("b")
        clk[0] += 6.0
        reg.heartbeat("b")
        epoch_before = reg.epoch
        assert reg.sweep() == ["a"]
        assert reg.epoch == epoch_before + 1   # ONE bump per sweep
        assert not reg.is_healthy("a") and reg.is_healthy("b")
        members = reg.snapshot()["replicas"]
        assert members["a"]["auto_down"] is True
        assert members["b"]["auto_down"] is False

    def test_sweep_bumps_epoch_once_for_many(self):
        reg, clk = self._reg()
        for rid in ("a", "b", "c"):
            reg.register(rid)
        clk[0] += 6.0
        epoch_before = reg.epoch
        assert reg.sweep() == ["a", "b", "c"]
        assert reg.epoch == epoch_before + 1

    def test_heartbeat_revives_auto_downed_not_admin_downed(self):
        reg, clk = self._reg()
        reg.register("a")
        reg.register("b")
        reg.mark("b", up=False)               # administrative pull
        clk[0] += 6.0
        reg.sweep()
        assert not reg.is_healthy("a")
        epoch = reg.epoch
        reg.heartbeat("a")                    # fresh beat: revive
        assert reg.is_healthy("a")
        assert reg.epoch == epoch + 1         # revival rebuilds rings
        reg.heartbeat("b")                    # admin down stays down
        assert not reg.is_healthy("b")

    def test_mark_up_clears_auto_down(self):
        reg, clk = self._reg()
        reg.register("a")
        clk[0] += 6.0
        reg.sweep()
        reg.mark("a", up=True)
        assert reg.is_healthy("a")
        assert reg.snapshot()["replicas"]["a"]["auto_down"] is False

    def test_sweep_noop_without_ttl(self):
        reg = ReplicaRegistry(registry=MetricsRegistry())
        reg.register("a")
        epoch = reg.epoch
        assert reg.sweep() == []
        assert reg.epoch == epoch and reg.is_healthy("a")

    def test_auto_down_counter_minted_only_with_ttl(self):
        mreg = MetricsRegistry()
        ReplicaRegistry(registry=mreg)
        assert "fleet_auto_downs_total" not in mreg.snapshot()
        mreg2 = MetricsRegistry()
        ReplicaRegistry(heartbeat_timeout_s=1.0, registry=mreg2)
        assert "fleet_auto_downs_total" in mreg2.snapshot()

    def test_wedged_but_listening_replica_stops_owning_keys(self):
        """The ISSUE-16 regression: a replica whose TCP accept still
        works but whose heartbeat went stale is swept DOWN with an
        epoch bump, so the hash ring routes its keys elsewhere — it
        stops receiving forwards, not just failing them."""
        reg, clk = self._reg()
        reg.register("a", transport=object())
        reg.register("b", transport=object())
        router = ConsistentHashRouter(reg, self_id="a",
                                      metrics=MetricsRegistry())
        b_keys = [f"k{i}" for i in range(64)
                  if router.owner_for(f"k{i}") == "b"]
        assert b_keys                         # b owns some keyspace
        decision = router.route(b_keys[0])
        assert not decision.is_local and decision.reason == "forward"
        # b wedges: keeps listening (stays registered) but stops
        # heartbeating; a stays fresh
        clk[0] += 6.0
        reg.heartbeat("a")
        assert reg.sweep() == ["b"]
        for key in b_keys:
            assert router.owner_for(key) == "a"
            assert router.route(key).is_local
        # b recovers: one heartbeat re-admits it to the ring
        reg.heartbeat("b")
        assert router.owner_for(b_keys[0]) == "b"


# -- feature-pool resize --------------------------------------------------

@pytest.mark.quick
class TestFeaturePoolResize:
    def test_resize_in_place(self):
        pool = FeaturePool(workers=2, registry=MetricsRegistry())
        try:
            assert pool.resize(5) == 5 and pool.workers == 5
            assert pool.resize(1) == 1 and pool.workers == 1
            assert pool.resizes == 2
        finally:
            pool.stop()

    def test_same_width_is_a_noop(self):
        pool = FeaturePool(workers=3, registry=MetricsRegistry())
        try:
            assert pool.resize(3) == 3
            assert pool.resizes == 0
        finally:
            pool.stop()

    def test_bounds_and_lifecycle_errors(self):
        pool = FeaturePool(workers=2, registry=MetricsRegistry())
        with pytest.raises(ValueError):
            pool.resize(0)
        pool.stop()
        with pytest.raises(RuntimeError):
            pool.resize(3)

    def test_snapshot_resizes_key_only_after_a_resize(self):
        pool = FeaturePool(workers=2, registry=MetricsRegistry())
        try:
            assert "resizes" not in pool.snapshot()   # PR-15 stats pin
            pool.resize(3)
            assert pool.snapshot()["resizes"] == 1
        finally:
            pool.stop()


# -- front-door admin surface ---------------------------------------------

class _Door:
    def __init__(self, rollout=None, model_tag="cp", replica_id="fd0"):
        self.metrics = MetricsRegistry()
        self.scheduler = _scheduler(model_tag=model_tag)
        self.server = FrontDoorServer(self.scheduler, rollout=rollout,
                                      replica_id=replica_id,
                                      metrics=self.metrics)

    def __enter__(self):
        self.scheduler.start()
        self.server.start()
        return self

    def __exit__(self, *exc):
        self.server.stop()
        self.scheduler.stop()


class TestFrontDoorAdmin:
    def test_stats_identity_matches_metrics_series(self):
        with _Door() as d:
            stats = json.loads(_get(d.server.url + "/admin/stats"))
            ident = stats["identity"]
            assert ident["replica_id"] == "fd0"
            assert ident["incarnation"]
            claimed = parse_identity(_get(d.server.url + "/metrics"))
            assert claimed is not None
            assert claimed["replica_id"] == "fd0"
            assert claimed["incarnation"] == ident["incarnation"]

    def test_rollout_moves_identity_one_series_stays_live(self):
        rollout = fleet.RolloutState("v1", registry=MetricsRegistry())
        with _Door(rollout=rollout) as d:
            before = parse_identity(_get(d.server.url + "/metrics"))
            assert before["model_tag"] == "v1"
            status, body = _post(d.server.url + "/admin/rollout",
                                 {"tag": "v2"})
            assert status == 200 and body["tag"] == "v2"
            text = _get(d.server.url + "/metrics")
            after = parse_identity(text)
            # parse_identity returning non-None IS the exactly-one-
            # series-at-1 pin; the superseded tag's series reads 0
            assert after is not None and after["model_tag"] == "v2"
            assert 'model_tag="v1"' in text

    def test_resize_without_pool_is_400(self):
        with _Door() as d:
            status, body = _post(d.server.url + "/admin/resize",
                                 {"workers": 3})
            assert status == 400 and "no feature pool" in body["error"]

    def test_resize_roundtrip_and_errors(self):
        with _Door() as d:
            pool = FeaturePool(workers=2, registry=MetricsRegistry())
            d.scheduler.feature_pool = pool
            try:
                status, body = _post(d.server.url + "/admin/resize",
                                     {"workers": 5})
                assert status == 200
                assert body == {"replica": "fd0", "workers": 5}
                assert pool.workers == 5
                status, body = _post(d.server.url + "/admin/resize",
                                     {"workers": 0})
                assert status == 400      # ValueError surfaces as 400
                status, body = _post(d.server.url + "/admin/resize",
                                     {"wrong": 1})
                assert status == 400 and "bad payload" in body["error"]
            finally:
                d.scheduler.feature_pool = None
                pool.stop()

    def test_peers_requires_wired_admin(self):
        with _Door() as d:
            status, body = _post(
                d.server.url + "/admin/peers",
                {"op": "up", "peer": {"replica_id": "x"}})
            assert status == 400 and "no peer admin" in body["error"]

    def test_peers_dispatch_and_errors(self):
        calls = []
        with _Door() as d:
            def admin(op, peer):
                calls.append((op, peer))
                if op == "down":
                    raise RuntimeError("boom")
                return {"members": 2}

            d.server.peer_admin = admin
            status, body = _post(
                d.server.url + "/admin/peers",
                {"op": "register",
                 "peer": {"replica_id": "r1", "host": "h"}})
            assert status == 200
            assert body == {"members": 2, "op": "register"}
            assert calls[-1] == ("register",
                                 {"replica_id": "r1", "host": "h"})
            status, body = _post(
                d.server.url + "/admin/peers",
                {"op": "reboot", "peer": {}})
            assert status == 400 and "unknown op" in body["error"]
            status, body = _post(
                d.server.url + "/admin/peers",
                {"op": "down", "peer": {"replica_id": "r1"}})
            assert status == 500 and "boom" in body["error"]


# -- telemetry helpers ----------------------------------------------------

@pytest.mark.quick
class TestTelemetryHelpers:
    def test_parse_identity_single_series(self):
        text = ('# HELP fleet_replica_identity x\n'
                'fleet_replica_identity{replica_id="r0",model_tag="v1",'
                'incarnation="abc"} 1\n'
                'fleet_replica_identity{replica_id="r0",model_tag="v0",'
                'incarnation="old"} 0\n')
        ident = parse_identity(text)
        assert ident == {"replica_id": "r0", "model_tag": "v1",
                         "incarnation": "abc"}

    def test_parse_identity_ambiguous_or_absent_is_none(self):
        two = ('fleet_replica_identity{replica_id="r0",'
               'incarnation="a"} 1\n'
               'fleet_replica_identity{replica_id="r0",'
               'incarnation="b"} 1\n')
        assert parse_identity(two) is None
        assert parse_identity("up 1\n") is None
        assert parse_identity(
            'fleet_replica_identity{replica_id="r0"} 0\n') is None

    def test_content_digest_msa_separator(self):
        assert content_digest([1, 2, 3]) == content_digest([1, 2, 3])
        assert content_digest([1, 2, 3]) != content_digest([1, 2, 4])
        assert content_digest([1, 2], [[3]]) != content_digest([1, 2])
        # matches KeyFrequencyLog's aggregation key: same payload, same
        # digest whether it arrives as list or ndarray
        assert content_digest(np.asarray([5, 6], np.int32)) \
            == content_digest([5, 6])
        assert content_digest("not tokens") is None

    def test_merge_key_profiles_sums_across_replicas(self, tmp_path):
        a = tmp_path / "a.keys.jsonl"
        b = tmp_path / "b.keys.jsonl"
        a.write_text(json.dumps({"seq": [1, 2, 3], "count": 4}) + "\n"
                     + json.dumps({"seq": [9, 9], "count": 1}) + "\n")
        b.write_text(json.dumps({"seq": [1, 2, 3], "count": 3}) + "\n"
                     + '{"torn": \n')
        profile = merge_key_profiles([str(a), str(b),
                                      str(tmp_path / "missing.jsonl")])
        assert [(r["seq"], r["count"]) for r in profile] \
            == [([1, 2, 3], 7), ([9, 9], 1)]

    def test_key_frequency_log_roundtrip(self, tmp_path):
        path = str(tmp_path / "keys.jsonl")
        log = KeyFrequencyLog(path, flush_every=3)
        seq = np.asarray([4, 5, 6], np.int32)
        msa = np.asarray([[1, 1, 1]], np.int32)
        log.observe(seq, msa)
        log.observe(seq, msa)
        log.observe(np.asarray([7, 8], np.int32))   # 3rd: auto-flush
        assert os.path.exists(path)
        snap = log.snapshot()
        assert snap["observed"] == 3 and snap["unique"] == 2
        profile = merge_key_profiles([path])
        assert profile[0]["count"] == 2           # hottest first
        assert profile[0]["seq"] == [4, 5, 6]
        assert profile[0]["msa"] == [[1, 1, 1]]
        # the digest the controller dedups by matches the log's key
        assert content_digest(profile[0]["seq"], profile[0]["msa"]) \
            == content_digest(seq, msa)


# -- the reconcile cycle --------------------------------------------------

class _MiniFleet:
    """In-process actuator: real FrontDoorServers over localhost HTTP,
    stub executors, fleet verbs as plain method calls."""

    def __init__(self, tmp_path=None, tag="v1"):
        self.tag = tag
        self.tmp_path = tmp_path
        self.doors = {}                # rid -> _Door
        self.extra_endpoints = {}      # rid -> url (fakes/dead ports)
        self.scale_down_calls = []
        self._next = 0

    def spawn(self):
        rid = f"r{self._next}"
        self._next += 1
        rollout = fleet.RolloutState(self.tag,
                                     registry=MetricsRegistry())
        door = _Door(rollout=rollout, replica_id=rid)
        door.__enter__()
        self.doors[rid] = door
        return rid

    def endpoints(self):
        out = {rid: d.server.url for rid, d in self.doors.items()}
        out.update(self.extra_endpoints)
        return out

    def scale_up(self):
        return self.spawn()

    def scale_down(self, rid):
        self.scale_down_calls.append(rid)
        return self.remove(rid)

    def remove(self, rid):
        door = self.doors.pop(rid, None)
        if door is None:
            return self.extra_endpoints.pop(rid, None) is not None
        door.__exit__()
        return True

    def key_log_paths(self):
        if self.tmp_path is None:
            return {}
        return {rid: os.path.join(str(self.tmp_path),
                                  f"{rid}.keys.jsonl")
                for rid in self.doors}

    def stop(self):
        for rid in list(self.doors):
            self.remove(rid)


def _controller(mini, clk, **kwargs):
    kwargs.setdefault("policy", ScalingPolicy(min_replicas=1,
                                              max_replicas=4,
                                              cooldown_s=5.0))
    kwargs.setdefault("registry", MetricsRegistry())
    kwargs.setdefault("heartbeat_timeout_s", 5.0)
    kwargs.setdefault("probe_timeout_s", 5.0)
    return FleetController(mini, clock=lambda: clk[0], **kwargs)


class _StaleHandler(http.server.BaseHTTPRequestHandler):
    """A replica whose stats and metrics disagree on incarnation — the
    scrape a restart tears in half."""

    def _json(self, obj):
        body = json.dumps(obj).encode("utf-8")
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        if self.path == "/healthz":
            return self._json({"replica": "stale0", "tag": "",
                               "running": True, "draining": False})
        if self.path == "/admin/stats":
            return self._json({
                "queue_depth": 0, "served": 0,
                "slo": {"classes": {"all": {"latency":
                                            {"burn_rate": 99.0}}}},
                "identity": {"replica_id": "stale0", "model_tag": "",
                             "incarnation": "old"}})
        if self.path == "/metrics":
            body = ('fleet_replica_identity{replica_id="stale0",'
                    'model_tag="",incarnation="new"} 1\n'
                    ).encode("utf-8")
            self.send_response(200)
            self.send_header("Content-Type", "text/plain")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            return self.wfile.write(body)
        self.send_response(404)
        self.end_headers()

    def log_message(self, *args):
        pass


class TestFleetController:
    def test_join_leave_and_sweep(self, tmp_path):
        mini = _MiniFleet()
        clk = [100.0]
        try:
            r0, r1 = mini.spawn(), mini.spawn()
            ctrl = _controller(mini, clk)
            rec = ctrl.reconcile()
            assert rec["joined"] == [r0, r1]
            assert rec["healthy"] == 2 and rec["left"] == []
            assert rec["decision"]["action"] == HOLD
            rec = ctrl.reconcile()
            assert rec["joined"] == []        # already members
            # r1 wedges: endpoint still listed, but its server is gone
            # (connection refused = failed probe = no heartbeat)
            url = mini.doors[r1].server.url
            mini.doors[r1].__exit__()
            del mini.doors[r1]
            mini.extra_endpoints[r1] = url
            clk[0] += 6.0
            rec = ctrl.reconcile()
            assert rec["swept"] == [r1]
            assert not ctrl.registry.is_healthy(r1)
            assert r1 in ctrl.registry.member_ids()   # down, not gone
            # the endpoint vanishes entirely: unregister
            del mini.extra_endpoints[r1]
            rec = ctrl.reconcile()
            assert rec["left"] == [r1]
            assert r1 not in ctrl.registry.member_ids()
        finally:
            mini.stop()

    def test_quorum_restore_spawns_through_the_actuator(self):
        mini = _MiniFleet()
        clk = [100.0]
        try:
            mini.spawn()
            ctrl = _controller(
                mini, clk,
                policy=ScalingPolicy(min_replicas=2, max_replicas=4,
                                     cooldown_s=5.0))
            rec = ctrl.reconcile()
            assert rec["decision"]["action"] == SCALE_UP
            assert "quorum restore" in rec["decision"]["reason"]
            assert rec["actions"] and \
                rec["actions"][0]["verb"] == "scale_up"
            assert len(mini.doors) == 2
            clk[0] += 1.0
            rec = ctrl.reconcile()           # restored: no more spawns
            assert rec["healthy"] == 2
            assert rec["decision"]["action"] == HOLD
            assert len(mini.doors) == 2
            snap = ctrl.snapshot()
            assert snap["scale_ups"] == 1 and snap["scale_downs"] == 0
        finally:
            mini.stop()

    def test_slow_boot_spawn_is_not_respawned_every_cycle(self):
        """Runaway-restore regression: a replica whose boot spans many
        reconcile intervals (endpoint listed, healthz refusing) counts
        as pending toward quorum; restore only re-fires after the boot
        grace expires."""

        class _SlowBootFleet(_MiniFleet):
            def scale_up(self):
                rid = f"boot{len(self.extra_endpoints)}"
                with socket.socket() as s:
                    s.bind(("127.0.0.1", 0))
                    port = s.getsockname()[1]
                self.extra_endpoints[rid] = f"http://127.0.0.1:{port}"
                return rid

        mini = _SlowBootFleet()
        clk = [100.0]
        try:
            mini.spawn()
            ctrl = _controller(
                mini, clk,
                policy=ScalingPolicy(min_replicas=2, max_replicas=4,
                                     cooldown_s=5.0),
                probe_timeout_s=0.5, boot_grace_s=60.0)
            rec = ctrl.reconcile()
            assert rec["decision"]["action"] == SCALE_UP
            assert len(mini.extra_endpoints) == 1
            # more cycles while the spawn "boots" — inside cooldown the
            # hold is the cooldown's, past it the pending spawn alone
            # must keep restore quiet: either way, no more spawns
            for step, want in ((0.5, "cooldown"), (6.0, "pending"),
                               (6.0, "pending")):
                clk[0] += step
                rec = ctrl.reconcile()
                assert rec["decision"]["action"] == HOLD
                assert rec["pending"] == list(mini.extra_endpoints)
                assert want in rec["decision"]["reason"]
            assert len(mini.extra_endpoints) == 1
            assert ctrl.snapshot()["scale_ups"] == 1
            # the boot grace expires without a join: restore re-fires
            clk[0] += 61.0
            rec = ctrl.reconcile()
            assert rec["decision"]["action"] == SCALE_UP
            assert "quorum restore" in rec["decision"]["reason"]
            assert len(mini.extra_endpoints) == 2
        finally:
            mini.stop()

    def test_stale_scrape_contributes_neutral_signals(self):
        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                              _StaleHandler)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        mini = _MiniFleet()
        clk = [100.0]
        try:
            mini.extra_endpoints["stale0"] = \
                f"http://127.0.0.1:{srv.server_address[1]}"
            ctrl = _controller(mini, clk)
            rec = ctrl.reconcile()
            assert rec["stale_scrapes"] == 1
            sig = rec["signals"][0]
            # burn 99 was in the stats body — discarded, not acted on
            assert sig["burn"] == 0.0 and sig["idle"] == 0.0
            assert rec["decision"]["action"] == HOLD
            assert len(mini.doors) == 0      # nothing spawned
        finally:
            mini.stop()
            srv.shutdown()
            srv.server_close()

    def test_resize_actuation_end_to_end(self):
        mini = _MiniFleet()
        clk = [100.0]
        pool = FeaturePool(workers=2, registry=MetricsRegistry())
        try:
            rid = mini.spawn()
            mini.doors[rid].scheduler.feature_pool = pool
            ctrl = _controller(
                mini, clk,
                policy=ScalingPolicy(feature_queue_per_worker=2.0))
            sig = ReplicaSignals(rid, healthy=True, incarnation="x",
                                 featurize_queue_depth=10,
                                 featurize_workers=2)
            out = ctrl._actuate_resize(mini.endpoints(), [sig])
            assert out == {rid: 5} and pool.workers == 5
            # stale (no incarnation) and draining replicas are skipped
            assert ctrl._actuate_resize(
                mini.endpoints(),
                [ReplicaSignals(rid, featurize_queue_depth=50,
                                featurize_workers=1)]) == {}
            assert ctrl._actuate_resize(
                mini.endpoints(),
                [ReplicaSignals(rid, incarnation="x", draining=True,
                                featurize_queue_depth=50,
                                featurize_workers=1)]) == {}
        finally:
            mini.doors[rid].scheduler.feature_pool = None
            pool.stop()
            mini.stop()

    def test_rollout_converges_and_rolls_late_joiners(self):
        mini = _MiniFleet(tag="v1")
        clk = [100.0]
        try:
            mini.spawn(), mini.spawn()
            ctrl = _controller(mini, clk, rollout_attempts=2,
                               rollout_backoff_s=0.01)
            ctrl.reconcile()
            report = ctrl.rollout("v2")
            assert report["converged"] and report["stragglers"] == []
            assert sorted(report["epochs"]) == sorted(mini.doors)
            for d in mini.doors.values():
                hz = json.loads(_get(d.server.url + "/healthz"))
                assert hz["tag"] == "v2"
            # a late joiner boots on v1; the next cycle re-rolls it
            late = mini.spawn()
            rec = ctrl.reconcile()
            assert rec["rollout_target"] == "v2"
            assert rec["rollout_stragglers"] == [late]
            clk[0] += 1.0
            rec = ctrl.reconcile()
            assert rec["rollout_stragglers"] == []
            hz = json.loads(_get(mini.doors[late].server.url
                                 + "/healthz"))
            assert hz["tag"] == "v2"
        finally:
            mini.stop()

    def test_rollout_reports_unreachable_stragglers(self):
        mini = _MiniFleet()
        clk = [100.0]
        try:
            mini.spawn()
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                dead_port = s.getsockname()[1]
            mini.extra_endpoints["dead0"] = \
                f"http://127.0.0.1:{dead_port}"
            ctrl = _controller(mini, clk, rollout_attempts=2,
                               rollout_backoff_s=0.01,
                               probe_timeout_s=0.5)
            report = ctrl.rollout("v2")
            assert not report["converged"]
            assert report["stragglers"] == ["dead0"]
            assert report["epochs"]["dead0"] is None
        finally:
            mini.stop()

    def test_warm_from_telemetry_dedups(self, tmp_path):
        mini = _MiniFleet(tmp_path=tmp_path)
        clk = [100.0]
        try:
            rid = mini.spawn()
            # the replica's served-key telemetry: one hot key over the
            # min count, one cold key under it
            log = KeyFrequencyLog(mini.key_log_paths()[rid],
                                  flush_every=1)
            hot = np.asarray(list(range(12)), np.int32)
            log.observe(hot)
            log.observe(hot)
            log.observe(np.asarray([1] * 12, np.int32))
            ctrl = _controller(mini, clk, warm=True, warm_top_k=4,
                               warm_min_count=2)
            rec = ctrl.reconcile()
            assert rec["warm_submissions"] == 1
            assert len(ctrl._warmed) == 1
            # held here: a reconcile drops the tickets that are done, and
            # the warm fold may land before the next one
            ticket, = ctrl._warm_tickets
            clk[0] += 1.0
            rec = ctrl.reconcile()           # same head: dedup holds
            assert rec["warm_submissions"] == 0
            # the warm fold actually lands
            resp = ticket.result(timeout=30)
            assert resp.ok
            assert resp.request_id.startswith("warm-")
            assert ctrl.snapshot()["warmed"] == 1
        finally:
            mini.stop()

    def test_decisions_jsonl_and_reconcile_trace(self, tmp_path):
        mini = _MiniFleet()
        clk = [100.0]
        decisions_path = str(tmp_path / "controller.decisions.jsonl")
        trace_path = str(tmp_path / "controller-traces.jsonl")
        tracer = Tracer(jsonl_path=trace_path, origin="controller")
        try:
            mini.spawn()
            ctrl = _controller(mini, clk,
                               decisions_path=decisions_path,
                               tracer=tracer)
            ctrl.reconcile()
            ctrl.reconcile()
            with open(decisions_path) as fh:
                records = [json.loads(line) for line in fh]
            assert [r["event"] for r in records] == ["reconcile"] * 2
            assert [r["reconcile"] for r in records] == [1, 2]
            assert records[0]["signals"] and records[0]["decision"]
            tracer.close()
            with open(trace_path) as fh:
                traces = [json.loads(line) for line in fh]
            assert len(traces) == 2
            assert traces[0]["origin"] == "controller"
            assert [s["name"] for s in traces[0]["spans"]] \
                == ["reconcile"]
        finally:
            mini.stop()

    def test_loop_survives_reconcile_errors(self):
        class _Broken:
            def endpoints(self):
                raise RuntimeError("actuator detonated")

        ctrl = FleetController(_Broken(), interval_s=0.01,
                               registry=MetricsRegistry())
        ctrl.start()
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                with ctrl._lock:
                    errors = [d for d in ctrl.decisions
                              if d.get("event") == "reconcile_error"]
                if len(errors) >= 2:     # it kept cycling past a crash
                    break
                time.sleep(0.01)
            assert len(errors) >= 2
            assert "actuator detonated" in errors[0]["error"]
        finally:
            ctrl.stop()


# -- controller-off byte-identity ----------------------------------------

@pytest.mark.quick
class TestOffIdentity:
    def test_scheduler_without_key_log_stats_unchanged(self):
        sched = _scheduler()
        with sched:
            assert sched.submit(_request(seed=1)).result(timeout=60).ok
            stats = sched.serve_stats()
        assert "key_log" not in stats
        # ... and arming it mints exactly the one new key
        sched2 = _scheduler(key_log=KeyFrequencyLog(
            os.path.join("/tmp", f"cp-keys-{os.getpid()}.jsonl"),
            flush_every=10**6))
        with sched2:
            assert sched2.submit(_request(seed=1)).result(
                timeout=60).ok
            stats2 = sched2.serve_stats()
        assert stats2["key_log"]["observed"] == 1
        assert set(stats2) - set(stats) == {"key_log"}

    def test_no_controller_metric_names_without_a_controller(self):
        reg = MetricsRegistry()
        sched = Scheduler(_OkExecutor(), BucketPolicy((16,)),
                          SchedulerConfig(max_batch_size=2,
                                          max_wait_ms=10.0, poll_ms=2.0,
                                          msa_depth=MSA_DEPTH),
                          model_tag="cp", registry=reg)
        server = FrontDoorServer(sched, replica_id="fd0", metrics=reg)
        sched.start()
        server.start()
        try:
            names = set(reg.snapshot())
        finally:
            server.stop()
            sched.stop()
        assert not {n for n in names if n.startswith("controller_")}
        assert "fleet_auto_downs_total" not in names
        # a controller on the same registry mints them
        reg2 = MetricsRegistry()
        FleetController(_MiniFleet(), registry=reg2)
        names2 = set(reg2.snapshot())
        assert "controller_reconciles_total" in names2
        assert "fleet_auto_downs_total" in names2   # TTL registry

    def test_registry_without_ttl_snapshot_unchanged(self):
        reg = ReplicaRegistry(registry=MetricsRegistry())
        reg.register("a")
        assert "auto_down" not in reg.snapshot()["replicas"]["a"]
        ttl = ReplicaRegistry(heartbeat_timeout_s=5.0,
                              registry=MetricsRegistry())
        ttl.register("a")
        assert "auto_down" in ttl.snapshot()["replicas"]["a"]


# -- obs_fleet rendering --------------------------------------------------

@pytest.mark.quick
class TestObsFleetControlPlane:
    def test_classify_jsonl(self):
        assert obs_fleet._classify_jsonl("keys.jsonl") == "keys"
        assert obs_fleet._classify_jsonl("r0.keys.jsonl") == "keys"
        assert obs_fleet._classify_jsonl(
            "controller.decisions.jsonl") == "decisions"
        assert obs_fleet._classify_jsonl("traces.jsonl") == "trace"

    def test_gather_paths_routes_by_kind(self, tmp_path):
        (tmp_path / "traces.jsonl").write_text("{}\n")
        (tmp_path / "keys.jsonl").write_text("{}\n")
        (tmp_path / "controller.decisions.jsonl").write_text("{}\n")
        (tmp_path / "m.prom").write_text("up 1\n")
        traces, proms, decisions, keys = obs_fleet.gather_paths(
            [str(tmp_path)])
        assert [os.path.basename(p) for p in traces] == ["traces.jsonl"]
        assert [os.path.basename(p) for p in proms] == ["m.prom"]
        assert [os.path.basename(p) for p in decisions] \
            == ["controller.decisions.jsonl"]
        assert [os.path.basename(p) for p in keys] == ["keys.jsonl"]

    def test_load_decisions_flags_torn_lines(self, tmp_path):
        p = tmp_path / "d.decisions.jsonl"
        p.write_text(json.dumps({"event": "reconcile",
                                 "reconcile": 1}) + "\n"
                     + '{"torn\n'
                     + json.dumps({"no_event": True}) + "\n")
        records, problems = obs_fleet.load_decisions([str(p)])
        assert len(records) == 1 and records[0]["reconcile"] == 1
        assert len(problems) == 2

    def test_controller_summary(self):
        decisions = [
            {"event": "reconcile", "reconcile": 1, "healthy": 2,
             "endpoints": ["r0", "r1"], "joined": ["r0", "r1"],
             "decision": {"reason": "quorum"}, "stale_scrapes": 1,
             "actions": [{"verb": "scale_up", "replica": "r2"}],
             "resized": {"r0": 4}, "warm_submissions": 2},
            {"event": "reconcile_error", "error": "x"},
            {"event": "rollout", "tag": "v2", "converged": True,
             "stragglers": []},
        ]
        s = obs_fleet.controller_summary(decisions)
        assert s["reconciles"] == 1 and s["errors"] == 1
        assert s["actions"] == [{"reconcile": 1, "verb": "scale_up",
                                 "replica": "r2", "error": None,
                                 "reason": "quorum"}]
        assert s["joined"] == ["r0", "r1"]
        assert s["stale_scrapes"] == 1 and s["resizes"] == 1
        assert s["warm_submissions"] == 2
        assert s["rollouts"] == [{"tag": "v2", "converged": True,
                                  "stragglers": []}]
        assert s["replicas_over_time"] == [{"reconcile": 1,
                                            "healthy": 2,
                                            "endpoints": 2}]

    def test_check_identity_pins_and_conflicts(self):
        good = ('fleet_replica_identity{replica_id="r0",model_tag="v1",'
                'incarnation="a"} 1\n')
        assert obs_fleet.check_identity({"s0.prom": good}) == []
        # two series at 1 in one exposition
        two = good + ('fleet_replica_identity{replica_id="r0",'
                      'model_tag="v1",incarnation="b"} 1\n')
        problems = obs_fleet.check_identity({"s0.prom": two})
        assert len(problems) == 1 and "2" in problems[0]
        # same replica_id, two incarnations across sources
        other = ('fleet_replica_identity{replica_id="r0",'
                 'model_tag="v1",incarnation="b"} 1\n')
        problems = obs_fleet.check_identity({"s0.prom": good,
                                             "s1.prom": other})
        assert len(problems) == 1
        assert "stale scrape hazard" in problems[0]
        # expositions without the metric are exempt (pre-fleet runs)
        assert obs_fleet.check_identity({"s0.prom": "up 1\n"}) == []
