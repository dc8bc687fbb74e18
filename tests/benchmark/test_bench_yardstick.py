"""The yardstick's own parts: the trace reduction on a small trace recorded on
a TPU v5e, the copied FLOP count against the program's, the chip gate, the
traffic generator, the weights and the manifest's wiring."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import bench_tiny

REPO = bench_tiny.REPO
MANIFEST = bench_tiny.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


# -- trace reduction --------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_trace():
    from benchmark import trace_reduce
    path = os.path.join(os.path.dirname(__file__), "data",
                        "tiny_tpu.xplane.pb")
    return trace_reduce.reduce_xplane(path, ("submit", "wait", "input_prep"))


def test_trace_window_is_the_marked_one(tiny_trace):
    # three calls of one jitted program inside one `bench_window` mark
    assert tiny_trace["window_s"] == pytest.approx(0.010612829)
    assert tiny_trace["devices"] == ["/device:TPU:0"]
    assert tiny_trace["device_events"] == 9


def test_trace_busy_is_the_union_of_device_ops(tiny_trace):
    assert tiny_trace["busy_s"] == pytest.approx(3.5678e-05)
    assert 0 < tiny_trace["busy_s"] < tiny_trace["window_s"]


def test_trace_names_ops_without_their_types(tiny_trace):
    names = [name for name, _ in tiny_trace["device_ops"]]
    assert names[0] == "%fusion fusion"
    assert all(len(n) < 100 and "{" not in n for n in names)
    assert sum(s for _, s in tiny_trace["device_ops"]) == pytest.approx(
        tiny_trace["busy_s"], rel=1e-3)


def test_trace_books_idle_gaps_to_host_annotations(tiny_trace):
    gaps = dict(tiny_trace["idle_gaps"])
    assert max(gaps, key=gaps.get) == "input_prep"     # the 2 ms sleeps
    assert sum(gaps.values()) == pytest.approx(
        tiny_trace["window_s"] - tiny_trace["busy_s"], rel=1e-6)


def test_device_idle_reader_returns_nothing_without_a_trace():
    from benchmark.layer_metrics import device_idle
    assert device_idle.read([], {}, None, {}) is None
    assert device_idle.read([], {}, {"busy_s": 1.0, "window_s": 4.0},
                            {}) == pytest.approx(75.0)


@pytest.mark.parametrize("text,short", [
    ("%fusion.3 = bf16[8]{0:T(256)} fusion(bf16[8]{0} %p), kind=kLoop",
     "%fusion.3 fusion"),
    ("%while.118 = (s32[]{:T(128)}, f32[1,640,3]{1,2,0:T(4,128)S(1)}) "
     "while((s32[]{:T(128)}) %tuple), condition=%c", "%while.118 while"),
    ("plain-name", "plain-name"),
])
def test_short_op_name(text, short):
    from benchmark import trace_reduce
    assert trace_reduce.short_op_name(text) == short


# -- FLOP count -------------------------------------------------------------

def test_flops_copy_agrees_with_the_programs_count():
    import jax
    import jax.numpy as jnp
    from alphafold2_tpu import predict
    from alphafold2_tpu.utils import flops as program_flops
    from benchmark import flops, reference, weights
    from benchmark.run import build_model
    cfg = dict(bench_tiny.TINY_MODEL, predict_coords=True,
               structure_module_depth=2, use_scan=True, depth=3)
    model = build_model(cfg)
    shapes = weights.param_shapes(model)
    seq = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    msa = jax.ShapeDtypeStruct((1, 4, 16), jnp.int32)
    fold = lambda p, s, m: predict.fold(model, p, s, msa=m, num_recycles=1)
    ours = flops.forward_flops(fold, shapes, seq, msa)
    theirs = program_flops.forward_flops(fold, shapes, seq, msa)
    assert ours == theirs > 0
    # and the plain reference needs what the program computes (one chain)
    ref = flops.forward_flops(
        lambda p, s, m: reference.fold(p, cfg, s, m, 1), shapes,
        jax.ShapeDtypeStruct((16,), jnp.int32),
        jax.ShapeDtypeStruct((4, 16), jnp.int32))
    assert ref == pytest.approx(ours, rel=0.02)


# -- the look for a chip ----------------------------------------------------

class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


class _FakeJax:
    def __init__(self, devices):
        self._devices = devices

    def devices(self):
        return self._devices


@pytest.mark.parametrize("devices,chips", [
    ([_FakeDevice("cpu", "cpu")], 1),                      # no accelerator
    ([_FakeDevice("tpu", "TPU v9 unheard-of")], 1),        # no peak recorded
    ([_FakeDevice("tpu", "TPU v5 lite")], 4),              # too few chips
])
def test_no_chip_no_result(devices, chips, capsys):
    from benchmark import run as brun
    with pytest.raises(SystemExit) as exit_:
        brun.require_chip(_FakeJax(devices), chips, brun.load_peaks())
    assert exit_.value.code == 2
    assert capsys.readouterr().out == ""


def test_a_known_chip_passes():
    from benchmark import run as brun
    devices = [_FakeDevice("tpu", "TPU v5 lite")] * 4
    assert len(brun.require_chip(_FakeJax(devices), 1,
                                 brun.load_peaks())) == 1


def test_command_line_on_the_cpu_exits_nonzero_with_no_result():
    cell = MANIFEST["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 2
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout


# -- traffic ----------------------------------------------------------------

def test_every_seed_gets_the_same_lengths_in_another_order():
    from benchmark import traffic_gen
    lengths = [200, 216, 232, 248, 256, 304, 344, 384, 480, 620]
    orders = []
    for seed in (1, 2, 2 ** 31 + 7):
        gen = traffic_gen.closed_loop_requests(seed, lengths, 2)
        cycle = [next(gen).length for _ in range(len(lengths))]
        assert sorted(cycle) == lengths
        orders.append(cycle)
    assert orders[0] != orders[1] != orders[2]


def test_open_loop_schedule_keeps_rate_and_mix():
    from benchmark import traffic_gen
    traffic = {"rate_per_s": 5.0, "block": 40, "below_edge": 0.25,
               "length_steps": 4, "length_mix": [[64, 0.3], [128, 0.4], [256, 0.3]]}
    for seed in (3, 2 ** 31 + 11):
        sched = traffic_gen.open_loop_schedule(seed, traffic, 40.0)
        assert len(sched) in (199, 200)                    # 5/s for 40 s
        times = [t for t, _ in sched]
        assert times == sorted(times) and times[-1] < 40.0
        edge = lambda n: 64 if n <= 64 else 128 if n <= 128 else 256
        share = {e: sum(edge(n) == e for _, n in sched) / len(sched)
                 for e in (64, 128, 256)}
        assert share[64] == pytest.approx(0.3, abs=0.02)
        assert share[128] == pytest.approx(0.4, abs=0.02)
        assert {n for _, n in sched} <= {48, 53, 59, 64, 96, 107, 117, 128,
                                         192, 213, 235, 256}
    a = traffic_gen.open_loop_schedule(3, traffic, 40.0)
    b = traffic_gen.open_loop_schedule(4, traffic, 40.0)
    assert a != b
    gaps = lambda s: sorted(np.round(np.diff([0.0] + [t for t, _ in s][:40]),
                                     9))
    assert gaps(a) == gaps(b)              # the same gaps, another order


def test_train_batches_differ_by_step_and_by_seed():
    from benchmark import traffic_gen
    a = traffic_gen.train_batch(5, 0, 16, 4)
    assert a["seq"].shape == (1, 16) and a["msa"].shape == (1, 4, 16)
    steps = np.linalg.norm(np.diff(a["coords"][0], axis=0), axis=-1)
    assert steps == pytest.approx(3.8, rel=1e-4)
    assert np.array_equal(a["seq"], traffic_gen.train_batch(5, 0, 16, 4)["seq"])
    assert not np.array_equal(a["seq"],
                              traffic_gen.train_batch(5, 1, 16, 4)["seq"])
    assert not np.array_equal(a["seq"],
                              traffic_gen.train_batch(6, 0, 16, 4)["seq"])


def test_percentile_is_nearest_rank_over_all_values():
    from benchmark.drivers.open_fold import percentile
    values = list(range(1, 101))
    assert percentile(values, 50) == 50
    assert percentile(values, 95) == 95
    assert percentile([7.0], 95) == 7.0


# -- weights ----------------------------------------------------------------

def test_weights_come_from_the_seed_and_leave_nothing_at_zero():
    import jax
    from benchmark import weights
    from benchmark.run import build_model
    cfg = dict(bench_tiny.TINY_MODEL, predict_coords=True,
               structure_module_depth=2, use_scan=True)
    model = build_model(cfg)
    a = weights.make_params(model, 2 ** 31 + 12345)
    b = weights.make_params(model, 2 ** 31 + 12345)
    c = weights.make_params(model, 12345)
    flat = lambda t: np.concatenate([np.ravel(x) for x in jax.tree.leaves(t)])
    assert np.array_equal(flat(a), flat(b))
    assert not np.array_equal(flat(a), flat(c))
    for path, leaf in jax.tree_util.tree_flatten_with_path(a)[0]:
        names = weights._path_names(path)
        assert float(np.abs(np.asarray(leaf)).max()) > 0, names
        if names[-1] == "scale":
            assert float(np.mean(leaf)) == pytest.approx(1.0, abs=0.05)


# -- the manifest's wiring --------------------------------------------------

@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda c: c["name"])
def test_cell_is_wired_by_data_alone(cell):
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    assert NAME.match(cell["name"]) and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    with open(os.path.join(REPO, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    assert set(configs[cell["config"]]["reduced"]) == set(config["reduced"])
    with open(os.path.join(REPO, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "drivers", traffic["driver"] + ".py"))
    assert all(0 < limit < 1 for limit in traffic["limits"].values())
    reports = lambda m: cell["name"] in m.get("workloads", [cell["name"]])
    e2e = [m["name"] for m in MANIFEST["end_to_end"] if reports(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reports(m) for m in MANIFEST["per_layer"])


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_what_its_cells_report(
        metric):
    assert NAME.match(metric["name"])
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", metric["unit"])
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "layer_metrics",
        metric["name"].split(".")[0] + ".py"))
    moved = {m["name"]: m for m in MANIFEST["end_to_end"]}[metric["moves"]]
    cells = [c["name"] for c in MANIFEST["workloads"]]
    for cell in metric.get("workloads", cells):
        assert cell in moved.get("workloads", cells)


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric_is_bounded_and_taken_by_the_benchmark(metric):
    assert NAME.match(metric["name"])
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")
    assert metric["better"] in ("lower", "higher")
