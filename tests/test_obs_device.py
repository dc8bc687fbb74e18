"""`obs.device`: the kernel vocabulary against the programs' own compiled
text, the reducer against a small capture recorded on a TPU v5e through
`serve.Scheduler` with the tracer on (`tools/record_worker_capture.py`), and
the worker's intervals: off means off, and on they tile the worker's time.
"""

import gzip
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_programs
from alphafold2_tpu import obs
from alphafold2_tpu.obs import device
from alphafold2_tpu.obs import trace as obs_trace
from alphafold2_tpu.obs.trace import NULL_TRACE
from alphafold2_tpu.serve import (BucketPolicy, FoldRequest, Scheduler,
                                  SchedulerConfig, ServeMetrics)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAMED = set(device.FOLD_KERNEL_NAMES)       # the folder's: what these programs run


# -- the vocabulary ----------------------------------------------------------

@pytest.mark.quick
@pytest.mark.parametrize("op_name,kernel", [
    ("jit(run)/while/body/closed_call/recycle/Alphafold2/net/while/body/"
     "closed_call/layers/checkpoint/block/attn/triangle_multiply_outgoing/"
     "to_out/dot_general", "triangle_multiply"),
    ("jit(train_step)/transpose(jvp(loss))/Alphafold2/net/while/body/"
     "closed_call/layers/layers/checkpoint/rematted_computation/block/attn/"
     "triangle_attention_ingoing/attn/bhid,bhjd->bhij/dot_general",
     "triangle_attention"),
    ("jit(run)/Alphafold2/net/layers_1/msa_attn/row_attn/attn/"
     "attn.project_qkv/to_q/dot_general", "msa_row_attention"),
    ("jit(run)/Alphafold2/net/layers_0/msa_attn/col_attn/attn/to_out/add",
     "msa_col_attention"),
    ("jit(run)/Alphafold2/net/layers_0/attn/outer_mean/proj_out/dot_general",
     "outer_product_mean"),
    ("jit(run)/Alphafold2/net/layers_0/ff/Dense_0/dot_general", "transition"),
    ("jit(run)/jvp(loss)/Alphafold2/net/layers_0/msa_ff/Dense_1/dot_general",
     "transition"),
    # the structure module's own transitions and attention are its own
    ("jit(run)/Alphafold2/structure_module/ipa_block/ff_1/dot_general",
     "structure"),
    ("jit(run)/Alphafold2/structure_module/ipa_block/attn/to_out/dot_general",
     "structure"),
    ("jit(run)/Alphafold2/to_distogram_logits/dot_general", "other"),
    ("jit(train_step)/optimizer/mul", "other"),
    # XLA joins merged instructions' names: the first is the instruction's
    ("jit(f)/Alphafold2/net/layers_0/ff/Dense_0/reshape;jit(f)/Alphafold2/"
     "structure_module/transpose", "transition"),
    ("", "other"),
    (None, "other"),
])
def test_kernel_of(op_name, kernel):
    assert device.kernel_of(op_name) == kernel


def test_the_table_names_only_the_kernels():
    assert {kernel for _, kernel in device.KERNELS} == \
        set(device.KERNEL_NAMES) - {"other"}
    assert NAMED < set(device.KERNEL_NAMES)
    assert len(device.KERNEL_NAMES) == 16
    assert len(device.FOLD_KERNEL_NAMES) == 7


HLO = """HloModule jit_f, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/Alphafold2/net/layers_0/ff/Dense_0/mul"}
  ROOT %bitcast.1 = f32[8]{0} bitcast(%mul.1)
}

%fused_computation.2 (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %add.2 = f32[8]{0} add(%p0.1, %p0.1), metadata={op_name="jit(f)/Alphafold2/net/layers_0/attn/outer_mean/add"}
}

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/Alphafold2/net/layers_0/msa_ff/add"}
  %copy.4 = f32[8]{0} copy(%fusion.2)
  ROOT %fusion.3 = f32[8]{0} fusion(%copy.4), kind=kLoop, calls=%fused_computation.2
}
"""


def test_instruction_table_joins_fusions_to_their_roots():
    table = device.instruction_op_names(HLO)
    # its own name first; else its root's; else the nearest before the root
    assert table["fusion.2"].endswith("msa_ff/add")
    assert table["fusion.3"].endswith("outer_mean/add")
    assert table["fusion.1"].endswith("ff/Dense_0/mul")
    # no name anywhere: left out, and what is fused is no operation of its own
    assert set(table) == {"fusion.1", "fusion.2", "fusion.3"}


@pytest.mark.parametrize("program", tiny_programs.PROGRAMS)
def test_every_trunk_contraction_resolves_to_a_named_kernel(program):
    """Every contraction of the optimized HLO whose path passes through the
    trunk or the structure module resolves to one of the seven named
    kernels, forward and backward, scan, remat and unrolled. How many
    contractions carry no `op_name` at all is the compiler's doing, and is
    held under 1% where it counts: in the programs compiled for the chip
    (tests/test_chip_compile.py). The CPU compiler rewrites batched dots
    without carrying their metadata over (45 of the 192 here)."""
    text = tiny_programs.compile_tiny(program).as_text()
    resolved, through = {}, 0
    for op_name in filter(None, tiny_programs.contraction_op_names(text)):
        parts = op_name.split("/")
        if "net" in parts or "structure_module" in parts:
            through += 1
            kernel = device.kernel_of(op_name)
            assert kernel in NAMED, op_name
            resolved.setdefault(kernel, set()).add(
                "transpose(jvp(" in op_name)
    assert through > 100
    assert set(resolved) == NAMED
    if program == "train_step":     # every kernel is met going both ways
        assert all(ways == {False, True} for ways in resolved.values())
        assert 'op_name="jit(train_step)/optimizer/' in text
    else:
        assert "/recycle/Alphafold2/" in text


# -- the reducer, on a capture recorded on the chip --------------------------

@pytest.fixture(scope="module")
def profile_data():
    with gzip.open(os.path.join(DATA, "worker_capture.xplane.pb.gz")) as f:
        return jax.profiler.ProfileData.from_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def capture(profile_data):
    with open(os.path.join(DATA, "worker_capture.json")) as f:
        planted = json.load(f)
    return planted, device.reduce(profile_data, planted["op_names"])


def test_capture_kernels_sum_to_busy_time(capture):
    _, reduced = capture
    by_kernel = sum(k["seconds"] for k in reduced["kernels"].values())
    assert by_kernel == pytest.approx(reduced["busy_s"], rel=5e-3)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["unnamed_s"] <= reduced["kernels"]["other"]["seconds"]
    # the tiny model runs every kernel, and the table names them
    assert all(reduced["kernels"][k]["seconds"] > 0 for k in NAMED)
    xla_name, kernel, tail, seconds = reduced["top"][0]
    assert xla_name.startswith("%") and kernel in device.KERNEL_NAMES
    assert seconds > 0 and (tail or kernel == "other")


def test_capture_holds_the_workers_annotations(capture):
    planted, reduced = capture
    for span in ("hold", "batch_form", "dispatch", "device_wait", "fetch",
                 "resolve"):
        assert reduced["annotations"].get(span, 0) >= planted["requests"], span


def test_capture_books_the_planted_gap_to_hold(capture):
    """Each request was sent alone into a batch of two held open for
    `hold_ms`: between two folds the device idles that long, in `hold`."""
    planted, reduced = capture
    idle = reduced["idle"]
    gaps = (planted["requests"] - 1) * planted["hold_ms"] / 1e3
    assert idle["hold"] >= 0.8 * gaps
    assert max(idle, key=idle.get) == "hold"
    long_gaps = sum(v for k, v in idle.items() if k != "between_ops")
    assert idle.get("unannotated", 0.0) < 0.05 * long_gaps
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_reduce_without_a_table_books_everything_to_other(profile_data):
    bare = device.reduce(profile_data)
    assert bare["kernels"]["other"]["seconds"] == pytest.approx(
        bare["busy_s"], rel=5e-3)
    assert bare["unnamed_s"] == pytest.approx(
        bare["kernels"]["other"]["seconds"])


FUSED = ("jit(fold)/Alphafold2/net/block/attn/triangle_attention_outgoing/"
         "attn/fused_attention/pallas_call")


FUSED_MULTIPLY = ("jit(fold)/Alphafold2/net/block/attn/"
                  "triangle_multiply_outgoing/fused_triangle_multiply/"
                  "pallas_call")


@pytest.mark.parametrize("opcode,op_name,fused", [
    ("custom-call", FUSED, True),
    # XLA joins merged instructions' names: the first is the instruction's
    ("custom-call", FUSED + ";jit(fold)/Alphafold2/net/reshape", True),
    # the XLA attention a differentiated trace runs under the same scope
    ("fusion", FUSED.replace("pallas_call", "dot_general"), False),
    # another kernel's custom call
    ("custom-call", "jit(fold)/Alphafold2/net/block/attn/block_sparse/"
     "pallas_call", False),
    ("custom-call", None, False),
    # the fused triangle multiply's three stages, and the XLA formulation a
    # differentiated trace runs under the same scope for their backward
    ("custom-call", FUSED_MULTIPLY, True),
    ("custom-call", FUSED_MULTIPLY + ";jit(fold)/Alphafold2/net/reshape",
     True),
    ("fusion", FUSED_MULTIPLY.replace("pallas_call",
                                      "left_proj/dot_general"), False),
])
def test_is_fused(opcode, op_name, fused):
    assert device.is_fused(opcode, op_name) is fused


def test_reduce_books_the_fused_kernels_time_beside_its_kernels():
    """`fused_s`, the counter of a mechanism that engages when the program
    is traced: the device seconds of the fused attention's custom calls,
    within the `seconds` of the kernel their module belongs to."""
    from types import SimpleNamespace as NS
    table = {"fused_attention.3": FUSED,
             "fusion.7": FUSED.replace("fused_attention/pallas_call",
                                       "attn.project_merged/to_q/dot_general"),
             "fused_attention.4": FUSED.replace("triangle_attention_outgoing",
                                                "row_attn")}
    texts = {"fused_attention.3": "bf16[8,64,128] custom-call(bf16[8] %a)",
             "fusion.7": "bf16[8,64,128] fusion(bf16[8] %a), kind=kOutput",
             "fused_attention.4": "bf16[8,64,128] custom-call(bf16[8] %a)"}
    durations = {"fused_attention.3": 3_000_000, "fusion.7": 1_000_000,
                 "fused_attention.4": 500_000}
    start, events = 10_000, []
    for _ in range(2):
        for instr, ns in durations.items():
            events.append(NS(name=f"%{instr} = {texts[instr]}", start_ns=start,
                             duration_ns=ns, stats=()))
            start += ns + 100
    data = NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=events)])])
    kernels = device.reduce(data, table)["kernels"]
    assert kernels["triangle_attention"]["fused_s"] == pytest.approx(6e-3)
    assert kernels["triangle_attention"]["seconds"] == pytest.approx(8e-3)
    assert kernels["msa_row_attention"]["fused_s"] == pytest.approx(1e-3) \
        == pytest.approx(kernels["msa_row_attention"]["seconds"])
    assert all(kernels[k]["fused_s"] == 0 for k in device.KERNEL_NAMES
               if k not in ("triangle_attention", "msa_row_attention"))


def test_reduce_books_the_fused_triangle_multiply_to_its_kernel():
    """`fused_s` counts two mechanisms: the triangle multiply's custom calls
    (scope `fused_triangle_multiply` inside the module's own) are booked to
    `triangle_multiply`, beside what XLA still runs there, and leave every
    other kernel's counter alone."""
    from types import SimpleNamespace as NS
    xla = FUSED_MULTIPLY.replace("fused_triangle_multiply/pallas_call",
                                 "to_out/dot_general")
    table = {"fused_triangle_multiply.3": FUSED_MULTIPLY,
             "fused_triangle_multiply.4": FUSED_MULTIPLY.replace(
                 "outgoing", "ingoing"),
             "fusion.9": xla, "fused_attention.3": FUSED}
    opcode = lambda instr: "fusion" if instr.startswith("fusion") \
        else "custom-call"
    durations = {"fused_triangle_multiply.3": 2_000_000,
                 "fused_triangle_multiply.4": 1_500_000,
                 "fusion.9": 500_000, "fused_attention.3": 3_000_000}
    start, events = 10_000, []
    for instr, ns in durations.items():
        events.append(NS(
            name=f"%{instr} = bf16[64,256,64] {opcode(instr)}(bf16[8] %a)",
            start_ns=start, duration_ns=ns, stats=()))
        start += ns + 100
    data = NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=events)])])
    kernels = device.reduce(data, table)["kernels"]
    assert kernels["triangle_multiply"]["fused_s"] == pytest.approx(3.5e-3)
    assert kernels["triangle_multiply"]["seconds"] == pytest.approx(4e-3)
    assert kernels["triangle_attention"]["fused_s"] == pytest.approx(3e-3)
    assert all(kernels[k]["fused_s"] == 0 for k in device.KERNEL_NAMES
               if k not in ("triangle_multiply", "triangle_attention"))


REMAT = ("jit(step)/transpose(jvp(Alphafold2))/net/while/body/closed_call/"
         "layers/checkpoint/rematted_computation/block/attn/"
         "triangle_multiply_outgoing/to_out/dot_general")


@pytest.mark.parametrize("op_name,remat", [
    (REMAT, True),
    (REMAT + ";jit(step)/jvp(Alphafold2)/net/reshape", True),
    # the same module in the forward pass and in the backward proper
    (REMAT.replace("checkpoint/rematted_computation/", "checkpoint/"), False),
    # merged INTO an instruction of the backward: that one's name decides
    ("jit(step)/transpose(jvp(Alphafold2))/net/mul;" + REMAT, False),
    (None, False),
])
def test_is_remat(op_name, remat):
    assert device.is_remat(op_name) is remat


def test_reduce_books_the_forward_made_again_beside_its_kernels():
    """`remat_s`, what a remat policy moves: the device seconds of operations
    under `rematted_computation`, in all and within the `seconds` of the
    kernel each belongs to; the same module's forward and backward are not
    counted."""
    from types import SimpleNamespace as NS
    forward = REMAT.replace("checkpoint/rematted_computation/", "checkpoint/")
    table = {"fusion.1": REMAT, "fusion.2": forward,
             "fused_attention.3": FUSED.replace(
                 "net/block", "net/checkpoint/rematted_computation/block"),
             "fusion.4": REMAT.replace("attn/triangle_multiply_outgoing",
                                       "msa_ff")}
    durations = {"fusion.1": 2_000_000, "fusion.2": 3_000_000,
                 "fused_attention.3": 1_000_000, "fusion.4": 500_000,
                 "fusion.5": 250_000}       # no name in the table: `other`
    start, events = 10_000, []
    for instr, ns in durations.items():
        opcode = "custom-call" if instr.startswith("fused") else "fusion"
        events.append(NS(name=f"%{instr} = bf16[8,64,128] {opcode}(bf16[8] "
                         "%a)", start_ns=start, duration_ns=ns, stats=()))
        start += ns + 100
    data = NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=events)])])
    reduced = device.reduce(data, table)
    kernels = reduced["kernels"]
    assert kernels["triangle_multiply"]["remat_s"] == pytest.approx(2e-3)
    assert kernels["triangle_multiply"]["seconds"] == pytest.approx(5e-3)
    assert kernels["triangle_attention"]["remat_s"] == pytest.approx(1e-3) \
        == pytest.approx(kernels["triangle_attention"]["fused_s"])
    assert kernels["transition"]["remat_s"] == pytest.approx(5e-4)
    assert kernels["other"]["remat_s"] == 0 < kernels["other"]["seconds"]
    assert reduced["remat_s"] == pytest.approx(3.5e-3)
    assert all(k["remat_s"] <= k["seconds"] for k in kernels.values())


def test_capture_of_a_fold_books_no_forward_made_again(capture):
    _, reduced = capture     # a fold is not differentiated
    assert reduced["remat_s"] == 0
    assert all(k["remat_s"] == 0 for k in reduced["kernels"].values())


def test_capture_of_the_xla_attention_books_no_fused_time(capture):
    _, reduced = capture     # recorded before the kernel took the folds
    assert all(k["fused_s"] == 0 for k in reduced["kernels"].values())


def test_profile_needs_a_device_plane():
    """The CPU backend records no device plane: `profile` says so, and
    leaves no capture behind."""
    fn = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
    with pytest.raises(RuntimeError, match="no device operation"):
        device.profile(fn, lambda: jax.block_until_ready(
            fn(jnp.ones((8, 8)))), repeats=1)


# -- the worker's intervals --------------------------------------------------

class _StubResult:
    def __init__(self, b, n):
        self.coords = np.zeros((b, n, 3), np.float32)
        self.confidence = np.ones((b, n), np.float32)


class _StubExecutor:
    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s

    def run(self, batch, num_recycles, trace=NULL_TRACE):
        with trace.span("fold"):
            time.sleep(self.delay_s)
            b, n = batch["seq"].shape
            return _StubResult(b, n)


def _scheduler(executor, tracer=None, **config):
    reg = obs.MetricsRegistry()
    return Scheduler(executor, BucketPolicy((16,)),
                     SchedulerConfig(num_recycles=0, **config),
                     ServeMetrics(registry=reg), registry=reg, tracer=tracer)


class _CountingAnnotation:
    entered = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _CountingAnnotation.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.quick
def test_off_means_off(monkeypatch):
    """With NULL_TRACER the worker enters no profiler annotation and builds
    no context per batch or per wait; with a Tracer every interval it times
    is one."""
    monkeypatch.setattr(obs_trace, "TraceAnnotation", _CountingAnnotation)
    monkeypatch.setattr(_CountingAnnotation, "entered", [])
    assert obs.NULL_TRACER.annotate("idle") is obs.NULL_TRACER.annotate(
        "resolve") is NULL_TRACE.span("fetch")        # one shared no-op
    rng = np.random.default_rng(0)
    requests = lambda: [FoldRequest(seq=rng.integers(0, 20, 12))
                        for _ in range(4)]
    with _scheduler(_StubExecutor(), max_batch_size=2,
                    max_wait_ms=10.0) as off:
        for request in requests():
            assert off.submit(request).result(timeout=30).ok
    assert _CountingAnnotation.entered == []
    snap = off.metrics.snapshot()
    assert snap["worker_busy_s"] > 0 and snap["worker_hold_s"] > 0
    assert snap["fetch_s"] > 0 and snap["resolve_s"] > 0

    with _scheduler(_StubExecutor(), tracer=obs.Tracer(), max_batch_size=2,
                    max_wait_ms=10.0) as on:
        for request in requests():
            assert on.submit(request).result(timeout=30).ok
    assert {"idle", "hold", "batch_form", "fold", "fetch",
            "resolve"} <= set(_CountingAnnotation.entered)
    # worker intervals stay out of the requests' records
    spans = {s["name"] for rec in on.tracer.slowest() for s in rec["spans"]}
    assert {"queue", "batch_form", "fold", "fetch"} <= spans
    assert not spans & {"idle", "hold", "resolve"}


def test_worker_counters_tile_its_time_in_service():
    """idle + hold + busy is the worker's wall clock from the first request
    enqueued to its exit, within 2%; what came before the first request is
    not idleness in service."""
    scheduler = _scheduler(_StubExecutor(delay_s=0.05), max_batch_size=2,
                           max_wait_ms=40.0, poll_ms=20.0)
    rng = np.random.default_rng(1)
    with scheduler:
        time.sleep(0.3)                  # parked before the first arrival
        t0 = time.monotonic()
        tickets = []
        for i in range(16):
            tickets.append(scheduler.submit(
                FoldRequest(seq=rng.integers(0, 20, 12))))
            time.sleep(0.12 if i % 4 == 3 else 0.02)
        assert all(t.result(timeout=30).ok for t in tickets)
    # read once the worker has gone: a ticket resolves before its batch is
    # booked, and a wait still under way is not counted yet
    wall = time.monotonic() - t0
    snap = scheduler.metrics.snapshot()
    tiled = snap["worker_idle_s"] + snap["worker_hold_s"] \
        + snap["worker_busy_s"]
    assert tiled == pytest.approx(wall, rel=0.02)
    assert snap["worker_idle_s"] > 0 and snap["worker_hold_s"] > 0
    assert snap["fetch_s"] + snap["resolve_s"] < snap["worker_busy_s"]
    assert snap["exec_busy_s"] <= snap["worker_busy_s"]


# -- the token decoder's names, beside the folder's ---------------------------

# the table as it stood before the decoder's names joined it (PR 34), and its
# rule: everything under the structure module is the structure module's, else
# the innermost component that the table maps
FOLD_TABLE = {
    "structure_module": "structure",
    "triangle_multiply_outgoing": "triangle_multiply",
    "triangle_multiply_ingoing": "triangle_multiply",
    "triangle_attention_outgoing": "triangle_attention",
    "triangle_attention_ingoing": "triangle_attention",
    "row_attn": "msa_row_attention", "col_attn": "msa_col_attention",
    "outer_mean": "outer_product_mean", "ff": "transition",
    "msa_ff": "transition"}


def _fold_verdict(op_name):
    parts = op_name.split(";", 1)[0].split("/") if op_name else []
    if "structure_module" in parts:
        return "structure"
    return next((FOLD_TABLE[p] for p in reversed(parts) if p in FOLD_TABLE),
                "other")


def test_the_fold_tables_verdicts_are_what_they_were():
    """On the `op_name`s of a fold recorded on the v5e: each goes where the
    folder's table alone sent it, none to a kernel of the decoder."""
    with open(os.path.join(DATA, "worker_capture.json")) as f:
        recorded = sorted(set(json.load(f)["op_names"].values()))
    assert len(recorded) > 100
    verdicts = {name: device.kernel_of(name) for name in recorded}
    assert verdicts == {name: _fold_verdict(name) for name in recorded}
    assert set(verdicts.values()) <= set(device.FOLD_KERNEL_NAMES) | {"other"}
    assert len(set(verdicts.values())) >= 6
    assert not set(FOLD_TABLE) & set(device.DECODER_KERNEL_NAMES)


# each decoder family's kernels: its attention's kind, then what both have
DECODER_CELLS = {
    "kanana2_30b_a3b_ep8": ("kanana2", {"mla_attention"}),
    "laguna_s21_ep32": ("laguna", {"full_attention", "window_attention"})}


@pytest.mark.parametrize("config_name", sorted(DECODER_CELLS))
def test_every_decoder_instruction_lands_in_a_named_kernel(config_name):
    """A tiny training step of the causal decoder, traced and compiled here:
    every instruction whose `op_name` passes through the model belongs to one
    of the decoder's kernels, forward, backward and made again, and every one
    of the family's has some: its attention's and the six the layers share
    (the laguna step has both attentions, and no latent one)."""
    import jax
    from benchmark import families
    from benchmark.drivers import train_steps
    name, attention = DECODER_CELLS[config_name]
    family = families.load(name)
    with open(os.path.join(os.path.dirname(DATA), os.pardir, "benchmark",
                           "configs", config_name + ".json")) as f:
        config = {**json.load(f), **family.TINY}
    traffic = dict(batch=2, tokens=16, learning_rate=3e-4)
    model = family.build_model(config)
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype)
    shapes = family.param_shapes(model)
    _, step, args = train_steps.largest_program(model, shapes, config,
                                                traffic, place)
    # an executable from the persistent cache keeps the names of the trace
    # that first compiled it: compile this one anew
    from jax.experimental.compilation_cache import compilation_cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = step.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    table = device.instruction_op_names(text)
    parts = lambda name: name.split(";", 1)[0].split("/")
    inside = [name for name in table.values()        # `remat2`: the call
              if "CausalDecoder" in parts(name)      # that holds a layer
              and parts(name)[-1] != "remat2"]
    assert len(inside) > 100
    verdicts = {name: device.kernel_of(name) for name in inside}
    astray = sorted(n for n, k in verdicts.items()
                    if k not in device.DECODER_KERNEL_NAMES)
    assert not astray, astray[:10]
    shared = set(device.DECODER_KERNEL_NAMES) - {
        "mla_attention", "full_attention", "window_attention"}
    assert set(verdicts.values()) == shared | attention
    assert any(device.is_remat(name) for name in inside)


def test_a_window_attention_call_goes_to_its_kernel_made_again_and_backward():
    """The banded kernel's calls as the laguna step names them: forward, made
    again under remat, and the backward's (dq; dk and dv) all go to
    `window_attention`; the made-again one is counted as remat; the full
    layers' go to `full_attention`."""
    layer = "jit(step)/{}CausalDecoder/layers_1/{}window_attention/" \
        "causal_attention/{}"
    cases = [
        layer.format("jvp(loss)/", "", "pallas_call"),
        layer.format("jvp(loss)/", "checkpoint/rematted_computation/",
                     "pallas_call"),
        layer.format("transpose(jvp(loss))/", "checkpoint/",
                     "splash_mha_dq_no_residuals/pallas_call"),
        layer.format("transpose(jvp(loss))/", "checkpoint/",
                     "splash_mha_dkv_no_residuals/pallas_call")]
    assert [device.kernel_of(c) for c in cases] == ["window_attention"] * 4
    assert [device.is_remat(c) for c in cases] == [False, True, False, False]
    assert device.kernel_of(cases[0].replace(
        "layers_1/window_attention", "layers_4/full_attention")) \
        == "full_attention"


def test_an_instruction_over_several_lines_keeps_its_name_and_its_module():
    """A Pallas call of the blocked causal attention carries an attribute
    with line breaks; its `metadata` follows on a line that begins "}}". The
    computation goes on after it, and the call takes its own name."""
    text = "\n".join([
        "HloModule jit_step, is_scheduled=true", "",
        "ENTRY %main.9 (p0: bf16[8]) -> bf16[8] {",
        "  %p0 = bf16[8]{0} parameter(0)",
        '  %splash_mha_fwd.1 = bf16[8]{0} custom-call(%p0), '
        'custom_call_target="tpu_custom_call", frontend_attributes={'
        'kernel_metadata={', '"xprof_metadata":"{\\"block_q\\": 1024}"',
        '}}, metadata={op_name="jit(step)/jvp(loss)/CausalDecoder/layers_0/'
        'mla_attention/causal_attention/pallas_call" stack_frame_id=3}',
        '  ROOT %add.2 = bf16[8]{0} add(%splash_mha_fwd.1, %p0), metadata={'
        'op_name="jit(step)/jvp(loss)/CausalDecoder/layers_0/dense_mlp/add"}',
        "}", ""])
    table = device.instruction_op_names(text)
    assert device.kernel_of(table["splash_mha_fwd.1"]) == "mla_attention"
    assert device.kernel_of(table["add.2"]) == "dense_mlp"
