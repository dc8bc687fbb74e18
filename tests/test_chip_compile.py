"""Ask the chip's compiler before the chip: compile the main path's
kernels (and the scan fold) at real widths for a DESCRIBED v5e:2x2 — the
TPU compiler is installed here, the chip is not (on-chip-measurement
guide, section 2). Mosaic refusals that interpret mode cannot see — a
slice off the tiling, too much fast memory — fail here at no chip time.

Nothing runs: these tests say nothing about results or times. A compile
that passes is not a chip run; `chip_smoke.py` is.

All such tests live in THIS file (one xdist worker loads the TPU library
and keeps it), the topology is described inside a module-scoped fixture
(never at import/collection/parametrize time), and the persistent compile
cache is off around the compiles (an entry written for a described chip
cannot be read back without one).
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import tiny_programs
from alphafold2_tpu import Alphafold2, predict
from alphafold2_tpu.ops.attention import fused_attention_merged
from alphafold2_tpu.ops.block_sparse import (banded_block_pattern,
                                             block_sparse_attention)

HEADS, D, BLOCK, FOLD_AXIS = 8, 64, 128, 2
LENGTHS = (256, 384, 1024)
# every bucket of the benchmark's three cells (64 is half a lane tile, 384
# and 640 are no powers of two), and the long-fold bucket
FUSED_LENGTHS = (64, 128, 256, 384, 512, 640, 1024)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _attention_shapes(n, sharding):
    """q/k/v bf16 (B, n, D) with heads folded innermost and a folded axis
    of 2, the unrepeated f32 pair bias, and the (B // heads, n) key mask:
    the layout model/primitives.py hands the block-sparse kernel."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    b = FOLD_AXIS * HEADS
    qkv = sds((b, n, D), jnp.bfloat16)
    return (qkv, qkv, qkv, sds((HEADS, n, n), jnp.float32),
            sds((FOLD_AXIS, n), jnp.bool_))


def _merged_shapes(n, sharding, batch=1, fold_axis=FOLD_AXIS):
    """What `Attention.__call__` hands the fused kernel: q (rows, n,
    heads * D) and [k | v] (rows, n, 2 * heads * D) in bf16 as the Dense
    projections lay them out, the unrepeated pair bias in bf16 as
    `edges_to_attn_bias` produces it, and the (rows, n) mask."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    rows = batch * fold_axis
    return (sds((rows, n, HEADS * D), jnp.bfloat16),
            sds((rows, n, 2 * HEADS * D), jnp.bfloat16),
            sds((batch * HEADS, n, n), jnp.bfloat16),
            sds((rows, n), jnp.bool_))


def _compiled_kernel_text(fn, shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text   # Mosaic, not the interpreter
    return text


@pytest.mark.parametrize("n,batch", [(n, 1) for n in FUSED_LENGTHS]
                         + [(n, 8) for n in FUSED_LENGTHS[:3]])
def test_fused_attention_compiles_for_v5e(n, batch, one_chip,
                                          no_persistent_cache):
    """bf16 operands and bias, query and key masks, as many folded rows as
    positions (the pair track's shape: the step takes several rows), at
    batch 1 and, for the online cell's buckets, at its batch of 8."""
    def fwd(q, kv, bias, mask):
        return fused_attention_merged(q, kv, bias=bias, q_mask=mask,
                                      k_mask=mask, heads=HEADS,
                                      bias_repeat=n)

    _compiled_kernel_text(fwd, _merged_shapes(n, one_chip, batch, n))


def _pair_sized_copies(text, elements, kernel=None):
    """The `copy` instructions of an executable's text that move at least
    `elements` elements (of `kernel`'s alone, by `obs.device`'s booking of
    their `op_name`, where one is named)."""
    import math
    import re

    from alphafold2_tpu.obs import device
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"copy\(", line)
        if not m or not m.group(2):
            continue
        if math.prod(int(d) for d in m.group(2).split(",")) < elements:
            continue
        named = re.search(r'op_name="([^"]*)"', line)
        if kernel is None or (named and device.kernel_of(
                named.group(1)) == kernel):
            found.append(m.group(1))
    return found


def _triangle_multiply_leaves(sds, dim, hidden):
    """The shapes of `TriangleMultiplicativeModule`'s parameter leaves."""
    from alphafold2_tpu.ops.triangle_multiply import PROJECTIONS
    dense = lambda i, o: {"kernel": sds((i, o), jnp.float32),
                          "bias": sds((o,), jnp.float32)}
    norm = lambda w: {"LayerNorm_0": {"scale": sds((w,), jnp.float32),
                                      "bias": sds((w,), jnp.float32)}}
    return dict({name: dense(dim, hidden) for name in PROJECTIONS},
                LayerNorm_0=norm(dim), LayerNorm_1=norm(hidden),
                to_out=dense(hidden, dim))


@pytest.mark.parametrize("n,batch", [(n, 1) for n in FUSED_LENGTHS]
                         + [(n, 8) for n in FUSED_LENGTHS[:3]])
def test_fused_triangle_multiply_compiles_for_v5e(n, batch, one_chip,
                                                  no_persistent_cache):
    """The fused update at the published widths (dim = hidden = 256, bf16,
    a mask, the residual), both mixes in one program, at every bucket of the
    three cells and the long-fold bucket: six Mosaic custom calls (three
    stages a mix) and no pair-sized copy: nothing relays an operand between
    the stages."""
    from alphafold2_tpu.ops import triangle_multiply as tm
    dim = hidden = 256
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    p = _triangle_multiply_leaves(sds, dim, hidden)

    def both(p, x, mask):
        for mix in ("outgoing", "ingoing"):
            x = tm.fused_triangle_multiply(p, x, mask, x, mix=mix,
                                           dtype=jnp.bfloat16)
        return x

    text = _compiled_kernel_text(both, (
        p, sds((batch, n, n, dim), jnp.bfloat16),
        sds((batch, n, n), jnp.bool_)))
    calls = sum("tpu_custom_call" in line and " custom-call(" in line
                for line in text.splitlines())
    assert calls == 6, calls
    assert not _pair_sized_copies(text, batch * n * n * hidden)


def test_fused_triangle_multiply_gradient_is_xlas_for_v5e(
        one_chip, no_persistent_cache):
    """The differentiated path that was kept (PERF.md section 6, PR 36): a
    trace under `jax.grad` runs `triangle_multiply_xla` forward and backward,
    the program the training step had before the kernels: no Mosaic custom
    call is left in it (the crop-256 step's `fused_s` reads 0 for
    `triangle_multiply`), while the same function undifferentiated holds the
    three."""
    from alphafold2_tpu.ops import triangle_multiply as tm
    n, dim = 256, 256
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    p = _triangle_multiply_leaves(sds, dim, dim)
    shapes = (p, sds((1, n, n, dim), jnp.bfloat16),
              sds((1, n, n), jnp.bool_))

    def update(p, x, mask):
        return tm.fused_triangle_multiply(p, x, mask, x, mix="ingoing",
                                          dtype=jnp.bfloat16)

    def loss(p, x, mask):
        return jnp.sum(update(p, x, mask).astype(jnp.float32) ** 2)

    count = lambda text: sum("tpu_custom_call" in line
                             and " custom-call(" in line
                             for line in text.splitlines())
    assert count(jax.jit(update).lower(*shapes).compile().as_text()) == 3
    assert count(jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *shapes).compile().as_text()) == 0


@pytest.mark.parametrize("n", LENGTHS)
def test_block_sparse_attention_compiles_for_v5e(n, one_chip,
                                                 no_persistent_cache):
    pattern = banded_block_pattern(n // BLOCK, window=0, num_global=1)

    def fwd(q, k, v, bias, mask):
        return block_sparse_attention(
            q, k, v, pattern, bias=bias, bias_repeat=FOLD_AXIS, k_mask=mask,
            heads=HEADS, scale=1.0, block=BLOCK)

    _compiled_kernel_text(fwd, _attention_shapes(n, one_chip))


# (n, rows, bias): the training cell's three attentions (triangle, MSA row,
# MSA column), two folded rows at 256 and 384, the longest whole row of
# queries, and a length whose queries the forward blocks
GRADIENT_CASES = {
    "triangle-256": (256, 256, True),
    "msa-row-256": (256, 128, True),
    "msa-col-128-no-bias": (128, 256, False),
    "two-rows-256": (256, FOLD_AXIS, True),
    "two-rows-384": (384, FOLD_AXIS, True),
    "pair-640": (640, 640, True),
    "blocked-queries-1024": (1024, FOLD_AXIS, True),
}


@pytest.mark.parametrize("case", sorted(GRADIENT_CASES))
def test_fused_attention_gradient_compiles_for_v5e(case, one_chip,
                                                   no_persistent_cache):
    """jax.grad through the custom_vjp, cotangents for q, [k | v] and the
    unrepeated bias: the forward and the backward are Mosaic kernels and no
    tensor of the logits' shape is left in the program, at every length
    whose row of queries is one grid step; beyond it (1,024) forward and
    backward are the XLA attention's and the logits are there."""
    from alphafold2_tpu.ops.attention import backward_admits
    n, rows, has_bias = GRADIENT_CASES[case]
    q, kv, bias, mask = _merged_shapes(n, one_chip, 1, rows)

    def loss(q, kv, bias, mask):
        out = fused_attention_merged(q, kv, bias=bias, q_mask=mask,
                                     k_mask=mask, heads=HEADS,
                                     bias_repeat=rows if has_bias else 1)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2) if has_bias else (0, 1))
                   ).lower(q, kv, bias if has_bias else None,
                           mask).compile().as_text()
    calls = sum("tpu_custom_call" in line and " custom-call(" in line
                for line in text.splitlines())
    logits = _logits_shaped(text, rows * HEADS, n)
    if backward_admits(n, n):
        assert calls == 2 and not logits, (calls, logits)
    else:
        assert calls == 0 and logits, (calls, logits)


def _logits_shaped(text, rows_heads, n):
    """The array shapes in an executable's text that hold one logit for
    every (row, head, query, key): rows x heads x n x n elements with (n, n)
    innermost, however the leading axes are grouped."""
    import math
    import re
    found = set()
    for dims in re.findall(r"\b(?:bf16|f32)\[([\d,]+)\]", text):
        shape = tuple(int(d) for d in dims.split(","))
        if shape[-2:] == (n, n) and math.prod(shape[:-2]) == rows_heads:
            found.add(shape)
    return found


@pytest.mark.parametrize("rule", ("on_a_tpu", "off_the_chip"))
def test_scan_fold_compiles_for_v5e(rule, one_chip, no_persistent_cache,
                                    monkeypatch):
    """The 3-recycle predict.fold of chip_smoke.py's model (published
    widths, depth 2) at L=256, MSA 5, from eval_shape parameter shapes; the
    program must fit one v5e's 16 GB with room to spare. With the platform
    predicate saying TPU (`Attention.__call__`'s rule sees the CPU here, so
    the test steers it), both triangle attentions and the MSA row attention
    are Mosaic custom calls that `obs.device` books to their kernels, and
    no tensor of the logits' shape is left in the program; so are both
    triangle multiplies (three stages each: `ops/triangle_multiply.py`),
    booked to `triangle_multiply`, with no pair-sized copy left among that
    kernel's instructions; with the predicate as it is here the same
    detectors find the logits and the copies."""
    import re

    import chip_smoke
    from alphafold2_tpu import runtime
    from alphafold2_tpu.obs import device
    from alphafold2_tpu.ops import triangle_multiply

    monkeypatch.setattr(runtime, "on_tpu", lambda: rule == "on_a_tpu")
    # one 256 map is 32 MiB, what XLA keeps on the chip: the rule leaves it
    # to XLA, and the test takes the size out of the rule
    monkeypatch.setattr(triangle_multiply, "_MIN_PAIR_BYTES", 0)
    n, m = chip_smoke.BUCKET, chip_smoke.MSA_DEPTH
    model = Alphafold2(predict_coords=True, dtype=jnp.bfloat16,
                       **chip_smoke.FULL_MODEL)
    seq = jnp.zeros((1, n), jnp.int32)
    msa = jnp.zeros((1, m, n), jnp.int32)
    params = jax.eval_shape(
        functools.partial(model.init, msa=msa, mask=jnp.ones((1, n), bool),
                          msa_mask=jnp.ones((1, m, n), bool)),
        jax.random.PRNGKey(0), seq)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = jax.tree.map(lambda s: sds(s.shape, s.dtype), params)

    def fold(params, seq, msa, mask, msa_mask):
        return predict.fold(model, params, seq, msa=msa, mask=mask,
                            msa_mask=msa_mask,
                            num_recycles=chip_smoke.NUM_RECYCLES)

    compiled = jax.jit(fold).lower(
        params, sds((1, n), jnp.int32), sds((1, m, n), jnp.int32),
        sds((1, n), jnp.bool_), sds((1, m, n), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < 8 * 2**30, total

    text = compiled.as_text()
    logits = _logits_shaped(text, n * chip_smoke.FULL_MODEL["heads"], n)
    calls = [op_name for line in text.splitlines()
             if "tpu_custom_call" in line
             for op_name in re.findall(r'op_name="([^"]*)"', line)]
    pair = n * n * chip_smoke.FULL_MODEL["dim"]
    copies = _pair_sized_copies(text, pair, "triangle_multiply")
    if rule == "off_the_chip":
        assert logits and copies and not calls
        return
    assert not logits, logits
    assert not copies, copies
    booked = {}
    for op_name in calls:
        assert device.is_fused("custom-call", op_name), op_name
        site = [p for p in op_name.split("/") if p in device._BY_COMPONENT]
        booked.setdefault(site[-1], set()).add(device.kernel_of(op_name))
    # the MSA column attention attends 5 alignment rows: under the rule's
    # lower bound, so it stays with XLA
    assert booked == {
        "triangle_multiply_outgoing": {"triangle_multiply"},
        "triangle_multiply_ingoing": {"triangle_multiply"},
        "triangle_attention_outgoing": {"triangle_attention"},
        "triangle_attention_ingoing": {"triangle_attention"},
        "row_attn": {"msa_row_attention"}}, booked
    # three stages a mix, in each of the model's two (unrolled) blocks
    assert sum("fused_triangle_multiply" in name for name in calls) \
        == 6 * chip_smoke.FULL_MODEL["depth"]


@pytest.mark.parametrize("sees", ("no_limit", "a_v5es_limit"))
def test_train_step_books_every_fused_call_to_its_attention(
        sees, one_chip, no_persistent_cache, monkeypatch):
    """A tiny training step (scan + remat, 64 residues, 64 alignment rows:
    the shortest every attention's rule admits) with the platform predicate
    saying TPU. Where the trace sees no device memory (here, as on a
    described topology) the remat policy keeps nothing and each of the four
    attentions of a block is three Mosaic custom calls (the forward, remat's
    forward again, the backward); handed a v5e's limit the trunk's rule
    (`model/evoformer.py`) keeps the kernels' outputs and there are two, none
    under `rematted_computation`. Either way every one is under the fused
    scope and booked by `obs.device` to its attention's kernel, none to
    `other`, and no tensor of the logits' shape is left."""
    import re

    from alphafold2_tpu import runtime, train
    from alphafold2_tpu.model import evoformer
    from alphafold2_tpu.obs import device

    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setattr(
        evoformer, "device_bytes_limit",
        lambda: int(15.75 * 2 ** 30) if sees == "a_v5es_limit" else None)
    n, heads = 64, 2
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    model = Alphafold2(dim=32, depth=2, heads=heads, dim_head=16,
                       predict_coords=True, structure_module_depth=1,
                       dtype=jnp.bfloat16, use_scan=True)
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, n), jnp.int32),
                             msa=jnp.zeros((1, n, n), jnp.int32)),
        jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda p: train.TrainState.create(
        apply_fn=model.apply, params=p, tx=train.adam(3e-4),
        rng=jax.random.PRNGKey(0)), params)
    batch = {"seq": sds((1, n), jnp.int32), "msa": sds((1, n, n), jnp.int32),
             "mask": sds((1, n), jnp.bool_),
             "msa_mask": sds((1, n, n), jnp.bool_),
             "coords": sds((1, n, 3), jnp.float32)}
    text = jax.jit(train.make_train_step(model)).lower(
        jax.tree.map(lambda s: sds(s.shape, s.dtype), state),
        batch).compile().as_text()

    assert not _logits_shaped(text, n * heads, n)
    booked = {}
    for line in text.splitlines():
        if "tpu_custom_call" in line and " custom-call(" in line:
            op_name, = re.findall(r'op_name="([^"]*)"', line)
            assert device.is_fused("custom-call", op_name), op_name
            site = [p for p in op_name.split("/")
                    if p in device._BY_COMPONENT][-1]
            part = "again" if device.is_remat(op_name) else \
                "backward" if "transpose(" in op_name else "forward"
            booked.setdefault((site, device.kernel_of(op_name)),
                              []).append(part)
    calls = ["again", "backward", "forward"] if sees == "no_limit" \
        else ["backward", "forward"]
    assert {k: sorted(v) for k, v in booked.items()} == {
        (site, kernel): calls for site, kernel in (
            ("triangle_attention_outgoing", "triangle_attention"),
            ("triangle_attention_ingoing", "triangle_attention"),
            ("row_attn", "msa_row_attention"),
            ("col_attn", "msa_col_attention"))}, booked


@pytest.mark.parametrize("program", tiny_programs.PROGRAMS)
def test_the_chips_program_names_its_kernels(program, one_chip,
                                             no_persistent_cache):
    """What `obs.device` joins device events to, in the text the chip's
    compiler writes: under 1% of the contractions carry no `op_name`, every
    one whose path passes through the trunk or the structure module resolves
    to a named kernel, and of the fusions the device runs as operations of
    their own over 99% find a name in the instruction table (the training
    step has two of 1,130 that the compiler made of nothing named)."""
    import re

    from alphafold2_tpu.obs import device

    text = tiny_programs.compile_tiny(program, one_chip,
                                      jnp.bfloat16).as_text()
    names = list(tiny_programs.contraction_op_names(text))
    assert len(names) > 100 and names.count(None) < 0.01 * len(names)
    kernels = set()
    for op_name in filter(None, names):
        parts = op_name.split("/")
        if "net" in parts or "structure_module" in parts:
            kernels.add(device.kernel_of(op_name))
    assert kernels == set(device.FOLD_KERNEL_NAMES)

    table = device.instruction_op_names(text)
    fused = set(re.findall(r"\bcalls=%?([\w.\-]+)", text))
    run = named = 0
    current = None
    for line in text.splitlines():
        head = device._COMPUTATION.match(line)
        if head and " = " not in line.split("(", 1)[0]:
            current = head.group(1)
        m = device._INSTRUCTION.match(line)
        if m and current not in fused and " fusion(" in line:
            run += 1
            named += m.group(2) in table
    assert run > 100 and named >= 0.99 * run, (named, run)


# -- the token decoder's causal attention ------------------------------------

@pytest.mark.parametrize("n", [8192, 1152])
def test_causal_attention_compiles_for_v5e(n, one_chip, no_persistent_cache):
    """The blocked causal kernel at the published widths of the benchmark's
    decoder (32 heads, 192-wide keys, 128-wide values, bf16), forward and
    backward: three Mosaic calls (forward, dq, dk and dv), at the cell's
    8,192 positions (blocks of 1,024) and at a length only the smallest
    block divides."""
    from alphafold2_tpu.ops.attention import causal_attention

    heads, dk, dv = 32, 192, 128
    shape = lambda width: jax.ShapeDtypeStruct(
        (1, heads, n, width), jnp.bfloat16, sharding=one_chip)

    def loss_gradients(q, k, v):
        return jax.grad(lambda *a: causal_attention(*a).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    # the suite's float32 default would ask Mosaic for float32 passes over
    # bf16 operands; the chip's default asks for none
    with jax.default_matmul_precision("default"):
        text = _compiled_kernel_text(loss_gradients,
                                     (shape(dk), shape(dk), shape(dv)))
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 3, len(calls)


@pytest.mark.parametrize("heads,window", [(12, None), (18, 512)],
                         ids=["full", "window"])
def test_grouped_causal_attention_compiles_for_v5e(heads, window, one_chip,
                                                   no_persistent_cache):
    """The same kernel with grouped queries at the laguna cell's shapes (2
    key heads held, groups of 6 under the causal mask and of 9 under a band
    of 512 keys, 128 wide, 8,192 positions, bf16), forward and backward:
    three Mosaic calls."""
    from alphafold2_tpu.ops.attention import causal_attention

    n, kv, width = 8192, 2, 128
    shape = lambda h: jax.ShapeDtypeStruct((1, h, n, width), jnp.bfloat16,
                                           sharding=one_chip)

    def loss_gradients(q, k, v):
        return jax.grad(lambda *a: causal_attention(
            *a, window=window).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("default"):
        text = _compiled_kernel_text(loss_gradients,
                                     (shape(heads), shape(kv), shape(kv)))
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 3, len(calls)


@pytest.mark.parametrize("live", [False, True],
                         ids=["every_tile", "live_tiles"])
def test_grouped_matmul_compiles_for_v5e(live, one_chip, no_persistent_cache):
    """The expert layer's grouped matmuls at the benchmark's decoder's own
    sizes (a buffer of 6 x 16,384 slots and a tile of 128 rows for each of
    16 held experts, 2,048 -> 768 -> 2,048, bf16), forward and backward: six
    Mosaic calls (y, dx and dw of each of two matmuls); with every tile
    computed, or with the count of live tiles handed in as data."""
    from alphafold2_tpu.ops.grouped_matmul import grouped_matmul

    rows, dim, width, held, tile = 6 * 16384 + 16 * 128, 2048, 768, 16, 128
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dtype, sharding=one_chip)

    def loss_gradients(x, w_in, w_out, tile_group, live_tiles):
        live_tiles = live_tiles if live else None

        def loss(x, w_in, w_out):
            hidden = grouped_matmul(x, w_in, tile_group, live_tiles)
            return grouped_matmul(hidden, w_out, tile_group,
                                  live_tiles).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(x, w_in, w_out)

    with jax.default_matmul_precision("default"):
        text = _compiled_kernel_text(loss_gradients, (
            shape(rows, dim), shape(held, dim, width),
            shape(held, width, dim), shape(rows // tile, dtype=jnp.int32),
            shape(dtype=jnp.int32)))
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 5, len(calls)


@pytest.mark.parametrize("tokens,dim,k,held,rows", [
    (8192, 3072, 10, 8, 66560), (16384, 2048, 6, 16, 100352)],
    ids=["laguna", "kanana"])
def test_expert_row_moves_compile_for_v5e(tokens, dim, k, held, rows,
                                          one_chip, no_persistent_cache):
    """The expert layer's row moves at the decoder cells' own sizes (a
    buffer of `rows` x `dim` in bf16, tiles of 128 rows, `held` experts,
    `k` slots a token), forward and backward, the index lists built from the
    routing's arrays: seven Mosaic calls (the table packed, dispatched,
    combined; the cotangent packed, dispatched scaled and dotted with the
    experts' output; the buffer's cotangent combined), and no XLA gather or
    scatter that moves rows of `dim` values: what XLA still gathers are
    scalars of the index lists."""
    import re

    from alphafold2_tpu.ops import expert_rows

    shape = lambda *s, dtype=jnp.int32: jax.ShapeDtypeStruct(
        s, dtype, sharding=one_chip)

    def moves(u, out, weights, token_of_row, slot_of_row, row_of_slot,
              expert_of_slot, group_start, live):
        plan = expert_rows.plan_rows(token_of_row, slot_of_row, row_of_slot,
                                     expert_of_slot, group_start, live, k=k,
                                     tile=128)

        def loss(u, out, weights):
            buf = expert_rows.dispatch_rows(u, plan)
            routed = expert_rows.combine_rows(out, weights, plan)
            return buf.astype(jnp.float32).sum() + routed.astype(
                jnp.float32).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(u, out, weights)

    with jax.default_matmul_precision("default"):
        text = _compiled_kernel_text(moves, (
            shape(tokens, dim, dtype=jnp.bfloat16),
            shape(rows, dim, dtype=jnp.bfloat16),
            shape(tokens, k, dtype=jnp.float32), shape(rows), shape(rows),
            shape(tokens * k), shape(tokens * k), shape(held), shape()))
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 7, len(calls)
    moved = [line for line in text.splitlines()
             if re.search(r"\b(gather|scatter)\(", line)
             and (" scatter(" in line or re.search(
                 rf"slice_sizes=\{{[^}}]*\b{dim}\b", line))]
    assert not moved, moved
