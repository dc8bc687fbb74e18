"""Block-level parity with the repository's own plain reference: each flax
block of the program against `benchmark/reference.py`'s function of the same
name, on the module's own parameter tree, forward and `jax.grad`.

The reference is float32 `jax.numpy` with no padding and no masks (masks are
`test_ops.py`'s); it imports nothing of the program. A case runs at a real
length, the smallest the fused kernel admits (`MIN_FUSED_LENGTH` positions,
heads of 8), so that the same inputs go through both doors of
`Attention.__call__`: XLA's einsum + softmax + einsum (what the CPU suite
takes) and the fused kernel, interpreted (what a TPU takes); the triangle
multiply on its own goes through `ops/triangle_multiply.py`'s fused stages the
same way. Through the
kernel the tolerances are the ones `test_ops.py` holds its float32 cases
to; everywhere else 1e-5 of the tensor's scale.

These are the tests a PR on one kernel runs first: a block, seconds, an
independent forward and an independent gradient.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.core.rigid import Rigid
from alphafold2_tpu.model import evoformer, primitives, structure
from alphafold2_tpu.ops import attention as ops_attn
from alphafold2_tpu.ops import triangle_multiply as ops_tm
from benchmark import reference

N = ops_attn.MIN_FUSED_LENGTH       # residues: the attended axis
ROWS = 4                            # alignment rows, or rows of a folded axis
DIM, HEADS, DIM_HEAD = 32, 2, 8
TRIANGLE_HIDDEN = 128               # of the triangle multiply on its own
DEPTH = 2                           # of the trunk and the structure module
NX = reference.Numerics("f32")

TOL = 1e-5
KERNEL_FORWARD_TOL = 1e-5           # test_ops.py, float32, forward
KERNEL_BACKWARD_TOL = 1e-4          # test_ops.py, float32, backward


def _axial(column: bool, edges: bool):
    module = primitives.AxialAttention(
        dim=DIM, heads=HEADS, dim_head=DIM_HEAD, row_attn=not column,
        col_attn=column, accept_edges=edges)
    shapes = [(N, ROWS, DIM) if column else (ROWS, N, DIM)]
    if edges:
        shapes.append((N, N, DIM))

    def program(params, x, e=None):
        return module.apply(params, x[None],
                            edges=None if e is None else e[None])[0]

    def plain(p, x, e=None):
        att = functools.partial(reference._axial_attention, NX, p,
                                edges=e, heads=HEADS, dim_head=DIM_HEAD)
        return att(x.swapaxes(0, 1)).swapaxes(0, 1) if column else att(x)
    return module, shapes, program, plain


def _triangle(outgoing: bool):
    # a hidden width the fused stages admit (a lane tile), so that the same
    # module goes through both doors
    module = primitives.TriangleMultiplicativeModule(
        dim=DIM, hidden_dim=TRIANGLE_HIDDEN,
        mix="outgoing" if outgoing else "ingoing")
    return (module, [(N, N, DIM)],
            lambda params, x: module.apply(params, x[None])[0],
            lambda p, x: reference._triangle_multiply(NX, p, x, outgoing))


def _feed_forward():
    module = primitives.FeedForward(dim=DIM)
    return (module, [(ROWS, N, DIM)],
            lambda params, x: module.apply(params, x[None])[0],
            lambda p, x: reference._feed_forward(NX, p, x))


def _outer_mean():
    module = primitives.OuterMean(dim=DIM)
    return (module, [(ROWS, N, DIM)],
            lambda params, m: module.apply(params, m[None])[0],
            lambda p, m: reference._outer_mean(NX, p, m))


def _pair_and_msa(module, plain):
    def program(params, x, m):
        x, m = module.apply(params, x[None], m[None])
        return x[0], m[0]
    return module, [(N, N, DIM), (ROWS, N, DIM)], program, plain


def _evoformer_block():
    return _pair_and_msa(
        evoformer.EvoformerBlock(dim=DIM, heads=HEADS, dim_head=DIM_HEAD),
        lambda p, x, m: reference._evoformer_block(NX, p, x, m, HEADS,
                                                   DIM_HEAD))


def _trunk():
    return _pair_and_msa(
        evoformer.Evoformer(dim=DIM, depth=DEPTH, heads=HEADS,
                            dim_head=DIM_HEAD),
        lambda p, x, m: reference._trunk(NX, p, x, m, HEADS, DIM_HEAD,
                                         remat=False))


def _ipa():
    module = structure.InvariantPointAttention(dim=DIM, heads=1,
                                               pairwise_repr_dim=DIM)

    def program(params, s, pair, quats, trans):
        frames = Rigid(quats[None], trans[None])
        return module.apply(params, s[None], pair[None], frames)[0]

    def plain(p, s, pair, quats, trans):
        return reference._ipa(NX, p, s, pair, reference._rotations(quats),
                              trans)
    return module, [(N, DIM), (N, N, DIM), (N, 4), (N, 3)], program, plain


def _structure_module():
    module = structure.StructureModule(dim=DIM, depth=DEPTH, heads=1)

    def program(params, s, pair):
        coords, single = module.apply(params, s[None], pair[None])
        return coords[0], single[0]
    return (module, [(N, DIM), (N, N, DIM)], program,
            lambda p, s, pair: reference._structure_module(NX, p, s, pair,
                                                           DEPTH))


# block -> (module, input shapes, program(params, *inputs),
#           plain(params["params"], *inputs)); inputs and outputs unbatched
TRUNK_BLOCKS = {
    "feed_forward": _feed_forward,
    "axial_row": functools.partial(_axial, False, False),
    "axial_row_edges": functools.partial(_axial, False, True),
    "axial_column": functools.partial(_axial, True, False),
    "axial_column_edges": functools.partial(_axial, True, True),
    "triangle_multiply_outgoing": functools.partial(_triangle, True),
    "triangle_multiply_ingoing": functools.partial(_triangle, False),
    "outer_mean": _outer_mean,
    "evoformer_block": _evoformer_block,
}
BLOCKS = dict(TRUNK_BLOCKS, trunk=_trunk, ipa=_ipa,
              structure_module=_structure_module)
HOLD_AN_ATTENTION = [name for name in TRUNK_BLOCKS
                     if name.startswith(("axial", "evoformer"))]
# the blocks whose triangle multiply the fused stages admit (inside the
# evoformer block it is `DIM` wide, under a lane tile: XLA's)
HOLD_A_FUSED_MULTIPLY = [name for name in TRUNK_BLOCKS
                         if name.startswith("triangle_multiply")]
THROUGH_KERNELS = HOLD_AN_ATTENTION + HOLD_A_FUSED_MULTIPLY


def _draw(tree, key):
    """Every leaf of the module's own tree drawn anew, well-conditioned: the
    modules initialise their output projections to zero and their gates to
    pass-through, which would hide most of a block's arithmetic."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    drawn = []
    for k, (path, leaf) in zip(jax.random.split(key, len(leaves)), leaves):
        noise = jax.random.normal(k, leaf.shape, jnp.float32)
        name = path[-1].key
        if name in ("kernel", "embedding"):
            # fan-in is the axis before last (the trunk stacks its layers)
            drawn.append(noise / np.sqrt(leaf.shape[-2]))
        else:
            drawn.append(0.1 * noise + (1.0 if name == "scale" else 0.0))
    return treedef.unflatten(drawn)


def _case(name):
    module, shapes, program, plain = BLOCKS[name]()
    keys = jax.random.split(jax.random.PRNGKey(len(name)), len(shapes) + 2)
    inputs = tuple(jax.random.normal(k, s, jnp.float32)
                   for k, s in zip(keys, shapes))
    batched = [t[None] for t in inputs]
    if name == "ipa":
        batched = batched[:2] + [Rigid(*batched[2:])]
    params = _draw(module.init(keys[-2], *batched), keys[-1])
    return params, inputs, program, plain


def _as_tuple(out):
    return out if isinstance(out, tuple) else (out,)


def _assert_close(got, want, tol, what):
    got_leaves, got_def = jax.tree_util.tree_flatten_with_path(got)
    want_leaves, want_def = jax.tree_util.tree_flatten_with_path(want)
    assert got_def == want_def, what
    for (path, a), (_, w) in zip(got_leaves, want_leaves):
        where = f"{what}{jax.tree_util.keystr(path)}"
        a, w = np.asarray(a), np.asarray(w)
        assert a.shape == w.shape and a.dtype == w.dtype == np.float32, where
        assert np.isfinite(a).all() and np.abs(w).max() > 0, where
        scale = max(1.0, float(np.abs(w).max()))
        assert float(np.abs(a - w).max()) <= tol * scale, where


@pytest.fixture
def door(request, monkeypatch):
    """Which path the blocks take: "xla", as on the CPU, or "kernel", the
    fused attention and the fused triangle multiply (interpreted), as on a
    TPU; and afterwards, that each block took the one it holds."""
    calls = {"attention": [], "triangle_multiply": []}

    def spy(kind, fn):
        def spied(*args, **kwargs):
            calls[kind].append(kwargs)
            return fn(*args, **dict(kwargs, interpret=True))
        return spied

    monkeypatch.setattr(ops_attn, "fused_attention_merged",
                        spy("attention", ops_attn.fused_attention_merged))
    monkeypatch.setattr(ops_tm, "fused_triangle_multiply",
                        spy("triangle_multiply",
                            ops_tm.fused_triangle_multiply))
    # a test's map is far under the size XLA keeps on the chip
    monkeypatch.setattr(ops_tm, "_MIN_PAIR_BYTES", 0)
    with ops_attn.pallas_attention(request.param == "kernel"):
        yield request.param
    name = request.node.callspec.params["name"]
    kernel = request.param == "kernel"
    assert {kind: bool(made) for kind, made in calls.items()} == {
        "attention": kernel and name in HOLD_AN_ATTENTION,
        "triangle_multiply": kernel and name in HOLD_A_FUSED_MULTIPLY}, calls


def _doors(names):
    """Every block through XLA, and those that hold an attention or a
    triangle multiply of a fused width through the kernels as well."""
    return [pytest.param(name, door, id=f"{name}-{door}")
            for door in ("xla", "kernel") for name in names
            if door == "xla" or name in THROUGH_KERNELS]


@pytest.mark.parametrize("name,door", _doors(BLOCKS), indirect=["door"])
def test_block_forward_matches_reference(name, door):
    params, inputs, program, plain = _case(name)
    got = jax.jit(program)(params, *inputs)
    want = jax.jit(plain)(params["params"], *inputs)
    _assert_close(_as_tuple(got), _as_tuple(want),
                  KERNEL_FORWARD_TOL if door == "kernel" else TOL, name)


@pytest.mark.parametrize("name,door", _doors(TRUNK_BLOCKS),
                         indirect=["door"])
def test_block_gradient_matches_reference(name, door):
    """`jax.grad` of one scalar of the block's output, with respect to the
    parameters and the inputs, against the reference's own gradient: with
    the door open the backward kernel answers to it."""
    params, inputs, program, plain = _case(name)
    outs = _as_tuple(jax.eval_shape(program, params, *inputs))
    weights = [jax.random.normal(jax.random.PRNGKey(7 + i), o.shape)
               for i, o in enumerate(outs)]

    def scalar_of(fn):
        return lambda p, *xs: sum(jnp.sum(o * w) for o, w in
                                  zip(_as_tuple(fn(p, *xs)), weights))
    argnums = tuple(range(len(inputs) + 1))
    got = jax.jit(jax.grad(scalar_of(program), argnums))(params, *inputs)
    want = jax.jit(jax.grad(scalar_of(plain), argnums))(params["params"],
                                                       *inputs)
    _assert_close((got[0]["params"],) + got[1:], want,
                  KERNEL_BACKWARD_TOL if door == "kernel" else TOL, name)
