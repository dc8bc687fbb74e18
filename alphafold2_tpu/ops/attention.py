"""Fused attention Pallas kernel for the Evoformer's axial attention.

The hot loop of the trunk is gated axial attention over rows/columns of
length <= crop (128-384) with an additive pair bias
(SURVEY.md §3.1; reference Attention at alphafold2.py:98-190). XLA already
fuses bias+softmax well, but it materializes the (B*L, H, N, N) logits in
HBM between the two matmuls; this kernel keeps the whole row block
resident in VMEM (crop-sized N fits comfortably: 384*64*4B per head-block)
and writes only the (N, D) output — one HBM round-trip instead of three.

Bias and masks are OPTIONAL and never materialized at full batch size in
HBM (round-1 ADVICE/VERDICT finding: the old contract forced callers to
allocate a dense fp32 (B, Nq, Nk) bias of zeros even with no bias/mask,
re-introducing exactly the O(N^2) HBM traffic the kernel exists to avoid):
- `bias` may be passed *unrepeated* — shape (Bb, Nq, Nk) with
  B == Bb//heads * bias_repeat * heads — and the BlockSpec index map
  replays it across the folded axial axis, so the axial row/col edge bias
  (b, h, N, N) is read as-is instead of being `jnp.repeat`-ed to
  (b*L, h, N, N);
- `q_mask`/`k_mask` are (B//heads, N) vectors; the (Nq, Nk) fill is
  computed inside the kernel in VMEM.

Shapes are the post-folding axial layout: q/k/v (B, N, D) with heads
folded innermost into B (B = batch*heads, head fastest). Softmax runs in
fp32 regardless of input dtype.

Selection: `use_pallas_attention(True)` flips the backend globally (the
flax modules read the flag at trace time); it requires a TPU backend —
under CPU tests the kernel runs in interpreter mode only inside its own
unit tests.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

# Large-negative fill for masked logits (matches model/primitives.py).
MASK_VALUE = -1e9

_BACKEND = {"pallas": False}


def use_pallas_attention(enabled: bool = True):
    """Globally select the Pallas fused-attention path."""
    _BACKEND["pallas"] = bool(enabled)


def pallas_attention_enabled() -> bool:
    return _BACKEND["pallas"]


@contextlib.contextmanager
def pallas_attention(enabled: bool = True):
    prev = _BACKEND["pallas"]
    use_pallas_attention(enabled)
    try:
        yield
    finally:
        _BACKEND["pallas"] = prev


def _attn_kernel(*refs, scale, has_bias, has_qm, has_km):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    idx = 3
    bias_ref = refs[idx] if has_bias else None
    idx += int(has_bias)
    qm_ref = refs[idx] if has_qm else None
    idx += int(has_qm)
    km_ref = refs[idx] if has_km else None
    idx += int(has_km)
    o_ref = refs[idx]

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (n, d)
    v = v_ref[0].astype(jnp.float32)                  # (n, d)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # (bq, n)
    if has_bias:
        logits = logits + bias_ref[0].astype(jnp.float32)
    if has_qm or has_km:
        # masks arrive as (1, len) f32 rows; the (bq, n) fill pattern is
        # their outer AND, built here in VMEM rather than in HBM upstream.
        # Reshape the f32 rows BEFORE comparing: Mosaic (v5e) cannot
        # reshape i1 vectors across the minor dim ("Insertion of minor dim
        # that is not a no-op only supported for 32-bit types").
        valid = jnp.ones(logits.shape, dtype=bool)
        if has_qm:
            valid &= qm_ref[0].reshape(-1, 1) > 0     # (bq, 1)
        if has_km:
            valid &= km_ref[0].reshape(1, -1) > 0     # (1, n)
        logits = jnp.where(valid, logits, MASK_VALUE)

    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    out = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) / denom
    o_ref[0] = out.astype(o_ref.dtype)


def _fused_attention_pallas(
    q: jnp.ndarray,              # (B, Nq, D)
    k: jnp.ndarray,              # (B, Nk, D)
    v: jnp.ndarray,              # (B, Nk, D)
    bias=None,                   # (Bb, Nq, Nk) additive, optional
    q_mask=None,                 # (B // heads, Nq) bool/0-1, optional
    k_mask=None,                 # (B // heads, Nk) bool/0-1, optional
    *,
    heads: int = 1,
    bias_repeat: int = 1,
    block_q: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """The raw pallas_call (forward only — no AD rule; use
    `fused_attention`)."""
    b, n, d = q.shape
    nk = k.shape[1]
    # largest power-of-two block <= block_q that divides n, so any sequence
    # length works (crops are normally multiples of 8 anyway)
    bq = min(block_q, n)
    while bq > 1 and n % bq != 0:
        bq //= 2
    block_q = bq if n % bq == 0 else 1
    scale = 1.0  # caller pre-scales q (matches model convention)

    grid = (b, n // block_q)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, nk, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, nk, d), lambda i, j: (i, 0, 0)),
    ]
    args = [q, k, v]

    if bias is not None:
        assert bias.shape[0] * bias_repeat == b, (bias.shape, bias_repeat, b)
        rh = bias_repeat * heads
        in_specs.append(pl.BlockSpec(
            (1, block_q, nk),
            lambda i, j: ((i // rh) * heads + i % heads, j, 0)))
        args.append(bias)
    if q_mask is not None:
        assert q_mask.shape == (b // heads, n), (q_mask.shape, b, heads, n)
        in_specs.append(pl.BlockSpec(
            (1, 1, block_q), lambda i, j: (i // heads, 0, j)))
        args.append(q_mask.astype(jnp.float32).reshape(b // heads, 1, n))
    if k_mask is not None:
        assert k_mask.shape == (b // heads, nk), (k_mask.shape, b, heads, nk)
        in_specs.append(pl.BlockSpec(
            (1, 1, nk), lambda i, j: (i // heads, 0, 0)))
        args.append(k_mask.astype(jnp.float32).reshape(b // heads, 1, nk))

    kernel = functools.partial(
        _attn_kernel, scale=scale, has_bias=bias is not None,
        has_qm=q_mask is not None, has_km=k_mask is not None)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n, d), q.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(*args)


@functools.lru_cache(maxsize=None)
def _fused_attention_vjp(heads, bias_repeat, block_q, interpret):
    """custom_vjp wrapper: Pallas forward, XLA-recompute backward.

    The kernel stores only the (N, D) output, so the backward recomputes
    attention through `attention_reference` under jax.vjp — the same
    recompute-in-backward trade `jax.checkpoint` makes, with XLA free to
    fuse the recomputation. Grads flow to q/k/v and the (unrepeated)
    bias; masks get symbolic-zero cotangents."""

    def run(q, k, v, bias, q_mask, k_mask):
        return _fused_attention_pallas(
            q, k, v, bias, q_mask, k_mask, heads=heads,
            bias_repeat=bias_repeat, block_q=block_q, interpret=interpret)

    f = jax.custom_vjp(run)

    def fwd(q, k, v, bias, q_mask, k_mask):
        return run(q, k, v, bias, q_mask, k_mask), \
            (q, k, v, bias, q_mask, k_mask)

    def bwd(res, g):
        import numpy as np
        q, k, v, bias, q_mask, k_mask = res
        if bias is None:
            ref = lambda q, k, v: attention_reference(
                q, k, v, q_mask=q_mask, k_mask=k_mask, heads=heads,
                bias_repeat=bias_repeat)
            _, vjp = jax.vjp(ref, q, k, v)
            dq, dk, dv = vjp(g)
            dbias = None
        else:
            ref = lambda q, k, v, bias: attention_reference(
                q, k, v, bias=bias, q_mask=q_mask, k_mask=k_mask,
                heads=heads, bias_repeat=bias_repeat)
            _, vjp = jax.vjp(ref, q, k, v, bias)
            dq, dk, dv, dbias = vjp(g)

        def zero_cot(x):
            if x is None:
                return None
            if jnp.issubdtype(x.dtype, jnp.inexact):
                return jnp.zeros_like(x)
            return np.zeros(np.shape(x), dtype=jax.dtypes.float0)

        return dq, dk, dv, dbias, zero_cot(q_mask), zero_cot(k_mask)

    f.defvjp(fwd, bwd)
    return f


# a Pallas call is no flax module: the scope is the one name its
# instructions carry of their own (the enclosing module decides the kernel,
# obs/device.py)
@jax.named_scope("fused_attention")
def fused_attention(
    q: jnp.ndarray,              # (B, Nq, D)
    k: jnp.ndarray,              # (B, Nk, D)
    v: jnp.ndarray,              # (B, Nk, D)
    bias=None,                   # (Bb, Nq, Nk) additive, optional
    q_mask=None,                 # (B // heads, Nq) bool/0-1, optional
    k_mask=None,                 # (B // heads, Nk) bool/0-1, optional
    *,
    heads: int = 1,
    bias_repeat: int = 1,
    block_q: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused bias+mask+softmax+matmul attention (differentiable).

    Batch layout: B = batch * bias_repeat * heads with head fastest, i.e.
    flat index i = (batch * bias_repeat + fold) * heads + head. `bias`
    covers (batch, heads) and is replayed over the folded middle axis via
    the index map; masks cover (batch * bias_repeat) and are shared
    across heads. N and D should be multiples of the TPU lane/sublane
    tiling (128 / 8); callers pad crops accordingly.

    Degenerate tiles (Nq or Nk < 8 — e.g. the 1x1 pair maps the model's
    init-time branch coverage traces) fall back to the XLA reference:
    Mosaic lowers their dots to vector multi_reductions with loop-carried
    accumulators and refuses ("only constant accumulators supported",
    observed on-chip r05), and such shapes gain nothing from the kernel.
    """
    n, nk = q.shape[1], k.shape[1]
    if n < 8 or nk < 8:
        return attention_reference(q, k, v, bias=bias, q_mask=q_mask,
                                   k_mask=k_mask, heads=heads,
                                   bias_repeat=bias_repeat)
    return _fused_attention_vjp(heads, bias_repeat, block_q, interpret)(
        q, k, v, bias, q_mask, k_mask)


def attention_reference(q, k, v, bias=None, q_mask=None, k_mask=None,
                        *, heads=1, bias_repeat=1):
    """XLA reference of the same contract (used for tests and fallback)."""
    logits = jnp.einsum("bnd,bmd->bnm", q, k).astype(jnp.float32)
    if bias is not None:
        logits = logits + jnp.repeat(
            bias.astype(jnp.float32).reshape(
                -1, heads, *bias.shape[1:]),
            bias_repeat, axis=0).reshape(logits.shape)
    valid = None
    if q_mask is not None:
        valid = (q_mask > 0)[:, :, None]
    if k_mask is not None:
        km = (k_mask > 0)[:, None, :]
        valid = km if valid is None else valid & km
    if valid is not None:
        valid = jnp.broadcast_to(
            valid, (valid.shape[0],) + logits.shape[1:])
        valid = jnp.repeat(valid, heads, axis=0)
        logits = jnp.where(valid, logits, MASK_VALUE)
    attn = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnm,bmd->bnd", attn.astype(q.dtype), v)
