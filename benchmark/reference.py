"""The benchmark's plain reference: the model's forward pass (and, for the
training cell, its loss) in straightforward `jax.numpy`.

It imports nothing of the program. It reads the weights the benchmark itself
drew from the seed (`weights.py`), by the names the parameter tree gives
them, and computes in float32 with every contraction at
`jax.lax.Precision.HIGHEST`. It runs at a request's REAL length with no
padding and no masks, so it also holds the scheduler's bucketing, padding,
masking and unpadding to account. Row-wise parts (attention over a folded
axis, the pair feed-forward) run in blocks of rows so that a 640-residue fold
fits beside nothing else on one chip.

`Numerics(kind)` is the one switch: "f32" is the reference; "bf16" and "fp8"
round every contraction's inputs to that type first, in the backward pass
too (fp8 = e4m3 values and e5m2 gradients, each tensor at a scale of its own,
accumulated in float32), and are the controls that `correct` has to fail
(`fold_check.py`, `train_check.py`, PERF.md).

Departures from the published description, all taken from this repository's
stated semantics so that the comparison is of like with like:
- GELU is the tanh approximation (`jax.nn.gelu`'s default), not erf;
- the outer-product mean divides by the number of alignment rows (the
  program's masked mean adds 1e-5 to that count);
- the extra-MSA stack and the template stack get no input in any cell and
  are left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 32          # rows of a folded axis computed at once


class Numerics:
    """How contractions are computed: "f32" (reference), "bf16", "fp8".

    A lower precision rounds the two inputs of every contraction, forward and
    backward: in a gradient the cotangent that enters a contraction's two
    transposes is rounded as well (e5m2 for fp8, the type fp8 training keeps
    gradients in; e4m3 holds values). Every fp8 tensor is rounded at a scale
    of its own, so no gradient underflows for want of one."""

    _TYPES = {"bf16": (jnp.bfloat16, jnp.bfloat16),
              "fp8": (jnp.float8_e4m3fn, jnp.float8_e5m2)}

    def __init__(self, kind: str = "f32"):
        if kind not in ("f32", *self._TYPES):
            raise ValueError(f"unknown numerics {kind!r}")
        self.kind = kind

    def _round(self, x, cotangent=False):
        low = self._TYPES[self.kind][int(cotangent)]
        if self.kind == "bf16":
            return x.astype(low).astype(jnp.float32)
        # scale each tensor so that its largest value is the type's largest
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) \
            / float(jnp.finfo(low).max)
        return (x / scale).astype(low).astype(jnp.float32) * scale

    def ein(self, eq: str, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        contract = lambda x, y: jnp.einsum(eq, x, y, precision=_HIGHEST)
        if self.kind == "f32":
            return contract(a, b)

        @jax.custom_vjp
        def rounded(a, b):
            return contract(self._round(a), self._round(b))

        def forward(a, b):
            ra, rb = self._round(a), self._round(b)
            return contract(ra, rb), (ra, rb)

        def backward(inputs, g):
            return jax.vjp(contract, *inputs)[1](self._round(g, True))
        rounded.defvjp(forward, backward)
        return rounded(a, b)


# -- small parts ------------------------------------------------------------

def _dense(nx, p, x):
    y = nx.ein("...i,io->...o", x, p["kernel"])
    return y + p["bias"] if "bias" in p else y


def _layer_norm(p, x, eps=1e-5):
    while "scale" not in p:             # the program nests its LayerNorms
        p = p["LayerNorm_0"]
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _row_blocks(fn, x, block=ROW_BLOCK):
    """fn over blocks of x's leading axis (rows are independent). Each
    block is rematerialized in a backward pass, so that a gradient keeps a
    block's inputs and not its attention maps."""
    fn = jax.checkpoint(fn)
    rows = x.shape[0]
    if rows <= block:
        return fn(x)
    pad = (-rows) % block
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    out = jax.lax.map(fn, xp.reshape(-1, block, *x.shape[1:]))
    return out.reshape(-1, *out.shape[2:])[:rows]


def _feed_forward(nx, p, x):
    def rows(xb):
        h = _dense(nx, p["Dense_0"], _layer_norm(p["LayerNorm_0"], xb))
        a, gates = jnp.split(h, 2, axis=-1)
        return _dense(nx, p["Dense_1"], a * jax.nn.gelu(gates))
    return _row_blocks(rows, x)


def _axial_attention(nx, p, x, edges=None, heads=8, dim_head=64):
    """Gated attention along axis 1 of x (R, n, d), one row at a time;
    `edges` (n, n, d_pair), where given, biases every row's logits."""
    bias = None
    if edges is not None:
        bias = jnp.moveaxis(
            nx.ein("ijd,dh->ijh", edges, p["edges_to_attn_bias"]["kernel"]),
            -1, 0)                                          # (h, q, k)
    a = p["attn"]

    def rows(xb):
        r, n, _ = xb.shape
        xn = _layer_norm(p["LayerNorm_0"], xb)
        q = _dense(nx, a["to_q"], xn).reshape(r, n, heads, dim_head)
        kv = _dense(nx, a["to_kv"], xn)
        k, v = (t.reshape(r, n, heads, dim_head)
                for t in jnp.split(kv, 2, axis=-1))
        logits = nx.ein("rqhd,rkhd->rhqk", q * dim_head ** -0.5, k)
        if bias is not None:
            logits = logits + bias[None]
        attn = jax.nn.softmax(logits, axis=-1)
        out = nx.ein("rhqk,rkhd->rqhd", attn, v).reshape(r, n, -1)
        out = out * jax.nn.sigmoid(_dense(nx, a["gating"], xn))
        return _dense(nx, a["to_out"], out)
    return _row_blocks(rows, x)


def _triangle_multiply(nx, p, x, outgoing: bool):
    xn = _layer_norm(p["LayerNorm_0"], x)
    gate = lambda name: jax.nn.sigmoid(_dense(nx, p[name], xn))
    left = _dense(nx, p["left_proj"], xn) * gate("left_gate")
    right = _dense(nx, p["right_proj"], xn) * gate("right_gate")
    if outgoing:
        out = nx.ein("ikd,jkd->ijd", left, right)
    else:
        out = nx.ein("kjd,kid->ijd", left, right)
    out = _layer_norm(p["LayerNorm_1"], out) * gate("out_gate")
    return _dense(nx, p["to_out"], out)


def _outer_mean(nx, p, m):
    mn = _layer_norm(p["LayerNorm_0"], m)
    left = _dense(nx, p["left_proj"], mn)
    right = _dense(nx, p["right_proj"], mn)
    outer = nx.ein("mid,mjd->ijd", left, right) / m.shape[0]
    return _dense(nx, p["proj_out"], outer)


def _evoformer_block(nx, p, x, m, heads, dim_head):
    """x: pair (n, n, d); m: MSA (rows, n, d)."""
    att = lambda q, t, e=None: _axial_attention(nx, q, t, e, heads, dim_head)
    ma = p["msa_attn"]
    m = att(ma["row_attn"], m, x) + m
    m = att(ma["col_attn"], m.swapaxes(0, 1)).swapaxes(0, 1) + m
    m = _feed_forward(nx, p["msa_ff"], m) + m
    pa = p["attn"]
    x = x + _outer_mean(nx, pa["outer_mean"], m)
    x = _triangle_multiply(nx, pa["triangle_multiply_outgoing"], x, True) + x
    x = _triangle_multiply(nx, pa["triangle_multiply_ingoing"], x, False) + x
    x = att(pa["triangle_attention_outgoing"], x, x) + x
    x = att(pa["triangle_attention_ingoing"], x.swapaxes(0, 1),
            x).swapaxes(0, 1) + x
    x = _feed_forward(nx, p["ff"], x) + x
    return x, m


def _stacked_layers(net):
    """The trunk's layers with a leading depth axis, however the program
    stores them (scanned: net/layers/block; unrolled: net/layers_<i>)."""
    if "layers" in net:
        return net["layers"]["block"]
    names = sorted((k for k in net if k.startswith("layers_")),
                   key=lambda k: int(k.split("_")[1]))
    return jax.tree.map(lambda *ls: jnp.stack(ls), *[net[k] for k in names])


def _trunk(nx, net, x, m, heads, dim_head, remat):
    block = lambda p, x, m: _evoformer_block(nx, p, x, m, heads, dim_head)
    if remat:
        block = jax.checkpoint(block)

    def body(carry, p):
        return block(p, *carry), None
    (x, m), _ = jax.lax.scan(body, (x, m), _stacked_layers(net))
    return x, m


# -- structure module -------------------------------------------------------

def _quat_multiply(a, b):
    aw, ax, ay, az = jnp.moveaxis(a, -1, 0)
    bw, bx, by, bz = jnp.moveaxis(b, -1, 0)
    return jnp.stack([aw * bw - ax * bx - ay * by - az * bz,
                      aw * bx + ax * bw + ay * bz - az * by,
                      aw * by - ax * bz + ay * bw + az * bx,
                      aw * bz + ax * by - ay * bx + az * bw], axis=-1)


def _rotations(q):
    """Row-vector rotation matrices (v @ R) of (n, 4) wxyz quaternions."""
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    w, x, y, z = jnp.moveaxis(q, -1, 0)
    return jnp.stack([
        jnp.stack([1 - 2 * (y * y + z * z), 2 * (x * y - z * w),
                   2 * (x * z + y * w)], -1),
        jnp.stack([2 * (x * y + z * w), 1 - 2 * (x * x + z * z),
                   2 * (y * z - x * w)], -1),
        jnp.stack([2 * (x * z - y * w), 2 * (y * z + x * w),
                   1 - 2 * (x * x + y * y)], -1)], -2)


def _ipa(nx, p, s, pair, rot, trans):
    """Invariant point attention, one head (AF2 algorithm 22).
    s: (n, d); pair: (n, n, d); rot: (n, 3, 3); trans: (n, 3)."""
    n = s.shape[0]
    d_k, d_v, p_k, p_v = 16, 16, 4, 8
    lin = lambda name: nx.ein("nd,do->no", s, p[name]["kernel"])
    q_s, k_s, v_s = lin("to_scalar_q"), lin("to_scalar_k"), lin("to_scalar_v")
    to_global = lambda t: jnp.einsum(
        "npc,ncd->npd", t, rot, precision=_HIGHEST) + trans[:, None]
    q_p = to_global(lin("to_point_q").reshape(n, p_k, 3))
    k_p = to_global(lin("to_point_k").reshape(n, p_k, 3))
    v_p = to_global(lin("to_point_v").reshape(n, p_v, 3))

    logits = nx.ein("id,jd->ij", q_s, k_s) * d_k ** -0.5
    gamma = jax.nn.softplus(p["point_weights"])[0]
    d2 = jnp.sum((q_p[:, None] - k_p[None, :]) ** 2, axis=(-1, -2))
    logits = logits - 0.5 * (2.0 / (9.0 * p_k)) ** 0.5 * gamma * d2
    logits = logits + nx.ein("ijd,do->ijo", pair,
                             p["pairwise_to_bias"]["kernel"])[..., 0]
    attn = jax.nn.softmax(logits * (1.0 / 3.0) ** 0.5, axis=-1)

    out_s = nx.ein("ij,jd->id", attn, v_s)
    out_pg = nx.ein("ij,jpc->ipc", attn, v_p)
    out_p = jnp.einsum("npd,ncd->npc", out_pg - trans[:, None], rot,
                       precision=_HIGHEST)
    out_norm = jnp.sqrt(jnp.sum(out_p ** 2, -1) + 1e-8)
    out_pair = nx.ein("ij,ijd->id", attn, pair)
    out = jnp.concatenate([out_s, out_p.reshape(n, -1), out_norm, out_pair],
                          axis=-1)
    return _dense(nx, p["to_out"], out)


def _structure_module(nx, p, single, pair, depth):
    n = single.shape[0]
    quats = jnp.zeros((n, 4), jnp.float32).at[:, 0].set(1.0)
    trans = jnp.zeros((n, 3), jnp.float32)
    blk = p["ipa_block"]
    x = single
    for i in range(depth):
        rot_q = quats if i == depth - 1 else jax.lax.stop_gradient(quats)
        rot = _rotations(rot_q)
        x = _ipa(nx, blk["attn"], x, pair, rot, trans) + x
        x = _layer_norm(blk["attn_norm"], x)
        ff = jax.nn.relu(_dense(nx, blk["ff_0"], x))
        ff = jax.nn.relu(_dense(nx, blk["ff_1"], ff))
        x = _layer_norm(blk["ff_norm"], x + _dense(nx, blk["ff_2"], ff))
        update = _dense(nx, p["to_quaternion_update"], x)
        dq = jnp.concatenate([jnp.ones((n, 1), jnp.float32), update[:, :3]],
                             axis=-1)
        quats = _quat_multiply(quats, dq)
        trans = trans + jnp.einsum("nc,ncd->nd", update[:, 3:], rot,
                                   precision=_HIGHEST)
    points = _dense(nx, p["to_points"], x)
    coords = jnp.einsum("nc,ncd->nd", points, _rotations(quats),
                        precision=_HIGHEST) + trans
    return coords, x


# -- the model --------------------------------------------------------------

def one_pass(nx, params, cfg, seq, msa, recyclables=None, remat=False):
    """One trunk + structure pass over one unpadded chain.
    seq: (n,) int; msa: (rows, n) int (already noised, when training).
    Returns a dict: coords (n, 3), confidence_raw (n,), distogram (n, n, 37),
    msa_repr (rows, n, d) and the three recyclables."""
    p = params["params"]
    heads, dim_head = cfg["heads"], cfg["dim_head"]
    n = seq.shape[0]
    tok = p["token_emb"]["embedding"]
    single = tok[seq]
    m = tok[msa] + single[None]
    left, right = jnp.split(_dense(nx, p["to_pairwise_repr"], single), 2, -1)
    rel = jnp.clip(jnp.arange(n)[:, None] - jnp.arange(n)[None, :],
                   -32, 32) + 32
    x = left[:, None] + right[None, :] + p["pos_emb"]["embedding"][rel]

    if recyclables is not None:
        r_coords, r_single, r_pair = recyclables
        m = m.at[0].add(_layer_norm(p["recycling_msa_norm"], r_single))
        x = x + _layer_norm(p["recycling_pairwise_norm"], r_pair)
        dists = jnp.sqrt(jnp.maximum(jnp.sum(
            (r_coords[:, None] - r_coords[None, :]) ** 2, -1), 1e-12))
        bounds = jnp.linspace(2.0, 20.0, 32)[:-1]
        x = x + p["recycling_distance_embed"]["embedding"][
            jnp.sum(dists[..., None] > bounds, -1)]

    x, m = _trunk(nx, p["net"], x, m, heads, dim_head, remat)

    sym = (x + x.swapaxes(0, 1)) * 0.5
    distogram = _dense(nx, p["to_distogram_logits"],
                       _layer_norm(p["distogram_norm"], sym))
    single_repr = _dense(nx, p["msa_to_single_repr_dim"], m[0])
    pair_repr = _dense(nx, p["trunk_to_pairwise_repr_dim"], x)
    coords, single_out = _structure_module(
        nx, p["structure_module"], single_repr, pair_repr,
        cfg["structure_module_depth"])
    conf = _dense(nx, p["lddt_linear"], single_out)[:, 0]
    stop = jax.lax.stop_gradient
    return {"coords": coords, "confidence_raw": conf, "distogram": distogram,
            "msa_repr": m,
            "recyclables": (stop(coords), stop(m[0]), stop(pair_repr))}


def fold(params, cfg, seq, msa, num_recycles: int, kind: str = "f32"):
    """coords (n, 3) and confidence (n,) in [0, 1] of one chain after
    1 + num_recycles passes, each fed the one before."""
    nx = Numerics(kind)
    out = one_pass(nx, params, cfg, seq, msa)

    def body(carry, _):
        rec, _, _ = carry
        o = one_pass(nx, params, cfg, seq, msa, recyclables=rec)
        return (o["recyclables"], o["coords"], o["confidence_raw"]), None

    if num_recycles > 0:
        (_, coords, conf), _ = jax.lax.scan(
            body, (out["recyclables"], out["coords"], out["confidence_raw"]),
            None, length=num_recycles)
    else:
        coords, conf = out["coords"], out["confidence_raw"]
    return coords, jax.nn.sigmoid(conf)


# -- training: the loss of `train.make_train_step` --------------------------

def _cdist(x):
    d2 = jnp.sum((x[:, None] - x[None, :]) ** 2, -1)
    return jnp.sqrt(jnp.maximum(d2, 1e-12))


def _kabsch_rmsd(pred, true):
    """RMSD after the rotation that best lays `pred` on `true` (both
    centred; the rotation is a constant in the derivative)."""
    x = pred - pred.mean(0, keepdims=True)
    y = true - true.mean(0, keepdims=True)
    cov = jax.lax.stop_gradient(
        jnp.einsum("ni,nj->ij", x, y, precision=_HIGHEST))
    u, _, vt = jnp.linalg.svd(cov, full_matrices=False)
    flip = jnp.where(jnp.linalg.det(u) * jnp.linalg.det(vt) < 0, -1.0, 1.0)
    u = u.at[:, -1].multiply(flip)
    rot = jnp.einsum("ij,jk->ik", u, vt, precision=_HIGHEST)
    aligned = jnp.einsum("ni,ij->nj", x, rot, precision=_HIGHEST)
    return jnp.sqrt(jnp.mean((aligned - y) ** 2))


def _distogram_weights(probs):
    """Confidence weight of each pair from its predicted distance
    distribution: 1 / (1 + its standard deviation), 0 on the diagonal and
    where the mean lies beyond the last real bin."""
    bins = jnp.linspace(2.0, 20.0, 37)
    centres = (bins - 0.5 * (bins[2] - bins[1])).at[0].set(1.5)
    centres = centres.at[-1].set(1.33 * bins[-1])
    mass = probs.sum(-1) + 1e-7
    off_diagonal = 1.0 - jnp.eye(probs.shape[0])
    mean = (probs * centres).sum(-1) / mass
    valid = (mean <= bins[-2]).astype(jnp.float32)
    mean = mean * off_diagonal
    var = (probs * (centres - mean[..., None]) ** 2).sum(-1) / mass
    weights = valid / (1.0 + jnp.sqrt(jnp.maximum(var, 0.0)))
    return jnp.nan_to_num(weights) * off_diagonal


def _lddt_ca(true, pred):
    n = true.shape[0]
    dt, dp = _cdist(true), _cdist(pred)
    incl = (dt < 15.0).astype(jnp.float32) * (1.0 - jnp.eye(n))
    diff = jnp.abs(dp - dt)
    ok = (diff[..., None] < jnp.asarray([0.5, 1.0, 2.0, 4.0])).astype(
        jnp.float32).mean(-1)
    return (ok * incl).sum(-1) / jnp.maximum(incl.sum(-1), 1e-9)


def train_loss(params, cfg, batch, kind: str = "f32"):
    """The training step's loss on one unpadded crop, with MSA-MLM noising
    off (`mlm_mask_prob` 0 in the configuration: its draws come from the
    program's own random stream, which no independent reference can follow):
    Kabsch RMSD, plus the squared error of the distance matrix weighted by
    the distogram's confidence, plus the squared error of the confidence
    head against the prediction's own CA lDDT. batch: seq (n,), msa
    (rows, n), coords (n, 3)."""
    nx = Numerics(kind)
    out = one_pass(nx, params, cfg, batch["seq"], batch["msa"], remat=True)
    pred, true = out["coords"], batch["coords"].astype(jnp.float32)
    weights = _distogram_weights(jax.nn.softmax(out["distogram"], axis=-1))
    distmat = ((_cdist(pred) - _cdist(true)) ** 2 * weights).sum() \
        / jnp.maximum(weights.sum(), 1.0)
    target = jax.lax.stop_gradient(_lddt_ca(true, pred))
    confidence = jnp.mean(
        (jax.nn.sigmoid(out["confidence_raw"]) - target) ** 2)
    return _kabsch_rmsd(pred, true) + distmat + confidence
