"""Contraction FLOPs from a jaxpr walk: the benchmark's own copy.

Copied from `alphafold2_tpu/utils/flops.py` (PR 25) so that a later PR can
change the program and not the yardstick. `dot_general` and convolution
FLOPs only (2 x multiply-adds); `lax.scan` bodies times their trip count;
`lax.cond` charges its dearest branch; a `while` body one trip; remat,
`pjit` and custom-derivative calls once. Elementwise, softmax and
normalization work is left out, by the usual MFU convention, so a share of
peak computed from this count is of model FLOPs and cannot be raised by
recomputation.

The benchmark counts its PLAIN REFERENCE's forward pass with it
(`reference.py`), never the program's own trace: what the algorithm needs
does not change when the program does.
"""

from __future__ import annotations

import jax
from jax.extend import core as jax_core


def _prod(xs) -> float:
    out = 1.0
    for x in xs:
        out *= float(x)
    return out


def _dot_general_flops(eqn) -> float:
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = _prod(lhs[i] for i in lb)
    k = _prod(lhs[i] for i in lc)
    m = _prod(d for i, d in enumerate(lhs) if i not in set(lc) | set(lb))
    n = _prod(d for i, d in enumerate(rhs) if i not in set(rc) | set(_rb))
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval.shape
    kernel = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    # kernel's in-channel dim already holds C_in/groups
    rhs_spec = dn.rhs_spec  # (out_c, in_c, *spatial) positions
    in_c = kernel[rhs_spec[1]]
    spatial = _prod(kernel[i] for i in rhs_spec[2:])
    return 2.0 * _prod(out) * in_c * spatial


def _iter_sub_jaxprs(params):
    for v in params.values():
        if isinstance(v, jax_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jax_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jax_core.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jax_core.Jaxpr):
                    yield x


def _shard_map_multiplier(params) -> float:
    """Number of devices doing DISTINCT work in a shard_map: the product
    of the sizes of mesh axes that actually appear in an in/out spec.
    Axes the operands are not sharded over hold replicas — replicated
    compute is hardware work, not model FLOPs, so it must not inflate
    the MFU numerator (e.g. a batch too small to tile the data axis
    makes the ring kernel drop that axis from its specs)."""
    used = set()
    for spec in tuple(params.get("in_specs", ())) + \
            tuple(params.get("out_specs", ())):
        for entry in tuple(spec):
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                used.update(entry)
            else:
                used.add(entry)
    try:
        shape = dict(params["mesh"].shape)
    except Exception:
        return 1.0
    return _prod(shape.get(a, 1) for a in used)


def count_jaxpr_flops(jaxpr) -> float:
    """Contraction FLOPs (dot_general + conv) of one jaxpr, recursive."""
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "scan":
            total += eqn.params["length"] * count_jaxpr_flops(
                eqn.params["jaxpr"].jaxpr)
        elif name == "while":
            # no static trip count: charge one iteration (documented)
            total += count_jaxpr_flops(eqn.params["body_jaxpr"].jaxpr)
        elif name == "cond":
            total += max(count_jaxpr_flops(b.jaxpr)
                         for b in eqn.params["branches"])
        elif name == "shard_map":
            inner = sum(count_jaxpr_flops(s)
                        for s in _iter_sub_jaxprs(eqn.params))
            total += _shard_map_multiplier(eqn.params) * inner
        else:
            # pjit / remat(checkpoint) / custom_vjp / custom_jvp / core
            # calls: count their sub-jaxpr once
            for sub in _iter_sub_jaxprs(eqn.params):
                total += count_jaxpr_flops(sub)
    return total


def forward_flops(fn, *args, **kwargs) -> float:
    """Contraction FLOPs of fn's forward pass (traced, never executed)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return count_jaxpr_flops(closed.jaxpr)
