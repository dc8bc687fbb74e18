"""Model step (serving): the whole fold's share of the chip's bf16 peak
(`fold_mfu` in the bulk cell, `fold_mfu.online` in the online one).

Numerator: the contraction FLOPs the plain reference's forward pass needs for
one chain at each ok fold's BUCKET length (what ran, padding included; never
more), counted by the benchmark's own jaxpr walk. Denominator: the whole
window times the peak of `peaks.json`, times the chips used."""

import functools


@functools.lru_cache(maxsize=None)
def fold_flops(config_items: tuple, length: int) -> float:
    import jax
    import jax.numpy as jnp
    from benchmark import flops, reference, weights
    from benchmark.run import build_model
    cfg = dict(config_items)
    shapes = weights.param_shapes(build_model(cfg), 16, 4)
    seq = jax.ShapeDtypeStruct((length,), jnp.int32)
    msa = jax.ShapeDtypeStruct((cfg["msa_depth"], length), jnp.int32)
    return flops.forward_flops(
        lambda p, s, m: reference.fold(p, cfg, s, m, cfg["num_recycles"]),
        shapes, seq, msa)


def config_key(config: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, str, bool))))


def read(spans, snapshot, trace, cell):
    run = cell["run"]
    if not cell.get("folds"):
        return None
    items = config_key(run.config)
    total = sum(fold_flops(items, bucket) for _, bucket in cell["folds"])
    peak = run.peaks[run.devices[0].device_kind]["bf16_flops_per_s"]
    return 100.0 * total / (cell["window_s"] * peak * len(run.devices))
