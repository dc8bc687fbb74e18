"""Model step (training): the whole step's share of the chip's bf16 peak.

Numerator: 3 x the contraction FLOPs of the plain reference's forward loss on
one crop (forward once, backward twice; recomputation not counted), times the
batch, times the steps the window completed. Denominator: the whole window
times the peak of `peaks.json`, times the chips used."""

import functools


@functools.lru_cache(maxsize=None)
def step_flops(config_items: tuple, crop: int) -> float:
    import jax
    import jax.numpy as jnp
    from benchmark import flops, reference, weights
    from benchmark.run import build_model
    cfg = dict(config_items)
    shapes = weights.param_shapes(build_model(cfg))
    batch = {"seq": jax.ShapeDtypeStruct((crop,), jnp.int32),
             "msa": jax.ShapeDtypeStruct((cfg["msa_depth"], crop), jnp.int32),
             "coords": jax.ShapeDtypeStruct((crop, 3), jnp.float32)}
    return 3.0 * flops.forward_flops(
        lambda p, b: reference.train_loss(p, cfg, b), shapes, batch)


def read(spans, snapshot, trace, cell):
    from benchmark.layer_metrics.fold_mfu import config_key
    run = cell["run"]
    if not cell.get("steps"):
        return None
    per_step = step_flops(config_key(run.config), run.traffic["crop"]) \
        * run.traffic["batch"]
    peak = run.peaks[run.devices[0].device_kind]["bf16_flops_per_s"]
    return 100.0 * per_step * cell["steps"] \
        / (cell["window_s"] * peak * len(run.devices))
