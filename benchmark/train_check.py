"""`correct` for the training cell: the plain reference follows the
program's first three steps from the same weights on the same batches, in
float32, with Adam written out by hand. Four numbers are read; a number is
COMPARED where the traffic file gives it a limit and printed on an earlier
line where it does not (PERF.md gives the readings behind each):

- `loss_gap`: the first step's |loss - reference| / reference. It has no
  limit: the fp8 control moves the first loss no more than bf16 does;
- `later_loss_gap`: the larger of the next two steps'. Adam's first updates
  are all but sign(gradient) x the learning rate, so an entry whose gradient
  is near nought moves a whole step either way on a rounding, and sound runs
  swing from 0.0005 to 0.015; its limit lies under what a state left
  unchanged reads, not under the fp8 control (that is `grad_gap`'s to fail);
- `grad_gap`: the first gradient as the optimizer got it (Adam's first
  moment after one step, over 1 - b1), by the worst leaf: the gap between
  the program's norm and the reference's norm of that leaf, against the
  reference's norm of that leaf or of the median leaf, whichever is larger;
- `update_gap`: the same measure on the parameters' change after the three
  steps. Leaves whose first gradient is nought to rounding in the reference
  (under a thousandth of the median leaf's: the MLM head with noising off, the
  template and extra-MSA stacks that get no input) move under Adam by
  round-off alone and are left out of this one.
"""

from __future__ import annotations

import functools

import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8           # optax.adam's defaults


def _leaf_norms(tree) -> np.ndarray:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: jnp.stack(
        [jnp.linalg.norm(x.astype(jnp.float32).ravel())
         for x in jax.tree.leaves(t)]))(tree)
    return np.asarray(jax.device_get(norms), np.float64)


def first_gradient_norms(opt_state) -> np.ndarray:
    """Per-leaf norm of the first gradient, from Adam's state after one
    step (mu_1 = (1 - b1) g_1), wherever the optimizer keeps it."""
    import jax
    holders = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    return _leaf_norms(holders[0].mu) / (1.0 - B1)


def change_norms(params, params_before) -> np.ndarray:
    import jax
    return _leaf_norms(jax.tree.map(lambda a, b: a - b, params,
                                    params_before))


def worst_leaf_gap(got: np.ndarray, ref: np.ndarray, keep=None) -> float:
    scale = np.maximum(ref, np.median(ref[ref > 0]) if (ref > 0).any()
                       else 1.0)
    gap = np.abs(got - ref) / scale
    if keep is not None:
        gap = gap[keep]
    return float(gap.max())


def reference_step(cfg, learning_rate: float, kind: str):
    """(params, mu, nu), batch, step number -> the same after one step of
    Adam on the reference's gradient, the loss, and the gradient."""
    import jax
    import jax.numpy as jnp
    from benchmark import reference

    def step(state, batch, count):
        p, mu, nu = state
        loss, g = jax.value_and_grad(reference.train_loss)(
            p, cfg, batch, kind)
        mu = jax.tree.map(lambda m, x: B1 * m + (1 - B1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: B2 * v + (1 - B2) * x * x, nu, g)
        c1, c2 = 1 - B1 ** count, 1 - B2 ** count      # count is traced
        p = jax.tree.map(
            lambda w, m, v: w - learning_rate * (m / c1)
            / (jnp.sqrt(v / c2) + EPS), p, mu, nu)
        return (p, mu, nu), loss, g
    return step


@functools.lru_cache(maxsize=None)
def _compiled_reference_step(cfg_items: tuple, learning_rate: float,
                             kind: str):
    """One trace for all the seeds that one process reads (`readings.py`)."""
    import jax
    return jax.jit(reference_step(dict(cfg_items), learning_rate, kind),
                   donate_argnums=0)


def reference_steps(params, cfg, batches, learning_rate: float, kind: str):
    """The first len(batches) steps by the plain reference: losses, the first
    gradient's per-leaf norms, the parameters' change's per-leaf norms."""
    import jax
    import jax.numpy as jnp
    from benchmark.layer_metrics.fold_mfu import config_key
    step = _compiled_reference_step(config_key(cfg), learning_rate, kind)
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    state = (jax.tree.map(jnp.copy, params), zeros(), zeros())
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        one = {"seq": jnp.asarray(batch["seq"][0]),
               "msa": jnp.asarray(batch["msa"][0]),
               "coords": jnp.asarray(batch["coords"][0])}
        state, loss, g = step(state, one, jnp.float32(i + 1))
        losses.append(float(jax.device_get(loss)))
        if i == 0:
            grad_norms = _leaf_norms(g)
        del g
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": change_norms(state[0], params)}


def gaps(got: dict, ref: dict) -> dict:
    moved = ref["grad_norms"] >= 1e-3 * np.median(ref["grad_norms"])
    rel = [abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])]
    return {"loss_gap": float(rel[0]),
            "later_loss_gap": float(max(rel[1:], default=0.0)),
            "grad_gap": worst_leaf_gap(got["grad_norms"], ref["grad_norms"]),
            "update_gap": worst_leaf_gap(got["update_norms"],
                                         ref["update_norms"], keep=moved)}


def compare(run, first: dict, kinds, feed) -> dict:
    from benchmark.report import limited, say
    t = run.traffic
    batches = [feed(i) for i in range(t["checked_steps"])]
    ref = reference_steps(run.params, run.config, batches,
                          t["learning_rate"], "f32")
    got = gaps(first, ref)
    say(phase="check_train", losses=first["losses"],
        reference_losses=ref["losses"], **got)
    for kind in kinds[1:]:
        # "frozen" plants the fault of a state left unchanged in the reference
        control = reference_steps(run.params, run.config, batches, 0.0,
                                  "f32") if kind == "frozen" else \
            reference_steps(run.params, run.config, batches,
                            t["learning_rate"], kind)
        read = gaps(control, ref)
        got.update({f"control_{kind}_{k}": v for k, v in read.items()})
        say(phase="check_train_control", kind=kind, losses=control["losses"],
            **read)
    return limited(got, t["limits"])
