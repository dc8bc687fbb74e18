"""Training step + loop.

The reference's loops (/root/reference/train_pre.py:64-96,
train_end2end.py:99-166) are Python for-loops with manual grad accumulation
and .backward(); here the step is one jitted, pjit-shardable function:

- loss = distogram CE [+ coords Kabsch-RMSD + dispersion term + MLM + angle
  CE + confidence regression], selected by what the batch provides and the
  model config;
- gradient accumulation lives in the optimizer (optax.MultiSteps), so the
  jitted step stays a single program;
- under a mesh, batch inputs are sharded over the `data` axis and the
  in-model sharding constraints distribute the pair representation over
  (i, j) — XLA inserts the collectives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from alphafold2_tpu.obs import builds
from alphafold2_tpu.parallel.mesh import DATA_AXIS
from alphafold2_tpu.parallel.sharding import active_mesh
from alphafold2_tpu.train import losses
from alphafold2_tpu.train.state import TrainState


def compute_loss(model, params, batch, rng, train: bool = True,
                 recyclables=None):
    """Forward + composite loss. Returns (loss, metrics).

    `recyclables` feeds the recycling embedder (prior-iteration state from
    a no-grad prologue pass; see make_recycled_train_step)."""
    metrics = {}
    wants_coords = model.predict_coords and "coords" in batch

    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(batch["seq"].shape, dtype=bool)

    kwargs = dict(
        msa=batch.get("msa"),
        mask=mask,
        msa_mask=batch.get("msa_mask"),
        train=train,
        recyclables=recyclables,
    )
    # 'performer' redraws FAVOR+ random features every step (the per-step
    # form of performer-pytorch's feature_redraw_interval; unbiased). Eval
    # still needs a performer key: with rngs=None the scanned trunk would
    # hand every layer the same path-derived fallback key, so all layers
    # would share ONE FAVOR+ projection and their estimator errors add
    # coherently — a fixed key here lets nn.scan's split_rngs give each
    # layer an independent projection (predict.fold does the same).
    rngs = {"mlm": rng, "dropout": jax.random.fold_in(rng, 1),
            "performer": jax.random.fold_in(rng, 2)} if train \
        else {"performer": jax.random.PRNGKey(0)}

    if wants_coords:
        coords, ret = model.apply(params, batch["seq"], **kwargs,
                                  return_aux_logits=True,
                                  rngs=rngs)
        loss = losses.coords_loss(coords, batch["coords"], mask,
                                  distogram_logits=ret.distance)
        metrics["coords_loss"] = loss
        if ret.confidence is not None:
            c_loss = losses.lddt_confidence_loss(
                ret.confidence, coords, batch["coords"], mask)
            metrics["confidence_loss"] = c_loss
            loss = loss + c_loss
    elif model.predict_coords:
        # coords model but the batch has no coords target: still request
        # aux logits so `ret` is a ReturnValues, not a bare coords array
        # (only the MLM/angle terms below can contribute here — the
        # distogram term requires a coords target)
        _, ret = model.apply(params, batch["seq"], **kwargs,
                             return_aux_logits=True, rngs=rngs)
        loss = jnp.zeros((), jnp.float32)
    else:
        ret = model.apply(params, batch["seq"], **kwargs, rngs=rngs)
        loss = jnp.zeros((), jnp.float32)

    if "coords" in batch and not wants_coords:
        d_loss = losses.distogram_loss(ret.distance, batch["coords"], mask)
        metrics["distogram_loss"] = d_loss
        loss = loss + d_loss

    if model.predict_angles and "theta" in batch:
        a_loss = losses.angle_loss(
            ret.theta, ret.phi, ret.omega,
            batch["theta"], batch["phi"], batch["omega"])
        metrics["angle_loss"] = a_loss
        loss = loss + a_loss

    if ret.msa_mlm_loss is not None:
        metrics["mlm_loss"] = ret.msa_mlm_loss
        loss = loss + ret.msa_mlm_loss

    metrics["loss"] = loss
    return loss, metrics


def _make_step(loss_fn):
    """The one step builder: `loss_fn(params, batch, rng) -> (loss,
    metrics)` becomes state, batch -> state, metrics (gradient, the
    optimizer's update, the carried key split). Its body marks the build
    that traces it as the program `train_step` (`obs.builds`), whatever
    jit wraps it."""

    def train_step(state: TrainState, batch):
        builds.mark("train_step")
        rng, new_rng = jax.random.split(state.rng)

        # `loss` and `optimizer`: no flax module names what happens
        # outside the model, and the profiler's reader (obs/device.py)
        # goes by names
        @jax.named_scope("loss")
        def scoped(params):
            return loss_fn(params, batch, rng)

        grads, metrics = jax.grad(scoped, has_aux=True)(state.params)
        with jax.named_scope("optimizer"):
            new_state = state.apply_gradients(grads=grads)
        return new_state.replace(rng=new_rng), metrics

    return train_step


def make_train_step(model):
    """Build the jitted train step: state, batch -> state, metrics."""
    return _make_step(lambda params, batch, rng: compute_loss(
        model, params, batch, rng, train=True))


def make_decoder_train_step(model):
    """The step of a causal token decoder (`model/decoder.CausalDecoder`):
    `batch["tokens"]` is (b, n + 1); the model reads the first n and is
    held to the last n (mean next-token cross-entropy over the vocabulary
    held, float32 logits). The expert layers' counters (`expert_slots`,
    `expert_overflow`, `expert_max_load`, `expert_tiles`) come back beside
    the loss."""

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        logits, counters = model.apply(params, tokens[:, :-1])
        with jax.named_scope("lm_head"):
            loss = losses.next_token_loss(logits, tokens[:, 1:])
        return loss, dict(counters, loss=loss)

    return _make_step(loss_fn)


def make_recycled_train_step(model, max_recycles: int = 3):
    """Train step with SAMPLED recycling (the AF2 training protocol the
    reference only gestures at — its tests run the recycle loop by hand
    at inference, test_attention.py:344-385, but nothing trains the
    recycling embedder).

    Each step draws r ~ Uniform{0..max_recycles}, runs r no-grad passes
    threading `Recyclables` (the model already stop-gradients them), and
    takes the gradient only through the final pass — so the same weights
    serve every inference recycle count (predict.fold). One compiled
    program: the prologue is a fori_loop with a traced bound, the
    r==0 / r>0 split is a lax.cond."""
    assert model.predict_coords, "recycled training needs predict_coords"
    assert max_recycles >= 1

    def train_step(state: TrainState, batch):
        rng, new_rng = jax.random.split(state.rng)
        r = jax.random.randint(jax.random.fold_in(rng, 77), (), 0,
                               max_recycles + 1)

        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(batch["seq"].shape, dtype=bool)
        fwd_kwargs = dict(msa=batch.get("msa"), mask=mask,
                          msa_mask=batch.get("msa_mask"), train=False,
                          return_aux_logits=True, return_recyclables=True,
                          rngs={"performer": jax.random.PRNGKey(0)})

        def one_pass(rec):
            _, ret = model.apply(state.params, batch["seq"],
                                 recyclables=rec, **fwd_kwargs)
            return ret.recyclables

        # prologue: pass 1 from scratch, then r-1 recycled passes — all
        # outside the grad trace (recycling trains with stopped gradients,
        # matching the model's own stop_gradient on Recyclables). The
        # whole prologue sits under the r>0 cond so r==0 steps (1 in
        # max_recycles+1) skip it entirely; the false branch's zero
        # Recyclables are never consumed (the loss cond discards them).
        rec_shapes = jax.eval_shape(lambda: one_pass(None))

        def prologue(_):
            return jax.lax.fori_loop(
                0, jnp.maximum(r - 1, 0), lambda _, c: one_pass(c),
                one_pass(None))

        rec = jax.lax.cond(
            r > 0, prologue,
            lambda _: jax.tree.map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), rec_shapes),
            None)

        def loss_fn(params):
            return jax.lax.cond(
                r > 0,
                lambda _: compute_loss(model, params, batch, rng,
                                       train=True, recyclables=rec),
                lambda _: compute_loss(model, params, batch, rng,
                                       train=True),
                None)

        grads, metrics = jax.grad(loss_fn, has_aux=True)(state.params)
        metrics["recycles"] = r.astype(jnp.float32)
        new_state = state.apply_gradients(grads=grads).replace(rng=new_rng)
        return new_state, metrics

    return train_step


def make_eval_step(model):
    def eval_step(state: TrainState, batch):
        _, metrics = compute_loss(model, state.params, batch,
                                  jax.random.PRNGKey(0), train=False)
        return metrics

    return eval_step


def shard_batch(batch, mesh=None):
    """Place a host batch on the mesh, sharded over the data axis."""
    mesh = mesh or active_mesh()
    if mesh is None:
        return batch

    def place(x):
        spec = [None] * x.ndim
        if x.ndim >= 1 and x.shape[0] % mesh.shape[DATA_AXIS] == 0:
            spec[0] = DATA_AXIS
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    return jax.tree.map(place, batch)


def fit(
    model,
    state: TrainState,
    batches,
    num_steps: int,
    log_every: int = 10,
    logger=None,
    step_timer=None,
    prefetch: int = 2,
    registry=None,
):
    """Minimal host loop (reference train_pre.py:64-96 analog): consumes an
    iterator of batches, runs the jitted step, logs scalar metrics.
    `prefetch` stages that many batches onto device from a background
    thread (train/prefetch.py) so host featurization/transfer overlaps
    the step; 0 disables.

    Training reports into the same process-wide metrics registry the
    serving stack uses (`registry=None` = obs.get_registry()):
    `train_steps_total`, a `train_step_seconds` histogram (when a
    `step_timer` measures steps), and last-logged loss terms as
    `train_metric{name=...}` gauges — one Prometheus scrape sees train
    and serve side by side."""
    from alphafold2_tpu.obs.registry import get_registry

    reg = registry or get_registry()
    m_steps = reg.counter("train_steps_total", "optimizer steps run")
    m_step_s = reg.histogram("train_step_seconds",
                             "wall time per training step")
    m_metric = reg.gauge("train_metric",
                         "last logged training metric value", ("name",))

    pre_placed = prefetch > 0
    if pre_placed:
        from alphafold2_tpu.train.prefetch import device_prefetch
        batches = device_prefetch(batches, size=prefetch)
    train_step = jax.jit(make_train_step(model), donate_argnums=(0,))
    history = []
    for i in range(num_steps):
        batch = next(batches)
        if step_timer is not None:
            step_timer.start()
        # the prefetch worker already owns placement; re-sharding every
        # step would redo a tree of device_puts on the hot path
        state, metrics = train_step(
            state, batch if pre_placed else shard_batch(batch))
        if step_timer is not None:
            jax.block_until_ready(metrics["loss"])
            step_timer.stop()
            # a StepTimer already wired to a registry histogram
            # (StepTimer(histogram=...)) records itself; observing here
            # too would double-count every step
            if getattr(step_timer, "histogram", None) is None:
                m_step_s.observe(step_timer.durations[-1])
        m_steps.inc()
        if i % log_every == 0:
            scalars = {k: float(v) for k, v in metrics.items()}
            history.append(scalars)
            for k, v in scalars.items():
                m_metric.set(v, name=k)
            if logger is not None:
                logger.log(step=i, **scalars)
    return state, history
