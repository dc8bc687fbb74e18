"""Training steps back to back: the jitted `train.make_train_step` (state
donated), batch after batch prepared on the host from the seed.

Set-up builds ONE object, the compiled step with its state, and drives it
through its first `checked_steps` steps by the window's own call and feed;
what those steps produced (each loss, the first gradient as Adam's first
moment holds it, the parameters' change) is kept for the check. The window
then goes on from the same object: all steps completed over all the time,
closed by one `device_get` that depends on the last step.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import traffic_gen, train_check


def compiled_step(model):
    """The program's jitted training step, its state donated."""
    import jax
    from alphafold2_tpu import train
    return jax.jit(train.make_train_step(model), donate_argnums=(0,))


class Driver:
    def __init__(self, run):
        import jax
        import jax.numpy as jnp
        from alphafold2_tpu.train import TrainState, adam
        self.run, t = run, run.traffic
        # the step donates its state: it gets a copy, the benchmark's own
        # weights stay for the reference
        self.state = TrainState.create(
            apply_fn=run.model.apply, params=jax.tree.map(jnp.copy,
                                                          run.params),
            tx=adam(t["learning_rate"]),
            rng=jax.random.PRNGKey(run.seed & 0x7FFFFFFF))
        self.step = compiled_step(run.model)
        self.steps_done = 0
        self.first = {"losses": []}

    def feed(self, index: int) -> dict:
        t = self.run.traffic
        with self.run.annotate("input_prep"):
            return traffic_gen.train_batch(
                self.run.seed, index, t["crop"], self.run.config["msa_depth"],
                t["batch"])

    def advance(self):
        """One step by the one call that set-up and the window share."""
        batch = self.feed(self.steps_done)
        with self.run.annotate("submit"):
            self.state, metrics = self.step(self.state, batch)
        self.steps_done += 1
        return metrics["loss"]

    def warm(self):
        import jax
        for i in range(self.run.traffic["checked_steps"]):
            self.first["losses"].append(float(jax.device_get(self.advance())))
            if i == 0:
                self.first["grad_norms"] = train_check.first_gradient_norms(
                    self.state.opt_state)
        self.first["update_norms"] = train_check.change_norms(
            self.state.params, self.run.params)

    def window(self) -> dict:
        import jax
        run, ahead = self.run, self.run.traffic["steps_in_flight"]
        losses, pending = [], []
        start = self.steps_done
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < run.seconds:
            run.tick(time.perf_counter() - t0)
            pending.append(self.advance())
            if len(pending) > ahead:
                with run.annotate("wait"):
                    losses.append(float(jax.device_get(pending.pop(0))))
        with run.annotate("wait"):
            losses += [float(x) for x in jax.device_get(pending)]
        window_s = time.perf_counter() - t0
        steps = self.steps_done - start
        bad = int(np.sum(~np.isfinite(losses)))
        return {"window_s": window_s, "attempted": steps, "failed": bad,
                "steps": steps,
                "end_to_end": {"train_step_time": 1e3 * window_s / steps},
                "notes": {"first_losses": self.first["losses"],
                          "last_loss": losses[-1]}}

    def release(self):
        self.state = self.step = None

    def check(self, kinds) -> dict:
        return train_check.compare(self.run, self.first, kinds, self.feed)


def largest_program(model, param_shapes, config, traffic, place):
    """For `reckon_bytes.py`: the jitted step with its state donated."""
    import jax
    import jax.numpy as jnp
    from alphafold2_tpu.train import TrainState, adam
    b, n, m = traffic["batch"], traffic["crop"], config["msa_depth"]
    state = jax.eval_shape(lambda p: TrainState.create(
        apply_fn=model.apply, params=p, tx=adam(traffic["learning_rate"]),
        rng=jax.random.PRNGKey(0)), param_shapes)
    state = jax.tree.map(lambda s: place(s.shape, s.dtype), state)
    batch = {"seq": place((b, n), jnp.int32),
             "msa": place((b, m, n), jnp.int32),
             "mask": place((b, n), bool), "msa_mask": place((b, m, n), bool),
             "coords": place((b, n, 3), jnp.float32)}
    return (f"train step b={b} crop={n} msa={m}", compiled_step(model),
            (state, batch))


def reference_program(param_shapes, config, traffic, place):
    """For `reckon_bytes.py --reference`: one float32 reference step (loss,
    gradient, Adam by hand) with its state donated."""
    import jax
    import jax.numpy as jnp
    step = train_check.reference_step(config, traffic["learning_rate"], "f32")
    n, m = traffic["crop"], config["msa_depth"]
    batch = {"seq": place((n,), jnp.int32), "msa": place((m, n), jnp.int32),
             "coords": place((n, 3), jnp.float32)}
    return (f"f32 reference train step crop={n}",
            jax.jit(lambda state, b: step(state, b, jnp.float32(1)),
                    donate_argnums=0),
            ((param_shapes,) * 3, batch))
