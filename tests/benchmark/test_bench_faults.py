"""`correct` has to come out false when the timed path is broken underneath:
the harness's look for a chip is skipped and the rest of a run is driven, once
for each fault a cell can have (an answer altered where it is produced, for
the fold cells; a step that returns its state unchanged, for training), and
once with the reference computed in the next lower precision put in the
program's place (the control: through the whole of `run.py` for training,
beside the served folds for the fold cells), at a size a test run can hold."""

import numpy as np
import pytest

import bench_tiny

MANIFEST = bench_tiny.manifest()
FOLD_CELLS = [c["name"] for c in MANIFEST["workloads"]
              if c["traffic"] != "train_crop256"]
TRAIN_CELLS = [c["name"] for c in MANIFEST["workloads"]
               if c["traffic"] == "train_crop256"]


@pytest.mark.parametrize("workload", FOLD_CELLS)
def test_an_altered_answer_is_not_correct(workload, tmp_path, monkeypatch,
                                          capsys):
    from alphafold2_tpu import serve
    sound = serve.FoldExecutor._invoke

    def altered(self, *args, **kwargs):
        result = sound(self, *args, **kwargs)
        # one residue in eight of every fold comes back a few Angstrom off
        coords = np.array(result.coords)
        coords[:, ::8] += 3.0
        return result._replace(coords=coords)
    monkeypatch.setattr(serve.FoldExecutor, "_invoke", altered)
    rc, result = bench_tiny.run_tiny(tmp_path, monkeypatch, capsys, workload,
                                     0)
    assert rc == 0 and result["correct"] is False
    value, limit = result["compared"]["coords_gap"]
    assert value > limit


@pytest.mark.parametrize("workload", TRAIN_CELLS)
def test_a_step_that_keeps_its_state_is_not_correct(workload, tmp_path,
                                                    monkeypatch, capsys):
    from alphafold2_tpu import train
    sound = train.make_train_step

    def frozen(model):
        step = sound(model)

        def keeps_state(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return keeps_state
    monkeypatch.setattr(train, "make_train_step", frozen)
    rc, result = bench_tiny.run_tiny(tmp_path, monkeypatch, capsys, workload,
                                     0)
    assert rc == 0 and result["correct"] is False
    value, limit = result["compared"]["update_gap"]
    assert value > limit


@pytest.mark.parametrize("seed", [3000000019, 7, 20260930])
@pytest.mark.parametrize("workload", TRAIN_CELLS)
def test_the_fp8_control_in_the_steps_place_is_not_correct(
        workload, seed, tmp_path, monkeypatch, capsys):
    """The reference's loss in fp8 (values and gradients rounded at every
    contraction, each tensor at its own scale) under the program's optimizer,
    handed to the harness as the program's training step."""
    import jax
    from alphafold2_tpu import train
    from benchmark import reference

    def control(model):
        cfg = {k: getattr(model, k)
               for k in ("heads", "dim_head", "structure_module_depth")}

        def step(state, batch):
            crop = {k: batch[k][0] for k in ("seq", "msa", "coords")}
            loss, grads = jax.value_and_grad(reference.train_loss)(
                state.params, cfg, crop, "fp8")
            return state.apply_gradients(grads=grads), {"loss": loss}
        return step
    monkeypatch.setattr(train, "make_train_step", control)
    rc, result = bench_tiny.run_tiny(tmp_path, monkeypatch, capsys, workload,
                                     0, seed=seed)
    assert rc == 0 and result["correct"] is False
    assert any(value > limit for value, limit in result["compared"].values())


@pytest.mark.parametrize("workload", FOLD_CELLS)
def test_the_fp8_control_fails_the_fold_limits(workload):
    """The reference in fp8, in the program's place, at a small size."""
    import jax
    import jax.numpy as jnp
    from benchmark import fold_check, reference, weights
    from benchmark.run import build_model, load_cell
    spec = load_cell(bench_tiny.REPO, workload)
    cfg = dict(spec["config"], depth=4, num_recycles=3)
    params = weights.make_params(build_model(cfg), 7)
    rng = np.random.default_rng(7)
    seq = jnp.asarray(rng.integers(0, 21, (24,)))
    msa = jnp.asarray(rng.integers(0, 21, (8, 24)))
    out = {kind: jax.jit(lambda p, kind=kind: reference.fold(
        p, cfg, seq, msa, cfg["num_recycles"], kind))(params)
        for kind in ("f32", "fp8")}
    got = fold_check.gaps(*out["fp8"], *out["f32"])
    limits = spec["traffic"]["limits"]
    assert any(got[k] > limits[k] for k in limits), (got, limits)
