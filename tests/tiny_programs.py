"""The three tiny programs whose compiled text the kernel vocabulary is held
to (tests/test_obs_device.py on the CPU, tests/test_chip_compile.py for a
described chip): the fold with scan + remat, the fold unrolled, and the
training step."""

import re

import jax
import jax.numpy as jnp

from alphafold2_tpu import Alphafold2, predict, train

PROGRAMS = ("fold_scan_remat", "fold_unrolled", "train_step")
_CONTRACTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s+=\s+\S+\s+(?:dot|convolution)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def compile_tiny(program: str, sharding=None, dtype=jnp.float32):
    """The compiled executable of one of PROGRAMS, from shapes alone (with
    `sharding`, a described device's, for that device)."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    placed = lambda tree: jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)
    model = Alphafold2(dim=32, depth=2, heads=2, dim_head=16,
                       predict_coords=True, structure_module_depth=2,
                       dtype=dtype, use_scan=program != "fold_unrolled")
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 16), jnp.int32),
                             msa=jnp.zeros((1, 4, 16), jnp.int32)),
        jax.random.PRNGKey(0))
    batch = {"seq": sds((1, 16), jnp.int32), "msa": sds((1, 4, 16), jnp.int32),
             "mask": sds((1, 16), jnp.bool_),
             "msa_mask": sds((1, 4, 16), jnp.bool_)}
    if program == "train_step":
        state = jax.eval_shape(lambda p: train.TrainState.create(
            apply_fn=model.apply, params=p, tx=train.adam(3e-4),
            rng=jax.random.PRNGKey(0)), params)
        batch["coords"] = sds((1, 16, 3), jnp.float32)
        return jax.jit(train.make_train_step(model)).lower(
            placed(state), batch).compile()
    return jax.jit(lambda p, b: predict.fold(
        model, p, b["seq"], msa=b["msa"], mask=b["mask"],
        msa_mask=b["msa_mask"], num_recycles=1)).lower(
            placed(params), batch).compile()


def contraction_op_names(hlo_text: str):
    """The `op_name` (None where there is none) of every `dot` and
    `convolution` instruction of an executable's text."""
    for line in hlo_text.splitlines():
        if _CONTRACTION.match(line):
            named = _OP_NAME.search(line)
            yield named.group(1) if named else None
