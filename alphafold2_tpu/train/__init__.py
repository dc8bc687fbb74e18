from alphafold2_tpu.train import losses  # noqa: F401
from alphafold2_tpu.train.checkpoint import CheckpointManager  # noqa: F401
from alphafold2_tpu.train.loop import (  # noqa: F401
    compute_loss,
    fit,
    make_decoder_train_step,
    make_eval_step,
    make_recycled_train_step,
    make_train_step,
    shard_batch,
)
from alphafold2_tpu.train.prefetch import device_prefetch  # noqa: F401
from alphafold2_tpu.train.state import TrainState, adam  # noqa: F401
