"""One typed configuration tree for model, data, mesh, and training.

The reference spreads configuration over three uncoordinated mechanisms
(SURVEY.md §5.6: constructor kwargs, script-level module constants, argparse
in one DataModule). Here a single dataclass tree drives everything;
`Experiment.build()` materializes the model, optimizer, mesh, and train
step from it.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax.numpy as jnp


@dataclass
class ModelConfig:
    dim: int = 256
    depth: int = 6
    heads: int = 8
    dim_head: int = 64
    max_rel_dist: int = 32
    predict_angles: bool = False
    symmetrize_omega: bool = False
    predict_coords: bool = False
    structure_module_depth: int = 4
    structure_module_heads: int = 1
    structure_module_type: str = "ipa"
    structure_module_refinement_iters: int = 0
    structure_module_refinement: str = "residue"   # 'residue' | 'egnn-atom'
    reversible: bool = False
    ring_attention: bool = False
    pipeline_stages: int = 1          # GPipe trunk stages (mesh pipe axis)
    pipeline_microbatches: int = 0
    use_conv: bool = False            # trRosetta2-style trunk conv blocks
    # README-era efficient-attention menu for the MSA row track: bools
    # (all layers) or per-layer lists, e.g. sparse_self_attn =
    # [true, false, true, false] interleaves sparse and full layers
    # (reference README.md:388-487; Evoformer documents semantics).
    # kv_compress_ratio: 0 = off.
    sparse_self_attn: Any = False
    linear_attn: Any = False
    kron_attn: Any = False
    kv_compress_ratio: Any = 0
    linear_attn_kind: str = "favor"   # "favor" (Performer) | "elu"
    performer_nb_features: int = 256
    sparse_block: int = 32
    sparse_num_global: int = 1
    sparse_window: int = 1
    extra_msa_evoformer_layers: int = 4
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    bfloat16: bool = True

    def build(self):
        from alphafold2_tpu import Alphafold2
        kwargs = dataclasses.asdict(self)
        use_bf16 = kwargs.pop("bfloat16")
        return Alphafold2(
            **kwargs, dtype=jnp.bfloat16 if use_bf16 else jnp.float32)


def draft_preset(base: ModelConfig) -> ModelConfig:
    """The draft-tier config derived from a flagship config (ISSUE 19).

    HelixFold-style tiered efficiency: half the width, a third of the
    depth (floored at 1) — the quadratic-in-dim trunk cost drops
    roughly an order of magnitude while the architecture, attention
    menu, and structure module stay the flagship's, so every serving
    path (bucketing, kernel policy, mesh planning) works on the draft
    unchanged. Deriving instead of hardcoding keeps the pair coupled:
    a flagship config change cannot strand a stale draft preset.

    The returned config is a DIFFERENT model with different params —
    the cascade keys its cache entries apart by model_tag, never by
    config digest, so the tag discipline (serve.cascade) still applies.
    """
    return dataclasses.replace(
        base,
        dim=max(base.dim // 2, 1),
        depth=max(base.depth // 3, 1),
        structure_module_depth=max(base.structure_module_depth // 2, 1),
    )


@dataclass
class DataConfig:
    crop_len: int = 128
    msa_depth: int = 5
    batch_size: int = 1
    root: Optional[str] = None        # trrosetta-style data dir; None=synthetic


@dataclass
class MeshConfig:
    pipe: int = 1
    data: int = 1
    i: int = 1
    j: int = 1

    def build(self):
        from alphafold2_tpu.parallel import make_mesh
        if self.pipe * self.data * self.i * self.j == 1:
            return None
        return make_mesh(self.data, self.i, self.j, pipe=self.pipe)


@dataclass
class TrainConfig:
    learning_rate: float = 3e-4
    grad_accum_every: int = 16        # reference train_pre.py:16
    max_grad_norm: Optional[float] = None
    # warmup+cosine schedule (0 / None = the reference's constant LR)
    warmup_steps: int = 0
    decay_steps: Optional[int] = None
    num_steps: int = 1000
    log_every: int = 10
    checkpoint_dir: Optional[str] = None
    seed: int = 0


@dataclass
class Experiment:
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    train: TrainConfig = field(default_factory=TrainConfig)

    # --- serialization -----------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Experiment":
        return cls(
            model=ModelConfig(**d.get("model", {})),
            data=DataConfig(**d.get("data", {})),
            mesh=MeshConfig(**d.get("mesh", {})),
            train=TrainConfig(**d.get("train", {})),
        )

    @classmethod
    def from_json(cls, text: str) -> "Experiment":
        return cls.from_dict(json.loads(text))

    # --- materialization ---------------------------------------------------

    def build(self):
        """Returns (model, tx, mesh)."""
        from alphafold2_tpu.train import adam
        model = self.model.build()
        tx = adam(self.train.learning_rate, self.train.grad_accum_every,
                  self.train.max_grad_norm,
                  warmup_steps=self.train.warmup_steps,
                  decay_steps=self.train.decay_steps)
        mesh = self.mesh.build()
        return model, tx, mesh
