from alphafold2_tpu.ops.attention import (  # noqa: F401
    attention_reference,
    fused_attention,
    fused_attention_merged,
    pallas_attention,
    pallas_attention_enabled,
    use_pallas_attention,
)
from alphafold2_tpu.ops.block_sparse import (  # noqa: F401
    block_sparse_attention,
    plan_block_pattern,
)
