from alphafold2_tpu.utils.hashing import stable_digest  # noqa: F401
from alphafold2_tpu.utils.logging import MetricsLogger  # noqa: F401
from alphafold2_tpu.utils.profiling import (  # noqa: F401
    StepTimer,
    percentile,
)
