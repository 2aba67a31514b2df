"""TPU-native distributed training framework.

Brand-new JAX/XLA/pjit/Pallas implementation with the capabilities of
`ldh127/distributed_pytorch_from_scratch` (Megatron-style tensor parallelism
from first principles), re-designed TPU-first. See SURVEY.md at the repo root
for the reference analysis and build plan.
"""

__version__ = "0.1.0"

from .config import (
    BOS_TOKEN, EOS_TOKEN, UNK_TOKEN, IGNORE_INDEX,
    EvalConfig, MeshConfig, ModelConfig, OptimizerConfig, TrainConfig,
)
from .models.gpt2 import GPT2Transformer
from .models.transformer import Transformer
from .models.vanilla import VanillaGPT2, VanillaTransformer
from .runtime.mesh import make_mesh, tp_mesh, single_device_mesh
