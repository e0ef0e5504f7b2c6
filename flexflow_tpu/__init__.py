"""flexflow-tpu: a TPU-native deep learning framework with the
capabilities of FlexFlow (training with auto-parallelization; LLM serving
with speculative inference), re-designed for JAX/XLA/Pallas/pjit.

Reference: ArulselvanMadhavan/FlexFlow (studied at /root/reference);
see SURVEY.md for the full capability map.
"""

from .config import FFConfig, init, get_config
from .core import (
    DataType,
    TensorSpec,
    MachineSpec,
    Graph,
    TensorRef,
)
from .model import FFModel, Tensor, TRAINING, INFERENCE
from .data import SingleDataLoader
from .optimizers import SGDOptimizer, AdamOptimizer
from . import losses, metrics, initializers
from . import keras, frontends  # noqa: F401  (import frontends)

__version__ = "0.1.0"

__all__ = [
    "FFConfig",
    "init",
    "get_config",
    "DataType",
    "TensorSpec",
    "MachineSpec",
    "Graph",
    "TensorRef",
    "FFModel",
    "Tensor",
    "TRAINING",
    "INFERENCE",
    "SGDOptimizer",
    "AdamOptimizer",
    "losses",
    "metrics",
    "initializers",
    "keras",
    "frontends",
]
