"""Framework configuration — the TPU-native FFConfig.

Mirrors the reference's three-tier flag system (reference
``src/runtime/model.cc:4049-4200`` ``FFConfig::parse_args`` and the Python
``ff.init(**configs)`` dict, ``python/flexflow/serve/__init__.py:32-77``),
collapsed into one dataclass. Legion resource flags (``-ll:gpu`` etc.)
have no TPU meaning: device inventory comes from ``jax.devices()`` and
process topology from ``jax.distributed``.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional

import jax

from .core.dtypes import DataType
from .core.mesh import MachineSpec


@dataclasses.dataclass
class FFConfig:
    # --- training loop (reference FFConfig epochs/batchSize/learningRate) ---
    batch_size: int = 64
    epochs: int = 1
    learning_rate: float = 0.01
    weight_decay: float = 0.0
    seed: int = 0

    # --- parallelism degrees (reference -data/tensor/pipeline-parallelism-degree)
    data_parallelism_degree: int = 1
    tensor_parallelism_degree: int = 1
    pipeline_parallelism_degree: int = 1
    expert_parallelism_degree: int = 1
    # New capability vs the reference (SURVEY.md §2.2: SP absent there).
    sequence_parallelism_degree: int = 1
    only_data_parallel: bool = False

    # --- numerics ---
    compute_dtype: DataType = DataType.FLOAT
    param_dtype: DataType = DataType.FLOAT

    # --- auto-parallel search (reference --budget/--alpha/--enable-*-parallel)
    search_budget: int = -1
    search_alpha: float = 1.2
    enable_sample_parallel: bool = True
    enable_parameter_parallel: bool = True
    enable_attribute_parallel: bool = True
    # Calibrate the search cost model with on-device op timings
    # (reference inner_measure_operator_cost, model.cu:38).
    search_measured: bool = False
    # Persist those timings to a JSON file and reuse across processes
    # (per-(op, shape) timing costs a compile on TPU — SURVEY §7:
    # "cache aggressively"); keyed by device kind.
    search_measured_cache: Optional[str] = None
    # Replace the chip preset's mxu/hbm efficiency guesses with measured
    # roofline fractions (search.machine_model.calibrate_chip) before
    # searching — the other half of the fidelity loop.
    search_calibrate_chip: bool = False
    # User-editable machine config for the search topology (reference
    # --machine-model-file + machine_config_example); overrides the
    # default v5e preset via TPUTopology.from_file.
    machine_config_file: Optional[str] = None
    export_strategy_file: Optional[str] = None
    import_strategy_file: Optional[str] = None
    # extra declarative rewrite rules (reference --substitution-json)
    substitution_json_file: Optional[str] = None

    # --- perf knobs (reference --fusion/--offload/--4bit-quantization) ---
    fusion: bool = True
    cpu_offload: bool = False
    offload_reserve_space_gb: float = 8.0
    quantization_type: Optional[DataType] = None  # DataType.INT4 / INT8
    profiling: bool = False
    remat: bool = False  # jax.checkpoint on per-layer blocks

    # --- serving limits (reference batch_config.h:58-60) ---
    max_requests_per_batch: int = 16
    max_tokens_per_batch: int = 1024
    max_sequence_length: int = 2048

    num_devices: Optional[int] = None

    def __post_init__(self):
        if self.num_devices is None:
            try:
                self.num_devices = len(jax.devices())
            except RuntimeError:
                self.num_devices = 1
        if self.only_data_parallel:
            self.tensor_parallelism_degree = 1
            self.pipeline_parallelism_degree = 1
            self.expert_parallelism_degree = 1
            self.sequence_parallelism_degree = 1

    def machine_spec(self) -> MachineSpec:
        return MachineSpec.from_degrees(
            self.num_devices,
            tensor=self.tensor_parallelism_degree,
            pipeline=self.pipeline_parallelism_degree,
            expert=self.expert_parallelism_degree,
            sequence=self.sequence_parallelism_degree,
        )

    @classmethod
    def from_dict(cls, configs: Dict[str, Any]) -> "FFConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        kwargs = {}
        for k, v in configs.items():
            # Reference boolean quantization flags → DataType values.
            if k == "use_4bit_quantization":
                if v:
                    kwargs["quantization_type"] = DataType.INT4
                continue
            if k == "use_8bit_quantization":
                if v:
                    kwargs.setdefault("quantization_type", DataType.INT8)
                continue
            key = _ALIASES.get(k, k)
            if key in known:
                kwargs[key] = v
        return cls(**kwargs)


# Reference ff.init() key names → our field names.
_ALIASES = {
    "num_gpus": "num_devices",
    "tensor_parallelism_degree": "tensor_parallelism_degree",
    "data_parallelism_degree": "data_parallelism_degree",
    "pipeline_parallelism_degree": "pipeline_parallelism_degree",
    "offload": "cpu_offload",
    "use_4bit_quantization": "quantization_type",
    "batchSize": "batch_size",
    "learningRate": "learning_rate",
}

_global_config: Optional[FFConfig] = None


def init(configs: Optional[Dict[str, Any]] = None, **kwargs) -> FFConfig:
    """``ff.init()`` — set the process-global config (reference
    ``python/flexflow/serve/__init__.py:32``). Safe to call repeatedly."""
    global _global_config
    merged = dict(configs or {})
    merged.update(kwargs)
    _global_config = FFConfig.from_dict(merged)
    return _global_config


def get_config() -> FFConfig:
    global _global_config
    if _global_config is None:
        _global_config = FFConfig()
    return _global_config


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a process this
    repo owns (``chip_smoke.py``, ``python -m flexflow_tpu``,
    ``benchmarks/run.py``) — never at package import, never from the tests. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already honours it and
    nothing is set here; otherwise the cache lives at ONE fixed path
    inside the checkout (the path is part of the cache key, so a
    directory that moves never hits). Returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(repo, ".jax_cache")
        )
    return jax.config.jax_compilation_cache_dir
