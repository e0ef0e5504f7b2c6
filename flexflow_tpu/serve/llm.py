"""High-level ``LLM``/``SSM`` serving API.

TPU-native counterpart of the reference's Python serving entry points
(reference ``python/flexflow/serve/serve.py:71-502``: ``LLM``/``SSM``
classes that download + convert HF weights, compile per inference mode,
and generate). Differences by design: weights load from a *local* HF
checkpoint directory straight into sharded device arrays (no binary
file cache), and "compile" builds jitted step functions over the mesh
instead of a Legion task graph.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from ..core.mesh import MachineSpec
from .. import models as zoo
from ..models import hf_utils
from .batch_config import GenerationConfig, GenerationResult
from .engine import InferenceEngine, ServingConfig
from .request_manager import RequestManager
from .specinfer import SpecConfig, SpecInferManager


def detect_family(hf_config: Dict[str, Any]):
    """Map an HF config to a model-family module (reference
    ``serve.py:__get_ff_model_type`` dispatch on architectures)."""
    mt = hf_config.get("model_type", "")
    if mt in zoo.FAMILIES:
        return zoo.FAMILIES[mt]
    for arch in hf_config.get("architectures", []):
        # longest key first: "qwen2" must not shadow "qwen2_moe" when
        # only the architectures list is present
        for key in sorted(zoo.FAMILIES, key=len, reverse=True):
            if key.replace("_", "") in arch.lower().replace("_", ""):
                return zoo.FAMILIES[key]
    raise ValueError(f"unsupported model family: {mt!r} / "
                     f"{hf_config.get('architectures')}")


class LLM:
    """A servable causal LM bound to a mesh.

    Build either from a local HF checkpoint directory
    (``LLM.from_pretrained``) or from in-memory (family, cfg, params)
    — the latter is what tests and SSM distillation use.
    """

    def __init__(
        self,
        family: Any,
        cfg: Any,
        params: Optional[Dict[str, Any]] = None,
        *,
        tokenizer: Any = None,
        machine: Optional[MachineSpec] = None,
        mesh=None,
        seed: int = 0,
    ):
        self.family = family
        self.cfg = cfg
        self.tokenizer = tokenizer
        if mesh is None:
            machine = machine or MachineSpec()
            mesh = machine.make_mesh(jax.devices()[: machine.num_devices])
        self.mesh = mesh
        if params is None:
            params = family.init_params(jax.random.PRNGKey(seed), cfg)
        self.params = params
        self.engine: Optional[InferenceEngine] = None
        self.rm: Optional[RequestManager] = None

    # ------------------------------------------------------------------

    @classmethod
    def from_pretrained(
        cls,
        model_dir: str,
        *,
        dtype: Any = jnp.bfloat16,
        tokenizer: Any = "auto",
        machine: Optional[MachineSpec] = None,
        mesh=None,
        **cfg_overrides,
    ) -> "LLM":
        """Load config + weights from a local HF checkpoint directory
        (this environment has no network egress; the reference's HF-hub
        download step happens out of band)."""
        hf_cfg = hf_utils.load_hf_config(model_dir)
        family = detect_family(hf_cfg)
        cfg = family.from_hf(hf_cfg, dtype=dtype, **cfg_overrides)
        sd = hf_utils.load_state_dict(model_dir)
        params = family.convert_hf_state_dict(sd, cfg)
        if tokenizer == "auto":
            try:
                from transformers import AutoTokenizer

                tokenizer = AutoTokenizer.from_pretrained(
                    model_dir, local_files_only=True
                )
            except Exception:
                tokenizer = None
        return cls(
            family, cfg, params, tokenizer=tokenizer, machine=machine, mesh=mesh
        )

    # ------------------------------------------------------------------

    def compile(
        self,
        serving: Optional[ServingConfig] = None,
        *,
        ssms: Sequence["LLM"] = (),
        spec: Optional[SpecConfig] = None,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        quantization: Optional[str] = None,  # "int8" | "int4"
        offload: bool = False,
        output_file: Optional[str] = None,
    ) -> None:
        """Build the inference engine(s) and request manager (reference
        ``LLM.compile`` → InferenceManager.compile_model_and_allocate_buffer).
        With ``ssms`` the request manager runs the SpecInfer loop.

        ``quantization`` converts the layer matmul weights to int8/int4
        {"q","scale"} form at placement time (reference
        ``file_loader.cc:651,710`` quantized loading + decompress
        kernels); ``offload`` places params in pinned host memory on TPU
        so XLA streams them per step (the reference's ``--offload``
        zero-copy double buffering, config.h:155-157).
        """
        serving = serving or ServingConfig()
        # Cluster-field validation fails HERE, before any params are
        # placed or engines built. SpecInfer composes with replicated
        # clusters (each replica gets its own SSM mirror engines,
        # serve/cluster/replica.py); only the disaggregated
        # prefill/decode pools still reject the combination.
        serving.validate_cluster(specinfer=bool(ssms))
        from ..core.mesh import PIPE_AXIS
        from ..config import get_config
        from ..core.dtypes import DataType

        # ff.init(use_4bit_quantization=..., offload=...) flags apply
        # here (the reference's FFConfig → FileDataLoader path).
        ffc = get_config()
        if quantization is None and ffc.quantization_type is not None:
            quantization = {
                DataType.INT8: "int8", DataType.INT4: "int4"
            }[ffc.quantization_type]
        offload = offload or ffc.cpu_offload
        pipelined = self.mesh.shape.get(PIPE_AXIS, 1) > 1
        self.params = self._place_params(
            self.family, self.cfg, self.params, pipelined, quantization, offload
        )
        if (
            serving.replicas > 1 or serving.prefill_replicas
            or serving.journal_dir
        ):
            # Cluster serving (serve/cluster/): N engine replicas behind
            # the prefix-aware router. With ``ssms`` every replica runs
            # a SpecInferManager over its OWN draft mirror engines —
            # draft params are placed once here and shared by reference
            # across replicas, exactly like the target's. A journal_dir
            # forces the cluster manager even at replicas=1 — the
            # durable request journal (crash recovery, scale_out from
            # one replica) lives at the cluster control plane.
            from .cluster import ClusterManager

            ssm_triples = []
            for ssm in ssms:
                ssm.params = self._place_params(
                    ssm.family, ssm.cfg, ssm.params, pipelined,
                    quantization, offload,
                )
                ssm_triples.append((ssm.family, ssm.cfg, ssm.params))
            self.rm = ClusterManager.build(
                self.family, self.cfg, self.params, serving,
                tokenizer=self.tokenizer, eos_token_id=eos_token_id,
                seed=seed, ssms=ssm_triples, spec=spec,
            )
            self.engine = self.rm.replicas[0].engine
            return
        self.engine = InferenceEngine(
            self.family, self.cfg, self.params, serving, self.mesh
        )
        if ssms or getattr(spec, "draft", "ssm") == "early_exit":
            # SpecInfer serving: external SSM drafts, or — with
            # SpecConfig(draft="early_exit") and no ssms — the target
            # self-speculating off its own truncated layer stack.
            for ssm in ssms:
                ssm.params = self._place_params(
                    ssm.family, ssm.cfg, ssm.params, pipelined, quantization,
                    offload,
                )
                ssm.engine = InferenceEngine(
                    ssm.family, ssm.cfg, ssm.params, serving, self.mesh
                )
            self.rm = SpecInferManager(
                self.engine, [s.engine for s in ssms], spec,
                tokenizer=self.tokenizer, eos_token_id=eos_token_id, seed=seed,
                output_file=output_file,
            )
        else:
            self.rm = RequestManager(
                self.engine,
                tokenizer=self.tokenizer,
                eos_token_id=eos_token_id,
                seed=seed,
                output_file=output_file,
            )

    def _place_params(
        self, family, cfg, params, pipelined: bool,
        quantization: Optional[str], offload: bool,
    ):
        """Quantize (optionally), shard, and place params — on device,
        or in pinned host memory when offloading on TPU."""
        if pipelined:
            from ..core.mesh import PIPE_AXIS

            pp = self.mesh.shape[PIPE_AXIS]
            if cfg.num_hidden_layers % pp:
                raise ValueError(
                    f"pipeline serving needs num_hidden_layers "
                    f"({cfg.num_hidden_layers}) divisible by the pipe "
                    f"degree ({pp})"
                )
        pspecs = family.param_pspecs(cfg, pipeline=pipelined)
        if quantization is not None:
            from .. import quantization as quant

            bits = {"int8": 8, "int4": 4}[quantization]
            params = quant.quantize_params(params, bits)
            pspecs = quant.quantize_pspecs(pspecs, params)
        memory_kind = None
        if offload:
            if self.mesh.devices.flat[0].platform == "tpu":
                memory_kind = "pinned_host"
            else:
                import warnings

                warnings.warn(
                    "offload=True has no effect off-TPU (params already "
                    "live in host memory on this backend)", stacklevel=3,
                )
        return hf_utils.device_put_sharded(
            params, self.mesh, pspecs, memory_kind=memory_kind
        )

    def generate(
        self,
        prompts: Union[str, Sequence[Union[str, Sequence[int]]]],
        gen: Optional[GenerationConfig] = None,
        max_new_tokens: Optional[int] = None,
    ) -> List[GenerationResult]:
        if self.rm is None:
            self.compile()
        if gen is not None and gen.num_beams > 1:
            from .beam import generate_with_beams

            if gen.do_sample:
                # Beam scoring here is deterministic log-prob ranking —
                # fail loudly rather than silently ignore sampling knobs
                # (same contract as SpecInferManager.register_request).
                raise ValueError(
                    "num_beams > 1 is greedy-scored; do_sample cannot be "
                    "honored — use num_beams=1 for sampling"
                )
            if max_new_tokens is not None:
                gen = dataclasses.replace(gen, max_new_tokens=max_new_tokens)
            if isinstance(prompts, str):
                prompts = [prompts]
            return generate_with_beams(
                self.engine, prompts, gen,
                eos_token_id=self.rm.eos_token_id, tokenizer=self.tokenizer,
            )
        return self.rm.generate(prompts, gen, max_new_tokens)


class SSM(LLM):
    """Small speculative model (reference ``serve.py`` SSM): same object
    as LLM, compiled onto the LLM's mesh by ``LLM.compile(ssms=[...])``."""
