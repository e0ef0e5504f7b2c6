from . import llama, transformer, opt, falcon, mpt, starcoder, qwen2, qwen2_moe, mixtral, mistral, gemma, phi, gpt2, hf_utils

# Model-family registry (reference python/flexflow/serve/models/__init__.py
# maps HF architectures to FlexFlow builders; qwen2 and mixtral go beyond
# the reference's five-family zoo — mixtral adds sparse-MoE serving).
FAMILIES = {
    "llama": llama,
    "opt": opt,
    "falcon": falcon,
    "mpt": mpt,
    "starcoder": starcoder,
    "gpt_bigcode": starcoder,
    "qwen2": qwen2,
    "mixtral": mixtral,
    "mistral": mistral,
    "qwen2_moe": qwen2_moe,
    "gemma": gemma,
    "phi": phi,
    "gpt2": gpt2,
}

# The drawn families are served from in-memory (family, cfg, params)
# on the paged path and have no checkpoint converter, so they are
# modules to import, not entries above: minicpm_sala, lfm2_moe,
# deepseek_v3, olmo_hybrid, granite_hybrid, smallthinker, qwen3_next,
# laguna, longcat_flash.

__all__ = [
    "llama", "transformer", "opt", "falcon", "mpt", "starcoder", "qwen2",
    "mixtral", "mistral", "qwen2_moe", "gemma", "phi", "gpt2",
    "hf_utils", "FAMILIES",
]
