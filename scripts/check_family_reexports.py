#!/usr/bin/env python
"""Guard: every model-family module re-exports the full serve API.

The generic-decoder families (falcon, gemma, gpt2, llama, mistral,
mixtral, mpt, opt, phi, qwen2, qwen2_moe, starcoder) implement nothing
serving-specific themselves — they re-export ``models/transformer.py``'s
serving protocol so the InferenceEngine can treat any family module
uniformly (``engine.model.serve_step_paged`` etc.). That re-export list
is copy-pasted per family and silently rots: a new serve symbol (e.g.
``copy_page_kv``, added for prefix-cache copy-on-write) lands in
transformer.py, and any family module that misses it keeps importing
fine until an engine feature hits the missing attribute at runtime.

This script asserts the full surface on every family module. It is
importable (``check()`` returns {module: [missing symbols]}) and wired
into tier-1 via tests/test_family_reexports.py; standalone use::

    python scripts/check_family_reexports.py
"""
from __future__ import annotations

import importlib
import os
import sys
from typing import Dict, List

# standalone invocation from anywhere: put the repo root on the path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The engine-facing serving protocol (see InferenceEngine's docstring
# and engine._serve_step_fn/_get_step/commit/reorder/copy_page call
# sites) plus the param/config helpers every family ships. THIS list is
# the source of truth — extend it when the engine starts calling a new
# model hook, and the test fails on any family that lags.
#
# Hooks can also grow NEW KEYWORD ARGUMENTS without growing the list:
# the quantized paged KV cache (PR 5, serve/kv_quant.py) extended
# init_paged_kv_cache / paged_kv_cache_pspecs / serve_step_paged /
# commit_kv_paged / serve_debug_activations with ``kv_quant=...``
# rather than adding symbols — family modules re-export transformer.py's
# functions BY REFERENCE, so kwargs ride along automatically and only
# genuinely new attribute names need an entry here. The meta-check in
# tests/test_family_reexports.py cross-checks every ``.model.<name>``
# access across the whole serve package (engine.py is merely where they
# all live today) against this list.
SERVE_API = (
    # dense serving
    "init_kv_cache",
    "kv_cache_pspecs",
    "serve_step",
    "commit_kv",
    "reorder_slots",
    # paged serving (PR 1) + prefix-cache COW (PR 3); the quantized
    # pool (PR 5) reuses these same entry points via kv_quant kwargs
    "init_paged_kv_cache",
    "paged_kv_cache_pspecs",
    "serve_step_paged",
    "commit_kv_paged",
    "reorder_slots_paged",
    "copy_page_kv",
    # hierarchical KV cache host tier (PR 7): page spill/re-admit —
    # the engine's fetch_page/upload_page programs slice one physical
    # page out of (or back into) every cache buffer
    "gather_page_kv",
    "scatter_page_kv",
    # megakernel decode step (PR 6): the per-family capability tuple
    # the engine validates ServingConfig.fused_decode against — the
    # fused variants themselves ride on serve_step_paged's
    # ``fused_rope=...`` kwarg (carried by reference, like kv_quant)
    "FUSED_DECODE",
    # triage + params
    "serve_debug_activations",
    "forward",
    "init_params",
    "num_params",
    "param_pspecs",
)

# Every family module the zoo serves by re-exporting
# models/transformer.py.
FAMILIES = (
    "falcon",
    "gemma",
    "gpt2",
    "llama",
    "mistral",
    "mixtral",
    "mpt",
    "opt",
    "phi",
    "qwen2",
    "qwen2_moe",
    "starcoder",
)


def check() -> Dict[str, List[str]]:
    """Returns {family module: [missing serve symbols]} — empty dict
    means every family exposes the full surface."""
    missing: Dict[str, List[str]] = {}
    for fam in FAMILIES:
        mod = importlib.import_module(f"flexflow_tpu.models.{fam}")
        gone = [sym for sym in SERVE_API if not hasattr(mod, sym)]
        if gone:
            missing[fam] = gone
    return missing


def main() -> int:
    missing = check()
    if not missing:
        print(
            f"ok: {len(FAMILIES)} family modules re-export all "
            f"{len(SERVE_API)} serve symbols"
        )
        return 0
    for fam, gone in sorted(missing.items()):
        print(f"flexflow_tpu/models/{fam}.py is missing: {', '.join(gone)}")
    return 1


if __name__ == "__main__":
    sys.exit(main())
