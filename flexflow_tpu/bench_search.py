"""Unity-searched training benchmark.

Builds the flagship LLaMA-style LM through the graph IR (embedding →
fused decoder stack → lm head), lets ``compile(auto_parallel=True)``
run the Unity-style search, and times the resulting compiled step. With
the fused :class:`~flexflow_tpu.ops.fused_transformer
.TransformerDecoderStackOp` the searched strategy executes the same
scan + remat + flash-attention program as the hand-sharded
``models/transformer.make_train_step`` — the search reaches the fast path
instead of the interpreted per-op graph (reference: the searched PCG is
lowered back to real operators via ``convert_graph_to_operators``,
src/runtime/graph.cc:2108 + model.cc:3347).
"""
from __future__ import annotations

from .core.mesh import set_mesh as _set_mesh

import time
from typing import Any, Dict, Optional


def build_searched_lm(
    *,
    vocab_size: int,
    hidden_size: int,
    intermediate_size: int,
    num_layers: int,
    num_heads: int,
    batch: int,
    seq: int,
    dtype,
    attention: str = "xla",
    remat_policy=None,
    config=None,
):
    """FFModel: tokens (B, S) → embed → fused decoder stack → logits."""
    from .config import FFConfig
    from .core.dtypes import DataType
    from .model import FFModel

    config = config or FFConfig(batch_size=batch, num_devices=1)
    ff = FFModel(config)
    dt = DataType.from_any(dtype)
    tokens = ff.create_tensor((batch, seq), dtype=DataType.INT32, name="tokens")
    x = ff.embedding(
        tokens, num_entries=vocab_size, out_dim=hidden_size, dtype=dt,
        name="embed",
    )
    x = ff.transformer_decoder_stack(
        x,
        num_layers=num_layers,
        num_heads=num_heads,
        intermediate_size=intermediate_size,
        attention=attention,
        remat_policy=remat_policy,
        name="decoder",
    )
    ff.dense(x, vocab_size, use_bias=False, name="lm_head")
    return ff


def searched_train_mfu(
    on_tpu: bool, iters: int = 10, attention_override: Optional[str] = None
) -> Dict[str, Any]:
    """Compile the flagship LM with auto_parallel=True, time the searched
    step, and return MFU + the search-fidelity ratio from
    ``validate_search`` (predicted/measured ∈ [0.5, 2] is the
    acceptance band)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .config import FFConfig
    from .models import llama
    from .optimizers import AdamOptimizer

    if on_tpu:
        V, D, F, L, H = 32000, 2048, 5504, 16, 16
        B, S = 8, 1024
        dt, attention = jnp.bfloat16, "flash"
        remat_policy = "dots"
    else:
        V, D, F, L, H = 256, 64, 128, 2, 4
        B, S = 2, 32
        dt, attention = jnp.float32, "xla"
        remat_policy = None
        iters = 2
    if attention_override is not None:
        attention = attention_override

    # On the chip, swap the preset efficiency guesses for measured ones
    # (machine_model.calibrate_chip) so validate_search judges the
    # calibrated model, not the guesses.
    cfg = FFConfig(
        batch_size=B, num_devices=1, search_budget=8,
        search_calibrate_chip=on_tpu,
    )
    ff = build_searched_lm(
        vocab_size=V, hidden_size=D, intermediate_size=F, num_layers=L,
        num_heads=H, batch=B, seq=S, dtype=dt, attention=attention,
        remat_policy=remat_policy, config=cfg,
    )
    ff.compile(
        optimizer=AdamOptimizer(lr=1e-4),
        loss_type="sparse_categorical_crossentropy",
        metrics=(),
        auto_parallel=True,
    )

    rng = np.random.default_rng(0)
    data = rng.integers(0, V, size=(B, S + 1)).astype(np.int32)
    inputs, labels = {"tokens": data[:, :-1][:, :S]}, data[:, 1 : S + 1]
    with _set_mesh(ff.mesh):
        batch = ff._shard_batch(inputs)
        yb = ff._shard_batch({"y": labels})["y"]
        key = jax.random.PRNGKey(0)
        params, opt, st = ff.params, ff.opt_state, ff.model_state
        params, opt, st, loss, _ = ff._train_step(
            params, opt, st, key, batch, yb
        )
        _ = float(loss)  # sync (compile + first step)
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt, st, loss, _ = ff._train_step(
                params, opt, st, key, batch, yb
            )
        _ = float(loss)
        dt_s = (time.perf_counter() - t0) / iters
        ff.params, ff.opt_state, ff.model_state = params, opt, st

    lcfg = llama.LLaMAConfig(
        vocab_size=V, hidden_size=D, intermediate_size=F,
        num_hidden_layers=L, num_attention_heads=H, num_key_value_heads=H,
        max_position_embeddings=S,
    )
    flops = 3 * llama.flops_per_token(lcfg, S) * B * S
    peak = 197e12 if on_tpu else 1e12  # v5e bf16 peak FLOP/s
    report = ff._search_report
    fidelity = ff.validate_search(iters=max(2, iters // 2))
    return {
        "mfu": flops / dt_s / peak,
        "step_ms": round(dt_s * 1e3, 2),
        "tokens_per_sec": round(B * S / dt_s, 1),
        "search_machine": f"dp{report.machine.data}xtp{report.machine.model}",
        "search_candidates": report.candidates_evaluated,
        # predicted/measured ∈ [0.5, 2] is the acceptance band ON TPU —
        # the prediction uses the TPU roofline, so a CPU run's ratio is
        # meaninglessly tiny (report the raw times alongside)
        "search_fidelity_ratio": round(fidelity["ratio"], 4),
        "search_predicted_ms": round(fidelity["predicted_s"] * 1e3, 3),
        "search_measured_ms": round(fidelity["measured_s"] * 1e3, 3),
        "attention": attention,
        **(
            {
                "calibrated_mxu_eff": round(chip.mxu_efficiency, 3),
                "calibrated_hbm_eff": round(chip.hbm_efficiency, 3),
            }
            if (chip := getattr(ff, "_calibrated_chip", None)) is not None
            else {}
        ),
    }
