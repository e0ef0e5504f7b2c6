"""Flagship LLaMA tests: numerics vs HF transformers, causality, GQA,
and sharded-layout equivalence (the reference's TP×PP output-equality
test strategy, tests/inference/python_inference_tests.sh:128-131)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.core.mesh import MachineSpec, set_mesh as _set_mesh
from flexflow_tpu.models import llama
from flexflow_tpu.optimizers import AdamOptimizer, SGDOptimizer

CFG = llama.LLaMAConfig.tiny(dtype=jnp.float32)
KEY = jax.random.PRNGKey(0)


def test_forward_shape_and_causality():
    params = llama.init_params(KEY, CFG)
    toks = jax.random.randint(KEY, (2, 12), 0, CFG.vocab_size)
    logits = llama.forward(params, toks, CFG)
    assert logits.shape == (2, 12, CFG.vocab_size)
    t2 = toks.at[:, -1].set((toks[:, -1] + 1) % CFG.vocab_size)
    l2 = llama.forward(params, t2, CFG)
    np.testing.assert_allclose(logits[:, :-1], l2[:, :-1], atol=1e-5)
    assert not np.allclose(logits[:, -1], l2[:, -1])


def test_vs_hf_transformers():
    """Numerics vs HuggingFace LlamaForCausalLM with copied weights —
    the analog of the reference's huggingface_inference.py comparison."""
    transformers = pytest.importorskip("transformers")
    import torch

    hf_cfg = transformers.LlamaConfig(
        vocab_size=CFG.vocab_size,
        hidden_size=CFG.hidden_size,
        intermediate_size=CFG.intermediate_size,
        num_hidden_layers=CFG.num_hidden_layers,
        num_attention_heads=CFG.num_attention_heads,
        num_key_value_heads=CFG.num_key_value_heads,
        rms_norm_eps=CFG.norm_eps,
        rope_theta=CFG.rope_theta,
        max_position_embeddings=CFG.max_position_embeddings,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(hf_cfg).eval()

    # copy HF weights into our stacked layout
    sd = hf.state_dict()

    def t2j(name):
        return jnp.asarray(sd[name].numpy())

    L = CFG.num_hidden_layers
    params = {
        "embed": t2j("model.embed_tokens.weight"),
        "final_norm_scale": t2j("model.norm.weight"),
        "lm_head": t2j("lm_head.weight").T,
        "layers": {
            "attn_norm_scale": jnp.stack(
                [t2j(f"model.layers.{i}.input_layernorm.weight") for i in range(L)]
            ),
            "wq": jnp.stack(
                [t2j(f"model.layers.{i}.self_attn.q_proj.weight").T for i in range(L)]
            ),
            "wk": jnp.stack(
                [t2j(f"model.layers.{i}.self_attn.k_proj.weight").T for i in range(L)]
            ),
            "wv": jnp.stack(
                [t2j(f"model.layers.{i}.self_attn.v_proj.weight").T for i in range(L)]
            ),
            "wo": jnp.stack(
                [t2j(f"model.layers.{i}.self_attn.o_proj.weight").T for i in range(L)]
            ),
            "mlp_norm_scale": jnp.stack(
                [
                    t2j(f"model.layers.{i}.post_attention_layernorm.weight")
                    for i in range(L)
                ]
            ),
            "w_gate": jnp.stack(
                [t2j(f"model.layers.{i}.mlp.gate_proj.weight").T for i in range(L)]
            ),
            "w_down": jnp.stack(
                [t2j(f"model.layers.{i}.mlp.down_proj.weight").T for i in range(L)]
            ),
            "w_up": jnp.stack(
                [t2j(f"model.layers.{i}.mlp.up_proj.weight").T for i in range(L)]
            ),
        },
    }
    toks = np.array([[1, 5, 9, 200, 7, 42, 13, 99]], dtype=np.int32)
    ours = llama.forward(params, jnp.asarray(toks), CFG)
    with torch.no_grad():
        theirs = hf(torch.tensor(toks.astype(np.int64))).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=2e-3, atol=2e-3)


def test_train_loss_decreases():
    mesh = MachineSpec().make_mesh(jax.devices()[:1])
    with _set_mesh(mesh):
        init_fn, step, ds = llama.make_train_step(
            CFG, mesh, AdamOptimizer(lr=1e-2), remat=False,
            shard_activations=False,
        )
        params, opt = init_fn(KEY)
        toks = jax.device_put(
            jax.random.randint(KEY, (4, 16), 0, CFG.vocab_size, dtype=jnp.int32), ds
        )
        losses = []
        for _ in range(5):
            params, opt, loss = step(params, opt, toks)
            losses.append(float(loss))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize(
    "degrees",
    [
        dict(tensor=1, pipeline=1),  # 8-way DP
        dict(tensor=2, pipeline=1),  # DP×TP
        dict(tensor=2, sequence=2),  # DP×TP×SP
        dict(tensor=2, pipeline=2),  # DP×TP×PP
        dict(tensor=4, pipeline=2),  # TP×PP
    ],
)
def test_layout_equivalence(degrees):
    """Every parallel layout must reproduce the single-device multi-step
    loss *trajectory* (forward AND gradients through shard_map/ppermute)
    — the TPU version of the reference's 'TP×PP=2×2 vs 1×4 outputs must
    match' test."""
    if (
        degrees.get("pipeline", 1) > 1
        and degrees.get("tensor", 1) > 1
        and jax.default_backend() == "cpu"
    ):
        # TP inside the partial-manual pipeline shard_map makes the XLA
        # SPMD partitioner visit the stage body's PartitionId, which
        # XLA:CPU rejects (UNIMPLEMENTED: PartitionId instruction is not
        # supported for SPMD partitioning); TPU compiles these layouts.
        pytest.skip("XLA:CPU SPMD partitioner lacks PartitionId support "
                    "for TP-inside-pipeline shard_map — TPU-only layout")
    cfg = llama.LLaMAConfig.tiny(num_hidden_layers=4, dtype=jnp.float32)
    toks_host = np.asarray(
        jax.random.randint(KEY, (8, 16), 0, cfg.vocab_size, dtype=jnp.int32)
    )

    def trajectory(spec_degrees=None, steps=3):
        if spec_degrees is None:
            mesh = MachineSpec().make_mesh(jax.devices()[:1])
            mb = 1
        else:
            mesh = MachineSpec.from_degrees(8, **spec_degrees).make_mesh()
            mb = 2 if spec_degrees.get("pipeline", 1) > 1 else 1
        with _set_mesh(mesh):
            init_fn, step, ds = llama.make_train_step(
                cfg, mesh, SGDOptimizer(lr=0.1), num_microbatches=mb
            )
            params, opt = init_fn(KEY)
            toks = jax.device_put(toks_host, ds)
            losses = []
            for _ in range(steps):
                params, opt, loss = step(params, opt, toks)
                losses.append(float(loss))
        return losses

    ref = trajectory(None)
    got = trajectory(degrees)
    np.testing.assert_allclose(got, ref, rtol=2e-4), degrees


def test_graft_entry_single_and_multichip():
    import importlib, sys

    sys.path.insert(0, "/root/repo")
    ge = importlib.import_module("__graft_entry__")
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[-1] == 2048
    if jax.default_backend() == "cpu":
        # single-chip entry verified above; the multichip dryrun uses a
        # TP×PP mesh, and TP inside the partial-manual pipeline
        # shard_map hits XLA:CPU's UNIMPLEMENTED PartitionId in the SPMD
        # partitioner (same limitation as test_layout_equivalence's
        # pipeline layouts). TPU compiles it.
        pytest.skip("XLA:CPU SPMD partitioner lacks PartitionId support "
                    "for TP-inside-pipeline shard_map — TPU-only dryrun")
    ge.dryrun_multichip(8)
