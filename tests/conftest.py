"""Test config: force CPU with 8 virtual devices so multi-chip sharding
paths (DP/TP/PP/SP meshes) compile and run without TPU hardware — the
analog of the reference's single-box multinode emulation
(reference ``tests/multinode_helpers/mpi_wrapper2.sh`` slices
CUDA_VISIBLE_DEVICES per MPI rank)."""
import math
import os
from typing import Any, NamedTuple

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# The test suite runs on the CPU (backends are not initialised yet at
# conftest import time).
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)


def _llama_recorded_params(key, cfg):
    """The weights ``models/llama.init_params`` drew until ISSUE 49 (its
    own 8-way key split, ``lm_head`` from ``fold_in(key, 99)``), under the
    decoder's parameter names. Two assertions are exact on these weights
    and not on the decoder's own draw from the same key, at the parent as
    here (``CHANGES.md``, PR 49 has the witnesses): SpecInfer == incremental on an int4 pool
    (tests/test_adaptive_spec.py) and the fused-RoPE step's bitwise
    logits (tests/test_fused_decode.py). Those files keep the weights
    their assertions were recorded on."""
    from flexflow_tpu.models.transformer import seeded_normal

    L, D, F = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size
    H, KV, dk = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    out = 0.02 / math.sqrt(2 * L)

    def w(k, shape, std=0.02):
        return seeded_normal(k, std, shape=shape, dtype=cfg.dtype)

    ones = lambda shape: jax.numpy.ones(shape, cfg.dtype)
    return {
        "embed": w(ks[0], (cfg.vocab_size, D)),
        "layers": {
            "attn_norm_scale": ones((L, D)),
            "wq": w(ks[1], (L, D, H * dk)),
            "wk": w(ks[2], (L, D, KV * dk)),
            "wv": w(ks[3], (L, D, KV * dk)),
            "wo": w(ks[4], (L, H * dk, D), out),
            "mlp_norm_scale": ones((L, D)),
            "w_gate": w(ks[5], (L, D, F)),
            "w_down": w(ks[6], (L, F, D), out),
            "w_up": w(ks[7], (L, D, F)),
        },
        "final_norm_scale": ones((D,)),
        "lm_head": w(jax.random.fold_in(key, 99), (D, cfg.vocab_size)),
    }


@pytest.fixture(scope="session")
def llama_recorded_params():
    return _llama_recorded_params


@pytest.fixture(scope="session")
def ref_greedy():
    """``ref_greedy(cfg, params, prompt, n_new)``: the greedy continuation
    by ``llama.forward`` over the whole sequence, a token at a time. One
    jitted forward a width (the tokens padded to a multiple of 32: causal
    attention hides the padding from every real position), where an eager
    forward at every new length compiled each of its operations again
    (29 s for four prompts of 8 new tokens against 0.4 s)."""
    from flexflow_tpu.models import llama

    forward = jax.jit(llama.forward, static_argnames=("cfg",))

    def greedy(cfg, params, prompt, n_new):
        toks = list(prompt)
        for _ in range(n_new):
            pad = -len(toks) % 32
            logits = forward(
                params, jax.numpy.asarray([toks + [0] * pad], "int32"), cfg=cfg)
            toks.append(int(jax.numpy.argmax(logits[0, len(toks) - 1])))
        return toks[len(prompt):]

    return greedy


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full-training/example tests; deselect with -m 'not slow' "
        "for a fast iteration loop",
    )


# ---------------------------------------------------------------------------
# The tiny servers. A server is its compiled step programs: seconds to
# minutes of one worker's clock a build, so a file builds each (family,
# serving configuration) once and its cases share it.

TINY_SERVING = dict(
    kv_layout="paged", kernels="xla", page_size=16, max_requests_per_batch=4,
    max_sequence_length=128, prefill_chunk=16, cache_dtype=jax.numpy.float32)


class TinyServer(NamedTuple):
    module: Any
    cfg: Any
    params: Any
    engine: Any
    manager: Any
    llm: Any


class TinyServers:
    """``servers(module, **overrides)``: the tiny float32 preset of family
    ``module`` behind ``serving(**overrides)``, built on first use and kept
    for the test file that asked. ``fresh=True`` builds one nobody else
    sees, for a test that counts compiles or retraces, reads build-log
    ordinals, or leaves the pool other than it found it; a ``cfg`` or
    ``params`` of the caller's own is fresh too. The weights are drawn once
    a (family, dtype, ``draw``) and outlive the file."""

    def __init__(self):
        self._drawn = {}
        self._kept = {}

    @staticmethod
    def serving(**overrides):
        """The tiny serving configuration: paged, a float32 cache, pages
        of 16 lines, 4 slots x chunk 16 (a ladder of two packed rungs,
        16 and 32, under the padded 64), 128 positions, XLA's kernels."""
        from flexflow_tpu.serve import ServingConfig

        return ServingConfig(**{**TINY_SERVING, **overrides})

    def params(self, module, dtype=jax.numpy.float32, draw=None):
        """``(cfg, params)`` of ``module.tiny(dtype=dtype)`` from
        ``PRNGKey(0)``, drawn by ``draw(key, cfg)`` (a module-level
        function of the file that judges on other weights) or by the
        family's ``init_params``."""
        name = (module, jax.numpy.dtype(dtype).name, draw)
        if name not in self._drawn:
            cfg = module.tiny(dtype=dtype)
            self._drawn[name] = cfg, (draw or module.init_params)(
                jax.random.PRNGKey(0), cfg)
        return self._drawn[name]

    def __call__(self, module, *, fresh=False, cfg=None, params=None,
                 draw=None, **overrides):
        from flexflow_tpu.serve.llm import LLM

        name = (module, draw,
                tuple(sorted((k, str(v)) for k, v in overrides.items())))
        fresh = fresh or cfg is not None or params is not None
        if not fresh and name in self._kept:
            return self._drained(self._kept[name])
        drawn = self.params(module, draw=draw)
        cfg, params = cfg or drawn[0], drawn[1] if params is None else params
        llm = LLM(module, cfg, params=params)
        llm.compile(self.serving(**overrides))
        server = TinyServer(module, cfg, params, llm.engine, llm.rm, llm)
        if not fresh:
            self._kept[name] = server
        return server

    @staticmethod
    def _drained(server):
        """A kept server is handed over as it was built: a case that left
        a request or a page behind fails at the next case's door."""
        rm, pager = server.manager, server.engine.pager
        live = [r for r in rm.slots if r is not None]
        assert not (live or rm.pending or rm._inflight), (
            f"a kept {server.module.__name__} server was left with requests "
            f"in flight: slots {rm.slots}, pending {rm.pending}")
        assert pager is None or pager.used_pages == 0, (
            f"a kept {server.module.__name__} server was left holding "
            f"{pager.used_pages} pages")
        return server

    def release(self):
        self._kept.clear()


@pytest.fixture(scope="session")
def tiny_servers():
    return TinyServers()


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs(tiny_servers):
    """Every compiled program is a few memory maps of its worker's process,
    which may have 65530: a family's file leaves some five thousand, and a
    worker that passes the limit aborts inside a later file's compile
    (PR 60's measurement). So a file's servers and programs go when the
    file is done. The kept servers are per file on purpose: under ``--dist
    loadfile`` no other file could count on meeting them, and a server
    that outlived its file would keep its programs' maps with it. The
    drawn weights stay (arrays, no maps to speak of)."""
    yield
    tiny_servers.release()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# The described chip (tests/test_chip_compile_*.py). Only the ``topo``
# fixture describes a topology: module-scoped, never at import, never in a
# skipif or parametrize argument. The description loads the TPU library in
# the test's own process; the driver's command sets
# ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, so several workers may hold it at once.


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip(one_chip, monkeypatch):
    """Steer the kernels to Mosaic (the default backend here is the CPU,
    whose branch is interpret mode) and keep the persistent compile
    cache off: a described-device executable is written to it but can
    never be read back without a chip. Gives ``sds(shape, dtype)``: a
    ``ShapeDtypeStruct`` on the described chip."""
    import functools

    from jax.experimental.compilation_cache import compilation_cache as cc

    from flexflow_tpu.ops import flash_attention
    from flexflow_tpu.serve import kernels

    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
