"""CPU tests of the chip bring-up pieces: ``chip_smoke.py``'s control
flow at the tiny preset, the compile-cache helper, and the places where
a missing device used to be hidden and is now an error."""
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def no_cache_dir(monkeypatch):
    """Undo whatever an entry point under test does to the persistent
    compile cache, so the rest of the worker's tests compile as before."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_tiny_rehearsal_runs_every_phase_and_prints_no_result(
    chip_smoke, capsys, monkeypatch, tmp_path, no_cache_dir
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = chip_smoke.main(["--tiny"])
    out = capsys.readouterr().out
    assert rc == chip_smoke.RESULT_EXIT_REHEARSAL != 0
    assert '"ok"' not in out
    for phase in ("[probe kernels=xla]", "[probe kernels=pallas]",
                  "[pallas vs xla] mixed", "[pallas vs xla] decode",
                  "[generate] traces per step key", "all phases passed"):
        assert phase in out, out


def test_without_tiny_a_cpu_is_a_failure(chip_smoke, capsys, monkeypatch,
                                         tmp_path, no_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("env_dir", ["/somewhere/else", None])
def test_compile_cache_helper(monkeypatch, no_cache_dir, env_dir):
    """Env var set → JAX honours it and the helper sets nothing in code;
    unset → the one fixed path inside the checkout."""
    from flexflow_tpu.config import enable_compile_cache

    jax.config.update("jax_compilation_cache_dir", None)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache"
        )
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None  # not set in code


@pytest.mark.parametrize("backend, want", [("tpu", False), ("cpu", True)])
def test_interpret_mode_is_the_cpu_backends_alone(monkeypatch, backend, want):
    from flexflow_tpu.ops import flash_attention

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert flash_attention._interpret() is want


def test_interpret_mode_unknown_backend_is_an_error(monkeypatch):
    from flexflow_tpu.ops import flash_attention
    from flexflow_tpu.serve import kernels

    assert kernels._interpret is flash_attention._interpret  # one switch
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        flash_attention._interpret()


def test_local_socket_replica_of_a_chip_holder_is_an_error(monkeypatch):
    from flexflow_tpu.serve import ServingConfig
    from flexflow_tpu.serve.cluster import manager

    serving = ServingConfig(replica_transport="socket",
                            replica_endpoints=("127.0.0.1:1",))
    monkeypatch.setattr(manager, "_holds_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="one process at a time"):
        manager._build_member(serving, {}, 0, "mixed")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_seeded_normal_is_the_eager_draw_bit_for_bit(dtype):
    """init_params draws each stacked weight in one jitted program (no
    float32 copy of the model at 7B widths); the values must stay those
    of the eager draw → scale → cast, so no golden value moves."""
    from flexflow_tpu.models.transformer import seeded_normal

    key = jax.random.PRNGKey(3)
    for shape in [(2, 64, 128), (256, 64), (3, 1000, 77)]:
        for scale in (0.02, 0.02 / math.sqrt(2 * 6)):
            eager = (jax.random.normal(key, shape, jnp.float32)
                     * scale).astype(dtype)
            got = seeded_normal(key, scale, shape=shape, dtype=dtype)
            assert got.dtype == eager.dtype
            np.testing.assert_array_equal(
                np.asarray(got, np.float32), np.asarray(eager, np.float32)
            )


