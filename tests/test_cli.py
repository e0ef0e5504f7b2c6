"""CLI driver + observability: python -m flexflow_tpu subcommands
(the reference's app drivers / flexflow_python launcher, SURVEY.md L11),
dot export, and leveled loggers."""
import logging
import os
import subprocess
import sys

import pytest


def _run(args, timeout=420):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    return subprocess.run(
        [sys.executable, "-m", "flexflow_tpu", *args],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )


def test_cli_train():
    r = _run(["train", "--devices", "2", "--epochs", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final:" in r.stdout


def test_cli_serve_spec_reference_style_flags(tmp_path):
    import jax

    if jax.default_backend() == "cpu":
        # the tp2×pp2 serve mesh puts TP inside the partial-manual
        # pipeline shard_map, whose PartitionId the XLA:CPU SPMD
        # partitioner rejects as UNIMPLEMENTED (same limitation as
        # test_serve_parallel[tp2pp2]); the flag PARSING path is still
        # covered by the other CLI tests. TPU compiles this layout.
        pytest.skip("XLA:CPU SPMD partitioner lacks PartitionId support "
                    "for TP-inside-pipeline shard_map — TPU-only layout")
    r = _run([
        "serve", "--spec", "--max-new-tokens", "8",
        "-tensor-parallelism-degree", "2",
        "-pipeline-parallelism-degree", "2",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "steps=" in r.stdout


def test_cli_serve_cluster_flags():
    """--replicas/--router-policy/--prefill-replicas/--decode-replicas
    drive the cluster path end to end (serve/cluster/): disaggregated
    1 prefill + 1 decode over the tiny random model."""
    r = _run([
        "serve", "--max-new-tokens", "6",
        "--kv-layout", "paged", "--page-size", "16",
        "--replicas", "2", "--prefill-replicas", "1",
        "--decode-replicas", "1", "--router-policy", "prefix",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "steps=" in r.stdout
    # bad cluster configs die at construction with a clear error
    r = _run(["serve", "--replicas", "2", "--prefill-replicas", "1"])
    assert r.returncode != 0
    assert "BOTH pools" in r.stderr


def test_cli_serve_refuses_a_fusion_that_is_gone():
    r = _run(["serve", "--kv-layout", "paged", "--fused-decode", "whole_step",
              "--max-new-tokens", "2"])
    assert r.returncode != 0
    assert "unknown fused_decode entry" in r.stderr


def test_cli_serve_refuses_pallas_on_the_dense_layout():
    r = _run(["serve", "--pallas", "--max-new-tokens", "2"])
    assert r.returncode == 2 and "Traceback" not in r.stderr
    assert "--pallas requires --kv-layout paged" in r.stderr


def test_cli_search_exports(tmp_path):
    dot = str(tmp_path / "strategy.dot")
    strat = str(tmp_path / "strategy.json")
    r = _run([
        "search", "--devices", "4", "--export-dot", dot,
        "--export-strategy", strat,
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "strategy:" in r.stdout
    assert os.path.exists(dot) and "digraph" in open(dot).read()
    assert os.path.exists(strat) and "choices" in open(strat).read()


def test_leveled_loggers(capsys):
    os.environ["FF_LOG"] = "unittest=debug"
    try:
        from flexflow_tpu.logging_utils import get_logger

        log = get_logger("unittest")
        assert log.isEnabledFor(logging.DEBUG)
        other = get_logger("quiet_category")
        assert not other.isEnabledFor(logging.INFO)
    finally:
        del os.environ["FF_LOG"]
