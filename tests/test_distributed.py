"""Multi-host runtime emulation — 2 processes × 2 virtual CPU devices
form one 4-device DP mesh via jax.distributed (the reference's
multinode CI runs mpirun ranks on one box the same way,
tests/multinode_helpers/mpi_wrapper2.sh + multinode-test.yml). DP
training across processes must produce exactly the single-process
4-device losses."""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

WORKER = os.path.join(os.path.dirname(__file__), "distributed_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_dp_matches_single_process():
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            JAX_COORDINATOR=f"127.0.0.1:{port}",
            NPROC="2",
            PID=str(pid),
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, WORKER],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = [p.communicate(timeout=540)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-3000:]
    losses = []
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("LOSSES ")]
        assert line, out[-2000:]
        losses.append(json.loads(line[-1][len("LOSSES "):]))
    # both controllers observe the same (replicated) losses
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)

    # single-process 4-device reference: same model, same data, same mesh
    import flexflow_tpu as ff

    cfg = ff.FFConfig(batch_size=32, epochs=3, num_devices=4, seed=11)
    model = ff.FFModel(cfg)
    t = model.create_tensor((32, 16), name="x")
    t = model.dense(t, 32, activation="relu")
    t = model.dense(t, 4)
    t = model.softmax(t)
    model.compile(optimizer=ff.SGDOptimizer(lr=0.05))
    rng = np.random.default_rng(5)
    y = rng.integers(0, 4, size=128).astype(np.int32)
    centers = rng.normal(size=(4, 16)) * 3
    x = (centers[y] + rng.normal(size=(128, 16))).astype(np.float32)
    ref = []
    for _ in range(3):
        perf = model.fit(x, y, epochs=1, shuffle=False, verbose=False)
        ref.append(float(perf.averages()["loss"]))
    np.testing.assert_allclose(losses[0], ref, rtol=1e-5)
