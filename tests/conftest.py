"""Test config: force CPU with 8 virtual devices so multi-chip sharding
paths (DP/TP/PP/SP meshes) compile and run without TPU hardware — the
analog of the reference's single-box multinode emulation
(reference ``tests/multinode_helpers/mpi_wrapper2.sh`` slices
CUDA_VISIBLE_DEVICES per MPI rank)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# The test suite runs on the CPU (backends are not initialised yet at
# conftest import time).
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)


def pytest_addoption(parser):
    # Compile cost dominates the suite on the 1-core CPU box; a full run
    # exceeds a 10-minute window. `--shard i/n` deterministically
    # partitions tests so N short invocations cover everything. THREE
    # shards fit 10-minute windows on this box (r5 final green run:
    # 1/3 = 8:28, 2/3 = 8:42, 3/3 = 8:08 — 291 passed); use --shard i/4
    # when a tighter (<8 min guaranteed) window is needed:
    #   for i in 1 2 3; do pytest tests/ -q --shard $i/3; done
    parser.addoption(
        "--shard", default=None,
        help="deterministic test sharding as i/n (1-based)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full-training/example tests; deselect with -m 'not slow' "
        "for a fast iteration loop",
    )


def pytest_collection_modifyitems(config, items):
    shard = config.getoption("--shard")
    if not shard:
        return
    i, n = (int(x) for x in shard.split("/"))
    order = sorted(items, key=lambda it: it.nodeid)
    keep = {id(it) for idx, it in enumerate(order) if idx % n == i - 1}
    deselected = [it for it in items if id(it) not in keep]
    items[:] = [it for it in items if id(it) in keep]
    if deselected:
        config.hook.pytest_deselected(items=deselected)
