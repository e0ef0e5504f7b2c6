"""Machine-model tests: 2-D torus link multiplicity + the user-editable
machine-config file (the TPU analogs of the reference's
``machine_config_example`` + ``NetworkedMachineModel``,
machine_model.cc:1-1287)."""
import math

import pytest

from flexflow_tpu.search.machine_model import (
    CollectiveModel,
    TPUChip,
    TPUTopology,
)


def test_torus_multiplicity_speeds_up_model_axis_allreduce():
    """A v5e 4x4 slice is a 2-D torus with 2 links per dimension: an
    all-reduce over a 4-wide model axis must come out ~2x faster than
    the single-ring estimate, and a whole-slice 16-wide axis ~4x."""
    chip = TPUChip.v5e()
    # latency-free so the bandwidth terms compare exactly (hop latency
    # is per-hop, not per-link, and does not shrink with striping)
    flat = CollectiveModel(
        TPUTopology(chip=chip, num_chips=16, per_hop_latency=0.0)
    )
    torus = CollectiveModel(
        TPUTopology(chip=chip, num_chips=16, torus=(4, 4),
                    per_hop_latency=0.0)
    )
    nbytes = 256e6
    t_flat = flat.all_reduce(nbytes, 4, "model")
    t_torus = torus.all_reduce(nbytes, 4, "model")
    assert t_torus == pytest.approx(t_flat / 2, rel=1e-3)

    t_flat16 = flat.all_reduce(nbytes, 16, "model")
    t_torus16 = torus.all_reduce(nbytes, 16, "model")
    assert t_torus16 == pytest.approx(t_flat16 / 4, rel=1e-3)


def test_torus_multiplicity_walks_axis_order_on_asymmetric_torus():
    """Mesh axes map onto the torus innermost-first (core.mesh
    AXIS_ORDER): on a 2x8 torus with model=2, data=8, the data axis
    rides ONLY the single size-8 torus dim (2 links) — the old
    start-at-dim-0 walk credited it with both dims (4 links)."""
    topo = TPUTopology(chip=TPUChip.v5e(), num_chips=16, torus=(2, 8))
    degrees = {"data": 8, "expert": 1, "pipe": 1, "seq": 1, "model": 2}
    assert topo.axis_link_multiplicity("model", 2, degrees) == 2
    assert topo.axis_link_multiplicity("data", 8, degrees) == 2
    # without the degree map the conservative dim-0 walk is unchanged
    assert topo.axis_link_multiplicity("data", 8) == 4
    # inner axes consuming the whole torus leave the outer axis 1 link
    topo44 = TPUTopology(chip=TPUChip.v5e(), num_chips=16, torus=(4, 4))
    d2 = {"data": 2, "expert": 1, "pipe": 1, "seq": 1, "model": 16}
    assert topo44.axis_link_multiplicity("data", 2, d2) == 1


def test_torus_multiplicity_never_applies_to_dcn_axes():
    topo = TPUTopology(
        chip=TPUChip.v5e(), num_chips=16, torus=(4, 4), dcn_axes=("data",)
    )
    assert topo.axis_link_multiplicity("data", 4) == 1
    assert topo.axis_link_multiplicity("model", 4) == 2


def test_explicit_axis_links_override_torus():
    topo = TPUTopology(
        chip=TPUChip.v5e(), num_chips=16, torus=(4, 4),
        axis_links={"model": 3},
    )
    assert topo.axis_link_multiplicity("model", 4) == 3


def test_from_file_v5e16(tmp_path):
    p = tmp_path / "machine.cfg"
    p.write_text(
        """
# v5e-16
chip = v5e
num_chips = 16
torus = 4x4
dcn_axes = data
mxu_efficiency = 0.60   # calibrated override
dcn_bandwidth = 20e9
"""
    )
    topo = TPUTopology.from_file(str(p))
    assert topo.chip.name == "v5e"
    assert topo.num_chips == 16
    assert topo.torus == (4, 4)
    assert topo.dcn_axes == ("data",)
    assert topo.chip.mxu_efficiency == pytest.approx(0.60)
    assert topo.dcn_bandwidth == pytest.approx(20e9)
    # untouched preset fields survive
    assert topo.chip.bf16_flops == pytest.approx(197e12)


def test_from_file_custom_chip_and_errors(tmp_path):
    p = tmp_path / "machine.cfg"
    p.write_text(
        """
chip = custom
bf16_flops = 100e12
hbm_bandwidth = 500e9
hbm_capacity = 8e9
ici_bandwidth = 30e9
num_chips = 8
"""
    )
    topo = TPUTopology.from_file(str(p))
    assert topo.chip.bf16_flops == pytest.approx(100e12)
    assert topo.chip.hbm_capacity == pytest.approx(8e9)

    bad = tmp_path / "bad.cfg"
    bad.write_text("chip = v5e\nnot_a_key = 3\n")
    with pytest.raises(ValueError, match="unknown machine-config"):
        TPUTopology.from_file(str(bad))

    mismatch = tmp_path / "mismatch.cfg"
    mismatch.write_text("chip = v5e\nnum_chips = 16\ntorus = 4x2\n")
    with pytest.raises(ValueError, match="torus"):
        TPUTopology.from_file(str(mismatch))


def test_search_accepts_file_loaded_topology(tmp_path):
    """optimize() must run against a file-loaded topology — the
    machine-config workflow end to end (reference --machine-model-file)."""
    import flexflow_tpu as ff
    from flexflow_tpu.search import optimize

    p = tmp_path / "machine.cfg"
    p.write_text("chip = v5e\nnum_chips = 8\ntorus = 4x2\n")
    topo = TPUTopology.from_file(str(p))

    m = ff.FFModel(ff.FFConfig(batch_size=4, num_devices=8))
    t = m.create_tensor((4, 64), name="x")
    t = m.dense(t, 128)
    m.dense(t, 64)
    g2, strat, report = optimize(m.graph, num_devices=8, topo=topo, budget=4)
    assert report.best_cost > 0
    assert strat.machine.num_devices == 8


def test_calibrate_chip_measures_and_clamps():
    """calibrate_chip must return measured efficiencies within the
    documented clamp [0.05, 8.0] — the upper bound is deliberately >1
    (hardware faster than the preset, e.g. a v5p calibrated against the
    v5e numbers, legitimately measures above the assumed peak; see the
    clamp comment in machine_model.calibrate_chip). On this CPU host the
    fractions-of-TPU-peak are tiny and clamp to the 0.05 floor, proving
    the measurement actually ran. Small microbench sizes: the test only
    asserts the clamp, and the full-size default (~137 GFLOP matmul)
    costs ~20s of tier-1 budget on the 1-core CPU host."""
    from flexflow_tpu.search.machine_model import calibrate_chip

    chip = TPUChip.v5e()
    cal = calibrate_chip(chip, iters=1, n=512, stream_mb=16)
    assert 0.05 <= cal.mxu_efficiency <= 8.0
    assert 0.05 <= cal.hbm_efficiency <= 8.0
    # presets elsewhere untouched
    assert cal.bf16_flops == chip.bf16_flops


def test_compile_uses_machine_config_file(tmp_path):
    """FFConfig.machine_config_file must reach the Unity search
    (reference --machine-model-file end to end)."""
    import numpy as np

    import flexflow_tpu as ff

    p = tmp_path / "machine.cfg"
    p.write_text("chip = v5e\nnum_chips = 8\ntorus = 4x2\n")
    cfg = ff.FFConfig(
        batch_size=8, num_devices=8, search_budget=2,
        machine_config_file=str(p),
    )
    m = ff.FFModel(cfg)
    t = m.create_tensor((8, 16), name="x")
    t = m.dense(t, 32, activation="relu")
    t = m.dense(t, 4)
    m.softmax(t)
    m.compile(optimizer=ff.SGDOptimizer(lr=0.05), auto_parallel=True)
    x = np.random.default_rng(0).normal(size=(16, 16)).astype(np.float32)
    y = np.random.default_rng(0).integers(0, 4, size=(16,)).astype(np.int32)
    m.fit(x, y, epochs=1, verbose=False)
    assert m._search_report is not None
