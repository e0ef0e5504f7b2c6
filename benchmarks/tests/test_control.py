"""The comparison that decides ``correct`` has been shown to fail: at a
size a test run can hold (the rehearsal preset of ``run.py``: tiny
widths, CPU, interpret-mode kernels, float32) the served path comes out
correct and the control — the reference put in the program's place and
computed in int8, ``references/decoder.py`` — does not, by the same
``probe.verdict`` the benchmark's runs use. At the cells' own size the
control ran on the chip (``tools/control.py``; the readings are in each
configuration file and in PERF.md).

  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import numpy as np
import pytest


@pytest.mark.parametrize("workload", ["mistral-7b.decode-closed",
                                      "mixtral-8x7b.prefill-closed"])
def test_control_fails_and_served_passes(workload):
    from benchmarks import run as bench_run
    from benchmarks.harness import model, probe, spec

    cell = spec.Cell(workload)
    assert cell.config["tolerance"]["control"] == "ref_int8"
    bench_run.tiny(cell)
    config = cell.config
    reference = spec.load_module("references", config["reference"])
    quiet = lambda msg: None
    for seed in (1, 2, 3):
        llm, params = model.build_server(config, seed)
        seqs, judged = probe.served_logits(
            llm.engine, cell.traffic, np.random.default_rng(seed))
        want = probe.reference_rows(config, params, seqs, judged)
        assert probe.verdict(config, probe.against(config, want, judged), quiet)
        logits = reference.judged_logits(params, config, *want[1],
                                         control_bits=8)[0]
        control = [(row, pos, logits[row, j, 0]) for (row, pos, _), j
                   in zip(judged, want[2])]
        assert not probe.verdict(config, probe.against(config, want, control), quiet)


def test_routing_0_is_the_plain_pass_and_the_others_are_not():
    """The single-token pass that carries the other routings gives, for
    float32's own routing, the plain pass's logits (window mask too)."""
    from benchmarks import run as bench_run
    from benchmarks.harness import model, spec

    cell = spec.Cell("mixtral-8x7b.prefill-closed")
    bench_run.tiny(cell)
    config = dict(cell.config, sliding_window=8)
    reference = spec.load_module("references", config["reference"])
    params = model.make_params(model.family_of(config), model.decoder_config(config),
                               model.make_mesh(config), model.seed_key(7))
    tokens = np.random.default_rng(7).integers(0, config["vocab_size"], (2, 24))
    judge = np.array([[23, 9, 0], [5, 17, 0]])
    logits, flip_margin, margin = reference.judged_logits(params, config, tokens, judge)
    plain = reference.judged_logits(params, config, tokens, judge, routings=False)[0]
    assert logits.shape[2] == 2 ** config["num_hidden_layers"] and plain.shape[2] == 1
    assert np.abs(logits[:, :, 0] - plain[:, :, 0]).max() < 1e-5 * np.abs(plain).max()
    assert (flip_margin[:, :, 0] == 0).all() and (flip_margin[:, :, 1:] > 0).all()
    assert np.abs(logits[:, :, 1] - plain[:, :, 0]).max() > 1e-3 * np.abs(plain).max()
