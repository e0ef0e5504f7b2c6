"""Every seed offers the same work in another order."""
import numpy as np

from benchmarks.harness import lengths, spec


def test_closed_first_request_is_cut_and_never_a_sample():
    kind = spec.load_module("generators", "closed")
    traffic = spec.load_json("traffic", "decode-closed.json")
    gen = kind.Generator(traffic, np.random.default_rng(3), 100, 10.0)
    gen.start(0.0)
    first = gen.due(0.0)
    assert len(first) == traffic["clients"]
    assert not any(s.judged for s in first)
    shares = sorted(s.max_new / traffic["answer_tokens"]["hi"] for s in first)
    assert shares[0] < 0.15 and shares[-1] > 0.5  # out of phase from the start
    gen.completed(first[0], 1.0)
    (nxt,) = gen.due(1.0)
    assert nxt.judged and nxt.due == 1.0 and nxt.client == first[0].client
    other = kind.Generator(traffic, np.random.default_rng(2**31 + 4), 100, 10.0)
    for r in (1, 2, 40):  # round by round the clients hold the same lengths
        assert sorted(q[r] for q in gen.queues) != [q[r] for q in gen.queues]
        assert sorted(p for p, _ in (q[r] for q in gen.queues)) == sorted(
            p for p, _ in (q[r] for q in other.queues))
    assert traffic["answer_tokens"]["lo"] <= nxt.max_new <= traffic["answer_tokens"]["hi"]


def test_quantiles_stay_inside_the_range():
    dist = {"dist": "uniform", "lo": 512, "hi": 832}
    xs = lengths.block(dist, 200, 0, np.random.default_rng(0))
    assert 512 <= min(xs) <= 514 and 830 <= max(xs) <= 832
    assert abs(sorted(xs)[100] - 672) <= 2
