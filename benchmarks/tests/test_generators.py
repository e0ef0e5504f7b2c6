"""Every seed offers the same work in another order."""
import hashlib
import json

import numpy as np
import pytest

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


def _drive(gen, turns=400):
    """A fixed schedule of completions: every request the generator
    yields, and the live count after every turn."""
    gen.start(0.0)
    now, live, sent, counts = 0.0, [], [], []
    for turn in range(turns):
        due = gen.due(now)
        sent += due
        live += due
        counts.append(len(live))
        now += 0.05
        # the oldest live request completes on every third turn, and
        # one more on every seventh
        for _ in range((turn % 3 == 0) + (turn % 7 == 0)):
            if live:
                gen.completed(live.pop(0), now)
    return sent, counts


def _digest(sent):
    h = hashlib.sha256()
    for s in sent:
        h.update(json.dumps([s.client, s.prompt, s.max_new, s.due, s.judged]).encode())
    return h.hexdigest()[:16]


# the sequence of ``Sent`` the generator of PR 36 yields under ``_drive``
# (its ``closed.py`` from ``git archive``, PR 38): a traffic file without
# the ``order`` key runs what it ran then, byte for byte
PINNED = {
    ("decode-closed", 3): "9e53099fc4d54e51",
    ("decode-closed", 2**31 + 4): "c770fbe85886d739",
    ("decode-wide-closed", 3): "f0dd85507fe40921",
    ("decode-wide-closed", 2**31 + 4): "633210008f4cbbd0",
    ("longdoc-closed", 3): "0aa738f1470a5921",
    ("longdoc-closed", 2**31 + 4): "7ee0a6fd8bcd6154",
    ("prefill-closed", 3): "7d8a7d3092ff9ce0",
    ("prefill-closed", 2**31 + 4): "420c1299b4022750",
}


@pytest.mark.parametrize("mix,seed", sorted(PINNED))
def test_without_the_order_key_a_seed_yields_what_it_always_did(mix, seed):
    kind = spec.load_module("generators", "closed")
    traffic = spec.load_json("traffic", mix + ".json")
    if mix != "prefill-closed":  # the other cells' files are as they were
        assert "order" not in traffic
    traffic.pop("order", None)
    gen = kind.Generator(traffic, np.random.default_rng(seed), 32000, 50.0)
    sent, _ = _drive(gen)
    assert _digest(sent) == PINNED[mix, seed]


def _ordered(seed, **over):
    kind = spec.load_module("generators", "closed")
    traffic = dict(spec.load_json("traffic", "prefill-closed.json"), **over)
    return kind.Generator(traffic, np.random.default_rng(seed), 32000, 50.0)


def test_prefill_closed_states_its_order():
    assert isinstance(spec.load_json("traffic", "prefill-closed.json")["order"], int)


def test_under_one_order_every_seed_plays_the_same_lengths_on_other_seats():
    a, b = _ordered(3), _ordered(2**31 + 4)
    # the same sequences of lengths, cut first requests and all ...
    assert sorted(map(tuple, a.queues)) == sorted(map(tuple, b.queues))
    # ... held by other clients, with other tokens
    assert a.queues != b.queues
    sa, ca = _drive(a)
    sb, cb = _drive(b)
    n = len(a.queues)
    assert set(ca) == set(cb) == {n}  # closed, no think time: every client live
    assert [len(s.prompt) for s in sa[:n]] != [len(s.prompt) for s in sb[:n]]
    whole = len(sa) // n * n - n  # rounds that every client has sent
    assert sorted((len(s.prompt), s.max_new) for s in sa[:whole]) == sorted(
        (len(s.prompt), s.max_new) for s in sb[:whole])
    assert {tuple(s.prompt) for s in sa}.isdisjoint(tuple(s.prompt) for s in sb)
    assert not any(s.judged for s in sa[:n]) and all(s.judged for s in sa[n:])


def test_another_order_is_another_sequence_of_the_same_rounds():
    a, b = _ordered(3), _ordered(3, order=39)
    assert sorted(map(tuple, a.queues)) != sorted(map(tuple, b.queues))
    for r in (1, 2, 40):  # round by round still the same multiset of lengths
        for i in (0, 1):  # prompts, answers; paired otherwise
            assert sorted(q[r][i] for q in a.queues) == sorted(q[r][i] for q in b.queues)
