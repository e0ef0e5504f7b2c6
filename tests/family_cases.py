"""The cases every served family answers, run in the family's own file on
the server that file builds (``--dist loadfile`` gives a file one worker,
so a second file could share nothing with it).

A family's test file takes them with::

    from family_cases import *  # noqa: F401,F403
    FAMILIES = {"<name>": Family(<module>, ALWAYS | {...})}

and nothing cross-family is touched when a family is added. This module
is not collected itself (its name is no ``test_*.py``).

(a) Device time by sublayer (ISSUE 42): every working operation of the
    family's compiled step programs lies under an ``ff.*`` scope, and the
    scopes met are the family's own.
(b) The scheduler keeps a mixed step under a rung (ISSUE 61): a closed
    loop whose steps give prompt tokens up
    (``SchedulerStats.rung_trims``), so that chunks start off a page
    boundary and span two pages, generates, greedy, the tokens of the same
    requests served one at a time; no step of the run sits in a rung that
    it passes the next narrower one by no more than the slots. On the
    Pallas kernels the family's cell runs (in interpret mode). The
    families that keep a state a slot beside the pool also split a prompt
    of ONE chunk at any token. 4 slots x chunk 16 on pages of 16 lines:
    the ladder is (16, 32, 64), DeepSeek's cell at an eighth of its
    extents, and three decoding rows beside one prompt's chunk hold 19
    tokens, two beside two 34.
"""
import re
from typing import Any, Callable, NamedTuple, Optional

import numpy as np
import pytest

from flexflow_tpu.obs.sublayers import SUBLAYERS, parse_scope_map
from flexflow_tpu.serve import RequestManager

__all__ = [
    "ALWAYS", "Family", "family_server", "step_texts", "pytest_generate_tests",
    "test_every_working_operation_has_a_sublayer",
    "test_trimmed_steps_generate_what_one_request_at_a_time_does",
]

CHUNK, PAGE, SLOTS = 16, 16, 4   # conftest.TINY_SERVING's, as the cases read them
ATTENTION = {"ff.attn.proj", "ff.attn.core", "ff.attn.write"}
ALWAYS = frozenset(ATTENTION | {"ff.ffn", "ff.head", "ff.glue"})


class Family(NamedTuple):
    """What a file declares of a family it serves: its module, the
    sublayers its paged step has, the kernels on which its mixed step is
    trimmed to a rung (none where the family packs no step), the weights
    the file judges on (``conftest.TinyServers.params``), and what its
    server's configuration adds to the tiny one."""
    module: Any
    sublayers: frozenset
    trims: tuple = ("pallas",)
    draw: Optional[Callable] = None
    serving: dict = {}


@pytest.hookimpl(tryfirst=True)  # the family leads a case's id
def pytest_generate_tests(metafunc):
    """The cases of this module once a family of the file that took them
    (and once an arm of its ``trims``)."""
    if metafunc.function.__module__ != __name__:
        return
    families = metafunc.module.FAMILIES
    if "kernels" in metafunc.fixturenames:
        metafunc.parametrize("family, kernels", [
            (name, arm) for name, f in families.items() for arm in f.trims])
    elif "family" in metafunc.fixturenames:
        metafunc.parametrize("family", list(families))


@pytest.fixture(scope="module")
def family_server(request, tiny_servers):
    """``family_server(name, kernels="pallas")``: the file's kept tiny
    server of that family, the one its own cases on those kernels use."""
    families = request.module.FAMILIES

    def get(name, kernels="pallas"):
        f = families[name]
        return tiny_servers(f.module, draw=f.draw,
                            **{"kernels": kernels, **f.serving})

    return get


# ---------------------------------------------------------------------------
# (a) every working operation of the family's step lies under a scope

# the operations that do a step's work: none may lie outside the scopes
WORK = ("dot", "convolution", "sort", "scatter", "gather", "custom-call")
_OPCODE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (?:\([^=]*\)|\S+) ([\w\-]+)\(", re.M)


def serve_one_prompt(server):
    """One prompt of two chunks and a few decode steps: the greedy head's
    mixed and C=1 programs are compiled after it."""
    rm = server.manager
    rid = rm.submit([(7 * i + 3) % 250 for i in range(CHUNK + 2)],
                    max_new_tokens=4)
    while not rm.result(rid).profile.finish_time:
        rm.step()
    rm.drain()


@pytest.fixture(scope="module")
def step_texts(family_server):
    """family -> {program: compiled text} of the greedy head's step
    programs, the C=1 one and every width of the mixed one.
    ``step_program_texts`` compiles what it is asked for a second time,
    so it is asked once a family and for these names alone (a kept
    server holds other heads' and callers' programs too)."""
    made = {}

    def get(family):
        if family not in made:
            server = family_server(family)
            serve_one_prompt(server)
            eng = server.engine
            names = ["ff_step_c1", f"ff_step_c{CHUNK}"] + [
                f"ff_step_c{CHUNK}_t{w}" for w in eng.pack_ladder(CHUNK)]
            made[family] = eng.step_program_texts(names=names)
            assert sorted(made[family]) == sorted(names)
        return made[family]

    return get


@pytest.mark.parametrize("step", ["c1", "mixed"])
def test_every_working_operation_has_a_sublayer(request, step_texts, family, step):
    texts = {n: t for n, t in step_texts(family).items()
             if (n == "ff_step_c1") == (step == "c1")}
    assert texts
    met = set()
    for name, text in texts.items():
        scopes = parse_scope_map(text)
        # an instruction the compiler made of others carries no op_name
        # at all (XLA:CPU's second dot of a three-operand einsum): that is
        # what step.sub_ms.unscoped is for, and no scope could reach it
        named = {m.group(1) for m in _OPCODE.finditer(text)
                 if "op_name=" in text[m.end():].split("\n", 1)[0]}
        opcodes = dict(_OPCODE.findall(text))
        work = {i: op for i, op in opcodes.items()
                if op in WORK and i in named}
        assert work, f"{name}: no working operation parsed"
        bare = {i: op for i, op in work.items() if scopes[i] is None}
        assert not bare, f"{name}: under no ff.* scope: {bare}"
        # a matmul is some sublayer's work, never the step's glue
        glue = {i: op for i, op in work.items()
                if op in ("dot", "convolution") and scopes[i] == "ff.glue"}
        assert not glue, f"{name}: matmuls under ff.glue: {glue}"
        met |= {s for s in scopes.values() if s is not None}
    assert met == request.module.FAMILIES[family].sublayers
    assert met <= {"ff." + s for s in SUBLAYERS}


# ---------------------------------------------------------------------------
# (b) trimmed steps generate what one request at a time does


def prompts(n, least=40, spread=37, vocab=250):
    """Prompts of three to five chunks that end inside one, unless
    told other lengths."""
    rng = np.random.default_rng(61)
    return [rng.integers(1, vocab, least + (i * 11) % spread).tolist()
            for i in range(n)]


def watch(rm, monkeypatch):
    """Every mixed step of ``rm`` from here on: (real tokens, width,
    tokens given up, the rows' first positions and real queries). The
    engine may be a kept one: ``monkeypatch`` takes the watch off it."""
    steps, eng = [], rm.engine
    run, scratch = eng.run_mixed, eng.scratch_pos

    def run_mixed(last, tokens, use_last, positions, *a, **kw):
        positions = np.asarray(positions)
        if positions.shape[1] > 1:  # not the decode step
            steps.append(dict(first=positions[:, 0].copy(),
                              count=(positions != scratch).sum(1)))
        return run(last, tokens, use_last, positions, *a, **kw)

    def note(real, width, trimmed=0):
        steps[-1].update(real=real, width=width, trimmed=trimmed)
        type(rm.stats).note_step_tokens(rm.stats, real, width, trimmed)

    monkeypatch.setattr(eng, "run_mixed", run_mixed, raising=False)
    monkeypatch.setattr(rm.stats, "note_step_tokens", note, raising=False)
    return steps


def finish(rm):
    while rm.step():
        pass
    rm.drain()


def served(eng, requests, new, monkeypatch):
    """The outputs of ``requests`` served one at a time, then all at
    once (a closed loop over the engine's slots) by a scheduler of their
    own, and the second run's steps."""
    rm = RequestManager(eng)
    alone = []
    for i, p in enumerate(requests):
        rid = rm.submit(p, max_new_tokens=new(i))
        finish(rm)
        alone.append(list(rm.requests[rid].output_tokens))
    assert rm.stats.rung_trims == 0 and rm.stats.mixed_steps > 0
    steps = watch(rm, monkeypatch)
    rids = [rm.submit(p, max_new_tokens=new(i)) for i, p in enumerate(requests)]
    finish(rm)
    return rm, alone, [list(rm.requests[r].output_tokens) for r in rids], steps


def test_trimmed_steps_generate_what_one_request_at_a_time_does(
        family_server, family, kernels, monkeypatch):
    eng = family_server(family, kernels).engine
    ladder = eng.pack_ladder(CHUNK)
    assert ladder == (16, 32)
    rm, alone, together, steps = served(
        eng, prompts(9), lambda i: 6 + (i * 5) % 9, monkeypatch)
    assert together == alone
    s = rm.stats
    assert s.rung_trims > 0 and s.rung_trim_tokens >= s.rung_trims
    assert s.rung_trims == sum(st["trimmed"] > 0 for st in steps)
    assert s.rung_trim_tokens == sum(st["trimmed"] for st in steps)
    assert s.failed == 0 and s.preemptions == 0
    for st in steps:
        # on the narrowest program that holds it, and over no rung by
        # the few tokens that decoding rows add
        assert st["width"] == eng.pack_width(st["real"], CHUNK)
        assert st["real"] == st["count"].sum()
        assert not any(0 < st["real"] - w <= SLOTS for w in ladder), st
        if st["trimmed"]:
            assert st["real"] in ladder
    # the 19- and 34-token steps of DeepSeek's cell, an eighth the size
    assert {st["real"] + st["trimmed"] for st in steps if st["trimmed"]} >= {19, 34}
    # chunks that start off a page boundary and span two pages
    spans = [(int(f), int(n)) for st in steps
             for f, n in zip(st["first"], st["count"]) if n > 1 and f % PAGE]
    assert any(f // PAGE != (f + n - 1) // PAGE for f, n in spans), spans

    if not getattr(eng.model, "SLOT_STATE", ()):
        return
    # the 64-slot cells' step: prompts of ONE chunk, 13 to 16 tokens,
    # admitted beside three decoding rows. The chunk is the prompt's
    # last and is split all the same, at whatever token the rung says,
    # and the state beside the pool is carried across the split
    rm, alone, together, steps = served(
        eng, prompts(8, least=13, spread=4), lambda i: 4 + (i * 3) % 4,
        monkeypatch)
    assert together == alone and rm.stats.rung_trims > 0
    assert any(f > 0 and n > 1 and f + n <= CHUNK for st in steps
               for f, n in zip(st["first"], st["count"])), steps
