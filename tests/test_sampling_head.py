"""The step's sampling head does what its batch asks (ISSUE 41): the
pipelined step program is chosen, on every dispatch, from the batch's
decode-head arrays (``serve/sampling.choose_sample_mode``) and from no
``ServingConfig`` field.

(a) an all-greedy run under a default config compiles only the argmax
    head's programs, and their lowered text holds no sort over the
    logits and no random draw;
(b) whatever head a batch takes, the generations are bitwise those of
    the full-sort reference head (the same run with the choice patched
    to ``("full", 0)``): greedy, temperature-only, top-k and top-p
    batches and a mix that changes mid-run, on the padded step (llama)
    and on a packed rung (Mixtral, LFM2);
(c) each (chunk, head) ladder is compiled once and a return to a head
    seen before compiles nothing;
(f) ``SchedulerStats.head_steps`` / ``head_greedy_steps`` count them.

Tiny llama, Mixtral and LFM2-MoE on the CPU in float32, ``kernels="xla"``.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.metrics import SchedulerStats
from flexflow_tpu.models import lfm2_moe, llama, mixtral
from flexflow_tpu.serve import (
    GenerationConfig,
    InferenceEngine,
    RequestManager,
    ServingConfig,
)
from flexflow_tpu.serve import engine as engine_mod
from flexflow_tpu.serve.engine import program_name

# the geometry is the test's own: prompts of 5 to 17 tokens are one to
# three chunks of 8, and the ladder is (8, 16, 32), two packed rungs
R, C, PS = 4, 8, 8


def _config(name):
    if name == "llama":
        return llama, llama.LLaMAConfig.tiny(dtype=jnp.float32)
    if name == "mixtral":
        return mixtral, mixtral.tiny(dtype=jnp.float32)
    return lfm2_moe, lfm2_moe.tiny(dtype=jnp.float32)


@pytest.fixture(scope="module", params=["llama", "mixtral", "lfm2_moe"])
def model(request):
    return _config(request.param)[0]


@pytest.fixture
def manager(tiny_servers):
    """``manager(model, ...)``: a scheduler of its own (its counters start
    at zero) over the file's kept engine of that shape, or with
    ``fresh=True`` over an engine nobody else has compiled a head into."""
    def get(model, slots=R, chunk=C, **kw):
        return RequestManager(tiny_servers(
            model, max_requests_per_batch=slots, max_sequence_length=48,
            prefill_chunk=chunk, max_spec_tree_tokens=8, page_size=PS,
            **kw).engine)

    return get


def _prompts(n, vocab=250):
    return [[(i * 37 + j * 11 + 3) % vocab for j in range(5 + (i * 5) % 13)]
            for i in range(n)]


def _finish(rm, rids, took=None):
    """Serve to the end; ``took`` gathers the head of every pipelined
    step on the way."""
    more = True
    while more:
        before = rm.stats.head_steps
        more = rm.step()
        if took is not None and rm.stats.head_steps > before:
            took.add(rm.engine.step_head)
    rm.drain()
    return [list(rm.requests[r].output_tokens) for r in rids]


def _heads(eng):
    """The (mode, cap) of every pipelined step program the engine holds."""
    return {k[3:] for k in eng._steps
            if isinstance(k, tuple) and k[0] in ("mixed_fused", "mixed_packed")}


# ---------------------------------------------------------------------------
# (a) a greedy server compiles no sort and no draw


def _lower(eng, key):
    chunk = key[1]
    z = lambda *shape: jnp.zeros(shape, jnp.int32)
    return eng._steps[key].lower(
        eng.params, eng.cache, z(R), z(R, chunk), jnp.zeros((R,), jnp.bool_),
        z(R, chunk), z(R), jax.random.PRNGKey(0), jnp.ones((R,), jnp.bool_),
        jnp.ones((R,), jnp.float32), jnp.ones((R,), jnp.float32), z(R),
        page_table=eng.page_table_device(),
    ).as_text()


def _sorted_shapes(text):
    """The operand type of every sort in a lowered program."""
    return re.findall(r"stablehlo\.sort.*?\}\) : \(tensor<([^>]*)>", text,
                      re.S)


def test_a_greedy_server_compiles_the_argmax_head_alone(model, manager):
    """Default ``ServingConfig``, every request greedy: every pipelined
    key is tagged ``("greedy", 0)``, the programs keep the unmarked
    names, and neither the C=1 nor the C=chunk program sorts its
    logits or draws; the full head of the same engine does both (so the
    reading is of the head, not of how it is read)."""
    rm = manager(model, fresh=True)     # what it has compiled is the reading
    eng = rm.engine
    assert eng.serving.fused_decode == ()
    _finish(rm, [rm.submit(p, max_new_tokens=5) for p in _prompts(6)])
    assert _heads(eng) == {("greedy", 0)}
    assert eng._ladders_compiled <= {(C, "greedy", 0)}
    assert rm.stats.sync_steps == 0 and rm.stats.decode_steps > 0
    assert rm.stats.head_greedy_steps == rm.stats.head_steps == rm.stats.steps
    V = eng.cfg.vocab_size
    logits = f"{R}x{V}xf32"
    for chunk in (1, C):
        key = ("mixed_fused", chunk, False, "greedy", 0)
        assert program_name(key) == f"ff_step_c{chunk}"
        text = _lower(eng, key)
        assert f"module @jit_ff_step_c{chunk} " in text
        assert logits not in _sorted_shapes(text)
        assert "threefry" not in text and "rng" not in text
        full = ("mixed_fused", chunk, False, "full", 0)
        eng._get_mixed_step(chunk, False, "full", 0)
        text = _lower(eng, full)
        assert f"module @jit_ff_step_c{chunk}_full " in text
        assert logits in _sorted_shapes(text) and "threefry" in text


# ---------------------------------------------------------------------------
# (b) every head's generations are the full head's, to the bit


def _gens(kind, n):
    greedy = GenerationConfig()
    some = {
        "greedy": [greedy],
        "temperature": [GenerationConfig(do_sample=True, temperature=0.8,
                                         topk=0, topp=2.0),
                        GenerationConfig(do_sample=True, temperature=1.3,
                                         topk=0, topp=2.0)],
        "topk": [GenerationConfig(do_sample=True, temperature=0.9, topk=5,
                                  topp=2.0), greedy,
                 GenerationConfig(do_sample=True, temperature=1.1, topk=17,
                                  topp=2.0)],
        "topp": [GenerationConfig(do_sample=True, temperature=0.9, topk=0,
                                  topp=0.8), greedy,
                 GenerationConfig(do_sample=True, temperature=1.2, topk=7,
                                  topp=0.6)],
    }[kind]
    return [some[i % len(some)] for i in range(n)]


def _serve(manager, model, kind, n=7):
    """On the file's kept engine of ``model`` (every head's ladder is
    compiled into it once, the full head's too): the run's scheduler, its
    outputs and the heads its steps took."""
    rm, took = manager(model, sanitizers=("retrace",)), set()
    rids = [rm.submit(p, g, max_new_tokens=6)
            for p, g in zip(_prompts(n), _gens(kind, n))]
    return rm, _finish(rm, rids, took), took


@pytest.mark.parametrize("kind, head, others", [
    ("greedy", ("greedy", 0), set()),
    ("temperature", ("sample", 0), set()),
    ("topk", ("topk", 32), {("topk", 8), ("greedy", 0)}),
    ("topp", ("full", 0), {("greedy", 0)}),
])
def test_generations_are_the_full_heads(model, manager, kind, head, others,
                                        monkeypatch):
    """More requests than slots, so admissions come in waves and the
    batch's rows change as requests finish: a run takes ``head``, may
    take ``others`` as its rows come and go, and its tokens are those
    of the run that sorts at every step."""
    rm, outs, took = _serve(manager, model, kind)
    assert all(len(o) == 6 for o in outs)
    assert took <= _heads(rm.engine)
    assert head in took and took <= {head} | others, took
    assert rm.engine.retrace_guard.retraces == 0
    s = rm.stats
    assert s.head_steps == s.mixed_steps + s.decode_steps == s.steps
    if kind == "greedy":
        assert s.head_greedy_steps == s.head_steps
    if kind == "temperature":
        assert s.head_greedy_steps == 0

    monkeypatch.setattr(engine_mod, "choose_sample_mode",
                        lambda *a: ("full", 0))
    ref, want, took = _serve(manager, model, kind)
    assert took == {("full", 0)}
    assert ref.stats.head_greedy_steps == 0
    assert outs == want


def _mixed_run(manager, model, slots=R, chunk=C, fresh=False):
    """Greedy requests decode; a top-k request is admitted among them,
    finishes, and the greedy ones decode on; then a second one."""
    rm = manager(model, slots, chunk, fresh=fresh, sanitizers=("retrace",))
    guard = rm.engine.retrace_guard
    prompts = _prompts(5)
    rids = [rm.submit(p, max_new_tokens=20) for p in prompts[:3]]
    log = []                             # (head of the step, compiles so far)

    def steps(n):
        for _ in range(n):
            before = rm.stats.head_steps
            more = rm.step()
            if rm.stats.head_steps > before:
                log.append((rm.engine.step_head, guard.total_compiles))
            if not more:
                break

    steps(5)
    topk = GenerationConfig(do_sample=True, temperature=0.9, topk=5, topp=2.0)
    rids.append(rm.submit(prompts[3], topk, max_new_tokens=3))
    steps(8)
    rids.append(rm.submit(prompts[4], topk, max_new_tokens=3))
    steps(1000)
    return rm, _finish(rm, rids), log


@pytest.mark.parametrize("R, C", [
    (R, C),
    # (32, 48, 96, 192): each head's ladder holds the admission rung
    # (ISSUE 45), and the admitted top-k row's steps run on it
    (12, 16)], ids=["4x8", "12x16-admission"])
def test_a_mix_that_changes_mid_run(model, manager, monkeypatch, R, C):
    rm, outs, log = _mixed_run(manager, model, R, C, fresh=True)  # counts compiles
    assert [len(o) for o in outs] == [20, 20, 20, 3, 3]
    heads = [h for h, _ in log]
    # greedy, then the top-k head while the sampling row is there, then
    # greedy again, then top-k again
    runs = [h for i, h in enumerate(heads) if i == 0 or heads[i - 1] != h]
    assert runs[:4] == [("greedy", 0), ("topk", 8), ("greedy", 0),
                        ("topk", 8)], runs
    # (c) a head's ladder is compiled when its first batch arrives and
    # never again: nothing is compiled after the first top-k step's
    # (the decode program of that head comes with its first decode step)
    eng, guard = rm.engine, rm.engine.retrace_guard
    guard.assert_one_compile_per_key()
    assert guard.retraces == 0
    second_greedy = heads.index(("greedy", 0), heads.index(("topk", 8)))
    settled = max(n for h, n in log[:second_greedy + 1])
    back = [n for h, n in log[second_greedy:] if h == ("greedy", 0)]
    assert back and set(back) <= {settled}, (settled, back)
    assert _heads(eng) == {("greedy", 0), ("topk", 8)}
    ladder = eng.pack_ladder(C)
    if ladder:
        assert eng._ladders_compiled == {(C, "greedy", 0), (C, "topk", 8)}
        assert ladder == {8: (8, 16), 16: (32, 48, 96)}[C]
        assert C == 8 or set(rm.stats.steps_by_width) == {32}
    counts = guard.compile_counts()
    for head in (("greedy", 0), ("topk", 8)):
        for width in ladder:
            assert counts[("mixed_packed", C, width, *head)] == 1
        assert counts[("mixed_fused", C, False, *head)] == 1
        assert counts[("mixed_fused", 1, False, *head)] == 1
    # (f) the counters: every pipelined step, and the all-greedy ones
    s = rm.stats
    assert s.head_steps == len(heads) == s.steps
    assert s.head_greedy_steps == heads.count(("greedy", 0))
    assert 0 < s.head_greedy_steps < s.head_steps
    snap = s.snapshot()
    assert (snap["head_steps"], snap["head_greedy_steps"]) == (
        s.head_steps, s.head_greedy_steps)
    assert f"greedy_head={s.head_greedy_steps}/{s.head_steps} " in s.report()

    monkeypatch.setattr(engine_mod, "choose_sample_mode",
                        lambda *a: ("full", 0))
    ref, want, log = _mixed_run(manager, model, R, C)
    assert {head for head, _ in log} == {("full", 0)}
    assert outs == want


def test_the_counters_count_by_head():
    s = SchedulerStats()
    for mode in ("greedy", "greedy", "topk", "full", "sample", "greedy"):
        s.note_head(mode)
    assert (s.head_steps, s.head_greedy_steps) == (6, 3)


def test_the_head_is_no_option():
    """Nothing of ``ServingConfig`` names the head: it is the batch's."""
    import dataclasses

    fields = {f.name for f in dataclasses.fields(ServingConfig)}
    assert not {f for f in fields if "sampl" in f or "head_" in f}
    with pytest.raises(ValueError, match="unknown fused_decode entry"):
        mod, cfg = _config("llama")
        InferenceEngine(mod, cfg, None, ServingConfig(
            kv_layout="paged", fused_decode=("sampling",)))
