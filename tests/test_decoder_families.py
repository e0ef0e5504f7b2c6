"""The generic decoder's families that have no file of their own
(``models/transformer.py`` behind ``mistral``, ``llama`` and ``mixtral``)
answer the cases every family answers (tests/family_cases.py), and the
cases of those two subjects that want a server of the generic decoder:
the scope map asked of a serving engine, and the scheduler's trim where a
prompt's row is left empty and where there is no ladder.

Tiny presets on the CPU, float32; the Pallas kernels in interpret mode.
"""
from flexflow_tpu.models import llama, mistral, mixtral
from flexflow_tpu.obs import sublayers
from flexflow_tpu.obs.sublayers import scope_maps
from flexflow_tpu.serve import RequestManager
from flexflow_tpu.serve.request_manager import RequestStatus

from family_cases import *  # noqa: F401,F403 (the cases every family answers)
from family_cases import CHUNK, SLOTS, finish, prompts, serve_one_prompt, watch

FAMILIES = {
    # the llama-shaped family on XLA's kernels too; its Pallas server
    # under the strict retrace sentinel (the map is asked of it below)
    "mistral": Family(mistral, ALWAYS, trims=("xla", "pallas"),
                      serving=dict(sanitizers=("retrace",))),
    "llama": Family(llama, ALWAYS, trims=()),
    "mixtral": Family(mixtral, ALWAYS | {"ff.moe.route"}, trims=()),
}


# ---------------------------------------------------------------------------
# asking for the map traces, compiles and dispatches nothing


def test_asking_for_the_map_is_no_retrace(family_server, step_texts):
    step_texts("mistral")                # the greedy head's programs are there
    _, _, _, eng, rm, _ = family_server("mistral")
    counts = dict(eng.retrace_guard.compile_counts())
    before = (rm.stats.compiles, rm.stats.retraces, eng.dispatch_count,
              len(eng.retrace_guard.events))
    assert counts and set(counts.values()) == {1}
    maps = scope_maps([eng])              # raises under the strict sentinel
    assert eng.retrace_guard.compile_counts() == counts
    assert (rm.stats.compiles, rm.stats.retraces, eng.dispatch_count,
            len(eng.retrace_guard.events)) == before
    for name in ("jit_ff_step_c1", f"jit_ff_step_c{CHUNK}"):
        assert set(maps[name].values()) - {None} == FAMILIES["mistral"].sublayers
    # and the server still serves on the programs it had
    rid = rm.submit(list(range(1, CHUNK + 3)), max_new_tokens=3)
    while not rm.result(rid).profile.finish_time:
        rm.step()
    rm.drain()
    assert eng.retrace_guard.compile_counts() == counts


def test_an_engine_is_not_kept_alive_by_the_registry(tiny_servers):
    import gc
    import weakref

    server = tiny_servers(mistral, fresh=True, kernels="pallas")
    serve_one_prompt(server)
    assert sublayers.live_engines()[-1] is server.engine
    assert "jit_ff_step_c1" in scope_maps()   # the newest engine's stands
    ref = weakref.ref(server.engine)
    del server
    gc.collect()
    assert ref() is None and None not in sublayers.live_engines()


# ---------------------------------------------------------------------------
# the trim, where the geometry is the test


def test_a_row_left_with_no_token_is_not_in_the_step(tiny_servers, monkeypatch):
    """6 slots x chunk 4, ladder (6, 12, 24): two decoding rows beside
    four prompts' chunks hold 18 tokens, six over the rung at 12, and
    the newest prompt gives its whole chunk up. Its request stays as
    it was: no row, no pipeline reference, no entry in the flush."""
    eng = tiny_servers(mistral, max_requests_per_batch=6, prefill_chunk=4).engine
    assert eng.pack_ladder(4) == (6, 12)
    rm = RequestManager(eng)
    steps = watch(rm, monkeypatch)
    requests = prompts(6)
    first = [rm.submit(p[:5], max_new_tokens=30) for p in requests[:2]]
    while any(rm.requests[r].status is not RequestStatus.DECODING for r in first):
        assert rm.step()
    rids = [rm.submit(p, max_new_tokens=3) for p in requests[2:]]
    assert rm.step()
    st = steps[-1]
    assert (st["real"], st["trimmed"], st["width"]) == (12, 6, 12)
    reqs = [rm.requests[r] for r in rids]
    assert [r.n_sched for r in reqs] == [4, 4, 2, 0]
    left = reqs[-1]
    assert left.status is RequestStatus.PREFILLING and left.slot >= 0
    assert st["count"][left.slot] == 0 and left.pipeline_refs == 0
    assert all(rid != left.request_id for rid, *_ in rm._inflight[-1][1])
    finish(rm)
    got = [list(rm.requests[r].output_tokens) for r in first + rids]

    trims, want = rm.stats.rung_trims, []
    for p, n in [(p[:5], 30) for p in requests[:2]] + [(p, 3) for p in requests[2:]]:
        rid = rm.submit(p, max_new_tokens=n)
        finish(rm)
        want.append(list(rm.requests[rid].output_tokens))
    assert got == want and trims > 0
    assert rm.stats.rung_trims == trims  # one at a time: nothing to give up


def test_no_ladder_no_trim(tiny_servers, monkeypatch):
    """An engine whose mixed step is not packed (here the fused RoPE
    prologue; the dense layout, the ring and a family without
    ``PACKED_STEP`` likewise) hands every prompt its whole chunk."""
    eng = tiny_servers(mistral, kernels="pallas",
                       fused_decode=("rope_kv_write",)).engine
    assert eng.pack_ladder(CHUNK) == ()
    rm = RequestManager(eng)
    steps = watch(rm, monkeypatch)
    for i, p in enumerate(prompts(6)):
        rm.submit(p, max_new_tokens=6 + i)
    finish(rm)
    s = rm.stats
    assert s.rung_trims == 0 and s.rung_trim_tokens == 0
    assert set(s.steps_by_width) == {SLOTS * CHUNK}
    assert any(st["real"] in (19, 34) for st in steps)
    snap = s.snapshot()
    assert snap["rung_trims"] == 0 and snap["rung_trim_tokens"] == 0
    assert " trims=0/0tok" in s.report()
