#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving main path still
starts on the chip.

One process, no children. Builds Mistral-7B at its published widths
(only depth is cut — 32 layers are 14.5 GB in bf16 and do not fit beside
a KV pool on a 16 GB v5e) with seeded random weights through the normal
``LLM`` constructor, and drives ``LLM.compile(ServingConfig(...))`` →
``LLM.generate`` — RequestManager continuous batching over the paged KV
cache with the ``kernels="pallas"`` ragged paged attention. Before the
generate, one prefill step, one mixed step (C=prefill chunk) and one
decode step (C=1) run through both ``kernels="pallas"`` and
``kernels="xla"`` on the same inputs and their logits are compared.

  python chip_smoke.py            one TPU chip; the driver's form
  python chip_smoke.py --chips 4  tensor=4 over the host's four chips vs
                                  the one-chip run, and nothing else
  JAX_PLATFORMS=cpu python chip_smoke.py --tiny
                                  CPU rehearsal of the same control flow
                                  (tiny preset, interpret-mode kernels);
                                  prints no result line, exits 2

Any phase that fails raises: the exit code is non-zero and no result
line is printed. A platform that is not "tpu" is a failure, not a
branch. The last line of a successful run is the device line alone.
"""
import argparse
import gc
import json
import sys
import time

import numpy as np

SEED = 0
# Depth. One layer is 436 MB of bf16 weights and 4 KB per cached token;
# the page pool is the layer loop's carry, held once (PR 28; before, the
# step program held it twice and N=24 needed 16.8 GB through the XLA
# reference). Compiled for a described v5e (tests/test_chip_compile_*.py
# has the program, /opt/skills/guides/on-chip-measurement section 2 the
# method), the C=128 step with a 16k-token pool needs, of the 15.49 GB
# the chip leaves a program: N=20 10.7 GB through Pallas and 11.8 GB
# through the XLA reference it is compared with (1.2 GB of gathered
# cache and scores), N=24 12.7 and 13.8 GB. 24 would fit; the depth stays
# where every recorded run was made.
LAYERS = 20
POOL_TOKENS = 16384
# Pallas-vs-XLA (and tensor=4-vs-one-chip) logit tolerance, as a share of
# the largest reference logit. bf16 keeps 8 significand bits, so every
# rounding is off by up to 2^-9 relative. The two paths round at
# different points — the kernel runs an online softmax page by page, XLA
# takes one softmax over the gathered cache; tensor=4 sums row-parallel
# partials in another order — and the differences grow through N
# residual layers of random weights. Predicted before the first chip run:
# about sqrt(N) * 2^-8, 2%. Measured there (PR 23, v5e, N=20): 4.0% on
# the prefill step, 3.8% mixed, 4.3% decode — the random layers amplify
# more than a random walk. A wrong page, mask or scale is wrong by the
# logits' own size (a share near 1), so 10% — twice the measured error,
# a fifth of a wrong answer's — separates the two. Sampled tokens are NOT
# compared: with random weights the top logits are closer than this and
# the argmax flips on rounding.
LOGIT_TOL = 0.1
RESULT_EXIT_REHEARSAL = 2


def log(msg):
    print(msg, flush=True)


def preset(tiny):
    import jax.numpy as jnp

    from flexflow_tpu.models import mistral
    from flexflow_tpu.serve import ServingConfig

    if tiny:
        cfg = mistral.tiny(dtype=jnp.bfloat16, num_attention_heads=8,
                           num_key_value_heads=4)
        serving = dict(kv_layout="paged", page_size=8,
                       max_requests_per_batch=4, max_sequence_length=64,
                       prefill_chunk=16)
        traffic = dict(n=8, lo=10, hi=40, new=8)
    else:
        cfg = mistral.mistral_7b(dtype=jnp.bfloat16,
                                 num_hidden_layers=LAYERS)
        serving = dict(kv_layout="paged", page_size=128,
                       max_requests_per_batch=16, max_sequence_length=2048,
                       max_cached_tokens=POOL_TOKENS)
        traffic = dict(n=8, lo=256, hi=1024, new=64)

    def make_serving(kernels, **kw):
        return ServingConfig(kernels=kernels, **serving, **kw)

    return cfg, make_serving, traffic


def build_llm(cfg, tensor):
    """Seeded random weights through the normal constructor, on a
    ``tensor``-way mesh over the first ``tensor`` devices."""
    from flexflow_tpu.core.mesh import MachineSpec
    from flexflow_tpu.models import mistral
    from flexflow_tpu.serve.llm import LLM

    return LLM(mistral, cfg, seed=SEED, machine=MachineSpec(model=tensor))


def bytes_per_device(tree):
    """Bytes of ``tree``'s shards on each device, from the arrays' own
    shard lists (works on every backend)."""
    import jax

    held = {d: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return [held[d] for d in jax.devices()]


def report_memory(tag):
    import jax

    for d in jax.devices():
        stats = d.memory_stats()  # None on backends that keep no count
        if stats:
            log(f"[{tag}] device {d.id}: bytes_in_use="
                f"{stats['bytes_in_use']} peak={stats['peak_bytes_in_use']} "
                f"limit={stats.get('bytes_limit')}")


def probe_batches(engine, rng):
    """Three (R, C) step inputs that exercise chunked prefill, the mixed
    step and steady decode on one engine: [prefill C tokens on the first
    half of the slots] → [mixed: a quarter decodes, a quarter takes its
    second chunk, a quarter starts ragged fresh prompts] → [decode, C=1,
    every started slot]."""
    R = engine.num_slots
    C = engine.serving.mixed_chunk
    V = engine.cfg.vocab_size
    scratch = engine.scratch_pos
    q = R // 4
    lens = np.zeros(R, np.int64)  # lines cached so far, per slot

    def batch(chunk, rows):  # rows: {slot: n new tokens}
        toks = np.zeros((R, chunk), np.int32)
        pos = np.full((R, chunk), scratch, np.int32)
        idx = np.zeros((R,), np.int32)
        for s, n in rows.items():
            toks[s, :n] = rng.integers(0, V, n)
            pos[s, :n] = np.arange(lens[s], lens[s] + n)
            idx[s] = n - 1
            lens[s] += n
            assert engine.pager.ensure(s, int(lens[s])), "page pool too small"
        return toks, pos, idx, sorted(rows)

    yield "prefill", batch(C, {s: C for s in range(2 * q)})
    mixed = {s: 1 for s in range(q)}
    mixed.update({s: C for s in range(q, 2 * q)})
    mixed.update({s: int(rng.integers(C // 4, C + 1))
                  for s in range(2 * q, 3 * q)})
    yield "mixed", batch(C, mixed)
    yield "decode", batch(1, {s: 1 for s in range(3 * q)})


def probe_logits(llm, serving):
    """Compile ``serving`` and run the three probe steps through
    ``engine.run_mixed``; returns {step: (active rows' logits, f32)} and
    frees the engine (its KV pool) before returning."""
    import jax

    t0 = time.perf_counter()
    llm.compile(serving, seed=SEED)
    engine = llm.engine
    R = engine.num_slots
    rng = np.random.default_rng(SEED)
    key = jax.random.PRNGKey(SEED)
    head = (np.ones(R, bool), np.ones(R, np.float32),
            np.ones(R, np.float32), np.zeros(R, np.int32))
    out = {}
    for name, (toks, pos, idx, rows) in probe_batches(engine, rng):
        _, logits = engine.run_mixed(
            np.zeros(R, np.int32), toks, np.zeros(R, bool), pos, idx, key,
            *head, with_logits=True,
        )
        logits = np.asarray(jax.device_get(logits), np.float32)
        assert logits.shape == (R, engine.cfg.vocab_size), logits.shape
        out[name] = logits[rows]
        assert np.isfinite(out[name]).all(), f"{name}: non-finite logits"
    log(f"[probe kernels={serving.kernels}] 3 steps in "
        f"{time.perf_counter() - t0:.1f}s (compile included)")
    llm.engine = llm.rm = None
    del engine
    gc.collect()
    return out


def compare(tag, got, ref):
    worst = 0.0
    for name in ref:
        err = float(np.abs(got[name] - ref[name]).max()
                    / np.abs(ref[name]).max())
        log(f"[{tag}] {name}: max|dlogit|/max|logit| = {err:.5f} "
            f"(tolerance {LOGIT_TOL})")
        worst = max(worst, err)
    assert worst <= LOGIT_TOL, f"{tag}: logit error {worst} > {LOGIT_TOL}"
    return worst


def serve(llm, serving, traffic):
    """The main path: compile, then answer the requests through
    LLM.generate — twice, so the second pass shows run time without
    compiles and that the same program gives the same tokens."""
    rng = np.random.default_rng(SEED + 1)
    V = llm.cfg.vocab_size
    prompts = [
        rng.integers(0, V, int(rng.integers(traffic["lo"], traffic["hi"] + 1)))
        .tolist() for _ in range(traffic["n"])
    ]
    llm.compile(serving, seed=SEED)
    passes = []
    for _ in range(2):
        t0 = time.perf_counter()
        results = llm.generate(prompts, max_new_tokens=traffic["new"])
        passes.append((time.perf_counter() - t0, results))
    for res, prompt in zip(passes[0][1], prompts):
        assert res.error is None, res.error
        assert res.input_tokens == prompt
        assert len(res.output_tokens) == traffic["new"], len(res.output_tokens)
        assert all(0 <= t < V for t in res.output_tokens)
    assert [r.output_tokens for r in passes[0][1]] == \
        [r.output_tokens for r in passes[1][1]], "greedy rerun differs"
    n_in = sum(len(p) for p in prompts)
    n_out = traffic["n"] * traffic["new"]
    log(f"[generate] {traffic['n']} requests, {n_in} prompt tokens, "
        f"{n_out} new tokens; first pass {passes[0][0]:.1f}s (compile "
        f"included), second pass {passes[1][0]:.2f}s (run only)")
    counts = llm.engine.retrace_guard.compile_counts()
    log(f"[generate] traces per step key: {counts}")
    assert counts and all(c == 1 for c in counts.values()), counts
    stats = llm.rm.stats
    log(f"[generate] steps: {stats.steps} (mixed {stats.mixed_steps}, "
        f"decode {stats.decode_steps})")
    assert stats.mixed_steps > 0 and stats.decode_steps > 0
    return passes[0][1]


def run_one_chip(cfg, make_serving, traffic):
    llm = build_llm(cfg, tensor=1)
    log(f"weights: {sum(bytes_per_device(llm.params))} bytes")
    ref = probe_logits(llm, make_serving("xla"))
    got = probe_logits(llm, make_serving("pallas"))
    compare("pallas vs xla", got, ref)
    serve(llm, make_serving("pallas", sanitizers=("retrace",)), traffic)
    report_memory("one chip")


def run_four_chips(cfg, make_serving, traffic):
    import jax

    assert len(jax.devices()) >= 4, f"need 4 devices, see {jax.devices()}"
    llm = build_llm(cfg, tensor=4)
    serve(llm, make_serving("pallas", sanitizers=("retrace",)), traffic)
    held = bytes_per_device((llm.params, llm.engine.cache))[:4]
    log(f"[tensor=4] weight+pool bytes per device: {held}")
    report_memory("tensor=4")
    assert max(held) <= 1.25 * min(held), \
        f"weights and pool are not spread over four devices: {held}"
    got = probe_logits(llm, make_serving("pallas"))
    del llm
    gc.collect()
    one = build_llm(cfg, tensor=1)
    ref = probe_logits(one, make_serving("pallas"))
    compare("tensor=4 vs one chip", got, ref)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at the tiny preset; prints no "
                         "result line")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the tensor=4 path and the one-chip run "
                         "it is compared with")
    args = ap.parse_args(argv)

    import jax

    from flexflow_tpu.config import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"jax {jax.__version__} on {device}")
    if not args.tiny and dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {device} — no result")
    if device["count"] < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees {device}")

    cfg, make_serving, traffic = preset(args.tiny)
    log(f"model: mistral hidden={cfg.hidden_size} ffn={cfg.intermediate_size} "
        f"heads={cfg.num_attention_heads}/{cfg.num_key_value_heads} "
        f"head_dim={cfg.head_dim} vocab={cfg.vocab_size} "
        f"window={cfg.sliding_window} dtype={np.dtype(cfg.dtype).name} "
        f"N={cfg.num_hidden_layers} layers")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(cfg, make_serving, traffic)
    else:
        run_one_chip(cfg, make_serving, traffic)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    if args.tiny:
        print("chip_smoke: rehearsal complete — not a chip run, no result",
              file=sys.stderr)
        return RESULT_EXIT_REHEARSAL
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
