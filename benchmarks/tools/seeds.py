#!/usr/bin/env python3
"""Many windows of one cell in ONE process, a seed each: what the seed
of the TRAFFIC does to a window, set-up paid once. Run by hand:

  chiprun --timeout 1800 -- python3 benchmarks/tools/seeds.py \
      --workload mistral-7b.prefill-closed --seeds 11,12,13,11 --seconds 50 \
      [--arms '[{"order": null}, {"order": 38, "seeds": [21, 22]}]'] [--tag name]

The server is built once (weights from the first seed: a dense step's
time does not depend on them) and its step programs warmed; no probe
(``correct`` is ``run.py``'s). Each seed then gets a generator from
``numpy.random.default_rng(seed)``, ``harness/loop.run`` and the
cell's end-to-end readers, as a run has them; after a window the live
requests are stepped out. These seeds are NOT ``run.py``'s (there the
probe draws from the stream before the generator does): they are other
orders of the same work. ``--arms`` is a JSON list of dicts, each laid
over the traffic file in turn (null takes a key out; ``seeds`` gives
the arm its own seeds), so a remedy is tried beside today's traffic in
one call.

Per window, one JSON line on stdout and in ``chiprun_out/<tag>.jsonl``:
the metrics, the longest turn, the window's mixed steps by width, its
occupancy and pack fill, and over the samples: requests by
(steps from due to first token) - chunks, and the mean share of the
steps a sample waited through that ran at the widest rungs.
"""
import argparse
import bisect
import collections
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated; repeats allowed")
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--arms", default="[{}]", help="JSON list of dicts over the traffic file")
    ap.add_argument("--tag", default=None)
    ap.add_argument("--rehearse", type=int, default=0, help="1: tiny sizes on the CPU")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    import jax
    import numpy as np

    from benchmarks import run as bench
    from benchmarks.harness import loop, model, reduce, spec
    from flexflow_tpu.config import enable_compile_cache

    cell = spec.Cell(args.workload)
    if args.rehearse:
        bench.tiny(cell)
    arms = json.loads(args.arms)
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        sys.exit("seeds.py: needs a TPU")
    t = time.perf_counter()
    llm, params = model.build_server(cell.config, seeds[0])
    jax.block_until_ready(params)
    rm, engine = llm.rm, llm.engine
    vocab = llm.cfg.vocab_size
    chunk, ahead = engine.serving.mixed_chunk, engine.serving.dispatch_ahead
    bench.warm_step_keys(rm, chunk, np.random.default_rng(seeds[0]), vocab)
    print(f"[seeds] server up and warm in {time.perf_counter() - t:.1f}s", flush=True)
    compiles = bench.Compiles()
    kind = spec.load_module("generators", cell.traffic["kind"])
    tag = args.tag or args.workload
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out", tag + ".jsonl")

    # one row a turn of the loop: when it began, and the width the
    # scheduler dispatched in it (0: no mixed step)
    turns, widths = [], []
    inner = rm.step

    def step():
        turns.append(loop.clock())
        before = rm.stats.step_tokens_width
        more = inner()
        widths.append(rm.stats.step_tokens_width - before)
        return more

    rm.step = step
    for arm, seed in [(a, s) for a in arms for s in a.get("seeds", seeds)]:
        del turns[:], widths[:]
        traffic = {k: v for k, v in {**cell.traffic, **arm}.items()
                   if v is not None and k != "seeds"}
        gen = kind.Generator(traffic, np.random.default_rng(seed), vocab, args.seconds)
        win = loop.run(rm, gen, args.seconds, compiles=compiles, log=lambda m: None)
        ctx = reduce.Context(window=win, setup_s=0.0, cfg=cell.config,
                             engine_serving=engine.serving, peaks=None,
                             pool_pages=engine.pager.num_pages, log=lambda m: None,
                             tracer=None)
        row = {"seed": seed, "arm": {k: v for k, v in arm.items() if k != "seeds"},
               "samples": win.attempted, "failed": win.failed, "compiles": win.compiles,
               "longest_turn_ms": win.longest_step_s * 1e3,
               "longest_turn_at_s": win.longest_step_at}
        for m in cell.end_to_end:
            if m["name"] != "setup_s":
                row[m["name"]] = spec.load_module("end_to_end", m["name"]).read(ctx)
        row["steps_by_width"] = {str(w): n for w, n in ctx.steps_by_width().items()}
        row["steps"] = ctx.stats_delta("steps")
        row["occupancy_pct"] = spec.load_module("per_layer", "sched.occupancy_pct").read(ctx)
        row["pack_fill_pct"] = spec.load_module("per_layer", "step.pack_fill_pct").read(ctx)
        narrow = min((w for w in widths if w), default=0)
        extra, wide, spans = collections.Counter(), [], []
        for s in win.samples:
            if not s.first_token:
                continue
            i = bisect.bisect_right(turns, s.due)          # first turn after it was due
            j = bisect.bisect_right(turns, s.first_token)  # turns begun by its first token
            chunks = -(-len(s.prompt) // chunk)
            extra[j - i - chunks] += 1
            # the steps whose device time lay between due and first token:
            # dispatched ``dispatch_ahead`` turns before the host saw them
            seen = widths[max(0, i - ahead): max(0, j - ahead)]
            spans.append(len(seen))
            wide.append(sum(1 for w in seen if w > narrow) / max(1, len(seen)))
        row["steps_due_to_first_minus_chunks"] = dict(sorted(extra.items()))
        row["wide_share_of_waited_steps"] = sum(wide) / max(1, len(wide))
        row["waited_steps_mean"] = sum(spans) / max(1, len(spans))
        line = json.dumps(row)
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")
        while rm.step():
            pass
        rm.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
