#!/usr/bin/env python3
"""A closed-loop cell in STEP space, on the CPU, with no server: what a
seed's order of the work does to the mixed steps' widths and to TTFT.

Not a measurement. The scheduler gives every prefilling row a whole
chunk a step and every decoding row a token, the engine runs the step
at the narrowest rung that holds its real tokens, the device is never
idle and the host sees a step's tokens ``ahead`` turns after it
dispatched it (PERF.md sections 3 and 5). So given the generator's
lengths a window is determined but for the time a rung takes, which
``--ms`` states from a traced chip run. Used to choose between remedies
before paying chip time for one; the chip's runs decide.

  python3 benchmarks/tools/phase_sim.py --traffic prefill-closed \
      --ms 512:35.5,1024:61.7,2048:112 --seeds 200 [--set key=value ...]
"""
import argparse
import collections
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmarks.harness import spec, stats  # noqa: E402


def simulate(traffic, seed, seconds, ms, chunk=128, ahead=4, vocab=1000):
    kind = spec.load_module("generators", traffic["kind"])
    gen = kind.Generator(traffic, np.random.default_rng(seed), vocab, seconds)
    rungs = sorted(ms)
    now = 0.0
    gen.start(now)
    opened = gen.warmup_s
    closed = opened + seconds
    rows = {}          # client -> [sent, prompt left, answer left]
    firsts = collections.defaultdict(list)   # step that samples a first token -> sent
    done_at = collections.defaultdict(list)  # step whose flush completes them -> sent
    ends = []          # device finish time of each step
    samples, tokens, widths = [], 0, {}
    step = 0
    while True:
        for s in gen.due(now):
            rows[s.client] = [s, len(s.prompt), s.max_new]
        real = 0
        for c, row in list(rows.items()):
            s, left, answer = row
            if left > 0:
                n = min(chunk, left)
                row[1] -= n
                real += n
                if row[1] == 0:
                    firsts[step].append(s)
                    row[2] -= 1
            elif answer > 0:
                real += 1
                row[2] -= 1
            if row[1] == 0 and row[2] == 0:
                done_at[step].append(s)
                del rows[c]
        width = next(w for w in rungs if real <= w) if real else 0
        start = ends[-1] if ends else 0.0
        ends.append(start + (ms[width] if real else 1.0) / 1e3)
        # the turn ends when the step ``ahead`` before this one is flushed
        if step >= ahead:
            now = ends[step - ahead]
            flushed = step - ahead
            for s in firsts.pop(flushed, ()):
                s.first = now
            if opened <= now < closed:
                widths[width] = widths.get(width, 0) + 1
            for s in done_at.pop(flushed, ()):
                if opened <= now < closed:
                    tokens += s.max_new
                    if s.judged:
                        samples.append((s.first - s.due) * 1e3)
                gen.completed(s, now)
        if now >= closed:
            break
        step += 1
    return {"ttft_p50_ms": stats.percentile(samples, 50),
            "ttft_p90_ms": stats.percentile(samples, 90),
            "out_tokens_per_s": tokens / seconds, "samples": len(samples),
            "widths": widths}


def spread(xs):
    q = statistics.quantiles(xs, n=4)
    return (q[2] - q[0]) / statistics.median(xs)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", default="prefill-closed")
    ap.add_argument("--ms", default="512:35.5,1024:61.7,2048:112")
    ap.add_argument("--seeds", type=int, default=100)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--set", action="append", default=[],
                    help="key=json, laid over the traffic file")
    args = ap.parse_args()
    traffic = spec.load_json("traffic", args.traffic + ".json")
    for kv in args.set:
        k, v = kv.split("=", 1)
        traffic[k] = json.loads(v)
    traffic = {k: v for k, v in traffic.items() if v is not None}  # null takes a key out
    ms = {int(k): float(v) for k, v in
          (p.split(":") for p in args.ms.split(","))}
    runs = [simulate(traffic, 1000 + 7919 * i, args.seconds, ms)
            for i in range(args.seeds)]
    for r in runs[:6]:
        print(r)
    for m in ("ttft_p50_ms", "ttft_p90_ms", "out_tokens_per_s"):
        xs = [r[m] for r in runs]
        print(f"{m}: median {statistics.median(xs):.2f} range "
              f"{min(xs):.2f}-{max(xs):.2f} ({(max(xs) - min(xs)) / statistics.median(xs):.2%}) "
              f"quartile spread {spread(xs):.2%} sd {statistics.pstdev(xs) / statistics.mean(xs):.2%}")
    share = [r["widths"].get(1024, 0) / sum(r["widths"].values()) for r in runs]
    print(f"share of steps at 1024: {min(share):.3f}-{max(share):.3f}")


if __name__ == "__main__":
    main()
