#!/usr/bin/env python3
"""Runs of the benchmark's own command, one process each, in one chip
call: the sets of six a bound is set from, a parent's tree beside this
one. This process never touches JAX (a chip belongs to one process).

  chiprun --timeout 1500 -- python3 benchmarks/tools/sets.py --tag f1 \
      --workload mistral-7b.prefill-closed --seeds 11,12,13 [--trace 1] \
      [--root _parent] [--seconds 50]

Each run's whole output goes to ``chiprun_out/<tag>_<cell>_<seed>_t<trace>.log``;
its result line, with the cell, the seed, the root, the exit code, the
longest turn of the loop and the window's mixed steps by width, is
appended to ``chiprun_out/<tag>.jsonl``.
"""
import argparse
import json
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tag", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="50")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--root", default=".", help="run this tree's command (relative to the repo)")
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    side = os.path.basename(os.path.normpath(args.root)) if args.root != "." else "tree"
    for seed in args.seeds.split(","):
        cmd = command + ["--workload", args.workload, "--seed", seed,
                         "--seconds", args.seconds, "--trace", args.trace]
        run = subprocess.run(cmd, cwd=os.path.join(ROOT, args.root), text=True,
                             stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        name = f"{args.tag}_{args.workload}_{seed}_t{args.trace}_{side}"
        with open(os.path.join(out_dir, name + ".log"), "w") as f:
            f.write(run.stdout)
        lines = [ln for ln in run.stdout.splitlines() if ln.startswith("{")]
        row = {"cell": args.workload, "seed": int(seed), "trace": int(args.trace),
               "side": side, "rc": run.returncode}
        if run.returncode == 0 and lines:
            row.update(json.loads(lines[-1]))
        turn = re.search(r"longest turn of the loop ([0-9.]+) ms at \+([0-9.]+)s", run.stdout)
        if turn:
            row["longest_turn_ms"], row["longest_turn_at_s"] = map(float, turn.groups())
        widths = re.search(r"mixed steps by width[^{]*(\{[^}]*\})", run.stdout)
        if widths:
            row["steps_by_width"] = widths.group(1)
        row.pop("breakdown", None)
        print(json.dumps(row), flush=True)
        with open(os.path.join(out_dir, args.tag + ".jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
