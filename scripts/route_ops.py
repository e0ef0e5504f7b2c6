#!/usr/bin/env python
"""A traced benchmark run's device time under ONE ``ff.*`` scope, by
instruction: which operations of ``ff.moe.route`` (the routed layer
less its routers' names and its kernels) a step pays for, a program.

``step.sub_ms.moe_route`` is one number a cell. This script runs the
benchmark command with the arguments given (``--trace 1``) and, where
the harness joins the profile's operations to the step programs' scope
maps, keeps each program's compiled text too and adds up the same
events by what the instruction IS: its opcode, the JAX primitive that
made it (the tail of its ``op_name`` past the scope; a fusion's is its
root's, and the primitives fused into it are listed beside) and its
result's shape. Twelve layers' copies of one instruction are one row
(``n`` a step). Since PR 59 the way out is choice-major: the table
shows the results' gather (``f32[P, D]``) and ONE fusion that reads it
as ``[k, T, D]`` and writes ``(T, D)``; a ``reshape`` or ``copy`` to
``f32[T, k, D]`` (k padded to 8 sublanes) or a ``reduce`` over it in
``ff.moe.route`` is the token-major way out come back.

    chiprun -- python scripts/route_ops.py [--scope ff.moe.route] \
        [--tag parent] --workload <cell> --seed 1 --seconds 50 --trace 1

Prints a table a program (ms a step, instructions a step) and writes
``<--out>/<workload>.<tag>.json`` (``chiprun_out/route_ops`` of its
own tree where nothing is said). It reads what a run
reads and changes nothing that is measured; it is no benchmark cell
(not under ``benchmarks/``) and runs on any tree that has the scopes
(copy it into a parent's checkout to read the parent).
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from flexflow_tpu.obs.sublayers import parse_instructions  # noqa: E402

# what a fusion's body holds beside its work
_PLUMBING = frozenset((
    "parameter", "constant", "bitcast", "broadcast", "convert", "tuple",
    "get-tuple-element", "reshape", "iota", "copy"))


def _tail(op_name: str) -> str:
    """An ``op_name`` past its last ``ff.*`` scope: the JAX primitive."""
    parts = op_name.split("/")
    at = max((i for i, p in enumerate(parts) if p.startswith("ff.")),
             default=-1)
    return "/".join(parts[at + 1:]) or parts[-1]


def describe(hlo_text: str, scope: str):
    """``{instruction: "opcode primitive [fused opcodes] shape"}`` over
    a compiled module's text, for the instructions whose own or called
    root's ``op_name`` holds ``scope``."""
    instructions = parse_instructions(hlo_text)
    roots = {i.computation: i for i in instructions.values() if i.root}
    bodies = collections.defaultdict(collections.Counter)
    for i in instructions.values():
        if i.opcode not in _PLUMBING:
            bodies[i.computation][i.opcode] += 1
    out = {}
    for name, i in instructions.items():
        op_name, fused = i.op_name, ""
        if i.calls in roots:
            if scope not in op_name:
                op_name = roots[i.calls].op_name
            fused = " [" + ",".join(
                f"{o}x{c}" if c > 1 else o
                for o, c in sorted(bodies[i.calls].items())) + "]"
        if scope in op_name.split("/"):
            out[name] = f"{i.opcode} {_tail(op_name)}{fused} {i.shape}"
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scope", default="ff.moe.route")
    ap.add_argument("--tag", default="tree")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "route_ops"))
    args, rest = ap.parse_known_args()
    workload = rest[rest.index("--workload") + 1]

    from benchmarks import run as bench_run
    from benchmarks.harness import reduce, sublayers
    from flexflow_tpu.obs import sublayers as obs

    texts = {}

    def keeping_maps(engines=None, programs=None):
        names = None if programs is None else {
            p[len("jit_"):] for p in programs if p.startswith("jit_")}
        for engine in obs.live_engines() if engines is None else engines:
            for name, text in engine.step_program_texts(names).items():
                texts["jit_" + name] = text
        return {name: obs.parse_scope_map(text) for name, text in texts.items()}

    inner_reduce = sublayers.reduce_sublayers

    def reducing(trace, maps):
        table = inner_reduce(trace, maps)
        steps = sublayers.step_modules(trace)
        starts = [s for s, _, _ in steps]
        what = {p: describe(t, args.scope) for p, t in texts.items()}
        rows = collections.defaultdict(lambda: [0.0, 0])
        for name, _, opcode, _, s, dur in trace.ops:
            if opcode in reduce.CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= steps[i][1]:
                continue
            program = steps[i][2]
            if maps.get(program, {}).get(name) != args.scope:
                continue
            row = rows[program, what.get(program, {}).get(name, name)]
            row[0] += dur / 1e6
            row[1] += 1
        result = {}
        for program, by in sorted(table.by_program.items()):
            n = by["steps"]
            mine = sorted(((ms / n, c / n, k) for (p, k), (ms, c) in rows.items()
                           if p == program), reverse=True)
            total = sum(ms for ms, _, _ in mine)
            print(f"[route_ops] {program}: {n} steps, {args.scope} "
                  f"{total:.4f} ms a step", flush=True)
            for ms, c, k in mine:
                print(f"[route_ops]   {ms:9.4f} ms  x{c:6.1f}  {k[:200]}",
                      flush=True)
            result[program] = {"steps": n, "ms": total, "rows": [
                {"ms": ms, "n": c, "what": k} for ms, c, k in mine]}
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"{workload}.{args.tag}.json"),
                  "w") as f:
            json.dump(result, f, indent=1)
        return table

    obs.scope_maps = keeping_maps
    sublayers.reduce_sublayers = reducing
    return bench_run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
