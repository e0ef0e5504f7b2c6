#!/usr/bin/env python3
"""Look at a trace by hand: runs the benchmark command with the
arguments given and, when the traced run reads its profile, also writes
under ``chiprun_out/trace_dump/`` a summary of every plane and line
(event counts, the commonest names, one event's full stats) and a plain
JSON slice of the first ``SLICE_S`` seconds — what
``benchmarks/tests/trace_sample.json`` was cut from.

  chiprun -- python3 benchmarks/tools/trace_dump.py --workload <cell> --seed 1 --seconds 12 --trace 1
"""
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
SLICE_S = 0.6


def main():
    from benchmarks import run as bench_run
    from benchmarks.harness import trace

    inner = trace.read_xplane
    out = os.path.join(ROOT, "chiprun_out", "trace_dump")
    os.makedirs(out, exist_ok=True)

    def dumping(path, **kw):
        planes = inner(path, all_stats=True)
        lines = []
        t0 = min((ev[1] for p in planes.values() for l in p.values() for ev in l),
                 default=0.0)
        sample = {}
        for pname, plane in planes.items():
            lines.append(f"PLANE {pname}")
            for lname, events in plane.items():
                names = collections.Counter(ev[0] for ev in events)
                lines.append(f"  LINE {lname}: {len(events)} events; "
                             f"{names.most_common(12)}")
                if events:
                    lines.append(f"    first: {events[0]}")
                kept = [ev for ev in events if ev[1] - t0 < SLICE_S * 1e9][:4000]
                if kept:
                    sample.setdefault(pname, {})[lname] = kept
        with open(os.path.join(out, "summary.txt"), "w") as f:
            f.write("\n".join(lines))
        with open(os.path.join(out, "slice.json"), "w") as f:
            json.dump(sample, f, default=str)
        print(f"[trace_dump] wrote {out}", flush=True)
        return inner(path, **kw)

    trace.read_xplane = dumping
    return bench_run.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
