#!/usr/bin/env python3
"""What the harness cannot yet print of a traced run, read by hand:
runs the benchmark command with the arguments given and, when the
traced run has loaded its profile, also writes
``chiprun_out/phases/<cell>.seed<n>.json`` (and logs the same):

* the device's idle seconds by the ``ff.step.*`` phase of
  ``RequestManager.step`` the host was in (``reduce.gaps`` /
  ``reduce.overlap``, as ``Trace.idle_by_annotation`` does for the
  benchmark's own two spans), each phase taken as its SELF time: a
  flush less its blocking fetch, an admission or a reservation less the
  flush nested in it;
* the host's time per turn of the loop by the same phases;
* the longest idle gap, the phase it lay in and the runtime's own
  events that overlap it (a host stall shows here);
* program names on ``XLA Modules``, kernel names on ``XLA Ops``, host
  events per turn;
* per sample, queue wait + prefill dispatch + first-token lag against
  ``ttft_ms`` (the largest difference; it should be rounding).

  chiprun -- python3 benchmarks/tools/phases.py --workload <cell> --seed 1 --seconds 12 --trace 1

``--dump`` before the other arguments runs ``tools/trace_dump.py``'s
plane summary and slice in the same process as well. ``--sample
<seconds>`` also writes ``<cell>.seed<n>.sample.json``: a stretch of
the trace small enough to keep with the tests
(``tests/trace_sample_spans.json``; ``trace_dump``'s slice stops at 4000
events a line, 2.7 mixed steps, less than the host runs ahead), from
the first whole ``bench.step``, op names compacted by
``reduce.compact_op``, of the host only the events the reduction and
the readers read, and a ``bench.traced`` span laid over exactly that
stretch. A program without the spans (before PR 27) gives zeros for
them, no error.
"""
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# obs.tracer.STEP_SPANS as a profile shows them; spelled out because this
# file also runs over a program that has none (the parent of PR 27)
PHASES = ("ff.step.admit", "ff.step.reserve", "ff.step.build",
          "ff.step.dispatch", "ff.step.flush", "ff.step.flush_wait")
STALL_EVENTS = 8  # runtime events listed under the longest idle gap


def intersect(a, b):
    """Two merged, sorted interval lists -> their intersection."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def self_times(t):
    """``{leaf: merged intervals}``: every moment of the traced window
    under exactly one name — a phase's self time, the rest of a
    ``bench.step``, ``bench.submit``, or outside both."""
    from benchmarks.harness import reduce

    def minus(a, b):
        return intersect(a, [list(g) for g in reduce.gaps(b, t.lo, t.hi)])

    span = {n: reduce.union(t.spans(n)) for n in PHASES}
    flush, wait = span["ff.step.flush"], span["ff.step.flush_wait"]
    leaves = {
        "ff.step.admit": minus(span["ff.step.admit"], flush),
        "ff.step.reserve": minus(span["ff.step.reserve"], flush),
        "ff.step.build": span["ff.step.build"],
        "ff.step.dispatch": span["ff.step.dispatch"],
        "ff.step.flush": minus(flush, wait),
        "ff.step.flush_wait": wait,
    }
    phased = reduce.union([iv for n in PHASES for iv in span[n]])
    turn = reduce.union(t.spans("bench.step"))
    submit = reduce.union(t.spans("bench.submit"))
    leaves["bench.step outside every phase"] = minus(turn, phased)
    leaves["bench.submit"] = submit
    leaves["outside the loop's spans"] = minus(
        [[t.lo, t.hi]], reduce.union(turn + submit + phased))
    return leaves


def report(ctx):
    from benchmarks.harness import reduce

    t = ctx.trace
    leaves = self_times(t)
    idle = reduce.gaps(t.busy, t.lo, t.hi)
    turns = t.spans("bench.step")
    out = {
        "window_s": t.window_s,
        "idle_s": reduce.total(idle) / 1e9,
        "turns": len(turns),
        "idle_s_by_phase": {n: reduce.overlap(idle, iv) / 1e9
                            for n, iv in leaves.items()},
        "host_ms_per_turn_by_phase": {
            n: reduce.total(iv) / 1e6 / max(1, len(turns))
            for n, iv in leaves.items() if n.startswith(("ff.", "bench.step"))},
        "host_events_per_turn": sum(
            1 for _, s, _, _ in t.host if t.lo <= s < t.hi) / max(1, len(turns)),
        "modules": dict(collections.Counter(
            n.split("(")[0] for n, s, d, _ in t.modules
            if t.lo <= s and s + d <= t.hi)),
        "kernels": dict(collections.Counter(
            name for name, _, _, kernel, s, _ in t.ops
            if kernel and t.lo <= s < t.hi)),
    }
    if idle:
        s, e = max(idle, key=lambda g: g[1] - g[0])
        under = sorted(((min(e, hs + hd) - max(s, hs), n)
                        for n, hs, hd, _ in t.host
                        if hs < e and hs + hd > s
                        and not n.startswith(("bench.", "ff."))),
                       reverse=True)[:STALL_EVENTS]
        out["longest_idle_gap"] = {
            "ms": (e - s) / 1e6,
            "at_s": (s - t.lo) / 1e9,
            "phase": max(leaves, key=lambda n: reduce.overlap([(s, e)], leaves[n])),
            "runtime_events_under_it_ms": [[n, d / 1e6] for d, n in under],
        }
    parts = [
        abs((p.admit_time - smp.due) + (p.prefill_dispatched_time - p.admit_time)
            + (p.first_token_time - p.prefill_dispatched_time)
            - smp.ttft_ms / 1e3) * 1e3
        for smp in ctx.window.samples
        for p in [smp.profile]
        if getattr(p, "prefill_dispatched_time", 0.0) and smp.ttft_ms is not None]
    out["ttft_parts"] = {"samples": len(parts),
                         "largest_difference_ms": max(parts, default=None)}
    return out


def cut(planes, seconds):
    """The sample as :func:`load_sample` reads it back: a mixed step is
    2,200 operations under 230 names, so an event holds its name's index
    in ``names`` and whole nanoseconds."""
    from benchmarks.harness import reduce

    device = next(p for p in sorted(planes) if reduce.DEVICE_PLANE.match(p))
    host_lines = planes[reduce.HOST_PLANE].values()
    lo = min(s for line in host_lines for n, s, _, _ in line
             if n == "bench.step")
    hi = lo + seconds * 1e9

    def inside(events):
        return [ev for ev in events if lo <= ev[1] and ev[1] + ev[2] <= hi]

    read = ("bench.submit", "bench.step", reduce.ENQUEUE_EVENT,
            *reduce.WAIT_EVENTS)
    host = sorted(
        ([n, s, d, {k: v for k, v in stats.items() if k == "run_id"}]
         for line in host_lines for n, s, d, stats in inside(line)
         if n in read or n.startswith("ff.")),
        key=lambda ev: ev[1])
    kept = {
        device: {
            reduce.MODULES_LINE: [
                [n, s, d, {"run_id": stats.get("run_id")}]
                for n, s, d, stats in inside(planes[device][reduce.MODULES_LINE])],
            reduce.OPS_LINE: [
                [reduce.compact_op(n), s, d, {}]
                for n, s, d, _ in inside(planes[device][reduce.OPS_LINE])],
        },
        reduce.HOST_PLANE: {
            "all threads": [[reduce.TRACED, lo, hi - lo, {}]] + host},
    }
    names = sorted({ev[0] for lines in kept.values()
                    for events in lines.values() for ev in events})
    index = {n: i for i, n in enumerate(names)}
    return {"names": names, "planes": {
        p: {line: [[index[n], round(s), round(d), stats]
                   for n, s, d, stats in events]
            for line, events in lines.items()}
        for p, lines in kept.items()}}


def load_sample(path):
    """(planes as ``reduce.Trace`` takes them, the rest of the file)."""
    with open(path) as f:
        sample = json.load(f)
    names = sample.pop("names")
    planes = {p: {line: [(names[i], float(s), float(d), stats)
                         for i, s, d, stats in events]
                  for line, events in lines.items()}
              for p, lines in sample.pop("planes").items()}
    return planes, sample


def main():
    from benchmarks import run as bench_run
    from benchmarks.harness import reduce, trace

    argv = sys.argv[1:]
    dump = "--dump" in argv
    if dump:
        argv.remove("--dump")
    sample_s = None
    if "--sample" in argv:
        i = argv.index("--sample")
        sample_s = float(argv[i + 1])
        del argv[i:i + 2]
    cell = argv[argv.index("--workload") + 1]
    seed = argv[argv.index("--seed") + 1] if "--seed" in argv else "0"
    out_dir = os.path.join(ROOT, "chiprun_out", "phases")
    inner = reduce.Context.load_trace

    read_xplane = trace.read_xplane
    seen = {}

    def keeping(path, **kw):
        seen["planes"] = read_xplane(path, **kw)
        return seen["planes"]

    def reporting(ctx):
        inner(ctx)
        found = report(ctx)
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{cell}.seed{seed}.json"), "w") as f:
            json.dump(found, f, indent=1)
        print(f"[phases] {json.dumps(found)}", flush=True)
        if sample_s:
            path = os.path.join(out_dir, f"{cell}.seed{seed}.sample.json")
            with open(path, "w") as f:
                json.dump(cut(seen["planes"], sample_s), f,
                          separators=(",", ":"))

    trace.read_xplane = keeping
    reduce.Context.load_trace = reporting
    if dump:
        from benchmarks.tools import trace_dump

        sys.argv[1:] = argv
        return trace_dump.main()
    return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
