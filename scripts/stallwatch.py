#!/usr/bin/env python3
"""Run one benchmark cell under a watcher that says what a host stall was
(ROADMAP A17: one loop turn of 1-15 s inside the flush's ``device_get``,
the device idle, about one run in ten; cause not found).

  chiprun -- python3 scripts/stallwatch.py --workload <cell> --seed <n> --seconds 50 --trace 0

The arguments are ``benchmarks/run.py``'s, which runs in this process
unchanged; its result line is still the last line of stdout. The watcher
writes to stderr at the end: every collection of the oldest generation
and any over 50 ms (``gc.callbacks``), every gap over 150 ms of a thread
that only sleeps 5 ms (it stops with the GIL, the process or the
machine; it keeps ticking while the runtime alone waits), and, when the
main thread sits in one frame for over 0.4 s, that stack, the states of
the process's threads and which of them burned CPU meanwhile. PR 34: 12
runs of ``lfm2-24b-a2b.decode-wide-closed`` caught no stall; no
collection fell inside a window (PERF.md section 2).
"""
import gc
import os
import runpy
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
clock = time.perf_counter
T0 = clock()
LOG = []
TICK_S, GAP_S, HELD_S = 0.005, 0.15, 0.4
MAIN = threading.main_thread().ident


def say(msg):
    LOG.append(f"[watch +{clock() - T0:8.3f}] {msg}")


def watch_gc():
    began = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            began[0] = clock()
            return
        took = clock() - began[0]
        if took > 0.05 or info.get("generation") == 2:
            say(f"gc generation {info.get('generation')}: {took * 1e3:.1f} ms, "
                f"collected {info.get('collected')}")

    gc.callbacks.append(on_gc)


def tasks():
    """(tid, name, state, cpu ticks, wait channel) of this process's threads."""
    rows = []
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                stat = f.read()
            with open(f"/proc/self/task/{tid}/wchan") as f:
                wchan = f.read().strip()
        except OSError:
            continue
        close = stat.rindex(")")
        fields = stat[close + 2:].split()
        rows.append((tid, stat[stat.index("(") + 1:close], fields[0],
                     int(fields[11]) + int(fields[12]), wchan))
    return rows


def watcher():
    last = held_since = clock()
    key = name = before = None
    told = False
    while True:
        time.sleep(TICK_S)
        now = clock()
        if now - last > GAP_S:
            say(f"the ticking thread did not run for {(now - last) * 1e3:.1f} ms")
        last = now
        frame = sys._current_frames().get(MAIN)
        here = (id(frame), frame.f_lasti) if frame is not None else None
        if here != key:
            if told:
                burned = {t[0]: t[3] for t in tasks()}
                say(f"main thread left {name} after {(now - held_since) * 1e3:.1f} ms; "
                    f"cpu ticks meanwhile: "
                    f"{[(t[1], burned.get(t[0], t[3]) - t[3]) for t in before if burned.get(t[0], t[3]) - t[3] > 5]}")
            key, held_since, told = here, now, False
            continue
        if not told and now - held_since > HELD_S and frame is not None:
            told = True
            stack = traceback.extract_stack(frame)[-8:]
            name = f"{stack[-1].name}:{stack[-1].lineno}"
            say(f"main thread in {name} for {(now - held_since) * 1e3:.1f} ms so far: "
                + " <- ".join(f"{s.name}:{s.lineno}" for s in reversed(stack)))
            before = tasks()
            states = {}
            for _, comm, state, _, wchan in before:
                comm = comm.rstrip("-0123456789")
                states[(comm, state, wchan)] = states.get((comm, state, wchan), 0) + 1
            say(f"threads: {states}")


def main():
    watch_gc()
    threading.Thread(target=watcher, daemon=True, name="stallwatch").start()
    run = os.path.join(ROOT, "benchmarks", "run.py")
    sys.argv = [run] + sys.argv[1:]
    try:
        runpy.run_path(run, run_name="__main__")
    finally:
        say(f"gc stats {gc.get_stats()}")
        sys.stderr.write("\n".join(LOG) + "\n")
        sys.stderr.flush()


if __name__ == "__main__":
    main()
