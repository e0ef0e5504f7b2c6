"""The traced sub-window: a few seconds of the profiler inside the
measured window (traces are large and tracing slows the host), and the
reading of what it wrote. ``reduce.py`` turns the events into numbers.
"""
import dataclasses
import glob
import os
import shutil
import tempfile

import jax


class Tracer:
    """Traces the LAST ``length_s`` seconds of the window: the loop
    stops it when the window has closed, so the seconds the profiler
    takes to write its file fall outside. While it runs, notes at every
    loop turn how many rows decode and prefill and how long their
    contexts are — what the roofline counts need and the scheduler's
    totals do not say."""

    def __init__(self, length_s):
        self.length_s = length_s
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")  # under $TMPDIR
        self.state = "waiting"
        self.span = None
        self.stats_start = self.stats_stop = None
        self.rows = []  # (decoding rows, their context sum, prefilling rows, their prompt sum)

    def tick(self, now, win, rm, live):
        if self.state == "waiting" and now >= win.closed - self.length_s:
            # no Python-function events: they are most of a trace's
            # size and of its cost to the host; the bench.* spans and
            # the runtime's own events are host events, kept
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.span = jax.profiler.TraceAnnotation("bench.traced")
            self.span.__enter__()
            self.stats_start = dataclasses.replace(rm.stats)
            self.state = "tracing"
        elif self.state == "tracing":
            dec = [len(s.prompt) + len(rm.result(s.rid).output_tokens)
                   for s in live if s.first_token]
            pre = [len(s.prompt) for s in live if not s.first_token]
            self.rows.append((len(dec), sum(dec), len(pre), sum(pre)))

    def stop(self, rm):
        if self.state != "tracing":
            return
        self.stats_stop = dataclasses.replace(rm.stats)
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def events(self):
        """The trace as plain data (see ``read_xplane``); removes the
        profiler's files."""
        try:
            paths = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not paths:
                raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
            return read_xplane(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# what reduce.py reads of an event's stats
KEPT_STATS = ("run_id",)


def read_xplane(path, *, all_stats=False):
    """``{plane name: {line name: [(name, start_ns, duration_ns,
    stats)]}}`` with starts on the profile's one clock (a line's own
    ``timestamp_ns`` offset is already in ``start_ns``)."""
    data = jax.profiler.ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                stats = {k: v for k, v in ev.stats
                         if all_stats or k in KEPT_STATS}
                events.append((ev.name, float(ev.start_ns),
                               float(ev.duration_ns), stats))
    return planes
