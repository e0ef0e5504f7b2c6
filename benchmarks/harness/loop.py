"""The load loop: ONE process, one thread, the server's own public
surface — ``RequestManager.submit`` / ``step`` / ``result``,
``SchedulerStats``, each request's ``ProfileInfo`` and the page
allocator's counts. A generator (``generators/<kind>.py``) says what is
due and when; this loop submits it, steps the server, notices
completions and keeps the samples: the judged requests that complete
inside the window. Two annotations on the profiler's own clock
(``bench.submit``, ``bench.step``) cover what the host does, so an idle
gap of the device can be laid at one of them.
"""
import dataclasses
import time
from typing import Any, List, Optional

import jax

clock = time.perf_counter


@dataclasses.dataclass
class Sent:
    """One request as the generator made it and the loop saw it."""

    prompt: List[int]
    max_new: int
    due: float                 # when it should have been sent
    judged: bool = True        # False: a warm-up request, never a sample
    client: int = -1
    rid: int = -1
    profile: Any = None        # the server's ProfileInfo for it
    output_tokens: int = 0
    error: Optional[str] = None

    @property
    def first_token(self):
        return self.profile.first_token_time if self.profile else 0.0

    @property
    def finished(self):
        return self.profile.finish_time if self.profile else 0.0

    @property
    def ttft_ms(self):
        """Due time to the host seeing the first token; None without one."""
        return (self.first_token - self.due) * 1e3 if self.first_token else None

    @property
    def gap_ms(self):
        """Mean gap between output tokens of a completed request: (last
        token - first token) / (output tokens - 1); None where undefined."""
        if not (self.finished and self.first_token and self.output_tokens > 1):
            return None
        return (self.finished - self.first_token) * 1e3 / (self.output_tokens - 1)


class Window:
    """What one measured window kept."""

    def __init__(self):
        self.opened = self.closed = 0.0
        self.samples: List[Sent] = []
        self.attempted = 0
        self.failed = 0
        self.tokens_received = 0
        self.stats_open = self.stats_close = None
        self.pages_peak = 0
        self.compiles = 0
        # the longest single turn of the window and when it began: a
        # stall of seconds shows here, not in a median
        self.longest_step_s = self.longest_step_at = 0.0

    @property
    def seconds(self):
        return self.closed - self.opened


def run(rm, gen, seconds, *, tracer=None, compiles=lambda: 0, log=print):
    """Warm up for ``gen.warmup_s``, then measure for ``seconds``."""
    pager = rm.engine.pager
    live: List[Sent] = []
    win = Window()
    t0 = clock()
    gen.start(t0)
    win.opened = t0 + gen.warmup_s
    opened = False
    at_open = {}

    def outputs(s):
        return len(rm.result(s.rid).output_tokens)

    while True:
        now = clock()
        if not opened and now >= win.opened:
            opened = True
            win.opened = now
            win.closed = now + seconds
            win.stats_open = dataclasses.replace(rm.stats)
            at_open = {s.rid: outputs(s) for s in live}
            compiles_open = compiles()
            log(f"[window] opened after {now - t0:.2f}s of traffic, "
                f"{len(live)} requests live")
        if opened and now >= win.closed:
            win.closed = now
            win.stats_close = dataclasses.replace(rm.stats)
            win.compiles = compiles() - compiles_open
            if tracer is not None:
                tracer.stop(rm)
            win.tokens_received += sum(
                outputs(s) - at_open.get(s.rid, 0) for s in live)
            break
        if tracer is not None and opened:
            tracer.tick(now, win, rm, live)

        for s in gen.due(now):
            with jax.profiler.TraceAnnotation("bench.submit"):
                s.rid = rm.submit(s.prompt, max_new_tokens=s.max_new)
                s.profile = rm.result(s.rid).profile
            live.append(s)
        with jax.profiler.TraceAnnotation("bench.step"):
            rm.step()
        win.pages_peak = max(win.pages_peak, pager.used_pages)

        before, now = now, clock()
        if opened and now - before > win.longest_step_s:
            win.longest_step_s, win.longest_step_at = now - before, before - win.opened
        for s in [s for s in live if s.finished]:
            live.remove(s)
            res = rm.result(s.rid)
            s.output_tokens = len(res.output_tokens)
            s.error = res.error
            if opened:
                win.tokens_received += s.output_tokens - at_open.get(s.rid, 0)
                if s.judged:
                    win.samples.append(s)
            gen.completed(s, now)

    win.attempted = len(win.samples)
    win.failed = sum(1 for s in win.samples if s.error)
    return win
