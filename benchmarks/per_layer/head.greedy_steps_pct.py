"""Share of the window's pipelined steps (mixed and decode) whose batch
was all greedy and so ran the program with the argmax head:
``SchedulerStats.head_greedy_steps / head_steps``. The engine chooses a
step's sampling head from its batch's decode-head arrays on every
dispatch (``serve/sampling.choose_sample_mode``); the other heads scale,
filter and draw over the whole (slots, vocabulary) logits, the ``full``
one behind a sort of them. None where the server keeps no such counters
(a program before PR 41) or the window held no pipelined step."""


def read(ctx):
    if not hasattr(ctx.window.stats_close, "head_steps"):
        return None
    steps = ctx.stats_delta("head_steps")
    return 100.0 * ctx.stats_delta("head_greedy_steps") / steps if steps else None
