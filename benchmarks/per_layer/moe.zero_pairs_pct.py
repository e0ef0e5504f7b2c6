"""Share of the real tokens' router choices that fell on outputs that
are no expert (zero-compute experts that return their input), over the
window's flushed steps and layers: ``SchedulerStats.moe_zero_pairs /
moe_routed_pairs`` (the step returns both with its sampled tokens).
Such a pair costs no expert FFN anywhere in the deployment: a third
under level scores (256 of 768 outputs), and a router that moves it
moves the step. None where the server keeps no such counters (a program
before PR 60, a family whose router's outputs are all experts)."""


def read(ctx):
    if not hasattr(ctx.window.stats_close, "moe_routed_pairs"):
        return None
    routed = ctx.stats_delta("moe_routed_pairs")
    return 100.0 * ctx.stats_delta("moe_zero_pairs") / routed if routed else None
