"""Device programs launched per scheduler step: the ``XLA Modules``
events of the traced window over those named ``jit_ff_step_*`` (the
engine names every step program from its key). 1.0 is a step that costs
one launch; each program beside it (the key split, an unstack) is a
launch the host pays for. None where no program carries the name (a
program before PR 27)."""


def read(ctx):
    t = ctx.trace
    inside = [n for n, s, dur, _ in getattr(t, "modules", ())
              if t.lo <= s and s + dur <= t.hi]
    steps = sum(1 for n in inside if n.startswith("jit_ff_step_"))
    return len(inside) / steps if steps else None
