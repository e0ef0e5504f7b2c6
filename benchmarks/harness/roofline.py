"""A roofline share: the least time the chip could take for the work
the algorithm needs — the larger of operations over peak FLOP/s and
bytes over peak bytes/s — over the time the trace shows. The peaks come
from ``peaks.json``; the counts from ``counts/``; the time from the
device trace, never from the host's clock."""
from . import spec


def step_mix(ctx, kind):
    """What an average step of ``kind`` ("decode": no row prefills;
    "mixed": some row does) held during the traced sub-window, from the
    loop's own notes of every turn (``trace.Tracer.rows``) and the
    scheduler's counters. A prefilling row is taken as half-way through
    its prompt — the mean over a prefill — so its cached lines are P/2
    and each of its tokens attends (P+1)/2 keys."""
    turns = [r for r in ctx.tracer.rows
             if (r[2] > 0) == (kind == "mixed") and r[0] + r[2] > 0]
    if not turns:
        return None
    n = len(turns)
    dec_rows = sum(r[0] for r in turns) / n
    dec_ctx = sum(r[1] for r in turns) / n
    pre_rows = sum(r[2] for r in turns) / n
    pre_prompt = sum(r[3] for r in turns) / n   # sum of their prompt lengths
    mixed_steps = ctx.stats_delta("mixed_steps", sub=True)
    pre_tokens = (ctx.stats_delta("prefill_tokens", sub=True) / mixed_steps
                  if kind == "mixed" and mixed_steps else 0.0)
    mean_prompt = pre_prompt / pre_rows if pre_rows else 0.0
    return dict(decode_rows=dec_rows, decode_ctx=dec_ctx,
                prefill_rows=pre_rows, prefill_tokens=pre_tokens,
                prefill_row_ctx=pre_prompt / 2.0,
                prefill_tok_ctx=pre_tokens * (mean_prompt + 1.0) / 2.0)


def share(ctx, counter, kind, seconds, label):
    """100 * least time / ``seconds`` for one step (or kernel call) of
    ``kind``; None where there is nothing to read. Says which bound
    binds on an earlier line."""
    if seconds is None or ctx.tracer is None:
        return None
    mix = step_mix(ctx, kind)
    if mix is None:
        return None
    flops, nbytes = spec.load_module("counts", counter).count(ctx.cfg, mix)
    by_compute = flops / ctx.peaks["bf16_flops_per_s"]
    by_memory = nbytes / ctx.peaks["hbm_bytes_per_s"]
    least = max(by_compute, by_memory)
    ctx.log(f"[roofline] {label}: {flops / 1e12:.4f} TFLOP -> "
            f"{by_compute * 1e3:.4f} ms, {nbytes / 1e9:.4f} GB -> "
            f"{by_memory * 1e3:.4f} ms; "
            f"{'compute' if by_compute > by_memory else 'memory'} binds; "
            f"measured {seconds * 1e3:.4f} ms; mix "
            f"{ {k: round(v, 1) for k, v in mix.items()} }")
    return 100.0 * least / seconds
