"""What decides ``correct``: seeded sequences go through the served
step programs — chunked prefill, a mixed step in which some rows decode
while others prefill, pure decode steps, all over the paged pool with
the Pallas kernel — and every logit row the server would have sampled
from is compared with the plain reference's (``references/``), which
sees the same tokens and the same seeded weights and nothing else.

Decoded tokens are forced (drawn from the seed), not sampled: with
random weights the top logits are closer than any rounding difference,
so a sampled continuation would fork and there would be nothing to
compare. Logits are compared, row by row.

Two numbers per row:
  max_share  max|d| / max|reference row|      (the issue's, PR 23's)
  rms_share  rms(d) / rms(reference row)
The configuration file's ``tolerance`` says which one is judged and
against which limit, with the chip readings it was set from; every
judged row has to be inside it (``verdict``).

This covers the step programs WITH their logits returned
(``run_mixed(with_logits=True)``, driven here row by row with the
engine's own pager), siblings of the programs the window runs
(``with_logits=False``, driven by the scheduler): the same model code,
kernels and page pool, not the scheduler's path to them.
"""
import jax
import numpy as np

from . import lengths, spec

ROWS = 8          # probe sequences: the first half starts first
DECODE_STEPS = 4  # pure C=1 steps after both halves have prefilled


def _plan(traffic, rng, vocab, n_rows):
    """Prompt lengths at evenly spaced quantiles of the CELL'S OWN
    prompt distribution, both ends included (the longest crosses the
    most pages), and enough forced tokens for every later step."""
    dist = traffic["prompt_tokens"]
    lens = [lengths.quantile(dist, min(max(u, 1e-6), 1 - 1e-6))
            for u in np.linspace(0.0, 1.0, n_rows)]
    lens = [lens[i] for i in rng.permutation(n_rows)]
    return [lengths.tokens(rng, n, vocab) for n in lens]


class _Rows:
    """Host-side bookkeeping of the probe's slots: what each row has
    cached, and the (R, C) arrays one ``run_mixed`` call takes."""

    def __init__(self, engine, seqs):
        self.engine = engine
        self.seqs = seqs            # row -> prompt + forced tokens so far
        self.done = [0] * len(seqs)  # tokens of seqs[row] already cached
        self.judged = []            # (row, position, logits)

    def step(self, chunk, feed):
        """Run one (R, chunk) step; ``feed`` maps row -> number of new
        tokens (from ``seqs[row][done:]``). Keeps each fed row's logits
        at its last fed position."""
        eng = self.engine
        R = eng.num_slots
        toks = np.zeros((R, chunk), np.int32)
        pos = np.full((R, chunk), eng.scratch_pos, np.int32)
        idx = np.zeros((R,), np.int32)
        for row, n in feed.items():
            lo = self.done[row]
            toks[row, :n] = self.seqs[row][lo:lo + n]
            pos[row, :n] = np.arange(lo, lo + n)
            idx[row] = n - 1
            self.done[row] = lo + n
            if not eng.pager.ensure(row, lo + n):
                raise RuntimeError("probe: the page pool is too small")
        ones = np.ones(R, np.float32)
        _, logits = eng.run_mixed(
            np.zeros(R, np.int32), toks, np.zeros(R, bool), pos, idx,
            jax.random.PRNGKey(0), np.ones(R, bool), ones, ones,
            np.zeros(R, np.int32), with_logits=True,
        )
        logits = np.asarray(jax.device_get(logits), np.float32)
        for row in feed:
            self.judged.append((row, self.done[row] - 1, logits[row]))


def served_logits(engine, traffic, rng):
    """Drive the probe through ``engine``; returns (sequences, judged)
    where judged is a list of (row, position, logits (V,))."""
    C = engine.serving.mixed_chunk
    vocab = engine.cfg.vocab_size
    n_rows = min(ROWS, engine.num_slots)
    prompts = _plan(traffic, rng, vocab, n_rows)
    first, second = range(n_rows // 2), range(n_rows // 2, n_rows)
    mixed_steps = max(-(-len(prompts[r]) // C) for r in second)
    forced = mixed_steps + DECODE_STEPS
    plens = [len(p) for p in prompts]
    seqs = [p + lengths.tokens(rng, forced, vocab) for p in prompts]
    rows = _Rows(engine, seqs)

    def chunk_of(row):
        return min(C, plens[row] - rows.done[row])

    # chunked prefill of the first half; only a row's FINAL chunk yields
    # a row the server samples from, so only that one is judged
    while any(rows.done[r] < plens[r] for r in first):
        before = len(rows.judged)
        rows.step(C, {r: chunk_of(r) for r in first if rows.done[r] < plens[r]})
        rows.judged[before:] = [j for j in rows.judged[before:]
                                if j[1] == plens[j[0]] - 1]
    # mixed steps: the first half decodes one forced token a step while
    # the second half prefills
    while any(rows.done[r] < plens[r] for r in second):
        before = len(rows.judged)
        feed = {r: 1 for r in first}
        feed.update({r: chunk_of(r) for r in second if rows.done[r] < plens[r]})
        rows.step(C, feed)
        rows.judged[before:] = [j for j in rows.judged[before:]
                                if j[1] >= plens[j[0]] - 1]
    for _ in range(DECODE_STEPS):
        rows.step(1, {r: 1 for r in range(n_rows)})
    for r in range(n_rows):
        engine.pager.release(r)
    return seqs, rows.judged


def reference_rows(config, params, seqs, judged):
    """The reference's side: (logits (B, J, R, V), flip_margin
    (B, J, R), margin (B, J)) as ``references/<name>.judged_logits``
    returns them, the (tokens, positions) it was asked for, and for
    each judged row its index j."""
    T = max(len(s) for s in seqs)
    T = -(-T // 128) * 128  # few distinct shapes for the reference's jits
    tokens = np.zeros((len(seqs), T), np.int64)
    for r, s in enumerate(seqs):
        tokens[r, :len(s)] = s
    by_row = {}
    for row, pos, _ in judged:
        by_row.setdefault(row, []).append(pos)
    judge = np.zeros((len(seqs), max(len(v) for v in by_row.values())), np.int64)
    for row, ps in by_row.items():
        judge[row, :len(ps)] = ps
    reference = spec.load_module("references", config["reference"])
    want = reference.judged_logits(params, config, tokens, judge)
    index = [by_row[row].index(pos) for row, pos, _ in judged]
    return want, (tokens, judge), index


def against(config, reference, judged):
    """Per judged row: (row, position, max_share, rms_share, margin,
    routing, every routing's (rms_share, flip_margin)). A sparse model's
    row is read against the NEAREST of the reference's routings of that
    token that overrule no router margin over the tolerance's
    ``routing_margin`` (``references/decoder.py``); routing 0 is
    float32's own, and a dense model has no other."""
    (logits, flip_margin, margin), _, index = reference
    allowed = config["tolerance"].get("routing_margin", 0.0)
    out = []
    for (row, pos, got), j in zip(judged, index):
        ref = logits[row, j]                                  # (R, V)
        d = got[None, :] - ref
        max_share = np.abs(d).max(-1) / np.abs(ref).max(-1)
        rms_share = np.sqrt(np.mean(d * d, -1) / np.mean(ref * ref, -1))
        ok = flip_margin[row, j] <= allowed
        r = int(np.argmin(np.where(ok, rms_share, np.inf)))
        out.append((row, pos, float(max_share[r]), float(rms_share[r]),
                    float(margin[row, j]), r,
                    [(float(e), float(m)) for e, m
                     in zip(rms_share, flip_margin[row, j])]))
    return out


def compare(config, params, seqs, judged):
    return against(config, reference_rows(config, params, seqs, judged), judged)


def verdict(config, readings, log):
    """Prints every number compared beside its limit and decides. Every
    judged row is compared, each against the routing ``against`` found
    nearest, with the configuration's ``tolerance`` (``metric``,
    ``limit``); at most ``rows_over_allowed`` of them (none, unless the
    file says otherwise and why) may read over the limit."""
    tol = config["tolerance"]
    for r in readings:
        log(f"[probe] row {r[0]} pos {r[1]}: max_share {r[2]:.5f} "
            f"rms_share {r[3]:.5f} router_margin {r[4]:.4f} routing {r[5]} "
            f"(limit on {tol['metric']}: {tol['limit']})")
    c = compared(config, readings)
    worst, limit = c["worst_row_" + tol["metric"]]
    over, allowed = c["rows_over_limit"]
    log(f"[probe] {len(readings)} rows judged; worst {tol['metric']} "
        f"{worst:.5f} against the limit {limit}; rows over it {over} "
        f"against {allowed} allowed")
    return bool(over <= allowed)


def compared(config, readings):
    """The numbers ``verdict`` compares, each beside its limit, under
    short names: what a run prints last and keeps in its result."""
    tol = config["tolerance"]
    which = {"max_share": 2, "rms_share": 3}[tol["metric"]]
    return {
        "rows_judged": len(readings),
        "worst_row_" + tol["metric"]: [float(max(r[which] for r in readings)),
                                       tol["limit"]],
        "rows_over_limit": [int(sum(r[which] > tol["limit"] for r in readings)),
                            tol.get("rows_over_allowed", 0)],
    }
