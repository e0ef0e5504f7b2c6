#!/usr/bin/env python3
"""``tools/control.py`` for a configuration whose window layers free
their pages: the readings its ``tolerance`` is set from, in ONE process,
for each seed the probe's numbers for

  served      the program as the configuration serves it
  ref_int8    the float32 reference computed in int8 (``control_bits=8``)
  ref_whole   the float32 reference with its window layers attending the
              WHOLE context (``window=False``): what a window layer
              would compute if its mask, its table or the freeing of
              its pages were wrong towards more keys

  served_deep the served rows that read OVER the limit (after ``served``),
              judged again with the reference's ``MAX_FLIPPED`` raised from 4
              to 8 (256 routings a token): the rows that then read under the
              limit were a token of the served path going the other way in a
              layer past its four tightest, which the bounded rule does not
              try; ``tight_layers`` counts each such token's layers under
              ``routing_margin``

  served_routing the rows over the limit, served AGAIN one row at a time
              (the sequence prefilled alone, then a C=1 step that holds the
              judged token and nothing else: the step's ``moe_counts`` then
              name that token's experts, a layer), and the float32 reference
              computed under THAT routing: whether a row over the limit is
              the served path's routing and nothing else, and which of its
              choices no routing of the bounded rule tries

each against the float32 reference on the same seeded weights and
tokens. Both controls have to FAIL the configuration's limit: if
``ref_whole`` passed, ``correct`` would be blind to the mechanism the
cell is for. Prints one line per seed and arm and the span over seeds;
row by row readings go to ``chiprun_out/control_<config>.jsonl``.

  chiprun -- python3 benchmarks/tools/window_control.py --workload smallthinker-21b-a3b.doc12k-closed --seeds 101 102
"""
import argparse
import gc
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = {"ref_int8": dict(control_bits=8), "ref_whole": dict(window=False)}
DEEP = 8  # MAX_FLIPPED of the ``served_deep`` arm


def rejudged_deep(np, probe, reference, config, params, want, judged, over):
    """``probe.against``'s readings of the judged rows ``over`` (indices
    into ``judged``) under 2^DEEP routings of each row's token, one
    sequence at a time (a row's logits under 256 routings are 155 MB)."""
    tokens, kept = want[1][0], reference.MAX_FLIPPED
    reference.MAX_FLIPPED = DEEP
    try:
        out = {}
        for row in sorted({judged[i][0] for i in over}):
            mine = [i for i in over if judged[i][0] == row]
            at = np.asarray([[judged[i][1] for i in mine]], np.int64)
            deep = reference.judged_logits(params, config, tokens[row:row + 1], at)
            again = probe.against(
                config, (deep, None, list(range(len(mine)))),
                [(0, judged[i][1], judged[i][2]) for i in mine])
            out.update(zip(mine, again))
        return out
    finally:
        reference.MAX_FLIPPED = kept


def served_routings(np, jax, engine, seqs, wanted):
    """{(row, position): (experts (layers, k), logits (V,))} of the
    tokens ``wanted`` [(row, position)] as the engine serves them alone
    in their slot: chunked prefill of ``seqs[row]`` up to the position,
    then a C=1 step whose one real token is the wanted one."""
    C, R = engine.serving.mixed_chunk, engine.num_slots
    ones = np.ones(R, np.float32)

    def step(row, lo, n, chunk):
        toks = np.zeros((R, chunk), np.int32)
        pos = np.full((R, chunk), engine.scratch_pos, np.int32)
        idx = np.zeros((R,), np.int32)
        toks[row, :n] = seqs[row][lo:lo + n]
        pos[row, :n] = np.arange(lo, lo + n)
        idx[row] = n - 1
        if not engine.pager.ensure(row, lo + n):
            raise RuntimeError("the page pool is too small")
        _, logits = engine.run_mixed(
            np.zeros(R, np.int32), toks, np.zeros(R, bool), pos, idx,
            jax.random.PRNGKey(0), np.ones(R, bool), ones, ones,
            np.zeros(R, np.int32), with_logits=True)
        counts = engine.split_fetch(np.asarray(jax.device_get(engine.step_fetch)))[1]
        return counts["moe_counts"], np.asarray(jax.device_get(logits), np.float32)[row]

    out = {}
    for row in sorted({r for r, _ in wanted}):
        done = 0
        for at in sorted(p for r, p in wanted if r == row):
            while done < at:
                n = min(C, at - done)
                step(row, done, n, C)
                done += n
            counts, logits = step(row, at, 1, 1)
            done = at + 1
            assert set(counts.sum(-1).tolist()) == {counts[0].sum()}, counts.sum(-1)
            out[row, at] = (np.stack([np.flatnonzero(c) for c in counts]), logits)
        engine.pager.release(row)
    return out


def under_routing(np, jax, reference, params, config, tokens, at, experts):
    """The reference's float32 logits (J, V) of ONE sequence's tokens at
    positions ``at`` (J,) with each layer's experts GIVEN (``experts``
    (J, layers, k): the weights are still the softmax over the chosen
    router outputs), and the router's outputs (J, layers, E) along that
    path, by which the caller says what float32 would have chosen."""
    jnp, F32 = jax.numpy, jax.numpy.float32
    a = reference._sizes(config)
    E, W = config["moe_num_primary_experts"], int(config["sliding_window_size"])
    at = jnp.asarray(at, jnp.int32)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        _, kvs, _ = reference._hidden(params, config, tokens)
        xv = jnp.take(params["embed"], tokens[at], axis=0).astype(F32)[:, None]
        routers = []
        for l, ((group, index, windowed, roped), (k, v)) in enumerate(
                zip(reference.layout(config), kvs)):
            r = xv @ params["route"]["w_router"][l].astype(F32)      # (J, 1, E)
            ids = jnp.asarray(experts[:, l], jnp.int32)[:, None, :]
            g = jax.nn.softmax(jnp.take_along_axis(r, ids, -1), axis=-1)
            gate = jnp.sum(jax.nn.one_hot(ids, E, dtype=F32) * g[..., None], axis=-2)
            routers.append(np.asarray(r[:, 0]))
            xv = reference._attention_at(
                xv, at, k, v, reference._layer(params, group, index), **a,
                roped=roped, window=W if windowed else 0)
            J, R, D = xv.shape
            xv = reference._experts(config, params, l, xv.reshape(J * R, D),
                                    gate.reshape(J * R, -1), 0).reshape(J, R, D)
        return (np.asarray(reference._head(params, config, xv[:, 0])),
                np.stack(routers, 1))


def other_choices(np, router, experts):
    """[(layer, ranks float32 would have chosen and the path did not,
    ranks the path chose instead, the margin overruled in the router
    outputs' standard deviations)] of one token: ``router`` (layers, E),
    ``experts`` (layers, k); ranks count from 1."""
    out = []
    for l, (r, mine) in enumerate(zip(router, experts)):
        order = np.argsort(-r, kind="stable")
        rank = {int(e): i + 1 for i, e in enumerate(order)}
        own = set(order[:len(mine)].tolist())
        gone, taken = own - set(mine.tolist()), set(mine.tolist()) - own
        if gone:
            margin = (min(r[e] for e in gone) - max(r[e] for e in taken)) / r.std()
            out.append((l, sorted(rank[e] for e in gone),
                        sorted(rank[e] for e in taken), round(float(margin), 5)))
    return out


def rms_share(np, got, want):
    d = got - want
    return float(np.sqrt(np.mean(d * d) / np.mean(want * want)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--arms", nargs="+", default=["served", "ref_int8", "ref_whole"])
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmarks.harness import model, probe, spec
    from flexflow_tpu.config import enable_compile_cache

    cell = spec.Cell(args.workload)
    enable_compile_cache()
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    config = cell.config
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows_path = os.path.join(out_dir, f"control_{config['name']}.jsonl")
    reference = spec.load_module("references", config["reference"])
    tol = config["tolerance"]
    summary = {}
    for seed in args.seeds:
        llm, params = model.build_server(config, seed)
        seqs, judged = probe.served_logits(
            llm.engine, cell.traffic, np.random.default_rng(seed))
        llm.engine = llm.rm = None
        del llm
        gc.collect()
        want = probe.reference_rows(config, params, seqs, judged)
        for arm in args.arms:
            if arm == "served":
                readings = served = probe.against(config, want, judged)
            elif arm == "served_routing":
                over = [i for i, r in enumerate(served) if r[3] > tol["limit"]]
                wanted = [(judged[i][0], judged[i][1]) for i in over]
                llm = model.build_server(config, seed, params=params)[0]
                again = served_routings(np, jax, llm.engine, seqs, wanted)
                llm.engine = llm.rm = None
                del llm
                gc.collect()
                with open(rows_path, "a") as f:
                    for row in sorted({r for r, _ in wanted}):
                        mine = [i for i in over if judged[i][0] == row]
                        at = [judged[i][1] for i in mine]
                        experts = np.stack([again[row, p][0] for p in at])
                        logits, router = under_routing(
                            np, jax, reference, params, config, want[1][0][row],
                            at, experts)
                        for j, i in enumerate(mine):
                            line = {
                                "seed": seed, "arm": arm, "row": row, "pos": at[j],
                                "judged": served[i][3],
                                "again_to_judged": rms_share(np, again[row, at[j]][1], judged[i][2]),
                                "again_to_its_routing": rms_share(np, again[row, at[j]][1], logits[j]),
                                "judged_to_that_routing": rms_share(np, judged[i][2], logits[j]),
                                "other_choices": other_choices(np, router[j], experts[j])}
                            print("[routing] " + json.dumps(line), flush=True)
                            f.write(json.dumps(line) + "\n")
                continue
            elif arm == "served_deep":
                over = [i for i, r in enumerate(served) if r[3] > tol["limit"]]
                deep = rejudged_deep(np, probe, reference, config, params, want,
                                     judged, over)
                readings = [deep[i] for i in over]
                for i in over:
                    print(f"[deep] row {served[i][0]} pos {served[i][1]}: "
                          f"{served[i][3]:.5f} -> {deep[i][3]:.5f} routing "
                          f"{deep[i][5]} of {sum(m != float('inf') for _, m in deep[i][6])}"
                          f" allowed", flush=True)
            else:
                got = reference.judged_logits(
                    params, config, *want[1], routings=False, **CONTROLS[arm])[0]
                readings = probe.against(config, want, [
                    (row, pos, got[row, j, 0])
                    for (row, pos, _), j in zip(judged, want[2])])
            over = [r for r in readings if r[3] > tol["limit"]]
            line = {"seed": seed, "arm": arm, "rows": len(readings),
                    "rms_share_worst": max(r[3] for r in readings),
                    "rms_share_median": statistics.median(r[3] for r in readings),
                    "rms_share_smallest": min(r[3] for r in readings),
                    f"rows_over_{tol['limit']}": len(over),
                    "rms_share_worst_own_routing": max(r[6][0][0] for r in readings),
                    "largest_margin_overruled": max(r[6][r[5]][1] for r in readings),
                    # by where the judged row sits: inside its first
                    # window no control on the window can show
                    # 2^(layers under routing_margin, of at most
                    # MAX_FLIPPED) routings overrule no larger margin
                    "tight_layers": sorted(
                        sum(m != float("inf") for _, m in r[6]).bit_length() - 1
                        for r in readings) if arm == "served_deep" else None,
                    "smallest_past_the_window": min(
                        (r[3] for r in readings
                         if r[1] >= config["sliding_window_size"]), default=None)}
            print(json.dumps(line), flush=True)
            with open(rows_path, "a") as f:
                for r in readings:
                    f.write(json.dumps({"seed": seed, "arm": arm, "row": r}) + "\n")
            summary.setdefault(arm, []).append(line)
        del params, want
        gc.collect()
    for arm, lines in summary.items():
        for k in [k for k in lines[0]
                  if k not in ("seed", "arm", "rows", "tight_layers")]:
            vals = [l[k] for l in lines if l[k] is not None]
            print(f"{arm} {k}: smallest {min(vals):.5f} largest {max(vals):.5f} "
                  f"over {len(vals)} seeds", flush=True)


if __name__ == "__main__":
    main()
