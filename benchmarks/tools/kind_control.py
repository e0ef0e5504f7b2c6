#!/usr/bin/env python3
"""``tools/control.py`` for a configuration whose layers are of two
KINDS with a reference that has an arm a mechanism (``references/
laguna.py``): the readings its ``tolerance`` is set from, in ONE
process, for each seed the probe's numbers for

  served     the program as the configuration serves it
  ref_int8   the float32 reference computed in int8 (``control_bits=8``)
  ref_whole  the float32 reference with its window layers attending the
             WHOLE context (``window=False``): what a window layer would
             compute if its mask, its table or the freeing of its pages
             were wrong towards more keys
  ref_plain  the float32 reference with its full layers' rope PLAIN
             (``yarn=False``: no ramp, cos and sin times 1): what a full
             layer would compute under the window layers' kind of table

each against the float32 reference on the same seeded weights and
tokens. Every control has to FAIL the configuration's limit on every
row, or the file says what ``correct`` cannot see. Prints one line per
seed and arm and the span over seeds; row by row readings go to
``chiprun_out/control_<config>.jsonl``.

  chiprun -- python3 benchmarks/tools/kind_control.py --workload laguna-xs.2.agent12k-closed --seeds 101 102
"""
import argparse
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = {"ref_int8": dict(control_bits=8), "ref_whole": dict(window=False),
            "ref_plain": dict(yarn=False)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--arms", nargs="+", default=["served", "ref_int8"])
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
        t0 = time.perf_counter()
        llm, params = model.build_server(config, seed)
        seqs, judged = probe.served_logits(
            llm.engine, cell.traffic, np.random.default_rng(seed))
        llm.engine = llm.rm = None
        del llm
        gc.collect()
        t1 = time.perf_counter()
        want = probe.reference_rows(config, params, seqs, judged)
        print(f"[seed {seed}] server and probe {t1 - t0:.1f}s, reference "
              f"{time.perf_counter() - t1:.1f}s, rows {len(judged)}", flush=True)
        for arm in args.arms:
            if arm == "served":
                readings = probe.against(config, want, judged)
            else:
                got = reference.judged_logits(
                    params, config, *want[1], routings=False, **CONTROLS[arm])[0]
                readings = probe.against(config, want, [
                    (row, pos, got[row, j, 0])
                    for (row, pos, _), j in zip(judged, want[2])])
            share = sorted(r[3] for r in readings)
            line = {"seed": seed, "arm": arm, "rows": len(readings),
                    "rms_share_worst": share[-1],
                    "rms_share_median": statistics.median(share),
                    "rms_share_smallest": share[0],
                    f"rows_over_{tol['limit']}": sum(s > tol["limit"] for s in share),
                    # the rows a limit would have to leave room for
                    "rms_share_top": [round(s, 5) for s in share[-12:]],
                    "rms_share_worst_own_routing": max(r[6][0][0] for r in readings),
                    "rows_under_another_routing": sum(r[5] != 0 for r in readings),
                    "largest_margin_overruled": max(r[6][r[5]][1] for r in readings),
                    "smallest_router_margin": min(r[4] for r in readings)}
            print(json.dumps(line), flush=True)
            with open(rows_path, "a") as f:
                for r in readings:
                    f.write(json.dumps({"seed": seed, "arm": arm, "row": r}) + "\n")
            summary.setdefault(arm, []).append(line)
        del params, want
        gc.collect()
    for arm, lines in summary.items():
        for k in [k for k in lines[0] if k not in ("seed", "arm", "rows", "rms_share_top")]:
            vals = [l[k] for l in lines]
            print(f"{arm} {k}: smallest {min(vals):.5f} largest {max(vals):.5f} "
                  f"over {len(vals)} seeds", flush=True)


if __name__ == "__main__":
    main()
