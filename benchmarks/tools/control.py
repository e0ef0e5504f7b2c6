#!/usr/bin/env python3
"""The readings a configuration's ``tolerance`` is set from (How
``correct`` is decided, steps 3 and 4): in ONE process, for each seed,
the probe's numbers for

  served    the program as the configuration serves it (bf16 pool)
  kv_int8   the program's own int8 path: ``kv_quant="int8"``
  kv_int4   ``kv_quant="int4"``
  ref_int8  the float32 reference computed in int8: matmul weights and
            inputs and the cached K and V rounded to int8 (no program
            involved: the reference put in the program's place)

each against the float32 reference on the same seeded weights and
tokens. Prints one line per seed and arm (the worst row, the median
row, the rows over the configuration's limit) and the span over seeds at
the end; row by row readings, every routing's (``probe.against``), go to
``chiprun_out/control_<config>.jsonl``.

  chiprun -- python3 benchmarks/tools/control.py --workload <cell> --seeds 101 102 103 --arms served kv_int8 kv_int4
"""
import argparse
import gc
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

ARMS = {"served": {}, "kv_int8": {"kv_quant": "int8"},
        "kv_int4": {"kv_quant": "int4"}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--arms", nargs="+", default=["served", "kv_int8", "kv_int4", "ref_int8"])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU (control flow only)")
    args = ap.parse_args()

    from benchmarks import run as bench_run
    from benchmarks.harness import spec

    cell = spec.Cell(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        bench_run.tiny(cell)

    import jax
    import numpy as np

    from benchmarks.harness import model, probe
    from flexflow_tpu.config import enable_compile_cache

    enable_compile_cache()
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    config = cell.config
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows_path = os.path.join(out_dir, f"control_{config['name']}"
                             + ("_rehearsal" if args.rehearse else "") + ".jsonl")
    reference = spec.load_module("references", config["reference"])
    summary = {}
    for seed in args.seeds:
        params = None
        want = None
        for arm in args.arms:
            if arm == "ref_int8":
                got = reference.judged_logits(params, config, *want[1], control_bits=8)[0]
                readings = probe.against(config, want, [
                    (row, pos, got[row, j, 0]) for (row, pos, _), j in zip(judged, want[2])])
            else:
                llm, params = model.build_server(
                    config, seed, params=params, **ARMS[arm])
                seqs, judged = probe.served_logits(
                    llm.engine, cell.traffic, np.random.default_rng(seed))
                llm.engine = llm.rm = None
                del llm
                gc.collect()
                if want is None:
                    want = probe.reference_rows(config, params, seqs, judged)
                readings = probe.against(config, want, judged)
            line = {"seed": seed, "arm": arm, "rows": len(readings),
                    "max_share_worst": max(r[2] for r in readings),
                    "rms_share_worst": max(r[3] for r in readings),
                    "rms_share_median": statistics.median(r[3] for r in readings)}
            tol = config["tolerance"]
            line[f"rows_over_{tol['limit']}"] = sum(r[3] > tol["limit"] for r in readings)
            if len(readings[0][6]) > 1:  # a sparse model: what the routings bought
                line["rms_share_worst_own_routing"] = max(r[6][0][0] for r in readings)
                line["largest_margin_overruled"] = max(r[6][r[5]][1] for r in readings)
            print(json.dumps(line), flush=True)
            with open(rows_path, "a") as f:
                for r in readings:
                    f.write(json.dumps({"seed": seed, "arm": arm, "row": r}) + "\n")
            summary.setdefault(arm, []).append(line)
        del params
        gc.collect()
    for arm, lines in summary.items():
        for k in [k for k in lines[0] if k not in ("seed", "arm", "rows")]:
            vals = [l[k] for l in lines]
            print(f"{arm} {k}: smallest {min(vals):.5f} largest {max(vals):.5f} "
                  f"over {len(vals)} seeds", flush=True)


if __name__ == "__main__":
    main()
