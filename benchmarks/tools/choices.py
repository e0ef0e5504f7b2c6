#!/usr/bin/env python3
"""How many block choices differ between the served path and the
reference on the probe's rows (a configuration with block-sparse
attention layers; ``minicpm-sala``): a choice is one judged row's set of
``topk`` blocks in one sparse layer and one KV group. The served
program keeps, in ``cache["chosen"]``, the blocks each row's LAST real
position chose in the newest step, which is every row the probe judges;
the reference returns its own choice for every position
(``references/minicpm_sala.forward``). Two nearly level block scores
change places on any rounding difference, so a few choices differ in a
sound run; each is one block of ``topk``.

Prints one line per seed: choices compared (rows above ``dense_len``
only), choices that differ, blocks that differ, and the judged rows
with a differing choice in some layer.

  chiprun -- python3 benchmarks/tools/choices.py --workload minicpm-sala.longdoc-closed --seeds 101 102
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmarks.harness import model, probe, spec
    from flexflow_tpu.config import enable_compile_cache

    enable_compile_cache()
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    cell = spec.Cell(args.workload)
    config = cell.config
    reference = spec.load_module("references", config["reference"])
    dense_len = config["sparse_config"]["dense_len"]
    for seed in args.seeds:
        llm, params = model.build_server(config, seed)
        engine = llm.engine
        served = {}  # (row, position) -> (sparse layers, KV, blocks) bool
        run_mixed = engine.run_mixed

        def noting(last, toks, use_last, positions, *rest, **kw):
            out = run_mixed(last, toks, use_last, positions, *rest, **kw)
            chosen = np.asarray(jax.device_get(engine.cache["chosen"]))
            for row in range(positions.shape[0]):
                real = positions[row][positions[row] < engine.scratch_pos]
                if real.size:
                    served[(row, int(real[-1]))] = chosen[:, row]
            return out

        engine.run_mixed = noting
        seqs, judged = probe.served_logits(engine, cell.traffic,
                                           np.random.default_rng(seed))
        llm.engine = llm.rm = engine.run_mixed = None   # the server's 8 GB, before the reference
        del llm, engine, run_mixed, noting
        gc.collect()
        T = -(-max(len(s) for s in seqs) // 128) * 128
        tokens = np.zeros((len(seqs), T), np.int64)
        for r, s in enumerate(seqs):
            tokens[r, :len(s)] = s
        _, chosen = reference.forward(params, config, tokens)
        chosen = [np.asarray(c) for c in chosen]            # (B, T, KV, blocks) a layer
        compared = differ = blocks = 0
        rows_differ = set()
        for row, pos, _ in judged:
            if pos < dense_len:
                continue
            for layer, ref in enumerate(chosen):
                got = served[(row, pos)][layer][:, :ref.shape[-1]]
                for group in range(ref.shape[2]):
                    n = int((got[group] != ref[row, pos, group]).sum()) // 2
                    compared += 1
                    differ += n > 0
                    blocks += n
                    if n:
                        rows_differ.add((row, pos))
        line = {"seed": seed, "judged_rows": len(judged),
                "choices_compared": compared, "choices_that_differ": int(differ),
                "blocks_that_differ": blocks, "rows_with_a_differing_choice": len(rows_differ)}
        print(json.dumps(line), flush=True)
        del params, chosen
        gc.collect()


if __name__ == "__main__":
    main()
