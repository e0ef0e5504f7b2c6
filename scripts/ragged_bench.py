#!/usr/bin/env python
"""The ragged paged attention call alone, on a RECORDED step of a cell.

Each case is one call of ``serve/kernels._ragged_paged_attention`` with
the operands a served step of a benchmark cell hands it: the slots, the
chunk, the heads, the table's width, and the rows of the step's recorded
mix (the ``mix`` of the cell's ``[roofline] kernel.*`` line in a traced
run: prefilling rows and their contexts, decoding rows and theirs; the
numbers are quoted beside each case). The page table is the SERVER's: a
row's live logical pages name pages of the pool and every other entry
names the scratch page (``serve/paging.PageAllocator``), whose block is
fetched once and not again, so a grid step no query sees costs what it
costs in the cell and not a page's read; an idle slot's row is all
scratch. The call is handed the rows' work list as a step makes it
(``kernels.step_work``, from the positions; a tree from before PR 63
has none and walks the table's whole width). The pool is two layers'
pages read through ``row_offset``, as the layer loop reads its carried
pool. (PR 55's first cases drew a page
for every entry and one prefilling row, and promised +18% where the
served call read -24%: PERF.md section 6.)

    chiprun -- python scripts/ragged_bench.py                  # every case
    chiprun -- python scripts/ragged_bench.py smallthinker.full smallthinker.full:wide
    python scripts/ragged_bench.py --tiny                      # CPU rehearsal

``<case>:wide`` / ``:narrow`` / ``:idle`` keep only the prefilling rows,
only the decoding rows, or none (the others idle), so that a call's time
splits into its chunk-wide grid steps, its narrow ones and the floor of
steps that compute nothing; ``:fetch`` is the whole step over a table
with a page of the pool behind EVERY entry, which is not the server's
and is there to show what that costs. ``--tree DIR`` times another checkout's
kernels (the parent's, unpacked with ``git archive``) under ``--label``;
each line carries a ``digest`` of the result and its distance from the
XLA reference in float32 on the same values. ``--ablate "a;b"`` sets
``RB_ABLATE`` to each name in turn and clears the call's cache: only a
scratch copy of the tree with hooks for it reads that variable, the
package does not.

``--bundles`` needs no chip: it compiles each case's call for a
DESCRIBED v5e (as tests/test_chip_compile_*.py does) with libtpu's own
dump of the kernel's final instruction bundles, and prints the kernel's
straight-line blocks (between branch targets) with their bundle counts
and what fills them: vector loads and stores, the vector ALUs, matmul
pushes and result pops, lane reductions, transcendentals. A bundle is
an issue cycle where nothing stalls, and the chip's times follow the
counts: the chunk-wide step of ``smallthinker.full`` is 5768 bundles on
the parent and 4944 on PR 55's first body, where the chip read 4.6 and
3.95 us a step (0.8 ns a bundle, both). A v5e bundle holds at most one
vector store, three loads and four ALU operations.

Off a TPU it exits without a reading unless ``--tiny`` or ``--bundles``
is given (``--tiny``: Pallas interpret mode, a rehearsal of the code, no
time). A time is the median
of ``--reps`` runs of a jitted loop of ``--loop`` calls chained through
one element of the query, by the host clock around
``block_until_ready``. Nothing here is a benchmark cell.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

PAGE = 128


def _cases(tiny: bool):
    """name -> dict(R, C, H, KV, dk, NP, group_mask, tag, window, rows):
    rows are (first position, real queries) a slot."""
    c = {}
    # smallthinker-21b-a3b.doc12k-closed, traced (PR 55, call 11): mix
    # decode_rows 2.2 (ctx 28180: 12.8k each), prefill_rows 5.7 (706
    # tokens: 124 each; row ctx 38227: 6.7k each, uniform over 0-13k)
    st = [(1024, 128), (3328, 128), (5632, 128), (7936, 128), (10240, 128),
          (12032, 96), (12500, 1), (13100, 1)]
    c["smallthinker.full"] = dict(R=8, C=128, H=32, KV=4, dk=128, NP=129,
                                  rows=st)
    c["smallthinker.win"] = dict(R=8, C=128, H=32, KV=4, dk=128, NP=34,
                                 tag="_win", window=4096, rows=st)
    # mistral-7b.prefill-closed (PR 55, call 11): decode_rows 10 (ctx
    # 6884: 680 each), prefill_rows 5.3 (443 tokens: 84 each, the last
    # chunks short; row ctx 1737: 330 each)
    c["mistral.prefill"] = dict(
        R=16, C=128, H=32, KV=8, dk=128, NP=16,
        rows=[(0, 128), (128, 128), (256, 128), (512, 128), (768, 40)]
        + [(560 + 24 * i, 1) for i in range(10)] + [(0, 0)])
    # minicpm-sala.longdoc-closed (call 12): decode_rows 1.1 (17k),
    # prefill_rows 2.8 (354 tokens; row ctx 23353: 8.3k each); a mask a
    # KV group that keeps 64 blocks of 64 lines, a window of 2048 and
    # the first block past dense_len 8192: some 4 pages in 10 at 12k
    c["sala.sparse"] = dict(
        R=4, C=128, H=32, KV=2, dk=128, NP=146, group_mask=True, keep=0.4,
        rows=[(4096, 128), (8320, 128), (12544, 126), (17000, 1)])
    # mistral-7b.decode-closed (ledger, PR 62: prompts 64-128, answers
    # 384-640, 16 slots all decoding, a table of 16 entries for
    # max_sequence_length 2048): 16 rows at 70-700 lines, 50 pages of
    # the table's 256 entries
    c["mistral.decode"] = dict(
        R=16, C=1, H=32, KV=8, dk=128, NP=16,
        rows=[(n, 1) for n in (70, 101, 133, 166, 198, 231, 263, 296, 330,
                               365, 402, 441, 483, 530, 590, 700)])
    # laguna-xs.2.agent12k-closed, a full layer's call (PERF.md section
    # 5, PR 58: 13 rows decoding at 12.5 k beside 3 prefilling prompts
    # of 8-16 k; 48 query heads over 8, each group of 6 padded to 8 as
    # ``smallthinker._pad_groups`` hands them over)
    c["laguna.full"] = dict(
        R=16, C=128, H=64, KV=8, dk=128, NP=133,
        rows=[(2048, 128), (6144, 128), (11008, 128)]
        + [(12000 + 80 * i, 1) for i in range(13)])
    if tiny:  # the same code at a size the interpreter finishes
        for case in c.values():
            case.update(R=2, H=case["H"] // case["KV"] * 2, KV=2, NP=4,
                        rows=[(128, case["C"]), (300, 1)], window=256 * bool(
                            case.get("window")))
    return c


def _operands(case, part, rng, np, jax, K):
    jnp = jax.numpy
    R, C, H, KV, dk, NP = (case[k] for k in ("R", "C", "H", "KV", "dk", "NP"))
    keep_row = {"": lambda n: True, "wide": lambda n: n > 1,
                "narrow": lambda n: n == 1, "idle": lambda n: False,
                "fetch": lambda n: True}[part]
    rows = [(f, n) if keep_row(n) else (0, 0) for f, n in case["rows"]]
    window = case.get("window", 0)
    cache_len = NP * PAGE - 1 if not window else 1 << 20
    P = R * NP
    pos = np.full((R, C), cache_len, np.int64)
    table = np.full((R, NP), P, np.int32)       # the scratch page
    if part == "fetch":  # NOT the server's: a page behind every entry
        table = rng.permutation(P).reshape(R, NP).astype(np.int32)
    start = np.zeros((R,), np.int64)            # a window table's first line
    free = iter(rng.permutation(P))
    for r, (first, n) in enumerate(rows):
        if not n:
            continue
        pos[r, :n] = np.arange(first, first + n)
        last = (first + n - 1) // PAGE
        lo = max(0, last - NP + 1) if window else 0
        start[r] = lo * PAGE
        for j in range(lo, last + 1):
            table[r, j - lo] = next(free)
    pos = jnp.asarray(pos, jnp.int32)
    if window:  # models/smallthinker._window_mask, from true positions
        key = jnp.asarray(start, jnp.int32)[:, None] + jnp.arange(NP * PAGE)[None]
        key, q_pos = key[:, None, :], pos[:, :, None]
        mask = (key <= q_pos) & (key > q_pos - window) & (key < cache_len)
    else:
        mask = K.paged_serve_mask(None, pos, NP, PAGE, cache_len)
    if case.get("group_mask"):
        keep = rng.random((R, KV, C // 64, NP)) < case["keep"]
        keep = np.repeat(keep, 64, axis=2)
        own = (np.asarray(pos) // PAGE)[:, None, :, None] == np.arange(NP)
        keep = keep | own | (np.asarray(pos) >= cache_len)[:, None, :, None]
        mask = mask[:, None] & jnp.asarray(np.repeat(keep, PAGE, axis=-1))
    q = jnp.asarray(rng.normal(size=(R, C, H, dk)), jnp.bfloat16)
    layers, layer = 2, 1  # the carried pool: layer l's pages at l * (P + 1)
    pools = [jnp.asarray(rng.normal(size=(layers * (P + 1), PAGE, KV, dk)),
                         jnp.bfloat16) for _ in range(2)]
    q_len = jnp.asarray([n for _, n in rows], jnp.int32)
    work = None
    if hasattr(K, "step_work"):  # the step's list, made once a step
        work = jax.jit(lambda: K.step_work(
            pos, q_len, PAGE, NP, window, jnp.asarray(start, jnp.int32)))()
    return (q, *pools, jnp.asarray(table), mask, q_len,
            jnp.int32(layer * (P + 1)), work)


#: an operation of a bundle -> the unit whose slot it takes
_UNITS = (("vld", "load"), ("vst", "store"), ("vmatmul", "mxu"),
          ("vmatpush", "mxu"), ("vpop.f32.mrf", "mxu pop"),
          ("vpop.eup", "eup"), ("vpow", "eup"), ("vrcp", "eup"),
          ("vrot", "xlu"), ("vperm", "xlu"), ("vxpose", "xlu"))


def _bundles(args) -> None:
    """``--bundles``: the parent process starts itself once a case with
    libtpu told to dump, and reads the listing the compile leaves."""
    if os.environ.get("RAGGED_BENCH_DUMP"):  # the child: compile, no run
        import jax
        import jax.numpy as jnp
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        from flexflow_tpu.ops import flash_attention
        from flexflow_tpu.serve import kernels as K

        K._interpret = flash_attention._interpret = lambda: False
        jax.config.update("jax_enable_compilation_cache", False)
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        case = _cases(False)[args.cases[0].partition(":")[0]]
        R, C, H, KV, dk, NP = (case[k] for k in ("R", "C", "H", "KV", "dk", "NP"))
        gm, tag = case.get("group_mask", False), case.get("tag", "")
        pool = (2 * (R * NP + 1), PAGE, KV, dk)
        shapes = [((R, C, H, dk), jnp.bfloat16), (pool, jnp.bfloat16),
                  (pool, jnp.bfloat16), ((R, NP), jnp.int32),
                  ((R, KV, C, NP * PAGE) if gm else (R, C, NP * PAGE), jnp.bool_),
                  ((R,), jnp.int32), ((), jnp.int32)]
        if hasattr(K, "step_work"):
            shapes += [((), jnp.int32)] + [((R * NP + 1,), jnp.int32)] * 2

        def one(q, kp, vp, table, mask, q_len, offset, *work):
            return K._ragged_paged_attention(
                q, kp, vp, table, mask, q_len=q_len, group_mask=gm, tag=tag,
                row_offset=offset, **(dict(work=work) if work else {}))

        jax.jit(one).lower(*[jax.ShapeDtypeStruct(s, d, sharding=chip)
                             for s, d in shapes]).compile()
        return
    for want in args.cases or list(_cases(False)):
        dump = tempfile.mkdtemp(prefix="ragged_bench_llo_")
        env = dict(os.environ, RAGGED_BENCH_DUMP="1", TPU_LOG_DIR="disabled",
                   LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                                    "--xla_jf_dump_llo_text=true")
        run = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--bundles", "--tree",
             args.tree, want], env=env, capture_output=True, text=True)
        listings = [f for f in glob.glob(f"{dump}/*paged*final_bundles.txt")
                    if "schedule-analysis" not in f]
        if not listings:
            print(want, "no listing:", run.stderr.strip()[-400:])
            continue
        bundle = {}
        for line in open(listings[0]):
            m = re.match(r"\s*(0x[0-9a-f]+|\d+)\s+(?:[A-Z]{2})?\s*:\s*>?\s*\{(.*)", line)
            if m:
                bundle[int(m.group(1), 0)] = m.group(2)
        cuts = {0, max(bundle) + 1}
        for n, text in bundle.items():
            for m in re.finditer(r"sbr\.rel.*?target bundleno = (\d+)", text):
                cuts |= {int(m.group(1)), n + 1}
        cuts = sorted(cuts)
        print(f"{want} [{args.label}]: {max(bundle) + 1} bundles in all; "
              f"blocks of {args.block} or more:")
        for lo, hi in zip(cuts, cuts[1:]):
            if hi - lo < args.block:
                continue
            units = collections.Counter()
            for n in range(lo, hi):
                for op in re.findall(r"= (v[\w.]+)", bundle.get(n, "")):
                    units[next((u for prefix, u in _UNITS if op.startswith(prefix)),
                               "lane reduce" if "xlane" in op else "alu")] += 1
            if units:
                print(f"  [{lo:6d}, {hi:6d}) {hi - lo:6d} bundles: " + ", ".join(
                    f"{u} {n}" for u, n in units.most_common()))
        shutil.rmtree(dump, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cases", nargs="*")
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--label", default="tree")
    ap.add_argument("--ablate", default="")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--bundles", action="store_true")
    ap.add_argument("--block", type=int, default=1000)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--loop", type=int, default=8)
    ap.add_argument("--out", default="chiprun_out/ragged_bench.jsonl")
    args = ap.parse_args()
    sys.path.insert(0, args.tree)
    if args.bundles:
        return _bundles(args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.serve import kernels as K

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        sys.exit("not on a TPU: no reading (--tiny rehearses the code)")
    if args.tiny:
        args.reps, args.loop = 1, 1
    cases = _cases(args.tiny)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for want in args.cases or list(cases):
        name, _, part = want.partition(":")
        case = cases[name]
        gm, tag = case.get("group_mask", False), case.get("tag", "")
        ops = _operands(case, part, np.random.default_rng(55), np, jax, K)

        def one(q, kp, vp, table, mask, q_len, offset, work):
            return K._ragged_paged_attention(
                q, kp, vp, table, mask, q_len=q_len, group_mask=gm, tag=tag,
                row_offset=offset, **({} if work is None else dict(work=work)))

        def loop(q, *rest):
            def body(_, q):  # chained through one element: no pass over q
                o = one(q, *rest)
                return q.at[0, 0, 0, :].add((o[0, 0, 0, :] * 1e-6).astype(q.dtype))
            return jax.lax.fori_loop(0, args.loop, body, q)

        q, kp, vp, table, mask, q_len, offset, work = ops
        f32 = lambda x: x.astype(jnp.float32)
        layer = lambda x: f32(jax.lax.dynamic_slice_in_dim(
            x, offset, x.shape[0] // 2))
        R, C, H, dk = q.shape
        KV = case["KV"]
        k32, v32 = layer(kp), layer(vp)

        @jax.jit
        def ref_row(q, k32, v32, table, mask):  # a row at a time: (C, H, S) scores
            if not gm:
                return K.ragged_paged_attention_xla(f32(q), k32, v32, table, mask)
            return jnp.stack([
                K.ragged_paged_attention_xla(
                    f32(q), k32, v32, table, mask[:, g]
                ).reshape(1, C, KV, H // KV, dk)[:, :, g]
                for g in range(KV)], axis=2).reshape(1, C, H, dk)

        ref = np.concatenate([np.asarray(ref_row(
            q[r:r + 1], k32, v32, table[r:r + 1], mask[r:r + 1]))
            for r in range(R)])
        live = np.arange(C)[None, :] < np.asarray(q_len)[:, None]
        for abl in args.ablate.split(";"):
            if args.ablate:
                os.environ["RB_ABLATE"] = abl
                clear = getattr(getattr(K, "_ragged_call", None),
                                "cache_clear", None)
                if clear:
                    clear()
                jax.clear_caches()  # ``one`` is traced anew
            out = np.asarray(f32(jax.jit(one)(*ops)))
            err = float(np.abs(out - ref)[live].max()) if live.any() else 0.0
            fn = jax.jit(loop)
            fn(*ops).block_until_ready()
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                fn(*ops).block_until_ready()
                times.append((time.perf_counter() - t0) * 1e3 / args.loop)
            line = dict(
                platform=dev.platform, device_kind=dev.device_kind, case=want,
                side=args.label + (f"[{abl}]" if abl else ""),
                ms=round(float(np.median(times)), 5),
                ms_min=round(min(times), 5), err_vs_f32_ref=err,
                steps=R * case["NP"] if work is None else int(work[0]),
                digest=float(np.abs(out[live]).sum()) if live.any() else 0.0)
            print(json.dumps(line), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
