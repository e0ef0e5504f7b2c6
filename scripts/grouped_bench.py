#!/usr/bin/env python
"""The grouped expert matmuls alone, by row tile and column block.

``serve/kernels.grouped_tile`` and ``grouped_block`` choose the block
shape of ``ff_moe_grouped_glu_*`` / ``ff_moe_grouped_down_*`` from
static shapes. This script times one sparse layer's two calls on the
chip at a cell's shapes under every candidate (tile, block of the
up-projections, block of the down-projection) and prints a table, so
that the rule is chosen from readings (PERF.md section 6, PR 51). The
rows are laid out as ``transformer.routed_experts_ffn`` lays them:
sorted by expert, every expert's rows from a multiple of the tile,
under the static bound ``pairs + experts * (tile - 1)``. The tokens per
expert are a multinomial draw of ``real`` pairs (even routing with its
natural skew), the same draw for every candidate of a case.

    chiprun -- python scripts/grouped_bench.py            # every case
    chiprun -- python scripts/grouped_bench.py --rule     # the rule's own
    chiprun -- python scripts/grouped_bench.py --route    # the layer whole
    python scripts/grouped_bench.py --tiny                 # CPU rehearsal

Beside each case it prints the least times of its weights' read and of
its FLOPs, their larger (where a call that hides one under the other
stands) and their sum (where one that hides nothing does), and each
line carries a ``digest`` of the layer's result, so that two trees'
runs of one case can be held to each other bit for bit (PR 52).

``--route`` times ``transformer.routed_experts_ffn`` WHOLE at a case's
shapes under the rule's tile (``--middle-tile`` sets
``GROUPED_MIDDLE_TILE`` for the run), a drawn routing of ``real / k``
real tokens, and beside it the two calls alone on that routing's tokens
per expert: their difference is what the layer costs OUTSIDE its
kernels (the layout's tables, the rows gathered in, the results
gathered out and summed; PR 57), with a digest of the layer's result.
The way out is CHOICE-MAJOR since PR 59 (``transformer.pairs_to_tokens``:
one gather by ``place.T``, the terms added in choice order): the
digests of k = 4, 6 and 10 are another sum order's than PR 57's, those
of k = 2 and 8 are PR 57's (CHANGES.md, PR 59, has both lists).

Off a TPU whose ``device_kind`` is in ``benchmarks/peaks.json`` it
exits without a reading unless ``--tiny`` is given (on the CPU the
kernels run in Pallas interpret mode: a rehearsal of the code, no
time), and every line it writes names the platform and the device kind
it ran on. A time is the mean over ``--reps`` calls of a jitted loop over
``--layers`` layers' weights (each layer reads its own experts, as a
step does), by the host clock around ``block_until_ready``. Nothing
here is a benchmark cell: it is not under ``benchmarks/``.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.serve import kernels

#: name -> (experts, D, F, static pairs, real pairs, layers, candidates);
#: a candidate is (tile, up block, down block), None: the rule's own
CASES = {
    # SmallThinker's padded step: 1024 places x 6, some 780 real tokens
    "smallthinker.1024": (64, 2560, 768, 6144, 4680, 6, [
        (16, 384, 512), (64, 384, 512), (128, 384, 512),
        (16, 768, 2560), (32, 768, 2560), (64, 768, 2560), (128, 768, 2560),
        (64, 768, 512), (64, 384, 2560), (64, 768, 1280)]),
    # its half rung (512 places), full
    "smallthinker.512": (64, 2560, 768, 3072, 3000, 6, [
        (16, 384, 512), (16, 768, 2560), (64, 768, 2560), (128, 768, 2560)]),
    # Mixtral's admission rung: 256 places x 2, 16 decoding rows and a chunk
    "mixtral.256": (8, 4096, 14336, 512, 288, 2, [
        (16, None, None), (64, None, None), (128, None, None)]),
    # Mixtral's 512 rung (128 rows an expert) and its 1024 rung (256)
    "mixtral.512": (8, 4096, 14336, 1024, 1000, 2, [
        (64, None, None), (128, None, None)]),
    "mixtral.1024": (8, 4096, 14336, 2048, 2000, 2, [
        (128, None, None), (256, None, None), (256, 512, None)]),
    # round two: the candidates the first table left open
    "smallthinker.served": (64, 2560, 768, 6144, 4680, 6, [
        (16, 384, 512), (16, 768, 2560), (32, 768, 2560), (64, 768, 2560),
        (128, 768, 2560)]),
    "smallthinker.512b": (64, 2560, 768, 3072, 3000, 6, [
        (32, 768, 2560), (64, 768, 2560)]),
    "mixtral.256b": (8, 4096, 14336, 512, 288, 2, [
        (32, None, None), (64, None, None)]),
    "mixtral.1024b": (8, 4096, 14336, 2048, 2000, 2, [
        (128, None, None), (256, 1024, 256), (256, 512, 256),
        (128, 1024, 256)]),
    # LFM2's decode step (64 rows x 4), its admission rung (256 places)
    # and its 2048 rung: blocks of 512 (as before PR 51) against whole
    "lfm2.c1": (64, 2048, 1536, 256, 256, 6, [
        (16, 512, 512), (16, 1536, 2048), (16, 768, 1024)]),
    "lfm2.256": (64, 2048, 1536, 1024, 700, 6, [
        (16, 512, 512), (16, 1536, 2048)]),
    "lfm2.2048": (64, 2048, 1536, 8192, 8000, 6, [
        (128, 512, 512), (128, 1536, 2048)]),
    # DeepSeek-V3's held share: 16 experts of 256 at the 256 rung's 2048
    # pairs, a sixteenth of them for the experts here (one tile each)
    "deepseek.256": (16, 7168, 2048, 2048, 128, 2, [(128, None, None)]),
    # its padded step: 512 places x 8, some 300 real tokens
    "deepseek.512": (16, 7168, 2048, 4096, 2400, 2, [(128, None, None)]),
    # Qwen3-Next's held quarter (128 of 512): the decode step (64 rows x
    # 10) and its widest rung (2048 places)
    "qwen3next.c1": (128, 2048, 512, 640, 640, 6, [(16, None, None)]),
    "qwen3next.2048": (128, 2048, 512, 20480, 20000, 6, [(128, None, None)]),
}
TINY = {"tiny": (4, 128, 256, 64, 40, 2, [(16, None, None), (64, 128, 128)])}
#: name -> (experts a token, the router's outputs) of ``--route``; the
#: experts held are the router's first ``experts`` outputs
ROUTES = {"smallthinker": (6, 64), "mixtral": (2, 8), "lfm2": (4, 64),
          "deepseek": (8, 256), "qwen3next": (10, 512), "tiny": (2, 8)}


def layout(counts, tile, pairs):
    """(rows' length, tile_group, n_active) as ``routed_experts_ffn``
    lays ``counts`` tokens per expert out."""
    n = len(counts)
    tiles = -(-(pairs + n * (tile - 1)) // tile)
    aligned = -(-counts // tile) * tile
    ends = np.cumsum(aligned)
    n_active = int(ends[-1] // tile)
    group = np.searchsorted(ends, np.arange(tiles) * tile, side="right")
    group = np.minimum(np.where(np.arange(tiles) < n_active, group,
                                group[max(n_active - 1, 0)]), n - 1)
    return tiles * tile, group.astype(np.int32), n_active


def draw(key, shape):
    """Seeded bfloat16 weights (or rows) at 0.02 an element."""
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(
        jnp.bfloat16)


def device_peaks(tiny):
    """({platform, device_kind}, the device's peaks of
    ``benchmarks/peaks.json`` or None): no reading off a TPU the file
    knows, as ``benchmarks/run.py`` has it; ``tiny`` rehearses anywhere
    and reckons no least time."""
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind}
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "peaks.json")) as f:
        peaks = json.load(f)["devices"].get(dev.device_kind)
    if not tiny and (dev.platform != "tpu" or peaks is None):
        sys.exit(f"grouped_bench: needs a TPU of benchmarks/peaks.json, JAX "
                 f"found {device}: no reading (--tiny rehearses on the CPU)")
    return device, peaks


def run_case(name, case, reps, out, device, peaks, counts=None):
    E, D, F, pairs, real, L, candidates = case
    rng = np.random.default_rng(51)
    if counts is None:
        counts = rng.multinomial(real, np.full(E, 1.0 / E))
    real = int(counts.sum())
    key = jax.random.PRNGKey(51)
    kg, ku, kd, kx = jax.random.split(key, 4)
    w_gate, w_up = draw(kg, (L * E, D, F)), draw(ku, (L * E, D, F))
    w_down = draw(kd, (L * E, F, D))
    print(f"# {name}: {E} experts, D {D}, F {F}, {pairs} static pairs, "
          f"{real} real (max {counts.max()} an expert, "
          f"{int((counts > 0).sum())} hit), {L} layers", flush=True)
    # the least times of the experts hit and of the real pairs, a layer
    # (the up-projections' are two thirds of each), with their larger,
    # which a call that hides one under the other stands at, and their
    # sum, which one that hides nothing does ({}: a rehearsal on a
    # device without peaks)
    least = {}
    if peaks:
        weights_ms = (3 * D * F * 2 * int((counts > 0).sum())
                      / peaks["hbm_bytes_per_s"] * 1e3)
        flops_ms = 6 * real * D * F / peaks["bf16_flops_per_s"] * 1e3
        least = {k: round(v, 4) for k, v in dict(
            weights_ms=weights_ms, flops_ms=flops_ms,
            larger_ms=max(weights_ms, flops_ms),
            sum_ms=weights_ms + flops_ms).items()}
        print("#   a layer's " + ", ".join(
            f"{k[:-3]} {v} ({round(v * 2 / 3, 4)} the up-projections')"
            for k, v in least.items()) + " ms", flush=True)
    rule = (kernels.grouped_tile(pairs, E),
            kernels.grouped_block(F, D, 2, 2), kernels.grouped_block(D, F, 1, 2))
    reference = None
    for tile, tf, td in candidates:
        line = None
        blocks = {(F, D, 2): tf, (D, F, 1): td}
        chosen = lambda w, d, n, i: blocks.get((w, d, n)) or rule_block(w, d, n, i)
        kernels.grouped_block, rule_block = chosen, kernels.grouped_block
        try:
            P, group, n_active = layout(counts, tile, pairs)
            rows = draw(kx, (pairs, D))
            # real rows at their aligned places, zeros elsewhere
            at = np.concatenate([
                np.arange(c) + s for c, s in zip(
                    counts, np.cumsum(-(-counts // tile) * tile)
                    - -(-counts // tile) * tile)])
            x = jnp.zeros((P, D), jnp.bfloat16).at[jnp.asarray(at)].set(
                rows[:real])

            @jax.jit
            def layers(x, w_gate, w_up, w_down, group):
                def body(l, acc):
                    g = group + l * E
                    act = kernels.grouped_glu(x, w_gate, w_up, g,
                                              jnp.int32(n_active), tm=tile)
                    glu_only = act[:1, :1].astype(jnp.float32)
                    y = kernels.grouped_down(act, w_down, g,
                                             jnp.int32(n_active), tm=tile)
                    return acc + y[jnp.asarray(at[:1])] + glu_only
                return jax.lax.fori_loop(0, L, body, jnp.zeros((1, D), jnp.float32))

            @jax.jit
            def glu_layers(x, w_gate, w_up, group):
                def body(l, acc):
                    act = kernels.grouped_glu(x, w_gate, w_up, group + l * E,
                                              jnp.int32(n_active), tm=tile)
                    return acc + act[jnp.asarray(at[:1])].astype(jnp.float32)
                return jax.lax.fori_loop(0, L, body, jnp.zeros((1, F), jnp.float32))

            def timed(fn, *args):
                jax.block_until_ready(fn(*args))
                t = time.perf_counter()
                for _ in range(reps):
                    r = fn(*args)
                jax.block_until_ready(r)
                return (time.perf_counter() - t) / (reps * L) * 1e3

            line = dict(
                device, case=name, tile=tile,
                up_block=kernels.grouped_block(F, D, 2, 2),
                down_block=kernels.grouped_block(D, F, 1, 2),
                rows=P, tiles_active=n_active, **least)
            line["rule"] = (tile, line["up_block"], line["down_block"]) == rule
            glu = timed(glu_layers, x, w_gate, w_up, jnp.asarray(group))
            line["glu_ms"] = round(glu, 4)
            both = timed(layers, x, w_gate, w_up, w_down, jnp.asarray(group))
            line.update(layer_ms=round(both, 4), down_ms=round(both - glu, 4))
            # the whole layer's result, for agreement between candidates
            act = kernels.grouped_glu(x, w_gate, w_up, jnp.asarray(group),
                                      jnp.int32(n_active), tm=tile)
            y = np.asarray(kernels.grouped_down(
                act, w_down, jnp.asarray(group), jnp.int32(n_active),
                tm=tile))[at]
            if reference is None:
                reference = y
            # to hold one tree's result to another's bit for bit
            line["digest"] = hashlib.sha256(y.tobytes()).hexdigest()[:16]
            line["off_first"] = float(
                np.abs(y - reference).max() / np.abs(reference).max())
        except Exception as e:  # a candidate the compiler refuses is a reading too
            line = dict(line or dict(device, case=name, tile=tile,
                                     up_block=tf, down_block=td),
                        failed=str(e)[:300])
        finally:
            kernels.grouped_block = rule_block
        print(json.dumps(line), flush=True)
        out.append(line)


def run_route(name, case, reps, out, device, peaks):
    """One line: the layer whole, its two calls alone on the same
    tokens per expert, their difference, the layer's digest."""
    from flexflow_tpu.models import transformer

    E, D, F, pairs, real, L, _ = case
    k, routed = ROUTES[name.split(".")[0]]
    T = pairs // k
    rng = np.random.default_rng(57)
    experts = np.argsort(rng.random((T, routed)), axis=1)[:, :k].astype(np.int32)
    is_real = np.arange(T) < real // k
    tile = transformer.routed_tile(T, k, (0, E), routed)
    counts = np.bincount(experts[is_real].reshape(-1), minlength=routed)[:E]
    run_case(name, case[:-1] + ([(tile, None, None)],), reps, out, device,
             peaks, counts)
    alone = out.pop()
    key = jax.random.PRNGKey(57)
    kg, ku, kd, kx = jax.random.split(key, 4)
    stacks = (draw(kg, (L, E, D, F)), draw(ku, (L, E, D, F)),
              draw(kd, (L, E, F, D)))
    h = draw(kx, (T, D)) * 50
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    layer = lambda h, experts, l, stacks: transformer.routed_experts_ffn(
        h, jnp.asarray(is_real), experts, weights, *stacks,
        experts_held=(0, E), routed=routed, layer=l, kernels="pallas")[0]

    @jax.jit
    def whole(h, experts, stacks):
        # every layer routes anew (its experts a rotation of the draw),
        # as a model's layers do: nothing of the layout leaves the loop
        return jax.lax.fori_loop(
            0, L, lambda l, x: x + layer(x, (experts + l) % routed, l, stacks),
            h)

    args = (h, jnp.asarray(experts), stacks)
    jax.block_until_ready(whole(*args))
    t = time.perf_counter()
    for _ in range(reps):
        r = whole(*args)
    jax.block_until_ready(r)
    whole_ms = (time.perf_counter() - t) / (reps * L) * 1e3
    y = np.asarray(jax.jit(layer)(h, args[1], jnp.int32(0), stacks).astype(
        jnp.float32))
    line = dict(device, case=name, tile=tile, pairs=pairs,
                real_pairs=int(is_real.sum()) * k, held_pairs=int(counts.sum()),
                rows=alone.get("rows"), whole_ms=round(whole_ms, 4),
                kernels_ms=alone.get("layer_ms"))
    if "layer_ms" in alone:
        line["route_ms"] = round(whole_ms - alone["layer_ms"], 4)
    line["digest"] = hashlib.sha256(y.tobytes()).hexdigest()[:16]
    print(json.dumps(line), flush=True)
    out.append(line)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", nargs="*", default=None)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--rule", action="store_true", help=(
        "time each case under the rule's own tile and blocks alone"))
    ap.add_argument("--counts", default=None, help=(
        "a .npy of served tokens per expert, (steps, layers, experts): the "
        "first case named runs on the step of median load, its middle layer"))
    ap.add_argument("--route", action="store_true", help=(
        "time routed_experts_ffn whole and its two calls alone: the layer "
        "outside its kernels"))
    ap.add_argument("--middle-tile", type=int, default=None, help=(
        "run under this GROUPED_MIDDLE_TILE (a reading; the rule keeps its own)"))
    args = ap.parse_args()
    if args.middle_tile:
        kernels.GROUPED_MIDDLE_TILE = args.middle_tile
    cases = TINY if args.tiny else CASES
    device, peaks = device_peaks(args.tiny)
    print(f"# {device}", flush=True)
    out = []
    served = None
    if args.counts:
        steps = np.load(args.counts)
        step = steps[np.argsort(steps.sum(axis=(1, 2)))[len(steps) // 2]]
        served = step[len(step) // 2].astype(np.int64)
    for name in args.cases or cases:
        if args.route:
            run_route(name, cases[name], 2 if args.tiny else args.reps, out,
                      device, peaks)
            continue
        if args.rule:
            E, _, _, pairs = cases[name][:4]
            cases[name] = cases[name][:-1] + (
                [(kernels.grouped_tile(pairs, E), None, None)],)
        run_case(name, cases[name], 2 if args.tiny else args.reps, out,
                 device, peaks, served)
        served = None
    if args.tiny:  # a rehearsal's times are the interpreter's: not kept
        return
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/grouped_bench.jsonl", "a") as f:
        for line in out:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
