#!/usr/bin/env python3
"""The benchmark's one command.

  python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Builds the cell's configuration with weights drawn from
``--seed`` on the device, compiles the server, warms the cell's own
step programs, checks the served logits against the plain reference
(that decides ``correct``), runs the cell's traffic — warm-up, then a
window of ``--seconds`` — and prints ONE JSON object as its last line:
with ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics from a few traced seconds inside the window.
Everything else (set-up parts, sample counts, compile counts,
memory) goes on earlier lines.

It needs a TPU whose ``device_kind`` is in ``peaks.json`` and as many
chips as the cell asks for; otherwise it exits non-zero with no result.

  JAX_PLATFORMS=cpu python3 benchmarks/run.py --workload <cell> --rehearse 1
      the same control flow at a tiny size on the CPU (interpret-mode
      kernels; ``--rehearse 4``: four virtual devices, tensor=4, for the
      sharded weight draw). Prints no result line and exits 2.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

EXIT_REHEARSAL = 2
# tiny sizes of the CPU rehearsal: every width down, lengths cut by 16
TINY_MODEL = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=8,
                  num_key_value_heads=4)
TINY_SERVING = dict(page_size=8, max_requests_per_batch=4,
                    max_sequence_length=160, max_cached_tokens=640,
                    prefill_chunk=16)
TINY_LENGTHS = 16
# float32 at two layers: sound runs read under 1e-3, the int8 control
# over 3e-3 (my CPU readings, PR 26; benchmarks/tests/test_control.py)
TINY_TOLERANCE = dict(limit=1.5e-3, rows_over_allowed=0)


def log(msg):
    print(msg, flush=True)


def tiny(cell):
    """The cell at rehearsal size (CPU only; never a result)."""
    # float32: XLA:CPU has no bf16 x bf16 -> f32 dot for the expert einsum
    cell.config = dict(cell.config, dtype="float32", **TINY_MODEL)
    cell.config["serving"] = dict(cell.config["serving"], **TINY_SERVING)
    cell.config["tolerance"] = dict(cell.config["tolerance"], **TINY_TOLERANCE)
    t = dict(cell.traffic)
    for k in ("prompt_tokens", "answer_tokens"):
        d = dict(t[k])
        for f in ("lo", "hi"):
            d[f] = max(2, d[f] // TINY_LENGTHS)
        t[k] = d
    t["clients"] = 4
    t["warmup_s"] = 1.0
    cell.traffic = t


class Compiles:
    """Programs lowered in this process, by JAX's own monitoring events
    (a persistent-cache hit lowers too): the window may hold none."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1

    def __call__(self):
        return self.n


def warm_step_keys(rm, chunk, rng, vocab):
    """Run the cell's own two step programs once through the server:
    a prompt of two chunks (the mixed step) answered with a few tokens
    (the decode step)."""
    from benchmarks.harness import lengths

    rid = rm.submit(lengths.tokens(rng, chunk + 2, vocab), max_new_tokens=6)
    while not rm.result(rid).profile.finish_time:
        rm.step()
    rm.drain()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(1, 4), default=0,
                    help="CPU rehearsal on this many virtual devices; "
                         "no result line, exit 2")
    args = ap.parse_args(argv)

    from benchmarks.harness import spec

    cell = spec.Cell(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.rehearse}")
        tiny(cell)
        cell.config["machine"] = {"model": args.rehearse}
        cell.chips = args.rehearse

    import jax
    import numpy as np

    from benchmarks.harness import loop, model, probe, reduce, trace
    from flexflow_tpu.config import enable_compile_cache

    def phase(name, since):
        log(f"[setup] {name}: {time.perf_counter() - since:.2f}s")
        return time.perf_counter()

    t = phase("imports", T_PROCESS)
    log(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    t = phase("device runtime start", t)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell.chips}
    log(f"jax {jax.__version__}; {len(devices)} x {device['kind']} "
        f"({device['platform']}); cell {cell.name} wants {cell.chips}")
    peaks = spec.load_json("peaks.json")["devices"]
    if len(devices) < cell.chips:
        sys.exit(f"benchmark: {cell.name} needs {cell.chips} chips, JAX "
                 f"sees {len(devices)} — no result")
    if not args.rehearse:
        if device["platform"] != "tpu":
            sys.exit(f"benchmark: needs a TPU, JAX found {device} — no result")
        if device["kind"] not in peaks:
            sys.exit(f"benchmark: no peaks for device kind "
                     f"{device['kind']!r} in peaks.json — no result")
    compiles = Compiles()

    llm, params = model.build_server(cell.config, args.seed)
    jax.block_until_ready(params)
    rm, engine = llm.rm, llm.engine
    log(f"[setup] weights {model.param_bytes(params)} bytes, pool "
        f"{engine.pager.num_pages} pages of {engine.pager.page_size}")
    t = phase("weights and server", t)
    rng = np.random.default_rng(args.seed)
    vocab = llm.cfg.vocab_size
    warm_step_keys(rm, engine.serving.mixed_chunk, rng, vocab)
    t = phase("step programs (compile or cache load)", t)

    seqs, judged = probe.served_logits(engine, cell.traffic, rng)
    t = phase("probe through the served path", t)
    readings = probe.compare(cell.config, params, seqs, judged)
    correct = probe.verdict(cell.config, readings, log)
    compared = probe.compared(cell.config, readings)
    t = phase("reference", t)

    kind = spec.load_module("generators", cell.traffic["kind"])
    gen = kind.Generator(cell.traffic, rng, vocab, args.seconds)
    tracer = None
    if args.trace:
        tracer = trace.Tracer(length_s=min(4.0, 0.4 * args.seconds))
    t = phase("traffic drawn", t)
    log(f"[setup] traced steps so far: "
        f"{engine.retrace_guard.compile_counts()}")

    t_traffic = time.perf_counter()
    win = loop.run(rm, gen, args.seconds, tracer=tracer, compiles=compiles,
                   log=log)
    setup_s = win.opened - T_PROCESS
    log(f"[setup] traffic warm-up: {win.opened - t_traffic:.2f}s; "
        f"setup_s {setup_s:.2f}")
    log(f"[window] {win.seconds:.3f}s; samples {win.attempted}, failed "
        f"{win.failed}; tokens received {win.tokens_received}; programs "
        f"lowered inside the window {win.compiles}; longest turn of the "
        f"loop {win.longest_step_s * 1e3:.1f} ms at +{win.longest_step_at:.1f}s; "
        f"traced steps {engine.retrace_guard.compile_counts()}")
    if win.compiles:
        sys.exit(f"benchmark: {win.compiles} programs compiled inside the "
                 "measured window — no result")
    stats = [d.memory_stats() or {} for d in devices[: cell.chips]]
    device["memory_peak_bytes"] = max(
        (s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    log(f"[memory] peak_bytes_in_use per device: "
        f"{[s.get('peak_bytes_in_use') for s in stats]}; bytes_limit "
        f"{[s.get('bytes_limit') for s in stats]}")

    ctx = reduce.Context(window=win, setup_s=setup_s,
                         cfg=cell.config, engine_serving=engine.serving,
                         peaks=peaks.get(device["kind"]),
                         pool_pages=engine.pager.num_pages, log=log,
                         tracer=tracer)
    log(f"[window] mixed steps by width: {ctx.steps_by_width()}")
    out = {"correct": bool(correct), "attempted": win.attempted,
           "failed": win.failed, "metrics": {}, "device": device}
    if tracer is not None:
        try:
            ctx.load_trace()
        except reduce.NoDevicePlane as e:
            if not args.rehearse:
                raise
            log(f"[trace] rehearsal: {e}; trace metrics left out")
        else:
            device["busy_s"] = ctx.trace.busy_s
            device["window_s"] = ctx.trace.window_s
            out["breakdown"] = ctx.trace.breakdown()
    metrics = cell.per_layer if args.trace else cell.end_to_end
    folder = "per_layer" if args.trace else "end_to_end"
    for m in metrics:
        value = spec.load_module(folder, m["name"]).read(ctx)
        if value is not None:
            out["metrics"][m["name"]] = {"value": float(value),
                                         "unit": m["unit"]}
    out["compared"] = compared  # last: each number beside its limit
    log(f"[run] {time.perf_counter() - T_PROCESS:.1f}s in all")
    print(f"[compared] correct {bool(correct)}: {json.dumps(compared)} "
          "(number, limit)", file=sys.stderr, flush=True)
    if args.rehearse:
        log(json.dumps(out)[:2000])
        print("benchmark: rehearsal complete — not a chip run, no result",
              file=sys.stderr)
        return EXIT_REHEARSAL
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
