"""CLI driver — ``python -m flexflow_tpu <cmd>``.

The reference ships C++ app drivers plus a ``flexflow_python``
interpreter launcher (reference ``inference/incr_decoding``,
``inference/spec_infer/spec_infer.cc:260``, ``python/flexflow/core/
flexflow_python``, flags parsed by ``FFConfig::parse_args``
model.cc:4049-4200). The TPU framework's equivalents:

  train        MLP training smoke (the mnist_mlp example)
  serve        incremental decoding or SpecInfer over an HF checkpoint
               directory (or a tiny random model when omitted)
  search       Unity auto-parallel compile + strategy/dot export
  serve-search offline ServingConfig search over the serving cost model
  spec-distill distill a draft from target logits + rank the draft
               ladder by measured accept-rate-per-draft-GFLOP

Reference-style degree flags are accepted with either one or two
leading dashes (-tensor-parallelism-degree / --tensor-parallelism-degree).
"""
from __future__ import annotations

import argparse
import os
import sys


def _degree_args(p: argparse.ArgumentParser):
    for flag, dest in [
        ("tensor-parallelism-degree", "tp"),
        ("pipeline-parallelism-degree", "pp"),
        ("data-parallelism-degree", "dp"),
        ("expert-parallelism-degree", "ep"),
        ("sequence-parallelism-degree", "sp"),
    ]:
        p.add_argument(
            f"--{flag}", f"-{flag}", dest=dest, type=int, default=1
        )


def _load_repo_module(relpath: str, name: str):
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(repo, relpath)
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cmd_train(args):
    mnist_mlp = _load_repo_module("examples/mnist_mlp.py", "mnist_mlp")
    mnist_mlp.main(num_devices=args.devices, epochs=args.epochs,
                   profiling=args.profiling)


def cmd_serve(args):
    import jax

    from .core.mesh import MachineSpec
    from .serve import GenerationConfig, ServingConfig, SpecConfig
    from .serve.llm import LLM, SSM

    n = args.tp * args.pp * args.ep * args.sp * max(1, args.dp)
    mesh = MachineSpec.from_degrees(
        n, tensor=args.tp, pipeline=args.pp, expert=args.ep,
        sequence=args.sp,
    ).make_mesh(jax.devices()[:n])
    if args.model_dir:
        llm = LLM.from_pretrained(args.model_dir, mesh=mesh)
    else:
        import jax.numpy as jnp

        from .models import llama

        cfg = llama.LLaMAConfig(
            vocab_size=512, hidden_size=128, intermediate_size=344,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=4, max_position_embeddings=512,
            dtype=jnp.float32,
        )
        llm = LLM(llama, cfg, mesh=mesh)
    sc = ServingConfig(
        max_requests_per_batch=args.max_requests_per_batch,
        max_sequence_length=args.max_sequence_length,
        kernels="pallas" if args.pallas else "xla",
        kv_layout=args.kv_layout,
        page_size=args.page_size,
        max_cached_tokens=args.max_cached_tokens,
        kv_quant=args.kv_quant,
        kv_shard=args.kv_shard,
        context_shards=args.context_shards,
        prefix_caching=args.prefix_caching,
        host_cache_bytes=args.host_cache_bytes,
        cache_policy=args.cache_policy,
        fused_decode=tuple(
            s for s in (args.fused_decode or "").split(",") if s
        ),
        replicas=args.replicas,
        router_policy=args.router_policy,
        prefill_replicas=args.prefill_replicas,
        decode_replicas=args.decode_replicas,
        slo_queue_delay_s=args.slo_queue_delay_s,
        migration_queue_budget=args.migration_queue_budget,
        replica_transport=args.replica_transport,
        replica_endpoints=tuple(
            s for s in (args.replica_endpoints or "").split(",") if s
        ),
        standby_replicas=args.standby_replicas,
        journal_dir=args.journal_dir,
        autoscale=args.autoscale,
        slo_ttft_s=args.slo_ttft_s,
        slo_tpot_s=args.slo_tpot_s,
        autoscale_cooldown_steps=args.autoscale_cooldown_steps,
        autoscale_min_replicas=args.autoscale_min_replicas,
        autoscale_max_replicas=(
            args.autoscale_max_replicas or args.replicas
        ),
    )
    ssms = []
    spec = None
    if args.ssm_dir or args.spec:
        if args.ssm_dir:
            ssms = [SSM.from_pretrained(args.ssm_dir, mesh=mesh)]
        else:  # layer-skip self-draft
            import dataclasses

            # round up to a multiple of pp so the draft stack also
            # shards over the pipe axis
            k = max(args.pp, llm.cfg.num_hidden_layers // 4)
            k = ((k + args.pp - 1) // args.pp) * args.pp
            dcfg = dataclasses.replace(llm.cfg, num_hidden_layers=k)
            dparams = dict(llm.params)
            dparams["layers"] = {
                nme: v[:k] for nme, v in llm.params["layers"].items()
            }
            ssms = [SSM(llm.family, dcfg, dparams, mesh=mesh)]
        spec = SpecConfig(beam_width=2, beam_depth=4)
    llm.compile(sc, ssms=ssms, spec=spec,
                quantization=args.quantization, offload=args.offload,
                output_file=args.output_file)
    if args.fault_plan:
        from .serve.cluster import ClusterManager, FaultPlan

        if not isinstance(llm.rm, ClusterManager):
            raise SystemExit(
                "--fault-plan requires a cluster (--replicas > 1 or "
                "disaggregated pools) — faults inject at the Replica "
                "surface"
            )
        llm.rm.attach_faults(FaultPlan.from_json(args.fault_plan))
    obs_buf = None
    recorder = None
    if args.trace_out or args.metrics_out or args.flight_recorder:
        # Observability (flexflow_tpu/obs): tracing + flight recorder
        # attach to whichever manager compile built (bare scheduler or
        # cluster); exports are written after the run below.
        from .obs import FlightRecorder, attach_observability

        if args.flight_recorder:
            recorder = FlightRecorder(out_dir=args.flight_recorder)
        obs_buf = attach_observability(llm.rm, recorder=recorder)
    prompts = args.prompt or [[3, 17, 91, 42, 7]]
    gen = GenerationConfig(num_beams=args.num_beams)
    outs = llm.generate(
        prompts,
        gen=gen if args.num_beams > 1 else None,
        max_new_tokens=args.max_new_tokens,
    )
    if obs_buf is not None:
        from .obs import write_chrome_trace, write_prometheus
        from .serve.cluster import ClusterManager

        if args.trace_out:
            doc = write_chrome_trace(args.trace_out, obs_buf)
            print(f"trace: {len(doc['traceEvents'])} events -> "
                  f"{args.trace_out} (load in ui.perfetto.dev)")
        if args.metrics_out:
            if isinstance(llm.rm, ClusterManager):
                sched = {str(r.index): r.rm.stats for r in llm.rm.replicas}
                cluster = llm.rm.stats
            else:
                sched = {"0": llm.rm.stats}
                cluster = None
            write_prometheus(
                args.metrics_out, scheduler=sched, cluster=cluster,
                profiles=[o.profile for o in outs],
            )
            print(f"metrics: prometheus snapshot -> {args.metrics_out}")
        if recorder is not None and recorder.paths:
            print(f"flight recorder: {len(recorder.paths)} dump(s) -> "
                  f"{args.flight_recorder}")
    for o in outs:
        p = o.profile
        print(o.output_text or o.output_tokens)
        print(
            f"  [steps={p.llm_decoding_steps} accepted={p.accepted_tokens} "
            f"latency={p.latency_s:.2f}s]"
        )


def cmd_search(args):
    import numpy as np

    import flexflow_tpu as ff

    cfg = ff.FFConfig(
        batch_size=8 * args.devices, num_devices=args.devices,
        search_budget=args.budget, search_measured=args.measured,
        export_strategy_file=args.export_strategy,
    )
    m = ff.FFModel(cfg)
    t = m.create_tensor((cfg.batch_size, 64), name="x")
    for _ in range(args.layers):
        t = m.dense(t, args.hidden, activation="relu")
    t = m.dense(t, 8)
    t = m.softmax(t)
    m.compile(optimizer=ff.SGDOptimizer(lr=0.05), auto_parallel=True)
    print("strategy:", m._search_report.machine)
    print("predicted step:", f"{m._search_report.best_cost*1e3:.3f} ms")
    if args.export_dot:
        m.export_dot(args.export_dot)
        print("dot written to", args.export_dot)


def cmd_serve_search(args):
    from .serve.autotune import (
        ModelGeometry,
        TrafficProfile,
        search_serving_config,
    )

    if args.model_dir:
        import json
        import types

        with open(os.path.join(args.model_dir, "config.json")) as f:
            geom = ModelGeometry.from_model_config(
                types.SimpleNamespace(**json.load(f))
            )
    else:
        geom = ModelGeometry(
            hidden_size=args.hidden_size,
            num_layers=args.num_layers,
            num_heads=args.num_heads,
            num_kv_heads=args.num_kv_heads or args.num_heads,
            intermediate_size=args.intermediate_size,
            vocab_size=args.vocab_size,
            param_bytes=args.param_bytes,
        )
    traffic = TrafficProfile(
        arrival_rate_rps=args.arrival_rate_rps,
        prompt_len_p50=args.prompt_p50,
        prompt_len_p99=args.prompt_p99 or 4 * args.prompt_p50,
        output_len_p50=args.output_p50,
        output_len_p99=args.output_p99 or 4 * args.output_p50,
        prefix_share=args.prefix_share,
        spec_accept_rate=args.spec_accept_rate,
    )
    best, report = search_serving_config(
        geom, traffic,
        chip_budget=args.chip_budget,
        slo_ttft_s=args.slo_ttft_s,
        slo_tpot_s=args.slo_tpot_s,
        max_requests_per_batch=args.max_requests_per_batch,
        max_sequence_length=args.max_sequence_length,
        allow_disagg=not args.no_disagg,
        top_k=args.top_k,
    )
    print(report.summary())
    if best is None:
        raise SystemExit(2)
    for cand, pred in report.table:
        print(
            f"  tp={cand.tp} pp={cand.pp} replicas={cand.replicas} "
            f"page={cand.page_size} kv={cand.kv_quant or 'fp'} "
            f"spec={'on' if cand.speculation else 'off'} "
            f"disagg={cand.prefill_replicas}p/{cand.decode_replicas}d "
            f"-> {pred.tokens_per_s:.0f} tok/s "
            f"ttft_p99={pred.ttft_s_p99 * 1e3:.1f}ms "
            f"tpot_p99={pred.tpot_s_p99 * 1e3:.2f}ms "
            f"{'feasible' if pred.feasible else pred.reason}"
        )
    sc = best.to_serving_config()
    sc.validate_cluster()
    flags = [
        "--kv-layout paged",
        f"--page-size {sc.page_size}",
        f"--max-requests-per-batch {sc.max_requests_per_batch}",
        f"--max-sequence-length {sc.max_sequence_length}",
        f"--replicas {sc.replicas}",
        f"--tensor-parallelism-degree {best.tp}",
        f"--pipeline-parallelism-degree {best.pp}",
    ]
    if sc.kv_quant:
        flags.append(f"--kv-quant {sc.kv_quant}")
    if sc.prefill_replicas:
        flags += [f"--prefill-replicas {sc.prefill_replicas}",
                  f"--decode-replicas {sc.decode_replicas}"]
    if best.speculation:
        flags.append("--spec")
    print("serve with: python -m flexflow_tpu serve " + " ".join(flags))


def cmd_spec_distill(args):
    import dataclasses
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from .serve import (
        InferenceEngine,
        ServingConfig,
        SpecConfig,
        SpecInferManager,
    )
    from .serve import spec_distill as sd

    if args.model_dir:
        from .serve.llm import LLM

        llm = LLM.from_pretrained(args.model_dir)
        family, cfg, params = llm.family, llm.cfg, llm.params
    else:
        from .models import llama as family

        cfg = family.LLaMAConfig(
            vocab_size=512, hidden_size=128, intermediate_size=344,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=4, max_position_embeddings=512,
            dtype=jnp.float32,
        )
        params = family.init_params(jax.random.PRNGKey(0), cfg)

    def make_sc():
        return ServingConfig(
            max_requests_per_batch=4,
            max_sequence_length=args.max_sequence_length,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
        )

    k = max(1, cfg.num_hidden_layers // 4)
    dcfg = dataclasses.replace(cfg, num_hidden_layers=k)
    dparams = dict(params)
    dparams["layers"] = {n: v[:k] for n, v in params["layers"].items()}

    def make_mgr(draft_cfg=None, draft_params=None, spec=None):
        eng = InferenceEngine(family, cfg, params, make_sc())
        ssms = []
        if draft_cfg is not None:
            ssms = [InferenceEngine(family, draft_cfg, draft_params,
                                    make_sc())]
        return SpecInferManager(
            eng, ssms, spec or SpecConfig(2, 4, adaptive=True)
        )

    rng = np.random.RandomState(args.seed)
    prompts = [
        rng.randint(1, cfg.vocab_size, size=rng.randint(4, 12)).tolist()
        for _ in range(args.num_prompts)
    ]

    # 1. harvest teacher logits: offline trace replay, or live from the
    #    layer-skip manager's verify rounds
    if args.trace_file:
        with open(args.trace_file) as f:
            traces = json.load(f)
        buf = sd.harvest_offline(family, cfg, params, traces)
        print(f"harvested {len(buf)} examples from "
              f"{len(traces)} offline trace(s)")
    else:
        buf = sd.harvest_online(
            make_mgr(dcfg, dparams), prompts,
            max_new_tokens=args.max_new_tokens,
        )
        print(f"harvested {len(buf)} examples from live verify rounds")

    # 2. distill the student
    distill = sd.DistillConfig(
        hidden_size=args.hidden, num_layers=args.layers,
        num_heads=args.heads, seq_len=args.seq_len,
        batch_size=args.batch_size, steps=args.steps, lr=args.lr,
        temperature=args.temperature, seed=args.seed,
    )
    scfg, sparams, hist = sd.train_distilled_draft(
        buf, cfg, distill, family=family
    )
    print(f"distilled {distill.num_layers}L/{distill.hidden_size}h draft: "
          f"loss {hist[0]:.4f} -> {hist[-1]:.4f} over {len(hist)} steps")

    # 3. rank the draft ladder by measured accept-rate-per-draft-GFLOP
    evals = [
        sd.measure_draft_utility(
            make_mgr(scfg, sparams), prompts,
            max_new_tokens=args.max_new_tokens, name="distilled",
        ),
        sd.measure_draft_utility(
            make_mgr(dcfg, dparams), prompts,
            max_new_tokens=args.max_new_tokens, name="layer_skip",
        ),
        sd.measure_draft_utility(
            make_mgr(spec=SpecConfig(2, 4, adaptive=True,
                                     draft="early_exit", draft_layers=k)),
            prompts, max_new_tokens=args.max_new_tokens, name="early_exit",
        ),
    ]
    print(f"{'draft':<12} {'accept':>8} {'GF/tok':>10} {'accept/GF':>12}")
    for e in sd.rank_drafts(evals):
        print(f"{e.name:<12} {e.accept_rate:>8.3f} "
              f"{e.draft_gflops_per_token:>10.6f} "
              f"{e.accept_rate_per_gflop:>12.2f}")
    best = sd.rank_drafts(evals)[0]
    print(f"best draft: {best.name} "
          f"(feed measured_accept_rate={best.accept_rate:.3f} to the "
          f"serving cost model)")

    if args.out:
        sd.save_distilled_draft(args.out, scfg, sparams)
        print(f"distilled draft checkpoint -> {args.out} "
              f"(load as an SSM spec)")


def main(argv=None):
    p = argparse.ArgumentParser(prog="flexflow_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("train", help="MLP training smoke run")
    t.add_argument("--devices", type=int, default=1)
    t.add_argument("--epochs", type=int, default=2)
    t.add_argument("--profiling", action="store_true")
    t.set_defaults(fn=cmd_train)

    s = sub.add_parser("serve", help="incremental / speculative serving")
    s.add_argument("--model-dir", default=None)
    s.add_argument("--ssm-dir", default=None)
    s.add_argument("--spec", action="store_true",
                   help="SpecInfer with a layer-skip self-draft")
    s.add_argument("--prompt", action="append", default=None)
    s.add_argument("--max-new-tokens", type=int, default=32)
    s.add_argument("--max-requests-per-batch", type=int, default=4)
    s.add_argument("--max-sequence-length", type=int, default=512)
    s.add_argument("--num-beams", type=int, default=1)
    s.add_argument("--quantization", choices=["int8", "int4"], default=None)
    s.add_argument("--offload", action="store_true")
    s.add_argument("--pallas", action="store_true",
                   help="the paged step's Pallas kernels (requires "
                        "--kv-layout paged)")
    s.add_argument("--kv-layout", choices=["dense", "paged"], default="dense",
                   help="paged = block-paged KV cache (HBM scales with "
                        "live tokens; enables high request concurrency)")
    s.add_argument("--page-size", type=int, default=128)
    s.add_argument("--max-cached-tokens", type=int, default=None,
                   help="paged KV pool budget in tokens (default: worst "
                        "case slots*max_len; smaller oversubscribes with "
                        "recompute preemption)")
    s.add_argument("--kv-quant", choices=["int8", "int4"], default=None,
                   help="quantized paged KV pages (requires "
                        "--kv-layout paged): int8 codes, or int4 "
                        "packed nibbles (two codes per byte along the "
                        "head dim, unpacked in-kernel), plus per-page "
                        "amax scales dequantized inside attention; the "
                        "--max-cached-tokens HBM budget then buys ~2x "
                        "(int8) / ~4x (int4) the pages — ≥1.9x / ≥3.8x "
                        "after scale rows. int4 generation stays "
                        "bitwise run-to-run; its logit tolerance is "
                        "wider than int8's (see README)")
    s.add_argument("--kv-shard", choices=["none", "context"],
                   default="none",
                   help="context-parallel long-context serving "
                        "(requires --kv-layout paged): shard ONE "
                        "request's KV pages across sequence shards — "
                        "logical page j stripes to shard j%%n, "
                        "--max-cached-tokens becomes a PER-SHARD HBM "
                        "budget, and prompts beyond one shard's pool "
                        "serve at the aggregate capacity via ring "
                        "ragged paged attention "
                        "(--sequence-parallelism-degree > 1 runs the "
                        "ppermute ring; a seq-degree-1 mesh uses the "
                        "bitwise table-gather layout)")
    s.add_argument("--context-shards", type=int, default=0,
                   help="context-parallel shard degree (0 = derive "
                        "from the mesh --sequence-parallelism-degree; "
                        "must match it when both are set)")
    s.add_argument("--prefix-caching", action="store_true",
                   help="automatic prefix caching (paged layout only): "
                        "reuse cached KV pages for shared prompt "
                        "prefixes, prefilling only the uncached suffix")
    s.add_argument("--host-cache-bytes", type=int, default=None,
                   help="hierarchical KV cache: spill cold prefix-"
                        "cache pages to host RAM (async DMA) instead "
                        "of evicting, up to this many bytes, and "
                        "re-admit them on a later prompt match — a "
                        "host hit instead of a prefill recompute "
                        "(requires --prefix-caching; re-admitted pages "
                        "generate bitwise the warm path)")
    s.add_argument("--cache-policy", choices=["complete", "prefill"],
                   default="complete",
                   help="when prompt blocks enter the prefix cache: at "
                        "request completion incl. generated tokens "
                        "(complete) or as soon as prefill ends (prefill)")
    s.add_argument("--fused-decode", default=None,
                   help="decode-step fusions, comma-separated "
                        "(rope_kv_write): fold RoPE + the KV page "
                        "write into the ragged paged Pallas kernel "
                        "(requires --kv-layout paged; active with "
                        "--pallas); bitwise-identical to the unfused "
                        "step")
    s.add_argument("--replicas", type=int, default=1,
                   help="cluster serving (serve/cluster/): drive this "
                        "many engine replicas — each its own mesh and "
                        "KV pool — behind the front-end router")
    s.add_argument("--router-policy",
                   choices=["prefix", "round_robin", "least_loaded"],
                   default="prefix",
                   help="replica placement: longest prefix-cache match "
                        "(prefix, the default — falls back to least-"
                        "loaded on a miss), round_robin, or the "
                        "smallest queue-delay estimate (least_loaded)")
    s.add_argument("--prefill-replicas", type=int, default=0,
                   help="disaggregated serving: the first N replicas "
                        "only prefill — finished prefills migrate "
                        "their KV pages to a decode-pool replica "
                        "(byte-exact; requires --kv-layout paged; "
                        "must pair with --decode-replicas and sum to "
                        "--replicas)")
    s.add_argument("--decode-replicas", type=int, default=0,
                   help="disaggregated serving: the last N replicas "
                        "only decode (see --prefill-replicas)")
    s.add_argument("--slo-queue-delay-s", type=float, default=None,
                   help="SLO admission: shed a request (terminal "
                        "GenerationResult.error, never a hang) when "
                        "every replica's queue-delay estimate exceeds "
                        "this many seconds")
    s.add_argument("--autoscale", choices=["drive", "advise"],
                   default=None,
                   help="self-driving serving (serve/autotune): a cost-"
                        "model policy loop in the cluster drive loop — "
                        "'drive' applies journaled scale_out/scale_in/"
                        "retune decisions, 'advise' journals + counts "
                        "every decision without applying (dry run); "
                        "requires --slo-ttft-s and/or --slo-tpot-s and "
                        "an --autoscale-max-replicas ceiling")
    s.add_argument("--slo-ttft-s", type=float, default=None,
                   help="autoscale objective: predicted time-to-first-"
                        "token p99 SLO in seconds (admission wait on "
                        "the routed pool + the prefill pass)")
    s.add_argument("--slo-tpot-s", type=float, default=None,
                   help="autoscale objective: predicted time-per-output-"
                        "token p99 SLO in seconds (the decode-step "
                        "interval)")
    s.add_argument("--autoscale-cooldown-steps", type=int, default=64,
                   help="minimum CLUSTER STEPS between applied "
                        "autoscale actions (hysteresis floor; never "
                        "wall clock, so replays reproduce decisions)")
    s.add_argument("--autoscale-min-replicas", type=int, default=1,
                   help="floor of the replica band the autoscaler may "
                        "move within")
    s.add_argument("--autoscale-max-replicas", type=int, default=0,
                   help="ceiling of the replica band (required >= the "
                        "floor when --autoscale is set — an unbounded "
                        "scale_out is a cost bug)")
    s.add_argument("--migration-queue-budget", type=int, default=None,
                   help="disaggregated back-pressure: at most this many "
                        "finished prefills wait for decode-pool "
                        "capacity holding their slot + pages; overflow "
                        "entries release their pages and drain through "
                        "recompute re-admission on the decode pool's "
                        "own queue (default: unbounded holds)")
    s.add_argument("--fault-plan", default=None,
                   help="deterministic fault injection "
                        "(serve/cluster/faults.py; requires a cluster): "
                        "a JSON list of faults, e.g. "
                        "'[{\"kind\": \"crash\", \"replica\": 1, "
                        "\"step\": 20}]' — replica kinds: crash, "
                        "transient, latency, migration, oom; transport "
                        "kinds (remote replicas only — rejected loudly "
                        "against --replica-transport inproc): drop, "
                        "delay, disconnect, partition. The same plan "
                        "replays the same failure scenario bit-for-bit; "
                        "failed replicas' requests fail over to "
                        "survivors via recompute re-admission")
    s.add_argument("--replica-transport", default="inproc",
                   choices=("inproc", "loopback", "socket"),
                   help="how the cluster drives its replicas: direct "
                        "method calls (inproc, default), the binary "
                        "RPC wire codec in-process (loopback — bitwise "
                        "the inproc cluster, exercises deadlines/"
                        "retries/heartbeats for real), or localhost TCP "
                        "to subprocess replica servers (socket; see "
                        "python -m flexflow_tpu.serve.cluster.server)")
    s.add_argument("--replica-endpoints", default=None,
                   help="comma-separated host:port per remote replica "
                        "(then per standby) for --replica-transport "
                        "socket")
    s.add_argument("--journal-dir", default=None, metavar="DIR",
                   help="elastic control plane: write the durable "
                        "request journal (submissions, flushed-token "
                        "deltas, terminal records, membership "
                        "snapshots) into DIR — a SIGKILL'd serve "
                        "process restarts with ClusterManager.recover "
                        "and finishes every journaled request bitwise "
                        "(forces the cluster manager even at "
                        "--replicas 1)")
    s.add_argument("--standby-replicas", type=int, default=0,
                   help="warm standbys: pre-built engines outside "
                        "routing that ADOPT a circuit-broken replica's "
                        "position — its prefix radix tree (block keys + "
                        "page bytes) ships over the transport and "
                        "re-admits on the standby before it joins "
                        "routing, instead of survivors re-seeding the "
                        "families cold")
    # reference -output-file (request_manager.cc:417-440): append each
    # finished request's latency/steps/token-ids
    s.add_argument("--output-file", "-output-file", default=None)
    s.add_argument("--trace-out", default=None,
                   help="write a Chrome/Perfetto trace_event JSON of the "
                        "run (one lane per replica; load in "
                        "ui.perfetto.dev)")
    s.add_argument("--metrics-out", default=None,
                   help="write a Prometheus text-format metrics snapshot "
                        "(SchedulerStats/ClusterStats/ProfileInfo, "
                        "drift-guarded)")
    s.add_argument("--flight-recorder", default=None, metavar="DIR",
                   help="arm the failure flight recorder: bounded "
                        "per-replica event rings dumping redacted JSON "
                        "post-mortems into DIR on DOWN trips, failover "
                        "errors and terminal request errors")
    _degree_args(s)
    s.set_defaults(fn=cmd_serve)

    q = sub.add_parser("search", help="Unity auto-parallel compile")
    q.add_argument("--devices", type=int, default=4)
    q.add_argument("--layers", type=int, default=3)
    q.add_argument("--hidden", type=int, default=256)
    q.add_argument("--budget", type=int, default=32)
    q.add_argument("--measured", action="store_true")
    q.add_argument("--export-strategy", default=None)
    q.add_argument("--export-dot", default=None)
    q.set_defaults(fn=cmd_search)

    ss = sub.add_parser(
        "serve-search",
        help="offline ServingConfig search over the serving cost model",
        description="serve/autotune offline search: enumerate + refine "
                    "serving candidates (TPxPP, replicas, page size, KV "
                    "quant, disagg, speculation) through the analytical "
                    "cost model for a model geometry and traffic "
                    "profile, under a chip budget and optional TTFT/"
                    "TPOT p99 SLO constraints; prints the leaderboard "
                    "and the validated `serve` flags for the winner.")
    ss.add_argument("--model-dir", default=None,
                    help="derive geometry from DIR/config.json instead "
                         "of the --hidden-size/... flags")
    ss.add_argument("--hidden-size", type=int, default=128)
    ss.add_argument("--num-layers", type=int, default=4)
    ss.add_argument("--num-heads", type=int, default=8)
    ss.add_argument("--num-kv-heads", type=int, default=0,
                    help="0 = same as --num-heads (no GQA)")
    ss.add_argument("--intermediate-size", type=int, default=344)
    ss.add_argument("--vocab-size", type=int, default=512)
    ss.add_argument("--param-bytes", type=float, default=2.0,
                    help="bytes per weight (2=bf16, 1=int8, 0.5=int4)")
    ss.add_argument("--arrival-rate-rps", type=float, default=1.0)
    ss.add_argument("--prompt-p50", type=float, default=128.0)
    ss.add_argument("--prompt-p99", type=float, default=0.0,
                    help="0 = 4x the p50")
    ss.add_argument("--output-p50", type=float, default=128.0)
    ss.add_argument("--output-p99", type=float, default=0.0,
                    help="0 = 4x the p50")
    ss.add_argument("--prefix-share", type=float, default=0.0,
                    help="fraction of prompt tokens expected to hit the "
                         "prefix cache")
    ss.add_argument("--spec-accept-rate", type=float, default=0.0,
                    help="expected speculative acceptance rate (0 "
                         "disables speculation candidates)")
    ss.add_argument("--chip-budget", type=int, default=8,
                    help="max chips = tp * pp * replicas")
    ss.add_argument("--slo-ttft-s", type=float, default=None,
                    help="TTFT p99 SLO constraint in seconds (breaching "
                         "candidates are infeasible, not down-weighted)")
    ss.add_argument("--slo-tpot-s", type=float, default=None,
                    help="TPOT p99 SLO constraint in seconds")
    ss.add_argument("--max-requests-per-batch", type=int, default=16)
    ss.add_argument("--max-sequence-length", type=int, default=2048)
    ss.add_argument("--no-disagg", action="store_true",
                    help="exclude disaggregated prefill/decode pools")
    ss.add_argument("--top-k", type=int, default=8,
                    help="leaderboard rows to print")
    ss.set_defaults(fn=cmd_serve_search)

    sdp = sub.add_parser(
        "spec-distill",
        help="distill a draft from target logits; rank distilled vs "
             "layer-skip vs early-exit by accept-rate-per-draft-GFLOP",
    )
    sdp.add_argument("--model-dir", default=None,
                     help="teacher HF checkpoint dir (default: tiny "
                          "random model)")
    sdp.add_argument("--trace-file", default=None,
                     help="JSON list of token-id lists to replay offline "
                          "(default: harvest live verify rounds)")
    sdp.add_argument("--out", default=None,
                     help="save the distilled draft checkpoint here")
    sdp.add_argument("--hidden", type=int, default=64)
    sdp.add_argument("--layers", type=int, default=2)
    sdp.add_argument("--heads", type=int, default=4)
    sdp.add_argument("--steps", type=int, default=200)
    sdp.add_argument("--lr", type=float, default=1e-3)
    sdp.add_argument(
        "--temperature", type=float, default=0.25,
        help="distillation temperature: softmax(teacher_logits / T) "
        "targets; < 1 sharpens toward the teacher argmax (what a "
        "greedy verify ladder accepts on)",
    )
    sdp.add_argument("--seq-len", type=int, default=64)
    sdp.add_argument("--batch-size", type=int, default=8)
    sdp.add_argument("--num-prompts", type=int, default=16)
    sdp.add_argument("--max-new-tokens", type=int, default=24)
    sdp.add_argument("--max-sequence-length", type=int, default=256)
    sdp.add_argument("--seed", type=int, default=0)
    sdp.set_defaults(fn=cmd_spec_distill)

    args = p.parse_args(argv)
    if args.fn is cmd_serve and args.pallas and args.kv_layout == "dense":
        s.error("--pallas requires --kv-layout paged")
    # this process owns its compiles
    from .config import enable_compile_cache

    enable_compile_cache()
    args.fn(args)


if __name__ == "__main__":
    main()
