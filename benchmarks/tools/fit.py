#!/usr/bin/env python3
"""Does a configuration's step program fit one chip? Compiles the
served step (``serve_step_paged`` with the Pallas kernel, the pool
donated as the engine donates it) for a DESCRIBED v5e — no chip needed,
run it here before any chip call — at each depth asked for, and prints
the compiler's ``memory_analysis``. This is how a configuration's depth
cut is sized (``reduced`` in its file, PERF.md section 4).

  JAX_PLATFORMS=cpu python3 benchmarks/tools/fit.py --config mixtral-8x7b --layers 4 5
"""
import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int, nargs="+", required=True)
    ap.add_argument("--chunks", type=int, nargs="+", default=[128, 1])
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import model, spec
    from flexflow_tpu.serve import kernels

    jax.config.update("jax_enable_compilation_cache", False)
    kernels._interpret = lambda: False  # compile Mosaic, not interpret mode
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    config = spec.load_json("configs", args.config + ".json")
    family = model.family_of(config)
    serving = model.serving_config(config)
    R = serving.max_requests_per_batch

    def on(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    for n in args.layers:
        cfg = model.decoder_config(config, num_hidden_layers=n)
        params = on(jax.eval_shape(
            functools.partial(family.init_params, cfg=cfg), jax.random.PRNGKey(0)))
        cache = on(jax.eval_shape(functools.partial(
            family.init_paged_kv_cache, cfg, serving.num_pages,
            serving.page_size, jnp.bfloat16)))
        weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
        pool = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
        for C in args.chunks:
            def step(params, cache, tokens, positions, logits_idx, table):
                return family.serve_step_paged(
                    params, cache, tokens, positions, logits_idx, None, None,
                    table, cfg=cfg, cache_len=serving.cache_len,
                    kernels="pallas")

            try:
                mem = jax.jit(step, donate_argnums=(1,)).lower(
                    params, cache, sds((R, C), jnp.int32),
                    sds((R, C), jnp.int32), sds((R,), jnp.int32),
                    sds((R, serving.pages_per_slot), jnp.int32),
                ).compile().memory_analysis()
                need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                        + mem.output_size_in_bytes - mem.alias_size_in_bytes)
                print(f"{args.config} N={n} C={C}: weights {weights / 1e9:.2f} GB, "
                      f"pool {pool / 1e9:.2f} GB, arguments "
                      f"{mem.argument_size_in_bytes / 1e9:.2f}, temporaries "
                      f"{mem.temp_size_in_bytes / 1e9:.2f}, outputs "
                      f"{mem.output_size_in_bytes / 1e9:.2f}, aliased "
                      f"{mem.alias_size_in_bytes / 1e9:.2f}: {need / 1e9:.2f} GB",
                      flush=True)
            except Exception as e:  # the compiler's refusal IS the reading
                print(f"{args.config} N={n} C={C}: refused: "
                      f"{str(e).splitlines()[0][:300]}", flush=True)


if __name__ == "__main__":
    main()
