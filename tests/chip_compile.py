"""What the chip-compile files share (tests/test_chip_compile_*.py): the
shapes of the serving main path at Mistral-7B's published widths and the
readings of a compiled program. Not collected itself. The described
topology is conftest.py's ``topo`` fixture and nobody else's."""
import functools
import re

import jax
import jax.numpy as jnp
from flexflow_tpu.models import mistral
from flexflow_tpu.serve import kernels

__all__ = [
    "R", "PAGE", "PAGES_PER_SLOT", "NUM_PAGES", "CACHE_LEN",
    "_on", "_compile", "_attention_args", "_step_args", "_step",
    "_assert_pool_in_place", "_assert_pool_carried", "_need", "_pair_rows",
    "_assert_kernel_calls"]

R, PAGE, PAGES_PER_SLOT = 16, 128, 16          # slots, tokens/page, NP
NUM_PAGES = R * PAGES_PER_SLOT                 # worst-case pool
CACHE_LEN = PAGE * PAGES_PER_SLOT              # 2048


def _on(tree, sds):
    return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)


def _compile(fn, *args, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    return compiled, compiled.as_text()


def _attention_args(sds, C, cfg, pool_dtype=jnp.bfloat16, dk_pool=None):
    H, KV, dk = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    pool = sds((NUM_PAGES + 1, PAGE, KV, dk_pool or dk), pool_dtype)
    return (
        sds((R, C, H, dk), jnp.bfloat16), pool, pool,
        sds((R, PAGES_PER_SLOT), jnp.int32),
        sds((R, C, CACHE_LEN), jnp.bool_),
    )


def _step_args(sds, cfg, C, kv_quant=None, family=mistral):
    params = _on(
        jax.eval_shape(
            functools.partial(family.init_params, cfg=cfg),
            jax.random.PRNGKey(0),
        ),
        sds,
    )
    cache = _on(
        jax.eval_shape(
            functools.partial(
                family.init_paged_kv_cache, cfg, NUM_PAGES, PAGE,
                jnp.bfloat16, kv_quant=kv_quant,
            )
        ),
        sds,
    )
    return (
        params, cache,
        sds((R, C), jnp.int32), sds((R, C), jnp.int32), sds((R,), jnp.int32),
        sds((R, PAGES_PER_SLOT), jnp.int32),
    )


def _step(cfg, family=mistral, **kw):
    def step(params, cache, tokens, positions, logits_idx, page_table):
        return family.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=CACHE_LEN, **kw,
        )

    return step


def _assert_pool_in_place(compiled, text, pool):
    """The donated pool is the layer loop's carry, updated in place: the
    program copies no whole pool and its temporaries are less than one.
    (As scanned inputs and outputs the pools were two buffers: two
    ``copy`` of a whole pool a step, a slice and a write-back a layer,
    a second pool among the temporaries; PERF.md, PR 28.)"""
    dims = ",".join(map(str, pool.shape))
    assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool.size * pool.dtype.itemsize


def _assert_pool_carried(text, pool):
    """The same, read off the program itself and not off its
    temporaries (a sparse model's expert einsums hold more than a pool):
    both stacked pools are parameters the program aliases to its
    outputs, the layer loop's ``while`` carries them whole, nothing
    yields ONE layer of a pool (a per-layer slice or write-back), and a
    whole pool comes only from the line write's scatter, in place."""
    whole = rf"\w+\[{','.join(map(str, pool.shape))}\]"
    layer = rf"\w+\[(1,)?{','.join(map(str, pool.shape[1:]))}\]"
    params = {int(n) for n in re.findall(
        rf"= {whole}\S* parameter\((\d+)\), sharding", text)}
    alias, = re.findall(r"input_output_alias={(.*?) }, entry", text)
    aliased = {int(n) for n in re.findall(r"\((\d+), {}, \S+?\)", alias)}
    assert len(params) == 2 and params <= aliased
    # (a routed expert layer's grouping has small loops of its own)
    loop, = (carry for carry in re.findall(r"= \((.*?)\) while\(", text)
             if re.search(whole, carry))
    assert len(re.findall(whole, loop)) == 2
    assert not re.findall(rf"= {layer}\S* [\w-]+\(", text)
    makers = set(re.findall(rf"= {whole}\S* ([\w-]+)\(", text))
    assert makers <= {"parameter", "get-tuple-element", "fusion", "scatter",
                      "bitcast"}, makers


def _need(compiled):
    """Bytes the program holds on the device, as benchmarks/tools/fit.py
    counts them."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def _pair_rows(pairs, experts, routed=None):
    """Rows of the grouped expert matmuls at ``pairs`` static (token,
    expert) pairs: every expert's rows aligned to the row tile."""
    tm = kernels.grouped_tile(pairs, experts, routed)
    return -(-(pairs + experts * (tm - 1)) // tm) * tm


def _assert_kernel_calls(text, want, slots, C):
    """The program's Pallas calls are ``want`` ((name, what its result
    starts with) pairs, sorted by name), each result [slots, C, ...]:
    the trace reduction keys the step by its FIRST kernel's result,
    whichever call that is."""
    calls = re.findall(
        r"%(\w+?)(?:\.\d+)* = (.+?) custom-call\(.*tpu_custom_call", text)
    assert sorted(name for name, _ in calls) == [name for name, _ in want], calls
    for name, shape in calls:
        assert dict(want)[name] in shape, calls
        assert re.search(r"\[(\d+),(\d+),", shape).groups() == (str(slots), str(C))
