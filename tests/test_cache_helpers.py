"""The decoder's cache helpers against a plain expectation (ISSUE 49):
``commit_kv_paged`` moves exactly the lines ``src -> dst`` and no other
(codes and scale rows together on a quantized pool),
``reorder_slots_paged`` and ``copy_page_kv`` copy page content,
``gather_page_kv`` then ``scatter_page_kv`` is the identity, and
``commit_kv`` / ``reorder_slots`` do the same on the dense cache. Array
operations on random pools: no model is run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import llama, mistral, transformer
from flexflow_tpu.serve import kv_quant

PS, PAGES = 8, 6                     # page size, pool pages; PAGES = scratch
TABLE = np.array([[0, 1, PAGES, PAGES], [2, 3, PAGES, PAGES]], np.int32)
# lines of each row (row, line) -> (page, offset) by TABLE
SRC = np.array([[5, 7], [9, 10]], np.int32)
DST = np.array([[3, 4], [6, 7]], np.int32)

PLAIN = llama.LLaMAConfig.tiny(dtype=jnp.float32)
WINDOW = mistral.tiny(dtype=jnp.float32)   # its caches carry ``pos``


def _at(row, line):
    return TABLE[row, line // PS], line % PS


def _random(cache, seed=0):
    """The cache with every buffer random (int32 positions, float K/V)."""
    rng = np.random.default_rng(seed)
    return {
        name: jnp.asarray(
            rng.integers(0, 50, buf.shape) if buf.dtype == jnp.int32
            else rng.normal(size=buf.shape), buf.dtype)
        for name, buf in cache.items()
    }


def _paged(cfg, seed=0):
    return _random(transformer.init_paged_kv_cache(cfg, PAGES, PS), seed)


def _np(cache):
    return {name: np.array(buf) for name, buf in cache.items()}


def _same(got, want):
    assert set(got) == set(want)
    for name in want:
        np.testing.assert_array_equal(np.asarray(got[name]), want[name],
                                      err_msg=name)


def test_commit_kv_paged_moves_the_lines_and_no_other():
    cache = _paged(WINDOW)
    assert set(cache) == {"k", "v", "pos"}
    want = _np(cache)
    old = _np(cache)
    for r in range(2):
        for s, d in zip(SRC[r], DST[r]):
            for name in want:
                lead = () if name == "pos" else (slice(None),)
                want[name][lead + _at(r, d)] = old[name][lead + _at(r, s)]
    got = transformer.commit_kv_paged(
        cache, jnp.asarray(TABLE), jnp.asarray(SRC), jnp.asarray(DST))
    _same(got, want)
    assert np.any(np.asarray(got["k"]) != old["k"])


def test_commit_kv_paged_reads_every_line_before_it_writes():
    """Overlapping ranges: line 4 is a source and a destination."""
    cache = _paged(PLAIN)
    old, want = _np(cache), _np(cache)
    src, dst = np.array([[4, 5]], np.int32), np.array([[3, 4]], np.int32)
    for name in want:
        want[name][(slice(None),) + _at(0, 3)] = old[name][:, 0, 4]
        want[name][(slice(None),) + _at(0, 4)] = old[name][:, 0, 5]
    _same(transformer.commit_kv_paged(
        cache, jnp.asarray(TABLE[:1]), jnp.asarray(src), jnp.asarray(dst)),
        want)


def _dequant(cache, name, pack):
    codes = cache[name]
    codes = (np.asarray(kv_quant.unpack_nibbles(codes)) if pack == 2
             else np.asarray(codes, np.float32))
    return codes * np.asarray(cache[name + "_scale"])[:, :, None, :, None]


@pytest.mark.parametrize("quant", ["int8", "int4"])
def test_commit_kv_paged_quantized_moves_values_and_scales(quant):
    """On a quantized pool the codes cannot move verbatim: the moved
    lines read back as the values they held (to half a step of the
    destination page's scale), the destination pages' scales are the
    larger of what they were and what the lines need, their other lines
    keep their values (to half a step of the regrown scale), and no
    other page's codes or scales change at all."""
    spec = kv_quant.resolve_spec(quant)
    cache = transformer.init_paged_kv_cache(PLAIN, PAGES, PS, kv_quant=quant)
    rng = np.random.default_rng(1)
    L, KV, dk = 2, PLAIN.num_key_value_heads, PLAIN.head_dim
    # every line of pages 0..3 written through the quantizing write, page
    # by page with growing magnitude so that the pages' scales differ
    phys = jnp.asarray(np.repeat(np.arange(4), PS).reshape(4, PS), jnp.int32)
    off = jnp.asarray(np.tile(np.arange(PS), (4, 1)), jnp.int32)
    for name in ("k", "v"):
        vals = rng.normal(size=(L, 4, PS, KV, dk)) * (
            1 + np.arange(4))[None, :, None, None, None]
        cache[name], cache[name + "_scale"] = jax.vmap(
            lambda b, s, v: kv_quant.quant_line_write(
                b, s, phys, off, v, spec.qmax)
        )(cache[name], cache[name + "_scale"], jnp.asarray(vals, jnp.float32))
    old = _np(cache)
    got = _np(transformer.commit_kv_paged(
        cache, jnp.asarray(TABLE), jnp.asarray(SRC), jnp.asarray(DST),
        kv_quant=quant))
    assert set(got) == set(old)
    dst_pages = sorted({int(_at(r, d)[0]) for r in range(2) for d in DST[r]})
    others = [p for p in range(PAGES + 1) if p not in dst_pages]
    for name in ("k", "v"):
        sname = name + "_scale"
        np.testing.assert_array_equal(got[name][:, others], old[name][:, others])
        np.testing.assert_array_equal(got[sname][:, others], old[sname][:, others])
        before = _dequant(old, name, spec.pack)
        after = _dequant(got, name, spec.pack)
        need = old[sname].copy()
        moved = set()
        for r in range(2):
            for s, d in zip(SRC[r], DST[r]):
                (sp, so), (dp, do) = _at(r, s), _at(r, d)
                moved.add((int(dp), int(do)))
                line = before[:, sp, so]                       # (L, KV, dk)
                need[:, dp] = np.maximum(
                    need[:, dp], np.abs(line).max(-1) / spec.qmax)
                half = got[sname][:, dp][..., None] / 2
                assert np.all(np.abs(after[:, dp, do] - line) <= half * 1.001)
        np.testing.assert_allclose(got[sname][:, dst_pages],
                                   need[:, dst_pages], rtol=1e-6)
        for p in dst_pages:
            for o in range(PS):
                if (p, o) not in moved:
                    half = got[sname][:, p][..., None] / 2
                    assert np.all(np.abs(after[:, p, o] - before[:, p, o])
                                  <= half * 1.001)


def test_reorder_slots_paged_copies_page_content():
    """Slot 0 takes slot 1's lines: the content of slot 1's pages lands
    in slot 0's OWN pages; the table, slot 1 and every other page stay."""
    cache = _paged(WINDOW)
    old, want = _np(cache), _np(cache)
    for name in want:
        lead = () if name == "pos" else (slice(None),)
        want[name][lead + (0,)] = old[name][lead + (2,)]
        want[name][lead + (1,)] = old[name][lead + (3,)]
    _same(transformer.reorder_slots_paged(
        cache, jnp.asarray(TABLE), jnp.asarray([1, 1], jnp.int32)), want)


def test_copy_page_kv_copies_one_page():
    cache = _paged(WINDOW)
    old, want = _np(cache), _np(cache)
    for name in want:
        lead = () if name == "pos" else (slice(None),)
        want[name][lead + (4,)] = old[name][lead + (2,)]
    _same(transformer.copy_page_kv(cache, jnp.int32(2), jnp.int32(4)), want)


def test_gather_then_scatter_page_is_the_identity():
    quant = transformer.init_paged_kv_cache(WINDOW, PAGES, PS, kv_quant="int8")
    cache = _random({k: v for k, v in quant.items() if v.dtype != jnp.int8})
    cache.update({k: jnp.asarray(np.random.default_rng(2).integers(
        -127, 128, v.shape), jnp.int8)
        for k, v in quant.items() if v.dtype == jnp.int8})
    assert set(cache) == {"k", "v", "k_scale", "v_scale", "pos"}
    page = transformer.gather_page_kv(cache, jnp.int32(3))
    assert page["k"].shape == cache["k"].shape[:1] + cache["k"].shape[2:]
    assert page["k_scale"].shape == (2, WINDOW.num_key_value_heads)
    assert page["pos"].shape == (PS,)
    _same(transformer.scatter_page_kv(cache, jnp.int32(3), page), _np(cache))
    # and into another row it is a page copy, scales and positions too
    _same(transformer.scatter_page_kv(cache, jnp.int32(5), page),
          _np(transformer.copy_page_kv(cache, jnp.int32(3), jnp.int32(5))))


def _dense(cfg):
    return _random(transformer.init_kv_cache(cfg, 2, 15))


def test_commit_kv_dense_moves_the_lines_and_no_other():
    cache = _dense(WINDOW)
    assert cache["k"].shape[:3] == (2, 2, 16) and cache["pos"].shape == (2, 16)
    old, want = _np(cache), _np(cache)
    for r in range(2):
        for s, d in zip(SRC[r], DST[r]):
            want["pos"][r, d] = old["pos"][r, s]
            for name in ("k", "v"):
                want[name][:, r, d] = old[name][:, r, s]
    _same(transformer.commit_kv(cache, jnp.asarray(SRC), jnp.asarray(DST)),
          want)


def test_reorder_slots_dense_gathers_slots():
    cache = _dense(WINDOW)
    old = _np(cache)
    src = np.array([1, 1], np.int32)
    _same(transformer.reorder_slots(cache, jnp.asarray(src)),
          {"k": old["k"][:, src], "v": old["v"][:, src],
           "pos": old["pos"][src]})
