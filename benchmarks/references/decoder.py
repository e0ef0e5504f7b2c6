"""The plain reference for the decoder families (Mistral, Mixtral): the
published forward pass in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision — no cache, no kernels, no batching tricks.

  x = embed[tokens]
  per layer:  h = rmsnorm(x) ; q,k,v = h Wq, h Wk, h Wv ; rope(q), rope(k)
              x += softmax(mask(q k^T / sqrt(d))) v Wo      (grouped-query,
                   causal, keys within ``sliding_window`` of the query)
              h = rmsnorm(x)
              dense:  x += (silu(h Wg) * (h Wu)) Wd
              sparse: r = h Wr ; the k largest of r, softmax over those k ;
                      x += sum_k gate_k * expert_k(h)         (HF Mixtral)
  logits = rmsnorm(x) W_head

It reads sizes from the configuration FILE (Hugging Face's names) and
weights from the arrays it is handed, which the benchmark drew from the
seed; it imports nothing of the program. Weights are upcast one layer
(sparse: one expert) at a time, so it fits beside the served model.

Sparse models: a token whose k-th and (k+1)-th router logits are
nearly level is sent to the other expert by ANY rounding difference, in
a sound bf16 program as in anything else, and its logits then differ
from this file's by as much as their own size. Which expert is "right"
there is not something float32 can say. So for each judged token the
reference also returns the logits under every other ROUTING of that
token itself — at each layer, the k-th expert kept or given up for the
(k+1)-th — with, for each, the largest router margin it had to overrule
(``flip_margin``; 0 for the routing float32 takes). The comparison
accepts the nearest routing among those that overrule nothing but near
ties. Tokens it attends to keep float32's routing: one of ~700 attended
tokens going the other way is part of the ordinary error.

Departure from the published model, noted: rotary angles use the
half-split layout of Hugging Face's implementation (``rotate_half``),
which is what the published checkpoints are trained with.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rounded(x, bits, axis):
    """``x`` rounded to ``bits``-bit integers, symmetric, one scale per
    slice along ``axis`` — or ``x`` itself where ``bits`` is 0."""
    if not bits:
        return x
    top = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / top
    return jnp.round(x / scale) * scale


def _weight(w, bits):
    """A weight as the reference computes with it: upcast to float32.
    In the lower-precision CONTROL (``bits`` set: the reference put in
    the program's place and computed in int8, the step below bf16 that
    would tempt a later PR) every matmul weight is rounded per output
    column, every matmul input per token (``_act``), and K and V per
    token and head, as an int8 matmul unit and an int8 cache would."""
    return _rounded(w.astype(F32), bits, -2)


def _act(x, bits):
    return _rounded(x, bits, -1)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, positions, theta):
    """x (T, heads, d); rotate_half convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "window", "bits"))
def _attention(x, norm, wq, wk, wv, wo, *, heads, kv_heads, eps, theta, window, bits):
    """x (B, T, D) -> x + attention(rmsnorm(x)), one row at a time."""
    T = x.shape[1]
    d = wq.shape[-1] // heads
    group = heads // kv_heads
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask &= pos[None, :] > pos[:, None] - window
    wq, wk, wv, wo = (_weight(w, bits) for w in (wq, wk, wv, wo))

    def one(row):
        h = _act(_rmsnorm(row, norm, eps), bits)
        q = _rope((h @ wq).reshape(T, heads, d), pos, theta)
        k = _act(_rope((h @ wk).reshape(T, kv_heads, d), pos, theta), bits)
        v = _act((h @ wv).reshape(T, kv_heads, d), bits)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
        s = jnp.where(mask[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        return row + _act(o.reshape(T, heads * d), bits) @ wo

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("bits",))
def _glu(h, w_gate, w_up, w_down, *, bits):
    w_gate, w_up, w_down = (_weight(w, bits) for w in (w_gate, w_up, w_down))
    h = _act(h, bits)
    return _act(jax.nn.silu(h @ w_gate) * (h @ w_up), bits) @ w_down


@functools.partial(
    jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "window"))
def _attention_at(x, xv, at, norm, wq, wk, wv, wo, *, heads, kv_heads, eps, theta, window):
    """The same attention for single tokens whose residual is not the
    row's own (another routing upstream): token ``xv[b, j, r]`` sits at
    position ``at[b, j]`` of row ``b``, attends to the row's keys and
    values BEFORE it (from ``x``) and to its own. -> xv + attention."""
    T, D = x.shape[1:]
    J, R = xv.shape[1:3]
    d = wq.shape[-1] // heads
    group = heads // kv_heads
    pos = jnp.arange(T)
    wq, wk, wv, wo = (w.astype(F32) for w in (wq, wk, wv, wo))

    def one(args):
        row, rv, p = args
        h = _rmsnorm(row, norm, eps)
        k = jnp.repeat(_rope((h @ wk).reshape(T, kv_heads, d), pos, theta), group, axis=1)
        v = jnp.repeat((h @ wv).reshape(T, kv_heads, d), group, axis=1)
        p = jnp.repeat(p, R)
        hv = _rmsnorm(rv.reshape(J * R, D), norm, eps)
        q = _rope((hv @ wq).reshape(J * R, heads, d), p, theta)
        k_own = jnp.repeat(_rope((hv @ wk).reshape(J * R, kv_heads, d), p, theta), group, axis=1)
        v_own = jnp.repeat((hv @ wv).reshape(J * R, kv_heads, d), group, axis=1)
        mask = pos[None, :] < p[:, None]
        if window:
            mask &= pos[None, :] > p[:, None] - window
        s = jnp.where(mask[:, None, :], jnp.einsum("nhd,khd->nhk", q, k), -jnp.inf)
        s_own = jnp.einsum("nhd,nhd->nh", q, k_own)
        w = jax.nn.softmax(jnp.concatenate([s, s_own[..., None]], -1) / np.sqrt(d), axis=-1)
        o = jnp.einsum("nhk,khd->nhd", w[..., :T], v) + w[..., T:] * v_own
        return rv + (o.reshape(J * R, heads * d) @ wo).reshape(J, R, D)

    return jax.lax.map(one, (x, xv, at))


@functools.partial(jax.jit, static_argnames=("k",))
def _route(h, w_router, flip, *, k):
    """(gate (..., E) with zeros off the chosen k, margin (...)): the
    margin is the distance between the k-th and the (k+1)-th router
    logit as a share of the token's router-logit spread — how far this
    token is from choosing another expert. Where ``flip`` (...) is set
    the k-th expert gives way to the (k+1)-th."""
    r = h @ w_router.astype(F32)
    top, idx = jax.lax.top_k(r, k + 1)
    last = jnp.where(jnp.broadcast_to(flip, r.shape[:-1]), k, k - 1)[..., None]
    top_k = jnp.concatenate([top[..., :k - 1], jnp.take_along_axis(top, last, -1)], -1)
    idx_k = jnp.concatenate([idx[..., :k - 1], jnp.take_along_axis(idx, last, -1)], -1)
    gate = jax.nn.softmax(top_k, axis=-1)
    dense = jnp.sum(jax.nn.one_hot(idx_k, r.shape[-1], dtype=F32) * gate[..., None], axis=-2)
    margin = (top[..., k - 1] - top[..., k]) / jnp.std(r, axis=-1)
    return dense, margin


MAX_ROUTED_LAYERS = 5  # 2**layers routings a judged token


def _routings(layers):
    """(R, layers) bool: every subset of the layers at which a token's
    k-th expert gives way; row 0 is float32's own routing."""
    return np.array([[(r >> l) & 1 for l in range(layers)]
                     for r in range(2 ** layers)], bool)


def judged_logits(params, config, tokens, judge, *, control_bits=0, routings=True):
    """Float32 logits of ``tokens`` (B, T) at the positions ``judge``
    (B, J): (logits (B, J, R, V), flip_margin (B, J, R), margin (B, J)).
    R is 1 for a dense model, for the control and without ``routings``;
    for a sparse model it counts the routings of the judged token itself
    (module docstring), float32's own first. ``margin`` is the smallest router margin of the
    judged token over the layers (inf for a dense model). Positions past
    a row's own length are padding: causal attention keeps them out of
    every judged position before them. ``control_bits``: the control
    (see ``_weight``); the router, the norms, the embedding and the head
    stay as they are."""
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    eps = float(config["rms_norm_eps"])
    experts = config.get("num_local_experts", 0)
    depth = config["num_hidden_layers"]
    attn = dict(heads=heads, kv_heads=kv_heads, eps=eps,
                theta=float(config["rope_theta"]),
                window=int(config.get("sliding_window") or 0))
    tokens = jnp.asarray(tokens, jnp.int32)
    judge = jnp.asarray(judge, jnp.int32)
    rows = jnp.arange(tokens.shape[0])[:, None]
    margin = jnp.full(judge.shape, jnp.inf, F32)
    routed = bool(experts) and routings and not control_bits
    if routed and depth > MAX_ROUTED_LAYERS:
        raise ValueError(
            f"{2 ** depth} routings a token at {depth} sparse layers: the "
            "reference needs a rule for which to take (PERF.md section 7)")
    flips = jnp.asarray(_routings(depth if routed else 0))       # (R, depth)
    flip_margin = jnp.zeros(judge.shape + flips.shape[:1], F32)  # (B, J, R)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
        xv = jnp.broadcast_to(x[rows, judge][:, :, None], flip_margin.shape + x.shape[-1:])
        layers = params["layers"]
        for l in range(depth):
            w = [layers[n][l] for n in ("attn_norm_scale", "wq", "wk", "wv", "wo")]
            if routed:
                xv = _attention_at(x, xv, judge, *w, **attn)
            x = _attention(x, *w, **attn, bits=control_bits)
            h = _rmsnorm(x, layers["mlp_norm_scale"][l], eps)
            if experts:
                k = config["num_experts_per_tok"]
                gate, m = _route(h, layers["w_router"][l], False, k=k)
                margin = jnp.minimum(margin, m[rows, judge])
                if routed:
                    hv = _rmsnorm(xv, layers["mlp_norm_scale"][l], eps)
                    flip = jnp.broadcast_to(flips[:, l], flip_margin.shape)
                    gate_v, m = _route(hv, layers["w_router"][l], flip, k=k)
                    flip_margin = jnp.maximum(flip_margin, jnp.where(flip, m, 0.0))
                for e in range(experts):
                    glu = functools.partial(
                        _glu, w_gate=layers["w_gate"][l, e], w_up=layers["w_up"][l, e],
                        w_down=layers["w_down"][l, e], bits=control_bits)
                    x = x + gate[..., e:e + 1] * glu(h)
                    if routed:
                        xv = xv + gate_v[..., e:e + 1] * glu(hv)
            else:
                x = x + _glu(h, layers["w_gate"][l], layers["w_up"][l],
                             layers["w_down"][l], bits=control_bits)
        if not routed:
            xv = x[rows, judge][:, :, None]
        xv = _rmsnorm(xv, params["final_norm_scale"], eps)
        head = params["lm_head"].astype(F32)
        # one routing at a time: all of them at once are 200 MB of logits
        logits = np.stack([np.asarray(xv[:, :, r] @ head)
                           for r in range(xv.shape[2])], axis=2)
    return logits, np.asarray(flip_margin), np.asarray(margin)
