"""The plain reference for Laguna (poolside/Laguna-XS.2, ``model_type:
laguna``): the forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision. No cache, no pages, no kernels, no
batching: one sequence at a time, nothing imported from the program. One
layer's attention weights (of the experts: one expert's) are upcast at a
time, so a layer's 3.2 GB of float32 experts never exist at once.

  x = embed[tokens]
  per layer i (norm(x) = x rsqrt(mean x^2 + eps) w), with H_i query heads
  (num_attention_heads_per_layer), 8 K/V heads of d = 128:
    h = norm_1(x)
    q, k, v = h Wq, h Wk, h Wv         no bias
    g = sigmoid(h Wg)                  (D, H_i): ONE scalar a head and token
    q, k = norm_head(q), norm_head(k)  over a head's d channels, a scale of d each
    layer_types[i] == sliding_attention:
        rope over the whole head, theta 1e4, rotate_half
        a_t = softmax_j(q_t . k_j / sqrt(d)) v_j   over t - window < j <= t
    layer_types[i] == full_attention:
        rope on the FIRST partial_rotary_factor * d channels, the others pass;
        frequencies by YaRN over those channels (the plain theta^(-2j/r) below
        channel floor(c(beta_fast)), those over ``factor`` above
        ceil(c(beta_slow)), a linear ramp between; c(n) = r ln(original_max /
        (2 pi n)) / (2 ln theta)); cos and sin both times attention_factor
        a_t = softmax_j(q_t . k_j / sqrt(d)) v_j   over j <= t
    x += concat_head(g_head * a_head) Wo
    h = norm_2(x)
    mlp_layer_types[i] == dense:   x += (silu(h Wg1) * (h Wu)) Wd
    else:  s = sigmoid(h Wr)                     all num_experts outputs
           E = the k largest of s + offset       the lower index first among equals
           w_e = s_e / (sum_E s + 1e-20) * moe_routed_scaling_factor
           x += sum_{e in E, e held} w_e expert_e(h) + shared(h)
           every expert (silu(h Wg_e) * (h Wu_e)) Wd_e; the shared one ungated
  logits = norm(x) W_head              (untied)

The causal mask and the window are ONE inequality on positions over the
whole sequence; queries are taken a block at a time so that a prompt of
16 384 tokens fits beside the served model (a block's scores against
every key a full layer may see, or against the window's span), and an
expert computes the tokens sent to it, taken out by a plain stable sort
into a fixed number of places (every token through every one of 256
experts would be thirty times the work and compute the same numbers).

It reads sizes from the configuration FILE (the published key names)
and weights from the arrays it is handed, under the program's names:
groups ``full`` and ``window`` (attn_norm_scale, wq, wk, wv, wg,
q_norm_scale, k_norm_scale, wo; by the layer's kind), ``dense``
(mlp_norm_scale, w_gate, w_up, w_down) and ``sparse`` (mlp_norm_scale,
w_router, router_bias: the selection offset, w_gate, w_up, w_down,
``shared`` with its own three), each stacked over its layers in layer
order.

ASSUMED (the published ``config.json`` does not settle them; the
configuration file lists them under ``assumed``): the gate's sigmoid,
the norm of q and k a head, the router's rule (sigmoid scores, a
selection offset, the chosen renormalised), no gate on the shared
expert; SiLU in both FFNs; ``attention_factor`` on cos and sin with the
softmax scale unchanged; the window keeps a query's own position and
the ``sliding_window - 1`` before it. ``num_hidden_layers`` under the
lists' length takes their first entries; ``experts_held`` [lo, hi)
(absent: every expert) is the range of the router's outputs whose
experts exist here.

Sparse layers and ``correct``: the BOUNDED routing rule of
``references/lfm2_moe.py``. For each judged token, float32's own
routing (routing 0) and the routings that give up the k-th chosen
expert for the (k+1)-th in every subset of that token's at most
``MAX_FLIPPED`` tightest sparse layers whose margin is under the file's
``tolerance.routing_margin``. The margin is the distance between the
k-th and the (k+1)-th of ``s + offset`` as a share of the token's
spread of them. Tokens a judged token attends to keep float32's
routing: their keys and values, a layer, are kept from the first pass.

``control_bits``: the lower-precision control: every matmul weight
rounded per output column, every matmul input per token, K and V per
token and head, to that many bits; norms, the router, the embedding and
the head stay float32. ``window=False``: the second control, in which
the window layers attend the whole context. ``yarn=False``: the third,
in which the full layers' rope is plain (no ramp, cos and sin times 1).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MAX_FLIPPED = 4   # sparse layers of a judged token that may go the other way
Q_BLOCK = 64      # queries a block of attention
V_BLOCKS = 8      # column blocks of the head
AT_BLOCK = 32     # single tokens a block of ``_attention_at``


def _rounded(x, bits, axis):
    if not bits:
        return x
    top = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / top
    return jnp.round(x / scale) * scale


def _weight(w, bits):
    return _rounded(w.astype(F32), bits, -2)


def _act(x, bits):
    return _rounded(x, bits, -1)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


# --- sizes and positions, from the file --------------------------------------


def yarn_inv_freq(r, theta, factor, original_max, beta_fast, beta_slow):
    """The r / 2 frequencies of r rotated channels (module docstring)."""
    plain = theta ** -(np.arange(0, r, 2, dtype=np.float64) / r)
    if factor <= 1:
        return plain

    def channel(turns):
        return r * math.log(original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(channel(beta_fast)), 0)
    high = min(math.ceil(channel(beta_slow)), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rope_of(config, layer_type, yarn):
    """(frequencies, the factor on cos and sin) of one kind of layer."""
    p = config["rope_parameters"][layer_type]
    r = int(config["head_dim"] * float(p.get("partial_rotary_factor", 1.0)))
    theta = float(p["rope_theta"])
    if p.get("rope_type", "default") != "yarn" or not yarn:
        return tuple(yarn_inv_freq(r, theta, 1.0, 0, 0, 0)), 1.0
    factor = float(p["factor"])
    inv = yarn_inv_freq(
        r, theta, factor,
        int(p.get("original_max_position_embeddings", config["max_position_embeddings"])),
        float(p.get("beta_fast", 32)), float(p.get("beta_slow", 1)))
    return tuple(inv), float(p.get("attention_factor", 0.1 * math.log(factor) + 1.0))


def layout(config):
    """[(attention group, index in its stack, query heads, FFN group,
    index in the FFN's stack)] a layer."""
    n = config["num_hidden_layers"]
    heads = list(config.get("num_attention_heads_per_layer")
                 or [config["num_attention_heads"]] * n)
    ffns = list(config.get("mlp_layer_types") or [
        "dense" if i in config.get("mlp_only_layers", ()) else "sparse"
        for i in range(n)])
    out, seen = [], {}
    for t, h, ffn in zip(config["layer_types"][:n], heads, ffns):
        group = "window" if t == "sliding_attention" else "full"
        out.append((group, seen.get(group, 0), h, ffn, seen.get(ffn, 0)))
        seen[group] = seen.get(group, 0) + 1
        seen[ffn] = seen.get(ffn, 0) + 1
    return out


def _kind(config, group, heads, *, window=True, yarn=True):
    """What one layer's attention is, hashable (a jit's static)."""
    t = "sliding_attention" if group == "window" else "full_attention"
    inv, factor = _rope_of(config, t, yarn)
    return (heads, config["num_key_value_heads"], float(config["rms_norm_eps"]),
            inv, factor,
            int(config["sliding_window"]) if group == "window" and window else 0)


def _rope(x, positions, inv, factor):
    """x (T, heads, d) at ``positions``: the first 2 len(inv) channels
    rotated (rotate_half over them), the others passed."""
    r = 2 * len(inv)
    ang = positions.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos = (jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * factor)[:, None, :]
    sin = (jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * factor)[:, None, :]
    xr = x[..., :r]
    rot = jnp.concatenate([-xr[..., r // 2:], xr[..., :r // 2]], -1)
    return jnp.concatenate([xr * cos + rot * sin, x[..., r:]], -1)


# --- attention ---------------------------------------------------------------


def _qkv(h, w, pos, kind, bits):
    """h (n, D) at positions ``pos`` -> q (n, H, d), k, v (n, KV, d),
    the gates (n, H)."""
    heads, kv_heads, eps, inv, factor, _ = kind
    n = h.shape[0]
    d = w["wq"].shape[-1] // heads
    h = _act(h, bits)
    q = (h @ _weight(w["wq"], bits)).reshape(n, heads, d)
    k = (h @ _weight(w["wk"], bits)).reshape(n, kv_heads, d)
    v = (h @ _weight(w["wv"], bits)).reshape(n, kv_heads, d)
    gate = jax.nn.sigmoid(h @ _weight(w["wg"], bits))
    q = _rope(_rmsnorm(q, w["q_norm_scale"], eps), pos, inv, factor)
    k = _rope(_rmsnorm(k, w["k_norm_scale"], eps), pos, inv, factor)
    return q, _act(k, bits), _act(v, bits), gate


def _attend(q, q_pos, k, v, k_pos, window):
    """q (n, H, d) at ``q_pos`` against every key k, v (T, KV, d) at
    ``k_pos``: key j is seen by query i where k_pos[j] <= q_pos[i] and,
    with a ``window``, k_pos[j] > q_pos[i] - window. -> (n, H, d)."""
    n, H, d = q.shape
    KV = k.shape[1]
    seen = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] >= 0)
    if window:
        seen &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.einsum("ngqd,tgd->gqnt", q.reshape(n, KV, H // KV, d), k) / np.sqrt(d)
    s = jnp.where(seen[None, None], s, -jnp.inf)
    o = jnp.einsum("gqnt,tgd->ngqd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(n, H, d)


@functools.partial(jax.jit, static_argnames=("kind", "bits"))
def _attention(x, w, *, kind, bits):
    """x (T, D) -> (x + attention(norm_1(x)), k, v): one sequence, the
    queries a block at a time under the whole mask: against every key,
    or, with a window shorter than the sequence, against the keys from
    a window before the block's first query to its last."""
    eps, window = kind[2], kind[5]
    T = x.shape[0]
    pos = jnp.arange(T)
    q, k, v, gate = _qkv(_rmsnorm(x, w["attn_norm_scale"], eps), w, pos, kind, bits)
    blocks = -(-T // Q_BLOCK)
    pad = blocks * Q_BLOCK - T
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape((blocks, Q_BLOCK) + q.shape[1:])
    pb = jnp.pad(pos, (0, pad)).reshape(blocks, Q_BLOCK)
    span = window + Q_BLOCK
    if window and span < T:
        front = ((span, pad), (0, 0), (0, 0))
        kp, vp = jnp.pad(k, front), jnp.pad(v, front)

        def block(a):
            end = a[1][0] + Q_BLOCK     # one past the block's last query
            near = (jax.lax.dynamic_slice_in_dim(kv, end, span) for kv in (kp, vp))
            return _attend(a[0], a[1], *near, end - span + jnp.arange(span), window)
    else:
        def block(a):
            return _attend(a[0], a[1], k, v, pos, window)
    o = jax.lax.map(block, (qb, pb))
    o = o.reshape((blocks * Q_BLOCK,) + q.shape[1:])[:T] * gate[..., None]
    return x + _act(o.reshape(T, -1), bits) @ _weight(w["wo"], bits), k, v


@functools.partial(jax.jit, static_argnames=("kind",))
def _attention_at(xv, at, k, v, w, *, kind):
    """Attention for single tokens whose residual is not the sequence's
    own: token ``xv[j, r]`` sits at position ``at[j]`` and attends to the
    sequence's keys and values BEFORE it (``k``, ``v`` of the first
    pass) and to its own."""
    eps, window = kind[2], kind[5]
    J, R, D = xv.shape
    T = k.shape[0]
    p = jnp.repeat(at, R)
    q, k_own, v_own, gate = _qkv(
        _rmsnorm(xv.reshape(J * R, D), w["attn_norm_scale"], eps), w, p, kind, 0)
    pos = jnp.arange(T)
    n, H, d = q.shape
    KV = k.shape[1]

    def block(a):
        # the sequence's own key at a token's position gives way to the token's
        qg, k_own, v_own, p = a
        seen = pos[None, :] < p[:, None]
        if window:
            seen &= pos[None, :] > p[:, None] - window
        s = jnp.where(seen[:, None, None], jnp.einsum("ngqd,tgd->ngqt", qg, k), -jnp.inf)
        s_own = jnp.einsum("ngqd,ngd->ngq", qg, k_own)
        a = jax.nn.softmax(jnp.concatenate([s, s_own[..., None]], -1) / np.sqrt(d), axis=-1)
        return jnp.einsum("ngqt,tgd->ngqd", a[..., :T], v) + a[..., T:] * v_own[:, :, None]

    # a block of tokens at a time: every token's scores against the
    # whole sequence at once would be gigabytes
    blocks = -(-n // AT_BLOCK)
    pad = blocks * AT_BLOCK - n

    def blocked(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((blocks, AT_BLOCK) + x.shape[1:])

    o = jax.lax.map(block, (blocked(q.reshape(n, KV, H // KV, d)), blocked(k_own),
                            blocked(v_own), blocked(p)))
    o = o.reshape(blocks * AT_BLOCK, H, d)[:n] * gate[..., None]
    return xv + (o.reshape(n, H * d) @ w["wo"].astype(F32)).reshape(J, R, D)


# --- the two feed-forward kinds ----------------------------------------------


@functools.partial(jax.jit, static_argnames=("bits",))
def _glu(h, w_gate, w_up, w_down, *, bits):
    w_gate, w_up, w_down = (_weight(w, bits) for w in (w_gate, w_up, w_down))
    h = _act(h, bits)
    return _act(jax.nn.silu(h @ w_gate) * (h @ w_up), bits) @ w_down


@functools.partial(jax.jit, static_argnames=("k", "scaling"))
def _route(h, w_router, offset, flip, *, k, scaling):
    """(gate (..., E): the chosen experts' weights, zero elsewhere;
    margin (...)) from the normed tokens ``h``. Where ``flip`` (...) is
    set the k-th chosen expert gives way to the (k+1)-th."""
    s = jax.nn.sigmoid(h @ w_router.astype(F32))
    t = s + offset.astype(F32)
    top, idx = jax.lax.top_k(t, k + 1)
    last = jnp.where(jnp.broadcast_to(flip, t.shape[:-1]), k, k - 1)[..., None]
    idx_k = jnp.concatenate([idx[..., :k - 1], jnp.take_along_axis(idx, last, -1)], -1)
    g = jnp.take_along_axis(s, idx_k, -1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) * scaling
    gate = jnp.sum(jax.nn.one_hot(idx_k, t.shape[-1], dtype=F32) * g[..., None], axis=-2)
    margin = (top[..., k - 1] - top[..., k]) / jnp.std(t, axis=-1)
    return gate, margin


@functools.partial(jax.jit, static_argnames=("bits", "room"))
def _add_expert(x, h, gate, w, l, e, at, *, bits, room):
    """x + gate[:, e] * expert(h) over a flat token axis (N, D): expert
    ``e`` of the router, whose weights are entry ``at`` of layer ``l``'s
    stack ``w``. The tokens sent to it are taken out by a stable sort
    that puts them first (``room`` places: the caller has counted; a
    spare place holds a token with gate 0, which adds nothing) and
    their results put back by the inverse order: gathers alone, every
    index in range."""
    one = {name: jax.lax.dynamic_index_in_dim(
        jax.lax.dynamic_index_in_dim(w[name], l, 0, keepdims=False), at, 0,
        keepdims=False) for name in ("w_gate", "w_up", "w_down")}
    g = jnp.take(gate, e, axis=-1)
    order = jnp.argsort(g <= 0, stable=True)     # the tokens sent here first
    sent = order[:room]
    out = g[sent][:, None] * _glu(h[sent], one["w_gate"], one["w_up"],
                                  one["w_down"], bits=bits)
    place = jnp.argsort(order)                   # where each token went
    out = jnp.concatenate([out, jnp.zeros((1, out.shape[1]), out.dtype)])
    return x + out[jnp.minimum(place, room)]


def _sparse_ffn(config, w, l, x, flip, bits):
    """One sparse layer over a flat token axis: x (N, D) -> (x + its
    experts held + the shared expert, margin (N,)); ``flip`` (N,) or
    False."""
    E = config["num_experts"]
    lo, hi = config.get("experts_held") or (0, E)
    h = _rmsnorm(x, w["mlp_norm_scale"][l], float(config["rms_norm_eps"]))
    gate, margin = _route(
        h, w["w_router"][l], w["router_bias"][l], jnp.asarray(flip),
        k=config["num_experts_per_tok"],
        scaling=float(config.get("moe_routed_scaling_factor", 1.0)))
    sent = np.asarray(jnp.sum(gate > 0, axis=0))
    # a few sizes of program: the fullest expert's tokens, rounded up
    room = min(x.shape[0], int(2 ** np.ceil(np.log2(max(int(sent.max()), 1)))))
    stacks = {name: w[name] for name in ("w_gate", "w_up", "w_down")}
    out = x
    for e in range(lo, hi):
        if sent[e]:
            out = _add_expert(out, h, gate, stacks, l, e, e - lo, bits=bits, room=room)
    if "shared" in w:
        sh = w["shared"]
        out = out + _glu(h, sh["w_gate"][l], sh["w_up"][l], sh["w_down"][l], bits=bits)
    return out, margin


def _dense_ffn(config, w, x, bits):
    h = _rmsnorm(x, w["mlp_norm_scale"], float(config["rms_norm_eps"]))
    return x + _glu(h, w["w_gate"], w["w_up"], w["w_down"], bits=bits)


# --- the forward pass --------------------------------------------------------


def _layer(params, group, index):
    return {name: w[index] for name, w in params[group].items()
            if not isinstance(w, dict)}


def _hidden(params, config, tokens, *, control_bits=0, window=True, yarn=True):
    """One sequence ``tokens`` (T,): (the last layer's residual (T, D),
    each layer's (k, v), each SPARSE layer's margins (T,))."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    kvs, margins = [], []
    for group, index, heads, ffn, fi in layout(config):
        x, k, v = _attention(
            x, _layer(params, group, index), bits=control_bits,
            kind=_kind(config, group, heads, window=window, yarn=yarn))
        kvs.append((k, v))
        if ffn == "dense":
            x = _dense_ffn(config, _layer(params, "dense", fi), x, control_bits)
        else:
            x, m = _sparse_ffn(config, params["sparse"], fi, x, False, control_bits)
            margins.append(m)
    return x, kvs, margins


def _head(params, config, x):
    x = _rmsnorm(x, params["final_norm_scale"], float(config["rms_norm_eps"]))
    if "lm_head" not in params:
        return x @ params["embed"].T.astype(F32)
    # the head a column block at a time: whole, its float32 copy is 0.8 GB
    V = params["lm_head"].shape[1]
    step = -(-V // V_BLOCKS)
    return jnp.concatenate([x @ params["lm_head"][:, i:i + step].astype(F32)
                            for i in range(0, V, step)], axis=-1)


def forward(params, config, tokens, *, control_bits=0, window=True, yarn=True):
    """Float32 logits (B, T, V) of ``tokens`` (B, T) under float32's
    own routing: what the tests compare the served path with."""
    with jax.default_matmul_precision("highest"):
        out = []
        for row in np.asarray(tokens):
            x, _, _ = _hidden(params, config, jnp.asarray(row, jnp.int32),
                              control_bits=control_bits, window=window, yarn=yarn)
            out.append(np.asarray(_head(params, config, x)))
        return np.stack(out)


def flipped_layers(margins, allowed):
    """(flips (J, R, S) bool, valid (J, R) bool) from a sequence's
    judged tokens' margins (J, S) along float32's own routing, S its
    sparse layers: routing r flips the token's i-th tightest layer, of
    those under ``allowed``, where bit i of r is set; a routing that
    names a layer the token does not have is not valid."""
    J, S = margins.shape
    n = min(MAX_FLIPPED, S)
    order = np.argsort(margins, axis=-1, kind="stable")[..., :n]     # (J, n)
    tight = np.take_along_axis(margins, order, -1) < allowed
    bits = ((np.arange(2 ** n)[:, None] >> np.arange(n)[None, :]) & 1).astype(bool)
    flips = np.zeros((J, 2 ** n, S), bool)
    chosen = bits[None] & tight[:, None, :]                          # (J, R, n)
    np.put_along_axis(flips, np.broadcast_to(order[:, None, :], chosen.shape),
                      chosen, axis=-1)
    valid = ~(bits[None] & ~tight[:, None, :]).any(-1)
    return flips, valid


def _judged_row(params, config, tokens, judge, control_bits, routings, window, yarn):
    """One sequence: (logits (J, R, V), flip_margin (J, R), margin (J,))."""
    x, kvs, margins = _hidden(params, config, tokens, control_bits=control_bits,
                              window=window, yarn=yarn)
    own = (np.stack([np.asarray(m[judge]) for m in margins], -1) if margins
           else np.zeros(judge.shape + (0,), np.float32))             # (J, S)
    margin = own.min(-1) if own.size else np.full(judge.shape, np.inf, np.float32)
    if control_bits or not routings or not margins:
        logits = np.asarray(_head(params, config, x[judge]))[:, None]
        return logits, np.zeros(judge.shape + (1,), np.float32), margin
    allowed = float(config.get("tolerance", {}).get("routing_margin", 0.0))
    flips, valid = flipped_layers(own, allowed)
    flips = jnp.asarray(flips)
    flip_margin = jnp.zeros(valid.shape, F32)
    x0 = jnp.take(params["embed"], tokens[judge], axis=0).astype(F32)
    xv = jnp.broadcast_to(x0[:, None], valid.shape + x0.shape[-1:])   # (J, R, D)
    J, R, D = xv.shape
    for (group, index, heads, ffn, fi), (k, v) in zip(layout(config), kvs):
        xv = _attention_at(xv, judge, k, v, _layer(params, group, index),
                           kind=_kind(config, group, heads, window=window, yarn=yarn))
        if ffn == "dense":
            xv = _dense_ffn(config, _layer(params, "dense", fi), xv, 0)
        else:
            flat, m = _sparse_ffn(config, params["sparse"], fi, xv.reshape(J * R, D),
                                  flips[..., fi].reshape(J * R), 0)
            xv = flat.reshape(J, R, D)
            flip_margin = jnp.maximum(
                flip_margin, jnp.where(flips[..., fi], m.reshape(J, R), 0.0))
    flip_margin = np.where(valid, np.asarray(flip_margin), np.inf)
    logits = np.stack([np.asarray(_head(params, config, xv[:, r]))
                       for r in range(R)], axis=1)
    return logits, flip_margin, margin


def judged_logits(params, config, tokens, judge, *, control_bits=0,
                  routings=True, window=True, yarn=True):
    """Float32 logits of ``tokens`` (B, T) at the positions ``judge``
    (B, J): (logits (B, J, R, V), flip_margin (B, J, R), margin (B, J)),
    the shape ``harness/probe.py::against`` reads. R is 1 for a control
    and without ``routings``, else 2^min(MAX_FLIPPED, sparse layers)
    (module docstring). ``margin``: the judged token's smallest router
    margin over the sparse layers. Positions past a row's own length
    are padding: a causal model keeps them out of every judged position
    before them."""
    out = []
    with jax.default_matmul_precision("highest"):
        for row, at in zip(np.asarray(tokens), np.asarray(judge)):
            out.append(_judged_row(
                params, config, jnp.asarray(row, jnp.int32),
                jnp.asarray(at, jnp.int32), control_bits, routings, window, yarn))
    return tuple(np.stack(part) for part in zip(*out))
