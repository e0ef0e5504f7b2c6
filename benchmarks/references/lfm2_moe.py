"""The plain reference for LFM2-MoE (LiquidAI/LFM2-24B-A2B, ``model_type:
lfm2_moe``; Hugging Face ``Lfm2Moe*``): the forward pass in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision. No cache, no state, no kernels, no grouping of tokens by
expert, nothing imported from the program. One layer's weights (of a
sparse layer: one expert's) are upcast at a time.

  x = embed[tokens]
  per layer:  h = rmsnorm_op(x)
    conv:       [B | C | z] = h W_in  (D -> 3 D, no bias) ; u_t = B_t * z_t
                c_t = sum_{j=0..L-1} w[j] * u_{t-(L-1)+j}     L = conv_L_cache taps,
                      depthwise, causal, u before the sequence's start is 0
                x += (C_t * c_t) W_out
    attention:  q, k, v = h Wq, h Wk, h Wv        (H / KV heads of d, no bias)
                q, k = rmsnorm_d(q), rmsnorm_d(k)  a learned scale over each
                      head's own d values, BEFORE rope
                rope(q), rope(k)                    rotate_half layout
                x += softmax(causal(q k^T / sqrt(d))) v Wo
    h = rmsnorm_ffn(x)
    layers < num_dense_layers:  x += (silu(h Wg) * (h Wu)) Wd
    the others:  s = sigmoid(h W_r)                 E scores, W_r without bias
                 sel = the k largest of (s + offset)  the offset (HF ``expert_bias``)
                       chooses, it does not weigh ; the lower index first among equals
                 g = s[sel] / (sum(s[sel]) + 1e-6)  (norm_topk_prob)
                     times routed_scaling_factor
                 x += sum_{e in sel} g_e * (silu(h Wg_e) * (h Wu_e)) Wd_e
  logits = rmsnorm_out(x) W_head    W_head = embed^T (tied)

It reads sizes from the configuration FILE (the published key names)
and weights from the arrays it is handed, under the program's names:
groups ``conv`` (attn_norm_scale, w_in, conv_w (L, D), wo), ``attn``
(attn_norm_scale, wq, wk, wv, q_norm_scale, k_norm_scale, wo),
``dense`` and ``sparse`` (mlp_norm_scale, w_gate, w_up, w_down; w_router,
router_offset), each stacked over the layers of its kind in layer order.

ASSUMED (the catalog's row of the published ``config.json`` does not
carry them; the configuration file lists them under ``assumed``):
``tie_word_embeddings`` true (the LFM2 family ties its head), head size
hidden_size / num_attention_heads, ``num_hidden_layers`` under
``len(layer_types)`` takes the first entries, ``experts_held`` [lo, hi)
(absent: every expert) is the range of the router's outputs whose
experts exist here: what the others would add is left out.

Departures, noted: rotary angles use the half-split layout
(``rotate_half``), Hugging Face's; the convolution is the explicit sum
over its taps, not a padded ``conv1d``.

Sparse layers and ``correct``: a token whose k-th and (k+1)-th biased
scores are nearly level is sent to the other expert by any rounding
difference, and its logits then differ by as much as their own size
(``references/decoder.py`` has the argument). That file computes every
subset of layers, 2^layers routings a token; here the rule is BOUNDED:
for each judged token, float32's own routing (routing 0) and the
routings that give up the k-th chosen expert for the (k+1)-th in every
subset of that token's at most ``MAX_FLIPPED`` tightest sparse layers
whose margin, along float32's own routing, is under the file's
``tolerance.routing_margin``: at most 2^MAX_FLIPPED = 16 routings, at
any depth. A routing's ``flip_margin`` is the largest margin it
overruled (0 for routing 0; inf for a subset that names a layer the
token does not have: never taken). The margin is the distance between
the k-th and the (k+1)-th biased score as a share of the token's spread
of biased scores. Tokens a judged token attends to, or convolves over,
keep float32's routing.

``control_bits``: the lower-precision control (``references/decoder.py``
has the same): every matmul weight rounded per output column, every
matmul input per token, K and V per token and head, and the conv
layers' ``u`` (what a slot would keep as state) per token, to that many
bits; norms, the conv taps, the router, the embedding and the head stay
float32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
CONV, ATTENTION = "conv", "full_attention"
MAX_FLIPPED = 4  # sparse layers of a judged token that may go the other way


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


def layout(config):
    """[(mixer group, FFN group, index in the mixer's stack, index in
    the FFN's stack)] a layer, from the file's ``layer_types``."""
    out, seen = [], {}
    kinds = list(config["layer_types"])[: config["num_hidden_layers"]]
    for i, t in enumerate(kinds):
        mixer = "conv" if t == CONV else "attn"
        ffn = "dense" if i < config["num_dense_layers"] else "sparse"
        out.append((mixer, ffn, seen.get(mixer, 0), seen.get(ffn, 0)))
        seen[mixer] = seen.get(mixer, 0) + 1
        seen[ffn] = seen.get(ffn, 0) + 1
    return out


# --- the two mixers, for whole rows and for single tokens --------------------


def _u_of(h, w_in, bits):
    """(u = B * z, C) of normed inputs h (..., D)."""
    b, c, z = jnp.split(_act(h, bits) @ _weight(w_in, bits), 3, axis=-1)
    return _act(b * z, bits), c


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _conv(x, w, *, eps, bits):
    """x (B, T, D) -> x + conv mixer(rmsnorm(x))."""
    T = x.shape[1]
    taps = w["conv_w"].astype(F32)                      # (L, D)
    L = taps.shape[0]
    u, c_gate = _u_of(_rmsnorm(x, w["attn_norm_scale"], eps), w["w_in"], bits)
    past = jnp.pad(u, ((0, 0), (L - 1, 0), (0, 0)))     # zeros before the start
    c = sum(taps[j] * past[:, j:j + T] for j in range(L))
    return x + _act(c_gate * c, bits) @ _weight(w["wo"], bits)


@functools.partial(jax.jit, static_argnames=("eps",))
def _conv_at(x, xv, at, w, *, eps):
    """The same for single tokens whose residual is not the row's own:
    token ``xv[b, j, r]`` sits at position ``at[b, j]`` of row ``b``
    and convolves over the row's own ``u`` before it (from ``x``)."""
    taps = w["conv_w"].astype(F32)
    L = taps.shape[0]
    u, _ = _u_of(_rmsnorm(x, w["attn_norm_scale"], eps), w["w_in"], 0)
    uv, c_gate = _u_of(_rmsnorm(xv, w["attn_norm_scale"], eps), w["w_in"], 0)
    rows = jnp.arange(x.shape[0])[:, None]
    c = taps[L - 1] * uv
    for back in range(1, L):
        prev = jnp.where((at >= back)[..., None], u[rows, jnp.maximum(at - back, 0)], 0.0)
        c = c + taps[L - 1 - back] * prev[:, :, None]
    return xv + (c_gate * c) @ w["wo"].astype(F32)


def _qkv(h, w, pos, heads, kv_heads, eps, theta, bits):
    n = h.shape[0]
    d = w["wq"].shape[-1] // heads
    h = _act(h, bits)
    q = _rmsnorm((h @ _weight(w["wq"], bits)).reshape(n, heads, d), w["q_norm_scale"], eps)
    k = _rmsnorm((h @ _weight(w["wk"], bits)).reshape(n, kv_heads, d), w["k_norm_scale"], eps)
    v = (h @ _weight(w["wv"], bits)).reshape(n, kv_heads, d)
    group = heads // kv_heads
    k = jnp.repeat(_act(_rope(k, pos, theta), bits), group, axis=1)
    v = jnp.repeat(_act(v, bits), group, axis=1)
    return _rope(q, pos, theta), k, v


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "bits"))
def _attention(x, w, *, heads, kv_heads, eps, theta, bits):
    """x (B, T, D) -> x + attention(rmsnorm(x)), one row at a time."""
    T = x.shape[1]
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]

    def one(row):
        q, k, v = _qkv(_rmsnorm(row, w["attn_norm_scale"], eps), w, pos,
                       heads, kv_heads, eps, theta, bits)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(mask[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        return row + _act(o.reshape(T, -1), bits) @ _weight(w["wo"], bits)

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta"))
def _attention_at(x, xv, at, w, *, heads, kv_heads, eps, theta):
    """Attention for single tokens whose residual is not the row's own
    (see ``_conv_at``): a token attends to the row's keys and values
    BEFORE it (from ``x``) and to its own."""
    T, D = x.shape[1:]
    J, R = xv.shape[1:3]
    pos = jnp.arange(T)
    kw = dict(heads=heads, kv_heads=kv_heads, eps=eps, theta=theta, bits=0)

    def one(args):
        row, rv, p = args
        _, k, v = _qkv(_rmsnorm(row, w["attn_norm_scale"], eps), w, pos, **kw)
        p = jnp.repeat(p, R)
        q, k_own, v_own = _qkv(_rmsnorm(rv.reshape(J * R, D), w["attn_norm_scale"], eps),
                               w, p, **kw)
        s = jnp.where((pos[None, :] < p[:, None])[:, None, :],
                      jnp.einsum("nhd,khd->nhk", q, k), -jnp.inf)
        s_own = jnp.einsum("nhd,nhd->nh", q, k_own)
        a = jax.nn.softmax(jnp.concatenate([s, s_own[..., None]], -1)
                           / np.sqrt(q.shape[-1]), axis=-1)
        o = jnp.einsum("nhk,khd->nhd", a[..., :T], v) + a[..., T:] * v_own
        return rv + (o.reshape(J * R, -1) @ w["wo"].astype(F32)).reshape(J, R, D)

    return jax.lax.map(one, (x, xv, at))


# --- the two feed-forward kinds ---------------------------------------------


@functools.partial(jax.jit, static_argnames=("bits",))
def _glu(h, w_gate, w_up, w_down, *, bits):
    w_gate, w_up, w_down = (_weight(w, bits) for w in (w_gate, w_up, w_down))
    h = _act(h, bits)
    return _act(jax.nn.silu(h @ w_gate) * (h @ w_up), bits) @ w_down


@functools.partial(jax.jit, static_argnames=("bits",))
def _add_expert(x, h, gate, w, l, e, at, *, bits):
    """x + gate[..., e] * expert(h): expert ``e`` of the router, whose
    weights are entry ``at`` of layer ``l``'s stack ``w``; one expert's
    weights upcast, one program for every expert and layer."""
    one = {name: jax.lax.dynamic_index_in_dim(
        jax.lax.dynamic_index_in_dim(w[name], l, 0, keepdims=False), at, 0,
        keepdims=False) for name in ("w_gate", "w_up", "w_down")}
    g = jnp.take(gate, e, axis=-1)[..., None]
    return x + g * _glu(h, one["w_gate"], one["w_up"], one["w_down"], bits=bits)


@functools.partial(jax.jit, static_argnames=("k", "norm", "scaling"))
def _route(h, w_router, offset, flip, *, k, norm, scaling):
    """(gate (..., E): the chosen experts' weights, zero elsewhere;
    margin (...)). Where ``flip`` (...) is set the k-th chosen expert
    gives way to the (k+1)-th."""
    s = jax.nn.sigmoid(h @ w_router.astype(F32))
    biased = s + offset.astype(F32)
    top, idx = jax.lax.top_k(biased, k + 1)
    last = jnp.where(jnp.broadcast_to(flip, s.shape[:-1]), k, k - 1)[..., None]
    idx_k = jnp.concatenate([idx[..., :k - 1], jnp.take_along_axis(idx, last, -1)], -1)
    g = jnp.take_along_axis(s, idx_k, -1)
    if norm:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
    gate = jnp.sum(jax.nn.one_hot(idx_k, s.shape[-1], dtype=F32) * (g * scaling)[..., None],
                   axis=-2)
    margin = (top[..., k - 1] - top[..., k]) / jnp.std(biased, axis=-1)
    return gate, margin


def _sparse_ffn(config, w, l, streams, flips, bits):
    """One sparse layer over several token sets that share the expert
    loop: ``streams`` a list of residuals (..., D), ``flips`` the
    matching ``flip`` arrays. -> (new residuals, margins)."""
    E = config["num_experts"]
    lo, hi = config.get("experts_held") or (0, E)
    eps = float(config["norm_eps"])
    offset = w["router_offset"][l] if "router_offset" in w else jnp.zeros((E,), F32)
    hs = [_rmsnorm(x, w["mlp_norm_scale"][l], eps) for x in streams]
    routed = [_route(h, w["w_router"][l], offset, flip,
                     k=config["num_experts_per_tok"],
                     norm=bool(config.get("norm_topk_prob", True)),
                     scaling=float(config.get("routed_scaling_factor", 1.0)))
              for h, flip in zip(hs, flips)]
    out = list(streams)
    stacks = {name: w[name] for name in ("w_gate", "w_up", "w_down")}
    for e in range(lo, hi):
        out = [_add_expert(x, h, gate, stacks, l, e, e - lo, bits=bits)
               for x, h, (gate, _) in zip(out, hs, routed)]
    return out, [m for _, m in routed]


# --- the forward pass --------------------------------------------------------


def _sizes(config):
    rope = config.get("rope_parameters") or {}
    return dict(heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"],
                eps=float(config["norm_eps"]),
                theta=float(rope.get("rope_theta", config.get("rope_theta", 1e6))))


def _layer(params, group, index):
    return {name: w[index] for name, w in params[group].items()}


def _mixer(params, config, mixer, index, x, bits):
    a = _sizes(config)
    w = _layer(params, mixer, index)
    if mixer == "conv":
        return _conv(x, w, eps=a["eps"], bits=bits)
    return _attention(x, w, **a, bits=bits)


def _hidden(params, config, tokens, *, control_bits=0):
    """(the last layer's residual (B, T, D), each layer's INPUT
    residual, each sparse layer's margins (B, T))."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    inputs, margins = [], []
    eps = float(config["norm_eps"])
    for mixer, ffn, mi, fi in layout(config):
        inputs.append(x)
        x = _mixer(params, config, mixer, mi, x, control_bits)
        if ffn == "dense":
            w = _layer(params, "dense", fi)
            x = x + _glu(_rmsnorm(x, w["mlp_norm_scale"], eps), w["w_gate"],
                         w["w_up"], w["w_down"], bits=control_bits)
        else:
            (x,), (m,) = _sparse_ffn(config, params["sparse"], fi, [x], [False],
                                     control_bits)
            margins.append(m)
    return x, inputs, margins


def _head(params, config, x):
    x = _rmsnorm(x, params["final_norm_scale"], float(config["norm_eps"]))
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return x @ head.astype(F32)


def forward(params, config, tokens, *, control_bits=0):
    """Float32 logits (B, T, V) of ``tokens`` (B, T) under float32's
    own routing: what the tests compare the served path with."""
    with jax.default_matmul_precision("highest"):
        x, _, _ = _hidden(params, config, jnp.asarray(tokens, jnp.int32),
                          control_bits=control_bits)
        return np.asarray(_head(params, config, x))


def flipped_layers(margins, allowed):
    """(flips (B, J, R, S) bool, valid (B, J, R) bool) from a judged
    token's margins (B, J, S) along float32's own routing: routing r
    flips the token's i-th tightest sparse layer, of those under
    ``allowed``, where bit i of r is set; a routing that names a layer
    the token does not have is not valid."""
    B, J, S = margins.shape
    n = min(MAX_FLIPPED, S)
    order = np.argsort(margins, axis=-1, kind="stable")[..., :n]     # (B, J, n)
    tight = np.take_along_axis(margins, order, -1) < allowed
    bits = (np.arange(2 ** n)[:, None] >> np.arange(n)[None, :]) & 1  # (R, n)
    flips = np.zeros((B, J, 2 ** n, S), bool)
    chosen = bits[None, None].astype(bool) & tight[:, :, None, :]    # (B, J, R, n)
    np.put_along_axis(flips, np.broadcast_to(order[:, :, None, :], chosen.shape),
                      chosen, axis=-1)
    valid = ~(bits[None, None].astype(bool) & ~tight[:, :, None, :]).any(-1)
    return flips, valid


def judged_logits(params, config, tokens, judge, *, control_bits=0, routings=True):
    """Float32 logits of ``tokens`` (B, T) at the positions ``judge``
    (B, J): (logits (B, J, R, V), flip_margin (B, J, R), margin (B, J)),
    the shape ``harness/probe.py::against`` reads. R is 1 for the
    control and without ``routings``, else 2^min(MAX_FLIPPED, sparse
    layers) (module docstring). ``margin``: the judged token's smallest
    router margin over the layers. Positions past a row's own length
    are padding: a causal model keeps them out of every judged position
    before them."""
    tokens = jnp.asarray(tokens, jnp.int32)
    judge = jnp.asarray(judge, jnp.int32)
    rows = jnp.arange(tokens.shape[0])[:, None]
    a = _sizes(config)
    with jax.default_matmul_precision("highest"):
        x, inputs, margins = _hidden(params, config, tokens, control_bits=control_bits)
        own = np.stack([np.asarray(m[rows, judge]) for m in margins], -1)   # (B, J, S)
        margin = own.min(-1) if own.size else np.full(judge.shape, np.inf, np.float32)
        if control_bits or not routings or not margins:
            logits = np.asarray(_head(params, config, x[rows, judge]))[:, :, None]
            return logits, np.zeros(judge.shape + (1,), np.float32), margin
        allowed = float(config.get("tolerance", {}).get("routing_margin", 0.0))
        flips, valid = flipped_layers(own, allowed)
        flips = jnp.asarray(flips)
        flip_margin = jnp.zeros(valid.shape, F32)
        xv = jnp.broadcast_to(inputs[0][rows, judge][:, :, None],
                              valid.shape + x.shape[-1:])
        for (mixer, ffn, mi, fi), x_in in zip(layout(config), inputs):
            w = _layer(params, mixer, mi)
            if mixer == "conv":
                xv = _conv_at(x_in, xv, judge, w, eps=a["eps"])
            else:
                xv = _attention_at(x_in, xv, judge, w, **a)
            if ffn == "dense":
                w = _layer(params, "dense", fi)
                xv = xv + _glu(_rmsnorm(xv, w["mlp_norm_scale"], a["eps"]),
                               w["w_gate"], w["w_up"], w["w_down"], bits=0)
            else:
                (xv,), (m,) = _sparse_ffn(config, params["sparse"], fi, [xv],
                                          [flips[..., fi]], 0)
                flip_margin = jnp.maximum(flip_margin, jnp.where(flips[..., fi], m, 0.0))
        flip_margin = np.where(valid, np.asarray(flip_margin), np.inf)
        # one routing at a time: all of them at once are 200 MB of logits
        logits = np.stack([np.asarray(_head(params, config, xv[:, :, r]))
                           for r in range(xv.shape[2])], axis=2)
    return logits, flip_margin, margin
