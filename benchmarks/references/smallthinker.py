"""The plain reference for SmallThinker (PowerInfer/SmallThinker-21BA3B-
Instruct): the forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision. No cache, no pages, no kernels, no
batching: one sequence at a time, nothing imported from the program. One
layer's attention weights (of the experts: one expert's) are upcast at
a time.

  x = embed[tokens]
  per layer l, with input x:
    r = x W_r                          64 router outputs, from the layer's INPUT:
                                       ahead of the input norm and of attention
    E = the 6 largest of r             the lower index first among equals
    g = softmax(r[E])                  moe_primary_router_apply_softmax
    h = rmsnorm_in(x)
    q, k, v = h Wq, h Wk, h Wv         H / KV heads of d, no bias, no q/k norm
    if rope_layout[l]:  rope(q), rope(k)      rotate_half over the whole head;
                                       a layer without it has no positions at all
    a_i = softmax_j(q_i . k_j / sqrt(d)) v_j  over j <= i, and where
          sliding_window_layout[l] also j > i - sliding_window_size
    x += a Wo
    h2 = rmsnorm_post(x)
    x += sum_{e in E} g_e * (relu(h2 Wg_e) * (h2 Wu_e)) Wd_e      ReGLU
  logits = rmsnorm_out(x) W_head       (untied)

The causal mask and the window are ONE inequality on positions over the
whole sequence; queries are taken a block at a time so that a prompt of
15 360 tokens fits beside the served model (a block's scores against
every key, not the sequence's), and an expert computes the tokens sent
to it, taken out by a plain stable sort into a fixed number of places
(every token through every expert would be ten times the work and
compute the same numbers).

It reads sizes from the configuration FILE (the published key names)
and weights from the arrays it is handed, under the program's names:
groups ``route`` (w_router, every layer), ``full`` and ``window``
(attn_norm_scale, wq, wk, wv, wo; by the layer's kind) and ``sparse``
(mlp_norm_scale, w_gate, w_up, w_down; every layer), each stacked over
its layers in layer order.

ASSUMED (the catalog's row of the published ``config.json`` does not
settle them; the configuration file lists them under ``assumed``):
(1) the router's input is the residual stream as it ENTERS the layer,
not normed (the published llama.cpp graph multiplies ``ffn_gate_inp``
by the layer's input ahead of ``attn_norm``); (2) the window keeps a
query's own position and the ``sliding_window_size - 1`` before it;
(3) no "secondary" experts: the config has no key for any.
``num_hidden_layers`` under ``len(sliding_window_layout)`` takes the
first entries; ``experts_held`` [lo, hi) (absent: every expert) is the
range of the router's outputs whose experts exist here.

Sparse layers and ``correct``: the BOUNDED routing rule of
``references/lfm2_moe.py``. For each judged token, float32's own
routing (routing 0) and the routings that give up the k-th chosen
expert for the (k+1)-th in every subset of that token's at most
``MAX_FLIPPED`` tightest layers whose margin is under the file's
``tolerance.routing_margin``. The margin is the distance between the
k-th and the (k+1)-th router output as a share of the token's spread of
router outputs. Tokens a judged token attends to keep float32's
routing: their keys and values, a layer, are kept from the first pass.

``control_bits``: the lower-precision control: every matmul weight
rounded per output column, every matmul input per token, K and V per
token and head, to that many bits; norms, the router, the embedding and
the head stay float32. ``window=False``: the second control, in which
the window layers attend the whole context (rope kept): what the served
path would compute if a window layer's mask, table or freed pages were
wrong in the other direction.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MAX_FLIPPED = 4   # layers of a judged token that may go the other way
Q_BLOCK = 128     # queries a block of attention
V_BLOCKS = 8      # column blocks of the head
AT_BLOCK = 64     # single tokens a block of ``_attention_at``


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
    """[(attention group, index in its stack, windowed, roped)] a layer."""
    n = config["num_hidden_layers"]
    window = list(config["sliding_window_layout"])[:n]
    rope = list(config.get("rope_layout", window))[:n]
    out, seen = [], {}
    for w, r in zip(window, rope):
        group = "window" if w else "full"
        out.append((group, seen.get(group, 0), bool(w), bool(r)))
        seen[group] = seen.get(group, 0) + 1
    return out


def _sizes(config):
    return dict(heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"],
                eps=float(config["rms_norm_eps"]),
                theta=float(config["rope_theta"]))


# --- attention ---------------------------------------------------------------


def _qkv(h, w, pos, heads, kv_heads, theta, roped, bits):
    """h (n, D) at positions ``pos`` -> q (n, H, d), k, v (n, KV, d)."""
    n = h.shape[0]
    d = w["wq"].shape[-1] // heads
    h = _act(h, bits)
    q = (h @ _weight(w["wq"], bits)).reshape(n, heads, d)
    k = (h @ _weight(w["wk"], bits)).reshape(n, kv_heads, d)
    v = (h @ _weight(w["wv"], bits)).reshape(n, kv_heads, d)
    if roped:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    return q, _act(k, bits), _act(v, bits)


def _attend(q, q_pos, k, v, k_pos, window):
    """q (n, H, d) at ``q_pos`` against every key k, v (T, KV, d) at
    ``k_pos``: key j is seen by query i where k_pos[j] <= q_pos[i] and,
    with a ``window``, k_pos[j] > q_pos[i] - window."""
    n, H, d = q.shape
    KV = k.shape[1]
    seen = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] >= 0)
    if window:
        seen &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.einsum("ngqd,tgd->gqnt", q.reshape(n, KV, H // KV, d), k) / np.sqrt(d)
    s = jnp.where(seen[None, None], s, -jnp.inf)
    o = jnp.einsum("gqnt,tgd->ngqd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(n, H * d)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "theta", "roped", "window", "bits"))
def _attention(x, w, *, heads, kv_heads, eps, theta, roped, window, bits):
    """x (T, D) -> (x + attention(rmsnorm(x)), k, v): one sequence, the
    queries a block at a time under the whole mask: against every key,
    or, with a ``window`` shorter than the sequence, against the keys
    from a window before the block's first query to its last (the
    others are masked for every query of the block; a position before
    the sequence's start is no key)."""
    T = x.shape[0]
    pos = jnp.arange(T)
    q, k, v = _qkv(_rmsnorm(x, w["attn_norm_scale"], eps), w, pos, heads,
                   kv_heads, theta, roped, bits)
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
    o = o.reshape(blocks * Q_BLOCK, -1)[:T]
    return x + _act(o, bits) @ _weight(w["wo"], bits), k, v


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "theta", "roped", "window"))
def _attention_at(xv, at, k, v, w, *, heads, kv_heads, eps, theta, roped, window):
    """Attention for single tokens whose residual is not the sequence's
    own: token ``xv[j, r]`` sits at position ``at[j]`` and attends to the
    sequence's keys and values BEFORE it (``k``, ``v`` of the first
    pass) and to its own."""
    J, R, D = xv.shape
    T = k.shape[0]
    p = jnp.repeat(at, R)
    q, k_own, v_own = _qkv(_rmsnorm(xv.reshape(J * R, D), w["attn_norm_scale"], eps),
                           w, p, heads, kv_heads, theta, roped, 0)
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
    o = o.reshape(blocks * AT_BLOCK, H * d)[:n]
    return xv + (o @ w["wo"].astype(F32)).reshape(J, R, D)


# --- the router and the experts ----------------------------------------------


@functools.partial(jax.jit, static_argnames=("k",))
def _route(x, w_router, flip, *, k):
    """(gate (..., E): the chosen experts' weights, zero elsewhere;
    margin (...)) from the layer's INPUT ``x``. Where ``flip`` (...) is
    set the k-th chosen expert gives way to the (k+1)-th."""
    r = x @ w_router.astype(F32)
    top, idx = jax.lax.top_k(r, k + 1)
    last = jnp.where(jnp.broadcast_to(flip, r.shape[:-1]), k, k - 1)[..., None]
    idx_k = jnp.concatenate([idx[..., :k - 1], jnp.take_along_axis(idx, last, -1)], -1)
    g = jax.nn.softmax(jnp.take_along_axis(r, idx_k, -1), axis=-1)
    gate = jnp.sum(jax.nn.one_hot(idx_k, r.shape[-1], dtype=F32) * g[..., None], axis=-2)
    margin = (top[..., k - 1] - top[..., k]) / jnp.std(r, axis=-1)
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
    one = {name: _weight(jax.lax.dynamic_index_in_dim(
        jax.lax.dynamic_index_in_dim(w[name], l, 0, keepdims=False), at, 0,
        keepdims=False), bits) for name in ("w_gate", "w_up", "w_down")}
    g = jnp.take(gate, e, axis=-1)
    order = jnp.argsort(g <= 0, stable=True)     # the tokens sent here first
    sent = order[:room]
    rows = _act(h[sent], bits)
    act = jax.nn.relu(rows @ one["w_gate"]) * (rows @ one["w_up"])
    out = g[sent][:, None] * (_act(act, bits) @ one["w_down"])
    place = jnp.argsort(order)                   # where each token went
    out = jnp.concatenate([out, jnp.zeros((1, out.shape[1]), out.dtype)])
    return x + out[jnp.minimum(place, room)]


def _experts(config, params, l, x, gate, bits):
    """x (N, D) + the routed experts of layer ``l`` under ``gate``."""
    E = config["moe_num_primary_experts"]
    lo, hi = config.get("experts_held") or (0, E)
    w = params["sparse"]
    h = _rmsnorm(x, w["mlp_norm_scale"][l], float(config["rms_norm_eps"]))
    sent = np.asarray(jnp.sum(gate > 0, axis=0))
    # a few sizes of program: the fullest expert's tokens, rounded up
    room = min(x.shape[0], int(2 ** np.ceil(np.log2(max(int(sent.max()), 1)))))
    stacks = {name: w[name] for name in ("w_gate", "w_up", "w_down")}
    for e in range(lo, hi):
        if sent[e]:
            x = _add_expert(x, h, gate, stacks, l, e, e - lo, bits=bits, room=room)
    return x


# --- the forward pass --------------------------------------------------------


def _layer(params, group, index):
    return {name: w[index] for name, w in params[group].items()}


def _hidden(params, config, tokens, *, control_bits=0, window=True):
    """One sequence ``tokens`` (T,): (the last layer's residual (T, D),
    each layer's (k, v), each layer's margins (T,))."""
    a = _sizes(config)
    k_top = config["moe_num_active_primary_experts"]
    W = int(config["sliding_window_size"]) if window else 0
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    kvs, margins = [], []
    for l, (group, index, windowed, roped) in enumerate(layout(config)):
        gate, margin = _route(x, params["route"]["w_router"][l], False, k=k_top)
        x, k, v = _attention(x, _layer(params, group, index), **a, roped=roped,
                             window=W if windowed else 0, bits=control_bits)
        x = _experts(config, params, l, x, gate, control_bits)
        kvs.append((k, v))
        margins.append(margin)
    return x, kvs, margins


def _head(params, config, x):
    x = _rmsnorm(x, params["final_norm_scale"], float(config["rms_norm_eps"]))
    if "lm_head" not in params:
        return x @ params["embed"].T.astype(F32)
    # the head a column block at a time: whole, its float32 copy is 1.5 GB
    V = params["lm_head"].shape[1]
    step = -(-V // V_BLOCKS)
    return jnp.concatenate([x @ params["lm_head"][:, i:i + step].astype(F32)
                            for i in range(0, V, step)], axis=-1)


def forward(params, config, tokens, *, control_bits=0, window=True):
    """Float32 logits (B, T, V) of ``tokens`` (B, T) under float32's
    own routing: what the tests compare the served path with."""
    with jax.default_matmul_precision("highest"):
        out = []
        for row in np.asarray(tokens):
            x, _, _ = _hidden(params, config, jnp.asarray(row, jnp.int32),
                              control_bits=control_bits, window=window)
            out.append(np.asarray(_head(params, config, x)))
        return np.stack(out)


def flipped_layers(margins, allowed):
    """(flips (J, R, S) bool, valid (J, R) bool) from a sequence's
    judged tokens' margins (J, S) along float32's own routing: routing r
    flips the token's i-th tightest layer, of those under ``allowed``,
    where bit i of r is set; a routing that names a layer the token does
    not have is not valid."""
    J, S = margins.shape
    n = min(MAX_FLIPPED, S)
    order = np.argsort(margins, axis=-1, kind="stable")[..., :n]     # (J, n)
    tight = np.take_along_axis(margins, order, -1) < allowed
    bits = (np.arange(2 ** n)[:, None] >> np.arange(n)[None, :]) & 1  # (R, n)
    flips = np.zeros((J, 2 ** n, S), bool)
    chosen = bits[None].astype(bool) & tight[:, None, :]              # (J, R, n)
    np.put_along_axis(flips, np.broadcast_to(order[:, None, :], chosen.shape),
                      chosen, axis=-1)
    valid = ~(bits[None].astype(bool) & ~tight[:, None, :]).any(-1)
    return flips, valid


def _judged_row(params, config, tokens, judge, control_bits, routings, window):
    """One sequence: (logits (J, R, V), flip_margin (J, R), margin (J,))."""
    a = _sizes(config)
    k_top = config["moe_num_active_primary_experts"]
    W = int(config["sliding_window_size"]) if window else 0
    x, kvs, margins = _hidden(params, config, tokens, control_bits=control_bits,
                              window=window)
    own = np.stack([np.asarray(m[judge]) for m in margins], -1)       # (J, S)
    margin = own.min(-1)
    if control_bits or not routings:
        logits = np.asarray(_head(params, config, x[judge]))[:, None]
        return logits, np.zeros(judge.shape + (1,), np.float32), margin
    allowed = float(config.get("tolerance", {}).get("routing_margin", 0.0))
    flips, valid = flipped_layers(own, allowed)
    flips = jnp.asarray(flips)
    flip_margin = jnp.zeros(valid.shape, F32)
    x0 = jnp.take(params["embed"], tokens[judge], axis=0).astype(F32)
    xv = jnp.broadcast_to(x0[:, None], valid.shape + x0.shape[-1:])   # (J, R, D)
    for l, ((group, index, windowed, roped), (k, v)) in enumerate(
            zip(layout(config), kvs)):
        gate, m = _route(xv, params["route"]["w_router"][l], flips[..., l], k=k_top)
        flip_margin = jnp.maximum(flip_margin, jnp.where(flips[..., l], m, 0.0))
        xv = _attention_at(xv, judge, k, v, _layer(params, group, index), **a,
                           roped=roped, window=W if windowed else 0)
        J, R, D = xv.shape
        xv = _experts(config, params, l, xv.reshape(J * R, D),
                      gate.reshape(J * R, -1), 0).reshape(J, R, D)
    flip_margin = np.where(valid, np.asarray(flip_margin), np.inf)
    logits = np.stack([np.asarray(_head(params, config, xv[:, r]))
                       for r in range(xv.shape[1])], axis=1)
    return logits, flip_margin, margin


def judged_logits(params, config, tokens, judge, *, control_bits=0,
                  routings=True, window=True):
    """Float32 logits of ``tokens`` (B, T) at the positions ``judge``
    (B, J): (logits (B, J, R, V), flip_margin (B, J, R), margin (B, J)),
    the shape ``harness/probe.py::against`` reads. R is 1 for the
    control and without ``routings``, else 2^min(MAX_FLIPPED, layers)
    (module docstring). ``margin``: the judged token's smallest router
    margin over the layers. Positions past a row's own length are
    padding: a causal model keeps them out of every judged position
    before them."""
    out = []
    with jax.default_matmul_precision("highest"):
        for row, at in zip(np.asarray(tokens), np.asarray(judge)):
            out.append(_judged_row(
                params, config, jnp.asarray(row, jnp.int32),
                jnp.asarray(at, jnp.int32), control_bits, routings, window))
    return tuple(np.stack(part) for part in zip(*out))
