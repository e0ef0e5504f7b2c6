"""The plain reference for Qwen3-Next (Qwen/Qwen3-Next-80B-A3B-Instruct,
``model_type: qwen3_next``; Hugging Face ``Qwen3Next*``): the forward
pass in straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision. No cache, no kernels, no batching of the mixers (one row at
a time), the recurrence TOKEN BY TOKEN (never a chunk form), no
grouping of tokens by expert, nothing imported from the program. One
layer's weights (of the sparse block: one expert's) are upcast at a
time, the head in blocks of positions, so that it fits beside the
served model.

  norm(x)   = x * rsqrt(mean(x^2) + eps) * (1 + w)       zero-centred scale
  x = embed[tokens]
  layer i:  x += mixer(norm_1(x)) ; x += moe(norm_2(x))
            mixer: full_attention where (i + 1) % full_attention_interval == 0,
                   else linear_attention
  logits = norm(x) W_head                                 (an untied head)

  ``linear_attention`` (Gated DeltaNet), Hk key heads of dk, H value heads of dv:
    [q' | k' | v' | z] = h W_qkvz            (Hk dk, Hk dk, H dv, H dv columns)
    [q' | k' | v'] through ONE depthwise causal convolution:
      c_t = sum_{j=0..L-1} w[j] * u_{t-(L-1)+j}   L = linear_conv_kernel_dim taps,
            inputs before the start are 0, no bias ; then SiLU
    q = l2norm(q) * dk^-0.5 ; k = l2norm(k)  a head, x * rsqrt(sum x^2 + 1e-6)
    key head j is the q and k of the value heads j H / Hk .. (j + 1) H / Hk - 1
    [b | a] = h W_ba                         (H, H columns)
    beta = sigmoid(b) ; g = -exp(A_log) * softplus(a + dt_bias)
    a value head's state S (dk, dv), S_{-1} = 0, a token:
      S <- exp(g) S ; u = beta (v - S^T k) ; S <- S + k u^T ; o = S^T q
    mixer = (o * rsqrt(mean_dv(o^2) + eps) * w_o_norm * silu(z)) W_o   (scales by w)

  ``full_attention``, H query heads and KV key/value heads of d:
    a head's 2 d columns of h Wq: its query, then its output gate
    q = norm_d(q) ; k = norm_d(h Wk)         a head, the (1 + w) norm ; v = h Wv
    rope on the first d * partial_rotary_factor channels of a head
      (rotate_half pairing inside them), the rest pass
    o = softmax(causal(q k^T / sqrt(d))) v ; mixer = (o * sigmoid(gate)) W_o

  the sparse block:
    p = softmax(h W_r) over all router outputs ; sel = the k largest
      (the lower index first among equals)
    weight_e = p_e / sum_{sel} p               (norm_topk_prob)
    moe = sum_{e in sel} weight_e (silu(h Wg_e) * (h Wu_e)) Wd_e
          + sigmoid(h w_sg) * (silu(h Wg_s) * (h Wu_s)) Wd_s     one shared expert

It reads sizes from the configuration FILE (the published key names)
and weights from the arrays it is handed, under the program's names:
groups ``gdn`` (attn_norm_w, w_qkvz, conv_w (L, channels), w_gates,
dt_bias, A_log, o_norm_scale, wo), ``attn`` (attn_norm_w, wq, wk, wv,
q_norm_w, k_norm_w, wo) and ``sparse`` (mlp_norm_w, w_router, w_gate,
w_up, w_down, ``shared`` (w_gate, w_up, w_down), w_shared_gate), each
stacked over the layers of its kind in layer order.

The chip's share: ``experts_held`` [lo, hi) (absent: every expert) is
the range of the router's ``router_outputs`` (absent: ``num_experts``)
whose experts exist here; what the others would add is left out. The
vocabulary is whatever the head holds.

Departures, noted: the published checkpoint interleaves ``W_qkvz`` and
``W_ba`` a key head, here they are plain column blocks; the convolution
is the explicit sum over its taps; the multi-token-prediction module is
left out.

Sparse layers and ``correct`` (``references/lfm2_moe.py`` has the
argument): for each judged token, float32's own routing (routing 0) and
the routings that give up the k-th chosen expert for the (k+1)-th in
every subset of that token's at most ``MAX_FLIPPED`` tightest layers
whose margin is under the file's ``tolerance.routing_margin``. The
margin is the distance between the k-th and the (k+1)-th router logit
as a share of the token's spread of router logits, and counts only
where one of the two experts is HELD here: a flip between two absent
experts moves nothing this chip computes but the renormalisation, by
less than the margin. Tokens a judged token attends to, convolves over
or holds in its state keep float32's routing.

``control_bits``: the lower-precision control (``references/decoder.py``
has the same): every matmul weight rounded per output column, every
matmul input per token, K and V per token and head, and the recurrent
layers' q, k and v per token and head, to that many bits; norms, the
taps, the gates, the state, the router, the embedding and the head
stay float32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MAX_FLIPPED = 6   # layers of a judged token that may go the other way
POSITIONS = 256   # positions a block of the head


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


def _norm(x, w, eps):
    """The zero-centred RMSNorm: scales by 1 + w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        1.0 + w.astype(F32))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _rope(x, positions, theta, rot):
    """x (n, heads, d): the first ``rot`` channels rotated (rotate_half
    inside them), the rest passed."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    xr, rest = x[..., :rot], x[..., rot:]
    half = rot // 2
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + turned * sin, rest], -1)


def layout(config):
    """[(mixer group, index in the mixer's stack)] a layer; the sparse
    block's index is the layer's own."""
    out, seen = [], {}
    every = config.get("full_attention_interval", 4)
    for i in range(config["num_hidden_layers"]):
        mixer = "attn" if (i + 1) % every == 0 else "gdn"
        out.append((mixer, seen.get(mixer, 0)))
        seen[mixer] = seen.get(mixer, 0) + 1
    return out


def _sizes(config):
    d = config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]
    return dict(
        gdn=(config["linear_num_key_heads"], config["linear_num_value_heads"],
             config["linear_key_head_dim"], config["linear_value_head_dim"]),
        attn=(config["num_attention_heads"], config["num_key_value_heads"], d,
              int(d * config.get("partial_rotary_factor", 1.0)),
              float(config.get("rope_theta", 1e7))),
        eps=float(config.get("rms_norm_eps", 1e-6)))


# --- the Gated DeltaNet layer, for whole rows and for single tokens ----------


def _gdn_tokens(c, gates, w, heads, bits):
    """From the convolution's output c (n, channels) and the gates'
    inputs (n, 2 H): (q, k (n, H, dk) a VALUE head, v (n, H, dv), g, beta
    (n, H))."""
    Hk, H, dk, dv = heads
    n = c.shape[0]
    q, k, v = jnp.split(jax.nn.silu(c), (Hk * dk, 2 * Hk * dk), axis=-1)
    q = _act(_l2norm(q.reshape(n, Hk, dk)) * dk ** -0.5, bits)
    k = _act(_l2norm(k.reshape(n, Hk, dk)), bits)
    q, k = (jnp.repeat(x, H // Hk, axis=1) for x in (q, k))
    v = _act(v.reshape(n, H, dv), bits)
    beta = jax.nn.sigmoid(gates[:, :H])
    g = -jnp.exp(w["A_log"].astype(F32)) * jax.nn.softplus(
        gates[:, H:] + w["dt_bias"].astype(F32))
    return q, k, v, g, beta


def _delta_token(S, q, k, v, g, beta):
    """One token of the rule over S (H, dk, dv): -> (S, o (H, dv))."""
    S = S * jnp.exp(g)[:, None, None]
    u = beta[:, None] * (v - jnp.einsum("hde,hd->he", S, k))
    S = S + k[:, :, None] * u[:, None, :]
    return S, jnp.einsum("hde,hd->he", S, q)


def _gdn_out(o, z, w, eps, bits):
    """(n, H, dv) -> the mixer's output (n, D)."""
    n = o.shape[0]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * w["o_norm_scale"].astype(F32) * jax.nn.silu(z).reshape(o.shape)
    return _act(o.reshape(n, -1), bits) @ _weight(w["wo"], bits)


def _gdn_project(h, w, heads, bits):
    """Normed tokens (n, D) -> (the convolution's inputs (n, channels),
    z (n, H dv), the gates' inputs (n, 2 H))."""
    Hk, H, dk, dv = heads
    proj = _act(h, bits) @ _weight(w["w_qkvz"], bits)
    channels = 2 * Hk * dk + H * dv
    return proj[:, :channels], proj[:, channels:], h @ w["w_gates"].astype(F32)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "bits"))
def _gdn(x, at, w, *, heads, eps, bits):
    """x (T, D) -> (x + mixer(norm(x)), the state BEFORE each position
    ``at[j]`` (J, H, dk, dv)), the state stepped a token at a time from
    zeros."""
    Hk, H, dk, dv = heads
    T = x.shape[0]
    pre, z, gates = _gdn_project(_norm(x, w["attn_norm_w"], eps), w, heads, bits)
    taps = w["conv_w"].astype(F32)                           # (L, channels)
    L = taps.shape[0]
    past = jnp.pad(pre, ((L - 1, 0), (0, 0)))
    c = sum(taps[j] * past[j:j + T] for j in range(L))
    tokens = _gdn_tokens(c, gates, w, heads, bits)

    def token(carry, t):
        S, kept = carry
        i, rest = t[0], t[1:]
        kept = jnp.where((at == i)[:, None, None, None], S[None], kept)
        S, o = _delta_token(S, *rest)
        return (S, kept), o

    zeros = jnp.zeros((H, dk, dv), F32)
    (_, kept), o = jax.lax.scan(
        token, (zeros, jnp.zeros(at.shape + zeros.shape, F32)),
        (jnp.arange(T),) + tokens)
    return x + _gdn_out(o, z, w, eps, bits), kept


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _gdn_at(x, xv, at, kept, w, *, heads, eps):
    """The same for single tokens whose residual is not the row's own:
    token ``xv[j, r]`` sits at position ``at[j]`` of the row ``x``,
    convolves over the row's own inputs before it and reads the row's
    own state ``kept[j]`` stepped once by ITS token: with a = exp(g),
    ``u = beta (v - a S^T k)`` and ``o = (a S + k u^T)^T q = a S^T q +
    (k . q) u`` (the state it would leave is nobody's to read)."""
    J, R, D = xv.shape
    pre, _, _ = _gdn_project(_norm(x, w["attn_norm_w"], eps), w, heads, 0)
    own, z, gates = _gdn_project(
        _norm(xv.reshape(J * R, D), w["attn_norm_w"], eps), w, heads, 0)
    taps = w["conv_w"].astype(F32)
    L = taps.shape[0]
    c = taps[L - 1] * own.reshape(J, R, -1)
    for back in range(1, L):
        prev = jnp.where((at >= back)[:, None], pre[jnp.maximum(at - back, 0)], 0.0)
        c = c + taps[L - 1 - back] * prev[:, None]
    q, k, v, g, beta = (t.reshape((J, R) + t.shape[1:]) for t in _gdn_tokens(
        c.reshape(J * R, -1), gates, w, heads, 0))
    a = jnp.exp(g)[..., None]                                # (J, R, H, 1)
    u = beta[..., None] * (v - a * jnp.einsum("jhde,jrhd->jrhe", kept, k))
    o = a * jnp.einsum("jhde,jrhd->jrhe", kept, q) + jnp.sum(
        k * q, axis=-1, keepdims=True) * u
    return xv + _gdn_out(o.reshape((J * R,) + o.shape[2:]), z, w, eps, 0).reshape(J, R, D)


# --- the gated softmax layer -------------------------------------------------


def _qkv(h, w, pos, sizes, eps, bits):
    """Normed tokens (n, D) at ``pos`` -> (q, gate, k, v), each (n, H,
    d): K and V repeated to their query heads."""
    H, KV, d, rot, theta = sizes
    n = h.shape[0]
    h = _act(h, bits)
    qg = (h @ _weight(w["wq"], bits)).reshape(n, H, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    q = _rope(_norm(q, w["q_norm_w"], eps), pos, theta, rot)
    k = _norm((h @ _weight(w["wk"], bits)).reshape(n, KV, d), w["k_norm_w"], eps)
    k = _act(_rope(k, pos, theta, rot), bits)
    v = _act((h @ _weight(w["wv"], bits)).reshape(n, KV, d), bits)
    k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
    return q, gate, k, v


@functools.partial(jax.jit, static_argnames=("sizes", "eps", "bits"))
def _attention(x, w, *, sizes, eps, bits):
    """x (T, D) -> x + attention(norm(x))."""
    T = x.shape[0]
    pos = jnp.arange(T)
    q, gate, k, v = _qkv(_norm(x, w["attn_norm_w"], eps), w, pos, sizes, eps, bits)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where((pos[None, :] <= pos[:, None])[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    o = o * jax.nn.sigmoid(gate)
    return x + _act(o.reshape(T, -1), bits) @ _weight(w["wo"], bits)


@functools.partial(jax.jit, static_argnames=("sizes", "eps"))
def _attention_at(x, xv, at, w, *, sizes, eps):
    """Attention for single tokens whose residual is not the row's own
    (see ``_gdn_at``): a token attends to the row's keys and values
    BEFORE it (from ``x``) and to its own."""
    T, D = x.shape
    J, R = xv.shape[:2]
    pos = jnp.arange(T)
    _, _, k, v = _qkv(_norm(x, w["attn_norm_w"], eps), w, pos, sizes, eps, 0)
    p = jnp.repeat(at, R)
    q, gate, k_own, v_own = _qkv(
        _norm(xv.reshape(J * R, D), w["attn_norm_w"], eps), w, p, sizes, eps, 0)
    s = jnp.where((pos[None, :] < p[:, None])[:, None, :],
                  jnp.einsum("nhd,khd->nhk", q, k), -jnp.inf)
    s_own = jnp.einsum("nhd,nhd->nh", q, k_own)
    a = jax.nn.softmax(jnp.concatenate([s, s_own[..., None]], -1)
                       / np.sqrt(q.shape[-1]), axis=-1)
    o = jnp.einsum("nhk,khd->nhd", a[..., :T], v) + a[..., T:] * v_own
    o = o * jax.nn.sigmoid(gate)
    return xv + (o.reshape(J * R, -1) @ w["wo"].astype(F32)).reshape(J, R, D)


# --- the sparse block --------------------------------------------------------


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


@functools.partial(jax.jit, static_argnames=("k", "norm", "held"))
def _route(h, w_router, flip, *, k, norm, held):
    """(gate (..., E): the chosen experts' weights, zero elsewhere;
    margin (...): inf where neither the k-th nor the (k+1)-th expert is
    held). Where ``flip`` (...) is set the k-th chosen expert gives way
    to the (k+1)-th."""
    r = h @ w_router.astype(F32)
    top, idx = jax.lax.top_k(r, k + 1)
    last = jnp.where(jnp.broadcast_to(flip, r.shape[:-1]), k, k - 1)[..., None]
    idx_k = jnp.concatenate([idx[..., :k - 1], jnp.take_along_axis(idx, last, -1)], -1)
    p = jax.nn.softmax(r, axis=-1)
    g = jnp.take_along_axis(p, idx_k, -1)
    if norm:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx_k, r.shape[-1], dtype=F32) * g[..., None], axis=-2)
    lo, hi = held
    edge = idx[..., k - 1:]
    here = jnp.any((edge >= lo) & (edge < hi), axis=-1)
    margin = (top[..., k - 1] - top[..., k]) / jnp.std(r, axis=-1)
    return gate, jnp.where(here, margin, jnp.inf)


@functools.partial(jax.jit, static_argnames=("bits",))
def _shared(h, w, *, bits):
    gate = jax.nn.sigmoid(h @ w["w_shared_gate"].astype(F32))
    s = w["shared"]
    return gate * _glu(h, s["w_gate"], s["w_up"], s["w_down"], bits=bits)


def held_range(config):
    E = config.get("router_outputs", config["num_experts"])
    lo, hi = config.get("experts_held") or (0, E)
    return int(lo), int(hi)


def moe(config, w, l, x, flip=False, *, bits=0, shared=True):
    """x (..., D) -> (moe(norm_2(x)), margin (...)) of sparse layer
    ``l`` of the stacks ``w``: the experts held compute their part,
    the shared expert (unless told to leave it out: the share test
    counts it once) the whole of its own."""
    eps = _sizes(config)["eps"]
    lo, hi = held_range(config)
    h = _norm(x, w["mlp_norm_w"][l], eps)
    gate, margin = _route(h, w["w_router"][l], flip,
                          k=config["num_experts_per_tok"],
                          norm=bool(config.get("norm_topk_prob", True)),
                          held=(lo, hi))
    out = jnp.zeros_like(x)
    stacks = {name: w[name] for name in ("w_gate", "w_up", "w_down")}
    for e in range(lo, hi):
        out = _add_expert(out, h, gate, stacks, l, e, e - lo, bits=bits)
    if shared:
        out = out + _shared(h, jax.tree.map(
            lambda a: a[l], {"shared": w["shared"],
                             "w_shared_gate": w["w_shared_gate"]}), bits=bits)
    return out, margin


def sparse_block(config, w, l, x, flip=False, *, bits=0):
    """x (..., D) -> (x + moe(norm_2(x)), margin (...))."""
    out, margin = moe(config, w, l, x, flip, bits=bits)
    return x + out, margin


# --- the forward pass --------------------------------------------------------


def _layer(params, group, index):
    return {name: w[index] for name, w in params[group].items()}


def _hidden(params, config, tokens, *, control_bits=0):
    """(the last layer's residual (B, T, D), each layer's INPUT
    residual, each layer's margins (B, T))."""
    a = _sizes(config)
    B = tokens.shape[0]
    at = jnp.zeros((B, 0), jnp.int32)   # no state is kept on this pass
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    inputs, margins = [], []
    for l, (mixer, mi) in enumerate(layout(config)):
        inputs.append(x)
        w = _layer(params, mixer, mi)
        if mixer == "gdn":
            x = jnp.stack([_gdn(x[b], at[b], w, heads=a["gdn"], eps=a["eps"],
                                bits=control_bits)[0] for b in range(B)])
        else:
            x = jnp.stack([_attention(x[b], w, sizes=a["attn"], eps=a["eps"],
                                      bits=control_bits) for b in range(B)])
        x, m = sparse_block(config, params["sparse"], l, x, bits=control_bits)
        margins.append(m)
    return x, inputs, margins


def _head(params, config, x):
    x = _norm(x, params["final_norm_w"], _sizes(config)["eps"])
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return x @ head.astype(F32)


def forward(params, config, tokens, *, control_bits=0):
    """Float32 logits (B, T, V) of ``tokens`` (B, T) under float32's
    own routing: what the tests compare the served path with. The head
    in blocks of positions."""
    with jax.default_matmul_precision("highest"):
        x, _, _ = _hidden(params, config, jnp.asarray(tokens, jnp.int32),
                          control_bits=control_bits)
        return np.stack([np.concatenate(
            [np.asarray(_head(params, config, row[lo:lo + POSITIONS]))
             for lo in range(0, row.shape[0], POSITIONS)]) for row in x])


def flipped_layers(margins, allowed):
    """(flips (B, J, R, S) bool, valid (B, J, R) bool) from a judged
    token's margins (B, J, S) along float32's own routing
    (``references/lfm2_moe.py::flipped_layers``)."""
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
    control and without ``routings``, else 2^min(MAX_FLIPPED, layers)
    (module docstring). ``margin``: the judged token's smallest router
    margin over the layers. Positions past a row's own length are
    padding: a causal model keeps them out of every judged position
    before them."""
    tokens = jnp.asarray(tokens, jnp.int32)
    judge = jnp.asarray(judge, jnp.int32)
    B = tokens.shape[0]
    rows = jnp.arange(B)[:, None]
    a = _sizes(config)
    with jax.default_matmul_precision("highest"):
        x, inputs, margins = _hidden(params, config, tokens, control_bits=control_bits)
        own = np.stack([np.asarray(m[rows, judge]) for m in margins], -1)   # (B, J, S)
        margin = own.min(-1)
        if control_bits or not routings:
            logits = np.asarray(_head(params, config, x[rows, judge]))[:, :, None]
            return logits, np.zeros(judge.shape + (1,), np.float32), margin
        allowed = float(config.get("tolerance", {}).get("routing_margin", 0.0))
        flips, valid = flipped_layers(own, allowed)
        flips = jnp.asarray(flips)
        flip_margin = jnp.zeros(valid.shape, F32)
        xv = jnp.broadcast_to(inputs[0][rows, judge][:, :, None],
                              valid.shape + x.shape[-1:])
        for l, ((mixer, mi), x_in) in enumerate(zip(layout(config), inputs)):
            w = _layer(params, mixer, mi)
            if mixer == "gdn":
                # the row's own pass again, for the states it held at
                # the judged positions: 2 MB each, kept a layer at a time
                xv = jnp.stack([_gdn_at(
                    x_in[b], xv[b], judge[b],
                    _gdn(x_in[b], judge[b], w, heads=a["gdn"], eps=a["eps"],
                         bits=0)[1], w, heads=a["gdn"], eps=a["eps"])
                    for b in range(B)])
            else:
                xv = jnp.stack([_attention_at(
                    x_in[b], xv[b], judge[b], w, sizes=a["attn"], eps=a["eps"])
                    for b in range(B)])
            xv, m = sparse_block(config, params["sparse"], l, xv, flips[..., l])
            flip_margin = jnp.maximum(flip_margin, jnp.where(flips[..., l], m, 0.0))
        flip_margin = np.where(valid, np.asarray(flip_margin), np.inf)
        # one routing at a time: all of them at once are GBs of logits
        logits = np.stack([np.asarray(_head(params, config, xv[:, :, r]))
                           for r in range(xv.shape[2])], axis=2)
    return logits, flip_margin, margin
