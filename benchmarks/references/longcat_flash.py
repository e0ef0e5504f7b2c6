"""The plain reference for LongCat-Flash (meituan-longcat/LongCat-Flash-Chat,
``model_type: longcat_flash``): the forward pass in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision, attention in
its EXPANDED form (a key and a value a head, made from the compressed
line), full causal attention. No cache, no pages, no kernels, no
absorbed weights, no grouping of tokens by expert, nothing imported
from the program. One sequence at a time; what is per token (norms,
FFNs, the router, the experts) in blocks of tokens, a weight matrix
upcast a block of columns at a time, attention a group of heads and a
block of queries at a time, so that a 16 k prompt at the published
widths fits beside the served model and its pool.

  x = embed[tokens]                     norm(x) = x rsqrt(mean(x^2) + eps) w,  eps 1e-5
  per layer (its own eight norm scales, attn_0, attn_1, ffn_0, ffn_1, moe):
    x <- x + attn_0(norm_a0(x))
    h  = norm_f0(x)
    s  = moe(h)                         # the shortcut: read here ...
    x <- x + ffn_0(h)
    x <- x + attn_1(norm_a1(x))
    x <- x + ffn_1(norm_f1(x)) + s      # ... added here
  attn_j(h):  c_q = norm(h W_qa) ; q = f_q (c_q W_qb)       H heads of [q_nope | q_rope]
              [c_raw | kr_raw] = h W_kva ; c = f_kv norm(c_raw) ; kr = rope(kr_raw)
              f_q = sqrt(hidden / q_lora_rank), f_kv = sqrt(hidden / kv_lora_rank)
              where ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` are true (2 and
              sqrt(12) as published), else 1; kr carries no factor
              a head: [k_nope_h | v_h] = c W_kvb  (head h's columns)
              score_h = (q_nope_h . k_nope_h + rope(q_rope_h) . kr) (nope + rope)^-0.5
              out = concat_h(softmax(causal(score_h)) v_h) W_o
              rope: on the rope channels only, plain, theta^(-2i/d), theta 1e7
  ffn_j(h):   (silu(h W_g) * (h W_u)) W_d                   width ffn_hidden_size
  moe(h):     p = softmax(h W_r)        all router outputs (experts, then
                                        zero_expert_num identity outputs), float32
              sel = the moe_topk largest of p + offset       (lower index first)
              g_e = p_e * routed_scaling_factor              not renormalised
              moe = sum_{e in sel, e an expert held} g_e E_e(h)
                    + h * sum_{e in sel, e an identity output} g_e
              E_e a SiLU GLU of expert_ffn_hidden_size
  logits = norm(x) W_head                                    (untied)

It reads sizes from the configuration FILE (the published key names)
and weights from the arrays it is handed, under the program's names:
groups ``mla0`` / ``mla1`` (attn_norm_scale, w_qa, q_norm_scale, w_qb,
w_kva, kv_norm_scale, w_kvb, wo), ``ffn0`` / ``ffn1`` (mlp_norm_scale,
w_gate, w_up, w_down) and ``sparse`` (w_router,
e_score_correction_bias: the selection offset; w_gate, w_up, w_down by
expert held), each stacked by layer.

Departures from the published description, each on purpose:

* THE SHARE OF EXPERTS. ``experts_held`` [lo, hi) (absent: every
  expert; the file then counts them in ``n_routed_experts`` and gives
  the router's width, identity outputs included, as ``router_outputs``)
  is the range of the router's outputs whose experts exist here: one
  chip's share of an expert-parallel stage. The router chooses over ALL
  its outputs; what the absent experts would add is left out, here as
  in the program; the identity outputs' part is the token's own chip's
  and is kept whole.
* the multi-token-prediction module of the release is not in the
  published config and is no part of this forward pass.
* rotary angles use the half-split layout (``rotate_half``) on the rope
  channels; the checkpoint pairs adjacent channels, the same model
  under a permutation of those columns of W_qb and W_kva.

Sparse layers and ``correct``: a token whose choice is nearly level is
sent the other way by any rounding difference (``references/
decoder.py`` has the argument). A layer makes ONE choice that can be
level: which output is the last chosen (the k-th or the (k+1)-th of
``p + offset``); its ``margin`` is their distance as a share of the
spread (standard deviation) of the token's ``p + offset`` over all the
router's outputs. A layer COUNTS only where one of the two is an expert
held or an identity output: two absent experts add nothing either way.
The rule is BOUNDED as ``references/deepseek_v3.py``'s: for each judged
token, float32's own routing (routing 0) and the routings that take the
other side in every subset of that token's at most ``MAX_FLIPPED``
tightest layers that count whose margin, along float32's own routing,
is under the file's ``tolerance.routing_margin``: at most 16 routings,
at any depth. Tokens a judged token attends to keep float32's routing.

``control_bits``: the lower-precision control: every matmul weight
rounded per output column (of the block of columns it is upcast by),
every matmul input per token, and the cached line (``c`` and ``kr``,
each per token) to that many bits; norms, the router, the embedding and
the head stay float32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MAX_FLIPPED = 4    # layers of a judged token that may go the other way
HEAD_GROUP = 8     # heads computed at a time
QUERY_BLOCK = 128  # queries a block of one head group's attention
TOKEN_BLOCK = 2048  # tokens a block of what is per token
F_BLOCK = 2048     # columns of an FFN's width upcast at a time


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


# --- sizes, from the file ----------------------------------------------------


def _sizes(config):
    """A hashable tuple of (name, value): the static sizes."""
    D, nope, dr = config["hidden_size"], config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    zero = int(config.get("zero_expert_num", 0))
    experts = (config["router_outputs"] - zero if "router_outputs" in config
               else config["n_routed_experts"])
    held = tuple(config.get("experts_held") or (0, experts))
    theta = float(config.get("rope_theta", 1e7))
    return tuple(sorted(dict(
        heads=config["num_attention_heads"], nope=nope, dr=dr,
        dv=config["v_head_dim"], rank=config["kv_lora_rank"],
        eps=float(config.get("rms_norm_eps", 1e-5)),
        scale=(nope + dr) ** -0.5,
        f_q=(D / config["q_lora_rank"]) ** 0.5 if config.get("mla_scale_q_lora") else 1.0,
        f_kv=(D / config["kv_lora_rank"]) ** 0.5 if config.get("mla_scale_kv_lora") else 1.0,
        inv_freq=tuple(theta ** -(np.arange(0, dr, 2, dtype=np.float64) / dr)),
        k=config["moe_topk"], experts=experts, lo=held[0], hi=held[1],
        scaling=float(config.get("routed_scaling_factor", 1.0)),
        norm=bool(config.get("norm_topk_prob", False)),
    ).items()))


def _rope(x, positions, a):
    """x (T, ..., dr) at ``positions`` (T,); rotate_half convention."""
    inv = jnp.asarray(a["inv_freq"], F32)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1).reshape(shape)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1).reshape(shape)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _at(w, l):
    """Layer ``l`` (traced) of every stacked leaf of a group."""
    return {name: jax.lax.dynamic_index_in_dim(v, l, 0, keepdims=False)
            for name, v in w.items()}


# --- latent attention, expanded ----------------------------------------------


def _line(h, w, pos, a, bits):
    """(c (n, rank), kr (n, dr)) of normed inputs h (n, D) at ``pos``:
    what a cache would keep of each token, the factor on c included."""
    raw = _act(h, bits) @ _weight(w["w_kva"], bits)
    c = a["f_kv"] * _rmsnorm(raw[:, :a["rank"]], w["kv_norm_scale"], a["eps"])
    return _act(c, bits), _act(_rope(raw[:, a["rank"]:], pos, a), bits)


def _query_latent(h, w, a, bits):
    return _act(_rmsnorm(_act(h, bits) @ _weight(w["w_qa"], bits),
                         w["q_norm_scale"], a["eps"]), bits)


@functools.partial(jax.jit, static_argnames=("a", "bits"))
def _latents(x, w, l, *, a, bits):
    """x (T, D) -> (c_q (T, q_lora), c (T, rank), kr (T, dr)) of layer
    ``l``'s attention sublayer ``w``, in blocks of tokens."""
    a, w = dict(a), _at(w, l)
    T = x.shape[0]
    B = TOKEN_BLOCK if T % TOKEN_BLOCK == 0 else T

    def block(args):
        xb, pos = args
        h = _rmsnorm(xb, w["attn_norm_scale"], a["eps"])
        return (_query_latent(h, w, a, bits), *_line(h, w, pos, a, bits))

    out = jax.lax.map(block, (x.reshape(T // B, B, -1),
                              jnp.arange(T).reshape(T // B, B)))
    return tuple(y.reshape(T, -1) for y in out)


def _head_group(w, g, a):
    """Head group g's columns of W_qb (q_lora, G, nope + dr) and W_kvb
    (rank, G, nope + dv), and its rows of W_o (G * dv, D)."""
    G = min(HEAD_GROUP, a["heads"])
    wq = w["w_qb"].reshape(w["w_qb"].shape[0], a["heads"], -1)
    wkv = w["w_kvb"].reshape(a["rank"], a["heads"], -1)
    wo = w["wo"].reshape(a["heads"], a["dv"], -1)
    take = functools.partial(jax.lax.dynamic_slice_in_dim, start_index=g * G,
                             slice_size=G)
    return take(wq, axis=1), take(wkv, axis=1), take(wo, axis=0)


def _expand(cq, c, wq, wkv, pos, a, bits):
    """One head group's (q_nope, q_rope, k_nope, v) from the query
    latents cq (n, q_lora) at ``pos`` and the lines' c (m, rank)."""
    G = wq.shape[1]
    q = a["f_q"] * (cq @ _weight(wq.reshape(wq.shape[0], -1), bits))
    q = q.reshape(-1, G, a["nope"] + a["dr"])
    kv = (c @ _weight(wkv.reshape(wkv.shape[0], -1), bits)).reshape(-1, G, a["nope"] + a["dv"])
    return (q[..., :a["nope"]], _rope(q[..., a["nope"]:], pos, a),
            kv[..., :a["nope"]], kv[..., a["nope"]:])


@functools.partial(jax.jit, static_argnames=("a", "bits"), donate_argnums=(0,))
def _attend_group(x, cq, c, kr, w, l, g, *, a, bits):
    """x + head group g's part of the attention output, (T, D): causal
    softmax over the whole row, queries a block at a time."""
    a = dict(a)
    T = cq.shape[0]
    pos = jnp.arange(T)
    wq, wkv, wo = _head_group(_at(w, l), g, a)
    qn, qr, kn, v = _expand(cq, c, wq, wkv, pos, a, bits)
    B = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def block(args):
        qn_b, qr_b, pos_b = args
        s = (jnp.einsum("qhd,khd->hqk", qn_b, kn)
             + jnp.einsum("qhd,kd->hqk", qr_b, kr)) * a["scale"]
        s = jnp.where((pos[None, :] <= pos_b[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, tuple(
        y.reshape((T // B, B) + y.shape[1:]) for y in (qn, qr, pos)))
    o = _act(o.reshape(T, -1), bits)  # per token and head group
    return x + o @ _weight(wo.reshape(-1, wo.shape[-1]), bits)


def _mla(x, w, l, a, bits):
    """x (T, D) -> (x + attn(norm(x)), the sublayer's lines (c, kr))."""
    cq, c, kr = _latents(x, w, l, a=a, bits=bits)
    for g in range(-(-dict(a)["heads"] // HEAD_GROUP)):
        x = _attend_group(x, cq, c, kr, w, l, g, a=a, bits=bits)
    return x, (c, kr)


@functools.partial(jax.jit, static_argnames=("a",))
def _attend_group_at(c, kr, cq_v, c_v, kr_v, at, w, l, g, *, a):
    """The same for single tokens whose residual is not the row's own
    (another routing upstream): token (j, r) sits at position ``at[j]``,
    attends to the row's lines BEFORE it (c, kr) and to its own
    (c_v, kr_v (J, R, .)). -> head group g's output (J, R, D)."""
    a = dict(a)
    T = c.shape[0]
    pos = jnp.arange(T)
    wq, wkv, wo = _head_group(_at(w, l), g, a)
    _, _, kn, v = _expand(cq_v[0, :1], c, wq, wkv, pos[:1], a, 0)

    def one(args):
        cq_j, c_j, kr_j, p = args                      # (R, .) at position p
        ps = jnp.full((cq_j.shape[0],), p)
        qn, qr, kn_own, v_own = _expand(cq_j, c_j, wq, wkv, ps, a, 0)
        s = (jnp.einsum("rhd,khd->rhk", qn, kn) + jnp.einsum("rhd,kd->rhk", qr, kr))
        s = jnp.where((pos < p)[None, None, :], s, -jnp.inf)
        s_own = jnp.einsum("rhd,rhd->rh", qn, kn_own) + jnp.einsum("rhd,rd->rh", qr, kr_j)
        p_all = jax.nn.softmax(
            jnp.concatenate([s, s_own[..., None]], -1) * a["scale"], axis=-1)
        o = jnp.einsum("rhk,khd->rhd", p_all[..., :T], v) + p_all[..., T:] * v_own
        return o.reshape(o.shape[0], -1) @ wo.reshape(-1, wo.shape[-1]).astype(F32)

    return jax.lax.map(one, (cq_v, c_v, kr_v, at))


@functools.partial(jax.jit, static_argnames=("a",))
def _latents_at(xv, at, w, l, *, a):
    a, w = dict(a), _at(w, l)
    J, R, D = xv.shape
    h = _rmsnorm(xv.reshape(J * R, D), w["attn_norm_scale"], a["eps"])
    cq = _query_latent(h, w, a, 0)
    c, kr = _line(h, w, jnp.repeat(at, R), a, 0)
    return tuple(y.reshape(J, R, -1) for y in (cq, c, kr))


def _mla_at(lines, xv, at, w, l, a):
    """``lines`` the row's own (c, kr) at this sublayer, xv (J, R, D)
    the judged tokens' residuals under their routings -> xv + attn."""
    cq_v, c_v, kr_v = _latents_at(xv, at, w, l, a=a)
    for g in range(-(-dict(a)["heads"] // HEAD_GROUP)):
        xv = xv + _attend_group_at(*lines, cq_v, c_v, kr_v, at, w, l, g, a=a)
    return xv


# --- what is per token: the FFNs and the routed block ------------------------


def _block(w, index, start, size, axis):
    """Rows (axis 0) or columns (axis 1) [start, start + size) of the
    matrix ``w[index]`` of a stack, taken from the stack itself: the
    matrix is never made (a layer's experts are 1.2 GB)."""
    lead = len(index)
    starts = [*index, 0, 0]
    sizes = [1] * lead + list(w.shape[lead:])
    starts[lead + axis], sizes[lead + axis] = start, size
    return jax.lax.dynamic_slice(w, starts, sizes).reshape(sizes[lead:])


def _glu(h, w, index, bits):
    """(silu(h Wg) * (h Wu)) Wd under the matrices ``w[name][index]`` of
    the stacks ``w``, ``F_BLOCK`` columns of the width at a time, one
    after the other (a loop: one block's weights are upcast, used and
    dropped before the next's)."""
    F = w["w_gate"].shape[-1]
    B = F_BLOCK if F % F_BLOCK == 0 else F
    h = _act(h, bits)

    def block(j, out):
        cut = lambda name, axis: _weight(_block(w[name], index, j * B, B, axis), bits)
        act = jax.nn.silu(h @ cut("w_gate", 1)) * (h @ cut("w_up", 1))
        return out + _act(act, bits) @ cut("w_down", 0)

    return jax.lax.fori_loop(0, F // B, block, jnp.zeros_like(h))


def _route(h, w_router, offset, flip, a):
    """(gate (..., E): the chosen outputs' weights, zero elsewhere;
    margins (..., 2): the layer's choice, [as it is, where it counts]).
    Where ``flip`` (...,) is set the k-th chosen output gives way to
    the (k+1)-th."""
    k, lo, hi = a["k"], a["lo"], a["hi"]
    p = jax.nn.softmax(h @ w_router.astype(F32), axis=-1)
    t = p + offset.astype(F32)
    top, idx = jax.lax.top_k(t, k + 1)
    margin = (top[..., k - 1] - top[..., k]) / jnp.std(t, axis=-1)
    pair = idx[..., k - 1:]
    counts = (((pair >= lo) & (pair < hi)) | (pair >= a["experts"])).any(-1)
    last = jnp.where(jnp.broadcast_to(flip, margin.shape), k, k - 1)[..., None]
    idx_k = jnp.concatenate([idx[..., :k - 1], jnp.take_along_axis(idx, last, -1)], -1)
    g = jnp.take_along_axis(p, idx_k, -1)
    if a["norm"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx_k, t.shape[-1], dtype=F32)
                   * (g * a["scaling"])[..., None], axis=-2)
    return gate, jnp.stack([margin, jnp.where(counts, margin, jnp.inf)], -1)


def _moe(h, w, l, flip, a, bits):
    """Layer ``l``'s routed block of normed tokens h (..., D) under the
    stacks ``w``: (the experts held and the identity outputs' part,
    margins (..., 2)), one expert at a time."""
    gate, margins = _route(h, w["w_router"][l], w["e_score_correction_bias"][l], flip, a)
    s = h * jnp.sum(gate[..., a["experts"]:], axis=-1, keepdims=True)

    def expert(e, s):
        g = jax.lax.dynamic_index_in_dim(gate, e, gate.ndim - 1, True)
        return s + g * _glu(h, w, (l, e - a["lo"]), bits)

    return jax.lax.fori_loop(a["lo"], a["hi"], expert, s), margins


def _first_half(x, ffn, sparse, l, flip, a, bits):
    """After attn_0: (x + ffn_0(h), s = moe(h), margins), h = norm_f0(x)."""
    h = _rmsnorm(x, ffn["mlp_norm_scale"][l], a["eps"])
    s, margins = _moe(h, sparse, l, flip, a, bits)
    return x + _glu(h, ffn, (l,), bits), s, margins


def _second_half(x, s, ffn, l, a, bits):
    """After attn_1: x + ffn_1(norm_f1(x)) + s."""
    h = _rmsnorm(x, ffn["mlp_norm_scale"][l], a["eps"])
    return x + _glu(h, ffn, (l,), bits) + s


def _token_blocks(T):
    """[(start, size)] that cover T tokens in blocks of one size (the
    last block starts early and computes some tokens twice)."""
    n = min(TOKEN_BLOCK, T)
    return [(min(lo, T - n), n) for lo in range(0, T, n)]


@functools.partial(jax.jit, static_argnames=("n", "a", "bits"), donate_argnums=(0, 1, 2))
def _first_half_block(out, s, margins, x, start, ffn, sparse, l, *, n, a, bits):
    xb = jax.lax.dynamic_slice_in_dim(x, start, n)
    got = _first_half(xb, ffn, sparse, l, False, dict(a), bits)
    return tuple(jax.lax.dynamic_update_slice_in_dim(whole, part, start, 0)
                 for whole, part in zip((out, s, margins), got))


@functools.partial(jax.jit, static_argnames=("n", "a", "bits"), donate_argnums=(0,))
def _second_half_block(out, x, s, start, ffn, l, *, n, a, bits):
    cut = functools.partial(jax.lax.dynamic_slice_in_dim, start_index=start, slice_size=n)
    got = _second_half(cut(x), cut(s), ffn, l, dict(a), bits)
    return jax.lax.dynamic_update_slice_in_dim(out, got, start, 0)


@functools.partial(jax.jit, static_argnames=("a",))
def _first_half_at(xv, ffn, sparse, flip, l, *, a):
    return _first_half(xv, ffn, sparse, l, flip, dict(a), 0)


@functools.partial(jax.jit, static_argnames=("a",))
def _second_half_at(xv, s, ffn, l, *, a):
    return _second_half(xv, s, ffn, l, dict(a), 0)


# --- the forward pass --------------------------------------------------------


def _hidden(params, config, tokens, *, control_bits=0):
    """ONE sequence, tokens (T,): (the last layer's residual (T, D),
    each attention sublayer's lines (c, kr) in order, each layer's
    margins (T, 2))."""
    a, bits = _sizes(config), control_bits
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    T = tokens.shape[0]
    lines, margins = [], []
    for l in range(config["num_layers"]):
        x, line = _mla(x, params["mla0"], l, a, bits)
        lines.append(line)
        out, s, m = jnp.zeros_like(x), jnp.zeros_like(x), jnp.zeros((T, 2), F32)
        for start, n in _token_blocks(T):
            out, s, m = _first_half_block(
                out, s, m, x, start, params["ffn0"], params["sparse"], l,
                n=n, a=a, bits=bits)
        margins.append(m)
        x, line = _mla(out, params["mla1"], l, a, bits)
        lines.append(line)
        out = jnp.zeros_like(x)
        for start, n in _token_blocks(T):
            out = _second_half_block(out, x, s, start, params["ffn1"], l,
                                     n=n, a=a, bits=bits)
        x = out
    return x, lines, margins


def _head(params, config, x):
    x = _rmsnorm(x, params["final_norm_scale"], float(config.get("rms_norm_eps", 1e-5)))
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return x @ head.astype(F32)


def forward(params, config, tokens, *, control_bits=0):
    """Float32 logits (B, T, V) of ``tokens`` (B, T) under float32's
    own routing: what the tests compare the served path with."""
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(_head(params, config, _hidden(
            params, config, jnp.asarray(row, jnp.int32),
            control_bits=control_bits)[0])) for row in np.asarray(tokens)])


def flipped_layers(margins, allowed):
    """(flips (J, R, S) bool, valid (J, R) bool) from a row's judged
    tokens' margins (J, S) along float32's own routing, S the layers
    (inf: the layer does not count): routing r flips the token's i-th
    tightest layer, of those under ``allowed``, where bit i of r is
    set; a routing that names a layer the token does not have is not
    valid."""
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


def _judged_row(params, config, tokens, judge, control_bits, routings):
    """One sequence: (logits (J, R, V), flip_margin (J, R), margin (J,))."""
    a = _sizes(config)
    x, lines, margins = _hidden(params, config, tokens, control_bits=control_bits)
    own = np.stack([np.asarray(m[judge]) for m in margins], 1)       # (J, S, 2)
    margin = own[..., 0].min(-1)
    if control_bits or not routings:
        logits = np.asarray(_head(params, config, x[judge]))[:, None]
        return logits, np.zeros(judge.shape + (1,), np.float32), margin
    del x
    allowed = float(config.get("tolerance", {}).get("routing_margin", 0.0))
    flips, valid = flipped_layers(own[..., 1], allowed)
    flips = jnp.asarray(flips)
    flip_margin = jnp.zeros(valid.shape, F32)
    x0 = jnp.take(params["embed"], tokens[judge], axis=0).astype(F32)
    xv = jnp.broadcast_to(x0[:, None], valid.shape + x0.shape[-1:])
    for l in range(config["num_layers"]):
        xv = _mla_at(lines[2 * l], xv, judge, params["mla0"], l, a)
        xv, s, m = _first_half_at(xv, params["ffn0"], params["sparse"],
                                  flips[..., l], l, a=a)
        flip_margin = jnp.maximum(flip_margin, jnp.where(flips[..., l], m[..., 1], 0.0))
        xv = _mla_at(lines[2 * l + 1], xv, judge, params["mla1"], l, a)
        xv = _second_half_at(xv, s, params["ffn1"], l, a=a)
    flip_margin = np.where(valid, np.asarray(flip_margin), np.inf)
    logits = np.stack([np.asarray(_head(params, config, xv[:, r]))
                       for r in range(xv.shape[1])], axis=1)
    return logits, flip_margin, margin


def judged_logits(params, config, tokens, judge, *, control_bits=0, routings=True):
    """Float32 logits of ``tokens`` (B, T) at the positions ``judge``
    (B, J): (logits (B, J, R, V), flip_margin (B, J, R), margin (B, J)),
    the shape ``harness/probe.py::against`` reads. R is 1 for the
    control and without ``routings``, else 2^min(MAX_FLIPPED, layers)
    (module docstring). ``margin``: the judged token's smallest router
    margin over its layers. Positions past a row's own length are
    padding: a causal model keeps them out of every judged position
    before them. One sequence at a time."""
    tokens = np.asarray(tokens)
    judge = np.asarray(judge)
    with jax.default_matmul_precision("highest"):
        rows = [_judged_row(params, config, jnp.asarray(t, jnp.int32),
                            jnp.asarray(j, jnp.int32), control_bits, routings)
                for t, j in zip(tokens, judge)]
    return tuple(np.stack(part) for part in zip(*rows))
