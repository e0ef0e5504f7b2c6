"""The plain reference for DeepSeek-V3 (deepseek-ai/DeepSeek-V3,
``model_type: deepseek_v3``): the forward pass in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision, attention in
its EXPANDED form (a key and a value a head, made from the compressed
line), full causal attention. No cache, no pages, no kernels, no
absorbed weights, no grouping of tokens by expert, nothing imported
from the program. One sequence at a time, its heads in groups and its
queries in blocks, so that a 10 k prompt at the published widths fits
beside the served model; a layer's weights (of a sparse layer: one
expert's) are upcast as they are used.

  x = embed[tokens]
  per layer:  h = rmsnorm(x)                                 eps 1e-6
    queries:  c_q = rmsnorm(h W_qa) ; q = c_q W_qb           H heads of [q_nope | q_rope]
    the line: [c_raw | kr_raw] = h W_kva ; c = rmsnorm(c_raw) ; kr = rope(kr_raw)
              (one rope key, shared by every head)
    a head:   [k_nope_h | v_h] = c W_kvb  (head h's columns)
              score_h = (q_nope_h . k_nope_h + rope(q_rope_h) . kr) * s
              s = (nope + rope)^-0.5 * m^2 ,  m = 0.1 * mscale_all_dim * ln(factor) + 1
              x += concat_h(softmax(causal(score_h)) v_h) W_o
    rope:     on the rope channels only, theta 10000, YaRN frequencies: the plain
              theta^(-2i/d) below channel floor(ch(beta_fast)), those over ``factor``
              above ceil(ch(beta_slow)), a linear ramp between, where
              ch(n) = d ln(original_max / (2 pi n)) / (2 ln theta) ;
              cos and sin times mscale / mscale_all_dim (= 1)
    h = rmsnorm(x)
    layers < first_k_dense_replace:  x += (silu(h Wg) * (h Wu)) Wd
    the others:  s = sigmoid(h W_r)                          all router outputs, float32
                 t = s + offset                              (HF ``e_score_correction_bias``)
                 a group's score: the sum of its two largest t (n_group equal runs)
                 the topk_group best groups stay (the lower index first among equals)
                 sel = the k largest t among the experts of those groups
                 g = s[sel] / (sum(s[sel]) + 1e-20) * routed_scaling_factor
                 x += sum_{e in sel, e held} g_e * E_e(h) + E_shared(h)
                 each E a SiLU GLU of moe_intermediate_size (the shared one of
                 n_shared_experts times that)
  logits = rmsnorm(x) W_head                                 (untied)

It reads sizes from the configuration FILE (the published key names)
and weights from the arrays it is handed, under the program's names:
groups ``mla`` (attn_norm_scale, w_qa, q_norm_scale, w_qb, w_kva,
kv_norm_scale, w_kvb, wo), ``dense`` and ``sparse`` (mlp_norm_scale,
w_gate, w_up, w_down; w_router, router_offset, ``shared`` with its own
w_gate / w_up / w_down), each stacked over the layers of its kind.

Departures from the published description, each on purpose:

* THE SHARE OF EXPERTS. ``experts_held`` [lo, hi) (absent: every
  expert; the file then counts them in ``n_routed_experts`` and gives
  the router's width as ``router_outputs``) is the range of the
  router's outputs whose experts exist here: one chip's share of an
  expert-parallel stage. The router chooses over ALL its outputs; what
  the absent experts would add is left out, here as in the program.
* THE MTP MODULE (``num_nextn_predict_layers``) sits behind the last of
  the published 61 layers and is not part of this forward pass.
* bf16 weights from a seed where the checkpoint is FP8 with block
  scales: the reference upcasts what it is handed.
* rotary angles use the half-split layout (``rotate_half``) on the rope
  channels; the checkpoint pairs adjacent channels, which is the same
  model under a permutation of those columns of W_qb and W_kva.
* ``num_hidden_layers`` under the published count takes the first
  layers, ``first_k_dense_replace`` of them dense.

Sparse layers and ``correct``: a token whose choice is nearly level is
sent the other way by any rounding difference, and its logits then
differ by as much as the experts it lost or gained (``references/
decoder.py`` has the argument). A sparse layer makes TWO choices that
can be level: which group is the last to stay (the ``topk_group``-th or
the next), and which expert is the last chosen inside the groups that
stay (the k-th or the (k+1)-th). A choice's ``margin`` is the distance
between the two it orders (group scores; t) as a share of the spread
(standard deviation) of the token's t over all the router's outputs.
The rule is BOUNDED as ``references/lfm2_moe.py``'s: for each judged
token, float32's own routing (routing 0) and the routings that take
the other side in every subset of that token's at most ``MAX_FLIPPED``
tightest choices, over all its sparse layers, whose margin, along
float32's own routing, is under the file's ``tolerance.routing_margin``:
at most 2^MAX_FLIPPED = 16 routings, at any depth. A routing's
``flip_margin`` is the largest margin it overruled (an expert's, inside
groups that were flipped too, along the flipped groups). Tokens a
judged token attends to keep float32's routing.

``control_bits``: the lower-precision control: every matmul weight
rounded per output column, every matmul input per token, and the cached
line (``c`` and ``kr``, each per token) to that many bits; norms, the
router, the embedding and the head stay float32.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MAX_FLIPPED = 4   # sparse layers of a judged token that may go the other way
HEAD_GROUP = 16   # heads computed at a time
QUERY_BLOCK = 128  # queries a block of one head group's attention


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
    rope = config.get("rope_scaling") or {}
    factor = float(rope.get("factor", 1.0))
    m = 1.0 if factor <= 1 else 0.1 * float(rope.get("mscale_all_dim", 0)) * math.log(factor) + 1.0
    m_rope = 1.0 if factor <= 1 else 0.1 * float(rope.get("mscale", 1)) * math.log(factor) + 1.0
    nope, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return dict(
        heads=config["num_attention_heads"], nope=nope, dr=dr,
        dv=config["v_head_dim"], rank=config["kv_lora_rank"],
        eps=float(config.get("rms_norm_eps", 1e-6)),
        scale=(nope + dr) ** -0.5 * m * m,
        inv_freq=tuple(yarn_inv_freq(
            dr, float(config.get("rope_theta", 10000.0)), factor,
            int(rope.get("original_max_position_embeddings",
                         config["max_position_embeddings"])),
            float(rope.get("beta_fast", 32)), float(rope.get("beta_slow", 1)))),
        rope_mscale=m_rope / m)


def yarn_inv_freq(d, theta, factor, original_max, beta_fast, beta_slow):
    """The d / 2 rope frequencies (module docstring), float64."""
    plain = theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)
    if factor <= 1:
        return plain

    def channel(turns):
        return d * math.log(original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(channel(beta_fast)), 0)
    high = min(math.ceil(channel(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rope(x, positions, a):
    """x (T, ..., dr) at ``positions`` (T,); rotate_half convention."""
    inv = jnp.asarray(a["inv_freq"], F32)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    cos = (jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * a["rope_mscale"]).reshape(shape)
    sin = (jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * a["rope_mscale"]).reshape(shape)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def layout(config):
    """[(FFN group, index in the FFN's stack)] a layer."""
    out, seen = [], {}
    for i in range(config["num_hidden_layers"]):
        ffn = "dense" if i < config["first_k_dense_replace"] else "sparse"
        out.append((ffn, seen.get(ffn, 0)))
        seen[ffn] = seen.get(ffn, 0) + 1
    return out


def _held(config):
    """[lo, hi): the router's outputs whose experts exist here."""
    held = config.get("experts_held")
    return tuple(held) if held else (
        0, config.get("router_outputs", config["n_routed_experts"]))


# --- latent attention, expanded ----------------------------------------------


def _line(h, w, pos, a, bits):
    """(c (n, rank), kr (n, dr)) of normed inputs h (n, D) at ``pos``:
    what a cache would keep of each token."""
    raw = _act(h, bits) @ _weight(w["w_kva"], bits)
    c = _rmsnorm(raw[:, :a["rank"]], w["kv_norm_scale"], a["eps"])
    return _act(c, bits), _act(_rope(raw[:, a["rank"]:], pos, a), bits)


def _query_latent(h, w, a, bits):
    return _act(_rmsnorm(_act(h, bits) @ _weight(w["w_qa"], bits),
                         w["q_norm_scale"], a["eps"]), bits)


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
    q = (cq @ _weight(wq.reshape(wq.shape[0], -1), bits)).reshape(-1, G, a["nope"] + a["dr"])
    kv = (c @ _weight(wkv.reshape(wkv.shape[0], -1), bits)).reshape(-1, G, a["nope"] + a["dv"])
    return (q[..., :a["nope"]], _rope(q[..., a["nope"]:], pos, a),
            kv[..., :a["nope"]], kv[..., a["nope"]:])


@functools.partial(jax.jit, static_argnames=("a", "bits"))
def _attend_group(cq, c, kr, w, g, *, a, bits):
    """Head group g's part of the attention output, (T, D): causal
    softmax over the whole row, queries a block at a time."""
    a = dict(a)
    T = cq.shape[0]
    pos = jnp.arange(T)
    wq, wkv, wo = _head_group(w, g, a)
    qn, qr, kn, v = _expand(cq, c, wq, wkv, pos, a, bits)
    B = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def block(args):
        qn_b, qr_b, pos_b = args
        s = (jnp.einsum("qhd,khd->hqk", qn_b, kn)
             + jnp.einsum("qhd,kd->hqk", qr_b, kr)) * a["scale"]
        s = jnp.where((pos[None, :] <= pos_b[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, tuple(
        x.reshape((T // B, B) + x.shape[1:]) for x in (qn, qr, pos)))
    o = _act(o.reshape(T, -1), bits)  # per token and head group
    return o @ _weight(wo.reshape(-1, wo.shape[-1]), bits)


def _mla(x, w, a, bits):
    """x (T, D) -> x + MLA(rmsnorm(x))."""
    h = _rmsnorm(x, w["attn_norm_scale"], a["eps"])
    cq = _query_latent(h, w, a, bits)
    c, kr = _line(h, w, jnp.arange(x.shape[0]), a, bits)
    frozen = tuple(sorted(a.items()))
    for g in range(-(-a["heads"] // HEAD_GROUP)):
        x = x + _attend_group(cq, c, kr, w, g, a=frozen, bits=bits)
    return x


@functools.partial(jax.jit, static_argnames=("a",))
def _attend_group_at(cq, c, kr, cq_v, c_v, kr_v, at, w, g, *, a):
    """The same for single tokens whose residual is not the row's own
    (another routing upstream): token (j, r) sits at position ``at[j]``,
    attends to the row's lines BEFORE it (c, kr) and to its own
    (c_v, kr_v (J, R, .)). -> head group g's output (J, R, D)."""
    a = dict(a)
    T = c.shape[0]
    pos = jnp.arange(T)
    wq, wkv, wo = _head_group(w, g, a)
    _, _, kn, v = _expand(cq[:1], c, wq, wkv, pos[:1], a, 0)

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


def _mla_at(x, xv, at, w, a):
    """x (T, D) the row's own residual, xv (J, R, D) the judged tokens'
    under their routings -> xv + MLA."""
    J, R, D = xv.shape
    h = _rmsnorm(x, w["attn_norm_scale"], a["eps"])
    cq = _query_latent(h, w, a, 0)
    c, kr = _line(h, w, jnp.arange(x.shape[0]), a, 0)
    hv = _rmsnorm(xv.reshape(J * R, D), w["attn_norm_scale"], a["eps"])
    cq_v = _query_latent(hv, w, a, 0).reshape(J, R, -1)
    c_v, kr_v = (y.reshape(J, R, -1) for y in _line(hv, w, jnp.repeat(at, R), a, 0))
    frozen = tuple(sorted(a.items()))
    for g in range(-(-a["heads"] // HEAD_GROUP)):
        xv = xv + _attend_group_at(cq, c, kr, cq_v, c_v, kr_v, at, w, g, a=frozen)
    return xv


# --- the two feed-forward kinds ----------------------------------------------


@functools.partial(jax.jit, static_argnames=("bits",))
def _glu(h, w_gate, w_up, w_down, *, bits):
    w_gate, w_up, w_down = (_weight(w, bits) for w in (w_gate, w_up, w_down))
    h = _act(h, bits)
    return _act(jax.nn.silu(h @ w_gate) * (h @ w_up), bits) @ w_down


@functools.partial(jax.jit, static_argnames=("bits",))
def _add_expert(x, h, gate, w, l, e, at, *, bits):
    """x + gate[..., e] * expert(h): expert ``e`` of the router, whose
    weights are entry ``at`` of layer ``l``'s stack ``w``."""
    one = {name: jax.lax.dynamic_index_in_dim(
        jax.lax.dynamic_index_in_dim(w[name], l, 0, keepdims=False), at, 0,
        keepdims=False) for name in ("w_gate", "w_up", "w_down")}
    g = jnp.take(gate, e, axis=-1)[..., None]
    return x + g * _glu(h, one["w_gate"], one["w_up"], one["w_down"], bits=bits)


@functools.partial(jax.jit, static_argnames=("k", "n_group", "topk_group", "norm", "scaling"))
def _route(h, w_router, offset, flip, *, k, n_group, topk_group, norm, scaling):
    """(gate (..., E): the chosen experts' weights, zero elsewhere;
    margins (..., 2): the layer's two choices, [groups, experts]).
    ``flip`` (..., 2) bool: where [..., 0] is set the last group that
    stays gives way to the next, where [..., 1] the k-th chosen expert
    (inside the groups that then stay) to the (k+1)-th."""
    s = jax.nn.sigmoid(h @ w_router.astype(F32))
    t = s + offset.astype(F32)
    E = t.shape[-1]
    flip = jnp.broadcast_to(flip, t.shape[:-1] + (2,))
    spread = jnp.std(t, axis=-1)
    grouped = t.reshape(t.shape[:-1] + (n_group, E // n_group))
    score = jax.lax.top_k(grouped, min(2, E // n_group))[0].sum(-1)   # (..., n_group)
    if topk_group < n_group:
        top_g, idx_g = jax.lax.top_k(score, topk_group + 1)
        m_group = (top_g[..., topk_group - 1] - top_g[..., topk_group]) / spread
        last = jnp.where(flip[..., 0], topk_group, topk_group - 1)[..., None]
        kept = jnp.concatenate([idx_g[..., :topk_group - 1],
                                jnp.take_along_axis(idx_g, last, -1)], -1)
        stays = jnp.sum(jax.nn.one_hot(kept, n_group, dtype=F32), axis=-2) > 0
        t = jnp.where(stays[..., None], grouped, -jnp.inf).reshape(t.shape)
    else:
        m_group = jnp.full(t.shape[:-1], jnp.inf, F32)
    top, idx = jax.lax.top_k(t, k + 1)
    m_expert = (top[..., k - 1] - top[..., k]) / spread
    last = jnp.where(flip[..., 1], k, k - 1)[..., None]
    idx_k = jnp.concatenate([idx[..., :k - 1], jnp.take_along_axis(idx, last, -1)], -1)
    g = jnp.take_along_axis(s, idx_k, -1)
    if norm:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    gate = jnp.sum(jax.nn.one_hot(idx_k, E, dtype=F32) * (g * scaling)[..., None], axis=-2)
    return gate, jnp.stack([m_group, m_expert], axis=-1)


def _sparse_ffn(config, w, l, x, flip, bits):
    """One sparse layer: x (..., D) -> (x + its experts held + the
    shared expert, margins (..., 2)); ``flip`` (..., 2) or False."""
    lo, hi = _held(config)
    eps = float(config.get("rms_norm_eps", 1e-6))
    h = _rmsnorm(x, w["mlp_norm_scale"][l], eps)
    gate, margins = _route(
        h, w["w_router"][l], w["router_offset"][l], jnp.asarray(flip),
        k=config["num_experts_per_tok"], n_group=config.get("n_group", 1),
        topk_group=config.get("topk_group", 1),
        norm=bool(config.get("norm_topk_prob", True)),
        scaling=float(config.get("routed_scaling_factor", 1.0)))
    stacks = {name: w[name] for name in ("w_gate", "w_up", "w_down")}
    for e in range(lo, hi):
        x = _add_expert(x, h, gate, stacks, l, e, e - lo, bits=bits)
    if "shared" in w:
        sh = w["shared"]
        x = x + _glu(h, sh["w_gate"][l], sh["w_up"][l], sh["w_down"][l], bits=bits)
    return x, margins


# --- the forward pass --------------------------------------------------------


def _layer(params, group, index):
    return {name: w[index] for name, w in params[group].items()
            if not isinstance(w, dict)}


def _hidden(params, config, tokens, *, control_bits=0):
    """ONE sequence, tokens (T,): (the last layer's residual (T, D),
    each layer's INPUT residual, each sparse layer's margins (T, 2))."""
    a = _sizes(config)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    inputs, margins = [], []
    for i, (ffn, fi) in enumerate(layout(config)):
        inputs.append(x)
        x = _mla(x, _layer(params, "mla", i), a, control_bits)
        if ffn == "dense":
            w = _layer(params, "dense", fi)
            x = x + _glu(_rmsnorm(x, w["mlp_norm_scale"], a["eps"]), w["w_gate"],
                         w["w_up"], w["w_down"], bits=control_bits)
        else:
            x, m = _sparse_ffn(config, params["sparse"], fi, x, False, control_bits)
            margins.append(m)
    return x, inputs, margins


def _head(params, config, x):
    x = _rmsnorm(x, params["final_norm_scale"], float(config.get("rms_norm_eps", 1e-6)))
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return x @ head.astype(F32)


def forward(params, config, tokens, *, control_bits=0):
    """Float32 logits (B, T, V) of ``tokens`` (B, T) under float32's
    own routing: what the tests compare the served path with."""
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(_head(params, config, _hidden(
            params, config, jnp.asarray(row, jnp.int32),
            control_bits=control_bits)[0])) for row in np.asarray(tokens)])


def flipped_choices(margins, allowed):
    """(flips (J, R, S) bool, valid (J, R) bool) from a row's judged
    tokens' margins (J, S) along float32's own routing, S the token's
    choices (two a sparse layer): routing r flips the token's i-th
    tightest choice, of those under ``allowed``, where bit i of r is
    set; a routing that names a choice the token does not have is not
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
    x, inputs, margins = _hidden(params, config, tokens, control_bits=control_bits)
    own = (np.concatenate([np.asarray(m[judge]) for m in margins], -1) if margins
           else np.zeros(judge.shape + (0,), np.float32))            # (J, 2 S)
    margin = own.min(-1) if own.size else np.full(judge.shape, np.inf, np.float32)
    if control_bits or not routings or not margins:
        logits = np.asarray(_head(params, config, x[judge]))[:, None]
        return logits, np.zeros(judge.shape + (1,), np.float32), margin
    allowed = float(config.get("tolerance", {}).get("routing_margin", 0.0))
    flips, valid = flipped_choices(own, allowed)
    flips = jnp.asarray(flips)
    flip_margin = jnp.zeros(valid.shape, F32)
    xv = jnp.broadcast_to(inputs[0][judge][:, None], valid.shape + x.shape[-1:])
    for i, ((ffn, fi), x_in) in enumerate(zip(layout(config), inputs)):
        xv = _mla_at(x_in, xv, judge, _layer(params, "mla", i), a)
        if ffn == "dense":
            w = _layer(params, "dense", fi)
            xv = xv + _glu(_rmsnorm(xv, w["mlp_norm_scale"], a["eps"]),
                           w["w_gate"], w["w_up"], w["w_down"], bits=0)
        else:
            flip = flips[..., 2 * fi:2 * fi + 2]
            xv, m = _sparse_ffn(config, params["sparse"], fi, xv, flip, 0)
            flip_margin = jnp.maximum(flip_margin, jnp.where(flip, m, 0.0).max(-1))
    flip_margin = np.where(valid, np.asarray(flip_margin), np.inf)
    logits = np.stack([np.asarray(_head(params, config, xv[:, r]))
                       for r in range(xv.shape[1])], axis=1)
    return logits, flip_margin, margin


def judged_logits(params, config, tokens, judge, *, control_bits=0, routings=True):
    """Float32 logits of ``tokens`` (B, T) at the positions ``judge``
    (B, J): (logits (B, J, R, V), flip_margin (B, J, R), margin (B, J)),
    the shape ``harness/probe.py::against`` reads. R is 1 for the
    control and without ``routings``, else 2^min(MAX_FLIPPED, sparse
    layers) (module docstring). ``margin``: the judged token's smallest
    router margin over its choices. Positions past a row's own length
    are padding: a causal model keeps them out of every judged position
    before them. One sequence at a time."""
    tokens = np.asarray(tokens)
    judge = np.asarray(judge)
    with jax.default_matmul_precision("highest"):
        rows = [_judged_row(params, config, jnp.asarray(t, jnp.int32),
                            jnp.asarray(j, jnp.int32), control_bits, routings)
                for t, j in zip(tokens, judge)]
    return tuple(np.stack(part) for part in zip(*rows))
