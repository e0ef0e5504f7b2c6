"""The plain reference for MiniCPM-SALA (openbmb/MiniCPM-SALA,
``model_type: minicpm_sala``): the forward pass in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision. No cache, no
kernels, no chunking of the recurrence, nothing imported from the
program. One row at a time, weights upcast one layer at a time, the
feed-forward and the sparse layers' queries in blocks of positions, so
that it fits beside the served model at 18k tokens.

  x0 = scale_emb * embed[tokens]          a = scale_depth / sqrt(scale_depth_layers)
  every layer:  u = rmsnorm(x) ; x += a * mixer(u) ; n = rmsnorm(x)
                x += a * (silu(n Wg) * (n Wu)) Wd
  logits = (rmsnorm(x) / (hidden_size / dim_model_base)) W_head

  ``lightning-attn``, head h of H = lightning_nh, d = lightning_head_dim:
    q = rmsnorm_d(u Wq) ; k = rmsnorm_d(u Wk) ; v = u Wv ; q, k = rope(q, k, t)
    S_t = lambda_h * S_{t-1} + k_t^T v_t       (d x d a head ; S_{-1} = 0)
    o_t = (q_t S_t) / sqrt(d)
    mixer = (rmsnorm(o) * sigmoid(u Wgate)) Wo     rmsnorm over all H d values
    lambda_h = exp(-2^(-8 (h + 1) / H))

  ``minicpm4``, H query heads in KV groups, one K/V head a group, no rope:
    q = rmsnorm_d(u Wq) ; k = rmsnorm_d(u Wk) ; v = u Wv ; n = t + 1 keys are visible
    n <= dense_len:  causal softmax attention over all n keys
    n >  dense_len:  kbar_j = mean(k[stride j : stride j + kernel]) for every j
                     with stride j + kernel <= n
                     p_{h,j} = softmax_j(q_{t,h} . kbar_j / sqrt(d))
                     r_j = sum over the group's heads of p_{h,j}
                     b_m = max of r_j over the j whose span meets block m
                     chosen = the first init_blocks blocks, the blocks that hold
                       any of the last ``window`` tokens (the query's own too),
                       then the largest b_m, topk blocks in all (the lower index
                       first among equal scores) ; one choice for the group
                     causal softmax attention over the tokens of the chosen blocks
    mixer = (o * sigmoid(u Wgate)) Wo

ASSUMED (the published ``config.json`` lacks them; the configuration
file lists them under ``assumed``): the seven ``sparse_config`` sizes,
MiniCPM4's (kernel 32, stride 16, block 64, topk 64, window 2048,
init_blocks 1, dense_len 8192); the decay rule, Lightning Attention's
slope with no per-layer factor; the output gates as plain (hidden,
heads x d) projections; ``scale_depth_layers`` 32, the published depth,
also where the depth is cut; ``mup_denominator`` is recorded in the file
and unused in the forward pass.

Departures and readings, noted: (1) dense or sparse is decided per
POSITION (n = t + 1), not per sequence as an implementation that
prefills whole prompts would, so that the answer does not depend on how
a prompt is chunked. (2) A block without a whole compressed key over it
(the newest, outside the window only when ``window`` is tiny) scores
below every block with one. (3) Rotary angles use the half-split layout
(``rotate_half``). (4) The lightning layers' output norm is over the
concatenated heads, as the issue's equation has it; q/k norms are per
head. (5) The attended set is the chosen BLOCKS' tokens under the causal
mask; the window is not attended beyond its blocks.

``control_bits``: the lower-precision control (``references/decoder.py``
has the same): every matmul weight rounded per output column, every
matmul input per token, and K and V (the lightning layers' k and v too)
per token and head, to that many bits; norms, the embedding, the head,
the recurrent state and the block scores stay float32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
GROUPS = {LIGHTNING: "lightning", SPARSE: "sparse"}  # the program's weight groups
SPARSE_KEYS = ("kernel_size", "kernel_stride", "block_size", "topk",
               "window_size", "init_blocks", "dense_len")
POSITIONS = 2048  # positions a block of the feed-forward
QUERIES = 128     # queries a block of a sparse layer


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


def _blocks(fn, x, size):
    """``fn`` over ``x`` in blocks of ``size`` along axis 0 (padded with
    zeros to a whole number of blocks, the padding dropped)."""
    T = x.shape[0]
    n = -(-T // size)
    pad = jnp.pad(x, ((0, n * size - T),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, pad.reshape((n, size) + x.shape[1:]))
    return out.reshape((n * size,) + out.shape[2:])[:T]


@functools.partial(jax.jit, static_argnames=("eps", "bits", "scale"))
def _glu(x, norm, w_gate, w_up, w_down, *, eps, bits, scale):
    """x (T, D) -> x + scale * glu(rmsnorm(x))."""
    w_gate, w_up, w_down = (_weight(w, bits) for w in (w_gate, w_up, w_down))

    def one(xb):
        h = _act(_rmsnorm(xb, norm, eps), bits)
        return xb + scale * (_act(jax.nn.silu(h @ w_gate) * (h @ w_up), bits) @ w_down)

    return _blocks(one, x, POSITIONS)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "theta", "bits", "scale"))
def _lightning(x, w, *, heads, eps, theta, bits, scale):
    """x (T, D) -> x + scale * lightning mixer: the recurrence, one
    position after another."""
    T = x.shape[0]
    d = w["wq"].shape[-1] // heads
    pos = jnp.arange(T)
    u = _act(_rmsnorm(x, w["attn_norm_scale"], eps), bits)
    q = _rmsnorm((u @ _weight(w["wq"], bits)).reshape(T, heads, d), w["q_norm_scale"], eps)
    k = _rmsnorm((u @ _weight(w["wk"], bits)).reshape(T, heads, d), w["k_norm_scale"], eps)
    v = (u @ _weight(w["wv"], bits)).reshape(T, heads, d)
    q, k = _rope(q, pos, theta), _act(_rope(k, pos, theta), bits)
    v = _act(v, bits)
    lam = jnp.exp(-(2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=F32) / heads)))

    def step(S, qkv):
        q_t, k_t, v_t = qkv
        S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hd,hde->he", q_t, S) / np.sqrt(d)

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), F32), (q, k, v))
    o = _rmsnorm(o.reshape(T, heads * d), w["o_norm_scale"], eps)
    o = _act(o * jax.nn.sigmoid(u @ _weight(w["w_ogate"], bits)), bits)
    return x + scale * (o @ _weight(w["wo"], bits))


def chosen_blocks(q, kbar, t, sp, B):
    """The blocks each query attends: q (Q, KV, G, d), kbar (J, KV, d)
    (every compressed key of the row; those not yet whole at a query's
    position are masked here), t (Q,) positions -> (Q, KV, B) bool.
    All blocks where n = t + 1 <= dense_len."""
    ker, st, blk = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    d = q.shape[-1]
    J = kbar.shape[0]
    n = t + 1
    starts = st * jnp.arange(J)
    whole = (starts + ker)[None, :] <= n[:, None]                      # (Q, J)
    s = jnp.einsum("qkgd,jkd->qkgj", q, kbar) / np.sqrt(d)
    s = jnp.where(whole[:, None, None, :], s, -1e30)
    p = jnp.where(whole[:, None, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    r = p.sum(axis=2)                                                  # (Q, KV, J)
    m = jnp.arange(B)
    meets = (starts[:, None] < blk * (m[None, :] + 1)) & (starts[:, None] + ker > blk * m[None, :])
    b = jnp.max(jnp.where((meets[None] & whole[:, :, None])[:, None], r[..., None], -1.0), axis=2)
    visible = blk * m[None, :] <= t[:, None]                           # (Q, B)
    forced = (m[None, :] < sp["init_blocks"]) | (blk * (m[None, :] + 1) > n[:, None] - sp["window_size"])
    score = jnp.where(forced[:, None, :], 1e9, b)
    score = jnp.where(visible[:, None, :], score, -2.0)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    chosen = (rank < sp["topk"]) & visible[:, None, :]
    return chosen | (n <= sp["dense_len"])[:, None, None]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "bits", "scale", "sp"))
def _sparse(x, w, *, heads, kv_heads, eps, bits, scale, sp):
    """x (T, D) -> (x + scale * minicpm4 mixer, chosen (T, KV, blocks)):
    the choice made explicitly for every query, then attention over the
    chosen blocks' tokens."""
    sp = dict(sp)
    T = x.shape[0]
    d = w["wq"].shape[-1] // heads
    G = heads // kv_heads
    ker, st, blk = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    u = _act(_rmsnorm(x, w["attn_norm_scale"], eps), bits)
    q = _rmsnorm((u @ _weight(w["wq"], bits)).reshape(T, kv_heads, G, d), w["q_norm_scale"], eps)
    k = _rmsnorm((u @ _weight(w["wk"], bits)).reshape(T, kv_heads, d), w["k_norm_scale"], eps)
    k = _act(k, bits)
    v = _act((u @ _weight(w["wv"], bits)).reshape(T, kv_heads, d), bits)
    J = max(1, (T - ker) // st + 1)  # (a row shorter than one kernel: masked, n < kernel)
    inside = jnp.minimum(st * jnp.arange(J)[:, None] + jnp.arange(ker)[None, :], T - 1)
    kbar = k[inside].mean(axis=1)                                      # (J, KV, d)
    tok = jnp.arange(T)
    B = -(-T // blk)

    def one(args):
        qb, tb = args                                                  # (Q, KV, G, d), (Q,)
        chosen = chosen_blocks(qb, kbar, tb, sp, B)                    # (Q, KV, B)
        seen = chosen[:, :, tok // blk] & (tok[None, None, :] <= tb[:, None, None])
        s = jnp.einsum("qkgd,tkd->qkgt", qb, k) / np.sqrt(d)
        s = jnp.where(seen[:, :, None, :], s, -jnp.inf)
        o = jnp.einsum("qkgt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(-1, heads * d), chosen

    Q = min(QUERIES, T)
    n = -(-T // Q)
    qp = jnp.pad(q, ((0, n * Q - T), (0, 0), (0, 0), (0, 0))).reshape(n, Q, kv_heads, G, d)
    tp = jnp.pad(tok, (0, n * Q - T)).reshape(n, Q)
    o, chosen = jax.lax.map(one, (qp, tp))
    o = o.reshape(n * Q, heads * d)[:T]
    chosen = chosen.reshape((n * Q,) + chosen.shape[2:])[:T]
    o = _act(o * jax.nn.sigmoid(u @ _weight(w["w_ogate"], bits)), bits)
    return x + scale * (o @ _weight(w["wo"], bits)), chosen


def _sparse_config(config):
    sp = config["sparse_config"]
    return tuple((k, int(sp[k])) for k in SPARSE_KEYS)


def forward(params, config, tokens, *, control_bits=0):
    """(hidden (B, T, D) after the final norm and the muP divisor,
    chosen: for each sparse layer, (B, T, KV, blocks) bool) of
    ``tokens`` (B, T), float32."""
    depth = config["num_hidden_layers"]
    kinds = list(config["mixer_types"])[:depth]
    eps = float(config["rms_norm_eps"])
    scale = float(config["scale_depth"]) / float(
        config.get("scale_depth_layers", len(config["mixer_types"]))) ** 0.5
    sp = _sparse_config(config)
    tokens = jnp.asarray(tokens, jnp.int32)
    rows, choices = [], []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            x = jnp.take(params["embed"], tokens[b], axis=0).astype(F32) * float(config["scale_emb"])
            seen = {LIGHTNING: 0, SPARSE: 0}
            chosen = []
            for kind in kinds:
                i = seen[kind]
                seen[kind] += 1
                w = {name: a[i] for name, a in params[GROUPS[kind]].items()}
                if kind == LIGHTNING:
                    x = _lightning(x, w, heads=config["lightning_nh"], eps=eps,
                                   theta=float(config["rope_theta"]),
                                   bits=control_bits, scale=scale)
                else:
                    x, c = _sparse(x, w, heads=config["num_attention_heads"],
                                   kv_heads=config["num_key_value_heads"], eps=eps,
                                   bits=control_bits, scale=scale, sp=sp)
                    chosen.append(c)
                x = _glu(x, w["mlp_norm_scale"], w["w_gate"], w["w_up"], w["w_down"],
                         eps=eps, bits=control_bits, scale=scale)
            x = _rmsnorm(x, params["final_norm_scale"], eps)
            rows.append(x / (config["hidden_size"] / config["dim_model_base"]))
            choices.append(chosen)
    chosen = [jnp.stack([c[l] for c in choices]) for l in range(len(choices[0]))]
    return jnp.stack(rows), chosen


def judged_logits(params, config, tokens, judge, *, control_bits=0, routings=True):
    """Float32 logits of ``tokens`` (B, T) at the positions ``judge``
    (B, J): (logits (B, J, 1, V), flip_margin (B, J, 1) zeros, margin
    (B, J) inf), the interface of ``references/decoder.py`` for a model
    with no router. Positions past a row's own length are padding: every
    layer is causal, so they reach no judged position before them."""
    del routings
    x, _ = forward(params, config, tokens, control_bits=control_bits)
    judge = np.asarray(judge)
    rows = np.arange(judge.shape[0])[:, None]
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(x[rows, judge] @ params["lm_head"].astype(F32))
    return (logits[:, :, None], np.zeros(judge.shape + (1,), np.float32),
            np.full(judge.shape, np.inf, np.float32))
