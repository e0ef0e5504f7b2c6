"""The plain reference for Olmo-Hybrid (allenai/Olmo-Hybrid-7B,
``model_type: olmo_hybrid``): the forward pass in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision. No cache, no
kernels, no batching, the recurrence TOKEN BY TOKEN (never a chunk
form), nothing imported from the program. One row at a time, one
layer's weights upcast at a time, so that it fits beside the served
model.

  x = embed[tokens]                         h = hidden_size, no norm before a sublayer
  every layer:  x += rmsnorm(mixer(x)) ; x += rmsnorm((silu(x Wg) * (x Wu)) Wd)
  logits = rmsnorm(x) W_head                 (an untied head)

  ``linear_attention`` (Gated DeltaNet), H heads of dk keys and dv values:
    [q' | k' | v'] = x W_qkv                 (H dk, H dk, H dv columns; no bias)
    each channel: c_t = sum_{j=0..L-1} w[j] * u_{t-(L-1)+j}   L = linear_conv_kernel_dim
                  taps, depthwise, causal, inputs before the start are 0 ; then SiLU
    q = l2norm(q) * dk^-0.5 ; k = l2norm(k)  a head, x * rsqrt(sum x^2 + 1e-6)
    [B | A] = x W_gates                      (H, H columns)
    b = sigmoid(B)  (times 2 where linear_allow_neg_eigval)
    g = -exp(A_log) * softplus(A + dt_bias) ; a = exp(g)   in (0, 1)
    a head's state S (dk, dv), S_{-1} = 0, a token:
      S <- a S ; u = b (v - S^T k) ; S <- S + k u^T ; o = S^T q
    mixer = (rmsnorm_dv(o) * o_norm_scale * silu(x W_ogate)) W_o     a head's own dv values

  ``full_attention``, H heads of d = hidden_size / H, as many K/V heads:
    q = rmsnorm(x Wq) ; k = rmsnorm(x Wk)    ONE learned scale over the whole
                                             projection, not a head ; v = x Wv
    mixer = softmax(causal(q k^T / sqrt(d))) v Wo        no rope

It reads sizes from the configuration FILE (the published key names)
and weights from the arrays it is handed, under the program's names:
groups ``gdn`` (w_qkv, conv_w (L, channels), w_gates, dt_bias, A_log,
o_norm_scale, w_ogate, wo, mixer_norm_scale), ``attn`` (wq, wk, wv,
q_norm_scale, k_norm_scale, wo, mixer_norm_scale) and ``ffn`` (w_gate,
w_up, w_down, mlp_norm_scale), each stacked over the layers of its kind
in layer order.

ASSUMED (the published ``config.json`` does not carry them; the
configuration file lists them under ``assumed``): the block's norm
placement (after each sublayer, none before: Olmo 2 and 3), the q/k
norm over the whole projection, NO rope (``rope_theta`` is null in the
published file), head size hidden_size / num_attention_heads,
``num_hidden_layers`` under ``len(layer_types)`` takes the first
entries.

Departures, noted: the convolution is the explicit sum over its taps,
not a padded ``conv1d``; q', k' and v' are the columns of ONE matrix and
the two gates' inputs of another (published: a projection each; the
same mathematics under random weights).

``control_bits``: the lower-precision control (``references/decoder.py``
has the same): every matmul weight rounded per output column, every
matmul input per token, K and V per token and head, and the recurrent
layers' q, k and v per token and head, to that many bits; norms, the
taps, the gates, the state, the embedding and the head stay float32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
LINEAR, ATTENTION = "linear_attention", "full_attention"
POSITIONS = 256  # positions a block of the head


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


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def layout(config):
    """[(mixer group, index in the mixer's stack)] a layer, from the
    file's ``layer_types``; the FFN's index is the layer's own."""
    out, seen = [], {}
    for t in list(config["layer_types"])[: config["num_hidden_layers"]]:
        mixer = "gdn" if t == LINEAR else "attn"
        out.append((mixer, seen.get(mixer, 0)))
        seen[mixer] = seen.get(mixer, 0) + 1
    return out


@functools.partial(jax.jit, static_argnames=("heads", "dk", "dv", "neg", "eps", "bits"))
def _gated_deltanet(x, w, *, heads, dk, dv, neg, eps, bits):
    """x (T, D) -> the mixer's output (T, D), the state stepped a token
    at a time from zeros."""
    T = x.shape[0]
    xa = _act(x, bits)
    taps = w["conv_w"].astype(F32)                           # (L, channels)
    L = taps.shape[0]
    past = jnp.pad(xa @ _weight(w["w_qkv"], bits), ((L - 1, 0), (0, 0)))
    c = jax.nn.silu(sum(taps[j] * past[j:j + T] for j in range(L)))
    q, k, v = jnp.split(c, (heads * dk, 2 * heads * dk), axis=-1)
    q = _act(_l2norm(q.reshape(T, heads, dk)) * dk ** -0.5, bits)
    k = _act(_l2norm(k.reshape(T, heads, dk)), bits)
    v = _act(v.reshape(T, heads, dv), bits)
    gates = x @ w["w_gates"].astype(F32)
    b = jax.nn.sigmoid(gates[:, :heads]) * (2.0 if neg else 1.0)
    g = -jnp.exp(w["A_log"].astype(F32)) * jax.nn.softplus(
        gates[:, heads:] + w["dt_bias"].astype(F32))

    def token(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hde,hd->he", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv), F32), (q, k, v, g, b))
    o = _rmsnorm(o, w["o_norm_scale"], eps)
    o = o * jax.nn.silu(xa @ _weight(w["w_ogate"], bits)).reshape(T, heads, dv)
    return _act(o.reshape(T, heads * dv), bits) @ _weight(w["wo"], bits)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "bits"))
def _attention(x, w, *, heads, kv_heads, eps, bits):
    """x (T, D) -> the mixer's output (T, D)."""
    T = x.shape[0]
    xa = _act(x, bits)
    q = _rmsnorm(xa @ _weight(w["wq"], bits), w["q_norm_scale"], eps)
    k = _rmsnorm(xa @ _weight(w["wk"], bits), w["k_norm_scale"], eps)
    v = xa @ _weight(w["wv"], bits)
    d = q.shape[-1] // heads
    q = q.reshape(T, heads, d)
    k = jnp.repeat(_act(k.reshape(T, kv_heads, d), bits), heads // kv_heads, axis=1)
    v = jnp.repeat(_act(v.reshape(T, kv_heads, d), bits), heads // kv_heads, axis=1)
    pos = jnp.arange(T)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    s = jnp.where((pos[None, :] <= pos[:, None])[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return _act(o.reshape(T, -1), bits) @ _weight(w["wo"], bits)


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _ffn(x, w, *, eps, bits):
    h = _act(x, bits)
    act = jax.nn.silu(h @ _weight(w["w_gate"], bits)) * (h @ _weight(w["w_up"], bits))
    return x + _rmsnorm(_act(act, bits) @ _weight(w["w_down"], bits),
                        w["mlp_norm_scale"], eps)


def _layer(params, group, index):
    return {name: w[index] for name, w in params[group].items()}


def hidden(params, config, tokens, *, control_bits=0):
    """The last layer's residual (T, D) of one row of ``tokens`` (T,)."""
    eps = float(config["rms_norm_eps"])
    heads = config["num_attention_heads"]
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    for i, (mixer, mi) in enumerate(layout(config)):
        w = _layer(params, mixer, mi)
        if mixer == "gdn":
            out = _gated_deltanet(
                x, w, heads=config["linear_num_value_heads"],
                dk=config["linear_key_head_dim"], dv=config["linear_value_head_dim"],
                neg=bool(config.get("linear_allow_neg_eigval", False)),
                eps=eps, bits=control_bits)
        else:
            out = _attention(x, w, heads=heads,
                             kv_heads=config["num_key_value_heads"],
                             eps=eps, bits=control_bits)
        x = x + _rmsnorm(out, w["mixer_norm_scale"], eps)
        x = _ffn(x, _layer(params, "ffn", i), eps=eps, bits=control_bits)
    return x


def _head(params, config, x):
    x = _rmsnorm(x, params["final_norm_scale"], float(config["rms_norm_eps"]))
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return x @ head.astype(F32)


def forward(params, config, tokens, *, control_bits=0):
    """Float32 logits (B, T, V) of ``tokens`` (B, T): what the tests
    compare the served path with. The head in blocks of positions."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        rows = []
        for row in tokens:
            x = hidden(params, config, row, control_bits=control_bits)
            rows.append(np.concatenate(
                [np.asarray(_head(params, config, x[lo:lo + POSITIONS]))
                 for lo in range(0, x.shape[0], POSITIONS)]))
    return np.stack(rows)


def judged_logits(params, config, tokens, judge, *, control_bits=0, routings=True):
    """Float32 logits of ``tokens`` (B, T) at the positions ``judge``
    (B, J): (logits (B, J, 1, V), flip_margin (B, J, 1) zeros, margin
    (B, J) inf), the interface of ``references/decoder.py`` for a model
    with no router. Positions past a row's own length are padding: every
    layer is causal, so they reach no judged position before them."""
    del routings
    tokens = jnp.asarray(tokens, jnp.int32)
    judge = np.asarray(judge)
    with jax.default_matmul_precision("highest"):
        logits = np.stack([
            np.asarray(_head(params, config, hidden(
                params, config, row, control_bits=control_bits)[at]))
            for row, at in zip(tokens, judge)])
    return (logits[:, :, None], np.zeros(judge.shape + (1,), np.float32),
            np.full(judge.shape, np.inf, np.float32))
