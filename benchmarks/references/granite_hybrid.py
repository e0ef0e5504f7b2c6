"""The plain reference for Granite 4.0-H (ibm-granite/granite-4.0-h-micro,
``model_type: granitemoehybrid`` with no routed expert): the forward
pass in straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision. No cache, no kernels, no batching, the state-space recurrence
TOKEN BY TOKEN (never the chunk form), nothing imported from the
program. One row at a time, one layer's weights upcast at a time, so
that it fits beside the served model. The published modelling code
(``transformers/models/granitemoehybrid``: ``GraniteMoeHybridMambaLayer.
torch_forward``, ``GraniteMoeHybridRMSNormGated``, ``GraniteMoeHybridMLP``,
``GraniteMoeHybridDecoderLayer``, ``GraniteMoeHybridAttention``) is what
it follows; ``tests/test_granite_hybrid.py`` holds it to that code at a
tiny size.

  x = embedding_multiplier * embed[tokens]
  every layer:  x += residual_multiplier * mixer(rmsnorm(x))
                x += residual_multiplier * (silu(u Wgate) * (u Wup)) Wdown,  u = rmsnorm(x)
  logits = (rmsnorm(x) embed^T) / logits_scaling          (a tied head)

  ``mamba`` (Mamba-2), H heads of P channels, a state of N a channel, one group:
    [z | xBC | dt'] = u W_in                  (H P, H P + 2 N, H columns; no bias)
    each channel of xBC: c_t = sum_{j=0..L-1} w[j] * xBC_{t-(L-1)+j} + conv_bias
                  L = mamba_d_conv taps, depthwise, causal, inputs before the start are 0
    [xs | B | C] = silu(c)                    (H P, N, N); xs a head: (H, P)
    dt = softplus(dt' + dt_bias)  a head ;  a = exp(-exp(A_log) dt)   in (0, 1)
    a head's state S (P, N), S_{-1} = 0, a token:
      S <- a S + (dt xs) B^T ;  y = S C + D xs         (written, then read)
    mixer = rmsnorm_{H P}(y * silu(z)) * ssm_norm_scale W_o
                  the gate BEFORE the norm, the norm over the whole inner width

  ``attention``, H heads of d = hidden_size / H over KV shared heads:
    q = u Wq ; k = u Wk ; v = u Wv            no bias, no rope, no q/k norm
    mixer = softmax(causal(attention_multiplier * q k^T)) v Wo

It reads sizes from the configuration FILE (the published key names)
and weights from the arrays it is handed, under the program's names:
groups ``ssm`` (mixer_norm_scale, w_in, conv_w (L, channels), conv_bias,
dt_bias, A_log, D, ssm_norm_scale, w_out), ``attn`` (mixer_norm_scale,
wq, wk, wv, w_out) and ``ffn`` (mlp_norm_scale, w_gate, w_up, w_out), each
stacked over the layers of its kind in layer order.

ASSUMED (the configuration file lists them under ``assumed``): head
size hidden_size / num_attention_heads; ``num_hidden_layers`` under
``len(layer_types)`` takes the first entries. ``mamba_chunk_size`` is
the published kernel's tiling and appears in no equation.

Departures, noted: the convolution is the explicit sum over its taps,
not a padded ``conv1d``; the MLP's gate and up projections are two
matrices (published: the halves of ``input_linear``; the same
mathematics under random weights).

``control_bits``: the lower-precision control (``references/decoder.py``
has the same): every matmul weight rounded per output column, every
matmul input per token, K and V per token and head, and the ``mamba``
layers' xs per token and head and B and C per token, to that many bits;
norms, the taps, dt, the decay, the state, the embedding and the head
stay float32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MAMBA = "mamba"
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


def layout(config):
    """[(mixer group, index in the mixer's stack)] a layer, from the
    file's ``layer_types``; the FFN's index is the layer's own."""
    out, seen = [], {}
    for t in list(config["layer_types"])[: config["num_hidden_layers"]]:
        mixer = "ssm" if t == MAMBA else "attn"
        out.append((mixer, seen.get(mixer, 0)))
        seen[mixer] = seen.get(mixer, 0) + 1
    return out


@functools.partial(jax.jit, static_argnames=("heads", "head", "state", "eps", "bits"))
def _mamba(x, w, *, heads, head, state, eps, bits):
    """x (T, D) -> the mixer's output (T, D), the state stepped a token
    at a time from zeros."""
    T = x.shape[0]
    inner = heads * head
    u = _act(_rmsnorm(x, w["mixer_norm_scale"], eps), bits)
    z, xbc, dt = jnp.split(u @ _weight(w["w_in"], bits),
                           (inner, 2 * inner + 2 * state), axis=-1)
    taps = w["conv_w"].astype(F32)                           # (L, channels)
    L = taps.shape[0]
    past = jnp.pad(xbc, ((L - 1, 0), (0, 0)))
    c = jax.nn.silu(sum(taps[j] * past[j:j + T] for j in range(L))
                    + w["conv_bias"].astype(F32))
    xs, B, C = jnp.split(c, (inner, inner + state), axis=-1)
    xs = _act(xs.reshape(T, heads, head), bits)
    B, C = _act(B, bits), _act(C, bits)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(F32))       # (T, H)
    decay = jnp.exp(-jnp.exp(w["A_log"].astype(F32)) * dt)
    skip = w["D"].astype(F32)

    def token(S, t):
        x_t, b_t, c_t, dt_t, a_t = t
        S = a_t[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * b_t
        return S, jnp.einsum("hpn,n->hp", S, c_t) + skip[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, head, state), F32),
                        (xs, B, C, dt, decay))
    y = _rmsnorm(y.reshape(T, inner) * jax.nn.silu(z), w["ssm_norm_scale"], eps)
    return _act(y, bits) @ _weight(w["w_out"], bits)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "scale", "eps", "bits"))
def _attention(x, w, *, heads, kv_heads, scale, eps, bits):
    """x (T, D) -> the mixer's output (T, D)."""
    T = x.shape[0]
    u = _act(_rmsnorm(x, w["mixer_norm_scale"], eps), bits)
    q = u @ _weight(w["wq"], bits)
    d = q.shape[-1] // heads
    q = q.reshape(T, heads, d)
    k, v = (jnp.repeat(_act((u @ _weight(w[n], bits)).reshape(T, kv_heads, d), bits),
                       heads // kv_heads, axis=1) for n in ("wk", "wv"))
    pos = jnp.arange(T)
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    s = jnp.where((pos[None, :] <= pos[:, None])[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return _act(o.reshape(T, -1), bits) @ _weight(w["w_out"], bits)


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _mlp(x, w, *, eps, bits):
    u = _act(_rmsnorm(x, w["mlp_norm_scale"], eps), bits)
    act = jax.nn.silu(u @ _weight(w["w_gate"], bits)) * (u @ _weight(w["w_up"], bits))
    return _act(act, bits) @ _weight(w["w_out"], bits)


def _layer(params, group, index):
    return {name: w[index] for name, w in params[group].items()}


def hidden(params, config, tokens, *, control_bits=0):
    """The last layer's residual (T, D) of one row of ``tokens`` (T,)."""
    eps = float(config["rms_norm_eps"])
    res = float(config["residual_multiplier"])
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    x = x * float(config["embedding_multiplier"])
    for i, (mixer, mi) in enumerate(layout(config)):
        w = _layer(params, mixer, mi)
        if mixer == "ssm":
            if config.get("mamba_n_groups", 1) != 1:
                raise NotImplementedError("mamba_n_groups != 1")
            out = _mamba(x, w, heads=config["mamba_n_heads"],
                         head=config["mamba_d_head"], state=config["mamba_d_state"],
                         eps=eps, bits=control_bits)
        else:
            out = _attention(x, w, heads=config["num_attention_heads"],
                             kv_heads=config["num_key_value_heads"],
                             scale=float(config["attention_multiplier"]),
                             eps=eps, bits=control_bits)
        x = x + res * out
        x = x + res * _mlp(x, _layer(params, "ffn", i), eps=eps, bits=control_bits)
    return x


def _head(params, config, x):
    x = _rmsnorm(x, params["final_norm_scale"], float(config["rms_norm_eps"]))
    return x @ params["embed"].T.astype(F32) / float(config["logits_scaling"])


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
