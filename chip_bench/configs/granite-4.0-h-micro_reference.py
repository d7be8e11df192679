"""The plain reference of granite-4.0-h-micro's layers in training: forward
pass, the next-token loss and, by ``jax.grad`` of it, the gradients, in
float32 at the highest matmul precision, the Mamba-2 recurrence a token at a
time.  It imports ``jax`` and ``jax.numpy`` and nothing of ``horovod_tpu``;
it takes the program's parameter tree.

The one file: the benchmark decides ``correct`` by it and the tier-1 suites
hold the program to it (``tests/helpers.py::load_reference``).
"""
# The architecture as published (ibm-granite/granite-4.0-h-micro config.json,
# model_type granitemoehybrid; Mamba-2, arXiv:2405.21060).  With E the
# embedding [vocab, d] and four scalars of the configuration,
#
#   h_0    = embedding_multiplier * E[tokens]                              12
#   h      = h + residual_multiplier * mixer_i(RMSNorm(h))                 0.22
#   h      = h + residual_multiplier * W_out(silu(W_g x) * W_u x)
#                                          with x = RMSNorm(h)
#   logits = RMSNorm(h_L) E^T / logits_scaling                   tied; 8
#
# every layer a mixer and a dense SwiGLU of width shared_intermediate_size
# (num_local_experts 0: no routed term), each under its own RMSNorm, eps
# rms_norm_eps.  The mixer by the layer's entry in layer_types.
#
# "mamba", Mamba-2, with u = RMSNorm(h), H = mamba_n_heads heads of P =
# mamba_d_head channels in G = mamba_n_groups groups (one), N = mamba_d_state,
# L = mamba_d_conv:
#   z, xBC, dt = split(u W_in)             widths H*P, H*P + 2*G*N, H; no bias
#   xBC    = silu(conv(xBC) + b)           depthwise, causal: c_t = sum_{j<L}
#                                          w[:, j] xBC_{t-(L-1)+j}, zero before
#                                          position 0
#   x, B, C = split(xBC)                   widths H*P, G*N, G*N
#   delta  = softplus(dt + dt_bias)        a head
#   a      = -exp(A_log)                   a head
#   S_t    = exp(delta_t a) S_{t-1} + delta_t x_t B_t^T    S [P, N] a head,
#                                          zero before position 0; B, C those
#                                          of the head's group h // (H / G)
#   y_t    = S_t C_t + D x_t
#   y      = RMSNorm_g(y * silu(z))        the gate first, then the norm over
#                                          each group's H*P/G channels (with
#                                          one group: all 4096), one scale a
#                                          channel
#   mixer  = y W_out
# The recurrence is run as written, a token at a time.
#
# "attention": q, k, v = u W_q, u W_k, u W_v, num_attention_heads query heads
# on num_key_value_heads KV heads of hidden_size / num_attention_heads, KV
# head j serving the query heads j*r..j*r+r-1, no bias, no positions of any
# kind (position_embedding_type nope), no QK-norm;
#   mixer  = softmax_{j<=i}(attention_multiplier * q k^T) v W_o        1/64, in
#                                          place of head_dim ** -0.5 = 1/8
#
# Loss: next-token cross-entropy, the logits at position i against the token
# at i + 1, the mean over the s - 1 positions that have a next token; no
# auxiliary term.
#
# Nothing is chunked, tiled or cached: the recurrence a token at a time, the
# convolution L shifted sums, attention a dense masked softmax over all keys.
#
# Departures from the published description:
# - the parameter tree is the program's: a layer holds "ln1" and "mamba"
#   (in_proj [d, 2HP + 2GN + H] in the order z, x, B, C, dt; conv [HP + 2GN,
#   L], conv_bias, dt_bias, A_log, D, norm [HP], out_proj [HP, d]) or "ln1"
#   and "attn" (q [d, heads*head_dim], k and v fused as "kv", k's heads
#   first, out), then "ln2", ffn_gate, ffn_up [d, width] (the release's
#   input_linear, its halves in the order gate, up) and ffn_down [width, d];
#   "embed", "ln_f";
# - a share of the model (the configuration's ``deployment``): the layers
#   here are the published layers ``layers_held``, each of its published
#   type; the vocabulary is the slice's, embedding and readout alike;
# - the chunk of the release's kernels (mamba_chunk_size) appears nowhere:
#   it blocks the same sum;
# - blocks (one layer; inside it 128 tokens of the recurrence, one head and
#   1024 of its queries, 1024 positions of the readout at a time; each
#   recomputed in the backward pass) bound the memory; they change no
#   result.
#
# ``wrong`` names what a check may break on purpose, so that the limits of
# ``correct`` can be shown to refuse it: the four scalars one at a time
# ("no_embedding_multiplier", "no_residual_multiplier", "no_logits_scaling":
# 1 for the published value; "scores_over_sqrt_head": head_dim ** -0.5 for
# attention_multiplier), "norm_before_gate" (RMSNorm(y) * silu(z)),
# "up_as_gate" (silu(W_u x) * W_g x), "decay_without_dt" (exp(a) for
# exp(delta a)), "rope" (rotary positions at rope_theta in the attention
# layer).

import functools

import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 1024
_HEAD_BLOCK = 1024
_TOKEN_BLOCK = 128


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def layer_plan(sizes):
    """The types of the layers held here, each as the published layer it is:
    "mamba" or "attention"."""
    return [sizes["layer_types"][p] for p in sizes["layers_held"]]


def _scalar(sizes, name, wrong):
    return 1.0 if "no_" + name in wrong else float(sizes[name])


def _recurrence(x, delta, a, b, c, wrong=()):
    """One sequence, a token at a time: x [s, H, P], delta [s, H], a [H],
    b, c [s, G, N] -> y [s, H, P] without the D term."""
    s, heads, p = x.shape
    groups, n = b.shape[1:]
    group = jnp.arange(heads) // (heads // groups)

    def token(state, now):
        x_t, delta_t, b_t, c_t = now
        decay = jnp.exp(a) if "decay_without_dt" in wrong \
            else jnp.exp(delta_t * a)
        state = decay[:, None, None] * state \
            + (delta_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :]
        return state, jnp.sum(state * c_t[group][:, None, :], axis=-1)

    block = _TOKEN_BLOCK if s % _TOKEN_BLOCK == 0 else s

    @jax.checkpoint
    def tokens(state, these):
        return lax.scan(token, state, these)

    _, y = lax.scan(
        tokens, jnp.zeros((heads, p, n), x.dtype),
        tuple(t.reshape((s // block, block) + t.shape[1:])
              for t in (x, delta, b, c)))
    return y.reshape(s, heads, p)


def _mamba(p, u, sizes, wrong=()):
    """One sequence: u [s, d] -> [s, d]."""
    s = u.shape[0]
    heads, hp = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    groups, n = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    inner = heads * hp
    zxbcdt = u @ p["in_proj"]["kernel"]
    z, xbc = zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * groups * n]
    dt = zxbcdt[:, 2 * inner + 2 * groups * n:]
    taps = p["conv"].shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), u.dtype),
                              xbc])
    xbc = jax.nn.silu(sum(p["conv"][:, j] * padded[j:j + s]
                          for j in range(taps)) + p["conv_bias"])
    x = xbc[:, :inner].reshape(s, heads, hp)
    b = xbc[:, inner:inner + groups * n].reshape(s, groups, n)
    c = xbc[:, inner + groups * n:].reshape(s, groups, n)
    y = _recurrence(x, jax.nn.softplus(dt + p["dt_bias"]),
                    -jnp.exp(p["A_log"]), b, c, wrong)
    y = (y + p["D"][:, None] * x).reshape(s, inner)
    eps = sizes["rms_norm_eps"]

    def norm(t):
        return _rms_norm(t.reshape(s, groups, inner // groups), 1.0,
                         eps).reshape(s, inner)

    y = norm(y) * jax.nn.silu(z) if "norm_before_gate" in wrong \
        else norm(y * jax.nn.silu(z))
    return (y * p["norm"]) @ p["out_proj"]["kernel"]


def _rotated(x, theta):
    """Rotary positions on [s, heads, d], the halves rotated: there for the
    "rope" fault alone."""
    s, _, d = x.shape
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] \
        / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos = jnp.cos(angles)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p, x, sizes, wrong=()):
    """One sequence: x [s, d] -> [s, d], causal, no positions."""
    s = x.shape[0]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = sizes["hidden_size"] // h
    scale = dh ** -0.5 if "scores_over_sqrt_head" in wrong \
        else float(sizes["attention_multiplier"])
    q = (x @ p["q"]["kernel"]).reshape(s, h, dh)
    kv = (x @ p["kv"]["kernel"]).reshape(s, 2 * h_kv, dh)
    k, v = kv[:, :h_kv], kv[:, h_kv:]
    if "rope" in wrong:
        theta = sizes["rope_theta"]
        q, k = _rotated(q, theta), _rotated(k, theta)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    qb = min(_QUERY_BLOCK, s)
    assert s % qb == 0
    # Each query block's rows of the [s, s] table, made once, outside the
    # loop over heads.
    tables = [(start, jnp.arange(s)[None, :]
               <= start + jnp.arange(qb)[:, None])
              for start in range(0, s, qb)]

    @jax.checkpoint
    def one_block(q_block, k_head, v_head, seen):
        scores = q_block @ k_head.T * scale
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) \
            @ v_head

    @jax.checkpoint
    def one_head(args):
        head, q_head = args                                # q_head [s, dh]
        kv_head = head // (h // h_kv)
        return jnp.concatenate([
            one_block(q_head[start:start + qb], k[kv_head], v[kv_head], seen)
            for start, seen in tables])

    out = lax.map(one_head, (jnp.arange(h), q.transpose(1, 0, 2)))
    out = out.reshape(h, s, dh).transpose(1, 0, 2).reshape(s, h * dh)
    return out @ p["out"]["kernel"]


def _ffn(p, x, wrong=()):
    """x [n, d] -> [n, d]: the dense SwiGLU."""
    gate, up = x @ p["ffn_gate"]["kernel"], x @ p["ffn_up"]["kernel"]
    if "up_as_gate" in wrong:
        gate, up = up, gate
    return (jax.nn.silu(gate) * up) @ p["ffn_down"]["kernel"]


def hidden_states(params, tokens, sizes, wrong=()):
    """tokens [b, s] -> the hidden state before the final norm [b, s, d]."""
    eps = sizes["rms_norm_eps"]
    residual = _scalar(sizes, "residual_multiplier", wrong)
    x = params["embed"]["embedding"][tokens] \
        * _scalar(sizes, "embedding_multiplier", wrong)
    b, s, d = x.shape

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def layer(p, h, kind):
        u = _rms_norm(h, p["ln1"]["scale"], eps)
        if kind == "mamba":
            y = lax.map(lambda row: _mamba(p["mamba"], row, sizes, wrong), u)
        elif kind == "attention":
            y = lax.map(lambda row: _attention(p["attn"], row, sizes, wrong),
                        u)
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        h = h + residual * y
        m = _rms_norm(h, p["ln2"]["scale"], eps).reshape(b * s, d)
        return h + residual * _ffn(p, m, wrong).reshape(b, s, d)

    for i, kind in enumerate(layer_plan(sizes)):
        x = layer(params[f"layer_{i}"], x, kind)
    return x


def _computed_in(params, dtype):
    """(the parameters, the matmul precision) for a pass in ``dtype``: the
    reference is float32 at the highest precision; any other ``dtype`` is
    there for the checks that tell a lower precision apart, and then
    everything is in it: parameters, norms, the convolution, delta, the
    decays and the state, softmax and loss, the matmuls at the default
    precision."""
    if dtype == jnp.float32:
        return params, jax.default_matmul_precision("highest")
    return (jax.tree_util.tree_map(lambda x: x.astype(dtype), params),
            jax.default_matmul_precision("default"))


def logits(params, batch, sizes, dtype=jnp.float32, wrong=()):
    """Logits of every position, [b, s, vocab], in ``dtype``."""
    params, precision = _computed_in(params, dtype)
    with precision:
        x = hidden_states(params, batch["tokens"], sizes, wrong)
        x = _rms_norm(x, params["ln_f"]["scale"], sizes["rms_norm_eps"])
        return x @ params["embed"]["embedding"].T \
            / _scalar(sizes, "logits_scaling", wrong)


def _weighted_nll(x, head, labels, weights, scaling):
    """sum_i weights_i * -log softmax(x_i @ head / scaling)[labels_i], the
    head applied to ``_HEAD_BLOCK`` positions at a time; x [n, d]."""
    n, d = x.shape
    block = min(_HEAD_BLOCK, n)
    assert n % block == 0
    shape = (n // block, block)

    @jax.checkpoint
    def one_block(total, xs):
        xb, lb, wb = xs
        logp = jax.nn.log_softmax(xb @ head / scaling, axis=-1)
        picked = jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]
        return total - jnp.sum(picked * wb), None

    total, _ = lax.scan(one_block, jnp.zeros((), x.dtype),
                        (x.reshape(shape + (d,)), labels.reshape(shape),
                         weights.reshape(shape)))
    return total


def loss(params, aux, batch, *, sizes, dtype=jnp.float32, wrong=()):
    """``(params, aux, batch) -> (loss, aux)``.  ``batch``: ``tokens``
    [b, s].  The model keeps no state from step to step, so ``aux`` goes
    through as it came.  ``dtype`` and ``wrong`` are there for the checks
    that tell a lower precision and a wrong layer apart (``_computed_in``,
    the note above)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    # Position i is held to token i + 1; the last position has none.
    labels = jnp.roll(tokens, -1, axis=1)
    weights = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s)).astype(dtype)
    params, precision = _computed_in(params, dtype)
    with precision:
        x = hidden_states(params, tokens, sizes, wrong)
        x = _rms_norm(x, params["ln_f"]["scale"], sizes["rms_norm_eps"])
        nll = _weighted_nll(x.reshape(b * s, -1),
                            params["embed"]["embedding"].T,
                            labels.reshape(-1), weights.reshape(-1),
                            _scalar(sizes, "logits_scaling", wrong))
    return nll / (b * (s - 1)), aux


def make_loss(sizes, **variant):
    return functools.partial(loss, sizes=sizes, **variant)
