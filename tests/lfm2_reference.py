"""The plain reference of LFM2-8B-A1B's layers in training: forward pass, the
next-token loss, the router counts and the selection bias's step, in float32
at the highest matmul precision.  It imports ``jax`` and ``jax.numpy`` and
nothing of ``horovod_tpu``; it takes the program's parameter tree.

This copy serves the tier-1 tests (``tests/test_lfm2.py``);
``chip_bench/configs/lfm2-8b-a1b_reference.py`` is the benchmark's own, and
``tests/test_olmoe.py::test_reference_copies_share_their_text`` holds the two
to the same text below the marker line.
"""
# ---- below this line the two copies are the same text ----
# The architecture as published (LiquidAI/LFM2-8B-A1B config.json, model_type
# lfm2_moe, and the model code of that type).  With h a layer's input, d =
# hidden_size, L = conv_L_cache:
#
#   a      = RMSNorm_op(h)
#   u      = h + Mixer(a)
#   m      = RMSNorm_ffn(u)
#   h'     = u + FFN(m)
#
# Mixer, where layer_types[l] is "conv":
#   B,C,X  = split3(a W_in)                  W_in [d, 3d], in that order
#   z_t    = B_t * X_t
#   c_t    = sum_{j<L} w[:, j] * z_{t-(L-1)+j}     z = 0 before position 0;
#                                            one filter of L taps a channel,
#                                            never across sequences
#   Mixer  = (C * c) W_out
# and where it is "full_attention":
#   q,k,v  = a W_q, a W_k, a W_v             32 / 8 / 8 heads of d/32 = 64, no
#                                            bias; KV head j serves query heads
#                                            4j..4j+3
#   q,k    = RoPE(RMSNorm_q(q)), RoPE(RMSNorm_k(k))   the norm over each
#                                            head's 64, one scale the heads
#                                            share; halves rotated, theta 1e6
#   Mixer  = softmax_{j<=i}(q k^T / sqrt(64)) v W_o
#
# FFN, in the first num_dense_layers published layers:
#   W_2 (silu(W_1 m) * W_3 m)                width intermediate_size
# and in the others:
#   s      = sigmoid(m W_r)                  all num_experts, in fp32
#   top    = the num_experts_per_tok largest of s + b
#   p_e    = s_e / (sum_{e in top} s_e + 1e-6) * routed_scaling_factor
#   FFN    = sum_{e in top} p_e W_2,e (silu(W_1,e m) * W_3,e m)
# b [num_experts] is state, not a parameter: it enters the choice and nothing
# else, no gradient reaches it, and after every step
#   b <- b + rate * sign(mean_e(n) - n)      n_e the rows routed to expert e in
#                                            that step, over the whole batch.
#
# Then a final RMSNorm and the readout against the embedding (tied).  Loss:
# next-token cross-entropy, the logits at position i against the token at
# i + 1, the mean over the s - 1 positions that have a next token; no
# auxiliary term.
#
# Nothing is sorted, grouped, tiled or cached: the convolution is L shifted
# sums, attention a dense masked softmax over all keys, each held expert is
# applied densely to every position under a mask, one at a time.
#
# Departures from the published description:
# - the parameter tree is the program's: in_proj [d, 3d], conv [d, L],
#   out_proj [d, d]; q [d, 32*64]; k and v fused as "kv" [d, 2*8*64] (k's
#   heads first); ffn_gate, ffn_up, ffn_down for W_1, W_3, W_2; the held
#   experts stacked on a leading axis in the order of ``experts_held``;
# - a share of the model (the configuration's ``deployment``): the layers
#   here are the published layers ``layers_held``, each of its published
#   kind; of the 32 experts the 8 in ``experts_held`` live here.  The router,
#   its scores, the bias, the top 4, the renormalisation and the counts are
#   over all 32; what the absent experts would add is left out, and that
#   partial sum goes on to the next layer.  The vocabulary is the slice's:
#   embedding, readout, softmax and loss are over ``vocab_size`` ids;
# - the bias's rule and rate, the absence of an auxiliary loss, the tied
#   readout, the per-head QK-norm and the final norm are ``assumed`` in the
#   configuration's file, which says why: config.json gives
#   ``use_expert_bias`` and nothing of its training;
# - blocks (one layer; inside it one head and 1024 of its queries, one
#   expert, 1024 positions of the readout at a time; each recomputed in the
#   backward pass) bound the memory; they change no result.
#
# ``wrong`` names what a check may break on purpose, so that
# ``chip_bench/tools/lfm2_reference_check.py`` can show that the limits of
# ``correct`` refuse it: "taps_reversed" (w[:, L-1-j] for w[:, j]),
# "no_c_gate" (Mixer = c W_out), "softmax" (for the sigmoid, the top 4
# renormalised the same way), "bias_in_weights" (p from s + b, not from s),
# "no_qk_norm", "scale_128" (scores over sqrt(128)).

import functools

import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 1024
_HEAD_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta, index):
    """[s, h, dh] at the indices [s]: x*cos + rotate_half(x)*sin."""
    dh = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = index.astype(x.dtype)[:, None] * inv_freq.astype(x.dtype)[None]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def layer_plan(sizes):
    """[(mixer, ffn)] of the layers held here: ``"conv"`` or
    ``"full_attention"``, ``"dense"`` or ``"experts"``, each as the published
    layer it is."""
    return [(sizes["layer_types"][p],
             "dense" if p < sizes["num_dense_layers"] else "experts")
            for p in sizes["layers_held"]]


def _short_conv(p, x, wrong=()):
    """One sequence: x [s, d] -> [s, d]."""
    s, d = x.shape
    w = p["conv"]                                          # [d, L]
    taps = w.shape[1]
    if "taps_reversed" in wrong:
        w = w[:, ::-1]
    bcx = x @ p["in_proj"]["kernel"]
    gate_in, gate_out, xs = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = jnp.concatenate([jnp.zeros((taps - 1, d), x.dtype), gate_in * xs])
    c = sum(w[:, j] * z[j:j + s] for j in range(taps))
    if "no_c_gate" not in wrong:
        c = gate_out * c
    return c @ p["out_proj"]["kernel"]


def _attention(p, x, sizes, wrong=()):
    """One sequence: x [s, d] -> [s, d], causal."""
    s, d = x.shape
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = d // h
    eps = sizes["norm_eps"]
    q = (x @ p["q"]["kernel"]).reshape(s, h, dh)
    kv = (x @ p["kv"]["kernel"]).reshape(s, 2 * h_kv, dh)
    k, v = kv[:, :h_kv], kv[:, h_kv:]
    if "no_qk_norm" not in wrong:
        q = _rms_norm(q, p["q_norm"]["scale"], eps)
        k = _rms_norm(k, p["k_norm"]["scale"], eps)
    q = _rope(q, sizes["rope_theta"], jnp.arange(s))
    k = _rope(k, sizes["rope_theta"], jnp.arange(s))
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)      # [h_kv, s, dh]
    scale = (128 if "scale_128" in wrong else dh) ** -0.5
    qb = min(_QUERY_BLOCK, s)
    assert s % qb == 0
    # Each query block's rows of the [s, s] table, made once, outside the
    # loop over heads: made inside it, every head's copy would be kept for
    # the backward pass.
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


def _dense_ffn(p, x):
    return (jax.nn.silu(x @ p["ffn_gate"]["kernel"])
            * (x @ p["ffn_up"]["kernel"])) @ p["ffn_down"]["kernel"]


def _experts(p, bias, x, sizes, wrong=()):
    """x [n, d], bias [E] -> (the held experts' part of y [n, d], counts over
    all experts [E])."""
    logits = x @ p["router"]
    n_experts = logits.shape[-1]
    scores = jax.nn.softmax(logits, axis=-1) if "softmax" in wrong \
        else jax.nn.sigmoid(logits)
    biased = scores + lax.stop_gradient(bias.astype(scores.dtype))
    _, chosen = lax.top_k(biased, sizes["num_experts_per_tok"])
    weights = jnp.take_along_axis(
        biased if "bias_in_weights" in wrong else scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    weights = weights * sizes["routed_scaling_factor"]

    @jax.checkpoint
    def one_expert(y, ew):
        e, gate, up, down = ew
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # [n]
        return y + w[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down), \
            None

    y, _ = lax.scan(one_expert, jnp.zeros_like(x),
                    (jnp.asarray(sizes["experts_held"]), p["experts_gate"],
                     p["experts_up"], p["experts_down"]))
    counts = jnp.sum(chosen[..., None] == jnp.arange(n_experts), axis=(0, 1))
    return y, counts.astype(jnp.int32)


def hidden_states(params, bias, tokens, sizes, wrong=()):
    """tokens [b, s], bias [expert layers, E] -> (hidden before the final
    norm [b, s, d], counts [expert layers, E])."""
    eps = sizes["norm_eps"]
    x = params["embed"]["embedding"][tokens]
    b, s, d = x.shape
    counts = []

    @functools.partial(jax.checkpoint, static_argnums=(3, 4))
    def layer(p, layer_bias, h, mixer, ffn):
        a = _rms_norm(h, p["ln1"]["scale"], eps)
        if mixer == "conv":
            mixed = lax.map(lambda row: _short_conv(p["conv"], row, wrong), a)
        elif mixer == "full_attention":
            mixed = lax.map(
                lambda row: _attention(p["attn"], row, sizes, wrong), a)
        else:
            raise ValueError(f"unknown layer type {mixer!r}")
        u = h + mixed
        m = _rms_norm(u, p["ln2"]["scale"], eps).reshape(b * s, d)
        if ffn == "dense":
            return u + _dense_ffn(p, m).reshape(b, s, d), None
        y, c = _experts(p, layer_bias, m, sizes, wrong)
        return u + y.reshape(b, s, d), c

    for i, (mixer, ffn) in enumerate(layer_plan(sizes)):
        layer_bias = bias[len(counts)] if ffn == "experts" else None
        x, c = layer(params[f"layer_{i}"], layer_bias, x, mixer, ffn)
        if c is not None:
            counts.append(c)
    return x, jnp.stack(counts)


def _computed_in(params, dtype):
    """(the parameters, the matmul precision) for a pass in ``dtype``: the
    reference is float32 at the highest precision; any other ``dtype`` is
    there for the checks that tell a lower precision apart, and then
    everything is in it: parameters, norms, rotary angles, gates and taps,
    router, softmax and loss, the matmuls at the default precision."""
    if dtype == jnp.float32:
        return params, jax.default_matmul_precision("highest")
    return (jax.tree_util.tree_map(lambda x: x.astype(dtype), params),
            jax.default_matmul_precision("default"))


def zero_bias(sizes):
    n = sum(ffn == "experts" for _, ffn in layer_plan(sizes))
    return jnp.zeros((n, sizes["num_experts_published"]), jnp.float32)


def logits(params, batch, sizes, dtype=jnp.float32, wrong=(), bias=None):
    """Logits of every position, [b, s, vocab], in ``dtype``; ``bias``
    [expert layers, E], zeros by default."""
    bias = zero_bias(sizes) if bias is None else bias
    params, precision = _computed_in(params, dtype)
    with precision:
        x = hidden_states(params, bias, batch["tokens"], sizes, wrong)[0]
        return _rms_norm(x, params["ln_f"]["scale"], sizes["norm_eps"]) \
            @ params["embed"]["embedding"].T


def _weighted_nll(x, head, labels, weights):
    """sum_i weights_i * -log softmax(x_i @ head)[labels_i], the head applied
    to ``_HEAD_BLOCK`` positions at a time; x [n, d]."""
    n, d = x.shape
    block = min(_HEAD_BLOCK, n)
    assert n % block == 0
    shape = (n // block, block)

    @jax.checkpoint
    def one_block(total, xs):
        xb, lb, wb = xs
        logp = jax.nn.log_softmax(xb @ head, axis=-1)
        picked = jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]
        return total - jnp.sum(picked * wb), None

    total, _ = lax.scan(one_block, jnp.zeros((), x.dtype),
                        (x.reshape(shape + (d,)), labels.reshape(shape),
                         weights.reshape(shape)))
    return total


def step_bias(bias, counts, rate):
    """b + rate * sign(mean_e(n) - n), a layer."""
    n = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(n, axis=-1, keepdims=True) - n)


def loss(params, aux, batch, *, sizes, dtype=jnp.float32, wrong=()):
    """``(params, aux, batch) -> (loss, new aux)``.  ``batch``: ``tokens``
    [b, s].  ``aux`` carries the router counters and the selection bias
    (``expert_bias`` [expert layers, E]), which the new ``aux`` holds stepped
    by this step's counts.  ``dtype`` and ``wrong`` are there for the checks
    that tell a lower precision and a wrong layer apart (``_computed_in``, the
    note above)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    # Position i is held to token i + 1; the last position has none.
    labels = jnp.roll(tokens, -1, axis=1)
    weights = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s)).astype(dtype)
    params, precision = _computed_in(params, dtype)
    with precision:
        x, counts = hidden_states(params, aux["expert_bias"], tokens, sizes,
                                  wrong)
        x = _rms_norm(x, params["ln_f"]["scale"], sizes["norm_eps"])
        nll = _weighted_nll(x.reshape(b * s, -1),
                            params["embed"]["embedding"].T,
                            labels.reshape(-1), weights.reshape(-1))
    here = jnp.sum(counts[:, jnp.asarray(sizes["experts_held"])], axis=1)
    return nll / (b * (s - 1)), {
        "tokens_per_expert": aux["tokens_per_expert"] + counts,
        "steps": aux["steps"] + 1,
        "rows_held": aux["rows_held"] + here,
        "rows_elsewhere": aux["rows_elsewhere"] + jnp.sum(counts, axis=1)
        - here,
        "expert_bias": step_bias(aux["expert_bias"], counts,
                                 sizes["expert_bias_update_rate"])}


def make_loss(sizes, **variant):
    return functools.partial(loss, sizes=sizes, **variant)
