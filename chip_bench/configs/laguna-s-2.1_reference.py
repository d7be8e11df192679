"""The plain reference of Laguna-S-2.1's layers in training: forward pass,
cross-entropy and the router counts, in float32 at the highest matmul
precision.  It imports ``jax`` and ``jax.numpy`` and nothing of
``horovod_tpu``; it takes the program's parameter tree.  The one copy: the
benchmark decides ``correct`` by it and tier-1 loads it through
``tests/helpers.py::load_reference``.
"""
# The architecture as published (poolside/Laguna-S-2.1 config.json, model_type
# laguna).  k the layer's kind (layer_types: full_attention | sliding_attention),
# H_k its query heads (num_attention_heads_per_layer: 48 | 72), 8 KV heads,
# d = head_dim 128, kv(h) = h // (H_k / 8):
#
#   h        = E[t]
#   u        = RMSNorm(h)                                      eps 1e-6
#   q        = u W_q  [s, H_k, d]   k = u W_k, v = u W_v  [s, 8, d]
#   g        = sigmoid(u W_g)  [s, H_k]                        gating: per-head
#   full:      q, k <- only dims 0..63 of a head turned (the halves rotated,
#              x cos + rotate_half(x) sin, as transformers), angles pos f_i,
#              i = 0..31, YaRN (rope_parameters.full_attention):
#              f_i = (1 - r_i) t^(-2i/64) / 128 + r_i t^(-2i/64),  t = 500,000
#              r_i = 1 - clip((i - low) / (high - low), 0, 1)
#              low = floor(c(32)) = 9,  high = ceil(c(1)) = 18
#              c(n) = 64 ln(8192 / (2 pi n)) / (2 ln t)    (9.04 and 17.49)
#              cos and sin times attention_factor 1.4852030263919618
#              (= 0.1 ln 128 + 1); dims 64..127 go through as they are, and
#              nothing goes into the softmax's scale
#   sliding:   q, k <- all 128 dims turned, f_i = 10,000^(-2i/128), i = 0..63
#   a_h      = softmax_j(q_h . k_kv(h) / sqrt(d) + mask) v_kv(h)
#              mask: j <= i; sliding: and i - j < sliding_window 512
#   h        = h + concat_h(g_h a_h) W_o
#   y        = RMSNorm(h)
#   layer 0:   h = h + W_down(silu(y W_gate) * (y W_up))       width 12,288
#   else:      p = softmax(y W_r) over all 256;  T = top 10 of p
#              w_e = 2.5 p_e / sum_T p
#              h = h + sum_{e in T} w_e E_e(y) + E_shared(y)
#              E = W_down(silu(. W_gate) * (. W_up)), width 1024
#   logits   = RMSNorm(h_L) W_head
#
# Loss: the mean cross-entropy over the s - 1 positions that have a next
# token; no auxiliary term.
#
# Nothing is sorted, grouped, tiled, fused or laid out for a chip: attention
# is a dense masked softmax over all keys, a query head at a time with its KV
# head looked up (repeated for its group), the gate a broadcast over the
# head's width, each held expert applied densely to every position under a
# mask, one at a time.
#
# Departures from the published description:
# - what config.json does not settle is ``assumed`` in the configuration's
#   file, each with its source: the gate's input (the normed state the
#   queries read) and its sigmoid; no QK-norm; softmax scores without a
#   selection bias; the shared expert ungated; the router's weights on the
#   experts' outputs; YaRN as transformers' ``_compute_yarn_parameters`` on a
#   rotary width of 64 with the range truncated to whole pairs;
# - the parameter tree is the program's: a layer holds "ln1", "attn" (q
#   [d, H_k d]; kv [d, 2 x 8 d], the 8 key heads before the 8 value heads;
#   gate [d, H_k]; out [H_k d, d]), "ln2" and ffn_gate, ffn_up, ffn_down, or
#   router [d, E], the held experts stacked on a leading axis as
#   experts_gate, experts_up [e, d, width] and experts_down [e, width, d] in
#   the order of ``experts_held``, shared_gate, shared_up, shared_down;
# - a share of the model (the configuration's ``deployment``): the layers
#   here are the published layers ``layers_held``, each with its own entry
#   of layer_types, mlp_layer_types and num_attention_heads_per_layer;
#   num_experts counts the experts held here: the router, its softmax, the
#   top k, the renormalisation and the counts are over all
#   ``num_experts_published``; what the absent experts would add is left out,
#   the shared expert is whole, and that partial sum goes on to the next
#   layer.  The vocabulary is the slice's;
# - blocks (one layer; inside it one query head and 1024 of its queries, one
#   expert, 1024 positions of the dense FFN and of the readout at a time;
#   each recomputed in the backward pass) bound the memory; they change no
#   result.
#
# ``wrong`` names what a check may break on purpose, so that
# ``chip_bench/tools/laguna_reference_check.py`` and ``tests/test_laguna.py``
# can show that the limits of ``correct`` refuse it: "yarn_in_sliding" (a
# sliding layer turned by the full layers' table), "plain_in_full" (a full
# layer by the sliding layers'), "no_attention_factor" (cos and sin as they
# are), "whole_head_turned" (a full layer's table over all 128),
# "window_1024", "no_gate", "gate_a_channel" (the gate's columns laid over the
# concatenated heads a channel at a time: channel j takes column j mod H_k),
# "no_routed_scale" (w_e without its 2.5), "sigmoid_scores".

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 1024
_HEAD_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def layer_kind(sizes, i):
    """(layer type, query heads, whether the FFN is the dense one) of layer
    ``i`` here, the published layer ``layers_held[i]``."""
    p = sizes["layers_held"][i]
    return (sizes["layer_types"][p], sizes["num_attention_heads_per_layer"][p],
            sizes["mlp_layer_types"][p] == "dense")


def expert_blocks(sizes):
    """The indices of the layers with experts."""
    return [i for i in range(sizes["num_hidden_layers"])
            if not layer_kind(sizes, i)[2]]


def yarn_range(rope, width):
    """(low, high): the rotary pairs between which the frequencies blend."""
    def pair(turns):
        return width * math.log(rope["original_max_position_embeddings"]
                                / (turns * 2 * math.pi)) \
            / (2 * math.log(rope["rope_theta"]))

    return (max(math.floor(pair(rope["beta_fast"])), 0),
            min(math.ceil(pair(rope["beta_slow"])), width - 1))


def rotary_table(sizes, layer_type, wrong=()):
    """(the width of a head that turns, f_i for i = 0..width/2 - 1, the
    factor on cos and sin) of a layer of ``layer_type``."""
    if "yarn_in_sliding" in wrong and layer_type == "sliding_attention":
        layer_type = "full_attention"
    elif "plain_in_full" in wrong and layer_type == "full_attention":
        layer_type = "sliding_attention"
    rope = sizes["rope_parameters"][layer_type]
    share = rope["partial_rotary_factor"]
    if "whole_head_turned" in wrong:
        share = 1
    width = int(sizes["head_dim"] * share)
    # As transformers writes them, 1 / t^(2i/width): at position 8191 an ulp
    # of a frequency is 5e-4 of a radian, so the form is part of the answer.
    i = jnp.arange(0, width, 2, dtype=jnp.float32)
    plain = 1.0 / float(rope["rope_theta"]) ** (i / width)
    if rope["rope_type"] == "default":
        return width, plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    low, high = yarn_range(rope, width)
    r = 1.0 - jnp.clip((i / 2 - low) / ((high - low) or 0.001), 0.0, 1.0)
    factor = 1.0 if "no_attention_factor" in wrong \
        else rope["attention_factor"]
    return width, (1.0 - r) * plain / rope["factor"] + r * plain, factor


def _turn(x, width, freq, factor):
    """x [s, heads, d]: the first ``width`` of every head turned, the halves
    rotated: position t's angle t freq[i] on dims i and i + width / 2."""
    s = x.shape[0]
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos = (jnp.cos(angles) * factor).astype(x.dtype)[:, None, :]
    sin = (jnp.sin(angles) * factor).astype(x.dtype)[:, None, :]
    x1, x2 = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., width:]], axis=-1)


def _attention(p, x, sizes, layer_type, heads, wrong=()):
    """One sequence: x [s, hidden] -> [s, hidden]."""
    s = x.shape[0]
    h_kv, d = sizes["num_key_value_heads"], sizes["head_dim"]
    group = heads // h_kv
    q = (x @ p["q"]["kernel"]).reshape(s, heads, d)
    kv = (x @ p["kv"]["kernel"]).reshape(s, 2 * h_kv, d)
    k, v = kv[:, :h_kv], kv[:, h_kv:]
    gate = jax.nn.sigmoid(x @ p["gate"]["kernel"])                 # [s, H]
    width, freq, factor = rotary_table(sizes, layer_type, wrong)
    q, k = _turn(q, width, freq, factor), _turn(k, width, freq, factor)
    window = sizes["sliding_window"] if layer_type == "sliding_attention" \
        else 0
    if "window_1024" in wrong and window:
        window = 1024
    qb = min(_QUERY_BLOCK, s)
    assert s % qb == 0

    def seen(start):
        i = start + jnp.arange(qb)[:, None]
        j = jnp.arange(s)[None, :]
        return (j <= i) & (i - j < window) if window else j <= i

    tables = [(start, seen(start)) for start in range(0, s, qb)]

    @jax.checkpoint
    def one_block(q_block, keys, values, mask):
        scores = q_block @ keys.T / math.sqrt(d)
        return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1) \
            @ values

    @jax.checkpoint
    def one_head(q_head, keys, values):             # [s, d] each
        return jnp.concatenate([
            one_block(q_head[start:start + qb], keys, values, mask)
            for start, mask in tables])

    def one_kv_head(args):
        # The KV head repeated for the query heads it serves: one after
        # another, each against the same keys and values.
        q_group, keys, values = args                # [group, s, d], [s, d] x 2
        return lax.map(lambda q_head: one_head(q_head, keys, values), q_group)

    out = lax.map(one_kv_head, (
        q.reshape(s, h_kv, group, d).transpose(1, 2, 0, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.reshape(heads, s, d).transpose(1, 0, 2)              # [s, H, d]
    if "gate_a_channel" in wrong:
        out = (out.reshape(s, heads * d) * jnp.tile(gate, (1, d))) \
            .reshape(s, heads, d)
    elif "no_gate" not in wrong:
        out = out * gate[:, :, None]
    return out.reshape(s, heads * d) @ p["out"]["kernel"]


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def _experts(p, m, sizes, wrong=()):
    """m [n, d] -> (the held experts' part of the routed sum plus the shared
    expert, [n, d]; counts over all experts [E])."""
    logits = m @ p["router"]
    scores = jax.nn.sigmoid(logits) if "sigmoid_scores" in wrong \
        else jax.nn.softmax(logits, axis=-1)
    n_experts = scores.shape[-1]
    weights, chosen = lax.top_k(scores, sizes["num_experts_per_tok"])
    if sizes["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if "no_routed_scale" not in wrong:
        weights = weights * sizes["moe_routed_scaling_factor"]

    @jax.checkpoint
    def one_expert(routed, ew):
        e, gate, up, down = ew
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # [n]
        return routed + w[:, None] * _swiglu(m, gate, up, down), None

    routed, _ = lax.scan(one_expert, jnp.zeros_like(m),
                         (jnp.asarray(sizes["experts_held"]),
                          p["experts_gate"], p["experts_up"],
                          p["experts_down"]))
    y = routed + _swiglu(m, p["shared_gate"]["kernel"],
                         p["shared_up"]["kernel"], p["shared_down"]["kernel"])
    counts = jnp.sum(chosen[..., None] == jnp.arange(n_experts), axis=(0, 1))
    return y, counts.astype(jnp.int32)


def hidden_states(params, tokens, sizes, wrong=()):
    """tokens [b, s] -> (the stream behind the last layer [b, s, d], counts
    [expert layers, E])."""
    eps = sizes["rms_norm_eps"]
    counts = []

    @functools.partial(jax.checkpoint, static_argnums=(2, 3, 4))
    def one_layer(p, x, layer_type, heads, dense):
        b, s, d = x.shape
        u = _rms_norm(x, p["ln1"]["scale"], eps)
        x = x + lax.map(lambda row: _attention(p["attn"], row, sizes,
                                               layer_type, heads, wrong), u)
        y = _rms_norm(x, p["ln2"]["scale"], eps)
        if dense:
            # 12,288 wide: a block of positions at a time.
            block = min(_HEAD_BLOCK, b * s)
            assert (b * s) % block == 0
            wide = jax.checkpoint(lambda rows: _swiglu(
                rows, p["ffn_gate"]["kernel"], p["ffn_up"]["kernel"],
                p["ffn_down"]["kernel"]))
            return x + lax.map(wide, y.reshape(-1, block, d)) \
                .reshape(b, s, d), None
        out, c = _experts(p, y.reshape(b * s, d), sizes, wrong)
        return x + out.reshape(b, s, d), c

    x = params["embed"]["embedding"][tokens]
    for i in range(sizes["num_hidden_layers"]):
        x, c = one_layer(params[f"layer_{i}"], x, *layer_kind(sizes, i))
        if c is not None:
            counts.append(c)
    return x, jnp.stack(counts)


def _computed_in(params, dtype):
    """(the parameters, the matmul precision) for a pass in ``dtype``: the
    reference is float32 at the highest precision; any other ``dtype`` is
    there for the checks that tell a lower precision apart, and then
    everything is in it: parameters, norms, rotary tables, the gate, router,
    softmax and loss, the matmuls at the default precision."""
    if dtype == jnp.float32:
        return params, jax.default_matmul_precision("highest")
    return (jax.tree_util.tree_map(lambda x: x.astype(dtype), params),
            jax.default_matmul_precision("default"))


def logits(params, batch, sizes, dtype=jnp.float32, wrong=()):
    """Logits of every position, [b, s, vocab], in ``dtype``."""
    params, precision = _computed_in(params, dtype)
    with precision:
        x = hidden_states(params, batch["tokens"], sizes, wrong)[0]
        return _rms_norm(x, params["ln_f"]["scale"], sizes["rms_norm_eps"]) \
            @ params["lm_head"]["kernel"]


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


def loss(params, aux, batch, *, sizes, dtype=jnp.float32, wrong=()):
    """``(params, aux, batch) -> (loss, new aux)``.  ``batch``: ``tokens``
    [b, s].  ``aux`` carries the router counters.  ``dtype`` and ``wrong``
    are there for the checks that tell a lower precision and a wrong layer
    apart."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    params, precision = _computed_in(params, dtype)
    with precision:
        x, counts = hidden_states(params, tokens, sizes, wrong)
        y = _rms_norm(x, params["ln_f"]["scale"], sizes["rms_norm_eps"])
        # Position i is held to token i + 1; the last position has none.
        weights = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s))
        total = _weighted_nll(
            y.reshape(b * s, -1), params["lm_head"]["kernel"],
            jnp.roll(tokens, -1, axis=1).reshape(-1),
            weights.astype(dtype).reshape(-1)) / (b * (s - 1))
    here = jnp.sum(counts[:, jnp.asarray(sizes["experts_held"])], axis=1)
    return total, {
        "tokens_per_expert": aux["tokens_per_expert"] + counts,
        "steps": aux["steps"] + 1,
        "rows_held": aux["rows_held"] + here,
        "rows_elsewhere": aux["rows_elsewhere"] + jnp.sum(counts, axis=1)
        - here}


def make_loss(sizes, **variant):
    return functools.partial(loss, sizes=sizes, **variant)
