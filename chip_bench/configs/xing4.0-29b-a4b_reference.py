"""The plain reference of Xing4.0-29B-A4B's layers in training: forward pass,
cross-entropy, the router counts and the selection bias's step, in float32 at
the highest matmul precision.  It imports ``jax`` and ``jax.numpy`` and
nothing of ``horovod_tpu``; it takes the program's parameter tree.  The one
copy: the benchmark decides ``correct`` by it and tier-1 loads it through
``tests/helpers.py::load_reference``.
"""
# The architecture as published (XingChen-AGI/Xing4.0-29B-A4B config.json,
# model_type xing4_0: DeepSeek-V3's keys, arXiv:2412.19437 sections 2.1, and
# the hc_* keys of manifold-constrained hyper-connections, Xie et al.,
# arXiv:2512.24880, over hyper-connections, Zhu et al., arXiv:2409.19606).
#
# The residual stream is n = hc_mult streams of C = hidden_size; X [n, C] a
# token's streams.  One hyper-connection a sublayer (a layer's attention, a
# layer's FFN) with its own phi [n C, n + n + n^2], b [n + n + n^2], alpha [3]:
#
#   X_0     = [E[t]; E[t]; E[t]; E[t]]          the embedding on every stream
#   x~      = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)     over all n C
#   H~_pre  = a_pre  (x~ phi_pre)  + b_pre      [n]      H_pre  = sigmoid(.)
#   H~_post = a_post (x~ phi_post) + b_post     [n]      H_post = 2 sigmoid(.)
#   H~_res  = a_res mat(x~ phi_res) + b_res     [n, n]   row-major: the
#             column of phi numbered 2 n + i n + j is H~_res[i, j]
#   H_res   = SK(clip(H~_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
#   SK(A)   : M_0 = exp(A);  M_t = T_r(T_c(M_{t-1})), t = 1..hc_sinkhorn_iters
#             T_c divides every column by its sum + hc_eps, T_r every row
#   u       = H_pre X                           [C]      the sublayer's input
#   y       = F(RMSNorm(u))                     F = MLA | SwiGLU | experts
#   X'      = H_res X + H_post^T y              X'_i = sum_j H_res[i, j] X_j
#                                                      + H_post[i] y
#   h_L     = sum over the n streams of X_L;  logits = Head(RMSNorm(h_L))
#
# F, attention: MLA with x = RMSNorm(u), H heads, a head's query and key
# nope = qk_nope_head_dim wide without positions beside r = qk_rope_head_dim
# rotary, values v_head_dim:
#   c_q = RMSNorm(x W_dq);  [q_n; q_r] = c_q W_uq;  [c_kv; k_r] = x W_dkv
#   c_kv = RMSNorm(c_kv);   [k_n; v] = c_kv W_ukv
#   q_r, k_r <- RoPE: the pair (2i, 2i+1) of position t turned by t f_i
#   o_j = softmax_{t<=i}(q_j . [k_n,j ; k_r] scale) v_j,   out = [o] W_o
# under YaRN (rope_scaling, DeepSeek-V3's rotary embedding), i = 0..r/2 - 1:
#   f_i  = (1 - g_i) theta^(-2i/r) / factor + g_i theta^(-2i/r)
#   g_i  = 1 - clip((i - low) / (high - low), 0, 1)
#   low  = floor(c(beta_fast)), high = ceil(c(beta_slow)), inside 0..r - 1
#   c(t) = r ln(original_max_position_embeddings / (2 pi t)) / (2 ln theta)
#   cos and sin times m(mscale) / m(mscale_all_dim), m(x) = 0.1 x ln factor + 1
#   scale = (nope + r)^-0.5 m(mscale_all_dim)^2
# (r 64, theta 10,000, factor 64 from 4096: low 10, high 23, m 1.41589, the
# tables' factor 1, scale 192^-0.5 x 2.00474 = 0.14468.)
#
# F, the FFN of the first first_k_dense_replace published layers:
#   W_2(silu(W_1 m) * W_3 m)                        width intermediate_size
# F, every other layer's, m = RMSNorm(u):
#   s    = sigmoid(m W_r)                           all n_routed_experts, fp32
#   top  = the num_experts_per_tok largest of s + b (n_group = topk_group = 1)
#   p_e  = s_e / (sum_{e in top} s_e + 1e-20) * routed_scaling_factor
#   FFN  = sum_{e in top} p_e E_e(m) + E_shared(m)  every E a SwiGLU of width
#                                                   moe_intermediate_size
# b [n_routed_experts] is state, not a parameter: it enters the choice and
# nothing else, no gradient reaches it, and after every step
#   b <- b + rate * sign(mean_e(n) - n).
#
# Loss: the mean cross-entropy over the s - 1 positions that have a next
# token; no auxiliary term; no prediction module (num_nextn_predict_layers 0
# here).
#
# Nothing is sorted, grouped, tiled, fused or laid out for a chip: the streams
# are [s, n, C] and every mix an einsum; Sinkhorn is a Python loop of
# hc_sinkhorn_iters column and row divisions on [s, n, n]; attention a dense
# masked softmax over all keys, the keys built a head at a time; each held
# expert applied densely to every position under a mask, one at a time.
#
# Departures from the published description:
# - what config.json does not settle is ``assumed`` in the configuration's
#   file, each with its source: the Sinkhorn order (columns, then rows) and
#   hc_eps in the denominators; rms_norm_eps in the flattened norm, which has
#   no scale of its own; the fan-out and the fold; the rotary pairs
#   interleaved; the bias's rule and rate;
# - the parameter tree is the program's: a layer holds "hc_mixer" and
#   "hc_ffn" (phi, bias, alpha), "ln1", "attn" (q_a, q_a_norm, q_b, kv_a the
#   latent first, kv_a_norm, kv_b a head's k_n before its v, out), "ln2" and
#   ffn_gate, ffn_up, ffn_down, or router [d, E], the held experts stacked on
#   a leading axis as experts_gate, experts_up [e, d, width] and
#   experts_down [e, width, d] in the order of ``experts_held``, shared_gate,
#   shared_up, shared_down;
# - a share of the model (the configuration's ``deployment``): the layers
#   here are the published layers ``layers_held``; n_routed_experts counts
#   the experts held here: the router, its scores, the bias, the top k, the
#   renormalisation and the counts are over all
#   ``n_routed_experts_published``; what the absent experts would add is left
#   out, the shared expert is whole, and that partial sum goes on to the next
#   sublayer.  The vocabulary is the slice's;
# - blocks (one layer, inside it one sublayer; inside that 1024 tokens of the
#   coefficients, one head and 1024 of its queries, one expert, 1024
#   positions of the readout at a time; each recomputed in the backward pass)
#   bound the memory; they change no result.
#
# ``wrong`` names what a check may break on purpose, so that
# ``chip_bench/tools/xing_reference_check.py`` and ``tests/test_xing.py`` can
# show that the limits of ``correct`` refuse it: "sinkhorn_one_iteration" (one
# iteration for hc_sinkhorn_iters), "rows_first" (T_c(T_r(.)) for
# T_r(T_c(.))), "post_without_2" (H_post = sigmoid(.)), "clamp_3" (the clip at
# -+3), "scale_without_mscale" (scores times (nope + r)^-0.5 alone),
# "plain_rope" (f_i = theta^(-2i/r): no blend).

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 1024
_HEAD_BLOCK = 1024
_TOKEN_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(sizes):
    """(low, high): the rotary pairs between which the frequencies blend."""
    r, theta = sizes["qk_rope_head_dim"], float(sizes["rope_theta"])
    y = sizes["rope_scaling"]

    def pair(turns):
        return r * math.log(y["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    return (max(math.floor(pair(y["beta_fast"])), 0),
            min(math.ceil(pair(y["beta_slow"])), r - 1))


def inv_freq(sizes, wrong=()):
    """f_i, i = 0..r/2 - 1."""
    r, theta = sizes["qk_rope_head_dim"], float(sizes["rope_theta"])
    i = jnp.arange(r // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / r)
    if sizes["rope_scaling"] is None or "plain_rope" in wrong:
        return plain
    low, high = yarn_range(sizes)
    g = 1.0 - jnp.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    return (1.0 - g) * plain / sizes["rope_scaling"]["factor"] + g * plain


def softmax_scale(sizes, wrong=()):
    scale = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5
    y = sizes["rope_scaling"]
    if y is None or "scale_without_mscale" in wrong:
        return scale
    return scale * yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2


def _rope_pairs(x, freq, factor):
    """x [s, ..., r]: the pair (2i, 2i+1) of position t turned by the angle
    t * freq[i], cos and sin times ``factor``."""
    s, r = x.shape[0], x.shape[-1]
    angles = (jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]) \
        .reshape((s,) + (1,) * (x.ndim - 2) + (r // 2,))
    cos = (jnp.cos(angles) * factor).astype(x.dtype)
    sin = (jnp.sin(angles) * factor).astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def is_dense(sizes, i):
    """Whether layer ``i`` here carries the dense FFN."""
    return sizes["layers_held"][i] < sizes["first_k_dense_replace_published"]


def expert_blocks(sizes):
    """The indices of the layers with experts."""
    return [i for i in range(sizes["num_hidden_layers"])
            if not is_dense(sizes, i)]


def _mla(p, x, sizes, wrong=()):
    """One sequence: x [s, d] -> [s, d], causal."""
    s = x.shape[0]
    h, latent = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    n, r = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, eps = sizes["v_head_dim"], sizes["rms_norm_eps"]
    c_q = _rms_norm(x @ p["q_a"]["kernel"], p["q_a_norm"]["scale"], eps)
    q = (c_q @ p["q_b"]["kernel"]).reshape(s, h, n + r)
    down = x @ p["kv_a"]["kernel"]
    c_kv = _rms_norm(down[:, :latent], p["kv_a_norm"]["scale"], eps)
    freq = inv_freq(sizes, wrong).astype(x.dtype)
    y = sizes["rope_scaling"]
    factor = 1.0 if y is None else \
        yarn_mscale(y["factor"], y["mscale"]) \
        / yarn_mscale(y["factor"], y["mscale_all_dim"])
    q_n, q_r = q[..., :n], _rope_pairs(q[..., n:], freq, factor)
    k_r = _rope_pairs(down[:, latent:], freq, factor)
    scale = softmax_scale(sizes, wrong)
    up = p["kv_b"]["kernel"].reshape(latent, h, n + dv)
    qb = min(_QUERY_BLOCK, s)
    assert s % qb == 0
    tables = [(start, jnp.arange(s)[None, :]
               <= start + jnp.arange(qb)[:, None])
              for start in range(0, s, qb)]

    @jax.checkpoint
    def one_block(q_block, keys, values, seen):
        scores = q_block @ keys.T * scale
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) \
            @ values

    @jax.checkpoint
    def one_head(args):
        q_head, up_head = args              # [s, n + r], [latent, n + dv]
        expanded = c_kv @ up_head
        keys = jnp.concatenate([expanded[:, :n], k_r], axis=-1)
        return jnp.concatenate([
            one_block(q_head[start:start + qb], keys, expanded[:, n:], seen)
            for start, seen in tables])

    queries = jnp.concatenate([q_n, q_r], axis=-1)
    out = lax.map(one_head, (queries.transpose(1, 0, 2),
                             up.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2).reshape(s, h * dv) @ p["out"]["kernel"]


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def _experts(p, bias, m, sizes):
    """m [n, d], bias [E] -> (the held experts' part of the routed sum plus
    the shared expert, [n, d]; counts over all experts [E])."""
    scores = jax.nn.sigmoid(m @ p["router"])
    n_experts = scores.shape[-1]
    _, chosen = lax.top_k(
        scores + lax.stop_gradient(bias.astype(scores.dtype)),
        sizes["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * sizes["routed_scaling_factor"]

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


def sinkhorn(a, sizes, wrong=()):
    """SK(a), a [..., n, n] (row, column): exp, then hc_sinkhorn_iters times
    every column over its sum + hc_eps and every row over its sum + hc_eps, a
    Python loop of explicit steps."""
    eps = sizes["hc_eps"]
    m = jnp.exp(a)
    steps = 1 if "sinkhorn_one_iteration" in wrong \
        else sizes["hc_sinkhorn_iters"]
    for _ in range(steps):
        if "rows_first" in wrong:
            m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
            m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        else:
            m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)   # T_c
            m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)   # T_r
    return m


def connection(p, streams, sizes, wrong=()):
    """One token block: streams [t, n, C] -> (H_pre [t, n], H_post [t, n],
    H_res [t, n, n])."""
    t, n, c = streams.shape
    flat = streams.reshape(t, n * c)
    unit = flat * lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                            + sizes["rms_norm_eps"])
    z = unit @ p["phi"]
    a, b = p["alpha"], p["bias"]
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    if "post_without_2" not in wrong:
        post = 2.0 * post
    low, high = sizes["mhc_h_res_clamp_min"], sizes["mhc_h_res_clamp_max"]
    if "clamp_3" in wrong:
        low, high = -3.0, 3.0
    logits = a[2] * z[:, 2 * n:].reshape(t, n, n) + b[2 * n:].reshape(n, n)
    return pre, post, sinkhorn(jnp.clip(logits, low, high), sizes, wrong)


def _sublayer(p, streams, fn, sizes, wrong):
    """streams [b, s, n, C] -> (X' = H_res X + H_post^T fn(H_pre X), what
    ``fn`` returns beside y, max |a row's or a column's sum of H_res - 1|)."""
    b, s, n, c = streams.shape
    block = min(_TOKEN_BLOCK, b * s)
    assert (b * s) % block == 0
    one_block = jax.checkpoint(
        lambda x: connection(p, x, sizes, wrong))
    pre, post, res = (
        x.reshape((b, s) + x.shape[2:]) for x in lax.map(
            one_block, streams.reshape(b * s // block, block, n, c)))
    u = jnp.einsum("bsj,bsjc->bsc", pre, streams)
    y, extra = fn(u)
    new = jnp.einsum("bsij,bsjc->bsic", res, streams) \
        + jnp.einsum("bsi,bsc->bsic", post, y)
    sums = lax.stop_gradient(res)
    deviation = jnp.maximum(
        jnp.max(jnp.abs(jnp.sum(sums, axis=-1) - 1.0)),
        jnp.max(jnp.abs(jnp.sum(sums, axis=-2) - 1.0)))
    return new, extra, deviation


def hidden_states(params, bias, tokens, sizes, wrong=()):
    """tokens [b, s], bias [expert layers, E] -> (the streams' sum behind the
    last layer [b, s, d], counts [expert layers, E], the largest deviation of
    H_res's row and column sums from 1)."""
    eps = sizes["rms_norm_eps"]
    experts = expert_blocks(sizes)
    counts, deviations = [], []

    @jax.checkpoint
    def attention_sublayer(p, streams):
        def attention(u):
            x = _rms_norm(u, p["ln1"]["scale"], eps)
            return lax.map(lambda row: _mla(p["attn"], row, sizes, wrong),
                           x), None

        return _sublayer(p["hc_mixer"], streams, attention, sizes, wrong)

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def ffn_sublayer(p, layer_bias, streams, dense):
        b, s, _, d = streams.shape

        def ffn(u):
            m = _rms_norm(u, p["ln2"]["scale"], eps)
            if dense:
                return _swiglu(m, p["ffn_gate"]["kernel"],
                               p["ffn_up"]["kernel"],
                               p["ffn_down"]["kernel"]), None
            y, c = _experts(p, layer_bias, m.reshape(b * s, d), sizes)
            return y.reshape(b, s, d), c

        return _sublayer(p["hc_ffn"], streams, ffn, sizes, wrong)

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def one_layer(p, layer_bias, streams, dense):
        streams, _, dev_a = attention_sublayer(p, streams)
        streams, c, dev_f = ffn_sublayer(p, layer_bias, streams, dense)
        return streams, c, jnp.maximum(dev_a, dev_f)

    x = params["embed"]["embedding"][tokens]
    streams = jnp.broadcast_to(
        x[:, :, None], x.shape[:2] + (sizes["hc_mult"],) + x.shape[2:])
    for i in range(sizes["num_hidden_layers"]):
        dense = is_dense(sizes, i)
        layer_bias = None if dense else bias[experts.index(i)]
        streams, c, deviation = one_layer(params[f"layer_{i}"], layer_bias,
                                          streams, dense)
        deviations.append(deviation)
        if c is not None:
            counts.append(c)
    return (jnp.sum(streams, axis=2), jnp.stack(counts),
            functools.reduce(jnp.maximum, deviations))


def _computed_in(params, dtype):
    """(the parameters, the matmul precision) for a pass in ``dtype``: the
    reference is float32 at the highest precision; any other ``dtype`` is
    there for the checks that tell a lower precision apart, and then
    everything is in it: parameters, norms, the hyper-connections'
    coefficients and iterations, rotary positions, router, softmax and loss,
    the matmuls at the default precision."""
    if dtype == jnp.float32:
        return params, jax.default_matmul_precision("highest")
    return (jax.tree_util.tree_map(lambda x: x.astype(dtype), params),
            jax.default_matmul_precision("default"))


def zero_bias(sizes):
    return jnp.zeros((len(expert_blocks(sizes)),
                      sizes["n_routed_experts_published"]), jnp.float32)


def logits(params, batch, sizes, dtype=jnp.float32, wrong=(), bias=None):
    """Logits of every position, [b, s, vocab], in ``dtype``; ``bias``
    [expert layers, E], zeros by default."""
    bias = zero_bias(sizes) if bias is None else bias
    params, precision = _computed_in(params, dtype)
    with precision:
        x, _, _ = hidden_states(params, bias, batch["tokens"], sizes, wrong)
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


def step_bias(bias, counts, rate):
    """b + rate * sign(mean_e(n) - n), a layer."""
    n = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(n, axis=-1, keepdims=True) - n)


def loss(params, aux, batch, *, sizes, dtype=jnp.float32, wrong=()):
    """``(params, aux, batch) -> (loss, new aux)``.  ``batch``: ``tokens``
    [b, s].  ``aux`` carries the router counters, the selection bias
    (``expert_bias`` [expert layers, E]), which the new ``aux`` holds stepped
    by this step's counts, and ``hc_deviation``, the largest deviation of a
    row's or a column's sum of any ``H_res`` of the step from 1.  ``dtype``
    and ``wrong`` are there for the checks that tell a lower precision and a
    wrong layer apart."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    params, precision = _computed_in(params, dtype)
    with precision:
        x, counts, deviation = hidden_states(params, aux["expert_bias"],
                                             tokens, sizes, wrong)
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
        - here,
        "expert_bias": step_bias(aux["expert_bias"], counts,
                                 sizes["expert_bias_update_rate"]),
        "hc_deviation": deviation.astype(jnp.float32)}


def make_loss(sizes, **variant):
    return functools.partial(loss, sizes=sizes, **variant)
