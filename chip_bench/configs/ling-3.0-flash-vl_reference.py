"""The plain reference of Ling-3.0-flash-VL's language model in training:
forward pass, the next-token loss, the router counts and the selection bias's
step, in float32 at the highest matmul precision.  It imports ``jax`` and
``jax.numpy`` and nothing of ``horovod_tpu``; it takes the program's parameter
tree.  The one copy: the benchmark decides ``correct`` by it and tier-1 loads
it through ``tests/helpers.py::load_reference``.
"""
# The architecture as published (inclusionAI/Ling-3.0-flash-VL config.json,
# model_type bailing_hybrid; the mixer is Kimi Linear's, arXiv:2510.26692, the
# latent attention and the router DeepSeek-V3's, arXiv:2412.19437).  A layer is
#
#   h <- h + Mixer(RMSNorm(h)),  h <- h + FFN(RMSNorm(h))    eps rms_norm_eps
#
# Published layer i has latent attention where (i + 1) % layer_group_size == 0
# and Kimi Delta Attention (KDA) elsewhere.
#
# KDA, with x = RMSNorm(h), H heads of K = V = head_dim, TOKEN BY TOKEN:
#   [q ; k ; v ; f ; z] = x W_in          each H * 128 wide, head by head
#   b = x W_beta                          one a head
#   [q ; k ; v] = silu(conv4([q ; k ; v]))    depthwise, causal, zero before
#                                         the sequence, as shifted sums
#   beta = sigmoid(b)
#   g = kda_lower_bound * sigmoid(exp(A_log_h) (f + dt_bias))   a key channel,
#                                         in (-5, 0)
#   q = q / |q| / sqrt(128);  k = k / |k|     x rsqrt(sum x^2 + 1e-6)
#   a head, S [128, 128] zero before the sequence:
#       S <- diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
#       o_t = S^T q_t
#   y = o / rms(o) * w_n * sigmoid(z)     a head, one w_n [128] for all
#   Mixer = y W_o
#
# Latent attention, H heads, a head's query and key n = qk_nope_head_dim wide
# without positions beside r = qk_rope_head_dim rotary, values v_head_dim:
#   [q_n; q_r] = x W_q                    a head: n + r; no query latent
#   [c_kv; k_r] = x W_dkv                 kv_lora_rank + r
#   c_kv       = RMSNorm(c_kv)
#   [k_n; v]   = c_kv W_ukv               a head: n + v_head_dim
#   q_r, k_r   <- RoPE                    k_r once: one rotary key that every
#                                         head shares; the pairs (2i, 2i+1)
#                                         turn by position * theta^(-2i/r)
#   o_j = softmax_{t<=i}(q_j . [k_n,j ; k_r] (n + r)^-0.5) v_j
#   Mixer = (concat(o) * sigmoid(x W_gate)) W_o     W_gate one column a head
#
# FFN of the first first_k_dense_replace published layers:
#   W_2(silu(W_1 m) * W_3 m)              width intermediate_size
# FFN of every other layer, with m = RMSNorm(h):
#   s    = sigmoid(m W_r)                 all num_experts_published, fp32
#   s'   = s + b                          b the selection bias
#   a group (n_group groups of neighbours) scores the sum of its two largest
#   s'; the topk_group best groups are kept, every other s' set to 0
#   top  = the num_experts_per_tok largest of what is left
#   p_e  = s_e / (sum_{e in top} s_e + 1e-20) * routed_scaling_factor
#   FFN  = sum_{e in top} p_e E_e(m) + E_shared(m)  every E a SwiGLU of width
#                                                   moe_intermediate_size
# b [experts] is state, not a parameter: after every step
#   b <- b + rate * sign(mean_e(n) - n)   n_e the rows routed to expert e.
#
# Then a final RMSNorm and an untied head: logits_i predicts token i + 1.
# Loss: the mean cross-entropy over the s - 1 positions that have a next
# token; no auxiliary term.
#
# Nothing is chunked, sorted, grouped by expert, tiled or cached: the rule
# runs a position at a time, attention is a dense masked softmax over all
# keys a head at a time, each held expert is applied densely to every
# position under a mask.
#
# Departures from the published description:
# - the parameter tree is the program's: a KDA layer holds "kda" (in_proj
#   [d, 5 H 128] with all q, then all k, v, f, z head by head, where the
#   release keeps five matrices; beta_proj [d, H]; conv [3 H 128, 4] over
#   [q ; k ; v], where it keeps three; A_log [H]; dt_bias [H 128]; norm
#   [128]; out_proj), an attention layer "attn" (q [d, H (n + r)], kv_a
#   [d, kv_lora_rank + r] the latent first, kv_a_norm, kv_b [kv_lora_rank,
#   H (n + v)] a head's k_n before its v, gate [d, H], out); then ffn_gate,
#   ffn_up, ffn_down, or router [d, E], the held experts stacked on a leading
#   axis as experts_gate, experts_up [e, d, width], experts_down [e, width,
#   d] in the order of ``experts_held``, shared_gate, shared_up, shared_down;
# - a share of the model (the configuration's ``deployment``): the layers
#   here are the published layers ``layers_held``; num_experts counts the
#   experts held here: the router, its scores, the bias, the groups, the top
#   k, the renormalisation and the counts are over all
#   ``num_experts_published``; what the absent experts would add is left out,
#   the shared expert is whole, and that partial sum goes on to the next
#   layer.  The vocabulary is the slice's;
# - what the configuration's file lists under ``assumed``, each with why;
# - blocks (one layer; inside it four heads of a KDA mixer and 64 positions
#   of their recurrence, one attention head and 1024 of its queries, one
#   expert, 1024 rows of an FFN or of the readout at a time;
#   each recomputed in the backward pass) bound the memory; they change no
#   result.
#
# ``wrong`` names what a check may break on purpose, so that
# ``chip_bench/tools/ling_reference_check.py`` can show that the limits of
# ``correct`` refuse it: "scalar_decay" (a head's g its mean over the key
# channels: Gated DeltaNet's decay), "gate_unbounded" (g = -exp(A_log)
# softplus(f + dt_bias), the gate without its bound), "no_group_mask" (the k
# largest s' among all experts), "no_head_gate" (latent attention's output
# ungated), "no_l2norm" (q and k as the convolution left them).

import functools

import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 1024
_HEAD_BLOCK = 1024
_RULE_BLOCK = 64
_RULE_HEADS = 4


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope_pairs(x, theta):
    """x [s, ..., r]: the pair (2i, 2i+1) of position t turned by the angle
    t * theta^(-2i/r)."""
    s, r = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = (jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]) \
        .reshape((s,) + (1,) * (x.ndim - 2) + (r // 2,))
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def is_attention(sizes, i):
    """Whether the ``i``-th layer held here mixes by latent attention."""
    return (sizes["layers_held"][i] + 1) % sizes["layer_group_size"] == 0


def is_dense(sizes, i):
    """Whether the ``i``-th layer held here carries the dense FFN."""
    return sizes["layers_held"][i] < sizes["first_k_dense_replace_published"]


def expert_layers(sizes):
    """The indices of the layers held here that carry experts."""
    return [i for i in range(sizes["num_hidden_layers"])
            if not is_dense(sizes, i)]


def recurrent_rule(q, k, v, g, beta):
    """Kimi Delta Attention's rule a position at a time: ``q``, ``k``, ``g
    [s, heads, K]``, ``v [s, heads, V]``, ``beta [s, heads]`` -> ``o [s,
    heads, V]``, the state zero before the sequence."""
    s, heads, dk = k.shape
    block = _RULE_BLOCK if s % _RULE_BLOCK == 0 else s

    def step(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[:, :, None]
        d = (vt - jnp.einsum("hkv,hk->hv", state, kt)) * bt[:, None]
        state = state + kt[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    @jax.checkpoint
    def some(state, xs):
        return lax.scan(step, state, xs)

    xs = tuple(t.reshape((s // block, block) + t.shape[1:])
               for t in (q, k, v, g, beta))
    _, o = lax.scan(some, jnp.zeros((heads, dk, v.shape[2]), v.dtype), xs)
    return o.reshape(v.shape)


def _l2_normed(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(p, x, sizes, wrong=()):
    """One sequence: x [s, d] -> [s, d], ``_RULE_HEADS`` heads at a time."""
    s, d = x.shape
    h, dh = sizes["num_attention_heads"], sizes["head_dim"]
    some = _RULE_HEADS if h % _RULE_HEADS == 0 else h
    groups, wide = h // some, some * dh
    # A group's columns of the five projections, of beta's and of the taps.
    weights = (
        p["in_proj"]["kernel"].reshape(d, 5, groups, wide)
        .transpose(2, 0, 1, 3),
        p["beta_proj"]["kernel"].reshape(d, groups, some).transpose(1, 0, 2),
        p["conv"].reshape(3, groups, wide, -1).transpose(1, 0, 2, 3),
        p["dt_bias"].reshape(groups, some, dh),
        p["A_log"].reshape(groups, some))
    length = p["conv"].shape[1]
    assert length == sizes["short_conv_kernel_size"]

    @jax.checkpoint
    def some_heads(ws):
        w_in, w_beta, taps, dt_bias, a_log = ws
        proj = jnp.einsum("sd,dfc->fsc", x, w_in)           # [5, s, wide]
        f, z = proj[3], proj[4]
        # Tap L - 1 lies on the position itself, tap 0 on the one L - 1
        # before.
        padded = jnp.concatenate(
            [jnp.zeros((3, length - 1, wide), proj.dtype), proj[:3]], axis=1)
        qkv = jax.nn.silu(sum(taps[:, None, :, j] * padded[:, j:j + s]
                              for j in range(length)))
        q, k, v = (qkv[j].reshape(s, some, dh) for j in range(3))
        beta = jax.nn.sigmoid(x @ w_beta)
        gate_in = f.reshape(s, some, dh) + dt_bias
        rate = jnp.exp(a_log)[:, None]
        if "gate_unbounded" in wrong:
            g = -rate * jax.nn.softplus(gate_in)
        else:
            g = sizes["kda_lower_bound"] * jax.nn.sigmoid(rate * gate_in)
        if "scalar_decay" in wrong:
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        if "no_l2norm" not in wrong:
            q, k = _l2_normed(q), _l2_normed(k)
        q = q / dh ** 0.5
        o = recurrent_rule(q, k, v, g.astype(q.dtype), beta)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + sizes["rms_norm_eps"]) * p["norm"] \
            * jax.nn.sigmoid(z.reshape(s, some, dh))
        return o.reshape(s, wide)

    y = lax.map(some_heads, weights)                        # [groups, s, wide]
    return y.transpose(1, 0, 2).reshape(s, h * dh) @ p["out_proj"]["kernel"]


def _mla(p, x, sizes, wrong=()):
    """One sequence: x [s, d] -> [s, d], causal."""
    s = x.shape[0]
    h, latent = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    n, r = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, eps = sizes["v_head_dim"], sizes["rms_norm_eps"]
    theta = float(sizes["rope_theta"])
    down = x @ p["kv_a"]["kernel"]
    c_kv = _rms_norm(down[:, :latent], p["kv_a_norm"]["scale"], eps)
    k_r = _rope_pairs(down[:, latent:], theta)
    scale = (n + r) ** -0.5
    to_q = p["q"]["kernel"].reshape(-1, h, n + r)
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
        to_q_head, up_head = args           # [d, n + r], [latent, n + dv]
        q_head = x @ to_q_head
        q_head = jnp.concatenate(
            [q_head[:, :n], _rope_pairs(q_head[:, n:], theta)], axis=-1)
        expanded = c_kv @ up_head
        keys = jnp.concatenate([expanded[:, :n], k_r], axis=-1)
        return jnp.concatenate([
            one_block(q_head[start:start + qb], keys, expanded[:, n:], seen)
            for start, seen in tables])

    out = lax.map(one_head, (to_q.transpose(1, 0, 2), up.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2)                            # [s, h, dv]
    if "no_head_gate" not in wrong:
        out = out * jax.nn.sigmoid(x @ p["gate"]["kernel"])[:, :, None]
    return out.reshape(s, h * dv) @ p["out"]["kernel"]


def _swiglu(m, gate, up, down):
    """m [..., d] -> [..., d], ``_HEAD_BLOCK`` rows at a time."""
    rows = m.reshape(-1, m.shape[-1])
    block = _HEAD_BLOCK if rows.shape[0] % _HEAD_BLOCK == 0 else rows.shape[0]

    @jax.checkpoint
    def some_rows(x):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down

    return lax.map(some_rows, rows.reshape(-1, block, rows.shape[-1])) \
        .reshape(m.shape[:-1] + (down.shape[-1],))


def choose(chosen_by, sizes, wrong=()):
    """The k experts a row takes, ``[n, k]``, from ``chosen_by [n, E]`` (the
    scores plus the bias): inside the ``topk_group`` best of ``n_group``
    groups of neighbours, a group scored by the sum of its two largest."""
    n = chosen_by.shape[0]
    groups, kept = sizes["n_group"], sizes["topk_group"]
    if "no_group_mask" not in wrong and (groups, kept) != (1, 1):
        by_group = chosen_by.reshape(n, groups, -1)
        group_scores = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)
        _, best = lax.top_k(group_scores, kept)
        is_kept = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
        chosen_by = jnp.where(is_kept[:, :, None], by_group, 0.0) \
            .reshape(chosen_by.shape)
    return lax.top_k(chosen_by, sizes["num_experts_per_tok"])[1]


def _experts(p, bias, m, sizes, wrong=()):
    """m [n, d], bias [E] -> (the held experts' part of the routed sum plus
    the shared expert, [n, d]; counts over all experts [E])."""
    scores = jax.nn.sigmoid(m @ p["router"])
    n_experts = scores.shape[-1]
    chosen = choose(scores + lax.stop_gradient(bias.astype(scores.dtype)),
                    sizes, wrong)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * sizes["routed_scaling_factor"]

    @jax.checkpoint
    def weighted(ew):
        e, gate, up, down = ew
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # [n]
        return w[:, None] * _swiglu(m, gate, up, down)

    def one_expert(routed, ew):
        # The sum outside the recomputed part: the backward pass then keeps
        # no copy of ``routed`` an expert.
        return routed + weighted(ew), None

    routed, _ = lax.scan(one_expert, jnp.zeros_like(m),
                         (jnp.asarray(sizes["experts_held"]),
                          p["experts_gate"], p["experts_up"],
                          p["experts_down"]))
    y = routed + _swiglu(m, p["shared_gate"]["kernel"],
                         p["shared_up"]["kernel"], p["shared_down"]["kernel"])
    counts = jnp.sum(chosen[..., None] == jnp.arange(n_experts), axis=(0, 1))
    return y, counts.astype(jnp.int32)


def hidden_states(params, bias, tokens, sizes, wrong=()):
    """tokens [b, s], bias [expert layers, E] -> (the stack's output before
    the final norm [b, s, d], counts [expert layers, E])."""
    eps = sizes["rms_norm_eps"]
    experts = expert_layers(sizes)
    counts = []

    @functools.partial(jax.checkpoint, static_argnums=(3, 4))
    def one_block(p, layer_bias, h, attention, dense):
        b, s, d = h.shape
        u = _rms_norm(h, p["ln1"]["scale"], eps)
        if attention:
            mixed = lax.map(lambda row: _mla(p["attn"], row, sizes, wrong), u)
        else:
            mixed = lax.map(lambda row: _kda(p["kda"], row, sizes, wrong), u)
        h = h + mixed
        m = _rms_norm(h, p["ln2"]["scale"], eps)
        if dense:
            return h + _swiglu(m, p["ffn_gate"]["kernel"],
                               p["ffn_up"]["kernel"],
                               p["ffn_down"]["kernel"]), None
        y, c = _experts(p, layer_bias, m.reshape(b * s, d), sizes, wrong)
        return h + y.reshape(b, s, d), c

    x = params["embed"]["embedding"][tokens]
    for i in range(sizes["num_hidden_layers"]):
        dense = is_dense(sizes, i)
        layer_bias = None if dense else bias[experts.index(i)]
        x, c = one_block(params[f"layer_{i}"], layer_bias, x,
                         is_attention(sizes, i), dense)
        if c is not None:
            counts.append(c)
    return x, jnp.stack(counts)


def _computed_in(params, dtype):
    """(the parameters, the matmul precision) for a pass in ``dtype``: the
    reference is float32 at the highest precision; any other ``dtype`` is
    there for the checks that tell a lower precision apart, and then
    everything is in it: parameters, norms, gates, the recurrence's state,
    rotary positions, router, softmax and loss, the matmuls at the default
    precision."""
    if dtype == jnp.float32:
        return params, jax.default_matmul_precision("highest")
    return (jax.tree_util.tree_map(lambda x: x.astype(dtype), params),
            jax.default_matmul_precision("default"))


def zero_bias(sizes):
    return jnp.zeros((len(expert_layers(sizes)),
                      sizes["num_experts_published"]), jnp.float32)


def logits(params, batch, sizes, dtype=jnp.float32, wrong=(), bias=None):
    """Logits of every position, [b, s, vocab], in ``dtype``; ``bias``
    [expert layers, E], zeros by default."""
    bias = zero_bias(sizes) if bias is None else bias
    params, precision = _computed_in(params, dtype)
    with precision:
        x, _ = hidden_states(params, bias, batch["tokens"], sizes, wrong)
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
    [b, s].  ``aux`` carries the router counters and the selection bias
    (``expert_bias`` [expert layers, E]), which the new ``aux`` holds stepped
    by this step's counts.  ``dtype`` and ``wrong`` are there for the checks
    that tell a lower precision and a wrong layer apart."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    # Position i is held to token i + 1; the last position has none.
    labels = jnp.roll(tokens, -1, axis=1)
    weights = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s)).astype(dtype)
    params, precision = _computed_in(params, dtype)
    with precision:
        x, counts = hidden_states(params, aux["expert_bias"], tokens, sizes,
                                  wrong)
        x = _rms_norm(x, params["ln_f"]["scale"], sizes["rms_norm_eps"])
        nll = _weighted_nll(x.reshape(b * s, -1),
                            params["lm_head"]["kernel"],
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
