"""The plain reference of JoyAI-LLM-Flash's layers in training: forward pass,
both cross-entropies (the next token's, and the one-ahead prediction
module's), the router counts and the selection bias's step, in float32 at the
highest matmul precision.  It imports ``jax`` and ``jax.numpy`` and nothing of
``horovod_tpu``; it takes the program's parameter tree.  The one copy: the
benchmark decides ``correct`` by it and tier-1 loads it through
``tests/helpers.py::load_reference``.
"""
# The architecture as published (jdopensource/JoyAI-LLM-Flash config.json,
# model_type joyai_llm_flash, whose every key is DeepSeek-V3's; the equations
# are arXiv:2412.19437 sections 2.1 and 2.2).  A layer is
#
#   h <- h + MLA(RMSNorm(h)),  h <- h + FFN(RMSNorm(h))      eps rms_norm_eps
#
# MLA, with x = RMSNorm(h), H heads, a head's query and key n =
# qk_nope_head_dim wide without positions beside r = qk_rope_head_dim rotary,
# values v_head_dim:
#   c_q        = RMSNorm(x W_dq)                    q_lora_rank
#   [q_n; q_r] = c_q W_uq                           a head: n + r
#   [c_kv; k_r] = x W_dkv                           kv_lora_rank + r
#   c_kv       = RMSNorm(c_kv)
#   [k_n; v]   = c_kv W_ukv                         a head: n + v_head_dim
#   q_r, k_r   <- RoPE                              k_r once: one rotary key
#                                                   that every head shares
#   o_j = softmax_{t<=i}(q_j . [k_n,j ; k_r] (n + r)^-0.5) v_j,  out = [o] W_o
# RoPE rotates the pairs (2i, 2i+1) (rope_interleave) by the angle
# position * theta^(-2i/r): as a complex number x_2i + i x_2i+1 times
# e^(i angle); no scaling (rope_scaling null).
#
# FFN of the first first_k_dense_replace published layers:
#   W_2(silu(W_1 m) * W_3 m)                        width intermediate_size
# FFN of every other layer, with m = RMSNorm(h):
#   s    = sigmoid(m W_r)                           all n_routed_experts, fp32
#   top  = the num_experts_per_tok largest of s + b (n_group = topk_group = 1)
#   p_e  = s_e / (sum_{e in top} s_e + 1e-20) * routed_scaling_factor
#   FFN  = sum_{e in top} p_e E_e(m) + E_shared(m)  every E a SwiGLU of width
#                                                   moe_intermediate_size
# b [n_routed_experts] is state, not a parameter: it enters the choice and
# nothing else, no gradient reaches it, and after every step
#   b <- b + rate * sign(mean_e(n) - n)             n_e the rows routed to
#                                                   expert e in that step.
#
# Then a final RMSNorm and an untied head: logits_i predicts token i + 1.
#
# The multi-token-prediction module (depth 1), h_i the last layer's output
# before the final norm:
#   h'_i     = [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))] W_eh     2d -> d
#   logits'_i = Head(RMSNorm'(Block(h')_i))         predicts token i + 2
# Block a layer of the sparse kind with its own weights, router and bias, at
# the positions 0..s-1; Emb and Head the model's own.  A chain of
# num_nextn_predict_layers modules: module k reads module k-1's block's
# output and token i + k.
#
# Loss: CE_main + mtp_loss_weight * mean_k CE_k, CE_main the mean over the
# s - 1 positions that have a next token, CE_k over the s - 1 - k that have
# token i + 1 + k; no auxiliary term.
#
# Nothing is sorted, grouped, tiled or cached: attention a dense masked
# softmax over all keys, the keys built a head at a time; each held expert
# applied densely to every position under a mask, one at a time.
#
# Departures from the published description:
# - the parameter tree is the program's: a layer holds "ln1", "attn" (q_a
#   [d, q_lora_rank], q_a_norm, q_b [q_lora_rank, H (n + r)], kv_a
#   [d, kv_lora_rank + r] the latent first, kv_a_norm, kv_b [kv_lora_rank,
#   H (n + v)] a head's k_n before its v, out), "ln2" and ffn_gate, ffn_up,
#   ffn_down, or router [d, E], the held experts stacked on a leading axis as
#   experts_gate, experts_up [e, d, width] and experts_down [e, width, d] in
#   the order of ``experts_held``, shared_gate, shared_up, shared_down; module
#   k holds "mtp_<k>" (hnorm, enorm, eh_proj [2d, d] the state's rows first,
#   norm) and its block is "layer_<num_hidden_layers + k>";
# - a share of the model (the configuration's ``deployment``): the layers
#   here are the published layers ``layers_held``; n_routed_experts counts the
#   experts held here: the router, its scores, the bias, the top k, the
#   renormalisation and the counts are over all
#   ``n_routed_experts_published``; what the absent experts would add is left
#   out, the shared expert is whole, and that partial sum goes on to the next
#   layer.  The vocabulary is the slice's;
# - the bias's rule and rate, the module's equations and the loss's weight are
#   ``assumed`` in the configuration's file, which says why;
# - blocks (one layer; inside it one head and 1024 of its queries, one expert,
#   1024 positions of the readout at a time; each recomputed in the backward
#   pass) bound the memory; they change no result.
#
# ``wrong`` names what a check may break on purpose, so that
# ``chip_bench/tools/joyai_reference_check.py`` can show that the limits of
# ``correct`` refuse it: "rope_key_unrotated" (k_r enters the scores as
# projected), "no_kv_norm" (c_kv without its RMSNorm), "module_reads_token_i"
# (the module is fed Emb(t_i) for Emb(t_{i+1})), "scale_by_nope" (scores
# times n^-0.5 for (n + r)^-0.5).

import functools

import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 1024
_HEAD_BLOCK = 1024


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


def is_dense(sizes, i):
    """Whether block ``i`` here (a held layer, or a prediction module's block
    behind them) carries the dense FFN."""
    held = sizes["layers_held"]
    return i < len(held) and held[i] < sizes["first_k_dense_replace"]


def expert_blocks(sizes):
    """The indices of the blocks with experts, the modules' blocks last."""
    n = sizes["num_hidden_layers"] + sizes["num_nextn_predict_layers"]
    return [i for i in range(n) if not is_dense(sizes, i)]


def _mla(p, x, sizes, wrong=()):
    """One sequence: x [s, d] -> [s, d], causal."""
    s = x.shape[0]
    h, latent = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    n, r = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, eps = sizes["v_head_dim"], sizes["rms_norm_eps"]
    theta = float(sizes["rope_theta"])
    c_q = _rms_norm(x @ p["q_a"]["kernel"], p["q_a_norm"]["scale"], eps)
    q = (c_q @ p["q_b"]["kernel"]).reshape(s, h, n + r)
    down = x @ p["kv_a"]["kernel"]
    c_kv, k_r = down[:, :latent], down[:, latent:]
    if "no_kv_norm" not in wrong:
        c_kv = _rms_norm(c_kv, p["kv_a_norm"]["scale"], eps)
    q_n, q_r = q[..., :n], _rope_pairs(q[..., n:], theta)
    if "rope_key_unrotated" not in wrong:
        k_r = _rope_pairs(k_r, theta)
    scale = (n if "scale_by_nope" in wrong else n + r) ** -0.5
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


def hidden_states(params, bias, tokens, sizes, wrong=(), tables=None):
    """tokens [b, s], bias [expert blocks, E] -> (the stack's output before
    the final norm [b, s, d], each prediction module's block's output, counts
    [expert blocks, E]).  ``tables``: the parameters whose ``embed`` the
    modules read (the model's own, ``params``, by default)."""
    tables = params if tables is None else tables
    eps = sizes["rms_norm_eps"]
    experts = expert_blocks(sizes)
    counts = []

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def one_block(p, layer_bias, h, dense):
        b, s, d = h.shape
        u = _rms_norm(h, p["ln1"]["scale"], eps)
        h = h + lax.map(lambda row: _mla(p["attn"], row, sizes, wrong), u)
        m = _rms_norm(h, p["ln2"]["scale"], eps)
        if dense:
            return h + _swiglu(m, p["ffn_gate"]["kernel"],
                               p["ffn_up"]["kernel"],
                               p["ffn_down"]["kernel"]), None
        y, c = _experts(p, layer_bias, m.reshape(b * s, d), sizes)
        return h + y.reshape(b, s, d), c

    def block(i, h):
        dense = is_dense(sizes, i)
        layer_bias = None if dense else bias[experts.index(i)]
        h, c = one_block(params[f"layer_{i}"], layer_bias, h, dense)
        if c is not None:
            counts.append(c)
        return h

    x = params["embed"]["embedding"][tokens]
    for i in range(sizes["num_hidden_layers"]):
        x = block(i, x)
    ahead, state = [], x
    for k in range(sizes["num_nextn_predict_layers"]):
        p = params[f"mtp_{k}"]
        shift = k if "module_reads_token_i" in wrong else k + 1
        following = tables["embed"]["embedding"][jnp.roll(tokens, -shift, 1)]
        both = jnp.concatenate(
            [_rms_norm(state, p["hnorm"]["scale"], eps),
             _rms_norm(following, p["enorm"]["scale"], eps)], axis=-1)
        state = block(sizes["num_hidden_layers"] + k,
                      both @ p["eh_proj"]["kernel"])
        ahead.append(state)
    return x, ahead, jnp.stack(counts)


def _computed_in(params, dtype):
    """(the parameters, the matmul precision) for a pass in ``dtype``: the
    reference is float32 at the highest precision; any other ``dtype`` is
    there for the checks that tell a lower precision apart, and then
    everything is in it: parameters, norms, rotary positions, router,
    softmax and loss, the matmuls at the default precision."""
    if dtype == jnp.float32:
        return params, jax.default_matmul_precision("highest")
    return (jax.tree_util.tree_map(lambda x: x.astype(dtype), params),
            jax.default_matmul_precision("default"))


def zero_bias(sizes):
    return jnp.zeros((len(expert_blocks(sizes)),
                      sizes["n_routed_experts_published"]), jnp.float32)


def _readouts(params, x, ahead, sizes):
    """The states the head multiplies: the stack's under the final norm, then
    each module's under its own."""
    eps = sizes["rms_norm_eps"]
    return [_rms_norm(x, params["ln_f"]["scale"], eps)] + [
        _rms_norm(state, params[f"mtp_{k}"]["norm"]["scale"], eps)
        for k, state in enumerate(ahead)]


def logits(params, batch, sizes, dtype=jnp.float32, wrong=(), bias=None):
    """Logits of every position of every head, [1 + modules, b, s, vocab], in
    ``dtype``: the next token's, then each prediction module's; ``bias``
    [expert blocks, E], zeros by default."""
    bias = zero_bias(sizes) if bias is None else bias
    params, precision = _computed_in(params, dtype)
    with precision:
        x, ahead, _ = hidden_states(params, bias, batch["tokens"], sizes,
                                    wrong)
        return jnp.stack([y @ params["lm_head"]["kernel"]
                          for y in _readouts(params, x, ahead, sizes)])


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


def loss(params, aux, batch, *, sizes, dtype=jnp.float32, wrong=(),
         tables=None):
    """``(params, aux, batch) -> (loss, new aux)``.  ``batch``: ``tokens``
    [b, s].  ``aux`` carries the router counters, the selection bias
    (``expert_bias`` [expert blocks, E]), which the new ``aux`` holds stepped
    by this step's counts, and ``cross_entropy`` [1 + modules], the step's
    cross-entropies apart.  ``dtype`` and ``wrong`` are there for the checks
    that tell a lower precision and a wrong layer apart.  ``tables``: a tree
    whose ``embed`` and ``lm_head`` the prediction modules read in place of
    the model's own (a test tells the two uses of the shared leaves
    apart)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    modules = sizes["num_nextn_predict_layers"]
    params, precision = _computed_in(params, dtype)
    tables = params if tables is None else _computed_in(tables, dtype)[0]
    with precision:
        x, ahead, counts = hidden_states(params, aux["expert_bias"], tokens,
                                         sizes, wrong, tables)
        entropies = []
        for k, y in enumerate(_readouts(params, x, ahead, sizes)):
            # Head k's position i is held to token i + 1 + k; the last 1 + k
            # positions have none.
            has = s - 1 - k
            weights = jnp.broadcast_to(jnp.arange(s) < has, (b, s))
            head = (params if k == 0 else tables)["lm_head"]["kernel"]
            entropies.append(_weighted_nll(
                y.reshape(b * s, -1), head,
                jnp.roll(tokens, -(1 + k), axis=1).reshape(-1),
                weights.astype(dtype).reshape(-1)) / (b * has))
    total = entropies[0]
    if modules:
        total = total + sizes["mtp_loss_weight"] * sum(entropies[1:]) / modules
    here = jnp.sum(counts[:, jnp.asarray(sizes["experts_held"])], axis=1)
    return total, {
        "tokens_per_expert": aux["tokens_per_expert"] + counts,
        "steps": aux["steps"] + 1,
        "rows_held": aux["rows_held"] + here,
        "rows_elsewhere": aux["rows_elsewhere"] + jnp.sum(counts, axis=1)
        - here,
        "expert_bias": step_bias(aux["expert_bias"], counts,
                                 sizes["expert_bias_update_rate"]),
        "cross_entropy": jnp.stack(entropies).astype(jnp.float32)}


def make_loss(sizes, **variant):
    return functools.partial(loss, sizes=sizes, **variant)
