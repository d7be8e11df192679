"""The plain reference of Nemotron-3-Super-120B-A12B's layers in training:
forward pass, the next-token loss, the router counts and the selection bias's
step, in float32 at the highest matmul precision, the Mamba-2 recurrence a
token at a time.  It imports ``jax`` and ``jax.numpy`` and nothing of
``horovod_tpu``; it takes the program's parameter tree.

This copy serves the tier-1 tests; the benchmark keeps its own
(``chip_bench/configs/nemotron-3-super-120b-a12b_reference.py``), and
``tests/test_olmoe.py::test_reference_copies_share_their_text`` holds the two
to the same text below the marker line.
"""
# ---- below this line the two copies are the same text ----
# The architecture as published (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
# config.json, model_type nemotron_h; Nemotron-H, arXiv:2504.03624; Mamba-2,
# arXiv:2405.21060).  A layer is one module under one norm,
#
#   h' = h + Module(RMSNorm(h))            eps norm_eps
#
# the module by the layer's letter in hybrid_override_pattern.
#
# "M", Mamba-2, with u = RMSNorm(h), H heads of P = mamba_head_dim channels in
# G groups, N = ssm_state_size, L = conv_kernel:
#   z, xBC, dt = split(u W_in)             widths H*P, H*P + 2*G*N, H; no bias
#   xBC    = silu(conv(xBC) + b)           depthwise, causal: c_t = sum_{j<L}
#                                          w[:, j] xBC_{t-(L-1)+j}, zero before
#                                          position 0, never across sequences
#   x, B, C = split(xBC)                   widths H*P, G*N, G*N
#   delta  = softplus(dt + dt_bias)        a head
#   a      = -exp(A_log)                   a head
#   S_t    = exp(delta_t a) S_{t-1} + delta_t x_t B_t^T    S [P, N] a head,
#                                          zero before position 0; B, C those
#                                          of the head's group h // (H / G)
#   y_t    = S_t C_t + D x_t
#   y      = RMSNorm_g(y * silu(z))        the norm over each group's H*P/G
#                                          channels, one scale a channel
#   Module = y W_out
# The recurrence is run as written, a token at a time.
#
# "*", attention: q, k, v = u W_q, u W_k, u W_v, num_attention_heads query
# heads on num_key_value_heads KV heads of head_dim, KV head j serving the
# query heads j*r..j*r+r-1, no bias, no positions of any kind, no QK-norm;
# Module = softmax_{j<=i}(q k^T / sqrt(head_dim)) v W_o.
#
# "E", LatentMoE, with m = RMSNorm(h):
#   s      = sigmoid(m W_r)                all n_routed_experts, in fp32
#   top    = the num_experts_per_tok largest of s + b     (n_group =
#                                          topk_group = 1: no group limit)
#   p_e    = s_e / (sum_{e in top} s_e + 1e-20) * routed_scaling_factor
#   l      = m W_fc1                       hidden -> moe_latent_size
#   r      = sum_{e in top} p_e W_2,e relu(W_1,e l)^2     no gate, no bias
#   Module = r W_fc2 + W_2,s relu(W_1,s m)^2              the shared expert,
#                                          width moe_shared_expert_
#                                          intermediate_size, on the full m
# b [n_routed_experts] is state, not a parameter: it enters the choice and
# nothing else, no gradient reaches it, and after every step
#   b <- b + rate * sign(mean_e(n) - n)    n_e the rows routed to expert e in
#                                          that step, over the whole batch.
#
# Then a final RMSNorm and an untied head.  Loss: next-token cross-entropy,
# the logits at position i against the token at i + 1, the mean over the
# s - 1 positions that have a next token; no auxiliary term.
#
# Nothing is chunked, sorted, grouped, tiled or cached: the recurrence a
# token at a time, the convolution L shifted sums, attention a dense masked
# softmax over all keys, each held expert applied densely to every position
# under a mask, one at a time.
#
# Departures from the published description:
# - the parameter tree is the program's: a layer holds "ln1" and "mamba"
#   (in_proj [d, 2HP + 2GN + H] in the order z, x, B, C, dt; conv [HP + 2GN,
#   L], conv_bias, dt_bias, A_log, D, norm [HP], out_proj [HP, d]) or "ln1"
#   and "attn" (q [d, heads*head_dim], k and v fused as "kv", k's heads
#   first, out) or "ln2", router [d, E], latent_in, latent_out, the held
#   experts stacked on a leading axis as experts_up [e, latent, width] and
#   experts_down [e, width, latent] in the order of ``experts_held``,
#   shared_up, shared_down;
# - a share of the model (the configuration's ``deployment``): the layers
#   here are the published layers ``layers_held``, each of its published
#   letter; mamba_num_heads, n_groups, num_attention_heads,
#   num_key_value_heads and n_routed_experts count what is held here, and the
#   modules return their heads' and their experts' part of the result: the
#   router, its scores, the bias, the top k, the renormalisation and the
#   counts are over all ``n_routed_experts_published``; what the absent
#   heads and experts would add is left out, the shared expert is whole, and
#   that partial sum goes on to the next layer.  The vocabulary is the
#   slice's;
# - the multi-token-prediction module is not built (``reduced``);
# - the bias's rule and rate, the absence of positions in attention and the
#   order of the expert layer's operations are ``assumed`` in the
#   configuration's file, which says why;
# - blocks (one layer; inside it 128 tokens of the recurrence, one head and
#   1024 of its queries, one expert, 1024 positions of the readout at a time;
#   each recomputed in the backward pass) bound the memory; they change no
#   result.
#
# ``wrong`` names what a check may break on purpose, so that
# ``chip_bench/tools/nemotron_reference_check.py`` can show that the limits
# of ``correct`` refuse it: "decay_without_dt" (exp(a) for exp(delta a)),
# "wrong_group" (a head reads the B and C of the next group),
# "norm_over_all" (one RMSNorm over all the mixer's channels),
# "gated_experts" (relu(W_1 l)^2 * W_1 l: the up projection as its own
# gate), "weights_dropped" (r W_fc2 with every p_e = 1), "no_shared_expert".

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
    """The letters of the layers held here, each as the published layer it
    is: "M", "E" or "*"."""
    return [sizes["hybrid_override_pattern"][p] for p in sizes["layers_held"]]


def _recurrence(x, delta, a, b, c, wrong=()):
    """One sequence, a token at a time: x [s, H, P], delta [s, H], a [H],
    b, c [s, G, N] -> y [s, H, P] without the D term."""
    s, heads, p = x.shape
    groups, n = b.shape[1:]
    group = jnp.arange(heads) // (heads // groups)
    if "wrong_group" in wrong:
        group = (group + 1) % groups

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
    heads, hp = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n = sizes["n_groups"], sizes["ssm_state_size"]
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
    y = (y + p["D"][:, None] * x).reshape(s, inner) * jax.nn.silu(z)
    over = 1 if "norm_over_all" in wrong else groups
    y = _rms_norm(y.reshape(s, over, inner // over), 1.0,
                  sizes["norm_eps"]).reshape(s, inner) * p["norm"]
    return y @ p["out_proj"]["kernel"]


def _attention(p, x, sizes):
    """One sequence: x [s, d] -> [s, d], causal, no positions."""
    s = x.shape[0]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = sizes["head_dim"]
    q = (x @ p["q"]["kernel"]).reshape(s, h, dh)
    kv = (x @ p["kv"]["kernel"]).reshape(s, 2 * h_kv, dh)
    k, v = kv[:, :h_kv].transpose(1, 0, 2), kv[:, h_kv:].transpose(1, 0, 2)
    qb = min(_QUERY_BLOCK, s)
    assert s % qb == 0
    # Each query block's rows of the [s, s] table, made once, outside the
    # loop over heads.
    tables = [(start, jnp.arange(s)[None, :]
               <= start + jnp.arange(qb)[:, None])
              for start in range(0, s, qb)]

    @jax.checkpoint
    def one_block(q_block, k_head, v_head, seen):
        scores = q_block @ k_head.T * dh ** -0.5
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


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _experts(p, bias, m, sizes, wrong=()):
    """m [n, d], bias [E] -> (the held experts' part of the routed sum
    through W_fc2, plus the shared expert, [n, d]; counts over all experts
    [E])."""
    scores = jax.nn.sigmoid(m @ p["router"])
    n_experts = scores.shape[-1]
    _, chosen = lax.top_k(scores + lax.stop_gradient(bias.astype(scores.dtype)),
                          sizes["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * sizes["routed_scaling_factor"]
    if "weights_dropped" in wrong:
        weights = jnp.ones_like(weights)
    latent = m @ p["latent_in"]["kernel"]

    @jax.checkpoint
    def one_expert(r, ew):
        e, up, down = ew
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # [n]
        hidden = _relu2(latent @ up)
        if "gated_experts" in wrong:
            hidden = hidden * (latent @ up)
        return r + w[:, None] * (hidden @ down), None

    routed, _ = lax.scan(one_expert, jnp.zeros_like(latent),
                         (jnp.asarray(sizes["experts_held"]),
                          p["experts_up"], p["experts_down"]))
    y = routed @ p["latent_out"]["kernel"]
    if "no_shared_expert" not in wrong:
        y = y + _relu2(m @ p["shared_up"]["kernel"]) \
            @ p["shared_down"]["kernel"]
    counts = jnp.sum(chosen[..., None] == jnp.arange(n_experts), axis=(0, 1))
    return y, counts.astype(jnp.int32)


def hidden_states(params, bias, tokens, sizes, wrong=()):
    """tokens [b, s], bias [expert layers, E] -> (hidden before the final
    norm [b, s, d], counts [expert layers, E])."""
    eps = sizes["norm_eps"]
    x = params["embed"]["embedding"][tokens]
    b, s, d = x.shape
    counts = []

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def layer(p, layer_bias, h, letter):
        if letter == "M":
            u = _rms_norm(h, p["ln1"]["scale"], eps)
            return h + lax.map(
                lambda row: _mamba(p["mamba"], row, sizes, wrong), u), None
        if letter == "*":
            u = _rms_norm(h, p["ln1"]["scale"], eps)
            return h + lax.map(
                lambda row: _attention(p["attn"], row, sizes), u), None
        if letter != "E":
            raise ValueError(f"unknown layer {letter!r}")
        m = _rms_norm(h, p["ln2"]["scale"], eps).reshape(b * s, d)
        y, c = _experts(p, layer_bias, m, sizes, wrong)
        return h + y.reshape(b, s, d), c

    for i, letter in enumerate(layer_plan(sizes)):
        layer_bias = bias[len(counts)] if letter == "E" else None
        x, c = layer(params[f"layer_{i}"], layer_bias, x, letter)
        if c is not None:
            counts.append(c)
    return x, jnp.stack(counts)


def _computed_in(params, dtype):
    """(the parameters, the matmul precision) for a pass in ``dtype``: the
    reference is float32 at the highest precision; any other ``dtype`` is
    there for the checks that tell a lower precision apart, and then
    everything is in it: parameters, norms, the convolution, delta, the
    decays and the state, router, softmax and loss, the matmuls at the
    default precision."""
    if dtype == jnp.float32:
        return params, jax.default_matmul_precision("highest")
    return (jax.tree_util.tree_map(lambda x: x.astype(dtype), params),
            jax.default_matmul_precision("default"))


def zero_bias(sizes):
    n = sum(letter == "E" for letter in layer_plan(sizes))
    return jnp.zeros((n, sizes["n_routed_experts_published"]), jnp.float32)


def logits(params, batch, sizes, dtype=jnp.float32, wrong=(), bias=None):
    """Logits of every position, [b, s, vocab], in ``dtype``; ``bias``
    [expert layers, E], zeros by default."""
    bias = zero_bias(sizes) if bias is None else bias
    params, precision = _computed_in(params, dtype)
    with precision:
        x = hidden_states(params, bias, batch["tokens"], sizes, wrong)[0]
        return _rms_norm(x, params["ln_f"]["scale"], sizes["norm_eps"]) \
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
        nll = _weighted_nll(x.reshape(b * s, -1), params["lm_head"]["kernel"],
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
