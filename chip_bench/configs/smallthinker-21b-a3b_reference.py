"""The plain reference of SmallThinker-21BA3B's layers in training: forward
pass, the next-token loss with the load-balancing term, and router counts, in
float32 at the highest matmul precision.  It imports ``jax`` and
``jax.numpy`` and nothing of ``horovod_tpu``; it takes the program's
parameter tree.

This copy is the benchmark's own, so that the files under ``chip_bench/`` stay
enough by themselves; ``tests/smallthinker_reference.py`` serves the tier-1
tests, and ``tests/test_olmoe.py::test_reference_copies_share_their_text``
holds the two to the same text below the marker line.
"""
# ---- below this line the two copies are the same text ----
# The architecture as published (PowerInfer/SmallThinker-21BA3B-Instruct
# config.json, model_name smallthinker_21b_instruct, and the catalog's
# description of the family).  With h the layer's input, l its index and
# w = sliding_window_size:
#
#   r      = h W_router                      64 outputs, from the layer's INPUT
#   a      = RMSNorm_1(h)
#   q,k,v  = a W_q, a W_k, a W_v             28 / 4 / 4 heads of 128, no bias,
#                                            no QK-norm; KV head j serves query
#                                            heads 7j..7j+6
#   q,k    = RoPE(q), RoPE(k) at theta       only where rope_layout[l] = 1
#   allowed(i,j) = j <= i                    where sliding_window_layout[l] = 0
#                = j <= i and i - j < w      where it is 1
#   h'     = h + softmax_allowed(q k^T / sqrt(128)) v W_o
#   m      = RMSNorm_2(h')
#   top    = the 6 largest of r;  p = softmax over all 64, those 6 taken and
#            renormalised to sum to 1 (= softmax over the 6 logits)
#   out    = h' + sum_{e in top} p_e W_down,e (relu(m W_gate,e) * (m W_up,e))
#
# then a final RMSNorm and an untied head.  Loss: next-token cross-entropy,
# the logits at position i against the token at i + 1, the mean over the
# s - 1 positions that have a next token, plus the router's load-balancing
# loss.
#
# Nothing is sorted, grouped, tiled by the mask or cached: attention is a
# dense masked softmax over all keys, each held expert is applied densely to
# every position under a mask, one at a time.
#
# Departures from the published description:
# - the parameter tree is the program's: q [d, 28*128]; k and v fused as
#   "kv" [d, 2*4*128] (k's heads first); the held experts stacked on a
#   leading axis in the order of ``experts_held``;
# - a share of the model (the configuration's ``deployment``): of the 64
#   experts the 8 in ``experts_held`` live here.  The router, its softmax,
#   the top 6, the renormalisation and the counts are over all 64; what the
#   absent experts would add is left out, and that partial sum goes on to
#   the next layer.  The vocabulary is the slice's: embedding, head, softmax
#   and loss are over ``vocab_size`` ids;
# - the router reads h itself and not RMSNorm_1(h), the projections have no
#   biases, and the auxiliary loss is the Switch form over top-k
#   (transformers' load_balancing_loss_func) over the positions of the batch
#   given, averaged over layers: all three ``assumed`` in the configuration's
#   file, which config.json does not settle; no "secondary experts" (the
#   config has none);
# - blocks (one layer; inside it one head and 1024 of its queries, one
#   expert, 1024 positions of the head at a time; each recomputed in the
#   backward pass) bound the memory; they change no result.
#
# ``wrong`` names what a check may break on purpose, so that
# ``chip_bench/tools/smallthinker_reference_check.py`` can show that the
# limits of ``correct`` refuse it: "no_window" (causal in every layer),
# "rope_everywhere" (rotary positions in the global layers too),
# "router_after_attention" (the router reads m, where the experts read),
# "silu" (for relu).

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


def may_see(i, j, window):
    """Query positions i, key positions j; ``window`` 0: causal alone."""
    seen = j <= i
    return seen & (i - j < window) if window else seen


def layer_kind(sizes, layer, wrong=()):
    """(window or 0, whether RoPE is applied) of one layer."""
    window = sizes["sliding_window_size"] \
        if sizes["sliding_window_layout"][layer] \
        and "no_window" not in wrong else 0
    rope = bool(sizes["rope_layout"][layer]) or "rope_everywhere" in wrong
    return window, rope


def _attention(p, x, sizes, window, rope):
    """One sequence: x [s, d] -> [s, d]."""
    s = x.shape[0]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = sizes["head_dim"]
    q = (x @ p["q"]["kernel"]).reshape(s, h, dh)
    kv = (x @ p["kv"]["kernel"]).reshape(s, 2 * h_kv, dh)
    k, v = kv[:, :h_kv], kv[:, h_kv:]
    if rope:
        q = _rope(q, sizes["rope_theta"], jnp.arange(s))
        k = _rope(k, sizes["rope_theta"], jnp.arange(s))
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)      # [h_kv, s, dh]
    qb = min(_QUERY_BLOCK, s)
    assert s % qb == 0
    # Each query block's rows of the [s, s] table, made once, outside the
    # loop over heads: made inside it, every head's copy would be kept for
    # the backward pass.
    tables = [(start, may_see(start + jnp.arange(qb)[:, None],
                              jnp.arange(s)[None, :], window))
              for start in range(0, s, qb)]

    @jax.checkpoint
    def one_block(q_block, k_head, v_head, seen):
        scores = q_block @ k_head.T / dh ** 0.5
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


def _experts(p, routed_by, x, sizes, wrong=()):
    """``routed_by`` [n, d] -> the router; x [n, d] -> the experts.  Returns
    (the held experts' part of y [n, d], load-balancing loss, counts over all
    experts [E])."""
    n = x.shape[0]
    logits = routed_by @ p["router"]
    n_experts = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, chosen = lax.top_k(probs, sizes["moe_num_active_primary_experts"])
    if sizes["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    act = jax.nn.silu if "silu" in wrong else jax.nn.relu

    @jax.checkpoint
    def one_expert(y, ew):
        e, gate, up, down = ew
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # [n]
        return y + w[:, None] * ((act(x @ gate) * (x @ up)) @ down), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(x),
                    (jnp.asarray(sizes["experts_held"]), p["experts_gate"],
                     p["experts_up"], p["experts_down"]))
    counts = jnp.sum(chosen[..., None] == jnp.arange(n_experts), axis=(0, 1))
    balance = n_experts * jnp.sum((counts / n).astype(probs.dtype)
                                  * jnp.mean(probs, axis=0))
    return y, balance, counts.astype(jnp.int32)


def hidden_states(params, tokens, sizes, wrong=()):
    """tokens [b, s] -> (hidden before the final norm [b, s, d], per-layer
    load-balancing losses [layers], counts [layers, E])."""
    eps = sizes["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens]
    b, s, d = x.shape
    balances, counts = [], []

    @functools.partial(jax.checkpoint, static_argnums=(2, 3))
    def layer(p, h, window, rope):
        normed = _rms_norm(h, p["ln1"]["scale"], eps)
        x = h + lax.map(
            lambda row: _attention(p["attn"], row, sizes, window, rope),
            normed)
        m = _rms_norm(x, p["ln2"]["scale"], eps).reshape(b * s, d)
        routed_by = m if "router_after_attention" in wrong \
            else h.reshape(b * s, d)
        y, balance, c = _experts(p, routed_by, m, sizes, wrong)
        return x + y.reshape(b, s, d), balance, c

    for i in range(sizes["num_hidden_layers"]):
        x, balance, c = layer(params[f"layer_{i}"], x,
                              *layer_kind(sizes, i, wrong))
        balances.append(balance), counts.append(c)
    return x, jnp.stack(balances), jnp.stack(counts)


def _computed_in(params, dtype):
    """(the parameters, the matmul precision) for a pass in ``dtype``: the
    reference is float32 at the highest precision; any other ``dtype`` is
    there for the checks that tell a lower precision apart, and then
    everything is in it: parameters, norms, rotary angles, router, softmax
    and loss, the matmuls at the default precision."""
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
    apart (``_computed_in``, the note above)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    # Position i is held to token i + 1; the last position has none.
    labels = jnp.roll(tokens, -1, axis=1)
    weights = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s)).astype(dtype)
    params, precision = _computed_in(params, dtype)
    with precision:
        x, balances, counts = hidden_states(params, tokens, sizes, wrong)
        x = _rms_norm(x, params["ln_f"]["scale"], sizes["rms_norm_eps"])
        nll = _weighted_nll(x.reshape(b * s, -1),
                            params["lm_head"]["kernel"],
                            labels.reshape(-1), weights.reshape(-1))
    total = nll / (b * (s - 1)) \
        + sizes["load_balancing_loss_weight"] * jnp.mean(balances)
    here = jnp.sum(counts[:, jnp.asarray(sizes["experts_held"])], axis=1)
    return total, {
        "tokens_per_expert": aux["tokens_per_expert"] + counts,
        "steps": aux["steps"] + 1,
        "rows_held": aux["rows_held"] + here,
        "rows_elsewhere": aux["rows_elsewhere"] + jnp.sum(counts, axis=1)
        - here}


def make_loss(sizes, **variant):
    return functools.partial(loss, sizes=sizes, **variant)
