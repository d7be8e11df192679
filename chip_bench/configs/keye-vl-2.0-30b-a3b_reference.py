"""The plain reference of Keye-VL-2.0-30B-A3B's language model in the sparse
training stage of DeepSeek Sparse Attention: forward pass, the three-term loss
(next-token cross-entropy, the router's load-balancing term, the indexer's
divergence) and router counts, in float32 at the highest matmul precision.  It
imports ``jax`` and ``jax.numpy`` and nothing of ``horovod_tpu``; it takes the
program's parameter tree.
"""
# The architecture as published (Kwai-Keye/Keye-VL-2.0-30B-A3B config.json,
# model_type KeyeVL2; the trunk's keys are Qwen3-MoE's): pre-norm block
# x + attn(norm(x)), x + moe(norm(x)); RMSNorm; q, k, v projected without
# bias, 32 query heads on 4 KV heads of 128 (KV head j serves query heads
# 8j..8j+7); an RMSNorm over the 128 of each head of q and of k, one learned
# scale each, shared by the heads; M-RoPE: rotary pair i of a head takes its
# angle from the temporal, height or width position by mrope_section
# [16, 24, 24] (halves rotated; on text the three streams are the token's
# index); scores scaled by 128**-0.5; the router's softmax over all 128
# experts in fp32, the top 8 taken and renormalised to sum to 1; each expert
# down(silu(gate(x)) * up(x)); final RMSNorm and an untied head.
#
# sa_config (DeepSeek Sparse Attention's lightning indexer at this model's
# sizes; the configuration's file lists what is assumed): with n the layer's
# normed input and x = stop_gradient(n),
#   q_j = turn(x W_q)_j           16 heads of 64
#   k   = turn(LN(x W_k))         one key of 64 for all heads, LayerNorm
#   w   = x W_w * 16**-0.5 * 64**-0.5
#   I[t, s] = sum_j w[t, j] relu(q_j[t] . k[s])         s <= t
# S_t = the topk = 2048 positions s <= t of the largest I[t, s] (all where
# t < 2048; of equal scores the lower position: lax.top_k's order);
# attention is a softmax over S_t a head; and with
#   p[t, s] = stop_gradient(mean over the 32 heads of a[t, h, s])
#   L_I = mean over t of sum over S_t of p (log p - log_softmax_{S_t} I)
# summed over the layers, loss = cross-entropy + 0.001 balance + L_I.
#
# Nothing is sorted by hand, packed, tiled or cached: a block of q_chunk_size
# queries at a time the scores are a dense [block, s] table, the choice is
# lax.top_k on it, attention a dense masked softmax a head, the target the
# mean of those softmaxes, each held expert is applied densely to every
# position under a mask, one at a time.
#
# Departures from the published description:
# - the parameter tree is the program's: q [d, 32*128]; k and v fused as
#   "kv" [d, 2*4*128] (k's heads first); the indexer's four under
#   attn/indexer (q [d, 16*64], k [d, 64], k_norm scale and bias, weights
#   [d, 16]); the held experts stacked on a leading axis in the order of
#   ``experts_held``;
# - a share of the model (the configuration's ``deployment``): of the 128
#   experts the 16 in ``experts_held`` live here.  The router, its softmax,
#   the top 8, the renormalisation and the counts are over all 128; what the
#   absent experts would add is left out, and that partial sum goes on to
#   the next layer.  The vocabulary is the slice's: embedding, head, softmax
#   and loss are over ``vocab_size`` ids;
# - the router reads the fp32 output of the norm and is fp32 throughout;
# - the load-balancing loss (Switch form over top-k, transformers'
#   load_balancing_loss_func) is taken over the positions of the batch given
#   and averaged over layers;
# - the vision tower is left out: tokens are text ids and the three position
#   streams coincide;
# - blocks (one layer; inside it one head, one block of queries, one expert,
#   1024 positions of the head at a time; each recomputed in the backward
#   pass) bound the memory; they change no result.

import functools

import jax
import jax.numpy as jnp
from jax import lax

_HEAD_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * lax.rsqrt(
        jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) \
        * scale + bias


def mrope_tables(streams, width, theta, sections, dtype=jnp.float32):
    """(cos, sin), ``[s, width]`` each, of M-RoPE over heads of ``width``:
    ``streams [3, s]`` the temporal, height and width position of every
    token; pair i (of width / 2) turns by its stream's position times
    ``theta ** (-2 i / width)``, the streams dealt to the pairs by
    ``sections`` in order (scaled to width / 2 pairs where the head is
    narrower than the published 128)."""
    pairs = width // 2
    sections = [n * pairs // sum(sections) for n in sections]
    inv_freq = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=jnp.float32)
                               / width)
    stream_of_pair = jnp.asarray(
        [stream for stream, n in enumerate(sections) for _ in range(n)])
    positions = streams.astype(dtype)[stream_of_pair]          # [pairs, s]
    angles = positions.T * inv_freq.astype(dtype)[None]        # [s, pairs]
    return (jnp.concatenate([jnp.cos(angles)] * 2, axis=-1),
            jnp.concatenate([jnp.sin(angles)] * 2, axis=-1))


def _turn(x, tables):
    """[s, h, d] by (cos, sin) [s, d]: x*cos + rotate_half(x)*sin."""
    cos, sin = (t[:, None, :] for t in tables)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _index(p, x, sizes, tables, wrong):
    """The indexer's operands of one sequence from the normed input
    ``x [s, d]`` (cut from the graph): q_i [s, H, d_i], k_i [s, d_i],
    w [s, H]."""
    sa = sizes["sa_config"]
    heads, width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    if "input_in_graph" not in wrong:
        x = lax.stop_gradient(x)
    q_i = _turn((x @ p["q"]["kernel"]).reshape(-1, heads, width), tables)
    k_i = _layer_norm(x @ p["k"]["kernel"], p["k_norm"]["scale"],
                      p["k_norm"]["bias"], sizes["rms_norm_eps"])
    if "key_unturned" not in wrong:
        k_i = _turn(k_i[:, None, :], tables)[:, 0]
    w = x @ p["weights"]["kernel"] * (heads ** -0.5 * width ** -0.5)
    if "no_weights" in wrong:
        w = jnp.full_like(w, heads ** -0.5 * width ** -0.5)
    return q_i, k_i, w


def index_scores(q_i, k_i, w, start, rows, wrong=()):
    """I [rows, s] of the queries start .. start + rows: the dense table,
    -inf behind the query."""
    s = k_i.shape[0]
    q_rows = lax.dynamic_slice_in_dim(q_i, start, rows, axis=0)
    w_rows = lax.dynamic_slice_in_dim(w, start, rows, axis=0)
    by_head = jnp.einsum("tjd,sd->tjs", q_rows, k_i)
    if "no_relu" not in wrong:
        by_head = jax.nn.relu(by_head)
    table = jnp.einsum("tjs,tj->ts", by_head, w_rows)
    return jnp.where(jnp.arange(s)[None, :]
                     <= start + jnp.arange(rows)[:, None], table, -jnp.inf)


def chosen_block(q_i, k_i, w, start, rows, topk, wrong=()):
    """The chosen sets of the queries start .. start + rows as a boolean
    [rows, s]: lax.top_k on the block's table, inside the causal keys."""
    s = k_i.shape[0]
    table = index_scores(q_i, k_i, w, start, rows, wrong)
    causal = table > -jnp.inf
    if "dense" in wrong:
        return causal
    if "half_the_keys" in wrong:
        topk = topk // 2
    _, ids = lax.top_k(table, min(topk, s))
    taken = jnp.zeros((rows, s), bool).at[
        jnp.arange(rows)[:, None], ids].set(True)
    return taken & causal


def _attention(p, x, sizes, rotary, wrong=()):
    """One sequence: x [s, d] (the normed input) -> (the attention's output
    [s, d], the indexer's divergence summed over the queries, the chosen sets
    [s, s] boolean)."""
    s = x.shape[0]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    sa = sizes["sa_config"]
    q = (x @ p["q"]["kernel"]).reshape(s, h, dh)
    kv = (x @ p["kv"]["kernel"]).reshape(s, 2 * h_kv, dh)
    k, v = kv[:, :h_kv], kv[:, h_kv:]
    q = _turn(_rms_norm(q, p["q_norm"]["scale"], eps), rotary["head"])
    k = _turn(_rms_norm(k, p["k_norm"]["scale"], eps), rotary["head"])
    q = q.transpose(1, 0, 2)                                # [h, s, dh]
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)      # [h_kv, s, dh]
    q_i, k_i, w = _index(p["indexer"], x, sizes, rotary["indexer"], wrong)
    rows = min(sa["q_chunk_size"], s)
    assert s % rows == 0
    starts = jnp.arange(0, s, rows)
    # Each block's rows of the [s, s] table of chosen pairs, made once,
    # outside the loop over heads; a choice has no gradient.
    chosen = lax.stop_gradient(lax.map(
        lambda start: chosen_block(q_i, k_i, w, start, rows, sa["topk"],
                                   wrong), starts))        # [blocks, rows, s]

    def probabilities(q_block, k_head, seen):
        scores = q_block @ k_head.T / dh ** 0.5
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)

    @jax.checkpoint
    def one_head(args):
        head, q_head = args                                # q_head [s, dh]
        kv_head = head // (h // h_kv)

        @jax.checkpoint
        def one_block(block):
            q_block, seen = block
            return probabilities(q_block, k[kv_head], seen) @ v[kv_head]

        return lax.map(one_block, (q_head.reshape(-1, rows, dh), chosen))

    out = lax.map(one_head, (jnp.arange(h), q))
    out = out.reshape(h, s, dh).transpose(1, 0, 2).reshape(s, h * dh)

    target_q, target_k = (q, k) if "target_in_graph" in wrong \
        else (lax.stop_gradient(q), lax.stop_gradient(k))

    @jax.checkpoint
    def divergence(block):
        start, seen = block

        def add_head(total, head):
            q_block = lax.dynamic_slice_in_dim(target_q[head], start, rows,
                                               axis=0)
            return total + probabilities(
                q_block, target_k[head // (h // h_kv)], seen), None

        target, _ = lax.scan(add_head, jnp.zeros(seen.shape, q.dtype),
                             jnp.arange(h))
        target = target / h
        log_q = jax.nn.log_softmax(jnp.where(
            seen, index_scores(q_i, k_i, w, start, rows, wrong), -jnp.inf),
            axis=-1)
        held = seen & (target > 0)
        return jnp.sum(jnp.where(
            held, target * (jnp.log(jnp.where(held, target, 1.0))
                            - jnp.where(held, log_q, 0.0)), 0.0))

    kl = jnp.sum(lax.map(divergence, (starts, chosen)))
    return out @ p["out"]["kernel"], kl, chosen.reshape(s, s)


def _experts(p, x, sizes):
    """x [n, d] -> (the held experts' part of y [n, d], load-balancing loss,
    counts over all experts [E])."""
    n = x.shape[0]
    logits = x @ p["router"]
    n_experts = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, chosen = lax.top_k(probs, sizes["num_experts_per_tok"])
    if sizes["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

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
    balance = n_experts * jnp.sum((counts / n).astype(probs.dtype)
                                  * jnp.mean(probs, axis=0))
    return y, balance, counts.astype(jnp.int32)


def rotary_tables(sizes, s, dtype=jnp.float32):
    """M-RoPE's tables for a text sequence of ``s`` tokens, the three streams
    all the token's index: of the attention's heads and of the indexer's."""
    streams = jnp.broadcast_to(jnp.arange(s), (3, s))
    sections = sizes["rope_scaling"]["mrope_section"]
    return {"head": mrope_tables(streams, sizes["head_dim"],
                                 sizes["rope_theta"], sections, dtype),
            "indexer": mrope_tables(
                streams, sizes["sa_config"]["indexer_head_dim"],
                sizes["rope_theta"], sections, dtype)}


def hidden_states(params, tokens, sizes, wrong=(), sets=False):
    """tokens [b, s] -> (hidden before the final norm [b, s, d], per-layer
    load-balancing losses [layers], counts [layers, E], the indexer's
    divergence a layer, each a mean over the batch's queries [layers]); with
    ``sets`` also every layer's chosen sets [layers, b, s, s]."""
    eps = sizes["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens]
    b, s, d = x.shape
    rotary = rotary_tables(sizes, s, x.dtype)
    balances, counts, divergences, chosen = [], [], [], []

    @jax.checkpoint
    def layer(p, x):
        normed = _rms_norm(x, p["ln1"]["scale"], eps)
        mixed, kl, seen = lax.map(
            lambda row: _attention(p["attn"], row, sizes, rotary, wrong),
            normed)
        x = x + mixed
        y, balance, c = _experts(
            p, _rms_norm(x, p["ln2"]["scale"], eps).reshape(b * s, d), sizes)
        return x + y.reshape(b, s, d), balance, c, jnp.sum(kl) / (b * s), \
            seen if sets else None

    for i in range(sizes["num_hidden_layers"]):
        x, balance, c, kl, seen = layer(params[f"layer_{i}"], x)
        balances.append(balance), counts.append(c), divergences.append(kl)
        chosen.append(seen)
    out = (x, jnp.stack(balances), jnp.stack(counts), jnp.stack(divergences))
    return out + (jnp.stack(chosen),) if sets else out


def _computed_in(params, dtype):
    """(the parameters, the matmul precision) for a pass in ``dtype``: the
    reference is float32 at the highest precision; any other ``dtype`` is
    there for the checks that tell a lower precision apart
    (``chip_bench/tools/keye_reference_check.py``), and then everything is
    in it: parameters, norms, rotary tables, indexer, router, softmax and
    loss, the matmuls at the default precision."""
    if dtype == jnp.float32:
        return params, jax.default_matmul_precision("highest")
    return (jax.tree_util.tree_map(lambda x: x.astype(dtype), params),
            jax.default_matmul_precision("default"))


def logits(params, batch, sizes, dtype=jnp.float32, wrong=()):
    """Logits of every position, [b, s, vocab], in ``dtype``; ``wrong``:
    one thing of the layers broken (what the limits have to refuse)."""
    params, precision = _computed_in(params, dtype)
    with precision:
        x = hidden_states(params, batch["tokens"], sizes, wrong)[0]
        return _rms_norm(x, params["ln_f"]["scale"], sizes["rms_norm_eps"]) \
            @ params["lm_head"]["kernel"]


def logits_and_chosen_sets(params, batch, sizes):
    """(:func:`logits`, every layer's chosen sets [layers, b, s, s] boolean)
    from one pass in float32."""
    with jax.default_matmul_precision("highest"):
        x, *_, chosen = hidden_states(params, batch["tokens"], sizes,
                                      sets=True)
        return _rms_norm(x, params["ln_f"]["scale"], sizes["rms_norm_eps"]) \
            @ params["lm_head"]["kernel"], chosen


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


def terms(params, batch, sizes, wrong=(), dtype=jnp.float32):
    """(cross-entropy, the load-balancing loss, the indexer's loss, the
    counts [layers, E]): the three terms apart, unweighted."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    params, precision = _computed_in(params, dtype)
    with precision:
        x, balances, counts, divergences = hidden_states(
            params, tokens, sizes, wrong)
        x = _rms_norm(x, params["ln_f"]["scale"], sizes["rms_norm_eps"])
        # Position i is held to token i + 1; the last position has no next
        # token and weighs nothing.
        weights = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s))
        nll = _weighted_nll(x.reshape(b * s, -1),
                            params["lm_head"]["kernel"],
                            jnp.roll(tokens, -1, axis=1).reshape(-1),
                            weights.reshape(-1).astype(x.dtype))
    return nll / (b * (s - 1)), jnp.mean(balances), jnp.sum(divergences), \
        counts


def loss(params, aux, batch, *, sizes, wrong=(), dtype=jnp.float32):
    """``(params, aux, batch) -> (loss, new aux)``.  ``batch``: ``tokens``
    [b, s].  ``aux`` carries the router counters and the indexer's loss of
    the last step.  ``wrong`` and ``dtype`` are there for the checks that
    tell a wrong layer and a lower precision apart (``_computed_in``)."""
    nll, balance, divergence, counts = terms(params, batch, sizes, wrong,
                                             dtype)
    total = nll + sizes["load_balancing_loss_weight"] * balance \
        + sizes["indexer_loss_weight"] * divergence
    here = jnp.sum(counts[:, jnp.asarray(sizes["experts_held"])], axis=1)
    return total, {
        "tokens_per_expert": aux["tokens_per_expert"] + counts,
        "steps": aux["steps"] + 1,
        "rows_held": aux["rows_held"] + here,
        "rows_elsewhere": aux["rows_elsewhere"] + jnp.sum(counts, axis=1)
        - here,
        "indexer_loss": divergence.astype(jnp.float32)}


def make_loss(sizes, **variant):
    return functools.partial(loss, sizes=sizes, **variant)
