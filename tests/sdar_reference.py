"""The plain reference of SDAR-30B-A3B's block under block-diffusion
training, for the tier-1 tests: float32 ``jax.numpy`` at the highest matmul
precision, nothing of ``horovod_tpu``.  The benchmark keeps its own copy,
``chip_bench/configs/sdar-30b-a3b_reference.py``;
``tests/test_olmoe.py::test_reference_copies_share_their_text`` holds the two
to the same text below the marker line.
"""
# ---- below this line the two copies are the same text ----
# The architecture as published (JetLM/SDAR-30B-A3B-Chat config.json,
# model_type sdar_moe, built on Qwen3-MoE): pre-norm block x + attn(norm(x)),
# x + moe(norm(x)); RMSNorm; q, k, v projected without bias, 32 query heads
# on 4 KV heads of 128 (KV head j serves query heads 8j..8j+7); an RMSNorm
# over the 128 of each head of q and of k, one learned scale each, shared by
# the heads; rotary positions (halves rotated) at the token's index; scores
# scaled by 128**-0.5; the router's softmax over all 128 experts in fp32, the
# top 8 taken and renormalised to sum to 1; each expert
# down(silu(gate(x)) * up(x)); final RMSNorm and an untied head.
#
# Block-diffusion training (BD3-LMs, arXiv:2503.09573, the efficient form):
# the model runs once on [x_t ; x_0], 2L positions.  Position p has half
# H(p) = p // L (0 noisy, 1 clean), index i(p) = p mod L (what RoPE sees) and
# block B(p) = i(p) // b.  Query p sees key r iff
#   (H(p)=0 and H(r)=0 and B(r) == B(p)) or
#   (H(p)=0 and H(r)=1 and B(r) <  B(p)) or
#   (H(p)=1 and H(r)=1 and B(r) <= B(p)).
# Loss: (1/L) sum over masked i of (1/t_{B(i)}) * -log softmax(logits_i)[x_0
# at i], logits taken at the noisy position i itself (no shift), plus the
# router's load-balancing loss.
#
# Nothing is sorted, grouped, tiled by the mask or cached: attention is a
# dense masked softmax over all 2L keys, each held expert is applied densely
# to every position under a mask, one at a time.
#
# Departures from the published description:
# - the parameter tree is the program's: q [d, 32*128]; k and v fused as
#   "kv" [d, 2*4*128] (k's heads first); the held experts stacked on a
#   leading axis in the order of ``experts_held``;
# - a share of the model (the configuration's ``deployment``): of the 128
#   experts the 16 in ``experts_held`` live here.  The router, its softmax,
#   the top 8, the renormalisation and the counts are over all 128; what the
#   absent experts would add is left out, and that partial sum goes on to
#   the next layer.  The vocabulary is the slice's: embedding, head, softmax
#   and loss are over ``vocab_size`` ids;
# - the router reads the fp32 output of the norm and is fp32 throughout;
# - the load-balancing loss (Switch form over top-k, transformers'
#   load_balancing_loss_func) is taken over the 2L positions of the batch
#   given, which in data-parallel training is one rank's, and averaged over
#   layers (transformers concatenates the layers' tokens first: the same
#   mean); SDAR's own report may weight it otherwise (``assumed``);
# - the block length, the noise schedule and the 1/t weight are ``assumed``
#   in the configuration's file; tokens, mask and t arrive as data;
# - blocks (one layer; inside it one head and 1024 of its queries, one
#   expert, 1024 positions of the head at a time; each recomputed in the
#   backward pass) bound the memory; they change no result.

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


def may_see(p, r, half_len, block):
    """The three clauses, for query positions p and key positions r."""
    hp, hr = p // half_len, r // half_len
    bp, br = (p % half_len) // block, (r % half_len) // block
    return ((hp == 0) & (hr == 0) & (br == bp)) \
        | ((hp == 0) & (hr == 1) & (br < bp)) \
        | ((hp == 1) & (hr == 1) & (br <= bp))


def _attention(p, x, sizes, mask=may_see):
    """One sequence [x_t ; x_0]: x [2L, d] -> [2L, d]."""
    s = x.shape[0]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    half_len, block = s // 2, sizes["block_length"]
    index = jnp.arange(s) % half_len
    q = (x @ p["q"]["kernel"]).reshape(s, h, dh)
    kv = (x @ p["kv"]["kernel"]).reshape(s, 2 * h_kv, dh)
    k, v = kv[:, :h_kv], kv[:, h_kv:]
    q = _rope(_rms_norm(q, p["q_norm"]["scale"], eps), sizes["rope_theta"],
              index)
    k = _rope(_rms_norm(k, p["k_norm"]["scale"], eps), sizes["rope_theta"],
              index)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)      # [h_kv, s, dh]
    qb = min(_QUERY_BLOCK, s)
    assert s % qb == 0
    # Each query block's rows of the [2L, 2L] table, made once, outside the
    # loop over heads: made inside it, every head's copy would be kept for
    # the backward pass (8 GB at the cell's size).
    tables = [(start, mask(start + jnp.arange(qb)[:, None],
                           jnp.arange(s)[None, :], half_len, block))
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


def hidden_states(params, both, sizes, mask=may_see):
    """[x_t ; x_0] as tokens [b, 2L] -> (hidden before the final norm
    [b, 2L, d], per-layer load-balancing losses [layers], counts
    [layers, E])."""
    eps = sizes["rms_norm_eps"]
    x = params["embed"]["embedding"][both]
    b, s, d = x.shape
    balances, counts = [], []

    @jax.checkpoint
    def layer(p, x):
        normed = _rms_norm(x, p["ln1"]["scale"], eps)
        x = x + lax.map(lambda row: _attention(p["attn"], row, sizes, mask),
                        normed)
        y, balance, c = _experts(
            p, _rms_norm(x, p["ln2"]["scale"], eps).reshape(b * s, d), sizes)
        return x + y.reshape(b, s, d), balance, c

    for i in range(sizes["num_hidden_layers"]):
        x, balance, c = layer(params[f"layer_{i}"], x)
        balances.append(balance), counts.append(c)
    return x, jnp.stack(balances), jnp.stack(counts)


def _both(batch):
    return jnp.concatenate([batch["noisy"], batch["tokens"]], axis=1)


def _computed_in(params, dtype):
    """(the parameters, the matmul precision) for a pass in ``dtype``: the
    reference is float32 at the highest precision; any other ``dtype`` is
    there for the checks that tell a lower precision apart
    (``chip_bench/tools/sdar_reference_check.py``), and then everything is
    in it: parameters, norms, rotary angles, router, softmax and loss, the
    matmuls at the default precision."""
    if dtype == jnp.float32:
        return params, jax.default_matmul_precision("highest")
    return (jax.tree_util.tree_map(lambda x: x.astype(dtype), params),
            jax.default_matmul_precision("default"))


def logits(params, batch, sizes, dtype=jnp.float32):
    """Logits of the noisy half, [b, L, vocab], in ``dtype``."""
    params, precision = _computed_in(params, dtype)
    with precision:
        x = hidden_states(params, _both(batch), sizes)[0]
        x = x[:, :x.shape[1] // 2]
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


def loss(params, aux, batch, *, sizes, mask=may_see, dtype=jnp.float32):
    """``(params, aux, batch) -> (loss, new aux)``.  ``batch``: ``tokens``
    x_0 [b, L], ``noisy`` x_t [b, L], ``masked`` [b, L] (where x_t is
    [MASK]), ``t`` [b, L / block] (each block's noise level).  ``aux``
    carries the router counters.  ``mask`` and ``dtype`` are there for the
    checks that tell a wrong mask and a lower precision apart
    (``_computed_in``)."""
    tokens = batch["tokens"]
    b, half_len = tokens.shape
    weights = batch["masked"].astype(dtype) \
        / jnp.repeat(batch["t"], sizes["block_length"], axis=1).astype(dtype)
    params, precision = _computed_in(params, dtype)
    with precision:
        x, balances, counts = hidden_states(params, _both(batch), sizes, mask)
        x = _rms_norm(x[:, :half_len], params["ln_f"]["scale"],
                      sizes["rms_norm_eps"])
        nll = _weighted_nll(x.reshape(b * half_len, -1),
                            params["lm_head"]["kernel"],
                            tokens.reshape(-1), weights.reshape(-1))
    total = nll / (b * half_len) \
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
