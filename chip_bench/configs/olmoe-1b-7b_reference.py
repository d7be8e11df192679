"""The plain reference of the OLMoE block: forward pass, loss with both
auxiliary terms, and router counts, in float32 at the highest matmul
precision.  It imports ``jax`` and ``jax.numpy`` and nothing of
``horovod_tpu``; it takes the program's parameter tree.

This copy is the benchmark's own, so that the files under ``chip_bench/`` stay
enough by themselves; ``tests/olmoe_reference.py`` serves the tier-1 tests, and
``tests/test_olmoe_reference.py`` holds the two to the same text below the
marker line.
"""
# ---- below this line the two copies are the same text ----
# The architecture as published (OLMoE, arXiv:2409.02060; transformers'
# modeling_olmoe.py): pre-norm block x + attn(norm(x)), x + moe(norm(x));
# RMSNorm; q, k, v projected without bias, RMSNorm over the whole projected q
# and k, rotary positions (halves rotated), causal softmax attention; the
# router's softmax over all experts in fp32, the top k taken and NOT
# renormalised; each expert down(silu(gate(x)) * up(x)); final RMSNorm and an
# untied head.  Nothing is sorted, grouped or cached: every expert is applied
# densely to every token under a mask, one expert at a time.
#
# Departures from the published description:
# - the parameter tree is the program's: one fused [d, 3*h*dh] projection for
#   q, k, v (columns in that order), experts stacked on a leading axis;
# - the router reads the fp32 output of the norm and is fp32 throughout
#   (transformers feeds it the model's dtype);
# - the load-balancing loss (Switch form over top-k, transformers'
#   load_balancing_loss_func) and the router z-loss are taken over the tokens
#   of the batch given, which in data-parallel training is one rank's batch,
#   and averaged over layers; transformers concatenates the layers' tokens
#   first, which is the same at one layer;
# - the next-token loss is the mean over the s-1 positions of every sequence
#   that have a next token;
# - blocks (one head, one expert, 1024 positions of the head at a time, each
#   recomputed in the backward pass) bound the memory; they change no result.

import functools

import jax
import jax.numpy as jnp
from jax import lax

_HEAD_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """[b, s, h, dh]: x*cos + rotate_half(x)*sin."""
    s, dh = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@jax.checkpoint
def _one_head(qkv):
    q, k, v = qkv                                          # [s, dh] each
    s, dh = q.shape
    scores = q @ k.T / jnp.sqrt(jnp.float32(dh))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1) @ v


def _attention(p, x, sizes):
    b, s, _ = x.shape
    h = sizes["num_attention_heads"]
    qkv = x @ p["qkv"]["kernel"]
    q, k, v = jnp.split(qkv, 3, axis=-1)                   # [b, s, h*dh]
    eps = sizes["rms_norm_eps"]
    q = _rms_norm(q, p["q_norm"]["scale"], eps).reshape(b, s, h, -1)
    k = _rms_norm(k, p["k_norm"]["scale"], eps).reshape(b, s, h, -1)
    q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
    heads = lambda t: t.reshape(b, s, h, -1).transpose(0, 2, 1, 3) \
        .reshape(b * h, s, -1)                             # noqa: E731
    out = lax.map(_one_head, (heads(q), heads(k), heads(v)))
    out = out.reshape(b, h, s, -1).transpose(0, 2, 1, 3).reshape(b, s, -1)
    return out @ p["out"]["kernel"]


def _experts(p, x, sizes):
    """x [n, d] -> (y [n, d], load-balancing loss, z-loss, counts [E])."""
    n = x.shape[0]
    logits = x @ p["router"]
    n_experts = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, chosen = lax.top_k(probs, sizes["num_experts_per_tok"])

    @jax.checkpoint
    def one_expert(y, ew):
        e, gate, up, down = ew
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # [n]
        return y + w[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down), \
            None

    y, _ = lax.scan(one_expert, jnp.zeros_like(x),
                    (jnp.arange(n_experts), p["experts_gate"],
                     p["experts_up"], p["experts_down"]))
    counts = jnp.sum(chosen[..., None] == jnp.arange(n_experts), axis=(0, 1))
    balance = n_experts * jnp.sum(counts / n * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y, balance, z, counts.astype(jnp.int32)


def hidden_states(params, tokens, sizes):
    """Tokens [b, s] -> (final normed hidden [b, s, d], per-layer
    load-balancing losses [L], z-losses [L], counts [L, E])."""
    eps = sizes["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens]
    b, s, d = x.shape
    balances, zs, counts = [], [], []
    for i in range(sizes["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        x = x + _attention(p["attn"], _rms_norm(x, p["ln1"]["scale"], eps),
                           sizes)
        y, balance, z, c = _experts(
            p, _rms_norm(x, p["ln2"]["scale"], eps).reshape(b * s, d), sizes)
        x = x + y.reshape(b, s, d)
        balances.append(balance), zs.append(z), counts.append(c)
    return (_rms_norm(x, params["ln_f"]["scale"], eps), jnp.stack(balances),
            jnp.stack(zs), jnp.stack(counts))


def logits(params, tokens, sizes, last=None):
    """fp32 logits [b, s, vocab], or of the last ``last`` positions."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, sizes)[0]
        if last is not None:
            x = x[:, -last:]
        return x @ params["lm_head"]["kernel"]


def _next_token_loss(x, head, tokens):
    """Sum over positions 0..s-2 of -log p(token[t+1]), the head applied to
    ``_HEAD_BLOCK`` positions at a time."""
    b, s, d = x.shape
    labels = jnp.roll(tokens, -1, axis=1).reshape(b * s)
    valid = (jnp.arange(b * s) % s != s - 1).astype(jnp.float32)
    block = min(_HEAD_BLOCK, b * s)
    assert (b * s) % block == 0
    shape = ((b * s) // block, block)

    @jax.checkpoint
    def one_block(total, xs):
        xb, lb, vb = xs
        logp = jax.nn.log_softmax(xb @ head, axis=-1)
        picked = jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]
        return total - jnp.sum(picked * vb), None

    total, _ = lax.scan(one_block, jnp.zeros((), jnp.float32),
                        (x.reshape(shape + (d,)), labels.reshape(shape),
                         valid.reshape(shape)))
    return total / (b * (s - 1))


def loss(params, aux, batch, *, sizes):
    """``(params, aux, batch) -> (loss, new aux)``: next-token cross-entropy
    plus the weighted auxiliary losses; ``aux`` carries the router counters
    (``tokens_per_expert`` [L, E] summed over steps, ``steps``)."""
    tokens = batch["tokens"]
    with jax.default_matmul_precision("highest"):
        x, balances, zs, counts = hidden_states(params, tokens, sizes)
        ce = _next_token_loss(x, params["lm_head"]["kernel"], tokens)
    total = ce + sizes["load_balancing_loss_weight"] * jnp.mean(balances) \
        + sizes["router_z_loss_weight"] * jnp.mean(zs)
    return total, {"tokens_per_expert": aux["tokens_per_expert"] + counts,
                   "steps": aux["steps"] + 1}


def make_loss(sizes):
    return functools.partial(loss, sizes=sizes)
