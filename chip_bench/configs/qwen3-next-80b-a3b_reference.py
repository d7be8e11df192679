"""The plain reference of Qwen3-Next-80B-A3B's layers in training: forward
pass, the next-token loss with the load-balancing term, and router counts, in
float32 at the highest matmul precision.  It imports ``jax`` and
``jax.numpy`` and nothing of ``horovod_tpu``; it takes the program's
parameter tree.  This is the one copy: tier-1 loads it through
``tests/helpers.py::load_reference``.

The architecture as published (Qwen/Qwen3-Next-80B-A3B-Instruct config.json,
model_type qwen3_next, and ``transformers``' modeling_qwen3_next.py, which
tests/test_qwen3_next.py holds this file to on copied weights).  With h a
layer's input, N(x) = x / rms(x) * (1 + w) and no bias anywhere:

  h' = h + Mixer(N_1(h));  out = h' + FFN(N_2(h'))

Mixer, layers 0, 1, 2 of every four, a Gated DeltaNet over x = N_1(h):
  [q ; k ; v ; z] = x W_qkvz          16 key heads and 32 value heads of 128
  [b ; a]         = x W_ba            one of each a value head
  [q ; k ; v]     = silu(conv4([q ; k ; v]))    depthwise, causal, zero
                                      before the sequence, as shifted sums
  beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
  q = q / |q| / sqrt(128);  k = k / |k|         x rsqrt(sum x^2 + 1e-6)
  a value head i, with the q and k of key head i // 2, TOKEN BY TOKEN:
      S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
      o_t = S^T q_t                   S [128, 128], zero before the sequence
  y = o / rms(o) * w_n * silu(z)      a value head, one w_n [128] for all
  Mixer = y W_o

Mixer, layer 3 of every four, gated attention over x = N_1(h):
  [q_j ; gate_j] = x W_q              a head j of 16, 256 + 256
  k, v           = x W_k, x W_v       2 KV heads of 256, head j on j // 8
  q, k           = N_q(q), N_k(k)     over a head, one w [256] each
  q, k           = RoPE on the first 64 of a head's 256, halves of those 64
                   rotated, theta 1e7; the other 192 untouched
  o_j  = softmax_{key <= query}(q_j k^T / sqrt(256)) v
  Mixer = (concat(o) * sigmoid(concat(gate))) W_o

FFN over m = N_2(h'):
  p    = softmax(m W_r) over all 512; the 10 largest, divided by their sum
  FFN  = sum_{e in top} p_e E_e(m) + sigmoid(m . w_g) E_shared(m)
  E(x) = W_2 (silu(W_1 x) * W_3 x)    width 512, routed and shared alike

then a final N and an untied head.  Loss: next-token cross-entropy over the
s - 1 positions that have a next token, plus ``router_aux_loss_coef`` times
the mean over layers of the load-balancing loss (the Switch form over the
top k, ``transformers``' load_balancing_loss_func).

Nothing is sorted, grouped, chunked, tiled by the mask or cached: the rule
runs a position at a time (the recurrence above, not the chunked form the
program's kernels use), attention is a dense masked softmax over all keys,
each held expert is applied densely to every position under a mask.

Departures from the published description:
- the parameter tree is the program's: ``in_proj_qkvz``'s columns are all q,
  all k, all v, all z head by head and ``in_proj_ba``'s all b, all a (the
  release interleaves them by key head: a fixed permutation,
  ``horovod_tpu/models/gated_delta.py::release_columns``); k and v fused as
  "kv" [d, 2*2*256] (k's heads first); the held experts stacked on a
  leading axis in the order of ``experts_held``;
- a share of the model (the configuration's ``deployment``): of the 512
  experts the 16 in ``experts_held`` live here.  The router, its softmax, the
  top 10, the renormalisation and the counts are over all 512; what the
  absent experts would add is left out, and that partial sum goes on to the
  next layer; the gated shared expert is whole.  The vocabulary is the
  slice's;
- blocks (one layer; inside it 64 positions of the recurrence, one head and
  1024 of its queries, one expert, 1024 positions of the head at a time;
  each recomputed in the backward pass) bound the memory; they change no
  result.

``wrong`` names what a check may break on purpose, so that
``chip_bench/tools/qwen3_next_reference_check.py`` can show that the limits
of ``correct`` refuse it: "no_delta" (S <- exp(g) S + beta k v^T, a decay
without the delta), "no_l2norm" (q and k as the convolution left them),
"rope_everywhere" (RoPE over all 256 of a head), "no_attention_gate",
"no_shared_gate".
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 1024
_HEAD_BLOCK = 1024
_RULE_BLOCK = 64


def _rms_norm(x, w, eps):
    """x / rms(x) * (1 + w)."""
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1 + w)


def partial_rope(x, theta, index, rotary):
    """[s, h, dh] at the indices [s]: the first ``rotary`` of a head as a head
    of that width, x*cos + rotate_half(x)*sin; the rest as it is."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                               / rotary)
    angles = index.astype(x.dtype)[:, None] * inv_freq.astype(x.dtype)[None]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    turned, rest = x[..., :rotary], x[..., rotary:]
    x1, x2 = turned[..., :rotary // 2], turned[..., rotary // 2:]
    return jnp.concatenate(
        [turned * cos + jnp.concatenate([-x2, x1], axis=-1) * sin, rest],
        axis=-1)


def is_attention(sizes, layer):
    return (layer + 1) % sizes["full_attention_interval"] == 0


def _attention(p, x, sizes, wrong=()):
    """One sequence: x [s, d] -> [s, d]."""
    s = x.shape[0]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    q_gate = (x @ p["q"]["kernel"]).reshape(s, h, 2 * dh)
    q, gate = q_gate[..., :dh], q_gate[..., dh:]
    kv = (x @ p["kv"]["kernel"]).reshape(s, 2 * h_kv, dh)
    k, v = kv[:, :h_kv], kv[:, h_kv:]
    q = _rms_norm(q, p["q_norm"]["scale"], eps)
    k = _rms_norm(k, p["k_norm"]["scale"], eps)
    rotary = dh if "rope_everywhere" in wrong \
        else int(dh * sizes["partial_rotary_factor"])
    q = partial_rope(q, sizes["rope_theta"], jnp.arange(s), rotary)
    k = partial_rope(k, sizes["rope_theta"], jnp.arange(s), rotary)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)      # [h_kv, s, dh]
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
    out = out.transpose(1, 0, 2)                           # [s, h, dh]
    if "no_attention_gate" not in wrong:
        out = out * jax.nn.sigmoid(gate)
    return out.reshape(s, h * dh) @ p["out"]["kernel"]


def recurrent_rule(q, k, v, g, beta, delta=True):
    """The gated delta rule a position at a time: ``q``, ``k [s, heads, K]``
    (a value head's own copy), ``v [s, heads, V]``, ``g``, ``beta [s,
    heads]`` -> ``o [s, heads, V]``, the state zero before the sequence.
    ``delta`` False: the write is ``beta k v^T``, the state's own answer for
    the key not taken off."""
    s, heads, dk = k.shape
    block = _RULE_BLOCK if s % _RULE_BLOCK == 0 else s

    def step(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[:, None, None]
        held = jnp.einsum("hkv,hk->hv", state, kt) if delta else 0.0
        d = (vt - held) * bt[:, None]
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


def _gated_delta_net(p, x, sizes, wrong=()):
    """One sequence: x [s, d] -> [s, d]."""
    s = x.shape[0]
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    qkvz = x @ p["in_proj_qkvz"]["kernel"]
    ba = x @ p["in_proj_ba"]["kernel"]
    qkv, z = qkvz[:, :2 * key_dim + value_dim], qkvz[:, 2 * key_dim
                                                     + value_dim:]
    # Tap L - 1 lies on the position itself, tap 0 on the one L - 1 before.
    taps = p["conv"]
    length = taps.shape[1]
    assert length == sizes["linear_conv_kernel_dim"]
    padded = jnp.concatenate(
        [jnp.zeros((length - 1, qkv.shape[1]), qkv.dtype), qkv])
    qkv = jax.nn.silu(sum(taps[:, j] * padded[j:j + s]
                          for j in range(length)))
    q = qkv[:, :key_dim].reshape(s, hk, dk)
    k = qkv[:, key_dim:2 * key_dim].reshape(s, hk, dk)
    v = qkv[:, 2 * key_dim:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    if "no_l2norm" not in wrong:
        q, k = _l2_normed(q), _l2_normed(k)
    q = q / dk ** 0.5
    q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))
    o = recurrent_rule(q, k, v, g, beta, "no_delta" not in wrong)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + sizes["rms_norm_eps"]) * p["norm"] \
        * jax.nn.silu(z.reshape(s, hv, dv))
    return o.reshape(s, value_dim) @ p["out_proj"]["kernel"]


def _experts(p, x, sizes, wrong=()):
    """x [n, d] -> (the held experts' part of the layer plus the gated shared
    expert [n, d], load-balancing loss, counts over all experts [E])."""
    n = x.shape[0]
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    n_experts = probs.shape[-1]
    weights, chosen = lax.top_k(probs, sizes["num_experts_per_tok"])
    if sizes["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    def swiglu(gate, up, down):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down

    @jax.checkpoint
    def one_expert(y, ew):
        e, gate, up, down = ew
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # [n]
        return y + w[:, None] * swiglu(gate, up, down), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(x),
                    (jnp.asarray(sizes["experts_held"]), p["experts_gate"],
                     p["experts_up"], p["experts_down"]))
    shared = swiglu(p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                    p["shared_down"]["kernel"])
    if "no_shared_gate" not in wrong:
        shared = shared * jax.nn.sigmoid(x @ p["shared_expert_gate"]["kernel"])
    counts = jnp.sum(chosen[..., None] == jnp.arange(n_experts), axis=(0, 1))
    balance = n_experts * jnp.sum((counts / n).astype(probs.dtype)
                                  * jnp.mean(probs, axis=0))
    return y + shared, balance, counts.astype(jnp.int32)


def hidden_states(params, tokens, sizes, wrong=()):
    """tokens [b, s] -> (hidden before the final norm [b, s, d], per-layer
    load-balancing losses [layers], counts [layers, E])."""
    eps = sizes["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens]
    b, s, d = x.shape
    balances, counts = [], []

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def layer(p, h, attention):
        normed = _rms_norm(h, p["ln1"]["scale"], eps)
        if attention:
            mixed = lax.map(
                lambda row: _attention(p["attn"], row, sizes, wrong), normed)
        else:
            mixed = lax.map(
                lambda row: _gated_delta_net(p["gdn"], row, sizes, wrong),
                normed)
        x = h + mixed
        m = _rms_norm(x, p["ln2"]["scale"], eps).reshape(b * s, d)
        y, balance, c = _experts(p, m, sizes, wrong)
        return x + y.reshape(b, s, d), balance, c

    for i in range(sizes["num_hidden_layers"]):
        x, balance, c = layer(params[f"layer_{i}"], x,
                              is_attention(sizes, i))
        balances.append(balance), counts.append(c)
    return x, jnp.stack(balances), jnp.stack(counts)


def _computed_in(params, dtype):
    """(the parameters, the matmul precision) for a pass in ``dtype``: the
    reference is float32 at the highest precision; any other ``dtype`` is
    there for the checks that tell a lower precision apart, and then
    everything is in it: parameters, norms, rotary angles, gates, the
    recurrence's state, router, softmax and loss, the matmuls at the default
    precision."""
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
        + sizes["router_aux_loss_coef"] * jnp.mean(balances)
    here = jnp.sum(counts[:, jnp.asarray(sizes["experts_held"])], axis=1)
    return total, {
        "tokens_per_expert": aux["tokens_per_expert"] + counts,
        "steps": aux["steps"] + 1,
        "rows_held": aux["rows_held"] + here,
        "rows_elsewhere": aux["rows_elsewhere"] + jnp.sum(counts, axis=1)
        - here}


def make_loss(sizes, **variant):
    return functools.partial(loss, sizes=sizes, **variant)
