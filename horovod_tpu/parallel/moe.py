"""Sparse experts: an expert-parallel top-1 layer and a dropless top-k layer.

The reference exposes only the raw alltoall primitive
(`operations.cc:1081-1142`; SURVEY §2.9 notes it as the building block
"users could use for MoE-style exchange, but no EP strategy ships").  Here
the strategy ships: Switch-style top-1 routing with capacity, tokens
exchanged over the ``expert`` mesh axis with two tiled ``all_to_all``s
(dispatch and return), one expert per axis member.

Capacity drops are the standard trade: tokens over an expert's capacity
pass through unchanged (residual connection keeps them sane), keeping all
shapes static for XLA.

:func:`moe_ffn` is the other layer (OLMoE's, ``docs/moe.md``): every expert on
every device, top k of many, and no capacity: the routed rows are sorted by
expert and multiplied through a grouped matmul (``jax.lax.ragged_dot``), so
every token reaches its k experts whatever the imbalance, and the work is the k
routed rows a token, not one per expert.  Told which experts it ``held``
(SDAR-30B-A3B: 16 of 128, a layer's experts shared among 8 chips), it routes
over all of them and computes the part of the result its own experts give.
The router may read another tensor than the rows it multiplies
(``router_input``) and the gate's activation is an argument (SmallThinker:
the block's input, ``relu``).  Its scores are a softmax over all experts or a
sigmoid each (``scoring``), and a ``bias`` an expert may enter the choice of
the top k without entering their weights (LFM2-8B-A1B; the bias is state the
step keeps by :func:`update_expert_bias`, not a parameter).  An expert may
have no gate at all (``gate=None``: ``down(act(up x))``, Nemotron-H's
squared-relu experts, which multiply rows of a latent width while the router
reads the model's through ``router_input``).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.timeline import scope
from .collectives import axis_size
from .mesh import AXIS_EXPERT


def moe_dispatch_combine(x: jax.Array, gate_logits: jax.Array,
                         expert_fn: Callable[[jax.Array], jax.Array],
                         axis_name: str = AXIS_EXPERT,
                         capacity_factor: float = 1.25,
                         capacity: Optional[int] = None) -> jax.Array:
    """Top-1 MoE layer body; inside ``shard_map`` over ``axis_name``.

    - ``x``: local tokens ``[t, d]``;
    - ``gate_logits``: ``[t, n_experts]`` with ``n_experts == axis_size``;
    - ``expert_fn``: this device's expert, ``[c, d] -> [c, d]``.

    Returns ``[t, d]``: gate-weighted expert outputs (dropped tokens get 0,
    callers add the residual).
    """
    n = axis_size(axis_name)
    t, d = x.shape
    if capacity is None:
        capacity = max(1, int(capacity_factor * t / n))
    c = capacity

    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)  # [t, n]
    expert_idx = jnp.argmax(probs, axis=-1)                           # [t]
    gate = jnp.max(probs, axis=-1)                                    # [t]
    onehot = jax.nn.one_hot(expert_idx, n, dtype=jnp.float32)         # [t, n]
    # Position of each token within its expert's queue; >=c means dropped.
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot                # [t, n]
    keep = (pos < c) * onehot
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), c, dtype=jnp.float32)
    dispatch = keep[..., None] * pos_oh                               # [t, n, c]

    # [n, c, d]: slot (e, j) holds the j-th local token routed to expert e.
    send = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    # Exchange: device e receives every peer's slice for expert e.
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                                 # [n, c, d]
    out = expert_fn(recv.reshape(n * c, d).astype(x.dtype))
    out = out.reshape(n, c, d).astype(jnp.float32)
    back = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                                 # [n, c, d]
    combine = dispatch * gate[:, None, None]
    return jnp.einsum("tec,ecd->td", combine, back).astype(x.dtype)


def load_balancing_loss(gate_logits: jax.Array, axis_name: str = AXIS_EXPERT) -> jax.Array:
    """Switch-Transformer auxiliary loss: n * sum(fraction_tokens * mean_prob)."""
    n = gate_logits.shape[-1]
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    frac = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, -1), n), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return n * jnp.sum(frac * mean_prob)


# -- the dropless top-k layer -------------------------------------------------


class MoEStats(NamedTuple):
    """What one call of :func:`moe_ffn` says about its routing.  The leading
    axis has one entry for each set of rows that was routed by itself (one
    without ``data_axis``, one a rank under it)."""

    load_balancing_loss: jax.Array    # [sets] fp32
    router_z_loss: jax.Array          # [sets] fp32
    tokens_per_expert: jax.Array      # [sets, experts] int32, sums to k*tokens


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_to_slots(x, order, inverse, k):
    """``x[order // k]``: row n of ``x`` to its k slots in expert order.  The
    cotangent is a gather through ``inverse`` and a sum over k, where
    autodiff would scatter-add 4 KB rows."""
    return x[order // k]


def _rows_to_slots_fwd(x, order, inverse, k):
    return x[order // k], inverse


def _rows_to_slots_bwd(k, inverse, g):
    n = g.shape[0] // k
    with scope("moe.dispatch"):
        return (g[inverse].reshape(n, k, -1).sum(axis=1, dtype=jnp.float32)
                .astype(g.dtype),
                None, None)


_rows_to_slots.defvjp(_rows_to_slots_fwd, _rows_to_slots_bwd)


@jax.custom_vjp
def _slots_to_rows(y, order, inverse):
    """``y[inverse]``: the slots back in token order; the cotangent is the
    gather through ``order``."""
    return y[inverse]


def _slots_to_rows_bwd(order, g):
    with scope("moe.combine"):
        return g[order], None, None


_slots_to_rows.defvjp(lambda y, order, inverse: (y[inverse], order),
                      _slots_to_rows_bwd)


# The gate's activation, ``down(act(gate x) * up x)``, or, of an expert
# without a gate, the hidden layer's: ``down(act(up x))``.
_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
                "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def _activation(name: str):
    try:
        return _ACTIVATIONS[name]
    except KeyError:
        raise ValueError(f"unknown activation {name!r}; have "
                         f"{sorted(_ACTIVATIONS)}") from None


class RouterRows(NamedTuple):
    """What the router reads, as the product it is made of: the logical rows
    are ``rows * row_scale[..., None] * col_scale`` (an RMSNorm of a bf16
    stream: the stream, each row's ``rsqrt(mean(x**2) + eps)`` and the norm's
    scale).  :func:`moe_ffn` takes it as ``router_input``, for rows that are
    a bfloat16 array: the factors move out of the product (:func:`_logits`)
    and the rows are multiplied as they are."""

    rows: jax.Array         # [rows, tokens, d] bf16
    row_scale: jax.Array    # [rows, tokens] fp32
    col_scale: jax.Array    # [d] fp32


def router_product_passes(dtype) -> int:
    """bf16 passes of the MXU that the router's logits take over rows of
    ``dtype``: three where the rows are a bfloat16 array (only the weights
    are split), six for any other (fp32 by fp32 at the highest precision)."""
    return 3 if dtype == jnp.bfloat16 else 6


def _bf16_pieces(w, axis: int = -1):
    """``w`` (fp32) as three bfloat16 arrays, joined along ``axis``, whose
    sum in fp32 is ``w`` to its last bit: 8 + 8 + 8 bits of its 24.
    ``reduce_precision`` and not a cast there and back, which XLA may take
    for no rounding at all (``xla_allow_excess_precision``)."""
    pieces = []
    for _ in range(3):
        piece = lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
        pieces.append(piece.astype(jnp.bfloat16))
        w = w - piece
    return jnp.concatenate(pieces, axis=axis)


def _sum_of_slabs(s3):
    """``[n, 3 e]`` as its three slabs of e columns added up, the smallest
    first."""
    e = s3.shape[-1] // 3
    return (s3[:, 2 * e:] + s3[:, e:2 * e]) + s3[:, :e]


def _bf16_dot(a, b, contract):
    """One bf16 by bf16 product with fp32 sums."""
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


@jax.custom_vjp
def _rows_dot(x, w):
    """``x [n, d]`` in bfloat16 times ``w [d, e]`` in fp32, to fp32: ``x`` is
    exact in bf16, so three bf16 products against the pieces of ``w``
    (:func:`_bf16_pieces`; one product ``[n, d] x [d, 3 e]``) hold every
    term there is, where the highest precision splits both operands and
    makes six.  Backward ``dw = x^T [u1 | u2 | u3]``, three passes over the
    cotangent's pieces, and ``dx = u w^T``, two fp32 operands, in the six
    passes of the highest precision, as it always was."""
    return _rows_dot_fwd(x, w)[0]


def _rows_dot_fwd(x, w):
    s3 = _bf16_dot(x, _bf16_pieces(w), ((1,), (0,)))
    return _sum_of_slabs(s3), (x, w)


def _rows_dot_bwd(res, u):
    x, w = res
    with scope("moe.router"):
        dw = _sum_of_slabs(_bf16_dot(x, _bf16_pieces(u), ((0,), (0,))))
        dx = jnp.dot(u, w.T, precision=lax.Precision.HIGHEST)
        return dx.astype(x.dtype), dw


_rows_dot.defvjp(_rows_dot_fwd, _rows_dot_bwd)


def _logits(xf, router, row_scale=None, col_scale=None):
    """The router's logits ``(xf * row_scale[:, None] * col_scale) @ router``
    in fp32 for rows ``xf [n, d]`` in bfloat16: ``row_scale * (xf @
    (col_scale[:, None] * router))``, the fp32 factors moved out of the
    product so that its left operand is the bf16 rows as they are
    (:func:`_rows_dot`)."""
    w = router.astype(jnp.float32)
    if col_scale is not None:
        w = col_scale.astype(jnp.float32)[:, None] * w
    # Under moe_ffn's shard_map the router is one for all members and its
    # cotangent a sum over them: as varying as the rows, that sum is taken
    # behind the product's own cotangent.
    axes = jax.typeof(xf).vma
    s = _rows_dot(xf, lax.pcast(w, tuple(axes - jax.typeof(w).vma),
                                to="varying"))
    if row_scale is not None:
        s = row_scale.astype(jnp.float32)[:, None] * s
    return s


def _chosen(probs, experts):
    """``probs [n, E]`` at ``experts [n, k]`` (distinct within a row), ``[n,
    k]``: ``take_along_axis(probs, experts, -1)`` to the last bit, read by
    comparing the row's j-th index with an iota over the E outputs and
    summing the one term that is left, and differentiated the same way: the
    cotangent of ``probs`` is the sum over the k of ``g[:, j]`` where
    ``experts[:, j]`` is the column, at most one term an entry.  No gather
    forward and no scatter backward, whose scalars cost the TPU 10 ns each;
    k selects over ``[n, E]`` each way, written out, which XLA fuses with
    the scores' neighbours better than one select over ``[n, k, E]``
    (``PERF.md`` §6, PR 48).  What the backward keeps is ``experts`` alone."""
    k, n_experts = experts.shape[1], probs.shape[1]

    def column(a, j):
        return lax.slice_in_dim(a, j, j + 1, axis=1)               # [n, 1]

    def where_chosen(experts, j, values):
        """``values`` in the column that is the row's j-th expert, else 0."""
        outputs = lax.broadcasted_iota(jnp.int32, (1, n_experts), 1)
        return jnp.where(column(experts, j) == outputs, values, 0)

    @jax.custom_vjp
    def read(probs, experts):
        return jnp.stack([jnp.sum(where_chosen(experts, j, probs), axis=1)
                          for j in range(k)], axis=1)

    def backward(experts, g):
        with scope("moe.router"):
            return functools.reduce(jnp.add, [
                where_chosen(experts, j, column(g, j))
                for j in range(k)]), None

    read.defvjp(lambda probs, experts: (read(probs, experts), experts),
                backward)
    return read(probs, experts)


def _within_groups(scores, n_group: int, topk_group: int):
    """``scores [n, experts]`` (what the k experts are chosen by) with every
    expert outside the row's ``topk_group`` best groups set to 0: the experts
    lie in ``n_group`` groups of neighbours, a group's score is the sum of
    its two largest entries, and the k are then taken among the groups kept
    (DeepSeek-V3's node-limited routing, ``transformers``'
    ``DeepseekV3TopkRouter``: ``masked_fill(~mask, 0.0)``, to the letter).
    The mask by comparison, no scatter.  1 and 1: ``scores`` itself."""
    if n_group == topk_group == 1:
        return scores
    n, n_experts = scores.shape
    if n_experts % n_group or not 1 <= topk_group <= n_group \
            or n_experts // n_group < 2:
        raise ValueError(f"{topk_group} of {n_group} groups over {n_experts} "
                         f"experts")
    scores = lax.stop_gradient(scores)
    by_group = scores.reshape(n, n_group, n_experts // n_group)
    # lax.top_k's own order among equal groups: the first topk_group of them.
    _, kept = lax.top_k(jnp.sum(lax.top_k(by_group, 2)[0], axis=-1),
                        topk_group)                          # [n, topk_group]
    mask = jnp.any(kept[:, :, None] == lax.broadcasted_iota(
        jnp.int32, (1, 1, n_group), 2), axis=1)              # [n, n_group]
    return jnp.where(mask[:, :, None], by_group, 0.0).reshape(n, n_experts)


def _route(xf, router, k, norm_topk_prob=False, router_input=None,
           scoring="softmax", bias=None, scale=1.0, row_scale=None,
           col_scale=None, n_group=1, topk_group=1):
    """The router on rows ``xf [n, d]`` (or, where given, on ``router_input
    [rows, tokens, d_r]``, the same n rows, times ``row_scale [rows,
    tokens]`` and ``col_scale [d_r]`` where those are given), in fp32: each
    row's k weights and experts ``[n, k]``, the rows routed to each expert
    ``[experts]``, the load-balancing loss and the z-loss, all over every
    expert of the router.  The logits of rows that are a bfloat16 array are
    three bf16 products over the split weights (:func:`_logits`); rows of
    any other dtype are multiplied in fp32 at the highest precision.
    ``scoring``: the scores are a softmax over the experts, or a
    sigmoid of each logit, whose k weights are divided by their sum plus
    1e-6 under ``norm_topk_prob`` and carry no auxiliary loss (both zero).
    ``bias [experts]`` is added to the scores for the choice of the k alone,
    which is made inside the ``topk_group`` best of ``n_group`` groups of
    experts (:func:`_within_groups`; 1 and 1: among all);
    the weights are the scores themselves, read back at the chosen indices
    by :func:`_chosen` whatever the scoring (the choice sees no tangent).
    ``scale`` multiplies the weights."""
    n, n_experts = xf.shape[0], router.shape[-1]
    with scope("moe.router"):
        if router_input is not None:
            xf = router_input.reshape(n, -1)
        if router_product_passes(xf.dtype) == 3:
            if row_scale is not None:
                row_scale = row_scale.reshape(n)
            logits = _logits(xf, router, row_scale, col_scale)
        elif row_scale is not None or col_scale is not None:
            raise ValueError(f"RouterRows of {xf.dtype} rows: the factors "
                             f"beside them are a bfloat16 stream's")
        else:
            logits = jnp.dot(xf.astype(jnp.float32),
                             router.astype(jnp.float32),
                             precision=lax.Precision.HIGHEST)
        if scoring == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
        elif scoring == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"unknown scoring {scoring!r}")
        chosen_by = probs if bias is None \
            else probs + bias.astype(jnp.float32)
        chosen_by = _within_groups(chosen_by, n_group, topk_group)
        _, experts = lax.top_k(lax.stop_gradient(chosen_by), k)    # [n, k]
        weights = _chosen(probs, experts)
        if norm_topk_prob:
            total = jnp.sum(weights, axis=-1, keepdims=True)
            weights = weights / (total + 1e-6 if scoring == "sigmoid"
                                 else total)
        if scale != 1.0:
            weights = weights * scale
        counts = jnp.sum(jax.nn.one_hot(experts, n_experts, dtype=jnp.int32),
                         axis=(0, 1))                          # [experts]
        if scoring == "sigmoid":
            # The bias is the balancing: no loss of either kind.
            balance = z = jnp.zeros((), jnp.float32)
        else:
            # Switch's loss over top-k (transformers'
            # load_balancing_loss_func): experts * sum_e (routed share of e)
            # * (mean probability of e).
            balance = n_experts * jnp.sum(counts.astype(jnp.float32) / n
                                          * jnp.mean(probs, axis=0))
            z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return weights, experts, counts, balance, z


def _hidden(grouped, rows, gate_up, act, dtype=None):
    """``act(gate x) * up x`` for stacks ``(gate, up)``, ``act(up x)`` for
    ``(up,)``, through the grouped product ``grouped``; the stacks cast to
    ``dtype`` where one is given."""
    def cast(w):
        return w if dtype is None else w.astype(dtype)

    *gate, up = gate_up
    if gate:
        return act(grouped(rows, cast(gate[0]))) * grouped(rows, cast(up))
    return act(grouped(rows, cast(up)))


def _moe_rows(x, router, *stacks, k, dtype, act=jax.nn.silu, **route):
    """:func:`moe_ffn` on the rows of one rank, routed as one set; ``stacks``
    are ``(gate, up, down)`` or, of experts without a gate, ``(up, down)``;
    ``route`` is what :func:`_route` takes besides the rows."""
    *gate_up, down = stacks
    rows, tokens, d = x.shape
    n = rows * tokens
    with scope("moe.dispatch"):
        xf = x.reshape(n, d)
    weights, experts, counts, balance, z = _route(xf, router, k, **route)
    with scope("moe.dispatch"):
        order = jnp.argsort(experts.reshape(n * k))      # stable: by expert
        inverse = jnp.argsort(order)
        slots = _rows_to_slots(xf.astype(dtype), order, inverse, k)
    with scope("moe.experts"):
        grouped = functools.partial(lax.ragged_dot, group_sizes=counts,
                                    preferred_element_type=dtype)
        hidden = _hidden(grouped, slots, gate_up, act, dtype)
        out = grouped(hidden, down.astype(dtype))              # [n*k, d]
    with scope("moe.combine"):
        out = _slots_to_rows(out, order, inverse).reshape(n, k, d)
        y = jnp.einsum("nkd,nk->nd", out.astype(jnp.float32), weights)
        y = y.astype(dtype).reshape(rows, tokens, d)
    with scope("moe.router"):
        return y, MoEStats(balance[None], z[None], counts[None])


# -- a share of the experts ---------------------------------------------------


def _rows_to_tokens(rows, token, group, tokens, ws=None, slot=None):
    """``rows [cap, d]``, each times its slot's weight ``ws[slot]`` where
    weights are given, added up by ``token [cap]`` into ``[tokens, d]``, in
    fp32.  A row whose token is ``tokens`` (an unused place) is dropped.  The
    places are runs of lengths ``group``, the tokens ascending inside each.

    On the TPU, for the shapes it takes, ``kernels/rows_to_tokens.py``: the
    rows read once as they are, each token's sum written once.  Elsewhere
    one scatter-add of the chunk's rows, in the order of the places, where a
    gather the other way round fetches a row for each of the ``tokens * k``
    slots."""
    # Here, not at the top: the package brings flax with it, which a user of
    # horovod_tpu.parallel alone does not import.
    from ..kernels import rows_to_tokens as kernel

    if jax.default_backend() == "tpu" \
            and kernel.takes(*rows.shape, tokens, rows.dtype):
        return kernel.rows_to_tokens(
            rows, token, group, tokens, None if ws is None else ws[slot])
    rows = rows.astype(jnp.float32)
    if ws is not None:
        rows = rows * ws[slot][:, None]
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[token].add(
        rows, mode="drop")


@jax.custom_vjp
def _spread(x, token, group):
    """``x[token]``: the rows of the routed slots that sit at one chunk of the
    sorted places, a zero row where the place is unused (``token`` is then
    one past the last); ``group`` are the lengths of the chunk's runs.  The
    cotangent adds the chunk's rows up by token in fp32
    (:func:`_rows_to_tokens`), where autodiff would add in ``x``'s dtype."""
    return x.at[token].get(mode="fill", fill_value=0)


def _spread_fwd(x, token, group):
    # x[:, :0] holds nothing and tells the cotangent how many tokens there are.
    return _spread(x, token, group), (token, group, x[:, :0])


def _spread_bwd(res, g):
    token, group, like = res
    with scope("moe.dispatch"):
        return (_rows_to_tokens(g, token, group, like.shape[0])
                .astype(g.dtype),
                None, None)


_spread.defvjp(_spread_fwd, _spread_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine(out, ws, slot, token, group, tokens):
    """``y[t] = sum ws[slot[p]] * out[p]`` over the chunk's places p that
    hold a slot of token t, in fp32 (:func:`_rows_to_tokens`; ``group`` are
    the lengths of the chunk's runs).  Backward, both cotangents are taken
    at the chunk's places (a gather of ``g`` rows by token), so nothing of
    ``[tokens*k, d]`` is fetched or kept in either direction."""
    return _rows_to_tokens(out, token, group, tokens, ws, slot)


def _combine_fwd(out, ws, slot, token, group, tokens):
    return (_combine(out, ws, slot, token, group, tokens),
            (out, ws, slot, token))


def _combine_bwd(tokens, res, g):
    out, ws, slot, token = res
    with scope("moe.combine"):
        g_rows = g.at[token].get(mode="fill", fill_value=0)    # [cap, d] fp32
        d_out = (g_rows * ws[slot][:, None]).astype(out.dtype)
        # An unused place of ``out`` holds whatever the grouped product left.
        d_w = jnp.where(token < tokens,
                        jnp.sum(g_rows * out.astype(jnp.float32), axis=-1), 0)
        # The slots of a chunk's places are distinct, its unused places' too.
        return (d_out,
                jnp.zeros_like(ws).at[slot].set(d_w.astype(ws.dtype),
                                                unique_indices=True),
                None, None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def _held_chunk(xf, ws, *rest, k, cap, dtype, act=jax.nn.silu):
    """What the held experts give for the routed slots at sorted places
    ``lo .. lo+cap``: ``[tokens, d]`` in fp32.  ``rest`` are the experts'
    stacks (``gate, up, down``, or ``up, down`` without a gate), then
    ``order, sizes, lo``.  ``sizes`` are the held experts' row counts over
    the whole step; this chunk takes of each what falls inside it."""
    *gate_up, down, order, sizes, lo = rest
    tokens = xf.shape[0]
    with scope("moe.dispatch"):
        ends = jnp.cumsum(sizes)
        group = jnp.clip(ends, lo, lo + cap) \
            - jnp.clip(ends - sizes, lo, lo + cap)
        slot = lax.dynamic_slice_in_dim(order, lo, cap)
        # Each place's token, and one past the last where it is unused.
        token = jnp.where(jnp.arange(cap) < jnp.sum(group), slot // k, tokens)
        rows_in = _spread(xf, token, group)
    with scope("moe.experts"):
        grouped = functools.partial(lax.ragged_dot, group_sizes=group,
                                    preferred_element_type=dtype)
        hidden = _hidden(grouped, rows_in, gate_up, act)
        out = grouped(hidden, down)                            # [cap, d]
    with scope("moe.combine"):
        return _combine(out, ws, slot, token, group, tokens)


def overflow_reached(rows_held, first: int, quantum: int):
    """The chunks of ``quantum`` places that ``rows_held`` rows need behind a
    first chunk of ``first``: what the loops of :func:`_chunks` run
    and :func:`count_routing` counts."""
    return (jnp.maximum(rows_held - first, 0) + quantum - 1) // quantum


def _chunks(chunk, first: int, quantum: int):
    """The held experts' rows in chunks: the first, ``first`` places, on the
    normal path (its residuals kept), and behind it ``quantum`` places at a
    time in a loop of as many trips as the held rows reach
    (:func:`overflow_reached`: none in a step whose routing stays inside the
    first chunk), recomputed in the backward pass instead of kept.  No
    routing drops a row.  Both directions are written out, so neither
    differentiates through a loop and each loop is a ``while``; what the
    first chunk gives, its sum forward and its cotangents (of the rows, the
    weights and the stacks) backward, is
    what the loops start from, so a loop of no trip costs no pass over
    either."""

    def place(j):
        return first + j * quantum

    def reached(sizes):
        return overflow_reached(jnp.sum(sizes), first, quantum)

    # The loops lie under ``moe.combine`` in both directions; what a chunk
    # does keeps its own scopes inside.
    def behind(y, *operands):
        with scope("moe.combine"):
            return lax.fori_loop(
                0, reached(operands[-1]),
                lambda j, y: y + chunk(*operands, place(j), cap=quantum), y)

    @jax.custom_vjp
    def run(*operands):
        return behind(chunk(*operands, 0, cap=first), *operands)

    def fwd(*operands):
        weights, (order, sizes) = operands[:-2], operands[-2:]
        y, back = jax.vjp(
            lambda *w: chunk(*w, order, sizes, 0, cap=first), *weights)
        return behind(y, *operands), (back, operands)

    def bwd(res, g):
        back, operands = res
        weights, (order, sizes) = operands[:-2], operands[-2:]

        def more(j, acc):
            _, vjp = jax.vjp(
                lambda *w: chunk(*w, order, sizes, place(j), cap=quantum),
                *weights)
            return tuple(a + d for a, d in zip(acc, vjp(g)))

        acc = back(g)
        with scope("moe.combine"):
            acc = lax.fori_loop(0, reached(sizes), more, acc)
        return (*acc, None, None)

    run.defvjp(fwd, bwd)

    def varying(xf, *rest):
        # Under moe_ffn's shard_map the expert weights are one for all
        # members and their cotangent a sum over them.  Made as varying as
        # the rows out here, that sum is taken once behind the loop, not in
        # trips of which each member makes its own number.
        axes = jax.typeof(xf).vma
        return run(xf, *(
            lax.pcast(a, tuple(axes - jax.typeof(a).vma), to="varying")
            for a in rest))

    return varying


def row_quantum(slots: int, n_held: int, n_experts: int) -> int:
    """Rows an overflow chunk: a quarter of the mean share ``slots * n_held /
    n_experts`` of the ``slots`` routed rows that the ``n_held`` of
    ``n_experts`` experts held here take (or, where that is no whole
    multiple of the kernel's 128 rows and at least as many, the first
    multiple above it that divides the slots).  0 where the rows are taken as one
    chunk of them all: the quarter is no whole multiple of the 128 rows
    ``kernels/rows_to_tokens.py`` multiplies at a time (so the kernel would
    refuse it and the first chunk), the slots behind the first chunk
    (:func:`row_buffer`) are no whole number of quarters, or there are
    none."""
    # Here, not at the top: see _rows_to_tokens.
    from ..kernels import rows_to_tokens as kernel

    quantum, rest = divmod(slots * n_held, 4 * n_experts)
    if quantum >= kernel.CHUNK and (rest or quantum % kernel.CHUNK):
        # A quarter that is rows enough for the kernel and no whole number
        # of its 128: the next multiple of 128 above it that the slots are
        # whole chunks of, if there is one under twice the quarter (22 of
        # 512 experts a token over 8192 tokens, 8 held: 704 -> 1024).
        quarter, quantum = quantum, 0
        for rows in range(-(-(quarter + 1) // kernel.CHUNK) * kernel.CHUNK,
                          2 * quarter, kernel.CHUNK):
            if slots % rows == 0:
                quantum = rows
                break
    elif rest:
        return 0
    if not quantum or quantum % kernel.CHUNK or slots % quantum \
            or 5 * quantum >= slots:
        return 0
    return quantum


def row_buffer(slots: int, n_held: int, n_experts: int):
    """(chunks, rows of the first chunk) for ``slots`` routed rows of which
    the ``n_held`` of ``n_experts`` held here take ``n_held / n_experts`` on
    average: a first chunk of five quarters of that share (five of
    :func:`row_quantum`'s rows: more where it rounded the quarter up to the
    kernel's 128), which every step
    runs, and behind it as many chunks of a quarter (:func:`row_quantum`) as
    the worst routing (every row here) needs, of which a step runs those its
    rows reach.  Five quarters: at the mean itself an even routing overflows
    every other step, and every place costs its gathered row, its activation
    and its fp32 cotangent row whether a row sits in it or not (the grouped
    products and the rows kernel follow the rows: ``docs/moe.md``)."""
    quantum = row_quantum(slots, n_held, n_experts)
    if not quantum:
        return 1, slots
    return slots // quantum - 4, 5 * quantum


def _moe_rows_share(x, router, *stacks, k, dtype, held, act=jax.nn.silu,
                    **route):
    """:func:`moe_ffn` on the rows of one rank where only ``held`` of the
    router's experts live here; ``stacks`` as :func:`_moe_rows` takes them;
    ``route`` is what :func:`_route` takes besides the rows."""
    rows, tokens, d = x.shape
    n, n_experts = rows * tokens, router.shape[-1]
    held = tuple(held)
    if len(held) != stacks[0].shape[0] or len(set(held)) != len(held) \
            or not all(0 <= e < n_experts for e in held):
        raise ValueError(f"held experts {held} for {stacks[0].shape[0]} "
                         f"stacked experts and a router of {n_experts}")
    with scope("moe.dispatch"):
        xf = x.reshape(n, d)
    weights, experts, counts, balance, z = _route(xf, router, k, **route)
    with scope("moe.dispatch"):
        # Each routed slot's expert as its index among the held ones; the
        # slots bound elsewhere sort behind them all.  By comparison with
        # every held id: a table lookup is a gather of tokens * k scalars
        # (1.0 ms a layer on a v5e at 131,072 slots; PERF.md, PR 31).
        match = experts.reshape(n * k, 1) == np.asarray(held, np.int32)
        local = jnp.where(jnp.any(match, axis=1), jnp.argmax(match, axis=1),
                          len(held))
        order = jnp.argsort(local)
        sizes = counts[np.asarray(held)]
        rows_in = (xf.astype(dtype), weights.reshape(n * k))
    with scope("moe.experts"):
        stacks = tuple(w.astype(dtype) for w in stacks)
    _, first = row_buffer(n * k, len(held), n_experts)
    quantum = row_quantum(n * k, len(held), n_experts)
    chunk = functools.partial(_held_chunk, k=k, dtype=dtype, act=act)
    operands = (*rows_in, *stacks, order, sizes)
    if quantum:
        y = _chunks(chunk, first, quantum)(*operands)
    else:
        y = chunk(*operands, 0, cap=first)
    with scope("moe.combine"):
        y = y.astype(dtype).reshape(rows, tokens, d)
    with scope("moe.router"):
        return y, MoEStats(balance[None], z[None], counts[None])


def moe_ffn(x: jax.Array, router: jax.Array, gate: Optional[jax.Array],
            up: jax.Array, down: jax.Array, *, k: int, data_axis: Optional[str] = None,
            dtype=jnp.bfloat16, held: Optional[Sequence[int]] = None,
            norm_topk_prob: bool = False,
            router_input: Union[jax.Array, RouterRows, None] = None,
            activation: str = "silu", scoring: str = "softmax",
            bias: Optional[jax.Array] = None, scale: float = 1.0,
            n_group: int = 1, topk_group: int = 1):
    """Dropless top-k expert layer: ``sum_j p_j * down_j(act(gate_j x) *
    up_j x)`` over a token's k best-scored experts, the scores a softmax
    over all experts, renormalised over the k only with
    ``norm_topk_prob``.

    - ``x``: ``[rows, tokens, d]``;
    - ``router``: ``[d, experts]``; logits, scores and top-k run in fp32;
    - ``gate``, ``up``: ``[experts, d, width]``; ``down``:
      ``[experts, width, d]``; multiplied in ``dtype``.  ``gate`` None: the
      experts have no gate and are ``down(act(up x))``;
    - ``held``: the ids of the experts that live here, in the order of the
      stacks, where a layer's experts are shared among chips (default: all,
      and the layer lowers to what it lowered to without the option).  The
      router, its scores, the top k, the renormalisation, the counts and the
      auxiliary losses are over all experts wherever they live; only the rows
      routed to a held expert are sorted and multiplied, and the sum returned
      is those experts' part of the layer: what the absent ones add is added
      by whoever holds them (on one chip, by no one).  No row bound here is
      dropped: the rows are taken in chunks of :func:`row_buffer`'s sizes, a
      first of five quarters of the mean share always, then a quarter at a
      time in a loop of as many trips as the step's routing reaches.
    - ``router_input``: ``[rows, tokens, d_r]``, what the router reads where
      that is not the rows it multiplies (SmallThinker routes by the block's
      input, before attention; ``router`` is then ``[d_r, experts]``).  Its
      gradient flows through the k weights and the auxiliary losses.  Or a
      :class:`RouterRows`: those rows beside an fp32 factor a row and one a
      column, the router reading their product (an RMSNorm of a bf16 stream
      handed over as the stream and the norm's two factors).  Rows that are
      a bfloat16 array, given either way or as ``x`` itself, are multiplied
      in three bf16 passes over the split weights (:func:`_rows_dot`); any
      other dtype in fp32 at the highest precision.
    - ``activation``: the gate's, ``"silu"`` or ``"relu"``, or, without a
      gate, the hidden layer's (``"relu2"``: the relu squared).
    - ``scoring``: ``"softmax"``, or ``"sigmoid"``: each expert's score is
      the sigmoid of its logit, the k weights are divided by their sum plus
      1e-6 under ``norm_topk_prob``, and there is no auxiliary loss (the
      stats' two losses are zeros).
    - ``bias``: ``[experts]`` fp32, added to the scores where the k experts
      are chosen and nowhere else: the weights are the scores without it, and
      no gradient reaches it.  It is the caller's state
      (:func:`update_expert_bias` after each step, from
      ``MoEStats.tokens_per_expert``).
    - ``scale``: a factor on the k weights (``routed_scaling_factor``).
    - ``n_group``, ``topk_group``: the k are chosen inside the ``topk_group``
      best of ``n_group`` groups of experts (:func:`_within_groups`); 1, 1:
      among all, and the layer lowers to what it lowered to without them.

    All of the rows given are routed as one set: sorted by expert, multiplied
    by a grouped matmul, brought back.  The auxiliary losses are taken over
    that same set.  Under a mesh in context (``jax.set_mesh``) that binds
    ``data_axis``, the rows are taken as sharded over that axis and every
    member routes its own rows inside a ``shard_map``: a program traced over
    the global batch (``hvd.make_overlapped_train_step``) then holds no sort,
    gather or count across ranks, only the gradients' all-reduce.  Where no
    mesh binds the axis the caller holds only its own rows (the eager path,
    a plain ``jit``).

    Returns ``(y [rows, tokens, d] in dtype, MoEStats)``.
    """
    common = dict(k=k, dtype=dtype, norm_topk_prob=norm_topk_prob,
                  act=_activation(activation), scoring=scoring, scale=scale,
                  n_group=n_group, topk_group=topk_group)
    if held is None:
        body = functools.partial(_moe_rows, **common)
    else:
        body = functools.partial(_moe_rows_share, held=held, **common)
    # The rows themselves are what the router reads by default, and no bias:
    # the program is then the one without the arguments.
    sharded = P(data_axis)
    extras = {}
    if isinstance(router_input, RouterRows):
        router_input, row_scale, col_scale = router_input
        extras["row_scale"] = (row_scale, sharded)
        extras["col_scale"] = (col_scale, P())
    if router_input is not None and (router_input is not x or extras):
        if router_input.shape[:2] != x.shape[:2]:
            raise ValueError(f"router_input {router_input.shape} for rows "
                             f"{x.shape}")
        extras["router_input"] = (router_input, sharded)
    if bias is not None:
        extras["bias"] = (bias, P())

    stacks = (up, down) if gate is None else (gate, up, down)

    def call(x, router, *rest):
        return body(x, router, *rest[:len(stacks)],
                    **dict(zip(extras, rest[len(stacks):])))

    operands = (x, router, *stacks) + tuple(
        value for value, _ in extras.values())
    if data_axis is None or \
            data_axis not in jax.sharding.get_abstract_mesh().axis_names:
        return call(*operands)
    return jax.shard_map(
        call,
        in_specs=(sharded, P()) + (P(),) * len(stacks) + tuple(
            spec for _, spec in extras.values()),
        out_specs=(sharded, MoEStats(sharded, sharded, sharded)),
    )(*operands)


def update_expert_bias(bias: jax.Array, tokens_per_expert: jax.Array,
                       rate: float) -> jax.Array:
    """The selection bias after a step that routed ``tokens_per_expert``
    (``[layers, experts]``, or ``[layers, sets, experts]`` as
    ``MoEStats`` stacks them: the sets are then summed, which under
    ``data_axis`` sums over every rank's rows): ``b + rate * sign(mean - n)``
    an expert, ``mean`` the layer's rows an expert (DeepSeek-V3's
    auxiliary-loss-free balancing, arXiv:2412.19437 section 2.1.2): an
    expert that got more than its share is chosen a little less readily in
    the next step.  ``bias``: ``[layers, experts]`` fp32; runs inside the
    step, on the device, outside the gradient."""
    n = tokens_per_expert
    if n.ndim == bias.ndim + 1:
        n = jnp.sum(n, axis=-2)
    with scope("moe.router"):
        n = n.astype(jnp.float32)
        return bias + rate * jnp.sign(jnp.mean(n, axis=-1, keepdims=True) - n)


def moe_counters(n_layers: int, n_experts: int, share: bool = False,
                 expert_bias: bool = False, overflow: bool = False) -> dict:
    """Zeroed router counters for a step's ``aux``; with ``share`` also the
    rows routed to the experts held here and those bound elsewhere, a
    layer; with ``expert_bias`` also the layers' selection bias
    (``moe_ffn(bias=)``), state that :func:`count_routing` steps; with
    ``overflow`` (of a ``share``) also the chunks behind the first that ran,
    a layer."""
    counters = {"tokens_per_expert":
                jnp.zeros((n_layers, n_experts), jnp.int32),
                "steps": jnp.zeros((), jnp.int32)}
    if share:
        counters["rows_held"] = jnp.zeros((n_layers,), jnp.int32)
        counters["rows_elsewhere"] = jnp.zeros((n_layers,), jnp.int32)
    if expert_bias:
        counters["expert_bias"] = jnp.zeros((n_layers, n_experts),
                                            jnp.float32)
    if overflow:
        counters["overflow_chunks"] = jnp.zeros((n_layers,), jnp.int32)
    return counters


def count_routing(counters: dict, tokens_per_expert: jax.Array,
                  held: Optional[Sequence[int]] = None,
                  bias_update_rate: float = 0.0,
                  slots: Optional[int] = None) -> dict:
    """``counters`` after one more step that routed ``tokens_per_expert``
    ``[layers, experts]``; runs inside the step, on the device.  ``held``:
    the ids of the experts that live here, for counters made with
    ``share``.  Counters made with ``expert_bias`` have it stepped by
    :func:`update_expert_bias` at ``bias_update_rate``.  Counters made with
    ``overflow`` need ``slots``, the routed rows a layer and call of
    :func:`moe_ffn` (its tokens times k), and gain the chunks behind
    :func:`row_buffer`'s first that those calls ran."""
    out = {"tokens_per_expert":
           counters["tokens_per_expert"] + tokens_per_expert,
           "steps": counters["steps"] + 1}
    if held is not None:
        here = jnp.sum(tokens_per_expert[:, np.asarray(held)], axis=1)
        out["rows_held"] = counters["rows_held"] + here
        out["rows_elsewhere"] = counters["rows_elsewhere"] \
            + jnp.sum(tokens_per_expert, axis=1) - here
    if "expert_bias" in counters:
        out["expert_bias"] = update_expert_bias(
            counters["expert_bias"], tokens_per_expert, bias_update_rate)
    if "overflow_chunks" in counters:
        sizes = (slots, len(held), tokens_per_expert.shape[-1])
        quantum = row_quantum(*sizes)
        out["overflow_chunks"] = counters["overflow_chunks"] + (
            overflow_reached(here, row_buffer(*sizes)[1], quantum)
            if quantum else 0)
    return out


def publish_routing(counters: dict) -> dict:
    """Read the counters to the host (outside the step: it waits for the
    device) and set the gauges ``moe_max_load_ratio`` (busiest expert over the
    mean, per layer), ``moe_routed_tokens_per_step`` and ``moe_steps``; for
    counters of a share of the experts also, per layer,
    ``moe_rows_held_per_step`` (rows the experts here multiplied) and
    ``moe_rows_elsewhere_share`` (the share of the routed rows bound for
    experts that live elsewhere); for counters with the overflow, per layer,
    ``moe_overflow_chunks_per_step`` (chunks behind the first that ran); for
    counters with the selection bias, per layer,
    ``moe_expert_bias_abs_max``.
    Returns ``{"max_load_ratio": [per layer], "steps": n}``, with
    ``"rows_held_per_step"`` and ``"rows_elsewhere_share"`` for a share,
    ``"overflow_chunks_per_step"`` with the overflow and
    ``"expert_bias_abs_max"`` with a bias."""
    import numpy as np

    from ..core import metrics

    counts = np.asarray(counters["tokens_per_expert"], dtype=np.float64)
    steps = int(counters["steps"])
    ratios = [float(c.max() / c.mean()) if c.sum() else float("nan")
              for c in counts]
    for layer, ratio in enumerate(ratios):
        metrics.set_gauge("moe_max_load_ratio", ratio, layer=str(layer))
    metrics.set_gauge("moe_routed_tokens_per_step",
                      float(counts.sum()) / max(steps, 1))
    metrics.set_gauge("moe_steps", steps)
    out = {"max_load_ratio": ratios, "steps": steps}
    if "rows_held" in counters:
        here = np.asarray(counters["rows_held"], dtype=np.float64)
        away = np.asarray(counters["rows_elsewhere"], dtype=np.float64)
        out["rows_held_per_step"] = [float(h) / max(steps, 1) for h in here]
        out["rows_elsewhere_share"] = [
            float(a / (h + a)) if h + a else float("nan")
            for h, a in zip(here, away)]
        for layer, (per_step, share) in enumerate(zip(
                out["rows_held_per_step"], out["rows_elsewhere_share"])):
            metrics.set_gauge("moe_rows_held_per_step", per_step,
                              layer=str(layer))
            metrics.set_gauge("moe_rows_elsewhere_share", share,
                              layer=str(layer))
    if "overflow_chunks" in counters:
        out["overflow_chunks_per_step"] = [
            float(c) / max(steps, 1)
            for c in np.asarray(counters["overflow_chunks"])]
        for layer, per_step in enumerate(out["overflow_chunks_per_step"]):
            metrics.set_gauge("moe_overflow_chunks_per_step", per_step,
                              layer=str(layer))
    if "expert_bias" in counters:
        out["expert_bias_abs_max"] = [
            float(b) for b in np.abs(np.asarray(
                counters["expert_bias"], dtype=np.float64)).max(axis=1)]
        for layer, b in enumerate(out["expert_bias_abs_max"]):
            metrics.set_gauge("moe_expert_bias_abs_max", b, layer=str(layer))
    return out
