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
routed rows a token, not one per expert.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

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
    return (g[inverse].reshape(n, k, -1).sum(axis=1, dtype=jnp.float32)
            .astype(g.dtype),
            None, None)


_rows_to_slots.defvjp(_rows_to_slots_fwd, _rows_to_slots_bwd)


@jax.custom_vjp
def _slots_to_rows(y, order, inverse):
    """``y[inverse]``: the slots back in token order; the cotangent is the
    gather through ``order``."""
    return y[inverse]


_slots_to_rows.defvjp(lambda y, order, inverse: (y[inverse], order),
                      lambda order, g: (g[order], None, None))


def _moe_rows(x, router, gate, up, down, *, k, dtype):
    """:func:`moe_ffn` on the rows of one rank, routed as one set."""
    rows, tokens, d = x.shape
    n, n_experts = rows * tokens, router.shape[-1]
    xf = x.reshape(n, d)
    with jax.named_scope("hvd.moe.router"):
        logits = jnp.dot(xf.astype(jnp.float32), router.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = lax.top_k(probs, k)                 # [n, k]
        counts = jnp.sum(jax.nn.one_hot(experts, n_experts, dtype=jnp.int32),
                         axis=(0, 1))                          # [experts]
        # Switch's loss over top-k (transformers' load_balancing_loss_func):
        # experts * sum_e (routed share of e) * (mean probability of e).
        balance = n_experts * jnp.sum(counts.astype(jnp.float32) / n
                                      * jnp.mean(probs, axis=0))
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    with jax.named_scope("hvd.moe.dispatch"):
        order = jnp.argsort(experts.reshape(n * k))      # stable: by expert
        inverse = jnp.argsort(order)
        slots = _rows_to_slots(xf.astype(dtype), order, inverse, k)
    with jax.named_scope("hvd.moe.experts"):
        grouped = functools.partial(lax.ragged_dot, group_sizes=counts,
                                    preferred_element_type=dtype)
        hidden = jax.nn.silu(grouped(slots, gate.astype(dtype))) \
            * grouped(slots, up.astype(dtype))
        out = grouped(hidden, down.astype(dtype))              # [n*k, d]
    with jax.named_scope("hvd.moe.combine"):
        out = _slots_to_rows(out, order, inverse).reshape(n, k, d)
        y = jnp.einsum("nkd,nk->nd", out.astype(jnp.float32), weights)
    return (y.astype(dtype).reshape(rows, tokens, d),
            MoEStats(balance[None], z[None], counts[None]))


def moe_ffn(x: jax.Array, router: jax.Array, gate: jax.Array, up: jax.Array,
            down: jax.Array, *, k: int, data_axis: Optional[str] = None,
            dtype=jnp.bfloat16):
    """Dropless top-k expert layer: ``sum_j p_j * down_j(silu(gate_j x) *
    up_j x)`` over a token's k most probable experts, the probabilities a
    softmax over all experts and not renormalised.

    - ``x``: ``[rows, tokens, d]``;
    - ``router``: ``[d, experts]``; logits, softmax and top-k run in fp32;
    - ``gate``, ``up``: ``[experts, d, width]``; ``down``:
      ``[experts, width, d]``; multiplied in ``dtype``.

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
    body = functools.partial(_moe_rows, k=k, dtype=dtype)
    if data_axis is None or \
            data_axis not in jax.sharding.get_abstract_mesh().axis_names:
        return body(x, router, gate, up, down)
    sharded = P(data_axis)
    return jax.shard_map(
        body, in_specs=(sharded, P(), P(), P(), P()),
        out_specs=(sharded, MoEStats(sharded, sharded, sharded)),
    )(x, router, gate, up, down)


def moe_counters(n_layers: int, n_experts: int) -> dict:
    """Zeroed router counters for a step's ``aux``."""
    return {"tokens_per_expert": jnp.zeros((n_layers, n_experts), jnp.int32),
            "steps": jnp.zeros((), jnp.int32)}


def count_routing(counters: dict, tokens_per_expert: jax.Array) -> dict:
    """``counters`` after one more step that routed ``tokens_per_expert``
    ``[layers, experts]``; runs inside the step, on the device."""
    return {"tokens_per_expert":
            counters["tokens_per_expert"] + tokens_per_expert,
            "steps": counters["steps"] + 1}


def publish_routing(counters: dict) -> dict:
    """Read the counters to the host (outside the step: it waits for the
    device) and set the gauges ``moe_max_load_ratio`` (busiest expert over the
    mean, per layer), ``moe_routed_tokens_per_step`` and ``moe_steps``.
    Returns ``{"max_load_ratio": [per layer], "steps": n}``."""
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
    return {"max_load_ratio": ratios, "steps": steps}
