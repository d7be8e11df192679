"""Ring attention: blockwise sequence/context parallelism.

Not present in the reference (SURVEY §5.7 — it scales batch, never
sequence); required here because long-context is first-class for the TPU
build.  Design: Q/K/V are sharded along the sequence axis over the ``seq``
mesh axis.  Each device keeps its Q shard resident and streams K/V shards
around the ring with ``ppermute`` (ICI-neighbor CollectivePermute — the
cheapest TPU collective), accumulating attention with the numerically-stable
online-softmax (flash) recurrence.  Communication overlaps compute: XLA
schedules the ppermute of block t+1 concurrently with the matmuls of block
t because there is no data dependence between them.

Memory per device is O(seq/n) for activations — full-sequence attention
never materializes.  Causal masking is applied per block from global
positions; blocks entirely in the future contribute nothing (their masked
exp() terms are zero) but are still computed — a pallas kernel that skips
them is the profile-guided next step (`/opt/skills/guides/pallas_guide.md`).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .collectives import axis_size
from .mesh import AXIS_SEQ


def _online_block(carry, kv_block, q, q_pos, kv_pos_fn, scale, causal):
    """One flash-accumulation step against the K/V block currently held.

    carry: (o, m, l, step) with o [b,h,sq,d], m/l [b,h,sq,1].
    kv_block: (k, v) each [b, skv, h, d].
    """
    o, m, l, step = carry
    k, v = kv_block
    # [b, h, sq, skv]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        kv_pos = kv_pos_fn(step)                       # [skv]
        mask = q_pos[:, None] >= kv_pos[None, :]       # [sq, skv]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # Guard -inf - -inf = nan for fully-masked rows / first block.
    alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_new))
    p = jnp.exp(s - m_new)
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    o = o * alpha + pv
    return (o, m_new, l, step + 1)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = AXIS_SEQ, causal: bool = False,
                   sm_scale: Optional[float] = None) -> jax.Array:
    """Ring self-attention over sequence shards.

    Must run inside ``shard_map`` with ``axis_name`` bound; q/k/v are the
    local shards shaped ``[batch, seq_shard, heads, head_dim]`` (sequence
    split contiguously across the axis, shard i owning positions
    ``[i*seq_shard, (i+1)*seq_shard)``).  Returns the local output shard in
    q's dtype.
    """
    n = axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = sm_scale if sm_scale is not None else d ** -0.5

    q32 = q.astype(jnp.float32)
    q_pos = my_idx * sq + jnp.arange(sq)

    def kv_pos_fn(step):
        # After `step` +1-shifts, this device holds the block that
        # originated on rank (my_idx - step) mod n.
        owner = (my_idx - step) % n
        return owner * skv + jnp.arange(skv)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def scan_body(carry, _):
        o_m_l_step, (k_cur, v_cur) = carry
        new_acc = _online_block(o_m_l_step, (k_cur, v_cur), q32, q_pos,
                                kv_pos_fn, scale, causal)
        k_nxt = lax.ppermute(k_cur, axis_name, perm=perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm=perm)
        return (new_acc, (k_nxt, v_nxt)), None

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), jnp.float32)
    init = ((o0, m0, l0, jnp.zeros((), jnp.int32)), (k, v))
    (final_acc, _), _ = lax.scan(scan_body, init, None, length=n)
    o, _, l, _ = final_acc
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros, not NaN
    out = (o / l).astype(q.dtype)
    return jnp.einsum("bhqd->bqhd", out)
