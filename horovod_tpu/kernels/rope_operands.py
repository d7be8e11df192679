"""Rotary positions on q and k, written as the attention kernels take them,
each activation read once and written once a direction.

``models/transformer.py::Attention`` projects a position to ``h`` query heads
and ``h_kv`` key heads of 128 side by side, ``[b, s, h * 128]`` and ``[b, s,
h_kv * 128]``; the attention kernels (``kernels/masked_attention.py``) take
``q`` turned by its position and scaled and ``k`` turned, ``[b, h, s, 128]``
and ``[b, h_kv, s, 128]``.  What lies between is

    q_j <- turn(q_j) * scale          into [b, h, s, 128]
    k_j <- turn(k_j)                  into [b, h_kv, s, 128]
    turn(t) = t * cos + swap(t) * sin        (the halves of the turned
                                              columns change places)

and backward the same turned back (a rotation's transpose is the rotation by
the other angle: no residual but the two tables).  Left to XLA that is
``_rope``'s cast, split, four products, concatenation and cast, autodiff's
pads and slices for their transposes, and the wrapper's scale and copies into
``[b, h, s, d]``: 419 small operations a step of Laguna-S-2.1 at 386 to 465
GB/s (27.6 ms and 6.7 more of the copies, where these kernels and what is
left around them take 7.0 and 0.4: ``PERF.md`` section 6, PR 65); here
the forward kernel reads q and k once and writes them once, and the backward
kernel reads ``dq`` and ``dk`` once and writes the cotangents ``[b, s, h *
128]``, which the projections' backward products contract as they lie.

**The tables** are the caller's (:func:`tables`), ``[s, 128]`` in fp32 each:
``[cos ; cos]`` and ``[-sin ; sin]`` over the turned columns and 1 and 0 over
the columns a partial share leaves alone, so the kernels know nothing of
theta, YaRN, ``attention_factor`` or ``positions``; they are told ``half``,
how far apart the two columns of a pair lie (64: the whole head turns; 32:
its first half, Laguna-S-2.1's global layers).

**How it goes.**  A grid step is :data:`TILE` positions of one KV head and
the query heads it serves, which lie side by side in the projection: one
block ``[tile, group * 128]`` of q and one ``[tile, 128]`` of k in, ``[group,
tile, 128]`` and ``[tile, 128]`` out.  The halves change places by one
rotation of the lanes where the whole head turns, by two and a select where
half of it does.  The KV heads are the innermost axis, so the tables' blocks
stay where they are while a tile's heads go by.

**Precision**: what ``_rope`` and the wrapper's scale did: the rotation in
fp32 on the bf16 projection, one rounding behind it, then the product with
the scale rounded to the rows' dtype, rounded again; backward ``dq * scale``
rounded, turned back in fp32, rounded.

On the device's op line the calls are :data:`FWD_NAME` and :data:`BWD_NAME`.
Pallas is imported where a kernel is built; each direction is one jitted
function, traced once a process and shape whatever the number of layers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import masked_attention
# The scale as the rows' dtype has it, a product rounded to that dtype, an
# output's shape and type: latent attention's pass has the same three.
from .mla_operands import _like, _rounded, _scaled

FWD_NAME = "hvd_rope_operands_fwd"
BWD_NAME = "hvd_rope_operands_bwd"
OP_LINE_NAMES = r"^hvd_rope_operands"

_LANES = 128
# Positions a grid step.  Measured on a v5e, a kernel alone (PERF.md section
# 6, PR 65; ``benchmarks/results/rope_operands_sweep_pr65*.jsonl``): 72 heads
# on 8 at 8192 positions 0.55 to 0.57 ms a call either direction at tiles of
# 256, 512, 1024 and 2048 and with 1, 2 or 4 KV heads a grid step (607 to 624
# GB/s over the bytes: the copies' pace, whatever the blocks), so the tile is
# the attention kernels' own and a grid step one KV head.
TILE = masked_attention.BLOCK
_VMEM_LIMIT = 64 * 2 ** 20


def takes(rule, seq_len: int, head_dim: int, turned: int, dtype) -> bool:
    """Whether a layer's q and k go through the kernels: on a TPU, bf16 heads
    of one lane group of which all or the first half turns, under a rule
    (``rule`` None: no rule) whose attention kernels take them in this
    module's output layout, the positions whole tiles.  Otherwise ``_rope``
    and the wrapper's copies."""
    return (jax.default_backend() == "tpu" and rule is not None
            and jnp.dtype(dtype) == jnp.bfloat16 and head_dim == _LANES
            and turned in (_LANES, _LANES // 2) and seq_len % TILE == 0
            and masked_attention.takes(rule, seq_len, head_dim))


def tables(angles, width: int = _LANES, factor: float = 1.0):
    """The cosines and sines of ``angles [s, half]`` (``_rope_angles``'),
    times ``factor``, as the kernels take them, ``[s, width]`` in fp32 each:
    ``[cos ; cos]`` and ``[-sin ; sin]``, and 1 and 0 over the columns from
    ``2 * half`` on."""
    s, half = angles.shape
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    rest = (s, width - 2 * half)
    return (jnp.concatenate([cos, cos, jnp.ones(rest, cos.dtype)], axis=-1),
            jnp.concatenate([-sin, sin, jnp.zeros(rest, sin.dtype)], axis=-1))


def _turn(t, cos, sin, half: int):
    """One head's ``[tile, 128]`` turned: in fp32, rounded to ``t``'s
    dtype.  Column ``l`` of a turned pair meets column ``l + half`` or ``l -
    half``; beyond the turned columns the sine is 0 and what it meets is
    nothing."""
    from jax.experimental.pallas import tpu as pltpu

    t32 = t.astype(jnp.float32)
    if 2 * half == _LANES:
        swapped = pltpu.roll(t32, half, 1)
    else:
        lane = lax.broadcasted_iota(jnp.int32, t32.shape, 1)
        swapped = jnp.where(lane % (2 * half) < half,
                            pltpu.roll(t32, _LANES - half, 1),  # from l + half
                            pltpu.roll(t32, half, 1))           # from l - half
    return (t32 * cos + swapped * sin).astype(t.dtype)


def _head(g: int):
    return slice(g * _LANES, (g + 1) * _LANES)


def _fwd_kernel(cos_ref, sin_ref, q_ref, k_ref, qo_ref, ko_ref, *, half: int,
                scale: float):
    cos, sin = cos_ref[...], sin_ref[...]
    for g in range(qo_ref.shape[0]):
        qo_ref[g] = _scaled(_turn(q_ref[:, _head(g)], cos, sin, half), scale)
    for g in range(ko_ref.shape[0]):
        ko_ref[g] = _turn(k_ref[:, _head(g)], cos, sin, half)


def _bwd_kernel(cos_ref, sin_ref, dq_ref, dk_ref, dqo_ref, dko_ref, *,
                half: int, scale: float):
    cos, sin = cos_ref[...], -sin_ref[...]
    for g in range(dq_ref.shape[0]):
        dqo_ref[:, _head(g)] = _turn(_scaled(dq_ref[g], scale), cos, sin, half)
    for g in range(dk_ref.shape[0]):
        dko_ref[:, _head(g)] = _turn(dk_ref[g], cos, sin, half)


def _specs(heads: int, tile: int):
    """A grid step (sequence, tile, KV head) takes ``heads`` heads: their
    block of a ``[b, heads', s, 128]`` operand, of a flat ``[b, s, heads' *
    128]`` one; and a table's."""
    import jax.experimental.pallas as pl

    return (pl.BlockSpec((None, heads, tile, _LANES),
                         lambda i, t, j: (i, j, t, 0)),
            pl.BlockSpec((None, tile, heads * _LANES),
                         lambda i, t, j: (i, t, j)))


def _call(kernel, name, grid, tile, operands, in_specs, out_specs, out_shape,
          interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    table = pl.BlockSpec((tile, _LANES), lambda i, t, j: (t, 0))
    tables, rows = operands[:2], operands[2:]
    return pl.pallas_call(
        kernel, grid=grid, in_specs=[table, table, *in_specs],
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        # The tables read once, every row read once and written once.
        cost_estimate=pl.CostEstimate(
            flops=8 * sum(x.size for x in rows), transcendentals=0,
            bytes_accessed=sum(x.nbytes for x in tables)
            + 2 * sum(x.nbytes for x in rows)),
        name=name, interpret=interpret)(*operands)


_STATIC = ("half", "scale", "tile", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(q, k, cos, sin, *, half: int, scale: float, tile: int = TILE,
             interpret: bool = False):
    b, s, width = q.shape
    h, h_kv = width // _LANES, k.shape[2] // _LANES
    q_hsd, q_flat = _specs(h // h_kv, tile)
    k_hsd, k_flat = _specs(1, tile)
    return _call(
        functools.partial(_fwd_kernel, half=half,
                          scale=_rounded(scale, q.dtype)),
        FWD_NAME, (b, s // tile, h_kv), tile, (cos, sin, q, k),
        [q_flat, k_flat], [q_hsd, k_hsd],
        [_like((b, h, s, _LANES), q), _like((b, h_kv, s, _LANES), k)],
        interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(cos, sin, dq, dk, *, half: int, scale: float, tile: int = TILE,
              interpret: bool = False):
    """-> ``(d q, d k)``, flat."""
    b, h, s, _ = dq.shape
    h_kv = dk.shape[1]
    q_hsd, q_flat = _specs(h // h_kv, tile)
    k_hsd, k_flat = _specs(1, tile)
    return _call(
        functools.partial(_bwd_kernel, half=half,
                          scale=_rounded(scale, dq.dtype)),
        BWD_NAME, (b, s // tile, h_kv), tile, (cos, sin, dq, dk),
        [q_hsd, k_hsd], [q_flat, k_flat],
        [_like((b, s, h * _LANES), dq), _like((b, s, h_kv * _LANES), dk)],
        interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _operands(q, k, cos, sin, static):
    return tuple(_forward(q, k, cos, sin, **dict(static)))


def _operands_fwd(q, k, cos, sin, static):
    return tuple(_forward(q, k, cos, sin, **dict(static))), (cos, sin)


def _operands_bwd(static, kept, cotangents):
    return (*_backward(*kept, *cotangents, **dict(static)), None, None)


_operands.defvjp(_operands_fwd, _operands_bwd)


def operands(q, k, cos, sin, scale: float, *, half: int = _LANES // 2,
             tile: int = TILE, interpret: bool = False):
    """``(q, k)`` as the attention kernels take them, ``[b, h, s, 128]``
    turned and times ``scale`` and ``[b, h_kv, s, 128]`` turned, from the
    projections' ``q [b, s, h * 128]`` and ``k [b, s, h_kv * 128]`` (``h_kv``
    divides ``h``; ``s`` whole ``tile``s) and :func:`tables`' ``cos`` and
    ``sin``, pairs ``half`` apart.  Differentiable in ``q`` and ``k``.  The
    kernels whatever the backend (``interpret``: in interpret mode); the
    caller asks :func:`takes`."""
    return _operands(q, k, cos, sin, (("half", half), ("scale", scale),
                                      ("tile", tile),
                                      ("interpret", interpret)))
