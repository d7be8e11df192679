"""Latent attention's q and k as the attention kernels take them, each
activation read once and written once a direction.

``models/deepseek.py::LatentAttention`` (DeepSeek-V3's MLA) projects a
position to a query of ``nope + rope`` columns a head, a key of ``nope`` a
head and one rotary key of ``rope`` that all heads share; the attention
kernels (``kernels/masked_attention.py``) take ``q`` rotated and scaled and
``k = [k_nope ; k_r]`` with the rotated ``k_r`` in every head, both
``[b, h, s, nope + rope]``.  The weights' columns are the model's to order
(the interleave already lies on them), so the query comes as two flat
products, every head's ``nope`` columns side by side and every head's rotary
columns (pairs first) side by side, both dense and whole lane groups wide,
which is what the MXU multiplies at its rate forward and backward (a head's
192 columns as one block cost the products 15 to 25%: ``PERF.md`` section 6,
PR 49); ``k_nope`` is written ``[b, h, s, nope]`` by its product.  What is
left is

    q_j <- [q_nope_j ; turn(q_rope_j)] * scale      into [b, h, s, .]
    k_j <- [k_nope_j ; k_r]                         every head j, k_r turned
    turn(t) = t * [cos ; cos] + [t_2 ; t_1] * [-sin ; sin]        (halves)

and backward the same turned back (a rotation's transpose is the rotation by
the other angle: no residual but the two tables), ``dk`` cut into
``d k_nope`` and the sum of its rotary part over the heads.  Left to XLA that
is three to five fusions a direction over 100 MB tensors, whichever way it is
written (a concatenation is a fusion of its own, and a value used twice, as
both halves are, is written out between two: 31.5 ms of JoyAI-LLM-Flash's
step where these kernels and what stands around them take 9.8, ``PERF.md``
section 6, PR 49); here the forward kernel reads
the query, ``k_nope`` and ``k_r`` once and writes ``q`` and ``k`` once, the
backward kernel reads ``dq`` and ``dk`` once and writes the cotangents once.
The one rotary key, 1 MB, is turned by XLA in front of the kernel and its
cotangent turned back behind it.

**How it goes.**  A grid step is :data:`TILE` positions of as many heads as
one lane group holds rotary parts of (two of 64): their ``nope`` columns are
one block of the flat product, their rotary parts another, and their rows of
``q`` and ``k`` one block ``[heads, tile, nope + rope]``.  The halves change
places by two rotations of the lanes and a select, on whole lane groups.  The
heads are the innermost axis: the tables' and ``k_r``'s blocks stay where
they are while a tile's heads go by, and ``d k_r`` adds up in fp32 in VMEM
over them.

**Precision**: what ``_rope`` and the wrapper's scale did on the rows: the
rotation in fp32 on the bf16 projection, one rounding behind it, then the
product with the scale rounded to the rows' dtype, rounded again; backward
``dq * scale`` rounded, turned back in fp32, rounded, and the sum over the
heads rounded once.  :func:`reference` is the same function in
``jax.numpy``, differentiated by JAX: the path off the TPU, in float32 and
for shapes :func:`takes` refuses.

On the device's op line the calls are :data:`FWD_NAME` and :data:`BWD_NAME`.
Pallas is imported where a kernel is built; each direction is one jitted
function, traced once a process whatever the number of layers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

FWD_NAME = "hvd_mla_operands_fwd"
BWD_NAME = "hvd_mla_operands_bwd"
OP_LINE_NAMES = r"^hvd_mla_operands"

_LANES = 128
TILE = 1024       # positions a grid step
_VMEM_LIMIT = 64 * 2 ** 20


def takes(seq_len: int, heads: int, nope: int, rope: int,
          dtype=jnp.bfloat16) -> bool:
    """Whether the kernels take ``heads`` heads of ``nope + rope`` over
    ``seq_len`` positions in ``dtype``: one lane group of ``nope`` and half a
    one of ``rope`` (DeepSeek-V3's 128 + 64), the heads two by two;
    otherwise, and off the TPU, :func:`reference`."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and seq_len % TILE == 0
            and nope == _LANES and rope == _LANES // 2 and heads % 2 == 0)


def tables(angles):
    """The cosines and sines of ``angles [s, rope / 2]`` (``_rope``'s) as
    :func:`turn` takes them, ``[s, rope]`` in fp32 each: ``[cos ; cos]`` and
    ``[-sin ; sin]``."""
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def turn(t, cos, sin):
    """``t [..., s, rope]`` rotated as ``_rope`` rotates its halves: in fp32,
    rounded to ``t``'s dtype."""
    half = t.shape[-1] // 2
    t32 = t.astype(jnp.float32)
    swapped = jnp.concatenate([t32[..., half:], t32[..., :half]], axis=-1)
    return (t32 * cos + swapped * sin).astype(t.dtype)


def reference(q_nope, q_rope, k_nope, k_r, cos, sin, scale: float):
    """:func:`operands` in ``jax.numpy``."""
    b, h, s, nope = k_nope.shape
    rope = k_r.shape[-1]

    def by_head(flat):
        return flat.reshape(b, s, h, -1).transpose(0, 2, 1, 3)

    q = jnp.concatenate(
        [by_head(q_nope), turn(by_head(q_rope), cos, sin)], axis=-1) \
        * jnp.asarray(scale, q_nope.dtype)
    return q, jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (b, h, s, rope))], axis=-1)


def _rounded(scale: float, dtype) -> float:
    return float(np.asarray(scale, jnp.dtype(dtype)))


def _scaled(t, scale: float):
    """A product in ``t``'s dtype with the scale as that dtype has it."""
    return (t.astype(jnp.float32) * scale).astype(t.dtype)


def _turn_lanes(t, cos, sin, rope: int):
    """:func:`turn` on one lane group ``[tile, 128]`` that holds the rotary
    parts of ``128 / rope`` heads side by side."""
    from jax.experimental.pallas import tpu as pltpu

    half = rope // 2
    t32 = t.astype(jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, t32.shape, 1)
    swapped = jnp.where(lane % rope < half,
                        pltpu.roll(t32, _LANES - half, 1),   # from l + half
                        pltpu.roll(t32, half, 1))            # from l - half
    return (t32 * cos + swapped * sin).astype(t.dtype)


def _fwd_kernel(cos_ref, sin_ref, qn_ref, qr_ref, kn_ref, kr_ref, qo_ref,
                ko_ref, *, nope: int, rope: int, scale: float):
    turned = _scaled(_turn_lanes(qr_ref[...], cos_ref[...], sin_ref[...],
                                 rope), scale)
    for g in range(qo_ref.shape[0]):
        qo_ref[g, :, :nope] = _scaled(qn_ref[:, g * nope:(g + 1) * nope],
                                      scale)
        qo_ref[g, :, nope:] = turned[:, g * rope:(g + 1) * rope]
        ko_ref[g, :, :nope] = kn_ref[g]
        ko_ref[g, :, nope:] = kr_ref[...]


def _bwd_kernel(cos_ref, sin_ref, dq_ref, dk_ref, dqn_ref, dqr_ref, dkn_ref,
                dkr_ref, sum_ref, *, nope: int, rope: int, scale: float):
    import jax.experimental.pallas as pl

    step, heads = pl.program_id(2), dq_ref.shape[0]

    @pl.when(step == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    for g in range(heads):
        dqn_ref[:, g * nope:(g + 1) * nope] = _scaled(dq_ref[g, :, :nope],
                                                      scale)
        dkn_ref[g] = dk_ref[g, :, :nope]
        sum_ref[...] += dk_ref[g, :, nope:].astype(jnp.float32)
    rotary = jnp.concatenate([dq_ref[g, :, nope:] for g in range(heads)],
                             axis=1)
    dqr_ref[...] = _turn_lanes(_scaled(rotary, scale), cos_ref[...],
                               -sin_ref[...], rope)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        dkr_ref[...] = sum_ref[...].astype(dkr_ref.dtype)


def _specs(heads: int, rope: int):
    """A grid step (sequence, tile, group of heads): the group's block of a
    ``[b, h, s, width]`` operand, of a flat ``[b, s, h * width]`` one, the
    one rotary key's, a table's."""
    import jax.experimental.pallas as pl

    group = _LANES // rope

    def by_head(width):
        return pl.BlockSpec((None, group, TILE, width),
                            lambda i, t, j: (i, j, t, 0))

    def flat(width):
        return pl.BlockSpec((None, TILE, group * width),
                            lambda i, t, j: (i, t, j))

    shared = pl.BlockSpec((None, None, TILE, rope),
                          lambda i, t, j: (i, 0, t, 0))
    table = pl.BlockSpec((TILE, _LANES), lambda i, t, j: (t, 0))
    return heads // group, by_head, flat, shared, table


def _params():
    from jax.experimental.pallas import tpu as pltpu

    # The rotary key's cotangent adds up along a tile's heads.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _like(shape, x):
    return jax.ShapeDtypeStruct(shape, x.dtype, vma=jax.typeof(x).vma)


def _lanes(table):
    """A table ``[s, rope]`` side by side as often as a lane group holds."""
    return jnp.tile(table, (1, _LANES // table.shape[-1]))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _forward(q_nope, q_rope, k_nope, k_r, cos, sin, *, scale: float,
             interpret: bool):
    import jax.experimental.pallas as pl

    b, h, s, nope = k_nope.shape
    rope = k_r.shape[3]
    steps, by_head, flat, shared, table = _specs(h, rope)
    wide = _like((b, h, s, nope + rope), k_nope)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nope=nope, rope=rope,
                          scale=_rounded(scale, k_nope.dtype)),
        grid=(b, s // TILE, steps),
        in_specs=[table, table, flat(nope), flat(rope), by_head(nope),
                  shared],
        out_specs=[by_head(nope + rope)] * 2, out_shape=[wide, wide],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=b * h * s * (nope + 8 * rope), transcendentals=0,
            bytes_accessed=k_nope.dtype.itemsize * b * h * s
            * (4 * nope + 3 * rope)),
        name=FWD_NAME, interpret=interpret,
    )(_lanes(cos), _lanes(sin), q_nope, q_rope, k_nope, k_r)


@functools.partial(jax.jit, static_argnames=("rope", "scale", "interpret"))
def _backward(cos, sin, dq, dk, *, rope: int, scale: float, interpret: bool):
    """-> ``(d q_nope, d q_rope, d k_nope, d k_r)``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = dq.shape
    nope = d - rope
    steps, by_head, flat, shared, table = _specs(h, rope)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, nope=nope, rope=rope,
                          scale=_rounded(scale, dq.dtype)),
        grid=(b, s // TILE, steps),
        in_specs=[table, table, by_head(d), by_head(d)],
        out_specs=[flat(nope), flat(rope), by_head(nope), shared],
        out_shape=[_like((b, s, h * nope), dq), _like((b, s, h * rope), dq),
                   _like((b, h, s, nope), dq), _like((b, 1, s, rope), dq)],
        scratch_shapes=[pltpu.VMEM((TILE, rope), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=b * h * s * (nope + 9 * rope), transcendentals=0,
            bytes_accessed=dq.dtype.itemsize * b * h * s
            * (4 * nope + 3 * rope)),
        name=BWD_NAME, interpret=interpret,
    )(_lanes(cos), _lanes(sin), dq, dk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _operands(q_nope, q_rope, k_nope, k_r, cos, sin, scale, interpret):
    return tuple(_forward(q_nope, q_rope, k_nope, k_r, cos, sin, scale=scale,
                          interpret=interpret))


def _operands_fwd(q_nope, q_rope, k_nope, k_r, cos, sin, scale, interpret):
    return tuple(_forward(q_nope, q_rope, k_nope, k_r, cos, sin, scale=scale,
                          interpret=interpret)), (cos, sin)


def _operands_bwd(scale, interpret, kept, cotangents):
    cos, sin = kept
    return (*_backward(cos, sin, *cotangents, rope=cos.shape[-1],
                       scale=scale, interpret=interpret), None, None)


_operands.defvjp(_operands_fwd, _operands_bwd)


def operands(q_nope, q_rope, k_nope, k_r, cos, sin, scale: float, *,
             interpret: bool = False):
    """``(q, k)`` as the attention kernels take them, ``[b, h, s, nope +
    rope]``, from the query's two flat products ``q_nope [b, s, h * nope]``
    and ``q_rope [b, s, h * rope]`` (a head's rotary columns as halves: pairs
    first), ``k_nope [b, h, s, nope]`` and the one rotary key ``k_r [b, 1, s,
    rope]``, already turned, with :func:`tables`' ``cos`` and ``sin``: the
    query's rotary columns rotated behind its others and all of it times
    ``scale``, ``k_r`` behind every head's ``k_nope``.  Differentiable in
    the first four.  On a TPU (or with ``interpret``) the kernels, for what
    :func:`takes` takes; else :func:`reference`."""
    _, h, s, nope = k_nope.shape
    if (interpret or jax.default_backend() == "tpu") \
            and takes(s, h, nope, k_r.shape[3], k_nope.dtype):
        return _operands(q_nope, q_rope, k_nope, k_r, cos, sin, scale,
                         interpret)
    return reference(q_nope, q_rope, k_nope, k_r, cos, sin, scale)
