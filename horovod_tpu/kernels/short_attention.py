"""Softmax attention at short sequences with a head's whole score tile in
fast memory, forward and backward.

``models/transformer.py::_scaled_dot_attention`` computes unmasked (and plain
causal) attention through XLA's einsum and softmax, which writes the scores
of every head to HBM in fp32 and reads them back: ``[8, 16, 512, 512]`` is
134 MB a layer of BERT-large and XLA's count is twelve passes over it,
43 of the step's 100 ms on a v5e at 8 and 23 TFLOP/s (``PERF.md`` §5, PR 36).
At such lengths one head's ``[s, s]`` scores are 1 MB (16 MB at s 2048) and
fit the chip's 128 MiB of VMEM several times, so nothing has to be tiled: a grid step
takes one sequence's group of heads, computes the scores on the MXU, the
softmax in fp32 on the tile, the second product, and writes the output and
the rows' log-sum-exp.  The backward pass is **one** kernel a grid step: it
computes the scores again from q, k and the log-sum-exp and writes dq, dk
and dv together; with one tile a sequence there is nothing to add up across
grid steps.

**Layout.**  The heads are read straight out of the projections' ``[b, s,
h * d]`` layout in blocks of :data:`_LANES` lanes (two heads of 64, one of
128) and the output is written the same way, so no ``[b, h, s, d]`` copy
exists on either side and a head width of 64 is not padded to 128 in HBM.
Where a block holds two heads the kernel never cuts it along the lanes: a
head's scores are the product over all 128 lanes with the other head's
lanes of q set to zero (a product 64 wide takes the MXU the same time), and
a product that yields ``[s, 128]`` is right in that head's lanes, which are
then the ones kept.

**Orientation.**  The kernel holds the scores as ``[keys, queries]``: the
softmax's max and sum then run down the sublanes, the log-sum-exp is a row
``[1, s]`` as it lies in HBM (``[b, groups, heads a group, s]`` fp32: 256 KB
a layer of BERT-large, against the 67 MB of bf16 probabilities the einsum
keeps for its backward), and of the seven products only the two whose
result is ``[width, queries]`` (the output, dq) need a transpose, of a
``[s, 128]`` tile and never of the score tile.

**Precision** is the einsum path's: bf16 operands, scores accumulated in
fp32, max, exp and sum in fp32, probabilities cast to bf16 for the second
product; the scale ``d ** -0.5`` is applied to the fp32 scores inside the
kernel, not to q.  The output is divided by the row sum after the product
(in fp32) rather than the probabilities before it.

On the device's op line the calls are :data:`FWD_NAME` and :data:`BWD_NAME`
(``chip_bench/metrics/short_attention_ms_step.json``).  Pallas is imported
where a kernel is built, not with this module.  Each direction is one
jitted function: a model holds the call once a layer, and tracing and
lowering a pallas kernel is host work that a program pays at every start,
before the compile cache is asked (``PERF.md`` §6, PR 35).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# The calls' names on the device's op line, and what matches both.
FWD_NAME = "hvd_short_attention_fwd"
BWD_NAME = "hvd_short_attention_bwd"
OP_LINE_NAMES = r"^hvd_short_attention"

_LANES = 128
# The lengths measured faster than the einsum (and than the flash kernel) on
# a v5e, forward + backward a layer (``benchmarks/short_attention_sweep.py``;
# ``PERF.md`` §6, PR 37): 0.45 against 1.18 ms at (b 8, h 16, s 512, d 64),
# 1.45 against 5.81 at s 1024, 1.51 against 8.20 at (2, 16, 2048, 128).  At
# s 256 the einsum's 0.17 ms beats 0.19, at s 128 0.07 beats 0.11; past 2048
# the backward's four score tiles no longer fit the VMEM it may use.
_MIN_SEQ, _MAX_SEQ = 512, 2048
_VMEM_LIMIT = 96 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def fits(seq_len: int, head_dim: int, heads: int,
         dtype=jnp.bfloat16) -> bool:
    """Whether the kernel can be built for the shape at all: whole lane
    groups of heads (an odd number of heads of 64 has half a group left
    over) and whole tiles of positions."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and head_dim in (64, 128)
            and (heads * head_dim) % _LANES == 0 and seq_len > 0
            and seq_len % _LANES == 0)


def takes(seq_len: int, head_dim: int, heads: int,
          dtype=jnp.bfloat16) -> bool:
    """Whether ``_scaled_dot_attention`` hands ``[b, seq_len, heads,
    head_dim]`` to the kernel on a TPU: a shape it :func:`fits`, at a length
    it measured faster at than the einsum; everything else, and everything
    off the TPU, stays on the einsum (or the flash kernel)."""
    return fits(seq_len, head_dim, heads, dtype) \
        and _MIN_SEQ <= seq_len <= _MAX_SEQ


def _head_mask(d: int, i: int, shape, axis: int):
    """Where head ``i``'s lanes lie in a block of ``shape`` whose ``axis`` is
    the block's 128 lanes (axis 0 where the block lies transposed): bool of
    ``shape``, or None where the block is one head."""
    if d == _LANES:
        return None
    at = lax.broadcasted_iota(jnp.int32, shape, axis)
    return (at >= i * d) & (at < (i + 1) * d)


def _only(x, mask):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _allowed(s: int, causal: bool):
    """``[keys, queries]``: a query sees itself and the keys before it."""
    if not causal:
        return None
    return lax.broadcasted_iota(jnp.int32, (s, s), 0) \
        <= lax.broadcasted_iota(jnp.int32, (s, s), 1)


def _scores(q, k, lanes, allowed):
    """One head's ``[keys, queries]`` scores in fp32, unscaled: the product
    over the whole block with the other head's lanes of q at zero."""
    scores = lax.dot_general(k, _only(q, lanes), _NT,
                             preferred_element_type=jnp.float32)
    return scores if allowed is None else jnp.where(allowed, scores, -jnp.inf)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, d: int,
                causal: bool):
    """One sequence's group of heads: q, k, v ``[s, 128]`` in, the output
    ``[s, 128]`` and the log-sum-exp ``[heads, s]`` out."""
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    s = q.shape[0]
    scale = d ** -0.5
    allowed = _allowed(s, causal)
    v_t = v.T                                           # [128, keys]
    out_t = None
    for i in range(_LANES // d):
        scores = _scores(q, k, _head_mask(d, i, q.shape, 1), allowed)
        top = jnp.max(scores, axis=0, keepdims=True)    # [1, queries]
        p = jnp.exp((scores - top) * scale)
        total = jnp.sum(p, axis=0, keepdims=True)
        head_t = jnp.dot(v_t, p.astype(v.dtype),
                         preferred_element_type=jnp.float32) / total
        lse_ref[i:i + 1, :] = top * scale + jnp.log(total)
        out_t = head_t if i == 0 else jnp.where(
            _head_mask(d, i, head_t.shape, 0), head_t, out_t)
    o_ref[...] = out_t.T.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dq_ref, dk_ref,
                dv_ref, *, d: int, causal: bool):
    """The same grid step's cotangents: the scores again from q, k and the
    log-sum-exp, then dq, dk and dv ``[s, 128]``, all of them here."""
    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    s = q.shape[0]
    scale = d ** -0.5
    allowed = _allowed(s, causal)
    k_t = k.T                                           # [128, keys]
    # do * o, whose sum over a head's lanes each query's row needs, laid so
    # that the sum comes out as a row over queries.
    weighted_t = (do.astype(jnp.float32) * o_ref[...].astype(jnp.float32)).T
    dq_t = dk = dv = None
    for i in range(_LANES // d):
        lanes = _head_mask(d, i, q.shape, 1)
        rows = _head_mask(d, i, k_t.shape, 0)
        p = jnp.exp(_scores(q, k, lanes, allowed) * scale
                    - lse_ref[i:i + 1, :])
        dp = lax.dot_general(v, _only(do, lanes), _NT,
                             preferred_element_type=jnp.float32)
        delta = jnp.sum(_only(weighted_t, rows), axis=0, keepdims=True)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        head_dv = jnp.dot(p.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        head_dk = jnp.dot(ds, q, preferred_element_type=jnp.float32)
        head_dq_t = jnp.dot(k_t, ds, preferred_element_type=jnp.float32)
        if i == 0:
            dv, dk, dq_t = head_dv, head_dk, head_dq_t
        else:
            dv = jnp.where(lanes, head_dv, dv)
            dk = jnp.where(lanes, head_dk, dk)
            dq_t = jnp.where(rows, head_dq_t, dq_t)
    dq_ref[...] = dq_t.T.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _specs(b: int, s: int, width: int, d: int):
    """The grid, a ``[b, s, width]`` operand's block and the log-sum-exp's
    (``[b, groups, heads a group, s]``)."""
    import jax.experimental.pallas as pl

    groups, per = width // _LANES, _LANES // d
    block = pl.BlockSpec((None, s, _LANES), lambda i, j: (i, 0, j))
    lse = pl.BlockSpec((None, None, per, s), lambda i, j: (i, j, 0, 0))
    return (b, groups), block, lse, (b, groups, per, s)


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _cost(b: int, s: int, width: int, d: int, products: int, tensors: int):
    import jax.experimental.pallas as pl

    heads = width // d
    return pl.CostEstimate(
        flops=products * 2 * b * heads * s * s * d,
        transcendentals=b * heads * s * s,
        bytes_accessed=tensors * b * s * width * 2 + b * heads * s * 4)


@functools.partial(jax.jit, static_argnames=("d", "causal", "interpret"))
def _forward(q, k, v, *, d: int, causal: bool, interpret: bool):
    """``[b, s, h * d]`` in; the output in the same layout and the
    log-sum-exp.  Jitted: traced once a process and lowered once a program,
    whatever the number of layers."""
    import jax.experimental.pallas as pl

    b, s, width = q.shape
    grid, block, lse_block, lse_shape = _specs(b, s, width, d)
    vma = jax.typeof(q).vma
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, causal=causal),
        grid=grid, in_specs=[block] * 3, out_specs=[block, lse_block],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
                   jax.ShapeDtypeStruct(lse_shape, jnp.float32, vma=vma)],
        compiler_params=_params(),
        cost_estimate=_cost(b, s, width, d, products=2, tensors=4),
        name=FWD_NAME, interpret=interpret,
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=("d", "causal", "interpret"))
def _backward(q, k, v, o, lse, do, *, d: int, causal: bool, interpret: bool):
    import jax.experimental.pallas as pl

    b, s, width = q.shape
    grid, block, lse_block, _ = _specs(b, s, width, d)
    like = jax.ShapeDtypeStruct(q.shape, q.dtype, vma=jax.typeof(q).vma)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, causal=causal),
        grid=grid, in_specs=[block] * 4 + [lse_block, block],
        out_specs=[block] * 3, out_shape=[like] * 3,
        compiler_params=_params(),
        cost_estimate=_cost(b, s, width, d, products=5, tensors=8),
        name=BWD_NAME, interpret=interpret,
    )(q, k, v, o, lse, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, d, causal, interpret):
    return _forward(q, k, v, d=d, causal=causal, interpret=interpret)[0]


def _attention_fwd(q, k, v, d, causal, interpret):
    o, lse = _forward(q, k, v, d=d, causal=causal, interpret=interpret)
    return o, (q, k, v, o, lse)


def _attention_bwd(d, causal, interpret, kept, do):
    return tuple(_backward(*kept, do, d=d, causal=causal,
                           interpret=interpret))


_attention.defvjp(_attention_fwd, _attention_bwd)


def attention(q, k, v, causal: bool = False, *, interpret: bool = False):
    """``softmax(q k^T / sqrt(d)) v`` over ``[b, s, h, d]`` bf16, every
    query head with a KV head of its own, unmasked or causal; differentiable
    in q, k and v.  Shapes :func:`fits` refuses are an error here."""
    b, s, h, d = q.shape
    if not (q.shape == k.shape == v.shape and fits(s, d, h, q.dtype)
            and k.dtype == v.dtype == q.dtype):
        raise ValueError(f"no kernel for q {q.shape} {q.dtype}, "
                         f"k {k.shape}, v {v.shape}")
    o = _attention(*(t.reshape(b, s, h * d) for t in (q, k, v)), d,
                   bool(causal), interpret)
    return o.reshape(b, s, h, d)
