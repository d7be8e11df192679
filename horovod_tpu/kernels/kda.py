"""Kimi Delta Attention's rule (Kimi Linear, arXiv:2510.26692) in its chunked
form, forward and backward in one kernel each: a delta rule whose decay is a
vector a key channel and not a scalar a head.

A KDA mixer (``models/kda.py``; Ling-3.0-flash's linear-attention layers,
five of every six) carries, a head, a state ``S [K, V]`` along the sequence:

    S   <- diag(exp(g_t)) S                  g_t [K] <= 0, a key channel
    d_t  = beta_t (v_t - S^T k_t)            what the state lacks for k_t
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

``kernels/gated_delta.py``'s rule with ``g_t`` a vector: the decay between
two positions of a chunk, ``exp(Gamma_t - Gamma_s)`` with ``Gamma`` the
running sum of ``g`` inside the chunk, is then no ``[C, C]`` mask on ``k k^T``
but lies inside the sum over the channels.  In chunks of ``C`` positions:

    A  = strict_tril(beta_t sum_c k_t[c] k_s[c] exp(Gamma_t[c] - Gamma_s[c]))
    T  = (I + A)^-1                          unit lower triangular
    U  = T (beta v)          W = T (beta k * exp(Gamma))
    V' = U - W S                             the chunk's writes, all at once
    O  = (q * exp(Gamma)) S
         + tril(sum_c q_t[c] k_s[c] exp(Gamma_t[c] - Gamma_s[c])) V'
    S <- diag(exp(Gamma_C)) S + (k * exp(Gamma_C - Gamma))^T V'

**The pairwise sums and their exponents.**  ``exp(Gamma_t - Gamma_s)`` must
be split between the two operands of a product, and ``exp(-Gamma)`` over a
whole chunk is out of fp32's reach (the release bounds ``g`` below by -5, so
a chunk's ``Gamma`` goes down to -320).  The rows are therefore taken in
sub-blocks of :data:`SUB` = 16 positions, each against its own reference
``Gamma_ref``, the ``Gamma`` of the sub-block's first position:

    (k_t * exp(Gamma_t - Gamma_ref)) . (k_s * exp(Gamma_ref - Gamma_s))

for ``t`` in the sub-block and every ``s`` up to its end.  The first exponent
is at most 0; the second is at most 0 for an ``s`` in front of the sub-block
and at most ``15 x 5 = 75`` inside it, which fp32 and bf16 hold (the bound of
-5 a position is what makes that so: :data:`LOWER_BOUND`, which a caller's
``g`` must keep); columns behind the sub-block are zeroed before the product.
A chunk of 64 is four such products ``[16, K] x [K, 64]`` where the scalar
rule has one ``[64, K] x [K, 64]``, the same multiply-adds.

**The kernels.**  A grid step is one chunk of up to eight heads of one
sequence, the chunks in order (backward: in reverse), the heads a leading axis
of every product (independent chains abreast: ``kernels/gated_delta.py`` says
what a chain alone costs), their states in fp32 in VMEM across the chunks,
**transposed** (``S^T [V, K]``: the key channels on the lanes, where ``g``
has them, so that ``diag(exp(Gamma_C)) S`` is a row spread over the sublanes).
``Gamma`` is summed inside the kernel, a lower-triangular product over the
three bf16 pieces of ``g`` (exact to fp32's last bits), so the kernels take
``g`` itself and the backward returns its cotangent.  The forward kernel
writes ``o`` and, for the backward pass, the state every chunk *started* from
(``[chunks, heads, V, K]`` fp32: 268 MB a layer at 8192 positions and 32
heads).  The backward kernel starts from those, carries the state's cotangent
from the last chunk to the first and gives the cotangents of ``q``, ``k``,
``v``, ``g`` and ``beta``: the same chunk's algebra taken backward by
``jax.vjp`` inside the kernel, the inverse by its closed form
(``gated_delta.unit_lower_inverse``, imported with the products' helpers;
that module's text stays as it is).

**Precision**: ``q``, ``k``, ``v`` and ``o`` in bf16; ``g``, ``beta``,
``Gamma``, ``A``, ``T`` and the states in fp32; the products on the MXU in
bf16 with fp32 sums, the inverse's in three bf16 passes.  :func:`chunked` is
the same chunked form in ``jax.numpy`` (fp32 throughout, differentiated by
autodiff from the chunk-boundary states), the path off the TPU, in float32
and for what :func:`takes` refuses.

On the device's op line the calls are :data:`FWD_NAME` and :data:`BWD_NAME`
(``chip_bench/metrics/kda_ms_step.json``).  Pallas is imported where a kernel
is built, not with this module, and each direction is one jitted function
(``kernels/short_conv.py`` says why).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .gated_delta import (
    _BLOCK as SUB,
    _HEADS_A_STEP,
    _KERNEL_INVERSE_PASSES,
    _LANES,
    CHUNK,
    _by_head,
    _lower,
    _mm,
    _params,
    _to_heads,
    unit_lower_inverse,
)

# The calls' names on the device's op line, and what matches both.
FWD_NAME = "hvd_kda_fwd"
BWD_NAME = "hvd_kda_bwd"
OP_LINE_NAMES = r"^hvd_kda_"

# The least ``g`` a position and channel may carry (the release's
# ``kda_lower_bound``): inside a sub-block the exponents reach ``-(SUB - 1) *
# LOWER_BOUND``, and :data:`_CAP` is where they are cut off, finite in fp32.
LOWER_BOUND = -5.0
_CAP = -SUB * LOWER_BOUND


def heads_a_step(heads: int) -> int:
    """The heads one grid step takes: the most, up to eight, that divide
    them."""
    return max(n for n in range(1, _HEADS_A_STEP + 1) if heads % n == 0)


def takes(seq_len: int, heads: int, key_dim: int, value_dim: int,
          dtype=jnp.bfloat16) -> bool:
    """Whether the kernels take ``q``, ``k``, ``g`` ``[b, seq_len, heads,
    key_dim]`` and ``v [b, seq_len, heads, value_dim]`` with ``q``, ``k``,
    ``v`` of ``dtype``; otherwise, and off the TPU, :func:`chunked`."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and heads > 0
            and key_dim == value_dim == _LANES and seq_len > 0
            and seq_len % CHUNK == 0)


def _running_sum(g, dot):
    """``Gamma``: the sum of ``g [B, C, K]`` from a chunk's first position to
    each.  In the kernels (``dot`` given) a lower-triangular product of ones
    over ``g``'s three bf16 pieces, each exact in bf16 and summed in fp32."""
    if dot is None:
        return jnp.cumsum(g, axis=-2)
    c = g.shape[-2]
    lower, _ = _lower(c, False)
    ones = jnp.broadcast_to(lower.astype(dot), g.shape[:-2] + (c, c))
    total, left = None, g
    for _ in range(3):
        piece = left.astype(dot)
        left = left - piece.astype(jnp.float32)
        part = _mm(ones, piece)
        total = part if total is None else total + part
    return total


def _chunk(q, k, v, g, beta, state, dot=None):
    """One chunk of ``B`` heads (of any sequences: the leading axis): ``q``,
    ``k``, ``g [B, C, K]``, ``v [B, C, V]``, ``beta [B, C, 1]`` and the
    transposed state the chunk starts from ``[B, V, K]``, all fp32 -> ``(o
    [B, C, V], the transposed state it ends with)``.  ``dot``: the dtype the
    MXU's operands are rounded to (None: as they are, and the inverse at the
    highest precision)."""
    heads, c, kd = q.shape
    n = c // SUB
    cast = (lambda t: t) if dot is None else (lambda t: t.astype(dot))
    lower, _ = _lower(c, False)
    strict, _ = _lower(c, True)
    gamma = _running_sum(g, dot)
    # A sub-block's rows against its own reference: [B, n, SUB, K].
    by_sub = gamma.reshape(heads, n, SUB, kd)
    reference = by_sub[:, :, :1, :]
    falls = jnp.exp(by_sub - reference)                     # exponents <= 0
    # Every column up to the sub-block's end against the same reference:
    # [B, n, C, K], zero behind the sub-block.
    block = lax.broadcasted_iota(jnp.int32, (1, n, c, 1), 1)
    column = lax.broadcasted_iota(jnp.int32, (1, n, c, 1), 2)
    rises = jnp.where(
        column < (block + 1) * SUB,
        jnp.exp(jnp.minimum(reference - gamma[:, None], _CAP)), 0.0)
    columns = cast((k[:, None] * rises).reshape(heads * n, c, kd))

    def pairwise(rows):
        """``sum_c rows_t[c] k_s[c] exp(Gamma_t[c] - Gamma_s[c])`` for ``s``
        up to the end of ``t``'s sub-block, ``[B, C, C]``."""
        rows = cast((rows.reshape(heads, n, SUB, kd) * falls)
                    .reshape(heads * n, SUB, kd))
        return _mm(rows, columns, tb=True).reshape(heads, c, c)

    a = jnp.where(strict, beta * pairwise(k), 0.0)
    t = cast(unit_lower_inverse(
        a, 6 if dot is None else _KERNEL_INVERSE_PASSES))
    grown = jnp.exp(gamma)
    u = _mm(t, cast(beta * v))
    w = _mm(t, cast(beta * k * grown))
    state_in = cast(state)
    writes = cast(u - _mm(cast(w), state_in, tb=True))
    o = _mm(cast(q * grown), state_in, tb=True) \
        + _mm(cast(jnp.where(lower, pairwise(q), 0.0)), writes)
    last = gamma[:, c - 1:c, :]
    new = jnp.exp(last) * state \
        + _mm(writes, cast(k * jnp.exp(last - gamma)), ta=True)
    return o, new


def chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """:func:`kda` in ``jax.numpy``: the same chunks, the inverse a chunk and
    the state carried between them, in fp32; any length (the last chunk is
    filled with steps of ``g = 0`` and ``beta = 0``, which neither decay nor
    write), any widths; ``chunk`` whole sub-blocks of :data:`SUB`."""
    if chunk % SUB:
        raise ValueError(f"a chunk of {chunk}: whole sub-blocks of {SUB}")
    batch, s, heads, dk = q.shape
    dv = v.shape[3]
    pad = -s % chunk
    nc = (s + pad) // chunk

    def by_chunk(t):
        t = t.astype(jnp.float32)
        if t.ndim == 3:
            t = t[..., None]
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return t.reshape(batch, nc, chunk, heads, -1) \
            .transpose(1, 0, 3, 2, 4).reshape(nc, batch * heads, chunk, -1)

    def carry(state, chunk_in):
        o, state = _chunk(*chunk_in, state)
        return state, o

    _, o = lax.scan(carry, jnp.zeros((batch * heads, dv, dk), jnp.float32),
                    tuple(by_chunk(t) for t in (q, k, v, g, beta)))
    o = o.reshape(nc, batch, heads, chunk, dv).transpose(1, 0, 3, 2, 4) \
        .reshape(batch, -1, heads, dv)
    return o[:, :s].astype(v.dtype)


# -- the kernels --------------------------------------------------------------


def _heads_column(x):
    """``[C, heads]`` -> ``[heads, C, 1]``: each head's column as one masked
    sum over the lanes (``gated_delta._columns_under``'s way)."""
    c, heads = x.shape
    spread = jnp.broadcast_to(x, (heads, c, heads))
    head = lax.broadcasted_iota(jnp.int32, spread.shape, 0)
    lane = lax.broadcasted_iota(jnp.int32, spread.shape, 2)
    return jnp.sum(jnp.where(lane == head, spread, 0.0), axis=2,
                   keepdims=True)


def _heads_beside(x):
    """:func:`_heads_column` undone: ``[heads, C, 1]`` -> ``[C, heads]``."""
    heads, c, _ = x.shape
    lane = lax.broadcasted_iota(jnp.int32, (c, heads), 1)
    return sum(jnp.where(lane == h, x[h], 0.0) for h in range(heads))


def _operands(q_ref, k_ref, v_ref, g_ref, beta_ref):
    """:func:`_chunk`'s operands but the states, a grid step's heads
    leading."""
    return (_by_head(q_ref), _by_head(k_ref), _by_head(v_ref),
            _by_head(g_ref), _heads_column(beta_ref[...]))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, before_ref,
                state_ref):
    """One chunk of one step's heads: ``q``, ``k``, ``v``, ``g [C, heads *
    128]``, ``beta [C, heads]``; ``o`` out, and the transposed states the
    chunk started from, ``[heads, 128, 128]``."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    before_ref[...] = state_ref[...]
    o, state = _chunk(*_operands(q_ref, k_ref, v_ref, g_ref, beta_ref),
                      state_ref[...], dot=q_ref.dtype)
    state_ref[...] = state
    _to_heads(o_ref, o)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, before_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref):
    """The same chunk's cotangents, the chunks taken last to first:
    ``dstate`` holds the cotangent of the state the chunk *ends* with and
    leaves that of the state it started from (``before_ref``)."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    _, back = jax.vjp(
        functools.partial(_chunk, dot=q_ref.dtype),
        *_operands(q_ref, k_ref, v_ref, g_ref, beta_ref), before_ref[...])
    dq, dk, dv, dg, dbeta, dstate = back((_by_head(do_ref), dstate_ref[...]))
    dstate_ref[...] = dstate
    dbeta_ref[...] = _heads_beside(dbeta)
    for d_ref, d in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv), (dg_ref, dg)):
        _to_heads(d_ref, d)


def _specs(s: int, heads: int, reverse: bool):
    """The blocks of one grid step ``(sequence, step's heads, chunk)``: the
    heads' channels', the per-head columns', the states'."""
    import jax.experimental.pallas as pl

    nc = s // CHUNK

    def at(j):
        return nc - 1 - j if reverse else j

    wide = pl.BlockSpec((None, CHUNK, heads * _LANES),
                        lambda i, g, j: (i, at(j), g))
    columns = pl.BlockSpec((None, None, CHUNK, heads),
                           lambda i, g, j: (i, g, at(j), 0))
    states = pl.BlockSpec((None, None, None, heads, _LANES, _LANES),
                          lambda i, g, j: (i, g, at(j), 0, 0, 0))
    return nc, wide, columns, states


def chunk_products(c: int, key_dim: int, value_dim: int) -> int:
    """Multiply-adds of one chunk of one head forward, as the chunked form
    needs them (the inverse at its ten ``[C, C]`` products, ``Gamma``'s sum
    not counted)."""
    return (2 * c * c * key_dim + 10 * c ** 3       # A, q k^T, the inverse
            + c * c * (value_dim + key_dim)         # U, W
            + 2 * c * key_dim * value_dim           # W S, (q exp) S
            + c * c * value_dim                     # tril(q k^T) V'
            + c * key_dim * value_dim)              # the state's writes


@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward(q, k, v, g, beta, *, interpret: bool):
    """``q``, ``k``, ``v [batch, s, heads * 128]``, ``g`` alike in fp32,
    ``beta [batch, steps, s, heads a step]`` fp32 -> ``(o, the transposed
    states the chunks started from)``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, s, _ = v.shape
    steps, heads = beta.shape[1], beta.shape[3]
    nc, wide, columns, states = _specs(s, heads, False)
    vma = jax.typeof(v).vma
    calls = batch * steps * nc * heads
    return pl.pallas_call(
        _fwd_kernel,
        grid=(batch, steps, nc),
        in_specs=[wide, wide, wide, wide, columns],
        out_specs=[wide, states],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
            jax.ShapeDtypeStruct((batch, steps, nc, heads, _LANES, _LANES),
                                 jnp.float32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((heads, _LANES, _LANES), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * calls * chunk_products(CHUNK, _LANES, _LANES),
            transcendentals=6 * calls * CHUNK * _LANES,
            bytes_accessed=2 * 4 * v.size + 4 * g.size
            + 4 * calls * _LANES * _LANES),
        name=FWD_NAME, interpret=interpret,
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(q, k, v, g, beta, before, do, *, interpret: bool):
    """-> the cotangents of ``q``, ``k``, ``v``, ``g`` and ``beta``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, s, _ = v.shape
    steps, heads = beta.shape[1], beta.shape[3]
    nc, wide, columns, states = _specs(s, heads, True)
    vma = jax.typeof(v).vma

    def like(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype, vma=vma)

    calls = batch * steps * nc * heads
    return pl.pallas_call(
        _bwd_kernel,
        grid=(batch, steps, nc),
        in_specs=[wide, wide, wide, wide, columns, states, wide],
        out_specs=[wide, wide, wide, wide, columns],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[pltpu.VMEM((heads, _LANES, _LANES), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=6 * calls * chunk_products(CHUNK, _LANES, _LANES),
            transcendentals=6 * calls * CHUNK * _LANES,
            bytes_accessed=2 * 7 * v.size + 2 * 4 * g.size
            + 4 * calls * _LANES * _LANES),
        name=BWD_NAME, interpret=interpret,
    )(q, k, v, g, beta, before, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    return _forward(q, k, v, g, beta, interpret=interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    o, before = _forward(q, k, v, g, beta, interpret=interpret)
    return o, (q, k, v, g, beta, before)


def _rule_bwd(interpret, kept, do):
    return _backward(*kept, do, interpret=interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _by_step(beta):
    """``beta [batch, s, heads]`` as the kernels take it: fp32, ``[batch,
    steps, s, heads a step]``."""
    batch, s, heads = beta.shape
    a_step = heads_a_step(heads)
    return beta.astype(jnp.float32) \
        .reshape(batch, s, heads // a_step, a_step) \
        .transpose(0, 2, 1, 3)


def kda(q, k, v, g, beta, *, interpret: bool = False):
    """``o_t = S_t^T q_t`` with ``S`` Kimi Delta Attention's state a head,
    zero before each sequence: ``q``, ``k [batch, s, heads, K]`` (``k`` of
    unit length, ``q`` scaled: the caller's), ``v [batch, s, heads, V]``, ``g
    [batch, s, heads, K]`` (the decay's logarithm a key channel, in
    [:data:`LOWER_BOUND`, 0]) and ``beta [batch, s, heads]`` (the write
    strength); ``o`` as ``v``.  Differentiable in all five.  On a TPU (or
    with ``interpret``) the kernels, for what :func:`takes` takes; else
    :func:`chunked`."""
    batch, s, heads, dk = q.shape
    dv = v.shape[3]
    if k.shape != q.shape or g.shape != q.shape \
            or v.shape[:3] != (batch, s, heads) \
            or beta.shape != (batch, s, heads):
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}, "
                         f"g {g.shape}, beta {beta.shape}")
    same = q.dtype == k.dtype == v.dtype
    if not ((interpret or jax.default_backend() == "tpu") and same
            and takes(s, heads, dk, dv, v.dtype)):
        return chunked(q, k, v, g, beta)
    beta = _by_step(beta)
    o = _rule(q.reshape(batch, s, heads * dk), k.reshape(batch, s, heads * dk),
              v.reshape(batch, s, heads * dv),
              g.astype(jnp.float32).reshape(batch, s, heads * dk), beta,
              interpret)
    return o.reshape(v.shape)


def kda_flat(q, k, v, g, beta, *, interpret: bool = False):
    """:func:`kda` on the layout its kernels take and give: ``q``, ``k``,
    ``v``, ``g [batch, s, heads * 128]``, a head a lane group, and ``beta
    [batch, s, heads]``; ``o`` as ``v``.  No head-major array is made where
    the kernels run (``kernels/head_rows.py`` writes ``q``, ``k`` and ``g``
    so and reads ``o`` so); elsewhere :func:`kda` on the heads as an axis."""
    batch, s, heads = beta.shape
    if not (q.shape == k.shape == v.shape == g.shape
            == (batch, s, heads * _LANES)):
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}, "
                         f"g {g.shape}, beta {beta.shape}")
    if not ((interpret or jax.default_backend() == "tpu")
            and q.dtype == k.dtype == v.dtype
            and takes(s, heads, _LANES, _LANES, v.dtype)):
        by_head = (batch, s, heads, _LANES)
        return kda(*(t.reshape(by_head) for t in (q, k, v, g)), beta) \
            .reshape(v.shape)
    return _rule(q, k, v, g.astype(jnp.float32), _by_step(beta), interpret)
