"""The gated delta rule of a Gated DeltaNet layer in its chunked form,
forward and backward in one kernel each, a pair of value heads a block.

A Gated DeltaNet mixer (``models/gated_delta.py``, arXiv:2412.06464;
Qwen3-Next's linear-attention layers) carries, a value head, a state
``S [K, V]`` along the sequence:

    S   <- exp(g_t) S                        g_t <= 0, a scalar a head
    d_t  = beta_t (v_t - S^T k_t)            what the state lacks for k_t
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

with ``q_t``, ``k_t [K]`` those of the head's key head (a key head serves
``value heads / key heads`` value heads), ``v_t [V]`` and ``beta_t`` in (0, 1).
The write subtracts what the state already holds for the key, so a chunk is
no plain sum as ``kernels/ssd_scan.py``'s is.  In chunks of ``C`` positions,
with ``gamma_t`` the sum of ``g`` from the chunk's first position to ``t`` and
``D_ts = exp(gamma_t - gamma_s)`` for ``s <= t``:

    A  = strict_tril((beta k) k^T * D)       [C, C], nilpotent
    T  = (I + A)^-1                          unit lower triangular
    U  = T (beta v)          W = T (beta k * exp(gamma))
    V' = U - W S                             the chunk's writes, all at once
    O  = (q * exp(gamma)) S + tril(q k^T * D) V'
    S <- exp(gamma_C) S + (k * exp(gamma_C - gamma))^T V'

(the publisher's ``torch_chunk_gated_delta_rule``), so only the state crosses
from chunk to chunk.

**The inverse.**  ``A`` is strictly lower triangular, so ``(I + A)^-1 = (I -
A)(I + A^2)(I + A^4)...`` ends after ``log2 C`` factors; but the powers of a
64-wide ``A`` grow like binomial coefficients where neighbouring keys are
alike, and cancel in fp32.  :func:`unit_lower_inverse` therefore inverts the
diagonal blocks of 16 by that series (three squarings: powers below the
16th) and merges them by the same series over the blocks' strictly lower
part (``B = T_d A_o`` has ``B^4 = 0``): ten ``[C, C]`` products of fp32 by
fp32, at the highest precision in :func:`chunked` and in three bf16 passes in
the kernels (:data:`_KERNEL_INVERSE_PASSES`), and its cotangent is the closed
form ``-T^T dT T^T``, two.

**The kernels.**  A grid step is one chunk of up to eight value heads (their
key heads beside them, read once for the value heads they serve and not
copied) of one sequence, the chunks in order (backward: in reverse), the
heads' states ``[heads, K, V]`` in fp32 in VMEM across them.  The two value
heads of a key head are one block-diagonal chunk of ``2C = 128`` positions
(:func:`_pair_chunk`: the second head's rows under the first's, ``A``, ``T``
and ``tril(q k^T D)`` ``[128, 128]`` with zeros where the heads differ), so
``k k^T`` and ``q k^T`` are computed once a key head and the inverse, ``U``,
``W`` and ``tril(q k^T D) V'`` are one 128-wide product for both; only the
three products with a head's own state stay a head's: 41 MXU passes a pair
where two single heads took 76.  The inverse's series ends where a chunk's
powers do, whatever the width (ten products), and the zeros add exact zeros:
``o`` is the single heads' to the bit.  A step's pairs go through that
algebra together, the pairs a leading axis of every operand: a chunk is one
chain of dependent products, the chip's compiler overlaps only products that
one line of the program holds, and a chain alone waits out each product's
latency (128 ns a dependent ``[64, 64]`` product, 175 a ``[128, 128]`` one,
23 and 49 with eight and four chains abreast: ``benchmarks/
gated_delta_sweep.py --what passes``, PR 51).  :func:`takes` wants an even
number of value heads a key head; one, or an odd number, leaves no pair
inside a key head and goes to :func:`chunked`.  The forward
kernel writes ``o`` and, for the backward pass, the state every chunk
*started* from (``[chunks, heads, K, V]`` fp32: 268 MB a layer at 8192
positions and 32 heads of 128 x 128; a state a token would be 64 times
that).  The backward kernel starts from those, carries the state's cotangent
from the last chunk to the first, and gives the cotangents of ``q``, ``k``,
``v``, ``beta`` and ``gamma``; it is the same pairs' algebra taken backward
by ``jax.vjp`` inside the kernel (a key head's ``dq`` and ``dk`` come out
summed over its pair), the inverse by its closed form.  The sums that turn
``gamma``'s cotangent into ``g``'s run in XLA, by autodiff of the cumulative
sum that made it.

**Precision**: ``q``, ``k``, ``v`` and ``o`` in bf16; ``g``, ``beta``,
``gamma``, ``D``, ``A``, ``T`` and the states in fp32; the products with the
state and the values on the MXU in bf16 with fp32 sums.  :func:`chunked` is
the same chunked form in ``jax.numpy`` (fp32 throughout, differentiated by
autodiff from the chunk-boundary states), the path off the TPU and for what
:func:`takes` refuses.

On the device's op line the calls are :data:`FWD_NAME` and :data:`BWD_NAME`
(``chip_bench/metrics/gated_delta_ms_step.json``).  Pallas is imported where
a kernel is built, not with this module, and each direction is one jitted
function (``kernels/short_conv.py`` says why).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# The calls' names on the device's op line, and what matches both.
FWD_NAME = "hvd_gated_delta_fwd"
BWD_NAME = "hvd_gated_delta_bwd"
OP_LINE_NAMES = r"^hvd_gated_delta"

CHUNK = 64
_LANES = 128
_BLOCK = 16                 # the diagonal blocks unit_lower_inverse starts from
_HEADS_A_STEP = 8
# The bf16 passes the kernels' inverses multiply fp32 by fp32 in: 6 (the
# highest precision) or 3 (:func:`_mm3`).  ``T`` goes into its products
# rounded to bf16, so the three passes' 2**-16 cost ``o`` and the cotangents
# nothing that shows (3.016e-3 and 4.748e-3 of float32's either way) and save
# a sixth of both kernels: a layer forward + backward 19.67 -> 16.31 ms at
# Qwen3-Next's shape a head at a time (`benchmarks/gated_delta_sweep.py`, my
# chip run, PR 50), and 7.73 in pairs, a step's four abreast (PR 51: two
# pairs a step 9.71, eight 7.48).
_KERNEL_INVERSE_PASSES = 3
_VMEM_LIMIT = 64 * 2 ** 20


def heads_a_step(key_heads: int, value_heads: int) -> int:
    """The value heads one grid step takes: the most, up to eight, that are
    whole key heads' and divide the heads; none where a key head serves an
    odd number of value heads, which leaves no pairs inside it."""
    ratio = value_heads // key_heads
    if ratio % 2:
        return 0
    return max((n for n in range(ratio, _HEADS_A_STEP + 1, ratio)
                if value_heads % n == 0), default=0)


def takes(seq_len: int, key_heads: int, value_heads: int, key_dim: int,
          value_dim: int, dtype=jnp.bfloat16) -> bool:
    """Whether the kernels take ``q``, ``k`` ``[b, seq_len, key_heads,
    key_dim]`` and ``v [b, seq_len, value_heads, value_dim]`` of ``dtype``:
    an even number of value heads a key head (a chunk is a pair's); otherwise,
    and off the TPU, :func:`chunked`."""
    if key_heads <= 0 or value_heads % key_heads:
        return False
    return (jnp.dtype(dtype) == jnp.bfloat16
            and key_dim == value_dim == _LANES and seq_len > 0
            and seq_len % CHUNK == 0
            and heads_a_step(key_heads, value_heads) > 0)


def _mm(a, b, ta: bool = False, tb: bool = False, precision=None):
    """``a b`` over the last two axes (``ta``: ``a^T b``; ``tb``: ``a b^T``),
    the axes in front a batch both share; fp32 sums."""
    n = a.ndim - 2
    batch = tuple(range(n))
    return lax.dot_general(
        a, b, (((n if ta else n + 1,), (n + 1 if tb else n,)),
               (batch, batch)),
        precision=precision, preferred_element_type=jnp.float32)


def _lower(c: int, strict: bool, block: int = _BLOCK):
    """``[c, c]``: whether ``s <= t`` (``strict``: ``s < t``), and whether
    both lie in one diagonal block."""
    t = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return (t > s if strict else t >= s), t // block == s // block


def _mm3(a, b, ta: bool = False, tb: bool = False):
    """:func:`_mm` of fp32 by fp32 in three bf16 passes: each operand as its
    bf16 rounding plus what that left, the product of the two remainders
    dropped (2**-16 of the result, where one pass leaves 2**-8)."""
    def halves(t):
        high = t.astype(jnp.bfloat16)
        return high, (t - high.astype(jnp.float32)).astype(jnp.bfloat16)

    (a_high, a_low), (b_high, b_low) = halves(a), halves(b)
    return _mm(a_high, b_high, ta, tb) + (_mm(a_high, b_low, ta, tb)
                                          + _mm(a_low, b_high, ta, tb))


def _inverse_dot(passes: int):
    if passes == 3:
        return _mm3
    if passes != 6:
        raise ValueError(f"{passes} passes: 3 or 6")
    return functools.partial(_mm, precision=lax.Precision.HIGHEST)


def _inverse_products(a, passes: int):
    high = _inverse_dot(passes)
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    _, same_block = _lower(c, True)
    inside = jnp.where(same_block, a, 0.0)
    # The diagonal blocks: (I - a)(I + a^2)(I + a^4)(I + a^8), a^16 = 0.
    t, power, width = eye - inside, inside, 1
    while 2 * width < min(_BLOCK, c):
        power, width = high(power, power), 2 * width
        t = high(t, eye + power)
    if c <= _BLOCK:
        return t
    # The blocks under them: (I + t a_o)^-1 t, (t a_o)^(c / 16) = 0; an ``a``
    # wider than a chunk is block diagonal by chunks, and so are the powers.
    b = high(t, a - inside)
    merged, power, width = eye - b, b, 1
    while 2 * width < -(-min(c, CHUNK) // _BLOCK):
        power, width = high(power, power), 2 * width
        merged = high(merged, eye + power)
    return high(merged, t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(a, passes: int = 6):
    """``(I + a)^-1`` for ``a [..., C, C]`` strictly lower triangular (wider
    than :data:`CHUNK`: block diagonal by chunks besides), fp32, its products
    in ``passes`` bf16 passes (the module's text says how)."""
    return _inverse_products(a, passes)


def _inverse_fwd(a, passes):
    t = _inverse_products(a, passes)
    return t, t


def _inverse_bwd(passes, t, dt):
    high = _inverse_dot(passes)
    strict, _ = _lower(t.shape[-1], True)
    return (jnp.where(strict, -high(high(t, dt, ta=True), t, tb=True), 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _chunk(q, k, v, gamma_col, gamma_row, beta, state, dot=None):
    """One chunk of one value head (or of a batch of them in the leading
    axes): ``q``, ``k [C, K]``, ``v [C, V]``, ``gamma`` as a column ``[C, 1]``
    and as a row ``[1, C]``, ``beta [C, 1]`` and the state the chunk starts
    from ``[K, V]``, all fp32 -> ``(o [C, V], the state it ends with)``.
    ``dot``: the dtype the MXU's operands are rounded to (None: as they
    are)."""
    c = q.shape[-2]
    cast = (lambda t: t) if dot is None else (lambda t: t.astype(dot))
    lower, _ = _lower(c, False)
    strict, _ = _lower(c, True)
    decay = jnp.where(lower, jnp.exp(jnp.minimum(gamma_col - gamma_row, 0.0)),
                      0.0)
    q, k = cast(q), cast(k)
    a = jnp.where(strict, beta * _mm(k, k, tb=True) * decay, 0.0)
    t = cast(unit_lower_inverse(
        a, 6 if dot is None else _KERNEL_INVERSE_PASSES))
    grown = jnp.exp(gamma_col)
    kf = k.astype(jnp.float32)
    u = _mm(t, cast(beta * v))
    w = _mm(t, cast(beta * kf * grown))
    state_in = cast(state)
    writes = cast(u - _mm(cast(w), state_in))
    o = _mm(cast(q.astype(jnp.float32) * grown), state_in) \
        + _mm(cast(_mm(q, k, tb=True) * decay), writes)
    last = gamma_col[..., c - 1:c, :]
    new = jnp.exp(last) * state \
        + _mm(cast(kf * jnp.exp(last - gamma_col)), writes, ta=True)
    return o, new


def _pair_chunk(q, k, v, gamma_col, gamma_row, beta, state, dot):
    """:func:`_chunk` of the two value heads of one key head as one block-
    diagonal problem ``[2C, 2C]``, the second head's rows under the first's:
    ``q``, ``k [C, K]`` the key head's, ``v [2C, V]``, ``gamma`` as ``[2C,
    1]`` and as ``[1, 2C]``, ``beta [2C, 1]``, the states ``[2, K, V]`` ->
    ``(o [2C, V], the states the chunk ends with)``.  ``k k^T`` and ``q k^T``
    are computed once, and the inverse, ``U``, ``W`` and ``tril(q k^T D) V'``
    are one product each for both heads; only the products with a head's own
    state stay a head's.  Every operand is rounded where :func:`_chunk`
    rounds it, and the zeros off the diagonal blocks add exact zeros."""
    c = q.shape[-2]

    def cast(t):
        return t.astype(dot)

    def twice(t):                  # the key head's rows, once a value head
        return jnp.concatenate([t, t], axis=-2)

    def a_head(rows, states, ta=False):
        """``rows [2C, .]`` by ``states [2, ., .]``, each head's by its own."""
        return jnp.concatenate(
            [_mm(rows[..., h * c:(h + 1) * c, :], states[..., h, :, :], ta)
             for h in range(2)], axis=-2)

    lower, own = _lower(2 * c, False, c)
    strict, _ = _lower(2 * c, True)
    decay = jnp.where(lower & own,
                      jnp.exp(jnp.minimum(gamma_col - gamma_row, 0.0)), 0.0)
    q, k = cast(q), twice(cast(k))
    # [2C, 2C] of a [C, C] in every quarter: the decays keep the diagonal two.
    kk = twice(_mm(k[..., :c, :], k, tb=True))
    qk = twice(_mm(q, k, tb=True))
    a = jnp.where(strict, beta * kk * decay, 0.0)
    t = cast(unit_lower_inverse(a, _KERNEL_INVERSE_PASSES))
    grown = jnp.exp(gamma_col)
    kf = k.astype(jnp.float32)
    u = _mm(t, cast(beta * v))
    w = _mm(t, cast(beta * kf * grown))
    state_in = cast(state)
    writes = cast(u - a_head(cast(w), state_in))
    o = a_head(cast(twice(q.astype(jnp.float32)) * grown), state_in) \
        + _mm(cast(qk * decay), writes)
    new = []
    for h in range(2):
        rows = slice(h * c, (h + 1) * c)
        last = gamma_col[..., (h + 1) * c - 1:(h + 1) * c, :]
        # Over the state's rows first, then its lanes: the cotangent of one
        # spread over both is a vector a pair, which Mosaic does not lay out.
        kept = jnp.exp(last) * jnp.ones((state.shape[-2], 1), jnp.float32)
        new.append(kept * state[..., h, :, :] + _mm(
            cast(kf[..., rows, :] * jnp.exp(last - gamma_col[..., rows, :])),
            writes[..., rows, :], ta=True))
    return o, jnp.stack(new, axis=-3)


def _chunks_of(q, k, v, g, beta, chunk: int):
    """The operands of :func:`_chunk` in fp32, chunks leading: ``q``, ``k``
    given to the value heads they serve, the sequence filled to whole chunks
    with steps that neither decay nor write; ``gamma`` the sum of ``g``
    inside each chunk."""
    batch, s, hk, _ = q.shape
    hv = v.shape[2]
    pad = -s % chunk
    nc = (s + pad) // chunk

    def by_chunk(t):
        t = t.astype(jnp.float32)
        if t.ndim == 3:
            t = t[..., None]
        elif t.shape[2] != hv:
            t = jnp.repeat(t, hv // hk, axis=2)
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return t.reshape(batch, nc, chunk, hv, -1).transpose(1, 0, 3, 2, 4)

    q, k, v, g, beta = (by_chunk(t) for t in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=3)                          # [nc, b, hv, C, 1]
    return q, k, v, gamma, jnp.swapaxes(gamma, 3, 4), beta


def chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """:func:`gated_delta` in ``jax.numpy``: the same chunks, the inverse a
    chunk and the state carried between them, in fp32; any length (the last
    chunk is filled with steps of ``g = 0`` and ``beta = 0``, which neither
    decay nor write), any widths."""
    batch, s = q.shape[:2]
    hv, dv = v.shape[2:]
    operands = _chunks_of(q, k, v, g, beta, chunk)

    def carry(state, chunk_in):
        o, state = _chunk(*chunk_in, state)
        return state, o

    _, o = lax.scan(
        carry, jnp.zeros((batch, hv, q.shape[3], dv), jnp.float32), operands)
    o = o.transpose(1, 0, 3, 2, 4).reshape(batch, -1, hv, dv)
    return o[:, :s].astype(v.dtype)


# -- the kernels --------------------------------------------------------------


def _by_head(ref, heads=None):
    """A block ``[C, heads * 128]`` as ``[heads, C, 128]`` in fp32 (``heads``:
    which, in order)."""
    picked = range(ref.shape[1] // _LANES) if heads is None else heads
    return jnp.stack([ref[:, h * _LANES:(h + 1) * _LANES]
                      for h in picked]).astype(jnp.float32)


def _to_heads(ref, by_head):
    """:func:`_by_head` undone, into ``ref``."""
    for h in range(by_head.shape[0]):
        ref[:, h * _LANES:(h + 1) * _LANES] = by_head[h].astype(ref.dtype)


def _columns_under(x):
    """``[C, heads]`` -> ``[pairs, 2C, 1]``: a pair's first column with its
    second under it, as one masked sum over the lanes (``ssd_scan._column``'s
    way: the sum leaves every lane holding the column, which a ``[1, 1]`` of
    it spread over a state needs)."""
    c, heads = x.shape
    both = jnp.broadcast_to(jnp.concatenate([x, x], axis=0),
                            (heads // 2, 2 * c, heads))
    pair, row, lane = (lax.broadcasted_iota(jnp.int32, both.shape, axis)
                       for axis in range(3))
    return jnp.sum(jnp.where(lane == 2 * pair + row // c, both, 0.0), axis=2,
                   keepdims=True)


def _columns_beside(x):
    """:func:`_columns_under` undone: ``[pairs, 2C, 1]`` -> ``[C, heads]``."""
    heads, c = 2 * x.shape[0], x.shape[1] // 2
    x = x.reshape(heads, c, 1)
    lane = lax.broadcasted_iota(jnp.int32, (c, heads), 1)
    return sum(jnp.where(lane == h, x[h], 0.0) for h in range(heads))


def _pairs(q_ref, k_ref, v_ref, gc_ref, gr_ref, beta_ref, ratio: int):
    """:func:`_pair_chunk`'s operands but the states for all of a grid
    step's pairs, the pairs leading: their chains of products are independent
    and the compiler overlaps only what one line of the program holds."""
    c, heads = gc_ref.shape
    key_heads = [2 * p // ratio for p in range(heads // 2)]
    return (_by_head(q_ref, key_heads), _by_head(k_ref, key_heads),
            _by_head(v_ref).reshape(heads // 2, 2 * c, _LANES),
            _columns_under(gc_ref[...]), gr_ref[...][:, None, :],
            _columns_under(beta_ref[...]))


def _fwd_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, beta_ref, o_ref,
                before_ref, state_ref, *, ratio: int):
    """One chunk of one step's heads, in pairs: ``q``, ``k [C, key heads *
    128]``, ``v [C, heads * 128]``, ``gamma`` as ``[C, heads]`` and as
    ``[pairs, 2C]``, ``beta [C, heads]``; ``o`` out, and the states the
    chunk started from, ``[heads, 128, 128]``."""
    import jax.experimental.pallas as pl

    c, heads = gc_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    before_ref[...] = state_ref[...]
    o, state = _pair_chunk(
        *_pairs(q_ref, k_ref, v_ref, gc_ref, gr_ref, beta_ref, ratio),
        state_ref[...].reshape(heads // 2, 2, _LANES, _LANES),
        dot=q_ref.dtype)
    state_ref[...] = state.reshape(state_ref.shape)
    _to_heads(o_ref, o.reshape(heads, c, _LANES))


def _bwd_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, beta_ref, before_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dgc_ref, dgr_ref, dbeta_ref,
                dstate_ref, *, ratio: int):
    """The same chunk's cotangents, the chunks taken last to first:
    ``dstate`` holds the cotangent of the state the chunk *ends* with and
    leaves that of the state it started from (``before_ref``).  ``gamma``'s
    cotangent comes in two parts, what falls out as columns ``[C, heads]``
    and what falls out as rows ``[pairs, 2C]``; the caller adds them."""
    import jax.experimental.pallas as pl

    c, heads = gc_ref.shape
    paired = (heads // 2, 2, _LANES, _LANES)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    _, back = jax.vjp(
        functools.partial(_pair_chunk, dot=q_ref.dtype),
        *_pairs(q_ref, k_ref, v_ref, gc_ref, gr_ref, beta_ref, ratio),
        before_ref[...].reshape(paired))
    dq, dk, dv, col, row, dbeta, dstate = back(
        (_by_head(do_ref).reshape(heads // 2, 2 * c, _LANES),
         dstate_ref[...].reshape(paired)))
    dstate_ref[...] = dstate.reshape(dstate_ref.shape)
    dgr_ref[...] = row.reshape(dgr_ref.shape)
    dgc_ref[...] = _columns_beside(col)
    dbeta_ref[...] = _columns_beside(dbeta)
    _to_heads(dv_ref, dv.reshape(heads, c, _LANES))
    # A key head's cotangents are the sum over the value heads it serves:
    # over a pair already, and here over the key head's pairs.
    for d_ref, d in ((dq_ref, dq), (dk_ref, dk)):
        _to_heads(d_ref, jnp.sum(
            d.reshape(heads // ratio, ratio // 2, c, _LANES), axis=1))


def _specs(s: int, heads: int, ratio: int, reverse: bool):
    """The blocks of one grid step ``(sequence, step's heads, chunk)``: the
    keys', the values', the per-head columns' and rows', the states'."""
    import jax.experimental.pallas as pl

    nc = s // CHUNK

    def at(j):
        return nc - 1 - j if reverse else j

    keys = pl.BlockSpec((None, CHUNK, heads // ratio * _LANES),
                        lambda i, g, j: (i, at(j), g))
    values = pl.BlockSpec((None, CHUNK, heads * _LANES),
                          lambda i, g, j: (i, at(j), g))
    columns = pl.BlockSpec((None, None, CHUNK, heads),
                           lambda i, g, j: (i, g, at(j), 0))
    # A chunk's rows, a pair of heads a row of one lane group.
    rows = pl.BlockSpec((None, None, None, heads // 2, 2 * CHUNK),
                        lambda i, g, j: (i, g, at(j), 0, 0))
    states = pl.BlockSpec((None, None, None, heads, _LANES, _LANES),
                          lambda i, g, j: (i, g, at(j), 0, 0, 0))
    return nc, keys, values, columns, rows, states


def _rows(columns):
    """``[batch, steps, s, heads]`` as ``[batch, steps, chunks, pairs, 2C]``,
    a pair's second head behind its first."""
    batch, steps, s, heads = columns.shape
    return columns.reshape(batch, steps, s // CHUNK, CHUNK, heads // 2, 2) \
        .transpose(0, 1, 2, 4, 5, 3) \
        .reshape(batch, steps, s // CHUNK, heads // 2, 2 * CHUNK)


def _columns(rows):
    """:func:`_rows` undone."""
    batch, steps, nc, pairs, _ = rows.shape
    return rows.reshape(batch, steps, nc, pairs, 2, CHUNK) \
        .transpose(0, 1, 2, 5, 3, 4) \
        .reshape(batch, steps, nc * CHUNK, 2 * pairs)


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def chunk_products(c: int, key_dim: int, value_dim: int) -> int:
    """Multiply-adds of one chunk of one value head forward, as the chunked
    form needs them (``k k^T`` and ``q k^T`` counted a value head, the
    inverse at its ten ``[C, C]`` products)."""
    return (2 * c * c * key_dim + 10 * c ** 3
            + c * c * (value_dim + key_dim)         # U, W
            + 2 * c * key_dim * value_dim           # W S, (q exp) S
            + c * c * value_dim                     # tril(q k^T D) V'
            + c * key_dim * value_dim)              # the state's writes


@functools.partial(jax.jit, static_argnames=("ratio", "interpret"))
def _forward(q, k, v, gamma, beta, *, ratio: int, interpret: bool):
    """``q``, ``k [batch, s, key heads * 128]``, ``v [batch, s, heads *
    128]``, ``gamma``, ``beta`` ``[batch, steps, s, heads a step]`` fp32 ->
    ``(o, the states the chunks started from)``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, s, _ = v.shape
    steps, heads = gamma.shape[1], gamma.shape[3]
    nc, keys, values, columns, rows, states = _specs(s, heads, ratio, False)
    vma = jax.typeof(v).vma
    calls = batch * steps * nc * heads
    return pl.pallas_call(
        functools.partial(_fwd_kernel, ratio=ratio),
        grid=(batch, steps, nc),
        in_specs=[keys, keys, values, columns, rows, columns],
        out_specs=[values, states],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
            jax.ShapeDtypeStruct((batch, steps, nc, heads, _LANES, _LANES),
                                 jnp.float32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((heads, _LANES, _LANES), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * calls * chunk_products(CHUNK, _LANES, _LANES),
            transcendentals=calls * CHUNK * CHUNK,
            bytes_accessed=2 * (q.size + k.size + 2 * v.size)
            + 4 * calls * _LANES * _LANES),
        name=FWD_NAME, interpret=interpret,
    )(q, k, v, gamma, _rows(gamma), beta)


@functools.partial(jax.jit, static_argnames=("ratio", "interpret"))
def _backward(q, k, v, gamma, beta, before, do, *, ratio: int,
              interpret: bool):
    """-> the cotangents of ``q``, ``k``, ``v``, ``gamma`` and ``beta``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, s, _ = v.shape
    steps, heads = gamma.shape[1], gamma.shape[3]
    nc, keys, values, columns, rows, states = _specs(s, heads, ratio, True)
    vma = jax.typeof(v).vma

    def like(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype, vma=vma)

    by_rows = jax.ShapeDtypeStruct((batch, steps, nc, heads // 2, 2 * CHUNK),
                                   jnp.float32, vma=vma)
    calls = batch * steps * nc * heads
    dq, dk, dv, dgc, dgr, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, ratio=ratio),
        grid=(batch, steps, nc),
        in_specs=[keys, keys, values, columns, rows, columns, states, values],
        out_specs=[keys, keys, values, columns, rows, columns],
        out_shape=[like(q), like(k), like(v), like(gamma), by_rows,
                   like(beta)],
        scratch_shapes=[pltpu.VMEM((heads, _LANES, _LANES), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=6 * calls * chunk_products(CHUNK, _LANES, _LANES),
            transcendentals=calls * CHUNK * CHUNK,
            bytes_accessed=4 * (q.size + k.size + 2 * v.size)
            + 4 * calls * _LANES * _LANES),
        name=BWD_NAME, interpret=interpret,
    )(q, k, v, gamma, _rows(gamma), beta, before, do)
    return dq, dk, dv, dgc + _columns(dgr), dbeta


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, gamma, beta, ratio, interpret):
    return _forward(q, k, v, gamma, beta, ratio=ratio, interpret=interpret)[0]


def _rule_fwd(q, k, v, gamma, beta, ratio, interpret):
    o, before = _forward(q, k, v, gamma, beta, ratio=ratio,
                         interpret=interpret)
    return o, (q, k, v, gamma, beta, before)


def _rule_bwd(ratio, interpret, kept, do):
    return _backward(*kept, do, ratio=ratio, interpret=interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta(q, k, v, g, beta, *, interpret: bool = False):
    """``o_t = S_t^T q_t`` with ``S`` the gated delta rule's state a value
    head, zero before each sequence: ``q``, ``k [batch, s, key heads, K]``
    (``k`` of unit length, ``q`` scaled: the caller's), ``v [batch, s, value
    heads, V]``, ``g`` (the decay's logarithm, <= 0) and ``beta`` (the write
    strength) ``[batch, s, value heads]``; value head ``h`` reads key head ``h
    // (value heads / key heads)``; ``o`` as ``v``.  Differentiable in all
    five.  On a TPU (or with ``interpret``) the kernels, for what
    :func:`takes` takes; else :func:`chunked`."""
    batch, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    if k.shape != q.shape or v.shape[:2] != (batch, s) \
            or g.shape != (batch, s, hv) or beta.shape != g.shape \
            or hv % hk:
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}, "
                         f"g {g.shape}, beta {beta.shape}")
    same = q.dtype == k.dtype == v.dtype
    if not ((interpret or jax.default_backend() == "tpu") and same
            and takes(s, hk, hv, dk, dv, v.dtype)):
        return chunked(q, k, v, g, beta)
    heads = heads_a_step(hk, hv)

    def by_step(t):                                   # [b, steps, s, heads]
        return t.astype(jnp.float32).reshape(batch, s, hv // heads, heads) \
            .transpose(0, 2, 1, 3)

    g = by_step(g)
    gamma = jnp.cumsum(g.reshape(batch, hv // heads, s // CHUNK, CHUNK,
                                 heads), axis=3).reshape(g.shape)
    o = _rule(q.reshape(batch, s, hk * dk), k.reshape(batch, s, hk * dk),
              v.reshape(batch, s, hv * dv), gamma, by_step(beta), hv // hk,
              interpret)
    return o.reshape(v.shape)
