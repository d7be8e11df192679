"""The state-space-dual scan of Mamba-2 in its chunked form, forward and
backward in one kernel each.

A Mamba-2 mixer (``models/mamba2.py``, arXiv:2405.21060) carries, a head, a
state ``S [P, N]`` along the sequence:

    S_t = exp(dt_t * a) S_{t-1} + dt_t * x_t B_t^T        a < 0 a head
    y_t = S_t C_t

with ``x_t [P]`` the head's channels, ``B_t``, ``C_t [N]`` those of the head's
group and ``dt_t > 0`` the head's step.  Token by token that is ``s`` steps
of a few hundred operations each.  In chunks of ``Q`` positions it is matrix
products: with ``cum_t`` the sum of ``dt * a`` from the chunk's first position
to ``t``,

    y_t  = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s      (inside)
           + exp(cum_t) S_prev C_t                                   (carried)
    S_new = exp(cum_Q) S_prev + sum_s exp(cum_Q - cum_s) dt_s x_s B_s^T

so a chunk costs a masked ``C B^T`` of ``[Q, Q]`` a group, one ``[Q, Q] x [Q,
P]`` product a head and three ``[Q, N]`` products with the state, and only
the state crosses from chunk to chunk.

**The kernels.**  A grid step is one chunk of one group of one sequence, the
chunks in order (backward: in reverse), the group's states ``[heads, P, N]``
in fp32 in VMEM across them.  Channels are taken 128 lanes at a time, which
at ``P = 64`` is two heads: their decays differ, so the ``[Q, Q]`` product
runs once a head over the pair's lanes (the MXU is 128 wide whatever is
asked of it) and each head keeps its half.  The forward kernel writes ``y``
and, for the backward pass, the state every chunk *started* from
(``[chunks, heads, P, N]`` fp32: 33.5 MB a layer at 8192 positions, 16 heads
of 64 and a state of 128, nemotron-3-super-120b-a12b's share of a mixer; 134
MB at 64 heads, granite-4.0-h-micro's whole mixer; a state a token would be
128 times that).  The
backward kernel starts from those, carries the state's cotangent from the
last chunk to the first, and gives the cotangents of ``x``, ``B``, ``C``,
``dt`` and ``cum``; the sums that turn ``cum``'s into ``dt``'s and ``a``'s
are a few KB and run in XLA, by autodiff of the cumulative sum that made it.

**A group's size.**  The heads of a group are unrolled inside a grid step,
a lane pair at a time, so the group sets what a step holds and how long its
program is.  At 16 heads a group (Nemotron's): 8 pairs, ``x`` ``[128, 1024]``,
0.5 MB of states in VMEM, 33.5 MB of starting states a layer.  At 64 heads in
one group (Granite's): 32 pairs, ``x`` ``[128, 4096]`` (1 MB a block, two
buffers each of ``x``, ``y`` or ``dy`` and ``dx``), 2 MB of states and 2 MB
of their cotangent, blocks of 2 MB of starting states in and out, all well
inside :data:`_VMEM_LIMIT`; the same kernels take both, compiled for a v5e in
5 s forward and 9 s backward at 64 heads
(``tests/test_granite_compile.py``).  ``B`` and ``C`` are read once a grid
step whatever the group's size, and ``C B^T`` is computed once for all its
heads, so a larger group amortises them further; a grid axis over the
group's heads would read them once a tile instead.  ``PERF.md`` section 6
(PR 56) has both shapes' times on the chip.

**The chunk** is 128 (:data:`CHUNK`) and :func:`takes` refuses any other: a
release's own chunk (granite-4.0-h-micro's ``mamba_chunk_size`` 256) is a
blocking of the same sum and changes no result, so a configuration keeps
that key as published and runs these kernels at 128.

**Precision**: ``x``, ``B``, ``C`` and ``y`` in bf16; ``dt``, ``cum``, the
decays and the states in fp32; the products on the MXU in bf16 with fp32
sums.  :func:`chunked` is the same chunked form in ``jax.numpy`` (fp32
throughout, differentiated by autodiff from the chunk-boundary states), the
path off the TPU and for what :func:`takes` refuses.

On the device's op line the calls are :data:`FWD_NAME` and :data:`BWD_NAME`
(``chip_bench/metrics/ssd_scan_ms_step.json``).  Pallas is imported where a
kernel is built, not with this module, and each direction is one jitted
function (``kernels/short_conv.py`` says why).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# The calls' names on the device's op line, and what matches both.
FWD_NAME = "hvd_ssd_scan_fwd"
BWD_NAME = "hvd_ssd_scan_bwd"
OP_LINE_NAMES = r"^hvd_ssd_scan"

CHUNK = 128
_LANES = 128
_VMEM_LIMIT = 64 * 2 ** 20


def takes(seq_len: int, heads: int, head_dim: int, groups: int, state: int,
          chunk: int = CHUNK, dtype=jnp.bfloat16) -> bool:
    """Whether the kernels take ``x [b, seq_len, heads, head_dim]`` of
    ``dtype`` with ``B``, ``C`` ``[b, seq_len, groups, state]``; otherwise,
    and off the TPU, :func:`chunked`."""
    if groups <= 0 or heads % groups:
        return False
    per_group = heads // groups
    return (jnp.dtype(dtype) == jnp.bfloat16 and chunk == CHUNK
            and head_dim in (64, 128) and state % _LANES == 0
            and per_group % 8 == 0 and seq_len > 0 and seq_len % chunk == 0)


def chunked(x, dt, a, b, c, chunk: int = CHUNK):
    """:func:`ssd_scan` in ``jax.numpy``: the same chunks, the masked ``C
    B^T`` inside each and the state carried between them, in fp32; any
    length (the last chunk is filled with steps of ``dt = 0``, which neither
    decay nor add), any number of groups."""
    batch, s, heads, p = x.shape
    groups, n = b.shape[2:]
    per = heads // groups
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    f32 = jnp.float32
    xs = x.astype(f32).reshape(batch, nc, chunk, groups, per, p)
    dts = dt.astype(f32).reshape(batch, nc, chunk, groups, per)
    bs = b.astype(f32).reshape(batch, nc, chunk, groups, n)
    cs = c.astype(f32).reshape(batch, nc, chunk, groups, n)
    cum = jnp.cumsum(dts * a.astype(f32).reshape(groups, per), axis=2)
    last = cum[:, :, -1]                                   # [b, nc, g, per]
    # Inside a chunk: t reads s <= t.
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    lam = jnp.where(lower, jnp.exp(jnp.minimum(
        cum[:, :, :, None] - cum[:, :, None], 0.0)), 0.0)  # [b,nc,t,s,g,per]
    cb = jnp.einsum("bctgn,bcsgn->bctsg", cs, bs)
    w = cb[..., None] * lam * dts[:, :, None]
    y = jnp.einsum("bctsgh,bcsghp->bctghp", w, xs)
    # What each chunk adds to the state, and the state each starts from.
    add = jnp.einsum("bcsgh,bcsghp,bcsgn->bcghpn",
                     jnp.exp(last[:, :, None] - cum) * dts, xs, bs)

    def carry(state, chunk_in):
        decay, added = chunk_in
        return decay[..., None, None] * state + added, state

    _, before = lax.scan(
        carry, jnp.zeros((batch, groups, per, p, n), f32),
        (jnp.moveaxis(jnp.exp(last), 1, 0), jnp.moveaxis(add, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                    # [b,nc,g,per,p,n]
    y = y + jnp.exp(cum)[..., None] \
        * jnp.einsum("bctgn,bcghpn->bctghp", cs, before)
    return y.reshape(batch, s + pad, heads, p)[:, :s].astype(x.dtype)


# -- the kernels --------------------------------------------------------------


def _nt(a, b):
    """``a b^T``: both contract their lanes."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _tn(a, b):
    """``a^T b``: both contract their rows."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _column(block, h: int):
    """Column ``h`` of ``block [rows, heads]`` as ``[rows, 1]``: a masked
    sum over the lanes, where a slice one lane wide would leave the tiling."""
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == h, block, 0.0), axis=1, keepdims=True)


def _by_lanes(columns, p: int, rows: int):
    """``[rows, 128]`` whose lanes ``i*p .. (i+1)*p`` hold ``columns[i]``
    (``[rows, 1]`` or ``[1, 1]`` each)."""
    lane = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    out = jnp.broadcast_to(columns[0], (rows, _LANES))
    for i in range(1, len(columns)):
        out = jnp.where(lane >= i * p, columns[i], out)
    return out


def _by_rows(scalars, p: int):
    """``[128, 1]`` whose rows ``i*p .. (i+1)*p`` hold ``scalars[i] [1, 1]``."""
    row = lax.broadcasted_iota(jnp.int32, (_LANES, 1), 0)
    out = jnp.broadcast_to(scalars[0], (_LANES, 1))
    for i in range(1, len(scalars)):
        out = jnp.where(row >= i * p, scalars[i], out)
    return out


def _head(dt_ref, cum_ref, dtr_ref, cumr_ref, h: int):
    """One head's ``dt`` and ``cum`` down a chunk as columns ``[Q, 1]`` and
    as rows ``[1, Q]``, ``cum`` at the chunk's last position ``[1, 1]`` and
    the decay ``exp(cum_t - cum_s)`` for ``s <= t``, else 0, ``[Q, Q]``."""
    q = dt_ref.shape[0]
    dt_c, cum_c = _column(dt_ref[...], h), _column(cum_ref[...], h)
    dt_r, cum_r = dtr_ref[h:h + 1, :], cumr_ref[h:h + 1, :]
    last = _column(cum_ref[q - 1:q, :], h)
    lower = lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lam = jnp.where(lower, jnp.exp(jnp.minimum(cum_c - cum_r, 0.0)), 0.0)
    return dt_c, cum_c, dt_r, last, lam


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, dtr_ref, cumr_ref,
                y_ref, before_ref, state_ref, *, p: int):
    """One chunk of one group: ``x [Q, heads*p]``, ``B``, ``C`` ``[Q, N]``,
    ``dt`` and ``cum`` as ``[Q, heads]`` and as ``[heads, Q]``; ``y`` out,
    and the states the chunk started from, ``[tiles, 128, N]`` (a tile's rows
    are its heads' ``p`` channels each)."""
    import jax.experimental.pallas as pl

    q = x_ref.shape[0]
    per = _LANES // p

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    before_ref[...] = state_ref[...]
    bm, cm = b_ref[...], c_ref[...]
    cb = _nt(cm, bm)                                       # [Q, Q]
    for t in range(state_ref.shape[0]):
        lanes = slice(t * _LANES, (t + 1) * _LANES)
        xp = x_ref[:, lanes]
        inside, reads, adds, decays = None, [], [], []
        for i in range(per):
            dt_c, cum_c, dt_r, last, lam = _head(
                dt_ref, cum_ref, dtr_ref, cumr_ref, t * per + i)
            w = (cb * lam * dt_r).astype(xp.dtype)
            part = jnp.dot(w, xp, preferred_element_type=jnp.float32)
            if i:
                lane = lax.broadcasted_iota(jnp.int32, part.shape, 1)
                inside = jnp.where(lane >= i * p, part, inside)
            else:
                inside = part
            reads.append(jnp.exp(cum_c))
            adds.append(jnp.exp(last - cum_c) * dt_c)
            decays.append(jnp.exp(last))
        state = state_ref[t]                               # [128, N] fp32
        carried = _nt(cm, state.astype(cm.dtype))          # [Q, 128]
        y_ref[:, lanes] = (inside + _by_lanes(reads, p, q) * carried) \
            .astype(y_ref.dtype)
        scaled = (xp.astype(jnp.float32) * _by_lanes(adds, p, q)) \
            .astype(xp.dtype)
        state_ref[t] = _by_rows(decays, p) * state + _tn(scaled, bm)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, dtr_ref, cumr_ref,
                before_ref, dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref,
                ddtr_ref, dcumr_ref, dstate_ref, *, p: int):
    """The same chunk's cotangents, the chunks taken last to first:
    ``dstate`` holds the cotangent of the state the chunk *ends* with and
    leaves that of the state it started from (``before_ref``).  ``dt``'s and
    ``cum``'s cotangents come in two parts, those that fall out as columns
    ``[Q, heads]`` and those that fall out as rows ``[heads, Q]``; the caller
    adds them."""
    import jax.experimental.pallas as pl

    q, heads = dt_ref.shape
    per = _LANES // p
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    bm, cm = b_ref[...], c_ref[...]
    cb = _nt(cm, bm)
    d_cb = jnp.zeros((q, q), f32)
    db = jnp.zeros(db_ref.shape, f32)
    dc = jnp.zeros(dc_ref.shape, f32)
    ddt_c = jnp.zeros((q, heads), f32)
    dcum_c = jnp.zeros((q, heads), f32)
    ddt_r = jnp.zeros((heads, q), f32)
    dcum_r = jnp.zeros((heads, q), f32)
    head_lane = lax.broadcasted_iota(jnp.int32, (q, heads), 1)
    head_row = lax.broadcasted_iota(jnp.int32, (heads, q), 0)
    at_last = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    lane = lax.broadcasted_iota(jnp.int32, (q, _LANES), 1)
    state_row = lax.broadcasted_iota(jnp.int32, (_LANES, 1), 0)
    for t in range(dstate_ref.shape[0]):
        lanes = slice(t * _LANES, (t + 1) * _LANES)
        xp, dyp = x_ref[:, lanes], dy_ref[:, lanes]
        xf, dyf = xp.astype(f32), dyp.astype(f32)
        state, dstate = before_ref[t], dstate_ref[t]       # [128, N] fp32
        state_b, dstate_b = state.astype(cm.dtype), dstate.astype(bm.dtype)
        carried = _nt(cm, state_b)                         # C S^T   [Q, 128]
        fed = _nt(bm, dstate_b)                            # B dS^T  [Q, 128]
        kept = jnp.sum(dstate * state, axis=1, keepdims=True)    # [128, 1]
        dx_inside, reads, adds, decays = None, [], [], []
        for i in range(per):
            h = t * per + i
            dt_c, cum_c, dt_r, last, lam = _head(
                dt_ref, cum_ref, dtr_ref, cumr_ref, h)
            mine = (lane >= i * p) & (lane < (i + 1) * p)
            cb_lam = cb * lam
            w = cb_lam * dt_r
            # d_w[t, s] = dy_t . x_s over this head's channels.
            d_w = _nt(jnp.where(mine, dyp, jnp.zeros_like(dyp)), xp)
            through_dt = d_w * cb_lam
            through_cum = through_dt * dt_r
            d_cb = d_cb + d_w * lam * dt_r
            part = _tn(w.astype(dyp.dtype), dyp)           # W^T dy  [Q, 128]
            dx_inside = jnp.where(lane >= i * p, part, dx_inside) if i \
                else part
            read, decay = jnp.exp(cum_c), jnp.exp(last)
            add_plain = jnp.exp(last - cum_c)
            read_back = read * jnp.sum(
                jnp.where(mine, dyf * carried, 0.0), axis=1, keepdims=True)
            fed_dt = add_plain * jnp.sum(
                jnp.where(mine, xf * fed, 0.0), axis=1, keepdims=True)
            fed_cum = fed_dt * dt_c
            mine_rows = (state_row >= i * p) & (state_row < (i + 1) * p)
            at_end = jnp.sum(fed_cum, axis=0, keepdims=True) + decay * jnp.sum(
                jnp.where(mine_rows, kept, 0.0), axis=0, keepdims=True)
            column = jnp.sum(through_cum, axis=1, keepdims=True) + read_back \
                - fed_cum + jnp.where(at_last, at_end, 0.0)
            dcum_c = jnp.where(head_lane == h, column, dcum_c)
            ddt_c = jnp.where(head_lane == h, fed_dt, ddt_c)
            dcum_r = jnp.where(
                head_row == h,
                -jnp.sum(through_cum, axis=0, keepdims=True), dcum_r)
            ddt_r = jnp.where(
                head_row == h,
                jnp.sum(through_dt, axis=0, keepdims=True), ddt_r)
            reads.append(read)
            adds.append(add_plain * dt_c)
            decays.append(decay)
        add = _by_lanes(adds, p, q)
        dy_read = (dyf * _by_lanes(reads, p, q)).astype(cm.dtype)
        dc = dc + jnp.dot(dy_read, state_b, preferred_element_type=f32)
        db = db + jnp.dot((xf * add).astype(bm.dtype), dstate_b,
                          preferred_element_type=f32)
        dx_ref[:, lanes] = (dx_inside + add * fed).astype(dx_ref.dtype)
        dstate_ref[t] = _by_rows(decays, p) * dstate + _tn(dy_read, cm)
    d_cb = d_cb.astype(bm.dtype)
    db_ref[...] = (db + _tn(d_cb, cm)).astype(db_ref.dtype)
    dc_ref[...] = (dc + jnp.dot(d_cb, bm, preferred_element_type=f32)) \
        .astype(dc_ref.dtype)
    ddt_ref[...] = ddt_c
    dcum_ref[...] = dcum_c
    ddtr_ref[...] = ddt_r
    dcumr_ref[...] = dcum_r


def _specs(s: int, width: int, n: int, heads: int, reverse: bool):
    """The blocks of one grid step ``(sequence, group, chunk)``: ``x``'s,
    ``B``'s and ``C``'s, the per-head columns' and rows', the states'."""
    import jax.experimental.pallas as pl

    nc = s // CHUNK

    def at(j):
        return nc - 1 - j if reverse else j

    channels = pl.BlockSpec((None, CHUNK, width), lambda i, g, j: (i, at(j), g))
    group = pl.BlockSpec((None, CHUNK, n), lambda i, g, j: (i, at(j), g))
    columns = pl.BlockSpec((None, None, CHUNK, heads),
                           lambda i, g, j: (i, g, at(j), 0))
    rows = pl.BlockSpec((None, None, heads, CHUNK),
                        lambda i, g, j: (i, g, 0, at(j)))
    states = pl.BlockSpec((None, None, None, width // _LANES, _LANES, n),
                          lambda i, g, j: (i, g, at(j), 0, 0, 0))
    return nc, channels, group, columns, rows, states


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _chunk_products(q: int, n: int, tiles: int, per: int, backward: bool):
    """Multiply-adds of one chunk of one group, as the kernels run them."""
    unit = q * _LANES
    if backward:
        return (3 * q * n + tiles * (2 * per * q + 5 * n)) * unit
    return (q * n + tiles * (per * q + 2 * n)) * unit


@functools.partial(jax.jit, static_argnames=("p", "interpret"))
def _forward(x, b, c, dt, cum, *, p: int, interpret: bool):
    """``x [batch, s, heads*p]``, ``b``, ``c`` ``[batch, s, groups*n]``,
    ``dt``, ``cum`` ``[batch, groups, s, heads a group]`` fp32 -> ``(y, the
    states the chunks started from)``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, s, _ = x.shape
    groups, heads = dt.shape[1], dt.shape[3]
    width, n = heads * p, b.shape[2] // groups
    tiles = width // _LANES
    nc, channels, group, columns, rows, states = _specs(s, width, n, heads,
                                                        False)
    vma = jax.typeof(x).vma
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        grid=(batch, groups, nc),
        in_specs=[channels, group, group, columns, columns, rows, rows],
        out_specs=[channels, states],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma),
            jax.ShapeDtypeStruct((batch, groups, nc, tiles, _LANES, n),
                                 jnp.float32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((tiles, _LANES, n), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * groups * nc * _chunk_products(
                CHUNK, n, tiles, _LANES // p, False),
            transcendentals=batch * groups * nc * heads * CHUNK * CHUNK,
            bytes_accessed=2 * batch * s * (2 * groups * width
                                            + 2 * groups * n)
            + 4 * batch * groups * nc * tiles * _LANES * n),
        name=FWD_NAME, interpret=interpret,
    )(x, b, c, dt, cum, jnp.swapaxes(dt, 2, 3), jnp.swapaxes(cum, 2, 3))


@functools.partial(jax.jit, static_argnames=("p", "interpret"))
def _backward(x, b, c, dt, cum, before, dy, *, p: int, interpret: bool):
    """-> the cotangents of ``x``, ``b``, ``c``, ``dt`` and ``cum``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, s, _ = x.shape
    groups, heads = dt.shape[1], dt.shape[3]
    width, n = heads * p, b.shape[2] // groups
    tiles = width // _LANES
    nc, channels, group, columns, rows, states = _specs(s, width, n, heads,
                                                        True)
    vma = jax.typeof(x).vma

    def like(t, dtype=None):
        return jax.ShapeDtypeStruct(t.shape, dtype or t.dtype, vma=vma)

    by_rows = jax.ShapeDtypeStruct((batch, groups, heads, s), jnp.float32,
                                   vma=vma)
    dx, db, dc, ddt, dcum, ddt_r, dcum_r = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=(batch, groups, nc),
        in_specs=[channels, group, group, columns, columns, rows, rows,
                  states, channels],
        out_specs=[channels, group, group, columns, columns, rows, rows],
        out_shape=[like(x), like(b), like(c), like(dt), like(cum), by_rows,
                   by_rows],
        scratch_shapes=[pltpu.VMEM((tiles, _LANES, n), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * groups * nc * _chunk_products(
                CHUNK, n, tiles, _LANES // p, True),
            transcendentals=batch * groups * nc * heads * CHUNK * CHUNK,
            bytes_accessed=2 * batch * s * (3 * groups * width
                                            + 4 * groups * n)
            + 4 * batch * groups * nc * tiles * _LANES * n),
        name=BWD_NAME, interpret=interpret,
    )(x, b, c, dt, cum, jnp.swapaxes(dt, 2, 3), jnp.swapaxes(cum, 2, 3),
      before, dy)
    return (dx, db, dc, ddt + jnp.swapaxes(ddt_r, 2, 3),
            dcum + jnp.swapaxes(dcum_r, 2, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, b, c, dt, cum, p, interpret):
    return _forward(x, b, c, dt, cum, p=p, interpret=interpret)[0]


def _scan_fwd(x, b, c, dt, cum, p, interpret):
    y, before = _forward(x, b, c, dt, cum, p=p, interpret=interpret)
    return y, (x, b, c, dt, cum, before)


def _scan_bwd(p, interpret, kept, dy):
    return _backward(*kept, dy, p=p, interpret=interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, a, b, c, *, chunk: int = CHUNK, interpret: bool = False):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``
    a head, the state zero before each sequence: ``x [batch, s, heads, p]``,
    ``dt [batch, s, heads]`` (positive: after its softplus), ``a [heads]``
    (negative), ``b``, ``c`` ``[batch, s, groups, n]``, head ``h`` reading
    group ``h // (heads / groups)``; ``y`` as ``x``.  Differentiable in all
    five.  The ``D x`` that Mamba-2 adds is the caller's.  On a TPU (or with
    ``interpret``) the kernels, for what :func:`takes` takes; else
    :func:`chunked`."""
    batch, s, heads, p = x.shape
    groups, n = b.shape[2:]
    if b.shape != c.shape or dt.shape != (batch, s, heads) \
            or a.shape != (heads,) or heads % groups:
        raise ValueError(f"x {x.shape}, dt {dt.shape}, a {a.shape}, "
                         f"b {b.shape}, c {c.shape}")
    same = x.dtype == b.dtype == c.dtype
    if not ((interpret or jax.default_backend() == "tpu") and same
            and takes(s, heads, p, groups, n, chunk, x.dtype)):
        return chunked(x, dt, a, b, c, chunk)
    per = heads // groups
    dt = dt.astype(jnp.float32).reshape(batch, s, groups, per) \
        .transpose(0, 2, 1, 3)                             # [b, g, s, per]
    steps = dt * a.astype(jnp.float32).reshape(groups, 1, per)
    cum = jnp.cumsum(steps.reshape(batch, groups, s // chunk, chunk, per),
                     axis=3).reshape(dt.shape)
    y = _scan(x.reshape(batch, s, heads * p), b.reshape(batch, s, groups * n),
              c.reshape(batch, s, groups * n), dt, cum, p, interpret)
    return y.reshape(x.shape)
