"""The backward of ``kernels/masked_attention.py::attention`` in one kernel.

dq, dk and dv of softmax attention under a rule from a single pass over the
tiles the rule allows.  Per allowed tile (query tile ``i``, key tile ``j``) of
one query head the kernel rebuilds ``s = k q^T`` (keys on the rows, as the
library's dkv kernel lays it out) and ``p = exp(s - lse)`` under the mask,
and from them ``dv[j] += p do``, ``dp = v do^T``, ``ds = p (dp - di)``,
``dk[j] += ds q`` and ``dq[i] += ds^T k``: five products where a dq and a dkv
kernel that each rebuild ``p`` and ``dp`` make seven, bf16 operands, every sum
in fp32.

The grid is (sequence, KV head, query head of its group, allowed tile), the
allowed tiles of a query head in row-major order from :func:`tile_table`
through scalar prefetch, so that no grid step lands on an empty tile and no
index map fetches one.  ``dq[i]`` is summed in a tile of scratch over the
``j`` allowed with ``i`` and written when they are done.  **dk and dv of the
KV head, the whole sequence in fp32 (16 MiB at 16,384 x 128), stay in fast
memory while the grid walks every tile of every query head of the group**,
and are rounded and written once a KV head: no partial sums exist, the group
is folded without an XLA sum, and KV heads are never repeated.  Full tiles
skip the mask; partial tiles compute it from ``rule.allowed`` on iotas plus
the tile's offsets.  Under a rule whose mask is data (``rule.data``:
``masked_attention.Sparse``) every tile is partial and its mask is one more
operand, the chosen sets as bits (:func:`pack_chosen`), which :func:`chosen`
reads a lane group of keys at a time.

On the device's op line the kernel is :data:`NAME`, which
``masked_attention.OP_LINE_NAMES`` matches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NAME = "splash_mha_dkv_dq"

# What a forbidden pair's score becomes before the exponential (the library's
# ``DEFAULT_MASK_VALUE``).
_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)

# ``flags`` of :func:`tile_table`.
PARTIAL, FIRST, LAST = 1, 2, 4

# A chosen set as the kernels read it: one int32 word holds 32 keys that lie
# a lane group apart, so that a word's bit ``j`` over 128 lanes is 128
# neighbouring keys and a tile's mask is shifts and compares with no lane
# moved.  A group is the keys one row of 128 words holds.
_LANES = 128
CHOSEN_GROUP = 32 * _LANES

# The fast memory the kernel may take: the resident dk and dv (2 x 8 MiB at
# 16,384 positions), their bf16 output blocks twice (2 x 2 x 4 MiB), the
# operands' tiles twice and the [keys, queries] fp32 temporaries of a step.
# A v5e core has 128 MiB.
_VMEM_LIMIT = 96 * 2 ** 20


@functools.lru_cache(maxsize=32)
def tile_table(rule, seq_len: int, block_q: int, block_kv: int):
    """The tiles ``rule`` allows at ``seq_len`` positions, row-major:
    ``(q_tile, kv_tile, flags)``, int32 arrays with one entry a tile that
    holds an allowed pair.  ``flags`` adds up :data:`PARTIAL` (the tile also
    holds a forbidden pair: the kernel computes the mask there),
    :data:`FIRST` and :data:`LAST` (of its query tile's run).  The rule is
    evaluated a band of rows at a time; no ``[s, s]`` table is kept."""
    if seq_len % block_q or seq_len % block_kv:
        raise ValueError(f"tiles of {block_q} x {block_kv} do not divide "
                         f"{seq_len} positions")
    kv_ids = np.arange(seq_len, dtype=np.int32)[None, :]
    # A band's temporaries stay at a couple of million elements: the largest
    # that the allocator hands out again without asking the system.
    band = block_q // max(1, block_q * seq_len >> 21)
    q_tiles, kv_tiles, flags = [], [], []
    for i in range(seq_len // block_q):
        some, every = False, True
        for start in range(i * block_q, (i + 1) * block_q, band):
            q_ids = np.arange(start, start + band, dtype=np.int32)[:, None]
            allowed = np.broadcast_to(rule.allowed(q_ids, kv_ids, seq_len),
                                      (band, seq_len))
            some = some | allowed.any(axis=0).reshape(-1, block_kv).any(axis=1)
            every = every & allowed.all(axis=0).reshape(-1, block_kv) \
                .all(axis=1)
        held = np.flatnonzero(some)
        if not held.size:
            raise ValueError(f"{rule} allows query tile {i} no key at "
                             f"{seq_len} positions")
        # A mask that is data may forbid a pair of any tile.
        flag = np.where(every[held] & (not is_data(rule)), 0, PARTIAL)
        flag[0] |= FIRST
        flag[-1] |= LAST
        q_tiles.append(np.full(held.size, i))
        kv_tiles.append(held)
        flags.append(flag)
    return tuple(np.concatenate(a).astype(np.int32)
                 for a in (q_tiles, kv_tiles, flags))


def _bwd_kernel(q_tile_ref, kv_tile_ref, flags_ref, q_ref, k_ref, v_ref,
                lse_ref, di_ref, do_ref, *rest, rule, seq_len: int,
                block_kv_compute: int):
    import jax.experimental.pallas as pl

    data = is_data(rule)
    # A rule that is data brings the chosen sets' words as the last operand
    # and a scratch for their transposition as the last scratch.
    words_ref, rest = (rest[0], rest[1:]) if data else (None, rest)
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *words_t = rest
    block_q, block_kv = q_ref.shape[0], k_ref.shape[0]
    member, step = pl.program_id(2), pl.program_id(3)
    flags = flags_ref[step]
    q_start = q_tile_ref[step] * block_q
    kv_start = kv_tile_ref[step] * block_kv

    @pl.when((member == 0) & (step == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(flags & FIRST != 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if data:
        # Keys on the rows here: the words [queries, lanes] turned once a
        # group of keys, not once a tile.
        @pl.when((flags & FIRST != 0) | (kv_start % CHOSEN_GROUP == 0))
        def _():
            words_t[0][...] = words_ref[...].T

    def tile(masked: bool):
        q, do = q_ref[...], do_ref[...]
        lse, di = lse_ref[...], di_ref[...]                     # [1, bq]
        nt = (((1,), (1,)), ((), ()))
        for c in range(block_kv // block_kv_compute):
            rows = pl.ds(c * block_kv_compute, block_kv_compute)
            k, v = k_ref[rows, :], v_ref[rows, :]
            s = lax.dot_general(k, q, nt,
                                preferred_element_type=jnp.float32)
            if masked and data:
                s = jnp.where(chosen(words_t[0][...],
                                     kv_start + c * block_kv_compute,
                                     block_kv_compute, 0), s, _MASK_VALUE)
            elif masked:
                # A column of keys against a row of queries: what a rule
                # computes a position it computes on these.
                kv_ids = kv_start + c * block_kv_compute \
                    + lax.broadcasted_iota(jnp.int32,
                                           (block_kv_compute, 1), 0)
                q_ids = q_start + lax.broadcasted_iota(jnp.int32,
                                                       (1, block_q), 1)
                s = jnp.where(rule.allowed(q_ids, kv_ids, seq_len), s,
                              _MASK_VALUE)
            p = jnp.exp(s - lse)
            dv = lax.dot(p.astype(do.dtype), do,
                         preferred_element_type=jnp.float32)
            dp = lax.dot_general(v, do, nt,
                                 preferred_element_type=jnp.float32)
            ds = (dp - di) * p
            dk = lax.dot(ds.astype(q.dtype), q,
                         preferred_element_type=jnp.float32)
            at = pl.ds(pl.multiple_of(kv_start + c * block_kv_compute,
                                      block_kv_compute), block_kv_compute)
            dv_acc[at, :] += dv
            dk_acc[at, :] += dk
            dq_acc[...] += lax.dot(ds.T.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    pl.when(flags & PARTIAL != 0)(lambda: tile(True))
    pl.when(flags & PARTIAL == 0)(lambda: tile(False))

    @pl.when(flags & LAST != 0)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when((member == pl.num_programs(2) - 1)
             & (step == pl.num_programs(3) - 1))
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rule", "tiles", "interpret"))
def dq_dk_dv(q, k, v, lse, di, do, words=None, *, rule, tiles,
             interpret: bool = False):
    """The three gradients of attention under ``rule``: ``q`` (scaled)
    ``[b, h, s, d]`` and ``k`` ``[b, h_kv, s, d]``, ``v`` ``[b, h_kv, s, dv]``
    and ``do`` ``[b, h, s, dv]`` (``dv`` is ``d`` but for latent attention,
    whose keys are 192 wide over values of 128: the three products with ``q``
    and ``k`` contract or produce ``d``, the two with ``v`` and ``do``
    ``dv``), ``lse`` (the rows' log-sum-exp) and ``di`` (``sum(out * do)`` a
    row) fp32 ``[b, h, s]``; ``tiles`` is (queries, keys, keys multiplied at
    a time); ``words``: the chosen sets of a rule that is data
    (:func:`pack_chosen`).  Jitted: traced once a process and lowered once a
    program, whatever the number of layers."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    h_kv, dv = k.shape[1], v.shape[3]
    group = h // h_kv
    block_q, block_kv, block_kv_compute = tiles
    if block_kv % block_kv_compute:
        raise ValueError(f"{block_kv_compute} keys at a time do not divide a "
                         f"tile of {block_kv}")
    with jax.ensure_compile_time_eval():
        table = tuple(jnp.asarray(a)
                      for a in tile_table(rule, s, block_q, block_kv))
    data = () if words is None else (words,)

    def of_query(n, i, g, t, q_tile, kv_tile, flags):
        return n, i * group + g, q_tile[t], 0

    def of_query_row(n, i, g, t, q_tile, kv_tile, flags):
        return n, i * group + g, 0, q_tile[t]

    def of_key(n, i, g, t, q_tile, kv_tile, flags):
        return n, i, kv_tile[t], 0

    def blocks(width):
        """(a query tile, a key tile, a KV head's whole sequence) of tensors
        ``width`` wide."""
        return (pl.BlockSpec((None, None, block_q, width), of_query),
                pl.BlockSpec((None, None, block_kv, width), of_key),
                pl.BlockSpec((None, None, s, width),
                             lambda n, i, g, t, *_: (n, i, 0, 0)))

    q_block, k_block, whole_k = blocks(d)
    do_block, v_block, whole_v = blocks(dv)
    row_block = pl.BlockSpec((None, None, 1, block_q), of_query_row)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, rule=rule, seq_len=s,
                          block_kv_compute=block_kv_compute),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h_kv, group, table[0].shape[0]),
            in_specs=[q_block, k_block, v_block, row_block, row_block,
                      do_block] + [words_block(block_q, block_kv)
                                   for _ in data],
            out_specs=[q_block, whole_k, whole_v],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                            pltpu.VMEM((s, d), jnp.float32),
                            pltpu.VMEM((s, dv), jnp.float32)]
            + [pltpu.VMEM((_LANES, block_q), jnp.int32) for _ in data]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,
            vmem_limit_bytes=_VMEM_LIMIT),
        name=NAME, interpret=interpret,
    )(*table, q, k, v, lse[:, :, None, :], di[:, :, None, :], do, *data)


def is_data(rule) -> bool:
    """Whether ``rule``'s mask is an operand (``masked_attention.Sparse``)
    and not a function of two positions alone."""
    return getattr(rule, "data", False)


def pack_chosen(mask):
    """A chosen set ``mask [..., queries, keys]`` (boolean) as the kernels
    read it, int32 ``[..., queries, groups * 128]``: the keys filled up to
    whole groups of :data:`CHOSEN_GROUP`, key ``g * 4096 + j * 128 + lane``
    of a query the bit ``j`` of its word ``g * 128 + lane``."""
    *lead, s = mask.shape
    groups = -(-s // CHOSEN_GROUP)
    mask = jnp.pad(mask, [(0, 0)] * len(lead)
                   + [(0, groups * CHOSEN_GROUP - s)])
    bits = mask.reshape(*lead, groups, 32, _LANES).astype(jnp.uint32) \
        << jnp.arange(32, dtype=jnp.uint32)[:, None]
    return lax.bitcast_convert_type(
        jnp.sum(bits, axis=-2, dtype=jnp.uint32), jnp.int32) \
        .reshape(*lead, groups * _LANES)


def unpack_chosen(words, seq_len: int):
    """:func:`pack_chosen` undone: ``[..., queries, seq_len]`` boolean."""
    *lead, n = words.shape
    bits = (words.reshape(*lead, n // _LANES, 1, _LANES)
            >> jnp.arange(32, dtype=jnp.int32)[:, None]) & 1
    return bits.reshape(*lead, n * 32)[..., :seq_len] != 0


def chosen(words, first_key, keys: int, axis: int):
    """Inside a kernel: the mask of ``keys`` neighbouring keys from
    ``first_key`` on (both whole lane groups, inside one group of
    :data:`CHOSEN_GROUP`) out of the words of a tile's queries, ``[queries,
    128]`` with the keys to go along ``axis`` 1 or turned ``[128, queries]``
    with the keys along 0: one shift and compare a lane group."""
    first_bit = first_key % CHOSEN_GROUP // _LANES
    return jnp.concatenate(
        [(words >> (first_bit + j)) & 1 for j in range(keys // _LANES)],
        axis=axis) != 0


def words_block(block_q: int, block_kv: int):
    """The block of :func:`pack_chosen`'s words ``[b, s, groups * 128]`` that
    holds a tile's mask under the kernels' grid ``(sequence, KV head, query
    head of its group, tile)``: the tile's queries, their words of the
    tile's group of keys.  The index is the same for the tiles of one group
    and for every head, so a block is fetched once a group."""
    import jax.experimental.pallas as pl

    if block_kv % _LANES or CHOSEN_GROUP % block_kv:
        raise ValueError(f"a tile of {block_kv} keys does not divide a group "
                         f"of {CHOSEN_GROUP} by lane groups")
    return pl.BlockSpec(
        (None, block_q, _LANES),
        lambda n, i, g, t, q_tile, kv_tile, flags:
        (n, q_tile[t], kv_tile[t] * block_kv // CHOSEN_GROUP))
