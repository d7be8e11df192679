"""DeepSeek Sparse Attention's lightning indexer on the device: the exact
choice of the ``topk`` best keys a query, and the indexer's loss with its
gradient, each in one kernel in which no ``[s, s]`` array leaves fast memory.

The indexer (``models/indexer.py``) scores every causal pair from ``H`` small
heads on one shared key,

    I[t, s] = sum_j w[t, j] * relu(q_j[t] . k[s])          (fp32, s <= t)

and the attention of its layer runs over the ``topk`` keys with the largest
``I[t, .]`` (``kernels/masked_attention.py::Sparse``).  At 16,384 positions
``I`` is 1 GiB in fp32 a layer and the choice a sort of 16,384 rows of up to
16,384; the two kernels here keep both in fast memory.

:func:`choose` (``hvd_dsa_choose`` on the op line): a grid step takes
:data:`ROWS` queries, multiplies their 16 heads with the keys up to the last
of them, :data:`KEYS` at a time (bf16 operands, fp32 sums), and keeps the
scores of the block ``[ROWS, s]`` in scratch as integers that order as the
floats do.  The ``k``-th largest of a row (``k = min(topk, t + 1)``) is then
found **exactly** by counting: passes over the block, one a bit from the sign
down, each asking of every row how many of its scores reach the threshold
built so far with this bit set.  A pass's own count says when a row is
settled (exactly ``k`` of its scores reach its threshold: they are its set),
and the passes end when every row of the block is, after 32 at the most.
Only a block left with a row that more than ``k`` reach (scores equal at the
threshold) runs the passes that break ties: of those scores the lower
positions are taken, found by the same counting over the bits of a position
(``lax.top_k``'s order).  The chosen set leaves as
``masked_attention_bwd.pack_chosen``'s words, a bit a pair, beside the
log-sum-exp of ``I[t, .]`` over the set (what :func:`kl_sum` normalises by)
and, a block, the rows that tied and the passes it ran.

:func:`kl_sum` (``hvd_dsa_loss``): the sum over the queries of ``KL(p[t, .]
|| softmax over the set of I[t, .])``, ``p`` the layer's own attention
summed over its heads and divided by their number, from the attention's q, k
and log-sum-exp; one pass over the causal tiles (``tile_table`` under
``Causal``) that rebuilds ``p`` and ``I`` a tile, adds up the divergence and,
in the same pass, its gradient to ``q_j``, ``k`` and ``w`` (``dI = softmax -
p`` over the set, through the ReLU and the weights), keys on the rows as the
attention's backward kernel lays a tile out.  The products bind the kernel
(the 64-wide ones fill half the MXU), so a head's scores are multiplied once
a tile and kept in scratch behind the ReLU for the gradient; there a head
costs a compare, a select and a cast a pair: its weight leaves the tile
(``dk += g_j (w_j q_j)``, the weighted queries made once a tile of queries;
``dq_j = w_j sum_s g_j k``, scaled at the tile's last step) and the queries'
sum is kept with the queries on the lanes (``k^T g_j``), so ``g_j`` is never
transposed.  ``p`` is a constant of the step
(the target is cut from the graph), so the backward pass of the step only
scales what the forward pass wrote.

Both are jitted: traced once a process and shape whatever the number of
layers.  Off the TPU, and for shapes :func:`takes` refuses,
``models/indexer.py`` runs the same rule in ``jax.numpy``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .masked_attention import BLOCK, Causal
from .masked_attention_bwd import (
    CHOSEN_GROUP,
    FIRST,
    LAST,
    chosen,
    tile_table,
)

CHOOSE_NAME = "hvd_dsa_choose"
LOSS_NAME = "hvd_dsa_loss"
# A regular expression for the kernels' names on the device's op line.
OP_LINE_NAMES = r"^hvd_dsa_"

_LANES = 128
# :func:`choose`: queries a grid step, and keys multiplied at a time (the
# configuration's ``q_chunk_size`` and ``kv_chunk_size`` are blocks of the
# computation and change no result: these are the kernel's).
ROWS = 256
KEYS = 1024
# :func:`kl_sum`'s tiles, queries x keys.
LOSS_TILES = (256, 512)
_VMEM_LIMIT = 100 * 2 ** 20
_INT_MIN = int(np.iinfo(np.int32).min)


def takes(seq_len: int, head_dim: int) -> bool:
    """Whether the kernels take an indexer with heads of ``head_dim`` at
    ``seq_len`` positions: on a TPU, whole tiles of the attention kernels'
    (which read the words), heads of half a lane group or a whole one."""
    return (jax.default_backend() == "tpu" and seq_len % BLOCK == 0
            and head_dim in (_LANES // 2, _LANES))


def passes_at_most(seq_len: int) -> int:
    """The counting passes of a block of :func:`choose` that ends none early
    and breaks ties: one for the sign and 31 for the bits of a score, one for
    the scores above the threshold and one a bit of a position."""
    return 32 + 1 + _position_bits(seq_len)


def _position_bits(seq_len: int) -> int:
    return max(1, (seq_len - 1).bit_length())


def _ordered(x):
    """fp32 as int32 that compare as the floats do (and back: the map is its
    own inverse)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _choose_kernel(q_ref, k_ref, w_ref, words_ref, lse_ref, blocks_ref,
                   keys_ref, *, topk: int, seq_len: int, rows: int, keys: int):
    import jax.experimental.pallas as pl

    heads = q_ref.shape[0]
    q_start = pl.program_id(1) * rows
    # The keys up to this block's last query, in whole chunks.
    chunks = (q_start + rows + keys - 1) // keys
    q_ids = q_start + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    wanted = jnp.minimum(q_ids + 1, topk)                     # [rows, 1]
    w = w_ref[...].astype(jnp.float32)

    def columns(c):
        return pl.ds(pl.multiple_of(c * keys, keys), keys)

    def ids_of(c):
        return c * keys + lax.broadcasted_iota(jnp.int32, (1, keys), 1)

    def lanes_max(x):
        out = x[:, :_LANES]
        for g in range(1, keys // _LANES):
            out = jnp.maximum(out, x[:, g * _LANES:(g + 1) * _LANES])
        return out

    def score_chunk(c, best):
        k = k_ref[columns(c), :]
        total = jnp.zeros((rows, keys), jnp.float32)
        for j in range(heads):
            a = lax.dot_general(q_ref[j], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            total = total + w[:, j:j + 1] * jnp.maximum(a, 0.0)
        total = jnp.where(ids_of(c) <= q_ids, total, -jnp.inf)
        keys_ref[:, columns(c)] = _ordered(total)
        return jnp.maximum(best, lanes_max(total))

    best = lax.fori_loop(0, chunks, score_chunk,
                         jnp.full((rows, _LANES), -jnp.inf, jnp.float32))
    best = jnp.max(best, axis=1, keepdims=True)               # [rows, 1]

    def count(test):
        """How many of a row's scores ``test(scores, ids)`` accepts."""
        def body(c, acc):
            hit = test(keys_ref[:, columns(c)], ids_of(c)).astype(jnp.int32)
            for g in range(keys // _LANES):
                acc = acc + hit[:, g * _LANES:(g + 1) * _LANES]
            return acc

        acc = lax.fori_loop(0, chunks, body,
                            jnp.zeros((rows, _LANES), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def inexact(reached):
        """How many rows more than `wanted` scores reach."""
        return jnp.sum((reached != wanted).astype(jnp.int32))

    # The wanted-th largest score of a row, bit by bit from the sign down:
    # the largest threshold that at least `wanted` scores reach, and beside
    # it how many reach it (the accepted pass's own count; at the start
    # every score of the block does).  A row that exactly `wanted` reach is
    # settled, its set is `x >= threshold` whatever the bits below: the
    # passes end once every row of the block is.
    first = count(lambda x, ids: x >= 0)
    reach = first >= wanted
    threshold = jnp.where(reach, 0, _INT_MIN).astype(jnp.int32)
    reached = jnp.where(reach, first, chunks * keys)

    def score_bit(carry):
        i, threshold, reached, _ = carry
        with_bit = threshold | jnp.left_shift(jnp.int32(1), 30 - i)
        n = count(lambda x, ids: x >= with_bit)
        reach = n >= wanted
        reached = jnp.where(reach, n, reached)
        return (i + 1, jnp.where(reach, with_bit, threshold), reached,
                inexact(reached))

    bit_passes, threshold, reached, tied = lax.while_loop(
        lambda carry: (carry[0] < 31) & (carry[3] > 0), score_bit,
        (jnp.int32(0), threshold, reached, inexact(reached)))
    bits = _position_bits(seq_len)

    def break_ties():
        # Of the scores equal to the threshold, the lower positions: the
        # last position taken, by the bits of a position.
        above = count(lambda x, ids: x > threshold)
        ties_wanted = wanted - above

        def position_bit(i, last):
            with_bit = last | jnp.left_shift(jnp.int32(1), bits - 1 - i)
            below = count(lambda x, ids: (x == threshold) & (ids < with_bit))
            return jnp.where(below < ties_wanted, with_bit, last)

        return lax.fori_loop(0, bits, position_bit,
                             jnp.zeros((rows, 1), jnp.int32))

    # Only a block with a row that is not settled breaks ties.
    last = lax.cond(tied > 0, break_ties,
                    lambda: jnp.full((rows, 1), seq_len - 1, jnp.int32))
    passes = 1 + bit_passes + jnp.where(tied > 0, 1 + bits, 0)
    blocks_ref[...] = jnp.where(
        lax.broadcasted_iota(jnp.int32, blocks_ref.shape, 1) == 0, tied,
        passes)

    # The set as words, a lane group of keys a bit, and its log-sum-exp.
    total = jnp.zeros((rows, _LANES), jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    for g in range(words_ref.shape[1] // _LANES):
        word = jnp.zeros((rows, _LANES), jnp.int32)
        for j in range(32):
            first = g * CHOSEN_GROUP + j * _LANES
            if first >= seq_len:
                break
            x = keys_ref[:, first:first + _LANES]
            ids = first + lane
            # Causal: what lies beyond the chunks scored is no score.
            taken = ((x > threshold) | ((x == threshold) & (ids <= last))) \
                & (ids <= q_ids)
            word = word | (taken.astype(jnp.int32) << j)
            score = lax.bitcast_convert_type(_ordered(x), jnp.float32)
            total = total + jnp.where(taken, jnp.exp(score - best), 0.0)
        words_ref[:, g * _LANES:(g + 1) * _LANES] = word
    lse = best + jnp.log(jnp.sum(total, axis=1, keepdims=True))
    lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


@functools.partial(jax.jit, static_argnames=("topk", "interpret", "rows",
                                             "keys"))
def choose(q_i, k_i, w, *, topk: int, interpret: bool = False,
           rows: int = ROWS, keys: int = KEYS):
    """The chosen sets of every query: ``q_i [b, H, s, d]`` and ``k_i [b, s,
    d]`` (both turned by their positions) and ``w [b, s, H]`` give ``(words
    [b, s, groups * 128]`` int32, ``pack_chosen``'s, ``lse [b, s]`` fp32, the
    log-sum-exp of a query's scores over its set, ``blocks [b, s // rows,
    2]`` int32, of a block of ``rows`` queries how many rows had more scores
    at their threshold than they take (none: the block broke no ties) and
    how many counting passes it ran``)``.  Query ``t``'s set is the
    ``min(topk, t + 1)`` keys ``s <= t`` of the largest ``I[t, s]``, of equal
    scores the lower position."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, s, d = q_i.shape
    rows, keys = min(rows, s), min(keys, s)
    if s % rows or s % keys or keys % _LANES:
        raise ValueError(f"blocks of {rows} queries and {keys} keys do not "
                         f"divide {s} positions by lane groups")
    width = -(-s // CHOSEN_GROUP) * _LANES
    words, lse, blocks = pl.pallas_call(
        functools.partial(_choose_kernel, topk=topk, seq_len=s, rows=rows,
                          keys=keys),
        grid=(b, s // rows),
        in_specs=[pl.BlockSpec((None, heads, rows, d),
                               lambda n, i: (n, 0, i, 0)),
                  pl.BlockSpec((None, s, d), lambda n, i: (n, 0, 0)),
                  pl.BlockSpec((None, rows, heads), lambda n, i: (n, i, 0))],
        out_specs=[pl.BlockSpec((None, rows, width), lambda n, i: (n, i, 0)),
                   pl.BlockSpec((None, rows, _LANES),
                                lambda n, i: (n, i, 0)),
                   pl.BlockSpec((None, None, 8, _LANES),
                                lambda n, i: (n, i, 0, 0))],
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)],
        out_shape=[jax.ShapeDtypeStruct((b, s, width), jnp.int32),
                   jax.ShapeDtypeStruct((b, s, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, s // rows, 8, _LANES),
                                        jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=CHOOSE_NAME, interpret=interpret,
    )(q_i, k_i, w)
    return words, lse[:, :, 0], blocks[:, :, 0, :2]


def _loss_kernel(q_tile_ref, kv_tile_ref, flags_ref, q_ref, k_ref, lse_ref,
                 qi_ref, ki_ref, wt_ref, lse_i_ref, words_ref, kl_ref,
                 dqi_ref, dki_ref, dwt_ref, words_t, kl_acc, qw, kept, g_acc,
                 dki_acc, dwt_acc):
    import jax.experimental.pallas as pl

    heads, block_q = q_ref.shape[0], q_ref.shape[1]
    group = heads // k_ref.shape[0]
    block_kv, i_heads = ki_ref.shape[0], qi_ref.shape[0]
    step = pl.program_id(1)
    flags = flags_ref[step]
    kv_start = kv_tile_ref[step] * block_kv
    nt = (((1,), (1,)), ((), ()))
    wt = wt_ref[...].astype(jnp.float32)                  # [H, queries]

    @pl.when(step == 0)
    def _():
        dki_acc[...] = jnp.zeros_like(dki_acc)

    @pl.when(flags & FIRST != 0)
    def _():
        kl_acc[...] = jnp.zeros_like(kl_acc)
        g_acc[...] = jnp.zeros_like(g_acc)
        dwt_acc[...] = jnp.zeros_like(dwt_acc)
        # A head's weight times its queries, once a tile of queries: what
        # the keys' gradient multiplies.
        for j in range(i_heads):
            turned = qi_ref[j].astype(jnp.float32).T      # [d, queries]
            qw[j] = (turned * wt[j:j + 1]).T.astype(qw.dtype)

    @pl.when((flags & FIRST != 0) | (kv_start % CHOSEN_GROUP == 0))
    def _():
        words_t[...] = words_ref[...].T

    mask = chosen(words_t[...], kv_start, block_kv, 0)    # [keys, queries]
    # The target: the attention's own probabilities, a head at a time.
    p = jnp.zeros((block_kv, block_q), jnp.float32)
    for h in range(heads):
        s = lax.dot_general(k_ref[h // group], q_ref[h], nt,
                            preferred_element_type=jnp.float32)
        p = p + jnp.exp(s - lse_ref[h])
    p = jnp.where(mask, p * (1.0 / heads), 0.0)
    ki = ki_ref[...]
    # The heads' scores behind the ReLU stay in scratch for the gradient.
    scores = jnp.zeros((block_kv, block_q), jnp.float32)
    for j in range(i_heads):
        a = jnp.maximum(lax.dot_general(
            ki, qi_ref[j], nt, preferred_element_type=jnp.float32), 0.0)
        kept[j] = a
        scores = scores + wt[j:j + 1] * a
    log_q = scores - lse_i_ref[...]
    kl_acc[...] += jnp.sum(
        jnp.where(p > 0.0, p * (jnp.log(jnp.where(p > 0.0, p, 1.0)) - log_q),
                  0.0), axis=0, keepdims=True)
    d_scores = jnp.where(mask, jnp.exp(log_q) - p, 0.0)
    at = pl.ds(pl.multiple_of(kv_start, block_kv), block_kv)
    ki_t = ki.T                                           # [d, keys]
    for j in range(i_heads):
        # Through the ReLU alone: the head's weight stands in `qw` for the
        # keys and waits for the queries' sum at the tile's last step.
        a = kept[j]
        g = jnp.where(a > 0.0, d_scores, 0.0)
        dwt_acc[j:j + 1, :] += jnp.sum(g * a, axis=0, keepdims=True)
        g = g.astype(ki.dtype)
        dki_acc[at, :] += lax.dot(g, qw[j],
                                  preferred_element_type=jnp.float32)
        # Keys contracted with the queries on the lanes as they lie: no
        # transposition of `g`.
        g_acc[j] += lax.dot(ki_t, g, preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def _():
        kl_ref[...] = kl_acc[...]
        dwt_ref[...] = dwt_acc[...]
        for j in range(i_heads):
            dqi_ref[j] = (g_acc[j] * wt[j:j + 1]).T.astype(dqi_ref.dtype)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        dki_ref[...] = dki_acc[...].astype(dki_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _kl_and_gradients(q_i, k_i, w, words, lse_i, q, k, lse, *, tiles,
                      interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, i_heads, s, d_i = q_i.shape
    heads, d = q.shape[1], q.shape[3]
    block_q, block_kv = min(tiles[0], s), min(tiles[1], s)
    with jax.ensure_compile_time_eval():
        table = tuple(jnp.asarray(a) for a in tile_table(
            Causal(), s, block_q, block_kv))
    if block_kv % _LANES or CHOSEN_GROUP % block_kv:
        raise ValueError(f"a tile of {block_kv} keys does not divide a group "
                         f"of {CHOSEN_GROUP} by lane groups")

    def heads_of_queries(n, t, q_tile, kv_tile, flags):
        return n, 0, q_tile[t], 0

    def heads_of_keys(n, t, q_tile, kv_tile, flags):
        return n, 0, kv_tile[t], 0

    def rows_of_queries(n, t, q_tile, kv_tile, flags):
        return n, 0, q_tile[t]

    kl, dq_i, dk_i, dwt = pl.pallas_call(
        _loss_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, table[0].shape[0]),
            in_specs=[
                pl.BlockSpec((None, heads, block_q, d), heads_of_queries),
                pl.BlockSpec((None, k.shape[1], block_kv, d), heads_of_keys),
                pl.BlockSpec((None, heads, 1, block_q),
                             lambda n, t, q_tile, *_: (n, 0, 0, q_tile[t])),
                pl.BlockSpec((None, i_heads, block_q, d_i),
                             heads_of_queries),
                pl.BlockSpec((None, block_kv, d_i),
                             lambda n, t, q_tile, kv_tile, _:
                             (n, kv_tile[t], 0)),
                pl.BlockSpec((None, i_heads, block_q), rows_of_queries),
                pl.BlockSpec((None, 1, block_q), rows_of_queries),
                pl.BlockSpec((None, block_q, _LANES),
                             lambda n, t, q_tile, kv_tile, _:
                             (n, q_tile[t],
                              kv_tile[t] * block_kv // CHOSEN_GROUP))],
            out_specs=[
                pl.BlockSpec((None, 1, block_q), rows_of_queries),
                pl.BlockSpec((None, i_heads, block_q, d_i),
                             heads_of_queries),
                pl.BlockSpec((None, s, d_i), lambda n, t, *_: (n, 0, 0)),
                pl.BlockSpec((None, i_heads, block_q), rows_of_queries)],
            scratch_shapes=[
                pltpu.VMEM((_LANES, block_q), jnp.int32),
                pltpu.VMEM((1, block_q), jnp.float32),
                pltpu.VMEM((i_heads, block_q, d_i), q_i.dtype),
                pltpu.VMEM((i_heads, block_kv, block_q), jnp.float32),
                pltpu.VMEM((i_heads, d_i, block_q), jnp.float32),
                pltpu.VMEM((s, d_i), jnp.float32),
                pltpu.VMEM((i_heads, block_q), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, 1, s), jnp.float32),
                   jax.ShapeDtypeStruct(q_i.shape, q_i.dtype),
                   jax.ShapeDtypeStruct(k_i.shape, k_i.dtype),
                   jax.ShapeDtypeStruct((b, i_heads, s), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=LOSS_NAME, interpret=interpret,
    )(*table, q, k, lse[:, :, None, :], q_i, k_i, w.transpose(0, 2, 1),
      lse_i[:, None, :], words)
    return jnp.sum(kl), (dq_i, dk_i, dwt.transpose(0, 2, 1).astype(w.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _kl_sum(q_i, k_i, w, words, lse_i, q, k, lse, tiles, interpret):
    return _kl_and_gradients(q_i, k_i, w, words, lse_i, q, k, lse,
                             tiles=tiles, interpret=interpret)[0]


def _kl_sum_fwd(q_i, k_i, w, words, lse_i, q, k, lse, tiles, interpret):
    return _kl_and_gradients(q_i, k_i, w, words, lse_i, q, k, lse,
                             tiles=tiles, interpret=interpret)


def _kl_sum_bwd(tiles, interpret, gradients, g):
    # The sets, the target's operands and the log-sum-exp (whose part of
    # the gradient `softmax - p` holds) are constants of the step.
    return tuple((x.astype(jnp.float32) * g).astype(x.dtype)
                 for x in gradients) + (None,) * 5


_kl_sum.defvjp(_kl_sum_fwd, _kl_sum_bwd)


def kl_sum(q_i, k_i, w, words, lse_i, q, k, lse, *, tiles=LOSS_TILES,
           interpret: bool = False):
    """``sum over the sequences and queries of KL(p[t, .] || softmax over
    the set of I[t, .])``, a scalar in fp32, differentiable in ``q_i [b, H,
    s, d]``, ``k_i [b, s, d]`` and ``w [b, s, H]`` alone.  ``words`` and
    ``lse_i`` are :func:`choose`'s; ``q [b, h, s, dh]`` (scaled), ``k [b,
    h_kv, s, dh]`` and ``lse [b, h, s]`` the attention's operands and its
    rows' log-sum-exp over their sets, which make the target ``p[t, s] = mean
    over the heads of exp(q_h[t] . k[s] - lse_h[t])`` and are constants."""
    return _kl_sum(q_i, k_i, w, words, lax.stop_gradient(lse_i),
                   lax.stop_gradient(q), lax.stop_gradient(k),
                   lax.stop_gradient(lse), tiles, interpret)
