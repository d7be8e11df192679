"""Softmax attention under a mask that is a rule, not a table.

One wrapper for every mask the models here state as a rule over (query
position, key position), and two kernels of this repo: the forward
(:func:`out_lse`, here) and the backward
(``kernels/masked_attention_bwd.py``: dq, dk and dv from a single pass).  Both
walk ``masked_attention_bwd.tile_table``'s list of the tiles the rule allows
by scalar prefetch and compute the mask from the rule in the tiles that also
hold a forbidden pair, so no empty tile is visited or fetched, a full tile is
not masked, no ``[s, s]`` table exists anywhere, and grouped KV heads are
served without repeating them.  A rule is a small hashable object with

- ``scope``: the ``jax.named_scope`` its kernel calls lie under;
- ``allowed(q_ids, kv_ids, seq_len)``: the rule itself, a boolean array, on
  numpy or JAX integers that broadcast against each other (the table of tiles
  and the kernels' mask in a partial tile come from it);
- ``allowed_pairs(seq_len)``: how many pairs it allows in one sequence;
- ``takes(seq_len)``: whether the kernels' tiles fit the rule at this length.

The rules: :class:`Causal` (key <= query; ``hvd.attn.causal``),
:class:`Window` (causal, and the key inside the last ``size`` positions:
``hvd.attn.window``), ``kernels/blockdiff_attention.py``'s
``BlockDiffusion``, and one whose mask is data, :class:`Sparse` (causal, and
the key in the query's chosen set, which a learned indexer made in the same
step: ``hvd.attn.sparse``; the sets are one more operand of both kernels, a
bit a pair, and every causal tile is visited and masked).  At 16,384 positions
and tiles of 1024 a causal layer visits 136 of 256 tiles and a window of 4096
visits 70; a window narrower than a tile gets tiles of 512 (:func:`_tiles`:
at 8192 positions a window of 512 visits 31 of them, half of each allowed).
:func:`attention` is the kernels, :func:`einsum` the same mask through a
grouped einsum (off the TPU, and for shapes the kernels do not take).  On the
device's op line the two kernels are :data:`FWD_NAME` and
``splash_mha_dkv_dq`` (:data:`OP_LINE_NAMES`) whatever the rule.

The forward lays a tile out queries on the rows and keys on the lanes
(``s = q k^T``), so that both of its products stream a tile's 1024 queries
through the MXU past stationary keys and values.  With keys on the rows, as
the backward has them, the softmax's statistics are one row and their
reductions element-wise, but the second product then either streams the 128
rows of ``v^T`` past ``p`` as the stationary operand or needs ``p``
transposed, and both are slower than the library's kernel at every width but
64 (PERF.md §6, PR 61).  The running maximum and sum of a row are kept
copied over one lane group (``[block_q, 128]``), the form the lane
reductions leave them in and the accumulator ``[block_q, dv]`` takes them in
without a broadcast.  ``p`` goes to the second product in the operands' dtype
with an fp32 sum (bf16 operands: rounded, as :func:`einsum` and the
backward's ``dv`` round it, and as the MXU rounds a float32 operand at the
default precision; float32 operands: as it is).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.timeline import scope
from . import masked_attention_bwd

# A regular expression for the kernels' names on the device's op line.
OP_LINE_NAMES = r"^splash_mha_(fwd|dq|dkv)"

# The kernels' names on the op line: the forward's starts as the library's
# did (``splash_mha_fwd_residuals``), so that what reads ``OP_LINE_NAMES``
# reads the same work, and is not the library's.
FWD_NAME = "splash_mha_fwd_out_lse"

# The forward kernel's tiles: queries x keys, and the keys multiplied at a
# time.  Measured on a v5e, the kernel alone, ms a layer (PERF.md §6, PR 61;
# the library's splash forward at its 1024 x 1024 x 512 beside them): one
# sequence of 8192, 32 heads, keys of 192 over values of 128, causal
# (JoyAI-LLM-Flash, Xing4.0) 6.31 with these, 6.52 with the keys 512 at a
# time, 6.69 with 1024, 7.42 with 128, 6.65 with queries of 512, 7.11 with
# 2048, 6.61 with keys of 2048 (the library 7.35); 16,384 positions, 28
# heads on 4 of 128, causal 13.40 (13.58 at 512; the library 14.62), under a
# window of 4096 7.12 (7.44); 2 x 8192, 32 heads on 8 of 64 8.93 (10.40);
# 8192, 16 heads on 2 of 256 3.56 (3.96).  The loop over the keys is
# unrolled: rolled (``lax.fori_loop``) it reads 7.55, 16.66, 8.78, 10.89,
# 3.93.
BLOCK = 1024
FWD_TILES = (BLOCK, BLOCK, BLOCK // 4)
# Float32 operands wider than a lane group (the float32 twins of latent
# attention, 192 wide, and of Qwen3-Next's heads of 256, which hold a bf16
# program's logits to a reference): tiles of 512, as they had under the
# library's kernel since PR 47.
FWD_TILES_WIDE_FLOAT32 = (BLOCK // 2, BLOCK // 2, BLOCK // 2)
# The fast memory the forward may take: its operands' and outputs' tiles
# twice, the statistics and the accumulator, and the [queries, keys at a
# time] fp32 temporaries of a step.  A v5e core has 128 MiB.
_FWD_VMEM_LIMIT = 64 * 2 ** 20
# A vreg's lanes: the width the forward keeps a row's maximum and sum at.
_LANES = 128
# The backward kernel's: queries x keys, and the keys multiplied at a time.
# Under the block-diffusion mask at 16,384 positions, 32 query heads on 4 KV
# heads of 128 (PERF.md, PR 44; the backward alone, ms a layer, with the
# block rule still in three clauses): these 23.96, the keys 256 or 1024 at a
# time 23.88 and 23.93, queries of 512 25.12, of 2048 30.53 (fewer, larger
# tiles hold more forbidden pairs); by codes these read 20.35.
BWD_TILES = (BLOCK, BLOCK, BLOCK // 2)
# Both kernels' under a window narrower than BLOCK, where a tile of BLOCK is
# three quarters forbidden pairs or more: half its sides, and all of a tile's
# keys multiplied at once (:func:`_tiles` has the sweep; PERF.md §6, PR 64).
NARROW_WINDOW_TILES = (BLOCK // 2, BLOCK // 2, BLOCK // 2)


@dataclasses.dataclass(frozen=True)
class Causal:
    """Query i sees key j iff ``j <= i``."""

    scope = "hvd.attn.causal"

    def allowed(self, q_ids, kv_ids, seq_len=None):
        return kv_ids <= q_ids

    def allowed_pairs(self, seq_len: int) -> int:
        return seq_len * (seq_len + 1) // 2

    def takes(self, seq_len: int) -> bool:
        return seq_len % BLOCK == 0


@dataclasses.dataclass(frozen=True)
class Window:
    """Query i sees key j iff ``j <= i`` and ``i - j < size``: itself and the
    ``size - 1`` positions before it."""

    size: int
    scope = "hvd.attn.window"

    def __post_init__(self):
        if self.size < 1:
            raise ValueError(f"a window of {self.size} positions")

    def allowed(self, q_ids, kv_ids, seq_len=None):
        return (kv_ids <= q_ids) & (q_ids - kv_ids < self.size)

    def allowed_pairs(self, seq_len: int) -> int:
        beyond = max(seq_len - self.size, 0)
        return seq_len * (seq_len + 1) // 2 - beyond * (beyond + 1) // 2

    def takes(self, seq_len: int) -> bool:
        return seq_len % BLOCK == 0


@dataclasses.dataclass(frozen=True)
class Sparse:
    """Query i sees key j iff ``j <= i`` and j is one of the ``topk`` keys
    chosen for i (all of them where ``i < topk``).  The sets are data, made
    on the device in the same step (``models/indexer.py``), and reach the
    kernels and the einsum as ``masked_attention_bwd.pack_chosen``'s words;
    a set holds no key behind its query, so the words are the whole mask.
    ``allowed`` is what the table of tiles is made from: every causal tile
    may hold a chosen pair, and each is masked (``data``)."""

    topk: int
    scope = "hvd.attn.sparse"
    data = True

    def __post_init__(self):
        if self.topk < 1:
            raise ValueError(f"{self.topk} keys chosen a query")

    def allowed(self, q_ids, kv_ids, seq_len=None):
        return kv_ids <= q_ids

    def allowed_pairs(self, seq_len: int) -> int:
        full = min(seq_len, self.topk)
        return full * (full + 1) // 2 + (seq_len - full) * self.topk

    def takes(self, seq_len: int) -> bool:
        return seq_len % BLOCK == 0


def takes(rule, seq_len: int, head_dim: int, head_dim_v=None) -> bool:
    """Whether the kernel takes this shape under ``rule``; otherwise, and off
    the TPU, the same mask goes through :func:`einsum`.  Heads of 64 go in
    as they are (LFM2-8B-A1B: 32 query heads on 8 KV heads): the kernels
    take half a lane group, and the chip's compiler pads it.
    ``head_dim_v``: the values' width where it is not the keys'; the one
    such pair taken is latent attention's 192 over 128 (JoyAI-LLM-Flash), a
    lane group and a half that the compiler pads likewise."""
    if head_dim_v not in (None, head_dim):
        return (head_dim, head_dim_v) == (192, 128) and rule.takes(seq_len)
    return (head_dim % 128 == 0 or head_dim == 64) and rule.takes(seq_len)


def _fwd_kernel(q_tile_ref, kv_tile_ref, flags_ref, q_ref, k_ref, v_ref,
                *rest, rule, seq_len: int, block_kv_compute: int):
    import jax.experimental.pallas as pl

    data = masked_attention_bwd.is_data(rule)
    # A rule that is data brings the chosen sets' words as the last operand.
    words_ref, rest = (rest[0], rest[1:]) if data else (None, rest)
    out_ref, lse_ref, m_ref, l_ref, acc_ref = rest

    PARTIAL, FIRST, LAST = (masked_attention_bwd.PARTIAL,
                            masked_attention_bwd.FIRST,
                            masked_attention_bwd.LAST)
    mask_value = masked_attention_bwd._MASK_VALUE
    block_q, block_kv, dv = q_ref.shape[0], k_ref.shape[0], v_ref.shape[1]
    step = pl.program_id(3)
    flags = flags_ref[step]
    q_start = q_tile_ref[step] * block_q
    kv_start = kv_tile_ref[step] * block_kv

    def over_lanes(stat, width):
        """A row statistic ``[block_q, _LANES]``, one value a row copied over
        a lane group, against ``width`` columns."""
        return jnp.tile(stat, (1, -(-width // _LANES)))[:, :width]

    @pl.when(flags & FIRST != 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, mask_value)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked: bool):
        q = q_ref[...]
        for c in range(block_kv // block_kv_compute):
            rows = pl.ds(c * block_kv_compute, block_kv_compute)
            k, v = k_ref[rows, :], v_ref[rows, :]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            if masked and data:
                s = jnp.where(masked_attention_bwd.chosen(
                    words_ref[...], kv_start + c * block_kv_compute,
                    block_kv_compute, 1), s, mask_value)
            elif masked:
                # A column of queries against a row of keys: what a rule
                # computes a position it computes on these.
                q_ids = q_start + lax.broadcasted_iota(jnp.int32,
                                                       (block_q, 1), 0)
                kv_ids = kv_start + c * block_kv_compute \
                    + lax.broadcasted_iota(jnp.int32,
                                           (1, block_kv_compute), 1)
                s = jnp.where(rule.allowed(q_ids, kv_ids, seq_len), s,
                              mask_value)
            m_prev, l_prev = m_ref[...], l_ref[...]
            # A row that sees no key of its tile's first chunks keeps the
            # mask's value as its maximum there and sums ones: alpha is
            # zero at its first allowed key and takes them out again.
            m_next = jnp.maximum(m_prev, s.max(axis=1)[:, None])
            p = jnp.exp(s - over_lanes(m_next, block_kv_compute))
            alpha = jnp.exp(m_prev - m_next)
            m_ref[...] = m_next
            l_ref[...] = alpha * l_prev + lax.broadcast_in_dim(
                p.sum(axis=1), l_prev.shape, (0,))
            acc_ref[...] = over_lanes(alpha, dv) * acc_ref[...] + lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    pl.when(flags & PARTIAL != 0)(lambda: tile(True))
    pl.when(flags & PARTIAL == 0)(lambda: tile(False))

    @pl.when(flags & LAST != 0)
    def _():
        l = l_ref[...]
        out_ref[...] = (acc_ref[...] * over_lanes(1.0 / l, dv)) \
            .astype(out_ref.dtype)
        # One row of the statistics' transposition: the log-sum-exp leaves
        # as the backward reads it, positions on the lanes.
        lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1]


@functools.partial(jax.jit, static_argnames=("rule", "tiles", "interpret"))
def out_lse(q, k, v, words=None, *, rule, tiles, interpret: bool = False):
    """Attention under ``rule`` and its rows' log-sum-exp: ``q`` (scaled)
    ``[b, h, s, d]``, ``k`` ``[b, h_kv, s, d]`` and ``v`` ``[b, h_kv, s, dv]``
    give ``out [b, h, s, dv]`` in ``q``'s dtype and ``lse [b, h, s]`` fp32;
    ``tiles`` is (queries, keys, keys multiplied at a time); ``words``: the
    chosen sets of a rule that is data (``pack_chosen``'s, ``[b, s, groups *
    128]``).  Jitted: traced once a process and lowered once a program,
    whatever the number of layers."""
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
        table = tuple(jnp.asarray(a) for a in masked_attention_bwd.tile_table(
            rule, s, block_q, block_kv))
    data = () if words is None else (words,)

    def of_query(n, i, g, t, q_tile, kv_tile, flags):
        return n, i * group + g, q_tile[t], 0

    def of_query_row(n, i, g, t, q_tile, kv_tile, flags):
        return n, i * group + g, 0, q_tile[t]

    def of_key(n, i, g, t, q_tile, kv_tile, flags):
        return n, i, kv_tile[t], 0

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, rule=rule, seq_len=s,
                          block_kv_compute=block_kv_compute),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h_kv, group, table[0].shape[0]),
            in_specs=[pl.BlockSpec((None, None, block_q, d), of_query),
                      pl.BlockSpec((None, None, block_kv, d), of_key),
                      pl.BlockSpec((None, None, block_kv, dv), of_key)]
            + [masked_attention_bwd.words_block(block_q, block_kv)
               for _ in data],
            out_specs=[pl.BlockSpec((None, None, block_q, dv), of_query),
                       pl.BlockSpec((None, None, 1, block_q), of_query_row)],
            scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32),
                            pltpu.VMEM((block_q, _LANES), jnp.float32),
                            pltpu.VMEM((block_q, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",),
            vmem_limit_bytes=_FWD_VMEM_LIMIT),
        name=FWD_NAME, interpret=interpret,
    )(*table, q, k, v, *data)
    return out, lse[:, :, 0, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attend(q, k, v, rule, interpret):
    """``[b, h, s, d]`` in and out, ``q`` scaled."""
    return _attend_fwd(q, k, v, rule, interpret)[0]


def _attend_fwd(q, k, v, rule, interpret, words=None):
    with scope(rule.scope.removeprefix("hvd.")):
        out, logsumexp = out_lse(q, k, v, words, rule=rule,
                                 tiles=_tiles(rule, q)[0],
                                 interpret=interpret)
    return out, (words, q, k, v, out, logsumexp)


def _attend_bwd(rule, interpret, kept, do):
    words, q, k, v, out, logsumexp = kept
    with scope(rule.scope.removeprefix("hvd.")):
        di = jnp.einsum("bhsd,bhsd->bhs", out.astype(jnp.float32),
                        do.astype(jnp.float32))
        return tuple(masked_attention_bwd.dq_dk_dv(
            q, k, v, logsumexp, di, do, words, rule=rule,
            tiles=_tiles(rule, q)[1], interpret=interpret))


_attend.defvjp(_attend_fwd, _attend_bwd)


def attention(q, k, v, rule, *, interpret: bool = False, scale=None):
    """Softmax attention of ``q [b, s, h, d]`` on ``k [b, s, h_kv, d]`` and
    ``v [b, s, h_kv, dv]`` under ``rule``, scores scaled by ``d ** -0.5``
    (by ``scale`` where one is given: Granite's ``attention_multiplier``);
    ``h_kv`` divides ``h`` and KV head ``j`` serves query heads ``j*h/h_kv``
    to ``(j+1)*h/h_kv - 1``.  Returns ``[b, s, h, dv]``.  Differentiable: the
    forward is :func:`out_lse`, the backward
    ``kernels/masked_attention_bwd.py``'s kernel, both of which take ``q``
    already scaled and so know nothing of the scale."""
    _, s, h, d = q.shape
    if not takes(rule, s, d, v.shape[3]):
        raise ValueError(f"no kernel under {rule} for {s} positions, head "
                         f"width {d} over values of {v.shape[3]}")
    hsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
    # The copies into and out of the kernels' [heads, positions, width]
    # layout apart from the kernels, which alone lie under the rule's scope.
    if scale is None:
        scale = d ** -0.5
    with scope("attn.layout"):
        q, k, v = hsd(q * jnp.asarray(scale, q.dtype)), hsd(k), hsd(v)
    out = _attend(q, k, v, rule, interpret)
    with scope("attn.layout"):
        return out.transpose(0, 2, 1, 3)


def attention_hsd(q, k, v, rule):
    """:func:`attention` for a caller that builds its operands in the
    kernels' layout: ``q [b, h, s, d]`` already scaled by ``d ** -0.5``,
    ``k [b, h_kv, s, d]`` and ``v [b, h_kv, s, dv]``.  Returns
    ``[b, h, s, dv]``: no copy on either side of the kernels."""
    _, _, s, d = q.shape
    if not takes(rule, s, d, v.shape[3]):
        raise ValueError(f"no kernel under {rule} for {s} positions, head "
                         f"width {d} over values of {v.shape[3]}")
    return _attend(q, k, v, rule, False)


def _probabilities(scores, rule, dtype, words=None):
    """The softmax of ``scores [b, ..., s, s]`` over the keys ``rule``
    allows, the mask from iota comparisons, or from ``words [b, s, ...]``
    under a rule that is data."""
    s = scores.shape[-1]
    if words is not None:
        mask = masked_attention_bwd.unpack_chosen(words, s)
        mask = mask.reshape(mask.shape[:1] + (1,) * (scores.ndim - 3)
                            + (s, s))
    else:
        mask = rule.allowed(lax.broadcasted_iota(jnp.int32, (s, s), 0),
                            lax.broadcasted_iota(jnp.int32, (s, s), 1), s)
        mask = mask[(None,) * (scores.ndim - 2)]
    scores = jnp.where(mask, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1).astype(dtype)


def einsum(q, k, v, rule, scale=None, words=None):
    """:func:`attention` through the einsum, KV heads grouped, the mask from
    iota comparisons (from ``words`` under a rule that is data): below the
    kernel's smallest shape, and off the TPU."""
    b, s, h, dh = q.shape
    h_kv = k.shape[2]
    if scale is None:
        scale = dh ** -0.5
    with scope("attn.einsum"):
        q = q.reshape(b, s, h_kv, h // h_kv, dh)
        scores = jnp.einsum("bqngd,bknd->bngqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        return jnp.einsum("bngqk,bknd->bqngd",
                          _probabilities(scores, rule, q.dtype, words), v) \
            .reshape(b, s, h, v.shape[3])


def einsum_hsd(q, k, v, rule):
    """:func:`attention_hsd` through the einsum (``h_kv = h``): off the TPU,
    and for shapes the kernel does not take.  ``q`` comes scaled, as the
    kernels take it, where :func:`einsum` scales the fp32 scores: in bf16 the
    two round at different points and agree to bf16's rounding, in float32
    to float32's."""
    with scope("attn.einsum"):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          _probabilities(scores, rule, q.dtype), v)


# Down here, below the kernels' call sites, whose lines the compile cache's
# keys of every program with these kernels hold (ROADMAP.md Q1.9 (f)).
# Measured on a v5e, a kernel alone, ms a call (PERF.md §6, PR 64;
# ``benchmarks/results/laguna_attention_sweep_pr64.jsonl``): 8192 positions,
# 72 heads on 8 of 128 inside a window of 512 (Laguna-S-2.1), forward 4.17
# with FWD_TILES (15 tiles, 26% of their pairs allowed), 2.73 with
# NARROW_WINDOW_TILES (31 tiles, 50%), 3.44 with their keys 256 at a time,
# 3.54 at 1024 x 512 x 512 (23, 34%), 3.62 at 256 x 512 x 512, 4.07 at 512 x
# 256 x 256 (62, 50%), 4.76 at 256 x 256 x 256 (93 of which 31 are full,
# 67%), 12.27 at 128 x 128 x 128 (310, 80%); backward 8.13 with BWD_TILES,
# 4.99 with NARROW_WINDOW_TILES, 5.16, 6.61, 6.74, 6.31, 7.77 and 15.29 at
# those: a grid step costs what it costs however small its tile, so tiles
# finer than the band lose more than their area saves.  Under windows of 256
# and 128 the same: forward 2.72 with these against 3.39 at 256 x 256 x 256
# and 5.37 at 128 x 128 x 128, backward 4.98 against 5.34 and 6.41.
def _tiles(rule, q):
    """(the forward kernel's tiles, the backward's) under ``rule`` for
    operands like ``q [..., d]``: :data:`NARROW_WINDOW_TILES` for both where
    the rule is a :class:`Window` narrower than a tile (float32 operands
    wider than a lane group ask for nothing finer), else :data:`FWD_TILES`
    (those operands: :data:`FWD_TILES_WIDE_FLOAT32`) and :data:`BWD_TILES`."""
    if isinstance(rule, Window) and rule.size < BLOCK:
        return NARROW_WINDOW_TILES, NARROW_WINDOW_TILES
    wide_float32 = q.dtype.itemsize > 2 and q.shape[-1] > 128
    return FWD_TILES_WIDE_FLOAT32 if wide_float32 else FWD_TILES, BWD_TILES


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attend_chosen(q, k, v, words, rule, interpret):
    """:func:`_attend` under a rule that is data: ``words`` the chosen sets,
    and the rows' log-sum-exp handed out beside the output."""
    return _attend_chosen_fwd(q, k, v, words, rule, interpret)[0]


def _attend_chosen_fwd(q, k, v, words, rule, interpret):
    out, kept = _attend_fwd(q, k, v, rule, interpret, words)
    return (out, kept[-1]), kept


def _attend_chosen_bwd(rule, interpret, kept, cotangents):
    # The log-sum-exp leaves as a constant (:func:`attention_lse_hsd`).
    return *_attend_bwd(rule, interpret, kept, cotangents[0]), None


_attend_chosen.defvjp(_attend_chosen_fwd, _attend_chosen_bwd)


def attention_lse_hsd(q, k, v, rule, words, interpret: bool = False):
    """:func:`attention_hsd` under a rule that is data (:class:`Sparse`):
    ``words`` the chosen sets, ``pack_chosen``'s ``[b, s, groups * 128]``.
    Returns ``(out [b, h, s, dv], lse [b, h, s])``, the second the rows'
    log-sum-exp over their chosen keys in fp32 and cut from the graph: what
    reads it (the indexer's target, ``models/indexer.py``) is a constant of
    the step."""
    _, _, s, d = q.shape
    if not takes(rule, s, d, v.shape[3]):
        raise ValueError(f"no kernel under {rule} for {s} positions, head "
                         f"width {d} over values of {v.shape[3]}")
    out, lse = _attend_chosen(q, k, v, words, rule, interpret)
    return out, lax.stop_gradient(lse)
