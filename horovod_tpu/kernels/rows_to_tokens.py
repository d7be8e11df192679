"""The rows of a chunk of sorted places added up by token, in one pass.

``parallel/moe.py::moe_ffn(held=)`` sorts the routed slots by held expert and
multiplies the rows of one chunk of the sorted places (``[cap, d]``, bf16).
Twice a layer the chunk's rows go back to token order: ``y[t] = sum of w[p] *
rows[p]`` over the places p whose slot is token t's (``_combine``, with the
router's fp32 weights; ``_spread``'s cotangent, without).  XLA does that as a
scatter-add of fp32 rows into ``[tokens, d]`` and charges by the place, used
or not, and by the target (24,576 places into 16,384 tokens of 2560: 9.0 ms
on a v5e; 32,768 into 2048: 3.7; ``PERF.md`` §6, PR 35).  This kernel reads
each row once, in bf16, and writes each token's sum once (0.45-0.75 ms).

**What it leans on** (``_held_chunk`` states all of it, nothing is checked at
run time):

- the chunk's used places are runs, one a held expert, of lengths ``group``,
  in the order of the experts; the unused places lie behind the last run;
- ``jnp.argsort`` is stable, so inside a run the slots ascend, and a token
  picks k *distinct* experts, so inside a run the tokens ascend and none
  repeats;
- an unused place's token is ``tokens``, one past the last, and its row holds
  whatever the grouped product left there (it is never multiplied).

So a tile of :data:`TILE` tokens owns, in each run, one contiguous stretch of
places, and the stretches' ends are a count of places by (run, tile).

**How it goes.**  :func:`_plan` (XLA, a few small fusions) lists for every
tile the :data:`PIECE`-row pieces of ``rows`` that cover its stretches, tile
after tile, each tile's list padded to whole chunks of :data:`CHUNK` rows,
and says for each piece which of its rows are the stretch's.  The kernel is a
grid over the tiles.  For each chunk of a tile it copies the pieces HBM to
VMEM, side by side (the next chunk's copies are in flight meanwhile, across
tiles too), with each piece's tokens and weights, which arrive spread over
the lanes (``[cap, 128]``, so that a place's value lies along a sublane like
its row); places a row in the tile's fp32 block by one product on the MXU,
``O[t, p] = w[p]`` where place p holds token t and 0 elsewhere, ``O @
rows``; and writes the block once.  The weights are fp32 and the rows bf16:
``O`` goes in as three bf16 parts that add up to it exactly, every product is
exact in fp32 and the sums are fp32, so the result is the scatter path's
within fp32 rounding (the order of a token's k terms is the MXU's, not the
places').  Without weights ``O`` is 0 or 1 and one product does.

A non-finite row the scatter path would add to one token reaches every token
of its tile here (0 x inf); the rows behind the last run are zeroed in VMEM
for that reason.

On the device's op line the call is :data:`OP_LINE_NAME`
(``chip_bench/metrics/moe_rows_to_tokens_ms_step.json``).  Pallas is imported
where the kernel is built, not with this module.  The plan and the call are
one jitted function (:func:`_planned_call`): a model holds the call four
times a layer, and tracing and lowering a pallas kernel is host work that a
program pays at every start, before the compile cache is asked.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

# The call's name on the device's op line.
OP_LINE_NAME = "hvd_rows_to_tokens"

TILE = 256    # tokens a grid step
PIECE = 16    # rows a copy: one tile of bf16 in HBM
CHUNK = 128   # rows a product: the MXU's depth
_LANES = 128
_PER = CHUNK // PIECE


def takes(cap: int, d: int, tokens: int, dtype=jnp.bfloat16) -> bool:
    """Whether the kernel takes ``[cap, d]`` rows of ``dtype`` for ``tokens``
    tokens; otherwise, and off the TPU, the rows go through the
    scatter-add."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and cap % CHUNK == 0
            and d % _LANES == 0 and tokens % TILE == 0)


def _list_length(cap: int, n_runs: int, tokens: int) -> int:
    """The most pieces a plan can list: a stretch of n rows lies in at most
    ``n / PIECE + 2`` pieces, and a tile's list is padded to whole chunks."""
    tiles = tokens // TILE
    most = cap // PIECE + 2 * n_runs * tiles + (_PER - 1) * tiles
    return -(-most // _PER) * _PER


def _plan(token, group, tokens: int):
    """For places ``token [cap]`` in runs of ``group [runs]``: where each
    tile's chunks begin in the list (``[tiles + 1]``), and for every listed
    piece its index in ``rows`` (``[list]``), the rows of it that are the
    stretch's (``lo <= row < hi``) and how many of its rows lie in a run at
    all (``good``).  A piece that only pads a tile's list is piece 0 with no
    row taken."""
    cap, runs, tiles = token.shape[0], group.shape[0], tokens // TILE
    ends = jnp.cumsum(group.astype(jnp.int32))
    run = jnp.sum(lax.iota(jnp.int32, cap)[:, None] >= ends[None, :], axis=1)
    # Places by (run, tile), as a product of two one-hots: exact in fp32.
    in_run = run[:, None] == lax.iota(jnp.int32, runs)[None, :]
    in_tile = (token // TILE)[:, None] == lax.iota(jnp.int32, tiles)[None, :]
    counts = lax.dot_general(
        in_run.astype(jnp.bfloat16), in_tile.astype(jnp.bfloat16),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    stop = jnp.cumsum(counts.reshape(-1)).reshape(runs, tiles)
    start, stop = (stop - counts).T, stop.T                   # [tiles, runs]
    first = start // PIECE
    pieces = jnp.where(stop > start, (stop - 1) // PIECE - first + 1, 0)
    chunks = (jnp.sum(pieces, axis=1) + _PER - 1) // _PER
    chunk_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(chunks)])
    base = _PER * chunk_start[:-1, None] + jnp.cumsum(pieces, axis=1) - pieces
    # Each listed piece's stretch: the last whose pieces begin at or before
    # it (an empty stretch shares its beginning with the next).
    at = lax.iota(jnp.int32, _list_length(cap, runs, tokens))
    stretch = jnp.sum(base.reshape(1, -1) <= at[:, None], axis=1) - 1
    table = jnp.stack([base, pieces, first, start, stop], axis=-1) \
        .reshape(runs * tiles, 5)
    mine = lax.iota(jnp.int32, runs * tiles)[None, :] == stretch[:, None]
    base, pieces, first, start, stop = jnp.sum(
        jnp.where(mine[:, :, None], table[None], 0), axis=1).T
    listed = at - base < pieces
    src = jnp.where(listed, first + at - base, 0)
    row0 = src * PIECE
    lo = jnp.where(listed, jnp.clip(start - row0, 0, PIECE), 0)
    hi = jnp.where(listed, jnp.clip(stop - row0, 0, PIECE), 0)
    good = jnp.clip(ends[-1] - row0, 0, PIECE)
    return chunk_start, src, lo, hi, good


def _exact_bf16_parts(x):
    """Three bf16 arrays that add up to fp32 ``x`` exactly (8 bits of its 24
    each)."""
    parts = []
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        parts.append(part)
        x = x - part.astype(jnp.float32)
    return parts


def _kernel(chunk_start, src, lo, hi, good, rows, *refs, weighted: bool):
    """One tile of tokens: the plan's five arrays (prefetched scalars), the
    rows, tokens and weights in HBM, the tile's fp32 block, and the VMEM
    buffers and semaphores of the copies."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # (HBM array, its VMEM buffer [2, CHUNK, ...]) for the rows, the tokens
    # and, where weighted, the weights.
    n = 3 if weighted else 2
    sources = (rows,) + refs[:n - 1]
    out, buffers, sems = refs[n - 1], refs[n:2 * n], refs[2 * n]
    d = out.shape[1]
    cols = math.gcd(d, 512)     # columns a product
    tile = pl.program_id(0)
    total = chunk_start[pl.num_programs(0)]

    def copies(chunk, slot):
        for u in range(_PER):
            row0 = pl.multiple_of(src[chunk * _PER + u] * PIECE, PIECE)
            for kind, (source, buffer) in enumerate(zip(sources, buffers)):
                yield pltpu.make_async_copy(
                    source.at[pl.ds(row0, PIECE)],
                    buffer.at[slot, pl.ds(u * PIECE, PIECE)],
                    sems.at[slot, kind])

    def start(chunk, slot):
        for copy in copies(chunk, slot):
            copy.start()

    @pl.when((tile == 0) & (total > 0))
    def _():
        start(0, 0)

    out[...] = jnp.zeros_like(out)
    row_in_piece = lax.broadcasted_iota(jnp.int32, (PIECE, _LANES), 0)
    token0 = tile * TILE + lax.broadcasted_iota(jnp.int32, (CHUNK, _LANES), 0)

    def add(chunk, _):
        slot = chunk % 2

        @pl.when(chunk + 1 < total)
        def _():
            start(chunk + 1, 1 - slot)

        for copy in copies(chunk, slot):
            copy.wait()
        row_buffer, token_buffer = buffers[0], buffers[1]
        tokens_here = []
        for u in range(_PER):
            at = chunk * _PER + u
            piece = pl.ds(u * PIECE, PIECE)

            # Behind the last run lies what the grouped product left.
            @pl.when(good[at] < PIECE)
            def _():
                rows_here = row_buffer[slot, piece, :]
                keep = lax.broadcasted_iota(jnp.int32, rows_here.shape, 0) \
                    < good[at]
                row_buffer[slot, piece, :] = jnp.where(
                    keep, rows_here, jnp.zeros_like(rows_here))

            taken = (row_in_piece >= lo[at]) & (row_in_piece < hi[at])
            tokens_here.append(jnp.where(taken, token_buffer[slot, piece, :],
                                         -1))
        # [any, place]: the place's token, and its weight, down each column.
        token_of = jnp.concatenate(tokens_here, axis=0).T
        weight_of = buffers[2][slot].T if weighted else None
        for half in range(TILE // _LANES):
            holds = token_of == token0 + half * _LANES
            if weighted:
                parts = _exact_bf16_parts(jnp.where(holds, weight_of, 0.0))
            else:
                parts = [jnp.where(holds, 1.0, 0.0).astype(jnp.bfloat16)]
            to = pl.ds(half * _LANES, _LANES)
            for c in range(0, d, cols):
                block = row_buffer[slot, :, c:c + cols]
                out[to, c:c + cols] += sum(
                    jnp.dot(part, block, preferred_element_type=jnp.float32)
                    for part in parts)

    lax.fori_loop(chunk_start[tile], chunk_start[tile + 1], add, None)


@functools.partial(jax.jit, static_argnames=("tokens", "interpret"))
def _planned_call(rows, token, group, weights, *, tokens: int,
                  interpret: bool):
    """The plan and the kernel's call, as one jitted function: a program
    holds the call once for each layer, chunk and direction, and tracing the
    kernel and lowering it for the chip's compiler is host work of a quarter
    of a second a time that no compile cache saves (it comes before the
    cache's key).  Jitted, the call is traced once a process and lowered
    once a program, whatever the number of layers (``PERF.md`` §6, PR 35)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    cap, d = rows.shape
    weighted = weights is not None

    def over_lanes(v):
        return jnp.broadcast_to(v[:, None], (cap, _LANES))

    operands = [rows, over_lanes(token.astype(jnp.int32))]
    scratch = [pltpu.VMEM((2, CHUNK, d), rows.dtype),
               pltpu.VMEM((2, CHUNK, _LANES), jnp.int32)]
    if weighted:
        operands.append(over_lanes(weights.astype(jnp.float32)))
        scratch.append(pltpu.VMEM((2, CHUNK, _LANES), jnp.float32))
    return pl.pallas_call(
        functools.partial(_kernel, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(tokens // TILE,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(operands),
            out_specs=pl.BlockSpec((TILE, d), lambda i, *_: (i, 0)),
            scratch_shapes=scratch
            + [pltpu.SemaphoreType.DMA((2, len(operands)))]),
        # Under a shard_map the sums vary over the axes the rows vary over.
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32,
                                       vma=jax.typeof(rows).vma),
        # A chunk's copies are started a step ahead: the tiles go in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 2 ** 20),
        name=OP_LINE_NAME,
        interpret=interpret,
    )(*_plan(token, group, tokens), *operands)


def rows_to_tokens(rows, token, group, tokens: int, weights=None, *,
                   interpret: bool = False):
    """``y[t] = sum of weights[p] * rows[p]`` over the places p with
    ``token[p] == t``, in fp32: ``[tokens, d]``.

    - ``rows``: ``[cap, d]`` bf16, the rows at one chunk of the sorted places;
    - ``token``: ``[cap]`` int32, each place's token, ascending inside a run,
      ``tokens`` where the place is unused;
    - ``group``: ``[runs]``, the runs' lengths, in the order of the places;
    - ``weights``: ``[cap]`` fp32, or None for ones.

    Shapes :func:`takes` refuses are an error here.
    """
    if not takes(*rows.shape, tokens, rows.dtype):
        raise ValueError(f"no kernel for {rows.shape[0]} rows of "
                         f"{rows.shape[1]} {rows.dtype} and {tokens} tokens")
    return _planned_call(rows, token, group, weights, tokens=tokens,
                         interpret=interpret)
