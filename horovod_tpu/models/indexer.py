"""A learned indexer in front of attention: DeepSeek Sparse Attention's
lightning indexer (DeepSeek-V3.2-Exp) at a configuration's sizes, as
Keye-VL-2.0-30B-A3B's ``sa_config`` states them.

A layer with ``cfg.indexer_topk`` scores every causal pair from
``cfg.indexer_heads`` small heads on one shared key,

    x    = stop_gradient(n)                      n the layer's normed input
    q_j  = turn(x W_q)_j                         H heads of d
    k    = turn(LN(x W_k))                       one key of d for all heads
    w    = x W_w * H^-0.5 * d^-0.5               one weight a head
    I[t, s] = sum_j w[t, j] * relu(q_j[t] . k[s])        (fp32, s <= t)

and its attention runs over the ``topk`` keys a query with the largest
``I[t, .]`` (all of them below ``topk`` positions; of equal scores the lower
position), ``kernels/masked_attention.py::Sparse``.  The choice has no
gradient, so the indexer learns from a loss of its own (the sparse training
stage): ``KL(p[t, .] || softmax over the set of I[t, .])``, ``p`` the
attention's own probabilities over the set averaged over its heads and cut
from the graph, a mean over the queries.  By the two cuts that loss reaches
the indexer's four parameters alone, and no other loss reaches them.

:class:`Indexer` is the projections (``hvd.indexer.proj``); :func:`choose`
the scores and the choice (``hvd.indexer.scores``, ``hvd.indexer.choose``),
:func:`loss` the target and the divergence (``hvd.indexer.target``,
``hvd.indexer.loss``): on a TPU ``kernels/dsa.py``'s two kernels where they
take the shape (one kernel each: the op line tells no scores from the choice
and no target from the loss, the scopes name what a kernel holds), else the
same rule in ``jax.numpy`` a block of queries at a time, the choice by
``lax.top_k``.  The choosing kernel counts its way to a row's threshold and
breaks ties only in a block that has one; what it says of its blocks (rows
that tied, passes run) is ``benchmarks/sparse_attention_sweep.py``'s to
read and is dropped here.  :func:`scores` is that form's table of a block.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..core.timeline import scope
from ..kernels import dsa
from ..kernels.masked_attention_bwd import pack_chosen, unpack_chosen

# Queries a block of the ``jax.numpy`` form (``q_chunk_size``'s 512).
BLOCK = 512


class Indexer(nn.Module):
    """``(x [b, s, d_model], rope) -> (q_i [b, H, s, d], k_i [b, s, d], w
    [b, s, H] fp32)``; ``rope(t)`` turns ``[b, s, heads, d]`` by the layer's
    rotary table over the whole ``d``.  The input is cut from the graph
    here."""

    cfg: "object"

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.cfg
        b, s, _ = x.shape
        heads, d = cfg.indexer_heads, cfg.indexer_head_dim
        x = lax.stop_gradient(x)

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            param_dtype=jnp.float32, name=name,
                            kernel_init=nn.initializers.normal(0.02))

        with scope("indexer.proj"):
            q_i = dense(heads * d, "q")(x).reshape(b, s, heads, d)
            k_i = nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                               name="k_norm")(dense(d, "k")(x))
            w = dense(heads, "weights")(x).astype(jnp.float32) \
                * (heads ** -0.5 * d ** -0.5)
            q_i = rope(q_i).transpose(0, 2, 1, 3)
            k_i = rope(k_i.astype(cfg.dtype)[:, :, None, :])[:, :, 0, :]
        return q_i, k_i, w


def scores(q_i, k_i, w, start: int = 0, rows=None):
    """``I [b, rows, s]`` in fp32 for the queries ``start .. start + rows``
    (all of them by default): the dense table of a block, ``-inf`` behind
    the query."""
    s = k_i.shape[1]
    rows = s - start if rows is None else rows
    q_i = lax.dynamic_slice_in_dim(q_i, start, rows, axis=2)
    w = lax.dynamic_slice_in_dim(w, start, rows, axis=1)
    by_head = jnp.einsum("bjtd,bsd->btjs", q_i, k_i,
                         preferred_element_type=jnp.float32)
    table = jnp.einsum("btjs,btj->bts", jax.nn.relu(by_head), w)
    causal = jnp.arange(s)[None, :] <= start + jnp.arange(rows)[:, None]
    return jnp.where(causal, table, -jnp.inf)


def _blocks(s: int) -> int:
    return s // BLOCK if s % BLOCK == 0 else 1


def _by_blocks(fn, s: int):
    """``fn(start)`` over the blocks of ``s`` queries, stacked on a leading
    axis, one block alive at a time."""
    n = _blocks(s)
    return lax.map(fn, jnp.arange(n) * (s // n))


def _choose(q_i, k_i, w, topk: int):
    b, _, s, _ = q_i.shape
    rows = s // _blocks(s)

    def block(start):
        table = scores(q_i, k_i, w, start, rows)
        _, ids = lax.top_k(table, min(topk, s))
        taken = jnp.zeros(table.shape, bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(rows)[None, :, None],
            ids].set(True)
        return pack_chosen(taken & (table > -jnp.inf))

    words = _by_blocks(block, s)                    # [blocks, b, rows, n]
    return words.transpose(1, 0, 2, 3).reshape(b, s, -1)


def choose(q_i, k_i, w, topk: int):
    """``(words, lse_i)``: the chosen sets of every query as
    ``pack_chosen``'s words ``[b, s, groups * 128]``, cut from the graph (a
    choice has no gradient), and, where the kernels ran, the log-sum-exp of a
    query's scores over its set (else None: :func:`loss` computes its
    own)."""
    q_i, k_i, w = (lax.stop_gradient(t) for t in (q_i, k_i, w))
    s, d = q_i.shape[2], q_i.shape[3]
    # One kernel holds both on a TPU; the scopes nest so that the account
    # by block (chip_bench/scopes.py) reads it under the choice.
    with scope("indexer.scores"), scope("indexer.choose"):
        if dsa.takes(s, d):
            # The blocks that broke ties are the sweep's to read, not the
            # step's: no leaf of the `indexer` collection.
            return dsa.choose(q_i, k_i, w, topk=topk)[:2]
        return _choose(q_i, k_i, w, topk), None


def _kl_sum(q_i, k_i, w, words, q, k):
    """The ``jax.numpy`` form of ``dsa.kl_sum``: the target from a softmax of
    its own over the set, a block of queries at a time."""
    b, heads, s, _ = q.shape
    group = heads // k.shape[1]
    rows = s // _blocks(s)
    q, k = lax.stop_gradient(q), lax.stop_gradient(k)

    @jax.checkpoint
    def block(start):
        mask = unpack_chosen(
            lax.dynamic_slice_in_dim(words, start, rows, axis=1), s)
        with scope("indexer.target"):
            q_rows = lax.dynamic_slice_in_dim(q, start, rows, axis=2)
            logits = jnp.einsum(
                "bngtd,bnsd->bngts",
                q_rows.reshape(b, heads // group, group, rows, -1), k,
                preferred_element_type=jnp.float32)
            p = jnp.mean(jax.nn.softmax(
                jnp.where(mask[:, None, None], logits, -jnp.inf), axis=-1),
                axis=(1, 2))
        with scope("indexer.loss"):
            log_q = jax.nn.log_softmax(
                jnp.where(mask, scores(q_i, k_i, w, start, rows), -jnp.inf),
                axis=-1)
            held = mask & (p > 0.0)
            return jnp.sum(jnp.where(
                held, p * (jnp.log(jnp.where(held, p, 1.0))
                           - jnp.where(held, log_q, 0.0)), 0.0))

    return jnp.sum(_by_blocks(block, s))


def loss(q_i, k_i, w, words, lse_i, q, k, lse):
    """The indexer's loss of one layer, a scalar in fp32: the mean over the
    sequences and queries of ``KL(p[t, .] || softmax over the set of I[t,
    .])``.  ``q [b, h, s, dh]`` (scaled) and ``k [b, h_kv, s, dh]`` are the
    attention's operands as its kernels take them and ``lse [b, h, s]`` its
    rows' log-sum-exp over their sets (None off the kernels' path, as
    ``lse_i``, :func:`choose`'s); all three are cut from the graph, so the
    loss reaches ``q_i``, ``k_i`` and ``w`` alone."""
    b, _, s, d = q_i.shape
    if lse is not None and lse_i is not None and dsa.takes(s, d):
        # One kernel holds the target, the divergence and its gradient.
        with scope("indexer.target"), scope("indexer.loss"):
            total = dsa.kl_sum(q_i, k_i, w, words, lse_i, q, k, lse)
    else:
        total = _kl_sum(q_i, k_i, w, words, q, k)
    return total / (b * s)


def indexer_loss(collection):
    """The layers' losses out of the ``indexer`` collection that
    ``apply(..., mutable=["indexer"])`` returns, summed."""
    return sum(jax.tree_util.tree_leaves(collection))
