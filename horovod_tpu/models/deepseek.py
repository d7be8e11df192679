"""DeepSeek-V3's two own parts (arXiv:2412.19437), as JoyAI-LLM-Flash has
them: latent attention (section 2.1.1, MLA) and the multi-token-prediction
module behind the stack (section 2.2).

:class:`LatentAttention`, with ``c_q = RMSNorm(x W_dq)`` and
``[c_kv ; k_r] = x W_dkv``, ``c_kv <- RMSNorm(c_kv)``:

    [q_nope ; q_rope] = c_q W_uq          a head: nope + rope wide
    [k_nope ; v]      = c_kv W_ukv        a head: nope + v wide
    q_rope, k_r       <- RoPE             k_r once: one head that all share
    o_j = softmax_causal(q_j [k_nope_j ; k_r]^T (nope + rope)^-0.5) v_j
    out = concat(o) W_o

In training the latent is expanded, as here; the absorbed form (the
up-projections folded into the query and the output, attention over the
latent itself) is a decoding matter and is not built.  The keys are wider
than the values (192 over 128 in JoyAI-LLM-Flash): on a TPU both go as they
are to ``kernels/masked_attention.py``'s kernels under ``Causal``, one call
forward and one backward a layer with 32 "KV heads" (nothing is grouped),
and elsewhere through its einsum.

**q, k and v are born for the kernels** (PR 49).  ``rope_interleave`` (the
pairs (2i, 2i+1) rotate together) is a fixed evens-then-odds permutation of
the rotary *columns of the weights* (``W_uq`` a head at a time, ``W_dkv``'s
last ``rope``), applied in the forward pass, and not of the rows: a column of
a product is its own sum, so q and ``k_r`` leave their projections with the
pair (2i, 2i+1) at (i, i + rope/2), where the halves rotate together, on the
query's rotary part and on the key's alike; every score is the interleaved
form's and nothing is permuted back.  The parameters keep the published
layout: nothing is permuted when a checkpoint is loaded.  The weights' columns
being ours to order, the query is two flat products (every head's ``nope``
columns side by side, every head's rotary columns side by side: whole lane
groups for the MXU, where a head's 192 as one block cost its products 15 to
25%), ``W_ukv`` is cut into its two halves and ``k_nope`` and ``v`` are
written ``[b, h, s, .]`` by their products (``v`` is never touched again), and
``kernels/mla_operands.py`` finishes q and k in one pass each way: the
rotation of the rotary columns, the scale, and ``k_r`` behind every head's
``k_nope``, in the attention kernels' layout.  Behind the kernels the output
projection takes a copy ``[b, s, h * dv]`` of their output, which is made
again in the backward pass and not kept (:func:`_merged`).

**YaRN** (``cfg.yarn_factor`` above 1; Xing4.0-29B-A4B, PR 58), as
DeepSeek-V3's rotary embedding has it: the rotary columns' frequencies are
``transformer.yarn_inv_freq``'s blend, the tables of cosines and sines carry
the ratio of the two mscales and the scores' scale the square of
``mscale_all_dim``'s, which rides where the scale rode, in
``kernels/mla_operands.py``'s pass over q: the attention kernels and their
backward know nothing of it, and at the default nothing here moves.

Scopes: ``attn.latent`` (the two down-projections and their norms),
``attn.proj`` (the up-projections and ``out``), ``attn.rope`` (the weights'
permutation, the tables of cosines and sines and ``k_r``'s rotation),
``attn.layout`` (the two kernels that finish q and k, and the copy of the
attention's output that ``out`` takes, forward and again backward),
``attn.causal`` (the attention kernels alone); ``mtp.proj`` (the module's two
norms and ``eh_proj``).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..core.timeline import scope
from ..kernels import masked_attention, mla_operands
from .transformer import (
    LayerKind,
    TransformerConfig,
    _dense,
    _norm,
    _rope_angles,
    yarn_inv_freq,
    yarn_mscale,
)


def _pairs_first(x):
    """The last axis' even entries, then its odd ones: after it the pair
    (2i, 2i+1) lies at (i, i + d/2), where the halves rotate together."""
    d = x.shape[-1]
    return jnp.concatenate(
        [lax.slice_in_dim(x, first, d, 2, axis=-1) for first in (0, 1)],
        axis=-1)


def _rotary_columns_pairs_first(w, rope: int):
    """``w`` with the last ``rope`` entries of its last axis evens first,
    then odds: on a projection's weights, so that its rows leave it that
    way.  A column of a product is its own sum: the values are those of the
    permutation on the rows."""
    return jnp.concatenate([w[..., :-rope], _pairs_first(w[..., -rope:])],
                           axis=-1)


class _Weights(nn.Dense):
    """An ``nn.Dense`` (``_dense``'s, with ``cls``) that declares its
    parameters as ``nn.Dense`` does, from its own fields, and hands them out
    cast for the product as ``nn.Dense`` casts them, and not multiplied:
    latent attention permutes and cuts its projections' columns in front of
    the products.  ``(kernel, bias)``, the bias ``None`` where the model has
    none."""

    @nn.compact
    def __call__(self, rows: int):
        kernel = self.param("kernel", self.kernel_init,
                            (rows, self.features), self.param_dtype)
        bias = self.param("bias", self.bias_init, (self.features,),
                          self.param_dtype) if self.use_bias else None
        return _columns(lambda w: w.astype(self.dtype), (kernel, bias))


def _columns(fn, weights):
    """``fn`` on the kernel and on the bias, if there is one."""
    return jax.tree_util.tree_map(fn, weights)


def _by_head(weights, heads: int):
    """An up-projection's columns a head at a time: kernel ``[l, h, d]``,
    bias ``[h, d]``."""
    return _columns(lambda w: w.reshape(w.shape[:-1] + (heads, -1)), weights)


def _product(rows, weights):
    """``rows @ kernel + bias``, as ``nn.Dense`` has it."""
    kernel, bias = weights
    y = rows @ kernel
    return y if bias is None else y + bias


def _flat(rows, weights, columns):
    """``rows [b, s, l]`` through ``columns`` of an up-projection by head,
    every head's side by side: ``[b, s, h * d]``."""
    return _product(rows, _columns(
        lambda w: w[..., columns].reshape(w.shape[:-2] + (-1,)), weights))


def _up(rows, weights, columns):
    """``rows [b, s, l]`` through ``columns`` of an up-projection by head,
    written in the attention kernels' layout: ``[b, h, s, d]``."""
    kernel, bias = _columns(lambda w: w[..., columns], weights)
    y = jnp.einsum("bsl,lhd->bhsd", rows, kernel)
    return y if bias is None else y + bias[:, None]


@jax.checkpoint
def _merged(out, weights):
    """The attention's output ``[b, h, s, dv]`` through the output
    projection: the copy ``[b, s, h * dv]`` that the product takes is made
    again in the backward pass, for the weights' gradient, and not kept from
    the forward one (the kernels' own layout is kept anyway, for their
    backward; left to itself XLA keeps both, however the product is
    written).  At JoyAI-LLM-Flash's size that is 64 MiB a block not held, at
    0.15 ms a block for the second copy and 0.2 for what fuses otherwise
    beside the barrier: with it the step takes 0.23 GiB less than before
    PR 49, without it 0.08 more, which is more than its configuration states
    (``PERF.md`` section 6, PR 49)."""
    b, h, s, dv = out.shape
    with scope("attn.layout"):
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    with scope("attn.proj"):
        return _product(out, weights)


def _kernel_operands(c_q, c_kv, k_r, w_uq, w_ukv, tables, scale=1.0):
    """``(q, k, v)`` as the attention kernels take them, ``[b, h, s, .]``
    with ``q`` rotated and scaled and ``k = [k_nope ; k_r]``, from the two
    latents, the one rotary key ``[b, 1, s, rope]``, rotated, and the
    up-projections by head, ``w_uq``'s rotary columns pairs-first.  The query
    comes as two flat products (a head's 128 and its 64 apart: whole lane
    groups for the MXU, forward and backward), ``k_nope`` and ``v`` are
    written in the kernels' layout by theirs, and ``kernels/mla_operands.py``
    finishes q and k in one pass.  ``scale``: a factor on the scores' scale
    (YaRN's squared mscale)."""
    rope = k_r.shape[-1]
    nope = w_uq[0].shape[-1] - rope
    with scope("attn.proj"):
        q_nope = _flat(c_q, w_uq, slice(None, nope))
        q_rope = _flat(c_q, w_uq, slice(nope, None))
        k_nope = _up(c_kv, w_ukv, slice(None, nope))
        v = _up(c_kv, w_ukv, slice(nope, None))
    with scope("attn.layout"):
        q, k = mla_operands.operands(q_nope, q_rope, k_nope, k_r, *tables,
                                     (nope + rope) ** -0.5 * scale)
    return q, k, v


class LatentAttention(nn.Module):
    cfg: TransformerConfig
    kind: LayerKind = LayerKind()

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.cfg
        b, s, d = x.shape
        h, latent = cfg.num_heads, cfg.kv_lora_rank
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        if not (cfg.causal and cfg.attention == "full"
                and cfg.positions == "rope" and self.kind.rope) \
                or self.kind.window or cfg.block_diffusion or cfg.qk_norm \
                or cfg.num_kv_heads not in (None, h):
            raise ValueError(
                "latent attention is built causal, attention='full', with "
                "rotary positions, and without a window, the "
                "block-diffusion mask, QK-norm or grouped KV heads")
        def pairs_first(w):
            """The interleave, on a projection's rotary columns (``kv_a``'s
            last ``rope``, ``q_b``'s a head at a time) and not on the rows;
            the parameters keep the published layout."""
            if not cfg.rope_interleave:
                return w
            with scope("attn.rope"):
                return _rotary_columns_pairs_first(w, rope)

        with scope("attn.latent"):
            c_q, q_name = _query_rows(cfg, x)
            c_q = c_q.astype(cfg.dtype)
            down = _product(x.astype(cfg.dtype), _columns(pairs_first, _dense(
                cfg, latent + rope, (None, None), "kv_a", cls=_Weights)(d)))
            c_kv = _norm(cfg, "kv_a_norm")(down[..., :latent]) \
                .astype(cfg.dtype)
        with scope("attn.proj"):
            w_uq = _columns(pairs_first, _by_head(_dense(
                cfg, h * (nope + rope), (None, cfg.model_axis), q_name,
                cls=_Weights)(c_q.shape[-1]), h))
            w_ukv = _by_head(_dense(
                cfg, h * (nope + dv), (None, cfg.model_axis), "kv_b",
                cls=_Weights)(latent), h)
        # YaRN (yarn_factor above 1): the rotary columns' frequencies
        # blended, the tables times the ratio of the two mscales and the
        # scores' scale times the second one's square; nothing else moves.
        inv_freq, table_scale, score_scale = None, 1.0, 1.0
        if cfg.yarn_factor > 1.0:
            inv_freq = yarn_inv_freq(cfg, rope)
            all_dim = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
            table_scale = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) \
                / all_dim
            score_scale = all_dim * all_dim
        with scope("attn.rope"):
            tables = mla_operands.tables(
                _rope_angles(s, rope, cfg.rope_theta, positions, inv_freq))
            if table_scale != 1.0:
                tables = tuple(t * table_scale for t in tables)
            k_r = mla_operands.turn(down[:, None, :, latent:], *tables)
        q, k, v = _kernel_operands(c_q, c_kv, k_r, w_uq, w_ukv, tables,
                                   score_scale)
        rule = masked_attention.Causal()
        if jax.default_backend() == "tpu" \
                and masked_attention.takes(rule, s, nope + rope, dv):
            out = masked_attention.attention_hsd(q, k, v, rule)
        else:
            out = masked_attention.einsum_hsd(q, k, v, rule)
        if cfg.attention_gate:
            out = _gated(cfg, x, out)
        with scope("attn.proj"):
            w_o = _dense(cfg, cfg.d_model, (cfg.model_axis, None), "out",
                         cls=_Weights)(h * dv)
        return _merged(out, w_o)


class PredictionModule(nn.Module):
    """What a multi-token-prediction module has beside its block:
    ``join(h, e) = [RMSNorm_h(h) ; RMSNorm_e(e)] W_eh`` in front of it, ``h``
    the state the module reads and ``e`` the following token's embedding,
    and the norm behind it, in front of the model's own head.  The block is
    the model's ``layer_{num_layers + k}`` (``Transformer.__call__``)."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        self.hnorm = _norm(cfg, None)
        self.enorm = _norm(cfg, None)
        self.eh_proj = _dense(cfg, cfg.d_model, (None, None), None)
        self.norm = _norm(cfg, None)

    def join(self, h, e):
        with scope("mtp.proj"):
            both = jnp.concatenate([self.hnorm(h), self.enorm(e)], axis=-1)
            return self.eh_proj(both.astype(self.cfg.dtype))

    def readout_norm(self, x):
        with scope("norm"):
            return self.norm(x)


def _query_rows(cfg: TransformerConfig, x):
    """(what the query's up-projection reads, that projection's name): the
    query latent under its norm and ``"q_b"``; or, with ``q_lora_rank`` 0,
    the stream itself and ``"q"`` (Ling-3.0-flash: no query latent, no
    query norm), the rest of the layer as it is."""
    if not cfg.q_lora_rank:
        return x, "q"
    c_q = _dense(cfg, cfg.q_lora_rank, (None, None), "q_a")(x)
    return _norm(cfg, "q_a_norm")(c_q), "q_b"


def _gated(cfg: TransformerConfig, x, out):
    """The attention's output ``[b, h, s, dv]`` times a sigmoid gate a head
    (``attention_gate="head"``: a projection ``gate`` of one column a head,
    read from what the queries read, as :class:`Attention` has it)."""
    if cfg.attention_gate != "head":
        raise ValueError("latent attention's gate is one column a head: "
                         f"attention_gate={cfg.attention_gate!r}")
    with scope("attn.gate"):
        gate = _dense(cfg, out.shape[1], (None, cfg.model_axis), "gate")(x)
        gate = jax.nn.sigmoid(gate.astype(jnp.float32)).transpose(0, 2, 1)
        return (out * gate[..., None]).astype(out.dtype)
