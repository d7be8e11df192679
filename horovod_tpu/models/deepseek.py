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
and elsewhere through its einsum.  ``rope_interleave`` (the pairs (2i, 2i+1)
rotate together) is a fixed permutation in front of ``_rope``, which rotates
the halves: evens first, then odds, on the query's rotary part and on the
key's alike, so every score is the interleaved form's and nothing is permuted
back.

Scopes: ``attn.latent`` (the two down-projections and their norms),
``attn.proj`` (the up-projections and ``out``), ``attn.rope``,
``attn.layout`` (the assembly of ``[k_nope ; k_r]`` and the kernels' layout),
``attn.causal`` (the kernels alone); ``mtp.proj`` (the module's two norms and
``eh_proj``).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..core.timeline import scope
from ..kernels import masked_attention
from .transformer import LayerKind, TransformerConfig, _dense, _norm, _rope


def _pairs_first(x):
    """The last axis' even entries, then its odd ones: after it the pair
    (2i, 2i+1) lies at (i, i + d/2), where ``_rope`` rotates it."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


class LatentAttention(nn.Module):
    cfg: TransformerConfig
    kind: LayerKind = LayerKind()

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.cfg
        b, s, _ = x.shape
        h, latent = cfg.num_heads, cfg.kv_lora_rank
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        if not (cfg.causal and cfg.attention == "full" and cfg.q_lora_rank
                and cfg.positions == "rope" and self.kind.rope) \
                or self.kind.window or cfg.block_diffusion or cfg.qk_norm \
                or cfg.num_kv_heads not in (None, h):
            raise ValueError(
                "latent attention is built causal, attention='full', with a "
                "query latent and rotary positions, and without a window, "
                "the block-diffusion mask, QK-norm or grouped KV heads")
        with scope("attn.latent"):
            c_q = _dense(cfg, cfg.q_lora_rank, (None, None), "q_a")(x)
            c_q = _norm(cfg, "q_a_norm")(c_q).astype(cfg.dtype)
            down = _dense(cfg, latent + rope, (None, None), "kv_a")(x)
            c_kv = _norm(cfg, "kv_a_norm")(down[..., :latent]) \
                .astype(cfg.dtype)
            k_r = down[..., None, latent:]                  # [b, s, 1, rope]
        with scope("attn.proj"):
            q = _dense(cfg, h * (nope + rope), (None, cfg.model_axis),
                       "q_b")(c_q).reshape(b, s, h, nope + rope)
            kv = _dense(cfg, h * (nope + dv), (None, cfg.model_axis),
                        "kv_b")(c_kv).reshape(b, s, h, nope + dv)
        with scope("attn.rope"):
            q_r = q[..., nope:]
            if cfg.rope_interleave:
                q_r, k_r = _pairs_first(q_r), _pairs_first(k_r)
            q_r = _rope(q_r, cfg.rope_theta, positions)
            k_r = _rope(k_r, cfg.rope_theta, positions)
        with scope("attn.layout"):
            q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, h, rope))],
                axis=-1)
            v = kv[..., nope:]
        rule = masked_attention.Causal()
        if jax.default_backend() == "tpu" \
                and masked_attention.takes(rule, s, nope + rope, dv):
            out = masked_attention.attention(q, k, v, rule)
        else:
            out = masked_attention.einsum(q, k, v, rule)
        with scope("attn.proj"):
            return _dense(cfg, cfg.d_model, (cfg.model_axis, None),
                          "out")(out.reshape(b, s, h * dv))


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
