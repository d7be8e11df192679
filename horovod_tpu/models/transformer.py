"""Transformer encoder/decoder — BERT-large, GPT and OLMoE presets.

Targets the reference's BERT-large Adasum pretraining config (BASELINE.md
benchmark 4) and serves as the long-context flagship.  TPU-first choices:

- bfloat16 activations, fp32 params/layernorm/softmax accumulation;
- tensor parallelism by construction: qkv/FFN kernels carry
  ``nn.with_partitioning`` annotations over the ``model`` mesh axis
  (Megatron-style column→row sharding) so ``jit`` + GSPMD inserts the
  collectives — no hand-written TP code;
- pluggable attention: ``full`` (XLA-fused einsum; the pallas flash kernel
  from 4096 positions on, on a TPU), ``ring``
  (:func:`horovod_tpu.parallel.ring_attention`) or ``ulysses``
  (:func:`horovod_tpu.parallel.ulysses_attention`) for sequence-parallel
  long context — the latter two run inside ``shard_map`` with the ``seq``
  axis bound (see :mod:`horovod_tpu.models.training`);
- optional ``lax.scan``-friendly uniform blocks + remat for HBM headroom;
- the block's parts are options of one config (norm kind, learned or rotary
  positions, QK-norm, biases, tied or untied head, dense or sparse-expert
  FFN): OLMoE is ``olmoe_1b_7b_config()`` over the same ``Transformer``, its
  expert layer :func:`horovod_tpu.parallel.moe.moe_ffn` (``docs/moe.md``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.mesh import AXIS_MODEL, AXIS_SEQ
from ..parallel.moe import MoEStats, moe_ffn


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_len: int = 512
    causal: bool = True               # decoder (GPT); False = encoder (BERT)
    attention: str = "full"           # full | ring | ulysses
    seq_axis: str = AXIS_SEQ
    model_axis: str = AXIS_MODEL
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # The block's parts; the defaults are BERT's and GPT-2's.
    norm: str = "layernorm"           # layernorm | rmsnorm
    norm_eps: float = 1e-6
    positions: str = "learned"        # learned (a table of max_len) | rope
    rope_theta: float = 10000.0
    qk_norm: bool = False             # `norm` over the projected q and k
    use_bias: bool = True
    tie_embeddings: bool = True       # False: an untied ``lm_head``
    ffn: str = "gelu"                 # gelu (dense, d_ff) | moe
    # ffn == "moe": silu-gated experts of width d_ff, top experts_per_token
    # of num_experts, dropless.  moe_data_axis names the mesh axis the batch
    # is sharded over where the step is one program over the global batch
    # (hvd.make_overlapped_train_step: hvd.PROCESS_AXIS); see moe_ffn.
    num_experts: int = 0
    experts_per_token: int = 0
    moe_data_axis: Optional[str] = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


def bert_large_config(**overrides) -> TransformerConfig:
    """BERT-large (the reference's Adasum pretraining benchmark model)."""
    return TransformerConfig(**{**dict(
        vocab_size=30522, num_layers=24, num_heads=16, d_model=1024,
        d_ff=4096, max_len=512, causal=False), **overrides})


def gpt_small_config(**overrides) -> TransformerConfig:
    return TransformerConfig(**{**dict(
        vocab_size=50257, num_layers=12, num_heads=12, d_model=768,
        d_ff=3072, max_len=1024, causal=True), **overrides})


def olmoe_1b_7b_config(**overrides) -> TransformerConfig:
    """OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct ``config.json``): 16
    layers of 64 experts of width 1024, 8 a token, 16 heads of 128, RMSNorm,
    RoPE, QK-norm, no biases, untied head."""
    return TransformerConfig(**{**dict(
        vocab_size=50304, num_layers=16, num_heads=16, d_model=2048,
        d_ff=1024, max_len=4096, causal=True, norm="rmsnorm", norm_eps=1e-5,
        positions="rope", rope_theta=10000.0, qk_norm=True, use_bias=False,
        tie_embeddings=False, ffn="moe", num_experts=64,
        experts_per_token=8), **overrides})


def tiny_config(**overrides) -> TransformerConfig:
    """For tests and the multichip dryrun: tiny shapes, same code paths."""
    return TransformerConfig(**{**dict(
        vocab_size=128, num_layers=2, num_heads=4, d_model=32,
        d_ff=64, max_len=64, causal=True), **overrides})


def _dense(cfg: TransformerConfig, features: int, kernel_spec, name: str):
    """Dense with a TP partitioning annotation on the kernel."""
    return nn.Dense(
        features, dtype=cfg.dtype, param_dtype=jnp.float32, name=name,
        use_bias=cfg.use_bias,
        kernel_init=nn.with_partitioning(
            nn.initializers.normal(0.02), kernel_spec))


def _norm(cfg: TransformerConfig, name: str):
    """The configuration's norm, computed and handed on in fp32."""
    if cfg.norm == "layernorm":
        return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                            name=name)
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)
    raise ValueError(f"unknown norm {cfg.norm!r}")


def _rope(x, theta: float):
    """Rotary positions on ``[b, s, h, d]``, halves rotated as in
    ``transformers`` (``x*cos + rotate_half(x)*sin``), in fp32."""
    s, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        h, dh = cfg.num_heads, cfg.head_dim
        # Column-parallel qkv: heads split over the model axis.
        qkv = _dense(cfg, 3 * h * dh, (None, cfg.model_axis), "qkv")(x)
        q, k, v = jnp.split(qkv.reshape(b, s, 3 * h, dh), 3, axis=2)
        if cfg.qk_norm:
            # Over the whole projection (all heads), as OLMoE has it.
            flat = lambda t: t.reshape(b, s, h * dh)  # noqa: E731
            q = _norm(cfg, "q_norm")(flat(q)).astype(cfg.dtype)
            k = _norm(cfg, "k_norm")(flat(k)).astype(cfg.dtype)
            q, k = q.reshape(b, s, h, dh), k.reshape(b, s, h, dh)
        if cfg.positions == "rope":
            if cfg.attention != "full":
                raise ValueError("rope positions need attention='full'")
            q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)

        if cfg.attention == "ring":
            from ..parallel.ring_attention import ring_attention

            out = ring_attention(q, k, v, axis_name=cfg.seq_axis,
                                 causal=cfg.causal)
        elif cfg.attention == "ulysses":
            from ..parallel.ulysses import ulysses_attention

            out = ulysses_attention(q, k, v, axis_name=cfg.seq_axis,
                                    causal=cfg.causal)
        elif cfg.attention == "full":
            out = _scaled_dot_attention(q, k, v, cfg.causal, dh)
        else:
            raise ValueError(f"unknown attention mode {cfg.attention!r}")

        out = out.reshape(b, s, h * dh)
        # Row-parallel output projection closes the TP pair.
        return _dense(cfg, cfg.d_model, (cfg.model_axis, None), "out")(out)


# The pallas flash kernel's blocks, and the shortest sequence it takes.  On a
# v5e at b=2, h=16, s=4096, d=128, causal, forward + backward (PERF.md, PR
# 27): the XLA-fused einsum 32.5 ms, the kernel with its default blocks of 128
# 36.9 ms, with blocks of 512 7.8 ms and of 1024 7.4 ms; it also keeps the
# [b, h, s, s] scores (2.1 GB in fp32 there) out of HBM.  With its default
# blocks it had measured slower than the einsum at s=512 (27.6k against
# 38.5k tokens/s, BERT-large b8) and s=2048 (11.7k against 14.4k, b2); those
# shapes were not measured with larger blocks and stay on the einsum.
_FLASH_BLOCK = 1024
_FLASH_MIN_SEQ = 4096


def _flash_attention(q, k, v, causal: bool, dh: int):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    n = _FLASH_BLOCK
    blocks = BlockSizes(
        block_q=n, block_k_major=n, block_k=n, block_b=1,
        block_q_major_dkv=n, block_k_major_dkv=n, block_k_dkv=n,
        block_q_dkv=n, block_k_major_dq=n, block_k_dq=n, block_q_dq=n)
    bhsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
    o = flash_attention(bhsd(q), bhsd(k), bhsd(v), causal=causal,
                        sm_scale=dh ** -0.5, block_sizes=blocks)
    return o.transpose(0, 2, 1, 3)


def _scaled_dot_attention(q, k, v, causal: bool, dh: int):
    """Single-device attention for the "full" mode, [b, s, h, d] layout: the
    XLA-fused einsum softmax, and on a TPU from ``_FLASH_MIN_SEQ`` positions
    on the pallas flash-attention kernel, picked from the shape alone.  A
    kernel that fails to lower fails the step: it is never silently the
    einsum."""
    s = q.shape[1]
    if jax.default_backend() == "tpu" and s >= _FLASH_MIN_SEQ \
            and s % _FLASH_BLOCK == 0 and dh % 128 == 0:
        return _flash_attention(q, k, v, causal, dh)
    scale = dh ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        # From iota comparisons: a [s, s] constant is 16 MB at s = 4096.
        mask = lax.broadcasted_iota(jnp.int32, (s, s), 0) >= \
            lax.broadcasted_iota(jnp.int32, (s, s), 1)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        x = x + Attention(cfg, name="attn")(_norm(cfg, "ln1")(x))
        y = _norm(cfg, "ln2")(x)
        if cfg.ffn == "gelu":
            y = _dense(cfg, cfg.d_ff, (None, cfg.model_axis), "ffn_in")(y)
            y = nn.gelu(y)
            y = _dense(cfg, cfg.d_model, (cfg.model_axis, None), "ffn_out")(y)
        elif cfg.ffn == "moe":
            y = self._experts(y)
        else:
            raise ValueError(f"unknown ffn {cfg.ffn!r}")
        return x + y

    def _experts(self, y):
        """The sparse-expert FFN; its MoEStats are sown into the ``moe``
        collection (``apply(..., mutable=["moe"])``, then ``moe_stats``)."""
        cfg = self.cfg
        e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
        init = nn.initializers.normal(0.02)
        weights = [self.param(name, init, shape, jnp.float32)
                   for name, shape in (("router", (d, e)),
                                       ("experts_gate", (e, d, f)),
                                       ("experts_up", (e, d, f)),
                                       ("experts_down", (e, f, d)))]
        y, stats = moe_ffn(y, *weights, k=cfg.experts_per_token,
                           data_axis=cfg.moe_data_axis, dtype=cfg.dtype)
        self.sow("moe", "stats", stats)
        return y


def moe_stats(collection) -> MoEStats:
    """The layers' ``MoEStats`` out of the ``moe`` collection that
    ``apply(..., mutable=["moe"])`` returns, stacked on a leading layer
    axis: ``[layers, sets]`` losses and ``[layers, sets, experts]`` counts."""
    layers = sorted(collection, key=lambda name: int(name.split("_")[-1]))
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[collection[n]["stats"][0] for n in layers])


class Transformer(nn.Module):
    """Token ids ``[batch, seq]`` → logits ``[batch, seq, vocab]``."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
            param_dtype=jnp.float32, name="embed",
            embedding_init=nn.with_partitioning(
                nn.initializers.normal(0.02), (cfg.model_axis, None)))
        s = tokens.shape[1]
        if s > cfg.max_len:
            raise ValueError(f"{s} positions, max_len is {cfg.max_len}")
        x = embed(tokens)
        if cfg.positions == "learned":
            x = x + self._learned_positions(s).astype(cfg.dtype)
        elif cfg.positions != "rope":
            raise ValueError(f"unknown positions {cfg.positions!r}")
        block = Block
        if cfg.remat:
            block = nn.remat(Block)
        for i in range(cfg.num_layers):
            x = block(cfg, name=f"layer_{i}")(x)
        x = _norm(cfg, "ln_f")(x)
        if cfg.tie_embeddings:
            # Weight-tied readout against the (model-axis-sharded) embedding.
            return embed.attend(x.astype(jnp.float32))
        return _dense(cfg, cfg.vocab_size, (None, cfg.model_axis),
                      "lm_head")(x)

    def _learned_positions(self, s):
        cfg = self.cfg
        pos_embed = self.param(
            "pos_embed",
            nn.with_partitioning(nn.initializers.normal(0.02), (None, None)),
            (cfg.max_len, cfg.d_model), jnp.float32)
        if cfg.attention in ("ring", "ulysses"):
            # Inside shard_map the local shard sees only its sequence slice;
            # index positions globally.
            start = lax.axis_index(cfg.seq_axis) * s
            return lax.dynamic_slice_in_dim(jnp.asarray(pos_embed), start, s,
                                            0)
        return jnp.asarray(pos_embed)[:s]
