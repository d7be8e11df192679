"""Transformer encoder/decoder — BERT-large, GPT, OLMoE, SDAR, SmallThinker,
LFM2, Nemotron-H, JoyAI-LLM-Flash, Qwen3-Next, Granite-4.0-H, Xing4.0,
Laguna, Ling and Keye-VL-2.0 presets.

Targets the reference's BERT-large Adasum pretraining config (BASELINE.md
benchmark 4) and serves as the long-context flagship.  TPU-first choices:

- bfloat16 activations, fp32 params/layernorm/softmax accumulation;
- tensor parallelism by construction: qkv/FFN kernels carry
  ``nn.with_partitioning`` annotations over the ``model`` mesh axis
  (Megatron-style column→row sharding) so ``jit`` + GSPMD inserts the
  collectives — no hand-written TP code;
- pluggable attention: ``full`` (XLA-fused einsum; on a TPU our fused kernel
  at short lengths and the pallas flash kernel from 4096 positions), ``ring``
  (:func:`horovod_tpu.parallel.ring_attention`) or ``ulysses``
  (:func:`horovod_tpu.parallel.ulysses_attention`) for sequence-parallel
  long context — the latter two run inside ``shard_map`` with the ``seq``
  axis bound (see :mod:`horovod_tpu.models.training`);
- optional ``lax.scan``-friendly uniform blocks + remat for HBM headroom;
- the block's parts are options of one config (norm kind, learned or rotary
  positions, QK-norm over the projection or per head, grouped KV heads, an
  explicit head width, biases, tied or untied head, dense or sparse-expert
  FFN, all experts or a share of them, the gate's activation, a router that
  reads the FFN's input or the block's, softmax or sigmoid scores with a
  selection bias the step keeps, causal, unmasked or block-diffusion
  attention) and a layer pattern says, layer by layer, which mixer runs
  (attention, or the gated short convolution of :class:`ShortConv` over
  ``kernels/short_conv.py``), which layers attend inside a window, which
  carry rotary positions and which carry a gated dense FFN in place of the
  configuration's, or that the layer is a mixer alone or an FFN alone under
  one norm (Nemotron-H: a Mamba-2 mixer of ``models/mamba2.py`` over
  ``kernels/ssd_scan.py``, attention, or experts without a gate on a latent
  width beside a shared expert), and attention is plain or latent
  (``models/deepseek.py``: DeepSeek-V3's MLA, and its multi-token-prediction
  module behind the stack, JoyAI-LLM-Flash's): OLMoE is
  ``olmoe_1b_7b_config()``,
  SDAR-30B-A3B ``sdar_30b_a3b_config()``, SmallThinker-21BA3B
  ``smallthinker_21b_a3b_config()``, LFM2-8B-A1B ``lfm2_8b_a1b_config()``,
  Nemotron-3-Super-120B-A12B ``nemotron_3_super_config()`` and
  JoyAI-LLM-Flash ``joyai_llm_flash_config()`` over the same
  ``Transformer`` (and Qwen3-Next-80B-A3B ``qwen3_next_80b_a3b_config()``:
  Gated DeltaNet mixers of ``models/gated_delta.py`` over
  ``kernels/gated_delta.py``, a gate on the attention's output, rotary
  positions over a share of a head, norms whose scale is ``1 + w``, a gated
  shared expert; and granite-4.0-h-micro ``granite_4_0_h_micro_config()``:
  a Mamba-2 mixer or attention without positions *and* a dense SwiGLU in
  every layer, under four muP scalars, ``embedding_multiplier`` on the
  embedding, ``residual_multiplier`` on both branches of a block,
  ``attention_multiplier`` as the scores' scale and ``logits_scaling`` under
  the logits, each a field whose default leaves every other program as it
  was), their expert layer
  :func:`horovod_tpu.parallel.moe.moe_ffn` (``docs/moe.md``), their masks
  that are rules ``kernels/masked_attention.py``'s;
- ``remat``: every block under ``nn.remat``, its forward pass run again in
  the backward pass from the block's input, the one activation kept
  (granite-4.0-h-micro's cell, whose 12.35 GB of weights, gradients and
  AdamW state leave no room for ten layers' activations; the mixers' and
  attention's ``custom_vjp`` kernels run their forward twice then; and
  xing4.0-29b-a4b's, whose layer input is four streams wide);
- ``hc_mult``: the residual path itself is an option: the pre-norm add on
  one stream, or manifold-constrained hyper-connections over ``hc_mult``
  streams (``models/hyper_connections.py``, loaded where a configuration
  asks for it; Xing4.0-29B-A4B ``xing4_0_29b_a4b_config()``, whose latent
  attention also runs under YaRN, ``yarn_*``);
- a layer's kind also says how many query heads its attention has and by
  which rotary table they turn (``LayerKind.heads``, ``LayerKind.rotary``, a
  :class:`Rotary`: theta, the share of a head turned, YaRN's numbers), where
  those are not the model's: Laguna-S-2.1 ``laguna_s_2_1_config()``, 48
  global heads under YaRN on half a head to 72 under plain RoPE inside a
  window of 512, a sigmoid gate a head (``attention_gate="head"``);
- Ling-3.0-flash ``ling_3_0_flash_config()``: Kimi Delta Attention mixers
  (``models/kda.py`` over ``kernels/kda.py``, a delta rule whose decay is a
  vector a key channel) five to one layer of latent attention without a
  query latent (``q_lora_rank`` 0) under a gate a head, and experts chosen
  inside the ``moe_groups_kept`` best of ``moe_groups`` groups;
- on a TPU the rotary positions of bf16 heads of 128 under a rule of
  ``kernels/masked_attention.py`` are one kernel a direction over q and k
  (``kernels/rope_operands.py``: turned, q scaled, written where the
  attention kernels read them; chosen by ``rope_operands.takes`` from the
  backend, the dtype, the head's width and the rule, never by a model);
  everything else is :func:`_rope` on the rows;
- Keye-VL-2.0-30B-A3B's language model ``keye_vl_2_0_30b_a3b_config()``:
  grouped-query attention over the ``indexer_topk`` keys a query that a
  learned indexer chooses (``models/indexer.py``, DeepSeek Sparse Attention's
  lightning indexer, loaded where a layer that has one is built; the mask is
  data, ``kernels/masked_attention.py::Sparse``), the indexer's own loss
  sown into the ``indexer`` collection (``indexer.indexer_loss``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..core.timeline import scope
from ..kernels import (
    masked_attention,
    rope_operands,
    short_attention,
    short_conv,
)
from ..kernels.blockdiff_attention import BlockDiffusion
from ..parallel.mesh import AXIS_MODEL, AXIS_SEQ
from ..parallel.moe import (
    MoEStats,
    RouterRows,
    _activation,
    moe_ffn,
    router_product_passes,
)


class Rotary(NamedTuple):
    """A rotary table of a layer kind's own (:attr:`LayerKind.rotary`), in
    the keys of ``transformers``' ``rope_parameters``: pair ``i`` of the
    first ``share`` of a head turns by ``rope_theta ** (-2 i / width)`` a
    position, ``width`` that share's; with ``yarn_factor`` above 1 by YaRN's
    frequencies over that width (:func:`yarn_inv_freq`, whose range
    :func:`yarn_correction_range` truncates to whole pairs), cosines and
    sines times ``attention_factor`` (None: ``0.1 ln yarn_factor + 1``), so
    that a score's turned part carries the factor's square and the part
    without positions none; nothing goes into the softmax's scale
    (``transformers``' ``_compute_yarn_parameters``; latent attention's YaRN
    is DeepSeek-V3's, ``TransformerConfig.yarn_*``, which scales the scores).
    The field names are the configuration's, so the YaRN helpers read
    either."""

    rope_theta: float = 10000.0
    share: float = 1.0
    yarn_factor: float = 1.0
    yarn_original_max_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: Optional[float] = None


class LayerKind(NamedTuple):
    """What one layer of a pattern differs in.  ``window``: attention is
    causal inside this many positions (a query sees itself and the
    ``window - 1`` before it); 0: the model's mask.  ``rope``: whether the
    layer applies the rotary positions (only where ``positions == "rope"``);
    a layer without them carries no position at all.  ``mixer``: what mixes
    the tokens, ``"attention"``, ``"conv"`` (:class:`ShortConv`, which
    takes neither window nor positions), ``"mamba2"``
    (``models/mamba2.py``, nor that), ``"gated_delta"``
    (``models/gated_delta.py``, nor that), ``"kda"`` (``models/kda.py``, nor
    that) or ``"none"``: the layer is its FFN
    alone, under one norm.  ``ffn``: None, the configuration's ``ffn``;
    ``"dense"``, the gated dense FFN of width ``d_ff_dense`` (a sparse
    model's leading dense layers); ``"moe"``; or ``"none"``: the layer is
    its mixer alone, under one norm (Nemotron-H's layers are one or the
    other).  ``heads``: the layer's query heads where they are not the
    configuration's ``num_heads`` (0), on the same KV heads of the same
    width: the q and ``out`` projections and a gate a head follow it.
    ``rotary``: the layer's own rotary table (:class:`Rotary`) where it is
    not the configuration's ``rope_theta`` over ``partial_rotary_factor``
    of a head (None); plain attention's alone."""

    window: int = 0
    rope: bool = True
    mixer: str = "attention"
    ffn: Optional[str] = None
    heads: int = 0
    rotary: Optional[Rotary] = None


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
    # `norm` over the projected q and k: True over the whole projection (all
    # heads, OLMoE), "head" over each head's width with one scale the heads
    # share (SDAR, Qwen3).
    qk_norm: Any = False
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
    # The ids of the experts that live here, where a layer's experts are
    # shared among chips (None: all num_experts); the router keeps its
    # num_experts outputs.  norm_topk_prob: a token's k weights sum to 1.
    experts_held: Optional[Tuple[int, ...]] = None
    norm_topk_prob: bool = False
    # What the router reads: "ffn", the normed post-attention state the
    # experts multiply, or "block", the block's input before attention and
    # its norm.  expert_activation: the gate's, silu | relu.
    router_input: str = "ffn"
    expert_activation: str = "silu"
    # Grouped-query attention: num_kv_heads KV heads, each serving
    # num_heads / num_kv_heads query heads (None: as many as heads, one fused
    # qkv projection).  head_width: a head's width where it is not
    # d_model / num_heads.
    num_kv_heads: Optional[int] = None
    head_width: Optional[int] = None
    # Block-diffusion training (kernels/blockdiff_attention.py): blocks of
    # this many tokens; the model then takes [x_t ; x_0], 2L positions, gives
    # both halves the indices 0..L-1, masks attention by the block rule in
    # place of `causal`, and returns the noisy half's logits.  0: off.
    block_diffusion: int = 0
    # One period of the layers' kinds, repeated over num_layers (layer i is
    # layer_pattern[i % len]), or all num_layers of them where the layers
    # keep to no period; None: every layer is LayerKind(), today's uniform
    # model.
    layer_pattern: Optional[Tuple[LayerKind, ...]] = None
    # The layers of kind ffn="dense": W_2(silu(W_1 x) * W_3 x) of this width.
    d_ff_dense: int = 0
    # The layers of kind mixer="conv", and "kda"'s convolution over q, k and
    # v: taps of the depthwise filter.
    conv_taps: int = 3
    # ffn == "moe": the router's scores, softmax | sigmoid (moe_ffn's
    # ``scoring``); expert_bias: the "moe" collection carries a selection
    # bias [num_experts] a layer (variable "bias"), added to the scores for
    # the choice of the experts alone: state the training step keeps
    # (moe.update_expert_bias), no parameter.  routed_scaling_factor: a
    # factor on the chosen experts' weights.
    router_scoring: str = "softmax"
    expert_bias: bool = False
    routed_scaling_factor: float = 1.0
    # ffn == "moe", Nemotron-H's LatentMoE.  expert_gate False: the experts
    # are down(act(up x)) with no gate.  moe_latent: the width the routed
    # experts multiply where that is not d_model: a projection d_model ->
    # moe_latent in front of them and one back behind their weighted sum,
    # the router still reading d_model.  d_ff_shared: the width of a shared
    # expert of the experts' form that every token passes through on the
    # full d_model, added to the routed sum (0: none).
    expert_gate: bool = True
    moe_latent: int = 0
    d_ff_shared: int = 0
    # The layers of kind mixer="mamba2" (models/mamba2.py): heads of
    # mamba_head_dim channels in mamba_groups groups that share B and C of
    # width mamba_state, a depthwise convolution of mamba_conv taps, the scan
    # in chunks of mamba_chunk.  mamba_groups_held: the groups that live
    # here where a mixer's heads are shared among chips (None: all).
    # mamba_dt_limits: (time_step_min, time_step_max, time_step_floor) of
    # dt_bias's initialisation.
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    mamba_groups: int = 1
    mamba_state: int = 128
    mamba_conv: int = 4
    mamba_chunk: int = 128
    mamba_groups_held: Optional[Tuple[int, ...]] = None
    mamba_dt_limits: Tuple[float, float, float] = (1e-3, 1e-1, 1e-4)
    # Latent attention (DeepSeek-V2/V3's MLA, models/deepseek.py), where
    # kv_lora_rank is set: queries through a latent of q_lora_rank and keys
    # and values through one of kv_lora_rank, an RMSNorm on each; a head's
    # query and key are qk_nope_head_dim without positions beside
    # qk_rope_head_dim rotary, the rotary key one head that all share; values
    # of v_head_dim; scores scaled by the key's whole width.  rope_interleave:
    # the rotary part rotates the pairs (2i, 2i+1), not the halves.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # Multi-token-prediction modules behind the stack (DeepSeek-V3 section
    # 2.2, models/deepseek.py): module k reads the state before ln_f (or
    # module k-1's) beside the embedding of token i+k, runs one block of the
    # last layer's kind, named layer_{num_layers + k - 1}, and goes through a
    # norm of its own and the model's head; the model then returns (logits,
    # (module 1's logits, ...)).
    mtp_modules: int = 0
    # Qwen3-Next's parts.  norm_offset: an RMSNorm's scale is ``1 + w``, w
    # zero at the start (the block's norms, ln_f and the per-head QK-norm).
    # attention_gate: True, the query projection is twice as wide, a head's
    # second half a sigmoid gate on that head's output in front of the output
    # projection; "head" (Laguna), a projection ``gate`` of one column a
    # head, its sigmoid on the head's whole width.  partial_rotary_factor:
    # the share of a head's width, from its start, that the rotary positions
    # turn; the rest carries none.
    # shared_expert_gate: the shared expert's output times sigmoid(x . w_g).
    norm_offset: bool = False
    attention_gate: Any = False
    partial_rotary_factor: float = 1.0
    shared_expert_gate: bool = False
    # The layers of kind mixer="gated_delta" (models/gated_delta.py):
    # gdn_key_heads key heads of gdn_key_dim, each serving gdn_value_heads /
    # gdn_key_heads value heads of gdn_value_dim, a depthwise convolution of
    # gdn_conv taps over q, k and v (the rule's chunk is the kernels' own,
    # kernels/gated_delta.py::CHUNK).
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4
    # The layers of kind mixer="kda" (models/kda.py): num_heads heads of
    # kda_head_dim, each with keys of its own, a depthwise convolution of
    # conv_taps taps over q, k and v (the decay's bound is the kernels' own).
    kda_head_dim: int = 128
    # ffn == "moe": the k experts are chosen inside the moe_groups_kept best
    # of moe_groups groups of experts (moe._route; 1 and 1: among all).
    moe_groups: int = 1
    moe_groups_kept: int = 1
    # Granite's four scalars (``granitemoehybrid``; each at its default
    # leaves the program as it was): the embedding times
    # embedding_multiplier; every branch (mixer and FFN) times
    # residual_multiplier before it is added to the stream; attention's
    # scores times attention_multiplier in place of head_dim ** -0.5 (None);
    # the logits divided by logits_scaling.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    # Manifold-constrained hyper-connections (models/hyper_connections.py,
    # Xing4.0's ``hc_*`` keys), where hc_mult is set: the residual stream is
    # hc_mult streams of d_model, every sublayer reads one mix of them and
    # writes back to all, and the matrix that carries the streams past it is
    # made doubly stochastic by hc_sinkhorn_iters Sinkhorn normalisations of
    # the exponential of its logits clipped to -+hc_res_clamp, hc_eps in
    # their denominators.  0: the pre-norm add, one stream.
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # YaRN (arXiv:2309.00071) on latent attention's rotary columns, as
    # DeepSeek-V3's rotary embedding has it, where yarn_factor is above 1:
    # frequencies that turn less than yarn_beta_slow times in
    # yarn_original_max_len positions are divided by the factor, those that
    # turn more than yarn_beta_fast times are kept, the others blended;
    # cosines and sines times the ratio of the two mscales (0.1 m ln factor +
    # 1) and the scores' scale times the second one's square.
    yarn_factor: float = 1.0
    yarn_original_max_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # A learned indexer in front of every attention layer (models/indexer.py,
    # DeepSeek Sparse Attention's; Keye-VL-2.0's ``sa_config``), where
    # indexer_topk is set: indexer_heads heads of indexer_head_dim on one
    # shared key score every causal pair, and a query attends to the
    # indexer_topk keys of its largest scores (all of them below that many
    # positions).  The indexer's loss leaves through the ``indexer``
    # collection.  0: no indexer, every causal key.
    indexer_heads: int = 0
    indexer_head_dim: int = 64
    indexer_topk: int = 0

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.num_heads

    def layer_kind(self, i: int) -> LayerKind:
        if self.layer_pattern is None:
            return LayerKind()
        if self.num_layers % len(self.layer_pattern):
            raise ValueError(f"{self.num_layers} layers are no whole periods "
                             f"of {len(self.layer_pattern)}")
        if i >= self.num_layers:
            # A prediction module's block: the stack's last layer's kind.
            i = self.num_layers - 1
        return LayerKind(*self.layer_pattern[i % len(self.layer_pattern)])

    def kind_heads(self, i: int) -> int:
        """The query heads of block ``i``'s attention."""
        return self.layer_kind(i).heads or self.num_heads

    @property
    def num_blocks(self) -> int:
        """The stack's layers and the prediction modules' blocks behind."""
        return self.num_layers + self.mtp_modules

    def expert_layers(self) -> Tuple[int, ...]:
        """The indices of the blocks whose FFN (their kind's, else the
        configuration's) is the sparse-expert one."""
        return tuple(i for i in range(self.num_blocks)
                     if (self.layer_kind(i).ffn or self.ffn) == "moe")


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


def sdar_30b_a3b_config(**overrides) -> TransformerConfig:
    """SDAR-30B-A3B-Chat (JetLM/SDAR-30B-A3B-Chat ``config.json``,
    ``sdar_moe``): 48 layers of 128 experts of width 768, 8 a token with
    renormalised weights, 32 query heads on 4 KV heads of 128 (not
    2048 / 32), per-head QK-norm, RoPE at 1e6, RMSNorm, no biases, untied
    head; trained by block diffusion (``block_diffusion``: the block length
    is not in ``config.json``; 4 is the released chat models' default)."""
    return TransformerConfig(**{**dict(
        vocab_size=151936, num_layers=48, num_heads=32, num_kv_heads=4,
        head_width=128, d_model=2048, d_ff=768, max_len=32768, causal=False,
        norm="rmsnorm", norm_eps=1e-6, positions="rope", rope_theta=1e6,
        qk_norm="head", use_bias=False, tie_embeddings=False, ffn="moe",
        num_experts=128, experts_per_token=8, norm_topk_prob=True,
        block_diffusion=4), **overrides})


def smallthinker_21b_a3b_config(**overrides) -> TransformerConfig:
    """SmallThinker-21BA3B-Instruct (PowerInfer/SmallThinker-21BA3B-Instruct
    ``config.json``): 52 layers in periods of four, the first of each global
    and without positions, the other three inside a window of 4096 with RoPE
    at 1.5e6; 28 query heads on 4 KV heads of 128 (not 2560 / 28), no
    QK-norm; every layer 64 relu-gated experts of width 768, 6 a token with
    renormalised weights, routed by the block's input; RMSNorm, no biases,
    untied head."""
    return TransformerConfig(**{**dict(
        vocab_size=151936, num_layers=52, num_heads=28, num_kv_heads=4,
        head_width=128, d_model=2560, d_ff=768, max_len=16384, causal=True,
        norm="rmsnorm", norm_eps=1e-6, positions="rope", rope_theta=1.5e6,
        use_bias=False, tie_embeddings=False, ffn="moe", num_experts=64,
        experts_per_token=6, norm_topk_prob=True, router_input="block",
        expert_activation="relu",
        layer_pattern=(LayerKind(0, False),) + (LayerKind(4096, True),) * 3),
        **overrides})


def lfm2_8b_a1b_config(**overrides) -> TransformerConfig:
    """LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B ``config.json``, ``lfm2_moe``): 24
    layers, 18 of them gated short convolutions of 3 taps and 6 (layers 2, 6,
    10, 14, 18, 21) causal attention of 32 query heads on 8 KV heads of 64
    with per-head QK-norm and RoPE at 1e6; the first two layers carry a
    dense SwiGLU of width 7168, the others 32 experts of width 1792, 4 a
    token, chosen by sigmoid scores plus a bias the step keeps
    (``expert_bias``), weighted by the scores renormalised; RMSNorm at 1e-5,
    no biases, a tied readout."""
    attention = (2, 6, 10, 14, 18, 21)
    pattern = tuple(
        LayerKind(0, True, "attention" if i in attention else "conv",
                  "dense" if i < 2 else None) for i in range(24))
    return TransformerConfig(**{**dict(
        vocab_size=65536, num_layers=24, num_heads=32, num_kv_heads=8,
        head_width=64, d_model=2048, d_ff=1792, d_ff_dense=7168,
        max_len=128000, causal=True, norm="rmsnorm", norm_eps=1e-5,
        positions="rope", rope_theta=1e6, qk_norm="head", use_bias=False,
        tie_embeddings=True, ffn="moe", num_experts=32, experts_per_token=4,
        norm_topk_prob=True, router_scoring="sigmoid", expert_bias=True,
        layer_pattern=pattern), **overrides})


# Nemotron-H's letters for a layer: a Mamba-2 mixer, an expert FFN or an
# attention layer, each alone under one norm; attention carries no positions
# (arXiv:2504.03624).
HYBRID_KINDS = {"M": LayerKind(0, False, "mamba2", "none"),
                "E": LayerKind(0, False, "none", "moe"),
                "*": LayerKind(0, False, "attention", "none")}


def hybrid_pattern(letters: str) -> Tuple[LayerKind, ...]:
    """The layers' kinds of a ``hybrid_override_pattern``."""
    return tuple(HYBRID_KINDS[letter] for letter in letters)


def nemotron_3_super_config(**overrides) -> TransformerConfig:
    """NVIDIA-Nemotron-3-Super-120B-A12B (``config.json`` of the BF16
    release, ``nemotron_h``): 88 layers, each a Mamba-2 mixer (40: 128 heads
    of 64 in 8 groups, state 128, 4 taps), a LatentMoE (40: 512 experts
    ``down(relu(up x)^2)`` of width 2688 on a latent of 1024, 22 a token by
    sigmoid scores plus a bias the step keeps, weights renormalised and
    times 5, beside a shared expert of width 5376 on the full width) or
    causal attention without positions (8: 32 query heads on 2 KV heads of
    128), under one RMSNorm at 1e-5; no biases, an untied head.  The
    multi-token-prediction module behind the stack is not built."""
    letters = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
               "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    return TransformerConfig(**{**dict(
        vocab_size=131072, num_layers=88, num_heads=32, num_kv_heads=2,
        head_width=128, d_model=4096, d_ff=2688, max_len=262144, causal=True,
        norm="rmsnorm", norm_eps=1e-5, positions="rope", use_bias=False,
        tie_embeddings=False, ffn="moe", num_experts=512,
        experts_per_token=22, norm_topk_prob=True, router_scoring="sigmoid",
        expert_bias=True, routed_scaling_factor=5.0, expert_gate=False,
        expert_activation="relu2", moe_latent=1024, d_ff_shared=5376,
        mamba_heads=128, mamba_head_dim=64, mamba_groups=8, mamba_state=128,
        mamba_conv=4, mamba_chunk=128,
        layer_pattern=hybrid_pattern(letters)), **overrides})


def joyai_llm_flash_config(**overrides) -> TransformerConfig:
    """JoyAI-LLM-Flash (jdopensource/JoyAI-LLM-Flash ``config.json``,
    ``joyai_llm_flash``; every key is DeepSeek-V3's, arXiv:2412.19437): 40
    layers of latent attention, 32 heads whose keys are 128 without positions
    beside a rotary 64 that the heads share (interleaved pairs, theta 3.2e7)
    over values of 128, through latents of 1536 (queries) and 512 (keys and
    values); layer 0 a dense SwiGLU of width 7168, the others 256 experts of
    width 768, 8 a token by sigmoid scores plus a bias the step keeps,
    weights renormalised and times 2.5, beside a gated shared expert of
    width 768; one multi-token-prediction module behind the stack; RMSNorm
    at 1e-6, no biases, an untied head."""
    pattern = tuple(LayerKind(ffn="dense" if i < 1 else None)
                    for i in range(40))
    return TransformerConfig(**{**dict(
        vocab_size=129280, num_layers=40, num_heads=32, d_model=2048,
        d_ff=768, d_ff_dense=7168, d_ff_shared=768, max_len=131072,
        causal=True, norm="rmsnorm", norm_eps=1e-6, positions="rope",
        rope_theta=3.2e7, rope_interleave=True, use_bias=False,
        tie_embeddings=False, ffn="moe", num_experts=256,
        experts_per_token=8, norm_topk_prob=True, router_scoring="sigmoid",
        expert_bias=True, routed_scaling_factor=2.5, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, mtp_modules=1, layer_pattern=pattern), **overrides})


def qwen3_next_80b_a3b_config(**overrides) -> TransformerConfig:
    """Qwen3-Next-80B-A3B-Instruct (Qwen/Qwen3-Next-80B-A3B-Instruct
    ``config.json``, ``qwen3_next``): 48 layers in periods of four, three
    Gated DeltaNet mixers (16 key heads serving 32 value heads of 128, 4
    taps) then one causal attention layer of 16 query heads on 2 KV heads of
    256 with a sigmoid gate on its output, per-head QK-norm and RoPE at 1e7
    over the first quarter of a head; every layer 512 experts of width 512,
    10 a token by a softmax renormalised, beside a shared expert of width
    512 behind a sigmoid gate; RMSNorm with a scale of ``1 + w`` at 1e-6, no
    biases, an untied head.  The multi-token-prediction weights of the
    release are not in ``config.json`` and are not built."""
    pattern = (LayerKind(mixer="gated_delta"),) * 3 + (LayerKind(),)
    return TransformerConfig(**{**dict(
        vocab_size=151936, num_layers=48, num_heads=16, num_kv_heads=2,
        head_width=256, d_model=2048, d_ff=512, d_ff_shared=512,
        max_len=262144, causal=True, norm="rmsnorm", norm_eps=1e-6,
        norm_offset=True, positions="rope", rope_theta=1e7,
        partial_rotary_factor=0.25, qk_norm="head", attention_gate=True,
        use_bias=False, tie_embeddings=False, ffn="moe", num_experts=512,
        experts_per_token=10, norm_topk_prob=True, shared_expert_gate=True,
        gdn_key_heads=16, gdn_value_heads=32, gdn_key_dim=128,
        gdn_value_dim=128, gdn_conv=4,
        layer_pattern=pattern), **overrides})


def granite_4_0_h_micro_config(**overrides) -> TransformerConfig:
    """granite-4.0-h-micro (ibm-granite/granite-4.0-h-micro ``config.json``,
    ``granitemoehybrid``): 40 layers in periods of ten, the sixth of each
    (5, 15, 25, 35) causal attention of 32 query heads on 8 KV heads of 64
    without positions, the other nine Mamba-2 mixers of 64 heads of 64 in
    one group, state 128, 4 taps with a bias; every layer a mixer and a
    dense SwiGLU of width 8192 (no experts), each under its own RMSNorm at
    1e-5; no biases, a tied readout, and four scalars: the embedding times
    12, each branch times 0.22, the scores times 1/64 and the logits over
    8."""
    pattern = tuple(
        LayerKind(0, False, "attention" if i == 5 else "mamba2", "dense")
        for i in range(10))
    return TransformerConfig(**{**dict(
        vocab_size=100352, num_layers=40, num_heads=32, num_kv_heads=8,
        head_width=64, d_model=2048, d_ff=8192, d_ff_dense=8192,
        max_len=131072, causal=True, norm="rmsnorm", norm_eps=1e-5,
        positions="rope", use_bias=False, tie_embeddings=True, ffn="dense",
        mamba_heads=64, mamba_head_dim=64, mamba_groups=1, mamba_state=128,
        mamba_conv=4, mamba_chunk=128, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.015625,
        logits_scaling=8.0, layer_pattern=pattern), **overrides})


def xing4_0_29b_a4b_config(**overrides) -> TransformerConfig:
    """Xing4.0-29B-A4B (XingChen-AGI/Xing4.0-29B-A4B ``config.json``,
    ``xing4_0``; DeepSeek-V3's keys and the ``hc_*`` keys of
    manifold-constrained hyper-connections): 40 layers around **four residual
    streams** of 3584 (``models/hyper_connections.py``: every sublayer reads
    one mix of them and writes back to all, the matrix that carries them
    past it made doubly stochastic by 20 Sinkhorn iterations); latent
    attention of 32 heads (keys 128 + a rotary 64 over values of 128,
    latents of 768 and 512) under YaRN (factor 64 from 4096 positions: the
    scores' scale times 2.005); layers 0 and 1 a dense SwiGLU of width 9216,
    the others 64 experts of width 1024, 4 a token by sigmoid scores plus a
    bias the step keeps, weights renormalised and times 2, beside a shared
    expert of width 1024; RMSNorm at 1e-6, no biases, an untied head.  The
    multi-token-prediction module is not built under several streams."""
    pattern = tuple(LayerKind(ffn="dense" if i < 2 else None)
                    for i in range(40))
    return TransformerConfig(**{**dict(
        vocab_size=131072, num_layers=40, num_heads=32, d_model=3584,
        d_ff=1024, d_ff_dense=9216, d_ff_shared=1024, max_len=262144,
        causal=True, norm="rmsnorm", norm_eps=1e-6, positions="rope",
        rope_theta=10000.0, rope_interleave=True, use_bias=False,
        tie_embeddings=False, ffn="moe", num_experts=64,
        experts_per_token=4, norm_topk_prob=True, router_scoring="sigmoid",
        expert_bias=True, routed_scaling_factor=2.0, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_res_clamp=30.0, yarn_factor=64.0, yarn_original_max_len=4096,
        yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=1.0,
        yarn_mscale_all_dim=1.0, layer_pattern=pattern), **overrides})


def laguna_s_2_1_config(**overrides) -> TransformerConfig:
    """Laguna-S-2.1 (poolside/Laguna-S-2.1 ``config.json``, ``laguna``): 48
    layers in periods of four whose attention differs by kind in three
    things at once: the first of each global, **48 query heads** under YaRN
    (theta 5e5, factor 128 from 8192 positions, cosines and sines times
    1.4852) over the first half of a head, the other three **72 query
    heads** under plain RoPE at 1e4 inside a window of 512; all on 8 KV
    heads of 128, a sigmoid gate a head on the attention's output; layer 0 a
    dense SwiGLU of width 12,288, the others 256 experts of width 1024, 10 a
    token by a softmax renormalised and times 2.5, beside an ungated shared
    expert of width 1024; RMSNorm at 1e-6, no biases, an untied head."""
    full = LayerKind(heads=48, rotary=Rotary(
        rope_theta=5e5, share=0.5, yarn_factor=128.0,
        yarn_original_max_len=8192, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
        attention_factor=1.4852030263919618))
    sliding = LayerKind(window=512, heads=72, rotary=Rotary(rope_theta=1e4))
    pattern = tuple(
        (sliding if i % 4 else full)._replace(ffn="dense" if i < 1 else None)
        for i in range(48))
    return TransformerConfig(**{**dict(
        vocab_size=100352, num_layers=48, num_heads=48, num_kv_heads=8,
        head_width=128, d_model=3072, d_ff=1024, d_ff_dense=12288,
        d_ff_shared=1024, max_len=1048576, causal=True, norm="rmsnorm",
        norm_eps=1e-6, positions="rope", use_bias=False,
        tie_embeddings=False, ffn="moe", num_experts=256,
        experts_per_token=10, norm_topk_prob=True, routed_scaling_factor=2.5,
        attention_gate="head", layer_pattern=pattern), **overrides})


def ling_3_0_flash_config(**overrides) -> TransformerConfig:
    """Ling-3.0-flash, the language model of Ling-3.0-flash-VL
    (inclusionAI/Ling-3.0-flash-VL ``config.json``, ``bailing_hybrid``): 42
    layers in periods of six (``layer_group_size``), five Kimi Delta Attention
    mixers (32 heads of 128, 4 taps, a decay a key channel behind a gate
    bounded at -5) then one layer of latent attention, 32 heads whose keys
    are 128 without positions beside a rotary 64 that the heads share (theta
    6e6) over values of 128, the keys and values through a latent of 512 and
    the queries straight from the stream (``q_lora_rank`` null), a sigmoid
    gate a head on its output; layers 0 and 1 a dense SwiGLU of width 6144,
    the others 512 experts of width 768, 8 a token by sigmoid scores plus a
    bias the step keeps, chosen inside the 4 best of 8 groups, weights
    renormalised and times 2.5, beside a shared expert of width 768; RMSNorm
    at 1e-6, no biases, an untied head.  Neither the vision tower nor the
    multi-token-prediction module is built."""
    pattern = tuple(
        LayerKind(mixer="attention" if (i + 1) % 6 == 0 else "kda",
                  ffn="dense" if i < 2 else None) for i in range(42))
    return TransformerConfig(**{**dict(
        vocab_size=157184, num_layers=42, num_heads=32, d_model=2560,
        d_ff=768, d_ff_dense=6144, d_ff_shared=768, max_len=131072,
        causal=True, norm="rmsnorm", norm_eps=1e-6, positions="rope",
        rope_theta=6e6, rope_interleave=True, use_bias=False,
        tie_embeddings=False, ffn="moe", num_experts=512,
        experts_per_token=8, norm_topk_prob=True, router_scoring="sigmoid",
        expert_bias=True, routed_scaling_factor=2.5, moe_groups=8,
        moe_groups_kept=4, q_lora_rank=0, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        attention_gate="head", kda_head_dim=128, conv_taps=4,
        layer_pattern=pattern), **overrides})


def keye_vl_2_0_30b_a3b_config(**overrides) -> TransformerConfig:
    """Keye-VL-2.0-30B-A3B's language model (Kwai-Keye/Keye-VL-2.0-30B-A3B
    ``config.json``, ``KeyeVL2``): 48 layers of 128 experts of width 768, 8 a
    token with renormalised weights, 32 query heads on 4 KV heads of 128,
    per-head QK-norm, RoPE at 1e7 (M-RoPE's three position streams coincide
    on text), RMSNorm, no biases, untied head, and ``sa_config``: an indexer
    of 16 heads of 64 on one shared key chooses the 2048 keys a query
    attends to."""
    return TransformerConfig(**{**dict(
        vocab_size=151936, num_layers=48, num_heads=32, num_kv_heads=4,
        head_width=128, d_model=2048, d_ff=768, max_len=262144, causal=True,
        norm="rmsnorm", norm_eps=1e-6, positions="rope", rope_theta=1e7,
        qk_norm="head", use_bias=False, tie_embeddings=False, ffn="moe",
        num_experts=128, experts_per_token=8, norm_topk_prob=True,
        indexer_heads=16, indexer_head_dim=64, indexer_topk=2048),
        **overrides})


def tiny_config(**overrides) -> TransformerConfig:
    """For tests and the multichip dryrun: tiny shapes, same code paths."""
    return TransformerConfig(**{**dict(
        vocab_size=128, num_layers=2, num_heads=4, d_model=32,
        d_ff=64, max_len=64, causal=True), **overrides})


def _dense(cfg: TransformerConfig, features: int, kernel_spec, name: str,
           cls=nn.Dense):
    """Dense with a TP partitioning annotation on the kernel (``cls``: a
    subclass that does something else with the same parameters)."""
    return cls(
        features, dtype=cfg.dtype, param_dtype=jnp.float32, name=name,
        use_bias=cfg.use_bias,
        kernel_init=nn.with_partitioning(
            nn.initializers.normal(0.02), kernel_spec))


class _OffsetScale(nn.Module):
    """``x * (1 + scale)``, ``scale`` zero at the start: what stands behind
    an RMSNorm without a scale where ``cfg.norm_offset`` (the parameter keeps
    the name and the place ``nn.RMSNorm``'s has)."""

    epsilon: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                           jnp.float32)
        return nn.RMSNorm(epsilon=self.epsilon, dtype=jnp.float32,
                          use_scale=False, name="unit")(x) * (1.0 + scale)


def _norm(cfg: TransformerConfig, name: str):
    """The configuration's norm, computed and handed on in fp32."""
    if cfg.norm == "layernorm":
        return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                            name=name)
    if cfg.norm == "rmsnorm" and cfg.norm_offset:
        return _OffsetScale(cfg.norm_eps, name=name)
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)
    raise ValueError(f"unknown norm {cfg.norm!r}")


def _rope_angles(s: int, d: int, theta: float, positions=None,
                 inv_freq=None):
    """``[s, d / 2]`` in fp32: position times frequency, the pair ``i`` of a
    head of ``d`` at ``theta ** (-2 i / d)`` (or at ``inv_freq[i]``, where
    the frequencies are scaled: :func:`yarn_inv_freq`)."""
    if inv_freq is None:
        inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.float32)
    return positions.astype(jnp.float32)[:, None] * inv_freq[None]


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's ``0.1 mscale ln(factor) + 1`` (1 at a factor of 1 or less)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(cfg: "TransformerConfig", d: int):
    """``(low, high)``: the pairs of a head of ``d`` between which YaRN blends
    (DeepSeek-V3's ``yarn_find_correction_range``): pair ``c(r) = d
    ln(original / (2 pi r)) / (2 ln theta)`` turns ``r`` times in the
    original positions; floor of ``c(beta_fast)``, ceiling of
    ``c(beta_slow)``, inside ``0..d - 1``."""
    def pair(turns):
        return d * math.log(cfg.yarn_original_max_len
                            / (turns * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    return (max(math.floor(pair(cfg.yarn_beta_fast)), 0),
            min(math.ceil(pair(cfg.yarn_beta_slow)), d - 1))


def yarn_inv_freq(cfg: "TransformerConfig", d: int):
    """``[d / 2]`` in fp32: pair ``i``'s frequency ``(1 - g_i) f_i / factor +
    g_i f_i``, ``f_i = theta ** (-2 i / d)``, ``g_i = 1 - clip((i - low) /
    (high - low), 0, 1)``."""
    low, high = yarn_correction_range(cfg, d)
    if low == high:
        high += 0.001
    plain = 1.0 / cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                     / d)
    kept = 1.0 - jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                          / (high - low), 0.0, 1.0)
    return plain / cfg.yarn_factor * (1.0 - kept) + plain * kept


def _rope(x, theta: float, positions=None, share: float = 1.0,
          rotary: Optional[Rotary] = None):
    """Rotary positions on ``[b, s, h, d]``, halves rotated as in
    ``transformers`` (``x*cos + rotate_half(x)*sin``), in fp32.  ``positions``
    ``[s]``: each position's index (default ``0..s-1``).  ``share`` below 1:
    only the first ``share * d`` of a head are turned, as a head of that
    width, and the rest goes through as it is.  ``rotary``: a layer kind's
    own table in place of ``theta`` and ``share``."""
    if rotary is not None:
        theta, share = rotary.rope_theta, rotary.share
    if share != 1.0:
        turned = int(x.shape[3] * share)
        if rotary is not None:
            rotary = rotary._replace(share=1.0)
        return jnp.concatenate(
            [_rope(x[..., :turned], theta, positions, rotary=rotary),
             x[..., turned:]], axis=-1)
    scaled = rotary is not None and rotary.yarn_factor > 1.0
    angles = _rope_angles(x.shape[1], x.shape[3], theta, positions,
                          yarn_inv_freq(rotary, x.shape[3]) if scaled
                          else None)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if scaled:
        factor = rotary.attention_factor \
            or yarn_mscale(rotary.yarn_factor, 1.0)
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _turned_width(d: int, share: float, rotary: Optional[Rotary]) -> int:
    """How many of a head's ``d`` columns :func:`_rope` turns."""
    return int(d * (share if rotary is None else rotary.share))


def _rope_tables(s: int, d: int, theta: float, positions=None,
                 share: float = 1.0, rotary: Optional[Rotary] = None):
    """:func:`_rope`'s cosines and sines over ``s`` positions of heads of
    ``d`` as ``kernels/rope_operands.py`` takes them (``[s, d]`` each, 1 and
    0 over the columns a share below 1 leaves alone), and how far apart the
    two columns of a pair lie."""
    turned = _turned_width(d, share, rotary)
    if rotary is not None:
        theta = rotary.rope_theta
    scaled = rotary is not None and rotary.yarn_factor > 1.0
    angles = _rope_angles(s, turned, theta, positions,
                          yarn_inv_freq(rotary, turned) if scaled else None)
    factor = 1.0 if not scaled else \
        rotary.attention_factor or yarn_mscale(rotary.yarn_factor, 1.0)
    return *rope_operands.tables(angles, d, factor), turned // 2


class Attention(nn.Module):
    cfg: TransformerConfig
    kind: LayerKind = LayerKind()

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.cfg
        b, s, _ = x.shape
        h, dh = self.kind.heads or cfg.num_heads, cfg.head_dim
        h_kv = cfg.num_kv_heads or h
        if h % h_kv:
            raise ValueError(f"{h} heads on {h_kv} KV heads")
        with scope("attn.proj"):
            if cfg.num_kv_heads is None:
                if cfg.attention_gate:
                    raise ValueError("the output gate is written for the "
                                     "split q and kv projections "
                                     "(num_kv_heads)")
                # Column-parallel qkv: heads split over the model axis.
                qkv = _dense(cfg, 3 * h * dh, (None, cfg.model_axis),
                             "qkv")(x)
                q, k, v = jnp.split(qkv.reshape(b, s, 3 * h, dh), 3, axis=2)
            elif cfg.attention_gate is True:
                # A head's columns are its query, then its gate.
                q, gate = jnp.split(
                    _dense(cfg, 2 * h * dh, (None, cfg.model_axis),
                           "q")(x).reshape(b, s, h, 2 * dh), 2, axis=-1)
            else:
                q = _dense(cfg, h * dh, (None, cfg.model_axis), "q")(x) \
                    .reshape(b, s, h, dh)
            if cfg.num_kv_heads is not None:
                kv = _dense(cfg, 2 * h_kv * dh, (None, cfg.model_axis),
                            "kv")(x)
                k, v = jnp.split(kv.reshape(b, s, 2 * h_kv, dh), 2, axis=2)
        if cfg.attention_gate == "head":
            # One column a head, read from what the queries read.
            with scope("attn.gate"):
                gate = _dense(cfg, h, (None, cfg.model_axis),
                              "gate")(x)[..., None]
        elif cfg.attention_gate not in (False, True):
            raise ValueError(f"unknown attention_gate "
                             f"{cfg.attention_gate!r}")
        with scope("attn.norm"):
            if cfg.qk_norm == "head":
                # Over each head's width; the heads share the scale.
                q = _norm(cfg, "q_norm")(q).astype(cfg.dtype)
                k = _norm(cfg, "k_norm")(k).astype(cfg.dtype)
            elif cfg.qk_norm:
                # Over the whole projection (all heads), as OLMoE has it.
                flat = lambda t: t.reshape(b, s, -1)  # noqa: E731
                q = _norm(cfg, "q_norm")(flat(q)).astype(cfg.dtype)
                k = _norm(cfg, "k_norm")(flat(k)).astype(cfg.dtype)
                q, k = q.reshape(b, s, h, dh), k.reshape(b, s, h_kv, dh)
        in_kernels_layout = False
        if cfg.positions == "rope" and self.kind.rope:
            if cfg.attention != "full":
                raise ValueError("rope positions need attention='full'")
            rotary = (cfg.rope_theta, positions, cfg.partial_rotary_factor,
                      self.kind.rotary)
            rule = _rule(cfg.causal, cfg.block_diffusion, self.kind.window,
                         h_kv != h, cfg.indexer_topk)
            turned = _turned_width(dh, cfg.partial_rotary_factor,
                                   self.kind.rotary)
            in_kernels_layout = rope_operands.takes(rule, s, dh, turned,
                                                    cfg.dtype)
            if not in_kernels_layout:
                with scope("attn.rope"):
                    q, k = _rope(q, *rotary), _rope(k, *rotary)
        elif cfg.indexer_topk:
            raise ValueError("an indexer turns its heads by the layer's "
                             "rotary positions: it needs positions='rope'")
        if (h_kv != h or cfg.block_diffusion or self.kind.window
                or cfg.indexer_topk) and cfg.attention != "full":
            raise ValueError("grouped KV heads, a window, an indexer and the "
                             "block-diffusion mask need attention='full'")

        if in_kernels_layout:
            # One pass turns q and k, scales q and writes both where the
            # attention kernels read them; v's copy and the output's stay.
            scale = dh ** -0.5 if cfg.attention_multiplier is None \
                else cfg.attention_multiplier
            if cfg.qk_norm:
                # The kernel reads q and k row-major.  A norm's fp32 rows
                # leave the products with the positions minor, and without
                # this XLA turns them (two fp32 copies of q a layer) in front
                # of the norm's last product and keeps them for the backward
                # (SDAR-30B-A3B: 1.5 GiB and 10 ms a step); with it the one
                # bf16 result is turned.
                q, k = lax.optimization_barrier((q, k))
            with scope("attn.rope"):
                *tables, half = _rope_tables(s, dh, *rotary)
                q, k = rope_operands.operands(
                    q.reshape(b, s, h * dh), k.reshape(b, s, h_kv * dh),
                    *tables, scale, half=half)
            with scope("attn.layout"):
                v = v.transpose(0, 2, 1, 3)
            if cfg.indexer_topk:
                out = self._over_chosen_keys(x, q, k, v, rule, positions)
            else:
                out = masked_attention.attention_hsd(q, k, v, rule)
            with scope("attn.layout"):
                out = out.transpose(0, 2, 1, 3)
        elif cfg.indexer_topk:
            hsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
            scale = dh ** -0.5 if cfg.attention_multiplier is None \
                else cfg.attention_multiplier
            with scope("attn.layout"):
                q, k, v = hsd(q * jnp.asarray(scale, q.dtype)), hsd(k), hsd(v)
            out = self._over_chosen_keys(x, q, k, v, rule, positions)
            with scope("attn.layout"):
                out = hsd(out)
        elif cfg.attention == "ring":
            from ..parallel.ring_attention import ring_attention

            with scope("attn.ring"):
                out = ring_attention(q, k, v, axis_name=cfg.seq_axis,
                                     causal=cfg.causal,
                                     sm_scale=cfg.attention_multiplier)
        elif cfg.attention == "ulysses":
            from ..parallel.ulysses import ulysses_attention

            with scope("attn.ulysses"):
                out = ulysses_attention(q, k, v, axis_name=cfg.seq_axis,
                                        causal=cfg.causal,
                                        sm_scale=cfg.attention_multiplier)
        elif cfg.attention == "full":
            out = _scaled_dot_attention(q, k, v, cfg.causal, dh,
                                        block_diffusion=cfg.block_diffusion,
                                        window=self.kind.window,
                                        scale=cfg.attention_multiplier)
        else:
            raise ValueError(f"unknown attention mode {cfg.attention!r}")

        if cfg.attention_gate:
            with scope("attn.gate"):
                out = (out * jax.nn.sigmoid(gate.astype(jnp.float32))) \
                    .astype(cfg.dtype)
        with scope("attn.proj"):
            out = out.reshape(b, s, h * dh)
            # Row-parallel output projection closes the TP pair.
            return _dense(cfg, cfg.d_model, (cfg.model_axis, None),
                          "out")(out)

    def _over_chosen_keys(self, x, q, k, v, rule, positions):
        """Attention over the keys the layer's indexer chooses from the
        normed input ``x``; ``q [b, h, s, d]`` (turned and scaled), ``k`` and
        ``v [b, h_kv, s, d]`` in the kernels' layout, as is the result.  The
        indexer's loss is sown into the ``indexer`` collection, the sets
        (``pack_chosen``'s words) into ``chosen``."""
        from . import indexer

        cfg = self.cfg
        s, dh = q.shape[2], q.shape[3]
        q_i, k_i, w = indexer.Indexer(cfg, name="indexer")(
            x, lambda t: _rope(t, cfg.rope_theta, positions))
        words, lse_i = indexer.choose(q_i, k_i, w, cfg.indexer_topk)
        if jax.default_backend() == "tpu" \
                and masked_attention.takes(rule, s, dh):
            out, lse = masked_attention.attention_lse_hsd(q, k, v, rule,
                                                          words)
        else:
            hsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
            lse = None
            out = hsd(masked_attention.einsum(hsd(q), hsd(k), hsd(v), rule,
                                              1.0, words))
        self.sow("indexer", "loss",
                 indexer.loss(q_i, k_i, w, words, lse_i, q, k, lse))
        # The sets themselves, for whoever asks (``mutable=["chosen"]``).
        self.sow("chosen", "words", words)
        return out


# The pallas flash kernel's blocks, and the shortest sequence it takes.  On a
# v5e at b=2, h=16, s=4096, d=128, causal, forward + backward (PERF.md, PR
# 27): the XLA-fused einsum 32.5 ms, the kernel with its default blocks of 128
# 36.9 ms, with blocks of 512 7.8 ms and of 1024 7.4 ms; it also keeps the
# [b, h, s, s] scores (2.1 GB in fp32 there) out of HBM.  Shorter sequences
# that kernels/short_attention.py takes (BERT-large's s=512 among them) keep
# a head's whole score tile in VMEM instead (PERF.md section 6, PR 37, has
# both kernels and the einsum side by side); the rest stays on the einsum.
_FLASH_BLOCK = 1024
_FLASH_MIN_SEQ = 4096


def _flash_attention(q, k, v, causal: bool, dh: int, scale=None):
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
    with scope("attn.layout"):
        q, k, v = bhsd(q), bhsd(k), bhsd(v)
    with scope("attn.flash"):
        o = flash_attention(q, k, v, causal=causal,
                            sm_scale=dh ** -0.5 if scale is None else scale,
                            block_sizes=blocks)
    with scope("attn.layout"):
        return o.transpose(0, 2, 1, 3)


def _rule(causal: bool, block_diffusion: int, window: int, grouped: bool,
          topk: int = 0):
    """The mask as a rule of ``kernels/masked_attention.py``: the
    block-diffusion rule with ``block_diffusion``, a block length, in place
    of ``causal``; causal inside ``window`` positions; causal inside the
    ``topk`` keys an indexer chooses (a rule that is data); plain causal
    where KV heads are ``grouped``; else None (one KV head a query head under
    ``causal``, no mask)."""
    if topk:
        if block_diffusion or window or not causal:
            raise ValueError("an indexer chooses among a causal layer's "
                             "keys: no window, no block diffusion")
        return masked_attention.Sparse(topk)
    if block_diffusion:
        return BlockDiffusion(block_diffusion)
    if window:
        if not causal:
            raise ValueError("a window is causal: it needs causal=True")
        return masked_attention.Window(window)
    if causal and grouped:
        return masked_attention.Causal()
    return None


def _scaled_dot_attention(q, k, v, causal: bool, dh: int,
                          block_diffusion: int = 0, window: int = 0,
                          scale=None):
    """Single-device attention for the "full" mode, [b, s, h, d] layout.
    ``scale``: what the scores are multiplied by where that is not
    ``dh ** -0.5`` (``cfg.attention_multiplier``).

    A mask that is a rule of ``kernels/masked_attention.py`` (the
    block-diffusion rule with ``block_diffusion``, a block length, in place
    of ``causal``; causal inside ``window`` positions; plain causal where KV
    heads are grouped) goes on a TPU to that module's kernel wherever it
    takes the shape, KV heads grouped and not repeated, and else through its
    einsum under the same mask.  A kernel that fails to lower fails the
    step: it is never silently the einsum.  Everything else (one KV head a
    query head under ``causal``, no mask) goes on a TPU, by its shape alone,
    to the pallas flash kernel from ``_FLASH_MIN_SEQ`` positions on and to
    ``kernels/short_attention.py`` at the lengths it takes; else the einsum."""
    s = q.shape[1]
    grouped = k.shape[2] != q.shape[2]
    rule = _rule(causal, block_diffusion, window, grouped)
    if rule is not None:
        if jax.default_backend() == "tpu" \
                and masked_attention.takes(rule, s, dh):
            return masked_attention.attention(q, k, v, rule, scale=scale)
        return masked_attention.einsum(q, k, v, rule, scale)
    if grouped:
        # Grouped KV heads under no mask: each KV head repeated for the query
        # heads it serves, then the paths below.
        group = q.shape[2] // k.shape[2]
        with scope("attn.layout"):
            k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    if jax.default_backend() == "tpu" and s >= _FLASH_MIN_SEQ \
            and s % _FLASH_BLOCK == 0 and dh % 128 == 0:
        return _flash_attention(q, k, v, causal, dh, scale)
    # The short kernel scales by dh ** -0.5 inside: a scale of its own goes
    # through the einsum.
    if jax.default_backend() == "tpu" and scale is None \
            and short_attention.takes(s, dh, q.shape[2], q.dtype):
        with scope("attn.short"):
            return short_attention.attention(q, k, v, causal)
    with scope("attn.einsum"):
        if scale is None:
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


class ShortConv(nn.Module):
    """LFM2's token mixer in place of attention: ``[B, C, X] = x W_in``,
    ``y = (C * conv(B * X)) W_out``, the convolution depthwise, causal and
    ``cfg.conv_taps`` long, with no activation but the two gates
    (``kernels/short_conv.py``: its kernels on a TPU, ``jax.numpy``
    elsewhere).  The projections lie under ``conv.proj``, the gates and taps
    under ``conv.gate``."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        with scope("conv.proj"):
            bcx = _dense(cfg, 3 * cfg.d_model, (None, cfg.model_axis),
                         "in_proj")(x)
        taps = self.param("conv", nn.initializers.normal(0.02),
                          (cfg.d_model, cfg.conv_taps), jnp.float32)
        with scope("conv.gate"):
            y = short_conv.gated_conv(bcx, taps)
        with scope("conv.proj"):
            return _dense(cfg, cfg.d_model, (cfg.model_axis, None),
                          "out_proj")(y)


def _branch(cfg: TransformerConfig, y):
    """A block's branch as it is added to the stream: times
    ``cfg.residual_multiplier`` where that is not 1 (Granite)."""
    if cfg.residual_multiplier == 1.0:
        return y
    return y * jnp.asarray(cfg.residual_multiplier, y.dtype)


class Block(nn.Module):
    cfg: TransformerConfig
    kind: LayerKind = LayerKind()

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.cfg
        block_input = x
        mixer, ffn = self.kind.mixer, self.kind.ffn or cfg.ffn
        if mixer == ffn == "none":
            raise ValueError("a layer of neither mixer nor FFN")
        if cfg.hc_mult and cfg.router_input == "block" and ffn == "moe":
            raise ValueError("a router that reads the block's input reads "
                             "one stream: not written under hc_mult")
        if mixer != "none":
            if cfg.hc_mult:
                x, back = self._hyper_connection("hc_mixer", 0)(x)
            with scope("norm"):
                y = _norm(cfg, "ln1")(x)
            if mixer == "conv":
                y = ShortConv(cfg, name="conv")(y)
            elif mixer == "attention" and cfg.kv_lora_rank:
                from .deepseek import LatentAttention

                if cfg.attention_multiplier is not None:
                    raise ValueError("latent attention scales by its keys' "
                                     "width: no attention_multiplier")

                y = LatentAttention(cfg, self.kind, name="attn")(y, positions)
            elif mixer == "attention":
                y = Attention(cfg, self.kind, name="attn")(y, positions)
            elif mixer == "mamba2":
                # Here, not at the top: the mixer and its kernel load where
                # a configuration asks for them.
                from .mamba2 import Mamba2

                y = Mamba2(cfg, name="mamba")(y)
            elif mixer == "gated_delta":
                from .gated_delta import GatedDeltaNet

                y = GatedDeltaNet(cfg, name="gdn")(y)
            elif mixer == "kda":
                from .kda import KimiDeltaAttention

                y = KimiDeltaAttention(cfg, name="kda")(y)
            else:
                raise ValueError(f"unknown mixer {mixer!r}")
            if cfg.hc_mult:
                x = back(_branch(cfg, y))
            else:
                with scope("norm"):
                    x = x + _branch(cfg, y)
        if ffn == "none":
            return x
        if cfg.hc_mult:
            x, back = self._hyper_connection("hc_ffn", 1)(x)
        with scope("norm"):
            ln2 = _norm(cfg, "ln2")
            y = ln2(x)
        if ffn == "gelu":
            with scope("ffn"):
                y = _dense(cfg, cfg.d_ff, (None, cfg.model_axis),
                           "ffn_in")(y)
                y = nn.gelu(y)
                y = _dense(cfg, cfg.d_model, (cfg.model_axis, None),
                           "ffn_out")(y)
        elif ffn == "dense":
            with scope("ffn"):
                hidden = nn.silu(_dense(cfg, cfg.d_ff_dense,
                                        (None, cfg.model_axis),
                                        "ffn_gate")(y)) \
                    * _dense(cfg, cfg.d_ff_dense, (None, cfg.model_axis),
                             "ffn_up")(y)
                y = _dense(cfg, cfg.d_model, (cfg.model_axis, None),
                           "ffn_down")(hidden)
        elif ffn == "moe":
            if cfg.router_input not in ("ffn", "block"):
                raise ValueError(f"unknown router_input {cfg.router_input!r}")
            if cfg.router_input == "block":
                router_input = block_input
            elif cfg.norm == "rmsnorm" \
                    and router_product_passes(x.dtype) == 3:
                # y as the product it is, so that the router multiplies the
                # bf16 stream itself (moe.RouterRows); the statistics are
                # the norm's own formula, which XLA computes once (held to
                # ln2's output in tests/test_lfm2.py).
                with scope("norm"):
                    mean2 = jnp.mean(jnp.square(x.astype(jnp.float32)),
                                     axis=-1)
                    scale = ln2.variables["params"]["scale"]
                    router_input = RouterRows(
                        x, lax.rsqrt(mean2 + cfg.norm_eps),
                        1.0 + scale if cfg.norm_offset else scale)
            else:
                router_input = None
            y = self._experts(y, router_input)
        else:
            raise ValueError(f"unknown ffn {ffn!r}")
        if cfg.hc_mult:
            return back(_branch(cfg, y))
        with scope("norm"):
            return x + _branch(cfg, y)

    def _hyper_connection(self, name: str, sublayer: int):
        """The hyper-connection around this block's mixer (``sublayer`` 0) or
        FFN (1): ``streams -> (the sublayer's input, back)``.  A fresh one
        reads the stream of its sublayer's number among the model's (a block
        built by itself is layer 0).  Loaded here: the module loads where a
        configuration with ``hc_mult`` builds a block."""
        from .hyper_connections import HyperConnection

        layer = self.name.rsplit("_", 1)[-1] if self.name else "0"
        read = 2 * int(layer) + sublayer if layer.isdigit() else sublayer
        return HyperConnection(self.cfg, read, name=name)

    def _experts(self, y, router_input=None):
        """The sparse-expert FFN; its MoEStats are sown into the ``moe``
        collection (``apply(..., mutable=["moe"])``, then ``moe_stats``)."""
        cfg = self.cfg
        d, f = cfg.d_model, cfg.d_ff
        # What the routed experts multiply: the model's width, or a latent.
        width = cfg.moe_latent or d
        # The router is as wide as the model has experts; the stacks hold
        # the experts that live here.
        routed = cfg.num_experts
        e = routed if cfg.experts_held is None else len(cfg.experts_held)
        init = nn.initializers.normal(0.02)
        shapes = {"router": (d, routed), "experts_gate": (e, width, f),
                  "experts_up": (e, width, f), "experts_down": (e, f, width)}
        if not cfg.expert_gate:
            del shapes["experts_gate"]
        router, *stacks = [self.param(name, init, shape, jnp.float32)
                           for name, shape in shapes.items()]
        gate = stacks.pop(0) if cfg.expert_gate else None
        rows = y
        if cfg.moe_latent:
            with scope("moe.latent"):
                rows = _dense(cfg, width, (None, cfg.model_axis),
                              "latent_in")(y)
            if router_input is None:
                router_input = y
        # The selection bias is read here and stepped by the training step
        # (``expert_bias_collection``, ``moe.update_expert_bias``).
        bias = self.variable(
            "moe", "bias", jnp.zeros, (routed,), jnp.float32).value \
            if cfg.expert_bias else None
        out, stats = moe_ffn(rows, router, gate, *stacks,
                             k=cfg.experts_per_token,
                             data_axis=cfg.moe_data_axis, dtype=cfg.dtype,
                             held=cfg.experts_held,
                             norm_topk_prob=cfg.norm_topk_prob,
                             router_input=router_input,
                             activation=cfg.expert_activation,
                             scoring=cfg.router_scoring, bias=bias,
                             scale=cfg.routed_scaling_factor,
                             n_group=cfg.moe_groups,
                             topk_group=cfg.moe_groups_kept)
        self.sow("moe", "stats", stats)
        self._publish_router_product(
            rows if router_input is None
            else getattr(router_input, "rows", router_input))
        if cfg.moe_latent:
            # Of a share: applied to its own experts' partial sum.
            with scope("moe.latent"):
                out = _dense(cfg, d, (cfg.model_axis, None),
                             "latent_out")(out)
        if cfg.shared_expert_gate and not cfg.d_ff_shared:
            raise ValueError("shared_expert_gate gates the shared expert: "
                             "d_ff_shared is 0")
        if cfg.d_ff_shared:
            # Every chip that shares the layer computes it alike: the sum
            # over the shares counts it once.
            with scope("moe.shared"):
                act = _activation(cfg.expert_activation)
                up = _dense(cfg, cfg.d_ff_shared, (None, cfg.model_axis),
                            "shared_up")(y)
                hidden = act(_dense(cfg, cfg.d_ff_shared,
                                    (None, cfg.model_axis),
                                    "shared_gate")(y)) * up \
                    if cfg.expert_gate else act(up)
                shared = _dense(cfg, d, (cfg.model_axis, None),
                                "shared_down")(hidden)
                if not cfg.shared_expert_gate:
                    out = out + shared
            if cfg.shared_expert_gate:
                # One logit a token; every share gates alike.
                with scope("moe.shared_gate"):
                    logit = _dense(cfg, 1, (None, None),
                                   "shared_expert_gate")(y)
                    out = out + shared * jax.nn.sigmoid(
                        logit.astype(jnp.float32)).astype(shared.dtype)
        return out

    def _publish_router_product(self, read):
        """Set the gauge ``moe_router_product_passes`` of this layer, numbered
        among the expert layers as ``moe.publish_routing`` numbers them, from
        the dtype of the rows ``read`` that its router reads; runs where the
        layer is traced (a block built by itself has no number and sets
        none)."""
        from ..core import metrics

        layers = [f"layer_{i}" for i in self.cfg.expert_layers()]
        if self.name in layers:
            metrics.set_gauge("moe_router_product_passes",
                              router_product_passes(read.dtype),
                              layer=str(layers.index(self.name)))


def moe_stats(collection) -> MoEStats:
    """The layers' ``MoEStats`` out of the ``moe`` collection that
    ``apply(..., mutable=["moe"])`` returns, stacked on a leading layer
    axis: ``[layers, sets]`` losses and ``[layers, sets, experts]`` counts."""
    layers = sorted(collection, key=lambda name: int(name.split("_")[-1]))
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[collection[n]["stats"][0] for n in layers])


def expert_bias_collection(cfg: TransformerConfig, bias) -> dict:
    """The ``moe`` collection that hands ``bias [expert layers, experts]``
    to the layers of a model with ``cfg.expert_bias``:
    ``model.apply({"params": p, "moe": this}, tokens, mutable=["moe"])``."""
    return {f"layer_{i}": {"bias": bias[j]}
            for j, i in enumerate(cfg.expert_layers())}


def attention_pairs(cfg: TransformerConfig, seq_len: int,
                    by_head: bool = False) -> dict:
    """``{"window": n, "global": n}``: the (query, key) pairs the masks of
    one sequence of ``seq_len`` positions allow, summed over the blocks (the
    stack's and the prediction modules') that attend inside a window and
    over those under the model's own mask; from the shapes and the rules
    alone.  ``by_head``: each block's pairs times its query heads (its
    kind's, else the model's)."""
    if cfg.block_diffusion:
        everywhere = BlockDiffusion(cfg.block_diffusion).allowed_pairs(seq_len)
    elif cfg.indexer_topk:
        everywhere = masked_attention.Sparse(cfg.indexer_topk) \
            .allowed_pairs(seq_len)
    elif cfg.causal:
        everywhere = masked_attention.Causal().allowed_pairs(seq_len)
    else:
        everywhere = seq_len * seq_len
    pairs = {"window": 0, "global": 0}
    for i in range(cfg.num_blocks):
        if cfg.layer_kind(i).mixer != "attention":
            continue
        window = cfg.layer_kind(i).window
        heads = cfg.kind_heads(i) if by_head else 1
        if window:
            pairs["window"] += heads * masked_attention.Window(window) \
                .allowed_pairs(seq_len)
        else:
            pairs["global"] += heads * everywhere
    return pairs


def publish_attention(cfg: TransformerConfig, seq_len: int,
                      sequences: int = 1) -> dict:
    """Set the gauges ``attn_allowed_pairs_per_step`` and
    ``attn_head_pairs_per_step`` (the pairs times each block's query heads:
    what the kernels' work goes by where the head count differs by kind) per
    ``kind=`` for a step of ``sequences`` sequences of ``seq_len``
    positions, and return the first's values; called outside the step,
    beside ``moe.publish_routing``."""
    from ..core import metrics

    pairs, head_pairs = ({kind: n * sequences for kind, n in attention_pairs(
        cfg, seq_len, by_head).items()} for by_head in (False, True))
    for kind in pairs:
        metrics.set_gauge("attn_allowed_pairs_per_step", float(pairs[kind]),
                          kind=kind)
        metrics.set_gauge("attn_head_pairs_per_step",
                          float(head_pairs[kind]), kind=kind)
    return pairs


def publish_gated_delta(cfg: TransformerConfig, seq_len: int,
                        sequences: int = 1) -> int:
    """Set the gauge ``gdn_chunks_per_step`` for a step of ``sequences``
    sequences of ``seq_len`` positions, and return it: the chunks of the
    gated delta rule, one a value head, ``kernels.gated_delta.CHUNK``
    positions (the last of a sequence filled) and layer of kind
    ``gated_delta``; from the shapes alone, called outside the step, beside
    :func:`publish_attention`."""
    from ..core import metrics
    from ..kernels.gated_delta import CHUNK

    layers = sum(cfg.layer_kind(i).mixer == "gated_delta"
                 for i in range(cfg.num_blocks))
    chunks = layers * sequences * cfg.gdn_value_heads \
        * -(-seq_len // CHUNK)
    metrics.set_gauge("gdn_chunks_per_step", float(chunks))
    return chunks


def publish_kda(cfg: TransformerConfig, seq_len: int,
                sequences: int = 1) -> int:
    """Set the gauge ``kda_chunks_per_step`` for a step of ``sequences``
    sequences of ``seq_len`` positions, and return it: the chunks of Kimi
    Delta Attention's rule, one a head, ``kernels.kda.CHUNK`` positions (the
    last of a sequence filled) and layer of kind ``kda``; from the shapes
    alone, called outside the step, beside :func:`publish_gated_delta`."""
    from ..core import metrics
    from ..kernels.kda import CHUNK

    layers = sum(cfg.layer_kind(i).mixer == "kda"
                 for i in range(cfg.num_blocks))
    chunks = layers * sequences * cfg.num_heads * -(-seq_len // CHUNK)
    metrics.set_gauge("kda_chunks_per_step", float(chunks))
    return chunks


def publish_indexer(cfg: TransformerConfig, seq_len: int,
                    sequences: int = 1) -> dict:
    """Set the gauges ``indexer_pairs_scored_per_step`` (the causal pairs of
    every sequence and layer with an indexer) and
    ``attention_pairs_chosen_per_step`` (the pairs their chosen sets hold,
    every query head counted once) for a step of ``sequences`` sequences of
    ``seq_len`` positions, and return both; from the shapes alone, called
    outside the step, beside :func:`publish_attention`."""
    from ..core import metrics

    layers = sequences * sum(cfg.layer_kind(i).mixer == "attention"
                             for i in range(cfg.num_blocks)) \
        if cfg.indexer_topk else 0
    scored = layers * masked_attention.Causal().allowed_pairs(seq_len)
    chosen = layers and layers * masked_attention.Sparse(
        cfg.indexer_topk).allowed_pairs(seq_len)
    metrics.set_gauge("indexer_pairs_scored_per_step", float(scored))
    metrics.set_gauge("attention_pairs_chosen_per_step", float(chosen))
    return {"indexer_pairs_scored_per_step": scored,
            "attention_pairs_chosen_per_step": chosen}


class Transformer(nn.Module):
    """Token ids ``[batch, seq]`` → logits ``[batch, seq, vocab]``; with
    ``cfg.mtp_modules`` → (those, a tuple of each prediction module's logits
    of the same shape).

    ``positions`` ``[seq]``: each position's index for the rotary embedding
    (default ``0..seq-1``).  With ``cfg.block_diffusion`` the tokens are
    ``[x_t ; x_0]``, both halves count ``0..seq/2-1`` unless told otherwise,
    and the logits are the noisy half's, ``[batch, seq/2, vocab]``: the clean
    half's enter no loss."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True, positions=None):
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
            param_dtype=jnp.float32, name="embed",
            embedding_init=nn.with_partitioning(
                nn.initializers.normal(0.02), (cfg.model_axis, None)))
        s = tokens.shape[1]
        if s > cfg.max_len:
            raise ValueError(f"{s} positions, max_len is {cfg.max_len}")
        if cfg.positions not in ("learned", "rope"):
            raise ValueError(f"unknown positions {cfg.positions!r}")
        with scope("embed"):
            x = embed(tokens)
            if cfg.embedding_multiplier != 1.0:
                x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
            if cfg.positions == "learned":
                x = x + self._learned_positions(s).astype(cfg.dtype)
        if cfg.block_diffusion:
            if s % 2 or cfg.positions != "rope":
                raise ValueError("block diffusion takes [x_t ; x_0], an even "
                                 "number of positions, and rotary positions")
            if positions is None:
                positions = jnp.arange(s) % (s // 2)
        block = Block
        if cfg.remat:
            block = nn.remat(Block)
        if cfg.hc_mult:
            if cfg.mtp_modules:
                raise ValueError("how a prediction module joins several "
                                 "streams is not written: no mtp_modules "
                                 "under hc_mult")
            from . import hyper_connections

            x = hyper_connections.fan_out(x, cfg.hc_mult)
        for i in range(cfg.num_layers):
            x = block(cfg, cfg.layer_kind(i), name=f"layer_{i}")(x, positions)
        if cfg.hc_mult:
            x = hyper_connections.fold(x)
        stack_output = x
        with scope("norm"):
            if cfg.block_diffusion:
                x = x[:, :s // 2]
            x = _norm(cfg, "ln_f")(x)
        if cfg.tie_embeddings:
            # Weight-tied readout against the (model-axis-sharded)
            # embedding; ``attend`` multiplies in the table's ``dtype``
            # whatever it is handed.
            head = lambda y: embed.attend(y.astype(jnp.float32))  # noqa: E731
        else:
            head = _dense(cfg, cfg.vocab_size, (None, cfg.model_axis),
                          "lm_head")
        if cfg.logits_scaling != 1.0:
            readout = head
            head = lambda y: readout(y) / cfg.logits_scaling  # noqa: E731
        with scope("head"):
            logits = head(x)
        if not cfg.mtp_modules:
            return logits
        if cfg.block_diffusion:
            raise ValueError("no prediction modules under block diffusion")
        from .deepseek import PredictionModule

        # Module k reads token i + k beside position i's state and predicts
        # token i + k + 1; it runs over all positions (the sequence rolled,
        # so that the kernels take its length), and the last k positions,
        # which read a token from the sequence's start, enter no loss.
        ahead, state = [], stack_output
        for k in range(cfg.mtp_modules):
            module = PredictionModule(cfg, name=f"mtp_{k}")
            with scope("embed"):
                following = embed(jnp.roll(tokens, -(k + 1), axis=1))
            i = cfg.num_layers + k
            state = block(cfg, cfg.layer_kind(i), name=f"layer_{i}")(
                module.join(state, following), positions)
            with scope("head"):
                ahead.append(head(module.readout_norm(state)))
        return logits, tuple(ahead)

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
