"""SmallThinker-21BA3B at its published widths, cut to one chip's share of a
layer and to one period of its layer pattern: the model, its next-token loss,
its data and optimizer from a seed, its FLOPs per sample and the attention
kernels' cost from its shapes.

The model is the program's (``horovod_tpu/models/transformer.py`` with a
layer pattern, over ``horovod_tpu/kernels/masked_attention.py`` and
``horovod_tpu/parallel/moe.py``), stepped through ``config.loss``.  The plain
reference is ``smallthinker-21b-a3b_reference.py`` beside this file, which
imports nothing of the program: ``chip_bench/reference.py`` takes its
gradient from ``config._chip_bench_grad``, so ``matches_reference`` compares
the step under test with that float32 model and not with the program's own.

The losses of fresh weights lie near ln(vocabulary) in any precision, so the
harness's one limit on them (3e-4) sees a dropped update and a wrong layer,
and little of the rounding.  The configuration therefore brings a limit of
its own, ``logits_rtol`` in its file, as ``sdar-30b-a3b`` does: before the
reference's first step, the program's logits at the timed sizes against the
float32 reference's, as a share of their norm (``Config.logits_error``).  The
harness has no place for a configuration's own check (PERF.md section 7
(g)), so a run outside the limit ends there, loudly, with no result line.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from chip_bench import peaks, readers
from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
from horovod_tpu.models.transformer import (
    LayerKind,
    Transformer,
    TransformerConfig,
    moe_stats,
)
from horovod_tpu.parallel.moe import count_routing, moe_counters


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "smallthinker-21b-a3b_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_smallthinker_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_kinds(sizes):
    """[(window or 0, RoPE or not)] of the layers held here: the first
    ``num_hidden_layers`` entries of the published layouts."""
    n = sizes["num_hidden_layers"]
    return [(sizes["sliding_window_size"] if windowed else 0, bool(rope))
            for windowed, rope in zip(sizes["sliding_window_layout"][:n],
                                      sizes["rope_layout"][:n])]


def allowed_pairs(sizes):
    """{"window": n, "global": n}: the (query, key) pairs one sequence's
    masks allow, summed over the window layers (j <= i and i - j < w:
    s(s+1)/2 less the (s-w)(s-w+1)/2 beyond the window) and over the global
    ones (j <= i: s(s+1)/2)."""
    s = sizes["sequence_length"]
    causal = s * (s + 1) // 2
    pairs = {"window": 0, "global": 0}
    for window, _ in layer_kinds(sizes):
        beyond = max(s - window, 0)
        if window:
            pairs["window"] += causal - beyond * (beyond + 1) // 2
        else:
            pairs["global"] += causal
    return pairs


def matmul_macs(sizes):
    """{name: multiply-adds per sample} of every matrix multiplication of the
    forward pass, from the shapes alone.  A sample is one sequence.
    Attention is counted over the pairs the masks allow and not over the
    square; the experts at the rows this chip's share sees when the routing
    is even, k * held / published a position."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    layers = sizes["num_hidden_layers"]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = sizes["head_dim"]
    rows = sizes["moe_num_active_primary_experts"] \
        * sizes["moe_num_primary_experts"] \
        / sizes["moe_num_primary_experts_published"]
    pairs = sum(allowed_pairs(sizes).values())
    return {
        "qkvo": layers * s * (2 * d * h * dh + 2 * d * h_kv * dh),
        "attention_scores": pairs * h * dh,
        "attention_values": pairs * h * dh,
        "router": layers * s * d * sizes["moe_num_primary_experts_published"],
        "experts": layers * s * rows * 3 * d * sizes["moe_ffn_hidden_size"],
        "head": s * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, a multiply-add
    counted as 2, nothing recomputed: 2 forward and 4 backward.  Embedding
    lookup, norms, rotary positions, softmax, top-k, the sort, gathers and
    scatter-adds of dispatch and combine, and AdamW are not counted."""
    return float(6 * sum(matmul_macs(sizes).values()))


def mixed_attention_cost(sizes):
    """(operations, HBM bytes) of the attention kernels of every layer held
    here on one sequence, forward and backward, as the algorithm needs them:
    two products forward (scores, values) and four backward (dv, dp, dq, dk)
    over the allowed pairs of every query head, window layers and global
    ones alike; what the backward kernels compute again (the scores, twice)
    is not counted.  Bytes: q, k, v, the output and the gradient of each,
    once a layer, in bf16."""
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh, s = sizes["head_dim"], sizes["sequence_length"]
    operations = 2 * 6 * sum(allowed_pairs(sizes).values()) * h * dh
    return operations, sizes["num_hidden_layers"] \
        * 2 * 2 * s * dh * (2 * h + 2 * h_kv)


def _attention_roofline_pct(sizes):
    """The reduction behind ``mixed_attention_roofline_pct``: the least time
    the chip could take for the attention kernels of one step (the larger of
    operations over the bf16 peak and bytes over the HBM peak) over the time
    they took on the device's op line."""
    operations, bytes_moved = mixed_attention_cost(sizes)
    calls = sizes["per_chip_batch"]

    def reduction(p, ctx):
        w = ctx["window"]
        if w is None or not w.ops:
            return None
        measured = w.op_s(p["pattern"]) / w.steps
        if not measured:
            return None
        kind = jax.local_devices()[0].device_kind
        least = calls * max(operations / peaks.peak(kind, "bf16_flops"),
                            bytes_moved / peaks.peak(kind, "hbm_bytes_per_s"))
        return 100.0 * least / measured

    return reduction


class Config:
    def __init__(self, sizes):
        self.sizes = sizes
        self.per_chip_batch = sizes["per_chip_batch"]
        self.first_loss = math.log(sizes["vocab_size"])
        self.held = tuple(sizes["experts_held"])
        if len(self.held) != sizes["moe_num_primary_experts"]:
            raise ValueError("moe_num_primary_experts counts the experts "
                             "held here")
        if not (sizes["moe_primary_router_apply_softmax"]
                and sizes["rope_scaling"] is None):
            raise ValueError("the router's softmax and unscaled RoPE are "
                             "the only forms written here")
        self.model = Transformer(TransformerConfig(
            vocab_size=sizes["vocab_size"],
            num_layers=sizes["num_hidden_layers"],
            num_heads=sizes["num_attention_heads"],
            num_kv_heads=sizes["num_key_value_heads"],
            head_width=sizes["head_dim"], d_model=sizes["hidden_size"],
            d_ff=sizes["moe_ffn_hidden_size"],
            max_len=sizes["max_position_embeddings"], causal=True,
            attention="full", dtype=jnp.bfloat16, norm="rmsnorm",
            norm_eps=sizes["rms_norm_eps"], positions="rope",
            rope_theta=float(sizes["rope_theta"]), qk_norm=False,
            use_bias=False, tie_embeddings=sizes["tie_word_embeddings"],
            ffn="moe", num_experts=sizes["moe_num_primary_experts_published"],
            experts_per_token=sizes["moe_num_active_primary_experts"],
            experts_held=self.held, norm_topk_prob=sizes["norm_topk_prob"],
            router_input="block", expert_activation="relu",
            layer_pattern=tuple(LayerKind(*kind)
                                for kind in layer_kinds(sizes)),
            moe_data_axis=PROCESS_AXIS))
        # The harness's named hooks, both set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the one reduction readers.py lacks.
        self.reference = _load_reference()
        self._chip_bench_grad = self._checked_once(jax.jit(jax.value_and_grad(
            self.reference.make_loss(sizes), has_aux=True)))
        readers.REDUCTIONS["trace_mixed_attention_roofline_pct"] = \
            _attention_roofline_pct(sizes)

    def _checked_once(self, grad):
        """``grad``, the plain reference's, behind the configuration's own
        limit: its first call, which the harness makes on the seed's fresh
        weights and batch, first holds the program's logits to the
        reference's."""
        pending = [True]

        def checked(params, aux, batch):
            if pending:
                pending.clear()
                error, limit = self.logits_error(params, batch), \
                    self.sizes["logits_rtol"]
                print(f"chip_bench smallthinker-21b-a3b: logits {error:.3e} "
                      f"from the float32 reference's (limit {limit:.1e})",
                      file=sys.stderr, flush=True)
                if not error <= limit:
                    raise SystemExit(
                        "chip_bench smallthinker-21b-a3b: FAILED: the "
                        f"program's logits lie {error:.3e} of their norm "
                        "from the float32 reference's, over the limit "
                        f"{limit:.1e}")
            return grad(params, aux, batch)

        return checked

    def logits_error(self, params, batch, dtype=None, wrong=()):
        """|z - z_ref| / |z_ref| over the logits [b, s, vocab] of one batch,
        z_ref the float32 reference's.  z is the program's (the model as the
        step runs it), or with ``dtype`` the reference's computed in that
        precision, with ``wrong`` one thing of its layers broken (what the
        limit has to refuse)."""
        want = self._logits(jnp.float32, ())(params, batch)
        got = self._logits(dtype, tuple(wrong))(params, batch)
        return float(self._distance(got, want))

    @functools.lru_cache(maxsize=None)
    def _logits(self, dtype, wrong):
        """The jitted ``(params, batch) -> logits``: the program's for
        ``dtype`` None, else the reference's in ``dtype``."""
        def program(params, batch):
            return self.model.apply({"params": params}, batch["tokens"],
                                    mutable=["moe"])[0]

        def reference(params, batch):
            return self.reference.logits(params, batch, self.sizes, dtype,
                                         wrong)

        return jax.jit(program if dtype is None else reference)

    @staticmethod
    @jax.jit
    def _distance(got, want):
        return jnp.linalg.norm((got.astype(jnp.float32) - want).ravel()) \
            / jnp.linalg.norm(want.ravel())

    def init(self, key):
        """(params, aux) from a key; meant to run under one ``jax.jit``.
        aux carries the router's counters."""
        v = self.model.init(key, jnp.zeros((1, 16), jnp.int32))
        params = nn.meta.unbox(v["params"])
        # The embedding at its own width (`assumed.init`), as sdar-30b-a3b
        # found: at 0.02 the residual stream is one common vector after the
        # first attention and the fresh router sends every position the same
        # way.
        scale = self.sizes["embedding_init_std"] / 0.02
        params["embed"] = {"embedding": params["embed"]["embedding"] * scale}
        return params, moe_counters(
            self.sizes["num_hidden_layers"],
            self.sizes["moe_num_primary_experts_published"], share=True)

    def make_batch(self, key):
        z = self.sizes
        return {"tokens": jax.random.randint(
            key, (self.per_chip_batch, z["sequence_length"]), 0,
            z["vocab_size"])}

    def loss(self, params, aux, batch):
        z = self.sizes
        tokens = batch["tokens"]
        b, s = tokens.shape
        logits, state = self.model.apply({"params": params}, tokens,
                                         mutable=["moe"])
        stats = moe_stats(state["moe"])
        # Position i is held to token i + 1; the last position has no next
        # token and weighs nothing (a roll and a weight keep the shapes
        # whole, where a slice would leave 16,383 positions).
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1))
        total = jnp.sum(nll * (jnp.arange(s) < s - 1)) / (b * (s - 1)) \
            + z["load_balancing_loss_weight"] \
            * jnp.mean(stats.load_balancing_loss)
        return total, count_routing(
            aux, jnp.sum(stats.tokens_per_expert, axis=1), held=self.held)

    def optimizer(self, world):
        z = self.sizes
        peak, warmup = z["adamw_learning_rate"], z["warmup_steps"]
        start = z["warmup_start_share"] * peak

        def learning_rate(count):
            # Linear warm-up from a share of the peak.
            return start + (peak - start) * jnp.minimum(1.0, count / warmup)

        return optax.chain(
            optax.clip_by_global_norm(z["clip_global_norm"]),
            optax.adamw(learning_rate, b1=z["adamw_b1"],
                        b2=z["adamw_b2"], eps=z["adamw_eps"],
                        weight_decay=z["adamw_weight_decay"]))

    def flops_per_sample(self):
        return flops_per_sample(self.sizes)
