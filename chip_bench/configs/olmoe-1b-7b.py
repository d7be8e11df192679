"""OLMoE-1B-7B at its published widths, cut to one layer: the model, its loss,
its data and optimizer from a seed, its FLOPs per sample and the grouped
products' cost from its shapes.

The model is the program's (``horovod_tpu/models/transformer.py`` over
``horovod_tpu/parallel/moe.py``), stepped through ``config.loss``.  The plain
reference is ``olmoe-1b-7b_reference.py`` beside this file, which imports
nothing of the program: ``chip_bench/reference.py`` takes its gradient from
``config._chip_bench_grad``, so ``matches_reference`` compares the step under
test with that float32 model and not with the program's own.
"""

from __future__ import annotations

import importlib.util
import math
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from chip_bench import peaks, readers
from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
from horovod_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
    moe_stats,
)
from horovod_tpu.parallel.moe import count_routing, moe_counters

# The grouped products on the device's op line, as XLA's TPU compiler names
# the kernels it makes of ``ragged_dot`` (forward and both gradients).
GROUPED_OPS = "^ragged-dot"


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "olmoe-1b-7b_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_olmoe_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def matmul_macs(sizes):
    """{name: multiply-adds per sample (one sequence)} of every matrix
    multiplication of the forward pass, from the shapes alone.  Attention is
    counted as the full square of scores; ``attention_causal_half`` is what a
    kernel that skips the masked half would need, and is not in the sum."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    layers, k = sizes["num_hidden_layers"], sizes["num_experts_per_tok"]
    return {
        "qkvo": layers * s * 4 * d * d,
        "attention_scores": layers * s * s * d,   # all heads: A * s*s*(d/A)
        "attention_values": layers * s * s * d,
        "router": layers * s * d * sizes["num_experts"],
        "experts": layers * s * k * 3 * d * sizes["intermediate_size"],
        "head": s * d * sizes["vocab_size"],
    }


def attention_causal_half_macs(sizes):
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    return sizes["num_hidden_layers"] * s * (s + 1) * d  # scores + values


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, a multiply-add
    counted as 2, nothing recomputed: 2 forward and 4 backward.  The routed
    experts count 8 a token, not 64.  Embedding lookup, norms, rotary
    positions, softmax, top-k, the sort and gathers of dispatch and combine,
    and AdamW are not counted."""
    return float(6 * sum(matmul_macs(sizes).values()))


def grouped_matmul_cost(sizes, rows):
    """(operations, HBM bytes) of the three grouped products of one expert
    layer over ``rows`` routed rows, forward and backward: nine products of
    ``rows`` x ``hidden`` x ``width`` (each forward product has a gradient to
    its rows and one to its weights).  Bytes: every product reads its two
    operands and writes its result once, in bf16, the weights' side being the
    whole stack of experts."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    experts = sizes["num_experts"]
    operations = 9 * 2 * rows * d * f
    wide, narrow, weights = rows * d * 2, rows * f * 2, experts * d * f * 2
    # gate, up: [rows, d] x [E, d, f] -> [rows, f]; down the other way round.
    # Each of forward, row gradient and weight gradient touches one wide, one
    # narrow and one stack of weights.
    return operations, 9 * (wide + narrow + weights)


def _experts_roofline_pct(sizes):
    """The reduction behind ``moe_experts_roofline_pct``: the least time the
    chip could take for the grouped products of one step (the larger of
    operations over the bf16 peak and bytes over the HBM peak) over the time
    their kernels took on the device's op line."""
    rows = sizes["per_chip_batch"] * sizes["sequence_length"] \
        * sizes["num_experts_per_tok"]
    operations, bytes_moved = grouped_matmul_cost(sizes, rows)
    operations *= sizes["num_hidden_layers"]
    bytes_moved *= sizes["num_hidden_layers"]

    def reduction(p, ctx):
        w = ctx["window"]
        if w is None or not w.ops:
            return None
        measured = w.op_s(p["pattern"]) / w.steps
        if not measured:
            return None
        kind = jax.local_devices()[0].device_kind
        least = max(operations / peaks.peak(kind, "bf16_flops"),
                    bytes_moved / peaks.peak(kind, "hbm_bytes_per_s"))
        return 100.0 * least / measured

    return reduction


class Config:
    def __init__(self, sizes):
        self.sizes = sizes
        self.per_chip_batch = sizes["per_chip_batch"]
        self.first_loss = math.log(sizes["vocab_size"])
        if sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
            raise ValueError("as many KV heads as heads, or not this model")
        self.model = Transformer(TransformerConfig(
            vocab_size=sizes["vocab_size"],
            num_layers=sizes["num_hidden_layers"],
            num_heads=sizes["num_attention_heads"],
            d_model=sizes["hidden_size"], d_ff=sizes["intermediate_size"],
            max_len=sizes["max_position_embeddings"], causal=True,
            attention="full", dtype=jnp.bfloat16, norm="rmsnorm",
            norm_eps=sizes["rms_norm_eps"], positions="rope",
            rope_theta=float(sizes["rope_theta"]), qk_norm=True,
            use_bias=sizes["attention_bias"],
            tie_embeddings=sizes["tie_word_embeddings"], ffn="moe",
            num_experts=sizes["num_experts"],
            experts_per_token=sizes["num_experts_per_tok"],
            moe_data_axis=PROCESS_AXIS))
        # The harness's named hooks, both set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the one reduction readers.py lacks.
        self._chip_bench_grad = jax.jit(jax.value_and_grad(
            _load_reference().make_loss(sizes), has_aux=True))
        readers.REDUCTIONS["trace_moe_experts_roofline_pct"] = \
            _experts_roofline_pct(sizes)

    def init(self, key):
        """(params, aux) from a key; meant to run under one ``jax.jit``.
        aux carries the router's counters."""
        v = self.model.init(key, jnp.zeros((1, 16), jnp.int32))
        return nn.meta.unbox(v["params"]), moe_counters(
            self.sizes["num_hidden_layers"], self.sizes["num_experts"])

    def make_batch(self, key):
        return {"tokens": jax.random.randint(
            key, (self.per_chip_batch, self.sizes["sequence_length"]), 0,
            self.sizes["vocab_size"])}

    def loss(self, params, aux, batch):
        tokens = batch["tokens"]
        logits, state = self.model.apply({"params": params}, tokens,
                                         mutable=["moe"])
        stats = moe_stats(state["moe"])
        # Next-token loss over the s-1 positions that have a next token,
        # summed in fp32; the last position is masked rather than the logits
        # sliced (a copy of 0.8 GB).
        s = tokens.shape[1]
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1))
        ce = jnp.sum(nll * (jnp.arange(s) < s - 1)) / (tokens.shape[0]
                                                       * (s - 1))
        total = ce \
            + self.sizes["load_balancing_loss_weight"] \
            * jnp.mean(stats.load_balancing_loss) \
            + self.sizes["router_z_loss_weight"] \
            * jnp.mean(stats.router_z_loss)
        return total, count_routing(
            aux, jnp.sum(stats.tokens_per_expert, axis=1))

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
