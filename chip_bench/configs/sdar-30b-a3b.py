"""SDAR-30B-A3B at its published widths, cut to one chip's share of a layer
and to a few layers: the model, its block-diffusion loss, its data, noise and
optimizer from a seed, its FLOPs per sample and the attention kernel's cost
from its shapes.

The model is the program's (``horovod_tpu/models/transformer.py`` over
``horovod_tpu/kernels/blockdiff_attention.py`` and
``horovod_tpu/parallel/moe.py``), stepped through ``config.loss``.  The plain
reference is ``sdar-30b-a3b_reference.py`` beside this file, which imports
nothing of the program: ``chip_bench/reference.py`` takes its gradient from
``config._chip_bench_grad``, so ``matches_reference`` compares the step under
test with that float32 model and not with the program's own.

The losses of fresh weights lie near ln(vocabulary) in any precision, so the
harness's one limit on them (3e-4) sees a dropped update, a wrong mask and a
missing 1/t, and no rounding.  The configuration therefore brings a limit of
its own, ``logits_rtol`` in its file: before the reference's first step, the
program's logits of the noisy half at the timed sizes against the float32
reference's, as a share of their norm (``Config.logits_error``).  The harness
has no place for a configuration's own check (PERF.md section 7 (g)), so a
run outside the limit ends there, loudly, with no result line.
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
    Transformer,
    TransformerConfig,
    moe_stats,
)
from horovod_tpu.parallel.moe import count_routing, moe_counters


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "sdar-30b-a3b_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_sdar_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def allowed_pairs(sizes):
    """(query, key) pairs the block-diffusion mask allows in one sequence of
    L data tokens run as [x_t ; x_0]: L*b noisy-noisy, L(L-b)/2 noisy-clean,
    L(L+b)/2 clean-clean = L**2 + L*b of the 4 L**2."""
    s, b = sizes["sequence_length"], sizes["block_length"]
    return s * s + s * b


def matmul_macs(sizes):
    """{name: multiply-adds per sample} of every matrix multiplication of the
    forward pass, from the shapes alone.  A sample is one sequence of L data
    tokens, run as 2L positions.  Attention is counted over the pairs the
    mask allows and not over the square of 2L; the head over the noisy half's
    L positions; the experts at the rows this chip's share sees when the
    routing is even, k * held / published a position."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    layers = sizes["num_hidden_layers"]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = sizes["head_dim"]
    rows = sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / sizes["num_experts_published"]
    return {
        "qkvo": layers * 2 * s * (2 * d * h * dh + 2 * d * h_kv * dh),
        "attention_scores": layers * allowed_pairs(sizes) * h * dh,
        "attention_values": layers * allowed_pairs(sizes) * h * dh,
        "router": layers * 2 * s * d * sizes["num_experts_published"],
        "experts": layers * 2 * s * rows * 3 * d
        * sizes["moe_intermediate_size"],
        "head": s * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, a multiply-add
    counted as 2, nothing recomputed: 2 forward and 4 backward.  Embedding
    lookup, norms, rotary positions, softmax, top-k, the sort and gathers of
    dispatch and combine, and AdamW are not counted."""
    return float(6 * sum(matmul_macs(sizes).values()))


def blockdiff_attention_cost(sizes):
    """(operations, HBM bytes) of the attention kernels of one layer on one
    sequence, forward and backward, as the algorithm needs them: two products
    forward (scores, values) and four backward (dv, dp, dq, dk) over the
    allowed pairs of every query head; what the backward kernels compute
    again (the scores, twice) is not counted.  Bytes: q, k, v, the output and
    the gradient of each, once, in bf16."""
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh, positions = sizes["head_dim"], 2 * sizes["sequence_length"]
    operations = 2 * 6 * allowed_pairs(sizes) * h * dh
    return operations, 2 * 2 * positions * dh * (2 * h + 2 * h_kv)


def _attention_roofline_pct(sizes):
    """The reduction behind ``blockdiff_attention_roofline_pct``: the least
    time the chip could take for the attention kernels of one step (the
    larger of operations over the bf16 peak and bytes over the HBM peak) over
    the time they took on the device's op line."""
    operations, bytes_moved = blockdiff_attention_cost(sizes)
    calls = sizes["num_hidden_layers"] * sizes["per_chip_batch"]

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
        if len(self.held) != sizes["num_experts"]:
            raise ValueError("num_experts counts the experts held here")
        self.model = Transformer(TransformerConfig(
            vocab_size=sizes["vocab_size"],
            num_layers=sizes["num_hidden_layers"],
            num_heads=sizes["num_attention_heads"],
            num_kv_heads=sizes["num_key_value_heads"],
            head_width=sizes["head_dim"], d_model=sizes["hidden_size"],
            d_ff=sizes["moe_intermediate_size"],
            max_len=sizes["max_position_embeddings"], causal=False,
            attention="full", dtype=jnp.bfloat16, norm="rmsnorm",
            norm_eps=sizes["rms_norm_eps"], positions="rope",
            rope_theta=float(sizes["rope_theta"]), qk_norm="head",
            use_bias=sizes["attention_bias"],
            tie_embeddings=sizes["tie_word_embeddings"], ffn="moe",
            num_experts=sizes["num_experts_published"],
            experts_per_token=sizes["num_experts_per_tok"],
            experts_held=self.held, norm_topk_prob=sizes["norm_topk_prob"],
            block_diffusion=sizes["block_length"],
            moe_data_axis=PROCESS_AXIS))
        # The harness's named hooks, both set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the one reduction readers.py lacks.
        self.reference = _load_reference()
        self._chip_bench_grad = self._checked_once(jax.jit(jax.value_and_grad(
            self.reference.make_loss(sizes), has_aux=True)))
        readers.REDUCTIONS["trace_blockdiff_attention_roofline_pct"] = \
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
                print(f"chip_bench sdar-30b-a3b: logits {error:.3e} from the "
                      f"float32 reference's (limit {limit:.1e})",
                      file=sys.stderr, flush=True)
                if not error <= limit:
                    raise SystemExit(
                        "chip_bench sdar-30b-a3b: FAILED: the program's "
                        f"logits lie {error:.3e} of their norm from the "
                        f"float32 reference's, over the limit {limit:.1e}")
            return grad(params, aux, batch)

        return checked

    def logits_error(self, params, batch, dtype=None):
        """|z - z_ref| / |z_ref| over the noisy half's logits [b, L, vocab]
        of one batch, z_ref the float32 reference's.  z is the program's
        (the model as the step runs it), or with ``dtype`` the reference's
        computed in that precision (what the limit has to refuse)."""
        want = self._logits(jnp.float32)(params, batch)
        got = self._logits(dtype)(params, batch)
        return float(self._distance(got, want))

    @functools.lru_cache(maxsize=None)
    def _logits(self, dtype):
        """The jitted ``(params, batch) -> logits``: the program's for
        ``dtype`` None, else the reference's in ``dtype``."""
        def program(params, batch):
            both = jnp.concatenate([batch["noisy"], batch["tokens"]], axis=1)
            return self.model.apply({"params": params}, both,
                                    mutable=["moe"])[0]

        def reference(params, batch):
            return self.reference.logits(params, batch, self.sizes, dtype)

        return jax.jit(program if dtype is None else reference)

    @staticmethod
    @jax.jit
    def _distance(got, want):
        return jnp.linalg.norm((got.astype(jnp.float32) - want).ravel()) \
            / jnp.linalg.norm(want.ravel())

    def init(self, key):
        """(params, aux) from a key; meant to run under one ``jax.jit``.
        aux carries the router's counters."""
        v = self.model.init(
            key, jnp.zeros((1, 4 * self.sizes["block_length"]), jnp.int32))
        params = nn.meta.unbox(v["params"])
        # The embedding at its own width (`assumed.init`): with 0.02 like the
        # other matrices the first attention's mean over the keys outweighs
        # every token's own embedding, the residual stream is one common
        # vector, and from the second layer on every position is routed to
        # the same 8 experts (PERF.md section 6, PR 31).
        scale = self.sizes["embedding_init_std"] / 0.02
        params["embed"] = {"embedding": params["embed"]["embedding"] * scale}
        return params, moe_counters(
            self.sizes["num_hidden_layers"],
            self.sizes["num_experts_published"], share=True)

    def make_batch(self, key):
        """Tokens, noise levels and the mask, all data: x_0 from the ids
        below [MASK], one t a block, a token of the block [MASK] with
        probability t."""
        z = self.sizes
        shape = (self.per_chip_batch, z["sequence_length"])
        k_tokens, k_t, k_mask = jax.random.split(key, 3)
        tokens = jax.random.randint(k_tokens, shape, 0, z["mask_token_id"])
        t = jax.random.uniform(
            k_t, (shape[0], shape[1] // z["block_length"]), jnp.float32,
            z["noise_level_min"], 1.0)
        masked = jax.random.uniform(k_mask, shape) \
            < jnp.repeat(t, z["block_length"], axis=1)
        return {"tokens": tokens, "t": t, "masked": masked,
                "noisy": jnp.where(masked, z["mask_token_id"], tokens)}

    def loss(self, params, aux, batch):
        z = self.sizes
        tokens = batch["tokens"]
        logits, state = self.model.apply(
            {"params": params},
            jnp.concatenate([batch["noisy"], tokens], axis=1),
            mutable=["moe"])
        stats = moe_stats(state["moe"])
        # The noisy half's logits, each at its own position (no shift): the
        # masked tokens' cross-entropy weighted by 1/t, over L, in fp32.
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), tokens)
        weights = batch["masked"] / jnp.repeat(batch["t"], z["block_length"],
                                               axis=1)
        total = jnp.sum(nll * weights) / tokens.size \
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
