"""LFM2-8B-A1B at its published widths, cut to one chip's share of a layer and
to its leading dense layer plus one period of its layer pattern: the model,
its next-token loss, the selection bias it keeps from step to step, its data
and optimizer from a seed, its FLOPs per sample and the convolution kernels'
cost from its shapes.

The model is the program's (``horovod_tpu/models/transformer.py`` with a
layer pattern that names each layer's mixer and FFN, over
``horovod_tpu/kernels/short_conv.py``, ``kernels/masked_attention.py`` and
``horovod_tpu/parallel/moe.py``), stepped through ``config.loss``, whose
``aux`` carries the router's counters and the bias ``b`` and whose new ``aux``
carries ``b`` stepped by the counts of the step.  The plain reference is
``lfm2-8b-a1b_reference.py`` beside this file, which imports nothing of the
program: ``chip_bench/reference.py`` takes its gradient from
``config._chip_bench_grad``, so ``matches_reference`` compares the step under
test with that float32 model and not with the program's own.

The losses of fresh weights lie near ln(vocabulary) in any precision, so the
harness's one limit on them (3e-4) sees a dropped update and little of a
wrong layer or of the rounding.  The configuration therefore brings limits
of its own, in its file, as ``sdar-30b-a3b`` and ``smallthinker-21b-a3b``
do: before the reference's first step, the program's logits at the timed
sizes against the float32 reference's (``Config.logits_errors``).  Three of
them, because a position in twenty chooses another expert once its router
reads a state rounded to bf16, and those positions carry the norm of the
difference: ``logits_rtol`` on the difference as a share of the logits' norm
(what the siblings hold), ``logits_median_rtol`` on the median over the
positions of each position's own share (the rounding of the other nineteen,
which reads the same to four digits on every seed), and
``logits_float32_rtol`` on the same model computed in float32 at the highest
precision, where nothing is rounded and no expert changes (it reads 5e-7,
and a wrong layer reads ten thousand times that: the file's
``reference_limits`` has every reading).  The harness has no place for a
configuration's own check (PERF.md section 7 (g)), so a run outside a limit
ends there, loudly, with no result line.
"""

from __future__ import annotations

import dataclasses
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
    expert_bias_collection,
    lfm2_8b_a1b_config,
    moe_stats,
)
from horovod_tpu.parallel.moe import count_routing, moe_counters


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lfm2-8b-a1b_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_lfm2_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_plan(sizes):
    """[(mixer, ffn)] of the layers held here, each as the published layer it
    is: ``"conv"`` or ``"full_attention"``, ``"dense"`` or ``"experts"``."""
    if len(sizes["layers_held"]) != sizes["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers held here")
    return [(sizes["layer_types"][p],
             "dense" if p < sizes["num_dense_layers"] else "experts")
            for p in sizes["layers_held"]]


def matmul_macs(sizes):
    """{name: multiply-adds per sample} of every matrix multiplication and
    convolution of the forward pass, from the shapes alone.  A sample is one
    sequence.  Attention is counted over the causal pairs and not over the
    square; the experts at the rows this chip's share sees when the routing
    is even, k * held / published a position; the convolution's taps as
    convolution work, L a channel and position; its two gates are not
    counted."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = d // h
    plan = layer_plan(sizes)
    convs = sum(mixer == "conv" for mixer, _ in plan)
    attns = len(plan) - convs
    sparse = sum(ffn == "experts" for _, ffn in plan)
    rows = sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / sizes["num_experts_published"]
    pairs = attns * s * (s + 1) // 2
    return {
        "conv_proj": convs * s * (3 * d * d + d * d),
        "conv_taps": convs * s * d * sizes["conv_L_cache"],
        "qkvo": attns * s * (2 * d * h * dh + 2 * d * h_kv * dh),
        "attention_scores": pairs * h * dh,
        "attention_values": pairs * h * dh,
        "dense_ffn": (len(plan) - sparse) * s * 3 * d
        * sizes["intermediate_size"],
        "router": sparse * s * d * sizes["num_experts_published"],
        "experts": sparse * s * rows * 3 * d * sizes["moe_intermediate_size"],
        "head": s * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications and the taps, a
    multiply-add counted as 2, nothing recomputed: 2 forward and 4 backward.
    Embedding lookup, norms, rotary positions, the convolution's gates,
    softmax, sigmoid, top-k, the sort, gathers and sums of dispatch and
    combine, and AdamW are not counted."""
    return float(6 * sum(matmul_macs(sizes).values()))


def short_conv_cost(sizes):
    """(operations, HBM bytes) of ``kernels/short_conv.py``'s two kernels
    over every convolution layer held here on one sequence, as the algorithm
    needs them.  Operations a channel and position: forward B*X, L
    multiply-adds, the gate by C (2L + 2); backward z and c again, dC, dc, dz
    (L multiply-adds), dB, dX and the taps' gradient (L multiply-adds): 6L +
    6.  Bytes in bf16: ``bcx`` read and ``y`` written forward (3d + d a
    position), ``bcx`` and ``dy`` read and ``d_bcx`` written backward (3d + d
    + 3d); the taps and their gradient are a few KB."""
    s, d, taps = sizes["sequence_length"], sizes["hidden_size"], \
        sizes["conv_L_cache"]
    convs = sum(mixer == "conv" for mixer, _ in layer_plan(sizes))
    operations = convs * s * d * ((2 * taps + 2) + (6 * taps + 6))
    return operations, convs * s * d * 2 * (4 + 7)


def _short_conv_roofline_pct(sizes):
    """The reduction behind ``short_conv_roofline_pct``: the least time the
    chip could take for the convolution kernels of one step (the larger of
    operations over the bf16 peak and bytes over the HBM peak: the bytes) over
    the time they took on the device's op line."""
    operations, bytes_moved = short_conv_cost(sizes)
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
        if len(self.held) != sizes["num_experts"]:
            raise ValueError("num_experts counts the experts held here")
        if not (sizes["use_expert_bias"] and sizes["tie_word_embeddings"]
                and not sizes["conv_bias"]):
            raise ValueError("a selection bias, a tied readout and a "
                             "convolution without bias are the only forms "
                             "written here")
        plan = layer_plan(sizes)
        self.model = Transformer(lfm2_8b_a1b_config(
            vocab_size=sizes["vocab_size"],
            num_layers=sizes["num_hidden_layers"],
            num_heads=sizes["num_attention_heads"],
            num_kv_heads=sizes["num_key_value_heads"],
            head_width=sizes["hidden_size"] // sizes["num_attention_heads"],
            d_model=sizes["hidden_size"], d_ff=sizes["moe_intermediate_size"],
            d_ff_dense=sizes["intermediate_size"],
            conv_taps=sizes["conv_L_cache"],
            max_len=sizes["max_position_embeddings"],
            norm_eps=sizes["norm_eps"],
            rope_theta=float(sizes["rope_theta"]),
            num_experts=sizes["num_experts_published"],
            experts_per_token=sizes["num_experts_per_tok"],
            experts_held=self.held, norm_topk_prob=sizes["norm_topk_prob"],
            routed_scaling_factor=float(sizes["routed_scaling_factor"]),
            layer_pattern=tuple(
                LayerKind(0, True,
                          "conv" if mixer == "conv" else "attention",
                          "dense" if ffn == "dense" else None)
                for mixer, ffn in plan),
            dtype=jnp.bfloat16, moe_data_axis=PROCESS_AXIS))
        self.expert_layers = len(self.model.cfg.expert_layers())
        # The harness's named hooks, both set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the one reduction readers.py lacks.
        self.reference = _load_reference()
        self._chip_bench_grad = self._checked_once(jax.jit(jax.value_and_grad(
            self.reference.make_loss(sizes), has_aux=True)))
        readers.REDUCTIONS["trace_short_conv_roofline_pct"] = \
            _short_conv_roofline_pct(sizes)

    def _checked_once(self, grad):
        """``grad``, the plain reference's, behind the configuration's own
        limits: its first call, which the harness makes on the seed's fresh
        weights and batch, first holds the program's logits to the
        reference's."""
        pending = [True]

        def checked(params, aux, batch):
            if pending:
                pending.clear()
                z = self.sizes
                operands = (params, batch, self.reference.zero_bias(z))
                want = self._logits(jnp.float32, ())(*operands)
                whole, median = (float(x) for x in self._distance(
                    self._logits(None, ())(*operands), want))
                exact = float(self._distance(
                    self._logits("program_float32", ())(*operands), want)[0])
                held = ((whole, z["logits_rtol"], "their norm"),
                        (median, z["logits_median_rtol"],
                         "a position's norm, the median position"),
                        (exact, z["logits_float32_rtol"],
                         "their norm, computed in float32"))
                print("chip_bench lfm2-8b-a1b: logits from the float32 "
                      "reference's: " + "; ".join(
                          f"{error:.3e} of {what} (limit {limit:.2e})"
                          for error, limit, what in held),
                      file=sys.stderr, flush=True)
                for error, limit, what in held:
                    if not error <= limit:
                        raise SystemExit(
                            "chip_bench lfm2-8b-a1b: FAILED: the program's "
                            f"logits lie {error:.3e} of {what} from the "
                            "float32 reference's, over the limit "
                            f"{limit:.2e}")
            return grad(params, aux, batch)

        return checked

    def logits_errors(self, params, batch, dtype=None, wrong=(), bias=None):
        """(|z - z_ref| / |z_ref| over the logits [b, s, vocab] of one batch,
        the median over the positions of the same share taken a position at
        a time), z_ref the float32 reference's.  z is the program's (the
        model as the step runs it); with ``dtype`` "program_float32" the
        program's model computed in float32 at the highest precision; with
        any other ``dtype`` the reference's computed in that precision, with
        ``wrong`` one thing of its layers broken (what the limits have to
        refuse).  ``bias`` [expert layers, experts]: the selection bias both
        sides route by (zeros, a fresh run's, by default)."""
        if bias is None:
            bias = self.reference.zero_bias(self.sizes)
        want = self._logits(jnp.float32, ())(params, batch, bias)
        got = self._logits(dtype, tuple(wrong))(params, batch, bias)
        return tuple(float(x) for x in self._distance(got, want))

    def logits_error(self, params, batch, dtype=None, wrong=(), bias=None):
        """The first of :meth:`logits_errors`, the share of the norm."""
        return self.logits_errors(params, batch, dtype, wrong, bias)[0]

    @functools.lru_cache(maxsize=None)
    def _logits(self, dtype, wrong):
        """The jitted ``(params, batch, bias) -> logits``: the program's for
        ``dtype`` None or "program_float32", else the reference's in
        ``dtype``."""
        exact = Transformer(dataclasses.replace(self.model.cfg,
                                                dtype=jnp.float32))

        def program(params, batch, bias):
            return self._apply(params, bias, batch["tokens"])[0]

        def program_float32(params, batch, bias):
            with jax.default_matmul_precision("highest"):
                return self._apply(params, bias, batch["tokens"], exact)[0]

        def reference(params, batch, bias):
            return self.reference.logits(params, batch, self.sizes, dtype,
                                         wrong, bias)

        return jax.jit(program if dtype is None else program_float32
                       if dtype == "program_float32" else reference)

    @staticmethod
    @jax.jit
    def _distance(got, want):
        difference = got.astype(jnp.float32) - want
        by_position = jnp.linalg.norm(difference, axis=-1) \
            / jnp.linalg.norm(want, axis=-1)
        return (jnp.linalg.norm(difference.ravel())
                / jnp.linalg.norm(want.ravel()),
                jnp.median(by_position.ravel()))

    def _apply(self, params, bias, tokens, model=None):
        """(logits, the ``moe`` collection) of the model routed by ``bias``."""
        return (model or self.model).apply(
            {"params": params,
             "moe": expert_bias_collection(self.model.cfg, bias)},
            tokens, mutable=["moe"])

    def init(self, key):
        """(params, aux) from a key; meant to run under one ``jax.jit``.
        aux carries the router's counters and the selection bias, zero."""
        v = self.model.init(key, jnp.zeros((1, 16), jnp.int32))
        params = nn.meta.unbox(v["params"])
        # The embedding's own width (`assumed.init` says what the readings
        # of fresh routing asked for).
        scale = self.sizes["embedding_init_std"] / 0.02
        params["embed"] = {"embedding": params["embed"]["embedding"] * scale}
        return params, moe_counters(
            self.expert_layers, self.sizes["num_experts_published"],
            share=True, expert_bias=True)

    def make_batch(self, key):
        z = self.sizes
        return {"tokens": jax.random.randint(
            key, (self.per_chip_batch, z["sequence_length"]), 0,
            z["vocab_size"])}

    def loss(self, params, aux, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        logits, state = self._apply(params, aux["expert_bias"], tokens)
        stats = moe_stats(state["moe"])
        # Position i is held to token i + 1; the last position has no next
        # token and weighs nothing (a roll and a weight keep the shapes
        # whole, where a slice would leave 8191 positions).
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1))
        total = jnp.sum(nll * (jnp.arange(s) < s - 1)) / (b * (s - 1))
        # The counts are summed over the sets that were routed by themselves:
        # over every rank's rows where the step is one program over the
        # global batch.
        return total, count_routing(
            aux, jnp.sum(stats.tokens_per_expert, axis=1), held=self.held,
            bias_update_rate=self.sizes["expert_bias_update_rate"])

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
