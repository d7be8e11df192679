"""Qwen3-Next-80B-A3B-Instruct at its published widths, cut to one chip's
share of a layer (16 of 512 experts, which 32 chips share; an eighth of the
vocabulary; both mixers whole) and to one period of its layers (three Gated DeltaNet layers, then one
gated attention layer): the model, its next-token loss with the
load-balancing term, its data and optimizer from a seed, its FLOPs per sample
and the delta rule's kernels' cost from its shapes.

The model is the program's (``horovod_tpu/models/transformer.py`` under a
layer pattern, over ``horovod_tpu/models/gated_delta.py``,
``horovod_tpu/kernels/gated_delta.py``, ``kernels/masked_attention.py`` and
``horovod_tpu/parallel/moe.py``), stepped through ``config.loss``, whose
``aux`` carries the router's counters.  The plain reference is
``qwen3-next-80b-a3b_reference.py`` beside this file, which imports nothing
of the program and runs the delta rule a token at a time:
``chip_bench/reference.py`` takes its gradient from
``config._chip_bench_grad``, so ``matches_reference`` compares the step
under test with that float32 model and not with the program's own.

The losses of fresh weights lie near ln(vocabulary) in any precision, so the
harness's one limit on them (3e-4) sees a dropped update and little of a
wrong layer or of the rounding.  The configuration therefore brings limits
of its own, in its file, in ``lfm2-8b-a1b``'s form: before the reference's
first step, the program's logits at the timed sizes against the float32
reference's (``Config.logits_errors``): ``logits_rtol`` on the difference as
a share of the logits' norm, ``logits_median_rtol`` on the median over the
positions of each position's own share (which the positions that chose
another expert under a rounded router input do not move), and
``logits_float32_rtol`` on the median position's share of the same model
computed in float32 at the highest precision, where nothing is rounded, so
that a wrong layer fails (the file's ``reference_limits`` has every
reading).  The harness has no place for a configuration's own check (PERF.md
section 7 (g)), so a run outside a limit ends there, loudly, with no result
line.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import math
import os
import sys
import weakref

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from chip_bench import peaks, readers
from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
from horovod_tpu.kernels.gated_delta import CHUNK
from horovod_tpu.models.transformer import (
    Transformer,
    moe_stats,
    qwen3_next_80b_a3b_config,
)
from horovod_tpu.parallel.moe import count_routing, moe_counters


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "qwen3-next-80b-a3b_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_qwen3_next_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_plan(sizes):
    """``"D"`` a Gated DeltaNet layer, ``"*"`` gated attention, for the layers
    held here: layer ``i`` attends where ``(i + 1) % full_attention_interval
    == 0``."""
    if len(sizes["layers_held"]) != sizes["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers held here")
    return ["*" if (i + 1) % sizes["full_attention_interval"] == 0 else "D"
            for i in sizes["layers_held"]]


def _rule_macs(sizes):
    """Multiply-adds of the chunked gated delta rule of one layer on one
    sequence, as the algorithm needs them once the chunk's inverse ``T`` is
    had: a chunk's ``k k^T`` and ``q k^T`` a key head; a value head's ``U = T
    (beta v)`` and ``W = T (beta k exp(gamma))``, its two products with the
    state (``W S``, ``(q exp(gamma)) S``), ``tril(q k^T D) V'`` and the
    state's writes.  How ``T`` is had (ten ``[C, C]`` products in the
    program's kernels, a substitution elsewhere) is not counted."""
    s, c = sizes["sequence_length"], CHUNK
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    chunk = hk * 2 * c * c * dk \
        + hv * (c * c * (dv + dk) + 2 * c * dk * dv + c * c * dv
                + c * dk * dv)
    return s // c * chunk


def matmul_macs(sizes):
    """{name: multiply-adds per sample} of every matrix multiplication and
    convolution of the forward pass, from the shapes alone.  A sample is one
    sequence.  Attention is counted over the causal pairs and not over the
    square; the experts at the rows this chip's share sees when the routing
    is even, k * held / published a position; the delta rule by its chunked
    form (:func:`_rule_macs`)."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = sizes["head_dim"]
    plan = layer_plan(sizes)
    deltas, attns, layers = plan.count("D"), plan.count("*"), len(plan)
    key_dim = sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
    value_dim = sizes["linear_num_value_heads"] \
        * sizes["linear_value_head_dim"]
    rows = sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / sizes["num_experts_published"]
    pairs = attns * s * (s + 1) // 2
    return {
        "gdn_proj": deltas * s * d * (
            2 * key_dim + 3 * value_dim
            + 2 * sizes["linear_num_value_heads"]),
        "gdn_conv": deltas * s * (2 * key_dim + value_dim)
        * sizes["linear_conv_kernel_dim"],
        "gdn_rule": deltas * _rule_macs(sizes),
        "qkvo": attns * s * (3 * d * h * dh + 2 * d * h_kv * dh),
        "attention_scores": pairs * h * dh,
        "attention_values": pairs * h * dh,
        "router": layers * s * d * sizes["num_experts_published"],
        "shared_expert": layers * s * d
        * (3 * sizes["shared_expert_intermediate_size"] + 1),
        "experts": layers * s * rows * 3 * d * sizes["moe_intermediate_size"],
        "head": s * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, the taps and the
    rule's products, a multiply-add counted as 2, nothing recomputed: 2
    forward and 4 backward.  Embedding lookup, norms, the gates, the decays,
    the chunks' inverses, rotary positions, softmax, top-k, the sort, gathers
    and sums of dispatch and combine, and AdamW are not counted."""
    return float(6 * sum(matmul_macs(sizes).values()))


def gated_delta_cost(sizes):
    """(operations, HBM bytes) of ``kernels/gated_delta.py``'s two kernels
    over every Gated DeltaNet layer held here on one sequence, as the
    algorithm needs them: the same work whatever kernel does it.
    Operations: the chunked form's products at the kernels' chunk of 64
    (:func:`_rule_macs`), 2 forward and 4 backward a multiply-add.  Bytes:
    forward ``q``, ``k``, ``v`` read and ``o`` written in bf16, ``g`` and
    ``beta`` read in fp32 a value head and position, and the state every
    chunk starts from written in fp32; backward those and ``do`` read and
    the five cotangents written.  Nothing recomputed or padded is counted."""
    s, c = sizes["sequence_length"], CHUNK
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    layers = layer_plan(sizes).count("D")
    qk, v = 2 * 2 * s * hk * dk, 2 * s * hv * dv
    small = 2 * 4 * s * hv
    states = 4 * (s // c) * hv * dk * dv
    forward = (qk + v + small) + (v + states)
    backward = (qk + v + small + states + v) + (qk + v + small)
    return 6 * layers * _rule_macs(sizes), layers * (forward + backward)


def _gated_delta_roofline_pct(sizes):
    """The reduction behind ``gated_delta_roofline_pct``: the least time the
    chip could take for the rule's kernels of one step (the larger of
    operations over the bf16 peak and bytes over the HBM peak: the bytes)
    over the time they took on the device's op line."""
    operations, bytes_moved = gated_delta_cost(sizes)
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
        if sizes["tie_word_embeddings"] or sizes["mlp_only_layers"] \
                or sizes["decoder_sparse_step"] != 1 \
                or sizes["rope_scaling"] is not None \
                or sizes["use_sliding_window"] \
                or sizes["hidden_act"] != "silu" \
                or sizes["layers_held"] != list(range(len(
                    sizes["layers_held"]))):
            raise ValueError("an untied head, every layer sparse, unscaled "
                             "RoPE, no window, silu and the leading layers "
                             "are the only forms written here")
        plan = layer_plan(sizes)
        period = sizes["full_attention_interval"]
        if plan != (["D"] * (period - 1) + ["*"]) * (len(plan) // period):
            raise ValueError("whole periods of the layers")
        self.model = Transformer(qwen3_next_80b_a3b_config(
            vocab_size=sizes["vocab_size"],
            num_layers=sizes["num_hidden_layers"],
            num_heads=sizes["num_attention_heads"],
            num_kv_heads=sizes["num_key_value_heads"],
            head_width=sizes["head_dim"], d_model=sizes["hidden_size"],
            d_ff=sizes["moe_intermediate_size"],
            d_ff_shared=sizes["shared_expert_intermediate_size"],
            max_len=sizes["max_position_embeddings"],
            norm_eps=sizes["rms_norm_eps"],
            rope_theta=float(sizes["rope_theta"]),
            partial_rotary_factor=sizes["partial_rotary_factor"],
            num_experts=sizes["num_experts_published"],
            experts_per_token=sizes["num_experts_per_tok"],
            experts_held=self.held, norm_topk_prob=sizes["norm_topk_prob"],
            gdn_key_heads=sizes["linear_num_key_heads"],
            gdn_value_heads=sizes["linear_num_value_heads"],
            gdn_key_dim=sizes["linear_key_head_dim"],
            gdn_value_dim=sizes["linear_value_head_dim"],
            gdn_conv=sizes["linear_conv_kernel_dim"],
            dtype=jnp.bfloat16, moe_data_axis=PROCESS_AXIS))
        # The harness's named hooks, both set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the one reduction readers.py lacks.
        self.reference = _load_reference()
        self._chip_bench_grad = self._checked_once(jax.jit(jax.value_and_grad(
            self.reference.make_loss(sizes), has_aux=True)))
        readers.REDUCTIONS["trace_gated_delta_roofline_pct"] = \
            _gated_delta_roofline_pct(sizes)

    def _checked_once(self, grad):
        """``grad``, the plain reference's, behind the configuration's own
        limits: its first call, which the harness makes on the seed's fresh
        weights and batch, first holds the program's logits to the
        reference's.  A gradient it returned is deleted at the next call
        (the harness has used it by then), or the reference's steps would
        not fit beside the harness's state (``nemotron-3-super-120b-a12b.py``
        has the reckoning: 2.5 GB of gradients here beside 7.5 of parameters
        and AdamW state)."""
        pending, last = [True], []

        def checked(params, aux, batch):
            for old in (ref() for ref in last):
                if old is not None:
                    old.delete()
            last.clear()
            if pending:
                pending.clear()
                z = self.sizes
                want = self._logits(jnp.float32, ())(params, batch)
                whole, median = (float(x) for x in self._distance(
                    self._logits(None, ())(params, batch), want))
                _, exact_median = (float(x) for x in self._distance(
                    self._logits("program_float32", ())(params, batch),
                    want))
                held = ((whole, z["logits_rtol"], "their norm"),
                        (median, z["logits_median_rtol"],
                         "a position's norm, the median position"),
                        (exact_median, z["logits_float32_rtol"],
                         "a position's norm, the median position, computed "
                         "in float32"))
                print(f"chip_bench {z['name']}: logits from the float32 "
                      "reference's: " + "; ".join(
                          f"{error:.3e} of {what} (limit {limit:.2e})"
                          for error, limit, what in held),
                      file=sys.stderr, flush=True)
                for error, limit, what in held:
                    if not error <= limit:
                        raise SystemExit(
                            f"chip_bench {z['name']}: FAILED: the program's "
                            f"logits lie {error:.3e} of {what} from the "
                            "float32 reference's, over the limit "
                            f"{limit:.2e}")
                # The reference's step needs the room the three programs'
                # scratch holds while they are loaded.
                del want
                self._logits.cache_clear()
            out = grad(params, aux, batch)
            last.extend(weakref.ref(g)
                        for g in jax.tree_util.tree_leaves(out[1]))
            return out

        return checked

    def logits_errors(self, params, batch, dtype=None, wrong=()):
        """(|z - z_ref| / |z_ref| over the logits [b, s, vocab] of one batch,
        the median over the positions of the same share taken a position at
        a time), z_ref the float32 reference's.  z is the program's (the
        model as the step runs it); with ``dtype`` "program_float32" the
        program's model computed in float32 at the highest precision; with
        any other ``dtype`` the reference's computed in that precision, with
        ``wrong`` one thing of its layers broken (what the limits have to
        refuse)."""
        want = self._logits(jnp.float32, ())(params, batch)
        got = self._logits(dtype, tuple(wrong))(params, batch)
        return tuple(float(x) for x in self._distance(got, want))

    @functools.lru_cache(maxsize=None)
    def _logits(self, dtype, wrong):
        """The jitted ``(params, batch) -> logits``: the program's for
        ``dtype`` None or "program_float32", else the reference's in
        ``dtype``."""
        exact = Transformer(dataclasses.replace(self.model.cfg,
                                                dtype=jnp.float32))

        def program(params, batch):
            return self._apply(params, batch["tokens"])[0]

        def program_float32(params, batch):
            with jax.default_matmul_precision("highest"):
                return self._apply(params, batch["tokens"], exact)[0]

        def reference(params, batch):
            return self.reference.logits(params, batch, self.sizes, dtype,
                                         wrong)

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

    def _apply(self, params, tokens, model=None):
        """(logits, the ``moe`` collection) of the model."""
        return (model or self.model).apply({"params": params}, tokens,
                                           mutable=["moe"])

    def init(self, key):
        """(params, aux) from a key; meant to run under one ``jax.jit``.
        aux carries the router's counters.  The model's initialisers are the
        release's (``assumed.init``) but for the embedding's width."""
        v = self.model.init(key, jnp.zeros((1, 16), jnp.int32))
        params = nn.meta.unbox(v["params"])
        scale = self.sizes["embedding_init_std"] / 0.02
        params["embed"] = {"embedding": params["embed"]["embedding"] * scale}
        return params, moe_counters(
            self.sizes["num_hidden_layers"],
            self.sizes["num_experts_published"], share=True)

    def make_batch(self, key):
        z = self.sizes
        return {"tokens": jax.random.randint(
            key, (self.per_chip_batch, z["sequence_length"]), 0,
            z["vocab_size"])}

    def loss(self, params, aux, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        logits, state = self._apply(params, tokens)
        stats = moe_stats(state["moe"])
        # Position i is held to token i + 1; the last position has no next
        # token and weighs nothing (a roll and a weight keep the shapes
        # whole, where a slice would leave 8191 positions).
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1))
        total = jnp.sum(nll * (jnp.arange(s) < s - 1)) / (b * (s - 1)) \
            + self.sizes["router_aux_loss_coef"] \
            * jnp.mean(stats.load_balancing_loss)
        # The counts are summed over the sets that were routed by themselves:
        # over every rank's rows where the step is one program over the
        # global batch.
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
