"""JoyAI-LLM-Flash at its published widths, cut to one chip's share of a layer
(latent attention whole, 16 of 256 experts, an eighth of the vocabulary) and
to the first five layers with the multi-token-prediction module whole behind
them: the model, its two cross-entropies, the selection bias it keeps from
step to step, its data and optimizer from a seed, its FLOPs per sample and
the attention kernels' cost from its shapes.

The model is the program's (``horovod_tpu/models/transformer.py`` over
``horovod_tpu/models/deepseek.py``, ``kernels/masked_attention.py`` at a key
width of 192 over values of 128, and ``horovod_tpu/parallel/moe.py``),
stepped through ``config.loss``, whose ``aux`` carries the router's counters,
the bias ``b`` and the step's two cross-entropies apart, and whose new ``aux``
carries ``b`` stepped by the counts of the step.  The plain reference is
``joyai-llm-flash_reference.py`` beside this file, which imports nothing of
the program: ``chip_bench/reference.py`` takes its gradient from
``config._chip_bench_grad``, so ``matches_reference`` compares the step under
test with that float32 model and not with the program's own.

The losses of fresh weights lie near ln(vocabulary) in any precision, so the
harness's one limit on them (3e-4) sees a dropped update and little of a
wrong layer or of the rounding.  The configuration therefore brings limits of
its own, in its file, in ``nemotron-3-super-120b-a12b``'s form: before the
reference's first step, the program's logits **of both heads** at the timed
sizes against the float32 reference's (``Config.logits_errors``):
``logits_rtol`` on the difference as a share of the logits' norm,
``logits_median_rtol`` on the median over a head's positions of each
position's own share (the worse head's), and ``logits_float32_rtol`` on the
median position's share of the same model computed in float32 at the highest
precision, where nothing is rounded, so that a wrong layer fails (``logits_float32_norm_rtol`` holds the
share of the norm there, with room for a position that takes another expert
where two scores lie within float32's rounding).  The harness has no place
for a configuration's own check (PERF.md section 7 (g)), so a run outside a
limit ends there, loudly, with no result line.
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
from horovod_tpu.models.transformer import (
    LayerKind,
    Transformer,
    expert_bias_collection,
    joyai_llm_flash_config,
    moe_stats,
)
from horovod_tpu.parallel.moe import count_routing, moe_counters


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "joyai-llm-flash_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_joyai_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def blocks(sizes):
    """(dense blocks, sparse blocks) held here: the held layers by their
    published index against ``first_k_dense_replace``, and the prediction
    modules' blocks, which are sparse."""
    if len(sizes["layers_held"]) != sizes["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers held here")
    dense = sum(p < sizes["first_k_dense_replace"]
                for p in sizes["layers_held"])
    return dense, sizes["num_hidden_layers"] - dense \
        + sizes["num_nextn_predict_layers"]


def allowed_pairs(sizes):
    """The (query, key) pairs the causal mask allows in one sequence."""
    s = sizes["sequence_length"]
    return s * (s + 1) // 2


def matmul_macs(sizes):
    """{name: multiply-adds per sample} of every matrix multiplication of the
    forward pass, from the shapes alone.  A sample is one sequence.
    Attention is counted over the causal pairs and not over the square, the
    scores at the key's width and the values at theirs; the experts at the
    rows this chip's share sees when the routing is even, k * held /
    published a position."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    h = sizes["num_attention_heads"]
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, width = sizes["v_head_dim"], sizes["moe_intermediate_size"]
    dense, sparse = blocks(sizes)
    modules = sizes["num_nextn_predict_layers"]
    rows = sizes["num_experts_per_tok"] * sizes["n_routed_experts"] \
        / sizes["n_routed_experts_published"]
    pairs = (dense + sparse) * allowed_pairs(sizes)
    return {
        "mla_down": (dense + sparse) * s * d * (q_rank + kv_rank + rope),
        "mla_up": (dense + sparse) * s * h * (q_rank * (nope + rope)
                                              + kv_rank * (nope + dv)),
        "mla_out": (dense + sparse) * s * h * dv * d,
        "attention_scores": pairs * h * (nope + rope),
        "attention_values": pairs * h * dv,
        "dense_ffn": dense * s * 3 * d * sizes["intermediate_size"],
        "router": sparse * s * d * sizes["n_routed_experts_published"],
        "shared_expert": sparse * s * 3 * d * sizes["n_shared_experts"]
        * width,
        "experts": sparse * s * rows * 3 * d * width,
        "eh_proj": modules * s * 2 * d * d,
        "head": (1 + modules) * s * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, a multiply-add
    counted as 2, nothing recomputed: 2 forward and 4 backward.  Embedding
    lookup, norms, rotary positions, the gates, softmax, sigmoid, top-k, the
    sort, gathers and sums of dispatch and combine, and AdamW are not
    counted."""
    return float(6 * sum(matmul_macs(sizes).values()))


def mla_attention_cost(sizes):
    """(operations, HBM bytes) of the attention kernels of every block held
    here on one sequence, forward and backward, as the algorithm needs them:
    per allowed pair and head 320 multiply-adds forward (the score at the
    key's 192, the value at 128) and 640 backward (dv and dp at 128, dq and
    dk at 192); what the backward kernel computes again (the scores) and
    what a kernel pads (192 to two lane groups) is not counted: the same
    work whatever kernel does it.  Bytes: q, k, v, the output and the
    gradient of each, once a block, in bf16."""
    h, s = sizes["num_attention_heads"], sizes["sequence_length"]
    dqk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    n = sum(blocks(sizes))
    operations = 2 * 3 * n * allowed_pairs(sizes) * h * (dqk + dv)
    return operations, n * 2 * 2 * s * h * (2 * dqk + 2 * dv)


def _attention_roofline_pct(sizes):
    """The reduction behind ``mla_attention_roofline_pct``: the least time the
    chip could take for the attention kernels of one step (the larger of
    operations over the bf16 peak and bytes over the HBM peak: the
    operations) over the time they took on the device's op line."""
    operations, bytes_moved = mla_attention_cost(sizes)
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


def model_config(sizes, **overrides):
    """The program's configuration of the share ``sizes`` describes."""
    held = sizes["layers_held"]
    pattern = tuple(LayerKind(
        ffn="dense" if p < sizes["first_k_dense_replace"] else None)
        for p in held)
    return joyai_llm_flash_config(**{**dict(
        vocab_size=sizes["vocab_size"], num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"], d_model=sizes["hidden_size"],
        d_ff=sizes["moe_intermediate_size"],
        d_ff_dense=sizes["intermediate_size"],
        d_ff_shared=sizes["n_shared_experts"] * sizes["moe_intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        norm_eps=sizes["rms_norm_eps"], rope_theta=float(sizes["rope_theta"]),
        rope_interleave=sizes["rope_interleave"],
        num_experts=sizes["n_routed_experts_published"],
        experts_per_token=sizes["num_experts_per_tok"],
        experts_held=tuple(sizes["experts_held"]),
        norm_topk_prob=sizes["norm_topk_prob"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        q_lora_rank=sizes["q_lora_rank"], kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        mtp_modules=sizes["num_nextn_predict_layers"], layer_pattern=pattern,
        dtype=jnp.bfloat16, moe_data_axis=PROCESS_AXIS), **overrides})


class Config:
    def __init__(self, sizes):
        self.sizes = sizes
        self.per_chip_batch = sizes["per_chip_batch"]
        # Both heads of fresh weights predict every token about alike.
        self.first_loss = math.log(sizes["vocab_size"]) * (
            1 + sizes["mtp_loss_weight"]
            * bool(sizes["num_nextn_predict_layers"]))
        self.held = tuple(sizes["experts_held"])
        if len(self.held) != sizes["n_routed_experts"]:
            raise ValueError("n_routed_experts counts the experts held here")
        if sizes["tie_word_embeddings"] or sizes["n_shared_experts"] != 1 \
                or sizes["hidden_act"] != "silu" or sizes["attention_bias"] \
                or sizes["rope_scaling"] is not None \
                or sizes["scoring_func"] != "sigmoid" \
                or sizes["topk_method"] != "noaux_tc" \
                or sizes["n_group"] != 1 or sizes["topk_group"] != 1 \
                or sizes["moe_layer_freq"] != 1 \
                or sizes["qk_head_dim"] != sizes["qk_nope_head_dim"] \
                + sizes["qk_rope_head_dim"] \
                or sizes["num_key_value_heads"] \
                != sizes["num_attention_heads"]:
            raise ValueError("an untied head, one shared expert, silu, no "
                             "bias, unscaled RoPE, sigmoid scores with the "
                             "bias and no group limit, experts in every "
                             "layer behind the dense ones and one key head "
                             "a query head are the only forms written here")
        self.model = Transformer(model_config(sizes))
        self.expert_layers = len(self.model.cfg.expert_layers())
        # The harness's named hooks, both set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the one reduction readers.py lacks.
        self.reference = _load_reference()
        self._chip_bench_grad = self._checked_once(jax.jit(jax.value_and_grad(
            self.reference.make_loss(sizes), has_aux=True)))
        readers.REDUCTIONS["trace_mla_attention_roofline_pct"] = \
            _attention_roofline_pct(sizes)

    def _checked_once(self, grad):
        """``grad``, the plain reference's, behind the configuration's own
        limits: its first call, which the harness makes on the seed's fresh
        weights and batch, first holds the program's logits to the
        reference's.  A gradient it returned is deleted at the next call
        (the harness has used it by then), or the reference's steps would
        not fit beside the harness's state (PERF.md section 7 (m))."""
        pending, last = [True], []

        def checked(params, aux, batch):
            # `reference_losses` still names the gradient of its last step
            # when it asks for the next (2.7 GB beside 8.2 of parameters and
            # AdamW state and 2.7 of new gradients).  Its update has
            # consumed that gradient, so its buffers go here, before the
            # next ones are made.  Weak references: the last step's
            # gradient goes with the harness's own name for it.
            for old in (ref() for ref in last):
                if old is not None:
                    old.delete()
            last.clear()
            if pending:
                pending.clear()
                self.check_logits(params, batch)
            out = grad(params, aux, batch)
            last.extend(weakref.ref(g)
                        for g in jax.tree_util.tree_leaves(out[1]))
            return out

        return checked

    def check_logits(self, params, batch):
        """Hold the program's logits of both heads on ``batch`` to the
        float32 reference's by the file's four limits; a reading outside one
        ends the run."""
        z = self.sizes
        operands = (params, batch, self.reference.zero_bias(z))
        want = self._logits(jnp.float32, ())(*operands)
        whole, median = (float(x) for x in self._distance(
            self._logits(None, ())(*operands), want))
        exact, exact_median = (float(x) for x in self._distance(
            self._logits("program_float32", ())(*operands), want))
        held = ((whole, z["logits_rtol"], "their norm"),
                (median, z["logits_median_rtol"],
                 "a position's norm, the median position"),
                (exact_median, z["logits_float32_rtol"],
                 "a position's norm, the median position, computed in "
                 "float32"),
                (exact, z["logits_float32_norm_rtol"],
                 "their norm, computed in float32"))
        print(f"chip_bench {z['name']}: both heads' logits from the float32 "
              "reference's: " + "; ".join(
                  f"{error:.3e} of {what} (limit {limit:.2e})"
                  for error, limit, what in held),
              file=sys.stderr, flush=True)
        for error, limit, what in held:
            if not error <= limit:
                raise SystemExit(
                    f"chip_bench {z['name']}: FAILED: the program's logits "
                    f"lie {error:.3e} of {what} from the float32 "
                    f"reference's, over the limit {limit:.2e}")
        # The reference's step needs the room the three programs above hold
        # their scratch in while loaded.
        del want, operands
        self._logits.cache_clear()

    def logits_errors(self, params, batch, dtype=None, wrong=(), bias=None):
        """(|z - z_ref| / |z_ref| over both heads' logits [2, b, s, vocab] of
        one batch, the median over a head's positions of the same share taken
        a position at a time, the worse head's), z_ref the float32
        reference's.  z is the
        program's (the model as the step runs it); with ``dtype``
        "program_float32" the program's model computed in float32 at the
        highest precision; with any other ``dtype`` the reference's computed
        in that precision, with ``wrong`` one thing of its layers broken
        (what the limits have to refuse).  ``bias`` [expert blocks, experts]:
        the selection bias both sides route by (zeros, a fresh run's, by
        default)."""
        if bias is None:
            bias = self.reference.zero_bias(self.sizes)
        want = self._logits(jnp.float32, ())(params, batch, bias)
        got = self._logits(dtype, tuple(wrong))(params, batch, bias)
        return tuple(float(x) for x in self._distance(got, want))

    @functools.lru_cache(maxsize=None)
    def _logits(self, dtype, wrong):
        """The jitted ``(params, batch, bias) -> logits [heads, b, s,
        vocab]``: the program's for ``dtype`` None or "program_float32",
        else the reference's in ``dtype``."""
        exact = Transformer(dataclasses.replace(self.model.cfg,
                                                dtype=jnp.float32))

        def stacked(out):
            logits, ahead = out
            return jnp.stack((logits,) + ahead)

        def program(params, batch, bias):
            return stacked(self._apply(params, bias, batch["tokens"])[0])

        def program_float32(params, batch, bias):
            with jax.default_matmul_precision("highest"):
                return stacked(self._apply(params, bias, batch["tokens"],
                                           exact)[0])

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
        # A head at a time: one median over both would sit between a sound
        # head's positions and a wrong head's.
        return (jnp.linalg.norm(difference.ravel())
                / jnp.linalg.norm(want.ravel()),
                jnp.max(jnp.median(by_position, axis=(1, 2))))

    def _apply(self, params, bias, tokens, model=None):
        """((logits, the modules' logits), the ``moe`` collection) of the
        model routed by ``bias``."""
        return (model or self.model).apply(
            {"params": params,
             "moe": expert_bias_collection(self.model.cfg, bias)},
            tokens, mutable=["moe"])

    def init(self, key):
        """(params, aux) from a key; meant to run under one ``jax.jit``.
        aux carries the router's counters, the selection bias, zero, and the
        step's cross-entropies.  The model's initialisers are the model
        code's (normal(0.02) every matrix, the norms' scales 1) but for the
        embedding's width, set here (`assumed.init`)."""
        z = self.sizes
        v = self.model.init(key, jnp.zeros((1, 16), jnp.int32))
        params = nn.meta.unbox(v["params"])
        scale = z["embedding_init_std"] / 0.02
        params["embed"] = {"embedding": params["embed"]["embedding"] * scale}
        aux = moe_counters(self.expert_layers, z["n_routed_experts_published"],
                           share=True, expert_bias=True)
        aux["cross_entropy"] = jnp.zeros(
            (1 + z["num_nextn_predict_layers"],), jnp.float32)
        return params, aux

    def make_batch(self, key):
        z = self.sizes
        return {"tokens": jax.random.randint(
            key, (self.per_chip_batch, z["sequence_length"]), 0,
            z["vocab_size"])}

    def loss(self, params, aux, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        z = self.sizes
        (logits, ahead), state = self._apply(params, aux["expert_bias"],
                                             tokens)
        stats = moe_stats(state["moe"])
        # Head k's position i is held to token i + 1 + k; the last 1 + k
        # positions have no such token and weigh nothing (a roll and a
        # weight keep the shapes whole, where a slice would leave 8191
        # positions).
        entropies = []
        for k, z_k in enumerate((logits,) + ahead):
            nll = optax.softmax_cross_entropy_with_integer_labels(
                z_k.astype(jnp.float32), jnp.roll(tokens, -(1 + k), axis=1))
            has = s - 1 - k
            entropies.append(jnp.sum(nll * (jnp.arange(s) < has)) / (b * has))
        total = entropies[0]
        if ahead:
            total = total + z["mtp_loss_weight"] * sum(entropies[1:]) \
                / len(ahead)
        # The counts are summed over the sets that were routed by themselves:
        # over every rank's rows where the step is one program over the
        # global batch.
        new = count_routing(
            {k: v for k, v in aux.items() if k != "cross_entropy"},
            jnp.sum(stats.tokens_per_expert, axis=1), held=self.held,
            bias_update_rate=z["expert_bias_update_rate"])
        new["cross_entropy"] = jnp.stack(entropies)
        return total, new

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
