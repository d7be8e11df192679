"""The language model of Ling-3.0-flash-VL at its published widths, cut to one
chip's share of a layer (both mixers whole, 8 of 512 experts, which 64 chips
share; an eighth of the vocabulary) and to the published layers 1-7 (the
second dense layer, then a whole period of six expert layers: five Kimi Delta
Attention mixers to one of latent attention): the model, its next-token loss,
the selection bias it keeps from step to step, its data and optimizer from a
seed, its FLOPs per sample and the cost of the rule's and the attention's
kernels from its shapes.

The model is the program's (``horovod_tpu/models/transformer.py`` under a
layer pattern, over ``horovod_tpu/models/kda.py``,
``horovod_tpu/kernels/kda.py``, ``horovod_tpu/models/deepseek.py``,
``kernels/masked_attention.py`` at a key width of 192 over values of 128 and
``horovod_tpu/parallel/moe.py`` with its group limit), every block
recomputed (``TransformerConfig.remat``), stepped through ``config.loss``,
whose ``aux`` carries the router's counters and the bias ``b``, and whose new
``aux`` carries ``b`` stepped by the counts of the step.  The plain reference
is ``ling-3.0-flash-vl_reference.py`` beside this file, which imports nothing
of the program and runs the delta rule a token at a time:
``chip_bench/reference.py`` takes its gradient from
``config._chip_bench_grad``, so ``matches_reference`` compares the step under
test with that float32 model and not with the program's own.

The losses of fresh weights lie near ln(vocabulary) in any precision, so the
harness's one limit on them (3e-4) sees a dropped update and little of a
wrong layer or of the rounding.  The configuration therefore brings limits of
its own, in its file, in ``joyai-llm-flash``'s form: before the reference's
first step, the program's logits at the timed sizes against the float32
reference's (``Config.logits_errors``): ``logits_rtol`` on the difference as
a share of the logits' norm, ``logits_median_rtol`` on the median over the
positions of each position's own share, and, on the same model computed in
float32 at the highest precision, where nothing is rounded, so that a wrong
layer fails, ``logits_float32_rtol`` on the median position's share and
``logits_float32_norm_rtol`` on the share of the norm (with room for a
position that takes another expert where two scores lie within float32's
rounding).  The harness has no place for a configuration's own check (PERF.md
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

from chip_bench import peaks, readers, trace_reduce
from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
from horovod_tpu.kernels.kda import CHUNK, LOWER_BOUND
from horovod_tpu.models.transformer import (
    LayerKind,
    Transformer,
    expert_bias_collection,
    ling_3_0_flash_config,
    moe_stats,
)
from horovod_tpu.parallel.moe import count_routing, moe_counters

# What JAX calls the second forward of a block under ``jax.checkpoint`` in an
# operation's ``op_name`` (the profiler's ``tf_op``).
RECOMPUTED = "rematted_computation"


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ling-3.0-flash-vl_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_ling_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_plan(sizes):
    """[(``"K"`` a Kimi Delta Attention layer or ``"*"`` latent attention,
    whether the FFN is the dense one)] of the layers held here, each by its
    published index: layer ``p`` attends where ``(p + 1) % layer_group_size
    == 0`` and is dense where ``p < first_k_dense_replace_published``."""
    held = sizes["layers_held"]
    if len(held) != sizes["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers held here")
    plan = [("*" if (p + 1) % sizes["layer_group_size"] == 0 else "K",
             p < sizes["first_k_dense_replace_published"]) for p in held]
    if sum(dense for _, dense in plan) != sizes["first_k_dense_replace"]:
        raise ValueError("first_k_dense_replace counts the dense layers "
                         "held here")
    return plan


def allowed_pairs(sizes):
    """The (query, key) pairs the causal mask allows in one sequence."""
    s = sizes["sequence_length"]
    return s * (s + 1) // 2


def _rule_macs(sizes):
    """Multiply-adds of the chunked rule of one KDA layer on one sequence, as
    the algorithm needs them once the chunk's inverse ``T`` is had: a head's
    two pairwise sums over the key channels (``A`` and ``q k^T`` under their
    decays), ``U = T (beta v)`` and ``W = T (beta k exp(Gamma))``, its two
    products with the state, ``tril(q k^T) V'`` and the state's writes.  How
    ``T`` is had and how ``Gamma`` is summed is not counted."""
    s, c = sizes["sequence_length"], CHUNK
    h, dk = sizes["num_attention_heads"], sizes["head_dim"]
    dv = dk
    chunk = h * (2 * c * c * dk + c * c * (dv + dk) + 2 * c * dk * dv
                 + c * c * dv + c * dk * dv)
    return s // c * chunk


def matmul_macs(sizes):
    """{name: multiply-adds per sample} of every matrix multiplication and
    convolution of **one** forward pass, from the shapes alone.  A sample is
    one sequence.  Attention is counted over the causal pairs and not over
    the square, the scores at the key's width and the values at theirs; the
    experts at the rows this chip's share sees when the routing is even, k *
    held / published a position; the rule by its chunked form
    (:func:`_rule_macs`)."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    h, dh = sizes["num_attention_heads"], sizes["head_dim"]
    inner = h * dh
    latent = sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, width = sizes["v_head_dim"], sizes["moe_intermediate_size"]
    plan = layer_plan(sizes)
    kdas = sum(kind == "K" for kind, _ in plan)
    attns = len(plan) - kdas
    dense = sum(is_dense for _, is_dense in plan)
    sparse = len(plan) - dense
    rows = sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / sizes["num_experts_published"]
    pairs = attns * allowed_pairs(sizes)
    return {
        "kda_proj": kdas * s * d * (6 * inner + h),
        "kda_conv": kdas * s * 3 * inner * sizes["short_conv_kernel_size"],
        "kda_rule": kdas * _rule_macs(sizes),
        "mla_q": attns * s * d * h * (nope + rope),
        "mla_down": attns * s * d * (latent + rope),
        "mla_up": attns * s * latent * h * (nope + dv),
        "mla_gate_out": attns * s * d * h * (1 + dv),
        "attention_scores": pairs * h * (nope + rope),
        "attention_values": pairs * h * dv,
        "dense_ffn": dense * s * 3 * d * sizes["intermediate_size"],
        "router": sparse * s * d * sizes["num_experts_published"],
        "shared_expert": sparse * s * 3 * d
        * sizes["moe_shared_expert_intermediate_size"],
        "experts": sparse * s * rows * 3 * d * width,
        "head": s * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, the taps and the
    rule's products, a multiply-add counted as 2: 2 forward and 4 backward,
    **the model's work and not the chip's**: the second forward of every
    block, which this configuration recomputes, is not counted.  Embedding
    lookup, norms, the gates, the decays, the chunks' inverses, rotary
    positions, softmax, top-k, the sort, gathers and sums of dispatch and
    combine, and AdamW are not counted either."""
    return float(6 * sum(matmul_macs(sizes).values()))


def forwards(sizes):
    """How often a block's forward pass runs a step."""
    return 2 if sizes["recompute_blocks"] else 1


def kda_cost(sizes):
    """(operations, HBM bytes) of ``kernels/kda.py``'s two kernels over every
    KDA layer held here on one sequence as the step runs them, **the forward
    kernel twice** where the blocks are recomputed (the time the share is
    taken over holds both calls: :func:`mla_attention_cost`'s convention, so
    that the cell's two rooflines read alike, each can reach 100 and neither
    moves with what is recomputed) and the backward kernel once; the same
    work whatever kernel does it.  Operations: the chunked form's products
    at the kernels' chunk of 64 (:func:`_rule_macs`), 2 a forward call and 4
    backward a multiply-add.  Bytes: a forward call reads ``q``, ``k``,
    ``v`` and writes ``o`` in bf16, reads ``g`` in fp32 a key channel and
    ``beta`` a head, and writes the state every chunk starts from in fp32;
    backward those and ``do`` read and the five cotangents written.  Nothing
    the backward kernel computes again and nothing padded is counted.
    :func:`flops_per_sample`, which ``mfu_pct`` reads, counts the model's
    work alone: one forward."""
    s, c = sizes["sequence_length"], CHUNK
    h, dk = sizes["num_attention_heads"], sizes["head_dim"]
    layers = sum(kind == "K" for kind, _ in layer_plan(sizes))
    wide, decays, small = 2 * s * h * dk, 4 * s * h * dk, 4 * s * h
    states = 4 * (s // c) * h * dk * dk
    forward = (3 * wide + decays + small) + (wide + states)
    backward = (4 * wide + decays + small + states) \
        + (3 * wide + decays + small)
    calls = forwards(sizes)
    return (2 * calls + 4) * layers * _rule_macs(sizes), \
        layers * (calls * forward + backward)


def mla_attention_cost(sizes):
    """(operations, HBM bytes) of the attention kernels of the latent-
    attention layers held here on one sequence as the step runs them, **the
    forward kernel twice** where the blocks are recomputed (the time the
    share is taken over holds both calls) and the backward kernel once: per
    allowed pair and head 320 multiply-adds a forward call (the score at the
    key's 192, the value at 128) and 640 backward; what the backward kernel
    computes again and what a kernel pads (192 to two lane groups) is not
    counted.  Bytes: q, k, v and the output once a forward call, the gradient
    of each once backward, in bf16."""
    h, s = sizes["num_attention_heads"], sizes["sequence_length"]
    dqk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    n = sum(kind == "*" for kind, _ in layer_plan(sizes))
    calls = forwards(sizes) + 2
    operations = 2 * calls * n * allowed_pairs(sizes) * h * (dqk + dv)
    return operations, n * (calls - 1) * 2 * s * h * (2 * dqk + 2 * dv)


def _roofline_pct(cost, sequences):
    """A reduction: the least time the chip could take for ``sequences``
    times ``cost`` (operations, bytes) a sequence (the larger of operations
    over the bf16 peak and bytes over the HBM peak) over the device time of
    the operations ``pattern`` names on the op line."""
    operations, bytes_moved = (sequences * x for x in cost)

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


def _xplane_of(ctx):
    """The ``.xplane.pb`` that ``ctx["window"]`` was cut from: the harness's
    ``ctx["xplane"]`` where it hands one on; today it does not
    (``chip_bench/scopes.py`` says so), and the file lies under the worker's
    own ``--out``."""
    if ctx.get("xplane"):
        return ctx["xplane"]
    if "--out" in sys.argv[:-1]:
        return trace_reduce.find_xplane(os.path.join(
            sys.argv[sys.argv.index("--out") + 1], "trace"))
    return None


def recompute_ms_per_step(p, ctx):
    """``recompute_ms_step`` (granite-4.0-h-micro's, in this module's own
    copy: a configuration registers what it reads): device milliseconds a
    step of the traced stretch in the operations of the blocks' second
    forward, whose ``op_name`` (their own, or the one ``chip_bench/
    scopes.py`` adopts) lies under ``rematted_computation``.  None where no
    trace was kept or the program recomputes no block."""
    from chip_bench import scopes

    w, path = ctx["window"], _xplane_of(ctx)
    if w is None or not w.ops or not path:
        return None
    seconds = sum(min(op.end, w.hi) - max(op.start, w.lo)
                  for op in scopes.device_ops(path)
                  if min(op.end, w.hi) > max(op.start, w.lo)
                  and (RECOMPUTED in op.tf_op or RECOMPUTED in op.adopted))
    return 1e3 * seconds / w.steps if seconds else None


def model_config(sizes, **overrides):
    """The program's configuration of the share ``sizes`` describes."""
    pattern = tuple(
        LayerKind(mixer="attention" if kind == "*" else "kda",
                  ffn="dense" if dense else None)
        for kind, dense in layer_plan(sizes))
    return ling_3_0_flash_config(**{**dict(
        vocab_size=sizes["vocab_size"], num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"], d_model=sizes["hidden_size"],
        d_ff=sizes["moe_intermediate_size"],
        d_ff_dense=sizes["intermediate_size"],
        d_ff_shared=sizes["num_shared_experts"]
        * sizes["moe_shared_expert_intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        norm_eps=sizes["rms_norm_eps"], rope_theta=float(sizes["rope_theta"]),
        rope_interleave=sizes["rope_interleave"],
        num_experts=sizes["num_experts_published"],
        experts_per_token=sizes["num_experts_per_tok"],
        experts_held=tuple(sizes["experts_held"]),
        norm_topk_prob=sizes["norm_topk_prob"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        moe_groups=sizes["n_group"], moe_groups_kept=sizes["topk_group"],
        q_lora_rank=sizes["q_lora_rank"] or 0,
        kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        kda_head_dim=sizes["head_dim"],
        conv_taps=sizes["short_conv_kernel_size"],
        layer_pattern=pattern, remat=bool(sizes["recompute_blocks"]),
        dtype=jnp.bfloat16, moe_data_axis=PROCESS_AXIS), **overrides})


class Config:
    def __init__(self, sizes):
        self.sizes = z = sizes
        self.per_chip_batch = z["per_chip_batch"]
        self.first_loss = math.log(z["vocab_size"])
        self.held = tuple(z["experts_held"])
        if len(self.held) != z["num_experts"]:
            raise ValueError("num_experts counts the experts held here")
        limits = [z[key][p] for p in z["layers_held"]
                  for key in ("expert_swiglu_limit_list",
                              "share_expert_swiglu_limit_list")]
        if z["tie_word_embeddings"] or z["num_shared_experts"] != 1 \
                or z["hidden_act"] != "silu" or z["use_bias"] \
                or z["use_qkv_bias"] or z["q_lora_rank"] \
                or z["score_function"] != "sigmoid" \
                or not z["moe_router_enable_expert_bias"] \
                or not (z["use_qk_norm"] and z["kda_safe_gate"]
                        and z["no_kda_lora"] and z["linear_silu"]) \
                or z["use_kda_lora"] or z["use_nGPT"] \
                or z["scale_router_input"] or z["value_norm"] \
                or z["up_proj_norm"] or z["use_mla_nope"] \
                or z["num_kv_heads_for_linear_attn"] \
                or z["kda_lower_bound"] != LOWER_BOUND \
                or z["group_norm_size"] != 1 or any(limits) \
                or z["gated_attention_proj_granularity_type"] != "head_wise" \
                or z["rotary_dim"] != z["qk_rope_head_dim"] \
                or z["num_key_value_heads"] != z["num_attention_heads"]:
            raise ValueError(
                "an untied head, one shared expert, silu, no bias, no query "
                "latent, sigmoid scores with the bias, L2-normed q and k "
                "behind a silu'd convolution, the full-rank decay gate "
                "bounded at the kernels' -5, ungrouped heads under a norm a "
                "head, no clamp on an expert, a gate a head on latent "
                "attention and one key head "
                "a query head are the only forms written here")
        self.model = Transformer(model_config(z))
        self.expert_layers = len(self.model.cfg.expert_layers())
        # The harness's named hooks, all set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the reductions readers.py lacks.
        self.reference = _load_reference()
        self._chip_bench_grad = self._checked_once(jax.jit(jax.value_and_grad(
            self.reference.make_loss(z), has_aux=True)))
        readers.REDUCTIONS.update({
            "trace_kda_roofline_pct": _roofline_pct(
                kda_cost(z), self.per_chip_batch),
            "trace_mla_attention_roofline_pct": _roofline_pct(
                mla_attention_cost(z), self.per_chip_batch),
            "trace_recompute_ms_per_step": recompute_ms_per_step})

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
            # when it asks for the next (3.5 GB beside 10.6 of parameters
            # and AdamW state and 3.5 of new gradients).  Its update has
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
        """Hold the program's logits on ``batch`` to the float32 reference's
        by the file's four limits; a reading outside one ends the run."""
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
        print(f"chip_bench {z['name']}: logits from the float32 "
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

    @functools.lru_cache(maxsize=None)
    def _logits(self, dtype, wrong):
        """The jitted ``(params, batch, bias) -> logits [b, s, vocab]``: the
        program's for ``dtype`` None or "program_float32" (neither
        recomputes: there is no backward pass), else the reference's in
        ``dtype``."""
        cfg = dataclasses.replace(self.model.cfg, remat=False)
        timed = Transformer(cfg)
        exact = Transformer(dataclasses.replace(cfg, dtype=jnp.float32))

        def program(params, batch, bias):
            return self._apply(params, bias, batch["tokens"], timed)[0]

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
        aux carries the router's counters and the selection bias, zero.  The
        model's initialisers are the model code's (normal(0.02) every
        matrix, the norms' scales 1, the decay gate's ``A_log`` and
        ``dt_bias`` flash-linear-attention's) but for the embedding's width,
        set here (`assumed.init`)."""
        z = self.sizes
        v = self.model.init(key, jnp.zeros((1, 16), jnp.int32))
        params = nn.meta.unbox(v["params"])
        scale = z["embedding_init_std"] / 0.02
        params["embed"] = {"embedding": params["embed"]["embedding"] * scale}
        return params, moe_counters(
            self.expert_layers, z["num_experts_published"], share=True,
            expert_bias=True)

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
