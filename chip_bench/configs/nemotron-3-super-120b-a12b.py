"""NVIDIA-Nemotron-3-Super-120B-A12B at its published widths, cut to one
chip's share of a layer (a group of the Mamba-2 mixers' heads, 4 query heads
on their KV head, 8 of 512 experts, an eighth of the vocabulary) and to one
period of its layer pattern: the model, its next-token loss, the selection
bias it keeps from step to step, its data and optimizer from a seed, its
FLOPs per sample and the scan kernels' cost from its shapes.

The model is the program's (``horovod_tpu/models/transformer.py`` with a
layer pattern whose layers are a mixer alone or an FFN alone, over
``horovod_tpu/models/mamba2.py``, ``horovod_tpu/kernels/ssd_scan.py``,
``kernels/masked_attention.py`` and ``horovod_tpu/parallel/moe.py``), stepped
through ``config.loss``, whose ``aux`` carries the router's counters and the
bias ``b`` and whose new ``aux`` carries ``b`` stepped by the counts of the
step.  The plain reference is ``nemotron-3-super-120b-a12b_reference.py``
beside this file, which imports nothing of the program and runs the
recurrence a token at a time: ``chip_bench/reference.py`` takes its gradient
from ``config._chip_bench_grad``, so ``matches_reference`` compares the step
under test with that float32 model and not with the program's own.

The losses of fresh weights lie near ln(vocabulary) in any precision, so the
harness's one limit on them (3e-4) sees a dropped update and little of a
wrong layer or of the rounding.  The configuration therefore brings limits
of its own, in its file, in ``lfm2-8b-a1b``'s form: before the reference's
first step, the program's logits at the timed sizes against the float32
reference's (``Config.logits_errors``): ``logits_rtol`` on the difference as
a share of the logits' norm, ``logits_median_rtol`` on the median over the
positions of each position's own share (which the positions that chose
another expert under a rounded router input do not move), and two on the
same model computed in float32 at the highest precision, where nothing is
rounded, so that a wrong layer fails: ``logits_float32_rtol`` on the median
position's share (1e-6 on every seed) and ``logits_float32_norm_rtol`` on
the share of the norm, which has to leave room for the one position in
some seeds whose 22nd and 23rd of 512 scores lie within float32's rounding
of each other and that takes another expert than the reference's (3e-4 of
the norm from one position of 8192; the file's ``reference_limits`` has
every reading).  The
harness has no place for a configuration's own check (PERF.md section 7
(g)), so a run outside a limit ends there, loudly, with no result line.
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
    Transformer,
    expert_bias_collection,
    hybrid_pattern,
    moe_stats,
    nemotron_3_super_config,
)
from horovod_tpu.parallel.moe import count_routing, moe_counters


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "nemotron-3-super-120b-a12b_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_nemotron_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_plan(sizes):
    """The letters of the layers held here, each as the published layer it
    is: ``"M"`` a Mamba-2 mixer, ``"E"`` the expert layer, ``"*"``
    attention."""
    if len(sizes["layers_held"]) != sizes["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers held here")
    return [sizes["hybrid_override_pattern"][p] for p in sizes["layers_held"]]


def _scan_macs(sizes):
    """Multiply-adds of the chunked scan of one Mamba-2 layer on one
    sequence, as the algorithm needs them: a chunk's ``C B^T`` a group, the
    causal half of its ``[Q, Q] x [Q, P]`` product a head, and the two
    products with the state a head (what the chunk reads of it and what it
    adds to it)."""
    s, q = sizes["sequence_length"], sizes["chunk_size"]
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n = sizes["n_groups"], sizes["ssm_state_size"]
    chunk = groups * q * q * n + heads * (q * (q + 1) // 2 * p
                                          + 2 * q * n * p)
    return s // q * chunk


def matmul_macs(sizes):
    """{name: multiply-adds per sample} of every matrix multiplication and
    convolution of the forward pass, from the shapes alone.  A sample is one
    sequence.  Attention is counted over the causal pairs and not over the
    square; the experts at the rows this chip's share sees when the routing
    is even, k * held / published a position; the scan by its chunked form
    (:func:`_scan_macs`)."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = sizes["head_dim"]
    plan = layer_plan(sizes)
    mixers, sparse, attns = (plan.count(x) for x in "ME*")
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    conv_dim = inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    latent = sizes["moe_latent_size"]
    rows = sizes["num_experts_per_tok"] * sizes["n_routed_experts"] \
        / sizes["n_routed_experts_published"]
    pairs = attns * s * (s + 1) // 2
    return {
        "mamba_proj": mixers * s * d * (2 * inner + conv_dim
                                        + sizes["mamba_num_heads"]),
        "mamba_conv": mixers * s * conv_dim * sizes["conv_kernel"],
        "mamba_scan": mixers * _scan_macs(sizes),
        "qkvo": attns * s * (2 * d * h * dh + 2 * d * h_kv * dh),
        "attention_scores": pairs * h * dh,
        "attention_values": pairs * h * dh,
        "router": sparse * s * d * sizes["n_routed_experts_published"],
        "latent": sparse * s * 2 * d * latent,
        "shared_expert": sparse * s * 2 * d * sizes["n_shared_experts"]
        * sizes["moe_shared_expert_intermediate_size"],
        "experts": sparse * s * rows * 2 * latent
        * sizes["moe_intermediate_size"],
        "head": s * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, the taps and the
    scan's products, a multiply-add counted as 2, nothing recomputed: 2
    forward and 4 backward.  Embedding lookup, norms, the gates, the decays,
    softmax, sigmoid, top-k, the sort, gathers and sums of dispatch and
    combine, and AdamW are not counted."""
    return float(6 * sum(matmul_macs(sizes).values()))


def ssd_scan_cost(sizes):
    """(operations, HBM bytes) of ``kernels/ssd_scan.py``'s two kernels over
    every Mamba-2 layer held here on one sequence, as the algorithm needs
    them.  Operations: the chunked form's products, 2 forward and 4 backward
    a multiply-add (the decays' exponentials and the masks are not counted).
    Bytes: forward ``x``, ``B``, ``C`` read and ``y`` written in bf16 and the
    state every chunk starts from written in fp32 (the backward pass starts
    from those and from no state a token); backward ``x``, ``B``, ``C``,
    ``dy`` and those states read, ``dx``, ``dB``, ``dC`` written; ``dt`` and
    the cumulative sums, fp32 a head and position, read twice and their
    cotangents written."""
    s, q = sizes["sequence_length"], sizes["chunk_size"]
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n = sizes["n_groups"], sizes["ssm_state_size"]
    mixers = layer_plan(sizes).count("M")
    x, bc = 2 * s * heads * p, 2 * 2 * s * groups * n
    states = 4 * (s // q) * heads * p * n
    small = 4 * 2 * s * heads
    moved = (x + bc + x + states + 2 * small) \
        + (2 * x + bc + states + 2 * small) + (x + bc + 2 * small)
    return 6 * mixers * _scan_macs(sizes), mixers * moved


def _ssd_scan_roofline_pct(sizes):
    """The reduction behind ``ssd_scan_roofline_pct``: the least time the
    chip could take for the scan kernels of one step (the larger of
    operations over the bf16 peak and bytes over the HBM peak: the bytes)
    over the time they took on the device's op line."""
    operations, bytes_moved = ssd_scan_cost(sizes)
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
        if len(self.held) != sizes["n_routed_experts"]:
            raise ValueError("n_routed_experts counts the experts held here")
        groups = tuple(sizes["mamba_groups_held"])
        per_group = sizes["mamba_num_heads_published"] \
            // sizes["n_groups_published"]
        if len(groups) != sizes["n_groups"] \
                or sizes["mamba_num_heads"] != per_group * len(groups):
            raise ValueError("n_groups and mamba_num_heads count the groups "
                             "and heads held here")
        if sizes["num_nextn_predict_layers"] or sizes["tie_word_embeddings"] \
                or sizes["n_shared_experts"] != 1 \
                or sizes["mlp_hidden_act"] != "relu2" \
                or not sizes["use_conv_bias"] or sizes["mamba_proj_bias"]:
            raise ValueError("no prediction module, an untied head, one "
                             "shared expert, relu2 and a convolution with "
                             "bias are the only forms written here")
        self.model = Transformer(nemotron_3_super_config(
            vocab_size=sizes["vocab_size"],
            num_layers=sizes["num_hidden_layers"],
            num_heads=sizes["num_attention_heads"],
            num_kv_heads=sizes["num_key_value_heads"],
            head_width=sizes["head_dim"], d_model=sizes["hidden_size"],
            d_ff=sizes["moe_intermediate_size"],
            max_len=sizes["max_position_embeddings"],
            norm_eps=sizes["norm_eps"],
            num_experts=sizes["n_routed_experts_published"],
            experts_per_token=sizes["num_experts_per_tok"],
            experts_held=self.held, norm_topk_prob=sizes["norm_topk_prob"],
            routed_scaling_factor=float(sizes["routed_scaling_factor"]),
            moe_latent=sizes["moe_latent_size"],
            d_ff_shared=sizes["moe_shared_expert_intermediate_size"],
            mamba_heads=sizes["mamba_num_heads_published"],
            mamba_head_dim=sizes["mamba_head_dim"],
            mamba_groups=sizes["n_groups_published"],
            mamba_groups_held=groups, mamba_state=sizes["ssm_state_size"],
            mamba_conv=sizes["conv_kernel"], mamba_chunk=sizes["chunk_size"],
            mamba_dt_limits=(sizes["time_step_min"], sizes["time_step_max"],
                             sizes["time_step_floor"]),
            layer_pattern=hybrid_pattern(layer_plan(sizes)),
            dtype=jnp.bfloat16, moe_data_axis=PROCESS_AXIS))
        self.expert_layers = len(self.model.cfg.expert_layers())
        # The harness's named hooks, both set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the one reduction readers.py lacks.
        self.reference = _load_reference()
        self._chip_bench_grad = self._checked_once(jax.jit(jax.value_and_grad(
            self.reference.make_loss(sizes), has_aux=True)))
        readers.REDUCTIONS["trace_ssd_scan_roofline_pct"] = \
            _ssd_scan_roofline_pct(sizes)

    def _checked_once(self, grad):
        """``grad``, the plain reference's, behind the configuration's own
        limits: its first call, which the harness makes on the seed's fresh
        weights and batch, first holds the program's logits to the
        reference's.  A gradient it returned is deleted at the next call
        (the harness has used it by then), or the reference's steps would
        not fit beside the harness's state."""
        pending, last = [True], []

        def checked(params, aux, batch):
            # `reference_losses` still names the gradient of its last step
            # when it asks for the next (2.8 GB beside 8.4 of parameters and
            # AdamW state, 2.8 of new gradients and 3.3 of this program's
            # scratch: more than the chip has).  Its update has consumed
            # that gradient, so its buffers go here, before the next ones
            # are made.  Weak references: the last step's gradient goes
            # with the harness's own name for it.
            for old in (ref() for ref in last):
                if old is not None:
                    old.delete()
            last.clear()
            if pending:
                pending.clear()
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
                         "a position's norm, the median position, computed "
                         "in float32"),
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
                            f"chip_bench {z['name']}: FAILED: the program's "
                            f"logits lie {error:.3e} of {what} from the "
                            "float32 reference's, over the limit "
                            f"{limit:.2e}")
                # The reference's step needs the room: beside the harness's
                # parameters and AdamW state (8.4 GB) it takes 6.6 GB, and
                # the three programs above hold their scratch while loaded.
                del want, operands
                self._logits.cache_clear()
            out = grad(params, aux, batch)
            last.extend(weakref.ref(g)
                        for g in jax.tree_util.tree_leaves(out[1]))
            return out

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
        aux carries the router's counters and the selection bias, zero.  The
        model's initialisers are the model code's but for two things set
        here (`assumed.init`): the embedding's width, and the mixers'
        ``out_proj`` under ``rescale_prenorm_residual``."""
        z = self.sizes
        v = self.model.init(key, jnp.zeros((1, 16), jnp.int32))
        params = nn.meta.unbox(v["params"])
        scale = z["embedding_init_std"] / 0.02
        params["embed"] = {"embedding": params["embed"]["embedding"] * scale}
        if z["rescale_prenorm_residual"]:
            # kaiming_uniform(a=sqrt(5)) over the whole mixer's inner width,
            # of which the rows here are a share, over sqrt(layers).
            bound = (z["mamba_num_heads_published"] * z["mamba_head_dim"]) \
                ** -0.5 / math.sqrt(z["num_hidden_layers_published"])
            for i, letter in enumerate(layer_plan(z)):
                if letter == "M":
                    out = params[f"layer_{i}"]["mamba"]["out_proj"]
                    out["kernel"] = jax.random.uniform(
                        jax.random.fold_in(key, 7919 + i),
                        out["kernel"].shape, jnp.float32, -bound, bound)
        return params, moe_counters(
            self.expert_layers, z["n_routed_experts_published"],
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
