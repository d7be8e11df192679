"""granite-4.0-h-micro at its published widths, cut to one period of its
layer pattern (nine Mamba-2 mixers of 64 heads in one group to one attention
layer without positions, each with a dense SwiGLU) and an eighth of its
vocabulary: the model, its next-token loss, its data and optimizer from a
seed, its FLOPs per sample, the scan kernels' cost from its shapes and the
reduction that reads the recomputed forward out of a trace.

The model is the program's (``horovod_tpu/models/transformer.py`` with a
layer pattern of ``LayerKind(mixer="mamba2", ffn="dense")`` and
``LayerKind(mixer="attention", rope=False, ffn="dense")``, the four muP
scalars as fields of its configuration, over ``horovod_tpu/models/mamba2.py``,
``horovod_tpu/kernels/ssd_scan.py`` and ``kernels/masked_attention.py``),
**every block recomputed whole in the backward pass**
(``TransformerConfig.remat``: 12.35 GB of weights, gradients and AdamW
moments leave no room for ten layers' activations; the file's ``recomputed``
and ``fit``).  The plain reference is ``granite-4.0-h-micro_reference.py``
beside this file, which imports nothing of the program and runs the
recurrence a token at a time: ``chip_bench/reference.py`` takes its gradient
from ``config._chip_bench_grad``, so ``matches_reference`` compares the step
under test with that float32 model and not with the program's own.

The losses of fresh weights lie near ln(vocabulary) in any precision, so the
harness's one limit on them (3e-4) sees a dropped update and little of a
wrong layer or of the rounding.  The configuration therefore brings limits
of its own, in its file, in ``nemotron-3-super-120b-a12b``'s form: before the
reference's first step, the program's logits at the timed sizes against the
float32 reference's (``Config.logits_errors``): ``logits_rtol`` on the
difference as a share of the logits' norm and ``logits_median_rtol`` on the
median over the positions of each position's own share, both between the
program's reading and the bf16 reference's, and ``logits_float32_rtol`` on
the same model computed in float32 at the highest precision, where nothing is
rounded, so that a wrong layer or a wrong scalar fails.  The harness has no
place for a configuration's own check (PERF.md section 7 (g)), so a run
outside a limit ends there, loudly, with no result line.
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
from horovod_tpu.models.transformer import (
    LayerKind,
    Transformer,
    granite_4_0_h_micro_config,
)

# What JAX calls the second forward of a block under ``jax.checkpoint`` in an
# operation's ``op_name`` (the profiler's ``tf_op``).
RECOMPUTED = "rematted_computation"


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "granite-4.0-h-micro_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_granite_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_plan(sizes):
    """The types of the layers held here, each as the published layer it is:
    ``"mamba"`` or ``"attention"``."""
    if len(sizes["layers_held"]) != sizes["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers held here")
    return [sizes["layer_types"][p] for p in sizes["layers_held"]]


def _scan_macs(sizes):
    """Multiply-adds of the chunked scan of one Mamba-2 layer on one
    sequence, as the algorithm needs them: a chunk's ``C B^T`` a group, the
    causal half of its ``[Q, Q] x [Q, P]`` product a head, and the two
    products with the state a head."""
    s, q = sizes["sequence_length"], sizes["chunk_size"]
    heads, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    groups, n = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    chunk = groups * q * q * n + heads * (q * (q + 1) // 2 * p
                                          + 2 * q * n * p)
    return s // q * chunk


def matmul_macs(sizes):
    """{name: multiply-adds per sample} of every matrix multiplication and
    convolution of **one** forward pass, from the shapes alone.  A sample is
    one sequence.  Attention is counted over the causal pairs and not over
    the square, the scan by its chunked form (:func:`_scan_macs`)."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = d // h
    plan = layer_plan(sizes)
    mixers, attns = plan.count("mamba"), plan.count("attention")
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    conv_dim = inner + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    pairs = attns * s * (s + 1) // 2
    return {
        "mamba_proj": mixers * s * d * (2 * inner + conv_dim
                                        + sizes["mamba_n_heads"]),
        "mamba_conv": mixers * s * conv_dim * sizes["mamba_d_conv"],
        "mamba_scan": mixers * _scan_macs(sizes),
        "qkvo": attns * s * (2 * d * h * dh + 2 * d * h_kv * dh),
        "attention_scores": pairs * h * dh,
        "attention_values": pairs * h * dh,
        "ffn": len(plan) * s * 3 * d * sizes["shared_intermediate_size"],
        "head": s * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, the taps and the
    scan's products, a multiply-add counted as 2: 2 forward and 4 backward,
    **the model's work and not the chip's**: the second forward of every
    block, which this configuration recomputes, is not counted (a third more
    than is counted here, all but the head's).  Embedding lookup, norms, the
    gates, the decays, softmax and AdamW are not counted either."""
    return float(6 * sum(matmul_macs(sizes).values()))


def ssd_scan_cost(sizes):
    """(operations, HBM bytes) of ``kernels/ssd_scan.py``'s kernels over
    every Mamba-2 layer held here on one sequence, as the step runs them:
    **the forward kernel twice** (the blocks are recomputed, and the time the
    share is taken over holds both calls) and the backward kernel once.
    Operations: the chunked form's products, 2 a multiply-add forward (each
    time) and 4 backward.  Bytes, a forward call: ``x``, ``B``, ``C`` read
    and ``y`` written in bf16 and the state every chunk starts from written
    in fp32; the backward call: ``x``, ``B``, ``C``, ``dy`` and those states
    read, ``dx``, ``dB``, ``dC`` written; ``dt`` and the cumulative sums,
    fp32 a head and position, read by every call and their cotangents
    written."""
    s, q = sizes["sequence_length"], sizes["chunk_size"]
    heads, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    groups, n = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    mixers = layer_plan(sizes).count("mamba")
    forwards = 2 if sizes["recompute_blocks"] else 1
    x, bc = 2 * s * heads * p, 2 * 2 * s * groups * n
    states = 4 * (s // q) * heads * p * n
    small = 4 * 2 * s * heads
    forward = x + bc + x + states + 2 * small
    backward = (2 * x + bc + states + 2 * small) + (x + bc + 2 * small)
    return ((2 * forwards + 4) * mixers * _scan_macs(sizes),
            mixers * (forwards * forward + backward))


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
    """The reduction behind ``recompute_ms_step``: device milliseconds a
    step in operations of the blocks' second forward, those whose ``op_name``
    (their own, or the one ``chip_bench/scopes.py`` adopts for an instruction
    of XLA's) lies under ``rematted_computation``.  None where no trace was
    kept or the program recomputes no block."""
    from chip_bench import scopes

    w, path = ctx["window"], _xplane_of(ctx)
    if w is None or not w.ops or not path:
        return None
    seconds = sum(
        min(op.end, w.hi) - max(op.start, w.lo)
        for op in scopes.device_ops(path)
        if min(op.end, w.hi) > max(op.start, w.lo)
        and (RECOMPUTED in op.tf_op or RECOMPUTED in op.adopted))
    return 1e3 * seconds / w.steps if seconds else None


class Config:
    def __init__(self, sizes):
        self.sizes = z = sizes
        self.per_chip_batch = z["per_chip_batch"]
        self.first_loss = math.log(z["vocab_size"])
        if z["num_local_experts"] or z["num_experts_per_tok"] \
                or not z["tie_word_embeddings"] or z["attention_bias"] \
                or z["mamba_proj_bias"] or not z["mamba_conv_bias"] \
                or z["position_embedding_type"] != "nope" \
                or z["normalization_function"] != "rmsnorm" \
                or z["hidden_act"] != "silu" \
                or z["mamba_expand"] * z["hidden_size"] \
                != z["mamba_n_heads"] * z["mamba_d_head"]:
            raise ValueError("no experts, a tied readout, no biases but the "
                             "convolution's, no positions, RMSNorm and silu "
                             "are the only forms written here")
        kinds = {"mamba": LayerKind(0, False, "mamba2", "dense"),
                 "attention": LayerKind(0, False, "attention", "dense")}
        self.model = Transformer(granite_4_0_h_micro_config(
            vocab_size=z["vocab_size"], num_layers=z["num_hidden_layers"],
            num_heads=z["num_attention_heads"],
            num_kv_heads=z["num_key_value_heads"],
            head_width=z["hidden_size"] // z["num_attention_heads"],
            d_model=z["hidden_size"], d_ff=z["intermediate_size"],
            d_ff_dense=z["shared_intermediate_size"],
            max_len=z["max_position_embeddings"], norm_eps=z["rms_norm_eps"],
            mamba_heads=z["mamba_n_heads"], mamba_head_dim=z["mamba_d_head"],
            mamba_groups=z["mamba_n_groups"], mamba_state=z["mamba_d_state"],
            mamba_conv=z["mamba_d_conv"], mamba_chunk=z["chunk_size"],
            mamba_dt_limits=(z["time_step_min"], z["time_step_max"],
                             z["time_step_floor"]),
            embedding_multiplier=float(z["embedding_multiplier"]),
            residual_multiplier=float(z["residual_multiplier"]),
            attention_multiplier=float(z["attention_multiplier"]),
            logits_scaling=float(z["logits_scaling"]),
            layer_pattern=tuple(kinds[k] for k in layer_plan(z)),
            remat=bool(z["recompute_blocks"]), dtype=jnp.bfloat16))
        # The harness's named hooks, all set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the two reductions readers.py lacks.
        self.reference = _load_reference()
        self._chip_bench_grad = self._checked_once(jax.jit(jax.value_and_grad(
            self.reference.make_loss(sizes), has_aux=True)))
        readers.REDUCTIONS["trace_ssd_scan_roofline_pct"] = \
            _ssd_scan_roofline_pct(sizes)
        readers.REDUCTIONS["trace_recompute_ms_per_step"] = \
            recompute_ms_per_step

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
            # when it asks for the next (3.1 GB beside 9.3 of parameters and
            # AdamW state and 3.1 of new gradients: more than the chip has
            # with the reference's scratch).  Its update has consumed that
            # gradient, so its buffers go here, before the next ones are
            # made.  Weak references: the last step's gradient goes with the
            # harness's own name for it.
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
                exact = float(self._distance(
                    self._logits("program_float32", ())(params, batch),
                    want)[0])
                held = ((whole, z["logits_rtol"], "their norm"),
                        (median, z["logits_median_rtol"],
                         "a position's norm, the median position"),
                        (exact, z["logits_float32_rtol"],
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
                # The reference's step needs the room: the three programs
                # above hold their scratch while loaded.
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
        ``dtype`` None or "program_float32" (neither recomputes: there is no
        backward pass), else the reference's in ``dtype``."""
        cfg = dataclasses.replace(self.model.cfg, remat=False)
        timed = Transformer(cfg)
        exact = Transformer(dataclasses.replace(cfg, dtype=jnp.float32))

        def program(params, batch):
            return timed.apply({"params": params}, batch["tokens"])

        def program_float32(params, batch):
            with jax.default_matmul_precision("highest"):
                return exact.apply({"params": params}, batch["tokens"])

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

    def init(self, key):
        """(params, aux) from a key; meant to run under one ``jax.jit``.  The
        model keeps no state from step to step: aux is empty.  The
        initialisers are the model code's (`assumed.init`)."""
        v = self.model.init(key, jnp.zeros((1, 16), jnp.int32))
        return nn.meta.unbox(v["params"]), {}

    def make_batch(self, key):
        z = self.sizes
        return {"tokens": jax.random.randint(
            key, (self.per_chip_batch, z["sequence_length"]), 0,
            z["vocab_size"])}

    def loss(self, params, aux, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        logits = self.model.apply({"params": params}, tokens)
        # Position i is held to token i + 1; the last position has no next
        # token and weighs nothing (a roll and a weight keep the shapes
        # whole, where a slice would leave 8191 positions).
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1))
        return jnp.sum(nll * (jnp.arange(s) < s - 1)) / (b * (s - 1)), aux

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
