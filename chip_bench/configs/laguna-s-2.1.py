"""Laguna-S-2.1 at its published widths, cut to one chip's share of a layer
(attention, its gate, the router and the shared expert whole, 8 of 256
experts, an eighth of the vocabulary) and to the first stage's five layers:
the model, its next-token loss, its data and optimizer from a seed, its FLOPs
per sample, the attention kernels' cost from its shapes, by layer kind, and
the reductions that read the recomputed forward, the window layers' kernels
and the rotary positions out of a trace.

The model is the program's (``horovod_tpu/models/transformer.py`` with a
layer pattern whose kinds carry their own head count and rotary table, over
``kernels/masked_attention.py`` at groups of 6 and 9 query heads a KV head
and ``horovod_tpu/parallel/moe.py``), **every block recomputed whole in the
backward pass** (``TransformerConfig.remat``: 12.98 GB of weights, gradients
and AdamW moments leave no room for five layers' activations; the file's
``recomputed`` and ``fit``).  The plain reference is
``laguna-s-2.1_reference.py`` beside this file, which imports nothing of the
program: ``chip_bench/reference.py`` takes its gradient from
``config._chip_bench_grad``, so ``matches_reference`` compares the step under
test with that float32 model and not with the program's own.

The losses of fresh weights lie near ln(vocabulary) in any precision, so the
harness's one limit on them (3e-4) sees a dropped update and little of a
wrong layer or of the rounding.  The configuration therefore brings limits of
its own, in its file, in ``granite-4.0-h-micro``'s form: before the
reference's first step, the program's logits at the timed sizes against the
float32 reference's (``Config.logits_errors``): ``logits_rtol`` on the
difference as a share of the logits' norm, ``logits_median_rtol`` on the
median over the positions of each position's own share, and
``logits_float32_rtol`` on the same model computed in float32 at the highest
precision, where nothing is rounded, so that a wrong layer fails.  The
harness has no place for a configuration's own check (PERF.md section 7
(g)), so a run outside a limit ends there, loudly, with no result line.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import math
import os
import re
import sys
import weakref

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from chip_bench import peaks, readers, trace_reduce
from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
from horovod_tpu.kernels.masked_attention import OP_LINE_NAMES
from horovod_tpu.models.transformer import (
    LayerKind,
    Rotary,
    Transformer,
    laguna_s_2_1_config,
    moe_stats,
)
from horovod_tpu.parallel.moe import count_routing, moe_counters

# What JAX calls the second forward of a block under ``jax.checkpoint`` in an
# operation's ``op_name`` (the profiler's ``tf_op``).
RECOMPUTED = "rematted_computation"
WINDOW, FULL = "sliding_attention", "full_attention"


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "laguna-s-2.1_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_laguna_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def kinds(sizes):
    """[(layer type, query heads, the FFN is the dense one)] of the layers
    held here, each from its published layer's entry of ``layer_types``,
    ``num_attention_heads_per_layer`` and ``mlp_layer_types``."""
    if len(sizes["layers_held"]) != sizes["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers held here")
    return [(sizes["layer_types"][p],
             sizes["num_attention_heads_per_layer"][p],
             sizes["mlp_layer_types"][p] == "dense")
            for p in sizes["layers_held"]]


def allowed_pairs(sizes, layer_type):
    """The (query, key) pairs one sequence's mask allows in a layer of
    ``layer_type``: j <= i, s(s+1)/2, and in a sliding layer i - j < w, which
    takes off the (s-w)(s-w+1)/2 beyond the window."""
    s = sizes["sequence_length"]
    beyond = max(s - sizes["sliding_window"], 0) if layer_type == WINDOW \
        else 0
    return s * (s + 1) // 2 - beyond * (beyond + 1) // 2


def head_pairs(sizes, only=None):
    """The allowed pairs times the query heads, summed over the layers held
    here (over those of type ``only`` where given)."""
    return sum(heads * allowed_pairs(sizes, kind)
               for kind, heads, _ in kinds(sizes) if only in (None, kind))


def matmul_macs(sizes):
    """{name: multiply-adds per sample} of every matrix multiplication of
    **one** forward pass, from the shapes alone.  A sample is one sequence.
    Attention is counted over the pairs each layer's mask allows times that
    layer's query heads, not over the square; the experts at the rows this
    chip's share sees when the routing is even, k * held / published a
    position."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    dh, h_kv = sizes["head_dim"], sizes["num_key_value_heads"]
    width = sizes["moe_intermediate_size"]
    layers = kinds(sizes)
    dense = sum(is_dense for _, _, is_dense in layers)
    sparse = len(layers) - dense
    rows = sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / sizes["num_experts_published"]
    return {
        "q_out": sum(2 * s * d * heads * dh for _, heads, _ in layers),
        "kv": len(layers) * s * d * 2 * h_kv * dh,
        "gate": sum(s * d * heads for _, heads, _ in layers),
        "attention_scores": head_pairs(sizes) * dh,
        "attention_values": head_pairs(sizes) * dh,
        "dense_ffn": dense * s * 3 * d * sizes["intermediate_size"],
        "router": sparse * s * d * sizes["num_experts_published"],
        "shared_expert": sparse * s * 3 * d
        * sizes["shared_expert_intermediate_size"],
        "experts": sparse * s * rows * 3 * d * width,
        "head": s * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, a multiply-add
    counted as 2: 2 forward and 4 backward, **the model's work and not the
    chip's**: the second forward of every block, which this configuration
    recomputes (a third more than is counted here, all but the head's), is
    not counted.  Embedding lookup, norms, rotary positions, the gate's
    sigmoid, softmax, top-k, the sort, gathers and sums of dispatch and
    combine, and AdamW are not counted either."""
    return float(6 * sum(matmul_macs(sizes).values()))


def forwards(sizes):
    """How often a block's forward pass runs a step."""
    return 2 if sizes["recompute_blocks"] else 1


def attention_cost(sizes, only=None):
    """(operations, HBM bytes) of the attention kernels of every layer held
    here (of type ``only`` where given) on one sequence as the step runs
    them: **the forward kernel twice** where the blocks are recomputed (the
    time the share is taken over holds both calls) and the backward kernel
    once.  Per allowed pair and query head 256 multiply-adds a forward call
    (the score and the value at 128) and 512 backward (dv, dp, dq, dk); what
    the backward kernel computes again (the scores) and what a tile of 1024
    holds beyond a window of 512 is not counted: the same work whatever
    kernel does it.  Bytes: q and the output at the layer's heads, k and v at
    the 8 KV heads, once a forward call, those and the gradient of each once
    backward, in bf16."""
    dh, s = sizes["head_dim"], sizes["sequence_length"]
    h_kv = sizes["num_key_value_heads"]
    calls = forwards(sizes) + 2
    operations = 2 * calls * head_pairs(sizes, only) * 2 * dh
    moved = sum(calls * 2 * s * dh * (2 * heads + 2 * h_kv)
                for kind, heads, _ in kinds(sizes) if only in (None, kind))
    return operations, moved


def mixed_attention_cost(sizes):
    """:func:`attention_cost` over the sliding and the full layers alike."""
    return attention_cost(sizes)


def window_attention_cost(sizes):
    """:func:`attention_cost` over the sliding layers alone: the pairs a
    window of ``sliding_window`` allows and nothing a tile pads."""
    return attention_cost(sizes, WINDOW)


def _least_s(operations, bytes_moved):
    kind = jax.local_devices()[0].device_kind
    return max(operations / peaks.peak(kind, "bf16_flops"),
               bytes_moved / peaks.peak(kind, "hbm_bytes_per_s"))


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


@functools.lru_cache(maxsize=1)
def _device_ops(path):
    """The op line of the trace at ``path``, read once for the reductions
    below (a traced run's file is tens of megabytes)."""
    from chip_bench import scopes

    return scopes.device_ops(path)


def _ms_per_step(wanted):
    """A reduction: device milliseconds a step of the traced stretch in the
    operations ``wanted(op, row)`` accepts, ``row`` the operation's block by
    ``chip_bench/scopes.py::row_of`` (the innermost ``hvd.`` scope of its own
    ``op_name`` or of the one adopted for it).  None where no trace was kept
    or no operation is accepted (a program without the scope)."""
    def reduction(p, ctx):
        from chip_bench import scopes

        w, path = ctx["window"], _xplane_of(ctx)
        if w is None or not w.ops or not path:
            return None
        seconds = sum(min(op.end, w.hi) - max(op.start, w.lo)
                      for op in _device_ops(path)
                      if min(op.end, w.hi) > max(op.start, w.lo)
                      and wanted(op, scopes.row_of(op)[0]))
        return 1e3 * seconds / w.steps if seconds else None

    return reduction


# ``recompute_ms_step`` (granite-4.0-h-micro's, in this module's own copy: a
# configuration registers what it reads): the operations of the blocks'
# second forward, whose ``op_name`` (their own, or the adopted one) lies
# under ``rematted_computation``.
recompute_ms_per_step = _ms_per_step(
    lambda op, row: RECOMPUTED in op.tf_op or RECOMPUTED in op.adopted)
# ``window_attention_ms_step``: the attention kernels under
# ``hvd.attn.window`` alone, by their names on the op line.
_KERNEL = re.compile(OP_LINE_NAMES)
window_attention_ms_per_step = _ms_per_step(
    lambda op, row: row == "attn.window" and bool(_KERNEL.search(op.name)))
# ``attn_rope_ms_step``: everything under ``hvd.attn.rope``, forward,
# backward and recomputed.
attn_rope_ms_per_step = _ms_per_step(lambda op, row: row == "attn.rope")


def _roofline_pct(cost, sequences, measured_ms):
    """A reduction: the least time the chip could take for ``sequences``
    times ``cost`` (operations, bytes) a sequence (the larger of operations
    over the bf16 peak and bytes over the HBM peak) over the
    ``measured_ms(p, ctx)`` a step."""
    operations, bytes_moved = (sequences * x for x in cost)

    def reduction(p, ctx):
        ms = measured_ms(p, ctx)
        if not ms:
            return None
        return 100.0 * _least_s(operations, bytes_moved) * 1e3 / ms

    return reduction


def _rotary(rope):
    """One group of ``rope_parameters`` as the program's :class:`Rotary`."""
    return Rotary(
        rope_theta=float(rope["rope_theta"]),
        share=float(rope["partial_rotary_factor"]),
        yarn_factor=float(rope.get("factor", 1.0)),
        yarn_original_max_len=rope.get("original_max_position_embeddings", 0),
        yarn_beta_fast=float(rope.get("beta_fast", 32.0)),
        yarn_beta_slow=float(rope.get("beta_slow", 1.0)),
        attention_factor=rope.get("attention_factor"))


def model_config(sizes, **overrides):
    """The program's configuration of the share ``sizes`` describes."""
    pattern = tuple(
        LayerKind(window=sizes["sliding_window"] if kind == WINDOW else 0,
                  ffn="dense" if dense else None, heads=heads,
                  rotary=_rotary(sizes["rope_parameters"][kind]))
        for kind, heads, dense in kinds(sizes))
    return laguna_s_2_1_config(**{**dict(
        vocab_size=sizes["vocab_size"], num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_width=sizes["head_dim"], d_model=sizes["hidden_size"],
        d_ff=sizes["moe_intermediate_size"],
        d_ff_dense=sizes["intermediate_size"],
        d_ff_shared=sizes["shared_expert_intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        norm_eps=sizes["rms_norm_eps"],
        num_experts=sizes["num_experts_published"],
        experts_per_token=sizes["num_experts_per_tok"],
        experts_held=tuple(sizes["experts_held"]),
        norm_topk_prob=sizes["norm_topk_prob"],
        routed_scaling_factor=float(sizes["moe_routed_scaling_factor"]),
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
        if z["tie_word_embeddings"] or z["attention_bias"] \
                or z["gating"] != "per-head" \
                or z["moe_apply_router_weight_on_input"] \
                or z["moe_router_logit_softcapping"] \
                or z["decoder_sparse_step"] != 1:
            raise ValueError("an untied head, no bias, a gate a head, the "
                             "router's weights on the experts' outputs, no "
                             "soft cap and experts in every layer that is "
                             "not dense are the only forms written here")
        self.model = Transformer(model_config(z))
        self.expert_layers = len(self.model.cfg.expert_layers())
        # The harness's named hooks, all set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the reductions readers.py lacks.
        self.reference = _load_reference()
        self._chip_bench_grad = self._checked_once(jax.jit(jax.value_and_grad(
            self.reference.make_loss(z), has_aux=True)))
        readers.REDUCTIONS.update({
            "trace_mixed_attention_roofline_pct": _roofline_pct(
                mixed_attention_cost(z), self.per_chip_batch,
                readers.REDUCTIONS["trace_op_ms_per_step"]),
            "trace_recompute_ms_per_step": recompute_ms_per_step,
            "trace_window_attention_ms_per_step":
            window_attention_ms_per_step,
            "trace_window_attention_roofline_pct": _roofline_pct(
                window_attention_cost(z), self.per_chip_batch,
                window_attention_ms_per_step),
            "trace_attn_rope_ms_per_step": attn_rope_ms_per_step})

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
            # when it asks for the next (3.2 GB beside 9.7 of parameters and
            # AdamW state and 3.2 of new gradients).  Its update has
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
        by the file's three limits; a reading outside one ends the run."""
        z = self.sizes
        want = self._logits(jnp.float32, ())(params, batch)
        whole, median = (float(x) for x in self._distance(
            self._logits(None, ())(params, batch), want))
        _, exact = (float(x) for x in self._distance(
            self._logits("program_float32", ())(params, batch), want))
        held = ((whole, z["logits_rtol"], "their norm"),
                (median, z["logits_median_rtol"],
                 "a position's norm, the median position"),
                (exact, z["logits_float32_rtol"],
                 "a position's norm, the median position, computed in "
                 "float32"))
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
        del want
        self._logits.cache_clear()

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
        """The jitted ``(params, batch) -> logits [b, s, vocab]``: the
        program's for ``dtype`` None or "program_float32" (neither
        recomputes: there is no backward pass), else the reference's in
        ``dtype``."""
        cfg = dataclasses.replace(self.model.cfg, remat=False)
        timed = Transformer(cfg)
        exact = Transformer(dataclasses.replace(cfg, dtype=jnp.float32))

        def program(params, batch):
            return timed.apply({"params": params}, batch["tokens"],
                               mutable=["moe"])[0]

        def program_float32(params, batch):
            with jax.default_matmul_precision("highest"):
                return exact.apply({"params": params}, batch["tokens"],
                                   mutable=["moe"])[0]

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
        """(params, aux) from a key; meant to run under one ``jax.jit``.
        aux carries the router's counters.  The model's initialisers are the
        model code's (normal(0.02) every matrix, the norms' scales 1) but
        for the embedding's width, set here (`assumed.init`)."""
        z = self.sizes
        v = self.model.init(key, jnp.zeros((1, 16), jnp.int32))
        params = nn.meta.unbox(v["params"])
        scale = z["embedding_init_std"] / 0.02
        params["embed"] = {"embedding": params["embed"]["embedding"] * scale}
        return params, moe_counters(self.expert_layers,
                                    z["num_experts_published"], share=True)

    def make_batch(self, key):
        z = self.sizes
        return {"tokens": jax.random.randint(
            key, (self.per_chip_batch, z["sequence_length"]), 0,
            z["vocab_size"])}

    def loss(self, params, aux, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        logits, state = self.model.apply({"params": params}, tokens,
                                         mutable=["moe"])
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
