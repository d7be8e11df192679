"""Keye-VL-2.0-30B-A3B's language model at its published widths, cut to one
chip's share of a layer (attention, the indexer, the router and the norms
whole, 16 of 128 experts, an eighth of the vocabulary) and to four layers:
the model, its three-term loss (next-token cross-entropy, the router's
load-balancing term, the indexer's divergence), its data and optimizer from a
seed, its FLOPs per sample, the cost of attention over the chosen pairs and of
the indexer from its shapes, and the reductions that read both out of a trace.

The model is the program's (``horovod_tpu/models/transformer.py`` with
``models/indexer.py`` in front of every attention layer, over
``kernels/masked_attention.py`` under a mask that is data,
``kernels/dsa.py`` and ``horovod_tpu/parallel/moe.py``), stepped through
``config.loss``.  The plain reference is ``keye-vl-2.0-30b-a3b_reference.py``
beside this file, which imports nothing of the program:
``chip_bench/reference.py`` takes its gradient from
``config._chip_bench_grad``, so ``matches_reference`` compares the step under
test with that float32 model and not with the program's own.

The losses of fresh weights lie near ln(vocabulary) in any precision, so the
harness's one limit on them (3e-4) sees a dropped update and little of a wrong
layer or of the rounding.  The configuration therefore brings limits of its
own, in its file, in ``laguna-s-2.1``'s form: before the reference's first
step, the program's logits at the timed sizes against the float32 reference's
(``Config.logits_errors``): ``logits_rtol`` on the difference as a share of
the logits' norm, ``logits_median_rtol`` on the median over the positions of
each position's own share, ``logits_float32_rtol`` on the same model computed
in float32 at the highest precision, where nothing is rounded, so that a wrong
layer fails; and, what this configuration adds, **the chosen sets
themselves**: ``chosen_sets_differ_share`` on the share of a layer's chosen
pairs that the program's float32 model chooses and the reference does not
(``Config.chosen_sets_differ``).  The harness has no place for a
configuration's own check (PERF.md section 7 (g)), so a run outside a limit
ends there, loudly, with no result line.
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
    Transformer,
    keye_vl_2_0_30b_a3b_config,
    moe_stats,
)
from horovod_tpu.parallel.moe import count_routing, moe_counters

# The indexer's kernels on the op line (``horovod_tpu/kernels/dsa.py``; named
# here and not imported: a program without them is read alike).
INDEXER_KERNELS = r"^hvd_dsa_"


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "keye-vl-2.0-30b-a3b_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_keye_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def causal_pairs(sizes):
    """The (query, key) pairs key <= query of one sequence: what the indexer
    scores."""
    s = sizes["sequence_length"]
    return s * (s + 1) // 2


def chosen_pairs(sizes):
    """The pairs the chosen sets of one sequence hold: every causal pair
    below ``topk`` positions, ``topk`` a query from there on."""
    s, topk = sizes["sequence_length"], sizes["sa_config"]["topk"]
    full = min(s, topk)
    return full * (full + 1) // 2 + (s - full) * topk


def matmul_macs(sizes):
    """{name: multiply-adds per sample} of every matrix multiplication of the
    forward pass and of the indexer's loss, from the shapes alone, **as the
    equations state them and not as a dense tile computes them**.  A sample
    is one sequence.  Attention over the chosen pairs; the indexer's scores
    over the causal pairs (the choice needs every one); the target's pass (the
    attention's scores once more) and the divergence's scores over the chosen
    pairs; the experts at the rows this chip's share sees when the routing is
    even, k * held / published a position."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    layers = sizes["num_hidden_layers"]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh, sa = sizes["head_dim"], sizes["sa_config"]
    index = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    rows = sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / sizes["num_experts_published"]
    return {
        "qkvo": layers * s * (2 * d * h * dh + 2 * d * h_kv * dh),
        "attention_scores": layers * chosen_pairs(sizes) * h * dh,
        "attention_values": layers * chosen_pairs(sizes) * h * dh,
        "indexer_projections": layers * s * d * (
            index + sa["indexer_head_dim"] + sa["indexer_num_heads"]),
        "indexer_scores": layers * causal_pairs(sizes) * index,
        "indexer_target": layers * chosen_pairs(sizes) * h * dh,
        "indexer_loss_scores": layers * chosen_pairs(sizes) * index,
        "router": layers * s * d * sizes["num_experts_published"],
        "experts": layers * s * rows * 3 * d * sizes["moe_intermediate_size"],
        "head": s * d * sizes["vocab_size"],
    }


# What has no backward pass: the choice's scores and the target are cut from
# the graph.
FORWARD_ONLY = ("indexer_scores", "indexer_target")


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, a multiply-add
    counted as 2: 2 forward and 4 backward, but for the two passes that are
    cut from the graph (:data:`FORWARD_ONLY`: 2).  Embedding lookup, norms,
    rotary positions, softmax, the choice of the keys, top-k of the router,
    the sort and gathers of dispatch and combine, and AdamW are not
    counted."""
    return float(sum((2 if name in FORWARD_ONLY else 6) * macs
                     for name, macs in matmul_macs(sizes).items()))


def sparse_attention_cost(sizes):
    """(operations, HBM bytes) of attention over the **chosen** pairs of
    every layer on one sequence as the step runs it: two products forward
    (scores, values) and four backward (dv, dp, dq, dk) a chosen pair and
    query head; what the backward kernel computes again (the scores) and
    what a dense tile computes and masks away (three quarters of the causal
    pairs at 16,384 positions) is not counted: the same work whatever kernel
    does it.  Bytes: q, k, v and the output once forward, those, the gradient
    of each and the chosen sets' words (a bit a causal pair, fetched by each
    kernel) once backward, in bf16."""
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh, s = sizes["head_dim"], sizes["sequence_length"]
    layers = sizes["num_hidden_layers"]
    forwards = 2 if sizes["recompute_blocks"] else 1
    operations = layers * 2 * (forwards + 2) * chosen_pairs(sizes) \
        * h * 2 * dh
    words = s * s // 8
    moved = layers * ((forwards + 2) * 2 * s * dh * (2 * h + 2 * h_kv)
                      + (forwards + 1) * words)
    return operations, moved


def indexer_cost(sizes):
    """(operations, HBM bytes) of the indexer's two kernels of every layer
    on one sequence (``hvd_dsa_choose``, ``hvd_dsa_loss``): the scores'
    products over the causal pairs for the choice; over the chosen pairs the
    scores once more, the target's product with the attention's q and k, and
    the gradient's two products; the counting that finds the 2048th largest
    score of a row is no product and is not counted.  Bytes: the indexer's
    q, k and weights read by both kernels, the attention's q, k and
    log-sum-exp read by the second, the words written and read, the three
    gradients written."""
    s = sizes["sequence_length"]
    h, h_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh, sa = sizes["head_dim"], sizes["sa_config"]
    heads, width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    layers = sizes["num_hidden_layers"]
    operations = layers * 2 * (
        causal_pairs(sizes) * heads * width
        + chosen_pairs(sizes) * (3 * heads * width + h * dh))
    own = s * (2 * heads * width + 2 * width + 4 * heads)
    moved = layers * (3 * own + 2 * s * dh * (h + h_kv) + 4 * s * h
                      + 2 * s * s // 8)
    return operations, moved


def _least_s(operations, bytes_moved):
    kind = jax.local_devices()[0].device_kind
    return max(operations / peaks.peak(kind, "bf16_flops"),
               bytes_moved / peaks.peak(kind, "hbm_bytes_per_s"))


def _xplane_of(ctx):
    """The ``.xplane.pb`` that ``ctx["window"]`` was cut from: the harness's
    ``ctx["xplane"]`` where it hands one on; today it does not, and the file
    lies under the worker's own ``--out``."""
    if ctx.get("xplane"):
        return ctx["xplane"]
    if "--out" in sys.argv[:-1]:
        return trace_reduce.find_xplane(os.path.join(
            sys.argv[sys.argv.index("--out") + 1], "trace"))
    return None


@functools.lru_cache(maxsize=1)
def _device_ops(path):
    from chip_bench import scopes

    return scopes.device_ops(path)


def _ms_per_step(wanted):
    """A reduction: device milliseconds a step of the traced stretch in the
    operations ``wanted(op, row)`` accepts, ``row`` the operation's block by
    ``chip_bench/scopes.py::row_of``.  None where no trace was kept or no
    operation is accepted (a program without the scope or the kernel)."""
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


_ATTENTION_KERNEL = re.compile(OP_LINE_NAMES)
_INDEXER_KERNEL = re.compile(INDEXER_KERNELS)
# ``sparse_attention_ms_step``: the attention kernels under
# ``hvd.attn.sparse``, by their names on the op line.
sparse_attention_ms_per_step = _ms_per_step(
    lambda op, row: row == "attn.sparse"
    and bool(_ATTENTION_KERNEL.search(op.name)))
# ``indexer_ms_step``: everything under the ``hvd.indexer.*`` scopes and the
# indexer's kernels by name, forward and backward.
indexer_ms_per_step = _ms_per_step(
    lambda op, row: row.startswith("indexer.")
    or bool(_INDEXER_KERNEL.search(op.name)))
# The two kernels alone, for their share of a roofline.
indexer_kernels_ms_per_step = _ms_per_step(
    lambda op, row: bool(_INDEXER_KERNEL.search(op.name)))


def _roofline_pct(cost, sequences, measured_ms):
    """A reduction: the least time the chip could take for ``sequences``
    times ``cost`` (operations, bytes) a sequence over the
    ``measured_ms(p, ctx)`` a step."""
    operations, bytes_moved = (sequences * x for x in cost)

    def reduction(p, ctx):
        ms = measured_ms(p, ctx)
        if not ms:
            return None
        return 100.0 * _least_s(operations, bytes_moved) * 1e3 / ms

    return reduction


def model_config(sizes, **overrides):
    """The program's configuration of the share ``sizes`` describes."""
    sa = sizes["sa_config"]
    return keye_vl_2_0_30b_a3b_config(**{**dict(
        vocab_size=sizes["vocab_size"], num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_width=sizes["head_dim"], d_model=sizes["hidden_size"],
        d_ff=sizes["moe_intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        norm_eps=sizes["rms_norm_eps"],
        rope_theta=float(sizes["rope_theta"]),
        num_experts=sizes["num_experts_published"],
        experts_per_token=sizes["num_experts_per_tok"],
        experts_held=tuple(sizes["experts_held"]),
        norm_topk_prob=sizes["norm_topk_prob"],
        indexer_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], indexer_topk=sa["topk"],
        remat=bool(sizes["recompute_blocks"]), dtype=jnp.bfloat16,
        moe_data_axis=PROCESS_AXIS), **overrides})


class Config:
    def __init__(self, sizes):
        self.sizes = z = sizes
        self.per_chip_batch = z["per_chip_batch"]
        self.first_loss = math.log(z["vocab_size"])
        self.held = tuple(z["experts_held"])
        if len(self.held) != z["num_experts"] \
                or z["num_local_experts"] != z["num_experts"]:
            raise ValueError("num_experts and num_local_experts count the "
                             "experts held here")
        if z["tie_word_embeddings"] or z["attention_bias"] \
                or z["decoder_sparse_step"] != 1 or z["mlp_only_layers"] \
                or z["use_sliding_window"] or z["hidden_act"] != "silu" \
                or z["sa_config"]["indexer_num_kv_heads"] != 1:
            raise ValueError("an untied head, no bias, experts in every "
                             "layer, no window, silu and one indexer key "
                             "for all heads are the only forms written here")
        self.model = Transformer(model_config(z))
        # The harness's named hooks, all set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the reductions readers.py lacks.
        self.reference = _load_reference()
        self._chip_bench_grad = self._checked_once(jax.jit(jax.value_and_grad(
            self.reference.make_loss(z), has_aux=True)))
        readers.REDUCTIONS.update({
            "trace_sparse_attention_ms_per_step":
            sparse_attention_ms_per_step,
            "trace_sparse_attention_roofline_pct": _roofline_pct(
                sparse_attention_cost(z), self.per_chip_batch,
                sparse_attention_ms_per_step),
            "trace_indexer_ms_per_step": indexer_ms_per_step,
            "trace_indexer_roofline_pct": _roofline_pct(
                indexer_cost(z), self.per_chip_batch,
                indexer_kernels_ms_per_step)})

    def _checked_once(self, grad):
        """``grad``, the plain reference's, behind the configuration's own
        limits: its first call, which the harness makes on the seed's fresh
        weights and batch, first holds the program's logits and chosen sets
        to the reference's.  A gradient it returned is deleted at the next
        call (the harness has used it by then), or the reference's steps
        would not fit beside the harness's state (PERF.md section 7 (m))."""
        pending, last = [True], []

        def checked(params, aux, batch):
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
        by the file's three limits and its float32 model's chosen sets to the
        reference's by the fourth; a reading outside one ends the run."""
        z = self.sizes
        want, (_, exact), differ = self._float32_readings(params, batch)
        whole, median = (float(x) for x in self._distance(
            self._logits(None, ())(params, batch), want))
        held = ((whole, z["logits_rtol"], "the logits' norm"),
                (median, z["logits_median_rtol"],
                 "a position's logits' norm, the median position"),
                (float(exact), z["logits_float32_rtol"],
                 "a position's logits' norm, the median position, computed "
                 "in float32"),
                (float(jnp.max(differ)), z["chosen_sets_differ_share"],
                 "a layer's chosen pairs, computed in float32, that the "
                 "reference does not choose"))
        print(f"chip_bench {z['name']}: from the float32 reference's: "
              + "; ".join(f"{error:.3e} of {what} (limit {limit:.2e})"
                          for error, limit, what in held),
              file=sys.stderr, flush=True)
        for error, limit, what in held:
            if not error <= limit:
                raise SystemExit(
                    f"chip_bench {z['name']}: FAILED: the program lies "
                    f"{error:.3e} of {what} from the float32 reference, "
                    f"over the limit {limit:.2e}")
        # The reference's step needs the room these programs hold their
        # scratch in while loaded.
        del want
        self._logits.cache_clear()
        del self._float32_readings

    @functools.cached_property
    def _float32_readings(self):
        """The jitted ``(params, batch) -> (the float32 reference's logits,
        :meth:`_distance` of the program's float32 model's from them, a layer
        at a time the share of that model's chosen pairs that the reference
        does not choose)``, one pass of each: the sets have equal sizes, so
        as many differ the other way round; they are ties and last-bit
        neighbours of the threshold, and from the second layer on what those
        did to the stream."""
        from horovod_tpu.kernels.masked_attention_bwd import unpack_chosen

        exact = Transformer(dataclasses.replace(
            self.model.cfg, remat=False, dtype=jnp.float32))

        def readings(params, batch):
            tokens = batch["tokens"]
            want, sets = self.reference.logits_and_chosen_sets(
                params, batch, self.sizes)
            with jax.default_matmul_precision("highest"):
                got, state = exact.apply(
                    {"params": params}, tokens,
                    mutable=["moe", "indexer", "chosen"])
            shares = []
            for i in range(self.sizes["num_hidden_layers"]):
                words = state["chosen"][f"layer_{i}"]["attn"]["words"][0]
                chosen = unpack_chosen(words, tokens.shape[1])
                shares.append(jnp.sum(chosen & ~sets[i]) / jnp.sum(chosen))
            return want, self._distance(got, want), jnp.stack(shares)

        return jax.jit(readings)

    def chosen_sets_differ(self, params, batch):
        """:meth:`_float32_readings`' shares, a float a layer."""
        return [float(x) for x in self._float32_readings(params, batch)[2]]

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
        program's for ``dtype`` None or "program_float32", else the
        reference's in ``dtype``."""
        cfg = dataclasses.replace(self.model.cfg, remat=False)
        timed = Transformer(cfg)
        exact = Transformer(dataclasses.replace(cfg, dtype=jnp.float32))

        def program(params, batch):
            return timed.apply({"params": params}, batch["tokens"],
                               mutable=["moe", "indexer"])[0]

        def program_float32(params, batch):
            with jax.default_matmul_precision("highest"):
                return exact.apply({"params": params}, batch["tokens"],
                                   mutable=["moe", "indexer"])[0]

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
        aux carries the router's counters and the indexer's loss of the last
        step.  The model's initialisers are the model code's (normal(0.02)
        every matrix, the norms' scales 1) but for the embedding's width,
        set here (`assumed.init`)."""
        z = self.sizes
        v = self.model.init(key, jnp.zeros((1, 16), jnp.int32))
        params = nn.meta.unbox(v["params"])
        scale = z["embedding_init_std"] / 0.02
        params["embed"] = {"embedding": params["embed"]["embedding"] * scale}
        aux = moe_counters(z["num_hidden_layers"],
                           z["num_experts_published"], share=True)
        return params, {**aux, "indexer_loss": jnp.zeros((), jnp.float32)}

    def make_batch(self, key):
        z = self.sizes
        return {"tokens": jax.random.randint(
            key, (self.per_chip_batch, z["sequence_length"]), 0,
            z["vocab_size"])}

    def loss(self, params, aux, batch):
        from horovod_tpu.models.indexer import indexer_loss

        z = self.sizes
        tokens = batch["tokens"]
        b, s = tokens.shape
        logits, state = self.model.apply({"params": params}, tokens,
                                         mutable=["moe", "indexer"])
        stats = moe_stats(state["moe"])
        divergence = indexer_loss(state["indexer"])
        # Position i is held to token i + 1; the last position has no next
        # token and weighs nothing (a roll and a weight keep the shapes
        # whole, where a slice would leave 16,383 positions).
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1))
        total = jnp.sum(nll * (jnp.arange(s) < s - 1)) / (b * (s - 1)) \
            + z["load_balancing_loss_weight"] \
            * jnp.mean(stats.load_balancing_loss) \
            + z["indexer_loss_weight"] * divergence
        counters = {k: v for k, v in aux.items() if k != "indexer_loss"}
        return total, {
            **count_routing(counters,
                            jnp.sum(stats.tokens_per_expert, axis=1),
                            held=self.held),
            "indexer_loss": divergence.astype(jnp.float32)}

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
