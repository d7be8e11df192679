"""Xing4.0-29B-A4B at its published widths, cut to one chip's share of a layer
(latent attention, the hyper-connections and the shared expert whole, 8 of 64
experts, an eighth of the vocabulary) and to the first stage's five layers:
the model, its next-token loss, the selection bias and the Sinkhorn counter
it keeps from step to step, its data and optimizer from a seed, its FLOPs per
sample, the attention kernels' and the hyper-connections' cost from its
shapes and the reductions that read the recomputed forward and the ``hc.*``
scopes out of a trace.

The model is the program's (``horovod_tpu/models/transformer.py`` with
``hc_mult`` 4 over ``horovod_tpu/models/hyper_connections.py``, latent
attention of ``horovod_tpu/models/deepseek.py`` under YaRN,
``kernels/masked_attention.py`` at a key width of 192 over values of 128,
and ``horovod_tpu/parallel/moe.py``), **every block recomputed whole in the
backward pass** (``TransformerConfig.remat``: a layer's input is four streams
of 3584, 235 MB in bf16, and a layer's activations beside 12.15 GB of
weights, gradients and AdamW moments do not fit otherwise; the file's
``recomputed`` and ``fit``).  The plain reference is
``xing4.0-29b-a4b_reference.py`` beside this file, which imports nothing of
the program: ``chip_bench/reference.py`` takes its gradient from
``config._chip_bench_grad``, so ``matches_reference`` compares the step under
test with that float32 model and not with the program's own.

The losses of fresh weights lie near ln(vocabulary) in any precision, so the
harness's one limit on them (3e-4) sees a dropped update and little of a
wrong layer or of the rounding.  The configuration therefore brings limits of
its own, in its file, in ``joyai-llm-flash``'s form: before the reference's
first step, the program's logits at the timed sizes against the float32
reference's (``Config.logits_errors``): ``logits_rtol`` on the difference as
a share of the logits' norm, ``logits_median_rtol`` on the median over the
positions of each position's own share, and ``logits_float32_rtol`` and
``logits_float32_norm_rtol`` on the same model computed in float32 at the
highest precision, where nothing is rounded, so that a wrong layer fails.
The harness has no place for a configuration's own check (PERF.md section 7
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

from chip_bench import peaks, readers, trace_reduce
from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
from horovod_tpu.models.transformer import (
    LayerKind,
    Transformer,
    expert_bias_collection,
    moe_stats,
    xing4_0_29b_a4b_config,
)
from horovod_tpu.parallel.moe import count_routing, moe_counters

# What JAX calls the second forward of a block under ``jax.checkpoint`` in an
# operation's ``op_name`` (the profiler's ``tf_op``).
RECOMPUTED = "rematted_computation"
# The hyper-connections' scopes (``horovod_tpu/core/timeline.py::SCOPES``).
HC_SCOPES = ("hc.coeff", "hc.sinkhorn", "hc.pre", "hc.post")


def _load_reference():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "xing4.0-29b-a4b_reference.py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_xing_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def blocks(sizes):
    """(dense layers, sparse layers) held here: the held layers by their
    published index against the published ``first_k_dense_replace``."""
    if len(sizes["layers_held"]) != sizes["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers held here")
    dense = sum(p < sizes["first_k_dense_replace_published"]
                for p in sizes["layers_held"])
    if dense != sizes["first_k_dense_replace"]:
        raise ValueError("first_k_dense_replace counts the dense layers "
                         "held here")
    return dense, sizes["num_hidden_layers"] - dense


def allowed_pairs(sizes):
    """The (query, key) pairs the causal mask allows in one sequence."""
    s = sizes["sequence_length"]
    return s * (s + 1) // 2


def matmul_macs(sizes):
    """{name: multiply-adds per sample} of every matrix multiplication of
    **one** forward pass, from the shapes alone.  A sample is one sequence.
    Attention is counted over the causal pairs and not over the square, the
    scores at the key's width and the values at theirs; the experts at the
    rows this chip's share sees when the routing is even, k * held /
    published a position; the hyper-connections by their one product, the
    flattened streams with ``phi``, two a layer (the mixes are sums over
    four streams on the vector unit and no matrix product)."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    h, n = sizes["num_attention_heads"], sizes["hc_mult"]
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, width = sizes["v_head_dim"], sizes["moe_intermediate_size"]
    dense, sparse = blocks(sizes)
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
        "hc_phi": 2 * (dense + sparse) * s * n * d * n * (n + 2),
        "head": s * d * sizes["vocab_size"],
    }


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, a multiply-add
    counted as 2: 2 forward and 4 backward, **the model's work and not the
    chip's**: the second forward of every block, which this configuration
    recomputes (a third more than is counted here, all but the head's), and
    the hyper-connections' mixes, norms and Sinkhorn iterations on the vector
    unit are not counted.  Embedding lookup, norms, rotary positions, the
    gates, softmax, sigmoid, top-k, the sort, gathers and sums of dispatch
    and combine, and AdamW are not counted either."""
    return float(6 * sum(matmul_macs(sizes).values()))


def forwards(sizes):
    """How often a block's forward pass runs a step."""
    return 2 if sizes["recompute_blocks"] else 1


def mla_attention_cost(sizes):
    """(operations, HBM bytes) of the attention kernels of every layer held
    here on one sequence as the step runs them: **the forward kernel twice**
    (the blocks are recomputed, and the time the share is taken over holds
    both calls) and the backward kernel once.  Per allowed pair and head 320
    multiply-adds a forward call (the score at the key's 192, the value at
    128) and 640 backward (dv and dp at 128, dq and dk at 192); what the
    backward kernel computes again (the scores) and what a kernel pads (192
    to two lane groups) is not counted: the same work whatever kernel does
    it.  Bytes: q, k, v and the output once a forward call, those and the
    gradient of each once backward, in bf16."""
    h, s = sizes["num_attention_heads"], sizes["sequence_length"]
    dqk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    n = sum(blocks(sizes))
    operations = 2 * (forwards(sizes) + 2) * n * allowed_pairs(sizes) * h \
        * (dqk + dv)
    return operations, n * (forwards(sizes) + 2) * 2 * s * h \
        * (2 * dqk + 2 * dv)


def hyper_connection_cost(sizes):
    """(operations, HBM bytes) of the hyper-connections of every layer held
    here on one sequence as the step runs them: bytes as the algorithm needs
    them, whatever implements them, the forward twice where the blocks are
    recomputed.  ``X`` is the streams ``[s, n, C]`` and ``u`` one stream
    ``[s, C]``, both in bf16; the coefficients (24 fp32 numbers a token, 0.8
    MB a sublayer against X's 235) and ``phi`` (1.4 MB) are left out.

    A sublayer's forward, at the least: X read once for the flattened norm,
    the product with ``phi`` and the mix down (a token's coefficients need
    all of that token's streams and nothing of another token's, so one pass
    can hold a block of tokens and do all three), u written; behind the
    sublayer X and y read and X' written: 3 X + 2 u.  Its backward: in front
    of the sublayer's own backward pass dX', X and y read (dy, and the
    cotangents of H_post and H_res) and dy written: 2 X + 2 u; behind it du,
    X and dX' read (H_res^T dX' + H_pre du + what the coefficients'
    cotangents send back through ``phi`` and the norm) and dX written: 3 X +
    u.  The fold behind the last layer reads X and writes u, its backward
    the reverse, and the fan-out's backward reads dX and writes du: 3 X + 3
    u a step; the fan-out itself is counted as free (the first layer's input
    could be read from the embedding).  Operations: the product with
    ``phi`` (:func:`matmul_macs`), 2 a multiply-add a forward pass and 4
    backward; the mixes' multiply-adds run on the vector unit, which the
    peaks' table has no rate for, and are left out: the HBM peak binds."""
    s, d, n = sizes["sequence_length"], sizes["hidden_size"], sizes["hc_mult"]
    u = 2 * s * d
    x = n * u
    sublayers = 2 * sum(blocks(sizes))
    forward, backward = 3 * x + 2 * u, 5 * x + 3 * u
    moved = sublayers * (forwards(sizes) * forward + backward) \
        + 3 * x + 3 * u
    operations = (2 * forwards(sizes) + 4) * matmul_macs(sizes)["hc_phi"]
    return operations, moved


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
        return 100.0 * calls * _least_s(operations, bytes_moved) / measured

    return reduction


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


def _op_seconds(ctx, wanted):
    """(seconds of the traced stretch in the operations ``wanted(op)``
    accepts, the stretch), or None where no trace was kept."""
    from chip_bench import scopes

    w, path = ctx["window"], _xplane_of(ctx)
    if w is None or not w.ops or not path:
        return None
    return sum(min(op.end, w.hi) - max(op.start, w.lo)
               for op in scopes.device_ops(path)
               if min(op.end, w.hi) > max(op.start, w.lo) and wanted(op)), w


def recompute_ms_per_step(p, ctx):
    """The reduction behind ``recompute_ms_step`` (granite-4.0-h-micro's, in
    this module's own copy: a configuration registers what it reads): device
    milliseconds a step in operations of the blocks' second forward, those
    whose ``op_name`` (their own, or the one ``chip_bench/scopes.py`` adopts
    for an instruction of XLA's) lies under ``rematted_computation``."""
    found = _op_seconds(ctx, lambda op: RECOMPUTED in op.tf_op
                        or RECOMPUTED in op.adopted)
    if found is None or not found[0]:
        return None
    return 1e3 * found[0] / found[1].steps


def _scope_ms_per_step(names):
    """A reduction: device milliseconds a step in the operations whose row
    (``chip_bench/scopes.py::row_of``: the innermost ``hvd.`` scope of their
    own ``op_name`` or of the one adopted for them) is one of ``names``, in
    both directions and in the recomputed forward.  None where no trace was
    kept or the program wrote no such scope (a parent of PR 58)."""
    def reduction(p, ctx):
        from chip_bench import scopes

        found = _op_seconds(ctx, lambda op: scopes.row_of(op)[0] in names)
        if found is None or not found[0]:
            return None
        return 1e3 * found[0] / found[1].steps

    return reduction


def _hyper_connection_roofline_pct(sizes):
    """The reduction behind ``hyper_connection_roofline_pct``: the least time
    the chip could take for one step's hyper-connections (the larger of
    operations over the bf16 peak and bytes over the HBM peak: the bytes)
    over the time the operations under the four ``hc.*`` scopes took."""
    operations, bytes_moved = hyper_connection_cost(sizes)
    calls = sizes["per_chip_batch"]
    measured_ms = _scope_ms_per_step(HC_SCOPES)

    def reduction(p, ctx):
        ms = measured_ms(p, ctx)
        if not ms:
            return None
        return 100.0 * calls * _least_s(operations, bytes_moved) * 1e3 / ms

    return reduction


def model_config(sizes, **overrides):
    """The program's configuration of the share ``sizes`` describes."""
    blocks(sizes)                       # the counts agree with layers_held
    pattern = tuple(LayerKind(
        ffn="dense" if p < sizes["first_k_dense_replace_published"]
        else None) for p in sizes["layers_held"])
    yarn = sizes["rope_scaling"]
    return xing4_0_29b_a4b_config(**{**dict(
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
        v_head_dim=sizes["v_head_dim"], hc_mult=sizes["hc_mult"],
        hc_sinkhorn_iters=sizes["hc_sinkhorn_iters"],
        hc_eps=sizes["hc_eps"],
        hc_res_clamp=float(sizes["mhc_h_res_clamp_max"]),
        yarn_factor=float(yarn["factor"]),
        yarn_original_max_len=yarn["original_max_position_embeddings"],
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_mscale=float(yarn["mscale"]),
        yarn_mscale_all_dim=float(yarn["mscale_all_dim"]),
        layer_pattern=pattern, remat=bool(sizes["recompute_blocks"]),
        dtype=jnp.bfloat16, moe_data_axis=PROCESS_AXIS), **overrides})


class Config:
    def __init__(self, sizes):
        self.sizes = z = sizes
        self.per_chip_batch = z["per_chip_batch"]
        self.first_loss = math.log(z["vocab_size"])
        self.held = tuple(z["experts_held"])
        if len(self.held) != z["n_routed_experts"]:
            raise ValueError("n_routed_experts counts the experts held here")
        if z["tie_word_embeddings"] or z["n_shared_experts"] != 1 \
                or z["hidden_act"] != "silu" or z["attention_bias"] \
                or (z["rope_scaling"] or {}).get("type") != "yarn" \
                or z["scoring_func"] != "sigmoid" \
                or z["topk_method"] != "noaux_tc" \
                or z["n_group"] != 1 or z["topk_group"] != 1 \
                or z["moe_layer_freq"] != 1 \
                or z["num_nextn_predict_layers"] \
                or z["mhc_h_res_clamp_min"] != -z["mhc_h_res_clamp_max"] \
                or z["num_key_value_heads"] != z["num_attention_heads"]:
            raise ValueError("an untied head, one shared expert, silu, no "
                             "bias, YaRN, sigmoid scores with the bias and "
                             "no group limit, experts in every layer behind "
                             "the dense ones, no prediction module, a "
                             "symmetric clamp and one key head a query head "
                             "are the only forms written here")
        self.model = Transformer(model_config(z))
        self.expert_layers = len(self.model.cfg.expert_layers())
        # The harness's named hooks, all set from here because it has no
        # others (PERF.md section 7): the plain model that `matches_reference`
        # steps, and the reductions readers.py lacks.
        self.reference = _load_reference()
        self._chip_bench_grad = self._checked_once(jax.jit(jax.value_and_grad(
            self.reference.make_loss(z), has_aux=True)))
        readers.REDUCTIONS.update({
            "trace_mla_attention_roofline_pct": _attention_roofline_pct(z),
            "trace_recompute_ms_per_step": recompute_ms_per_step,
            "trace_hyper_connection_ms_per_step":
            _scope_ms_per_step(HC_SCOPES),
            "trace_sinkhorn_ms_per_step": _scope_ms_per_step(("hc.sinkhorn",)),
            "trace_hyper_connection_roofline_pct":
            _hyper_connection_roofline_pct(z)})

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
            # when it asks for the next (3.0 GB beside 9.1 of parameters and
            # AdamW state and 3.0 of new gradients).  Its update has
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
        """(logits, the ``moe`` and ``hc`` collections) of the model routed
        by ``bias``."""
        return (model or self.model).apply(
            {"params": params,
             "moe": expert_bias_collection(self.model.cfg, bias)},
            tokens, mutable=["moe", "hc"])

    def init(self, key):
        """(params, aux) from a key; meant to run under one ``jax.jit``.
        aux carries the router's counters, the selection bias, zero, and the
        Sinkhorn counter.  The model's initialisers are the model code's
        (normal(0.02) every matrix and ``phi``, the norms' scales 1, the
        hyper-connections' ``alpha`` and biases as
        ``models/hyper_connections.py`` has them) but for the embedding's
        width, set here (`assumed.init`)."""
        z = self.sizes
        v = self.model.init(key, jnp.zeros((1, 16), jnp.int32))
        params = nn.meta.unbox(v["params"])
        scale = z["embedding_init_std"] / 0.02
        params["embed"] = {"embedding": params["embed"]["embedding"] * scale}
        aux = moe_counters(self.expert_layers, z["n_routed_experts_published"],
                           share=True, expert_bias=True)
        aux["hc_deviation"] = jnp.zeros((), jnp.float32)
        return params, aux

    def make_batch(self, key):
        z = self.sizes
        return {"tokens": jax.random.randint(
            key, (self.per_chip_batch, z["sequence_length"]), 0,
            z["vocab_size"])}

    def loss(self, params, aux, batch):
        from horovod_tpu.models.hyper_connections import max_deviation

        tokens = batch["tokens"]
        b, s = tokens.shape
        z = self.sizes
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
        new = count_routing(
            {k: v for k, v in aux.items() if k != "hc_deviation"},
            jnp.sum(stats.tokens_per_expert, axis=1), held=self.held,
            bias_update_rate=z["expert_bias_update_rate"])
        # What the Sinkhorn iterations left of this step's H_res: the largest
        # distance of a row's or a column's sum from 1, over every sublayer
        # and token.
        new["hc_deviation"] = max_deviation(state["hc"])
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
