"""How far the program, the reference in bf16 and wrong variants lie from
qwen3-next-80b-a3b's float32 reference, on the chip at the published widths,
beside the limits of ``correct``.

    python3 chip_bench/tools/qwen3_next_reference_check.py [--seeds a,b]
        [--variants v,..] [--losses] [--steps N] [--set key=value ...]
        [--rule-gradients]

A reading is the pair (the logits' difference as a share of their norm, the
median over the positions of each position's own share), over all positions
of one batch of fresh weights from a seed.  For every seed: the program as it
is timed, the reference in bf16 throughout (both held to ``logits_rtol`` and
``logits_median_rtol``) and the program's model in float32 (held to
``logits_float32_rtol`` by the median).  For the first seed each wrong
variant, held to the limit on the program's own model in float32: five wrong
layers of the float32 reference (``WRONG_LAYERS``: the delta term dropped,
q and k without their L2 norm, RoPE over all 256, the attention's gate left
out, the shared expert's gate left out) and one wrong *program*,
``w_without_decay``: the program's model in float32 with ``exp(gamma)`` left
off ``W`` in the chunked form (``kernels/gated_delta.py::_chunk`` patched
here, in this process alone), which no token-by-token reference can have.
``--losses`` also steps the float32 reference three times beside its bf16
form and a dropped update and holds their losses to the harness's 3e-4.
``--steps N`` steps the program through ``hvd.make_overlapped_train_step``
and prints the rows the held experts got a layer and step beside
``row_buffer``'s first chunk, and the gauges of the two mixers.
``--rule-gradients`` leaves the logits out and holds the rule's two kernels
alone, at the timed sizes (one sequence of ``sequence_length``, the
configuration's heads), to float32:
``o`` and the cotangents of ``q``, ``k``, ``v``, ``g`` and ``beta`` under one
random ``do``, each as a share of the norm of ``chunked()``'s in float32 and
of the token-by-token recurrence's (the reference's ``recurrent_rule``), held
to ``RULE_GRADIENT_RTOL``; beside them ``chunked()`` against the recurrence,
and the kernels with a fault planted in the *backward* alone
(``BACKWARD_FAULTS``), each of which has to pass the limit in some cotangent
under some draw.
The logits, and the harness's three losses, see the forward kernel and little
of the backward one: this is what holds ``hvd_gated_delta_bwd``.  The
operands are drawn twice, under both ceilings of ``A`` in ``RULE_DECAYS``: 16
(a fresh layer's: most heads forget within a few positions) and 0.1 (states
that outlive many chunks, so that what the chunks hand each other is read).
Exits non-zero if a variant is refused by no limit, a cotangent of the kernels
lies over its limit or a planted fault under it.  One process, one chip; no
result line comes from here.
"""

from __future__ import annotations

import argparse
import functools
import inspect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

WRONG_LAYERS = ("no_delta", "no_l2norm", "rope_everywhere",
                "no_attention_gate", "no_shared_gate")
WRONG_PROGRAMS = ("w_without_decay",)
# The kernels' o and cotangents from float32's, as a share of float32's norm:
# bf16 operands and bf16 products with fp32 sums read 2.5e-3 to 6.4e-3 at the
# timed sizes over three seeds (my chip runs, PR 50; PERF.md section 6 has
# every reading); the least a planted fault reads in its worst cotangent is
# 5.5e-2 under fresh decays and 0.27 under slow ones.
RULE_GRADIENT_RTOL = 2e-2
RULE_DECAYS = (16.0, 0.1)
BACKWARD_FAULTS = ("inverse_cotangent_dropped", "state_cotangent_dropped")
COTANGENTS = ("dq", "dk", "dv", "dg", "dbeta")


def _with_text(function, right, wrong):
    """``function`` of ``kernels/gated_delta.py`` from its own text with
    ``right`` replaced by ``wrong``."""
    from horovod_tpu.kernels import gated_delta

    text = inspect.getsource(function)
    if text.count(right) != 1:
        raise SystemExit(f"kernels/gated_delta.py::{function.__name__} no "
                         f"longer reads {right!r} once: restate the fault")
    scope = dict(vars(gated_delta))
    exec(text.replace(right, wrong), scope)  # noqa: S102 — the repo's own text
    return scope[function.__name__]


def chunk_without_decay_on_w():
    """``gated_delta._chunk`` with ``W = T (beta k)``: its own text, one
    factor taken out."""
    from horovod_tpu.kernels import gated_delta

    return _with_text(gated_delta._chunk, "cast(beta * kf * grown)",
                      "cast(beta * kf)")


def plant(fault):
    """Break ``kernels/gated_delta.py``'s backward, and only that, in this
    process; returns what undoes it.  ``inverse_cotangent_dropped``: the
    chunk's inverse passes no cotangent back to ``A`` (what ``k``, ``g`` and
    ``beta`` get through ``T`` is lost).  ``state_cotangent_dropped``: the
    backward kernel starts every chunk from a zero cotangent of the state,
    and not the last alone (what a chunk's writes are worth to the chunks
    behind it is lost)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import gated_delta as gd

    if fault == "inverse_cotangent_dropped":
        name = "unit_lower_inverse"
        wrong = jax.custom_vjp(gd._inverse_products, nondiff_argnums=(1,))
        wrong.defvjp(gd._inverse_fwd,
                     lambda passes, t, dt: (jnp.zeros_like(dt),))
    elif fault == "state_cotangent_dropped":
        name = "_bwd_kernel"
        wrong = _with_text(gd._bwd_kernel, "@pl.when(pl.program_id(2) == 0)",
                           "@pl.when(pl.program_id(2) >= 0)")
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    kept = getattr(gd, name)
    setattr(gd, name, wrong)
    jax.clear_caches()

    def undo():
        setattr(gd, name, kept)
        jax.clear_caches()

    return undo


def rule_gradients(seed, sizes, recurrent_rule, a_max):
    """The readings of ``--rule-gradients`` for one seed and one ceiling of
    ``A``: ``{what: {against: {"o" and each of COTANGENTS: share}}}``."""
    import jax
    import jax.numpy as jnp

    from chip_bench import reference
    from horovod_tpu.kernels import gated_delta as gd

    s = sizes["sequence_length"]
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    if not gd.takes(s, hk, hv, dk, dv):
        raise SystemExit("the kernels do not take these sizes: nothing of "
                         "them would be read")
    # Off the TPU a rehearsal of the same code, the kernels interpreted.
    kernels = functools.partial(gd.gated_delta,
                                interpret=jax.default_backend() != "tpu")
    keys = jax.random.split(reference.seed_key(seed), 7)
    q, k = (jax.random.normal(key, (1, s, hk, dk)) for key in keys[:2])
    q = (q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5) \
        .astype(jnp.bfloat16)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, s, hv, dv)).astype(jnp.bfloat16)
    # A layer's decays: A in U(0, a_max), softplus(1 + a) near 1.3.
    g = -jax.random.uniform(keys[3], (1, 1, hv), maxval=a_max) \
        * jax.nn.softplus(1 + 0.1 * jax.random.normal(keys[4], (1, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, s, hv)))
    do = jax.random.normal(keys[6], v.shape).astype(jnp.bfloat16)
    operands = (q, k, v, g, beta)

    def token_by_token(q, k, v, g, beta):
        q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
        return jax.vmap(recurrent_rule)(q, k, v, g, beta)

    def everything(rule, dtype):
        """(o, dq, dk, dv, dg, dbeta) of ``rule`` computed in ``dtype``."""
        @jax.jit
        def run(operands, do):
            o, back = jax.vjp(rule, *operands)
            return (o,) + back(do.astype(o.dtype))

        ins = tuple(t.astype(dtype) for t in operands)
        if dtype == jnp.float32:
            with jax.default_matmul_precision("highest"):
                return jax.block_until_ready(run(ins, do))
        return jax.block_until_ready(run(operands, do))

    def shares(got, want):
        return {name: float(
            jnp.linalg.norm((a.astype(jnp.float32) - b).ravel())
            / jnp.linalg.norm(b.ravel()))
            for name, a, b in zip(("o",) + COTANGENTS, got, want)}

    exact = {"chunked_float32": everything(gd.chunked, jnp.float32),
             "recurrent_float32": everything(token_by_token, jnp.float32)}
    told = {"chunked_float32": {"recurrent_float32": shares(
        exact["chunked_float32"], exact["recurrent_float32"])}}
    for what in ("kernels",) + BACKWARD_FAULTS:
        undo = plant(what) if what != "kernels" else lambda: None
        try:
            got = everything(kernels, jnp.bfloat16)
        finally:
            undo()
        told[what] = {against: shares(got, want)
                      for against, want in exact.items()}
        print("rule_gradients", seed, a_max, what, told[what],
              file=sys.stderr, flush=True)
    return told


def rule_gradients_hold(readings):
    """Whether, a seed, every reading of the kernels keeps to
    RULE_GRADIENT_RTOL and every planted fault passes it in some cotangent
    under some draw of the decays."""
    return all(
        all(share <= RULE_GRADIENT_RTOL for told in by_decay.values()
            for against in told["kernels"].values()
            for share in against.values())
        and all(max(told[fault]["chunked_float32"][c]
                    for told in by_decay.values() for c in COTANGENTS)
                > RULE_GRADIENT_RTOL for fault in BACKWARD_FAULTS)
        for by_decay in readings.values())


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", default=str(2 ** 31 + 50),
                   help="whole numbers, comma-separated")
    p.add_argument("--variants", default=None,
                   help="wrong variants (default: all)")
    p.add_argument("--losses", action="store_true")
    p.add_argument("--steps", type=int, default=0)
    p.add_argument("--rule-gradients", action="store_true")
    p.add_argument("--set", action="append", default=[],
                   help="key=value (JSON) over the configuration's sizes")
    p.add_argument("--workload", default="qwen3-next-80b-a3b-wfbp-1chip")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from chip_bench import reference, spec, worker
    from horovod_tpu.kernels import gated_delta
    from horovod_tpu.models.transformer import (
        publish_attention,
        publish_gated_delta,
    )
    from horovod_tpu.parallel.moe import row_buffer

    cell = spec.Cell(args.workload)
    sizes = dict(cell.sizes)
    for item in args.set:
        key, value = item.split("=", 1)
        sizes[key] = json.loads(value)
    module = cell.config_module()
    config = module.Config(sizes)
    ref = config.reference
    dev = jax.devices()[0]
    put = functools.partial(jax.device_put, device=dev)
    tx = config.optimizer(1)
    seeds = [int(x) for x in args.seeds.split(",")]
    everything = WRONG_LAYERS + WRONG_PROGRAMS
    variants = list(everything) if args.variants is None \
        else [v for v in args.variants.split(",") if v]
    if set(variants) - set(everything):
        p.error(f"unknown variants; have {everything}")
    make_batch, init = jax.jit(config.make_batch), jax.jit(config.init)
    own, median, exact = (sizes[k] for k in (
        "logits_rtol", "logits_median_rtol", "logits_float32_rtol"))

    program_steps = None
    if args.steps:
        # First, on a device that holds nothing else, as the worker has it.
        import horovod_tpu as hvd

        hvd.init()
        params, aux = init(put(reference.seed_key(seeds[0])))
        batch = make_batch(put(reference.rank_key(seeds[0], 0)))
        step = hvd.make_overlapped_train_step(config.loss, tx, has_aux=True)
        params, state, aux = step.init(params, jax.jit(tx.init)(params), aux)
        slots = sizes["per_chip_batch"] * sizes["sequence_length"] \
            * sizes["num_experts_per_tok"]
        chunks, cap = row_buffer(slots, len(sizes["experts_held"]),
                                 sizes["num_experts_published"])
        before = np.zeros(sizes["num_hidden_layers"], np.int64)
        rows, losses = [], []
        for _ in range(args.steps):
            params, state, aux, loss = step(params, state, batch, aux)
            now = step.fetch(aux)
            held = np.asarray(now["rows_held"], np.int64)
            rows.append((held - before).tolist())
            before = held
            losses.append(float(loss))
        counts = np.asarray(now["tokens_per_expert"], np.float64)
        cfg = config.model.cfg
        program_steps = {
            "first_chunk": cap, "chunks": chunks, "mean_share": slots
            * len(sizes["experts_held"]) / sizes["num_experts_published"],
            "rows_held_min": int(np.min(rows)),
            "rows_held_max": int(np.max(rows)),
            "rows_held_by_step": rows if args.steps <= 12
            else rows[:6] + rows[-6:],
            "max_load_ratio": [float(c.max() / c.mean()) for c in counts],
            "busiest_held_over_mean": [
                float(c[np.asarray(sizes["experts_held"])].max() / c.mean())
                for c in counts],
            "losses": losses[:4] + losses[-2:],
            "attn_allowed_pairs_per_step": publish_attention(
                cfg, sizes["sequence_length"], sizes["per_chip_batch"]),
            "gdn_chunks_per_step": publish_gated_delta(
                cfg, sizes["sequence_length"], sizes["per_chip_batch"])}
        print("program_steps", program_steps, file=sys.stderr, flush=True)
        hvd.shutdown()
        del params, state, aux, batch, step, now

    def wrong_program(name, params, data):
        """The reading of the program's float32 model with its chunk's
        algebra broken."""
        assert name == "w_without_decay"
        kept, gated_delta._chunk = gated_delta._chunk, \
            chunk_without_decay_on_w()
        try:
            fresh = type(config)._logits.__wrapped__(
                config, "program_float32", (name,))
            got = fresh(params, data)
        finally:
            gated_delta._chunk = kept
        want = config._logits(jnp.float32, ())(params, data)
        return tuple(float(x) for x in config._distance(got, want))

    rule_readings = {}
    if args.rule_gradients:
        # First, while the device holds nothing else.
        for seed in seeds:
            rule_readings[str(seed)] = {
                f"{a_max:g}": rule_gradients(seed, sizes, ref.recurrent_rule,
                                             a_max)
                for a_max in RULE_DECAYS}
        jax.clear_caches()

    logits = {}
    for seed in ([] if args.rule_gradients else seeds):
        params, _ = init(put(reference.seed_key(seed)))
        data = make_batch(put(reference.rank_key(seed, 0)))
        errors = functools.partial(config.logits_errors, params, data)
        logits[str(seed)] = {
            "program": errors(), "bfloat16": errors(jnp.bfloat16),
            "program_float32": errors("program_float32")}
        if seed == seeds[0]:
            for name in variants:
                logits[str(seed)][name] = errors(jnp.float32, (name,)) \
                    if name in WRONG_LAYERS \
                    else wrong_program(name, params, data)
        print(seed, logits[str(seed)], file=sys.stderr, flush=True)
        # `errors` names them too, and the reference's steps need the room.
        del params, data, errors
    told = {}
    if logits:
        first = logits[str(seeds[0])]
        told = {name: {"logits": first[name],
                       "refused": first[name][1] > exact}
                for name in variants}
        told["bfloat16"] = {
            "logits": first["bfloat16"], "refused":
            first["bfloat16"][0] > own or first["bfloat16"][1] > median}
    out = {"device": dev.device_kind, "seeds": seeds, "set": args.set,
           "logits_rtol": own, "logits_median_rtol": median,
           "logits_float32_rtol": exact, "logits": logits}

    if args.losses:
        rtol = worker.REFERENCE_RTOL
        type(config)._logits.cache_clear()

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update(params, opt_state, grads):
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        def three_losses(batch, skip=None, **variant):
            grad = jax.jit(jax.value_and_grad(
                ref.make_loss(sizes, **variant), has_aux=True))
            params, aux = init(put(reference.seed_key(seeds[0])))
            opt_state = jax.jit(tx.init)(params)
            losses = []
            for step in range(3):
                (loss, aux), g = grad(params, aux, batch)
                losses.append(float(loss))
                if step != skip:
                    params, opt_state = update(params, opt_state, g)
                # Or the next step's gradient is made beside this one.
                del g
            return losses

        batch = make_batch(put(reference.rank_key(seeds[0], 0)))
        want = out["float32_losses"] = three_losses(batch)
        for name, how in (("bfloat16", dict(dtype=jnp.bfloat16)),
                          ("dropped_update", dict(skip=1))):
            got = three_losses(batch, **how)
            rel = [abs(a - b) / abs(b) for a, b in zip(got, want)]
            entry = told.setdefault(name, {"refused": False})
            entry.update(losses=got, rel=rel,
                         over_reference_rtol=max(rel) > rtol)
            entry["refused"] = entry["refused"] or max(rel) > rtol
            print(name, entry, file=sys.stderr, flush=True)
    out["variants"] = told
    if rule_readings:
        out["rule_gradients"] = {"rtol": RULE_GRADIENT_RTOL,
                                 "readings": rule_readings,
                                 "hold": rule_gradients_hold(rule_readings)}
    if program_steps is not None:
        out["program_steps"] = program_steps
    print(json.dumps(out), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)
    return 0 if all(t["refused"] for t in told.values()) \
        and rule_gradients_hold(rule_readings) else 1


if __name__ == "__main__":
    sys.exit(main())
