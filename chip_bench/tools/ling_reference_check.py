"""How far the program, the reference in bf16 and planted faults of the
reference lie from ling-3.0-flash-vl's float32 reference, on the chip at the
published widths, beside the limits of ``correct``; and how far the rule's two
kernels lie from float32, cotangent by cotangent.

    python3 chip_bench/tools/ling_reference_check.py [--seeds a,b]
        [--variants v,..] [--losses] [--steps N] [--rule-gradients]
        [--set key=value ...]

A reading is the pair (the logits' difference as a share of their norm, the
median over the positions of each position's own share), over all positions
of one batch of weights from a seed.  For every seed, on **fresh** weights
(what a run of the cell holds): the program as it is timed, the reference in
bf16 throughout (both held to ``logits_rtol`` and ``logits_median_rtol``) and
the program's model in float32 (held to ``logits_float32_rtol`` by the median
and to ``logits_float32_norm_rtol`` by the norm).  For the first seed also
each planted fault of the float32 reference (``FAULTS``: the decay taken as a
scalar a head, the gate without its bound, the group mask left out, the head
gate left out, q and k without their L2 norm), held to the limit on the
program's own model in float32 by the median, twice: on the fresh weights,
and on **seeded** weights (``seeded``: the mixers' projections and the
routers twice as large and the decay gate's ``dt_bias`` raised by 3, so that
the decays leave zero, the gates their middle and the groups differ), where
the program's model in float32 is held to the same limits and every fault
has to be refused.  ``--losses`` also steps the float32 reference three times
beside its bf16 form and a dropped update and holds their losses to the
harness's 3e-4.  ``--steps N`` steps the program through
``hvd.make_overlapped_train_step`` and prints the rows the held experts got a
layer and step beside ``row_buffer``'s first chunk, the busiest expert over
the mean a layer, and the mixers' gauges.  ``--rule-gradients`` leaves the
logits out and holds ``kernels/kda.py``'s two kernels alone at the timed
sizes to float32: ``o`` and all five cotangents against ``chunked()`` in
float32 and the token-by-token recurrence, under the mixer's fresh decays
(most heads hardly decay) and under fast ones (every channel near the middle
of the bound), beside two faults planted in the backward (the inverse's
cotangent dropped; the state's cotangent not handed from chunk to chunk), and
exits non-zero if a cotangent passes its limit (``RULE_GRADIENT_RTOL``;
``RULE_DECAY_GRADIENT_RTOL`` for ``dg``, a sum of terms that cancel) or a
fault does not; on the CPU (``--set sequence_length=256 --set
num_attention_heads=2 --set num_key_value_heads=2``) the same code in
interpret mode.  Exits non-zero if
a fault is refused on neither kind of weights, or the program's float32 model
is refused.  One process, one chip; no result line comes from here.
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

FAULTS = ("scalar_decay", "gate_unbounded", "no_group_mask", "no_head_gate",
          "no_l2norm")
# The kernels' o and cotangents from float32's, as a share of float32's norm:
# bf16 operands and bf16 products with fp32 sums read 3.5e-3 to 4.4e-3 at the
# timed sizes (my chip runs, PR 66; PERF.md section 6 has every reading);
# ``dg`` alone is a sum over a chunk of terms of both signs that cancel the
# more the faster the channels decay: 1.2e-2 under moderate decays and 4.9e-2
# where every channel decays by e^-2.5 a position.
RULE_GRADIENT_RTOL = 2e-2
RULE_DECAY_GRADIENT_RTOL = 1e-1
RULE_DECAYS = ("fresh", "fast")
BACKWARD_FAULTS = ("inverse_cotangent_dropped", "state_cotangent_dropped")
COTANGENTS = ("dq", "dk", "dv", "dg", "dbeta")


def seeded(params, by=2.0, raised=3.0):
    """``params`` with the mixers' input projections, the gates and the
    routers ``by`` times as large and ``dt_bias`` ``raised``."""
    import jax

    def moved(path, x):
        under = {getattr(k, "key", None) for k in path}
        if "dt_bias" in under:
            return x + raised
        grown = under & {"in_proj", "beta_proj", "q", "kv_a", "kv_b", "gate",
                         "router"}
        return x * by if grown else x

    return jax.tree_util.tree_map_with_path(moved, params)


def plant(fault):
    """Break ``kernels/kda.py``'s backward, and only that, in this process;
    returns what undoes it.  ``inverse_cotangent_dropped``: the chunk's
    inverse passes no cotangent back to ``A``.  ``state_cotangent_dropped``:
    the backward kernel starts every chunk from a zero cotangent of the
    state, and not the last alone."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import gated_delta as gd
    from horovod_tpu.kernels import kda

    if fault == "inverse_cotangent_dropped":
        name = "unit_lower_inverse"
        wrong = jax.custom_vjp(gd._inverse_products, nondiff_argnums=(1,))
        wrong.defvjp(gd._inverse_fwd,
                     lambda passes, t, dt: (jnp.zeros_like(dt),))
    elif fault == "state_cotangent_dropped":
        name = "_bwd_kernel"
        text = inspect.getsource(kda._bwd_kernel)
        right = "@pl.when(pl.program_id(2) == 0)"
        if text.count(right) != 1:
            raise SystemExit("kernels/kda.py::_bwd_kernel no longer reads "
                             f"{right!r} once: restate the fault")
        scope = dict(vars(kda))
        exec(text.replace(right, "@pl.when(pl.program_id(2) >= 0)"),  # noqa: S102
             scope)
        wrong = scope[name]
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    kept = getattr(kda, name)
    setattr(kda, name, wrong)
    jax.clear_caches()

    def undo():
        setattr(kda, name, kept)
        jax.clear_caches()

    return undo


def rule_gradients(seed, sizes, recurrent_rule, decays):
    """The readings of ``--rule-gradients`` for one seed and one draw of the
    decays: ``{what: {against: {"o" and each of COTANGENTS: share}}}``."""
    import jax
    import jax.numpy as jnp

    from chip_bench import reference
    from horovod_tpu.kernels import kda
    from horovod_tpu.models import kda as mixer

    s, h = sizes["sequence_length"], sizes["num_attention_heads"]
    d = sizes["head_dim"]
    if not kda.takes(s, h, d, d):
        raise SystemExit("the kernels do not take these sizes: nothing of "
                         "them would be read")
    # Off the TPU a rehearsal of the same code, the kernels interpreted.
    kernels = functools.partial(kda.kda,
                                interpret=jax.default_backend() != "tpu")
    keys = jax.random.split(reference.seed_key(seed), 8)
    q, k = (jax.random.normal(key, (1, s, h, d)) for key in keys[:2])
    q = (q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5) \
        .astype(jnp.bfloat16)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, s, h, d)).astype(jnp.bfloat16)
    f = jax.random.normal(keys[3], (1, s, h, d))
    if decays == "fresh":
        # A fresh layer's: the gate's own initialisers under a unit input.
        rate = jnp.exp(mixer._a_log_init(keys[6], (h,)))[:, None]
        g = sizes["kda_lower_bound"] * jax.nn.sigmoid(
            rate * (f + mixer._dt_bias_init(keys[7], (h, d))))
    else:
        g = sizes["kda_lower_bound"] * jax.nn.sigmoid(f)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, s, h)))
    do = jax.random.normal(keys[5], v.shape).astype(jnp.bfloat16)
    operands = (q, k, v, g, beta)

    def token_by_token(q, k, v, g, beta):
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

    exact = {"chunked_float32": everything(kda.chunked, jnp.float32),
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
        print("rule_gradients", seed, decays, what, told[what],
              file=sys.stderr, flush=True)
    return told


def _limit(cotangent):
    return RULE_DECAY_GRADIENT_RTOL if cotangent == "dg" \
        else RULE_GRADIENT_RTOL


def rule_gradients_hold(readings):
    """Whether, a seed, every reading of the kernels keeps to its limit and
    every planted fault passes it in some cotangent under some draw of the
    decays."""
    return all(
        all(share <= _limit(name) for told in by_decay.values()
            for against in told["kernels"].values()
            for name, share in against.items())
        and all(any(told[fault]["chunked_float32"][c] > _limit(c)
                    for told in by_decay.values() for c in COTANGENTS)
                for fault in BACKWARD_FAULTS)
        for by_decay in readings.values())


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", default=str(2 ** 31 + 66),
                   help="whole numbers, comma-separated")
    p.add_argument("--variants", default=None, help="faults (default: all)")
    p.add_argument("--losses", action="store_true")
    p.add_argument("--steps", type=int, default=0)
    p.add_argument("--rule-gradients", action="store_true")
    p.add_argument("--set", action="append", default=[],
                   help="key=value (JSON) over the configuration's sizes")
    p.add_argument("--workload", default="ling-3.0-flash-vl-wfbp-1chip")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from chip_bench import reference, spec, worker
    from horovod_tpu.parallel.moe import row_buffer

    cell = spec.Cell(args.workload)
    sizes = dict(cell.sizes)
    for item in args.set:
        key, value = item.split("=", 1)
        sizes[key] = json.loads(value)
    config = cell.config_module().Config(sizes)
    ref = config.reference
    dev = jax.devices()[0]
    put = functools.partial(jax.device_put, device=dev)
    tx = config.optimizer(1)
    seeds = [int(x) for x in args.seeds.split(",")]

    def finish(out, ok):
        print(json.dumps(out), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f)
        return 0 if ok else 1

    if args.rule_gradients:
        readings = {str(seed): {decays: rule_gradients(
            seed, sizes, ref.recurrent_rule, decays) for decays in RULE_DECAYS}
            for seed in seeds}
        ok = rule_gradients_hold(readings)
        return finish({"device": dev.device_kind, "seeds": seeds,
                       "set": args.set, "rule_gradients": {
                           "rtol": RULE_GRADIENT_RTOL,
                           "dg_rtol": RULE_DECAY_GRADIENT_RTOL,
                           "readings": readings, "hold": ok}}, ok)

    variants = list(FAULTS) if args.variants is None \
        else [v for v in args.variants.split(",") if v]
    if set(variants) - set(FAULTS):
        p.error(f"unknown variants; have {FAULTS}")
    make_batch, init = jax.jit(config.make_batch), jax.jit(config.init)
    own, median, exact, exact_norm = (sizes[k] for k in (
        "logits_rtol", "logits_median_rtol", "logits_float32_rtol",
        "logits_float32_norm_rtol"))

    program_steps = None
    if args.steps:
        # First, on a device that holds nothing else, as the worker has it.
        import horovod_tpu as hvd
        from horovod_tpu.models.transformer import publish_kda

        hvd.init()
        params, aux = init(put(reference.seed_key(seeds[0])))
        batch = make_batch(put(reference.rank_key(seeds[0], 0)))
        step = hvd.make_overlapped_train_step(config.loss, tx, has_aux=True)
        params, state, aux = step.init(params, jax.jit(tx.init)(params), aux)
        slots = sizes["per_chip_batch"] * sizes["sequence_length"] \
            * sizes["num_experts_per_tok"]
        chunks, cap = row_buffer(slots, len(sizes["experts_held"]),
                                 sizes["num_experts_published"])
        before = np.zeros(config.expert_layers, np.int64)
        rows, losses = [], []
        for _ in range(args.steps):
            params, state, aux, loss = step(params, state, batch, aux)
            now = step.fetch(aux)
            held = np.asarray(now["rows_held"], np.int64)
            rows.append((held - before).tolist())
            before = held
            losses.append(float(loss))
        counts = np.asarray(now["tokens_per_expert"], np.float64)
        here = counts[:, np.asarray(sizes["experts_held"])]
        program_steps = {
            "first_chunk": cap, "chunks": chunks, "mean_share": slots
            * len(sizes["experts_held"]) / sizes["num_experts_published"],
            "rows_held_min": int(np.min(rows)),
            "rows_held_max": int(np.max(rows)),
            "rows_held_by_step": rows if args.steps <= 12
            else rows[:6] + rows[-6:],
            "max_load_ratio": [float(c.max() / c.mean()) for c in counts],
            "busiest_held_over_mean": [
                float(c.max() / counts.mean(axis=1)[i])
                for i, c in enumerate(here)],
            "expert_bias_abs_max": [float(x) for x in np.abs(np.asarray(
                now["expert_bias"])).max(axis=1)],
            "kda_chunks_per_step": publish_kda(
                config.model.cfg, sizes["sequence_length"],
                sizes["per_chip_batch"]),
            "losses": losses[:4] + losses[-2:]}
        print("program_steps", program_steps, file=sys.stderr, flush=True)
        hvd.shutdown()
        del params, state, aux, batch, step, now

    logits = {}
    for seed in seeds:
        params, _ = init(put(reference.seed_key(seed)))
        data = make_batch(put(reference.rank_key(seed, 0)))
        errors = functools.partial(config.logits_errors, params, data)
        logits[str(seed)] = {
            "program_fresh": errors(), "bfloat16_fresh": errors(jnp.bfloat16),
            "program_float32_fresh": errors("program_float32")}
        if seed == seeds[0]:
            for name in variants:
                logits[str(seed)][name + "_fresh"] = errors(jnp.float32,
                                                            (name,))
            moved = jax.jit(seeded)(params)
            errors = functools.partial(config.logits_errors, moved, data)
            logits[str(seed)].update(
                program=errors(), bfloat16=errors(jnp.bfloat16),
                program_float32=errors("program_float32"))
            for name in variants:
                logits[str(seed)][name] = errors(jnp.float32, (name,))
            del moved
        print(seed, logits[str(seed)], file=sys.stderr, flush=True)
        # `errors` names them too, and the reference's steps need the room.
        del params, data, errors
    first = logits[str(seeds[0])]

    def refused(reading):
        return reading[1] > exact

    told = {name: {"logits": first[name], "fresh": first[name + "_fresh"],
                   "refused_fresh": refused(first[name + "_fresh"]),
                   "refused": refused(first[name])
                   or refused(first[name + "_fresh"])}
            for name in variants}
    told["bfloat16"] = {
        "logits": first["bfloat16_fresh"],
        "refused": first["bfloat16_fresh"][0] > own
        or first["bfloat16_fresh"][1] > median}
    sound = all(not refused(first[which]) and first[which][0] <= exact_norm
                for which in ("program_float32", "program_float32_fresh"))
    out = {"device": dev.device_kind, "seeds": seeds, "set": args.set,
           "logits_rtol": own, "logits_median_rtol": median,
           "logits_float32_rtol": exact,
           "logits_float32_norm_rtol": exact_norm, "logits": logits,
           "program_float32_inside_its_limits": sound}

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
    if program_steps is not None:
        out["program_steps"] = program_steps
    return finish(out, sound and all(t["refused"] for t in told.values()))


if __name__ == "__main__":
    sys.exit(main())
