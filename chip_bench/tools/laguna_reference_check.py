"""How far the program, the reference in bf16 and planted faults of the
reference lie from laguna-s-2.1's float32 reference, on the chip at the
published widths, beside the limits of ``correct``.

    python3 chip_bench/tools/laguna_reference_check.py [--seeds a,b]
        [--variants v,..] [--losses] [--steps N] [--set key=value ...]

A reading is the pair (the logits' difference as a share of their norm, the
median over the positions of each position's own share), over all positions
of one batch of weights from a seed.  For every seed, on **fresh** weights
(what a run of the cell holds): the program as it is timed, the reference in
bf16 throughout (both held to ``logits_rtol`` and ``logits_median_rtol``) and
the program's model in float32 (held to ``logits_float32_rtol`` by the
median).  For the first seed also each planted fault of the float32 reference
(``FAULTS``: the full layers' table in a sliding layer and the reverse,
``attention_factor`` left out, the whole head turned in a full layer, a
window of 1024, the gate left out, the gate a channel, the routed weights
without their 2.5, sigmoid scores), held to the limit on the program's own
model in float32, twice: on the fresh weights, and on **seeded** weights
(``seeded``: every attention projection and router twice as large, so that
the scores are of order 5, a softmax row leans on few keys and positions
matter more), where the program's model in float32 is held to the same limit
and every fault has to be refused.  ``--losses`` also steps the float32
reference three times beside its bf16 form and a dropped update and holds
their losses to the harness's 3e-4.  ``--steps N`` steps the program through
``hvd.make_overlapped_train_step`` and prints the rows the held experts got a
layer and step beside ``row_buffer``'s first chunk.  Exits non-zero if a
fault is refused on neither kind of weights, or the program's float32 model
is refused.  One process, one chip; no result line comes from here.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = ("yarn_in_sliding", "plain_in_full", "no_attention_factor",
          "whole_head_turned", "window_1024", "no_gate", "gate_a_channel",
          "no_routed_scale", "sigmoid_scores")


def seeded(params, by=2.0):
    """``params`` with the q, kv and gate projections and the routers ``by``
    times as large."""
    import jax

    def moved(path, x):
        under = {getattr(k, "key", None) for k in path}
        grown = under & {"q", "kv", "gate", "router"} and "out" not in under
        return x * by if grown else x

    return jax.tree_util.tree_map_with_path(moved, params)

def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", default=str(2 ** 31 + 41),
                   help="whole numbers, comma-separated")
    p.add_argument("--variants", default=None, help="faults (default: all)")
    p.add_argument("--losses", action="store_true")
    p.add_argument("--steps", type=int, default=0)
    p.add_argument("--set", action="append", default=[],
                   help="key=value (JSON) over the configuration's sizes")
    p.add_argument("--workload", default="laguna-s-2.1-wfbp-1chip")
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
    variants = list(FAULTS) if args.variants is None \
        else [v for v in args.variants.split(",") if v]
    if set(variants) - set(FAULTS):
        p.error(f"unknown variants; have {FAULTS}")
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
        program_steps = {
            "first_chunk": cap, "chunks": chunks, "mean_share": slots
            * len(sizes["experts_held"]) / sizes["num_experts_published"],
            "rows_held_min": int(np.min(rows)),
            "rows_held_max": int(np.max(rows)),
            "rows_held_by_step": rows if args.steps <= 12
            else rows[:6] + rows[-6:],
            "max_load_ratio": [float(c.max() / c.mean()) for c in counts],
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
    sound = not refused(first["program_float32"]) \
        and not refused(first["program_float32_fresh"])
    out = {"device": dev.device_kind, "seeds": seeds, "set": args.set,
           "logits_rtol": own, "logits_median_rtol": median,
           "logits_float32_rtol": exact, "logits": logits,
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
    print(json.dumps(out), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)
    return 0 if sound and all(t["refused"] for t in told.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
