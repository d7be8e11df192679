"""What ``correct`` tells apart in ``keye-vl-2.0-30b-a3b-wfbp-1chip``, by the
harness's limit and the configuration's four: the plain float32 reference at
the timed sizes on the device that is attached, and beside it the same
reference with one thing wrong.

    python3 chip_bench/tools/keye_reference_check.py [--seeds N,N]
        [--variants NAME,NAME] [--losses] [--steps N] [--set key=value]
        [--out FILE]

For every seed of ``--seeds``, on the seed's fresh weights and batch
(``Config.logits_errors``: the difference as a share of the logits' norm, and
the median position's share): the program as timed and the reference in
``bfloat16`` throughout (the nearest precision below the configuration's:
parameters, norms, rotary tables, indexer, router, softmax), both held to
``logits_rtol`` and ``logits_median_rtol``; the program's own model computed
in float32, held to ``logits_float32_rtol``; and a layer at a time the share
of that model's chosen pairs that the reference does not choose, held to
``chosen_sets_differ_share``.  For the first seed, each planted fault of the
float32 reference (``--variants``, by default all: ``half_the_keys`` 1024
keys chosen for 2048, ``no_relu``, ``no_weights`` the heads summed evenly,
``dense`` the choice ignored, ``key_unturned`` the indexer's key without its
rotary turn), which ``logits_float32_rtol`` has to refuse.  Exits non-zero
if the program lies outside a limit, if the bf16 reference lies inside both
of its two, or if a fault is refused by none.

``--losses``: the float32 reference stepped three times beside its bf16 form
and a dropped update, each relative to it, against the harness's limit
(``worker.py``'s ``REFERENCE_RTOL``).  ``--steps N``: the program stepped N
times through ``hvd.make_overlapped_train_step``: the indexer's loss a step
(it has to fall: the indexer is the one part that starts untrained against a
target that hardly moves), and the rows the held experts got a layer, the
busiest against the mean.  ``--set key=value`` overrides a size of the
configuration's file (JSON values; ``--set adamw_learning_rate=2e-5``).

Prints one JSON line; ``PERF.md`` records the readings.  The attention
kernels' cotangents under a chosen set against float32 at the timed sizes,
beside a planted fault, are ``benchmarks/sparse_attention_sweep.py``'s.  A
tool, run once per builder session; nothing of the benchmark's result line
comes from here.
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

WRONG = ("half_the_keys", "no_relu", "no_weights", "dense", "key_unturned")


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", default=str(2 ** 31 + 31),
                   help="whole numbers, comma-separated")
    p.add_argument("--variants", default=",".join(WRONG))
    p.add_argument("--losses", action="store_true")
    p.add_argument("--steps", type=int, default=0)
    p.add_argument("--set", action="append", default=[], metavar="key=value")
    p.add_argument("--workload", default="keye-vl-2.0-30b-a3b-wfbp-1chip")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from chip_bench import reference, spec, worker

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
    variants = [v for v in args.variants.split(",") if v]
    make_batch, init = jax.jit(config.make_batch), jax.jit(config.init)
    limits = {k: sizes[k] for k in (
        "logits_rtol", "logits_median_rtol", "logits_float32_rtol",
        "chosen_sets_differ_share")}
    out = {"device": dev.device_kind, "seeds": seeds, "limits": limits,
           "set": args.set, "readings": {}}
    ok = True

    for seed in seeds:
        params, _ = init(put(reference.seed_key(seed)))
        data = make_batch(put(reference.rank_key(seed, 0)))
        program = config.logits_errors(params, data)
        exact = config.logits_errors(params, data, "program_float32")
        rounded = config.logits_errors(params, data, jnp.bfloat16)
        differ = config.chosen_sets_differ(params, data)
        out["readings"][str(seed)] = {
            "program": program, "program_float32": exact,
            "bfloat16": rounded, "chosen_sets_differ": differ}
        ok = ok and program[0] <= limits["logits_rtol"] \
            and program[1] <= limits["logits_median_rtol"] \
            and exact[1] <= limits["logits_float32_rtol"] \
            and max(differ) <= limits["chosen_sets_differ_share"] \
            and (rounded[0] > limits["logits_rtol"]
                 or rounded[1] > limits["logits_median_rtol"])
        if seed == seeds[0]:
            out["faults"] = {}
            for fault in variants:
                reading = config.logits_errors(params, data, jnp.float32,
                                               (fault,))
                out["faults"][fault] = reading
                ok = ok and reading[1] > limits["logits_float32_rtol"]
        config._logits.cache_clear()
        del params, data

    batch = make_batch(put(reference.rank_key(seeds[0], 0)))
    if args.losses:
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update(params, opt_state, grads):
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        def three_losses(skip=None, **variant):
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
                del g
            return losses

        want = three_losses()
        stepped = {"bfloat16": three_losses(dtype=jnp.bfloat16),
                   "dropped_update": three_losses(skip=1)}
        out["losses"] = {
            "reference_rtol": worker.REFERENCE_RTOL, "float32": want,
            "variants": {
                name: {"losses": got,
                       "rel": [abs(a - b) / abs(b)
                               for a, b in zip(got, want)],
                       "told_apart": any(
                           abs(a - b) > worker.REFERENCE_RTOL * abs(b)
                           for a, b in zip(got, want))}
                for name, got in stepped.items()}}
        ok = ok and stepped["dropped_update"] != want \
            and out["losses"]["variants"]["dropped_update"]["told_apart"]

    if args.steps:
        import numpy as np

        import horovod_tpu as hvd
        from horovod_tpu.models.transformer import publish_indexer

        hvd.init()
        out["gauges"] = publish_indexer(
            config.model.cfg, sizes["sequence_length"],
            sizes["per_chip_batch"])
        step = hvd.make_overlapped_train_step(config.loss, tx, has_aux=True)
        params, aux = init(put(reference.seed_key(seeds[0])))
        params, opt_state, aux = step.init(
            params, jax.jit(tx.init)(params), aux)
        losses, divergences, rows = [], [], []
        before = np.zeros((sizes["num_hidden_layers"],
                           sizes["num_experts_published"]), np.int64)
        for _ in range(args.steps):
            params, opt_state, aux, loss = step(params, opt_state, batch,
                                                aux)
            losses.append(float(loss))
            divergences.append(float(aux["indexer_loss"]))
            counts = np.asarray(aux["tokens_per_expert"], np.int64)
            held = (counts - before)[:, np.asarray(sizes["experts_held"])]
            before = counts
            rows.append({"busiest": held.max(axis=1).tolist(),
                         "mean": held.mean(axis=1).tolist()})
        out["steps"] = {"losses": losses, "indexer_loss": divergences,
                        "rows_a_held_expert": rows[:2] + rows[-1:]}
        ok = ok and divergences[-1] < divergences[0]

    out["ok"] = bool(ok)
    print(json.dumps(out), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
