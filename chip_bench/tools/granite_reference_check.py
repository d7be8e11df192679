"""How far the program, the reference in bf16 and wrong variants of the
reference lie from granite-4.0-h-micro's float32 reference, on the chip at
the published widths, beside the limits of ``correct``.

    python3 chip_bench/tools/granite_reference_check.py [--seeds a,b]
        [--variants v,..] [--losses] [--set key=value ...]

A reading is the pair (the logits' difference as a share of their norm, the
median over the positions of each position's own share), over all positions
of one batch of fresh weights from a seed.  For every seed: the program as it
is timed and the reference in bf16 throughout (both held to ``logits_rtol``
and ``logits_median_rtol``) and the program's model in float32 (held to
``logits_float32_rtol`` by the norm).  For the first seed each wrong variant
of the float32 reference (``WRONG``: the four muP scalars one at a time at
the value every other model runs, and four wrong layers), held to
``logits_float32_rtol``: what refuses the same fault in the program.
``--losses`` also steps the float32 reference three times beside its bf16
form and a dropped update and holds their losses to the harness's 3e-4.
Exits non-zero if a variant is refused by no limit, or the program by one.
One process, one chip; no result line comes from here.
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

WRONG = ("no_embedding_multiplier", "no_residual_multiplier",
         "scores_over_sqrt_head", "no_logits_scaling", "norm_before_gate",
         "up_as_gate", "decay_without_dt", "rope")


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", default=str(2 ** 31 + 56),
                   help="whole numbers, comma-separated")
    p.add_argument("--variants", default=None,
                   help="wrong variants of the reference (default: all)")
    p.add_argument("--losses", action="store_true")
    p.add_argument("--set", action="append", default=[],
                   help="key=value (JSON) over the configuration's sizes")
    p.add_argument("--workload", default="granite-4.0-h-micro-wfbp-1chip")
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
    config = cell.config_module().Config(sizes)
    ref = config.reference
    dev = jax.devices()[0]
    put = functools.partial(jax.device_put, device=dev)
    tx = config.optimizer(1)
    seeds = [int(x) for x in args.seeds.split(",")]
    variants = list(WRONG) if args.variants is None \
        else [v for v in args.variants.split(",") if v]
    if set(variants) - set(WRONG):
        p.error(f"unknown variants; have {WRONG}")
    make_batch, init = jax.jit(config.make_batch), jax.jit(config.init)
    own, median, exact = (sizes[k] for k in (
        "logits_rtol", "logits_median_rtol", "logits_float32_rtol"))

    logits = {}
    for seed in seeds:
        params, _ = init(put(reference.seed_key(seed)))
        data = make_batch(put(reference.rank_key(seed, 0)))
        errors = functools.partial(config.logits_errors, params, data)
        logits[str(seed)] = {
            "program": errors(), "bfloat16": errors(jnp.bfloat16),
            "program_float32": errors("program_float32")}
        if seed == seeds[0]:
            for name in variants:
                logits[str(seed)][name] = errors(jnp.float32, (name,))
        print(seed, logits[str(seed)], file=sys.stderr, flush=True)
        del params, data, errors
    first = logits[str(seeds[0])]
    told = {name: {"logits": first[name], "refused": first[name][0] > exact}
            for name in variants}
    told["bfloat16"] = {
        "logits": first["bfloat16"],
        "refused": first["bfloat16"][0] > own or first["bfloat16"][1] > median}
    program_passes = all(
        r["program"][0] <= own and r["program"][1] <= median
        and r["program_float32"][0] <= exact for r in logits.values())
    out = {"device": dev.device_kind, "seeds": seeds, "set": args.set,
           "logits_rtol": own, "logits_median_rtol": median,
           "logits_float32_rtol": exact, "logits": logits,
           "program_passes": program_passes}

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
            worst = max(abs(a - b) / abs(b) for a, b in zip(got, want))
            told.setdefault(name, {"refused": False})
            told[name]["losses"] = got
            told[name]["loss_rel_err"] = worst
            told[name]["refused"] = told[name]["refused"] or worst > rtol
            print(name, got, worst, file=sys.stderr, flush=True)

    out["variants"] = told
    text = json.dumps(out, indent=1)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    return 0 if program_passes and all(
        v["refused"] for v in told.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
