"""How far the program, the reference in bf16 and wrong variants of the
reference lie from joyai-llm-flash's float32 reference, on the chip at the
published widths, beside the limits of ``correct``.

    python3 chip_bench/tools/joyai_reference_check.py [--seeds a,b]
        [--variants v,..] [--losses] [--steps N] [--set key=value ...]

A reading is the pair (both heads' logits' difference as a share of their
norm, the median over a head's positions of each position's own share, the
worse head's), over all positions of one batch of fresh weights from a seed.
For every seed: the program as it is timed, the reference in bf16 throughout
(both held to ``logits_rtol`` and ``logits_median_rtol``) and the program's
model in float32 (held to ``logits_float32_rtol`` by the median and
``logits_float32_norm_rtol`` by the norm).  For the first seed, under a
seeded selection bias of width ``--bias``: the same three, and each wrong
layer of the float32 reference (``WRONG_LAYERS``: the rotary key left
unrotated, ``kv_a_layernorm`` left out, the module fed token i for token
i + 1, scores scaled by 128^-0.5; held to the two limits on the program's own
model in float32).  ``--losses`` also steps the float32 reference three times
beside its bf16 form and a dropped update and holds their losses to the
harness's 3e-4.  ``--steps N`` steps the program through
``hvd.make_overlapped_train_step`` and prints the rows the held experts got a
block and step beside ``row_buffer``'s first chunk, the bias's largest
magnitude, the two cross-entropies apart and the allowed pairs a step
(``publish_attention``).  Exits non-zero if a variant is refused by no limit.
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

WRONG_LAYERS = ("rope_key_unrotated", "no_kv_norm", "module_reads_token_i",
                "scale_by_nope")


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", default=str(2 ** 31 + 41),
                   help="whole numbers, comma-separated")
    p.add_argument("--variants", default=None,
                   help="wrong layers (default: all)")
    p.add_argument("--losses", action="store_true")
    p.add_argument("--bias", type=float, default=0.05,
                   help="width of the seeded selection bias of the logits")
    p.add_argument("--steps", type=int, default=0)
    p.add_argument("--set", action="append", default=[],
                   help="key=value (JSON) over the configuration's sizes")
    p.add_argument("--workload",
                   default="joyai-llm-flash-wfbp-1chip")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from chip_bench import reference, spec, worker
    from horovod_tpu.models.transformer import publish_attention
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
    if args.variants is None:
        variants = list(WRONG_LAYERS)
    else:
        variants = [v for v in args.variants.split(",") if v]
    if set(variants) - set(WRONG_LAYERS):
        p.error(f"unknown variants; have {WRONG_LAYERS}")
    make_batch, init = jax.jit(config.make_batch), jax.jit(config.init)
    own, median, exact, exact_norm = (sizes[k] for k in (
        "logits_rtol", "logits_median_rtol", "logits_float32_rtol",
        "logits_float32_norm_rtol"))

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
                                 sizes["n_routed_experts_published"])
        before = np.zeros(config.expert_layers, np.int64)
        rows, losses, bias_max, entropies = [], [], [], []
        for _ in range(args.steps):
            params, state, aux, loss = step(params, state, batch, aux)
            now = step.fetch(aux)
            held = np.asarray(now["rows_held"], np.int64)
            rows.append((held - before).tolist())
            before = held
            losses.append(float(loss))
            bias_max.append(float(np.abs(np.asarray(
                now["expert_bias"])).max()))
            entropies.append(np.asarray(now["cross_entropy"]).tolist())
        counts = np.asarray(now["tokens_per_expert"], np.float64)
        program_steps = {
            "first_chunk": cap, "chunks": chunks, "mean_share": slots
            * len(sizes["experts_held"]) / sizes["n_routed_experts_published"],
            "rows_held_min": int(np.min(rows)),
            "rows_held_max": int(np.max(rows)),
            "rows_held_by_step": rows if args.steps <= 12
            else rows[:6] + rows[-6:],
            "max_load_ratio": [float(c.max() / c.mean()) for c in counts],
            "losses": losses[:4] + losses[-2:],
            "bias_abs_max": bias_max[:3] + bias_max[-1:],
            "cross_entropies": entropies[:3] + entropies[-1:],
            "attn_allowed_pairs_per_step": publish_attention(
                config.model.cfg, sizes["sequence_length"],
                sizes["per_chip_batch"])}
        print("program_steps", program_steps, file=sys.stderr, flush=True)
        hvd.shutdown()
        del params, state, aux, batch, step, now

    def some_bias(seed):
        return args.bias * jax.random.normal(
            jax.random.fold_in(reference.seed_key(seed), 7),
            ref.zero_bias(sizes).shape, jnp.float32)

    logits = {}
    for seed in seeds:
        params, _ = init(put(reference.seed_key(seed)))
        data = make_batch(put(reference.rank_key(seed, 0)))
        errors = functools.partial(config.logits_errors, params, data)
        logits[str(seed)] = {
            "program_fresh": errors(), "bfloat16_fresh": errors(jnp.bfloat16),
            "program_float32_fresh": errors("program_float32")}
        if seed == seeds[0]:
            bias = some_bias(seed)
            logits[str(seed)].update(
                program=errors(bias=bias),
                bfloat16=errors(jnp.bfloat16, bias=bias),
                program_float32=errors("program_float32", bias=bias))
            for name in variants:
                logits[str(seed)][name] = errors(jnp.float32, (name,), bias)
        print(seed, logits[str(seed)], file=sys.stderr, flush=True)
        # `errors` names them too, and the reference's steps need the room.
        del params, data, errors
    first = logits[str(seeds[0])]
    told = {name: {"logits": first[name],
                   "refused": first[name][1] > exact
                   or first[name][0] > exact_norm} for name in variants}
    told["bfloat16"] = {
        "logits": first["bfloat16"],
        "refused": first["bfloat16"][0] > own or first["bfloat16"][1] > median}
    out = {"device": dev.device_kind, "seeds": seeds, "bias": args.bias,
           "set": args.set, "logits_rtol": own, "logits_median_rtol": median,
           "logits_float32_rtol": exact,
           "logits_float32_norm_rtol": exact_norm, "logits": logits}

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
    return 0 if all(t["refused"] for t in told.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
