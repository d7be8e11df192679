"""What ``correct`` tells apart in ``lfm2-8b-a1b-wfbp-1chip``, by its two
limits: the plain float32 reference at the timed sizes on the device that is
attached, and beside it the same reference with one thing wrong.

    python3 chip_bench/tools/lfm2_reference_check.py [--seeds N,N]
        [--variants a,b] [--logits-only] [--steps N] [--set key=value]
        [--out FILE]

Each variant is held to both limits, for the first seed: the harness's on the
first three losses (``worker.py``'s ``REFERENCE_RTOL``) and the
configuration's own on the logits (``logits_rtol``, ``logits_median_rtol``
and ``logits_float32_rtol`` in its file, ``Config.logits_errors``: a reading
is the pair of the share of the norm and the median position's share; the
reference in bf16 is held to the first two, which hold the program as it is
timed, and a wrong layer to the third, which holds the program's model
computed in float32, since a wrong layer is a wrong model in any
precision).  The variants: ``bfloat16`` (the nearest precision
below the configuration's: parameters, norms, rotary angles, gates and taps,
router, softmax and loss in bf16 too, matmuls at the default precision),
``dropped_update`` (the second update left out; it moves no logits of fresh
weights) and the wrong layers ``taps_reversed``, ``no_c_gate``, ``softmax``
(for the sigmoid), ``bias_in_weights``, ``no_qk_norm`` and ``scale_128``.
The logits of every variant, and the program's, are taken under a selection
bias that is not zero (``--bias``, a seeded normal of that width: under a
fresh run's zeros ``bias_in_weights`` is the sound model).  Each must fail at
least one limit (``refused``).  For every seed of ``--seeds`` it prints the
program's and the bf16 reference's logits error, under the zero bias of a
fresh run and under that bias.

With ``--steps N`` it also steps the program through
``hvd.make_overlapped_train_step(has_aux=True)`` N times on the first seed
and reads ``aux`` after each: the rows the held experts got in each layer
(against the first chunk of ``row_buffer``), the bias's largest magnitude,
and after the third step how many of the bias's entries differ from the
float32 reference's after its three steps.  ``--set key=value`` overrides a
size of the configuration's file (``embedding_init_std=1.0``).

One JSON line with the readings beside both limits; ``PERF.md`` records
them.  A tool, run once per builder session; nothing of the benchmark's
result line comes from here.
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

WRONG_LAYERS = ("taps_reversed", "no_c_gate", "softmax", "bias_in_weights",
                "no_qk_norm", "scale_128")
VARIANTS = ("bfloat16", "dropped_update") + WRONG_LAYERS


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", default=str(2 ** 31 + 31),
                   help="whole numbers, comma-separated")
    p.add_argument("--variants", default=",".join(VARIANTS))
    p.add_argument("--logits-only", action="store_true",
                   help="leave out the three losses (a compile a variant)")
    p.add_argument("--bias", type=float, default=0.05,
                   help="width of the seeded selection bias of the logits")
    p.add_argument("--steps", type=int, default=0)
    p.add_argument("--set", action="append", default=[],
                   help="key=value (JSON) over the configuration's sizes")
    p.add_argument("--workload", default="lfm2-8b-a1b-wfbp-1chip")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from chip_bench import reference, spec, worker
    from horovod_tpu.parallel.moe import publish_routing, row_buffer

    cell = spec.Cell(args.workload)
    sizes = dict(cell.sizes)
    for item in args.set:
        key, value = item.split("=", 1)
        sizes[key] = json.loads(value)
    module = cell.config_module()
    config = module.Config(sizes)
    ref = module._load_reference()
    dev = jax.devices()[0]
    put = functools.partial(jax.device_put, device=dev)
    tx = config.optimizer(1)
    seeds = [int(x) for x in args.seeds.split(",")]
    variants = [v for v in args.variants.split(",") if v]
    unknown = set(variants) - set(VARIANTS)
    if unknown:
        p.error(f"unknown variants {sorted(unknown)}; have {VARIANTS}")
    make_batch, init = jax.jit(config.make_batch), jax.jit(config.init)
    rtol, own = worker.REFERENCE_RTOL, sizes["logits_rtol"]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def update(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def three_losses(batch, skip=None, **variant):
        """``reference.reference_losses`` at one rank, with ``variant`` of
        the reference's loss and the update after step ``skip`` left out;
        also ``aux`` after the three steps."""
        grad = jax.jit(jax.value_and_grad(ref.make_loss(sizes, **variant),
                                          has_aux=True))
        params, aux = init(put(reference.seed_key(seeds[0])))
        opt_state = jax.jit(tx.init)(params)
        losses = []
        for step in range(3):
            (loss, aux), g = grad(params, aux, batch)
            losses.append(float(loss))
            if step != skip:
                params, opt_state = update(params, opt_state, g)
        return losses, aux

    def how(name):
        """(the reference's loss variant, the logits variant) of a name."""
        if name == "bfloat16":
            return {"dtype": jnp.bfloat16}, {"dtype": jnp.bfloat16}
        if name == "dropped_update":
            return {}, None
        return {"wrong": (name,)}, {"dtype": jnp.float32, "wrong": (name,)}

    def some_bias(seed):
        return args.bias * jax.random.normal(
            jax.random.fold_in(reference.seed_key(seed), 7),
            ref.zero_bias(sizes).shape, jnp.float32)

    # The program's steps first, on a device that holds nothing else, as the
    # harness's worker has it: the step's program needs the bottom of the
    # memory in one piece.
    program_steps = after_three = None
    if args.steps:
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
        held_before = np.zeros(config.expert_layers, np.int64)
        rows, losses, bias_max = [], [], []
        for i in range(args.steps):
            params, state, aux, loss = step(params, state, batch, aux)
            now = step.fetch(aux)
            held = np.asarray(now["rows_held"], np.int64)
            rows.append((held - held_before).tolist())
            held_before = held
            losses.append(float(loss))
            bias_max.append(float(np.abs(np.asarray(
                now["expert_bias"])).max()))
            if i == 2:
                after_three = {key: np.asarray(now[key]) for key in (
                    "expert_bias", "tokens_per_expert")}
        program_steps = {
            "first_chunk": cap, "chunks": chunks,
            "rows_held_max": int(np.max(rows)),
            "rows_held_by_step": rows if args.steps <= 12
            else rows[:6] + rows[-6:],
            "losses": losses[:4] + losses[-2:],
            "bias_abs_max": bias_max[:3] + bias_max[-1:]}
        hvd.shutdown()
        del params, state, aux, batch, step, now

    logits = {}
    for seed in seeds:
        params, _ = init(put(reference.seed_key(seed)))
        data = make_batch(put(reference.rank_key(seed, 0)))
        bias = some_bias(seed)
        errors = functools.partial(config.logits_errors, params, data)
        logits[str(seed)] = {
            "program_fresh": errors(), "bfloat16_fresh": errors(jnp.bfloat16),
            "program_float32_fresh": errors("program_float32")}
        if seed == seeds[0]:
            logits[str(seed)].update(
                program=errors(bias=bias),
                bfloat16=errors(jnp.bfloat16, bias=bias),
                program_float32=errors("program_float32", bias=bias))
            for name in variants:
                if how(name)[1] is not None and name != "bfloat16":
                    logits[str(seed)][name] = errors(bias=bias,
                                                     **how(name)[1])
        print(seed, logits[str(seed)], file=sys.stderr, flush=True)
        del params, data
    out = {"device": dev.device_kind, "seeds": seeds, "logits_rtol": own,
           "reference_rtol": rtol, "bias": args.bias, "set": args.set,
           "logits": logits}
    median, exact = sizes["logits_median_rtol"], sizes["logits_float32_rtol"]
    out["logits_median_rtol"], out["logits_float32_rtol"] = median, exact
    # A reading is (share of the norm, the median position's share).  The
    # reference in bf16 is held to the two limits of the program as it is
    # timed; a wrong layer, which is in float32, to the float32 program's.
    told = {name: {"logits": err, "over_logits_rtol":
                   err[0] > (own if name == "bfloat16" else exact)
                   or (name == "bfloat16" and err[1] > median)}
            for name, err in logits[str(seeds[0])].items()
            if not name.startswith("program") and name != "bfloat16_fresh"}
    want_aux = None
    if not args.logits_only:
        batch = make_batch(put(reference.rank_key(seeds[0], 0)))
        want, want_aux = three_losses(batch)
        out["float32"] = want
        routing = publish_routing(want_aux)
        out["gauges"] = {key: routing[key] for key in (
            "max_load_ratio", "rows_held_per_step", "rows_elsewhere_share",
            "expert_bias_abs_max")}
        for name in variants:
            got, _ = three_losses(
                batch, skip=1 if name == "dropped_update" else None,
                **how(name)[0])
            rel = [abs(a - b) / abs(b) for a, b in zip(got, want)]
            told.setdefault(name, {}).update(
                losses=got, rel=rel, over_reference_rtol=max(rel) > rtol)
            print(name, told[name], file=sys.stderr, flush=True)
    for name, t in told.items():
        t["refused"] = bool(t.get("over_logits_rtol")
                            or t.get("over_reference_rtol"))
    out["variants"] = told
    if program_steps is not None:
        out["program_steps"] = program_steps
    if after_three is not None and want_aux is not None:
        got_bias, want_bias = after_three["expert_bias"], \
            np.asarray(want_aux["expert_bias"])
        out["bias_after_three_steps"] = {
            "entries": int(got_bias.size),
            "differ": int((got_bias != want_bias).sum()),
            "abs_max": float(np.abs(got_bias).max()),
            "counts_differ": int((
                after_three["tokens_per_expert"]
                != np.asarray(want_aux["tokens_per_expert"])).sum())}

    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f)
    return 0 if all(t["refused"] for t in told.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
