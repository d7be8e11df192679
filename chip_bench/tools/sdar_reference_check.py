"""What ``correct`` tells apart in ``sdar-30b-a3b-wfbp-1chip``, by its two
limits: the plain float32 reference at the timed sizes on the device that is
attached, and beside it the same reference with one thing wrong.

    python3 chip_bench/tools/sdar_reference_check.py [--seeds N,N]
        [--logits-only] [--out FILE]

Against the harness's limit on the first three losses (``worker.py``'s
``REFERENCE_RTOL``), for the first seed, each variant's losses relative to
the float32 reference's: ``bfloat16`` (the nearest precision below the
configuration's: parameters, norms, rotary angles, router, softmax and loss
in bf16 too, matmuls at the default precision), ``dropped_update`` (the
second update left out), ``causal_mask`` (key <= query over the 2L positions
in place of the block rule), ``no_1_over_t`` (every masked token weighted 1).
Against the configuration's own limit (``logits_rtol`` in its file), for
every seed, ``Config.logits_error`` of the program and of the bf16 reference.
Prints one JSON line with the readings beside both limits; ``PERF.md``
records them.  A tool, run once per builder session; nothing of the
benchmark's result line comes from here.
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


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", default=str(2 ** 31 + 31),
                   help="whole numbers, comma-separated")
    p.add_argument("--logits-only", action="store_true",
                   help="leave out the variants' losses (four more compiles)")
    p.add_argument("--workload", default="sdar-30b-a3b-wfbp-1chip")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from chip_bench import reference, spec, worker

    cell = spec.Cell(args.workload)
    sizes = cell.sizes
    module = cell.config_module()
    config = module.Config(sizes)
    ref = module._load_reference()
    dev = jax.devices()[0]
    put = functools.partial(jax.device_put, device=dev)
    tx = config.optimizer(1)
    seeds = [int(x) for x in args.seeds.split(",")]
    make_batch, init = jax.jit(config.make_batch), jax.jit(config.init)
    batch = make_batch(put(reference.rank_key(seeds[0], 0)))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def update(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def three_losses(batch, skip=None, **variant):
        """``reference.reference_losses`` at one rank, with ``variant`` of
        the reference's loss and the update after step ``skip`` left out."""
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
        return losses

    logits = {}
    for seed in seeds:
        params, _ = init(put(reference.seed_key(seed)))
        data = make_batch(put(reference.rank_key(seed, 0)))
        logits[str(seed)] = {
            "program": config.logits_error(params, data),
            "bfloat16": config.logits_error(params, data, jnp.bfloat16)}
        del params, data
    out = {"device": dev.device_kind, "seeds": seeds,
           "logits_rtol": sizes["logits_rtol"], "logits": logits}
    if not args.logits_only:
        want = three_losses(batch)
        every_t_one = {**batch, "t": jnp.ones_like(batch["t"])}
        variants = {
            "bfloat16": three_losses(batch, dtype=jnp.bfloat16),
            "dropped_update": three_losses(batch, skip=1),
            "causal_mask": three_losses(
                batch, mask=lambda q, k, half_len, block: k <= q),
            "no_1_over_t": three_losses(every_t_one)}
        out.update({
            "reference_rtol": worker.REFERENCE_RTOL, "float32": want,
            "variants": {
                name: {"losses": got,
                       "rel": [abs(a - b) / abs(b)
                               for a, b in zip(got, want)],
                       "told_apart": any(
                           abs(a - b) > worker.REFERENCE_RTOL * abs(b)
                           for a, b in zip(got, want))}
                for name, got in variants.items()}})
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
