"""What ``correct`` tells apart in ``smallthinker-21b-a3b-wfbp-1chip``, by its
two limits: the plain float32 reference at the timed sizes on the device that
is attached, and beside it the same reference with one thing wrong.

    python3 chip_bench/tools/smallthinker_reference_check.py [--seeds N,N]
        [--variants a,b] [--logits-only] [--out FILE]

Each variant is held to both limits, for the first seed: the harness's on the
first three losses (``worker.py``'s ``REFERENCE_RTOL``) and the
configuration's own on the logits (``logits_rtol`` in its file,
``Config.logits_error``).  The variants: ``bfloat16`` (the nearest precision
below the configuration's: parameters, norms, rotary angles, router, softmax
and loss in bf16 too, matmuls at the default precision), ``dropped_update``
(the second update left out; it moves no logits of fresh weights),
``no_window`` (causal in every layer), ``rope_everywhere`` (rotary positions
in the global layers too), ``router_after_attention`` (the router reads the
normed post-attention state the experts read) and ``silu`` (for relu).  Each
must fail at least one limit (``refused``).  For every seed of ``--seeds`` it
prints the program's and the bf16 reference's logits error.  It also sets the
gauges of this model's routing and masks from the float32 reference's three
steps (``publish_routing``, ``publish_attention``) and prints them.  One JSON
line with the readings beside both limits; ``PERF.md`` records them.  A tool,
run once per builder session; nothing of the benchmark's result line comes
from here.
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

WRONG_LAYERS = ("no_window", "rope_everywhere", "router_after_attention",
                "silu")
VARIANTS = ("bfloat16", "dropped_update") + WRONG_LAYERS


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", default=str(2 ** 31 + 31),
                   help="whole numbers, comma-separated")
    p.add_argument("--variants", default=",".join(VARIANTS))
    p.add_argument("--logits-only", action="store_true",
                   help="leave out the three losses (a compile a variant)")
    p.add_argument("--workload", default="smallthinker-21b-a3b-wfbp-1chip")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from chip_bench import reference, spec, worker
    from horovod_tpu.models.transformer import publish_attention
    from horovod_tpu.parallel.moe import publish_routing

    cell = spec.Cell(args.workload)
    sizes = cell.sizes
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
        also the router's counters after the three steps."""
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

    logits = {}
    for seed in seeds:
        params, _ = init(put(reference.seed_key(seed)))
        data = make_batch(put(reference.rank_key(seed, 0)))
        logits[str(seed)] = {
            "program": config.logits_error(params, data),
            "bfloat16": config.logits_error(params, data, jnp.bfloat16)}
        if seed == seeds[0]:
            for name in variants:
                if how(name)[1] is not None and name != "bfloat16":
                    logits[str(seed)][name] = config.logits_error(
                        params, data, **how(name)[1])
        del params, data
    out = {"device": dev.device_kind, "seeds": seeds, "logits_rtol": own,
           "reference_rtol": rtol, "logits": logits}
    told = {name: {"logits": err, "over_logits_rtol": err > own}
            for name, err in logits[str(seeds[0])].items()
            if name != "program"}
    if not args.logits_only:
        batch = make_batch(put(reference.rank_key(seeds[0], 0)))
        want, aux = three_losses(batch)
        out["float32"] = want
        routing = publish_routing(aux)
        out["gauges"] = {
            "moe_max_load_ratio": routing["max_load_ratio"],
            "moe_rows_held_per_step": routing["rows_held_per_step"],
            "moe_rows_elsewhere_share": routing["rows_elsewhere_share"],
            "attn_allowed_pairs_per_step": publish_attention(
                config.model.cfg, sizes["sequence_length"],
                sizes["per_chip_batch"])}
        for name in variants:
            got, _ = three_losses(
                batch, skip=1 if name == "dropped_update" else None,
                **how(name)[0])
            rel = [abs(a - b) / abs(b) for a, b in zip(got, want)]
            told.setdefault(name, {}).update(
                losses=got, rel=rel, over_reference_rtol=max(rel) > rtol)
    for name, t in told.items():
        t["refused"] = bool(t.get("over_logits_rtol")
                            or t.get("over_reference_rtol"))
    out["variants"] = told
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f)
    return 0 if all(t["refused"] for t in told.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
