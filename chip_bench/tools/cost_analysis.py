"""Model FLOPs per sample from the shapes (what ``mfu_pct`` uses) beside XLA's
own count for the un-kerneled plain step, per configuration.

    python3 chip_bench/tools/cost_analysis.py [--describe v5e:2x2] [config ...]

Compiles ``value_and_grad`` + optax update of the configuration's loss for
one chip from shapes alone (no weights are made) and prints
``compiled.cost_analysis()["flops"]`` per sample, the configuration's own
``flops_per_sample()`` and XLA's memory analysis.  With ``--describe`` the
chip is described, not attached (nothing runs; needs ``JAX_PLATFORMS=cpu``);
without it the first attached device is used.  XLA counts elementwise work
too and counts nothing inside a Pallas custom call, which is why the
benchmark does not take its FLOPs from here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--describe", default=None)
    p.add_argument("configs", nargs="*")
    args = p.parse_args()

    import jax
    import optax
    from jax.sharding import SingleDeviceSharding

    from chip_bench import spec

    if args.describe:
        from jax.experimental import topologies

        device = topologies.get_topology_desc(
            platform="tpu", topology_name=args.describe).devices[0]
    else:
        device = jax.devices()[0]
    sharding = SingleDeviceSharding(device)
    bench = spec.benchmark()
    for entry in bench["configs"]:
        if args.configs and entry["name"] not in args.configs:
            continue
        cell = [w["name"] for w in bench["workloads"]
                if w["config"] == entry["name"]][0]
        cell = spec.Cell(cell)
        config = cell.config_module().Config(cell.sizes)
        tx = config.optimizer(1)

        def step(params, aux, opt_state, batch):
            (loss, aux), grads = jax.value_and_grad(
                config.loss, has_aux=True)(params, aux, batch)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), aux, opt_state, loss

        key = jax.ShapeDtypeStruct((2,), jax.numpy.uint32)
        params, aux = jax.eval_shape(config.init, key)
        shapes = (params, aux, jax.eval_shape(tx.init, params),
                  jax.eval_shape(config.make_batch, key))
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), shapes)
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            *shapes).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        mem = compiled.memory_analysis()
        print(json.dumps({
            "config": entry["name"],
            "device": getattr(device, "device_kind", str(device)),
            "attached": args.describe is None,
            "per_chip_batch": config.per_chip_batch,
            "flops_per_sample_from_shapes": config.flops_per_sample(),
            "flops_per_sample_cost_analysis":
                float(cost["flops"]) / config.per_chip_batch,
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
        }), flush=True)


if __name__ == "__main__":
    main()
