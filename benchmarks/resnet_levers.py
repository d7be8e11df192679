"""ResNet-50 BN-statistics levers, measured (VERDICT r3 next #3).

The r3 profile showed 46.6% of device time in ``convert_reduce_fusion`` —
BatchNorm statistics (fwd moments + bwd reductions) reading bf16
activations into fp32 reductions — and defended 31% MFU with a roofline
whose byte count was admittedly overcounted.  This harness measures the
levers instead of arguing:

- **baseline** — fp32 BN reductions, one-pass variance (the shipped
  config);
- **bf16_stats** — ``force_float32_reductions=False``: statistics
  reduce in bf16 (XLA picks the accumulator).  Numerics check: loss
  trajectory + running-stat drift vs baseline over the same batches;
- **two_pass_var** — ``use_fast_variance=False``: textbook two-pass
  variance, expected slower (one more full activation read) — measured
  to bound how much the one-pass trick is already buying;
- **XLA flag experiments** (child processes, so the flag reaches backend
  init; they run BEFORE this process first touches JAX, because a parent
  that holds the chip leaves none for a child):
  ``--xla_tpu_scoped_vmem_limit_kib=65536`` (deeper fusion headroom).

Each config: compile, warmup, timed steps on the attached chip →
images/sec + MFU.  Needs a TPU.  Output: one JSON object.

Run: ``python benchmarks/resnet_levers.py [--iters 20]``
Single-config child mode (used for flag experiments):
``python benchmarks/resnet_levers.py --single baseline``
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def run_config(name: str, iters: int, warmup: int, batch_size: int,
               check_numerics: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import bench
    from horovod_tpu.models import ResNet50
    from horovod_tpu.models.training import (
        create_train_state,
        make_sharded_train_step,
    )
    from horovod_tpu.parallel import MeshSpec, build_mesh, shard_batch

    device, peak = bench.require_tpu()
    bs, img = batch_size, 224

    overrides = {
        "baseline": {},
        "bf16_stats": {"bn_f32_stats": False},
        "two_pass_var": {"bn_fast_variance": False},
        # The structural lever (r4's "one option left"): BN statistics
        # fused into the 1x1 convs' pallas epilogue — eliminates the
        # stats re-read of those activations entirely
        # (horovod_tpu/kernels/conv_bn_stats.py).
        "fused_conv1x1_bn": {"fuse_conv1x1_bn": True},
    }[name]  # unknown names must raise, not silently measure baseline

    mesh = build_mesh(MeshSpec(data=-1))
    n_dev = len(jax.devices())
    if overrides.get("fuse_conv1x1_bn") and n_dev > 1:
        overrides["fused_bn_mesh"] = mesh  # shard_map flavor
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, **overrides)
    tx = optax.sgd(0.01, momentum=0.9)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(bs, img, img, 3), jnp.float32)
    y = jnp.asarray(rng.randint(0, 1000, size=(bs,)), jnp.int32)

    state = create_train_state(model, jax.random.PRNGKey(0), x, tx,
                               mesh=mesh, init_kwargs={"train": True})
    step = make_sharded_train_step(model, tx, mesh, has_batch_stats=True,
                                   donate=True)
    batch = shard_batch(mesh, {"x": x, "y": y})
    compiled = step.lower(state, batch).compile()
    flops = compiled.cost_analysis()["flops"]

    losses = []
    for _ in range(max(1, warmup)):  # >=1: compile outside the timed loop
        state, loss = compiled(state, batch)
    losses.append(float(loss))
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = compiled(state, batch)
    losses.append(float(loss))
    dt = (time.perf_counter() - t0) / iters

    out = {
        "config": name,
        "batch_size": bs,
        "step_ms": round(dt * 1e3, 3),
        "images_per_sec": round(bs / dt, 2),
        "mfu": round(flops / dt / peak, 4),
        "device": device.device_kind,
        "final_loss": losses[-1],
        "finite": bool(np.isfinite(losses[-1])),
    }
    if check_numerics:
        # Running-stat drift vs what fp32 stats produce on one batch: an
        # absolute BN-mean comparison after `warmup+iters` identical
        # steps.  (Cheap proxy; convergence claims need real training.)
        means = jax.tree_util.tree_leaves(state.batch_stats)
        out["stat_abs_max"] = float(max(
            jnp.max(jnp.abs(m)) for m in means))
    return out


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=5)
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--single", default=None,
                        help="run ONE config and print its JSON (child "
                             "mode for flag experiments)")
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    # bench.py (the peaks table) lives at the repo root
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    if args.single:
        print(json.dumps(run_config(args.single, args.iters, args.warmup,
                                    args.batch_size, True)))
        return 0

    results = {}
    # Flag experiments first, each in a child process: XLA_FLAGS latch at
    # backend init, and a chip belongs to one process at a time, so the
    # children must be done before this process makes its first JAX call.
    here = os.path.abspath(__file__)
    for flag_name, flags in (
            ("vmem64m", "--xla_tpu_scoped_vmem_limit_kib=65536"),):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flags).strip()
        proc = subprocess.run(
            [sys.executable, here, "--single", "baseline",
             "--iters", str(args.iters), "--warmup", str(args.warmup),
             "--batch-size", str(args.batch_size)],
            env=env, capture_output=True, text=True, timeout=560)
        lines = proc.stdout.strip().splitlines()
        if proc.returncode == 0 and lines and lines[-1].startswith("{"):
            results[flag_name] = json.loads(lines[-1])
        else:
            results[flag_name] = {"error": proc.stderr[-500:],
                                  "returncode": proc.returncode}
        results[flag_name]["xla_flags"] = flags
        print(flag_name, "->", results[flag_name], file=sys.stderr)

    # fused lever: multi-device runs use the shard_map flavor (psum'd
    # statistics).
    for name in ("baseline", "bf16_stats", "two_pass_var",
                 "fused_conv1x1_bn"):
        results[name] = run_config(name, args.iters, args.warmup,
                                   args.batch_size, True)
        print(name, "->", results[name], file=sys.stderr)

    payload = {"metric": "resnet50_bn_levers", "results": results}
    line = json.dumps(payload)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
