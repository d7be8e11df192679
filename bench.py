"""Headline benchmark: ResNet-50 synthetic training throughput + MFU.

Mirror of the reference's synthetic benchmark
(`examples/tensorflow2/tensorflow2_synthetic_benchmark.py`: ResNet-50,
synthetic ImageNet-shaped batches, warmup then timed iterations, reports
images/sec).  Runs on the attached TPU, one GSPMD train step over every
visible chip.  Without a TPU it exits non-zero and prints no metric: a
number from another platform is not this metric.

``vs_baseline`` is **MFU** — measured FLOP/s divided by the chip's bf16
peak.  FLOPs/step come from XLA's own cost model
(``compiled.cost_analysis()['flops']``, multiply-add = 2 ops, the same
convention as the peak numbers).  The reference's published numbers
remain in BASELINE.md for context.

Output: ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import json
import os
import sys
import time

# bf16 peak FLOP/s per chip, keyed by ``device_kind`` exactly as JAX
# reports it (Google Cloud TPU documentation, system architecture pages
# for v4, v5e and v6e).  A device that is not listed is an error.
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v6 lite": 918e12,
}

_BATCH_SIZE = 128   # global; split over the visible chips
_IMAGE_SIZE = 224
_WARMUP = 5
_ITERS = 30


def peak_flops(device_kind: str) -> float:
    try:
        return _PEAK_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s known for device_kind {device_kind!r}; add it "
            f"to bench._PEAK_FLOPS with its source") from None


def require_tpu():
    """(first device, its peak FLOP/s); exits when JAX found no TPU."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"{os.path.basename(sys.argv[0])} measures a TPU; JAX found "
            f"platform {device.platform!r} ({device.device_kind})")
    return device, peak_flops(device.device_kind)


def main() -> None:
    import jax

    from horovod_tpu.common.compile_cache import configure_compile_cache

    configure_compile_cache()
    device, peak = require_tpu()

    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.models import ResNet50
    from horovod_tpu.models.training import (
        create_train_state,
        make_sharded_train_step,
    )
    from horovod_tpu.parallel import MeshSpec, build_mesh, shard_batch

    n_dev = len(jax.devices())
    batch_size = -(-_BATCH_SIZE // n_dev) * n_dev

    mesh = build_mesh(MeshSpec(data=-1))
    # Opt-in pallas conv1x1+BN-stat fusion (kernels/conv_bn_stats.py);
    # flip the default only on a measured win (benchmarks/resnet_levers.py
    # "fused_conv1x1_bn" lever).  Multi-device runs go through the
    # shard_map flavor (psum'd statistics) via fused_bn_mesh.
    fused_bn = os.environ.get("HVD_BENCH_FUSED_BN") == "1"
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                     fuse_conv1x1_bn=fused_bn,
                     fused_bn_mesh=mesh if fused_bn and n_dev > 1
                     else None)
    tx = optax.sgd(0.01, momentum=0.9)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch_size, _IMAGE_SIZE, _IMAGE_SIZE, 3),
                    jnp.float32)
    y = jnp.asarray(rng.randint(0, 1000, size=(batch_size,)), jnp.int32)
    batch = shard_batch(mesh, {"x": x, "y": y})

    state = create_train_state(model, jax.random.PRNGKey(0), x, tx,
                               mesh=mesh, init_kwargs={"train": True})
    step = make_sharded_train_step(model, tx, mesh, has_batch_stats=True,
                                   donate=True)

    # AOT-compile once: the same executable serves the timed loop AND the
    # FLOPs measurement (no second trace/compile).  cost_analysis describes
    # the SPMD-partitioned per-device module.
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    flops_per_step_dev = float(compiled.cost_analysis()["flops"])

    # Each sync is a host read of the step's loss: it depends on every
    # chained step, so it returns only when the device has finished them.
    for _ in range(_WARMUP):
        state, loss = compiled(state, batch)
    float(loss)

    t0 = time.perf_counter()
    for _ in range(_ITERS):
        state, loss = compiled(state, batch)
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    if not np.isfinite(final_loss):
        raise SystemExit(f"non-finite loss {final_loss}")

    img_per_sec = batch_size * _ITERS / dt / n_dev
    flops_per_sec = flops_per_step_dev * _ITERS / dt
    mfu = round(flops_per_sec / peak, 4)

    print(json.dumps({
        "metric": "resnet50_synthetic_images_per_sec_per_chip",
        "value": round(img_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": mfu,
        "mfu": mfu,
        "tflops_per_sec_per_chip": round(flops_per_sec / 1e12, 2),
        "flops_per_step_per_device": flops_per_step_dev,
        "flops_source": "xla_cost_analysis",
        "batch_size": batch_size,
        "platform": device.platform,
        "device": device.device_kind,
        "device_count": n_dev,
        "compile_s": round(compile_s, 2),
    }), flush=True)


if __name__ == "__main__":
    main()
