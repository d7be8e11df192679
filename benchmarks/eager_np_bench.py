"""Multi-process eager dispatch-chain benchmark (VERDICT r3 missing #6).

The r3 eager-vs-jit number was measured at np=1, where ``XlaAllreduce``
takes the ``local_allreduce`` shortcut — the np>1 chain (fuse →
``make_array_from_single_device_arrays`` → global-mesh jit → unfuse) had
appeared in no perf number.  This harness runs UNDER THE LAUNCHER on the
virtual CPU mesh and measures, per process:

- **jit**: local train step, no communication (the per-chip compute
  baseline);
- **eager**: the same step with grads through ``DistributedOptimizer``
  (full negotiate → fuse → global-mesh collective → unfuse chain);
- **eager_overlap**: ``DistributedOptimizer(overlap=True,
  backward_passes_per_step=2)`` — the WFBP microbatch pipeline;
- **wfbp_step**: the in-program overlapped step
  (``make_overlapped_train_step`` — forward+backward+allreduce+update in
  one XLA program);
- **dispatch probe**: enqueue→synchronize wall time of a single fused
  allreduce at several payload sizes; the small-payload time is almost
  pure per-dispatch overhead (negotiation cycle + fuse + global-array
  assembly + jit launch + unfuse), the scaling-model input the r3 model
  had to assume.

Run (CPU mesh, one device per process):

    JAX_PLATFORMS=cpu python -m horovod_tpu.runner.launch -np 8 \
        --data-plane xla python benchmarks/eager_np_bench.py \
        --out benchmarks/results/eager_np8_cpu.json

Rank 0 writes the JSON artifact.
"""

from __future__ import annotations

import argparse
import json
import time


def _bench(fn, warmup: int, iters: int, after_warmup=None) -> float:
    """Mean seconds per call; ``after_warmup`` runs between the warmup and
    the timed region (e.g. resetting profile accumulators)."""
    for _ in range(warmup):
        fn()
    if after_warmup is not None:
        after_warmup()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--hidden", type=int, default=512)
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--out", default=None)
    parser.add_argument("--profile", action="store_true", default=False,
                        help="include the per-phase dispatch-chain "
                             "breakdown and controller fast-path counters")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.frameworks.jax.optimizer import DistributedOptimizer
    from horovod_tpu.frameworks.jax.wfbp import make_overlapped_train_step

    hvd.init()
    rank, size = hvd.rank(), hvd.size()

    # -- model: plain MLP pytree, ~1M params at defaults ----------------
    rng = np.random.RandomState(0)
    dims = [args.hidden] * (args.layers + 1)
    params = {f"w{i}": jnp.asarray(rng.randn(dims[i], dims[i + 1]) * 0.05,
                                   jnp.float32)
              for i in range(args.layers)}
    grad_bytes = sum(int(np.prod(v.shape)) * 4 for v in params.values())
    x = jnp.asarray(rng.randn(args.batch_size, args.hidden), jnp.float32)
    y = jnp.asarray(rng.randn(args.batch_size, args.hidden), jnp.float32)

    def loss_fn(p, batch):
        h = batch["x"]
        for i in range(args.layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - batch["y"]) ** 2)

    batch = {"x": x, "y": y}
    tx = optax.sgd(0.01, momentum=0.9)

    # -- jit baseline: local step, zero comm ----------------------------
    @jax.jit
    def jit_step(p, s, b):
        loss, grads = jax.value_and_grad(loss_fn)(p, b)
        upd, s = tx.update(grads, s, p)
        return optax.apply_updates(p, upd), s, loss

    box = [params, tx.init(params)]

    def run_jit():
        p, s, loss = jit_step(box[0], box[1], batch)
        box[0], box[1] = p, s
        jax.block_until_ready(loss)

    jit_dt = _bench(run_jit, args.warmup, args.iters)

    vg = jax.jit(jax.value_and_grad(loss_fn))
    apply_updates = jax.jit(optax.apply_updates)

    def eager_flavor(dopt, n_calls=1):
        st = [params, dopt.init(params)]

        def run():
            for _ in range(n_calls):
                loss, grads = vg(st[0], batch)
                upd, st[1] = dopt.update(grads, st[1], st[0])
                st[0] = apply_updates(st[0], upd)
            jax.block_until_ready(st[0])
        return run

    # -- eager: negotiate+fuse+collective every step --------------------
    from horovod_tpu.core.timeline import phase_stats, wire_stats

    # phase_stats/wire_stats reset after warmup so the breakdown covers
    # the steady-state (cache-warm) timed region only.
    def _reset_stats():
        phase_stats.reset()
        wire_stats.reset()

    eager_dt = _bench(eager_flavor(DistributedOptimizer(tx)),
                      args.warmup, args.iters,
                      after_warmup=_reset_stats)
    phase_breakdown = phase_stats.snapshot()
    wire_counters = wire_stats.snapshot()

    # -- eager overlap: WFBP microbatch pipeline (2 backwards/step) ------
    # n_calls=2 → one full accumulation window per run; per-backward time
    # is dt/2, comparable against the non-overlap bpps=2 flavor.
    ov_dt = _bench(
        eager_flavor(DistributedOptimizer(
            tx, backward_passes_per_step=2, overlap=True), n_calls=2),
        args.warmup, args.iters) / 2
    acc_dt = _bench(
        eager_flavor(DistributedOptimizer(
            tx, backward_passes_per_step=2), n_calls=2),
        args.warmup, args.iters) / 2

    # -- in-program overlapped step -------------------------------------
    step = make_overlapped_train_step(loss_fn, tx)
    gp, gs = step.init(params, tx.init(params))
    wf = [gp, gs]

    def run_wfbp():
        p, s, loss = step(wf[0], wf[1], batch)
        wf[0], wf[1] = p, s
        jax.block_until_ready(loss)

    wfbp_dt = _bench(run_wfbp, args.warmup, args.iters)

    # -- dispatch probe: per-op cost of the full async chain ------------
    probe = {}
    for elems in (256, 65_536, 1_048_576):
        buf = jnp.asarray(rng.randn(elems), jnp.float32)

        def run_probe():
            hvd.synchronize(hvd.allreduce_async(
                buf, op=hvd.Sum, name=f"probe.{elems}"))

        probe[elems] = round(_bench(run_probe, args.warmup,
                                    args.iters) * 1e3, 3)

    from horovod_tpu.backend import xla as xla_backend
    from horovod_tpu.core.state import global_state

    ctrl = global_state().controller
    result = {
        "metric": "eager_np_dispatch_chain",
        "world_size": size,
        "grad_bytes": grad_bytes,
        "platform": jax.devices()[0].platform,
        "jit_step_ms": round(jit_dt * 1e3, 3),
        "eager_step_ms": round(eager_dt * 1e3, 3),
        "eager_gap_pct": round((eager_dt - jit_dt) / jit_dt * 100, 2),
        "eager_overlap_per_backward_ms": round(ov_dt * 1e3, 3),
        "eager_accum_per_backward_ms": round(acc_dt * 1e3, 3),
        "overlap_speedup_pct": round((acc_dt - ov_dt) / acc_dt * 100, 2),
        "wfbp_step_ms": round(wfbp_dt * 1e3, 3),
        "wfbp_gap_vs_jit_pct": round((wfbp_dt - jit_dt) / jit_dt * 100, 2),
        "dispatch_probe_ms": probe,
        "per_dispatch_overhead_ms": probe[256],
        "xla_dispatch_stats": dict(xla_backend.stats),
        # Steady-state fast-path engagement over the whole run: cycles
        # negotiated with mask frames only (zero Request/Response
        # payloads) vs Requests ever serialized by this rank.
        "fast_cycles": ctrl.fast_cycle_count if ctrl else 0,
        "requests_serialized": ctrl.serialized_request_count if ctrl else 0,
        "cache_hits": ctrl.cache_hit_count if ctrl else 0,
    }
    if args.profile:
        result["phase_breakdown_ms"] = phase_breakdown
        # Data-plane counters (core/timeline.py wire_stats): payload bytes
        # the transport moved and heap materializations in the host data
        # plane during the steady-state eager region.
        result["wire_counters"] = wire_counters
    hvd.shutdown()
    if rank == 0:
        line = json.dumps(result)
        print(line)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
