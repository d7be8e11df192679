"""Does the product path start on the chip?  Not a benchmark.

    python chip_smoke.py

drives what a user drives — ``hvdrun`` → ``hvd.init()`` → ResNet-50
(224x224, bf16, per-chip batch 128) optimizer steps — once through each
product step builder (``hvd.DistributedOptimizer`` on the eager XLA plane,
``hvd.make_overlapped_train_step``), with one process when one chip is
visible and with one process per chip as well when there are several;
compiles the repo's Pallas kernel at ResNet-50's 1x1-conv shapes and
compares it with the unfused composition; and runs ``bench.py`` once.

It exits non-zero, printing no result line, when JAX finds no TPU, when
any stage fails, or when the repo is not beside it.  Otherwise its last
line of output is ``{"ok": true, "device": {...}}``.

This process never imports jax: a chip belongs to one process at a time,
so the device is probed by a short-lived child and every stage runs in
children (``--worker`` below is what they run).  Wall times, compile
seconds and img/s are printed as observations, not as measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
_TOTAL_BUDGET_S = 1150  # the contract allows 1200 for the whole script
_TAG = "SMOKE "


# ---------------------------------------------------------------------------
# parent: probe, launch stages, judge
# ---------------------------------------------------------------------------

def _run(cmd, timeout):
    """Run ``cmd`` in its own process group, echo its stdout, return
    (returncode, lines).  The whole group is killed at ``timeout`` and on
    any exit from here, so no stage outlives the script."""
    proc = subprocess.Popen(cmd, cwd=HERE, text=True, stdout=subprocess.PIPE,
                            start_new_session=True)

    def kill():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(timeout, kill)
    timer.start()
    lines = []
    try:
        for line in proc.stdout:
            print(line, end="", flush=True)
            lines.append(line.rstrip("\n"))
        return proc.wait(), lines
    finally:
        timer.cancel()
        kill()


def _results(lines):
    return [json.loads(ln[len(_TAG):]) for ln in lines if ln.startswith(_TAG)]


def _probe():
    code = ("import json, jax; d = jax.devices(); print(%r + json.dumps("
            "{'platform': d[0].platform, 'kind': d[0].device_kind, "
            "'count': len(d)}))" % _TAG)
    rc, lines = _run([sys.executable, "-c", code], timeout=300)
    found = _results(lines)
    if rc != 0 or len(found) != 1:
        sys.exit(f"chip_smoke: the device probe failed (exit {rc})")
    if found[0]["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU, only {found[0]}")
    return found[0]


def main_parent() -> int:
    deadline = time.monotonic() + _TOTAL_BUDGET_S
    if not os.path.isdir(os.path.join(HERE, "horovod_tpu")):
        sys.exit(f"chip_smoke: no horovod_tpu package beside {__file__}")
    device = _probe()
    where = f"[{device['platform']} {device['kind']!r} x{device['count']}]"
    nps = [1] + ([device["count"]] if device["count"] > 1 else [])
    print(f"chip_smoke {where}: running np={nps} "
          f"({device['count']} chip(s) visible)", flush=True)

    hvdrun = [sys.executable, "-m", "horovod_tpu.runner.launch",
              "--data-plane", "xla"]
    worker = [sys.executable, os.path.join(HERE, "chip_smoke.py"),
              "--platform", "tpu", "--worker"]
    stages = [("kernel np=1", hvdrun + ["-np", "1"] + worker + ["kernel"], 1)]
    for n in nps:
        for mode in ("eager", "wfbp"):
            stages.append((f"{mode} np={n}",
                           hvdrun + ["-np", str(n)] + worker + [mode], n))
    stages.append(("bench.py", [sys.executable, "bench.py"], 0))

    losses = {}
    for name, cmd, n_ranks in stages:
        t0 = time.monotonic()
        rc, lines = _run(cmd, timeout=max(1.0, deadline - t0))
        took = time.monotonic() - t0
        if rc != 0:
            sys.exit(f"chip_smoke {where}: stage {name} FAILED "
                     f"(exit {rc}, {took:.0f}s)")
        if n_ranks:
            got = _results(lines)
            if sorted({r["rank"] for r in got}) != list(range(n_ranks)):
                sys.exit(f"chip_smoke {where}: stage {name} reported ranks "
                         f"{[r['rank'] for r in got]}, wanted {n_ranks}")
            losses[name] = got[0].get("loss")
        else:
            rec = json.loads([ln for ln in lines if ln.startswith("{")][-1])
            if rec["device"] != device["kind"] or not rec["mfu"] > 0:
                sys.exit(f"chip_smoke {where}: bench.py printed {rec}")
        print(f"chip_smoke {where}: stage {name} ok, {took:.0f}s wall "
              f"(not a benchmark)", flush=True)

    # The two step builders are the same synchronous SGD; on one process
    # (one batch, one set of BN statistics) their losses must agree.
    a, b = losses["eager np=1"], losses["wfbp np=1"]
    if abs(a - b) > 0.01 * abs(a):
        sys.exit(f"chip_smoke {where}: eager and wfbp disagree at np=1: "
                 f"loss {a} vs {b}")

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# worker: one rank of one stage (runs under hvdrun)
# ---------------------------------------------------------------------------

def _check(cond, what):
    if not cond:
        raise SystemExit(f"chip_smoke worker: FAILED: {what}")


def _emit(stage, rank, **fields):
    import jax

    d = jax.devices()[0]
    print(_TAG + json.dumps({
        "stage": stage, "rank": rank, "platform": d.platform,
        "device_kind": d.device_kind, "global_devices": jax.device_count(),
        "local_devices": jax.local_device_count(), **fields}), flush=True)


def _cache_counters():
    """Count this process's compilations that consulted the persistent
    cache and those it answered.  (JAX's own ``cache_misses`` event counts
    entries written, and only process 0 writes.)"""
    import jax

    counts = {"requests": 0, "hits": 0}
    events = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits"}

    def listen(event, **_):
        if event in events:
            counts[events[event]] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def _host_ring_bytes():
    from horovod_tpu.core import metrics
    from horovod_tpu.core.timeline import wire_stats

    return wire_stats.get("bytes_on_wire") + \
        metrics.registry.get_counter("shm_bytes_total")


def worker_train(mode, args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    cache = _cache_counters()
    import horovod_tpu as hvd
    from horovod_tpu.backend import xla as xla_backend
    from horovod_tpu.core.timeline import phase_stats
    from horovod_tpu.models import ResNet50

    sys.path.insert(0, os.path.join(HERE, "examples", "jax"))
    from jax_synthetic_benchmark import build_step

    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    dev = jax.local_devices()[0]
    _check(dev.platform == args.platform,
           f"platform {dev.platform!r}, wanted {args.platform!r}")
    if size > 1:
        _check(jax.local_device_count() == 1,
               f"{jax.local_device_count()} local devices under chip binding")
        _check(jax.device_count() == size,
               f"{jax.device_count()} global devices for {size} ranks")
        chips = hvd.allgather_object(dev.id, name="smoke.chips")
        _check(len(set(chips)) == size, f"ranks share chips: {chips}")

    # Weights from a seed (the same on every rank), data from the rank.
    model = ResNet50(num_classes=1000)
    rng = np.random.RandomState(1000 + rank)
    images = jnp.asarray(
        rng.rand(args.batch, args.image, args.image, 3), jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, 1000, size=(args.batch,)), jnp.int32)
    tx = optax.sgd(0.01 * size, momentum=0.9)
    step, params = build_step(mode, model, tx, images, labels)

    abs_sum = jax.jit(lambda tree: sum(
        jnp.sum(jnp.abs(p.astype(jnp.float32)))
        for p in jax.tree_util.tree_leaves(tree)))

    def checksum():
        return float(abs_sum(params()))

    before = checksum()
    losses, step_s = [], []
    for i in range(1 + args.steps):  # the first step compiles
        t0 = time.perf_counter()
        losses.append(float(step()))
        step_s.append(time.perf_counter() - t0)
        if i == 0:
            host0 = _host_ring_bytes()
    host_bytes = _host_ring_bytes() - host0
    first_loss, loss = losses[0], losses[-1]

    _check(np.isfinite(first_loss) and np.isfinite(loss),
           f"loss {first_loss} -> {loss}")
    # Random weights and 1000 classes: the first loss is near ln(1000).
    _check(abs(first_loss - np.log(1000)) < 1.0, f"first loss {first_loss}")
    after = checksum()
    _check(np.isfinite(after) and after != before,
           f"parameters did not move: {before} -> {after}")
    sums = hvd.allgather_object(after, name="smoke.checksum")
    _check(len(set(sums)) == 1, f"parameter checksums differ: {sums}")
    _check(hvd.xla_enabled(), "the XLA data plane is not up")
    grad_bytes = 4 * sum(p.size for p in
                         jax.tree_util.tree_leaves(params()))
    # Negotiation frames ride the host ring, a few dozen bytes per cycle
    # whether or not anything is ready; a gradient must not, and one
    # gradient over the ring would be more than this bound.
    _check(host_bytes < args.steps * grad_bytes // 10,
           f"{host_bytes} bytes on the host ring over {args.steps} steps "
           f"({grad_bytes} bytes of gradients per step)")
    if mode == "eager":
        _check(xla_backend.stats.get("allreduce", 0) > 0,
               f"no XLA allreduce ran: {xla_backend.stats}")
        if size > 1:
            _alltoall_uneven(hvd, xla_backend, rank, size, dev.platform)

    _emit(f"{mode} np={size}", rank, chip=dev.id,
          loss=loss, first_loss=first_loss, checksum=after,
          compile_s=round(step_s[0], 1),
          step_ms=[round(t * 1e3, 1) for t in step_s[1:]],
          img_s_per_chip=round(args.batch / step_s[-1], 1),
          cache_dir=jax.config.jax_compilation_cache_dir,
          cache_requests=cache["requests"], cache_hits=cache["hits"],
          host_ring_bytes=host_bytes,
          xla_ops=dict(xla_backend.stats),
          host_phase_ms={k: round(v["total_ms"], 1)
                         for k, v in phase_stats.snapshot().items()},
          note="not a benchmark")
    hvd.shutdown()


def _alltoall_uneven(hvd, xla_backend, rank, size, platform):
    """``hvd.alltoall`` with uneven splits on device tensors: rank r sends
    ``(r + j) % 3 + 1`` rows of the value ``100 r + j`` to rank j."""
    import jax.numpy as jnp
    import numpy as np

    splits = [(rank + j) % 3 + 1 for j in range(size)]
    rows = np.concatenate([np.full((n, 8), 100 * rank + j, np.float32)
                           for j, n in enumerate(splits)])
    out = np.asarray(hvd.alltoall(jnp.asarray(rows), splits=splits,
                                  name="smoke.alltoall"))
    want = np.concatenate([np.full(((r + rank) % 3 + 1, 8),
                                   100 * r + rank, np.float32)
                           for r in range(size)])
    _check(out.shape == want.shape and np.array_equal(out, want),
           f"alltoall returned {out[:, 0]}, wanted {want[:, 0]}")
    _check(xla_backend.stats.get("alltoall", 0) > 0,
           f"alltoall left the XLA plane: {xla_backend.stats}")
    if platform == "tpu":
        _check(xla_backend.stats.get("alltoall_ragged", 0) > 0,
               f"ragged_all_to_all did not run: {xla_backend.stats}")


def worker_kernel(args):
    """``matmul_bn_stats`` against ``x @ w`` + fp32 statistics, values and
    gradients, at ResNet-50's first and last 1x1-conv shapes; tolerances
    are the bf16 ones of tests/test_conv_bn_kernel.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.common.compile_cache import configure_compile_cache
    from horovod_tpu.kernels import matmul_bn_stats

    configure_compile_cache()
    dev = jax.devices()[0]
    _check(dev.platform == args.platform,
           f"platform {dev.platform!r}, wanted {args.platform!r}")

    def ref(x, w):
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        return y, jnp.sum(y, axis=0), jnp.sum(y * y, axis=0)

    def bn_loss(fn):
        # BatchNorm-shaped, so the cotangents of y, s1 and s2 are all live;
        # the row pattern keeps the normalised term's gradient away from 0.
        def loss(x, w):
            y, s1, s2 = fn(x, w)
            m = y.shape[0]
            mean = s1 / m
            var = s2 / m - mean * mean
            normed = (y.astype(jnp.float32) - mean) \
                * jax.lax.rsqrt(var + 1e-5)
            pattern = (jnp.arange(m) % 7 - 3.0)[:, None]
            return jnp.mean(normed * pattern) \
                + 1e-3 * jnp.mean(s1 + s2) / m
        return jax.jit(jax.grad(loss, argnums=(0, 1)))

    def close(a, b, rtol, atol, what):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        _check(np.allclose(a, b, rtol=rtol, atol=atol),
               f"{what}: max abs err {np.abs(a - b).max()} "
               f"(max abs ref {np.abs(b).max()})")

    rng = np.random.RandomState(0)
    shapes = [(args.batch * 56 * 56, 64, 256), (args.batch * 7 * 7, 2048, 512)]
    for m, k, n in shapes:
        x = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
        w = jnp.asarray(rng.randn(k, n) / np.sqrt(k), jnp.bfloat16)
        fused = jax.jit(matmul_bn_stats)
        if dev.platform == "tpu":
            _check("tpu_custom_call" in fused.lower(x, w).as_text(),
                   "the kernel was not lowered through Mosaic")
        t0 = time.perf_counter()
        y, s1, s2 = jax.block_until_ready(fused(x, w))
        compile_s = time.perf_counter() - t0
        yr, s1r, s2r = ref(x, w)
        close(y, yr, 2e-2, 2e-1, f"y {m}x{k}x{n}")
        close(s1, s1r, 2e-2, 2.0, f"s1 {m}x{k}x{n}")
        close(s2, s2r, 2e-2, 2.0, f"s2 {m}x{k}x{n}")
        for g, gr, nm in zip(bn_loss(matmul_bn_stats)(x, w),
                             bn_loss(ref)(x, w), ("dx", "dw")):
            scale = float(jnp.max(jnp.abs(gr.astype(jnp.float32))))
            close(g, gr, 2e-2, 2e-2 * scale, f"{nm} {m}x{k}x{n}")
        _emit("kernel np=1", 0, shape=[m, k, n],
              compile_s=round(compile_s, 2), note="not a benchmark")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--worker", choices=["eager", "wfbp", "kernel"],
                   help="run one rank of one stage (what the parent "
                        "launches under hvdrun)")
    p.add_argument("--platform", default="tpu",
                   help="the platform a worker must find itself on")
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--image", type=int, default=224)
    p.add_argument("--steps", type=int, default=3)
    args = p.parse_args()
    if args.worker is None:
        return main_parent()
    if args.worker == "kernel":
        worker_kernel(args)
    else:
        worker_train(args.worker, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
