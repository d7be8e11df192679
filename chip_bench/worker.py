"""One rank of one run of one cell; ``run.py`` starts it under ``hvdrun``.

Set-up (counted as ``setup_s``): ``hvd.init()``, the plain reference on rank
0, weights and data made on the device from the seed, the step built through
the product's API, its first call (compile or cache read), a pipelined warm-up
and the correctness checks.  Then the measured window, then one JSON record
for this rank in ``--out``.

The loop (``PERF.md`` section 2): dispatch step i, then read the loss of step
i-k to the host, k being the traffic mix's ``steps_in_flight``.  The read
returns when step i-k has finished on the device, stamps its completion and
leaves the device k steps of work ahead, so the window is neither drained at
every step nor unobserved, and a pause of the host shorter than k steps costs
the device nothing, as in a training loop that logs every few steps.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import math
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Loss of the step builder under test against the plain reference's, relative.
# Both run the same bf16 model on the same data; they differ in how XLA fuses
# and orders the bf16 and fp32 roundings (one program against three) and, at
# several ranks, in the order of the gradient mean.  On the chip they agreed
# to 5.4e-5 or better in every cell (PERF.md, PR 23).  A dropped update, a sum
# in place of the mean or another rank's batch moves the third loss by 2e-3
# or more.
REFERENCE_RTOL = 3e-4
# |first loss - ln(classes)| as a share of ln(classes): fresh weights predict
# every class about alike (ResNet-50 starts at 6.9-7.2 against ln 1000 = 6.91).
FIRST_LOSS_RTOL = 0.25


def _fail(what):
    raise SystemExit(f"chip_bench worker: FAILED: {what}")


def _monitor():
    """Count this process's compilations, and those that asked the persistent
    cache and that it answered (JAX's own ``cache_misses`` counts entries
    written, which only process 0 does)."""
    import jax

    counts = {"compiles": 0, "cache_requests": 0, "cache_hits": 0}
    events = {"/jax/compilation_cache/compile_requests_use_cache":
              "cache_requests",
              "/jax/compilation_cache/cache_hits": "cache_hits"}

    def on_event(event, **_):
        if event in events:
            counts[events[event]] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compiles"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return counts


def _counters(compile_counts):
    """The program's own counters, flat, for differencing over the window."""
    from horovod_tpu.backend import xla as xla_backend
    from horovod_tpu.core import metrics
    from horovod_tpu.core.timeline import phase_stats, wire_stats

    out = {"compiles": compile_counts["compiles"],
           "host_ring_bytes": wire_stats.get("bytes_on_wire")
           + metrics.registry.get_counter("shm_bytes_total")}
    for phase, v in phase_stats.snapshot().items():
        out[f"phase_ms.{phase}"] = v["total_ms"]
        out[f"phase_count.{phase}"] = v["count"]
    for op, n in dict(xla_backend.stats).items():
        out[f"xla_ops.{op}"] = n
    return out


def _pipelined(step, n, lag, on_step=None):
    """Run ``n`` steps, reading each loss to the host ``lag`` steps after its
    dispatch.  Returns (start, stamps, losses): ``start`` is taken just before
    the first dispatch, stamp i when the loss of step i reached the host."""
    from jax.profiler import TraceAnnotation as span

    stamps, losses = [], []
    pending = collections.deque()

    def read():
        with span("loss_read"):
            losses.append(float(pending.popleft()))
        stamps.append(time.perf_counter())

    start = time.perf_counter()
    for i in range(n):
        if on_step is not None:
            on_step(i)
        pending.append(step())
        if len(pending) > lag:
            read()
    while pending:
        read()
    return start, stamps, losses


def _intervals_ms(stamps):
    """Stamp-to-stamp intervals.  The stretch from the window's start to the
    first stamp is the pipeline filling and is no interval."""
    return [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], required=True)
    p.add_argument("--out", required=True, help="directory for the records")
    p.add_argument("--t0", type=float, required=True,
                   help="time.time() at the command's start")
    p.add_argument("--platform", default="tpu",
                   help="the platform this rank must find itself on")
    p.add_argument("--keep-trace", default=None,
                   help="copy the .xplane.pb here (tools only)")
    args = p.parse_args(argv)

    sys.path.insert(0, os.path.dirname(HERE))
    from chip_bench import peaks, readers, reference, spec, steps
    from chip_bench import trace_reduce

    cell = spec.Cell(args.workload, root=os.path.dirname(HERE))
    traffic = cell.traffic

    import jax

    counts = _monitor()
    import horovod_tpu as hvd

    t_imported = time.time()
    hvd.init()
    rank, world = hvd.rank(), hvd.size()
    # The first sight of the device brings the TPU runtime up (about 9 s);
    # with several ranks jax.distributed has done that inside hvd.init().
    dev = jax.local_devices()[0]
    t_init = time.time()
    if dev.platform != args.platform:
        _fail(f"JAX found platform {dev.platform!r} ({dev.device_kind}), "
              f"this cell needs {args.platform!r}")
    if world != traffic["processes"]:
        _fail(f"{world} ranks, the traffic mix asks {traffic['processes']}")
    if jax.device_count() < cell.chips and args.platform == "tpu":
        _fail(f"{jax.device_count()} chips, the cell asks {cell.chips}")
    if world > 1:
        if jax.local_device_count() != 1:
            _fail(f"{jax.local_device_count()} local devices under binding")
        chips = hvd.allgather_object(dev.id, name="bench.chips")
        if len(set(chips)) != world:
            _fail(f"ranks share chips: {chips}")

    config = cell.config_module().Config(cell.sizes)
    tx = config.optimizer(world)
    n_ref = traffic["reference_steps"]

    # The reference first, on rank 0 alone (the other ranks build their step
    # meanwhile and wait at the first collective), and its state dropped
    # before the step under test builds its own: two copies of BERT-large's
    # weights, gradients and AdamW state do not fit one chip.
    t = time.time()
    ref_losses = reference.reference_losses(
        config, args.seed, world, n_ref, dev) if rank == 0 else None
    t_reference = time.time() - t

    t = time.time()
    put = lambda x: jax.device_put(x, dev)  # noqa: E731
    params, aux = jax.jit(config.init)(put(reference.seed_key(args.seed)))
    batch = jax.jit(config.make_batch)(
        put(reference.rank_key(args.seed, rank)))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    step, current_params = steps.build(traffic["step_builder"], config, tx,
                                       params, aux, batch)
    del params, aux
    t_build = time.time() - t

    t = time.time()
    first_loss = float(step())
    first_step_s = time.time() - t

    t = time.time()
    n_warm = traffic["warmup_steps"]
    lag = traffic["steps_in_flight"]
    _, w_stamps, w_losses = _pipelined(step, n_warm, lag)
    t_warmup = time.time() - t
    losses = [first_loss] + w_losses

    # How many steps fill --seconds: from the warm-up's own steady intervals,
    # the same on every rank.
    warm_ms = statistics.median(_intervals_ms(w_stamps)[2:])
    n_steps = max(20, round(args.seconds * 1e3 / warm_ms))
    if world > 1:
        n_steps = hvd.broadcast_object(n_steps, root_rank=0,
                                       name="bench.n_steps")

    checksum = jax.jit(lambda tree: sum(
        jax.numpy.sum(jax.numpy.abs(x.astype(jax.numpy.float32)))
        for x in jax.tree_util.tree_leaves(tree)))

    traced = None
    trace_dir = os.path.join(args.out, "trace")
    n_traced = traffic["traced_steps"]
    trace_at = min(20, n_steps // 4)

    def on_step(i):
        # Rank 0 traces its own chip for a short steady stretch; the other
        # ranks meet it at the next collective.
        nonlocal traced
        if i == trace_at:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            traced = i
        elif traced is not None and i == traced + n_traced:
            jax.profiler.stop_trace()
            traced = None

    # Host hygiene a user could apply too: no collector pause over the
    # long-lived objects of set-up, nothing printed inside the window.
    gc.collect()
    gc.freeze()
    if world > 1:
        hvd.barrier(name="bench.window")
    before = _counters(counts)
    t_window = time.time()
    start, stamps, window_losses = _pipelined(
        step, n_steps, lag, on_step if args.trace and rank == 0 else None)
    after = _counters(counts)
    if traced is not None:
        jax.profiler.stop_trace()
    gc.unfreeze()

    # ---- outside the window: the checks of `correct`, and the record ----
    deltas = {k: after[k] - before.get(k, 0) for k in after}
    seconds = stamps[-1] - start
    ivals = _intervals_ms(stamps)
    p50 = statistics.median(ivals)
    checks = {}
    checks["first_loss_near_ln_classes"] = \
        abs(first_loss - config.first_loss) < \
        FIRST_LOSS_RTOL * config.first_loss
    checks["losses_finite"] = all(
        math.isfinite(x) for x in losses + window_losses)
    checks["no_compile_in_window"] = deltas["compiles"] == 0
    if ref_losses is not None:
        checks["matches_reference"] = all(
            abs(a - b) <= REFERENCE_RTOL * abs(b)
            for a, b in zip(losses[:n_ref], ref_losses))
    final = float(checksum(current_params()))
    sums = hvd.allgather_object(final, name="bench.checksum") \
        if world > 1 else [final]
    checks["ranks_hold_same_parameters"] = \
        len(set(sums)) == 1 and math.isfinite(final)
    grad_bytes = 4 * n_params
    checks["gradients_stayed_off_host_ring"] = \
        deltas["host_ring_bytes"] < n_steps * grad_bytes / 10
    if traffic["step_builder"] == "eager":
        checks["xla_allreduce_ran"] = deltas.get("xla_ops.allreduce", 0) > 0

    memory = dev.memory_stats() or {}
    # The TPU runtime counts live arrays (`in_use`) and the scratch it holds
    # for compiled programs (`reserved`) apart; both are HBM.  Their peaks
    # need not coincide, so the sum is an upper bound of the true peak.
    peak_bytes = (memory["peak_bytes_in_use"]
                  + memory.get("peak_bytes_reserved", 0)
                  if "peak_bytes_in_use" in memory else None)
    fields = {
        "launch_s": t_imported - args.t0,
        "init_s": t_init - t_imported,
        "reference_s": t_reference,
        "build_s": t_build,
        "first_step_s": first_step_s,
        "warmup_s": t_warmup,
        "setup_s": t_window - args.t0,
        "cache_requests": counts["cache_requests"],
        "cache_hits": counts["cache_hits"],
        "compiles": counts["compiles"],
        "peak_gib": peak_bytes / 2 ** 30 if peak_bytes else None,
        "step_ms_p50": p50,
        # Linear interpolation between order statistics, as numpy has it.
        "step_ms_p95": statistics.quantiles(ivals, n=20,
                                            method="inclusive")[18],
    }
    flops_per_step = config.flops_per_sample() * config.per_chip_batch
    window = None
    if args.trace and rank == 0:
        path = trace_reduce.find_xplane(trace_dir)
        if path is not None:
            window = trace_reduce.Window.between_reads(
                trace_reduce.Trace.from_file(path, steps.SPANS))
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(path, os.path.join(args.keep_trace,
                                               cell.name + ".xplane.pb"))
    ctx = {"fields": fields, "deltas": deltas, "steps": n_steps,
           "window": window, "world": world,
           "flops_per_step": flops_per_step,
           "peak_flops": peaks.peak(dev.device_kind)
           if dev.platform == "tpu" else float("nan")}
    per_layer = {m["name"]: readers.read(cell.reader(m["name"]), ctx)
                 for m in cell.per_layer} if args.trace else {}

    stalls = [[i, x] for i, x in enumerate(ivals) if x > 1.5 * p50]
    record = {
        "rank": rank, "world": world,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count(),
                   "memory_peak_bytes": peak_bytes},
        "steps": n_steps, "samples": n_steps * config.per_chip_batch,
        "window_s": seconds, "intervals_ms": ivals,
        "flops_per_sample": config.flops_per_sample(),
        "losses": losses[:n_ref + 1], "reference_losses": ref_losses,
        "final_loss": window_losses[-1], "checksum": final,
        "failed_steps": sum(1 for x in window_losses
                            if not math.isfinite(x)),
        "checks": checks, "fields": fields, "deltas": deltas,
        "per_layer": per_layer,
        "host": {"cpu_count": os.cpu_count(),
                 "loadavg": list(os.getloadavg()),
                 "over_1p5_median": len(stalls),
                 "stalls_index_ms": stalls[:10],
                 "max_ms": max(ivals),
                 "thirds_p50_ms": [statistics.median(
                     ivals[k * len(ivals) // 3:(k + 1) * len(ivals) // 3])
                     for k in range(3)]},
    }
    if window is not None:
        record["traced"] = {
            "busy_s": window.busy_s(), "window_s": window.seconds,
            "steps": window.steps,
            "device_ops": [[n, s] for n, s in window.top_ops(10)],
            "idle_gaps": sorted(([n, s] for n, s in
                                 window.idle_by_span().items()),
                                key=lambda kv: -kv[1])[:10]}
    tmp = os.path.join(args.out, f"rank{rank}.json.tmp")
    with open(tmp, "w") as f:
        json.dump(record, f)
    os.replace(tmp, os.path.join(args.out, f"rank{rank}.json"))
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
