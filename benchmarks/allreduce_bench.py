"""Eager allreduce micro-benchmark: bytes/sec across payload sizes and
world sizes (BASELINE.md metric #2 — allreduce scaling efficiency — had no
harness at all in round 1; reference recipe: ``docs/benchmarks.rst:16-64``).

Spawns real worker processes per world size (the same runtime path as
``hvdrun``), times a fixed number of eager ``hvd.allreduce`` rounds per
payload, and reports:

- ``busbw``: algorithm bandwidth ``2·(N−1)/N · bytes / time`` (the ring's
  wire traffic, comparable across world sizes — NCCL-tests convention),
  in GB/s and MB/s;
- ``scaling_efficiency``: busbw at N ranks / busbw at 2 ranks, per size.

Measurement discipline for this box (±20% run-to-run noise): every
reported time is the MEDIAN of ``--repeats`` samples, and when two
variants are compared (``--crc-sweep``: HOROVOD_WIRE_CRC on vs off;
``--segment-sweep``: HOROVOD_RING_SEGMENT_BYTES values) the samples are
INTERLEAVED — A B C, A B C, ... — so slow drift of the shared host hits
every variant equally instead of biasing whichever ran last.

Modes::

    python benchmarks/allreduce_bench.py                  # size × np grid
    python benchmarks/allreduce_bench.py --crc-sweep      # CRC on/off ratio
    python benchmarks/allreduce_bench.py --segment-sweep 65536 262144 ...
                                                          # pipeline knob sweep
    python benchmarks/allreduce_bench.py --compression-sweep
                                          # none/fp16/bf16 × CRC on/off

``--out FILE`` writes the result records as a JSON artifact (the segment
sweep's canonical home is ``benchmarks/results/ring_segment_sweep.json``).

On this CI image every rank is a localhost process over the TCP data
plane, so this measures the framework's own overhead curve (negotiation,
fusion, framing, the segment pipeline) rather than ICI — the TPU device
plane's collectives are XLA's own.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _worker(size_bytes: int, rounds: int) -> float:
    import time

    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    n = size_bytes // 4
    x = np.ones(n, np.float32) * (hvd.rank() + 1)
    # warmup: negotiation + cache line for this named tensor
    for i in range(3):
        hvd.allreduce(x, op=hvd.Sum, name=f"warm.{size_bytes}")
    hvd.barrier()
    t0 = time.perf_counter()
    for i in range(rounds):
        out = hvd.allreduce(x, op=hvd.Sum, name=f"bench.{size_bytes}")
    np.asarray(out)
    dt = time.perf_counter() - t0
    hvd.barrier()
    hvd.shutdown()
    return dt / rounds


def _measure(nbytes: int, np_: int, rounds: int, extra_env=None) -> float:
    """One sample: slowest-rank per-step seconds for (payload, world)."""
    import horovod_tpu.runner as runner

    use_env = {"JAX_PLATFORMS": "cpu"}
    if extra_env:
        use_env.update(extra_env)
    per_rank = runner.run(_worker, args=(nbytes, rounds),
                          np=np_, timeout=600, use_env=use_env)
    return max(per_rank)  # slowest rank bounds the collective


def _interleaved_medians(variants, repeats: int, nbytes: int, np_: int,
                         rounds: int):
    """Median step time per variant, sampled A B C, A B C, ... so host
    drift cannot bias one variant (the box's bench-noise discipline)."""
    samples = {key: [] for key, _ in variants}
    for _ in range(repeats):
        for key, env in variants:
            samples[key].append(_measure(nbytes, np_, rounds, env))
    return {key: statistics.median(vals) for key, vals in samples.items()}, \
        samples


def _record(nbytes: int, np_: int, step_s: float, base_busbw=None) -> dict:
    busbw = 2 * (np_ - 1) / np_ * nbytes / step_s
    rec = {
        "metric": "eager_allreduce_busbw",
        "payload_bytes": nbytes,
        "world_size": np_,
        "step_ms": round(step_s * 1e3, 3),
        "busbw_GBps": round(busbw / 1e9, 3),
        "busbw_MBps": round(busbw / 1e6, 1),
        "goodput_MBps": round(nbytes / step_s / 1e6, 1),
        # N workers timeshare this host's cores AND its loopback: when
        # world_size >> host_cpus the efficiency curve measures the box,
        # not the framework.
        "host_cpus": os.cpu_count(),
    }
    if base_busbw:
        rec["scaling_efficiency"] = round(busbw / base_busbw, 3)
    return rec


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--sizes", type=int, nargs="+",
                   default=[1 << 16, 1 << 20, 1 << 24, 1 << 26],
                   help="payload bytes per allreduce")
    p.add_argument("--world-sizes", type=int, nargs="+", default=[2, 4, 8])
    p.add_argument("--rounds", type=int, default=10)
    p.add_argument("--repeats", type=int, default=3,
                   help="interleaved samples per config; medians reported")
    p.add_argument("--crc-sweep", action="store_true",
                   help="run every config with HOROVOD_WIRE_CRC on AND "
                        "off (interleaved) and report the overhead ratio")
    p.add_argument("--segment-sweep", type=int, nargs="*", default=None,
                   help="sweep HOROVOD_RING_SEGMENT_BYTES over these "
                        "values (interleaved) at --sizes[0] per world "
                        "size; 0 means chunk-sized (pipeline off)")
    p.add_argument("--metrics-sweep", action="store_true",
                   help="run --sizes[0] with HOROVOD_METRICS on AND off "
                        "(interleaved) and report the overhead ratio — "
                        "the observability plane's ±10%% guard "
                        "(docs/observability.md)")
    p.add_argument("--compression-sweep", action="store_true",
                   help="sweep HOROVOD_WIRE_COMPRESSION none/fp16/bf16 × "
                        "HOROVOD_WIRE_CRC on/off (interleaved) and report "
                        "per-variant step time + speedup vs uncompressed")
    p.add_argument("--transport-sweep", action="store_true",
                   help="sweep HOROVOD_TRANSPORT shm/tcp/auto "
                        "(interleaved) per config and report per-variant "
                        "step time + shm speedup over loopback TCP")
    p.add_argument("--out", type=str, default=None,
                   help="write result records to this JSON file")
    args = p.parse_args()

    results = []

    if args.segment_sweep is not None:
        seg_values = args.segment_sweep or [
            1 << 14, 1 << 16, 1 << 18, 1 << 20, 0]
        nbytes = args.sizes[0]
        for np_ in args.world_sizes:
            variants = []
            for seg in seg_values:
                # 0 → a segment at least the whole chunk: pipeline off.
                eff = seg if seg > 0 else max(nbytes, 1)
                variants.append(
                    (seg, {"HOROVOD_RING_SEGMENT_BYTES": str(eff)}))
            medians, samples = _interleaved_medians(
                variants, args.repeats, nbytes, np_, args.rounds)
            for seg, _ in variants:
                rec = _record(nbytes, np_, medians[seg])
                rec.update({
                    "metric": "ring_segment_sweep",
                    "segment_bytes": seg,
                    "samples_ms": [round(s * 1e3, 3)
                                   for s in samples[seg]],
                    "repeats": args.repeats,
                })
                results.append(rec)
                print(json.dumps(rec), flush=True)
    elif args.metrics_sweep:
        nbytes = args.sizes[0]
        for np_ in args.world_sizes:
            variants = [("on", {"HOROVOD_METRICS": "1"}),
                        ("off", {"HOROVOD_METRICS": "0"})]
            medians, samples = _interleaved_medians(
                variants, args.repeats, nbytes, np_, args.rounds)
            rec = _record(nbytes, np_, medians["on"])
            rec.update({
                "metric": "eager_allreduce_metrics_overhead",
                "step_ms_metrics_on": round(medians["on"] * 1e3, 3),
                "step_ms_metrics_off": round(medians["off"] * 1e3, 3),
                "metrics_on_off_ratio": round(
                    medians["on"] / medians["off"], 3),
                "samples_ms": {k: [round(s * 1e3, 3) for s in v]
                               for k, v in samples.items()},
                "repeats": args.repeats,
            })
            results.append(rec)
            print(json.dumps(rec), flush=True)
    elif args.compression_sweep:
        try:
            import ml_dtypes  # noqa: F401
            comp_modes = ["none", "fp16", "bf16"]
        except ImportError:
            comp_modes = ["none", "fp16"]
        # Lossy codecs ride the same sweep: on a loopback box the win is
        # bytes, not wall-clock (the A/B harness gives the verdict); the
        # sweep records both so the scaling model can project wire-bound
        # topologies from measured numbers.
        comp_modes += ["int8", "onebit", "topk10"]
        for nbytes in args.sizes:
            for np_ in args.world_sizes:
                variants = [
                    (f"{mode}/crc-{crc}",
                     {"HOROVOD_WIRE_COMPRESSION": mode,
                      "HOROVOD_WIRE_CRC": "1" if crc == "on" else "0"})
                    for mode in comp_modes
                    for crc in ("on", "off")
                ]
                medians, samples = _interleaved_medians(
                    variants, args.repeats, nbytes, np_, args.rounds)
                base = medians["none/crc-on"]
                for key, _ in variants:
                    mode, crc = key.split("/crc-")
                    rec = _record(nbytes, np_, medians[key])
                    rec.update({
                        "metric": "ring_compression_sweep",
                        "compression": mode,
                        "wire_crc": crc,
                        "speedup_vs_none_crc_on": round(
                            base / medians[key], 3),
                        "samples_ms": [round(s * 1e3, 3)
                                       for s in samples[key]],
                        "repeats": args.repeats,
                    })
                    results.append(rec)
                    print(json.dumps(rec), flush=True)
    elif args.transport_sweep:
        for nbytes in args.sizes:
            for np_ in args.world_sizes:
                variants = [("shm", {"HOROVOD_TRANSPORT": "shm"}),
                            ("tcp", {"HOROVOD_TRANSPORT": "tcp"}),
                            ("auto", {"HOROVOD_TRANSPORT": "auto"})]
                medians, samples = _interleaved_medians(
                    variants, args.repeats, nbytes, np_, args.rounds)
                rec = _record(nbytes, np_, medians["shm"])
                rec.update({
                    "metric": "ring_transport_sweep",
                    "step_ms_shm": round(medians["shm"] * 1e3, 3),
                    "step_ms_tcp": round(medians["tcp"] * 1e3, 3),
                    "step_ms_auto": round(medians["auto"] * 1e3, 3),
                    "shm_speedup_vs_tcp": round(
                        medians["tcp"] / medians["shm"], 3),
                    "samples_ms": {k: [round(s * 1e3, 3) for s in v]
                                   for k, v in samples.items()},
                    "repeats": args.repeats,
                })
                results.append(rec)
                print(json.dumps(rec), flush=True)
    elif args.crc_sweep:
        for nbytes in args.sizes:
            for np_ in args.world_sizes:
                variants = [("on", {"HOROVOD_WIRE_CRC": "1"}),
                            ("off", {"HOROVOD_WIRE_CRC": "0"})]
                medians, samples = _interleaved_medians(
                    variants, args.repeats, nbytes, np_, args.rounds)
                rec = _record(nbytes, np_, medians["on"])
                rec.update({
                    "metric": "eager_allreduce_crc_overhead",
                    "step_ms_crc_on": round(medians["on"] * 1e3, 3),
                    "step_ms_crc_off": round(medians["off"] * 1e3, 3),
                    "crc_on_off_ratio": round(
                        medians["on"] / medians["off"], 3),
                    "samples_ms": {k: [round(s * 1e3, 3) for s in v]
                                   for k, v in samples.items()},
                    "repeats": args.repeats,
                })
                results.append(rec)
                print(json.dumps(rec), flush=True)
    else:
        for nbytes in args.sizes:
            base_busbw = None
            for np_ in args.world_sizes:
                medians, samples = _interleaved_medians(
                    [("t", None)], args.repeats, nbytes, np_, args.rounds)
                rec = _record(nbytes, np_, medians["t"], base_busbw)
                rec["samples_ms"] = [round(s * 1e3, 3)
                                     for s in samples["t"]]
                if base_busbw is None:
                    base_busbw = 2 * (np_ - 1) / np_ * nbytes / medians["t"]
                    rec["scaling_efficiency"] = 1.0
                results.append(rec)
                print(json.dumps(rec), flush=True)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
