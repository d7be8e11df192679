"""Run one cell of ``BENCHMARK.json`` and print the contract's result line.

    python3 chip_bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX: a chip belongs to one process at a time.  It
starts ``hvdrun -np <processes>`` on ``worker.py``, gathers one record per
rank, and prints the result as the last line of its output, after a line each
for the set-up breakdown and for the window's other estimators.  It exits
non-zero, printing no result, when the workers found no TPU or fewer chips
than the cell asks, when any rank failed, or when the program is not beside
it.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chip_bench import peaks, readers, spec  # noqa: E402

# The contract allows a cell's first run in a checkout 1200 s.
_LIMIT_S = 1150


def _run(cmd, env, timeout):
    """Run ``cmd`` in its own process group with its output passed through;
    the whole group is killed at ``timeout`` and on any exit from here, so no
    worker outlives the command."""
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, start_new_session=True)

    def kill():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(timeout, kill)
    timer.start()
    try:
        return proc.wait()
    finally:
        timer.cancel()
        kill()
        proc.wait()


def result_line(cell, records, trace):
    """(the contract's result, the names of the checks that failed) from the
    ranks' records, given in rank order."""
    r0 = records[0]
    world = len(records)
    # Every rank trains `samples` in lockstep; the slowest rank's window is
    # the time they all took.
    seconds = max(r["window_s"] for r in records)
    rate = sum(r["samples"] for r in records) / seconds / world
    peak = peaks.peak(r0["device"]["kind"])
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = readers.across_ranks(
                cell.reader(m["name"]),
                [r["per_layer"].get(m["name"]) for r in records])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {
            "samples_per_s_chip": rate,
            "mfu_pct": 100.0 * rate * r0["flops_per_sample"] / peak,
            "step_ms_p95": r0["fields"]["step_ms_p95"],
            "setup_s": max(r["fields"]["setup_s"] for r in records),
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    checks = {f"rank{r['rank']}.{k}": v
              for r in records for k, v in r["checks"].items()}
    memory = [r["device"]["memory_peak_bytes"] for r in records]
    device = {"platform": r0["device"]["platform"],
              "kind": r0["device"]["kind"], "count": r0["device"]["count"],
              "memory_peak_bytes": max(memory) if all(memory) else None}
    out = {"correct": all(checks.values()),
           "attempted": r0["steps"],
           "failed": max(r["failed_steps"] for r in records),
           "metrics": metrics, "device": device}
    if trace and "traced" in r0:
        device["busy_s"] = r0["traced"]["busy_s"]
        device["window_s"] = r0["traced"]["window_s"]
        out["breakdown"] = {"device_ops": r0["traced"]["device_ops"],
                            "idle_gaps": r0["traced"]["idle_gaps"]}
    return out, sorted(k for k, v in checks.items() if not v)


def other_lines(records):
    """What the result line leaves out: where set-up went, and the window's
    other estimators (``tools/aa_study.py`` reads these)."""
    r0 = records[0]
    setup = {k: max(r["fields"][k] for r in records)
             for k in ("launch_s", "init_s", "reference_s", "build_s",
                       "first_step_s", "warmup_s", "setup_s")}
    setup["compiles"] = [r["fields"]["compiles"] for r in records]
    setup["cache_hits"] = [r["fields"]["cache_hits"] for r in records]
    batch = r0["samples"] // r0["steps"]
    p50 = r0["fields"]["step_ms_p50"]
    window = {"steps": r0["steps"], "window_s": r0["window_s"],
              "whole_window_rate": r0["samples"] / r0["window_s"],
              "median_interval_rate": batch / p50 * 1e3,
              "p50_ms": p50, "p95_ms": r0["fields"]["step_ms_p95"],
              **r0["host"],
              "losses": r0["losses"], "reference": r0["reference_losses"],
              "final_loss": r0["final_loss"], "checksum": r0["checksum"]}
    return setup, window


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], required=True)
    p.add_argument("--keep-trace", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    cell = spec.Cell(args.workload)
    if importlib.util.find_spec("horovod_tpu") is None:
        sys.exit("chip_bench: the program (horovod_tpu) is not beside "
                 "BENCHMARK.json or on the path")
    n = cell.traffic["processes"]
    out_dir = tempfile.mkdtemp(prefix="chip_bench-")
    env = dict(os.environ)
    env["PYTHONHASHSEED"] = str(args.seed % 2 ** 32)
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [x for x in env.get("PYTHONPATH", "").split(os.pathsep)
                  if x])
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", str(n),
           "--data-plane", "xla",
           sys.executable, os.path.join(HERE, "worker.py"),
           "--workload", cell.name, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace),
           "--out", out_dir, "--t0", repr(T0)]
    if args.keep_trace:
        cmd += ["--keep-trace", args.keep_trace]
    try:
        rc = _run(cmd, env, _LIMIT_S)
        if rc != 0:
            sys.exit(f"chip_bench: the workers of {cell.name} failed "
                     f"(exit {rc}); no result")
        records = []
        for rank in range(n):
            with open(os.path.join(out_dir, f"rank{rank}.json")) as f:
                records.append(json.load(f))
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)

    device = records[0]["device"]
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        sys.exit(f"chip_bench: {cell.name} asks {cell.chips} TPU chip(s); "
                 f"the workers found {device}")
    setup, window = other_lines(records)
    result, window["checks_failed"] = result_line(cell, records, args.trace)
    print("CHIP_BENCH setup " + json.dumps(setup), flush=True)
    print("CHIP_BENCH window " + json.dumps(window), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
