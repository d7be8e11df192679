"""Run one cell's command several times in a row in this checkout and print,
per run and per set, what the bounds are worked out from.

    python3 chip_bench/tools/aa_study.py --workload resnet50-eager-1chip \
        --sets 2 --runs 6 --seconds 30 [--first] [--traced]

``--first`` makes one extra run first and sets it aside (it compiles);
``--traced`` adds one ``--trace 1`` run at the end.  Run r of every set uses
seed ``seed0 + r``, so the sets share their seeds.  A set's spread is the
distance between its first and third quartile (``statistics.quantiles(v,
n=4)``) as a share of its median.  Everything printed is also written to
``chiprun_out/aa_<workload>.json``.  The command is the one ``BENCHMARK.json``
names; this tool only repeats it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_once(command, workload, seed, seconds, trace, keep_trace=None):
    cmd = command + ["--workload", workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(trace)]
    if keep_trace:
        cmd += ["--keep-trace", keep_trace]
    t0 = time.time()
    done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.time() - t0
    lines = done.stdout.strip().splitlines()
    if done.returncode != 0 or not lines:
        sys.stderr.write(done.stdout[-4000:] + done.stderr[-6000:])
        raise SystemExit(f"aa_study: run failed (exit {done.returncode})")
    extra = {ln.split()[1]: json.loads(ln.split(" ", 2)[2])
             for ln in lines if ln.startswith("CHIP_BENCH ")}
    return {"seed": seed, "trace": trace, "wall_s": wall,
            "result": json.loads(lines[-1]), **extra}


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def describe(run):
    r, w, s = run["result"], run["window"], run["setup"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    return {"seed": run["seed"], "correct": r["correct"],
            "failed_checks": w["checks_failed"], **m,
            "whole_window_rate": w["whole_window_rate"],
            "median_interval_rate": w["median_interval_rate"],
            "p50_ms": w["p50_ms"], "p95_ms": w["p95_ms"],
            "max_ms": w["max_ms"], "over_1p5_median": w["over_1p5_median"],
            "thirds_p50_ms": w["thirds_p50_ms"],
            "stalls_index_ms": w["stalls_index_ms"],
            "steps": w["steps"], "cpu_count": w["cpu_count"],
            "loadavg1": w["loadavg"][0], "wall_s": run["wall_s"],
            "setup": {k: (round(v, 2) if isinstance(v, float) else v)
                      for k, v in s.items()},
            "memory_peak_bytes": r["device"]["memory_peak_bytes"]}


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--sets", type=int, default=1)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--seed0", type=int, default=2 ** 31 + 1000)
    p.add_argument("--first", action="store_true")
    p.add_argument("--traced", action="store_true")
    args = p.parse_args()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    out = {"workload": args.workload, "seconds": seconds, "sets": []}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)

    def show(tag, run):
        d = describe(run)
        print(f"AA {args.workload} {tag} " + json.dumps(d), flush=True)
        return d

    if args.first:
        out["first"] = show("first", run_once(
            bench["command"], args.workload, args.seed0 - 1, seconds, 0))
    keys = ("samples_per_s_chip", "mfu_pct", "step_ms_p95", "setup_s",
            "whole_window_rate", "median_interval_rate", "p50_ms", "max_ms")
    for s in range(args.sets):
        runs = [show(f"set{s}.run{r}", run_once(
            bench["command"], args.workload, args.seed0 + r, seconds, 0))
            for r in range(args.runs)]
        stats = {}
        for k in keys:
            v = [r[k] for r in runs if k in r]
            if len(v) >= 2:
                stats[k] = {"median": statistics.median(v),
                            "min": min(v), "max": max(v),
                            "spread": spread(v)}
        print(f"AA {args.workload} set{s} " + json.dumps(stats), flush=True)
        out["sets"].append({"runs": runs, "stats": stats})
    if args.traced:
        run = run_once(bench["command"], args.workload, args.seed0, seconds,
                       1, keep_trace=os.path.join(ROOT, "chiprun_out",
                                                  "traces"))
        out["traced"] = {"result": run["result"], "setup": run["setup"],
                         "window": run["window"]}
        print(f"AA {args.workload} traced " + json.dumps(run["result"]),
              flush=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"aa_{args.workload}.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
