"""Rehearse the benchmark on the CPU at a tiny size, from a throw-away copy.

``make_root`` copies ``BENCHMARK.json`` and the benchmark's directory into a
temporary directory and drops tiny configurations, cells and whatever else a
test names into the copy, touching no file that is there; ``run_worker``
drives ``worker.py`` under ``hvdrun`` on the CPU plane and returns the ranks'
records.  Nothing here is a measurement.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

TINY = {
    "tiny-resnet": {
        "module": "resnet50", "stage_sizes": [1, 1, 1, 1], "num_filters": 8,
        "bottleneck_expansion": 4, "num_classes": 10, "image_size": 32,
        "image_channels": 3, "per_chip_batch": 2, "sgd_learning_rate": 0.01,
        "sgd_momentum": 0.9},
    "tiny-bert": {
        "module": "bert-large", "num_hidden_layers": 2, "hidden_size": 32,
        "num_attention_heads": 4, "intermediate_size": 64, "vocab_size": 128,
        "max_position_embeddings": 16, "sequence_length": 16,
        "per_chip_batch": 2, "adamw_learning_rate": 1e-4},
}


def make_root(tmp, cells, files=None, per_layer=None):
    """A copy of the benchmark under ``tmp`` with ``cells`` ([(name, config,
    traffic, chips)], configurations from ``TINY``) appended to its
    ``workloads``, ``files`` ({path relative to the benchmark's directory:
    JSON value or text}) dropped in, and ``per_layer`` entries appended."""
    root = os.path.join(str(tmp), "root")
    os.makedirs(root)
    shutil.copytree(BENCH, os.path.join(root, "chip_bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = dict(files or {})
    for name, config, traffic, chips in cells:
        if config in TINY:
            files.setdefault(f"configs/{config}.json", TINY[config])
        if config not in [c["name"] for c in bench["configs"]]:
            bench["configs"].append({
                "name": config, "source": "rehearsal",
                "file": f"chip_bench/configs/{config}.json", "reduced": [],
                "why": "rehearsal"})
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "rehearsal"})
    bench["per_layer"] += per_layer or []
    for rel, value in files.items():
        path = os.path.join(root, "chip_bench", rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            if isinstance(value, str):
                f.write(value)
            else:
                json.dump(value, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def run_worker(root, workload, processes, trace=0, seconds=0.5, seed=2 ** 31 + 7,
               timeout=600):
    out = os.path.join(root, "records-" + workload + f"-{trace}")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "PYTHONPATH": os.pathsep.join([root, REPO])})
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", str(processes), "--data-plane", "xla",
           sys.executable, os.path.join(root, "chip_bench", "worker.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace), "--out", out,
           "--t0", repr(time.time()), "--platform", "cpu"]
    done = subprocess.run(cmd, cwd=root, env=env, timeout=timeout,
                          capture_output=True, text=True)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    records = []
    for rank in range(processes):
        with open(os.path.join(out, f"rank{rank}.json")) as f:
            records.append(json.load(f))
    return records
