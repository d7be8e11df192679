"""The harness on the CPU: the command refuses to measure there, the worker's
loop yields well-formed records at a tiny size, and no module of the
benchmark reaches for a TPU while it is imported."""

import os
import subprocess
import sys

import pytest
import rehearse

from chip_bench import spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_command_prints_no_result_without_a_tpu(cell):
    command = spec.benchmark()["command"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        command + ["--workload", cell, "--seed", str(2 ** 31 + 3),
                   "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "{" not in done.stdout, done.stdout
    assert "needs 'tpu'" in done.stderr


def test_command_refuses_a_directory_without_the_program(tmp_path):
    root = rehearse.make_root(tmp_path, [])
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, os.path.join(root, "chip_bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=120)
    assert done.returncode != 0 and "{" not in done.stdout
    assert "horovod_tpu" in done.stderr


NP2 = {"traffic/eager-np2.json": {
    "name": "eager-np2", "step_builder": "eager", "processes": 2,
    "warmup_steps": 12, "reference_steps": 3, "traced_steps": 14,
    "steps_in_flight": 4}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.make_root(
        tmp_path_factory.mktemp("bench"),
        [("tiny-eager", "tiny-resnet", "eager", 1),
         ("tiny-wfbp", "tiny-resnet", "wfbp", 1),
         ("tiny-bert-wfbp", "tiny-bert", "wfbp", 1),
         ("tiny-eager-np2", "tiny-resnet", "eager-np2", 2)], files=NP2)


@pytest.mark.parametrize("cell,processes,trace", [
    ("tiny-eager", 1, 1), ("tiny-wfbp", 1, 0), ("tiny-bert-wfbp", 1, 1),
    ("tiny-eager-np2", 2, 1)])
def test_worker_loop_yields_one_record_per_rank(root, cell, processes, trace):
    records = rehearse.run_worker(root, cell, processes, trace=trace)
    assert [r["rank"] for r in records] == list(range(processes))
    for r in records:
        assert r["world"] == processes
        assert r["device"]["platform"] == "cpu"      # and so not a result
        assert r["steps"] >= 20
        assert r["samples"] == r["steps"] * 2
        # One completion stamp per step: steps - 1 intervals between them.
        assert len(r["intervals_ms"]) == r["steps"] - 1
        assert all(x > 0 for x in r["intervals_ms"])
        assert r["window_s"] >= sum(r["intervals_ms"]) / 1e3
        assert r["fields"]["compiles"] > 0
        assert r["deltas"]["compiles"] == 0
        assert all(r["checks"].values()), r["checks"]
        assert r["failed_steps"] == 0
        assert r["fields"]["setup_s"] > r["fields"]["first_step_s"] > 0
    r0 = records[0]
    assert "matches_reference" in r0["checks"]
    assert len(r0["reference_losses"]) == 3
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"],
                                             rel=3e-4)
    assert len({r["checksum"] for r in records}) == 1
    if "eager" in cell:
        assert r0["deltas"]["xla_ops.allreduce"] == r0["steps"]
        assert "xla_allreduce_ran" in r0["checks"]
    if trace:
        # The host spans are read back from the trace even where the CPU has
        # no device plane; the device metrics then find nothing and are left
        # out.
        assert r0["traced"]["steps"] == 12
        assert r0["per_layer"]["device_idle_pct"] is None
        assert r0["per_layer"]["step_ms_p50"] > 0
        if "eager" in cell:
            assert r0["per_layer"]["xla_collectives_step"] == 1.0
            assert r0["deltas"]["phase_ms.negotiate"] > 0
        else:
            assert r0["per_layer"]["xla_collectives_step"] is None
            assert "phase_ms.negotiate" not in r0["deltas"]
    else:
        assert r0["per_layer"] == {}


def test_result_line_is_the_contracts(root):
    """The parent's assembly, fed CPU records with the device renamed: the
    keys the driver reads and no others."""
    sys.path.insert(0, spec.ROOT)
    from chip_bench import run

    records = rehearse.run_worker(root, "tiny-eager-np2", 2, trace=0)
    for r in records:
        r["device"].update(kind="TPU v5 lite", memory_peak_bytes=123)
    cell = spec.Cell("tiny-eager-np2", root=root)
    line, failed = run.result_line(cell, records, trace=0)
    assert failed == []
    assert sorted(line) == ["attempted", "correct", "device", "failed",
                            "metrics"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == records[0]["steps"]
    # A throw-away cell is on no metric's list, so it reports the metrics
    # every cell reports.
    assert sorted(line["metrics"]) == ["samples_per_s_chip", "setup_s"]
    listed = spec.Cell("resnet50-wfbp-1chip")
    assert [m["name"] for m in listed.end_to_end] == [
        "samples_per_s_chip", "mfu_pct", "step_ms_p95", "setup_s"]
    assert "step_ms_p95.observed" not in [
        m["name"] for m in listed.per_layer]
    rate = line["metrics"]["samples_per_s_chip"]["value"]
    slowest = max(r["window_s"] for r in records)
    assert rate == pytest.approx(records[0]["samples"] / slowest)
    cell.end_to_end = listed.end_to_end
    line, _ = run.result_line(cell, records, trace=0)
    assert line["metrics"]["mfu_pct"]["value"] == pytest.approx(
        100 * rate * records[0]["flops_per_sample"] / 197e12)
    assert line["metrics"]["step_ms_p95"] == {
        "value": records[0]["fields"]["step_ms_p95"], "unit": "ms"}
    assert line["device"] == {"platform": "cpu", "kind": "TPU v5 lite",
                              "count": 2, "memory_peak_bytes": 123}
    records[1]["checks"]["losses_finite"] = False
    line, failed = run.result_line(cell, records, trace=0)
    assert line["correct"] is False and failed == ["rank1.losses_finite"]


def test_no_module_reaches_for_a_tpu_at_import():
    code = """
import glob, importlib.util, os, sys
sys.path.insert(0, %r)
from chip_bench import run, spec, readers, peaks
assert 'jax' not in sys.modules, 'the parent imports jax'
from chip_bench import worker, steps, reference, trace_reduce
for path in glob.glob(os.path.join(spec.BENCH_DIR, 'configs', '*.py')) + \\
        glob.glob(os.path.join(spec.BENCH_DIR, 'tools', '*.py')):
    s = importlib.util.spec_from_file_location('m', path)
    s.loader.exec_module(importlib.util.module_from_spec(s))
import jax
from jax._src import xla_bridge
assert not xla_bridge._backends, 'a backend was initialised at import'
assert 'libtpu' not in open('/proc/self/maps').read(), 'libtpu was loaded'
print('clean')
""" % spec.ROOT
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0 and "clean" in done.stdout, done.stderr
