"""TPU process-per-chip launch model: pod-slice discovery, per-slot chip
visibility env, and the --start-timeout watchdog.

Reference role: ``runner/gloo_run.py:65-76`` per-slot env construction; on
TPU the launcher additionally carves chips into one-per-process windows
(no reference equivalent — NCCL jobs use CUDA_VISIBLE_DEVICES instead)."""

import os
import subprocess
import sys
import textwrap
import time

from horovod_tpu.runner import tpu_topology
from horovod_tpu.runner.tpu_topology import (
    discover,
    parse_accelerator_type,
    slot_tpu_env,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parse_accelerator_type():
    # v5e counts chips directly; v4 counts TensorCores (2/chip).
    assert parse_accelerator_type("v5litepod-16") == (16, 4)
    assert parse_accelerator_type("v5litepod-4") == (4, 4)
    assert parse_accelerator_type("v4-32") == (16, 4)
    assert parse_accelerator_type("v3-8") == (4, 4)
    assert parse_accelerator_type("gpu-8") is None
    assert parse_accelerator_type("nonsense") is None


def test_discover_pod_slice(monkeypatch):
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "t1w-0,t1w-1,t1w-2,t1w-3")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-16")
    assert discover() == "t1w-0:4,t1w-1:4,t1w-2:4,t1w-3:4"


def test_discover_single_host_slice(monkeypatch):
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-8")
    assert discover() == "localhost:8"


def test_discover_absent(monkeypatch):
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    assert discover() is None


def test_slot_tpu_env_disjoint_chips():
    """Two workers on one host must see disjoint devices (VERDICT #44)."""
    envs = [slot_tpu_env(i, i, [("localhost", 4)]) for i in range(4)]
    chips = {e["TPU_VISIBLE_CHIPS"] for e in envs}
    assert chips == {"0", "1", "2", "3"}
    ports = {e["TPU_PROCESS_PORT"] for e in envs}
    assert len(ports) == 4
    # every process agrees on the tiling and the address list
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    assert len({e["TPU_PROCESS_ADDRESSES"] for e in envs}) == 1
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)


def test_slot_tpu_env_multi_host_slice_wide():
    """The process tiling must cover the whole slice, not one host — a
    per-host grid would stitch each host into an independent slice."""
    hosts = [("w0", 4), ("w1", 4), ("w2", 4), ("w3", 4)]
    # rank 5 = host w1, local_rank 1, 4 chips/host
    env = slot_tpu_env(5, 1, hosts)
    assert env["TPU_PROCESS_BOUNDS"] == "4,4,1"          # 16 processes
    assert env["CLOUD_TPU_TASK_ID"] == "5"               # global rank
    addrs = env["TPU_PROCESS_ADDRESSES"].split(",")
    assert len(addrs) == 16
    assert addrs[0] == "w0:8476" and addrs[4] == "w1:8476"
    assert env["TPU_PROCESS_PORT"] == "8477"


def test_slot_tpu_env_partial_last_host_consistent():
    """-np that doesn't fill the last host: every rank must still derive
    the identical tiling (6 procs on 2x4-chip hosts → 2,3,1 and 6 addrs)."""
    hosts = [("w0", 4), ("w1", 2)]
    envs = [slot_tpu_env(r, lr, hosts)
            for r, lr in [(0, 0), (3, 3), (4, 0), (5, 1)]]
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,3,1"}
    assert {len(e["TPU_PROCESS_ADDRESSES"].split(",")) for e in envs} == {6}


def test_host_slots_of():
    from horovod_tpu.runner.hosts import get_host_assignments, parse_hosts
    from horovod_tpu.runner.launch import host_slots_of

    slots = get_host_assignments(parse_hosts("a:4,b:4"), 6)
    assert host_slots_of(slots) == [("a", 4), ("b", 2)]


def test_process_bounds_shapes():
    assert tpu_topology._process_bounds(1) == "1,1,1"
    assert tpu_topology._process_bounds(2) == "1,2,1"
    assert tpu_topology._process_bounds(4) == "2,2,1"
    assert tpu_topology._process_bounds(8) == "2,4,1"


def test_hvdrun_exports_chip_binding(tmp_path):
    """hvdrun on a (simulated) TPU VM gives each slot its own chip and,
    with no --data-plane, the strict XLA plane with its coordinator:
    gradients that live on a chip are not reduced over the host ring."""
    script = tmp_path / "show.py"
    script.write_text(textwrap.dedent("""
        import os
        print("CHIP", os.environ["HOROVOD_RANK"],
              os.environ.get("TPU_VISIBLE_CHIPS"), flush=True)
        print("PLANE", os.environ.get("HOROVOD_DATA_PLANE"),
              bool(os.environ.get("HOROVOD_JAX_COORDINATOR")), flush=True)
    """))
    env = dict(os.environ, TPU_ACCELERATOR_TYPE="v5litepod-4")
    env.pop("TPU_WORKER_HOSTNAMES", None)
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "-H", "localhost:2", sys.executable, str(script)],
        cwd=REPO_ROOT, text=True, capture_output=True, timeout=60, env=env)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "CHIP 0 0" in proc.stdout and "CHIP 1 1" in proc.stdout
    assert proc.stdout.count("PLANE xla True") == 2
    # an explicit plane wins over the default
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "-H", "localhost:2", "--data-plane", "tcp",
         sys.executable, str(script)],
        cwd=REPO_ROOT, text=True, capture_output=True, timeout=60, env=env)
    assert proc.stdout.count("PLANE tcp False") == 2, proc.stdout


def test_hvdrun_no_chip_binding_off_tpu(tmp_path):
    script = tmp_path / "show.py"
    script.write_text(
        "import os; print('CHIP', repr(os.environ.get('TPU_VISIBLE_CHIPS')),"
        " repr(os.environ.get('HOROVOD_DATA_PLANE')))")
    env = dict(os.environ)
    env.pop("TPU_ACCELERATOR_TYPE", None)
    env.pop("TPU_WORKER_HOSTNAMES", None)
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "1",
         sys.executable, str(script)],
        cwd=REPO_ROOT, text=True, capture_output=True, timeout=60, env=env)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "CHIP None None" in proc.stdout


def test_start_timeout_aborts_unstarted_job(tmp_path):
    """A worker that never calls hvd.init() must fail the job at
    --start-timeout, not hang forever (VERDICT: --start-timeout was parsed
    and never used)."""
    script = tmp_path / "stall.py"
    script.write_text("import time; time.sleep(60)\n")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "--start-timeout", "3", sys.executable, str(script)],
        cwd=REPO_ROOT, text=True, capture_output=True, timeout=45)
    elapsed = time.monotonic() - t0
    assert proc.returncode != 0
    assert "failed to start" in proc.stderr
    assert elapsed < 30, elapsed
