"""The chip entry points fail without a chip and stay off the chip's way.

``bench.py`` and ``chip_smoke.py`` exist to say something about a TPU.
On any other platform they must exit non-zero and print no metric or
pass line — a CPU number under a chip metric's name is worse than none.
The rest pins what the chip run depends on: an unknown ``device_kind`` is
an error, the compile cache can be placed from outside and is otherwise
one fixed path, a v5e host is recognised by its ``/dev/vfio`` nodes, the
launcher (the parent of every worker) never imports jax, and the smoke's
worker code runs end to end at a tiny size on CPU.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import bench  # noqa: E402
from horovod_tpu.runner import tpu_topology  # noqa: E402


def _run(cmd, env=None, cwd=REPO_ROOT, timeout=300):
    return subprocess.run(cmd, capture_output=True, text=True, cwd=cwd,
                          timeout=timeout, env=env or os.environ.copy())


@pytest.mark.smoke
def test_bench_fails_without_tpu():
    proc = _run([sys.executable, "bench.py"])  # conftest pins JAX to cpu
    assert proc.returncode != 0
    assert "metric" not in proc.stdout, proc.stdout
    assert "needs a TPU" in proc.stderr or "measures a TPU" in proc.stderr


@pytest.mark.smoke
def test_chip_smoke_fails_without_tpu():
    proc = _run([sys.executable, "chip_smoke.py"])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout, proc.stdout
    assert "no TPU" in proc.stderr, proc.stderr


@pytest.mark.smoke
def test_unknown_device_kind_is_an_error():
    assert bench.peak_flops("TPU v5 lite") == 197e12
    for kind in ("cpu", "TPU v9", "v6", "TPU v5 lite pod"):
        with pytest.raises(ValueError, match="no peak FLOP/s known"):
            bench.peak_flops(kind)


_CACHE_SRC = (
    "import jax; "
    "from horovod_tpu.common.compile_cache import configure_compile_cache; "
    "print(configure_compile_cache()); "
    "print(jax.config.jax_compilation_cache_dir)")


def _cache_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = REPO_ROOT
    env.update(extra)
    return env


@pytest.mark.smoke
def test_compile_cache_honours_env(tmp_path):
    proc = _run([sys.executable, "-c", _CACHE_SRC], cwd=str(tmp_path),
                env=_cache_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(tmp_path), str(tmp_path)]


@pytest.mark.smoke
def test_compile_cache_default_is_one_fixed_path(tmp_path):
    """No variable set: the same in-checkout directory from two cwd's (and
    so two pids), never a temporary one; none at all when pinned to CPU."""
    want = os.path.join(REPO_ROOT, ".jax_cache")
    for cwd in (REPO_ROOT, str(tmp_path)):
        proc = _run([sys.executable, "-c", _CACHE_SRC], cwd=cwd,
                    env=_cache_env())
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.split() == [want, want]
    proc = _run([sys.executable, "-c", _CACHE_SRC], cwd=str(tmp_path),
                env=_cache_env(JAX_PLATFORMS="cpu"))
    assert proc.stdout.split() == ["None", "None"]


def test_running_on_tpu_vm_sees_vfio_nodes(monkeypatch):
    """A v5e host has no /dev/accel*; its chips are /dev/vfio/<N>."""
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    listing = {"/dev": ["null", "shm", "vfio"],
               "/dev/vfio": ["0", "1", "2", "3", "vfio"]}
    monkeypatch.setattr(tpu_topology.os, "listdir", lambda d: listing[d])
    assert tpu_topology.running_on_tpu_vm()
    listing["/dev/vfio"] = ["vfio"]  # the control node alone is no chip
    assert not tpu_topology.running_on_tpu_vm()
    del listing["/dev/vfio"]

    def no_vfio(d):
        if d not in listing:
            raise FileNotFoundError(d)
        return listing[d]

    monkeypatch.setattr(tpu_topology.os, "listdir", no_vfio)
    assert not tpu_topology.running_on_tpu_vm()


@pytest.mark.smoke
def test_parents_of_workers_never_import_jax():
    """A process that has touched JAX holds the chip; the launcher and the
    smoke's parent start the processes that need it."""
    proc = _run([sys.executable, "-c",
                 "import sys; import horovod_tpu.runner.launch; "
                 "import chip_smoke; "
                 "print(sorted(m for m in sys.modules "
                 "if m.split('.')[0] in ('jax', 'jaxlib', 'flax')))"])
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "[]"


def test_chip_smoke_worker_runs_tiny_on_cpu_np2():
    """The code the chip runs, at a tiny size: two processes, strict XLA
    plane, DistributedOptimizer steps, uneven alltoall, every check of the
    worker but the platform's name."""
    from .helpers import _timeout_scale, scaled_mesh_startup_timeout

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.setdefault("HOROVOD_MESH_STARTUP_TIMEOUT",
                   scaled_mesh_startup_timeout())
    proc = _run([sys.executable, "-m", "horovod_tpu.runner.launch",
                 "-np", "2", "--data-plane", "xla", sys.executable,
                 "chip_smoke.py", "--worker", "eager", "--platform", "cpu",
                 "--batch", "2", "--image", "32", "--steps", "1"],
                env=env, timeout=300 * _timeout_scale())
    assert proc.returncode == 0, proc.stderr[-3000:]
    recs = [json.loads(ln[len("SMOKE "):])
            for ln in proc.stdout.splitlines() if ln.startswith("SMOKE ")]
    assert sorted(r["rank"] for r in recs) == [0, 1]
    assert len({r["checksum"] for r in recs}) == 1
    assert len({r["chip"] for r in recs}) == 2
    for r in recs:
        assert r["platform"] == "cpu" and r["local_devices"] == 1
        assert r["xla_ops"]["allreduce"] >= 2 and r["xla_ops"]["alltoall"] == 1
