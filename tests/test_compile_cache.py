"""Every process writes the compile-cache entries of its own programs.

JAX writes persistent-cache entries from ``jax.distributed`` process 0
alone; ``configure_compile_cache()`` installs a hook through which the
other processes write the entries of programs on their own devices
(``horovod_tpu/common/compile_cache.py``).  Pinned here, on the CPU: a
process told it is 0, 1 or 3 finds all of its programs again in a second
run; under ``hvdrun -np 2`` both ranks do, and no file has two writers;
with another JAX underneath the hook stays out and says so once; a torn
entry is a miss.  Every case is a real process: the cache is decided once
per process, at its first compilation.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from .helpers import _timeout_scale, scaled_mesh_startup_timeout

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# What a process under test reports of itself, as one `CACHE {json}` line:
# JAX's own cache events, the backend's compiles, the program's counter, and
# the keys this process handed to the cache's writer (JAX's or the hook's).
_PROBE = r"""
import json, jax
from jax._src import compilation_cache as cc, compiler

counts = {"requests": 0, "hits": 0, "compiles": 0, "keys": []}
events = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
          "/jax/compilation_cache/cache_hits": "hits"}

def on_event(event, **_):
    if event in events:
        counts[events[event]] += 1

jax.monitoring.register_event_listener(on_event)
_compile, _put = compiler.backend_compile_and_load, cc.put_executable_and_time

def backend_compile_and_load(*args, **kwargs):
    counts["compiles"] += 1
    return _compile(*args, **kwargs)

def put_executable_and_time(cache_key, module_name, executable, backend,
                            compile_time):
    counts["keys"].append(cache_key)
    return _put(cache_key, module_name, executable, backend, compile_time)

compiler.backend_compile_and_load = backend_compile_and_load
cc.put_executable_and_time = put_executable_and_time

def report(**more):
    from horovod_tpu.core import metrics
    counts["written"] = metrics.registry.get_counter(
        "compile_cache_entries_written")
    print("CACHE " + json.dumps(dict(counts, **more)), flush=True)
"""

# One process, told its process id; two programs on its own device.
_ONE_PROCESS = _PROBE + r"""
import sys, numpy as np
from jax._src import distributed
from horovod_tpu.common.compile_cache import configure_compile_cache

configure_compile_cache()
distributed.global_state.process_id = int(sys.argv[1])
x = np.arange(8, dtype=np.float32)
a = jax.jit(lambda v: v * 2 + 1)(x)
b = jax.jit(lambda v: (v @ v) - 3)(x)
report(a=float(a.sum()), b=float(b))
"""

# Two ranks, XLA plane: the product's eager path, three steps.
_EAGER_LOOP = _PROBE + r"""
import numpy as np, jax.numpy as jnp, optax
import horovod_tpu as hvd

hvd.init()
params = {"w": jnp.asarray(np.ones((4, 3), np.float32)),
          "b": jnp.asarray(np.zeros((3,), np.float32))}
x = jnp.asarray(np.full((2, 4), hvd.rank() + 1, np.float32))
grad = jax.jit(jax.grad(lambda p, x: jnp.sum((x @ p["w"] + p["b"]) ** 2)))
apply = jax.jit(optax.apply_updates)
dopt = hvd.DistributedOptimizer(optax.sgd(0.01))
state = dopt.init(params)
for _ in range(3):
    updates, state = dopt.update(grad(params, x), state, params)
    params = apply(params, updates)
checksum = float(sum(jnp.sum(v) for v in jax.tree_util.tree_leaves(params)))
report(rank=hvd.rank(), process=jax.process_index(), checksum=checksum)
hvd.shutdown()
"""


def _env(cache_dir, **extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)
    env.update(extra)
    return env


def _reports(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(ln[len("CACHE "):])
            for ln in proc.stdout.splitlines() if ln.startswith("CACHE ")]


def _one_process(cache_dir, process_id):
    proc = subprocess.run(
        [sys.executable, "-c", _ONE_PROCESS, str(process_id)],
        capture_output=True, text=True, cwd=str(cache_dir),
        env=_env(cache_dir),
        timeout=120 * _timeout_scale())
    (report,) = _reports(proc)
    return report, proc


def _entries(cache_dir):
    return sorted(f for f in os.listdir(cache_dir) if f.endswith("-cache"))


@pytest.mark.timeout(240)
@pytest.mark.parametrize("process_id", [0, 1, 3])
def test_a_second_run_finds_every_program_of_any_process(tmp_path,
                                                         process_id):
    cold, _ = _one_process(tmp_path, process_id)
    assert cold["requests"] >= 2 and cold["hits"] == 0
    assert cold["compiles"] == cold["requests"]
    assert len(_entries(tmp_path)) == len(cold["keys"]) == cold["requests"]
    # Process 0 wrote through JAX; the others through the hook, which counts.
    assert cold["written"] == (0 if process_id == 0 else cold["requests"])

    warm, _ = _one_process(tmp_path, process_id)
    assert warm["hits"] == warm["requests"] == cold["requests"]
    assert warm["compiles"] == 0 and warm["keys"] == []
    assert warm["written"] == 0
    assert (warm["a"], warm["b"]) == (cold["a"], cold["b"])


@pytest.mark.timeout(600)
def test_np2_eager_loop_every_rank_hits_and_no_file_has_two_writers(
        tmp_path):
    def run():
        env = _env(tmp_path)
        env.setdefault("HOROVOD_MESH_STARTUP_TIMEOUT",
                       scaled_mesh_startup_timeout())
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
             "--data-plane", "xla", sys.executable, "-c", _EAGER_LOOP],
            capture_output=True, text=True, cwd=str(tmp_path), env=env,
            timeout=280 * _timeout_scale())
        by_process = {r["process"]: r for r in _reports(proc)}
        assert sorted(by_process) == [0, 1]
        return by_process

    cold = run()
    for r in cold.values():
        assert r["hits"] == 0 and r["compiles"] == r["requests"] > 0
    first, other = cold[0], cold[1]
    # Process 0 wrote everything it compiled, the programs that span both
    # processes among them; the other process its local programs alone.
    assert len(first["keys"]) == first["requests"] and first["written"] == 0
    assert 0 < len(other["keys"]) < other["requests"]
    assert other["written"] == len(other["keys"])
    assert not set(first["keys"]) & set(other["keys"])
    assert len(set(first["keys"]) | set(other["keys"])) == \
        len(first["keys"]) + len(other["keys"]) == len(_entries(tmp_path))

    # A program that spans processes is process 0's to write.  On a TPU its
    # key is the same everywhere and every process reads that entry; the
    # CPU's key holds the process's own view of the topology, so here the
    # other process compiles those again, and finds all of its own.
    spanning = other["requests"] - len(other["keys"])
    warm = run()
    for process, r in warm.items():
        assert r["requests"] == cold[process]["requests"]
        assert r["compiles"] == r["requests"] - r["hits"]
        assert r["compiles"] == (spanning if process else 0)
        assert r["keys"] == [] and r["written"] == 0
        assert r["checksum"] == cold[process]["checksum"]


# Another JAX underneath: the symbol gone, the symbol with one parameter
# more (which this JAX can still call), the cache's writer likewise.
_OTHER_JAX = {
    "absent": "del compiler._compile_and_write_cache",
    "other_signature": """
def _next(backend, computation, executable_devices, compile_options,
          host_callbacks, module_name, cache_key, pgle_profiler=None,
          _was=compiler._compile_and_write_cache):
    return _was(backend, computation, executable_devices, compile_options,
                host_callbacks, module_name, cache_key)
compiler._compile_and_write_cache = _next
""",
    "other_writer": """
def _next(cache_key, module_name, executable, backend, compile_time,
          executable_devices=None,
          _was=compilation_cache.put_executable_and_time):
    return _was(cache_key, module_name, executable, backend, compile_time)
compilation_cache.put_executable_and_time = _next
""",
}

_INSTALL_CHECK = r"""
import logging, sys, jax
from jax._src import compilation_cache, compiler
from horovod_tpu.common.compile_cache import configure_compile_cache

{change}
before = (getattr(compiler, "_compile_and_write_cache", None),
          compiler._cache_write, compilation_cache.put_executable_and_time)

class Count(logging.Handler):
    records = []
    def emit(self, record):
        self.records.append(record.getMessage())

logging.getLogger("horovod_tpu.compile_cache").addHandler(Count())
print(configure_compile_cache())
print(configure_compile_cache())
after = (getattr(compiler, "_compile_and_write_cache", None),
         compiler._cache_write, compilation_cache.put_executable_and_time)
assert all(a is b for a, b in zip(before, after)), (before, after)
assert len(Count.records) == 1, Count.records
assert jax.__version__ in Count.records[0], Count.records
if before[0] is not None:  # else this JAX cannot compile with a cache on
    print(float(jax.jit(lambda v: v + 1)(1.0)))
"""


@pytest.mark.timeout(120)
@pytest.mark.parametrize("change", sorted(_OTHER_JAX))
def test_under_another_jax_the_hook_stays_out_and_warns_once(tmp_path,
                                                             change):
    proc = subprocess.run(
        [sys.executable, "-c",
         _INSTALL_CHECK.format(change=_OTHER_JAX[change])],
        capture_output=True, text=True, cwd=str(tmp_path),
        env=_env(tmp_path), timeout=100 * _timeout_scale())
    assert proc.returncode == 0, proc.stderr[-3000:]
    ran = [] if change == "absent" else ["2.0"]
    assert proc.stdout.split() == [str(tmp_path), str(tmp_path)] + ran


@pytest.mark.timeout(240)
def test_a_truncated_entry_is_a_miss_and_a_recompile(tmp_path):
    cold, _ = _one_process(tmp_path, 1)
    entries = _entries(tmp_path)
    assert len(entries) == cold["requests"]
    torn = os.path.join(tmp_path, entries[0])
    with open(torn, "rb") as f:
        whole = f.read()
    with open(torn, "wb") as f:
        f.write(whole[:len(whole) // 2])

    warm, proc = _one_process(tmp_path, 1)
    assert warm["requests"] == cold["requests"]
    assert warm["hits"] == warm["requests"] - 1 and warm["compiles"] == 1
    assert (warm["a"], warm["b"]) == (cold["a"], cold["b"])
    assert "Error reading persistent compilation cache entry" in proc.stderr
