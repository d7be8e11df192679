"""Ray integration tests against the in-repo fake ray (real subprocess
actors; see ``fake_ray.py``).  Mirrors the reference's ``test_ray.py``
strategy of a local mini-cluster, minus the ray dependency."""

import sys

import numpy as np
import pytest

from . import fake_ray


@pytest.fixture
def ray_env(monkeypatch):
    monkeypatch.setitem(sys.modules, "ray", fake_ray)
    fake_ray.NODES = []
    yield fake_ray


def _train_fn(scale):
    # Runs inside a spawned actor process, which inherits conftest's
    # JAX_PLATFORMS=cpu.
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    out = hvd.allreduce(np.ones(3) * (hvd.rank() + 1), op=hvd.Sum)
    result = float(np.asarray(out)[0]) * scale
    hvd.shutdown()
    return result


def test_ray_executor_end_to_end(ray_env):
    from horovod_tpu.ray import RayExecutor, RaySettings

    ex = RayExecutor(RaySettings(timeout_s=120, placement_timeout_s=120),
                     num_workers=2)
    ex.start(extra_env_vars={"JAX_PLATFORMS": "cpu"})
    assert len(ex.slots) == 2
    assert [s.rank for s in ex.slots] == [0, 1]
    results = ex.run(_train_fn, args=(10.0,))
    assert results == [30.0, 30.0], results
    single = ex.execute_single(lambda: "solo")
    assert single == "solo"
    ex.shutdown()


class _Exe:
    def __init__(self, base):
        self.base = base

    def value(self):
        return self.base * 2


def test_ray_executor_executable_cls(ray_env):
    from horovod_tpu.ray import RayExecutor, RaySettings

    ex = RayExecutor(RaySettings(timeout_s=60), num_workers=1)
    ex.start(executable_cls=_Exe, executable_args=[21])
    out = ex.execute(lambda exe: exe.value())
    assert out == [42]
    ex.shutdown()


def test_ray_host_discovery(ray_env):
    from horovod_tpu.ray import RayHostDiscovery

    fake_ray.NODES = [
        {"Alive": True, "NodeManagerHostname": "n1",
         "Resources": {"CPU": 8.0}},
        {"Alive": True, "NodeManagerHostname": "n2",
         "Resources": {"CPU": 4.0, "TPU": 4.0}},
        {"Alive": False, "NodeManagerHostname": "dead",
         "Resources": {"CPU": 16.0}},
    ]
    d = RayHostDiscovery(cpus_per_slot=2)
    assert d.find_available_hosts_and_slots() == {"n1": 4, "n2": 2}
    dt = RayHostDiscovery(use_tpu=True)
    assert dt.find_available_hosts_and_slots() == {"n2": 4}


def test_ray_requires_worker_spec(ray_env):
    from horovod_tpu.ray import RayExecutor

    with pytest.raises(ValueError):
        RayExecutor(num_hosts=2)  # num_slots missing


def _elastic_fn():
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    out = hvd.allreduce(np.ones(2), op=hvd.Sum, name="er")
    result = float(np.asarray(out)[0])
    hvd.shutdown()
    return result


def test_elastic_ray_executor_fixed_hosts(ray_env):
    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.ray import ElasticRayExecutor, RaySettings
    from horovod_tpu.runner.hosts import HostInfo

    ex = ElasticRayExecutor(
        RaySettings(timeout_s=120,
                    extra_env_vars={"JAX_PLATFORMS": "cpu"}),
        min_np=2, discovery=FixedHosts([HostInfo("localhost", 2)]))
    ex.start()
    results = ex.run(_elastic_fn)
    assert results == [2.0, 2.0], results
    ex.shutdown()
