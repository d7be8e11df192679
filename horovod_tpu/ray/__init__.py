"""Ray integration: actor-per-slot execution of horovod_tpu jobs.

Role of the reference's ``horovod/ray/runner.py`` (``RayExecutor``,
``BaseHorovodWorker``, ``Coordinator``, ``NodeColocator``) and
``horovod/ray/elastic.py`` (``RayHostDiscovery``, ``ElasticRayExecutor``):
the Ray cluster replaces ssh as the process-placement fabric — one Ray
actor per slot, pinned to its node, with the rank/rendezvous env injected
before the user function runs.  The control plane is unchanged: the same
RendezvousServer, TCP mesh, and (for elastic) ElasticDriver as the CLI
launcher; only worker *spawning* is delegated to Ray.

TPU-first differences: no NIC-negotiation dance (workers advertise all
candidate addresses, ``transport/tcp.py``), per-chip TPU visibility env
comes from ``runner.tpu_topology`` when a node hosts multiple slots, and
``use_gpu``/GPU resource knobs are replaced by ``use_tpu``.

``import horovod_tpu.ray`` works without ray installed; only constructing
an executor requires it.
"""

from __future__ import annotations

import os
import socket
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ..common import env as env_mod
from ..common import secret as secret_mod
from ..common.logging_util import get_logger
from ..elastic.discovery import HostDiscovery
from ..runner.hosts import HostInfo, SlotInfo, get_host_assignments
from ..runner.rendezvous import RendezvousServer

log = get_logger("horovod_tpu.ray")


def _ray():
    try:
        import ray
    except ImportError as e:  # pragma: no cover
        raise ImportError(
            "horovod_tpu.ray requires the `ray` package "
            "(pip install horovod-tpu[ray])") from e
    return ray


@dataclass
class RaySettings:
    """Executor knobs (reference ``MiniSettings``, ``ray/runner.py:22-41``)."""

    timeout_s: float = 30.0
    placement_timeout_s: float = 100.0
    cpus_per_slot: int = 1
    use_tpu: bool = False
    extra_env_vars: Dict[str, str] = field(default_factory=dict)


class BaseHorovodWorker:
    """The per-slot Ray actor (reference ``ray/runner.py:48-88``).

    Instantiated remotely via ``ray.remote``; every method call executes in
    the actor's own process, so env mutations land before ``hvd.init``.
    """

    def __init__(self):
        self.executable = None

    def hostname(self) -> str:
        return socket.gethostname()

    def node_ip(self) -> str:
        from ..transport.tcp import _default_advertise_addr

        return _default_advertise_addr()

    def update_env_vars(self, env_vars: Dict[str, str]) -> None:
        os.environ.update({k: str(v) for k, v in env_vars.items()})

    def env_vars(self) -> Dict[str, str]:
        return dict(os.environ)

    def start_executable(self, executable_cls=None, executable_args=None,
                         executable_kwargs=None) -> None:
        if executable_cls is not None:
            self.executable = executable_cls(*(executable_args or []),
                                             **(executable_kwargs or {}))

    def execute(self, fn: Callable) -> Any:
        """Run ``fn(executable)`` (or ``fn()`` when no executable was
        started) inside the actor."""
        if self.executable is not None:
            return fn(self.executable)
        return fn()

    def shutdown_horovod(self) -> None:
        import horovod_tpu as hvd

        if hvd.is_initialized():
            hvd.shutdown()


class RayExecutor:
    """Static Ray job: N actors, one per slot (reference
    ``ray/runner.py:250-480``).

    Usage::

        executor = RayExecutor(RaySettings(), num_workers=4)
        executor.start()
        results = executor.run(train_fn, args=(cfg,))
        executor.shutdown()
    """

    @classmethod
    def create_settings(cls, timeout_s: float = 30.0,
                        **kwargs) -> RaySettings:
        return RaySettings(timeout_s=timeout_s, **kwargs)

    def __init__(self, settings: Optional[RaySettings] = None,
                 num_workers: Optional[int] = None,
                 num_hosts: Optional[int] = None,
                 num_slots: Optional[int] = None,
                 cpus_per_slot: Optional[int] = None,
                 use_tpu: Optional[bool] = None):
        self.settings = settings or RaySettings()
        if cpus_per_slot is not None:
            self.settings.cpus_per_slot = cpus_per_slot
        if use_tpu is not None:
            self.settings.use_tpu = use_tpu
        if num_workers is None and (num_hosts is None or num_slots is None):
            raise ValueError(
                "specify num_workers, or num_hosts together with num_slots "
                "(reference RayExecutor has the same contract)")
        self.num_workers = num_workers or (num_hosts * num_slots)
        self.num_hosts = num_hosts
        self.num_slots = num_slots
        self.workers: List = []
        self.slots: List[SlotInfo] = []
        self._server: Optional[RendezvousServer] = None

    # -- lifecycle ------------------------------------------------------

    def start(self, executable_cls=None, executable_args=None,
              executable_kwargs=None,
              extra_env_vars: Optional[Dict[str, str]] = None) -> None:
        ray = _ray()
        remote_cls = ray.remote(BaseHorovodWorker)
        opts = {"num_cpus": self.settings.cpus_per_slot}
        self.workers = [remote_cls.options(**opts).remote()
                        for _ in range(self.num_workers)]

        # Coordinator role (reference ray/runner.py:178-249): learn where
        # Ray placed each actor, derive host-major rank coordinates.
        hostnames = ray.get([w.hostname.remote() for w in self.workers],
                            timeout=self.settings.placement_timeout_s)
        by_host: Dict[str, int] = {}
        for h in hostnames:
            by_host[h] = by_host.get(h, 0) + 1
        if self.num_hosts is not None and len(by_host) != self.num_hosts:
            log.warning("requested %d hosts, Ray placed actors on %d",
                        self.num_hosts, len(by_host))
        host_infos = [HostInfo(h, n) for h, n in by_host.items()]
        self.slots = get_host_assignments(host_infos, self.num_workers)

        # Actors were created unpinned; order them host-major to match the
        # slot table (actor i ↔ slot i).
        order: Dict[str, List[int]] = {}
        for i, h in enumerate(hostnames):
            order.setdefault(h, []).append(i)
        arranged = []
        for slot in self.slots:
            arranged.append(self.workers[order[slot.hostname].pop(0)])
        self.workers = arranged

        # Rendezvous + per-job secret live in the driver process.
        job_secret = secret_mod.ensure_job_secret()
        self._server = RendezvousServer(bind_addr="0.0.0.0",
                                        job_secret=job_secret.encode())
        port = self._server.start()
        self._server.publish_slots([{
            "hostname": s.hostname, "rank": s.rank,
            "local_rank": s.local_rank, "cross_rank": s.cross_rank,
            "size": s.size, "local_size": s.local_size,
            "cross_size": s.cross_size,
        } for s in self.slots])

        from ..transport.tcp import _default_advertise_addr

        rdv_addr = _default_advertise_addr()
        env_refs = []
        for slot, worker in zip(self.slots, self.workers):
            env = dict(slot.to_env())
            env.update({
                env_mod.HOROVOD_RENDEZVOUS_ADDR: rdv_addr,
                env_mod.HOROVOD_RENDEZVOUS_PORT: str(port),
                env_mod.HOROVOD_SECRET_KEY: job_secret,
            })
            if self.settings.use_tpu and slot.local_size > 1:
                from ..runner import tpu_topology
                from ..runner.launch import host_slots_of

                env.update(tpu_topology.slot_tpu_env(
                    slot.rank, slot.local_rank, host_slots_of(self.slots)))
            env.update(self.settings.extra_env_vars)
            env.update(extra_env_vars or {})
            env_refs.append(worker.update_env_vars.remote(env))
        ray.get(env_refs, timeout=self.settings.timeout_s)
        ray.get([w.start_executable.remote(executable_cls, executable_args,
                                           executable_kwargs)
                 for w in self.workers], timeout=self.settings.timeout_s)

    # -- execution ------------------------------------------------------

    def execute(self, fn: Callable) -> List[Any]:
        """Run ``fn`` on every worker; returns per-rank results."""
        ray = _ray()
        return ray.get([w.execute.remote(fn) for w in self.workers])

    def run(self, fn: Callable, args: Optional[list] = None,
            kwargs: Optional[dict] = None) -> List[Any]:
        args, kwargs = args or [], kwargs or {}
        return self.execute(lambda *exe: fn(*args, **kwargs))

    def run_remote(self, fn: Callable, args: Optional[list] = None,
                   kwargs: Optional[dict] = None) -> List[Any]:
        """Non-blocking flavor: returns Ray object refs."""
        args, kwargs = args or [], kwargs or {}
        return [w.execute.remote(lambda *exe: fn(*args, **kwargs))
                for w in self.workers]

    def execute_single(self, fn: Callable) -> Any:
        ray = _ray()
        return ray.get(self.workers[0].execute.remote(fn))

    def shutdown(self) -> None:
        ray = _ray()
        try:
            ray.get([w.shutdown_horovod.remote() for w in self.workers],
                    timeout=self.settings.timeout_s)
        except Exception:  # noqa: BLE001 — best-effort drain
            pass
        for w in self.workers:
            ray.kill(w)
        self.workers = []
        if self._server is not None:
            self._server.stop()
            self._server = None


class RayHostDiscovery(HostDiscovery):
    """Ray cluster state as the elastic discovery source (reference
    ``ray/elastic.py:36-60``): alive nodes with enough CPUs (or TPU
    resources) become hosts; slots = resource count / per-slot demand."""

    def __init__(self, use_tpu: bool = False, cpus_per_slot: int = 1):
        self.use_tpu = use_tpu
        self.cpus_per_slot = cpus_per_slot

    def find_available_hosts_and_slots(self) -> Dict[str, int]:
        ray = _ray()
        hosts: Dict[str, int] = {}
        for node in ray.nodes():
            if not node.get("Alive"):
                continue
            resources = node.get("Resources", {})
            if self.use_tpu:
                slots = int(resources.get("TPU", 0))
            else:
                slots = int(resources.get("CPU", 0) // self.cpus_per_slot)
            hostname = node.get("NodeManagerHostname") or \
                node.get("NodeManagerAddress")
            if slots > 0 and hostname:
                hosts[hostname] = slots
        return hosts


class ElasticRayExecutor:
    """Elastic job over Ray actors (reference ``ray/elastic.py:61-300``):
    the shared ElasticDriver handles discovery/rank-reshuffle/blacklists;
    worker creation spawns a Ray actor per slot instead of an ssh child."""

    def __init__(self, settings: Optional[RaySettings] = None,
                 min_np: int = 1, max_np: Optional[int] = None,
                 reset_limit: Optional[int] = None,
                 discovery: Optional[HostDiscovery] = None):
        self.settings = settings or RaySettings()
        self.min_np = min_np
        self.max_np = max_np
        self.reset_limit = reset_limit
        self.discovery = discovery or RayHostDiscovery(
            use_tpu=self.settings.use_tpu,
            cpus_per_slot=self.settings.cpus_per_slot)
        self.driver = None
        self._server: Optional[RendezvousServer] = None
        self._results: Dict[int, Any] = {}
        self._actors: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def start(self) -> None:
        from ..elastic.discovery import HostManager
        from ..elastic.driver import ElasticDriver

        self._job_secret = secret_mod.ensure_job_secret()
        self._server = RendezvousServer(
            bind_addr="0.0.0.0", job_secret=self._job_secret.encode())
        self._server.start()
        self.driver = ElasticDriver(
            self._server, HostManager(self.discovery),
            min_np=self.min_np, max_np=self.max_np,
            reset_limit=self.reset_limit)

    def run(self, worker_fn: Callable) -> List[Any]:
        """Run ``worker_fn`` elastically; returns results of the ranks that
        finished successfully (reference ``elastic.py:266-300``)."""
        assert self.driver is not None, "call start() first"
        ray = _ray()
        from ..elastic.registration import FAILURE, SUCCESS
        from ..transport.tcp import _default_advertise_addr

        rdv_addr = _default_advertise_addr()
        port = self._server.port
        remote_cls = ray.remote(BaseHorovodWorker)

        def create_worker(slot: SlotInfo, epoch: int) -> None:
            actor = remote_cls.options(
                num_cpus=self.settings.cpus_per_slot).remote()
            identity = f"{slot.hostname}:{slot.local_rank}"
            env = dict(slot.to_env())
            env.update({
                env_mod.HOROVOD_RENDEZVOUS_ADDR: rdv_addr,
                env_mod.HOROVOD_RENDEZVOUS_PORT: str(port),
                env_mod.HOROVOD_SECRET_KEY: self._job_secret,
                env_mod.HOROVOD_ELASTIC: "1",
                env_mod.HOROVOD_EPOCH: str(epoch),
            })
            env.update(self.settings.extra_env_vars)
            with self._lock:
                self._actors[identity] = actor
            ref = actor.execute.remote(_elastic_worker_main(
                worker_fn, env))

            def monitor():
                code = 0
                try:
                    result = ray.get(ref)
                    with self._lock:
                        self._results[slot.rank] = result
                except Exception as e:  # noqa: BLE001 — actor died/failed
                    log.info("elastic ray worker %s failed: %s", identity, e)
                    code = 1
                finally:
                    with self._lock:
                        self._actors.pop(identity, None)
                    self.driver.record_worker_exit(slot, code)
                    ray.kill(actor)

            threading.Thread(target=monitor, daemon=True,
                             name=f"ray-monitor-{identity}").start()

        try:
            self.driver.start(create_worker)
            while True:
                time.sleep(0.5)
                with self._lock:
                    alive = len(self._actors)
                successes = self.driver._registry.count(SUCCESS)
                failures = self.driver._registry.count(FAILURE)
                if successes and successes >= len(self.driver.current_slots) \
                        and alive == 0:
                    break
                if alive == 0 and failures and \
                        self.driver.hosts.total_slots() < self.min_np:
                    raise RuntimeError(
                        f"elastic ray job lost all capacity "
                        f"({failures} failures)")
                if self.driver.stopped_error:
                    raise RuntimeError(self.driver.stopped_error)
        finally:
            self.driver.stop()
        with self._lock:
            return [self._results[r] for r in sorted(self._results)]

    def shutdown(self) -> None:
        if self.driver is not None:
            self.driver.stop()
        if self._server is not None:
            self._server.stop()
            self._server = None


def _elastic_worker_main(worker_fn: Callable, env: Dict[str, str]):
    """Build the closure an elastic Ray actor executes: env first (before
    any horovod import state latches), then the user fn."""

    def main():
        os.environ.update(env)
        return worker_fn()

    return main
