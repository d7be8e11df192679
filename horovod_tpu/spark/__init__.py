"""Spark integration: run a horovod_tpu job inside a Spark job's tasks.

Role of the reference's ``horovod/spark/runner.py:195-303`` (``run``) and
its driver/task services: Spark provides process placement (one task per
slot); the driver collects each task's location, assigns host-major ranks
by executor locality, and the tasks then run the user function under the
normal horovod_tpu runtime (rendezvous + TCP mesh), exactly like workers
spawned by ``hvdrun``.

Differences from the reference: no mpirun/orted re-exec dance and no
pickled-RPC service framework — each Spark task registers and fetches its
rank table directly through the launcher's HMAC-signed rendezvous KV
server (the secret rides the Spark closure — note Spark's RPC/closure
transport is cleartext unless the cluster enables
``spark.network.crypto.enabled`` or SSL, so enable one of those on
untrusted networks; the reference's "Spark RPC communicates the key"
approach, ``spark/runner.py:46-48``, has the same property), and the
user function runs in the task process itself.

``import horovod_tpu.spark`` works without pyspark; ``run()`` accepts any
SparkContext-shaped object (``parallelize(...).mapPartitionsWithIndex(...)
.collect()``), which is also how tests drive it without a Spark install.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..common import env as env_mod
from ..common import secret as secret_mod
from ..common.logging_util import get_logger
from ..runner.hosts import HostInfo, get_host_assignments
from ..runner.rendezvous import RendezvousServer

log = get_logger("horovod_tpu.spark")

_REG_SCOPE = "spark.reg"
_ENV_SCOPE = "spark.env"
_RESULT_SCOPE = "spark.result"


def _default_spark_context():
    try:
        import pyspark
    except ImportError as e:  # pragma: no cover
        raise ImportError(
            "horovod_tpu.spark.run() needs an active SparkContext: pass "
            "one via sc=, or install pyspark") from e
    sc = pyspark.SparkContext._active_spark_context
    if sc is None:  # pragma: no cover
        raise RuntimeError("no active SparkContext; create one first")
    return sc


def _task_fn(index: int, fn: Callable, args: tuple, kwargs: dict,
             rdv_addr: str, rdv_port: int, key: str, start_timeout: float,
             extra_env: Dict[str, str]):
    """Runs inside each Spark task (reference ``_task_fn``,
    ``spark/runner.py:45-116``): register location, wait for the rank
    table, run the user fn under the horovod_tpu runtime."""
    import socket

    # The key arrives via the Spark closure; export before any rendezvous
    # traffic so every request is signed.
    os.environ[env_mod.HOROVOD_SECRET_KEY] = key
    from ..transport.store import HTTPStoreClient

    store = HTTPStoreClient(rdv_addr, rdv_port)
    store.set(_REG_SCOPE, str(index), socket.gethostname().encode())

    got = store.wait(_ENV_SCOPE, [str(index)], timeout=start_timeout)
    env = json.loads(got[str(index)].decode())
    os.environ.update({k: str(v) for k, v in env.items()})
    os.environ.update({k: str(v) for k, v in extra_env.items()})

    result = fn(*args, **kwargs)
    store.set(_RESULT_SCOPE, str(index), _dumps(result))
    return index


from ..common.pickling import dumps as _dumps  # noqa: E402
from ..common.pickling import loads as _loads  # noqa: E402


def run(fn: Callable, args: tuple = (), kwargs: Optional[dict] = None,
        num_proc: Optional[int] = None, sc=None,
        extra_env: Optional[Dict[str, str]] = None,
        start_timeout: float = 120.0) -> List[Any]:
    """Run ``fn`` on ``num_proc`` Spark tasks as one horovod_tpu job;
    returns per-rank results ordered by rank (reference
    ``horovod.spark.run``, ``spark/runner.py:195-301``)."""
    sc = sc or _default_spark_context()
    if num_proc is None:
        num_proc = int(sc.defaultParallelism)
    elif num_proc > int(getattr(sc, "defaultParallelism", num_proc)):
        # All tasks must run CONCURRENTLY (they form one collective job);
        # over-subscribing deadlocks until start_timeout (reference
        # validates executor capacity up front the same way).
        raise ValueError(
            f"num_proc={num_proc} exceeds the cluster's parallelism "
            f"({sc.defaultParallelism}); a horovod_tpu Spark job needs "
            "every task running at once")
    kwargs = kwargs or {}

    key = secret_mod.ensure_job_secret()
    server = RendezvousServer(bind_addr="0.0.0.0",
                              job_secret=key.encode())
    port = server.start()
    from ..transport.tcp import _default_advertise_addr

    rdv_addr = _default_advertise_addr()

    # Assignment thread (reference Coordinator role): once every task has
    # registered its hostname, compute host-major ranks and publish each
    # task's env — the Spark job is already running by then, so this must
    # happen concurrently with collect().
    assign_err: List[BaseException] = []

    def _assign():
        try:
            deadline = time.monotonic() + start_timeout
            hostnames: Dict[int, str] = {}
            while len(hostnames) < num_proc:
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"only {len(hostnames)}/{num_proc} Spark tasks "
                        f"registered within {start_timeout}s")
                for i in range(num_proc):
                    if i not in hostnames:
                        val = server.get(_REG_SCOPE, str(i))
                        if val is not None:
                            hostnames[i] = val.decode()
                time.sleep(0.05)

            by_host: Dict[str, List[int]] = {}
            for i in range(num_proc):
                by_host.setdefault(hostnames[i], []).append(i)
            hosts = [HostInfo(h, len(idxs)) for h, idxs in by_host.items()]
            slots = get_host_assignments(hosts, num_proc)
            server.publish_slots([{
                "hostname": s.hostname, "rank": s.rank,
                "local_rank": s.local_rank, "cross_rank": s.cross_rank,
                "size": s.size, "local_size": s.local_size,
                "cross_size": s.cross_size,
            } for s in slots])
            # slot i of a host ↔ i-th registered task on that host
            for slot in slots:
                index = by_host[slot.hostname][slot.local_rank]
                env = dict(slot.to_env())
                env.update({
                    env_mod.HOROVOD_RENDEZVOUS_ADDR: rdv_addr,
                    env_mod.HOROVOD_RENDEZVOUS_PORT: str(port),
                })
                server.set(_ENV_SCOPE, str(index),
                           json.dumps(env).encode())
        except BaseException as e:  # noqa: BLE001 — surfaced after collect
            assign_err.append(e)

    assigner = threading.Thread(target=_assign, daemon=True,
                                name="hvd-spark-assign")
    assigner.start()

    mapper = _make_mapper(fn, args, kwargs, rdv_addr, port, key,
                          start_timeout, dict(extra_env or {}))
    try:
        indices = sc.parallelize(range(num_proc), num_proc) \
            .mapPartitionsWithIndex(mapper).collect()
        if assign_err:
            raise assign_err[0]
        if sorted(indices) != list(range(num_proc)):
            raise RuntimeError(f"Spark job lost tasks: got {indices}")
        # Results come back rank-ordered: map index → rank via the
        # published env table.
        by_rank: Dict[int, Any] = {}
        for i in range(num_proc):
            env = json.loads(server.get(_ENV_SCOPE, str(i)).decode())
            blob = server.get(_RESULT_SCOPE, str(i))
            by_rank[int(env[env_mod.HOROVOD_RANK])] = _loads(blob)
        return [by_rank[r] for r in range(num_proc)]
    finally:
        server.stop()


def _make_mapper(fn, args, kwargs, rdv_addr, port, key, start_timeout,
                 extra_env):
    """Top-level closure factory (reference ``_make_mapper``,
    ``spark/runner.py:118-125``) — keeps the lambda cloudpickle-friendly."""

    def _mapper(index, _iterator):
        yield _task_fn(index, fn, args, kwargs, rdv_addr, port, key,
                       start_timeout, extra_env)

    return _mapper


# ---------------------------------------------------------------------------
# elastic (reference ``horovod.spark.run_elastic``, spark/runner.py:303)
# ---------------------------------------------------------------------------

_ECMD_SCOPE = "spark.cmd"
_EEXIT_SCOPE = "spark.exit"
_EBEAT_SCOPE = "spark.beat"

# A task whose heartbeat counter hasn't advanced for this long is treated
# as dead even without an exit marker (SIGKILLed executors never write
# one); compared against a driver-local monotonic clock, so client clock
# skew is irrelevant.
_BEAT_STALE_SECS = 10.0


def _elastic_task_fn(index: int, fn: Callable, args: tuple, kwargs: dict,
                     rdv_addr: str, rdv_port: int, key: str,
                     start_timeout: float, extra_env: Dict[str, str]):
    """Elastic Spark task: register as a single-slot host, wait for the
    driver's slot assignment, run ``fn`` under the in-process elastic
    machinery.  Each task ATTEMPT is an individual host, like the
    reference salting its host hash per attempt (``spark/runner.py:52-55``):
    the attempt-unique identity means a Spark retry registers as a fresh
    host with fresh cmd/exit keys and rejoins the job, while the dead
    attempt's exit marker keeps it out of discovery."""
    import secrets as _secrets

    os.environ[env_mod.HOROVOD_SECRET_KEY] = key
    from ..transport.store import HTTPStoreClient

    store = HTTPStoreClient(rdv_addr, rdv_port)
    identity = f"task-{index}-{_secrets.token_hex(4)}"
    store.set(_REG_SCOPE, identity, b"1")

    # Heartbeat: a counter the driver watches with ITS clock — a
    # SIGKILLed executor writes no exit marker, and only a stalled beat
    # reveals it (the finally below cannot run for process death).
    beat_stop = threading.Event()

    def _beat():
        n = 0
        while not beat_stop.is_set():
            try:
                store.set(_EBEAT_SCOPE, identity, str(n).encode())
            except OSError:
                pass  # driver gone: the job is over anyway
            n += 1
            beat_stop.wait(1.0)

    threading.Thread(target=_beat, daemon=True,
                     name=f"hvd-spark-beat-{index}").start()

    # EVERYTHING after registration sits under one try/finally: a failure
    # while waiting for the command (timeout, bad JSON) must still stop
    # the beat and write an exit marker, or a reused Spark python worker
    # would keep heartbeating as an immortal ghost host.
    code = 1  # anything that escapes assignment below counts as a crash
    try:
        got = store.wait(_ECMD_SCOPE, [identity], timeout=start_timeout)
        env = json.loads(got[identity].decode())
        os.environ.update({k: str(v) for k, v in env.items()})
        os.environ.update({k: str(v) for k, v in extra_env.items()})
        result = fn(*args, **kwargs)
        store.set(_RESULT_SCOPE, identity, _dumps(result))
        code = 0
    except SystemExit as e:
        # Preserve elastic exit semantics: the in-process machinery uses
        # a distinct TRANSIENT exit code for "my peer died, recycle me" —
        # flattening it to 1 would count the healthy survivor against the
        # much stricter crash blacklist threshold.  Non-integer codes
        # (incl. bool) are failure by Python convention
        # (sys.exit("msg") == status 1).
        code = 0 if e.code is None else \
            (e.code if isinstance(e.code, int)
             and not isinstance(e.code, bool) else 1)
        raise
    finally:
        beat_stop.set()
        store.set(_EEXIT_SCOPE, identity, str(code).encode())
    return index


def run_elastic(fn: Callable, args: tuple = (),
                kwargs: Optional[dict] = None,
                num_proc: Optional[int] = None, min_np: int = 1,
                max_np: Optional[int] = None, sc=None,
                extra_env: Optional[Dict[str, str]] = None,
                start_timeout: float = 120.0) -> List[Any]:
    """Elastic job over Spark tasks (reference ``horovod.spark.run_elastic``,
    ``spark/runner.py:303``): Spark provides up to ``num_proc`` task
    slots, the shared ElasticDriver assigns ranks and survives task loss
    down to ``min_np`` (Spark's own task retry provides replacement
    hosts).

    Returns a list indexed by FINAL rank (the assignment in force when the
    job wound down).  **Partial-results contract**: after mid-run
    failures/resizes, entries for ranks whose last incarnation did not
    report a result are ``None`` — the job succeeds as long as at least
    one rank reported (rank 0's host being pruned mid-run is survivable;
    re-ranked survivors' results land at their final indices).  Callers
    needing one definitive value should read the first non-``None`` entry
    or have every rank return the coordinator-broadcast state."""
    from ..elastic.discovery import HostDiscovery, HostManager
    from ..elastic.driver import ElasticDriver
    from ..elastic.registration import FAILURE
    from ..runner.hosts import SlotInfo
    from ..transport.tcp import _default_advertise_addr

    sc = sc or _default_spark_context()
    if num_proc is None:
        num_proc = int(sc.defaultParallelism)
    kwargs = kwargs or {}

    key = secret_mod.ensure_job_secret()
    server = RendezvousServer(bind_addr="0.0.0.0", job_secret=key.encode())
    port = server.start()
    rdv_addr = _default_advertise_addr()

    class _SparkTaskDiscovery(HostDiscovery):
        """Registered, not-yet-exited, still-heartbeating Spark task
        ATTEMPTS are the host set (attempt-unique identities; see
        _elastic_task_fn).  The staleness check uses the DRIVER's
        monotonic clock on counter changes, so a SIGKILLed executor —
        which writes no exit marker — drops out of discovery once its
        beat stops advancing."""

        def __init__(self):
            self._beats: Dict[str, tuple] = {}  # identity → (val, seen_at)

        def _alive(self, identity: str) -> bool:
            # A missing beat key gets the SAME staleness deadline from
            # first sighting: an executor SIGKILLed before its first beat
            # write must still age out of discovery.
            raw = server.get(_EBEAT_SCOPE, identity)
            now = time.monotonic()
            prev = self._beats.get(identity)
            if prev is None or prev[0] != raw:
                self._beats[identity] = (raw, now)
                return True
            return now - prev[1] < _BEAT_STALE_SECS

        def find_available_hosts_and_slots(self) -> Dict[str, int]:
            return {identity: 1
                    for identity in server.keys(_REG_SCOPE)
                    if server.get(_EEXIT_SCOPE, identity) is None
                    and self._alive(identity)}

    driver = ElasticDriver(server, HostManager(_SparkTaskDiscovery()),
                           min_np=min_np, max_np=max_np or num_proc,
                           timeout=start_timeout)
    assigned: Dict[str, SlotInfo] = {}  # identity → last assigned slot

    def create_worker(slot: SlotInfo, epoch: int) -> None:
        env = dict(slot.to_env())
        env.update({
            env_mod.HOROVOD_RENDEZVOUS_ADDR: rdv_addr,
            env_mod.HOROVOD_RENDEZVOUS_PORT: str(port),
            env_mod.HOROVOD_ELASTIC: "1",
            env_mod.HOROVOD_EPOCH: str(epoch),
        })
        assigned[slot.hostname] = slot
        server.set(_ECMD_SCOPE, slot.hostname, json.dumps(env).encode())

    monitor_stop = threading.Event()
    rank_results: Dict[int, str] = {}  # rank → identity that succeeded
    seen_exits: set = set()

    def sweep_exits():
        # Walk ALL ever-assigned identities, not driver.current_slots: the
        # discovery loop may prune a finished host before the next tick,
        # and a missed exit would lose its success/result.
        for identity, slot in list(assigned.items()):
            if identity in seen_exits:
                continue
            raw = server.get(_EEXIT_SCOPE, identity)
            if raw is not None:
                seen_exits.add(identity)
                try:
                    code = int(raw.decode())
                except ValueError:
                    code = 1  # garbage marker counts as a crash
                if code == 0:
                    rank_results[slot.rank] = identity
                driver.record_worker_exit(slot, code)

    def monitor():
        while not monitor_stop.is_set():
            sweep_exits()
            time.sleep(0.2)

    mapper = _make_elastic_mapper(fn, args, kwargs, rdv_addr, port, key,
                                  start_timeout, dict(extra_env or {}))
    spark_err: List[BaseException] = []

    def spark_job():
        try:
            # Per-task results flow through the KV store (keyed by the
            # winning attempt identities); collect() only drives execution.
            sc.parallelize(range(num_proc), num_proc) \
                .mapPartitionsWithIndex(mapper).collect()
        except BaseException as e:  # noqa: BLE001 — surfaced by the loop
            spark_err.append(e)

    job_thread = threading.Thread(target=spark_job, daemon=True,
                                  name="hvd-spark-elastic-job")
    job_thread.start()
    try:
        driver.start(create_worker, start_np=num_proc)
        threading.Thread(target=monitor, daemon=True,
                         name="hvd-spark-elastic-mon").start()
        while True:
            time.sleep(0.3)
            failures = driver._registry.count(FAILURE)
            job_over = not job_thread.is_alive()
            all_exited = not driver.hosts.total_slots()
            if rank_results and (all_exited or job_over):
                break  # attempts done; at least one rank succeeded
            if (all_exited or job_over) and (failures or spark_err) \
                    and not rank_results:
                if spark_err:
                    raise spark_err[0]
                raise RuntimeError(
                    f"elastic spark job lost all capacity "
                    f"({failures} failures)")
            if driver.stopped_error:
                raise RuntimeError(driver.stopped_error)
        # One last sweep: the break conditions (job thread done, discovery
        # empty) race the monitor's 0.2s tick, and an exit marker written
        # just before the break must still yield its rank's result.
        sweep_exits()
        out: Dict[int, Any] = {}
        for rank_, identity in rank_results.items():
            blob = server.get(_RESULT_SCOPE, identity)
            if blob is not None:
                out[rank_] = _loads(blob)
        # Final-rank-indexed, None for ranks whose last incarnation never
        # reported (the partial-results contract in the docstring).
        width = max(out) + 1 if out else 0
        return [out.get(r) for r in range(width)]
    finally:
        monitor_stop.set()
        driver.stop()
        server.stop()


def _make_elastic_mapper(fn, args, kwargs, rdv_addr, port, key,
                         start_timeout, extra_env):
    def _mapper(index, _iterator):
        yield _elastic_task_fn(index, fn, args, kwargs, rdv_addr, port,
                               key, start_timeout, extra_env)

    return _mapper
