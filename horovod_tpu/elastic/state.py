"""Worker-side elastic state + the ``@hvd.elastic.run`` wrapper.

Reference: ``common/elastic.py:1-168`` (``State``/``ObjectState``/``run_fn``)
and ``torch/elastic/state.py:27-178`` (handler-based ``TorchState``).  The
contract:

- ``state.commit()`` — snapshot to host memory + raise
  ``HostsUpdatedInterrupt`` if the driver notified us of membership change;
- ``HorovodInternalError`` (collective failed: peer died) → roll back to
  the last commit, re-rendezvous, retry;
- ``HostsUpdatedInterrupt`` (graceful change) → keep state, re-rendezvous,
  retry;
- after every reset the coordinator broadcasts its state so new/restored
  workers agree (``state.sync()``).

``JaxState`` snapshots pytrees (params/opt_state/any arrays) by copying to
host numpy — cheap, and exactly the commit/rollback semantics the
reference implements with ``deepcopy`` of torch state dicts.
"""

from __future__ import annotations

import copy
import threading
from typing import Any, Callable, Dict, List, Optional

from ..common.exceptions import HorovodInternalError, HostsUpdatedInterrupt

_host_update_lock = threading.Lock()
_host_update_event = threading.Event()
_host_update_skip_sync = [True]
_host_update_epoch = [-1.0]  # highest epoch seen; inf for epoch-less pings


def notify_hosts_updated(added_only: bool = False,
                         epoch: Optional[int] = None) -> None:
    """Called by the worker notification service when the driver reports a
    host-set change; surfaces at the next ``commit()``/``check`` point.

    ``epoch`` is the driver's epoch at ping time.  Staleness is judged at
    CONSUME time (a ping can arrive before the worker re-rendezvouses into
    the very epoch it advertises — acting on it afterwards would strand the
    worker waiting for an epoch that never comes, the round-1 failure)."""
    with _host_update_lock:
        _host_update_skip_sync[0] = _host_update_skip_sync[0] and added_only
        _host_update_epoch[0] = max(
            _host_update_epoch[0], float("inf") if epoch is None else epoch)
        _host_update_event.set()


def _consume_host_update() -> Optional[bool]:
    from ..common import env as env_mod

    with _host_update_lock:
        if not _host_update_event.is_set():
            return None
        _host_update_event.clear()
        skip = _host_update_skip_sync[0]
        _host_update_skip_sync[0] = True
        epoch = _host_update_epoch[0]
        _host_update_epoch[0] = -1.0
    if epoch <= env_mod.get_epoch():
        return None  # stale: we already adopted this (or a newer) epoch
    return skip


class State:
    """Base elastic state (reference ``common/elastic.py:24-100``)."""

    def __init__(self, **kwargs):
        self._reset_callbacks: List[Callable[[], None]] = []
        # Logical clock of ``commit()``; survivors compare it before they
        # skip a post-reset sync (``_sync_for_epoch``).
        self._commits = 0

    def register_reset_callbacks(self, callbacks: List[Callable[[], None]]) -> None:
        self._reset_callbacks.extend(callbacks)

    def on_reset(self) -> None:
        self.reset()
        for cb in self._reset_callbacks:
            cb()

    def commit(self) -> None:
        self.save()
        self._commits += 1
        self.check_host_updates()

    def check_host_updates(self) -> None:
        skip = _consume_host_update()
        if skip is not None:
            raise HostsUpdatedInterrupt(skip_sync=skip)

    # subclass responsibilities -----------------------------------------
    def save(self) -> None:
        raise NotImplementedError

    def restore(self) -> None:
        raise NotImplementedError

    def sync(self, root_rank: int = 0) -> None:
        raise NotImplementedError

    def reset(self) -> None:
        pass


class ObjectState(State):
    """Arbitrary picklable attributes, synced by coordinator broadcast
    (reference ``common/elastic.py:103-144``)."""

    def __init__(self, **kwargs):
        super().__init__()
        self._saved: Dict[str, Any] = {}
        for k, v in kwargs.items():
            setattr(self, k, v)
        self._known = list(kwargs.keys())
        self.save()

    def save(self) -> None:
        self._saved = {k: copy.deepcopy(getattr(self, k)) for k in self._known}

    def restore(self) -> None:
        for k, v in self._saved.items():
            setattr(self, k, copy.deepcopy(v))

    def sync(self, root_rank: int = 0) -> None:
        from ..frameworks.jax.functions import broadcast_object

        values = {k: getattr(self, k) for k in self._known}
        synced = broadcast_object(values, root_rank=root_rank,
                                  name="elastic.objstate")
        # Adopt the ROOT's attribute set, not just its values: a joiner
        # whose constructor defaults differ from the coordinator's
        # evolved set (attributes added/dropped across restarts) must
        # track exactly what the root tracks, or its next save/restore
        # cycle snapshots keys nobody else agrees on.
        self._known = list(synced.keys())
        for k, v in synced.items():
            setattr(self, k, v)
        self.save()


class JaxState(ObjectState):
    """Pytree-aware elastic state: array leaves snapshot to host numpy and
    sync via per-leaf broadcast (cheaper + dtype-exact vs pickling)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def _trees(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self._known}

    def save(self) -> None:
        import jax
        import numpy as np

        def snap(x):
            if hasattr(x, "device") or hasattr(x, "sharding"):
                return np.asarray(jax.device_get(x))
            return copy.deepcopy(x)

        self._saved = {
            k: jax.tree_util.tree_map(snap, v) for k, v in self._trees().items()
        }

    def restore(self) -> None:
        for k, v in self._saved.items():
            setattr(self, k, copy.deepcopy(v))

    def sync(self, root_rank: int = 0) -> None:
        import jax

        from ..frameworks.jax.functions import broadcast_parameters

        for k in self._known:
            tree = getattr(self, k)
            leaves = jax.tree_util.tree_leaves(tree)
            if leaves and all(hasattr(l, "shape") for l in leaves):
                setattr(self, k, broadcast_parameters(
                    tree, root_rank=root_rank))
            else:
                from ..frameworks.jax.functions import broadcast_object

                setattr(self, k, broadcast_object(
                    tree, root_rank=root_rank, name=f"elastic.sync.{k}"))
        self.save()


def _reset_and_reinit() -> None:
    """Full runtime teardown + re-init from the (possibly new) rendezvous
    assignment — the analog of the reference's shutdown/re-init reset path
    (``tensorflow/elastic.py:64-67`` + ``gloo_context.cc:154-189``)."""
    from ..core import state as core_state
    from ..frameworks.jax import basics

    basics._internal_reset()
    from .rendezvous_client import refresh_topology_from_rendezvous

    topo = refresh_topology_from_rendezvous()
    _reinit_xla_plane(topo)
    core_state.global_state().initialize(topology=topo)


def _reinit_xla_plane(topo) -> None:
    """Re-establish the XLA data plane for the NEW world (the part SURVEY
    §7.4 flags as hard; reference analog: the Gloo elastic re-rendezvous
    branch, ``gloo_context.cc:154-189``).

    jax refuses ``distributed.initialize`` once backends exist, so the
    sequence is: shut the old multi-controller runtime down, drop the
    backend singletons (old-world device arrays become invalid — elastic
    state lives in host numpy snapshots, so nothing live depends on them),
    then bring the runtime up against a coordinator for THIS epoch.  The
    new rank 0 binds a free port and publishes ``host:port`` to the
    rendezvous store under an epoch-scoped key; everyone else polls it.
    """
    import os

    from ..backend import xla as xla_backend
    from ..common import env as env_mod

    plane = xla_backend.data_plane_requested()
    if plane not in ("xla", "auto"):
        return
    xla_backend.context().reset()
    import jax

    # Tear the OLD world's runtime down whenever one exists — including a
    # shrink to size 1, where a leftover distributed client would keep
    # heartbeating a coordinator that may live on the dead host.
    if xla_backend.jax_distributed_initialized():
        import jax.extend.backend

        jax.distributed.shutdown()
        jax.clear_caches()
        jax.extend.backend.clear_backends()
    elif plane != "xla":
        return  # auto mode never had a device plane; keep TCP

    if topo.size <= 1:
        return  # single survivor: local mesh only, no distributed runtime

    # Epoch-scoped coordinator handoff (the old coordinator host may be
    # the one that died).
    coord = negotiate_jax_coordinator(topo)
    os.environ[env_mod.HOROVOD_JAX_COORDINATOR] = coord
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=topo.size,
                               process_id=topo.rank)
    # Verify the NEW world actually took: a stale backend surviving the
    # clear (or a straggler thread rebuilding it mid-teardown) would
    # otherwise poison every later jax call with the OLD topology and
    # surface as a confusing mismatch deep inside core init.  Fail fast
    # and specific here instead; the run wrapper's retry tears down again.
    if jax.process_count() != topo.size or \
            jax.process_index() != topo.rank:
        raise HorovodInternalError(
            f"jax.distributed re-init did not take: jax reports "
            f"{jax.process_index()}/{jax.process_count()} but the new "
            f"world is {topo.rank}/{topo.size} (stale backend survived "
            f"teardown)")


def negotiate_jax_coordinator(topo) -> str:
    """Publish/fetch the jax.distributed coordinator for THIS elastic
    epoch through the rendezvous store: the new rank 0 binds a free port
    and publishes ``host:port``; everyone else polls.  Epoch-scoped keys
    keep a stale coordinator from a previous incarnation out of play."""
    from ..common import env as env_mod
    from ..common.exceptions import HorovodInternalError
    from ..transport.store import HTTPStoreClient
    from ..transport.tcp import candidate_advertise_addrs

    addr = env_mod.get_str(env_mod.HOROVOD_RENDEZVOUS_ADDR)
    port = env_mod.get_int(env_mod.HOROVOD_RENDEZVOUS_PORT, 0)
    if not addr or not port:
        raise HorovodInternalError(
            "jax coordinator negotiation requires the rendezvous store")
    store = HTTPStoreClient(addr, port)
    epoch = env_mod.get_epoch()
    scope = f"jaxcoord.{epoch}"
    if topo.rank == 0:
        import socket as _socket

        s = _socket.socket()
        s.bind(("", 0))
        coord_port = s.getsockname()[1]
        s.close()  # jax's coordinator service rebinds it immediately
        coord = f"{candidate_advertise_addrs()[0]}:{coord_port}"
        store.set(scope, "addr", coord.encode())
        return coord
    import time as _time

    deadline = _time.monotonic() + 120
    while True:
        raw = store.get(scope, "addr")
        if raw:
            return raw.decode()
        if _time.monotonic() > deadline:
            raise HorovodInternalError(
                "no jax coordinator published for epoch %d" % epoch)
        _time.sleep(0.25)


def _request_epoch_reset(err: BaseException) -> None:
    """Best-effort worker→driver epoch-reset request (elastic jobs only;
    static jobs have no driver and surface the error to the caller).

    Deliberately fires for EVERY HorovodInternalError, not just
    corruption aborts: any all-survivors abort (wire corruption, stall
    shutdown, a deadline trip on a wedged-but-alive peer) leaves no
    process exit for the driver to react to.  When the failure WAS a
    process death, the request can race the driver's exit monitor and
    cost one spurious epoch bump (the dead identity is respawned one
    epoch later) — a bounded waste that self-corrects, accepted over the
    alternative of filtering by error type and silently breaking
    recovery for whichever alive-abort flavor the filter missed."""
    from ..common import env as env_mod

    if not env_mod.get_bool(env_mod.HOROVOD_ELASTIC):
        return
    from .rendezvous_client import request_reset

    request_reset(f"{type(err).__name__}: {err}")


def _sync_for_epoch(state: State) -> None:
    """Post-reinit state sync, reshard-aware (docs/elastic.md "Live
    resharding").

    Legacy path: broadcast everything from rank 0.  Under a
    reshard-marked epoch: a pure shrink (no joiners) skips the sync when
    every survivor stands on the same commit, so the broadcast would
    move zero information.  That has to be checked, not assumed: the
    abort that ended the epoch can reach one rank after its last receive
    of a step and its peer before it (a frame corrupted on the ring's
    final allgather hop: the sender has finished and committed the step
    its receiver rejects), and survivors one commit apart never meet
    again.  With joiners, or with survivors apart, broadcast from
    ``sync_root`` (the lowest SURVIVING rank — rank 0
    itself may be the fresh process being state-filled, which on the
    legacy root-0 rule would broadcast its blank init state over the
    survivors' progress).  The marker is read from the store per
    identity+epoch, so spawned joiners and re-rendezvoused survivors
    agree on the same root without a side channel; any read miss
    degrades to the legacy full sync, never the reverse."""
    from ..common import env as env_mod

    info = None
    if env_mod.get_bool(env_mod.HOROVOD_ELASTIC) and \
            env_mod.get_bool(env_mod.HOROVOD_RESHARD, True):
        from .rendezvous_client import current_reshard_info

        info = current_reshard_info()
    if info is None:
        state.sync()
        return
    from ..core import flight_recorder
    from ..frameworks.jax.functions import allgather_object

    commits = allgather_object(state._commits, name="elastic.commits")
    if not info["joiners"] and len(set(commits)) == 1:
        flight_recorder.record("reshard_sync_skipped", epoch=info["epoch"])
        return
    flight_recorder.record("reshard_sync", epoch=info["epoch"],
                           root=info["sync_root"],
                           joiners=len(info["joiners"]))
    state.sync(root_rank=info["sync_root"])
    state._commits = commits[info["sync_root"]]


def _teardown() -> None:
    """Best-effort runtime teardown; never raises (used between retries)."""
    try:
        from ..frameworks.jax import basics

        basics._internal_reset()
    except Exception:  # noqa: BLE001
        pass


def run(func: Callable) -> Callable:
    """Decorator: retry ``func(state, ...)`` across membership changes
    (reference ``run_fn``, ``common/elastic.py:147-168``).

    Re-initialization failures (rendezvous timeout, mesh rebuild races
    against a concurrent epoch bump) RETRY instead of killing the worker;
    after ``WORKER_REINIT_ATTEMPTS`` consecutive failures the worker exits
    with ``TRANSIENT_EXIT_CODE`` so the driver respawns a fresh process
    rather than blacklisting the host."""

    def wrapper(state: State, *args, **kwargs):
        import sys

        from ..common.logging_util import get_logger
        from ..core.state import global_state
        from .constants import TRANSIENT_EXIT_CODE, WORKER_REINIT_ATTEMPTS

        log = get_logger("horovod_tpu.elastic.run")
        notification_manager.start()
        reset_limit = notification_manager.reset_limit
        resets = 0
        skip_sync = False
        reinit_failures = 0
        pending_reset = False
        while True:
            if not global_state().initialized.is_set():
                try:
                    _reset_and_reinit()
                except (SystemExit, KeyboardInterrupt):
                    raise  # removed from the job / user interrupt
                except BaseException as e:  # noqa: BLE001
                    reinit_failures += 1
                    log.warning("elastic re-init failed (%d/%d): %s",
                                reinit_failures, WORKER_REINIT_ATTEMPTS, e)
                    if reinit_failures >= WORKER_REINIT_ATTEMPTS:
                        log.error("giving up after %d re-init failures; "
                                  "exiting for a driver respawn",
                                  reinit_failures)
                        sys.exit(TRANSIENT_EXIT_CODE)
                    _teardown()
                    continue
                reinit_failures = 0
            if pending_reset:
                # AFTER re-init (reference run_fn order: reset() then
                # on_reset()): handlers see the NEW rank/size — e.g. an
                # ElasticSampler reshards here, which matters on the
                # skip-sync path where sync() won't run to do it.
                state.on_reset()
                pending_reset = False
            try:
                if not skip_sync:
                    _sync_for_epoch(state)
                return func(state, *args, **kwargs)
            except HorovodInternalError as e:
                state.restore()
                skip_sync = False
                # Integrity-plane recovery trigger: a corruption abort
                # (FrameCorruptError / CoordinatedAbortError relaying one)
                # leaves EVERY worker alive, so no exit or host change
                # would ever produce the new epoch the retry below waits
                # for.  Ask the driver for one; stale/duplicate requests
                # are epoch-filtered driver-side, and a dead store just
                # falls back to the slow transient-exit path.
                _request_epoch_reset(e)
            except HostsUpdatedInterrupt as e:
                skip_sync = e.skip_sync
            resets += 1
            if reset_limit is not None and resets >= reset_limit:
                raise RuntimeError(
                    f"Exceeded elastic reset limit ({reset_limit})")
            pending_reset = True
            _teardown()

    return wrapper


class _NotificationManager:
    """Lazily starts the worker-side notification server (reference
    ``elastic/worker.py``: an RPC server the driver pings on host
    changes)."""

    def __init__(self):
        self._started = False
        self.reset_limit: Optional[int] = None

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        from ..common import env as env_mod

        if not env_mod.get_bool(env_mod.HOROVOD_ELASTIC):
            return
        from .worker import start_notification_service

        start_notification_service()
        limit = env_mod.get_int(env_mod.HOROVOD_ELASTIC_RESET_LIMIT, 0)
        self.reset_limit = limit if limit > 0 else None


notification_manager = _NotificationManager()
