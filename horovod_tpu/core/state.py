"""Global runtime state and the background coordination loop.

Role of the reference's ``HorovodGlobalState`` + ``BackgroundThreadLoop`` /
``RunLoopOnce`` (``operations.cc:117, 361-689``) and the ``Enqueue*`` entry
points (``operations.cc:942-1170``): a singleton owning the topology, the
transport, the controller, the tensor queue and the op chains; a background
thread that wakes every cycle, runs one negotiation round, and executes the
agreed responses; framework threads enqueue named tensors with callbacks and
never touch the network.

The process model is one Python process per Horovod rank (per host or per
chip), exactly like ``horovodrun``'s worker processes — the background thread
here is the analog of the reference's C++ background thread, and the
GIL-free sections (socket I/O, numpy kernels) are where the real work
happens.
"""

from __future__ import annotations

import atexit
import queue
import threading
import time
from typing import Callable, List, Optional

import numpy as np

from ..backend import cpu_ring
from ..common import env as env_mod
from ..common import faults
from ..common.exceptions import (
    CoordinatedAbortError,
    HorovodInternalError,
    PeerGoneError,
)
from ..common.logging_util import get_logger
from ..common.topology import ProcessTopology, from_env
from ..transport.select import build_link_mesh
from ..transport.store import HTTPStoreClient, MemoryStore, Store
from ..transport.tcp import TcpMesh
from . import flight_recorder, metrics
from . import timeline as timeline_mod
from .controller import BARRIER_TENSOR_NAME, JOIN_TENSOR_NAME, Controller
from .messages import (
    DataType,
    Request,
    RequestType,
    Response,
    ResponseType,
)
from .operation_manager import OperationManager
from .tensor_queue import Status, TensorQueue, TensorTableEntry

log = get_logger("horovod_tpu.state")


class HorovodGlobalState:
    def __init__(self):
        self.topo: Optional[ProcessTopology] = None
        self.mesh: Optional[TcpMesh] = None
        self.controller: Optional[Controller] = None
        self.tensor_queue = TensorQueue()
        self.op_manager = OperationManager()
        self.initialized = threading.Event()
        self.shutdown_requested = threading.Event()
        self.shutdown_complete = threading.Event()
        # Ends the metrics-and-lease pusher: with the loop, or with a
        # bring-up that failed after the pusher had begun.
        self._push_stop = threading.Event()
        self.joined = False
        self.join_event: Optional[threading.Event] = None
        self.cycle_time_ms = env_mod.DEFAULT_CYCLE_TIME_MS
        self.background: Optional[threading.Thread] = None
        self.init_error: Optional[BaseException] = None
        # Adaptive cycle timing: enqueues set this event so an idle loop
        # wakes immediately instead of sleeping out the cycle; busy cycles
        # skip the sleep entirely (spin-then-park — the cycle_time_ms knob,
        # autotuned by the ParameterManager, becomes the IDLE backstop
        # rather than a floor under every dispatch's latency).
        self._wake = threading.Event()
        self._last_cycle_had_work = False
        # The park the loop last took, in ms, 0 after a round with work
        # (_next_idle_park_ms).  The loop thread's alone.
        self._idle_park_ms = 0.0
        # What phase_stats gets of the idle rounds (negotiate_idle) and of
        # the loop thread's CPU (cpu.loop), kept here until a round has
        # work (_account_loop): an idle round costs two additions, not a
        # clock's system call and two locked updates while the calling
        # thread waits for the interpreter.
        self._idle_rounds = 0
        self._idle_seconds = 0.0
        self._loop_cpu_at = 0.0
        # Pipelined negotiate/dispatch (double-buffered background loop):
        # device-plane responses are handed to a dedicated dispatcher
        # thread so cycle i+1's negotiation overlaps cycle i's XLA dispatch
        # host work.  Host-TCP responses still execute inline (they share
        # the mesh sockets with negotiation; interleaving would cross
        # frames) after a drain barrier, preserving the identical-order
        # dispatch invariant on every rank.
        self._dispatch_queue: Optional[queue.SimpleQueue] = None
        self._dispatch_thread: Optional[threading.Thread] = None
        self._dispatch_inflight = 0
        self._dispatch_cv = threading.Condition()
        self.pipeline_dispatch = True
        self.timeline = None  # attached by core.timeline when enabled
        self.parameter_manager = None  # attached when autotune enabled
        self.cycle_count = 0
        # Finalizer pool (reference gpu_operations.h:98-127 finalizer
        # threads, one per stream via ThreadPool operations.cc:421):
        # completes async device collectives so the negotiation loop never
        # blocks; HOROVOD_NUM_FINALIZER_THREADS (NUM_NCCL_STREAMS analog)
        # lets multiple in-flight fused batches finalize concurrently.
        self._finalizer_pool = None
        # Sticky failure from the eager-complete watchdog (NCCL
        # async-error-watchdog role): raised by the next enqueue.
        self.async_error: Optional[str] = None

    # ------------------------------------------------------------------

    def initialize(self, store: Optional[Store] = None,
                   topology: Optional[ProcessTopology] = None) -> None:
        """``InitializeHorovodOnce`` analog (``operations.cc:693-739``):
        spawn the background thread, block until transport + controller are
        up."""
        if self.initialized.is_set():
            return
        self.async_error = None
        self.topo = topology or from_env()
        self._store = store
        self.cycle_time_ms = env_mod.get_float(
            env_mod.HOROVOD_CYCLE_TIME, env_mod.DEFAULT_CYCLE_TIME_MS)
        # Pipelining pays only when there is negotiation latency to hide;
        # at size 1 it would just add a thread hop per dispatch.
        self.pipeline_dispatch = self.topo.size > 1 and env_mod.get_bool(
            env_mod.HOROVOD_PIPELINE_DISPATCH, True)
        self.tensor_queue.set_wake_event(self._wake)
        self.background = threading.Thread(
            target=self._background_loop, name="horovod-background", daemon=True)
        self.background.start()
        self.initialized.wait()
        if self.init_error is not None:
            # Leave the object retryable: the background thread is dead and
            # nothing must look initialized.
            err, self.init_error = self.init_error, None
            self.initialized.clear()
            self.background = None
            raise HorovodInternalError(f"initialization failed: {err}") from err
        atexit.register(self.shutdown)

    def _build_transport(self) -> None:
        topo = self.topo
        from ..backend import xla as xla_backend

        if xla_backend.data_plane_requested() in ("xla", "auto") \
                and topo.size > 1:
            # jax.distributed must already be up (frameworks.jax.basics
            # initializes it before starting this thread).
            xla_backend.context().initialize(topo)
        else:
            xla_backend.context().reset()
        startup_timeout = env_mod.get_float(
            env_mod.HOROVOD_MESH_STARTUP_TIMEOUT, 60.0)
        epoch = env_mod.get_epoch()
        store = None
        if topo.size == 1:
            self.mesh = None
        else:
            store = self._store
            if store is None:
                addr = env_mod.get_str(env_mod.HOROVOD_RENDEZVOUS_ADDR)
                port = env_mod.get_int(env_mod.HOROVOD_RENDEZVOUS_PORT, 0)
                if not addr or not port:
                    raise HorovodInternalError(
                        "size > 1 requires a rendezvous store "
                        "(HOROVOD_GLOO_RENDEZVOUS_ADDR/PORT, set by the launcher)")
                store = HTTPStoreClient(addr, port)
            # Epoch-scoped keys so elastic re-init never reads stale peer
            # addresses from a previous incarnation of the job.
            # Check-in mark for the launcher's --start-timeout watchdog
            # (reference: workers surface through the rendezvous server and
            # horovodrun aborts if they don't within the timeout).
            store.set("worker_started", str(topo.rank), b"1")
            # The lease is renewed from here on, before the mesh is up: a
            # survivor of an epoch change waits in the mesh's bring-up for
            # a joiner that may take longer to start than a lease lasts,
            # and a live process that the driver declares dead is
            # respawned beside itself.
            self._start_metrics_pusher(store)
            # Per-link transport selection (transport/select.py): shm for
            # intra-host links, TCP cross-host, per HOROVOD_TRANSPORT.
            # Under the "tcp" policy this IS a plain TcpMesh.
            self.mesh = build_link_mesh(
                topo, store, epoch=epoch, timeout=startup_timeout)
        fusion = env_mod.get_int(
            env_mod.HOROVOD_FUSION_THRESHOLD, env_mod.DEFAULT_FUSION_THRESHOLD)
        stall_secs = 0 if env_mod.get_bool(env_mod.HOROVOD_STALL_CHECK_DISABLE) \
            else env_mod.get_float(env_mod.HOROVOD_STALL_CHECK_TIME_SECONDS,
                                   env_mod.DEFAULT_STALL_CHECK_TIME_SECONDS)
        if env_mod.get_bool(env_mod.HOROVOD_AUTOTUNE) and topo.rank == 0:
            from .parameter_manager import ParameterManager

            self.parameter_manager = ParameterManager(
                enabled=True,
                warmup_samples=env_mod.get_int(
                    env_mod.HOROVOD_AUTOTUNE_WARMUP_SAMPLES, 3),
                steps_per_sample=env_mod.get_int(
                    env_mod.HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE, 10),
                initial_fusion_bytes=fusion,
                initial_cycle_ms=self.cycle_time_ms,
                log_path=env_mod.get_str(env_mod.HOROVOD_AUTOTUNE_LOG) or None,
                tune_codec=env_mod.get_bool(env_mod.HOROVOD_AUTOTUNE_CODEC))
        self.controller = Controller(
            topo, self.mesh,
            fusion_threshold_bytes=fusion,
            stall_warning_secs=stall_secs,
            stall_shutdown_secs=env_mod.get_float(
                env_mod.HOROVOD_STALL_SHUTDOWN_TIME_SECONDS, 0.0),
            cache_capacity=env_mod.get_int(env_mod.HOROVOD_CACHE_CAPACITY,
                                           env_mod.DEFAULT_CACHE_CAPACITY),
            parameter_manager=self.parameter_manager)
        # Resolved store (caller-provided OR the HTTP fallback built
        # above) kept for teardown-path writes: the stale-aggregator
        # veto must land BEFORE the abort broadcast tears the job down.
        self._active_store = store
        if store is not None:
            self._sync_controller_topology(store, epoch, startup_timeout)
        timeline_path = env_mod.get_str(env_mod.HOROVOD_TIMELINE)
        if timeline_path:
            # EVERY rank writes a trace (pid = rank; rank 0 keeps the
            # configured path, others get <path>.rankN) so
            # tools/trace_merge.py can build the one cross-rank view; the
            # coordinator-side negotiation lanes still exist only on rank
            # 0 (the message table lives there, reference
            # operations.cc:424-432).
            from .timeline import (
                Timeline,
                estimate_server_clock_offset_ns,
                rank_trace_path,
            )

            self.timeline = Timeline(
                rank_trace_path(timeline_path, topo.rank),
                mark_cycles=env_mod.get_bool(
                    env_mod.HOROVOD_TIMELINE_MARK_CYCLES),
                rank=topo.rank,
                clock_offset_ns=estimate_server_clock_offset_ns())
            if topo.rank == 0:
                self.controller.timeline = self.timeline
        metrics.registry.register_view("controller",
                                       self._controller_metrics_view)
        self._register_default_ops()

    def _sync_controller_topology(self, store, epoch: int,
                                  timeout: float) -> None:
        """Publish rank 0's negotiated controller fan-out through the
        rendezvous store and validate every worker against it.

        The star/tree choice is derived per-rank from
        ``HOROVOD_CONTROLLER_TOPOLOGY``; a multi-host launch with partial
        env propagation could give ranks different answers, and a
        star-vs-tree mismatch deadlocks the first negotiation round with no
        diagnostic (each side recv-blocks on a peer that will never send).
        Making rank 0's choice authoritative-and-checked turns that silent
        hang into a loud bring-up error naming the env fix.

        The negotiation fan-in decision (docs/data_plane.md "Negotiation
        fan-in") rides the same scope: rank 0 resolves the mode, folds in
        any still-cooling stale-aggregator vetoes, and publishes
        ``{"mode": ..., "vetoed": [host indices]}``; workers ADOPT the
        record (no per-rank comparison — the record plus the shared
        topology numbers determine every role arithmetically), then each
        rank installs its FaninPlan before the first cycle.  Mid-epoch
        installs are impossible by construction: the lockstep recv sets
        must agree from cycle one."""
        import json

        from . import negotiation_fanin as fanin_mod

        scope = f"controller.{epoch}"
        chosen = self.controller.fanout_topology
        if self.topo.rank == 0:
            mode = fanin_mod.resolve_mode(self.topo)
            vetoed = self._read_fanin_vetoes(store, epoch) \
                if mode == "on" else []
            decision = {"mode": mode, "vetoed": vetoed}
            store.batch([
                ("set", scope, "topology", chosen.encode()),
                ("set", scope, "fanin", json.dumps(decision).encode()),
            ])
        else:
            try:
                got = store.wait(scope, ["topology", "fanin"],
                                 timeout=timeout)
                agreed = got["topology"].decode()
                decision = json.loads(got["fanin"].decode())
            except Exception as e:  # noqa: BLE001
                raise HorovodInternalError(
                    f"rank {self.topo.rank} could not read rank 0's "
                    f"controller topology/fan-in decision from the "
                    f"rendezvous store: {e}") from e
            if agreed != chosen:
                raise HorovodInternalError(
                    f"controller topology mismatch: rank 0 negotiates over "
                    f"{agreed!r} but rank {self.topo.rank} derived "
                    f"{chosen!r} from its environment — "
                    f"HOROVOD_CONTROLLER_TOPOLOGY (or world size) differs "
                    f"across ranks; propagate the same value to every host "
                    f"(a star/tree mismatch would deadlock the first "
                    f"negotiation round)")
        self._configure_negotiation_fanin(decision, store)

    def _read_fanin_vetoes(self, store, epoch: int) -> List[int]:
        """Cross-rank indices of hosts under an active stale-aggregator
        veto (rank 0 only).  Best-effort end to end — a veto is an
        optimization hint (keep a convicted host off the tree), never a
        correctness dependency, so store trouble or an unresolvable
        hostname silently yields no veto."""
        import json

        from ..transport.scopes import (
            NEGOTIATION_VETO_SCOPE,
            RANK_AND_SIZE_SCOPE,
        )
        from .negotiation_fanin import active_vetoes

        try:
            names = store.keys(NEGOTIATION_VETO_SCOPE)
            if not names:
                return []
            records = {}
            for name in names:
                raw = store.get(NEGOTIATION_VETO_SCOPE, name)
                if raw is not None:
                    records[name] = json.loads(bytes(raw).decode())
            hostnames = active_vetoes(records, epoch)
            if not hostnames:
                return []
            # hostname → host index via the driver's slot table
            # (identities are ``hostname:local_rank`` keys).
            vetoed = set()
            for key in store.keys(RANK_AND_SIZE_SCOPE):
                hostname = key.rsplit(":", 1)[0]
                if hostname not in hostnames:
                    continue
                raw = store.get(RANK_AND_SIZE_SCOPE, key)
                if raw is None:
                    continue
                slot = json.loads(bytes(raw).decode())
                if slot.get("epoch", 0) != epoch or slot.get("rank", -1) < 0:
                    continue
                vetoed.add(int(slot["rank"]) // self.topo.local_size)
            if vetoed:
                log.info("negotiation fan-in: hosts %s run DIRECT this "
                         "epoch (stale-aggregator veto cooldown)",
                         sorted(vetoed))
            return sorted(vetoed)
        except Exception as e:  # noqa: BLE001 — hint, not load-bearing
            log.warning("negotiation fan-in veto read failed (%s); "
                        "no hosts vetoed", e)
            return []

    def _configure_negotiation_fanin(self, decision, store) -> None:
        from . import negotiation_fanin as fanin_mod

        if not decision or decision.get("mode") != "on":
            self.controller.configure_fanin(None)
            return
        plan = fanin_mod.build_plan(self.topo,
                                    decision.get("vetoed") or ())
        job_key = getattr(store, "_base", None) or "in-process"
        heartbeat = fanin_mod.make_heartbeat(plan, self.topo, str(job_key))
        self.controller.configure_fanin(plan, heartbeat)

    def _write_fanin_veto(self, error: BaseException) -> None:
        """Best-effort veto on the way down: a member that convicted its
        aggregator as wedged (AggregatorStaleError) records the verdict
        in the store BEFORE the abort broadcast, so the recovered epoch's
        rank 0 keeps this host on the direct path for the cooldown
        window.  Every failure here is swallowed — the abort must
        proceed, and a lost veto only means the next epoch re-trees (and
        re-convicts within ~1.5 heartbeat periods if still wedged)."""
        from ..common.exceptions import AggregatorStaleError

        if not isinstance(error, AggregatorStaleError):
            return
        store = getattr(self, "_active_store", None)
        if store is None:
            return
        import json

        from ..transport.scopes import NEGOTIATION_VETO_SCOPE

        hostname = env_mod.get_str(env_mod.HOROVOD_HOSTNAME) \
            or f"host-{self.topo.cross_rank}"
        try:
            store.set(NEGOTIATION_VETO_SCOPE, hostname, json.dumps({
                "epoch": env_mod.get_epoch(),
                "aggregator_rank": error.aggregator_rank,
                "reason": str(error)[:300],
            }).encode())
            log.warning("negotiation fan-in veto posted for host %s "
                        "(aggregator rank %d convicted as wedged)",
                        hostname, error.aggregator_rank)
        except Exception as e:  # noqa: BLE001 — teardown must proceed
            log.warning("negotiation fan-in veto write failed: %s", e)

    def _controller_metrics_view(self) -> dict:
        """Metrics-registry view over the controller's fast-path counters
        (registered at init; re-registration on elastic re-init replaces
        the stale closure).  Runs only at snapshot time — the negotiation
        hot path pays nothing for these."""
        c = self.controller
        if c is None:
            return {}
        cycles = max(1, self.cycle_count)
        fast = c.fast_cycle_count + c.idle_fast_cycle_count
        counters = {
            "controller_cycles_total": self.cycle_count,
            "controller_fast_cycles_total": c.fast_cycle_count,
            "controller_idle_fast_cycles_total": c.idle_fast_cycle_count,
            "controller_serialized_requests_total":
                c.serialized_request_count,
            # Negotiation fan-in instrumentation (plain controller ints,
            # folded here so the per-cycle hot path never touches the
            # registry).  Ingress counters exist on every rank but only
            # the coordinator's move; exporting them everywhere keeps the
            # view shape uniform for the aggregating scrape.
            metrics.flat("negotiation_fanin_frames_total", path="tree"):
                c.fanin_tree_frame_count,
            metrics.flat("negotiation_fanin_frames_total", path="direct"):
                c.fanin_direct_frame_count,
            "negotiation_fanin_fallbacks_total": c.fanin_fallback_count,
            "controller_ingress_frames_total": c.ingress_frame_count,
            "controller_ingress_bytes_total": c.ingress_byte_count,
        }
        return {
            "counters": counters,
            "gauges": {"controller_fast_cycle_ratio": fast / cycles},
        }

    def _start_metrics_pusher(self, store) -> None:
        """Periodically push this rank's metrics snapshot to the
        rendezvous KV (``PUT /metrics/rank-N``) so the server's
        ``GET /metrics`` can serve a cross-rank aggregate of a LIVE job,
        and renew this identity's liveness lease on the same cadence
        (``PUT /lease/<identity>`` — the elastic driver's dead-vs-
        partitioned signal, docs/control_plane.md).  The snapshot+lease
        pair rides one batched transaction; with host fan-in enabled
        (``elastic/fanin.py``) colocated ranks hand their pair to the
        host aggregator instead, so the store sees one request per HOST
        per period.  0 disables."""
        period = env_mod.get_float(env_mod.HOROVOD_METRICS_PUSH_SECS,
                                   env_mod.DEFAULT_METRICS_PUSH_SECS)
        if period <= 0 or not metrics.ENABLED:
            return
        import json as json_mod

        from ..elastic import fanin as fanin_mod
        from ..elastic.rendezvous_client import lease_renew_ops

        fanin = fanin_mod.maybe_create(store, period)

        rank = self.topo.rank
        done = self._push_stop = threading.Event()
        identity = (
            f"{env_mod.get_str(env_mod.HOROVOD_HOSTNAME) or 'localhost'}:"
            f"{env_mod.get_int(env_mod.HOROVOD_LOCAL_RANK, 0)}")
        # Store-outage state machine: pushes are best-effort.  Each
        # attempt rebuilds the snapshot (so the NEWEST one is what lands
        # when the store returns — nothing stale is ever replayed), we
        # log once per outage instead of once per period, and the blind
        # window is accumulated into counters the first post-outage
        # snapshot carries out.  Boxed floats: closure-mutable state.
        outage_since = [None]   # monotonic start of the current outage
        counted_upto = [0.0]    # outage seconds already accounted
        renewals = [0]          # lease value must CHANGE every renewal

        def _push() -> None:
            renewals[0] += 1
            snap = metrics.registry.snapshot()
            snap["rank"] = rank
            # Epoch-stamped so the scrape can drop snapshots from
            # ranks that left at an elastic re-rendezvous (their last
            # push would otherwise be served forever).
            snap["epoch"] = env_mod.get_epoch()
            ops = lease_renew_ops(identity, rank, env_mod.get_epoch(),
                                  renewals[0],
                                  json_mod.dumps(snap).encode())
            try:
                # Fan-in first: True means the ops were delivered (or
                # spooled under a live host aggregator); False means no
                # aggregator is alive — push directly, same as before.
                if fanin is None or not fanin.submit(ops):
                    store.batch(ops)
            except Exception as e:  # noqa: BLE001 — a scrape/lease gap
                # must never hurt the job; the store may be restarting.
                now = time.monotonic()
                metrics.inc("lease_renew_failures_total")
                if outage_since[0] is None:
                    outage_since[0] = now
                    log.warning(
                        "rendezvous store unreachable (%s); metrics/lease "
                        "pushes degrade to best-effort until it returns", e)
                else:
                    metrics.inc("store_outage_seconds_total",
                                now - counted_upto[0])
                counted_upto[0] = now
                return
            if outage_since[0] is not None:
                now = time.monotonic()
                metrics.inc("store_outage_seconds_total",
                            now - counted_upto[0])
                log.info("rendezvous store reachable again after %.1fs; "
                         "resuming normal pushes",
                         now - outage_since[0])
                outage_since[0] = None

        def _push_loop() -> None:
            _push()
            while not done.wait(period):
                _push()
            _push()  # final snapshot so short jobs still land one
            if fanin is not None:
                fanin.close()

        threading.Thread(target=_push_loop,
                         name=f"hvd-metrics-push-r{rank}",
                         daemon=True).start()

    def _register_default_ops(self) -> None:
        topo, mesh = self.topo, self.mesh
        self.op_manager = OperationManager()
        # One persistent staging arena shared by every host-side op
        # (reference: one FusionBufferManager in HorovodGlobalState).
        self.fusion_buffers = cpu_ring.FusionBufferManager()
        fbm = self.fusion_buffers
        # XLA device ops lead each chain (reference registration order,
        # operations.cc:145-252: most-specialized backend first); their
        # enabled() checks the negotiated device set, so every rank makes
        # the same choice.
        from ..backend import xla as xla_backend

        self.op_manager.register(
            ResponseType.ALLREDUCE, xla_backend.XlaAllreduce(topo))
        self.op_manager.register(
            ResponseType.ALLGATHER, xla_backend.XlaAllgather(topo))
        self.op_manager.register(
            ResponseType.BROADCAST, xla_backend.XlaBroadcast(topo))
        self.op_manager.register(
            ResponseType.ALLTOALL, xla_backend.XlaAlltoall(topo))
        # Hierarchical ahead of the flat ring (reference chain order,
        # operations.cc:145-252: NCCL-hierarchical before NCCL); applicable()
        # is pure topology, so every rank registers identically.
        if cpu_ring.HierarchicalAllreduce.applicable(topo):
            self.op_manager.register(
                ResponseType.ALLREDUCE,
                cpu_ring.HierarchicalAllreduce(topo, mesh, fbm))
        self.op_manager.register(
            ResponseType.ALLREDUCE, cpu_ring.RingAllreduce(topo, mesh, fbm))
        self.op_manager.register(
            ResponseType.ALLGATHER, cpu_ring.RingAllgather(topo, mesh, fbm))
        self.op_manager.register(
            ResponseType.BROADCAST, cpu_ring.TreeBroadcast(topo, mesh))
        self.op_manager.register(
            ResponseType.ALLTOALL, cpu_ring.PairwiseAlltoall(topo, mesh))
        from ..backend.adasum import AdasumAllreduce, AdasumRingFallback

        # Device VHDD ahead of the host backends (like the reference's
        # AdasumGpu ahead of AdasumMPI, operations.cc registration order).
        self.op_manager.register(
            ResponseType.ADASUM, xla_backend.XlaAdasum(topo))
        self.op_manager.register(
            ResponseType.ADASUM, AdasumAllreduce(topo, mesh, fbm))
        # Non-power-of-two worlds fall back to an averaging ring allreduce
        # (the reference simply rejects them; averaging approximates
        # Adasum's identical-gradient behavior and keeps hvd.Adasum usable).
        self.op_manager.register(
            ResponseType.ADASUM, AdasumRingFallback(topo, mesh, fbm))

    # ------------------------------------------------------------------
    # background loop
    # ------------------------------------------------------------------

    def _background_loop(self) -> None:
        timeline_mod.name_os_thread()
        try:
            self._build_transport()
        except BaseException as e:  # noqa: BLE001
            self.init_error = e
            self._push_stop.set()  # the lease pusher, if it had begun
            self.initialized.set()
            return
        self.initialized.set()

        try:
            self._loop_cpu_at = time.thread_time()
            while True:
                start = time.monotonic()
                # Clear BEFORE popping: an add landing between pop and a
                # clear-afterwards would lose its wakeup.
                self._wake.clear()
                if not self._run_loop_once():
                    self._account_loop()
                    break
                if self._last_cycle_had_work:
                    # Spin: a busy cycle usually has an immediate follow-up
                    # (the next microbatch, unfused stragglers) — skip the
                    # sleep and negotiate again at once.  The blocking TCP
                    # recv provides the backstop: an eager rank parks in
                    # the kernel waiting for its peers, it does not burn
                    # CPU.
                    self._idle_park_ms = 0.0
                    continue
                # Idle: park on the wake event, so an enqueue starts the
                # next negotiation immediately instead of after the residue
                # of a fixed sleep.  The park backs off while nothing
                # arrives (env.DEFAULT_CYCLE_TIME_MS has why).
                self._idle_park_ms = self._next_idle_park_ms()
                left = self._idle_park_ms / 1000.0 \
                    - (time.monotonic() - start)
                if left > 0:
                    self._wake.wait(left)
        except BaseException as e:  # noqa: BLE001
            log.error("background loop died: %s", e, exc_info=True)
            # Sticky failure (NCCL async-watchdog role): the NEXT enqueue on
            # this rank raises the same error a synchronous failure would,
            # so the elastic run_fn retry loop picks it up identically.
            if self.async_error is None:
                self.async_error = str(e)
            self._write_fanin_veto(e)
            self._broadcast_abort(e)
            self._dump_flight_recorder(e)
            self._stop_dispatcher()
            self._fail_all_pending(str(e))
        else:
            # Clean shutdown must also unblock waiters: entries that never
            # negotiated get SHUT_DOWN_ERROR-style callbacks, like the
            # reference draining the tensor table on shutdown.
            self._stop_dispatcher()
            self._fail_all_pending("Horovod has been shut down")
            # Agreed in lockstep, so no member checks the heartbeat again.
            # (A loop that died leaves it: a member that has not heard
            # the abort yet would convict an aggregator of its absence.)
            if self.controller.fanin_heartbeat is not None:
                self.controller.fanin_heartbeat.close()
        finally:
            if self._finalizer_pool is not None:
                # In-flight device work must complete (and fire callbacks)
                # before shutdown is declared done.
                self._finalizer_pool.shutdown(timeout=60)
            if self.mesh is not None:
                self.mesh.close()
            if self.timeline is not None:
                self.timeline.close()
            self._push_stop.set()
            self.shutdown_complete.set()

    def _next_idle_park_ms(self) -> float:
        """How long the loop parks after an idle round: the floor after
        the first idle round that follows work, twice the last park after
        every further one, never above ``cycle_time_ms`` (the
        environment's or the autotuner's), which under the floor is both."""
        return min(self.cycle_time_ms, max(env_mod.IDLE_PARK_FLOOR_MS,
                                           2.0 * self._idle_park_ms))

    def _dump_flight_recorder(self, error: BaseException) -> None:
        """Loop-death post-mortem: dump the flight-recorder ring + metrics
        snapshot (+ held locks under lockdep) to the per-rank JSON.  Runs
        after the abort broadcast — peers must hear the abort within one
        poll quantum; the dump is for the human who arrives later."""
        try:
            path = flight_recorder.recorder.dump(
                f"background loop death: {type(error).__name__}: {error}")
            if path:
                log.error("flight-recorder post-mortem written to %s", path)
        except Exception as e:  # noqa: BLE001 — diagnostics must never
            # mask the error being diagnosed
            log.warning("flight-recorder dump failed: %s", e)

    def _broadcast_abort(self, error: BaseException) -> None:
        """Coordinated abort: tell every surviving peer WHY this rank's
        loop died so they fail loudly with the original reason instead of
        hanging (or timing out) on a silent mesh.  A received
        CoordinatedAbortError is re-broadcast too — that is what propagates
        an abort through tree-mode relays — but with the ORIGIN's identity
        preserved; receivers already aborted ignore duplicates via their
        mesh abort flag."""
        if self.mesh is None:
            return
        try:
            if isinstance(error, CoordinatedAbortError):
                self.mesh.send_abort(error.reason, epoch=error.epoch,
                                     origin_rank=error.origin_rank)
            else:
                self.mesh.send_abort(
                    f"rank {self.topo.rank}: {error}")
        except Exception as e:  # noqa: BLE001 — teardown must proceed
            log.warning("abort broadcast failed: %s", e)

    def _run_loop_once(self) -> bool:
        """One cycle (``RunLoopOnce``, ``operations.cc:595-689``): negotiate,
        then execute every agreed response. Returns False to stop.

        Device-plane responses are handed to the dispatcher thread so this
        loop can start negotiating the next cycle while cycle i's XLA
        dispatch host work runs — the double-buffered schedule.  Everything
        else (host-TCP collectives, which share the mesh with negotiation;
        JOIN/ERROR/BARRIER bookkeeping) executes inline behind a drain
        barrier so the cross-rank execution order stays identical."""
        requests = self.tensor_queue.pop_messages()
        # While this rank announces a tensor or has one waiting for the
        # others, the round's blocked receives are negotiate_recv
        # (controller._blocked_recv).
        self.controller.tensors_in_flight = bool(requests) \
            or self.tensor_queue.size() > 0
        # The profiler's span shows every round, with the number of
        # requests it took, and the step of the first of them.
        with timeline_mod.phase(
                "negotiate", cycle=self.cycle_count + 1,
                requests=len(requests),
                step=getattr(requests[0], "_step", None)
                if requests else None) as span:
            if self.timeline is not None:
                # Tag this round's spans with the lockstep cycle id BEFORE
                # negotiating — the same id names the same global round on
                # every rank (trace_merge matches lanes on it).
                self.timeline.set_cycle(self.cycle_count + 1)
            response_list = self.controller.compute_response_list(
                requests, self.shutdown_requested.is_set())
            if response_list.responses:
                # Where negotiate_wait ends and dispatch_wait begins, for
                # every tensor this round agreed on.
                agreed_at = time.monotonic()
                for response in response_list.responses:
                    response._agreed_at = agreed_at
                span.annotate(agreed=sum(
                    len(r.tensor_names) for r in response_list.responses))
            self.cycle_count += 1
            self._last_cycle_had_work = bool(requests) \
                or bool(response_list.responses)
            metrics.set_gauge("tensor_queue_depth", self.tensor_queue.size())
            # Busy cycles only: timing idle lockstep parks would swamp the
            # negotiate total with waiting, not negotiating.
            span.record = self._last_cycle_had_work
        if self._last_cycle_had_work:
            metrics.observe("controller_cycle_seconds", span.seconds)
            # How far the back-off had grown when work arrived (busy rounds
            # only: an idle round stays two additions).
            metrics.set_gauge("controller_idle_park_ms", self._idle_park_ms)
            flight_recorder.record("cycle", n=self.cycle_count,
                                   requests=len(requests),
                                   responses=len(response_list.responses))
            # Before the responses go out: whoever hears of this round's
            # tensors finds the rounds before it counted.
            self._account_loop()
        else:
            # The rounds negotiate leaves out: its count and negotiate_idle's
            # are the rounds (_account_loop hands these on).
            self._idle_rounds += 1
            self._idle_seconds += span.seconds
        if response_list.tuned_params is not None:
            # Autotuner moved (reference SynchronizeParameters): adopt the
            # broadcast cycle time on every rank.
            self.cycle_time_ms = response_list.tuned_params[1]
        if self.timeline is not None:
            self.timeline.mark_cycle()
        for response in response_list.responses:
            # The cycle this response was negotiated in (pipelined device
            # dispatches execute under the NEXT cycle's negotiation, so
            # the timeline/metrics must not read the live counter).
            response._cycle = self.cycle_count
            if self.pipeline_dispatch and self._device_plane_response(response):
                self._dispatch_async(response)
            else:
                self._dispatch_drain()
                self._perform_operation(response)
        if response_list.shutdown:
            return False
        return True

    def _account_loop(self) -> None:
        """Into phase_stats, in every round that had work and when the loop
        ends: the idle rounds since the last call (``negotiate_idle``) and
        this thread's CPU seconds since then (``cpu.loop``: by
        ``time.thread_time()``, not wall time; the rounds, what they ran
        inline and the parks between them)."""
        if self._idle_rounds:
            timeline_mod.phase_stats.add(
                "negotiate_idle", self._idle_seconds, n=self._idle_rounds)
            self._idle_rounds, self._idle_seconds = 0, 0.0
        cpu = time.thread_time()
        timeline_mod.phase_stats.add("cpu.loop", cpu - self._loop_cpu_at)
        self._loop_cpu_at = cpu

    def _device_plane_response(self, response: Response) -> bool:
        """True when this response will execute on the XLA device plane
        (safe to dispatch from the pipeline thread: it never touches the
        TCP mesh the negotiation loop is using).  Mirrors the op chain's
        enabled() preconditions; any response this misjudges simply takes
        the inline path after a drain — correctness is unaffected, only
        overlap."""
        from ..backend import xla as xla_backend

        if response.response_type not in (
                ResponseType.ALLREDUCE, ResponseType.ALLGATHER,
                ResponseType.BROADCAST, ResponseType.ALLTOALL,
                ResponseType.ADASUM):
            return False
        if response.devices != [xla_backend.XLA_DEVICE_ID]:
            return False
        if not xla_backend.context().ready:
            return False
        if self.joined:
            # Zero-substituted entries are host buffers; the op chain will
            # fall back to the TCP ring on this rank.
            return False
        if response.response_type == ResponseType.ADASUM:
            p = self.topo.size
            if p & (p - 1):
                return False  # XlaAdasum needs a power-of-two world
        return True

    # -- pipelined dispatcher -------------------------------------------

    def _dispatch_async(self, response: Response) -> None:
        if self._dispatch_thread is None or not self._dispatch_thread.is_alive():
            self._dispatch_queue = queue.SimpleQueue()
            self._dispatch_thread = threading.Thread(
                target=self._dispatch_loop, name="horovod-dispatch",
                daemon=True)
            self._dispatch_thread.start()
        with self._dispatch_cv:
            self._dispatch_inflight += 1
        self._dispatch_queue.put(response)

    def _dispatch_loop(self) -> None:
        timeline_mod.name_os_thread()
        cpu_at = time.thread_time()
        while True:
            response = self._dispatch_queue.get()
            if response is None:
                return
            # dispatch_wait starts where negotiate_wait ended.
            timeline_mod.phase_stats.add(
                "dispatch_wait", time.monotonic() - response._agreed_at)
            try:
                self._perform_operation(response, require_device=True)
            except BaseException as e:  # noqa: BLE001 — the negotiation
                # loop must survive a dispatch failure; entries' callbacks
                # already fired with an error inside _perform_operation for
                # op-level faults, so anything reaching here is
                # infrastructure — surface it like an async device error.
                log.error("pipelined dispatch failed: %s", e, exc_info=True)
                self.async_error = f"pipelined dispatch failed: {e}"
            finally:
                # cpu.dispatch: this thread's CPU seconds, not wall time,
                # a response (the blocked get costs none).
                cpu = time.thread_time()
                timeline_mod.phase_stats.add("cpu.dispatch", cpu - cpu_at)
                cpu_at = cpu
                with self._dispatch_cv:
                    self._dispatch_inflight -= 1
                    if self._dispatch_inflight == 0:
                        self._dispatch_cv.notify_all()

    def _dispatch_drain(self, timeout: float = 300.0,
                        must_drain: bool = True) -> None:
        """Barrier: wait until every queued device dispatch has been issued
        (NOT until the device finished — completion stays with the
        finalizer).  Precedes any inline execution so the per-rank
        dispatch order stays the negotiated order.

        A drain timeout with ``must_drain`` RAISES: proceeding would run a
        host op out of order against a still-queued device dispatch and
        silently desync the cross-rank dispatch sequence — a loud loop
        failure (which fails every pending entry) is strictly better."""
        with self._dispatch_cv:
            drained = self._dispatch_cv.wait_for(
                lambda: self._dispatch_inflight == 0, timeout=timeout)
        if not drained and must_drain:
            raise HorovodInternalError(
                f"pipelined dispatch did not drain within {timeout:.0f}s "
                f"({self._dispatch_inflight} responses still in flight); "
                "refusing to execute a host op out of dispatch order")

    def _stop_dispatcher(self) -> None:
        # Shutdown path: a wedged dispatch must not mask the original
        # failure — log and move on rather than raise.
        try:
            self._dispatch_drain(timeout=60.0)
        except HorovodInternalError as e:
            log.error("dispatcher did not drain at shutdown: %s", e)
        if self._dispatch_thread is not None \
                and self._dispatch_thread.is_alive():
            self._dispatch_queue.put(None)
            self._dispatch_thread.join(timeout=10)
        self._dispatch_thread = None

    def _perform_operation(self, response: Response,
                           require_device: bool = False) -> None:
        """``PerformOperation`` analog (``operations.cc:256-336``).

        ``require_device`` is set on the pipelined-dispatch path: a
        response routed there must execute on the XLA plane — running a
        host-TCP op from the dispatcher thread would interleave frames
        with the concurrent negotiation on the same mesh sockets, so a
        mis-route fails the entries cleanly instead of executing."""
        if faults.ACTIVE:
            faults.inject("dispatch.collective",
                          rank=self.topo.rank if self.topo else None)
        if response.response_type == ResponseType.JOIN:
            self.joined = False
            if self.join_event is not None:
                self.join_event.set()
                self.join_event = None
            return

        entries = self.tensor_queue.get_entries_for_response(response)

        # Lifecycle spans: close each tensor's LC_SUBMITTED (opened at
        # enqueue) and stamp the cycle-tagged LC_NEGOTIATED instant.
        # Zero-substituted entries (built below) never enqueued, so they
        # correctly get neither.  negotiate_wait closes here too: from the
        # round that took this rank's request to the round that agreed.
        agreed_at = getattr(response, "_agreed_at", None)
        if agreed_at is not None:
            waits = [agreed_at - e.announced_at for e in entries
                     if e.announced_at is not None]
            if waits:
                timeline_mod.phase_stats.add("negotiate_wait", sum(waits),
                                             n=len(waits))
        if timeline_mod.ACTIVE is not None and timeline_mod.LIFECYCLE_ENABLED:
            cyc = getattr(response, "_cycle", None)
            for e in entries:
                timeline_mod.lifecycle_end(e.tensor_name, "LC_SUBMITTED")
                timeline_mod.lifecycle_instant(e.tensor_name, "LC_NEGOTIATED",
                                               cycle=cyc)

        if response.response_type == ResponseType.ERROR:
            for e in entries:
                e.callback(Status.error(response.error_message), e)
            return

        if response.response_type == ResponseType.BARRIER:
            for e in entries:
                e.callback(Status.OK(), e)
            return

        # Zero-substitution: a joined rank executes collectives it never
        # submitted, contributing zeros (reference tensor_queue.h:39-41).
        if len(entries) != len(response.tensor_names):
            by_name = {e.tensor_name: e for e in entries}
            aligned: List[TensorTableEntry] = []
            for i, name in enumerate(response.tensor_names):
                if name in by_name:
                    aligned.append(by_name[name])
                else:
                    n = response.tensor_sizes[i] if i < len(response.tensor_sizes) else 0
                    aligned.append(cpu_ring.zero_entry_for(response, i, 0, n))
            entries = aligned

        if require_device:
            from ..backend.xla import XlaOp

            op = self.op_manager.select(response, entries)
            if not isinstance(op, XlaOp):
                for e in entries:
                    self._fire_callback(e, Status.error(
                        "pipelined dispatch expected a device-plane op for "
                        f"{response.response_type.name} but the chain "
                        f"selected {type(op).__name__}; host ops cannot run "
                        "concurrently with negotiation"))
                return
        if self.timeline is not None:
            self.timeline.op_start(response, entries)
        t_op = time.monotonic()
        try:
            # Every phase of this dispatch carries the step of the
            # optimizer update that submitted the tensors, and the cycle.
            with timeline_mod.span_ids(
                    step=entries[0].step if entries else None,
                    cycle=getattr(response, "_cycle", None)):
                status = self.op_manager.execute(response, entries)
        except (PeerGoneError, CoordinatedAbortError) as e:
            # A dead mesh is FATAL, not an entry-level error: if this rank
            # kept cycling, its next negotiation frames would be consumed
            # by peers still blocked mid-collective on the same sockets —
            # positional framing desyncs and survivors read control bytes
            # as tensor data.  Fail THIS response's entries first (they
            # were already popped from the tensor queue, so the loop-death
            # _fail_all_pending sweep cannot see them — skipping this
            # strands their waiters), then re-raise so the background loop
            # dies, broadcasts the coordinated abort, and fails everything
            # still queued.
            for en in entries:
                self._fire_callback(en, Status.error(str(e)))
            raise
        except HorovodInternalError as e:
            status = Status.error(str(e))
        except Exception as e:  # noqa: BLE001
            log.error("op execution failed: %s", e, exc_info=True)
            status = Status.error(f"{type(e).__name__}: {e}")
        if self.timeline is not None:
            # For async (pending) ops this marks dispatch end; completion
            # happens on the finalizer thread.
            self.timeline.op_end(response, entries)
        if status.ok:
            self._record_collective_latency(response,
                                            time.monotonic() - t_op)
        if status.pending:
            # Async device work dispatched: a finalizer-pool worker waits
            # for readiness, so this loop moves straight on to the next
            # negotiation cycle.  In eager_complete mode (XLA plane:
            # outputs are immutable jax futures) the callbacks fire NOW
            # with unready arrays — downstream jax work chains on array
            # readiness without a host round trip — and the finalizer
            # degrades to a failure watchdog (sticky error surfaced on the
            # next enqueue, the NCCL async-watchdog design).
            if self._finalizer_pool is None:
                from .thread_pool import ThreadPool

                self._finalizer_pool = ThreadPool(
                    env_mod.get_int(env_mod.HOROVOD_NUM_FINALIZER_THREADS, 1),
                    name="horovod-finalizer")
            if status.eager_complete:
                for e in entries:
                    self._fire_callback(e, Status.OK())
                self._finalizer_pool.execute(
                    lambda ents=entries: self._watch_entries(ents))
            else:
                self._finalizer_pool.execute(
                    lambda ents=entries: self._finalize_entries(ents))
            return
        for e in entries:
            timeline_mod.lifecycle_begin(e.tensor_name, "LC_CALLBACK")
            e.callback(status, e)
            timeline_mod.lifecycle_end(e.tensor_name, "LC_CALLBACK")

    _TIMED_RESPONSES = (ResponseType.ALLREDUCE, ResponseType.ALLGATHER,
                        ResponseType.BROADCAST, ResponseType.ALLTOALL,
                        ResponseType.ADASUM)

    def _record_collective_latency(self, response: Response,
                                   seconds: float) -> None:
        """Per-collective latency histogram by op/dtype/size bucket.  For
        host-plane ops this is dispatch-to-done; device-async ops record
        the host dispatch cost (device completion belongs to the
        finalizer) — the catalog documents the distinction."""
        if not metrics.ENABLED \
                or response.response_type not in self._TIMED_RESPONSES \
                or response.tensor_type is None:
            return
        # _payload_bytes (coordinator-computed, controller.py) is the true
        # byte count — ALLGATHER/ALLTOALL tensor_sizes are first dims /
        # splits, not element counts.  The wire Response doesn't carry it,
        # so worker ranks fall back to the flat-sum approximation (exact
        # for ALLREDUCE/ADASUM/BROADCAST, a lower bound for the others —
        # same compromise _fuse_responses makes).
        nbytes = getattr(
            response, "_payload_bytes",
            sum(response.tensor_sizes) * response.tensor_type.itemsize)
        metrics.observe(
            "collective_latency_seconds", seconds,
            op=response.response_type.name,
            dtype=response.tensor_type.name,
            size=metrics.size_bucket_label(nbytes))

    @staticmethod
    def _fire_callback(e, status) -> None:
        timeline_mod.lifecycle_begin(e.tensor_name, "LC_CALLBACK")
        try:
            e.callback(status, e)
        except Exception:  # noqa: BLE001 — a raising callback must not
            # kill the dispatching thread (later collectives would strand
            # on unfired callbacks)
            log.error("callback for %r raised", e.tensor_name, exc_info=True)
        finally:
            timeline_mod.lifecycle_end(e.tensor_name, "LC_CALLBACK")

    @staticmethod
    def _finalize_entries(entries) -> None:
        try:
            import jax

            jax.block_until_ready(
                [e.output for e in entries if e.output is not None])
            status = Status.OK()
        except Exception as e:  # noqa: BLE001
            status = Status.error(f"XLA collective failed: {e}")
        for e in entries:
            HorovodGlobalState._fire_callback(e, status)

    def _watch_entries(self, entries) -> None:
        """Failure watchdog for eager-complete dispatches: callbacks
        already fired with unready arrays; here we only wait for the
        device and convert an async failure into a sticky error that the
        next enqueue raises (elastic's retry loop picks it up exactly
        like a synchronous collective failure)."""
        try:
            import jax

            jax.block_until_ready(
                [e.output for e in entries if e.output is not None])
        except Exception as e:  # noqa: BLE001
            names = ", ".join(en.tensor_name for en in entries[:3])
            log.error("async XLA collective failed (%s...): %s", names, e)
            self.async_error = f"async XLA collective failed: {e}"

    def _fail_all_pending(self, msg: str) -> None:
        # Close first: an add racing the drain must fail fast, not strand.
        self.tensor_queue.close()
        for name in self.tensor_queue.names():
            entry = self.tensor_queue.remove(name)
            if entry is not None:
                entry.callback(Status.error(msg), entry)
        # A thread blocked in hvd.join() must not sleep forever either.
        if self.join_event is not None:
            self.joined = False
            self.join_event.set()
            self.join_event = None

    # ------------------------------------------------------------------
    # framework-facing enqueue API (EnqueueTensor*, operations.cc:942-1170)
    # ------------------------------------------------------------------

    def _stage_tensor(self, tensor):
        """(tensor, device_id): keep jax arrays on-device when the XLA data
        plane is (or can be lazily made) ready; host numpy otherwise."""
        from ..backend import xla as xla_backend

        if xla_backend.is_jax_array(tensor):
            ctx = xla_backend.context()
            if not ctx.ready and self.topo.size == 1:
                # Single-process mesh is always safe; build it lazily the
                # first time a device tensor shows up (avoids touching jax
                # device state for numpy-only users).
                ctx.initialize(self.topo)
            if ctx.ready:
                if not getattr(tensor, "is_fully_addressable", True):
                    # Replicated cross-process arrays (e.g. a previous
                    # collective result fed straight back in) enter as
                    # this rank's full local copy — the fuse jit is a
                    # local computation.  A SHARDED global array has no
                    # local equivalent: substituting the shard would
                    # silently reduce shards instead of the value.
                    if getattr(tensor.sharding, "is_fully_replicated",
                               False):
                        tensor = xla_backend._localize(tensor)
                    else:
                        raise HorovodInternalError(
                            "a non-replicated multi-process global array "
                            "was passed to an eager collective; gather or "
                            "reshard it first (eager ops operate on each "
                            "rank's local value).")
                return tensor, xla_backend.XLA_DEVICE_ID
        return np.asarray(tensor), -1

    def _check_initialized(self) -> None:
        if not self.initialized.is_set() or self.topo is None:
            raise HorovodInternalError(
                "horovod_tpu has not been initialized; call hvd.init() first.")
        if self.async_error is not None:
            raise HorovodInternalError(self.async_error)
        if self.init_error is not None:
            raise HorovodInternalError(f"initialization failed: {self.init_error}")
        if self.shutdown_complete.is_set() or \
                (self.background is not None and not self.background.is_alive()):
            # The loop died (peer failure / shutdown): enqueues must fail
            # fast — nothing will ever complete them.  Elastic's run
            # wrapper turns this into a rollback + re-init.
            raise HorovodInternalError(
                "Horovod background loop is not running (shut down or "
                "failed); reinitialize before submitting collectives")

    def enqueue_allreduce(self, name: str, tensor: np.ndarray,
                          callback: Callable[[Status], None],
                          prescale_factor: float = 1.0,
                          postscale_factor: float = 1.0,
                          op: RequestType = RequestType.ALLREDUCE) -> None:
        self._check_initialized()
        tensor, device = self._stage_tensor(tensor)
        entry = TensorTableEntry(
            tensor_name=name, tensor=tensor, callback=callback,
            request_type=op, device=device,
            prescale_factor=prescale_factor, postscale_factor=postscale_factor)
        req = Request(
            request_rank=self.topo.rank, request_type=op,
            tensor_name=name, tensor_type=DataType.from_numpy(tensor.dtype),
            tensor_shape=list(tensor.shape), device=device,
            prescale_factor=prescale_factor, postscale_factor=postscale_factor)
        self.tensor_queue.add(entry, req)

    def enqueue_allgather(self, name: str, tensor: np.ndarray,
                          callback: Callable[[Status], None]) -> None:
        self._check_initialized()
        tensor, device = self._stage_tensor(tensor)
        if device == -1:
            tensor = np.atleast_1d(tensor)
        elif tensor.ndim == 0:
            tensor = tensor.reshape(1)
        entry = TensorTableEntry(tensor_name=name, tensor=tensor,
                                 callback=callback, device=device,
                                 request_type=RequestType.ALLGATHER)
        req = Request(
            request_rank=self.topo.rank, request_type=RequestType.ALLGATHER,
            tensor_name=name, tensor_type=DataType.from_numpy(tensor.dtype),
            tensor_shape=list(tensor.shape), device=device)
        self.tensor_queue.add(entry, req)

    def enqueue_broadcast(self, name: str, tensor: np.ndarray, root_rank: int,
                          callback: Callable[[Status], None]) -> None:
        self._check_initialized()
        tensor, device = self._stage_tensor(tensor)
        entry = TensorTableEntry(tensor_name=name, tensor=tensor,
                                 root_rank=root_rank, callback=callback,
                                 device=device,
                                 request_type=RequestType.BROADCAST)
        req = Request(
            request_rank=self.topo.rank, request_type=RequestType.BROADCAST,
            tensor_name=name, tensor_type=DataType.from_numpy(tensor.dtype),
            tensor_shape=list(tensor.shape), root_rank=root_rank,
            device=device)
        self.tensor_queue.add(entry, req)

    def enqueue_alltoall(self, name: str, tensor: np.ndarray,
                         splits: Optional[List[int]],
                         callback: Callable[[Status], None]) -> None:
        self._check_initialized()
        tensor, device = self._stage_tensor(tensor)
        if device == -1:
            tensor = np.atleast_1d(tensor)
        elif tensor.ndim == 0:
            tensor = tensor.reshape(1)
        if splits is None:
            if tensor.shape[0] % self.topo.size != 0:
                raise ValueError(
                    f"alltoall first dim {tensor.shape[0]} not divisible by "
                    f"size {self.topo.size}; pass explicit splits")
            splits = [tensor.shape[0] // self.topo.size] * self.topo.size
        entry = TensorTableEntry(tensor_name=name, tensor=tensor,
                                 splits=list(splits), callback=callback,
                                 device=device,
                                 request_type=RequestType.ALLTOALL)
        req = Request(
            request_rank=self.topo.rank, request_type=RequestType.ALLTOALL,
            tensor_name=name, tensor_type=DataType.from_numpy(tensor.dtype),
            tensor_shape=list(tensor.shape), splits=list(splits),
            device=device)
        self.tensor_queue.add(entry, req)

    def enqueue_join(self) -> threading.Event:
        """Rank is done with its data: contribute zeros until everyone joins
        (``EnqueueJoin``, ``operations.cc:1146-1170``)."""
        self._check_initialized()
        event = threading.Event()
        if self.topo.size == 1:
            event.set()
            return event
        self.joined = True
        self.join_event = event
        req = Request(request_rank=self.topo.rank, request_type=RequestType.JOIN,
                      tensor_name=JOIN_TENSOR_NAME)
        # JOIN carries no tensor entry; push the request directly.
        self.tensor_queue.push_messages([req])
        if self.shutdown_complete.is_set():
            # Loop died between the liveness check and the push: unblock.
            event.set()
        return event

    def enqueue_barrier(self, callback: Callable[[Status], None],
                        name: Optional[str] = None) -> None:
        self._check_initialized()
        name = name or BARRIER_TENSOR_NAME
        entry = TensorTableEntry(tensor_name=name, callback=callback,
                                 tensor=np.zeros(0, dtype=np.uint8),
                                 request_type=RequestType.BARRIER)
        req = Request(request_rank=self.topo.rank,
                      request_type=RequestType.BARRIER, tensor_name=name)
        self.tensor_queue.add(entry, req)

    # ------------------------------------------------------------------

    def shutdown(self) -> None:
        """Graceful global shutdown (``horovod_shutdown``,
        ``operations.cc:752-778``)."""
        if not self.initialized.is_set() or self.shutdown_complete.is_set():
            return
        self.shutdown_requested.set()
        # Leaving does not wait out a grown park.
        self._wake.set()
        self.shutdown_complete.wait(timeout=60)
        try:
            atexit.unregister(self.shutdown)
        except Exception:  # noqa: BLE001
            pass

    def reset(self) -> None:
        """Forget everything — used between elastic re-initializations and
        by tests."""
        self.shutdown()
        self.__init__()  # type: ignore[misc]


_global_state = HorovodGlobalState()


def global_state() -> HorovodGlobalState:
    return _global_state


def abort_for_reshard(epoch: Optional[int] = None) -> None:
    """Prompt-abort hook for a reshard-marked notify ping (elastic
    worker service → here): flip this rank's mesh abort flag and relay
    the abort, so a survivor blocked in a collective on a dead peer
    raises ``CoordinatedAbortError`` within one poll quantum instead of
    riding out the TCP progress deadline — the dominant term in legacy
    churn-to-first-step latency.  Best-effort by contract (the retry
    wrapper's normal reset path is the backstop) and epoch-filtered:
    a ping carrying an epoch ≤ the one we already run at is stale
    (the same consume-time staleness rule ``notify_hosts_updated``
    applies) and must not poison the CURRENT world's collectives."""
    from ..common import env as env_mod

    if epoch is not None and epoch <= env_mod.get_epoch():
        return
    st = _global_state
    if st.mesh is None or not st.initialized.is_set():
        return
    try:
        st.mesh.send_abort(
            f"elastic reshard to epoch {epoch}: re-rendezvous in place")
        # A parked loop meets the flag in its next round: now, not after
        # what is left of a grown park.
        st._wake.set()
    except Exception as e:  # noqa: BLE001 — best-effort fast path; the
        # progress deadline still unblocks the slow way
        log.debug("reshard abort broadcast failed: %s", e)


def reset_global_state() -> HorovodGlobalState:
    global _global_state
    _global_state.reset()
    _global_state = HorovodGlobalState()
    return _global_state
