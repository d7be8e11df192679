"""The coordination controller — global agreement on which named tensors are
ready everywhere, every cycle.

Role of the reference's ``horovod/common/controller.cc:97-525``
(``ComputeResponseList``) with the rank-0 coordinator protocol documented at
``controller.h:68-103``:

  1. every rank drains its TensorQueue into a RequestList;
  2. workers send their lists to rank 0 (the coordinator); rank 0 tallies
     per-tensor readiness in a MessageTable (``IncrementTensorCount``,
     ``controller.cc:1030-1053``);
  3. when a tensor has been requested by every (non-joined) rank, the
     coordinator validates cross-rank consistency and builds a Response
     (``ConstructResponse``, ``controller.cc:547-824``);
  4. completed responses are fused under the fusion threshold
     (``FuseResponses``, ``controller.cc:859-998``) and broadcast back;
  5. every rank executes the ResponseList in identical order.

The reference implements step 2/4 with MPI gather/bcast or gloo
allgatherv/broadcast (tree-structured inside those libraries); ours run
over the self-contained ``TcpMesh`` with a choice of fan-out
(``HOROVOD_CONTROLLER_TOPOLOGY=star|tree|auto``): the star does a
sequential recv/send loop at rank 0 (lowest latency at small P), the
binomial tree relays gather bundles / response broadcasts through
O(log P) levels (rank-0 cost stops growing linearly with P).  ``auto``
switches at ``TREE_TOPOLOGY_THRESHOLD``, set by
``benchmarks/controller_bench.py`` measurement.

Also here: Join bookkeeping (zero-substitution for finished ranks) and the
stall inspector hook.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from ..common import faults
from ..common.logging_util import get_logger
from . import flight_recorder
from ..common.topology import ProcessTopology
from ..transport.tcp import TcpMesh
from . import metrics
from . import timeline as timeline_mod
from .messages import (
    DataType,
    HostMaskFrame,
    MaskFrame,
    Request,
    RequestList,
    RequestType,
    Response,
    ResponseList,
    ResponseType,
    is_host_mask_frame,
    is_mask_frame,
)

log = get_logger("horovod_tpu.controller")

JOIN_TENSOR_NAME = "__join__"
BARRIER_TENSOR_NAME = "__barrier__"

#: World size at which ``HOROVOD_CONTROLLER_TOPOLOGY=auto`` switches from
#: the star to the binomial tree.  Set by measurement
#: (``benchmarks/controller_bench.py``): the star's O(P) serial recv/send
#: at the coordinator crosses the tree's O(log P) depth around this size
#: for control-plane-sized messages.
TREE_TOPOLOGY_THRESHOLD = 64


def tree_parent(rank: int) -> int:
    """Binomial-tree parent rooted at 0: clear the lowest set bit
    (the role MPI's internal gather/bcast trees play for the reference,
    ``mpi_controller.cc:108-162``)."""
    return rank & (rank - 1)


def tree_children(rank: int, size: int) -> List[int]:
    """Binomial-tree children of ``rank`` in a ``size``-rank job: rank+2^k
    for every power of two below rank's lowest set bit (all powers for
    the root), capped by size."""
    low = (rank & -rank) if rank else size
    children, bit = [], 1
    while bit < low and rank + bit < size:
        children.append(rank + bit)
        bit <<= 1
    return children


def _encode_bundle(entries: List[tuple]) -> bytes:
    """[(rank, payload)] → wire bytes for the up-tree gather."""
    parts = [len(entries).to_bytes(4, "little")]
    for rank, payload in entries:
        parts.append(rank.to_bytes(4, "little"))
        parts.append(len(payload).to_bytes(4, "little"))
        parts.append(payload)
    return b"".join(parts)


def _decode_bundle(data: bytes) -> List[tuple]:
    n = int.from_bytes(data[:4], "little")
    entries, off = [], 4
    for _ in range(n):
        rank = int.from_bytes(data[off:off + 4], "little")
        ln = int.from_bytes(data[off + 4:off + 8], "little")
        entries.append((rank, data[off + 8:off + 8 + ln]))
        off += 8 + ln
    return entries


@dataclass
class _TableEntry:
    requests: List[Request] = field(default_factory=list)
    ranks: Set[int] = field(default_factory=set)
    first_seen: float = field(default_factory=time.monotonic)
    # When the MEDIAN announcer became ready (the instant half the active
    # ranks had tallied): the straggler detector measures the remaining
    # ranks' lag from here, not from first_seen, so one early rank cannot
    # smear everyone else as "behind".
    majority_seen: Optional[float] = None


class DemotionPolicy:
    """Chronic-straggler verdict state machine (pure; no I/O, no clocks).

    Promotes the per-cycle straggler *flag* to a demotion *verdict*: a
    rank whose readiness-lag EWMA stays above ``demote_secs`` for
    ``demote_cycles`` consecutive busy cycles is chronically slow and
    worth shedding.  Three safety properties are built in:

    - **Hysteresis window**: one streak counter per rank, reset the
      moment its EWMA dips back under the threshold — a transient stall
      can never accumulate a verdict across gaps.
    - **Whole-world-slow guard**: when half or more of the active ranks
      are over threshold, the mesh is globally stalled (GC pause, shared
      NFS hiccup, coordinator overload) and *nobody* is demoted; all
      streaks reset so the stall doesn't seed later verdicts.  At
      np <= 2 one slow rank IS half the world, so demotion needs at
      least 3 active ranks — by construction, not by special case.
    - **One demotion per epoch**: a misconfigured threshold demotes at
      most one host before the epoch advances and the world is
      re-evaluated; it cannot cascade the fleet to zero.

    Fed by ``Controller._update_stragglers`` on busy cycles only (idle
    cycles stamp no majorities, so "consecutive cycles" means cycles
    that actually measured lag).  ``docs/elastic.md`` has the diagram.
    """

    def __init__(self, demote_secs: float, demote_cycles: int):
        if demote_cycles < 1:
            raise ValueError(
                f"HOROVOD_STRAGGLER_DEMOTE_CYCLES={demote_cycles!r}: "
                "expected >= 1")
        self.demote_secs = demote_secs
        self.demote_cycles = demote_cycles
        self._streak: Dict[int, int] = {}
        self._demoted_epochs: Set[int] = set()

    @property
    def enabled(self) -> bool:
        return self.demote_secs > 0.0

    def observe(self, epoch: int, ewma: Dict[int, float],
                active: Set[int]) -> Optional[int]:
        """One busy cycle's EWMA snapshot → the rank to demote, or None.

        Marks the epoch demoted when it returns a victim; callers own
        delivering the verdict (the coordinator posts it to the driver).
        """
        if not self.enabled or not active:
            return None
        over = {r for r in active if ewma.get(r, 0.0) > self.demote_secs}
        if not over or 2 * len(over) >= len(active):
            # Nothing chronic, or the whole world is slow — either way no
            # rank is individually at fault this cycle.
            self._streak.clear()
            return None
        for r in [r for r in self._streak if r not in over]:
            del self._streak[r]
        for r in over:
            self._streak[r] = self._streak.get(r, 0) + 1
        if epoch in self._demoted_epochs:
            return None
        chronic = [r for r in over if self._streak[r] >= self.demote_cycles]
        if not chronic:
            return None
        victim = max(chronic, key=lambda r: ewma.get(r, 0.0))
        self._demoted_epochs.add(epoch)
        self._streak.pop(victim, None)
        return victim


class Controller:
    def __init__(self, topology: ProcessTopology, mesh: Optional[TcpMesh],
                 fusion_threshold_bytes: int = 64 * 1024 * 1024,
                 stall_warning_secs: float = 60.0,
                 stall_shutdown_secs: float = 0.0,
                 cache_capacity: int = 1024,
                 parameter_manager=None):
        from .response_cache import CoordinatorCache, WorkerCacheMirror

        self.topo = topology
        self.mesh = mesh
        self.fusion_threshold = fusion_threshold_bytes
        self.stall_warning_secs = stall_warning_secs
        self.stall_shutdown_secs = stall_shutdown_secs
        self._message_table: Dict[str, _TableEntry] = {}
        self._joined_ranks: Set[int] = set()
        self._last_stall_check = time.monotonic()
        self.timeline = None  # coordinator-side negotiation lanes
        self.param_manager = parameter_manager
        # Cache fast path (response_cache.py): coordinator owns assignments,
        # workers mirror keys; disabled when capacity <= 0.
        self.cache_enabled = cache_capacity > 0 and topology.size > 1
        self._cache = CoordinatorCache(cache_capacity) \
            if self.cache_enabled and topology.rank == 0 else None
        self._mirror = WorkerCacheMirror() \
            if self.cache_enabled and topology.rank != 0 else None
        self._cycle_assignments: List[tuple] = []
        self._cycle_evictions: List[int] = []
        self.cache_hit_count = 0
        self.cache_miss_count = 0
        # Fast-path accounting (tests + benchmarks assert against these):
        # fast_cycle_count counts mask-only cycles that COMPLETED at least
        # one tensor (idle polling cycles also ride the compact frames but
        # would swamp the metric, so they count separately), and
        # serialized_request_count is the number of Requests this rank
        # ever put on / took off the wire.
        self.fast_cycle_count = 0
        self.idle_fast_cycle_count = 0
        self.mask_only_sent_count = 0
        self.serialized_request_count = 0
        # Mask fast path (coordinator): per-rank pending cache-bit masks,
        # aggregated with big-int AND/OR — O(ranks) C-speed work per cycle
        # instead of O(ranks × tensors) Python (reference bitvector
        # allreduce role, ``mpi_controller.cc:88-106``).
        self._pending_masks: Dict[int, int] = {}
        self._mask_bit_since: Dict[int, float] = {}
        # When each leftover bit reached majority announcement (the mask
        # path's majority_seen analog); keyed like _mask_bit_since.
        self._mask_bit_majority: Dict[int, float] = {}
        # Tensors completed by a stall-time bit→table conversion (after this
        # cycle's responses were already built); delivered next cycle.
        self._stall_completed: List[str] = []
        # Negotiation fan-out topology: the star does O(P) serial
        # recv/send at rank 0; the binomial tree spreads that over
        # O(log P) levels (every rank relays its subtree's bundles).
        # "auto" picks by world size at the measured crossover.
        from ..common import env as env_mod

        topo_env = env_mod.get_str(
            env_mod.HOROVOD_CONTROLLER_TOPOLOGY, "auto").strip().lower()
        if topo_env not in ("auto", "star", "tree"):
            raise ValueError(
                f"HOROVOD_CONTROLLER_TOPOLOGY={topo_env!r}: expected "
                "auto|star|tree")
        if topo_env == "auto":
            topo_env = "tree" if topology.size >= TREE_TOPOLOGY_THRESHOLD \
                else "star"
        # A 2-rank tree degenerates to the star exactly.
        self.fanout_topology = "star" if topology.size <= 2 else topo_env
        # Fusion ordering: "arrival" emits responses in the order tensors
        # *complete* within the cycle (biased by the coordinator's rank scan
        # order); "readiness" (default) sorts the cycle's completed set by
        # each tensor's first_seen timestamp, so the tensors that have been
        # negotiating longest — the ones downstream ranks are most likely
        # already blocked on — pack into the front fusion buckets.  Only the
        # coordinator sorts (it alone decides order, workers replay the
        # ResponseList), so determinism is preserved.
        order = env_mod.get_str(
            env_mod.HOROVOD_FUSION_ORDER, "readiness").strip().lower()
        if order not in ("readiness", "arrival"):
            raise ValueError(
                f"HOROVOD_FUSION_ORDER={order!r}: expected readiness|arrival")
        self.fusion_order = order
        # Online straggler detection (coordinator-side, single-threaded —
        # all state below is touched only from the coordinator's own cycle
        # path, so the hot path gains no locks).  Per-rank EWMAs of how
        # long each rank keeps tensors waiting past the median announcer;
        # crossing the threshold flags the rank (metrics + flight-recorder
        # event + log line).  docs/observability.md#straggler-detection.
        self.straggler_threshold = env_mod.get_float(
            env_mod.HOROVOD_STRAGGLER_THRESHOLD_SECS,
            env_mod.DEFAULT_STRAGGLER_THRESHOLD_SECS)
        alpha = env_mod.get_float(env_mod.HOROVOD_STRAGGLER_EWMA_ALPHA,
                                  env_mod.DEFAULT_STRAGGLER_EWMA_ALPHA)
        if not 0.0 < alpha <= 1.0:
            raise ValueError(
                f"HOROVOD_STRAGGLER_EWMA_ALPHA={alpha!r}: expected (0, 1]")
        self.straggler_alpha = alpha
        self._straggler_ewma: Dict[int, float] = {}
        self._straggler_suspects: Set[int] = set()
        # False while every EWMA sits at zero and nothing lags: the
        # per-cycle update early-outs to two dict checks in steady state.
        self._straggler_decaying = False
        # A fresh controller is a fresh world (elastic epoch restart in
        # the same process): the process-global suspect gauge must not
        # keep naming a suspect from the previous world's EWMA map.
        # Only a stale non-cleared gauge is reset — a clean start leaves
        # the registry untouched (steady state stays metrics-silent).
        if topology.rank == 0 and metrics.registry.get_gauge(
                "straggler_suspect") not in (None, -1):
            self._set_suspect_gauge()
        # Chronic-straggler demotion (docs/elastic.md "self-healing
        # demotion"): verdict state machine fed by the EWMAs above;
        # disabled unless HOROVOD_STRAGGLER_DEMOTE_SECS > 0.
        self.demotion = DemotionPolicy(
            env_mod.get_float(env_mod.HOROVOD_STRAGGLER_DEMOTE_SECS,
                              env_mod.DEFAULT_STRAGGLER_DEMOTE_SECS),
            env_mod.get_int(env_mod.HOROVOD_STRAGGLER_DEMOTE_CYCLES,
                            env_mod.DEFAULT_STRAGGLER_DEMOTE_CYCLES))
        # Tallies parked by a ``controller.tally`` delay_ms injection:
        # (maturity monotonic time, Request), replayed by
        # _mature_deferred_tallies once mature — the injected slowness
        # lands on one rank's tallies while the cycle keeps turning.
        self._deferred_tallies: List[Tuple[float, Request]] = []
        # Tree negotiation fan-in (core/negotiation_fanin.py): installed
        # per epoch by state._sync_controller_topology via
        # configure_fanin; while a plan is active it supersedes
        # fanout_topology — the wire shape is plan-defined end to end.
        self.fanin_plan = None
        self.fanin_heartbeat = None
        # Fast-path counters (exposed through state's controller metrics
        # view, like the cycle counters above — the ~1 ms negotiation
        # hot path never touches the metrics registry): coordinator
        # ingress frames/bytes per gather (every fan-out shape counts
        # them, so star-vs-fanin comparisons read the same series), the
        # per-rank upward-frame split by path, and stale-aggregator
        # convictions.
        self.ingress_frame_count = 0
        self.ingress_byte_count = 0
        self.fanin_tree_frame_count = 0
        self.fanin_direct_frame_count = 0
        self.fanin_fallback_count = 0
        # Lockstep cycle index: every rank increments once per
        # compute_response_list, so it is consistent across ranks without
        # a wire field — the FANIN_RELAY span's cycle tag rides it.
        self.cycle_index = 0
        # The loop's word, before each round, that this rank has a tensor
        # to announce or one announced and not yet agreed on: what the
        # round then sits blocked on other ranks' frames is that tensor's
        # waiting, and goes to ``negotiate_recv`` (``_blocked_recv``).
        self.tensors_in_flight = False

    # ------------------------------------------------------------------
    # the per-cycle negotiation round
    # ------------------------------------------------------------------

    def compute_response_list(self, requests: List[Request],
                              should_shutdown: bool = False) -> ResponseList:
        """One synchronous negotiation round. All ranks must call this every
        cycle; the TCP recv provides the lockstep."""
        self.cycle_index += 1
        if faults.ACTIVE:
            faults.inject("controller.negotiate", rank=self.topo.rank)
        if self.topo.size == 1:
            return self._single_process_responses(requests, should_shutdown)
        if self.topo.rank == 0:
            return self._coordinator_round(requests, should_shutdown)
        return self._worker_round(requests, should_shutdown)

    def _worker_payload(self, requests: List[Request],
                        should_shutdown: bool) -> bytes:
        """This rank's cycle contribution: a compact MaskFrame when every
        pending tensor hit the cache mirror (the steady-state case —
        including idle cycles, whose mask is empty), a full RequestList
        otherwise."""
        hits: List[int] = []
        if self._mirror is not None:
            misses = []
            for req in requests:
                bit = self._mirror.hit(req)
                if bit is not None:
                    hits.append(bit)
                else:
                    misses.append(req)
            requests = misses
            self.cache_hit_count += len(hits)
            self.cache_miss_count += len(requests)
        mask = 0
        for bit in hits:
            mask |= 1 << bit
        mask_bytes = mask.to_bytes((mask.bit_length() + 7) // 8, "little")
        if self._mirror is not None and not requests:
            self.mask_only_sent_count += 1
            return MaskFrame(mask=mask_bytes,
                             shutdown=should_shutdown).to_bytes()
        self.serialized_request_count += len(requests)
        return RequestList(requests=requests, shutdown=should_shutdown,
                           cache_mask=mask_bytes).to_bytes()

    def _apply_response_list(self, rlist: ResponseList) -> ResponseList:
        if self._mirror is not None:
            self._mirror.apply(rlist.cache_assignments, rlist.evicted_bits)
        if rlist.tuned_params is not None:
            self.fusion_threshold = rlist.tuned_params[0]
        return rlist

    def _apply_reply(self, payload: bytes) -> ResponseList:
        """Decode the coordinator's verdict: a MaskFrame reply means every
        rank's cycle was fully cached — reconstruct the Responses locally
        from the mirrored templates (zero Response payloads shipped)."""
        if is_mask_frame(payload):
            frame = MaskFrame.from_bytes(payload)
            if frame.mask_int:
                self.fast_cycle_count += 1
            else:
                self.idle_fast_cycle_count += 1
            return self._responses_from_agreed_mask(frame.mask_int,
                                                    frame.shutdown)
        return self._apply_response_list(ResponseList.from_bytes(payload))

    def configure_fanin(self, plan, heartbeat=None) -> None:
        """Install (plan != None) or clear this epoch's negotiation
        fan-in plan (``core/negotiation_fanin.py:FaninPlan``).  Called at
        epoch bring-up, after every rank adopted rank 0's decision
        (``state._sync_controller_topology``) — mid-epoch installs would
        desynchronize the lockstep recv sets.  An active plan supersedes
        ``fanout_topology``: gather, broadcast, and worker rounds all
        follow the plan's roles."""
        self.fanin_plan = plan
        self.fanin_heartbeat = heartbeat
        if plan is not None:
            log.debug("negotiation fan-in active: rank %d role=%s "
                      "aggregator=%d members=%s",
                      self.topo.rank, plan.role, plan.aggregator_rank,
                      list(plan.member_ranks))

    def _worker_round(self, requests: List[Request],
                      should_shutdown: bool) -> ResponseList:
        payload = self._worker_payload(requests, should_shutdown)
        plan = self.fanin_plan
        if plan is not None:
            if plan.role == "member":
                return self._worker_round_member(payload)
            if plan.role == "aggregator":
                return self._worker_round_aggregator(payload)
            # "direct": host 0 or a vetoed host — star semantics, but
            # counted so the tree-vs-direct split is observable.
            self.fanin_direct_frame_count += 1
            self.mesh.send(0, payload)
            return self._apply_reply(self._blocked_recv(0))
        if self.fanout_topology == "tree":
            return self._worker_round_tree(payload)
        self.mesh.send(0, payload)
        return self._apply_reply(self._blocked_recv(0))

    def _blocked_recv(self, peer: int) -> bytes:
        """One receive of the round: on the coordinator a rank's frame
        (``_recv_ingress``), on a relay its members' or children's, on
        every other rank the verdict, from the rank that hands it on (rank
        0, the host's aggregator, the tree's parent), which waits for the
        coordinator and, through it, for the slowest rank.  While a tensor
        of this rank's is in flight it is a ``negotiate_recv``: a
        ``hvd.negotiate_recv`` span with ``peer=`` inside the round's
        ``hvd.negotiate``, and its length in the total.  A round with
        nothing in flight (the idle lockstep exchange) takes no reading and
        builds no span."""
        if not self.tensors_in_flight:
            return self.mesh.recv(peer)
        with timeline_mod.phase("negotiate_recv", peer=peer):
            return self.mesh.recv(peer)

    def _worker_round_member(self, payload: bytes) -> ResponseList:
        """Fan-in member: heartbeat-gate, then route this cycle through
        the host's aggregator.  A stale heartbeat raises
        AggregatorStaleError BEFORE the send — the member must not park
        a frame with (and then recv-block on) an aggregator it has
        already convicted.  Aggregator DEATH needs no gate: the blocking
        recv raises PeerGoneError promptly and the coordinated abort +
        reshard recovery owns it."""
        hb = self.fanin_heartbeat
        if hb is not None:
            from ..common.exceptions import AggregatorStaleError

            try:
                hb.check()
            except AggregatorStaleError:
                self.fanin_fallback_count += 1
                raise
        self.fanin_tree_frame_count += 1
        agg = self.fanin_plan.aggregator_rank
        self.mesh.send(agg, payload)
        return self._apply_reply(self._blocked_recv(agg))

    def _worker_round_aggregator(self, payload: bytes) -> ResponseList:
        """Fan-in aggregator: collect the host's cycle payloads, fold the
        mask frames that agree into one HostMaskFrame (fold_host —
        stateless, pure per cycle), forward ONE bundle to the
        coordinator, and relay the
        response payload down verbatim (it is identical for every rank,
        like the tree fan-out's relays).  Heartbeat is touched AFTER the
        relay completes: a wedged coordinator link must not keep
        advertising a live aggregator while members' frames pile up."""
        from .negotiation_fanin import fold_host

        t0 = time.monotonic_ns() if timeline_mod.control_active() else None
        collected = [(self.topo.rank, payload)]
        for member in self.fanin_plan.member_ranks:
            collected.append((member, self._blocked_recv(member)))
        self.mesh.send(0, _encode_bundle(fold_host(collected)))
        self.fanin_tree_frame_count += 1
        reply = self._blocked_recv(0)
        for member in self.fanin_plan.member_ranks:
            self.mesh.send(member, reply)
        hb = self.fanin_heartbeat
        if hb is not None:
            hb.touch()
        if t0 is not None:
            timeline_mod.control_span_since(
                "controller", "FANIN_RELAY", t0, cycle=self.cycle_index,
                members=len(self.fanin_plan.member_ranks))
        return self._apply_reply(reply)

    def _worker_round_tree(self, payload: bytes) -> ResponseList:
        """Binomial-tree flavor: relay the subtree's gather bundles up to
        the parent, then relay the response broadcast down to the
        children.  Depth is O(log P) versus the star's O(P) serial
        coordinator loop; interior ranks do O(subtree) byte copies but
        those run in parallel across the tree.  Payloads (and the reply)
        are opaque bytes to the relays, so mask frames ride unchanged."""
        rank, size = self.topo.rank, self.topo.size
        entries = [(rank, payload)]
        for child in tree_children(rank, size):
            entries.extend(_decode_bundle(self._blocked_recv(child)))
        self.mesh.send(tree_parent(rank), _encode_bundle(entries))
        resp_payload = self._blocked_recv(tree_parent(rank))
        for child in tree_children(rank, size):
            self.mesh.send(child, resp_payload)
        return self._apply_reply(resp_payload)

    def _decode_worker_payload(self, payload: bytes):
        """(RequestList, was_mask_frame) from either wire flavor."""
        if is_mask_frame(payload):
            frame = MaskFrame.from_bytes(payload)
            return RequestList(shutdown=frame.shutdown,
                               cache_mask=frame.mask), True
        rl = RequestList.from_bytes(payload)
        self.serialized_request_count += len(rl.requests)
        return rl, False

    def _recv_ingress(self, sender: int) -> bytes:
        """One coordinator gather recv, counted: every fan-out shape
        funnels through here so ``controller_ingress_frames_total`` /
        ``_bytes_total`` compare star vs tree vs fan-in like for like —
        one increment per frame that actually arrived at rank 0.  It is
        also the coordinator's blocked receive: how long rank 0 waited
        for ``sender``."""
        data = self._blocked_recv(sender)
        self.ingress_frame_count += 1
        self.ingress_byte_count += len(data)
        return data

    def _gather_request_lists(self):
        """Yield every other rank's (rank, RequestList, was_mask) for this
        cycle, in deterministic rank order for the tree and fan-in shapes
        (the star's serial loop is ordered by construction).

        Under fan-in, a HostMaskFrame expands to one identical
        pending-mask contribution per covered rank — bit-exact with the
        star's per-rank MaskFrames because the frame covers exactly the
        ranks that sent this very mask (``fold_host``)."""
        plan = self.fanin_plan
        if plan is not None and plan.role == "coordinator":
            entries: List[tuple] = []
            for sender in plan.coordinator_senders:
                data = self._recv_ingress(sender)
                if sender in plan.bundle_senders:
                    entries.extend(_decode_bundle(data))
                else:
                    entries.append((sender, data))
            entries.sort()
            for rank, payload in entries:
                if is_host_mask_frame(payload):
                    frame = HostMaskFrame.from_bytes(payload)
                    for covered in frame.covered:
                        yield covered, RequestList(
                            shutdown=frame.shutdown,
                            cache_mask=frame.mask), True
                else:
                    rl, was_mask = self._decode_worker_payload(payload)
                    yield rank, rl, was_mask
        elif self.fanout_topology == "tree":
            entries = []
            for child in tree_children(0, self.topo.size):
                entries.extend(_decode_bundle(self._recv_ingress(child)))
            entries.sort()
            for rank, payload in entries:
                rl, was_mask = self._decode_worker_payload(payload)
                yield rank, rl, was_mask
        else:
            for worker in range(1, self.topo.size):
                rl, was_mask = self._decode_worker_payload(
                    self._recv_ingress(worker))
                yield worker, rl, was_mask

    def _broadcast_response_payload(self, payload: bytes) -> None:
        plan = self.fanin_plan
        if plan is not None and plan.role == "coordinator":
            for sender in plan.coordinator_senders:
                self.mesh.send(sender, payload)
        elif self.fanout_topology == "tree":
            for child in tree_children(0, self.topo.size):
                self.mesh.send(child, payload)
        else:
            for worker in range(1, self.topo.size):
                self.mesh.send(worker, payload)

    def _coordinator_round(self, own_requests: List[Request],
                           should_shutdown: bool) -> ResponseList:
        from .response_cache import CACHEABLE, cache_key

        self._cycle_assignments = []
        self._cycle_evictions = []
        ready: List[str] = list(self._stall_completed)
        self._stall_completed.clear()
        pending = self._pending_masks
        own_all_cached = True
        for req in own_requests:
            bit = self._cache.lookup(cache_key(req)) \
                if self._cache is not None \
                and req.request_type in CACHEABLE else None
            if bit is not None:
                pending[0] = pending.get(0, 0) | (1 << bit)
                self.cache_hit_count += 1
            else:
                own_all_cached = False
                if self._increment(req):
                    ready.append(req.tensor_name)
        all_mask_frames = True
        for worker, rl, was_mask in self._gather_request_lists():
            all_mask_frames = all_mask_frames and was_mask
            should_shutdown = should_shutdown or rl.shutdown
            if rl.cache_mask:
                pending[worker] = pending.get(worker, 0) | int.from_bytes(
                    rl.cache_mask, "little")
            for bit in rl.cache_hits:  # legacy list flavor
                pending[worker] = pending.get(worker, 0) | (1 << bit)
            for req in rl.requests:
                if self._increment(req):
                    ready.append(req.tensor_name)
        ready.extend(self._mature_deferred_tallies())

        # A JOIN that lands after a tensor's last active-rank request must
        # still complete that tensor: re-check pending entries against the
        # updated joined set (the reference re-evaluates the join-adjusted
        # count inside ComputeResponseList each cycle).
        if self._joined_ranks:
            ready_set = set(ready)
            for name, entry in self._message_table.items():
                if name in ready_set:
                    continue
                needed = self.topo.size - len(self._joined_ranks - entry.ranks)
                if len(entry.ranks) >= needed:
                    ready.append(name)

        # Readiness-ordered fusion: sort this cycle's completions by how
        # long each tensor has been negotiating (first_seen) before the
        # table entries are popped below.  The stable sort keeps arrival
        # order among ties; JOIN (never in the table) sorts first.  The
        # mask fast path is untouched — its bit order is already mirrored
        # deterministically on every rank.
        if self.fusion_order == "readiness" and len(ready) > 1:
            table = self._message_table
            by_age = sorted(
                ready,
                key=lambda n: e.first_seen
                if (e := table.get(n)) is not None else 0.0)
            if by_age != ready:
                metrics.inc("fusion_reorders_total")
                ready = by_age
        responses = [self._construct_response(name) for name in ready]
        responses = [r for r in responses if r is not None]
        mask_responses, ready_mask, mask_pure = self._mask_round(pending)
        responses.extend(mask_responses)
        tuned = self._autotune(responses)
        responses = self._fuse_responses(responses)
        self._update_stragglers()
        self._check_stalls()
        if self._cache is not None:
            self._cache.tick()

        # Zero-payload fast path: every rank's cycle was pure cache bits
        # (or idle) and the verdict is pure templates — broadcast only the
        # agreed bitvector; every rank (this one included, above)
        # reconstructs the identical fused ResponseList locally.  Any
        # cache-maintenance, tally, join, stall, or autotune traffic this
        # cycle forces the full ResponseList so that state ships.
        fast = (self.cache_enabled and own_all_cached and all_mask_frames
                and mask_pure and not ready and not self._joined_ranks
                and tuned is None and not self._cycle_assignments
                and not self._cycle_evictions and not self._stall_completed)
        if fast:
            if ready_mask:
                self.fast_cycle_count += 1
            else:
                self.idle_fast_cycle_count += 1
            mask_bytes = ready_mask.to_bytes(
                (ready_mask.bit_length() + 7) // 8, "little")
            self._broadcast_response_payload(
                MaskFrame(mask=mask_bytes,
                          shutdown=should_shutdown).to_bytes())
            return ResponseList(responses=responses,
                                shutdown=should_shutdown)

        rlist = ResponseList(responses=responses, shutdown=should_shutdown,
                             cache_assignments=self._cycle_assignments,
                             evicted_bits=self._cycle_evictions,
                             tuned_params=tuned)
        payload = rlist.to_bytes()
        self._broadcast_response_payload(payload)
        return rlist

    def _bit_template(self, bit: int) -> Optional[Request]:
        """Cached request template for a bit, from whichever side's cache
        this rank holds."""
        if self._cache is not None:
            return self._cache.rehydrate(bit, 0)
        if self._mirror is not None:
            return self._mirror.template(bit)
        return None

    def _responses_from_agreed_mask(self, mask: int,
                                    shutdown: bool) -> ResponseList:
        """Reconstruct the cycle's ResponseList from an agreed bitvector —
        the worker half of the zero-payload fast path.  Must mirror the
        coordinator's construction exactly: templates in ascending bit
        order, then the deterministic fusion scan under the (synchronized)
        threshold."""
        from ..common.exceptions import HorovodInternalError

        responses: List[Response] = []
        rm = mask
        while rm:
            low = rm & -rm
            bit = low.bit_length() - 1
            rm ^= low
            tpl = self._bit_template(bit)
            if tpl is None:
                # Protocol invariant: an agreed bit was announced by every
                # rank, so every rank holds its template.  Losing it means
                # divergent cache state — fail loudly, don't desync.
                raise HorovodInternalError(
                    f"fast-path agreed cache bit {bit} has no local "
                    "template (cache mirror diverged from coordinator)")
            responses.append(self._response_from_template(tpl))
        return ResponseList(responses=self._fuse_responses(responses),
                            shutdown=shutdown)

    def _mask_round(self, pending: Dict[int, int]):
        """Resolve the cache-bit masks: a bit set in EVERY active rank's
        pending mask is globally ready and its Response comes straight from
        the cached template (no per-rank tallying or re-validation — a hit
        means the rank's request matched the template key exactly).

        Also merges the transition case where some ranks sent a bit while
        others sent a full Request for the same tensor (e.g. around an
        eviction): those bits convert into table tallies so neither side
        strands.

        Returns ``(responses, ready_mask, pure)``; ``pure`` is True iff
        every response came straight from a live template in ready-bit
        order — the precondition for answering the cycle with the agreed
        bitvector alone (the coordinator half of the fast path).  Any
        eviction recovery, table merge, dropped bit, or error response
        clears it."""
        if not pending:
            return [], 0, True
        pure = True
        responses: List[Response] = []
        if self._cycle_evictions:
            # A bit evicted this cycle may still be pending on some ranks
            # (partial announcement): convert those announcements to table
            # tallies via the tombstoned template so the bit id can be
            # recycled safely once its tombstone expires.
            from dataclasses import replace as _replace

            for bit in self._cycle_evictions:
                low = 1 << bit
                if not any(m & low for m in pending.values()):
                    continue
                pure = False
                tpl = self._cache.rehydrate(bit, 0) if self._cache else None
                completed = False
                for r, m in list(pending.items()):
                    if m & low:
                        pending[r] = m & ~low
                        if tpl is not None:
                            completed |= self._increment(
                                _replace(tpl, request_rank=r))
                self._mask_bit_since.pop(bit, None)
                self._mask_bit_majority.pop(bit, None)
                if completed:
                    resp = self._construct_response(tpl.tensor_name)
                    if resp is not None:
                        responses.append(resp)

        union = 0
        for m in pending.values():
            union |= m
        if union == 0:
            return responses, 0, pure

        ready_mask = None
        for r in range(self.topo.size):
            eff = -1 if r in self._joined_ranks else pending.get(r, 0)
            ready_mask = eff if ready_mask is None else (ready_mask & eff)
            if ready_mask == 0:
                break
        ready_mask = ready_mask or 0
        # Bound to announced bits: with every rank joined each eff is -1 and
        # the AND-fold yields -1 (infinite sign-extended mask) — the bit
        # extraction loop below would never terminate on a negative int.
        ready_mask &= union
        if ready_mask:
            # One big-int op per rank clears every completing bit (the
            # per-bit/per-rank loop this path exists to avoid).
            for r, m in list(pending.items()):
                pending[r] = m & ~ready_mask

        rm = ready_mask
        while rm:
            low = rm & -rm
            bit = low.bit_length() - 1
            rm ^= low
            self._mask_bit_since.pop(bit, None)
            self._mask_bit_majority.pop(bit, None)
            tpl = self._cache.rehydrate(bit, 0) if self._cache else None
            if tpl is None:
                log.error("ready unknown cache bit %d; dropping", bit)
                pure = False
                continue
            if tpl.request_type == RequestType.BROADCAST and \
                    self._joined_ranks:
                pure = False
                responses.append(Response(
                    response_type=ResponseType.ERROR,
                    tensor_names=[tpl.tensor_name],
                    error_message=f"broadcast for {tpl.tensor_name} cannot "
                                  "complete with joined ranks (Join "
                                  "supports allreduce only)."))
                continue
            responses.append(self._response_from_template(tpl))

        # Leftover bits (present on SOME ranks only): start their stall
        # clock and merge with any same-tensor full-Request tally so mixed
        # bit/Request submissions cannot strand each other.  Steady state
        # (every bit completes in its cycle) leaves this loop empty.
        leftover = union & ~ready_mask
        if leftover:
            from dataclasses import replace as _replace

            now = time.monotonic()
            while leftover:
                low = leftover & -leftover
                bit = low.bit_length() - 1
                leftover ^= low
                self._mask_bit_since.setdefault(bit, now)
                if bit not in self._mask_bit_majority:
                    have = sum(1 for m in pending.values() if m & low)
                    if 2 * have >= self.topo.size - len(self._joined_ranks):
                        self._mask_bit_majority[bit] = now
                tpl = self._cache.rehydrate(bit, 0) if self._cache else None
                if tpl is None:
                    log.error("pending unknown cache bit %d; dropping", bit)
                    self._clear_bit(bit)
                    pure = False
                    continue
                if tpl.tensor_name in self._message_table:
                    pure = False
                    completed = False
                    for r, m in list(pending.items()):
                        if m & low:
                            pending[r] = m & ~low
                            completed |= self._increment(
                                _replace(tpl, request_rank=r))
                    self._mask_bit_since.pop(bit, None)
                    self._mask_bit_majority.pop(bit, None)
                    if completed:
                        resp = self._construct_response(tpl.tensor_name)
                        if resp is not None:
                            responses.append(resp)
        return responses, ready_mask, pure

    def _clear_bit(self, bit: int) -> None:
        low = 1 << bit
        for r, m in list(self._pending_masks.items()):
            if m & low:
                self._pending_masks[r] = m & ~low
        self._mask_bit_since.pop(bit, None)
        self._mask_bit_majority.pop(bit, None)

    def _response_from_template(self, tpl: Request) -> Response:
        """Response for a fully-hit cached tensor — field-for-field what
        ``_construct_response`` emits for a validated single-tensor
        ALLREDUCE/ADASUM/BROADCAST (the only cacheable ops)."""
        rtype = {
            RequestType.ALLREDUCE: ResponseType.ALLREDUCE,
            RequestType.ADASUM: ResponseType.ADASUM,
            RequestType.BROADCAST: ResponseType.BROADCAST,
        }[tpl.request_type]
        resp = Response(
            response_type=rtype,
            tensor_names=[tpl.tensor_name],
            tensor_type=tpl.tensor_type,
            tensor_sizes=[tpl.num_elements],
            devices=[tpl.device],
            prescale_factor=tpl.prescale_factor,
            postscale_factor=tpl.postscale_factor,
            last_joined_rank=min(self._joined_ranks)
            if self._joined_ranks else -1,
        )
        resp._payload_bytes = tpl.num_elements * tpl.tensor_type.itemsize
        return resp

    def _autotune(self, responses: List[Response]):
        """Feed the cycle's reduced byte volume to the ParameterManager;
        returns new (fusion_bytes, cycle_ms) when the tuner moves."""
        if self.param_manager is None or not self.param_manager.enabled:
            return None
        nbytes = sum(
            sum(r.tensor_sizes) * r.tensor_type.itemsize
            for r in responses
            if r.response_type in (ResponseType.ALLREDUCE, ResponseType.ADASUM))
        tuned = self.param_manager.update(nbytes)
        if tuned is not None:
            self.fusion_threshold = tuned[0]
        return tuned

    def _single_process_responses(self, requests: List[Request],
                                  should_shutdown: bool) -> ResponseList:
        responses = []
        for req in requests:
            if self._increment(req):
                resp = self._construct_response(req.tensor_name)
                if resp is not None:
                    responses.append(resp)
        return ResponseList(responses=self._fuse_responses(responses),
                            shutdown=should_shutdown)

    # ------------------------------------------------------------------
    # message table
    # ------------------------------------------------------------------

    def _increment(self, req: Request, defer_faults: bool = True) -> bool:
        """Tally one rank's readiness; True when the tensor is globally ready.

        Reference ``IncrementTensorCount`` (``controller.cc:1030-1053``):
        completion when (requesting ranks) + (joined ranks) covers the world.

        ``controller.tally`` fault site: a matching ``delay_ms`` clause
        parks this tally on ``_deferred_tallies`` instead of sleeping —
        sleeping here would slow the whole lockstep cycle equally and
        attribute lag to nobody, while a parked tally leaves the tensor
        incomplete *missing exactly this rank* across cycles, which is
        what a chronically slow rank looks like to the straggler EWMAs.
        Replayed tallies pass ``defer_faults=False`` so an ``after=``
        clause cannot re-defer them forever.  Only the request-table path
        is injectable: cache-bit announcements never reach this tally.
        """
        if faults.ACTIVE and defer_faults and self.topo.size > 1 \
                and req.request_type != RequestType.JOIN:
            delay = faults.inject_deferred("controller.tally",
                                           rank=req.request_rank)
            if delay > 0.0:
                self._deferred_tallies.append(
                    (time.monotonic() + delay, req))
                return False
        if req.request_type == RequestType.JOIN:
            self._joined_ranks.add(req.request_rank)
            # Join completes when *every* rank has joined.
            return len(self._joined_ranks) == self.topo.size

        entry = self._message_table.get(req.tensor_name)
        if entry is None:
            entry = self._message_table[req.tensor_name] = _TableEntry()
            if self.timeline is not None:
                self.timeline.negotiate_start(req.tensor_name,
                                              req.request_type.name)
        if req.request_rank in entry.ranks:
            log.warning("rank %d re-submitted tensor %s before completion",
                        req.request_rank, req.tensor_name)
            return False
        entry.ranks.add(req.request_rank)
        entry.requests.append(req)
        if self.timeline is not None:
            self.timeline.negotiate_rank_ready(req.tensor_name, req.request_rank)
        needed = self.topo.size - len(self._joined_ranks - entry.ranks)
        if entry.majority_seen is None and \
                2 * len(entry.ranks) >= self.topo.size - len(self._joined_ranks):
            entry.majority_seen = time.monotonic()
        return len(entry.ranks) >= needed

    def _mature_deferred_tallies(self) -> List[str]:
        """Replay parked tallies whose injected delay has matured; returns
        tensors the replays completed (merged into the cycle's ready list).
        Empty-list fast path when nothing is parked (the normal case)."""
        if not self._deferred_tallies:
            return []
        now = time.monotonic()
        completed: List[str] = []
        parked: List[Tuple[float, Request]] = []
        for due, req in self._deferred_tallies:
            if due <= now:
                if self._increment(req, defer_faults=False):
                    completed.append(req.tensor_name)
            else:
                parked.append((due, req))
        self._deferred_tallies = parked
        return completed

    # ------------------------------------------------------------------
    # response construction & validation
    # ------------------------------------------------------------------

    def _construct_response(self, name: str) -> Optional[Response]:
        """Validate cross-rank consistency and emit the Response.

        Reference ``ConstructResponse`` (``controller.cc:547-824``): any
        dtype/op/shape/root/scale disagreement yields an ERROR response that
        every rank delivers to the waiting callback."""
        if name == JOIN_TENSOR_NAME or not self._message_table.get(name):
            if len(self._joined_ranks) == self.topo.size:
                self._joined_ranks.clear()
                return Response(response_type=ResponseType.JOIN,
                                tensor_names=[JOIN_TENSOR_NAME])
            return None

        entry = self._message_table.pop(name)
        if self.timeline is not None:
            self.timeline.negotiate_end(name)
        reqs = entry.requests
        first = reqs[0]

        error = None
        for req in reqs[1:]:
            if req.tensor_type != first.tensor_type:
                error = (f"Mismatched data types for {name}: rank "
                         f"{first.request_rank} sent {first.tensor_type.name}, rank "
                         f"{req.request_rank} sent {req.tensor_type.name}.")
                break
            if req.request_type != first.request_type:
                error = (f"Mismatched operations for {name}: ranks disagree on "
                         f"{first.request_type.name} vs {req.request_type.name}.")
                break
            if req.prescale_factor != first.prescale_factor or \
                    req.postscale_factor != first.postscale_factor:
                error = f"Mismatched pre/postscale factors for {name}."
                break

        op = first.request_type
        tensor_sizes: List[int] = []
        devices = sorted({r.device for r in reqs})

        if error is None and op in (RequestType.ALLREDUCE, RequestType.ADASUM,
                                    RequestType.BROADCAST):
            for req in reqs[1:]:
                if req.tensor_shape != first.tensor_shape:
                    error = (f"Mismatched {op.name.lower()} tensor shapes for "
                             f"{name}: rank {first.request_rank} has "
                             f"{first.tensor_shape}, rank {req.request_rank} has "
                             f"{req.tensor_shape}.")
                    break
            tensor_sizes = [first.num_elements]

        if error is None and op == RequestType.BROADCAST:
            for req in reqs[1:]:
                if req.root_rank != first.root_rank:
                    error = (f"Mismatched broadcast root ranks for {name}: "
                             f"{first.root_rank} vs {req.root_rank}.")
                    break
            # A joined rank has no root_rank/output for a broadcast it never
            # submitted; like the reference, Join supports allreduce only.
            if error is None and len(entry.ranks) != self.topo.size:
                error = (f"broadcast for {name} cannot complete with joined "
                         f"ranks (Join supports allreduce only).")

        if error is None and op == RequestType.ALLGATHER:
            # Shapes must agree on every dim except the first; response
            # carries each rank's first dimension, ordered by rank
            # (reference packs the same into tensor_sizes).
            by_rank = sorted(reqs, key=lambda r: r.request_rank)
            for req in by_rank:
                if len(req.tensor_shape) != len(first.tensor_shape) or \
                        req.tensor_shape[1:] != first.tensor_shape[1:]:
                    error = (f"Mismatched allgather tensor shapes for {name}: "
                             f"all dims but the first must match "
                             f"({first.tensor_shape} vs {req.tensor_shape}).")
                    break
            if error is None:
                if len(by_rank) != self.topo.size:
                    error = (f"allgather for {name} cannot complete with joined "
                             f"ranks (Join supports allreduce only, as in the "
                             f"reference JoinOp).")
                else:
                    tensor_sizes = [r.tensor_shape[0] if r.tensor_shape else 1
                                    for r in by_rank]

        if error is None and op == RequestType.ALLTOALL:
            by_rank = sorted(reqs, key=lambda r: r.request_rank)
            if len(by_rank) != self.topo.size:
                error = f"alltoall for {name} cannot complete with joined ranks."
            else:
                for req in by_rank:
                    # Trailing dims must agree (like allgather): a
                    # mismatch would give ranks different row sizes and
                    # hang the exchange instead of erroring.
                    if len(req.tensor_shape) != len(first.tensor_shape) \
                            or req.tensor_shape[1:] != first.tensor_shape[1:]:
                        error = (f"Mismatched alltoall tensor shapes for "
                                 f"{name}: all dims but the first must "
                                 f"match ({first.tensor_shape} vs "
                                 f"{req.tensor_shape}).")
                        break
                    if len(req.splits) != self.topo.size:
                        error = (f"alltoall splits for {name} must have one entry "
                                 f"per rank (rank {req.request_rank} sent "
                                 f"{len(req.splits)}).")
                        break
                    dim0 = req.tensor_shape[0] if req.tensor_shape else 0
                    if sum(req.splits) != dim0:
                        error = (f"alltoall splits for {name} sum to "
                                 f"{sum(req.splits)} but first dimension is "
                                 f"{dim0} on rank {req.request_rank}.")
                        break
                if error is None:
                    # Flattened N×N send-split matrix, row r = rank r's splits;
                    # rank k's recv splits are column k.
                    for req in by_rank:
                        tensor_sizes.extend(req.splits)

        if error is not None:
            return Response(response_type=ResponseType.ERROR,
                            tensor_names=[name], error_message=error)

        rtype = {
            RequestType.ALLREDUCE: ResponseType.ALLREDUCE,
            RequestType.ALLGATHER: ResponseType.ALLGATHER,
            RequestType.BROADCAST: ResponseType.BROADCAST,
            RequestType.ADASUM: ResponseType.ADASUM,
            RequestType.ALLTOALL: ResponseType.ALLTOALL,
            RequestType.BARRIER: ResponseType.BARRIER,
        }[op]
        if self._cache is not None:
            bit, evicted = self._cache.maybe_insert(first)
            self._cycle_evictions.extend(evicted)
            if bit is not None:
                self._cycle_assignments.append((bit, first))
        resp = Response(
            response_type=rtype,
            tensor_names=[name],
            tensor_type=first.tensor_type,
            tensor_sizes=tensor_sizes,
            devices=devices,
            prescale_factor=first.prescale_factor,
            postscale_factor=first.postscale_factor,
            last_joined_rank=min(self._joined_ranks) if self._joined_ranks else -1,
        )
        # Coordinator-local payload accounting for the fusion threshold.
        # ALLGATHER tensor_sizes are first dims only; the true bytes scale
        # by the inner-dim product (available here from the request shape,
        # not in the wire Response).
        itemsize = first.tensor_type.itemsize
        if rtype == ResponseType.ALLGATHER:
            dim0 = first.tensor_shape[0] if first.tensor_shape else 1
            inner_n = first.num_elements // max(1, dim0)
            resp._payload_bytes = sum(tensor_sizes) * inner_n * itemsize
        else:
            resp._payload_bytes = sum(tensor_sizes) * itemsize
        return resp

    # ------------------------------------------------------------------
    # fusion
    # ------------------------------------------------------------------

    _FUSIBLE = (ResponseType.ALLREDUCE, ResponseType.ADASUM,
                ResponseType.ALLGATHER)

    @staticmethod
    def _fusion_compatible(a: Response, b: Response) -> bool:
        return (a.response_type == b.response_type
                and a.tensor_type == b.tensor_type
                and a.devices == b.devices
                and a.prescale_factor == b.prescale_factor
                and a.postscale_factor == b.postscale_factor)

    def _fuse_responses(self, responses: List[Response]) -> List[Response]:
        """FIFO scan with look-ahead (reference ``FuseResponses``,
        ``controller.cc:859-998``): pop the front response, then sweep the
        REMAINING queue for compatible ones to pack under the threshold —
        interleaved dtypes no longer defeat fusion (they merely get skipped
        and seed their own buckets).  ALLREDUCE/ADASUM fuse flat element
        counts; ALLGATHER fuses whole per-rank size blocks (each tensor
        contributes ``size`` entries to ``tensor_sizes``)."""
        fused: List[Response] = []
        pending = list(responses)
        while pending:
            resp = pending.pop(0)
            if resp.response_type not in self._FUSIBLE:
                fused.append(resp)
                continue
            itemsize = resp.tensor_type.itemsize

            def payload_bytes(r: Response) -> int:
                return getattr(r, "_payload_bytes",
                               sum(r.tensor_sizes) * itemsize)

            total = payload_bytes(resp)
            rest: List[Response] = []
            for cand in pending:
                cand_bytes = payload_bytes(cand)
                if (self._fusion_compatible(resp, cand)
                        and total + cand_bytes <= self.fusion_threshold):
                    resp.tensor_names.extend(cand.tensor_names)
                    resp.tensor_sizes.extend(cand.tensor_sizes)
                    total += cand_bytes
                else:
                    rest.append(cand)
            pending = rest
            fused.append(resp)
        return fused

    # ------------------------------------------------------------------
    # straggler detection (coordinator-side; docs/observability.md)
    # ------------------------------------------------------------------

    def _update_stragglers(self) -> None:
        """Per-cycle readiness-lag EWMAs from the tallies the coordinator
        already keeps: a rank is *behind* by ``now - majority_seen`` for
        every incomplete tensor (table entry or announced cache bit) whose
        median announcer is ready but this rank is not.  Steady state —
        every tensor completes in its announcement cycle — stamps no
        majorities, so the whole update is two falsy checks."""
        if not self._straggler_decaying and not self._mask_bit_majority \
                and not any(e.majority_seen is not None
                            for e in self._message_table.values()):
            return
        now = time.monotonic()
        behind: Dict[int, float] = {}
        active = set(range(self.topo.size)) - self._joined_ranks
        for entry in self._message_table.values():
            if entry.majority_seen is None:
                continue
            age = now - entry.majority_seen
            for r in active - entry.ranks:
                if age > behind.get(r, 0.0):
                    behind[r] = age
        for bit, since in self._mask_bit_majority.items():
            low = 1 << bit
            age = now - since
            for r in active:
                if not (self._pending_masks.get(r, 0) & low) \
                        and age > behind.get(r, 0.0):
                    behind[r] = age
        ewma = self._straggler_ewma
        thresh = self.straggler_threshold
        decaying = False
        for r in range(self.topo.size):
            lag = behind.get(r, 0.0)
            v = ewma.get(r, 0.0)
            v += self.straggler_alpha * (lag - v)
            ewma[r] = v
            decaying = decaying or v > 1e-9
            if lag > 0.0:
                metrics.observe("straggler_lag_seconds", lag, rank=str(r))
            if thresh <= 0.0:
                continue
            if v > thresh and r not in self._straggler_suspects:
                self._straggler_suspects.add(r)
                metrics.inc("straggler_flags_total", rank=str(r))
                flight_recorder.record(
                    "straggler", rank=r, lag_ewma=round(v, 6),
                    threshold=thresh)
                log.warning(
                    "straggler detected: rank %d readiness-lag EWMA %.3fs "
                    "exceeds HOROVOD_STRAGGLER_THRESHOLD_SECS=%.3fs "
                    "(it keeps completing tensors %0.3fs after the median "
                    "announcer)", r, v, thresh, lag)
                self._set_suspect_gauge()
            elif v < thresh / 2.0 and r in self._straggler_suspects:
                # Hysteresis: clear at half the flag threshold so a rank
                # oscillating near it doesn't spam flag transitions.
                self._straggler_suspects.discard(r)
                flight_recorder.record("straggler_cleared", rank=r,
                                       lag_ewma=round(v, 6))
                log.info("straggler cleared: rank %d readiness-lag EWMA "
                         "back to %.3fs", r, v)
                self._set_suspect_gauge()
        self._straggler_decaying = decaying or bool(self._straggler_suspects)
        if self.demotion.enabled:
            from ..common import env as env_mod

            victim = self.demotion.observe(env_mod.get_epoch(), ewma, active)
            if victim is not None:
                self._report_demotion(victim, ewma.get(victim, 0.0))

    def _report_demotion(self, victim: int, lag_ewma: float) -> None:
        """Deliver a chronic-straggler verdict: flight-recorder event +
        log line on the coordinator, and a best-effort demotion report to
        the elastic driver over the rendezvous store.  Outside an elastic
        job (no store in the environment) the verdict is detector-only —
        named loudly, acted on by nobody."""
        flight_recorder.record(
            "straggler_demotion", rank=victim, lag_ewma=round(lag_ewma, 6),
            threshold=self.demotion.demote_secs,
            cycles=self.demotion.demote_cycles)
        log.warning(
            "chronic straggler: rank %d readiness-lag EWMA %.3fs stayed "
            "over HOROVOD_STRAGGLER_DEMOTE_SECS=%.3fs for %d consecutive "
            "busy cycles — reporting for demotion", victim, lag_ewma,
            self.demotion.demote_secs, self.demotion.demote_cycles)
        try:
            from ..elastic import rendezvous_client

            posted = rendezvous_client.post_demotion_report(
                victim, lag_ewma, self.demotion.demote_secs,
                self.demotion.demote_cycles)
        except Exception as exc:  # noqa: BLE001 — a demotion report must
            # never take down the negotiation cycle it rode along with
            posted = False
            log.warning("demotion report for rank %d failed: %s",
                        victim, exc)
        if not posted:
            log.warning("no rendezvous store reachable: demotion verdict "
                        "for rank %d is detector-only", victim)

    def _set_suspect_gauge(self) -> None:
        worst = max(self._straggler_suspects,
                    key=lambda r: self._straggler_ewma.get(r, 0.0)) \
            if self._straggler_suspects else -1
        metrics.set_gauge("straggler_suspect", worst)

    def _lag_suffix(self, missing: List[int]) -> str:
        """Name the laggard for the stall-inspector warnings: the missing
        rank with the worst readiness-lag EWMA (empty when no lag has been
        observed — e.g. a rank that never announced anything)."""
        candidates = [r for r in missing
                      if self._straggler_ewma.get(r, 0.0) > 1e-9]
        if not candidates:
            return ""
        worst = max(candidates, key=lambda r: self._straggler_ewma[r])
        return (f"; slowest by readiness-lag EWMA: rank {worst} "
                f"({self._straggler_ewma[worst]:.3f}s)")

    # ------------------------------------------------------------------
    # stall inspection (coordinator-side; reference stall_inspector.cc)
    # ------------------------------------------------------------------

    def _check_stalls(self) -> None:
        # The shutdown deadline is independent of the warning: disabling
        # stall WARNINGS must not silently disable the hard abort, and a
        # shutdown time shorter than the warning time must still fire on
        # its own schedule.
        warn, shut = self.stall_warning_secs, self.stall_shutdown_secs
        enabled = [t for t in (warn, shut) if t > 0]
        if not enabled:
            return
        now = time.monotonic()
        if now - self._last_stall_check < min(enabled):
            return
        self._last_stall_check = now
        # Surface the inspector's view into the metrics registry: how many
        # tensors are currently past the stall threshold (gauge, refreshed
        # every check) and how many hard shutdowns ever fired (counter).
        stall_age = min(t for t in (warn, shut) if t > 0)
        stalled = sum(
            1 for e in self._message_table.values()
            if now - e.first_seen > stall_age)
        stalled += sum(1 for since in self._mask_bit_since.values()
                       if now - since > stall_age)
        metrics.set_gauge("stalled_tensors", stalled)
        for name, entry in self._message_table.items():
            age = now - entry.first_seen
            missing = sorted(set(range(self.topo.size))
                             - entry.ranks - self._joined_ranks)
            if shut > 0 and age > shut:
                # Hard abort (reference stall_inspector.h:77-80): tearing
                # down the coordinator breaks the mesh, so every healthy
                # rank surfaces a HorovodInternalError instead of hanging
                # forever on the missing ones.
                from ..common.exceptions import HorovodInternalError

                metrics.inc("stall_shutdowns_total")
                raise HorovodInternalError(
                    f"stall shutdown: tensor {name} incomplete for "
                    f"{age:.0f}s (> {shut}s), missing ranks {missing}")
            if warn <= 0 or age <= warn:
                continue
            log.warning(
                "One or more tensors were submitted to be reduced, gathered "
                "or broadcasted by subset of ranks and are waiting for the "
                "remainder: %s stalled for %.0fs, missing ranks: %s%s",
                name, age, missing, self._lag_suffix(missing))
            # A stalled tensor's cached negotiation is stale
            # (reference InvalidateStalledCachedTensors): evict so any
            # post-recovery resubmission renegotiates from scratch.
            if self._cache is not None:
                bit = self._cache.invalidate_name(name)
                if bit is not None:
                    self._cycle_evictions.append(bit)

        # Mask-path stalls: a bit some ranks announced long ago that never
        # reached all ranks.  Convert the partial announcements into table
        # tallies (so the waiting ranks eventually resolve — typically as a
        # loud mismatch/stall on the table path) and invalidate the entry.
        from dataclasses import replace as _replace

        for bit, since in list(self._mask_bit_since.items()):
            age = now - since
            have = [r for r, m in self._pending_masks.items()
                    if m & (1 << bit)]
            missing = sorted(set(range(self.topo.size)) - set(have)
                             - self._joined_ranks)
            if shut > 0 and age > shut:
                from ..common.exceptions import HorovodInternalError

                tpl = self._cache.rehydrate(bit, 0) if self._cache else None
                name = tpl.tensor_name if tpl else f"<bit {bit}>"
                metrics.inc("stall_shutdowns_total")
                raise HorovodInternalError(
                    f"stall shutdown: cached tensor {name} incomplete for "
                    f"{age:.0f}s (> {shut}s), missing ranks {missing}")
            if warn <= 0 or age <= warn:
                continue
            tpl = self._cache.rehydrate(bit, 0) if self._cache else None
            if tpl is None:
                self._clear_bit(bit)
                continue
            log.warning(
                "cached tensor %s announced by ranks %s stalled for %.0fs, "
                "missing ranks: %s%s — invalidating its cache entry",
                tpl.tensor_name, have, age, missing,
                self._lag_suffix(missing))
            for r in have:
                self._pending_masks[r] &= ~(1 << bit)
                if self._increment(_replace(tpl, request_rank=r)):
                    self._stall_completed.append(tpl.tensor_name)
            self._mask_bit_since.pop(bit, None)
            self._mask_bit_majority.pop(bit, None)
            evicted = self._cache.invalidate_name(tpl.tensor_name)
            if evicted is not None:
                self._cycle_evictions.append(evicted)

    # ------------------------------------------------------------------
    # small collective helpers for init/shutdown/elastic paths
    # ------------------------------------------------------------------

    def bcast_bytes(self, payload: Optional[bytes], root: int = 0) -> bytes:
        if self.topo.size == 1:
            return payload or b""
        if self.topo.rank == root:
            for peer in range(self.topo.size):
                if peer != root:
                    self.mesh.send(peer, payload or b"")
            return payload or b""
        return self.mesh.recv(root)

    def gather_bytes(self, payload: bytes, root: int = 0) -> Optional[List[bytes]]:
        if self.topo.size == 1:
            return [payload]
        if self.topo.rank == root:
            out: List[Optional[bytes]] = [None] * self.topo.size
            out[root] = payload
            for peer in range(self.topo.size):
                if peer != root:
                    out[peer] = self.mesh.recv(peer)
            return out  # type: ignore[return-value]
        self.mesh.send(root, payload)
        return None

    def barrier(self) -> None:
        self.gather_bytes(b"")
        self.bcast_bytes(b"")
