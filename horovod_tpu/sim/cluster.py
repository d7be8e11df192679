"""Simulated cluster: real control plane, simulated workers, shaped wire.

What is REAL here: the journaled :class:`RendezvousServer` (full HTTP
stack, HMAC auth path, journal fsyncs, ``RV_*`` trace spans), the
:class:`ElasticDriver` (lease judgment, reset-request handling, epoch
publication, batched tick reads), and the :class:`HTTPStoreClient` wire
codec.  What is SIMULATED: the workers — lightweight
:class:`SimWorker` records whose only behavior is renewing leases,
pushing metrics snapshots, posting reset requests, and acking epochs —
and the network, via :class:`~horovod_tpu.sim.wire.ShapedStore` per-link
delay injection.

That split is the point (ISSUE 15): membership churn at np=512 exercises
exactly the code a real 512-rank job would exercise on the control
plane, without 512 processes.  Each simulated HOST owns one shaped
client link and batches its ranks' per-period ops into ONE ``/batch``
transaction — the host-level fan-in shape — so control traffic scales
with hosts, and the shaped wire makes that visible in wall time.

Determinism: the churn schedule (event kinds + victims) comes from
``random.Random(seed)`` over the static slot layout, and every link's
jitter stream is seeded from ``(seed, link_id)``.  The artifact carries
a ``determinism.digest`` — a SHA-256 over the schedule plus each link's
:meth:`~horovod_tpu.sim.wire.ShapedWire.preview` — that is a pure
function of (seed, topology, shape params): two runs with the same
``HOROVOD_SIM_SEED`` produce byte-identical digests.

Traces: the server writes its control-plane timeline and the sim process
activates a driver-pid timeline, so the REAL driver's ``CHURN_EVENT`` /
``DRV_SPAWN`` spans and the client's ``RVC_*`` round-trips (including
``RVC_WIRE`` shaped-delay spans) land exactly as in production —
``hvd-control-path`` attributes a sim run identically to a live one.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import random
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ..common import env as env_mod
from ..common.logging_util import get_logger
from ..core import metrics
from ..core.timeline import DRIVER_TRACE_PID, Timeline
from ..elastic.discovery import FixedHosts, HostManager
from ..elastic.driver import ElasticDriver
from ..elastic.rendezvous_client import (
    DEMOTION_REPORT_SCOPE,
    EPOCH_ACK_SCOPE,
    RESET_REQUEST_SCOPE,
)
from ..runner.hosts import HostInfo, SlotInfo
from ..runner.rendezvous import ExternalRendezvous, RendezvousServer
from ..transport.store import LEASE_SCOPE, HTTPStoreClient
from .wire import ShapedStore, ShapedWire

log = get_logger("horovod_tpu.sim.cluster")

#: Kinds the schedule samples for ordinary churn events.  The final
#: event of every run is always ``coordinated_abort`` (the acceptance
#: criterion pins it at np=128/256/512).
EVENT_KINDS = ("lease_expiry", "reset_request")

COORDINATED_ABORT = "coordinated_abort"


@dataclass
class SimWorker:
    """A simulated rank: all control-plane behavior, no training."""

    identity: str
    hostname: str
    local_rank: int
    rank: int = -1
    epoch: int = 0
    #: Bumped every (re)spawn; embedded in the lease value so a revived
    #: victim's renewals never collide with its previous incarnation's.
    incarnation: int = 0
    renewals: int = 0
    renewing: bool = True

    def lease_value(self) -> bytes:
        return json.dumps({"rank": self.rank, "inc": self.incarnation,
                           "renewals": self.renewals}).encode()

    def metrics_value(self) -> bytes:
        # Shape of a real worker push (core/state.py) at snapshot size
        # zero — the op MIX matters for the wire model, not the payload.
        return json.dumps({"version": 1, "rank": self.rank,
                           "renewals": self.renewals}).encode()


class SimCluster:
    """One simulated elastic job.  Single-threaded on the sim side: the
    renewal loop runs on the caller's thread (the REAL driver's
    discovery thread runs concurrently, as in production)."""

    def __init__(self, np: int, slots_per_host: int = 8,
                 seed: Optional[int] = None,
                 lease_timeout: float = 1.5, renew_period: float = 0.25,
                 trace: bool = True, min_np: Optional[int] = None):
        if seed is None:
            seed = env_mod.get_int(env_mod.HOROVOD_SIM_SEED, 0)
        self.np = np
        # Churn runs pin min_np == np (every epoch restores full
        # capacity); demotion runs SHED hosts without replacement, so
        # they must leave headroom or the driver would wait for capacity
        # that never comes (run_demotion computes the floor itself).
        self.min_np = np if min_np is None else min_np
        self.slots_per_host = slots_per_host
        self.seed = seed
        self.lease_timeout = lease_timeout
        self.renew_period = renew_period
        self.trace = trace
        n_hosts = math.ceil(np / slots_per_host)
        self.hostnames = [f"h{i:03d}" for i in range(n_hosts)]
        self._host_infos = []
        remaining = np
        for h in self.hostnames:
            self._host_infos.append(HostInfo(h, min(slots_per_host,
                                                    remaining)))
            remaining -= self._host_infos[-1].slots
        self.identities = [f"{hi.hostname}:{lr}" for hi in self._host_infos
                           for lr in range(hi.slots)]
        self.workers: Dict[str, SimWorker] = {}
        self._host_clients: Dict[str, ShapedStore] = {}
        self._wires: Dict[str, ShapedWire] = {}
        self._jdir: Optional[str] = None
        self._tdir: Optional[str] = None
        self._server: Optional[RendezvousServer] = None
        self._timeline: Optional[Timeline] = None
        self.driver: Optional[ElasticDriver] = None
        self.port: Optional[int] = None

    # -- lifecycle -----------------------------------------------------

    def _wire(self, link_id: str) -> ShapedWire:
        w = ShapedWire.from_env(link_id, seed=self.seed)
        self._wires[link_id] = w
        return w

    def start(self) -> None:
        self._jdir = tempfile.mkdtemp(prefix="hvd-sim-journal-")
        server_trace = None
        if self.trace:
            self._tdir = tempfile.mkdtemp(prefix="hvd-sim-trace-")
            server_trace = os.path.join(self._tdir, "server.json")
        self._server = RendezvousServer("127.0.0.1", journal_dir=self._jdir,
                                        trace_path=server_trace)
        self.port = self._server.start()
        if self.trace:
            # Driver-pid timeline, activated: the real driver code below
            # runs in THIS process, so its CHURN_EVENT / DRV_SPAWN spans
            # and every client RVC_* span have a sink.
            self._timeline = Timeline(
                os.path.join(self._tdir, "driver.json"),
                rank=DRIVER_TRACE_PID, clock_offset_ns=0,
                process_name=f"sim driver (np={self.np})")
        for hi in self._host_infos:
            self._host_clients[hi.hostname] = ShapedStore(
                HTTPStoreClient("127.0.0.1", self.port),
                self._wire(hi.hostname))
        rendezvous = ExternalRendezvous(
            "127.0.0.1", self.port,
            client=ShapedStore(HTTPStoreClient("127.0.0.1", self.port),
                               self._wire("driver")))
        self.driver = ElasticDriver(
            rendezvous, HostManager(FixedHosts(self._host_infos)),
            min_np=self.min_np, max_np=self.np,
            lease_timeout=self.lease_timeout)
        self.driver.start(self._spawn_worker)
        if metrics.ENABLED:
            metrics.set_gauge("sim_identities", len(self._live()))

    def stop(self, keep_dirs: bool = False) -> None:
        if self.driver is not None:
            self.driver.stop()
        if self._timeline is not None:
            self._timeline.close()
        if self._server is not None:
            self._server.stop()
        if not keep_dirs:
            for d in (self._jdir, self._tdir):
                if d:
                    shutil.rmtree(d, ignore_errors=True)

    def _spawn_worker(self, slot: SlotInfo, epoch: int) -> None:
        """The driver's ``create_worker`` callback: (re)vives the
        identity's simulated rank.  Runs on the driver thread."""
        identity = f"{slot.hostname}:{slot.local_rank}"
        w = self.workers.get(identity)
        if w is None:
            w = SimWorker(identity, slot.hostname, slot.local_rank)
            self.workers[identity] = w
        w.rank = slot.rank
        w.epoch = epoch
        w.incarnation += 1
        w.renewing = True

    # -- per-period traffic (the host fan-in shape) --------------------

    def _live(self) -> List[SimWorker]:
        return [w for w in self.workers.values() if w.renewing]

    def renewal_round(self) -> None:
        """One push period: every host batches its live ranks' lease
        renewals + metrics snapshots into ONE shaped ``/batch`` — N ops,
        one wire charge per HOST, exactly the fan-in aggregator's
        traffic shape."""
        for hi in self._host_infos:
            ops: List[tuple] = []
            for w in self._live():
                if w.hostname != hi.hostname:
                    continue
                w.renewals += 1
                ops.append(("set", metrics.METRICS_SCOPE, w.identity,
                            w.metrics_value()))
                ops.append(("set", LEASE_SCOPE, w.identity,
                            w.lease_value()))
            if ops:
                self._host_clients[hi.hostname].batch(ops)
        # Renewals landed; a tick now sees fresh leases — don't make the
        # driver wait out its 1s poll to notice.
        self.driver._wakeup.set()

    def ack_round(self, epoch: int) -> None:
        """Every live rank acks ``epoch``, batched per host, so the
        driver's renotify scan converges (driver-spawned victims were
        implicitly acked; survivors ack here, as real workers do from
        ``refresh_topology_from_rendezvous``)."""
        for hi in self._host_infos:
            ops = [("set", EPOCH_ACK_SCOPE, w.identity, str(epoch).encode())
                   for w in self._live() if w.hostname == hi.hostname]
            if ops:
                self._host_clients[hi.hostname].batch(ops)
        self.driver._wakeup.set()

    # -- churn injection -----------------------------------------------

    def schedule(self, events: int) -> List[Tuple[str, Optional[str]]]:
        """The deterministic churn plan: ``events - 1`` kinds sampled
        from :data:`EVENT_KINDS` with victims drawn over the static slot
        layout, then one coordinated abort.  Pure function of
        (seed, topology, events) — runs do not consume this RNG."""
        rng = random.Random(f"{self.seed}:schedule")
        plan: List[Tuple[str, Optional[str]]] = []
        for _ in range(max(0, events - 1)):
            plan.append((rng.choice(EVENT_KINDS),
                         rng.choice(self.identities)))
        plan.append((COORDINATED_ABORT, None))
        return plan

    def inject(self, kind: str, victim: Optional[str]) -> None:
        epoch = self.driver.epoch
        if kind == "lease_expiry":
            # The victim goes silent; the REAL lease judgment must
            # notice the unchanged value and declare it dead.
            self.workers[victim].renewing = False
        elif kind == "reset_request":
            # Alive-but-rolled-back: the victim posts a current-epoch
            # reset request over its host's shaped link.
            self._host_clients[self.workers[victim].hostname].batch([
                ("set", RESET_REQUEST_SCOPE, victim, json.dumps(
                    {"epoch": epoch, "reason": "sim: corruption abort"}
                ).encode())])
        elif kind == COORDINATED_ABORT:
            # Every survivor posts the same-epoch reset request (the
            # coordinated-abort recovery contract): one epoch advance
            # answers all of them.
            for hi in self._host_infos:
                ops = [("set", RESET_REQUEST_SCOPE, w.identity,
                        json.dumps({"epoch": epoch,
                                    "reason": "sim: coordinated abort"}
                                   ).encode())
                       for w in self._live() if w.hostname == hi.hostname]
                if ops:
                    self._host_clients[hi.hostname].batch(ops)
        else:
            raise ValueError(f"unknown churn kind {kind!r}")
        if metrics.ENABLED:
            metrics.inc("sim_churn_events_total", kind=kind)
        self.driver._wakeup.set()

    def await_epoch(self, target: int, timeout: float) -> None:
        """Drive renewal rounds until the driver reaches ``target`` —
        live ranks must keep renewing while the driver works out the
        victim, or the sim would manufacture cascading expiries."""
        deadline = time.monotonic() + timeout
        while self.driver.epoch < target:
            if self.driver.finished():
                raise RuntimeError(
                    f"driver stopped at epoch {self.driver.epoch} "
                    f"awaiting {target}: {self.driver.stopped_error}")
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"epoch {target} not reached in {timeout:.0f}s "
                    f"(at {self.driver.epoch})")
            self.renewal_round()
            time.sleep(self.renew_period)

    # -- the run -------------------------------------------------------

    def determinism_digest(self, events: int) -> str:
        """SHA-256 over everything that shapes a run: schedule, slot
        layout, and each link's wire preview.  Independent of wall
        time — the fixed-seed reproducibility witness in the artifact."""
        links = {link: self._probe_wire(link).preview(4096, 4)
                 for link in ["driver"] + self.hostnames}
        blob = json.dumps({
            "seed": self.seed, "np": self.np,
            "slots_per_host": self.slots_per_host,
            "identities": self.identities,
            "schedule": self.schedule(events),
            "wire_previews": links,
        }, sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()

    def _probe_wire(self, link_id: str) -> ShapedWire:
        # A started cluster previews its actual wires; an unstarted one
        # (digest-only use) builds throwaway probes with the same params.
        return self._wires.get(link_id) or ShapedWire.from_env(
            link_id, seed=self.seed)

    def run(self, events: int, keep_dirs: bool = False) -> dict:
        """Bring up np ranks, drive ``events`` churn events through the
        real driver (the last being a coordinated abort), and return the
        artifact record (per-event timings, hvd-control-path
        attribution, journal cost, determinism digest)."""
        plan = self.schedule(events)
        t0 = time.perf_counter()
        self.start()
        bringup_ms = (time.perf_counter() - t0) * 1e3
        event_records: List[dict] = []
        try:
            # Warm-up: a couple of observed renewal rounds so every
            # lease has driver-side tracking state before churn starts.
            for _ in range(2):
                self.renewal_round()
                time.sleep(self.renew_period)
            for kind, victim in plan:
                target = self.driver.epoch + 1
                t0 = time.perf_counter()
                self.inject(kind, victim)
                self.await_epoch(
                    target, timeout=30.0 + 3 * self.lease_timeout)
                self.ack_round(self.driver.epoch)
                event_records.append({
                    "kind": kind, "victim": victim,
                    "epoch": self.driver.epoch,
                    "ms": round((time.perf_counter() - t0) * 1e3, 3),
                })
                if metrics.ENABLED:
                    metrics.set_gauge("sim_identities", len(self._live()))
                # lease_expiry respawns the victim; give its fresh lease
                # one observed round before the next injection.
                self.renewal_round()
                time.sleep(self.renew_period)
        finally:
            self.stop(keep_dirs=True)  # dirs still needed below

        attribution = None
        if self.trace:
            from ..tools.control_path import analyze
            from ..tools.trace_merge import load_trace, merge

            doc = analyze(merge([
                load_trace(os.path.join(self._tdir, "server.json")),
                load_trace(os.path.join(self._tdir, "driver.json"))]))
            attribution = {
                "coverage": doc["coverage"],
                "event_count": doc["event_count"],
                "phase_share": doc["phase_share"],
                "phase_ms_per_event": {
                    p: round(v / 1e3 / max(len(event_records), 1), 3)
                    for p, v in doc["phase_totals_us"].items()},
                "event_wall_ms_p50": round(doc["wall_us"]["p50"] / 1e3, 3),
            }
        journal_bytes = sum(
            os.path.getsize(os.path.join(self._jdir, f))
            for f in os.listdir(self._jdir))
        if not keep_dirs:
            for d in (self._jdir, self._tdir):
                if d:
                    shutil.rmtree(d, ignore_errors=True)

        lat = [e["ms"] for e in event_records]
        lat_sorted = sorted(lat)
        abort_ms = next(e["ms"] for e in event_records
                        if e["kind"] == COORDINATED_ABORT)
        rec = {
            "metric": "sim_churn",
            "np": self.np,
            "hosts": len(self.hostnames),
            "slots_per_host": self.slots_per_host,
            "seed": self.seed,
            "lease_timeout_s": self.lease_timeout,
            "renew_period_s": self.renew_period,
            "final_epoch": self.driver.epoch,
            "bringup_ms": round(bringup_ms, 3),
            "events": event_records,
            "event_ms_p50": lat_sorted[len(lat_sorted) // 2],
            "event_ms_max": lat_sorted[-1],
            "coordinated_abort_ms": abort_ms,
            "sim_wire_delay_s": round(
                sum(w.injected_s for w in self._wires.values()), 4),
            "journal_bytes": journal_bytes,
            "determinism": {
                "digest": self.determinism_digest(events),
                "schedule": [list(p) for p in plan],
            },
        }
        if attribution is not None:
            rec["attribution"] = attribution
        return rec

    # -- self-healing demotion (docs/elastic.md) -----------------------
    #
    # A separate runner, NOT a new EVENT_KINDS member: adding a kind
    # would reshuffle every existing churn schedule (and so every
    # committed determinism digest) for the same seed.

    def demotion_schedule(self, demotions: int) -> List[str]:
        """Deterministic demotion plan: ``demotions`` DISTINCT victim
        hosts sampled from everything but the coordinator's host (the
        whole-world-slow guard aside, rank 0 reporting its own host
        would shed the coordinator mid-verdict — not the scenario this
        lane measures).  Pure function of (seed, topology)."""
        if demotions >= len(self.hostnames):
            raise ValueError(
                f"{demotions} demotions need at least {demotions + 1} "
                f"hosts (have {len(self.hostnames)})")
        rng = random.Random(f"{self.seed}:demotion")
        return rng.sample(self.hostnames[1:], demotions)

    def inject_demotion(self, victim_host: str) -> int:
        """Post a coordinator demotion report naming ``victim_host``'s
        first live rank, over the coordinator host's shaped link — the
        exact store write ``post_demotion_report`` makes.  The EWMA
        evidence is synthesized (the verdict machinery upstream of the
        report is proven by the unit + np=3 chaos lanes); everything
        downstream — report parse, staleness rule, blacklist, epoch
        advance, metrics — is the REAL driver code."""
        epoch = self.driver.epoch
        victim = next(w for w in self._live()
                      if w.hostname == victim_host)
        payload = json.dumps({
            "epoch": epoch,
            "rank": victim.rank,
            "hostname": victim_host,
            "ewma": 3.0 * self.lease_timeout,
            "threshold": self.lease_timeout,
            "cycles": 10,
            "posted_unix": time.time(),
        }).encode()
        self._host_clients[self.hostnames[0]].batch([
            ("set", DEMOTION_REPORT_SCOPE, self.identities[0], payload)])
        if metrics.ENABLED:
            metrics.inc("sim_churn_events_total", kind="demotion")
        self.driver._wakeup.set()
        return victim.rank

    def demotion_digest(self, demotions: int) -> str:
        """Demotion-lane analog of :meth:`determinism_digest`: SHA-256
        over the demotion plan, slot layout, capacity floor, and wire
        previews — reproducibility witness for the committed artifact."""
        links = {link: self._probe_wire(link).preview(4096, 4)
                 for link in ["driver"] + self.hostnames}
        blob = json.dumps({
            "seed": self.seed, "np": self.np, "min_np": self.min_np,
            "slots_per_host": self.slots_per_host,
            "identities": self.identities,
            "demotion_schedule": self.demotion_schedule(demotions),
            "wire_previews": links,
        }, sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()

    def run_demotion(self, demotions: int, keep_dirs: bool = False) -> dict:
        """Drive ``demotions`` chronic-straggler demotions through the
        real driver and return the demotion-latency artifact: per event,
        flag→epoch (report posted to the shed host's epoch published)
        and flag→first-round (through the first completed control round
        of the NEW world — the control-plane floor under the first
        training step, since simulated workers take no steps)."""
        plan = self.demotion_schedule(demotions)
        shed = sum(hi.slots for hi in self._host_infos
                   if hi.hostname in plan)
        if self.min_np > self.np - shed:
            # Shedding below min_np would park the driver at "waiting
            # for capacity" forever (FixedHosts never adds machines).
            self.min_np = self.np - shed
        # The registry is process-global and runs can share a process
        # (test suites): report THIS run's demotion transitions.
        base_transitions = metrics.registry.get_counter(
            "driver_epoch_transitions_total", cause="demotion")
        t0 = time.perf_counter()
        self.start()
        bringup_ms = (time.perf_counter() - t0) * 1e3
        event_records: List[dict] = []
        try:
            for _ in range(2):
                self.renewal_round()
                time.sleep(self.renew_period)
            for victim_host in plan:
                target = self.driver.epoch + 1
                t_flag = time.perf_counter()
                rank = self.inject_demotion(victim_host)
                self.await_epoch(
                    target, timeout=30.0 + 3 * self.lease_timeout)
                t_epoch = time.perf_counter()
                self.ack_round(self.driver.epoch)
                # The shed host's ranks saw rank -1 and exited (real
                # workers do this from refresh_topology_from_rendezvous
                # after acking).
                for w in self.workers.values():
                    if w.hostname == victim_host:
                        w.renewing = False
                self.renewal_round()
                t_step = time.perf_counter()
                event_records.append({
                    "victim_host": victim_host,
                    "rank": rank,
                    "epoch": self.driver.epoch,
                    "flag_to_epoch_ms": round((t_epoch - t_flag) * 1e3, 3),
                    "flag_to_first_round_ms": round(
                        (t_step - t_flag) * 1e3, 3),
                })
                if metrics.ENABLED:
                    metrics.set_gauge("sim_identities", len(self._live()))
                time.sleep(self.renew_period)
        finally:
            self.stop(keep_dirs=True)  # dirs still needed below

        attribution = None
        if self.trace:
            from ..tools.control_path import analyze
            from ..tools.trace_merge import load_trace, merge

            doc = analyze(merge([
                load_trace(os.path.join(self._tdir, "server.json")),
                load_trace(os.path.join(self._tdir, "driver.json"))]))
            attribution = {
                "coverage": doc["coverage"],
                "event_count": doc["event_count"],
                "phase_share": doc["phase_share"],
                "event_wall_ms_p50": round(doc["wall_us"]["p50"] / 1e3, 3),
            }
        journal_bytes = sum(
            os.path.getsize(os.path.join(self._jdir, f))
            for f in os.listdir(self._jdir))
        if not keep_dirs:
            for d in (self._jdir, self._tdir):
                if d:
                    shutil.rmtree(d, ignore_errors=True)

        epoch_lat = sorted(e["flag_to_epoch_ms"] for e in event_records)
        step_lat = sorted(e["flag_to_first_round_ms"]
                          for e in event_records)
        rec = {
            "metric": "sim_demotion",
            "np": self.np,
            "min_np": self.min_np,
            "hosts": len(self.hostnames),
            "slots_per_host": self.slots_per_host,
            "seed": self.seed,
            "lease_timeout_s": self.lease_timeout,
            "renew_period_s": self.renew_period,
            "final_epoch": self.driver.epoch,
            "bringup_ms": round(bringup_ms, 3),
            "events": event_records,
            "flag_to_epoch_ms_p50": epoch_lat[len(epoch_lat) // 2],
            "flag_to_epoch_ms_max": epoch_lat[-1],
            "flag_to_first_round_ms_p50": step_lat[len(step_lat) // 2],
            "flag_to_first_round_ms_max": step_lat[-1],
            "driver_demotion_transitions": metrics.registry.get_counter(
                "driver_epoch_transitions_total",
                cause="demotion") - base_transitions,
            "sim_wire_delay_s": round(
                sum(w.injected_s for w in self._wires.values()), 4),
            "journal_bytes": journal_bytes,
            "determinism": {
                "digest": self.demotion_digest(demotions),
                "schedule": list(plan),
            },
        }
        if attribution is not None:
            rec["attribution"] = attribution
        return rec

    # -- zero-restart resharding (docs/elastic.md "Live resharding") ---
    #
    # Same separate-runner rationale as demotion: a new EVENT_KINDS
    # member would reshuffle every committed churn schedule (and so
    # every committed determinism digest) for the same seed.

    def reshard_schedule(self, kills: int) -> List[str]:
        """Deterministic preemption plan: ``kills`` victims sampled over
        the static slot layout (repeats allowed — real preemption churn
        revisits hosts).  Pure function of (seed, topology)."""
        rng = random.Random(f"{self.seed}:reshard")
        return [rng.choice(self.identities) for _ in range(kills)]

    def await_reshard_commit(self, timeout: float) -> None:
        """Drive renewal rounds until the driver's pending reshard
        commits (every survivor's epoch ack on record).  Returns
        immediately when nothing is pending — the HOROVOD_RESHARD=0
        baseline arm never arms one."""
        deadline = time.monotonic() + timeout
        while self.driver._reshard_pending is not None:
            if self.driver.finished():
                raise RuntimeError(
                    f"driver stopped awaiting reshard commit: "
                    f"{self.driver.stopped_error}")
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"reshard at epoch {self.driver.epoch} not committed "
                    f"in {timeout:.0f}s (unacked: "
                    f"{self.driver._reshard_pending.get('missing')})")
            self.renewal_round()
            time.sleep(self.renew_period)

    def reshard_digest(self, kills: int) -> str:
        """Reshard-lane analog of :meth:`determinism_digest`: SHA-256
        over the kill plan, slot layout, and wire previews — the
        reproducibility witness for the committed artifact."""
        links = {link: self._probe_wire(link).preview(4096, 4)
                 for link in ["driver"] + self.hostnames}
        blob = json.dumps({
            "seed": self.seed, "np": self.np,
            "slots_per_host": self.slots_per_host,
            "identities": self.identities,
            "reshard_schedule": self.reshard_schedule(kills),
            "wire_previews": links,
        }, sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()

    def run_reshard(self, kills: int, keep_dirs: bool = False) -> dict:
        """Drive ``kills`` preemptions through the real driver with
        live resharding and return the reshard-latency artifact.

        Per kill: the victim goes silent, the REAL lease judgment
        expires it, the epoch advance publishes the reshard-marked
        table (survivors stay in place; the victim's slot respawns as a
        joiner), survivors ack, and the driver's commit probe writes
        the commit record.  Measured: kill→epoch (marked publish
        visible), kill→commit (all survivor acks on record), and
        kill→first-round (through the first completed control round of
        the new world — the control-plane floor under the first
        post-churn training step).  Under ``HOROVOD_RESHARD=0`` the
        same runner measures the legacy full-teardown control path —
        the baseline arm of the committed A/B artifact."""
        plan = self.reshard_schedule(kills)
        base_reshards = metrics.registry.get_counter(
            "driver_epoch_transitions_total", cause="reshard")
        base_fallbacks = metrics.registry.get_counter(
            "reshard_fallbacks_total")
        t0 = time.perf_counter()
        self.start()
        reshard_on = self.driver.reshard_enabled
        bringup_ms = (time.perf_counter() - t0) * 1e3
        event_records: List[dict] = []
        try:
            for _ in range(2):
                self.renewal_round()
                time.sleep(self.renew_period)
            for victim in plan:
                target = self.driver.epoch + 1
                t_kill = time.perf_counter()
                self.workers[victim].renewing = False
                if metrics.ENABLED:
                    metrics.inc("sim_churn_events_total", kind="reshard")
                self.driver._wakeup.set()
                self.await_epoch(
                    target, timeout=30.0 + 3 * self.lease_timeout)
                t_epoch = time.perf_counter()
                pend = self.driver._reshard_pending
                marked = pend is not None and pend["epoch"] >= target
                self.ack_round(self.driver.epoch)
                self.await_reshard_commit(
                    timeout=30.0 + 3 * self.lease_timeout)
                t_commit = time.perf_counter()
                self.renewal_round()
                t_round = time.perf_counter()
                event_records.append({
                    "victim": victim,
                    "epoch": self.driver.epoch,
                    "marked": marked,
                    "kill_to_epoch_ms": round(
                        (t_epoch - t_kill) * 1e3, 3),
                    "kill_to_commit_ms": round(
                        (t_commit - t_kill) * 1e3, 3),
                    "kill_to_first_round_ms": round(
                        (t_round - t_kill) * 1e3, 3),
                })
                if metrics.ENABLED:
                    metrics.set_gauge("sim_identities", len(self._live()))
                time.sleep(self.renew_period)
        finally:
            self.stop(keep_dirs=True)  # dirs still needed below

        attribution = None
        if self.trace:
            from ..tools.control_path import analyze
            from ..tools.trace_merge import load_trace, merge

            doc = analyze(merge([
                load_trace(os.path.join(self._tdir, "server.json")),
                load_trace(os.path.join(self._tdir, "driver.json"))]))
            attribution = {
                "coverage": doc["coverage"],
                "event_count": doc["event_count"],
                "phase_share": doc["phase_share"],
                "event_wall_ms_p50": round(doc["wall_us"]["p50"] / 1e3, 3),
            }
        journal_bytes = sum(
            os.path.getsize(os.path.join(self._jdir, f))
            for f in os.listdir(self._jdir))
        if not keep_dirs:
            for d in (self._jdir, self._tdir):
                if d:
                    shutil.rmtree(d, ignore_errors=True)

        commit_lat = sorted(e["kill_to_commit_ms"] for e in event_records)
        round_lat = sorted(e["kill_to_first_round_ms"]
                           for e in event_records)
        rec = {
            "metric": "sim_reshard",
            "np": self.np,
            "hosts": len(self.hostnames),
            "slots_per_host": self.slots_per_host,
            "seed": self.seed,
            "reshard_enabled": reshard_on,
            "lease_timeout_s": self.lease_timeout,
            "renew_period_s": self.renew_period,
            "final_epoch": self.driver.epoch,
            "bringup_ms": round(bringup_ms, 3),
            "events": event_records,
            "kill_to_commit_ms_p50": commit_lat[len(commit_lat) // 2],
            "kill_to_commit_ms_max": commit_lat[-1],
            "kill_to_first_round_ms_p50": round_lat[len(round_lat) // 2],
            "kill_to_first_round_ms_max": round_lat[-1],
            "driver_reshard_transitions": metrics.registry.get_counter(
                "driver_epoch_transitions_total",
                cause="reshard") - base_reshards,
            "reshard_fallbacks": metrics.registry.get_counter(
                "reshard_fallbacks_total") - base_fallbacks,
            "sim_wire_delay_s": round(
                sum(w.injected_s for w in self._wires.values()), 4),
            "journal_bytes": journal_bytes,
            "determinism": {
                "digest": self.reshard_digest(kills),
                "schedule": list(plan),
            },
        }
        if attribution is not None:
            rec["attribution"] = attribution
        return rec
