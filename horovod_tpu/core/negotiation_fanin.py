"""Tree negotiation fan-in — O(hosts) coordinator ingress on the mask
fast path.

The star negotiation ships every rank's readiness bitvector (PR 1 mask
frames, ``core/messages.py:MaskFrame``) straight to the coordinator:
O(ranks) blocking recvs per cycle at rank 0, the last O(ranks) hot path
after the control plane (elastic/fanin.py) and membership churn
(docs/elastic.md "Live resharding") were fixed.  This module supplies
the data-plane analog of the reference's hierarchical controller: each
host's ``local_rank 0`` becomes the **negotiation aggregator** — it
collects its colocated ranks' cycle payloads, folds the mask frames that
agree into ONE :class:`~.messages.HostMaskFrame`, forwards a single
bundle up to the coordinator, and fans the coordinator's
(identical-for-everyone)
response payload back down.  Coordinator ingress per cycle drops from
``np - 1`` frames to ``(hosts - 1) + (local_size - 1)``.

Scope is deliberately the mask fast path only: a rank whose cycle needs
a full ``RequestList`` (cache miss, join, shutdown-with-requests) rides
the aggregator's bundle UNFOLDED, and the coordinator ingests it exactly
as the star would — the PR 1 cache-bit semantics stay bit-exact because
folding only ever merges frames that say the same thing.

Statelessness is the correctness keystone: the aggregator keeps no
accumulated readiness and combines no two ranks' bits — a worker
announces a cached tensor's bit once and the coordinator alone keeps it
pending, rank by rank, as under the star — so each cycle's fold is a
pure function of that cycle's frames, and no crash/reorder can lose or
double-count a bit across cycles (``tools/mck/fanin_model.py`` checks
the fold with an aggregator that dies or stalls at any step).

Degrade semantics mirror ``elastic/fanin.py``'s aggregator-liveness
idiom, adapted to a blocking lockstep mesh where a member CANNOT
unilaterally reroute mid-epoch (the coordinator's recv set is fixed):

- aggregator DEATH: the member's blocking ``recv`` raises
  ``PeerGoneError`` promptly → coordinated abort → cheap in-place
  reshard (PR 19) → the respawned epoch re-trees.  No bit is lost: the
  aborted cycle is discarded on every path and the next cycle
  re-announces everything.
- aggregator WEDGE (alive but stuck): members check the aggregator's
  heartbeat file before each send; ~1.5 heartbeat periods of staleness
  (``elastic/fanin.py:HEARTBEAT_STALE_PERIODS``) convicts it —
  ``AggregatorStaleError`` → abort, with a best-effort veto written to
  the rendezvous store (``transport/scopes.py:NEGOTIATION_VETO_SCOPE``)
  so the recovered epoch runs this host DIRECT for the veto-cooldown
  window instead of re-treeing under the same wedge.
"""

from __future__ import annotations

import hashlib
import os
import shutil
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from ..common import env as env_mod
from ..common.exceptions import AggregatorStaleError, HorovodInternalError
from ..common.logging_util import get_logger
from ..common.topology import ProcessTopology
from ..elastic.fanin import HEARTBEAT_STALE_PERIODS
from .messages import HostMaskFrame, MaskFrame, is_mask_frame

__all__ = [
    "AggregatorHeartbeat",
    "AggregatorStaleError",
    "FaninPlan",
    "build_plan",
    "fold_host",
    "heartbeat_dir",
    "resolve_mode",
]

log = get_logger("horovod_tpu.core.negotiation_fanin")


# ---------------------------------------------------------------------------
# the fold (the production kernel the mck model drives)
# ---------------------------------------------------------------------------

def fold_host(collected: Sequence[Tuple[int, bytes]]) -> List[Tuple[int, bytes]]:
    """One host's per-cycle fold: ``[(rank, payload)]`` (the aggregator's
    own payload included) → bundle entries for the coordinator.

    Mask frames that carry the SAME bitvector collapse into one
    :class:`HostMaskFrame` — that mask, ``covered`` = exactly those
    senders, shutdown = OR of their flags (matching the coordinator's own
    OR-fold over per-rank frames) — so a host whose ranks agree this
    cycle (all idle, the steady state, or all announcing the same
    tensors) sends one frame, and one frame more for each mask that
    differs.  Masks are never ANDed across ranks: a worker announces a
    cached tensor's bit ONCE, in the cycle it pops the request, and the
    coordinator keeps it pending per rank; an AND over one cycle's frames
    drops the bit of every rank whose neighbour announces a cycle later,
    for good, and the job waits for ever on a tensor all ranks are ready
    for (the wedge of ``ROADMAP.md`` D0 (ii)).  Everything else passes
    through unfolded, so full-RequestList cycles keep per-rank fidelity.
    Pure and stateless: the output is a function of this cycle's input
    alone.
    """
    by_mask: Dict[int, HostMaskFrame] = {}
    entries: List[Tuple[int, bytes]] = []
    for rank, payload in collected:
        if is_mask_frame(payload):
            frame = MaskFrame.from_bytes(payload)
            host = by_mask.setdefault(
                frame.mask_int, HostMaskFrame(mask=frame.mask))
            host.covered.append(rank)
            host.shutdown = host.shutdown or frame.shutdown
        else:
            entries.append((rank, payload))
    for host in by_mask.values():
        host.covered.sort()
        entries.append((host.covered[0], host.to_bytes()))
    entries.sort()
    return entries


# ---------------------------------------------------------------------------
# role / plan derivation
# ---------------------------------------------------------------------------

def _blocked_layout(topology: ProcessTopology) -> bool:
    """True when global ranks are laid out host-major ("blocked"):
    rank = cross_rank * local_size + local_rank.  The plan derives every
    rank's role from arithmetic on this layout, so all three parties
    (member, aggregator, coordinator) agree without exchanging a table.
    """
    ls = topology.local_size
    return (ls > 0
            and topology.local_rank == topology.rank % ls
            and topology.cross_rank == topology.rank // ls)


def resolve_mode(topology: ProcessTopology) -> str:
    """The ``HOROVOD_NEGOTIATION_FANIN`` gate → "on" | "off".

    "auto" (default) turns the tree on exactly when it can pay: a
    blocked-homogeneous layout with >= 2 ranks per host on >= 2 hosts
    (single-rank hosts have nothing to fold — they bypass the tree
    entirely).  A forced "1" on a layout the plan cannot cover is a loud
    config error, never a silent star fallback.
    """
    raw = (env_mod.get_str(env_mod.HOROVOD_NEGOTIATION_FANIN, "auto")
           or "auto").strip().lower()
    if raw not in ("auto", "0", "1"):
        raise ValueError(
            f"HOROVOD_NEGOTIATION_FANIN={raw!r}: expected auto|0|1")
    if raw == "0":
        return "off"
    structural = (topology.size > 2
                  and topology.local_size >= 2
                  and topology.cross_size >= 2
                  and topology.is_homogeneous
                  and _blocked_layout(topology))
    if raw == "1" and not structural:
        raise HorovodInternalError(
            "HOROVOD_NEGOTIATION_FANIN=1 but the rank layout cannot host "
            f"a fan-in tree (size={topology.size}, "
            f"local_size={topology.local_size}, "
            f"cross_size={topology.cross_size}, "
            f"homogeneous={topology.is_homogeneous}, "
            f"blocked={_blocked_layout(topology)}); fan-in needs a "
            "blocked-homogeneous layout with >= 2 ranks/host on >= 2 "
            "hosts — fix the launcher's HOROVOD_LOCAL_* env or unset the "
            "knob")
    return "on" if structural else "off"


@dataclass(frozen=True)
class FaninPlan:
    """This rank's role in the negotiation tree for one epoch.

    Derived identically on every rank from (topology, vetoed hosts) —
    rank 0's decision record (``core/state.py``) carries only the mode
    and the vetoed host list, the rest is arithmetic.  While a plan is
    active it fully determines the wire shape (it supersedes
    ``HOROVOD_CONTROLLER_TOPOLOGY``): the coordinator's recv set is
    ``coordinator_senders`` and nothing else.
    """

    #: "coordinator" | "aggregator" | "member" | "direct"
    role: str
    #: member: the aggregator rank this member's frames route through.
    aggregator_rank: int
    #: aggregator: the colocated ranks it serves (itself excluded).
    member_ranks: Tuple[int, ...]
    #: coordinator: every rank it exchanges payloads with, sorted.
    coordinator_senders: Tuple[int, ...]
    #: coordinator: the subset of senders whose upward frame is a bundle.
    bundle_senders: frozenset

    @property
    def active(self) -> bool:
        return self.role != "direct" or bool(self.coordinator_senders)


def build_plan(topology: ProcessTopology,
               vetoed_hosts: Sequence[int] = ()) -> FaninPlan:
    """Build this rank's :class:`FaninPlan`.  ``vetoed_hosts`` are
    cross-rank indices whose ranks run direct (stale-aggregator
    conviction cooldown).  Host 0 is always direct: its would-be
    aggregator IS the coordinator, so its members' star sends already
    land at rank 0 — a fold there would add a hop to save nothing.
    """
    if not _blocked_layout(topology) or not topology.is_homogeneous:
        raise HorovodInternalError(
            f"rank {topology.rank}: negotiation fan-in requires a "
            "blocked-homogeneous rank layout "
            f"(local_rank={topology.local_rank}, "
            f"local_size={topology.local_size}, "
            f"cross_rank={topology.cross_rank}, size={topology.size})")
    ls = topology.local_size
    vetoed = set(vetoed_hosts)
    rank, host = topology.rank, topology.cross_rank

    senders: List[int] = []
    bundles: List[int] = []
    for h in range(topology.cross_size):
        base = h * ls
        if h == 0:
            senders.extend(range(1, base + ls))
        elif h in vetoed:
            senders.extend(range(base, base + ls))
        else:
            senders.append(base)
            bundles.append(base)

    if rank == 0:
        role, agg = "coordinator", -1
        members: Tuple[int, ...] = ()
    elif host == 0 or host in vetoed:
        role, agg, members = "direct", -1, ()
    elif topology.local_rank == 0:
        role, agg = "aggregator", rank
        members = tuple(range(rank + 1, rank + ls))
    else:
        role, agg = "member", host * ls
        members = ()
    return FaninPlan(role=role, aggregator_rank=agg, member_ranks=members,
                     coordinator_senders=tuple(senders),
                     bundle_senders=frozenset(bundles))


# ---------------------------------------------------------------------------
# aggregator-liveness heartbeat (elastic/fanin.py idiom)
# ---------------------------------------------------------------------------

def heartbeat_dir(job_key: str, cross_rank: int) -> str:
    """Per-(job, host) heartbeat directory shared by the host's ranks —
    keyed like ``elastic/fanin.py``'s spool root: the job key (store
    endpoint; two jobs on one box must not share heartbeats) plus the
    host identity and cross rank (two hosts simulated on one box via
    ``HOROVOD_SHM_HOSTID`` get distinct directories)."""
    from ..transport.select import host_identity

    base = env_mod.get_str(env_mod.HOROVOD_NEGOTIATION_FANIN_DIR) or None
    if base is None:
        import tempfile

        base = tempfile.gettempdir()
    token = hashlib.sha1(
        f"{job_key}|{host_identity(cross_rank)}".encode()).hexdigest()[:16]
    return os.path.join(base, f"hvd-neg-fanin-{token}")


class AggregatorHeartbeat:
    """Heartbeat file between one host's aggregator and its members.

    Aggregator side: :meth:`touch` after each completed relay cycle,
    rate-limited to one utime per half period — a wedged aggregator
    stops touching, which is the whole signal.  Member side:
    :meth:`check` before each upward send — raises
    :class:`AggregatorStaleError` when the file is older than
    ``HEARTBEAT_STALE_PERIODS`` periods; an ABSENT file is fresh during
    the same-sized arming grace (the aggregator may not have finished
    its first cycle) and stale after.  Stat calls are rate-limited the
    same way, so ~1 ms negotiation cycles don't turn into an fstat storm.
    Like ``elastic/fanin.py``, filesystem trouble on the aggregator side
    degrades loudly-but-gracefully: members will convict and the job
    falls back to direct.
    """

    def __init__(self, dir_path: str, period: float, aggregator_rank: int,
                 cross_rank: int, is_aggregator: bool):
        self._path = os.path.join(dir_path, "negotiation.hb")
        self._period = max(period, 1e-3)
        self._aggregator_rank = aggregator_rank
        self._cross_rank = cross_rank
        self._is_aggregator = is_aggregator
        self._armed_at = time.time()
        self._last_touch = 0.0
        self._last_check = 0.0
        self._last_age = 0.0
        if is_aggregator:
            try:
                os.makedirs(dir_path, exist_ok=True)
                self._touch(force=True)
            except OSError as e:
                log.warning(
                    "negotiation heartbeat unavailable (%s); members will "
                    "convict this aggregator and the job will degrade to "
                    "direct pushes", e)

    # -- aggregator side ----------------------------------------------

    def _touch(self, force: bool = False) -> None:
        now = time.time()
        if not force and now - self._last_touch < self._period / 2:
            return
        self._last_touch = now
        try:
            with open(self._path, "a"):
                os.utime(self._path, None)
        except OSError as e:
            log.warning("negotiation heartbeat write failed (%s); members "
                        "will degrade this host to direct pushes", e)

    def touch(self) -> None:
        self._touch()

    def close(self) -> None:
        """The aggregator takes its heartbeat with it when its loop ends
        (its members' loops have ended with it: the recv sets are
        lockstep), so that no later job with the same key finds it."""
        if self._is_aggregator:
            shutil.rmtree(os.path.dirname(self._path), ignore_errors=True)

    # -- member side --------------------------------------------------

    def check(self) -> None:
        """Raise :class:`AggregatorStaleError` on a convicted (wedged)
        aggregator; return silently otherwise."""
        now = time.time()
        if now - self._last_check < self._period / 2:
            return
        self._last_check = now
        window = HEARTBEAT_STALE_PERIODS * self._period
        try:
            age = now - os.stat(self._path).st_mtime
        except OSError:
            # Absent: the aggregator hasn't completed a cycle yet (or
            # its filesystem is broken).  Grace-period from arming, then
            # convict — a host must never be silenced by a heartbeat
            # that was simply never born.
            age = now - self._armed_at
            if age < window:
                return
            raise AggregatorStaleError(self._aggregator_rank,
                                       self._cross_rank, age, window) \
                from None
        self._last_age = age
        if age >= window:
            raise AggregatorStaleError(self._aggregator_rank,
                                       self._cross_rank, age, window)


def make_heartbeat(plan: FaninPlan, topology: ProcessTopology,
                   job_key: str) -> Optional[AggregatorHeartbeat]:
    """Heartbeat for this rank's role, or None for roles that need none
    (coordinator / direct)."""
    if plan.role not in ("member", "aggregator"):
        return None
    period = env_mod.get_float(
        env_mod.HOROVOD_NEGOTIATION_FANIN_HEARTBEAT_SECS,
        env_mod.DEFAULT_NEGOTIATION_FANIN_HEARTBEAT_SECS)
    return AggregatorHeartbeat(
        heartbeat_dir(job_key, topology.cross_rank), period,
        aggregator_rank=plan.aggregator_rank
        if plan.role == "member" else topology.rank,
        cross_rank=topology.cross_rank,
        is_aggregator=plan.role == "aggregator")


# ---------------------------------------------------------------------------
# veto bookkeeping helpers (state.py reads/writes through these)
# ---------------------------------------------------------------------------

def veto_cooldown_epochs() -> int:
    return max(1, env_mod.get_int(
        env_mod.HOROVOD_NEGOTIATION_FANIN_VETO_EPOCHS,
        env_mod.DEFAULT_NEGOTIATION_FANIN_VETO_EPOCHS))


def active_vetoes(records: Dict[str, dict], epoch: int) -> List[str]:
    """Hostnames whose veto is still inside the cooldown window at
    ``epoch``.  ``records`` maps hostname → the stored veto JSON
    (``{"epoch": N, ...}``); malformed records are ignored — a veto is
    an optimization hint, never a correctness dependency."""
    out = []
    cooldown = veto_cooldown_epochs()
    for hostname, rec in records.items():
        try:
            veto_epoch = int(rec["epoch"])
        except (KeyError, TypeError, ValueError):
            continue
        if epoch - veto_epoch < cooldown:
            out.append(hostname)
    return sorted(out)
