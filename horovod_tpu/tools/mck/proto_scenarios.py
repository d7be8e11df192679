"""Bounded workloads for ``hvd-mck proto``.

Each scenario is one small cluster — a driver ticking the production
judgment kernels, workers posting through the production payload
builders, optionally the coordinator's real DemotionPolicy — plus
explicit crash and clock budgets.  Crash and clock actions are
environment moves (preemption-free), so a scenario with
``store_crashes=1`` explores the crash at EVERY schedule position,
including between a batched transaction's journal append and its ack.

The clean suite must pass COMPLETE (never truncated); the kill suite
(proto_mutations.py) asserts each seeded protocol bug dies in the
scenario named here.  Sizing note: scenarios are deliberately tiny —
the explorer replays prefixes generator-by-generator, and the claim is
per-protocol-phase, not per-fleet.  Grow a scenario only with a bound
check (``--smoke`` trips exit 2 on truncation).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from ...elastic.rendezvous_client import (
    DEMOTION_REPORT_SCOPE,
    RANK_AND_SIZE_SCOPE,
    RESET_REQUEST_SCOPE,
    demotion_report_payload,
    reset_request_payload,
)
from ...transport.scopes import LEASE_SCOPE


class ProtoScenario:
    """One bounded cluster workload (see module docstring)."""

    __slots__ = ("name", "description", "preemptions", "ticks", "epoch0",
                 "lease_timeout", "slots", "workers", "coordinator",
                 "seeds", "clock_steps", "store_crashes", "driver_crashes",
                 "active_np", "reshard")

    def __init__(self, name: str, description: str, preemptions: int,
                 ticks: int, slots: Dict[str, Tuple[int, str]],
                 epoch0: int = 0, lease_timeout: float = 10.0,
                 workers: Optional[List[dict]] = None,
                 coordinator: Optional[dict] = None,
                 seeds: Optional[List[List[tuple]]] = None,
                 clock_steps: Optional[List[float]] = None,
                 store_crashes: int = 0, driver_crashes: int = 0,
                 active_np: Optional[int] = None,
                 reshard: bool = False):
        self.name = name
        self.description = description
        self.preemptions = preemptions
        self.ticks = ticks
        self.epoch0 = epoch0
        self.lease_timeout = lease_timeout
        self.slots = dict(slots)
        self.workers = list(workers or [])
        self.coordinator = coordinator
        self.seeds = [list(s) for s in (seeds or [])]
        self.clock_steps = list(clock_steps or [])
        self.store_crashes = store_crashes
        self.driver_crashes = driver_crashes
        self.active_np = len(slots) if active_np is None else active_np
        # Zero-restart resharding enabled for the model driver: epoch
        # publishes run the real reshard_plan (marker / fallback) and
        # each tick probes reshard_commit_steps.  Off by default so the
        # PR-18 scenarios keep their exact proven state spaces.
        self.reshard = reshard


def _lease_seed(identity: str, rank: int, epoch: int) -> tuple:
    import json

    return ("set", LEASE_SCOPE, identity,
            json.dumps({"rank": rank, "epoch": epoch,
                        "renewals": 0}).encode())


def _slot_seed(identity: str, rank: int, epoch: int, host: str) -> tuple:
    import json

    return ("set", RANK_AND_SIZE_SCOPE, identity,
            json.dumps({"rank": rank, "epoch": epoch,
                        "hostname": host}).encode())


PROTO_SCENARIOS: Dict[str, ProtoScenario] = {s.name: s for s in (
    ProtoScenario(
        "tick_posts",
        "two workers renew leases while one posts a current-epoch reset "
        "request, racing two driver ticks: the tick-vs-worker-posts "
        "interleavings, including a post landing between a tick's fetch "
        "and its judgment",
        preemptions=2, ticks=2,
        slots={"h0:0": (0, "h0"), "h1:0": (1, "h1")},
        workers=[
            {"name": "w0", "identity": "h0:0", "rank": 0, "epoch": 0,
             "script": [("renew",), ("renew",)]},
            {"name": "w1", "identity": "h1:0", "rank": 1, "epoch": 0,
             "script": [("reset", 0, "corruption abort"), ("renew",)]},
        ]),
    ProtoScenario(
        "txn_crash",
        "one 2-op batched transaction (metrics snapshot + lease renewal) "
        "with a store crash explored at every micro-step: the WAL "
        "ordering and group-atomicity proof (acked writes durable, no "
        "recoverable half-transaction)",
        preemptions=2, ticks=1,
        slots={"h0:0": (0, "h0")},
        workers=[
            {"name": "w0", "identity": "h0:0", "rank": 0, "epoch": 0,
             "script": [("renew",)]},
        ],
        store_crashes=1),
    ProtoScenario(
        "stale_race",
        "a reset request and a demotion report from epoch 0 sit in the "
        "store while the driver judges at epoch 1: stale reports must "
        "never advance anything",
        preemptions=2, ticks=1, epoch0=1,
        slots={"h0:0": (0, "h0"), "h1:0": (1, "h1")},
        workers=[
            {"name": "w0", "identity": "h0:0", "rank": 0, "epoch": 1,
             "script": [("renew",)]},
        ],
        seeds=[
            [("set", RESET_REQUEST_SCOPE, "h0:0",
              reset_request_payload(0, "corruption abort"))],
            [("set", DEMOTION_REPORT_SCOPE, "h1:0",
              demotion_report_payload(0, 1, "h1", 9.9, 1.0, 2, 0.0))],
        ],
        active_np=4),
    ProtoScenario(
        "lease_expiry",
        "one worker keeps renewing while another stops, and the clock "
        "jumps past the lease timeout between ticks: expiry-vs-renewal "
        "races, with expiry legitimate only outside a re-grace window",
        preemptions=2, ticks=3, lease_timeout=10.0,
        slots={"h0:0": (0, "h0"), "h1:0": (1, "h1")},
        workers=[
            {"name": "w0", "identity": "h0:0", "rank": 0, "epoch": 0,
             "script": [("renew",), ("renew",), ("renew",)]},
            {"name": "w1", "identity": "h1:0", "rank": 1, "epoch": 0,
             "script": [("renew",)]},
        ],
        clock_steps=[11.0]),
    ProtoScenario(
        "outage_regrace",
        "the store crashes (possibly failing a driver fetch) and the "
        "clock jumps past the lease timeout: after an observed outage "
        "the driver must re-grace every lease before it may expire one",
        preemptions=2, ticks=3, lease_timeout=10.0,
        slots={"h0:0": (0, "h0")},
        workers=[
            {"name": "w0", "identity": "h0:0", "rank": 0, "epoch": 0,
             "script": [("renew",), ("renew",)]},
        ],
        clock_steps=[11.0], store_crashes=1),
    ProtoScenario(
        "np2_demotion",
        "a 2-rank world with one rank chronically over threshold: the "
        "real DemotionPolicy must never post a verdict (one slow rank "
        "IS half the world), and the store flags any report that lands",
        preemptions=2, ticks=1,
        slots={"h0:0": (0, "h0"), "h1:0": (1, "h1")},
        coordinator={"identity": "h0:0", "epoch": 0, "demote_secs": 1.0,
                     "demote_cycles": 2, "active": (0, 1),
                     "observations": [{1: 9.0}, {1: 9.0}, {1: 9.0}]},
        active_np=2),
    ProtoScenario(
        "np4_demotion",
        "a 4-rank world where rank 3 stays over threshold for the full "
        "streak: the real DemotionPolicy convicts it, the driver must "
        "blacklist the host STRICTLY before this tick's discovery poll, "
        "then advance cause-tagged demotion",
        preemptions=2, ticks=2,
        slots={"h0:0": (0, "h0"), "h1:0": (1, "h1"),
               "h2:0": (2, "h2"), "h3:0": (3, "h3")},
        coordinator={"identity": "h0:0", "epoch": 0, "demote_secs": 1.0,
                     "demote_cycles": 2, "active": (0, 1, 2, 3),
                     "observations": [{3: 10.0}, {3: 10.0}]},
        active_np=4),
    ProtoScenario(
        "driver_crash_recovery",
        "a current-epoch reset request drives an advance while the "
        "driver may crash at any step and restart through recover_steps: "
        "the restarted driver must adopt exactly the journal-replayed "
        "epoch and never act on the now-stale request twice",
        preemptions=2, ticks=2, lease_timeout=10.0,
        slots={"h0:0": (0, "h0"), "h1:0": (1, "h1")},
        workers=[
            {"name": "w0", "identity": "h0:0", "rank": 0, "epoch": 0,
             "script": [("reset", 0, "rollback"), ("renew",)]},
        ],
        seeds=[
            [_slot_seed("h0:0", 0, 0, "h0"), _lease_seed("h0:0", 0, 0)],
            [_slot_seed("h1:0", 1, 0, "h1"), _lease_seed("h1:0", 1, 0)],
        ],
        driver_crashes=1),
    ProtoScenario(
        "reshard_commit",
        "zero-restart reshard round-trip with a store crash explored at "
        "every step: one worker goes silent and expires after a clock "
        "jump, the advance publishes the reshard-marked table, the "
        "survivor acks the epoch, and the driver's commit probe may "
        "write the commit record ONLY once every survivor's ack is on "
        "record (publish -> survivor-ack -> topology-commit)",
        preemptions=2, ticks=3, lease_timeout=10.0,
        slots={"h0:0": (0, "h0"), "h1:0": (1, "h1")},
        workers=[
            {"name": "w0", "identity": "h0:0", "rank": 0, "epoch": 0,
             "script": [("renew",), ("ack", 1)]},
        ],
        seeds=[
            [_slot_seed("h0:0", 0, 0, "h0"), _lease_seed("h0:0", 0, 0)],
            [_slot_seed("h1:0", 1, 0, "h1"), _lease_seed("h1:0", 1, 0)],
        ],
        clock_steps=[11.0], store_crashes=1, reshard=True),
    ProtoScenario(
        "reshard_driver_crash",
        "the driver may crash at any step of a reshard (before the "
        "marked publish, between publish and commit, after commit) and "
        "restart through recover_steps: the pending reshard dies with "
        "the driver's memory and the recovery republish (unmarked, at "
        "the adopted epoch) must retire it — a crashed driver degrades "
        "the reshard to the legacy path, never strings survivors along",
        preemptions=2, ticks=3, lease_timeout=10.0,
        slots={"h0:0": (0, "h0"), "h1:0": (1, "h1")},
        workers=[
            {"name": "w0", "identity": "h0:0", "rank": 0, "epoch": 0,
             "script": [("renew",), ("ack", 1)]},
        ],
        seeds=[
            [_slot_seed("h0:0", 0, 0, "h0"), _lease_seed("h0:0", 0, 0)],
            [_slot_seed("h1:0", 1, 0, "h1"), _lease_seed("h1:0", 1, 0)],
        ],
        clock_steps=[11.0], driver_crashes=1, reshard=True),
    ProtoScenario(
        "reshard_fallback",
        "a survivor crashes mid-reshard (its epoch ack never lands "
        "before a current-epoch reset forces the next advance): the "
        "still-pending reshard must drop the marker from the next "
        "publish — the degradation to the legacy full-teardown path is "
        "load-bearing, survivors of a failed reshard may hold blank "
        "never-synced state",
        preemptions=2, ticks=3, lease_timeout=10.0,
        slots={"h0:0": (0, "h0"), "h1:0": (1, "h1")},
        workers=[
            {"name": "w0", "identity": "h0:0", "rank": 0, "epoch": 0,
             "script": [("renew",), ("reset", 1, "peer hard-crash"),
                        ("ack", 1)]},
        ],
        seeds=[
            [_slot_seed("h0:0", 0, 0, "h0"), _lease_seed("h0:0", 0, 0)],
            [_slot_seed("h1:0", 1, 0, "h1"), _lease_seed("h1:0", 1, 0)],
        ],
        clock_steps=[11.0], reshard=True),
    ProtoScenario(
        "finished_beside_respawn",
        "two workers go silent, their leases run out after a clock jump "
        "and the advance respawns their identities, while the silent "
        "processes live on and exit 0: the driver must end the job "
        "at the first tick that finds every identity of the slot table "
        "exited 0 since its last spawn (an exit before the respawn does "
        "not count, and once a worker has finished no advance respawns "
        "anything), and at no tick before",
        preemptions=2, ticks=4, lease_timeout=10.0,
        slots={"h0:0": (0, "h0"), "h1:0": (1, "h1")},
        workers=[
            {"name": "w0", "identity": "h0:0", "rank": 0, "epoch": 0,
             "script": [("exit0",)]},
            {"name": "w1", "identity": "h1:0", "rank": 1, "epoch": 0,
             "script": [("exit0",)]},
        ],
        seeds=[
            [_slot_seed("h0:0", 0, 0, "h0"), _lease_seed("h0:0", 0, 0)],
            [_slot_seed("h1:0", 1, 0, "h1"), _lease_seed("h1:0", 1, 0)],
        ],
        clock_steps=[11.0], reshard=True),
)}

# The negotiation fan-in degrade scenario rides the same registry so the
# CLI, the smoke gate, and the kill suite cover it with zero extra
# plumbing; its execution model lives in fanin_model.py and is routed by
# scenario.kind in proto_model.proto_execution_factory.
from .fanin_model import FANIN_DEGRADE  # noqa: E402

PROTO_SCENARIOS[FANIN_DEGRADE.name] = FANIN_DEGRADE
