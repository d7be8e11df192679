"""Message-passing + crash execution model for the elastic epoch protocol.

``hvd-mck proto`` checks the control plane the same way the shm mode
checks the ring (model.py): the protocol logic under test is the REAL
production code — the driver's epoch-judgment generators
(:mod:`horovod_tpu.elastic.driver`: ``tick_read_steps`` /
``tick_judgment_steps`` / ``outage_recovery_steps`` / ``recover_steps``),
the store's batched-transaction kernel
(:func:`horovod_tpu.transport.store.batch_steps`), the worker-post
payload builders (:mod:`horovod_tpu.elastic.rendezvous_client`), and the
straggler :class:`~horovod_tpu.core.controller.DemotionPolicy` — driven
here against a model cluster instead of live sockets:

- **Processes** (driver "drv", workers "w*", coordinator "coord") are
  glue generators that yield ``("send", ops, tag)`` to put one batched
  transaction on the store's wire, or ``("pause", label)`` at a protocol
  phase boundary.  Each yield is a scheduling point.
- **The store** is one sequential server with a keyed inbox: delivery
  order is a scheduling choice (``("s", (client, seq))`` picks ANY
  queued request), which models message reordering across senders, and
  the keying makes enqueue order irrelevant to the state — two clients'
  sends genuinely commute, which the sleep-set footprints
  (:meth:`ProtoExecution.touches`) rely on; service itself advances one
  ``batch_steps`` micro-op per ``("t",)`` action, so a crash can land
  between any two store steps — including between the group-journal
  append and the reply ack.
- **The journal** is a byte blob of ``pack_frame`` frames, exactly the
  on-disk format (transport/journal.py).  Crash recovery replays it with
  the production longest-valid-prefix rule.  A byte-level torn tail
  truncates to a frame boundary, so checking every FRAME-boundary prefix
  state covers every byte-level crash point (tests/test_mck_proto.py
  asserts this equivalence on a real blob, byte by byte).
- **Crashes** are explicit actions: ``("c", "st")`` kills the store at
  the current micro-step (in-flight and queued requests error back to
  their callers; state recovers by journal replay), ``("c", "drv")``
  kills the driver and restarts it through the production
  ``recover_steps`` kernel.  ``("k", i)`` advances the lease clock by
  the scenario's i-th increment.  All three are environment actions —
  free under the preemption bound — so every schedule in a crash-budget
  scenario includes the crash, at an explored position.

Invariants (violation vocabulary below):

- epoch monotonicity at the store, and at most one STEP_ADVANCE per
  judged tick at the driver;
- every transaction the store ACKED is durable across a crash at every
  point (the WAL ordering: group journal strictly before first apply,
  reply strictly after);
- every journal frame boundary is a transaction boundary (group
  atomicity — no torn half-transaction state is ever recoverable);
- a stale (prior-epoch) reset request or demotion report never advances
  the epoch, judged against the STORE's ground truth of what it served,
  which a driver-side mutant cannot rewrite;
- a demotion report never lands at np <= 2 (structural: the
  whole-world-slow guard makes one slow rank half the world);
- a live-leased identity is never dropped inside the post-outage
  re-grace window;
- a restarted driver adopts exactly the epoch the journal-backed store
  served it — never 0, never a stale predecessor;
- a zero-restart reshard commit record never lands before every
  survivor the marked publish listed has acked that epoch, judged on
  the store's own data (V_RESHARD_EARLY_COMMIT);
- a reshard-marked slot table never publishes while an older marked
  epoch sits uncommitted — the degradation to the legacy full-teardown
  path is mandatory, not best-effort (V_RESHARD_FALLBACK_MISSED);
- at the head of a tick the driver ends the job exactly when every
  identity of the slot table has exited 0 since it was last spawned,
  whatever respawn is still in flight (V_JOB_END).
"""

from __future__ import annotations

import json
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

from ...core.controller import DemotionPolicy
from ...elastic.driver import (
    DRIVER_SCOPE,
    STEP_ADVANCE,
    STEP_BLACKLIST,
    STEP_CLOCK,
    STEP_EXPIRE,
    STEP_FINISH,
    STEP_GATE,
    STEP_GRACE,
    STEP_POLL_HOSTS,
    STEP_TXN,
    job_end_steps,
    outage_recovery_steps,
    recover_steps,
    reshard_commit_steps,
    reshard_plan,
    tick_judgment_steps,
    tick_read_steps,
)
from ...elastic.rendezvous_client import (
    DEMOTION_REPORT_SCOPE,
    EPOCH_ACK_SCOPE,
    RANK_AND_SIZE_SCOPE,
    RESET_REQUEST_SCOPE,
    demotion_report_payload,
    lease_renew_ops,
    reset_request_payload,
)
from ...transport.journal import (
    JOURNAL_MAGIC,
    OP_DELETE,
    OP_GROUP,
    OP_SET,
    decode_group,
    decode_op,
    encode_group,
    iter_frames,
    pack_frame,
)
from ...transport.store import (
    STEP_APPLY,
    STEP_JOURNAL,
    STEP_KEYS,
    STEP_LOAD,
    STEP_NOTIFY,
    STEP_REPLY,
    batch_steps,
)
from .model import Violation

__all__ = [
    "ProtoExecution", "proto_execution_factory", "proto_unit",
    "demotion_report_payload", "reset_request_payload",
    "V_EPOCH_REGRESSION", "V_MULTI_ADVANCE", "V_ACKED_LOST",
    "V_TORN_GROUP", "V_STALE_ACTED", "V_SMALL_WORLD_DEMOTION",
    "V_LIVE_DROPPED", "V_DEMOTED_HOST_KEPT", "V_RECOVER_MISMATCH",
    "V_RESHARD_EARLY_COMMIT", "V_RESHARD_FALLBACK_MISSED",
    "V_JOB_END", "V_MODEL_ERROR",
]

#: Violation names — the proto checker's vocabulary, referenced by the
#: kill suite (proto_mutations.py), tests, and docs/static_analysis.md.
V_EPOCH_REGRESSION = "epoch-regression"
V_MULTI_ADVANCE = "multi-advance"
V_ACKED_LOST = "acked-op-lost"
V_TORN_GROUP = "torn-group"
V_STALE_ACTED = "stale-report-acted"
V_SMALL_WORLD_DEMOTION = "small-world-demotion"
V_LIVE_DROPPED = "live-lease-dropped"
V_DEMOTED_HOST_KEPT = "demoted-host-kept"
V_RECOVER_MISMATCH = "recover-epoch-mismatch"
V_RESHARD_EARLY_COMMIT = "reshard-early-commit"
V_RESHARD_FALLBACK_MISSED = "reshard-fallback-missed"
V_JOB_END = "job-end-misjudged"
V_MODEL_ERROR = "model-error"

RUNNABLE = "runnable"
WAITING = "waiting"
FINISHED = "finished"

_EPOCH_KEY = f"{DRIVER_SCOPE}/epoch"
_RESHARD_COMMIT_KEY = f"{DRIVER_SCOPE}/reshard_commit"

#: Reply sentinels: not-yet-served vs served-with-a-store-error.
_PENDING = object()
_ERROR = object()


class _StoreDown(Exception):
    """Raised INTO a glue generator when its in-flight transaction died
    with the store (the model's URLError/ConnectionError)."""


def proto_unit(action: tuple) -> str:
    """Scheduling unit for preemption accounting: each process is a
    unit, the store (inbox pop + micro-steps) is one unit, and clock
    advancement / crashes are the environment (free — a crash is never
    a scheduler preemption, so crash-at-every-point costs no budget)."""
    kind = action[0]
    if kind == "p":
        return action[1]
    if kind in ("s", "t"):
        return "st"
    return "env"


def _fold_ops(state: Dict[str, bytes], ops) -> Dict[str, bytes]:
    """The post-state one batched transaction commits over ``state`` —
    ground truth straight from the op list, shared with no production
    code path, so a store-side mutant cannot bend both sides at once."""
    out = dict(state)
    for op in ops:
        if op[0] == "check":
            # CAS guard, evaluated against the overlay exactly as
            # batch_steps does: a mismatch aborts the WHOLE batch, so
            # its only legal post-state is the untouched pre-state.
            if out.get(f"{op[1]}/{op[2]}") != op[3]:
                return dict(state)
        elif op[0] == "set":
            out[f"{op[1]}/{op[2]}"] = op[3]
        elif op[0] == "delete":
            out.pop(f"{op[1]}/{op[2]}", None)
    return out


def _journal_records(blob: bytes):
    """Yield every (op, key, value) in the journal's valid prefix, in
    order, expanding group frames — the replay view of the blob."""
    first = True
    for _end, payload in iter_frames(blob):
        if first:
            first = False
            if payload != JOURNAL_MAGIC:
                return
            continue
        if payload and payload[0] == OP_GROUP:
            records = decode_group(payload)
        else:
            records = [decode_op(payload)]
        for rec in records:
            yield rec


def _replay(blob: bytes) -> Dict[str, bytes]:
    """Journal replay with the production longest-valid-prefix rule
    (iter_frames stops at the first torn/corrupt frame)."""
    state: Dict[str, bytes] = {}
    for op, key, value in _journal_records(blob):
        if op == OP_SET:
            state[key] = value
        elif op == OP_DELETE:
            state.pop(key, None)
    return state


class _Req:
    __slots__ = ("client", "ops", "tag", "token")

    def __init__(self, client: str, ops: tuple, tag: str, token: int):
        self.client = client
        self.ops = ops
        self.tag = tag
        self.token = token


class _Proc:
    __slots__ = ("gen", "status", "reply", "token")

    def __init__(self, gen, token: int = 0):
        self.gen = gen
        self.status = RUNNABLE
        self.reply = _PENDING
        self.token = token


# -- glue generators: production kernels wired to the model cluster ------

def _maybe_wrap(ex: "ProtoExecution", role: str, gen, ctx):
    mut = ex.mutation
    if mut is not None and mut.role == role:
        return mut.wrap(gen, ctx)
    return gen


def _drive_kernel(ex: "ProtoExecution", kernel, d: dict):
    """Sub-generator driving a driver kernel whose external steps are
    STEP_TXN (one wire round-trip — a real scheduling point), STEP_CLOCK
    and STEP_GRACE.  A store error is thrown in at the TXN yield as
    :class:`_StoreDown` and propagates to the caller."""
    resp = None
    while True:
        try:
            step = kernel.send(resp)
        except StopIteration as fin:
            return fin.value
        kind = step[0]
        if kind == STEP_TXN:
            resp = yield ("send", tuple(step[1]), step[2])
        elif kind == STEP_CLOCK:
            resp = ex.now
        elif kind == STEP_GRACE:
            d["grace"] = step[1]
            resp = None
        else:
            raise AssertionError(f"unexpected kernel step {step!r}")


def _drive_local(ex: "ProtoExecution", kernel, d: dict) -> None:
    """Drive a kernel with no wire steps (outage re-grace) to completion
    inside the current process step — clock read and grace arm are one
    atomic stamp, exactly as in the production ``_store_recovered``."""
    resp = None
    while True:
        try:
            step = kernel.send(resp)
        except StopIteration:
            return
        resp = None
        if step[0] == STEP_CLOCK:
            resp = ex.now
        elif step[0] == STEP_GRACE:
            d["grace"] = step[1]


def _driver_ticks(ex: "ProtoExecution", d: dict):
    """The driver's tick loop over the production kernels.  Mirrors
    ``ElasticDriver._tick``: fetch (one batched read), outage re-grace
    on the first fetch after a failure, then the judgment generator with
    every step executed against the model cluster."""
    scn = ex.scenario
    while d["tick"] < scn.ticks:
        d["tick"] += 1
        # The job's end is judged first and from nothing the store
        # holds, as in ``ElasticDriver._tick``.  The ground truth is the
        # cluster's own record of who exited 0 since its last spawn.
        ended = any(step[0] == STEP_FINISH for step in _maybe_wrap(
            ex, "driver_job_end",
            job_end_steps(set(ex.slots), set(d["succeeded"])), d))
        if ended != (set(ex.slots) <= ex.exited_ok):
            ex._fail(V_JOB_END,
                     f"tick {d['tick']}: the driver "
                     f"{'ended' if ended else 'did not end'} the job "
                     f"with {sorted(ex.exited_ok)} of {sorted(ex.slots)} "
                     "exited 0 since their last spawn")
            return
        if ended:
            return
        reads = _maybe_wrap(
            ex, "driver_reads",
            tick_read_steps(d["epoch"], None, sorted(ex.slots), (), ()), d)
        try:
            fetched = yield from _drive_kernel(ex, reads, d)
        except _StoreDown:
            d["outage"] = True
            continue
        if d["outage"]:
            d["outage"] = False
            ex.last_recovery_at = ex.now
            _drive_local(
                ex, _maybe_wrap(ex, "driver_recovery",
                                outage_recovery_steps(scn.lease_timeout),
                                d), d)
        # Commit-probe of a pending reshard (production kernel, same tick
        # position as ``_reshard_commit_probe``): reads the survivors'
        # epoch acks over the wire, writes the commit record only when
        # every one has adopted the epoch.
        if scn.reshard and d.get("reshard_pending") is not None:
            pend = d["reshard_pending"]
            probe = _maybe_wrap(ex, "driver_reshard",
                                reshard_commit_steps(pend["epoch"],
                                                     pend["survivors"]), d)
            try:
                res = yield from _drive_kernel(ex, probe, d)
            except _StoreDown:
                d["outage"] = True
                continue
            pend["missing"] = res["missing"]
            if res["committed"]:
                d["reshard_pending"] = None
        # Phase boundary: worker posts may land between the fetch and the
        # judgment of its snapshot — the tick-vs-posts race under test.
        yield ("pause", "judge")
        judgment = _maybe_wrap(
            ex, "driver_judgment",
            tick_judgment_steps(d["epoch"], fetched, ex.rank_to_host,
                                set(d["known"]), set(ex.slots),
                                d["lease_seen"], d["grace"],
                                scn.lease_timeout), d)
        j = ex._drive_judgment(judgment, d)
        if j is None:
            return  # violation recorded mid-judgment
        if j.get("advanced"):
            d["epoch"] += 1
            table = {}
            for ident in sorted(ex.slots):
                rank, host = ex.slots[ident]
                table[ident] = {"rank": rank, "epoch": d["epoch"],
                                "hostname": host}
            plan = None
            if scn.reshard:
                # The REAL plan kernel judges the publish about to go
                # out — marker stamped into the same atomic transaction,
                # fallback (no marker) while a previous reshard is still
                # uncommitted, exactly as ``_rendezvous_epoch`` does.
                plan = reshard_plan(
                    table, set(d["known"]), enabled=True,
                    pending=d.get("reshard_pending"),
                    recent_joiners=d.get("last_joiners") or ())
                if ex.mutation is not None \
                        and ex.mutation.role == "driver_plan":
                    plan = ex.mutation.wrap(plan, d)
                if plan["fallback"]:
                    d["reshard_pending"] = None
                if plan["eligible"]:
                    for slot in table.values():
                        slot["reshard"] = True
                        slot["sync_root"] = plan["sync_root"]
                        slot["joiners"] = plan["joiners"]
                        slot["survivors"] = plan["survivors"]
            ops: List[tuple] = [("set", DRIVER_SCOPE, "epoch",
                                 str(d["epoch"]).encode())]
            ops.extend(("set", RANK_AND_SIZE_SCOPE, ident,
                        json.dumps(table[ident]).encode())
                       for ident in sorted(table))
            if scn.reshard and plan["eligible"]:
                # Armed BEFORE the publish, exactly as production: a
                # store crash mid-service may land the marked table in
                # the journal while losing only the ack, and an armed
                # pending is safe either way — no marker on the wire
                # means no survivor ack, so the commit never fires and
                # the next advance falls back.
                d["reshard_pending"] = {
                    "epoch": d["epoch"],
                    "survivors": plan["survivors"],
                    "missing": list(plan["survivors"]),
                }
                d["last_joiners"] = set(plan["joiners"])
            elif scn.reshard:
                d["last_joiners"] = set()
            try:
                yield ("send", tuple(ops), "advance_publish")
            except _StoreDown:
                d["outage"] = True
            else:
                if scn.reshard:
                    # Mirror the spawn loop: every ranked identity has a
                    # live process after a successful publish, and a
                    # spawned identity's earlier exit no longer counts.
                    spawned = set(ex.slots) - d["known"]
                    d["succeeded"] -= spawned
                    ex.exited_ok -= spawned
                    d["known"] = set(ex.slots)


def _driver_proc(ex: "ProtoExecution"):
    yield from _driver_ticks(ex, ex.drv)


def _driver_recovery_proc(ex: "ProtoExecution"):
    """A restarted driver: the production ``recover_steps`` kernel
    against the journal-backed store, then the remaining ticks."""
    d = ex.drv
    d["outage"] = False
    while True:
        while True:
            try:
                rec = yield from _drive_kernel(
                    ex, _maybe_wrap(ex, "driver_recovery",
                                    recover_steps(ex.scenario.lease_timeout),
                                    d), d)
                break
            except _StoreDown:
                continue  # store died mid-recovery: retry, as production
        if rec is None:
            d["epoch"] = ex.scenario.epoch0
            d["known"] = set(ex.slots)
            d["lease_seen"] = {}
            recovered_epoch = None
        else:
            served = ex.recover_epoch_served
            truth = None if served is None else int(bytes(served).decode())
            if truth is None or rec["epoch"] != truth:
                ex._fail(V_RECOVER_MISMATCH,
                         f"restarted driver adopted epoch {rec['epoch']}, "
                         f"but the journal-backed store served {truth}")
                return
            d["epoch"] = rec["epoch"]
            d["known"] = set(rec["adopted"])
            d["lease_seen"] = {ident: (bytes(lease), ex.now)
                               for ident, (_slot, lease)
                               in sorted(rec["adopted"].items())}
            recovered_epoch = rec["epoch"]
        ex.last_recovery_at = ex.now
        if not ex.scenario.reshard:
            break
        # A reshard pending at crash time lived only in driver memory:
        # the restarted driver knows nothing of it, and its initial
        # republish (``start`` → ``_rendezvous_epoch(initial=True)``,
        # never marker-eligible) overwrites the marked table with an
        # unmarked one at the adopted epoch — driver crash mid-reshard
        # degrades to the legacy path by construction.  The republish is
        # CAS-fenced on the adopted epoch: the dead incarnation's
        # in-flight publish may land AFTER our recovery read, and an
        # unfenced republish would regress the durable epoch.  A lost
        # fence means re-adopt and retry — exactly ``start()``'s loop.
        d["reshard_pending"] = None
        d["last_joiners"] = set()
        expected = None if recovered_epoch is None \
            else str(recovered_epoch).encode()
        ops: List[tuple] = [
            ("check", DRIVER_SCOPE, "epoch", expected),
            ("set", DRIVER_SCOPE, "epoch", str(d["epoch"]).encode())]
        ops.extend(("set", RANK_AND_SIZE_SCOPE, ident,
                    json.dumps({"rank": ex.slots[ident][0],
                                "epoch": d["epoch"],
                                "hostname": ex.slots[ident][1]}).encode())
                   for ident in sorted(ex.slots))
        try:
            res = yield ("send", tuple(ops), "recover_publish")
        except _StoreDown:
            d["outage"] = True
            break
        if res and res[0] is False:
            continue  # fence lost: the epoch moved under us; re-adopt
        break
    yield from _driver_ticks(ex, d)


def _worker_proc(ex: "ProtoExecution", spec: dict):
    """One worker: lease renewals and reset requests, built by the SAME
    payload builders production posts through (rendezvous_client.py /
    core/state.py's pusher), sent best-effort like production."""
    renewals = 0
    for item in spec["script"]:
        if item[0] == "renew":
            renewals += 1
            ops = lease_renew_ops(spec["identity"], spec["rank"],
                                  spec["epoch"], renewals, b"{}")
            tag = "lease_renew"
        elif item[0] == "reset":
            ops = [("set", RESET_REQUEST_SCOPE, spec["identity"],
                    reset_request_payload(item[1], item[2]))]
            tag = "reset_request"
        elif item[0] == "ack":
            # Epoch-adoption ack, the exact write a survivor's
            # ``refresh_topology_from_rendezvous`` makes after ADOPTING
            # a published epoch — never before.  The one-shot poll
            # models the refresh's blocking read of the slot table: a
            # survivor only acks an epoch it has OBSERVED published.
            # Acking unconditionally would be a fidelity bug — it lets
            # the model commit a reshard whose marked publish never
            # landed, a schedule no real worker can produce.
            try:
                res = yield ("send",
                             (("get", RANK_AND_SIZE_SCOPE,
                               spec["identity"]),), "epoch_poll")
            except _StoreDown:
                continue
            raw = res[0] if res else None
            if raw is None:
                continue
            try:
                observed = json.loads(bytes(raw).decode()).get("epoch", -1)
            except (ValueError, TypeError):
                continue
            if observed < item[1]:
                continue  # publish not visible yet: no adoption, no ack
            ops = [("set", EPOCH_ACK_SCOPE, spec["identity"],
                    str(observed).encode())]
            tag = "epoch_ack"
        elif item[0] == "exit0":
            # The process ends with code 0 and the launcher's monitor
            # tells the driver (``record_worker_exit``): no wire, one
            # step of its own.
            yield ("pause", "exit")
            ex.exited_ok.add(spec["identity"])
            ex.drv["succeeded"].add(spec["identity"])
            ex.drv["success"] = True
            continue
        else:
            raise AssertionError(f"unknown worker script item {item!r}")
        try:
            yield ("send", tuple(ops), tag)
        except _StoreDown:
            continue  # best-effort, exactly like the production posters


def _coordinator_proc(ex: "ProtoExecution", spec: dict):
    """The coordinator's straggler plane: the REAL DemotionPolicy judges
    each scripted EWMA snapshot; a verdict posts through the production
    payload builder.  posted_unix is 0.0 — evidence only, and the model
    must stay wall-clock free."""
    policy = DemotionPolicy(spec["demote_secs"], spec["demote_cycles"])
    for obs in spec["observations"]:
        yield ("pause", "observe")
        victim = policy.observe(spec["epoch"], dict(obs),
                                set(spec["active"]))
        if victim is None:
            continue
        payload = demotion_report_payload(
            spec["epoch"], victim, ex.rank_to_host.get(victim),
            dict(obs).get(victim, 0.0), spec["demote_secs"],
            spec["demote_cycles"], 0.0)
        try:
            yield ("send", (("set", DEMOTION_REPORT_SCOPE,
                             spec["identity"], payload),),
                   "demotion_report")
        except _StoreDown:
            continue


# -- the execution ------------------------------------------------------

class ProtoExecution:
    """One schedulable run of the model cluster.  Duck-types the shm
    :class:`~horovod_tpu.tools.mck.model.Execution` interface the
    explorer drives (``enabled_actions`` / ``touches`` / ``step`` /
    ``final_check`` / ``violation`` / ``steps``)."""

    #: Fallback footprint (everything conflicts); real actions report
    #: per-location footprints from :meth:`touches`.
    _TOUCH: FrozenSet[tuple] = frozenset({("w", "cluster")})

    def __init__(self, scenario, mutation=None, max_steps: int = 600):
        self.scenario = scenario
        self.mutation = mutation
        self.max_steps = max_steps
        self.steps = 0
        self.now = 0.0
        self.trace: List[str] = []
        self.violation: Optional[Violation] = None

        # store state.  The inbox is keyed (client, per-client seq):
        # delivery order is the POP's choice, so the key space — not
        # arrival order — is the canonical state, and two enqueues by
        # different clients genuinely commute (the independence the
        # sleep sets rely on).
        self.data: Dict[str, bytes] = {}
        self.journal: bytes = pack_frame(JOURNAL_MAGIC)
        self.inbox: Dict[Tuple[str, int], _Req] = {}
        self._send_seq: Dict[str, int] = {}
        self.store_cur: Optional[dict] = None
        self.acked_sets: List[Tuple[str, bytes, str]] = []
        self._fold_keys: Set[frozenset] = {frozenset()}
        self.true_tick_reply: Optional[Tuple[tuple, tuple]] = None
        self.recover_epoch_served: Optional[bytes] = None
        # Store-side reshard ledger (ground truth for the reshard
        # invariants, rebuilt from replayed durable state on a store
        # crash): marked-published epochs awaiting their commit record,
        # with the survivor set each one published, and epochs whose
        # commit landed.
        self.reshard_pending_store: Dict[int, FrozenSet[str]] = {}
        self.reshard_committed: Set[int] = set()

        # topology ground truth
        self.slots: Dict[str, Tuple[int, str]] = dict(scenario.slots)
        self.rank_to_host: Dict[int, str] = {
            rank: host for rank, host in self.slots.values()}
        self.hosts: FrozenSet[str] = frozenset(
            host for _rank, host in self.slots.values())
        self.blacklisted: Set[str] = set()
        self.drv_last_poll: FrozenSet[str] = self.hosts
        self.tick_poll_served: FrozenSet[str] = frozenset()

        # crash / clock budgets
        self.clock_idx = 0
        self.store_crashes_used = 0
        self.driver_crashes_used = 0
        self.last_recovery_at: Optional[float] = None

        # Durable seed state, committed through the REAL batch kernel so
        # the journal, the data map and the fold set all agree.  The
        # driver's own epoch is always seeded — a restarted driver must
        # find what a prior incarnation persisted.
        self._seed([("set", DRIVER_SCOPE, "epoch",
                     str(scenario.epoch0).encode())])
        for ops in scenario.seeds:
            self._seed(list(ops))

        # driver state (carried across driver restarts)
        self.drv: dict = {
            "epoch": scenario.epoch0, "tick": 0, "outage": False,
            "grace": 0.0, "known": set(self.slots), "lease_seen": {},
            "reshard_pending": None, "last_joiners": set(),
            "succeeded": set(), "success": False,
        }
        # Who exited 0 since its last spawn: the cluster's own record.
        self.exited_ok: Set[str] = set()

        self._exiting = {spec["name"] for spec in scenario.workers
                         if ("exit0",) in spec["script"]}
        self.procs: Dict[str, _Proc] = {"drv": _Proc(_driver_proc(self))}
        for spec in scenario.workers:
            self.procs[spec["name"]] = _Proc(_worker_proc(self, spec))
        if scenario.coordinator is not None:
            self.procs["coord"] = _Proc(
                _coordinator_proc(self, scenario.coordinator))
        assert "st" not in self.procs
        for name in list(self.procs):
            self._prime(name)

    # -- seeding -------------------------------------------------------

    def _seed(self, ops: List[tuple]) -> None:
        fold = _fold_ops(self.data, ops)
        self._fold_keys.add(frozenset(fold.items()))
        gen = batch_steps(list(ops))
        resp = None
        while True:
            try:
                step = gen.send(resp)
            except StopIteration:
                return
            resp = None
            kind = step[0]
            if kind == STEP_LOAD:
                resp = self.data.get(step[1])
            elif kind == STEP_KEYS:
                resp = sorted(k for k in self.data
                              if k.startswith(step[1]))
            elif kind == STEP_JOURNAL:
                if step[1]:
                    self.journal += pack_frame(encode_group(list(step[1])))
            elif kind == STEP_APPLY:
                if step[2] is None:
                    self.data.pop(step[1], None)
                else:
                    self.data[step[1]] = step[2]

    # -- scheduling interface (explorer-facing) ------------------------

    def enabled_actions(self) -> List[tuple]:
        if self.violation is not None or self.steps >= self.max_steps:
            return []
        if self.store_cur is not None:
            # Partial-order reduction: mid-transaction, the only action
            # that does not commute with the store's micro-steps is a
            # store crash (intra-transaction state is observable ONLY
            # through the reply, which the micro-steps themselves
            # deliver).  A process step, clock advance, or driver crash
            # scheduled mid-service reaches exactly the states it
            # reaches scheduled before the pop or after the reply, so
            # exploring it here would only duplicate schedules.
            acts = [("t",)]
            if self.store_crashes_used < self.scenario.store_crashes:
                acts.append(("c", "st"))
            return acts
        acts = []
        for name in sorted(self.procs):
            p = self.procs[name]
            if p.status == RUNNABLE or (p.status == WAITING
                                        and p.reply is not _PENDING):
                acts.append(("p", name))
        acts.extend(("s", key) for key in sorted(self.inbox))
        if self.clock_idx < len(self.scenario.clock_steps):
            acts.append(("k", self.clock_idx))
        if self.store_crashes_used < self.scenario.store_crashes:
            acts.append(("c", "st"))
        if self.driver_crashes_used < self.scenario.driver_crashes:
            acts.append(("c", "drv"))
        return acts

    def touches(self, action: tuple) -> FrozenSet[tuple]:
        """Per-action location footprint for sleep-set pruning.

        The locations are the model's real shared state, partitioned so
        that genuinely commuting pairs stay independent:

        - ``proc:<name>`` — a process's generator + reply slot.  Written
          by the process's own steps and by the store action that serves
          ITS request (reply delivery), so post-vs-consume races stay
          dependent while two different workers commute.
        - ``inbox:<name>`` — the client's key range of the keyed inbox.
          Written by the client's sends and by pops of its requests.  A
          store crash writes EVERY inbox range: crash-before-send and
          crash-after-send genuinely differ (the errored ack), even for
          a client with nothing queued yet.
        - ``store`` — data map, journal, acked ledger.  All pops,
          micro-steps and store crashes; never processes (a process sees
          store state only through a served reply, which the ``proc:``
          location already orders).
        - ``clock`` — written by clock advances, read only by driver
          steps (lease scan, expiry, re-grace stamps).  Workers and the
          coordinator never look at the clock, so they commute with it.

        - ``exits`` — who exited 0 since its last spawn.  Written by a
          worker's exit and by the driver (a spawn forgets an exit; the
          head of a tick reads it), in scenarios whose workers exit.

        Over-approximation stays sound; the risk is UNDER-approximation,
        which tests/test_mck_proto.py guards by diffing a sleep-set run
        against a ``--no-sleep-sets`` run on a full scenario.
        """
        kind = action[0]
        if kind == "p":
            name = action[1]
            touch = {("w", f"proc:{name}"), ("w", f"inbox:{name}")}
            if name == "drv":
                touch.add(("r", "clock"))
            if self._exiting and name in self._exiting | {"drv"}:
                touch.add(("w", "exits"))
            return frozenset(touch)
        if kind == "s":
            req = self.inbox[action[1]]
            return frozenset({("w", "store"),
                              ("w", f"inbox:{req.client}"),
                              ("w", f"proc:{req.client}")})
        if kind == "t":
            client = self.store_cur["req"].client
            return frozenset({("w", "store"), ("w", f"proc:{client}")})
        if kind == "k":
            return frozenset({("w", "clock")})
        if kind == "c" and action[1] == "st":
            touch = {("w", "store")}
            for name in self.procs:
                touch.add(("w", f"inbox:{name}"))
            doomed = list(self.inbox.values())
            if self.store_cur is not None:
                doomed.append(self.store_cur["req"])
            for req in doomed:
                touch.add(("w", f"proc:{req.client}"))
            return frozenset(touch)
        if kind == "c" and action[1] == "drv":
            return frozenset({("w", "proc:drv"), ("w", "inbox:drv")})
        return self._TOUCH

    def step(self, action: tuple) -> None:
        self.steps += 1
        kind = action[0]
        if kind == "p":
            self.trace.append(f"p:{action[1]}")
            self._proc_step(action[1])
        elif kind == "s":
            key = action[1]
            self.trace.append(
                f"s:{key[0]}#{key[1]}[{self.inbox[key].tag}]")
            self._pop_request(key)
        elif kind == "t":
            self.trace.append("t:store")
            self._store_step()
        elif kind == "k":
            delta = self.scenario.clock_steps[action[1]]
            self.trace.append(f"k:+{delta:g}")
            self.clock_idx += 1
            self.now += delta
        elif kind == "c" and action[1] == "st":
            self.trace.append("c:store-crash")
            self._crash_store()
        elif kind == "c" and action[1] == "drv":
            self.trace.append("c:driver-crash")
            self._crash_driver()
        else:
            self._fail(V_MODEL_ERROR, f"unknown action {action!r}")

    def final_check(self) -> Optional[Violation]:
        if self.violation is not None:
            return self.violation
        v = self._torn_sweep() or self._acked_check()
        if v is not None:
            return v
        for name in sorted(self.procs):
            p = self.procs[name]
            if p.status != FINISHED:
                return Violation(
                    V_MODEL_ERROR,
                    f"process {name} never finished (status {p.status}; "
                    f"steps={self.steps}/{self.max_steps}) — either a "
                    "dropped reply or a too-small --max-steps budget",
                    list(self.trace))
        return None

    # -- processes -----------------------------------------------------

    def _prime(self, name: str) -> None:
        p = self.procs[name]
        try:
            item = next(p.gen)
        except StopIteration:
            p.status = FINISHED
            return
        self._dispatch_yield(name, p, item)

    def _proc_step(self, name: str) -> None:
        p = self.procs[name]
        try:
            if p.status == WAITING:
                reply = p.reply
                p.reply = _PENDING
                p.status = RUNNABLE
                if reply is _ERROR:
                    item = p.gen.throw(_StoreDown())
                else:
                    item = p.gen.send(reply)
            else:
                item = p.gen.send(None)
        except StopIteration:
            p.status = FINISHED
            return
        except _StoreDown:
            p.status = FINISHED
            self._fail(V_MODEL_ERROR,
                       f"process {name}: unhandled store outage")
            return
        self._dispatch_yield(name, p, item)

    def _dispatch_yield(self, name: str, p: _Proc, item: tuple) -> None:
        if item[0] == "send":
            seq = self._send_seq.get(name, 0)
            self._send_seq[name] = seq + 1
            self.inbox[(name, seq)] = _Req(name, tuple(item[1]), item[2],
                                           p.token)
            p.status = WAITING
            p.reply = _PENDING
        elif item[0] == "pause":
            pass  # a pure scheduling point
        else:
            self._fail(V_MODEL_ERROR,
                       f"process {name}: unknown yield {item!r}")

    # -- store ---------------------------------------------------------

    def _pop_request(self, key: Tuple[str, int]) -> None:
        req = self.inbox.pop(key)
        # The expected post-state of THIS transaction, from the ops
        # themselves: the torn sweep's ground truth.  At pop time the
        # store is idle, so self.data is exactly the journal state.
        fold = _fold_ops(self.data, req.ops)
        self._fold_keys.add(frozenset(fold.items()))
        gen = batch_steps(list(req.ops))
        if self.mutation is not None and self.mutation.role == "store":
            gen = self.mutation.wrap(gen, None)
        self.store_cur = {"req": req, "gen": gen, "resp": None}
        if self.store_crashes_used >= self.scenario.store_crashes:
            # No crash can land mid-service anymore, so the micro-step
            # boundaries are indistinguishable to every other unit:
            # serve the whole transaction atomically (same reduction as
            # enabled_actions' mid-transaction restriction).
            while self.store_cur is not None and self.violation is None:
                self._store_step()

    def _store_step(self) -> None:
        cur = self.store_cur
        try:
            step = cur["gen"].send(cur["resp"])
        except StopIteration:
            self.store_cur = None
            return
        cur["resp"] = None
        kind = step[0]
        if kind == STEP_LOAD:
            cur["resp"] = self.data.get(step[1])
        elif kind == STEP_KEYS:
            cur["resp"] = sorted(k for k in self.data
                                 if k.startswith(step[1]))
        elif kind == STEP_JOURNAL:
            if step[1]:
                self.journal += pack_frame(encode_group(list(step[1])))
        elif kind == STEP_APPLY:
            self._store_apply(step[1], step[2], cur["req"])
        elif kind == STEP_NOTIFY:
            pass
        elif kind == STEP_REPLY:
            self._serve_reply(cur["req"], step[1])
        else:
            self._fail(V_MODEL_ERROR, f"unknown store step {step!r}")

    def _store_apply(self, flat: str, value: Optional[bytes],
                     req: _Req) -> None:
        if value is None:
            self.data.pop(flat, None)
            return
        if flat == _EPOCH_KEY and _EPOCH_KEY in self.data:
            old = int(bytes(self.data[_EPOCH_KEY]).decode())
            new = int(bytes(value).decode())
            if new < old:
                self._fail(V_EPOCH_REGRESSION,
                           f"driver epoch regressed {old} -> {new} "
                           f"(txn {req.tag!r} from {req.client})")
        if flat.startswith(f"{DEMOTION_REPORT_SCOPE}/") \
                and self.scenario.active_np <= 2:
            self._fail(V_SMALL_WORLD_DEMOTION,
                       f"demotion report landed at np="
                       f"{self.scenario.active_np} (<= 2): the whole-"
                       "world-slow guard should make this structurally "
                       "impossible")
        if flat.startswith(f"{RANK_AND_SIZE_SCOPE}/"):
            self._apply_slot_doc(flat, value, req)
        if flat == _RESHARD_COMMIT_KEY:
            self._apply_reshard_commit(value, req)
        self.data[flat] = value

    def _apply_slot_doc(self, flat: str, value: bytes, req: _Req) -> None:
        """Reshard ledger + fallback invariant on every published slot
        entry.  A MARKED entry landing at epoch E while an older marked
        epoch never committed is the load-bearing fallback deleted: the
        workers of the failed reshard (some possibly holding blank,
        never-synced state) would be strung along as survivors instead
        of degraded to the legacy full-sync path.  An UNMARKED entry at
        epoch >= E *is* that fallback and retires E."""
        try:
            doc = json.loads(bytes(value).decode())
        except (ValueError, TypeError):
            return
        if not isinstance(doc, dict) or not isinstance(doc.get("epoch"),
                                                       int):
            return
        ep = doc["epoch"]
        if doc.get("reshard"):
            stale = sorted(e for e in self.reshard_pending_store if e < ep)
            if stale:
                self._fail(
                    V_RESHARD_FALLBACK_MISSED,
                    f"reshard-marked slot table published at epoch {ep} "
                    f"(txn {req.tag!r}) while the epoch-{stale[0]} "
                    "reshard never committed: the fallback to the "
                    "legacy full-teardown path was skipped")
            self.reshard_pending_store[ep] = frozenset(
                doc.get("survivors") or ())
        else:
            for e in [e for e in self.reshard_pending_store if e <= ep]:
                del self.reshard_pending_store[e]

    def _apply_reshard_commit(self, value: bytes, req: _Req) -> None:
        """Early-commit invariant, judged on the STORE's own data: when
        the commit record for epoch E lands, every survivor the marked
        publish listed must already have an epoch ack >= E on record —
        the driver-side guard a mutant deletes cannot bend this."""
        try:
            ep = int(bytes(value).decode())
        except ValueError:
            self._fail(V_MODEL_ERROR,
                       f"unparseable reshard commit record {value!r}")
            return
        survivors = self.reshard_pending_store.get(ep)
        if survivors is None:
            if ep not in self.reshard_committed:
                self._fail(
                    V_RESHARD_EARLY_COMMIT,
                    f"reshard commit record for epoch {ep} (txn "
                    f"{req.tag!r}) with no marked publish pending at "
                    "that epoch")
            return
        unacked = []
        for ident in sorted(survivors):
            raw = self.data.get(f"{EPOCH_ACK_SCOPE}/{ident}")
            try:
                acked = int(bytes(raw).decode()) if raw is not None else -1
            except ValueError:
                acked = -1
            if acked < ep:
                unacked.append(ident)
        if unacked:
            self._fail(
                V_RESHARD_EARLY_COMMIT,
                f"reshard commit for epoch {ep} landed with survivor(s) "
                f"{unacked} never having acked it: an in-place "
                "re-rendezvous was declared done over workers that may "
                "still be running the old topology")
            return
        del self.reshard_pending_store[ep]
        self.reshard_committed.add(ep)

    def _serve_reply(self, req: _Req, results: tuple) -> None:
        # A batch aborted by a failed CAS ``check`` journals and applies
        # NOTHING — its sets were never promised, so recording them as
        # acked would manufacture a false V_ACKED_LOST.
        aborted = any(op[0] == "check" and idx < len(results)
                      and results[idx] is False
                      for idx, op in enumerate(req.ops))
        if not aborted:
            for op in req.ops:
                if op[0] == "set":
                    self.acked_sets.append(
                        (f"{op[1]}/{op[2]}", op[3], req.tag))
        p = self.procs.get(req.client)
        current = p is not None and p.token == req.token
        if current and req.client == "drv":
            # The store's ground truth of what the driver was told —
            # captured on the SERVER side, out of reach of driver-side
            # mutants that rewrite what the kernel returns.
            if req.tag == "tick_reads":
                self.true_tick_reply = (tuple(req.ops), tuple(results))
            elif req.tag == "recover_epoch":
                self.recover_epoch_served = results[0]
        if current and p.status == WAITING:
            p.reply = list(results)

    # -- crashes and recovery ------------------------------------------

    def _crash_store(self) -> None:
        self.store_crashes_used += 1
        v = self._torn_sweep() or self._acked_check()
        if v is not None and self.violation is None:
            self.violation = v
        doomed = list(self.inbox.values())
        self.inbox = {}
        if self.store_cur is not None:
            doomed.append(self.store_cur["req"])
            self.store_cur = None
        for req in doomed:
            p = self.procs.get(req.client)
            if p is not None and p.token == req.token \
                    and p.status == WAITING:
                p.reply = _ERROR
        # Restart: state is whatever the journal's valid prefix replays.
        self.data = _replay(self.journal)
        self._rebuild_reshard_ledger()

    def _rebuild_reshard_ledger(self) -> None:
        """Re-derive the reshard ledger from replayed durable state: a
        marked epoch is pending iff its marked entries are still the
        latest for some identity (an unmarked/later publish overwrote
        them — the retirement the incremental path applies) and its
        commit record is absent."""
        pending: Dict[int, FrozenSet[str]] = {}
        for flat, value in self.data.items():
            if not flat.startswith(f"{RANK_AND_SIZE_SCOPE}/"):
                continue
            try:
                doc = json.loads(bytes(value).decode())
            except (ValueError, TypeError):
                continue
            if isinstance(doc, dict) and doc.get("reshard") \
                    and isinstance(doc.get("epoch"), int):
                pending[doc["epoch"]] = frozenset(
                    doc.get("survivors") or ())
        committed = set(self.reshard_committed)
        raw = self.data.get(_RESHARD_COMMIT_KEY)
        if raw is not None:
            try:
                committed.add(int(bytes(raw).decode()))
            except ValueError:
                pass
        for ep in committed:
            pending.pop(ep, None)
        self.reshard_pending_store = pending
        self.reshard_committed = committed

    def _crash_driver(self) -> None:
        self.driver_crashes_used += 1
        old = self.procs["drv"]
        self.procs["drv"] = _Proc(_driver_recovery_proc(self),
                                  token=old.token + 1)
        self._prime("drv")

    # -- judgment side effects (the driver's world) --------------------

    def _drive_judgment(self, kernel, d: dict) -> Optional[dict]:
        """Execute one judgment generator to completion.  Runs inside a
        single process step: the judgment is driver-local compute — its
        store reads already happened in the fetch — so there is no wire
        yield to interleave at (crashing the driver mid-judgment is
        indistinguishable from crashing before it)."""
        advances = 0
        resp = None
        while True:
            try:
                step = kernel.send(resp)
            except StopIteration as fin:
                return fin.value
            resp = None
            kind = step[0]
            if kind == STEP_CLOCK:
                resp = self.now
            elif kind == STEP_BLACKLIST:
                self.blacklisted.add(step[1])
            elif kind == STEP_POLL_HOSTS:
                resp = self._poll_hosts()
            elif kind == STEP_GATE:
                resp = step[1] == "success" and d["success"]
            elif kind == STEP_EXPIRE:
                self._apply_expire(step[1], d)
            elif kind == STEP_ADVANCE:
                advances += 1
                if advances > 1:
                    self._fail(V_MULTI_ADVANCE,
                               "two STEP_ADVANCE in one judged tick")
                    return None
                self._check_advance(step[1], d)
            else:
                self._fail(V_MODEL_ERROR,
                           f"unknown judgment step {step!r}")
                return None

    def _poll_hosts(self) -> Tuple[bool, bool]:
        available = self.hosts - frozenset(self.blacklisted)
        changed = available != self.drv_last_poll
        removal = bool(self.drv_last_poll - available)
        self.tick_poll_served = available
        self.drv_last_poll = available
        return changed, removal

    def _apply_expire(self, identity: str, d: dict) -> None:
        d["known"].discard(identity)
        d["lease_seen"].pop(identity, None)
        if self.last_recovery_at is not None and \
                self.now < self.last_recovery_at + \
                self.scenario.lease_timeout:
            self._fail(
                V_LIVE_DROPPED,
                f"identity {identity} expired at t={self.now:g}, inside "
                f"the post-outage re-grace window (recovered at "
                f"t={self.last_recovery_at:g}, timeout "
                f"{self.scenario.lease_timeout:g}): a worker that could "
                "not renew through the outage was shed as dead")

    def _check_advance(self, cause: str, d: dict) -> None:
        """Advance legitimacy against the STORE's ground truth: the ops
        and results it actually served the driver's current-incarnation
        tick fetch.  A driver-side mutant can rewrite what the kernel
        returns, never what the server served."""
        ops, results = self.true_tick_reply or ((), ())

        def current_reports(scope: str) -> List[dict]:
            # d["epoch"] is still the JUDGED epoch here: the driver
            # increments only after the judgment generator returns.
            docs = []
            for op, raw in zip(ops, results):
                if op[0] != "get" or op[1] != scope or raw is None:
                    continue
                try:
                    doc = json.loads(bytes(raw).decode())
                except (ValueError, TypeError):
                    continue
                if isinstance(doc, dict) and doc.get("epoch", -1) \
                        == d["epoch"]:
                    docs.append(doc)
            return docs

        if cause == "reset_request":
            if not current_reports(RESET_REQUEST_SCOPE):
                self._fail(
                    V_STALE_ACTED,
                    "epoch advanced for a reset request, but the store "
                    f"served no epoch-{d['epoch']} reset in this tick's "
                    "fetch — a stale request was acted on")
        elif cause == "demotion":
            reps = current_reports(DEMOTION_REPORT_SCOPE)
            if not reps:
                self._fail(
                    V_STALE_ACTED,
                    "epoch advanced for a demotion, but the store "
                    f"served no epoch-{d['epoch']} report in this "
                    "tick's fetch — a stale report was acted on")
                return
            shed = set()
            for rep in reps:
                host = self.rank_to_host.get(rep.get("rank")) \
                    or rep.get("hostname")
                if isinstance(host, str) and host:
                    shed.add(host)
            kept = shed & self.tick_poll_served
            if kept:
                self._fail(
                    V_DEMOTED_HOST_KEPT,
                    f"demotion advance with host(s) {sorted(kept)} still "
                    "in the discovery poll this tick served — the "
                    "blacklist must land strictly before the poll")

    # -- durability invariants -----------------------------------------

    def _torn_sweep(self) -> Optional[Violation]:
        """Every frame-boundary prefix of the journal must replay to a
        transaction-boundary state.  Byte-level crash points collapse to
        frame boundaries under the longest-valid-prefix rule, so this
        sweep covers a crash at EVERY journal byte."""
        state: Dict[str, bytes] = {}
        first = True
        frame_no = 0
        for _end, payload in iter_frames(self.journal):
            if first:
                first = False
                continue  # the magic frame
            frame_no += 1
            if payload and payload[0] == OP_GROUP:
                records = decode_group(payload)
            else:
                records = [decode_op(payload)]
            for op, key, value in records:
                if op == OP_SET:
                    state[key] = value
                elif op == OP_DELETE:
                    state.pop(key, None)
            if frozenset(state.items()) not in self._fold_keys:
                return Violation(
                    V_TORN_GROUP,
                    f"journal prefix ending at frame {frame_no} replays "
                    "to a state that is no transaction boundary: a crash "
                    "there recovers half a batched transaction",
                    list(self.trace))
        return None

    def _acked_check(self) -> Optional[Violation]:
        """Every SET the store ACKED must be in the journal: the reply
        is the durability promise (WAL ordering — group record strictly
        before the first apply, reply strictly after)."""
        present = {(key, bytes(value))
                   for op, key, value in _journal_records(self.journal)
                   if op == OP_SET}
        for flat, value, tag in self.acked_sets:
            if (flat, bytes(value)) not in present:
                return Violation(
                    V_ACKED_LOST,
                    f"acked set of {flat!r} (txn {tag!r}) is not in the "
                    "journal: a crash after the ack loses an "
                    "acknowledged write",
                    list(self.trace))
        return None

    # -- plumbing ------------------------------------------------------

    def _fail(self, name: str, detail: str) -> None:
        if self.violation is None:
            self.violation = Violation(name, detail, list(self.trace))


def proto_execution_factory(scenario, model, mutation=None,
                            max_steps: int = 600):
    """``execution_factory`` for :func:`explore.check`; ``model`` is the
    mode label ("proto") and carries no semantics here.  Scenarios with
    ``kind == "fanin"`` route to the negotiation fan-in degrade model
    (fanin_model.py), which shares this mode's action vocabulary and
    therefore its ``proto_unit`` pricing."""
    if getattr(scenario, "kind", "proto") == "fanin":
        from .fanin_model import FaninExecution

        return FaninExecution(scenario, mutation=mutation,
                              max_steps=max_steps)
    return ProtoExecution(scenario, mutation=mutation, max_steps=max_steps)
