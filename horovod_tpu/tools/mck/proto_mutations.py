"""Seeded elastic-protocol bugs ``hvd-mck proto`` must kill.

Same contract as the shm kill suite (mutations.py): each mutation wraps
one REAL step generator — the store's batch kernel or one of the
driver's judgment kernels — and perturbs its op stream into a protocol
bug this control plane was specifically designed against.  The
exhaustive run must kill every one with a named violation and a
reproducing schedule; a surviving mutant means the bounds or the
invariants got too weak, and CI fails the build rather than shrink the
claim.

Wrappers take ``(gen, ctx)``: ``ctx`` is the driver's state dict for
driver-side roles (the stale-epoch mutant needs the current epoch to
forge with) and None for the store.
"""

from __future__ import annotations

import json
from typing import Dict

from ...elastic.driver import (
    STEP_BLACKLIST,
    STEP_FINISH,
    STEP_GRACE,
    STEP_POLL_HOSTS,
    STEP_TXN,
)
from ...transport.store import STEP_JOURNAL, STEP_REPLY
from .fanin_model import V_FANIN_BIT_LOST, fanin_bits_dropped_wrap
from .mutations import Mutation
from .proto_model import (
    V_ACKED_LOST,
    V_DEMOTED_HOST_KEPT,
    V_JOB_END,
    V_LIVE_DROPPED,
    V_RESHARD_EARLY_COMMIT,
    V_RESHARD_FALLBACK_MISSED,
    V_STALE_ACTED,
    V_TORN_GROUP,
)


def _apply_before_journal(gen, ctx):
    """Defer the group-journal append until after the reply: the classic
    WAL inversion.  A crash between the ack and the deferred append
    loses a write the client was promised."""
    held = None
    resp = None
    while True:
        try:
            step = gen.send(resp)
        except StopIteration as fin:
            if held is not None:
                yield held
            return fin.value
        if step[0] == STEP_JOURNAL:
            held = step
            resp = None
            continue
        resp = yield step
        if step[0] == STEP_REPLY and held is not None:
            yield held
            held = None


def _group_split(gen, ctx):
    """Journal a batched transaction as per-op records instead of one
    group frame: a crash between records recovers half the transaction
    — the atomicity the single-frame group encoding exists to buy."""
    resp = None
    while True:
        try:
            step = gen.send(resp)
        except StopIteration as fin:
            return fin.value
        if step[0] == STEP_JOURNAL and len(step[1]) > 1:
            for record in step[1]:
                yield (STEP_JOURNAL, (record,))
            resp = None
            continue
        resp = yield step


def _stale_epoch_check_removed(gen, ctx):
    """Erase the staleness filter by forging every fetched reset request
    and demotion report to carry the current epoch — equivalent to
    deleting the ``epoch == current`` checks from the parsers.  The
    store-side ground truth still holds the real (stale) stamps, so any
    advance these forged reports cause is caught."""
    resp = None
    while True:
        try:
            step = gen.send(resp)
        except StopIteration as fin:
            fetched = fin.value
            for scope in ("reset", "demotion"):
                rewritten = {}
                for ident, raw in (fetched.get(scope) or {}).items():
                    if raw is not None:
                        try:
                            doc = json.loads(bytes(raw).decode())
                            doc["epoch"] = ctx["epoch"]
                            raw = json.dumps(doc).encode()
                        except (ValueError, TypeError):
                            pass
                    rewritten[ident] = raw
                fetched[scope] = rewritten
            return fetched
        resp = yield step


def _blacklist_after_poll(gen, ctx):
    """Move the demotion blacklist AFTER the discovery poll: the shed
    host is still in the very host set the advance is judged on, so the
    new epoch re-rendezvouses with the straggler it just convicted."""
    held = []
    resp = None
    while True:
        try:
            step = gen.send(resp)
        except StopIteration as fin:
            return fin.value
        if step[0] == STEP_BLACKLIST:
            held.append(step)
            resp = None
            continue
        if step[0] == STEP_POLL_HOSTS:
            poll = yield step
            for blk in held:
                yield blk
            held = []
            resp = poll
            continue
        resp = yield step


def _reshard_commit_unguarded(gen, ctx):
    """Forge every fetched survivor epoch-ack to the pending epoch —
    equivalent to deleting the acked-at-epoch guard from
    ``reshard_commit_steps``.  The commit record lands the moment the
    probe runs; the store's ground-truth acks are still real, so the
    early commit is caught server-side."""
    resp = None
    while True:
        try:
            step = gen.send(resp)
        except StopIteration as fin:
            return fin.value
        resp = yield step
        if step[0] == STEP_TXN and step[2] == "reshard_acks":
            epoch = ctx["reshard_pending"]["epoch"]
            resp = [str(epoch).encode() for _ in resp]


def _reshard_fallback_dropped(plan, ctx):
    """Delete the legacy-fallback branch from the publish plan: the
    marker is kept even while a previous reshard sits uncommitted, so
    survivors of the failed reshard — possibly holding blank,
    never-synced state — are strung along instead of degraded to the
    full-teardown path.  NOTE: role ``driver_plan`` wraps the plan DICT
    (not a generator) — the model applies it to ``reshard_plan``'s
    return value at each publish."""
    if not plan["fallback"]:
        return plan
    out = dict(plan)
    out["fallback"] = False
    out["eligible"] = bool(out["survivors"])
    return out


def _regrace_dropped(gen, ctx):
    """Swallow the re-grace arm after a store outage: replayed leases
    read as last-renewed before the outage, so a live worker whose
    renewals could not get through is expired as dead the moment the
    store is back."""
    resp = None
    while True:
        try:
            step = gen.send(resp)
        except StopIteration as fin:
            return fin.value
        if step[0] == STEP_GRACE:
            resp = None
            continue
        resp = yield step


def _job_end_awaits_respawn(gen, ctx):
    """Swallow the job's end while any identity was respawned: the
    launcher's rule before the driver judged the end (every rank
    finished AND no process alive), under which the joiner of a world
    that has exited is waited for without end."""
    for step in gen:
        if step[0] == STEP_FINISH and ctx["epoch"] > 0:
            continue
        yield step


PROTO_MUTATIONS: Dict[str, Mutation] = {m.name: m for m in (
    Mutation(
        "apply_before_journal", role="store", scenario="txn_crash",
        expected=frozenset({V_ACKED_LOST}),
        description="group journal record deferred until after the "
                    "reply ack (WAL ordering inverted)",
        wrap=_apply_before_journal),
    Mutation(
        "group_split", role="store", scenario="txn_crash",
        expected=frozenset({V_TORN_GROUP}),
        description="batched transaction journaled as per-op records "
                    "instead of one atomic group frame",
        wrap=_group_split),
    Mutation(
        "stale_epoch_check_removed", role="driver_reads",
        scenario="stale_race",
        expected=frozenset({V_STALE_ACTED}),
        description="fetched reset/demotion reports forged to the "
                    "current epoch (staleness filter deleted)",
        wrap=_stale_epoch_check_removed),
    Mutation(
        "blacklist_after_poll", role="driver_judgment",
        scenario="np4_demotion",
        expected=frozenset({V_DEMOTED_HOST_KEPT}),
        description="demotion blacklist reordered to after the "
                    "discovery poll it must precede",
        wrap=_blacklist_after_poll),
    Mutation(
        "regrace_dropped", role="driver_recovery",
        scenario="outage_regrace",
        expected=frozenset({V_LIVE_DROPPED}),
        description="lease re-grace window dropped after store-outage "
                    "recovery",
        wrap=_regrace_dropped),
    Mutation(
        "reshard_commit_unguarded", role="driver_reshard",
        scenario="reshard_commit",
        expected=frozenset({V_RESHARD_EARLY_COMMIT}),
        description="survivor epoch-acks forged at the commit probe "
                    "(all-survivors-acked guard deleted)",
        wrap=_reshard_commit_unguarded),
    Mutation(
        "reshard_fallback_dropped", role="driver_plan",
        scenario="reshard_fallback",
        expected=frozenset({V_RESHARD_FALLBACK_MISSED}),
        description="reshard marker kept while a previous reshard is "
                    "still uncommitted (legacy-fallback branch deleted)",
        wrap=_reshard_fallback_dropped),
    Mutation(
        "job_end_awaits_respawn", role="driver_job_end",
        scenario="finished_beside_respawn",
        expected=frozenset({V_JOB_END}),
        description="the job's end withheld while a respawn is in "
                    "flight (every rank exited 0, the joiner waited for)",
        wrap=_job_end_awaits_respawn),
    Mutation(
        "fanin_bits_dropped", role="fanin_forward",
        scenario="fanin_degrade",
        expected=frozenset({V_FANIN_BIT_LOST}),
        description="aggregator zeroes one member's mask on forward "
                    "while still covering its rank (bits dropped from "
                    "the host fold)",
        wrap=fanin_bits_dropped_wrap),
)}
