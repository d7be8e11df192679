"""``hvd-mck proto`` fan-in mode — crash/reorder checking of the
negotiation fan-in degrade protocol (core/negotiation_fanin.py).

One host's negotiation tree under the same bounded-exhaustive engine as
the epoch protocol: two members and their aggregator announce the
scenario's cache-bit masks every cycle (the model's workers hold what
they are ready for and say all of it each round; a real worker says a
bit once and the coordinator keeps it pending), the aggregator folds
them through the REAL production ``fold_host`` kernel into one bundle
(a ``HostMaskFrame`` for each distinct mask), and the coordinator
ingests bundles/direct frames, ANDs them into the agreed mask, and fans
replies back (bundle replies relay through the aggregator).  The
explorer crashes the aggregator at every step (free,
like proto crashes) and advances a model clock that stales the
aggregator's heartbeat, driving the degrade path at every possible
point of the cycle.

Checked invariants (the ISSUE's "no bit lost / double-counted"):

- **fanin-bit-lost**: at every completed round the agreed mask must
  contain every bit that ALL covered ranks announced — a bit the whole
  host was ready for must never be silenced by the fold or the degrade.
- **fanin-bit-double**: the agreed mask must never contain a bit some
  covered rank did NOT announce (the coordinator would fire a
  collective on a rank that never declared readiness), and no rank may
  be covered by two frames in one round.
- **fanin-rank-silenced**: every live rank finishes all its cycles —
  degrade-to-direct must leave no member stuck behind a dead or wedged
  aggregator.

Degrade model: members check the heartbeat before acting; staleness
(the clock advanced since the aggregator's last relay, or the dead
aggregator can never touch it again) convicts — a coordinated abort
discards the torn round, vetoes the host, and every survivor re-enters
DIRECT.  Statelessness is what makes this safe and is exactly what the
checker leans on: the aggregator holds nothing between rounds, so the
retry round re-delivers everything the aborted round consumed.  A
send to an already-dead aggregator (``PeerGoneError`` in production →
abort → reshard → re-tree) collapses to the same veto-direct outcome
here: the respawned re-treed epoch is bit-equivalent to a fresh model
run, so re-exploring it would add schedules but no new states.

The kill-suite mutant (``fanin_bits_dropped``, proto_mutations.py)
wraps the aggregator's fold stream and zeroes one member's mask on
forward while keeping its rank covered — the classic
missing-treated-as-ready-for-nothing fold bug — and must die by
``fanin-bit-lost``.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, List, Optional, Tuple

from ...core.messages import HostMaskFrame, MaskFrame, is_host_mask_frame, \
    is_mask_frame
from ...core.negotiation_fanin import fold_host
from .model import Violation

__all__ = [
    "FANIN_DEGRADE",
    "FaninExecution",
    "FaninScenario",
    "V_FANIN_BIT_DOUBLE",
    "V_FANIN_BIT_LOST",
    "V_FANIN_SILENCED",
    "fanin_bits_dropped_wrap",
]

V_FANIN_BIT_LOST = "fanin-bit-lost"
V_FANIN_BIT_DOUBLE = "fanin-bit-double"
V_FANIN_SILENCED = "fanin-rank-silenced"
V_FANIN_MODEL_ERROR = "model-error"  # shared name with proto_model


class FaninScenario:
    """A fan-in degrade scenario — duck-types the attribute surface the
    proto CLI listing and the explorer read (``name`` / ``description``
    / ``preemptions`` / ``ticks`` / ``slots`` / ``store_crashes`` /
    ``driver_crashes``), plus the fan-in specifics.  ``kind`` routes
    :func:`proto_model.proto_execution_factory` here."""

    kind = "fanin"

    __slots__ = ("name", "description", "preemptions", "ticks", "slots",
                 "masks", "clock_steps", "agg_crashes", "store_crashes",
                 "driver_crashes")

    def __init__(self, name: str, description: str, *, preemptions: int,
                 ticks: int, slots: Dict[str, Tuple[int, str]],
                 masks: Dict[str, int], clock_steps: Tuple[float, ...] = (),
                 agg_crashes: int = 0):
        self.name = name
        self.description = description
        self.preemptions = preemptions
        self.ticks = ticks                # negotiation cycles per worker
        self.slots = dict(slots)          # name -> (rank, host)
        self.masks = dict(masks)          # name -> announced mask int
        self.clock_steps = tuple(clock_steps)
        self.agg_crashes = agg_crashes
        self.store_crashes = 0            # proto-listing compatibility
        self.driver_crashes = 0


class FaninExecution:
    """One schedule of the fan-in protocol — duck-types the explorer's
    :class:`Execution` contract (``enabled_actions`` / ``touches`` /
    ``step`` / ``final_check`` / ``violation`` / ``steps``); actions use
    the proto vocabulary so ``proto_unit`` prices them (process steps
    cost preemptions, clock advances and crashes are free environment
    moves)."""

    _TOUCH = frozenset({("w", "fanin")})

    def __init__(self, scenario: FaninScenario, mutation=None,
                 max_steps: int = 600):
        self.scenario = scenario
        self.mutation = mutation
        self.max_steps = max_steps
        self.steps = 0
        self.violation: Optional[Violation] = None
        self.trace: List[str] = []

        # name -> per-worker state; "agg" is the aggregator, the rest
        # are its colocated members.  mode "tree" flips to "direct" for
        # everyone at once on the veto (a vetoed HOST runs direct).
        self.workers: Dict[str, dict] = {}
        for name, (rank, _host) in scenario.slots.items():
            self.workers[name] = {
                "rank": rank, "mask": scenario.masks[name],
                "state": "idle", "via": None, "cycles": 0,
            }
        self.rank_of = {n: w["rank"] for n, w in self.workers.items()}
        self.name_of = {r: n for n, r in self.rank_of.items()}
        self.mode = "tree"
        self.vetoed = False
        self.fallbacks = 0

        # aggregator internals
        self.agg_alive = True
        self.agg_crashes_used = 0
        self.agg_collected: Dict[str, Tuple[int, bytes]] = {}
        self.agg_forwarded = False
        self.relay_pending: Optional[Tuple[int, Tuple[int, ...]]] = None
        self.hb_at = 0.0

        # coordinator internals
        self.coord_inbox: List[Tuple[int, bytes]] = []
        self.coord_frames = 0  # wire frames behind coord_inbox's entries
        self.replies: Dict[str, int] = {}
        self.completions: List[dict] = []

        # model clock
        self.now = 0.0
        self.clock_idx = 0

    # -- protocol predicates -------------------------------------------

    def _payload(self, name: str) -> bytes:
        return MaskFrame(
            mask=self.workers[name]["mask"].to_bytes(2, "little"),
            shutdown=False).to_bytes()

    def _finished(self, name: str) -> bool:
        return self.workers[name]["cycles"] >= self.scenario.ticks

    def _stale(self) -> bool:
        """Member-side heartbeat verdict.  The clock advancing past the
        aggregator's last relay touch convicts (the 1.5-period window
        collapsed to one model tick); a dead aggregator with the clock
        budget spent convicts too — in production its silence WILL
        outlive any finite window, and without this escape the model
        would deadlock on an artifact of the bounded clock."""
        if self.mode == "direct":
            return False
        if self.now > self.hb_at:
            return True
        return (not self.agg_alive
                and self.clock_idx >= len(self.scenario.clock_steps))

    def _accounted(self) -> Dict[int, int]:
        """rank -> number of inbox frames covering it this round."""
        counts: Dict[int, int] = {}
        for sender, payload in self.coord_inbox:
            if is_host_mask_frame(payload):
                for r in HostMaskFrame.from_bytes(payload).covered:
                    counts[r] = counts.get(r, 0) + 1
            else:
                counts[sender] = counts.get(sender, 0) + 1
        return counts

    def _round_ready(self) -> bool:
        """The coordinator's fixed recv set is satisfied: every live
        unfinished worker's frame landed (directly or via a bundle)."""
        if not self.coord_inbox:
            return False
        accounted = self._accounted()
        for name, w in self.workers.items():
            if self._finished(name):
                continue
            if name == "agg" and not self.agg_alive:
                continue  # a dead rank is excused, not silenced
            if w["rank"] not in accounted:
                return False
        return True

    # -- scheduling interface (explorer-facing) ------------------------

    def enabled_actions(self) -> List[tuple]:
        if self.violation is not None or self.steps >= self.max_steps:
            return []
        acts: List[tuple] = []
        for name in sorted(self.workers):
            if name == "agg":
                if self._agg_enabled():
                    acts.append(("p", name))
            elif self._member_enabled(name):
                acts.append(("p", name))
        if self._round_ready():
            acts.append(("p", "coord"))
        if self.clock_idx < len(self.scenario.clock_steps):
            acts.append(("k", self.clock_idx))
        if (self.agg_alive
                and self.agg_crashes_used < self.scenario.agg_crashes
                and not all(self._finished(n) for n in self.workers)):
            acts.append(("c", "agg"))
        return acts

    def _member_enabled(self, name: str) -> bool:
        w = self.workers[name]
        if self._finished(name):
            return False
        if w["state"] == "idle":
            return True
        # posted: runnable once the reply landed, or once the stale
        # heartbeat lets it convict its way out from behind the tree.
        return name in self.replies or (w["via"] == "agg" and self._stale())

    def _agg_enabled(self) -> bool:
        if not self.agg_alive or self._finished("agg"):
            return False
        w = self.workers["agg"]
        if self.mode == "direct":
            return w["state"] == "idle" or "agg" in self.replies
        if w["state"] == "idle":
            # fold-and-forward: blocks until every member of the FIXED
            # plan has pushed this round's frame (the plan never shrinks
            # mid-epoch — a member that convicts instead aborts everyone).
            members = [n for n in self.workers if n != "agg"
                       and not self._finished(n)]
            return bool(members) and all(n in self.agg_collected
                                         for n in members) \
                and not self.agg_forwarded
        return self.relay_pending is not None

    def touches(self, action: tuple) -> FrozenSet[tuple]:
        """Per-action location footprints for sleep-set pruning,
        computed at the CURRENT state (the ProtoExecution idiom):

        - ``proc:<name>`` — a worker's own state machine.  The abort
          path writes every proc, which is what keeps a conviction
          dependent on everything it resets.
        - ``collect:<name>`` / ``inbox:<name>`` / ``reply:<name>`` —
          the per-sender slices of the aggregator's collect set, the
          coordinator's inbox, and the reply fan-out, so two members
          pushing frames commute (the fold is an AND — order-free).
        - ``agg`` — aggregator liveness + forward/relay bookkeeping:
          crashes, tree-path member sends (they observe liveness), the
          fold, the relay, and the coordinator's reply routing.
        - ``clock`` / ``hb`` — staleness inputs: written by clock
          advances and the relay's heartbeat touch, read by every
          tree-path member action.

        Over-approximation stays sound; UNDER-approximation is guarded
        by tests/test_mck_proto.py's reduced-vs-unreduced diff on this
        scenario."""
        kind = action[0]
        if kind == "k":
            return frozenset({("w", "clock")})
        if kind == "c":
            touch = {("w", "agg"), ("w", "proc:agg"), ("w", "reply:agg")}
            for n in self.workers:
                touch.add(("w", f"collect:{n}"))
            return frozenset(touch)
        name = action[1]
        if name == "coord":
            touch = {("w", "proc:coord"), ("w", "agg")}
            for n in self.workers:
                touch.add(("w", f"inbox:{n}"))
                touch.add(("w", f"reply:{n}"))
            return frozenset(touch)
        w = self.workers[name]
        if name == "agg":
            if self.mode == "direct":
                if w["state"] == "idle":
                    return frozenset({("w", "proc:agg"),
                                      ("w", "inbox:agg")})
                return frozenset({("w", "proc:agg"), ("w", "reply:agg")})
            if w["state"] == "idle":
                touch = {("w", "proc:agg"), ("w", "agg"),
                         ("w", "inbox:agg")}
                for n in self.workers:
                    touch.add(("w", f"collect:{n}"))
                return frozenset(touch)
            touch = {("w", "proc:agg"), ("w", "agg"), ("w", "hb"),
                     ("r", "clock")}
            for n in self.workers:
                touch.add(("w", f"reply:{n}"))
            return frozenset(touch)
        # members
        if w["state"] == "idle" and self.mode == "direct":
            return frozenset({("w", f"proc:{name}"),
                              ("w", f"inbox:{name}")})
        if w["state"] == "posted" and name in self.replies:
            return frozenset({("w", f"proc:{name}"),
                              ("w", f"reply:{name}")})
        if w["state"] == "idle" and self.agg_alive and not self._stale():
            # tree-path push: observes liveness + heartbeat, lands in
            # the aggregator's collect slice
            return frozenset({("w", f"proc:{name}"),
                              ("w", f"collect:{name}"), ("r", "agg"),
                              ("r", "clock"), ("r", "hb")})
        # conviction / dead-aggregator send: the coordinated abort
        # resets everyone — it conflicts with the world.
        touch = {("w", "agg"), ("r", "clock"), ("r", "hb")}
        for n in self.workers:
            touch.add(("w", f"proc:{n}"))
            touch.add(("w", f"collect:{n}"))
            touch.add(("w", f"inbox:{n}"))
            touch.add(("w", f"reply:{n}"))
        return frozenset(touch)

    def step(self, action: tuple) -> None:
        self.steps += 1
        kind = action[0]
        if kind == "p" and action[1] == "coord":
            self.trace.append("p:coord")
            self._coord_step()
        elif kind == "p" and action[1] == "agg":
            self.trace.append("p:agg")
            self._agg_step()
        elif kind == "p":
            self.trace.append(f"p:{action[1]}")
            self._member_step(action[1])
        elif kind == "k":
            delta = self.scenario.clock_steps[action[1]]
            self.trace.append(f"k:+{delta:g}")
            self.clock_idx += 1
            self.now += delta
        elif kind == "c":
            self.trace.append("c:agg-crash")
            self.agg_crashes_used += 1
            self.agg_alive = False
            # frames it collected but never forwarded die with it, as
            # does an unrelayed reply — exactly the consumed-but-lost
            # window statelessness must heal.
            self.agg_collected = {}
            self.relay_pending = None
            self.replies.pop("agg", None)
        else:
            self._fail(V_FANIN_MODEL_ERROR, f"unknown action {action!r}")

    # -- member / aggregator / coordinator steps -----------------------

    def _member_step(self, name: str) -> None:
        w = self.workers[name]
        if w["state"] == "idle":
            if self.mode == "direct":
                self.coord_inbox.append((w["rank"], self._payload(name)))
                self.coord_frames += 1
                w["state"], w["via"] = "posted", "coord"
            elif self._stale():
                self._abort_and_veto(f"{name} convicted a stale heartbeat")
            elif not self.agg_alive:
                # PeerGoneError on the send: coordinated abort; the
                # production re-treed retry collapses to direct here
                # (see module docstring).
                self._abort_and_veto(f"{name} hit a dead aggregator")
            else:
                self.agg_collected[name] = (w["rank"], self._payload(name))
                w["state"], w["via"] = "posted", "agg"
            return
        if name in self.replies:
            self.replies.pop(name)
            w["state"], w["via"] = "idle", None
            w["cycles"] += 1
        elif w["via"] == "agg" and self._stale():
            self._abort_and_veto(
                f"{name} convicted a stale heartbeat waiting for the relay")
        else:
            self._fail(V_FANIN_MODEL_ERROR,
                       f"{name} stepped with nothing to do")

    def _agg_step(self) -> None:
        w = self.workers["agg"]
        if self.mode == "direct":
            if w["state"] == "idle":
                self.coord_inbox.append((w["rank"], self._payload("agg")))
                self.coord_frames += 1
                w["state"], w["via"] = "posted", "coord"
            else:
                self.replies.pop("agg")
                w["state"], w["via"] = "idle", None
                w["cycles"] += 1
            return
        if w["state"] == "idle":
            entries = [(w["rank"], self._payload("agg"))]
            entries += [self.agg_collected[n]
                        for n in sorted(self.agg_collected)]
            stream = iter(entries)
            if self.mutation is not None \
                    and self.mutation.role == "fanin_forward":
                stream = self.mutation.wrap(stream,
                                            {"agg_rank": w["rank"]})
            # the REAL production fold — the kernel under check
            self.coord_inbox.extend(fold_host(list(stream)))
            self.coord_frames += 1  # one bundle, whatever it holds
            self.agg_collected = {}
            self.agg_forwarded = True
            w["state"] = "posted"
            return
        # relay: fan the agreed mask down to every covered member,
        # consume the aggregator's own share, and touch the heartbeat —
        # a relay that completed IS the liveness signal.
        agreed, covered = self.relay_pending
        self.relay_pending = None
        for r in covered:
            name = self.name_of.get(r)
            if name is None or name == "agg":
                continue
            self.replies[name] = agreed
        w["state"], w["via"] = "idle", None
        w["cycles"] += 1
        self.agg_forwarded = False
        self.hb_at = self.now

    def _coord_step(self) -> None:
        inbox, self.coord_inbox = self.coord_inbox, []
        frames, self.coord_frames = self.coord_frames, 0
        agreed: Optional[int] = None
        counts: Dict[int, int] = {}
        bundle_covered: Tuple[int, ...] = ()
        for sender, payload in inbox:
            if is_host_mask_frame(payload):
                frame = HostMaskFrame.from_bytes(payload)
                for r in frame.covered:
                    counts[r] = counts.get(r, 0) + 1
                bundle_covered = tuple(sorted(
                    bundle_covered + tuple(frame.covered)))
                mask = frame.mask_int
            elif is_mask_frame(payload):
                counts[sender] = counts.get(sender, 0) + 1
                mask = MaskFrame.from_bytes(payload).mask_int
            else:
                self._fail(V_FANIN_MODEL_ERROR,
                           f"coordinator ingested a non-mask frame "
                           f"from rank {sender}")
                return
            agreed = mask if agreed is None else agreed & mask

        doubled = sorted(r for r, c in counts.items() if c > 1)
        if doubled:
            self._fail(V_FANIN_BIT_DOUBLE,
                       f"rank(s) {doubled} covered by more than one frame "
                       "in a single round — their bits were counted twice")
            return
        truth = None
        for r in counts:
            name = self.name_of.get(r)
            if name is None:
                self._fail(V_FANIN_BIT_DOUBLE,
                           f"round covered unknown rank {r} — bits were "
                           "invented for a rank that never announced")
                return
            m = self.workers[name]["mask"]
            truth = m if truth is None else truth & m
        if truth & ~agreed:
            self._fail(V_FANIN_BIT_LOST,
                       f"agreed mask {agreed:#06x} lost bit(s) "
                       f"{truth & ~agreed:#06x} that every covered rank "
                       "announced — a ready-everywhere tensor was silenced "
                       "by the fold")
            return
        if agreed & ~truth:
            self._fail(V_FANIN_BIT_DOUBLE,
                       f"agreed mask {agreed:#06x} carries bit(s) "
                       f"{agreed & ~truth:#06x} outside some covered "
                       "rank's announced set — a collective would fire on "
                       "a rank that never declared readiness")
            return
        self.completions.append({
            "round": len(self.completions), "agreed": agreed,
            "covered": tuple(sorted(counts)), "ingress_frames": frames,
        })
        for sender, payload in inbox:
            if is_host_mask_frame(payload):
                # the bundle reply rides back through the aggregator
                self.relay_pending = (agreed, bundle_covered)
            else:
                self.replies[self.name_of[sender]] = agreed

    # -- degrade -------------------------------------------------------

    def _abort_and_veto(self, why: str) -> None:
        """Coordinated abort + veto: the torn round is discarded on
        every path (inbox, collected frames, undelivered replies), the
        host is convicted, and every survivor re-enters DIRECT at its
        current cycle — where it re-announces its FULL mask, which is
        why nothing the dead round consumed is lost."""
        self.trace.append(f"abort:{why}")
        self.fallbacks += 1
        self.vetoed = True
        self.mode = "direct"
        self.coord_inbox = []
        self.coord_frames = 0
        self.agg_collected = {}
        self.agg_forwarded = False
        self.relay_pending = None
        self.replies = {}
        for w in self.workers.values():
            if w["cycles"] < self.scenario.ticks:
                w["state"], w["via"] = "idle", None

    # -- verdicts ------------------------------------------------------

    def final_check(self) -> Optional[Violation]:
        if self.violation is not None:
            return self.violation
        for name in sorted(self.workers):
            if name == "agg" and not self.agg_alive:
                continue
            if not self._finished(name):
                return Violation(
                    V_FANIN_SILENCED,
                    f"rank {self.rank_of[name]} ({name}) finished only "
                    f"{self.workers[name]['cycles']}/{self.scenario.ticks} "
                    f"cycles (steps={self.steps}/{self.max_steps}) — the "
                    "degrade path left it stuck behind the aggregator",
                    list(self.trace))
        if len(self.completions) < self.scenario.ticks:
            return Violation(
                V_FANIN_MODEL_ERROR,
                f"only {len(self.completions)} completed rounds for "
                f"{self.scenario.ticks} cycles", list(self.trace))
        return None

    def _fail(self, name: str, detail: str) -> None:
        if self.violation is None:
            self.violation = Violation(name, detail, list(self.trace))


def fanin_bits_dropped_wrap(gen, ctx):
    """The seeded fold bug: zero the FIRST member MaskFrame in the
    aggregator's forward stream while keeping its rank covered — the
    member's announced bits silently vanish from the AND, so the agreed
    mask loses bits the whole host was ready for (``fanin-bit-lost``)."""
    dropped = False
    for rank, payload in gen:
        if not dropped and rank != ctx["agg_rank"] and is_mask_frame(payload):
            frame = MaskFrame.from_bytes(payload)
            yield rank, MaskFrame(mask=b"", shutdown=frame.shutdown).to_bytes()
            dropped = True
        else:
            yield rank, payload


#: Distinct per-rank masks so any fold corruption is attributable: the
#: exact agreed mask of a clean round is 0b0010 (the only bit all three
#: ranks announce); dropping m4's bits zeroes it (bit-lost), dropping
#: m4's ENTRY would resurrect 0b0100 (bit-double).
FANIN_DEGRADE = FaninScenario(
    "fanin_degrade",
    "one host's negotiation tree (aggregator + 2 members) over 2 "
    "cycles with the aggregator crashed at any step and the heartbeat "
    "staled by a clock jump: every degrade interleaving must fall back "
    "to direct pushes with no mask bit lost or double-counted and no "
    "rank silenced",
    preemptions=3, ticks=2,
    slots={"agg": (3, "h001"), "m4": (4, "h001"), "m5": (5, "h001")},
    masks={"agg": 0b0111, "m4": 0b1011, "m5": 0b1110},
    clock_steps=(1.0,), agg_crashes=1)
