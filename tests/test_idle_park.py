"""How long an idle background loop parks (ISSUE 54): 1 ms after the first
idle round that follows work, twice the last park after every further one,
up to ``HOROVOD_CYCLE_TIME`` (5 ms as in the reference, or what the
autotuner hands out); a round with work starts it over, an enqueue ends any
park at once.  The real ``_background_loop`` and ``_run_loop_once`` over a
scripted controller with no transport, and two real ranks for what only an
exchange shows.  Counts and orders only: a bound on a clock here says that
a wake-up came, never how fast anything is."""

import json
import threading
import time
from types import SimpleNamespace

import pytest

from horovod_tpu.common import env as env_mod
from horovod_tpu.core import metrics, timeline
from horovod_tpu.core.state import HorovodGlobalState
from horovod_tpu.common.topology import ProcessTopology

from .helpers import run_distributed

CAP = env_mod.DEFAULT_CYCLE_TIME_MS
FLOOR = env_mod.IDLE_PARK_FLOOR_MS
LONG_MS = 600_000.0         # a park no test waits out
SOON_S = 10.0               # "at once", on a loaded host


class _Wake(threading.Event):
    """The loop's wake event, with every park written down: the seconds it
    asked for beside the park in ms the loop had chosen."""

    def __init__(self, state):
        super().__init__()
        self.state = state
        self.parks = []
        self.parked = threading.Event()

    def wait(self, timeout=None):
        self.parks.append((timeout, self.state._idle_park_ms))
        self.parked.set()
        return super().wait(timeout)


class _Script:
    """Stands where the controller does.  A round a character: ``w`` has a
    request, ``i`` has none; after the last the loop is told to leave
    (``rounds=None``: idle rounds until somebody asks it to).  ``tuned``
    maps a round's index to the cycle time the autotuner hands out in it."""

    fanin_heartbeat = None

    def __init__(self, state, rounds, tuned=None):
        self.state, self.rounds, self.tuned = state, rounds, tuned or {}
        self.seen = 0
        self.round_began = threading.Event()
        self._request_if_busy(0)

    def _kind(self, i):
        return "i" if self.rounds is None else self.rounds[i:i + 1]

    def _request_if_busy(self, i):
        if self._kind(i) == "w":
            self.state.tensor_queue.push_messages([SimpleNamespace()])

    def compute_response_list(self, requests, shutdown):
        i, self.seen = self.seen, self.seen + 1
        self.round_began.set()
        assert bool(requests) == (self._kind(i) == "w"), (i, requests)
        self._request_if_busy(i + 1)
        tuned = (64 << 20, self.tuned[i]) if i in self.tuned else None
        return SimpleNamespace(responses=[], tuned_params=tuned,
                               shutdown=shutdown or self._kind(i) == "")


@pytest.fixture
def loop(monkeypatch):
    """``loop(rounds, cycle_ms)`` starts a state's background loop on a
    thread of its own and returns the state; ``state.wake`` has the parks,
    ``state.script`` the rounds, ``phase_stats`` is this test's alone.
    ``parked_ms`` is the park the loop is taken to have made last."""
    monkeypatch.setattr(timeline, "phase_stats", timeline.PhaseStats())
    started = []

    def start(rounds, cycle_ms=CAP, tuned=None, parked_ms=0.0):
        state = HorovodGlobalState()
        state.cycle_time_ms = cycle_ms
        state._idle_park_ms = parked_ms
        state._build_transport = lambda: None
        state._wake = state.wake = _Wake(state)
        state.tensor_queue.set_wake_event(state.wake)
        state.controller = state.script = _Script(state, rounds, tuned)
        state.background = threading.Thread(
            target=state._background_loop, daemon=True)
        state.background.start()
        started.append(state)
        return state

    yield start
    for state in started:
        state.shutdown_requested.set()
        state._wake.set()
        state.background.join(SOON_S)
        assert not state.background.is_alive()


def _park_ms(state):
    for asked, park_ms in state.wake.parks:
        # What the round itself took is taken off the park.
        assert 0 < asked <= park_ms / 1000.0, state.wake.parks
    return [park_ms for _, park_ms in state.wake.parks]


def _schedule(rounds, cycle_ms, expect, tuned=None):
    def case(loop):
        state = loop(rounds, cycle_ms, tuned)
        state.background.join(SOON_S)
        assert state.shutdown_complete.is_set()
        assert _park_ms(state) == expect
        assert state.script.seen == state.cycle_count == len(rounds) + 1
    return case


def _gauge_is_the_park_before_a_round_with_work(loop):
    seen = []
    set_gauge = metrics.set_gauge

    def record(name, value, **labels):
        if name == "controller_idle_park_ms":
            seen.append(value)
        set_gauge(name, value, **labels)

    metrics.set_gauge = record
    try:
        state = loop("wwiiiwiw", CAP)
        state.background.join(SOON_S)
    finally:
        metrics.set_gauge = set_gauge
    # A reading a round with work, none in an idle one: how far the
    # back-off had grown when the work arrived.
    assert seen == [0.0, 0.0, 4.0, 1.0]
    assert metrics.CATALOG["controller_idle_park_ms"][0] == "gauge"


def _an_add_ends_a_park_at_the_cap_at_once(loop):
    state = loop(None, LONG_MS, parked_ms=LONG_MS)
    assert state.wake.parked.wait(SOON_S)
    assert _park_ms(state) == [LONG_MS]
    state.script.round_began.clear()
    state.script.rounds = "i" * state.script.seen + "w"
    t0 = time.monotonic()
    # What TensorQueue.add does once the entry is in the table: the round
    # that takes the request starts long before the cap's time is out.
    state.tensor_queue.push_messages([SimpleNamespace()])
    assert state.script.round_began.wait(SOON_S)
    assert time.monotonic() - t0 < SOON_S
    state.background.join(SOON_S)
    assert state.shutdown_complete.is_set() and state._idle_park_ms == 0.0


def _shutdown_does_not_wait_out_a_park_at_the_cap(loop):
    state = loop(None, LONG_MS, parked_ms=LONG_MS)
    assert state.initialized.wait(SOON_S) and state.wake.parked.wait(SOON_S)
    t0 = time.monotonic()
    state.shutdown()
    assert time.monotonic() - t0 < SOON_S
    assert state.shutdown_complete.is_set()


CASES = {
    # The same rounds, fewer of them: 1, 2, 4, then the cap.
    "the_park_doubles_from_the_floor_to_the_cap": _schedule(
        "wiiiiii", CAP, [FLOOR, 2.0, 4.0, CAP, CAP, CAP]),
    "idle_from_the_start": _schedule("iiii", CAP, [FLOOR, 2.0, 4.0, CAP]),
    "a_round_with_work_sets_the_park_back_to_its_floor": _schedule(
        "wiiiiwiiwi", CAP, [FLOOR, 2.0, 4.0, CAP, FLOOR, 2.0, FLOOR]),
    "a_round_with_work_does_not_park": _schedule("wwww", CAP, []),
    "collectives_a_round_apart_never_park_above_the_floor": _schedule(
        "wiwiwiwiwi", CAP, [FLOOR] * 5),
    # HOROVOD_CYCLE_TIME, --cycle-time-ms and the autotuner's value mean
    # one thing, the longest an idle loop parks.
    "a_cycle_time_above_the_default_is_the_cap": _schedule(
        "wiiiiii", 12.0, [FLOOR, 2.0, 4.0, 8.0, 12.0, 12.0]),
    "a_cycle_time_under_the_floor_is_floor_and_cap": _schedule(
        "wiiiwi", 0.25, [0.25] * 4),
    "a_cycle_time_at_the_floor_is_the_old_flat_park": _schedule(
        "wiiiwi", 1.0, [1.0] * 4),
    "no_cycle_time_no_park": _schedule("wiiii", 0.0, []),
    "a_tuned_cycle_time_is_the_cap_from_then_on": _schedule(
        "wiiiiiwiii", CAP, [FLOOR, 2.0, 3.0, 3.0, 3.0, FLOOR, 2.0, 3.0],
        tuned={2: 3.0}),
    "a_tuned_cycle_time_above_the_default": _schedule(
        "wiiiiii", CAP, [FLOOR, 2.0, 4.0, CAP, 9.0, 9.0], tuned={5: 9.0}),
    "gauge_is_the_park_before_a_round_with_work":
        _gauge_is_the_park_before_a_round_with_work,
    "an_add_ends_a_park_at_the_cap_at_once":
        _an_add_ends_a_park_at_the_cap_at_once,
    "shutdown_does_not_wait_out_a_park_at_the_cap":
        _shutdown_does_not_wait_out_a_park_at_the_cap,
}


@pytest.mark.timeout(120)
@pytest.mark.parametrize("case", sorted(CASES))
def test_idle_park(loop, case):
    CASES[case](loop)


@pytest.mark.parametrize("knob, cap", [(None, CAP), ("8", 8.0), ("0.5", 0.5)])
def test_the_environments_cycle_time_is_the_cap(monkeypatch, knob, cap):
    """What ``initialize`` reads of ``HOROVOD_CYCLE_TIME`` is the longest an
    idle loop parks, above the floor and below it."""
    if knob is None:
        monkeypatch.delenv(env_mod.HOROVOD_CYCLE_TIME, raising=False)
    else:
        monkeypatch.setenv(env_mod.HOROVOD_CYCLE_TIME, knob)
    state = HorovodGlobalState()
    state.initialize(topology=ProcessTopology(
        rank=0, size=1, local_rank=0, local_size=1))
    state.shutdown()
    assert state.cycle_time_ms == cap
    parks, state._idle_park_ms = [], 0.0    # as after a round with work
    for _ in range(6):
        state._idle_park_ms = state._next_idle_park_ms()
        parks.append(state._idle_park_ms)
    assert parks[0] == min(FLOOR, cap) and parks[-1] == cap
    assert parks == sorted(parks) and max(parks) == cap


# Two ranks, one job: 200 ms in which nobody enqueues anything, at the
# default and at the old flat 1 ms (the cap at the floor); then scalar
# allreduces one after another with every park written down.
TWO_RANKS = """
import json, threading, time
from horovod_tpu.core.state import global_state

st = global_state()

class Wake(threading.Event):
    parks = None
    def wait(self, timeout=None):
        if self.parks is not None:
            self.parks.append(st._idle_park_ms)
        return super().wait(timeout)

wake = Wake()
st._wake = wake
st.tensor_queue.set_wake_event(wake)
x = np.ones((), np.float32)
report = {"default_cap": st.cycle_time_ms}

def idle_rounds(cap_ms):
    hvd.barrier()
    st.cycle_time_ms = cap_ms
    hvd.allreduce(x, op=hvd.Sum, name="park.sync")
    before = st.cycle_count
    time.sleep(0.2)
    rounds = st.cycle_count - before
    hvd.barrier()
    return rounds

report["rounds_backoff"] = idle_rounds(report["default_cap"])
report["rounds_flat_1ms"] = idle_rounds(1.0)
st.cycle_time_ms = report["default_cap"]
hvd.barrier()
time.sleep(0.05)                    # both loops parked at the cap
wake.parks = []
for i in range(100):
    hvd.allreduce(x, op=hvd.Sum, name="park.scalar")
report["parks"], wake.parks = wake.parks, None
print("REPORT " + json.dumps(report), flush=True)
"""


@pytest.fixture(scope="module")
def two_ranks():
    out = run_distributed(2, TWO_RANKS, timeout=240)
    return [json.loads([x for x in o.splitlines()
                        if x.startswith("REPORT ")][-1][len("REPORT "):])
            for o in out]


@pytest.mark.timeout(400)
@pytest.mark.parametrize("rank", [0, 1])
def test_an_idle_stretch_makes_a_fraction_of_the_flat_parks_rounds(
        two_ranks, rank):
    r = two_ranks[rank]
    assert r["default_cap"] == CAP
    # 200 ms at the cap are 40 rounds and the three of the way up; a loaded
    # host makes fewer, never more.  The flat 1 ms made a round a
    # millisecond: five times as many on a quiet host (a fifth is the
    # limit the doubling nears from above, 43 of 200), and still over
    # twice as many on one whose short waits run long.
    assert 10 <= r["rounds_backoff"] <= 55, r
    assert r["rounds_flat_1ms"] >= 2.5 * r["rounds_backoff"], r


@pytest.mark.timeout(400)
@pytest.mark.parametrize("rank", [0, 1])
def test_back_to_back_scalar_allreduces_keep_the_floor(two_ranks, rank):
    parks = two_ranks[rank]["parks"]
    # Work in every round or the next: the first idle round after it parks
    # the floor and the enqueue ends that park.  A second idle round in a
    # row is a caller this host kept off the CPU for a millisecond: rare,
    # and never the way up to the cap.
    assert parks, "a synchronous caller leaves its loop a round to park in"
    above = [p for p in parks if p > FLOOR]
    assert len(above) <= len(parks) / 5, parks
    assert sum(p >= CAP for p in parks) <= 2, parks
