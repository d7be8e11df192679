"""How long an idle background loop parks (ISSUE 54): 1 ms after the first
idle round that follows work, twice the last park after every further one,
up to ``HOROVOD_CYCLE_TIME`` (5 ms as in the reference, or what the
autotuner hands out); a round with work starts it over, an enqueue ends any
park at once.  The real ``_background_loop`` and ``_run_loop_once`` over a
scripted controller with no transport, whose clock is the parks the loop
chose (how many rounds a stretch holds is said there, exactly), and two real
ranks for what only an exchange shows: that every idle round of a real job
takes its park by that rule.  Counts and orders only: a bound on a clock
here says that a wake-up came, never how fast anything is."""

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
    maps a round's index to the cycle time the autotuner hands out in it.
    ``began_ms`` is the script's clock: when each round began if a round
    took no time and every park ran out, whatever the host was doing."""

    fanin_heartbeat = None

    def __init__(self, state, rounds, tuned=None):
        self.state, self.rounds, self.tuned = state, rounds, tuned or {}
        self.seen = 0
        self.began_ms = []
        self.round_began = threading.Event()
        self._request_if_busy(0)

    def _kind(self, i):
        return "i" if self.rounds is None else self.rounds[i:i + 1]

    def _request_if_busy(self, i):
        if self._kind(i) == "w":
            self.state.tensor_queue.push_messages([SimpleNamespace()])

    def compute_response_list(self, requests, shutdown):
        i, self.seen = self.seen, self.seen + 1
        self.began_ms.append(self.began_ms[-1] + self.state._idle_park_ms
                             if self.began_ms else 0.0)
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
    state.script.rounds = "i" * state.script.seen + "wi"
    t0 = time.monotonic()
    # What TensorQueue.add does once the entry is in the table: the round
    # that takes the request starts long before the cap's time is out.
    state.tensor_queue.push_messages([SimpleNamespace()])
    assert state.script.round_began.wait(SOON_S)
    assert time.monotonic() - t0 < SOON_S
    state.background.join(SOON_S)
    # ... and the idle round after it parks the floor, not the cap again.
    assert state.shutdown_complete.is_set() and state._idle_park_ms == FLOOR


def _rounds_in_200_ms(loop, cycle_ms, rounds):
    state = loop("i" * rounds, cycle_ms)
    state.background.join(SOON_S)
    assert state.script.seen == rounds + 1
    return sum(ms < 200.0 for ms in state.script.began_ms)


def _an_idle_stretch_makes_a_fifth_of_the_flat_parks_rounds(loop):
    # Rounds at 0, 1, 3 and 7 ms and every 5 ms from there, against the
    # old flat park's one a millisecond (the cap at the floor): a fifth is
    # the limit the doubling nears from above as the stretch grows.
    assert _rounds_in_200_ms(loop, CAP, 45) == 42
    assert _rounds_in_200_ms(loop, FLOOR, 205) == 200


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
    "an_idle_stretch_makes_a_fifth_of_the_flat_parks_rounds":
        _an_idle_stretch_makes_a_fifth_of_the_flat_parks_rounds,
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
# allreduces one after another.  Every idle round's park is written down
# beside the round's number, whether or not the loop had time left to wait
# it out.
TWO_RANKS = """
import json, time
from horovod_tpu.core.state import global_state

st = global_state()
chosen = []                     # [round, the park it chose], idle rounds
next_park = st._next_idle_park_ms

def written_down():
    park = next_park()
    chosen.append([st.cycle_count, park])
    return park

st._next_idle_park_ms = written_down
x = np.ones((), np.float32)
report = {"default_cap": st.cycle_time_ms}

def idle_stretch(cap_ms):
    hvd.barrier()
    st.cycle_time_ms = cap_ms
    hvd.allreduce(x, op=hvd.Sum, name="park.sync")
    t0, first = time.monotonic(), st.cycle_count
    time.sleep(0.2)
    last, ms = st.cycle_count, 1e3 * (time.monotonic() - t0)
    hvd.barrier()
    return first, last, ms

report["backoff"] = idle_stretch(report["default_cap"])
report["flat_1ms"] = idle_stretch(1.0)
st.cycle_time_ms = report["default_cap"]
hvd.barrier()
time.sleep(0.05)                    # both loops parked at the cap
first = st.cycle_count
for i in range(100):
    hvd.allreduce(x, op=hvd.Sum, name="park.scalar")
report["scalars"] = first, st.cycle_count
hvd.barrier()                       # the last park is written down
report["chosen"] = chosen
print("REPORT " + json.dumps(report), flush=True)
"""


@pytest.fixture(scope="module")
def two_ranks():
    out = run_distributed(2, TWO_RANKS, timeout=240)
    return [json.loads([x for x in o.splitlines()
                        if x.startswith("REPORT ")][-1][len("REPORT "):])
            for o in out]


def _idle_rounds(report, stretch, cap):
    """The parks a rank chose in the rounds of a stretch, each one of the
    ladder's (1, 2, 4 ms and the cap) and held to the rule by the round
    before it: twice the last park after an idle round, the floor after a
    round with work, never above the cap."""
    first, last = report[stretch][:2]
    idle = [(n, park) for n, park in report["chosen"] if first < n <= last]
    ladder = {min(cap, FLOOR * 2 ** k) for k in range(4)}
    assert {park for _, park in idle} <= ladder, (stretch, idle)
    for (before, last_park), (n, park) in zip(idle, idle[1:]):
        start = 2.0 * last_park if n == before + 1 else FLOOR
        assert park == min(cap, max(FLOOR, start)), (stretch, idle)
    return [park for _, park in idle]


@pytest.mark.timeout(400)
@pytest.mark.parametrize("rank", [0, 1])
def test_an_idle_stretch_makes_a_fraction_of_the_flat_parks_rounds(
        two_ranks, rank):
    r = two_ranks[rank]
    assert r["default_cap"] == CAP
    # Nobody enqueues: every round of the two stretches is an idle one, and
    # its park the ladder's or the flat 1 ms.
    for stretch, cap in (("backoff", CAP), ("flat_1ms", FLOOR)):
        first, last, ms = r[stretch]
        assert len(_idle_rounds(r, stretch, cap)) == last - first, r[stretch]
        assert ms >= 200, r[stretch]
    # 200 ms at the cap are 40 rounds and the three of the way up, and a
    # sleep that ran long had that much longer; a loaded host makes fewer,
    # never more.  How many the flat park makes of the same stretch is on
    # the script's clock (CASES): two stretches of a host's wall time do
    # not compare.
    first, last, ms = r["backoff"]
    assert last - first <= 55 * ms / 200, r["backoff"]


@pytest.mark.timeout(400)
@pytest.mark.parametrize("rank", [0, 1])
def test_back_to_back_scalar_allreduces_keep_the_floor(two_ranks, rank):
    # Work in every round or the next: the first idle round after it parks
    # the floor (the enqueue that ends the park is on the script's clock,
    # CASES).  Further idle rounds in a row are a caller this host kept off
    # the CPU: each doubles, and only the fourth in a row is at the cap.
    parks = _idle_rounds(two_ranks[rank], "scalars", CAP)
    assert parks, "a synchronous caller leaves its loop a round to park in"
    assert sum(p >= CAP for p in parks) <= 2, parks
