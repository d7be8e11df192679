"""Chaos suite: deterministic fault injection against the failure plane.

Every subprocess test here asserts the NO-HANG property: with a fault spec
killing, hanging, or starving a rank, all surviving ranks either raise a
coordinated ``HorovodInternalError`` or complete an elastic recovery —
within a hard wall-clock bound (the ``timeout`` marker's SIGALRM watchdog
in conftest).  ``ci/chaos.sh`` runs this lane standalone, together with
``test_fault_injection_elastic.py`` (the launcher's end-to-end jobs).

Spec grammar and site list: ``docs/fault_injection.md`` /
``horovod_tpu/common/faults.py``.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from horovod_tpu.common import faults
from horovod_tpu.common.exceptions import FaultInjectedError

from .helpers import REPO_ROOT, run_distributed

pytestmark = pytest.mark.chaos

# Chaos workers run with a short recv progress deadline so hang-flavored
# faults convert to PeerGoneError within seconds, not the 600 s production
# default.  Transport pinned to tcp: these scenarios inject on the
# tcp.* sites, which the auto policy would route around on a single host
# (the shm twins live in test_shm_transport.py).
_FAST_DEADLINE = {"HOROVOD_TCP_PROGRESS_DEADLINE_SECS": "3",
                  "HOROVOD_TRANSPORT": "tcp"}


@pytest.fixture(autouse=True)
def _clean_faults():
    """Injection state must never leak between tests (or into the suite)."""
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# the injection registry itself (in-process)
# ---------------------------------------------------------------------------


class TestFaultSpec:
    def test_inactive_by_default(self):
        assert not faults.ACTIVE
        assert faults.inject("tcp.send", rank=0) is False

    def test_grammar_errors_are_loud(self):
        for bad in ["nosuch.site:action=raise",
                    "tcp.send:action=explode",
                    "tcp.send:frobnicate",
                    "tcp.send:nth=0:action=raise",
                    "tcp.send:nth=1:after=2:action=raise",
                    # payload actions are send-only: anywhere else they
                    # would silently inject nothing
                    "tcp.recv:action=drop",
                    "dispatch.collective:action=drop",
                    "tcp.recv:action=corrupt",
                    "rendezvous.get:action=truncate,3",
                    "ckpt.save:action=corrupt,2"]:
            with pytest.raises(ValueError):
                faults.configure(bad)

    def test_rank_and_peer_filters(self):
        faults.configure("tcp.send:rank=1:peer=2:action=drop")
        assert faults.inject("tcp.send", rank=0, peer=2) is False
        assert faults.inject("tcp.send", rank=1, peer=0) is False
        assert faults.inject("tcp.recv", rank=1, peer=2) is False
        assert faults.inject("tcp.send", rank=1, peer=2) is True

    def test_nth_fires_exactly_once_deterministically(self):
        for _ in range(2):  # same spec → same firing call, run after run
            faults.configure("tcp.send:nth=3:action=drop")
            fired = [faults.inject("tcp.send", rank=0) for _ in range(6)]
            assert fired == [False, False, True, False, False, False]

    def test_after_fires_on_every_later_call(self):
        faults.configure("tcp.send:after=2:action=drop")
        fired = [faults.inject("tcp.send", rank=0) for _ in range(5)]
        assert fired == [False, False, True, True, True]

    def test_counters_are_per_clause(self):
        faults.configure(
            "tcp.send:rank=0:nth=1:action=drop;tcp.send:rank=1:nth=2:action=drop")
        assert faults.inject("tcp.send", rank=0) is True
        assert faults.inject("tcp.send", rank=1) is False  # its own call #1
        assert faults.inject("tcp.send", rank=1) is True

    def test_raise_action(self):
        faults.configure("controller.negotiate:action=raise")
        with pytest.raises(FaultInjectedError, match="controller.negotiate"):
            faults.inject("controller.negotiate", rank=0)

    def test_raise_oserror_action(self):
        faults.configure("rendezvous.get:action=raise_oserror")
        with pytest.raises(OSError, match="injected connection reset"):
            faults.inject("rendezvous.get")

    def test_delay_action(self):
        faults.configure("dispatch.collective:action=delay_ms,150")
        t0 = time.monotonic()
        assert faults.inject("dispatch.collective", rank=0) is False
        assert time.monotonic() - t0 >= 0.14

    def test_hang_action_blocks(self):
        faults.configure("tcp.recv:action=hang")
        done = threading.Event()

        def call():
            faults.inject("tcp.recv", rank=0)
            done.set()  # unreachable

        threading.Thread(target=call, daemon=True).start()
        assert not done.wait(0.3), "hang action returned"

    def test_env_spec_parsed_in_fresh_process(self):
        """Workers self-configure from HOROVOD_FAULT_SPEC at import."""
        out = subprocess.run(
            [sys.executable, "-c",
             "from horovod_tpu.common import faults; print(faults.ACTIVE)"],
            env={**os.environ,
                 "HOROVOD_FAULT_SPEC": "tcp.send:nth=1:action=drop"},
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=60)
        assert out.stdout.strip() == "True", (out.stdout, out.stderr)

    def test_inject_deferred_returns_delay_without_sleeping(self):
        """The tally site's deferral contract: a delay_ms clause hands the
        delay back (in seconds) instead of sleeping, so the coordinator
        can park the tally rather than stall its whole lockstep cycle."""
        faults.configure("controller.tally:rank=1:action=delay_ms,150")
        t0 = time.monotonic()
        delay = faults.inject_deferred("controller.tally", rank=1)
        assert time.monotonic() - t0 < 0.1, "inject_deferred slept"
        assert delay == pytest.approx(0.150)

    def test_inject_deferred_rank_filter(self):
        faults.configure("controller.tally:rank=1:action=delay_ms,150")
        assert faults.inject_deferred("controller.tally", rank=0) == 0.0
        assert faults.inject_deferred("controller.tally", rank=2) == 0.0

    def test_inject_deferred_non_delay_actions_still_run(self):
        """Only delay_ms is deferred; raise keeps its normal semantics
        through the deferred entry point."""
        faults.configure("controller.tally:action=raise")
        with pytest.raises(faults.FaultInjectedError):
            faults.inject_deferred("controller.tally", rank=0)

    def test_inject_deferred_nth_fires_once(self):
        faults.configure("controller.tally:rank=1:nth=2:action=delay_ms,200")
        assert faults.inject_deferred("controller.tally", rank=1) == 0.0
        assert faults.inject_deferred("controller.tally", rank=1) \
            == pytest.approx(0.200)
        assert faults.inject_deferred("controller.tally", rank=1) == 0.0

    def test_inject_deferred_after_fires_every_call(self):
        faults.configure("controller.tally:rank=1:after=1:action=delay_ms,50")
        assert faults.inject_deferred("controller.tally", rank=1) == 0.0
        for _ in range(3):
            assert faults.inject_deferred("controller.tally", rank=1) \
                == pytest.approx(0.050)


# ---------------------------------------------------------------------------
# chaos: subprocess worker jobs under injected faults
# ---------------------------------------------------------------------------

_SURVIVOR_BODY = """
from horovod_tpu.common.exceptions import HorovodInternalError
try:
    for i in range(500):
        hvd.allreduce(np.ones(32, np.float32), name=f"t{i % 4}")
    print("NO_FAULT_SEEN", rank, flush=True)
except HorovodInternalError as e:
    print("SURVIVOR_ABORT", rank, str(e).replace("\\n", " "), flush=True)
"""


@pytest.mark.timeout(150)
def test_kill_rank_mid_allreduce_np4_coordinated_abort():
    """A rank hard-dying mid-collective (os._exit via the
    dispatch.collective site) must surface as a coordinated
    HorovodInternalError on EVERY survivor — not an eternal block in
    recv."""
    outs = run_distributed(
        4, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        # A death is seen at once, as the end of a stream: no deadline is
        # this test's subject, and _FAST_DEADLINE's 3 s of no progress is
        # what a rank starved by the other xdist workers' jobs shows too,
        # so that the victim heard a survivor's abort before its own
        # fault fired.
        extra_env={"HOROVOD_TRANSPORT": "tcp",
                   "HOROVOD_FAULT_SPEC":
                       "dispatch.collective:rank=2:nth=2:action=exit,9"})
    for r in (0, 1, 3):
        assert f"SURVIVOR_ABORT {r}" in outs[r], (r, outs[r])
    assert "SURVIVOR_ABORT 2" not in outs[2]  # the victim died, silently


@pytest.mark.timeout(150)
def test_hang_recv_np2_deadline_then_coordinated_abort():
    """A rank wedged inside recv (bounded-hang flavor of ``action=hang``,
    so the harness can also observe the VICTIM's recovery): the healthy
    rank's progress deadline trips, it broadcasts the abort, and when the
    victim unwedges it reads the abort frame instead of re-blocking."""
    outs = run_distributed(
        2, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        extra_env={**_FAST_DEADLINE,
                   "HOROVOD_FAULT_SPEC":
                       "tcp.recv:rank=1:nth=3:action=delay_ms,8000"})
    assert "SURVIVOR_ABORT 0" in outs[0], outs[0]
    assert "no recv progress" in outs[0], outs[0]
    assert "SURVIVOR_ABORT 1" in outs[1], outs[1]
    # The victim's exact error depends on whether rank 0's process is
    # still alive when it unwedges: it either reads the buffered abort
    # frame (coordinated abort) or fails fast on the torn socket
    # (PeerGoneError).  Both are clean errors; neither is a hang.
    assert "coordinated abort from rank 0" in outs[1] \
        or "peer rank 0 is gone" in outs[1], outs[1]


@pytest.mark.timeout(150)
def test_drop_negotiation_frame_np2_coordinated_abort():
    """A silently-lost control-plane frame must not strand the job: the
    coordinator sees no progress, marks the peer gone, aborts both
    sides."""
    outs = run_distributed(
        2, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        extra_env={**_FAST_DEADLINE,
                   "HOROVOD_FAULT_SPEC":
                       "tcp.send:rank=1:nth=5:action=drop"})
    assert "SURVIVOR_ABORT 0" in outs[0], outs[0]
    assert "SURVIVOR_ABORT 1" in outs[1], outs[1]


@pytest.mark.timeout(150)
def test_delayed_frames_complete_without_false_abort():
    """Slow-but-alive must NOT abort: per-frame delays well under the
    deadline reset the progress clock (any bytes count), and the job
    completes normally."""
    outs = run_distributed(
        2, """
for i in range(5):
    out = hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name=f"d{i}")
    assert np.allclose(np.asarray(out), 2.0), out
print("DELAY_OK", rank, flush=True)
""", timeout=120, retries=0,
        extra_env={**_FAST_DEADLINE,
                   "HOROVOD_FAULT_SPEC":
                       "tcp.send:rank=1:after=0:action=delay_ms,80"})
    for r in range(2):
        assert f"DELAY_OK {r}" in outs[r], outs[r]


@pytest.mark.timeout(150)
def test_stall_shutdown_np4_propagates_to_all_ranks():
    """The stall inspector's hard abort must reach the ranks that DID
    submit: the coordinator raises locally and the abort broadcast carries
    the stall text (tensor + missing ranks) to every survivor."""
    outs = run_distributed(
        4, """
import time
from horovod_tpu.common.exceptions import HorovodInternalError
if rank == 3:
    # Never submits, and outlives the stall deadline whatever the machine's
    # load: it stays until the abort has reached its own loop (a fixed 8 s
    # ran out first on a busy machine, and its exit was what the others
    # reported).
    from horovod_tpu.core.state import global_state
    until = time.monotonic() + 100
    while global_state().background.is_alive() and time.monotonic() < until:
        time.sleep(0.1)
else:
    try:
        hvd.allreduce(np.ones(4, np.float32), name="never")
        print("STALL_NOT_DETECTED", rank, flush=True)
    except HorovodInternalError as e:
        print("STALL_ABORT", rank, str(e).replace("\\n", " "), flush=True)
""", timeout=120, expect_failure=True, retries=0,
        # The stall inspector's clocks are the subject, not the
        # transport's 3 s of _FAST_DEADLINE, which a starved rank trips
        # first on a busy machine.
        extra_env={"HOROVOD_TRANSPORT": "tcp",
                   "HOROVOD_STALL_CHECK_TIME_SECONDS": "1",
                   "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "3"})
    for r in (0, 1, 2):
        assert f"STALL_ABORT {r}" in outs[r], (r, outs[r])
        assert "stall shutdown" in outs[r], (r, outs[r])
        assert "never" in outs[r], (r, outs[r])


@pytest.mark.timeout(150)
def test_rendezvous_failure_fails_init_fast():
    """A dying rendezvous store during bring-up must fail init promptly on
    every rank (HorovodInternalError out of hvd.init) — the no-hang bound
    is this test's own watchdog."""
    outs = run_distributed(
        2, "", timeout=90, expect_failure=True, retries=0,
        extra_env={"HOROVOD_FAULT_SPEC":
                       "rendezvous.get:action=raise_oserror",
                   "HOROVOD_MESH_STARTUP_TIMEOUT": "10"})
    for out in outs:
        assert "WORKER_OK" not in out  # init must have failed


@pytest.mark.timeout(150)
def test_corrupt_frame_np2_coordinated_abort():
    """A single in-flight byte flip must abort BOTH ranks with the wire-CRC
    diagnosis within one poll quantum — never desync into reading
    negotiation bytes as tensor data (the PR 2 failure this plane
    closes)."""
    outs = run_distributed(
        2, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        extra_env={**_FAST_DEADLINE,
                   "HOROVOD_FAULT_SPEC":
                       "tcp.send:rank=1:nth=6:action=corrupt,1"})
    # rank 0 detects (its recv fails CRC); rank 1 hears the abort naming
    # the CRC failure — or observes the torn socket, both clean errors
    assert "SURVIVOR_ABORT 0" in outs[0], outs[0]
    assert "wire CRC" in outs[0], outs[0]
    assert "SURVIVOR_ABORT 1" in outs[1], outs[1]


@pytest.mark.timeout(150)
def test_corrupt_abort_writes_flight_recorder_dump_on_every_rank(tmp_path):
    """The flight recorder's contract (docs/observability.md): an injected
    mid-train corruption abort leaves a parseable post-mortem JSON on
    EVERY rank — the detector (CRC failure) and the survivor (coordinated
    abort) alike — naming the reason and carrying the recent-event ring
    plus a metrics snapshot.  The injecting rank's ring must contain the
    fired fault itself (recorded before the action ran)."""
    import json

    outs = run_distributed(
        2, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        extra_env={**_FAST_DEADLINE,
                   "HOROVOD_FLIGHT_RECORDER_DIR": str(tmp_path),
                   "HOROVOD_FAULT_SPEC":
                       "tcp.send:rank=1:nth=6:action=corrupt,1"})
    for r in range(2):
        assert f"SURVIVOR_ABORT {r}" in outs[r], (r, outs[r])
        dump = tmp_path / "hvd_flight_recorder" \
            / f"hvd_flight_recorder.rank{r}.json"
        assert dump.exists(), (r, outs[r])
        doc = json.loads(dump.read_text())  # parseable on every rank
        assert doc["rank"] == r
        assert "background loop death" in doc["reason"], doc["reason"]
        assert doc["events"], "flight-recorder ring was empty"
        kinds = {e["kind"] for e in doc["events"]}
        assert "frame" in kinds, (r, doc["events"])
        assert doc["metrics"] and "counters" in doc["metrics"]
    # the detector's dump names the CRC failure; the injector's ring
    # recorded its own fired fault clause
    dump_dir = tmp_path / "hvd_flight_recorder"
    doc0 = json.loads((dump_dir / "hvd_flight_recorder.rank0.json")
                      .read_text())
    assert "wire CRC" in doc0["reason"] or "FrameCorrupt" in doc0["reason"]
    doc1 = json.loads((dump_dir / "hvd_flight_recorder.rank1.json")
                      .read_text())
    assert "fault" in {e["kind"] for e in doc1["events"]}, doc1["events"]


@pytest.mark.timeout(150)
def test_corrupt_compressed_frame_np2_coordinated_abort():
    """Compression must not open an integrity hole: a byte flip on a
    COMPRESSED (fp16-on-the-wire, digest-deferred) frame is caught by the
    step digest and aborts both ranks with the wire-CRC diagnosis."""
    outs = run_distributed(
        2, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        extra_env={**_FAST_DEADLINE,
                   "HOROVOD_WIRE_COMPRESSION": "fp16",
                   "HOROVOD_FAULT_SPEC":
                       "tcp.send:rank=1:nth=6:action=corrupt,1"})
    assert "SURVIVOR_ABORT 0" in outs[0], outs[0]
    assert "wire CRC" in outs[0], outs[0]
    assert "SURVIVOR_ABORT 1" in outs[1], outs[1]


@pytest.mark.timeout(150)
def test_truncate_compressed_frame_np2_coordinated_abort():
    """A truncated compressed frame misframes the stream; the size/parse
    layer (or the step digest, whichever meets it first) must convert it
    into a coordinated abort — never a hang or a struct.error."""
    outs = run_distributed(
        2, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        extra_env={**_FAST_DEADLINE,
                   "HOROVOD_WIRE_COMPRESSION": "fp16",
                   "HOROVOD_FAULT_SPEC":
                       "tcp.send:rank=1:nth=6:action=truncate,4"})
    for r in range(2):
        assert f"SURVIVOR_ABORT {r}" in outs[r], (r, outs[r])
        assert "struct.error" not in outs[r], (r, outs[r])


@pytest.mark.timeout(150)
def test_corrupt_int8_frame_np2_coordinated_abort():
    """The lossy codecs ride the same integrity plane: a byte flip on an
    int8-quantized (digest-deferred) byte blob is caught by the step
    digest and aborts both ranks with the wire-CRC diagnosis."""
    outs = run_distributed(
        2, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        extra_env={**_FAST_DEADLINE,
                   "HOROVOD_WIRE_COMPRESSION": "int8",
                   "HOROVOD_FAULT_SPEC":
                       "tcp.send:rank=1:nth=6:action=corrupt,1"})
    assert "SURVIVOR_ABORT 0" in outs[0], outs[0]
    assert "wire CRC" in outs[0], outs[0]
    assert "SURVIVOR_ABORT 1" in outs[1], outs[1]


@pytest.mark.timeout(150)
def test_truncate_topk_frame_np2_coordinated_abort():
    """A truncated variable-length topk frame misframes the stream; the
    exact-size contract (sizes derived from wire_nbytes on both ends, not
    from the bytes) converts it into a coordinated abort — never a hang
    or a struct.error."""
    outs = run_distributed(
        2, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        extra_env={**_FAST_DEADLINE,
                   "HOROVOD_WIRE_COMPRESSION": "topk10",
                   "HOROVOD_FAULT_SPEC":
                       "tcp.send:rank=1:nth=6:action=truncate,4"})
    for r in range(2):
        assert f"SURVIVOR_ABORT {r}" in outs[r], (r, outs[r])
        assert "struct.error" not in outs[r], (r, outs[r])


@pytest.mark.timeout(150)
def test_truncated_frame_np2_typed_abort():
    """A misframed (short) application frame passes the wire CRC by
    construction and must be caught by the defensive parse layer as a
    typed error — both ranks abort, nobody hangs or struct-errors."""
    outs = run_distributed(
        2, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        extra_env={**_FAST_DEADLINE,
                   "HOROVOD_FAULT_SPEC":
                       "tcp.send:rank=1:nth=6:action=truncate,4"})
    for r in range(2):
        assert f"SURVIVOR_ABORT {r}" in outs[r], (r, outs[r])
        assert "struct.error" not in outs[r], (r, outs[r])


# ---------------------------------------------------------------------------
# performance attribution plane (docs/observability.md): straggler
# detector + lifecycle trace + critical-path report, one np=3 run
# ---------------------------------------------------------------------------


_STRAGGLER_BODY = """
from horovod_tpu.core import flight_recorder, metrics

gauge_named_rank1 = 0
for i in range(24):
    # DISTINCT names every round: cache misses keep the negotiation on
    # the table path, so the coordinator emits NEGOTIATE spans with
    # per-rank readiness instants (critical_path's attribution input).
    hvd.allreduce(np.ones(4096, np.float32), name=f"cp{i}")
    if rank == 0 and metrics.registry.get_gauge("straggler_suspect") == 1:
        gauge_named_rank1 += 1
hvd.barrier()
if rank == 0:
    flags = metrics.registry.get_counter("straggler_flags_total", rank="1")
    assert flags >= 1, f"rank 1 never flagged (flags={flags})"
    for r in (0, 2):
        assert metrics.registry.get_counter(
            "straggler_flags_total", rank=str(r)) == 0, r
    assert gauge_named_rank1 > 0, "straggler_suspect gauge never hit 1"
    stragglers = [e for e in flight_recorder.recorder.events()
                  if e["kind"] == "straggler"]
    assert stragglers, "no straggler event in the coordinator's ring"
    assert all(e["rank"] == 1 for e in stragglers), stragglers
    path = flight_recorder.recorder.dump("straggler-proof")
    assert path, "flight-recorder dump failed"
    print("STRAGGLER_OK", flush=True)
"""


@pytest.mark.timeout(360)
def test_straggler_attribution_np3_all_surfaces_agree(tmp_path):
    """Headline acceptance: ONE np=3 run with an injected 60 ms delay on
    every rank-1 collective submission (the ``enqueue.collective`` site),
    run under lockdep, must make all three attribution surfaces agree:

    - the online detector flags rank 1 (``straggler_flags_total`` +
      ``straggler_suspect`` gauge observed naming rank 1, never 0 or 2),
    - the coordinator's flight-recorder dump carries ``straggler`` events
      for rank 1,
    - the merged 3-rank timeline's critical-path report attributes the
      inflated step time to rank 1's negotiation-wait phase."""
    from horovod_tpu.tools import critical_path, trace_merge

    tl = tmp_path / "tl.json"
    outs = run_distributed(
        3, _STRAGGLER_BODY, timeout=300,
        extra_env={
            "HOROVOD_FAULT_SPEC":
                "enqueue.collective:rank=1:action=delay_ms,60",
            "HOROVOD_STRAGGLER_THRESHOLD_SECS": "0.015",
            "HOROVOD_STRAGGLER_EWMA_ALPHA": "0.6",
            "HOROVOD_TIMELINE": str(tl),
            "HOROVOD_FLIGHT_RECORDER_DIR": str(tmp_path),
            "HOROVOD_LOCK_DEBUG": "1",
        })
    assert "STRAGGLER_OK" in outs[0], outs[0]

    # surface 2: the dump artifact (hvd_flight_recorder/ subdir) parses
    # and names rank 1
    dump = tmp_path / "hvd_flight_recorder" / "hvd_flight_recorder.rank0.json"
    assert dump.exists()
    doc = json.loads(dump.read_text())
    events = [e for e in doc["events"] if e["kind"] == "straggler"]
    assert events and all(e["rank"] == 1 for e in events), doc["events"]

    # surface 3: hvd-critical-path over the merged trace pins the
    # inflation on rank 1's negotiation wait
    traces = [trace_merge.load_trace(
        str(tl) if r == 0 else f"{tl}.rank{r}") for r in range(3)]
    report = critical_path.analyze(trace_merge.merge(traces))
    waits = {r: report["totals_us"].get(str(r), {})
             .get("negotiation_wait", 0.0) for r in range(3)}
    # 24 rounds x 60 ms injected: rank 1 owes most of a second of
    # negotiation wait; the healthy ranks only scheduling jitter.
    assert waits[1] > 500e3, waits
    assert waits[1] > 5 * max(waits[0], waits[2]), waits
    dominated = [s for s in report["steps"]
                 if s["dominant"]["rank"] == 1
                 and s["dominant"]["phase"] == "negotiation_wait"]
    assert dominated, report["steps"][:3]


_KILL_MID_SAVE_BODY = """
import horovod_tpu.frameworks.jax.checkpoint as ckpt
base = BASE_DIR + "/run"
for step in (1, 2, 3):
    ckpt.save_rotating(
        base, {"w": np.full(4, float(step), np.float32), "step": step},
        keep=5, step=step)
    print("SAVED", step, flush=True)
print("SURVIVED_ALL_SAVES", flush=True)
"""

_RESTORE_AFTER_KILL_BODY = """
import logging, sys
import horovod_tpu.frameworks.jax.checkpoint as ckpt
_log = logging.getLogger("horovod_tpu.frameworks.jax.checkpoint")
_log.addHandler(logging.StreamHandler(sys.stdout))
_log.setLevel(logging.INFO)
state = ckpt.restore_latest(
    BASE_DIR + "/run",
    like={"w": np.zeros(4, np.float32), "step": 0})
assert int(state["step"]) == 2, state
assert np.allclose(np.asarray(state["w"]), 2.0), state
print("RESTORED_PREVIOUS_VALID", rank, flush=True)
"""


@pytest.mark.timeout(150)
def test_kill_mid_ckpt_save_restore_latest_skips_half_written(tmp_path):
    """A rank hard-dying inside ``ckpt.save`` (between payload publish
    and manifest commit — the ``ckpt.save`` site's window) leaves a
    half-written newest snapshot; ``restore_latest`` must detect it,
    LOG the skip, and land on the last intact snapshot."""
    prelude = f"BASE_DIR = {str(tmp_path)!r}\n"
    outs = run_distributed(
        1, prelude + _KILL_MID_SAVE_BODY, timeout=120,
        expect_failure=True, retries=0,
        extra_env={"HOROVOD_FAULT_SPEC": "ckpt.save:nth=3:action=exit,9"})
    assert "SAVED 2" in outs[0], outs[0]
    assert "SURVIVED_ALL_SAVES" not in outs[0], outs[0]

    outs = run_distributed(1, prelude + _RESTORE_AFTER_KILL_BODY,
                           timeout=120, retries=0)
    assert "RESTORED_PREVIOUS_VALID 0" in outs[0], outs[0]
    assert "skipping snapshot" in outs[0], outs[0]
    assert "00000003" in outs[0], outs[0]   # names WHAT it skipped
    assert "no manifest" in outs[0], outs[0]  # ...and why
