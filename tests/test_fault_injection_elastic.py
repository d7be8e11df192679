"""Chaos suite, second half: the launcher's end-to-end jobs.

Every test here starts ``python -m horovod_tpu.runner.launch`` (elastic
driver, rendezvous server, real worker processes) under a fault spec and
asserts recovery: the job finishes, bit-identical to an undisturbed run
where the test says so.  The in-process registry tests and the
``run_distributed`` abort cases are in ``test_fault_injection.py``; the
two files are one lane (marker ``chaos``, ``ci/chaos.sh``) and two files
only so that xdist's ``--dist loadfile`` can run them side by side.
"""

import json
import os
import re
import socket
import subprocess
import sys
import threading
import time

import pytest

from .helpers import REPO_ROOT, release_reservations, reserve_port
from .test_fault_injection import _FAST_DEADLINE

pytestmark = pytest.mark.chaos

_ELASTIC_CHAOS_TRAIN = """
import os, time
import numpy as np
try:
    import jax
    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass
import horovod_tpu as hvd

hvd.init()
state = hvd.elastic.ObjectState(batch=0)

@hvd.elastic.run
def train(state):
    while state.batch < 25:
        hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="g")
        print(f"BATCH {state.batch} rank={hvd.rank()} size={hvd.size()}",
              flush=True)
        state.batch += 1
        state.commit()
        time.sleep(0.05)

train(state)
print("ELASTIC_DONE", hvd.rank(), flush=True)
hvd.shutdown()
"""


_ELASTIC_CORRUPTION_TRAIN = """
import numpy as np
try:
    import jax
    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass
import horovod_tpu as hvd

hvd.init()
state = hvd.elastic.ObjectState(batch=0, params=np.zeros(4, np.float32))

@hvd.elastic.run
def train(state):
    while state.batch < 15:
        grad = hvd.allreduce(
            np.full(4, float(state.batch + 1), np.float32),
            op=hvd.Sum, name="g")
        state.params = state.params + np.asarray(grad)
        state.batch += 1
        state.commit()

train(state)
print("FINAL_PARAMS r%d %s" % (
    hvd.rank(), np.asarray(state.params).tobytes().hex()), flush=True)
print("ELASTIC_DONE", hvd.rank(), flush=True)
hvd.shutdown()
"""


_ELASTIC_INT8_TRAIN = _ELASTIC_CORRUPTION_TRAIN.replace(
    "np.full(4, float(state.batch + 1), np.float32)",
    "np.full(4, 127.0 * float(state.batch + 1), np.float32)")


def _run_elastic_corruption_job(tmp_path, fault_spec, extra_env=None,
                                train_src=_ELASTIC_CORRUPTION_TRAIN):
    disc = tmp_path / "discover.sh"
    disc.write_text("#!/bin/sh\necho localhost:1\necho 127.0.0.1:1\n")
    disc.chmod(0o755)
    train = tmp_path / f"train_{'fault' if fault_spec else 'clean'}.py"
    train.write_text(train_src)

    env = os.environ.copy()
    env.update(_FAST_DEADLINE)
    env.update(extra_env or {})
    env["HOROVOD_LOG_LEVEL"] = "info"  # driver logs the reset trigger
    env.pop("HOROVOD_FAULT_SPEC", None)
    if fault_spec:
        env["HOROVOD_FAULT_SPEC"] = fault_spec
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "-np", "2", "--min-np", "2",
         "--host-discovery-script", str(disc),
         sys.executable, str(train)],
        cwd=REPO_ROOT, text=True, env=env,
        capture_output=True, timeout=60)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    params = dict(re.findall(r"FINAL_PARAMS r(\d+) ([0-9a-f]+)",
                             proc.stdout))
    assert set(params) == {"0", "1"}, proc.stdout[-2000:]
    assert params["0"] == params["1"], "ranks diverged"
    return params["0"], proc


@pytest.mark.timeout(180)
def test_elastic_recovers_from_frame_corruption_bit_identical(tmp_path):
    """The integrity plane end to end: an in-flight byte flip mid-training
    aborts both (still-alive) ranks, the worker-posted reset request makes
    the driver advance an epoch, both workers roll back to their last
    commit and re-rendezvous — and the finished run's params are
    BIT-identical to a no-fault run of the same script."""
    clean, _ = _run_elastic_corruption_job(tmp_path, None)
    faulted, proc = _run_elastic_corruption_job(
        tmp_path, "tcp.send:rank=1:nth=25:action=corrupt,1")
    assert faulted == clean, "recovery did not converge to the no-fault run"
    # the fault actually fired and recovered through the epoch plane: the
    # driver logged the worker's reset request naming the CRC failure
    assert "reset_requests" in proc.stderr and "advancing epoch" \
        in proc.stderr, proc.stderr[-3000:]
    assert "wire CRC" in proc.stderr, proc.stderr[-3000:]


@pytest.mark.timeout(180)
def test_elastic_recovers_from_corruption_with_compression_on(tmp_path):
    """The full composition: fp16 wire compression + shadow digests +
    an in-flight byte flip.  The step digest catches the flip, both ranks
    roll back and re-rendezvous, and the finished params are BIT-identical
    to a no-fault run with the same compression config (quantization is
    deterministic, so recovery replay converges exactly)."""
    comp_env = {"HOROVOD_WIRE_COMPRESSION": "fp16"}
    clean, _ = _run_elastic_corruption_job(tmp_path, None,
                                           extra_env=comp_env)
    faulted, proc = _run_elastic_corruption_job(
        tmp_path, "tcp.send:rank=1:nth=25:action=corrupt,1",
        extra_env=comp_env)
    assert faulted == clean, "recovery did not converge to the no-fault run"
    assert "wire CRC" in proc.stderr, proc.stderr[-3000:]


@pytest.mark.timeout(180)
def test_elastic_recovers_with_int8_compression_bit_identical(tmp_path):
    """Lossy compression composes with elastic recovery: int8 + error
    feedback + an in-flight byte flip.  The gradients are crafted so the
    int8 round trip is EXACT (magnitudes 127·(batch+1) → scale divides
    out, residuals stay zero), so dropping the EF accumulators at
    re-init — which recovery must do, state is op-owned — leaves the
    faulted run BIT-identical to a no-fault run."""
    comp_env = {"HOROVOD_WIRE_COMPRESSION": "int8"}
    clean, _ = _run_elastic_corruption_job(
        tmp_path, None, extra_env=comp_env,
        train_src=_ELASTIC_INT8_TRAIN)
    faulted, proc = _run_elastic_corruption_job(
        tmp_path, "tcp.send:rank=1:nth=25:action=corrupt,1",
        extra_env=comp_env, train_src=_ELASTIC_INT8_TRAIN)
    assert faulted == clean, "recovery did not converge to the no-fault run"
    assert "wire CRC" in proc.stderr, proc.stderr[-3000:]


@pytest.mark.timeout(300)
def test_elastic_recovers_from_injected_rank_death(tmp_path):
    """End-to-end: HOROVOD_FAULT_SPEC hard-kills rank 1 mid-run under the
    elastic launcher; the survivor rolls back to its last commit,
    re-rendezvouses at size 1, and finishes — an injected fault rides the
    exact recovery path a real worker death does."""
    disc = tmp_path / "discover.sh"
    disc.write_text("#!/bin/sh\necho localhost:1\necho 127.0.0.1:1\n")
    disc.chmod(0o755)
    train = tmp_path / "train.py"
    train.write_text(_ELASTIC_CHAOS_TRAIN)

    env = os.environ.copy()
    env.update(_FAST_DEADLINE)
    # Fires only in rank 1's worker process (rank filter); the respawned
    # world has no rank 1, so recovery runs fault-free.
    env["HOROVOD_FAULT_SPEC"] = "dispatch.collective:rank=1:nth=8:action=exit,9"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "-np", "2", "--min-np", "1",
         "--host-discovery-script", str(disc),
         sys.executable, str(train)],
        cwd=REPO_ROOT, text=True, env=env,
        capture_output=True, timeout=240)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "ELASTIC_DONE" in proc.stdout, proc.stdout[-2000:]
    assert "size=2" in proc.stdout, "never ran at full size"
    assert "size=1" in proc.stdout, "never recovered at reduced size"


# ---------------------------------------------------------------------------
# self-healing straggler demotion (docs/elastic.md "self-healing demotion")
# ---------------------------------------------------------------------------

# Averaging allreduce (the default op) with IDENTICAL per-rank
# contributions: the average equals the contribution at every world size,
# so a run that sheds a host mid-training must still land on params
# BIT-identical to an undisturbed run.  Contributions are small integers
# (exact in fp32; sum/divide round-trips exactly), so "bit-identical" is
# a meaningful assertion, not a tolerance.
_ELASTIC_DEMOTION_TRAIN = """
import numpy as np
try:
    import jax
    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass
import horovod_tpu as hvd

hvd.init()
state = hvd.elastic.ObjectState(batch=0, params=np.zeros(4, np.float32))

@hvd.elastic.run
def train(state):
    while state.batch < 30:
        grad = hvd.allreduce(
            np.full(4, float(state.batch + 1), np.float32), name="g")
        state.params = state.params + np.asarray(grad)
        state.batch += 1
        state.commit()

train(state)
print("FINAL_PARAMS r%d %s" % (
    hvd.rank(), np.asarray(state.params).tobytes().hex()), flush=True)
print("ELASTIC_DONE", hvd.rank(), flush=True)
hvd.shutdown()
"""

# Aggressive-but-stable detector tuning for a CI-sized job.  The chronic
# clause defers rank 1's tallies by 300ms per cycle, far over the 0.1s
# demote threshold; 3 consecutive over-threshold cycles take ~1s of
# wall-clock.  The response cache must be OFF: cache-bit announcements
# bypass the request-table tally path the controller.tally site lives on
# (docs/fault_injection.md).
_DEMOTION_KNOBS = {
    "HOROVOD_STRAGGLER_THRESHOLD_SECS": "0.08",
    "HOROVOD_STRAGGLER_EWMA_ALPHA": "0.5",
    "HOROVOD_STRAGGLER_DEMOTE_SECS": "0.1",
    "HOROVOD_STRAGGLER_DEMOTE_CYCLES": "3",
    "HOROVOD_CACHE_CAPACITY": "0",
    "HOROVOD_LOCK_DEBUG": "1",
}


def _run_demotion_job(tmp_path, fault_spec, min_np=2, extra_env=None):
    """np=3 elastic job across three loopback 'hosts' (one slot each) so a
    demotion sheds exactly one host.  Returns (rank->params map, proc)."""
    disc = tmp_path / "discover3.sh"
    disc.write_text("#!/bin/sh\necho localhost:1\necho 127.0.0.1:1\n"
                    "echo 127.0.0.2:1\n")
    disc.chmod(0o755)
    train = tmp_path / f"train_{'fault' if fault_spec else 'clean'}.py"
    train.write_text(_ELASTIC_DEMOTION_TRAIN)

    env = os.environ.copy()
    env.update(_FAST_DEADLINE)
    env.update(_DEMOTION_KNOBS)
    env.update(extra_env or {})
    env["HOROVOD_LOG_LEVEL"] = "info"  # driver logs the demotion cause
    env.pop("HOROVOD_FAULT_SPEC", None)
    if fault_spec:
        env["HOROVOD_FAULT_SPEC"] = fault_spec
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "-np", "3", "--min-np", str(min_np),
         "--host-discovery-script", str(disc),
         sys.executable, str(train)],
        cwd=REPO_ROOT, text=True, env=env,
        capture_output=True, timeout=240)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    params = dict(re.findall(r"FINAL_PARAMS r(\d+) ([0-9a-f]+)",
                             proc.stdout))
    assert params, proc.stdout[-2000:]
    assert len(set(params.values())) == 1, "ranks diverged"
    return params, proc


@pytest.mark.timeout(600)
def test_chronic_straggler_demoted_job_converges_bit_identical(tmp_path):
    """The tentpole end to end: a chronically slow rank (every tally
    deferred 300ms via controller.tally) trips the demotion state machine,
    the coordinator posts the verdict over the rendezvous store, the
    driver blacklists the straggler's host and advances the epoch with
    cause=demotion, and the surviving np=2 world finishes with params
    BIT-identical to an undisturbed np=3 run."""
    clean, _ = _run_demotion_job(tmp_path, None)
    assert set(clean) == {"0", "1", "2"}
    faulted, proc = _run_demotion_job(
        tmp_path, "controller.tally:rank=1:after=0:action=delay_ms,300")
    # The straggler's host was shed: the run finished at size 2, and the
    # demoted worker never printed final params.
    assert set(faulted) == {"0", "1"}, proc.stdout[-2000:]
    assert faulted["0"] == clean["0"], \
        "demoted run did not converge to the no-fault run"
    # The full demotion chain is visible in the driver/coordinator logs:
    # chronic verdict -> blacklist with EWMA evidence -> epoch advance
    # attributed to the demotion (not to a worker death or reset).
    assert "chronic straggler" in proc.stderr, proc.stderr[-3000:]
    assert "blacklisting host 127.0.0.1" in proc.stderr, proc.stderr[-3000:]
    assert "readiness-lag EWMA" in proc.stderr, proc.stderr[-3000:]
    assert "cause=demotion" in proc.stderr, proc.stderr[-3000:]
    assert "advancing epoch" in proc.stderr, proc.stderr[-3000:]


@pytest.mark.timeout(600)
def test_one_shot_straggle_flags_but_does_not_demote(tmp_path):
    """Demotion false-positive guard: a single 200ms spike trips the
    straggler FLAG (threshold 0.05s) but can never fill the demotion
    window — the lag EWMA is bounded by the largest observed lag (~0.2s),
    which stays strictly under the 0.3s demote threshold, so no streak
    ever starts.  The job keeps all three ranks and still converges
    bit-identically to the clean run: flagging is free, shedding is not."""
    spike_knobs = {"HOROVOD_STRAGGLER_THRESHOLD_SECS": "0.05",
                   "HOROVOD_STRAGGLER_DEMOTE_SECS": "0.3"}
    clean, _ = _run_demotion_job(tmp_path, None, extra_env=spike_knobs)
    faulted, proc = _run_demotion_job(
        tmp_path, "controller.tally:rank=1:nth=3:action=delay_ms,200",
        extra_env=spike_knobs)
    assert set(faulted) == {"0", "1", "2"}, \
        "a one-shot delay cost the job a host"
    assert faulted["0"] == clean["0"]
    assert "straggler detected" in proc.stderr, \
        "the spike never even flagged — the test exercised nothing"
    assert "chronic straggler" not in proc.stderr, proc.stderr[-3000:]
    assert "blacklisting host" not in proc.stderr, proc.stderr[-3000:]
    assert "cause=demotion" not in proc.stderr, proc.stderr[-3000:]


# ---------------------------------------------------------------------------
# zero-restart elastic resharding (docs/elastic.md "Live resharding")
# ---------------------------------------------------------------------------

# Crash limit raised over the default of 1 so the SIGKILL'd victim's host
# is NOT shed: its identity must come back as a JOINER of the resharded
# epoch (exercising the sync_root broadcast), not vanish with the host.
# min_np == np below pins the world size, so the averaging-allreduce
# bit-identity argument needs no size-change caveat.
_RESHARD_KNOBS = {
    "HOROVOD_ELASTIC_CRASH_FAILURE_LIMIT": "5",
    "HOROVOD_LOCK_DEBUG": "1",
}


# The victim's fault must fire ONCE per job, not once per process: the
# respawned joiner inherits HOROVOD_FAULT_SPEC and would kill itself
# again every nth collectives until the host blacklists.  Each identity
# marks its first incarnation with a flag file keyed on
# HOROVOD_LOCAL_RANK (set per slot by the launcher, readable before
# hvd.init); a REspawned incarnation finds its own flag and disarms the
# spec before the faults registry parses it at import.
_RESHARD_DISARM_PREAMBLE = """
import os
_flag = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "spawned_%s" % os.environ.get("HOROVOD_LOCAL_RANK"))
if os.path.exists(_flag):
    os.environ.pop("HOROVOD_FAULT_SPEC", None)
else:
    open(_flag, "w").close()
"""


def _run_reshard_job(tmp_path, fault_spec, extra_env=None):
    """np=8 elastic job on ONE loopback host (8 slots).  Returns
    (rank->params map, proc)."""
    disc = tmp_path / "discover8.sh"
    disc.write_text("#!/bin/sh\necho localhost:8\n")
    disc.chmod(0o755)
    arm = "fault" if fault_spec else "clean"
    jobdir = tmp_path / arm
    jobdir.mkdir()
    train = jobdir / "train.py"
    train.write_text(_RESHARD_DISARM_PREAMBLE + _ELASTIC_DEMOTION_TRAIN)

    env = os.environ.copy()
    env.update(_FAST_DEADLINE)
    env.update(_RESHARD_KNOBS)
    env.update(extra_env or {})
    env["HOROVOD_LOG_LEVEL"] = "info"  # driver logs publish/commit/fallback
    env.pop("HOROVOD_FAULT_SPEC", None)
    if fault_spec:
        env["HOROVOD_FAULT_SPEC"] = fault_spec
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "-np", "8", "--min-np", "8",
         "--host-discovery-script", str(disc),
         sys.executable, str(train)],
        cwd=REPO_ROOT, text=True, env=env,
        capture_output=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    params = dict(re.findall(r"FINAL_PARAMS r(\d+) ([0-9a-f]+)",
                             proc.stdout))
    assert params, proc.stdout[-2000:]
    assert len(set(params.values())) == 1, "ranks diverged"
    return params, proc


def _spawns_by_epoch(stderr):
    """[(identity, epoch), ...] from the driver's spawn log lines."""
    return [(ident, int(ep)) for ident, ep in
            re.findall(r"spawning worker (\S+) \(epoch (\d+)", stderr)]


@pytest.mark.slow
@pytest.mark.timeout(900)
def test_live_reshard_np8_survivors_keep_processes_joiner_syncs(tmp_path):
    """The tentpole end to end at np=8: rank 3 is SIGKILL'd mid-train, the
    driver publishes the next assignment with the reshard marker, the 7
    survivors abort their in-flight collectives and re-rendezvous IN PLACE
    (the driver spawns exactly one post-churn process: the victim's
    identity, back as a joiner), the joiner receives mid-training state
    over the sync_root broadcast — this job has no checkpointing at all,
    so the joiner finishing bit-identical IS the proof the state came over
    collectives — and the commit record lands only after every survivor
    acked the new epoch."""
    clean, _ = _run_reshard_job(tmp_path, None)
    assert set(clean) == {str(r) for r in range(8)}
    faulted, proc = _run_reshard_job(
        tmp_path, "dispatch.collective:rank=3:nth=8:action=exit,9")
    assert set(faulted) == {str(r) for r in range(8)}, proc.stdout[-2000:]
    assert faulted["0"] == clean["0"], \
        "resharded run did not converge to the no-churn run"
    # The reshard protocol ran — marked publish, then the commit that
    # requires every survivor's ack — and never degraded to the legacy
    # full-teardown path.
    assert "published with reshard marker" in proc.stderr, \
        proc.stderr[-3000:]
    assert "reshard committed at epoch" in proc.stderr, proc.stderr[-3000:]
    assert "falls back to the full-teardown path" not in proc.stderr, \
        proc.stderr[-3000:]
    # Zero restarts for survivors: 8 spawns at epoch 0, then exactly ONE
    # post-churn spawn, and it is the victim's identity.
    spawns = _spawns_by_epoch(proc.stderr)
    initial = [ident for ident, ep in spawns if ep == 0]
    later = [ident for ident, ep in spawns if ep > 0]
    assert len(initial) == 8, spawns
    assert later == ["localhost:3"], \
        f"survivors were respawned (or the victim was not): {spawns}"


@pytest.mark.slow
@pytest.mark.timeout(900)
def test_live_reshard_kill_switch_falls_back_and_still_converges(tmp_path):
    """HOROVOD_RESHARD=0 is the operator kill-switch: the same SIGKILL
    churn must publish NO reshard marker and write NO commit record — the
    job recovers on the legacy path (survivors ride out the progress
    deadline instead of the prompt abort) and still converges
    bit-identical.  The fallback is load-bearing: this is also the path a
    wedged reshard degrades to."""
    clean, _ = _run_reshard_job(tmp_path, None,
                                extra_env={"HOROVOD_RESHARD": "0"})
    faulted, proc = _run_reshard_job(
        tmp_path, "dispatch.collective:rank=3:nth=8:action=exit,9",
        extra_env={"HOROVOD_RESHARD": "0"})
    assert set(faulted) == {str(r) for r in range(8)}, proc.stdout[-2000:]
    assert faulted["0"] == clean["0"]
    assert "published with reshard marker" not in proc.stderr, \
        proc.stderr[-3000:]
    assert "reshard committed" not in proc.stderr, proc.stderr[-3000:]
    # The legacy path also keeps survivor processes: only the victim's
    # identity is respawned.  What the kill-switch changes is the abort
    # latency and the sync discipline, not the process-lifetime contract.
    later = [ident for ident, ep in _spawns_by_epoch(proc.stderr) if ep > 0]
    assert later == ["localhost:3"], proc.stderr[-3000:]


# ---------------------------------------------------------------------------
# negotiation fan-in aggregator death (docs/data_plane.md "Negotiation
# fan-in"): np=4 on TWO loopback hosts — the smallest layout that trees
# ---------------------------------------------------------------------------

# Keyed on HOROVOD_RANK (not LOCAL_RANK: two loopback hosts collide on
# local_rank 0) so the respawned aggregator incarnation disarms the kill
# before the faults registry parses it at import.
_FANIN_DISARM_PREAMBLE = """
import os
_flag = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "spawned_%s" % os.environ.get("HOROVOD_RANK"))
if os.path.exists(_flag):
    os.environ.pop("HOROVOD_FAULT_SPEC", None)
else:
    open(_flag, "w").close()
"""


_ELASTIC_FANIN_TRAIN = """
import numpy as np
try:
    import jax
    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass
import horovod_tpu as hvd

hvd.init()
from horovod_tpu.core.state import global_state
_plan = global_state().controller.fanin_plan
print("FANIN_ROLE r%d %s" % (
    hvd.rank(), _plan.role if _plan is not None else "none"), flush=True)
state = hvd.elastic.ObjectState(batch=0, params=np.zeros(4, np.float32))

@hvd.elastic.run
def train(state):
    while state.batch < 15:
        grad = hvd.allreduce(
            np.full(4, float(state.batch + 1), np.float32),
            op=hvd.Sum, name="g")
        state.params = state.params + np.asarray(grad)
        state.batch += 1
        state.commit()

train(state)
print("FINAL_PARAMS r%d %s" % (
    hvd.rank(), np.asarray(state.params).tobytes().hex()), flush=True)
print("ELASTIC_DONE", hvd.rank(), flush=True)
hvd.shutdown()
"""


def _run_fanin_death_job(tmp_path, fault_spec, extra_env=None):
    """np=4 elastic job on TWO loopback hosts (2 slots each): the blocked
    2x2 layout turns tree negotiation fan-in on (auto), making rank 2 the
    host-1 aggregator.  Returns (rank->params map, proc)."""
    arm = "fault" if fault_spec else "clean"
    jobdir = tmp_path / arm
    jobdir.mkdir()
    disc = jobdir / "discover.sh"
    disc.write_text("#!/bin/sh\necho localhost:2\necho 127.0.0.1:2\n")
    disc.chmod(0o755)
    train = jobdir / "train.py"
    train.write_text(_FANIN_DISARM_PREAMBLE + _ELASTIC_FANIN_TRAIN)

    env = os.environ.copy()
    env.update(_FAST_DEADLINE)
    env.update(_RESHARD_KNOBS)
    env.update(extra_env or {})
    env["HOROVOD_LOG_LEVEL"] = "info"
    env.pop("HOROVOD_FAULT_SPEC", None)
    if fault_spec:
        env["HOROVOD_FAULT_SPEC"] = fault_spec
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "-np", "4", "--min-np", "4",
         "--host-discovery-script", str(disc),
         sys.executable, str(train)],
        cwd=REPO_ROOT, text=True, env=env,
        capture_output=True, timeout=100)  # healthy: 6 s; starved: 42 s
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    params = dict(re.findall(r"FINAL_PARAMS r(\d+) ([0-9a-f]+)",
                             proc.stdout))
    assert set(params) == {str(r) for r in range(4)}, proc.stdout[-2000:]
    assert len(set(params.values())) == 1, "ranks diverged"
    return params, proc


@pytest.mark.timeout(300)
def test_fanin_aggregator_death_np4_reconverges_bit_identical(tmp_path):
    """An aggregator death must never silence its host or lose a
    readiness bit: rank 2 (host 1's negotiation aggregator) is SIGKILL'd
    mid-train; its member's blocking recv raises PeerGoneError promptly,
    the coordinated abort discards the in-flight cycle on every path,
    the PR 19 reshard respawns exactly the victim's identity, and the
    re-treed epoch finishes BIT-identical to an undisturbed run — the
    stateless-fold property live (every cycle re-announces the full
    mask, so the discarded cycle loses nothing).  The wedge flavor
    (stale heartbeat -> veto -> direct) is exhaustively model-checked in
    test_mck_proto.py and unit-covered in test_negotiation_fanin.py."""
    clean, cproc = _run_fanin_death_job(tmp_path, None)
    faulted, proc = _run_fanin_death_job(
        tmp_path, "dispatch.collective:rank=2:nth=8:action=exit,9")
    assert faulted == clean, \
        "aggregator-death recovery did not converge to the no-fault run"
    # The tree was live in both runs and rank 2 WAS host 1's aggregator
    # (the respawned incarnation re-trees into the same role).
    for out in (cproc.stdout, proc.stdout):
        roles = dict(re.findall(r"FANIN_ROLE r(\d+) (\w+)", out))
        assert roles == {"0": "coordinator", "1": "direct",
                         "2": "aggregator", "3": "member"}, out[-2000:]
    # Zero-restart recovery: exactly one post-churn spawn, the dead
    # aggregator's identity.
    later = [ident for ident, ep in _spawns_by_epoch(proc.stderr) if ep > 0]
    assert later == ["127.0.0.1:0"], proc.stderr[-3000:]


# ---------------------------------------------------------------------------
# control-plane survivability (docs/control_plane.md)
# ---------------------------------------------------------------------------


@pytest.mark.timeout(120)
def test_dead_worker_lease_expiry_advances_epoch_within_one_tick():
    """A worker whose PROCESS is alive but whose lease stops renewing is
    genuinely dead to the job: the driver must declare it dead and advance
    the epoch on the first tick after expiry — the liveness half of
    dead-vs-partitioned (a store outage, by contrast, must freeze this
    judgment; tested in the SIGKILL run below)."""
    from horovod_tpu.core import metrics as metrics_mod
    from horovod_tpu.elastic.discovery import FixedHosts, HostManager
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner.hosts import parse_hosts
    from horovod_tpu.runner.rendezvous import RendezvousServer
    from horovod_tpu.transport.store import LEASE_SCOPE

    server = RendezvousServer("127.0.0.1")
    server.start()
    spawned = []
    driver = ElasticDriver(
        server,
        HostManager(FixedHosts(parse_hosts("localhost:1,127.0.0.1:1"))),
        min_np=2, lease_timeout=1.5)
    stop_renewals = threading.Event()

    def renew_survivor():
        n = 0
        while not stop_renewals.is_set():
            n += 1  # the VALUE must change: freshness is change-based
            server.set(LEASE_SCOPE, "localhost:0",
                       json.dumps({"rank": 0, "epoch": 0,
                                   "renewals": n}).encode())
            time.sleep(0.3)

    expirations_before = metrics_mod.registry.get_counter(
        "lease_expirations_total")
    try:
        driver.start(lambda slot, epoch: spawned.append(
            (f"{slot.hostname}:{slot.local_rank}", epoch)))
        assert driver.epoch == 0 and len(spawned) == 2
        threading.Thread(target=renew_survivor, daemon=True).start()
        # The doomed worker posts exactly ONE lease, then goes silent —
        # no exit event ever reaches the driver.
        server.set(LEASE_SCOPE, "127.0.0.1:0",
                   json.dumps({"rank": 1, "epoch": 0,
                               "renewals": 1}).encode())
        t0 = time.monotonic()
        while driver.epoch == 0 and time.monotonic() - t0 < 30:
            time.sleep(0.05)
        elapsed = time.monotonic() - t0
        assert driver.epoch >= 1, "lease expiry never advanced the epoch"
        # Bound: baseline sighting (≤1 tick) + timeout (1.5 s) + one
        # judgment tick (1 s) + scheduling slack.  Anything near the 15 s
        # production default means expiry didn't drive the advance.
        assert elapsed < 10.0, f"epoch advance took {elapsed:.1f}s"
        # The dead identity was respawned at the new epoch; the renewing
        # survivor was left alone.
        deadline = time.monotonic() + 10
        while ("127.0.0.1:0", 1) not in spawned and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        assert ("127.0.0.1:0", 1) in spawned, spawned
        assert ("localhost:0", 1) not in spawned, spawned
        assert metrics_mod.registry.get_counter(
            "lease_expirations_total") >= expirations_before + 1
        # The transition itself must be attributable after the fact: a
        # cause-tagged flight-recorder event and counter (the driver runs
        # in this process, so both are inspectable directly).
        from horovod_tpu.core import flight_recorder

        trans = [e for e in flight_recorder.recorder.events()
                 if e.get("kind") == "epoch_transition"]
        assert trans, "driver recorded no epoch_transition event"
        assert trans[-1]["cause"] == "lease_expiry", trans[-1]
        assert "127.0.0.1:0" in trans[-1]["dead_workers"], trans[-1]
        assert metrics_mod.registry.get_counter(
            "driver_epoch_transitions_total", cause="lease_expiry") >= 1
    finally:
        stop_renewals.set()
        driver.stop()
        driver._discovery_thread.join(timeout=10)
        server.stop()


@pytest.mark.timeout(120)
def test_job_ends_when_every_rank_finished_whatever_respawn_is_in_flight():
    """The second half of ROADMAP D0 (ii): a live worker's lease runs out,
    its identity is respawned, and then the world finishes, the silent
    process with it (a remote one the launcher cannot kill, or one that
    exits before the kill lands).  The joiner has no world to join; the
    launcher waited on it without end.  The driver ends the job at its
    next tick (``job_end_steps``), and the respawned identity's stale
    lease is gone from the store, so the joiner is not judged by it."""
    from horovod_tpu.elastic.discovery import FixedHosts, HostManager
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner.hosts import parse_hosts
    from horovod_tpu.runner.rendezvous import RendezvousServer
    from horovod_tpu.transport.store import LEASE_SCOPE

    server = RendezvousServer("127.0.0.1")
    server.start()
    spawned = {}
    driver = ElasticDriver(
        server,
        HostManager(FixedHosts(parse_hosts("localhost:1,127.0.0.1:1"))),
        min_np=2, lease_timeout=1.0)
    try:
        driver.start(lambda slot, epoch: spawned.setdefault(
            f"{slot.hostname}:{slot.local_rank}", []).append((slot, epoch)))
        for identity in ("localhost:0", "127.0.0.1:0"):
            server.set(LEASE_SCOPE, identity, b"once")   # then silence
        deadline = time.monotonic() + 30
        while driver.epoch == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert driver.epoch == 1
        assert [e for _s, e in spawned["127.0.0.1:0"]] == [0, 1], spawned
        assert server.get(LEASE_SCOPE, "127.0.0.1:0") is None
        assert not driver.job_ended
        # The silent processes finish after all, beside their joiners.
        for identity in spawned:
            driver.record_worker_exit(spawned[identity][0][0], 0)
        deadline = time.monotonic() + 10
        while not driver.job_ended and time.monotonic() < deadline:
            time.sleep(0.05)
        assert driver.job_ended and driver.finished()
        assert driver.epoch == 1, "a finished job advanced its epoch"
    finally:
        driver.stop()
        driver._discovery_thread.join(timeout=10)
        server.stop()


_SURVIVABILITY_TRAIN = """
import time
import numpy as np
try:
    import jax
    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass
import horovod_tpu as hvd

hvd.init()
state = hvd.elastic.ObjectState(batch=0, params=np.zeros(4, np.float32))

@hvd.elastic.run
def train(state):
    while state.batch < 80:
        grad = hvd.allreduce(
            np.full(4, float(state.batch + 1), np.float32),
            op=hvd.Sum, name="g")
        state.params = state.params + np.asarray(grad)
        if state.batch % 5 == 0:
            print(f"BATCH {state.batch} rank={hvd.rank()}", flush=True)
        state.batch += 1
        state.commit()
        time.sleep(0.1)

train(state)
print("FINAL_PARAMS r%d %s" % (
    hvd.rank(), np.asarray(state.params).tobytes().hex()), flush=True)
hvd.shutdown()
"""


def _spawn_external_server(port, journal_dir, env):
    """Start the standalone journaled rendezvous server and wait for it
    to accept connections."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.rendezvous",
         "--bind", "127.0.0.1", "--port", str(port),
         "--journal-dir", str(journal_dir)],
        cwd=REPO_ROOT, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1):
                return proc
        except OSError:
            if proc.poll() is not None:
                break
            time.sleep(0.1)
    proc.kill()
    raise RuntimeError("standalone rendezvous server never came up")


def _pump(stream, sink):
    for line in iter(stream.readline, ""):
        sink.append(line)
    stream.close()


def _run_survivable_job(tmp_path, kill_server):
    """np=2 elastic job against an EXTERNAL journaled rendezvous server;
    optionally SIGKILL the server mid-train and restart it over the same
    journal ~2 s later.  Returns (params_hex, stdout, stderr)."""
    label = "kill" if kill_server else "clean"
    jdir = tmp_path / f"journal_{label}"
    port = reserve_port()
    release_reservations()  # hand the port to the server child

    env = os.environ.copy()
    env.update(_FAST_DEADLINE)
    env.pop("HOROVOD_FAULT_SPEC", None)
    env["HOROVOD_LOG_LEVEL"] = "info"
    env["HOROVOD_SECRET_KEY"] = "survivability-chaos"
    env["HOROVOD_METRICS_PUSH_SECS"] = "0.5"  # lease-renewal cadence
    env["HOROVOD_RENDEZVOUS_EXTERNAL"] = f"127.0.0.1:{port}"

    disc = tmp_path / f"discover_{label}.sh"
    disc.write_text("#!/bin/sh\necho localhost:1\necho 127.0.0.1:1\n")
    disc.chmod(0o755)
    train = tmp_path / f"train_{label}.py"
    train.write_text(_SURVIVABILITY_TRAIN)

    server = _spawn_external_server(port, jdir, env)
    launcher = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "-np", "2", "--min-np", "2",
         "--host-discovery-script", str(disc),
         sys.executable, str(train)],
        cwd=REPO_ROOT, text=True, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    out_lines, err_lines = [], []
    pumps = [threading.Thread(target=_pump, args=(launcher.stdout, out_lines),
                              daemon=True),
             threading.Thread(target=_pump, args=(launcher.stderr, err_lines),
                              daemon=True)]
    for t in pumps:
        t.start()
    try:
        if kill_server:
            # Wait until BOTH ranks are demonstrably past init and
            # training (a kill during init would be a different test).
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                text = "".join(out_lines)
                if re.search(r"BATCH \d+ rank=0", text) and \
                        re.search(r"BATCH \d+ rank=1", text):
                    break
                if launcher.poll() is not None:
                    break
                time.sleep(0.2)
            else:
                raise RuntimeError("ranks never reached training")
            server.kill()  # SIGKILL: no flush, no goodbye
            server.wait()
            time.sleep(2.0)  # a real supervisor restart delay
            server = _spawn_external_server(port, jdir, env)
        rc = launcher.wait(timeout=300)
    finally:
        if launcher.poll() is None:
            launcher.kill()
            launcher.wait()
        server.kill()
        server.wait()
    for t in pumps:
        t.join(timeout=10)
    stdout, stderr = "".join(out_lines), "".join(err_lines)
    assert rc == 0, (stdout[-2000:], stderr[-2000:])
    params = dict(re.findall(r"FINAL_PARAMS r(\d+) ([0-9a-f]+)", stdout))
    assert set(params) == {"0", "1"}, stdout[-2000:]
    assert params["0"] == params["1"], "ranks diverged"
    return params["0"], stdout, stderr


@pytest.mark.timeout(600)
def test_rendezvous_server_sigkill_restart_bit_identical(tmp_path):
    """The headline survivability proof: SIGKILL the external rendezvous
    server mid-train and restart it over the same journal — the np=2 job
    rides out the outage (best-effort pushes, partitioned-mode driver),
    reattaches, and converges BIT-identical to a no-fault run with ZERO
    epoch advances."""
    clean, _, _ = _run_survivable_job(tmp_path, kill_server=False)
    killed, _, stderr = _run_survivable_job(tmp_path, kill_server=True)
    assert killed == clean, \
        "post-restart run diverged from the no-fault run"
    # Zero epoch bumps: the outage must read as partitioned, never as
    # dead workers.
    assert "advancing epoch" not in stderr, stderr[-3000:]
    # And the outage actually happened and healed — this test must not
    # pass vacuously if the kill lands in a blind spot.
    assert "unreachable" in stderr, stderr[-3000:]
    assert "reachable again" in stderr, stderr[-3000:]


_STATIC_SURVIVABILITY_TRAIN = """
import jax
jax.config.update("jax_platforms", "cpu")
import time
import numpy as np
import horovod_tpu as hvd

hvd.init()
params = np.zeros(4, dtype=np.float32)
for batch in range(30):
    g = hvd.allreduce(np.full(4, batch + 1, dtype=np.float32),
                      name="g%d" % batch, average=False)
    params += np.asarray(g)
    print("BATCH %d rank=%d" % (batch, hvd.rank()), flush=True)
    time.sleep(0.1)
print("FINAL_PARAMS r%d %s" % (
    hvd.rank(), params.tobytes().hex()), flush=True)
hvd.shutdown()
"""


@pytest.mark.timeout(300)
def test_static_launch_attaches_external_server_and_survives_restart(
        tmp_path):
    """HOROVOD_RENDEZVOUS_EXTERNAL on the PLAIN (non-elastic) launch
    path: the static launcher must attach to the standalone journaled
    server instead of starting its own, the np=2 job must ride out a
    SIGKILL+restart of that server mid-train, and the restarted server's
    journal must replay the slot table the launcher published."""
    jdir = tmp_path / "journal_static"
    port = reserve_port()
    release_reservations()

    env = os.environ.copy()
    env.update(_FAST_DEADLINE)
    env.pop("HOROVOD_FAULT_SPEC", None)
    env["HOROVOD_SECRET_KEY"] = "survivability-chaos"
    env["HOROVOD_METRICS_PUSH_SECS"] = "0.5"
    env["HOROVOD_RENDEZVOUS_EXTERNAL"] = f"127.0.0.1:{port}"
    train = tmp_path / "train_static.py"
    train.write_text(_STATIC_SURVIVABILITY_TRAIN)

    server = _spawn_external_server(port, jdir, env)
    launcher = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         sys.executable, str(train)],
        cwd=REPO_ROOT, text=True, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    out_lines = []
    pump = threading.Thread(target=_pump, args=(launcher.stdout, out_lines),
                            daemon=True)
    pump.start()
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            text = "".join(out_lines)
            if re.search(r"BATCH \d+ rank=0", text) and \
                    re.search(r"BATCH \d+ rank=1", text):
                break
            if launcher.poll() is not None:
                break
            time.sleep(0.2)
        else:
            raise RuntimeError("ranks never reached training")
        server.kill()
        server.wait()
        time.sleep(1.0)
        server = _spawn_external_server(port, jdir, env)
        rc = launcher.wait(timeout=180)
    finally:
        if launcher.poll() is None:
            launcher.kill()
            launcher.wait()
        server.kill()
        server.wait()
    pump.join(timeout=10)
    stdout = "".join(out_lines)
    assert rc == 0, stdout[-3000:]
    params = dict(re.findall(r"FINAL_PARAMS r(\d+) ([0-9a-f]+)", stdout))
    assert set(params) == {"0", "1"} and params["0"] == params["1"], \
        stdout[-2000:]
    # The launcher really went THROUGH the external server: its published
    # slot table (and both workers' leases) replay from the journal.
    from horovod_tpu.transport.store import LEASE_SCOPE, DurableMemoryStore
    store = DurableMemoryStore(str(jdir))
    try:
        assert sorted(store.keys("rank_and_size")) == \
            ["localhost:0", "localhost:1"]
        assert len(store.keys(LEASE_SCOPE)) == 2
    finally:
        store.close()
