"""The eager runtime's spans on the profiler's clock: ``timeline.phase`` and
the sites it instruments (ISSUE 24).

One np=1 worker drives ``DistributedOptimizer`` on a small MLP and reports
what one step added to ``phase_stats``, what a ``jax.profiler`` trace of
three steps holds, and ``backend.xla.stats``; the cases below each assert
one fact of that report.  Counts only: nothing here is a timing claim.
"""

import json
import sys
import threading
from statistics import median_high

import pytest

from horovod_tpu.common.env import DEFAULT_CYCLE_TIME_MS as CAP

from .helpers import reserve_port, run_distributed

# The tree: four float32 leaves, so one fused buffer, one request, one
# response a step.  SGD with momentum: ``tx.update`` returns a delta per
# leaf and the momentum as one buffer (the state lives fused, ISSUE 30).
LEAVES = 4

# One tensor at a time from ``add`` to its callback, the test's own clock
# beside the program's stamps: ``chain(n)`` returns a row a tensor, the five
# readings in order and what the six phases between them added.
CHAIN = """
def chain(n):
    import time
    import jax.numpy as jnp
    from horovod_tpu.core.state import global_state
    from horovod_tpu.core.timeline import phase_stats
    st = global_state()
    q, seen = st.tensor_queue, {}
    add0, perform0 = q.add, st._perform_operation
    def add(entry, request):
        inner = entry.callback
        def callback(status, e):
            seen["callback"] = time.monotonic()
            inner(status, e)
        entry.callback = callback
        seen["entry"] = entry
        seen["add"] = time.monotonic()
        add0(entry, request)
    def perform(response, **kw):
        seen["agreed"] = response._agreed_at
        seen["dispatched"] = time.monotonic()
        perform0(response, **kw)
    q.add, st._perform_operation = add, perform
    parts = ("queue_wait", "negotiate_wait", "dispatch_wait", "fuse",
             "collective", "unfuse")
    rows = []
    try:
        for i in range(n):
            before = phase_stats.snapshot()
            hvd.synchronize(hvd.allreduce_async(
                jnp.full((8,), float(i)), name="chain.%d" % i))
            after = phase_stats.snapshot()
            rows.append({
                "stamps": [seen["add"], seen["entry"].announced_at,
                           seen["agreed"], seen["dispatched"],
                           seen["callback"]],
                "parts_ms": sum(after[k]["total_ms"]
                                - before.get(k, {"total_ms": 0.0})["total_ms"]
                                for k in parts if k in after),
                "counts": {k: after[k]["count"]
                           - before.get(k, {"count": 0})["count"]
                           for k in parts if k in after}})
    finally:
        q.add, st._perform_operation = add0, perform0
    return rows
"""

WORKER = CHAIN + """
import glob, json, tempfile
import jax, jax.numpy as jnp, optax
from horovod_tpu.backend import xla
from horovod_tpu.core.state import global_state
from horovod_tpu.core.timeline import phase_stats

def loss_fn(p, b):
    h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
    return jnp.mean((h @ p["w2"] + p["b2"] - b["y"]) ** 2)

params = {"w1": jnp.ones((4, 8)) * 0.1, "b1": jnp.zeros((8,)),
          "w2": jnp.ones((8, 2)) * 0.1, "b2": jnp.zeros((2,))}
batch = {"x": jnp.ones((8, 4)), "y": jnp.ones((8, 2))}
grads = jax.jit(jax.grad(loss_fn))(params, batch)
inner = optax.sgd(0.1, momentum=0.9)

def delta(fn):
    before = phase_stats.snapshot()
    out = fn()
    jax.block_until_ready(out)
    after = phase_stats.snapshot()
    zero = {"count": 0, "total_ms": 0.0}
    return out, {k: {"count": v["count"] - before.get(k, zero)["count"],
                     "ms": v["total_ms"] - before.get(k, zero)["total_ms"]}
                 for k, v in after.items()}

report = {}
dopt = hvd.DistributedOptimizer(inner)
state = dopt.init(params)
for _ in range(2):                          # compile everything
    updates, state = dopt.update(grads, state, params)
(updates, state), report["step"] = delta(
    lambda: dopt.update(grads, state, params))
report["update_outputs"] = len(jax.tree_util.tree_leaves(
    (updates, state.inner_state)))
report["xla_stats"] = dict(xla.stats)

# An entry point that hands the gradient tree back to the caller.
dvg = hvd.distributed_value_and_grad(loss_fn)
for _ in range(2):
    dvg(params, batch)
(_, returned), report["value_and_grad"] = delta(lambda: dvg(params, batch))
report["returned_arrays"] = sum(
    isinstance(l, jax.Array) for l in jax.tree_util.tree_leaves(returned))

# Local aggregation: the off step accumulates and sends nothing.
acc = hvd.DistributedOptimizer(inner, backward_passes_per_step=2)
acc_state = acc.init(params)
for _ in range(2):
    _, acc_state = acc.update(grads, acc_state, params)
(_, acc_state), report["off_step"] = delta(
    lambda: acc.update(grads, acc_state, params))

# A sleep inside update, on the calling thread (the fault site in
# TensorQueue.add): wall time and no CPU.
from horovod_tpu.common import faults
faults.configure("enqueue.collective:action=delay_ms,50")
(updates, state), report["slept"] = delta(
    lambda: dopt.update(grads, state, params))
faults.reset()
report["chain"] = chain(5)

# The one-program path.
wstep = hvd.make_overlapped_train_step(loss_fn, inner)
wp, ws = wstep.init(params, inner.init(params))
wp, ws, _ = wstep(wp, ws, batch)
(wp, ws, _), report["wfbp"] = delta(lambda: wstep(wp, ws, batch))

# Three steps under the profiler, the collective dispatched inline (as at
# one rank) and then from the dispatcher thread (as at several).
def traced(pipelined):
    global state
    global_state().pipeline_dispatch = pipelined
    updates, state = dopt.update(grads, state, params)   # thread comes up
    jax.block_until_ready(updates)
    d = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=options)
    for _ in range(3):
        updates, state = dopt.update(grads, state, params)
        jax.block_until_ready(updates)
    jax.profiler.stop_trace()
    path = glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("hvd."):
                    events.append([line.name, e.name, dict(e.stats)])
    return events

report["trace_inline"] = traced(False)
_, report["pipelined"] = delta(lambda: report.__setitem__(
    "trace_pipelined", traced(True)))
global_state().pipeline_dispatch = False
print("REPORT " + json.dumps(report), flush=True)
"""


@pytest.fixture(scope="module")
def report():
    out = run_distributed(1, WORKER, timeout=240)[0]
    line = [x for x in out.splitlines() if x.startswith("REPORT ")][-1]
    return json.loads(line[len("REPORT "):])


# What one plain step at np=1 adds to each phase's count.  program_call
# counts output arrays: flatten 1 + allreduce 1 + tx.update LEAVES + 1; the
# reduced buffer goes into tx.update's program uncut (ISSUE 26), so no
# step enters tree_unflatten, and the state comes and goes as its buffers
# (ISSUE 30), so no step after init enters state_fuse.
STEP_COUNTS = {
    "update": 1, "fuse": 1, "enqueue": 1, "queue_wait": 1, "negotiate": 1,
    "collective": 1, "unfuse": 1, "wait": 1, "tree_unflatten": 0,
    "state_fuse": 0, "optimizer_update": 1, "program_call": 2 + LEAVES + 1,
    "negotiate_wait": 1, "cpu.update": 1, "negotiate_recv": 0,
    "cpu.dispatch": 0, "dispatch_wait": 0,
}
# The loop thread's own account (the idle rounds since its last busy one, its
# CPU clock): handed on after the busy round, which the caller's snapshot may
# or may not have seen, and how many rounds a step spans is the scheduler's.
A_ROUND = {"negotiate_idle", "cpu.loop"}


@pytest.mark.parametrize("name", sorted(STEP_COUNTS))
def test_one_step_adds_exactly(report, name):
    zero = {"count": 0}
    assert report["step"].get(name, zero)["count"] == STEP_COUNTS[name]


def test_one_step_enters_no_other_phase(report):
    from horovod_tpu.core.timeline import PHASES

    entered = {k for k, v in report["step"].items() if v["count"]} - A_ROUND
    assert entered == {k for k, n in STEP_COUNTS.items() if n}
    assert entered < set(PHASES)
    # The rest belong to the dispatcher thread, the one-program path, the
    # entry points that return a gradient tree and an update that is
    # handed the optimizer's state as a plain tree.  Of the six names of
    # ISSUE 52 a step at one rank may not enter three: negotiate_recv (no
    # other rank, so no frame to block on), cpu.dispatch and with it
    # dispatch_wait (one rank dispatches inline, on the loop thread, whose
    # CPU is cpu.loop's).
    assert set(PHASES) - entered - A_ROUND == {
        "dispatch_wait", "wfbp_dispatch", "tree_unflatten", "state_fuse",
        "negotiate_recv", "cpu.dispatch"}


def test_program_call_counts_outputs_worked_out_from_the_tree(report):
    groups = 1                                  # one dtype
    expect = groups + groups + report["update_outputs"]
    assert report["update_outputs"] == LEAVES + groups
    assert report["step"]["program_call"]["count"] == expect == 7


# distributed_value_and_grad hands the tree to the user, so it alone still
# cuts the reduced buffer into one array per leaf: flatten 1 + allreduce 1
# + unflatten LEAVES.
VALUE_AND_GRAD_COUNTS = {
    "fuse": 1, "enqueue": 1, "queue_wait": 1, "negotiate": 1,
    "collective": 1, "unfuse": 1, "wait": 1, "tree_unflatten": 1,
    "program_call": 2 + LEAVES, "update": 0, "optimizer_update": 0,
}


@pytest.mark.parametrize("name", sorted(VALUE_AND_GRAD_COUNTS))
def test_value_and_grad_still_returns_the_tree(report, name):
    zero = {"count": 0}
    got = report["value_and_grad"].get(name, zero)["count"]
    assert got == VALUE_AND_GRAD_COUNTS[name]
    assert report["returned_arrays"] == LEAVES


def test_callers_parts_sum_to_no_more_than_update(report):
    step = report["step"]
    parts = sum(step[k]["ms"] for k in (
        "fuse", "enqueue", "wait", "optimizer_update"))
    # total_ms is rounded to a microsecond per phase.
    assert 0 < parts <= step["update"]["ms"] + 0.005


def test_old_phases_record_where_the_parent_did(report):
    # The parent commit (PR 23), same tree, same step: one each.
    parent = {"fuse": 1, "unfuse": 1, "collective": 1, "wait": 1,
              "negotiate": 1}
    assert {k: report["step"][k]["count"] for k in parent} == parent


def test_xla_stats_gains_no_key(report):
    assert sorted(report["xla_stats"]) == ["allreduce"]


def test_off_step_of_local_aggregation_is_one_optimizer_program(report):
    entered = {k: v["count"] for k, v in report["off_step"].items()
               if v["count"] and k not in A_ROUND}
    # accumulate returns the accumulator and the zero updates.
    assert entered == {"update": 1, "cpu.update": 1, "optimizer_update": 1,
                       "program_call": 2 * LEAVES}


def test_overlapped_step_is_one_wfbp_dispatch(report):
    entered = {k: v["count"] for k, v in report["wfbp"].items()
               if v["count"] and k not in A_ROUND}
    assert entered == {"wfbp_dispatch": 1}


def _by_name(events, name):
    return [(line, ids) for line, n, ids in events if n == name]


@pytest.mark.parametrize("mode,thread", [
    ("trace_inline", "horovod-background"),
    ("trace_pipelined", "horovod-dispatch")])
def test_trace_holds_update_and_collective_with_one_step_id(report, mode,
                                                            thread):
    events = report[mode]
    updates = _by_name(events, "hvd.update")
    collectives = _by_name(events, "hvd.collective")
    assert len(updates) == len(collectives) == 3
    caller = {line for line, _ in updates}
    assert len(caller) == 1
    # The profiler labels a line with the OS thread's name, 15 characters.
    assert {line for line, _ in collectives} == {thread[:15]}
    assert caller != {thread[:15]}
    steps = [ids["step"] for _, ids in updates]
    assert steps == list(range(steps[0], steps[0] + 3))
    assert [ids["step"] for _, ids in collectives] == steps
    cycles = [ids["cycle"] for _, ids in collectives]
    assert cycles == sorted(set(cycles))
    # Every span of the step carries its id, on both threads.
    for name in ("hvd.fuse", "hvd.enqueue", "hvd.wait", "hvd.unfuse",
                 "hvd.optimizer_update"):
        assert [ids["step"] for _, ids in _by_name(events, name)] == steps
    assert _by_name(events, "hvd.tree_unflatten") == []
    busy = [ids for _, ids in _by_name(events, "hvd.negotiate")
            if ids["requests"]]
    assert [ids["step"] for ids in busy] == steps
    assert [ids["cycle"] for ids in busy] == cycles
    programs = {ids["program"] for _, ids in
                _by_name(events, "hvd.program_call")}
    assert programs == {"hvd_tree_flatten", "hvd_local_allreduce",
                        "hvd_optimizer_update"}


def test_dispatcher_thread_records_dispatch_wait(report):
    # Four pipelined steps: one before the trace and three in it.
    assert report["pipelined"]["dispatch_wait"]["count"] == 4
    assert report["pipelined"]["dispatch_wait"]["ms"] >= 0
    assert "dispatch_wait" not in report["step"]


def test_dispatcher_thread_records_its_cpu(report):
    # cpu.dispatch: one reading a response on the dispatcher thread, CPU
    # seconds, so no more than the stretch's wall time.
    assert report["pipelined"]["cpu.dispatch"]["count"] == 4
    assert 0 <= report["pipelined"]["cpu.dispatch"]["ms"]
    assert "cpu.dispatch" not in report["step"]


def test_a_sleep_inside_update_moves_update_and_not_its_cpu(report):
    slept, step = report["slept"], report["step"]
    assert slept["update"]["count"] == slept["cpu.update"]["count"] == 1
    assert slept["update"]["ms"] >= 50
    assert slept["enqueue"]["ms"] >= 50           # where the sleep stood
    assert slept["cpu.update"]["ms"] <= slept["update"]["ms"] - 40
    # CPU seconds never pass the wall time of the same stretch.
    assert 0 <= step["cpu.update"]["ms"] <= step["update"]["ms"] + 0.005


def _assert_chain_has_no_hole(rows, dispatcher):
    for row in rows:
        stamps = row["stamps"]
        assert all(t is not None for t in stamps), row
        # add <= announced <= agreed <= dispatched <= callback
        assert stamps == sorted(stamps), row
        assert row["counts"]["queue_wait"] == 1
        assert row["counts"]["negotiate_wait"] == 1
        assert row["counts"].get("dispatch_wait", 0) == dispatcher
        assert row["counts"]["collective"] == row["counts"]["unfuse"] == 1
    gaps = [1e3 * (row["stamps"][-1] - row["stamps"][0]) - row["parts_ms"]
            for row in rows]
    # The six phases are disjoint stretches between add and the callback
    # (totals are rounded to a microsecond each) ...
    assert min(gaps) > -0.01, gaps
    # ... and leave no hole: within 2 ms of the test's own clock.  The
    # median, since one thread switch under load costs more than that.
    assert sorted(gaps)[len(gaps) // 2] < 2.0, gaps


def test_one_tensor_one_rank_has_no_hole_from_add_to_callback(report):
    _assert_chain_has_no_hole(report["chain"], dispatcher=0)


@pytest.mark.parametrize("mode", ["trace_inline", "trace_pipelined"])
def test_agreeing_round_says_how_many_tensors_it_agreed_on(report, mode):
    rounds = [ids for _, ids in _by_name(report[mode], "hvd.negotiate")]
    busy = [ids for ids in rounds if ids["requests"]]
    assert [ids["agreed"] for ids in busy] == [1, 1, 1]
    assert all("agreed" not in ids for ids in rounds if not ids["requests"])
    # One rank has no frame to block on.
    assert _by_name(report[mode], "hvd.negotiate_recv") == []



@pytest.mark.timeout(300)
def test_two_ranks_stamp_queue_wait_and_dispatch_wait():
    out = run_distributed(2, """
import jax, jax.numpy as jnp, optax
from horovod_tpu.core.timeline import phase_stats

params = {"w": jnp.ones((4, 8)), "b": jnp.zeros((8,))}
grads = {"w": jnp.full((4, 8), float(rank + 1)), "b": jnp.ones((8,))}
dopt = hvd.DistributedOptimizer(optax.sgd(0.1))
state = dopt.init(params)
for _ in range(2):
    updates, state = dopt.update(grads, state, params)
before = phase_stats.snapshot()
for _ in range(3):
    updates, state = dopt.update(grads, state, params)
assert abs(float(updates["w"][0, 0]) + 0.1 * 1.5) < 1e-6, updates["w"]
after = phase_stats.snapshot()
d = {k: after[k]["count"] - before[k]["count"] for k in after}
# A step's one tensor waits once in each queue; fuse runs on the caller
# (the tree's flatten) and on the dispatcher (the bucket's staging).
assert d["queue_wait"] == d["dispatch_wait"] == 3, d
assert d["fuse"] == 6 and d["collective"] == d["unfuse"] == 3, d
assert d["update"] == d["wait"] == d["optimizer_update"] == 3, d
assert d.get("tree_unflatten", 0) == 0, d
assert d["negotiate"] >= 3, d
for k in ("queue_wait", "dispatch_wait"):
    assert after[k]["total_ms"] >= before[k]["total_ms"]
print("SPANS_NP2_OK", rank, flush=True)
""", timeout=240, extra_env={
        "HOROVOD_DATA_PLANE": "xla",
        "HOROVOD_JAX_COORDINATOR": f"127.0.0.1:{reserve_port()}"})
    for r, o in enumerate(out):
        assert f"SPANS_NP2_OK {r}" in o


# Two ranks, one job (ISSUE 52): five plain steps, five with rank 1 asleep
# 50 ms in TensorQueue.add, five with rank 0 asleep there, five single
# tensors with the test's clock beside the program's stamps, two steps
# under the profiler, and the totals after shutdown.
TWO_RANKS = CHAIN + """
import glob, json, tempfile, time
import jax, jax.numpy as jnp, optax
from horovod_tpu.common import faults
from horovod_tpu.core.state import global_state
from horovod_tpu.core.timeline import phase_stats

params = {"w": jnp.ones((4, 8)), "b": jnp.zeros((8,))}
grads = {"w": jnp.full((4, 8), float(rank + 1)), "b": jnp.ones((8,))}
dopt = hvd.DistributedOptimizer(optax.sgd(0.1))
state = dopt.init(params)

def step():
    global state
    updates, state = dopt.update(grads, state, params)
    jax.block_until_ready(updates)

def stretch(n):
    rows = []
    for _ in range(n):
        before, t0 = phase_stats.snapshot(), time.monotonic()
        step()
        wall, after = time.monotonic() - t0, phase_stats.snapshot()
        zero = {"count": 0, "total_ms": 0.0}
        row = {k: [v["count"] - before.get(k, zero)["count"],
                   v["total_ms"] - before.get(k, zero)["total_ms"]]
               for k, v in after.items()}
        row["wall_ms"] = 1e3 * wall
        row["snapshot"] = {k: v["total_ms"] for k, v in after.items()}
        rows.append(row)
    hvd.barrier()
    return rows

for _ in range(3):
    step()
hvd.barrier()
report = {"plain": stretch(5)}
for late in (1, 0):
    faults.configure("enqueue.collective:rank=%d:action=delay_ms,50" % late)
    report["late%d" % late] = stretch(5)
faults.reset()
report["chain"] = chain(5)
hvd.barrier()

d = tempfile.mkdtemp()
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
jax.profiler.start_trace(d, profiler_options=options)
step(); step()
jax.profiler.stop_trace()
path = glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0]
report["trace"] = [
    [line.name, e.name, dict(e.stats), e.start_ns, e.start_ns + e.duration_ns]
    for plane in jax.profiler.ProfileData.from_file(path).planes
    for line in plane.lines for e in line.events
    if e.name in ("hvd.negotiate", "hvd.negotiate_recv")]

hvd.shutdown()                      # the loop has made its last round
after = phase_stats.snapshot()
report["rounds"] = after["negotiate"]["count"] \
    + after["negotiate_idle"]["count"]
report["cycle_count"] = global_state().cycle_count
report["busy_rounds"] = after["negotiate"]["count"]
report["cpu_loop_readings"] = after["cpu.loop"]["count"]
print("REPORT " + json.dumps(report), flush=True)
"""


@pytest.fixture(scope="module")
def two_ranks():
    out = run_distributed(2, TWO_RANKS, timeout=300, extra_env={
        "HOROVOD_DATA_PLANE": "xla",
        "HOROVOD_JAX_COORDINATOR": f"127.0.0.1:{reserve_port()}"})
    return [json.loads([x for x in o.splitlines()
                        if x.startswith("REPORT ")][-1][len("REPORT "):])
            for o in out]


def _median_ms(rows, name, per_tensor=False):
    return median_high([ms / max(n, 1) if per_tensor else ms
                    for n, ms in (row[name] for row in rows)])


def _busy_rounds_a_tensor(rows):
    """The median step's rounds that took a request or brought a response
    (``negotiate``'s count), by the tensors that were agreed on in it."""
    return median_high([row["negotiate"][0] / row["negotiate_wait"][0]
                    for row in rows])


@pytest.mark.timeout(400)
@pytest.mark.parametrize("late", [1, 0])
def test_the_rank_that_waits_reads_the_wait_and_the_late_rank_none(
        two_ranks, late):
    early = 1 - late
    waited, slept = two_ranks[early]["late%d" % late], \
        two_ranks[late]["late%d" % late]
    # The early rank hands its tensor over 50 ms before the late rank does
    # (the sleep guarantees them, less what the two callers start apart):
    # it waits for a round to take it and then for the late rank's
    # announcement, and however much longer a loaded host takes.
    held = median_high([row["queue_wait"][1] + row["negotiate_wait"][1]
                    for row in waited])
    assert held >= 40, held
    # ... and its rounds sit blocked on the late rank's frames (rank 0) or
    # on the coordinator's reply (rank 1) for most of it: one receive a
    # round, so never more than the steps themselves took.
    assert 20 <= _median_ms(waited, "negotiate_recv") \
        <= median_high([row["wall_ms"] for row in waited])
    # How long is the host's; how many rounds is the protocol's.  A step
    # has one tensor, which the early rank announces in one round with work
    # and hears agreed in another, and the late rank in the round that
    # takes it.
    assert _busy_rounds_a_tensor(waited) == 2
    assert _busy_rounds_a_tensor(slept) == 1
    # ... so the late rank's wait, and its receives, which are counted
    # only while it has a tensor in flight, are never near what the early
    # rank reads.
    for name in ("negotiate_wait", "negotiate_recv"):
        late_ms = _median_ms(slept, name, per_tensor=name == "negotiate_wait")
        assert late_ms < 0.5 * _median_ms(waited, name), (name, late_ms)
    # The sleep is wall time of update and none of its CPU.
    assert _median_ms(slept, "update") >= 50
    assert _median_ms(slept, "cpu.update") \
        <= _median_ms(slept, "update") - 40


@pytest.mark.timeout(400)
@pytest.mark.parametrize("rank", [0, 1])
def test_a_blocked_receive_lies_inside_its_round(two_ranks, rank):
    for name in ("plain", "late0", "late1"):
        for row in two_ranks[rank][name]:
            snap = row["snapshot"]
            # negotiate_recv counts every round this rank had a tensor in
            # flight in, negotiate the rounds with a request or a response
            # and negotiate_idle the others: a receive is inside one of
            # the two (the issue's ``negotiate >= negotiate_recv`` cannot
            # hold: the rounds a tensor waits through are idle ones).
            assert snap["negotiate_recv"] <= snap["negotiate"] \
                + snap["negotiate_idle"] + 0.005, (name, snap)
            # CPU seconds against the wall time of the same stretch, a
            # thread each (a count a round or a response may land a
            # reading from before the stretch: 1 ms).
            for cpu in ("cpu.loop", "cpu.dispatch", "cpu.update"):
                assert 0 <= row[cpu][1] <= row["wall_ms"] + 1, (name, cpu)
            assert row["cpu.update"][1] <= row["update"][1] + 0.005


@pytest.mark.timeout(400)
@pytest.mark.parametrize("rank", [0, 1])
def test_rounds_are_negotiate_and_negotiate_idle(two_ranks, rank):
    r = two_ranks[rank]
    # Every one of the job's 25 steps takes a round with work or two, and
    # the late stretches' sleeps are idle rounds.
    assert r["rounds"] == r["cycle_count"] > r["busy_rounds"] >= 25
    # The loop hands its idle rounds and its CPU clock on after a round that
    # had work and when it ends: a reading a busy round, and the last.
    assert r["busy_rounds"] <= r["cpu_loop_readings"] <= r["busy_rounds"] + 1
    # A step of the late stretches spans the 50 ms of idle rounds: after
    # each of its rounds with work 1, 2 and 4 ms and then the cap's 5
    # (ISSUE 54; a round a millisecond before it).  So eleven or twelve a
    # step on a quiet host, and on any host no more than one for every 5 ms
    # of the steps' own wall time beside the three of the way up after each
    # round with work (two a step at most, and as many again to spare): a
    # loaded host makes fewer, never more.
    for name in ("plain", "late0", "late1"):
        idle = sum(row["negotiate_idle"][0] for row in r[name])
        wall_ms = sum(row["wall_ms"] for row in r[name])
        assert idle <= 12 * len(r[name]) + wall_ms / CAP, (name, r[name])


@pytest.mark.timeout(400)
def test_one_tensor_two_ranks_has_no_hole_from_add_to_callback(two_ranks):
    for r in two_ranks:
        _assert_chain_has_no_hole(r["chain"], dispatcher=1)


@pytest.mark.timeout(400)
@pytest.mark.parametrize("rank", [0, 1])
def test_trace_holds_the_blocked_receive_inside_its_round(two_ranks, rank):
    events = two_ranks[rank]["trace"]
    rounds = {ids["cycle"]: (line, start, end)
              for line, name, ids, start, end in events
              if name == "hvd.negotiate"}
    recvs = [(line, ids, start, end) for line, name, ids, start, end in events
             if name == "hvd.negotiate_recv"]
    # The trace's end may cut the last round open: its receive is there and
    # its own span is not.  The last round's, and no other.
    cycles = [ids["cycle"] for _, ids, _, _ in recvs]
    assert {c for c in cycles if c not in rounds} <= {max(cycles)}, cycles
    assert any(c in rounds for c in cycles)
    for line, ids, start, end in recvs:
        assert ids["peer"] == 1 - rank            # two ranks: the other one
        if ids["cycle"] not in rounds:
            continue
        round_line, round_start, round_end = rounds[ids["cycle"]]
        assert line == round_line == "horovod-backgro"
        assert round_start <= start <= end <= round_end
    agreed = [ids["agreed"] for _, name, ids, _, _ in events
              if name == "hvd.negotiate" and "agreed" in ids]
    assert agreed == [1, 1]



# ---------------------------------------------------------------------------
# the primitive alone, in this process
# ---------------------------------------------------------------------------


@pytest.fixture
def stats(monkeypatch):
    from horovod_tpu.core import timeline

    fresh = timeline.PhaseStats()
    monkeypatch.setattr(timeline, "phase_stats", fresh)
    return fresh


def test_phase_records_when_its_body_raises(stats):
    from horovod_tpu.core.timeline import current_ids, phase

    with pytest.raises(ValueError):
        with phase("wait", step=3):
            raise ValueError("boom")
    assert stats.snapshot()["wait"]["count"] == 1
    assert current_ids() == {}


def test_phase_without_jax_is_the_accumulator_alone(stats, monkeypatch):
    from horovod_tpu.core import timeline

    monkeypatch.setattr(timeline, "_annotation", None)
    monkeypatch.setitem(sys.modules, "jax", None)       # import jax raises
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    with timeline.phase("fuse", step=1) as span:
        assert span._span is None
    assert timeline._annotation is False
    assert stats.snapshot()["fuse"]["count"] == 1


def test_phase_never_imports_jax_itself(stats, monkeypatch):
    from horovod_tpu.core import timeline

    monkeypatch.setattr(timeline, "_annotation", None)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    with timeline.phase("fuse"):
        pass
    assert "jax" not in sys.modules and timeline._annotation is None
    assert stats.snapshot()["fuse"]["count"] == 1


def test_spans_inherit_ids_per_thread_and_drop_none(stats):
    from horovod_tpu.core.timeline import current_ids, phase, span_ids

    seen = {}

    def other():
        seen["thread"] = current_ids()

    with phase("update", step=7):
        with phase("fuse", cycle=None):
            seen["nested"] = current_ids()
        with span_ids(cycle=2), phase("collective"):
            seen["scoped"] = current_ids()
        t = threading.Thread(target=other)
        t.start()
        t.join()
        seen["after"] = current_ids()
    assert seen == {"nested": {"step": 7}, "scoped": {"step": 7, "cycle": 2},
                    "thread": {}, "after": {"step": 7}}
    assert current_ids() == {}


def test_record_false_and_n_steer_the_accumulator(stats):
    from horovod_tpu.core.timeline import phase

    with phase("negotiate") as idle:
        idle.record = False
    assert "negotiate" not in stats.snapshot()
    with phase("negotiate") as busy:
        busy.n = 5
    assert stats.snapshot()["negotiate"]["count"] == 5
    assert busy.seconds >= 0 and idle.seconds >= 0


def test_program_call_counts_output_arrays(stats):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.core.timeline import program_call

    @jax.jit
    def three(x):
        return x, {"a": x + 1, "b": (x * 2,)}

    out = program_call(three, jnp.ones(2))
    assert float(out[1]["b"][0][0]) == 2.0
    snap = stats.snapshot()["program_call"]
    assert snap["count"] == 3
    # mean_ms is per output buffer.
    assert snap["mean_ms"] == pytest.approx(snap["total_ms"] / 3, abs=1e-3)


def test_tensor_queue_stamps_step_and_queue_wait(stats):
    from horovod_tpu.core.messages import Request
    from horovod_tpu.core.tensor_queue import TensorQueue, TensorTableEntry
    from horovod_tpu.core.timeline import phase

    q = TensorQueue()
    entry, bare = TensorTableEntry("t0"), TensorTableEntry("t1")
    with phase("update", step=11):
        q.add(entry, Request(tensor_name="t0"))
    q.add(bare, Request(tensor_name="t1"))
    q.push_messages([Request(tensor_name="join")])          # no stamp
    popped = q.pop_messages()
    assert [r.tensor_name for r in popped] == ["join", "t0", "t1"]
    assert (entry.step, bare.step) == (11, None)
    assert stats.snapshot()["queue_wait"]["count"] == 2
    # Re-queued requests have waited already.
    q.push_messages(popped)
    q.pop_messages()
    assert stats.snapshot()["queue_wait"]["count"] == 2


def test_requeued_request_keeps_its_first_announcement(stats):
    from horovod_tpu.core.messages import Request
    from horovod_tpu.core.tensor_queue import TensorQueue, TensorTableEntry

    q = TensorQueue()
    entry = TensorTableEntry("t0")
    assert entry.announced_at is None
    q.add(entry, Request(tensor_name="t0"))
    popped = q.pop_messages()
    first = entry.announced_at
    assert first is not None
    q.push_messages(popped)
    assert q.pop_messages() == popped
    assert entry.announced_at == first
    # A request whose entry has left the table stamps nothing.
    q.remove("t0")
    q.push_messages(popped)
    q.pop_messages()
    assert entry.announced_at == first


def test_annotate_reaches_an_open_span_and_needs_none(stats, monkeypatch):
    from horovod_tpu.core import timeline

    with timeline.phase("negotiate") as span:
        span.annotate(agreed=3)                 # no profiler: nothing to do
    seen = []

    class Annotation:
        def __init__(self, name, **ids):
            seen.append((name, ids))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

        def set_metadata(self, **ids):
            seen.append(ids)

    monkeypatch.setattr(timeline, "_annotation", Annotation)
    with timeline.phase("negotiate", cycle=4) as span:
        span.annotate(agreed=3)
    assert seen == [("hvd.negotiate", {"cycle": 4}), {"agreed": 3}]


def test_phases_are_the_catalogued_and_documented_names():
    import os

    from horovod_tpu.core import metrics
    from horovod_tpu.core.timeline import PHASES

    assert len(set(PHASES)) == len(PHASES) == 20
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "observability.md")).read()
    section = doc.split("## Reading a step on the profiler's clock")[1] \
        .split("\n## ")[0]
    for name in PHASES:
        assert metrics.CATALOG[name][0] == "stat"
        assert f"`{name}`" in section, name
