"""The reduction from a trace to busy, idle and exposed time, against values
worked out by hand: first on intervals written out here, then on the small
recorded ``.xplane.pb`` files kept in ``data/`` (see ``data/README.txt`` for
how they were recorded and read by hand)."""

import os

import pytest

from chip_bench import readers
from chip_bench.trace_reduce import (COLLECTIVE, OUTSIDE, Trace, Window,
                                     subtract, union)

MS = 1e-3


def ms(name, start, end):
    return (name, start * MS, end * MS)


# Two steps of a made-up eager loop, times in ms.
#
#   device: |fusion.1 0-10|fusion.2 10-14|all-reduce.1 14-20|
#                                   fusion.3 18-25|   idle 25-30
#           |fusion.1 30-40|all-reduce.1 40-44|idle 44-50|fusion.4 50-52|
#   host:   loss_read 0-1 | grad_step 1-3 | dopt.update 3-27 |
#           apply_updates 27-28 | loss_read 28-31 | grad_step 31-33 |
#           dopt.update 33-47 | (nothing 47-49) | loss_read 49-52
OPS = [ms("fusion.1", 0, 10), ms("fusion.2", 10, 14),
       ms("all-reduce.1", 14, 20), ms("fusion.3", 18, 25),
       ms("fusion.1", 30, 40), ms("all-reduce.1", 40, 44),
       ms("fusion.4", 50, 52)]
SPANS = [ms("loss_read", 0, 1), ms("grad_step", 1, 3),
         ms("dopt.update", 3, 27), ms("apply_updates", 27, 28),
         ms("loss_read", 28, 31), ms("grad_step", 31, 33),
         ms("dopt.update", 33, 47), ms("loss_read", 49, 52)]


def test_interval_arithmetic():
    assert union([(3, 5), (0, 2), (1, 4), (7, 7), (8, 9)]) == [(0, 5), (8, 9)]
    assert subtract([(0, 10)], [(2, 3), (5, 11)]) == [(0, 2), (3, 5)]
    assert subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]
    assert subtract([(1, 2)], [(0, 5)]) == []


def test_window_between_reads_by_hand():
    trace = Trace(OPS, SPANS)
    # Reads end at 1, 31 and 52 ms; skipping the first leaves 1 -> 52 ms and
    # the two steps read in between.
    w = Window.between_reads(trace, skip=1)
    assert (w.lo, w.hi, w.steps) == (pytest.approx(1 * MS),
                                     pytest.approx(52 * MS), 2)
    # Busy: 1-25 (fusion.1 is cut at the window's edge), 30-44, 50-52.
    assert w.busy_s() == pytest.approx((24 + 14 + 2) * MS)
    assert w.seconds == pytest.approx(51 * MS)
    # Idle gaps: 25-30 and 44-50.  25-27 lies in dopt.update, 27-28 in
    # apply_updates, 28-30 in loss_read; 44-47 in dopt.update, 47-49 in no
    # span, 49-50 in loss_read.
    idle = w.idle_by_span()
    assert idle == {"dopt.update": pytest.approx(5 * MS),
                    "apply_updates": pytest.approx(1 * MS),
                    "loss_read": pytest.approx(3 * MS),
                    "grad_step": pytest.approx(0.0),
                    OUTSIDE: pytest.approx(2 * MS)}
    assert sum(idle.values()) == pytest.approx(w.seconds - w.busy_s())
    # Collectives run 14-20 and 40-44 = 10 ms; fusion.3 hides 18-20, so 8 ms
    # are exposed.
    assert w.op_s(COLLECTIVE) == pytest.approx(10 * MS)
    assert w.exposed_s(COLLECTIVE) == pytest.approx(8 * MS)
    assert w.op_count(COLLECTIVE) == 2
    # fusion.4 ends on the window's edge and is counted.
    assert w.op_count(r"^fusion") == 5
    assert w.top_ops(2) == [("fusion.1", pytest.approx(19 * MS)),
                            ("all-reduce.1", pytest.approx(10 * MS))]


def test_readers_over_the_window():
    w = Window.between_reads(Trace(OPS, SPANS), skip=1)
    ctx = {"fields": {"a": 3.0, "hits": 1, "asked": 4},
           "deltas": {"phase_ms.fuse": 6.0, "phase_ms.unfuse": 2.0,
                      "xla_ops.allreduce": 4}, "steps": 4, "world": 1,
           "window": w, "flops_per_step": 1e9, "peak_flops": 1e12}

    def one(**reader):
        return readers.read({"readers": [reader]}, ctx)

    assert one(reduction="field", field="a", scale=2) == 6.0
    assert one(reduction="field", field="missing") is None
    assert one(reduction="ratio", num="hits", den="asked", scale=100) == 25.0
    assert one(reduction="delta_per_step",
               counters=["phase_ms.fuse", "phase_ms.unfuse"]) == 2.0
    assert one(reduction="delta_per_step", counters=["xla_ops.*"]) == 1.0
    assert one(reduction="delta_per_step", counters=["nothing.*"]) is None
    assert one(reduction="trace_idle_pct") == pytest.approx(100 * 11 / 51)
    # 2 steps x 1 GFLOP over 40 ms busy = 50 GFLOP/s of a 1 TFLOP/s peak.
    assert one(reduction="trace_busy_mfu_pct") == pytest.approx(5.0)
    assert one(reduction="trace_exposed_ms_per_step",
               pattern=COLLECTIVE) == pytest.approx(4.0)
    assert one(reduction="trace_exposed_ms_per_step", pattern=COLLECTIVE,
               min_world=2) is None
    assert one(reduction="trace_op_ms_per_step",
               pattern="^fusion") == pytest.approx(16.0)  # 9+4+7+10+2 over 2
    assert one(reduction="trace_op_count_per_step",
               pattern=COLLECTIVE) == 1.0
    assert one(reduction="trace_idle_in_span_ms_per_step",
               span="dopt.update") == pytest.approx(2.5)
    # The first reader that finds something wins; none leaves the metric out.
    both = {"readers": [
        {"reduction": "delta_per_step", "counters": ["nothing.*"]},
        {"reduction": "trace_op_count_per_step", "pattern": COLLECTIVE}]}
    assert readers.read(both, ctx) == 1.0
    assert readers.read(both, dict(ctx, window=None)) is None
    assert readers.across_ranks({"ranks": "max"}, [1.0, None, 3.0]) == 3.0
    assert readers.across_ranks({"ranks": "mean"}, [1.0, None, 3.0]) == 2.0
    assert readers.across_ranks({"ranks": "rank0"}, [None, 3.0]) is None
    assert readers.across_ranks({}, [None, None]) is None


def test_too_few_reads_give_no_window():
    assert Window.between_reads(Trace(OPS, SPANS[:5]), skip=2) is None


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NS = 1e-9


def test_recorded_one_chip_trace_by_hand():
    """``data/small_np1.xplane.pb``: five steps of ``tools/
    record_small_trace.py`` on one TPU v5e chip (PR 23).  Each step is six
    operations back to back (copy-start, copy-done, three matmul fusions, one
    reduce) of about 373 us; the host sleeps 3 ms in ``dopt.update``.  The
    values below were added up by hand from ``tools/dump_trace.py``'s listing
    of the file (``data/README.txt`` has the listing's relevant lines)."""
    from chip_bench import steps

    trace = Trace.from_file(os.path.join(DATA, "small_np1.xplane.pb"),
                            steps.SPANS)
    assert len(trace.ops) == 30
    assert [n for n, _, _ in trace.ops[:6]] == [
        "copy-start", "copy-done", "fusion.3", "fusion.2", "fusion.1",
        "convert_reduce_fusion"]
    w = Window.between_reads(trace, skip=2)
    near = lambda ns: pytest.approx(ns * NS, abs=20 * NS)  # noqa: E731
    # From the end of the second loss_read (50360349 + 664700) to the end of
    # the fifth (63551299 + 542060): three steps.
    assert w.steps == 3
    assert w.lo == near(51_025_049) and w.hi == near(64_093_359)
    assert w.seconds == near(13_068_310)
    # Busy: the durations of steps 3-5's operations, which do not overlap:
    # 372,823 + 373,047 + 372,852.
    assert w.busy_s() == near(1_118_722)
    # Idle 11,949,588 ns of 13,068,310: 91.44%.
    ctx = {"window": w, "world": 1, "fields": {}, "deltas": {}, "steps": 0}
    assert readers.read({"readers": [{"reduction": "trace_idle_pct"}]},
                        ctx) == pytest.approx(91.4395, abs=1e-3)
    idle = w.idle_by_span()
    # The device never runs during a loss_read: their three durations.
    assert idle["loss_read"] == near(578_340 + 581_880 + 542_060)
    # Each dopt.update less the tail of the step still running at its start:
    # 3,120,957 + 3,162,515 + 3,194,471.
    assert idle["dopt.update"] == near(9_477_943)
    # Each grad_step less the head of the step launched inside it:
    # 143,680 + 212,038 + 287,497.
    assert idle["grad_step"] == near(643_215)
    # The idle stretches between spans: 33,070 + 6,760 + 35,640 + 4,150 +
    # 42,970 + 3,560.
    assert idle[OUTSIDE] == near(126_150)
    assert w.op_count(r"^fusion") == 9
    assert w.op_s(r"^fusion\.3$") == near(3 * 89_970)
    assert w.exposed_s(COLLECTIVE) == 0.0
    assert w.top_ops(1)[0][0] == "convert_reduce_fusion"


def test_recorded_four_chip_trace_by_hand():
    """``data/small_np4.xplane.pb``: the same loop under ``hvdrun -np 4`` on
    the four-chip host, rank 0's chip (PR 23).  Each step's ``dopt.update``
    allreduces the 8 MB result: a copy, a reshape and a program of convert,
    all-reduce, multiply and convert.  Nothing else runs during an
    all-reduce, so all of its time is exposed."""
    from chip_bench import steps

    trace = Trace.from_file(os.path.join(DATA, "small_np4.xplane.pb"),
                            steps.SPANS)
    assert len(trace.ops) == 60
    w = Window.between_reads(trace, skip=2)
    near = lambda ns: pytest.approx(ns * NS, abs=20 * NS)  # noqa: E731
    # Reads end at 61338866 (2nd) and 84944664 (5th).
    assert w.steps == 3
    assert w.seconds == near(23_605_798)
    # Step 3's matmuls end at 61298576, before the window; inside it lie step
    # 3's allreduce programs (408,855 ns) and the whole of steps 4 and 5
    # (374,012 + 408,845 and 373,802 + 408,263).
    assert w.busy_s() == near(1_973_777)
    # all-reduce: 283,667 + 283,065 + 282,890, each alone on the op line.
    assert w.op_count(COLLECTIVE) == 3
    assert w.op_s(COLLECTIVE) == near(849_622)
    assert w.exposed_s(COLLECTIVE) == near(849_622)
    ctx = {"window": w, "world": 4, "fields": {}, "deltas": {}, "steps": 0}
    exposed = {"readers": [{"reduction": "trace_exposed_ms_per_step",
                            "pattern": COLLECTIVE, "min_world": 2}]}
    assert readers.read(exposed, ctx) == pytest.approx(0.283207, abs=1e-5)
    assert readers.read(exposed, dict(ctx, world=1)) is None
    assert readers.read({"readers": [{"reduction": "trace_idle_pct"}]},
                        ctx) == pytest.approx(91.6386, abs=1e-3)
    assert sum(w.idle_by_span().values()) == near(23_605_798 - 1_973_777)
    assert w.top_ops(1)[0] == ("all-reduce", near(849_622))
