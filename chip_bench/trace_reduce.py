"""From a profiler trace (``.xplane.pb``) to busy, idle and exposed time.

``jax.profiler.ProfileData`` reads the file; everything after that is interval
arithmetic on (start, end) pairs in seconds, kept here so that every PR
computes the same number in the same way.  ``tests/test_trace_reduce.py``
checks it on a small recorded trace against values worked out by hand.

The device's operations are the events of the line ``XLA Ops`` of the plane
``/device:TPU:<n>``; the benchmark's host spans are ``TraceAnnotation`` events
on the host plane's thread lines.  The profiler puts both on one clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# What XLA calls a collective on the op line, sync or async (-start/-done).
COLLECTIVE = (r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
              r"collective-permute|collective-broadcast|ragged-all-to-all)")
OUTSIDE = "outside_the_benchmarks_spans"


def op_name(event_name):
    """``fusion.13`` from the op line's ``%fusion.13 = (f32[256]...) fusion(
    ...)``: the profiler names a TPU operation by its whole HLO text."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


class Trace:
    """``ops``: [(name, start_s, end_s)] of one chip, in start order;
    ``spans``: [(name, start_s, end_s)] of the wanted host spans."""

    def __init__(self, ops, spans):
        self.ops = sorted(ops, key=lambda e: e[1])
        self.spans = sorted(spans, key=lambda e: e[1])

    @classmethod
    def from_file(cls, path, span_names, chip=None):
        """Read one chip's op line (the lowest-numbered device plane unless
        ``chip`` is given) and the host spans named in ``span_names``."""
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        planes = {}
        spans = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                planes[int(m.group(1))] = plane
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for e in line.events:
                        if e.name in span_names:
                            spans.append((e.name, e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns) * 1e-9))
        ops = []
        if planes:
            plane = planes[min(planes) if chip is None else chip]
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops = [(op_name(e.name), e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
        return cls(ops, spans)


def union(intervals):
    """Merge (start, end) pairs into disjoint ones, in order."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def subtract(a, b):
    """The part of the disjoint, ordered intervals ``a`` that no interval of
    the disjoint, ordered ``b`` covers."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def overlap(a, b):
    """Seconds in which both of two disjoint interval lists are on."""
    return total(a) - total(subtract(a, b))


class Window:
    """One steady stretch of a trace, ``lo`` to ``hi`` seconds, that holds
    ``steps`` whole steps of device work."""

    def __init__(self, trace, lo, hi, steps):
        self.lo, self.hi, self.steps = lo, hi, steps
        inside = [(n, s, e) for n, s, e in trace.ops
                  if min(e, hi) > max(s, lo)]
        self.ops = [(n, max(s, lo), min(e, hi)) for n, s, e in inside]
        # An operation cut by the window's edge counts where it ends.
        self._ends_here = [e <= hi for _, _, e in inside]
        self.spans = [(n, max(s, lo), min(e, hi)) for n, s, e in trace.spans
                      if min(e, hi) > max(s, lo)]
        self.busy = union((s, e) for _, s, e in self.ops)

    @classmethod
    def between_reads(cls, trace, read_span="loss_read", skip=2):
        """From the end of the ``skip``-th ``loss_read`` to the end of the
        last.  A read returns when its step has finished on the device, and
        the device works on the steps in order, so between two such ends lies
        exactly the work of the steps read in between; the first reads after
        the profiler starts are left out because the pipeline refills
        there."""
        ends = [e for n, _, e in trace.spans if n == read_span]
        if len(ends) < skip + 2:
            return None
        return cls(trace, ends[skip - 1], ends[-1], len(ends) - skip)

    @property
    def seconds(self):
        return self.hi - self.lo

    def busy_s(self):
        return total(self.busy)

    def matching(self, pattern):
        rx = re.compile(pattern)
        return [(n, s, e) for n, s, e in self.ops if rx.search(n)]

    def op_s(self, pattern):
        return sum(e - s for _, s, e in self.matching(pattern))

    def op_count(self, pattern):
        rx = re.compile(pattern)
        return sum(1 for (n, _, _), here in zip(self.ops, self._ends_here)
                   if here and rx.search(n))

    def exposed_s(self, pattern):
        """Time in which an operation matching ``pattern`` runs and no other
        operation does."""
        rx = re.compile(pattern)
        hit = union((s, e) for n, s, e in self.ops if rx.search(n))
        rest = union((s, e) for n, s, e in self.ops if not rx.search(n))
        return total(subtract(hit, rest))

    def idle_by_span(self):
        """{span name: seconds the device idled while the host was inside
        that span}, with what no span covers under ``OUTSIDE``."""
        gaps = subtract([(self.lo, self.hi)], self.busy)
        out = {}
        covered = 0.0
        for name in sorted({n for n, _, _ in self.spans}):
            mine = union((s, e) for n, s, e in self.spans if n == name)
            out[name] = overlap(gaps, mine)
            covered += out[name]
        # Spans of one thread do not overlap; nested or parallel ones would
        # make the remainder negative, which is clamped and visible as 0.
        out[OUTSIDE] = max(0.0, total(gaps) - covered)
        return out

    def top_ops(self, n=10):
        by_name = {}
        for name, s, e in self.ops:
            by_name[name] = by_name.get(name, 0.0) + (e - s)
        return sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
