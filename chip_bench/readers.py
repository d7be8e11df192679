"""The reductions a per-layer metric's file can name.

A metric is ``metrics/<name>.json``: ``{"readers": [{"reduction": ..., ...},
...], "ranks": "max" | "mean" | "rank0"}``.  Each rank's worker evaluates the
readers in order on what it recorded and keeps the first that finds
something to read; a metric no reader can read on any rank is left out of the
result line.  ``ranks`` says how the ranks' values become one.

What a reader sees (``ctx``): ``fields`` (numbers taken once: set-up times,
cache counts, memory), ``deltas`` (counters differenced over the measured
window: ``phase_ms.<phase>``, ``xla_ops.<op>``, ``host_ring_bytes``,
``compiles``), ``steps`` (steps in that window), ``window`` (the traced
stretch as a ``trace_reduce.Window``, or None), ``flops_per_step`` and
``peak_flops`` of this rank's chip.
"""

from __future__ import annotations

import fnmatch


def _field(p, ctx):
    v = ctx["fields"].get(p["field"])
    return None if v is None else v * p.get("scale", 1.0)


def _ratio(p, ctx):
    num, den = ctx["fields"].get(p["num"]), ctx["fields"].get(p["den"])
    if num is None or not den:
        return None
    return num / den * p.get("scale", 1.0)


def _delta_per_step(p, ctx):
    names = [k for pat in p["counters"] for k in ctx["deltas"]
             if fnmatch.fnmatchcase(k, pat)]
    if not names or not ctx["steps"]:
        return None
    return sum(ctx["deltas"][k] for k in set(names)) / ctx["steps"] \
        * p.get("scale", 1.0)


def _traced(fn):
    def reader(p, ctx):
        w = ctx["window"]
        return None if w is None or not w.ops else fn(p, ctx, w)
    return reader


@_traced
def _idle_pct(p, ctx, w):
    return 100.0 * (1.0 - w.busy_s() / w.seconds)


@_traced
def _busy_mfu_pct(p, ctx, w):
    return 100.0 * ctx["flops_per_step"] * w.steps / w.busy_s() \
        / ctx["peak_flops"]


@_traced
def _op_ms_per_step(p, ctx, w):
    return 1e3 * w.op_s(p["pattern"]) / w.steps


@_traced
def _op_count_per_step(p, ctx, w):
    return w.op_count(p["pattern"]) / w.steps


@_traced
def _exposed_ms_per_step(p, ctx, w):
    return 1e3 * w.exposed_s(p["pattern"]) / w.steps


@_traced
def _idle_in_span_ms_per_step(p, ctx, w):
    return 1e3 * w.idle_by_span().get(p["span"], 0.0) / w.steps


REDUCTIONS = {
    "field": _field,
    "ratio": _ratio,
    "delta_per_step": _delta_per_step,
    "trace_idle_pct": _idle_pct,
    "trace_busy_mfu_pct": _busy_mfu_pct,
    "trace_op_ms_per_step": _op_ms_per_step,
    "trace_op_count_per_step": _op_count_per_step,
    "trace_exposed_ms_per_step": _exposed_ms_per_step,
    "trace_idle_in_span_ms_per_step": _idle_in_span_ms_per_step,
}


def read(metric, ctx):
    """This rank's value of one metric, or None where there is nothing to
    read."""
    for p in metric["readers"]:
        if p.get("min_world", 1) > ctx["world"]:
            continue
        value = REDUCTIONS[p["reduction"]](p, ctx)
        if value is not None:
            return float(value)
    return None


def across_ranks(metric, values):
    """One value from the ranks' values, given in rank order."""
    how = metric.get("ranks", "max")
    if how == "rank0":
        return values[0]
    values = [v for v in values if v is not None]
    if not values:
        return None
    if how == "mean":
        return sum(values) / len(values)
    if how == "max":
        return max(values)
    raise ValueError(f"unknown ranks rule {how!r}")
