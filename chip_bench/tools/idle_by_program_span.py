"""Where the device idled, by the program's own spans: for every ``hvd.*``
span of a kept trace (``run.py --keep-trace <dir>``) and every span of the
benchmark, per host thread, the span's time per step and the device's idle
time that fell inside it.  Read by no metric; for ``PERF.md``.

    python3 chip_bench/tools/idle_by_program_span.py <file.xplane.pb> [step]

The stretch is the benchmark's own (``Window.between_reads``), the device's
ops and the interval arithmetic are ``trace_reduce``'s.  Spans nest
(``program_call`` in ``fuse`` in ``update``), so a column does not add up:
each row is that span name alone.  With ``step``, the spans that carry
``step=<step>`` are listed in time order with their thread and identifiers.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chip_bench import steps, trace_reduce  # noqa: E402

PREFIX = "hvd."


def host_spans(path):
    """[(thread line, name, start_s, end_s, {id: value})] of the program's
    spans and the benchmark's, from the host plane."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX) or e.name in steps.SPANS:
                    out.append((line.name, e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                dict(e.stats)))
    return out


def table(path):
    spans = host_spans(path)
    names = sorted({n for _, n, _, _, _ in spans})
    window = trace_reduce.Window.between_reads(
        trace_reduce.Trace.from_file(path, names))
    if window is None:
        raise SystemExit("too few loss_read spans for a steady stretch")
    lo, hi, n = window.lo, window.hi, window.steps
    gaps = trace_reduce.subtract([(lo, hi)], window.busy)
    idle = trace_reduce.total(gaps)
    rows = []
    for thread, name in sorted({(t, n_) for t, n_, _, _, _ in spans}):
        mine = [(max(s, lo), min(e, hi)) for t, n_, s, e, _ in spans
                if (t, n_) == (thread, name) and min(e, hi) > max(s, lo)]
        if mine:
            merged = trace_reduce.union(mine)
            rows.append((thread, name, len(mine) / n,
                         1e3 * trace_reduce.total(merged) / n,
                         1e3 * trace_reduce.overlap(gaps, merged) / n))
    print(f"stretch {1e3 * (hi - lo) / n:.3f} ms/step over {n} steps; device "
          f"busy {1e3 * window.busy_s() / n:.3f}, idle {1e3 * idle / n:.3f} "
          "ms/step")
    print(f"{'thread':<18}{'span':<24}{'per step':>9}{'ms/step':>10}"
          f"{'idle inside':>13}{'of idle':>9}")
    for thread, name, count, ms, idle_ms in rows:
        print(f"{thread:<18}{name:<24}{count:>9.2f}{ms:>10.3f}"
              f"{idle_ms:>13.3f}{100 * idle_ms * n / 1e3 / idle:>8.1f}%")
    return spans


def one_step(spans, step):
    mine = sorted((s for s in spans if s[4].get("step") == step),
                  key=lambda s: s[2])
    if not mine:
        raise SystemExit(f"no span carries step={step}")
    t0 = mine[0][2]
    for thread, name, s, e, ids in mine:
        print(f"{1e3 * (s - t0):>9.3f} ms +{1e3 * (e - s):>8.3f}  "
              f"{thread:<18}{name:<22}{ids}")


def main():
    spans = table(sys.argv[1])
    if len(sys.argv) > 2:
        one_step(spans, int(sys.argv[2]))


if __name__ == "__main__":
    main()
