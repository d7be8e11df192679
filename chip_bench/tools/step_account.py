"""The account of one traced stretch by block: for every ``hvd.<block>`` scope
of a kept trace (``run.py --keep-trace <dir>``, or a trace of your own job
that reads its loss under a ``loss_read`` span) and each direction, the
device's milliseconds a step, the share of its busy time, the operations a
step, XLA's own count of their floating-point operations and bytes and the
rates they make of the row's time; then what no scope covers, by the largest
``tf_op`` prefixes; then the check that the rows add up to the op line.  Read
by no metric yet; for ``PERF.md`` and for whoever asks where a step's time
goes.

    python3 chip_bench/tools/step_account.py <file.xplane.pb> [--ops N]
        [--read-span NAME] [--skip N]

The stretch is the benchmark's own (``Window.between_reads``), the rule is
``chip_bench/scopes.py``'s: the innermost scope names the row, and an
instruction XLA made itself, which carries no name, is adopted by what it
calls, its nearest user or its nearest operand in the HLO the trace holds
(column ``adopted``: how much of the row came that way).  A row's TFLOP/s
and GB/s are XLA's counts over the row's time; a pallas kernel is a custom
call of which XLA counts nothing (column ``uncounted``: the time of what
went uncounted).  A fusion is one operation under one name: where XLA fused
another block's operations into a row's fusions, the line ``fused with`` says
which, and in how much of the row's time (an upper bound of what that block
does there).  ``--ops N`` lists each row's N largest
operations by name (a scope's kernels beside the small operations around
them).  Exits 1 if an ``hvd.`` segment of the trace is not in
``timeline.SCOPES``.
"""

from __future__ import annotations

import argparse
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chip_bench import scopes, trace_reduce  # noqa: E402


def stretch(path, read_span="loss_read", skip=2):
    window = trace_reduce.Window.between_reads(
        trace_reduce.Trace.from_file(path, (read_span,)), read_span, skip)
    if window is None:
        raise SystemExit(f"too few {read_span} spans for a steady stretch")
    return window


def table(path, window, top_ops=0, out=None):
    """Print the account; returns (rows, seconds the rows hold, seconds of
    the op line in the stretch)."""
    ops = scopes.device_ops(path)
    rows = scopes.account(ops, window.lo, window.hi)
    n, busy = window.steps, window.busy_s()
    line_s = sum(e - s for _, s, e in window.ops)
    rows_s = sum(row.seconds for row in rows.values())
    say = lambda text="": print(text, file=out)  # noqa: E731
    say(f"stretch {1e3 * window.seconds / n:.3f} ms/step over {n} steps; "
        f"device busy {1e3 * busy / n:.3f} ms/step "
        f"({100 * (1 - busy / window.seconds):.3f}% idle)")
    say(f"{'block':<16}{'dir':<5}{'ms/step':>10}{'of busy':>9}{'ops/step':>10}"
        f"{'GFLOP/step':>12}{'GB/step':>10}{'TFLOP/s':>9}{'GB/s':>9}"
        f"{'adopted':>9}{'uncounted':>10}  (ms/step)")
    ordered = sorted(rows.items(), key=lambda kv: (kv[0][0] == scopes.UNSCOPED,
                                                   -kv[1].seconds))
    for (name, way), row in ordered:
        s = row.seconds
        say(f"{name:<16}{way:<5}{1e3 * s / n:>10.3f}{100 * s / busy:>8.2f}%"
            f"{row.ops / n:>10.1f}{row.flops / n / 1e9:>12.2f}"
            f"{row.bytes_accessed / n / 1e9:>10.3f}"
            f"{row.flops / s / 1e12 if s else 0:>9.2f}"
            f"{row.bytes_accessed / s / 1e9 if s else 0:>9.1f}"
            f"{1e3 * row.adopted_s / n:>9.3f}{1e3 * row.uncounted_s / n:>10.3f}")
        if row.also:
            say("      fused with: " + ", ".join(
                f"{other} in {1e3 * also_s / n:.3f}"
                for other, also_s in row.also.most_common(4)))
        for op, op_s in row.by_name.most_common(top_ops):
            say(f"    {1e3 * op_s / n:>10.3f}  {op}")
    unscoped = sum(row.seconds for (name, _), row in rows.items()
                   if name == scopes.UNSCOPED)
    say(f"unscoped {1e3 * unscoped / n:.3f} ms/step, "
        f"{100 * unscoped / busy:.2f}% of busy; its largest tf_op prefixes:")
    prefixes = sum((row.by_prefix for (name, _), row in rows.items()
                    if name == scopes.UNSCOPED), collections.Counter())
    for prefix, s in prefixes.most_common(5):
        say(f"    {1e3 * s / n:>10.3f}  {prefix}")
    say(f"rows add up to {1e3 * rows_s / n:.6f} ms/step, the op line holds "
        f"{1e3 * line_s / n:.6f}: "
        + ("equal" if abs(rows_s - line_s) <= 1e-9 * max(line_s, 1e-9)
           else "NOT EQUAL"))
    return rows, rows_s, line_s


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("xplane")
    p.add_argument("--ops", type=int, default=0,
                   help="list each row's N largest operations by name")
    p.add_argument("--read-span", default="loss_read")
    p.add_argument("--skip", type=int, default=2)
    args = p.parse_args(argv)
    window = stretch(args.xplane, args.read_span, args.skip)
    _, rows_s, line_s = table(args.xplane, window, args.ops)

    from horovod_tpu.core.timeline import SCOPES

    unknown = scopes.unknown_scopes(scopes.device_ops(args.xplane), SCOPES)
    if unknown:
        print("scopes not in timeline.SCOPES: " + ", ".join(unknown))
    return 1 if unknown or abs(rows_s - line_s) > 1e-9 * max(line_s, 1e-9) \
        else 0


if __name__ == "__main__":
    sys.exit(main())
