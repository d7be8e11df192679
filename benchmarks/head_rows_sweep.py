"""``kernels/head_rows.py``'s four kernels alone at Ling-3.0-flash-VL's shape,
beside XLA's form of the same work (PERF.md §6, PR 67).

One KDA layer's rows each side of the rule at one sequence of 8192 positions
and 32 heads of 128: the convolution's output ``[1, 8192, 12288]`` and the
input projection's row ``[1, 8192, 20480]`` in bf16, out of which the gate's
kernels read ``q``, ``k`` and ``f`` and the norm's read ``z``.  A line a
block (``tile`` positions by ``cols`` columns a grid step) gives ms a call of
each kernel **by the device's op line** (a profiler trace of ``--iters``
calls, the kernel's events by its name; the host's clock around a call of
half a millisecond reads the dispatch) and the bytes the work has to move, every activation read once and
written once, over that time (``gbs``).  The line ``xla`` gives
``head_rows.gate_reference`` and ``norm_reference`` on the slices, what
``models/kda.py`` ran until PR 67 and runs where ``takes`` refuses, forward
alone and the backward XLA makes of it alone: every operation of the program
on the op line, added up.  Every kernel line holds its outputs and
cotangents against that form's (``errors``: a difference's norm as a share
of the value's) and the sweep exits non-zero above :data:`LIMIT`.

Needs a TPU; ``--seq 256 --heads 4 --blocks 128x256 --interpret`` on the CPU
is a rehearsal of the same code (the kernels in interpret mode, no time).
One JSON object a line; ``--out`` also writes them to a file.  PR 67's rows
are ``benchmarks/results/head_rows_sweep_pr67.jsonl``: its first sweep also took
the sum over a head's lanes both ways (``sums``: ``xlu``, the lane reduction
the kernels keep; ``mxu``, a product with a block of ones over the terms'
bf16 pieces) through a parameter that left the tree when the gate's kernels
read the same and the norm's 4 to 9% slower by it; the rows without
``sums`` are the final tree's.

Run: ``python benchmarks/head_rows_sweep.py [--blocks 512x512 ...]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# A difference's norm as a share of the value's: the two forms round at the
# same points, and one bf16 rounding the other way on every element would be
# 2 ** -9 of the norm.
LIMIT = 2 ** -9
EPS = 1e-6                  # the configuration's ``rms_norm_eps``
KERNELS = ("gate_fwd", "gate_bwd", "norm_fwd", "norm_bwd")
# Bytes an element of ``[s, inner]`` that each has to move: q, k, f in and
# q, k (bf16) and g (fp32) out; those three, dq, dk and dg in and dq, dk, df
# out; o, z in and y out; o, z, dy in and do, dz out.
BYTES = {"gate_fwd": 14, "gate_bwd": 20, "norm_fwd": 6, "norm_bwd": 10}


def op_line_ms(run, iters):
    """``run()`` under the profiler: every operation's ms on the device's op
    line by name, over ``iters``."""
    import jax

    from chip_bench import trace_reduce

    with tempfile.TemporaryDirectory() as kept:
        jax.profiler.start_trace(kept)
        try:
            jax.block_until_ready(run())
        finally:
            jax.profiler.stop_trace()
        trace = trace_reduce.Trace.from_file(
            trace_reduce.find_xplane(kept), ())
    by_name = {}
    for name, start, end in trace.ops:
        by_name[name] = by_name.get(name, 0.0) + (end - start) * 1e3 / iters
    return by_name


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--blocks", nargs="+",
                   default=["256x512", "512x512", "1024x512", "512x256",
                            "256x1024", "512x1024", "128x4096"],
                   help="<positions>x<columns> a grid step")
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--heads", type=int, default=32)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--interpret", action="store_true")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import head_rows as hr
    from horovod_tpu.kernels.kda import LOWER_BOUND

    if not args.interpret and jax.default_backend() != "tpu":
        sys.exit("head_rows_sweep: needs a TPU (or --interpret)")
    out = open(args.out, "w") if args.out else None

    def emit(line):
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()

    s, inner = args.seq, args.heads * 128
    keys = jax.random.split(jax.random.PRNGKey(0), 10)
    bf16, f32 = jnp.bfloat16, jnp.float32
    conv = jax.random.normal(keys[0], (1, s, 3 * inner), bf16)
    row = jax.random.normal(keys[1], (1, s, 5 * inner), bf16)
    a = jnp.repeat(jax.random.uniform(keys[2], (args.heads,), f32, 1.0, 16.0),
                   128)[None]
    dt_bias = jax.random.uniform(keys[3], (1, inner), f32, -6.9, -2.3)
    w = 1.0 + 0.1 * jax.random.normal(keys[4], (1, inner), f32)
    o, dq, dk, dy = (jax.random.normal(key, (1, s, inner), bf16)
                     for key in keys[5:9])
    dg = jax.random.normal(keys[9], (1, s, inner), f32)
    gate_static = dict(f_at=3 * inner, scale=128 ** -0.5, lower=LOWER_BOUND)
    norm_static = dict(z_at=4 * inner, eps=EPS)

    def xla_gate(conv, row, a, dt_bias):
        return hr.gate_reference(
            conv[..., :inner], conv[..., inner:2 * inner],
            row[..., 3 * inner:4 * inner], a, dt_bias,
            scale=gate_static["scale"], lower=LOWER_BOUND)

    def xla_norm(o, row, w):
        return hr.norm_reference(o, row[..., 4 * inner:], w, eps=EPS)

    xla = {
        "gate_fwd": (jax.jit(xla_gate), (conv, row, a, dt_bias)),
        "gate_bwd": (jax.jit(lambda *t: jax.vjp(xla_gate, *t[:4])[1](t[4:])),
                     (conv, row, a, dt_bias, dq, dk, dg)),
        "norm_fwd": (jax.jit(xla_norm), (o, row, w)),
        "norm_bwd": (jax.jit(lambda *t: jax.vjp(xla_norm, *t[:3])[1](t[3])),
                     (o, row, w, dy)),
    }
    want = {name: jax.block_until_ready(fn(*operands))
            for name, (fn, operands) in xla.items()}
    moved = {name: BYTES[name] * s * inner for name in KERNELS}
    line = {"seq": s, "heads": args.heads, "mb": {
        name: moved[name] / 1e6 for name in KERNELS},
        "device": jax.devices()[0].device_kind}
    if not args.interpret:
        ms = {}
        for name, (fn, operands) in xla.items():
            ms[name] = sum(op_line_ms(
                lambda: [fn(*operands) for _ in range(args.iters)],
                args.iters).values())
        emit({**line, "path": "xla", "ms": ms, "gbs": {
            name: moved[name] / ms[name] / 1e6 for name in KERNELS}})

    def share(got, want):
        got, want = (jnp.asarray(t, f32) for t in (got, want))
        return float(jnp.linalg.norm((got - want).ravel())
                     / jnp.linalg.norm(want.ravel()))

    def cut(name, got):
        """A kernel's outputs as the XLA form's: the cotangents of the rows
        and vectors from the kernels' own slices and partial sums."""
        if name == "gate_bwd":
            d_q, d_k, d_f, d_a, d_dt = got
            return (hr._within(jnp.concatenate([d_q, d_k], -1), conv, 0),
                    hr._within(d_f, row, 3 * inner), hr._row_sum(d_a),
                    hr._row_sum(d_dt))
        if name == "norm_bwd":
            d_o, d_z, d_w = got
            return d_o, hr._within(d_z, row, 4 * inner), hr._row_sum(d_w)
        return got

    ok = True
    for block in args.blocks:
        tile, cols = map(int, block.split("x"))
        if s % tile or inner % cols:
            continue
        static = dict(tile=tile, cols=cols, interpret=args.interpret)
        calls = {
            "gate_fwd": lambda: hr._gate_forward(
                conv, row, a, dt_bias, **gate_static, **static),
            "gate_bwd": lambda: hr._gate_backward(
                conv, row, a, dt_bias, dq, dk, dg, **gate_static,
                **static),
            "norm_fwd": lambda: hr._norm_forward(
                o, row, w, **norm_static, **static),
            "norm_bwd": lambda: hr._norm_backward(
                o, row, w, dy, **norm_static, **static),
        }
        errors = {}
        for name, call in calls.items():
            got, wanted = cut(name, call()), want[name]
            if name == "norm_fwd":
                got, wanted = (got,), (wanted,)
            errors[name] = max(map(share, got, wanted))
        inside = max(errors.values()) < LIMIT
        ok = ok and inside
        result = {**line, "path": "kernels", "tile": tile, "cols": cols,
                  "errors": errors, "limit": LIMIT, "ok": inside}
        if not args.interpret:
            by_name = op_line_ms(
                lambda: [call() for call in calls.values()
                         for _ in range(args.iters)], args.iters)
            result["ms"] = {
                name: sum(ms for op, ms in by_name.items()
                          if op.startswith(f"hvd_head_rows_{name}"))
                for name in KERNELS}
            result["gbs"] = {name: moved[name] / result["ms"][name] / 1e6
                             for name in KERNELS}
        emit(result)
    if not ok:
        sys.exit("head_rows_sweep: a kernel's output left XLA's form's by "
                 f"more than {LIMIT}")


if __name__ == "__main__":
    main()
