"""The chunked scan's two kernels (``kernels/ssd_scan.py``) alone, on the chip,
at the two shapes the benchmark runs them at: one group of 16 heads of 64
(nemotron-3-super-120b-a12b's share of a mixer) and one group of 64 heads of
64 (granite-4.0-h-micro's whole mixer), one sequence of 8192, state 128.

    python3 benchmarks/ssd_scan_sweep.py [--what time,gradients]
        [--heads 16,64] [--seeds a,b] [--out file]

``time``: the forward kernel and forward + backward, ms a call, beside the
least the chip could take for the bytes a call moves (``x``, ``B``, ``C``
read, ``y`` and the chunks' starting states written forward; those, ``dy``
and the states read and ``dx``, ``dB``, ``dC`` written backward; ``dt`` and
the cumulative sums a head and position both ways), the share that is, and
the same scan through :func:`chunked` in bf16 operands (XLA's form of it,
what ``takes()`` hands a shape it refuses).
``gradients``: ``y`` and the cotangents of all five operands (``x``, ``dt``,
``a``, ``B``, ``C``) of the kernels on bf16 operands against ``chunked()`` in
float32 at the highest matmul precision on the same numbers, each as a share
of float32's norm, a seed at a time; beside them the same readings with a
fault planted in the backward kernel (``state_cotangent_dropped``: every
chunk starts from a zero cotangent of the state, and not the last alone).
Exits non-zero if a reading of the kernels passes ``GRADIENT_RTOL`` or the
fault passes it in no cotangent.  Off the TPU the same code runs the kernels
in interpret mode: a rehearsal at ``--seq 256``, never a time.

Times are wall-clock around ``block_until_ready`` over ``--iters`` calls of
one jitted function, one process, one chip; a time, not a result line.
"""

from __future__ import annotations

import argparse
import functools
import inspect
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# The kernels' y and cotangents from float32's, as a share of float32's norm:
# bf16 operands and bf16 products with fp32 sums (PERF.md section 6, PR 56,
# has the readings).
GRADIENT_RTOL = 2e-2
OPERANDS = ("x", "dt", "a", "b", "c")
HBM_BYTES_PER_S = 819e9          # TPU v5e (chip_bench/peaks.py)


def timed(fn, args, iters):
    import jax

    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - start) / iters


def bytes_moved(s, heads, p, n, chunk=128):
    """(forward, backward) HBM bytes of one call at one group, as
    ``chip_bench/configs/*.py::ssd_scan_cost`` counts them."""
    x, bc = 2 * s * heads * p, 2 * 2 * s * n
    states, small = 4 * (s // chunk) * heads * p * n, 4 * 2 * s * heads
    return (x + bc + x + states + 2 * small,
            (2 * x + bc + states + 2 * small) + (x + bc + 2 * small))


def inputs(seed, s, heads, p, n):
    """A layer's operands at fresh weights' statistics: ``x``, ``B``, ``C``
    unit normal in bf16, ``dt`` a softplus near 0.1, ``A`` in U(1, 16)."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf16 = jnp.bfloat16
    return ((jax.random.normal(ks[0], (1, s, heads, p)).astype(bf16),
             jax.nn.softplus(jax.random.normal(ks[1], (1, s, heads)) - 2),
             -jax.random.uniform(ks[2], (heads,), minval=1.0, maxval=16.0),
             jax.random.normal(ks[3], (1, s, 1, n)).astype(bf16),
             jax.random.normal(ks[4], (1, s, 1, n)).astype(bf16)),
            jax.random.normal(ks[5], (1, s, heads, p)).astype(bf16))


def plant_state_cotangent_dropped():
    """Break the backward kernel, and only that, in this process: its own
    text with the reset of the state's cotangent at every chunk.  Returns
    what undoes it."""
    import jax

    from horovod_tpu.kernels import ssd_scan

    text = inspect.getsource(ssd_scan._bwd_kernel)
    right, wrong = "@pl.when(pl.program_id(2) == 0)", \
        "@pl.when(pl.program_id(2) >= 0)"
    if text.count(right) != 1:
        raise SystemExit("kernels/ssd_scan.py::_bwd_kernel no longer reads "
                         f"{right!r} once: restate the fault")
    scope = dict(vars(ssd_scan))
    exec(text.replace(right, wrong), scope)  # noqa: S102 — the repo's own text
    kept = ssd_scan._bwd_kernel
    ssd_scan._bwd_kernel = scope["_bwd_kernel"]
    jax.clear_caches()

    def undo():
        ssd_scan._bwd_kernel = kept
        jax.clear_caches()

    return undo


def gradients(seed, s, heads, p, n):
    """{"kernels" | "state_cotangent_dropped": {"y" and each operand: the
    share of float32's norm}} for one seed."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import ssd_scan

    if not ssd_scan.takes(s, heads, p, 1, n):
        raise SystemExit("the kernels do not take these sizes: nothing of "
                         "them would be read")
    operands, dy = inputs(seed, s, heads, p, n)
    kernels = functools.partial(ssd_scan.ssd_scan,
                                interpret=jax.default_backend() != "tpu")

    def everything(scan, cast):
        @jax.jit
        def run(operands, dy):
            y, back = jax.vjp(scan, *operands)
            return (y,) + back(dy.astype(y.dtype))

        return jax.block_until_ready(run(tuple(cast(t) for t in operands),
                                         dy))

    with jax.default_matmul_precision("highest"):
        want = everything(ssd_scan.chunked, lambda t: t.astype(jnp.float32))
    told = {}
    for what in ("kernels", "state_cotangent_dropped"):
        undo = plant_state_cotangent_dropped() if what != "kernels" \
            else lambda: None
        try:
            got = everything(kernels, lambda t: t)
        finally:
            undo()
        told[what] = {name: float(
            jnp.linalg.norm((a.astype(jnp.float32) - b).ravel())
            / jnp.linalg.norm(b.ravel()))
            for name, a, b in zip(("y",) + OPERANDS, got, want)}
        print("gradients", heads, seed, what, told[what], file=sys.stderr,
              flush=True)
    return told


def gradients_hold(by_seed):
    return all(
        all(v <= GRADIENT_RTOL for v in told["kernels"].values())
        and max(told["state_cotangent_dropped"][c] for c in OPERANDS)
        > GRADIENT_RTOL for told in by_seed.values())


def times(s, heads, p, n, iters):
    import jax

    from horovod_tpu.kernels import ssd_scan

    operands, dy = inputs(0, s, heads, p, n)
    interpret = jax.default_backend() != "tpu"

    def forms(scan):
        def both(*operands):
            y, back = jax.vjp(scan, *operands)
            return back(dy)

        return jax.jit(scan), jax.jit(both)

    fwd, both = forms(functools.partial(ssd_scan.ssd_scan,
                                        interpret=interpret))
    xla_fwd, xla_both = forms(ssd_scan.chunked)
    forward, backward = bytes_moved(s, heads, p, n)
    out = {"heads": heads, "seq": s,
           "fwd_ms": timed(fwd, operands, iters),
           "fwd_bwd_ms": timed(both, operands, iters),
           "xla_fwd_ms": timed(xla_fwd, operands, iters),
           "xla_fwd_bwd_ms": timed(xla_both, operands, iters),
           "fwd_least_ms": 1e3 * forward / HBM_BYTES_PER_S,
           "fwd_bwd_least_ms": 1e3 * (forward + backward) / HBM_BYTES_PER_S}
    out["fwd_share_pct"] = 100 * out["fwd_least_ms"] / out["fwd_ms"]
    out["fwd_bwd_share_pct"] = 100 * out["fwd_bwd_least_ms"] \
        / out["fwd_bwd_ms"]
    print("time", out, file=sys.stderr, flush=True)
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--what", default="time,gradients")
    p.add_argument("--heads", default="16,64")
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--seeds", default=f"1,{2 ** 31 + 56}")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax

    what = args.what.split(",")
    heads = [int(h) for h in args.heads.split(",")]
    out = {"device": jax.devices()[0].device_kind, "seq": args.seq}
    if "time" in what:
        out["time"] = [times(args.seq, h, 64, 128, args.iters)
                       for h in heads]
    ok = True
    if "gradients" in what:
        out["gradient_rtol"] = GRADIENT_RTOL
        out["gradients"] = {}
        for h in heads:
            by_seed = {seed: gradients(int(seed), args.seq, h, 64, 128)
                       for seed in args.seeds.split(",")}
            out["gradients"][str(h)] = by_seed
            ok = ok and gradients_hold(by_seed)
        out["gradients_hold"] = ok
    text = json.dumps(out, indent=1)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
