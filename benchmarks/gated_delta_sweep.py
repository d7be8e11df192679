"""The gated delta rule's kernels and the DeltaNet's convolution at
qwen3-next-80b-a3b's shapes, on the chip, a form at a time.

    python3 benchmarks/gated_delta_sweep.py [--what rule,conv] [--out file]

``rule``: ``kernels/gated_delta.py``'s forward kernel and forward + backward
at one sequence of 8192, 16 key heads serving 32 value heads of 128, with the
chunks' inverses in 6 and in 3 bf16 passes and 4, 8 and 16 value heads a grid
step, beside how far ``o`` and ``dv`` lie from ``chunked()``'s in float32.
``conv``: ``models/mamba2.py::causal_conv`` (padded, four shifted slices,
fp32) at ``[1, 8192, 8192]`` in bf16 beside the same taps as rolls under a
mask, of the fp32 copy and of the bf16 input itself
(``models/gated_delta.py::causal_conv``), forward and forward + backward.
Times are wall-clock around ``block_until_ready`` over ``--iters`` calls of
one jitted function, one process, one chip; a time, not a result line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def timed(fn, args, iters):
    import jax

    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - start) / iters


def rule(iters):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import gated_delta as gd

    s, hk, hv, d = 8192, 16, 32, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k = (jax.random.normal(key, (1, s, hk, d)) for key in keys[:2])
    q = (q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5) \
        .astype(jnp.bfloat16)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, s, hv, d)).astype(jnp.bfloat16)
    # A fresh layer's decays: A in U(0, 16), softplus(1 + a) near 1.3.
    g = -jax.random.uniform(keys[3], (1, 1, hv), maxval=16.0) \
        * jax.nn.softplus(1 + 0.1 * jax.random.normal(keys[4], (1, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, s, hv)))
    args = (q, k, v, g, beta)

    def both(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2, 3, 4)))

    with jax.default_matmul_precision("highest"):
        want = jax.jit(gd.chunked)(*(t.astype(jnp.float32) for t in args))
        want_dv = both(gd.chunked)(
            *(t.astype(jnp.float32) for t in args))[2]

    def distance(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm((a - b).ravel())
                     / jnp.linalg.norm(b.ravel()))

    rows = []
    for passes in (6, 3):
        for heads in (8, 4, 16):
            gd._KERNEL_INVERSE_PASSES, gd._HEADS_A_STEP = passes, heads
            jax.clear_caches()
            forward = jax.jit(gd.gated_delta)
            backward = both(gd.gated_delta)
            rows.append({
                "inverse_passes": passes, "heads_a_step": heads,
                "forward_ms": timed(forward, args, iters),
                "forward_backward_ms": timed(backward, args, iters),
                "o_from_float32": distance(forward(*args), want),
                "dv_from_float32": distance(backward(*args)[2], want_dv)})
            print(rows[-1], file=sys.stderr, flush=True)
    return rows


def conv(iters):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.models import gated_delta, mamba2

    s, c, taps = 8192, 8192, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (1, s, c)) \
        .astype(jnp.bfloat16)
    w = 0.02 * jax.random.normal(jax.random.PRNGKey(1), (c, taps))

    def causal_conv(x, w):
        # Mamba-2's form adds a bias; the DeltaNet's convolution has none.
        return mamba2.causal_conv(x, w, jnp.zeros((c,), jnp.float32))

    def rolled(x, w):
        f = x.astype(jnp.float32)
        at = lax.broadcasted_iota(jnp.int32, (1, s, 1), 1)
        out = w[:, taps - 1] * f
        for back in range(1, taps):
            out = out + w[:, taps - 1 - back] * jnp.where(
                at >= back, jnp.roll(f, back, axis=1), 0.0)
        return jax.nn.silu(out).astype(x.dtype)

    forms = {"padded_slices": causal_conv, "rolled": rolled,
             "rolled_bf16": gated_delta.causal_conv}
    want = causal_conv(x, w).astype(jnp.float32)
    rows = []
    for name, form in forms.items():
        forward = jax.jit(form)
        backward = jax.jit(jax.grad(
            lambda x, w, form=form: jnp.sum(
                form(x, w).astype(jnp.float32) ** 2), argnums=(0, 1)))
        rows.append({
            "form": name, "forward_ms": timed(forward, (x, w), iters),
            "forward_backward_ms": timed(backward, (x, w), iters),
            "from_padded_slices": float(jnp.abs(
                forward(x, w).astype(jnp.float32) - want).max())})
        print(rows[-1], file=sys.stderr, flush=True)
    return rows


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--what", default="rule,conv")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax

    out = {"device": jax.devices()[0].device_kind}
    for what in args.what.split(","):
        out[what] = {"rule": rule, "conv": conv}[what](args.iters)
    print(json.dumps(out), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)


if __name__ == "__main__":
    sys.exit(main())
