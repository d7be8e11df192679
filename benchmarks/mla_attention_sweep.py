"""Latent attention's kernels at a key width of 192 over values of 128, two
ways (PERF.md section 6, PR 47).

``kernels/masked_attention.py::attention`` under ``Causal`` at
JoyAI-LLM-Flash's shape (one sequence of 8192, 32 heads, nothing grouped),
forward + backward, on the attached chip:

- **direct**: q and k 192 wide as they are (a lane group and a half, which
  the chip's compiler pads inside the kernels);
- **padded**: q and k padded with zeros to 256 in XLA (q scaled so that the
  scores keep 192^-0.5), values 128: two whole lane groups, a third more
  multiply-adds in the three products that carry the keys' width;
- **equal128**: keys and values both 128 (no rotary part), for the rate the
  kernels reach at a whole lane group.

A line gives ms a layer forward alone (``ms_fwd``) and forward + backward
(``ms_layer``: the gradient's program, which runs the forward kernel too, as
a training step does), the share of the bf16 peak that the counted
operations (320 multiply-adds a pair and head forward, 640 backward, nothing
padded counted) reach in that time, and the norm of the difference of out, dq, dk and dv from
a float32 einsum's as a share of their norm (the run fails beyond
:data:`ERROR_LIMIT`).  Needs a TPU.  One JSON object a line; ``--out`` also
writes them to a file.

Run: ``python benchmarks/mla_attention_sweep.py``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ERROR_LIMIT = 2e-2
PEAK = 197e12           # TPU v5 lite, bf16 (chip_bench/peaks.py)


def timed(fn, *args, iters=8):
    import jax

    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--positions", type=int, default=8192)
    p.add_argument("--heads", type=int, default=32)
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import masked_attention as ma

    if jax.default_backend() != "tpu":
        sys.exit("mla_attention_sweep: needs a TPU")
    s, h, dqk, dv = args.positions, args.heads, 192, 128
    rule = ma.Causal()
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(key, (1, s, h, dqk), jnp.bfloat16)
            for key in keys[:2])
    v, do = (jax.random.normal(key, (1, s, h, dv), jnp.bfloat16)
             for key in keys[2:])

    def direct(q, k, v):
        return ma.attention(q, k, v, rule)

    def padded(q, k, v):
        def wide(t):
            return jnp.pad(t, ((0, 0),) * 3 + ((0, 256 - dqk),))

        return ma.attention(wide(q * jnp.asarray((256 / dqk) ** 0.5, q.dtype)),
                            wide(k), v, rule)

    def equal128(q, k, v):
        return ma.attention(q[..., :dv], k[..., :dv], v, rule)

    def exact(q, k, v):
        with jax.default_matmul_precision("highest"):
            return ma.einsum(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), rule)

    def both(fn, do):
        forward = jax.jit(fn)

        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32)
                           * do.astype(jnp.float32))

        return forward, jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    def share(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    # The float32 einsum a head at a time would do; 8 heads keep its scores
    # (8 x 8192 x 8192 x 4 B = 2 GiB) on the chip.
    few = slice(0, min(h, 8))
    want_fwd, want_bwd = both(exact, do[:, :, few])
    small = (q[:, :, few], k[:, :, few], v[:, :, few])
    pairs = s * (s + 1) // 2
    lines, failed = [], False
    want = (want_fwd(*small),) + want_bwd(*small)
    ma_takes = ma.takes
    for name, fn in (("direct", direct), ("padded", padded),
                     ("equal128", equal128)):
        # The sweep's other widths are the wrapper's to refuse in a model.
        ma.takes = lambda rule, s, d, dv=None: True
        try:
            forward, backward = both(fn, do)
            line = {"path": name, "positions": s, "heads": h,
                    "ms_fwd": timed(forward, q, k, v),
                    "ms_layer": timed(backward, q, k, v)}
            if name != "equal128":
                forward, backward = both(fn, do[:, :, few])
                got = (forward(*small),) + backward(*small)
                line["errors"] = dict(zip(("out", "dq", "dk", "dv"), (
                    share(g, w) for g, w in zip(got, want))))
                failed |= max(line["errors"].values()) > ERROR_LIMIT
                macs = 960
            else:
                macs = 3 * 256
            line["peak_share_pct"] = 100 * 2 * macs * pairs * h / PEAK \
                / (line["ms_layer"] / 1e3)
        finally:
            ma.takes = ma_takes
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
