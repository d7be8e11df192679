"""What the router's logits cost, product by product (PERF.md §6, PR 45).

``parallel/moe.py::_route`` multiplies rows ``x * r * g`` (a bf16 stream, its
RMSNorm's row factor and scale) by the router's fp32 weights.  This times, on
the attached chip, at the five sparse cells' router shapes, forward alone and
forward + backward (the loss and its gradients of the rows, the weights and
both factors), a product's share of a call that makes eight in turn:

- **highest**: the line every dtype but bfloat16 still runs,
  ``jnp.dot(rows_fp32, w, precision=HIGHEST)`` on the rows multiplied out in
  fp32: six bf16 passes a product, forward and both cotangents;
- **split**: ``_logits`` as it ships: one bf16 product against the three
  pieces of ``g * w`` forward, ``dw`` from the three pieces of the cotangent,
  ``dx = jnp.dot(u, w.T, HIGHEST)``, XLA's own six passes;
- **split_dx_pieces**: the same with ``dx`` as one bf16 product that contracts
  the six pairs of pieces of ``u`` and ``w`` that the highest precision keeps
  (:func:`dx_by_pieces`, here alone: bare it is ahead below 128 outputs, in
  the step it was behind at 512 and never paired below; PERF.md §6, PR 45);
- **split_three_dots**: the forward as three products of e columns and not
  one of 3 e.

Each variant's logits, ``dx`` and ``dw`` are held against a float64 product on
the host, as a share of the largest entry; the run fails if the shipped
product's logits or ``dw`` lie further off than twice the highest-precision
line's (that line rounds the rows once more, so it is the looser of the two),
or its ``dx``, which the step rounds to the rows' bf16 (2**-9) behind the
product, further than 1e-6 (a few fp32 roundings).

Needs a TPU.  One JSON object a line; ``--out`` also writes them to a file.

Run: ``python benchmarks/router_product_sweep.py [--cases nemotron olmoe]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (rows, width, experts) of a step's router in each sparse cell.
CASES = {
    "nemotron": (8192, 4096, 512),
    "sdar": (16384, 2048, 128),
    "smallthinker": (16384, 2560, 64),
    "lfm2": (16384, 2048, 32),
    "olmoe": (12288, 2048, 64),
}


REPS = 8


def timed(fn, *args, iters=10):
    """Milliseconds a call, after two calls that compile and warm."""
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def dx_by_pieces(u, w):
    """``u w^T`` in fp32 for fp32 ``u [n, e]`` and ``w [d, e]`` from their
    bf16 pieces: the six products of pieces whose ranks add up to four at
    most (what ``Precision.HIGHEST`` keeps of the nine), contracted together
    in one bf16 product of 6 e, the smallest pairs first."""
    import jax.numpy as jnp

    from horovod_tpu.parallel import moe

    e = u.shape[-1]
    u3, w3 = moe._bf16_pieces(u), moe._bf16_pieces(w)
    piece = lambda a, i: a[:, (i - 1) * e:i * e]  # noqa: E731
    pairs = [(3, 1), (2, 2), (1, 3), (2, 1), (1, 2), (1, 1)]
    return moe._bf16_dot(
        jnp.concatenate([piece(u3, i) for i, _ in pairs], axis=1),
        jnp.concatenate([piece(w3, j) for _, j in pairs], axis=1),
        ((1,), (1,)))


def dx_highest(u, w):
    """``u w^T`` as ``_rows_dot``'s backward takes it."""
    import jax.numpy as jnp
    from jax import lax

    return jnp.dot(u, w.T, precision=lax.Precision.HIGHEST)


def variants():
    """{name: logits(x, w, r, g)} for bf16 rows ``x``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.parallel import moe

    def highest(x, w, r, g):
        rows = x.astype(jnp.float32) * r[:, None] * g
        return jnp.dot(rows, w, precision=lax.Precision.HIGHEST)

    @jax.custom_vjp
    def rows_dot_dx_pieces(x, w):
        """``_rows_dot`` with ``dx_by_pieces`` for the rows' cotangent."""
        return moe._rows_dot(x, w)

    def bwd(res, u):
        x, w = res
        dw = moe._sum_of_slabs(moe._bf16_dot(x, moe._bf16_pieces(u),
                                             ((0,), (0,))))
        return dx_by_pieces(u, w).astype(x.dtype), dw

    rows_dot_dx_pieces.defvjp(lambda x, w: (moe._rows_dot(x, w), (x, w)), bwd)

    def three_dots(x, w):
        w3 = moe._bf16_pieces(w)
        e = w.shape[1]
        s = [moe._bf16_dot(x, w3[:, i * e:(i + 1) * e], ((1,), (0,)))
             for i in range(3)]
        return (s[2] + s[1]) + s[0]

    def scaled(product):
        return lambda x, w, r, g: r[:, None] * product(x, g[:, None] * w)

    return {"highest": highest, "split": moe._logits,
            "split_dx_pieces": scaled(rows_dot_dx_pieces),
            "split_three_dots": scaled(three_dots)}


def case_phase(emit, name):
    """Times and errors of every variant at one cell's shape; the shipped
    product's worst error as a share of its limit (over 1: too far off)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    n, d, e = CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(len(name) + n), 5)
    x = jax.random.normal(ks[0], (n, d)).astype(jnp.bfloat16)
    w = 0.02 * jax.random.normal(ks[1], (d, e))
    r = jnp.exp(0.3 * jax.random.normal(ks[2], (n,)))
    g = 1.0 + 0.1 * jax.random.normal(ks[3], (d,))
    u = jax.random.normal(ks[4], (n, e))
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    rows64 = f64(x) * f64(r)[:, None] * f64(g)
    # dx's product alone, on the fp32 operands both forms are handed: the
    # step rounds it to the rows' bf16 behind either.
    ru, gw = r[:, None] * u, g[:, None] * w
    want = {"logits": rows64 @ f64(w), "dw": rows64.T @ f64(u),
            "dx": f64(ru) @ f64(gw).T}
    # A product of a fifth of a millisecond is under what the host's clock
    # resolves a dispatch (0.5 ms): a call multiplies by REPS routers in turn.
    ws = jnp.stack([w * (1.0 + 1e-3 * i) for i in range(REPS)])
    errors = {}
    for variant, logits in variants().items():
        forward = jax.jit(logits)

        def loss(x, w, r, g, logits=logits):
            return jnp.sum(logits(x, w, r, g) * u)

        # The value too: the loss is linear in the logits, and its gradient
        # alone would need no forward through ``highest``.
        both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))
        in_turn = lambda f: jax.jit(  # noqa: E731
            lambda x, ws, r, g, f=f: lax.map(lambda w: f(x, w, r, g), ws))
        dx = jax.jit(dx_by_pieces if variant == "split_dx_pieces"
                     else dx_highest)
        got = {"logits": forward(x, w, r, g), "dx": dx(ru, gw),
               "dw": both(x, w, r, g)[1][1]}
        if variant == "split_three_dots":
            del got["dw"]
        errors[variant] = {
            key: float(np.abs(f64(got[key]) - want[key]).max()
                       / np.abs(want[key]).max()) for key in got}
        # Three separate products have no cotangent rule of their own.
        backward = None if variant == "split_three_dots" \
            else round(timed(in_turn(both), x, ws, r, g) / REPS, 4)
        emit({"phase": "router_product", "case": name, "rows": n, "width": d,
              "experts": e, "variant": variant,
              "forward_ms": round(
                  timed(in_turn(forward), x, ws, r, g) / REPS, 4),
              "forward_backward_ms": backward,
              **{f"{key}_error": err
                 for key, err in errors[variant].items()}})
    return max(errors["split"]["dx"] / 1e-6,
               *(errors["split"][key] / (2 * errors["highest"][key])
                 for key in ("logits", "dw")))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cases", nargs="*", default=list(CASES))
    parser.add_argument("--out")
    args = parser.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU: a CPU's times are not the chip's")
    out = None
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        out = open(args.out, "w")

    def emit(line):
        line["device_kind"] = jax.devices()[0].device_kind
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    worst = max(case_phase(emit, name) for name in args.cases)
    emit({"phase": "verdict", "worst_error_over_its_limit": worst})
    if worst > 1.0:
        raise SystemExit(
            f"the split product's error is {worst:.2f} times its limit")


if __name__ == "__main__":
    main()
