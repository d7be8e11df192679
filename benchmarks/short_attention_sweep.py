"""Unmasked attention at short sequences, three ways (PERF.md §6, PR 37).

``models/transformer.py::_scaled_dot_attention`` picks, from the shape alone,
the program that computes softmax attention.  This times, on the attached
chip, forward + backward of one layer's attention at the shapes of
:data:`SHAPES` (the first is ``bert-large-wfbp-1chip``'s):

- **einsum**: XLA's einsum and softmax, the path off the TPU and for every
  shape no kernel takes;
- **flash512**, **flash1024**: JAX's pallas flash kernel through
  ``_flash_attention`` with every block ``min(s, 512)`` and ``min(s, 1024)``
  (its transposes into ``[b, h, s, d]`` and back included, as a model pays
  them);
- **short**: ``kernels/short_attention.py``.

A call of a millisecond is at the floor of what the host's clock resolves
here, so a timed program is :data:`DEPTH` attentions in a row (each one's
output the next one's queries) and its gradient; a line gives ms a layer.
Beside the times, each path's error in the output and in dq, dk and dv
against the same einsum in fp32 at the highest matmul precision: the norm of
the difference as a share of the norm (``errors``; the largest difference as
a share of the largest value beside it, ``errors_max``, which one element
decides); the kernel's ``errors`` may be at most :data:`ERROR_ROOM` times the
einsum path's own, or the run fails.

Needs a TPU.  One JSON object a line; ``--out`` also writes them to a file.

Run: ``python benchmarks/short_attention_sweep.py [--shapes 8,16,512,64 ...]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (sequences, heads, positions, head width)
SHAPES = [(8, 16, 512, 64), (8, 16, 1024, 64), (8, 8, 512, 128),
          (2, 16, 2048, 128)]
DEPTH = 8
ERROR_ROOM = 1.5


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


def einsum(q, k, v, causal, precision=None):
    """``_scaled_dot_attention``'s last branch, in the operands' dtype."""
    import jax
    import jax.numpy as jnp

    s, d = q.shape[1], q.shape[3]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision,
                        preferred_element_type=jnp.float32) * d ** -0.5
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None],
                           scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=precision)


def paths(s, causal):
    """{name: attention on [b, s, h, d]}."""
    from horovod_tpu.kernels import short_attention
    from horovod_tpu.models import transformer

    def flash(block):
        def attention(q, k, v):
            # The blocks are read while the call is traced.
            kept = transformer._FLASH_BLOCK
            transformer._FLASH_BLOCK = min(s, block)
            try:
                return transformer._flash_attention(q, k, v, causal,
                                                    q.shape[3])
            finally:
                transformer._FLASH_BLOCK = kept
        return attention

    return {
        "einsum": lambda q, k, v: einsum(q, k, v, causal),
        "flash512": flash(512),
        "flash1024": flash(1024),
        "short": lambda q, k, v: short_attention.attention(q, k, v, causal),
    }


def shape_phase(emit, shape, causal, names):
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, h, s, d = shape
    keys = jax.random.split(jax.random.PRNGKey(s + d), 4)
    q, k, v, w = (jax.random.normal(key, (b, s, h, d), jnp.float32)
                  for key in keys)
    low = [t.astype(jnp.bfloat16) for t in (q, k, v)]

    def value_and_grads(attention, *operands):
        out, pull = jax.vjp(attention, *operands)
        return (out,) + pull(w.astype(out.dtype))

    exact = jax.jit(lambda *a: value_and_grads(
        lambda q, k, v: einsum(q, k, v, causal, lax.Precision.HIGHEST),
        *a))(*(t.astype(jnp.float32) for t in low))
    worst = {}
    for name, attention in paths(s, causal).items():
        if name not in names:
            continue
        line = {"phase": "shape", "path": name, "b": b, "h": h, "s": s,
                "d": d, "causal": causal, "depth": DEPTH}

        def stack(q, k, v):
            for _ in range(DEPTH):
                q = attention(q, k, v)
            return jnp.sum(q.astype(jnp.float32) * w)

        try:
            got = jax.jit(lambda *a: value_and_grads(attention, *a))(*low)
            off = [a.astype(jnp.float32) - e for a, e in zip(got, exact)]
            line["errors"] = [float(jnp.linalg.norm(o) / jnp.linalg.norm(e))
                              for o, e in zip(off, exact)]
            line["errors_max"] = [
                float(jnp.max(jnp.abs(o)) / jnp.max(jnp.abs(e)))
                for o, e in zip(off, exact)]
            worst[name] = line["errors"]
            line["ms_layer_fwd"] = timed(jax.jit(stack), *low) / DEPTH
            line["ms_layer"] = timed(
                jax.jit(jax.grad(stack, argnums=(0, 1, 2))), *low) / DEPTH
        except Exception as e:  # noqa: BLE001 — a shape a kernel refuses
            line["failed"] = f"{type(e).__name__}: {e}"[:400]
        emit(line)
    return worst


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", nargs="*",
                        default=[",".join(map(str, s)) for s in SHAPES])
    parser.add_argument("--paths", default="einsum,flash512,flash1024,short")
    parser.add_argument("--causal", default="0",
                        help="0, 1 or 0,1: unmasked, causal or both")
    parser.add_argument("--out")
    args = parser.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU: a CPU's times are not the chip's")
    lines = []

    def emit(line):
        line["device_kind"] = jax.devices()[0].device_kind
        lines.append(line)
        print(json.dumps(line), flush=True)

    failed = []
    for shape in args.shapes:
        shape = tuple(int(n) for n in shape.split(","))
        for causal in args.causal.split(","):
            worst = shape_phase(emit, shape, causal == "1",
                                args.paths.split(","))
            if "short" in worst and "einsum" in worst:
                for ours, theirs in zip(worst["short"], worst["einsum"]):
                    if not ours <= ERROR_ROOM * theirs:     # a NaN fails
                        failed.append((shape, causal, ours, theirs))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    if failed:
        raise SystemExit(f"FAILED: the kernel's error is beyond {ERROR_ROOM} "
                         f"x the einsum path's: {failed}")


if __name__ == "__main__":
    main()
