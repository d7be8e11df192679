"""What a row costs on its way back to token order (PERF.md §6, PR 35).

``parallel/moe.py::moe_ffn(held=)`` adds the rows of a chunk of sorted places
up by token twice a layer.  This times, on the attached chip:

- **scatter**: XLA's scatter-add of ``cap`` bf16 rows cast to fp32 into
  ``[tokens, d]`` (the path off the TPU and for shapes the kernel refuses),
  over widths and row counts, with and without the weights, and with the same
  rows in token order or shuffled: what sets the price of a row;
- **target**: the same at one row count over the target's size and the rows
  used;
- **kernel**: ``kernels/rows_to_tokens.py`` on the same places, beside the
  scatter's result (the largest difference is printed, and a difference
  beyond fp32 rounding fails the run); a call of a millisecond or less is
  at the floor of what the host's clock resolves here (0.5 ms a dispatch):
  the cells' traced runs have the device's time;
- **layer**: value and gradients of one ``moe_ffn(held=)`` at both cells'
  shapes, through the kernel and through the scatter.

Needs a TPU.  One JSON object a line; ``--out`` also writes them to a file.

Run: ``python benchmarks/rows_to_tokens_sweep.py [--phases scatter,target,kernel,layer]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, tokens, d, k, held, experts, width, activation): the two cells.
CELLS = {
    "smallthinker-21b-a3b": (16384, 2560, 6, 8, 64, 768, "relu"),
    "sdar-30b-a3b": (16384, 2048, 8, 16, 128, 768, "silu"),
}


def places(seed, tokens, k, held, experts, cap):
    """A balanced routing's chunk of sorted places: (token [cap], group
    [held]) as ``_held_chunk`` computes them."""
    import numpy as np

    rng = np.random.default_rng(seed)
    picks = np.argsort(rng.random((tokens, experts)), axis=1)[:, :k]
    local = np.where(picks < held, picks, held).reshape(-1)
    order = np.argsort(local, kind="stable")
    group = np.bincount(local, minlength=held + 1)[:held]
    ends = np.cumsum(group)
    group = np.clip(ends, 0, cap) - np.clip(ends - group, 0, cap)
    token = np.where(np.arange(cap) < group.sum(), order[:cap] // k, tokens)
    return token.astype(np.int32), group.astype(np.int32)


def timed(fn, *args, iters=20):
    """Milliseconds a call, after two calls that compile and warm."""
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def scatter(rows, token, tokens, weights=None):
    import jax.numpy as jnp

    rows = rows.astype(jnp.float32)
    if weights is not None:
        rows = rows * weights[:, None]
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[token].add(
        rows, mode="drop")


def inputs(seed, cap, d, tokens, k, held, experts):
    import jax
    import jax.numpy as jnp

    token, group = places(seed, tokens, k, held, experts, cap)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    rows = jax.random.normal(keys[0], (cap, d), jnp.bfloat16)
    weights = jax.random.uniform(keys[1], (cap,), jnp.float32)
    return rows, jnp.asarray(token), jnp.asarray(group), weights


def scatter_phase(emit):
    import jax
    import numpy as np

    tokens = 16384
    for cap in (24576, 32768):
        for d in (1024, 2048, 2304, 2560, 3072, 4096):
            rows, token, group, weights = inputs(1, cap, d, tokens, 6, 8, 64)
            shuffled = np.random.default_rng(2).permutation(cap)
            by_token = np.argsort(np.asarray(token), kind="stable")
            plain = jax.jit(lambda r, t: scatter(r, t, tokens))
            line = {"phase": "scatter", "cap": cap, "d": d, "tokens": tokens,
                    "rows_used": int(group.sum()),
                    "ms": timed(plain, rows, token),
                    "ms_weighted": timed(
                        jax.jit(lambda r, t, w: scatter(r, t, tokens, w)),
                        rows, token, weights),
                    "ms_places_shuffled": timed(plain, rows[shuffled],
                                                token[shuffled]),
                    "ms_places_by_token": timed(plain, rows[by_token],
                                                token[by_token])}
            line["ns_row"] = line["ms"] * 1e6 / cap
            emit(line)


def target_phase(emit):
    """The target's size and the rows used, a width at a time."""
    import jax

    cap = 24576
    for d in (2048, 2304, 2560, 3072, 4096):
        for tokens, k in ((4096, 6), (8192, 6), (16384, 12), (16384, 3),
                          (32768, 6)):
            if d != 2560 and tokens not in (8192, 32768):
                continue
            rows, token, group, _ = inputs(1, cap, d, tokens, k, 8, 64)
            emit({"phase": "target", "cap": cap, "d": d, "tokens": tokens,
                  "rows_used": int(group.sum()),
                  "ms": timed(jax.jit(lambda r, t: scatter(r, t, tokens)),
                              rows, token)})


def kernel_phase(emit):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import rows_to_tokens as rt

    worst = 0.0
    for name, (tokens, d, k, held, experts, _, _) in CELLS.items():
        cap = tokens * k // max(1, experts // (2 * held))
        for seed in (3, 4):
            rows, token, group, weights = inputs(seed, cap, d, tokens, k,
                                                 held, experts)
            if seed == 4:       # what the grouped product may leave behind
                rows = rows.at[int(group.sum()):].set(jnp.nan)
            for w in (None, weights):
                by_scatter = jax.jit(
                    lambda r, t, w_: scatter(r, t, tokens, w_))
                by_kernel = jax.jit(lambda r, t, g, w_: rt.rows_to_tokens(
                    r, t, g, tokens, w_))
                want = by_scatter(rows, token, w)
                got = by_kernel(rows, token, group, w)
                error = float(jnp.max(jnp.abs(got - want))
                              / jnp.max(jnp.abs(want)))
                if not error <= worst:      # a NaN stays
                    worst = error
                emit({"phase": "kernel", "cell": name, "cap": cap, "d": d,
                      "seed": seed, "weighted": w is not None,
                      "rows_used": int(group.sum()), "tile": rt.TILE,
                      "ms_scatter": timed(by_scatter, rows, token, w),
                      "ms_kernel": timed(by_kernel, rows, token, group, w),
                      "ms_plan": timed(
                          jax.jit(lambda t, g: rt._plan(t, g, tokens)),
                          token, group),
                      "error": error})
    return worst


def layer_phase(emit):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import rows_to_tokens as rt
    from horovod_tpu.parallel.moe import moe_ffn

    takes = rt.takes
    for name, (tokens, d, k, held, experts, width, act) in CELLS.items():
        keys = jax.random.split(jax.random.PRNGKey(5), 5)
        x = jax.random.normal(keys[0], (1, tokens, d), jnp.bfloat16)
        router = 0.02 * jax.random.normal(keys[1], (d, experts))
        gate, up = (0.02 * jax.random.normal(key, (held, d, width))
                    for key in keys[2:4])
        down = 0.02 * jax.random.normal(keys[4], (held, width, d))

        def loss(x, router, gate, up, down):
            y, stats = moe_ffn(x, router, gate, up, down, k=k,
                               held=tuple(range(held)), norm_topk_prob=True,
                               activation=act)
            return jnp.sum(y.astype(jnp.float32) ** 2) \
                + jnp.sum(stats.load_balancing_loss)

        line = {"phase": "layer", "cell": name}
        grads = {}
        for path in ("kernel", "scatter"):
            rt.takes = takes if path == "kernel" else (lambda *a: False)
            step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
            grads[path] = step(x, router, gate, up, down)
            line[f"ms_{path}"] = timed(step, x, router, gate, up, down,
                                       iters=10)
            line[f"has_kernel_{path}"] = rt.OP_LINE_NAME in step.lower(
                x, router, gate, up, down).compile().as_text()
        rt.takes = takes
        flat = [jax.tree.leaves(grads[p]) for p in ("kernel", "scatter")]
        line["grad_errors"] = [
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32)))
                  / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-30))
            for a, b in zip(*flat)]
        emit(line)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--phases", default="scatter,target,kernel,layer")
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

    phases = args.phases.split(",")
    if "scatter" in phases:
        scatter_phase(emit)
    if "target" in phases:
        target_phase(emit)
    worst = 0.0
    if "kernel" in phases:
        worst = kernel_phase(emit)
    if "layer" in phases:
        layer_phase(emit)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    if not worst <= 1e-5:
        raise SystemExit(f"FAILED: the kernel lies {worst:.3g} of the "
                         "largest sum from the scatter-add")


if __name__ == "__main__":
    main()
