"""Attention under a rule, forward + backward, four ways (PERF.md §6, PR 44).

``kernels/masked_attention.py::attention`` runs the library's splash forward
kernel and ``kernels/masked_attention_bwd.py``'s one backward kernel.  This
times, on the attached chip, one layer's attention at the rule and shape of
every cell that calls the wrapper (:data:`CASES`):

- **library**: splash attention's own backward, a dq and a dkv kernel with
  tiles of 1024 and the keys multiplied 512 at a time (the tree before PR 44);
- **library_fused**: the same with ``use_fused_bwd_kernel=True``: one kernel
  that writes dq in partial sums a key tile and walks every tile;
- **ours/<queries>x<keys>x<keys at a time>**: the wrapper with the backward
  kernel at each of ``--tiles``.

A line gives ms a layer forward alone (``ms_fwd``) and forward + backward
(``ms_layer``), the backward's rate by its five products over the allowed
tiles (``bwd_tflops``: what the hardware multiplied, a path with seven
products reads low by as much), the compiled program's temporaries
(``temp_mib``) and, for every path but the first, the norm of the difference
of its dq, dk and dv from the library's as a share of their norm (``errors``:
both are bf16 roundings of the same sums, so a rounding's size; the run fails
beyond :data:`ERROR_LIMIT`).

Needs a TPU.  One JSON object a line; ``--out`` also writes them to a file,
line by line.

Run: ``python benchmarks/masked_attention_sweep.py [--cases sdar ...]``
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (rule, sequences, positions, query heads, KV heads, head width)
CASES = {
    "sdar": ("blockdiff4", 1, 16384, 32, 4, 128),
    "smallthinker_global": ("causal", 1, 16384, 28, 4, 128),
    "smallthinker_window": ("window4096", 1, 16384, 28, 4, 128),
    "lfm2": ("causal", 2, 8192, 32, 8, 64),
    "nemotron": ("causal", 1, 8192, 4, 1, 128),
}
TILES = ["1024x1024x512", "1024x1024x256", "1024x1024x1024", "512x1024x512",
         "2048x1024x512"]
ERROR_LIMIT = 2e-2


def the_rule(name: str):
    from horovod_tpu.kernels import blockdiff_attention, masked_attention

    if name == "causal":
        return masked_attention.Causal()
    if name.startswith("window"):
        return masked_attention.Window(int(name.removeprefix("window")))
    return blockdiff_attention.BlockDiffusion(
        int(name.removeprefix("blockdiff")))


def timed(fn, *args, iters=8):
    """Milliseconds a call, after two calls that compile and warm."""
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def library(rule, fused: bool):
    """``[b, s, h, d]`` attention through the library's kernels alone, as
    ``masked_attention.attention`` called them before PR 44."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
    )

    from horovod_tpu.kernels import masked_attention as ma

    n = ma.BLOCK
    tiles = dict(ma._TILES, block_q_dkv=n, block_kv_dkv=n,
                 block_kv_dkv_compute=n // 2, use_fused_bwd_kernel=fused)
    if not fused:
        tiles.update(block_q_dq=n, block_kv_dq=n)

    @functools.lru_cache(maxsize=None)
    def the_kernel(s, h):
        mask = ma._mask_lib().MultiHeadMask([rule.mask(s)] * h)
        with jax.ensure_compile_time_eval():
            return splash.make_splash_mha(
                mask, block_sizes=splash.BlockSizes(**tiles), head_shards=1,
                q_seq_shards=1)

    def attention(q, k, v):
        _, s, h, d = q.shape
        kernel = the_kernel(s, h)
        hsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        out = jax.vmap(kernel)(hsd(q * jnp.asarray(d ** -0.5, q.dtype)),
                               hsd(k), hsd(v))
        return out.transpose(0, 2, 1, 3)

    return attention


def case_phase(emit, name, tile_names):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import masked_attention as ma
    from horovod_tpu.kernels import masked_attention_bwd

    rule_name, b, s, h, h_kv, d = CASES[name]
    rule = the_rule(rule_name)
    keys = jax.random.split(jax.random.PRNGKey(s + d), 4)
    q, w = (jax.random.normal(key, (b, s, h, d), jnp.bfloat16)
            for key in keys[:2])
    k, v = (jax.random.normal(key, (b, s, h_kv, d), jnp.bfloat16)
            for key in keys[2:])
    def wrapper(q, k, v):
        return ma.attention(q, k, v, rule)

    # path: (attention, the backward kernel's tiles while it is traced)
    paths = {"library": (library(rule, False), None),
             "library_fused": (library(rule, True), None)}
    for tiles in tile_names:
        paths["ours/" + tiles] = (wrapper,
                                  tuple(int(n) for n in tiles.split("x")))
    want, failed, kept = None, [], ma.BWD_TILES
    for path, (attention, tiles) in paths.items():
        line = {"phase": "case", "case": name, "path": path,
                "rule": rule_name, "b": b, "s": s, "h": h, "h_kv": h_kv,
                "d": d}

        def loss(q, k, v):
            return jnp.sum((attention(q, k, v) * w).astype(jnp.float32))

        ma.BWD_TILES = tiles or kept
        try:
            grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                q, k, v).compile()
            line["temp_mib"] = \
                grads.memory_analysis().temp_size_in_bytes / 2 ** 20
            got = [g.astype(jnp.float32) for g in grads(q, k, v)]
            if want is None:
                want = got
            else:
                line["errors"] = [
                    float(jnp.linalg.norm(g - e) / jnp.linalg.norm(e))
                    for g, e in zip(got, want)]
                if not max(line["errors"]) < ERROR_LIMIT:   # a NaN fails
                    failed.append((name, path, line["errors"]))
            line["ms_fwd"] = timed(jax.jit(loss).lower(q, k, v).compile(),
                                   q, k, v)
            line["ms_layer"] = timed(grads, q, k, v)
            if tiles:
                visited = masked_attention_bwd.tile_table(
                    rule, s, *tiles[:2])[0].size
                line["tiles_visited"] = visited
                line["bwd_tflops"] = (
                    5 * 2 * b * h * visited * tiles[0] * tiles[1] * d
                    / (line["ms_layer"] - line["ms_fwd"]) / 1e9)
        except Exception as e:  # noqa: BLE001 — tiles the compiler refuses
            line["failed"] = f"{type(e).__name__}: {e}"[-600:]
        finally:
            ma.BWD_TILES = kept
        emit(line)
    return failed


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cases", nargs="*", default=list(CASES))
    parser.add_argument("--tiles", nargs="*", default=TILES)
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

    failed = []
    for name in args.cases:
        failed += case_phase(emit, name, args.tiles)
    if failed:
        raise SystemExit(f"FAILED: gradients beyond {ERROR_LIMIT} of the "
                         f"library's: {failed}")


if __name__ == "__main__":
    main()
