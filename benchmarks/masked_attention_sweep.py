"""Attention under a rule, forward and backward, the library's kernels beside
this repo's (PERF.md §6, PRs 44 and 61).

``kernels/masked_attention.py::attention`` runs two kernels of this repo:
``out_lse`` forward and ``kernels/masked_attention_bwd.py``'s one backward
kernel.  This times, on the attached chip, one layer's attention at the rule
and shape of every cell that calls the wrapper (:data:`CASES`):

- **library**: splash attention alone, forward and its own backward, a dq and
  a dkv kernel with tiles of 1024 and the keys multiplied 512 at a time (the
  tree before PR 44);
- **library_fused**: the same with ``use_fused_bwd_kernel=True``: one kernel
  that writes dq in partial sums a key tile and walks every tile;
- **library_fwd**: the library's forward (``splash_mha_fwd_residuals``) into
  this repo's backward kernel (the tree from PR 44 to PR 60);
- **ours/<queries>x<keys>x<keys at a time>**: the wrapper with the backward
  kernel at each of ``--tiles``, the forward at ``masked_attention.FWD_TILES``;
- **ours_fwd/<...>**: the wrapper with the forward kernel at each of
  ``--fwd-tiles``, the backward at ``masked_attention.BWD_TILES``.

A line names its forward (``fwd``: ``library`` or ``ours/<tiles>``) and gives
ms a layer forward alone (``ms_fwd``) and forward + backward (``ms_layer``),
the backward's rate by its five products over the allowed tiles
(``bwd_tflops``: what the hardware multiplied, a path with seven products
reads low by as much), the compiled program's temporaries (``temp_mib``), for
every path with residuals the norm of the difference of ``out`` and of the
rows' log-sum-exp from a float32 einsum's over the first two query heads as a
share of its norm (``err_out``, ``err_lse``) and, for every path but the
first, the same of its dq, dk and dv from the library's (``errors``: both are
bf16 roundings of the same sums, so a rounding's size; the run fails beyond
:data:`ERROR_LIMIT`).

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

# name: (rule, sequences, positions, query heads, KV heads, head width[,
# the values' width]); Xing4.0's attention has JoyAI's shape.
CASES = {
    "sdar": ("blockdiff4", 1, 16384, 32, 4, 128),
    "smallthinker_global": ("causal", 1, 16384, 28, 4, 128),
    "smallthinker_window": ("window4096", 1, 16384, 28, 4, 128),
    "lfm2": ("causal", 2, 8192, 32, 8, 64),
    "nemotron": ("causal", 1, 8192, 4, 1, 128),
    "joyai": ("causal", 1, 8192, 32, 32, 192, 128),
    "granite": ("causal", 1, 8192, 32, 8, 64),
    "qwen3_next": ("causal", 1, 8192, 16, 2, 256),
}
TILES = ["1024x1024x512", "1024x1024x256", "1024x1024x1024", "512x1024x512",
         "2048x1024x512"]
FWD_TILES = ["1024x1024x512", "1024x1024x1024", "1024x1024x128",
             "512x1024x256", "2048x1024x256", "1024x2048x256"]
# The library's forward as the wrapper called it until PR 61 (PR 31's sweep).
LIBRARY_TILES = (1024, 1024, 512)
ERROR_LIMIT = 2e-2


def the_rule(name: str):
    from horovod_tpu.kernels import blockdiff_attention, masked_attention

    if name == "causal":
        return masked_attention.Causal()
    if name.startswith("window"):
        return masked_attention.Window(int(name.removeprefix("window")))
    return blockdiff_attention.BlockDiffusion(
        int(name.removeprefix("blockdiff")))


def library_mask(rule, seq_len: int):
    """``rule`` as a mask the library computes in its kernels."""
    import numpy as np
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as mask_lib,
    )

    from horovod_tpu.kernels import masked_attention

    if isinstance(rule, masked_attention.Causal):
        return mask_lib.CausalMask((seq_len, seq_len))
    if isinstance(rule, masked_attention.Window):
        return mask_lib.LocalMask((seq_len, seq_len),
                                  window_size=(rule.size - 1, 0), offset=0)

    half_len, block = seq_len // 2, rule.block
    shift = block.bit_length() - 1

    def code(ids):
        """``2 * B(p) + H(p)``, the number ``BlockDiffusion.allowed`` decides
        by, through a shift (the block is a power of two)."""
        clean = ids >= half_len
        return (((ids - clean * half_len) >> shift) << 1) | clean

    class BlockDiffusionMask(mask_lib._ComputableMask):
        """The library's kernel hands ``mask_function`` the rows' entries of
        ``q_sequence``, here already the queries' codes, and the keys' plain
        positions (``kernels/blockdiff_attention.py`` until PR 61)."""

        def __init__(self):
            def mask_function(q_codes, kv_ids):
                c = code(kv_ids)
                return (c == q_codes) | (((c & 1) == 1) & (c < q_codes))

            super().__init__(shape=(seq_len, seq_len),
                             mask_function=mask_function)
            self.q_sequence = code(np.arange(seq_len, dtype=np.int32)) \
                .astype(np.int32)

        def __eq__(self, other):
            return isinstance(other, type(self))

        def __hash__(self):
            return hash((type(self).__name__, half_len, block))

    return BlockDiffusionMask()


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


def hsd(t):
    """``[b, s, h, d]`` to the kernels' ``[b, h, s, d]`` and back."""
    return t.transpose(0, 2, 1, 3)


@functools.lru_cache(maxsize=None)
def library_kernel(rule, s: int, h: int, fused=None, save_residuals=False):
    """The library's kernel for one rule and shape at
    :data:`LIBRARY_TILES`; ``fused``: with its own backward, two kernels or
    the fused one; ``save_residuals``: the forward alone, which also returns
    the rows' log-sum-exp."""
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as mask_lib,
    )

    from horovod_tpu.kernels import masked_attention as ma

    tiles = dict(zip(("block_q", "block_kv", "block_kv_compute"),
                     LIBRARY_TILES))
    if fused is not None:
        n = ma.BLOCK
        tiles.update(block_q_dkv=n, block_kv_dkv=n,
                     block_kv_dkv_compute=n // 2, use_fused_bwd_kernel=fused)
        if not fused:
            tiles.update(block_q_dq=n, block_kv_dq=n)
    mask = mask_lib.MultiHeadMask([library_mask(rule, s)] * h)
    # Mask information is made of numpy arrays here, whatever trace is open.
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            mask, block_sizes=splash.BlockSizes(**tiles), head_shards=1,
            q_seq_shards=1, save_residuals=save_residuals)


def in_layout(attend):
    """``[b, s, h, d]`` attention from ``attend`` on ``[b, h, s, d]`` with
    ``q`` scaled, as ``masked_attention.attention`` lays its kernels'
    operands out."""
    import jax.numpy as jnp

    def attention(q, k, v):
        scale = jnp.asarray(q.shape[-1] ** -0.5, q.dtype)
        return hsd(attend(hsd(q * scale), hsd(k), hsd(v)))

    return attention


def library(rule, fused: bool):
    """Attention through the library's kernels alone, as
    ``masked_attention.attention`` called them before PR 44."""
    import jax

    return in_layout(lambda q, k, v: jax.vmap(library_kernel(
        rule, q.shape[2], q.shape[1], fused=fused))(q, k, v))


def library_forward(rule):
    """``out`` and the rows' log-sum-exp from the library's forward kernel,
    ``[b, h, s, d]`` operands, ``q`` scaled."""
    import jax

    def out_lse(q, k, v):
        out, (lse,) = jax.vmap(library_kernel(
            rule, q.shape[2], q.shape[1], save_residuals=True))(q, k, v)
        return out, lse

    return out_lse


def library_fwd(rule):
    """The library's forward into this repo's backward kernel: the wrapper
    from PR 44 to PR 60."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import masked_attention as ma
    from horovod_tpu.kernels import masked_attention_bwd

    out_lse = library_forward(rule)

    @jax.custom_vjp
    def attend(q, k, v):
        return out_lse(q, k, v)[0]

    def attend_fwd(q, k, v):
        out, lse = out_lse(q, k, v)
        return out, (q, k, v, out, lse)

    def attend_bwd(kept, do):
        q, k, v, out, lse = kept
        di = jnp.einsum("bhsd,bhsd->bhs", out.astype(jnp.float32),
                        do.astype(jnp.float32))
        return tuple(masked_attention_bwd.dq_dk_dv(
            q, k, v, lse, di, do, rule=rule, tiles=ma.BWD_TILES))

    attend.defvjp(attend_fwd, attend_bwd)
    return in_layout(attend)


def exact(q, k, v, rule, heads: int = 2):
    """``out [b, heads, s, dv]`` and the log-sum-exp ``[b, heads, s]`` of the
    first query heads by a float32 einsum at the highest precision."""
    import jax
    import jax.numpy as jnp

    b, s, h, d = q.shape
    group = h // k.shape[2]
    q = q[:, :, :heads].astype(jnp.float32) * d ** -0.5
    k, v = (jnp.repeat(t, group, axis=2)[:, :, :heads].astype(jnp.float32)
            for t in (k, v))
    ids = jnp.arange(s)
    with jax.default_matmul_precision("highest"):
        scores = jnp.where(rule.allowed(ids[:, None], ids[None, :], s),
                           jnp.einsum("bqhd,bkhd->bhqk", q, k), -jnp.inf)
        lse = jax.nn.logsumexp(scores, axis=-1)
        return jnp.einsum("bhqk,bkhd->bhqd",
                          jnp.exp(scores - lse[..., None]), v), lse


def case_phase(emit, name, tile_names, fwd_tile_names):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import masked_attention as ma
    from horovod_tpu.kernels import masked_attention_bwd

    rule_name, b, s, h, h_kv, d, *rest = CASES[name]
    dv = rest[0] if rest else d
    rule = the_rule(rule_name)
    keys = jax.random.split(jax.random.PRNGKey(s + d), 4)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.bfloat16)
    w = jax.random.normal(keys[1], (b, s, h, dv), jnp.bfloat16)
    k = jax.random.normal(keys[2], (b, s, h_kv, d), jnp.bfloat16)
    v = jax.random.normal(keys[3], (b, s, h_kv, dv), jnp.bfloat16)
    want_fwd = exact(q, k, v, rule)

    def rel(a, e):
        return float(jnp.linalg.norm(a.astype(jnp.float32) - e)
                     / jnp.linalg.norm(e))

    def wrapper(q, k, v):
        return ma.attention(q, k, v, rule)

    def as_tiles(text):
        return tuple(int(n) for n in text.split("x"))

    def as_text(tiles):
        return "x".join(str(n) for n in tiles)

    # path: (attention, the forward's tiles and the backward's while it is
    # traced; None: the library's kernel)
    paths = {"library": (library(rule, False), None, None),
             "library_fused": (library(rule, True), None, None),
             "library_fwd": (library_fwd(rule), None, ma.BWD_TILES)}
    for tiles in tile_names:
        paths["ours/" + tiles] = (wrapper, ma.FWD_TILES, as_tiles(tiles))
    for tiles in fwd_tile_names:
        paths["ours_fwd/" + tiles] = (wrapper, as_tiles(tiles), ma.BWD_TILES)
    want, failed, kept = None, [], (ma.FWD_TILES, ma.BWD_TILES)
    for path, (attention, fwd_tiles, bwd_tiles) in paths.items():
        line = {"phase": "case", "case": name, "path": path,
                "fwd": "ours/" + as_text(fwd_tiles) if fwd_tiles
                else "library",
                "rule": rule_name, "b": b, "s": s, "h": h, "h_kv": h_kv,
                "d": d, "dv": dv}

        def loss(q, k, v):
            return jnp.sum((attention(q, k, v) * w).astype(jnp.float32))

        ma.FWD_TILES, ma.BWD_TILES = fwd_tiles or kept[0], bwd_tiles or kept[1]
        try:
            grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                q, k, v).compile()
            line["temp_mib"] = \
                grads.memory_analysis().temp_size_in_bytes / 2 ** 20
            got = [g.astype(jnp.float32) for g in grads(q, k, v)]
            if want is None:
                want = got
            else:
                line["errors"] = [rel(g, e) for g, e in zip(got, want)]
                if not max(line["errors"]) < ERROR_LIMIT:   # a NaN fails
                    failed.append((name, path, line["errors"]))
            if path == "library_fwd" or fwd_tiles:
                out_lse = functools.partial(
                    ma.out_lse, rule=rule, tiles=fwd_tiles) if fwd_tiles \
                    else library_forward(rule)
                out, lse = jax.jit(out_lse)(
                    hsd(q * jnp.asarray(d ** -0.5, q.dtype)), hsd(k), hsd(v))
                line["err_out"] = rel(out[:, :2], want_fwd[0])
                line["err_lse"] = rel(lse[:, :2], want_fwd[1])
                if not max(line["err_out"], line["err_lse"]) < ERROR_LIMIT:
                    failed.append((name, path, line["err_out"],
                                   line["err_lse"]))
            line["ms_fwd"] = timed(jax.jit(loss).lower(q, k, v).compile(),
                                   q, k, v)
            line["ms_layer"] = timed(grads, q, k, v)
            if bwd_tiles:
                visited = masked_attention_bwd.tile_table(
                    rule, s, *bwd_tiles[:2])[0].size
                line["tiles_visited"] = visited
                line["bwd_tflops"] = (
                    2 * b * h * visited * bwd_tiles[0] * bwd_tiles[1]
                    * (3 * d + 2 * dv)
                    / (line["ms_layer"] - line["ms_fwd"]) / 1e9)
        except Exception as e:  # noqa: BLE001 — tiles the compiler refuses
            line["failed"] = f"{type(e).__name__}: {e}"[-600:]
        finally:
            ma.FWD_TILES, ma.BWD_TILES = kept
        emit(line)
    return failed


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cases", nargs="*", default=list(CASES))
    parser.add_argument("--tiles", nargs="*", default=TILES)
    parser.add_argument("--fwd-tiles", nargs="*", default=FWD_TILES)
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
        failed += case_phase(emit, name, args.tiles, args.fwd_tiles)
    if failed:
        raise SystemExit(f"FAILED: beyond {ERROR_LIMIT} of the library's "
                         f"gradients or the float32 einsum's: {failed}")


if __name__ == "__main__":
    main()
