"""The gated delta rule's kernels and the DeltaNet's convolution at
qwen3-next-80b-a3b's shapes, on the chip, a form at a time.

    python3 benchmarks/gated_delta_sweep.py [--what passes,rule,conv] [--out file]

``passes``: what an MXU pass costs by its shape, the hinge of PR 51: inside one
pallas kernel, a grid step of eight value heads' worth of independent chains
of dependent products (bf16 operands, fp32 sums, nothing to HBM between
them; 512 grid steps, a layer's), a chain being 30 ``[64, 64]`` products a
head; 30 ``[128, 128]`` ones a pair of heads (the pair's block diagonal); 20
of ``[64, 128] . [128, 64]`` a head (a three-pass fp32 product as ``[a_h | a_l]
[b_h ; b_h]`` and ``a_h b_l``); and 30 of ``[64, 128] . [128, 128]`` a pair (the
pair's blocks side by side against a block-diagonal right-hand side); each
with a step's chains written one after the other and ``abreast``, product i
of every chain on neighbouring lines of the program (the chip's compiler
overlaps only those: what ``kernels/gated_delta.py``'s leading axis of pairs
rests on).
``rule``: ``kernels/gated_delta.py``'s forward kernel and forward + backward
(a pair of value heads a block-diagonal chunk, a grid step's pairs abreast)
at one sequence of 8192, 16 key heads serving 32 value heads of 128, with the
chunks' inverses in 6 and in 3 bf16 passes (``--inverse-passes``) and 4, 8 and
16 value heads a grid step (``--heads-a-step``), beside how far ``o`` and
``dv`` lie from ``chunked()``'s in float32.  The script reads the package
beside it, so a copy of it in a checkout of another commit times that
commit's kernels on the same chip.
``conv``: the mixers' depthwise causal convolution at the three cells'
shapes (granite-4.0-h-micro's ``xBC``, 4352 channels from column 4096 of a row
of 8512, with a bias; nemotron-3-super-120b-a12b's share, 1280 from 1024 of
2320, with a bias; qwen3-next-80b-a3b's ``[q ; k ; v]``, 8192 from 0 of 12288,
without), one sequence of 8192 in bf16, four taps, forward and forward +
backward, a form at a time: ``padded_slices`` (what ``models/mamba2.py`` ran
until PR 57: a padded fp32 copy and four shifted slices; kept here alone, as
the thing measured against), ``rolled`` (rolls of the fp32 copy under a
mask), ``rolled_bf16`` (``kernels/causal_conv.py::reference``: rolls of the
input itself) and ``kernel`` (``kernels/causal_conv.py``'s two kernels, at
``--tiles`` positions a grid step), each on the channels ``alone`` (an array
of their own) and cut out of the ``row`` (what the mixers do; there
``kernel_of_slice`` hands the kernels XLA's slice and ``kernel`` the row and
the window's first column), beside the floor by bytes (4 B a channel and
position forward, 10 B forward + backward, at 819 GB/s) and how far ``y`` and
``dx`` lie from ``padded_slices``'s.
Times are wall-clock around ``block_until_ready`` over ``--iters`` calls of
one jitted function, one process, one chip; a time, not a result line.
"""

from __future__ import annotations

import argparse
import functools
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


# form: (rows, columns of a chain's fp32 tile, heads a chain, products a chain)
PASS_FORMS = {"64x64x64": (64, 64, 1, 30), "128x128x128": (128, 128, 2, 30),
              "64x128x64": (64, 64, 1, 20), "64x128x128": (64, 128, 2, 30)}


def _pass_product(form, y):
    """The next tile of a chain: both operands made of ``y``, fp32."""
    import jax.numpy as jnp
    from jax import lax

    bf16 = jnp.bfloat16
    left = right = y.astype(bf16)
    if form == "64x128x64":
        low = (y - left.astype(jnp.float32)).astype(bf16)
        left = jnp.concatenate([left, low], axis=1)
        right = jnp.concatenate([right, right], axis=0)
    elif form == "64x128x128":
        t = lax.broadcasted_iota(jnp.int32, (128, 128), 0)
        s = lax.broadcasted_iota(jnp.int32, (128, 128), 1)
        right = jnp.where(t // 64 == s // 64,
                          jnp.concatenate([right, right], axis=0), 0)
    return jnp.dot(left, right, preferred_element_type=jnp.float32)


def passes(iters, steps=512, heads=8):
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl

    def kernel(x_ref, o_ref, *, form, products, abreast):
        def advance(y, w):
            # Bounded by 1 where y and w are: timing, not a result.
            return _pass_product(form, y) * (0.5 / 128) + 0.5 * w

        ws = [x_ref[chain] for chain in range(x_ref.shape[0])]
        if abreast:        # product i of every chain on neighbouring lines
            ys = ws
            for _ in range(products):
                ys = [advance(y, w) for y, w in zip(ys, ws)]
        else:              # a chain after the other
            ys = []
            for w in ws:
                y = w
                for _ in range(products):
                    y = advance(y, w)
                ys.append(y)
        for chain, y in enumerate(ys):
            o_ref[chain] = y

    rows = []
    for abreast in (False, True):
        for form, (n, m, heads_a_chain, products) in PASS_FORMS.items():
            chains = heads // heads_a_chain
            x = jax.random.uniform(jax.random.PRNGKey(0),
                                   (steps * chains, n, m), minval=-1.0)
            block = pl.BlockSpec((chains, n, m), lambda i: (i, 0, 0))
            call = jax.jit(pl.pallas_call(
                functools.partial(kernel, form=form, products=products,
                                  abreast=abreast),
                grid=(steps,), in_specs=[block], out_specs=block,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                name=f"hvd_sweep_passes_{form}{'_abreast' * abreast}"))
            ms = timed(call, (x,), iters)
            rows.append({
                "form": form, "abreast": abreast, "chains_a_step": chains,
                "products_a_chain": products, "ms": ms,
                "finite": bool(jnp.isfinite(call(x)).all()),
                "ns_a_product": 1e6 * ms / (steps * chains * products),
                "ns_a_head_chain": 1e6 * ms / (steps * heads)})
            print(rows[-1], file=sys.stderr, flush=True)
    return rows


def rule(iters, inverse_passes=(6, 3), heads_a_step=(8, 4, 16)):
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
    for passes in inverse_passes:
        for heads in heads_a_step:
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


# cell: (the projection's row, the window's first column and width, a bias)
CONV_SHAPES = {"granite-4.0-h-micro": (8512, 4096, 4352, True),
               "nemotron-3-super-120b-a12b": (2320, 1024, 1280, True),
               "qwen3-next-80b-a3b": (12288, 0, 8192, False)}


def conv(iters, tiles=(512,), s=8192, taps=4):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.kernels import causal_conv as cc

    def padded_slices(x, w, bias):
        padded = jnp.pad(x.astype(jnp.float32),
                         ((0, 0), (taps - 1, 0), (0, 0)))
        out = sum(w[:, j] * padded[:, j:j + s] for j in range(taps))
        return jax.nn.silu(out if bias is None else out + bias) \
            .astype(x.dtype)

    def rolled(x, w, bias):
        f = x.astype(jnp.float32)
        at = lax.broadcasted_iota(jnp.int32, (1, s, 1), 1)
        out = w[:, taps - 1] * f
        for back in range(1, taps):
            out = out + w[:, taps - 1 - back] * jnp.where(
                at >= back, jnp.roll(f, back, axis=1), 0.0)
        return jax.nn.silu(out if bias is None else out + bias) \
            .astype(x.dtype)

    def of_slice(form):
        return lambda row, start, w, bias: form(
            row[..., start:start + w.shape[0]], w, bias)

    def kernel(row, start, w, bias):
        return cc.causal_conv(row[..., start:start + w.shape[0]], w, bias,
                              within=(row, start))

    forms = {"padded_slices": of_slice(padded_slices),
             "rolled": of_slice(rolled),
             "rolled_bf16": of_slice(cc.reference),
             "kernel_of_slice": of_slice(cc.causal_conv), "kernel": kernel}

    def far(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm((a - b).ravel())
                     / jnp.linalg.norm(b.ravel()))

    rows = []
    for cell, (width, first, c, biased) in CONV_SHAPES.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        w = 0.5 * jax.random.normal(keys[1], (c, taps))
        bias = jax.random.normal(keys[2], (c,)) if biased else None
        for operand, (width, first) in {"alone": (c, 0),
                                        "row": (width, first)}.items():
            row = jax.random.normal(keys[0], (1, s, width)) \
                .astype(jnp.bfloat16)
            want = None
            for name, form in forms.items():
                if operand == "alone" and name == "kernel_of_slice":
                    continue
                for tile in tiles if name.startswith("kernel") else (None,):
                    if tile:
                        cc._TILE = tile
                        jax.clear_caches()
                    forward = jax.jit(
                        lambda row, w, bias, form=form, first=first: form(
                            row, first, w, bias))
                    backward = jax.jit(jax.grad(
                        lambda row, w, bias, form=form, first=first: jnp.sum(
                            form(row, first, w, bias)
                            .astype(jnp.float32) ** 2),
                        argnums=(0, 1, 2) if biased else (0, 1)))
                    y = forward(row, w, bias)
                    dx = backward(row, w, bias)[0]
                    want = want or (y, dx)
                    rows.append({
                        "cell": cell, "operand": operand, "form": name,
                        "tile": tile,
                        "forward_ms": timed(forward, (row, w, bias), iters),
                        "forward_backward_ms": timed(
                            backward, (row, w, bias), iters),
                        "floor_forward_ms": 4e3 * s * c / 819e9,
                        "floor_forward_backward_ms": 10e3 * s * c / 819e9,
                        "y_from_padded_slices": far(y, want[0]),
                        "dx_from_padded_slices": far(dx, want[1])})
                    print(rows[-1], file=sys.stderr, flush=True)
    return rows


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--what", default="passes,rule,conv")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--inverse-passes", default="6,3", help="rule's rows")
    p.add_argument("--heads-a-step", default="8,4,16", help="rule's rows")
    p.add_argument("--tiles", default="512", help="conv's kernel rows")
    p.add_argument("--seq", type=int, default=8192,
                   help="conv's positions (small: a rehearsal on the CPU)")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax

    def numbers(text):
        return tuple(int(n) for n in text.split(","))

    forms = {"passes": passes,
             "conv": functools.partial(conv, tiles=numbers(args.tiles),
                                       s=args.seq),
             "rule": functools.partial(
        rule, inverse_passes=numbers(args.inverse_passes),
        heads_a_step=numbers(args.heads_a_step))}
    out = {"device": jax.devices()[0].device_kind}
    for what in args.what.split(","):
        out[what] = forms[what](args.iters)
    print(json.dumps(out), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)


if __name__ == "__main__":
    sys.exit(main())
