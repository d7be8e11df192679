"""Keye-VL-2.0's attention under a chosen set through this repo's kernels,
held to float32 and timed, a kernel at a time (PERF.md section 6, PR 68).

One sequence of 16,384 positions, 32 query heads on 4 KV heads of 128, an
indexer of 16 heads of 64 on one shared key, 2048 keys chosen a query:

- ``choose``: ``kernels/dsa.py::choose`` (scores and the exact choice by
  counting, in fast memory) beside the same choice by ``lax.top_k`` on a
  dense table a block of 512 queries at a time (``models/indexer.py``'s
  ``jax.numpy`` form, a sort on a TPU), ms a layer each, and the share of
  pairs on which the two sets differ (bf16 operands both: the sums' order is
  the only difference, so last-bit neighbours of the threshold); then over
  ``--tie-seeds`` seeds what the kernel says of its blocks: how many broke
  ties, how many rows tied, and the counting passes run of
  ``dsa.passes_at_most`` a block (PR 70);
- ``attention``: ``masked_attention``'s forward and forward + backward under
  ``Sparse`` (every causal tile masked from the words) beside the same shapes
  under ``Causal`` (16 of 136 tiles masked, from iotas): what the mask's
  fetch and compare cost; ``out`` and dq, dk, dv under the chosen sets
  against a float32 masked softmax at the highest precision, each as a share
  of its norm, beside a planted fault (the sets of ``topk / 2``), which has
  to read above :data:`GRADIENT_RTOL` where the kernels stay below it;
- ``loss``: ``kernels/dsa.py::kl_sum`` (the target, the divergence and its
  gradient in one pass) ms a layer, and its value and gradients against the
  ``jax.numpy`` form's on the same operands and on the same values in
  float32 at the highest precision (``--check-loss``: a dense pass, minutes
  at 16,384; ``--seq 4096 --topk 512`` takes seconds).

Needs a TPU; ``--seq 1024 --interpret`` on the CPU is a rehearsal of the same
code (the kernels in interpret mode, no time).  One JSON object a line;
``--out`` also writes them to a file.

Run: ``python benchmarks/sparse_attention_sweep.py [--phases choose ...]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HEADS, KV_HEADS, WIDTH = 32, 4, 128
I_HEADS, I_WIDTH = 16, 64
GRADIENT_RTOL = 1.5e-2


def timed(fn, *args, iters=6):
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def operands(seed, s, dtype):
    """The indexer's and the attention's operands of one sequence, in the
    kernels' layouts, q scaled."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda key, shape: jax.random.normal(  # noqa: E731
        key, shape, jnp.float32)
    return dict(
        q_i=normal(keys[0], (1, I_HEADS, s, I_WIDTH)).astype(dtype),
        k_i=normal(keys[1], (1, s, I_WIDTH)).astype(dtype),
        w=normal(keys[2], (1, s, I_HEADS)) * (I_HEADS * I_WIDTH) ** -0.5,
        q=(normal(keys[3], (1, HEADS, s, WIDTH)) * WIDTH ** -0.5)
        .astype(dtype),
        k=normal(keys[4], (1, KV_HEADS, s, WIDTH)).astype(dtype),
        v=normal(keys[5], (1, KV_HEADS, s, WIDTH)).astype(dtype),
        ct=normal(keys[6], (1, HEADS, s, WIDTH)).astype(dtype))


def exact(x, mask):
    """(out, dq, dk, dv) of attention over ``mask [s, s]`` in float32 at the
    highest precision for the cotangent ``x['ct']``: a dense masked softmax,
    a query head at a time, operands in the kernels' layout."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    group = HEADS // KV_HEADS

    def loss(q, k, v, ct, mask):
        @jax.checkpoint
        def one_head(args):
            q_head, ct_head, j = args
            scores = q_head @ k[0, j].T
            out = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf),
                                 axis=-1) @ v[0, j]
            return out, jnp.sum(out * ct_head)

        out, total = lax.map(one_head, (
            q[0], ct[0], jnp.arange(HEADS) // group))
        return jnp.sum(total), out[None]

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(
                *(x[n].astype(jnp.float32) for n in ("q", "k", "v", "ct")),
                mask)
    return (out,) + grads


def rel(got, want):
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--phases", nargs="*",
                   default=["choose", "attention", "loss"])
    p.add_argument("--seq", type=int, default=16384)
    p.add_argument("--topk", type=int, default=2048)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tie-seeds", type=int, default=6)
    p.add_argument("--interpret", action="store_true")
    p.add_argument("--check-loss", action="store_true")
    p.add_argument("--out")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import dsa
    from horovod_tpu.kernels import masked_attention as ma
    from horovod_tpu.kernels.masked_attention_bwd import unpack_chosen
    from horovod_tpu.models import indexer

    s, topk, interpret = args.seq, args.topk, args.interpret
    lines = []

    def emit(line):
        line = {"seq": s, "topk": topk,
                "device": jax.devices()[0].device_kind, **line}
        lines.append(line)
        print(json.dumps(line), flush=True)

    x = operands(args.seed, s, jnp.bfloat16)
    small = dict(rows=128, keys=128) if interpret else {}
    kernel = jax.jit(lambda q_i, k_i, w: dsa.choose(
        q_i, k_i, w, topk=topk, interpret=interpret, **small))
    by_sort = jax.jit(lambda q_i, k_i, w: indexer._choose(q_i, k_i, w, topk))
    words, lse_i, _ = kernel(x["q_i"], x["k_i"], x["w"])
    ok = True

    if "choose" in args.phases:
        other = by_sort(x["q_i"], x["k_i"], x["w"])
        mask, other_mask = (unpack_chosen(t, s)[0] for t in (words, other))
        wanted = jnp.minimum(jnp.arange(s) + 1, topk)
        line = {"phase": "choose",
                "pairs_chosen": int(mask.sum()),
                "rows_of_the_wrong_count": int(
                    (mask.sum(axis=1) != wanted).sum()),
                "pairs_that_differ_from_top_k": int(
                    (mask != other_mask).sum())}
        ok = ok and line["rows_of_the_wrong_count"] == 0
        if not interpret:
            line["ms_kernel"] = timed(kernel, x["q_i"], x["k_i"], x["w"])
            line["ms_top_k"] = timed(by_sort, x["q_i"], x["k_i"], x["w"],
                                     iters=2)
        emit(line)
        for seed in range(args.seed, args.seed + args.tie_seeds):
            y = operands(seed, s, jnp.bfloat16)
            trio = (y["q_i"], y["k_i"], y["w"])
            got, _, blocks = kernel(*trio)
            ties, passes = blocks[..., 0], blocks[..., 1]
            emit({"phase": "choose_ties", "seed": seed,
                  "blocks": int(ties.size),
                  "blocks_that_broke_ties": int((ties > 0).sum()),
                  "rows_that_tied": int(ties.sum()),
                  "passes_run": int(passes.sum()),
                  "passes_at_most": int(ties.size) * dsa.passes_at_most(s),
                  "pairs_that_differ_from_top_k": int(
                      (unpack_chosen(got, s)
                       != unpack_chosen(by_sort(*trio), s)).sum())})

    rule = ma.Sparse(topk)
    if "attention" in args.phases:
        tiles = ((128, 128, 128),) * 2 if interpret else ma._tiles(
            rule, x["q"])

        def both(rule):
            def run(q, k, v, ct, words):
                def f(*a):
                    if words is None:
                        return ma._attend(*a, rule, interpret)
                    return ma._attend_chosen(*a, words, rule, interpret)[0]

                out, back = jax.vjp(f, q, k, v)
                return (out,) + back(ct)
            return jax.jit(run)

        def forward(rule):
            return jax.jit(lambda q, k, v, ct, words: ma.out_lse(
                q, k, v, words, rule=rule, tiles=tiles[0],
                interpret=interpret))

        import unittest.mock

        with unittest.mock.patch.object(ma, "_tiles",
                                        lambda rule, q: tiles):
            line = {"phase": "attention"}
            qkv = (x["q"], x["k"], x["v"], x["ct"])
            if not interpret:
                for name, r, wd in (("sparse", rule, words),
                                    ("causal", ma.Causal(), None)):
                    line[f"ms_fwd_{name}"] = timed(forward(r), *qkv, wd)
                    line[f"ms_layer_{name}"] = timed(both(r), *qkv, wd)
            names = ("out", "dq", "dk", "dv")
            got = both(rule)(*qkv, words)
            mask = unpack_chosen(words, s)[0]
            want = exact(x, mask)
            line["errors"] = dict(zip(names, map(rel, got, want)))
            fewer = unpack_chosen(dsa.choose(
                x["q_i"], x["k_i"], x["w"], topk=topk // 2,
                interpret=interpret, **small)[0], s)[0]
            line["fault_half_the_keys"] = dict(zip(
                names, map(rel, exact(x, fewer), want)))
            line["limit"] = GRADIENT_RTOL
            line["ok"] = max(line["errors"].values()) < GRADIENT_RTOL \
                < max(line["fault_half_the_keys"].values())
            ok = ok and line["ok"]
            emit(line)

    if "loss" in args.phases:
        tiles = (128, 128) if interpret else dsa.LOSS_TILES
        out, lse = ma.out_lse(
            x["q"], x["k"], x["v"], words, rule=rule,
            tiles=(128, 128, 128) if interpret else ma._tiles(rule,
                                                              x["q"])[0],
            interpret=interpret)

        def value_and_gradients(fn):
            return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2)))

        kernel_loss = value_and_gradients(
            lambda q_i, k_i, w, words, lse_i, q, k, lse: dsa.kl_sum(
                q_i, k_i, w, words, lse_i, q, k, lse, tiles=tiles,
                interpret=interpret))
        line = {"phase": "loss"}
        trio = (x["q_i"], x["k_i"], x["w"], words, lse_i, x["q"], x["k"],
                lse)
        value, grads = kernel_loss(*trio)
        line["kl_mean"] = float(value) / s
        if not interpret:
            line["ms_kernel"] = timed(kernel_loss, *trio)
        if args.check_loss or interpret:
            blockwise = value_and_gradients(
                lambda q_i, k_i, w, words, lse_i, q, k, lse: indexer._kl_sum(
                    q_i, k_i, w, words, q, k))
            want_value, want = blockwise(*trio)
            with jax.default_matmul_precision("highest"):
                _, exact_grads = blockwise(*(
                    t.astype(jnp.float32) if t.dtype == jnp.bfloat16 else t
                    for t in trio))
            line["kl_mean_jnp"] = float(want_value) / s
            names = ("dq_i", "dk_i", "dw")
            line["errors"] = dict(zip(names, (
                rel(g, jnp.asarray(t, jnp.float32))
                for g, t in zip(grads, want))))
            line["errors_float32"] = dict(zip(names, map(
                rel, grads, exact_grads)))
            line["ok"] = max(line["errors"].values()) < GRADIENT_RTOL \
                and abs(line["kl_mean"] - line["kl_mean_jnp"]) \
                < 1e-2 * abs(line["kl_mean_jnp"])
            ok = ok and line["ok"]
        emit(line)

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
