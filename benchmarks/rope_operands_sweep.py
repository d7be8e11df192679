"""``kernels/rope_operands.py``'s two kernels alone at the shapes of the three
cells that run them, beside XLA's form of the same work (PERF.md §6, PR 65).

One layer's q and k as the projections write them, ``[1, s, h * 128]`` and
``[1, s, h_kv * 128]`` in bf16, turned by their rotary tables, q scaled, and
written ``[1, h, s, 128]`` and ``[1, h_kv, s, 128]``: Laguna-S-2.1's sliding
layer (72 on 8 at 8192 positions, the whole head turned) and its global one
(48 on 8, the first half turned by YaRN's table times ``attention_factor``),
SmallThinker's window layer (28 on 4 at 16,384) and SDAR's (32 on 4 at
16,384, positions ``0..8191`` twice).  A line a case and ``tile``
(positions a grid step; a grid step is one KV head and its query heads)
gives ms a call of each kernel by itself and the bytes it has to move (every
activation read once and written once, the tables read once) over that time
(``gbs_fwd``, ``gbs_bwd``); the line ``xla`` gives ``_rope`` + scale + the
copies into the kernels' layout as ``models/transformer.py`` and
``masked_attention.attention`` had them until PR 65, forward alone and the
backward XLA makes of it alone.  Every kernel line holds the two outputs and
the two cotangents against that form's run an operation at a time
(``errors``: the largest difference as a share of the largest value; the two
round at the same points and differ where a sum of two products rounds the
other way) and exits non-zero above :data:`LIMIT`.  An operation at a time,
because inside one program XLA makes the tables of cosines by other
instructions than the kernels' tables were made by, and a frequency an ulp
apart is an angle ``positions`` ulps apart: ``tables_in_one_program`` is how
far a table made under ``jax.jit`` lies from the same table made an
operation at a time (PR 65's first sweep compared against the jitted form and
read 0.8 to 2.2% of the largest value in every line, at every tile alike).

Needs a TPU; ``--seq 256 --tiles 128 --interpret`` on the CPU is a rehearsal
of the same code (the kernels in interpret mode, no time).  One JSON object a
line; ``--out`` also writes them to a file (PR 65's rows are
``benchmarks/results/rope_operands_sweep_pr65.jsonl``; its first sweep, which
also took 1, 2 and 4 KV heads a grid step through a parameter that left the
tree when they read the same, is ``rope_operands_sweep_pr65_kv_heads.jsonl``).

Run: ``python benchmarks/rope_operands_sweep.py [--cases laguna_sliding ...]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (positions, query heads, KV heads, the layer's Rotary or None for
# the configuration's theta over the whole head, positions given).
CASES = {
    "laguna_sliding": (8192, 72, 8, "sliding", False),
    "laguna_full": (8192, 48, 8, "full", False),
    "smallthinker_window": (16384, 28, 4, None, False),
    "sdar": (16384, 32, 4, None, True),
}
# The largest difference as a share of the largest value: one rounding of
# bf16 the other way is 2 ** -8 of the value it rounds.
LIMIT = 2 ** -7


def timed(fn, *args, iters=20):
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def case_phase(emit, name, seq, tiles, interpret):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import rope_operands as ro
    from horovod_tpu.models import transformer as tr

    s, h, h_kv, kind, given = CASES[name]
    s, d = seq or s, 128
    rotary = None
    if kind is not None:
        laguna = tr.laguna_s_2_1_config()
        rotary = laguna.layer_kind(0 if kind == "full" else 1).rotary
    positions = jnp.arange(s) % (s // 2) if given else None
    theta, scale = 1e6, d ** -0.5
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, dq = (jax.random.normal(key, (1, s, h * d), jnp.bfloat16)
             for key in keys[:2])
    k, dk = (jax.random.normal(key, (1, s, h_kv * d), jnp.bfloat16)
             for key in keys[2:])
    hsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
    dq, dk = hsd(dq.reshape(1, s, h, d)), hsd(dk.reshape(1, s, h_kv, d))

    def xla(q, k):
        q, k = (tr._rope(t.reshape(1, s, -1, d), theta, positions,
                         rotary=rotary) for t in (q, k))
        return hsd(q * jnp.asarray(scale, q.dtype)), hsd(k)

    xla_forward = jax.jit(xla)
    xla_backward = jax.jit(lambda q, k, dq, dk: jax.vjp(xla, q, k)[1](
        (dq, dk)))
    cos, sin, half = tr._rope_tables(s, d, theta, positions, rotary=rotary)
    in_one = jax.jit(lambda: tr._rope_tables(
        s, d, theta, positions, rotary=rotary)[0])()
    moved = 2 * 2 * s * (h + h_kv) * d + 2 * 4 * s * d
    line = {"case": name, "seq": s, "heads": h, "kv_heads": h_kv,
            "half": half, "mb": moved / 1e6,
            "tables_in_one_program": float(jnp.max(jnp.abs(in_one - cos))),
            "device": jax.devices()[0].device_kind}
    want = xla(q, k) + jax.vjp(xla, q, k)[1]((dq, dk))
    if not interpret:
        emit({**line, "path": "xla", "ms_fwd": timed(xla_forward, q, k),
              "ms_bwd": timed(xla_backward, q, k, dq, dk)})

    def share(got, want):
        got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    ok = True
    for tile in tiles:
        if s % tile:
            continue
        static = dict(half=half, scale=scale, tile=tile, interpret=interpret)
        got = tuple(ro._forward(q, k, cos, sin, **static)) \
            + tuple(ro._backward(cos, sin, dq, dk, **static))
        errors = dict(zip(("q", "k", "dq", "dk"), map(share, got, want)))
        inside = max(errors.values()) < LIMIT
        ok = ok and inside
        row = {**line, "path": "kernels", "tile": tile, "errors": errors,
               "limit": LIMIT, "ok": inside}
        if not interpret:
            row["ms_fwd"] = timed(
                lambda: ro._forward(q, k, cos, sin, **static))
            row["ms_bwd"] = timed(
                lambda: ro._backward(cos, sin, dq, dk, **static))
            row["gbs_fwd"] = moved / row["ms_fwd"] / 1e6
            row["gbs_bwd"] = moved / row["ms_bwd"] / 1e6
        emit(row)
    return ok


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cases", nargs="+", default=list(CASES),
                   choices=list(CASES))
    p.add_argument("--tiles", nargs="+", type=int,
                   default=[256, 512, 1024, 2048])
    p.add_argument("--seq", type=int, default=0,
                   help="positions in place of the cases' own (a rehearsal)")
    p.add_argument("--interpret", action="store_true")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    import jax

    if not args.interpret and jax.default_backend() != "tpu":
        sys.exit("rope_operands_sweep: needs a TPU (or --interpret)")
    out = open(args.out, "w") if args.out else None

    def emit(line):
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()

    ok = True
    for name in args.cases:
        ok = case_phase(emit, name, args.seq, args.tiles,
                        args.interpret) and ok
    if not ok:
        sys.exit("rope_operands_sweep: a kernel's output left XLA's form's "
                 f"by more than {LIMIT}")


if __name__ == "__main__":
    main()
