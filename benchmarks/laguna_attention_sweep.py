"""Laguna-S-2.1's two attention shapes through this repo's two kernels, held
to float32 and timed (PERF.md §6, PRs 63 and 64).

``kernels/masked_attention.py::attention`` at one sequence of 8192 positions,
heads of 128 on 8 KV heads: a full layer's 48 query heads under ``Causal``
(groups of 6) and a sliding layer's 72 under ``Window(512)`` (groups of 9;
3.9 tiles of 1024 x 1024 worth of allowed pairs, in 15 such tiles or 31 of
512 x 512), beside SmallThinker's two (28 on 4 at 16,384 positions, causal
and inside a window of 4096: groups of 7) for the ms a call.  A line gives
the tiles the wrapper chose (``fwd_tiles``, ``bwd_tiles``), ms a call forward
alone (``ms_fwd``) and forward + backward (``ms_layer``), the rate over the
allowed pairs (``tflops``: 2 products forward and 4 backward, what the model
needs; tiles that pad a window read low by as much) and, for Laguna's two,
``out`` and the three cotangents dq, dk and dv, **each by itself**, against a
float32 einsum at the highest precision on the same bf16 operands, as a
share of its norm (``errors``), beside two planted faults of that einsum (a
window of 511; KV head ``h // 8`` where ``h // 9`` is meant), which have to
read above :data:`GRADIENT_RTOL` in some cotangent where the kernels stay
below it in all.  Exits non-zero otherwise.

``--fwd-tiles`` and ``--tiles`` (queries x keys x keys multiplied at a time,
``512x512x256``) add a line a kernel and tiles, **each kernel by itself**:
``masked_attention.out_lse`` alone, and ``masked_attention_bwd.dq_dk_dv``
alone on the log-sum-exp of the wrapper's own forward: ms a call, the rate
over the allowed pairs (2 products forward, 4 backward), the tiles the table
holds and how many of them are partial, and what the kernel writes against
the same float32 einsum (PR 64: the sweep that chose ``_tiles``'s tiles; its
rows are ``benchmarks/results/laguna_attention_sweep_pr64.jsonl``).

Needs a TPU; ``--seq 2048 --interpret`` on the CPU is a rehearsal of the same
code (the kernels in interpret mode, no time).  One JSON object a line;
``--out`` also writes them to a file.

Run: ``python benchmarks/laguna_attention_sweep.py [--cases laguna_full ...]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (rule, positions, query heads, KV heads, head width, checked).
CASES = {
    "laguna_full": ("causal", 8192, 48, 8, 128, True),
    "laguna_sliding": ("window512", 8192, 72, 8, 128, True),
    "smallthinker_global": ("causal", 16384, 28, 4, 128, False),
    "smallthinker_window": ("window4096", 16384, 28, 4, 128, False),
    # Laguna's sliding layer under narrower windows: what the finest tiles
    # are worth where they fit the band better (PR 64).
    "window_256": ("window256", 8192, 72, 8, 128, False),
    "window_128": ("window128", 8192, 72, 8, 128, False),
}
# out and each cotangent against float32's, as a share of its norm: bf16
# operands and a bf16 ``p`` leave a few thousandths; a key in 512 left out,
# or a query head on its neighbour's keys, several hundredths and more.
GRADIENT_RTOL = 1.5e-2


def the_rule(name: str):
    from horovod_tpu.kernels import masked_attention

    if name == "causal":
        return masked_attention.Causal()
    return masked_attention.Window(int(name.removeprefix("window")))


def timed(fn, *args, iters=8):
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def exact(q, k, v, ct, rule, kv_of):
    """(out, dq, dk, dv) of attention under ``rule`` in float32 at the
    highest precision, ``q [1, s, h, d]`` on ``k, v [1, s, h_kv, d]``, query
    head ``h`` reading KV head ``kv_of(h)``, for the cotangent ``ct``: a
    dense masked softmax, a query head at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    s, h, d = q.shape[1], q.shape[2], q.shape[3]
    ids = jnp.arange(s)
    mask = rule.allowed(ids[:, None], ids[None, :], s)
    heads = jnp.asarray([kv_of(i) for i in range(h)])

    def loss(q, k, v):
        @jax.checkpoint
        def one_head(args):
            q_head, ct_head, j = args                   # [s, d], [s, d], ()
            scores = q_head @ k[0, :, j].T * d ** -0.5
            out = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf),
                                 axis=-1) @ v[0, :, j]
            return out, jnp.sum(out * ct_head)

        out, total = lax.map(one_head, (q[0].transpose(1, 0, 2),
                                        ct[0].transpose(1, 0, 2), heads))
        return jnp.sum(total), out.transpose(1, 0, 2)[None]

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(
                *(t.astype(jnp.float32) for t in (q, k, v)))
    return (out,) + grads


def as_tiles(text: str):
    return tuple(int(n) for n in text.split("x"))


def as_text(tiles) -> str:
    return "x".join(str(n) for n in tiles)


def case_phase(emit, name, seq, interpret, fwd_tiles=(), bwd_tiles=()):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import masked_attention as ma
    from horovod_tpu.kernels import masked_attention_bwd

    rule_name, s, h, h_kv, d, checked = CASES[name]
    s = seq or s
    rule, group = the_rule(rule_name), h // h_kv
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, ct = (jax.random.normal(key, (1, s, h, d), jnp.bfloat16)
             for key in keys[:2])
    k, v = (jax.random.normal(key, (1, s, h_kv, d), jnp.bfloat16)
            for key in keys[2:])

    forward = jax.jit(lambda q, k, v: ma.attention(
        q, k, v, rule, interpret=interpret))

    def both(q, k, v):
        out, back = jax.vjp(lambda *a: ma.attention(
            *a, rule, interpret=interpret), q, k, v)
        return (out,) + back(ct)

    both = jax.jit(both)
    chosen = ma._tiles(rule, q)
    pairs = rule.allowed_pairs(s) * h
    line = {"case": name, "rule": rule_name, "seq": s, "heads": h,
            "kv_heads": h_kv, "group": group,
            "fwd_tiles": as_text(chosen[0]), "bwd_tiles": as_text(chosen[1]),
            "device": jax.devices()[0].device_kind}
    if not interpret:
        line["ms_fwd"] = timed(forward, q, k, v)
        line["ms_layer"] = timed(both, q, k, v)
        line["tflops"] = 2 * 6 * pairs * d / (line["ms_layer"] * 1e-3) / 1e12

    def rel(got, want):
        got = jnp.asarray(got, jnp.float32)
        return float(jnp.linalg.norm((got - want).ravel())
                     / jnp.linalg.norm(want.ravel()))

    names = ("out", "dq", "dk", "dv")
    ok = True
    if checked:
        want = exact(q, k, v, ct, rule, lambda i: i // group)
        line["errors"] = dict(zip(names, map(rel, both(q, k, v), want)))
        ok = max(line["errors"].values()) < GRADIENT_RTOL
        faults = {"neighbours_keys": (rule, lambda i: min(
            i // (group - 1), h_kv - 1))}
        if isinstance(rule, ma.Window):
            faults["window_less_one"] = (ma.Window(rule.size - 1),
                                         lambda i: i // group)
        line["faults"] = {}
        for fault, (wrong_rule, kv_of) in faults.items():
            got = exact(q, k, v, ct, wrong_rule, kv_of)
            line["faults"][fault] = dict(zip(names, map(rel, got, want)))
            ok = ok and max(line["faults"][fault].values()) > GRADIENT_RTOL
        line["limit"], line["ok"] = GRADIENT_RTOL, ok
    emit(line)
    if not (fwd_tiles or bwd_tiles):
        return ok

    # Each kernel by itself, in the layout and with the scaled q the wrapper
    # hands it; the backward on the wrapper's own forward's residuals.
    hsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
    scale = jnp.asarray(d ** -0.5, q.dtype)
    qs, kh, vh, do = hsd(q * scale), hsd(k), hsd(v), hsd(ct)
    out, lse = ma.out_lse(qs, kh, vh, rule=rule, tiles=chosen[0],
                          interpret=interpret)
    di = jnp.einsum("bhsd,bhsd->bhs", out.astype(jnp.float32),
                    do.astype(jnp.float32))

    def alone(kernel, tiles, products, run, got_names, finish):
        """One kernel at ``tiles``: its line, and whether it is inside the
        limit."""
        flags = masked_attention_bwd.tile_table(rule, s, *tiles[:2])[2]
        row = {"case": name, "rule": rule_name, "seq": s, "heads": h,
               "kv_heads": h_kv, "kernel": kernel, "tiles": as_text(tiles),
               "tiles_visited": int(flags.size),
               "tiles_partial": int(
                   (flags & masked_attention_bwd.PARTIAL != 0).sum()),
               "allowed_share": rule.allowed_pairs(s)
               / (int(flags.size) * tiles[0] * tiles[1])}
        inside = True
        try:
            if not interpret:
                row["ms"] = timed(run, iters=16)
                row["tflops"] = 2 * products * pairs * d \
                    / (row["ms"] * 1e-3) / 1e12
            if checked:
                row["errors"] = {n: rel(g, want[names.index(n)])
                                 for n, g in zip(got_names, finish(run()))}
                inside = max(row["errors"].values()) < GRADIENT_RTOL
                row["limit"], row["ok"] = GRADIENT_RTOL, inside
        except Exception as e:  # noqa: BLE001 — tiles the compiler refuses
            row["failed"] = f"{type(e).__name__}: {e}"[-600:]
        emit(row)
        return inside

    for tiles in map(as_tiles, fwd_tiles):
        ok = alone("fwd", tiles, 2, lambda tiles=tiles: ma.out_lse(
            qs, kh, vh, rule=rule, tiles=tiles, interpret=interpret),
            ("out",), lambda got: (hsd(got[0]),)) and ok
    for tiles in map(as_tiles, bwd_tiles):
        # dq comes for the scaled q: the wrapper's product rule is the scale.
        ok = alone("bwd", tiles, 4, lambda tiles=tiles:
                   masked_attention_bwd.dq_dk_dv(
                       qs, kh, vh, lse, di, do, rule=rule, tiles=tiles,
                       interpret=interpret),
                   ("dq", "dk", "dv"), lambda got: (
                       hsd(got[0]).astype(jnp.float32) * d ** -0.5,
                       hsd(got[1]), hsd(got[2]))) and ok
    return ok


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cases", nargs="*", default=list(CASES))
    parser.add_argument("--seq", type=int, default=0,
                        help="positions in place of a case's own")
    parser.add_argument("--interpret", action="store_true")
    parser.add_argument("--tiles", nargs="*", default=[],
                        help="the backward kernel alone at these tiles too, "
                             "as 512x512x256")
    parser.add_argument("--fwd-tiles", nargs="*", default=[],
                        help="the forward kernel alone at these tiles too")
    parser.add_argument("--out")
    args = parser.parse_args()

    import jax

    if jax.default_backend() != "tpu" and not args.interpret:
        sys.exit("laguna_attention_sweep: needs a TPU (or --interpret)")
    out = open(args.out, "w") if args.out else None

    def emit(line):
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()

    ok = all([case_phase(emit, name, args.seq, args.interpret,
                         args.fwd_tiles, args.tiles)
              for name in args.cases])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
