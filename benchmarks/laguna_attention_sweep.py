"""Laguna-S-2.1's two attention shapes through this repo's two kernels, held
to float32 and timed (PERF.md §6, PR 63).

``kernels/masked_attention.py::attention`` at one sequence of 8192 positions,
heads of 128 on 8 KV heads: a full layer's 48 query heads under ``Causal``
(groups of 6) and a sliding layer's 72 under ``Window(512)`` (groups of 9, 15
tiles of 1024 x 1024 for 3.9 tiles' worth of allowed pairs), beside
SmallThinker's two (28 on 4 at 16,384 positions, causal and inside a window
of 4096: groups of 7) for the ms a call.  A line gives ms a call forward
alone (``ms_fwd``) and forward + backward (``ms_layer``), the rate over the
allowed pairs (``tflops``: 2 products forward and 4 backward, what the model
needs; tiles that pad a window read low by as much) and, for Laguna's two,
``out`` and the three cotangents dq, dk and dv, **each by itself**, against a
float32 einsum at the highest precision on the same bf16 operands, as a
share of its norm (``errors``), beside two planted faults of that einsum (a
window of 511; KV head ``h // 8`` where ``h // 9`` is meant), which have to
read above :data:`GRADIENT_RTOL` in some cotangent where the kernels stay
below it in all.  Exits non-zero otherwise.

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


def case_phase(emit, name, seq, interpret):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import masked_attention as ma

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
    line = {"case": name, "rule": rule_name, "seq": s, "heads": h,
            "kv_heads": h_kv, "group": group,
            "device": jax.devices()[0].device_kind}
    if not interpret:
        line["ms_fwd"] = timed(forward, q, k, v)
        line["ms_layer"] = timed(both, q, k, v)
        pairs = rule.allowed_pairs(s) * h
        line["tflops"] = 2 * 6 * pairs * d / (line["ms_layer"] * 1e-3) / 1e12
    ok = True
    if checked:
        def rel(got, want):
            got = jnp.asarray(got, jnp.float32)
            return float(jnp.linalg.norm((got - want).ravel())
                         / jnp.linalg.norm(want.ravel()))

        names = ("out", "dq", "dk", "dv")
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
    return ok


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cases", nargs="*", default=list(CASES))
    parser.add_argument("--seq", type=int, default=0,
                        help="positions in place of a case's own")
    parser.add_argument("--interpret", action="store_true")
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

    ok = all([case_phase(emit, name, args.seq, args.interpret)
              for name in args.cases])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
