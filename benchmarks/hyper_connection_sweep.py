"""One hyper-connection alone at Xing4.0-29B-A4B's shape, two layouts (PERF.md
section 6, PR 58).

A sublayer's hyper-connection (``horovod_tpu/models/hyper_connections.py``)
on one sequence of 8192 tokens in four streams of 3584 (``X [1, 8192, 4,
3584]`` in bf16, 235 MB) around a sublayer that costs nothing (``y = u / 2``),
on the attached chip:

- **tokens_minor**: as it ships: the coefficients ``[24, tokens]``, the
  Sinkhorn iterations on ``[4, 4, tokens]`` as slices and products under a
  ``custom_vjp`` that keeps the clipped logits alone, the product with
  ``phi`` one bf16 pass against its three pieces;
- **tokens_major**: the same numbers written as the reference writes them:
  the coefficients ``[tokens, 4]`` and ``[tokens, 4, 4]`` (which a TPU pads to
  ``(8, 128)`` tiles, 33.5 MB an array for 0.5 MB of data), the mixes as
  einsums, Sinkhorn as twenty column and row divisions by ``jnp.sum``, every
  iterate kept for the backward pass by autodiff, the product with ``phi`` in
  fp32 at the highest precision.

- **kernels**: the shipped layout with its backward pass through
  ``kernels/hyper_connection.py``'s two kernels (``connect``, what a TPU runs
  since PR 59; ``tokens_minor`` is ``reference``, plain autodiff of the same
  text): ``whole``, and ``post_bwd`` and ``pre_bwd`` alone over ``--blocks``
  tokens a grid step, each cotangent held to the float32 form's by itself
  (``dy``, ``dX~``, the sixteen ``dH_res``, the four ``dH_post``; ``dX``,
  ``dphi``, ``dbias``, ``dalpha``, the four ``dH_pre``) beside a fault
  planted in each that the limit has to refuse (``H_res`` not transposed in
  ``dX~``; the norm's term dropped from ``dX``).

A line gives a part (``whole``, and of the shipped layout ``coefficients``,
``sinkhorn``, ``mix_down``, ``mix_back``), ms forward alone (``ms_fwd``) and
forward + backward (``ms_layer``: the gradient's program, which runs the
forward too, as a training step does), the compiler's own count of the bytes
each program moves and of its temporaries, and for ``whole`` the share of the
HBM peak that the bytes the algorithm needs (``3 X + 2 u`` forward, ``8 X + 5
u`` forward + backward: ``chip_bench/configs/xing4.0-29b-a4b.py::
hyper_connection_cost``) reach in that time.  The shipped layout's output and
gradients, and the kernels', are held to the other's computed in float32 (the
run fails beyond :data:`ERROR_LIMIT` of their norm, or where a planted fault
stays within it).  Needs a TPU; ``--tokens 256 --width 128``
on the CPU is a rehearsal.  One JSON object a line; ``--out`` also writes
them to a file.

Run: ``python benchmarks/hyper_connection_sweep.py``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ERROR_LIMIT = 2e-2      # bf16 streams against float32 ones
HBM = 819e9             # TPU v5 lite, bytes/s (chip_bench/peaks.py)
ITERS, EPS, CLAMP = 20, 1e-6, 30.0


def timed(fn, *args, iters=8):
    import jax

    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tokens", type=int, default=8192)
    p.add_argument("--width", type=int, default=3584)
    p.add_argument("--blocks", default="128,256,512",
                   help="tokens a grid step, for each kernel alone")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import hyper_connection as kernels
    from horovod_tpu.models import hyper_connections as hc

    rehearsal = jax.default_backend() != "tpu"
    if rehearsal and args.tokens > 1024:
        sys.exit("hyper_connection_sweep: needs a TPU (or a small --tokens)")
    s, c, n = args.tokens, args.width, 4
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    streams = jax.random.normal(keys[0], (1, s, n, c), jnp.bfloat16)
    dout = jax.random.normal(keys[1], (1, s, n, c), jnp.bfloat16)
    phi = 0.02 * jax.random.normal(keys[2], (n * c, n * (n + 2)))
    bias = jax.random.normal(keys[3], (n * (n + 2),)) \
        * jnp.where(jnp.arange(n * (n + 2)) < 2 * n, 1.0, 4.0)
    alpha = jnp.asarray([0.7, 1.3, 2.0])

    def minor(streams, phi, bias, alpha):
        pre, post, logits = hc.coefficients(streams, phi, bias, alpha, n,
                                            EPS, CLAMP)
        res = hc.sinkhorn(logits, ITERS, EPS)
        u = hc.mix_down(pre, streams, streams.dtype)
        return hc.mix_back(res, post, streams, u * 0.5)

    cfg = types.SimpleNamespace(
        hc_mult=n, norm_eps=EPS, hc_res_clamp=CLAMP, hc_sinkhorn_iters=ITERS,
        hc_eps=EPS, dtype=jnp.bfloat16)

    def through_kernels(streams, phi, bias, alpha):
        u, back, _ = hc.connect(cfg, streams, phi, bias, alpha,
                                interpret=rehearsal)
        return back(u * 0.5)

    def major(streams, phi, bias, alpha):
        x = streams[0]                                      # [s, n, c]
        flat = x.reshape(s, n * c).astype(jnp.float32)
        unit = flat * jax.lax.rsqrt(
            jnp.mean(flat * flat, axis=-1, keepdims=True) + EPS)
        z = jnp.dot(unit, phi, precision=jax.lax.Precision.HIGHEST)
        pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + bias[n:2 * n])
        m = jnp.exp(jnp.clip(
            alpha[2] * z[:, 2 * n:].reshape(s, n, n)
            + bias[2 * n:].reshape(n, n), -CLAMP, CLAMP))
        for _ in range(ITERS):
            m = m / (jnp.sum(m, axis=1, keepdims=True) + EPS)
            m = m / (jnp.sum(m, axis=2, keepdims=True) + EPS)
        x32 = x.astype(jnp.float32)
        u = jnp.einsum("sj,sjc->sc", pre, x32).astype(x.dtype)
        y = (u * 0.5).astype(jnp.float32)
        out = jnp.einsum("sij,sjc->sic", m, x32) \
            + jnp.einsum("si,sc->sic", post, y)
        return out.astype(x.dtype)[None]

    def both(fn, wrt=(0, 1, 2, 3)):
        def loss(*operands):
            return jnp.sum(fn(*operands).astype(jnp.float32)
                           * dout.astype(jnp.float32))

        return jax.jit(fn), jax.jit(jax.grad(loss, argnums=wrt))

    def counted(jitted, *operands):
        compiled = jitted.lower(*operands).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        return {"xla_bytes": float(cost.get("bytes accessed", 0.0)),
                "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}

    def share(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    operands = (streams, phi, bias, alpha)
    u_bytes = 2 * s * c
    x_bytes = n * u_bytes
    least = {"ms_fwd": 3 * x_bytes + 2 * u_bytes,
             "ms_layer": 8 * x_bytes + 5 * u_bytes}
    lines, failed = [], False
    exact = both(major)
    exact_operands = (streams.astype(jnp.float32),) + operands[1:]
    want = (exact[0](*exact_operands),) + exact[1](*exact_operands)
    for name, fn in (("tokens_minor", minor), ("kernels", through_kernels),
                     ("tokens_major", major)):
        forward, backward = both(fn)
        line = {"layout": name, "part": "whole", "tokens": s, "width": c,
                "ms_fwd": timed(forward, *operands),
                "ms_layer": timed(backward, *operands),
                "fwd": counted(forward, *operands),
                "layer": counted(backward, *operands)}
        for key, needed in least.items():
            line[key.replace("ms_", "hbm_peak_pct_")] = \
                100 * needed / HBM / (line[key] / 1e3)
        if name != "tokens_major":
            got = (forward(*operands),) + backward(*operands)
            line["errors"] = dict(zip(
                ("out", "dx", "dphi", "dbias", "dalpha"),
                (share(g, w) for g, w in zip(got, want))))
            failed |= max(line["errors"].values()) > ERROR_LIMIT
        lines.append(line)
        print(json.dumps(line), flush=True)

    # The two kernels alone, each cotangent against float32, a fault each.
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    k = n * (n + 2)
    y = jax.random.normal(keys[4], (1, s, c), jnp.bfloat16)
    res, post = (jax.random.uniform(key, shape) for key, shape in zip(
        jax.random.split(keys[4]), ((n, n, 1, s), (n, 1, s))))
    dz = jax.random.normal(keys[1], (k, 1, s))
    # The kernels' operands with the tokens minor, turned here: a copy that
    # the step does not make (it holds the streams so) and no row times.
    minor_of = jax.jit(hc._tokens_minor)
    streams_m, dout_m, y_m = minor_of(streams), minor_of(dout), minor_of(y)

    # Cut here and not in a timed call: an eager slice is a dispatch of its
    # own, a millisecond of the host's beside a kernel of 1.3.
    res_t, post_t = res[:, :, 0], post[:, 0]

    def post_bwd(res_t, block=None):
        named = {} if block is None else {"block": block}
        return kernels.post_bwd(dout_m, streams_m, y_m, res_t, post_t,
                                interpret=rehearsal, **named)

    def pre_text(*operands):
        return hc._pre_side_fwd(*operands, n, EPS, False)[0]

    def pre_rows(dz, without_norm=False):
        """What ``hc._pre_side_bwd`` hands the kernel."""
        _, (_, _, _, pre, product, factor) = hc._pre_side_fwd(
            streams, phi, bias, alpha, n, EPS, False)
        rows, pieces = hc._pre_bwd_rows(phi, alpha, pre.reshape(n, s),
                                        product, factor, dz.reshape(k, s), n)
        if without_norm:
            rows = rows[:3] + (jnp.zeros((n, s)), jnp.zeros((1, s)))
        return rows, pieces

    def pre_bwd(du_m, dxt_m, rows, block=None):
        named = {} if block is None else {"block": block}
        return kernels.pre_bwd(du_m, streams_m, dxt_m, *rows,
                               interpret=rehearsal, **named)

    dres, dpost, dxt, dy = jax.jit(lambda *a: jax.vjp(hc.mix_back, *a[:4])[1](
        a[4]))(res, post, f32(streams), f32(y), f32(dout))
    got = post_bwd(res_t)
    errors = {"dy": share(got[0], minor_of(dy)),
              "dxt": share(got[1], minor_of(dxt))}
    errors.update({f"dres_{i}{j}": share(got[2][i, j], dres[i, j, 0])
                   for i in range(n) for j in range(n)})
    errors.update({f"dpost_{i}": share(got[3][i], dpost[i, 0])
                   for i in range(n)})
    fault = share(post_bwd(res_t.swapaxes(0, 1))[1], minor_of(dxt))
    du = dout[:, :, 0]
    cotangents = (du, dz, dout[:, :, ::-1])
    want = jax.jit(lambda *a: jax.vjp(pre_text, *a[:4])[1](a[4:]))(
        f32(streams), phi, bias, alpha, *(f32(t) for t in cotangents))
    ours = jax.jit(lambda *a: jax.vjp(
        lambda *b: hc._pre_side(*b, n, EPS, rehearsal), *a[:4])[1](a[4:]))(
        *operands, *cotangents)
    pre_errors = dict(zip(("dx", "dphi", "dbias", "dalpha"),
                          (share(g, w) for g, w in zip(ours, want))))
    dpre = jax.jit(lambda h, x, t: jax.vjp(
        lambda h: hc.mix_down(h, x, jnp.float32), h)[1](t)[0])(
        post, f32(streams), f32(du))
    rows, du_m, back_m = pre_rows(dz), minor_of(du), minor_of(cotangents[2])
    pre_errors.update({f"dpre_{j}": share(
        pre_bwd(du_m, back_m, rows)[1][j], dpre[j, 0]) for j in range(n)})
    # The norm's term alone: no dX~, no du, a cotangent along z itself.
    z = pre_text(streams, phi, jnp.zeros_like(bias), alpha)[1]
    along = z / jnp.std(z)
    nothing = jnp.zeros_like(du_m), jnp.zeros_like(streams_m)
    alone = minor_of(jax.jit(lambda *a: jax.vjp(pre_text, *a[:4])[1](a[4:]))(
        f32(streams), phi, bias, alpha, jnp.zeros(du.shape), along,
        jnp.zeros(streams.shape))[0])
    pre_fault = {
        "dx_alone": share(pre_bwd(*nothing, pre_rows(along))[0], alone),
        "dx_without_norm": share(
            pre_bwd(*nothing, pre_rows(along, without_norm=True))[0],
            alone)}
    moved = {"post_bwd": 3 * x_bytes + 2 * u_bytes,
             "pre_bwd": 3 * x_bytes + u_bytes}
    blocks = [int(b) for b in args.blocks.split(",") if s % int(b) == 0]

    def or_why(fn, block):
        """ms, or why not (a block of all channels that the fast memory
        does not hold)."""
        try:
            return fn(block)
        except Exception as e:  # noqa: BLE001 — the compiler's refusal
            return f"{type(e).__name__}: {str(e)[:120]}"

    for part, fn, checked in (
            ("post_bwd", lambda b: timed(post_bwd, res_t, b),
             {"errors": errors, "h_res_not_transposed": fault}),
            ("pre_bwd", lambda b: timed(pre_bwd, du_m, back_m, rows, b),
             {"errors": pre_errors, **pre_fault})):
        line = {"layout": "kernels", "part": part, "tokens": s, "width": c,
                "ms_by_block": {b: or_why(fn, b) for b in blocks}, **checked}
        shipped = fn(None)
        line.update(ms=shipped,
                    hbm_peak_pct=100 * moved[part] / HBM / (shipped / 1e3))
        failed |= max(line["errors"].values()) > ERROR_LIMIT
        lines.append(line)
        print(json.dumps(line), flush=True)
    failed |= fault <= ERROR_LIMIT or pre_fault["dx_alone"] > ERROR_LIMIT \
        or pre_fault["dx_without_norm"] <= ERROR_LIMIT

    # The shipped layout a part at a time.
    pre, post, logits = jax.jit(lambda *a: hc.coefficients(
        *a, n, EPS, CLAMP))(*operands)
    res = jax.jit(lambda a: hc.sinkhorn(a, ITERS, EPS))(logits)
    u = streams[:, :, 0]
    douts = {"coefficients": None, "sinkhorn": None, "mix_down": dout[:, :, 0],
             "mix_back": dout}
    parts = {
        "coefficients": (lambda x, w: jnp.concatenate([
            t.reshape(-1, 1, s) for t in hc.coefficients(
                x, w, bias, alpha, n, EPS, CLAMP)]), (streams, phi)),
        "sinkhorn": (lambda a: hc.sinkhorn(a, ITERS, EPS), (logits,)),
        "mix_down": (lambda h, x: hc.mix_down(h, x, x.dtype), (pre, streams)),
        "mix_back": (lambda r, h, x, y: hc.mix_back(r, h, x, y),
                     (res, post, streams, u))}
    for name, (fn, its) in parts.items():
        weight = douts[name]

        def loss(*its, fn=fn, weight=weight):
            out = fn(*its).astype(jnp.float32)
            return jnp.sum(out * (1.0 if weight is None
                                  else weight.astype(jnp.float32)))

        forward = jax.jit(fn)
        backward = jax.jit(jax.grad(loss, argnums=tuple(range(len(its)))))
        line = {"layout": "tokens_minor", "part": name,
                "ms_fwd": timed(forward, *its),
                "ms_layer": timed(backward, *its),
                "fwd": counted(forward, *its),
                "layer": counted(backward, *its)}
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
