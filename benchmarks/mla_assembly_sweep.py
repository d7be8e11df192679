"""What it costs to hand latent attention's q, k and v to the attention
kernels, and their cotangents back to the projections (PERF.md §6, PR 49).

One block of ``models/deepseek.py::LatentAttention`` at JoyAI-LLM-Flash's
shape (one sequence of 8192, 32 heads of 128 + 64 over values of 128, latents
of 1536 and 512), from the two latents and the block's input to ``q``, ``k``
and ``v`` as ``kernels/masked_attention.py``'s kernels take them
(``[b, h, s, .]``, ``q`` rotated and scaled, ``k = [k_nope ; k_r]``), forward
and forward + backward (cotangents of the three in, gradients of the three
inputs and the three weights out), on the attached chip:

- **products**: the two up-projections and the rotary key's alone, written
  flat as ``Dense`` writes them, and their transposes: the floor under every
  form, taken off each to give ``assembly_ms``;
- **parent**: what the block ran until PR 49, written out: the projections
  flat, the slice, the evens-then-odds copy of the rows, ``_rope``, the two
  concatenations, the scale and the transposes of ``attention()``;
- **xla**: this PR's projections (the interleave on the weights' columns,
  the products written ``[b, h, s, .]``) with the pass over q and k left to
  XLA (``kernels/mla_operands.py::reference``);
- **kernel**: the block as it ships (``deepseek._kernel_operands``: the same
  projections, ``hvd_mla_operands_fwd`` and ``_bwd``).

``gb_s`` is the bytes that have to move (q read and written, ``k_nope`` read
and ``k`` written, ``v`` read and written: 503 MB a direction in bf16) over
``assembly_ms``.  The run fails if a form's operands or gradients leave the
parent's by more than :data:`LIMIT` of their norm; ``mismatches`` counts the
entries of q, k and v that are not the parent's to the bit.

**A block alone is not the layer** (PERF.md §6, PR 49: bare, XLA's own form
reads first; in the cell it is 24 ms a step behind the kernels).  So the
whole ``LatentAttention`` layer is timed too (``phase: mla_layer``), with the
attention kernels and the down- and output projections, forward and forward +
backward to every parameter and the input, the pass over q and k by the
kernels (as shipped) and left to XLA (``mla_operands.operands`` replaced by
its ``reference`` while the layer is traced); ``kernel_ahead_ms`` on the
second line is what the kernels win a layer, of which the cell's step runs
six.

Needs a TPU.  One JSON object a line; ``--out`` also writes them to a file.

Run: ``python benchmarks/mla_assembly_sweep.py``
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LIMIT = 1e-2
S, H, NOPE, ROPE, DV = 8192, 32, 128, 64, 128
D_MODEL, Q_LATENT, KV_LATENT, THETA = 2048, 1536, 512, 3.2e7


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


def forms(positions, heads):
    """{name: fn(c_q, c_kv, x, w_uq, w_ukv, w_kr) -> (q, k, v)}: rows in
    bf16, weights in fp32 and the published layout, as the model holds
    them."""
    import jax.numpy as jnp

    from horovod_tpu.kernels import mla_operands
    from horovod_tpu.models import deepseek
    from horovod_tpu.models.transformer import _rope, _rope_angles

    b, s, h = 1, positions, heads
    scale = (NOPE + ROPE) ** -0.5

    def cast(*weights):
        return [w.astype(jnp.bfloat16) for w in weights]

    def pairs_first(x):
        # As the parent wrote it: indexing with a step lowers to a gather.
        return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)

    def products(c_q, c_kv, x, w_uq, w_ukv, w_kr):
        w_uq, w_ukv, w_kr = cast(w_uq, w_ukv, w_kr)
        return c_q @ w_uq, c_kv @ w_ukv, x @ w_kr

    def parent(c_q, c_kv, x, w_uq, w_ukv, w_kr):
        q, kv, k_r = products(c_q, c_kv, x, w_uq, w_ukv, w_kr)
        q = q.reshape(b, s, h, NOPE + ROPE)
        kv = kv.reshape(b, s, h, NOPE + DV)
        q_r = _rope(pairs_first(q[..., NOPE:]), THETA)
        k_r = _rope(pairs_first(k_r[:, :, None]), THETA)
        q = jnp.concatenate([q[..., :NOPE], q_r], axis=-1)
        k = jnp.concatenate(
            [kv[..., :NOPE], jnp.broadcast_to(k_r, (b, s, h, ROPE))], axis=-1)
        hsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        return hsd(q * jnp.asarray(scale, q.dtype)), hsd(k), \
            hsd(kv[..., NOPE:])

    def shipped(operands):
        def fn(c_q, c_kv, x, w_uq, w_ukv, w_kr):
            w_uq, w_ukv, w_kr = cast(w_uq, w_ukv, w_kr)
            w_uq, w_ukv = (deepseek._by_head((w, None), h)
                           for w in (w_uq, w_ukv))
            w_uq, (w_kr, _) = (deepseek._columns(
                lambda w: deepseek._rotary_columns_pairs_first(w, ROPE), w)
                for w in (w_uq, (w_kr, None)))
            tables = mla_operands.tables(_rope_angles(s, ROPE, THETA))
            k_r = mla_operands.turn((x @ w_kr)[:, None], *tables)
            with replaced(operands):
                return deepseek._kernel_operands(c_q, c_kv, k_r, w_uq, w_ukv,
                                                 tables)
        return fn

    return {"products": products, "parent": parent,
            "xla": shipped(mla_operands.reference),
            "kernel": shipped(mla_operands.operands)}


@contextlib.contextmanager
def replaced(operands):
    """``mla_operands.operands`` is ``operands`` while a form is traced."""
    from horovod_tpu.kernels import mla_operands

    real, mla_operands.operands = mla_operands.operands, operands
    try:
        yield
    finally:
        mla_operands.operands = real


def layers(positions, heads):
    """``(inputs, {name: fn(params, x) -> out})``: the whole layer as the
    cell runs it, in bf16 from fp32 parameters, the pass over q and k by the
    kernels and by XLA."""
    import dataclasses

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import mla_operands
    from horovod_tpu.models.deepseek import LatentAttention
    from horovod_tpu.models.transformer import joyai_llm_flash_config

    cfg = dataclasses.replace(joyai_llm_flash_config(dtype=jnp.bfloat16),
                              num_heads=heads)
    layer = LatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(50),
                          (1, positions, cfg.d_model), jnp.float32)
    params = nn.meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(51), x))

    def form(operands):
        def fn(params, x):
            with replaced(operands):
                return layer.apply(params, x)
        return fn

    return (params, x), {"xla": form(mla_operands.reference),
                         "kernel": form(mla_operands.operands)}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--positions", type=int, default=S)
    parser.add_argument("--heads", type=int, default=H)
    parser.add_argument("--out")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

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

    s, h = args.positions, args.heads
    keys = jax.random.split(jax.random.PRNGKey(49), 9)
    rows = [jax.random.normal(key, (1, s, width), jnp.bfloat16)
            for key, width in zip(keys, (Q_LATENT, KV_LATENT, D_MODEL))]
    weights = [0.02 * jax.random.normal(key, shape, jnp.float32)
               for key, shape in zip(keys[3:], (
                   (Q_LATENT, h * (NOPE + ROPE)), (KV_LATENT, h * (NOPE + DV)),
                   (D_MODEL, ROPE)))]
    moved = 2 * s * h * (3 * (NOPE + ROPE) + NOPE + 2 * DV)   # bytes, bf16

    def programs(fn):
        def both(inputs, cotangents):
            operands, back = jax.vjp(fn, *inputs)
            return operands, back(cotangents)
        return jax.jit(fn), jax.jit(both)

    def share(a, b):
        a, b = (np.asarray(t, np.float32) for t in (a, b))
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    inputs = rows + weights
    table = forms(s, h)

    def cotangents_of(form):
        return tuple(jax.random.normal(key, t.shape, t.dtype)
                     for key, t in zip(
                         keys[6:], jax.eval_shape(table[form], *inputs)))

    cotangents, flat = cotangents_of("parent"), cotangents_of("products")
    want, want_grads = programs(table["parent"])[1](inputs, cotangents)
    floor, failed = {}, False
    for name in ("products", "parent", "xla", "kernel"):
        forward, both = programs(table[name])
        given = flat if name == "products" else cotangents
        line = {"phase": "mla_assembly", "form": name, "positions": s,
                "heads": h,
                "forward_ms": round(timed(forward, *inputs), 4),
                "forward_backward_ms": round(timed(both, inputs, given), 4)}
        if name == "products":
            floor = line
        else:
            for key in ("forward_ms", "forward_backward_ms"):
                over = line[key] - floor[key]
                line[key.replace("_ms", "_assembly_ms")] = round(over, 4)
                passes = 1 if key == "forward_ms" else 2
                line[key.replace("_ms", "_gb_s")] = round(
                    passes * moved / over / 1e6, 1)
            got, grads = both(inputs, cotangents)
            line["mismatches"] = int(sum(
                (np.asarray(a, np.float32) != np.asarray(b, np.float32)).sum()
                for a, b in zip(got, want)))
            line["operands_off"] = max(map(share, got, want))
            line["gradients_off"] = max(map(share, grads, want_grads))
            failed |= max(line["operands_off"], line["gradients_off"]) > LIMIT
        emit(line)
    inputs, table = layers(s, h)
    cotangent = jax.random.normal(keys[6], inputs[1].shape, jnp.bfloat16)
    lines = {}
    for name in ("xla", "kernel"):
        forward, both = programs(table[name])
        lines[name] = {
            "phase": "mla_layer", "form": name, "positions": s, "heads": h,
            "forward_ms": round(timed(forward, *inputs), 4),
            "forward_backward_ms": round(timed(both, inputs, cotangent), 4)}
    lines["kernel"]["kernel_ahead_ms"] = round(
        lines["xla"]["forward_backward_ms"]
        - lines["kernel"]["forward_backward_ms"], 4)
    for line in lines.values():
        emit(line)
    emit({"phase": "verdict", "within_the_limit": not failed, "limit": LIMIT})
    if failed:
        raise SystemExit("a form's operands or gradients left the parent's")


if __name__ == "__main__":
    main()
