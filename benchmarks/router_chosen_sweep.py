"""What reading the router's k chosen scores costs, and writing their
cotangent (PERF.md §6, PR 48).

``parallel/moe.py::_route`` takes the top k of a row's E scores and reads the
k chosen ones back out of ``probs [n, E]``; backward the ``[n, k]`` cotangent
goes into ``[n, E]``.  This times, on the attached chip, at the six sparse
cells' (n, k, E), the read alone and the write alone, a call making ``REPS``
in turn:

- **gather**: ``jnp.take_along_axis(probs, experts, -1)`` and its transpose,
  a scatter-add of n k scalars: what ``_route`` ran until PR 48;
- **chosen**: ``_chosen`` as it ships: k selects over ``[n, E]`` each way,
  ``experts[:, j, None] == iota_E``, summed over E forward and over the k
  backward;
- **three_d**: the same compare as one select over ``[n, k, E]``, summed
  over E forward and over k backward: ahead of the gather at every shape and
  behind ``chosen`` wherever E fills a lane group.

``tail_forward_backward_ms`` is the router from its logits on (sigmoid, a
selection bias, the top k, the read, the renormalisation), forward +
backward: what XLA makes of the read among its neighbours, and what the form
was chosen by.  The run fails if a compare's read or cotangent differs from
``take_along_axis``'s in any bit.

Needs a TPU.  One JSON object a line; ``--out`` also writes them to a file.

Run: ``python benchmarks/router_chosen_sweep.py [--cases nemotron olmoe]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (rows, chosen, experts) of a step's router in each sparse cell.
CASES = {
    "nemotron": (8192, 22, 512),
    "joyai": (8192, 8, 256),
    "lfm2": (16384, 4, 32),
    "sdar": (16384, 8, 128),
    "smallthinker": (16384, 6, 64),
    "olmoe": (12288, 8, 64),
}

# A read of a tenth of a millisecond is under what the host's clock resolves
# a dispatch: a call makes REPS in turn, each on operands of its own.
REPS = 8


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


def variants(n_experts):
    """{name: (read(probs, experts) -> [n, k], write(experts, g) -> [n, E],
    the read with the write for its cotangent)} for routers of E outputs."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.parallel import moe

    def gather(probs, experts):
        return jnp.take_along_axis(probs, experts, axis=-1)

    def is_chosen(experts):
        return experts[:, :, None] == lax.broadcasted_iota(
            jnp.int32, (1, 1, n_experts), 2)

    @jax.custom_vjp
    def three_d(probs, experts):
        return jnp.sum(jnp.where(is_chosen(experts), probs[:, None, :], 0),
                       axis=2)

    def three_d_write(experts, g):
        return jnp.sum(jnp.where(is_chosen(experts), g[:, :, None], 0),
                       axis=1)

    three_d.defvjp(
        lambda probs, experts: (three_d(probs, experts), experts),
        lambda experts, g: (three_d_write(experts, g), None))

    def cotangent_of(read):
        def write(experts, g):
            zeros = jnp.zeros((experts.shape[0], n_experts), g.dtype)
            return jax.vjp(lambda p: read(p, experts), zeros)[1](g)[0]
        return write

    return {"gather": (gather, cotangent_of(gather), gather),
            "three_d": (three_d, three_d_write, three_d),
            "chosen": (moe._chosen, cotangent_of(moe._chosen), moe._chosen)}


def tail(read, k):
    """The router behind its logits, as Nemotron's and JoyAI's run it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def loss(logits, bias, c):
        probs = jax.nn.sigmoid(logits)
        _, experts = lax.top_k(lax.stop_gradient(probs + bias), k)
        weights = read(probs, experts)
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
        return jnp.sum(weights * c)

    return jax.value_and_grad(loss)


def case_phase(emit, name):
    """Times of every variant at one cell's shape; whether ``_chosen`` and
    its cotangent are ``take_along_axis``'s to the bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    n, k, e = CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(len(name) + n), 4)
    logits = jax.random.normal(ks[0], (REPS, n, e))
    probs = jax.nn.sigmoid(logits)
    bias = 0.05 * jax.random.normal(ks[1], (e,))
    experts = lax.top_k(probs + bias, k)[1]
    g = jax.random.normal(ks[2], (REPS, n, k))
    got = {}
    for variant, (read, write, chosen) in variants(e).items():
        reads = jax.jit(lambda ps, xs, read=read: lax.map(
            lambda a: read(*a), (ps, xs)))
        writes = jax.jit(lambda xs, gs, write=write: lax.map(
            lambda a: write(*a), (xs, gs)))
        got[variant] = (np.asarray(reads(probs, experts)),
                        np.asarray(writes(experts, g)))
        line = {"phase": "router_chosen", "case": name, "rows": n,
                "chosen": k, "experts": e, "variant": variant,
                "read_ms": round(timed(reads, probs, experts) / REPS, 4),
                "write_ms": round(timed(writes, experts, g) / REPS, 4)}
        tails = jax.jit(lambda ls, gs, chosen=chosen: lax.map(
            lambda a: tail(chosen, k)(a[0], bias, a[1]), (ls, gs)))
        line["tail_forward_backward_ms"] = round(
            timed(tails, logits, g) / REPS, 4)
        emit(line)
    return all((got[variant][i] == got["gather"][i]).all()
               for variant in got for i in (0, 1))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cases", nargs="*", default=list(CASES))
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

    exact = all([case_phase(emit, name) for name in args.cases])
    emit({"phase": "verdict", "equal_to_the_gather_bit_for_bit": exact})
    if not exact:
        raise SystemExit("a compare's read or cotangent is not the gather's")


if __name__ == "__main__":
    main()
