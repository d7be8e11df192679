"""The plain reference: the same task stepped with nothing of the framework.

A jitted ``value_and_grad`` of the configuration's loss, the gradients of the
world's rank batches averaged in a Python loop, one jitted optax update.  It
imports nothing from ``horovod_tpu`` (the configuration's file imports the
model and no more), so a step builder that drops an update, reduces to the
wrong mean or feeds a rank another rank's data disagrees with it.
"""

from __future__ import annotations

import functools

import jax
import optax


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63: the driver's seeds pass
    2**31, which one 32-bit word does not hold."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def rank_key(seed: int, rank: int):
    """The key of one rank's data (1000 + rank, as chip_smoke.py seeds it)."""
    return jax.random.fold_in(seed_key(seed), 1000 + rank)


def make_grad(config):
    """The jitted ``value_and_grad`` of the configuration's loss:
    ``(params, aux, batch) -> ((loss, new aux), gradients)``.  One function
    object per configuration, so that the eager step builder, which is plain
    JAX up to this point too, shares its compiled program and a run loads it
    once."""
    if not hasattr(config, "_chip_bench_grad"):
        config._chip_bench_grad = jax.jit(
            jax.value_and_grad(config.loss, has_aux=True))
    return config._chip_bench_grad


def reference_losses(config, seed: int, world: int, steps: int, device):
    """Rank 0's loss before each of ``steps`` plain synchronous steps.

    Every rank keeps its own auxiliary state (BatchNorm statistics are per
    process in the eager plane) and sees its own batch; the parameters follow
    the mean gradient.  All state lives on ``device`` and is dropped on
    return."""
    put = functools.partial(jax.device_put, device=device)
    tx = config.optimizer(world)
    params, aux = jax.jit(config.init)(put(seed_key(seed)))
    auxs = [aux] * world
    make_batch = jax.jit(config.make_batch)
    batches = [make_batch(put(rank_key(seed, r))) for r in range(world)]
    opt_state = jax.jit(tx.init)(params)
    grad = make_grad(config)
    mean = jax.jit(lambda *trees: jax.tree_util.tree_map(
        lambda *g: sum(g) / len(g), *trees))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def update(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    losses = []
    for _ in range(steps):
        grads = []
        for r in range(world):
            (loss, auxs[r]), g = grad(params, auxs[r], batches[r])
            grads.append(g)
            if r == 0:
                losses.append(loss)
        g = mean(*grads) if world > 1 else grads[0]
        del grads
        params, opt_state = update(params, opt_state, g)
    return [float(x) for x in losses]
