"""The chunked state-space scan (``kernels/ssd_scan.py``, Mamba-2's, which
Nemotron's and Granite's mixers run): the chunked form against the
token-by-token recurrence of the plain reference.
``tests/test_ssd_scan_kernels.py`` holds the two pallas kernels to the chunked
form (a file of its own since PR 60: together the two were the suite's longest
file), ``tests/test_nemotron.py`` has the mixer and the model.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from .helpers import load_reference
from .test_olmoe import rel_err

ref = load_reference("nemotron-3-super-120b-a12b")


def scan_inputs(seed, batch, s, heads, p, groups, n, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (batch, s, heads, p)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, s, heads)) - 2),
            -jnp.exp(jax.random.uniform(ks[2], (heads,), maxval=2.7)),
            jax.random.normal(ks[3], (batch, s, groups, n)).astype(dtype),
            jax.random.normal(ks[4], (batch, s, groups, n)).astype(dtype))


def recurrence(x, dt, a, b, c):
    return jax.vmap(lambda x, dt, b, c: ref._recurrence(x, dt, a, b, c))(
        x, dt, b, c)


@pytest.mark.parametrize("s,heads,groups,chunk", [
    (200, 4, 2, 64), (64, 2, 1, 16), (37, 6, 3, 128), (128, 4, 4, 128)],
    ids=["no_multiple_two_groups", "one_group", "shorter_than_a_chunk",
         "a_head_a_group"])
def test_the_chunked_scan_is_the_recurrence(s, heads, groups, chunk):
    """``ssd_scan.chunked`` (the path off the TPU) against the reference's
    token-by-token recurrence, forward and the gradients of all five."""
    from horovod_tpu.kernels import ssd_scan

    args = scan_inputs(0, 2, s, heads, 8, groups, 16)
    weight = jax.random.normal(jax.random.PRNGKey(7), args[0].shape)

    def loss(fn):
        return lambda *a: jnp.sum(weight * jnp.tanh(fn(*a)))

    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
        got = ssd_scan.ssd_scan(*args, chunk=chunk)
        assert rel_err(got, want) < 1e-5
        want_grads = jax.grad(loss(recurrence), argnums=range(5))(*args)
        grads = jax.grad(loss(functools.partial(ssd_scan.ssd_scan,
                                                chunk=chunk)),
                         argnums=range(5))(*args)
    for name, g, w in zip("x dt a b c".split(), grads, want_grads):
        assert rel_err(g, w) < 2e-4, name
