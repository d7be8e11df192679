"""The gated delta rule in chunks (``horovod_tpu/kernels/gated_delta.py``):
``chunked()`` against the recurrence a token at a time of Qwen3-Next's plain
reference, values and gradients, at lengths of one, several and no whole
number of chunks; the two kernels in interpret mode against ``chunked()``;
what the kernels take; the inverse of a chunk's unit lower triangle.  Counts
and correctness only: nothing here is a timing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .helpers import load_reference
from .test_olmoe import rel_err
from .test_sdar import equations_of

ref = load_reference("qwen3-next-80b-a3b")


def rule_operands(seed, batch, s, hk, hv, dk, dv, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(key, (batch, s, hk, dk)) for key in keys[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (batch, s, hv, dv))
    g = -0.5 * jax.random.uniform(keys[3], (batch, s, hv))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, s, hv)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def token_by_token(q, k, v, g, beta):
    """The reference's recurrence over a batch, key heads given to the value
    heads they serve."""
    per = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(t, per, axis=2) for t in (q, k))
    return jax.vmap(ref.recurrent_rule)(q, k, v, g, beta)


def all_gradients(rule, operands):
    return jax.grad(
        lambda *a: jnp.sum(jnp.sin(rule(*a).astype(jnp.float32))),
        argnums=(0, 1, 2, 3, 4))(*operands)


@pytest.mark.parametrize("s", [1, 70, 128], ids=lambda s: f"{s}positions")
def test_chunked_is_the_recurrence_token_by_token(s):
    """``gated_delta.chunked``, values and the gradients of all five
    operands, against the recurrence a position at a time: at one position,
    a chunk and a part, and two whole chunks."""
    from horovod_tpu.kernels import gated_delta as gd

    operands = rule_operands(s, 1, s, 1, 2, 16, 8)
    with jax.default_matmul_precision("highest"):
        assert rel_err(gd.chunked(*operands), token_by_token(*operands)) \
            < 1e-5
        for got, want in zip(all_gradients(gd.chunked, operands),
                             all_gradients(token_by_token, operands)):
            if float(jnp.abs(want).max()):
                assert rel_err(got, want) < 1e-5
            else:
                assert not got.any()    # one position: no decay is read


def one_head_at_a_time(q, k, v, g, beta):
    """PR 50's kernels' arithmetic without the kernels: ``_chunk`` a value
    head with the MXU's operands in bf16, the chunks in order (what the grid
    step's loop over single heads computed before PR 51 paired them)."""
    from horovod_tpu.kernels import gated_delta as gd

    batch, s = q.shape[:2]
    hv, dv = v.shape[2:]

    def carry(state, chunk_in):
        o, state = gd._chunk(*chunk_in, state, dot=jnp.bfloat16)
        return state, o

    _, o = jax.lax.scan(
        carry, jnp.zeros((batch, hv, q.shape[3], dv), jnp.float32),
        gd._chunks_of(q, k, v, g, beta, gd.CHUNK))
    return o.transpose(1, 0, 3, 2, 4).reshape(v.shape).astype(v.dtype)


@pytest.mark.parametrize("hk,hv", [(1, 2), (4, 16), (2, 4), (2, 2), (1, 3)])
def test_the_kernels_in_interpret_mode_are_chunked(hk, hv):
    """``hvd_gated_delta_fwd`` and ``_bwd`` in interpret mode at heads of
    128, bf16, three chunks: ``o`` and the five cotangents within bf16's
    rounding of ``chunked``'s on the same operands.  A grid step takes its
    value heads a pair of one key head at a time: (1, 2) is one pair, (2, 4)
    the cell's ratio, a pair a key head, (4, 16) two grid steps of eight
    heads, two pairs a key head.  One value head a key head (2, 2) or an odd
    number (1, 3) leaves no pairs inside a key head: ``takes`` refuses them
    and ``gated_delta`` is ``chunked`` there, whatever ``interpret`` says."""
    from horovod_tpu.kernels import gated_delta as gd

    s = 192 if hv < 16 else 128
    pairs = (hv // hk) % 2 == 0
    assert gd.takes(s, hk, hv, 128, 128) == pairs
    assert gd.heads_a_step(hk, hv) == (min(hv, 8) if pairs else 0)
    operands = rule_operands(hv, 1, s, hk, hv, 128, 128, jnp.bfloat16)
    kernels = jax.jit(lambda *a: gd.gated_delta(*a, interpret=True))
    names = [eqn.params["name"] for eqn in equations_of(
        kernels.trace(*operands).jaxpr, "pallas_call")]
    assert names == ([gd.FWD_NAME] if pairs else [])
    with jax.default_matmul_precision("highest"):
        want = gd.chunked(*operands).astype(jnp.float32)
        got = kernels(*operands)
        assert got.dtype == jnp.bfloat16
        assert rel_err(got, want) < 2e-2
        grads = all_gradients(kernels, operands)
        for got, want in zip(grads, all_gradients(gd.chunked, operands)):
            assert got.dtype == want.dtype and got.shape == want.shape
            assert rel_err(got, want.astype(jnp.float32)) < 2e-2


def test_a_pair_of_heads_is_two_heads():
    """The paired kernel's ``o`` against the same chunks a head at a time
    (PR 50's loop): the block diagonal's zeros add exact zeros and every
    operand is rounded where it was, so the two agree to 1e-6 of ``o``'s
    norm, far inside bf16's rounding of either."""
    from horovod_tpu.kernels import gated_delta as gd

    operands = rule_operands(7, 1, 192, 2, 4, 128, 128, jnp.bfloat16)
    got = gd.gated_delta(*operands, interpret=True)
    assert rel_err(got, one_head_at_a_time(*operands)) < 1e-6


def test_what_the_kernels_take():
    from horovod_tpu.kernels import gated_delta as gd

    assert gd.takes(8192, 16, 32, 128, 128)
    assert gd.heads_a_step(16, 32) == 8
    assert not gd.takes(8192 + 64 - 1, 16, 32, 128, 128)    # no whole chunks
    assert not gd.takes(8192, 16, 32, 64, 128)
    assert not gd.takes(8192, 16, 32, 128, 128, dtype=jnp.float32)
    assert not gd.takes(8192, 3, 32, 128, 128)
    assert not gd.takes(8192, 32, 32, 128, 128)             # no pairs
    assert not gd.takes(8192, 8, 24, 128, 128)              # an odd ratio
    assert gd.takes(8192, 4, 32, 128, 128) and gd.heads_a_step(4, 32) == 8
    operands = rule_operands(0, 1, 64, 1, 2, 128, 128, jnp.bfloat16)
    with pytest.raises(ValueError, match="beta"):
        gd.gated_delta(*operands[:4], operands[4][..., :1])


@pytest.mark.parametrize("c", [8, 16, 64])
def test_the_inverse_of_a_unit_lower_triangle(c):
    """``unit_lower_inverse`` against ``numpy.linalg.inv`` in float64: on a
    chunk's kind of matrix, and where every key is the same and nothing
    decays (all ones under the diagonal), whose plain power series cancels
    binomial coefficients of 1e18 at 64 and the blocked one does not; its
    cotangent is ``-T^T dT T^T`` under the diagonal."""
    from horovod_tpu.kernels import gated_delta as gd

    lower = np.tril(np.ones((c, c)), -1)
    with jax.default_matmul_precision("highest"):
        for a in (lower * np.random.RandomState(c).uniform(-1, 1, (c, c)),
                  lower):
            want = np.linalg.inv(np.eye(c) + a)
            got = gd.unit_lower_inverse(jnp.asarray(a, jnp.float32))
            assert rel_err(got, jnp.asarray(want, jnp.float32)) < 1e-5
        a = jnp.asarray(0.3 * lower * np.random.RandomState(1)
                        .normal(size=(c, c)), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(0), (c, c))
        got = jax.grad(lambda a: jnp.sum(gd.unit_lower_inverse(a) * w))(a)
        want = jax.grad(lambda a: jnp.sum(
            jnp.linalg.inv(jnp.eye(c) + jnp.tril(a, -1)) * w))(a)
    assert rel_err(got, want) < 1e-5 and not jnp.triu(got).any()
