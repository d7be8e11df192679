"""``kernels/mla_operands.py``: latent attention's q and k in the attention
kernels' layout, the two kernels in interpret mode against the same function
in ``jax.numpy`` and against ``_rope`` on the rows, forward and the four
gradients, over several tiles, groups of heads and sequences, and what
``takes`` refuses.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.kernels import mla_operands as mo

THETA = 3.2e7


def tables(s, rope, positions=None):
    from horovod_tpu.models.transformer import _rope_angles

    return mo.tables(_rope_angles(s, rope, THETA, positions))


def inputs(b, h, s, nope, rope, dtype=jnp.bfloat16, seed=0):
    """The query's two flat products, ``k_nope``, the one rotary key as its
    projection leaves it, and cotangents of q and k."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(b, s, h * nope), (b, s, h * rope), (b, h, s, nope),
              (b, 1, s, rope), (b, h, s, nope + rope), (b, h, s, nope + rope)]
    return [jax.random.normal(k, shape).astype(dtype)
            for k, shape in zip(ks, shapes)]


def both(fn, q_nope, q_rope, k_nope, k_r, gq, gk):
    """(q, k) and the gradients of the four operands under ``(gq, gk)``."""
    out, back = jax.vjp(fn, q_nope, q_rope, k_nope, k_r)
    return tuple(out) + back((gq, gk))


def shipped(cos, sin, scale, **how):
    """As ``models/deepseek.py`` calls it: XLA turns the one rotary key."""
    return lambda q_nope, q_rope, k_nope, k_r: mo.operands(
        q_nope, q_rope, k_nope, mo.turn(k_r, cos, sin), cos, sin, scale,
        **how)


def on_the_rows(q_nope, q_rope, k_nope, k_r, scale, positions=None):
    """What ``models/deepseek.py`` ran until PR 49, on rows that already lie
    pairs-first: ``_rope`` on the rotary part a head at a time, a
    concatenation, the scale in the rows' dtype, ``k_r`` copied to every
    head; then the kernels' ``[b, h, s, .]``."""
    from horovod_tpu.models.transformer import _rope

    b, h, s, nope = k_nope.shape
    rope = k_r.shape[-1]
    q = jnp.concatenate(
        [q_nope.reshape(b, s, h, nope),
         _rope(q_rope.reshape(b, s, h, rope), THETA, positions)], axis=-1)
    k_r = _rope(k_r.transpose(0, 2, 1, 3), THETA, positions)
    k = jnp.concatenate([k_nope.transpose(0, 2, 1, 3),
                         jnp.broadcast_to(k_r, (b, s, h, rope))], axis=-1)
    return (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3), \
        k.transpose(0, 2, 1, 3)


def f32(t):
    return np.asarray(t, np.float32)


def assert_same_to_an_ulp(a, want, name):
    """bf16 values that are the same fp32 sums rounded at the same points:
    equal, but for the few entries where a product fused into a sum (a CPU's
    FMA) lands the other side of a rounding."""
    a, want = f32(a), f32(want)
    off = a != want
    assert off.mean() < 1e-4, (name, off.mean())
    np.testing.assert_allclose(a, want, rtol=2.0 ** -7, atol=1e-5,
                               err_msg=name)


@pytest.mark.parametrize("b,h,s,nope,rope,tile", [
    (1, 2, 32, 128, 64, 32),        # one tile, one step of two heads
    (2, 6, 64, 128, 64, 16),        # four tiles, two sequences, three steps
    (1, 4, 48, 128, 64, 16),        # three tiles, two steps
    (3, 2, 32, 128, 64, 8),         # three sequences of four tiles, one step
])
def test_kernels_are_the_jax_numpy_form_and_rope_on_the_rows(
        b, h, s, nope, rope, tile, monkeypatch):
    """bf16 operands through the two kernels: q, k and the cotangents of
    the query's two products and of ``k_nope`` are the ``jax.numpy`` form's
    and the rows' ``_rope``'s (the same fp32 sums, rounded at the same
    points); ``d k_r``, a sum over the heads that the kernel adds up in fp32
    and XLA on a CPU in bf16, lies within bf16's rounding of a float32 sum's
    rotation."""
    monkeypatch.setattr(mo, "TILE", tile)
    jax.clear_caches()
    assert mo.takes(s, h, nope, rope)
    args = inputs(b, h, s, nope, rope)
    scale = (nope + rope) ** -0.5
    positions = jnp.arange(s)[::-1] if h == 6 else None
    cos, sin = tables(s, rope, positions)
    got = both(shipped(cos, sin, scale, interpret=True), *args)
    plain = both(lambda *a: mo.reference(
        *a[:3], mo.turn(a[3], cos, sin), cos, sin, scale), *args)
    rows = both(lambda *a: on_the_rows(*a, scale, positions), *args)
    names = ("q", "k", "dq_nope", "dq_rope", "dk_nope")
    for name, a, want, parent in zip(names, got, plain, rows):
        assert a.dtype == jnp.bfloat16 and a.shape == want.shape
        assert_same_to_an_ulp(a, want, name)
        assert_same_to_an_ulp(a, parent, name)
    exact = both(lambda *a: on_the_rows(*a, scale, positions),
                 *(t.astype(jnp.float32) for t in args))[5]
    assert got[5].shape == (b, 1, s, rope)
    np.testing.assert_allclose(f32(got[5]), exact, atol=2.0 ** -6 * h ** 0.5,
                               rtol=2.0 ** -7)
    jax.clear_caches()


def test_reference_in_float32_is_rope_on_the_rows():
    """The path off the TPU and of every float32 twin: to the last bit, or
    to float32's rounding where XLA fuses a product into a sum."""
    args = inputs(2, 3, 24, 16, 8, jnp.float32, seed=1)
    scale = 24 ** -0.5
    cos, sin = tables(24, 8)
    assert not mo.takes(24, 3, 16, 8, jnp.float32)
    got = both(shipped(cos, sin, scale), *args)
    want = both(lambda *a: on_the_rows(*a, scale), *args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_the_rotation_rounds_once_and_nothing_goes_below_the_rows_dtype():
    """bf16 rows: two conversions up to fp32 and two back down in the whole
    of the ``jax.numpy`` form (q's rotary part and ``k_r``, each rounded
    once behind its rotation), then the scale in the rows' dtype; no other
    dtype appears."""
    args = inputs(1, 2, 16, 16, 8)[:4]
    cos, sin = tables(16, 8)
    jaxpr = jax.make_jaxpr(shipped(cos, sin, 24 ** -0.5))(*args)
    converts = [str(eqn.params["new_dtype"]) for eqn in jaxpr.eqns
                if eqn.primitive.name == "convert_element_type"]
    assert sorted(converts) == ["bfloat16", "bfloat16", "float32", "float32"]
    dtypes = {str(v.aval.dtype) for eqn in jaxpr.eqns for v in eqn.outvars}
    assert dtypes == {"bfloat16", "float32"}
    assert all(str(v.aval.dtype) == "bfloat16" for v in jaxpr.jaxpr.outvars)


def test_takes():
    assert mo.takes(8192, 32, 128, 64)
    assert not mo.takes(8192, 32, 128, 64, jnp.float32)  # the float32 twins
    assert not mo.takes(8192 + 16, 32, 128, 64)          # no whole tiles
    assert not mo.takes(8192, 32, 96, 64)                # off the lanes
    assert not mo.takes(8192, 32, 256, 64)               # not 128 + 64
    assert not mo.takes(8192, 32, 128, 32)
    assert not mo.takes(8192, 32, 128, 128)
    assert not mo.takes(8192, 31, 128, 64)               # heads two by two
    # Off the TPU, and without ``interpret``, the jax.numpy form.
    args = inputs(1, 2, mo.TILE, 128, 64)[:4]
    cos, sin = tables(mo.TILE, 64)
    q, k = mo.operands(*args, cos, sin, 0.1)
    assert q.shape == k.shape == (1, 2, mo.TILE, 192)
