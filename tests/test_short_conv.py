"""``kernels/short_conv.py``: the gated short convolution's two kernels in
interpret mode against the same function in ``jax.numpy``, forward and the
three gradients, where a tile ends, at a sequence's first rows and with two
sequences in a batch, and what ``takes`` refuses.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.kernels import short_conv as sc


def inputs(b, s, d, taps, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcx = jax.random.normal(ks[0], (b, s, 3 * d)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[1], (d, taps), jnp.float32)
    g = jax.random.normal(ks[2], (b, s, d)).astype(jnp.bfloat16)
    return bcx, w, g


def through(fn, g):
    def loss(bcx, w):
        return jnp.sum(fn(bcx, w).astype(jnp.float32)
                       * g.astype(jnp.float32))

    return jax.value_and_grad(loss, argnums=(0, 1))


def kernel(bcx, w):
    return sc.gated_conv(bcx, w, interpret=True)


def by_hand(bcx, w):
    """Position by position and tap by tap, in float64."""
    bcx, w = np.asarray(bcx, np.float64), np.asarray(w, np.float64)
    b, s, width = bcx.shape
    d, taps = w.shape
    z = bcx[..., :d] * bcx[..., 2 * d:]
    y = np.zeros((b, s, d))
    for t in range(s):
        for j in range(taps):
            at = t - (taps - 1) + j
            if at >= 0:
                y[:, t] += w[:, j] * z[:, at]
    return y * bcx[..., d:2 * d]


# One ulp of bf16 at the values' size: the kernel and the jax.numpy form
# round the same fp32 sums, whose order may differ.
ULP = 2.0 ** -7


@pytest.mark.parametrize("b,s,d,taps", [
    (2, 64, 128, 3),        # one tile a sequence
    (1, 512, 256, 3),       # two tiles of 256: rows cross a tile's end
    (2, 96, 128, 3),        # six tiles of 16
    (2, 48, 640, 4),        # two column chunks, four taps
    (1, 32, 128, 1),        # one tap: the gates alone
])
def test_kernel_matches_the_jax_numpy_form(b, s, d, taps):
    """Forward and the gradients of ``bcx``, the taps and, through ``g``, of
    the output."""
    bcx, w, g = inputs(b, s, d, taps)
    assert sc.takes(s, d, taps)
    y, want_y = kernel(bcx, w), sc.reference(bcx, w)
    assert y.shape == (b, s, d) and y.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want_y, np.float32),
                               rtol=ULP, atol=ULP)
    np.testing.assert_allclose(np.asarray(want_y, np.float64),
                               by_hand(bcx, w), rtol=2 * ULP, atol=2 * ULP)
    (loss, (d_bcx, d_w)), (want, (want_bcx, want_w)) = \
        through(kernel, g)(bcx, w), through(sc.reference, g)(bcx, w)
    assert abs(float(loss) - float(want)) < 1e-3 * abs(float(want)) + 1e-2
    assert d_bcx.dtype == jnp.bfloat16 and d_w.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(d_bcx, np.float32),
                               np.asarray(want_bcx, np.float32),
                               rtol=2 * ULP, atol=2 * ULP)
    np.testing.assert_allclose(d_w, want_w, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want_w).max()))


def test_rows_before_a_tile_come_from_the_tile_before():
    """At a tile's first rows the taps reach into the tile before: changing
    that tile's last row changes the next tile's first two rows and no
    others, and the gradient flows back the same way."""
    bcx, w, g = inputs(1, 512, 128, 3)
    tile = sc._tile(512)
    assert tile == 256
    other = bcx.at[0, tile - 1].set(0)
    moved = np.any(np.asarray(kernel(bcx, w) != kernel(other, w)), axis=-1)[0]
    assert sorted(np.flatnonzero(moved)) == [tile - 1, tile, tile + 1]
    # d_bcx of the tile's last row hears from the next tile's first rows.
    only_next = g.at[0, :tile].set(0)
    d_bcx = through(kernel, only_next)(bcx, w)[1][0]
    want = through(sc.reference, only_next)(bcx, w)[1][0]
    heard = np.any(np.asarray(d_bcx[0, :tile, :128] != 0), axis=-1)
    assert sorted(np.flatnonzero(heard)) == [tile - 2, tile - 1]
    np.testing.assert_allclose(np.asarray(d_bcx, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2 * ULP, atol=2 * ULP)


def test_a_sequences_first_rows_see_zeros_and_no_other_sequence():
    """Two sequences in a batch: each is what it is alone (nothing leaks
    from the first sequence's last rows into the second's first), and a
    sequence's first row is the last tap's term alone."""
    bcx, w, g = inputs(2, 64, 128, 3)
    both = kernel(bcx, w)
    for i in range(2):
        np.testing.assert_array_equal(both[i], kernel(bcx[i:i + 1], w)[0])
    swapped = kernel(bcx[::-1], w)
    np.testing.assert_array_equal(both[1], swapped[0])
    f32 = np.asarray(bcx, np.float32)
    first = f32[:, 0, 128:256] * np.asarray(w)[:, 2] \
        * f32[:, 0, :128] * f32[:, 0, 256:]
    np.testing.assert_allclose(np.asarray(both[:, 0], np.float32), first,
                               rtol=ULP, atol=ULP)
    # And backward: the cotangent of a sequence's rows is its own.
    d_both = through(kernel, g)(bcx, w)[1][0]
    d_one = through(kernel, g[1:])(bcx[1:], w)[1][0]
    np.testing.assert_array_equal(d_both[1], d_one[0])


def test_takes_refuses_what_the_kernel_does_not_take_and_the_form_stands_in():
    assert sc.takes(8192, 2048, 3)                  # the cell's
    assert not sc.takes(8192, 2048, 3, jnp.float32)
    assert not sc.takes(8200, 2048, 3)              # no whole rows of 16
    assert not sc.takes(8192, 2000, 3)              # no whole lanes
    assert not sc.takes(8192, 2048, 9) and not sc.takes(8192, 2048, 0)
    assert not sc.takes(0, 2048, 3)
    assert sc._tile(8192) == 256 and sc._tile(48) == 16
    # A shape it refuses goes through jax.numpy whatever `interpret` says,
    # and so does everything off the TPU.
    bcx, w, _ = inputs(1, 24, 128, 3)
    np.testing.assert_array_equal(kernel(bcx, w), sc.reference(bcx, w))
    f32 = bcx.astype(jnp.float32)
    assert kernel(f32, w).dtype == jnp.float32
    big = inputs(1, 64, 128, 3)[0]
    np.testing.assert_array_equal(sc.gated_conv(big, w),
                                  sc.reference(big, w))
    with pytest.raises(ValueError, match="for taps"):
        sc.gated_conv(bcx[..., :256], w)


def test_pallas_is_not_imported_with_the_module():
    """``models/__init__.py`` imports every kernel module in every cell."""
    import subprocess
    import sys

    code = ("import sys; import horovod_tpu.kernels.short_conv; "
            "print(any(m.startswith('jax.experimental.pallas') "
            "for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True)
    assert out.stdout.strip() == "False", out.stdout
