"""``kernels/causal_conv.py``: the mixers' depthwise causal convolution, its
two kernels in interpret mode against the same function in ``jax.numpy`` in
float32, forward and the three cotangents, with and without a bias, alone
and as a window of a wider row, where a tile ends, at a sequence's first rows
and with two sequences in a batch, a fault planted in the backward kernel,
and what ``takes`` refuses.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.kernels import causal_conv as cc
from horovod_tpu.kernels import short_conv as sc

# One ulp of bf16 at the values' size, as tests/test_short_conv.py has it.
ULP = 2.0 ** -7


def inputs(b, s, c, taps, biased, width=None, seed=0):
    """(row, w, bias, cotangent of y); the row is ``c`` wide unless
    ``width`` says otherwise."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    row = jax.random.normal(ks[0], (b, s, width or c)).astype(jnp.bfloat16)
    w = 0.5 * jax.random.normal(ks[1], (c, taps), jnp.float32)
    bias = jax.random.normal(ks[2], (c,), jnp.float32) if biased else None
    g = jax.random.normal(ks[3], (b, s, c)).astype(jnp.bfloat16)
    return row, w, bias, g


def through(fn, g, biased):
    def loss(x, w, bias):
        return jnp.sum(fn(x, w, bias).astype(jnp.float32)
                       * g.astype(jnp.float32))

    return jax.value_and_grad(loss, argnums=(0, 1, 2) if biased else (0, 1))


def kernel(x, w, bias=None, within=None):
    return cc.causal_conv(x, w, bias, within=within, interpret=True)


def by_hand(x, w, bias):
    """Position by position and tap by tap over a zero history, in
    float64."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    taps = w.shape[1]
    pre = np.zeros(x.shape)
    for t in range(x.shape[1]):
        for j in range(taps):
            at = t - (taps - 1) + j
            if at >= 0:
                pre[:, t] += w[:, j] * x[:, at]
    if bias is not None:
        pre += np.asarray(bias, np.float64)
    return pre / (1 + np.exp(-pre))


def held_to_float32(got, want, biased):
    """The kernel's loss and cotangents beside ``jax.grad`` of
    :func:`cc.reference` in float32."""
    (loss, grads), (want_loss, wants) = got, want
    # y rounds to bf16 where the form in float32 does not: an ulp a term.
    assert abs(float(loss) - float(want_loss)) \
        < 1e-3 * abs(float(want_loss)) + ULP * grads[0].size ** 0.5
    assert grads[0].dtype == jnp.bfloat16 and grads[1].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(grads[0], np.float32),
                               np.asarray(wants[0]), rtol=2 * ULP,
                               atol=2 * ULP)
    for d, d_want in zip(grads[1:], wants[1:]):
        assert d.dtype == jnp.float32 and d.shape == d_want.shape
        np.testing.assert_allclose(
            d, d_want, rtol=1e-5, atol=2e-5 * float(jnp.abs(d_want).max()))
    assert len(grads) == (3 if biased else 2)


@pytest.mark.parametrize("b,s,c,taps,biased", [
    (1, 64, 128, 4, True),          # one tile a sequence
    (1, 64, 128, 4, False),
    (2, 96, 128, 4, True),          # three tiles of 32
    (2, 96, 128, 3, False),
    (1, 1024, 128, 4, True),        # two tiles of 512
    (1, 32, 128, 2, True),
    (1, 48, 1280, 4, True),         # nemotron's width: five blocks of 256
    (1, 32, 4352, 4, True),         # granite's: seventeen blocks of 256
    (1, 32, 4352, 3, False),
    (1, 32, 1024, 4, False),        # two blocks of 512
])
def test_kernels_match_the_jax_numpy_form_in_float32(b, s, c, taps, biased):
    """Forward and the cotangents of ``x``, the taps and the bias."""
    x, w, bias, g = inputs(b, s, c, taps, biased)
    assert cc.takes(s, c, taps)
    y = kernel(x, w, bias)
    want_y = cc.reference(x.astype(jnp.float32), w, bias)
    assert y.shape == (b, s, c) and y.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y, np.float32), want_y,
                               rtol=ULP, atol=ULP)
    np.testing.assert_allclose(np.asarray(want_y, np.float64),
                               by_hand(x, w, bias), rtol=1e-4, atol=1e-4)
    held_to_float32(through(kernel, g, biased)(x, w, bias),
                    through(cc.reference, g, biased)(
                        x.astype(jnp.float32), w, bias), biased)


@pytest.mark.parametrize("s,width,start,c,taps,biased", [
    (48, 1000, 256, 256, 4, True),      # a row of no whole lanes
    (96, 2320, 1024, 1280, 4, True),    # nemotron's [z, xBC, dt]
    (32, 8512, 4096, 4352, 4, True),    # granite's
    (64, 768, 0, 512, 4, False),        # qwen3-next's [q ; k ; v ; z]
    (32, 640, 192, 256, 4, True),       # a window off the lanes: x itself
])
def test_a_window_of_a_wider_row_is_read_where_it_lies(s, width, start, c,
                                                       taps, biased):
    """``within=(row, start)``: the same numbers as from the slice, the
    row's cotangent zero outside the window, and no read of ``x``."""
    row, w, bias, g = inputs(2, s, c, taps, biased, width=width)

    def windowed(row, w, bias):
        x = row[..., start:start + c]
        return kernel(jnp.full_like(x, jnp.nan) if start % 128 == 0 else x,
                      w, bias, within=(row, start))

    def sliced(row, w, bias):
        return cc.reference(
            row[..., start:start + c].astype(jnp.float32), w, bias)

    np.testing.assert_array_equal(
        windowed(row, w, bias), kernel(row[..., start:start + c], w, bias))
    got = through(windowed, g, biased)(row, w, bias)
    held_to_float32(got, through(sliced, g, biased)(row, w, bias), biased)
    d_row = np.asarray(got[1][0], np.float32)
    assert d_row.shape == row.shape
    assert not d_row[..., :start].any() and not d_row[..., start + c:].any()
    with pytest.raises(ValueError, match="within"):
        kernel(row[..., start:start + c], w, bias,
               within=(row, width - c + 128))


@pytest.mark.parametrize("taps", [2, 3, 4])
def test_the_first_rows_see_a_zero_history(taps):
    """The first ``L - 1`` positions written out by hand: position 0 is the
    last tap's term alone."""
    x, w, bias, _ = inputs(2, 32, 128, taps, True)
    y = np.asarray(kernel(x, w, bias), np.float32)
    f, wn, bn = np.asarray(x, np.float32), np.asarray(w), np.asarray(bias)

    def silu(pre):
        return pre / (1 + np.exp(-pre))

    for t in range(taps - 1):
        pre = bn + sum(wn[:, taps - 1 - k] * f[:, t - k]
                       for k in range(t + 1))
        np.testing.assert_allclose(y[:, t], silu(pre), rtol=ULP, atol=ULP)
    np.testing.assert_allclose(y[:, 0], silu(wn[:, -1] * f[:, 0] + bn),
                               rtol=ULP, atol=ULP)


def test_rows_before_a_tile_come_from_the_tile_before_and_g_from_behind():
    """Changing a tile's last row changes the next tile's first three rows
    and no others of it, and ``dx`` of a tile's last rows hears from the
    next tile's first rows through ``g``, the activation's derivative
    included."""
    x, w, bias, g = inputs(1, 96, 128, 4, True)
    tile = sc._tile(96, cc._TILE)
    assert tile == 32                               # three tiles
    other = x.at[0, tile - 1].set(0)
    moved = np.any(np.asarray(kernel(x, w, bias) != kernel(other, w, bias)),
                   axis=-1)[0]
    assert sorted(np.flatnonzero(moved)) == [tile - 1, tile, tile + 1,
                                             tile + 2]
    only_next = g.at[0, :tile].set(0).at[0, 2 * tile:].set(0)
    d_x = through(kernel, only_next, True)(x, w, bias)[1][0]
    want = through(cc.reference, only_next, True)(
        x.astype(jnp.float32), w, bias)[1][0]
    heard = np.any(np.asarray(d_x[0, :tile] != 0), axis=-1)
    assert sorted(np.flatnonzero(heard)) == [tile - 3, tile - 2, tile - 1]
    np.testing.assert_allclose(np.asarray(d_x, np.float32), want,
                               rtol=2 * ULP, atol=2 * ULP)


def test_a_batchs_second_sequence_reads_nothing_of_the_first():
    """Two sequences in a batch: each is what it is alone, forward and
    backward, whatever stands in the other."""
    x, w, bias, g = inputs(2, 96, 128, 4, True)
    both = kernel(x, w, bias)
    for i in range(2):
        np.testing.assert_array_equal(both[i], kernel(x[i:i + 1], w, bias)[0])
    np.testing.assert_array_equal(both[1], kernel(x[::-1], w, bias)[0])
    loud = x.at[0].set(100.0)
    np.testing.assert_array_equal(both[1], kernel(loud, w, bias)[1])
    d_both = through(kernel, g, True)(x, w, bias)[1][0]
    d_loud = through(kernel, g, True)(loud, w, bias)[1][0]
    d_one = through(kernel, g[1:], True)(x[1:], w, bias)[1][0]
    np.testing.assert_array_equal(d_both[1], d_one[0])
    np.testing.assert_array_equal(d_loud[1], d_one[0])


def test_a_backward_that_drops_the_rows_behind_a_tile_is_refused(monkeypatch):
    """The planted fault: ``dx`` without the ``g`` of the rows behind the
    tile.  The comparison that passes the kernel refuses it."""
    x, w, bias, g = inputs(1, 80, 128, 4, True)     # five tiles of 16
    want = through(cc.reference, g, True)(x.astype(jnp.float32), w, bias)

    def backward():
        # Not through the jitted function: the fault must not stay in its
        # cache.
        d = cc._backward.__wrapped__(x, w, bias, g, start=0, interpret=True)
        return want[0], d

    held_to_float32(backward(), want, True)
    monkeypatch.setattr(
        cc, "_later",
        lambda rows, after, k: sc._later(rows, jnp.zeros_like(after), k))
    with pytest.raises(AssertionError, match="Mismatched elements"):
        held_to_float32(backward(), want, True)


@pytest.mark.parametrize("s,c,dtype", [
    (32, 128, jnp.float32),     # a configuration's float32 twin
    (32, 192, jnp.bfloat16),    # no whole lanes
    (24, 128, jnp.bfloat16),    # no whole rows of 16
])
def test_what_takes_refuses_runs_the_jax_numpy_form_to_the_bit(s, c, dtype):
    assert not cc.takes(s, c, 4, dtype)
    x, w, bias, _ = inputs(1, s, c, 4, True)
    x = x.astype(dtype)
    for b in (bias, None):
        y = kernel(x, w, b)
        assert y.dtype == dtype
        np.testing.assert_array_equal(y, cc.reference(x, w, b))
        np.testing.assert_array_equal(kernel(x, w, b, within=(x, 0)), y)


def test_takes_and_the_form_off_the_tpu():
    for c in (4352, 1280, 8192):                    # the three cells'
        assert cc.takes(8192, c, 4)
    assert cc.takes(8192, 128, 7) and not cc.takes(8192, 128, 8)
    assert not cc.takes(8192, 128, 0) and not cc.takes(0, 128, 4)
    assert cc._columns(4352, 4096) == 256 and cc._columns(1280, 1024) == 256
    assert cc._columns(8192) == 512 and cc._columns(128) == 128
    # Off the TPU and without `interpret` everything is the jax.numpy form.
    x, w, bias, _ = inputs(1, 64, 128, 4, True)
    np.testing.assert_array_equal(cc.causal_conv(x, w, bias),
                                  cc.reference(x, w, bias))
    with pytest.raises(ValueError, match="for taps"):
        cc.causal_conv(x[..., :64], w, bias)
    with pytest.raises(ValueError, match="for taps"):
        cc.causal_conv(x, w, bias[:64])


def test_the_form_with_a_bias_is_the_padded_slices_that_went():
    """``models/mamba2.py::causal_conv`` as it stood until PR 57 (a padded
    fp32 copy, four shifted slices, the bias, silu), written out: the one
    ``jax.numpy`` form is it within float32 rounding, and without a bias it
    is the form with a bias of zero."""
    x, w, bias, _ = inputs(2, 40, 96, 4, True, seed=57)
    x = x.astype(jnp.float32)
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    went = jax.nn.silu(sum(w[:, j] * padded[:, j:j + 40] for j in range(4))
                       + bias)
    np.testing.assert_allclose(cc.reference(x, w, bias), went, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(cc.reference(x, w),
                               cc.reference(x, w, jnp.zeros_like(bias)),
                               rtol=0, atol=0)
    # In bf16 the sums stay in fp32 and round once.
    low = cc.reference(x.astype(jnp.bfloat16), w, bias)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low, np.float32), went, rtol=ULP,
                               atol=ULP)


def test_the_mixers_hold_no_convolution_of_their_own():
    from horovod_tpu.models import gated_delta, mamba2

    assert mamba2.causal_conv is cc.causal_conv is gated_delta.causal_conv
    for module in (mamba2, gated_delta):
        assert "jnp.roll" not in open(module.__file__).read()
        assert "jnp.pad" not in open(module.__file__).read()


def test_pallas_is_not_imported_with_the_module():
    """The mixers' modules import this one wherever such a layer is
    built."""
    import subprocess
    import sys

    code = ("import sys; import horovod_tpu.kernels.causal_conv; "
            "print(any(m.startswith('jax.experimental.pallas') "
            "for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True)
    assert out.stdout.strip() == "False", out.stdout


def test_the_metric_reads_both_kernels_by_their_names():
    """``causal_conv_ms_step``: the op line's names under the module's own
    pattern, in the three cells whose mixers run the convolution, and
    nothing of LFM2's kernels."""
    import json
    import os
    import re

    from .helpers import REPO_ROOT

    with open(os.path.join(REPO_ROOT, "chip_bench/metrics",
                           "causal_conv_ms_step.json")) as f:
        metric = json.load(f)
    (reader,) = metric["readers"]
    assert reader == {"reduction": "trace_op_ms_per_step",
                      "pattern": cc.OP_LINE_NAMES}
    assert metric["ranks"] == "rank0"
    for name in (cc.FWD_NAME, cc.BWD_NAME, cc.FWD_NAME + ".17"):
        assert re.search(reader["pattern"], name)
    for name in (sc.FWD_NAME, sc.BWD_NAME, "hvd_ssd_scan_fwd"):
        assert not re.search(reader["pattern"], name)
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == metric["name"]]
    assert entry["layer"] == "kernel" and entry["source"] == "device_trace"
    assert entry["moves"] == "samples_per_s_chip"
    # PR 66 appended its cell, the fourth whose mixers run the kernels.
    assert sorted(entry["workloads"]) == [
        "granite-4.0-h-micro-wfbp-1chip",
        "ling-3.0-flash-vl-wfbp-1chip",
        "nemotron-3-super-120b-a12b-wfbp-1chip",
        "qwen3-next-80b-a3b-wfbp-1chip"]
