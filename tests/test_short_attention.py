"""``kernels/short_attention.py``: attention with a head's whole score tile
in fast memory (the pallas kernels, interpreted here) against the einsum it
stands in for, forward and in its three gradients; which shapes it takes; and
that where it does not run the program is the einsum's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.kernels import short_attention as sa
from horovod_tpu.models import transformer


def einsum(q, k, v, causal):
    """Softmax attention on ``[b, s, h, d]`` in fp32."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    s, d = q.shape[1], q.shape[3]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None],
                           scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def operands(shape, seed=0, spread=1.0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (spread * jax.random.normal(key, shape, jnp.float32)
               for key in keys[:3])
    return [t.astype(jnp.bfloat16) for t in (q, k, v)], \
        jax.random.normal(keys[3], shape, jnp.float32)


# (sequences, positions, heads, head width): two heads a lane group and one;
# more than one group; more than one sequence.
SHAPES = [(2, 128, 4, 64), (1, 256, 2, 64), (2, 128, 2, 128),
          (1, 256, 1, 128)]


def _close(got, want, what):
    # bf16 operands and a bf16 result: 2 ** -8 of the largest value.
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all(), what
    assert np.abs(got - want).max() <= 8e-3 * np.abs(want).max(), what
    assert np.linalg.norm(got - want) <= 4e-3 * np.linalg.norm(want), what


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_forward_is_the_einsums(shape, causal):
    (q, k, v), _ = operands(shape)
    got = jax.jit(lambda *a: sa.attention(*a, causal, interpret=True))(
        q, k, v)
    assert got.shape == shape and got.dtype == jnp.bfloat16
    _close(got, einsum(q, k, v, causal), "output")


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_gradients_are_the_einsums(shape, causal):
    """dq, dk and dv under a random cotangent, one backward kernel."""
    (q, k, v), w = operands(shape, seed=1)

    def through(attention):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(attention(*a).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))(q, k, v)

    got = through(lambda *a: sa.attention(*a, causal, interpret=True))
    want = through(lambda *a: einsum(*a, causal))
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == shape and a.dtype == jnp.bfloat16
        _close(a, b, name)


def test_scores_far_apart_stay_finite():
    """Rows whose largest score is far from the rest: the max is taken off
    before the exp, forward and (through the log-sum-exp) backward."""
    (q, k, v), w = operands((1, 128, 2, 64), seed=2, spread=6.0)
    out, pull = jax.vjp(lambda *a: sa.attention(*a, interpret=True), q, k, v)
    _close(out, einsum(q, k, v, False), "output")
    assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
               for g in pull(w.astype(out.dtype)))


@pytest.mark.parametrize("shape,taken", [
    ((512, 64, 16), True),          # bert-large-wfbp-1chip
    ((1024, 64, 16), True),
    ((512, 128, 8), True),
    ((1024, 128, 16), True),
    ((768, 64, 12), True),
    ((2048, 64, 16), True),         # the longest whose tiles fit
    ((2048, 128, 16), True),
    ((256, 64, 16), False),         # the einsum measured faster
    ((128, 64, 16), False),
    ((2176, 128, 16), False),       # stays on the einsum
    ((4096, 128, 16), False),       # the flash kernel's
    ((512, 64, 3), False),          # an odd number of heads of 64: half a
    ((512, 64, 1), False),          # lane group is left over
    ((512, 32, 16), False),
    ((512, 256, 4), False),
    ((520, 64, 16), False),         # not whole tiles of positions
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else None)
def test_takes_table(shape, taken):
    assert sa.takes(*shape) is taken
    assert not sa.takes(*shape, jnp.float32)


def test_attention_refuses_what_does_not_fit():
    (q, k, v), _ = operands((1, 128, 3, 64))
    assert not sa.fits(128, 64, 3)
    with pytest.raises(ValueError, match="no kernel"):
        sa.attention(q, k, v, interpret=True)
    (q, k, v), _ = operands((1, 128, 2, 64))
    with pytest.raises(ValueError, match="no kernel"):       # grouped KV
        sa.attention(q, k[:, :, :1], v[:, :, :1], interpret=True)
    with pytest.raises(ValueError, match="no kernel"):
        sa.attention(*(t.astype(jnp.float32) for t in (q, k, v)),
                     interpret=True)


def _lowered(shape, causal=False, **rule):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    return jax.jit(lambda q, k, v: transformer._scaled_dot_attention(
        q, k, v, causal, shape[3], **rule)).lower(x, x, x).as_text(
            debug_info=True)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_off_the_tpu_the_program_is_the_einsums(causal):
    """BERT-large's shape on this backend: two dot_generals, no kernel."""
    assert sa.takes(512, 64, 16)
    text = _lowered((8, 512, 16, 64), causal)
    assert "stablehlo.custom_call" not in text
    assert text.count("stablehlo.dot_general") == 2


@pytest.mark.parametrize("case,shape,causal,rule,called", [
    ("berts", (8, 512, 16, 64), False, {}, True),
    ("causal", (2, 1024, 8, 128), True, {}, True),
    ("longest", (1, 2048, 8, 128), False, {}, True),
    ("too_long", (1, 2176, 8, 128), False, {}, False),
    ("too_short", (1, 256, 8, 128), False, {}, False),
    ("odd_heads", (1, 512, 3, 64), False, {}, False),
    ("a_window_is_a_rules", (1, 512, 8, 128), True, {"window": 128}, False),
    ("block_diffusion_is_a_rules", (1, 512, 8, 128), False,
     {"block_diffusion": 4}, False),
])
def test_on_a_tpu_the_shape_alone_picks_the_kernel(case, shape, causal, rule,
                                                   called, monkeypatch):
    """With the backend named ``tpu`` the kernel is called for the shapes
    ``takes()`` admits, with ``causal`` handed on, under ``hvd.attn.short``;
    a mask that is a rule never reaches it."""
    calls, kernel = [], sa.attention

    def attention(q, k, v, causal=False):
        calls.append((q.shape, causal))
        return kernel(q, k, v, causal, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sa, "attention", attention)
    # The other kernels cannot be lowered here; which one is asked is enough.
    monkeypatch.setattr(transformer.masked_attention, "takes",
                        lambda *a: False)
    text = _lowered(shape, causal, **rule)
    assert calls == ([(shape, causal)] if called else [])
    assert ("hvd.attn.short" in text) is called
    assert ("hvd.attn.einsum" in text) is not called


def test_grouped_kv_heads_are_repeated_before_the_kernel(monkeypatch):
    """Grouped KV heads under no mask: repeated, then the same choice."""
    calls = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        sa, "attention",
        lambda q, k, v, causal=False: calls.append((k.shape, causal)) or q)
    q = jax.ShapeDtypeStruct((1, 512, 8, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16)
    jax.jit(lambda q, k, v: transformer._scaled_dot_attention(
        q, k, v, False, 128)).lower(q, kv, kv)
    assert calls == [((1, 512, 8, 128), False)]


def test_the_call_is_traced_once_however_many_layers():
    """Forward and backward are each one jitted function: 3 layers hold
    one body each in the lowered text, called three times."""
    (q, k, v), w = operands((1, 128, 2, 64))

    def loss(q, k, v):
        for _ in range(3):
            q = sa.attention(q, k, v, interpret=True)
        return jnp.sum(q.astype(jnp.float32) * w)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).as_text()
    for name in ("_forward", "_backward"):
        assert len([ln for ln in text.splitlines()
                    if ln.lstrip().startswith("func.func")
                    and f"@{name}" in ln]) == 1, name
        assert text.count(f"call @{name}(") == 3, name
