"""``kernels/rows_to_tokens.py``: a chunk's rows added up by token (the pallas
kernel, interpreted here) against the scatter-add it stands in for, alone and
inside ``moe_ffn(held=)``; and that where it does not run the program is the
parent's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.kernels import rows_to_tokens as rt

TOKENS = 512        # two tiles of 256


def scatter(rows, token, tokens, weights=None):
    rows = rows.astype(jnp.float32)
    if weights is not None:
        rows = rows * weights[:, None]
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[token].add(
        rows, mode="drop")


def chunk(runs, cap, lo=0, k=4):
    """The places ``lo .. lo + cap`` of a step whose held experts took the
    tokens ``runs`` (one ascending list each): ``(token [cap], group)`` as
    ``_held_chunk`` computes them (the slot of expert e's row for token t is
    ``t * k + something``, so ``slot // k`` is t)."""
    sizes = np.array([len(r) for r in runs])
    ends = np.cumsum(sizes)
    group = np.clip(ends, lo, lo + cap) - np.clip(ends - sizes, lo, lo + cap)
    tokens_sorted = np.concatenate([np.asarray(r, np.int64) for r in runs]
                                   + [np.zeros(lo + cap, np.int64)])
    token = np.where(np.arange(cap) < group.sum(),
                     tokens_sorted[lo:lo + cap], TOKENS)
    for r in runs:
        assert (np.diff(r) > 0).all(), "tokens ascend inside a run"
    return token.astype(np.int32), group.astype(np.int32)


def _case(name):
    """(runs, cap, lo) and what the places must show."""
    rng = np.random.default_rng(7)

    def some(n, among=TOKENS):
        return np.sort(rng.choice(among, n, replace=False))

    if name == "balanced":
        return [some(90) for _ in range(4)], 512, 0
    if name == "an_empty_run":
        return [some(100), some(0), some(120), some(0)], 256, 0
    if name == "a_run_that_fills_a_tile":
        # Every token of the second tile, and of the first, in one run.
        return [some(40), np.arange(256, 512), np.arange(256), some(60)], \
            640, 0
    if name == "all_slots_of_a_token_here_and_a_token_with_none":
        # Token 5 is in every run, token 6 in none.
        runs = [np.union1d(np.setdiff1d(some(70), [6]), [5])
                for _ in range(4)]
        return runs, 384, 0
    if name == "an_unused_tail":
        return [some(30), some(25), some(2), some(20)], 256, 0
    if name == "a_chunk_that_starts_inside_a_run":
        # Places 128 .. 384 of 150 + 130 + 140: the first run's last 22
        # rows, the second run, and 104 rows of the third.
        return [some(150), some(130), some(140)], 256, 128
    if name == "nothing_held":
        return [some(0), some(0)], 128, 0
    if name == "short_runs_that_share_a_piece":
        # Runs of 3, 1, 5 rows: all in one 16-row piece, tokens of one tile
        # in each, so a piece's other rows would count twice if taken.
        return [np.array([3, 9, 200]), np.array([9]),
                np.array([3, 9, 10, 11, 300])], 128, 0
    raise ValueError(name)


CASES = ["balanced", "an_empty_run", "a_run_that_fills_a_tile",
         "all_slots_of_a_token_here_and_a_token_with_none", "an_unused_tail",
         "a_chunk_that_starts_inside_a_run", "nothing_held",
         "short_runs_that_share_a_piece"]


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["ones", "weighted"])
@pytest.mark.parametrize("d", [256, 384])
@pytest.mark.parametrize("name", CASES)
def test_kernel_is_the_scatter_add(name, d, weighted):
    """The kernel equals ``zeros.at[token].add(rows * w, mode="drop")``
    within fp32 rounding (exactly without weights: one term of a sum is
    exact, and k terms of bf16 rows add up exactly in fp32 here), whatever
    lies in the unused places."""
    runs, cap, lo = _case(name)
    token, group = chunk(runs, cap, lo)
    if name == "all_slots_of_a_token_here_and_a_token_with_none":
        assert (token == 5).sum() == 4 and (token == 6).sum() == 0
    if name == "a_chunk_that_starts_inside_a_run":
        assert list(group) == [22, 130, 104]
    if name == "a_run_that_fills_a_tile":
        assert group.sum() == cap - 28
    keys = jax.random.split(jax.random.PRNGKey(len(name) + d), 2)
    rows = jax.random.normal(keys[0], (cap, d), jnp.bfloat16)
    # What a grouped product leaves behind its last run.
    rows = rows.at[int(group.sum()):].set(jnp.nan)
    weights = jax.random.uniform(keys[1], (cap,), jnp.float32) \
        if weighted else None
    assert rt.takes(cap, d, TOKENS, rows.dtype)
    got = jax.jit(lambda r, t, g, w: rt.rows_to_tokens(
        r, t, g, TOKENS, w, interpret=True))(rows, token, group, weights)
    want = scatter(rows, jnp.asarray(token), TOKENS, weights)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=0, atol=0 if not weighted
                               else 4e-7 * float(jnp.max(jnp.abs(want))))


def test_the_plan_lists_every_used_place_once():
    """Each used place is taken by exactly one listed piece, by the tile of
    its token, and the list is as long as ``_list_length`` at most."""
    runs, cap, lo = _case("a_chunk_that_starts_inside_a_run")
    token, group = chunk(runs, cap, lo)
    chunk_start, src, lo_, hi, good = (np.asarray(a) for a in jax.jit(
        lambda t, g: rt._plan(t, g, TOKENS))(token, group))
    assert len(src) == rt._list_length(cap, len(group), TOKENS)
    assert chunk_start[-1] * (rt.CHUNK // rt.PIECE) <= len(src)
    taken = np.zeros(cap, int)
    per = rt.CHUNK // rt.PIECE
    for tile in range(TOKENS // rt.TILE):
        for at in range(chunk_start[tile] * per, chunk_start[tile + 1] * per):
            places = src[at] * rt.PIECE + np.arange(lo_[at], hi[at])
            assert (token[places] // rt.TILE == tile).all()
            taken[places] += 1
    used = int(group.sum())
    assert (taken[:used] == 1).all() and (taken[used:] == 0).all()
    assert (good == np.clip(used - src * rt.PIECE, 0, rt.PIECE)).all()


def test_takes_the_cells_shapes_and_no_other_dtype():
    # A first chunk and a quarter of the mean share (PR 39).
    assert rt.takes(15360, 2560, 16384) and rt.takes(3072, 2560, 16384)
    assert rt.takes(20480, 2048, 16384) and rt.takes(4096, 2048, 16384)
    assert not rt.takes(15360, 2560, 16384, jnp.float32)
    assert not rt.takes(15360, 2500, 16384)
    assert not rt.takes(94, 256, 512) and not rt.takes(256, 256, 47)
    with pytest.raises(ValueError, match="no kernel"):
        rt.rows_to_tokens(jnp.zeros((94, 256), jnp.bfloat16),
                          jnp.zeros((94,), jnp.int32),
                          jnp.zeros((2,), jnp.int32), 512)


# -- inside the layer ---------------------------------------------------------


def _layer(activation, routed_by_another):
    tokens, d, width, experts, k, held = 512, 256, 64, 16, 4, (1, 6, 11, 12)
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    x = jax.random.normal(keys[0], (1, tokens, d), jnp.bfloat16)
    router = 0.5 * jax.random.normal(keys[1], (d, experts))
    gate, up = (0.1 * jax.random.normal(key, (len(held), d, width))
                for key in keys[2:4])
    down = 0.1 * jax.random.normal(keys[4], (len(held), width, d))
    w = jax.random.normal(keys[5], x.shape)
    extra = {"router_input": jax.random.normal(keys[6], x.shape)} \
        if routed_by_another else {}

    def loss(x, router, gate, up, down):
        from horovod_tpu.parallel.moe import moe_ffn

        y, stats = moe_ffn(x, router, gate, up, down, k=k, held=held,
                           norm_topk_prob=True, activation=activation,
                           **extra)
        # Linear in y: the cotangent is the same on both paths.
        return jnp.sum(y.astype(jnp.float32) * w) \
            + jnp.sum(stats.load_balancing_loss), y

    return loss, (x, router, gate, up, down)


@pytest.mark.parametrize("activation,routed_by_another",
                         [("silu", False), ("relu", True)])
def test_layer_through_the_kernel_is_the_layer_through_the_scatter(
        monkeypatch, activation, routed_by_another):
    """Value and all five gradients of ``moe_ffn(held=)`` with the kernel
    forced (interpreted) equal the scatter path's within fp32 rounding; where
    the layer casts to bf16 afterwards, within one bf16 step of the few
    entries whose rounding that flips."""
    loss, args = _layer(activation, routed_by_another)

    def step():
        # As on the chip, a bf16 array is its rounded values on both paths:
        # XLA's CPU compiler would else hand the scatter-add the fp32 values
        # from before the cast (``xla_allow_excess_precision``).
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(
                *args).compile(
                    compiler_options={"xla_allow_excess_precision": False})

    (want_value, want_y), want = step()(*args)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    calls, kernel = [], rt.rows_to_tokens

    def interpreted(rows, *a):
        calls.append(rows.shape)
        return kernel(rows, *a, interpret=True)

    monkeypatch.setattr(rt, "rows_to_tokens", interpreted)
    (value, y), got = step()(*args)
    # _combine and _spread's cotangent of the first chunk (five quarters of
    # the mean share of 512 rows) and of the chunks of a quarter behind it,
    # traced inside their loops, went through the kernel.
    assert len(calls) >= 4 and set(calls) == {(640, 256), (128, 256)}

    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    assert abs(float(value) - float(want_value)) \
        <= 2e-6 * abs(float(want_value)) + 1e-4

    def close(a, b, bf16):
        a, b = f32(a), f32(b)
        scale = np.abs(b).max()
        if not bf16:
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-6 * scale)
            return
        # A bf16 value one step away, in a few entries.
        np.testing.assert_allclose(a, b, rtol=2 ** -7, atol=1e-6 * scale)
        assert (a != b).mean() < 0.01

    close(y, want_y, bf16=True)
    close(got[0], want[0], bf16=True)
    for g, wg in zip(got[1:], want[1:]):
        close(g, wg, bf16=False)


@pytest.mark.parametrize("why", ["on_the_cpu", "a_shape_it_does_not_take"])
def test_where_the_kernel_does_not_run_the_program_is_the_parents(
        monkeypatch, why):
    """Off the TPU, and on it for a shape ``takes()`` refuses, the layer
    lowers to the scatter-add, letter for letter what it lowers to with the
    kernel's module out of reach (the digests pinned in ``test_sdar.py`` and
    ``test_smallthinker.py`` hold this to the parent's text)."""
    from horovod_tpu.parallel import moe

    tokens = 512 if why == "on_the_cpu" else 47
    d, f, e, k, held = 256, 32, 16, 4, (1, 6)
    shape = jax.ShapeDtypeStruct
    args = [shape((1, tokens, d), jnp.bfloat16), shape((d, e), jnp.float32),
            shape((2, d, f), jnp.float32), shape((2, d, f), jnp.float32),
            shape((2, f, d), jnp.float32)]

    def loss(x, *a):
        y, stats = moe.moe_ffn(x, *a, k=k, held=held, norm_topk_prob=True)
        return jnp.sum(y.astype(jnp.float32)) \
            + jnp.sum(stats.load_balancing_loss)

    def text():
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).as_text()

    if why == "a_shape_it_does_not_take":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    here = text()
    assert rt.OP_LINE_NAME not in here and "scatter" in here

    def parents(rows, token, group, tokens, ws=None, slot=None):
        rows = rows.astype(jnp.float32)
        if ws is not None:
            rows = rows * ws[slot][:, None]
        return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[token].add(
            rows, mode="drop")

    monkeypatch.setattr(moe, "_rows_to_tokens", parents)
    assert text() == here
