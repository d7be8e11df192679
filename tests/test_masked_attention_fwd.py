"""The forward kernel of ``kernels/masked_attention.py::attention``
(``out_lse``, ISSUE 61): in interpret mode its ``out`` and its rows'
log-sum-exp against the grouped einsum's in float32 under every rule, and
``jax.grad`` through the wrapper (this forward into
``kernels/masked_attention_bwd.py``'s backward) against the einsum's.  Counts
and correctness only: nothing here is a timing.
"""

import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

import horovod_tpu
from horovod_tpu.kernels import blockdiff_attention as bd
from horovod_tpu.kernels import masked_attention as ma
from horovod_tpu.kernels import masked_attention_bwd as bwd
from .test_masked_attention_bwd import (  # noqa: F401 — tiles_of_128 is a fixture
    RULES, gradients, rel_err, tiles_of_128)

TILES = (128, 128, 64)


def hsd(t):
    return t.transpose(0, 2, 1, 3)


def operands(seed, b, s, h, h_kv, d, dv=None):
    """``q [b, s, h, d]``, ``k [b, s, h_kv, d]``, ``v [b, s, h_kv, dv]``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (b, s, h, d)),
            jax.random.normal(keys[1], (b, s, h_kv, d)),
            jax.random.normal(keys[2], (b, s, h_kv, dv or d)))


def plain(q, k, v, rule):
    """``out [b, h, s, dv]`` of the grouped einsum and the rows' log-sum-exp
    ``[b, h, s]`` of the same scores, in float32 at the highest precision."""
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("bqngd,bknd->bngqk",
                            q.reshape(b, s, h_kv, h // h_kv, d), k) * d ** -0.5
        ids = jnp.arange(s)
        scores = jnp.where(rule.allowed(ids[:, None], ids[None, :], s),
                           scores, -jnp.inf)
        return hsd(ma.einsum(q, k, v, rule)), \
            jax.nn.logsumexp(scores, axis=-1).reshape(b, h, s)


def kernel(q, k, v, rule, tiles=TILES, dtype=jnp.float32):
    d = q.shape[-1]
    with jax.default_matmul_precision("highest"):
        return ma.out_lse(hsd(q * d ** -0.5).astype(dtype),
                          hsd(k).astype(dtype), hsd(v).astype(dtype),
                          rule=rule, tiles=tiles, interpret=True)


@pytest.mark.parametrize("group,width", [(1, 128), (4, 64), (7, 128)])
@pytest.mark.parametrize("rule_name", list(RULES))
def test_out_and_lse_in_interpret_mode_match_the_grouped_einsum(rule_name,
                                                                group, width):
    """Four tiles of 128 a side, two sequences, ``group`` query heads a KV
    head (two KV heads where the group is 1 or 4).  Under the window that
    cuts a tile a query tile's first key tile holds rows that see none of its
    keys: their maximum stays the mask's value until a later tile."""
    rule = RULES[rule_name]
    s, h_kv = 512, 1 if group == 7 else 2
    q, k, v = operands(group + width, 2, s, group * h_kv, h_kv, width)
    out, lse = kernel(q, k, v, rule)
    want_out, want_lse = plain(q, k, v, rule)
    assert out.shape == want_out.shape and out.dtype == jnp.float32
    assert lse.shape == want_lse.shape and lse.dtype == jnp.float32
    assert rel_err(out, want_out) < 1e-5
    assert rel_err(lse, want_lse) < 1e-6


def test_out_and_lse_at_heads_of_256():
    """Qwen3-Next's gated layer: eight query heads on one KV head of 256."""
    rule = RULES["causal"]
    q, k, v = operands(256, 1, 512, 8, 1, 256)
    out, lse = kernel(q, k, v, rule)
    want_out, want_lse = plain(q, k, v, rule)
    assert rel_err(out, want_out) < 1e-5 and rel_err(lse, want_lse) < 1e-6


@pytest.mark.parametrize("rule_name", ["causal", "window_that_cuts_a_tile"])
def test_out_and_lse_at_keys_of_192_over_values_of_128(rule_name):
    """Latent attention's widths: the score contracts 192, the accumulator
    is 128 wide, the scores are scaled by the keys' width."""
    rule = RULES[rule_name]
    q, k, v = operands(192, 2, 512, 3, 3, 192, 128)
    out, lse = kernel(q, k, v, rule)
    want_out, want_lse = plain(q, k, v, rule)
    assert out.shape == (2, 3, 512, 128)
    assert rel_err(out, want_out) < 1e-5 and rel_err(lse, want_lse) < 1e-6


@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 1e-5),
                                         (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("tiles", [(128, 256, 128), (256, 128, 64),
                                   (256, 256, 256)])
def test_the_kernel_at_other_tiles_and_in_bf16(tiles, dtype, limit):
    """Queries and keys in tiles of different lengths; bf16 operands with
    fp32 statistics and sums, ``p`` rounded to bf16 for the second product:
    against the einsum in float32 the error is a rounding of the operands'
    dtype, and ``out`` comes in it."""
    rule = ma.Window(300)
    q, k, v = operands(11, 1, 512, 4, 2, 128)
    out, lse = kernel(q, k, v, rule, tiles, dtype)
    want_out, want_lse = plain(q, k, v, rule)
    assert out.dtype == dtype and lse.dtype == jnp.float32
    assert rel_err(out.astype(jnp.float32), want_out) < limit
    assert rel_err(lse, want_lse) < limit / 10


def test_a_run_of_one_key_tile_and_a_last_key_tile_one_query_tile_sees():
    """Under a window of one tile the first query tile's run is one key tile
    (FIRST and LAST in one step: zeroed, summed and written), and the last
    key tile is seen from the last query tile alone."""
    rule, s = ma.Window(128), 512
    q_tile, kv_tile, flags = bwd.tile_table(rule, s, 128, 128)
    assert (q_tile == 0).sum() == 1 and (kv_tile == 3).sum() == 1
    assert flags[0] & bwd.FIRST and flags[0] & bwd.LAST
    q, k, v = operands(7, 1, s, 3, 1, 128)
    out, lse = kernel(q, k, v, rule)
    want_out, want_lse = plain(q, k, v, rule)
    for rows in (slice(0, 128), slice(-128, None), slice(None)):
        assert rel_err(out[:, :, rows], want_out[:, :, rows]) < 1e-5
        assert rel_err(lse[:, :, rows], want_lse[:, :, rows]) < 1e-6


@pytest.mark.parametrize("rule_name", list(RULES))
def test_grad_through_the_wrapper_matches_the_einsums(rule_name,
                                                      tiles_of_128):  # noqa: F811
    """Our forward into our backward: the residuals the one hands the other
    (``out``, the log-sum-exp) are the ones it needs, two sequences, three
    query heads on each of two KV heads."""
    rule = RULES[rule_name]
    q, k, v = operands(3, 2, 512, 6, 2, 128)
    w = jax.random.normal(jax.random.PRNGKey(4), q.shape)
    with jax.default_matmul_precision("highest"):
        got = gradients(lambda *qkv: ma.attention(*qkv, rule, interpret=True),
                        q, k, v, w)
        want = gradients(lambda *qkv: ma.einsum(*qkv, rule), q, k, v, w)
    for g, e in zip(got, want):
        assert g.shape == e.shape and g.dtype == e.dtype
        assert rel_err(g, e) < 1e-5


TODAY = ((1024, 1024, 256), (1024, 1024, 512))
NARROW = ((512, 512, 512), (512, 512, 512))


@pytest.mark.parametrize("rule,d,dtype,fwd_and_bwd", [
    (ma.Causal(), 128, jnp.bfloat16, TODAY),
    (ma.Causal(), 64, jnp.float32, TODAY),
    (bd.BlockDiffusion(4), 128, jnp.bfloat16, TODAY),
    (ma.Window(4096), 128, jnp.bfloat16, TODAY),
    (ma.Window(1024), 128, jnp.bfloat16, TODAY),
    (ma.Causal(), 192, jnp.float32, ((512, 512, 512), TODAY[1])),
    (ma.Causal(), 256, jnp.float32, ((512, 512, 512), TODAY[1])),
    (ma.Window(1024), 256, jnp.float32, ((512, 512, 512), TODAY[1])),
    (ma.Causal(), 256, jnp.bfloat16, TODAY),
    (ma.Window(512), 128, jnp.bfloat16, NARROW),
    (ma.Window(512), 128, jnp.float32, NARROW),
    (ma.Window(512), 256, jnp.float32, NARROW),
    (ma.Window(1023), 128, jnp.bfloat16, NARROW),
    (ma.Window(300), 64, jnp.bfloat16, NARROW),
    (ma.Window(1), 128, jnp.bfloat16, NARROW)])
def test_the_tiles_follow_the_rule_and_the_operands(rule, d, dtype,
                                                    fwd_and_bwd):
    """The one function that chooses both kernels' tiles.  Every rule but a
    window narrower than a tile gets :data:`TODAY`'s (float32 operands wider
    than a lane group go forward in tiles of 512: the float32 twins of
    latent attention, 192 wide, and of Qwen3-Next's heads of 256), a window
    of exactly a tile too; a narrower window gets tiles of 512 in both
    kernels with a tile's keys multiplied at once, whatever its width (the
    sweep of PR 64 read finer tiles slower even where they fit the band
    better), and wide float32 under such a window the same, which is the
    finer of its two in every dimension."""
    assert (ma.FWD_TILES, ma.BWD_TILES) == TODAY
    assert ma.FWD_TILES_WIDE_FLOAT32 == (512, 512, 512)
    assert (ma.NARROW_WINDOW_TILES,) * 2 == NARROW
    assert all(n <= wide for n, wide in zip(ma.NARROW_WINDOW_TILES,
                                            ma.FWD_TILES_WIDE_FLOAT32))
    got = ma._tiles(rule, jax.ShapeDtypeStruct((1, 2, 8, d), dtype))
    assert got == fwd_and_bwd
    for tiles in got:
        assert tiles[1] % tiles[2] == 0 and ma.BLOCK % tiles[0] == 0 \
            and ma.BLOCK % tiles[1] == 0    # whatever takes() takes, they cut


def test_tiles_that_do_not_divide_are_refused():
    q = jnp.zeros((1, 1, 128, 128))
    with pytest.raises(ValueError, match="at a time"):
        ma.out_lse(q, q, q, rule=ma.Causal(), tiles=(64, 64, 48),
                   interpret=True)
    with pytest.raises(ValueError, match="do not divide"):
        ma.out_lse(q, q, q, rule=ma.Causal(), tiles=(48, 64, 64),
                   interpret=True)


@pytest.mark.parametrize("planted", [None, "window_less_one"])
def test_a_window_narrower_than_a_tile_through_the_wrapper(planted):
    """Laguna's sliding layer cut to 2048 positions: nine query heads on one
    KV head inside a window of 512, the path ``_attend`` takes at the tiles
    ``_tiles`` gives it (seven tiles of 512 x 512, every one partial, where
    tiles of 1024 hold three): ``out``, the log-sum-exp, dq, dk and dv
    against the grouped einsum's in float32; and the einsum under a window
    of 511, which the same limit has to refuse in each of the five."""
    rule, s, limit = ma.Window(512), 2048, 1e-5
    q, k, v = operands(9, 1, s, 9, 1, 128)
    w = jax.random.normal(jax.random.PRNGKey(10), q.shape)
    tiles = ma._tiles(rule, q)
    assert tiles == NARROW
    assert bwd.tile_table(rule, s, 512, 512)[0].size == 7
    wrong = ma.Window(rule.size - 1) if planted else rule
    with jax.default_matmul_precision("highest"):
        want_out, want_lse = plain(q, k, v, rule)
        want = gradients(lambda *qkv: ma.einsum(*qkv, rule), q, k, v, w)
        if planted:
            out, lse = plain(q, k, v, wrong)
            got = gradients(lambda *qkv: ma.einsum(*qkv, wrong), q, k, v, w)
        else:
            out, (*_, lse) = ma._attend_fwd(hsd(q * 128 ** -0.5), hsd(k),
                                            hsd(v), rule, True)
            grad = jax.grad(lambda *qkv: jnp.sum(ma.attention(
                *qkv, rule, interpret=True) * w), argnums=(0, 1, 2))
            text = str(jax.make_jaxpr(grad)(q, k, v))
            # Both kernels' last grid dimension is the table's length.
            assert text.count("grid=(1, 1, 9, 7)") == 2
            got = grad(q, k, v)
    errors = [rel_err(out, want_out), rel_err(lse, want_lse)] \
        + [rel_err(g, e) for g, e in zip(got, want)]
    if planted:
        assert min(errors) > limit, errors
    else:
        assert max(errors) < limit, errors


def test_the_wrapper_builds_no_kernel_of_the_library():
    """Both kernels of the wrapper's step are this repo's, by their names on
    the op line, which ``OP_LINE_NAMES`` still reads; and nothing under
    ``horovod_tpu/`` imports the library's splash attention."""
    assert ma.FWD_NAME.startswith("splash_mha_fwd")
    assert ma.FWD_NAME != "splash_mha_fwd_residuals"
    assert re.match(ma.OP_LINE_NAMES, ma.FWD_NAME)
    assert re.match(ma.OP_LINE_NAMES, bwd.NAME)
    assert not re.match(r"^splash_mha_dq", bwd.NAME)
    shape = jax.ShapeDtypeStruct((1, 2 * ma.BLOCK, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(ma.attention(q, k, v, ma.Causal())
                       .astype(jnp.float32))

    text = str(jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        shape, shape, shape).jaxpr)
    names = set(re.findall(r"name=(splash\w+)", text))
    assert names == {ma.FWD_NAME, bwd.NAME}, names
    root = pathlib.Path(horovod_tpu.__file__).parent
    users = [str(path.relative_to(root)) for path in root.rglob("*.py")
             if re.search(r"^\s*(from|import)\s.*splash_attention",
                          path.read_text(), re.M)
             or "make_splash_mha" in path.read_text()]
    assert users == [], users
