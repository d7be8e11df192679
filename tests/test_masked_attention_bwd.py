"""The one backward kernel of ``kernels/masked_attention.py::attention``
(``kernels/masked_attention_bwd.py``, ISSUE 44): the host's table of allowed
tiles against every position, and the kernel in interpret mode against
``jax.grad`` of the grouped einsum under the same rule.  Counts and
correctness only: nothing here is a timing.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.kernels import blockdiff_attention as bd
from horovod_tpu.kernels import masked_attention as ma
from horovod_tpu.kernels import masked_attention_bwd as bwd

RULES = {
    "causal": ma.Causal(),
    "window_that_cuts_a_tile": ma.Window(200),
    "window_of_two_tiles": ma.Window(256),
    "blockdiff": bd.BlockDiffusion(4),
}


def rel_err(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture()
def tiles_of_128(monkeypatch):
    """The wrapper's tiles cut to 128, forward and backward, the keys 64 at
    a time: the modules read them while a call is traced."""
    for module in (ma, bd):
        monkeypatch.setattr(module, "BLOCK", 128)
    monkeypatch.setattr(ma, "FWD_TILES", (128, 128, 64))
    monkeypatch.setattr(ma, "BWD_TILES", (128, 128, 64))


def brute_force(rule, seq_len, block_q, block_kv):
    """{(query tile, key tile): (any pair allowed, every pair allowed)},
    pair by pair from ``rule.allowed`` on single positions."""
    table = np.zeros((seq_len, seq_len), bool)
    for i in range(seq_len):
        for j in range(seq_len):
            table[i, j] = rule.allowed(np.int32(i), np.int32(j), seq_len)
    return {(i, j): (tile.any(), tile.all())
            for i in range(seq_len // block_q)
            for j in range(seq_len // block_kv)
            for tile in [table[i * block_q:(i + 1) * block_q,
                               j * block_kv:(j + 1) * block_kv]]}


@pytest.mark.parametrize("blocks", [(32, 32), (32, 16), (16, 64)])
@pytest.mark.parametrize("rule_name", list(RULES))
def test_the_table_of_tiles_against_every_position(rule_name, blocks):
    """No allowed pair in a tile the table leaves out, no forbidden pair in
    one it calls full, row-major order, and the first and the last tile of
    every query tile's run flagged."""
    rule = {**RULES, "window_that_cuts_a_tile": ma.Window(100),
            "window_of_two_tiles": ma.Window(128)}[rule_name]
    seq_len = 256
    q_tile, kv_tile, flags = bwd.tile_table(rule, seq_len, *blocks)
    want = brute_force(rule, seq_len, *blocks)
    listed = list(zip(q_tile.tolist(), kv_tile.tolist()))
    assert listed == sorted(t for t, (some, _) in want.items() if some)
    for tile, flag in zip(listed, flags.tolist()):
        assert bool(flag & bwd.PARTIAL) == (not want[tile][1]), tile
    assert any(flags & bwd.PARTIAL) and not all(flags & bwd.PARTIAL)
    for n, (i, _) in enumerate(listed):
        first = n == 0 or listed[n - 1][0] != i
        last = n == len(listed) - 1 or listed[n + 1][0] != i
        assert bool(flags[n] & bwd.FIRST) == first
        assert bool(flags[n] & bwd.LAST) == last
    assert q_tile.dtype == kv_tile.dtype == flags.dtype == np.int32


@pytest.mark.parametrize("half_len,block", [(16, 1), (16, 4), (16, 16),
                                            (24, 3), (64, 8)])
def test_the_block_rule_by_codes_is_the_three_clause_rule(half_len, block):
    """``BlockDiffusion.allowed``, which the kernel and the table evaluate,
    against ``block_diffusion_mask``: on numpy ids, on a row against a
    column of JAX ids, and pair by pair; any block length."""
    n = 2 * half_len
    ids = np.arange(n, dtype=np.int32)
    want = bd.block_diffusion_mask(ids[:, None], ids[None, :], half_len,
                                   block)
    rule = bd.BlockDiffusion(block)
    got = rule.allowed(ids[:, None], ids[None, :], n)
    assert got.dtype == bool
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(rule.allowed(
        jnp.arange(n)[:, None], jnp.arange(n)[None, :], n)), want)
    assert all(bool(rule.allowed(np.int32(i), np.int32(j), n)) == want[i, j]
               for i in range(0, n, 3) for j in range(n))
    assert got.sum() == rule.allowed_pairs(n)


@pytest.mark.parametrize("rule,seq_len,blocks,visited,partial", [
    (bd.BlockDiffusion(4), 16384, None, 80, 24),
    (ma.Causal(), 16384, None, 136, 16),
    (ma.Window(4096), 16384, None, 70, 28), (ma.Causal(), 8192, None, 36, 8),
    (ma.Window(512), 8192, None, 31, 31),
    (ma.Window(512), 8192, (1024, 1024), 15, 15),
    (ma.Window(512), 8192, (256, 256), 93, 62)])
def test_tiles_visited_at_the_cells_shapes(rule, seq_len, blocks, visited,
                                           partial):
    """SDAR's, SmallThinker's two, LFM2's and Nemotron's, and Laguna's
    sliding layers', at the tiles the wrapper gives both kernels (``blocks``
    None) or at the ones named: the counts the library's kernels visit too,
    of which only the partial ones compute a mask; the table is made once a
    rule and shape.  A window of 512 in tiles of 1024 visits 15 tiles for
    3.875 tiles' worth of pairs (25.8% allowed), in the wrapper's tiles of
    512 31 quarter-tiles (50.0%), in tiles of 256 93 sixteenths, 31 of them
    full (66.7%)."""
    if blocks is None:
        fwd, back = ma._tiles(rule, jax.ShapeDtypeStruct((1, 8, seq_len, 128),
                                                         jnp.bfloat16))
        assert fwd[:2] == back[:2]              # one table of tiles
        blocks = back[:2]
    q_tile, kv_tile, flags = bwd.tile_table(rule, seq_len, *blocks)
    assert q_tile.size == visited
    assert int((flags & bwd.PARTIAL != 0).sum()) == partial
    assert np.unique(q_tile).size == seq_len // blocks[0]
    assert rule.allowed_pairs(seq_len) / (blocks[0] * blocks[1]) < visited
    assert bwd.tile_table(rule, seq_len, *blocks)[0] is q_tile


def test_a_rule_that_leaves_a_query_tile_no_key_and_tiles_that_do_not_divide():
    @dataclasses.dataclass(frozen=True)
    class Later:
        def allowed(self, q_ids, kv_ids, seq_len):
            return (kv_ids <= q_ids) & (q_ids >= 32)

    with pytest.raises(ValueError, match="query tile 0"):
        bwd.tile_table(Later(), 128, 32, 32)
    with pytest.raises(ValueError, match="do not divide"):
        bwd.tile_table(ma.Causal(), 128, 48, 32)
    q = jnp.zeros((1, 1, 128, 128))
    with pytest.raises(ValueError, match="at a time"):
        bwd.dq_dk_dv(q, q, q, q[..., 0], q[..., 0], q, rule=ma.Causal(),
                     tiles=(64, 64, 48), interpret=True)


def gradients(attention, q, k, v, w):
    return jax.grad(lambda *qkv: jnp.sum(attention(*qkv) * w),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("group,width", [(1, 128), (4, 64), (7, 128)])
@pytest.mark.parametrize("rule_name", list(RULES))
def test_gradients_in_interpret_mode_match_the_grouped_einsum(
        rule_name, group, width, tiles_of_128):
    """dq, dk and dv of ``attention(..., interpret=True)`` at four tiles of
    128, two sequences, ``group`` query heads a KV head (two KV heads where
    the group is 1 or 4): the forward kernel and the one backward kernel
    against ``jax.grad`` of the einsum."""
    rule = RULES[rule_name]
    s, h_kv = 512, 1 if group == 7 else 2
    assert ma.takes(rule, s, width)
    keys = jax.random.split(jax.random.PRNGKey(group + width), 4)
    q, w = (jax.random.normal(key, (2, s, group * h_kv, width))
            for key in keys[:2])
    k, v = (jax.random.normal(key, (2, s, h_kv, width)) for key in keys[2:])
    with jax.default_matmul_precision("highest"):
        got = gradients(lambda *qkv: ma.attention(*qkv, rule, interpret=True),
                        q, k, v, w)
        want = gradients(lambda *qkv: ma.einsum(*qkv, rule), q, k, v, w)
    for g, e in zip(got, want):
        assert g.shape == e.shape and g.dtype == e.dtype
        assert rel_err(g, e) < 1e-5


def test_gradients_at_heads_of_256(tiles_of_128):
    """Qwen3-Next's attention layer: eight query heads on one KV head of 256
    (two lane groups a head) under the causal rule, four tiles of 128: dq, dk
    and dv against ``jax.grad`` of the grouped einsum.  The kernel took the
    width as it was (``takes()`` admits multiples of 128): what 128 and 192
    over 128 lower to is pinned in ``tests/test_pinned_programs.py``
    (``causal_kernel_call``, ``latent_kernel_call``) and did not move."""
    rule, s, width = RULES["causal"], 512, 256
    assert ma.takes(rule, s, width)
    keys = jax.random.split(jax.random.PRNGKey(256), 4)
    q, w = (jax.random.normal(key, (1, s, 8, width)) for key in keys[:2])
    k, v = (jax.random.normal(key, (1, s, 1, width)) for key in keys[2:])
    with jax.default_matmul_precision("highest"):
        got = gradients(lambda *qkv: ma.attention(*qkv, rule, interpret=True),
                        q, k, v, w)
        want = gradients(lambda *qkv: ma.einsum(*qkv, rule), q, k, v, w)
    for g, e in zip(got, want):
        assert g.shape == e.shape and g.dtype == e.dtype
        assert rel_err(g, e) < 1e-5


@pytest.mark.parametrize("rule_name", ["causal", "window_that_cuts_a_tile"])
def test_gradients_at_keys_of_192_over_values_of_128(rule_name, tiles_of_128):
    """Latent attention's widths (JoyAI-LLM-Flash): q and k 192 wide, v and
    the output 128, one key head a query head: the forward kernel and the
    one backward kernel in interpret mode against ``jax.grad`` of the einsum
    at the two widths; the scores are scaled by the keys' width."""
    rule, s, h = RULES[rule_name], 512, 3
    assert ma.takes(rule, s, 192, 128)
    assert not ma.takes(rule, s, 128, 192) and not ma.takes(rule, s, 192)
    assert not ma.takes(rule, s, 256, 128) and ma.takes(rule, s, 128, 128)
    keys = jax.random.split(jax.random.PRNGKey(192), 4)
    q, k = (jax.random.normal(key, (2, s, h, 192)) for key in keys[:2])
    v, w = (jax.random.normal(key, (2, s, h, 128)) for key in keys[2:])
    with jax.default_matmul_precision("highest"):
        out = ma.attention(q, k, v, rule, interpret=True)
        plain = ma.einsum(q, k, v, rule)
        got = gradients(lambda *qkv: ma.attention(*qkv, rule, interpret=True),
                        q, k, v, w)
        want = gradients(lambda *qkv: ma.einsum(*qkv, rule), q, k, v, w)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 192 ** -0.5
        ids = jnp.arange(s)
        by_hand = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(
            rule.allowed(ids[:, None], ids[None, :], s), scores, -jnp.inf)),
            v)
    assert out.shape == plain.shape == (2, s, h, 128)
    assert rel_err(out, by_hand) < 1e-5 and rel_err(plain, by_hand) < 1e-5
    for g, e, like in zip(got, want, (q, k, v)):
        assert g.shape == e.shape == like.shape and g.dtype == e.dtype
        assert rel_err(g, e) < 1e-5
    with pytest.raises(ValueError, match="head width 128 over values of 192"):
        ma.attention(v, v, q, rule, interpret=True)


def test_equal_widths_give_bit_for_bit_what_one_width_gave(tiles_of_128):
    """At one width for q, k and v the kernel's blocks, scratch and products
    are what they were before it learnt two (the pinned call of
    ``tests/test_pinned_programs.py`` holds the text): the same call twice,
    and with the widths spelled apart through ``dq_dk_dv``, gives the same
    bits."""
    rule, b, s, h, d = ma.Causal(), 1, 256, 2, 128
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k, v, do = (jax.random.normal(key, (b, h, s, d), jnp.bfloat16)
                   for key in keys)
    lse = jnp.zeros((b, h, s)) + 3.0
    di = jnp.ones((b, h, s))
    once = bwd.dq_dk_dv(q, k, v, lse, di, do, rule=rule,
                        tiles=(128, 128, 64), interpret=True)
    # Keys padded by a lane group of zeros add nothing to any product:
    # dq and dk gain zero columns, dv is the same bits.
    wide = lambda t: jnp.pad(t, ((0, 0),) * 3 + ((0, 128),))  # noqa: E731
    apart = bwd.dq_dk_dv(wide(q), wide(k), v, lse, di, do, rule=rule,
                         tiles=(128, 128, 64), interpret=True)
    assert apart[0].shape == apart[1].shape == (b, h, s, 256)
    for got, want in zip(apart, once):
        np.testing.assert_array_equal(got[..., :d], want)
    assert not np.asarray(apart[0][..., d:]).any()
    assert not np.asarray(apart[1][..., d:]).any()


def test_a_last_key_tile_that_a_single_query_tile_sees(tiles_of_128):
    """Under a window of one tile every key tile but the last is seen from
    two query tiles, the last from one: its dk and dv are that one step's,
    and the first query tile's dq is one step's too."""
    rule, s = ma.Window(128), 512
    q_tile, kv_tile, _ = bwd.tile_table(rule, s, 128, 128)
    assert (kv_tile == 3).sum() == 1 and (kv_tile == 2).sum() == 2
    assert (q_tile == 0).sum() == 1
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, w = (jax.random.normal(key, (1, s, 3, 128)) for key in keys[:2])
    k, v = (jax.random.normal(key, (1, s, 1, 128)) for key in keys[2:])
    with jax.default_matmul_precision("highest"):
        got = gradients(lambda *qkv: ma.attention(*qkv, rule, interpret=True),
                        q, k, v, w)
        want = gradients(lambda *qkv: ma.einsum(*qkv, rule), q, k, v, w)
    for g, e in zip(got, want):
        assert rel_err(g[:, -128:], e[:, -128:]) < 1e-5
        assert rel_err(g, e) < 1e-5


@pytest.mark.parametrize("tiles", [(128, 256, 128), (256, 128, 64),
                                   (256, 256, 256)])
def test_the_kernel_at_other_tiles_and_in_bf16(tiles):
    """Queries and keys in tiles of different lengths, and bf16 operands
    with fp32 sums: against the einsum's gradients in float32 the error is a
    rounding of the operands' dtype."""
    rule, b, s, h, h_kv, d = ma.Window(300), 1, 512, 4, 2, 128
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q, do = (jax.random.normal(key, (b, h, s, d)) for key in keys[:2])
    k, v = (jax.random.normal(key, (b, h_kv, s, d)) for key in keys[2:])
    q = q * d ** -0.5
    bsh = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731

    def plain(q, k, v):                     # q arrives scaled
        return bsh(ma.einsum(bsh(q) * d ** 0.5, bsh(k), bsh(v), rule))

    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(plain, q, k, v)
        want = pull(do)
        scores = jnp.einsum("bngqd,bnkd->bngqk",
                            q.reshape(b, h_kv, h // h_kv, s, d), k)
        ids = jnp.arange(s)
        scores = jnp.where(rule.allowed(ids[:, None], ids[None, :], s),
                           scores, -jnp.inf)
        lse = jax.nn.logsumexp(scores, axis=-1).reshape(b, h, s)
        di = jnp.sum(out * do, axis=-1)
        for dtype, limit in ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)):
            got = bwd.dq_dk_dv(
                *(t.astype(dtype) for t in (q, k, v)), lse, di,
                do.astype(dtype), rule=rule, tiles=tiles, interpret=True)
            for g, e in zip(got, want):
                assert g.dtype == dtype and g.shape == e.shape
                assert rel_err(g.astype(jnp.float32), e) < limit
