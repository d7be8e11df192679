"""Keye-VL-2.0-30B-A3B's language model (``keye_vl_2_0_30b_a3b_config()``):
grouped-query attention over the keys a learned indexer chooses
(``models/indexer.py``), its mask that is data through both attention kernels
(``kernels/masked_attention.py::Sparse``), the indexer's two kernels
(``kernels/dsa.py``) and the whole model against the plain reference
(``chip_bench/configs/keye-vl-2.0-30b-a3b_reference.py``), all on seeded
weights at a small size.  ``tests/test_keye_cell.py`` holds the configuration
and the cell, ``tests/test_keye_compile.py`` the compiles for a described
chip.  Counts and correctness only: nothing here is a timing.
"""

import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .helpers import REPO_ROOT, load_reference
from .test_olmoe import layer_inputs, rel_err
from .test_sdar import dense_share

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

ref = load_reference("keye-vl-2.0-30b-a3b")



def full_sizes():
    with open(os.path.join(REPO_ROOT, "chip_bench/configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        return json.load(f)


def tiny_sizes(**overrides):
    """The configuration's file at tiny widths: two layers, 4 query heads
    on 1 KV head of 128 (wide enough for the kernels' interpret mode), an
    indexer of 4 heads of 64 that chooses 64 of 256 positions, 8 of 32
    experts held of top 4, a sliced vocabulary."""
    sizes = full_sizes()
    return {**sizes, **dict(
        num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=1, head_dim=128, moe_intermediate_size=32,
        num_experts=8, num_local_experts=8, num_experts_published=32,
        experts_held=[0, 5, 9, 14, 18, 23, 27, 31], num_experts_per_tok=4,
        sa_config={**sizes["sa_config"], "indexer_num_heads": 4,
                   "topk": 64, "q_chunk_size": 128, "kv_chunk_size": 128},
        vocab_size=128, sequence_length=256, per_chip_batch=2,
        max_position_embeddings=512, name="tiny-keye"), **overrides}


def tiny_model(sizes=None, dtype=jnp.float32, **overrides):
    from horovod_tpu.models.transformer import (
        Transformer,
        keye_vl_2_0_30b_a3b_config,
    )

    z = sizes or tiny_sizes()
    sa = z["sa_config"]
    return Transformer(keye_vl_2_0_30b_a3b_config(**{**dict(
        vocab_size=z["vocab_size"], num_layers=z["num_hidden_layers"],
        num_heads=z["num_attention_heads"],
        num_kv_heads=z["num_key_value_heads"], head_width=z["head_dim"],
        d_model=z["hidden_size"], d_ff=z["moe_intermediate_size"],
        max_len=z["max_position_embeddings"],
        num_experts=z["num_experts_published"],
        experts_per_token=z["num_experts_per_tok"],
        experts_held=tuple(z["experts_held"]),
        indexer_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], indexer_topk=sa["topk"],
        dtype=dtype), **overrides}))


def seeded(model, seed=0, tokens=None):
    """Weights whose attention and indexer have opinions: the embedding at
    its own width (the cell's ``embedding_init_std``), the indexer's and the
    attention's projections five times the initialiser's, so that scores
    differ by more than rounding and a wrong layer shows."""
    z = tiny_sizes()
    if tokens is None:
        tokens = jnp.zeros((1, z["sequence_length"]), jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(seed),
                                      tokens)["params"])
    params["embed"]["embedding"] = params["embed"]["embedding"] * 50.0
    for i in range(model.cfg.num_layers):
        attn = params[f"layer_{i}"]["attn"]
        for name in ("q", "kv"):
            attn[name]["kernel"] = attn[name]["kernel"] * 5.0
        if "indexer" in attn:
            for name in ("q", "k", "weights"):
                attn["indexer"][name]["kernel"] \
                    = attn["indexer"][name]["kernel"] * 5.0
    return params


def tokens_of(seed, sizes=None):
    z = sizes or tiny_sizes()
    return jax.random.randint(jax.random.PRNGKey(seed),
                              (z["per_chip_batch"], z["sequence_length"]), 0,
                              z["vocab_size"])


def program_terms(model, params, tokens):
    """(logits, load-balancing loss, the indexer's loss) of the program."""
    from horovod_tpu.models.indexer import indexer_loss
    from horovod_tpu.models.transformer import moe_stats

    logits, state = model.apply({"params": params}, tokens,
                                mutable=["moe", "indexer"])
    return logits, jnp.mean(moe_stats(state["moe"]).load_balancing_loss), \
        indexer_loss(state["indexer"])


@pytest.fixture(scope="module")
def tiny():
    """The tiny model in float32, its seeded weights, a batch, and the
    compiled value and gradient of its two losses apart (what reaches the
    indexer and what does not), shared by this file's cases."""
    model = tiny_model()
    params, tokens = seeded(model), tokens_of(1)

    def main(params):
        logits, balance, _ = program_terms(model, params, tokens)
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1)) + 0.1 * balance

    def divergence(params):
        return program_terms(model, params, tokens)[2]

    with jax.default_matmul_precision("highest"):
        grads = {"main": jax.jit(jax.value_and_grad(main))(params),
                 "divergence": jax.jit(jax.value_and_grad(divergence))(
                     params)}
    return model, params, tokens, grads


def _is_indexer(path):
    return any(getattr(k, "key", None) == "indexer" for k in path)


# fp32: the two differ in the order of their sums only, and in a choice
# between two scores a last bit apart (none on these seeds).  bf16: the
# stream rounds, so a position here and there takes another expert or another
# key; the logits move by hundredths, as the cell's own limits read on the
# chip.
@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 2e-5),
                                         (jnp.bfloat16, 8e-2)])
def test_program_agrees_with_the_plain_reference(dtype, limit, tiny):
    model, params, tokens, _ = tiny
    if dtype != jnp.float32:
        model = tiny_model(dtype=dtype)
    sizes, batch = tiny_sizes(), {"tokens": tokens}
    with jax.default_matmul_precision("highest"):
        logits, balance, divergence = jax.jit(
            lambda p: program_terms(model, p, tokens))(params)
        want = ref.logits(params, batch, sizes)
        _, want_balance, want_divergence, _ = ref.terms(params, batch, sizes)
    assert logits.shape == want.shape == (2, 256, 128)
    assert rel_err(logits, want) < limit
    assert float(balance) == pytest.approx(float(want_balance), rel=limit)
    assert float(divergence) == pytest.approx(float(want_divergence),
                                              rel=10 * limit)
    assert 0.01 < float(want_divergence) < 10.0
    # A planted fault of the reference moves the float32 logits by more than
    # the program lies from them (these two here, three more in
    # tests/test_keye_cell.py, all five at the timed sizes on the chip).
    if dtype == jnp.float32:
        for fault in ("no_weights", "key_unturned"):
            with jax.default_matmul_precision("highest"):
                wrong = ref.logits(params, batch, sizes, wrong=(fault,))
            assert rel_err(wrong, want) > 20 * limit, fault


def test_gradients_agree_with_the_plain_reference(tiny):
    """The three-term loss's gradient, the program's against the
    reference's, every parameter."""
    model, params, tokens, grads = tiny
    sizes, batch = tiny_sizes(), {"tokens": tokens}

    def reference(params):
        nll, balance, divergence, _ = ref.terms(params, batch, sizes)
        return nll + 0.1 * balance + divergence

    def program(params):
        logits, balance, divergence = program_terms(model, params, tokens)
        nll = -jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1),
            jnp.roll(tokens, -1, axis=1)[..., None], axis=-1)[..., 0]
        s = tokens.shape[1]
        return jnp.sum(nll * (jnp.arange(s) < s - 1)) \
            / (tokens.shape[0] * (s - 1)) + 0.1 * balance + divergence

    with jax.default_matmul_precision("highest"):
        got_value, got = jax.jit(jax.value_and_grad(program))(params)
        want_value, want = jax.jit(jax.value_and_grad(reference))(params)
    assert float(got_value) == pytest.approx(float(want_value), rel=1e-5)
    errors = jax.tree_util.tree_map(rel_err, got, want)
    worst = max(jax.tree_util.tree_leaves(errors))
    assert worst < 2e-3, errors


def test_each_loss_reaches_its_own_parameters_and_no_other(tiny):
    """**The gradient's paths.**  Cross-entropy and the balance term leave
    the indexer's four parameters exactly zero; the indexer's loss leaves
    every other parameter exactly zero, and all four of its own not."""
    _, _, _, grads = tiny
    for name, (_, g) in grads.items():
        for path, leaf in jax.tree_util.tree_flatten_with_path(g)[0]:
            own = _is_indexer(path) == (name == "divergence")
            biggest = float(jnp.max(jnp.abs(leaf)))
            assert (biggest > 0) == own, (name, jax.tree_util.keystr(path))
    indexer = grads["divergence"][1]["layer_0"]["attn"]["indexer"]
    assert sorted(indexer) == ["k", "k_norm", "q", "weights"]
    assert sorted(indexer["k_norm"]) == ["bias", "scale"]


@pytest.mark.parametrize("cut", ["target_in_graph", "input_in_graph"])
def test_each_cut_removed_is_seen(cut, tiny):
    """The reference with one of its two cuts removed sends the indexer's
    loss where it must not go: with the target in the graph to the
    attention's own projections, with the indexer's input in the graph to
    the norm in front of the layer and the embedding."""
    _, params, tokens, _ = tiny
    sizes, batch = tiny_sizes(), {"tokens": tokens}
    with jax.default_matmul_precision("highest"):
        g = jax.jit(jax.grad(lambda p: ref.terms(
            p, batch, sizes, wrong=(cut,))[2]))(params)
        sound = jax.jit(jax.grad(lambda p: ref.terms(
            p, batch, sizes)[2]))(params)
    reached = {jax.tree_util.keystr(path)
               for path, leaf in jax.tree_util.tree_flatten_with_path(g)[0]
               if not _is_indexer(path) and float(jnp.max(jnp.abs(leaf))) > 0}
    assert not any(
        float(jnp.max(jnp.abs(leaf))) > 0 and not _is_indexer(path)
        for path, leaf in jax.tree_util.tree_flatten_with_path(sound)[0])
    if cut == "target_in_graph":
        assert "['layer_0']['attn']['q']['kernel']" in reached
    else:
        assert "['layer_0']['ln1']['scale']" in reached
    assert "['embed']['embedding']" in reached


def test_the_programs_cuts_removed_are_seen(tiny, monkeypatch):
    """The same on the program, one layer of it: with ``stop_gradient`` taken
    out of ``models/indexer.py`` the indexer's loss reaches the rest of the
    model."""
    import types

    from horovod_tpu.models import indexer

    _, params, tokens, _ = tiny
    model = tiny_model(num_layers=1)
    params = {k: v for k, v in params.items() if k != "layer_1"}
    passes = types.SimpleNamespace(**{
        **{n: getattr(indexer.lax, n) for n in dir(indexer.lax)
           if not n.startswith("_")}, "stop_gradient": lambda x: x})
    monkeypatch.setattr(indexer, "lax", passes)
    g = jax.jit(jax.grad(lambda p: program_terms(
        model, p, tokens[:, :128])[2]))(params)
    assert float(jnp.max(jnp.abs(
        g["layer_0"]["attn"]["q"]["kernel"]))) > 0
    assert float(jnp.max(jnp.abs(g["embed"]["embedding"]))) > 0


def test_the_indexers_blocks_lie_under_their_scopes(tiny):
    """The six scopes of ``timeline.SCOPES`` that an indexer brings, in the
    lowered gradient of the tiny model off the TPU (``attn.sparse`` is the
    kernels' alone and cannot appear here), the scores under the choice."""
    model, params, tokens, _ = tiny
    text = jax.jit(jax.grad(lambda p: program_terms(
        model, p, tokens)[2])).lower(params).as_text(debug_info=True)
    for name in ("indexer.proj", "indexer.target", "indexer.loss",
                 "indexer.scores/hvd.indexer.choose"):
        assert "hvd." + name in text, name
    assert "hvd.attn.sparse" not in text and "hvd.attn.einsum" in text


def test_mropes_sectioned_table_on_equal_streams_is_the_programs():
    """On text the three position streams coincide and M-RoPE's table is
    plain RoPE's: the reference's, built from three streams by section,
    equals the program's ``_rope_tables`` bit for bit, at the heads' 128 and
    the indexer's 64; on streams that differ each section follows its
    own."""
    from horovod_tpu.models.transformer import _rope_tables

    s, theta = 256, 1e7
    streams = jnp.broadcast_to(jnp.arange(s), (3, s))
    for width in (128, 64):
        cos, sin = ref.mrope_tables(streams, width, theta, [16, 24, 24])
        want_cos, want_sin, half = _rope_tables(s, width, theta)
        assert half == width // 2
        np.testing.assert_array_equal(cos, want_cos)
        np.testing.assert_array_equal(
            sin, jnp.concatenate([-want_sin[:, :half], want_sin[:, half:]],
                                 axis=-1))
    apart = jnp.stack([jnp.arange(s), 2 * jnp.arange(s), 3 * jnp.arange(s)])
    cos, _ = ref.mrope_tables(apart, 128, theta, [16, 24, 24])
    for pair, stream in ((0, 0), (15, 0), (16, 1), (39, 1), (40, 2),
                         (63, 2)):
        want = ref.mrope_tables(
            jnp.broadcast_to(apart[stream], (3, s)), 128, theta,
            [16, 24, 24])[0]
        np.testing.assert_array_equal(cos[:, pair], want[:, pair])
        np.testing.assert_array_equal(cos[:, 64 + pair], want[:, pair])


def test_topk_at_the_sequence_is_causal_attention(tiny):
    """With as many keys chosen as the sequence has positions every causal
    key is chosen, and the model's logits (one layer of it here) are those
    of the same weights without an indexer, through the causal rule."""
    _, params, tokens, _ = tiny
    s = tokens.shape[1]
    params = {k: v for k, v in params.items() if k != "layer_1"}
    plain = {**params, "layer_0": {**params["layer_0"], "attn": {
        k: v for k, v in params["layer_0"]["attn"].items()
        if k != "indexer"}}}
    with jax.default_matmul_precision("highest"):
        chosen, causal = (tiny_model(num_layers=1, indexer_topk=k)
                          for k in (s, 0))
        got = jax.jit(lambda p: chosen.apply(
            {"params": p}, tokens, mutable=["moe", "indexer"])[0])(params)
        want = jax.jit(lambda p: causal.apply(
            {"params": p}, tokens, mutable=["moe"])[0])(plain)
    assert rel_err(got, want) < 1e-5


def _indexer_operands(seed, b, s, heads=4, width=64):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (b, heads, s, width)),
            jax.random.normal(keys[1], (b, s, width)),
            0.1 * jax.random.normal(keys[2], (b, s, heads)))


@pytest.mark.parametrize("topk,planted", [(64, True), (200, True),
                                          (64, False)])
def test_the_chosen_sets_are_top_ks_with_planted_ties(topk, planted):
    """``hvd_dsa_choose`` in interpret mode against ``lax.top_k`` on the
    reference's dense table, pair for pair: runs of keys that are one key
    (their scores tie, and the lower positions are taken), and queries whose
    weights are all zero (every score ties at zero); the ``jax.numpy`` form
    the same; a query's set holds ``min(topk, t + 1)`` keys; the
    log-sum-exp over the set.  A block with a planted tie says so and breaks
    it; without planted ties no block does (the passes over a position's
    bits are skipped) and the sets are ``lax.top_k``'s all the same."""
    from horovod_tpu.kernels import dsa
    from horovod_tpu.kernels.masked_attention_bwd import unpack_chosen
    from horovod_tpu.models import indexer

    # Four heads leave a pair in sixteen at exactly zero behind the ReLU,
    # ties of their own; sixteen leave none.
    b, s, heads = (1, 512, 4) if planted else (1, 256, 16)
    q_i, k_i, w = _indexer_operands(3, b, s, heads)
    if planted:
        k_i = k_i.at[:, 10:20].set(k_i[:, 10:11])
        k_i = k_i.at[:, 100:180].set(k_i[:, 100:101])
        w = w.at[:, 300:310].set(0.0)
    with jax.default_matmul_precision("highest"):
        words, lse, blocks = dsa.choose(q_i, k_i, w, topk=topk,
                                        interpret=True, rows=128, keys=128)
        by_top_k = indexer._choose(q_i, k_i, w, topk)
        want = jnp.stack([jnp.concatenate([ref.chosen_block(
            q_i[n].transpose(1, 0, 2), k_i[n], w[n], start, 128, topk)
            for start in range(0, s, 128)]) for n in range(b)])
        table = indexer.scores(q_i, k_i, w)
    got = unpack_chosen(words, s)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(unpack_chosen(by_top_k, s), want)
    np.testing.assert_array_equal(
        got.sum(axis=-1), np.broadcast_to(
            np.minimum(np.arange(s) + 1, topk), (b, s)))
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(jnp.where(want, table, -jnp.inf), axis=-1),
        rtol=1e-5, atol=1e-5)
    # A block's count of rows with more scores at their threshold than they
    # take: the dense table's own.
    dense, sets = np.asarray(table), np.asarray(want)
    kth = np.take_along_axis(
        -np.sort(-dense, axis=-1),
        np.minimum(np.arange(s), topk - 1)[None, :, None], axis=-1)
    tied = (dense >= kth).sum(axis=-1) != sets.sum(axis=-1)
    assert blocks.shape == (b, s // 128, 2) and blocks.dtype == jnp.int32
    ties, passes = np.asarray(blocks[..., 0]), np.asarray(blocks[..., 1])
    np.testing.assert_array_equal(
        ties, tied.reshape(b, s // 128, 128).sum(axis=-1))
    # 32 passes at the most over the scores' bits; a block with a tie runs
    # one more and one a bit of a position, any other none of them.
    assert np.all((1 <= passes[ties == 0]) & (passes[ties == 0] <= 32))
    assert np.all(passes[ties > 0] == dsa.passes_at_most(s))
    if not planted:
        assert not np.any(ties)
        return
    # The rows of no weight tie at zero: their block breaks ties.
    assert np.all(ties[:, 300 // 128] >= 10)
    # The planted ties cut a run: some of its keys in, the later ones out.
    run = np.asarray(got[:, :, 100:180])
    cut = (run.any(axis=-1) & ~run.all(axis=-1))[:, 180:]
    assert cut.any()
    first_out = np.argmin(run, axis=-1)
    assert np.all(run[:, 180:][cut][np.arange(cut.sum())[:, None],
                                   np.arange(80)[None, :]]
                  == (np.arange(80)[None, :]
                      < first_out[:, 180:][cut][:, None]))


def test_pack_and_unpack_and_the_rule():
    """The words hold a bit a pair, 32 keys a lane group apart a word; the
    rule visits every causal tile and masks each; its pairs are the chosen
    ones."""
    from horovod_tpu.kernels import masked_attention as ma
    from horovod_tpu.kernels import masked_attention_bwd as bwd

    mask = jax.random.bernoulli(jax.random.PRNGKey(0), 0.3, (2, 8, 5000))
    words = bwd.pack_chosen(mask)
    assert words.shape == (2, 8, 256) and words.dtype == jnp.int32
    np.testing.assert_array_equal(bwd.unpack_chosen(words, 5000), mask)
    assert int(words[0, 0, 5]) & 1 == int(mask[0, 0, 5])
    assert (int(words[0, 0, 5]) >> 3) & 1 == int(mask[0, 0, 3 * 128 + 5])
    assert (int(words[0, 0, 128 + 7]) >> 1) & 1 \
        == int(mask[0, 0, 4096 + 128 + 7])
    rule = ma.Sparse(2048)
    assert rule.scope == "hvd.attn.sparse" and rule.data
    assert rule.allowed_pairs(16384) == 31_458_304
    assert rule.allowed_pairs(1024) == ma.Causal().allowed_pairs(1024)
    assert rule.takes(16384) and not rule.takes(1000)
    q_tile, kv_tile, flags = bwd.tile_table(rule, 16384, 1024, 1024)
    causal = bwd.tile_table(ma.Causal(), 16384, 1024, 1024)
    np.testing.assert_array_equal(q_tile, causal[0])
    np.testing.assert_array_equal(kv_tile, causal[1])
    assert len(flags) == 136 and np.all(flags & bwd.PARTIAL)
    assert int(np.sum(causal[2] & bwd.PARTIAL != 0)) == 16
    with pytest.raises(ValueError, match="keys chosen"):
        ma.Sparse(0)


@pytest.mark.parametrize("group,dtype,limit", [(4, jnp.float32, 1e-5),
                                               (2, jnp.bfloat16, 3e-2)])
def test_attention_kernels_under_a_chosen_set_match_the_masked_einsum(
        group, dtype, limit, monkeypatch):
    """Both kernels in interpret mode with the chosen sets as an operand,
    two tiles of 128 a side (the words of one group of keys; the backward
    turns them in scratch), against the einsum under the same sets: the
    output, the rows' log-sum-exp and every cotangent."""
    from horovod_tpu.kernels import masked_attention as ma
    from horovod_tpu.kernels.masked_attention_bwd import pack_chosen

    b, s, h_kv, d, topk = 2, 256, 2, 128, 48
    keys = jax.random.split(jax.random.PRNGKey(group), 5)
    q = jax.random.normal(keys[0], (b, s, group * h_kv, d))
    k, v = (jax.random.normal(key, (b, s, h_kv, d)) for key in keys[1:3])
    ct = jax.random.normal(keys[3], (b, s, group * h_kv, d))
    table = jnp.where(jnp.arange(s)[None, :] <= jnp.arange(s)[:, None],
                      jax.random.normal(keys[4], (b, s, s)), -jnp.inf)
    _, ids = jax.lax.top_k(table, topk)
    mask = jnp.zeros((b, s, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        ids].set(True) & (table > -jnp.inf)
    words, rule = pack_chosen(mask), ma.Sparse(topk)
    hsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
    monkeypatch.setattr(ma, "_tiles",
                        lambda rule, q: ((128, 128, 128),) * 2)

    def kernels(q, k, v):
        out, lse = ma._attend_chosen(
            hsd(q * d ** -0.5).astype(dtype), hsd(k).astype(dtype),
            hsd(v).astype(dtype), words, rule, True)
        return jnp.sum(hsd(out).astype(jnp.float32) * ct), (out, lse)

    def einsum(q, k, v):
        out = ma.einsum(q, k, v, rule, words=words)
        return jnp.sum(out * ct), out

    with jax.default_matmul_precision("highest"):
        (_, (out, lse)), got = jax.value_and_grad(
            kernels, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        (_, want_out), want = jax.value_and_grad(
            einsum, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        scores = jnp.einsum("bqngd,bknd->bngqk",
                            q.reshape(b, s, h_kv, group, d), k) * d ** -0.5
        want_lse = jax.nn.logsumexp(
            jnp.where(mask[:, None, None], scores, -jnp.inf),
            axis=-1).reshape(b, group * h_kv, s)
    assert rel_err(hsd(out), want_out) < limit
    assert rel_err(lse, want_lse) < limit
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert rel_err(g, w) < limit, name


@pytest.mark.parametrize("dtype,value_limit,limits", [
    (jnp.float32, 1e-5, (1e-4, 1e-4, 1e-4)),
    (jnp.bfloat16, 1e-5, (4e-3, 4e-3, 1e-5))])
def test_the_loss_kernel_matches_the_blockwise_form(dtype, value_limit,
                                                    limits):
    """``hvd_dsa_loss`` in interpret mode, tiles of 128 a side: the sum of
    the divergences and its gradient to the indexer's three operands against
    the ``jax.numpy`` form's (a softmax of its own for the target, autodiff
    for the gradient); the attention's operands get none.  In bf16 operands
    the kernel rounds the divergence's gradient and a head's weighted query
    to bf16 in front of its two products where the blockwise form keeps
    fp32: PR 68's kernel read 2.8e-3, 2.7e-3 and 8e-7 at this shape (dq_i,
    dk_i, dw), half of the first two the bf16 outputs' own rounding."""
    from horovod_tpu.kernels import dsa
    from horovod_tpu.kernels.masked_attention_bwd import unpack_chosen
    from horovod_tpu.models import indexer

    b, s, heads, h_kv, d, topk = 1, 512, 4, 2, 128, 64
    if dtype == jnp.bfloat16:
        s, heads, h_kv, topk = 256, 2, 1, 32
    q_i, k_i, w = _indexer_operands(5, b, s, heads=heads)
    keys = jax.random.split(jax.random.PRNGKey(6), 2)
    q = jax.random.normal(keys[0], (b, heads, s, d)) * d ** -0.5
    k = jax.random.normal(keys[1], (b, h_kv, s, d))
    q_i, k_i, q, k = (t.astype(dtype) for t in (q_i, k_i, q, k))
    with jax.default_matmul_precision("highest"):
        words, lse_i = dsa.choose(q_i, k_i, w, topk=topk, interpret=True,
                                  rows=128, keys=128)[:2]
        mask = unpack_chosen(words, s)
        scores = jnp.einsum("bngtd,bnsd->bngts",
                            q.reshape(b, h_kv, heads // h_kv, s, d), k,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(
            jnp.where(mask[:, None, None], scores, -jnp.inf),
            axis=-1).reshape(b, heads, s)

        def kernel(q_i, k_i, w, q, k):
            return dsa.kl_sum(q_i, k_i, w, words, lse_i, q, k, lse,
                              tiles=(128, 128), interpret=True)

        def blockwise(q_i, k_i, w, q, k):
            return indexer._kl_sum(q_i, k_i, w, words, q, k)

        got_value, got = jax.value_and_grad(kernel, argnums=(0, 1, 2, 3, 4))(
            q_i, k_i, w, q, k)
        want_value, want = jax.value_and_grad(
            blockwise, argnums=(0, 1, 2, 3, 4))(q_i, k_i, w, q, k)
    assert float(got_value) == pytest.approx(float(want_value),
                                             rel=value_limit)
    assert float(want_value) > 1.0
    for name, g, t, limit in zip(("dq_i", "dk_i", "dw"), got[:3], want[:3],
                                 limits):
        assert rel_err(g, t) < limit, name
    for g in got[3:] + want[3:]:
        assert not np.any(g)


def test_eight_shares_of_sixteen_add_up_to_the_uncut_layer():
    """The share test of the guide's section 4 at this model's ratio: 128
    experts, 16 on each of 8 chips, top 8 renormalised: every share's
    partial result is its own experts' part and the eight add up to the
    uncut layer's (``tests/test_sdar.py`` holds the same at 16 experts, 2 a
    chip, and the rows' buffer)."""
    from horovod_tpu.parallel.moe import moe_ffn

    x, router, gate, up, down = layer_inputs(11, tokens=64, experts=128)
    k = 8
    whole = dense_share(x, router, gate, up, down, k, range(128))
    total, counts = np.zeros_like(whole), None
    for share in range(8):
        held = tuple(range(16 * share, 16 * share + 16))
        pick = np.asarray(held)
        with jax.default_matmul_precision("highest"):
            y, stats = jax.jit(lambda *a: moe_ffn(
                *a, k=k, dtype=jnp.float32, held=held, norm_topk_prob=True))(
                    x, router, gate[pick], up[pick], down[pick])
        np.testing.assert_allclose(
            y, dense_share(x, router, gate, up, down, k, held), atol=2e-5)
        total += np.asarray(y, np.float64)
        if counts is None:
            counts = np.asarray(stats.tokens_per_expert)
        np.testing.assert_array_equal(stats.tokens_per_expert, counts)
    np.testing.assert_allclose(total, whole, atol=1e-4)
    assert counts.sum() == x.shape[0] * x.shape[1] * k


def test_the_preset_counts_the_whole_model_and_publishes_its_pairs():
    """``keye_vl_2_0_30b_a3b_config()`` as published: the whole model by the
    program's own count ('30B'), a layer's indexer 2,261,120; the gauges of
    a step of one sequence of 16,384 from the shapes."""
    from horovod_tpu.core import metrics
    from horovod_tpu.models.transformer import (
        Transformer,
        attention_pairs,
        keye_vl_2_0_30b_a3b_config,
        publish_indexer,
    )

    cfg = keye_vl_2_0_30b_a3b_config()
    assert cfg.num_layers == 48
    # Two of the 48 layers traced, the other 46 counted like the second.
    shapes = jax.eval_shape(
        lambda key: Transformer(keye_vl_2_0_30b_a3b_config(
            num_layers=2)).init(key, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))["params"]
    count = lambda tree: sum(  # noqa: E731
        x.size for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layer_0"]) == count(shapes["layer_1"])
    assert count(shapes) + 46 * count(shapes["layer_1"]) == 30_640_656_384
    attn = nn.meta.unbox(shapes["layer_0"]["attn"])
    assert count(attn["indexer"]) == 2_261_120
    assert count(attn) == 18_874_624 + 2_261_120
    assert attn["indexer"]["q"]["kernel"].shape == (2048, 16 * 64)
    assert attn["indexer"]["k"]["kernel"].shape == (2048, 64)
    assert attn["indexer"]["weights"]["kernel"].shape == (2048, 16)
    four = keye_vl_2_0_30b_a3b_config(num_layers=4)
    read = publish_indexer(four, 16384)
    assert read == {"indexer_pairs_scored_per_step": 4 * 134_225_920,
                    "attention_pairs_chosen_per_step": 4 * 31_458_304}
    assert metrics.registry.get_gauge("indexer_pairs_scored_per_step") \
        == 4 * 134_225_920
    assert attention_pairs(four, 16384) \
        == {"window": 0, "global": 4 * 31_458_304}
    with pytest.raises(ValueError, match="positions='rope'"):
        tiny_model(positions="learned").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
