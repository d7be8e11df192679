"""SmallThinker-21BA3B: a layer pattern (window layers with RoPE among global
layers without positions), the causal and window rules of
``kernels/masked_attention.py`` (the rules, the einsum under them and the
pallas kernel in interpret mode), an expert layer routed by the block's input
with relu gates, and the whole model against the plain reference
(``chip_bench/configs/smallthinker-21b-a3b_reference.py``: float32, a dense masked softmax, one
dense expert at a time under a mask, nothing of ``horovod_tpu``) on seeded
weights at tiny widths.
"""

import dataclasses
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from .helpers import REPO_ROOT, load_reference
from .test_olmoe import layer_inputs, rel_err

ref = load_reference("smallthinker-21b-a3b")

# 4 layers of the published pattern with a window shorter than the sequence,
# 7 query heads a KV head, 2 of 8 experts held, a sliced vocabulary.
TINY = dict(num_hidden_layers=4, hidden_size=64, num_attention_heads=14,
            num_key_value_heads=2, head_dim=8, moe_ffn_hidden_size=32,
            moe_num_primary_experts_published=8, moe_num_primary_experts=2,
            experts_held=[1, 6], moe_num_active_primary_experts=3,
            norm_topk_prob=True, vocab_size=128, rms_norm_eps=1e-6,
            rope_theta=1500000, sliding_window_size=8,
            sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
            load_balancing_loss_weight=0.001, sequence_length=32)


def tiny_model(dtype=jnp.float32, **overrides):
    from horovod_tpu.models.transformer import (
        LayerKind,
        Transformer,
        smallthinker_21b_a3b_config,
    )

    z = {**TINY, **overrides}
    pattern = tuple(
        LayerKind(z["sliding_window_size"] if windowed else 0, bool(rope))
        for windowed, rope in zip(z["sliding_window_layout"],
                                  z["rope_layout"]))
    return Transformer(smallthinker_21b_a3b_config(
        vocab_size=z["vocab_size"], num_layers=z["num_hidden_layers"],
        num_heads=z["num_attention_heads"],
        num_kv_heads=z["num_key_value_heads"], head_width=z["head_dim"],
        d_model=z["hidden_size"], d_ff=z["moe_ffn_hidden_size"], max_len=64,
        num_experts=z["moe_num_primary_experts_published"],
        experts_per_token=z["moe_num_active_primary_experts"],
        experts_held=None if z["experts_held"] is None
        else tuple(z["experts_held"]),
        layer_pattern=pattern, dtype=dtype)), z


def tokens_of(sizes, seed, batch=2):
    return {"tokens": jax.random.randint(
        jax.random.PRNGKey(seed), (batch, sizes["sequence_length"]), 0,
        sizes["vocab_size"])}


def program_loss(model, sizes):
    """The program's model under the loss the reference states."""
    from horovod_tpu.models.transformer import moe_stats

    def loss(params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        logits, state = model.apply({"params": params}, tokens,
                                    mutable=["moe"])
        stats = moe_stats(state["moe"])
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32)[:, :-1], tokens[:, 1:])
        total = jnp.sum(nll) / (b * (s - 1)) \
            + sizes["load_balancing_loss_weight"] \
            * jnp.mean(stats.load_balancing_loss)
        return total, (logits, jnp.sum(stats.tokens_per_expert, axis=1))

    return loss


def louder(params, by=5.0):
    """Every matrix of the layers ``by`` times as large: at 64 wide and
    normal(0.02) the layers add little to the embedding, and what tells one
    layer from another (a window, a position, a gate) would hide in the
    rounding."""
    return jax.tree_util.tree_map(
        lambda x: x * by if x.ndim > 1 else x, params)


def seeded(model, seed=0):
    """Fresh weights with the embedding at normal(1.0), as the configuration
    draws them (at 0.02 every position is routed alike), and the layers'
    matrices at normal(0.1)."""
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 32), jnp.int32))["params"])
    return {**{name: louder(layer) for name, layer in params.items()},
            "embed": {"embedding": params["embed"]["embedding"] * 50.0},
            "lm_head": params["lm_head"]}


def zero_counters(sizes):
    from horovod_tpu.parallel.moe import moe_counters

    return moe_counters(sizes["num_hidden_layers"],
                        sizes["moe_num_primary_experts_published"],
                        share=True)


# fp32: the two differ in the order of their sums only (measured 3e-7 to
# 1e-6).  bf16 against the fp32 reference, as tests/test_sdar.py argues, on
# seeds where both chose the same experts for every position (5 of 30 at
# these louder weights); measured over them: logits 5.5e-3 to 7.9e-3, loss
# 1.2e-5 to 4.7e-5, the worst gradient leaf (always a gate's: relu's
# derivative is a step, and an expert here sees a few dozen rows) 0.09 to
# 0.15.  The limits are about three times the largest measured.
TOLERANCE = {"float32": dict(logits=1e-5, loss=1e-5, grads=1e-5),
             "bfloat16": dict(logits=2.5e-2, loss=1.5e-4, grads=4.5e-1)}


@pytest.mark.parametrize("dtype,seed", [
    ("float32", 0), ("float32", 1), ("bfloat16", 2), ("bfloat16", 12)])
def test_program_agrees_with_the_plain_reference(dtype, seed):
    """Logits, loss, router counts, rows held and every gradient leaf."""
    model, sizes = tiny_model(getattr(jnp, dtype))
    params, batch = seeded(model, seed), tokens_of(sizes, seed + 1)
    with jax.default_matmul_precision("highest"):
        (loss, (logits, counts)), grads = jax.jit(jax.value_and_grad(
            program_loss(model, sizes), has_aux=True))(params, batch)
    (want_loss, aux), want_grads = jax.jit(jax.value_and_grad(
        ref.make_loss(sizes), has_aux=True))(
            params, zero_counters(sizes), batch)
    tol = TOLERANCE[dtype]
    assert logits.shape == (2, 32, 128)
    assert rel_err(logits, ref.logits(params, batch, sizes)) < tol["logits"]
    assert abs(float(loss) - float(want_loss)) < tol["loss"] * float(want_loss)
    np.testing.assert_array_equal(counts, aux["tokens_per_expert"])
    # 2 x 32 positions, 3 experts each, held or elsewhere, in every layer.
    np.testing.assert_array_equal(
        aux["rows_held"] + aux["rows_elsewhere"], [2 * 32 * 3] * 4)
    errs = jax.tree_util.tree_map(rel_err, grads, want_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < tol["grads"], (jax.tree_util.keystr(worst[0]), worst[1])


@pytest.mark.parametrize("wrong", ["no_window", "rope_everywhere",
                                   "router_after_attention", "silu"])
def test_each_wrong_variant_of_the_reference_is_another_model(wrong):
    """What the check tool breaks on purpose moves the logits by far more
    than any rounding, so a limit can tell it apart."""
    model, sizes = tiny_model()
    params, batch = seeded(model), tokens_of(sizes, 1)
    want = ref.logits(params, batch, sizes)
    got = ref.logits(params, batch, sizes, wrong=(wrong,))
    assert rel_err(got, want) > 1e-2


# -- the layer pattern ---------------------------------------------------------


def test_all_global_layers_with_rope_are_todays_uniform_causal_model():
    """A pattern that says of every layer what the uniform model says of all
    of them is that model, on the same weights."""
    from horovod_tpu.models.transformer import LayerKind, Transformer

    model, sizes = tiny_model()
    params, batch = seeded(model), tokens_of(sizes, 2)
    spelled = Transformer(dataclasses.replace(
        model.cfg, layer_pattern=(LayerKind(0, True),) * 4))
    uniform = Transformer(dataclasses.replace(model.cfg, layer_pattern=None))
    got = spelled.apply({"params": params}, batch["tokens"],
                        mutable=["moe"])[0]
    want = uniform.apply({"params": params}, batch["tokens"],
                         mutable=["moe"])[0]
    np.testing.assert_array_equal(got, want)
    # And the published pattern is another model on those weights.
    mixed = model.apply({"params": params}, batch["tokens"],
                        mutable=["moe"])[0]
    assert rel_err(mixed, want) > 1e-2
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(model.cfg, layer_pattern=(LayerKind(),) * 3) \
            .layer_kind(0)


def test_a_global_layer_carries_no_position_and_a_window_layer_does():
    from horovod_tpu.models.transformer import Block

    model, sizes = tiny_model()
    cfg = model.cfg
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    shifted = jnp.arange(32) + 1000
    for layer, moves in ((0, False), (1, True)):
        block = Block(cfg, cfg.layer_kind(layer))
        params = louder(block.init(jax.random.PRNGKey(1), x)["params"])
        run = lambda positions: block.apply(  # noqa: E731
            {"params": params}, x, positions, mutable=["moe"])[0]
        if moves:
            # RoPE is relative: a common shift moves nothing but rounding,
            # a stretch does.
            assert rel_err(run(shifted), run(None)) < 1e-4
            assert rel_err(run(2 * jnp.arange(32)), run(None)) > 1e-3
        else:
            np.testing.assert_array_equal(run(shifted), run(None))
            np.testing.assert_array_equal(run(2 * jnp.arange(32)), run(None))


def test_pairs_allowed_become_gauges_by_kind():
    from horovod_tpu.core import metrics
    from horovod_tpu.models.transformer import (
        attention_pairs,
        publish_attention,
        smallthinker_21b_a3b_config,
    )

    cfg = smallthinker_21b_a3b_config(num_layers=4)
    pairs = attention_pairs(cfg, 16384)
    causal = 16384 * 16385 // 2
    assert pairs == {"window": 3 * (causal - 12288 * 12289 // 2),
                     "global": causal}
    assert pairs["window"] // 3 == 58_722_304
    assert publish_attention(cfg, 16384, sequences=2) == {
        "window": 2 * pairs["window"], "global": 2 * pairs["global"]}
    assert "attn_allowed_pairs_per_step" in metrics.CATALOG
    # The whole model: 13 periods.
    whole = attention_pairs(smallthinker_21b_a3b_config(), 16384)
    assert whole == {k: 13 * v for k, v in pairs.items()}


# -- the rules and the kernel --------------------------------------------------


@pytest.mark.parametrize("seq_len,window", [(16, 1), (16, 5), (16, 16),
                                            (16, 40), (48, 7), (24, None)])
def test_window_rule_against_a_brute_force_table(seq_len, window):
    """The rule of kernels/masked_attention.py, on numpy and on JAX integers
    (what the kernels compute in a partial tile), and the reference's own,
    against a table filled pair by pair; a window of one position sees itself alone and one of the whole
    sequence or more is the causal rule.  No window: the causal rule."""
    from horovod_tpu.kernels import masked_attention as ma

    rule = ma.Causal() if window is None else ma.Window(window)
    table = np.zeros((seq_len, seq_len), bool)
    for i in range(seq_len):
        for j in range(seq_len):
            table[i, j] = j <= i and (window is None or i - j < window)
    ids = np.arange(seq_len)
    got = rule.allowed(ids[:, None], ids[None, :], seq_len)
    np.testing.assert_array_equal(got, table)
    assert rule.allowed_pairs(seq_len) == table.sum()
    np.testing.assert_array_equal(
        np.asarray(ref.may_see(ids[:, None], ids[None, :], window or 0)),
        table)
    on_device = rule.allowed(jnp.arange(seq_len)[:, None],
                             jnp.arange(seq_len)[None, :], seq_len)
    np.testing.assert_array_equal(np.asarray(on_device), table)
    if window == 1:
        np.testing.assert_array_equal(table, np.eye(seq_len, dtype=bool))
    if window is not None and window >= seq_len:
        np.testing.assert_array_equal(
            table, ma.Causal().allowed(ids[:, None], ids[None, :]))
    with pytest.raises(ValueError):
        ma.Window(0)


@pytest.mark.parametrize("rule_name,visited", [("window", 70),
                                               ("causal", 136)])
def test_tiles_visited_at_the_cells_shape(rule_name, visited):
    """16,384 positions in tiles of 1024: a window of 4096 touches a query
    tile's own key tile and the four before it, the causal rule the lower
    triangle; the kernel takes the shape, and rules hash by what they say."""
    from horovod_tpu.kernels import masked_attention as ma

    rule = ma.Window(4096) if rule_name == "window" else ma.Causal()
    n, s = ma.BLOCK, 16384
    assert ma.takes(rule, s, 128) and not ma.takes(rule, s, 96)
    assert not ma.takes(rule, s + 512, 128)
    from horovod_tpu.kernels import masked_attention_bwd

    assert masked_attention_bwd.tile_table(rule, s, n, n)[0].size == visited
    assert rule.allowed_pairs(s) / n ** 2 < visited
    assert ma.Window(4096) == ma.Window(4096) != ma.Window(2048)
    assert hash(ma.Causal()) == hash(ma.Causal())
    assert rule.scope == "hvd.attn." + rule_name


@pytest.mark.parametrize("rule_name", ["causal", "window"])
def test_kernel_in_interpret_mode_matches_the_grouped_einsum(rule_name):
    """Forward and the three gradients at two tiles, three query heads on
    one KV head of 128, the window's edge inside a tile."""
    from horovod_tpu.kernels import masked_attention as ma

    rule = ma.Window(1536) if rule_name == "window" else ma.Causal()
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, w = (jax.random.normal(k, (1, 2 * ma.BLOCK, 3, 128)) for k in ks[:2])
    k, v = (jax.random.normal(k, (1, 2 * ma.BLOCK, 1, 128)) for k in ks[2:])

    def through(attention):
        return jax.value_and_grad(
            lambda *qkv: jnp.sum(attention(*qkv) * w), argnums=(0, 1, 2))

    with jax.default_matmul_precision("highest"):
        got, got_grads = through(lambda *qkv: ma.attention(
            *qkv, rule, interpret=True))(q, k, v)
        want, want_grads = through(lambda *qkv: ma.einsum(*qkv, rule))(
            q, k, v)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    for g, wg in zip(got_grads, want_grads):
        assert rel_err(g, wg) < 1e-5


def test_grouped_heads_under_causal_are_not_repeated_and_multi_head_stays():
    """Grouped KV heads under `causal` go through the grouped einsum here
    (the kernel on a TPU), the same numbers as each KV head repeated;
    one KV head a query head keeps the path it had (OLMoE's)."""
    from horovod_tpu.models.transformer import _scaled_dot_attention

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 16, 6, 8))
    k, v = (jax.random.normal(key, (2, 16, 2, 8)) for key in ks[1:])
    got = _scaled_dot_attention(q, k, v, True, 8)
    want = _scaled_dot_attention(q, jnp.repeat(k, 3, axis=2),
                                 jnp.repeat(v, 3, axis=2), True, 8)
    assert rel_err(got, want) < 1e-6
    grouped = jax.jit(lambda *a: _scaled_dot_attention(*a, True, 8)) \
        .lower(q, k, v).as_text()
    assert "bqngd" not in grouped and "2x16x6x8" in grouped
    assert "tensor<2x16x2x3x8xf32>" in grouped     # q grouped, k not repeated
    windowed = _scaled_dot_attention(q, k, v, True, 8, window=4)
    assert rel_err(windowed, want) > 1e-2
    assert rel_err(_scaled_dot_attention(q, k, v, True, 8, window=16),
                   want) < 1e-6
    with pytest.raises(ValueError, match="causal"):
        _scaled_dot_attention(q, k, v, False, 8, window=4)


# -- the expert layer: another tensor routes, relu gates -----------------------


def dense_layer(x, routed_by, router, gate, up, down, k, held, act):
    """The held experts' part of the layer in plain jax: every expert on
    every token under a mask, weights renormalised over each token's k most
    probable experts wherever they live, those read from ``routed_by``."""
    xf = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(routed_by.reshape(xf.shape[0], -1) @ router,
                           axis=-1)
    top, chosen = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    y = 0.0
    for i, e in enumerate(held):
        we = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
        y = y + we[:, None] * ((act(xf @ gate[i]) * (xf @ up[i])) @ down[i])
    return y.reshape(x.shape)


@pytest.mark.parametrize("held", [None, (0, 5)])
def test_another_router_input_routes_by_it(held):
    """The counts and the weights follow ``router_input``, the products the
    rows; silu and relu are different layers."""
    from horovod_tpu.parallel.moe import moe_ffn

    x, router, gate, up, down = layer_inputs(4, experts=16)
    routed_by = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    k = 4
    ids = tuple(range(16)) if held is None else held
    pick = np.asarray(ids)
    share = {} if held is None else {"held": held}
    with jax.default_matmul_precision("highest"):
        y, stats = moe_ffn(x, router, gate[pick], up[pick], down[pick], k=k,
                           dtype=jnp.float32, norm_topk_prob=True,
                           router_input=routed_by, activation="relu",
                           **share)
        by_rows, rows_stats = moe_ffn(
            x, router, gate[pick], up[pick], down[pick], k=k,
            dtype=jnp.float32, norm_topk_prob=True, activation="relu",
            **share)
        want = dense_layer(x, routed_by, router, gate[pick], up[pick],
                           down[pick], k, ids, jax.nn.relu)
        silu = dense_layer(x, routed_by, router, gate[pick], up[pick],
                           down[pick], k, ids, jax.nn.silu)
    assert rel_err(y, want) < 1e-5
    assert rel_err(y, silu) > 1e-2 and rel_err(by_rows, want) > 1e-2
    chosen = jax.lax.top_k(routed_by.reshape(-1, 32) @ router, k)[1]
    np.testing.assert_array_equal(
        stats.tokens_per_expert[0], np.bincount(np.asarray(chosen).ravel(),
                                                minlength=16))
    assert (np.asarray(rows_stats.tokens_per_expert)
            != np.asarray(stats.tokens_per_expert)).any()
    with pytest.raises(ValueError, match="router_input"):
        moe_ffn(x, router, gate, up, down, k=k, router_input=routed_by[:1])
    with pytest.raises(ValueError, match="unknown activation"):
        moe_ffn(x, router, gate, up, down, k=k, activation="gelu")


@pytest.mark.parametrize("held,skew", [(None, 0.0), (None, 6.0),
                                       ((0, 5), 0.0), ((0, 5), 6.0)])
def test_relu_and_router_input_gradients_match_autodiff_of_the_dense_form(
        held, skew):
    """All six operands' gradients (the router's input among them) through
    the sort, the grouped products and the hand-written cotangents, whole
    layer and share, even routing and a skew that runs the chunks behind the
    first (recomputed in the backward pass)."""
    from horovod_tpu.parallel.moe import moe_ffn

    x, router, gate, up, down = layer_inputs(11, experts=16, skew=skew)
    routed_by = jax.random.normal(jax.random.PRNGKey(2), x.shape) \
        + (1.0 if skew else 0.0)
    k = 4
    ids = tuple(range(16)) if held is None else held
    pick = np.asarray(ids)
    share = {} if held is None else {"held": held}
    w = jax.random.normal(jax.random.PRNGKey(1), x.shape)

    def dense(x, routed_by, router, gate, up, down):
        return jnp.sum(dense_layer(x, routed_by, router, gate, up, down, k,
                                   ids, jax.nn.relu) * w)

    def program(x, routed_by, router, gate, up, down):
        y, _ = moe_ffn(x, router, gate, up, down, k=k, dtype=jnp.float32,
                       norm_topk_prob=True, router_input=routed_by,
                       activation="relu", **share)
        return jnp.sum(y * w)

    args = (x, routed_by, router, gate[pick], up[pick], down[pick])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(program, argnums=tuple(range(6))))(*args)
        want = jax.jit(jax.grad(dense, argnums=tuple(range(6))))(*args)
    for g, wg in zip(got, want):
        assert rel_err(g, wg) < 2e-5
    if not skew:          # with it the softmax is saturated: exactly zero
        assert float(jnp.abs(got[1]).max()) > 0  # the router's input learns


@pytest.mark.parametrize("skew", [0.0, 6.0])
def test_eight_shares_of_8_add_up_to_the_uncut_layer_of_64(skew):
    """64 relu-gated experts, 8 on each of 8 chips, top 6 renormalised,
    routed by another tensor than the rows: every share's partial result is
    its own experts' part, the eight add up to the uncut layer, and every
    share counts the same 64-wide routing."""
    from horovod_tpu.parallel.moe import moe_ffn, row_buffer

    x, router, gate, up, down = layer_inputs(7, experts=64, skew=skew)
    routed_by = jax.random.normal(jax.random.PRNGKey(5), x.shape) \
        + (1.0 if skew else 0.0)
    k, n = 6, x.shape[0] * x.shape[1]
    # A quarter of these 72 rows' mean share is no whole chunk: one chunk.
    assert row_buffer(n * k, 8, 64) == (1, n * k)
    assert row_buffer(16384 * 6, 8, 64) == (28, 15360)  # the cell's
    with jax.default_matmul_precision("highest"):
        whole, whole_stats = jax.jit(lambda *a: moe_ffn(
            *a[:5], k=k, dtype=jnp.float32, norm_topk_prob=True,
            router_input=a[5], activation="relu"))(
                x, router, gate, up, down, routed_by)
        want = dense_layer(x, routed_by, router, gate, up, down, k,
                           range(64), jax.nn.relu)
    assert rel_err(whole, want) < 1e-5
    total = np.zeros(x.shape, np.float64)
    for share in range(8):
        held = tuple(range(8 * share, 8 * share + 8))
        pick = np.asarray(held)
        with jax.default_matmul_precision("highest"):
            y, stats = jax.jit(lambda *a: moe_ffn(
                *a[:5], k=k, dtype=jnp.float32, held=held,
                norm_topk_prob=True, router_input=a[5],
                activation="relu"))(
                    x, router, gate[pick], up[pick], down[pick], routed_by)
            part = dense_layer(x, routed_by, router, gate[pick], up[pick],
                               down[pick], k, held, jax.nn.relu)
        np.testing.assert_allclose(y, part, atol=2e-5)
        total += np.asarray(y, np.float64)
        np.testing.assert_array_equal(stats.tokens_per_expert,
                                      whole_stats.tokens_per_expert)
    np.testing.assert_allclose(total, whole, atol=1e-4)
    counts = np.asarray(whole_stats.tokens_per_expert)
    assert counts.sum() == n * k
    if skew:
        assert counts[0, 0] > 0.9 * n        # expert 0 in nearly every top 6


# -- the configuration --------------------------------------------------------


def _config_module():
    import sys

    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from chip_bench import spec

    cell = spec.Cell("smallthinker-21b-a3b-wfbp-1chip", root=REPO_ROOT)
    return cell.config_module(), cell.sizes


# PowerInfer/SmallThinker-21BA3B-Instruct config.json, copied from the
# catalog's row.
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


def test_configuration_keeps_every_published_width():
    module, sizes = _config_module()
    reduced = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert sizes["reduced"] == reduced
    differs = [k for k, v in PUBLISHED.items() if sizes.get(k, "absent") != v]
    assert sorted(differs) == sorted(reduced)
    assert (sizes["num_hidden_layers"], sizes["moe_num_primary_experts"],
            sizes["vocab_size"]) == (4, 8, 18992)
    # The floors: a whole period and four layers, 8 experts, an eighth of the
    # vocabulary.
    assert sizes["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for key in reduced:
        assert sizes[key + "_published"] == PUBLISHED[key]
    assert sizes["experts_held"] == list(range(8))
    assert module.layer_kinds(sizes) == [(0, False)] + [(4096, True)] * 3
    for key in ("source", "assumed", "deployment", "reduced_how"):
        assert sizes[key]
    for key in ("router_input", "biases", "auxiliary_losses"):
        assert sizes["assumed"][key]
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == sizes["name"]][0]
    assert entry["reduced"] == reduced and entry["source"] == sizes["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        for row in (r for r in rows
                    if r["name"] == "SmallThinker-21BA3B-Instruct"):
            assert row["config"] == PUBLISHED
            assert row["source_url"] == sizes["source"]


def test_flops_and_attention_cost_come_from_the_shapes():
    module, sizes = _config_module()
    s = 16384
    causal = s * (s + 1) // 2
    window = causal - 12288 * 12289 // 2
    assert module.allowed_pairs(sizes) == {"window": 3 * window,
                                           "global": causal}
    macs = module.matmul_macs(sizes)
    assert {k: round(2 * v / 1e12, 2) for k, v in macs.items()} == {
        "qkvo": 2.75, "attention_scores": 2.22, "attention_values": 2.22,
        "router": 0.02, "experts": 0.58, "head": 1.59}
    assert macs["head"] == s * 2560 * 18992
    assert macs["experts"] == 4 * s * 0.75 * 3 * 2560 * 768
    # Over the allowed pairs: 2.3 times fewer than four causal layers, 3.5
    # times fewer than the square.
    assert macs["attention_scores"] == (3 * window + causal) * 28 * 128
    assert macs["attention_scores"] < 4 * causal * 28 * 128 / 1.7
    assert module.flops_per_sample(sizes) == 6 * sum(macs.values())
    operations, moved = module.mixed_attention_cost(sizes)
    assert operations == 2 * 6 * (3 * window + causal) * 28 * 128
    assert moved == 4 * 2 * 2 * s * 128 * (2 * 28 + 2 * 4)
    # Compute-bound on a v5e: 13.3 T operations against 0.54 GB.
    assert operations / 197e12 > 10 * moved / 819e9
    config = module.Config(sizes)
    assert [(k.window, k.rope, k.mixer, k.ffn)
            for k in config.model.cfg.layer_pattern] \
        == [(0, False, "attention", None)] \
        + [(4096, True, "attention", None)] * 3
    shapes = nn.meta.unbox(jax.eval_shape(
        config.model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 16), jnp.int32))["params"])
    count = lambda tree: sum(  # noqa: E731
        x.size for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layer_0"]["attn"]) == 20_971_520
    assert count(shapes["layer_0"]) == 68_326_400
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == 48_619_520
    assert count(shapes) == 370_547_200
    # Over a quarter of one chip's 16 GB at 16 B a parameter.
    assert 5.9e9 < count(shapes) * 16 < 6.0e9


def test_batch_and_weights_come_from_the_seed():
    module, sizes = _config_module()
    small = {**sizes, "sequence_length": 64}
    config = module.Config(small)
    batch = jax.jit(config.make_batch)(jax.random.PRNGKey(3))
    again = jax.jit(config.make_batch)(jax.random.PRNGKey(3))
    other = jax.jit(config.make_batch)(jax.random.PRNGKey(4))
    assert np.array_equal(batch["tokens"], again["tokens"])
    assert not np.array_equal(batch["tokens"], other["tokens"])
    assert batch["tokens"].shape == (1, 64)
    assert 0 <= int(batch["tokens"].min()) \
        and int(batch["tokens"].max()) < sizes["vocab_size"]
    # Weights from the seed too; the embedding at its own width (`assumed`).
    shapes, aux = jax.eval_shape(config.init, jax.random.PRNGKey(3))
    assert sorted(aux) == ["rows_elsewhere", "rows_held", "steps",
                           "tokens_per_expert"]
    assert aux["tokens_per_expert"].shape == (4, 64)
    assert "q_norm" not in shapes["layer_0"]["attn"]
    assert shapes["layer_0"]["router"].shape == (2560, 64)
    assert sizes["embedding_init_std"] == 1.0


TINY_CELL = {
    "module": "smallthinker-21b-a3b", "head_dim": 8, "hidden_size": 64,
    "max_position_embeddings": 64, "moe_ffn_hidden_size": 32,
    "moe_num_active_primary_experts": 3, "moe_num_primary_experts": 2,
    "moe_num_primary_experts_published": 8, "experts_held": [0, 1],
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 14, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1], "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1],
    "sliding_window_size": 8, "tie_word_embeddings": False,
    "vocab_size": 128, "embedding_init_std": 1.0, "sequence_length": 32,
    "per_chip_batch": 2, "load_balancing_loss_weight": 0.001,
    "adamw_learning_rate": 4e-4, "warmup_steps": 4,
    "warmup_start_share": 0.01, "adamw_b1": 0.9, "adamw_b2": 0.95,
    "adamw_eps": 1e-8, "adamw_weight_decay": 0.1, "clip_global_norm": 1.0,
    "logits_rtol": 0.2}


@pytest.mark.parametrize("limit,passes", [(0.2, True), (1e-6, False)])
def test_the_configurations_own_limit_holds_the_logits(limit, passes, capfd):
    """Behind ``_chip_bench_grad`` the program's logits are held to the
    float32 reference's, once, before the reference's first step: inside
    ``logits_rtol`` the reference's gradient comes back, outside it the run
    ends there.  The reference in a lower precision and with a layer wrong
    is what the limit is set against; in float32 and sound it is zero."""
    module, _ = _config_module()
    sizes = {k: v for k, v in TINY_CELL.items() if k != "module"}
    config = module.Config({**sizes, "logits_rtol": limit})
    params, aux = jax.jit(config.init)(jax.random.PRNGKey(5))
    batch = jax.jit(config.make_batch)(jax.random.PRNGKey(6))
    if not passes:
        with pytest.raises(SystemExit, match="over the limit 1.0e-06"):
            config._chip_bench_grad(params, aux, batch)
        return
    (loss, _), grads = config._chip_bench_grad(params, aux, batch)
    assert "float32 reference's (limit 2.0e-01)" in capfd.readouterr().err
    config._chip_bench_grad(params, aux, batch)      # checked once
    assert capfd.readouterr().err == ""
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    assert float(loss) == pytest.approx(config.first_loss, rel=0.25)
    assert 0 < config.logits_error(params, batch) < limit
    assert 0 < config.logits_error(params, batch, jnp.bfloat16) < limit
    assert config.logits_error(params, batch, jnp.float32) == 0
    assert config.logits_error(params, batch, jnp.float32,
                               wrong=("no_window",)) > 1e-2


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model (the layer pattern, 2 of 8 experts held under the step's
    ``shard_map``, the router's input beside the rows) against the plain
    reference's three losses, and the new per-layer metrics left out where
    there is no device op line to read."""
    import sys

    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from chip_bench.tests import rehearse

    names = ("mixed_attention_ms_step", "mixed_attention_roofline_pct",
             "moe_experts_ms_step", "wfbp_dispatch_ms_step")
    files = {"configs/tiny-smallthinker.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-smallthinker-wfbp", "tiny-smallthinker", "wfbp", 1)],
        files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-smallthinker-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    assert r0["per_layer"]["tiny.mixed_attention_ms_step"] is None
    assert r0["per_layer"]["tiny.mixed_attention_roofline_pct"] is None
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0
