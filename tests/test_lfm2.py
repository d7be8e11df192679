"""LFM2-8B-A1B: a layer pattern that names each layer's mixer and FFN (gated
short convolutions among attention layers, a dense gated FFN before the
expert layers), a router that scores by sigmoid and chooses by score plus a
bias the step keeps, and the whole model against the plain reference
(``chip_bench/configs/lfm2-8b-a1b_reference.py``: float32, the convolution as shifted sums, a
dense masked softmax, one dense expert at a time under a mask, nothing of
``horovod_tpu``) on seeded weights at tiny widths.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax

from .helpers import load_reference
from .test_olmoe import layer_inputs, rel_err

ref = load_reference("lfm2-8b-a1b")

LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "conv", "full_attention",
               "conv", "conv", "conv", "full_attention", "conv", "conv",
               "conv", "full_attention", "conv", "conv", "full_attention",
               "conv", "conv"]

# The cut's five layers (a convolution with the dense FFN, then attention and
# three convolutions with experts), 4 query heads a KV head, 2 of 8 experts
# held, a sliced vocabulary.
TINY = dict(num_hidden_layers=5, layers_held=[0, 2, 3, 4, 5],
            layer_types=LAYER_TYPES, num_dense_layers=2, hidden_size=64,
            intermediate_size=96, moe_intermediate_size=32,
            num_attention_heads=8, num_key_value_heads=2, conv_L_cache=3,
            num_experts_published=8, num_experts=2, experts_held=[1, 6],
            num_experts_per_tok=3, norm_topk_prob=True,
            routed_scaling_factor=1, vocab_size=128, norm_eps=1e-5,
            rope_theta=1000000, expert_bias_update_rate=1e-3,
            sequence_length=32)


def tiny_model(dtype=jnp.float32, **overrides):
    from horovod_tpu.models.transformer import (
        LayerKind,
        Transformer,
        lfm2_8b_a1b_config,
    )

    z = {**TINY, **overrides}
    held = z["experts_held"]
    pattern = tuple(
        LayerKind(0, True, "conv" if mixer == "conv" else "attention",
                  "dense" if ffn == "dense" else None)
        for mixer, ffn in ref.layer_plan(z))
    return Transformer(lfm2_8b_a1b_config(
        vocab_size=z["vocab_size"], num_layers=z["num_hidden_layers"],
        num_heads=z["num_attention_heads"],
        num_kv_heads=z["num_key_value_heads"],
        head_width=z["hidden_size"] // z["num_attention_heads"],
        d_model=z["hidden_size"], d_ff=z["moe_intermediate_size"],
        d_ff_dense=z["intermediate_size"], max_len=64,
        num_experts=z["num_experts_published"],
        experts_per_token=z["num_experts_per_tok"],
        experts_held=None if held is None else tuple(held),
        layer_pattern=pattern, dtype=dtype)), z


def all_held(**overrides):
    return dict(experts_held=list(range(8)), num_experts=8, **overrides)


def tokens_of(sizes, seed, batch=2):
    return {"tokens": jax.random.randint(
        jax.random.PRNGKey(seed), (batch, sizes["sequence_length"]), 0,
        sizes["vocab_size"])}


def seeded(model, seed=0, by=5.0):
    """Fresh weights with every matrix of the layers ``by`` times as large
    (at 64 wide and normal(0.02) the layers add little to the embedding, and
    what tells one layer from another would hide in the rounding), the taps
    at normal(0.5) and the embedding, which is the readout too, at
    normal(0.3)."""
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 32), jnp.int32))["params"])

    def louder(path, x):
        if path[-1].key == "conv":
            return x * 25.0
        return x * by if x.ndim > 1 else x

    return {**{name: jax.tree_util.tree_map_with_path(louder, layer)
               for name, layer in params.items()},
            "embed": {"embedding": params["embed"]["embedding"] * 15.0}}


def some_bias(sizes, seed=9, scale=0.2):
    """A selection bias large enough to change some of the top k."""
    return scale * jax.random.normal(
        jax.random.PRNGKey(seed), ref.zero_bias(sizes).shape)


def counters(sizes, bias=None):
    from horovod_tpu.parallel.moe import moe_counters

    out = moe_counters(4, sizes["num_experts_published"], share=True,
                       expert_bias=True)
    return out if bias is None else {**out, "expert_bias": bias}


def program_loss(model, sizes):
    """The program's model under the loss the reference states."""
    from horovod_tpu.models.transformer import (
        expert_bias_collection,
        moe_stats,
    )
    from horovod_tpu.parallel.moe import count_routing

    def loss(params, aux, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        logits, state = model.apply(
            {"params": params,
             "moe": expert_bias_collection(model.cfg, aux["expert_bias"])},
            tokens, mutable=["moe"])
        stats = moe_stats(state["moe"])
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32)[:, :-1], tokens[:, 1:])
        held = model.cfg.experts_held or range(model.cfg.num_experts)
        return jnp.sum(nll) / (b * (s - 1)), (logits, count_routing(
            aux, jnp.sum(stats.tokens_per_expert, axis=1), held=tuple(held),
            bias_update_rate=sizes["expert_bias_update_rate"]))

    return loss


# fp32: the two differ in the order of their sums only (measured 4e-7 to
# 6e-7 on the logits, 1e-7 on the loss, 2e-6 on the worst gradient leaf).
# bf16 against the fp32 reference on seeds where both chose the same experts
# for every position (4 of 100 at these louder weights and under a bias:
# a sigmoid's top 3 of 8 lie close); measured over them: logits 1.5e-2 to
# 1.6e-2, loss 2.8e-4 to 1.3e-3, the worst gradient leaf 6.5e-2 to 1.1e-1.
# The limits are about three times the largest measured.
# The rule for the seeds with a share held: the first two of 0..99 on which
# the bf16 program chooses the reference's experts at every position of every
# layer.  That is a property of how the bf16 program rounds, not of the
# router alone: the stream carries bf16's 4e-3 of rounding, a sigmoid's third
# and fourth score of 8 lie closer than that somewhere in nearly every batch,
# and any change of the program's rounding moves which seeds are spared.  At
# PR 45's parent they were 12 and 37 (and 46; 3 of 100); since PR 45, whose
# router sums the same terms in another order (three bf16 products over the
# split weights: 1e-7 of a logit, which one bf16 rounding of the stream
# behind it carries on as 4e-3), they are 26 and 46 (2 of 100); 96 seeds
# move rows on both trees, 44 of them the same rows.  On 12 the fourth
# expert layer now sends one row to expert 3 and not 2: position 24, where
# the reference's biased scores put 2 over 3 by 1.5e-3 of 0.59; on 37 one
# row to expert 2 and not 5: position 63, 2.0e-3 of 0.51.  Both gaps are
# inside bf16's rounding and four orders above fp32's, which
# ``test_three_pass_logits_are_the_float64_product`` holds the new product
# to.  On 26 and 46: logits 1.7e-2 and 1.8e-2, loss 4.6e-4 and 4.3e-4, the
# worst gradient leaf 9.4e-2 and 6.4e-2.
TOLERANCE = {"float32": dict(logits=2e-5, loss=1e-5, grads=5e-5),
             "bfloat16": dict(logits=5e-2, loss=4e-3, grads=3.5e-1)}


@pytest.mark.parametrize("dtype,seed,share", [
    ("float32", 0, True), ("float32", 1, True), ("float32", 2, False),
    ("bfloat16", 26, True), ("bfloat16", 46, True), ("bfloat16", 19, False)])
def test_program_agrees_with_the_plain_reference(dtype, seed, share):
    """Logits, loss, router counts, rows held, the stepped bias and every
    gradient leaf, a share of the experts held and all of them, routed under
    a bias that is not zero."""
    model, sizes = tiny_model(getattr(jnp, dtype),
                              **({} if share else all_held()))
    params, batch = seeded(model, seed), tokens_of(sizes, seed + 1)
    bias = some_bias(sizes, seed)
    aux = counters(sizes, bias)
    with jax.default_matmul_precision("highest"):
        (loss, (logits, new_aux)), grads = jax.jit(jax.value_and_grad(
            program_loss(model, sizes), has_aux=True))(params, aux, batch)
    (want_loss, want_aux), want_grads = jax.jit(jax.value_and_grad(
        ref.make_loss(sizes), has_aux=True))(params, aux, batch)
    tol = TOLERANCE[dtype]
    assert logits.shape == (2, 32, 128)
    assert rel_err(logits, ref.logits(params, batch, sizes, bias=bias)) \
        < tol["logits"]
    assert abs(float(loss) - float(want_loss)) < tol["loss"] * float(want_loss)
    for key in ("tokens_per_expert", "rows_held", "rows_elsewhere", "steps",
                "expert_bias"):
        np.testing.assert_array_equal(new_aux[key], want_aux[key])
    # 2 x 32 positions, 3 experts each, held or elsewhere, in every layer.
    np.testing.assert_array_equal(
        new_aux["rows_held"] + new_aux["rows_elsewhere"], [2 * 32 * 3] * 4)
    if not share:
        np.testing.assert_array_equal(new_aux["rows_elsewhere"], [0] * 4)
    assert np.all(np.abs(new_aux["expert_bias"] - bias) <= 1.001e-3)
    assert np.any(new_aux["expert_bias"] != bias)
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    errs = jax.tree_util.tree_map(rel_err, grads, want_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < tol["grads"], (jax.tree_util.keystr(worst[0]), worst[1])


@pytest.mark.parametrize("wrong", ["taps_reversed", "no_c_gate", "softmax",
                                   "bias_in_weights", "no_qk_norm",
                                   "scale_128"])
def test_each_wrong_variant_of_the_reference_is_another_model(wrong):
    """What the check tool breaks on purpose moves the logits by far more
    than any rounding, so a limit can tell it apart; the program stands with
    the sound reference."""
    model, sizes = tiny_model()
    params, batch = seeded(model), tokens_of(sizes, 1)
    bias = some_bias(sizes)
    want = ref.logits(params, batch, sizes, bias=bias)
    got = ref.logits(params, batch, sizes, wrong=(wrong,), bias=bias)
    assert rel_err(got, want) > 1e-2
    from horovod_tpu.models.transformer import expert_bias_collection

    with jax.default_matmul_precision("highest"):
        program = model.apply(
            {"params": params,
             "moe": expert_bias_collection(model.cfg, bias)},
            batch["tokens"], mutable=["moe"])[0]
    assert rel_err(program, want) < 2e-5


# -- the layer pattern ---------------------------------------------------------


def test_a_kind_names_its_mixer_and_its_ffn():
    """A convolution layer holds no attention and an attention layer no
    convolution; the dense layer holds a gated FFN of its own width and no
    router; the bias is no parameter."""
    model, sizes = tiny_model()
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32))
    shapes = jax.tree_util.tree_map(lambda x: x.shape,
                                    nn.meta.unbox(variables["params"]))
    assert sorted(shapes["layer_0"]) == ["conv", "ffn_down", "ffn_gate",
                                         "ffn_up", "ln1", "ln2"]
    assert shapes["layer_0"]["conv"] == {
        "in_proj": {"kernel": (64, 192)}, "conv": (64, 3),
        "out_proj": {"kernel": (64, 64)}}
    assert shapes["layer_0"]["ffn_gate"] == {"kernel": (64, 96)}
    assert sorted(shapes["layer_1"]) == ["attn", "experts_down",
                                         "experts_gate", "experts_up", "ln1",
                                         "ln2", "router"]
    assert shapes["layer_1"]["attn"]["q_norm"] == {"scale": (8,)}
    assert shapes["layer_1"]["router"] == (64, 8)
    assert shapes["layer_1"]["experts_gate"] == (2, 64, 32)
    assert "attn" not in shapes["layer_2"] and "conv" in shapes["layer_2"]
    assert "lm_head" not in shapes
    assert model.cfg.expert_layers() == (1, 2, 3, 4)
    assert sorted(variables["moe"]) == [f"layer_{i}" for i in (1, 2, 3, 4)]
    assert variables["moe"]["layer_1"]["bias"].shape == (8,)
    with pytest.raises(ValueError, match="unknown mixer"):
        from horovod_tpu.models.transformer import LayerKind, Transformer

        Transformer(dataclasses.replace(
            model.cfg, layer_pattern=(LayerKind(0, True, "scan"),) * 5)) \
            .init(jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32))


def test_the_preset_is_the_published_model():
    """All 24 layers from shapes alone: 18 convolutions and 6 attention
    layers where config.json puts them, 2 dense layers, 8.34 B parameters of
    which 7.75 B in experts."""
    from horovod_tpu.models.transformer import Transformer, lfm2_8b_a1b_config

    cfg = lfm2_8b_a1b_config()
    kinds = [cfg.layer_kind(i) for i in range(24)]
    assert ["full_attention" if k.mixer == "attention" else "conv"
            for k in kinds] == LAYER_TYPES
    assert [k.ffn for k in kinds] == ["dense"] * 2 + [None] * 22
    assert cfg.expert_layers() == tuple(range(2, 24))
    assert (cfg.head_dim, cfg.num_kv_heads, cfg.router_scoring,
            cfg.expert_bias, cfg.tie_embeddings) == (64, 8, "sigmoid", True,
                                                     True)
    shapes = nn.meta.unbox(jax.eval_shape(
        Transformer(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 16), jnp.int32))["params"])
    count = lambda tree: sum(  # noqa: E731
        x.size for x in jax.tree_util.tree_leaves(tree))
    experts = sum(count(layer[name]) for layer in shapes.values()
                  if "router" in layer
                  for name in ("experts_gate", "experts_up", "experts_down"))
    assert experts == 22 * 32 * 3 * 2048 * 1792
    assert count(shapes["layer_0"]["conv"]) == 16_783_360
    assert count(shapes["layer_2"]["attn"]) == 10_485_888
    assert 8.33e9 < count(shapes) < 8.35e9
    from horovod_tpu import models

    assert models.lfm2_8b_a1b_config is lfm2_8b_a1b_config


def test_a_pattern_as_a_whole_list_and_as_a_period_build_one_smallthinker():
    from horovod_tpu.models.transformer import (
        LayerKind,
        Transformer,
        smallthinker_21b_a3b_config,
    )

    period = (LayerKind(0, False),) + (LayerKind(8, True),) * 3
    small = dict(vocab_size=128, num_layers=8, num_heads=4, num_kv_heads=2,
                 head_width=8, d_model=32, d_ff=16, max_len=32,
                 num_experts=4, experts_per_token=2, dtype=jnp.float32)
    by_period = Transformer(smallthinker_21b_a3b_config(
        layer_pattern=period, **small))
    by_list = Transformer(smallthinker_21b_a3b_config(
        layer_pattern=period * 2, **small))
    tokens = tokens_of(dict(sequence_length=16, vocab_size=128), 0)["tokens"]
    params = by_period.init(jax.random.PRNGKey(0), tokens)["params"]
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        by_list.init(jax.random.PRNGKey(0), tokens)["params"])
    np.testing.assert_array_equal(
        by_period.apply({"params": params}, tokens, mutable=["moe"])[0],
        by_list.apply({"params": params}, tokens, mutable=["moe"])[0])
    assert [by_list.cfg.layer_kind(i) for i in range(8)] \
        == [by_period.cfg.layer_kind(i) for i in range(8)]


# -- the router ---------------------------------------------------------------


def _frozen_route(xf, router, k, norm_topk_prob=False, router_input=None,
                  scoring="softmax", bias=None, scale=1.0, n_group=1,
                  topk_group=1):
    """``parallel/moe.py::_route`` as the parent of PR 38 (3bb7be9) had it,
    word for word but for the scope; what it did not take has to arrive at
    its default."""
    assert (scoring, bias, scale, n_group, topk_group) \
        == ("softmax", None, 1.0, 1, 1)
    n, n_experts = xf.shape[0], router.shape[-1]
    with jax.named_scope("hvd.moe.router"):
        if router_input is not None:
            xf = router_input.reshape(n, -1)
        logits = jnp.dot(xf.astype(jnp.float32), router.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = lax.top_k(probs, k)                 # [n, k]
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        counts = jnp.sum(jax.nn.one_hot(experts, n_experts, dtype=jnp.int32),
                         axis=(0, 1))                          # [experts]
        balance = n_experts * jnp.sum(counts.astype(jnp.float32) / n
                                      * jnp.mean(probs, axis=0))
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return weights, experts, counts, balance, z


def _sibling(name):
    """(loss function of params, params) of a sibling's tiny model."""
    from . import test_olmoe, test_sdar, test_smallthinker

    if name == "olmoe":
        model, sizes = test_olmoe.tiny_model()
        params, batch = test_olmoe.seeded(model)
        loss = test_olmoe.program_loss(model, sizes)
    elif name == "sdar":
        model, sizes = test_sdar.tiny_model()
        params, batch = test_sdar.seeded(model), test_sdar.noised(sizes, 3)
        loss = test_sdar.program_loss(model, sizes)
    else:
        model, sizes = test_smallthinker.tiny_model()
        params = test_smallthinker.seeded(model)
        batch = test_smallthinker.tokens_of(sizes, 3)
        loss = test_smallthinker.program_loss(model, sizes)
    return lambda p: loss(p, batch)[0], params


@pytest.mark.parametrize("name", ["olmoe", "sdar", "smallthinker"])
def test_default_arguments_give_the_siblings_losses(name, monkeypatch):
    """With ``scoring``, ``bias`` and ``scale`` at their defaults the router
    computes what the parent's did: the sibling's loss through the new
    ``_route`` equals that through the parent's text, and its gradient lies
    within float32's rounding of it.  Bit for bit until PR 48 read the
    chosen scores by a compare (``moe._chosen``), around which XLA fuses the
    renormalisation in another order: SDAR's and SmallThinker's gradients
    still agree to the bit, OLMoE's to 1.3e-7 of its largest entry."""
    from horovod_tpu.parallel import moe

    loss, params = _sibling(name)
    # Jitted: one program a side (the session's compile cache then serves
    # the second), where the bare call compiles primitive by primitive.
    run = lambda: jax.jit(jax.value_and_grad(loss))(params)  # noqa: E731
    new, new_grads = run()
    monkeypatch.setattr(moe, "_route", _frozen_route)
    old, old_grads = run()
    assert float(new) == float(old)
    for a, b in zip(jax.tree_util.tree_leaves(new_grads),
                    jax.tree_util.tree_leaves(old_grads)):
        assert rel_err(a, b) < 1e-6


def test_the_bias_enters_the_choice_and_not_the_weights():
    """A bias large enough to change the top k changes which experts run and
    leaves the chosen experts' weights equal to their sigmoid scores
    renormalised (with the 1e-6); without renormalisation the weights are
    the scores themselves, times the scale."""
    from horovod_tpu.parallel.moe import _route

    x, router, *_ = layer_inputs(3, experts=8)
    xf = x.reshape(-1, x.shape[-1])
    k = 3
    scores = jax.nn.sigmoid(jnp.dot(xf, router,
                                    precision=lax.Precision.HIGHEST))
    bias = jnp.zeros((8,)).at[5].set(2.0).at[0].set(-2.0)
    plain = _route(xf, router, k, True, scoring="sigmoid")
    w, experts, counts, balance, z = _route(xf, router, k, True,
                                            scoring="sigmoid", bias=bias)
    np.testing.assert_array_equal(experts, lax.top_k(scores + bias, k)[1])
    assert np.all(np.any(np.asarray(experts) == 5, axis=1))
    assert not np.any(np.asarray(experts) == 0)
    assert np.any(np.asarray(plain[1]) != np.asarray(experts))
    at = jnp.take_along_axis(scores, experts, axis=-1)
    np.testing.assert_allclose(
        w, at / (jnp.sum(at, axis=-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert int(counts[5]) == xf.shape[0] and int(counts.sum()) == k * len(xf)
    assert float(balance) == 0.0 and float(z) == 0.0
    raw = _route(xf, router, k, False, scoring="sigmoid", bias=bias,
                 scale=2.5)[0]
    np.testing.assert_allclose(raw, 2.5 * at, rtol=1e-6)
    # No gradient reaches the bias, and the router's flows through the
    # weights alone.
    g_router, g_bias = jax.grad(
        lambda r, b: jnp.sum(_route(xf, r, k, True, scoring="sigmoid",
                                    bias=b)[0] ** 2), argnums=(0, 1))(
                                        router, bias)
    assert float(jnp.abs(g_router).sum()) > 0
    np.testing.assert_array_equal(g_bias, np.zeros(8))
    with pytest.raises(ValueError, match="unknown scoring"):
        _route(xf, router, k, scoring="tanh")


def test_update_expert_bias_against_a_hand_count():
    from horovod_tpu.parallel.moe import update_expert_bias

    # Layer 0: mean 3; layer 1: mean 2.
    counts = jnp.asarray([[5, 3, 1, 3], [2, 2, 2, 2]], jnp.int32)
    bias = jnp.asarray([[0.0, 0.5, -0.25, 0.0], [0.1, 0.0, 0.0, -0.1]])
    got = update_expert_bias(bias, counts, 1e-3)
    np.testing.assert_allclose(
        got, [[-0.001, 0.5, -0.249, 0.0], [0.1, 0.0, 0.0, -0.1]], atol=1e-9)
    np.testing.assert_array_equal(got, ref.step_bias(bias, counts, 1e-3))
    # As MoEStats stacks them, [layers, sets, experts]: the sets are summed,
    # so two ranks' counts give what their sum gives.
    by_rank = jnp.stack([counts - counts // 2, counts // 2], axis=1)
    np.testing.assert_array_equal(
        update_expert_bias(bias, by_rank, 1e-3), got)


def test_counters_carry_the_bias_and_publish_its_gauge():
    from horovod_tpu.core import metrics
    from horovod_tpu.parallel.moe import (
        count_routing,
        moe_counters,
        publish_routing,
    )

    aux = moe_counters(2, 4, share=True, expert_bias=True)
    assert aux["expert_bias"].shape == (2, 4)
    assert "expert_bias" not in moe_counters(2, 4, share=True)
    counts = jnp.asarray([[5, 3, 1, 3], [2, 2, 2, 2]], jnp.int32)
    for _ in range(3):
        aux = count_routing(aux, counts, held=(0, 1), bias_update_rate=1e-3)
    np.testing.assert_allclose(aux["expert_bias"][0],
                               [-0.003, 0.0, 0.003, 0.0], atol=1e-9)
    out = publish_routing(aux)
    assert out["expert_bias_abs_max"] == pytest.approx([0.003, 0.0])
    assert out["rows_held_per_step"] == [8.0, 4.0] and out["steps"] == 3
    assert "moe_expert_bias_abs_max" in metrics.CATALOG
    text = metrics.registry.render_prometheus() \
        if hasattr(metrics.registry, "render_prometheus") else ""
    assert "moe_expert_bias_abs_max" in text or not text


def dense_layer(x, router, gate, up, down, k, held, bias):
    """The layer as the reference computes it, for the held experts."""
    sizes = dict(num_experts_per_tok=k, norm_topk_prob=True,
                 routed_scaling_factor=1, experts_held=list(held))
    p = dict(router=router, experts_gate=gate, experts_up=up,
             experts_down=down)
    y, counts = ref._experts(p, bias, x.reshape(-1, x.shape[-1]), sizes)
    return y.reshape(x.shape), counts


@pytest.mark.parametrize("skew", [0.0, 6.0])
def test_four_shares_of_8_add_up_to_the_uncut_layer_of_32(skew):
    """32 experts, 8 on each of 4 chips, top 4 by sigmoid score plus bias,
    renormalised: every share's partial result is its own experts' part, the
    four add up to the uncut reference's layer, and every share counts the
    same 32-wide routing."""
    from horovod_tpu.parallel.moe import moe_ffn, row_buffer

    x, router, gate, up, down = layer_inputs(11, tokens=256, experts=32,
                                             skew=skew)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    k, n = 4, x.shape[0] * x.shape[1]
    # Five quarters of the mean share of 512 rows, and 11 quarters behind.
    assert row_buffer(n * k, 8, 32) == (12, 640)
    assert row_buffer(16384 * 4, 8, 32) == (12, 20480)      # the cell's
    route = dict(k=k, dtype=jnp.float32, norm_topk_prob=True,
                 scoring="sigmoid")
    with jax.default_matmul_precision("highest"):
        whole, whole_stats = jax.jit(lambda *a: moe_ffn(
            *a[:5], bias=a[5], **route))(x, router, gate, up, down, bias)
        want, want_counts = dense_layer(x, router, gate, up, down, k,
                                        range(32), bias)
    assert rel_err(whole, want) < 1e-5
    np.testing.assert_array_equal(whole_stats.tokens_per_expert[0],
                                  want_counts)
    total = np.zeros(x.shape, np.float64)
    for share in range(4):
        held = tuple(range(8 * share, 8 * share + 8))
        pick = np.asarray(held)
        with jax.default_matmul_precision("highest"):
            y, stats = jax.jit(lambda *a: moe_ffn(
                *a[:5], bias=a[5], held=held, **route))(
                    x, router, gate[pick], up[pick], down[pick], bias)
            part, _ = dense_layer(x, router, gate[pick], up[pick],
                                  down[pick], k, held, bias)
        np.testing.assert_allclose(y, part, atol=2e-5)
        total += np.asarray(y, np.float64)
        np.testing.assert_array_equal(stats.tokens_per_expert,
                                      whole_stats.tokens_per_expert)
        np.testing.assert_array_equal(stats.load_balancing_loss, [0.0])
    np.testing.assert_allclose(total, want, atol=1e-4)
    assert int(whole_stats.tokens_per_expert.sum()) == n * k


def test_the_bias_goes_through_the_steps_shard_map():
    """Under a mesh that binds ``data_axis`` every member routes its own rows
    by the one replicated bias; the counts come back a set a member."""
    from jax.sharding import Mesh

    from horovod_tpu.parallel.moe import moe_ffn

    x, router, gate, up, down = layer_inputs(5, rows=4, experts=8)
    bias = jnp.zeros((8,)).at[2].set(1.0)
    route = dict(k=2, dtype=jnp.float32, norm_topk_prob=True,
                 scoring="sigmoid", held=(1, 2))
    pick = np.asarray([1, 2])
    args = (x, router, gate[pick], up[pick], down[pick], bias)
    with jax.default_matmul_precision("highest"):
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("proc",))
        with jax.set_mesh(mesh):
            y, stats = jax.jit(lambda *a: moe_ffn(
                *a[:5], bias=a[5], data_axis="proc", **route))(*args)
        halves = [jax.jit(lambda *a: moe_ffn(*a[:5], bias=a[5], **route))(
            x[i:i + 2], *args[1:]) for i in (0, 2)]
    np.testing.assert_allclose(
        y, jnp.concatenate([h[0] for h in halves]), atol=1e-6)
    assert stats.tokens_per_expert.shape == (2, 8)
    np.testing.assert_array_equal(
        stats.tokens_per_expert,
        jnp.concatenate([h[1].tokens_per_expert for h in halves]))
    assert int(stats.tokens_per_expert[:, 2].sum()) == 4 * x.shape[1]


# -- attention at head width 64 -------------------------------------------------


def test_splash_kernel_in_interpret_mode_takes_heads_of_64():
    """Forward and the three gradients at two tiles, four query heads on one
    KV head of 64, against the grouped einsum."""
    from horovod_tpu.kernels import masked_attention as ma

    rule = ma.Causal()
    assert ma.takes(rule, 8192, 64) and ma.takes(rule, 8192, 128)
    assert not ma.takes(rule, 8192, 32) and not ma.takes(rule, 8000, 64)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, w = (jax.random.normal(k, (1, 2 * ma.BLOCK, 4, 64)) for k in ks[:2])
    k, v = (jax.random.normal(k, (1, 2 * ma.BLOCK, 1, 64)) for k in ks[2:])

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
