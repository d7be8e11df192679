"""Nemotron-3-Super-120B-A12B: layers that are a mixer alone or an FFN alone
under one norm, the Mamba-2 mixer over its chunked scan, experts without a
gate on a latent width beside a shared expert, the shares of heads and of
experts that add up to the uncut layers, and the whole model against the
plain reference (``chip_bench/configs/nemotron-3-super-120b-a12b_reference.py``: float32, the recurrence a
token at a time, nothing of ``horovod_tpu``) on seeded weights at tiny widths.
``tests/test_nemotron_cell.py`` holds the configuration and its cell.
"""

import dataclasses
import os
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .helpers import REPO_ROOT, load_reference
from .test_olmoe import rel_err

ref = load_reference("nemotron-3-super-120b-a12b")

# One of every letter twice over, 4 of 8 mixer heads in 2 of 4 groups, 2 of 4
# query heads on 1 of 2 KV heads, 4 of 16 experts held, a sliced vocabulary.
TINY = dict(
    hybrid_override_pattern="ME*EM", layers_held=[0, 1, 2, 3, 4],
    num_hidden_layers=5, num_hidden_layers_published=5, hidden_size=32,
    head_dim=8, num_attention_heads=2, num_key_value_heads=1,
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
    mamba_num_heads_published=8, n_groups_published=4,
    mamba_groups_held=[0, 1], ssm_state_size=16, conv_kernel=4, chunk_size=8,
    n_routed_experts=4, n_routed_experts_published=16,
    experts_held=[1, 5, 6, 12], num_experts_per_tok=3, norm_topk_prob=True,
    routed_scaling_factor=5, moe_latent_size=16, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, n_shared_experts=1,
    vocab_size=64, norm_eps=1e-5, expert_bias_update_rate=1e-3,
    sequence_length=20, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4)


def tiny_model(dtype=jnp.float32, **overrides):
    from horovod_tpu.models.transformer import (
        Transformer,
        hybrid_pattern,
        nemotron_3_super_config,
    )

    z = {**TINY, **overrides}
    return Transformer(nemotron_3_super_config(
        vocab_size=z["vocab_size"], num_layers=z["num_hidden_layers"],
        num_heads=z["num_attention_heads"],
        num_kv_heads=z["num_key_value_heads"], head_width=z["head_dim"],
        d_model=z["hidden_size"], d_ff=z["moe_intermediate_size"], max_len=64,
        num_experts=z["n_routed_experts_published"],
        experts_per_token=z["num_experts_per_tok"],
        experts_held=tuple(z["experts_held"]),
        moe_latent=z["moe_latent_size"],
        d_ff_shared=z["moe_shared_expert_intermediate_size"],
        mamba_heads=z["mamba_num_heads_published"],
        mamba_head_dim=z["mamba_head_dim"],
        mamba_groups=z["n_groups_published"],
        mamba_groups_held=tuple(z["mamba_groups_held"]),
        mamba_state=z["ssm_state_size"], mamba_chunk=z["chunk_size"],
        layer_pattern=hybrid_pattern(ref.layer_plan(z)), dtype=dtype)), z


def tokens_of(sizes, seed, batch=2):
    return {"tokens": jax.random.randint(
        jax.random.PRNGKey(seed), (batch, sizes["sequence_length"]), 0,
        sizes["vocab_size"])}


def seeded(model, seed=0, by=8.0):
    """Fresh weights with the layers' matrices ``by`` times as large as their
    initialisers draw them, so that at these widths every module moves the
    residual stream and a wrong one shows."""
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"])
    grow = {"kernel", "router", "experts_up", "experts_down"}

    def scaled(path, x):
        last = getattr(path[-1], "key", None)
        under = {getattr(k, "key", None) for k in path}
        return x * by if last in grow and "embed" not in under else x

    return jax.tree_util.tree_map_with_path(scaled, params)


def some_bias(sizes, seed=3, width=0.05):
    return width * jax.random.normal(
        jax.random.PRNGKey(seed), ref.zero_bias(sizes).shape, jnp.float32)


def zero_aux(sizes, bias=None):
    from horovod_tpu.parallel.moe import moe_counters

    aux = moe_counters(ref.layer_plan(sizes).count("E"),
                       sizes["n_routed_experts_published"], share=True,
                       expert_bias=True)
    return aux if bias is None else {**aux, "expert_bias": bias}


def program_loss(model, sizes):
    import optax

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
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1))
        total = jnp.sum(nll * (jnp.arange(s) < s - 1)) / (b * (s - 1))
        counts = jnp.sum(moe_stats(state["moe"]).tokens_per_expert, axis=1)
        return total, count_routing(
            aux, counts, held=tuple(sizes["experts_held"]),
            bias_update_rate=sizes["expert_bias_update_rate"])

    return loss


# -- the whole model ----------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, dict(loss=1e-6, grads=2e-5)),
    (jnp.bfloat16, dict(loss=2e-3, grads=0.25))], ids=["float32", "bfloat16"])
def test_transformer_under_the_pattern_matches_the_reference(dtype, tol):
    """Loss, gradients, counts and the stepped bias of the program's model
    against the plain reference's on the same seeded weights, under a
    selection bias that is not zero: in float32 to rounding, in the cell's
    precision (bf16 activations) to what bf16 leaves."""
    model, sizes = tiny_model(dtype)
    params, batch = seeded(model), tokens_of(sizes, 1)
    aux = zero_aux(sizes, some_bias(sizes))
    with jax.default_matmul_precision("highest"):
        (got, got_aux), grads = jax.jit(jax.value_and_grad(
            program_loss(model, sizes), has_aux=True))(params, aux, batch)
    (want, want_aux), want_grads = jax.jit(jax.value_and_grad(
        ref.make_loss(sizes), has_aux=True))(params, aux, batch)
    assert float(got) == pytest.approx(float(want), rel=tol["loss"])
    assert float(want) == pytest.approx(np.log(sizes["vocab_size"]), rel=0.3)
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    errs = jax.tree_util.tree_map(rel_err, grads, want_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < tol["grads"], (jax.tree_util.keystr(worst[0]), worst[1])
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(grads))
    if dtype == jnp.float32:
        for key in want_aux:
            np.testing.assert_array_equal(got_aux[key], want_aux[key])
    assert int(want_aux["tokens_per_expert"].sum()) == 2 * 2 * 20 * 3


WRONG = ("decay_without_dt", "wrong_group", "norm_over_all", "gated_experts",
         "weights_dropped", "no_shared_expert")


@pytest.mark.parametrize("wrong", WRONG)
def test_a_wrong_layer_of_the_reference_moves_the_logits(wrong):
    """What the configuration's float32 limit has to refuse: each wrong layer
    lies far from the sound reference where the program's model in float32
    lies within rounding of it."""
    model, sizes = tiny_model()
    params, batch = seeded(model), tokens_of(sizes, 1)
    bias = some_bias(sizes)
    want = ref.logits(params, batch, sizes, bias=bias)
    got = ref.logits(params, batch, sizes, wrong=(wrong,), bias=bias)
    assert rel_err(got, want) > 1e-3
    from horovod_tpu.models.transformer import expert_bias_collection

    with jax.default_matmul_precision("highest"):
        own = model.apply(
            {"params": params, "moe": expert_bias_collection(model.cfg, bias)},
            batch["tokens"], mutable=["moe"])[0]
    assert rel_err(own, want) < 1e-5


def test_a_layer_builds_only_what_its_kind_names():
    model, sizes = tiny_model()
    shapes = jax.eval_shape(lambda: nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))["params"]
    assert sorted(shapes["layer_0"]) == ["ln1", "mamba"]
    assert sorted(shapes["layer_2"]) == ["attn", "ln1"]
    assert sorted(shapes["layer_1"]) == [
        "experts_down", "experts_up", "latent_in", "latent_out", "ln2",
        "router", "shared_down", "shared_up"]
    assert sorted(shapes["layer_0"]["mamba"]) == [
        "A_log", "D", "conv", "conv_bias", "dt_bias", "in_proj", "norm",
        "out_proj"]
    # 4 heads of 8 in 2 groups of state 16: z 32, x 32, B 32, C 32, dt 4.
    assert shapes["layer_0"]["mamba"]["in_proj"]["kernel"].shape == (32, 132)
    assert shapes["layer_0"]["mamba"]["conv"].shape == (96, 4)
    assert shapes["layer_1"]["experts_up"].shape == (4, 16, 24)
    assert shapes["layer_1"]["router"].shape == (32, 16)
    assert shapes["layer_2"]["attn"]["kv"]["kernel"].shape == (32, 16)
    assert model.cfg.expert_layers() == (1, 3)
    from horovod_tpu.models.transformer import LayerKind, Transformer

    neither = dataclasses.replace(
        model.cfg, layer_pattern=(LayerKind(0, False, "none", "none"),) * 5)
    with pytest.raises(ValueError, match="neither mixer nor FFN"):
        Transformer(neither).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))


def test_the_preset_is_the_published_model():
    from horovod_tpu.models.transformer import (
        Transformer,
        hybrid_pattern,
        nemotron_3_super_config,
    )

    def count(cfg):
        shapes = jax.eval_shape(
            lambda: Transformer(cfg).init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32)))
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))

    cfg = nemotron_3_super_config()
    kinds = [(k.mixer, k.ffn) for k in cfg.layer_pattern]
    assert len(kinds) == cfg.num_layers == 88
    assert (kinds.count(("mamba2", "none")), kinds.count(("none", "moe")),
            kinds.count(("attention", "none"))) == (40, 40, 8)
    assert not any(k.rope or k.window for k in cfg.layer_pattern)
    assert count(cfg) == 120_668_687_360          # the published 120B
    # The cell's cut: one period, one group of 16 heads, 4 query heads on 1
    # KV head, 8 experts, an eighth of the vocabulary.
    cut = nemotron_3_super_config(
        vocab_size=16384, num_layers=11, num_heads=4, num_kv_heads=1,
        experts_held=tuple(range(8)), mamba_groups_held=(0,),
        layer_pattern=hybrid_pattern("MEMEMEM*EME"))
    assert count(cut) == 700_862_960


# -- the shares add up --------------------------------------------------------


def test_the_shares_of_a_mamba2_mixer_add_up_to_the_uncut_mixer():
    """Four groups of two heads: the parts that the four one-group shares
    return (each holding its columns of ``W_in``, its channels of the
    convolution and the norm, its rows of ``W_out``) add up to the whole
    mixer's output, which is the uncut reference's."""
    from horovod_tpu.models import mamba2

    whole, sizes = tiny_model(mamba_groups_held=[0, 1, 2, 3],
                              mamba_num_heads=8, n_groups=4)
    cfg = dataclasses.replace(whole.cfg, mamba_groups_held=None)
    params = seeded(whole)["layer_0"]["mamba"]
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 32))
    with jax.default_matmul_precision("highest"):
        want = mamba2.Mamba2(cfg).apply({"params": params}, u)
        plain = jax.vmap(lambda row: ref._mamba(params, row, sizes))(u)
        assert rel_err(want, plain) < 1e-5
        parts = []
        for groups in ((0,), (1,), (2,), (3,)):
            share = dataclasses.replace(cfg, mamba_groups_held=groups)
            held = mamba2.share_of(params, cfg, groups)
            assert held["in_proj"]["kernel"].shape == (32, 16 + 48 + 2)
            parts.append(mamba2.Mamba2(share).apply({"params": held}, u))
        two = mamba2.Mamba2(dataclasses.replace(
            cfg, mamba_groups_held=(1, 3))).apply(
                {"params": mamba2.share_of(params, cfg, (1, 3))}, u)
    assert rel_err(sum(parts), want) < 1e-5
    assert rel_err(parts[1] + parts[3], two) < 1e-5
    assert rel_err(parts[0], want) > 0.1


def test_the_shares_of_an_attention_layer_add_up_to_the_uncut_layer():
    """4 query heads on 2 KV heads: a share holds a KV head with the query
    heads it serves and those rows of the output projection."""
    from horovod_tpu.models.transformer import Attention, LayerKind

    whole, sizes = tiny_model(num_attention_heads=4, num_key_value_heads=2)
    cfg, kind = whole.cfg, LayerKind(0, False, "attention", "none")
    params = seeded(whole)["layer_2"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 32))
    dh = 8
    with jax.default_matmul_precision("highest"):
        want = Attention(cfg, kind).apply({"params": params}, x)
        plain = jax.vmap(lambda row: ref._attention(params, row, sizes))(x)
        assert rel_err(want, plain) < 1e-5
        share = dataclasses.replace(cfg, num_heads=2, num_kv_heads=1)
        parts = []
        for j in range(2):
            q = slice(2 * j * dh, 2 * (j + 1) * dh)
            kv = params["kv"]["kernel"]
            held = {"q": {"kernel": params["q"]["kernel"][:, q]},
                    "kv": {"kernel": jnp.concatenate(
                        [kv[:, j * dh:(j + 1) * dh],
                         kv[:, (2 + j) * dh:(3 + j) * dh]], axis=1)},
                    "out": {"kernel": params["out"]["kernel"][q]}}
            parts.append(Attention(share, kind).apply({"params": held}, x))
    assert rel_err(sum(parts), want) < 1e-5
    assert rel_err(parts[0], want) > 0.1


def test_the_shares_of_an_expert_layer_add_up_with_the_shared_expert_once():
    """16 experts over 4 shares of 4: each share routes over all 16, applies
    ``W_fc2`` to its own experts' partial sum and adds the shared expert,
    which every chip computes alike: the shares' sums plus the shared expert
    once are the uncut layer."""
    from horovod_tpu.models.transformer import Block, LayerKind

    ids = list(range(16))
    whole, sizes = tiny_model(experts_held=ids, n_routed_experts=16)
    kind = LayerKind(0, False, "none", "moe")
    params = seeded(whole)["layer_1"]
    bias = some_bias(sizes)[0]
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 32))

    def layer(cfg, p):
        y, _ = Block(cfg, kind).apply({"params": p, "moe": {"bias": bias}}, h,
                                      mutable=["moe"])
        return y - h

    with jax.default_matmul_precision("highest"):
        want = layer(whole.cfg, params)
        m = ref._rms_norm(h, params["ln2"]["scale"], 1e-5).reshape(40, 32)
        plain, _ = ref._experts(params, bias, m, sizes)
        assert rel_err(want, plain.reshape(2, 20, 32)) < 1e-5
        shared = (ref._relu2(m @ params["shared_up"]["kernel"])
                  @ params["shared_down"]["kernel"]).reshape(2, 20, 32)
        routed = []
        for held in (ids[0::4], ids[1::4], ids[2::4], ids[3::4]):
            cfg = dataclasses.replace(whole.cfg, experts_held=tuple(held))
            p = {**params,
                 "experts_up": params["experts_up"][np.asarray(held)],
                 "experts_down": params["experts_down"][np.asarray(held)]}
            routed.append(layer(cfg, p) - shared)
    assert rel_err(sum(routed) + shared, want) < 1e-5
    assert rel_err(sum(routed), want) > 0.1
    assert rel_err(routed[0] + shared, want) > 0.1


# -- the router over 512 outputs, top 22 (PR 45) --------------------------------


@pytest.mark.parametrize("factors", [True, False])
def test_three_pass_logits_over_512_outputs_choose_float64s_top_22(factors):
    """``tests/test_router_product.py``'s check of the split product at this model's
    router: sigmoid scores over 512 experts, the top 22."""
    from .test_router_product import check_three_pass_logits

    check_three_pass_logits("sigmoid", factors)


def test_three_pass_gradients_over_512_outputs_are_the_old_lines():
    from .test_router_product import check_gradients_are_the_old_lines

    check_gradients_are_the_old_lines("sigmoid")


@pytest.mark.parametrize("dtype,passes", [("bfloat16", 3), ("float32", 6)])
def test_a_layers_router_product_follows_the_streams_dtype(dtype, passes):
    """A bf16 stream reaches the router as it is, with the norm's two
    factors beside it, and its logits are one bf16 product against the three
    pieces of the weights; a float32 stream is multiplied as the parent
    multiplied it.  The gauge says which, a layer."""
    from horovod_tpu.core import metrics

    from .test_router_product import _dot_generals

    model, sizes = tiny_model(getattr(jnp, dtype))
    tokens = tokens_of(sizes, 0)["tokens"]
    metrics.registry.reset()
    jaxpr = jax.make_jaxpr(lambda: model.init(jax.random.PRNGKey(0), tokens))()
    for layer in range(ref.layer_plan(sizes).count("E")):
        assert metrics.registry.get_gauge(
            "moe_router_product_passes", layer=str(layer)) == passes
    n = tokens.size
    experts = sizes["n_routed_experts_published"]
    router = [eqn for eqn in _dot_generals(jaxpr.jaxpr)
              if eqn.outvars[0].aval.shape in ((n, experts), (n, 3 * experts))]
    assert len(router) == ref.layer_plan(sizes).count("E")
    for eqn in router:
        if passes == 3:
            assert eqn.outvars[0].aval.shape == (n, 3 * experts)
            assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2
        else:
            assert eqn.outvars[0].aval.shape == (n, experts)
            assert [v.aval.dtype for v in eqn.invars] == [jnp.float32] * 2
            assert eqn.params["precision"] is not None


# -- experts without a gate ----------------------------------------------------


@pytest.mark.parametrize("held", [None, (1, 2, 5)], ids=["whole", "held"])
def test_moe_ffn_without_a_gate_is_a_dense_loop_over_the_experts(held):
    """``moe_ffn(gate=None, activation="relu2")`` on rows of a latent width,
    routed by another tensor, forward and the gradients of rows, router and
    both stacks, against one expert at a time under a mask."""
    from horovod_tpu.parallel.moe import moe_ffn

    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    rows = jax.random.normal(ks[0], (2, 24, 16))
    seen = jax.random.normal(ks[1], (2, 24, 32))
    router = 0.5 * jax.random.normal(ks[2], (32, 8))
    up = 0.3 * jax.random.normal(ks[3], (8, 16, 24))
    down = 0.3 * jax.random.normal(ks[4], (8, 24, 16))
    bias = 0.1 * jax.random.normal(ks[5], (8,))
    ids = tuple(range(8)) if held is None else held

    def layer(rows, seen, router, up, down):
        y, stats = moe_ffn(
            rows, router, None, up[np.asarray(ids)], down[np.asarray(ids)],
            k=3, dtype=jnp.float32, held=held, norm_topk_prob=True,
            router_input=seen, activation="relu2", scoring="sigmoid",
            bias=bias, scale=5.0)
        return y, stats

    def dense(rows, seen, router, up, down):
        scores = jax.nn.sigmoid(seen.reshape(48, 32) @ router)
        _, chosen = jax.lax.top_k(scores + bias, 3)
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        w = 5.0 * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
        x = rows.reshape(48, 16)
        y = sum(jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)[:, None]
                * (jnp.square(jax.nn.relu(x @ up[e])) @ down[e]) for e in ids)
        return y.reshape(rows.shape)

    args = (rows, seen, router, up, down)
    with jax.default_matmul_precision("highest"):
        (got, stats), want = layer(*args), dense(*args)
        assert rel_err(got, want) < 1e-5
        assert int(stats.tokens_per_expert.sum()) == 48 * 3
        grads = jax.grad(lambda *a: jnp.sum(jnp.sin(layer(*a)[0])),
                         argnums=range(5))(*args)
        want_grads = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))),
                              argnums=range(5))(*args)
    for name, g, w in zip(("rows", "seen", "router", "up", "down"), grads,
                          want_grads):
        assert rel_err(g, w) < 1e-4, name
    with pytest.raises(ValueError, match="unknown activation"):
        moe_ffn(rows, router, None, up, down, k=3, activation="relu3")


@pytest.mark.parametrize("sizes,quantum,first,chunks", [
    ((8192 * 22, 8, 512), 1024, 5120, 172),     # the cell's: a quarter of 704
    ((4096 * 22, 8, 512), 512, 2560, 172),      # 352 -> 512
    ((16384 * 22, 8, 512), 1408, 7040, 252),    # 1408 = 11 x 128: as it is
    ((8192 * 22, 16, 512), 1408, 7040, 124),
    ((2048, 2, 16), 0, 2048, 1),                # a quarter of 64: one chunk
    ((127 * 128, 5, 32), 0, 127 * 128, 1)],     # 635: 127 chunks, a prime
    ids=["cell", "half", "double", "sixteen_held", "under_128", "no_divisor"])
def test_a_quarter_that_is_no_multiple_of_128_is_rounded_up(sizes, quantum,
                                                            first, chunks):
    """22 of 512 experts a token: the mean share's quarter is 704 rows, which
    the rows kernel does not take; the quantum is the next multiple of 128
    the slots are whole chunks of, and the rows are still covered to the
    last slot."""
    from horovod_tpu.parallel import moe

    assert moe.row_quantum(*sizes) == quantum
    assert moe.row_buffer(*sizes) == (chunks, first)
    if quantum:
        assert quantum % 128 == 0 and sizes[0] % quantum == 0
        assert first + (chunks - 1) * quantum == sizes[0]
        assert 4 * quantum >= sizes[0] * sizes[1] // sizes[2]


# -- what stays as it was ------------------------------------------------------

@pytest.mark.parametrize("suite,late", [
    ("test_nemotron", ("horovod_tpu.kernels.ssd_scan",
                       "horovod_tpu.models.mamba2")),
    ("test_joyai", ("horovod_tpu.models.deepseek",)),
    ("test_qwen3_next", ("horovod_tpu.kernels.gated_delta",
                         "horovod_tpu.models.gated_delta"))])
def test_the_scan_and_the_mixer_load_where_a_configuration_asks(suite, late):
    """Neither ``import horovod_tpu`` nor ``hvd.init()`` nor the models'
    package loads the kernel or the mixer's module; a layer of kind
    ``mamba2`` does.  Nor DeepSeek-V3's parts (latent attention, the
    prediction module): a model with ``kv_lora_rank`` does.  Nor the Gated
    DeltaNet and its rule: a layer of kind ``gated_delta`` does."""
    code = (
        "import sys, horovod_tpu as hvd\n"
        "hvd.init()\n"
        "import horovod_tpu.models.transformer, horovod_tpu.parallel.moe\n"
        f"late = {late!r}\n"
        "assert not [m for m in late if m in sys.modules], sys.modules.keys()\n"
        "assert 'jax.experimental.pallas' not in sys.modules\n"
        f"from tests.{suite} import tiny_model\n"
        "import jax, jax.numpy as jnp\n"
        "model, _ = tiny_model()\n"
        "jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), "
        "jnp.zeros((1, 8), jnp.int32)))\n"
        "assert all(m in sys.modules for m in late)\n"
        "print('ok')\n")
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO_ROOT, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": REPO_ROOT})
    assert done.returncode == 0 and "ok" in done.stdout, done.stderr[-2000:]
