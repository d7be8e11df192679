"""Laguna-S-2.1: attention whose head count, rotary table and mask differ by
layer kind (global heads under YaRN on half a head, more heads under plain
RoPE inside a window), a sigmoid gate a head, a dense layer and expert layers
beside a shared expert, and the whole model against the plain reference
(``chip_bench/configs/laguna-s-2.1_reference.py``: float32, nothing of
``horovod_tpu``) on seeded weights at tiny widths, every part present.
``tests/test_laguna_cell.py`` holds the configuration and its cell,
``tests/test_laguna_compile.py`` the step's compile for a described chip.
"""

import dataclasses
import functools
import math
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .helpers import REPO_ROOT, load_reference
from .test_olmoe import rel_err

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

ref = load_reference("laguna-s-2.1")

FULL, SLIDING = "full_attention", "sliding_attention"
# A full dense layer, two sliding sparse layers and a full sparse layer (the
# published layers 0, 1, 2, 4 of a model of five), 6 and 9 query heads on 3
# KV heads of 16, a window of 6 in 20 positions, YaRN from 8 original
# positions by a factor that moves three of the four frequencies, 2 of 8
# experts held of top 2, a sliced vocabulary; and what the configuration's
# module asks of a file beside the sizes.
TINY = dict(
    layers_held=[0, 1, 2, 4], num_hidden_layers=4,
    layer_types=[FULL, SLIDING, SLIDING, SLIDING, FULL],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_attention_heads_per_layer=[6, 9, 9, 9, 6],
    hidden_size=32, num_attention_heads=6, num_key_value_heads=3,
    head_dim=16, sliding_window=6, gating="per-head",
    rope_parameters={
        FULL: dict(rope_theta=10000, rope_type="yarn", factor=8,
                   original_max_position_embeddings=8, beta_slow=1,
                   beta_fast=32, attention_factor=0.1 * math.log(8) + 1,
                   partial_rotary_factor=0.5),
        SLIDING: dict(rope_type="default", rope_theta=100,
                      partial_rotary_factor=1)},
    intermediate_size=48, moe_intermediate_size=24,
    shared_expert_intermediate_size=24, num_experts=2,
    num_experts_published=8, experts_held=[1, 6], num_experts_per_tok=2,
    norm_topk_prob=True, moe_routed_scaling_factor=2.5,
    moe_apply_router_weight_on_input=False, moe_router_logit_softcapping=0,
    decoder_sparse_step=1, mlp_only_layers=[0], attention_bias=False,
    tie_word_embeddings=False, vocab_size=64, rms_norm_eps=1e-6,
    sequence_length=20, max_position_embeddings=64, per_chip_batch=2,
    embedding_init_std=1.0, recompute_blocks=False, name="tiny-laguna")

WRONG = ("yarn_in_sliding", "plain_in_full", "no_attention_factor",
         "whole_head_turned", "window_1024", "no_gate", "gate_a_channel",
         "no_routed_scale", "sigmoid_scores")


def config_module():
    """``chip_bench/configs/laguna-s-2.1.py``, found as the harness finds
    it."""
    from chip_bench import spec

    return spec.Cell("laguna-s-2.1-wfbp-1chip",
                     root=REPO_ROOT).config_module()


def tiny_config(dtype=jnp.float32, **overrides):
    """The cell's ``Config`` at the tiny sizes, its model in ``dtype`` and
    outside any mesh: its loss is the one the benchmark steps."""
    from horovod_tpu.models.transformer import Transformer

    module = config_module()
    config = module.Config({**TINY, **overrides})
    config.model = Transformer(module.model_config(
        config.sizes, dtype=dtype, moe_data_axis=None))
    return config


def tokens_of(sizes, seed, batch=2):
    return {"tokens": jax.random.randint(
        jax.random.PRNGKey(seed), (batch, sizes["sequence_length"]), 0,
        sizes["vocab_size"])}


def seeded(model, seed=0, by=8.0):
    """Fresh weights with the layers' matrices ``by`` times as large as their
    initialisers draw them, so that at these widths the scores see the
    positions, the gates leave one half, the router prefers some experts and
    a wrong part shows."""
    return _seeded(dataclasses.replace(model.cfg, dtype=jnp.float32,
                                       remat=False), seed, by)


@functools.lru_cache(maxsize=None)
def _seeded(cfg, seed, by):
    from horovod_tpu.models.transformer import Transformer

    params = nn.meta.unbox(jax.jit(Transformer(cfg).init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"])
    grow = {"kernel", "router", "experts_gate", "experts_up", "experts_down"}

    def scaled(path, x):
        under = {getattr(k, "key", None) for k in path}
        return x * by if under & grow and "embed" not in under else x

    return jax.tree_util.tree_map_with_path(scaled, params)


def zero_aux(sizes):
    from horovod_tpu.parallel.moe import moe_counters

    return moe_counters(len(ref.expert_blocks(sizes)),
                        sizes["num_experts_published"], share=True)


def apply(model, params, tokens):
    return model.apply({"params": params}, tokens, mutable=["moe"])[0]


# -- the whole model ----------------------------------------------------------


def test_the_kinds_differ_in_heads_table_and_mask():
    """What the tiny model has to hold for the tests below to mean what they
    say: both head counts, both tables, a window shorter than the sequence,
    a dense and a sparse FFN."""
    cfg = tiny_config().model.cfg
    kinds = [cfg.layer_kind(i) for i in range(4)]
    assert [k.heads for k in kinds] == [6, 9, 9, 6]
    assert [k.window for k in kinds] == [0, 6, 6, 0]
    assert [k.ffn for k in kinds] == ["dense", None, None, None]
    assert kinds[0].rotary.yarn_factor == 8 and kinds[0].rotary.share == 0.5
    assert kinds[1].rotary.yarn_factor == 1 and kinds[1].rotary.share == 1
    assert kinds[1].rotary.rope_theta == 100
    assert cfg.attention_gate == "head" and cfg.num_kv_heads == 3
    shapes = jax.eval_shape(lambda: tiny_config().model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    shape = lambda layer, name: nn.meta.unbox(  # noqa: E731
        shapes[layer]["attn"][name]["kernel"]).shape
    assert shape("layer_0", "q") == (32, 6 * 16)
    assert shape("layer_1", "q") == (32, 9 * 16)
    assert shape("layer_1", "gate") == (32, 9)
    assert shape("layer_3", "out") == (6 * 16, 32)
    assert shape("layer_1", "kv") == shape("layer_0", "kv") == (32, 2 * 48)


@pytest.mark.parametrize("dtype,seed,tol", [
    (jnp.float32, 0, dict(loss=2e-6, logits=2e-5, grads=1e-4)),
    (jnp.bfloat16, 1, dict(loss=5e-3, logits=8e-2, grads=0.6))],
    ids=["float32", "bfloat16"])
def test_the_model_matches_the_reference(dtype, seed, tol):
    """Logits, loss, the gradient of every leaf (the gates' and both kinds'
    q, kv and out among them) and the counts of the program's model against
    the plain reference's on the same seeded weights: in float32 to
    rounding, in the cell's precision (a bf16 stream) to what bf16 leaves."""
    config = tiny_config(dtype)
    model, sizes = config.model, config.sizes
    params, batch = seeded(model, seed), tokens_of(sizes, seed + 1)
    aux = zero_aux(sizes)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda *a: apply(model, *a))(params,
                                                      batch["tokens"])
        (got, got_aux), grads = jax.jit(jax.value_and_grad(
            config.loss, has_aux=True))(params, aux, batch)
    want_logits = jax.jit(lambda p, b: ref.logits(p, b, sizes))(params, batch)
    assert rel_err(logits, want_logits) < tol["logits"]
    (want, want_aux), want_grads = jax.jit(jax.value_and_grad(
        ref.make_loss(sizes), has_aux=True))(params, aux, batch)
    assert float(got) == pytest.approx(float(want), rel=tol["loss"])
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    errs = jax.tree_util.tree_map(rel_err, grads, want_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < tol["grads"], (jax.tree_util.keystr(worst[0]), worst[1])
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(grads))
    assert sorted(got_aux) == sorted(want_aux)
    if dtype == jnp.float32:
        for key in want_aux:
            np.testing.assert_array_equal(got_aux[key], want_aux[key])
    # Three sparse layers, 2 x 20 tokens, 2 a token.
    assert int(want_aux["tokens_per_expert"].sum()) == 3 * 2 * 20 * 2


@pytest.fixture(scope="module")
def sound_logits():
    """Seeded weights, a batch, the reference's logits on them and the
    program's in float32: made once for the nine faults."""
    config = tiny_config()
    model, sizes = config.model, config.sizes
    params, batch = seeded(model), tokens_of(sizes, 1)
    want = jax.jit(lambda p, b: ref.logits(p, b, sizes))(params, batch)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: apply(model, *a))(params, batch["tokens"])
    return sizes, params, batch, want, got


@pytest.mark.parametrize("wrong", WRONG)
def test_a_planted_fault_moves_the_logits(wrong, sound_logits):
    """What the configuration's float32 limit has to refuse: each fault of
    the reference lies far from the sound reference where the program's
    model in float32 lies within rounding of it."""
    sizes, params, batch, want, program = sound_logits
    got = jax.jit(lambda p, b: ref.logits(p, b, sizes, wrong=(wrong,)))(
        params, batch)
    assert rel_err(got, want) > 1e-3, rel_err(got, want)
    assert rel_err(program, want) < 2e-5


def test_recomputed_blocks_give_the_same_loss_and_gradients():
    config = tiny_config()
    sizes = config.sizes
    params, batch = seeded(config.model), tokens_of(sizes, 2)
    aux = zero_aux(sizes)
    out = {}
    for remat in (False, True):
        config.model = type(config.model)(dataclasses.replace(
            config.model.cfg, remat=remat))
        out[remat] = jax.jit(jax.value_and_grad(
            config.loss, has_aux=True))(params, aux, batch)
    (loss, aux_a), grads = out[False]
    (again, aux_b), grads_again = out[True]
    assert float(loss) == pytest.approx(float(again), rel=1e-6)
    assert max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        rel_err, grads, grads_again))) < 1e-4
    for key in aux_a:
        np.testing.assert_array_equal(aux_a[key], aux_b[key])


def test_the_32_shares_add_up_to_the_uncut_layer():
    """One sparse layer's FFN on the same input at the published counts (256
    experts, 10 a token, 8 a chip): the 32 shares, the shared expert counted
    once, add up to the reference's layer that holds all 256."""
    from horovod_tpu.models.transformer import Block, LayerKind

    sizes = {**TINY, "num_experts": 256, "num_experts_published": 256,
             "experts_held": list(range(256)), "num_experts_per_tok": 10}
    cfg = config_module().model_config(sizes, dtype=jnp.float32,
                                       moe_data_axis=None)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 20, 32))
    kind = LayerKind(mixer="none", ffn="moe")
    params = nn.meta.unbox(jax.jit(Block(cfg, kind).init)(
        jax.random.PRNGKey(0), x))["params"]
    grow = {"router", "experts_gate", "experts_up", "experts_down"}
    params = {k: v * 8.0 if k in grow else jax.tree_util.tree_map(
        lambda t: t * 8.0 if t.ndim == 2 else t, v)
        for k, v in params.items()}

    @functools.partial(jax.jit, static_argnums=0)
    def ffn(held, p):
        block = Block(dataclasses.replace(cfg, experts_held=held), kind)
        with jax.default_matmul_precision("highest"):
            return block.apply({"params": p}, x, mutable=["moe"])[0] - x

    total = 0.0
    for share in range(32):
        # The same program 32 times: a share's ids enter by its weights.
        held = tuple(range(8 * share, 8 * share + 8))
        mine = {k: v[jnp.asarray(held)] if k.startswith("experts_") else v
                for k, v in params.items()}
        total = total + ffn(held, mine)
    with jax.default_matmul_precision("highest"):
        m = ref._rms_norm(x, params["ln2"]["scale"], 1e-6).reshape(40, 32)
        want, counts = ref._experts(params, m, sizes)
        shared = ref._swiglu(m, *(params[f"shared_{name}"]["kernel"]
                                  for name in ("gate", "up", "down")))
    # Every share added the shared expert: 31 too many (their rounding, 32
    # times one expert's, is read against one layer's output).
    assert rel_err(total - 31 * shared.reshape(2, 20, 32),
                   want.reshape(2, 20, 32)) < 1e-4
    assert int(counts.sum()) == 40 * 10


# -- the parts -----------------------------------------------------------------


def test_both_tables_at_the_published_numbers():
    """The full layers' YaRN table and the sliding layers' plain one from the
    published ``rope_parameters``: the range 9 to 18 on a rotary width of 64,
    32 and 64 frequencies, the factor 0.1 ln 128 + 1; the program's against
    the reference's formulas."""
    from chip_bench import spec
    from horovod_tpu.models import transformer as T

    sizes = spec.Cell("laguna-s-2.1-wfbp-1chip", root=REPO_ROOT).sizes
    cfg = T.laguna_s_2_1_config()
    full, sliding = cfg.layer_kind(0).rotary, cfg.layer_kind(1).rotary
    assert T.yarn_correction_range(full, 64) == (9, 18)
    assert ref.yarn_range(sizes["rope_parameters"][FULL], 64) == (9, 18)
    assert full.attention_factor == 1.4852030263919618 \
        == pytest.approx(0.1 * math.log(128) + 1, rel=1e-15)
    width, freq, factor = ref.rotary_table(sizes, FULL)
    assert (width, freq.shape, factor) == (64, (32,), 1.4852030263919618)
    np.testing.assert_allclose(T.yarn_inv_freq(full, 64), freq, rtol=1e-6)
    i = np.arange(32)
    plain = 500000.0 ** (-2.0 * i / 64)
    kept = 1 - np.clip((i - 9) / 9, 0, 1)
    np.testing.assert_allclose(
        freq, (1 - kept) * plain / 128 + kept * plain, rtol=1e-6)
    # Pairs 0..9 turn as they did, 18..31 a 128th as fast.
    np.testing.assert_allclose(freq[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(freq[18:], plain[18:] / 128, rtol=1e-6)
    width, freq, factor = ref.rotary_table(sizes, SLIDING)
    assert (width, freq.shape, factor) == (128, (64,), 1.0)
    np.testing.assert_allclose(freq, 10000.0 ** (-2.0 * np.arange(64) / 128),
                               rtol=1e-6)
    assert (sliding.rope_theta, sliding.share, sliding.yarn_factor) \
        == (10000.0, 1.0, 1.0)


def test_rope_by_a_kinds_table_is_the_references_turn():
    """``_rope`` under a :class:`Rotary`: half a head turned by YaRN's
    frequencies with cos and sin times the factor, the other half as it was;
    a plain table the configuration's own ``_rope``."""
    from horovod_tpu.models import transformer as T

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 20, 6, 16))
    cfg = tiny_config().model.cfg
    full, sliding = cfg.layer_kind(0).rotary, cfg.layer_kind(1).rotary
    got = T._rope(x, 1.0, None, 1.0, full)
    width, freq, factor = ref.rotary_table(TINY, FULL)
    assert width == 8 and factor > 1.2
    np.testing.assert_allclose(got[0], ref._turn(x[0], width, freq, factor),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    # Three of the four frequencies moved.
    assert int(np.sum(~np.isclose(freq, 1e4 ** (-np.arange(4) / 4)))) == 3
    np.testing.assert_array_equal(T._rope(x, 1.0, None, 0.5, sliding),
                                  T._rope(x, 100.0))


def test_one_head_count_and_one_table_build_what_they_built():
    """A kind that states the model's own head count and table lowers to the
    text of the kind that states neither, and builds its parameter tree:
    SmallThinker's and Qwen3-Next's tiny twins (their digests are pinned in
    ``tests/test_pinned_programs.py``)."""
    from horovod_tpu.models.transformer import (
        LayerKind,
        Rotary,
        Transformer,
    )

    from . import test_qwen3_next, test_smallthinker

    for cfg in (test_smallthinker.tiny_model(jnp.float32)[0].cfg,
                test_qwen3_next.tiny_model(jnp.float32)[0].cfg):
        table = Rotary(rope_theta=cfg.rope_theta,
                       share=cfg.partial_rotary_factor)
        stated = dataclasses.replace(cfg, layer_pattern=tuple(
            LayerKind(*kind)._replace(heads=cfg.num_heads, rotary=table)
            for kind in cfg.layer_pattern))
        tokens = jnp.zeros((2, 16), jnp.int32)
        texts, trees = [], []
        for c in (cfg, stated):
            model = Transformer(c)
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
            trees.append(jax.tree_util.tree_map(
                lambda s: (s.shape, s.dtype), nn.meta.unbox(shapes)))
            texts.append(jax.jit(lambda v, t, m=model: m.apply(
                v, t, mutable=["moe"])[0]).lower(shapes, tokens).as_text())
        assert trees[0] == trees[1]
        assert texts[0] == texts[1]


def test_parameter_counts_held_and_whole():
    """By ``jax.eval_shape`` at the published widths: the cell's cut and the
    whole model ("118B")."""
    from chip_bench import spec
    from horovod_tpu.models.transformer import (
        Transformer,
        laguna_s_2_1_config,
    )

    def count(cfg):
        shapes = jax.eval_shape(lambda: Transformer(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
        return {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
                for k, v in shapes.items()}

    whole = count(laguna_s_2_1_config())
    assert sum(whole.values()) == 117_561_953_280
    cell = spec.Cell("laguna-s-2.1-wfbp-1chip", root=REPO_ROOT)
    held = count(cell.config_module().model_config(cell.sizes))
    assert held["layer_0"] == 157_440_000
    assert held["layer_1"] == held["layer_2"] == held["layer_3"] \
        == 148_862_976
    assert held["layer_4"] == 129_914_880
    assert held["embed"] + held["lm_head"] == 77_070_336
    assert held["ln_f"] == 3072
    assert sum(held.values()) == 811_017_216


def test_the_preset_is_the_published_model():
    import json
    import os

    from horovod_tpu.models.transformer import laguna_s_2_1_config

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = [r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1"]
    c, cfg = row[0]["config"], laguna_s_2_1_config()
    assert (cfg.vocab_size, cfg.d_model, cfg.num_layers, cfg.num_kv_heads,
            cfg.head_dim, cfg.d_ff_dense, cfg.d_ff, cfg.d_ff_shared,
            cfg.num_experts, cfg.experts_per_token, cfg.max_len) == (
        c["vocab_size"], c["hidden_size"], c["num_hidden_layers"],
        c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
        c["moe_intermediate_size"], c["shared_expert_intermediate_size"],
        c["num_experts"], c["num_experts_per_tok"],
        c["max_position_embeddings"])
    assert cfg.norm_topk_prob is c["norm_topk_prob"] is True
    assert cfg.routed_scaling_factor == c["moe_routed_scaling_factor"]
    assert cfg.norm_eps == c["rms_norm_eps"] and not cfg.use_bias
    assert not cfg.tie_embeddings and cfg.router_scoring == "softmax"
    rope = c["rope_parameters"]
    for i in range(48):
        kind = cfg.layer_kind(i)
        assert kind.heads == c["num_attention_heads_per_layer"][i]
        sliding = c["layer_types"][i] == SLIDING
        assert kind.window == (c["sliding_window"] if sliding else 0)
        assert (kind.ffn == "dense") == (c["mlp_layer_types"][i] == "dense")
        r = rope[c["layer_types"][i]]
        assert (kind.rotary.rope_theta, kind.rotary.share) \
            == (r["rope_theta"], r["partial_rotary_factor"])
        assert kind.rotary.yarn_factor == r.get("factor", 1)
        assert kind.rotary.attention_factor == r.get("attention_factor")
    full = cfg.layer_kind(0).rotary
    assert (full.yarn_original_max_len, full.yarn_beta_fast,
            full.yarn_beta_slow) == (8192, 32, 1)


def test_head_pairs_by_kind_and_the_gauge():
    """``attn_head_pairs_per_step``: each block's allowed pairs times its own
    query heads; a model with one head count reads pairs x ``num_heads``."""
    from horovod_tpu.core import metrics
    from horovod_tpu.models import transformer as T

    cfg = tiny_config().model.cfg
    s, w = 20, 6
    causal = s * (s + 1) // 2
    window = causal - (s - w) * (s - w + 1) // 2
    assert T.attention_pairs(cfg, s) == {"window": 2 * window,
                                         "global": 2 * causal}
    assert T.attention_pairs(cfg, s, by_head=True) \
        == {"window": 2 * 9 * window, "global": 2 * 6 * causal}
    assert T.publish_attention(cfg, s, sequences=3) \
        == {"window": 6 * window, "global": 6 * causal}
    read = lambda name, kind: metrics.registry.get_gauge(  # noqa: E731
        name, kind=kind)
    assert read("attn_head_pairs_per_step", "window") == 3 * 2 * 9 * window
    assert read("attn_head_pairs_per_step", "global") == 3 * 2 * 6 * causal
    assert read("attn_allowed_pairs_per_step", "window") == 3 * 2 * window
    assert "attn_head_pairs_per_step" in metrics.CATALOG
    plain = T.smallthinker_21b_a3b_config(num_layers=4)
    one = T.attention_pairs(plain, 64)
    assert T.attention_pairs(plain, 64, by_head=True) \
        == {k: 28 * v for k, v in one.items()}
