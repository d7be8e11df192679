"""Ling-3.0-flash-VL's language model: Kimi Delta Attention (a delta rule
whose decay is a vector a key channel: ``kernels/kda.py``, ``models/kda.py``),
latent attention without a query latent under a gate a head, experts chosen
inside the best groups (``moe._within_groups``), and the whole model against
the plain reference (``chip_bench/configs/ling-3.0-flash-vl_reference.py``:
float32, nothing of ``horovod_tpu``, the rule a token at a time) on seeded
weights at tiny widths, every part present.  One tiny model and its compiled
gradients serve the module.  ``tests/test_ling_cell.py`` holds the
configuration and its cell, ``tests/test_ling_compile.py`` the kernels' and
the step's compile for a described chip.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .helpers import REPO_ROOT, load_reference
from .test_olmoe import rel_err

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

ref = load_reference("ling-3.0-flash-vl")

CELL = "ling-3.0-flash-vl-wfbp-1chip"
WRONG = ("scalar_decay", "gate_unbounded", "no_group_mask", "no_head_gate",
         "no_l2norm")


def full_sizes():
    with open(os.path.join(REPO_ROOT, "chip_bench/configs",
                           "ling-3.0-flash-vl.json")) as f:
        return json.load(f)


def tiny_sizes(**overrides):
    """The configuration's file at tiny widths and at three of its layers,
    one of each kind (the published layers 1, 2 and 5: a dense KDA layer, a
    KDA layer with experts, the latent-attention layer with experts: every
    layer more is a third more to compile and nothing more to hold), 2 heads
    of 16, 8 of 64 experts held of top 4 inside 4 of 8 groups, a sliced
    vocabulary, 128 positions (two chunks of the rule)."""
    return {**full_sizes(), **dict(
        layers_held=[1, 2, 5], num_hidden_layers=3,
        hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
        head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=32, num_experts=8,
        num_experts_published=64, experts_held=[0, 9, 18, 27, 36, 45, 54, 63],
        num_experts_per_tok=4, n_group=8, topk_group=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, rotary_dim=8, v_head_dim=16,
        vocab_size=128, sequence_length=128, per_chip_batch=2,
        max_position_embeddings=256, recompute_blocks=False,
        name="tiny-ling"), **overrides}


def config_module():
    """``chip_bench/configs/ling-3.0-flash-vl.py``, found as the harness finds
    it."""
    from chip_bench import spec

    return spec.Cell(CELL, root=REPO_ROOT).config_module()


def tiny_config(dtype=jnp.float32, **overrides):
    """The cell's ``Config`` at the tiny sizes, its model in ``dtype`` and
    outside any mesh: its loss is the one the benchmark steps."""
    from horovod_tpu.models.transformer import Transformer

    module = config_module()
    config = module.Config(tiny_sizes(**overrides))
    config.model = Transformer(module.model_config(
        config.sizes, dtype=dtype, moe_data_axis=None))
    return config


def seeded(config, seed=0, by=6.0):
    """(params, aux): fresh weights with the layers' matrices ``by`` times as
    large as their initialisers draw them, so that at these widths the gates
    leave their middle, the scores see the positions, the router prefers some
    experts and a wrong part shows; and a selection bias that is not zero."""
    params, aux = jax.jit(config.init)(jax.random.PRNGKey(seed))
    grow = {"kernel", "router", "experts_gate", "experts_up", "experts_down"}

    def scaled(path, x):
        under = {getattr(k, "key", None) for k in path}
        return x * by if under & grow and "embed" not in under else x

    aux["expert_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), aux["expert_bias"].shape)
    return jax.tree_util.tree_map_with_path(scaled, params), aux


@pytest.fixture(scope="module")
def tiny():
    """The one tiny model of this module: its configuration, seeded weights,
    a batch, and the program's and the reference's loss and gradients in
    float32, each compiled once."""
    config = tiny_config()
    params, aux = seeded(config)
    batch = config.make_batch(jax.random.PRNGKey(1))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(config.loss, has_aux=True))(
            params, aux, batch)
        want = jax.jit(jax.value_and_grad(
            ref.make_loss(config.sizes), has_aux=True))(params, aux, batch)
    return dict(config=config, params=params, aux=aux, batch=batch, got=got,
                want=want)


# -- the whole model ----------------------------------------------------------


def test_the_tiny_model_holds_every_part(tiny):
    """What the tiny model has to hold for the tests below to mean what they
    say: both mixers, both FFNs, the group limit, the gate, no query latent,
    decays that differ by channel."""
    cfg = tiny["config"].model.cfg
    kinds = [cfg.layer_kind(i) for i in range(3)]
    assert [k.mixer for k in kinds] == ["kda", "kda", "attention"]
    assert [k.ffn for k in kinds] == ["dense", None, None]
    assert (cfg.moe_groups, cfg.moe_groups_kept) == (8, 4)
    assert cfg.attention_gate == "head" and cfg.q_lora_rank == 0
    layer = tiny["params"]["layer_2"]["attn"]
    assert set(layer) == {"q", "kv_a", "kv_a_norm", "kv_b", "gate", "out"}
    assert set(tiny["params"]["layer_0"]["kda"]) == {
        "in_proj", "beta_proj", "conv", "A_log", "dt_bias", "norm",
        "out_proj"}


def test_float32_loss_counts_and_gradients_are_the_references(tiny):
    """In float32 at the highest precision the program computes what the
    reference computes, by other means (chunks of 64 with an inverse where
    the reference steps a token at a time; a sort and grouped products where
    it masks; kernels' layouts): the loss to 1e-6, the routing to the row,
    the stepped bias to the bit, every gradient leaf to 1e-4 of its norm
    (float32's rounding through three layers reads 1e-5 here; a wrong term
    reads 1e-2 or more)."""
    (loss, aux), grads = tiny["got"]
    (want_loss, want_aux), want_grads = tiny["want"]
    assert abs(float(loss) - float(want_loss)) < 1e-6 * float(want_loss)
    for key in ("tokens_per_expert", "rows_held", "rows_elsewhere", "steps"):
        np.testing.assert_array_equal(aux[key], want_aux[key])
    np.testing.assert_array_equal(aux["expert_bias"],
                                  want_aux["expert_bias"])
    assert int(jnp.sum(aux["rows_held"])) > 0
    worst = max(
        (float(rel_err(g, w)), jax.tree_util.keystr(path))
        for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(want_grads)))
    assert worst[0] < 1e-4, worst


def test_bfloat16_logits_lie_near_the_references(tiny):
    """The model as the step runs it (a bf16 stream, bf16 products, the
    rule's fp32 states) against the float32 reference: the median position
    within 3e-2 of its own norm, where bf16's 2**-8 a product through three
    layers reads 2e-2 at these widths; the whole within 1e-1 of the logits'
    norm, because a position that takes another expert under a rounded
    router input (two expert layers of 64 experts on weights six times
    their draw) moves its own row by its whole size: 6e-2 here, which is why
    the cell's limits hold the median beside the norm."""
    config = tiny_config(jnp.bfloat16)
    whole, median = config.logits_errors(
        tiny["params"], tiny["batch"], bias=tiny["aux"]["expert_bias"])
    assert whole < 1e-1 and median < 3e-2, (whole, median)


@pytest.mark.parametrize("wrong", WRONG)
def test_a_wrong_layer_of_the_reference_moves_the_logits(wrong, tiny):
    """Each planted fault moves the float32 reference's logits by far more
    than the program's float32 model lies from them: the limit that refuses
    a wrong layer has something to refuse (1e-5 by either measure; the
    faintest here is the group mask left out of two expert layers, which
    moves the rows that then choose otherwise and the median position by
    2e-3)."""
    whole, median = tiny["config"].logits_errors(
        tiny["params"], tiny["batch"], jnp.float32, (wrong,),
        bias=tiny["aux"]["expert_bias"])
    assert whole > 2e-2 and median > 1e-3, (wrong, whole, median)


def test_the_programs_model_in_float32_is_the_sound_reference(tiny):
    whole, median = tiny["config"].logits_errors(
        tiny["params"], tiny["batch"], "program_float32",
        bias=tiny["aux"]["expert_bias"])
    assert whole < 1e-5 and median < 1e-5, (whole, median)


def test_the_preset_is_the_published_model():
    """``ling_3_0_flash_config()`` by the catalog's row, and the whole model's
    parameters by the program's own count: the file's
    ``parameters_published``, the release's "~125B"."""
    from horovod_tpu.models.transformer import (
        Transformer,
        ling_3_0_flash_config,
    )

    cfg, z = ling_3_0_flash_config(), full_sizes()
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.vocab_size) == (
        z["num_hidden_layers_published"], z["hidden_size"],
        z["num_attention_heads"], z["vocab_size_published"])
    assert (cfg.d_ff, cfg.d_ff_dense, cfg.d_ff_shared) == (768, 6144, 768)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.moe_groups,
            cfg.moe_groups_kept) == (512, 8, 8, 4)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (512, 0, 128, 64, 128)
    assert (cfg.kda_head_dim, cfg.conv_taps) == (128, 4)
    kinds = [cfg.layer_kind(i) for i in range(42)]
    assert [i for i, k in enumerate(kinds) if k.mixer == "attention"] \
        == [5, 11, 17, 23, 29, 35, 41]
    assert [i for i, k in enumerate(kinds) if k.ffn == "dense"] == [0, 1]
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == z["parameters_published"] == 124_414_191_072


def test_fresh_decays_are_bounded_and_differ_by_channel():
    """The gate's initialisers (flash-linear-attention's: ``A_log = log U(1,
    16)`` a head, ``dt_bias`` the inverse softplus of a log-uniform step a
    channel) and its bound: every ``g`` in (-5, 0)."""
    from horovod_tpu.models import kda as mixer

    a_log = mixer._a_log_init(jax.random.PRNGKey(0), (64,))
    assert 0.0 <= float(a_log.min()) and float(a_log.max()) <= np.log(16)
    dt_bias = mixer._dt_bias_init(jax.random.PRNGKey(1), (4096,))
    assert -7.0 < float(dt_bias.min()) and float(dt_bias.max()) < -2.2
    assert float(jnp.std(dt_bias)) > 1.0


def test_an_unknown_mixer_and_another_bound_are_refused():
    """The decay's bound is the kernels' own (what keeps a sub-block's
    exponents inside fp32), so a file that states another is refused."""
    from horovod_tpu.kernels import kda
    from horovod_tpu.models.transformer import (
        LayerKind,
        Transformer,
        tiny_config as plain,
    )

    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="unknown mixer"):
        Transformer(plain(layer_pattern=(LayerKind(mixer="nope"),))).init(
            jax.random.PRNGKey(0), tokens)
    assert full_sizes()["kda_lower_bound"] == kda.LOWER_BOUND == -5
    with pytest.raises(ValueError, match="bounded at the kernels' -5"):
        config_module().Config(tiny_sizes(kda_lower_bound=-8))


# -- the rule -----------------------------------------------------------------


def rule_operands(seed, s, heads=2, d=128, at_bound=False, batch=1):
    """q (scaled), k (unit), v, g in (-5, 0) a channel, beta in (0, 1); with
    ``at_bound`` every other channel decays by -4.999 a position over the
    whole sequence, the most the gate allows: a chunk's running sum then
    reaches -320 and a sub-block's exponents 75."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(key, (batch, s, heads, d)) for key in keys[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (batch, s, heads, d))
    g = -5 * jax.nn.sigmoid(jax.random.normal(keys[3], (batch, s, heads, d))
                            - 2.0)
    if at_bound:
        g = jnp.where(jnp.arange(d) % 2 == 0, -4.999, g)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, s, heads)))
    return q, k, v, g, beta


def token_by_token(q, k, v, g, beta):
    return jax.vmap(ref.recurrent_rule)(q, k, v, g, beta)


def everything(rule, operands, do):
    """(o, dq, dk, dv, dg, dbeta) of ``rule``."""
    o, back = jax.vjp(rule, *operands)
    return (o,) + back(do.astype(o.dtype))


@pytest.mark.parametrize("s,at_bound", [(1, False), (70, False), (128, False),
                                        (128, True)],
                         ids=["1", "70", "128", "128_at_the_bound"])
def test_chunked_is_the_recurrence_token_by_token(s, at_bound):
    """``kda.chunked`` in float32 against the reference's recurrence, ``o``
    and all five cotangents to 1e-4 of their norm (float32's own rounding
    through a chunk's inverse reads 1e-6 to 1e-5), at lengths that are no
    whole chunks, and with gates at the bound over whole chunks, where an
    exponent taken over a chunk and not a sub-block would overflow: every
    value finite."""
    from horovod_tpu.kernels import kda

    operands = rule_operands(s, s, heads=2, d=32, at_bound=at_bound, batch=2)
    do = jax.random.normal(jax.random.PRNGKey(9), operands[2].shape)
    with jax.default_matmul_precision("highest"):
        got = everything(kda.chunked, operands, do)
        want = everything(token_by_token, operands, do)
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        # Under decays at the bound dg is a difference of nearly equal
        # terms: its own norm is 1e-3 of the terms'.
        assert rel_err(a, b) < (1e-3 if at_bound and name == "dg"
                                else 1e-4), (name, float(rel_err(a, b)))


@pytest.mark.parametrize("heads,at_bound", [(2, False), (8, True), (3, False)],
                         ids=["2heads", "8heads_at_the_bound", "3heads"])
def test_the_kernels_in_interpret_mode_are_the_recurrence(heads, at_bound):
    """The two kernels under ``interpret=True`` on bf16 operands against the
    float32 recurrence: ``o`` and the cotangents of q, k, v and beta within
    2e-2 of their norm (bf16 operands and bf16 products with fp32 sums read
    5e-3), ``dg`` within 1e-1 (a sum of terms that cancel: 1e-2 under
    moderate decays, 5e-2 where every channel decays fast); finite at the
    bound."""
    from horovod_tpu.kernels import kda

    assert kda.takes(128, heads, 128, 128)
    operands = rule_operands(3, 128, heads=heads, at_bound=at_bound)
    do = jax.random.normal(jax.random.PRNGKey(9), operands[2].shape)
    rounded = tuple(t.astype(jnp.bfloat16) for t in operands[:3]) \
        + operands[3:]
    got = everything(
        lambda *a: kda.kda(*a, interpret=True), rounded, do)
    with jax.default_matmul_precision("highest"):
        want = everything(token_by_token, operands, do)
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
        assert rel_err(a.astype(jnp.float32), b) \
            < (1e-1 if name == "dg" else 2e-2), (name, float(rel_err(
                a.astype(jnp.float32), b)))


def test_what_the_kernels_take():
    from horovod_tpu.kernels import kda

    assert kda.takes(8192, 32, 128, 128)
    assert not kda.takes(8192, 32, 128, 128, jnp.float32)
    assert not kda.takes(8192, 32, 64, 64)
    assert not kda.takes(100, 32, 128, 128)
    assert [kda.heads_a_step(h) for h in (32, 12, 7, 3)] == [8, 6, 7, 3]
    # Off the TPU, or on float32 operands, the chunked form.
    q, k, v, g, beta = rule_operands(0, 64, heads=1, d=128)
    with jax.default_matmul_precision("highest"):
        assert rel_err(kda.kda(q, k, v, g, beta),
                       kda.chunked(q, k, v, g, beta)) == 0.0
    with pytest.raises(ValueError):
        kda.kda(q, k, v, g[..., :64], beta)
    with pytest.raises(ValueError, match="sub-blocks"):
        kda.chunked(q, k, v, g, beta, chunk=24)


# -- the router ---------------------------------------------------------------


def route_operands(seed, n=96, d=32, experts=64):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (n, d)),
            jax.random.normal(keys[1], (d, experts)),
            0.3 * jax.random.normal(keys[2], (experts,)))


def test_the_group_limited_choice_is_the_references_and_transformers():
    """``_route(n_group=8, topk_group=4)`` on float32 rows: the experts and
    the weights are the reference's ``choose`` and, on copied weights,
    ``transformers``' ``DeepseekV3TopkRouter`` (4.57: the group's score the
    sum of its two largest biased scores, the losers' scores filled with 0),
    under a bias that is not zero and moves the choice; without the mask a
    row in ten or more chooses otherwise."""
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3.configuration_deepseek_v3 import (
        DeepseekV3Config,
    )
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import (
        DeepseekV3TopkRouter,
    )

    from horovod_tpu.parallel.moe import _route

    x, router, bias = route_operands(0)
    sizes = dict(n_group=8, topk_group=4, num_experts_per_tok=4)
    with jax.default_matmul_precision("highest"):
        weights, experts, counts, _, _ = _route(
            x, router, 4, norm_topk_prob=True, scoring="sigmoid", bias=bias,
            scale=2.5, n_group=8, topk_group=4)
        scores = jax.nn.sigmoid(x @ router)
    want = ref.choose(scores + bias, sizes)
    np.testing.assert_array_equal(np.sort(experts, axis=1),
                                  np.sort(want, axis=1))
    assert int(jnp.sum(counts)) == 96 * 4
    free = ref.choose(scores + bias, sizes, ("no_group_mask",))
    assert np.mean(np.any(np.sort(free, axis=1) != np.sort(want, axis=1),
                          axis=1)) > 0.1
    theirs = DeepseekV3TopkRouter(DeepseekV3Config(
        hidden_size=32, n_routed_experts=64, num_experts_per_tok=4, n_group=8,
        topk_group=4, norm_topk_prob=True, routed_scaling_factor=2.5))
    with torch.no_grad():
        theirs.weight.copy_(torch.tensor(np.asarray(router).T))
        theirs.e_score_correction_bias.copy_(torch.tensor(np.asarray(bias)))
        indices, their_weights = theirs(torch.tensor(np.asarray(x)))
    order = np.argsort(indices.numpy(), axis=1)
    ours = np.argsort(np.asarray(experts), axis=1)
    np.testing.assert_array_equal(
        np.take_along_axis(indices.numpy(), order, 1),
        np.take_along_axis(np.asarray(experts), ours, 1))
    np.testing.assert_allclose(
        np.take_along_axis(their_weights.numpy(), order, 1),
        np.take_along_axis(np.asarray(weights), ours, 1), rtol=2e-5)


def test_one_group_lowers_to_the_parents_text():
    """``n_group`` 1 and ``topk_group`` 1 are no group limit: the layer lowers
    to the text it lowered to without the arguments; 8 and 4 to another."""
    from horovod_tpu.parallel.moe import moe_ffn

    x = jnp.zeros((2, 16, 32), jnp.bfloat16)
    router = jnp.zeros((32, 64))
    stacks = (jnp.zeros((64, 32, 16)),) * 2 + (jnp.zeros((64, 16, 32)),)

    def text(**groups):
        return jax.jit(lambda x, r, *w: moe_ffn(
            x, r, *w, k=4, scoring="sigmoid", bias=jnp.zeros((64,)),
            **groups)).lower(x, router, *stacks).as_text()

    assert text() == text(n_group=1, topk_group=1)
    assert text() != text(n_group=8, topk_group=4)
    with pytest.raises(ValueError, match="groups"):
        text(n_group=7, topk_group=4)


def test_the_shares_of_an_expert_layer_add_up_with_the_shared_expert_once(
        tiny):
    """64 experts over 8 shares of 8, each share's experts one of every
    group: each share routes over all 64 inside 4 of 8 groups and adds the
    shared expert, which every chip computes alike: the shares' routed sums
    plus the shared expert once are the uncut layer, which is the uncut
    reference's."""
    from horovod_tpu.models.transformer import Block, LayerKind

    ids = list(range(64))
    whole = tiny_config(experts_held=ids, num_experts=64)
    cfg, sizes = whole.model.cfg, whole.sizes
    kind = LayerKind(mixer="none")
    params = {k: v for k, v in seeded(whole)[0]["layer_1"].items()
              if k not in ("kda", "ln1")}
    bias = tiny["aux"]["expert_bias"][1]
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64))

    def layer(cfg, p):
        y, _ = Block(cfg, kind).apply(
            {"params": p, "moe": {"bias": bias}}, h, mutable=["moe"])
        return y - h

    with jax.default_matmul_precision("highest"):
        want = layer(cfg, params)
        m = ref._rms_norm(h, params["ln2"]["scale"], 1e-6).reshape(48, 64)
        plain, _ = ref._experts(params, bias, m, sizes)
        assert rel_err(want, plain.reshape(2, 24, 64)) < 1e-5
        shared = ref._swiglu(m, params["shared_gate"]["kernel"],
                             params["shared_up"]["kernel"],
                             params["shared_down"]["kernel"]) \
            .reshape(2, 24, 64)
        routed = []
        for first in range(8):
            held = ids[first::8]
            share = dataclasses.replace(cfg, experts_held=tuple(held))
            p = {**params, **{
                name: params[name][np.asarray(held)]
                for name in ("experts_gate", "experts_up", "experts_down")}}
            routed.append(layer(share, p) - shared)
    assert rel_err(sum(routed) + shared, want) < 1e-5
    assert rel_err(sum(routed), want) > 0.05
    assert rel_err(routed[0] + shared, want) > 0.1


# -- latent attention's two options --------------------------------------------


def test_latent_attention_without_a_query_latent_and_under_a_gate(tiny):
    """``q_lora_rank`` 0 builds ``q`` from the stream (no ``q_a``, no norm)
    and ``attention_gate="head"`` a column a head; the layer is the
    reference's; JoyAI's form (a query latent, no gate) keeps its parameter
    tree; any other gate is refused."""
    from horovod_tpu.models.deepseek import LatentAttention
    from horovod_tpu.models.transformer import joyai_llm_flash_config

    cfg = tiny["config"].model.cfg
    p = tiny["params"]["layer_2"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 64))
    with jax.default_matmul_precision("highest"):
        got = LatentAttention(cfg).apply({"params": p}, x)
        want = jax.vmap(lambda row: ref._mla(p, row, tiny["config"].sizes))(x)
        ungated = jax.vmap(lambda row: ref._mla(
            p, row, tiny["config"].sizes, ("no_head_gate",)))(x)
    assert rel_err(got, want) < 1e-5
    assert rel_err(got, ungated) > 0.1
    joyai = joyai_llm_flash_config(
        num_heads=2, d_model=64, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, max_len=256,
        dtype=jnp.float32)
    shapes = jax.eval_shape(LatentAttention(joyai).init,
                            jax.random.PRNGKey(0), x)["params"]
    assert set(shapes) == {"q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
                           "kv_b", "out"}
    with pytest.raises(ValueError, match="one column a head"):
        LatentAttention(dataclasses.replace(cfg, attention_gate=True)).init(
            jax.random.PRNGKey(0), x)


def test_the_gauges_come_from_the_shapes(tiny):
    from horovod_tpu.core import metrics
    from horovod_tpu.models.transformer import publish_kda

    cfg = tiny["config"].model.cfg
    assert publish_kda(cfg, 8192) == 2 * 2 * 128
    assert publish_kda(cfg, 100, sequences=3) == 2 * 3 * 2 * 2
    assert metrics.registry.get_gauge("kda_chunks_per_step") == 24.0
