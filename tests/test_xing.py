"""Xing4.0-29B-A4B: four residual streams under manifold-constrained
hyper-connections (the coefficients, the Sinkhorn projection, the mixes),
latent attention under YaRN, a dense layer and expert layers behind it, and
the whole model against the plain reference
(``chip_bench/configs/xing4.0-29b-a4b_reference.py``: float32, nothing of
``horovod_tpu``) on seeded weights at tiny widths, every part present.
``tests/test_xing_cell.py`` holds the configuration and its cell,
``tests/test_xing_compile.py`` the step's compile for a described chip.
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

ref = load_reference("xing4.0-29b-a4b")

# A dense layer and two sparse ones (the published layers 0, 2, 3 of a model
# whose first two are dense), 4 streams, 4 heads of 16 + 8 over 12 under YaRN
# from 8 original positions, 2 of 8 experts held of top 2, a sliced
# vocabulary; and what the configuration's module asks of a file beside the
# sizes.
TINY = dict(
    layers_held=[0, 2, 3], num_hidden_layers=3, first_k_dense_replace=1,
    first_k_dense_replace_published=2, num_nextn_predict_layers=0,
    hidden_size=32, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=12, rope_theta=10000, rope_interleave=True,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=8,
                      type="yarn"),
    intermediate_size=48, moe_intermediate_size=24, n_shared_experts=1,
    n_routed_experts=2, n_routed_experts_published=8, experts_held=[1, 6],
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=2,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
    moe_layer_freq=1, hidden_act="silu", attention_bias=False,
    tie_word_embeddings=False, vocab_size=64, rms_norm_eps=1e-6,
    expert_bias_update_rate=1e-3, sequence_length=20,
    max_position_embeddings=64, per_chip_batch=2, embedding_init_std=1.0,
    recompute_blocks=False, name="tiny-xing")

WRONG = ("sinkhorn_one_iteration", "rows_first", "post_without_2", "clamp_3",
         "scale_without_mscale", "plain_rope")


def config_module():
    """``chip_bench/configs/xing4.0-29b-a4b.py``, found as the harness finds
    it."""
    from chip_bench import spec

    return spec.Cell("xing4.0-29b-a4b-wfbp-1chip",
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
    initialisers draw them, so that at these widths every module moves the
    streams and a wrong one shows; and every hyper-connection away from its
    start: ``alpha`` of order 1 and random biases, the logits of ``H_res``
    spread over a dozen units, so that the streams differ, the iterations
    have work to do and the clamp at 3 cuts."""
    return _seeded(dataclasses.replace(
        model.cfg, dtype=jnp.float32, remat=False, hc_sinkhorn_iters=1),
        seed, by)


@functools.lru_cache(maxsize=None)
def _seeded(cfg, seed, by):
    """Once a model and seed: neither the precision, the recomputation nor
    the number of iterations is in the parameters."""
    from horovod_tpu.models.transformer import Transformer

    params = nn.meta.unbox(jax.jit(Transformer(cfg).init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"])
    grow = {"kernel", "router", "experts_gate", "experts_up", "experts_down"}
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def scaled(path, x):
        last = getattr(path[-1], "key", None)
        under = {getattr(k, "key", None) for k in path}
        if last == "alpha":
            return jnp.asarray([0.7, 1.3, 2.0], x.dtype)
        if last == "bias" and under & {"hc_mixer", "hc_ffn"}:
            return jax.random.normal(next(keys), x.shape, x.dtype) \
                * jnp.where(jnp.arange(x.size) < 8, 1.0, 4.0)
        if last == "phi":
            return x * 4.0
        return x * by if last in grow and "embed" not in under else x

    return jax.tree_util.tree_map_with_path(scaled, params)


def some_bias(sizes, seed=3, width=0.05):
    return width * jax.random.normal(
        jax.random.PRNGKey(seed), ref.zero_bias(sizes).shape, jnp.float32)


def zero_aux(sizes, bias=None):
    from horovod_tpu.parallel.moe import moe_counters

    aux = moe_counters(len(ref.expert_blocks(sizes)),
                       sizes["n_routed_experts_published"], share=True,
                       expert_bias=True)
    aux["hc_deviation"] = jnp.zeros((), jnp.float32)
    return aux if bias is None else {**aux, "expert_bias": bias}


def apply(model, params, bias, tokens):
    from horovod_tpu.models.transformer import expert_bias_collection

    return model.apply(
        {"params": params, "moe": expert_bias_collection(model.cfg, bias)},
        tokens, mutable=["moe"])[0]


# -- the whole model ----------------------------------------------------------


@pytest.mark.parametrize("dtype,seed,tol", [
    (jnp.float32, 0, dict(loss=2e-6, logits=2e-5, grads=1e-4)),
    (jnp.bfloat16, 4, dict(loss=2e-3, logits=6e-2, grads=0.5))],
    ids=["float32", "bfloat16"])
def test_the_model_matches_the_reference(dtype, seed, tol):
    """Logits, loss, the gradient of every leaf (``phi``, ``bias`` and
    ``alpha`` of all six hyper-connections among them), the counts, the
    stepped bias and the Sinkhorn counter of the program's model against the
    plain reference's on the same seeded weights, under a selection bias that
    is not zero: in float32 to rounding, in the cell's precision (bf16
    streams) to what bf16 leaves on a seed where both choose the same experts
    for every token (read there: logits 2.8e-2 of the largest, loss 4e-6, the
    worst leaf, an ``alpha`` of three numbers, 0.32; on seeds 0 to 2, where
    bf16 moves a choice across a tie, 0.12 to 0.31 of the largest logit and
    up to 0.75 of a router's leaf).  Four Sinkhorn iterations here and in the
    next test, which compile in a fifth of twenty's time on a CPU; twenty
    are held by themselves below, value and cotangent, and in every forward
    pass of this file."""
    config = tiny_config(dtype, hc_sinkhorn_iters=4)
    model, sizes = config.model, config.sizes
    params, batch = seeded(model, seed), tokens_of(sizes, seed + 1)
    aux = zero_aux(sizes, some_bias(sizes))
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda *a: apply(model, *a))(
            params, aux["expert_bias"], batch["tokens"])
        (got, got_aux), grads = jax.jit(jax.value_and_grad(
            config.loss, has_aux=True))(params, aux, batch)
    want_logits = jax.jit(lambda p, b, bias: ref.logits(
        p, b, sizes, bias=bias))(params, batch, aux["expert_bias"])
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
    # What four iterations leave of matrices whose logits spread over a
    # dozen units: well above rounding, and the same on both sides.
    assert 1e-5 < float(want_aux["hc_deviation"]) < 1.0
    assert float(got_aux["hc_deviation"]) == pytest.approx(
        float(want_aux["hc_deviation"]), rel=1e-3 if dtype == jnp.float32
        else 0.2)
    if dtype == jnp.float32:
        for key in set(want_aux) - {"hc_deviation"}:
            np.testing.assert_array_equal(got_aux[key], want_aux[key])
    # Two sparse layers, 2 x 20 tokens, 2 a token.
    assert int(want_aux["tokens_per_expert"].sum()) == 2 * 2 * 20 * 2


@pytest.fixture(scope="module")
def sound_logits():
    """Seeded weights, a batch, the reference's logits on them and the
    program's in float32: made once for the six faults."""
    config = tiny_config()
    model, sizes = config.model, config.sizes
    params, batch = seeded(model), tokens_of(sizes, 1)
    want = jax.jit(lambda p, b: ref.logits(p, b, sizes))(params, batch)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: apply(model, *a))(
            params, ref.zero_bias(sizes), batch["tokens"])
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
    config = tiny_config(hc_sinkhorn_iters=4)
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
        np.testing.assert_allclose(aux_a[key], aux_b[key], rtol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One sparse layer's FFN on the same input: the four shares of two
    experts each, the shared expert counted once, add up to the layer that
    holds all eight, in the program and against the reference's layer."""
    from horovod_tpu.models.transformer import Block, LayerKind

    config = tiny_config(n_routed_experts=8, experts_held=list(range(8)))
    cfg = dataclasses.replace(config.model.cfg, hc_mult=0)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 20, 32))
    whole = Block(cfg, LayerKind(mixer="none", ffn="moe"))
    params = nn.meta.unbox(jax.jit(whole.init)(jax.random.PRNGKey(0),
                                               x))["params"]
    grow = {"router", "experts_gate", "experts_up", "experts_down"}
    params = {k: v * 8.0 if k in grow else jax.tree_util.tree_map(
        lambda t: t * 8.0 if t.ndim == 2 else t, v)
        for k, v in params.items()}
    bias = {"bias": jnp.zeros((8,), jnp.float32)}

    def ffn(block, p):
        with jax.default_matmul_precision("highest"):
            return block.apply({"params": p, "moe": bias}, x,
                               mutable=["moe"])[0] - x

    want = ffn(whole, params)
    total = 0.0
    for share in range(4):
        held = (2 * share, 2 * share + 1)
        block = Block(dataclasses.replace(cfg, experts_held=held),
                      LayerKind(mixer="none", ffn="moe"))
        mine = {k: v[jnp.asarray(held)] if k.startswith("experts_") else v
                for k, v in params.items()}
        total = total + ffn(block, mine)
    # Every share added the shared expert: three too many.
    one = Block(dataclasses.replace(cfg, num_experts=0, ffn="dense",
                                    d_ff_dense=24),
                LayerKind(mixer="none", ffn="dense"))
    shared = ffn(one, {"ln2": params["ln2"],
                       "ffn_gate": params["shared_gate"],
                       "ffn_up": params["shared_up"],
                       "ffn_down": params["shared_down"]})
    assert rel_err(total - 3 * shared, want) < 1e-5
    # The reference's layer on the normed input, all eight held.
    with jax.default_matmul_precision("highest"):
        m = ref._rms_norm(x, params["ln2"]["scale"], 1e-6).reshape(40, 32)
        plain, counts = ref._experts(params, jnp.zeros((8,)), m,
                                     config.sizes)
    assert rel_err(plain.reshape(2, 20, 32), want) < 1e-5
    assert int(counts.sum()) == 40 * 2


# -- the hyper-connection -----------------------------------------------------


def test_sinkhorn_is_the_references_loop_and_doubly_stochastic():
    """``sinkhorn`` on ``[n, n, tokens]`` against the reference's loop of
    twenty column and row divisions on ``[tokens, n, n]``, value and
    cotangent (the ``custom_vjp`` runs the iterations again): on logits of
    one size, where twenty iterations end (every row's and column's sum
    within 1e-5 of 1); near a fresh connection's, a matrix near the identity,
    which Sinkhorn balances slowly (the rows, divided last, within 1e-5 and
    the columns within 1e-3: what the step's ``hc_deviation`` reads); and
    spread over the whole clamp."""
    from horovod_tpu.models import hyper_connections as hc

    sizes = TINY
    key = jax.random.PRNGKey(0)
    noise = jax.random.normal(key, (64, 4, 4))
    near = jnp.where(jnp.eye(4, dtype=bool), 0.0, -8.0)[None] + 0.1 * noise
    for logits, columns_within in ((0.5 * noise, 1e-5), (near, 1e-3), (
            jnp.clip(12.0 * noise, -30, 30), None)):
        ours = lambda a: hc.sinkhorn(  # noqa: E731
            a.transpose(1, 2, 0), 20, 1e-6).transpose(2, 0, 1)
        plain = lambda a: ref.sinkhorn(a, sizes)  # noqa: E731
        got, want = ours(logits), plain(logits)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-9)
        columns = np.abs(np.asarray(got).sum(axis=1) - 1).max()
        assert np.abs(np.asarray(got).sum(axis=2) - 1).max() < 1e-5
        assert columns_within is None or columns < columns_within
        assert float(hc.deviation(got.transpose(1, 2, 0))) == pytest.approx(
            max(np.abs(np.asarray(want).sum(axis=1) - 1).max(),
                np.abs(np.asarray(want).sum(axis=2) - 1).max()),
            rel=2e-2, abs=2e-7)
        weights = jax.random.normal(jax.random.PRNGKey(1), got.shape)
        grad = jax.grad(lambda a: jnp.sum(ours(a) * weights))(logits)
        plain_grad = jax.grad(lambda a: jnp.sum(plain(a) * weights))(logits)
        assert rel_err(grad, plain_grad) < 1e-4
    # One array is kept for the backward pass: the logits.
    kept = jax.make_jaxpr(lambda a: jax.vjp(
        lambda t: hc.sinkhorn(t, 20, 1e-6), a)[1])(near.transpose(1, 2, 0))
    assert len(kept.jaxpr.outvars) == 1


def test_the_coefficients_keep_the_tokens_minor_and_match_the_reference():
    """``H_pre``, ``H_post`` and ``H_res`` of one hyper-connection on streams
    that differ, over float32 and over bf16 streams (one bf16 pass against
    the three pieces of ``phi``), against the reference's; no array of the
    forward pass or kept for the backward pass is ``[tokens, 4, 4]``."""
    from horovod_tpu.models import hyper_connections as hc

    key = jax.random.split(jax.random.PRNGKey(0), 4)
    streams = jax.random.normal(key[0], (2, 20, 4, 32))
    p = {"phi": 0.3 * jax.random.normal(key[1], (128, 24)),
         "bias": jax.random.normal(key[2], (24,)),
         "alpha": jnp.asarray([0.7, 1.3, 2.0])}
    want = ref.connection(p, streams.reshape(40, 4, 32), TINY)
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
        x = streams.astype(dtype)

        def ours(x, phi):
            pre, post, logits = hc.coefficients(x, phi, p["bias"],
                                                p["alpha"], 4, 1e-6, 30.0)
            return pre, post, hc.sinkhorn(logits, 20, 1e-6)

        with jax.default_matmul_precision("highest"):
            pre, post, res = ours(x, p["phi"])
        assert pre.shape == post.shape == (4, 2, 20)
        assert res.shape == (4, 4, 2, 20)
        assert rel_err(pre.reshape(4, 40).T, want[0]) < tol
        assert rel_err(post.reshape(4, 40).T, want[1]) < tol
        assert rel_err(res.reshape(4, 4, 40).transpose(2, 0, 1),
                       want[2]) < tol
        # Forward and backward: only phi (and its pieces and cotangent) has
        # the 24 coefficients minor; every array a token long has the tokens
        # minor, and none is [tokens, 4, 4].
        jaxpr = jax.make_jaxpr(lambda x, phi: jax.vjp(ours, x, phi)[1](
            (pre, post, res)))(x, p["phi"])
        shapes = {tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns
                  for v in eqn.outvars}
        assert not [s for s in shapes if len(s) > 1 and (
            s[-2:] == (4, 4) or s[-1] in (4, 16)
            or s[-1] in (24, 72) and s[0] != 128)], shapes
    # Over bf16 streams the product's gradients are the float32 product's on
    # the same bf16 values.
    x = streams.astype(jnp.bfloat16).reshape(40, 128)
    u = jax.random.normal(key[3], (24, 40))
    got = jax.grad(lambda x, w: jnp.sum(hc._phi_product(x, w) * u),
                   argnums=(0, 1))(x, p["phi"])
    with jax.default_matmul_precision("highest"):
        want_g = jax.grad(lambda x, w: jnp.sum((x @ w).T * u),
                          argnums=(0, 1))(x.astype(jnp.float32), p["phi"])
    assert rel_err(got[0].astype(jnp.float32), want_g[0]) < 1e-2   # bf16 out
    assert rel_err(got[1], want_g[1]) < 1e-5


def test_one_stream_read_whole_is_the_plain_block():
    """``hc_mult`` 1 with ``b_pre`` large and ``b_post`` 0: ``H_pre`` is 1,
    ``H_post`` 1 and ``H_res`` the 1 x 1 doubly stochastic matrix, so the
    block is the pre-norm residual block it replaces."""
    from horovod_tpu.models.transformer import Block, LayerKind

    config = tiny_config()
    cfg = dataclasses.replace(config.model.cfg, hc_mult=1, hc_eps=0.0)
    kind = LayerKind(ffn="dense")
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 20, 32))
    block = Block(cfg, kind)
    params = nn.meta.unbox(jax.jit(block.init)(jax.random.PRNGKey(0),
                                      x[:, :, None]))["params"]
    assert params["hc_mixer"]["phi"].shape == (32, 3)
    for name in ("hc_mixer", "hc_ffn"):
        params[name]["bias"] = jnp.asarray([40.0, 0.0, 0.3])
    plain = {k: v for k, v in params.items() if not k.startswith("hc_")}
    with jax.default_matmul_precision("highest"):
        got = block.apply({"params": params}, x[:, :, None])
        want = Block(dataclasses.replace(cfg, hc_mult=0), kind).apply(
            {"params": plain}, x)
    assert got.shape == (2, 20, 1, 32)
    assert rel_err(got[:, :, 0], want) < 2e-5


def test_a_fresh_connection_starts_near_the_pre_norm_residual():
    """The biases' start: sublayer k of the model reads stream k mod 4 with
    0.99 (the others 0.01), writes every stream with 1, and ``H_res`` is the
    identity to 1e-3; ``alpha`` 0.01."""
    from horovod_tpu.models import hyper_connections as hc

    config = tiny_config()
    params = nn.meta.unbox(jax.jit(config.model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    for layer in range(3):
        for sublayer, name in enumerate(("hc_mixer", "hc_ffn")):
            p = params[f"layer_{layer}"][name]
            np.testing.assert_allclose(p["alpha"], 0.01)
            pre = jax.nn.sigmoid(p["bias"][:4])
            read = (2 * layer + sublayer) % 4
            assert float(pre[read]) == pytest.approx(0.99)
            assert float(jnp.sum(pre)) == pytest.approx(0.99 + 3 * 0.01)
            np.testing.assert_array_equal(p["bias"][4:8], 0.0)
            res = hc.sinkhorn(p["bias"][8:].reshape(4, 4, 1), 20, 1e-6)
            np.testing.assert_allclose(res[..., 0], np.eye(4), atol=1.1e-3)
    # ln(0.99 / 0.01) and the exponential's -8.
    assert float(params["layer_0"]["hc_mixer"]["bias"][0]) \
        == pytest.approx(math.log(99.0))
    assert float(params["layer_0"]["hc_mixer"]["bias"][9]) == -8.0


# -- YaRN ---------------------------------------------------------------------


def test_yarn_frequencies_and_scale_at_the_published_numbers():
    """``inv_freq`` of the 64 rotary columns against the formula written out
    in float64, ``low`` and ``high`` as integers, the softmax scale's factor
    m^2 = 2.00474; the program's and the reference's."""
    from horovod_tpu.models.transformer import (
        xing4_0_29b_a4b_config,
        yarn_correction_range,
        yarn_inv_freq,
        yarn_mscale,
    )

    cfg = xing4_0_29b_a4b_config()
    published = dict(
        qk_rope_head_dim=64, qk_nope_head_dim=128, rope_theta=10000,
        rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                          mscale_all_dim=1,
                          original_max_position_embeddings=4096, type="yarn"))

    def pair(turns):
        return 64 * math.log(4096 / (2 * math.pi * turns)) \
            / (2 * math.log(10000))

    assert round(pair(32), 2) == 10.47 and round(pair(1), 2) == 22.51
    assert yarn_correction_range(cfg, 64) == ref.yarn_range(published) \
        == (10, 23)
    i = np.arange(32, dtype=np.float64)
    plain = 10000.0 ** (-2 * i / 64)
    g = 1 - np.clip((i - 10) / 13, 0, 1)
    want = (1 - g) * plain / 64 + g * plain
    np.testing.assert_allclose(yarn_inv_freq(cfg, 64), want, rtol=1e-6)
    np.testing.assert_allclose(ref.inv_freq(published), want, rtol=1e-6)
    np.testing.assert_allclose(ref.inv_freq(published, ("plain_rope",)),
                               plain, rtol=1e-6)
    # The fastest ten pairs are kept, the slowest nine divided by 64.
    np.testing.assert_allclose(want[:11], plain[:11])
    np.testing.assert_allclose(want[23:], plain[23:] / 64)
    m = yarn_mscale(64.0, 1.0)
    assert round(m, 5) == 1.41589 and round(m * m, 5) == 2.00474
    assert round(ref.softmax_scale(published), 5) == 0.14468
    assert round(ref.softmax_scale(published, ("scale_without_mscale",)),
                 5) == 0.07217
    assert yarn_mscale(1.0, 1.0) == 1.0


@pytest.mark.parametrize("mscale", [1, 0.5], ids=["xings", "tables_scaled"])
def test_latent_attention_under_yarn_is_the_references(mscale):
    """One latent-attention layer with the blended frequencies and the scale
    times m^2 against the reference's, and away from both faults; with
    ``mscale`` other than ``mscale_all_dim`` the tables of cosines and sines
    carry the ratio of the two (Xing's is 1)."""
    from horovod_tpu.models.deepseek import LatentAttention

    config = tiny_config(rope_scaling={**TINY["rope_scaling"],
                                       "mscale": mscale})
    params = seeded(config.model)["layer_0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 32))
    layers = jax.vmap(lambda row, wrong: ref._mla(
        params, row, config.sizes, wrong), in_axes=(0, None))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda x: LatentAttention(config.model.cfg).apply(
            {"params": params}, x))(x)
        want = layers(x, ())
        faults = [layers(x, (wrong,))
                  for wrong in ("scale_without_mscale", "plain_rope")]
        unscaled = jax.vmap(lambda row: ref._mla(
            params, row, {**config.sizes, "rope_scaling": TINY[
                "rope_scaling"]}))(x)
    assert rel_err(got, want) < 1e-5
    assert all(rel_err(fault, want) > 1e-2 for fault in faults)
    assert (rel_err(unscaled, want) > 1e-2) == (mscale != 1)


# -- the preset ---------------------------------------------------------------


def test_the_preset_is_the_published_model():
    """Parameter counts by ``jax.eval_shape`` at the published widths: the
    whole model (without the prediction module, which is not built under
    several streams) and the cell's cut."""
    from horovod_tpu.models.transformer import (
        LayerKind,
        Transformer,
        xing4_0_29b_a4b_config,
    )

    def count(cfg):
        # One iteration: the count is of the parameters, and eighty traces
        # of twenty iterations are twenty seconds.
        cfg = dataclasses.replace(cfg, hc_sinkhorn_iters=1)
        shapes = jax.eval_shape(
            lambda: Transformer(cfg).init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32)))
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))

    cfg = xing4_0_29b_a4b_config()
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp) == (4, 20, 1e-6, 30.0)
    kinds = [k.ffn for k in cfg.layer_pattern]
    assert kinds == ["dense"] * 2 + [None] * 38
    assert cfg.expert_layers() == tuple(range(2, 40))
    assert count(cfg) == 29_505_502_832                 # "29B-A4B"
    cut = xing4_0_29b_a4b_config(
        vocab_size=16384, num_layers=5, experts_held=tuple(range(8)),
        layer_pattern=tuple(LayerKind(ffn="dense" if i < 1 else None)
                            for i in range(5)))
    assert count(cut) == 759_346_190
    # A sublayer's hyper-connection: phi 14,336 x 24, 24 biases, 3 alphas.
    assert 14_336 * 24 + 24 + 3 == 344_091
    with pytest.raises(ValueError, match="prediction module"):
        jax.eval_shape(lambda: Transformer(dataclasses.replace(
            cut, mtp_modules=1)).init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32)))


def test_the_defaults_build_no_hyper_connection_and_load_no_module():
    """``hc_mult`` 0 and ``yarn_factor`` 1, the defaults, leave every other
    model's tree and program as they were (``tests/test_pinned_programs.py``
    holds the text), and ``import horovod_tpu`` does not load the module."""
    import subprocess

    from horovod_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig()
    assert (cfg.hc_mult, cfg.yarn_factor) == (0, 1.0)
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, horovod_tpu, horovod_tpu.models.transformer\n"
         "print('horovod_tpu.models.hyper_connections' in sys.modules)"],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": REPO_ROOT})
    assert out.stdout.strip() == "False", out.stderr[-2000:]
