"""JoyAI-LLM-Flash: latent attention (keys of 128 + 64 over values of 128, a
rotary key the heads share, interleaved RoPE), a leading dense layer, experts
by sigmoid scores plus a bias beside a gated shared expert, the
multi-token-prediction module behind the stack with the model's own embedding
and head, and the whole model against the plain reference
(``chip_bench/configs/joyai-llm-flash_reference.py``: float32, nothing of
``horovod_tpu``) on seeded weights at tiny widths.
``tests/test_joyai_cell.py`` holds the configuration and its cell.
"""

import dataclasses
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

ref = load_reference("joyai-llm-flash")

# The dense layer and two sparse ones with the prediction module behind them,
# 4 heads of 16 + 8 over 12, 4 of 16 experts held, a sliced vocabulary; and
# what the configuration's module asks of a file beside the sizes.
TINY = dict(
    layers_held=[0, 1, 2], num_hidden_layers=3, first_k_dense_replace=1,
    num_nextn_predict_layers=1, mtp_loss_weight=0.3, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
    v_head_dim=12, rope_theta=32000000, rope_interleave=True,
    rope_scaling=None, intermediate_size=48, moe_intermediate_size=24,
    n_shared_experts=1, n_routed_experts=4, n_routed_experts_published=16,
    experts_held=[1, 5, 6, 12], num_experts_per_tok=3, norm_topk_prob=True,
    routed_scaling_factor=2.5, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, moe_layer_freq=1,
    hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
    vocab_size=64, rms_norm_eps=1e-6, expert_bias_update_rate=1e-3,
    sequence_length=20, max_position_embeddings=64, per_chip_batch=2,
    embedding_init_std=1.0, name="tiny-joyai")


def config_module():
    """``chip_bench/configs/joyai-llm-flash.py``, found as the harness finds
    it."""
    from chip_bench import spec

    return spec.Cell("joyai-llm-flash-wfbp-1chip",
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


def tiny_model(dtype=jnp.float32, **overrides):
    config = tiny_config(dtype, **overrides)
    return config.model, config.sizes


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
    grow = {"kernel", "router", "experts_gate", "experts_up", "experts_down"}

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

    aux = moe_counters(len(ref.expert_blocks(sizes)),
                       sizes["n_routed_experts_published"], share=True,
                       expert_bias=True)
    aux["cross_entropy"] = jnp.zeros(
        (1 + sizes["num_nextn_predict_layers"],), jnp.float32)
    return aux if bias is None else {**aux, "expert_bias": bias}


def program_loss(model, sizes):
    """The cell's loss around ``model``."""
    config = config_module().Config(sizes)
    config.model = model
    return config.loss


def apply(model, params, bias, tokens):
    from horovod_tpu.models.transformer import expert_bias_collection

    return model.apply(
        {"params": params, "moe": expert_bias_collection(model.cfg, bias)},
        tokens, mutable=["moe"])[0]


# -- the whole model ----------------------------------------------------------


@pytest.mark.parametrize("dtype,seed,tol", [
    (jnp.float32, 0, dict(loss=1e-6, logits=1e-5, grads=2e-5)),
    (jnp.bfloat16, 1, dict(loss=2e-3, logits=5e-2, grads=0.25))],
    ids=["float32", "bfloat16"])
def test_the_model_and_its_module_match_the_reference(dtype, seed, tol):
    """Both heads' logits, both losses, the gradient of every leaf, the counts
    and the stepped bias of the program's model against the plain reference's
    on the same seeded weights, under a selection bias that is not zero: in
    float32 to rounding, in the cell's precision (bf16 activations) to what
    bf16 leaves on a seed where both choose the same experts for every token
    (read there: logits 1.7e-2 of the largest, loss 1.1e-4, the worst leaf
    4.7e-2; on seeds 0 and 2, where bf16 moves 14 and 2 of 360 choices across
    a tie, 0.26 and 0.05 of the largest logit and 0.5 and 0.6 of a leaf)."""
    model, sizes = tiny_model(dtype)
    params, batch = seeded(model, seed), tokens_of(sizes, seed + 1)
    aux = zero_aux(sizes, some_bias(sizes))
    with jax.default_matmul_precision("highest"):
        logits, (ahead,) = apply(model, params, aux["expert_bias"],
                                 batch["tokens"])
        (got, got_aux), grads = jax.jit(jax.value_and_grad(
            program_loss(model, sizes), has_aux=True))(params, aux, batch)
    want_logits = ref.logits(params, batch, sizes, bias=aux["expert_bias"])
    assert rel_err(logits, want_logits[0]) < tol["logits"]
    assert rel_err(ahead, want_logits[1]) < tol["logits"]
    (want, want_aux), want_grads = jax.jit(jax.value_and_grad(
        ref.make_loss(sizes), has_aux=True))(params, aux, batch)
    assert float(got) == pytest.approx(float(want), rel=tol["loss"])
    np.testing.assert_allclose(got_aux["cross_entropy"],
                               want_aux["cross_entropy"], rtol=tol["loss"])
    assert float(want) == pytest.approx(
        np.sum(want_aux["cross_entropy"] * np.array([1, 0.3])), rel=1e-6)
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    errs = jax.tree_util.tree_map(rel_err, grads, want_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < tol["grads"], (jax.tree_util.keystr(worst[0]), worst[1])
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(grads))
    np.testing.assert_array_equal(got_aux["tokens_per_expert"],
                                  want_aux["tokens_per_expert"])
    if dtype == jnp.float32:
        for key in set(want_aux) - {"cross_entropy"}:
            np.testing.assert_array_equal(got_aux[key], want_aux[key])
    # Two sparse layers and the module's block, 2 x 20 tokens, 3 a token.
    assert int(want_aux["tokens_per_expert"].sum()) == 3 * 2 * 20 * 3


def test_the_bias_after_two_steps_is_the_references():
    """The selection bias stepped twice by the counts of the program's own
    routing is the reference's, and has moved every layer's."""
    model, sizes = tiny_model()
    params, batch = seeded(model), tokens_of(sizes, 4)
    step = jax.jit(program_loss(model, sizes))
    plain = jax.jit(ref.make_loss(sizes))
    aux = want_aux = zero_aux(sizes)
    with jax.default_matmul_precision("highest"):
        for _ in range(2):
            aux = step(params, aux, batch)[1]
            want_aux = plain(params, want_aux, batch)[1]
    np.testing.assert_array_equal(aux["expert_bias"], want_aux["expert_bias"])
    moved = np.abs(np.asarray(aux["expert_bias"])).max(axis=1)
    assert moved.shape == (3,) and (moved > 0).all() and moved.max() <= 2e-3
    assert int(aux["steps"]) == 2


WRONG = ("rope_key_unrotated", "no_kv_norm", "module_reads_token_i",
         "scale_by_nope")


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
    if wrong == "module_reads_token_i":
        assert rel_err(got[0], want[0]) == 0     # the stack reads no module
    with jax.default_matmul_precision("highest"):
        logits, (ahead,) = apply(model, params, bias, batch["tokens"])
    assert rel_err(jnp.stack([logits, ahead]), want) < 1e-5


def test_a_block_builds_only_what_its_kind_names():
    from horovod_tpu.models.transformer import attention_pairs

    model, sizes = tiny_model()
    shapes = jax.eval_shape(lambda: nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))["params"]
    assert sorted(shapes) == ["embed", "layer_0", "layer_1", "layer_2",
                              "layer_3", "lm_head", "ln_f", "mtp_0"]
    assert sorted(shapes["layer_0"]) == ["attn", "ffn_down", "ffn_gate",
                                         "ffn_up", "ln1", "ln2"]
    sparse = ["attn", "experts_down", "experts_gate", "experts_up", "ln1",
              "ln2", "router", "shared_down", "shared_gate", "shared_up"]
    assert sorted(shapes["layer_1"]) == sorted(shapes["layer_3"]) == sparse
    assert sorted(shapes["mtp_0"]) == ["eh_proj", "enorm", "hnorm", "norm"]
    attn = shapes["layer_0"]["attn"]
    assert sorted(attn) == ["kv_a", "kv_a_norm", "kv_b", "out", "q_a",
                            "q_a_norm", "q_b"]
    # 4 heads: queries and keys 16 + 8, values 12, latents 24 and 16.
    assert attn["q_a"]["kernel"].shape == (32, 24)
    assert attn["q_b"]["kernel"].shape == (24, 4 * 24)
    assert attn["kv_a"]["kernel"].shape == (32, 16 + 8)
    assert attn["kv_b"]["kernel"].shape == (16, 4 * (16 + 12))
    assert attn["out"]["kernel"].shape == (4 * 12, 32)
    assert shapes["mtp_0"]["eh_proj"]["kernel"].shape == (64, 32)
    assert shapes["layer_1"]["router"].shape == (32, 16)
    assert shapes["layer_1"]["experts_gate"].shape == (4, 32, 24)
    assert model.cfg.expert_layers() == (1, 2, 3)
    # The module's block attends too: four blocks' causal pairs.
    assert attention_pairs(model.cfg, 20) == {"window": 0,
                                              "global": 4 * 20 * 21 // 2}


def test_the_preset_is_the_published_model():
    from horovod_tpu.models.transformer import (
        LayerKind,
        Transformer,
        joyai_llm_flash_config,
    )

    def count(cfg):
        shapes = jax.eval_shape(
            lambda: Transformer(cfg).init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32)))
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))

    cfg = joyai_llm_flash_config()
    kinds = [k.ffn for k in cfg.layer_pattern]
    assert len(kinds) == cfg.num_layers == 40
    assert kinds == ["dense"] + [None] * 39
    assert cfg.expert_layers() == tuple(range(1, 41))
    assert count(cfg) == 50_190_481_408           # 48.9 B and its module
    assert count(dataclasses.replace(cfg, mtp_modules=0)) == 48_942_532_608
    # The cell's cut: layers 0-4 and the module, 16 experts, an eighth of
    # the vocabulary.
    cut = joyai_llm_flash_config(
        vocab_size=16160, num_layers=5, experts_held=tuple(range(16)),
        layer_pattern=tuple(LayerKind(ffn="dense" if i < 1 else None)
                            for i in range(5)))
    assert count(cut) == 680_439_808


# -- latent attention ---------------------------------------------------------


def latent_layer(x, **overrides):
    from horovod_tpu.models.deepseek import LatentAttention

    model, sizes = tiny_model(**overrides)
    params = seeded(model)["layer_0"]["attn"]
    with jax.default_matmul_precision("highest"):
        return (LatentAttention(model.cfg).apply({"params": params}, x),
                params, sizes)


def test_latent_attention_is_multi_head_attention_on_the_expanded_weights():
    """The latent form against plain multi-head attention whose per-head
    projections are the expanded ones: W_q = W_dq . norm . W_uq cannot fold
    (a norm lies between), so the latents are computed and each head's
    query, key and value taken from the up-projections' columns; the rotary
    key is one head's, copied to every head; scores over 24^-0.5."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 32))
    got, p, z = latent_layer(x)
    h, n, r, dv = 4, 16, 8, 12
    eps = z["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        c_q = ref._rms_norm(x @ p["q_a"]["kernel"], p["q_a_norm"]["scale"],
                            eps)
        down = x @ p["kv_a"]["kernel"]
        c_kv = ref._rms_norm(down[..., :16], p["kv_a_norm"]["scale"], eps)
        turn = jax.vmap(lambda t: ref._rope_pairs(t, float(z["rope_theta"])))
        k_r = turn(down[..., 16:])
        heads = []
        for j in range(h):
            w_q = p["q_b"]["kernel"][:, j * (n + r):(j + 1) * (n + r)]
            w_kv = p["kv_b"]["kernel"][:, j * (n + dv):(j + 1) * (n + dv)]
            q = c_q @ w_q
            q = jnp.concatenate([q[..., :n], turn(q[..., n:])], axis=-1)
            k = jnp.concatenate([c_kv @ w_kv[:, :n], k_r], axis=-1)
            scores = jnp.einsum("bqd,bkd->bqk", q, k) * (n + r) ** -0.5
            seen = jnp.tril(jnp.ones((20, 20), bool))
            heads.append(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf))
                         @ (c_kv @ w_kv[:, n:]))
        want = jnp.concatenate(heads, axis=-1) @ p["out"]["kernel"]
        plain = jax.vmap(lambda row: ref._mla(p, row, z))(x)
    assert rel_err(got, want) < 1e-5
    assert rel_err(plain, want) < 1e-5


def test_interleaved_rope_is_complex_multiplication():
    """The program's form (evens first, then ``_rope`` on the halves) and the
    reference's (pairs in place) against x_2i + i x_2i+1 times e^(i t w_i):
    the reference's entry for entry, the program's up to its fixed
    permutation, which leaves every score what it was."""
    from horovod_tpu.models.deepseek import _pairs_first
    from horovod_tpu.models.transformer import _rope

    s, r, theta = 12, 8, 32000000.0
    q = jax.random.normal(jax.random.PRNGKey(0), (1, s, 3, r))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, s, 1, r))
    angle = np.arange(s)[:, None] * theta ** (-np.arange(0, r, 2) / r)

    def turned(x):
        z = (np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])) \
            * np.exp(1j * angle)[None, :, None, :]
        return np.stack([z.real, z.imag], axis=-1).reshape(x.shape)

    want_q, want_k = turned(q), turned(k)
    np.testing.assert_allclose(ref._rope_pairs(q[0], theta), want_q[0],
                               atol=1e-5)
    got_q = _rope(_pairs_first(q), theta)
    got_k = _rope(_pairs_first(k), theta)
    np.testing.assert_allclose(got_q, _pairs_first(want_q), atol=1e-5)
    np.testing.assert_allclose(
        jnp.einsum("bqhd,bkgd->bhqk", got_q, got_k),
        np.einsum("bqhd,bkgd->bhqk", want_q, want_k), atol=1e-4)
    # Not the half-rotating form on the same entries.
    assert rel_err(_rope(q, theta), want_q) > 0.1


def parents_block(p, x, cfg):
    """``LatentAttention`` as it ran until PR 49, written out: the
    projections flat, the slice, the evens-then-odds copy of the rows,
    ``_rope``, the two concatenations, then the scale and the transposes of
    ``masked_attention.attention``.  Returns the block's output and q, k, v
    as the kernels received them, ``[b, h, s, .]``."""
    from horovod_tpu.kernels import masked_attention
    from horovod_tpu.models.deepseek import _pairs_first
    from horovod_tpu.models.transformer import _rope

    b, s, _ = x.shape
    h, latent = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)

    def dense(name, rows):
        y = rows.astype(cfg.dtype) @ p[name]["kernel"].astype(cfg.dtype)
        return y + p[name]["bias"].astype(cfg.dtype) if cfg.use_bias else y

    def norm(name, rows):
        return ref._rms_norm(rows.astype(jnp.float32), p[name]["scale"],
                             cfg.norm_eps).astype(cfg.dtype)

    down = dense("kv_a", x)
    q = dense("q_b", norm("q_a_norm", dense("q_a", x))) \
        .reshape(b, s, h, nope + rope)
    kv = dense("kv_b", norm("kv_a_norm", down[..., :latent])) \
        .reshape(b, s, h, nope + dv)
    q_r = _rope(_pairs_first(q[..., nope:]), cfg.rope_theta)
    k_r = _rope(_pairs_first(down[..., None, latent:]), cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, h, rope))], axis=-1)
    v = kv[..., nope:]
    out = masked_attention.einsum(q, k, v, masked_attention.Causal())
    hsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
    return dense("out", out.reshape(b, s, h * dv)), (
        hsd(q * jnp.asarray((nope + rope) ** -0.5, q.dtype)), hsd(k), hsd(v))


def latent_block(model, params, x, monkeypatch):
    """The block's output and q, k, v as it hands them to the attention
    kernels' entry (off the TPU, the einsum in their layout)."""
    from horovod_tpu.kernels import masked_attention
    from horovod_tpu.models.deepseek import LatentAttention

    taken = []
    einsum = masked_attention.einsum_hsd

    def take(q, k, v, rule):
        taken.append((q, k, v))
        return einsum(q, k, v, rule)

    monkeypatch.setattr(masked_attention, "einsum_hsd", take)
    out = LatentAttention(model.cfg).apply({"params": params}, x)
    return out, taken[0]


@pytest.mark.parametrize("what", ["float32-operands", "float32-gradients",
                                  "float32-biases-gradients",
                                  "bfloat16-operands"])
def test_the_operands_built_in_the_kernels_layout_are_the_parents(
        what, monkeypatch):
    """PR 49 moved the interleave from the rows to the weights' rotary
    columns, the products' outputs into ``[b, h, s, .]`` and the rotation,
    the scale and ``[k_nope ; k_r]`` into one pass: q, k and v as the
    kernels receive them, the block's output and the gradients of ``q_b``,
    ``kv_a``, ``kv_b`` and of the block's input are the parent's, in float32
    to its rounding and in bf16 **to the bit** (the same values rounded at
    the same points: q's rotary part once behind the rotation and once
    behind the scale, nothing below bf16), from parameters in the published
    layout; and with biases on the five projections (``use_bias``, which no
    configuration sets), permuted and cut with their columns."""
    from horovod_tpu.models.deepseek import LatentAttention

    dtype = jnp.bfloat16 if what.startswith("bfloat16") else jnp.float32
    model, _ = tiny_model(dtype)
    params = seeded(model)["layer_0"]["attn"]
    if "biases" in what:
        model = model.clone(cfg=dataclasses.replace(model.cfg, use_bias=True))
        params = {name: {**leaves, "bias": 0.3 * jax.random.normal(
            jax.random.PRNGKey(len(name)), leaves["kernel"].shape[1:])}
            if "kernel" in leaves else leaves
            for name, leaves in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 32))
    g = jax.random.normal(jax.random.PRNGKey(3), (2, 20, 32))
    with jax.default_matmul_precision("highest"):
        if what.endswith("operands"):
            got, operands = latent_block(model, params, x, monkeypatch)
            want, parents = parents_block(params, x, model.cfg)
            for a, b in zip(operands, parents):
                assert a.dtype == b.dtype == dtype and a.shape == b.shape
                if dtype == jnp.bfloat16:
                    np.testing.assert_array_equal(
                        np.asarray(a, np.float32), np.asarray(b, np.float32))
                else:
                    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
            # Behind the operands the einsum off the TPU takes q scaled
            # where the parent's scaled the scores: bf16's rounding apart.
            assert got.dtype == want.dtype == dtype
            assert rel_err(got, want) < (1e-6 if dtype == jnp.float32
                                         else 2e-2)
            return
        def through(block):
            return jax.grad(lambda p, x: jnp.sum(block(p, x) * g),
                            argnums=(0, 1))(params, x)

        got = through(lambda p, x: LatentAttention(model.cfg).apply(
            {"params": p}, x))
        want = through(lambda p, x: parents_block(p, x, model.cfg)[0])
    for name in ("q_b", "kv_a", "kv_b", "q_a", "out"):
        for leaf in got[0][name]:
            assert rel_err(got[0][name][leaf], want[0][name][leaf]) < 1e-6
    assert rel_err(got[1], want[1]) < 1e-6


def test_latent_attention_refuses_what_it_does_not_build():
    from horovod_tpu.models.transformer import Transformer

    model, _ = tiny_model()
    for change in (dict(causal=False), dict(num_kv_heads=2),
                   dict(qk_norm="head")):
        with pytest.raises(ValueError, match="latent attention is built"):
            Transformer(dataclasses.replace(model.cfg, **change)).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # Since PR 66 a query without a latent is built (``q_lora_rank`` 0:
    # tests/test_ling.py); a gate that is no column a head is not.
    with pytest.raises(ValueError, match="one column a head"):
        Transformer(dataclasses.replace(model.cfg, attention_gate=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


# -- the shares add up --------------------------------------------------------


def test_the_shares_of_a_sparse_layer_add_up_with_the_shared_expert_once():
    """16 experts over 4 shares of 4: each share routes over all 16 and adds
    the gated shared expert, which every chip computes alike: the shares'
    sums with the shared expert once are the uncut layer, which is the uncut
    reference's."""
    from horovod_tpu.models.transformer import Block, LayerKind

    ids = list(range(16))
    whole, sizes = tiny_model(experts_held=ids, n_routed_experts=16)
    params = seeded(whole)["layer_1"]
    bias = some_bias(sizes)[0]
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 32))
    m = ref._rms_norm(h, params["ln2"]["scale"], sizes["rms_norm_eps"])

    def experts(cfg, p):
        """The FFN's part of a block: a block that is its FFN alone adds it
        to its input."""
        y, _ = Block(cfg, LayerKind(mixer="none", ffn="moe")).apply(
            {"params": p, "moe": {"bias": bias}}, h, mutable=["moe"])
        return y - h

    with jax.default_matmul_precision("highest"):
        want = experts(whole.cfg, params)
        plain, counts = ref._experts(params, bias, m.reshape(40, 32), sizes)
        assert rel_err(want, plain.reshape(2, 20, 32)) < 1e-5
        assert int(counts.sum()) == 40 * 3
        shared = ref._swiglu(m, *(params[f"shared_{n}"]["kernel"]
                                  for n in ("gate", "up", "down")))
        parts = []
        for start in range(0, 16, 4):
            held = tuple(ids[start:start + 4])
            p = {**params, **{k: params[k][start:start + 4] for k in (
                "experts_gate", "experts_up", "experts_down")}}
            cfg = dataclasses.replace(whole.cfg, experts_held=held)
            parts.append(experts(cfg, p) - shared)
    assert rel_err(sum(parts) + shared, want) < 1e-5
    assert rel_err(parts[0] + shared, want) > 0.05
    assert float(jnp.abs(shared).max()) > 0.05 * float(jnp.abs(want).max())


# -- the prediction module ----------------------------------------------------


def test_without_modules_the_model_is_the_stack_alone():
    """Count 0 builds no module, returns the logits alone and lowers to the
    text of a model that never heard of one; with count 1 the next token's
    logits are those same logits."""
    with_module, sizes = tiny_model()
    alone, _ = tiny_model(num_nextn_predict_layers=0)
    assert alone.cfg.mtp_modules == 0 and alone.cfg.num_blocks == 3
    params = seeded(with_module)
    stack = {k: v for k, v in params.items()
             if k not in ("mtp_0", "layer_3")}
    assert jax.tree_util.tree_structure(seeded(alone)) \
        == jax.tree_util.tree_structure(stack)
    tokens, bias = tokens_of(sizes, 1)["tokens"], some_bias(sizes)
    with jax.default_matmul_precision("highest"):
        logits, (ahead,) = apply(with_module, params, bias, tokens)
        only = apply(alone, stack, bias[:2], tokens)
    assert only.shape == logits.shape == ahead.shape == (2, 20, 64)
    np.testing.assert_array_equal(only, logits)

    def text(model, p):
        return jax.jit(lambda p, t: apply(model, p, bias[:2], t)).lower(
            p, tokens).as_text()

    never = dataclasses.replace(alone.cfg, mtp_modules=0)
    from horovod_tpu.models.transformer import Transformer

    assert text(alone, stack) == text(Transformer(never), stack)
    assert "mtp" not in text(alone, stack)


def test_the_module_reads_the_following_token_and_predicts_the_one_behind():
    """Position i of the module reads token i + 1 (changing it moves the
    module's logits at i and nothing of the stack's) and is held to token
    i + 2: the last two positions weigh nothing in its loss, the last one
    in the main one."""
    model, sizes = tiny_model()
    params, bias = seeded(model), some_bias(sizes)
    tokens = tokens_of(sizes, 1, batch=1)["tokens"]
    other = tokens.at[0, 8].set((tokens[0, 8] + 1) % 64)
    with jax.default_matmul_precision("highest"):
        logits, (ahead,) = apply(model, params, bias, tokens)
        logits2, (ahead2,) = apply(model, params, bias, other)
    moved = np.abs(np.asarray(ahead2 - ahead)).max(axis=-1)[0]
    assert moved[7] > 1e-3 and (moved[:7] == 0).all()
    stack_moved = np.abs(np.asarray(logits2 - logits)).max(axis=-1)[0]
    assert (stack_moved[:8] == 0).all() and stack_moved[8] > 1e-3

    loss = program_loss(model, sizes)
    aux = zero_aux(sizes, bias)

    def entropies(t):
        return np.asarray(loss(params, aux, {"tokens": t})[1]["cross_entropy"])

    with jax.default_matmul_precision("highest"):
        base = entropies(tokens)
        # Token 0 is no one's target: position 18's module reads it (rolled
        # in) but has no target, and position 19's next token is none.
        first = entropies(tokens.at[0, 0].set((tokens[0, 0] + 1) % 64))
    assert first[0] != base[0]                 # it is an input of the stack
    manual_main = -np.mean([jax.nn.log_softmax(logits[0, i])[tokens[0, i + 1]]
                            for i in range(19)])
    manual_ahead = -np.mean([jax.nn.log_softmax(ahead[0, i])[tokens[0, i + 2]]
                             for i in range(18)])
    np.testing.assert_allclose(base, [manual_main, manual_ahead], rtol=1e-5)


def test_a_shared_leafs_gradient_is_the_sum_over_its_two_uses():
    """The embedding and the head are one leaf each, read by the stack and by
    the module: the program's gradient of each is the sum of the reference's
    gradients with the module's tables held apart."""
    model, sizes = tiny_model()
    params, batch = seeded(model), tokens_of(sizes, 1)
    aux = zero_aux(sizes, some_bias(sizes))
    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.grad(program_loss(model, sizes), has_aux=True))(
            params, aux, batch)[0]
    tables = {k: params[k] for k in ("embed", "lm_head")}

    def apart(params, tables):
        return ref.loss(params, aux, batch, sizes=sizes, tables=tables)[0]

    of_stack, of_module = jax.jit(jax.grad(apart, argnums=(0, 1)))(
        params, tables)
    for leaf, name in (("embed", "embedding"), ("lm_head", "kernel")):
        stack, module = of_stack[leaf][name], of_module[leaf][name]
        assert float(jnp.abs(module).max()) > 1e-3 * float(
            jnp.abs(stack).max())
        assert rel_err(grads[leaf][name], stack + module) < 2e-5
        assert rel_err(grads[leaf][name], stack) > 1e-3


def test_two_modules_chain():
    """A count of 2: module 2 reads module 1's block's output and token
    i + 2, as the reference's chain does."""
    model, sizes = tiny_model(num_nextn_predict_layers=2)
    params, batch = seeded(model), tokens_of(sizes, 1)
    bias = some_bias(sizes)
    assert model.cfg.expert_layers() == (1, 2, 3, 4)
    with jax.default_matmul_precision("highest"):
        logits, ahead = apply(model, params, bias, batch["tokens"])
    want = ref.logits(params, batch, sizes, bias=bias)
    assert want.shape == (3, 2, 20, 64) and len(ahead) == 2
    assert rel_err(jnp.stack((logits,) + ahead), want) < 1e-5
