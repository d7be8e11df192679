"""SDAR-30B-A3B: block-diffusion attention (the mask rule, the einsum under it
and the pallas kernel in interpret mode), grouped KV heads, per-head QK-norm,
the share of a layer's experts, and the whole model against the plain
reference (``chip_bench/configs/sdar-30b-a3b_reference.py``: float32, a dense masked softmax, one
dense expert at a time under a mask, nothing of ``horovod_tpu``) on seeded
weights at tiny widths.
"""

import functools
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from .helpers import REPO_ROOT, load_reference
from .test_olmoe import dense_top_k, layer_inputs, rel_err

ref = load_reference("sdar-30b-a3b")

TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=8,
            num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
            num_experts_published=8, num_experts=4, experts_held=[1, 3, 4, 6],
            num_experts_per_tok=2, norm_topk_prob=True, vocab_size=128,
            mask_token_id=127, rms_norm_eps=1e-6, rope_theta=1e6,
            block_length=4, load_balancing_loss_weight=0.001,
            sequence_length=16)


def tiny_model(dtype=jnp.float32, **overrides):
    from horovod_tpu.models.transformer import (
        Transformer,
        sdar_30b_a3b_config,
    )

    z = {**TINY, **overrides}
    return Transformer(sdar_30b_a3b_config(
        vocab_size=z["vocab_size"], num_layers=z["num_hidden_layers"],
        num_heads=z["num_attention_heads"],
        num_kv_heads=z["num_key_value_heads"], head_width=z["head_dim"],
        d_model=z["hidden_size"], d_ff=z["moe_intermediate_size"], max_len=64,
        num_experts=z["num_experts_published"],
        experts_per_token=z["num_experts_per_tok"],
        experts_held=None if z["experts_held"] is None
        else tuple(z["experts_held"]),
        block_diffusion=z["block_length"], dtype=dtype)), z


def noised(sizes, seed, batch=2):
    """x_0, one noise level a block, the mask and x_t, as the
    configuration's ``make_batch`` draws them."""
    length, block = sizes["sequence_length"], sizes["block_length"]
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    tokens = jax.random.randint(k[0], (batch, length), 0,
                                sizes["mask_token_id"])
    t = jax.random.uniform(k[1], (batch, length // block), jnp.float32, 1e-3,
                           1.0)
    masked = jax.random.uniform(k[2], (batch, length)) \
        < jnp.repeat(t, block, axis=1)
    return {"tokens": tokens, "t": t, "masked": masked,
            "noisy": jnp.where(masked, sizes["mask_token_id"], tokens)}


def program_loss(model, sizes):
    """The program's model under the loss the reference states."""
    from horovod_tpu.models.transformer import moe_stats

    def loss(params, batch):
        logits, state = model.apply(
            {"params": params},
            jnp.concatenate([batch["noisy"], batch["tokens"]], axis=1),
            mutable=["moe"])
        stats = moe_stats(state["moe"])
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), batch["tokens"])
        weights = batch["masked"] / jnp.repeat(
            batch["t"], sizes["block_length"], axis=1)
        total = jnp.sum(nll * weights) / batch["tokens"].size \
            + sizes["load_balancing_loss_weight"] \
            * jnp.mean(stats.load_balancing_loss)
        return total, (logits, jnp.sum(stats.tokens_per_expert, axis=1))

    return loss


def seeded(model, seed=0):
    return nn.meta.unbox(model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 32), jnp.int32))["params"])


def zero_counters(sizes):
    from horovod_tpu.parallel.moe import moe_counters

    return moe_counters(sizes["num_hidden_layers"],
                        sizes["num_experts_published"], share=True)


# fp32: the two differ in the order of their sums only (measured 3e-7 to
# 1.5e-6).  bf16 against the fp32 reference, as tests/test_olmoe.py argues:
# one bf16 rounding is 2e-3 relative and the values pass some ten of them a
# layer; measured here over the seeds used, logits 6e-3 to 9e-3 of the largest
# logit, loss 2e-5 to 6e-4 (the 1/t weights put much of the loss on a few
# tokens), the worst gradient leaf 3e-2 to 8e-2 where both chose the same
# experts for every position.  The limits are about three times the largest
# measured.
TOLERANCE = {"float32": dict(logits=1e-5, loss=1e-5, grads=1e-5),
             "bfloat16": dict(logits=3e-2, loss=2e-3, grads=2.5e-1)}


@pytest.mark.parametrize("block,dtype,seed", [
    (1, "float32", 0), (4, "float32", 0), (16, "float32", 0),
    (4, "bfloat16", 0), (1, "bfloat16", 1)])
def test_program_agrees_with_the_plain_reference(block, dtype, seed):
    """Logits of the noisy half, loss, router counts, rows held and every
    gradient leaf, at block lengths 1, 4 and L."""
    model, sizes = tiny_model(getattr(jnp, dtype), block_length=block)
    params, batch = seeded(model, seed), noised(sizes, seed + 1)
    with jax.default_matmul_precision("highest"):
        (loss, (logits, counts)), grads = jax.jit(jax.value_and_grad(
            program_loss(model, sizes), has_aux=True))(params, batch)
    (want_loss, aux), want_grads = jax.jit(jax.value_and_grad(
        ref.make_loss(sizes), has_aux=True))(
            params, zero_counters(sizes), batch)
    tol = TOLERANCE[dtype]
    assert logits.shape == (2, 16, 128)
    assert rel_err(logits, ref.logits(params, batch, sizes)) < tol["logits"]
    assert abs(float(loss) - float(want_loss)) < tol["loss"] * float(want_loss)
    np.testing.assert_array_equal(counts, aux["tokens_per_expert"])
    # Two halves of 2 x 16 positions, 2 experts each, held or elsewhere.
    np.testing.assert_array_equal(
        aux["rows_held"] + aux["rows_elsewhere"], [2 * 2 * 32] * 2)
    errs = jax.tree_util.tree_map(rel_err, grads, want_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < tol["grads"], (jax.tree_util.keystr(worst[0]), worst[1])


def test_at_block_length_L_the_noisy_half_is_the_unmasked_model_on_x_t():
    """A known answer: with one block the halves do not see each other, so
    the noisy half's logits are those of the same weights run unmasked on
    x_t alone."""
    import dataclasses

    from horovod_tpu.models.transformer import Transformer

    model, sizes = tiny_model(block_length=16)
    params, batch = seeded(model), noised(sizes, 3)
    both = jnp.concatenate([batch["noisy"], batch["tokens"]], axis=1)
    got = model.apply({"params": params}, both, mutable=["moe"])[0]
    plain = Transformer(dataclasses.replace(model.cfg, block_diffusion=0))
    want = plain.apply({"params": params}, batch["noisy"], mutable=["moe"])[0]
    assert rel_err(got, want) < 1e-5
    # And the clean half matters at block length 4: not the same logits.
    model4, _ = tiny_model(block_length=4)
    got4 = model4.apply({"params": params}, both, mutable=["moe"])[0]
    assert rel_err(got4, want) > 1e-2


@pytest.mark.parametrize("half_len,block", [(16, 1), (16, 4), (16, 16),
                                            (24, 3), (512, 4)])
def test_mask_rule_against_a_brute_force_table(half_len, block):
    """The rule of kernels/blockdiff_attention.py, the reference's own and
    (where the kernel takes the block length) the mask the kernel computes,
    against a table filled pair by pair; L*L + L*b pairs are allowed."""
    from horovod_tpu.kernels import blockdiff_attention as bd

    n = 2 * half_len
    table = np.zeros((n, n), bool)
    for p in range(n if half_len < 100 else 0):
        for r in range(n):
            hp, hr = p // half_len, r // half_len
            bp, br = (p % half_len) // block, (r % half_len) // block
            table[p, r] = (hp == 0 and hr == 0 and br == bp) \
                or (hp == 0 and hr == 1 and br < bp) \
                or (hp == 1 and hr == 1 and br <= bp)
    ids = np.arange(n)
    rule = bd.block_diffusion_mask(ids[:, None], ids[None, :], half_len,
                                   block)
    if half_len < 100:
        np.testing.assert_array_equal(rule, table)
    assert rule.sum() == bd.allowed_pairs(half_len, block) \
        == half_len ** 2 + half_len * block
    np.testing.assert_array_equal(
        np.asarray(ref.may_see(ids[:, None], ids[None, :], half_len, block)),
        rule)
    on_device = bd.block_diffusion_mask(
        jnp.arange(n)[:, None], jnp.arange(n)[None, :], half_len, block)
    np.testing.assert_array_equal(np.asarray(on_device), rule)
    np.testing.assert_array_equal(
        bd.BlockDiffusion(block).allowed(ids[:, None], ids[None, :], n), rule)


def test_kernel_takes_the_cells_shape_and_visits_a_third_of_the_tiles():
    from horovod_tpu.kernels import blockdiff_attention as bd

    from horovod_tpu.kernels import masked_attention as ma
    from horovod_tpu.kernels import masked_attention_bwd

    n = bd.BLOCK
    assert n == max(ma.FWD_TILES) == max(ma.BWD_TILES)
    assert bd.takes(16384, 128, 4) and bd.takes(2 * n, 128, 1)
    assert not bd.takes(32, 16, 4) and not bd.takes(2 * n, 128, 3)
    assert not bd.takes(3 * n, 128, 4)          # a tile across the halves
    half_len = 8192
    tiles = 2 * half_len // n
    visited = masked_attention_bwd.tile_table(
        bd.BlockDiffusion(4), 2 * half_len, n, n)[0].size
    half = tiles // 2
    # noisy-noisy diagonal, noisy-clean and clean-clean lower triangles: at
    # tiles of 1024, 80 of 256 for 64 tiles' worth of allowed pairs.
    assert visited == half + 2 * (half * (half + 1) // 2)
    assert bd.allowed_pairs(half_len, 4) / n ** 2 < visited < 0.32 * tiles ** 2


def test_rules_of_one_shape_and_other_blocks_are_not_equal():
    """The kernels and their table of tiles are cached by the rule: equality
    and hash both go by the block, and the table by the length besides."""
    from horovod_tpu.kernels import blockdiff_attention as bd
    from horovod_tpu.kernels import masked_attention_bwd

    a, same, other = (bd.BlockDiffusion(4), bd.BlockDiffusion(4),
                      bd.BlockDiffusion(8))
    assert a == same and hash(a) == hash(same)
    assert a != other and hash(a) != hash(other)
    table = masked_attention_bwd.tile_table(a, 64, 16, 16)
    assert masked_attention_bwd.tile_table(same, 64, 16, 16)[0] is table[0]
    wider = masked_attention_bwd.tile_table(other, 64, 16, 16)
    assert wider[0] is not table[0]
    assert masked_attention_bwd.tile_table(a, 128, 16, 16)[0].size \
        > table[0].size


def test_kernel_in_interpret_mode_matches_the_einsum_with_grouped_heads():
    """Forward and the three gradients at the kernel's smallest shape (a half
    is one tile), two query heads on one KV head of 128."""
    from horovod_tpu.kernels import blockdiff_attention as bd
    from horovod_tpu.kernels import masked_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, w = (jax.random.normal(k, (1, 2 * bd.BLOCK, 2, 128)) for k in ks[:2])
    k, v = (jax.random.normal(k, (1, 2 * bd.BLOCK, 1, 128)) for k in ks[2:])

    def through(attention):
        return jax.value_and_grad(
            lambda *qkv: jnp.sum(attention(*qkv) * w), argnums=(0, 1, 2))

    with jax.default_matmul_precision("highest"):
        got, got_grads = through(lambda *qkv: masked_attention.attention(
            *qkv, bd.BlockDiffusion(4), interpret=True))(q, k, v)
        want, want_grads = through(lambda *qkv: masked_attention.einsum(
            *qkv, bd.BlockDiffusion(4)))(q, k, v)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    for g, wg in zip(got_grads, want_grads):
        assert rel_err(g, wg) < 1e-5


def test_grouped_kv_heads_equal_the_same_weights_repeated_per_query_head():
    model, sizes = tiny_model()
    params, batch = seeded(model), noised(sizes, 5)
    both = jnp.concatenate([batch["noisy"], batch["tokens"]], axis=1)
    got = model.apply({"params": params}, both, mutable=["moe"])[0]
    wide, _ = tiny_model(num_key_value_heads=8)
    d, dh, group = 64, 16, 4
    repeated = jax.tree_util.tree_map(lambda x: x, params)
    for name in ("layer_0", "layer_1"):
        kv = params[name]["attn"]["kv"]["kernel"].reshape(d, 2, 2, dh)
        attn = dict(repeated[name]["attn"])
        attn["kv"] = {"kernel": jnp.repeat(kv, group, axis=2)
                      .reshape(d, 2 * 8 * dh)}
        repeated[name] = {**repeated[name], "attn": attn}
    want = wide.apply({"params": repeated}, both, mutable=["moe"])[0]
    assert rel_err(got, want) < 1e-5


# -- the share of a layer's experts -------------------------------------------


def dense_share(x, router, gate, up, down, k, held, normalise=True):
    """The held experts' part of the layer in numpy float64: weights
    renormalised over each token's k most probable experts wherever they
    live."""
    _, _, probs = dense_top_k(x, router, gate, up, down, k)
    x, gate, up, down = (np.asarray(a, np.float64)
                         for a in (x, gate, up, down))
    xf = x.reshape(-1, x.shape[-1])
    chosen = np.argsort(-probs, axis=-1, kind="stable")[:, :k]
    top = np.take_along_axis(probs, chosen, axis=-1)
    norm = top.sum(-1) if normalise else np.ones(len(xf))
    y = np.zeros_like(xf)
    for e in held:
        w = np.where((chosen == e).any(-1), probs[:, e], 0.0) / norm
        g = xf @ gate[e]
        y += w[:, None] * (((g / (1 + np.exp(-g))) * (xf @ up[e])) @ down[e])
    return y.reshape(x.shape)


@pytest.mark.parametrize("skew", [0.0, 6.0])
def test_eight_shares_add_up_to_the_uncut_layer(skew):
    """16 experts, 2 on each of 8 chips, top 4 renormalised: every share's
    partial result is its own experts' part, the eight add up to the uncut
    layer, and every share counts the same 16-wide routing.  1024 tokens:
    a first chunk of 640 places, five quarters of the mean share, and 27 of
    128 behind it.  With the skew nearly every token's first experts are 0
    and 1, so share 0 takes three times the rows of its first chunk and ten
    and more of the chunks behind it run."""
    from horovod_tpu.parallel.moe import (
        moe_ffn,
        overflow_reached,
        row_buffer,
        row_quantum,
    )

    x, router, gate, up, down = layer_inputs(7, tokens=512, experts=16,
                                             skew=skew)
    k, n = 4, x.shape[0] * x.shape[1]
    chunks, cap = row_buffer(n * k, 2, 16)
    assert (chunks, cap) == (28, 640)
    assert row_quantum(n * k, 2, 16) == 128
    whole = dense_share(x, router, gate, up, down, k, range(16))
    total, counts = np.zeros_like(whole), None
    for share in range(8):
        held = (2 * share, 2 * share + 1)
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
            if skew:
                assert overflow_reached(counts[0, :2].sum(), cap, 128) >= 10
        np.testing.assert_array_equal(stats.tokens_per_expert, counts)
    np.testing.assert_allclose(total, whole, atol=1e-4)
    assert counts.sum() == n * k


def test_renormalised_weights_with_every_expert_held_take_the_whole_layer():
    """``norm_topk_prob`` alone does not select the share's path: with
    ``held=None`` it is one argument to the router of the whole layer, whose
    result is the uncut layer's and the share path's with every expert
    held."""
    from horovod_tpu.parallel import moe

    x, router, gate, up, down = layer_inputs(3, experts=16)
    k = 4
    with jax.default_matmul_precision("highest"):
        lowered = jax.jit(lambda *a: moe.moe_ffn(
            *a, k=k, dtype=jnp.float32, norm_topk_prob=True)).lower(
                x, router, gate, up, down)
        y, stats = lowered.compile()(x, router, gate, up, down)
        shared, _ = jax.jit(lambda *a: moe.moe_ffn(
            *a, k=k, dtype=jnp.float32, held=tuple(range(16)),
            norm_topk_prob=True))(x, router, gate, up, down)
    assert "cond" not in lowered.as_text()      # no chunk behind the first
    np.testing.assert_allclose(
        y, dense_share(x, router, gate, up, down, k, range(16)), atol=2e-5)
    np.testing.assert_allclose(y, shared, atol=2e-5)
    assert int(stats.tokens_per_expert.sum()) == x.shape[0] * x.shape[1] * k


@pytest.mark.parametrize("skew,normalise", [(0.0, True), (6.0, True),
                                            (0.0, False)])
def test_share_gradients_match_autodiff_of_the_dense_form(skew, normalise):
    """Every operand's gradient through the sort, the chunks (with the skew,
    the recomputed ones too) and the custom gathers, against autodiff of the
    masked dense form."""
    from horovod_tpu.parallel.moe import moe_ffn

    x, router, gate, up, down = layer_inputs(11, experts=16, skew=skew)
    k, held = 4, (0, 5)
    pick = np.asarray(held)
    w = jax.random.normal(jax.random.PRNGKey(1), x.shape)

    def dense(x, router, gate, up, down):
        xf = x.reshape(-1, x.shape[-1])
        probs = jax.nn.softmax(xf @ router, axis=-1)
        top, chosen = jax.lax.top_k(probs, k)
        if normalise:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        y = 0.0
        for i, e in enumerate(held):
            we = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
            y = y + we[:, None] * (
                (jax.nn.silu(xf @ gate[i]) * (xf @ up[i])) @ down[i])
        return jnp.sum(y.reshape(x.shape) * w)

    def program(x, router, gate, up, down):
        y, _ = moe_ffn(x, router, gate, up, down, k=k, dtype=jnp.float32,
                       held=held, norm_topk_prob=normalise)
        return jnp.sum(y * w)

    args = (x, router, gate[pick], up[pick], down[pick])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(program, argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(dense, argnums=(0, 1, 2, 3, 4)))(*args)
    for g, wg in zip(got, want):
        assert rel_err(g, wg) < 2e-5


def _routed_by_hand(picks, n=32, experts=16, d=32, width=16, seed=5):
    """Layer inputs whose first ``experts`` features are the router's logits
    but for a small mix of the others: token t's largest are ``picks(t)``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    logits = np.array(0.3 * jax.random.normal(ks[0], (n, experts)))
    for t in range(n):
        logits[t, list(picks(t))] += 8.0 + np.arange(len(picks(t)))
    x = jnp.concatenate(
        [jnp.asarray(logits, jnp.float32),
         jax.random.normal(ks[1], (n, d - experts))], axis=1)[None]
    router = jnp.concatenate(
        [jnp.eye(experts), 0.05 * jax.random.normal(ks[2], (d - experts,
                                                            experts))])
    gate, up, down = (0.2 * jax.random.normal(key, shape) for key, shape in
                      zip(ks[3:], [(experts, d, width), (experts, d, width),
                                   (experts, width, d)]))
    return x, router, gate, up, down


def _share_case(name):
    """(layer inputs, k, held, what the routing must show): ``places[t, j]``
    is the sorted place of token t's j-th slot where its expert is held, and
    -1 elsewhere."""
    if name == "every_slot_of_a_token_held":
        # Token 0 routes to the four held experts and to no other.
        held = (2, 7, 11, 4)
        inputs = _routed_by_hand(lambda t: held if t == 0
                                 else (0, 1, 3, 2 + t % 3))
        return inputs, 4, held, lambda places, cap: (places[0] >= 0).all()
    if name == "a_token_with_no_slot_held":
        held = (2, 7, 11, 4)
        inputs = _routed_by_hand(lambda t: (0, 1, 3, 5) if t == 9
                                 else (0, 1, 7, 2 + t % 3))
        return inputs, 4, held, lambda places, cap: (places[9] < 0).all() \
            and (places >= 0).any(axis=1).sum() == len(places) - 1
    if name == "a_run_cut_by_a_chunk_boundary":
        # 1024 tokens, top 4 of 16, 2 held: a first chunk of 640 places and
        # chunks of 128 behind it.  With the skew every token's first expert
        # is 0, so expert 0's run of a thousand rows is cut by the first
        # chunk's end and by three more, and a token's row for expert 0 lies
        # in the first chunk and its row for expert 1 in one behind it.
        inputs = layer_inputs(13, tokens=512, experts=16, skew=6.0)
        return inputs, 4, (0, 1), lambda places, cap: cap == 640 and (
            ((places >= 0) & (places < cap)).any(axis=1)
            & (places >= cap).any(axis=1)).any()
    if name == "a_chunk_filled_to_its_last_place":
        # 1024 tokens, top 4 of 16, 2 held: a first chunk of 640 places;
        # tokens 0..319 route to both held experts: 640 rows, and no chunk
        # behind.
        held = (2, 7)
        inputs = _routed_by_hand(lambda t: (2, 7, 0, 1) if t < 320
                                 else (0, 1, 3, 5), n=1024)
        return inputs, 4, held, \
            lambda places, cap: (places >= 0).sum() == cap == 640
    raise ValueError(name)


def _dense_share(held, k, w):
    """``(sum(y * w), y)`` of the held experts' part of the layer, one dense
    expert at a time under a mask, for autodiff: the weights renormalised
    over each token's k most probable experts wherever they live."""
    def dense(x, router, gate, up, down):
        xf = x.reshape(-1, x.shape[-1])
        top, experts = jax.lax.top_k(jax.nn.softmax(xf @ router, axis=-1), k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        y = 0.0
        for i, e in enumerate(held):
            we = jnp.sum(jnp.where(experts == e, top, 0.0), axis=-1)
            y = y + we[:, None] * (
                (jax.nn.silu(xf @ gate[i]) * (xf @ up[i])) @ down[i])
        y = y.reshape(x.shape)
        return jnp.sum(y * w), y

    return dense


@pytest.mark.parametrize("name", [
    "every_slot_of_a_token_held", "a_token_with_no_slot_held",
    "a_run_cut_by_a_chunk_boundary", "a_chunk_filled_to_its_last_place"])
def test_share_brings_each_tokens_rows_back_whatever_its_run(name):
    """The way back to token order sums each token's run of rows among the
    chunk's places: runs of k, of none, cut by the end of a chunk, and in a
    chunk with no unused place; the result and all five gradients against
    autodiff of the dense masked form."""
    from horovod_tpu.parallel.moe import moe_ffn, row_buffer

    (x, router, gate, up, down), k, held, shows = _share_case(name)
    n = x.shape[0] * x.shape[1]
    pick = np.asarray(held)
    _, cap = row_buffer(n * k, len(held), router.shape[1])
    logits = np.asarray(x, np.float64).reshape(n, -1) \
        @ np.asarray(router, np.float64)
    chosen = np.argsort(-logits, axis=-1, kind="stable")[:, :k].reshape(-1)
    local = np.array([held.index(e) if e in held else len(held)
                      for e in chosen])
    places = np.argsort(np.argsort(local, kind="stable"), kind="stable")
    places = np.where(local < len(held), places, -1).reshape(n, k)
    assert shows(places, cap), name
    w = jax.random.normal(jax.random.PRNGKey(1), x.shape)

    def program(x, router, gate, up, down):
        y, _ = moe_ffn(x, router, gate, up, down, k=k, dtype=jnp.float32,
                       held=held, norm_topk_prob=True)
        return jnp.sum(y * w), y

    args = (x, router, gate[pick], up[pick], down[pick])
    with jax.default_matmul_precision("highest"):
        (_, y), got = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        (_, want_y), want = jax.jit(jax.value_and_grad(
            _dense_share(held, k, w), argnums=(0, 1, 2, 3, 4),
            has_aux=True))(*args)
    assert rel_err(y, want_y) < 1e-5
    for g, wg in zip(got, want):
        assert rel_err(g, wg) < 1e-5


# Top 4 of 32, 4 held, 1024 tokens: 4096 slots, a first chunk of 640 places
# (five quarters of the mean share of 512) and 27 chunks of 128 behind it.
_OVERFLOW_HELD, _OVERFLOW_FIRST, _OVERFLOW_QUANTUM = (2, 7, 11, 4), 640, 128


@pytest.mark.parametrize("rows_held", [
    600, _OVERFLOW_FIRST, _OVERFLOW_FIRST + 1,
    _OVERFLOW_FIRST + _OVERFLOW_QUANTUM,
    _OVERFLOW_FIRST + _OVERFLOW_QUANTUM + 1, 4096],
    ids=["below_the_first_chunk", "the_first_chunk_full", "one_row_over",
         "a_quarter_over", "a_quarter_and_a_row_over", "every_slot_held"])
def test_share_runs_the_chunks_its_rows_reach_and_no_more(rows_held):
    """The loop behind the first chunk: the rows held below its size, at it,
    one over, a quarter over, one more, and every routed slot (27 chunks);
    the result and all five gradients in float32 against autodiff of one
    dense expert at a time, and the counter reads the trips the loop made."""
    from horovod_tpu.parallel.moe import (
        count_routing,
        moe_counters,
        moe_ffn,
        publish_routing,
        row_buffer,
        row_quantum,
    )

    held, k, n = _OVERFLOW_HELD, 4, 1024
    whole, rest = divmod(rows_held, k)
    away = (0, 1, 3, 5)

    def picks(t):       # every slot held, `rest` of them, none
        return held if t < whole else \
            held[:rest] + away[rest:] if t == whole else away

    x, router, gate, up, down = _routed_by_hand(picks, n=n, experts=32, d=48)
    assert row_buffer(n * k, len(held), 32) == (28, _OVERFLOW_FIRST)
    assert row_quantum(n * k, len(held), 32) == _OVERFLOW_QUANTUM
    pick = np.asarray(held)
    w = jax.random.normal(jax.random.PRNGKey(1), x.shape)

    def program(x, router, gate, up, down):
        y, stats = moe_ffn(x, router, gate, up, down, k=k, dtype=jnp.float32,
                           held=held, norm_topk_prob=True)
        return jnp.sum(y * w), (y, stats.tokens_per_expert)

    args = (x, router, gate[pick], up[pick], down[pick])
    with jax.default_matmul_precision("highest"):
        (_, (y, counts)), got = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        (_, want_y), want = jax.jit(jax.value_and_grad(
            _dense_share(held, k, w), argnums=(0, 1, 2, 3, 4),
            has_aux=True))(*args)
    assert int(counts.sum()) == n * k
    assert int(counts[0, pick].sum()) == rows_held
    assert rel_err(y, want_y) < 1e-5
    for g, wg in zip(got, want):
        assert rel_err(g, wg) < 1e-5
    counters = jax.jit(lambda c, r: count_routing(
        c, r, held=held, slots=n * k))(
            moe_counters(1, 32, share=True, overflow=True), counts)
    reached = -(-max(rows_held - _OVERFLOW_FIRST, 0) // _OVERFLOW_QUANTUM)
    assert int(counters["overflow_chunks"][0]) == reached
    assert publish_routing(counters)["overflow_chunks_per_step"] == [reached]


def test_each_member_of_the_data_axis_runs_the_chunks_its_own_rows_reach():
    """Under a mesh that binds ``data_axis`` the loop's trip count follows
    each member's own rows: one member's routing is skewed to the held
    experts (11 chunks behind the first), the other's is not (none), and
    loss and gradients are those of the two members by themselves."""
    from jax.sharding import Mesh

    from horovod_tpu.parallel.moe import moe_ffn, overflow_reached

    skewed, router, gate, up, down = layer_inputs(
        7, rows=1, tokens=1024, experts=16, skew=6.0)
    plain = layer_inputs(8, rows=1, tokens=1024, experts=16)[0] - 1.0
    x, held, k = jnp.concatenate([skewed, plain]), (0, 1), 4
    args = (x, router, gate[:2], up[:2], down[:2])

    def loss(*a, axis=None):
        y, stats = moe_ffn(*a, k=k, dtype=jnp.float32, held=held,
                           norm_topk_prob=True, data_axis=axis)
        return jnp.sum(y ** 2), stats.tokens_per_expert

    through = functools.partial(jax.value_and_grad, argnums=(0, 1, 2, 3, 4),
                                has_aux=True)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    with jax.default_matmul_precision("highest"):
        with jax.set_mesh(mesh):
            (got, counts), got_grads = jax.jit(through(
                functools.partial(loss, axis="data")))(*args)
        alone = [jax.jit(through(loss))(x[r:r + 1], *args[1:])
                 for r in range(2)]
    trips = [int(overflow_reached(c[:2].sum(), 640, 128)) for c in counts]
    assert trips[0] >= 10 and trips[1] == 0, trips
    assert abs(float(got) - sum(float(a[0][0]) for a in alone)) \
        < 1e-5 * float(got)
    want_grads = [jnp.concatenate([a[1][0] for a in alone])] + [
        alone[0][1][i] + alone[1][1][i] for i in range(1, 5)]
    for g, wg in zip(got_grads, want_grads):
        assert rel_err(g, wg) < 1e-5


@pytest.mark.parametrize("cell, sizes, d, first, quantum, chunks", [
    ("sdar-30b-a3b", (16384 * 8, 16, 128), 2048, 20480, 4096, 28),
    ("smallthinker-21b-a3b", (16384 * 6, 8, 64), 2560, 15360, 3072, 28),
    ("lfm2-8b-a1b", (16384 * 4, 8, 32), 2048, 20480, 4096, 12)])
def test_row_buffer_at_the_cells_sizes_is_what_the_rows_kernel_takes(
        cell, sizes, d, first, quantum, chunks):
    """(slots, held, experts) of the three share cells: five quarters of
    the mean share and a quarter, both whole multiples of the 128 rows
    ``kernels/rows_to_tokens.py`` multiplies at a time, so neither falls back
    to XLA's scatter-add; the worst routing fits the chunks."""
    from horovod_tpu.kernels import rows_to_tokens as rt
    from horovod_tpu.parallel import moe

    assert moe.row_buffer(*sizes) == (chunks, first)
    assert moe.row_quantum(*sizes) == quantum
    assert first + (chunks - 1) * quantum == sizes[0]
    assert 4 * first == 5 * sizes[0] * sizes[1] // sizes[2]
    for cap in (first, quantum):
        assert cap % 128 == 0 and rt.takes(cap, d, 16384)
    assert int(moe.overflow_reached(sizes[0], first, quantum)) == chunks - 1
    assert int(moe.overflow_reached(first, first, quantum)) == 0


@pytest.mark.parametrize("sizes", [
    (376, 2, 16),        # the quarter is no whole number of rows
    (2048, 2, 16),       # a quarter of 64 rows: no multiple of 128
    (16384, 3, 32),      # 16384 slots are no whole number of 384
    (5120, 4, 5),        # five quarters of the mean are all the slots
    (4096, 16, 16)],     # every expert held
    ids=["fraction", "under_128", "slots_not_in_quarters", "no_room",
         "all_held"])
def test_row_buffer_falls_back_to_one_chunk_of_every_slot(sizes):
    from horovod_tpu.parallel.moe import row_buffer, row_quantum

    assert row_buffer(*sizes) == (1, sizes[0])
    assert row_quantum(*sizes) == 0


def test_share_counters_become_gauges():
    from horovod_tpu.core import metrics
    from horovod_tpu.parallel.moe import (
        count_routing,
        moe_counters,
        publish_routing,
    )

    counters = moe_counters(2, 8, share=True)
    routed = jnp.asarray([[10, 0, 6, 0, 0, 0, 0, 0], [2, 2, 2, 2, 2, 2, 2, 2]])
    for _ in range(2):
        counters = jax.jit(lambda c, r: count_routing(c, r, held=(0, 1)))(
            counters, routed)
    out = publish_routing(counters)
    assert out["rows_held_per_step"] == [10.0, 4.0]
    assert out["rows_elsewhere_share"] == pytest.approx([6 / 16, 12 / 16])
    text = metrics.registry.to_prometheus() if hasattr(
        metrics.registry, "to_prometheus") else ""
    assert "moe_rows_held_per_step" in text or not text
    # Counters made without the share keep their two keys, and a share's
    # its four unless the overflow is asked for.
    plain = count_routing(moe_counters(2, 8), routed)
    assert sorted(plain) == ["steps", "tokens_per_expert"]
    assert sorted(counters) == ["rows_elsewhere", "rows_held", "steps",
                                "tokens_per_expert"]
    assert "overflow_chunks_per_step" not in out
    assert "moe_overflow_chunks_per_step" in metrics.CATALOG


def equations_of(jaxpr, primitive):
    """Every equation of ``primitive`` in ``jaxpr`` and in the jaxprs its
    equations hold."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations_of(inner, primitive)


# -- the configuration --------------------------------------------------------


def _config_module():
    import sys

    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from chip_bench import spec

    cell = spec.Cell("sdar-30b-a3b-wfbp-1chip", root=REPO_ROOT)
    return cell.config_module(), cell.sizes


# JetLM/SDAR-30B-A3B-Chat config.json, copied from the catalog's row.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def test_configuration_keeps_every_published_width():
    module, sizes = _config_module()
    reduced = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert sizes["reduced"] == reduced
    differs = [k for k, v in PUBLISHED.items() if sizes.get(k, "absent") != v]
    assert sorted(differs) == sorted(reduced)
    assert (sizes["num_hidden_layers"], sizes["num_experts"],
            sizes["vocab_size"]) == (4, 16, 18992)
    # The floors: four layers, 8 experts, an eighth of the vocabulary.
    assert sizes["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert sizes[key + "_published"] == PUBLISHED[key]
    assert sizes["experts_held"] == list(range(16))
    assert sizes["mask_token_id"] == sizes["vocab_size"] - 1
    for key in ("source", "assumed", "deployment", "reduced_how"):
        assert sizes[key]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        for row in (r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"):
            assert row["config"] == PUBLISHED
            assert row["source_url"] == sizes["source"]


def test_flops_and_attention_cost_come_from_the_shapes():
    module, sizes = _config_module()
    length, layers = 8192, sizes["num_hidden_layers"]
    pairs = length * length + length * 4
    assert module.allowed_pairs(sizes) == pairs
    macs = module.matmul_macs(sizes)
    per_layer_tera = {k: v / layers / 1e12 for k, v in macs.items()
                      if k != "head"}
    assert per_layer_tera == pytest.approx(
        {"qkvo": 0.309, "attention_scores": 0.275, "attention_values": 0.275,
         "router": 0.004, "experts": 0.077}, abs=0.001)
    assert macs["head"] == 8192 * 2048 * 18992
    # Over the allowed pairs and not the square of 16384 positions.
    assert macs["attention_scores"] == layers * pairs * 32 * 128
    assert macs["attention_scores"] < layers * 16384 ** 2 * 4096 / 3.99
    assert module.flops_per_sample(sizes) == 6 * sum(macs.values())
    operations, moved = module.blockdiff_attention_cost(sizes)
    assert operations == 2 * 6 * pairs * 32 * 128
    assert moved == 2 * 2 * 16384 * 128 * (2 * 32 + 2 * 4)
    # Compute-bound on a v5e: 3.30 T operations against 604 MB a layer.
    assert operations / 197e12 > 10 * moved / 819e9
    model = module.Config(sizes).model
    shapes = nn.meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 16), jnp.int32))["params"])
    count = lambda tree: sum(  # noqa: E731
        x.size for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layer_0"]) == 94_638_336
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == 38_895_616
    assert count(shapes) == 4 * 94_638_336 + 2 * 38_895_616 + 2048
    # Over a quarter of one chip's 16 GB at 16 B a parameter.
    assert count(shapes) * 16 > 7.3e9


def test_batch_and_weights_come_from_the_seed():
    module, sizes = _config_module()
    small = {**sizes, "sequence_length": 64, "num_hidden_layers": 1}
    config = module.Config(small)
    batch = jax.jit(config.make_batch)(jax.random.PRNGKey(3))
    again = jax.jit(config.make_batch)(jax.random.PRNGKey(3))
    assert all(np.array_equal(batch[k], again[k]) for k in batch)
    assert batch["t"].shape == (1, 16) and batch["tokens"].shape == (1, 64)
    assert float(batch["t"].min()) >= 1e-3 and float(batch["t"].max()) <= 1
    assert int(batch["tokens"].max()) < sizes["mask_token_id"]
    np.testing.assert_array_equal(
        batch["noisy"], np.where(batch["masked"], sizes["mask_token_id"],
                                 batch["tokens"]))
    # Weights from the seed too; the embedding at its own width (`assumed`).
    params, aux = jax.jit(config.init)(jax.random.PRNGKey(3))
    assert sizes["embedding_init_std"] == 1.0
    assert float(jnp.std(params["embed"]["embedding"])) == pytest.approx(
        1.0, rel=0.01)
    assert float(jnp.std(params["lm_head"]["kernel"])) == pytest.approx(
        0.02, rel=0.01)
    assert sorted(aux) == ["rows_elsewhere", "rows_held", "steps",
                           "tokens_per_expert"]


TINY_CELL = {
    "module": "sdar-30b-a3b", "attention_bias": False, "head_dim": 16,
    "hidden_size": 64, "moe_intermediate_size": 32,
    "max_position_embeddings": 64, "num_attention_heads": 8,
    "num_key_value_heads": 2, "num_experts": 2, "num_experts_published": 8,
    "experts_held": [0, 1], "num_experts_per_tok": 2,
    "norm_topk_prob": True, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "tie_word_embeddings": False, "vocab_size": 128,
    "mask_token_id": 127, "block_length": 4, "noise_level_min": 0.5,
    "embedding_init_std": 1.0,
    "sequence_length": 32, "per_chip_batch": 2,
    "load_balancing_loss_weight": 0.001, "adamw_learning_rate": 4e-4,
    "warmup_steps": 4, "warmup_start_share": 0.01, "adamw_b1": 0.9,
    "adamw_b2": 0.95, "adamw_eps": 1e-8, "adamw_weight_decay": 0.1,
    "clip_global_norm": 1.0, "logits_rtol": 0.2}


@pytest.mark.parametrize("limit,passes", [(0.2, True), (1e-6, False)])
def test_the_configurations_own_limit_holds_the_logits(limit, passes, capfd):
    """Behind ``_chip_bench_grad`` the program's logits are held to the
    float32 reference's, once, before the reference's first step: inside
    ``logits_rtol`` the reference's gradient comes back, outside it the run
    ends there.  The same error of the reference in a lower precision is
    what the limit is set against; in float32 it is zero."""
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


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model (2 of 8 experts held, so the chunks behind the first are
    in the program, under the step's ``shard_map``) against the plain
    reference's three losses, and the new per-layer metrics left out where
    there is no device op line to read."""
    import sys

    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from chip_bench.tests import rehearse

    names = ("blockdiff_attention_ms_step", "blockdiff_attention_roofline_pct",
             "moe_experts_ms_step", "wfbp_dispatch_ms_step")
    files = {"configs/tiny-sdar.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-sdar-wfbp", "tiny-sdar", "wfbp", 1)], files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-sdar-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    assert r0["per_layer"]["tiny.blockdiff_attention_ms_step"] is None
    assert r0["per_layer"]["tiny.blockdiff_attention_roofline_pct"] is None
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0
