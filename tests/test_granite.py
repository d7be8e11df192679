"""granite-4.0-h-micro: layers that are a Mamba-2 mixer of one group, or
attention without positions, each with a dense SwiGLU under two norms, the
four muP scalars, the tied readout over a slice of the vocabulary and blocks
recomputed in the backward pass, against the plain reference
(``chip_bench/configs/granite-4.0-h-micro_reference.py``: float32, the
recurrence a token at a time, nothing of ``horovod_tpu``) on seeded weights
at tiny widths.  ``tests/test_granite_cell.py`` holds the configuration and
its cell.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest

from .helpers import load_reference
from .test_olmoe import rel_err

ref = load_reference("granite-4.0-h-micro")

# Two periods of a short pattern (two mixers to one attention layer), one
# group of four heads, the four scalars at their published values, a tied
# readout.
TINY = dict(
    layer_types=["mamba", "attention", "mamba"] * 2,
    layers_held=[0, 1, 2, 3, 4, 5], num_hidden_layers=6, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
    mamba_d_head=16, mamba_n_groups=1, mamba_d_state=16, mamba_d_conv=4,
    chunk_size=8, shared_intermediate_size=48, vocab_size=64,
    rms_norm_eps=1e-5, rope_theta=10000, embedding_multiplier=12,
    attention_multiplier=0.015625, residual_multiplier=0.22,
    logits_scaling=8, sequence_length=20)
SCALARS = ("embedding_multiplier", "residual_multiplier",
           "attention_multiplier", "logits_scaling")


def tiny_model(dtype=jnp.float32, **overrides):
    from horovod_tpu.models.transformer import (
        LayerKind,
        Transformer,
        granite_4_0_h_micro_config,
    )

    z = {**TINY, **{k: v for k, v in overrides.items() if k in TINY}}
    mixers = {"mamba": "mamba2", "attention": "attention"}
    cfg = granite_4_0_h_micro_config(
        vocab_size=z["vocab_size"], num_layers=z["num_hidden_layers"],
        num_heads=z["num_attention_heads"],
        num_kv_heads=z["num_key_value_heads"],
        head_width=z["hidden_size"] // z["num_attention_heads"],
        d_model=z["hidden_size"], d_ff_dense=z["shared_intermediate_size"],
        max_len=64, mamba_heads=z["mamba_n_heads"],
        mamba_head_dim=z["mamba_d_head"], mamba_groups=z["mamba_n_groups"],
        mamba_state=z["mamba_d_state"], mamba_chunk=z["chunk_size"],
        embedding_multiplier=float(z["embedding_multiplier"]),
        residual_multiplier=float(z["residual_multiplier"]),
        attention_multiplier=float(z["attention_multiplier"]),
        logits_scaling=float(z["logits_scaling"]),
        layer_pattern=tuple(LayerKind(0, False, mixers[k], "dense")
                            for k in ref.layer_plan(z)), dtype=dtype)
    extra = {k: v for k, v in overrides.items() if k not in TINY}
    return Transformer(dataclasses.replace(cfg, **extra)), z


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

    def scaled(path, x):
        last = getattr(path[-1], "key", None)
        return x * by if last == "kernel" else x

    return jax.tree_util.tree_map_with_path(scaled, params)


def program_loss(model):
    def loss(params, aux, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        logits = model.apply({"params": params}, tokens)
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1))
        return jnp.sum(nll * (jnp.arange(s) < s - 1)) / (b * (s - 1)), aux

    return loss


def _worst(got, want):
    """The largest relative error over the leaves of two trees, by name."""
    errors = jax.tree_util.tree_map(rel_err, got, want)
    flat = jax.tree_util.tree_leaves_with_path(errors)
    path, worst = max(flat, key=lambda kv: kv[1])
    return float(worst), jax.tree_util.keystr(path)


# -- the whole model ----------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, dict(logits=1e-5, loss=1e-6, grads=5e-5)),
    (jnp.bfloat16, dict(logits=5e-2, loss=5e-3, grads=0.3))],
    ids=["float32", "bfloat16"])
def test_transformer_under_the_pattern_matches_the_reference(dtype, tol):
    """Logits, loss and gradients of the program's model against the plain
    reference's on the same seeded weights: in float32 to float32's
    rounding, in bf16 to bf16's."""
    model, z = tiny_model(dtype)
    params, batch = seeded(model), tokens_of(z, 1)
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, batch["tokens"])
        want = ref.logits(params, batch, z)
        assert got.shape == (2, z["sequence_length"], z["vocab_size"])
        assert rel_err(got, want) < tol["logits"]
        (loss, _), grads = jax.value_and_grad(
            program_loss(model), has_aux=True)(params, {}, batch)
        (want_loss, _), want_grads = jax.value_and_grad(
            ref.make_loss(z), has_aux=True)(params, {}, batch)
    assert abs(float(loss) - float(want_loss)) < tol["loss"] * float(want_loss)
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(want_grads)
    worst, where = _worst(grads, want_grads)
    assert worst < tol["grads"], where


@pytest.mark.parametrize("scalar", SCALARS)
def test_each_scalar_planted_wrong_fails(scalar):
    """The program with one of the four scalars at its default (the value
    every other configuration runs) lies far from the reference at the
    published values, where the program at the published values lies at
    float32's rounding; and the reference's own fault of that name lies as
    far from it."""
    right, z = tiny_model()
    params, batch = seeded(right), tokens_of(z, 2)
    default = {f.name: f.default for f in dataclasses.fields(right.cfg)}
    wrong = nn.Module.clone(right, cfg=dataclasses.replace(
        right.cfg, **{scalar: default[scalar]}))
    fault = {"attention_multiplier": "scores_over_sqrt_head"}.get(
        scalar, "no_" + scalar)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(params, batch, z)
        assert rel_err(right.apply({"params": params}, batch["tokens"]),
                       want) < 1e-5
        planted = rel_err(wrong.apply({"params": params}, batch["tokens"]),
                          want)
        assert planted > 1e-2, planted
        # The same fault in the reference is the same model.
        assert rel_err(wrong.apply({"params": params}, batch["tokens"]),
                       ref.logits(params, batch, z, wrong=(fault,))) < 1e-5


@pytest.mark.parametrize("fault", ["norm_before_gate", "up_as_gate",
                                   "decay_without_dt", "rope"])
def test_a_wrong_layer_of_the_reference_shows(fault):
    model, z = tiny_model()
    params, batch = seeded(model), tokens_of(z, 3)
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, batch["tokens"])
        assert rel_err(got, ref.logits(params, batch, z,
                                       wrong=(fault,))) > 1e-3


def test_recomputed_blocks_give_the_same_loss_and_gradients():
    """``remat`` on and off: the same parameter tree, the same loss and the
    same gradients (the second forward computes what the first did), and the
    recomputed forward is in the lowered step under JAX's name for it."""
    plain, z = tiny_model()
    again, _ = tiny_model(remat=True)
    params, batch = seeded(plain), tokens_of(z, 4)
    assert jax.tree_util.tree_structure(params) \
        == jax.tree_util.tree_structure(seeded(again))
    with jax.default_matmul_precision("highest"):
        steps = [jax.jit(jax.value_and_grad(program_loss(m), has_aux=True))
                 for m in (plain, again)]
        (loss, _), grads = steps[0](params, {}, batch)
        (loss_again, _), grads_again = steps[1](params, {}, batch)
    assert abs(float(loss) - float(loss_again)) < 1e-6 * float(loss)
    worst, where = _worst(grads_again, grads)
    assert worst < 1e-5, where
    text = [s.lower(params, {}, batch).as_text(debug_info=True)
            for s in steps]
    assert "rematted_computation" not in text[0]
    assert "rematted_computation" in text[1]


def test_the_vocabulary_slices_side_by_side_are_the_whole_model():
    """Eight chips share the vocabulary: each holds an eighth of the rows of
    the tied embedding and reads out over them.  With tokens drawn from slice
    0 (the cell's traffic) the chip that holds it is the cell's model: its
    logits are the whole model's first eighth, and the other seven slices'
    readouts of the same final state, side by side with it, are the whole
    model's logits."""
    whole, z = tiny_model()
    params = seeded(whole)
    table = params["embed"]["embedding"]
    rows = z["vocab_size"] // 8
    tokens = jax.random.randint(
        jax.random.PRNGKey(5), (2, z["sequence_length"]), 0, rows)
    held, held_sizes = tiny_model(vocab_size=rows)
    held_params = {**params, "embed": {"embedding": table[:rows]}}
    with jax.default_matmul_precision("highest"):
        want = whole.apply({"params": params}, tokens)
        first = held.apply({"params": held_params}, tokens)
        # The state every slice reads out of, by the reference's layers
        # (which the model matches to float32's rounding, above).
        state = ref._rms_norm(
            ref.hidden_states(held_params, tokens, held_sizes),
            params["ln_f"]["scale"], z["rms_norm_eps"])
        others = [state @ table[j * rows:(j + 1) * rows].T
                  / z["logits_scaling"] for j in range(1, 8)]
    assert rel_err(first, want[..., :rows]) < 1e-5
    assert rel_err(jnp.concatenate([first] + others, axis=-1), want) < 1e-5


def test_parameter_counts_at_published_widths():
    """772,160,448 parameters held (layers 0-9, 12,544 ids) and 3,191,396,096
    whole, by ``jax.eval_shape`` of the program's model at published widths."""
    from horovod_tpu.models.transformer import (
        Transformer,
        granite_4_0_h_micro_config,
    )

    def count(cfg):
        shapes = jax.eval_shape(
            Transformer(cfg).init, jax.random.PRNGKey(0),
            jnp.zeros((1, 128), jnp.int32))["params"]
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes))

    assert count(granite_4_0_h_micro_config()) == 3_191_396_096
    assert count(granite_4_0_h_micro_config(
        num_layers=10, vocab_size=12544)) == 772_160_448


def test_one_group_is_no_share():
    """With one group the gated norm runs over all the mixer's channels and
    every head reads the same B and C: ``share_of`` has one share to give,
    the whole mixer's, and half the heads are no part of the sum."""
    from horovod_tpu.models import mamba2

    model, z = tiny_model()
    cfg = model.cfg
    assert mamba2.sizes(cfg)[:2] == (1, z["mamba_n_heads"])
    params = seeded(model)["layer_0"]["mamba"]
    whole = mamba2.share_of(params, cfg, (0,))
    for got, want in zip(jax.tree_util.tree_leaves(whole),
                         jax.tree_util.tree_leaves(params)):
        assert got.shape == want.shape and bool(jnp.all(got == want))
    with pytest.raises(ValueError):
        mamba2.sizes(dataclasses.replace(cfg, mamba_groups=3))
