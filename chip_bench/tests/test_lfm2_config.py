"""``lfm2-8b-a1b``: FLOPs per sample, the convolution kernels' cost and the
parameter count against counts made by hand."""

import json
import os

import jax
import jax.numpy as jnp

from chip_bench import spec

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")


def _load():
    cell_like = spec.Cell.__new__(spec.Cell)
    with open(os.path.join(CONFIGS, "lfm2-8b-a1b.json")) as f:
        cell_like.sizes = json.load(f)
    cell_like.config_name = "lfm2-8b-a1b"
    cell_like._config_dir = CONFIGS
    return cell_like.config_module(), cell_like.sizes


def test_macs_by_hand():
    module, sizes = _load()
    s, d = 8192, 2048
    conv = s * (d * 3 * d + d * d)          # in and out projections
    assert conv == 137_438_953_472
    taps = s * d * 3
    qkvo = s * (2 * d * 32 * 64 + 2 * d * 8 * 64)
    pairs = s * (s + 1) // 2                # causal, one attention layer
    dense = s * 3 * d * 7168
    experts = s * (4 * 8 / 32) * 3 * d * 1792   # one row a position
    router = s * d * 32
    head = s * d * 16384
    by_hand = {"conv_proj": 4 * conv, "conv_taps": 4 * taps, "qkvo": qkvo,
               "attention_scores": pairs * 32 * 64,
               "attention_values": pairs * 32 * 64, "dense_ffn": dense,
               "router": 4 * router, "experts": 4 * experts, "head": head}
    assert module.matmul_macs(sizes) == by_hand
    # A token's multiply-adds, in millions: ISSUE 38's reckoning.
    per_token = {k: round(v / s / 1e6, 1) for k, v in by_hand.items()}
    assert per_token == {
        "conv_proj": 67.1, "conv_taps": 0.0, "qkvo": 10.5,
        "attention_scores": 8.4, "attention_values": 8.4, "dense_ffn": 44.0,
        "router": 0.3, "experts": 44.0, "head": 33.6}
    total = sum(by_hand.values())
    assert module.flops_per_sample(sizes) == 6 * total
    assert 1.29e9 < 6 * total / s < 1.31e9          # 1.3 GFLOP a token
    config = module.Config(sizes)
    assert config.flops_per_sample() == 6 * total
    assert config.per_chip_batch * 6 * total / 1e12 > 21    # a step


def test_short_conv_cost_by_hand():
    module, sizes = _load()
    operations, moved = module.short_conv_cost(sizes)
    s, d = 8192, 2048
    # Forward 3d in and d out, backward 3d + d in and 3d out, in bf16: 22 d
    # bytes a token and layer = 44 KB at this width.
    assert moved == 4 * s * d * 2 * 11 and moved // (4 * s) == 45_056
    assert operations == 4 * s * d * (8 + 24)
    # Memory-bound on a v5e by far: 1.8 ms of bytes against 22 us of
    # operations a sequence.
    assert moved / 819e9 > 50 * operations / 197e12
    assert 1.7e-3 < moved / 819e9 < 1.9e-3


def test_parameters_by_hand():
    import flax.linen as nn

    module, sizes = _load()
    config = module.Config(sizes)
    shapes, aux = jax.eval_shape(config.init, jax.random.PRNGKey(0))
    shapes = nn.meta.unbox(shapes)
    count = lambda tree: sum(  # noqa: E731
        x.size for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layer_0"]["conv"]) == 16_783_360
    assert count(shapes["layer_1"]["attn"]) == 10_485_888
    experts = sum(count(shapes["layer_1"][n]) for n in (
        "experts_gate", "experts_up", "experts_down"))
    assert experts == 88_080_384
    assert count(shapes["layer_1"]["router"]) == 65_536
    assert sum(count(shapes["layer_0"][n]) for n in (
        "ffn_gate", "ffn_up", "ffn_down")) == 44_040_192
    assert count(shapes["layer_0"]) == 60_827_648
    assert count(shapes["layer_1"]) == 98_635_904
    assert [count(shapes[f"layer_{i}"]) for i in (2, 3, 4)] \
        == [104_933_376] * 3
    assert count(shapes["embed"]) == 33_554_432 and "lm_head" not in shapes
    assert count(shapes) == 507_820_160
    # 8.13 GB at 16 B a parameter: over a quarter of one chip's 16 GB.
    assert 8.12e9 < count(shapes) * 16 < 8.13e9
    assert all(x.dtype == jnp.float32
               for x in jax.tree_util.tree_leaves(shapes))
    assert aux["expert_bias"].shape == (4, 32)
