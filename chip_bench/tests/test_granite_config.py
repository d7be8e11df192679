"""``granite-4.0-h-micro``: FLOPs per sample, the scan kernels' cost and the
parameter count against counts made by hand."""

import json
import os

import jax

from chip_bench import spec

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")


def _load():
    cell_like = spec.Cell.__new__(spec.Cell)
    with open(os.path.join(CONFIGS, "granite-4.0-h-micro.json")) as f:
        cell_like.sizes = json.load(f)
    cell_like.config_name = "granite-4.0-h-micro"
    cell_like._config_dir = CONFIGS
    return cell_like.config_module(), cell_like.sizes


def _scan_by_hand():
    # A chunk of 128 positions of one group of 64 heads of 64, state 128:
    # C B^T once, a head's causal half of [128, 128] x [128, 64], and its
    # two products with the state.
    chunk = 128 * 128 * 128 + 64 * (128 * 129 // 2 * 64 + 2 * 128 * 128 * 64)
    assert chunk == 170_131_456
    return 8192 // 128 * chunk


def test_macs_by_hand():
    module, sizes = _load()
    s, d = 8192, 2048
    proj = s * d * (4096 + 4352 + 64) + s * 4096 * d
    assert proj == s * d * 12608
    qkvo = s * (2 * d * 32 * 64 + 2 * d * 8 * 64)
    pairs = s * (s + 1) // 2                # causal, one attention layer
    by_hand = {
        "mamba_proj": 9 * proj, "mamba_conv": 9 * s * 4352 * 4,
        "mamba_scan": 9 * _scan_by_hand(), "qkvo": qkvo,
        "attention_scores": pairs * 32 * 64,
        "attention_values": pairs * 32 * 64,
        "ffn": 10 * s * 3 * d * 8192, "head": s * d * 12544}
    assert module.matmul_macs(sizes) == by_hand
    # A token's multiply-adds, in millions: ISSUE 56's reckoning (801).
    per_token = {k: round(v / s / 1e6, 1) for k, v in by_hand.items()}
    assert per_token == {
        "mamba_proj": 232.4, "mamba_conv": 0.2, "mamba_scan": 12.0,
        "qkvo": 10.5, "attention_scores": 8.4, "attention_values": 8.4,
        "ffn": 503.3, "head": 25.7}
    total = sum(by_hand.values())
    assert 800e6 < total / s < 802e6
    assert module.flops_per_sample(sizes) == 6 * total
    config = module.Config(sizes)
    assert config.flops_per_sample() == 6 * total
    # A step (one sequence): 39.4 T operations, the recomputed forward not
    # counted.
    assert 39.3 < config.per_chip_batch * 6 * total / 1e12 < 39.5


def test_ssd_scan_cost_by_hand():
    module, sizes = _load()
    operations, moved = module.ssd_scan_cost(sizes)
    # The forward kernel twice (the blocks are recomputed), the backward
    # once: 2 + 2 + 4 operations a multiply-add.
    assert operations == 8 * 9 * _scan_by_hand() == 783_965_749_248
    s = 8192
    x, bc = 2 * s * 4096, 2 * 2 * s * 128          # bf16
    states = 4 * 64 * 64 * 64 * 128                # fp32, a state a chunk
    small = 4 * 2 * s * 64                         # dt and cum, fp32
    assert states == 134_217_728
    forward = x + bc + x + states + 2 * small
    backward = (2 * x + bc + states + 2 * small) + (x + bc + 2 * small)
    assert moved == 9 * (2 * forward + backward) == 8_304_721_920
    # Memory-bound: 10.1 ms at 819 GB/s against 4.0 ms at the bf16 peak.
    assert moved / 819e9 > 2 * operations / 197e12
    once = module.ssd_scan_cost({**sizes, "recompute_blocks": False})
    assert once == (6 * 9 * _scan_by_hand(), 9 * (forward + backward))


def test_parameters_by_hand():
    module, sizes = _load()
    config = module.Config(sizes)
    params, aux = jax.eval_shape(config.init, jax.random.PRNGKey(0))
    assert aux == {}

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    d = 2048
    mixer = d * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * d
    assert mixer == 25_847_232
    ffn = 3 * d * 8192
    assert count(params["layer_0"]) == mixer + ffn + 2 * d == 76_182_976
    attention = 2 * d * 2048 + d * 1024
    assert count(params["layer_5"]) == attention + ffn + 2 * d == 60_821_504
    assert "lm_head" not in params                       # tied
    assert count(params) == 9 * 76_182_976 + 60_821_504 + 12544 * d + d \
        == 772_160_448
    assert 12.35e9 < 16 * count(params) < 12.36e9
