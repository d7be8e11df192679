"""``nemotron-3-super-120b-a12b``: FLOPs per sample, the scan kernels' cost
and the parameter count against counts made by hand."""

import json
import os

import jax

from chip_bench import spec

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")


def _load():
    cell_like = spec.Cell.__new__(spec.Cell)
    with open(os.path.join(CONFIGS, "nemotron-3-super-120b-a12b.json")) as f:
        cell_like.sizes = json.load(f)
    cell_like.config_name = "nemotron-3-super-120b-a12b"
    cell_like._config_dir = CONFIGS
    return cell_like.config_module(), cell_like.sizes


def _scan_by_hand():
    # A chunk of 128 positions of one group of 16 heads of 64, state 128:
    # C B^T once, a head's causal half of [128, 128] x [128, 64], and its
    # two products with the state.
    chunk = 128 * 128 * 128 + 16 * (128 * 129 // 2 * 64 + 2 * 128 * 128 * 64)
    assert chunk == 44_105_728
    return 8192 // 128 * chunk


def test_macs_by_hand():
    module, sizes = _load()
    s, d = 8192, 4096
    proj = s * d * (1024 + 1024 + 128 + 128 + 16) + s * 1024 * d
    assert proj == s * d * 3344
    qkvo = s * (2 * d * 4 * 128 + 2 * d * 1 * 128)
    pairs = s * (s + 1) // 2                # causal, one attention layer
    by_hand = {
        "mamba_proj": 5 * proj, "mamba_conv": 5 * s * 1280 * 4,
        "mamba_scan": 5 * _scan_by_hand(), "qkvo": qkvo,
        "attention_scores": pairs * 4 * 128,
        "attention_values": pairs * 4 * 128,
        "router": 5 * s * d * 512, "latent": 5 * s * 2 * d * 1024,
        "shared_expert": 5 * s * 2 * d * 5376,
        "experts": 5 * s * (22 * 8 / 512) * 2 * 1024 * 2688,
        "head": s * d * 16384}
    assert module.matmul_macs(sizes) == by_hand
    # A token's multiply-adds, in millions: ISSUE 41's reckoning (429).
    per_token = {k: round(v / s / 1e6, 1) for k, v in by_hand.items()}
    assert per_token == {
        "mamba_proj": 68.5, "mamba_conv": 0.0, "mamba_scan": 1.7, "qkvo": 5.2,
        "attention_scores": 2.1, "attention_values": 2.1, "router": 10.5,
        "latent": 41.9, "shared_expert": 220.2, "experts": 9.5, "head": 67.1}
    total = sum(by_hand.values())
    assert 428e6 < total / s < 430e6
    assert module.flops_per_sample(sizes) == 6 * total
    config = module.Config(sizes)
    assert config.flops_per_sample() == 6 * total
    assert 21.0 < config.per_chip_batch * 6 * total / 1e12 < 21.2   # a step


def test_ssd_scan_cost_by_hand():
    module, sizes = _load()
    operations, moved = module.ssd_scan_cost(sizes)
    assert operations == 6 * 5 * _scan_by_hand() == 84_682_997_760
    s = 8192
    x, bc = 2 * s * 1024, 2 * 2 * s * 128          # bf16
    states = 4 * 64 * 16 * 64 * 128                # fp32, a state a chunk
    small = 4 * 2 * s * 16                         # dt and cum, fp32
    assert states == 33_554_432
    forward = x + bc + x + states + 2 * small
    backward = (2 * x + bc + states + 2 * small) + (x + bc + 2 * small)
    assert moved == 5 * (forward + backward) == 849_346_560
    # Memory-bound: 1.05 ms at 819 GB/s against 0.43 ms at the bf16 peak.
    assert moved / 819e9 > 2 * operations / 197e12


def test_parameters_by_hand():
    module, sizes = _load()
    config = module.Config(sizes)
    params, _ = jax.eval_shape(config.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    d = 4096
    mixer = d * 2320 + 1280 * 4 + 1280 + 3 * 16 + 1024 + 1024 * d + d
    assert count(params["layer_0"]) == mixer == 13_708_592
    attention = d * 512 + d * 256 + 512 * d + d
    assert count(params["layer_7"]) == attention == 5_246_976
    experts = d * 512 + 2 * d * 1024 + 2 * d * 5376 \
        + 8 * 2 * 1024 * 2688 + d
    assert count(params["layer_1"]) == experts == 98_570_240
    head = 2 * 16384 * d + d
    assert count(params) == 5 * mixer + attention + 5 * experts + head \
        == 700_862_960
    assert 11.2e9 < 16 * count(params) < 11.22e9
