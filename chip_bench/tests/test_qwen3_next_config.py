"""``qwen3-next-80b-a3b``: FLOPs per sample, the delta rule's kernels' cost
and the parameter count against counts made by hand."""

import json
import os

import jax

from chip_bench import spec

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")


def _load():
    cell_like = spec.Cell.__new__(spec.Cell)
    with open(os.path.join(CONFIGS, "qwen3-next-80b-a3b.json")) as f:
        cell_like.sizes = json.load(f)
    cell_like.config_name = "qwen3-next-80b-a3b"
    cell_like._config_dir = CONFIGS
    return cell_like.config_module(), cell_like.sizes


def _rule_by_loop(s, c, hk, hv, dk, dv):
    """The chunked rule's multiply-adds counted product by product."""
    total = 0
    for _ in range(s // c):
        for _ in range(hk):
            total += c * c * dk             # k k^T
            total += c * c * dk             # q k^T
        for _ in range(hv):
            total += c * c * dv             # U = T (beta v)
            total += c * c * dk             # W = T (beta k exp(gamma))
            total += c * dk * dv            # W S
            total += c * dk * dv            # (q exp(gamma)) S
            total += c * c * dv             # tril(q k^T D) V'
            total += dk * c * dv            # the state's writes
    return total


def test_macs_by_hand():
    module, sizes = _load()
    s, d = 8192, 2048
    rule = _rule_by_loop(s, 64, 16, 32, 128, 128)
    assert rule == s * (16 * 2 * 64 * 128 + 32 * 73_728) == 21_474_836_480
    by_hand = {
        "gdn_proj": 3 * s * (d * 12288 + d * 64 + 4096 * d),
        "gdn_conv": 3 * s * 8192 * 4, "gdn_rule": 3 * rule,
        "qkvo": s * (d * 8192 + 2 * d * 512 + 4096 * d),
        "attention_scores": s * (s + 1) // 2 * 16 * 256,
        "attention_values": s * (s + 1) // 2 * 16 * 256,
        "router": 4 * s * d * 512,
        "shared_expert": 4 * s * (3 * d * 512 + d),
        "experts": 4 * s * (10 * 16 / 512) * 3 * d * 512,
        "head": s * d * 18992}
    assert module.matmul_macs(sizes) == by_hand
    # A token's multiply-adds, in millions: ISSUE 50's reckoning (234.0 with
    # 32 experts held, the rule there with k k^T and q k^T a value head: 2.9
    # a layer for 2.6; 16 held halve the experts' 7.86).
    per_token = {k: round(v / s / 1e6, 2) for k, v in by_hand.items()}
    assert per_token == {
        "gdn_proj": 101.06, "gdn_conv": 0.1, "gdn_rule": 7.86, "qkvo": 27.26,
        "attention_scores": 16.78, "attention_values": 16.78, "router": 4.19,
        "shared_expert": 12.59, "experts": 3.93, "head": 38.9}
    total = sum(by_hand.values())
    assert 229e6 < total / s < 230e6
    assert module.flops_per_sample(sizes) == 6 * total
    config = module.Config(sizes)
    assert config.flops_per_sample() == 6 * total
    assert 11.2 < config.per_chip_batch * 6 * total / 1e12 < 11.3   # a step


def test_gated_delta_cost_by_hand():
    module, sizes = _load()
    operations, moved = module.gated_delta_cost(sizes)
    assert operations == 6 * 3 * _rule_by_loop(8192, 64, 16, 32, 128, 128) \
        == 386_547_056_640
    small = {**sizes, "sequence_length": 128, "linear_num_key_heads": 2,
             "linear_num_value_heads": 4, "layers_held": [0],
             "num_hidden_layers": 1}
    assert module.gated_delta_cost(small)[0] \
        == 6 * _rule_by_loop(128, 64, 2, 4, 128, 128)
    s = 8192
    qk, v = 2 * 2 * s * 2048, 2 * s * 4096         # bf16
    small = 2 * 4 * s * 32                         # g and beta, fp32
    states = 4 * 128 * 32 * 128 * 128              # fp32, a state a chunk
    assert states == 268_435_456
    forward = (qk + v + small) + (v + states)
    backward = (qk + v + small + states + v) + (qk + v + small)
    assert moved == 3 * (forward + backward) == 3 * 1_080_033_280
    # Memory-bound: 3.96 ms at 819 GB/s against 1.96 ms at the bf16 peak.
    assert moved / 819e9 > 2 * operations / 197e12


def test_parameters_by_hand():
    module, sizes = _load()
    config = module.Config(sizes)
    params, _ = jax.eval_shape(config.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    d = 2048
    delta = d * 12288 + d * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * d
    assert count(params["layer_0"]["gdn"]) == delta == 33_718_464
    attention = d * 8192 + 2 * d * 512 + 4096 * d + 2 * 256
    assert count(params["layer_3"]["attn"]) == attention == 27_263_488
    ffn = d * 512 + 3 * d * 512 + d + 16 * 3 * d * 512
    assert ffn == 4_196_352 + 50_331_648
    assert count(params["layer_1"]) == delta + ffn + 2 * d == 88_250_560
    assert count(params["layer_3"]) == attention + ffn + 2 * d == 81_795_584
    head = 2 * 18992 * d + d
    assert count(params) == 3 * 88_250_560 + 81_795_584 + head \
        == 424_340_544
    assert 6.78e9 < 16 * count(params) < 6.8e9
    for number in ("33,718,464", "27,263,488", "50,331,648", "77,791,232",
                   "424,340,544", "625,667,136", "79,674,391,296"):
        assert number in sizes["reduced_how"], number
