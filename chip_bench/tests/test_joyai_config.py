"""``joyai-llm-flash``: FLOPs per sample, the attention kernels' cost and the
parameter count against counts made by hand."""

import json
import os

import jax

from chip_bench import spec

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")


def _load():
    cell_like = spec.Cell.__new__(spec.Cell)
    with open(os.path.join(CONFIGS, "joyai-llm-flash.json")) as f:
        cell_like.sizes = json.load(f)
    cell_like.config_name = "joyai-llm-flash"
    cell_like._config_dir = CONFIGS
    return cell_like.config_module(), cell_like.sizes


def test_macs_by_hand():
    module, sizes = _load()
    s, d = 8192, 2048
    pairs = 6 * s * (s + 1) // 2            # causal, five layers + the module
    by_hand = {
        "mla_down": 6 * s * d * (1536 + 512 + 64),
        "mla_up": 6 * s * (1536 * 32 * 192 + 512 * 32 * 256),
        "mla_out": 6 * s * 32 * 128 * d,
        "attention_scores": pairs * 32 * 192,
        "attention_values": pairs * 32 * 128,
        "dense_ffn": s * 3 * d * 7168,
        "router": 5 * s * d * 256,
        "shared_expert": 5 * s * 3 * d * 768,
        "experts": 5 * s * (8 * 16 / 256) * 3 * d * 768,
        "eh_proj": s * 2 * d * d,
        "head": 2 * s * d * 16160}
    assert module.matmul_macs(sizes) == by_hand
    # A token's multiply-adds, in millions: ISSUE 47's reckoning (566.4).
    per_token = {k: round(v / s / 1e6, 2) for k, v in by_hand.items()}
    assert per_token == {
        "mla_down": 25.95, "mla_up": 81.79, "mla_out": 50.33,
        "attention_scores": 151.01, "attention_values": 100.68,
        "dense_ffn": 44.04, "router": 2.62, "shared_expert": 23.59,
        "experts": 11.8, "eh_proj": 8.39, "head": 66.19}
    # The projections of one block: ISSUE 47's 26.35 M a token.
    assert (by_hand["mla_down"] + by_hand["mla_up"] + by_hand["mla_out"]) \
        // (6 * s) == 26_345_472
    total = sum(by_hand.values())
    assert round(total / s / 1e6, 1) == 566.4
    assert module.flops_per_sample(sizes) == 6 * total
    config = module.Config(sizes)
    assert config.flops_per_sample() == 6 * total
    assert round(config.per_chip_batch * 6 * total / 1e12, 2) == 27.84


def test_mla_attention_cost_by_hand_and_by_loop():
    module, sizes = _load()
    operations, moved = module.mla_attention_cost(sizes)
    pairs = 8192 * 8193 // 2
    assert pairs == 33_558_528
    # 320 multiply-adds forward and 640 backward a pair and head, 2 each.
    assert operations == 2 * (320 + 640) * pairs * 32 * 6 \
        == 12_371_015_761_920
    # q and k at 192, v and the output at 128, and a gradient of each, bf16.
    assert moved == 6 * 2 * 2 * 8192 * 32 * (192 + 192 + 128 + 128) \
        == 4_026_531_840
    # Compute-bound: 62.8 ms at the bf16 peak against 4.9 at 819 GB/s.
    assert round(1e3 * operations / 197e12, 1) == 62.8
    assert operations / 197e12 > 10 * moved / 819e9
    # A count by loop at a small size: every (query, key <= query) pair of
    # every head of every block, the six products written out.
    small = {**sizes, "sequence_length": 12, "num_attention_heads": 3,
             "num_hidden_layers": 2, "layers_held": [0, 1],
             "qk_nope_head_dim": 5, "qk_rope_head_dim": 2, "v_head_dim": 4}
    macs = 0
    for _block in range(2 + 1):
        for _head in range(3):
            for q in range(12):
                for _k in range(q + 1):
                    macs += (5 + 2) + 4                 # score, value
                    macs += 4 + 4 + (5 + 2) + (5 + 2)   # dv, dp, dq, dk
    assert module.mla_attention_cost(small)[0] == 2 * macs


def test_parameters_by_hand():
    module, sizes = _load()
    config = module.Config(sizes)
    params, _ = jax.eval_shape(config.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    d = 2048
    attention = d * 1536 + 1536 + 1536 * 32 * 192 + d * 576 + 512 \
        + 512 * 32 * 256 + 32 * 128 * d
    assert count(params["layer_0"]["attn"]) == attention == 26_347_520
    dense = attention + 2 * d + 3 * d * 7168
    assert count(params["layer_0"]) == dense == 70_391_808
    sparse = attention + 2 * d + d * 256 + 3 * d * 768 + 16 * 3 * d * 768
    assert count(params["layer_1"]) == count(params["layer_5"]) == sparse \
        == 107_091_968
    join = 2 * d * d + 3 * d
    assert count(params["mtp_0"]) == join == 8_394_752
    tables = 2 * 16160 * d
    assert count(params) == dense + 5 * sparse + join + tables + d \
        == 680_439_808
    assert 10.88e9 < 16 * count(params) < 10.89e9
    # Whole: a sparse layer with all 256 experts, the module, the tables.
    whole_sparse = sparse + 240 * 3 * d * 768
    assert whole_sparse == 1_239_554_048
    assert whole_sparse + join == 1_247_948_800
    assert dense + 39 * whole_sparse + 2 * 129280 * d + d == 48_942_532_608
