"""FLOPs per sample against counts made by hand, and the peaks table."""

import json
import os

import pytest

from chip_bench import peaks, spec

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")


def _load(name):
    cell_like = spec.Cell.__new__(spec.Cell)
    cell_like.sizes = json.load(open(os.path.join(CONFIGS, name + ".json")))
    cell_like.config_name = name
    cell_like._config_dir = CONFIGS
    return cell_like.config_module(), cell_like.sizes


def test_resnet50_forward_macs_by_hand():
    module, sizes = _load("resnet50")
    macs = dict(module.conv_macs(sizes))
    # Stem: 112 x 112 outputs, 7 x 7 x 3 -> 64.
    assert macs["conv_init"] == 112 * 112 * 49 * 3 * 64 == 118_013_952
    # A bottleneck after the first of a stage: 1x1 in->w, 3x3 w->w, 1x1
    # w->4w at 56x56 with w=64 is 51,380,224 + 115,605,504 + 51,380,224, and
    # the same 218,365,952 in every stage because w doubles as h*w quarters.
    later = 218_365_952
    assert sum(macs[f"s0b1.{c}"] for c in ("conv1", "conv2", "conv3")) == later
    # A stage's first block reads the previous stage's map: stage 0's has a
    # 64-wide input (12,845,056 for conv1) and a 64->256 projection.
    first0 = 12_845_056 + 115_605_504 + 51_380_224 + 51_380_224
    # Stages 1-3: conv1 at the old resolution (102,760,448) and a projection
    # at the new (102,760,448).
    first = 102_760_448 + 115_605_504 + 51_380_224 + 102_760_448
    by_hand = (118_013_952 + (first0 + 2 * later) + (first + 3 * later)
               + (first + 5 * later) + (first + 2 * later) + 2048 * 1000)
    assert by_hand == 4_089_184_256           # the familiar 4.09 GMAC
    assert sum(macs.values()) == by_hand
    assert len(macs) == 53 + 1                # 53 convolutions, 1 classifier
    # 2 forward + 4 backward per multiply-add; the stem needs no input
    # gradient, so 2 + 2 there.
    assert module.flops_per_sample(sizes) == \
        6 * by_hand - 2 * 118_013_952 == 24_299_077_632


def test_bert_large_macs_by_hand():
    module, sizes = _load("bert-large")
    s, d, ff, v = 512, 1024, 4096, 30522
    layer = (s * d * 3 * d      # qkv            1,610,612,736
             + 2 * s * s * d    # QK^T and PV      536,870,912
             + s * d * d        # output           536,870,912
             + 2 * s * d * ff)  # FFN            4,294,967,296
    assert layer == 6_979_321_856
    by_hand = 24 * layer + s * d * v
    assert by_hand == 183_506_042_880
    assert sum(module.matmul_macs(sizes).values()) == by_hand
    assert module.flops_per_sample(sizes) == 6 * by_hand == 1_101_036_257_280


def test_published_sizes_are_in_the_files():
    _, r = _load("resnet50")
    assert (r["stage_sizes"], r["num_filters"], r["num_classes"],
            r["image_size"], r["reduced"]) == ([3, 4, 6, 3], 64, 1000, 224, [])
    _, b = _load("bert-large")
    assert (b["num_hidden_layers"], b["hidden_size"],
            b["num_attention_heads"], b["intermediate_size"],
            b["vocab_size"], b["reduced"]) == (24, 1024, 16, 4096, 30522, [])


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peak("TPU v5 lite") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    for kind in ("cpu", "TPU v5", "tpu v5 lite", ""):
        with pytest.raises(ValueError, match="no bf16_flops peak known"):
            peaks.peak(kind)
