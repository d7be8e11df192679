"""ling-3.0-flash-vl's configuration and cell
(``chip_bench/configs/ling-3.0-flash-vl``): the published widths and the cut,
the counts from shapes, data and weights from the seed, the configuration's
own limits on the logits, the recomputed blocks through
``hvd.make_overlapped_train_step`` and the cell through the harness at a tiny
size.  ``tests/test_ling.py`` holds the model and its layers; the two are
apart so that the test workers can share them.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .helpers import REPO_ROOT
from .test_ling import tiny_sizes

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

CELL = "ling-3.0-flash-vl-wfbp-1chip"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]
NEW_METRICS = ("kda_ms_step", "kda_roofline_pct")
SHARED_METRICS = ("step_ms_p95.observed", "wfbp_dispatch_ms_step",
                  "moe_experts_ms_step", "moe_rows_to_tokens_ms_step",
                  "mla_attention_ms_step", "mla_attention_roofline_pct",
                  "causal_conv_ms_step", "recompute_ms_step", "mfu_pct")


def _config_module():
    from chip_bench import spec

    cell = spec.Cell(CELL, root=REPO_ROOT)
    return cell.config_module(), cell.sizes


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog of architectures here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if r["name"] == "Ling-3.0-flash-VL"][0]


def test_configuration_keeps_every_published_width():
    _, sizes = _config_module()
    row = _catalog_row()
    published = row["config"]
    assert row["source_url"] == sizes["source"]
    assert sizes["reduced"] == REDUCED
    differs = [k for k, v in published.items()
               if sizes.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED)
    assert [sizes[k] for k in REDUCED] == [7, 1, 8, 19648]
    for key in REDUCED:
        assert sizes[key + "_published"] == published[key]
    assert sizes["layers_held"] == [1, 2, 3, 4, 5, 6, 7]
    assert sizes["experts_held"] == list(range(8))
    assert sizes["vocab_size"] * 8 == published["vocab_size"]
    # No width among the cuts; the clamps' lists whole.
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "kv_lora_rank", "q_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "rotary_dim", "short_conv_kernel_size", "kda_lower_bound",
                "n_group", "topk_group", "layer_group_size",
                "routed_scaling_factor", "expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        assert sizes[key] == published[key], key
    assert len(sizes["expert_swiglu_limit_list"]) == 42
    assert sizes["recompute_blocks"] is True
    assert "64 chips" in sizes["deployment"] and "8" in sizes["deployment"]
    for key in ("recomputed", "fit", "reduced_how"):
        assert len(sizes[key]) > 200, key
    for key in ("block", "kda", "kda_gates", "latent_attention", "rotary",
                "experts", "unbuilt_keys", "init", "optimizer", "precision",
                "sequence", "left_out", "reference_limits"):
        assert len(sizes["assumed"][key]) > 100, key


def test_benchmark_json_names_the_cell_and_its_files():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = [c for c in bench["configs"] if c["name"] == "ling-3.0-flash-vl"]
    assert len(config) == 1 and config[0]["reduced"] == REDUCED
    assert os.path.exists(os.path.join(REPO_ROOT, config[0]["file"]))
    for suffix in (".py", "_reference.py"):
        assert os.path.exists(os.path.join(
            REPO_ROOT, config[0]["file"].replace(".json", suffix)))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": "ling-3.0-flash-vl",
                    "traffic": "wfbp", "chips": 1, "why": cell["why"]}
    # The exact counts are the newest configuration's
    # (tests/test_keye_cell.py).
    assert len(bench["configs"]) >= 13 and len(bench["workloads"]) >= 15
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in SHARED_METRICS:
        assert CELL in metrics[name]["workloads"], name
    names = [m["name"] for m in bench["per_layer"]]
    # PR 67 appended the row kernels' two (tests/test_head_rows.py), and
    # later cells' metrics stand behind those.
    at = names.index(NEW_METRICS[0])
    assert names[at:at + 4] == list(NEW_METRICS) + ["head_rows_ms_step",
                                                    "head_rows_calls_step"]
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["layer"] == "kernel"
        assert metrics[name]["moves"] == "samples_per_s_chip"
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics",
                               name + ".json")) as f:
            assert json.load(f)["name"] == name
    # 2 + 14 runs a cell of run_seconds + 60, 180 s more a cell, 1200 spare.
    runs = 2 + 14 * len(bench["workloads"])
    assert runs * (bench["run_seconds"] + 60) \
        + 180 * len(bench["workloads"]) + 1200 < 43200


def test_flops_and_costs_from_shapes():
    """A sample's multiply-adds by hand, and the two kernels' costs: the
    rule's and the attention's as the step runs them, with the recomputed
    forward's call (the rule memory-bound: 14.12 GB a step)."""
    module, sizes = _config_module()
    s, d, h, inner = 8192, 2560, 32, 4096
    causal = s * (s + 1) // 2
    rule = (s // 64) * h * (2 * 64 * 64 * 128 + 64 * 64 * 256
                            + 2 * 64 * 128 * 128 + 64 * 64 * 128
                            + 64 * 128 * 128)
    by_hand = {
        "kda_proj": 6 * s * d * (6 * inner + h),
        "kda_conv": 6 * s * 3 * inner * 4,
        "kda_rule": 6 * rule,
        "mla_q": s * d * h * 192,
        "mla_down": s * d * 576,
        "mla_up": s * 512 * h * 256,
        "mla_gate_out": s * d * h * 129,
        "attention_scores": causal * h * 192,
        "attention_values": causal * h * 128,
        "dense_ffn": s * 3 * d * 6144,
        "router": 6 * s * d * 512,
        "shared_expert": 6 * s * 3 * d * 768,
        "experts": 6 * s * (8 * 8 / 512) * 3 * d * 768,
        "head": s * d * 19648,
    }
    assert module.matmul_macs(sizes) == by_hand
    assert module.flops_per_sample(sizes) == 6.0 * sum(by_hand.values())
    assert module.flops_per_sample(sizes) == pytest.approx(29.9e12, rel=2e-2)
    assert module.Config(sizes).flops_per_sample() \
        == module.flops_per_sample(sizes)
    operations, moved = module.kda_cost(sizes)
    assert operations == 6 * 8 * rule
    wide, decays, states = 2 * s * inner, 4 * s * inner, 4 * 128 * h * 128 ** 2
    forward = 4 * wide + decays + 4 * s * h + states
    backward = 7 * wide + 2 * decays + 2 * 4 * s * h + states
    assert moved == 6 * (2 * forward + backward)
    assert moved == pytest.approx(14.12e9, rel=2e-3)
    assert operations / 197e12 < moved / 819e9          # the bytes bind
    once = module.kda_cost({**sizes, "recompute_blocks": False})
    assert once == (6 * 6 * rule, 6 * (forward + backward))
    operations, moved = module.mla_attention_cost(sizes)
    assert operations == 2 * 4 * causal * h * 320
    assert moved == 3 * 2 * s * h * 640
    assert module.mla_attention_cost(
        {**sizes, "recompute_blocks": False})[0] == 2 * 3 * causal * h * 320


def test_the_model_is_the_presets_at_the_cut():
    from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
    from horovod_tpu.models.transformer import ling_3_0_flash_config

    module, sizes = _config_module()
    config = module.Config(sizes)
    cfg, whole = config.model.cfg, ling_3_0_flash_config()
    differs = {f for f in cfg.__dataclass_fields__
               if getattr(cfg, f) != getattr(whole, f)}
    assert differs == {"num_layers", "vocab_size", "experts_held",
                       "layer_pattern", "remat", "moe_data_axis"}
    assert cfg.layer_pattern == whole.layer_pattern[1:8]
    assert cfg.remat and cfg.moe_data_axis == PROCESS_AXIS
    assert cfg.expert_layers() == (1, 2, 3, 4, 5, 6)
    assert module.layer_plan(sizes) == [("K", True)] + [("K", False)] * 3 \
        + [("*", False)] + [("K", False)] * 2
    shapes, aux = jax.eval_shape(config.init, jax.random.PRNGKey(3))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == sizes["parameters"] == 884_456_384
    kda = shapes["layer_0"]["kda"]
    assert kda["in_proj"]["kernel"].shape == (2560, 5 * 4096)
    assert kda["beta_proj"]["kernel"].shape == (2560, 32)
    assert kda["conv"].shape == (3 * 4096, 4)
    assert kda["A_log"].shape == (32,) and kda["dt_bias"].shape == (4096,)
    assert kda["norm"].shape == (128,)
    assert sum(x.size for x in jax.tree_util.tree_leaves(kda)) == 63_049_888
    attn = shapes["layer_4"]["attn"]
    assert attn["q"]["kernel"].shape == (2560, 32 * 192)
    assert attn["kv_a"]["kernel"].shape == (2560, 576)
    assert attn["kv_b"]["kernel"].shape == (512, 32 * 256)
    assert attn["gate"]["kernel"].shape == (2560, 32)
    assert sum(x.size for x in jax.tree_util.tree_leaves(attn)) == 31_965_696
    assert shapes["layer_0"]["ffn_gate"]["kernel"].shape == (2560, 6144)
    assert shapes["layer_1"]["experts_up"].shape == (8, 2560, 768)
    assert shapes["layer_1"]["router"].shape == (2560, 512)
    assert shapes["lm_head"]["kernel"].shape == (2560, 19648)
    assert sorted(aux) == ["expert_bias", "rows_elsewhere", "rows_held",
                           "steps", "tokens_per_expert"]
    assert aux["tokens_per_expert"].shape == (6, 512)


TINY_SIZES = {
    **tiny_sizes(), "sequence_length": 64, "recompute_blocks": True,
    "adamw_learning_rate": 4e-4, "warmup_steps": 4,
    "warmup_start_share": 0.01, "logits_rtol": 0.2,
    "logits_median_rtol": 0.2, "logits_float32_rtol": 1e-4,
    "logits_float32_norm_rtol": 1e-3}
TINY_CELL = {"module": "ling-3.0-flash-vl", **TINY_SIZES}


@pytest.fixture(scope="module")
def seeded_cell():
    """The tiny cell's configuration module, and the weights and the batch
    that seeds 5 and 6 give: made once for the cases that only read them."""
    module, _ = _config_module()
    config = module.Config(TINY_SIZES)
    params, aux = jax.jit(config.init)(jax.random.PRNGKey(5))
    batch = jax.jit(config.make_batch)(jax.random.PRNGKey(6))
    return module, params, aux, batch


def test_batch_and_weights_come_from_the_seed(seeded_cell):
    module, params, aux, batch = seeded_cell
    config = module.Config(TINY_SIZES)
    again = jax.jit(config.make_batch)(jax.random.PRNGKey(6))
    other = jax.jit(config.make_batch)(jax.random.PRNGKey(7))
    assert np.array_equal(batch["tokens"], again["tokens"])
    assert not np.array_equal(batch["tokens"], other["tokens"])
    assert batch["tokens"].shape == (2, 64)
    assert 0 <= int(batch["tokens"].min()) \
        and int(batch["tokens"].max()) < TINY_SIZES["vocab_size"]
    assert float(jnp.std(params["embed"]["embedding"])) \
        == pytest.approx(1.0, rel=0.1)
    assert float(jnp.std(params["layer_0"]["kda"]["in_proj"]["kernel"])) \
        == pytest.approx(0.02, rel=0.1)
    assert float(jnp.max(params["layer_0"]["kda"]["dt_bias"])) < -2.2
    assert int(aux["steps"]) == 0 and not np.any(aux["expert_bias"])


@pytest.mark.parametrize("which,limit,passes", [
    ("logits_rtol", 0.2, True), ("logits_rtol", 1e-6, False),
    ("logits_median_rtol", 1e-6, False),
    ("logits_float32_rtol", 1e-9, False),
    ("logits_float32_norm_rtol", 1e-9, False)])
def test_the_configurations_own_limit_holds_the_logits(which, limit, passes,
                                                       capfd, seeded_cell):
    """Behind ``_chip_bench_grad`` the program's logits are held to the
    float32 reference's, once, before the reference's first step: inside the
    four limits the reference's gradient comes back, outside any of them the
    run ends there."""
    module, params, aux, batch = seeded_cell
    config = module.Config({**TINY_SIZES, which: limit})
    if not passes:
        with pytest.raises(SystemExit, match=f"over the limit {limit:.2e}"):
            config._chip_bench_grad(params, aux, batch)
        return
    (loss, new_aux), grads = config._chip_bench_grad(params, aux, batch)
    said = capfd.readouterr().err
    assert said.count("(limit 2.00e-01)") == 2 and "(limit 1.00e-04)" in said
    assert "(limit 1.00e-03)" in said
    config._chip_bench_grad(params, aux, batch)      # checked once
    assert capfd.readouterr().err == ""
    assert sorted(new_aux) == sorted(aux) and int(new_aux["steps"]) == 1
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    assert float(loss) == pytest.approx(config.first_loss, rel=0.25)
    errors = config.logits_errors
    assert 0 < errors(params, batch)[0] < limit
    assert errors(params, batch, jnp.float32) == (0, 0)
    exact = errors(params, batch, "program_float32")[0]
    assert 0 < exact < 1e-5
    for fault in ("no_head_gate", "no_l2norm", "no_group_mask"):
        assert errors(params, batch, jnp.float32, (fault,))[0] > 20 * exact, \
            fault


def test_the_step_recomputes_and_follows_the_reference(seeded_cell):
    """``hvd.make_overlapped_train_step(has_aux=True)`` on the program's
    model with every block recomputed beside plain steps of the float32
    reference: three losses agree to the harness's limit, and the step's
    ``aux`` carries the reference's counts and a stepped bias."""
    import optax

    import horovod_tpu as hvd

    module, params, aux, batch = seeded_cell
    config = module.Config(TINY_SIZES)
    assert config.model.cfg.remat
    tx = config.optimizer(1)
    grad = jax.jit(jax.value_and_grad(
        config.reference.make_loss(TINY_SIZES), has_aux=True))
    want_params, want_state, want_aux, want = params, tx.init(params), aux, []
    for _ in range(3):
        (loss, want_aux), g = grad(want_params, want_aux, batch)
        updates, want_state = tx.update(g, want_state, want_params)
        want_params = optax.apply_updates(want_params, updates)
        want.append(float(loss))

    hvd.init()
    step = hvd.make_overlapped_train_step(config.loss, tx, has_aux=True)
    p, s, a = step.init(params, tx.init(params), aux)
    got = []
    for _ in range(3):
        p, s, a, loss = step(p, s, batch, a)
        got.append(float(loss))
    assert got == pytest.approx(want, rel=3e-4)
    assert want[2] < want[0]                     # the updates were applied
    assert int(a["steps"]) == 3
    # Two expert layers, 2 x 64 tokens, 4 a token, three steps.
    counts = np.asarray(a["tokens_per_expert"], np.int64)
    np.testing.assert_array_equal(counts.sum(axis=1), 3 * 2 * 64 * 4)
    # The bf16 stream moves a choice between two near scores here and there.
    assert np.abs(counts - np.asarray(want_aux["tokens_per_expert"])).sum() \
        <= 0.05 * counts.sum()
    bias = np.asarray(a["expert_bias"])
    assert bias.shape == (2, 64) and 0 < np.abs(bias).max() <= 3e-3 + 1e-9


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model (both mixers, the gate, a dense and two expert layers
    under the group limit, the blocks recomputed) against the plain
    reference's three losses, and the per-layer metrics of the device's op
    line left out where there is none to read."""
    from chip_bench.tests import rehearse

    names = NEW_METRICS + ("mla_attention_roofline_pct", "recompute_ms_step",
                           "causal_conv_ms_step", "wfbp_dispatch_ms_step")
    files = {"configs/tiny-ling.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-ling-wfbp", "tiny-ling", "wfbp", 1)],
        files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-ling-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    for n in names[:5]:
        assert r0["per_layer"]["tiny." + n] is None, n
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0


def test_the_new_reductions_read_their_kernels_and_nothing_on_a_parent(
        monkeypatch):
    """``kda_roofline_pct`` takes its time from the rule's kernels by their
    names; ``recompute_ms_step`` (this module's copy) adds up what lies under
    ``rematted_computation``, adopted operations too; without a trace, or on
    a program that ran no such kernel, they read nothing and never raise."""
    from chip_bench import readers, scopes

    module, sizes = _config_module()
    module.Config(sizes)
    share = readers.REDUCTIONS["trace_kda_roofline_pct"]
    attention = readers.REDUCTIONS["trace_mla_attention_roofline_pct"]
    again = readers.REDUCTIONS["trace_recompute_ms_per_step"]
    Op = scopes.Op
    remat = ("jit(step)/transpose(jvp(hvd.loss))/checkpoint/"
             "rematted_computation/layer_1/kda/hvd.kda.{}/x")
    ops = (Op("hvd_kda_fwd.2", 3.0, 4.0, remat.format("rule"), "", 0, 0),
           Op("copy.2", 7.0, 7.25, "", "", 0, 0, remat.format("norm")),
           Op("fusion.6", 8.0, 9.0,
              "jit(step)/jvp(hvd.loss)/layer_0/hvd.ffn/dot_general", "", 0, 0))
    monkeypatch.setattr(scopes, "device_ops", lambda path: ops)

    class Window:
        ops, steps, lo, hi = [1], 2, 0.0, 10.0

        def op_s(self, pattern):
            return 0.0

    ctx = {"window": Window(), "xplane": "a.xplane.pb"}
    assert again({}, ctx) == pytest.approx(1e3 * (1.0 + 0.25) / 2)
    # A parent's program ran no such kernel: nothing to take a share of.
    assert share({"pattern": "^hvd_kda_"}, ctx) is None
    assert attention({"pattern": "^splash_mha_(fwd|dq|dkv)"}, ctx) is None
    monkeypatch.setattr(scopes, "device_ops", lambda path: ops[-1:])
    assert again({}, ctx) is None
    # No trace, no file.
    assert share({"pattern": "^hvd_kda_"}, {"window": None}) is None
    monkeypatch.setattr(sys, "argv", ["worker.py"])
    assert again({}, {"window": Window()}) is None
