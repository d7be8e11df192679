"""xing4.0-29b-a4b's configuration and cell
(``chip_bench/configs/xing4.0-29b-a4b``): the published widths and the cut,
the counts from shapes, data and weights from the seed, the configuration's
own limits on the logits, the recomputed blocks through
``hvd.make_overlapped_train_step`` and the cell through the harness at a tiny
size.  ``tests/test_xing.py`` holds the model and its layers; the two are
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
from .test_xing import TINY

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

CELL = "xing4.0-29b-a4b-wfbp-1chip"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]
NEW_METRICS = ("hyper_connection_ms_step", "hyper_connection_roofline_pct",
               "sinkhorn_ms_step")
SHARED_METRICS = ("mfu_pct", "step_ms_p95.observed", "wfbp_dispatch_ms_step",
                  "moe_experts_ms_step", "moe_rows_to_tokens_ms_step",
                  "mla_attention_ms_step", "mla_attention_roofline_pct",
                  "recompute_ms_step")


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
    return [r for r in rows if r["name"] == "Xing4.0-29B-A4B"][0]


def test_configuration_keeps_every_published_width():
    _, sizes = _config_module()
    row = _catalog_row()
    published = row["config"]
    assert row["source_url"] == sizes["source"]
    assert sizes["reduced"] == REDUCED
    differs = [k for k, v in published.items()
               if sizes.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED)
    assert [sizes[k] for k in REDUCED] == [5, 1, 8, 16384, 0]
    for key in REDUCED:
        assert sizes[key + "_published"] == published[key]
    assert sizes["layers_held"] == [0, 2, 3, 4, 5]
    assert sizes["experts_held"] == list(range(8))
    # No width among the cuts, and the hyper-connections' and YaRN's numbers
    # letter for letter.
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
                "num_key_value_heads", "num_experts_per_tok", "hc_mult",
                "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                "mhc_h_res_clamp_max", "rope_scaling",
                "routed_scaling_factor"):
        assert sizes[key] == published[key], key
    assert sizes["recompute_blocks"] is True
    for key in ("hyper_connections", "hyper_connections_init", "yarn",
                "latent_attention", "experts", "precision", "sequence",
                "reference_limits"):
        assert len(sizes["assumed"][key]) > 100, key


def test_benchmark_json_names_the_cell_and_its_files():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = [c for c in bench["configs"] if c["name"] == "xing4.0-29b-a4b"]
    assert len(config) == 1 and config[0]["reduced"] == REDUCED
    # Appended, not inserted (PR 58); PR 63's configuration stands behind.
    assert bench["configs"][10] is config[0]
    assert os.path.exists(os.path.join(REPO_ROOT, config[0]["file"]))
    for suffix in (".py", "_reference.py"):
        assert os.path.exists(os.path.join(
            REPO_ROOT, config[0]["file"].replace(".json", suffix)))
    cell = bench["workloads"][12]
    assert cell == {"name": CELL, "config": "xing4.0-29b-a4b",
                    "traffic": "wfbp", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(config[0]["why"]) <= 200
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in SHARED_METRICS:
        assert CELL in metrics[name]["workloads"], name
    # Appended together (PR 58); PR 59's two kernel metrics and PR 63's three
    # stand behind.
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + 3] == list(NEW_METRICS)
    assert names[first + 3:first + 5] == [
        "hyper_connection_kernel_calls_step",
        "hyper_connection_kernels_ms_step"]
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["layer"] == "kernel"
        assert metrics[name]["moves"] == "samples_per_s_chip"
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics",
                               name + ".json")) as f:
            assert json.load(f)["name"] == name


def test_flops_and_costs_from_shapes():
    """A token's multiply-adds by hand, in millions (ISSUE 58's reckoning:
    579.8 M from its rounded parts, 580.0 M unrounded, 28.5 TFLOP a sample),
    the attention kernels' cost with the recomputed forward and the
    hyper-connections' bytes."""
    module, sizes = _config_module()
    s, d = 8192, 3584
    pairs = 5 * s * (s + 1) // 2
    by_hand = {
        "mla_down": 5 * s * d * (768 + 512 + 64),
        "mla_up": 5 * s * (768 * 32 * 192 + 512 * 32 * 256),
        "mla_out": 5 * s * 32 * 128 * d,
        "attention_scores": pairs * 32 * 192,
        "attention_values": pairs * 32 * 128,
        "dense_ffn": s * 3 * d * 9216,
        "router": 4 * s * d * 64,
        "shared_expert": 4 * s * 3 * d * 1024,
        "experts": 4 * s * (4 * 8 / 64) * 3 * d * 1024,
        "hc_phi": 10 * s * 4 * d * 24,
        "head": s * d * 16384}
    assert module.matmul_macs(sizes) == by_hand
    per_token = {k: round(v / s / 1e6, 1) for k, v in by_hand.items()}
    assert per_token == {
        "mla_down": 24.1, "mla_up": 44.6, "mla_out": 73.4,
        "attention_scores": 125.8, "attention_values": 83.9,
        "dense_ffn": 99.1, "router": 0.9, "shared_expert": 44.0,
        "experts": 22.0, "hc_phi": 3.4, "head": 58.7}
    # The projections of one layer: 28.41 M a token, less the two norms.
    assert (by_hand["mla_down"] + by_hand["mla_up"] + by_hand["mla_out"]) \
        // (5 * s) == 28_411_136 - 768 - 512
    total = sum(by_hand.values())
    assert round(total / s / 1e6, 1) == 580.0
    config = module.Config(sizes)
    assert config.flops_per_sample() == 6 * total
    assert round(config.per_chip_batch * 6 * total / 1e12, 1) == 28.5
    # The attention kernels: the forward twice (320 multiply-adds a pair and
    # head each) and the backward once (640), 2 each.
    operations, moved = module.mla_attention_cost(sizes)
    one_sequence = 8192 * 8193 // 2
    assert operations == 2 * (2 * 320 + 640) * one_sequence * 32 * 5
    assert moved == 5 * 4 * 2 * 8192 * 32 * (192 + 192 + 128 + 128)
    assert operations / 197e12 > 8 * moved / 819e9      # compute-bound
    assert round(1e3 * operations / 197e12, 1) == 69.8
    # The hyper-connections: X = 235 MB, u = 59 MB; ten sublayers' forward
    # twice (3 X + 2 u) and backward once (5 X + 3 u), the fold and the
    # fan-out's backward (3 X + 3 u).
    u = 2 * 8192 * 3584
    x = 4 * u
    operations, moved = module.hyper_connection_cost(sizes)
    assert moved == 10 * (2 * (3 * x + 2 * u) + 5 * x + 3 * u) + 3 * x + 3 * u
    assert round(moved / 1e9, 1) == 30.8
    assert round(1e3 * moved / 819e9, 1) == 37.6        # ms at the HBM peak
    assert operations == 8 * by_hand["hc_phi"]
    assert moved / 819e9 > 10 * operations / 197e12     # memory-bound
    without = {**sizes, "recompute_blocks": False}
    assert module.hyper_connection_cost(without)[1] \
        == moved - 10 * (3 * x + 2 * u)
    assert module.mla_attention_cost(without)[0] * 4 \
        == module.mla_attention_cost(sizes)[0] * 3


def test_the_model_is_the_presets_at_the_cut():
    from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
    from horovod_tpu.models.transformer import xing4_0_29b_a4b_config

    module, sizes = _config_module()
    config = module.Config(sizes)
    cfg, whole = config.model.cfg, xing4_0_29b_a4b_config()
    differs = {f for f in cfg.__dataclass_fields__
               if getattr(cfg, f) != getattr(whole, f)}
    assert differs == {"num_layers", "vocab_size", "experts_held",
                       "layer_pattern", "remat", "moe_data_axis"}
    assert cfg.remat and cfg.moe_data_axis == PROCESS_AXIS
    assert [cfg.layer_kind(i).ffn for i in range(5)] \
        == ["dense", None, None, None, None]
    assert cfg.expert_layers() == (1, 2, 3, 4)
    assert (cfg.yarn_factor, cfg.yarn_original_max_len, cfg.yarn_beta_fast,
            cfg.yarn_beta_slow, cfg.yarn_mscale, cfg.yarn_mscale_all_dim) \
        == (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    shapes, aux = jax.eval_shape(config.init, jax.random.PRNGKey(3))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 759_346_190
    assert shapes["layer_0"]["hc_mixer"]["phi"].shape == (14336, 24)
    assert shapes["layer_4"]["experts_up"].shape == (8, 3584, 1024)
    assert shapes["layer_4"]["router"].shape == (3584, 64)
    assert shapes["layer_0"]["ffn_gate"]["kernel"].shape == (3584, 9216)
    assert shapes["lm_head"]["kernel"].shape == (3584, 16384)
    assert sorted(aux) == ["expert_bias", "hc_deviation", "rows_elsewhere",
                           "rows_held", "steps", "tokens_per_expert"]
    assert aux["expert_bias"].shape == (4, 64)


TINY_SIZES = {
    **TINY, "recompute_blocks": True, "adamw_learning_rate": 4e-4,
    "warmup_steps": 4, "warmup_start_share": 0.01, "adamw_b1": 0.9,
    "adamw_b2": 0.95, "adamw_eps": 1e-8, "adamw_weight_decay": 0.1,
    "clip_global_norm": 1.0, "logits_rtol": 0.2, "logits_median_rtol": 0.2,
    "logits_float32_rtol": 1e-4, "logits_float32_norm_rtol": 2e-3,
    "hc_sinkhorn_iters": 3}
TINY_CELL = {"module": "xing4.0-29b-a4b", **TINY_SIZES}


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
    assert batch["tokens"].shape == (2, 20)
    assert 0 <= int(batch["tokens"].min()) \
        and int(batch["tokens"].max()) < TINY_SIZES["vocab_size"]
    assert float(jnp.std(params["embed"]["embedding"])) \
        == pytest.approx(1.0, rel=0.1)
    assert float(jnp.std(params["layer_1"]["hc_ffn"]["phi"])) \
        == pytest.approx(0.02, rel=0.1)
    assert float(aux["hc_deviation"]) == 0.0


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
    # At fresh weights (alpha 0.01, streams nearly equal) the faults of the
    # residual matrix hide under float32's rounding and the others show:
    # what chip_bench/tools/xing_reference_check.py adds a seeded check for.
    for fault in ("post_without_2", "scale_without_mscale", "plain_rope"):
        assert errors(params, batch, jnp.float32, (fault,))[0] > 20 * exact, \
            fault


def test_the_step_recomputes_and_follows_the_reference(seeded_cell):
    """``hvd.make_overlapped_train_step(has_aux=True)`` on the program's
    model with every block recomputed beside plain steps of the float32
    reference: three losses agree to the harness's limit, and the step's
    ``aux`` carries the bias stepped and the Sinkhorn counter."""
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
    np.testing.assert_array_equal(a["tokens_per_expert"],
                                  want_aux["tokens_per_expert"])
    np.testing.assert_array_equal(a["expert_bias"], want_aux["expert_bias"])
    assert 0 < float(a["hc_deviation"]) < 1e-2
    assert float(a["hc_deviation"]) == pytest.approx(
        float(want_aux["hc_deviation"]), rel=0.2)


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model (four streams, YaRN latent attention, a dense and two
    expert layers, the blocks recomputed) against the plain reference's
    three losses, and the per-layer metrics of the device's op line left out
    where there is none to read."""
    from chip_bench.tests import rehearse

    names = NEW_METRICS + ("mla_attention_roofline_pct", "recompute_ms_step",
                           "wfbp_dispatch_ms_step")
    files = {"configs/tiny-xing.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-xing-wfbp", "tiny-xing", "wfbp", 1)],
        files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-xing-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    for n in names[:5]:
        assert r0["per_layer"]["tiny." + n] is None, n
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0


def test_the_hc_scopes_are_read_by_their_names(monkeypatch, tmp_path):
    """``hyper_connection_ms_step`` adds up the operations whose innermost
    scope is one of the four ``hc.*``, forward, backward, recomputed and
    adopted alike, ``sinkhorn_ms_step`` those of ``hc.sinkhorn`` alone; on a
    parent's program, which writes no such scope, and without a trace they
    read nothing and never raise."""
    from chip_bench import readers, scopes

    module, sizes = _config_module()
    module.Config(sizes)
    whole = readers.REDUCTIONS["trace_hyper_connection_ms_per_step"]
    chain = readers.REDUCTIONS["trace_sinkhorn_ms_per_step"]
    share = readers.REDUCTIONS["trace_hyper_connection_roofline_pct"]
    Op = scopes.Op
    fwd = "jit(step)/jvp(hvd.loss)/layer_0/hc_mixer/hvd.hc.{}/mul"
    again = ("jit(step)/transpose(jvp(hvd.loss))/checkpoint/"
             "rematted_computation/layer_0/hc_ffn/hvd.hc.{}/mul")
    bwd = "jit(step)/transpose(jvp(hvd.loss))/layer_0/hc_ffn/hvd.hc.{}/mul"
    ops = (Op("fusion.1", 0.0, 1.0, fwd.format("coeff"), "", 0, 0),
           Op("fusion.2", 1.0, 1.5, fwd.format("sinkhorn"), "", 0, 0),
           Op("fusion.3", 1.5, 2.5, again.format("pre"), "", 0, 0),
           Op("copy.4", 2.5, 3.0, "", "", 0, 0, bwd.format("post")),
           Op("fusion.5", 3.0, 3.25, bwd.format("sinkhorn"), "", 0, 0),
           Op("fusion.6", 3.25, 6.0,
              "jit(step)/jvp(hvd.loss)/layer_0/hvd.ffn/dot_general", "", 0, 0),
           Op("fusion.7", 9.0, 12.0, fwd.format("post"), "", 0, 0))
    monkeypatch.setattr(scopes, "device_ops", lambda path: ops)

    class Window:
        ops, steps, lo, hi = [1], 2, 0.0, 10.0

    ctx = {"window": Window(), "xplane": "a.xplane.pb"}
    assert whole({}, ctx) == pytest.approx(
        1e3 * (1.0 + 0.5 + 1.0 + 0.5 + 0.25 + 1.0) / 2)
    assert chain({}, ctx) == pytest.approx(1e3 * (0.5 + 0.25) / 2)
    if jax.local_devices()[0].platform != "tpu":
        with pytest.raises(ValueError, match="peak"):
            share({}, ctx)
    # A parent's program: no operation under an hc scope.
    monkeypatch.setattr(scopes, "device_ops", lambda path: ops[5:6])
    assert whole({}, ctx) is None and chain({}, ctx) is None
    assert share({}, ctx) is None
    # No trace, no file, an empty directory.
    assert whole({}, {"window": None}) is None
    monkeypatch.setattr(sys, "argv", ["worker.py"])
    assert whole({}, {"window": Window()}) is None
    monkeypatch.setattr(sys, "argv", ["worker.py", "--out", str(tmp_path)])
    assert share({}, {"window": Window()}) is None
    assert readers.REDUCTIONS["trace_recompute_ms_per_step"](
        {}, {"window": Window()}) is None
