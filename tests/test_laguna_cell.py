"""laguna-s-2.1's configuration and cell
(``chip_bench/configs/laguna-s-2.1``): the published widths and the cut, the
counts from shapes, data and weights from the seed, the configuration's own
limits on the logits, the recomputed blocks through
``hvd.make_overlapped_train_step`` and the cell through the harness at a tiny
size.  ``tests/test_laguna.py`` holds the model and its layers; the two are
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
from .test_laguna import TINY

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

CELL = "laguna-s-2.1-wfbp-1chip"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_METRICS = ("window_attention_ms_step", "window_attention_roofline_pct",
               "attn_rope_ms_step")
SHARED_METRICS = ("mfu_pct", "step_ms_p95.observed", "wfbp_dispatch_ms_step",
                  "moe_experts_ms_step", "moe_rows_to_tokens_ms_step",
                  "recompute_ms_step", "mixed_attention_ms_step",
                  "mixed_attention_roofline_pct")


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
    return [r for r in rows if r["name"] == "Laguna-S-2.1"][0]


def test_configuration_keeps_every_published_width():
    _, sizes = _config_module()
    row = _catalog_row()
    published = row["config"]
    assert row["source_url"] == sizes["source"]
    assert sizes["reduced"] == REDUCED
    differs = [k for k, v in published.items()
               if sizes.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED)
    assert [sizes[k] for k in REDUCED] == [5, 8, 12544]
    for key in REDUCED:
        assert sizes[key + "_published"] == published[key]
    assert sizes["layers_held"] == [0, 1, 2, 3, 4]
    assert sizes["experts_held"] == list(range(8))
    # No width among the cuts; the layers' lists whole, the rotary groups
    # letter for letter.
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "shared_expert_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "sliding_window", "rope_parameters",
                "layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer", "moe_routed_scaling_factor"):
        assert sizes[key] == published[key], key
    assert len(sizes["layer_types"]) == 48
    assert sizes["recompute_blocks"] is True
    for key in ("attention", "gate", "yarn", "experts", "init", "precision",
                "sequence", "reference_limits"):
        assert len(sizes["assumed"][key]) > 100, key


def test_benchmark_json_names_the_cell_and_its_files():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = [c for c in bench["configs"] if c["name"] == "laguna-s-2.1"]
    assert len(config) == 1 and config[0]["reduced"] == REDUCED
    assert bench["configs"][-1] is config[0]         # appended, not inserted
    assert os.path.exists(os.path.join(REPO_ROOT, config[0]["file"]))
    for suffix in (".py", "_reference.py"):
        assert os.path.exists(os.path.join(
            REPO_ROOT, config[0]["file"].replace(".json", suffix)))
    cell = bench["workloads"][-1]
    assert cell == {"name": CELL, "config": "laguna-s-2.1",
                    "traffic": "wfbp", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(config[0]["why"]) <= 200
    assert len(bench["workloads"]) == 14
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in SHARED_METRICS:
        assert metrics[name]["workloads"][-1] == CELL, name
    # Appended together (PR 63); PR 65's count of the rotary kernels' calls
    # stands behind.
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-4:] == list(NEW_METRICS) + ["rope_operands_calls_step"]
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["layer"] == "kernel"
        assert metrics[name]["moves"] == "samples_per_s_chip"
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics",
                               name + ".json")) as f:
            assert json.load(f)["name"] == name


def test_flops_and_costs_from_shapes():
    """A token's multiply-adds by hand (ISSUE 63's reckoning: 482.3 M of
    products and 128.1 M of allowed pairs, 30.0 TFLOP a sample) and the
    attention kernels' cost with the recomputed forward, the two kinds
    together and the sliding layers alone."""
    module, sizes = _config_module()
    s, d = 8192, 3072
    causal = s * (s + 1) // 2
    window = causal - (s - 512) * (s - 511) // 2
    assert window == 4_063_488
    head_pairs = 2 * 48 * causal + 3 * 72 * window
    by_hand = {
        "q_out": s * 2 * d * 128 * (2 * 48 + 3 * 72),
        "kv": 5 * s * d * 2048,
        "gate": s * d * (2 * 48 + 3 * 72),
        "attention_scores": head_pairs * 128,
        "attention_values": head_pairs * 128,
        "dense_ffn": s * 3 * d * 12288,
        "router": 4 * s * d * 256,
        "shared_expert": 4 * s * 3 * d * 1024,
        "experts": 4 * s * (10 * 8 / 256) * 3 * d * 1024,
        "head": s * d * 12544,
    }
    assert module.matmul_macs(sizes) == by_hand
    per_token = sum(by_hand.values()) / s / 1e6
    assert per_token == pytest.approx(482.3 + 128.1, abs=0.05)
    assert module.flops_per_sample(sizes) == pytest.approx(30.0e12, rel=1e-3)
    assert module.Config(sizes).flops_per_sample() \
        == module.flops_per_sample(sizes)
    assert module.head_pairs(sizes) == head_pairs
    assert module.head_pairs(sizes, "sliding_attention") == 3 * 72 * window
    # The forward kernel twice and the backward once: 2 + 2 + 4 products.
    operations, moved = module.mixed_attention_cost(sizes)
    assert operations == 2 * 4 * head_pairs * 256
    assert moved == 4 * 2 * s * 128 * (2 * (2 * 48 + 8 * 2)
                                       + 3 * (2 * 72 + 8 * 2))
    operations, moved = module.window_attention_cost(sizes)
    assert operations == 2 * 4 * 3 * 72 * window * 256
    assert moved == 4 * 2 * s * 128 * 3 * (2 * 72 + 16)
    assert module.attention_cost({**sizes, "recompute_blocks": False})[0] \
        == 2 * 3 * head_pairs * 256
    # A sliding layer's kernels visit 15 tiles of 1024 x 1024 for 3.9 tiles'
    # worth of allowed pairs.
    from horovod_tpu.kernels import masked_attention as ma
    from horovod_tpu.kernels.masked_attention_bwd import tile_table

    assert len(tile_table(ma.Window(512), s, 1024, 1024)[0]) == 15
    assert window / 1024 ** 2 == pytest.approx(3.875, abs=1e-3)


def test_the_model_is_the_presets_at_the_cut():
    from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
    from horovod_tpu.models.transformer import laguna_s_2_1_config

    module, sizes = _config_module()
    config = module.Config(sizes)
    cfg, whole = config.model.cfg, laguna_s_2_1_config()
    differs = {f for f in cfg.__dataclass_fields__
               if getattr(cfg, f) != getattr(whole, f)}
    assert differs == {"num_layers", "vocab_size", "experts_held",
                       "layer_pattern", "remat", "moe_data_axis"}
    assert cfg.layer_pattern == whole.layer_pattern[:5]
    assert cfg.remat and cfg.moe_data_axis == PROCESS_AXIS
    assert cfg.expert_layers() == (1, 2, 3, 4)
    shapes, aux = jax.eval_shape(config.init, jax.random.PRNGKey(3))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 811_017_216
    assert shapes["layer_0"]["attn"]["q"]["kernel"].shape == (3072, 6144)
    assert shapes["layer_1"]["attn"]["q"]["kernel"].shape == (3072, 9216)
    assert shapes["layer_1"]["attn"]["gate"]["kernel"].shape == (3072, 72)
    assert shapes["layer_4"]["attn"]["out"]["kernel"].shape == (6144, 3072)
    assert shapes["layer_4"]["experts_up"].shape == (8, 3072, 1024)
    assert shapes["layer_4"]["router"].shape == (3072, 256)
    assert shapes["layer_0"]["ffn_gate"]["kernel"].shape == (3072, 12288)
    assert shapes["lm_head"]["kernel"].shape == (3072, 12544)
    assert sorted(aux) == ["rows_elsewhere", "rows_held", "steps",
                           "tokens_per_expert"]
    assert aux["tokens_per_expert"].shape == (4, 256)


TINY_SIZES = {
    **TINY, "recompute_blocks": True, "adamw_learning_rate": 4e-4,
    "warmup_steps": 4, "warmup_start_share": 0.01, "adamw_b1": 0.9,
    "adamw_b2": 0.95, "adamw_eps": 1e-8, "adamw_weight_decay": 0.1,
    "clip_global_norm": 1.0, "logits_rtol": 0.2, "logits_median_rtol": 0.2,
    "logits_float32_rtol": 1e-4}
TINY_CELL = {"module": "laguna-s-2.1", **TINY_SIZES}


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
    assert float(jnp.std(params["layer_1"]["attn"]["gate"]["kernel"])) \
        == pytest.approx(0.02, rel=0.15)
    assert int(aux["steps"]) == 0


@pytest.mark.parametrize("which,limit,passes", [
    ("logits_rtol", 0.2, True), ("logits_rtol", 1e-6, False),
    ("logits_median_rtol", 1e-6, False),
    ("logits_float32_rtol", 1e-9, False)])
def test_the_configurations_own_limit_holds_the_logits(which, limit, passes,
                                                       capfd, seeded_cell):
    """Behind ``_chip_bench_grad`` the program's logits are held to the
    float32 reference's, once, before the reference's first step: inside the
    three limits the reference's gradient comes back, outside any of them
    the run ends there."""
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
    for fault in ("no_gate", "window_1024", "no_routed_scale",
                  "plain_in_full"):
        assert errors(params, batch, jnp.float32, (fault,))[0] > 20 * exact, \
            fault


def test_the_step_recomputes_and_follows_the_reference(seeded_cell):
    """``hvd.make_overlapped_train_step(has_aux=True)`` on the program's
    model with every block recomputed beside plain steps of the float32
    reference: three losses agree to the harness's limit, and the step's
    ``aux`` carries the reference's counts."""
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
    # Three sparse layers, 2 x 20 tokens, 2 a token, three steps.
    counts = np.asarray(a["tokens_per_expert"], np.int64)
    np.testing.assert_array_equal(counts.sum(axis=1), 3 * 2 * 20 * 2)
    # The bf16 stream moves a choice between two near scores here and there.
    assert np.abs(counts - np.asarray(want_aux["tokens_per_expert"])).sum() \
        <= 0.05 * counts.sum()


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model (both kinds of attention, the gate, a dense and three
    expert layers, the blocks recomputed) against the plain reference's
    three losses, and the per-layer metrics of the device's op line left out
    where there is none to read."""
    from chip_bench.tests import rehearse

    names = NEW_METRICS + ("mixed_attention_roofline_pct",
                           "recompute_ms_step", "wfbp_dispatch_ms_step")
    files = {"configs/tiny-laguna.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-laguna-wfbp", "tiny-laguna", "wfbp", 1)],
        files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-laguna-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    for n in names[:5]:
        assert r0["per_layer"]["tiny." + n] is None, n
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0


def test_the_new_metrics_read_their_scopes_and_nothing_on_a_parent(
        monkeypatch):
    """``window_attention_ms_step`` adds up the attention kernels under
    ``hvd.attn.window`` alone (not the global layers' calls of the same
    kernels, not the small operations beside them), forward, backward and
    recomputed; ``attn_rope_ms_step`` everything under ``hvd.attn.rope``,
    adopted operations too; without a trace, or on a program that wrote no
    such scope, they read nothing and never raise."""
    from chip_bench import readers, scopes

    module, sizes = _config_module()
    module.Config(sizes)
    window = readers.REDUCTIONS["trace_window_attention_ms_per_step"]
    share = readers.REDUCTIONS["trace_window_attention_roofline_pct"]
    rope = readers.REDUCTIONS["trace_attn_rope_ms_per_step"]
    again = readers.REDUCTIONS["trace_recompute_ms_per_step"]
    Op = scopes.Op
    fwd = "jit(step)/jvp(hvd.loss)/layer_1/attn/hvd.attn.{}/x"
    remat = ("jit(step)/transpose(jvp(hvd.loss))/checkpoint/"
             "rematted_computation/layer_1/attn/hvd.attn.{}/x")
    bwd = "jit(step)/transpose(jvp(hvd.loss))/layer_1/attn/hvd.attn.{}/x"
    ops = (Op("splash_mha_fwd_out_lse", 0.0, 1.0, fwd.format("window"), "",
              0, 0),
           Op("splash_mha_fwd_out_lse.1", 1.0, 3.0, fwd.format("causal"),
              "", 0, 0),
           Op("splash_mha_fwd_out_lse.2", 3.0, 4.0, remat.format("window"),
              "", 0, 0),
           Op("splash_mha_dkv_dq", 4.0, 6.0, bwd.format("window"), "", 0, 0),
           Op("fusion.9", 6.0, 6.5, bwd.format("window"), "", 0, 0),
           Op("fusion.1", 6.5, 7.0, fwd.format("rope"), "", 0, 0),
           Op("copy.2", 7.0, 7.25, "", "", 0, 0, remat.format("rope")),
           Op("fusion.3", 7.25, 8.0, bwd.format("rope"), "", 0, 0),
           Op("fusion.6", 8.0, 9.0,
              "jit(step)/jvp(hvd.loss)/layer_0/hvd.ffn/dot_general", "", 0, 0))
    monkeypatch.setattr(scopes, "device_ops", lambda path: ops)

    class Window:
        ops, steps, lo, hi = [1], 2, 0.0, 10.0

    ctx = {"window": Window(), "xplane": "a.xplane.pb"}
    assert window({}, ctx) == pytest.approx(1e3 * (1.0 + 1.0 + 2.0) / 2)
    assert rope({}, ctx) == pytest.approx(1e3 * (0.5 + 0.25 + 0.75) / 2)
    assert again({}, ctx) == pytest.approx(1e3 * (1.0 + 0.25) / 2)
    if jax.local_devices()[0].platform != "tpu":
        with pytest.raises(ValueError, match="peak"):
            share({}, ctx)
    # A parent's program: no window kernel, nothing under attn.rope.
    monkeypatch.setattr(scopes, "device_ops", lambda path: ops[-1:])
    ctx["xplane"] = "parent.xplane.pb"       # the op line is read once a file
    assert window({}, ctx) is None and rope({}, ctx) is None
    assert share({}, ctx) is None and again({}, ctx) is None
    # No trace, no file.
    assert window({}, {"window": None}) is None
    monkeypatch.setattr(sys, "argv", ["worker.py"])
    assert rope({}, {"window": Window()}) is None
