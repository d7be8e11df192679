"""keye-vl-2.0-30b-a3b's configuration and cell
(``chip_bench/configs/keye-vl-2.0-30b-a3b``): the published widths and the
cut, the counts from shapes, data and weights from the seed, the
configuration's own limits on the logits and on the chosen sets, the
three-term loss through ``hvd.make_overlapped_train_step`` and the cell
through the harness at a tiny size.  ``tests/test_keye.py`` holds the model
and its kernels; the two are apart so that the test workers can share them.
"""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .helpers import REPO_ROOT
from .test_keye import tiny_sizes

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

CELL = "keye-vl-2.0-30b-a3b-wfbp-1chip"
REDUCED = ["num_hidden_layers", "num_experts", "num_local_experts",
           "vocab_size"]
NEW_METRICS = ("sparse_attention_ms_step", "sparse_attention_roofline_pct",
               "indexer_ms_step", "indexer_roofline_pct")
SHARED_METRICS = ("step_ms_p95.observed", "wfbp_dispatch_ms_step",
                  "moe_experts_ms_step", "moe_rows_to_tokens_ms_step",
                  "rope_operands_calls_step")


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
    return [r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B"][0]


def test_configuration_keeps_every_published_width():
    _, sizes = _config_module()
    row = _catalog_row()
    published = row["config"]
    assert row["source_url"] == sizes["source"]
    assert sizes["reduced"] == REDUCED
    differs = [k for k, v in published.items()
               if sizes.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED)
    assert [sizes[k] for k in REDUCED] == [4, 16, 16, 18992]
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert sizes[key + "_published"] == published[key]
    assert sizes["experts_held"] == list(range(16))
    assert sizes["vocab_size"] * 8 == published["vocab_size"]
    # No width among the cuts; sa_config whole.
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "rope_theta", "rope_scaling",
                "sa_config", "max_position_embeddings"):
        assert sizes[key] == published[key], key
    assert sizes["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert sizes["recompute_blocks"] is False
    assert sizes["sequence_length"] == 16384 and sizes["per_chip_batch"] == 1
    assert "8 chips" in sizes["deployment"]
    for key in ("recomputed", "fit", "reduced_how"):
        assert len(sizes[key]) > 200, key
    for key in ("qk_norm", "mrope", "indexer", "chosen_set", "indexer_loss",
                "auxiliary_losses", "optimizer", "init", "precision",
                "sequence", "reference_limits"):
        assert len(sizes["assumed"][key]) > 100, key


def test_benchmark_json_names_the_cell_and_its_files():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = [c for c in bench["configs"]
              if c["name"] == "keye-vl-2.0-30b-a3b"]
    assert len(config) == 1 and config[0]["reduced"] == REDUCED
    assert bench["configs"][-1] is config[0]         # appended, not inserted
    assert os.path.exists(os.path.join(REPO_ROOT, config[0]["file"]))
    for suffix in (".py", "_reference.py"):
        assert os.path.exists(os.path.join(
            REPO_ROOT, config[0]["file"].replace(".json", suffix)))
    cell = bench["workloads"][-1]
    assert cell == {"name": CELL, "config": "keye-vl-2.0-30b-a3b",
                    "traffic": "wfbp", "chips": 1, "why": cell["why"]}
    # The newest configuration's test counts exactly.
    assert len(bench["configs"]) == 14 and len(bench["workloads"]) == 16
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in SHARED_METRICS:
        assert metrics[name]["workloads"][-1] == CELL, name
    assert CELL not in metrics["recompute_ms_step"]["workloads"]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-4:] == list(NEW_METRICS)
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
    """A sample's multiply-adds by hand, as the equations state them (the
    chosen pairs, the indexer's scores over the causal pairs, the target's
    pass), and the two costs the shares of a roofline are taken from."""
    module, sizes = _config_module()
    s, d, h, dh, layers = 16384, 2048, 32, 128, 4
    causal, chosen = s * (s + 1) // 2, 2048 * 2049 // 2 + (s - 2048) * 2048
    assert (module.causal_pairs(sizes), module.chosen_pairs(sizes)) \
        == (causal, chosen) == (134_225_920, 31_458_304)
    by_hand = {
        "qkvo": layers * s * (2 * d * 4096 + 2 * d * 512),
        "attention_scores": layers * chosen * h * dh,
        "attention_values": layers * chosen * h * dh,
        "indexer_projections": layers * s * d * (1024 + 64 + 16),
        "indexer_scores": layers * causal * 1024,
        "indexer_target": layers * chosen * h * dh,
        "indexer_loss_scores": layers * chosen * 1024,
        "router": layers * s * d * 128,
        "experts": layers * s * (8 * 16 / 128) * 3 * d * 768,
        "head": s * d * 18992,
    }
    assert module.matmul_macs(sizes) == by_hand
    forward_only = by_hand["indexer_scores"] + by_hand["indexer_target"]
    assert module.flops_per_sample(sizes) \
        == 6.0 * (sum(by_hand.values()) - forward_only) + 2.0 * forward_only
    assert module.flops_per_sample(sizes) == pytest.approx(23.18e12, rel=1e-3)
    assert module.Config(sizes).flops_per_sample() \
        == module.flops_per_sample(sizes)
    operations, moved = module.sparse_attention_cost(sizes)
    assert operations == layers * 2 * 3 * chosen * h * 2 * dh
    assert moved == layers * (3 * 2 * s * dh * (2 * h + 8)
                              + 2 * s * s // 8)
    assert operations / 197e12 > moved / 819e9          # the products bind
    assert operations / 197e12 == pytest.approx(31.4e-3, rel=1e-2)
    twice = module.sparse_attention_cost({**sizes, "recompute_blocks": True})
    assert twice[0] == layers * 2 * 4 * chosen * h * 2 * dh
    operations, moved = module.indexer_cost(sizes)
    assert operations == layers * 2 * (causal * 1024
                                       + chosen * (3 * 1024 + h * dh))
    assert operations / 197e12 > moved / 819e9


def test_the_model_is_the_presets_at_the_cut():
    from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
    from horovod_tpu.models.transformer import keye_vl_2_0_30b_a3b_config

    module, sizes = _config_module()
    config = module.Config(sizes)
    cfg, whole = config.model.cfg, keye_vl_2_0_30b_a3b_config()
    differs = {f for f in cfg.__dataclass_fields__
               if getattr(cfg, f) != getattr(whole, f)}
    assert differs == {"num_layers", "vocab_size", "experts_held",
                       "moe_data_axis"}
    assert not cfg.remat and cfg.moe_data_axis == PROCESS_AXIS
    assert (cfg.indexer_heads, cfg.indexer_head_dim, cfg.indexer_topk) \
        == (16, 64, 2048)
    shapes, aux = jax.eval_shape(config.init, jax.random.PRNGKey(3))
    count = lambda tree: sum(  # noqa: E731
        x.size for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == 465_391_104
    assert "465,391,104" in sizes["reduced_how"]
    assert count(shapes["layer_0"]) == 96_899_456
    assert count(shapes["layer_0"]["attn"]["indexer"]) == 2_261_120
    assert shapes["layer_0"]["experts_up"].shape == (16, 2048, 768)
    assert shapes["layer_0"]["router"].shape == (2048, 128)
    assert shapes["lm_head"]["kernel"].shape == (2048, 18992)
    assert sorted(aux) == ["indexer_loss", "rows_elsewhere", "rows_held",
                           "steps", "tokens_per_expert"]
    assert aux["tokens_per_expert"].shape == (4, 128)


TINY_SIZES = {
    **tiny_sizes(), "sequence_length": 128, "adamw_learning_rate": 4e-4,
    "warmup_steps": 4, "warmup_start_share": 0.01, "logits_rtol": 0.2,
    "logits_median_rtol": 0.2, "logits_float32_rtol": 1e-4,
    "chosen_sets_differ_share": 1e-2,
    "sa_config": {**tiny_sizes()["sa_config"], "topk": 32,
                  "q_chunk_size": 64, "kv_chunk_size": 64}}
TINY_CELL = {"module": "keye-vl-2.0-30b-a3b", **TINY_SIZES}


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
    assert batch["tokens"].shape == (2, 128)
    assert 0 <= int(batch["tokens"].min()) \
        and int(batch["tokens"].max()) < TINY_SIZES["vocab_size"]
    assert float(jnp.std(params["embed"]["embedding"])) \
        == pytest.approx(1.0, rel=0.1)
    indexer = params["layer_0"]["attn"]["indexer"]
    assert float(jnp.std(indexer["q"]["kernel"])) \
        == pytest.approx(0.02, rel=0.1)
    assert not np.any(indexer["k_norm"]["bias"])
    assert int(aux["steps"]) == 0 and float(aux["indexer_loss"]) == 0.0


@pytest.mark.parametrize("which,limit,passes", [
    ("logits_rtol", 0.2, True), ("logits_rtol", 1e-6, False),
    ("logits_median_rtol", 1e-6, False),
    ("logits_float32_rtol", 1e-9, False),
    ("chosen_sets_differ_share", -1.0, False)])
def test_the_configurations_own_limits_hold_the_logits_and_the_sets(
        which, limit, passes, capfd, seeded_cell):
    """Behind ``_chip_bench_grad`` the program's logits and its float32
    model's chosen sets are held to the float32 reference's, once, before
    the reference's first step: inside the four limits the reference's
    gradient comes back, outside any of them the run ends there."""
    module, params, aux, batch = seeded_cell
    config = module.Config({**TINY_SIZES, which: limit})
    if not passes:
        with pytest.raises(SystemExit, match=re.escape(
                f"over the limit {limit:.2e}")):
            config._chip_bench_grad(params, aux, batch)
        return
    (loss, new_aux), grads = config._chip_bench_grad(params, aux, batch)
    said = capfd.readouterr().err
    assert said.count("(limit 2.00e-01)") == 2 and "(limit 1.00e-04)" in said
    assert "(limit 1.00e-02)" in said
    config._chip_bench_grad(params, aux, batch)      # checked once
    assert capfd.readouterr().err == ""
    assert sorted(new_aux) == sorted(aux) and int(new_aux["steps"]) == 1
    assert float(new_aux["indexer_loss"]) > 0
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    assert float(loss) == pytest.approx(
        config.first_loss + float(new_aux["indexer_loss"]), rel=0.25)
    errors = config.logits_errors
    assert 0 < errors(params, batch)[0] < limit
    assert errors(params, batch, jnp.float32) == (0, 0)
    exact = errors(params, batch, "program_float32")[0]
    assert 0 < exact < 1e-5
    assert config.chosen_sets_differ(params, batch) == [0.0, 0.0]
    for fault in ("half_the_keys", "dense", "no_relu"):
        assert errors(params, batch, jnp.float32, (fault,))[0] > 20 * exact, \
            fault


def test_the_step_follows_the_reference_and_the_indexer_learns(seeded_cell):
    """``hvd.make_overlapped_train_step(has_aux=True)`` on the program's
    model beside plain steps of the float32 reference: three losses agree to
    the harness's limit, the step's ``aux`` carries the reference's counts,
    and the indexer's loss, which starts untrained against a target that
    hardly moves, falls."""
    import optax

    import horovod_tpu as hvd

    module, params, aux, batch = seeded_cell
    config = module.Config(TINY_SIZES)
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
    got, divergences = [], []
    for _ in range(3):
        p, s, a, loss = step(p, s, batch, a)
        got.append(float(loss))
        divergences.append(float(a["indexer_loss"]))
    assert got == pytest.approx(want, rel=3e-4)
    assert want[2] < want[0]                     # the updates were applied
    assert divergences[2] < divergences[1] < divergences[0]
    assert divergences[2] == pytest.approx(float(want_aux["indexer_loss"]),
                                           rel=2e-2)
    assert int(a["steps"]) == 3
    # Two expert layers, 2 x 128 tokens, 4 a token, three steps.
    counts = np.asarray(a["tokens_per_expert"], np.int64)
    np.testing.assert_array_equal(counts.sum(axis=1), 3 * 2 * 128 * 4)
    # The bf16 stream moves a choice between two near scores here and there.
    assert np.abs(counts - np.asarray(want_aux["tokens_per_expert"])).sum() \
        <= 0.05 * counts.sum()


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model (the indexer, attention under its chosen sets, two
    expert layers) against the plain reference's three losses, and the
    per-layer metrics of the device's op line left out where there is none
    to read."""
    from chip_bench.tests import rehearse

    names = NEW_METRICS + ("wfbp_dispatch_ms_step",)
    files = {"configs/tiny-keye.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-keye-wfbp", "tiny-keye", "wfbp", 1)],
        files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-keye-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    for n in names[:4]:
        assert r0["per_layer"]["tiny." + n] is None, n
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0


def test_the_new_reductions_read_their_kernels_and_nothing_on_a_parent(
        monkeypatch):
    """``sparse_attention_ms_step`` adds up the attention kernels under
    ``hvd.attn.sparse`` and not those under another rule's scope;
    ``indexer_ms_step`` what lies under an ``hvd.indexer.*`` scope and the
    indexer's kernels by name; the two shares take their time from those;
    without a trace, or on a program that ran no such kernel, they read
    nothing and never raise."""
    from chip_bench import readers, scopes

    module, sizes = _config_module()
    module.Config(sizes)
    attention = readers.REDUCTIONS["trace_sparse_attention_ms_per_step"]
    share = readers.REDUCTIONS["trace_sparse_attention_roofline_pct"]
    indexer = readers.REDUCTIONS["trace_indexer_ms_per_step"]
    indexer_share = readers.REDUCTIONS["trace_indexer_roofline_pct"]
    Op = scopes.Op
    at = "jit(step)/jvp(hvd.loss)/layer_1/attn/hvd.{}/x"
    ops = (Op("splash_mha_fwd_out_lse.2", 1.0, 2.0,
              at.format("attn.sparse"), "", 0, 0),
           Op("splash_mha_dkv_dq.1", 2.0, 2.5,
              at.format("attn.causal"), "", 0, 0),
           Op("hvd_dsa_choose.3", 3.0, 3.5,
              at.format("indexer.scores/hvd.indexer.choose"), "", 0, 0),
           Op("fusion.9", 4.0, 4.25, at.format("indexer.proj"), "", 0, 0),
           Op("fusion.6", 8.0, 9.0,
              "jit(step)/jvp(hvd.loss)/layer_0/hvd.ffn/dot_general", "", 0, 0))
    monkeypatch.setattr(scopes, "device_ops", lambda path: ops)
    monkeypatch.setattr(jax, "local_devices", lambda: [
        type("D", (), {"device_kind": "TPU v5 lite"})()])

    class Window:
        ops, steps, lo, hi = [1], 2, 0.0, 10.0

    ctx = {"window": Window(), "xplane": "a.xplane.pb"}
    assert attention({}, ctx) == pytest.approx(1e3 * 1.0 / 2)
    assert indexer({}, ctx) == pytest.approx(1e3 * (0.5 + 0.25) / 2)
    least = module.sparse_attention_cost(sizes)[0] / 197e12
    assert share({}, ctx) == pytest.approx(100 * least * 1e3 / 500.0)
    least = module.indexer_cost(sizes)[0] / 197e12
    assert indexer_share({}, ctx) == pytest.approx(100 * least * 1e3 / 250.0)
    # A parent's program ran no such kernel and wrote no such scope.
    monkeypatch.setattr(scopes, "device_ops", lambda path: ops[-1:])
    module._device_ops.cache_clear()        # read once a path
    for reduction in (attention, share, indexer, indexer_share):
        assert reduction({}, ctx) is None
        assert reduction({}, {"window": None}) is None
    monkeypatch.setattr(sys, "argv", ["worker.py"])
    assert indexer({}, {"window": Window()}) is None
