"""granite-4.0-h-micro's configuration and cell
(``chip_bench/configs/granite-4.0-h-micro``): the published widths and the
cut, data and weights from the seed, the configuration's own limits on the
logits, the recomputed blocks through ``hvd.make_overlapped_train_step`` and
the cell through the harness at a tiny size.  ``tests/test_granite.py`` holds
the model and its layers; the two are apart so that the test workers can
share them.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from .helpers import REPO_ROOT, load_reference
from .test_granite import TINY

ref = load_reference("granite-4.0-h-micro")

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)
# The benchmark's own counts by hand (FLOPs, the scan's cost, parameters)
# run with the tier-1 tests too.
pytest.register_assert_rewrite("chip_bench.tests.test_granite_config")
from chip_bench.tests.test_granite_config import (  # noqa: E402,F401
    test_macs_by_hand,
    test_parameters_by_hand,
    test_ssd_scan_cost_by_hand,
)

CELL = "granite-4.0-h-micro-wfbp-1chip"
REDUCED = ["num_hidden_layers", "vocab_size"]


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
    return [r for r in rows if r["name"] == "granite-4.0-h-micro"][0]


def test_configuration_keeps_every_published_width():
    module, sizes = _config_module()
    row = _catalog_row()
    published = row["config"]
    assert row["source_url"] == sizes["source"]
    assert sizes["reduced"] == REDUCED
    differs = [k for k, v in published.items()
               if sizes.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED)
    assert [sizes[k] for k in REDUCED] == [10, 12544]
    for key in REDUCED:
        assert sizes[key + "_published"] == published[key]
    # No width among the cuts, nor a count of heads or groups: the mixer is
    # whole (one group cannot be shared).
    for key in ("hidden_size", "intermediate_size", "shared_intermediate_size",
                "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
                "mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_chunk_size", "num_attention_heads",
                "num_key_value_heads", "embedding_multiplier",
                "attention_multiplier", "residual_multiplier",
                "logits_scaling"):
        assert sizes[key] == published[key], key
    # The release's chunk is kept as published; the program's kernels block
    # the same sum by 128.
    assert (sizes["mamba_chunk_size"], sizes["chunk_size"]) == (256, 128)
    # The floors: one whole period (nine mixers to one attention layer, the
    # published 36 : 4) and an eighth of the vocabulary.
    kinds = published["layer_types"]
    assert len(kinds) == 40 and sizes["layer_types"] == kinds
    assert [i for i, k in enumerate(kinds) if k == "attention"] \
        == [5, 15, 25, 35]
    assert sizes["layers_held"] == list(range(10))
    assert module.layer_plan(sizes) == ref.layer_plan(sizes) \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert sizes["vocab_size"] * 8 == published["vocab_size"]
    for key in ("source", "assumed", "deployment", "reduced_how",
                "recomputed", "fit"):
        assert sizes[key], key
    for key in ("layer", "mamba2", "attention", "ffn", "scalars", "init",
                "optimizer", "precision", "reference_limits", "sequence",
                "data"):
        assert sizes["assumed"][key], key
    assert "four pipeline stages" in sizes["deployment"]
    assert sizes["recompute_blocks"] is True
    assert "TransformerConfig.remat" in sizes["recomputed"]
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == sizes["name"]][0]
    assert entry["reduced"] == REDUCED and entry["source"] == sizes["source"]
    assert os.path.exists(os.path.join(REPO_ROOT, entry["file"]))
    cells = [w for w in bench["workloads"] if w["config"] == sizes["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "wfbp", 1)]
    assert len(bench["configs"]) >= 10 and len(bench["workloads"]) >= 12
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert listed == {"mfu_pct", "step_ms_p95.observed",
                      "wfbp_dispatch_ms_step", "ssd_scan_ms_step",
                      "ssd_scan_roofline_pct", "gqa64_attention_ms_step",
                      "recompute_ms_step", "ssd_scan_fwd_calls_step",
                      "causal_conv_ms_step"}
    for name in listed - {"mfu_pct"}:
        assert os.path.exists(os.path.join(
            REPO_ROOT, "chip_bench/metrics", name + ".json")), name
    own = [m for m in bench["per_layer"]
           if m["name"] in ("recompute_ms_step", "ssd_scan_fwd_calls_step")]
    # PRs 58, 63 and 66 appended their cells, the second to the fourth
    # whose blocks are recomputed, to the first's list.
    assert [(m["layer"], m["moves"], m["workloads"]) for m in own] \
        == [("step builders", "samples_per_s_chip",
             [CELL, "xing4.0-29b-a4b-wfbp-1chip",
              "laguna-s-2.1-wfbp-1chip", "ling-3.0-flash-vl-wfbp-1chip"]),
            ("step builders", "samples_per_s_chip", [CELL])]


def test_the_model_is_the_presets_at_the_cut():
    """The configuration's model is ``granite_4_0_h_micro_config()`` but for
    the depth, the vocabulary, the chunk and ``remat``; its fields carry the
    four scalars as published."""
    from horovod_tpu.models.transformer import granite_4_0_h_micro_config

    module, sizes = _config_module()
    cfg = module.Config(sizes).model.cfg
    whole = granite_4_0_h_micro_config()
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) \
        == (12.0, 0.22, 0.015625, 8.0)
    assert cfg.remat and not whole.remat
    differs = {f for f in cfg.__dataclass_fields__
               if getattr(cfg, f) != getattr(whole, f)}
    assert differs == {"num_layers", "vocab_size", "remat"}
    assert [whole.layer_kind(i) for i in range(10)] \
        == [cfg.layer_kind(i) for i in range(10)]
    assert [whole.layer_kind(i).mixer for i in (5, 15, 25, 35, 36)] \
        == ["attention"] * 4 + ["mamba2"]


def test_batch_and_weights_come_from_the_seed():
    module, sizes = _config_module()
    small = {**sizes, "sequence_length": 64}
    config = module.Config(small)
    batch = jax.jit(config.make_batch)(jax.random.PRNGKey(3))
    again = jax.jit(config.make_batch)(jax.random.PRNGKey(3))
    other = jax.jit(config.make_batch)(jax.random.PRNGKey(4))
    assert np.array_equal(batch["tokens"], again["tokens"])
    assert not np.array_equal(batch["tokens"], other["tokens"])
    assert batch["tokens"].shape == (sizes["per_chip_batch"], 64)
    assert 0 <= int(batch["tokens"].min()) \
        and int(batch["tokens"].max()) < sizes["vocab_size"]
    shapes, aux = jax.eval_shape(config.init, jax.random.PRNGKey(3))
    assert aux == {}
    assert shapes["layer_0"]["mamba"]["in_proj"]["kernel"].shape \
        == (2048, 4096 + 4096 + 128 + 128 + 64)
    assert shapes["layer_0"]["mamba"]["norm"].shape == (4096,)
    assert shapes["layer_5"]["attn"]["q"]["kernel"].shape == (2048, 2048)
    assert shapes["layer_5"]["attn"]["kv"]["kernel"].shape == (2048, 1024)
    assert shapes["layer_9"]["ffn_gate"]["kernel"].shape == (2048, 8192)
    assert shapes["embed"]["embedding"].shape == (12544, 2048)
    assert sorted(shapes) == sorted(
        [f"layer_{i}" for i in range(10)] + ["embed", "ln_f"])


TINY_SIZES = {
    **TINY, "max_position_embeddings": 64, "tie_word_embeddings": True,
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "attention_bias": False, "mamba_proj_bias": False,
    "mamba_conv_bias": True, "position_embedding_type": "nope",
    "normalization_function": "rmsnorm", "hidden_act": "silu",
    "mamba_expand": 2, "intermediate_size": 48, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4, "recompute_blocks": True,
    "name": "tiny-granite", "per_chip_batch": 2, "adamw_learning_rate": 4e-4,
    "warmup_steps": 4, "warmup_start_share": 0.01, "adamw_b1": 0.9,
    "adamw_b2": 0.95, "adamw_eps": 1e-8, "adamw_weight_decay": 0.1,
    "clip_global_norm": 1.0, "logits_rtol": 0.2, "logits_median_rtol": 0.2,
    "logits_float32_rtol": 1e-4}
TINY_CELL = {"module": "granite-4.0-h-micro", **TINY_SIZES}


@pytest.fixture(scope="module")
def seeded_cell():
    """The tiny cell's configuration module, and the weights and the batch
    that seeds 5 and 6 give: made once for the cases that only read them."""
    module, _ = _config_module()
    config = module.Config(TINY_SIZES)
    params, aux = jax.jit(config.init)(jax.random.PRNGKey(5))
    batch = jax.jit(config.make_batch)(jax.random.PRNGKey(6))
    return module, params, aux, batch


def test_fresh_weights_follow_the_model_codes_rules(seeded_cell):
    _, params, _, _ = seeded_cell
    mixer = params["layer_0"]["mamba"]
    dt = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert (dt > 0.99e-3).all() and (dt < 0.101).all()
    a = np.exp(np.asarray(mixer["A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    assert (np.asarray(mixer["D"]) == 1).all()
    assert np.abs(np.asarray(mixer["conv"])).max() <= 0.5
    for kernel in (params["embed"]["embedding"],
                   mixer["out_proj"]["kernel"],
                   params["layer_1"]["attn"]["q"]["kernel"],
                   params["layer_2"]["ffn_down"]["kernel"]):
        assert float(jnp.std(kernel)) == pytest.approx(0.02, rel=0.15)


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
    assert new_aux == {}
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    assert float(loss) == pytest.approx(config.first_loss, rel=0.25)
    errors = config.logits_errors
    assert 0 < errors(params, batch)[0] < limit
    assert 0 < errors(params, batch, jnp.bfloat16)[0] < limit
    assert errors(params, batch, jnp.float32) == (0, 0)
    assert 0 < errors(params, batch, "program_float32")[0] < 1e-5
    for fault in ("no_embedding_multiplier", "no_residual_multiplier",
                  "no_logits_scaling", "norm_before_gate"):
        assert errors(params, batch, jnp.float32, (fault,))[0] > 1e-4, fault


def test_the_step_recomputes_and_follows_the_reference(seeded_cell):
    """``hvd.make_overlapped_train_step(has_aux=True)`` on the program's
    model with every block recomputed beside plain steps of the float32
    reference: three losses agree to the harness's limit."""
    import horovod_tpu as hvd

    module, params, aux, batch = seeded_cell
    config = module.Config(TINY_SIZES)
    assert config.model.cfg.remat
    tx = config.optimizer(1)
    grad = jax.jit(jax.value_and_grad(
        config.reference.make_loss(TINY_SIZES), has_aux=True))
    want_params, want_state, want = params, tx.init(params), []
    for _ in range(3):
        (loss, _), g = grad(want_params, aux, batch)
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


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model (Mamba-2 and attention layers with their dense FFNs, the
    blocks recomputed, nothing in ``aux``) against the plain reference's
    three losses, and the per-layer metrics of the device's op line left out
    where there is none to read."""
    from chip_bench.tests import rehearse

    names = ("ssd_scan_ms_step", "ssd_scan_roofline_pct",
             "gqa64_attention_ms_step", "recompute_ms_step",
             "ssd_scan_fwd_calls_step", "wfbp_dispatch_ms_step")
    files = {"configs/tiny-granite.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-granite-wfbp", "tiny-granite", "wfbp", 1)],
        files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-granite-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    for n in names[:5]:
        assert r0["per_layer"]["tiny." + n] is None, n
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0


def test_the_parents_program_reads_nothing_for_the_new_metrics(tmp_path,
                                                               monkeypatch):
    """The reductions the configuration registers return nothing where the
    window holds no such kernel, no window was traced or no trace file is
    found, and never raise."""
    from chip_bench import readers

    module, sizes = _config_module()

    module.Config(sizes)
    reduction = readers.REDUCTIONS["trace_ssd_scan_roofline_pct"]
    params = {"pattern": "^hvd_ssd_scan"}
    assert reduction(params, {"window": None}) is None

    class Window:
        ops, steps, lo, hi = [1], 2, 0.0, 1.0

        def __init__(self, seconds):
            self.seconds = seconds

        def op_s(self, pattern):
            assert pattern == "^hvd_ssd_scan"
            return self.seconds

    assert reduction(params, {"window": Window(0.0)}) is None
    if jax.local_devices()[0].platform != "tpu":
        with pytest.raises(ValueError, match="peak"):
            reduction(params, {"window": Window(0.01)})
    recompute = readers.REDUCTIONS["trace_recompute_ms_per_step"]
    assert recompute is module.recompute_ms_per_step
    assert recompute({}, {"window": None}) is None
    # A window and no file: this process was not started with ``--out``.
    monkeypatch.setattr(sys, "argv", ["worker.py"])
    assert recompute({}, {"window": Window(0.0)}) is None
    # ``--out`` with no trace under it.
    monkeypatch.setattr(sys, "argv", ["worker.py", "--out", str(tmp_path)])
    assert recompute({}, {"window": Window(0.0)}) is None


def test_the_recomputed_forward_is_read_by_its_name(monkeypatch):
    """``recompute_ms_step`` adds up the operations under
    ``rematted_computation``, by their own ``op_name`` or by the one adopted
    for an instruction of XLA's, cut to the window."""
    from chip_bench import scopes

    module, _ = _config_module()
    Op = scopes.Op
    fwd = "jit(step)/jvp(hvd.loss)/layer_0/hvd.ffn/dot_general"
    again = ("jit(step)/transpose(jvp(hvd.loss))/checkpoint/"
             "rematted_computation/layer_0/hvd.ffn/dot_general")
    bwd = "jit(step)/transpose(jvp(hvd.loss))/layer_0/hvd.ffn/dot_general"
    ops = (Op("fusion.1", 0.0, 1.0, fwd, "", 0, 0),
           Op("fusion.2", 1.0, 3.0, again, "", 0, 0),
           Op("copy.3", 3.0, 3.5, "", "", 0, 0, again),
           Op("hvd_ssd_scan_fwd.4", 3.5, 4.5, again.replace("ffn", "ssm.scan"),
              "", 0, 0),
           Op("fusion.5", 4.5, 6.0, bwd, "", 0, 0),
           Op("fusion.6", 9.0, 12.0, again, "", 0, 0))
    monkeypatch.setattr(scopes, "device_ops", lambda path: ops)

    class Window:
        ops, steps, lo, hi = [1], 2, 0.0, 10.0

    got = module.recompute_ms_per_step({}, {"window": Window(),
                                            "xplane": "a.xplane.pb"})
    assert got == pytest.approx(1e3 * (2.0 + 0.5 + 1.0 + 1.0) / 2)
