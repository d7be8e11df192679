"""Nemotron-3-Super-120B-A12B's configuration and cell
(``chip_bench/configs/nemotron-3-super-120b-a12b``): the published widths and
the cut, data, weights and the bias from the seed, the configuration's own
limits on the logits, the bias through ``hvd.make_overlapped_train_step`` and
the cell through the harness at a tiny size.  ``tests/test_nemotron.py`` holds
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
from .test_nemotron import TINY, some_bias

ref = load_reference("nemotron-3-super-120b-a12b")

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)
# The benchmark's own counts by hand (FLOPs, the scan's cost, parameters)
# run with the tier-1 tests too.
pytest.register_assert_rewrite("chip_bench.tests.test_nemotron_config")
from chip_bench.tests.test_nemotron_config import (  # noqa: E402,F401
    test_macs_by_hand,
    test_parameters_by_hand,
    test_ssd_scan_cost_by_hand,
)

CELL = "nemotron-3-super-120b-a12b-wfbp-1chip"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "mamba_num_heads", "n_groups", "num_attention_heads",
           "num_key_value_heads", "num_nextn_predict_layers"]


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
    return [r for r in rows
            if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"][0]


def test_configuration_keeps_every_published_width():
    module, sizes = _config_module()
    row = _catalog_row()
    published = row["config"]
    assert row["source_url"] == sizes["source"]
    assert sizes["reduced"] == REDUCED
    differs = [k for k, v in published.items()
               if sizes.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED)
    assert [sizes[k] for k in REDUCED] == [11, 8, 16384, 16, 1, 4, 1, 0]
    for key in REDUCED:
        assert sizes[key + "_published"] == published[key]
    # No width among the cuts: what is held are counts of layers, experts,
    # rows of the vocabulary, heads and groups of heads.
    for key in ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
                "chunk_size", "conv_kernel", "moe_latent_size",
                "moe_intermediate_size", "intermediate_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "expand"):
        assert sizes[key] == published[key], key
    # The floors: one whole period with five expert layers, 8 experts, an
    # eighth of the vocabulary.
    letters = published["hybrid_override_pattern"]
    assert len(letters) == 88 and sizes["hybrid_override_pattern"] == letters
    assert sizes["layers_held"] == list(range(11))
    assert module.layer_plan(sizes) == list("MEMEMEM*EME") \
        == ref.layer_plan(sizes)
    assert letters.index("*") == 7 and letters[8:19].count("*") == 1
    assert sizes["vocab_size"] * 8 == published["vocab_size"]
    assert sizes["experts_held"] == list(range(8))
    assert sizes["mamba_groups_held"] == [0]
    assert sizes["attention_heads_held"] == [0, 1, 2, 3]
    assert sizes["key_value_heads_held"] == [0]
    # A group of the mixer's heads, and the query heads one KV head serves.
    assert sizes["mamba_num_heads"] * 8 == published["mamba_num_heads"]
    assert sizes["num_attention_heads"] * 8 == published["num_attention_heads"]
    for key in ("source", "assumed", "deployment", "reduced_how"):
        assert sizes[key]
    for key in ("layer", "mamba2", "attention", "experts", "expert_bias",
                "multi_token_prediction", "init", "optimizer", "precision",
                "reference_limits", "sequence", "rows", "data"):
        assert sizes["assumed"][key], key
    assert "64 chips" in sizes["deployment"]
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == sizes["name"]][0]
    assert entry["reduced"] == REDUCED and entry["source"] == sizes["source"]
    cells = [w for w in bench["workloads"] if w["config"] == sizes["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "wfbp", 1)]
    assert len(bench["configs"]) == 7 and len(bench["workloads"]) == 9
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert {"ssd_scan_ms_step", "ssd_scan_roofline_pct", "moe_experts_ms_step",
            "moe_rows_to_tokens_ms_step", "wfbp_dispatch_ms_step",
            "step_ms_p95.observed"} <= listed


def test_batch_weights_and_bias_come_from_the_seed():
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
    assert sorted(aux) == ["expert_bias", "rows_elsewhere", "rows_held",
                           "steps", "tokens_per_expert"]
    assert aux["tokens_per_expert"].shape == (5, 512)
    assert aux["expert_bias"].shape == (5, 512)
    assert aux["expert_bias"].dtype == jnp.float32
    assert shapes["layer_1"]["router"].shape == (4096, 512)
    assert shapes["layer_1"]["experts_up"].shape == (8, 1024, 2688)
    assert shapes["layer_1"]["shared_up"]["kernel"].shape == (4096, 5376)
    assert shapes["layer_0"]["mamba"]["in_proj"]["kernel"].shape \
        == (4096, 1024 + 1024 + 128 + 128 + 16)
    assert shapes["layer_7"]["attn"]["q"]["kernel"].shape == (4096, 512)
    assert shapes["layer_7"]["attn"]["kv"]["kernel"].shape == (4096, 256)
    assert shapes["lm_head"]["kernel"].shape == (4096, 16384)
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == 700_862_960                  # 11.21 GB at 16 B a parameter
    # No gradient and no optimizer state exists for the bias.
    state = jax.eval_shape(config.optimizer(1).init, shapes)
    assert not [x for x in jax.tree_util.tree_leaves((shapes, state))
                if x.shape == (5, 512) or x.shape == (512,)]


def test_fresh_weights_follow_the_model_codes_rules(seeded_cell):
    _, params, _, _ = seeded_cell
    mixer = params["layer_0"]["mamba"]
    dt = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert (dt > 0.99e-3).all() and (dt < 0.101).all()
    a = np.exp(np.asarray(mixer["A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    assert (np.asarray(mixer["D"]) == 1).all()
    assert np.abs(np.asarray(mixer["conv"])).max() <= 0.5
    # rescale_prenorm_residual: uniform in 1 / sqrt(whole inner width), over
    # sqrt(published layers).
    bound = (8 * 8) ** -0.5 / np.sqrt(5)
    out = np.asarray(mixer["out_proj"]["kernel"])
    assert 0.9 * bound < np.abs(out).max() <= bound
    other = np.asarray(params["layer_4"]["mamba"]["out_proj"]["kernel"])
    assert not np.array_equal(out, other)
    assert float(jnp.std(params["embed"]["embedding"])) \
        == pytest.approx(TINY_SIZES["embedding_init_std"], rel=0.1)


TINY_SIZES = {
    **TINY, "max_position_embeddings": 64, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 0, "mlp_hidden_act": "relu2",
    "use_conv_bias": True, "mamba_proj_bias": False,
    "rescale_prenorm_residual": True, "embedding_init_std": 1.0,
    "name": "tiny-nemotron", "per_chip_batch": 2, "adamw_learning_rate": 4e-4,
    "warmup_steps": 4, "warmup_start_share": 0.01, "adamw_b1": 0.9,
    "adamw_b2": 0.95, "adamw_eps": 1e-8, "adamw_weight_decay": 0.1,
    "clip_global_norm": 1.0, "logits_rtol": 0.2, "logits_median_rtol": 0.2,
    "logits_float32_rtol": 1e-4, "logits_float32_norm_rtol": 2e-3}
TINY_CELL = {"module": "nemotron-3-super-120b-a12b", **TINY_SIZES}


@pytest.fixture(scope="module")
def seeded_cell():
    """The tiny cell's configuration module, and the weights, the bias and
    the batch that seeds 5 and 6 give: made once for the cases that only
    read them (they do not depend on a limit)."""
    module, _ = _config_module()
    config = module.Config(TINY_SIZES)
    params, aux = jax.jit(config.init)(jax.random.PRNGKey(5))
    batch = jax.jit(config.make_batch)(jax.random.PRNGKey(6))
    return module, params, aux, batch


@pytest.mark.parametrize("which,limit,passes", [
    ("logits_rtol", 0.2, True), ("logits_rtol", 1e-6, False),
    ("logits_median_rtol", 1e-6, False),
    ("logits_float32_rtol", 1e-9, False),
    ("logits_float32_norm_rtol", 1e-9, False)])
def test_the_configurations_own_limit_holds_the_logits(which, limit, passes,
                                                       capfd, seeded_cell):
    """Behind ``_chip_bench_grad`` the program's logits are held to the
    float32 reference's, once, before the reference's first step: inside the
    four limits the reference's gradient comes back, outside any of them
    the run ends there."""
    module, params, aux, batch = seeded_cell
    config = module.Config({**TINY_SIZES, which: limit})
    if not passes:
        with pytest.raises(SystemExit, match=f"over the limit {limit:.2e}"):
            config._chip_bench_grad(params, aux, batch)
        return
    (loss, new_aux), grads = config._chip_bench_grad(params, aux, batch)
    said = capfd.readouterr().err
    assert said.count("(limit 2.00e-01)") == 2 \
        and "(limit 1.00e-04)" in said and "(limit 2.00e-03)" in said
    config._chip_bench_grad(params, aux, batch)      # checked once
    assert capfd.readouterr().err == ""
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    assert float(loss) == pytest.approx(config.first_loss, rel=0.25)
    assert float(jnp.abs(new_aux["expert_bias"]).max()) \
        == pytest.approx(1e-3)
    assert 0 < config.logits_error(params, batch) < limit
    assert 0 < config.logits_error(params, batch, jnp.bfloat16) < limit
    assert config.logits_error(params, batch, jnp.float32) == 0
    assert 0 < config.logits_error(params, batch, "program_float32") < 1e-5
    bias = some_bias(TINY_SIZES)
    assert config.logits_error(params, batch, jnp.float32,
                               wrong=("norm_over_all",), bias=bias) > 1e-3
    assert config.logits_error(params, batch, jnp.float32,
                               wrong=("no_shared_expert",), bias=bias) > 1e-3


def test_the_step_keeps_the_bias_and_no_gradient_reaches_it(seeded_cell):
    """``hvd.make_overlapped_train_step(has_aux=True)`` on the program's
    model beside plain steps of the float32 reference: after three steps the
    bias is not zero, follows the rule over each step's own counts and is
    the reference's but where bf16 moved a count across its mean, and the
    losses agree."""
    import horovod_tpu as hvd

    module, params, aux, batch = seeded_cell
    config = module.Config(TINY_SIZES)
    tx = config.optimizer(1)
    grad = jax.jit(jax.value_and_grad(
        config.reference.make_loss(TINY_SIZES), has_aux=True))
    want_params, want_aux, want_state = params, aux, tx.init(params)
    want = []
    for _ in range(3):
        (loss, want_aux), g = grad(want_params, want_aux, batch)
        updates, want_state = tx.update(g, want_state, want_params)
        want_params = optax.apply_updates(want_params, updates)
        want.append(float(loss))

    hvd.init()
    step = hvd.make_overlapped_train_step(config.loss, tx, has_aux=True)
    p, s, a = step.init(params, tx.init(params), aux)
    got, seen = [], [jax.tree_util.tree_map(np.asarray, aux)]
    for _ in range(3):
        p, s, a, loss = step(p, s, batch, a)
        got.append(float(loss))
        seen.append(jax.tree_util.tree_map(np.asarray, step.fetch(a)))
    assert got == pytest.approx(want, rel=3e-4)
    for before, after in zip(seen, seen[1:]):
        n = (after["tokens_per_expert"]
             - before["tokens_per_expert"]).astype(np.float32)
        assert n.sum() == 2 * 2 * 20 * 3
        np.testing.assert_array_equal(
            after["expert_bias"], before["expert_bias"] + np.float32(1e-3)
            * np.sign(n.mean(axis=1, keepdims=True) - n))
    bias = seen[-1]["expert_bias"]
    assert np.mean(bias == np.asarray(want_aux["expert_bias"])) > 0.8
    assert np.abs(bias).max() == pytest.approx(3e-3)
    assert int(seen[-1]["steps"]) == 3


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model (Mamba-2, attention and expert layers, 4 of 16 experts
    held under the step's ``shard_map``, the bias in ``aux``) against the
    plain reference's three losses, and the new per-layer metrics left out
    where there is no device op line to read."""
    from chip_bench.tests import rehearse

    names = ("ssd_scan_ms_step", "ssd_scan_roofline_pct",
             "moe_experts_ms_step", "moe_rows_to_tokens_ms_step",
             "wfbp_dispatch_ms_step")
    files = {"configs/tiny-nemotron.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-nemotron-wfbp", "tiny-nemotron", "wfbp", 1)],
        files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-nemotron-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    assert r0["per_layer"]["tiny.ssd_scan_ms_step"] is None
    assert r0["per_layer"]["tiny.ssd_scan_roofline_pct"] is None
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0


def test_the_parents_program_reads_nothing_for_the_new_metrics():
    """The reduction the configuration registers returns nothing where the
    window holds no such kernel or no window was traced, and a value where
    it does."""
    from chip_bench import readers

    module, sizes = _config_module()

    module.Config(sizes)
    reduction = readers.REDUCTIONS["trace_ssd_scan_roofline_pct"]
    params = {"pattern": "^hvd_ssd_scan"}
    assert reduction(params, {"window": None}) is None

    class Window:
        ops, steps = [1], 2

        def __init__(self, seconds):
            self.seconds = seconds

        def op_s(self, pattern):
            assert pattern == "^hvd_ssd_scan"
            return self.seconds

    assert reduction(params, {"window": Window(0.0)}) is None
    if jax.local_devices()[0].platform != "tpu":
        with pytest.raises(ValueError, match="peak"):
            reduction(params, {"window": Window(0.01)})
