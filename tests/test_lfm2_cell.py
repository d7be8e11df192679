"""LFM2-8B-A1B's configuration and cell (``chip_bench/configs/lfm2-8b-a1b``):
the published widths and the cut, data, weights and the bias from the seed,
the configuration's own limits on the logits, the bias through
``hvd.make_overlapped_train_step`` and the cell through the harness at a tiny
size.  ``tests/test_lfm2.py`` holds the model and its layers; the two are
apart so that the test workers can share them.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from .helpers import REPO_ROOT, load_reference
from .test_lfm2 import LAYER_TYPES, TINY, some_bias

ref = load_reference("lfm2-8b-a1b")




def _config_module():
    import sys

    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from chip_bench import spec

    cell = spec.Cell("lfm2-8b-a1b-wfbp-1chip", root=REPO_ROOT)
    return cell.config_module(), cell.sizes


# LiquidAI/LFM2-8B-A1B config.json, copied from the catalog's row.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": LAYER_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def test_configuration_keeps_every_published_width():
    module, sizes = _config_module()
    reduced = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert sizes["reduced"] == reduced
    differs = [k for k, v in PUBLISHED.items() if sizes.get(k, "absent") != v]
    assert sorted(differs) == sorted(reduced)
    assert (sizes["num_hidden_layers"], sizes["num_experts"],
            sizes["vocab_size"]) == (5, 8, 16384)
    # The floors: the dense layer and a whole period of four, 8 experts, an
    # eighth of the vocabulary or more.
    assert sizes["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    for key in reduced:
        assert sizes[key + "_published"] == PUBLISHED[key]
    assert sizes["experts_held"] == list(range(8))
    assert sizes["layers_held"] == [0, 2, 3, 4, 5]
    assert module.layer_plan(sizes) == [("conv", "dense")] + [
        ("full_attention", "experts")] + [("conv", "experts")] * 3
    assert module.layer_plan(sizes) == ref.layer_plan(sizes)
    for key in ("source", "assumed", "deployment", "reduced_how"):
        assert sizes[key]
    for key in ("expert_bias", "auxiliary_losses", "tie_word_embeddings",
                "norms", "init", "reference_limits", "sequence"):
        assert sizes["assumed"][key]
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == sizes["name"]][0]
    assert entry["reduced"] == reduced and entry["source"] == sizes["source"]
    cells = [w for w in bench["workloads"] if w["config"] == sizes["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        ("lfm2-8b-a1b-wfbp-1chip", "wfbp", 1)]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        for row in (r for r in rows if r["name"] == "LFM2-8B-A1B"):
            assert row["config"] == PUBLISHED
            assert row["source_url"] == sizes["source"]


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
    assert aux["tokens_per_expert"].shape == (4, 32)
    assert aux["expert_bias"].shape == (4, 32)
    assert aux["expert_bias"].dtype == jnp.float32
    assert shapes["layer_1"]["router"].shape == (2048, 32)
    assert shapes["layer_1"]["attn"]["q_norm"]["scale"].shape == (64,)
    # No gradient and no optimizer state exists for the bias: neither tree
    # has a leaf of its shape.
    state = jax.eval_shape(config.optimizer(1).init, shapes)
    assert not [x for x in jax.tree_util.tree_leaves((shapes, state))
                if x.shape == (4, 32) or x.shape == (32,)]


TINY_CELL = {
    "module": "lfm2-8b-a1b", **{k: v for k, v in TINY.items()},
    "conv_bias": False, "use_expert_bias": True, "tie_word_embeddings": True,
    "max_position_embeddings": 64, "embedding_init_std": 0.02,
    "per_chip_batch": 2, "adamw_learning_rate": 4e-4, "warmup_steps": 4,
    "warmup_start_share": 0.01, "adamw_b1": 0.9, "adamw_b2": 0.95,
    "adamw_eps": 1e-8, "adamw_weight_decay": 0.1, "clip_global_norm": 1.0,
    "logits_rtol": 0.2, "logits_median_rtol": 0.2,
    "logits_float32_rtol": 1e-4}


@pytest.fixture(scope="module")
def seeded_cell():
    """The tiny cell's configuration module and sizes, and the weights, the
    bias and the batch that seeds 5 and 6 give: made once for the cases that
    only read them (they do not depend on a limit)."""
    module, _ = _config_module()
    sizes = {k: v for k, v in TINY_CELL.items() if k != "module"}
    config = module.Config(sizes)
    params, aux = jax.jit(config.init)(jax.random.PRNGKey(5))
    batch = jax.jit(config.make_batch)(jax.random.PRNGKey(6))
    return module, sizes, params, aux, batch


@pytest.mark.parametrize("which,limit,passes", [
    ("logits_rtol", 0.2, True), ("logits_rtol", 1e-6, False),
    ("logits_median_rtol", 1e-6, False),
    ("logits_float32_rtol", 1e-9, False)])
def test_the_configurations_own_limit_holds_the_logits(which, limit, passes,
                                                       capfd, seeded_cell):
    """Behind ``_chip_bench_grad`` the program's logits are held to the
    float32 reference's, once, before the reference's first step: inside the
    three limits the reference's gradient comes back, outside any of them
    the run ends there.  The reference in a lower precision and with a layer
    wrong is what the limits are set against; in float32 and sound it is
    zero, and the program's model in float32 lies within rounding of it."""
    module, sizes, params, aux, batch = seeded_cell
    config = module.Config({**sizes, which: limit})
    if not passes:
        with pytest.raises(SystemExit, match=f"over the limit {limit:.2e}"):
            config._chip_bench_grad(params, aux, batch)
        return
    (loss, new_aux), grads = config._chip_bench_grad(params, aux, batch)
    said = capfd.readouterr().err
    assert said.count("(limit 2.00e-01)") == 2 and "(limit 1.00e-04)" in said
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
    whole, median = config.logits_errors(params, batch)
    assert 0 < median < whole * 1.5
    assert 0 < config.logits_error(params, batch, "program_float32") < 1e-5
    assert config.logits_error(params, batch, jnp.float32,
                               wrong=("taps_reversed",)) > 1e-3
    bias = some_bias(sizes)
    assert config.logits_error(params, batch, jnp.float32,
                               wrong=("bias_in_weights",), bias=bias) > 1e-3
    assert config.logits_error(params, batch, jnp.float32,
                               wrong=("bias_in_weights",)) == 0


def test_the_step_keeps_the_bias_and_no_gradient_reaches_it(seeded_cell):
    """``hvd.make_overlapped_train_step(has_aux=True)`` on the program's
    model beside plain steps of the float32 reference: after three steps the
    bias is not zero, equals the reference's exactly (a sign rule over whole
    counts) and the losses agree."""
    import horovod_tpu as hvd

    module, sizes, params, aux, batch = seeded_cell
    config = module.Config(sizes)
    tx = config.optimizer(1)
    grad = jax.jit(jax.value_and_grad(config.reference.make_loss(sizes),
                                      has_aux=True))
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
    # Every step moves the bias by the rule over that step's own counts.
    for before, after in zip(seen, seen[1:]):
        n = (after["tokens_per_expert"]
             - before["tokens_per_expert"]).astype(np.float32)
        assert n.sum() == 4 * 2 * 32 * 3
        np.testing.assert_array_equal(
            after["expert_bias"], before["expert_bias"] + np.float32(1e-3)
            * np.sign(n.mean(axis=1, keepdims=True) - n))
        assert np.abs(after["expert_bias"] - before["expert_bias"]).max() > 0
    # The program rounds to bf16 where the reference does not, so a few
    # positions choose another expert and a few counts cross their mean: the
    # bias is the reference's but for those.
    bias = seen[-1]["expert_bias"]
    assert np.mean(bias == np.asarray(want_aux["expert_bias"])) > 0.8
    assert np.abs(bias).max() == pytest.approx(3e-3)
    assert int(seen[-1]["steps"]) == 3


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model (convolution and attention layers, a dense and three
    sparse FFNs with 2 of 8 experts held under the step's ``shard_map``, the
    bias in ``aux``) against the plain reference's three losses, and the new
    per-layer metrics left out where there is no device op line to read."""
    import sys

    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from chip_bench.tests import rehearse

    names = ("short_conv_ms_step", "short_conv_roofline_pct",
             "gqa64_attention_ms_step", "moe_rows_to_tokens_ms_step",
             "wfbp_dispatch_ms_step")
    files = {"configs/tiny-lfm2.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-lfm2-wfbp", "tiny-lfm2", "wfbp", 1)], files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-lfm2-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    assert r0["per_layer"]["tiny.short_conv_ms_step"] is None
    assert r0["per_layer"]["tiny.short_conv_roofline_pct"] is None
    assert r0["per_layer"]["tiny.gqa64_attention_ms_step"] is None
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0
