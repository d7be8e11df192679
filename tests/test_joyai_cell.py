"""JoyAI-LLM-Flash's configuration and cell
(``chip_bench/configs/joyai-llm-flash``): the published widths and the cut,
data, weights and the bias from the seed, the configuration's own limits on
both heads' logits, the bias and the two cross-entropies through
``hvd.make_overlapped_train_step`` and the cell through the harness at a tiny
size.  ``tests/test_joyai.py`` holds the model and its layers; the two are
apart so that the test workers can share them.
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
from .test_joyai import TINY, some_bias
from .test_joyai import config_module as _module

ref = load_reference("joyai-llm-flash")

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)
# The benchmark's own counts by hand (FLOPs, the attention kernels' cost,
# parameters) run with the tier-1 tests too.
pytest.register_assert_rewrite("chip_bench.tests.test_joyai_config")
from chip_bench.tests.test_joyai_config import (  # noqa: E402,F401
    test_macs_by_hand,
    test_mla_attention_cost_by_hand_and_by_loop,
    test_parameters_by_hand,
)

CELL = "joyai-llm-flash-wfbp-1chip"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


def _config_module():
    from chip_bench import spec

    return _module(), spec.Cell(CELL, root=REPO_ROOT).sizes


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog of architectures here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if r["name"] == "JoyAI-LLM-Flash"][0]


def test_configuration_keeps_every_published_width():
    module, sizes = _config_module()
    row = _catalog_row()
    published = row["config"]
    assert row["source_url"] == sizes["source"]
    assert sizes["reduced"] == REDUCED
    differs = [k for k, v in published.items()
               if sizes.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED)
    assert [sizes[k] for k in REDUCED] == [5, 16, 16160]
    for key in REDUCED:
        assert sizes[key + "_published"] == published[key]
    # No width among the cuts: what is held are counts of layers, experts
    # and rows of the vocabulary.
    for key in ("hidden_size", "q_lora_rank", "kv_lora_rank", "qk_head_dim",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "head_dim", "num_attention_heads", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "num_nextn_predict_layers"):
        assert sizes[key] == published[key], key
    # The floors: the leading dense layer and four sparse ones behind it,
    # 16 experts, an eighth of the vocabulary, the module whole.
    assert sizes["layers_held"] == list(range(5))
    assert module.blocks(sizes) == (1, 5)
    assert ref.expert_blocks(sizes) == [1, 2, 3, 4, 5]
    assert sizes["vocab_size"] * 8 == published["vocab_size"]
    assert sizes["experts_held"] == list(range(16))
    for key in ("source", "assumed", "deployment", "reduced_how"):
        assert sizes[key]
    for key in ("block", "latent_attention", "dense_ffn", "experts",
                "expert_bias", "multi_token_prediction", "init", "optimizer",
                "precision", "reference_limits", "sequence", "fit"):
        assert sizes["assumed"][key], key
    assert "16 chips" in sizes["deployment"]
    assert "GiB" in sizes["assumed"]["fit"]
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == sizes["name"]][0]
    assert entry["reduced"] == REDUCED and entry["source"] == sizes["source"]
    cells = [w for w in bench["workloads"] if w["config"] == sizes["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "wfbp", 1)]
    assert len(bench["configs"]) >= 8 and len(bench["workloads"]) >= 10
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert {"mla_attention_ms_step", "mla_attention_roofline_pct",
            "moe_experts_ms_step", "moe_rows_to_tokens_ms_step",
            "wfbp_dispatch_ms_step", "step_ms_p95.observed"} <= listed


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
    assert sorted(aux) == ["cross_entropy", "expert_bias", "rows_elsewhere",
                           "rows_held", "steps", "tokens_per_expert"]
    assert aux["tokens_per_expert"].shape == (5, 256)
    assert aux["expert_bias"].shape == (5, 256)
    assert aux["expert_bias"].dtype == jnp.float32
    assert aux["cross_entropy"].shape == (2,)
    assert shapes["layer_1"]["router"].shape == (2048, 256)
    assert shapes["layer_5"]["experts_up"].shape == (16, 2048, 768)
    assert shapes["layer_1"]["shared_gate"]["kernel"].shape == (2048, 768)
    assert shapes["layer_0"]["ffn_up"]["kernel"].shape == (2048, 7168)
    assert shapes["layer_0"]["attn"]["q_b"]["kernel"].shape == (1536, 6144)
    assert shapes["layer_0"]["attn"]["kv_a"]["kernel"].shape == (2048, 576)
    assert shapes["layer_0"]["attn"]["kv_b"]["kernel"].shape == (512, 8192)
    assert shapes["mtp_0"]["eh_proj"]["kernel"].shape == (4096, 2048)
    assert shapes["lm_head"]["kernel"].shape == (2048, 16160)
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == 680_439_808                  # 10.89 GB at 16 B a parameter
    # No gradient and no optimizer state exists for the bias.
    state = jax.eval_shape(config.optimizer(1).init, shapes)
    assert not [x for x in jax.tree_util.tree_leaves((shapes, state))
                if x.shape == (5, 256) or x.shape == (256,)]
    # Both heads of fresh weights start near ln(vocabulary).
    assert config.first_loss == pytest.approx(1.3 * np.log(16160))


TINY_SIZES = {
    **TINY, "adamw_learning_rate": 4e-4, "warmup_steps": 4,
    "warmup_start_share": 0.01, "adamw_b1": 0.9, "adamw_b2": 0.95,
    "adamw_eps": 1e-8, "adamw_weight_decay": 0.1, "clip_global_norm": 1.0,
    "logits_rtol": 0.2, "logits_median_rtol": 0.2,
    "logits_float32_rtol": 1e-4, "logits_float32_norm_rtol": 2e-3}
TINY_CELL = {"module": "joyai-llm-flash", **TINY_SIZES}


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


def test_fresh_weights_follow_the_configurations_rules(seeded_cell):
    _, params, aux, _ = seeded_cell
    assert float(jnp.std(params["embed"]["embedding"])) \
        == pytest.approx(TINY_SIZES["embedding_init_std"], rel=0.1)
    assert float(jnp.std(params["layer_1"]["attn"]["q_b"]["kernel"])) \
        == pytest.approx(0.02, rel=0.1)
    assert float(jnp.std(params["mtp_0"]["eh_proj"]["kernel"])) \
        == pytest.approx(0.02, rel=0.1)
    assert (np.asarray(params["mtp_0"]["hnorm"]["scale"]) == 1).all()
    assert not np.asarray(aux["expert_bias"]).any()


@pytest.mark.parametrize("which,limit,passes", [
    ("logits_rtol", 0.2, True), ("logits_rtol", 1e-6, False),
    ("logits_median_rtol", 1e-6, False),
    ("logits_float32_rtol", 1e-9, False),
    ("logits_float32_norm_rtol", 1e-9, False)])
def test_the_configurations_own_limit_holds_both_heads_logits(
        which, limit, passes, capfd, seeded_cell):
    """Behind ``_chip_bench_grad`` the program's logits of both heads are
    held to the float32 reference's, once, before the reference's first
    step: inside the four limits the reference's gradient comes back,
    outside any of them the run ends there."""
    module, params, aux, batch = seeded_cell
    config = module.Config({**TINY_SIZES, which: limit})
    if not passes:
        with pytest.raises(SystemExit, match=f"over the limit {limit:.2e}"):
            config._chip_bench_grad(params, aux, batch)
        return
    (loss, new_aux), grads = config._chip_bench_grad(params, aux, batch)
    said = capfd.readouterr().err
    assert "both heads' logits" in said
    assert said.count("(limit 2.00e-01)") == 2 \
        and "(limit 1.00e-04)" in said and "(limit 2.00e-03)" in said
    config._chip_bench_grad(params, aux, batch)      # checked once
    assert capfd.readouterr().err == ""
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    assert float(loss) == pytest.approx(config.first_loss, rel=0.25)
    assert float(jnp.abs(new_aux["expert_bias"]).max()) \
        == pytest.approx(1e-3)
    assert new_aux["cross_entropy"].shape == (2,)
    assert 0 < config.logits_errors(params, batch)[0] < limit
    assert 0 < config.logits_errors(params, batch, jnp.bfloat16)[0] < limit
    assert config.logits_errors(params, batch, jnp.float32) == (0, 0)
    assert 0 < config.logits_errors(params, batch, "program_float32")[0] \
        < 1e-6
    # Fresh weights at these widths attend almost evenly, so a wrong score
    # moves little: 4.6e-5 and 7.8e-5 of the norm against the float32
    # program's 4e-8 (at the published widths a score's deviation is 0.5).
    bias = some_bias(TINY_SIZES)
    for wrong in ("rope_key_unrotated", "no_kv_norm", "module_reads_token_i",
                  "scale_by_nope"):
        errors = config.logits_errors(params, batch, jnp.float32,
                                      wrong=(wrong,), bias=bias)
        assert min(errors) > 2e-5, (wrong, errors)


def test_the_step_keeps_the_bias_and_both_cross_entropies(seeded_cell):
    """``hvd.make_overlapped_train_step(has_aux=True)`` on the program's
    model beside plain steps of the float32 reference: after three steps the
    bias is not zero, follows the rule over each step's own counts and is
    the reference's but where bf16 moved a count across its mean, the losses
    agree, and ``aux`` holds the step's two cross-entropies, which add up to
    its loss."""
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
    for before, after, loss in zip(seen, seen[1:], got):
        n = (after["tokens_per_expert"]
             - before["tokens_per_expert"]).astype(np.float32)
        assert n.sum() == 3 * 2 * 20 * 3
        np.testing.assert_array_equal(
            after["expert_bias"], before["expert_bias"] + np.float32(1e-3)
            * np.sign(n.mean(axis=1, keepdims=True) - n))
        main, ahead = after["cross_entropy"]
        assert main + 0.3 * ahead == pytest.approx(loss, rel=1e-5)
        assert main == pytest.approx(np.log(64), rel=0.3)
        assert ahead == pytest.approx(np.log(64), rel=0.3)
    bias = seen[-1]["expert_bias"]
    assert np.mean(bias == np.asarray(want_aux["expert_bias"])) > 0.8
    assert np.abs(bias).max() == pytest.approx(3e-3)
    assert int(seen[-1]["steps"]) == 3
    np.testing.assert_allclose(seen[-1]["cross_entropy"],
                               want_aux["cross_entropy"], rtol=3e-4)


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model (latent attention, the dense layer, 4 of 16 experts held
    under the step's ``shard_map``, the module behind the stack, the bias in
    ``aux``) against the plain reference's three losses, and the new
    per-layer metrics left out where there is no device op line to read."""
    from chip_bench.tests import rehearse

    names = ("mla_attention_ms_step", "mla_attention_roofline_pct",
             "moe_experts_ms_step", "moe_rows_to_tokens_ms_step",
             "wfbp_dispatch_ms_step")
    files = {"configs/tiny-joyai.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-joyai-wfbp", "tiny-joyai", "wfbp", 1)],
        files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-joyai-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    assert r0["per_layer"]["tiny.mla_attention_ms_step"] is None
    assert r0["per_layer"]["tiny.mla_attention_roofline_pct"] is None
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0


def test_the_parents_program_reads_nothing_for_the_new_metrics():
    """The reduction the configuration registers returns nothing where the
    window holds no such kernel or no window was traced, and a value where
    it does."""
    from chip_bench import readers

    module, sizes = _config_module()

    module.Config(sizes)
    reduction = readers.REDUCTIONS["trace_mla_attention_roofline_pct"]
    params = {"pattern": "^splash_mha_(fwd|dq|dkv)"}
    assert reduction(params, {"window": None}) is None

    class Window:
        ops, steps = [1], 2

        def __init__(self, seconds):
            self.seconds = seconds

        def op_s(self, pattern):
            assert pattern == "^splash_mha_(fwd|dq|dkv)"
            return self.seconds

    assert reduction(params, {"window": Window(0.0)}) is None
    if jax.local_devices()[0].platform != "tpu":
        with pytest.raises(ValueError, match="peak"):
            reduction(params, {"window": Window(0.01)})
