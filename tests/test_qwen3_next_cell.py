"""Qwen3-Next-80B-A3B's configuration and cell
(``chip_bench/configs/qwen3-next-80b-a3b``): the published widths and the
cut, data and weights from the seed, the configuration's own limits on the
logits, the model through ``hvd.make_overlapped_train_step`` and the cell
through the harness at a tiny size.  ``tests/test_qwen3_next.py`` holds the
model and its layers; the two are apart so that the test workers can share
them.
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
from .test_qwen3_next import TINY

ref = load_reference("qwen3-next-80b-a3b")

if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)
# The benchmark's own counts by hand (FLOPs, the rule's cost, parameters)
# run with the tier-1 tests too.
pytest.register_assert_rewrite("chip_bench.tests.test_qwen3_next_config")
from chip_bench.tests.test_qwen3_next_config import (  # noqa: E402,F401
    test_gated_delta_cost_by_hand,
    test_macs_by_hand,
    test_parameters_by_hand,
)

CELL = "qwen3-next-80b-a3b-wfbp-1chip"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


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
    return [r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct"][0]


def test_configuration_keeps_every_published_width():
    module, sizes = _config_module()
    row = _catalog_row()
    published = row["config"]
    assert row["source_url"] == sizes["source"]
    assert sizes["reduced"] == REDUCED
    differs = [k for k, v in published.items()
               if sizes.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED)
    assert [sizes[k] for k in REDUCED] == [4, 16, 18992]
    for key in REDUCED:
        assert sizes[key + "_published"] == published[key]
    # No width among the cuts: what is held are counts of layers, experts
    # and rows of the vocabulary.
    for key in ("hidden_size", "head_dim", "linear_key_head_dim",
                "linear_value_head_dim", "linear_num_key_heads",
                "linear_num_value_heads", "linear_conv_kernel_dim",
                "num_attention_heads", "num_key_value_heads",
                "partial_rotary_factor", "moe_intermediate_size",
                "shared_expert_intermediate_size", "intermediate_size",
                "num_experts_per_tok", "full_attention_interval"):
        assert sizes[key] == published[key], key
    # The floors: one whole period, 16 experts, an eighth of the vocabulary.
    assert sizes["layers_held"] == [0, 1, 2, 3]
    assert module.layer_plan(sizes) == list("DDD*")
    assert [ref.is_attention(sizes, i) for i in range(4)] \
        == [False, False, False, True]
    assert sizes["vocab_size"] * 8 == published["vocab_size"]
    assert sizes["experts_held"] == list(range(16))
    for key in ("source", "assumed", "deployment", "reduced_how"):
        assert sizes[key]
    for key in ("block", "gated_delta_net", "gated_attention", "experts",
                "auxiliary_loss", "multi_token_prediction", "init",
                "optimizer", "precision", "reference_limits", "sequence",
                "fit"):
        assert sizes["assumed"][key], key
    assert "32 chips" in sizes["deployment"]
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == sizes["name"]][0]
    assert entry["reduced"] == REDUCED and entry["source"] == sizes["source"]
    cells = [w for w in bench["workloads"] if w["config"] == sizes["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "wfbp", 1)]
    # Every `why` of the file, and not this cell's alone: the driver refuses
    # the whole benchmark over one of 201 characters.
    for item in bench["configs"] + bench["workloads"]:
        assert 1 <= len(item["why"]) <= 200 and item["why"].isprintable(), \
            item["name"]
    # The one exact count of the benchmark (the newest configuration's test
    # holds it; the older cells' tests count at least their own).
    assert len(bench["configs"]) >= 9 and len(bench["workloads"]) >= 11
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert {"gated_delta_ms_step", "gated_delta_roofline_pct",
            "gqa256_attention_ms_step", "moe_experts_ms_step",
            "moe_rows_to_tokens_ms_step", "wfbp_dispatch_ms_step",
            "step_ms_p95.observed"} <= listed
    # And on no other cell: the three new metrics read this cell alone.
    for m in bench["per_layer"]:
        if m["name"] in ("gated_delta_ms_step", "gated_delta_roofline_pct",
                         "gqa256_attention_ms_step"):
            assert m["workloads"] == [CELL]


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
    assert sorted(aux) == ["rows_elsewhere", "rows_held", "steps",
                           "tokens_per_expert"]
    assert aux["tokens_per_expert"].shape == (4, 512)
    assert shapes["layer_1"]["router"].shape == (2048, 512)
    assert shapes["layer_1"]["experts_up"].shape == (16, 2048, 512)
    assert shapes["layer_1"]["shared_up"]["kernel"].shape == (2048, 512)
    assert shapes["layer_1"]["shared_expert_gate"]["kernel"].shape \
        == (2048, 1)
    assert shapes["layer_0"]["gdn"]["in_proj_qkvz"]["kernel"].shape \
        == (2048, 2048 + 2048 + 4096 + 4096)
    assert shapes["layer_0"]["gdn"]["conv"].shape == (8192, 4)
    assert shapes["layer_3"]["attn"]["q"]["kernel"].shape == (2048, 8192)
    assert shapes["layer_3"]["attn"]["kv"]["kernel"].shape == (2048, 1024)
    assert shapes["lm_head"]["kernel"].shape == (2048, 18992)
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == 424_340_544                  # 6.79 GB at 16 B a parameter
    cfg = config.model.cfg
    assert (cfg.partial_rotary_factor, cfg.rope_theta) == (0.25, 1e7)
    assert cfg.norm_offset and cfg.attention_gate and cfg.shared_expert_gate


def test_a_cut_that_is_no_whole_period_is_refused():
    module, sizes = _config_module()
    with pytest.raises(ValueError, match="whole periods"):
        module.Config({**sizes, "num_hidden_layers": 3,
                       "layers_held": [0, 1, 2]})
    with pytest.raises(ValueError, match="experts held"):
        module.Config({**sizes, "num_experts": 32})


TINY_SIZES = {
    **TINY, "max_position_embeddings": 256, "tie_word_embeddings": False,
    "mlp_only_layers": [], "decoder_sparse_step": 1, "rope_scaling": None,
    "use_sliding_window": False, "hidden_act": "silu",
    "layers_held": [0, 1, 2, 3],
    "embedding_init_std": 1.0, "name": "tiny-qwen3-next", "per_chip_batch": 2,
    "adamw_learning_rate": 4e-4, "warmup_steps": 4,
    "warmup_start_share": 0.01, "adamw_b1": 0.9, "adamw_b2": 0.95,
    "adamw_eps": 1e-8, "adamw_weight_decay": 0.1, "clip_global_norm": 1.0,
    "logits_rtol": 0.2, "logits_median_rtol": 0.2,
    "logits_float32_rtol": 1e-4}
TINY_CELL = {"module": "qwen3-next-80b-a3b", **TINY_SIZES}


@pytest.fixture(scope="module")
def seeded_cell():
    """The tiny cell's configuration module, and the weights and the batch
    that seeds 5 and 6 give: made once for the cases that only read them
    (they do not depend on a limit)."""
    module, _ = _config_module()
    config = module.Config(TINY_SIZES)
    params, aux = jax.jit(config.init)(jax.random.PRNGKey(5))
    batch = jax.jit(config.make_batch)(jax.random.PRNGKey(6))
    return module, params, aux, batch


def test_fresh_weights_follow_the_releases_rules(seeded_cell):
    _, params, _, _ = seeded_cell
    gdn = params["layer_0"]["gdn"]
    assert (np.asarray(gdn["dt_bias"]) == 1).all()
    assert (np.exp(np.asarray(gdn["A_log"])) < 16).all()
    assert (np.asarray(gdn["norm"]) == 1).all()
    assert not np.asarray(params["layer_0"]["ln1"]["scale"]).any()
    assert not np.asarray(params["ln_f"]["scale"]).any()
    assert float(jnp.std(params["embed"]["embedding"])) \
        == pytest.approx(TINY_SIZES["embedding_init_std"], rel=0.1)
    assert float(jnp.std(params["lm_head"]["kernel"])) \
        == pytest.approx(0.02, rel=0.1)


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
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    assert float(loss) == pytest.approx(config.first_loss, rel=0.25)
    assert int(new_aux["steps"]) == 1
    assert 0 < config.logits_errors(params, batch)[0] < limit
    assert 0 < config.logits_errors(params, batch, jnp.bfloat16)[0] < limit
    assert config.logits_errors(params, batch, jnp.float32) == (0, 0)
    exact = config.logits_errors(params, batch, "program_float32")
    assert 0 < exact[0] < 1e-5 and exact[1] < 1e-5
    # Fresh weights at these widths leave the delta term faint (the taps at
    # 0.02 shrink v, and with it the state): still a thousand times the
    # program's own distance.
    for wrong in ("no_delta", "no_shared_gate"):
        assert config.logits_errors(
            params, batch, jnp.float32, wrong=(wrong,))[1] \
            > max(2e-5, 1000 * exact[1]), wrong


def test_the_model_steps_through_the_one_program_like_the_reference(
        seeded_cell):
    """``hvd.make_overlapped_train_step(has_aux=True)`` on the program's
    model beside plain steps of the float32 reference: three losses agree
    inside the harness's limit and the counters count every routed row."""
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
    got = []
    for _ in range(3):
        p, s, a, loss = step(p, s, batch, a)
        got.append(float(loss))
    assert got == pytest.approx(want, rel=3e-4)
    assert got[2] < got[0]
    seen = jax.tree_util.tree_map(np.asarray, step.fetch(a))
    assert int(seen["steps"]) == 3
    assert seen["tokens_per_expert"].sum() == 3 * 4 * 2 * 70 * 3
    assert (seen["rows_held"] + seen["rows_elsewhere"]
            == 3 * 2 * 70 * 3).all()


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model (three Gated DeltaNet layers and a gated attention
    layer, 4 of 16 experts held under the step's ``shard_map``) against the
    plain reference's three losses, and the new per-layer metrics left out
    where there is no device op line to read."""
    from chip_bench.tests import rehearse

    names = ("gated_delta_ms_step", "gated_delta_roofline_pct",
             "gqa256_attention_ms_step", "moe_experts_ms_step",
             "moe_rows_to_tokens_ms_step", "wfbp_dispatch_ms_step")
    files = {"configs/tiny-qwen3-next.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-qwen3-next-wfbp", "tiny-qwen3-next", "wfbp", 1)],
        files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-qwen3-next-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    assert r0["per_layer"]["tiny.gated_delta_ms_step"] is None
    assert r0["per_layer"]["tiny.gated_delta_roofline_pct"] is None
    assert r0["per_layer"]["tiny.gqa256_attention_ms_step"] is None
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0


def test_the_parents_program_reads_nothing_for_the_new_metrics():
    """The reduction the configuration registers returns nothing where the
    window holds no such kernel or no window was traced, and a value where
    it does."""
    from chip_bench import readers

    module, sizes = _config_module()

    module.Config(sizes)
    reduction = readers.REDUCTIONS["trace_gated_delta_roofline_pct"]
    params = {"pattern": "^hvd_gated_delta"}
    assert reduction(params, {"window": None}) is None

    class Window:
        ops, steps = [1], 2

        def __init__(self, seconds):
            self.seconds = seconds

        def op_s(self, pattern):
            assert pattern == "^hvd_gated_delta"
            return self.seconds

    assert reduction(params, {"window": Window(0.0)}) is None
    if jax.local_devices()[0].platform != "tpu":
        with pytest.raises(ValueError, match="peak"):
            reduction(params, {"window": Window(0.01)})
    for name in ("gated_delta_ms_step", "gated_delta_roofline_pct"):
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics",
                               name + ".json")) as f:
            assert json.load(f)["readers"][0]["pattern"] == "^hvd_gated_delta"


def test_the_gauges_of_the_two_mixers_come_from_the_shapes():
    """``publish_attention`` counts the one attention layer's causal pairs
    and ``publish_gated_delta`` the rule's chunks: three layers of 32 value
    heads of 128 chunks at the cell's sizes."""
    from horovod_tpu.core import metrics
    from horovod_tpu.models.transformer import (
        publish_attention,
        publish_gated_delta,
    )

    module, sizes = _config_module()
    cfg = module.Config(sizes).model.cfg
    assert publish_attention(cfg, 8192) == {
        "window": 0, "global": 8192 * 8193 // 2}
    assert publish_gated_delta(cfg, 8192) == 3 * 32 * 128
    assert metrics.registry.get_gauge("gdn_chunks_per_step") == 3 * 32 * 128
    assert publish_gated_delta(cfg, 70, sequences=2) == 3 * 2 * 32 * 2
