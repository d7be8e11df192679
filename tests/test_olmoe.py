"""OLMoE: the dropless top-k expert layer, the block's options in
``models/transformer.py``, and both against the plain reference
(``chip_bench/configs/olmoe-1b-7b_reference.py``: float32, one dense expert at a time under a
mask, nothing of ``horovod_tpu``) on seeded weights at tiny widths.
"""

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from .helpers import (
    REPO_ROOT,
    load_reference,
    reference_path,
    reserve_port,
    run_distributed,
)

ref = load_reference("olmoe-1b-7b")

TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            vocab_size=128, rms_norm_eps=1e-5, rope_theta=10000.0,
            load_balancing_loss_weight=0.01, router_z_loss_weight=0.001)


def tiny_model(dtype=jnp.float32, **overrides):
    from horovod_tpu.models.transformer import Transformer, olmoe_1b_7b_config

    z = {**TINY, **overrides}
    return Transformer(olmoe_1b_7b_config(
        vocab_size=z["vocab_size"], num_layers=z["num_hidden_layers"],
        num_heads=z["num_attention_heads"], d_model=z["hidden_size"],
        d_ff=z["intermediate_size"], max_len=32,
        num_experts=z["num_experts"],
        experts_per_token=z["num_experts_per_tok"], dtype=dtype,
        moe_data_axis=z.get("moe_data_axis"))), z


def program_loss(model, sizes):
    """The program's model under the loss the reference states."""
    from horovod_tpu.models.transformer import moe_stats

    def loss(params, tokens):
        logits, state = model.apply({"params": params}, tokens,
                                    mutable=["moe"])
        stats = moe_stats(state["moe"])
        s = tokens.shape[1]
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1))
        ce = jnp.sum(nll * (jnp.arange(s) < s - 1)) \
            / (tokens.shape[0] * (s - 1))
        total = ce + sizes["load_balancing_loss_weight"] \
            * jnp.mean(stats.load_balancing_loss) \
            + sizes["router_z_loss_weight"] * jnp.mean(stats.router_z_loss)
        return total, (logits, jnp.sum(stats.tokens_per_expert, axis=1))

    return loss


def seeded(model, seed=0, batch=3, vocab=128):
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, 32), 0,
                                vocab)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(seed), tokens)["params"])
    return params, tokens


def zero_counters(sizes):
    from horovod_tpu.parallel.moe import moe_counters

    return moe_counters(sizes["num_hidden_layers"], sizes["num_experts"])


def skew_to_expert_0(params):
    """Every token's residual stream gets one large common component and the
    first layer's router points expert 0 at it: nearly every token's most
    probable expert is expert 0."""
    d = params["embed"]["embedding"].shape[1]
    u = jnp.ones((d,), jnp.float32)
    params = jax.tree_util.tree_map(lambda x: x, params)
    params["embed"] = {"embedding": params["embed"]["embedding"] + 0.2 * u}
    layer = dict(params["layer_0"])
    layer["router"] = layer["router"].at[:, 0].add(0.5 * u)
    params["layer_0"] = layer
    return params


def rel_err(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


# fp32: the two differ in the order of their sums only.  bf16 (the
# configuration's mode: bf16 activations and matmuls, fp32 parameters, norms,
# router and loss) against the fp32 reference: one bf16 rounding is 2**-9 =
# 2e-3 relative and the values pass some ten of them a layer.  Measured here
# over three seeds of each case: logits 5e-3 to 1.2e-2 of the largest logit,
# loss 3e-6 to 2e-5, the worst gradient leaf 2.5e-2 to 6.6e-2 of its largest
# element where both chose the same experts for every token.  Where a token's
# k-th and (k+1)-th probabilities lie closer than the bf16 residual stream
# resolves, the program takes another expert than the reference (1 to 3 of
# 192 slots on three of those six seeds), and an expert's gradient leaf then
# differs by 0.1 to 0.5: so the bf16 cases run on seeds without such a tie
# and assert the same counts.  The limits are about three times the largest
# measured.  The loss summed in bf16 misses its limit by ten times; a router
# in bf16 changes the counts.
TOLERANCE = {"float32": dict(logits=1e-5, loss=1e-5, grads=1e-5),
             "bfloat16": dict(logits=3e-2, loss=1e-4, grads=2e-1)}


@pytest.mark.parametrize("case,dtype,seed", [
    ("seeded", "float32", 0), ("skewed", "float32", 0),
    ("k_equals_e", "float32", 0), ("seeded", "bfloat16", 2),
    ("skewed", "bfloat16", 0)])
def test_program_agrees_with_the_plain_reference(case, dtype, seed):
    """Logits, loss, router counts and every gradient leaf."""
    overrides = dict(num_experts=4, num_experts_per_tok=4) \
        if case == "k_equals_e" else {}
    model, sizes = tiny_model(getattr(jnp, dtype), **overrides)
    params, tokens = seeded(model, seed=seed)
    if case == "skewed":
        params = skew_to_expert_0(params)
    with jax.default_matmul_precision("highest"):
        (loss, (logits, counts)), grads = jax.jit(jax.value_and_grad(
            program_loss(model, sizes), has_aux=True))(params, tokens)
    (want_loss, aux), want_grads = jax.jit(jax.value_and_grad(
        ref.make_loss(sizes), has_aux=True))(
            params, zero_counters(sizes), {"tokens": tokens})
    want_logits = ref.logits(params, tokens, sizes)
    tol = TOLERANCE[dtype]
    assert rel_err(logits, want_logits) < tol["logits"]
    assert abs(float(loss) - float(want_loss)) < tol["loss"] * float(want_loss)
    n_tokens = tokens.size
    np.testing.assert_array_equal(counts, aux["tokens_per_expert"])
    assert int(aux["steps"]) == 1
    np.testing.assert_array_equal(
        jnp.sum(counts, axis=-1), sizes["num_experts_per_tok"] * n_tokens)
    if case == "skewed":
        # Dropless: expert 0 of layer 0 takes nearly every token (four times
        # the mean load, the most that top-2 of 8 can give), and the answer
        # above still matched.
        assert int(counts[0, 0]) > 0.9 * n_tokens
    errs = jax.tree_util.tree_map(rel_err, grads, want_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errs),
                key=lambda kv: kv[1])
    assert worst[1] < tol["grads"], (jax.tree_util.keystr(worst[0]), worst[1])


@pytest.mark.parametrize("config", [
    "olmoe-1b-7b", "sdar-30b-a3b", "smallthinker-21b-a3b", "lfm2-8b-a1b",
    "nemotron-3-super-120b-a12b"])
def test_reference_stands_alone_at_the_highest_precision(config):
    """One reference a configuration, under ``chip_bench/configs/``, where
    the benchmark decides ``correct`` and these suites load it
    (``helpers.load_reference``).  It is independent of the code under
    test, and it computes under the highest matmul precision."""
    with open(reference_path(config)) as f:
        text = f.read()
    assert "import horovod_tpu" not in text
    assert "from horovod_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


# -- the layer alone ----------------------------------------------------------


def layer_inputs(seed, rows=2, tokens=48, d=32, experts=8, width=16,
                 skew=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (rows, tokens, d), jnp.float32)
    router = 0.5 * jax.random.normal(ks[1], (d, experts), jnp.float32)
    if skew:
        x = x + 1.0
        router = router.at[:, 0].add(skew)
    gate = 0.2 * jax.random.normal(ks[2], (experts, d, width), jnp.float32)
    up = 0.2 * jax.random.normal(ks[3], (experts, d, width), jnp.float32)
    down = 0.2 * jax.random.normal(ks[4], (experts, width, d), jnp.float32)
    return x, router, gate, up, down


def dense_top_k(x, router, gate, up, down, k):
    """Every expert on every token in numpy float64, weighted by the
    probabilities of each token's k largest and by zero elsewhere."""
    x, router, gate, up, down = (np.asarray(a, np.float64)
                                 for a in (x, router, gate, up, down))
    xf = x.reshape(-1, x.shape[-1])
    logits = xf @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    chosen = np.argsort(-probs, axis=-1, kind="stable")[:, :k]
    y = np.zeros_like(xf)
    for e in range(router.shape[1]):
        w = np.where((chosen == e).any(-1), probs[:, e], 0.0)
        g = xf @ gate[e]
        y += w[:, None] * (((g / (1 + np.exp(-g))) * (xf @ up[e])) @ down[e])
    counts = np.bincount(chosen.reshape(-1), minlength=router.shape[1])
    return y.reshape(x.shape), counts, probs


@pytest.mark.parametrize("k,skew,experts", [
    (1, 0.0, 8), (2, 0.0, 8), (3, 0.3, 8), (2, 1.0, 32), (8, 0.0, 8)])
def test_each_token_reaches_its_k_most_probable_experts(k, skew, experts):
    from horovod_tpu.parallel.moe import moe_ffn

    args = layer_inputs(k, skew=skew, experts=experts)
    with jax.default_matmul_precision("highest"):
        y, stats = jax.jit(lambda *a: moe_ffn(*a, k=k, dtype=jnp.float32))(
            *args)
    want, counts, probs = dense_top_k(*args, k)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    n = args[0].shape[0] * args[0].shape[1]
    assert stats.tokens_per_expert.shape == (1, experts)
    np.testing.assert_array_equal(stats.tokens_per_expert[0], counts)
    assert int(stats.tokens_per_expert.sum()) == k * n
    if skew == 1.0:
        # Over ten times the mean load on one expert, and nothing dropped:
        # every one of its rows is in the answer compared above.
        assert counts[0] == n and counts[0] >= 10 * counts.mean()
    balance = experts * np.sum(counts / n * probs.mean(0))
    np.testing.assert_allclose(stats.load_balancing_loss[0], balance,
                               rtol=1e-5)


def test_layer_gradients_match_autodiff_of_the_dense_form():
    """The hand-written cotangents of dispatch and combine (gathers through
    the inverse permutation in place of scatter-adds)."""
    from horovod_tpu.parallel.moe import moe_ffn

    args = layer_inputs(11)
    k = 2

    def dense(x, router, gate, up, down):
        xf = x.reshape(-1, x.shape[-1])
        probs = jax.nn.softmax(xf @ router, axis=-1)
        weights, chosen = jax.lax.top_k(probs, k)
        w = jnp.sum(jnp.where(chosen[..., None] == jnp.arange(8),
                              weights[..., None], 0.0), axis=1)   # [n, E]
        h = jax.nn.silu(jnp.einsum("nd,edf->enf", xf, gate)) \
            * jnp.einsum("nd,edf->enf", xf, up)
        return jnp.einsum("ne,end->nd", w,
                          jnp.einsum("enf,efd->end", h, down)).reshape(x.shape)

    probe = jax.random.normal(jax.random.PRNGKey(5), args[0].shape)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(
            moe_ffn(*a, k=k, dtype=jnp.float32)[0] * probe),
            argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * probe),
                        argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)


def test_rows_of_different_ranks_are_routed_apart_under_a_mesh():
    """Under a mesh in context that binds ``data_axis`` every member routes
    its own rows: the same answer as the rows routed one set at a time, one
    MoEStats entry a member, and no collective but the weights' gradients."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel.moe import moe_ffn

    args = layer_inputs(3, rows=4)
    mesh = Mesh(np.array(jax.devices()[:2]), ("proc",))
    layer = jax.jit(lambda *a: moe_ffn(*a, k=2, data_axis="proc",
                                       dtype=jnp.float32))
    with jax.set_mesh(mesh):
        x = jax.device_put(args[0], NamedSharding(mesh, P("proc")))
        y, stats = layer(x, *args[1:])
        text = layer.lower(x, *args[1:]).compile().as_text()
    assert stats.tokens_per_expert.shape == (2, 8)
    for member in range(2):
        rows = slice(2 * member, 2 * member + 2)
        y1, s1 = moe_ffn(args[0][rows], *args[1:], k=2, dtype=jnp.float32)
        np.testing.assert_allclose(y[rows], y1, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(stats.tokens_per_expert[member],
                                      s1.tokens_per_expert[0])
    for collective in ("all-gather", "all-to-all", "collective-permute",
                       "all-reduce", "reduce-scatter"):
        assert collective + "(" not in text, collective
    # With no mesh in context the axis is unbound: the caller's own rows.
    y0, s0 = layer(*args)
    assert s0.tokens_per_expert.shape == (1, 8)


def test_router_counters_become_gauges():
    from horovod_tpu.core import metrics
    from horovod_tpu.parallel.moe import (
        count_routing,
        moe_counters,
        publish_routing,
    )

    counters = moe_counters(2, 4)
    step = jax.jit(count_routing)
    counters = step(counters, jnp.array([[4, 4, 4, 4], [10, 2, 2, 2]]))
    counters = step(counters, jnp.array([[4, 4, 4, 4], [6, 6, 2, 2]]))
    out = publish_routing(counters)
    assert out == {"max_load_ratio": [1.0, 2.0], "steps": 2}
    reg = metrics.registry
    assert reg.get_gauge("moe_max_load_ratio", layer="1") == 2.0
    assert reg.get_gauge("moe_routed_tokens_per_step") == 32.0  # 2 layers
    assert reg.get_gauge("moe_steps") == 2


# -- the model's other options ------------------------------------------------


def test_causal_mask_comes_from_iota_and_matches_tril():
    from horovod_tpu.models.transformer import _scaled_dot_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 24, 3, 8)) for kk in ks)
    got = _scaled_dot_attention(q, k, v, True, 8)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 8 ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((24, 24), bool)), scores, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    text = jax.jit(lambda q, k, v: _scaled_dot_attention(
        q, k, v, True, 8)).lower(q, k, v).as_text()
    # No [s, s] constant in the program (16 MB at s = 4096): large constants
    # print as hex blobs.
    assert "iota" in text and 'dense<"0x' not in text


# -- through both entry points ------------------------------------------------

_TWO_RANKS = """
import re, sys
sys.path.insert(0, {tests!r})
import flax.linen as nn
import jax, jax.numpy as jnp, optax
from tests.test_olmoe import tiny_model, program_loss, zero_counters
from tests.helpers import load_reference
ref = load_reference("olmoe-1b-7b")
from horovod_tpu.parallel.moe import count_routing

model, sizes = tiny_model(moe_data_axis=hvd.PROCESS_AXIS)
tokens = [jax.random.randint(jax.random.PRNGKey(10 + r), (2, 32), 0, 128)
          for r in range(size)]
params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), tokens[0])["params"])
# Clipped SGD with momentum: linear in the gradient past the clip, so the
# parameters can be held tightly (Adam divides by the gradient's own size and
# turns a rounding difference on a near-zero element into a whole step).
tx = optax.chain(optax.clip_by_global_norm(1.0),
                 optax.sgd(0.5, momentum=0.9))
inner = program_loss(model, sizes)

def loss_fn(p, aux, batch):
    total, (_, counts) = inner(p, batch["tokens"])
    return total, count_routing(aux, counts)

# The plain reference stepped on the mean gradient of the ranks' batches.
ref_grad = jax.jit(jax.value_and_grad(ref.make_loss(sizes), has_aux=True))
want_p, want_s, want_losses = params, tx.init(params), []
auxs = [zero_counters(sizes)] * size
for _ in range(3):
    outs = [ref_grad(want_p, auxs[r], {{"tokens": tokens[r]}})
            for r in range(size)]
    auxs = [o[0][1] for o in outs]
    want_losses.append(sum(float(o[0][0]) for o in outs) / size)
    g = jax.tree_util.tree_map(lambda *x: sum(x) / size, *[o[1] for o in outs])
    u, want_s = tx.update(g, want_s, want_p)
    want_p = optax.apply_updates(want_p, u)

def close(got, want, what):
    err = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)),
        got, want)
    worst = max(jax.tree_util.tree_leaves(err))
    assert worst < 1e-5, (what, worst, err)
"""

_WFBP = _TWO_RANKS + """
step = hvd.make_overlapped_train_step(loss_fn, tx, has_aux=True)
p, s, a = step.init(params, tx.init(params), zero_counters(sizes))
batch = {{"tokens": tokens[rank]}}
losses = []
for _ in range(3):
    p, s, a, loss = step(p, s, batch, a)
    losses.append(float(np.asarray(step.fetch(loss))))
assert np.allclose(losses, want_losses, rtol=1e-5), (losses, want_losses)
close(step.fetch(p), want_p, "parameters")
# The compiled step holds all-reduces and no other collective.
ctx = step._context()
with jax.set_mesh(ctx.mesh):
    text = step._step.lower(
        p, s, a, step._lift_batch(ctx, batch)).compile().as_text()
# The counters rode aux: every rank holds the sum over ranks and steps.
a = step.fetch(a)
assert int(a["steps"]) == 3
np.testing.assert_array_equal(a["tokens_per_expert"],
                              sum(x["tokens_per_expert"] for x in auxs))
found = set(re.findall(r"= \\S+ (all-reduce|all-gather|all-to-all|"
                       r"collective-permute|reduce-scatter|"
                       r"collective-broadcast)(?:-start)?\\(", text))
assert found == {{"all-reduce"}}, found
print("OLMOE_WFBP_OK", rank, flush=True)
"""

_EAGER = _TWO_RANKS + """
dopt = hvd.DistributedOptimizer(tx)
p, s, a = params, dopt.init(params), zero_counters(sizes)
grad = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
for i in range(3):
    (loss, a), g = grad(p, a, {{"tokens": tokens[rank]}})
    u, s = dopt.update(g, s, p)
    p = optax.apply_updates(p, u)
close(p, want_p, "parameters")
np.testing.assert_array_equal(a["tokens_per_expert"],
                              auxs[rank]["tokens_per_expert"])
print("OLMOE_EAGER_OK", rank, flush=True)
"""


def _xla_env():
    return {"HOROVOD_DATA_PLANE": "xla",
            "HOROVOD_JAX_COORDINATOR": f"127.0.0.1:{reserve_port()}"}


@pytest.mark.parametrize("body,token", [(_WFBP, "OLMOE_WFBP_OK"),
                                        (_EAGER, "OLMOE_EAGER_OK")],
                         ids=["make_overlapped_train_step",
                              "DistributedOptimizer"])
def test_two_ranks_step_the_tiny_model_to_the_references_parameters(body,
                                                                    token):
    """np = 2, one CPU device a process, three clipped SGD steps: the loss and the
    parameters are those of the plain reference stepped on the ranks' mean
    gradient; the one-program step holds gradient all-reduces only."""
    out = run_distributed(2, body.format(tests=REPO_ROOT), timeout=300,
                          extra_env=_xla_env())
    for r, o in enumerate(out):
        assert f"{token} {r}" in o


# -- the benchmark's configuration -------------------------------------------


def _config_module():
    """The cell's configuration as the harness finds it: (module, sizes)."""
    import sys

    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from chip_bench import spec

    cell = spec.Cell("olmoe-1b-7b-wfbp-1chip", root=REPO_ROOT)
    return cell.config_module(), cell.sizes


PUBLISHED = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
             "hidden_size": 2048, "intermediate_size": 1024,
             "max_position_embeddings": 4096, "model_type": "olmoe",
             "norm_topk_prob": False, "num_attention_heads": 16,
             "num_experts": 64, "num_experts_per_tok": 8,
             "num_hidden_layers": 16, "num_key_value_heads": 16,
             "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
             "tie_word_embeddings": False, "vocab_size": 50304}


def test_configuration_keeps_every_published_width():
    module, sizes = _config_module()
    assert sizes["reduced"] == ["num_hidden_layers"]
    differs = [k for k, v in PUBLISHED.items() if sizes.get(k, "absent") != v]
    assert differs == ["num_hidden_layers"] and sizes["num_hidden_layers"] == 1
    for key in ("source", "assumed", "deployment"):
        assert sizes[key]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        # The catalog on some boxes has no such row; PUBLISHED pins the
        # widths either way.
        for row in (r for r in rows
                    if r["name"] == "OLMoE-1B-7B-0125-Instruct"):
            assert row["config"] == PUBLISHED
            assert row["source_url"] == sizes["source"]


def test_flops_and_grouped_cost_come_from_the_shapes():
    module, sizes = _config_module()
    s = sizes["sequence_length"]
    per_token = {k: v / s / 1e6 for k, v in module.matmul_macs(sizes).items()}
    assert per_token == pytest.approx(
        {"qkvo": 16.78, "attention_scores": 8.39, "attention_values": 8.39,
         "router": 0.13, "experts": 50.33, "head": 103.02}, abs=0.01)
    assert module.attention_causal_half_macs(sizes) / s / 1e6 == \
        pytest.approx(8.39, abs=0.01)
    assert module.flops_per_sample(sizes) == 6 * sum(
        module.matmul_macs(sizes).values())
    # 8 routed rows a token, not 64 experts a token.
    rows = sizes["per_chip_batch"] * s * 8
    operations, moved = module.grouped_matmul_cost(sizes, rows)
    assert operations == 9 * 2 * rows * 2048 * 1024
    assert module.grouped_matmul_cost(sizes, 2 * rows)[0] == 2 * operations
    assert moved == 9 * 2 * (rows * 2048 + rows * 1024 + 64 * 2048 * 1024)
    assert sizes["per_chip_batch"] * s * 8 // 64 == 1536   # rows an expert
    model = module.Config(sizes).model
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))["params"]
    n = sum(x.size for x in jax.tree_util.tree_leaves(nn.meta.unbox(shapes)))
    assert n == 625_616_896


TINY_CELL = {
    "module": "olmoe-1b-7b", "attention_bias": False, "hidden_size": 64,
    "intermediate_size": 32, "max_position_embeddings": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 2, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 128,
    "sequence_length": 32, "per_chip_batch": 2,
    "load_balancing_loss_weight": 0.01, "router_z_loss_weight": 0.001,
    "adamw_learning_rate": 4e-4, "warmup_steps": 4, "warmup_start_share": 0.01,
    "adamw_b1": 0.9, "adamw_b2": 0.95,
    "adamw_eps": 1e-8, "adamw_weight_decay": 0.1, "clip_global_norm": 1.0}


def test_the_cell_runs_through_the_harness_at_a_tiny_size(tmp_path):
    """``worker.py`` under ``hvdrun -np 1`` on the CPU: the wfbp step of the
    program's model against the plain reference's three losses, the router's
    counters in ``aux``, and the new per-layer metrics left out where there
    is no device op line to read."""
    import sys

    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from chip_bench.tests import rehearse

    # The cell's own metrics name their cell, so the tiny cell reads them
    # under names of its own, from copies of their files.
    names = ("moe_experts_ms_step", "moe_experts_roofline_pct",
             "wfbp_dispatch_ms_step")
    files = {"configs/tiny-olmoe.json": TINY_CELL}
    for n in names:
        with open(os.path.join(REPO_ROOT, "chip_bench/metrics", n + ".json")) \
                as f:
            files[f"metrics/tiny.{n}.json"] = json.load(f)
    root = rehearse.make_root(
        tmp_path, [("tiny-olmoe-wfbp", "tiny-olmoe", "wfbp", 1)], files=files,
        per_layer=[{"name": "tiny." + n, "unit": "x", "better": "lower",
                    "source": "device_trace", "layer": "kernel",
                    "moves": "samples_per_s_chip"} for n in names])
    r0 = rehearse.run_worker(root, "tiny-olmoe-wfbp", 1, trace=1)[0]
    assert all(r0["checks"].values()), r0["checks"]
    assert r0["losses"][:3] == pytest.approx(r0["reference_losses"], rel=3e-4)
    assert r0["failed_steps"] == 0 and r0["deltas"]["compiles"] == 0
    assert r0["per_layer"]["tiny.moe_experts_ms_step"] is None
    assert r0["per_layer"]["tiny.moe_experts_roofline_pct"] is None
    assert r0["per_layer"]["tiny.wfbp_dispatch_ms_step"] > 0
