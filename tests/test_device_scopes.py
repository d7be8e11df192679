"""Device time by block: every operation of a step the framework builds
carries one ``hvd.<block>`` scope (``timeline.scope``, ISSUE 36).

Two np=1 workers compile the same programs: tiny models through
``make_overlapped_train_step`` and, read from XLA's dump, the eager path's own
programs through ``DistributedOptimizer``, once as they are and once with
``jax.named_scope`` patched to a null context (that worker and the cases that
compare the two are ``tests/test_device_scopes_metadata.py``'s, so that the
two minutes each worker takes lie on two test workers).  Each reports, a
program, the
``op_name`` of every instruction of the optimized HLO, the instruction count
and a digest of the text without its metadata; the cases below each assert one
fact of those reports.  The reader's cases (``chip_bench/scopes.py`` on the
recorded traces) live in ``chip_bench/tests/test_scopes.py`` and are imported
here, so that tier-1 runs them.  Counts only: nothing here is a timing.
"""

import json
import tempfile

import pytest

from .helpers import run_distributed

pytest.register_assert_rewrite("chip_bench.tests.test_scopes")
from chip_bench import scopes  # noqa: E402
from chip_bench.tests.test_scopes import (  # noqa: E402,F401
    test_a_matmul_fusion_of_np1_by_hand,
    test_an_instruction_without_a_name_is_adopted_by_the_hlo_around_it,
    test_block_and_direction_on_the_forms_jax_writes,
    test_segments_are_listed_outermost_first,
    test_the_account_of_np4_by_hand,
    test_the_allreduce_of_np4_by_hand,
    test_the_raw_read_is_trace_reduces_op_line,
    test_the_recorded_traces_hold_their_programs,
    test_the_reductions_find_nothing_where_there_is_nothing,
    test_the_reductions_read_what_the_harness_hands_them,
    test_the_row_of_an_operation_xla_made,
    test_the_rows_add_up_to_the_op_line,
    test_the_tool_prints_an_account_that_adds_up,
    test_unknown_scopes_are_named,
)

# The blocks each tiny model must show in both directions.  The attention
# kernels' scopes cannot appear off the TPU: the masks go through the einsum.
_LM = ["loss", "embed", "norm", "attn.proj", "attn.einsum", "head"]
_MOE = ["moe.router", "moe.dispatch", "moe.experts", "moe.combine"]
BLOCKS = {
    "dense": _LM + ["ffn"],
    "moe": _LM + _MOE + ["attn.norm", "attn.rope"],
    "share_mixed": _LM + _MOE + ["attn.rope"],
    "share_blockdiff": _LM + _MOE + ["attn.norm", "attn.rope"],
    "share_conv": _LM + _MOE + ["attn.norm", "attn.rope", "ffn",
                                "conv.proj", "conv.gate"],
    "share_ssm": _LM + _MOE + ["ssm.proj", "ssm.conv", "ssm.scan", "ssm.norm",
                               "moe.latent", "moe.shared"],
    "share_latent": _LM + _MOE + ["attn.latent", "attn.rope", "attn.layout",
                                  "ffn", "moe.shared", "mtp.proj"],
    "share_delta": _LM + _MOE + ["attn.norm", "attn.rope", "attn.gate",
                                 "gdn.proj", "gdn.conv", "gdn.gates",
                                 "gdn.rule", "gdn.norm", "moe.shared",
                                 "moe.shared_gate"],
    "share_kda": _LM + _MOE + ["attn.latent", "attn.rope", "attn.layout",
                               "attn.gate", "ffn", "moe.shared", "kda.proj",
                               "kda.conv", "kda.gate", "kda.rule", "kda.norm",
                               "kda.out"],
    "share_streams": _LM + _MOE + ["attn.latent", "attn.rope", "attn.layout",
                                   "ffn", "moe.shared", "hc.coeff",
                                   "hc.sinkhorn", "hc.pre", "hc.post"],
    "resnet": ["loss", "bn", "resnet.stem", "resnet.stage1", "resnet.stage2",
               "resnet.stage3", "resnet.stage4", "resnet.head"],
}
# The eager path's programs and the scope each lies under.
EAGER = {"hvd_tree_flatten": "fuse", "hvd_local_allreduce": "allreduce",
         "hvd_optimizer_init": "fuse", "hvd_optimizer_update": "optimizer"}

WORKER = """
import collections, contextlib, glob, hashlib, json, re
import flax.linen as nn, jax, jax.numpy as jnp, optax
from horovod_tpu.models.resnet import BottleneckBlock, ResNet
from horovod_tpu.models.transformer import (
    LayerKind, Transformer, hybrid_pattern, joyai_llm_flash_config,
    lfm2_8b_a1b_config, ling_3_0_flash_config, moe_stats,
    nemotron_3_super_config,
    olmoe_1b_7b_config, qwen3_next_80b_a3b_config, sdar_30b_a3b_config,
    smallthinker_21b_a3b_config, tiny_config, xing4_0_29b_a4b_config)

if {null}:
    jax.named_scope = lambda name: contextlib.nullcontext()

def lm(cfg, moe):
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 32), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(0),
                                               tokens)["params"])

    def loss(params, aux, batch):
        if moe:
            logits, state = model.apply({{"params": params}}, batch["tokens"],
                                        mutable=["moe"])
            extra = 0.01 * jnp.sum(
                moe_stats(state["moe"]).load_balancing_loss)
            if cfg.mtp_modules:
                # Both heads enter the loss.
                logits = logits[0] + sum(logits[1])
        else:
            logits, extra = model.apply({{"params": params}},
                                        batch["tokens"]), 0.0
        labels = batch["tokens"][:, :logits.shape[1]]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels).mean() + extra, aux

    return loss, params, {{}}, {{"tokens": tokens}}, optax.adamw(1e-3)

def resnet():
    model = ResNet(stage_sizes=[1, 1, 1, 1], block_cls=BottleneckBlock,
                   num_classes=10, num_filters=8)
    batch = {{"x": jnp.ones((2, 32, 32, 3)), "y": jnp.zeros((2,), jnp.int32)}}
    v = jax.jit(lambda key, x: model.init(key, x, train=True))(
        jax.random.PRNGKey(0), batch["x"])

    def loss(params, aux, batch):
        logits, new = model.apply(
            {{"params": params, "batch_stats": aux}}, batch["x"], train=True,
            mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean(), new["batch_stats"]

    return (loss, v["params"], v["batch_stats"], batch,
            optax.sgd(0.1, momentum=0.9))

share = dict(vocab_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
             d_model=64, d_ff=32, max_len=64, num_experts=8)
MODELS = {{
    "dense": lambda: lm(tiny_config(causal=False), False),
    "moe": lambda: lm(olmoe_1b_7b_config(
        vocab_size=128, num_layers=2, num_heads=4, d_model=64, d_ff=32,
        max_len=32, num_experts=8, experts_per_token=2), True),
    # A global layer without positions, a window layer with RoPE, 2 of 8
    # experts held.
    "share_mixed": lambda: lm(smallthinker_21b_a3b_config(
        **share, head_width=8, experts_per_token=3, experts_held=(1, 6),
        layer_pattern=(LayerKind(0, False), LayerKind(8, True))), True),
    "share_blockdiff": lambda: lm(sdar_30b_a3b_config(
        **share, head_width=16, experts_per_token=2,
        experts_held=(1, 3, 4, 6), block_diffusion=4), True),
    # A convolution layer with the dense FFN, then attention and a
    # convolution with 2 of 8 experts held, chosen by sigmoid and a bias.
    "share_conv": lambda: lm(lfm2_8b_a1b_config(
        **{{**share, "num_layers": 3}}, head_width=16, d_ff_dense=96,
        experts_per_token=2, experts_held=(1, 6),
        layer_pattern=(LayerKind(0, True, "conv", "dense"),
                       LayerKind(0, True, "attention"),
                       LayerKind(0, True, "conv"))), True),
    # Layers that are a mixer alone or experts alone: a Mamba-2 mixer of 2
    # of 4 groups of heads, attention without positions, 2 of 8 experts
    # without a gate on a latent width beside a shared expert.
    "share_ssm": lambda: lm(nemotron_3_super_config(
        **{{**share, "num_layers": 3}}, head_width=16, experts_per_token=2,
        experts_held=(1, 6), moe_latent=16, d_ff_shared=48, mamba_heads=8,
        mamba_head_dim=8, mamba_groups=4, mamba_groups_held=(0, 2),
        mamba_state=16, mamba_chunk=16,
        layer_pattern=hybrid_pattern("M*E")), True),
    # Latent attention (heads of 16 + 8 over 12), a dense layer and one with
    # 2 of 8 experts held beside a gated shared expert, and a prediction
    # module with a block of its own behind them.
    "share_latent": lambda: lm(joyai_llm_flash_config(
        **{{**share, "num_kv_heads": None}}, d_ff_dense=96, d_ff_shared=32,
        experts_per_token=2, experts_held=(1, 6), q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, layer_pattern=(LayerKind(ffn="dense"), LayerKind())),
        True),
    # A Gated DeltaNet layer (2 key heads serving 4 value heads of 8), then
    # gated attention with partial rotary positions, 2 of 8 experts held
    # beside a shared expert behind its gate.
    "share_delta": lambda: lm(qwen3_next_80b_a3b_config(
        **share, head_width=16, d_ff_shared=32, experts_per_token=2,
        experts_held=(1, 6), gdn_key_heads=2, gdn_value_heads=4,
        gdn_key_dim=8, gdn_value_dim=8,
        layer_pattern=(LayerKind(mixer="gated_delta"), LayerKind())), True),
    # Four residual streams under hyper-connections (three Sinkhorn
    # iterations) around latent attention under YaRN: a dense layer and one
    # with 2 of 8 experts held beside a shared expert.
    "share_streams": lambda: lm(xing4_0_29b_a4b_config(
        **{{**share, "num_kv_heads": None}}, d_ff_dense=96, d_ff_shared=32,
        experts_per_token=2, experts_held=(1, 6), q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, hc_sinkhorn_iters=3, yarn_original_max_len=16,
        layer_pattern=(LayerKind(ffn="dense"), LayerKind())), True),
    # A Kimi Delta Attention layer (4 heads of 8, a decay a channel), then
    # latent attention without a query latent under a gate a head, 2 of 8
    # experts held, chosen inside 2 of 4 groups, beside a shared expert.
    "share_kda": lambda: lm(ling_3_0_flash_config(
        **{{**share, "num_kv_heads": None}}, d_ff_dense=96, d_ff_shared=32,
        experts_per_token=2, experts_held=(1, 6), moe_groups=4,
        moe_groups_kept=2, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, kda_head_dim=8,
        layer_pattern=(LayerKind(mixer="kda", ffn="dense"), LayerKind())),
        True),
    "resnet": resnet,
}}

def programs(seen):
    new = sorted(set(glob.glob("{dump}/*after_optimizations.txt")) - seen)
    seen.update(new)
    return new

def describe(text):
    lines = [l for l in text.splitlines()
             if " = " in l and " parameter(" not in l]
    # JAX's own names start with the program's: where XLA made an
    # instruction of an argument alone (a cast, a bitcast) it carries the
    # argument's name, which says nothing of a block.
    names = collections.Counter(
        m.group(1) for m in (re.search(r'op_name="(jit\\([^"]*)"', l)
                             for l in lines) if m)
    # The text without what is not the program: the metadata, the module's
    # own line and the tables of files and stack frames the metadata points
    # into.
    bare = "\\n".join(re.sub(r", metadata=\\{{[^}}]*\\}}", "", l)
                     for l in text.splitlines()[1:]
                     if " = " in l or l.endswith("{{") or l == "}}")
    # XLA numbers its instructions (%convert.431) from counters that threads
    # share: each name takes the order of its first appearance instead.
    order = {{}}
    bare = re.sub(r"%[\\w.-]+",
                  lambda m: order.setdefault(m.group(), f"%{{len(order)}}"),
                  bare)
    return {{"n": len(lines), "names": dict(names),
            "sha": hashlib.sha1(bare.encode()).hexdigest()}}

report, seen = {{}}, set()
for name, build in MODELS.items():
    loss, params, aux, batch, tx = build()
    step = hvd.make_overlapped_train_step(loss, tx, has_aux=True)
    p, s, a = step.init(params, jax.jit(tx.init)(params), aux)
    ctx = step._context()
    with jax.set_mesh(ctx.mesh):
        b = step._lift_batch(ctx, batch)
        report["wfbp:" + name] = describe(
            step._compile(ctx, p, s, b, aux=a).lower(p, s, a, b).compile()
            .as_text())
    if name in ("dense", "resnet"):
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        # A scale other than 1: at one rank the allreduce's program is then
        # a multiplication, and not nothing.
        dopt = hvd.DistributedOptimizer(tx, prescale_factor=0.5)
        state = dopt.init(params)
        for _ in range(2):
            updates, state = dopt.update(grads, state, params)
        jax.block_until_ready(updates)
        for f in programs(seen):
            program = f.split(".jit_")[1].split(".")[0]
            report[f"eager:{{name}}:{{program}}"] = describe(open(f).read())
print("REPORT " + json.dumps(report), flush=True)
"""


def _report(null):
    with tempfile.TemporaryDirectory() as dump:
        out = run_distributed(
            1, WORKER.format(null=null, dump=dump), timeout=480,
            extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"
                       f" --xla_dump_to={dump} --xla_dump_hlo_as_text"
                       " --xla_dump_hlo_module_re=jit_hvd_.*"})[0]
    line = [x for x in out.splitlines() if x.startswith("REPORT ")][-1]
    return json.loads(line[len("REPORT "):])


def report_once(null):
    """:func:`_report`, made once a session: ``tests/
    test_device_scopes_metadata.py`` reads the scoped worker's report too,
    on another test worker, and takes it from the session's directory
    (``tests/conftest.py``) where this file's fixture has written it, or
    writes it there itself."""
    import fcntl
    import os

    session = os.environ.get("HVD_TEST_SESSION_DIR")
    if not session:
        return _report(null)
    path = os.path.join(session, f"device_scopes_report_{int(null)}.json")
    with open(path + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not os.path.exists(path):
            with open(path + ".part", "w") as f:
                json.dump(_report(null), f)
            os.replace(path + ".part", path)
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def scoped():
    return report_once(False)


def _rows(program):
    """{(block, direction): instructions} of one program's report."""
    rows = {}
    for name, n in program["names"].items():
        key = scopes.block(name), scopes.direction(name)
        rows[key] = rows.get(key, 0) + n
    return rows


def _share_scoped(program):
    rows = _rows(program)
    named = sum(rows.values())
    return 1 - sum(n for (block, _), n in rows.items()
                   if block == scopes.UNSCOPED) / named


# -- the vocabulary ------------------------------------------------------------


def test_scope_refuses_a_name_outside_the_vocabulary():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.core.timeline import SCOPES, scope

    with pytest.raises(ValueError, match="nope"):
        scope("nope")
    assert len(set(SCOPES)) == len(SCOPES)

    def f(x):
        with scope("ffn"):
            return x * 2

    text = jax.jit(f).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "hvd.ffn" in text


def test_every_segment_of_every_program_is_in_the_vocabulary(scoped):
    from horovod_tpu.core.timeline import SCOPES

    found = {s for program in scoped.values() for name in program["names"]
             for s in scopes.segments(name)}
    assert found <= set(SCOPES), found - set(SCOPES)
    # What a CPU run can reach of it: everything but the kernels' scopes and
    # the sequence-parallel attentions (``attn.layout`` by latent
    # attention's assembly of its keys, which is no kernel's); the indexer's
    # five are held by ``tests/test_keye.py`` on its own tiny model, which
    # these tiny models do not pay for twice.
    assert set(SCOPES) - found == {
        "attn.flash", "attn.short", "attn.ring", "attn.ulysses",
        "attn.causal", "attn.window", "attn.blockdiff", "attn.sparse",
        "indexer.proj", "indexer.scores", "indexer.choose", "indexer.target",
        "indexer.loss"}


def test_the_rules_of_the_attention_kernels_name_scopes_of_the_vocabulary():
    from horovod_tpu.core.timeline import SCOPES
    from horovod_tpu.kernels import masked_attention
    from horovod_tpu.kernels.blockdiff_attention import BlockDiffusion

    rules = (masked_attention.Causal(), masked_attention.Window(8),
             BlockDiffusion(4), masked_attention.Sparse(8))
    assert [r.scope for r in rules] == [
        "hvd.attn.causal", "hvd.attn.window", "hvd.attn.blockdiff",
        "hvd.attn.sparse"]
    assert all(r.scope.removeprefix("hvd.") in SCOPES for r in rules)


def test_the_kernels_alone_lie_under_the_rules_scope():
    """The scale of q and the four transposes under ``attn.layout``, the
    ``pallas_call``s under the rule's scope, in both directions."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.kernels import masked_attention

    q = jnp.zeros((1, 1024, 2, 128), jnp.bfloat16)
    k = v = jnp.zeros((1, 1024, 1, 128), jnp.bfloat16)
    rule = masked_attention.Window(256)

    def loss(q, k, v):
        return masked_attention.attention(
            q, k, v, rule, interpret=True).astype(jnp.float32).sum()

    by_block = {}

    def walk(jaxpr, outer=""):
        # An equation's name stack is relative to the jaxpr it stands in.
        for eqn in jaxpr.eqns:
            stack = f"{outer}/{eqn.source_info.name_stack}"
            by_block.setdefault(scopes.block(stack), set()).add(
                eqn.primitive.name)
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, stack)

    walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr)
    assert "pallas_call" in by_block["attn.window"]
    assert "transpose" not in by_block["attn.window"]
    assert {"transpose", "mul"} <= by_block["attn.layout"]
    assert "pallas_call" not in by_block["attn.layout"]


# -- coverage ------------------------------------------------------------------


@pytest.mark.parametrize("model", sorted(BLOCKS))
def test_a_compiled_step_carries_a_scope_on_95_pct_of_its_instructions(
        scoped, model):
    program = scoped["wfbp:" + model]
    assert sum(program["names"].values()) > 1000
    assert _share_scoped(program) >= 0.95, sorted(
        (n, name) for name, n in program["names"].items()
        if scopes.block(name) == scopes.UNSCOPED)[-10:]


@pytest.mark.parametrize("model", sorted(BLOCKS))
def test_every_block_of_a_model_runs_forward_and_backward(scoped, model):
    rows = _rows(scoped["wfbp:" + model])
    for block in BLOCKS[model]:
        assert rows.get((block, "fwd"), 0) > 0, (block, "fwd")
        assert rows.get((block, "bwd"), 0) > 0, (block, "bwd")
    # The builder's own: the update, in no direction.
    assert rows.get(("optimizer", ""), 0) > 0
    assert not any(block == "optimizer" and way for block, way in rows)


@pytest.mark.parametrize("program,block", sorted(EAGER.items()))
@pytest.mark.parametrize("model", ["dense", "resnet"])
def test_the_eager_paths_programs_lie_under_their_scope(scoped, model,
                                                        program, block):
    report = scoped[f"eager:{model}:{program}"]
    rows = _rows(report)
    assert rows.get((block, ""), 0) > 0, rows
    assert _share_scoped(report) >= 0.95, report["names"]
    if program == "hvd_optimizer_update":
        # The state's cut and join inside the program, innermost there.
        assert rows.get(("fuse", ""), 0) > 0, rows
