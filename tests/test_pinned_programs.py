"""One rule, one table: default arguments build the parent's parameter tree
and lower to the parent's text.

A rewrite of shared code (``models/transformer.py``, ``parallel/moe.py``, the
kernels' wrappers) leaves the program of every model it did not mean to
change as it was.  This module holds that to a digest a program: ``PINS``
names each pinned program, ``digest`` hashes, and each case builds its
program from the tiny models of the suites (``test_olmoe.tiny_model`` and
its siblings) at abstract shapes, so nothing here compiles or runs.  A PR
that means to change a program edits one row and says in the row's comment
whose text the new digest is; a new model adds its rows.
"""

import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from . import test_joyai, test_lfm2, test_ling, test_nemotron, test_olmoe, \
    test_qwen3_next, test_xing, \
    test_router_product, test_sdar, test_smallthinker

RECORDED_WITH = "0.9.0"     # the text of a lowering is the JAX version's own

pytestmark = pytest.mark.skipif(
    jax.__version__ != RECORDED_WITH,
    reason=f"recorded with JAX {RECORDED_WITH}")

PINS = {
    # BERT-large on PR 26's tree: the parameter tree (paths, shapes, dtypes)
    # and the StableHLO text of the forward pass on 2 x 512 tokens.
    "bert_large/tree":
    "7a83d0db599ba93df161711175c48928b272ebe34eed8a54b5c37a65b9f3c743",
    "bert_large/forward":
    "5cc4b71051904c78462d6251b64486ec73f6d157015fca7e29aafccdd5f5ca32",
    # bf16 programs.  Taken on PR 30's tree (84b7007): test_olmoe.py's tiny
    # OLMoE model, loss and gradients, on (3, 32) tokens; moe_ffn with every
    # expert held at [2, 16, 64] x 8 experts of width 32, k = 2, gradients
    # of all five operands.  On PR 34's parent (fbf0cef): test_sdar.py's
    # tiny SDAR model, loss and gradients, on 2 x 16 noised tokens (the
    # einsum under the block mask); the jaxpr of the block-diffusion
    # kernel's call as a TPU gets it, forward and the three gradients, 4
    # query heads on 2 KV heads of 128 at two tiles (the kernels' names,
    # tiles, grids, layout and scale are in that text).  PR 44 gave the
    # wrapper a backward kernel of its own: ``blockdiff_kernel_call`` is
    # that PR's text, and ``blockdiff_forward_call`` the forward kernel's
    # equation in it without the line of profiler metadata that lists the
    # library's block sizes: on PR 44's parent (1e203e9) that equation
    # hashes to the same.  ``sdar_tiny_step`` is PR 44's too
    # (``BlockDiffusion.allowed`` by one code a position).  PR 45 moved the
    # four that hold a router over bf16 rows (three bf16 products over the
    # split weights, ``parallel/moe.py::_rows_dot``), and PR 48 moved them
    # again (the k chosen scores read, and their cotangent written, by a
    # compare and not by a gather and a scatter, ``_chosen``): they are PR
    # 48's text.  ``moe_ffn_share_1_6`` is the share (1, 6) of the same
    # layer: 64 slots are one chunk since PR 39.  PR 61 made the forward
    # kernel this repo's (``masked_attention.out_lse``): both blockdiff rows
    # are PR 61's text, the second the forward's equation alone, and no line
    # of profiler metadata is left to drop (it was the library's).
    "olmoe_tiny_step":
    "f207566475ff18a27f783a5c9534a4f9b89eec2f285b233b86f24ff384e1a295",
    "moe_ffn_all_held":
    "b984e2cd4967e02c21bb3e7a29f1851f46b1b7ecc9af285de185d6c78b212a93",
    "moe_ffn_share_1_6":
    "892af5ed1f0b554332c69f8306487e2cafd1f561d76537a250d650ab29cf91a2",
    "sdar_tiny_step":
    "b75516136f52cd075192c42f2dfd13486342c7747d8072ad24613fe0e5e5394e",
    "blockdiff_kernel_call":
    "5a92a863a5952f4b7dbea5a0cf3f76746d3a35de46dd27c2d67e79416987b097",
    "blockdiff_forward_call":
    "54fafc02c1dbe9dc388ae66dbc04f8121606a17fb3f07fad8e4b75e396e81459",
    # The lowered loss and gradients in float32, PR 48's text: rows that
    # are no bfloat16 array still run the highest-precision product they ran
    # before PR 45 (a float32 model is every configuration's float32 twin and
    # the references' programs), and since PR 48 every router reads its
    # chosen scores through ``_chosen``, so these rows moved with the bf16
    # ones; ``test_float32_numbers_are_the_gathers_and_the_scatters`` holds
    # their numbers to the line that went.
    "float32/moe_ffn_softmax":
    "faa377faec2b8acfd822dee4814944c0dddc1f5f3de5fd3d51bee652858b72b4",
    "float32/moe_ffn_sigmoid_bias":
    "90f5bbed449cc92b623d6f4f8f927b0313a6e61ba460fbf464c331471b365854",
    "float32/moe_ffn_router_input":
    "e8428a11f7c960cdc06794c96fe7615447647adea5644c2e9a7eb4b56b68f61e",
    "float32/olmoe_tiny_step":
    "0b7d0ff036f278367134135cc929f9ae5530b0bc1b2152b6191f24f99ead337f",
    "float32/smallthinker_tiny_step":
    "be9e351bc5aef0b3c569ec990a9f6dde74a4c8c5b108ae15e9cef29968bb7850",
    "float32/lfm2_tiny_step":
    "8922ad63109ea0d89831c6b2d8d8007265e6011d07a96d70bbd58b2694301683",
    # PR 57's text: the Mamba-2 mixer's convolution is
    # ``kernels/causal_conv.py::reference``, the rolled form that the Gated
    # DeltaNet's row below was taken with (it keeps its digest) with the
    # bias added, and no longer a padded copy and four slices.
    "float32/nemotron_tiny_step":
    "b94b58a5fd155b5dd246827c541508762d13962328e4f67e5e8c22f73bdc7a9a",
    # sha1 over the sorted (path, shape) pairs of the parameter tree that
    # each transformer configuration of the benchmark builds at a tiny size,
    # taken on the parent of PR 41 (3cce4b3): a layer of every kind they use.
    "tree/bert-large": "937ead76c45f971d816bd63ce22f6878266feefa",
    "tree/olmoe-1b-7b": "73df0b693c052979780575ddb5b5f3a9f59d46bb",
    "tree/sdar-30b-a3b": "fa6dff3fc67ccecb9d81983641bd0477aa24047a",
    "tree/smallthinker-21b-a3b": "5996a7811d123657dca6869ca4c999ef890137de",
    "tree/lfm2-8b-a1b": "811fb3c5a0e5cb8c1a78b62aaa51b32ca1585094",
    # PR 47 (JoyAI-LLM-Flash).  ``causal_kernel_call`` is the jaxpr of the
    # wrapper's call under ``Causal``, 4 query heads on 2 KV heads of 128,
    # forward and the three gradients, without the line of profiler
    # metadata, taken on PR 47's parent (7ca964c): the backward kernel learnt
    # a second width and at one width traces to what it traced to.  The
    # others are PR 47's own text: the same call at latent attention's
    # widths (keys of 192 over values of 128, 3 heads), test_joyai.py's tiny
    # model (latent attention, the dense layer, two sparse ones, the
    # prediction module), loss and gradients in float32 on 2 x 20 tokens
    # (PR 48's text since: it holds three routers; PR 49's since: latent
    # attention builds q, k and v in the kernels' layout, the interleave on
    # the weights' columns: ``test_joyai.py`` holds its numbers to the
    # parent's block written out), and its parameter tree, which PR 49 left
    # as it was: the parameters keep the published layout.  The two kernel
    # calls are PR 61's text since: the forward kernel is this repo's.
    "causal_kernel_call":
    "e4680dda763b5510ac200feeb08c3aa889e675e600bf9d55094b98dd0388fc79",
    "latent_kernel_call":
    "2896cff9a11dac5247f1e839d98cc4a112e6e2cd0b4c4c4ead4a96cc625425b0",
    "float32/joyai_tiny_step":
    "529388bca1069ec7110e721d773f271a50f10bdaaf5f98cd9e69f1f111cfc62f",
    "tree/joyai-llm-flash": "e2bc7c473a1f641f1f61e76d8b6ac6dadbacba87",
    # PR 50 (Qwen3-Next-80B-A3B), its own text: the wrapper's call under
    # ``Causal`` at 8 query heads on 1 KV head of 256 (the kernels took the
    # width as they were: no line of either changed, and the two rows above
    # keep their digests); the jaxpr of ``kernels/gated_delta.py``'s call,
    # forward and the five cotangents, 2 key heads serving 4 value heads of
    # 128 at three chunks; test_qwen3_next.py's tiny model (three Gated
    # DeltaNet layers through ``chunked``, gated attention with partial
    # rotary positions, the gated shared expert), loss and gradients in
    # float32 on 2 x 70 tokens, and its parameter tree.  The wrapper's call is
    # PR 61's text since (the forward kernel).
    "gqa256_kernel_call": "ecd9202dd86379a5c06a814526c8139ea5ba81a258770c505a079f4e7269fcbc",
    # PR 51 re-pinned this row alone: the kernels take a key head's two value
    # heads as one block-diagonal chunk 128 wide, a step's pairs abreast.
    "gated_delta_kernel_call": "712ade0f10b50ea922e16a9e4c7c0ef8d81fc7e670d8612025bbe8b767066393",
    "float32/qwen3_next_tiny_step": "2bf8b684115f1b20df7bdb3e03652029c01bceacaa61eb0270cbd136fc2a40e1",
    "tree/qwen3-next-80b-a3b": "cf7826f4e49abb86956d6246f258969362f43e4b",
    # PR 58 (Xing4.0-29B-A4B), its own: the parameter tree of test_xing.py's
    # tiny model (``hc_mixer`` and ``hc_ffn`` with ``phi``, ``bias`` and
    # ``alpha`` in every layer beside latent attention's seven, a dense layer
    # and two sparse ones).  Its step's text is not pinned: no other model
    # runs its module, and ``tests/test_xing.py`` holds its numbers.
    "tree/xing4.0-29b-a4b": "d1c50a3dfb8332d801c369ee20105e8081e79c9c",
    # PR 66 (Ling-3.0-flash-VL), its own: the jaxpr of ``kernels/kda.py``'s
    # call, forward and the five cotangents, 4 heads of 128 at three chunks
    # (the vector decay's sub-blocks, the running sum and the transposed
    # states are in that text), and the parameter tree of test_ling.py's
    # tiny model (``kda`` with its seven leaves in two layers, latent
    # attention's six without ``q_a`` and with ``gate`` in one, a dense
    # layer and two sparse ones).
    "kda_kernel_call": "29fc1d30455686957d9a1974d43294ab93205badde5e56cb8481de6f39abfd41",
    "tree/ling-3.0-flash-vl": "eb5954986052f62bc84572f081895d3d16a82dce",
    # PR 67 moved the KDA mixer's rows each side of the rule into
    # ``kernels/head_rows.py`` where it takes a layer (a TPU, bf16, heads of
    # 128); test_ling.py's tiny model in float32, loss and gradients, is the
    # text of PR 67's parent (6d9a6ff): what the kernels refuse runs the
    # lines it ran.
    "float32/ling_tiny_step": "ae7c21f91a6c2fcade8fdc77200dbcb2cb10300f1d9881ba3f0969275f793d68",
}


def digest(text, algorithm="sha256"):
    return hashlib.new(algorithm, text.encode()).hexdigest()


def names(prefix):
    return sorted(name[len(prefix):] for name in PINS
                  if name.startswith(prefix))


shape = jax.ShapeDtypeStruct


def abstract(model, like):
    """The model's parameters as shapes: ``like`` is the tokens it takes."""
    return nn.meta.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), like)["params"])


def lowered(program):
    """The lowered text of a program: a function and its operands' shapes."""
    fn, args = program
    return jax.jit(fn).lower(*args).as_text()


def step_program(loss, *args):
    """A loss and its gradients, auxiliary output kept, and its operands."""
    return jax.value_and_grad(loss, has_aux=True), args


def step_text(loss, *args):
    return lowered(step_program(loss, *args))


def moe_ffn_program(rows, held=None, gradients=5, **options):
    """The gradients of ``moe_ffn``'s sum, and of its balancing loss, at [2,
    16, 64] rows of dtype ``rows`` x 8 experts of width 32, k = 2, by the
    operands' first ``gradients``, and those operands.  ``options`` go to
    the layer; a value that names an operand (``"bias"``, ``"routed_by"``,
    ``"x"``) is that operand."""
    from horovod_tpu.parallel.moe import moe_ffn

    d, f, e, k = 64, 32, 8, 2
    n = e if held is None else len(held)
    args = [shape((2, 16, d), rows), shape((d, e), jnp.float32),
            shape((n, d, f), jnp.float32), shape((n, d, f), jnp.float32),
            shape((n, f, d), jnp.float32), shape((e,), jnp.float32),
            shape((2, 16, d), jnp.float32)][:gradients]

    def loss(*a):
        operands = dict(zip(("x", "router", "gate", "up", "down", "bias",
                             "routed_by"), a))
        given = {key: operands.get(value, value) if isinstance(value, str)
                 else value for key, value in options.items()}
        if held is not None:
            given.update(held=held, norm_topk_prob=True)
        y, stats = moe_ffn(*a[:5], k=k, **given)
        total = jnp.sum(y.astype(jnp.float32)) \
            + jnp.sum(stats.load_balancing_loss)
        if gradients == 7:
            total = total + jnp.sum(stats.router_z_loss)
        return total

    return jax.grad(loss, argnums=tuple(range(gradients))), args


def moe_ffn_text(rows, **options):
    return lowered(moe_ffn_program(rows, **options))


def test_bert_large_lowers_to_what_the_parent_lowered_to():
    from horovod_tpu.models.transformer import Transformer, bert_large_config

    model = Transformer(bert_large_config(attention="full"))
    tokens = shape((2, 512), jnp.int32)
    params = abstract(model, tokens)
    tree = sorted((jax.tree_util.keystr(k), tuple(v.shape), v.dtype.name)
                  for k, v in jax.tree_util.tree_leaves_with_path(params))
    assert len(tree) == 292
    text = jax.jit(lambda p, t: model.apply({"params": p}, t)).lower(
        params, tokens).as_text()
    assert digest(repr(tree)) == PINS["bert_large/tree"]
    assert digest(text) == PINS["bert_large/forward"]


@pytest.mark.parametrize("which", ["olmoe_tiny_step", "moe_ffn_all_held",
                                   "sdar_tiny_step", "blockdiff_kernel_call"])
def test_lowers_to_what_the_parent_lowered_to(which):
    if which == "olmoe_tiny_step":
        model, sizes = test_olmoe.tiny_model(jnp.bfloat16)
        tokens = shape((3, 32), jnp.int32)
        text = step_text(test_olmoe.program_loss(model, sizes),
                         abstract(model, tokens), tokens)
    elif which == "sdar_tiny_step":
        model, sizes = test_sdar.tiny_model(jnp.bfloat16)
        batch = jax.eval_shape(lambda: test_sdar.noised(sizes, 0))
        text = step_text(test_sdar.program_loss(model, sizes),
                         abstract(model, shape((1, 32), jnp.int32)), batch)
    elif which == "blockdiff_kernel_call":
        from horovod_tpu.kernels import blockdiff_attention as bd
        from horovod_tpu.kernels import masked_attention

        q = shape((1, 2 * bd.BLOCK, 4, 128), jnp.bfloat16)
        kv = shape((1, 2 * bd.BLOCK, 2, 128), jnp.bfloat16)

        def loss(q, k, v):
            return jnp.sum(masked_attention.attention(
                q, k, v, bd.BlockDiffusion(4)).astype(jnp.float32))

        jaxpr = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            q, kv, kv).jaxpr
        text = str(jaxpr)
        forward, = (str(eqn)
                    for eqn in test_sdar.equations_of(jaxpr, "pallas_call")
                    if eqn.params["name"].startswith("splash_mha_fwd"))
        assert digest(forward) == PINS["blockdiff_forward_call"]
    else:
        text = moe_ffn_text(jnp.bfloat16)
    assert digest(text) == PINS[which]


@pytest.mark.parametrize("which,heads,kv_heads,widths", [
    ("causal_kernel_call", 4, 2, (128, 128)),
    ("latent_kernel_call", 3, 3, (192, 128)),
    ("gqa256_kernel_call", 8, 1, (256, 256))])
def test_the_causal_kernels_call_traces_to_the_pinned_text(which, heads,
                                                           kv_heads, widths):
    from horovod_tpu.kernels import masked_attention as ma

    d, dv = widths
    q = shape((1, 2 * ma.BLOCK, heads, d), jnp.bfloat16)
    k = shape((1, 2 * ma.BLOCK, kv_heads, d), jnp.bfloat16)
    v = shape((1, 2 * ma.BLOCK, kv_heads, dv), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(ma.attention(q, k, v, ma.Causal())
                       .astype(jnp.float32))

    jaxpr = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, k, v).jaxpr
    assert digest(str(jaxpr)) == PINS[which]


def test_the_gated_delta_kernels_call_traces_to_the_pinned_text():
    from horovod_tpu.kernels import gated_delta as gd

    q = shape((1, 3 * gd.CHUNK, 2, 128), jnp.bfloat16)
    v = shape((1, 3 * gd.CHUNK, 4, 128), jnp.bfloat16)
    head = shape((1, 3 * gd.CHUNK, 4), jnp.float32)

    def loss(q, k, v, g, beta):
        return jnp.sum(gd.gated_delta(q, k, v, g, beta, interpret=True)
                       .astype(jnp.float32))

    jaxpr = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).trace(
        q, q, v, head, head).jaxpr
    names = [eqn.params["name"]
             for eqn in test_sdar.equations_of(jaxpr, "pallas_call")]
    assert names == [gd.FWD_NAME, gd.BWD_NAME]
    assert digest(str(jaxpr)) == PINS["gated_delta_kernel_call"]


def test_the_kda_kernels_call_traces_to_the_pinned_text():
    from horovod_tpu.kernels import kda

    wide = shape((1, 3 * kda.CHUNK, 4, 128), jnp.bfloat16)
    decays = shape((1, 3 * kda.CHUNK, 4, 128), jnp.float32)
    head = shape((1, 3 * kda.CHUNK, 4), jnp.float32)

    def loss(q, k, v, g, beta):
        return jnp.sum(kda.kda(q, k, v, g, beta, interpret=True)
                       .astype(jnp.float32))

    jaxpr = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).trace(
        wide, wide, wide, decays, head).jaxpr
    names = [eqn.params["name"]
             for eqn in test_sdar.equations_of(jaxpr, "pallas_call")]
    assert names == [kda.FWD_NAME, kda.BWD_NAME]
    assert digest(str(jaxpr)) == PINS["kda_kernel_call"]


def test_router_input_the_rows_themselves_and_silu_are_the_parents_program():
    """SmallThinker's two options at their defaults, spelled out, lower to
    what the parent lowered to, whole layer and share alike."""
    spelled = dict(router_input="x", activation="silu")
    assert digest(moe_ffn_text(jnp.bfloat16, **spelled)) \
        == PINS["moe_ffn_all_held"]
    assert digest(moe_ffn_text(jnp.bfloat16, held=(1, 6))) \
        == digest(moe_ffn_text(jnp.bfloat16, held=(1, 6), **spelled)) \
        == PINS["moe_ffn_share_1_6"]


def float32_program(which):
    """The loss and gradients of ``which`` in float32, and its operands'
    shapes."""
    tokens = shape((2, 32), jnp.int32)
    if which.startswith("moe_ffn"):
        return moe_ffn_program(
            jnp.float32, gradients=7, dtype=jnp.float32, **{
                "moe_ffn_softmax": {},
                "moe_ffn_sigmoid_bias": dict(
                    scoring="sigmoid", bias="bias", norm_topk_prob=True,
                    scale=2.5),
                "moe_ffn_router_input": dict(router_input="routed_by")}[which])
    if which == "olmoe_tiny_step":
        model, sizes = test_olmoe.tiny_model(jnp.float32)
        return step_program(test_olmoe.program_loss(model, sizes),
                            abstract(model, tokens), tokens)
    if which == "smallthinker_tiny_step":
        model, sizes = test_smallthinker.tiny_model(jnp.float32)
        return step_program(test_smallthinker.program_loss(model, sizes),
                            abstract(model, tokens), {"tokens": tokens})
    if which == "lfm2_tiny_step":
        model, sizes = test_lfm2.tiny_model(jnp.float32)
        aux = jax.eval_shape(lambda: test_lfm2.counters(sizes))
        return step_program(test_lfm2.program_loss(model, sizes),
                            abstract(model, tokens), aux, {"tokens": tokens})
    if which == "ling_tiny_step":
        config = test_ling.tiny_config(jnp.float32)
        key = shape((2,), jnp.uint32)
        return step_program(config.loss, *jax.eval_shape(config.init, key),
                            jax.eval_shape(config.make_batch, key))
    tiny = {"joyai_tiny_step": test_joyai,
            "qwen3_next_tiny_step": test_qwen3_next}.get(which, test_nemotron)
    model, sizes = tiny.tiny_model(jnp.float32)
    few = shape((2, sizes["sequence_length"]), jnp.int32)
    aux = jax.eval_shape(lambda: tiny.zero_aux(sizes))
    return step_program(tiny.program_loss(model, sizes),
                        abstract(model, few), aux, {"tokens": few})


@pytest.mark.parametrize("which", names("float32/"))
def test_float32_rows_lower_to_the_pinned_text(which):
    assert digest(lowered(float32_program(which))) == PINS["float32/" + which]


def test_the_interleave_is_on_the_weights_and_not_on_the_rows():
    """PR 49: a latent block's evens-then-odds permutation is a stride-2
    read of ``q_b``'s and ``kv_a``'s rotary columns (``[l, h, d]`` and
    ``[d, l]``: rank 3 and 2), in the forward pass, from parameters in the
    published layout; the lowered block and its gradients hold no stride-2
    slice of an activation (rank 4 a head at a time, rank 3 flat), so the
    copy of the rows cannot come back unnoticed."""
    import re

    from horovod_tpu.models.deepseek import LatentAttention

    model, _ = test_joyai.tiny_model(jnp.bfloat16)
    cfg, x = model.cfg, shape((2, 20, 32), jnp.float32)
    layer = LatentAttention(cfg)
    params = abstract(layer, x)
    text = lowered((jax.grad(
        lambda p, x: jnp.sum(layer.apply({"params": p}, x)
                             .astype(jnp.float32)), argnums=(0, 1)),
        (params, x)))
    strided = re.findall(
        r"stablehlo\.slice %\S+ \[[^\]]*\d+:\d+:2[^\]]*\] : \(tensor<([\dx]+)x\w+>\)",
        text)
    operands = sorted({tuple(map(int, dims.split("x"))) for dims in strided})
    rope = cfg.qk_rope_head_dim
    assert operands == [(cfg.q_lora_rank, cfg.num_heads, rope),
                        (cfg.d_model, rope)], operands
    assert len(strided) == 4            # evens and odds of each, forward only


def filled(shapes, seed=0):
    """Values for a program's operands: floats at normal(0.3), whole numbers
    (tokens, counters) below 32, the smallest vocabulary here being 128."""
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten([
        0.3 * jax.random.normal(key, leaf.shape, leaf.dtype)
        if jnp.issubdtype(leaf.dtype, jnp.floating)
        else jax.random.randint(key, leaf.shape, 0, 32, leaf.dtype)
        for key, leaf in zip(keys, leaves)])


@pytest.mark.parametrize("which", names("float32/"))
def test_float32_numbers_are_the_gathers_and_the_scatters(which, monkeypatch):
    """PR 48 moved the text of every program that holds a router and none of
    its numbers: the loss (or the layer's sum), what rides beside it and
    every gradient equal those of the same program with the chosen scores
    read by ``take_along_axis``, whose transpose is the scatter, as the
    parent read them, to 1e-5 of their norm.  That is float32's rounding
    under another fusion and nothing else: the same experts are chosen, op
    by op (``jax.disable_jit``) the two agree to the bit, and jitted on a
    CPU the losses do and the gradients lie 1e-7 to 7e-7 apart, 1.8e-6 in
    SmallThinker's routers and 2.9e-6 in Nemotron's last, whose gradient is
    a thousandth of its neighbours'."""
    from horovod_tpu.parallel import moe

    fn, shapes = float32_program(which)
    args = filled(shapes)

    def run():
        # A function of its own a side: jit keeps its traces by function.
        program = jax.jit(lambda *a: fn(*a))
        return program(*args), program.lower(*args).as_text().count("scatter")

    new, new_scatters = run()
    monkeypatch.setattr(moe, "_chosen", test_router_product.gathered)
    old, old_scatters = run()
    assert old_scatters > new_scatters
    got, want = (jax.tree_util.tree_leaves(out) for out in (new, old))
    assert len(got) == len(want) > 3
    for a, b in zip(got, want):
        if jnp.issubdtype(a.dtype, jnp.floating):
            assert float(jnp.linalg.norm((a - b).ravel())) \
                <= 1e-5 * float(jnp.linalg.norm(b.ravel()))
        else:
            assert (a == b).all()


def small_presets():
    from horovod_tpu.models import transformer as t

    share = dict(vocab_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
                 d_model=64, d_ff=32, max_len=64, num_experts=8)
    return {
        "bert-large": t.bert_large_config(
            vocab_size=128, num_layers=2, num_heads=4, d_model=64, d_ff=128,
            max_len=64),
        "olmoe-1b-7b": t.olmoe_1b_7b_config(
            vocab_size=128, num_layers=2, num_heads=4, d_model=64, d_ff=32,
            max_len=32, num_experts=8, experts_per_token=2),
        "sdar-30b-a3b": t.sdar_30b_a3b_config(
            **share, head_width=16, experts_per_token=2,
            experts_held=(1, 3, 4, 6), block_diffusion=4),
        "smallthinker-21b-a3b": t.smallthinker_21b_a3b_config(
            **share, head_width=8, experts_per_token=3, experts_held=(1, 6),
            layer_pattern=(t.LayerKind(0, False), t.LayerKind(8, True))),
        "joyai-llm-flash": test_joyai.tiny_model()[0].cfg,
        "qwen3-next-80b-a3b": test_qwen3_next.tiny_model()[0].cfg,
        "xing4.0-29b-a4b": test_xing.tiny_config().model.cfg,
        "ling-3.0-flash-vl": test_ling.tiny_config().model.cfg,
        "lfm2-8b-a1b": t.lfm2_8b_a1b_config(
            **{**share, "num_layers": 3}, head_width=16, d_ff_dense=96,
            experts_per_token=2, experts_held=(1, 6),
            layer_pattern=(t.LayerKind(0, True, "conv", "dense"),
                           t.LayerKind(0, True, "attention"),
                           t.LayerKind(0, True, "conv"))),
    }


@pytest.mark.parametrize("name", names("tree/"))
def test_every_kind_of_layer_builds_the_parents_parameter_tree(name):
    from horovod_tpu.models.transformer import Transformer

    shapes = jax.eval_shape(
        lambda: Transformer(small_presets()[name]).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    pairs = sorted((jax.tree_util.keystr(path), tuple(x.shape))
                   for path, x in jax.tree_util.tree_leaves_with_path(shapes))
    assert digest(repr(pairs), "sha1") == PINS["tree/" + name]
