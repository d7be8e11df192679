"""Qwen3-Next-80B-A3B: three Gated DeltaNet layers to one gated attention
layer (the chunked gated delta rule and its kernels against the recurrence a
token at a time are ``tests/test_gated_delta.py``'s), partial rotary positions, the RMSNorm whose scale is ``1 +
w``, the gated shared expert whose shares add up, the plain reference
(``chip_bench/configs/qwen3-next-80b-a3b_reference.py``: float32, nothing of
``horovod_tpu``) against the publisher's own ``Qwen3NextForCausalLM`` on
copied weights, and the whole model against that reference on seeded weights
at tiny widths.  ``tests/test_qwen3_next_cell.py`` holds the configuration
and its cell.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .helpers import load_reference
from .test_olmoe import rel_err

ref = load_reference("qwen3-next-80b-a3b")

# One period: 2 key heads serving 4 value heads of 8, 4 query heads on 2 KV
# heads of 16 with 4 of them rotary, 4 of 16 experts held, a sliced
# vocabulary; 70 positions: one chunk of 64 and a part of the next.
TINY = dict(
    num_hidden_layers=4, full_attention_interval=4, hidden_size=32,
    head_dim=16, num_attention_heads=4, num_key_value_heads=2,
    partial_rotary_factor=0.25, rope_theta=10000000,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel_dim=4,
    num_experts=4, num_experts_published=16, experts_held=[1, 5, 6, 12],
    num_experts_per_tok=3, norm_topk_prob=True, moe_intermediate_size=24,
    shared_expert_intermediate_size=40, vocab_size=64, rms_norm_eps=1e-6,
    router_aux_loss_coef=0.001, sequence_length=70)


def tiny_model(dtype=jnp.float32, **overrides):
    from horovod_tpu.models.transformer import (
        Transformer,
        qwen3_next_80b_a3b_config,
    )

    z = {**TINY, **overrides}
    return Transformer(qwen3_next_80b_a3b_config(
        vocab_size=z["vocab_size"], num_layers=z["num_hidden_layers"],
        num_heads=z["num_attention_heads"],
        num_kv_heads=z["num_key_value_heads"], head_width=z["head_dim"],
        d_model=z["hidden_size"], d_ff=z["moe_intermediate_size"],
        d_ff_shared=z["shared_expert_intermediate_size"], max_len=256,
        rope_theta=float(z["rope_theta"]),
        partial_rotary_factor=z["partial_rotary_factor"],
        num_experts=z["num_experts_published"],
        experts_per_token=z["num_experts_per_tok"],
        experts_held=tuple(z["experts_held"]),
        gdn_key_heads=z["linear_num_key_heads"],
        gdn_value_heads=z["linear_num_value_heads"],
        gdn_key_dim=z["linear_key_head_dim"],
        gdn_value_dim=z["linear_value_head_dim"],
        gdn_conv=z["linear_conv_kernel_dim"], dtype=dtype)), z


def tokens_of(sizes, seed, batch=2):
    return {"tokens": jax.random.randint(
        jax.random.PRNGKey(seed), (batch, sizes["sequence_length"]), 0,
        sizes["vocab_size"])}


def seeded(model, seed=0, by=3.0):
    """Fresh weights with the layers' matrices ``by`` times as large as their
    initialisers draw them, so that at these widths every module moves the
    residual stream and a wrong one shows; the norms' ``w`` off zero, so that
    ``1 + w`` differs from ``w``; ``A_log`` the logarithm of a draw below 2,
    so that every head's state outlives a chunk (a fresh draw goes up to 16,
    half the heads then forget within a position, and what their decay's
    gradient is in float32 is rounding, in any form of the rule)."""
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"])
    grow = {"kernel", "router", "experts_gate", "experts_up", "experts_down",
            "conv"}
    leaves = jax.tree_util.tree_leaves_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    by_path = {jax.tree_util.keystr(path): key
               for (path, _), key in zip(leaves, keys)}

    def scaled(path, x):
        last = getattr(path[-1], "key", None)
        under = {getattr(k, "key", None) for k in path}
        if last == "scale":
            return 0.3 * jax.random.normal(
                by_path[jax.tree_util.keystr(path)], x.shape, x.dtype)
        if last == "A_log":
            return jnp.log(jax.random.uniform(
                by_path[jax.tree_util.keystr(path)], x.shape, x.dtype, 0.05,
                2.0))
        return x * by if last in grow and "embed" not in under else x

    return jax.tree_util.tree_map_with_path(scaled, params)


def zero_aux(sizes):
    from horovod_tpu.parallel.moe import moe_counters

    return moe_counters(sizes["num_hidden_layers"],
                        sizes["num_experts_published"], share=True)


def program_loss(model, sizes):
    import optax

    from horovod_tpu.models.transformer import moe_stats
    from horovod_tpu.parallel.moe import count_routing

    def loss(params, aux, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        logits, state = model.apply({"params": params}, tokens,
                                    mutable=["moe"])
        stats = moe_stats(state["moe"])
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1))
        total = jnp.sum(nll * (jnp.arange(s) < s - 1)) / (b * (s - 1)) \
            + sizes["router_aux_loss_coef"] \
            * jnp.mean(stats.load_balancing_loss)
        return total, count_routing(
            aux, jnp.sum(stats.tokens_per_expert, axis=1),
            held=tuple(sizes["experts_held"]))

    return loss


# -- the whole model ----------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, dict(by=2.0, logits=1e-5, loss=1e-6, grads=1e-5,
                       decay=5e-5)),
    (jnp.bfloat16, dict(by=1.5, logits=None, loss=2e-3, grads=0.25,
                        decay=0.25))],
    ids=["float32", "bfloat16"])
def test_transformer_under_the_pattern_matches_the_reference(dtype, tol):
    """Logits, loss, the gradient of every leaf and the counts of the
    program's model against the plain reference's on the same seeded
    weights: in float32 to rounding (1e-5), in the cell's precision (bf16
    activations) the loss to 2e-3 and every leaf's gradient to a quarter of
    its largest entry (the tiny widths' sums are short; a position that
    takes another expert under a rounded router input moves its own logits
    by more than any limit on them could allow, so they are held in float32
    alone).  The decay's two leaves (``A_log``, ``dt_bias``) have 5e-5 in
    float32: the chunked form reads a decay as the exponential of a
    difference of two running sums of up to 64 logarithms, which float32
    holds to 1e-7 of the sums and so to 1e-5 of the decay, where the
    recurrence multiplies one step's decay at a time."""
    model, sizes = tiny_model(dtype)
    params, batch = seeded(model, by=tol["by"]), tokens_of(sizes, 1)
    aux = zero_aux(sizes)
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, batch["tokens"],
                             mutable=["moe"])[0]
        (got, got_aux), grads = jax.jit(jax.value_and_grad(
            program_loss(model, sizes), has_aux=True))(params, aux, batch)
    if tol["logits"]:
        assert rel_err(logits, reference_logits(sizes)(params, batch)) \
            < tol["logits"]
    (want, want_aux), want_grads = jax.jit(jax.value_and_grad(
        ref.make_loss(sizes), has_aux=True))(params, aux, batch)
    assert float(got) == pytest.approx(float(want), rel=tol["loss"])
    assert float(want) == pytest.approx(np.log(sizes["vocab_size"]), rel=0.3)
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)
    errs = jax.tree_util.tree_map(rel_err, grads, want_grads)
    for path, err in jax.tree_util.tree_leaves_with_path(errs):
        name = jax.tree_util.keystr(path)
        decay = name.endswith("['A_log']") or name.endswith("['dt_bias']")
        assert err < tol["decay" if decay else "grads"], (name, err)
    assert all(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(grads))
    if dtype == jnp.float32:
        for key in want_aux:
            np.testing.assert_array_equal(got_aux[key], want_aux[key])
    assert int(want_aux["tokens_per_expert"].sum()) == 4 * 2 * 70 * 3


WRONG = ("no_delta", "no_l2norm", "rope_everywhere", "no_attention_gate",
         "no_shared_gate")


def reference_logits(sizes, wrong=()):
    """The reference's logits as one program: run operation by operation its
    scans and maps compile a piece at a time."""
    return jax.jit(lambda params, batch: ref.logits(params, batch, sizes,
                                                    wrong=wrong))


@pytest.fixture(scope="module")
def sound():
    """Seeded weights, a batch and the sound reference's logits on them."""
    model, sizes = tiny_model()
    params, batch = seeded(model), tokens_of(sizes, 1)
    return model, sizes, params, batch, reference_logits(sizes)(params, batch)


@pytest.mark.parametrize("wrong", WRONG)
def test_a_wrong_layer_of_the_reference_moves_the_logits(wrong, sound):
    """What the configuration's float32 limit has to refuse: each wrong layer
    lies far from the sound reference where the program's model in float32
    lies within rounding of it."""
    model, sizes, params, batch, want = sound
    got = reference_logits(sizes, (wrong,))(params, batch)
    assert rel_err(got, want) > 1e-3


def test_the_programs_model_in_float32_is_the_sound_reference(sound):
    model, _, params, batch, want = sound
    with jax.default_matmul_precision("highest"):
        own = model.apply({"params": params}, batch["tokens"],
                          mutable=["moe"])[0]
    assert rel_err(own, want) < 1e-5


def test_a_layer_builds_only_what_its_kind_names():
    model, sizes = tiny_model()
    shapes = jax.eval_shape(lambda: nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))["params"]
    ffn = ["experts_down", "experts_gate", "experts_up", "ln1", "ln2",
           "router", "shared_down", "shared_expert_gate", "shared_gate",
           "shared_up"]
    for i in range(3):
        assert sorted(shapes[f"layer_{i}"]) == sorted(ffn + ["gdn"])
    assert sorted(shapes["layer_3"]) == sorted(ffn + ["attn"])
    assert sorted(shapes["layer_0"]["gdn"]) == [
        "A_log", "conv", "dt_bias", "in_proj_ba", "in_proj_qkvz", "norm",
        "out_proj"]
    gdn, attn = shapes["layer_0"]["gdn"], shapes["layer_3"]["attn"]
    # 2 key heads and 4 value heads of 8: q 16, k 16, v 32, z 32; b 4, a 4.
    assert gdn["in_proj_qkvz"]["kernel"].shape == (32, 96)
    assert gdn["in_proj_ba"]["kernel"].shape == (32, 8)
    assert gdn["conv"].shape == (64, 4) and gdn["norm"].shape == (8,)
    assert gdn["out_proj"]["kernel"].shape == (32, 32)
    # 4 heads of 16, each a query and a gate; 2 KV heads; one scale a norm.
    assert attn["q"]["kernel"].shape == (32, 128)
    assert attn["kv"]["kernel"].shape == (32, 64)
    assert attn["q_norm"] == {"scale": jax.ShapeDtypeStruct((16,),
                                                            jnp.float32)}
    assert shapes["layer_1"]["shared_expert_gate"]["kernel"].shape == (32, 1)
    assert shapes["layer_1"]["router"].shape == (32, 16)
    assert shapes["ln_f"]["scale"].shape == (32,)
    assert model.cfg.expert_layers() == (0, 1, 2, 3)


def test_a_gate_on_no_shared_expert_is_refused():
    model, _ = tiny_model(shared_expert_intermediate_size=0)
    with pytest.raises(ValueError, match="d_ff_shared is 0"):
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))


def test_the_preset_is_the_published_model():
    from horovod_tpu.models.transformer import (
        Transformer,
        qwen3_next_80b_a3b_config,
    )

    def count(cfg):
        shapes = jax.eval_shape(
            lambda: Transformer(cfg).init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32)))
        return sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))

    cfg = qwen3_next_80b_a3b_config()
    kinds = [cfg.layer_kind(i).mixer for i in range(cfg.num_layers)]
    assert (kinds.count("gated_delta"), kinds.count("attention")) == (36, 12)
    assert all(kinds[i] == "attention" for i in range(3, 48, 4))
    gdn, attn, ffn = 33_718_464, 27_263_488, 4_196_352 + 512 * 3_145_728
    assert count(cfg) == 36 * gdn + 12 * attn + 48 * (ffn + 4096) \
        + 2 * 151_936 * 2048 + 2048 == 79_674_391_296      # the published 80B
    # The cell's cut: one period, 16 experts held, an eighth of the
    # vocabulary; with 32 held it is ISSUE 50's 625,667,136.
    cut = qwen3_next_80b_a3b_config(vocab_size=18992, num_layers=4,
                                    experts_held=tuple(range(16)))
    assert count(cut) == 424_340_544
    assert count(dataclasses.replace(
        cut, experts_held=tuple(range(32)))) == 625_667_136


def test_fresh_weights_are_the_releases_initialisers():
    """``w`` of every ``1 + w`` norm zero and the DeltaNet's own norm one,
    ``dt_bias`` one, ``A_log`` the logarithm of a draw below 16, matrices and
    taps at 0.02."""
    model, _ = tiny_model()
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"])
    for name in ("ln1", "ln2"):
        assert not params["layer_0"][name]["scale"].any()
    assert not params["ln_f"]["scale"].any()
    assert not params["layer_3"]["attn"]["q_norm"]["scale"].any()
    gdn = params["layer_1"]["gdn"]
    assert (gdn["norm"] == 1).all() and (gdn["dt_bias"] == 1).all()
    assert (jnp.exp(gdn["A_log"]) < 16).all() and jnp.isfinite(
        gdn["A_log"]).all()
    assert 0.01 < float(jnp.std(gdn["conv"])) < 0.03
    assert 0.015 < float(jnp.std(gdn["in_proj_qkvz"]["kernel"])) < 0.025


# -- the reference against the publisher's own code -----------------------------


def test_the_reference_is_transformers_qwen3_next_on_copied_weights():
    """``Qwen3NextForCausalLM`` (``transformers``' modeling_qwen3_next.py, the
    publisher's: its chunked torch rule, its convolution, its rotary
    embedding, its experts one at a time) at a small size, float32, with this
    tree's weights copied across: the reference's logits within 1e-4.  The
    release interleaves ``in_proj_qkvz``'s and ``in_proj_ba``'s columns by
    key head; ``release_columns`` undoes that."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "Qwen3NextForCausalLM"):
        pytest.skip("this transformers has no qwen3_next")
    from horovod_tpu.models.gated_delta import release_columns

    ids = list(range(16))
    model, z = tiny_model(experts_held=ids, num_experts=16)
    params, batch = seeded(model), tokens_of(z, 5)
    config = transformers.Qwen3NextConfig(
        vocab_size=z["vocab_size"], hidden_size=z["hidden_size"],
        intermediate_size=64, num_hidden_layers=z["num_hidden_layers"],
        num_attention_heads=z["num_attention_heads"],
        num_key_value_heads=z["num_key_value_heads"], head_dim=z["head_dim"],
        hidden_act="silu", max_position_embeddings=256,
        rms_norm_eps=z["rms_norm_eps"], tie_word_embeddings=False,
        rope_theta=z["rope_theta"],
        partial_rotary_factor=z["partial_rotary_factor"],
        attention_bias=False, attention_dropout=0.0,
        linear_conv_kernel_dim=z["linear_conv_kernel_dim"],
        linear_key_head_dim=z["linear_key_head_dim"],
        linear_value_head_dim=z["linear_value_head_dim"],
        linear_num_key_heads=z["linear_num_key_heads"],
        linear_num_value_heads=z["linear_num_value_heads"],
        decoder_sparse_step=1, moe_intermediate_size=z["moe_intermediate_size"],
        shared_expert_intermediate_size=z["shared_expert_intermediate_size"],
        num_experts_per_tok=z["num_experts_per_tok"], num_experts=16,
        norm_topk_prob=True, mlp_only_layers=[])
    config._attn_implementation = "eager"
    theirs = transformers.Qwen3NextForCausalLM(config).float().eval()
    assert [layer.layer_type for layer in theirs.model.layers] == [
        "linear_attention"] * 3 + ["full_attention"]

    def t(x):
        return torch.from_numpy(np.array(x, np.float32))

    def released(kernel, columns):
        out = np.zeros(kernel.shape, np.float32)
        out[:, columns] = np.asarray(kernel)
        return t(out.T)

    qkvz, ba = release_columns(model.cfg)
    h_kv, dh = z["num_key_value_heads"], z["head_dim"]
    state = {"model.embed_tokens.weight": t(params["embed"]["embedding"]),
             "model.norm.weight": t(params["ln_f"]["scale"]),
             "lm_head.weight": t(params["lm_head"]["kernel"].T)}
    for i in range(z["num_hidden_layers"]):
        p, at = params[f"layer_{i}"], f"model.layers.{i}."
        state[at + "input_layernorm.weight"] = t(p["ln1"]["scale"])
        state[at + "post_attention_layernorm.weight"] = t(p["ln2"]["scale"])
        if "gdn" in p:
            g, to = p["gdn"], at + "linear_attn."
            state[to + "in_proj_qkvz.weight"] = released(
                g["in_proj_qkvz"]["kernel"], qkvz)
            state[to + "in_proj_ba.weight"] = released(
                g["in_proj_ba"]["kernel"], ba)
            state[to + "conv1d.weight"] = t(g["conv"][:, None, :])
            state[to + "dt_bias"] = t(g["dt_bias"])
            state[to + "A_log"] = t(g["A_log"])
            state[to + "norm.weight"] = t(g["norm"])
            state[to + "out_proj.weight"] = t(g["out_proj"]["kernel"].T)
        else:
            a, to = p["attn"], at + "self_attn."
            kv = a["kv"]["kernel"]
            state[to + "q_proj.weight"] = t(a["q"]["kernel"].T)
            state[to + "k_proj.weight"] = t(kv[:, :h_kv * dh].T)
            state[to + "v_proj.weight"] = t(kv[:, h_kv * dh:].T)
            state[to + "o_proj.weight"] = t(a["out"]["kernel"].T)
            state[to + "q_norm.weight"] = t(a["q_norm"]["scale"])
            state[to + "k_norm.weight"] = t(a["k_norm"]["scale"])
        to = at + "mlp."
        state[to + "gate.weight"] = t(p["router"].T)
        state[to + "shared_expert_gate.weight"] = t(
            p["shared_expert_gate"]["kernel"].T)
        for theirs_name, ours in (("gate_proj", "gate"), ("up_proj", "up"),
                                  ("down_proj", "down")):
            state[to + f"shared_expert.{theirs_name}.weight"] = t(
                p[f"shared_{ours}"]["kernel"].T)
            for e in ids:
                state[to + f"experts.{e}.{theirs_name}.weight"] = t(
                    p[f"experts_{ours}"][e].T)
    missing, unexpected = theirs.load_state_dict(state, strict=False)
    assert not unexpected and not missing, (missing, unexpected)
    with torch.no_grad():
        want = theirs(torch.from_numpy(np.array(batch["tokens"])).long()) \
            .logits.numpy()
    got = reference_logits(z)(params, batch)
    assert rel_err(got, want) < 1e-4
    # And the columns' order matters: the release's weights read in this
    # module's order are another model.
    shuffled = jax.tree_util.tree_map(lambda x: x, params)
    kernel = params["layer_0"]["gdn"]["in_proj_qkvz"]["kernel"]
    shuffled["layer_0"] = {**params["layer_0"], "gdn": {
        **params["layer_0"]["gdn"],
        "in_proj_qkvz": {"kernel": kernel[:, np.argsort(qkvz)]}}}
    assert rel_err(reference_logits(z)(shuffled, batch), want) > 1e-2


def test_release_columns_is_a_permutation_by_key_head():
    from horovod_tpu.models.gated_delta import release_columns

    model, _ = tiny_model()
    qkvz, ba = release_columns(model.cfg)
    assert sorted(qkvz) == list(range(96)) and sorted(ba) == list(range(8))
    # Key head 1's q is the release's columns 48..55: behind head 0's q 8, k
    # 8, v 16, z 16; value head 2's z (head 1's first) starts at 48 + 32.
    assert list(qkvz[8:16]) == list(range(48, 56))
    assert list(qkvz[16:24]) == list(range(8, 16))          # head 0's k
    assert list(qkvz[64 + 16:64 + 24]) == list(range(80, 88))
    assert list(ba) == [0, 1, 4, 5, 2, 3, 6, 7]


# -- partial rotary positions -----------------------------------------------------


@pytest.mark.parametrize("share", [0.25, 0.5, 1.0])
def test_partial_rope_is_a_complex_rotation_of_the_first_part(share):
    """The first ``share`` of a head, as a head of that width: channel i and
    channel i + r/2 are the two parts of a complex number turned by position
    x theta^(-2i/r); the rest of the head goes through untouched.  The
    program's ``_rope`` and the reference's ``partial_rope`` alike."""
    from horovod_tpu.models.transformer import _rope

    s, h, d, theta = 12, 3, 16, 1e4
    r = int(d * share)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, s, h, d))
    angle = np.arange(s)[:, None] * theta ** (-np.arange(0, r, 2) / r)[None]
    z = (np.asarray(x[..., :r // 2]) + 1j * np.asarray(x[..., r // 2:r])) \
        * np.exp(1j * angle)[None, :, None, :]
    want = np.concatenate([z.real, z.imag, np.asarray(x[..., r:])], axis=-1)
    got = _rope(x, theta, share=share)
    assert rel_err(got, jnp.asarray(want, jnp.float32)) < 1e-5
    if share < 1:
        np.testing.assert_array_equal(got[..., r:], x[..., r:])
    plain = jax.vmap(lambda row: ref.partial_rope(row, theta, jnp.arange(s),
                                                  r))(x)
    assert rel_err(plain, jnp.asarray(want, jnp.float32)) < 1e-5


# -- the shares add up --------------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_with_the_gated_shared_expert_once():
    """16 experts over 4 shares of 4: each share routes over all 16 and adds
    the shared expert behind its sigmoid gate, which every chip computes
    alike: the shares' routed sums plus the gated shared expert once are the
    uncut layer, which is the uncut reference's."""
    from horovod_tpu.models.transformer import Block, LayerKind

    ids = list(range(16))
    whole, sizes = tiny_model(experts_held=ids, num_experts=16)
    kind = LayerKind(mixer="none")
    params = {k: v for k, v in seeded(whole)["layer_1"].items()
              if k not in ("gdn", "ln1")}
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 32))

    def layer(cfg, p):
        y, _ = Block(cfg, kind).apply({"params": p}, h, mutable=["moe"])
        return y - h

    with jax.default_matmul_precision("highest"):
        want = layer(whole.cfg, params)
        m = ref._rms_norm(h, params["ln2"]["scale"], 1e-6).reshape(40, 32)
        plain, _, _ = ref._experts(params, m, sizes)
        assert rel_err(want, plain.reshape(2, 20, 32)) < 1e-5
        ungated = (jax.nn.silu(m @ params["shared_gate"]["kernel"])
                   * (m @ params["shared_up"]["kernel"])) \
            @ params["shared_down"]["kernel"]
        shared = (ungated * jax.nn.sigmoid(
            m @ params["shared_expert_gate"]["kernel"])).reshape(2, 20, 32)
        routed = []
        for held in (ids[0::4], ids[1::4], ids[2::4], ids[3::4]):
            cfg = dataclasses.replace(whole.cfg, experts_held=tuple(held))
            p = {**params, **{
                name: params[name][np.asarray(held)]
                for name in ("experts_gate", "experts_up", "experts_down")}}
            routed.append(layer(cfg, p) - shared)
    assert rel_err(sum(routed) + shared, want) < 1e-5
    assert rel_err(sum(routed), want) > 0.05
    assert rel_err(routed[0] + shared, want) > 0.1
    assert rel_err(sum(routed) + ungated.reshape(2, 20, 32), want) > 0.05


def test_the_norm_with_an_offset_is_one_plus_w():
    """``norm_offset``: ``x / rms(x) * (1 + w)`` with ``w`` where
    ``nn.RMSNorm`` keeps its scale; without the option the block's norms are
    flax's, as every other configuration has them."""
    from horovod_tpu.models.transformer import _norm

    model, _ = tiny_model()
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 32))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (32,))

    class Holder(nn.Module):
        cfg: object

        @nn.compact
        def __call__(self, x):
            return _norm(self.cfg, "ln")(x)

    got = Holder(model.cfg).apply({"params": {"ln": {"scale": w}}}, x)
    assert rel_err(got, ref._rms_norm(x, w, 1e-6)) < 1e-6
    plain = dataclasses.replace(model.cfg, norm_offset=False)
    assert rel_err(
        Holder(plain).apply({"params": {"ln": {"scale": 1 + w}}}, x),
        got) < 1e-6
    shapes = jax.eval_shape(lambda: Holder(model.cfg).init(
        jax.random.PRNGKey(0), x))["params"]
    assert jax.tree_util.tree_map(lambda s: s.shape, shapes) \
        == {"ln": {"scale": (32,)}}
