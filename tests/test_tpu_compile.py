"""Compile the main path's kernels at their real widths for a TPU v5e that
is described, not attached: what the chip's compiler refuses (a block that
does not fit the fast memory, a slice off the tiling) fails here, at no chip
time.  Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture, never while a module is imported:
only one process may hold the TPU's library, and every pytest worker imports
every test file.  All such tests live in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache and
    cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_compiles_at_olmoes_shape(one_chip, no_compile_cache):
    """2 sequences of 4096, 16 heads of 128, causal, forward and backward,
    with the blocks ``models/transformer.py`` gives the kernel."""
    from horovod_tpu.models.transformer import (
        _FLASH_BLOCK,
        _FLASH_MIN_SEQ,
        _flash_attention,
    )

    assert _FLASH_MIN_SEQ % _FLASH_BLOCK == 0
    qkv = [_shape((2, 4096, 16, 128), jnp.bfloat16, one_chip)] * 3

    def loss(q, k, v):
        return jnp.sum(_flash_attention(q, k, v, True, 128)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *qkv).compile().as_text()
    kernels = set(re.findall(r"%(flash\w*?)[.\d]* =", text))
    assert any("bwd_dkv" in k for k in kernels), kernels
    assert any("bwd_dq" in k for k in kernels), kernels
    assert f"block_q_{_FLASH_BLOCK}" in text


@pytest.mark.parametrize("shape,causal", [
    ((8, 512, 16, 64), False),          # bert-large-wfbp-1chip
    ((2, 2048, 16, 64), True),          # the longest it takes, two heads a
    ((2, 2048, 16, 128), False),        # lane group and one
], ids=["berts", "2048x64_causal", "2048x128"])
def test_short_attention_compiles_at_berts_shape(shape, causal, one_chip,
                                                 no_compile_cache,
                                                 monkeypatch):
    """``_scaled_dot_attention`` as a TPU sees it, forward and backward: the
    two kernels of ``kernels/short_attention.py``, once each, reading the
    projections' layout (no ``[b, h, s, d]`` copy), and no score square
    anywhere in the program."""
    from horovod_tpu.kernels import short_attention as sa
    from horovod_tpu.models.transformer import _scaled_dot_attention

    b, s, h, d = shape
    assert sa.takes(s, d, h)
    qkv = [_shape(shape, jnp.bfloat16, one_chip)] * 3
    # The program asks which backend it runs on; here that is the CPU.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(q, k, v):
        return jnp.sum(_scaled_dot_attention(q, k, v, causal, d)
                       .astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *qkv).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%(hvd_short_attention\w*?)[.\d]* =", text)
    assert sorted(kernels) == [sa.BWD_NAME, sa.FWD_NAME], kernels
    assert all(re.match(sa.OP_LINE_NAMES, k) for k in kernels)
    assert f",{h},{s},{s}]" not in text             # the scores, any dtype
    assert f"[{b},{h},{s},{d}]" not in text
    assert f"f32[{b},{h * d // 128},{128 // d},{s}]" in text    # log-sum-exp
    # q, k, v, o and their cotangents, not a score tensor.
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 8 * b * s * h * d * 2


def test_expert_layer_compiles_at_published_widths(one_chip,
                                                   no_compile_cache):
    """8192 tokens through 64 experts of 2048 x 1024, 8 a token: the grouped
    products are XLA's grouped-matmul kernels and their work is the routed
    rows, not 64 experts a token."""
    from horovod_tpu.parallel.moe import moe_ffn

    d, f, e, k = 2048, 1024, 64, 8
    args = [_shape((2, 4096, d), jnp.bfloat16, one_chip),
            _shape((d, e), jnp.float32, one_chip),
            _shape((e, d, f), jnp.float32, one_chip),
            _shape((e, d, f), jnp.float32, one_chip),
            _shape((e, f, d), jnp.float32, one_chip)]

    def loss(*a):
        y, stats = moe_ffn(*a, k=k)
        return jnp.sum(y.astype(jnp.float32)) \
            + jnp.sum(stats.load_balancing_loss)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%ragged-dot-none[.\d]* =", text)) == 9
    # Dispatch and combine are gathers in both directions: no scatter of
    # 4 KB rows (top-k's own cotangent is a scatter of 65536 scalars).
    assert not re.findall(r"= \w+\[\d+,2048\]\S* scatter\(", text)
    routed = 9 * 2 * (8192 * k) * d * f
    flops = compiled.cost_analysis()["flops"]
    assert routed < flops < 1.15 * routed, (flops, routed)


def test_blockdiff_attention_compiles_at_sdars_shape(one_chip,
                                                     no_compile_cache):
    """One sequence as [x_t ; x_0], 16384 positions, 32 query heads on 4 KV
    heads of 128, blocks of 4: the library's forward kernel and the one
    backward kernel of ``kernels/masked_attention_bwd.py`` with the tiles
    ``kernels/masked_attention.py`` gives them, and no [2L, 2L] table or
    score square in the program."""
    from horovod_tpu.kernels import blockdiff_attention as bd
    from horovod_tpu.kernels import masked_attention

    q = _shape((1, 16384, 32, 128), jnp.bfloat16, one_chip)
    kv = _shape((1, 16384, 4, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(masked_attention.attention(
            q, k, v, bd.BlockDiffusion(4)).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(splash\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_residuals", "splash_mha_dkv_dq"}, \
        kernels
    assert all(re.match(bd.OP_LINE_NAMES, k) for k in kernels)
    assert "16384,16384" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("rule_name", ["window", "causal"])
def test_masked_attention_compiles_at_smallthinkers_shape(rule_name, one_chip,
                                                          no_compile_cache):
    """One sequence of 16384 positions, 28 query heads on 4 KV heads of 128,
    causal and causal inside a window of 4096: the library's forward kernel
    and the one backward kernel (no ``splash_mha_dq*``) with the tiles
    ``kernels/masked_attention.py`` gives them, KV heads not repeated, and no
    [s, s] table or score square in the program."""
    from horovod_tpu.kernels import masked_attention as ma

    rule = ma.Window(4096) if rule_name == "window" else ma.Causal()
    q = _shape((1, 16384, 28, 128), jnp.bfloat16, one_chip)
    kv = _shape((1, 16384, 4, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(ma.attention(q, k, v, rule).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(splash\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_residuals", "splash_mha_dkv_dq"}, \
        kernels
    assert all(re.match(ma.OP_LINE_NAMES, k) for k in kernels)
    assert "16384,16384" not in text
    assert "28,16384,128" in text and "bf16[1,16384,28,128]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_expert_share_compiles_at_published_widths(one_chip,
                                                   no_compile_cache):
    """16384 positions through the 16 held of 128 experts of 2048 x 768, 8 a
    token: the first chunk's nine grouped products over 20,480 places (five
    quarters of the mean share), the chunks of 4096 behind it in a loop
    whose trip count follows the rows, and on the way back to token order
    nothing the size of every routed slot."""
    from horovod_tpu.parallel.moe import moe_ffn, row_buffer, row_quantum

    d, f, e, held, k = 2048, 768, 128, 16, 8
    args = [_shape((1, 16384, d), jnp.bfloat16, one_chip),
            _shape((d, e), jnp.float32, one_chip),
            _shape((held, d, f), jnp.float32, one_chip),
            _shape((held, d, f), jnp.float32, one_chip),
            _shape((held, f, d), jnp.float32, one_chip)]
    assert row_buffer(16384 * k, held, e) == (28, 20480)
    assert row_quantum(16384 * k, held, e) == 4096

    def loss(*a):
        # Not linear in y, so that the combine's forward stays in the program.
        y, stats = moe_ffn(*a, k=k, held=tuple(range(held)),
                           norm_topk_prob=True)
        return jnp.sum(y.astype(jnp.float32) ** 2) \
            + jnp.sum(stats.load_balancing_loss)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    text = compiled.as_text()
    # 9 of the first chunk, 6 over its rows and 3 that give the weights'
    # gradients; the loops behind it add their 3 forward and, in the
    # backward pass, the same 3 again (recomputed, not kept) and 6 more.
    products = re.findall(
        r"%ragged-dot-none[.\d]* = \w+\[(\d+),\d+[\],]", text)
    assert len(products) == 21, products
    assert products.count("20480") == 6 and products.count("4096") == 9
    assert products.count("16") == 6 and "[32768," not in text
    # Two loops, forward and backward, where the parent scanned over three
    # conditionals in each direction.
    assert len(re.findall(r" while\(", text)) == 2
    assert " conditional(" not in text
    # Rows are fetched for a chunk's 20480 places and added up by token into
    # [16384, 2048], in both directions: no gather, fusion or anything else
    # has a row for each of the 131072 routed slots (PR 32; the parent
    # gathered [131072, 2048] twice a chunk).  The row scatter-adds are the
    # measured choice (PERF.md, PR 32): 3.0-3.2 ms for 32768 rows on a v5e
    # against 5.6 for the gather of 131072 and its sum over k.
    assert not re.findall(r"= \(?\w+\[131072,2048\]", text)
    scatters = re.findall(r"= \w+\[(\d+),2048\]\S* scatter\(", text)
    assert scatters and set(scatters) == {"16384"}, scatters
    # The parent's (2f6b8c4) count for this program, a first chunk of 32768
    # places, was 1,734,507,520 bytes.
    # PR 39's was 780,872,704; since PR 45 (the router's logits as bf16
    # products over the split weights and their cotangents' pieces)
    # 781,324,288.
    assert compiled.memory_analysis().temp_size_in_bytes <= 1_000_000_000


# (tokens, d, k, held, experts, width, activation, the most temporary bytes:
# what the parent, 2f6b8c4, took with first chunks of twice the mean share;
# this tree takes 743,271,936, 695,154,688 and 882,345,984): the three cells
# that run moe_ffn(held=); LFM2's by its sizes alone, the router's scoring
# changes nothing here.
_SHARE_CELLS = {
    "smallthinker-21b-a3b": (16384, 2560, 6, 8, 64, 768, "relu",
                             1_337_387_520),
    "sdar-30b-a3b": (16384, 2048, 8, 16, 128, 768, "silu", 1_783_124_480),
    "lfm2-8b-a1b": (16384, 2048, 4, 8, 32, 1792, "silu", 1_833_361_920),
}


@pytest.mark.parametrize("weighted", [False, True], ids=["ones", "weighted"])
@pytest.mark.parametrize("chunk", ["first", "quarter"])
@pytest.mark.parametrize("cell", sorted(_SHARE_CELLS))
def test_rows_to_tokens_compiles_at_the_cells_shapes(cell, chunk, weighted,
                                                     one_chip,
                                                     no_compile_cache):
    """``kernels/rows_to_tokens.py`` for the first chunk of each cell (15,360
    rows of 2560 in 8 runs, 20,480 of 2048 in 16 and in 8) and for a quarter
    of the mean share behind it (3072, 4096), with the router's weights and
    without: the chip's compiler takes the copies of 16-row pieces, the
    transposes of the tokens and weights and the scalars it prefetches."""
    from horovod_tpu.kernels import rows_to_tokens as rt
    from horovod_tpu.parallel.moe import row_buffer, row_quantum

    tokens, d, k, held, experts = _SHARE_CELLS[cell][:5]
    cap = row_buffer(tokens * k, held, experts)[1] if chunk == "first" \
        else row_quantum(tokens * k, held, experts)
    assert rt.takes(cap, d, tokens)
    args = [_shape((cap, d), jnp.bfloat16, one_chip),
            _shape((cap,), jnp.int32, one_chip),
            _shape((held,), jnp.int32, one_chip)]
    if weighted:
        args.append(_shape((cap,), jnp.float32, one_chip))
    text = jax.jit(lambda r, t, g, w=None: rt.rows_to_tokens(
        r, t, g, tokens, w)).lower(*args).compile().as_text()
    assert len(re.findall(rf"%{rt.OP_LINE_NAME}[.\d]* =", text)) == 1
    assert f"f32[{tokens},{d}]" in text and " scatter(" not in text


@pytest.mark.parametrize("cell", sorted(_SHARE_CELLS))
def test_expert_share_through_the_rows_kernel_compiles(cell, topo,
                                                       no_compile_cache,
                                                       monkeypatch):
    """The share of a layer as the cells run it on the chip (under the one
    device's mesh, so inside ``moe_ffn``'s shard_map), with the way back to
    token order through the kernel: four calls (the first chunk's combine and
    dispatch cotangent, and those of the chunks behind it inside their
    loops), no scatter of rows left, grouped products over the first chunk's
    places and a quarter's and none over twice the mean, a ``while`` in each
    direction and no conditional, and less temporary memory than the
    parent's."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.kernels import rows_to_tokens as rt
    from horovod_tpu.parallel.moe import moe_ffn, row_buffer, row_quantum

    tokens, d, k, held, experts, width, act, most = _SHARE_CELLS[cell]
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))

    def shape(dims, dtype, spec=P()):
        return _shape(dims, dtype, NamedSharding(mesh, spec))

    args = [shape((1, tokens, d), jnp.bfloat16, P("data")),
            shape((d, experts), jnp.float32),
            shape((held, d, width), jnp.float32),
            shape((held, d, width), jnp.float32),
            shape((held, width, d), jnp.float32)]

    def loss(*a):
        y, stats = moe_ffn(*a, k=k, held=tuple(range(held)),
                           norm_topk_prob=True, activation=act,
                           data_axis="data")
        return jnp.sum(y.astype(jnp.float32) ** 2) \
            + jnp.sum(stats.load_balancing_loss)

    # The program asks which backend it runs on; here that is the CPU.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile()
    text = compiled.as_text()
    assert len(re.findall(rf"%{rt.OP_LINE_NAME}[.\d]* =", text)) == 4
    assert len(re.findall(r" while\(", text)) == 2
    assert " conditional(" not in text
    assert not re.findall(rf"= \w+\[\d+,{d}\]\S* scatter\(", text)
    products = re.findall(
        r"%ragged-dot-none[.\d]* = \w+\[(\d+),\d+[\],]", text)
    first = row_buffer(tokens * k, held, experts)[1]
    quantum = row_quantum(tokens * k, held, experts)
    assert len(products) == 21, products
    assert products.count(str(first)) == 6
    assert products.count(str(quantum)) == 9
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6 * most


def test_short_conv_compiles_at_lfm2s_shape(one_chip, no_compile_cache):
    """Two sequences of 8192 positions at width 2048, 3 taps: the forward
    and the backward kernel of ``kernels/short_conv.py``, the residual
    ``bcx`` alone."""
    from horovod_tpu.kernels import short_conv as sc

    assert sc.takes(8192, 2048, 3)
    bcx = _shape((2, 8192, 6144), jnp.bfloat16, one_chip)
    w = _shape((2048, 3), jnp.float32, one_chip)

    def loss(bcx, w):
        y = sc._gated_conv(bcx, w, False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(bcx, w).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(hvd_short_conv\w*?)[.\d]* =", text))
    assert kernels == {sc.FWD_NAME, sc.BWD_NAME}, kernels
    assert all(re.match(sc.OP_LINE_NAMES, k) for k in kernels)
    # y and its cotangent besides the arguments and d_bcx: nothing else of
    # their size, and nothing of [b, s, d] in fp32 (134 MB).
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28


@pytest.mark.parametrize("width,start,c,biased", [
    (8512, 4096, 4352, True),       # granite-4.0-h-micro: [z, xBC, dt]
    (2320, 1024, 1280, True),       # nemotron-3-super-120b-a12b's share
    (12288, 0, 8192, False),        # qwen3-next-80b-a3b: [q ; k ; v ; z]
])
def test_causal_conv_compiles_at_the_mixers_shapes(one_chip, no_compile_cache,
                                                   width, start, c, biased):
    """One sequence of 8192 positions, 4 taps over a window of the input
    projection's row: the forward and the backward kernel of
    ``kernels/causal_conv.py`` read the window where it lies (no copy of the
    slice in the program), the residual the row alone."""
    from horovod_tpu.kernels import causal_conv as cc

    assert cc.takes(8192, c, 4)
    row = _shape((1, 8192, width), jnp.bfloat16, one_chip)
    w = _shape((c, 4), jnp.float32, one_chip)
    bias = _shape((c,), jnp.float32, one_chip) if biased else None

    def loss(row, w, bias):
        y = cc._causal_conv(row, w, bias, start, False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2) if biased else (0, 1))
                       ).lower(row, w, bias).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(hvd_causal_conv\w*?)[.\d]* =", text))
    assert kernels == {cc.FWD_NAME, cc.BWD_NAME}, kernels
    assert all(re.match(cc.OP_LINE_NAMES, k) for k in kernels)
    # The kernels' operand is the row itself, not a slice of it.
    for name in kernels:
        call = next(line for line in text.splitlines()
                    if f"%{name}" in line and "custom-call(" in line)
        assert f"bf16[1,8192,{width}]{{2,1,0}}" in call


def test_masked_attention_compiles_at_lfm2s_shape(one_chip, no_compile_cache):
    """Two sequences of 8192 positions, 32 query heads on 8 KV heads of 64
    under the causal rule: the library's forward kernel and the one backward
    kernel take half a lane group as it is, KV heads not repeated, no score
    square in the program."""
    from horovod_tpu.kernels import masked_attention as ma

    rule = ma.Causal()
    assert ma.takes(rule, 8192, 64)
    q = _shape((2, 8192, 32, 64), jnp.bfloat16, one_chip)
    kv = _shape((2, 8192, 8, 64), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(ma.attention(q, k, v, rule).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(splash\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_residuals", "splash_mha_dkv_dq"}, \
        kernels
    assert "8192,8192" not in text
    assert "bf16[2,8192,8,64]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_masked_attention_compiles_at_nemotrons_shape(one_chip,
                                                      no_compile_cache):
    """One sequence of 8192 positions, 4 query heads on the KV head of 128
    that serves them, causal: the same two kernels."""
    from horovod_tpu.kernels import masked_attention as ma

    rule = ma.Causal()
    q = _shape((1, 8192, 4, 128), jnp.bfloat16, one_chip)
    kv = _shape((1, 8192, 1, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(ma.attention(q, k, v, rule).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(splash\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_residuals", "splash_mha_dkv_dq"}, \
        kernels
    assert all(re.match(ma.OP_LINE_NAMES, k) for k in kernels)
    assert "8192,8192" not in text


def test_ssd_scan_compiles_at_nemotrons_shape(one_chip, no_compile_cache):
    """One sequence of 8192 positions, a group of 16 heads of 64 with a state
    of 128, in chunks of 128: the forward and the backward kernel of
    ``kernels/ssd_scan.py``; the residuals are the inputs and the state every
    chunk starts from (33.5 MB in fp32), and nothing the size of a state a
    token (4.3 GB) is in the program."""
    from horovod_tpu.kernels import ssd_scan as ss

    assert ss.takes(8192, 16, 64, 1, 128)
    x = _shape((1, 8192, 1024), jnp.bfloat16, one_chip)
    bc = _shape((1, 8192, 128), jnp.bfloat16, one_chip)
    per_head = _shape((1, 1, 8192, 16), jnp.float32, one_chip)

    def loss(x, b, c, dt, cum):
        y = ss._scan(x, b, c, dt, cum, 64, False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, bc, bc, per_head, per_head).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(hvd_ssd_scan\w*?)[.\d]* =", text))
    assert kernels == {ss.FWD_NAME, ss.BWD_NAME}, kernels
    assert all(re.match(ss.OP_LINE_NAMES, k) for k in kernels)
    assert "f32[1,1,64,8,128,128]" in text          # the chunks' states
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27


def test_gateless_latent_expert_share_compiles_at_nemotrons_widths(
        topo, no_compile_cache, monkeypatch):
    """8192 positions, 22 of 512 experts a token, 8 held, rows of the latent
    1024 against experts of width 2688 without a gate, the router reading the
    model's 4096: a first chunk of 5120 places (the quarter of 704 rows
    rounded up to 1024) through the rows kernel, two grouped products forward
    where a gated expert has three, a ``while`` in each direction."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.kernels import rows_to_tokens as rt
    from horovod_tpu.parallel.moe import moe_ffn, row_buffer, row_quantum

    tokens, d, latent, k, held, experts, width = 8192, 4096, 1024, 22, 8, \
        512, 2688
    assert row_buffer(tokens * k, held, experts) == (172, 5120)
    assert row_quantum(tokens * k, held, experts) == 1024
    assert rt.takes(5120, latent, tokens) and rt.takes(1024, latent, tokens)
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))

    def shape(dims, dtype, spec=P()):
        return _shape(dims, dtype, NamedSharding(mesh, spec))

    args = [shape((1, tokens, latent), jnp.bfloat16, P("data")),
            shape((1, tokens, d), jnp.float32, P("data")),
            shape((d, experts), jnp.float32),
            shape((held, latent, width), jnp.float32),
            shape((held, width, latent), jnp.float32),
            shape((experts,), jnp.float32)]

    def loss(rows, seen, router, up, down, bias):
        y, _ = moe_ffn(rows, router, None, up, down, k=k,
                       held=tuple(range(held)), norm_topk_prob=True,
                       router_input=seen, activation="relu2",
                       scoring="sigmoid", bias=bias, scale=5.0,
                       data_axis="data")
        return jnp.sum(y.astype(jnp.float32) ** 2)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile()
    text = compiled.as_text()
    products = re.findall(
        r"%ragged-dot-none[.\d]* = \w+\[(\d+),\d+[\],]", text)
    # 6 of the first chunk (2 forward, 2 over its rows and 2 that give the
    # stacks' gradients), and the loops' 2 forward, the same 2 recomputed
    # and 4 more backward.
    assert len(products) == 14, products
    assert products.count("5120") == 4 and products.count("1024") == 6
    assert products.count("8") == 4
    assert len(re.findall(rf"%{rt.OP_LINE_NAME}[.\d]* =", text)) == 4
    assert len(re.findall(r" while\(", text)) == 2
    assert " conditional(" not in text
    assert not re.findall(r"= \(?\w+\[180224,1024\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


def test_masked_attention_compiles_at_joyais_widths(one_chip,
                                                    no_compile_cache):
    """One sequence of 8192 positions, 32 heads, keys of 192 over values of
    128, causal (latent attention, nothing grouped): the library's forward
    kernel and the one backward kernel take a lane group and a half as it
    is, and dq and dk come back 192 wide, dv 128."""
    from horovod_tpu.kernels import masked_attention as ma

    rule = ma.Causal()
    assert ma.takes(rule, 8192, 192, 128)
    qk = _shape((1, 8192, 32, 192), jnp.bfloat16, one_chip)
    v = _shape((1, 8192, 32, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(ma.attention(q, k, v, rule).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(splash\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_residuals", "splash_mha_dkv_dq"}, \
        kernels
    assert "8192,8192" not in text
    assert [tuple(x.shape) for x in compiled.output_shardings
            and jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)),
                               qk, qk, v)] == [
        (1, 8192, 32, 192), (1, 8192, 32, 192), (1, 8192, 32, 128)]
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_mla_operands_compile_at_joyais_widths(one_chip, no_compile_cache):
    """One sequence of 8192 positions, 32 heads of 128 + 64: the two kernels
    that finish latent attention's q and k in the attention kernels' layout
    (``kernels/mla_operands.py``), forward and backward, the query's two
    products flat."""
    from horovod_tpu.kernels import mla_operands as mo

    assert mo.takes(8192, 32, 128, 64)
    shapes = {"q_nope": (1, 8192, 32 * 128), "q_rope": (1, 8192, 32 * 64),
              "k_nope": (1, 32, 8192, 128), "k_r": (1, 1, 8192, 64)}
    wide, table = (1, 32, 8192, 192), (8192, 64)

    def both(q_nope, q_rope, k_nope, k_r, cos, sin, dq, dk):
        out, back = jax.vjp(
            lambda *a: mo._operands(*a, cos, sin, 192 ** -0.5, False),
            q_nope, q_rope, k_nope, k_r)
        return out, back((dq, dk))

    args = [_shape(shape, jnp.bfloat16, one_chip)
            for shape in (*shapes.values(), wide, wide)]
    args[4:4] = [_shape(table, jnp.float32, one_chip)] * 2
    compiled = jax.jit(both).lower(*args).compile()
    kernels = set(re.findall(r"%(hvd\w*?)[.\d]* =", compiled.as_text()))
    assert kernels == {mo.FWD_NAME, mo.BWD_NAME}, kernels
    assert [tuple(x.shape) for x in jax.tree_util.tree_leaves(
        jax.eval_shape(both, *args))] == [wide, wide, *shapes.values()]


def test_joyais_step_compiles_and_fits_the_chip(topo, no_compile_cache,
                                                monkeypatch,
                                                record_property):
    """``joyai-llm-flash-wfbp-1chip``'s whole step (loss, gradients, AdamW)
    at the timed sizes under the one device's mesh, as
    ``hvd.make_overlapped_train_step`` builds it: it compiles through the
    kernels' path (the two attention kernels, the rows kernel, no einsum over
    a score square) and the compiler's own count of its memory stays inside
    the 15.75 GiB it may use; the count goes into the junit."""
    import json
    import os

    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
    from horovod_tpu.kernels import masked_attention as ma

    from .helpers import REPO_ROOT
    from .test_joyai_cell import _config_module

    module, sizes = _config_module()
    config = module.Config(sizes)
    tx = config.optimizer(1)
    mesh = Mesh(np.array(topo.devices[:1]), (PROCESS_AXIS,))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P(PROCESS_AXIS))

    def step(params, opt_state, aux, batch):
        (loss, aux), grads = jax.value_and_grad(
            config.loss, has_aux=True)(params, aux, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, aux, loss

    def on(sharding, tree):
        return jax.tree_util.tree_map(
            lambda x: _shape(x.shape, x.dtype, sharding), tree)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params, aux = jax.eval_shape(config.init, key)
    args = (on(rep, params), on(rep, jax.eval_shape(tx.init, params)),
            on(rep, aux), on(rows, jax.eval_shape(config.make_batch, key)))
    # The forward kernel's mask tables are made of numpy arrays at trace
    # time, which a described device cannot hold: built here, outside the
    # mesh, once (the wrapper caches them).
    ma._kernel(ma.Causal(), sizes["sequence_length"],
               sizes["num_attention_heads"], False, False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            *args).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%((?:splash|hvd)\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_residuals", "splash_mha_dkv_dq",
                       "hvd_rows_to_tokens", "hvd_mla_operands_fwd",
                       "hvd_mla_operands_bwd"}, kernels
    for kernel in ("splash_mha_fwd_residuals", "hvd_mla_operands_fwd",
                   "hvd_mla_operands_bwd"):
        assert len(re.findall(rf"%{kernel}[.\d]* =", text)) == 6, kernel
    assert "32,8192,8192" not in text            # the scores, any layout
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    record_property("joyai_step_gib", round(gib, 3))
    record_property("joyai_step_argument_gib",
                    round(mem.argument_size_in_bytes / 2 ** 30, 3))
    record_property("joyai_step_temp_gib",
                    round(mem.temp_size_in_bytes / 2 ** 30, 3))
    assert 14.0 < gib < 15.75, gib
    # The file states what the compiler counted when the configuration was
    # sized (PR 47: 15.08 GiB).  A program that changed since may take less
    # (15.03 since PR 48's router keeps no gather's operands; 14.88 since
    # PR 49 makes the output projection's copy of the attention's output
    # again in the backward pass and keeps it no longer) and never more: the
    # file is the benchmark's, which only a benchmark PR restates.
    with open(os.path.join(REPO_ROOT, "chip_bench/configs",
                           "joyai-llm-flash.json")) as f:
        stated = float(re.search(r"takes ([\d.]+) GiB with 16 experts held",
                                 json.load(f)["assumed"]["fit"]).group(1))
    assert stated - 0.3 < gib < stated + 0.005, (gib, stated)


def test_joyais_float32_twin_compiles(one_chip, no_compile_cache,
                                      monkeypatch):
    """The program's model computed in float32 at the timed sizes, both
    heads' logits: what ``logits_float32_rtol`` reads on the chip.  Its
    forward kernel takes float32 keys of 192 in tiles of 512: at the bf16
    program's 1024 the chip's compiler refused the whole program for 16.9
    MiB of scoped fast memory where the kernel compiled alone passes (my
    chip run, PR 47)."""
    from horovod_tpu.kernels import masked_attention as ma

    from .test_joyai_cell import _config_module

    module, sizes = _config_module()
    config = module.Config(sizes)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _shape(x.shape, x.dtype, one_chip), tree)

    args = (on_chip(jax.eval_shape(config.init, key)[0]),
            on_chip(jax.eval_shape(config.make_batch, key)),
            on_chip(jax.eval_shape(
                lambda: config.reference.zero_bias(sizes))))
    assert ma._wide_float32(_shape((1, 8, 2, 192), jnp.float32, None))
    for shape, dtype in (((1, 8, 2, 192), jnp.bfloat16),
                         ((1, 8, 2, 128), jnp.float32)):
        assert not ma._wide_float32(_shape(shape, dtype, None))
    assert ma._TILES_WIDE_FLOAT32["block_q"] == 512
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = config._logits("program_float32", ()).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%splash_mha_fwd_residuals[.\d]* =", text)) == 6
    assert '\\"block_q\\": 512' in text
    assert "32,8192,8192" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30


def test_gated_delta_compiles_at_qwen3_nexts_shape(one_chip,
                                                   no_compile_cache):
    """One sequence of 8192 positions, 16 key heads serving 32 value heads
    of 128, in chunks of 64: the forward and the backward kernel of
    ``kernels/gated_delta.py``, a grid step's eight value heads as four
    pairs, a pair one block-diagonal chunk 128 wide and the four a leading
    axis of every product (PR 51; the backward is the pairs' algebra through
    ``jax.vjp`` inside the kernel: what the chip's compiler makes of its
    transposed and batched products, and of a cotangent that is a vector a
    pair, shows here and in no interpret-mode test); two kernel names, one
    call of each; the residuals are the inputs and the state every chunk
    starts from (268 MB in fp32, ``f32[1,4,128,8,128,128]``: four grid steps
    of eight heads, as before the pairs), and nothing the size of a state a
    token (17 GB) is in the program."""
    from horovod_tpu.kernels import gated_delta as gd

    assert gd.takes(8192, 16, 32, 128, 128)
    assert gd.heads_a_step(16, 32) == 8
    qk = _shape((1, 8192, 2048), jnp.bfloat16, one_chip)
    v = _shape((1, 8192, 4096), jnp.bfloat16, one_chip)
    per_head = _shape((1, 4, 8192, 8), jnp.float32, one_chip)

    def loss(q, k, v, gamma, beta):
        o = gd._rule(q, k, v, gamma, beta, 2, False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qk, qk, v, per_head, per_head).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(hvd_gated_delta\w*?)[.\d]* =", text))
    assert kernels == {gd.FWD_NAME, gd.BWD_NAME}, kernels
    assert all(re.match(gd.OP_LINE_NAMES, k) for k in kernels)
    assert "f32[1,4,128,8,128,128]" in text         # the chunks' states
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


def test_masked_attention_compiles_at_qwen3_nexts_width(one_chip,
                                                        no_compile_cache):
    """One sequence of 8192 positions, 16 query heads on 2 KV heads of 256,
    causal: the library's forward kernel and the one backward kernel take
    two lane groups a head as they are (the backward keeps a KV head's dk
    and dv, 2 x 8 MiB in fp32 at this width, in fast memory), KV heads not
    repeated, no score square in the program."""
    from horovod_tpu.kernels import masked_attention as ma

    rule = ma.Causal()
    assert ma.takes(rule, 8192, 256)
    q = _shape((1, 8192, 16, 256), jnp.bfloat16, one_chip)
    kv = _shape((1, 8192, 2, 256), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(ma.attention(q, k, v, rule).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(splash\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_residuals", "splash_mha_dkv_dq"}, \
        kernels
    assert all(re.match(ma.OP_LINE_NAMES, k) for k in kernels)
    assert "8192,8192" not in text
    assert "bf16[1,2,8192,256]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


def test_qwen3_nexts_step_compiles_and_fits_the_chip(topo, no_compile_cache,
                                                     monkeypatch,
                                                     record_property):
    """``qwen3-next-80b-a3b-wfbp-1chip``'s whole step (loss, gradients,
    AdamW) at the timed sizes under the one device's mesh, as
    ``hvd.make_overlapped_train_step`` builds it: it compiles through the
    kernels' path (the rule's two kernels a DeltaNet layer, three calls of
    each and no other name of theirs, the pairs' backward through ``jax.vjp``
    inside the one kernel; the convolution's two kernels as often, reading
    ``[q ; k ; v]`` in ``in_proj_qkvz``'s ``[8192, 12288]`` where it lies;
    the two attention
    kernels at width 256, the rows kernel, no einsum over a score square),
    the compiler computes nothing again to make it fit (with 32 experts held
    it does: the configuration's ``fit``), and its own count of the memory
    stays inside the 15.75 GiB it may use; the count goes into the junit."""
    import json
    import os

    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
    from horovod_tpu.kernels import masked_attention as ma

    from .helpers import REPO_ROOT
    from .test_qwen3_next_cell import _config_module

    module, sizes = _config_module()
    config = module.Config(sizes)
    tx = config.optimizer(1)
    mesh = Mesh(np.array(topo.devices[:1]), (PROCESS_AXIS,))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P(PROCESS_AXIS))

    def step(params, opt_state, aux, batch):
        (loss, aux), grads = jax.value_and_grad(
            config.loss, has_aux=True)(params, aux, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, aux, loss

    def on(sharding, tree):
        return jax.tree_util.tree_map(
            lambda x: _shape(x.shape, x.dtype, sharding), tree)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params, aux = jax.eval_shape(config.init, key)
    args = (on(rep, params), on(rep, jax.eval_shape(tx.init, params)),
            on(rep, aux), on(rows, jax.eval_shape(config.make_batch, key)))
    # The forward kernel's mask tables are made of numpy arrays at trace
    # time, which a described device cannot hold: built here, outside the
    # mesh, once (the wrapper caches them).
    ma._kernel(ma.Causal(), sizes["sequence_length"],
               sizes["num_attention_heads"], False, False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            *args).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%((?:splash|hvd)\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_residuals", "splash_mha_dkv_dq",
                       "hvd_rows_to_tokens", "hvd_gated_delta_fwd",
                       "hvd_gated_delta_bwd", "hvd_causal_conv_fwd",
                       "hvd_causal_conv_bwd"}, kernels
    for kernel, calls in (("hvd_gated_delta_fwd", 3),
                          ("hvd_gated_delta_bwd", 3),
                          ("hvd_causal_conv_fwd", 3),
                          ("hvd_causal_conv_bwd", 3),
                          ("splash_mha_fwd_residuals", 1),
                          ("splash_mha_dkv_dq", 1)):
        assert len(re.findall(rf"%{kernel}[.\d]* =", text)) == calls, kernel
    assert "16,8192,8192" not in text            # the scores, any layout
    assert ".remat" not in text                  # nothing computed again
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    record_property("qwen3_next_step_gib", round(gib, 3))
    record_property("qwen3_next_step_argument_gib",
                    round(mem.argument_size_in_bytes / 2 ** 30, 3))
    record_property("qwen3_next_step_temp_gib",
                    round(mem.temp_size_in_bytes / 2 ** 30, 3))
    assert 12.0 < gib < 15.75, gib
    # The file states what the compiler counted when the configuration was
    # sized (PR 50: 13.88 GiB).  A program that changed since may take less
    # and never more: the file is the benchmark's, which only a benchmark PR
    # restates.  PR 57: 12.54, the convolution's residual being the
    # projection's output where it lies and no fp32 copy of [q ; k ; v].
    with open(os.path.join(REPO_ROOT, "chip_bench/configs",
                           "qwen3-next-80b-a3b.json")) as f:
        stated = float(re.search(r"takes ([\d.]+) GiB with 16 experts held",
                                 json.load(f)["assumed"]["fit"]).group(1))
    assert stated - 1.5 < gib < stated + 0.005, (gib, stated)


def test_qwen3_nexts_float32_twin_compiles(one_chip, no_compile_cache,
                                           monkeypatch):
    """The program's model computed in float32 at the timed sizes: what
    ``logits_float32_rtol`` reads on the chip.  The rule goes through
    ``chunked()`` (the kernels take bf16 alone) and the attention layer
    through the splash forward kernel with float32 heads of 256 in tiles of
    512 (``_TILES_WIDE_FLOAT32``, PR 47's finding at 192)."""
    from horovod_tpu.kernels import masked_attention as ma

    from .test_qwen3_next_cell import _config_module

    module, sizes = _config_module()
    config = module.Config(sizes)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _shape(x.shape, x.dtype, one_chip), tree)

    args = (on_chip(jax.eval_shape(config.init, key)[0]),
            on_chip(jax.eval_shape(config.make_batch, key)))
    assert ma._wide_float32(_shape((1, 8, 2, 256), jnp.float32, None))
    ma._kernel(ma.Causal(), sizes["sequence_length"],
               sizes["num_attention_heads"], False, True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = config._logits("program_float32", ()).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%splash_mha_fwd_residuals[.\d]* =", text)) == 1
    assert "hvd_gated_delta" not in text and "hvd_causal_conv" not in text
    assert '\\"block_q\\": 512' in text
    assert "16,8192,8192" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30
