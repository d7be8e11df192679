"""Compile the main path's kernels at their real widths for a TPU v5e that
is described, not attached: what the chip's compiler refuses (a block that
does not fit the fast memory, a slice off the tiling) fails here, at no chip
time.  Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture, never while a module is imported:
every pytest worker imports every test file, and describing a chip loads the
TPU's library, which a process has to itself unless it is told otherwise (the
driver's command sets ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, so six workers may).

This file has the fixtures and the kernels that compile alone in seconds.
A configuration whose whole step is compiled has a file of its own with its
kernels beside the step (``test_joyai_compile.py``,
``test_qwen3_next_compile.py``, ``test_granite_compile.py``,
``test_xing_compile.py``), and so has the expert layer
(``test_moe_compile.py``); they import the fixtures from here.  A file is what
a test worker takes (``--dist loadfile``), and none is to sum to more than
220 s (``ROADMAP.md`` D0).
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


def test_blockdiff_attention_compiles_at_sdars_shape(one_chip,
                                                     no_compile_cache):
    """One sequence as [x_t ; x_0], 16384 positions, 32 query heads on 4 KV
    heads of 128, blocks of 4: the forward kernel and the one
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
    assert kernels == {"splash_mha_fwd_out_lse", "splash_mha_dkv_dq"}, \
        kernels
    assert all(re.match(bd.OP_LINE_NAMES, k) for k in kernels)
    assert "16384,16384" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("rule_name", ["window", "causal"])
def test_masked_attention_compiles_at_smallthinkers_shape(rule_name, one_chip,
                                                          no_compile_cache):
    """One sequence of 16384 positions, 28 query heads on 4 KV heads of 128,
    causal and causal inside a window of 4096: the forward kernel
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
    assert kernels == {"splash_mha_fwd_out_lse", "splash_mha_dkv_dq"}, \
        kernels
    assert all(re.match(ma.OP_LINE_NAMES, k) for k in kernels)
    assert "16384,16384" not in text
    assert "28,16384,128" in text and "bf16[1,16384,28,128]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_masked_attention_compiles_at_lagunas_sliding_shape(one_chip,
                                                            no_compile_cache):
    """One sequence of 8192 positions, 72 query heads on 8 KV heads of 128
    inside a window of 512: both kernels over the 31 tiles of 512 x 512 that
    ``masked_attention._tiles`` gives a window narrower than a tile (the
    table's length is the length of the scalar-prefetch operands), where
    tiles of 1024 made 15; KV heads not repeated, no score square."""
    from horovod_tpu.kernels import masked_attention as ma

    rule = ma.Window(512)
    q = _shape((1, 8192, 72, 128), jnp.bfloat16, one_chip)
    kv = _shape((1, 8192, 8, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(ma.attention(q, k, v, rule).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    tables = dict(re.findall(
        r"%(splash\w*?)[.\d]* = [^\n]*?operand_layout_constraints="
        r"\{s32\[(\d+)\]", text))
    assert tables == {"splash_mha_fwd_out_lse": "31",
                      "splash_mha_dkv_dq": "31"}, tables
    assert "8192,8192" not in text
    assert "72,8192,128" in text and "bf16[1,8192,72,128]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


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
    under the causal rule: the forward kernel and the one backward
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
    assert kernels == {"splash_mha_fwd_out_lse", "splash_mha_dkv_dq"}, \
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
    assert kernels == {"splash_mha_fwd_out_lse", "splash_mha_dkv_dq"}, \
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


