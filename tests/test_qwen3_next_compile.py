"""Compile qwen3-next-80b-a3b's kernels and its whole step at the timed sizes
for a TPU v5e that is described, not attached (``tests/test_tpu_compile.py``
says how and why): the gated delta rule's two kernels, the two attention
kernels at 16 heads on 2 of 256, the step inside the memory the file states
(its GiB go into the junit; no ``.remat`` instruction in it) and the float32
twin's logits program.  Nothing runs, so nothing here is a result or a time.

In a file of its own, as every configuration's compiles are: a file is what a
test worker takes, and the two whole programs are a minute each.
"""

import re

import jax
import jax.numpy as jnp

# The fixtures that describe the chip and switch the compile cache off are
# that file's; pytest makes a module-scoped one anew for this module.
from .test_tpu_compile import (  # noqa: F401
    _shape,
    no_compile_cache,
    one_chip,
    topo,
)


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
    causal: the forward kernel and the one backward kernel take
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
    assert kernels == {"splash_mha_fwd_out_lse", "splash_mha_dkv_dq"}, \
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
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            *args).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%((?:splash|hvd)\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_out_lse", "splash_mha_dkv_dq",
                       "hvd_rows_to_tokens", "hvd_gated_delta_fwd",
                       "hvd_gated_delta_bwd", "hvd_causal_conv_fwd",
                       "hvd_causal_conv_bwd"}, kernels
    for kernel, calls in (("hvd_gated_delta_fwd", 3),
                          ("hvd_gated_delta_bwd", 3),
                          ("hvd_causal_conv_fwd", 3),
                          ("hvd_causal_conv_bwd", 3),
                          ("splash_mha_fwd_out_lse", 1),
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
    through the forward kernel with float32 heads of 256 in tiles of 512
    (``FWD_TILES_WIDE_FLOAT32``, PR 47's finding at 192)."""
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
    assert ma._tiles(ma.Causal(), _shape((1, 8, 2, 256), jnp.float32,
                                         None))[0] \
        == ma.FWD_TILES_WIDE_FLOAT32 == (512, 512, 512)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = config._logits("program_float32", ()).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%splash_mha_fwd_out_lse[.\d]* =", text)) == 1
    assert "hvd_gated_delta" not in text and "hvd_causal_conv" not in text
    assert "16,8192,8192" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30
