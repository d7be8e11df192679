"""Compile joyai-llm-flash's kernels and its whole step at the timed sizes for
a TPU v5e that is described, not attached (``tests/test_tpu_compile.py`` says
how and why): the two attention kernels at keys of 192 over values of 128,
the two kernels that finish latent attention's q and k, the step inside the
memory the file states (its GiB go into the junit) and the float32 twin's
logits program.  Nothing runs, so nothing here is a result or a time.

In a file of its own, as every configuration's compiles are: a file is what a
test worker takes, and the two whole programs are a minute and more each.
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


def test_masked_attention_compiles_at_joyais_widths(one_chip,
                                                    no_compile_cache):
    """One sequence of 8192 positions, 32 heads, keys of 192 over values of
    128, causal (latent attention, nothing grouped): the forward
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
    assert kernels == {"splash_mha_fwd_out_lse", "splash_mha_dkv_dq"}, \
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
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            *args).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%((?:splash|hvd)\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_out_lse", "splash_mha_dkv_dq",
                       "hvd_rows_to_tokens", "hvd_mla_operands_fwd",
                       "hvd_mla_operands_bwd"}, kernels
    for kernel in ("splash_mha_fwd_out_lse", "hvd_mla_operands_fwd",
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
    causal = ma.Causal()
    assert ma._tiles(causal, _shape((1, 8, 2, 192), jnp.float32, None))[0] \
        == ma.FWD_TILES_WIDE_FLOAT32 == (512, 512, 512)
    for shape, dtype in (((1, 8, 2, 192), jnp.bfloat16),
                         ((1, 8, 2, 128), jnp.float32)):
        assert ma._tiles(causal, _shape(shape, dtype, None))[0] \
            == ma.FWD_TILES
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = config._logits("program_float32", ()).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%splash_mha_fwd_out_lse[.\d]* =", text)) == 6
    assert "32,8192,8192" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30
