"""Compile keye-vl-2.0-30b-a3b's kernels and whole step at the timed sizes for
a TPU v5e that is described, not attached (``tests/test_tpu_compile.py`` says
how and why): the attention kernels under a mask that is data and the
indexer's two kernels alone, then the step, inside the memory the file
states, with no recomputation of the compiler's own.  Nothing runs, so
nothing here is a result or a time.

In a file of its own, so that the minutes the step takes lie on another test
worker than ``tests/test_tpu_compile.py``'s and ``tests/test_keye.py``'s; the
topology is described inside a fixture, never while a module is imported.
"""

import json
import os
import re

import jax
import jax.numpy as jnp

from .helpers import REPO_ROOT
# The fixtures that describe the chip and switch the compile cache off are
# that file's; pytest makes a module-scoped one anew for this module.
from .test_tpu_compile import (  # noqa: F401
    _shape,
    no_compile_cache,
    one_chip,
    topo,
)

CELL = "keye-vl-2.0-30b-a3b-wfbp-1chip"
SEQ, HEADS, KV_HEADS, WIDTH = 16384, 32, 4, 128
I_HEADS, I_WIDTH, TOPK = 16, 64, 2048


def test_the_kernels_compile_at_keyes_shape(one_chip, no_compile_cache):
    """One sequence of 16,384 positions: the attention's forward and backward
    kernel with the chosen sets' words as one more operand (the backward
    turns a tile's words in fast memory: what the chip's compiler makes of an
    int32 transposition and of shifts by a traced count shows here and in no
    interpret-mode test), ``hvd_dsa_choose`` (a block of scores ``[256,
    16384]`` in scratch, loops whose trip count follows the block) and
    ``hvd_dsa_loss`` (keys on the rows, 32 + 48 products a tile); in bf16 as
    the step runs them (in float32, as the configuration's limits run them,
    they compiled for PR 68's builder and run on the chip in every run of
    the cell)."""
    from horovod_tpu.kernels import dsa
    from horovod_tpu.kernels import masked_attention as ma

    dtype, rule = jnp.bfloat16, ma.Sparse(TOPK)
    assert ma.takes(rule, SEQ, WIDTH) and rule.data
    words = _shape((1, SEQ, SEQ // 32), jnp.int32, one_chip)
    q = _shape((1, HEADS, SEQ, WIDTH), dtype, one_chip)
    kv = _shape((1, KV_HEADS, SEQ, WIDTH), dtype, one_chip)
    q_i = _shape((1, I_HEADS, SEQ, I_WIDTH), dtype, one_chip)
    k_i = _shape((1, SEQ, I_WIDTH), dtype, one_chip)
    w = _shape((1, SEQ, I_HEADS), jnp.float32, one_chip)

    def attention(q, k, v, words):
        out, lse = ma.attention_lse_hsd(q, k, v, rule, words)
        return jnp.sum(out.astype(jnp.float32) ** 2), lse

    text = jax.jit(jax.grad(attention, argnums=(0, 1, 2), has_aux=True)) \
        .lower(q, kv, kv, words).compile().as_text()
    assert set(re.findall(r"%(splash\w*?)[.\d]* =", text)) \
        == {ma.FWD_NAME, "splash_mha_dkv_dq"}
    assert "16384,16384" not in text

    text = jax.jit(lambda *a: dsa.choose(*a, topk=TOPK)) \
        .lower(q_i, k_i, w).compile().as_text()
    assert re.findall(r"%(hvd_dsa\w*?)[.\d]* =", text) == [dsa.CHOOSE_NAME]
    assert "16384,16384" not in text

    def loss(q_i, k_i, w, words, lse_i, q, k, lse):
        return dsa.kl_sum(q_i, k_i, w, words, lse_i, q, k, lse)

    row = _shape((1, SEQ), jnp.float32, one_chip)
    lse = _shape((1, HEADS, SEQ), jnp.float32, one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
        .lower(q_i, k_i, w, words, row, q, kv, lse).compile().as_text()
    assert re.findall(r"%(hvd_dsa\w*?)[.\d]* =", text) == [dsa.LOSS_NAME]
    assert all(re.match(dsa.OP_LINE_NAMES, k)
               for k in (dsa.CHOOSE_NAME, dsa.LOSS_NAME))
    assert "16384,16384" not in text


def test_keyes_step_compiles_and_fits_the_chip(topo, no_compile_cache,
                                               monkeypatch, record_property):
    """``keye-vl-2.0-30b-a3b-wfbp-1chip``'s whole step (the three-term loss,
    gradients, AdamW) at the timed sizes under the one device's mesh, as
    ``hvd.make_overlapped_train_step`` builds it: it compiles through the
    kernels' path (a layer: the rotary kernel, the choice, the attention's
    forward kernel under the chosen sets and the loss's kernel forward; the
    attention's backward and the rotary kernel's backward), nothing is
    recomputed, by the configuration or by the compiler, no ``[s, s]`` array
    of any type is alive anywhere, and the compiler's own count of the memory
    stays inside what the configuration's ``fit`` states; the count goes
    into the junit."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chip_bench import spec
    from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS

    cell = spec.Cell(CELL, root=REPO_ROOT)
    module, sizes = cell.config_module(), cell.sizes
    config = module.Config(sizes)
    cfg = config.model.cfg
    assert not cfg.remat and not sizes["recompute_blocks"]
    assert (cfg.indexer_heads, cfg.indexer_head_dim, cfg.indexer_topk) \
        == (I_HEADS, I_WIDTH, TOPK)
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
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == 465_391_104
    args = (on(rep, params), on(rep, jax.eval_shape(tx.init, params)),
            on(rep, aux), on(rows, jax.eval_shape(config.make_batch, key)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            *args).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%((?:splash|hvd)\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_out_lse", "splash_mha_dkv_dq",
                       "hvd_dsa_choose", "hvd_dsa_loss",
                       "hvd_rope_operands_fwd", "hvd_rope_operands_bwd",
                       "hvd_rows_to_tokens"}, kernels
    for kernel in ("splash_mha_fwd_out_lse", "splash_mha_dkv_dq",
                   "hvd_dsa_choose", "hvd_dsa_loss", "hvd_rope_operands_fwd",
                   "hvd_rope_operands_bwd"):
        assert len(re.findall(rf"%{kernel}[.\d]* =", text)) == 4, kernel
    assert "16384,16384" not in text             # no [s, s] table, any type
    assert ".remat" not in text                  # nothing the compiler's own
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    record_property("keye_step_gib", round(gib, 3))
    record_property("keye_step_argument_gib",
                    round(mem.argument_size_in_bytes / 2 ** 30, 3))
    record_property("keye_step_temp_gib",
                    round(mem.temp_size_in_bytes / 2 ** 30, 3))
    print("keye step GiB", gib, mem.argument_size_in_bytes / 2 ** 30,
          mem.temp_size_in_bytes / 2 ** 30)
    assert 5.1 < mem.argument_size_in_bytes / 2 ** 30 < 5.3
    assert 8.0 < gib < 15.75, gib
    with open(os.path.join(REPO_ROOT, "chip_bench/configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        stated = float(re.search(
            r"takes ([\d.]+) GiB at one sequence of 16,384",
            json.load(f)["fit"]).group(1))
    assert stated - 1.0 < gib < stated + 0.005, (gib, stated)
