"""Compile ling-3.0-flash-vl's kernels and whole step at the timed sizes for a
TPU v5e that is described, not attached (``tests/test_tpu_compile.py`` says
how and why): Kimi Delta Attention's two kernels alone, then the step with
every block recomputed, inside the memory the file states, with no
recomputation of the compiler's own.  Nothing runs, so nothing here is a
result or a time.

In a file of its own, so that the minutes the step takes lie on another test
worker than ``tests/test_tpu_compile.py``'s and ``tests/test_ling.py``'s; the
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

CELL = "ling-3.0-flash-vl-wfbp-1chip"


def test_kda_compiles_at_lings_shape(one_chip, no_compile_cache):
    """One sequence of 8192 positions, 32 heads of 128, in chunks of 64 and
    sub-blocks of 16: the forward and the backward kernel of
    ``kernels/kda.py``, eight heads a grid step as a leading axis of every
    product (the backward is the chunk's algebra through ``jax.vjp`` inside
    the kernel: what the chip's compiler makes of its batched products over
    ``[heads * 4, 16, 128]``, of the running sum as a triangular product and
    of the transposed states shows here and in no interpret-mode test); two
    kernel names, one call of each; the residuals are the inputs and the
    state every chunk starts from (268 MB in fp32,
    ``f32[1,4,128,8,128,128]``)."""
    from horovod_tpu.kernels import kda

    assert kda.takes(8192, 32, 128, 128) and kda.heads_a_step(32) == 8
    wide = _shape((1, 8192, 4096), jnp.bfloat16, one_chip)
    decays = _shape((1, 8192, 4096), jnp.float32, one_chip)
    beta = _shape((1, 4, 8192, 8), jnp.float32, one_chip)

    def loss(q, k, v, g, beta):
        o = kda._rule(q, k, v, g, beta, False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        wide, wide, wide, decays, beta).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(hvd_kda\w*?)[.\d]* =", text))
    assert kernels == {kda.FWD_NAME, kda.BWD_NAME}, kernels
    assert all(re.match(kda.OP_LINE_NAMES, k) for k in kernels)
    assert "f32[1,4,128,8,128,128]" in text         # the chunks' states
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


def test_lings_step_compiles_and_fits_the_chip(topo, no_compile_cache,
                                               monkeypatch, record_property):
    """``ling-3.0-flash-vl-wfbp-1chip``'s whole step (loss, gradients, AdamW)
    at the timed sizes under the one device's mesh, as
    ``hvd.make_overlapped_train_step`` builds it, every block under
    ``nn.remat``: it compiles through the kernels' path (the rule's and the
    convolution's forward kernel twice a KDA layer, once in the forward pass
    and once in the second forward, their backward once; the attention's
    likewise in the one latent layer), the compiler computes nothing again by
    itself, and its own count of the memory stays inside what the
    configuration's ``fit`` states; the count goes into the junit."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chip_bench import spec
    from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS

    cell = spec.Cell(CELL, root=REPO_ROOT)
    module, sizes = cell.config_module(), cell.sizes
    config = module.Config(sizes)
    cfg = config.model.cfg
    assert cfg.remat and cfg.attention_gate == "head" and not cfg.q_lora_rank
    assert [cfg.layer_kind(i).mixer for i in range(7)] \
        == ["kda"] * 4 + ["attention"] + ["kda"] * 2
    assert (cfg.moe_groups, cfg.moe_groups_kept) == (8, 4)
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
        == sizes["parameters"] == 884_456_384
    args = (on(rep, params), on(rep, jax.eval_shape(tx.init, params)),
            on(rep, aux), on(rows, jax.eval_shape(config.make_batch, key)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            *args).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%((?:splash|hvd)\w*?)[.\d]* =", text))
    assert kernels == {"hvd_kda_fwd", "hvd_kda_bwd", "hvd_causal_conv_fwd",
                       "hvd_causal_conv_bwd", "splash_mha_fwd_out_lse",
                       "splash_mha_dkv_dq", "hvd_mla_operands_fwd",
                       "hvd_mla_operands_bwd", "hvd_rows_to_tokens",
                       "hvd_head_rows_gate_fwd", "hvd_head_rows_gate_bwd",
                       "hvd_head_rows_norm_fwd",
                       "hvd_head_rows_norm_bwd"}, kernels
    for kernel, calls in (("hvd_kda_fwd", 12), ("hvd_kda_bwd", 6),
                          ("hvd_head_rows_gate_fwd", 12),
                          ("hvd_head_rows_gate_bwd", 6),
                          ("hvd_head_rows_norm_fwd", 12),
                          ("hvd_head_rows_norm_bwd", 6),
                          ("hvd_causal_conv_fwd", 12),
                          ("hvd_causal_conv_bwd", 6),
                          ("splash_mha_fwd_out_lse", 2),
                          ("splash_mha_dkv_dq", 1),
                          ("hvd_mla_operands_fwd", 2),
                          ("hvd_mla_operands_bwd", 1)):
        assert len(re.findall(rf"%{kernel}[.\d]* =", text)) == calls, kernel
    assert "32,8192,8192" not in text            # the scores, any layout
    # Between the convolution and ``out_proj`` a head is a lane group of the
    # flat row (``kernels/head_rows.py``): no instruction of the six mixers
    # holds the heads as an axis, in any dtype.  (The latent-attention
    # layer's own per-head arrays lie under ``attn.`` scopes.)
    head_major = [line for line in text.splitlines()
                  if "8192,32,128" in line
                  and re.search(r'op_name="[^"]*hvd\.kda\.', line)]
    assert not head_major, head_major[:3]
    assert ".remat" not in text                  # nothing the compiler's own
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    record_property("ling_step_gib", round(gib, 3))
    record_property("ling_step_argument_gib",
                    round(mem.argument_size_in_bytes / 2 ** 30, 3))
    record_property("ling_step_temp_gib",
                    round(mem.temp_size_in_bytes / 2 ** 30, 3))
    print("ling step GiB", gib, mem.argument_size_in_bytes / 2 ** 30,
          mem.temp_size_in_bytes / 2 ** 30)
    assert 9.8 < mem.argument_size_in_bytes / 2 ** 30 < 10.0
    assert 11.0 < gib < 15.75, gib
    # The file states what the compiler counted when the configuration was
    # sized.  A program that changed since may take less and never more: the
    # file is the benchmark's, which only a benchmark PR restates.  PR 67
    # took the fp32 rows and the head-major copies around the rule away:
    # 13.80 GiB, so the lower side alone is a GiB wide.
    with open(os.path.join(REPO_ROOT, "chip_bench/configs",
                           "ling-3.0-flash-vl.json")) as f:
        stated = float(re.search(r"takes ([\d.]+) GiB at one sequence of 8192",
                                 json.load(f)["fit"]).group(1))
    assert stated - 1.0 < gib < stated + 0.005, (gib, stated)
