"""Compile xing4.0-29b-a4b's whole step at the timed sizes for a TPU v5e that
is described, not attached (``tests/test_tpu_compile.py`` says how and why):
every block recomputed, inside the memory the file states, with no
recomputation of the compiler's own and with the hyper-connections'
coefficients in the tokens-minor layout.  Nothing runs, so nothing here is a
result or a time.

In a file of its own, so that the minutes the step takes lie on another test
worker than ``tests/test_tpu_compile.py``'s and ``tests/test_xing.py``'s; the
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
    topo,
)

CELL = "xing4.0-29b-a4b-wfbp-1chip"


def test_xings_step_compiles_and_fits_the_chip(topo, no_compile_cache,
                                               monkeypatch, record_property):
    """``xing4.0-29b-a4b-wfbp-1chip``'s whole step (loss, gradients, AdamW)
    at the timed sizes under the one device's mesh, as
    ``hvd.make_overlapped_train_step`` builds it, every block under
    ``nn.remat``: it compiles through the kernels' path (latent attention's
    forward kernel twice a layer, once in the forward pass and once in the
    second forward, its backward kernel once, the two kernels that finish q
    and k likewise; no einsum over a score square), the compiler computes
    nothing again by itself, no temporary is a ``[8192, 4, 4]`` array of
    coefficients, and the compiler's own count of the memory stays inside
    what the configuration's ``fit`` states; the count goes into the junit."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chip_bench import spec
    from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
    from horovod_tpu.kernels import masked_attention as ma

    cell = spec.Cell(CELL, root=REPO_ROOT)
    module, sizes = cell.config_module(), cell.sizes
    config = module.Config(sizes)
    cfg = config.model.cfg
    assert cfg.remat and cfg.hc_mult == 4 and cfg.hc_sinkhorn_iters == 20
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
        == 759_346_190
    args = (on(rep, params), on(rep, jax.eval_shape(tx.init, params)),
            on(rep, aux), on(rows, jax.eval_shape(config.make_batch, key)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            *args).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%((?:splash|hvd)\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_out_lse", "splash_mha_dkv_dq",
                       "hvd_mla_operands_fwd", "hvd_mla_operands_bwd",
                       "hvd_rows_to_tokens",
                       "hvd_hyper_connection_post_bwd",
                       "hvd_hyper_connection_pre_bwd"}, kernels
    for kernel, calls in (("splash_mha_fwd_out_lse", 10),
                          ("splash_mha_dkv_dq", 5),
                          ("hvd_mla_operands_fwd", 10),
                          ("hvd_mla_operands_bwd", 5),
                          ("hvd_hyper_connection_post_bwd", 10),
                          ("hvd_hyper_connection_pre_bwd", 10)):
        assert len(re.findall(rf"%{kernel}[.\d]* =", text)) == calls, kernel
    assert "32,8192,8192" not in text            # the scores, any layout
    assert ".remat" not in text                  # nothing the compiler's own
    # The coefficients keep the tokens minor: no [tokens, 4, 4] (or [tokens,
    # 16], [tokens, 24]) array in fp32, which a TPU pads to (8, 128) tiles.
    # (With its layout: ``{0,1`` is an array held tokens-minor, as the
    # router's four slices of ``f32[8192,64]{0,1}`` are.)
    assert not re.findall(r"f32\[(?:1,)?8192,(?:4,4|16|24)\]\{(?!0,1[:}])",
                          text)
    assert re.findall(r"f32\[4,4,1,8192\]", text)
    # The backward pass of the product with phi is inside the kernel: no
    # cotangent of the flattened streams in fp32.  And the streams reach the
    # kernels as the step holds them, the tokens minor: no copy of theirs.
    assert not re.findall(r"f32\[(?:1,)?8192,14336\]", text)
    assert re.findall(r"bf16\[1,8192,4,3584\]\{1,3,2,0", text)
    assert not re.findall(
        r"= bf16\[(?:1,8192,4,3584|4,3584,8192)\]\S* copy\(", text)
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    record_property("xing_step_gib", round(gib, 3))
    record_property("xing_step_argument_gib",
                    round(mem.argument_size_in_bytes / 2 ** 30, 3))
    record_property("xing_step_temp_gib",
                    round(mem.temp_size_in_bytes / 2 ** 30, 3))
    assert 8.4 < mem.argument_size_in_bytes / 2 ** 30 < 8.6
    assert 11.0 < gib < 15.75, gib
    # The file states what the compiler counted when the configuration was
    # sized.  A program that changed since may take less and never more: the
    # file is the benchmark's, which only a benchmark PR restates.
    with open(os.path.join(REPO_ROOT, "chip_bench/configs",
                           "xing4.0-29b-a4b.json")) as f:
        stated = float(re.search(r"takes ([\d.]+) GiB at one sequence of 8192",
                                 json.load(f)["fit"]).group(1))
    assert stated - 0.5 < gib < stated + 0.005, (gib, stated)
