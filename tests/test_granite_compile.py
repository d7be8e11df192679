"""Compile granite-4.0-h-micro's scan and its whole step at the timed sizes
for a TPU v5e that is described, not attached (``tests/test_tpu_compile.py``
says how and why): the scan's two kernels at a whole group of 64 heads, and
the step with every block recomputed, inside the memory the file states and
with no recomputation of the compiler's own.  Nothing runs, so nothing here is
a result or a time.

In a file of its own, so that the minute the step takes lies on another test
worker than ``tests/test_tpu_compile.py``'s; the topology is described inside
a fixture, never while a module is imported.
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


def test_ssd_scan_compiles_at_granites_shape(one_chip, no_compile_cache):
    """One sequence of 8192 positions, one group of 64 heads of 64 with a
    state of 128, in chunks of 128: the forward and the backward kernel of
    ``kernels/ssd_scan.py`` as they are, 32 lane pairs unrolled a grid step
    and 2 MB of states in VMEM; the residuals are the inputs and the state
    every chunk starts from (134 MB in fp32)."""
    from horovod_tpu.kernels import ssd_scan as ss

    assert ss.takes(8192, 64, 64, 1, 128)
    x = _shape((1, 8192, 4096), jnp.bfloat16, one_chip)
    bc = _shape((1, 8192, 128), jnp.bfloat16, one_chip)
    per_head = _shape((1, 1, 8192, 64), jnp.float32, one_chip)

    def loss(x, b, c, dt, cum):
        y = ss._scan(x, b, c, dt, cum, 64, False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, bc, bc, per_head, per_head).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%(hvd_ssd_scan\w*?)[.\d]* =", text))
    assert kernels == {ss.FWD_NAME, ss.BWD_NAME}, kernels
    assert "f32[1,1,64,32,128,128]" in text         # the chunks' states
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


def test_granites_step_compiles_and_fits_the_chip(topo, no_compile_cache,
                                                  monkeypatch,
                                                  record_property):
    """``granite-4.0-h-micro-wfbp-1chip``'s whole step (loss, gradients,
    AdamW) at the timed sizes under the one device's mesh, as
    ``hvd.make_overlapped_train_step`` builds it, every block under
    ``nn.remat``: it compiles through the kernels' path (the scan's and the
    convolution's forward kernel twice a mixer, once in the forward pass and
    once in the second forward, their backward kernels once, the
    convolution's reading ``xBC`` in ``in_proj``'s ``[8192, 8512]`` where it
    lies; the attention layer's forward kernel
    twice and its backward once; no einsum over a score square), the compiler
    computes nothing again by itself, and its own count of the memory stays
    inside what the configuration's ``fit`` states; the count goes into the
    junit."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.frameworks.jax.wfbp import PROCESS_AXIS
    from horovod_tpu.kernels import masked_attention as ma

    from .test_granite_cell import _config_module

    module, sizes = _config_module()
    config = module.Config(sizes)
    assert config.model.cfg.remat
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
                       "hvd_ssd_scan_fwd", "hvd_ssd_scan_bwd",
                       "hvd_causal_conv_fwd", "hvd_causal_conv_bwd"}, kernels
    for kernel, calls in (("hvd_ssd_scan_fwd", 18), ("hvd_ssd_scan_bwd", 9),
                          ("hvd_causal_conv_fwd", 18),
                          ("hvd_causal_conv_bwd", 9),
                          ("splash_mha_fwd_out_lse", 2),
                          ("splash_mha_dkv_dq", 1)):
        assert len(re.findall(rf"%{kernel}[.\d]* =", text)) == calls, kernel
    assert "32,8192,8192" not in text            # the scores, any layout
    assert ".remat" not in text                  # nothing the compiler's own
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    record_property("granite_step_gib", round(gib, 3))
    record_property("granite_step_argument_gib",
                    round(mem.argument_size_in_bytes / 2 ** 30, 3))
    record_property("granite_step_temp_gib",
                    round(mem.temp_size_in_bytes / 2 ** 30, 3))
    assert 8.6 < mem.argument_size_in_bytes / 2 ** 30 < 8.7
    assert 11.0 < gib < 15.75, gib
    # The file states what the compiler counted when the configuration was
    # sized.  A program that changed since may take less and never more: the
    # file is the benchmark's, which only a benchmark PR restates.
    with open(os.path.join(REPO_ROOT, "chip_bench/configs",
                           "granite-4.0-h-micro.json")) as f:
        stated = float(re.search(r"takes ([\d.]+) GiB at one sequence of 8192",
                                 json.load(f)["fit"]).group(1))
    assert stated - 0.5 < gib < stated + 0.005, (gib, stated)
