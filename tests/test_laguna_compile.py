"""Compile laguna-s-2.1's whole step at the timed sizes for a TPU v5e that is
described, not attached (``tests/test_tpu_compile.py`` says how and why):
every block recomputed, inside the memory the file states, with no
recomputation of the compiler's own, through the two attention kernels at
groups of 6 and 9 query heads a KV head, the sliding layers' calls over the
tiles a window of 512 gets (PR 64).  Nothing runs, so nothing here is a
result or a time.

In a file of its own, so that the minute the step takes lies on another test
worker than ``tests/test_tpu_compile.py``'s and ``tests/test_laguna.py``'s;
the topology is described inside a fixture, never while a module is imported.
"""

import collections
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

CELL = "laguna-s-2.1-wfbp-1chip"


def test_lagunas_step_compiles_and_fits_the_chip(topo, no_compile_cache,
                                                 monkeypatch, record_property):
    """``laguna-s-2.1-wfbp-1chip``'s whole step (loss, gradients, AdamW) at
    the timed sizes under the one device's mesh, as
    ``hvd.make_overlapped_train_step`` builds it, every block under
    ``nn.remat``: it compiles through the kernels' path (the forward kernel
    twice a layer, once in the forward pass and once in the second forward,
    the backward kernel once; at 48 and at 72 query heads on 8 KV heads; no
    einsum over a score square), the compiler computes nothing again by
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
    assert cfg.remat and cfg.attention_gate == "head"
    assert [cfg.kind_heads(i) for i in range(5)] == [48, 72, 72, 72, 48]
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
        == 811_017_216
    args = (on(rep, params), on(rep, jax.eval_shape(tx.init, params)),
            on(rep, aux), on(rows, jax.eval_shape(config.make_batch, key)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            *args).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"%((?:splash|hvd)\w*?)[.\d]* =", text))
    assert kernels == {"splash_mha_fwd_out_lse", "splash_mha_dkv_dq",
                       "hvd_rope_operands_fwd", "hvd_rope_operands_bwd",
                       "hvd_rows_to_tokens"}, kernels
    # q and k are turned, scaled and laid out by one kernel a direction in
    # front of each attention kernel (``kernels/rope_operands.py``, PR 65).
    for kernel, calls in (("splash_mha_fwd_out_lse", 10),
                          ("splash_mha_dkv_dq", 5),
                          ("hvd_rope_operands_fwd", 10),
                          ("hvd_rope_operands_bwd", 5)):
        assert len(re.findall(rf"%{kernel}[.\d]* =", text)) == calls, kernel
    # The sliding layers' calls walk the table of the tiles a window of 512
    # gets, 31 of 512 x 512 (the table's length is the kernels' last grid
    # dimension and the length of the three scalar-prefetch operands), the
    # global layers' the 36 causal tiles of 1024 x 1024 they always walked.
    from horovod_tpu.kernels import masked_attention as ma
    from horovod_tpu.kernels import masked_attention_bwd as bwd

    like = jax.ShapeDtypeStruct((1, 72, 8192, 128), jnp.bfloat16)
    walked = {heads: {str(bwd.tile_table(rule, 8192, *tiles[:2])[0].size)
                      for tiles in ma._tiles(rule, like)}   # both kernels'
              for heads, rule in (("72", ma.Window(512)), ("48", ma.Causal()))}
    assert walked == {"72": {"31"}, "48": {"36"}}
    calls = collections.Counter(re.findall(
        r"%(splash_mha\w+?)[.\d]* = [^\n]*?operand_layout_constraints="
        r"\{s32\[(\d+)\]\{0\}, s32\[\2\]\{0\}, s32\[\2\]\{0\}, "
        r"bf16\[1,(\d+),8192,128\]", text))
    assert calls == {("splash_mha_fwd_out_lse", "31", "72"): 6,
                     ("splash_mha_dkv_dq", "31", "72"): 3,
                     ("splash_mha_fwd_out_lse", "36", "48"): 4,
                     ("splash_mha_dkv_dq", "36", "48"): 2}, calls
    # Both head counts reach the kernels, KV heads never repeated.
    assert re.findall(r"bf16\[1,72,8192,128\]", text)
    assert re.findall(r"bf16\[1,48,8192,128\]", text)
    assert not re.findall(r"(?:48|72),8192,8192", text)   # the scores
    assert ".remat" not in text                  # nothing the compiler's own
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    record_property("laguna_step_gib", round(gib, 3))
    record_property("laguna_step_argument_gib",
                    round(mem.argument_size_in_bytes / 2 ** 30, 3))
    record_property("laguna_step_temp_gib",
                    round(mem.temp_size_in_bytes / 2 ** 30, 3))
    print("laguna step GiB", gib, mem.argument_size_in_bytes / 2 ** 30,
          mem.temp_size_in_bytes / 2 ** 30)
    assert 9.0 < mem.argument_size_in_bytes / 2 ** 30 < 9.2
    assert 11.0 < gib < 15.75, gib
    # The file states what the compiler counted when the configuration was
    # sized.  A program that changed since may take less and never more: the
    # file is the benchmark's, which only a benchmark PR restates.
    with open(os.path.join(REPO_ROOT, "chip_bench/configs",
                           "laguna-s-2.1.json")) as f:
        stated = float(re.search(r"takes ([\d.]+) GiB at one sequence of 8192",
                                 json.load(f)["fit"]).group(1))
    assert stated - 0.5 < gib < stated + 0.005, (gib, stated)
