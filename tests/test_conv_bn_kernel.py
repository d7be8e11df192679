"""Fused conv1x1+BN-stats kernel: numerics vs the unfused composition.

The pallas kernel (`horovod_tpu/kernels/conv_bn_stats.py`) targets the
measured ResNet-50 plateau (ROADMAP.md Q1.4: BN statistics re-read
every activation).  On this CPU rig it runs in interpret mode; the
contract pinned here — values, statistics, gradients, and module output
equal to flax's Conv+BatchNorm — is tile-size independent, so the
compiled TPU path computes the same thing (benchmarks/resnet_levers.py
measures its speed).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.kernels import FusedConv1x1BN, matmul_bn_stats


def _ref(x, w):
    y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    return y, jnp.sum(y, axis=0), jnp.sum(y * y, axis=0)


@pytest.mark.smoke
@pytest.mark.parametrize("m,k,n", [
    (64, 32, 48),        # everything unaligned -> padding on all axes
    (256, 256, 256),     # exact single/multi blocks
    (300, 130, 70),      # ragged
])
def test_matmul_stats_matches_reference(m, k, n):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    w = jnp.asarray(rng.randn(k, n), jnp.float32)
    y, s1, s2 = matmul_bn_stats(x, w, 128, 128, 128)
    yr, s1r, s2r = _ref(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s1r),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s2r),
                               rtol=1e-5, atol=1e-2)


@pytest.mark.smoke
def test_matmul_stats_bf16_inputs():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(128, 64), jnp.bfloat16)
    w = jnp.asarray(rng.randn(64, 96), jnp.bfloat16)
    y, s1, s2 = matmul_bn_stats(x, w, 128, 128, 128)
    assert y.dtype == jnp.bfloat16
    assert s1.dtype == s2.dtype == jnp.float32
    yr = jnp.dot(x, w, preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr),
                               rtol=2e-2, atol=2e-1)
    # stats accumulate in f32 from the f32 accumulator tile
    np.testing.assert_allclose(np.asarray(s1), np.asarray(jnp.sum(yr, 0)),
                               rtol=2e-2, atol=2.0)


@pytest.mark.smoke
def test_matmul_stats_gradients_match():
    """The custom VJP must equal autodiff of the unfused composition for
    a loss that touches y, s1, AND s2 (the BN-shaped dependency)."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(96, 40), jnp.float32)
    w = jnp.asarray(rng.randn(40, 24), jnp.float32)

    def loss_fused(x, w):
        y, s1, s2 = matmul_bn_stats(x, w, 128, 128, 128)
        mean = s1 / y.shape[0]
        var = s2 / y.shape[0] - mean * mean
        return jnp.sum((y - mean) * jax.lax.rsqrt(var + 1e-5)) \
            + 0.1 * jnp.sum(s2)

    def loss_ref(x, w):
        y, s1, s2 = _ref(x, w)
        mean = s1 / y.shape[0]
        var = s2 / y.shape[0] - mean * mean
        return jnp.sum((y - mean) * jax.lax.rsqrt(var + 1e-5)) \
            + 0.1 * jnp.sum(s2)

    gf = jax.grad(loss_fused, argnums=(0, 1))(x, w)
    gr = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-3)


def _flax_pair(features, strides, use_running_average):
    import flax.linen as nn

    class Pair(nn.Module):
        @nn.compact
        def __call__(self, x):
            y = nn.Conv(features, (1, 1), strides, use_bias=False,
                        dtype=jnp.float32, param_dtype=jnp.float32)(x)
            return nn.BatchNorm(
                use_running_average=use_running_average, momentum=0.9,
                epsilon=1e-5, dtype=jnp.float32,
                param_dtype=jnp.float32)(y)

    return Pair()


@pytest.mark.smoke
@pytest.mark.parametrize("strides", [(1, 1), (2, 2)])
def test_fused_module_matches_flax_conv_bn_train(strides):
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 8, 8, 16), jnp.float32)
    fused = FusedConv1x1BN(features=24, strides=strides, dtype=jnp.float32)
    fv = fused.init(jax.random.PRNGKey(0), x)
    ref = _flax_pair(24, strides, use_running_average=False)
    rv = ref.init(jax.random.PRNGKey(0), x)
    # share the conv kernel + BN affine params
    kernel = np.asarray(rng.randn(16, 24), np.float32) * 0.2
    fparams = {"params": {"kernel": jnp.asarray(kernel),
                          "scale": fv["params"]["scale"],
                          "bias": fv["params"]["bias"]},
               "batch_stats": fv["batch_stats"]}
    rparams = {"params": {"Conv_0": {"kernel": jnp.asarray(
                              kernel[None, None])},
                          "BatchNorm_0": {
                              "scale": fv["params"]["scale"],
                              "bias": fv["params"]["bias"]}},
               "batch_stats": rv["batch_stats"]}
    out_f, mut_f = fused.apply(fparams, x, mutable=["batch_stats"])
    out_r, mut_r = ref.apply(rparams, x, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               rtol=1e-4, atol=1e-4)
    for key in ("mean", "var"):
        f = np.asarray(jax.tree_util.tree_leaves(
            {k: v for k, v in mut_f["batch_stats"].items() if key in str(k)}
            or [mut_f["batch_stats"]["mean" if key == "mean" else "var"]])[0])
        r = np.asarray([v for path, v in
                        jax.tree_util.tree_flatten_with_path(
                            mut_r["batch_stats"])[0]
                        if key in jax.tree_util.keystr(path)][0])
        np.testing.assert_allclose(f, r, rtol=1e-4, atol=1e-4,
                                   err_msg=f"running {key} diverged")


@pytest.mark.smoke
def test_fused_module_eval_uses_running_stats():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 4, 4, 8), jnp.float32)
    mod_t = FusedConv1x1BN(features=8, dtype=jnp.float32)
    variables = mod_t.init(jax.random.PRNGKey(0), x)
    mod_e = FusedConv1x1BN(features=8, dtype=jnp.float32,
                           use_running_average=True)
    out = mod_e.apply(variables, x)
    # fresh init: mean 0 / var 1 -> eval output == scale*y + bias == y
    y = jnp.dot(x.reshape(-1, 8), variables["params"]["kernel"])
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, 8),
        np.asarray(y) / np.sqrt(1 + 1e-5), rtol=1e-4, atol=1e-4)


def test_resnet_bottleneck_with_fused_bn_trains():
    """ResNet (bottleneck) with fuse_conv1x1_bn=True: init, one
    value_and_grad step, finite loss/grads, batch_stats updated — the
    integration the levers bench measures on real TPU."""
    import optax

    from horovod_tpu.models.resnet import BottleneckBlock, ResNet

    model = ResNet(stage_sizes=[1, 1], block_cls=BottleneckBlock,
                   num_classes=10, num_filters=8, dtype=jnp.float32,
                   fuse_conv1x1_bn=True)
    x = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3), jnp.float32)
    y = jnp.asarray([1, 2], jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    param_paths = [jax.tree_util.keystr(p) for p, _ in
                   jax.tree_util.tree_flatten_with_path(
                       variables["params"])[0]]
    assert any("FusedConv1x1BN" in p or "fused_proj" in p
               for p in param_paths), param_paths[:10]

    def loss_fn(params):
        logits, mut = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, mut

    (loss, mut), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        variables["params"])
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert flat and all(np.isfinite(np.asarray(g)).all() for g in flat)
    # running stats moved off their init values
    ms = [np.asarray(v) for path, v in
          jax.tree_util.tree_flatten_with_path(mut["batch_stats"])[0]
          if "mean" in jax.tree_util.keystr(path)]
    assert any(np.abs(m).max() > 0 for m in ms), "running means never updated"
    # eval path (running stats, plain matmul) also runs
    logits_eval = model.apply(
        {"params": variables["params"],
         "batch_stats": mut["batch_stats"]}, x, train=False)
    assert np.isfinite(np.asarray(logits_eval)).all()


@pytest.mark.smoke
def test_fused_flag_rejects_other_bn_levers():
    """fuse_conv1x1_bn is hardwired to fp32 one-pass stats; combining it
    with the other BN levers must raise, not silently mix algorithms."""
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet

    for kw in ({"bn_f32_stats": False}, {"bn_fast_variance": False}):
        model = ResNet(stage_sizes=[1], block_cls=BottleneckBlock,
                       num_classes=4, num_filters=8, dtype=jnp.float32,
                       fuse_conv1x1_bn=True, **kw)
        with pytest.raises(ValueError, match="fuse_conv1x1_bn"):
            model.init(jax.random.PRNGKey(0),
                       jnp.ones((1, 16, 16, 3), jnp.float32), train=True)


def test_sharded_kernel_matches_single_device():
    """shard_map flavor on the 8-device virtual mesh: per-shard kernels +
    psum'd statistics must equal the single-device kernel (values AND the
    gradient through a BN-shaped loss) — the multi-chip integration that
    plain pallas_call cannot get from GSPMD."""
    from horovod_tpu.kernels import sharded_matmul_bn_stats
    from horovod_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=8))
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(16 * 8, 32), jnp.float32)   # 16 rows/shard
    w = jnp.asarray(rng.randn(32, 24), jnp.float32)

    def loss_sharded(x, w):
        y, s1, s2 = sharded_matmul_bn_stats(x, w, mesh)
        mean = s1 / y.shape[0]
        var = s2 / y.shape[0] - mean * mean
        return jnp.sum((y - mean) * jax.lax.rsqrt(var + 1e-5))

    def loss_single(x, w):
        y, s1, s2 = matmul_bn_stats(x, w, 128, 128, 128)
        mean = s1 / y.shape[0]
        var = s2 / y.shape[0] - mean * mean
        return jnp.sum((y - mean) * jax.lax.rsqrt(var + 1e-5))

    ys, s1s, s2s = sharded_matmul_bn_stats(x, w, mesh)
    yr, s1r, s2r = matmul_bn_stats(x, w, 128, 128, 128)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(yr),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1s), np.asarray(s1r),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s2s), np.asarray(s2r),
                               rtol=1e-5, atol=1e-2)
    gs = jax.grad(loss_sharded, argnums=(0, 1))(x, w)
    gr = jax.grad(loss_single, argnums=(0, 1))(x, w)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-3)


def test_fused_resnet_trains_on_sharded_mesh():
    """ResNet(fuse_conv1x1_bn=True, fused_bn_mesh=mesh) under the real
    sharded train step on the 8-device virtual mesh: compiles, executes,
    finite loss — the configuration a multi-chip TPU bench would run."""
    import optax

    from horovod_tpu.models.resnet import BottleneckBlock, ResNet
    from horovod_tpu.models.training import (
        create_train_state,
        make_sharded_train_step,
    )
    from horovod_tpu.parallel import MeshSpec, build_mesh, shard_batch

    mesh = build_mesh(MeshSpec(data=8))
    model = ResNet(stage_sizes=[1], block_cls=BottleneckBlock,
                   num_classes=10, num_filters=8, dtype=jnp.float32,
                   fuse_conv1x1_bn=True, fused_bn_mesh=mesh)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(8, 32, 32, 3), jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, size=(8,)), jnp.int32)
    tx = optax.sgd(0.1)
    state = create_train_state(model, jax.random.PRNGKey(0), x, tx,
                               mesh=mesh, init_kwargs={"train": True})
    step = make_sharded_train_step(model, tx, mesh, has_batch_stats=True,
                                   donate=False)
    batch = shard_batch(mesh, {"x": x, "y": y})
    state, loss = step(state, batch)
    assert np.isfinite(float(loss)), loss
