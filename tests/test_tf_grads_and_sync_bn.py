"""TF gradient registration + TF/Torch SyncBatchNorm.

Mirrors the reference's gradient-correctness tests
(``test_tensorflow.py:674-825`` style: differentiate THROUGH the
collective, compare against the closed form) and the sync-BN contract
(N ranks with per-rank batches normalize exactly like one rank with the
concatenated batch).
"""

from __future__ import annotations

import textwrap

import pytest

from tests.helpers import run_distributed


def _shut_down(hvd):
    """Shut the runtime this process started down and make its state new:
    ``init()`` on a state whose loop has drained is a no-op
    (``core/state.py::initialize``), and the next file in this process may
    need a runtime of its own."""
    from horovod_tpu.core import state

    hvd.shutdown()
    state.reset_global_state()


def test_tf_allreduce_gradient_two_ranks():
    """d/dx of sum(allreduce(x, Sum)) == size (each rank's x contributes to
    every rank's output once; custom gradient = allreduce of upstream)."""
    body = textwrap.dedent("""
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvdtf

    x = tf.constant([1.0, 2.0, 3.0]) * (rank + 1)
    with tf.GradientTape() as tape:
        tape.watch(x)
        y = hvdtf.allreduce(x, op=hvdtf.Sum, name="g.ar")
        loss = tf.reduce_sum(y)
    g = tape.gradient(loss, x)
    # loss = sum_r sum(x_r) on every rank; dL/dx = allreduce(ones, Sum) = size
    assert np.allclose(g.numpy(), 2.0), g.numpy()
    print("AR_GRAD_OK", rank)
    """)
    for out in run_distributed(2, body, timeout=180):
        assert "AR_GRAD_OK" in out


def test_tf_broadcast_and_allgather_gradients_two_ranks():
    body = textwrap.dedent("""
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvdtf

    # broadcast: grad accumulates on root, zero elsewhere
    x = tf.constant([1.0, 2.0])
    with tf.GradientTape() as tape:
        tape.watch(x)
        y = hvdtf.broadcast(x, root_rank=0, name="g.bc")
        loss = tf.reduce_sum(y * (rank + 1.0))
    g = tape.gradient(loss, x).numpy()
    if rank == 0:
        # every rank's upstream (rank+1) sums: 1 + 2 = 3
        assert np.allclose(g, 3.0), g
    else:
        assert np.allclose(g, 0.0), g

    # allgather: grad is the rank's own slice of the summed upstream
    z = tf.constant([[1.0], [2.0]]) * (rank + 1)
    with tf.GradientTape() as tape:
        tape.watch(z)
        y = hvdtf.allgather(z, name="g.ag")      # [4, 1]
        w = tf.constant([[1.0], [2.0], [3.0], [4.0]]) * (rank + 1.0)
        loss = tf.reduce_sum(y * w)
    g = tape.gradient(loss, z).numpy()
    # upstream dy = w_r on rank r; summed over ranks = [1,2,3,4]*(1+2)=3*
    expected = np.array([[3.0], [6.0]]) if rank == 0 else np.array([[9.0], [12.0]])
    assert np.allclose(g, expected), (rank, g)
    print("BC_AG_GRAD_OK", rank)
    """)
    for out in run_distributed(2, body, timeout=180):
        assert "BC_AG_GRAD_OK" in out


def test_tf_allreduce_gradient_inside_tf_function():
    """Graph mode: the custom gradient must survive @tf.function tracing
    (the py_function path has no intrinsic gradient)."""
    body = textwrap.dedent("""
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvdtf

    @tf.function
    def f(x):
        with tf.GradientTape() as tape:
            tape.watch(x)
            loss = tf.reduce_sum(hvdtf.allreduce(x, op=hvdtf.Sum, name="g.fn"))
        return tape.gradient(loss, x)

    g = f(tf.constant([1.0, 1.0]))
    assert np.allclose(g.numpy(), 2.0), g.numpy()
    print("FN_GRAD_OK", rank)
    """)
    for out in run_distributed(2, body, timeout=180):
        assert "FN_GRAD_OK" in out


def test_tf_sync_batch_norm_matches_big_batch():
    """2 ranks × batch 4 with SyncBatchNormalization == 1 process × batch 8
    with plain BatchNormalization (moments averaged across ranks)."""
    body = textwrap.dedent("""
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvdtf

    rng = np.random.RandomState(42)
    full = rng.rand(8, 3).astype(np.float32) * 4 - 2
    local = full[rank * 4:(rank + 1) * 4]

    sbn = hvdtf.SyncBatchNormalization(momentum=0.5, epsilon=1e-5)
    out = sbn(tf.constant(local), training=True)

    # closed form on the FULL batch
    mean = full.mean(axis=0)
    var = full.var(axis=0)
    expected = (local - mean) / np.sqrt(var + 1e-5)
    assert np.allclose(out.numpy(), expected, atol=1e-4), \\
        np.abs(out.numpy() - expected).max()
    # running stats adopted the global moments
    assert np.allclose(sbn.moving_mean.numpy(), 0.5 * mean, atol=1e-4)
    print("TF_SBN_OK", rank)
    """)
    for out in run_distributed(2, body, timeout=180):
        assert "TF_SBN_OK" in out


def test_torch_sync_batch_norm_matches_big_batch():
    pytest.importorskip("torch")
    body = textwrap.dedent("""
    import torch
    import horovod_tpu.torch as hvdt

    rng = np.random.RandomState(7)
    full = rng.rand(8, 3, 2).astype(np.float32) * 4 - 2
    local = torch.tensor(full[rank * 4:(rank + 1) * 4], requires_grad=True)

    sbn = hvdt.SyncBatchNorm(3, momentum=0.5, eps=1e-5)
    sbn.train()
    out = sbn(local)

    flat = full.transpose(1, 0, 2).reshape(3, -1)
    mean = flat.mean(axis=1)
    var = flat.var(axis=1)
    expected = (full[rank*4:(rank+1)*4] - mean[None, :, None]) \\
        / np.sqrt(var[None, :, None] + 1e-5)
    assert np.allclose(out.detach().numpy(), expected, atol=1e-4), \\
        np.abs(out.detach().numpy() - expected).max()

    # gradient parity with the big-batch reference BN
    loss = (out * torch.tensor(full[rank*4:(rank+1)*4] + 1.0)).sum()
    loss.backward()

    ref_in = torch.tensor(full, requires_grad=True)
    bn = torch.nn.BatchNorm2d(3, momentum=0.5, eps=1e-5) if False else \\
        torch.nn.BatchNorm1d(3, momentum=0.5, eps=1e-5)
    ref_out = bn(ref_in)
    ref_loss = (ref_out * torch.tensor(full + 1.0)).sum()
    ref_loss.backward()
    ref_grad = ref_in.grad.numpy()[rank*4:(rank+1)*4]
    assert np.allclose(local.grad.numpy(), ref_grad, atol=1e-3), \\
        np.abs(local.grad.numpy() - ref_grad).max()

    # running stats match the big batch's (unbiased var)
    assert np.allclose(sbn.running_mean.numpy(), 0.5 * mean, atol=1e-4)
    print("TORCH_SBN_OK", rank)
    """)
    for out in run_distributed(2, body, timeout=240):
        assert "TORCH_SBN_OK" in out


def test_torch_sync_bn_single_process_matches_plain_bn():
    """size=1: SyncBatchNorm must equal nn.BatchNorm exactly."""
    torch = pytest.importorskip("torch")
    import numpy as np

    import horovod_tpu.torch as hvdt

    hvdt.init()  # size() is runtime state, like the reference
    rng = np.random.RandomState(0)
    x = torch.tensor(rng.rand(6, 4).astype(np.float32), requires_grad=True)
    x2 = x.detach().clone().requires_grad_(True)

    sbn = hvdt.SyncBatchNorm(4, momentum=0.3)
    bn = torch.nn.BatchNorm1d(4, momentum=0.3)
    sbn.train(), bn.train()

    out_s = sbn(x)
    out_b = bn(x2)
    assert torch.allclose(out_s, out_b, atol=1e-5)

    out_s.sum().backward()
    out_b.sum().backward()
    assert torch.allclose(x.grad, x2.grad, atol=1e-5)
    assert torch.allclose(sbn.running_var, bn.running_var, atol=1e-5)
    _shut_down(hvdt)


def test_tf_sync_bn_multiple_instances():
    """Two SyncBatchNormalization layers must coexist in one model
    (auto-naming; distinct wire names)."""
    tf = pytest.importorskip("tensorflow")
    import numpy as np

    import horovod_tpu.tensorflow as hvdtf

    hvdtf.init()  # _moments consults size(), runtime state like the reference
    model = tf.keras.Sequential([
        tf.keras.layers.Input((4,)),
        tf.keras.layers.Dense(4),
        hvdtf.SyncBatchNormalization(),
        tf.keras.layers.Dense(4),
        hvdtf.SyncBatchNormalization(),
    ])
    out = model(np.random.rand(6, 4).astype("float32"), training=True)
    assert out.shape == (6, 4)
    names = [l.name for l in model.layers if "batch" in l.name.lower()]
    assert len(set(names)) == 2, names
    _shut_down(hvdtf)
