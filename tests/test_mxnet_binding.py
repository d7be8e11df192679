"""MXNet binding vs an async dependency engine (fake_mxnet).

Reference analog: ``mxnet/mpi_ops.cc:182-191`` serializes collectives with
NDArray compute via engine read/write var deps, covered upstream by
``test/parallel/test_mxnet.py``.  Our bridge relies on the NDArray sync
points instead (``asnumpy`` waits for pending writes; ``tensor[:] =``
enqueues a write); these tests run it against ``tests/fake_mxnet.py``'s
genuinely-asynchronous engine so an eager-execution assumption would read
stale buffers and fail.
"""

from __future__ import annotations

import sys

import numpy as np
import pytest

from . import fake_mxnet


@pytest.fixture(scope="module")
def _runtime():
    # One init/shutdown for the module: the eager runtime is a process
    # singleton and cycling it per-test leaves the next init a no-op
    # against a drained background loop.  The fake is installed
    # UNCONDITIONALLY (these tests assert fake types — running against a
    # previously-imported real mxnet would be a different suite) and the
    # prior sys.modules entry is restored afterwards.
    prior = sys.modules.get("mxnet")
    sys.modules["mxnet"] = fake_mxnet
    import horovod_tpu.mxnet as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()
    # The next file's init is to start a loop of its own, not to find this
    # one's drained state still set.
    from horovod_tpu.core import state

    state.reset_global_state()
    if prior is not None:
        sys.modules["mxnet"] = prior
    else:
        sys.modules.pop("mxnet", None)


@pytest.fixture()
def hvd_mx(_runtime):
    return _runtime


@pytest.mark.smoke
def test_allreduce_roundtrip(hvd_mx):
    x = fake_mxnet.nd.array([1.0, 2.0, 3.0])
    out = hvd_mx.allreduce(x, name="mx.rt")
    assert isinstance(out, fake_mxnet.NDArray)
    assert np.allclose(out.asnumpy(), [1.0, 2.0, 3.0])  # size 1: identity


@pytest.mark.smoke
def test_engine_ordering_interleaved_mutation(hvd_mx):
    """Mutate the same NDArray before and after in-place collectives: the
    collective must observe every mutation enqueued before it, and later
    mutations must land after it.  x_{k+1} = 2*x_k + 1 from x_0 = 1 gives
    x_n = 2^(n+1) - 1; any ordering violation (collective reading the
    pre-doubled buffer, or the +1 racing the write-back) breaks the
    closed form."""
    x = fake_mxnet.nd.ones((1024,))
    for _ in range(8):
        x *= 2.0                                   # pending engine write
        hvd_mx.allreduce_(x, name="mx.ord")        # must see the doubling
        x += 1.0                                   # must follow write-back
    assert np.allclose(x.asnumpy(), 2.0 ** 9 - 1.0), x.asnumpy()[:4]


@pytest.mark.smoke
def test_engine_ordering_broadcast_inplace(hvd_mx):
    x = fake_mxnet.nd.array(np.arange(16, dtype=np.float32))
    x *= 3.0
    hvd_mx.broadcast_(x, root_rank=0, name="mx.bc")
    x += 2.0
    assert np.allclose(x.asnumpy(), np.arange(16) * 3.0 + 2.0)


@pytest.mark.smoke
def test_out_of_place_does_not_mutate_input(hvd_mx):
    x = fake_mxnet.nd.array([5.0, 5.0])
    y = hvd_mx.allreduce(x, name="mx.oop")
    x += 1.0
    assert np.allclose(y.asnumpy(), [5.0, 5.0])
    assert np.allclose(x.asnumpy(), [6.0, 6.0])


@pytest.mark.smoke
def test_broadcast_parameters(hvd_mx):
    params = {"w": fake_mxnet.nd.ones((3,)), "b": fake_mxnet.nd.zeros((2,))}
    hvd_mx.broadcast_parameters(params, root_rank=0)
    assert np.allclose(params["w"].asnumpy(), 1.0)
    assert np.allclose(params["b"].asnumpy(), 0.0)
