"""Eager collective API over jax/numpy arrays.

Role of the reference's per-framework op modules (``torch/mpi_ops.py:85-630``,
``tensorflow/mpi_ops.py``): blocking and ``*_async`` variants of
allreduce / allgather / broadcast / alltoall plus ``join`` and ``barrier``,
all funneling into the core enqueue API.  jax arrays are staged to host
numpy for the controller/data plane and rehydrated on the way out; inside
``jit`` use the SPMD collectives (``horovod_tpu.parallel``) instead — that is
the fast TPU path, this is the any-tensor-any-time compatibility path.

Average is implemented as a postscale of 1/size exactly like the reference
(``operations.cc:953-956``).
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional

import numpy as np

from ...common.exceptions import HorovodInternalError
from ...core.handle_manager import HandleManager
from ...core.messages import RequestType
from ...core.state import global_state
from ...core.tensor_queue import Status
from ...core.timeline import phase

# Reduce-op constants (reference ``horovod/torch/mpi_ops.py`` Sum/Average/Adasum)
Sum = "sum"
Average = "average"
Adasum = "adasum"

_handles = HandleManager()
_name_lock = threading.Lock()
_name_counters = {}


def _auto_name(kind: str, name: Optional[str]) -> str:
    """Deterministic auto-naming: relies on identical call order across ranks,
    the same contract the reference's bindings use for unnamed tensors."""
    if name is not None:
        return name
    with _name_lock:
        n = _name_counters.get(kind, 0)
        _name_counters[kind] = n + 1
    return f"{kind}.noname.{n}"


def _to_numpy(tensor: Any):
    """Returns (tensor, rehydrate_fn).  jax arrays pass through unchanged —
    the core decides per-tensor whether they stay on device (XLA data
    plane) or are staged to host (TCP plane); either way a jax caller gets
    a jax array back."""
    try:
        import jax

        if isinstance(tensor, jax.Array):
            import jax.numpy as jnp

            return tensor, jnp.asarray
    except ImportError:  # pragma: no cover
        pass
    return np.asarray(tensor), lambda out: out


def _make_callback(handle: int, rehydrate, extract=None):
    def cb(status: Status, entry):
        if not status.ok:
            _handles.mark_done(handle, status)
            return
        if extract is not None:
            _handles.mark_done(handle, status, extract(entry))
        else:
            _handles.mark_done(handle, status, rehydrate(entry.output))
    return cb


def _submit(handle: int, enqueue_fn):
    """Run the enqueue; release the handle if it never made it into the
    queue (e.g. DuplicateNameError) so failed calls cannot leak events."""
    try:
        enqueue_fn()
    except BaseException:
        _handles.discard(handle)
        raise
    return handle


# ---------------------------------------------------------------------------
# allreduce
# ---------------------------------------------------------------------------

def allreduce_async(tensor, average: Optional[bool] = None, name: Optional[str] = None,
                    op: Optional[str] = None, prescale_factor: float = 1.0,
                    postscale_factor: float = 1.0) -> int:
    state = global_state()
    state._check_initialized()
    if op is None:
        op = Average if (average or average is None) else Sum
    elif average is not None:
        raise ValueError("specify either average or op, not both")
    request_type = RequestType.ADASUM if op == Adasum else RequestType.ALLREDUCE
    if op == Average:
        postscale_factor = postscale_factor / state.topo.size

    np_val, rehydrate = _to_numpy(tensor)
    name = _auto_name("allreduce", name)
    handle = _handles.allocate()
    return _submit(handle, lambda: state.enqueue_allreduce(
        name, np_val, _make_callback(handle, rehydrate),
        prescale_factor=prescale_factor, postscale_factor=postscale_factor,
        op=request_type))


def allreduce(tensor, average: Optional[bool] = None, name: Optional[str] = None,
              op: Optional[str] = None, prescale_factor: float = 1.0,
              postscale_factor: float = 1.0):
    return synchronize(allreduce_async(
        tensor, average=average, name=name, op=op,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor))


# ---------------------------------------------------------------------------
# allgather
# ---------------------------------------------------------------------------

def allgather_async(tensor, name: Optional[str] = None) -> int:
    state = global_state()
    np_val, rehydrate = _to_numpy(tensor)
    handle = _handles.allocate()
    return _submit(handle, lambda: state.enqueue_allgather(
        _auto_name("allgather", name), np_val,
        _make_callback(handle, rehydrate)))


def allgather(tensor, name: Optional[str] = None):
    return synchronize(allgather_async(tensor, name=name))


# ---------------------------------------------------------------------------
# broadcast
# ---------------------------------------------------------------------------

def broadcast_async(tensor, root_rank: int, name: Optional[str] = None) -> int:
    state = global_state()
    np_val, rehydrate = _to_numpy(tensor)
    handle = _handles.allocate()
    return _submit(handle, lambda: state.enqueue_broadcast(
        _auto_name("broadcast", name), np_val, root_rank,
        _make_callback(handle, rehydrate)))


def broadcast(tensor, root_rank: int, name: Optional[str] = None):
    return synchronize(broadcast_async(tensor, root_rank, name=name))


# ---------------------------------------------------------------------------
# alltoall
# ---------------------------------------------------------------------------

def alltoall_async(tensor, splits: Optional[List[int]] = None,
                   name: Optional[str] = None) -> int:
    state = global_state()
    np_val, rehydrate = _to_numpy(tensor)
    handle = _handles.allocate()

    def extract(entry):
        return rehydrate(entry.output), list(entry.received_splits or [])

    return _submit(handle, lambda: state.enqueue_alltoall(
        _auto_name("alltoall", name), np_val, splits,
        _make_callback(handle, rehydrate, extract=extract)))


def alltoall(tensor, splits: Optional[List[int]] = None,
             name: Optional[str] = None, return_received_splits: bool = False):
    out, received = synchronize(alltoall_async(tensor, splits, name=name))
    return (out, received) if return_received_splits else out


# ---------------------------------------------------------------------------
# join / barrier / handles
# ---------------------------------------------------------------------------

def join() -> int:
    """Block until every rank has joined; this rank contributes zeros to
    collectives in the meantime (reference ``hvd.join``,
    ``operations.cc:1146-1170``)."""
    state = global_state()
    event = state.enqueue_join()
    event.wait()
    return 0


def barrier(name: Optional[str] = None) -> None:
    done = threading.Event()
    status_box = [None]

    def cb(status: Status, entry):
        status_box[0] = status
        done.set()

    global_state().enqueue_barrier(cb, name=_auto_name("barrier", name))
    done.wait()
    if status_box[0] is not None and not status_box[0].ok:
        raise HorovodInternalError(status_box[0].error_message)


def initialized() -> bool:
    """True when ``hvd.init()`` has completed and the runtime is live."""
    return global_state().topo is not None


def poll(handle: int) -> bool:
    """True when the async op behind ``handle`` completed
    (reference ``mpi_ops_v2.cc:323``)."""
    return _handles.poll(handle)


def synchronize(handle: int, timeout: Optional[float] = None):
    """Wait for an async op and return its result."""
    with phase("wait"):
        return _handles.wait(handle, timeout=timeout)


def synchronize_many(handles, timeout: Optional[float] = None) -> list:
    """Wait for a batch of async ops; returns results in handle order.

    One wait per fused bucket instead of one per tensor — the batch flavor
    the DistributedOptimizer/WFBP step paths use."""
    with phase("wait"):
        return _handles.wait_many(handles, timeout=timeout)
