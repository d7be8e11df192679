"""Steady-state fast-path tests.

Three properties of the np>1 eager plane's hot loop:

1. **Zero-payload cycles** — once a tensor's negotiation is cached, later
   cycles exchange bitvector mask frames only: no ``Request`` is serialized
   by any rank and no ``ResponseList`` is broadcast (the controller's
   ``serialized_request_count`` / ``fast_cycle_count`` hooks pin this).
2. **Pipelined negotiate/dispatch** — with microbatch overlap, a window's
   collectives negotiate + dispatch UNDER the next microbatch's compute, so
   overlap mode's flush (and whole window) is not slower than
   accumulate-then-reduce despite communicating every backward.
3. **Topology agreement** — rank 0's controller fan-out choice is published
   through the rendezvous store; a worker whose env derived a different
   choice fails loudly at bring-up instead of deadlocking the first round.
"""

import threading
import types

import numpy as np
import pytest

from horovod_tpu.common.exceptions import HorovodInternalError
from horovod_tpu.common.topology import ProcessTopology
from horovod_tpu.core.controller import Controller
from horovod_tpu.core.messages import (
    DataType,
    Request,
    RequestType,
    ResponseType,
)
from horovod_tpu.transport import MemoryStore, TcpMesh

from .helpers import run_distributed


def _run_ranks(size, fn, timeout=60):
    from .helpers import _timeout_scale

    errs, results = [], [None] * size

    def wrap(r):
        try:
            results[r] = fn(r)
        except BaseException as e:  # noqa: BLE001
            errs.append((r, e))

    threads = [threading.Thread(target=wrap, args=(r,), daemon=True)
               for r in range(size)]
    for t in threads:
        t.start()
    budget = timeout * _timeout_scale()
    for t in threads:
        t.join(budget)
        assert not t.is_alive(), "rank thread hung"
    if errs:
        raise errs[0][1]
    return results


def _req(rank, name="t", shape=(4,)):
    return Request(request_rank=rank, request_type=RequestType.ALLREDUCE,
                   tensor_name=name, tensor_type=DataType.FLOAT32,
                   tensor_shape=list(shape))


def test_fully_cached_cycle_serializes_zero_requests():
    """Cycle 1 negotiates and caches; cycle 2 is all mask frames (zero
    Request serializations anywhere, coordinator answers with the agreed
    bitvector only); an idle cycle 3 is also a fast cycle."""
    store = MemoryStore()
    size = 2

    def body(rank):
        mesh = TcpMesh(rank, size, store, bind_addr="127.0.0.1",
                       advertise_addr="127.0.0.1")
        try:
            ctrl = Controller(ProcessTopology(rank=rank, size=size,
                                              local_rank=rank,
                                              local_size=size), mesh)
            # cycle 1: full negotiation, assigns a cache bit
            rl1 = ctrl.compute_response_list([_req(rank)], False)
            assert len(rl1.responses) == 1
            assert rl1.responses[0].response_type == ResponseType.ALLREDUCE
            assert ctrl.fast_cycle_count == 0
            base = ctrl.serialized_request_count

            # cycle 2: fully cached — the fast cycle
            rl2 = ctrl.compute_response_list([_req(rank)], False)
            assert len(rl2.responses) == 1
            assert rl2.responses[0].tensor_names == ["t"]
            assert rl2.responses[0].tensor_sizes == [4]
            assert ctrl.serialized_request_count == base, \
                "a Request was serialized during a fully-cached cycle"
            assert ctrl.fast_cycle_count == 1
            if rank != 0:
                assert ctrl.mask_only_sent_count >= 1

            # cycle 3: idle — still zero-payload, counted separately so
            # fast_cycle_count measures completed-work cycles only
            rl3 = ctrl.compute_response_list([], False)
            assert rl3.responses == []
            assert ctrl.serialized_request_count == base
            assert ctrl.fast_cycle_count == 1
            assert ctrl.idle_fast_cycle_count == 1
            return True
        finally:
            mesh.close()

    assert all(_run_ranks(size, body))


def test_cache_miss_after_fast_cycles_still_negotiates():
    """A new tensor (cache miss) after fast cycles goes through the full
    path — and both ranks still agree on the response order when a cached
    and an uncached tensor complete in the same cycle."""
    store = MemoryStore()
    size = 2

    def body(rank):
        mesh = TcpMesh(rank, size, store, bind_addr="127.0.0.1",
                       advertise_addr="127.0.0.1")
        try:
            ctrl = Controller(ProcessTopology(rank=rank, size=size,
                                              local_rank=rank,
                                              local_size=size), mesh)
            ctrl.compute_response_list([_req(rank, "a")], False)
            ctrl.compute_response_list([_req(rank, "a")], False)  # fast
            # mixed cycle: cached "a" + brand-new "b"
            rl = ctrl.compute_response_list(
                [_req(rank, "a"), _req(rank, "b", shape=(8,))], False)
            names = sorted(n for r in rl.responses for n in r.tensor_names)
            assert names == ["a", "b"], names
            # and the next all-cached cycle is fast again
            base = ctrl.serialized_request_count
            ctrl.compute_response_list(
                [_req(rank, "a"), _req(rank, "b", shape=(8,))], False)
            assert ctrl.serialized_request_count == base
            return True
        finally:
            mesh.close()

    assert all(_run_ranks(size, body))


def test_overlap_window_not_slower_than_accumulate_np4():
    """np=4: with real compute between microbatches (stood in by sleeps,
    which release the CPU exactly like a device-bound backward), overlap
    mode's window must not be slower than accumulate mode — its
    collectives negotiate and dispatch UNDER the sleeps, while accumulate
    pays the whole negotiate+collective after them.  This is the pipelined
    schedule the reference's WFBP exists to win (torch/optimizer.py:
    103-149) and the regression the eager_np8 baseline showed (overlap
    36.6% SLOWER)."""
    out = run_distributed(4, """
import time
import statistics
import jax
import jax.numpy as jnp
import optax
from horovod_tpu.frameworks.jax.optimizer import DistributedOptimizer
from horovod_tpu.core.state import global_state

SLEEP = 0.3
params = {"w": jnp.ones((64, 64), jnp.float32)}
grads = {"w": jnp.full((64, 64), float(rank + 1), jnp.float32)}

def run_windows(overlap, n_windows=5):
    tx = optax.sgd(0.1)
    dopt = DistributedOptimizer(tx, backward_passes_per_step=2,
                                overlap=overlap)
    st = dopt.init(params)
    walls, flushes = [], []
    for w in range(n_windows):
        t0 = time.perf_counter()
        for mb in range(2):
            time.sleep(SLEEP)            # stands in for backward compute
            t1 = time.perf_counter()
            upd, st = dopt.update(grads, st, params)
            dt = time.perf_counter() - t1
        jax.block_until_ready(upd["w"])
        walls.append(time.perf_counter() - t0)
        flushes.append(dt)               # the window-flush call
    return walls[1:], flushes[1:]        # window 0 warms compiles + cache

acc_walls, acc_flush = run_windows(False)
ov_walls, ov_flush = run_windows(True)
# min, not median: host-load spikes only ADD time, so the fastest window
# of each mode is the clean measurement; a genuine pipelining regression
# (the r5 baseline's 36.6% loss) shifts every window, min included.
acc_w, ov_w = min(acc_walls), min(ov_walls)
print("WINDOWS", rank, round(acc_w, 3), round(ov_w, 3),
      round(statistics.median(acc_flush), 4),
      round(statistics.median(ov_flush), 4), flush=True)
# overlap >= accumulate: the overlapped window must not be slower
# (10% + 80ms slack absorbs residual scheduler noise on a loaded core).
assert ov_w <= acc_w * 1.10 + 0.08, (ov_w, acc_w)
ctrl = global_state().controller
assert ctrl.fast_cycle_count > 0, "steady-state cycles never went fast"
print("OVERLAP_OK", rank, flush=True)
""", timeout=300)
    for r, o in enumerate(out):
        assert f"OVERLAP_OK {r}" in o


def test_controller_topology_mismatch_is_loud():
    """A worker whose env derived a different fan-out than rank 0
    published must raise a HorovodInternalError naming the knob — not
    deadlock the first negotiation round (ADVICE r5)."""
    from horovod_tpu.core.state import HorovodGlobalState

    store = MemoryStore()

    def fake_state(rank, fanout):
        st = HorovodGlobalState()
        st.topo = ProcessTopology(rank=rank, size=2, local_rank=rank,
                                  local_size=2)
        st.controller = types.SimpleNamespace(fanout_topology=fanout,
                                              configure_fanin=lambda plan: None)
        return st

    fake_state(0, "star")._sync_controller_topology(store, 0, timeout=5)
    # agreeing worker: fine
    fake_state(1, "star")._sync_controller_topology(store, 0, timeout=5)
    # disagreeing worker: loud
    with pytest.raises(HorovodInternalError,
                       match="HOROVOD_CONTROLLER_TOPOLOGY"):
        fake_state(1, "tree")._sync_controller_topology(store, 0, timeout=5)


def test_wake_event_cuts_idle_latency():
    """An enqueue while the background loop is parked must start the next
    cycle immediately: with a deliberately huge cycle time, and a loop that
    has backed off to parks of hundreds of ms (ISSUE 54: the cycle time is
    the longest an idle loop parks, reached by doubling from 1 ms), a round
    trip still completes far inside one park."""
    out = run_distributed(2, """
import time
from horovod_tpu.core.state import global_state
x = np.ones(16, np.float32)
# warm (negotiate + cache)
hvd.allreduce(x, op=hvd.Sum, name="wake.t")
# 1 + 2 + ... + 256 ms of idle rounds, and the next park is the cap's.
time.sleep(1.2)
assert global_state()._idle_park_ms >= 256, global_state()._idle_park_ms
t0 = time.perf_counter()
for i in range(3):
    hvd.allreduce(x, op=hvd.Sum, name="wake.t")
dt = (time.perf_counter() - t0) / 3
# the parks have grown to 500 ms: without the wake event the first op
# waits out the remainder of one; with it the three ops must finish well
# inside ONE such park each (generous 450 ms bound for loaded boxes).
assert dt < 0.45, f"enqueue->complete took {dt:.3f}s with 500ms cycles"
print("WAKE_OK", rank, flush=True)
""", extra_env={"HOROVOD_CYCLE_TIME": "500"}, timeout=240)
    for r, o in enumerate(out):
        assert f"WAKE_OK {r}" in o
