"""TensorQueue — the hand-off point between framework threads and the
background coordination thread.

Role of the reference's ``horovod/common/tensor_queue.h:32-58`` /
``tensor_queue.cc``: a mutex-guarded table of in-flight tensor entries plus a
queue of pending Requests.  Framework threads add (entry, request) pairs; the
background thread pops requests each cycle and later claims entries named by
a negotiated Response.  Duplicate in-flight names are an error
(``DUPLICATE_NAME_ERROR``, ``common.h:164-167``).

Entries hold host numpy buffers on the TCP data plane, or jax device
arrays on the XLA data plane (``entry.device`` distinguishes them and the
controller negotiates agreement); the controller itself only reads
shape/dtype metadata, staying framework-agnostic.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from ..common import faults
from ..common.exceptions import DuplicateNameError
from . import timeline as timeline_mod
from .messages import Request, RequestType, Response


@dataclass
class Status:
    ok: bool = True
    error_message: str = ""
    # True when the op dispatched async device work: outputs are unready
    # arrays and callbacks fire from the finalizer thread once the device
    # signals completion (reference IN_PROGRESS + finalizer-thread design,
    # ``gpu_operations.h:98-127``).
    pending: bool = False
    # True when outputs are immutable device futures (jax arrays): callbacks
    # fire IMMEDIATELY with the unready arrays — downstream jax work chains
    # on array readiness with no host wait — while a finalizer watchdog
    # still block_until_ready()s for failure detection, surfacing errors on
    # the next enqueue like the reference's NCCL async-error watchdog
    # (``nccl_operations.cc:96-109``).
    eager_complete: bool = False

    @staticmethod
    def OK() -> "Status":
        return Status(True, "")

    @staticmethod
    def in_progress() -> "Status":
        return Status(True, "", pending=True)

    @staticmethod
    def dispatched() -> "Status":
        return Status(True, "", pending=True, eager_complete=True)

    @staticmethod
    def error(msg: str) -> "Status":
        return Status(False, msg)


@dataclass
class TensorTableEntry:
    """Reference ``TensorTableEntry`` (``common.h:238-261``)."""

    tensor_name: str
    tensor: Optional[np.ndarray] = None      # input buffer (None for joined)
    output: Optional[np.ndarray] = None      # filled by the op
    root_rank: int = -1
    device: int = -1
    request_type: RequestType = RequestType.ALLREDUCE
    prescale_factor: float = 1.0
    postscale_factor: float = 1.0
    splits: Optional[List[int]] = None       # alltoall send splits
    received_splits: Optional[List[int]] = None
    # Called exactly once with (status, entry); entry.output holds the result.
    callback: Callable = field(default=lambda status, entry: None)
    # context fields used by the data plane to hand results back
    context: dict = field(default_factory=dict)
    # ordinal of the optimizer update that submitted this tensor, if one
    # did: the ``step`` of its spans on the runtime's threads
    step: Optional[int] = None
    # ``time.monotonic()`` of the ``pop_messages`` that handed this rank's
    # request to a negotiation round: where ``queue_wait`` ends and
    # ``negotiate_wait`` begins
    announced_at: Optional[float] = None


class TensorQueue:
    def __init__(self):
        self._lock = threading.Lock()
        self._table: Dict[str, TensorTableEntry] = {}
        self._pending: List[Request] = []
        self._closed = False
        # Optional wake signal: the background loop parks on this event
        # between idle cycles instead of a fixed sleep, so an enqueue cuts
        # enqueue→negotiate latency from ~cycle_time/2 to ~0 (the adaptive
        # cycle timing half of the steady-state fast path).
        self._wake: Optional[threading.Event] = None

    def set_wake_event(self, event: threading.Event) -> None:
        self._wake = event

    def add(self, entry: TensorTableEntry, request: Request) -> None:
        from ..common.exceptions import HorovodInternalError

        # The submission-side fault site: delaying here makes THIS rank a
        # genuine compute straggler (it announces readiness cycles after
        # its peers, which keep negotiating), unlike delays inside the
        # lockstep negotiation/dispatch paths that stall every rank
        # equally.  Fires before the lock — a hang/delay must not block
        # other framework threads (HVD001).
        if faults.ACTIVE:
            faults.inject("enqueue.collective")
        timeline_mod.lifecycle_begin(entry.tensor_name, "LC_SUBMITTED")
        entry.step = request._step = timeline_mod.current_ids().get("step")
        request._enqueued_at = time.monotonic()
        with self._lock:
            if self._closed:
                # The background loop has exited and drained the table; an
                # add after that point would strand its waiter forever.
                raise HorovodInternalError(
                    "Horovod background loop is not running (shut down or "
                    "failed); reinitialize before submitting collectives")
            if entry.tensor_name in self._table:
                raise DuplicateNameError(
                    f"tensor {entry.tensor_name!r} already in flight; collective "
                    f"names must be unique until the previous op completes")
            self._table[entry.tensor_name] = entry
            self._pending.append(request)
        if self._wake is not None:
            self._wake.set()

    def close(self) -> None:
        """Reject all future adds; called before the final drain."""
        with self._lock:
            self._closed = True

    def pop_messages(self) -> List[Request]:
        """Drain pending requests (one cycle's worth) —
        ``PopMessagesFromQueue`` (``tensor_queue.h:44``)."""
        # queue_wait: from ``add`` to this hand-over, per tensor (a JOIN
        # request or a re-queued one carries no stamp).  The same reading
        # opens negotiate_wait on the tensor's table entry, so a re-queued
        # request keeps its first.
        waits = []
        with self._lock:
            out, self._pending = self._pending, []
            now = time.monotonic()
            for r in out:
                enqueued_at = r.__dict__.pop("_enqueued_at", None)
                if enqueued_at is not None:
                    waits.append(now - enqueued_at)
                    entry = self._table.get(r.tensor_name)
                    if entry is not None:
                        entry.announced_at = now
        if waits:
            timeline_mod.phase_stats.add("queue_wait", sum(waits),
                                         n=len(waits))
        return out

    def push_messages(self, requests: List[Request]) -> None:
        """Re-queue requests (cache-invalidation / retry path)."""
        with self._lock:
            self._pending = requests + self._pending
        if self._wake is not None:
            self._wake.set()

    def get_entries_for_response(self, response: Response) -> List[TensorTableEntry]:
        """Claim (remove) the entries a Response names.

        For JOIN-substituted tensors absent from the table, the caller builds
        zero entries from the response metadata instead (reference
        ``GetTensorEntriesFromResponse`` zero-substitution,
        ``tensor_queue.h:39-41``)."""
        with self._lock:
            entries = []
            for name in response.tensor_names:
                entry = self._table.pop(name, None)
                if entry is not None:
                    entries.append(entry)
            return entries

    def peek(self, name: str) -> Optional[TensorTableEntry]:
        with self._lock:
            return self._table.get(name)

    def remove(self, name: str) -> Optional[TensorTableEntry]:
        with self._lock:
            return self._table.pop(name, None)

    def size(self) -> int:
        with self._lock:
            return len(self._table)

    def names(self) -> List[str]:
        with self._lock:
            return list(self._table)
