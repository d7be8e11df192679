"""Shared-memory intra-host transport — the zero-syscall sibling of tcp.py.

Colocated ranks talking over TCP loopback pay two syscalls and two kernel
copies per frame for bytes that never leave the machine (ROADMAP item 3:
the measured bottleneck of every intra-host sweep).  This module moves
those frames through per-peer-pair POSIX shared-memory segments instead:
each unordered rank pair {i, j} on one host shares ONE segment created by
the lower rank (name published through the rendezvous KV, exactly like
the TCP mesh publishes its listen addresses), holding two single-
producer/single-consumer byte rings — one per direction.  A frame send is
one ``memcpy`` into the ring; a ``recv_into`` is one ``memcpy`` out into
the caller's staging view.  No sockets, no syscalls, no kernel copies on
the steady-state path.

Frame discipline is IDENTICAL to ``transport/tcp.py`` — the same
``<Q len|flags>[<I crc32>]`` header, the same control/deferred/digest-
check/wire-dtype flag bits (imported from ``transport/frame_bits.py``,
the registry that owns the wire constants; HVD008), the same
poisoned-stream and coordinated-abort
semantics, the same progress deadline (reusing
``HOROVOD_TCP_PROGRESS_DEADLINE_SECS`` so the failure plane has ONE knob,
not one per transport).  The only intentional difference:
``HOROVOD_SHM_CRC`` defaults OFF — these bytes never cross a wire, and a
bit flip in host RAM is ECC's jurisdiction, so the default buys the
syscall win twice (no CRC pass either).  Turning it on restores the full
integrity plane, shadow digests included, for chaos tests and stomper
hunts.

Ring protocol: per direction a monotonic u64 ``head`` (total bytes ever
written, writer-owned) and u64 ``tail`` (total bytes ever read,
reader-owned) live in separate cache lines of the segment header;
``head - tail`` is the unread span, ``capacity - (head - tail)`` the free
span, and positions wrap modulo capacity.  Frames LARGER than the ring
stream through in chunks, so capacity bounds memory, never frame size.
Each side updates only its own counter and stores it strictly AFTER the
byte copy it covers — under CPython's bytecode ordering plus x86-64 TSO
an aligned 8-byte store is atomic and never reordered before the data
writes it publishes, which is the entirety of the memory model this
relies on.

Failure plane: a blocked ring wait wakes every ~0.5 ms (an Event nap, not
a sleep-under-lock) to observe the mesh-wide abort flag, enforce the
progress deadline, and — the shm equivalent of a TCP RST — probe the
peer's PID (stamped into the segment header at create/attach time) so a
SIGKILLed neighbour converts to ``PeerGoneError`` within one poll
quantum instead of a deadline timeout.  Orphan hygiene is layered:
attachers unregister from ``resource_tracker`` so exactly one process
(the creator) owns the unlink, the creator unlinks on ``close()``, the
creator's resource tracker unlinks after a hard kill, and the runner
sweeps ``/dev/shm`` by dead-worker PID (segment names embed the creator
PID) as the deterministic backstop.
"""

from __future__ import annotations

import ctypes
import errno
import glob
import os
import queue
import threading
import time
import uuid
import zlib
from multiprocessing import resource_tracker, shared_memory
from typing import Dict, Iterable, List, Optional, Tuple

from . import digest as digest_mod
from ..common import faults
from ..common.exceptions import (
    CoordinatedAbortError,
    FrameCorruptError,
    HorovodInternalError,
    PeerGoneError,
)
from ..common.logging_util import get_logger
from ..core import flight_recorder, metrics
from .frame_bits import (
    _CRC,
    _CTRL_FLAG,
    _DEFER_FLAG,
    _DIGEST_FLAG,
    _FLAGS_MASK,
    _FrameHeader,
    _LEN,
    _MAX_FRAME_BYTES,
    _WIRE_DTYPE_MASK,
    _WIRE_DTYPE_SHIFT,
)
from .store import Store
from .tcp import (
    _ABORT_POLL_SECS,
    _ProgressStall,
    AbortState,
    PendingRecv,
    _as_byte_view,
    _as_writable_byte_view,
)

log = get_logger("horovod_tpu.transport.shm")

#: Segment names are ``hvdshm-<creator pid>-e<epoch>-<lo>x<hi>-<nonce>`` so
#: leak scans and the runner's dead-PID sweep can address them by glob
#: without attaching.
SEG_PREFIX = "hvdshm-"

_SHM_MAGIC = 0x48565348  # "HVSH"
# v2: the per-direction doorbell split into two single-writer bells
# (data bell / space bell) after hvd-mck exhibited an ABA lost-update on
# the shared-bell layout — see the doorbell comment below.  Version skew
# fails loudly at attach, like every other layout change.
_SHM_VERSION = 2

# Segment header layout (native byte order: both ends are on this host).
# Direction counters sit 64 bytes
# apart so the two writers never share a cache line.
_OFF_MAGIC = 0          # u32
_OFF_VERSION = 4        # u32
_OFF_CAP = 8            # u64 ring capacity per direction
_OFF_CREATOR_PID = 16   # u64 lower rank's PID (stamped before publish)
_OFF_ATTACHER_PID = 24  # u64 higher rank's PID (0 until attach)
_OFF_L2H_HEAD = 64      # u64 lower→higher bytes written (lower owns)
_OFF_L2H_TAIL = 128     # u64 lower→higher bytes read (higher owns)
_OFF_H2L_HEAD = 192     # u64 higher→lower bytes written (higher owns)
_OFF_H2L_TAIL = 256     # u64 higher→lower bytes read (lower owns)
# Four doorbells, ONE WRITER EACH (see the doorbell comment below for
# why the shared-bell layout was an ABA bug): a direction's data bell is
# bumped only by its sender (waking a receiver out of data), its space
# bell only by its receiver (waking a sender out of ring space).
_OFF_L2H_DATA_BELL = 288   # u32: bumped by lower (L2H sender) only
_OFF_L2H_SPACE_BELL = 296  # u32: bumped by higher (L2H receiver) only
_OFF_H2L_DATA_BELL = 304   # u32: bumped by higher (H2L sender) only
_OFF_H2L_SPACE_BELL = 312  # u32: bumped by lower (H2L receiver) only
_RINGS_OFF = 320        # L2H ring, then H2L ring at +capacity

# Blocked ring waits sleep on a FUTEX DOORBELL: each direction carries
# two u32 bells, each with exactly ONE writer — the sender bumps the
# data bell (with a FUTEX_WAKE) after publishing head advances, the
# receiver bumps the space bell after publishing tail advances — and a
# rank out of data/space does a kernel FUTEX_WAIT on (peer's bell ==
# value-seen-before-checking).  That gives shm the property the TCP path
# gets from blocking sockets — the waiter wakes the instant bytes (or
# space) land, with zero polling — which is what lets shm beat loopback
# TCP on wakeup latency instead of losing every blocked wait to a poll
# quantum.  The wait is still bounded (_BELL_WAIT_SECS) so the abort
# flag and the peer-PID probe keep their poll cadence, and the
# bump-after-store protocol makes lost wakeups impossible: a store is
# visible before its bump (x86-64 TSO), so a waiter either sees the
# progress or sees a moved bell and returns immediately.  That claim is
# no longer prose-only: `hvd-mck` explores every bounded interleaving of
# sender_steps/receiver_steps below and proves it under a TSO
# store-buffer model — and exhibits the missed wakeup under a weaker
# model, so the fence the protocol leans on is a machine-checked fact
# (tools/mck; docs/static_analysis.md).
#
# Why one writer per bell: v1 had a single bell per direction that BOTH
# ends incremented with a plain load+store (no atomic RMW exists for a
# Python shm buffer).  hvd-mck found the resulting ABA the first time it
# ran: one end's increment, delayed in its store buffer (or just
# preempted between load and store), lands late, clobbers the other
# end's bumps, and can restore the exact value a waiter is about to
# FUTEX_WAIT on — the waiter sleeps a full bounded wait with its data
# already published.  Splitting the bell by writer makes the lost update
# structurally impossible: an increment is a data race only if the word
# has a second writer.
# Where the futex syscall is unavailable (non-Linux,
# unknown arch), waits fall back to a two-phase nap ramp: ~one scheduler
# tick for the first _RING_NAP_RAMP polls, then the long nap so a rank
# stalled across a whole negotiation naps instead of spinning.
_BELL_WAIT_SECS = 0.05
_RING_NAP_SECS = 0.0005
_RING_NAP_FAST_SECS = 0.00002
_RING_NAP_RAMP = 64

_FUTEX_WAIT = 0
_FUTEX_WAKE = 1
_SYS_FUTEX = {"x86_64": 202, "aarch64": 98}.get(os.uname().machine)


class _Timespec(ctypes.Structure):
    _fields_ = [("tv_sec", ctypes.c_long), ("tv_nsec", ctypes.c_long)]


def _futex_libc():
    if _SYS_FUTEX is None:
        return None
    try:
        libc = ctypes.CDLL(None, use_errno=True)
        libc.syscall.restype = ctypes.c_long
        # Self-test: WAIT with a mismatched expected value must return
        # EAGAIN immediately — proves the syscall number and calling
        # convention before the data plane trusts them.
        word = ctypes.c_uint32(0)
        res = libc.syscall(_SYS_FUTEX, ctypes.byref(word), _FUTEX_WAIT,
                           1, None, None, 0)
        if res == -1 and ctypes.get_errno() == errno.EAGAIN:
            return libc
    except Exception:  # pragma: no cover - exotic libc
        pass
    return None


_LIBC = _futex_libc()


def _futex_wait(addr: int, expected: int, timeout_s: float) -> None:
    ts = _Timespec(int(timeout_s), int(timeout_s % 1.0 * 1e9))
    _LIBC.syscall(_SYS_FUTEX, ctypes.c_void_p(addr), _FUTEX_WAIT,
                  expected, ctypes.byref(ts), None, 0)


def _futex_wake(addr: int) -> None:
    _LIBC.syscall(_SYS_FUTEX, ctypes.c_void_p(addr), _FUTEX_WAKE,
                  0x7FFFFFFF, None, None, 0)


_MIN_RING_BYTES = 4096


# Control-word accessors — the ONLY code allowed to move raw structs
# against the header offsets (hvd-lint HVD009).  Every head/tail load and
# store, every bell read and write, and the magic/version words go
# through these four functions, so the set of shared-memory accesses the
# model checker must consider is closed by construction.
#
# Each access must be ONE aligned machine-word move: the ring protocol and
# its proof take a load or store of head, tail or a bell to be atomic.
# ``struct.pack_into`` is not — it zero-fills its target and then, for an
# explicit byte order, writes a byte at a time, so a concurrent reader in
# the peer process sees 0 or a half-written counter (observed: a torn
# head gave a negative run and killed the background loop of an np=2
# ResNet-50 job).  Item access on a memoryview cast to a native word is a
# single word-sized memcpy; the peer is on this host, so native byte
# order is shared.  All header offsets are word-aligned.
def _load_u64(buf, off: int) -> int:
    return buf[off:off + 8].cast("Q")[0]


def _store_u64(buf, off: int, value: int) -> None:
    buf[off:off + 8].cast("Q")[0] = value


def _load_u32(buf, off: int) -> int:
    return buf[off:off + 4].cast("I")[0]


def _store_u32(buf, off: int, value: int) -> None:
    buf[off:off + 4].cast("I")[0] = value


# -- ring protocol kernel (model-checked; see tools/mck) ----------------------
#
# The SPSC ring-advance logic is written ONCE, as pure generators over an
# abstract memory: every shared-memory access is one yielded op tuple, in
# exact program order, and the caller (the "driver") executes it against
# real segment memory — or, under ``hvd-mck``, against a model memory
# with an explicit store-buffer semantics.  The model-checked code IS the
# production code; there is no second copy to drift (the pre-extraction
# tree had exactly that bug: ``_abort_write`` re-derived the send run
# with a diverging per-RUN bell discipline).
#
# Op vocabulary (first element is the kind; the driver answers loads and
# polls through ``generator.send``):
#
#   (OP_POLL,)                   -> SIG_OK | SIG_ABORT   abort-flag check
#   (OP_LOAD, loc, tag)          -> int                  read a control word
#   (OP_STORE, loc, value[, tag])                        write a control word
#   (OP_COPY, idx, off, pos, run)                        move run bytes
#                                   segment idx [off:off+run] <-> ring
#                                   [pos:pos+run] (direction is the
#                                   driver's; this op publishes nothing)
#   (OP_WAIT, expected)                                  bounded sleep until
#                                   the peer's bell moves off ``expected``
#   (OP_WAKE, tag)                                       FUTEX_WAKE own bell
#
# ``loc`` is LOC_HEAD / LOC_TAIL / LOC_BELL_OWN / LOC_BELL_PEER, always
# the DIRECTION'S words (the sender's head is the receiver's head).  The
# two bell locs are role-relative: LOC_BELL_OWN is the single-writer
# bell this role bumps (the sender's data bell, the receiver's space
# bell), LOC_BELL_PEER the one it prechecks and waits on.  ``tag``
# labels bell traffic for the checker ("precheck", "prewait", "final",
# "abort"); production drivers ignore it.  The generator returns DONE or
# ABORTED.

OP_POLL = "poll"
OP_LOAD = "load"
OP_STORE = "store"
OP_COPY = "copy"
OP_WAIT = "wait"
OP_WAKE = "wake"

LOC_HEAD = "head"
LOC_TAIL = "tail"
LOC_BELL_OWN = "own_bell"
LOC_BELL_PEER = "peer_bell"

SIG_OK = "ok"
SIG_ABORT = "abort"

DONE = "done"
ABORTED = "aborted"


def bell_bump_steps(tag: str):
    """Publish pending head/tail advances on this role's doorbell: move
    the bell and wake its futex waiters.  The increment is a plain
    load+store — safe ONLY because each bell has one writer (this role),
    so the RMW can never race another increment.  hvd-mck caught the v1
    layout, where both ends bumped one shared bell, losing updates and
    ABA-ing a waiter to sleep; the single-writer split is what makes
    this non-atomic bump correct, and the checker now proves it."""
    bell = yield (OP_LOAD, LOC_BELL_OWN, tag)
    yield (OP_STORE, LOC_BELL_OWN, (bell + 1) & 0xFFFFFFFF, tag)
    yield (OP_WAKE, tag)


def sender_steps(cap: int, lens: List[int]):
    """Write ``sum(lens)`` bytes (the segments' concatenation) into the
    ring, chunking at ring-wrap and ring-full boundaries.

    Data bytes land (OP_COPY) strictly BEFORE the head store that
    publishes them — under CPython's bytecode ordering plus x86-64 TSO an
    aligned 8-byte store is atomic and never reordered before the data
    writes it covers, which is the entirety of the memory model this
    relies on, and ``hvd-mck`` checks exactly that claim: the ``tso``
    model proves the protocol, the ``weak`` model (store-store
    reordering allowed) finds the missed wakeup.

    The bell is bumped once per CALL, not per run: each wake is a
    syscall plus a scheduler event, and on a timeshared core every extra
    wake is another chance to lose the CPU mid-frame.  The exception is
    going to sleep with unpublished advances — the peer may be asleep
    waiting for exactly those bytes, so the bump is published first
    (publish-before-sleep)."""
    pending = False  # head advances not yet published on the bell
    for idx, n in enumerate(lens):
        off = 0
        while off < n:
            if (yield (OP_POLL,)) == SIG_ABORT:
                if pending:
                    yield from bell_bump_steps("abort")
                return ABORTED
            # Space-bell load FIRST, ring state second: if the peer
            # frees space and bumps between these two loads, the futex
            # sees a stale expected value and returns immediately
            # (EAGAIN).
            bell = yield (OP_LOAD, LOC_BELL_PEER, "precheck")
            head = yield (OP_LOAD, LOC_HEAD, None)
            free = cap - (head - (yield (OP_LOAD, LOC_TAIL, None)))
            if free == 0:
                # Publish deferred advances before sleeping — the
                # peer may be asleep waiting for exactly those bytes.
                if pending:
                    yield from bell_bump_steps("prewait")
                    pending = False
                    continue
                yield (OP_WAIT, bell)
                continue
            pos = head % cap
            run = min(n - off, free, cap - pos)
            yield (OP_COPY, idx, off, pos, run)
            yield (OP_STORE, LOC_HEAD, head + run)
            pending = True
            off += run
    if pending:
        yield from bell_bump_steps("final")
    return DONE


def receiver_steps(cap: int, lens: List[int]):
    """Read ``sum(lens)`` bytes out of the ring into the segments'
    concatenation — the mirror of :func:`sender_steps` with tail in the
    writer role: the copy out of the ring happens strictly BEFORE the
    tail store that frees the span (the sender may overwrite those bytes
    the moment the tail moves), and the bell discipline is identical
    (one bump per call, publish-before-sleep)."""
    pending = False  # tail advances not yet published on the bell
    for idx, n in enumerate(lens):
        got = 0
        while got < n:
            if (yield (OP_POLL,)) == SIG_ABORT:
                if pending:
                    yield from bell_bump_steps("abort")
                return ABORTED
            # Same load order as the send side: the peer's (data) bell
            # first, ring state second.
            bell = yield (OP_LOAD, LOC_BELL_PEER, "precheck")
            tail = yield (OP_LOAD, LOC_TAIL, None)
            avail = (yield (OP_LOAD, LOC_HEAD, None)) - tail
            if avail == 0:
                # Publish deferred drains before sleeping — the peer may
                # be asleep waiting for exactly that ring space.
                if pending:
                    yield from bell_bump_steps("prewait")
                    pending = False
                    continue
                yield (OP_WAIT, bell)
                continue
            pos = tail % cap
            run = min(n - got, avail, cap - pos)
            yield (OP_COPY, idx, got, pos, run)
            yield (OP_STORE, LOC_TAIL, tail + run)
            pending = True
            got += run
    if pending:
        yield from bell_bump_steps("final")
    return DONE


def segment_size(ring_bytes: int) -> int:
    """Total segment size for a per-direction ring capacity."""
    return _RINGS_OFF + 2 * ring_bytes


def sweep_dead_segments(pids: Iterable[int]) -> List[str]:
    """Unlink ``/dev/shm`` segments created by the given (dead) PIDs.

    The runner's deterministic backstop after a worker exits: the
    creator's own resource tracker also unlinks after a hard kill, but
    asynchronously — this sweep makes "kill mid-step leaves no residue"
    a property the chaos suite can assert immediately.  Only ever called
    with PIDs whose processes have exited."""
    removed: List[str] = []
    root = "/dev/shm"
    if not os.path.isdir(root):
        return removed
    for pid in pids:
        for path in glob.glob(os.path.join(root, f"{SEG_PREFIX}{pid}-*")):
            try:
                os.unlink(path)
            except OSError:
                continue
            removed.append(os.path.basename(path))
            log.warning("swept orphaned shm segment %s (creator pid %d)",
                        os.path.basename(path), pid)
    return removed


class _ShmPeer:
    """One attached pair segment, viewed from this rank's side."""

    __slots__ = ("shm", "created", "cap", "out_ring", "in_ring",
                 "out_head_off", "out_tail_off", "in_head_off",
                 "in_tail_off", "out_data_bell_off", "out_space_bell_off",
                 "in_data_bell_off", "in_space_bell_off",
                 "base_addr", "addr_anchor", "peer_pid_off",
                 "send_lock", "recv_lock", "dead", "ever_received",
                 "frames_in")

    def __init__(self, shm: shared_memory.SharedMemory, created: bool,
                 cap: int, i_am_lower: bool):
        self.shm = shm
        self.created = created
        self.cap = cap
        buf = shm.buf
        if i_am_lower:
            self.out_head_off = _OFF_L2H_HEAD
            self.out_tail_off = _OFF_L2H_TAIL
            self.in_head_off = _OFF_H2L_HEAD
            self.in_tail_off = _OFF_H2L_TAIL
            # Sending L2H: I bump its data bell, wait on its space bell;
            # receiving H2L: I wait on its data bell, bump its space bell.
            self.out_data_bell_off = _OFF_L2H_DATA_BELL
            self.out_space_bell_off = _OFF_L2H_SPACE_BELL
            self.in_data_bell_off = _OFF_H2L_DATA_BELL
            self.in_space_bell_off = _OFF_H2L_SPACE_BELL
            self.out_ring = buf[_RINGS_OFF:_RINGS_OFF + cap]
            self.in_ring = buf[_RINGS_OFF + cap:_RINGS_OFF + 2 * cap]
            self.peer_pid_off = _OFF_ATTACHER_PID
        else:
            self.out_head_off = _OFF_H2L_HEAD
            self.out_tail_off = _OFF_H2L_TAIL
            self.in_head_off = _OFF_L2H_HEAD
            self.in_tail_off = _OFF_L2H_TAIL
            self.out_data_bell_off = _OFF_H2L_DATA_BELL
            self.out_space_bell_off = _OFF_H2L_SPACE_BELL
            self.in_data_bell_off = _OFF_L2H_DATA_BELL
            self.in_space_bell_off = _OFF_L2H_SPACE_BELL
            self.out_ring = buf[_RINGS_OFF + cap:_RINGS_OFF + 2 * cap]
            self.in_ring = buf[_RINGS_OFF:_RINGS_OFF + cap]
            self.peer_pid_off = _OFF_CREATOR_PID
        # Futex doorbells need the segment's MAPPED address; the ctypes
        # anchor pins a buffer export that close() must drop before the
        # mmap can unmap.
        if _LIBC is not None:
            self.addr_anchor = ctypes.c_ubyte.from_buffer(buf)
            self.base_addr = ctypes.addressof(self.addr_anchor)
        else:
            self.addr_anchor = None
            self.base_addr = 0
        self.send_lock = threading.Lock()
        self.recv_lock = threading.Lock()
        # Same failure-plane state as tcp._Peer: first failure marks the
        # peer dead, the recv deadline arms on first bytes, frames_in is
        # FrameCorruptError's diagnostic context.
        self.dead: Optional[str] = None
        self.ever_received = False
        self.frames_in = 0

    def wake(self, off: int) -> None:
        """FUTEX_WAKE the direction's bell waiters (the OP_WAKE half of
        :func:`bell_bump_steps` — the bell increment itself is a plain
        OP_STORE the driver already executed)."""
        if self.base_addr:
            _futex_wake(self.base_addr + off)
            # FUTEX_WAKE has no sync-wakeup hint (the thing a loopback
            # sendmsg gets for free), so on a timeshared core the woken
            # peer would otherwise sit runnable until this rank's slice
            # ends.  Yielding right after the wake hands the core over —
            # with idle cores it is a near-no-op.
            os.sched_yield()

    def bell_wait(self, off: int, seen: int, naps: int,
                  nap_event: threading.Event) -> int:
        """Sleep until the direction's bell moves off ``seen`` (or the
        bounded timeout / fallback nap elapses).  Returns the updated
        fallback nap counter."""
        if self.base_addr:
            _futex_wait(self.base_addr + off, seen, _BELL_WAIT_SECS)
            return naps
        nap_event.wait(_RING_NAP_FAST_SECS if naps < _RING_NAP_RAMP
                       else _RING_NAP_SECS)
        return naps + 1


class ShmMesh:
    """Framed shared-memory fabric between colocated ranks.

    ``peers`` is the subset of global ranks this mesh serves (the
    LinkMesh's intra-host set); ``size`` stays the WORLD size so epoch
    and abort semantics match the TCP mesh exactly.  The surface is the
    TcpMesh surface — send/recv/recv_into/recv_into_async/sendrecv/
    sendrecv_into/step digests/send_abort/close — so the selection layer
    can route per link without the collectives knowing which fabric they
    ride."""

    def __init__(self, rank: int, size: int, store: Store,
                 peers: Iterable[int], scope: str = "shm",
                 timeout: float = 60.0,
                 epoch: Optional[int] = None,
                 progress_deadline: Optional[float] = None,
                 abort_state: Optional[AbortState] = None,
                 ring_bytes: Optional[int] = None):
        from ..common import env as env_mod

        self.rank = rank
        self.size = size
        self._peers: Dict[int, _ShmPeer] = {}
        self._closed = False
        self._sr_thread: Optional[threading.Thread] = None
        self._sr_queue: Optional[queue.SimpleQueue] = None
        self.epoch = env_mod.get_epoch() if epoch is None else epoch
        # One deadline knob for the whole failure plane (see module
        # docstring): shm reuses the TCP progress deadline.
        self.progress_deadline = env_mod.get_float(
            env_mod.HOROVOD_TCP_PROGRESS_DEADLINE,
            env_mod.DEFAULT_TCP_PROGRESS_DEADLINE_SECS) \
            if progress_deadline is None else progress_deadline
        # Default OFF — the one deliberate divergence from TCP (module
        # docstring).  With it on, the shadow-digest machinery applies
        # unchanged.
        self.wire_crc = env_mod.get_bool(env_mod.HOROVOD_SHM_CRC, False)
        self.crc_shadow = env_mod.get_bool(
            env_mod.HOROVOD_WIRE_CRC_SHADOW, True)
        self.digest_algo = digest_mod.algo_from_name(
            env_mod.get_str(env_mod.HOROVOD_WIRE_DIGEST, "fold64")
            or "fold64")
        self._abort_state = abort_state if abort_state is not None \
            else AbortState()
        self.abort_relay = None
        # Nap timer for blocked ring waits: an Event, set only on abort/
        # close so every napping thread wakes instantly — never a bare
        # sleep under a peer lock (HVD001's jurisdiction).
        self._nap = threading.Event()
        cap = env_mod.get_int(env_mod.HOROVOD_SHM_RING_BYTES,
                              env_mod.DEFAULT_SHM_RING_BYTES) \
            if ring_bytes is None else ring_bytes
        cap = max(int(cap), _MIN_RING_BYTES)

        for j in sorted(set(int(p) for p in peers)):
            if j == rank:
                continue
            lo, hi = (rank, j) if rank < j else (j, rank)
            key = f"seg.{lo}.{hi}"
            if rank == lo:
                self._peers[j] = self._create_segment(store, scope, key,
                                                      lo, hi, cap)
            else:
                self._peers[j] = self._attach_segment(store, scope, key,
                                                      timeout)

    # -- segment bring-up ---------------------------------------------------

    def _create_segment(self, store: Store, scope: str, key: str,
                        lo: int, hi: int, cap: int) -> _ShmPeer:
        name = (f"{SEG_PREFIX}{os.getpid()}-e{self.epoch}-{lo}x{hi}-"
                f"{uuid.uuid4().hex[:8]}")
        seg = shared_memory.SharedMemory(name=name, create=True,
                                         size=segment_size(cap))
        buf = seg.buf
        # Header before publish: an attacher never sees a half-built
        # segment.  /dev/shm segments are born zero-filled, so the ring
        # counters and the attacher-PID slot start correct for free.
        _store_u32(buf, _OFF_MAGIC, _SHM_MAGIC)
        _store_u32(buf, _OFF_VERSION, _SHM_VERSION)
        _store_u64(buf, _OFF_CAP, cap)
        _store_u64(buf, _OFF_CREATOR_PID, os.getpid())
        store.set(scope, key, seg.name.encode())
        return _ShmPeer(seg, created=True, cap=cap, i_am_lower=True)

    def _attach_segment(self, store: Store, scope: str, key: str,
                        timeout: float) -> _ShmPeer:
        name = store.wait(scope, [key], timeout=timeout)[key].decode()
        seg = shared_memory.SharedMemory(name=name)
        # Python 3.10's SharedMemory registers EVERY attach with the
        # resource tracker; left alone, the attacher's tracker would
        # unlink the creator's still-live segment at exit.  Exactly one
        # owner: the creator (whose registration doubles as the hard-kill
        # safety net).
        try:
            resource_tracker.unregister(seg._name, "shared_memory")
        except Exception:  # pragma: no cover - tracker internals moved
            log.warning("could not unregister shm attach from the resource "
                        "tracker; exit may unlink %s early", name)
        buf = seg.buf
        magic = _load_u32(buf, _OFF_MAGIC)
        version = _load_u32(buf, _OFF_VERSION)
        if magic != _SHM_MAGIC or version != _SHM_VERSION:
            seg.close()
            raise HorovodInternalError(
                f"shm segment {name} has magic=0x{magic:08x} "
                f"version={version} (want 0x{_SHM_MAGIC:08x} "
                f"v{_SHM_VERSION}): mixed-version mesh or a foreign "
                "segment; refusing to attach")
        cap = _load_u64(buf, _OFF_CAP)
        _store_u64(buf, _OFF_ATTACHER_PID, os.getpid())
        return _ShmPeer(seg, created=False, cap=cap, i_am_lower=False)

    # -- shared failure-plane plumbing --------------------------------------

    @property
    def _abort(self) -> Optional[Tuple[int, int, str]]:
        return self._abort_state.value

    @_abort.setter
    def _abort(self, value: Optional[Tuple[int, int, str]]) -> None:
        self._abort_state.value = value

    @property
    def deferred_digests(self) -> bool:
        """Shadow-digest path applies only with the (default-off) shm CRC
        on — same rule as TCP, different default."""
        return self.wire_crc and self.crc_shadow

    def deferred_digests_for(self, peer: int) -> bool:
        return self.deferred_digests

    def new_digest(self) -> digest_mod.StreamDigest:
        return digest_mod.StreamDigest(self.digest_algo)

    @staticmethod
    def _crc32_timed(payload) -> int:
        if not metrics.ENABLED:
            return zlib.crc32(payload) & 0xFFFFFFFF
        t0 = time.perf_counter()
        crc = zlib.crc32(payload) & 0xFFFFFFFF
        metrics.inc("crc_verify_seconds_total", time.perf_counter() - t0)
        return crc

    @staticmethod
    def _digest_timed(dig: digest_mod.StreamDigest, view) -> None:
        if not metrics.ENABLED:
            dig.update(view)
            return
        t0 = time.perf_counter()
        dig.update(view)
        metrics.inc("crc_shadow_seconds_total", time.perf_counter() - t0)

    def _check_alive(self, p: _ShmPeer, peer: int) -> None:
        if self._abort is not None:
            raise CoordinatedAbortError(*self._abort)
        if p.dead is not None:
            raise PeerGoneError(peer, p.dead)

    @staticmethod
    def _mark_dead(p: _ShmPeer, reason: str) -> None:
        if p.dead is None:
            p.dead = reason

    @staticmethod
    def _peer_pid(p: _ShmPeer) -> int:
        return _load_u64(p.shm.buf, p.peer_pid_off)

    def _require_peer_alive(self, p: _ShmPeer) -> None:
        """The shm stand-in for a TCP RST: a peer that died mid-step can
        never drain or fill its ring, so a stalled wait probes the PID it
        stamped into the header.  PID 0 means the higher rank has not
        attached yet — bring-up stagger, the startup timeout's
        jurisdiction, never judged here."""
        pid = self._peer_pid(p)
        if pid == 0 or pid == os.getpid():
            return
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            raise OSError(f"peer process {pid} died (shm segment "
                          f"{p.shm.name} orphaned mid-stream)") from None
        except PermissionError:
            return  # alive, just not ours to signal

    # -- ring I/O -----------------------------------------------------------

    def _send_bounded(self, p: _ShmPeer, bufs: List[memoryview],
                      budget: Optional[float] = None,
                      ignore_abort: bool = False) -> None:
        """Copy ``bufs`` into the outbound ring by driving the pure
        :func:`sender_steps` protocol against the live segment — ring
        math, bell discipline, and memory-access ORDER all come from the
        generator (the model-checked code path); this driver only
        executes the ops and supplies the failure plane: abort flag on
        every poll, progress deadline on zero byte progress, peer-PID
        probe while stalled.

        ``ignore_abort=True`` is the abort-broadcast variant (the frame
        being written IS the abort — the flag is already set and the
        normal path would refuse to write): polls never report the
        abort, the first stalled wait probes the peer immediately, and
        blocked waits plain-sleep (the nap Event is already set on this
        path, so only a real sleep yields)."""
        buf = p.shm.buf
        budget = self.progress_deadline if budget is None else budget
        deadline = (time.monotonic() + budget) if budget > 0 else None
        next_probe = 0.0 if ignore_abort \
            else time.monotonic() + _ABORT_POLL_SECS
        naps = 0
        steps = sender_steps(p.cap, [len(b) for b in bufs])
        resp = None
        while True:
            try:
                op = steps.send(resp)
            except StopIteration as fin:
                if fin.value == ABORTED:
                    raise CoordinatedAbortError(*self._abort) from None
                return
            kind = op[0]
            resp = None
            if kind == OP_LOAD:
                if op[1] == LOC_BELL_PEER:
                    resp = _load_u32(buf, p.out_space_bell_off)
                elif op[1] == LOC_BELL_OWN:
                    resp = _load_u32(buf, p.out_data_bell_off)
                elif op[1] == LOC_HEAD:
                    resp = _load_u64(buf, p.out_head_off)
                else:
                    resp = _load_u64(buf, p.out_tail_off)
            elif kind == OP_COPY:
                _, idx, off, pos, run = op
                p.out_ring[pos:pos + run] = bufs[idx][off:off + run]
                naps = 0
                if deadline is not None:
                    deadline = time.monotonic() + budget
                if not ignore_abort:
                    next_probe = time.monotonic() + _ABORT_POLL_SECS
            elif kind == OP_STORE:
                if op[1] == LOC_BELL_OWN:
                    _store_u32(buf, p.out_data_bell_off, op[2])
                else:
                    _store_u64(buf, p.out_head_off, op[2])
            elif kind == OP_WAKE:
                p.wake(p.out_data_bell_off)
            elif kind == OP_POLL:
                resp = SIG_ABORT if not ignore_abort \
                    and self._abort is not None else SIG_OK
            else:  # OP_WAIT — ring full
                now = time.monotonic()
                if deadline is not None and now > deadline:
                    raise _ProgressStall(
                        "shm ring full while broadcasting abort"
                        if ignore_abort else
                        f"no send progress for {budget:.0f}s "
                        f"(HOROVOD_TCP_PROGRESS_DEADLINE_SECS="
                        f"{budget:g}, shm ring full)")
                if now >= next_probe:
                    self._require_peer_alive(p)
                    next_probe = now + _ABORT_POLL_SECS
                if ignore_abort:
                    time.sleep(_RING_NAP_SECS)  # hvdlint: disable=HVD001 -- bounded by the abort-broadcast deadline above
                else:
                    naps = p.bell_wait(p.out_space_bell_off, op[1], naps,
                                       self._nap)

    def _recv_bounded_into(self, p: _ShmPeer, view: memoryview,
                           with_crc: bool) -> Optional[int]:
        """Copy exactly ``len(view)`` bytes out of the inbound ring into
        the caller's view by driving the pure :func:`receiver_steps`
        protocol (see ``_send_bounded`` — same driver split), folding
        CRC32 over each landed span when asked — the incremental-CRC half
        of the zero-copy contract, same as the TCP side.  The deadline
        arms only after the peer's first-ever bytes (bring-up stagger is
        the startup timeout's problem)."""
        buf = p.shm.buf
        crc = 0
        measure_crc = with_crc and metrics.ENABLED
        crc_secs = 0.0
        budget = self.progress_deadline
        deadline = (time.monotonic() + budget) \
            if budget > 0 and p.ever_received else None
        next_probe = time.monotonic() + _ABORT_POLL_SECS
        naps = 0
        steps = receiver_steps(p.cap, [len(view)])
        resp = None
        while True:
            try:
                op = steps.send(resp)
            except StopIteration as fin:
                if fin.value == ABORTED:
                    raise CoordinatedAbortError(*self._abort) from None
                break
            kind = op[0]
            resp = None
            if kind == OP_LOAD:
                if op[1] == LOC_BELL_PEER:
                    resp = _load_u32(buf, p.in_data_bell_off)
                elif op[1] == LOC_BELL_OWN:
                    resp = _load_u32(buf, p.in_space_bell_off)
                elif op[1] == LOC_HEAD:
                    resp = _load_u64(buf, p.in_head_off)
                else:
                    resp = _load_u64(buf, p.in_tail_off)
            elif kind == OP_COPY:
                # Copy (and CRC) BEFORE the tail store the generator
                # yields next — the sender may overwrite the span the
                # moment the tail moves.
                _, _idx, got, pos, run = op
                naps = 0
                view[got:got + run] = p.in_ring[pos:pos + run]
                if with_crc:
                    if measure_crc:
                        tc = time.perf_counter()
                        crc = zlib.crc32(view[got:got + run], crc)
                        crc_secs += time.perf_counter() - tc
                    else:
                        crc = zlib.crc32(view[got:got + run], crc)
                if not p.ever_received:
                    p.ever_received = True
                    if budget > 0:
                        deadline = time.monotonic() + budget
                elif deadline is not None:
                    deadline = time.monotonic() + budget
                next_probe = time.monotonic() + _ABORT_POLL_SECS
            elif kind == OP_STORE:
                if op[1] == LOC_BELL_OWN:
                    _store_u32(buf, p.in_space_bell_off, op[2])
                else:
                    _store_u64(buf, p.in_tail_off, op[2])
            elif kind == OP_WAKE:
                p.wake(p.in_space_bell_off)
            elif kind == OP_POLL:
                resp = SIG_ABORT if self._abort is not None else SIG_OK
            else:  # OP_WAIT — ring empty
                now = time.monotonic()
                if deadline is not None and now > deadline:
                    raise _ProgressStall(
                        f"no recv progress for {budget:.0f}s "
                        f"(HOROVOD_TCP_PROGRESS_DEADLINE_SECS={budget:g})")
                if now >= next_probe:
                    self._require_peer_alive(p)
                    next_probe = now + _ABORT_POLL_SECS
                naps = p.bell_wait(p.in_data_bell_off, op[1], naps,
                                   self._nap)
        if measure_crc and crc_secs:
            metrics.inc("crc_verify_seconds_total", crc_secs)
        return (crc & 0xFFFFFFFF) if with_crc else None

    def _recv_bounded(self, p: _ShmPeer, n: int) -> bytes:
        buf = bytearray(n)
        self._recv_bounded_into(p, memoryview(buf), with_crc=False)
        return bytes(buf)

    # -- framed messaging (tcp.py's discipline over the ring) ---------------

    def send(self, peer: int, payload,
             digest: Optional[digest_mod.StreamDigest] = None,
             wire_dtype: int = 0, _check_frame: bool = False) -> None:
        """Frame and send one payload — one memcpy into the shared ring.
        Flag bits, deferred-digest handling, and fault-mutation semantics
        match :meth:`TcpMesh.send` bit for bit; shm data frames count
        under ``shm_bytes_total``, never ``bytes_on_wire`` (these bytes
        are not on a wire, and the zero-copy tests' exact wire accounting
        must hold)."""
        p = self._peer(peer)
        deferred = digest is not None and self.wire_crc
        with p.send_lock:
            self._check_alive(p, peer)
            try:
                payload = _as_byte_view(payload)
                wire = payload
                if faults.ACTIVE:
                    verdict = faults.inject(
                        "shm.send", rank=self.rank, peer=peer,
                        payload=payload)
                    if verdict is True:
                        return  # injected frame drop
                    if isinstance(verdict, faults.SendMutation):
                        # Same contract as tcp.send: truncate reframes
                        # self-consistently; corrupt flips wire bytes
                        # AFTER the CRC was computed over the original.
                        payload = _as_byte_view(verdict.payload)
                        wire = _as_byte_view(verdict.wire_bytes())
                flags = (wire_dtype << _WIRE_DTYPE_SHIFT) & _WIRE_DTYPE_MASK
                if deferred:
                    flags |= _DEFER_FLAG
                if _check_frame:
                    flags |= _DIGEST_FLAG
                header = _LEN.pack(len(payload) | flags)
                if self.wire_crc and not deferred:
                    header += _CRC.pack(self._crc32_timed(payload))
                self._send_bounded(p, [memoryview(header), wire])
                if deferred:
                    self._digest_timed(digest, payload)
                if not _check_frame:
                    metrics.inc("shm_bytes_total", len(payload))
                flight_recorder.record("frame", dir="send", peer=peer,
                                       nbytes=len(payload), via="shm")
            except _ProgressStall as e:
                self._mark_dead(p, str(e))
                raise PeerGoneError(peer, str(e)) from None
            except OSError as e:
                self._mark_dead(p, f"shm send to rank {peer} failed: {e}")
                raise PeerGoneError(
                    peer, f"shm send to rank {peer} failed: {e}") from e

    def _recv_header(self, p: _ShmPeer, peer: int) -> _FrameHeader:
        n = _LEN.unpack(self._recv_bounded(p, _LEN.size))[0]
        size = n & ~_FLAGS_MASK
        if size > _MAX_FRAME_BYTES:
            self._poison_stream(p, peer, HorovodInternalError(
                f"shm frame header from rank {peer} claims "
                f"{size} bytes (cap {_MAX_FRAME_BYTES}): "
                "corrupted length word; aborting before allocating it"))
        deferred = bool(n & _DEFER_FLAG)
        crc = _CRC.unpack(self._recv_bounded(p, _CRC.size))[0] \
            if self.wire_crc and not deferred else None
        return _FrameHeader(bool(n & _CTRL_FLAG), deferred,
                            bool(n & _DIGEST_FLAG),
                            (n & _WIRE_DTYPE_MASK) >> _WIRE_DTYPE_SHIFT,
                            size, crc)

    def recv(self, peer: int) -> bytes:
        """Materializing recv — the control/negotiation-plane primitive,
        identical contract to :meth:`TcpMesh.recv`."""
        p = self._peer(peer)
        with p.recv_lock:
            self._check_alive(p, peer)
            try:
                if faults.ACTIVE:
                    faults.inject("shm.recv", rank=self.rank, peer=peer)
                while True:
                    hdr = self._recv_header(p, peer)
                    if hdr.ctrl:
                        self._consume_control_frame(p, peer, hdr.size,
                                                    hdr.crc)
                        continue  # stale control frame: keep reading
                    if hdr.deferred or hdr.check or hdr.wire_dtype:
                        self._poison_stream(p, peer, HorovodInternalError(
                            f"flagged shm data frame from rank {peer} on "
                            f"the control recv path "
                            f"(deferred={hdr.deferred}, check={hdr.check}, "
                            f"wire_dtype={hdr.wire_dtype}): CRC/compression "
                            "framing skew between peers; aborting, resync "
                            "is impossible by design"))
                    payload = self._recv_bounded(p, hdr.size)
                    p.frames_in += 1
                    if hdr.crc is not None:
                        got = self._crc32_timed(payload)
                        if got != hdr.crc:
                            self._poison_stream(
                                p, peer,
                                FrameCorruptError(peer, p.frames_in,
                                                  hdr.crc, got))
                    metrics.inc("shm_bytes_total", hdr.size)
                    flight_recorder.record("frame", dir="recv", peer=peer,
                                           nbytes=hdr.size, via="shm")
                    return payload
            except _ProgressStall as e:
                self._mark_dead(p, str(e))
                raise PeerGoneError(peer, str(e)) from None
            except OSError as e:
                self._mark_dead(p, f"shm recv from rank {peer} failed: {e}")
                raise PeerGoneError(
                    peer, f"shm recv from rank {peer} failed: {e}") from e

    def recv_into(self, peer: int, dest,
                  digest: Optional[digest_mod.StreamDigest] = None,
                  wire_dtype: int = 0) -> int:
        """Zero-copy recv: one memcpy from the shared ring into ``dest``.
        All header-skew checks (deferred-ness, wire dtype, exact size)
        poison the stream exactly as on TCP — config skew between peers
        must fail loudly on every transport."""
        p = self._peer(peer)
        dv = _as_writable_byte_view(dest)
        with p.recv_lock:
            self._check_alive(p, peer)
            try:
                if faults.ACTIVE:
                    faults.inject("shm.recv", rank=self.rank, peer=peer)
                while True:
                    hdr = self._recv_header(p, peer)
                    if hdr.ctrl:
                        self._consume_control_frame(p, peer, hdr.size,
                                                    hdr.crc)
                        continue  # stale control frame: keep reading
                    if hdr.check:
                        self._poison_stream(p, peer, HorovodInternalError(
                            f"unexpected digest-check frame from rank "
                            f"{peer} where a data frame was due: ring-step "
                            "framing skew between peers; aborting"))
                    if hdr.deferred != (digest is not None):
                        self._poison_stream(p, peer, HorovodInternalError(
                            f"shm data frame from rank {peer} is "
                            f"{'digest-deferred' if hdr.deferred else 'inline-CRC'} "
                            f"but this rank expected the "
                            f"{'deferred' if digest is not None else 'inline'} "
                            "path: HOROVOD_SHM_CRC/"
                            "HOROVOD_WIRE_CRC_SHADOW skew between peers; "
                            "aborting loudly"))
                    if hdr.wire_dtype != wire_dtype:
                        self._poison_stream(p, peer, HorovodInternalError(
                            f"shm data frame from rank {peer} carries wire "
                            f"dtype code {hdr.wire_dtype} but this rank "
                            f"expects {wire_dtype}: "
                            "HOROVOD_WIRE_COMPRESSION skew between peers "
                            "(mixed-version or mixed-config mesh); "
                            "aborting loudly instead of mis-decoding"))
                    if hdr.size != len(dv):
                        self._poison_stream(p, peer, HorovodInternalError(
                            f"shm data frame from rank {peer} carries "
                            f"{hdr.size} bytes but the recv_into "
                            f"destination expects {len(dv)}: misframed "
                            "stream (truncated or desynced); aborting, "
                            "resync is impossible by design"))
                    got = self._recv_bounded_into(
                        p, dv, with_crc=hdr.crc is not None)
                    p.frames_in += 1
                    if hdr.crc is not None and got != hdr.crc:
                        self._poison_stream(
                            p, peer,
                            FrameCorruptError(peer, p.frames_in, hdr.crc,
                                              got))
                    if digest is not None:
                        self._digest_timed(digest, dv)
                    metrics.inc("shm_bytes_total", hdr.size)
                    flight_recorder.record("frame", dir="recv", peer=peer,
                                           nbytes=hdr.size, via="shm")
                    return hdr.size
            except _ProgressStall as e:
                self._mark_dead(p, str(e))
                raise PeerGoneError(peer, str(e)) from None
            except OSError as e:
                self._mark_dead(p, f"shm recv from rank {peer} failed: {e}")
                raise PeerGoneError(
                    peer, f"shm recv from rank {peer} failed: {e}") from e

    def send_step_digest(self, peer: int, dig: digest_mod.StreamDigest,
                         frames: int) -> None:
        self.send(peer, digest_mod.pack_check(dig, frames),
                  _check_frame=True)

    def verify_step_digest(self, peer: int, dig: digest_mod.StreamDigest,
                           frames: int) -> None:
        """Settle one deferred ring-step direction — same contract and
        same poison semantics as the TCP mesh's."""
        p = self._peer(peer)
        with p.recv_lock:
            self._check_alive(p, peer)
            try:
                while True:
                    hdr = self._recv_header(p, peer)
                    if hdr.ctrl:
                        self._consume_control_frame(p, peer, hdr.size,
                                                    hdr.crc)
                        continue  # stale control frame: keep reading
                    if not hdr.check:
                        self._poison_stream(p, peer, HorovodInternalError(
                            f"expected a digest-check frame from rank "
                            f"{peer} to close the ring step but got a "
                            "data frame: step framing skew between "
                            "peers; aborting"))
                    if hdr.size != digest_mod.CHECK_SIZE:
                        self._poison_stream(p, peer, HorovodInternalError(
                            f"digest-check frame from rank {peer} "
                            f"carries {hdr.size} bytes (expected "
                            f"{digest_mod.CHECK_SIZE}): misframed stream "
                            "(truncated or desynced); aborting"))
                    payload = self._recv_bounded(p, hdr.size)
                    p.frames_in += 1
                    if hdr.crc is not None:
                        got = self._crc32_timed(payload)
                        if got != hdr.crc:
                            self._poison_stream(
                                p, peer,
                                FrameCorruptError(peer, p.frames_in,
                                                  hdr.crc, got))
                    algo, value, count = digest_mod.unpack_check(payload)
                    if algo != dig.algo:
                        self._poison_stream(p, peer, HorovodInternalError(
                            f"digest-check frame from rank {peer} uses "
                            f"wire digest "
                            f"{digest_mod.algo_name(algo)!r} but this "
                            f"rank runs "
                            f"{digest_mod.algo_name(dig.algo)!r}: "
                            "HOROVOD_WIRE_DIGEST skew between peers"))
                    if count != frames or value != dig.value():
                        self._poison_stream(
                            p, peer,
                            FrameCorruptError(peer, p.frames_in, value,
                                              dig.value()))
                    flight_recorder.record("frame", dir="recv", peer=peer,
                                           nbytes=hdr.size, via="shm")
                    return
            except _ProgressStall as e:
                self._mark_dead(p, str(e))
                raise PeerGoneError(peer, str(e)) from None
            except OSError as e:
                self._mark_dead(p, f"shm recv from rank {peer} failed: {e}")
                raise PeerGoneError(
                    peer, f"shm recv from rank {peer} failed: {e}") from e

    # -- control plane ------------------------------------------------------

    def _consume_control_frame(self, p: _ShmPeer, peer: int, size: int,
                               crc: Optional[int]) -> None:
        payload = self._recv_bounded(p, size)
        p.frames_in += 1
        if crc is not None:
            got = self._crc32_timed(payload)
            if got != crc:
                self._poison_stream(
                    p, peer,
                    FrameCorruptError(peer, p.frames_in, crc, got))
        self._handle_control(payload, peer)

    def _poison_stream(self, p: _ShmPeer, peer: int,
                       err: HorovodInternalError) -> None:
        """Same unrecoverable-by-design contract as the TCP mesh: mark
        dead, broadcast the coordinated abort (via the LinkMesh relay
        when present, so TCP links hear it too), raise."""
        flight_recorder.record("stream_poisoned", peer=peer,
                               error=str(err)[:300], via="shm")
        self._mark_dead(p, str(err))
        self.send_abort(str(err))
        raise err

    def _handle_control(self, payload: bytes, peer: int) -> None:
        from ..core.messages import AbortFrame, is_abort_frame

        if not is_abort_frame(payload):
            raise HorovodInternalError(
                f"unknown control frame from rank {peer} (shm)")
        frame = AbortFrame.from_bytes(payload)
        if frame.epoch < self.epoch:
            log.warning(
                "discarding stale abort from rank %d (epoch %d < %d): %s",
                frame.origin_rank, frame.epoch, self.epoch, frame.reason)
            return
        metrics.inc("aborts_total", dir="received")
        flight_recorder.record("abort_received", origin=frame.origin_rank,
                               epoch=frame.epoch,
                               reason=frame.reason[:300])
        self._abort = (frame.epoch, frame.origin_rank, frame.reason)
        self._nap.set()
        raise CoordinatedAbortError(frame.epoch, frame.origin_rank,
                                    frame.reason)

    def send_abort(self, reason: str, epoch: Optional[int] = None,
                   origin_rank: Optional[int] = None,
                   _relayed: bool = False, _record: bool = True) -> None:
        """Broadcast a coordinated abort over every surviving shm link.

        Best-effort with a SHORT per-link budget: a dead peer's ring may
        be full forever, and the caller is already tearing down.  Flips
        the (possibly shared) abort flag first and wakes every napping
        ring wait.  ``_record`` lets the LinkMesh suppress the
        metrics/flight-recorder entries when it already recorded the
        broadcast via the TCP half."""
        if self._closed or self.size == 1:
            return
        if not _relayed and self.abort_relay is not None:
            self.abort_relay(reason, epoch=epoch, origin_rank=origin_rank)
            return
        from ..core.messages import AbortFrame

        epoch = self.epoch if epoch is None else epoch
        origin_rank = self.rank if origin_rank is None else origin_rank
        payload = AbortFrame(epoch=epoch, origin_rank=origin_rank,
                             reason=reason).to_bytes()
        if _record:
            metrics.inc("aborts_total", dir="sent")
            flight_recorder.record("abort_broadcast", origin=origin_rank,
                                   epoch=epoch, reason=reason[:300])
        if self._abort is None:
            self._abort = (epoch, origin_rank, reason)
        self._nap.set()
        header = _LEN.pack(len(payload) | _CTRL_FLAG)
        if self.wire_crc:
            header += _CRC.pack(zlib.crc32(payload) & 0xFFFFFFFF)
        for peer, p in list(self._peers.items()):
            # Dead-marked links are still tried, same as TCP: the peer's
            # recv direction may be fine and the abort is what unblocks
            # it.  The 2 s ring budget bounds a truly dead peer.
            if not p.send_lock.acquire(timeout=2.0):
                continue  # a wedged send holds the lock; skip this link
            try:
                self._abort_write(p, [memoryview(header),
                                      memoryview(payload)])
            except (OSError, _ProgressStall) as e:
                self._mark_dead(p, f"abort send failed: {e}")
            finally:
                p.send_lock.release()

    def _abort_write(self, p: _ShmPeer, bufs: List[memoryview]) -> None:
        """Ring write for the abort broadcast: ignores the mesh abort
        flag (it is ALREADY set — the normal path would refuse to write)
        but keeps a short no-progress deadline and the liveness probe.
        Rides the same :func:`sender_steps` protocol as every other send
        — one bump per call, publish-before-sleep — where a previous
        incarnation re-derived the ring run with a diverging per-RUN
        bell bump."""
        self._send_bounded(p, bufs, budget=2.0, ignore_abort=True)

    # -- concurrent helpers (ring-collective primitives) --------------------

    def sendrecv(self, send_to: int, payload, recv_from: int) -> bytes:
        done = threading.Event()
        box: List = [None, None]  # [result, error]

        def _recv():
            try:
                box[0] = self.recv(recv_from)
            except BaseException as e:  # noqa: BLE001
                box[1] = e
            finally:
                done.set()

        self._sr_submit(_recv)
        self.send(send_to, payload)
        done.wait()
        if box[1] is not None:
            raise box[1]
        return box[0]

    def recv_into_async(self, peer: int, dest,
                        digest: Optional[digest_mod.StreamDigest] = None,
                        wire_dtype: int = 0) -> PendingRecv:
        """FIFO posts on one helper thread map recvs onto the peer's
        frames in ring order — same serialization argument as the TCP
        helper, same digest-ordering guarantee."""
        done = threading.Event()
        box: List = [None, None]  # [nbytes, error]

        def _recv():
            try:
                box[0] = self.recv_into(peer, dest, digest=digest,
                                        wire_dtype=wire_dtype)
            except BaseException as e:  # noqa: BLE001
                box[1] = e
            finally:
                done.set()

        self._sr_submit(_recv)
        return PendingRecv(done, box)

    def sendrecv_into(self, send_to: int, payload, recv_from: int,
                      dest) -> int:
        pending = self.recv_into_async(recv_from, dest)
        self.send(send_to, payload)
        return pending.wait()

    def _sr_submit(self, task) -> None:
        if self._sr_thread is None or not self._sr_thread.is_alive():
            self._sr_queue = queue.SimpleQueue()
            self._sr_thread = threading.Thread(
                target=self._sr_loop, name="hvd-shm-sendrecv", daemon=True)
            self._sr_thread.start()
        self._sr_queue.put(task)

    def _sr_loop(self) -> None:
        while True:
            task = self._sr_queue.get()
            if task is None:
                return
            try:
                task()
            except BaseException:  # noqa: BLE001 — a raising task must not
                # kill the loop (queued tasks behind it would wait forever);
                # the posted closures catch their own errors into result
                # boxes, so anything here is a foreign/broken submission.
                log.error("shm sendrecv helper task raised", exc_info=True)

    # -- lifecycle ----------------------------------------------------------

    def _peer(self, peer: int) -> _ShmPeer:
        try:
            return self._peers[peer]
        except KeyError:
            raise HorovodInternalError(
                f"rank {self.rank} has no shm link to rank {peer}") from None

    def close(self) -> None:
        """Detach every segment; the CREATOR also unlinks it.  POSIX keeps
        the memory alive until the last mapping drops, so a peer still
        draining its ring is unaffected by the unlink — the name just
        leaves /dev/shm, which is exactly the no-residue property the
        leak tests assert."""
        if self._closed:
            return
        self._closed = True
        self._nap.set()
        if self._sr_thread is not None and self._sr_thread.is_alive():
            self._sr_queue.put(None)
        for p in self._peers.values():
            # Exported ring views and the ctypes futex anchor must drop
            # before SharedMemory.close() (its mmap refuses to unmap
            # under live exports).
            p.base_addr = 0
            p.addr_anchor = None
            p.out_ring.release()
            p.in_ring.release()
            try:
                p.shm.close()
            except (OSError, BufferError):
                pass
            if p.created:
                try:
                    p.shm.unlink()
                except FileNotFoundError:
                    pass
