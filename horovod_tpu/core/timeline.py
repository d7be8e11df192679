"""Chrome-tracing timeline — per-tensor lanes of negotiation + execution.

Role of the reference's ``horovod/common/timeline.cc:1-509`` /
``timeline.h:106-126``: a catapult-format JSON trace where each tensor gets
its own lane (tid), showing ``NEGOTIATE_*`` (how long ranks waited on each
other, with per-rank ready ticks) followed by the operation with nested
activities.  The reference feeds records through a boost lockfree spsc queue
drained by a writer thread so the background loop never blocks on disk; we
use a ``SimpleQueue`` + writer thread for the same property.

Cross-rank story (the Dapper-shaped half, docs/observability.md): every
rank writes its own trace with ``pid = rank`` (rank 0 at the configured
``HOROVOD_TIMELINE`` path, rank r at ``<path>.rank<r>``), every span is
tagged with its negotiation **cycle id** (the lockstep round counter,
identical on every rank), and a ``clock_sync`` metadata record carries the
wall-clock base plus an offset-to-the-rendezvous-server estimate
(:func:`estimate_server_clock_offset_ns`, Cristian-style against the
server's ``GET /clock``).  ``tools/trace_merge.py`` uses those to align
the per-rank files into ONE Chrome/Perfetto view where every rank's lanes
for the same collective line up.

View the output in ``chrome://tracing`` / Perfetto.  Runtime toggles via
``hvd.start_timeline()/stop_timeline()`` (reference ``operations.cc:780-806``)
or the ``HOROVOD_TIMELINE`` env knob.
"""

from __future__ import annotations

import json
import queue
import sys
import threading
import time
from typing import Dict, List, Optional

from ..common import env as env_mod
from . import metrics

_WRITER_SENTINEL = None

#: Name of the per-trace metadata record trace_merge aligns clocks on.
CLOCK_SYNC_EVENT = "clock_sync"

#: Per-tensor lifecycle spans (submitted → negotiated → fused → wire →
#: reduced → callback) on every rank, consumed by
#: ``tools/critical_path.py``.  Toggle-gated so the instrumented hot
#: paths stay at one module-attribute read when off or when no timeline
#: is active.
LIFECYCLE_ENABLED = env_mod.get_bool(env_mod.HOROVOD_TIMELINE_LIFECYCLE, True)

#: Control-plane spans (``RV_*`` on the server trace, ``RVC_*`` client
#: round-trips, ``DRV_*``/``CHURN_EVENT`` on the driver trace), consumed
#: by ``tools/control_path.py``.  Same gating discipline as
#: ``LIFECYCLE_ENABLED``.
CONTROL_PLANE_ENABLED = env_mod.get_bool(
    env_mod.HOROVOD_TIMELINE_CONTROL_PLANE, True)

#: Reserved trace pids for the control-plane processes.  Workers own the
#: non-negative pids (pid = rank); the rendezvous server and the elastic
#: driver get sentinel lanes so a merged trace keeps them distinct from
#: every possible rank.
SERVER_TRACE_PID = -1
DRIVER_TRACE_PID = -2

#: The process's live Timeline, set by the constructor and cleared by
#: ``close()``: instrumentation sites that can't reach the global state
#: object (tensor queue, ring backend) emit lifecycle records through the
#: module-level helpers below instead of threading the instance through
#: every call chain.
ACTIVE: Optional["Timeline"] = None


def lifecycle_begin(tensor_name: str, stage: str,
                    cycle: Optional[int] = None) -> None:
    tl = ACTIVE
    if tl is not None and LIFECYCLE_ENABLED:
        tl.lifecycle(tensor_name, stage, begin=True, cycle=cycle)


def lifecycle_end(tensor_name: str, stage: str) -> None:
    tl = ACTIVE
    if tl is not None and LIFECYCLE_ENABLED:
        tl.lifecycle(tensor_name, stage, begin=False)


def lifecycle_instant(tensor_name: str, stage: str,
                      cycle: Optional[int] = None) -> None:
    tl = ACTIVE
    if tl is not None and LIFECYCLE_ENABLED:
        tl.lifecycle_mark(tensor_name, stage, cycle=cycle)


def control_active() -> bool:
    """True when a control-plane span emitted now would land somewhere.
    Instrumentation sites sample ``time.monotonic_ns()`` only when this
    holds, so the off path stays at two module-attribute reads."""
    return ACTIVE is not None and CONTROL_PLANE_ENABLED


def control_span_since(lane: str, name: str, t0_mono_ns: int,
                       **args) -> None:
    """Retroactive control-plane span on the active timeline: covers
    ``[t0_mono_ns, now]`` (caller sampled ``time.monotonic_ns()`` before
    the work).  No-op when no timeline is active or the knob is off."""
    tl = ACTIVE
    if tl is not None and CONTROL_PLANE_ENABLED:
        tl.span_since(lane, name, t0_mono_ns, args or None)


def control_instant(lane: str, name: str, **args) -> None:
    tl = ACTIVE
    if tl is not None and CONTROL_PLANE_ENABLED:
        tl.instant(lane, name, args or None)


def rank_trace_path(path: str, rank: int) -> str:
    """Per-rank trace file layout: rank 0 owns the configured path
    (back-compat with single-file consumers), rank r writes
    ``<path>.rank<r>``."""
    return path if rank == 0 else f"{path}.rank{rank}"


def estimate_server_clock_offset_ns(samples: int = 3) -> Optional[int]:
    """Estimate this host's wall-clock offset to the rendezvous server
    (``local_wall - server_wall``, ns) via the server's ``GET /clock``:
    Cristian's algorithm, keeping the minimum-RTT sample.  Every rank
    measures against the SAME server clock, so cross-rank skew is the
    difference of these estimates.  Returns None when no rendezvous is
    configured or unreachable — trace_merge then assumes synced clocks."""
    import urllib.request

    addr = env_mod.get_str(env_mod.HOROVOD_RENDEZVOUS_ADDR)
    port = env_mod.get_int(env_mod.HOROVOD_RENDEZVOUS_PORT, 0)
    if not addr or not port:
        return None
    best = None  # (rtt_ns, offset_ns)
    try:
        for _ in range(samples):
            t0 = time.time_ns()
            with urllib.request.urlopen(
                    f"http://{addr}:{port}/clock", timeout=2.0) as resp:
                server_ns = int(resp.read())
            t1 = time.time_ns()
            cand = (t1 - t0, (t0 + t1) // 2 - server_ns)
            if best is None or cand[0] < best[0]:
                best = cand
    except (OSError, ValueError):
        return None if best is None else best[1]
    return best[1]


class Timeline:
    def __init__(self, path: str, mark_cycles: bool = False, rank: int = 0,
                 clock_offset_ns: Optional[int] = None,
                 activate: bool = True,
                 process_name: Optional[str] = None):
        self._path = path
        self._mark_cycles = mark_cycles
        self._pid = rank
        self._cycle = 0
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._tids: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._start = time.monotonic_ns()
        # Sampled back-to-back with _start: ts=0 on this trace's axis is
        # this wall-clock instant (trace_merge's alignment anchor).
        self._wall_base_ns = time.time_ns()
        self._closed = False
        self._file = open(path, "w", buffering=1024 * 1024)
        self._file.write("[\n")
        self._first = True
        self._writer = threading.Thread(
            target=self._writer_loop, name="horovod-timeline", daemon=True)
        self._writer.start()
        self._emit({"name": "process_name", "ph": "M", "pid": self._pid,
                    "args": {"name": process_name
                             or f"horovod_tpu rank {rank}"}})
        self._emit({"name": CLOCK_SYNC_EVENT, "ph": "M", "pid": self._pid,
                    "args": {"wall_base_ns": self._wall_base_ns,
                             "server_offset_ns": clock_offset_ns,
                             "rank": rank}})
        # Secondary timelines (the rendezvous server's trace lives inside
        # the launcher process next to the workers') opt out of owning the
        # module-level ACTIVE slot.
        if activate:
            global ACTIVE
            ACTIVE = self

    # -- producers (background/controller thread; never block) -------------

    def _ts_us(self) -> float:
        return (time.monotonic_ns() - self._start) / 1e3

    def set_cycle(self, cycle: int) -> None:
        """Current negotiation cycle id — the background loop advances it
        each round.  Rounds are lockstep across ranks (the TCP recv pairs
        them), so the same id names the same global round everywhere;
        spans tagged with it line up across merged per-rank traces."""
        self._cycle = cycle

    def _tid(self, tensor_name: str) -> int:
        with self._lock:
            tid = self._tids.get(tensor_name)
            if tid is None:
                tid = len(self._tids) + 1
                self._tids[tensor_name] = tid
                self._emit({"name": "thread_name", "ph": "M",
                            "pid": self._pid, "tid": tid,
                            "args": {"name": tensor_name}})
        return tid

    def _emit(self, record: dict) -> None:
        if not self._closed:
            self._queue.put(record)

    def negotiate_start(self, tensor_name: str, op_name: str) -> None:
        self._emit({"name": f"NEGOTIATE_{op_name}", "ph": "B",
                    "pid": self._pid, "tid": self._tid(tensor_name),
                    "ts": self._ts_us(), "args": {"cycle": self._cycle}})

    def negotiate_rank_ready(self, tensor_name: str, rank: int) -> None:
        """Per-rank readiness tick inside the negotiation phase
        (reference ``NegotiateRankReady``, ``timeline.h:113``)."""
        self._emit({"name": str(rank), "ph": "i", "s": "t", "pid": self._pid,
                    "tid": self._tid(tensor_name), "ts": self._ts_us()})

    def negotiate_end(self, tensor_name: str) -> None:
        self._emit({"name": "", "ph": "E", "pid": self._pid,
                    "tid": self._tid(tensor_name), "ts": self._ts_us()})

    def op_start(self, response, entries) -> None:
        name = response.response_type.name
        ts = self._ts_us()
        # Pipelined device dispatches run while the NEXT cycle negotiates;
        # the response carries the cycle it was negotiated in so the tag
        # stays right regardless of which thread executes it.
        cycle = getattr(response, "_cycle", self._cycle)
        for e in entries:
            self._emit({"name": name, "ph": "B", "pid": self._pid,
                        "tid": self._tid(e.tensor_name), "ts": ts,
                        "args": {"cycle": cycle}})

    def op_end(self, response, entries) -> None:
        ts = self._ts_us()
        for e in entries:
            self._emit({"name": "", "ph": "E", "pid": self._pid,
                        "tid": self._tid(e.tensor_name), "ts": ts})

    def activity(self, tensor_name: str, activity: str, begin: bool) -> None:
        """Nested activity markers (MEMCPY_IN_FUSION_BUFFER, ... —
        reference macro list ``common.h:31-62``)."""
        rec = {"name": activity if begin else "", "ph": "B" if begin else "E",
               "pid": self._pid, "tid": self._tid(tensor_name),
               "ts": self._ts_us()}
        self._emit(rec)

    def lifecycle(self, tensor_name: str, stage: str, begin: bool,
                  cycle: Optional[int] = None) -> None:
        """Cycle-tagged lifecycle span on the tensor's lane (``LC_*`` —
        submitted/fuse/wire/reduce/callback; docs/observability.md lists
        the schema).  Unlike :meth:`activity`, B records carry
        ``args.cycle`` so ``tools/critical_path.py`` can group a tensor's
        spans into per-step chains across ranks."""
        rec = {"name": stage if begin else "", "ph": "B" if begin else "E",
               "pid": self._pid, "tid": self._tid(tensor_name),
               "ts": self._ts_us()}
        if begin:
            rec["args"] = {"cycle": self._cycle if cycle is None else cycle}
        self._emit(rec)

    def lifecycle_mark(self, tensor_name: str, stage: str,
                       cycle: Optional[int] = None) -> None:
        """Instant lifecycle marker (e.g. ``LC_NEGOTIATED`` with the cycle
        the response was agreed in)."""
        self._emit({"name": stage, "ph": "i", "s": "t", "pid": self._pid,
                    "tid": self._tid(tensor_name), "ts": self._ts_us(),
                    "args": {"cycle": self._cycle if cycle is None
                             else cycle}})

    def span_since(self, lane: str, name: str, t0_mono_ns: int,
                   args: Optional[dict] = None) -> None:
        """Complete ("X") control-plane span on a named lane, covering
        ``[t0_mono_ns, now]``.  Complete events are atomic — concurrent
        handler threads can land overlapping spans on one lane without
        the B/E mis-nesting a shared stack would suffer."""
        b_us = (t0_mono_ns - self._start) / 1e3
        rec = {"name": name, "ph": "X", "pid": self._pid,
               "tid": self._tid(lane), "ts": b_us,
               "dur": self._ts_us() - b_us}
        if args:
            rec["args"] = dict(args)
        self._emit(rec)

    def instant(self, lane: str, name: str,
                args: Optional[dict] = None) -> None:
        """Instant marker on a named lane (control-plane events like
        ``EPOCH_TRANSITION``)."""
        rec = {"name": name, "ph": "i", "s": "t", "pid": self._pid,
               "tid": self._tid(lane), "ts": self._ts_us()}
        if args:
            rec["args"] = dict(args)
        self._emit(rec)

    def mark_cycle(self) -> None:
        if self._mark_cycles:
            self._emit({"name": "CYCLE", "ph": "i", "s": "g",
                        "pid": self._pid, "tid": 0, "ts": self._ts_us(),
                        "args": {"cycle": self._cycle}})

    # -- writer thread ------------------------------------------------------

    def _writer_loop(self) -> None:
        while True:
            rec = self._queue.get()
            if rec is _WRITER_SENTINEL:
                break
            try:
                if not self._first:
                    self._file.write(",\n")
                self._first = False
                self._file.write(json.dumps(rec))
            except ValueError:  # file closed under us
                break

    def close(self) -> None:
        global ACTIVE
        if ACTIVE is self:
            ACTIVE = None
        if self._closed:
            return
        self._closed = True
        self._queue.put(_WRITER_SENTINEL)
        self._writer.join(timeout=10)
        if self._writer.is_alive():
            # Writer still draining a deep backlog: do not write the epilogue
            # or close the file under it — a truncated-but-valid-prefix trace
            # beats an interleaved corrupt one.
            return
        self._file.write("\n]\n")
        self._file.close()


# ---------------------------------------------------------------------------
# per-phase dispatch-chain accounting
# ---------------------------------------------------------------------------


#: Every phase of the eager runtime, old names first.  ``core/metrics.py``'s
#: ``CATALOG``, ``docs/observability.md`` and lint rule HVD007 are held to it.
PHASES = (
    "negotiate", "fuse", "collective", "unfuse", "wait",
    "update", "enqueue", "tree_unflatten", "optimizer_update",
    "queue_wait", "dispatch_wait", "program_call", "wfbp_dispatch",
    "state_fuse",
    "negotiate_wait", "negotiate_recv", "negotiate_idle",
    "cpu.loop", "cpu.dispatch", "cpu.update",
)


class PhaseStats:
    """Always-on wall-time accumulator over the eager runtime's phases
    (``PHASES``).  On the dispatch chain: ``negotiate`` (controller round,
    busy cycles only), ``fuse`` (staging the fused buffer), ``collective``
    (host cost of dispatching the device collective), ``unfuse`` (results
    back to per-entry outputs), ``wait`` (framework-thread handle
    synchronization), and a tensor's three waits between threads and
    ranks, ``queue_wait`` (tensor queue → background loop),
    ``negotiate_wait`` (announced by this rank → agreed by all) and
    ``dispatch_wait`` (agreed → the dispatcher thread has it); inside
    ``negotiate``, ``negotiate_recv`` (a round blocked on another rank's
    frame while a tensor of this rank's is in flight), and beside it ``negotiate_idle`` (the rounds
    ``negotiate`` leaves out, so that the two counts are the rounds).  On
    the calling thread:
    ``update`` (the whole of ``DistributedOptimizer.update``) with its
    parts ``enqueue``, ``tree_unflatten``, ``optimizer_update`` and
    ``state_fuse`` (an update that was handed the inner state as a plain
    tree and joined it; none in a steady job);
    ``wfbp_dispatch`` (one call of an ``OverlappedTrainStep``); and
    ``program_call``, nested in the others, around every call of a jitted
    program the framework owns.  Three names are **thread CPU seconds**
    (``time.thread_time()``, user and system) and not wall time:
    ``cpu.loop`` (the loop thread: rounds, what they ran inline and the
    parks between), ``cpu.dispatch`` (the dispatcher thread, a response)
    and ``cpu.update`` (the calling thread inside ``update``).  A thread
    that holds the interpreter is on the CPU, so these bound from above
    how long each thread kept it from the others.

    This is the aggregate companion to the traces: a trace answers "what
    happened when", this answers "where does a step's millisecond budget
    go" cheaply enough to leave enabled (two monotonic reads + one dict
    update per phase).  :func:`phase` feeds it and puts the same extent
    into the jax profiler's trace.  Surfaced by the chip benchmark's
    per-layer metrics (``chip_bench/metrics/``),
    snapshot-able from tests, and registered as a view in the metrics
    registry (``phase_seconds_total``/``phase_ops_total``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._acc: Dict[str, List[float]] = {}

    def add(self, phase: str, seconds: float, n: int = 1) -> None:
        """``n`` is what ``count`` grows by: 1 event for most phases, the
        number of tensors that waited for ``queue_wait`` and
        ``negotiate_wait``, the receives for ``negotiate_recv``, and for
        ``program_call`` the number of output arrays the program returned
        (so ``mean_ms`` there is host ms per output buffer)."""
        with self._lock:
            slot = self._acc.get(phase)
            if slot is None:
                self._acc[phase] = [seconds, n]
            else:
                slot[0] += seconds
                slot[1] += n

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                phase: {
                    "total_ms": round(total * 1e3, 3),
                    "count": int(count),
                    "mean_ms": round(total / max(count, 1) * 1e3, 4),
                }
                for phase, (total, count) in self._acc.items()
            }

    def reset(self) -> None:
        with self._lock:
            self._acc.clear()


#: Process-global instance — the background loop, the XLA backend, and the
#: framework-side handle waits all record into this.
phase_stats = PhaseStats()

# ``jax.profiler.TraceAnnotation``, looked up once the process has imported
# jax (before that nobody can have started its profiler, and ``core/`` also
# serves bindings that never do): until then, and where jax cannot be
# imported (False), a phase is its accumulator alone.
_annotation = None
_local = threading.local()


def _trace_annotation():
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        try:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        except ImportError:
            _annotation = False
    return _annotation


def current_ids() -> dict:
    """The identifiers of the span this thread is inside (``step``, and
    ``cycle`` on the runtime's threads), for what carries them to another
    thread."""
    return getattr(_local, "ids", None) or {}


class span_ids:
    """``with span_ids(step=..., cycle=...):`` — every :func:`phase` opened
    inside, on this thread, carries these identifiers besides those of the
    scope around it.  None values are dropped."""

    __slots__ = ("ids", "_outer")

    def __init__(self, **ids):
        self.ids = ids

    def __enter__(self):
        outer = self._outer = current_ids()
        _local.ids = {**outer, **{k: v for k, v in self.ids.items()
                                  if v is not None}} if self.ids else outer
        return self

    def __exit__(self, *exc) -> None:
        _local.ids = self._outer


class phase(span_ids):
    """``with phase(name, **ids):`` — one extent, two records: a
    ``hvd.<name>`` span in the jax profiler's trace, on the clock of the
    device's op line, and the elapsed ``time.monotonic()`` added to
    :data:`phase_stats` under ``name``, also when the body raises.  With
    no profiler running the span is a no-op of under a microsecond, so
    nothing switches this off.

    ``ids`` become the span's arguments and, as in :class:`span_ids`, those
    of every span inside it on its thread: one ``step=`` at the top names
    every span of that step.  Inside the block, ``record = False`` keeps
    the extent out of the accumulator (the span stays) and ``n`` sets what
    ``count`` grows by; ``annotate(**ids)`` gives the open span arguments
    known only once its body has run; ``seconds`` holds the elapsed time
    afterwards."""

    __slots__ = ("name", "record", "n", "seconds", "_span", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self.record = True
        self.n = 1
        self.seconds = 0.0

    def __enter__(self) -> "phase":
        super().__enter__()
        annotation = _trace_annotation()
        self._span = annotation("hvd." + self.name, **_local.ids) \
            if annotation else None
        if self._span is not None:
            self._span.__enter__()
        self._t0 = time.monotonic()
        return self

    def annotate(self, **ids) -> None:
        if self._span is not None:
            self._span.set_metadata(**ids)

    def __exit__(self, *exc) -> None:
        self.seconds = time.monotonic() - self._t0
        if self._span is not None:
            self._span.__exit__(*exc)
        super().__exit__()
        if self.record:
            phase_stats.add(self.name, self.seconds, self.n)  # hvdlint: disable=HVD007 -- the one forwarding site: HVD007 checks the literal at every phase(...) call


#: Every block of a step on the device, for :func:`scope`.  A step builder's
#: parts come first, then a transformer's blocks, the expert layer's, the
#: attention kernels by their mask's rule, ResNet's.  ``docs/observability.md``
#: ("Device time by block") says what each covers and where it is entered.
SCOPES = (
    "loss", "optimizer", "fuse", "allreduce",
    "embed", "norm", "ffn", "head",
    "attn.proj", "attn.norm", "attn.rope", "attn.layout", "attn.latent",
    "attn.einsum", "attn.flash", "attn.short", "attn.ring", "attn.ulysses",
    "attn.causal", "attn.window", "attn.blockdiff", "attn.gate",
    "conv.proj", "conv.gate",
    "ssm.proj", "ssm.conv", "ssm.scan", "ssm.norm",
    "gdn.proj", "gdn.conv", "gdn.gates", "gdn.rule", "gdn.norm",
    "moe.router", "moe.dispatch", "moe.experts", "moe.combine",
    "moe.latent", "moe.shared", "moe.shared_gate", "mtp.proj",
    "resnet.stem", "resnet.stage1", "resnet.stage2", "resnet.stage3",
    "resnet.stage4", "resnet.head", "bn",
    "hc.coeff", "hc.sinkhorn", "hc.pre", "hc.post",
    "kda.proj", "kda.conv", "kda.gate", "kda.rule", "kda.norm", "kda.out",
    "attn.sparse", "indexer.proj", "indexer.scores", "indexer.choose",
    "indexer.target", "indexer.loss",
)


def scope(name: str):
    """``with scope(name):`` — the device's counterpart of :func:`phase`:
    every operation traced inside carries ``hvd.<name>`` in its HLO
    ``op_name``, which the profiler records as the op's ``tf_op`` on the
    device's ``XLA Ops`` line, in the same ``.xplane.pb`` and on the same
    clock as the host's spans.  ``name`` is one of :data:`SCOPES`; scopes
    nest, and a reader takes the innermost (``chip_bench/scopes.py``).  It
    is ``jax.named_scope`` and so metadata alone: it exists while a program
    is traced, adds no operation to it, and costs a compiled step
    nothing."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; timeline.SCOPES has "
                         f"{', '.join(SCOPES)}")
    import jax

    return jax.named_scope("hvd." + name)


def program_call(fn, *args):
    """Call a jitted program the framework owns inside a ``program_call``
    phase named for it.  ``count`` grows by the number of arrays the
    program returned, counted outside the timed call: the host's cost of a
    program is mostly its output buffers (``PERF.md`` section 5)."""
    from jax.tree_util import tree_leaves

    with phase("program_call", program=getattr(fn, "__name__", None)) as span:
        span.record = False
        out = fn(*args)
    phase_stats.add("program_call", span.seconds, n=len(tree_leaves(out)))
    return out


def name_os_thread() -> None:
    """Give the calling thread's OS thread its Python name (cut to the
    kernel's 15 characters): the jax profiler labels a host line with the
    OS name, which for a ``threading.Thread`` is the interpreter's."""
    try:
        import ctypes

        ctypes.CDLL(None).prctl(
            15, threading.current_thread().name.encode()[:15], 0, 0, 0)
    except (OSError, AttributeError):
        pass  # not Linux: the line keeps the interpreter's name


class CounterStats:
    """Monotonic event counters for the host data plane.

    The companion to :class:`PhaseStats` for quantities that are counts,
    not durations:

    - ``bytes_on_wire``: DATA payload bytes the TCP transport actually
      framed (sender side) or delivered (receiver side).  Each data frame
      is counted once per endpoint, so a process's number is its own
      traffic; control frames (coordinated abort) are excluded on both
      sides — they are teardown traffic, and counting them on only one
      side would break sender/receiver symmetry.
    - ``heap_copies``: payload materializations in the host data plane
      (``backend/cpu_ring.py`` / ``backend/adasum.py``) — every site that
      still copies tensor bytes onto the heap (fuse staging, unfuse
      ``copy=True``, output assembly) increments it.  The zero-copy
      invariant the test suite asserts: a steady-state ring *step*
      contributes **zero** (reduction reads staged segments in place;
      nothing is ever ``tobytes()``'d or ``frombuffer``-copied).

    Cheap enough to leave always-on (one dict update under a lock per
    event; the transport batches per frame, not per syscall).  Registered
    as a metrics-registry view (``wire_*_total``).

    ``seed`` names are present at 0 from construction (and after
    ``reset``): a counter family that scrapes/dashboards depend on must
    not vanish just because nothing incremented it — under
    ``HOROVOD_TRANSPORT=auto`` on one host, ALL data frames ride shm and
    ``bytes_on_wire`` legitimately never ticks."""

    def __init__(self, seed=()):
        self._lock = threading.Lock()
        self._seed = tuple(seed)
        self._counts: Dict[str, int] = {name: 0 for name in self._seed}

    def add(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def get(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._counts = {name: 0 for name in self._seed}


#: Process-global data-plane counters (bytes_on_wire, heap_copies);
#: surfaced by the benches' ``--profile`` output next to ``phase_stats``.
wire_stats = CounterStats(seed=("bytes_on_wire", "heap_copies"))


# -- registry views: fold the pre-existing accumulators into every
#    metrics snapshot (docs/observability.md) -------------------------------


def _phase_stats_view() -> dict:
    counters: Dict[str, float] = {}
    for phase, d in phase_stats.snapshot().items():
        counters[metrics.flat("phase_seconds_total", phase=phase)] = \
            d["total_ms"] / 1e3
        counters[metrics.flat("phase_ops_total", phase=phase)] = d["count"]
    return {"counters": counters}


def _wire_stats_view() -> dict:
    return {"counters": {
        f"wire_{name}_total": value
        for name, value in wire_stats.snapshot().items()}}


metrics.registry.register_view("phase_stats", _phase_stats_view)
metrics.registry.register_view("wire_stats", _wire_stats_view)
