"""Control-plane wire messages: Request / Response and their lists.

Role of the reference's ``horovod/common/message.h:48-217`` +
``wire/message.fbs``: every rank describes each tensor it wants to reduce
with a ``Request`` (name, op, dtype, shape, root rank, pre/post scale);
the coordinator answers with fused ``Response``s naming the tensors that are
globally ready.  The reference serializes with FlatBuffers; we use a
hand-rolled length-prefixed binary format (little-endian, fixed-width struct
fields) that is deliberately trivial to reimplement in C++ for the native
controller — no schema compiler needed, and decode is allocation-light.

DataType covers the TPU-relevant set (bfloat16 is first-class; the reference
only knows fp16 — ``message.h:20-33``).
"""

from __future__ import annotations

import enum
import struct
from dataclasses import dataclass, field
from typing import List, Sequence

import numpy as np

from ..common.exceptions import TruncatedFrameError

WIRE_MAGIC = 0x48564454  # "HVDT"
MASK_MAGIC = 0x4B53414D  # "MASK" — steady-state fast-path frame
HOST_MASK_MAGIC = 0x4B534D48  # "HMSK" — fan-in aggregated mask frame
ABORT_MAGIC = 0x54524241  # "ABRT" — coordinated-abort control frame

#: AbortFrame.reason budget (bytes, UTF-8): an abort carrying a giant
#: traceback must not bloat the control frame every surviving link relays.
MAX_ABORT_REASON_BYTES = 512
_TRUNCATION_MARK = "…[truncated]"


class DataType(enum.IntEnum):
    UINT8 = 0
    INT8 = 1
    UINT16 = 2
    INT16 = 3
    INT32 = 4
    INT64 = 5
    FLOAT16 = 6
    FLOAT32 = 7
    FLOAT64 = 8
    BOOL = 9
    BFLOAT16 = 10

    @property
    def itemsize(self) -> int:
        return _ITEMSIZE[self]

    def to_numpy(self) -> np.dtype:
        return _TO_NUMPY[self]

    @staticmethod
    def from_numpy(dtype) -> "DataType":
        key = np.dtype(dtype).name
        try:
            return _FROM_NUMPY[key]
        except KeyError:
            raise ValueError(f"unsupported dtype {dtype!r}") from None


def _bfloat16_dtype():
    try:
        import ml_dtypes  # jax's dtype extension package, always present with jax

        return np.dtype(ml_dtypes.bfloat16)
    except ImportError:  # pragma: no cover
        return np.dtype(np.uint16)  # raw-bits fallback


_ITEMSIZE = {
    DataType.UINT8: 1, DataType.INT8: 1, DataType.UINT16: 2, DataType.INT16: 2,
    DataType.INT32: 4, DataType.INT64: 8, DataType.FLOAT16: 2, DataType.FLOAT32: 4,
    DataType.FLOAT64: 8, DataType.BOOL: 1, DataType.BFLOAT16: 2,
}

_TO_NUMPY = {
    DataType.UINT8: np.dtype(np.uint8), DataType.INT8: np.dtype(np.int8),
    DataType.UINT16: np.dtype(np.uint16), DataType.INT16: np.dtype(np.int16),
    DataType.INT32: np.dtype(np.int32), DataType.INT64: np.dtype(np.int64),
    DataType.FLOAT16: np.dtype(np.float16), DataType.FLOAT32: np.dtype(np.float32),
    DataType.FLOAT64: np.dtype(np.float64), DataType.BOOL: np.dtype(np.bool_),
    DataType.BFLOAT16: _bfloat16_dtype(),
}

_FROM_NUMPY = {
    "uint8": DataType.UINT8, "int8": DataType.INT8, "uint16": DataType.UINT16,
    "int16": DataType.INT16, "int32": DataType.INT32, "int64": DataType.INT64,
    "float16": DataType.FLOAT16, "float32": DataType.FLOAT32,
    "float64": DataType.FLOAT64, "bool": DataType.BOOL, "bfloat16": DataType.BFLOAT16,
}


class RequestType(enum.IntEnum):
    """Reference ``message.h:51`` (ALLREDUCE/ALLGATHER/BROADCAST/JOIN/ADASUM/
    ALLTOALL); BARRIER is our addition for the elastic/commit path."""

    ALLREDUCE = 0
    ALLGATHER = 1
    BROADCAST = 2
    JOIN = 3
    ADASUM = 4
    ALLTOALL = 5
    BARRIER = 6


class ResponseType(enum.IntEnum):
    ALLREDUCE = 0
    ALLGATHER = 1
    BROADCAST = 2
    JOIN = 3
    ADASUM = 4
    ALLTOALL = 5
    BARRIER = 6
    ERROR = 7


# ---------------------------------------------------------------------------
# binary writer/reader helpers
# ---------------------------------------------------------------------------

class Writer:
    __slots__ = ("buf",)

    def __init__(self):
        self.buf = bytearray()

    def u8(self, v: int): self.buf += struct.pack("<B", v)
    def u32(self, v: int): self.buf += struct.pack("<I", v)
    def i32(self, v: int): self.buf += struct.pack("<i", v)
    def i64(self, v: int): self.buf += struct.pack("<q", v)
    def f64(self, v: float): self.buf += struct.pack("<d", v)

    def string(self, s: str):
        b = s.encode("utf-8")
        self.u32(len(b))
        self.buf += b

    def i64_list(self, xs: Sequence[int]):
        self.u32(len(xs))
        self.buf += struct.pack(f"<{len(xs)}q", *xs)

    def i32_list(self, xs: Sequence[int]):
        self.u32(len(xs))
        self.buf += struct.pack(f"<{len(xs)}i", *xs)

    def str_list(self, xs: Sequence[str]):
        self.u32(len(xs))
        for s in xs:
            self.string(s)

    def getvalue(self) -> bytes:
        return bytes(self.buf)


class Reader:
    """Bounds-checked binary reader.

    Wire input is UNTRUSTED even inside the CRC envelope: a truncated
    application frame (misframed sender, injected ``truncate`` fault)
    passes the transport CRC — it was computed over the short payload —
    and arrives here with length fields pointing past the buffer end.
    Every read therefore checks its bounds and raises typed
    :class:`TruncatedFrameError` instead of leaking a raw
    ``struct.error`` (or, worse, silently slicing short)."""

    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes):
        self.buf = buf
        self.pos = 0

    def _need(self, size: int) -> None:
        if self.pos + size > len(self.buf):
            raise TruncatedFrameError(
                f"frame truncated: need {size} bytes at offset {self.pos} "
                f"but only {len(self.buf) - self.pos} remain "
                f"(buffer is {len(self.buf)} bytes)")

    def _take(self, fmt: str, size: int):
        self._need(size)
        v = struct.unpack_from(fmt, self.buf, self.pos)[0]
        self.pos += size
        return v

    def u8(self) -> int: return self._take("<B", 1)
    def u32(self) -> int: return self._take("<I", 4)
    def i32(self) -> int: return self._take("<i", 4)
    def i64(self) -> int: return self._take("<q", 8)
    def f64(self) -> float: return self._take("<d", 8)

    def bytes_(self, n: int) -> bytes:
        """Exactly ``n`` raw bytes — a short slice would silently
        misparse everything after it."""
        self._need(n)
        out = bytes(self.buf[self.pos:self.pos + n])
        self.pos += n
        return out

    def string(self) -> str:
        n = self.u32()
        return self.bytes_(n).decode("utf-8")

    def i64_list(self) -> List[int]:
        n = self.u32()
        self._need(8 * n)
        out = list(struct.unpack_from(f"<{n}q", self.buf, self.pos))
        self.pos += 8 * n
        return out

    def i32_list(self) -> List[int]:
        n = self.u32()
        self._need(4 * n)
        out = list(struct.unpack_from(f"<{n}i", self.buf, self.pos))
        self.pos += 4 * n
        return out

    def str_list(self) -> List[str]:
        return [self.string() for _ in range(self.u32())]

    def expect_magic(self, expected: int, what: str) -> None:
        """Check the leading u32 wire tag; a mismatch reports got vs
        expected plus a hexdump of the frame head — the diagnostic that
        distinguishes "wrong frame type" from "stream desync" at a
        glance."""
        got = self.u32()
        if got != expected:
            head = self.buf[:16].hex(" ")
            raise ValueError(
                f"bad {what} magic: got 0x{got:08X}, expected "
                f"0x{expected:08X}; first {min(16, len(self.buf))} bytes: "
                f"{head}")


# ---------------------------------------------------------------------------
# messages
# ---------------------------------------------------------------------------

@dataclass
class Request:
    """One rank's declaration that a named tensor is ready.

    Reference ``message.h:48-113``."""

    request_rank: int = 0
    request_type: RequestType = RequestType.ALLREDUCE
    tensor_name: str = ""
    tensor_type: DataType = DataType.FLOAT32
    tensor_shape: List[int] = field(default_factory=list)
    root_rank: int = -1          # broadcast only
    device: int = -1             # -1 = host memory
    group_id: int = -1           # grouped allreduce
    prescale_factor: float = 1.0
    postscale_factor: float = 1.0
    # ALLTOALL send splits travel in-band (the reference distributes them via
    # a separate MPI_Alltoall, ``mpi_controller.cc:212``; in-band is simpler
    # and lets the coordinator validate consistency).
    splits: List[int] = field(default_factory=list)

    def serialize(self, w: Writer) -> None:
        w.u32(self.request_rank)
        w.u8(int(self.request_type))
        w.string(self.tensor_name)
        w.u8(int(self.tensor_type))
        w.i64_list(self.tensor_shape)
        w.i32(self.root_rank)
        w.i32(self.device)
        w.i32(self.group_id)
        w.f64(self.prescale_factor)
        w.f64(self.postscale_factor)
        w.i64_list(self.splits)

    @staticmethod
    def deserialize(r: Reader) -> "Request":
        return Request(
            request_rank=r.u32(),
            request_type=RequestType(r.u8()),
            tensor_name=r.string(),
            tensor_type=DataType(r.u8()),
            tensor_shape=r.i64_list(),
            root_rank=r.i32(),
            device=r.i32(),
            group_id=r.i32(),
            prescale_factor=r.f64(),
            postscale_factor=r.f64(),
            splits=r.i64_list(),
        )

    @property
    def num_elements(self) -> int:
        n = 1
        for d in self.tensor_shape:
            n *= d
        return n

    @property
    def nbytes(self) -> int:
        return self.num_elements * self.tensor_type.itemsize


@dataclass
class RequestList:
    requests: List[Request] = field(default_factory=list)
    shutdown: bool = False
    # Cache-hit bit positions (response_cache.py): tensors re-announced at
    # 4 bytes instead of a full Request — the steady-state fast path
    # (reference bitvector sync, ``controller.cc:826-851``).
    cache_hits: List[int] = field(default_factory=list)
    # Dense bitmask flavor of the same information (little-endian bytes of
    # a big integer): the coordinator aggregates these with C-speed
    # integer AND/OR instead of per-(rank × tensor) Python loops — the
    # part of the star protocol that must stay O(ranks) per cycle.
    cache_mask: bytes = b""

    def to_bytes(self) -> bytes:
        w = Writer()
        w.u32(WIRE_MAGIC)
        w.u8(1 if self.shutdown else 0)
        w.i32_list(self.cache_hits)
        w.u32(len(self.cache_mask))
        w.buf += self.cache_mask
        w.u32(len(self.requests))
        for req in self.requests:
            req.serialize(w)
        return w.getvalue()

    @staticmethod
    def from_bytes(data: bytes) -> "RequestList":
        r = Reader(data)
        r.expect_magic(WIRE_MAGIC, "request-list")
        shutdown = bool(r.u8())
        cache_hits = r.i32_list()
        mask = r.bytes_(r.u32())
        reqs = [Request.deserialize(r) for _ in range(r.u32())]
        return RequestList(requests=reqs, shutdown=shutdown,
                           cache_hits=cache_hits, cache_mask=mask)


@dataclass
class MaskFrame:
    """Compact steady-state negotiation frame — the zero-round-trip-payload
    cache fast path.

    When every pending tensor on a rank hits its cache mirror, the rank's
    whole cycle contribution is a bitvector; and when that holds on EVERY
    rank, the coordinator's whole verdict is the AND of those bitvectors.
    This frame carries exactly that (plus the shutdown flag) in both
    directions, replacing full ``RequestList``/``ResponseList`` payloads:
    each rank reconstructs the agreed Responses locally from its cached
    request templates (``controller._responses_from_agreed_mask``).  The
    reference's bitvector-allreduce cache sync (``controller.cc:826-851``)
    achieves the same wire shape inside MPI; ours is explicit because the
    frame must be self-describing next to the full-payload flavor (the
    leading magic distinguishes them).
    """

    mask: bytes = b""        # little-endian big-int bitvector
    shutdown: bool = False

    def to_bytes(self) -> bytes:
        w = Writer()
        w.u32(MASK_MAGIC)
        w.u8(1 if self.shutdown else 0)
        w.u32(len(self.mask))
        w.buf += self.mask
        return w.getvalue()

    @staticmethod
    def from_bytes(data: bytes) -> "MaskFrame":
        r = Reader(data)
        r.expect_magic(MASK_MAGIC, "mask-frame")
        shutdown = bool(r.u8())
        return MaskFrame(mask=r.bytes_(r.u32()), shutdown=shutdown)

    @property
    def mask_int(self) -> int:
        return int.from_bytes(self.mask, "little")


def is_mask_frame(data: bytes) -> bool:
    """True when ``data`` is a MaskFrame (vs RequestList/ResponseList)."""
    return len(data) >= 4 and \
        struct.unpack_from("<I", data)[0] == MASK_MAGIC


@dataclass
class HostMaskFrame:
    """One HOST's aggregated steady-state contribution — the negotiation
    fan-in frame (``core/negotiation_fanin.py``).

    Under tree fan-in the host's aggregator sends THIS frame in place of
    the MaskFrames of the colocated ranks whose bitvectors are the same
    this cycle, so coordinator ingress per busy cycle scales with hosts,
    not ranks.  Nothing is combined: a worker announces a cached
    tensor's bit once and the coordinator keeps it pending rank by rank,
    so a frame may only stand for ranks that said exactly this
    (``fold_host``), the aggregator accumulates nothing, and its death
    loses at most the in-flight cycle, which the lockstep abort already
    discards on every path.  ``covered`` names those ranks (ranks that
    sent another mask get a frame of their own, ranks that sent a full
    RequestList ride the bundle unfolded); the coordinator expands the
    frame to one identical pending-mask contribution per covered rank.
    ``shutdown`` is the OR of the covered ranks' flags, matching the
    coordinator's own OR-fold over per-rank frames.
    """

    covered: List[int] = field(default_factory=list)
    mask: bytes = b""        # little-endian big-int bitvector
    shutdown: bool = False

    def to_bytes(self) -> bytes:
        w = Writer()
        w.u32(HOST_MASK_MAGIC)
        w.u8(1 if self.shutdown else 0)
        w.i32_list(self.covered)
        w.u32(len(self.mask))
        w.buf += self.mask
        return w.getvalue()

    @staticmethod
    def from_bytes(data: bytes) -> "HostMaskFrame":
        r = Reader(data)
        r.expect_magic(HOST_MASK_MAGIC, "host-mask-frame")
        shutdown = bool(r.u8())
        covered = r.i32_list()
        return HostMaskFrame(covered=covered, mask=r.bytes_(r.u32()),
                             shutdown=shutdown)

    @property
    def mask_int(self) -> int:
        return int.from_bytes(self.mask, "little")


def is_host_mask_frame(data: bytes) -> bool:
    return len(data) >= 4 and \
        struct.unpack_from("<I", data)[0] == HOST_MASK_MAGIC


@dataclass
class AbortFrame:
    """Coordinated-abort broadcast: the detecting rank tells every
    surviving peer that the job is dead and why.

    Rides the transport's *control-frame* channel (``transport/tcp.py``
    marks the length header), so it can never be confused with in-flight
    negotiation or tensor payload bytes.  Carries the elastic epoch: a
    late abort from a pre-reset incarnation of the job must be discarded,
    not kill the freshly re-rendezvoused world.
    """

    epoch: int = 0
    origin_rank: int = 0
    reason: str = ""

    def __post_init__(self):
        # Bound the reason AT CONSTRUCTION (not serialization): the cap
        # must hold everywhere the frame travels — relays, logs, the mesh
        # abort flag — not just on this rank's wire.  A multi-KB
        # traceback in every control frame would bloat exactly the path
        # that must stay small to deliver promptly during teardown.
        raw = self.reason.encode("utf-8")
        if len(raw) > MAX_ABORT_REASON_BYTES:
            mark = _TRUNCATION_MARK.encode("utf-8")
            keep = raw[:MAX_ABORT_REASON_BYTES - len(mark)]
            # errors="ignore" drops a multi-byte sequence split by the
            # cut instead of raising (or keeping a mojibake tail).
            self.reason = keep.decode("utf-8", "ignore") + _TRUNCATION_MARK

    def to_bytes(self) -> bytes:
        w = Writer()
        w.u32(ABORT_MAGIC)
        w.i64(self.epoch)
        w.i32(self.origin_rank)
        w.string(self.reason)
        return w.getvalue()

    @staticmethod
    def from_bytes(data: bytes) -> "AbortFrame":
        r = Reader(data)
        r.expect_magic(ABORT_MAGIC, "abort-frame")
        return AbortFrame(epoch=r.i64(), origin_rank=r.i32(),
                          reason=r.string())


def is_abort_frame(data: bytes) -> bool:
    return len(data) >= 4 and \
        struct.unpack_from("<I", data)[0] == ABORT_MAGIC


@dataclass
class Response:
    """Coordinator verdict for one (possibly fused) set of tensors.

    Reference ``message.h:145-217``.  ``tensor_sizes`` carries per-rank first
    dimensions for ALLGATHER and flattened per-rank recv splits for ALLTOALL
    (reference packs both into the same field)."""

    response_type: ResponseType = ResponseType.ALLREDUCE
    tensor_names: List[str] = field(default_factory=list)
    tensor_type: DataType = DataType.FLOAT32
    tensor_sizes: List[int] = field(default_factory=list)
    error_message: str = ""
    devices: List[int] = field(default_factory=list)
    prescale_factor: float = 1.0
    postscale_factor: float = 1.0
    last_joined_rank: int = -1

    def serialize(self, w: Writer) -> None:
        w.u8(int(self.response_type))
        w.str_list(self.tensor_names)
        w.u8(int(self.tensor_type))
        w.i64_list(self.tensor_sizes)
        w.string(self.error_message)
        w.i32_list(self.devices)
        w.f64(self.prescale_factor)
        w.f64(self.postscale_factor)
        w.i32(self.last_joined_rank)

    @staticmethod
    def deserialize(r: Reader) -> "Response":
        return Response(
            response_type=ResponseType(r.u8()),
            tensor_names=r.str_list(),
            tensor_type=DataType(r.u8()),
            tensor_sizes=r.i64_list(),
            error_message=r.string(),
            devices=r.i32_list(),
            prescale_factor=r.f64(),
            postscale_factor=r.f64(),
            last_joined_rank=r.i32(),
        )


@dataclass
class ResponseList:
    responses: List[Response] = field(default_factory=list)
    shutdown: bool = False
    # Coordinator-authoritative cache maintenance (response_cache.py):
    # (bit, request-template) assignments workers mirror, and evictions.
    cache_assignments: List[tuple] = field(default_factory=list)
    evicted_bits: List[int] = field(default_factory=list)
    # Autotuned runtime parameters, broadcast when they change (reference
    # ``SynchronizeParameters``, ``controller.cc:43-57``): (fusion_threshold
    # bytes, cycle_time_ms) or None.
    tuned_params: "tuple | None" = None

    def to_bytes(self) -> bytes:
        w = Writer()
        w.u32(WIRE_MAGIC)
        w.u8(1 if self.shutdown else 0)
        w.i32_list(self.evicted_bits)
        w.u32(len(self.cache_assignments))
        for bit, template in self.cache_assignments:
            w.i32(bit)
            template.serialize(w)
        if self.tuned_params is None:
            w.u8(0)
        else:
            w.u8(1)
            w.i64(int(self.tuned_params[0]))
            w.f64(float(self.tuned_params[1]))
        w.u32(len(self.responses))
        for resp in self.responses:
            resp.serialize(w)
        return w.getvalue()

    @staticmethod
    def from_bytes(data: bytes) -> "ResponseList":
        r = Reader(data)
        r.expect_magic(WIRE_MAGIC, "response-list")
        shutdown = bool(r.u8())
        evicted = r.i32_list()
        assignments = []
        for _ in range(r.u32()):
            bit = r.i32()
            assignments.append((bit, Request.deserialize(r)))
        tuned = None
        if r.u8():
            tuned = (r.i64(), r.f64())
        resps = [Response.deserialize(r) for _ in range(r.u32())]
        return ResponseList(responses=resps, shutdown=shutdown,
                            cache_assignments=assignments,
                            evicted_bits=evicted, tuned_params=tuned)
