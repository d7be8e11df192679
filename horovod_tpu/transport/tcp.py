"""Full-mesh TCP transport — the self-contained Gloo role.

The reference leans on libgloo for its MPI-free path: every rank builds TCP
connections to every other rank through a rendezvous store
(``gloo_context.cc:63-84`` ``connectFullMesh``) and the controller/data ops
run over those sockets.  We are MPI- and gloo-free by design (north star), so
this module is that fabric: a framed, thread-safe, full-mesh TCP transport
bootstrapped through a ``Store``.

Framing: ``<Q len|flags>[<I crc32(payload)>]`` + payload — an 8-byte
little-endian length word whose top bits carry the frame flags, followed
by a 4-byte CRC32 of the payload when ``HOROVOD_WIRE_CRC`` is on (the
default) and the frame is not digest-deferred, then the payload bytes.
Flag bits: bit 63 marks control frames (``_CTRL_FLAG``); bit 62 marks a
digest-DEFERRED data frame (``_DEFER_FLAG``) — no inline CRC field
follows, the frame is covered instead by the ring step's chained shadow
digest (``transport/digest.py``), closed out by a digest-check frame; bit
61 marks that digest-check frame itself (``_DIGEST_FLAG``, always
inline-CRC'd — it IS the verification); bits 56-58 carry the wire dtype
code (``_WIRE_DTYPE_MASK``) stamped by cast-on-the-wire compression
(``backend/compression.py``), so peers that disagree on
``HOROVOD_WIRE_COMPRESSION`` poison the stream loudly instead of
mis-decoding bytes.  A pre-flags peer masks only bit 63, reads any
flagged frame as an absurd length, and aborts on the frame-size cap —
mixed-version meshes fail loudly by construction.  When
``HOROVOD_WIRE_CRC`` is off the CRC field is absent from every frame.
Connection establishment is deterministic to avoid crossed sockets: every
rank listens; rank *i* dials every rank *j < i* and introduces itself
with a hello (magic + rank + target), which the listener answers and the
dialer confirms.

Zero-copy data plane: ``send`` accepts any C-contiguous bytes-like object
(a memoryview over a numpy slice included) and writes ``[header, payload]``
vectored, never concatenating; ``recv_into`` lands a frame's payload
directly in a caller-provided buffer, computing the wire CRC incrementally
over the destination view as bytes arrive — no intermediate heap
materialization on either side (docs/data_plane.md).

Only the background/controller thread performs transport I/O in steady state,
but sends and recvs are independently locked per peer so the elastic
notification path can interleave safely.
"""

from __future__ import annotations

import queue
import select
import socket
import struct
import threading
import time
import zlib
from typing import Dict, List, Optional, Tuple

from . import digest as digest_mod
# Frame-header contract — bits, structs, and size cap — lives in the
# frame_bits registry (HVD008: defined once, imported everywhere).
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
from ..common import faults
from ..common.exceptions import (
    CoordinatedAbortError,
    FrameCorruptError,
    HorovodInternalError,
    PeerGoneError,
)
from ..common.logging_util import get_logger
from ..core import flight_recorder, metrics
from ..core.timeline import wire_stats
from .store import Store

log = get_logger("horovod_tpu.transport.tcp")

_HELLO = struct.pack("<I", 0x48564D54)  # "HVMT"
# What either side gives the other to answer a hello.  A dialer that has
# waited this long closes the socket and dials again.
_HELLO_TIMEOUT_SECS = 5.0
# How often a blocked recv wakes to check the mesh-wide abort flag and its
# progress deadline.  Bounds abort-propagation latency for threads blocked
# on a DIFFERENT peer's socket than the one the abort arrived on.
_ABORT_POLL_SECS = 0.25


class _ProgressStall(Exception):
    """Internal: a recv made no byte progress within the deadline."""


def _wait_ready(sock: socket.socket, timeout: float, write: bool) -> bool:
    """poll(2)-based readiness wait: select(2) breaks past fd 1024 and
    large meshes hold one socket per peer."""
    fd = sock.fileno()
    if fd < 0:
        # Closed under us (mesh teardown racing a blocked op): surface
        # as the socket error it is, not a ValueError from poll/select.
        raise OSError("socket closed")
    if hasattr(select, "poll"):
        p = select.poll()
        p.register(fd, select.POLLOUT if write else select.POLLIN)
        return bool(p.poll(timeout * 1000.0))
    sets = ([], [sock], []) if write else ([sock], [], [])
    r, w, _ = select.select(*sets, timeout)
    return bool(w if write else r)


def _wait_readable(sock: socket.socket, timeout: float) -> bool:
    return _wait_ready(sock, timeout, write=False)


def _wait_writable(sock: socket.socket, timeout: float) -> bool:
    return _wait_ready(sock, timeout, write=True)


def _as_byte_view(data) -> memoryview:
    """Flat byte view over any C-contiguous bytes-like object — bytes,
    bytearray, memoryview, or a numpy array/slice — without copying.
    Raises for non-contiguous input: the caller holds a strided view it
    must materialize itself (silently copying here would defeat the
    zero-copy contract and hide the cost)."""
    view = data if isinstance(data, memoryview) else memoryview(data)
    if view.format != "B" or view.ndim != 1:
        view = view.cast("B")
    return view


def _as_writable_byte_view(data) -> memoryview:
    view = _as_byte_view(data)
    if view.readonly:
        raise ValueError("recv_into needs a writable destination buffer")
    return view


class PendingRecv:
    """Handle for an in-flight ``recv_into_async``: ``wait()`` blocks until
    the frame landed and returns its payload size, re-raising any
    transport error (PeerGoneError, CoordinatedAbortError,
    FrameCorruptError) on the caller's thread."""

    __slots__ = ("_done", "_box")

    def __init__(self, done: threading.Event, box: List):
        self._done = done
        self._box = box

    def wait(self) -> int:
        self._done.wait()
        if self._box[1] is not None:
            raise self._box[1]
        return self._box[0]


class AbortState:
    """Mesh-wide abort flag: ``(epoch, origin_rank, reason)`` once any
    link delivered (or this rank broadcast) a coordinated abort.

    A tiny holder rather than a bare attribute so SEVERAL meshes can
    share one flag: under a ``LinkMesh`` (transport/select.py) the TCP
    and shm fabrics are two halves of the same failure domain — a thread
    blocked on an shm ring must observe an abort that arrived on a TCP
    socket within one poll quantum, and vice versa."""

    __slots__ = ("value",)

    def __init__(self):
        self.value: Optional[Tuple[int, int, str]] = None


class _Peer:
    __slots__ = ("sock", "send_lock", "recv_lock", "dead", "ever_received",
                 "frames_in")

    def __init__(self, sock: socket.socket):
        self.sock = sock
        # Registered peers run NON-BLOCKING: both directions are driven by
        # the poll loops in _send_bounded/_recv_bounded.  A blocking
        # send(2) queues its ENTIRE buffer before returning, so no
        # poll-first scheme can bound it once a live-but-wedged peer stops
        # reading; non-blocking send returns partial/EAGAIN and the loop
        # keeps the progress deadline and abort flag in charge.
        sock.setblocking(False)
        self.send_lock = threading.Lock()
        self.recv_lock = threading.Lock()
        # First send/recv failure marks the peer dead (reason string);
        # every later call fails fast with PeerGoneError instead of
        # re-blocking on the broken socket.
        self.dead: Optional[str] = None
        # The progress deadline ARMS on the first bytes ever received from
        # this peer: post-handshake bring-up staggers legitimately (slow
        # XLA init, store waits) and is covered by the startup timeout —
        # "gone" is a judgment about a peer that WAS participating and
        # stopped.
        self.ever_received = False
        # Completed frames received from this peer — diagnostic context
        # for FrameCorruptError ("which frame in the stream went bad").
        self.frames_in = 0


class TcpMesh:
    """Framed full-mesh TCP fabric between ``size`` ranks."""

    def __init__(self, rank: int, size: int, store: Store,
                 scope: str = "tcp", bind_addr: str = "0.0.0.0",
                 advertise_addr: Optional[str] = None,
                 timeout: float = 60.0,
                 epoch: Optional[int] = None,
                 progress_deadline: Optional[float] = None,
                 abort_state: Optional[AbortState] = None):
        from ..common import env as env_mod

        self.rank = rank
        self.size = size
        self._peers: Dict[int, _Peer] = {}
        self._closed = False
        self._sr_thread: Optional[threading.Thread] = None
        self._sr_queue: Optional[queue.SimpleQueue] = None
        # Elastic epoch stamped into abort frames; aborts from older epochs
        # are discarded on receipt (a pre-reset straggler must not kill the
        # re-rendezvoused world).
        self.epoch = env_mod.get_epoch() if epoch is None else epoch
        # Recv progress deadline (seconds; 0 disables): any bytes received
        # reset it, so slow-but-alive peers never trip it — only a peer
        # that stops sending entirely.
        self.progress_deadline = env_mod.get_float(
            env_mod.HOROVOD_TCP_PROGRESS_DEADLINE,
            env_mod.DEFAULT_TCP_PROGRESS_DEADLINE_SECS) \
            if progress_deadline is None else progress_deadline
        # Wire CRC (default on): sender stamps crc32(payload) into the
        # frame header, receiver verifies before handing bytes up.  All
        # ranks must agree (env-propagated like every other knob).
        self.wire_crc = env_mod.get_bool(env_mod.HOROVOD_WIRE_CRC, True)
        # Shadow (deferred) digesting for ring data frames (default on,
        # effective only with the CRC on): segment frames skip the inline
        # CRC field; each endpoint chains per-frame digests off the
        # serial path and a digest-check frame closes the step.  "0"
        # restores strict per-frame inline CRC.  All ranks must agree.
        self.crc_shadow = env_mod.get_bool(
            env_mod.HOROVOD_WIRE_CRC_SHADOW, True)
        self.digest_algo = digest_mod.algo_from_name(
            env_mod.get_str(env_mod.HOROVOD_WIRE_DIGEST, "fold64")
            or "fold64")
        # Mesh-wide abort state: (epoch, origin_rank, reason) once any link
        # delivered (or this rank broadcast) a coordinated abort.  Blocked
        # recvs observe it within _ABORT_POLL_SECS regardless of which
        # socket they wait on.  The holder may be SHARED with a sibling
        # shm mesh under a LinkMesh (see AbortState).
        self._abort_state = abort_state if abort_state is not None \
            else AbortState()
        # Set by LinkMesh: an abort detected HERE must fan out over every
        # transport, not just this mesh's links.
        self.abort_relay = None
        if size == 1:
            self._listener = None
            return

        from ..common import secret as secret_mod

        self._secret = secret_mod.job_secret()
        self._listener = socket.create_server((bind_addr, 0), backlog=size)
        port = self._listener.getsockname()[1]
        if advertise_addr is not None:
            candidates = [advertise_addr]
        else:
            # NIC negotiation, dial-side (reference role:
            # driver_service.py:162-194 intersects routable interfaces by
            # ssh-probing every host; here every rank advertises ALL its
            # candidate addresses and dialers try them in order — same
            # outcome on multi-homed hosts, no ssh dance).
            candidates = candidate_advertise_addrs()
        # Accept connections from higher ranks while dialing lower ranks.
        # The acceptor runs before the address is published: a dialer
        # reads the address the moment the store has it, which can be
        # seconds before a busy store's answer lets this thread go on.
        accept_err: List[BaseException] = []
        self._accept_done = threading.Event()
        n_expected = size - 1 - rank
        acceptor = threading.Thread(
            target=self._accept_loop, args=(n_expected, accept_err, timeout),
            name=f"hvd-tcp-accept-r{rank}", daemon=True)
        acceptor.start()
        store.set(scope, str(rank),
                  ",".join(f"{a}:{port}" for a in candidates).encode())

        lower = [str(j) for j in range(rank)]
        addrs = store.wait(scope, lower, timeout=timeout) if lower else {}
        for j in range(rank):
            endpoints = []
            for spec in addrs[str(j)].decode().split(","):
                host, p = spec.rsplit(":", 1)
                endpoints.append((host, int(p)))
            self._peers[j] = _Peer(
                self._dial_peer(j, endpoints, timeout))

        # The acceptor thread stays alive past the quota to service late
        # dial retries (see _accept_loop), so wait on its quota event, not
        # the thread itself.
        self._accept_done.wait(timeout=timeout)
        if accept_err:
            raise HorovodInternalError(f"tcp mesh accept failed: {accept_err[0]}")
        if len(self._peers) != size - 1:
            raise HorovodInternalError(
                f"tcp mesh incomplete: have {len(self._peers)}/{size - 1} peers")

    # -- handshake ----------------------------------------------------------
    #
    # dialer:   HELLO + my_rank + target_rank [+ HMAC]  →
    # acceptor:                    ←  HELLO + its_rank + dialer_rank [+ HMAC]
    # dialer:   HELLO (the bare magic: "I kept this socket")  →
    #
    # The acceptor registers a connection only when the third message has
    # arrived.  A dialer gives a handshake 5 s and then dials again; an
    # acceptor that was starved for longer found the abandoned socket
    # first in its queue, answered it and registered it, closed the
    # dialer's second attempt as a duplicate, and left both ranks holding a
    # dead socket: the job died at its first frame with "peer closed
    # connection".  An abandoned socket now ends in EOF where the third
    # message is read, and the attempt the dialer kept is the one that
    # registers.
    #
    # Carrying the intended TARGET lets the acceptor refuse (without
    # registering) a connection that reached the wrong machine — with
    # multi-addr advertisement a dial can land on another rank's listener,
    # and registering it would leave that rank holding a socket its dialer
    # is about to close.  The HMAC (when HOROVOD_SECRET_KEY is set) keeps
    # arbitrary LAN peers out of the data fabric (reference
    # network.py:50-85 role).

    def _hello_blob(self, my_rank: int, target_rank: int) -> bytes:
        blob = _HELLO + struct.pack("<II", my_rank, target_rank)
        if self._secret is not None:
            from ..common import secret as secret_mod

            blob += secret_mod.sign_blob(self._secret, blob)
        return blob

    def _check_hello(self, data: bytes) -> tuple:
        """Validate magic+sig; returns (peer_rank, intended_target)."""
        if data[:4] != _HELLO:
            raise HorovodInternalError("bad tcp mesh hello")
        if self._secret is not None:
            from ..common import secret as secret_mod

            if not secret_mod.verify_blob(self._secret, data[:12], data[12:]):
                raise HorovodInternalError("tcp mesh hello failed HMAC check")
        return struct.unpack("<II", data[4:12])

    def _hello_len(self) -> int:
        return 12 + (32 if self._secret is not None else 0)

    def _dial_peer(self, target: int, endpoints: List,
                   timeout: float) -> socket.socket:
        """Connect to one peer, racing the TCP connects to ALL advertised
        candidates concurrently (reference driver probe-and-intersect
        role, ``driver/driver_service.py:162-194``): on a multi-homed host
        a dead first candidate costs nothing — a reachable one wins the
        race instead of waiting out the dead one's timeout serially.

        Only the CONNECT races; the hello handshake runs serially on one
        socket at a time.  Losing sockets close before any hello, so the
        acceptor sees EOF and drops them without registering — racing full
        handshakes could leave dialer and acceptor registered on
        *different* winners for the same rank pair."""
        import queue as queue_mod

        deadline = time.monotonic() + timeout
        last: List[Optional[Exception]] = [None]
        # Endpoints with a connect attempt still in flight: each 50 ms retry
        # must NOT stack a fresh 5 s-timeout thread on a dead candidate the
        # previous retry is still waiting out (threads/fds would accumulate
        # linearly in retry count otherwise).
        inflight: set = set()
        inflight_lock = threading.Lock()

        def connect_all() -> List[socket.socket]:
            if len(endpoints) == 1:
                host, port = endpoints[0]
                try:
                    return [socket.create_connection(
                        (host, port), timeout=min(5.0, timeout))]
                except OSError as e:
                    last[0] = e
                    return []
            results: "queue_mod.Queue" = queue_mod.Queue()

            def conn(host, port):
                try:
                    results.put(socket.create_connection(
                        (host, port), timeout=min(5.0, timeout)))
                except OSError as e:
                    last[0] = e
                    results.put(None)
                finally:
                    with inflight_lock:
                        inflight.discard((host, port))

            spawned = 0
            for host, port in endpoints:
                with inflight_lock:
                    if (host, port) in inflight:
                        continue
                    inflight.add((host, port))
                threading.Thread(target=conn, args=(host, port),
                                 name=f"hvd-tcp-dial-r{target}",
                                 daemon=True).start()
                spawned += 1
            socks = []
            received = 0
            for _ in range(spawned):
                try:
                    s = results.get(
                        timeout=max(0.1, deadline - time.monotonic()))
                except queue_mod.Empty:
                    break
                received += 1
                if s is not None:
                    socks.append(s)
                elif socks:
                    break  # have a candidate; don't wait for stragglers
            if received < spawned:
                # Straggler threads will still deposit sockets after we
                # return — reap and close them so they don't leak until
                # queue GC (ADVICE r3).
                remaining = spawned - received

                def reap():
                    for _ in range(remaining):
                        try:
                            s = results.get(timeout=6.0)
                        except queue_mod.Empty:
                            return
                        if s is not None:
                            s.close()

                threading.Thread(target=reap, name="hvd-tcp-dial-reap",
                                 daemon=True).start()
            return socks

        while time.monotonic() < deadline:
            socks = connect_all()
            winner: Optional[socket.socket] = None
            for i, sock in enumerate(socks):
                if winner is not None:
                    sock.close()  # pre-hello close: acceptor drops on EOF
                    continue
                try:
                    winner = self._handshake(sock, target)
                except (OSError, HorovodInternalError) as e:
                    last[0] = e
                    sock.close()
            if winner is not None:
                return winner
            time.sleep(0.05)
        raise HorovodInternalError(
            f"could not connect to rank {target} at {endpoints}: {last[0]}")

    def _handshake(self, sock: socket.socket, target: int) -> socket.socket:
        _configure(sock)
        # Bounded handshake: an endpoint that accepts but never answers
        # must fall through to the next candidate, not hang the mesh
        # (symmetric with the accept side).
        sock.settimeout(_HELLO_TIMEOUT_SECS)
        sock.sendall(self._hello_blob(self.rank, target))
        got, _ = self._check_hello(_recv_exact(sock, self._hello_len()))
        if got != target:
            raise HorovodInternalError(f"peer answered as rank {got}")
        sock.sendall(_HELLO)
        sock.settimeout(None)
        return sock

    def _accept_one(self, sock: socket.socket) -> bool:
        """Handshake one inbound connection; True when a NEW peer was
        registered (duplicates and misroutes are answered, then closed)."""
        try:
            _configure(sock)
            sock.settimeout(_HELLO_TIMEOUT_SECS)
            peer_rank, intended = self._check_hello(
                _recv_exact(sock, self._hello_len()))
            # Always answer with our identity so a misrouted dialer
            # learns who it reached and falls through to its next
            # candidate; only register connections MEANT for us.
            sock.sendall(self._hello_blob(self.rank, peer_rank))
            if intended != self.rank:
                sock.close()
                return False
            if _recv_exact(sock, len(_HELLO)) != _HELLO:
                raise HorovodInternalError("bad tcp mesh hello")
            sock.settimeout(None)
        except (OSError, HorovodInternalError):
            # Unauthenticated or malformed connection: drop it
            # without counting toward the expected peer set.
            sock.close()
            return False
        if peer_rank not in self._peers:
            self._peers[peer_rank] = _Peer(sock)
            return True
        sock.close()
        return False

    def _accept_loop(self, n_expected: int, err: List[BaseException],
                     timeout: float) -> None:
        try:
            deadline = time.monotonic() + timeout
            registered = 0
            while registered < n_expected:
                self._listener.settimeout(
                    max(0.1, deadline - time.monotonic()))
                sock, _ = self._listener.accept()
                if self._accept_one(sock):
                    registered += 1
            self._accept_done.set()
        except BaseException as e:  # surfaced by constructor
            err.append(e)
            # Wake the constructor NOW: it waits on the event (the thread
            # outlives the quota), and an accept failure must fail
            # bring-up immediately, not after the full startup timeout.
            self._accept_done.set()
            return
        # Quota filled — keep answering until close.  A connection that
        # arrives now is a stray (a candidate address that lost the
        # dialer's connect race, a dial that reached the wrong machine):
        # with nobody accepting it would sit in the listen backlog.  It
        # cannot be a retry of a handshake that was counted: only a
        # socket its dialer kept is counted (the third message above).
        while not self._closed:
            try:
                self._listener.settimeout(1.0)
                sock, _ = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return  # listener closed (mesh teardown)
            self._accept_one(sock)

    # -- framed messaging ---------------------------------------------------

    @staticmethod
    def _crc32_timed(payload) -> int:
        """crc32 with its cost accounted to ``crc_verify_seconds_total``
        — ROADMAP item 2 (CRC off the hot path) needs the absolute cost
        measurable on live jobs, not only in bench sweeps.  The two clock
        reads are skipped entirely when metrics are off."""
        if not metrics.ENABLED:
            return zlib.crc32(payload) & 0xFFFFFFFF
        t0 = time.perf_counter()
        crc = zlib.crc32(payload) & 0xFFFFFFFF
        metrics.inc("crc_verify_seconds_total", time.perf_counter() - t0)
        return crc

    @property
    def deferred_digests(self) -> bool:
        """True when ring steps should use the shadow-digest path
        (``HOROVOD_WIRE_CRC`` on and ``HOROVOD_WIRE_CRC_SHADOW`` not
        disabled)."""
        return self.wire_crc and self.crc_shadow

    def deferred_digests_for(self, peer: int) -> bool:
        """Per-LINK form of :attr:`deferred_digests` — the seam the ring
        collectives ask so a mixed-transport mesh (LinkMesh) can answer
        differently per peer.  Both endpoints of a link answer alike
        (each transport's CRC knobs are env-propagated to all ranks), so
        the two directions of one ring step may differ but one link's
        framing never does.  On a plain TcpMesh every link agrees."""
        return self.deferred_digests

    def new_digest(self) -> digest_mod.StreamDigest:
        """Fresh chained digest for one direction of one ring step."""
        return digest_mod.StreamDigest(self.digest_algo)

    @staticmethod
    def _digest_timed(dig: digest_mod.StreamDigest, view) -> None:
        """``StreamDigest.update`` with its cost accounted to
        ``crc_shadow_seconds_total`` — the shadow path's counterpart of
        ``_crc32_timed``, so the deferred-digest cost stays measurable on
        live jobs next to the inline CRC's counter."""
        if not metrics.ENABLED:
            dig.update(view)
            return
        t0 = time.perf_counter()
        dig.update(view)
        metrics.inc("crc_shadow_seconds_total", time.perf_counter() - t0)

    @property
    def _abort(self) -> Optional[Tuple[int, int, str]]:
        return self._abort_state.value

    @_abort.setter
    def _abort(self, value: Optional[Tuple[int, int, str]]) -> None:
        self._abort_state.value = value

    def _check_alive(self, p: _Peer, peer: int) -> None:
        if self._abort is not None:
            raise CoordinatedAbortError(*self._abort)
        if p.dead is not None:
            raise PeerGoneError(peer, p.dead)

    def _mark_dead(self, p: _Peer, reason: str) -> None:
        if p.dead is None:
            p.dead = reason

    def send(self, peer: int, payload,
             digest: Optional[digest_mod.StreamDigest] = None,
             wire_dtype: int = 0, _check_frame: bool = False) -> None:
        """Frame and send one payload — any C-contiguous bytes-like object
        (memoryview over a numpy slice included), never copied: the frame
        header and the payload view go to the kernel as one vectored
        write.

        With ``digest`` (and the mesh CRC on), the frame goes out
        digest-DEFERRED: no inline CRC field — the payload is folded into
        ``digest`` right after the vectored write is handed to the
        kernel (the shadow slot: the fold runs while the bytes are on the
        wire), and the caller closes the step with
        :meth:`send_step_digest`.  ``wire_dtype`` stamps the compression
        dtype code into the header so peers that disagree on
        ``HOROVOD_WIRE_COMPRESSION`` fail loudly on receipt."""
        p = self._peer(peer)
        deferred = digest is not None and self.wire_crc
        with p.send_lock:
            self._check_alive(p, peer)
            try:
                payload = _as_byte_view(payload)
                wire = payload
                if faults.ACTIVE:
                    verdict = faults.inject(
                        "tcp.send", rank=self.rank, peer=peer,
                        payload=payload)
                    if verdict is True:
                        return  # injected frame drop
                    if isinstance(verdict, faults.SendMutation):
                        # truncate: the frame is self-consistent (header
                        # and CRC computed over the SHORT payload) — an
                        # application-level misframe for the parse layer.
                        # corrupt: wire_flips apply AFTER the CRC is
                        # computed — in-flight corruption for the CRC
                        # layer.
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
                    # Digest the LOGICAL payload, not the wire bytes: an
                    # injected corrupt flip mutates only the latter —
                    # exactly the disagreement the peer's chain must
                    # catch at the digest-check frame.
                    self._digest_timed(digest, payload)
                if not _check_frame:
                    # Digest-check frames are integrity metadata, not
                    # data payload — excluded like control frames so the
                    # zero-copy tests' exact byte accounting holds.
                    wire_stats.add("bytes_on_wire", len(payload))
                flight_recorder.record("frame", dir="send", peer=peer,
                                       nbytes=len(payload))
            except _ProgressStall as e:
                self._mark_dead(p, str(e))
                raise PeerGoneError(peer, str(e)) from None
            except OSError as e:
                self._mark_dead(p, f"send to rank {peer} failed: {e}")
                raise PeerGoneError(
                    peer, f"send to rank {peer} failed: {e}") from e

    def _send_bounded(self, p: _Peer, bufs: List[memoryview]) -> None:
        """Vectored ``sendall`` with the same failure-plane waits as the
        recv side: a peer that is alive but has stopped READING (hung
        mid-step) fills the socket buffer and a plain sendall would block
        forever — TCP never errors on a live-but-idle peer.  Any bytes the
        peer's stack accepts reset the progress clock; the mesh-wide abort
        flag is observed every poll quantum.  No first-bytes arming
        needed: the kernel accepts into the receive buffer even while the
        peer app is still initializing, so bring-up stagger cannot trip
        this.

        ``bufs`` is a writev(2)-style list (typically ``[header,
        payload]``) pushed via ``sendmsg`` so header and payload reach the
        kernel in one syscall without ever being concatenated on the
        heap."""
        sock = p.sock
        bufs = [b for b in bufs if len(b)]
        use_sendmsg = hasattr(sock, "sendmsg")
        budget = self.progress_deadline
        deadline = (time.monotonic() + budget) if budget > 0 else None
        while bufs:
            if self._abort is not None:
                raise CoordinatedAbortError(*self._abort)
            if not _wait_writable(sock, _ABORT_POLL_SECS):
                if deadline is not None and time.monotonic() > deadline:
                    raise _ProgressStall(
                        f"no send progress for {budget:.0f}s "
                        f"(HOROVOD_TCP_PROGRESS_DEADLINE_SECS={budget:g})")
                continue
            try:
                r = sock.sendmsg(bufs) if use_sendmsg \
                    else sock.send(bufs[0])
            except BlockingIOError:
                continue  # lost the race to buffer space; re-poll
            while r > 0:
                if r >= len(bufs[0]):
                    r -= len(bufs[0])
                    bufs.pop(0)
                else:
                    bufs[0] = bufs[0][r:]
                    r = 0
            if deadline is not None:
                deadline = time.monotonic() + budget

    def recv(self, peer: int) -> bytes:
        """Receive one data frame, materialized as fresh ``bytes`` — the
        control/negotiation-plane primitive.  The data plane uses
        :meth:`recv_into` instead, which lands the payload straight in a
        caller-owned buffer with no heap materialization."""
        p = self._peer(peer)
        with p.recv_lock:
            self._check_alive(p, peer)
            try:
                if faults.ACTIVE:
                    faults.inject("tcp.recv", rank=self.rank, peer=peer)
                while True:
                    hdr = self._recv_header(p, peer)
                    if hdr.ctrl:
                        self._consume_control_frame(p, peer, hdr.size,
                                                    hdr.crc)
                        continue  # stale control frame: keep reading
                    if hdr.deferred or hdr.check or hdr.wire_dtype:
                        self._poison_stream(p, peer, HorovodInternalError(
                            f"flagged data frame from rank {peer} on the "
                            f"control recv path (deferred={hdr.deferred}, "
                            f"check={hdr.check}, "
                            f"wire_dtype={hdr.wire_dtype}): wire-CRC/"
                            "compression framing skew between peers; "
                            "aborting, resync is impossible by design"))
                    payload = self._recv_bounded(p, hdr.size)
                    p.frames_in += 1
                    if hdr.crc is not None:
                        got = self._crc32_timed(payload)
                        if got != hdr.crc:
                            self._poison_stream(
                                p, peer,
                                FrameCorruptError(peer, p.frames_in,
                                                  hdr.crc, got))
                    wire_stats.add("bytes_on_wire", hdr.size)
                    flight_recorder.record("frame", dir="recv", peer=peer,
                                           nbytes=hdr.size)
                    return payload
            except _ProgressStall as e:
                self._mark_dead(p, str(e))
                raise PeerGoneError(peer, str(e)) from None
            except OSError as e:
                self._mark_dead(p, f"recv from rank {peer} failed: {e}")
                raise PeerGoneError(
                    peer, f"recv from rank {peer} failed: {e}") from e

    def recv_into(self, peer: int, dest,
                  digest: Optional[digest_mod.StreamDigest] = None,
                  wire_dtype: int = 0) -> int:
        """Receive one data frame's payload directly into ``dest`` (a
        writable C-contiguous bytes-like — typically a memoryview over a
        numpy staging slice); returns the payload size.

        Zero-copy contract: bytes go from the kernel straight into
        ``dest`` via ``socket.recv_into``, and the wire CRC is folded
        incrementally over each landed span (``zlib.crc32`` accepts
        memoryviews), so integrity stays default-on with no intermediate
        buffer.  The frame must fill ``dest`` EXACTLY: the caller sized it
        from the same negotiated layout the sender framed from, so any
        mismatch (a truncating fault, a desynced negotiation) poisons the
        stream like a CRC failure — reading on after a misframe would
        turn one bad frame into positional desync.

        With ``digest``, the frame is expected digest-DEFERRED (no inline
        CRC field): the landed payload is folded into ``digest`` — on the
        helper thread when posted via :meth:`recv_into_async`, i.e. in
        the shadow of the main thread's reduction — and the caller
        settles integrity with :meth:`verify_step_digest`.  ``wire_dtype``
        is the compression dtype code this rank expects; any header
        disagreement (deferred-ness or dtype code) poisons the stream —
        config/version skew must fail loudly, not decode garbage.

        Control frames (coordinated abort) interleave transparently, as
        on the :meth:`recv` path."""
        p = self._peer(peer)
        dv = _as_writable_byte_view(dest)
        with p.recv_lock:
            self._check_alive(p, peer)
            try:
                if faults.ACTIVE:
                    faults.inject("tcp.recv", rank=self.rank, peer=peer)
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
                            f"data frame from rank {peer} is "
                            f"{'digest-deferred' if hdr.deferred else 'inline-CRC'} "
                            f"but this rank expected the "
                            f"{'deferred' if digest is not None else 'inline'} "
                            "wire-CRC path: HOROVOD_WIRE_CRC_SHADOW skew "
                            "between peers; aborting loudly"))
                    if hdr.wire_dtype != wire_dtype:
                        self._poison_stream(p, peer, HorovodInternalError(
                            f"data frame from rank {peer} carries wire "
                            f"dtype code {hdr.wire_dtype} but this rank "
                            f"expects {wire_dtype}: "
                            "HOROVOD_WIRE_COMPRESSION skew between peers "
                            "(mixed-version or mixed-config mesh); "
                            "aborting loudly instead of mis-decoding"))
                    if hdr.size != len(dv):
                        self._poison_stream(p, peer, HorovodInternalError(
                            f"data frame from rank {peer} carries "
                            f"{hdr.size} bytes but the recv_into "
                            f"destination expects "
                            f"{len(dv)}: misframed stream (truncated or "
                            "desynced); aborting, resync is impossible by "
                            "design"))
                    got = self._recv_bounded_into(
                        p, dv, with_crc=hdr.crc is not None)
                    p.frames_in += 1
                    if hdr.crc is not None and got != hdr.crc:
                        self._poison_stream(
                            p, peer,
                            FrameCorruptError(peer, p.frames_in, hdr.crc,
                                              got))
                    if digest is not None:
                        # Shadow slot: the complete landed frame is
                        # folded here, off the main thread's serial path.
                        self._digest_timed(digest, dv)
                    wire_stats.add("bytes_on_wire", hdr.size)
                    flight_recorder.record("frame", dir="recv", peer=peer,
                                           nbytes=hdr.size)
                    return hdr.size
            except _ProgressStall as e:
                self._mark_dead(p, str(e))
                raise PeerGoneError(peer, str(e)) from None
            except OSError as e:
                self._mark_dead(p, f"recv from rank {peer} failed: {e}")
                raise PeerGoneError(
                    peer, f"recv from rank {peer} failed: {e}") from e

    def _consume_control_frame(self, p: _Peer, peer: int, size: int,
                               crc: Optional[int]) -> None:
        """Read, CRC-verify, and handle one control frame — shared by the
        materializing ``recv`` and the zero-copy ``recv_into`` so the two
        receive paths cannot diverge.  Returns normally only for STALE
        control frames (``_handle_control`` discards them); control
        traffic is deliberately NOT counted in ``bytes_on_wire`` on
        either side (see ``CounterStats``)."""
        payload = self._recv_bounded(p, size)
        p.frames_in += 1
        if crc is not None:
            got = self._crc32_timed(payload)
            if got != crc:
                self._poison_stream(
                    p, peer,
                    FrameCorruptError(peer, p.frames_in, crc, got))
        self._handle_control(payload, peer)

    def _recv_header(self, p: _Peer, peer: int) -> _FrameHeader:
        """Read and decode one frame header (flag bits per the module
        docstring).  The inline CRC field is present only when the mesh
        CRC is on AND the frame is not digest-deferred."""
        n = _LEN.unpack(self._recv_bounded(p, _LEN.size))[0]
        size = n & ~_FLAGS_MASK
        if size > _MAX_FRAME_BYTES:
            self._poison_stream(p, peer, HorovodInternalError(
                f"frame header from rank {peer} claims "
                f"{size} bytes (cap {_MAX_FRAME_BYTES}): "
                "corrupted length word; aborting before "
                "allocating it"))
        deferred = bool(n & _DEFER_FLAG)
        crc = _CRC.unpack(self._recv_bounded(p, _CRC.size))[0] \
            if self.wire_crc and not deferred else None
        return _FrameHeader(bool(n & _CTRL_FLAG), deferred,
                            bool(n & _DIGEST_FLAG),
                            (n & _WIRE_DTYPE_MASK) >> _WIRE_DTYPE_SHIFT,
                            size, crc)

    def send_step_digest(self, peer: int, dig: digest_mod.StreamDigest,
                         frames: int) -> None:
        """Close one deferred ring-step direction: emit the digest-check
        frame carrying (algo, chained digest, frame count), itself
        inline-CRC'd — the check frame IS the integrity settlement, so it
        never defers."""
        self.send(peer, digest_mod.pack_check(dig, frames),
                  _check_frame=True)

    def verify_step_digest(self, peer: int, dig: digest_mod.StreamDigest,
                           frames: int) -> None:
        """Read the peer's digest-check frame and compare it against the
        locally chained ``dig``; any disagreement — digest value, frame
        count, or algorithm — poisons the stream exactly like an inline
        CRC mismatch (corrupted data never escapes the collective that
        received it).  Must run strictly after every recv of the step
        completed (the ring waits each ``PendingRecv``), so the helper
        thread is quiescent for this peer and the check frame is next in
        FIFO order."""
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
                        # Same failure plane as an inline CRC mismatch:
                        # some frame in the step (or the step framing
                        # itself) went bad and resync is impossible.
                        self._poison_stream(
                            p, peer,
                            FrameCorruptError(peer, p.frames_in, value,
                                              dig.value()))
                    flight_recorder.record("frame", dir="recv", peer=peer,
                                           nbytes=hdr.size)
                    return
            except _ProgressStall as e:
                self._mark_dead(p, str(e))
                raise PeerGoneError(peer, str(e)) from None
            except OSError as e:
                self._mark_dead(p, f"recv from rank {peer} failed: {e}")
                raise PeerGoneError(
                    peer, f"recv from rank {peer} failed: {e}") from e

    def _recv_bounded(self, p: _Peer, n: int) -> bytes:
        buf = bytearray(n)
        self._recv_bounded_into(p, memoryview(buf), with_crc=False)
        return bytes(buf)

    def _recv_bounded_into(self, p: _Peer, view: memoryview,
                           with_crc: bool) -> Optional[int]:
        """``_recv_exact`` into a caller view, with the failure-plane
        waits: wakes every ``_ABORT_POLL_SECS`` to observe a mesh-wide
        abort (which may have arrived on a different peer's link) and
        enforces the progress deadline — *any* bytes received reset it.
        The deadline only applies once the peer has EVER sent bytes (see
        ``_Peer``): the first-ever frame may legitimately lag the whole
        bring-up stagger.

        With ``with_crc``, folds CRC32 over each landed span as it
        arrives and returns the final digest — the incremental-CRC half of
        the zero-copy recv path."""
        sock = p.sock
        n = len(view)
        got = 0
        crc = 0
        # Incremental-CRC accounting: perf_counter pairs per landed span
        # (tens of ns each, vs ~µs of crc32 per span), folded into ONE
        # counter update per frame; skipped entirely with metrics off.
        measure_crc = with_crc and metrics.ENABLED
        crc_secs = 0.0
        budget = self.progress_deadline
        deadline = (time.monotonic() + budget) \
            if budget > 0 and p.ever_received else None
        while got < n:
            if self._abort is not None:
                raise CoordinatedAbortError(*self._abort)
            if not _wait_readable(sock, _ABORT_POLL_SECS):
                if deadline is not None and time.monotonic() > deadline:
                    raise _ProgressStall(
                        f"no recv progress for {budget:.0f}s "
                        f"(HOROVOD_TCP_PROGRESS_DEADLINE_SECS={budget:g})")
                continue
            try:
                r = sock.recv_into(view[got:], n - got)
            except BlockingIOError:
                continue  # readable raced away (non-blocking socket)
            if r == 0:
                raise OSError("peer closed connection")
            if with_crc:
                if measure_crc:
                    tc = time.perf_counter()
                    crc = zlib.crc32(view[got:got + r], crc)
                    crc_secs += time.perf_counter() - tc
                else:
                    crc = zlib.crc32(view[got:got + r], crc)
            got += r
            if not p.ever_received:
                p.ever_received = True
                if budget > 0:
                    deadline = time.monotonic() + budget
            elif deadline is not None:
                deadline = time.monotonic() + budget
        if measure_crc and crc_secs:
            metrics.inc("crc_verify_seconds_total", crc_secs)
        return (crc & 0xFFFFFFFF) if with_crc else None

    def _poison_stream(self, p: _Peer, peer: int,
                       err: HorovodInternalError) -> None:
        """The stream from ``peer`` is poisoned (wire-CRC mismatch, or a
        length word claiming an absurd size).

        Resync is impossible by design — the framing after a corrupt
        frame cannot be trusted, so reading on would turn one bad byte
        into positional desync (the PR 2 failure mode: survivors reading
        negotiation bytes as tensor data).  Mark the peer dead, broadcast
        the coordinated abort so every rank tears down at a frame
        boundary, and let the mesh epoch (elastic plane) recover."""
        flight_recorder.record("stream_poisoned", peer=peer,
                               error=str(err)[:300])
        self._mark_dead(p, str(err))
        self.send_abort(str(err))
        raise err

    def _handle_control(self, payload: bytes, peer: int) -> None:
        """Returns normally only for STALE control frames (discard)."""
        from ..core.messages import AbortFrame, is_abort_frame

        if not is_abort_frame(payload):
            raise HorovodInternalError(
                f"unknown control frame from rank {peer}")
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
        raise CoordinatedAbortError(frame.epoch, frame.origin_rank,
                                    frame.reason)

    def send_abort(self, reason: str, epoch: Optional[int] = None,
                   origin_rank: Optional[int] = None,
                   _relayed: bool = False) -> None:
        """Broadcast a coordinated abort over every surviving link.

        Best-effort and non-blocking-ish (bounded lock waits + socket
        timeouts): the caller is already tearing down and must not hang on
        a wedged peer.  Also flips this mesh's own abort flag so any local
        thread still blocked in a recv (e.g. the sendrecv helper) unblocks
        within one poll quantum.  ``origin_rank`` lets a RELAY of someone
        else's abort keep the original detector's identity.

        Under a LinkMesh, ``abort_relay`` redirects the broadcast to the
        facade so it reaches EVERY transport's links (``_relayed`` marks
        the facade's call back down and breaks the recursion)."""
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
        metrics.inc("aborts_total", dir="sent")
        flight_recorder.record("abort_broadcast", origin=origin_rank,
                               epoch=epoch, reason=reason[:300])
        if self._abort is None:
            self._abort = (epoch, origin_rank, reason)
        for peer, p in list(self._peers.items()):
            # Dead-marked links are still TRIED: a recv-deadline mark only
            # proves the peer stopped sending — its recv direction may be
            # fine (e.g. hung mid-step), and the abort is exactly what
            # unblocks it.  A truly torn socket errors out immediately.
            if not p.send_lock.acquire(timeout=2.0):
                continue  # a wedged send holds the lock; skip this link
            try:
                p.sock.settimeout(5.0)
                header = _LEN.pack(len(payload) | _CTRL_FLAG)
                if self.wire_crc:
                    header += _CRC.pack(zlib.crc32(payload) & 0xFFFFFFFF)
                # hvdlint: disable=HVD001 -- bounded by the settimeout(5.0)
                # above; the teardown path must push the abort even though
                # the non-blocking poll loops are already torn down.
                p.sock.sendall(header)
                p.sock.sendall(payload)  # hvdlint: disable=HVD001 -- same 5s socket timeout bounds this write
            except OSError as e:
                self._mark_dead(p, f"abort send failed: {e}")
            finally:
                try:
                    p.sock.setblocking(False)  # peers stay non-blocking
                except OSError:
                    pass
                p.send_lock.release()

    def sendrecv(self, send_to: int, payload, recv_from: int) -> bytes:
        """Concurrent send+recv — the ring-collective step primitive.

        A sequential send-then-recv deadlocks on rings once payloads exceed
        socket buffers (everyone blocked in sendall), so the recv runs on a
        persistent helper thread (not thread-per-call: this sits on the hot
        path, 2*(N-1) steps per fused response per cycle)."""
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
        """Post a :meth:`recv_into` on the persistent helper thread and
        return a :class:`PendingRecv` handle — the segment-pipeline
        primitive: the collective layer posts the recv for segment k+1,
        sends its own segment, then reduces segment k while k+1 is still
        on the wire.

        Posts are FIFO on one helper thread, so posting recvs for
        segments k and k+1 back-to-back maps them onto the peer's frames
        in wire order — which also serializes ``digest`` updates in frame
        order without any extra locking."""
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
        """Zero-copy ``sendrecv``: concurrent send of ``payload`` (any
        bytes-like view) and recv of exactly ``len(dest)`` bytes straight
        into ``dest``.  Returns the received payload size."""
        pending = self.recv_into_async(recv_from, dest)
        self.send(send_to, payload)
        return pending.wait()

    def _sr_submit(self, task) -> None:
        if self._sr_thread is None or not self._sr_thread.is_alive():
            self._sr_queue = queue.SimpleQueue()
            self._sr_thread = threading.Thread(
                target=self._sr_loop, name="hvd-tcp-sendrecv", daemon=True)
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
                # kill the loop: tasks already queued behind it would never
                # run and their callers would wait forever on completion
                # events nobody sets.  (sendrecv's own task catches its
                # errors into the result box; anything reaching here is a
                # foreign/broken submission.)
                log.error("sendrecv helper task raised", exc_info=True)

    def _peer(self, peer: int) -> _Peer:
        try:
            return self._peers[peer]
        except KeyError:
            raise HorovodInternalError(
                f"rank {self.rank} has no connection to rank {peer}") from None

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._sr_thread is not None and self._sr_thread.is_alive():
            self._sr_queue.put(None)
        if self._listener is not None:
            self._listener.close()
        for p in self._peers.values():
            try:
                p.sock.close()
            except OSError:
                pass


def _default_advertise_addr() -> str:
    # Best-effort routable address; loopback fallback for single-host jobs.
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect(("10.255.255.255", 1))
            return s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        return "127.0.0.1"


def candidate_advertise_addrs() -> List[str]:
    """All plausible addresses of this host, best first.

    Multi-host jobs (HOROVOD_CROSS_SIZE > 1) exclude loopback: a remote
    peer dialing 127.0.0.1 would reach itself.  Single-host jobs put
    loopback first — always right and fastest.
    """
    from ..common import env as env_mod

    multi_host = env_mod.get_int(env_mod.HOROVOD_CROSS_SIZE, 1) > 1
    addrs: List[str] = []
    primary = _default_advertise_addr()
    if primary != "127.0.0.1":
        addrs.append(primary)
    try:
        for info in socket.getaddrinfo(socket.gethostname(), None,
                                       socket.AF_INET):
            a = info[4][0]
            if a not in addrs and not a.startswith("127."):
                addrs.append(a)
    except OSError:
        pass
    if multi_host:
        return addrs or [primary]
    return ["127.0.0.1"] + addrs


def _configure(sock: socket.socket) -> None:
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.settimeout(None)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise HorovodInternalError("peer closed connection")
        got += r
    return bytes(buf)
