"""Transport-layer tests: KV stores, rendezvous HTTP server, TCP mesh.

The mesh tests run N ranks as threads inside one process sharing a
MemoryStore / live HTTP server — the transport doesn't care, which is the
point (reference analog: gloo connectFullMesh through any Store)."""

import threading

import pytest

from horovod_tpu.runner.rendezvous import RendezvousServer
from horovod_tpu.transport import HTTPStoreClient, MemoryStore, TcpMesh


pytestmark = pytest.mark.smoke


def run_ranks(size, fn, timeout=30):
    """Run fn(rank) on `size` threads; re-raise the first failure.

    The join budget is load-scaled like every other suite timeout: mesh
    bring-up with 5 s-per-socket accept/dial steps legitimately exceeds a
    fixed 30 s when the box is saturated (the "rank thread hung" flake,
    run-2 audit)."""
    from .helpers import _timeout_scale

    errs = []
    results = [None] * size

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


def test_memory_store_wait():
    store = MemoryStore()
    store.set("s", "a", b"1")

    def delayed():
        store.set("s", "b", b"2")

    threading.Timer(0.05, delayed).start()
    got = store.wait("s", ["a", "b"], timeout=5)
    assert got == {"a": b"1", "b": b"2"}


def test_memory_store_wait_timeout():
    store = MemoryStore()
    with pytest.raises(TimeoutError):
        store.wait("s", ["missing"], timeout=0.1)


def test_http_store_roundtrip():
    server = RendezvousServer(bind_addr="127.0.0.1")
    port = server.start()
    try:
        client = HTTPStoreClient("127.0.0.1", port)
        assert client.get("scope", "k") is None
        client.set("scope", "k", b"\x00\x01binary\xff")
        assert client.get("scope", "k") == b"\x00\x01binary\xff"
        client.delete("scope", "k")
        assert client.get("scope", "k") is None
        client.delete("scope", "k")  # idempotent
        # scoping: same key name, different scope
        client.set("a", "k", b"1")
        client.set("b", "k", b"2")
        assert client.get("a", "k") == b"1"
        assert client.get("b", "k") == b"2"
    finally:
        server.stop()


def test_http_store_wait_across_clients():
    server = RendezvousServer(bind_addr="127.0.0.1")
    port = server.start()
    try:
        c1 = HTTPStoreClient("127.0.0.1", port)
        c2 = HTTPStoreClient("127.0.0.1", port)
        threading.Timer(0.05, lambda: c2.set("s", "x", b"hello")).start()
        got = c1.wait("s", ["x"], timeout=5)
        assert got["x"] == b"hello"
    finally:
        server.stop()


@pytest.mark.parametrize("size", [2, 4])
def test_tcp_mesh_pairwise(size):
    store = MemoryStore()

    def fn(rank):
        mesh = TcpMesh(rank, size, store, bind_addr="127.0.0.1",
                       advertise_addr="127.0.0.1", timeout=10)
        try:
            # everyone sends its rank to everyone else
            for peer in range(size):
                if peer != rank:
                    mesh.send(peer, f"from-{rank}".encode())
            got = {}
            for peer in range(size):
                if peer != rank:
                    got[peer] = mesh.recv(peer).decode()
            return got
        finally:
            mesh.close()

    results = run_ranks(size, fn)
    for rank, got in enumerate(results):
        assert got == {p: f"from-{p}" for p in range(size) if p != rank}


def test_tcp_mesh_forms_when_a_dialer_gave_up_a_hello_and_dialled_again(
        monkeypatch):
    """A listener starved for longer than the dialer's hello timeout finds
    the abandoned socket first in its queue.  It must not take that one
    for the link: it used to answer it, register it and close the dialer's
    second attempt as a duplicate, and both ranks held a dead socket ("peer
    closed connection" at the first frame of a job on a busy machine)."""
    import time

    from horovod_tpu.transport import tcp

    monkeypatch.setattr(tcp, "_HELLO_TIMEOUT_SECS", 0.3)
    accept_one = TcpMesh._accept_one
    starved = [True]

    def late_to_its_first(self, sock):
        if starved:
            starved.pop()
            time.sleep(0.9)
        return accept_one(self, sock)

    monkeypatch.setattr(TcpMesh, "_accept_one", late_to_its_first)
    store = MemoryStore()

    def fn(rank):
        mesh = TcpMesh(rank, 2, store, bind_addr="127.0.0.1",
                       advertise_addr="127.0.0.1", timeout=20)
        try:
            mesh.send(1 - rank, f"from-{rank}".encode())
            return mesh.recv(1 - rank).decode()
        finally:
            mesh.close()

    assert run_ranks(2, fn) == ["from-1", "from-0"]


def test_tcp_mesh_large_payload_ring():
    """Ring exchange with payloads larger than socket buffers must not
    deadlock (sendrecv overlaps directions)."""
    size = 3
    store = MemoryStore()
    payload = b"x" * (8 * 1024 * 1024)

    def fn(rank):
        mesh = TcpMesh(rank, size, store, bind_addr="127.0.0.1",
                       advertise_addr="127.0.0.1", timeout=10)
        try:
            nxt, prv = (rank + 1) % size, (rank - 1) % size
            got = mesh.sendrecv(nxt, payload, prv)
            assert got == payload
            return True
        finally:
            mesh.close()

    assert all(run_ranks(size, fn, timeout=60))


def test_tcp_mesh_size_one_noop():
    mesh = TcpMesh(0, 1, MemoryStore())
    with pytest.raises(Exception):
        mesh.send(1, b"nope")
    mesh.close()


def test_tcp_mesh_over_http_store():
    server = RendezvousServer(bind_addr="127.0.0.1")
    port = server.start()
    try:
        def fn(rank):
            client = HTTPStoreClient("127.0.0.1", port)
            mesh = TcpMesh(rank, 2, client, bind_addr="127.0.0.1",
                           advertise_addr="127.0.0.1", timeout=10)
            try:
                if rank == 0:
                    mesh.send(1, b"ping")
                    assert mesh.recv(1) == b"pong"
                else:
                    assert mesh.recv(0) == b"ping"
                    mesh.send(0, b"pong")
                return True
            finally:
                mesh.close()

        assert all(run_ranks(2, fn))
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# service-plane security (reference network.py:50-85, secret.py:36)
# ---------------------------------------------------------------------------


def test_rendezvous_rejects_unsigned_requests(monkeypatch):
    """A server holding a job secret must 403 unsigned/missigned traffic —
    otherwise any LAN peer can rewrite the rank table."""
    import urllib.error
    import urllib.request

    from horovod_tpu.common import env as env_mod

    server = RendezvousServer(bind_addr="127.0.0.1", job_secret=b"k" * 32)
    port = server.start()
    try:
        # unsigned PUT → 403
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/s/a", data=b"evil", method="PUT")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 403
        # signed client (secret via env) → accepted
        monkeypatch.setenv(env_mod.HOROVOD_SECRET_KEY, "k" * 32)
        good = HTTPStoreClient("127.0.0.1", port)
        good.set("s", "a", b"ok")
        assert good.get("s", "a") == b"ok"
        # client with the WRONG key → 403 on read too
        monkeypatch.setenv(env_mod.HOROVOD_SECRET_KEY, "x" * 32)
        bad = HTTPStoreClient("127.0.0.1", port)
        with pytest.raises(urllib.error.HTTPError) as ei:
            bad.get("s", "a")
        assert ei.value.code == 403
    finally:
        server.stop()


def test_tcp_mesh_authenticated_hello(monkeypatch):
    """With a job secret, mesh peers HMAC their hellos; an interloper
    without the key cannot join (its connection is dropped, the real mesh
    still forms)."""
    import socket as socket_mod

    from horovod_tpu.common import env as env_mod

    monkeypatch.setenv(env_mod.HOROVOD_SECRET_KEY, "s" * 32)
    store = MemoryStore()

    def make(rank):
        return TcpMesh(rank, 2, store, scope="auth")

    def attack():
        # wait for rank 1's advertised endpoint, connect with a bogus hello
        try:
            import time
            deadline = time.monotonic() + 5
            val = None
            while val is None and time.monotonic() < deadline:
                val = store.get("auth", "1")
                time.sleep(0.01)
            host, port = val.decode().split(",")[0].rsplit(":", 1)
            s = socket_mod.create_connection((host, int(port)), timeout=5)
            s.sendall(b"HVMT" + b"\x00" * 8 + b"\x00" * 32)  # bad sig
        except OSError:
            pass  # mesh dropping us mid-write is the expected outcome

    threading.Thread(target=attack, daemon=True).start()
    meshes = run_ranks(2, make)
    meshes[0].send(1, b"payload")
    assert meshes[1].recv(0) == b"payload"
    for m in meshes:
        m.close()


# ---------------------------------------------------------------------------
# failure plane: dead-peer state, progress deadline, coordinated abort
# ---------------------------------------------------------------------------


def _mesh_pair(store=None, **kwargs):
    store = store or MemoryStore()
    meshes = [None, None]

    def make(rank):
        meshes[rank] = TcpMesh(rank, 2, store, bind_addr="127.0.0.1",
                               advertise_addr="127.0.0.1", timeout=10,
                               **kwargs)
        return meshes[rank]

    run_ranks(2, make)
    return meshes


def test_recv_progress_deadline_marks_peer_gone():
    """A recv with zero byte progress past the deadline raises
    PeerGoneError; every later call to that peer fails fast instead of
    re-blocking on the socket.  The deadline arms only after the peer's
    FIRST bytes — bring-up staggering must never count as death."""
    import time as time_mod

    from horovod_tpu.common.exceptions import PeerGoneError

    meshes = _mesh_pair(progress_deadline=0.6)
    try:
        # pre-first-frame: generously slow bring-up does not trip it
        threading.Timer(1.2, lambda: meshes[0].send(1, b"up")).start()
        assert meshes[1].recv(0) == b"up"
        # armed now: total silence past the deadline marks the peer gone
        with pytest.raises(PeerGoneError, match="no recv progress"):
            meshes[1].recv(0)
        t0 = time_mod.monotonic()
        with pytest.raises(PeerGoneError):
            meshes[1].recv(0)
        with pytest.raises(PeerGoneError):
            meshes[1].send(0, b"late")
        assert time_mod.monotonic() - t0 < 0.3, "dead peer did not fail fast"
    finally:
        for m in meshes:
            m.close()


def test_recv_progress_resets_deadline():
    """Slow-but-alive traffic (bytes trickling in) must never trip the
    deadline — only a total stop does."""
    import time as time_mod

    meshes = _mesh_pair(progress_deadline=2.0)
    payload = b"y" * (256 * 1024)

    def drip():
        # hand-frame the payload and drip it in chunks spaced at ~25% of
        # the deadline: every chunk resets the progress clock, and the
        # 1.5 s margin keeps scheduler hiccups on a loaded box from
        # tripping it (this in-process test has no retry gate)
        import struct as struct_mod
        import zlib as zlib_mod

        sock = meshes[0]._peers[1].sock
        frame = struct_mod.pack("<Q", len(payload)) \
            + struct_mod.pack("<I", zlib_mod.crc32(payload) & 0xFFFFFFFF) \
            + payload
        for off in range(0, len(frame), len(frame) // 4):
            sock.sendall(frame[off:off + len(frame) // 4])
            time_mod.sleep(0.5)

    t = threading.Thread(target=drip, daemon=True)
    t.start()
    try:
        assert meshes[1].recv(0) == payload
    finally:
        t.join(10)
        for m in meshes:
            m.close()


def test_send_progress_deadline_on_unread_peer():
    """A peer that is alive but never READS must not hang the sender:
    once the socket buffers fill, zero accepted bytes past the deadline
    raises PeerGoneError (TCP itself would block forever — the peer is
    healthy at the transport level, just wedged at the app level)."""
    from horovod_tpu.common.exceptions import PeerGoneError

    meshes = _mesh_pair(progress_deadline=0.8)
    big = b"z" * (8 * 1024 * 1024)
    try:
        with pytest.raises(PeerGoneError, match="no send progress"):
            for _ in range(64):  # fill both ends' socket buffers
                meshes[0].send(1, big)
    finally:
        for m in meshes:
            m.close()


def test_abort_frame_unblocks_recv_and_carries_reason():
    from horovod_tpu.common.exceptions import CoordinatedAbortError

    meshes = _mesh_pair()
    try:
        errs = []

        def blocked():
            try:
                meshes[0].recv(1)
            except CoordinatedAbortError as e:
                errs.append(e)

        t = threading.Thread(target=blocked, daemon=True)
        t.start()
        import time as time_mod

        time_mod.sleep(0.2)
        meshes[1].send_abort("stall shutdown: tensor g missing ranks [2]")
        t.join(5)
        assert not t.is_alive(), "abort frame did not unblock the recv"
        assert errs and errs[0].origin_rank == 1
        assert "stall shutdown" in errs[0].reason
    finally:
        for m in meshes:
            m.close()


def test_stale_epoch_abort_discarded():
    """An abort stamped with a pre-reset elastic epoch must be dropped at
    the transport layer — data frames behind it still deliver."""
    meshes = _mesh_pair(epoch=5)
    try:
        meshes[0].send_abort("old world", epoch=3)
        meshes[0]._abort = None  # broadcast marks the sender; clear to reuse
        meshes[0].send(1, b"fresh")
        assert meshes[1].recv(0) == b"fresh"
    finally:
        for m in meshes:
            m.close()


def test_wire_crc_catches_inflight_corruption():
    """An injected in-flight byte flip (``action=corrupt``: the sender's
    CRC covers the ORIGINAL payload) must surface as FrameCorruptError on
    the receiver — naming the peer, frame index, and both CRCs — and
    broadcast a coordinated abort back across the mesh."""
    from horovod_tpu.common import faults
    from horovod_tpu.common.exceptions import (
        CoordinatedAbortError,
        FrameCorruptError,
    )

    meshes = _mesh_pair()
    try:
        meshes[0].send(1, b"clean")  # frame 1: intact
        assert meshes[1].recv(0) == b"clean"
        faults.configure("tcp.send:rank=0:nth=1:action=corrupt,2")
        meshes[0].send(1, b"poisoned-payload")
        with pytest.raises(FrameCorruptError) as exc:
            meshes[1].recv(0)
        err = exc.value
        assert err.peer == 0 and err.frame_index == 2
        assert err.expected_crc != err.got_crc
        assert "resync is impossible" in str(err)
        # the detector's abort reached the corrupting side
        with pytest.raises(CoordinatedAbortError, match="wire CRC"):
            meshes[0].recv(1)
        # and the detector itself fails fast now (peer marked dead)
        from horovod_tpu.common.exceptions import HorovodInternalError

        with pytest.raises(HorovodInternalError):
            meshes[1].recv(0)
    finally:
        faults.reset()
        for m in meshes:
            m.close()


def test_corrupt_injection_is_deterministic():
    """The same spec must flip the same bytes with the same masks — the
    reproducibility contract every other fault action keeps."""
    from horovod_tpu.common import faults

    outs = []
    for _ in range(2):
        faults.configure("tcp.send:nth=1:action=corrupt,3")
        v = faults.inject("tcp.send", rank=0, payload=b"x" * 64)
        outs.append((v.payload, v.wire_bytes()))
        faults.reset()
    assert outs[0] == outs[1]
    assert outs[0][0] != outs[0][1], "corrupt flipped nothing"


def test_truncate_fault_passes_crc_parse_layer_catches():
    """``action=truncate`` shortens the payload BEFORE framing: header
    and CRC agree with the short bytes, so the transport hands them up
    intact — and the defensive parse layer is what catches the damage
    (typed TruncatedFrameError, never a raw struct.error)."""
    from horovod_tpu.common import faults
    from horovod_tpu.common.exceptions import TruncatedFrameError
    from horovod_tpu.core.messages import Request, RequestList

    wire = RequestList(
        requests=[Request(tensor_name="layer0/kernel.grad",
                          tensor_shape=[128, 784])]).to_bytes()
    meshes = _mesh_pair()
    try:
        faults.configure("tcp.send:rank=0:nth=1:action=truncate,5")
        meshes[0].send(1, wire)
        got = meshes[1].recv(0)  # transport-level: a clean short frame
        assert got == wire[:-5]
        with pytest.raises(TruncatedFrameError, match="truncated"):
            RequestList.from_bytes(got)
    finally:
        faults.reset()
        for m in meshes:
            m.close()


def test_corrupted_length_word_aborts_before_allocating():
    """The length word is NOT CRC-covered: a flipped high byte claims
    terabytes, and recv must treat it as a poisoned stream (coordinated
    abort) BEFORE trying to allocate the claimed buffer — the failure
    mode is MemoryError/OOM-kill otherwise, which no abort path survives."""
    import struct as struct_mod

    from horovod_tpu.common.exceptions import HorovodInternalError

    meshes = _mesh_pair()
    try:
        sock = meshes[0]._peers[1].sock
        # hand-frame a header claiming 1 TiB (as a corrupted-in-flight
        # length word would); CRC field and payload never matter — the
        # cap must trip first
        sock.sendall(struct_mod.pack("<Q", 1 << 40))
        with pytest.raises(HorovodInternalError,
                           match="corrupted length word") as exc:
            meshes[1].recv(0)
        assert "aborting before allocating" in str(exc.value)
        # the abort reached the sending side too
        from horovod_tpu.common.exceptions import CoordinatedAbortError

        with pytest.raises(CoordinatedAbortError):
            meshes[0].recv(1)
    finally:
        for m in meshes:
            m.close()


def test_wire_crc_disabled_by_knob(monkeypatch):
    """HOROVOD_WIRE_CRC=0 falls back to the bare 8-byte header — frames
    still deliver (both sides read the knob from the shared env)."""
    monkeypatch.setenv("HOROVOD_WIRE_CRC", "0")
    meshes = _mesh_pair()
    try:
        assert all(not m.wire_crc for m in meshes)
        meshes[0].send(1, b"unverified")
        assert meshes[1].recv(0) == b"unverified"
    finally:
        for m in meshes:
            m.close()


def test_sendrecv_helper_recovers_after_task_error():
    """Regression: a raising helper task must not wedge the _sr_queue — the
    next sendrecv still completes (previously a dead helper thread orphaned
    queued tasks and their completion events)."""
    meshes = _mesh_pair()
    try:
        meshes[0]._sr_submit(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
        import time as time_mod

        time_mod.sleep(0.1)
        out = [None]

        def r0():
            out[0] = meshes[0].sendrecv(1, b"ring", 1)

        def r1():
            got = meshes[1].recv(0)
            assert got == b"ring"
            meshes[1].send(0, b"pong")

        t0, t1 = threading.Thread(target=r0), threading.Thread(target=r1)
        t0.start(); t1.start()
        t0.join(10); t1.join(10)
        assert not t0.is_alive() and not t1.is_alive(), "sendrecv wedged"
        assert out[0] == b"pong"
    finally:
        for m in meshes:
            m.close()


# ---------------------------------------------------------------------------
# zero-copy data plane: view sends, recv_into, incremental CRC
# ---------------------------------------------------------------------------


def test_send_accepts_numpy_views_and_recv_into_lands_in_place():
    """The zero-copy pair: a numpy slice goes out as a view (no tobytes)
    and the payload lands directly in a caller buffer (no fresh bytes),
    with the default-on wire CRC verified incrementally over the
    destination."""
    import numpy as np

    meshes = _mesh_pair()
    try:
        src = np.arange(64, dtype=np.float32)
        dest = np.zeros(16, dtype=np.float32)
        meshes[0].send(1, memoryview(src[8:24]).cast("B"))
        got = meshes[1].recv_into(0, memoryview(dest).cast("B"))
        assert got == 64
        assert np.array_equal(dest, src[8:24])
    finally:
        for m in meshes:
            m.close()


def test_sendrecv_into_concurrent_exchange():
    import numpy as np

    meshes = _mesh_pair()
    payloads = [np.full(1024, float(r), np.float32) for r in range(2)]
    outs = [np.empty(1024, np.float32) for _ in range(2)]
    results = [None, None]

    def fn(rank):
        results[rank] = meshes[rank].sendrecv_into(
            1 - rank, memoryview(payloads[rank]).cast("B"),
            1 - rank, memoryview(outs[rank]).cast("B"))

    try:
        threads = [threading.Thread(target=fn, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20)
            assert not t.is_alive(), "sendrecv_into wedged"
        for rank in range(2):
            assert results[rank] == 4096
            assert np.array_equal(outs[rank], payloads[1 - rank])
    finally:
        for m in meshes:
            m.close()


def test_recv_into_size_mismatch_poisons_stream():
    """A data frame whose size disagrees with the caller's negotiated
    destination is positional desync in the making (a truncating fault, a
    desynced negotiation): the stream must be poisoned — peer dead,
    coordinated abort broadcast — exactly like a CRC failure."""
    from horovod_tpu.common.exceptions import (
        CoordinatedAbortError,
        HorovodInternalError,
    )

    meshes = _mesh_pair()
    try:
        meshes[0].send(1, b"x" * 10)
        dest = bytearray(16)
        with pytest.raises(HorovodInternalError, match="misframed"):
            meshes[1].recv_into(0, memoryview(dest))
        # the abort reached the sending side
        with pytest.raises(CoordinatedAbortError):
            meshes[0].recv(1)
    finally:
        for m in meshes:
            m.close()


def test_recv_into_wire_crc_catches_inflight_corruption():
    """The incremental CRC over the recv_into destination must catch an
    injected in-flight flip exactly like the materializing recv path —
    typed FrameCorruptError, peer marked dead, abort broadcast back."""
    import numpy as np

    from horovod_tpu.common import faults
    from horovod_tpu.common.exceptions import (
        CoordinatedAbortError,
        FrameCorruptError,
    )

    meshes = _mesh_pair()
    try:
        src = np.ones(256, np.float32)
        dest = np.empty(256, np.float32)
        meshes[0].send(1, memoryview(src).cast("B"))
        assert meshes[1].recv_into(0, memoryview(dest).cast("B")) == 1024
        faults.configure("tcp.send:rank=0:nth=1:action=corrupt,2")
        meshes[0].send(1, memoryview(src).cast("B"))
        with pytest.raises(FrameCorruptError) as exc:
            meshes[1].recv_into(0, memoryview(dest).cast("B"))
        assert exc.value.peer == 0 and exc.value.frame_index == 2
        with pytest.raises(CoordinatedAbortError, match="wire CRC"):
            meshes[0].recv(1)
    finally:
        faults.reset()
        for m in meshes:
            m.close()


def test_recv_into_truncate_fault_caught_as_misframe():
    """``action=truncate`` on the view path: header and CRC agree with
    the short payload, so the CRC passes — and the size check against the
    negotiated destination is what catches it (poison + abort, never a
    silent short read into the staging buffer)."""
    import numpy as np

    from horovod_tpu.common import faults
    from horovod_tpu.common.exceptions import HorovodInternalError

    meshes = _mesh_pair()
    try:
        faults.configure("tcp.send:rank=0:nth=1:action=truncate,4")
        src = np.ones(64, np.float32)
        meshes[0].send(1, memoryview(src).cast("B"))
        dest = np.empty(64, np.float32)
        with pytest.raises(HorovodInternalError, match="misframed"):
            meshes[1].recv_into(0, memoryview(dest).cast("B"))
    finally:
        faults.reset()
        for m in meshes:
            m.close()


def test_abort_frame_interleaves_with_recv_into():
    """A control frame (coordinated abort) arriving while a recv_into is
    posted must surface as CoordinatedAbortError on the view path too."""
    from horovod_tpu.common.exceptions import CoordinatedAbortError

    meshes = _mesh_pair()
    try:
        errs = []

        def blocked():
            try:
                meshes[0].recv_into(1, memoryview(bytearray(128)))
            except CoordinatedAbortError as e:
                errs.append(e)

        t = threading.Thread(target=blocked, daemon=True)
        t.start()
        import time as time_mod

        time_mod.sleep(0.2)
        meshes[1].send_abort("pipelined step abort")
        t.join(5)
        assert not t.is_alive(), "abort did not unblock recv_into"
        assert errs and errs[0].origin_rank == 1
    finally:
        for m in meshes:
            m.close()


def test_recv_into_rejects_readonly_destination():
    meshes = _mesh_pair()
    try:
        with pytest.raises(ValueError, match="writable"):
            meshes[1].recv_into(0, memoryview(b"readonly"))
    finally:
        for m in meshes:
            m.close()


def test_tcp_mesh_multi_addr_fallback():
    """Dialers fall through dead advertised addresses to a live one
    (NIC-negotiation role, reference driver_service.py:162-194).  The
    dialing rank sees rank 0's advertisement with an unroutable first
    entry — as a multi-homed host with a dead NIC would publish."""
    store = MemoryStore()

    class DeadFirstStore(MemoryStore):
        """Rank 1's view: rank 0 advertises a dead endpoint first."""

        def get(self, scope, key):
            val = store.get(scope, key)
            if val is not None and scope == "nic" and key == "0":
                # 203.0.113.0/24 is TEST-NET-3: guaranteed unroutable.
                return b"203.0.113.1:59999," + val
            return val

        def set(self, scope, key, value):
            store.set(scope, key, value)

    dead_first = DeadFirstStore()

    def make(rank):
        if rank == 0:
            return TcpMesh(0, 2, store, scope="nic",
                           advertise_addr="127.0.0.1")
        return TcpMesh(1, 2, dead_first, scope="nic",
                       advertise_addr="127.0.0.1")

    res = run_ranks(2, make, timeout=60)
    res[1].send(0, b"hi")
    assert res[0].recv(1) == b"hi"
    for m in res:
        m.close()


def test_tcp_mesh_dead_first_candidate_races_fast():
    """Multi-addr dialing probes candidates CONCURRENTLY: a dead first
    candidate (blackhole address) must not serialize a connect timeout in
    front of the live one (reference probe-and-intersect role)."""
    import time as time_mod

    store = MemoryStore()

    class DeadFirstStore(MemoryStore):
        """Prepends an unroutable candidate to every advertisement."""

        def set(self, scope, key, value):
            if scope.startswith("tcp") or scope == "tcp":
                spec = value.decode()
                port = spec.rsplit(":", 1)[1]
                value = f"10.255.255.1:{port},{spec}".encode()
            super().set(scope, key, value)

    dead_store = DeadFirstStore()

    def fn(rank):
        t0 = time_mod.monotonic()
        mesh = TcpMesh(rank, 2, dead_store, bind_addr="127.0.0.1",
                       timeout=20)
        dt = time_mod.monotonic() - t0
        try:
            mesh.send(1 - rank, b"hi")
            assert mesh.recv(1 - rank) == b"hi"
        finally:
            mesh.close()
        return dt

    times = run_ranks(2, fn)
    # serial probing would eat the ~5s connect timeout on the dead
    # candidate first; the concurrent race finishes in well under that
    assert max(times) < 4.0, times
