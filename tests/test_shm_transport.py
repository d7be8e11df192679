"""Shared-memory intra-host transport: parity with TCP, exact byte
accounting, segment hygiene, and the per-link selection seam.

The contract under test (docs/data_plane.md "Transports"): shm carries
the SAME frame discipline as TCP — flag bits, abort/control frames,
deadline semantics, fault sites — so every guard the zero-copy and
chaos suites assert on TCP holds verbatim on shm.  The shm-specific
additions are (a) data bytes count under ``shm_bytes_total``, never
``bytes_on_wire`` (they are not on a wire), and (b) segment lifecycle:
no ``/dev/shm`` residue after clean exit, abort, or a kill-mid-step
sweep.
"""

import glob
import os
import threading
import time

import numpy as np
import pytest

from horovod_tpu.backend import cpu_ring
from horovod_tpu.common import faults
from horovod_tpu.common.exceptions import (CoordinatedAbortError,
                                           FrameCorruptError,
                                           HorovodInternalError)
from horovod_tpu.core import metrics
from horovod_tpu.core.timeline import wire_stats
from horovod_tpu.transport import LinkMesh, MemoryStore
from horovod_tpu.transport.shm import SEG_PREFIX, sweep_dead_segments

from .helpers import run_distributed
from .test_transport import run_ranks

pytestmark = pytest.mark.smoke


def _residue():
    return set(glob.glob(f"/dev/shm/{SEG_PREFIX}*"))


def _in_use_elsewhere(path):
    """The segment's creator (the PID in its name) is a live process
    other than this one: a job of another xdist worker, still running."""
    pid = int(os.path.basename(path)[len(SEG_PREFIX):].split("-", 1)[0])
    if pid == os.getpid():
        return False
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        pass
    return True


@pytest.fixture(autouse=True)
def _hygiene():
    """Every test starts fault-free and must leave zero NEW segments in
    /dev/shm — leak detection is part of every test, not one test.

    /dev/shm is shared with the jobs of every other xdist worker, so a
    new segment counts against this test only if its creator is this
    process or is dead (this test's workers are all reaped by now).  A
    foreign creator that was just killed leaves its segment until its
    own launcher sweeps it: wait for that, bounded."""
    faults.reset()
    before = _residue()
    yield
    faults.reset()
    deadline = time.monotonic() + 10
    while True:
        leaked = {p for p in _residue() - before
                  if not _in_use_elsewhere(p)}
        if not leaked or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    assert not leaked, f"test leaked shm segments: {sorted(leaked)}"


def _mesh(rank, size, store, **kw):
    kw.setdefault("policy", "auto")
    kw.setdefault("host_id", "testhost/0")
    return LinkMesh(rank, size, store, epoch=0, timeout=15,
                    bind_addr="127.0.0.1", advertise_addr="127.0.0.1",
                    **kw)


# ---------------------------------------------------------------------------
# fault-site grammar (HVD003: new sites must parse, and payload actions
# stay send-only — shm.recv:corrupt would silently inject nothing)
# ---------------------------------------------------------------------------

class TestShmFaultGrammar:
    def test_shm_sites_parse(self):
        faults.configure("shm.send:rank=1:nth=6:action=corrupt,1")
        faults.configure("shm.send:nth=2:action=truncate,4")
        faults.configure("shm.recv:action=hang")
        faults.configure("shm.recv:action=delay_ms,5")
        faults.reset()

    def test_payload_actions_rejected_on_shm_recv(self):
        for bad in ["shm.recv:action=corrupt,1",
                    "shm.recv:action=truncate,4",
                    "shm.recv:action=drop"]:
            with pytest.raises(ValueError):
                faults.configure(bad)


# ---------------------------------------------------------------------------
# the selection seam
# ---------------------------------------------------------------------------

def test_same_host_links_classify_shm():
    store = MemoryStore()

    def fn(rank):
        mesh = _mesh(rank, 2, store)
        try:
            assert mesh.route_table() == {1 - rank: "shm"}
            # data-plane sanity through the facade
            if rank == 0:
                mesh.send(1, b"ping")
                assert mesh.recv(1) == b"pong"
            else:
                assert mesh.recv(0) == b"ping"
                mesh.send(0, b"pong")
        finally:
            mesh.close()

    run_ranks(2, fn, timeout=30)


def test_cross_host_links_classify_tcp():
    store = MemoryStore()

    def fn(rank):
        mesh = _mesh(rank, 2, store, host_id=f"host{rank}/0")
        try:
            assert mesh.route_table() == {1 - rank: "tcp"}
            if rank == 0:
                mesh.send(1, b"x")
            else:
                assert mesh.recv(0) == b"x"
        finally:
            mesh.close()

    run_ranks(2, fn, timeout=30)


def test_forced_shm_across_hosts_is_a_loud_config_error():
    """HOROVOD_TRANSPORT=shm on a cross-host link must refuse, not
    silently widen to TCP (that would fake the perf being measured)."""
    store = MemoryStore()

    def fn(rank):
        with pytest.raises(HorovodInternalError, match="cannot carry"):
            _mesh(rank, 2, store, policy="shm", host_id=f"host{rank}/0")

    run_ranks(2, fn, timeout=30)


def test_transport_policy_typo_is_loud(monkeypatch):
    from horovod_tpu.transport.select import transport_policy

    monkeypatch.setenv("HOROVOD_TRANSPORT", "smh")
    with pytest.raises(HorovodInternalError, match="auto|tcp|shm"):
        transport_policy()


# ---------------------------------------------------------------------------
# zero-copy parity matrix: the test_data_plane_zero_copy guards, re-run
# with the ring riding shm through the selection facade
# ---------------------------------------------------------------------------

def _shm_ring_allreduce(arrays, fbms=None, timeout=60):
    size = len(arrays)
    store = MemoryStore()

    def fn(rank):
        mesh = _mesh(rank, size, store)
        try:
            buf = arrays[rank]
            wide = cpu_ring._accum_dtype(buf.dtype)
            fbm = fbms[rank] if fbms is not None else None
            group = list(range(size))
            bounds = cpu_ring._ring_reduce_scatter(
                mesh, buf, group, rank, wide, fbm)
            cpu_ring._ring_allgather_chunks(mesh, buf, group, rank, bounds)
        finally:
            mesh.close()

    run_ranks(size, fn, timeout=timeout)
    return arrays


def _expected_sum(inputs, dtype):
    acc = np.zeros(inputs[0].shape, np.float64)
    for x in inputs:
        acc += np.asarray(x, np.float64)
    return acc.astype(dtype)


def _int_valued(n, rank, dtype):
    return ((np.arange(n) + rank) % 5 + rank + 1).astype(dtype)


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32],
                         ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("n", [1, 7, 1023])
def test_shm_ring_bit_exact(dtype, n):
    inputs = [_int_valued(n, r, dtype) for r in range(3)]
    outs = _shm_ring_allreduce([x.copy() for x in inputs])
    exp = _expected_sum(inputs, dtype)
    for o in outs:
        assert np.array_equal(o, exp)


def test_shm_steady_state_zero_heap_copies_and_exact_accounting():
    """The zero-copy matrix on shm: steady-state ring steps make ZERO
    heap materializations, shm moves exactly the predicted payload
    bytes under ``shm_bytes_total``, and ``bytes_on_wire`` does not move
    at all — shm frames must never launder into the TCP counter."""
    size, n = 3, 999
    dtype = np.dtype(np.float32)
    fbms = [cpu_ring.FusionBufferManager() for _ in range(size)]
    inputs = [_int_valued(n, r, dtype) for r in range(size)]

    _shm_ring_allreduce([x.copy() for x in inputs], fbms)  # warm arenas

    before = wire_stats.snapshot()
    shm_before = metrics.registry.get_counter("shm_bytes_total")
    outs = _shm_ring_allreduce([x.copy() for x in inputs], fbms)
    after = wire_stats.snapshot()
    shm_after = metrics.registry.get_counter("shm_bytes_total")

    assert np.array_equal(outs[0], _expected_sum(inputs, dtype))
    assert after.get("heap_copies", 0) == before.get("heap_copies", 0), \
        "a steady-state shm ring step materialized payload bytes"
    assert after.get("bytes_on_wire", 0) == before.get("bytes_on_wire", 0), \
        "shm frames leaked into the TCP bytes_on_wire counter"

    # Exact accounting, same formula as the TCP twin: every rank sends
    # g-1 chunks per phase; sender and receiver both count.
    bounds = cpu_ring._chunk_bounds(n, size)
    sent_elems = 0
    for idx in range(size):
        for s in range(size - 1):
            c = (idx - s) % size
            sent_elems += int(bounds[c + 1] - bounds[c])
            c = (idx + 1 - s) % size
            sent_elems += int(bounds[c + 1] - bounds[c])
    expected = 2 * sent_elems * dtype.itemsize
    assert shm_after - shm_before == expected, \
        (shm_after - shm_before, expected)


def test_shm_sendrecv_into_bit_exact_both_directions():
    store = MemoryStore()
    n = 4096
    payloads = [(np.arange(n, dtype=np.float64) * (r + 1)) for r in range(2)]
    got = [None, None]

    def fn(rank):
        mesh = _mesh(rank, 2, store)
        try:
            dest = np.empty(n, np.float64)
            mesh.sendrecv_into(1 - rank, payloads[rank], 1 - rank, dest)
            got[rank] = dest
        finally:
            mesh.close()

    run_ranks(2, fn, timeout=30)
    assert np.array_equal(got[0], payloads[1])
    assert np.array_equal(got[1], payloads[0])


# ---------------------------------------------------------------------------
# failure plane: CRC, truncation, abort propagation, PID liveness
# ---------------------------------------------------------------------------

def test_shm_crc_catches_injected_corruption(monkeypatch):
    """HOROVOD_SHM_CRC=1 + a one-byte flip on shm.send → typed
    FrameCorruptError on the receiver, exactly like tcp.send."""
    monkeypatch.setenv("HOROVOD_SHM_CRC", "1")
    faults.configure("shm.send:rank=1:nth=1:action=corrupt,1")
    store = MemoryStore()
    errs = [None, None]

    def fn(rank):
        mesh = _mesh(rank, 2, store)
        try:
            if rank == 1:
                mesh.send(0, np.ones(64, np.float32))
            else:
                try:
                    mesh.recv(1)
                except FrameCorruptError as e:
                    errs[0] = e
        finally:
            mesh.close()

    run_ranks(2, fn, timeout=30)
    assert isinstance(errs[0], FrameCorruptError)
    assert "wire CRC" in str(errs[0])


def test_shm_truncated_frame_is_typed_misframe(monkeypatch):
    monkeypatch.setenv("HOROVOD_SHM_CRC", "1")
    faults.configure("shm.send:rank=1:nth=1:action=truncate,4")
    store = MemoryStore()
    errs = [None]

    def fn(rank):
        mesh = _mesh(rank, 2, store)
        try:
            if rank == 1:
                mesh.send(0, np.ones(64, np.float32))
            else:
                dest = np.empty(64, np.float32)
                try:
                    mesh.recv_into(1, dest)
                except HorovodInternalError as e:
                    errs[0] = e
        finally:
            mesh.close()

    run_ranks(2, fn, timeout=30)
    assert errs[0] is not None and "misframed" in str(errs[0])


def test_abort_unblocks_peer_mid_ring_wait():
    """A rank blocked in an shm recv must observe a peer's send_abort as
    CoordinatedAbortError within the poll quantum — the in-band abort
    frame plus the nap Event, not a deadline expiry."""
    store = MemoryStore()
    errs = [None, None]

    def fn(rank):
        mesh = _mesh(rank, 2, store)
        try:
            if rank == 0:
                try:
                    mesh.recv(1)  # nothing ever sent: blocks
                except CoordinatedAbortError as e:
                    errs[0] = e
            else:
                mesh.send_abort("test abort", origin_rank=1)
        finally:
            mesh.close()

    run_ranks(2, fn, timeout=30)
    assert isinstance(errs[0], CoordinatedAbortError)
    assert "test abort" in str(errs[0])


def test_no_residue_after_clean_close_and_after_abort():
    """Segment lifecycle: the creator unlinks on close; neither a clean
    pass nor an aborted one may leave /dev/shm residue.  (The autouse
    fixture asserts it; this test exists so the property is exercised by
    name, under both exits.)"""
    test_same_host_links_classify_shm()
    test_abort_unblocks_peer_mid_ring_wait()
    assert True  # residue asserted by _hygiene on exit


def test_sweep_dead_segments_reclaims_by_creator_pid():
    """The runner's kill-mid-step backstop: segments named with a dead
    creator pid are unlinked; other pids' segments are untouched."""
    from multiprocessing import shared_memory

    fake_dead, fake_live = 4194000, 4194001
    names = [f"{SEG_PREFIX}{fake_dead}-e0-0x1-deadbeef",
             f"{SEG_PREFIX}{fake_live}-e0-0x1-cafecafe"]
    segs = [shared_memory.SharedMemory(name=n, create=True, size=64)
            for n in names]
    try:
        removed = sweep_dead_segments([fake_dead])
        assert removed == [names[0]]
        left = _residue()
        assert f"/dev/shm/{names[0]}" not in left
        assert f"/dev/shm/{names[1]}" in left
    finally:
        for seg in segs:
            seg.close()
            try:
                seg.unlink()
            except FileNotFoundError:
                pass


# ---------------------------------------------------------------------------
# chaos: np=2 subprocess jobs riding shm under auto selection
# ---------------------------------------------------------------------------

# Mirrors test_fault_injection._FAST_DEADLINE but selects the shm path
# and arms its CRC so corruption is detectable; lockdep on throughout.
_SHM_CHAOS_ENV = {"HOROVOD_TCP_PROGRESS_DEADLINE_SECS": "3",
                  "HOROVOD_TRANSPORT": "auto",
                  "HOROVOD_SHM_CRC": "1",
                  "HOROVOD_LOCK_DEBUG": "1"}

_SURVIVOR_BODY = """
import os
print("PID", rank, os.getpid(), flush=True)
from horovod_tpu.common.exceptions import HorovodInternalError
try:
    for i in range(500):
        hvd.allreduce(np.ones(32, np.float32), name=f"t{i % 4}")
    print("NO_FAULT_SEEN", rank, flush=True)
except HorovodInternalError as e:
    print("SURVIVOR_ABORT", rank, str(e).replace("\\n", " "), flush=True)
"""


def _worker_pids(outs):
    pids = []
    for r, out in enumerate(outs):
        for line in out.splitlines():
            if line.startswith(f"PID {r} "):
                pids.append(int(line.split()[2]))
    return pids


@pytest.mark.timeout(150)
def test_shm_corrupt_frame_np2_coordinated_abort():
    """The TCP chaos headline, on shm: one flipped byte in a shared ring
    aborts BOTH ranks with the wire-CRC diagnosis — and the job leaves
    no segment residue (survivor unlink + post-exit sweep)."""
    outs = run_distributed(
        2, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        extra_env={**_SHM_CHAOS_ENV,
                   "HOROVOD_FAULT_SPEC":
                       "shm.send:rank=1:nth=6:action=corrupt,1"})
    assert "SURVIVOR_ABORT 0" in outs[0], outs[0]
    assert "wire CRC" in outs[0], outs[0]
    assert "SURVIVOR_ABORT 1" in outs[1], outs[1]
    sweep_dead_segments(_worker_pids(outs))


@pytest.mark.timeout(150)
def test_shm_kill_rank_mid_step_np2_survivor_aborts_and_sweep_cleans():
    """A rank hard-dying mid-collective while the data plane rides shm:
    the survivor's PID-liveness probe converts the stalled ring wait
    into a typed abort (no hang), and the launcher-side
    ``sweep_dead_segments`` backstop reclaims the victim's segments."""
    outs = run_distributed(
        2, _SURVIVOR_BODY, timeout=120, expect_failure=True, retries=0,
        extra_env={**_SHM_CHAOS_ENV,
                   "HOROVOD_FAULT_SPEC":
                       "dispatch.collective:rank=1:nth=8:action=exit,9"})
    assert "SURVIVOR_ABORT 0" in outs[0], outs[0]
    assert "NO_FAULT_SEEN" not in outs[0], outs[0]
    pids = _worker_pids(outs)
    assert len(pids) == 2, outs
    # the exact call runner/launch.py makes after reaping its workers
    sweep_dead_segments(pids)
    left = {p for p in _residue()
            for pid in pids if f"/{SEG_PREFIX}{pid}-" in p}
    assert not left, f"kill-mid-step left segments: {sorted(left)}"


# ---------------------------------------------------------------------------
# the headline: HierarchicalAllreduce rides shm intra-host + TCP
# cross-host through the seam, bit-identical to all-TCP
# ---------------------------------------------------------------------------

_HIER_BODY = """
import hashlib
x = (np.arange(4096, dtype=np.float32) % 7) * (rank + 1) + rank
o = np.asarray(hvd.allreduce(x, op=hvd.Sum, name="h"))
print("SUM", rank, hashlib.sha1(o.tobytes()).hexdigest(), flush=True)
from horovod_tpu.core import metrics as _m
print("LINKS", rank,
      int(_m.registry.get_counter("transport_links_total", transport="shm")),
      int(_m.registry.get_counter("transport_links_total", transport="tcp")),
      flush=True)
"""


def _sums(outs):
    got = {}
    for r, out in enumerate(outs):
        for line in out.splitlines():
            if line.startswith(f"SUM {r} "):
                got[r] = line.split()[2]
    return got


@pytest.mark.timeout(300)
def test_hierarchical_np4_shm_intra_tcp_cross_bit_identical():
    """4 ranks as 2 simulated hosts x 2 slots: under ``auto`` every rank
    must classify exactly 1 intra-host link as shm and 2 cross-host
    links as TCP (cross_rank folds into the host identity), and the
    hierarchical allreduce result must be BIT-identical to the same job
    forced all-TCP."""
    auto = run_distributed(4, _HIER_BODY, timeout=240, local_size=2,
                           extra_env={"HOROVOD_TRANSPORT": "auto"})
    tcp = run_distributed(4, _HIER_BODY, timeout=240, local_size=2,
                          extra_env={"HOROVOD_TRANSPORT": "tcp"})
    sums_auto, sums_tcp = _sums(auto), _sums(tcp)
    assert len(sums_auto) == len(sums_tcp) == 4, (auto, tcp)
    assert len(set(sums_auto.values())) == 1, sums_auto  # ranks agree
    assert sums_auto == sums_tcp, (sums_auto, sums_tcp)  # transports agree
    for r, out in enumerate(auto):
        assert f"LINKS {r} 1 2" in out, (r, out)
    for r, out in enumerate(tcp):
        # forced tcp takes the pre-seam TcpMesh path: no links classified
        assert f"LINKS {r} 0 0" in out, (r, out)


# ---------------------------------------------------------------------------
# control words move as single machine words
# ---------------------------------------------------------------------------

def _counter_writer(name, n):
    from multiprocessing import shared_memory

    from horovod_tpu.transport.shm import _store_u64

    seg = shared_memory.SharedMemory(name=name)
    try:
        value = 0
        for _ in range(n):
            value += 251  # every store changes several bytes
            _store_u64(seg.buf, 64, value)
        _store_u64(seg.buf, 128, 1)
    finally:
        seg.close()


def test_control_word_access_is_never_torn():
    """The ring protocol (and hvd-mck's proof of it) takes a load or store
    of head/tail to be atomic.  A reader in another process must only ever
    see values the writer stored: ``struct.pack_into`` zero-fills and then
    writes a byte at a time, and a torn head once gave a negative run that
    killed the background loop of a two-process ResNet-50 job."""
    import multiprocessing as mp
    from multiprocessing import shared_memory

    from horovod_tpu.transport.shm import _load_u64, _store_u64

    seg = shared_memory.SharedMemory(create=True, size=256)
    try:
        _store_u64(seg.buf, 64, 0)
        _store_u64(seg.buf, 128, 0)
        proc = mp.get_context("spawn").Process(
            target=_counter_writer, args=(seg.name, 400_000))
        proc.start()
        last = reads = 0
        while _load_u64(seg.buf, 128) == 0 and proc.is_alive():
            value = _load_u64(seg.buf, 64)
            reads += 1
            assert value >= last and value % 251 == 0, (last, value)
            last = value
        proc.join(timeout=60)
        assert not proc.is_alive() and proc.exitcode == 0
        assert reads > 1000 and _load_u64(seg.buf, 64) == 251 * 400_000
    finally:
        seg.close()
        seg.unlink()
