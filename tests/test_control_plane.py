"""Control-plane survivability: journal replay, torn-write recovery,
server restart, and driver crash-recovery (docs/control_plane.md).

The fast, in-process half of the survivability proof; the end-to-end
SIGKILL-and-restart chaos runs live in
tests/test_fault_injection_elastic.py's chaos lane.
"""

import json
import random
import shutil

import pytest

from horovod_tpu.transport.journal import (
    OP_DELETE,
    OP_SET,
    decode_op,
    encode_op,
    iter_frames,
    pack_frame,
)
from horovod_tpu.transport.store import (
    LEASE_SCOPE,
    DurableMemoryStore,
    HTTPStoreClient,
)
from horovod_tpu.runner.rendezvous import ExternalRendezvous, RendezvousServer


# ---------------------------------------------------------------------------
# frame / op encoding


class TestFrames:
    def test_op_roundtrip(self):
        for op, key, value in [(OP_SET, "scope/key", b"value"),
                               (OP_SET, "a/b", b""),
                               (OP_DELETE, "metrics/rank-0", b"")]:
            assert decode_op(encode_op(op, key, value)) == (op, key, value)

    def test_iter_frames_stops_at_crc_mismatch(self):
        blob = pack_frame(b"one") + pack_frame(b"two")
        corrupt = bytearray(blob)
        corrupt[-1] ^= 0xFF  # flip a byte of the second payload
        assert [p for _, p in iter_frames(bytes(corrupt))] == [b"one"]

    def test_iter_frames_rejects_absurd_length(self):
        import struct

        # A corrupt header claiming a huge payload must read as "torn",
        # not attempt the allocation.
        blob = struct.pack("<QI", 2 ** 62, 0) + b"x" * 64
        assert list(iter_frames(blob)) == []


# ---------------------------------------------------------------------------
# journal replay exactness


def _apply_random_ops(store, mirror, rng, n_ops):
    scopes = ["rank_and_size", "lease", "metrics"]
    for _ in range(n_ops):
        scope = rng.choice(scopes)
        key = f"k{rng.randrange(12)}"
        if rng.random() < 0.25 and mirror:
            flat = rng.choice(sorted(mirror))
            s, k = flat.split("/", 1)
            store.delete(s, k)
            mirror.pop(flat, None)
        else:
            value = bytes(rng.randrange(256)
                          for _ in range(rng.randrange(0, 64)))
            store.set(scope, key, value)
            mirror[f"{scope}/{key}"] = value


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_replay_equals_precrash_state_random_ops(tmp_path, seed):
    """Property: for a randomized op sequence (sets/deletes across scopes,
    with compactions forced every few ops), a fresh store over the same
    directory replays to the EXACT pre-close state."""
    rng = random.Random(seed)
    jdir = str(tmp_path / f"j{seed}")
    store = DurableMemoryStore(jdir, fsync=False,
                               snapshot_every=rng.choice([3, 7, 1000]))
    mirror = {}
    _apply_random_ops(store, mirror, rng, 120)
    store.close()

    recovered = DurableMemoryStore(jdir, fsync=False)
    assert recovered._data == mirror
    recovered.close()


def test_torn_write_every_offset_recovers_longest_prefix(tmp_path):
    """Truncate the journal at EVERY byte offset of the final record: the
    replay must recover exactly the state before that record — never
    misparse, never lose an earlier op (the PR-4 every-prefix fuzz
    discipline applied to the WAL)."""
    jdir = tmp_path / "j"
    store = DurableMemoryStore(str(jdir), fsync=False,
                               snapshot_every=10 ** 9)
    store.set("s", "a", b"alpha")
    store.set("s", "b", b"beta")
    store.delete("s", "a")
    state_before_final = dict(store._data)
    store.set("s", "final", b"the-final-record-payload")
    state_with_final = dict(store._data)
    store.close()

    jpath = jdir / "journal-00000000"
    blob = jpath.read_bytes()
    ends = [end for end, _ in iter_frames(blob)]
    assert ends[-1] == len(blob)
    final_start = ends[-2]

    # Sanity: the untruncated journal replays the full state.
    full = DurableMemoryStore(str(jdir), fsync=False)
    assert full._data == state_with_final
    full.close()

    for cut in range(final_start, len(blob)):
        case = tmp_path / f"cut{cut}"
        shutil.copytree(jdir, case)
        with open(case / "journal-00000000", "r+b") as f:
            f.truncate(cut)
        recovered = DurableMemoryStore(str(case), fsync=False)
        assert recovered._data == state_before_final, f"cut at {cut}"
        # The torn tail was truncated away: appending must extend the
        # valid prefix, not concatenate after garbage.
        recovered.set("s", "post", b"post-recovery")
        recovered.close()
        again = DurableMemoryStore(str(case), fsync=False)
        assert again._data == {**state_before_final,
                               "s/post": b"post-recovery"}, f"cut at {cut}"
        again.close()
        shutil.rmtree(case)


def test_aborted_compaction_falls_back_to_previous_generation(tmp_path):
    """A snapshot without its commit marker (crash mid-compaction) is
    ignored; the previous generation still holds every op."""
    jdir = tmp_path / "j"
    store = DurableMemoryStore(str(jdir), fsync=False, snapshot_every=5)
    for i in range(8):  # compacts at op 5 -> generation 1
        store.set("s", f"k{i}", b"v%d" % i)
    expect = dict(store._data)
    store.close()
    assert (jdir / "snap-00000001").exists()

    # Simulate a crash mid-compaction to generation 2: valid frames but
    # no SNAP_END commit marker, and no journal-2 yet.
    torn = pack_frame(b"HVDSNAP1") + pack_frame(
        encode_op(OP_SET, "s/k0", b"stale"))
    (jdir / "snap-00000002").write_bytes(torn)

    recovered = DurableMemoryStore(str(jdir), fsync=False)
    assert recovered._data == expect
    recovered.close()


def test_journal_disabled_is_plain_memory_store(tmp_path):
    store = DurableMemoryStore(None)
    store.set("s", "k", b"v")
    assert store.get("s", "k") == b"v"
    assert store.pop("s", "k") == b"v"
    assert store.pop("s", "k") is None
    store.close()
    assert list(tmp_path.iterdir()) == []


# ---------------------------------------------------------------------------
# batched transactions (ISSUE 15 tentpole): atomic group journaling


def test_batch_group_torn_at_every_offset_is_all_or_nothing(tmp_path):
    """Truncate the journal at EVERY byte offset of a batched
    transaction's group frame: replay must land on exactly the pre-batch
    state (frame torn ⇒ NONE of the group's ops) or the post-batch state
    (frame intact ⇒ ALL of them) — a partially-applied batch must be
    unobservable at every single cut point."""
    jdir = tmp_path / "j"
    store = DurableMemoryStore(str(jdir), fsync=False,
                               snapshot_every=10 ** 9)
    store.set("s", "keep", b"keep-me")
    store.set("s", "doomed", b"delete-me")
    state_before = dict(store._data)
    results = store.batch([
        ("set", "s", "a", b"alpha"),
        ("set", "lease", "h0:0", b'{"renewals": 1}'),
        ("delete", "s", "doomed"),
        ("get", "s", "keep"),
        ("set", "s", "a", b"alpha-2"),  # same-key overwrite inside group
        ("keys", "s"),
    ])
    assert results[3] == b"keep-me"
    assert results[5] == ["a", "keep"]
    state_after = dict(store._data)
    assert state_after != state_before
    store.close()

    jpath = jdir / "journal-00000000"
    blob = jpath.read_bytes()
    ends = [end for end, _ in iter_frames(blob)]
    assert ends[-1] == len(blob)
    group_start = ends[-2]

    seen = set()
    for cut in range(group_start, len(blob) + 1):
        case = tmp_path / f"cut{cut}"
        shutil.copytree(jdir, case)
        with open(case / "journal-00000000", "r+b") as f:
            f.truncate(cut)
        recovered = DurableMemoryStore(str(case), fsync=False)
        if recovered._data == state_before:
            seen.add("none")
        elif recovered._data == state_after:
            seen.add("all")
        else:
            pytest.fail(f"partial batch visible at cut {cut}: "
                        f"{recovered._data}")
        recovered.close()
        shutil.rmtree(case)
    assert seen == {"none", "all"}


def test_batch_http_roundtrip_per_op_results(monkeypatch):
    """One signed ``POST /batch`` carries ordered PUT/GET/DELETE/KEYS and
    returns positional per-op results with the same semantics as the
    per-op routes."""
    monkeypatch.setenv("HOROVOD_SECRET_KEY", "cp-test-secret")
    server = RendezvousServer("127.0.0.1", job_secret=b"cp-test-secret")
    port = server.start()
    client = HTTPStoreClient("127.0.0.1", port)
    results = client.batch([
        ("set", "s", "a", b"1"),
        ("set", "s", "b", b"2"),
        ("get", "s", "a"),
        ("get", "s", "absent"),
        ("keys", "s"),
        ("delete", "s", "a"),
        ("delete", "s", "a"),  # second delete: already gone
        ("keys", "s"),
    ])
    assert results == [True, True, b"1", None, ["a", "b"],
                       True, False, ["b"]]
    assert client._batch_unsupported is False
    server.stop()


def test_batch_falls_back_per_op_against_old_protocol_server(monkeypatch):
    """A server without the /batch route (old protocol, or the knob held
    off for A/B) answers 404; the client degrades to per-op calls with
    identical results and remembers (sticky) not to retry /batch."""
    monkeypatch.setenv("HOROVOD_SECRET_KEY", "cp-test-secret")
    monkeypatch.setenv("HOROVOD_RENDEZVOUS_BATCH", "0")  # server-side off
    server = RendezvousServer("127.0.0.1", job_secret=b"cp-test-secret")
    port = server.start()
    monkeypatch.delenv("HOROVOD_RENDEZVOUS_BATCH")  # client-side on
    client = HTTPStoreClient("127.0.0.1", port)
    ops = [("set", "s", "k", b"v"), ("get", "s", "k"), ("keys", "s"),
           ("delete", "s", "k"), ("get", "s", "k")]
    assert client.batch(ops) == [True, b"v", ["k"], True, None]
    assert client._batch_unsupported is True
    # Sticky: the second batch goes straight to per-op, still correct.
    assert client.batch([("set", "s", "x", b"y"), ("get", "s", "x")]) \
        == [True, b"y"]
    server.stop()


# ---------------------------------------------------------------------------
# host-level fan-in failure behavior (docs/control_plane.md)


def test_fanin_aggregator_death_degrades_to_direct_push(tmp_path):
    """The chaos property the fan-in must keep: peers spool only under a
    LIVE aggregator heartbeat; when the aggregator dies, submit() returns
    False within ~1.5 periods and the caller pushes directly — the host
    never goes silent, so no surviving rank's lease expires."""
    import time as time_mod

    from horovod_tpu.elastic.fanin import HostFanin
    from horovod_tpu.transport.store import MemoryStore

    store = MemoryStore()
    period = 0.05
    spool = str(tmp_path / "spool")
    agg = HostFanin(store, local_rank=0, period=period, spool_dir=spool)
    peer = HostFanin(store, local_rank=1, period=period, spool_dir=spool)

    def lease_op(rank, n):
        return ("set", LEASE_SCOPE, f"h0:{rank}",
                json.dumps({"renewals": n}).encode())

    # Before the aggregator's first forward there is no heartbeat:
    # the peer must push directly (False), not trust the spool.
    assert peer.submit([lease_op(1, 1)]) is False
    store.batch([lease_op(1, 1)])  # what the caller does on False

    # Aggregator forwards: its own ops + any spooled peer ops land in
    # ONE batch, and the heartbeat goes live.
    assert agg.submit([lease_op(0, 1)]) is True
    assert store.get(LEASE_SCOPE, "h0:0") is not None

    # Live aggregator: the peer's ops are spooled (True) and the NEXT
    # aggregator period delivers them.
    assert peer.submit([lease_op(1, 2)]) is True
    assert agg.submit([lease_op(0, 2)]) is True
    assert json.loads(store.get(LEASE_SCOPE, "h0:1"))["renewals"] == 2

    # An UNCHANGED spool is not re-forwarded: a dead peer's stale lease
    # must age out, not be renewed on its behalf.
    store.delete(LEASE_SCOPE, "h0:1")
    assert agg.submit([lease_op(0, 3)]) is True
    assert store.get(LEASE_SCOPE, "h0:1") is None

    # Aggregator dies (stops submitting): once the heartbeat goes stale
    # the peer degrades to direct pushes — no silence, no hang.
    time_mod.sleep(2.5 * period)
    assert peer.submit([lease_op(1, 3)]) is False
    store.batch([lease_op(1, 3)])
    assert json.loads(store.get(LEASE_SCOPE, "h0:1"))["renewals"] == 3


# ---------------------------------------------------------------------------
# server restart + keys endpoint


def test_server_restart_replays_state_and_serves_keys(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_SECRET_KEY", "cp-test-secret")
    jdir = str(tmp_path / "j")
    server = RendezvousServer("127.0.0.1", job_secret=b"cp-test-secret",
                              journal_dir=jdir)
    port = server.start()
    client = HTTPStoreClient("127.0.0.1", port)
    client.set("rank_and_size", "localhost:0", b'{"rank": 0}')
    client.set(LEASE_SCOPE, "localhost:0", b'{"renewals": 3}')
    client.set(LEASE_SCOPE, "otherhost:0", b'{"renewals": 1}')
    assert client.keys(LEASE_SCOPE) == ["localhost:0", "otherhost:0"]
    server.stop()  # SIGKILL-alike for state purposes: nothing flushed late

    server2 = RendezvousServer("127.0.0.1", job_secret=b"cp-test-secret",
                               journal_dir=jdir)
    port2 = server2.start()
    client2 = HTTPStoreClient("127.0.0.1", port2)
    assert client2.get("rank_and_size", "localhost:0") == b'{"rank": 0}'
    assert client2.keys(LEASE_SCOPE) == ["localhost:0", "otherhost:0"]
    assert client2.keys("empty_scope") == []
    server2.stop()


def test_external_rendezvous_adapter_matches_server_surface(tmp_path,
                                                            monkeypatch):
    monkeypatch.setenv("HOROVOD_SECRET_KEY", "cp-test-secret")
    server = RendezvousServer("127.0.0.1", job_secret=b"cp-test-secret")
    port = server.start()
    ext = ExternalRendezvous("127.0.0.1", port)
    assert ext.port == port
    ext.publish_slots([{
        "hostname": "localhost", "rank": 0, "local_rank": 0,
        "cross_rank": 0, "size": 1, "local_size": 1, "cross_size": 1,
        "epoch": 0,
    }])
    raw = ext.get("rank_and_size", "localhost:0")
    assert json.loads(raw.decode())["rank"] == 0
    assert ext.keys("rank_and_size") == ["localhost:0"]
    ext.stop()  # no-op: must NOT kill the external server
    assert ext.get("rank_and_size", "localhost:0") is not None
    server.stop()


# ---------------------------------------------------------------------------
# driver crash-recovery


def test_driver_recovers_epoch_and_readopts_leased_workers(tmp_path,
                                                           monkeypatch):
    """A restarted driver over a journaled store re-adopts the epoch and
    every live-leased identity instead of respawning the world."""
    from horovod_tpu.elastic.discovery import FixedHosts, HostManager
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner.hosts import parse_hosts

    monkeypatch.delenv("HOROVOD_SECRET_KEY", raising=False)
    jdir = str(tmp_path / "j")
    hosts = "localhost:1,127.0.0.1:1"

    server = RendezvousServer("127.0.0.1", journal_dir=jdir)
    server.start()
    spawned = []
    driver = ElasticDriver(server,
                           HostManager(FixedHosts(parse_hosts(hosts))),
                           min_np=2, lease_timeout=60.0)
    driver.start(lambda slot, epoch: spawned.append(
        (f"{slot.hostname}:{slot.local_rank}", epoch)))
    assert sorted(spawned) == [("127.0.0.1:0", 0), ("localhost:0", 0)]
    # Workers renew their leases (what the metrics pusher does).
    for identity in ("localhost:0", "127.0.0.1:0"):
        server.set(LEASE_SCOPE, identity,
                   json.dumps({"renewals": 1, "epoch": 0}).encode())
    driver.stop()
    driver._discovery_thread.join(timeout=10)
    server.stop()  # driver + server die together (launcher crash)

    server2 = RendezvousServer("127.0.0.1", journal_dir=jdir)
    server2.start()
    spawned2 = []
    driver2 = ElasticDriver(server2,
                            HostManager(FixedHosts(parse_hosts(hosts))),
                            min_np=2, lease_timeout=60.0)
    assert driver2.recover_from_store() is True
    assert driver2.epoch == driver.epoch
    driver2.start(lambda slot, epoch: spawned2.append(
        (f"{slot.hostname}:{slot.local_rank}", epoch)))
    # Live-leased workers re-adopted: NOBODY respawned, epoch unchanged.
    assert spawned2 == []
    assert driver2.epoch == driver.epoch
    driver2.stop()
    driver2._discovery_thread.join(timeout=10)
    server2.stop()


def test_driver_recover_is_noop_on_fresh_store(tmp_path):
    from horovod_tpu.elastic.discovery import FixedHosts, HostManager
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner.hosts import parse_hosts

    server = RendezvousServer("127.0.0.1")
    server.start()
    driver = ElasticDriver(server,
                           HostManager(FixedHosts(parse_hosts("localhost:1"))),
                           min_np=1)
    assert driver.recover_from_store() is False
    assert driver.epoch == 0
    server.stop()


# ---------------------------------------------------------------------------
# control-plane attribution (docs/observability.md)


def test_churn_attribution_covers_90pct_at_np8():
    """Acceptance floor for hvd-control-path: over a real np=8 churn run
    (traced server + traced driver-side client), the disjoint phase carve
    must explain at least 90% of every churn event's wall time."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "controller_sim", os.path.join(
            os.path.dirname(__file__), "..", "benchmarks",
            "controller_sim.py"))
    controller_sim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(controller_sim)

    rec = controller_sim.run_churn_case(8, events=3, trace=True)
    attr = rec["attribution"]
    assert attr["coverage"] >= 0.90, attr
    # The carve must name the dominant cost, not dump it in one bucket:
    # churn is HTTP round-trips with a real journal-fsync share.
    assert attr["phase_share"]["http_roundtrip"] > 0.3, attr
    assert attr["phase_share"]["journal_fsync"] > 0.0, attr
