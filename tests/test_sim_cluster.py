"""Simulated-cluster harness (horovod_tpu/sim/, docs/sim_cluster.md):
determinism of the shaped wire + churn schedule, and an end-to-end churn
run through the REAL driver and journaled server at small np.  The
bounded np=128 "large mesh" run lives in ci/chaos.sh (with
HOROVOD_LOCK_DEBUG=1 and a zero-lock-cycle assertion).
"""

import json

import pytest

from horovod_tpu.sim.cluster import COORDINATED_ABORT, SimCluster
from horovod_tpu.sim.wire import OP_OVERHEAD_BYTES, ShapedStore, ShapedWire


# ---------------------------------------------------------------------------
# shaped wire


def test_wire_jitter_stream_is_deterministic_per_link():
    a = ShapedWire("h000", seed=7, latency_s=0.001, jitter_s=0.0005,
                   bandwidth_bps=1e9)
    b = ShapedWire("h000", seed=7, latency_s=0.001, jitter_s=0.0005,
                   bandwidth_bps=1e9)
    other_link = ShapedWire("h001", seed=7, latency_s=0.001,
                            jitter_s=0.0005, bandwidth_bps=1e9)
    seq_a = [a.delay(1024) for _ in range(8)]
    seq_b = [b.delay(1024) for _ in range(8)]
    assert seq_a == seq_b
    assert seq_a != [other_link.delay(1024) for _ in range(8)]
    # preview() is a pure function: it never consumes the live stream.
    assert a.preview(1024, 8) == b.preview(1024, 8)
    assert [round(v, 9) for v in seq_a] != a.preview(1024, 8) or \
        seq_a == seq_b  # previews restart the stream from the beginning


def test_shaped_store_charges_batch_once(monkeypatch):
    """N ops through ``batch`` cost ONE latency term; the same N ops
    per-op cost N — the asymmetry the batching A/B measures."""
    from horovod_tpu.transport.store import MemoryStore

    sleeps = []
    monkeypatch.setattr("horovod_tpu.sim.wire.time.sleep",
                        lambda s: sleeps.append(s))
    wire = ShapedWire("link", seed=0, latency_s=0.010, jitter_s=0.0,
                      bandwidth_bps=1e9)
    store = ShapedStore(MemoryStore(), wire)
    ops = [("set", "s", f"k{i}", b"v") for i in range(10)]
    assert store.batch(ops) == [True] * 10
    assert len(sleeps) == 1
    batched_cost = sleeps[0]
    sleeps.clear()
    for _, scope, key, value in ops:
        store.set(scope, key, value)
    assert len(sleeps) == 10
    assert sum(sleeps) > 5 * batched_cost  # latency paid 10x, not 1x
    assert wire.injected_s == pytest.approx(batched_cost + sum(sleeps))
    assert store.get("s", "k0") == b"v"
    # Byte model sanity: bigger payloads cost more on a finite link.
    slow = ShapedWire("slow", seed=0, latency_s=0.0, jitter_s=0.0,
                      bandwidth_bps=1e6)
    assert slow.delay(10 * OP_OVERHEAD_BYTES) > slow.delay(1)


# ---------------------------------------------------------------------------
# schedule + digest determinism (the artifact's reproducibility witness)


def test_sim_schedule_and_digest_deterministic_under_seed():
    a = SimCluster(64, slots_per_host=8, seed=42, trace=False)
    b = SimCluster(64, slots_per_host=8, seed=42, trace=False)
    other = SimCluster(64, slots_per_host=8, seed=43, trace=False)
    assert a.schedule(6) == b.schedule(6)
    assert a.determinism_digest(6) == b.determinism_digest(6)
    assert a.determinism_digest(6) != other.determinism_digest(6)
    # The last event is always the coordinated abort.
    assert a.schedule(6)[-1] == (COORDINATED_ABORT, None)
    # Victims come from the static slot layout.
    for kind, victim in a.schedule(6)[:-1]:
        assert victim in a.identities


# ---------------------------------------------------------------------------
# end-to-end churn at small np (tier-1 sized; np=128 rides ci/chaos.sh)


def _events_attributed(attr, events, *phases):
    """What is certain of a few simulated events' attribution.  An event's
    wall time is 10-16 ms, and the share of it the spans cover follows the
    machine (0.86-0.93 here, either side of the 0.90 floor that the
    np=128 and np=512 artifacts hold, alone, over many events).  Certain
    are: one CHURN_EVENT window an event, every phase such an event passes
    through present, and each microsecond counted once (the shares are
    exclusive, so they add up to the coverage, which cannot pass 1)."""
    assert attr["event_count"] == events, attr
    for phase in phases:
        assert attr["phase_share"][phase] > 0.0, (phase, attr)
    assert abs(sum(attr["phase_share"].values())
               - attr["coverage"]) < 1e-3, attr
    assert 0.0 < attr["coverage"] <= 1.0, attr


def test_sim_churn_epochs_and_coordinated_abort_np16(monkeypatch):
    monkeypatch.delenv("HOROVOD_SECRET_KEY", raising=False)
    cluster = SimCluster(16, slots_per_host=8, seed=7, lease_timeout=1.0,
                         renew_period=0.2)
    rec = cluster.run(events=3)
    assert rec["np"] == 16 and rec["hosts"] == 2
    # Every scheduled event advanced exactly one epoch, abort included.
    assert rec["final_epoch"] == 3
    assert [e["epoch"] for e in rec["events"]] == [1, 2, 3]
    assert rec["events"][-1]["kind"] == COORDINATED_ABORT
    # The run produced the same attribution document a live run would.
    _events_attributed(rec["attribution"], 3, "journal_fsync",
                       "batch_apply", "http_roundtrip")
    assert rec["sim_wire_delay_s"] > 0.0
    assert rec["journal_bytes"] > 0
    assert rec["determinism"]["digest"] == \
        SimCluster(16, slots_per_host=8, seed=7,
                   trace=False).determinism_digest(3)
    json.dumps(rec)  # artifact must be JSON-serializable as-is


# ---------------------------------------------------------------------------
# self-healing demotion lane (docs/elastic.md "self-healing demotion")


def test_sim_demotion_schedule_and_digest_deterministic():
    a = SimCluster(64, slots_per_host=8, seed=42, trace=False)
    b = SimCluster(64, slots_per_host=8, seed=42, trace=False)
    other = SimCluster(64, slots_per_host=8, seed=43, trace=False)
    assert a.demotion_schedule(3) == b.demotion_schedule(3)
    assert a.demotion_digest(3) == b.demotion_digest(3)
    assert a.demotion_digest(3) != other.demotion_digest(3)
    plan = a.demotion_schedule(3)
    # Distinct victims, never the coordinator's host.
    assert len(set(plan)) == 3
    assert a.hostnames[0] not in plan
    # The demotion lane shares nothing with the churn schedule: asking
    # for it must not perturb churn digests for the same seed.
    assert a.determinism_digest(6) == \
        SimCluster(64, slots_per_host=8, seed=42,
                   trace=False).determinism_digest(6)
    with pytest.raises(ValueError):
        a.demotion_schedule(len(a.hostnames))


def test_sim_demotion_np16(monkeypatch):
    """A demotion report through the REAL driver at np=16: blacklist,
    epoch advance attributed to cause=demotion, and the flag->first-round
    latency curve — the np=128 artifact run rides ci/chaos.sh."""
    monkeypatch.delenv("HOROVOD_SECRET_KEY", raising=False)
    cluster = SimCluster(16, slots_per_host=8, seed=7, lease_timeout=1.0,
                         renew_period=0.2)
    rec = cluster.run_demotion(demotions=1)
    assert rec["metric"] == "sim_demotion"
    assert rec["np"] == 16 and rec["hosts"] == 2
    # One shed host of 8 slots: the capacity floor self-lowered to 8.
    assert rec["min_np"] == 8
    assert rec["final_epoch"] == 1
    assert rec["driver_demotion_transitions"] == 1
    (event,) = rec["events"]
    assert event["victim_host"] == rec["determinism"]["schedule"][0]
    assert 0 < event["flag_to_epoch_ms"] <= event["flag_to_first_round_ms"]
    _events_attributed(rec["attribution"], 1, "journal_fsync",
                       "batch_apply", "http_roundtrip")
    assert rec["determinism"]["digest"] == SimCluster(
        16, slots_per_host=8, seed=7, trace=False,
        min_np=rec["min_np"]).demotion_digest(1)
    json.dumps(rec)  # artifact must be JSON-serializable as-is


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_sim_demotion_np128_artifact(monkeypatch):
    """Scale proof + the committed artifact's non-fabrication witness:
    generates ``benchmarks/results/sim_demotion_np128.json`` through the
    real driver at np=128 and asserts every claim the artifact makes —
    the digest reproduces from a fresh same-seed cluster, every scheduled
    demotion became a cause=demotion driver transition, and attribution
    coverage holds the 0.90 floor.  Run by ci/chaos.sh."""
    import os

    from .helpers import REPO_ROOT

    monkeypatch.delenv("HOROVOD_SECRET_KEY", raising=False)
    cluster = SimCluster(128, slots_per_host=8, seed=42,
                         lease_timeout=1.5, renew_period=0.25)
    rec = cluster.run_demotion(demotions=3)
    assert rec["np"] == 128 and rec["hosts"] == 16
    assert rec["final_epoch"] == 3
    assert rec["driver_demotion_transitions"] == 3
    assert [e["victim_host"] for e in rec["events"]] == \
        rec["determinism"]["schedule"]
    for e in rec["events"]:
        assert 0 < e["flag_to_epoch_ms"] <= e["flag_to_first_round_ms"]
    assert rec["attribution"]["coverage"] >= 0.90, rec["attribution"]
    # Non-fabrication: the digest is a pure function of (seed, topology,
    # capacity floor, wire shaping) — a hand-edited artifact cannot
    # produce it without re-running the harness.
    assert rec["determinism"]["digest"] == SimCluster(
        128, slots_per_host=8, seed=42, trace=False,
        min_np=rec["min_np"]).demotion_digest(3)
    out = os.path.join(REPO_ROOT, "benchmarks", "results",
                       "sim_demotion_np128.json")
    with open(out, "w") as f:
        f.write(json.dumps(rec) + "\n")
    with open(out) as f:
        assert json.loads(f.read()) == rec

# ---------------------------------------------------------------------------
# zero-restart reshard lane (docs/elastic.md "Live resharding")


def test_sim_reshard_schedule_and_digest_deterministic():
    a = SimCluster(64, slots_per_host=8, seed=42, trace=False)
    b = SimCluster(64, slots_per_host=8, seed=42, trace=False)
    other = SimCluster(64, slots_per_host=8, seed=43, trace=False)
    assert a.reshard_schedule(4) == b.reshard_schedule(4)
    assert a.reshard_digest(4) == b.reshard_digest(4)
    assert a.reshard_digest(4) != other.reshard_digest(4)
    # The reshard lane shares nothing with the churn or demotion
    # schedules: asking for it must not perturb their digests.
    assert a.determinism_digest(6) == \
        SimCluster(64, slots_per_host=8, seed=42,
                   trace=False).determinism_digest(6)
    assert a.demotion_digest(3) == \
        SimCluster(64, slots_per_host=8, seed=42,
                   trace=False).demotion_digest(3)


def test_sim_reshard_np16(monkeypatch):
    """A preemption kill through the REAL driver at np=16: lease expiry,
    reshard-marked publish, survivor acks, commit record, cause=reshard
    transition, zero fallbacks — the np=512 artifact run is the same
    runner via ``python -m horovod_tpu.sim --reshards``."""
    monkeypatch.delenv("HOROVOD_SECRET_KEY", raising=False)
    monkeypatch.delenv("HOROVOD_RESHARD", raising=False)
    cluster = SimCluster(16, slots_per_host=8, seed=7, lease_timeout=1.0,
                         renew_period=0.2)
    rec = cluster.run_reshard(kills=1)
    assert rec["metric"] == "sim_reshard"
    assert rec["np"] == 16 and rec["reshard_enabled"] is True
    assert rec["final_epoch"] == 1
    (event,) = rec["events"]
    assert event["marked"] is True
    assert event["victim"] == rec["determinism"]["schedule"][0]
    assert 0 < event["kill_to_epoch_ms"] <= event["kill_to_commit_ms"] \
        <= event["kill_to_first_round_ms"]
    assert rec["driver_reshard_transitions"] == 1
    assert rec["reshard_fallbacks"] == 0
    _events_attributed(rec["attribution"], 1, "journal_fsync",
                       "batch_apply", "http_roundtrip")
    assert rec["determinism"]["digest"] == SimCluster(
        16, slots_per_host=8, seed=7, trace=False).reshard_digest(1)
    json.dumps(rec)  # artifact must be JSON-serializable as-is


def test_sim_reshard_kill_switch_baseline_arm(monkeypatch):
    """HOROVOD_RESHARD=0 is the committed A/B's baseline arm: the same
    kill advances the epoch with NO marker, NO pending commit, and NO
    cause=reshard transition."""
    monkeypatch.delenv("HOROVOD_SECRET_KEY", raising=False)
    monkeypatch.setenv("HOROVOD_RESHARD", "0")
    cluster = SimCluster(16, slots_per_host=8, seed=7, lease_timeout=1.0,
                         renew_period=0.2, trace=False)
    rec = cluster.run_reshard(kills=1)
    assert rec["reshard_enabled"] is False
    assert rec["final_epoch"] == 1
    assert rec["events"][0]["marked"] is False
    assert rec["driver_reshard_transitions"] == 0
    assert rec["reshard_fallbacks"] == 0


def test_sim_reshard_respects_min_np_quorum_during_demotion(monkeypatch):
    """Reshard/demotion interplay regression: a demotion that lands the
    world exactly AT quorum advances (and, with resharding on, rides the
    reshard path as a pure shrink); churn that would take it BELOW
    ``min_np`` must park the driver at the capacity gate — the epoch
    holds and no reshard is ever armed for a sub-quorum world."""
    import time

    monkeypatch.delenv("HOROVOD_SECRET_KEY", raising=False)
    monkeypatch.delenv("HOROVOD_RESHARD", raising=False)
    cluster = SimCluster(4, slots_per_host=1, seed=7, lease_timeout=1.0,
                         renew_period=0.2, trace=False, min_np=3)
    assert cluster.min_np == 3
    cluster.start()
    try:
        for _ in range(2):
            cluster.renewal_round()
            time.sleep(cluster.renew_period)
        # Demotion to exactly min_np: allowed, and the advance is a
        # reshard-marked pure shrink (no joiners) that commits.
        target = cluster.driver.epoch + 1
        victim_host = cluster.hostnames[1]
        cluster.inject_demotion(victim_host)
        cluster.await_epoch(target, timeout=30.0)
        assert cluster.driver._reshard_pending is not None
        cluster.ack_round(cluster.driver.epoch)
        for w in cluster.workers.values():
            if w.hostname == victim_host:
                w.renewing = False
        cluster.await_reshard_commit(timeout=30.0)
        # Second demotion would leave 2 < min_np=3: the capacity gate
        # must hold the epoch and never arm a reshard.
        epoch_at_quorum = cluster.driver.epoch
        cluster.inject_demotion(cluster.hostnames[2])
        deadline = time.monotonic() + 4 * cluster.lease_timeout
        while time.monotonic() < deadline:
            cluster.renewal_round()
            cluster.driver._wakeup.set()
            time.sleep(cluster.renew_period)
        assert cluster.driver.epoch == epoch_at_quorum, \
            "driver advanced the epoch below min_np quorum"
        assert cluster.driver._reshard_pending is None, \
            "a reshard was armed for a sub-quorum world"
        assert not cluster.driver.finished()
    finally:
        cluster.stop()

# ---------------------------------------------------------------------------
# negotiation fan-in sim (horovod_tpu/sim/negotiation.py, docs/data_plane.md
# "Negotiation fan-in"): the REAL coordinator mask path at large np over an
# arithmetic wire clock — no processes, no sleeping.


def test_sim_negotiation_counters_and_bit_exactness():
    """np=64 smoke of every claim the big artifact makes: the real
    coordinator ingests O(ranks) star frames vs O(hosts) fan-in frames
    (counter-asserted against controller_ingress_frames_total's backing
    counter), the agreed mask is bit-identical across shapes, and the
    fabricated trace attributes >= 0.90 of every step."""
    from horovod_tpu.sim.negotiation import SimNegotiation

    rec = SimNegotiation(64, slots_per_host=8, seed=0).run(cycles=3)
    assert rec["star"]["ingress_frames_per_cycle"] == 63
    assert rec["fanin"]["ingress_frames_per_cycle"] == 7 + 7
    assert rec["star"]["reply_mask"] == rec["fanin"]["reply_mask"] != 0
    assert rec["fanin"]["cycle_ms_p50"] < rec["star"]["cycle_ms_p50"]
    for mode in ("star", "fanin"):
        assert rec["attribution"][mode]["coverage"] >= 0.90, \
            rec["attribution"]
    assert rec["attribution"]["fanin"]["fanin_share"] > 0


def test_sim_negotiation_digest_deterministic():
    from horovod_tpu.sim.negotiation import SimNegotiation

    a = SimNegotiation(128, slots_per_host=8, seed=3)
    b = SimNegotiation(128, slots_per_host=8, seed=3)
    other = SimNegotiation(128, slots_per_host=8, seed=4)
    assert a.determinism_digest() == b.determinism_digest()
    assert a.determinism_digest() != other.determinism_digest()


@pytest.mark.slow
def test_sim_negotiation_np4096_artifact():
    """Regenerates ``benchmarks/results/sim_negotiation_np4096.json``
    (the committed star-vs-tree latency curves, np=1024-4096) through
    the real coordinator and asserts every claim it makes — monotone
    ingress reduction, bit-identical masks at every size, attribution
    coverage >= 0.90, and digests that reproduce from fresh same-seed
    sims (the non-fabrication witness).  Run by ci/chaos.sh."""
    import os

    from horovod_tpu.sim.negotiation import SimNegotiation, run_curve

    from .helpers import REPO_ROOT

    rec = run_curve([1024, 2048, 4096], slots_per_host=8, seed=0,
                    cycles=6)
    assert [p["np"] for p in rec["curve"]] == [1024, 2048, 4096]
    for p in rec["curve"]:
        star, fanin = p["star"], p["fanin"]
        assert star["ingress_frames_per_cycle"] == p["np"] - 1
        assert fanin["ingress_frames_per_cycle"] == \
            (p["hosts"] - 1) + (p["slots_per_host"] - 1)
        assert star["reply_mask"] == fanin["reply_mask"] != 0
        assert p["cycle_speedup_p50"] > 2.0, p
        for mode in ("star", "fanin"):
            assert p["attribution"][mode]["coverage"] >= 0.90, \
                p["attribution"]
        # Non-fabrication: pure function of (seed, topology, shaping).
        assert rec["determinism"]["digests"][str(p["np"])] == \
            SimNegotiation(p["np"], slots_per_host=8,
                           seed=0).determinism_digest()
    out = os.path.join(REPO_ROOT, "benchmarks", "results",
                       "sim_negotiation_np4096.json")
    with open(out, "w") as f:
        f.write(json.dumps(rec) + "\n")
    with open(out) as f:
        assert json.loads(f.read()) == rec
