"""Observability plane: metrics registry + /metrics scrape, cross-rank
merged timeline, flight-recorder post-mortems (docs/observability.md).

Fast unit tiers first (registry semantics, Prometheus rendering, flight
ring, trace alignment, stall-inspector surfacing, runtime timeline
toggles); the np=2 end-to-end proofs — a live ``GET /metrics`` scrape
with cross-rank latency histograms, and a merged two-rank trace where
both ranks' lanes share a cycle id — are chaos-marked so they sort after
the fast tiers (tier-1 budget rule: heavy multiprocess jobs run late).
"""

from __future__ import annotations

import json
import time
from collections import Counter

import numpy as np
import pytest

from horovod_tpu.core import flight_recorder, metrics

from .helpers import run_distributed


@pytest.fixture(autouse=True)
def _clean_registry():
    """Registry/ring state must not leak between tests."""
    metrics.registry.reset()
    flight_recorder.recorder.clear()
    yield
    metrics.configure(None)
    metrics.registry.reset()
    flight_recorder.recorder.clear()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


@pytest.mark.smoke
class TestRegistry:
    def test_counter_accumulates(self):
        metrics.inc("faults_injected_total")
        metrics.inc("faults_injected_total", 2)
        assert metrics.registry.get_counter("faults_injected_total") == 3

    def test_gauge_overwrites(self):
        metrics.set_gauge("tensor_queue_depth", 5)
        metrics.set_gauge("tensor_queue_depth", 2)
        assert metrics.registry.get_gauge("tensor_queue_depth") == 2

    def test_labels_partition_series(self):
        metrics.inc("rendezvous_store_ops_total", op="get")
        metrics.inc("rendezvous_store_ops_total", op="get")
        metrics.inc("rendezvous_store_ops_total", op="set")
        assert metrics.registry.get_counter(
            "rendezvous_store_ops_total", op="get") == 2
        assert metrics.registry.get_counter(
            "rendezvous_store_ops_total", op="set") == 1

    def test_histogram_buckets_and_sum(self):
        for v in (1e-5, 1e-5, 0.5, 1e9):  # last lands in overflow
            metrics.observe("controller_cycle_seconds", v)
        snap = metrics.registry.snapshot()
        h = snap["histograms"]["controller_cycle_seconds"]
        assert h["count"] == 4
        assert h["sum"] == pytest.approx(1e9 + 0.5 + 2e-5)
        assert len(h["counts"]) == len(metrics.BUCKET_BOUNDS) + 1
        assert sum(h["counts"]) == 4
        assert h["counts"][-1] == 1  # the +Inf overflow observation

    def test_disabled_is_a_noop(self):
        """Nothing is recorded *while disabled*: the three series read the
        same after the calls as before them.  (Not "absent from a snapshot
        taken afterwards": the registry is the process's, and a runtime
        that an earlier test of this worker left behind writes to it, in a
        view or from a thread, as soon as recording is on again.)"""
        def series():
            hists = metrics.registry.snapshot()["histograms"]
            return (metrics.registry.get_counter("faults_injected_total"),
                    metrics.registry.get_gauge("tensor_queue_depth"),
                    hists.get("controller_cycle_seconds"))

        metrics.configure(False)
        try:
            before = series()
            metrics.inc("faults_injected_total")
            metrics.observe("controller_cycle_seconds", 1.0)
            metrics.set_gauge("tensor_queue_depth", 9)
            after = series()
        finally:
            metrics.configure(True)
        assert after == before
        # The same calls do record once it is on again.
        metrics.inc("faults_injected_total")
        assert metrics.registry.get_counter("faults_injected_total") \
            == before[0] + 1

    def test_flat_roundtrip(self):
        flat = metrics.flat("x_total", op="GET", rank="3")
        assert flat == 'x_total{op="GET",rank="3"}'
        base, labels = metrics.parse_flat(flat)
        assert base == "x_total" and labels == {"op": "GET", "rank": "3"}
        assert metrics.parse_flat("plain") == ("plain", {})

    def test_flat_rejects_quotes_in_values(self):
        with pytest.raises(ValueError):
            metrics.flat("x", op='a"b')

    def test_size_bucket_label(self):
        assert metrics.size_bucket_label(1) == "2^0"
        assert metrics.size_bucket_label(1024) == "2^10"
        assert metrics.size_bucket_label(1025) == "2^11"
        assert metrics.size_bucket_label(4 << 20) == "2^22"

    def test_views_fold_into_snapshot_and_replace(self):
        metrics.registry.register_view(
            "t", lambda: {"counters": {"phase_ops_total": 7}})
        assert metrics.registry.snapshot()["counters"][
            "phase_ops_total"] == 7
        metrics.registry.register_view(
            "t", lambda: {"counters": {"phase_ops_total": 9}})
        assert metrics.registry.snapshot()["counters"][
            "phase_ops_total"] == 9

    def test_broken_view_does_not_break_snapshot(self):
        def bad():
            raise RuntimeError("boom")

        metrics.registry.register_view("bad", bad)
        metrics.inc("faults_injected_total")
        assert metrics.registry.snapshot()["counters"][
            "faults_injected_total"] == 1

    def test_wire_and_phase_stats_are_registered_views(self):
        from horovod_tpu.core.timeline import phase_stats, wire_stats

        wire_stats.add("bytes_on_wire", 128)
        phase_stats.add("negotiate", 0.25)
        snap = metrics.registry.snapshot()
        assert snap["counters"]["wire_bytes_on_wire_total"] >= 128
        key = metrics.flat("phase_seconds_total", phase="negotiate")
        assert snap["counters"][key] >= 0.25

    def test_catalog_covers_every_stat_literal(self):
        # The names the codebase feeds to phase_stats/wire_stats.add —
        # HVD007's contract, restated where a registry edit breaks it.
        from horovod_tpu.core.timeline import PHASES

        assert PHASES[:5] == ("negotiate", "fuse", "collective", "unfuse",
                              "wait")
        for name in PHASES + ("bytes_on_wire", "heap_copies"):
            assert name in metrics.CATALOG


# ---------------------------------------------------------------------------
# Prometheus rendering / cross-rank merge
# ---------------------------------------------------------------------------


def _snap(rank, counters=None, gauges=None, histograms=None):
    return {"version": 1, "rank": rank, "ts_unix_ns": 0,
            "bucket_bounds": list(metrics.BUCKET_BOUNDS),
            "counters": counters or {}, "gauges": gauges or {},
            "histograms": histograms or {}}


@pytest.mark.smoke
class TestPrometheusRender:
    def test_counters_sum_across_ranks(self):
        text = metrics.render_prometheus({
            0: _snap(0, counters={"aborts_total": 2}),
            1: _snap(1, counters={"aborts_total": 3})})
        assert "hvd_aborts_total 5" in text
        assert "# TYPE hvd_aborts_total counter" in text

    def test_gauges_labeled_by_rank(self):
        text = metrics.render_prometheus({
            0: _snap(0, gauges={"tensor_queue_depth": 1}),
            1: _snap(1, gauges={"tensor_queue_depth": 4})})
        assert 'hvd_tensor_queue_depth{rank="0"} 1' in text
        assert 'hvd_tensor_queue_depth{rank="1"} 4' in text

    def test_histograms_merge_cumulatively(self):
        counts = [0] * (len(metrics.BUCKET_BOUNDS) + 1)
        counts[0] = 1
        h0 = {"collective_latency_seconds": {
            "counts": list(counts), "sum": 0.5, "count": 1}}
        counts2 = list(counts)
        counts2[-1] = 2  # overflow bucket on rank 1
        h1 = {"collective_latency_seconds": {
            "counts": counts2, "sum": 1.5, "count": 3}}
        text = metrics.render_prometheus({0: _snap(0, histograms=h0),
                                          1: _snap(1, histograms=h1)})
        assert 'hvd_collective_latency_seconds_bucket{le="+Inf"} 4' in text
        assert "hvd_collective_latency_seconds_sum 2" in text
        assert "hvd_collective_latency_seconds_count 4" in text
        # cumulative: every bucket line's value is non-decreasing
        vals = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
                if line.startswith("hvd_collective_latency_seconds_bucket")]
        assert vals == sorted(vals)

    def test_three_rank_histogram_merge_is_exact(self):
        """Scrape-time merge exactness at np=3: the rendered cumulative
        distribution must equal the element-wise sum of the three ranks'
        bucket arrays — no drops, no double counts, any rank count."""
        n = len(metrics.BUCKET_BOUNDS) + 1
        per_rank = []
        for r in range(3):
            counts = [0] * n
            counts[r] = r + 1          # distinct bucket per rank
            counts[-1] = r             # plus overflow traffic on ranks 1-2
            per_rank.append(counts)
        snaps = {r: _snap(r, histograms={"collective_latency_seconds": {
            "counts": c, "sum": float(r), "count": sum(c)}})
            for r, c in enumerate(per_rank)}
        text = metrics.render_prometheus(snaps)
        merged = [sum(c[i] for c in per_rank) for i in range(n)]
        cumulative, acc = [], 0
        for v in merged:
            acc += v
            cumulative.append(acc)
        got = [float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
               if line.startswith("hvd_collective_latency_seconds_bucket")]
        assert got == cumulative
        assert f"hvd_collective_latency_seconds_count {acc}" in text
        assert "hvd_collective_latency_seconds_sum 3" in text

    def test_malformed_snapshot_is_skipped(self):
        text = metrics.render_prometheus({
            0: _snap(0, counters={"aborts_total": 1}), 1: "garbage"})
        assert "hvd_aborts_total 1" in text


@pytest.mark.smoke
def test_scrape_serves_only_newest_epoch():
    """Elastic staleness gate: a departed rank's last snapshot (stamped
    with the old epoch) must drop out of the scrape once survivors push
    under the new epoch."""
    import urllib.request

    from horovod_tpu.runner.rendezvous import RendezvousServer

    server = RendezvousServer(bind_addr="127.0.0.1")
    port = server.start()
    try:
        old = _snap(3, gauges={"tensor_queue_depth": 9})
        old["epoch"] = 0
        new = _snap(0, gauges={"tensor_queue_depth": 1})
        new["epoch"] = 1
        server.set(metrics.METRICS_SCOPE, "rank-3",
                   json.dumps(old).encode())
        server.set(metrics.METRICS_SCOPE, "rank-0",
                   json.dumps(new).encode())
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert 'hvd_tensor_queue_depth{rank="0"} 1' in text
        assert 'rank="3"' not in text, text
    finally:
        server.stop()


@pytest.mark.smoke
def test_server_request_metrics_and_scrape_fold_in():
    """Control-plane attribution, server side: every HTTP op lands in the
    per-op latency histogram and per-scope counters, and ``GET /metrics``
    folds the server's own registry into the scrape under rank="server"
    (never epoch-gated — the server can't be stale about itself)."""
    import urllib.request

    from horovod_tpu.runner.rendezvous import RendezvousServer
    from horovod_tpu.transport.store import HTTPStoreClient

    server = RendezvousServer("127.0.0.1")
    port = server.start()
    try:
        reg = metrics.registry
        puts0 = reg.get_counter("rendezvous_scope_ops_total",
                                op="put", scope="obs-smoke")
        client = HTTPStoreClient("127.0.0.1", port)
        client.set("obs-smoke", "k", b"v")
        client.get("obs-smoke", "k")
        client.keys("obs-smoke")
        # The handler counts a request behind its reply: the last one's
        # count may still be on its way when the client has its answer.
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not (
                reg.get_counter("rendezvous_scope_ops_total", op="keys",
                                scope="obs-smoke")
                and reg.get_gauge("rendezvous_requests_in_flight") == 0):
            time.sleep(0.01)
        assert reg.get_counter("rendezvous_scope_ops_total",
                               op="put", scope="obs-smoke") == puts0 + 1
        assert reg.get_counter("rendezvous_scope_ops_total",
                               op="keys", scope="obs-smoke") >= 1
        hists = reg.snapshot()["histograms"]
        for op in ("put", "get", "keys"):
            key = metrics.flat("rendezvous_request_seconds", op=op)
            assert hists.get(key, {}).get("count", 0) >= 1, (key, op)
        # the in-flight gauge settled back to 0 after the burst
        assert reg.get_gauge("rendezvous_requests_in_flight") == 0
        # store-lock wait is observed on every guarded acquire
        lock_key = metrics.flat("rendezvous_store_lock_wait_seconds")
        assert hists.get(lock_key, {}).get("count", 0) >= 1
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert 'rank="server"' in text, text[:2000]
        assert "hvd_rendezvous_request_seconds" in text
    finally:
        server.stop()


@pytest.mark.smoke
def test_journal_metrics(tmp_path):
    """Durability-plane attribution: appends/fsyncs/replay/compaction all
    observe, torn tails count, and the generation gauge tracks."""
    from horovod_tpu.transport.store import DurableMemoryStore

    def hist_count(name):
        h = metrics.registry.snapshot()["histograms"]
        return h.get(metrics.flat(name), {}).get("count", 0)

    appends0 = hist_count("journal_append_seconds")
    fsyncs0 = hist_count("journal_fsync_seconds")
    store = DurableMemoryStore(str(tmp_path))
    store.set("s", "k", b"v")
    store.pop("s", "k")
    store.close()
    assert hist_count("journal_append_seconds") == appends0 + 2
    assert hist_count("journal_fsync_seconds") >= fsyncs0 + 2
    assert metrics.registry.get_gauge("journal_generation") == 0

    # A recover replays (and times) the journal; garbage appended after
    # the valid prefix is a torn tail and must increment the counter.
    replays0 = hist_count("journal_replay_seconds")
    torn0 = metrics.registry.get_counter("journal_truncated_tails_total")
    jpath = tmp_path / "journal-00000000"
    with open(jpath, "ab") as f:
        f.write(b"\x01torn-garbage")
    store2 = DurableMemoryStore(str(tmp_path))
    store2.close()
    assert hist_count("journal_replay_seconds") == replays0 + 1
    assert metrics.registry.get_counter(
        "journal_truncated_tails_total") == torn0 + 1


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


@pytest.mark.smoke
class TestFlightRecorder:
    def test_ring_is_bounded(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_FLIGHT_RECORDER_EVENTS", "8")
        rec = flight_recorder.FlightRecorder()
        for i in range(50):
            rec.record("frame", n=i)
        events = rec.events()
        assert len(events) == 8
        assert [e["n"] for e in events] == list(range(42, 50))

    def test_dump_is_parseable_and_complete(self, tmp_path):
        flight_recorder.record("cycle", n=3)
        flight_recorder.record("fault", site="tcp.send")
        metrics.inc("faults_injected_total")
        path = flight_recorder.recorder.dump(
            "unit test", path=str(tmp_path / "dump.json"))
        doc = json.loads((tmp_path / "dump.json").read_text())
        assert path == str(tmp_path / "dump.json")
        assert doc["format"] == flight_recorder.DUMP_FORMAT
        assert doc["reason"] == "unit test"
        assert {e["kind"] for e in doc["events"]} == {"cycle", "fault"}
        assert doc["metrics"]["counters"]["faults_injected_total"] == 1
        for e in doc["events"]:
            assert "t_mono" in e and "t_wall" in e and "thread" in e

    def test_dump_dir_knob(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HOROVOD_FLIGHT_RECORDER_DIR", str(tmp_path))
        monkeypatch.setenv("HOROVOD_RANK", "7")
        flight_recorder.record("cycle", n=1)
        path = flight_recorder.recorder.dump("dir knob")
        assert path == str(tmp_path / "hvd_flight_recorder"
                           / "hvd_flight_recorder.rank7.json")
        assert json.loads(open(path).read())["rank"] == 7

    def test_disabled_records_and_dumps_nothing(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv("HOROVOD_FLIGHT_RECORDER", "0")
        rec = flight_recorder.FlightRecorder()
        rec.record("frame")
        assert rec.events() == []
        assert rec.dump("off", path=str(tmp_path / "no.json")) is None
        assert not (tmp_path / "no.json").exists()

    def test_dump_never_raises_on_bad_path(self):
        assert flight_recorder.recorder.dump(
            "bad", path="/nonexistent-dir-xyz/d.json") is None


# ---------------------------------------------------------------------------
# stall inspector -> metrics surfacing
# ---------------------------------------------------------------------------


@pytest.mark.smoke
class TestStallMetrics:
    def _controller(self, warn=0.01, shut=0.0):
        from horovod_tpu.common.topology import ProcessTopology
        from horovod_tpu.core.controller import Controller

        topo = ProcessTopology(rank=0, size=2, local_rank=0, local_size=2)
        c = Controller(topo, mesh=None, stall_warning_secs=warn,
                       stall_shutdown_secs=shut)
        c._last_stall_check = 0.0  # force the next check to run
        return c

    def _stall_tensor(self, c, name="stuck", age=10.0):
        from horovod_tpu.core.controller import _TableEntry

        entry = _TableEntry()
        entry.ranks.add(0)
        entry.first_seen = time.monotonic() - age
        c._message_table[name] = entry

    def test_stalled_gauge_counts_overdue_tensors(self):
        c = self._controller(warn=0.01)
        self._stall_tensor(c, "stuck", age=10.0)
        c._check_stalls()
        assert metrics.registry.get_gauge("stalled_tensors") == 1
        # recovery: the next check with an empty table zeroes the gauge
        c._message_table.clear()
        c._last_stall_check = 0.0
        c._check_stalls()
        assert metrics.registry.get_gauge("stalled_tensors") == 0

    def test_fresh_tensor_not_counted(self):
        c = self._controller(warn=60.0)
        self._stall_tensor(c, "young", age=0.001)
        c._check_stalls()
        assert metrics.registry.get_gauge("stalled_tensors") == 0

    def test_stall_shutdown_increments_counter(self):
        from horovod_tpu.common.exceptions import HorovodInternalError

        c = self._controller(warn=0.0, shut=0.01)
        self._stall_tensor(c, "doomed", age=10.0)
        with pytest.raises(HorovodInternalError, match="stall shutdown"):
            c._check_stalls()
        assert metrics.registry.get_counter("stall_shutdowns_total") == 1


# ---------------------------------------------------------------------------
# online straggler detection (coordinator-side EWMAs)
# ---------------------------------------------------------------------------


@pytest.mark.smoke
class TestStragglerDetector:
    def _controller(self, thresh=0.05, alpha=0.5, size=3):
        from horovod_tpu.common.topology import ProcessTopology
        from horovod_tpu.core.controller import Controller

        topo = ProcessTopology(rank=0, size=size, local_rank=0,
                               local_size=size)
        c = Controller(topo, mesh=None)
        c.straggler_threshold = thresh
        c.straggler_alpha = alpha
        return c

    def _lagging_entry(self, c, name="lag", ranks=(0, 2), age=1.0):
        from horovod_tpu.core.controller import _TableEntry

        entry = _TableEntry()
        entry.ranks.update(ranks)
        entry.majority_seen = time.monotonic() - age
        c._message_table[name] = entry

    def test_clean_state_early_outs(self):
        # Steady state (no majority stamps, no decaying EWMA) must not
        # even touch the EWMA dict — the hot path's two falsy checks.
        c = self._controller()
        c._update_stragglers()
        assert c._straggler_ewma == {}
        assert metrics.registry.get_gauge("straggler_suspect") is None

    def test_lag_flags_the_missing_rank(self):
        c = self._controller(thresh=0.05, alpha=0.5)
        self._lagging_entry(c, ranks=(0, 2), age=1.0)
        c._update_stragglers()
        # one EWMA step: 0 + 0.5 * (1.0s - 0) — only the missing rank lags
        assert c._straggler_ewma[1] == pytest.approx(0.5, rel=0.05)
        assert c._straggler_ewma[0] == 0.0
        assert c._straggler_ewma[2] == 0.0
        assert c._straggler_suspects == {1}
        assert metrics.registry.get_counter(
            "straggler_flags_total", rank="1") == 1
        assert metrics.registry.get_gauge("straggler_suspect") == 1
        key = metrics.flat("straggler_lag_seconds", rank="1")
        assert metrics.registry.snapshot()["histograms"][key]["count"] == 1
        flagged = [e for e in flight_recorder.recorder.events()
                   if e["kind"] == "straggler"]
        assert len(flagged) == 1 and flagged[0]["rank"] == 1

    def test_hysteresis_clears_at_half_threshold(self):
        c = self._controller(thresh=0.05, alpha=0.5)
        self._lagging_entry(c, age=1.0)
        c._update_stragglers()
        assert c._straggler_suspects == {1}
        c._message_table.clear()
        # decay: lag 0 every cycle, EWMA halves; the suspect must clear
        # only once it falls below thresh/2, and exactly once.
        for _ in range(50):
            c._update_stragglers()
            if not c._straggler_suspects:
                break
        assert not c._straggler_suspects
        assert c._straggler_ewma[1] < c.straggler_threshold / 2
        assert metrics.registry.get_gauge("straggler_suspect") == -1
        assert metrics.registry.get_counter(
            "straggler_flags_total", rank="1") == 1  # one episode, one flag
        kinds = [e["kind"] for e in flight_recorder.recorder.events()]
        assert kinds.count("straggler_cleared") == 1

    def test_mask_bit_majority_path_attributes_lag(self):
        # The cache fast path has no table entries: lag comes from
        # announced-bit majority stamps vs per-rank pending masks.
        c = self._controller(thresh=10.0, alpha=1.0)
        c._mask_bit_majority[3] = time.monotonic() - 0.5
        c._pending_masks = {0: 1 << 3, 2: 1 << 3}  # rank 1 silent on bit 3
        c._update_stragglers()
        assert c._straggler_ewma[1] == pytest.approx(0.5, rel=0.05)
        assert c._straggler_ewma[0] == 0.0
        assert c._straggler_ewma[2] == 0.0

    def test_joined_rank_is_not_blamed(self):
        c = self._controller(thresh=0.05, alpha=1.0)
        c._joined_ranks.add(1)
        self._lagging_entry(c, ranks=(0, 2), age=1.0)
        c._update_stragglers()
        assert c._straggler_ewma.get(1, 0.0) == 0.0
        assert not c._straggler_suspects

    def test_zero_threshold_disables_flagging_not_tracking(self):
        c = self._controller(thresh=0.0, alpha=1.0)
        self._lagging_entry(c, age=1.0)
        c._update_stragglers()
        assert c._straggler_ewma[1] > 0.9  # EWMA still tracks
        assert not c._straggler_suspects   # but nothing flags
        assert metrics.registry.get_gauge("straggler_suspect") is None

    def test_alpha_validation(self, monkeypatch):
        from horovod_tpu.common import env as env_mod

        monkeypatch.setenv(env_mod.HOROVOD_STRAGGLER_EWMA_ALPHA, "0")
        with pytest.raises(ValueError, match="STRAGGLER_EWMA_ALPHA"):
            self._controller()

    def test_stall_suffix_names_worst_laggard(self):
        c = self._controller()
        c._straggler_ewma = {1: 0.4, 2: 0.1}
        suffix = c._lag_suffix([1, 2])
        assert "rank 1" in suffix and "0.400" in suffix
        # a missing rank with no observed lag yields no accusation
        assert c._lag_suffix([0]) == ""

    # -- suspect-reset regression (ISSUE 17 satellite): demotion keys
    #    off live state, never a previous world's leftovers ------------

    def test_decay_clears_suspect_gauge_to_minus_one(self):
        c = self._controller(thresh=0.05, alpha=0.5)
        self._lagging_entry(c, age=1.0)
        c._update_stragglers()
        assert metrics.registry.get_gauge("straggler_suspect") == 1
        c._message_table.clear()
        for _ in range(50):
            c._update_stragglers()
        assert c._straggler_suspects == set()
        assert metrics.registry.get_gauge("straggler_suspect") == -1
        # ...and the decay loop itself un-wedges: once every EWMA is
        # at noise floor, the early-out flag drops back to False.
        assert c._straggler_decaying is False

    def test_fresh_controller_resets_stale_suspect_gauge(self):
        # An elastic epoch restart in the same process builds a NEW
        # controller; the process-global gauge must not keep naming the
        # old world's suspect (the demotion plane reads live state).
        c = self._controller(thresh=0.05, alpha=1.0)
        self._lagging_entry(c, age=1.0)
        c._update_stragglers()
        assert metrics.registry.get_gauge("straggler_suspect") == 1
        c2 = self._controller()
        assert metrics.registry.get_gauge("straggler_suspect") == -1
        assert c2._straggler_decaying is False
        assert c2._straggler_ewma == {}
        # the fresh world's clean cycles stay clean (no wedge from the
        # old controller's state)
        c2._update_stragglers()
        assert c2._straggler_suspects == set()


# ---------------------------------------------------------------------------
# chronic-straggler demotion: the verdict state machine as a pure unit
# (ISSUE 17; docs/elastic.md "self-healing demotion")
# ---------------------------------------------------------------------------


@pytest.mark.smoke
class TestDemotionPolicy:
    def _policy(self, secs=1.0, cycles=3):
        from horovod_tpu.core.controller import DemotionPolicy

        return DemotionPolicy(secs, cycles)

    def test_disabled_by_default_threshold(self):
        p = self._policy(secs=0.0)
        assert not p.enabled
        assert p.observe(0, {1: 99.0}, {0, 1, 2}) is None

    def test_cycles_validation(self):
        with pytest.raises(ValueError, match="DEMOTE_CYCLES"):
            self._policy(cycles=0)

    def test_hysteresis_window_edges(self):
        # Table-driven: cycles of (ewma map, expected verdict).  The
        # verdict fires exactly ON the Nth consecutive over-threshold
        # cycle, not before, and a single under-threshold cycle resets
        # the streak to zero.
        p = self._policy(secs=1.0, cycles=3)
        world = {0, 1, 2}
        cases = [
            ({1: 2.0}, None),   # streak 1
            ({1: 2.0}, None),   # streak 2
            ({1: 0.5}, None),   # dips under: streak resets
            ({1: 2.0}, None),   # streak 1 again
            ({1: 2.0}, None),   # streak 2
            ({1: 2.0}, 1),      # streak 3 == cycles: verdict
        ]
        for i, (ewma, expected) in enumerate(cases):
            assert p.observe(0, ewma, world) == expected, f"cycle {i}"

    def test_exactly_at_threshold_is_not_over(self):
        # strict >: an EWMA sitting exactly on the knob never streaks
        p = self._policy(secs=1.0, cycles=1)
        assert p.observe(0, {1: 1.0}, {0, 1, 2}) is None
        assert p.observe(0, {1: 1.0001}, {0, 1, 2}) == 1

    def test_whole_world_slow_guard(self):
        # Half-or-more of the active world over threshold = a global
        # stall, not a straggler: nobody is demoted and streaks reset.
        p = self._policy(secs=1.0, cycles=2)
        world = {0, 1, 2, 3}
        slow_world = {1: 5.0, 2: 5.0}          # 2 of 4 = half
        for _ in range(10):
            assert p.observe(0, slow_world, world) is None
        # the stall must not have seeded streaks: rank 1 alone still
        # needs the FULL window from zero
        assert p.observe(0, {1: 5.0}, world) is None
        assert p.observe(0, {1: 5.0}, world) == 1

    def test_two_rank_world_never_demotes(self):
        # At np=2 one slow rank is half the world — the guard blocks
        # demotion by construction, no special case needed.
        p = self._policy(secs=1.0, cycles=1)
        for _ in range(5):
            assert p.observe(0, {1: 99.0}, {0, 1}) is None

    def test_one_demotion_per_epoch_cap(self):
        p = self._policy(secs=1.0, cycles=1)
        world = {0, 1, 2, 3, 4}
        assert p.observe(7, {1: 5.0}, world) == 1
        # rank 3 is just as chronic, but epoch 7 already shed a host
        for _ in range(10):
            assert p.observe(7, {3: 5.0}, world) is None
        # a new epoch re-arms the cap
        assert p.observe(8, {3: 5.0}, world) == 3

    def test_worst_ewma_wins_among_chronic(self):
        p = self._policy(secs=1.0, cycles=2)
        world = {0, 1, 2, 3, 4, 5, 6}
        both = {1: 2.0, 3: 9.0}
        assert p.observe(0, both, world) is None
        assert p.observe(0, both, world) == 3

    def test_recovered_rank_drops_from_streaks(self):
        p = self._policy(secs=1.0, cycles=3)
        world = {0, 1, 2, 3, 4}
        p.observe(0, {1: 5.0, 3: 5.0}, world)
        p.observe(0, {1: 5.0, 3: 5.0}, world)
        # rank 3 recovers; rank 1 completes the window alone
        assert p.observe(0, {1: 5.0}, world) == 1
        # rank 3's streak was wiped, not frozen
        assert p.observe(1, {3: 5.0}, world) is None


# ---------------------------------------------------------------------------
# demotion report parsing (driver side, no sockets) + blacklist strikes
# ---------------------------------------------------------------------------


@pytest.mark.smoke
class TestDemotionReports:
    def _parse(self, raws, epoch):
        from horovod_tpu.elastic.driver import ElasticDriver

        return ElasticDriver._parse_demotion_reports(raws, epoch)

    def _report(self, epoch=3, rank=1, **extra):
        d = {"epoch": epoch, "rank": rank, "hostname": "h001",
             "ewma": 2.5, "threshold": 1.0, "cycles": 10}
        d.update(extra)
        return json.dumps(d).encode()

    def test_current_epoch_report_parses(self):
        reps = self._parse({"h000:0": self._report(epoch=3)}, epoch=3)
        assert len(reps) == 1
        assert reps[0]["rank"] == 1
        assert reps[0]["reporter"] == "h000:0"

    def test_stale_epoch_report_discarded(self):
        # A report stamped with an older epoch was answered by a later
        # bump already — it must not demote anyone in the new world.
        for stale in (0, 1, 2):
            assert self._parse(
                {"h000:0": self._report(epoch=stale)}, epoch=3) == []
        # future-stamped reports (clock/restart skew) are equally dead
        assert self._parse(
            {"h000:0": self._report(epoch=9)}, epoch=3) == []

    def test_absent_and_malformed_reports_skipped(self):
        raws = {"h000:0": None, "h001:0": b"not json",
                "h002:0": b"[1,2]", "h003:0": json.dumps(
                    {"epoch": 3, "rank": "one"}).encode()}
        assert self._parse(raws, epoch=3) == []

    def test_blacklist_idempotent_while_active(self):
        from horovod_tpu.elastic.discovery import FixedHosts, HostManager
        from horovod_tpu.runner.hosts import parse_hosts

        hm = HostManager(FixedHosts(parse_hosts("a:1,b:1")),
                         blacklist_cooldown=60.0)
        assert hm.blacklist("a", evidence="rank 1 EWMA 2.5s") is True
        expiry = hm._blacklist["a"]
        # repeated strikes within the window: no stacking, expiry KEPT
        assert hm.blacklist("a", evidence="again") is False
        assert hm.blacklist("a") is False
        assert hm._blacklist["a"] == expiry
        assert hm.is_blacklisted("a")
        assert not hm.is_blacklisted("b")

    def test_blacklist_fresh_strike_after_expiry(self):
        from horovod_tpu.elastic.discovery import FixedHosts, HostManager
        from horovod_tpu.runner.hosts import parse_hosts

        hm = HostManager(FixedHosts(parse_hosts("a:1")),
                         blacklist_cooldown=60.0)
        assert hm.blacklist("a") is True
        # simulate cooldown expiry
        hm._blacklist["a"] = hm._now() - 1.0
        assert hm.blacklist("a") is True  # a NEW strike, clock restarted
        assert hm._blacklist["a"] > hm._now()


# ---------------------------------------------------------------------------
# trace merge
# ---------------------------------------------------------------------------


def _trace(rank, wall_base_ns, server_offset_ns, events):
    head = [
        {"name": "process_name", "ph": "M", "pid": rank,
         "args": {"name": f"rank {rank}"}},
        {"name": "clock_sync", "ph": "M", "pid": rank,
         "args": {"wall_base_ns": wall_base_ns,
                  "server_offset_ns": server_offset_ns, "rank": rank}},
    ]
    return head + events


@pytest.mark.smoke
class TestTraceMerge:
    def test_clock_alignment_subtracts_skew(self):
        from horovod_tpu.tools import trace_merge

        # Rank 1's wall clock runs 5 ms ahead of rank 0's, and its
        # server-offset estimate says exactly that: after alignment, two
        # spans that happened at the same server time coincide.
        t0 = _trace(0, 1_000_000_000, 0,
                    [{"name": "A", "ph": "B", "pid": 0, "tid": 1, "ts": 100}])
        t1 = _trace(1, 1_000_000_000 + 5_000_000, 5_000_000,
                    [{"name": "A", "ph": "B", "pid": 1, "tid": 1, "ts": 100}])
        merged = trace_merge.merge([json.loads(json.dumps(t)) for t in (t0, t1)])
        ts = [e["ts"] for e in merged if e.get("ph") == "B"]
        assert ts[0] == pytest.approx(ts[1])

    def test_missing_clock_sync_falls_back_to_concat(self):
        from horovod_tpu.tools import trace_merge

        warnings = []
        t0 = _trace(0, 1_000, 0,
                    [{"name": "A", "ph": "B", "pid": 0, "tid": 1, "ts": 7}])
        t1 = [{"name": "A", "ph": "B", "pid": 1, "tid": 1, "ts": 9}]
        merged = trace_merge.merge([t0, t1], warn=warnings.append)
        assert warnings and "WITHOUT" in warnings[0]
        assert sorted(e["ts"] for e in merged if "ts" in e) == [7, 9]

    def test_truncated_trace_is_repaired(self, tmp_path):
        from horovod_tpu.tools import trace_merge

        p = tmp_path / "trunc.json"
        p.write_text('[\n{"name": "A", "ph": "B", "pid": 0, "ts": 1},\n'
                     '{"name": "B", "ph": "E", "pid": 0, "ts":')  # cut mid-record
        events = trace_merge.load_trace(str(p))
        assert [e["name"] for e in events] == ["A"]

    def test_cli_writes_merged_file(self, tmp_path):
        from horovod_tpu.tools import trace_merge

        for r in range(2):
            (tmp_path / f"t{r}.json").write_text(json.dumps(
                _trace(r, 1_000_000, 0,
                       [{"name": "X", "ph": "B", "pid": r, "tid": 1,
                         "ts": 5, "args": {"cycle": 3}}])))
        out = tmp_path / "merged.json"
        rc = trace_merge.main([str(tmp_path / "t0.json"),
                               str(tmp_path / "t1.json"), "-o", str(out)])
        assert rc == 0
        merged = json.loads(out.read_text())
        assert {e.get("pid") for e in merged if e.get("ph") == "B"} == {0, 1}

    def test_server_trace_merges_unshifted(self):
        """The server is trace_merge's clock base: its own trace carries
        offset 0, so when it is the earliest input its spans merge with
        shift 0 while worker spans are rebased onto its axis."""
        from horovod_tpu.core.timeline import SERVER_TRACE_PID
        from horovod_tpu.tools import trace_merge

        server = _trace(SERVER_TRACE_PID, 1_000_000_000, 0,
                        [{"name": "RV_PUT", "ph": "X",
                          "pid": SERVER_TRACE_PID, "tid": 1,
                          "ts": 40.0, "dur": 10.0}])
        # Worker wall clock runs 7 ms ahead; it started 2 ms of server
        # time after the server's trace began.
        worker = _trace(0, 1_000_000_000 + 9_000_000, 7_000_000,
                        [{"name": "RVC_SET", "ph": "X", "pid": 0,
                          "tid": 1, "ts": 40.0, "dur": 30.0}])
        merged = trace_merge.merge([server, worker])
        ts = {e["pid"]: e["ts"] for e in merged if e.get("ph") == "X"}
        assert ts[SERVER_TRACE_PID] == pytest.approx(40.0)
        assert ts[0] == pytest.approx(40.0 + 2_000.0)

    def test_live_server_trace_lane_and_crash_repair(self, tmp_path):
        """A real traced server: RV_* spans land on the reserved server
        pid with a zero-offset clock_sync, and a crash-truncated copy of
        the file repairs to a valid prefix on load."""
        from horovod_tpu.core.timeline import SERVER_TRACE_PID
        from horovod_tpu.runner.rendezvous import RendezvousServer
        from horovod_tpu.transport.store import HTTPStoreClient
        from horovod_tpu.tools import trace_merge

        path = tmp_path / "server.json"
        server = RendezvousServer("127.0.0.1", trace_path=str(path))
        port = server.start()
        try:
            client = HTTPStoreClient("127.0.0.1", port)
            for i in range(4):
                client.set("scope", f"k{i}", b"v")
            client.keys("scope")
            client.get("scope", "k0")
        finally:
            server.stop()
        events = trace_merge.load_trace(str(path))
        spans = [e for e in events if e.get("ph") == "X"]
        names = {e["name"] for e in spans}
        assert {"RV_PUT", "RV_KEYS", "RV_GET"} <= names, names
        assert {e["pid"] for e in spans} == {SERVER_TRACE_PID}
        sync = trace_merge._clock_sync(events)
        assert sync is not None and sync[1] == SERVER_TRACE_PID
        # Crash contract: cut mid-record (a SIGKILL'd server never writes
        # the closing bracket) and the loader keeps the valid prefix.
        text = path.read_text()
        trunc = tmp_path / "trunc.json"
        trunc.write_text(text[:text.rindex("{") + 10])
        repaired = trace_merge.load_trace(str(trunc))
        assert 0 < len(repaired) < len(events)
        assert all(isinstance(e, dict) for e in repaired)


# ---------------------------------------------------------------------------
# critical-path extraction
# ---------------------------------------------------------------------------


def _cp_ev(name, ph, pid, tid, ts, **args):
    e = {"name": name, "ph": ph, "pid": pid, "tid": tid, "ts": ts}
    if args:
        e["args"] = args
    return e


def _cp_step_events():
    """One negotiation cycle (7), three ranks: rank 1 announced 80 us
    after the span opened (everyone waited for it), rank 0 shows a full
    fuse/wire/reduce breakdown, rank 1's op span ends the step."""
    return [
        # coordinator negotiation lane (pid 0) with readiness instants
        _cp_ev("NEGOTIATE_ALLREDUCE", "B", 0, 9, 100, cycle=7),
        _cp_ev("0", "i", 0, 9, 110),
        _cp_ev("2", "i", 0, 9, 120),
        _cp_ev("1", "i", 0, 9, 180),
        _cp_ev("NEGOTIATE_ALLREDUCE", "E", 0, 9, 185),
        # rank 0 tensor lane: op span with nested lifecycle phases
        _cp_ev("ALLREDUCE", "B", 0, 1, 200, cycle=7),
        _cp_ev("LC_FUSE", "B", 0, 1, 200),           # inherits cycle 7
        _cp_ev("LC_FUSE", "E", 0, 1, 210),
        _cp_ev("LC_WIRE_REDUCE_SCATTER", "B", 0, 1, 215),
        _cp_ev("LC_WIRE_REDUCE_SCATTER", "E", 0, 1, 245),
        _cp_ev("LC_WIRE_ALLGATHER", "B", 0, 1, 245),
        _cp_ev("LC_WIRE_ALLGATHER", "E", 0, 1, 275),
        _cp_ev("ALLREDUCE", "E", 0, 1, 300),
        # ranks 1 and 2: bare op spans; rank 1 ends last
        _cp_ev("ALLREDUCE", "B", 1, 1, 150, cycle=7),
        _cp_ev("ALLREDUCE", "E", 1, 1, 320),
        _cp_ev("ALLREDUCE", "B", 2, 1, 150, cycle=7),
        _cp_ev("ALLREDUCE", "E", 2, 1, 260),
    ]


@pytest.mark.smoke
class TestCriticalPath:
    def test_step_attribution(self):
        from horovod_tpu.tools import critical_path

        doc = critical_path.analyze(_cp_step_events())
        assert doc["format"] == "hvd-critical-path-v1"
        assert doc["ranks_seen"] == [0, 1, 2]
        (step,) = doc["steps"]
        assert step["cycle"] == 7
        assert step["duration_us"] == 220.0        # 100 .. 320
        assert step["critical_rank"] == 1
        assert doc["critical_step_counts"] == {"1": 1}
        p0 = step["phases_us"]["0"]
        # negotiation wait goes to the LAST-ready rank (1), not pid 0
        assert "negotiation_wait" not in step["phases_us"].get("0", {}) \
            or p0["negotiation_wait"] == 0.0
        assert step["phases_us"]["1"]["negotiation_wait"] == 80.0
        assert p0["fusion"] == 10.0
        assert p0["reduce"] == 30.0
        assert p0["wire"] == 30.0
        # dispatch = op span minus the attributed sub-phases
        assert p0["dispatch"] == 100.0 - 70.0
        assert step["phases_us"]["2"]["dispatch"] == 110.0

    def test_fused_batch_counts_wire_once(self):
        from horovod_tpu.tools import critical_path

        # A fused batch emits the same wire span on every member tensor's
        # lane: attribution must union, not sum.
        events = [
            _cp_ev("LC_WIRE_ALLGATHER", "B", 0, 1, 10, cycle=1),
            _cp_ev("LC_WIRE_ALLGATHER", "E", 0, 1, 30),
            _cp_ev("LC_WIRE_ALLGATHER", "B", 0, 2, 10, cycle=1),
            _cp_ev("LC_WIRE_ALLGATHER", "E", 0, 2, 30),
        ]
        doc = critical_path.analyze(events)
        assert doc["totals_us"]["0"]["wire"] == 20.0

    def test_unclosed_span_closes_at_lane_end(self):
        from horovod_tpu.tools import critical_path

        events = [
            _cp_ev("ALLREDUCE", "B", 0, 1, 10, cycle=1),
            _cp_ev("LC_FUSE", "B", 0, 1, 20),
            _cp_ev("LC_FUSE", "E", 0, 1, 40),   # lane's last ts
        ]
        spans = critical_path.reconstruct(events)
        op = next(s for s in spans if s.name == "ALLREDUCE")
        assert op.e == 40
        assert all(s.cycle == 1 for s in spans)  # nested inheritance

    def test_cli_writes_json_report(self, tmp_path, capsys):
        from horovod_tpu.tools import critical_path

        trace = tmp_path / "tl.json"
        trace.write_text(json.dumps(_cp_step_events()))
        out = tmp_path / "cp.json"
        rc = critical_path.main([str(trace), "--json", str(out), "--top", "3"])
        assert rc == 0
        doc = json.loads(out.read_text())
        assert doc["steps"][0]["critical_rank"] == 1
        text = capsys.readouterr().out
        assert "critical rank by step count: rank 1" in text

    def test_no_cycles_degrades_gracefully(self):
        from horovod_tpu.tools import critical_path

        doc = critical_path.analyze([_cp_ev("X", "B", 0, 1, 5),
                                     _cp_ev("X", "E", 0, 1, 9)])
        assert doc["steps"] == []
        assert "HOROVOD_TIMELINE" in critical_path.render_text(doc)


# ---------------------------------------------------------------------------
# control-path attribution (hvd-control-path)
# ---------------------------------------------------------------------------


def _x(name, pid, ts, dur, **args):
    e = {"name": name, "ph": "X", "pid": pid, "tid": 1,
         "ts": float(ts), "dur": float(dur)}
    if args:
        e["args"] = args
    return e


def _churn_events():
    """One churn event window 0..100 µs: a 40 µs client round-trip with a
    server handler, lock wait, and fsync nested inside, plus a respawn."""
    from horovod_tpu.core.timeline import DRIVER_TRACE_PID, SERVER_TRACE_PID

    d, s = DRIVER_TRACE_PID, SERVER_TRACE_PID
    return [
        _x("CHURN_EVENT", d, 0, 100, cause="lease_expiry", epoch=3),
        _x("RVC_SET", d, 10, 40, scope="lease"),
        _x("RV_PUT", s, 15, 30, scope="lease"),
        _x("RV_LOCK_WAIT", s, 20, 10),
        _x("JR_FSYNC", s, 30, 10),
        _x("DRV_SPAWN", d, 60, 30),
    ]


@pytest.mark.smoke
class TestControlPath:
    def test_disjoint_carve_and_coverage(self):
        from horovod_tpu.tools import control_path

        doc = control_path.analyze(_churn_events())
        assert doc["format"] == "hvd-control-path-v1"
        (ev,) = doc["events"]
        assert ev["cause"] == "lease_expiry" and ev["epoch"] == 3
        ph = ev["phases_us"]
        # The lock wait and fsync nest inside the HTTP round-trip: they
        # keep their own phase, HTTP only keeps what they don't explain.
        assert ph["store_lock_wait"] == 10.0       # 20..30
        assert ph["journal_fsync"] == 10.0         # 30..40
        assert ph["http_roundtrip"] == 20.0        # 10..50 minus 20..40
        assert ph["respawn"] == 30.0               # 60..90
        assert ph["driver_tick_wait"] == 0.0
        assert ev["unattributed_us"] == 30.0
        assert ev["coverage"] == pytest.approx(0.7)
        assert doc["coverage"] == pytest.approx(0.7)
        assert doc["phase_share"]["respawn"] == pytest.approx(0.3)

    def test_spans_clip_to_their_window(self):
        from horovod_tpu.core.timeline import DRIVER_TRACE_PID
        from horovod_tpu.tools import control_path

        d = DRIVER_TRACE_PID
        doc = control_path.analyze([
            _x("CHURN_EVENT", d, 0, 100, cause="sim", epoch=1),
            # straddles the window's end: only 80..100 may count
            _x("RVC_GET", d, 80, 40, scope="lease"),
        ])
        (ev,) = doc["events"]
        assert ev["phases_us"]["http_roundtrip"] == 20.0

    def test_b_e_worker_spans_are_ignored(self):
        from horovod_tpu.core.timeline import DRIVER_TRACE_PID
        from horovod_tpu.tools import control_path

        d = DRIVER_TRACE_PID
        doc = control_path.analyze([
            _x("CHURN_EVENT", d, 0, 100, cause="sim", epoch=1),
            {"name": "ALLREDUCE", "ph": "B", "pid": 0, "tid": 1, "ts": 5},
            {"name": "ALLREDUCE", "ph": "E", "pid": 0, "tid": 1, "ts": 95},
        ])
        (ev,) = doc["events"]
        assert all(v == 0.0 for v in ev["phases_us"].values())

    def test_empty_trace_renders_hint(self):
        from horovod_tpu.tools import control_path

        doc = control_path.analyze([])
        assert doc["event_count"] == 0 and doc["coverage"] == 1.0
        assert "CHURN_EVENT" in control_path.render_text(doc)

    def test_cli_json_report(self, tmp_path, capsys):
        from horovod_tpu.tools import control_path

        trace = tmp_path / "merged.json"
        trace.write_text(json.dumps(_churn_events()))
        out = tmp_path / "cp.json"
        rc = control_path.main([str(trace), "--json", str(out)])
        assert rc == 0
        doc = json.loads(out.read_text())
        assert doc["event_count"] == 1
        text = capsys.readouterr().out
        assert "coverage 70.0%" in text
        assert "respawn" in text


# ---------------------------------------------------------------------------
# prometheus text validator (the metrics-smoke lane's checker)
# ---------------------------------------------------------------------------


@pytest.mark.smoke
class TestPromValidate:
    def test_real_render_is_valid(self):
        from horovod_tpu.tools import prom_validate

        counts = [0] * (len(metrics.BUCKET_BOUNDS) + 1)
        counts[2] = 1
        text = metrics.render_prometheus({
            0: _snap(0, counters={"aborts_total": 2},
                     gauges={"tensor_queue_depth": 1},
                     histograms={"collective_latency_seconds": {
                         "counts": counts, "sum": 0.5, "count": 1}}),
            1: _snap(1, gauges={"straggler_suspect": -1})})
        assert prom_validate.validate(text) == []

    def test_required_family_enforced(self):
        from horovod_tpu.tools import prom_validate

        text = metrics.render_prometheus(
            {0: _snap(0, counters={"aborts_total": 1})})
        errs = prom_validate.validate(
            text, required=["straggler_flags_total"])
        assert any("straggler_flags_total" in e and "missing" in e
                   for e in errs)

    def test_uncataloged_family_rejected(self):
        from horovod_tpu.tools import prom_validate

        text = ("# HELP hvd_bogus_total x\n"
                "# TYPE hvd_bogus_total counter\n"
                "hvd_bogus_total 1\n")
        errs = prom_validate.validate(text)
        assert any("not in CATALOG" in e for e in errs)

    def test_sample_before_metadata_rejected(self):
        from horovod_tpu.tools import prom_validate

        errs = prom_validate.validate("hvd_aborts_total 1\n")
        assert any("before its # TYPE" in e for e in errs)
        assert any("before its # HELP" in e for e in errs)

    def test_non_cumulative_buckets_rejected(self):
        from horovod_tpu.tools import prom_validate

        text = (
            "# HELP hvd_collective_latency_seconds x\n"
            "# TYPE hvd_collective_latency_seconds histogram\n"
            'hvd_collective_latency_seconds_bucket{le="0.1"} 3\n'
            'hvd_collective_latency_seconds_bucket{le="+Inf"} 2\n'
            "hvd_collective_latency_seconds_sum 1\n"
            "hvd_collective_latency_seconds_count 2\n")
        errs = prom_validate.validate(text)
        assert any("not cumulative" in e for e in errs)

    def test_inf_bucket_must_equal_count(self):
        from horovod_tpu.tools import prom_validate

        text = (
            "# HELP hvd_collective_latency_seconds x\n"
            "# TYPE hvd_collective_latency_seconds histogram\n"
            'hvd_collective_latency_seconds_bucket{le="+Inf"} 5\n'
            "hvd_collective_latency_seconds_sum 1\n"
            "hvd_collective_latency_seconds_count 4\n")
        errs = prom_validate.validate(text)
        assert any("+Inf bucket" in e and "_count" in e for e in errs)

    def test_kind_mismatch_rejected(self):
        from horovod_tpu.tools import prom_validate

        text = ("# HELP hvd_aborts_total x\n"
                "# TYPE hvd_aborts_total gauge\n"
                "hvd_aborts_total 1\n")
        errs = prom_validate.validate(text)
        assert any("catalog kind" in e for e in errs)


# ---------------------------------------------------------------------------
# metrics-dump --watch/--rate
# ---------------------------------------------------------------------------


@pytest.mark.smoke
class TestMetricsDumpWatch:
    def test_rates_are_per_second_deltas(self):
        from horovod_tpu.tools import metrics_dump

        prev = {"0": {"rank": 0, "counters": {"x_total": 10},
                      "histograms": {"h": {"count": 2, "sum": 1.0}}}}
        cur = {"0": {"rank": 0, "counters": {"x_total": 30},
                     "gauges": {"depth": 5},
                     "histograms": {"h": {"count": 6, "sum": 3.0}}}}
        out = metrics_dump._rates(prev, cur, 2.0)
        assert "x_total = +10/s" in out       # (30-10)/2s
        assert "depth = 5 (gauge)" in out     # gauges are levels
        assert "+2 obs/s" in out and "mean=0.5" in out

    def test_unchanged_counters_are_omitted(self):
        from horovod_tpu.tools import metrics_dump

        snap = {"0": {"rank": 0, "counters": {"x_total": 10}}}
        out = metrics_dump._rates(snap, snap, 1.0)
        assert "x_total" not in out

    def test_rate_requires_watch(self):
        from horovod_tpu.tools import metrics_dump

        with pytest.raises(SystemExit):
            metrics_dump.main(["--rate"])


# ---------------------------------------------------------------------------
# runtime timeline toggles (satellite: the core/timeline.py docstring's
# promise, with balanced B/E per lane)
# ---------------------------------------------------------------------------


def test_start_stop_timeline_balanced_lanes(tmp_path):
    import os

    from horovod_tpu.core import state as state_mod

    state_mod.reset_global_state()
    os.environ.pop("HOROVOD_SIZE", None)
    import horovod_tpu.frameworks.jax.basics as basics
    import horovod_tpu.frameworks.jax.ops as ops

    basics.init()
    try:
        tl = tmp_path / "toggle.json"
        basics.start_timeline(str(tl), mark_cycles=True)
        for i in range(3):
            ops.allreduce(np.ones(8, np.float32), name=f"tg{i}")
        basics.stop_timeline()
        events = json.loads(tl.read_text())  # completed file parses
        assert state_mod.global_state().timeline is None
        # every lane's B (begin) events are balanced by E (end) events
        per_lane = Counter()
        for e in events:
            if e.get("ph") in ("B", "E"):
                per_lane[(e.get("pid"), e.get("tid"), e["ph"])] += 1
        lanes = {(p, t) for (p, t, _ph) in per_lane}
        assert lanes, "no span events recorded"
        for p, t in lanes:
            assert per_lane[(p, t, "B")] == per_lane[(p, t, "E")], \
                (p, t, per_lane)
        # spans are cycle-tagged and the clock_sync anchor is present
        assert any(e.get("args", {}).get("cycle") for e in events
                   if e.get("ph") == "B")
        assert any(e.get("name") == "clock_sync" for e in events)
        # a second start after stop works (toggle, not one-shot)
        tl2 = tmp_path / "toggle2.json"
        basics.start_timeline(str(tl2))
        ops.allreduce(np.ones(8, np.float32), name="tg_again")
        basics.stop_timeline()
        assert any(e.get("ph") == "B" for e in json.loads(tl2.read_text()))
    finally:
        state_mod.global_state().shutdown()
        state_mod.reset_global_state()


# ---------------------------------------------------------------------------
# np=2 end-to-end proofs (chaos-marked: multiprocess jobs sort last)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.timeout(240)
def test_metrics_scrape_e2e_np2():
    """Acceptance proof (a): a live np=2 job's ``GET /metrics`` serves
    Prometheus text with cross-rank collective latency histograms and
    per-rank gauges."""
    body = """
import time, urllib.request
for i in range(6):
    hvd.allreduce(np.ones(1024, np.float32), name=f"m{i % 2}")
hvd.barrier()
time.sleep(1.2)
hvd.barrier()
if rank == 0:
    addr = os.environ["HOROVOD_GLOO_RENDEZVOUS_ADDR"]
    port = os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"]
    deadline = time.time() + 30
    text = ""
    while time.time() < deadline:
        text = urllib.request.urlopen(
            f"http://{addr}:{port}/metrics", timeout=5).read().decode()
        if ('hvd_collective_latency_seconds_bucket' in text
                and 'rank="1"' in text):
            break
        time.sleep(0.3)
    assert 'hvd_collective_latency_seconds_bucket' in text, text[:3000]
    assert 'op="ALLREDUCE"' in text, text[:3000]
    assert 'dtype="FLOAT32"' in text, text[:3000]
    assert 'rank="0"' in text and 'rank="1"' in text, text[:3000]
    assert 'hvd_wire_bytes_on_wire_total' in text, text[:3000]
    assert '# TYPE hvd_collective_latency_seconds histogram' in text
    print("SCRAPE_OK", flush=True)
"""
    outs = run_distributed(
        2, body, timeout=180,
        extra_env={"HOROVOD_METRICS_PUSH_SECS": "0.2"})
    assert "SCRAPE_OK" in outs[0], outs[0]


@pytest.mark.chaos
@pytest.mark.timeout(240)
def test_trace_merge_e2e_np2(tmp_path):
    """Acceptance proof (b): per-rank traces from a real np=2 job merge
    into one Chrome trace where both ranks' lanes for the same collective
    share a negotiation cycle id."""
    from horovod_tpu.tools import trace_merge

    tl = tmp_path / "tl.json"
    run_distributed(2, """
for i in range(4):
    hvd.allreduce(np.ones(64, np.float32), name="tm0")
""", timeout=180, extra_env={"HOROVOD_TIMELINE": str(tl)})
    merged_path = tmp_path / "merged.json"
    rc = trace_merge.main([str(tl), f"{tl}.rank1", "-o", str(merged_path)])
    assert rc == 0
    events = json.loads(merged_path.read_text())
    lane_names = {
        (e["pid"], e["tid"]): e["args"]["name"] for e in events
        if e.get("name") == "thread_name" and e.get("ph") == "M"}
    cycles = {0: [], 1: []}
    for e in events:
        if e.get("ph") == "B" and e.get("name") == "ALLREDUCE" \
                and lane_names.get((e["pid"], e["tid"])) == "tm0":
            cycles[e["pid"]].append(e["args"]["cycle"])
    assert cycles[0], "rank 0 recorded no ALLREDUCE spans"
    assert cycles[1], "rank 1 recorded no ALLREDUCE spans"
    assert sorted(cycles[0]) == sorted(cycles[1]), \
        "ranks disagree on the cycle ids of the same collectives"
