"""Tree negotiation fan-in (core/negotiation_fanin.py): fold semantics,
role/plan derivation, heartbeat conviction, veto bookkeeping, and a live
np=4 two-loopback-host run counter-asserting the O(ranks) -> O(hosts)
coordinator-ingress drop with bit-identical results against the star.

The degrade protocol's crash/reorder interleavings are model-checked in
tests/test_mck_proto.py (hvd-mck's fanin_degrade scenario); the
aggregator-death chaos test (abort -> reshard -> bit-identical
convergence) lives with the other elastic proofs in
tests/test_fault_injection_elastic.py.
"""

import os
import time

import numpy as np
import pytest

from horovod_tpu.common import env as env_mod
from horovod_tpu.common.exceptions import (
    AggregatorStaleError,
    HorovodInternalError,
)
from horovod_tpu.common.topology import ProcessTopology
from horovod_tpu.core.messages import (
    HostMaskFrame,
    MaskFrame,
    is_host_mask_frame,
)
from horovod_tpu.core.negotiation_fanin import (
    AggregatorHeartbeat,
    FaninPlan,
    active_vetoes,
    build_plan,
    fold_host,
    resolve_mode,
)
from horovod_tpu.elastic.fanin import HEARTBEAT_STALE_PERIODS

from .helpers import run_distributed


def _mask(bits: int, shutdown: bool = False) -> bytes:
    size = max(1, (bits.bit_length() + 7) // 8)
    return MaskFrame(mask=bits.to_bytes(size, "little"),
                     shutdown=shutdown).to_bytes()


def _topo(rank, size, ls):
    return ProcessTopology(rank=rank, size=size, local_rank=rank % ls,
                           local_size=ls, cross_rank=rank // ls,
                           cross_size=size // ls)


class TestFoldHost:
    def test_equal_masks_collapse_to_one_host_frame(self):
        entries = fold_host([(4, _mask(0b0110)), (5, _mask(0b0110)),
                             (6, _mask(0b0110))])
        assert len(entries) == 1
        rank, payload = entries[0]
        assert rank == 4 and is_host_mask_frame(payload)
        frame = HostMaskFrame.from_bytes(payload)
        assert frame.covered == [4, 5, 6]
        assert frame.mask_int == 0b0110
        assert frame.shutdown is False

    def test_a_bit_one_rank_announces_alone_reaches_the_coordinator(self):
        """A worker says a cached tensor's bit once, in the cycle it pops
        the request, and the coordinator keeps it pending for that rank.
        So the fold may merge frames and never masks: ANDed with a
        neighbour that announces a cycle later, both bits were lost and
        the job waited for ever (the wedge of ROADMAP D0 (ii))."""
        first = fold_host([(2, _mask(0)), (3, _mask(0b10))])
        later = fold_host([(2, _mask(0b10)), (3, _mask(0))])
        for entries, announcer in ((first, 3), (later, 2)):
            said = {}
            for _rank, payload in entries:
                frame = HostMaskFrame.from_bytes(payload)
                for covered in frame.covered:
                    said[covered] = frame.mask_int
            assert said == {announcer: 0b10, 5 - announcer: 0}

    def test_one_frame_a_distinct_mask(self):
        entries = fold_host([(4, _mask(0b0111)), (5, _mask(0b1011)),
                             (6, _mask(0b0111)), (7, _mask(0))])
        frames = [HostMaskFrame.from_bytes(p) for _r, p in entries]
        assert [(f.covered, f.mask_int) for f in frames] == [
            ([4, 6], 0b0111), ([5], 0b1011), ([7], 0)]

    def test_shutdown_is_or_of_covered_flags(self):
        entries = fold_host([(2, _mask(0b11)), (3, _mask(0b11,
                                                         shutdown=True))])
        assert HostMaskFrame.from_bytes(entries[0][1]).shutdown is True

    def test_non_mask_payloads_pass_unfolded(self):
        full = b"not-a-mask-frame"
        entries = fold_host([(2, _mask(0b10)), (3, full), (4, _mask(0b10))])
        assert entries == sorted(entries)
        assert (3, full) in entries
        frames = [e for e in entries if is_host_mask_frame(e[1])]
        assert len(frames) == 1
        assert HostMaskFrame.from_bytes(frames[0][1]).covered == [2, 4]

    def test_wide_masks_survive_per_host_bit_offsets(self):
        """Cache bits are a global big-int bitvector: a host whose ranks
        announce bits far past the first byte must fold without
        truncation (the little-endian width follows the mask's
        bit_length, not any fixed frame size)."""
        wide = (1 << 300) | (1 << 9) | 1
        entries = fold_host([(8, _mask(wide)), (9, _mask(wide))])
        frame = HostMaskFrame.from_bytes(entries[0][1])
        assert frame.covered == [8, 9] and frame.mask_int == wide
        # round-trips through the wire encoding untruncated
        assert HostMaskFrame.from_bytes(frame.to_bytes()).mask_int \
            == frame.mask_int

    def test_fold_is_pure_and_order_insensitive(self):
        """The mck model leans on the fold being a pure per-cycle
        function; the live bundle leans on member arrival order being
        invisible (covered is sorted, and so are the entries)."""
        a = [(4, _mask(0b0110)), (5, _mask(0b0011)), (6, _mask(0b0110))]
        assert fold_host(a) == fold_host(a) == fold_host(list(reversed(a)))

    def test_empty_input_folds_to_nothing(self):
        assert fold_host([]) == []


class TestResolveModeAndPlan:
    def test_auto_on_for_blocked_multihost(self, monkeypatch):
        monkeypatch.delenv(env_mod.HOROVOD_NEGOTIATION_FANIN, raising=False)
        assert resolve_mode(_topo(0, 4, 2)) == "on"

    @pytest.mark.parametrize("size,ls", [(2, 1), (4, 4), (4, 1), (8, 8)])
    def test_auto_off_when_tree_cannot_pay(self, monkeypatch, size, ls):
        """Single-rank hosts have nothing to fold and single-host jobs
        have no cross link to save: auto stays off (the bypass the
        ISSUE's satellite names)."""
        monkeypatch.delenv(env_mod.HOROVOD_NEGOTIATION_FANIN, raising=False)
        assert resolve_mode(_topo(1, size, ls)) == "off"

    def test_forced_off_and_bad_values(self, monkeypatch):
        monkeypatch.setenv(env_mod.HOROVOD_NEGOTIATION_FANIN, "0")
        assert resolve_mode(_topo(0, 4, 2)) == "off"
        monkeypatch.setenv(env_mod.HOROVOD_NEGOTIATION_FANIN, "banana")
        with pytest.raises(ValueError):
            resolve_mode(_topo(0, 4, 2))

    def test_forced_on_bad_layout_is_loud(self, monkeypatch):
        monkeypatch.setenv(env_mod.HOROVOD_NEGOTIATION_FANIN, "1")
        with pytest.raises(HorovodInternalError):
            resolve_mode(_topo(0, 4, 4))       # single host

    def test_roles_at_2x3(self):
        """np=6, local_size=2, three hosts: host 0 is direct (its
        would-be aggregator IS the coordinator), hosts 1-2 tree."""
        plans = {r: build_plan(_topo(r, 6, 2)) for r in range(6)}
        assert plans[0].role == "coordinator"
        assert plans[0].coordinator_senders == (1, 2, 4)
        assert plans[0].bundle_senders == frozenset({2, 4})
        assert plans[1].role == "direct"
        assert plans[2].role == "aggregator"
        assert plans[2].member_ranks == (3,)
        assert plans[3].role == "member"
        assert plans[3].aggregator_rank == 2
        assert plans[4].role == "aggregator" and plans[5].role == "member"

    def test_vetoed_host_degrades_to_direct(self):
        """A vetoed host's ranks all run direct and the coordinator
        expects them individually — exactly the star wire shape for that
        host, nothing silenced."""
        plans = {r: build_plan(_topo(r, 6, 2), vetoed_hosts=[1])
                 for r in range(6)}
        assert plans[2].role == "direct" and plans[3].role == "direct"
        assert plans[0].coordinator_senders == (1, 2, 3, 4)
        assert plans[0].bundle_senders == frozenset({4})
        assert plans[4].role == "aggregator"        # host 2 still trees

    def test_unblocked_layout_refused(self):
        bad = ProcessTopology(rank=1, size=4, local_rank=0, local_size=2,
                              cross_rank=1, cross_size=2)
        with pytest.raises(HorovodInternalError):
            build_plan(bad)


class TestAggregatorHeartbeat:
    def _hb(self, tmp_path, is_aggregator, period=1.0):
        return AggregatorHeartbeat(str(tmp_path / "hb"), period,
                                   aggregator_rank=2, cross_rank=1,
                                   is_aggregator=is_aggregator)

    def _mock_clock(self, monkeypatch, start=1000.0):
        """Drive both the heartbeat's wall clock AND the file mtimes it
        stats from one fake clock (os.utime(None) would otherwise stamp
        REAL time and every age computation would go negative)."""
        now = [start]
        real_utime = os.utime
        monkeypatch.setattr(time, "time", lambda: now[0])
        monkeypatch.setattr(
            os, "utime", lambda p, t=None: real_utime(p, (now[0], now[0])))
        return now

    def test_absent_file_fresh_during_arming_grace(self, tmp_path,
                                                   monkeypatch):
        now = self._mock_clock(monkeypatch)
        hb = self._hb(tmp_path, is_aggregator=False)
        hb.check()                                  # armed just now: fresh
        now[0] += HEARTBEAT_STALE_PERIODS - 0.1
        hb.check()                                  # still inside grace
        now[0] += 0.6                               # past grace + rate limit
        with pytest.raises(AggregatorStaleError) as ei:
            hb.check()
        assert ei.value.aggregator_rank == 2

    def test_touch_keeps_member_fresh_until_window(self, tmp_path,
                                                   monkeypatch):
        now = self._mock_clock(monkeypatch)
        agg = self._hb(tmp_path, is_aggregator=True)
        member = self._hb(tmp_path, is_aggregator=False)
        for _ in range(5):
            now[0] += 1.0
            agg.touch()
            member.check()                          # fresh every period
        # the aggregator wedges: stops touching; ~1.5 periods later the
        # member convicts (HEARTBEAT_STALE_PERIODS shared with
        # elastic/fanin.py so both planes degrade on the same clock)
        now[0] += HEARTBEAT_STALE_PERIODS + 0.1
        with pytest.raises(AggregatorStaleError):
            member.check()

    def test_checks_are_rate_limited(self, tmp_path, monkeypatch):
        now = self._mock_clock(monkeypatch)
        self._hb(tmp_path, is_aggregator=True)      # stamps the file once
        member = self._hb(tmp_path, is_aggregator=False)
        now[0] += HEARTBEAT_STALE_PERIODS + 1.0     # stale by now...
        member._last_check = now[0] - 0.1           # ...but just checked
        member.check()                              # rate limit: no stat
        now[0] += 0.5
        with pytest.raises(AggregatorStaleError):
            member.check()


class TestVetoBookkeeping:
    def test_active_vetoes_window_and_malformed(self, monkeypatch):
        monkeypatch.setenv(env_mod.HOROVOD_NEGOTIATION_FANIN_VETO_EPOCHS,
                           "2")
        records = {
            "host-a": {"epoch": 9},                 # 1 epoch old: active
            "host-b": {"epoch": 8},                 # 2 epochs old: expired
            "host-c": {"epoch": 10},                # this epoch: active
            "host-d": {"epoch": "not-an-int"},      # malformed: ignored
            "host-e": {},                           # malformed: ignored
        }
        assert active_vetoes(records, epoch=10) == ["host-a", "host-c"]


# ---------------------------------------------------------------------------
# live np=4 (2 simulated hosts x 2 ranks): the counter-asserted
# O(ranks) -> O(hosts) ingress drop, with star-vs-tree bit-identity
# ---------------------------------------------------------------------------

_NP4_BODY = """
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.core.state import global_state

hvd.init()
for i in range(6):
    out = hvd.allreduce(np.full(4, float(hvd.rank() + i), np.float32),
                        op=hvd.Sum, name=f"t{i}")
    print("SUM", i, hvd.rank(), np.asarray(out).tobytes().hex(), flush=True)
state = global_state()
c, rank = state.controller, hvd.rank()
plan = c.fanin_plan
print("ROLE", rank, plan.role if plan else "none", flush=True)
hvd.shutdown()
# After the loop's last cycle: the counters and the count of cycles are
# of the same moment.
print("COUNTS", rank, c.ingress_frame_count,
      c.fanin_tree_frame_count, c.fanin_direct_frame_count,
      c.fanin_fallback_count, state.cycle_count, flush=True)
"""


@pytest.mark.timeout(300)
def test_np4_tree_ingress_o_hosts_bit_identical_to_star():
    """Two loopback hosts x two ranks.  Under the tree the coordinator
    ingests 2 frames a cycle (host 0's direct member + host 1's bundle)
    where the star's ingests 3 — the counter assertion, not wall-clock,
    and each job held to its own count of cycles, which follows the
    machine's load — and every rank's allreduce bytes are identical
    between the two modes (the fold only merges frames that say the same
    thing, so the agreed masks and therefore the math cannot move)."""
    runs = {}
    for mode in ("auto", "0"):
        outs = run_distributed(
            4, _NP4_BODY, timeout=180, local_size=2,
            extra_env={"HOROVOD_NEGOTIATION_FANIN": mode})
        parsed = {"sums": {}, "roles": {}, "counts": {}}
        for out in outs:
            for line in out.splitlines():
                parts = line.split()
                if parts[:1] == ["SUM"]:
                    parsed["sums"][(int(parts[1]), int(parts[2]))] = parts[3]
                elif parts[:1] == ["ROLE"]:
                    parsed["roles"][int(parts[1])] = parts[2]
                elif parts[:1] == ["COUNTS"]:
                    parsed["counts"][int(parts[1])] = [int(x)
                                                       for x in parts[2:]]
        runs[mode] = parsed

    tree, star = runs["auto"], runs["0"]
    assert tree["roles"] == {0: "coordinator", 1: "direct",
                             2: "aggregator", 3: "member"}
    assert star["roles"] == {r: "none" for r in range(4)}
    # bit-identity: every (tensor, rank) sum matches across modes
    assert tree["sums"] == star["sums"]
    assert len(tree["sums"]) == 24
    # ingress drop, counter-asserted: senders a cycle 3 -> 2.  How many
    # cycles a job takes is its own affair (an idle cycle more under
    # load), so each job's frames are held to its own cycles.  No
    # fallbacks fired.
    star_ingress, star_cycles = star["counts"][0][0], star["counts"][0][4]
    tree_ingress, tree_cycles = tree["counts"][0][0], tree["counts"][0][4]
    assert star_cycles > 0 and tree_cycles > 0
    assert star_ingress == 3 * star_cycles, (star_ingress, star_cycles)
    assert tree_ingress == 2 * tree_cycles, (tree_ingress, tree_cycles)
    assert all(c[3] == 0 for c in tree["counts"].values())
    # the tree actually carried frames on both tree roles, and host 0's
    # non-coordinator rank rode the counted direct path
    assert tree["counts"][2][1] > 0 and tree["counts"][3][1] > 0
    assert tree["counts"][1][2] > 0


_NP4_STAGGERED_BODY = """
import time
import numpy as np
import horovod_tpu as hvd

hvd.init()
for i in range(5):
    # From the second pass on the tensor is a cache bit.  Host 1's two
    # ranks say it in different cycles (a cycle is milliseconds), in
    # either order.
    if i >= 2 and hvd.rank() == 2 + i % 2:
        time.sleep(0.3)
    out = hvd.allreduce(np.full(4, float(i), np.float32), op=hvd.Sum,
                        name="g")
    print("SUM", i, hvd.rank(), np.asarray(out).tolist(), flush=True)
hvd.shutdown()
"""


@pytest.mark.timeout(300)
def test_np4_hosts_ranks_that_announce_cycles_apart_still_agree():
    """The wedge of ROADMAP D0 (ii), with no fault and no load: the
    aggregator and its member announce one cached tensor a few cycles
    apart.  A worker says the bit once; ANDed into one host frame with a
    neighbour that has not said it yet, it never reached the coordinator
    and all four ranks waited without end."""
    outs = run_distributed(4, _NP4_STAGGERED_BODY, timeout=60, local_size=2,
                           retries=0)
    for rank, out in enumerate(outs):
        for i in range(5):
            assert f"SUM {i} {rank} {[4.0 * i] * 4}" in out, (rank, out)
