"""ResponseCache, ParameterManager, Adasum tests.

Mirrors the reference's split: cache/tuner logic unit-tested in-process
(`test/single/` style), Adasum numerics under real worker processes against
the closed-form operator (`test_adasum_pytorch.py` style).
"""

import numpy as np
import pytest

from horovod_tpu.core.messages import DataType, Request, RequestType
from horovod_tpu.core.parameter_manager import (
    _CODECS,
    _sign_test_p,
    BayesianOptimization,
    CodecArm,
    GaussianProcess,
    ParameterManager,
)
from horovod_tpu.core.response_cache import (
    CoordinatorCache,
    WorkerCacheMirror,
    cache_key,
)

from .helpers import run_distributed


def _req(name="t", shape=(4,), rank=1):
    return Request(request_rank=rank, request_type=RequestType.ALLREDUCE,
                   tensor_name=name, tensor_type=DataType.FLOAT32,
                   tensor_shape=list(shape))


class TestResponseCache:
    def test_insert_lookup_rehydrate(self):
        cache = CoordinatorCache(capacity=8)
        bit, evicted = cache.maybe_insert(_req())
        assert bit == 0 and evicted == []
        assert cache.lookup(cache_key(_req())) == 0
        re = cache.rehydrate(0, rank=3)
        assert re.request_rank == 3 and re.tensor_name == "t"
        # same key again: no new assignment
        assert cache.maybe_insert(_req()) == (None, [])

    def test_shape_change_evicts_stale_entry(self):
        cache = CoordinatorCache(capacity=8)
        bit0, _ = cache.maybe_insert(_req(shape=(4,)))
        bit1, evicted = cache.maybe_insert(_req(shape=(8,)))
        assert evicted == [bit0] and bit1 != bit0
        # old bit resolves through the tombstone for a few cycles
        assert cache.rehydrate(bit0, rank=1) is not None
        for _ in range(5):
            cache.tick()
        assert cache.rehydrate(bit0, rank=1) is None

    def test_lru_eviction_and_mirror(self):
        cache = CoordinatorCache(capacity=2)
        mirror = WorkerCacheMirror()
        assignments, evictions = [], []
        for i in range(3):
            bit, ev = cache.maybe_insert(_req(name=f"t{i}"))
            assignments.append((bit, _req(name=f"t{i}")))
            evictions.extend(ev)
        assert len(cache) == 2 and evictions  # t0 evicted
        mirror.apply(assignments, evictions)
        assert mirror.hit(_req(name="t0")) is None
        assert mirror.hit(_req(name="t2")) is not None
        # mirror miss on changed shape
        assert mirror.hit(_req(name="t2", shape=(9,))) is None

    def test_uncacheable_ops_skipped(self):
        cache = CoordinatorCache(capacity=8)
        req = _req()
        req.request_type = RequestType.ALLGATHER
        assert cache.maybe_insert(req) == (None, [])


class TestParameterManager:
    def test_gp_regression_interpolates(self):
        gp = GaussianProcess(length_scale=0.5, noise=1e-6)
        x = np.array([[0.0, 0.0], [1.0, 1.0], [0.5, 0.5]])
        y = np.array([0.0, 1.0, 0.5])
        gp.fit(x, y)
        mu, sigma = gp.predict(np.array([[0.5, 0.5]]))
        assert abs(mu[0] - 0.5) < 0.05
        assert sigma[0] < 0.2

    def test_bo_suggestions_in_bounds(self):
        bo = BayesianOptimization(seed=1)
        for i in range(6):
            fusion_mb, cycle = bo.suggest()
            assert 0.0 <= fusion_mb <= 64.0
            assert 1.0 <= cycle <= 25.0
            bo.observe((fusion_mb, cycle), float(i))

    def test_manager_settles_on_best(self):
        pm = ParameterManager(enabled=True, warmup_samples=1,
                              steps_per_sample=2, max_samples=4)
        changes = []
        for _ in range(40):
            tuned = pm.update(nbytes=1 << 20)
            if tuned is not None:
                changes.append(tuned)
        assert changes, "tuner never moved"
        assert pm._done
        # settled values must be a previously suggested configuration
        assert pm.fusion_threshold_bytes >= 0
        assert 1.0 <= pm.cycle_time_ms <= 25.0
        # no further movement after settling
        assert pm.update(nbytes=1 << 20) is None



    def test_idle_cycles_do_not_advance_samples(self, tmp_path):
        """The background loop ticks when idle too (a park of 1 ms doubling
        to cycle_time_ms between rounds); zero-byte cycles must not close samples (else the tuner scores
        noise — reference parameter_manager.cc:148-159 steps by actual
        reductions)."""
        pm = ParameterManager(enabled=True, warmup_samples=0,
                              steps_per_sample=2, max_samples=2)
        for _ in range(50):
            assert pm.update(nbytes=0) is None
        assert pm._samples_seen == 0
        pm.update(nbytes=100)
        for _ in range(50):
            pm.update(nbytes=0)
        assert pm._samples_seen == 0      # still mid-sample
        assert pm.update(nbytes=100) is not None   # closes the sample
        assert pm._samples_seen == 1

    def test_idle_gap_not_billed_to_sample_score(self, monkeypatch):
        """An idle gap BETWEEN samples must not inflate the next sample's
        elapsed time: the clock restarts on the first counted step."""
        from horovod_tpu.core import parameter_manager as pm_mod

        now = [0.0]
        monkeypatch.setattr(pm_mod.time, "monotonic", lambda: now[0])
        pm = ParameterManager(enabled=True, warmup_samples=0,
                              steps_per_sample=2, max_samples=8)
        now[0] = 100.0                 # long idle gap after init
        for _ in range(5):
            pm.update(nbytes=0)        # idle ticks during the gap
        pm.update(nbytes=1000)         # first counted step: clock restarts
        now[0] = 101.0
        pm.update(nbytes=1000)         # closes the sample after 1s
        # score must be 2000 bytes / 1s, not 2000/101s
        assert pm._bo._ys, "sample was not observed"
        assert abs(pm._bo._ys[-1] - 2000.0) < 1.0, pm._bo._ys

    def test_sample_clock_pins_unbiased_rate(self, monkeypatch):
        """Regression for the ADVICE r5 N/(N-1) bias: from sample 2 on,
        the clock anchors at the PREVIOUS sample's close, so N counted
        steps score over N inter-step intervals.  The old first-step
        restart scored this scenario at 2000 bytes/s (2x) instead of
        1000."""
        from horovod_tpu.core import parameter_manager as pm_mod

        now = [0.0]
        monkeypatch.setattr(pm_mod.time, "monotonic", lambda: now[0])
        pm = ParameterManager(enabled=True, warmup_samples=0,
                              steps_per_sample=2, max_samples=8)
        # sample 1: counted steps at t=1, 2 (first-ever sample keeps the
        # first-step clock start — no earlier close exists)
        for t in (1.0, 2.0):
            now[0] = t
            pm.update(nbytes=1000)
        # sample 2: counted steps at t=3, 4 → 2000 bytes over the two
        # intervals since the t=2 close = exactly 1000 bytes/s.
        for t in (3.0, 4.0):
            now[0] = t
            pm.update(nbytes=1000)
        assert pm._bo._ys, "sample 2 was not observed"
        assert abs(pm._bo._ys[-1] - 1000.0) < 1e-6, pm._bo._ys

    def test_autotune_log_csv_artifact(self, tmp_path):
        """--autotune-log-file emits the per-sample CSV record family the
        reference writes via HOROVOD_AUTOTUNE_LOG
        (parameter_manager.h:112, .cc:81,266-291): a header naming the
        tunables, one row per sample with (params, score), and a final
        best row when the tuner settles."""
        log = tmp_path / "autotune.csv"
        pm = ParameterManager(enabled=True, warmup_samples=1,
                              steps_per_sample=2, max_samples=3,
                              log_path=str(log))
        for _ in range(40):
            pm.update(nbytes=1 << 20)
        assert pm._done
        lines = log.read_text().strip().splitlines()
        assert lines[0] == ("sample,cycle_time_ms,"
                            "tensor_fusion_threshold_mb,score_bytes_per_sec")
        samples, best = lines[1:-1], lines[-1]
        assert len(samples) == 4  # warmup + max_samples
        for i, row in enumerate(samples):
            idx, cycle, fusion_mb, score = row.split(",")
            assert int(idx) == i + 1
            assert 0.0 < float(cycle) <= 50.0
            assert float(fusion_mb) >= 0.0
            assert float(score) > 0.0
        b0, bcycle, bfusion, bscore = best.split(",")
        assert b0 == "best"
        # the settled params are what the manager now reports
        assert abs(float(bcycle) - pm.cycle_time_ms) < 0.01
        assert abs(float(bfusion)
                   - pm.fusion_threshold_bytes / 1048576.0) < 0.01

    def test_codec_sign_test_matches_ab_harness(self):
        """The local gate must be numerically identical to the PR-10 A/B
        harness sign test — one formula, two call sites."""
        from benchmarks.ab_harness import sign_test_p

        for wins in range(0, 12):
            for losses in range(0, 12):
                assert _sign_test_p(wins, losses) == \
                    sign_test_p(wins, losses), (wins, losses)

    def test_codec_dimension_default_off(self):
        """HOROVOD_AUTOTUNE_CODEC defaults off: no arm, baseline codec
        reported, and the established 4-column CSV schema untouched
        (test_autotune_log_csv_artifact asserts the header verbatim)."""
        pm = ParameterManager(enabled=True, warmup_samples=0,
                              steps_per_sample=1, max_samples=2)
        assert pm._codec_arm is None
        assert pm.codec_under_test == "none"
        for _ in range(5):
            pm.update(nbytes=1 << 20)
        assert pm.recommended_codec == "none"

    def test_codec_arm_pairs_baseline_then_candidate(self):
        """Samples alternate baseline/candidate and candidates rotate
        round-robin, so every codec keeps accruing sign-test pairs."""
        arm = CodecArm()
        seen = []
        for i in range(2 * len(_CODECS[1:])):
            seen.append(arm.under_test)
            arm.observe(100.0)
        assert seen[0::2] == ["none"] * len(_CODECS[1:])
        assert seen[1::2] == list(_CODECS[1:])

    def test_codec_recommended_only_on_significant_win(self):
        """A candidate needs a lopsided paired record to clear the gate:
        6-0 over "none" is p=0.03125 < 0.05 and is recommended; a 3-3
        split (p=1.0) and even a 4-1 edge (p=0.375) are not.  Ties are
        discarded, like the harness."""
        codecs = ("none", "int8")
        win6 = CodecArm(codecs=codecs)
        for _ in range(6):
            win6.observe(100.0)     # baseline
            win6.observe(150.0)     # candidate wins
        assert win6.recommendation() == ("int8", _sign_test_p(6, 0))

        split = CodecArm(codecs=codecs)
        for cand in (150.0, 150.0, 150.0, 50.0, 50.0, 50.0):
            split.observe(100.0)
            split.observe(cand)
        assert split.recommendation() == ("none", 1.0)

        edge = CodecArm(codecs=codecs)
        for cand in (150.0, 150.0, 150.0, 150.0, 50.0):
            edge.observe(100.0)
            edge.observe(cand)
        assert edge.recommendation() == ("none", 1.0)

        ties = CodecArm(codecs=codecs)
        for _ in range(20):
            ties.observe(100.0)
            ties.observe(100.0)     # tie: no pair recorded
        assert ties._wins["int8"] == 0 and ties._losses["int8"] == 0
        assert ties.recommendation() == ("none", 1.0)

    def test_codec_column_in_autotune_log(self, tmp_path):
        """With the arm on, every CSV row carries the codec the sample
        was attributed to and the best row carries the sign-test-gated
        verdict — the report-only surface the env knob promises."""
        log = tmp_path / "autotune.csv"
        pm = ParameterManager(enabled=True, warmup_samples=1,
                              steps_per_sample=2, max_samples=4,
                              log_path=str(log), tune_codec=True)
        for _ in range(40):
            pm.update(nbytes=1 << 20)
        assert pm._done
        lines = log.read_text().strip().splitlines()
        assert lines[0].endswith(",codec")
        for row in lines[1:-1]:
            assert row.split(",")[-1] in _CODECS
        best = lines[-1].split(",")
        assert best[0] == "best" and len(best) == 5
        assert best[-1] == pm.recommended_codec
        # Real cycles are near-identical in score; a significant codec
        # win cannot appear from a handful of noisy pairs.
        assert pm.recommended_codec == "none"

    def test_codec_knob_wires_into_state(self, monkeypatch):
        """HOROVOD_AUTOTUNE_CODEC=1 at init turns the arm on for the
        coordinator's manager (core/state.py wiring); without it the
        manager tunes but reports the baseline codec only."""
        import horovod_tpu.frameworks.jax.basics as basics
        from horovod_tpu.common import env as env_mod
        from horovod_tpu.core import state as state_mod

        monkeypatch.delenv("HOROVOD_SIZE", raising=False)
        monkeypatch.setenv(env_mod.HOROVOD_AUTOTUNE, "1")
        monkeypatch.setenv(env_mod.HOROVOD_AUTOTUNE_CODEC, "1")
        state_mod.reset_global_state()
        basics.init()
        try:
            pm = state_mod.global_state().parameter_manager
            assert pm is not None and pm._codec_arm is not None
            assert pm.codec_under_test == "none"   # baseline half first
        finally:
            state_mod.global_state().shutdown()
            state_mod.reset_global_state()


class TestStallInspector:
    """Coordinator-side stall inspector (``controller._check_stalls``):
    the shutdown path, the mask-path cached-tensor flavor, and the
    both-knobs-disabled early return."""

    def _controller(self, warn=0.0, shut=0.0, size=3, cache=1024):
        from horovod_tpu.common.topology import ProcessTopology
        from horovod_tpu.core.controller import Controller

        topo = ProcessTopology(rank=0, size=size, local_size=size)
        return Controller(topo, mesh=None, stall_warning_secs=warn,
                          stall_shutdown_secs=shut, cache_capacity=cache)

    def _age_everything(self, ctrl, by: float) -> None:
        """Backdate every stall clock so the next check sees `by` seconds
        of age without the test sleeping."""
        import time

        past = time.monotonic() - by
        ctrl._last_stall_check = past
        for entry in ctrl._message_table.values():
            entry.first_seen = past
        for bit in list(ctrl._mask_bit_since):
            ctrl._mask_bit_since[bit] = past

    def test_both_knobs_disabled_early_return(self):
        ctrl = self._controller(warn=0.0, shut=0.0)
        ctrl._increment(_req(name="stuck", rank=1))
        self._age_everything(ctrl, by=10_000.0)
        before = ctrl._last_stall_check
        ctrl._check_stalls()  # no raise, no clock advance: fully disabled
        assert ctrl._last_stall_check == before
        assert "stuck" in ctrl._message_table

    def test_shutdown_path_names_tensor_and_missing_ranks(self):
        from horovod_tpu.common.exceptions import HorovodInternalError

        ctrl = self._controller(warn=0.0, shut=5.0)
        ctrl._increment(_req(name="grad/w0", rank=1))  # ranks 0,2 missing
        self._age_everything(ctrl, by=6.0)
        with pytest.raises(HorovodInternalError) as ei:
            ctrl._check_stalls()
        msg = str(ei.value)
        assert "stall shutdown" in msg
        assert "grad/w0" in msg
        assert "[0, 2]" in msg, msg

    def test_shutdown_independent_of_disabled_warning(self):
        """Disabling warnings must not silently disable the hard abort."""
        from horovod_tpu.common.exceptions import HorovodInternalError

        ctrl = self._controller(warn=0.0, shut=1.0)
        ctrl._increment(_req(name="t", rank=1))
        self._age_everything(ctrl, by=2.0)
        with pytest.raises(HorovodInternalError):
            ctrl._check_stalls()

    def test_mask_path_cached_stall_shutdown_names_tensor(self):
        """A cache-bit announced by a subset of ranks ages past the
        shutdown deadline: the abort must name the CACHED tensor (via the
        coordinator cache template), not just a bit number."""
        from horovod_tpu.common.exceptions import HorovodInternalError
        from horovod_tpu.core.response_cache import cache_key

        ctrl = self._controller(warn=0.0, shut=5.0)
        bit, _ = ctrl._cache.maybe_insert(_req(name="cached/t", rank=0))
        ctrl._pending_masks[1] = 1 << bit  # rank 1 announced; 0,2 missing
        ctrl._mask_bit_since[bit] = 0.0
        self._age_everything(ctrl, by=6.0)
        with pytest.raises(HorovodInternalError) as ei:
            ctrl._check_stalls()
        msg = str(ei.value)
        assert "stall shutdown" in msg and "cached/t" in msg
        assert "[0, 2]" in msg, msg

    def test_mask_path_warning_converts_and_invalidates(self):
        """Below shutdown but past warning, a stalled cached bit converts
        its partial announcements into table tallies and evicts the cache
        entry so a post-recovery resubmission renegotiates from scratch."""
        ctrl = self._controller(warn=5.0, shut=0.0)
        bit, _ = ctrl._cache.maybe_insert(_req(name="cached/w", rank=0))
        ctrl._pending_masks[1] = 1 << bit
        ctrl._mask_bit_since[bit] = 0.0
        self._age_everything(ctrl, by=6.0)
        ctrl._check_stalls()
        # bit cleared from the mask path, tallied in the message table
        assert bit not in ctrl._mask_bit_since
        assert "cached/w" in ctrl._message_table
        assert ctrl._message_table["cached/w"].ranks == {1}
        # cache entry invalidated: the eviction is queued for broadcast
        assert bit in ctrl._cycle_evictions


def test_cache_steady_state_hits_and_correctness():
    """Same tensor allreduced across many steps: later steps ride the cache
    bit path and results stay exact."""
    out = run_distributed(2, """
from horovod_tpu.core.state import global_state

for step in range(6):
    val = np.full(8, float((rank + 1) * (step + 1)), np.float32)
    result = hvd.allreduce(val, op=hvd.Sum, name="grad.w")
    expected = (1 + 2) * (step + 1)
    assert np.allclose(np.asarray(result), expected), (step, result)

ctrl = global_state().controller
if rank != 0:
    assert ctrl.cache_hit_count > 0, "cache fast path never used"
    assert ctrl.cache_hit_count >= ctrl.cache_miss_count, (
        ctrl.cache_hit_count, ctrl.cache_miss_count)
print("CACHE_OK", rank, flush=True)
""")
    for r, o in enumerate(out):
        assert f"CACHE_OK {r}" in o


def test_adasum_two_rank_matches_formula():
    """VHDD with 2 ranks matches the closed-form Adasum operator computed
    on the FULL vectors: the per-level (dot, ||a||², ||b||²) triplets are
    allreduced across the reduction group before coefficients are formed
    (reference adasum.h:368 SumAllreduceWithComm), so slicing does not
    change the math."""
    out = run_distributed(2, """
a = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
b = np.array([2.0, 2.0, -1.0, 0.5], np.float32)
mine = a if rank == 0 else b
result = np.asarray(hvd.allreduce(mine, op=hvd.Adasum, name="adasum.t"))

def combine(x, y):
    dot = float(np.dot(x, y)); nx = float(np.dot(x, x)); ny = float(np.dot(y, y))
    cx = 1 - dot / (2 * nx) if nx > 0 else 1.0
    cy = 1 - dot / (2 * ny) if ny > 0 else 1.0
    return cx * x + cy * y

expected = combine(a, b)
assert np.allclose(result, expected, atol=1e-5), (result, expected)
print("ADASUM_OK", rank, flush=True)
""")
    for r, o in enumerate(out):
        assert f"ADASUM_OK {r}" in o


def test_adasum_four_rank_matches_formula():
    """4-rank VHDD: pairwise tree of full-vector combines — (r0⊕r1) ⊕
    (r2⊕r3) with global coefficients at both levels."""
    out = run_distributed(4, """
vecs = [np.array([1.0, 2.0, 3.0, 4.0], np.float32),
        np.array([2.0, 2.0, -1.0, 0.5], np.float32),
        np.array([-1.0, 0.5, 2.0, 1.0], np.float32),
        np.array([0.5, -2.0, 1.0, 3.0], np.float32)]
result = np.asarray(hvd.allreduce(vecs[rank], op=hvd.Adasum, name="adasum.q"))

def combine(x, y):
    x = x.astype(np.float64); y = y.astype(np.float64)
    dot = float(x @ y); nx = float(x @ x); ny = float(y @ y)
    cx = 1 - dot / (2 * nx) if nx > 0 else 1.0
    cy = 1 - dot / (2 * ny) if ny > 0 else 1.0
    return cx * x + cy * y

expected = combine(combine(vecs[0], vecs[1]), combine(vecs[2], vecs[3]))
assert np.allclose(result, expected, atol=1e-4), (result, expected)
print("ADASUM4_OK", rank, flush=True)
""")
    for r, o in enumerate(out):
        assert f"ADASUM4_OK {r}" in o


def test_adasum_zero_gradient_passthrough():
    """A zero gradient has coefficient 1.0 on the other side (reference
    adasum.h:385-391): adasum(0, g) == g, not g/2."""
    out = run_distributed(2, """
g = np.array([1.0, -2.0, 3.0], np.float32)
mine = np.zeros(3, np.float32) if rank == 0 else g
result = np.asarray(hvd.allreduce(mine, op=hvd.Adasum, name="adasum.z"))
assert np.allclose(result, g, atol=1e-5), result
print("ZERO_OK", rank, flush=True)
""")
    for r, o in enumerate(out):
        assert f"ZERO_OK {r}" in o


def test_adasum_identical_gradients_average():
    """Identical inputs are scale-halved (dot == ||a||²  → coefficient 1/2
    each): Adasum of equal gradients is their average."""
    out = run_distributed(2, """
val = np.full(6, 4.0, np.float32)
result = np.asarray(hvd.allreduce(val, op=hvd.Adasum, name="adasum.same"))
assert np.allclose(result, 4.0, atol=1e-5), result
print("SAME_OK", rank, flush=True)
""")
    for r, o in enumerate(out):
        assert f"SAME_OK {r}" in o


def test_autotune_end_to_end():
    """HOROVOD_AUTOTUNE tunes without breaking correctness; params move."""
    out = run_distributed(2, """
for step in range(30):
    v = np.full(64, float(rank + step), np.float32)
    r = hvd.allreduce(v, op=hvd.Sum, name="t")
    assert np.allclose(np.asarray(r), (0 + 1) + 2 * step), (step, r)
from horovod_tpu.core.state import global_state
st = global_state()
if rank == 0:
    assert st.parameter_manager is not None
    assert st.parameter_manager._samples_seen > 0, "tuner saw no samples"
print("TUNE_OK", rank, flush=True)
""", extra_env={"HOROVOD_AUTOTUNE": "1",
                "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "3"})
    for r, o in enumerate(out):
        assert f"TUNE_OK {r}" in o


def test_adasum_four_rank_identity():
    """adasum(a, a) == a at every VHDD level: 4 identical gradients pass
    through unchanged (exercises both distance rounds + allgather-back)."""
    out = run_distributed(4, """
val = np.arange(1, 9, dtype=np.float32)
result = np.asarray(hvd.allreduce(val, op=hvd.Adasum, name="adasum.id"))
assert np.allclose(result, val, atol=1e-5), (result, val)
print("ID_OK", rank, flush=True)
""")
    for r, o in enumerate(out):
        assert f"ID_OK {r}" in o


def test_adasum_odd_length_and_non_pow2_world():
    """5-element tensor pads through VHDD cleanly; 3-rank world falls back
    to the ring op (plain sum) instead of erroring."""
    out = run_distributed(2, """
a = np.array([1.0, 2.0, 3.0, 4.0, 5.0], np.float32)
result = np.asarray(hvd.allreduce(a, op=hvd.Adasum, name="adasum.odd"))
assert result.shape == (5,) and np.all(np.isfinite(result)), result
# identical inputs -> identity
assert np.allclose(result, a, atol=1e-5), result
print("ODD_OK", rank, flush=True)
""", timeout=240)
    for r, o in enumerate(out):
        assert f"ODD_OK {r}" in o

    out = run_distributed(3, """
v = np.ones(4, np.float32)
result = np.asarray(hvd.allreduce(v, op=hvd.Adasum, name="adasum.np2"))
# averaging ring fallback: identical gradients -> ~g, matching Adasum's
# identical-gradient behavior instead of a silent size-x sum
assert np.allclose(result, 1.0), result
print("NP2_OK", rank, flush=True)
""", timeout=240)
    for r, o in enumerate(out):
        assert f"NP2_OK {r}" in o
