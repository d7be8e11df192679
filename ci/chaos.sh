#!/bin/sh
# Chaos lane (mirrors ci/real_integrations.sh): runs the fault-injection
# suite standalone — deterministic kill/hang/drop/starve faults against
# np=2/np=4 worker jobs, asserting the no-hang property (coordinated
# errors on all survivors, or a successful elastic recovery) under
# per-test wall-clock bounds.  The integrity-plane cases (wire-CRC
# corruption, truncated frames, kill-mid-ckpt.save, and the elastic
# corruption-recovery bit-identical proof) ride the same lane, as do the
# control-plane survivability cases (lease-expiry epoch advance, and the
# SIGKILL-and-restart of the external journaled rendezvous server that
# must converge bit-identical with zero epoch bumps —
# docs/control_plane.md); suite
# order keeps them AFTER the fast in-process spec tests and np=2/np=4
# abort cases, per the tier-1 budget rule — heavy multiprocess tests run
# late so DOTS_PASSED comparison stays meaningful on the 1-core box.
#
#   sh ci/chaos.sh [extra pytest args...]
#
# Needs only the repo's baseline deps (jax + numpy + pytest); the faults
# are injected via HOROVOD_FAULT_SPEC inside each test, so the lane is
# self-contained.  A hang here is a failed TEST, not a wedged lane: every
# chaos test carries a @pytest.mark.timeout SIGALRM watchdog
# (tests/conftest.py) on top of the harness's own subprocess timeouts.
set -eu
ROOT="$(cd "$(dirname "$0")/.." && pwd)"
cd "$ROOT"

# Sweep stale flight-recorder dumps BEFORE asserting: a crashed or
# aborted earlier run leaves hvd_flight_recorder/ post-mortems in the
# cwd, and any "dump exists / dump absent" assertion in the suite would
# then judge last week's wreckage instead of this run's.
rm -rf hvd_flight_recorder/ hvd_flight_recorder.rank*.json

# No `... | tee` here: plain sh has no pipefail, so a pipeline would
# swallow pytest's exit status and always report PASSED.  The slow-marked
# np=8 reshard proofs are excluded here and run in their own lane below.
rc=0
JAX_PLATFORMS=cpu python -m pytest tests/test_fault_injection.py \
    tests/test_fault_injection_elastic.py -m "chaos and not slow" \
    -v -p no:cacheprovider "$@" > ci/chaos.last.log 2>&1 || rc=$?
cat ci/chaos.last.log
[ "$rc" -eq 0 ] || { echo "chaos lane FAILED (rc=$rc)"; exit "$rc"; }

# Large-mesh lane (ISSUE 15): a bounded np=128 simulated cluster —
# the REAL journaled server + elastic driver over a shaped wire
# (horovod_tpu/sim/, docs/sim_cluster.md) — completes churn epochs
# including a coordinated abort, with the lock-dependency tracker armed
# and ZERO inversion cycles across the batched server/store/journal
# lock nests.  Deterministic: fixed HOROVOD_SIM_SEED, tight timeouts.
echo "large-mesh lane: np=128 simulated churn under HOROVOD_LOCK_DEBUG=1"
rc=0
JAX_PLATFORMS=cpu HOROVOD_LOCK_DEBUG=1 HOROVOD_SIM_SEED=0 \
python - > ci/chaos.largemesh.log 2>&1 <<'EOF' || rc=$?
from horovod_tpu.common import lockdep
from horovod_tpu.sim.cluster import COORDINATED_ABORT, SimCluster

rec = SimCluster(128, slots_per_host=8, seed=0, lease_timeout=1.2,
                 renew_period=0.25).run(events=4)
assert rec["final_epoch"] == 4, rec
assert rec["events"][-1]["kind"] == COORDINATED_ABORT, rec
assert rec["attribution"]["coverage"] >= 0.90, rec["attribution"]
cycles = lockdep.find_cycles()
assert not cycles, f"lock inversion cycles: {cycles}"
print(f"np=128 churn: {rec['final_epoch']} epochs, "
      f"abort {rec['coordinated_abort_ms']:.0f}ms, "
      f"coverage {rec['attribution']['coverage']:.2%}, 0 lock cycles")
EOF
cat ci/chaos.largemesh.log
[ "$rc" -eq 0 ] || { echo "large-mesh lane FAILED (rc=$rc)"; exit "$rc"; }

# Negotiation fan-in lane (docs/data_plane.md "Negotiation fan-in"): a
# bounded np=1024 sim — the REAL coordinator mask path behind a scripted
# mesh, star vs tree over the arithmetic wire clock — must show the
# O(ranks)->O(hosts) ingress drop counter-asserted, bit-identical agreed
# masks, and >= 0.90 critical-path coverage, with the lock-dependency
# tracker armed and ZERO inversion cycles.  The np=4096 curve artifact
# regenerates in the slow-marked test below.
echo "negotiation lane: np=1024 sim fan-in under HOROVOD_LOCK_DEBUG=1"
rc=0
JAX_PLATFORMS=cpu HOROVOD_LOCK_DEBUG=1 HOROVOD_SIM_SEED=0 \
python - > ci/chaos.negotiation.log 2>&1 <<'EOF' || rc=$?
from horovod_tpu.common import lockdep
from horovod_tpu.sim.negotiation import SimNegotiation

rec = SimNegotiation(1024, slots_per_host=8, seed=0).run(cycles=4)
assert rec["star"]["ingress_frames_per_cycle"] == 1023, rec
assert rec["fanin"]["ingress_frames_per_cycle"] == 127 + 7, rec
assert rec["star"]["reply_mask"] == rec["fanin"]["reply_mask"] != 0, rec
for mode in ("star", "fanin"):
    assert rec["attribution"][mode]["coverage"] >= 0.90, rec["attribution"]
cycles = lockdep.find_cycles()
assert not cycles, f"lock inversion cycles: {cycles}"
print(f"np=1024 negotiation: ingress {rec['star']['ingress_frames_per_cycle']}"
      f" -> {rec['fanin']['ingress_frames_per_cycle']} frames/cycle, "
      f"cycle speedup {rec['cycle_speedup_p50']}x, "
      f"coverage {rec['attribution']['fanin']['coverage']:.2%}, 0 lock cycles")
EOF
cat ci/chaos.negotiation.log
[ "$rc" -eq 0 ] || { echo "negotiation lane FAILED (rc=$rc)"; exit "$rc"; }

# The np=4096 committed-artifact proof (star-vs-tree latency curves,
# benchmarks/results/sim_negotiation_np4096.json): slow-marked, so
# tier-1 never pays for it; this lane regrows and re-verifies it.
echo "negotiation artifact lane: np=4096 curve regeneration"
rc=0
JAX_PLATFORMS=cpu HOROVOD_LOCK_DEBUG=1 \
python -m pytest "tests/test_sim_cluster.py::test_sim_negotiation_np4096_artifact" \
    -m slow -v -p no:cacheprovider > ci/chaos.negotiation_artifact.log 2>&1 || rc=$?
cat ci/chaos.negotiation_artifact.log
[ "$rc" -eq 0 ] || { echo "negotiation artifact lane FAILED (rc=$rc)"; exit "$rc"; }

# Self-healing demotion lane (docs/elastic.md "Self-healing demotion").
# The live np=3 chronic-straggler scenario (host shed, cause=demotion,
# bit-identical convergence, HOROVOD_LOCK_DEBUG=1 below) already ran in
# the pytest chaos lane above via the module's chaos mark; this lane adds
# the np=128 scale proof — the artifact-generating slow test drives 3
# demotion reports through the real driver over the shaped wire, regrows
# benchmarks/results/sim_demotion_np128.json, and asserts the committed
# artifact's digest reproduces from a fresh same-seed cluster (the
# non-fabrication witness), with zero lock-inversion cycles.
echo "demotion lane: np=128 simulated demotions under HOROVOD_LOCK_DEBUG=1"
rc=0
JAX_PLATFORMS=cpu HOROVOD_LOCK_DEBUG=1 \
python -m pytest "tests/test_sim_cluster.py::test_sim_demotion_np128_artifact" \
    -m slow -v -p no:cacheprovider > ci/chaos.demotion.log 2>&1 || rc=$?
cat ci/chaos.demotion.log
[ "$rc" -eq 0 ] || { echo "demotion lane FAILED (rc=$rc)"; exit "$rc"; }

# Zero-restart reshard lane (docs/elastic.md "Live resharding"): the
# np=8 live proof — a rank SIGKILL'd mid-train, the reshard-marked
# publish, the survivor-acked commit, exactly one post-churn spawn (the
# victim's identity back as a joiner), bit-identical convergence — plus
# the HOROVOD_RESHARD=0 kill-switch variant converging through the
# legacy fallback.  Both under HOROVOD_LOCK_DEBUG=1 (the jobs arm it in
# their own env too; this instruments the test process as well).
echo "reshard lane: np=8 live churn under HOROVOD_LOCK_DEBUG=1"
rc=0
JAX_PLATFORMS=cpu HOROVOD_LOCK_DEBUG=1 \
python -m pytest tests/test_fault_injection_elastic.py -m "chaos and slow" \
    -k "live_reshard" -v -p no:cacheprovider \
    > ci/chaos.reshard.log 2>&1 || rc=$?
cat ci/chaos.reshard.log
[ "$rc" -eq 0 ] || { echo "reshard lane FAILED (rc=$rc)"; exit "$rc"; }
echo "chaos lane PASSED"
