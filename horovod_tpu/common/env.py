"""Environment-variable knobs — the single source of config truth.

The reference centralizes all runtime knobs as ``HOROVOD_*`` environment
variables (``horovod/common/common.h:64-91``, parsed in ``env_parser.cc`` and
``operations.cc:404-540``); the launcher converts CLI flags into these
variables (``runner/common/util/config_parser.py``).  We keep the same model
and, where a knob has a direct equivalent, the same name, so that operational
knowledge transfers.
"""

from __future__ import annotations

import os

# -- topology (set by the launcher / rendezvous; reference gloo_run.py:65-76) --
HOROVOD_RANK = "HOROVOD_RANK"
HOROVOD_SIZE = "HOROVOD_SIZE"
HOROVOD_LOCAL_RANK = "HOROVOD_LOCAL_RANK"
HOROVOD_LOCAL_SIZE = "HOROVOD_LOCAL_SIZE"
HOROVOD_CROSS_RANK = "HOROVOD_CROSS_RANK"
HOROVOD_CROSS_SIZE = "HOROVOD_CROSS_SIZE"
HOROVOD_HOSTNAME = "HOROVOD_HOSTNAME"

# -- rendezvous / control plane --
HOROVOD_RENDEZVOUS_ADDR = "HOROVOD_GLOO_RENDEZVOUS_ADDR"
HOROVOD_RENDEZVOUS_PORT = "HOROVOD_GLOO_RENDEZVOUS_PORT"
# Full-mesh TCP bring-up budget (rendezvous wait + accept + dial), secs.
# Loaded CI hosts starting N jax runtimes concurrently need more than the
# 60 s default; the test harness load-scales it.
HOROVOD_MESH_STARTUP_TIMEOUT = "HOROVOD_MESH_STARTUP_TIMEOUT"
HOROVOD_SECRET_KEY = "HOROVOD_SECRET_KEY"
HOROVOD_ELASTIC = "HOROVOD_ELASTIC"
# Negotiation fan-out: "auto" | "star" | "tree" (core/controller.py picks
# tree at the measured world-size crossover when auto).
HOROVOD_CONTROLLER_TOPOLOGY = "HOROVOD_CONTROLLER_TOPOLOGY"
# -- control-plane survivability (docs/control_plane.md) --
# Directory for the rendezvous store's write-ahead journal + compacted
# snapshots; empty/unset = no journal (plain in-memory store).  A server
# restarted over the same directory replays to its pre-crash KV state.
HOROVOD_RENDEZVOUS_JOURNAL_DIR = "HOROVOD_RENDEZVOUS_JOURNAL_DIR"
# fsync each journal append ("1"/"0", default on): off trades the last
# few acknowledged ops on power loss for lower PUT latency; a plain
# process SIGKILL loses nothing either way (the page cache survives).
HOROVOD_RENDEZVOUS_JOURNAL_FSYNC = "HOROVOD_RENDEZVOUS_JOURNAL_FSYNC"
# Ops between snapshot compactions (bounds journal replay length).
HOROVOD_RENDEZVOUS_SNAPSHOT_EVERY = "HOROVOD_RENDEZVOUS_SNAPSHOT_EVERY"
# "host:port" of an externally-supervised rendezvous server (run
# ``python -m horovod_tpu.runner.rendezvous``); when set, the elastic
# launcher drives that server over HTTP instead of starting its own —
# the deployment shape where a SIGKILL'd server restarts under its
# supervisor and the job rides through.  Both sides must share
# HOROVOD_SECRET_KEY.
HOROVOD_RENDEZVOUS_EXTERNAL = "HOROVOD_RENDEZVOUS_EXTERNAL"
# Seconds without a lease renewal (with the store REACHABLE) before the
# elastic driver declares a worker dead and advances the epoch; store
# outages pause the clock — partitioned/restarting is not dead.
HOROVOD_LEASE_TIMEOUT_SECS = "HOROVOD_LEASE_TIMEOUT_SECS"
# -- scale-out control plane (docs/control_plane.md "Batched
#    transactions") --
# Batched rendezvous transactions ("1"/"0", default on): clients coalesce
# a tick's PUT/GET/DELETE/KEYS ops into one signed POST /batch the server
# applies under ONE store-lock acquisition and journals as ONE atomic
# record group.  The client degrades to per-op requests against a server
# that 404s the endpoint, so mixed-version jobs stay correct (just slow).
HOROVOD_RENDEZVOUS_BATCH = "HOROVOD_RENDEZVOUS_BATCH"
# Max ops per batch request; larger batches are split client-side.  Caps
# the store-lock hold time and the atomic journal frame size — one giant
# batch would serialize every other rendezvous request behind it.
HOROVOD_RENDEZVOUS_BATCH_MAX_OPS = "HOROVOD_RENDEZVOUS_BATCH_MAX_OPS"
# Host-level fan-in ("1"/"0"/"auto", default auto = on when local_size >
# 1 and batching is on): colocated ranks spool their lease renewals +
# metrics snapshots to the host's aggregator (lowest local rank), which
# merges them into one batch — control traffic scales with hosts, not
# ranks.  Ranks fall back to direct per-rank pushes whenever the
# aggregator's heartbeat goes stale (elastic/fanin.py).
HOROVOD_FANIN = "HOROVOD_FANIN"
# Base directory for the fan-in spool (per-host, must be shared by the
# host's ranks and is probed writable); default /dev/shm.
HOROVOD_FANIN_DIR = "HOROVOD_FANIN_DIR"
# -- negotiation fan-in (data plane; docs/data_plane.md "Negotiation
#    fan-in") --
# Tree-structured negotiation fan-in ("1"/"0"/"auto", default auto = on
# when the layout is blocked-homogeneous with >= 2 ranks/host on >= 2
# hosts): each host's local_rank-0 rank ANDs its host's mask frames into
# ONE HostMaskFrame forwarded to the coordinator, so coordinator ingress
# per busy cycle scales with HOSTS, not ranks.  "1" forces it on (a
# non-blocked rank layout is then a loud config error); supersedes
# HOROVOD_CONTROLLER_TOPOLOGY while active.
HOROVOD_NEGOTIATION_FANIN = "HOROVOD_NEGOTIATION_FANIN"
# Negotiation-aggregator heartbeat period (seconds).  The aggregator
# touches its heartbeat file once per period while cycles complete;
# members convict a WEDGED (alive-but-stuck) aggregator when the file
# goes ~1.5 periods stale (elastic/fanin.py's staleness constant) and
# raise AggregatorStaleError — coordinated abort + veto, so the next
# epoch runs the host direct.  Aggregator DEATH needs no heartbeat: the
# member's blocking recv raises PeerGoneError promptly.
HOROVOD_NEGOTIATION_FANIN_HEARTBEAT_SECS = \
    "HOROVOD_NEGOTIATION_FANIN_HEARTBEAT_SECS"
# Epochs a stale-aggregator veto keeps its host on the direct path
# before the host may re-tree (conviction hysteresis; >= 1).
HOROVOD_NEGOTIATION_FANIN_VETO_EPOCHS = \
    "HOROVOD_NEGOTIATION_FANIN_VETO_EPOCHS"
# Base directory for the per-host negotiation heartbeat file (must be
# shared by the host's ranks); default: the system temp dir.
HOROVOD_NEGOTIATION_FANIN_DIR = "HOROVOD_NEGOTIATION_FANIN_DIR"
# -- simulated-cluster harness (horovod_tpu/sim/; docs/sim_cluster.md) --
# Shaped-wire injection for sim runs: deterministic per-link base latency
# (ms), uniform jitter bound (ms), and bandwidth (MB/s) applied around
# every rendezvous client round-trip.  Latency/jitter/bandwidth model the
# wire the 1-box harness doesn't have; 0 latency + 0 jitter + 0 bandwidth
# disables shaping.
HOROVOD_SIM_LATENCY_MS = "HOROVOD_SIM_LATENCY_MS"
HOROVOD_SIM_JITTER_MS = "HOROVOD_SIM_JITTER_MS"
HOROVOD_SIM_BANDWIDTH_MBS = "HOROVOD_SIM_BANDWIDTH_MBS"
# Seed for the per-link shaping RNGs: the same seed reproduces the same
# per-link delay parameters and jitter sequence, so sim artifacts are
# deterministic in everything but raw wall-clock.
HOROVOD_SIM_SEED = "HOROVOD_SIM_SEED"

# -- elastic membership --
# Monotonic membership epoch, stamped by the elastic driver into every
# worker env and bumped on each re-rendezvous; read via ``get_epoch()``.
HOROVOD_EPOCH = "HOROVOD_EPOCH"
# Zero-restart resharding ("1"/"0", default on): on an epoch advance with
# ≥1 surviving worker the driver stamps the published slot table with a
# reshard marker; survivors abort in-flight collectives and re-rendezvous
# IN PLACE (no process exit/respawn) and joiners receive state over the
# collectives instead of a checkpoint read (docs/elastic.md "Live
# resharding").  "0" is the kill-switch back to the legacy full-teardown
# path; a survivor crash mid-reshard degrades to that path automatically.
HOROVOD_RESHARD = "HOROVOD_RESHARD"
HOROVOD_ELASTIC_RESET_LIMIT = "HOROVOD_ELASTIC_RESET_LIMIT"
# Blacklist strike thresholds (elastic/constants.py holds the defaults):
# crash exits use the low limit, TRANSIENT_EXIT_CODE exits the high one.
HOROVOD_ELASTIC_CRASH_FAILURE_LIMIT = "HOROVOD_ELASTIC_CRASH_FAILURE_LIMIT"
HOROVOD_ELASTIC_TRANSIENT_FAILURE_LIMIT = \
    "HOROVOD_ELASTIC_TRANSIENT_FAILURE_LIMIT"
# Override for the per-host GCE metadata relay URL template ({host}
# placeholder required; elastic/tpu_metadata.py).
HOROVOD_TPU_METADATA_URL = "HOROVOD_TPU_METADATA_URL"
# -- failure plane --
# Bounded-deadline transport: a mesh recv that makes no byte progress for
# this many seconds marks the peer dead and raises PeerGoneError (0 =
# disabled, block forever like pre-hardening).  Arms only after a peer's
# FIRST bytes — bring-up staggering (slow XLA init on one host) is the
# startup timeout's jurisdiction.  Generous default: cycles are continuous
# even when idle, so legitimate inter-frame gaps are small, but a host
# swapping hard can stall minutes.
HOROVOD_TCP_PROGRESS_DEADLINE = "HOROVOD_TCP_PROGRESS_DEADLINE_SECS"
# Deterministic fault injection spec (common/faults.py); unset = no-op.
HOROVOD_FAULT_SPEC = "HOROVOD_FAULT_SPEC"
# -- integrity plane --
# Wire CRC ("1"/"0", default on): every mesh frame (control frames
# included) carries crc32(payload) in the header; a recv-side mismatch is
# a FrameCorruptError + coordinated abort (docs/integrity.md).  All ranks
# must agree — the launcher env propagates it like every other knob.
HOROVOD_WIRE_CRC = "HOROVOD_WIRE_CRC"
# Shadow (deferred) digesting for ring data frames ("1"/"0", default on,
# effective only with HOROVOD_WIRE_CRC on): segment frames inside a ring
# step carry NO inline CRC field — each endpoint chains per-segment
# digests off the serial path and a small inline-CRC'd digest-check frame
# closes the step (transport/tcp.py; docs/integrity.md).  "0" restores
# the strict per-frame inline CRC.  All ranks must agree.
HOROVOD_WIRE_CRC_SHADOW = "HOROVOD_WIRE_CRC_SHADOW"
# Digest algorithm for the deferred (shadow) path: "fold64" (default —
# vectorized 64-bit sum/xor fold, ~10x faster than crc32 on the CI box)
# or "crc32" (chained zlib.crc32: the step chain equals the crc32 of the
# concatenated payload stream).  Control frames and non-ring frames keep
# inline crc32 regardless.  All ranks must agree.
HOROVOD_WIRE_DIGEST = "HOROVOD_WIRE_DIGEST"
# -- bandwidth plane (docs/data_plane.md) --
# Wire gradient compression for the host-ring allreduce: "none"
# (default) | "fp16" | "bf16" (lossless-ish casts) | "int8" | "onebit" |
# "topk<K>" (lossy codecs with error feedback; K is the kept density in
# percent, e.g. "topk10").  f32/f64 payloads are compressed per segment
# into a keyed staging arena at send and restored/reduced in wide
# precision on land (backend/compression.py); other dtypes pass through
# uncompressed.  Frame headers carry the wire dtype code, so ranks that
# disagree on this knob fail loudly (poisoned stream), not silently.
HOROVOD_WIRE_COMPRESSION = "HOROVOD_WIRE_COMPRESSION"
# Error feedback for the LOSSY codecs (int8/onebit/topk), default on:
# each rank keeps a per-(tensor set, segment) residual accumulator and
# folds the quantization error of step t back into the segment before
# encoding at step t+1 — the 1-bit-SGD convergence fix.  "0" disables it
# (the convergence test's control arm; measurably worse, never faster).
# No wire format change either way, so ranks may disagree harmlessly —
# but don't: the convergence guarantee is per-rank.
HOROVOD_WIRE_EF = "HOROVOD_WIRE_EF"
# Coordinator fusion-bucket ordering: "readiness" (default — tensors are
# packed in the order their negotiations were FIRST announced, so early
# gradients fly while late layers still compute) or "arrival" (the
# legacy completion order).  Applies to the full-ResponseList path only;
# the mask fast path keeps its deterministic ascending-bit order.
HOROVOD_FUSION_ORDER = "HOROVOD_FUSION_ORDER"
# Elastic blacklist cooldown: a blacklisted host rejoins the candidate
# pool after this many seconds (0 = permanent, the reference behavior).
HOROVOD_BLACKLIST_COOLDOWN_SECS = "HOROVOD_BLACKLIST_COOLDOWN_SECS"
# -- host data plane --
# Per-link transport selection (transport/select.py; docs/data_plane.md):
# "auto" (default — shared-memory rings for intra-host links, TCP for
# cross-host), "tcp" (everything over the TCP mesh, the pre-PR-11
# behavior), or "shm" (force shm on every link; a cross-host link under
# "shm" is a loud config error, not a silent TCP fallback).  All ranks
# must agree (launcher-propagated like every knob).
HOROVOD_TRANSPORT = "HOROVOD_TRANSPORT"
# Per-frame CRC32 on the shared-memory transport ("1"/"0", default OFF —
# the bytes never hit a wire, and host RAM is already ECC's jurisdiction;
# turn on to debug a suspected stomper or to run the corruption chaos
# tests against the shm path).  When on, the shadow-digest machinery
# (HOROVOD_WIRE_CRC_SHADOW / HOROVOD_WIRE_DIGEST) applies exactly as on
# TCP.  Both endpoints of a pair must agree.
HOROVOD_SHM_CRC = "HOROVOD_SHM_CRC"
# Per-direction byte capacity of each shm pair segment's ring
# (transport/shm.py).  Frames larger than this stream through in chunks,
# so it bounds memory, not frame size; one segment costs
# 2*ring_bytes + header per intra-host pair in /dev/shm.
HOROVOD_SHM_RING_BYTES = "HOROVOD_SHM_RING_BYTES"
# Override for this rank's host-identity string (default: a physical-
# machine probe — boot id + /dev/shm device — combined with the
# topology's cross_rank, so simulated multi-host tests on one box
# classify links exactly like real multi-host jobs).  Two ranks get an
# shm link iff their identity strings are equal.
HOROVOD_SHM_HOSTID = "HOROVOD_SHM_HOSTID"
# Ring-collective pipeline granularity (bytes): each ring step streams its
# chunk as segments of this size so segment k reduces in numpy while
# segment k+1 is on the wire (backend/cpu_ring.py; docs/data_plane.md).
# Clamped to at least one element; values >= the chunk size degrade to the
# unpipelined single-frame step.  All ranks must agree (launcher-propagated
# like every knob — peers derive identical segment boundaries from it).
HOROVOD_RING_SEGMENT_BYTES = "HOROVOD_RING_SEGMENT_BYTES"
# Lockdep-style runtime lock-order validator (common/lockdep.py): when
# truthy, Lock/RLock created inside this package are instrumented and an
# exit-time report names lock-order inversion cycles and blocking waits
# performed while holding another lock.  Diagnostics only — never on in
# production paths by default.
HOROVOD_LOCK_DEBUG = "HOROVOD_LOCK_DEBUG"
# Acquire waits longer than this (seconds) while holding another lock are
# recorded as held-lock blocking waits in the lockdep report.
HOROVOD_LOCK_DEBUG_SLOW_SECS = "HOROVOD_LOCK_DEBUG_SLOW_SECS"
# -- observability plane (docs/observability.md) --
# Metrics registry master switch ("1"/"0", default on): counters, gauges
# and latency histograms in core/metrics.py.  Always-on by design (like
# wire_stats); "0" turns every recording call into one attribute read —
# benchmarks/allreduce_bench.py --metrics-sweep is the overhead guard.
HOROVOD_METRICS = "HOROVOD_METRICS"
# Period (seconds) between a worker's metrics-snapshot pushes to the
# rendezvous KV (PUT /metrics/rank-N, served back aggregated by the
# server's GET /metrics).  0 disables pushing; recording still happens.
HOROVOD_METRICS_PUSH_SECS = "HOROVOD_METRICS_PUSH_SECS"
# Flight recorder ("1"/"0", default on): bounded in-memory ring of recent
# events (frames, cycles, faults, epoch changes) dumped as a per-rank
# post-mortem JSON when the background loop dies (coordinated abort,
# frame corruption, any fatal error).
HOROVOD_FLIGHT_RECORDER = "HOROVOD_FLIGHT_RECORDER"
# Base directory the post-mortem dumps land in; dumps go into an
# hvd_flight_recorder/ subdirectory of it (created on demand) so they
# never litter the job's cwd.  Default base: the worker's cwd; file name
# hvd_flight_recorder/hvd_flight_recorder.rank<N>.json.
HOROVOD_FLIGHT_RECORDER_DIR = "HOROVOD_FLIGHT_RECORDER_DIR"
# Ring capacity (events retained; oldest evicted first).
HOROVOD_FLIGHT_RECORDER_EVENTS = "HOROVOD_FLIGHT_RECORDER_EVENTS"
# Straggler detector (coordinator-side, docs/observability.md): a rank
# whose readiness-lag EWMA — how long it keeps tensors waiting after the
# median announcer is ready — exceeds this many seconds is flagged as a
# straggler suspect (metrics + flight-recorder event + log line naming
# the rank).  0 disables flagging; lag EWMAs still update.
HOROVOD_STRAGGLER_THRESHOLD_SECS = "HOROVOD_STRAGGLER_THRESHOLD_SECS"
# EWMA smoothing factor in (0, 1] for the per-rank readiness lag: higher
# reacts faster, lower rides out one-cycle noise.
HOROVOD_STRAGGLER_EWMA_ALPHA = "HOROVOD_STRAGGLER_EWMA_ALPHA"
# Chronic-straggler demotion (docs/elastic.md "self-healing demotion"):
# a rank whose lag EWMA stays above this many seconds for
# HOROVOD_STRAGGLER_DEMOTE_CYCLES consecutive busy cycles is reported to
# the elastic driver, which blacklists its host and advances the epoch.
# 0 (the default) disables demotion entirely — flagging alone never
# sheds capacity.
HOROVOD_STRAGGLER_DEMOTE_SECS = "HOROVOD_STRAGGLER_DEMOTE_SECS"
# Consecutive busy cycles the EWMA must stay over the demote threshold
# before the verdict fires (the hysteresis window; >= 1).
HOROVOD_STRAGGLER_DEMOTE_CYCLES = "HOROVOD_STRAGGLER_DEMOTE_CYCLES"
# Per-tensor lifecycle spans in the timeline ("1"/"0", default on):
# submitted → negotiated → fused → wire → reduced → callback spans on
# every rank.  Only consulted when a timeline is active; costs one
# module-attribute read otherwise.
HOROVOD_TIMELINE_LIFECYCLE = "HOROVOD_TIMELINE_LIFECYCLE"
# Path of the rendezvous server's own timeline trace file.  The server is
# the clock base every worker syncs against (tools/trace_merge.py), so its
# spans merge with worker traces unshifted.  Empty/unset: no server trace.
HOROVOD_SERVER_TIMELINE = "HOROVOD_SERVER_TIMELINE"
# Control-plane spans ("1"/"0", default on): rendezvous request spans on
# the server trace, store-client round-trip spans and driver churn spans
# on whichever timeline is active.  Only consulted when a timeline
# exists; costs one module-attribute read otherwise.
HOROVOD_TIMELINE_CONTROL_PLANE = "HOROVOD_TIMELINE_CONTROL_PLANE"

# -- core runtime tunables (reference common.h:64-91) --
HOROVOD_FUSION_THRESHOLD = "HOROVOD_FUSION_THRESHOLD"  # bytes, default 64MB
# float ms, default 5.0 as in the reference: the longest the background
# loop parks after an idle round (it parks IDLE_PARK_FLOOR_MS after the first
# and doubles from there; an enqueue ends any park at once).  Under the
# floor it is both floor and cap.
HOROVOD_CYCLE_TIME = "HOROVOD_CYCLE_TIME"
HOROVOD_CACHE_CAPACITY = "HOROVOD_CACHE_CAPACITY"
HOROVOD_STALL_CHECK_DISABLE = "HOROVOD_STALL_CHECK_DISABLE"
HOROVOD_STALL_CHECK_TIME_SECONDS = "HOROVOD_STALL_CHECK_TIME_SECONDS"
HOROVOD_STALL_SHUTDOWN_TIME_SECONDS = "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"
HOROVOD_TIMELINE = "HOROVOD_TIMELINE"
# Double-buffer the background loop: cycle i+1's negotiation overlaps cycle
# i's device-collective dispatch on a dedicated thread (size > 1 only;
# host-TCP responses still execute inline behind a drain barrier).
HOROVOD_PIPELINE_DISPATCH = "HOROVOD_PIPELINE_DISPATCH"
HOROVOD_TIMELINE_MARK_CYCLES = "HOROVOD_TIMELINE_MARK_CYCLES"
HOROVOD_AUTOTUNE = "HOROVOD_AUTOTUNE"
HOROVOD_AUTOTUNE_LOG = "HOROVOD_AUTOTUNE_LOG"
HOROVOD_AUTOTUNE_WARMUP_SAMPLES = "HOROVOD_AUTOTUNE_WARMUP_SAMPLES"
HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE = "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"
# Fold the wire-compression codec ({none, fp16, bf16, int8, onebit})
# into the autotuner's search space as a categorical dimension ("1"/"0",
# default off): codec verdicts are gated by the A/B sign test
# (benchmarks/ab_harness.py idiom) before a switch is recommended, and
# the tuned codec is only ever REPORTED (autotune log) — the live wire
# format still follows HOROVOD_WIRE_COMPRESSION, which all ranks must
# agree on.
HOROVOD_AUTOTUNE_CODEC = "HOROVOD_AUTOTUNE_CODEC"
HOROVOD_LOG_LEVEL = "HOROVOD_LOG_LEVEL"
HOROVOD_LOG_HIDE_TIMESTAMP = "HOROVOD_LOG_HIDE_TIMESTAMP"
# Force the hierarchical (intra-host ring + parallel cross-host rings)
# allreduce off/on ("0"/"1"; reference common.h:79).  Structural
# requirements still gate a forced "1" (backend/cpu_ring.py).
HOROVOD_HIERARCHICAL_ALLREDUCE = "HOROVOD_HIERARCHICAL_ALLREDUCE"
# Finalizer pool width (NUM_NCCL_STREAMS analog): concurrent in-flight
# fused-batch completions (core/state.py).
HOROVOD_NUM_FINALIZER_THREADS = "HOROVOD_NUM_FINALIZER_THREADS"
# Truthy: never build/load the optional native kernel library
# (_native/__init__.py).
HOROVOD_DISABLE_NATIVE = "HOROVOD_DISABLE_NATIVE"
# Row cap for the store-less (driver-collect) Spark fit path; 0 disables.
HOROVOD_SPARK_INLINE_MAX_ROWS = "HOROVOD_SPARK_INLINE_MAX_ROWS"

# -- TPU-specific (no reference equivalent: XLA data-plane knobs) --
HOROVOD_TPU_MESH_AXES = "HOROVOD_TPU_MESH_AXES"  # e.g. "dp:8" or "dp:4,tp:2"
HOROVOD_DATA_PLANE = "HOROVOD_DATA_PLANE"  # "xla" | "tcp" | "auto"
# "host:port" of the jax.distributed coordination service (rank 0's
# process); set by the launcher when the XLA data plane is requested.
HOROVOD_JAX_COORDINATOR = "HOROVOD_JAX_COORDINATOR"

DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024
# The longest an idle background loop parks between rounds (core/state.py
# ``_background_loop``): the reference's cycle, 5 ms (operations.cc:458).
# Every round is a lockstep exchange between Python processes, and at a
# round a millisecond sixty of them a ResNet-50 step took the interpreter
# from the coordinator's calling thread (PERF.md §6, PRs 52 and 54).
# Small-tensor latency keeps its pace through the floor: the first idle
# round after a round with work parks IDLE_PARK_FLOOR_MS, every further one
# twice the last up to this cap, and a round with a request or a response
# starts over.  HOROVOD_CYCLE_TIME moves the cap alone.
DEFAULT_CYCLE_TIME_MS = 5.0
IDLE_PARK_FLOOR_MS = 1.0
DEFAULT_CACHE_CAPACITY = 1024
DEFAULT_STALL_CHECK_TIME_SECONDS = 60
DEFAULT_STALL_SHUTDOWN_TIME_SECONDS = 0  # disabled
DEFAULT_TCP_PROGRESS_DEADLINE_SECS = 600.0
# 1 MiB: small enough that the numpy add of segment k genuinely overlaps
# segment k+1's wire time on MB-scale chunks, large enough that the
# per-segment cost (framing + helper-thread hop + context switch) stays
# noise.  Measured on the 1-core CI box (where overlap CANNOT pay — the
# "wire" is loopback CPU, so segmentation is pure overhead there): 4 MB
# np=2 medians 24.3 ms @ 1 MiB vs 28.9 @ 256 KiB vs 35.0 @ 64 KiB vs
# 24.4 unpipelined — 1 MiB is at parity with unpipelined even with no
# core to overlap on; see benchmarks/results/ring_segment_sweep.json.
DEFAULT_RING_SEGMENT_BYTES = 1024 * 1024
# 4 MiB per direction: holds a whole default-sized ring segment pipeline
# (4 segments of HOROVOD_RING_SEGMENT_BYTES) without backpressure, while
# an np=8 single-host job's 28 pairs still cost < 256 MiB of /dev/shm.
DEFAULT_SHM_RING_BYTES = 4 * 1024 * 1024
DEFAULT_SPARK_INLINE_MAX_ROWS = 100_000
DEFAULT_LOCK_DEBUG_SLOW_SECS = 1.0
# 5 s: fast enough that a scrape of a live job is near-current, slow
# enough that N ranks' pushes are noise to the rendezvous server (one
# small PUT per rank per period).
DEFAULT_METRICS_PUSH_SECS = 5.0
# 512 events ≈ the last few busy cycles' frames plus every rare event
# (faults, epoch changes, aborts) — sized so idle control-frame chatter
# cannot evict a whole incident's history.
DEFAULT_FLIGHT_RECORDER_EVENTS = 512
# 5 s: far above any healthy cycle's skew on a loaded CI box (negotiation
# cycles are ~ms), far below the 60 s stall warning — the detector names
# the lagging rank while the job is still making (slow) progress.
DEFAULT_STRAGGLER_THRESHOLD_SECS = 5.0
# 0.25: a sustained lag reaches ~90% of its value within 8 lagging
# cycles, while a single slow cycle decays below threshold immediately.
DEFAULT_STRAGGLER_EWMA_ALPHA = 0.25
# Demotion is opt-in: shedding capacity on a heuristic is a policy
# decision the operator must make explicitly, so the default threshold
# disables it (flagging/metrics still run).
DEFAULT_STRAGGLER_DEMOTE_SECS = 0.0
# 10 consecutive over-threshold busy cycles: with the default alpha a
# one-shot delay decays under threshold within a cycle or two, so only a
# persistently slow rank can hold a 10-cycle streak.
DEFAULT_STRAGGLER_DEMOTE_CYCLES = 10
# 512 ops between compactions: elastic churn writes ~2N keys per epoch,
# so replay stays bounded at a few epochs' worth of ops even at np=64
# while steady-state lease renewals don't compact every few seconds.
DEFAULT_RENDEZVOUS_SNAPSHOT_EVERY = 512
# 3× the default metrics-push period: one missed renewal is load noise,
# three in a row with a reachable store means the pusher thread (and so
# almost certainly the worker) is gone.
DEFAULT_LEASE_TIMEOUT_SECS = 15.0
# 512 ops per batch: an np=512 slot-table republish fits in one or two
# frames while the store-lock hold per batch stays sub-ms (ops are small
# JSON values); matches the snapshot cadence so one batch can't skip a
# compaction check by more than one interval.
DEFAULT_RENDEZVOUS_BATCH_MAX_OPS = 512
# Shaping defaults model a quiet intra-DC hop: 0.2 ms base one-way-ish
# latency + up to 0.05 ms jitter per round-trip, 1 GB/s of bandwidth —
# enough to make per-op vs batched round-trip counts visible without
# making np=512 sim runs take minutes.
DEFAULT_SIM_LATENCY_MS = 0.2
DEFAULT_SIM_JITTER_MS = 0.05
DEFAULT_SIM_BANDWIDTH_MBS = 1000.0
# 1 s heartbeat: conviction of a wedged negotiation aggregator lands in
# ~1.5 s — far under the stall-warning plane (60 s) that otherwise owns
# stuck negotiations, while the once-per-period utime stays noise next
# to ~1 ms negotiation cycles.
DEFAULT_NEGOTIATION_FANIN_HEARTBEAT_SECS = 1.0
# 2 epochs of direct traffic after a stale-aggregator conviction: one
# epoch would re-tree immediately after the very reshard the conviction
# caused; two keeps a flapping host from oscillating tree/direct every
# recovery.
DEFAULT_NEGOTIATION_FANIN_VETO_EPOCHS = 2


def get_int(name: str, default: int) -> int:
    val = os.environ.get(name)
    if val is None or val == "":
        return default
    return int(val)


def get_float(name: str, default: float) -> float:
    val = os.environ.get(name)
    if val is None or val == "":
        return default
    return float(val)


def get_bool(name: str, default: bool = False) -> bool:
    val = os.environ.get(name)
    if val is None or val == "":
        return default
    return val.lower() not in ("0", "false", "no", "off", "")


def get_str(name: str, default: str = "") -> str:
    return os.environ.get(name, default)


def get_epoch() -> int:
    """Current elastic membership epoch (0 outside elastic jobs).

    Every consumer of ``HOROVOD_EPOCH`` goes through here so the default
    lives in exactly one place."""
    return get_int(HOROVOD_EPOCH, 0)
