"""Process-global metrics registry — the cluster observability plane's core.

Role of the reference's timeline/stall-inspector telemetry plus what it
never had: a scrapeable, cross-rank metrics surface.  Three instrument
kinds, Prometheus-shaped:

- **Counter**: monotonically increasing float (``inc``); merged across
  ranks by summation at scrape time.
- **Gauge**: last-written value (``set_gauge``); labeled by rank at
  scrape time (a queue depth summed across ranks would be a lie).
- **Histogram**: fixed log2 buckets (powers of two from ~1 µs to 64 s,
  ``observe``); per-bucket counts merge across ranks by summation, so a
  cluster-wide latency distribution is exact, not approximated.

Labels ride as keyword arguments (``observe("collective_latency_seconds",
dt, op="ALLREDUCE", dtype="FLOAT32", size="2^22")``) and are flattened
into the Prometheus ``name{k="v"}`` form for storage and merging.

``phase_stats`` and ``wire_stats`` (core/timeline.py) predate this
registry and stay the hot-path accumulators; they are absorbed as
**registered views** — callables folded into every :func:`snapshot`, so
one scrape carries the whole process's story.  The controller's
fast-cycle counters join the same way (core/state.py registers the view).

Every metric name must be declared in :data:`CATALOG` — lint rule HVD007
(mirror of HVD003's fault-site registry) rejects an ``inc``/``observe``/
``set_gauge``/stats-``add`` call whose literal name is not cataloged, and
requires every catalog entry to appear in ``docs/observability.md``.  A
typo'd metric name must not silently record nothing.

Always-on by default like ``wire_stats`` (one small lock + dict update
per event); ``HOROVOD_METRICS=0`` turns every recording call into one
attribute read (the ``faults.ACTIVE`` pattern), and
``benchmarks/allreduce_bench.py --metrics-sweep`` is the overhead guard.
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..common import env as env_mod

#: Rendezvous-KV scope the workers push snapshots into (``PUT
#: /metrics/rank-N``) and the server's ``GET /metrics`` aggregates from.
#: Re-exported from the scope registry (transport/scopes.py, HVD010) at
#: the BOTTOM of this module: importing the transport package pulls in
#: core/timeline, which needs ``metrics.registry`` to exist already.

#: Prefix stamped onto every rendered Prometheus series.
PROM_PREFIX = "hvd_"

#: Fixed log2 histogram bucket upper bounds: 2^-20 s (~1 µs) .. 2^6 s
#: (64 s), plus an implicit +Inf overflow bucket.  Fixed (not
#: configurable) so per-rank bucket arrays always merge element-wise.
BUCKET_BOUNDS: Tuple[float, ...] = tuple(2.0 ** k for k in range(-20, 7))

#: The metric catalog: every observable name, its kind, and its meaning.
#: ``counter``/``gauge``/``histogram`` entries are recorded through this
#: module; ``stat`` entries are the raw names fed to the pre-existing
#: ``phase_stats``/``wire_stats`` accumulators (their registered views
#: surface them here as the ``phase_*``/``wire_*`` counters).  This dict
#: is the HVD007-enforced registry: call sites may only use names listed
#: here, and every name here must appear in ``docs/observability.md``.
CATALOG: Dict[str, Tuple[str, str]] = {
    # -- controller / negotiation plane --
    "controller_cycles_total": (
        "counter", "negotiation cycles completed (busy and idle)"),
    "controller_fast_cycles_total": (
        "counter", "zero-payload mask-only cycles that completed work"),
    "controller_idle_fast_cycles_total": (
        "counter", "zero-payload mask-only cycles with nothing to do"),
    "controller_serialized_requests_total": (
        "counter", "Requests this rank put on / took off the wire"),
    "controller_fast_cycle_ratio": (
        "gauge", "(fast + idle-fast cycles) / all cycles since init"),
    "controller_cycle_seconds": (
        "histogram", "busy negotiation-round duration (idle parks excluded)"),
    "negotiation_fanin_frames_total": (
        "counter", "readiness frames this rank pushed toward the "
                   "coordinator, labeled path=tree (via/as the host "
                   "aggregator) or path=direct (straight to rank 0)"),
    "negotiation_fanin_fallbacks_total": (
        "counter", "stale-aggregator convictions on this rank — each one "
                   "is a coordinated abort + reshard that degrades the "
                   "host to the direct path for the veto cooldown"),
    "controller_ingress_frames_total": (
        "counter", "negotiation frames rank 0 received per-sender (tree "
                   "bundles count once; O(hosts) under fan-in vs "
                   "O(ranks) star — nonzero on the coordinator only)"),
    "controller_ingress_bytes_total": (
        "counter", "payload bytes behind controller_ingress_frames_total "
                   "(nonzero on the coordinator only)"),
    "tensor_queue_depth": (
        "gauge", "tensors in flight (submitted, not yet completed)"),
    "controller_idle_park_ms": (
        "gauge", "the park the background loop last took before a round "
                 "with work, in ms: 0 while rounds with work follow each "
                 "other, up to HOROVOD_CYCLE_TIME after an idle stretch "
                 "(set on rounds with work only)"),
    # -- collectives --
    "collective_latency_seconds": (
        "histogram", "host-side dispatch latency per negotiated response, "
                     "labeled op/dtype/size (device-async ops record the "
                     "host dispatch cost; device completion is the "
                     "finalizer's)"),
    # -- stall inspector --
    "stalled_tensors": (
        "gauge", "tensors currently past the stall-warning age "
                 "(coordinator only; updated each stall check)"),
    "stall_shutdowns_total": (
        "counter", "hard stall-shutdown aborts fired (coordinator only)"),
    # -- straggler detector (coordinator-side; docs/observability.md) --
    "straggler_lag_seconds": (
        "histogram", "per-cycle readiness lag of a rank currently holding "
                     "tensors past the median announcer, labeled rank= "
                     "(coordinator only; lag-free cycles record nothing)"),
    "straggler_suspect": (
        "gauge", "rank id of the worst straggler suspect (readiness-lag "
                 "EWMA over HOROVOD_STRAGGLER_THRESHOLD_SECS), -1 when "
                 "no rank is flagged (coordinator only)"),
    "straggler_flags_total": (
        "counter", "straggler flag transitions — a rank's readiness-lag "
                   "EWMA crossing the threshold — labeled rank= "
                   "(coordinator only)"),
    "straggler_demotions_total": (
        "counter", "chronic-straggler demotions the elastic driver acted "
                   "on (host blacklisted + epoch advanced), labeled "
                   "rank=/host= (driver only; docs/elastic.md "
                   "self-healing demotion)"),
    "demotion_latency_seconds": (
        "histogram", "coordinator verdict posted -> driver blacklist "
                     "applied, wall-clock across processes (driver only; "
                     "the sim lane measures the full flag->first-step "
                     "curve on one clock)"),
    # -- runtime init (common/compile_cache.py) --
    "compile_cache_entries_written": (
        "counter", "persistent compile-cache entries this process wrote "
                   "through common/compile_cache.py's hook: programs on its "
                   "own devices, in a jax.distributed process other than 0 "
                   "(process 0 writes through JAX and counts none)"),
    # -- rendezvous / elastic --
    "rendezvous_store_ops_total": (
        "counter", "HTTP KV store requests, labeled op=get|set|delete|keys"),
    "elastic_epoch": ("gauge", "membership epoch this process last adopted"),
    "elastic_epoch_changes_total": (
        "counter", "elastic re-rendezvous epoch adoptions"),
    "store_outage_seconds_total": (
        "counter", "seconds the rendezvous store was unreachable from "
                   "this process's push loop (accumulated across outages)"),
    "lease_renew_failures_total": (
        "counter", "liveness-lease renewals that failed to reach the "
                   "rendezvous store"),
    "lease_expirations_total": (
        "counter", "worker leases the elastic driver declared expired "
                   "(dead worker => epoch advance; driver only)"),
    # -- control plane: rendezvous server / journal / driver
    #    (docs/observability.md "Control-plane attribution") --
    "rendezvous_request_seconds": (
        "histogram", "server-side HTTP request handling latency, labeled "
                     "op=put|get|delete|keys|metrics|clock (rendezvous "
                     "server process only)"),
    "rendezvous_requests_in_flight": (
        "gauge", "HTTP requests the rendezvous server is handling right "
                 "now (threaded server; >1 means concurrent clients)"),
    "rendezvous_scope_ops_total": (
        "counter", "server-side KV operations per namespace, labeled "
                   "scope=/op= (which plane — lease, metrics, discovery, "
                   "rendezvous table — generates the request load)"),
    "rendezvous_store_lock_wait_seconds": (
        "histogram", "time a server handler thread waited to acquire the "
                     "store lock (contention term of request latency)"),
    # -- batched transactions (POST /batch) --
    "rendezvous_batch_ops_total": (
        "counter", "KV sub-operations carried inside batched /batch "
                   "transactions (client side; compare against "
                   "rendezvous_store_ops_total to see the coalescing win)"),
    "rendezvous_batch_fallbacks_total": (
        "counter", "batched requests degraded to per-op calls because the "
                   "server 404/501'd /batch (old protocol; sticky per "
                   "client)"),
    "rendezvous_batch_size": (
        "histogram", "sub-ops per /batch transaction, server side "
                     "(bucket bounds top out at 64 — larger batches land "
                     "in +Inf; use sum/count for the mean)"),
    # -- simulated cluster (horovod_tpu/sim/) --
    "sim_identities": (
        "gauge", "simulated worker identities currently renewing leases "
                 "(sim harness only)"),
    "sim_churn_events_total": (
        "counter", "churn events the simulated cluster injected, labeled "
                   "kind=lease_expiry|reset_request|worker_exit|demotion"),
    "sim_wire_delay_seconds_total": (
        "counter", "artificial shaped-wire delay the sim injected across "
                   "all links (latency + bandwidth + jitter terms)"),
    "journal_append_seconds": (
        "histogram", "durable-store journal append, frame write through "
                     "fsync (the per-mutation durability tax)"),
    "journal_fsync_seconds": (
        "histogram", "fsync portion of a journal append/compaction "
                     "(0-sample when HOROVOD_JOURNAL_FSYNC=0)"),
    "journal_replay_seconds": (
        "histogram", "journal recovery replay duration at store open"),
    "journal_truncated_tails_total": (
        "counter", "torn journal tails discarded during recovery (each is "
                   "one crash mid-append survived)"),
    "journal_compaction_seconds": (
        "histogram", "snapshot compaction duration (journal rewrite)"),
    "journal_generation": (
        "gauge", "current journal snapshot generation (bumps once per "
                 "compaction; pairs with journal_compaction_seconds)"),
    "leases_live": (
        "gauge", "worker liveness leases the elastic driver currently "
                 "tracks as live (driver only; updated each lease scan)"),
    "lease_min_ttl_seconds": (
        "gauge", "smallest time-to-expiry across live leases (driver "
                 "only; negative means a lease is inside its grace "
                 "window and about to be declared expired)"),
    # -- sparse experts (parallel/moe.py::publish_routing) --
    "moe_max_load_ratio": (
        "gauge", "busiest expert's routed rows over the mean over experts, "
                 "per layer= , summed over the steps the router's counters "
                 "saw"),
    "moe_routed_tokens_per_step": (
        "gauge", "routed rows a step (k * tokens, summed over layers)"),
    "moe_steps": (
        "gauge", "steps the router's counters have counted"),
    "moe_rows_held_per_step": (
        "gauge", "where a layer holds a share of the experts "
                 "(moe_ffn(held=...)): routed rows a step whose expert "
                 "lives here, per layer="),
    "moe_rows_elsewhere_share": (
        "gauge", "share of a layer's routed rows bound for experts that "
                 "live elsewhere (counted, not computed), per layer="),
    "moe_overflow_chunks_per_step": (
        "gauge", "where a layer holds a share of the experts: chunks of "
                 "rows behind row_buffer's first that a step ran (and ran "
                 "again in its backward pass), per layer= ; zero while the "
                 "routing stays within five quarters of the mean share"),
    "moe_expert_bias_abs_max": (
        "gauge", "where the router chooses by scores plus a bias the step "
                 "keeps (moe_ffn(bias=...), update_expert_bias): the largest "
                 "magnitude among a layer's biases, per layer= ; zero until "
                 "the first step, then a multiple of the update rate"),
    "moe_router_product_passes": (
        "gauge", "bf16 passes of the MXU a layer's router spends on its "
                 "logits, per layer= : 3 where the rows it reads are a "
                 "bfloat16 array (the fp32 weights alone are split), 6 for "
                 "any other dtype (fp32 by fp32 at the highest precision); "
                 "set where the layer is traced (models/transformer.py)"),
    # -- attention under a layer pattern
    #    (models/transformer.py::publish_attention) --
    "attn_allowed_pairs_per_step": (
        "gauge", "(query, key) pairs a step's attention masks allow, from "
                 "the shapes, summed over the layers of kind=window (causal "
                 "inside a window) and of kind=global (the model's mask; the "
                 "prediction modules' blocks behind the stack among them), "
                 "every query head counted once"),
    "attn_head_pairs_per_step": (
        "gauge", "attn_allowed_pairs_per_step with each layer's pairs times "
                 "that layer's query heads (its kind's where a layer kind "
                 "states them, LayerKind.heads, else the model's): what the "
                 "attention kernels' work goes by, per kind=window and "
                 "kind=global"),
    "gdn_chunks_per_step": (
        "gauge", "chunks of the gated delta rule a step runs, one a value "
                 "head, chunk of positions and Gated DeltaNet layer, from "
                 "the shapes (models/transformer.py::publish_gated_delta)"),
    "kda_chunks_per_step": (
        "gauge", "chunks of Kimi Delta Attention's rule a step runs, one a "
                 "head, chunk of positions and KDA layer, from the shapes "
                 "(models/transformer.py::publish_kda)"),
    "indexer_pairs_scored_per_step": (
        "gauge", "(query, key) pairs a step's indexers score, the causal "
                 "pairs of every sequence and layer with an indexer, from "
                 "the shapes (models/transformer.py::publish_indexer)"),
    "attention_pairs_chosen_per_step": (
        "gauge", "(query, key) pairs the chosen sets of a step hold, topk a "
                 "query and every causal key below topk positions, summed "
                 "over the layers with an indexer, every query head counted "
                 "once, from the shapes (publish_indexer)"),
    "driver_tick_seconds": (
        "histogram", "elastic driver discovery-tick duration (lease scan "
                     "+ host discovery + any epoch transition it caused)"),
    "driver_epoch_transitions_total": (
        "counter", "elastic driver epoch advances, labeled cause="
                   "lease_expiry|demotion|reset_request|worker_exit|"
                   "host_change|reshard (driver only; the flight recorder "
                   "carries the same cause tag per event; a zero-restart "
                   "reshard counts BOTH its churn cause and one extra "
                   "cause=reshard sample when the commit lands)"),
    "reshard_seconds": (
        "histogram", "zero-restart reshard duration, driver side: "
                     "reshard-marked slot-table publish through the "
                     "survivor-acked topology commit (driver only; no "
                     "sample when the epoch falls back to the legacy "
                     "full-teardown path)"),
    "reshard_fallbacks_total": (
        "counter", "reshard attempts abandoned to the legacy full-"
                   "teardown path (a survivor crashed or stopped acking "
                   "mid-reshard, so the next epoch published without the "
                   "marker)"),
    # -- integrity / failure plane --
    "crc_verify_seconds_total": (
        "counter", "seconds spent computing/verifying wire CRC32 "
                   "(ROADMAP item 2's direct measurement)"),
    "crc_shadow_seconds_total": (
        "counter", "seconds spent in deferred (shadow) wire digests — "
                   "runs off the serial path, so this measures overlap "
                   "cost, not added step latency"),
    "wire_compress_seconds_total": (
        "counter", "seconds spent casting payloads to/from the wire "
                   "dtype (compress, widen-reduce, restore, quantize)"),
    "wire_codec_bytes_total": (
        "counter", "compressed payload bytes produced per wire codec, "
                   "labeled codec=fp16|bf16|int8|onebit|topk<K> — the "
                   "per-codec split of wire_compressed_bytes_total"),
    "wire_ef_residual_bytes": (
        "gauge", "bytes held in error-feedback residual accumulators "
                 "(lossy wire codecs; grows once per distinct "
                 "tensor-set/segment shape, then stays flat)"),
    "wire_ef_flush_seconds_total": (
        "counter", "seconds spent folding error-feedback residuals into "
                   "segments and computing the new residual after each "
                   "lossy encode"),
    "aborts_total": (
        "counter", "coordinated aborts, labeled dir=sent|received"),
    # -- transport selection (transport/select.py, transport/shm.py) --
    "shm_bytes_total": (
        "counter", "data payload bytes framed/delivered by the shared-"
                   "memory transport — the shm twin of "
                   "wire_bytes_on_wire_total, counted separately because "
                   "these bytes never cross a wire (one count per "
                   "endpoint per data frame; control and digest-check "
                   "frames excluded, same discipline as TCP)"),
    "transport_links_total": (
        "counter", "peer links classified at mesh bring-up, labeled "
                   "transport=shm|tcp (per-link selection seam)"),
    "faults_injected_total": (
        "counter", "fault-injection clauses fired (chaos runs only)"),
    # -- registered views (phase_stats / wire_stats) --
    "phase_seconds_total": (
        "counter", "accumulated wall time per dispatch-chain phase "
                   "(phase_stats view; labeled phase=)"),
    "phase_ops_total": (
        "counter", "events per dispatch-chain phase (phase_stats view)"),
    "wire_bytes_on_wire_total": (
        "counter", "data payload bytes framed/delivered by the TCP "
                   "transport (wire_stats view)"),
    "wire_heap_copies_total": (
        "counter", "payload materializations in the host data plane "
                   "(wire_stats view; the zero-copy guard's counter)"),
    "wire_compressed_bytes_total": (
        "counter", "narrow payload bytes produced/consumed by wire "
                   "compression (wire_stats view; compare against "
                   "wire_bytes_on_wire_total for the achieved ratio)"),
    # -- bandwidth plane --
    "fusion_reorders_total": (
        "counter", "negotiation cycles where readiness ordering changed "
                   "the fusion packing order (coordinator only)"),
    # -- raw stat names (the literals fed to phase_stats/wire_stats.add
    #    and to timeline.phase(); HVD007 checks those call sites against
    #    this catalog too, and the phases are timeline.PHASES) --
    "negotiate": ("stat", "phase_stats: controller round, busy cycles"),
    "fuse": ("stat", "phase_stats: staging the fused buffer"),
    "collective": ("stat", "phase_stats: host cost of the collective"),
    "unfuse": ("stat", "phase_stats: slicing results to outputs"),
    "wait": ("stat", "phase_stats: framework-thread handle waits"),
    "update": ("stat", "phase_stats: a whole DistributedOptimizer.update"),
    "enqueue": ("stat", "phase_stats: submitting a tree's tensors"),
    "tree_unflatten": ("stat", "phase_stats: fused buffers to leaves"),
    "optimizer_update": ("stat", "phase_stats: the inner optax programs"),
    "queue_wait": ("stat", "phase_stats: tensor queue to background loop"),
    "dispatch_wait": ("stat", "phase_stats: loop to dispatcher thread"),
    "program_call": ("stat", "phase_stats: calls of the framework's "
                             "jitted programs; count = output arrays"),
    "wfbp_dispatch": ("stat", "phase_stats: one OverlappedTrainStep call"),
    "state_fuse": ("stat", "phase_stats: updates that joined a tree state"),
    "negotiate_wait": ("stat", "phase_stats: a tensor from this rank's "
                               "announcement to the round that agreed"),
    "negotiate_recv": ("stat", "phase_stats: rounds blocked on another "
                               "rank's frame, a tensor in flight"),
    "negotiate_idle": ("stat", "phase_stats: the rounds negotiate leaves "
                               "out"),
    "cpu.loop": ("stat", "phase_stats: the loop thread's CPU seconds "
                         "(thread_time, not wall)"),
    "cpu.dispatch": ("stat", "phase_stats: the dispatcher thread's CPU "
                             "seconds (thread_time, not wall)"),
    "cpu.update": ("stat", "phase_stats: the calling thread's CPU seconds "
                           "inside update (thread_time, not wall)"),
    "bytes_on_wire": ("stat", "wire_stats: per-frame payload bytes"),
    "heap_copies": ("stat", "wire_stats: data-plane materializations"),
    "compressed_bytes": ("stat", "wire_stats: narrow wire-dtype bytes"),
}

#: Fast-path flag (the ``faults.ACTIVE`` pattern): when False every
#: module-level recording call returns after one attribute read.
ENABLED = env_mod.get_bool(env_mod.HOROVOD_METRICS, True)


def configure(enabled: Optional[bool] = None) -> None:
    """Set (or re-read from the environment) the enable flag — tests and
    the bench sweep use this; production processes inherit the env."""
    global ENABLED
    if enabled is None:
        enabled = env_mod.get_bool(env_mod.HOROVOD_METRICS, True)
    ENABLED = bool(enabled)


def flat(name: str, **labels) -> str:
    """Flatten a metric name + labels into the Prometheus series form
    (``name{k="v",...}``, keys sorted).  Label values must not contain
    ``"`` or newlines — enforced here because the flat string is also the
    storage/merge key and the renderer re-parses it."""
    if not labels:
        return name
    parts = []
    for k in sorted(labels):
        v = str(labels[k])
        if '"' in v or "\n" in v:
            raise ValueError(f"label value {v!r} for {k} contains a "
                             "forbidden character")
        parts.append(f'{k}="{v}"')
    return name + "{" + ",".join(parts) + "}"


def parse_flat(flat_name: str) -> Tuple[str, Dict[str, str]]:
    """Inverse of :func:`flat` (for the renderer's rank-label injection)."""
    if "{" not in flat_name:
        return flat_name, {}
    base, _, rest = flat_name.partition("{")
    labels: Dict[str, str] = {}
    for item in rest.rstrip("}").split(","):
        if not item:
            continue
        k, _, v = item.partition("=")
        labels[k] = v.strip('"')
    return base, labels


def size_bucket_label(nbytes: int) -> str:
    """Power-of-two-ceiling size label (``4 MiB`` → ``2^22``) for the
    per-collective latency histogram's ``size=`` dimension."""
    if nbytes <= 1:
        return "2^0"
    return f"2^{(int(nbytes) - 1).bit_length()}"


class MetricsRegistry:
    """One process's metric state.  All mutation is under one small lock;
    views are called OUTSIDE it (they hold their own locks)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        # flat -> [per-bucket counts (len(BUCKET_BOUNDS)+1), sum, count]
        self._hists: Dict[str, List] = {}
        self._views: Dict[str, Callable[[], dict]] = {}

    # -- recording ------------------------------------------------------

    def inc(self, name: str, value: float = 1, **labels) -> None:
        key = flat(name, **labels)
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + value

    def set_gauge(self, name: str, value: float, **labels) -> None:
        key = flat(name, **labels)
        with self._lock:
            self._gauges[key] = value

    def observe(self, name: str, value: float, **labels) -> None:
        key = flat(name, **labels)
        idx = bisect.bisect_left(BUCKET_BOUNDS, value)
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = [[0] * (len(BUCKET_BOUNDS) + 1),
                                        0.0, 0]
            h[0][idx] += 1
            h[1] += value
            h[2] += 1

    def register_view(self, name: str,
                      fn: Callable[[], dict]) -> None:
        """Register (or replace) a snapshot view: ``fn()`` returns
        ``{"counters": {flat: v}, "gauges": {flat: v}}`` folded into
        every snapshot.  Re-registration under the same name replaces —
        elastic re-initialization must not accumulate stale closures."""
        with self._lock:
            self._views[name] = fn

    # -- reading --------------------------------------------------------

    def get_counter(self, name: str, **labels) -> float:
        with self._lock:
            return self._counters.get(flat(name, **labels), 0)

    def get_gauge(self, name: str, **labels) -> Optional[float]:
        with self._lock:
            return self._gauges.get(flat(name, **labels))

    def snapshot(self) -> dict:
        """JSON-able copy of everything, views folded in — the unit the
        push thread ships to the rendezvous KV."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = {k: {"counts": list(h[0]), "sum": h[1], "count": h[2]}
                     for k, h in self._hists.items()}
            views = list(self._views.items())
        for _, fn in views:
            try:
                out = fn() or {}
            except Exception:  # noqa: BLE001 — a broken view must not
                # take down the scrape; the other series still matter.
                continue
            counters.update(out.get("counters", {}))
            gauges.update(out.get("gauges", {}))
        return {
            "version": 1,
            "rank": env_mod.get_int(env_mod.HOROVOD_RANK, 0),
            "ts_unix_ns": time.time_ns(),
            "bucket_bounds": list(BUCKET_BOUNDS),
            "counters": counters,
            "gauges": gauges,
            "histograms": hists,
        }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


#: The process-global registry every instrumented site records into.
registry = MetricsRegistry()


# -- module-level conveniences (the instrumented-site API; one attribute
#    read when disabled, like faults.ACTIVE) -------------------------------


def inc(name: str, value: float = 1, **labels) -> None:
    if ENABLED:
        registry.inc(name, value, **labels)


def set_gauge(name: str, value: float, **labels) -> None:
    if ENABLED:
        registry.set_gauge(name, value, **labels)


def observe(name: str, value: float, **labels) -> None:
    if ENABLED:
        registry.observe(name, value, **labels)


# -- cross-rank merge + Prometheus text rendering --------------------------


def _fmt(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else repr(float(v))


def _help_type(base: str, kind: str, out: List[str],
               emitted: set) -> None:
    if base in emitted:
        return
    emitted.add(base)
    entry = CATALOG.get(base)
    help_text = entry[1] if entry else ""
    out.append(f"# HELP {PROM_PREFIX}{base} {help_text}")
    out.append(f"# TYPE {PROM_PREFIX}{base} {kind}")


def render_prometheus(snapshots: Dict) -> str:
    """Aggregate per-rank snapshot dicts into Prometheus text format
    (version 0.0.4): counters and histogram buckets summed across ranks,
    gauges labeled by rank.  ``snapshots`` maps any key to a snapshot
    dict; the rank comes from each snapshot's own ``rank`` field."""
    counters: Dict[str, float] = {}
    hists: Dict[str, List] = {}
    gauge_lines: List[Tuple[str, str, float]] = []  # (base, flat+rank, v)
    for key, snap in sorted(snapshots.items(), key=lambda kv: str(kv[0])):
        if not isinstance(snap, dict):
            continue
        rank = snap.get("rank", key)
        for k, v in snap.get("counters", {}).items():
            counters[k] = counters.get(k, 0) + v
        for k, h in snap.get("histograms", {}).items():
            counts = list(h.get("counts", []))
            agg = hists.get(k)
            if agg is None:
                hists[k] = [counts, float(h.get("sum", 0.0)),
                            int(h.get("count", 0)),
                            list(snap.get("bucket_bounds", BUCKET_BOUNDS))]
            elif len(agg[0]) == len(counts):
                agg[0] = [a + b for a, b in zip(agg[0], counts)]
                agg[1] += float(h.get("sum", 0.0))
                agg[2] += int(h.get("count", 0))
        for k, v in snap.get("gauges", {}).items():
            base, labels = parse_flat(k)
            labels["rank"] = str(rank)
            gauge_lines.append((base, flat(base, **labels), v))

    out: List[str] = []
    emitted: set = set()
    for k in sorted(counters):
        base, _ = parse_flat(k)
        _help_type(base, "counter", out, emitted)
        out.append(f"{PROM_PREFIX}{k} {_fmt(counters[k])}")
    for base, flat_name, v in sorted(gauge_lines, key=lambda t: t[1]):
        _help_type(base, "gauge", out, emitted)
        out.append(f"{PROM_PREFIX}{flat_name} {_fmt(v)}")
    for k in sorted(hists):
        counts, total, n, bounds = hists[k]
        base, labels = parse_flat(k)
        _help_type(base, "histogram", out, emitted)
        cum = 0
        for i, bound in enumerate(list(bounds) + [float("inf")]):
            cum += counts[i] if i < len(counts) else 0
            le = "+Inf" if bound == float("inf") else repr(bound)
            out.append(PROM_PREFIX
                       + flat(base + "_bucket", **{**labels, "le": le})
                       + f" {cum}")
        out.append(f"{PROM_PREFIX}{flat(base + '_sum', **labels)} "
                   f"{_fmt(total)}")
        out.append(f"{PROM_PREFIX}{flat(base + '_count', **labels)} {n}")
    return "\n".join(out) + ("\n" if out else "")


# Deferred re-export (see the note near the top of the module): the
# transport package import chain reaches back into ``metrics.registry``,
# so the scope registry can only be imported once that exists.
from ..transport.scopes import METRICS_SCOPE  # noqa: E402,F401  (re-export)
