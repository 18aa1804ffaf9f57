"""Prometheus-style metrics, dependency-free.

Reference: TiKV instruments every crate with prometheus counters/
histograms behind lazy_static registries served at /metrics
(SURVEY.md §5.5; src/server/status_server/mod.rs:666).  This module is
the same shape: process-global default registry, Counter / Gauge /
Histogram with label support, text exposition format v0.0.4 — scrape
it with a stock Prometheus.

Thread-safety: one lock per metric family; hot-path increments are a
dict lookup + float add (measured ~0.3µs), cheap enough for the RPC
and raft paths they instrument.
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Sequence


class _Family:
    kind = "untyped"

    def __init__(self, name: str, help_: str, labels: Sequence[str] = ()):
        self.name = name
        self.help = help_
        self.label_names = tuple(labels)
        self._children: dict = {}
        self._lock = threading.Lock()

    def labels(self, *values):
        if len(values) != len(self.label_names):
            raise ValueError(f"{self.name}: want labels "
                             f"{self.label_names}, got {values!r}")
        key = tuple(str(v) for v in values)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.setdefault(key, self._new_child())
        return child

    def remove(self, *values) -> None:
        """Drop one label set (prometheus client remove()): callers with
        churning label values — per-region gauges across splits/merges —
        must retire dead series or the registry grows without bound."""
        key = tuple(str(v) for v in values)
        with self._lock:
            self._children.pop(key, None)

    def _default(self):
        return self.labels() if not self.label_names else None

    # -- exposition --

    def _render_lines(self):
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} {self.kind}"]
        with self._lock:
            items = list(self._children.items())
        for key, child in items:
            lbl = ""
            if key:
                pairs = ",".join(f'{n}="{v}"'
                                 for n, v in zip(self.label_names, key))
                lbl = "{" + pairs + "}"
            out.extend(child.render(self.name, lbl))
        return out


class _CounterChild:
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, by: float = 1.0) -> None:
        # += is LOAD/ADD/STORE bytecode — not atomic under the GIL
        with self._lock:
            self.value += by

    def render(self, name, lbl):
        return [f"{name}{lbl} {self.value!r}"]


class Counter(_Family):
    kind = "counter"

    def _new_child(self):
        return _CounterChild()

    def inc(self, by: float = 1.0) -> None:
        self.labels().inc(by)

    @property
    def value(self) -> float:
        child = self._children.get(())
        return child.value if child else 0.0


class _GaugeChild:
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self.value = v

    def inc(self, by: float = 1.0) -> None:
        with self._lock:
            self.value += by

    def dec(self, by: float = 1.0) -> None:
        with self._lock:
            self.value -= by

    def render(self, name, lbl):
        return [f"{name}{lbl} {self.value!r}"]


class Gauge(_Family):
    kind = "gauge"

    def _new_child(self):
        return _GaugeChild()

    def set(self, v: float) -> None:
        self.labels().set(v)

    def inc(self, by: float = 1.0) -> None:
        self.labels().inc(by)

    def dec(self, by: float = 1.0) -> None:
        self.labels().dec(by)

    @property
    def value(self) -> float:
        child = self._children.get(())
        return child.value if child else 0.0


# TiKV's standard latency buckets: exponential from 0.5ms
_DEFAULT_BUCKETS = tuple(0.0005 * (2 ** i) for i in range(20))


class _HistogramChild:
    __slots__ = ("buckets", "counts", "total", "count", "_lock")

    def __init__(self, buckets):
        self.buckets = buckets
        self.counts = [0] * len(buckets)
        self.total = 0.0
        self.count = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        with self._lock:
            self.count += 1
            self.total += v
            for i, ub in enumerate(self.buckets):
                if v <= ub:
                    self.counts[i] += 1

    def time(self):
        return _Timer(self)

    def render(self, name, lbl):
        out = []
        inner = lbl[1:-1] if lbl else ""
        sep = "," if inner else ""
        # counts[] is cumulative by construction (observe adds to every
        # bucket with v <= ub), matching _bucket semantics directly
        for ub, c in zip(self.buckets, self.counts):
            out.append(f'{name}_bucket{{{inner}{sep}le="{ub:g}"}} {c}')
        out.append(f'{name}_bucket{{{inner}{sep}le="+Inf"}} {self.count}')
        out.append(f"{name}_sum{lbl} {self.total!r}")
        out.append(f"{name}_count{lbl} {self.count}")
        return out


class Histogram(_Family):
    kind = "histogram"

    def __init__(self, name, help_, labels=(), buckets=_DEFAULT_BUCKETS):
        super().__init__(name, help_, labels)
        self.buckets = tuple(sorted(buckets))

    def _new_child(self):
        return _HistogramChild(self.buckets)

    def observe(self, v: float) -> None:
        self.labels().observe(v)

    def time(self):
        if self.label_names:
            # a silent no-op timer would discard every observation;
            # bind the labels first: h.labels(...).time()
            raise ValueError(
                f"{self.name} has labels {self.label_names}; use "
                "labels(...).time()")
        return _Timer(self.labels())


class _Timer:
    def __init__(self, child):
        self._child = child

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._child is not None:
            self._child.observe(time.perf_counter() - self._t0)
        return False


class Registry:
    def __init__(self):
        self._families: dict[str, _Family] = {}
        self._lock = threading.Lock()

    def register(self, fam: _Family) -> _Family:
        with self._lock:
            cur = self._families.get(fam.name)
            if cur is not None:
                return cur
            self._families[fam.name] = fam
            return fam

    def counter(self, name, help_, labels=()) -> Counter:
        return self.register(Counter(name, help_, labels))  # type: ignore

    def gauge(self, name, help_, labels=()) -> Gauge:
        return self.register(Gauge(name, help_, labels))  # type: ignore

    def histogram(self, name, help_, labels=(),
                  buckets=_DEFAULT_BUCKETS) -> Histogram:
        return self.register(
            Histogram(name, help_, labels, buckets))  # type: ignore

    def expose(self) -> str:
        """The /metrics payload (text format v0.0.4)."""
        lines = []
        with self._lock:
            fams = sorted(self._families.values(), key=lambda f: f.name)
        for fam in fams:
            lines.extend(fam._render_lines())
        return "\n".join(lines) + "\n"


REGISTRY = Registry()

# -- the framework's core instruments (metrics.rs analogs) --

GRPC_MSG_COUNTER = REGISTRY.counter(
    "tikv_grpc_msg_total", "gRPC requests by method and status",
    labels=("method", "status"))
GRPC_MSG_DURATION = REGISTRY.histogram(
    "tikv_grpc_msg_duration_seconds", "gRPC request duration",
    labels=("method",))
RAFT_PROPOSE_COUNTER = REGISTRY.counter(
    "tikv_raftstore_propose_total", "raft proposals by type",
    labels=("type",))
RAFT_APPLY_COUNTER = REGISTRY.counter(
    "tikv_raftstore_apply_total", "applied raft entries")
RAFT_READY_COUNTER = REGISTRY.counter(
    "tikv_raftstore_ready_handled_total", "raft ready batches handled")
RAFT_MSG_DROP_COUNTER = REGISTRY.counter(
    "tikv_server_raft_message_dropped_total",
    "raft messages dropped by the transport (queue full / send failed)",
    labels=("reason",))
SNAP_CHUNK_COUNTER = REGISTRY.counter(
    "tikv_server_snapshot_chunks_sent_total",
    "snapshot chunks shipped on the dedicated stream")
READ_POOL_RUNNING_GAUGE = REGISTRY.gauge(
    "tikv_unified_read_pool_running_tasks",
    "read-pool tasks currently executing")
READ_POOL_PENDING_GAUGE = REGISTRY.gauge(
    "tikv_unified_read_pool_pending_tasks",
    "read-pool tasks admitted and waiting for a slot")
COPR_REQ_COUNTER = REGISTRY.counter(
    "tikv_coprocessor_request_total", "coprocessor requests by backend",
    labels=("backend",))
COPR_REPLY_COUNTER = REGISTRY.counter(
    "tikv_coprocessor_reply_total",
    "coprocessor replies by how they carry their result "
    "(rows / chunk: a buffer a column, where the request asked for one)",
    labels=("encode",))
COPR_LOCKED_REPLY_COUNTER = REGISTRY.counter(
    "tikv_coprocessor_locked_reply_total",
    "coprocessor requests answered key_is_locked: a lock of a "
    "transaction started at or before the read's TSO lay in its ranges")
COPR_CHUNK_ROWS = REGISTRY.counter(
    "tikv_coprocessor_reply_chunk_rows_total",
    "rows that left in chunk replies")
COPR_CHUNK_BYTES = REGISTRY.counter(
    "tikv_coprocessor_reply_chunk_bytes_total",
    "bytes of the column buffers of chunk replies")
COPR_REQ_DURATION = REGISTRY.histogram(
    "tikv_coprocessor_request_duration_seconds",
    "coprocessor request duration", labels=("backend",))
COPR_CACHE_COUNTER = REGISTRY.counter(
    "tikv_coprocessor_region_cache_total",
    "region columnar cache lookups "
    "(hit / miss / delta = patched forward / rebuild = fallback)",
    labels=("result",))
COPR_TOMBSTONE_RATIO = REGISTRY.gauge(
    "tikv_coprocessor_region_cache_tombstone_ratio",
    "pending delete tombstones / rows in a delta-maintained columnar "
    "cache line (compaction input)", labels=("region",))
COPR_DELTA_LOG_DEPTH = REGISTRY.gauge(
    "tikv_coprocessor_delta_log_depth",
    "applied entries retained in the per-region committed-write delta "
    "log", labels=("region",))
READ_POOL_EMA_GAUGE = REGISTRY.gauge(
    "tikv_unified_read_pool_ema_service_seconds",
    "EWMA of read-pool task service time (deadline shedding input)")
DEADLINE_SHED_COUNTER = REGISTRY.counter(
    "tikv_server_deadline_exceeded_total",
    "requests shed because their deadline expired, by pipeline stage",
    labels=("stage",))
SLOW_SCORE_GAUGE = REGISTRY.gauge(
    "tikv_server_slow_score",
    "store slow score (1 healthy .. 100 dead-slow), PD heartbeat input",
    labels=("store",))
SLOW_TREND_GAUGE = REGISTRY.gauge(
    "tikv_server_slow_trend_ratio",
    "short/long window write latency ratio (>1 = degrading)",
    labels=("store",))
PEER_BREAKER_GAUGE = REGISTRY.gauge(
    "tikv_server_peer_breaker_state",
    "per-peer-store transport breaker (0 closed, 1 half-open, 2 open)",
    labels=("peer_store",))
HEDGE_COUNTER = REGISTRY.counter(
    "tikv_client_hedged_reads_total",
    "hedged reads by outcome — point gets (leader_fast / fired / "
    "follower_won / leader_won) and device coprocessor hedges against "
    "a follower replica feed (copr_leader_fast / copr_fired / "
    "copr_follower_won / copr_leader_won / copr_stale_refused = the "
    "lagging replica's resolved-ts gate refused and the leader leg "
    "answered)",
    labels=("outcome",))
DEVICE_SEL_ROUTE_COUNTER = REGISTRY.counter(
    "tikv_device_selection_route_total",
    "late-materialized device selection routing decisions "
    "(mask / index / compact / mask_fallback = capacity overflow / "
    "batched = coalesced stacked-group dispatch)",
    labels=("route",))
DEVICE_SEL_SELECTIVITY = REGISTRY.gauge(
    "tikv_device_selection_observed_selectivity",
    "last device-side observed selection selectivity "
    "(selected rows / scanned rows — the routing cost-model input)")
COPR_RESIDENT_LINES = REGISTRY.gauge(
    "tikv_coprocessor_region_cache_resident_lines",
    "delta-maintained columnar cache lines currently resident "
    "(lifecycle teardown + LRU keep this bounded)")
DEVICE_HBM_RESIDENT_BYTES = REGISTRY.gauge(
    "tikv_device_hbm_resident_bytes",
    "bytes of device-resident derived state (HBM feeds + cached "
    "sparse-slot planes) accounted by the runner's feed arena")
DEVICE_FEED_LINES = REGISTRY.gauge(
    "tikv_device_feed_resident_lines",
    "feed-arena entries (one per snapshot/lineage anchor) resident "
    "on device")
DEVICE_FEED_EVICTION_COUNTER = REGISTRY.counter(
    "tikv_device_feed_evictions_total",
    "device feed lines dropped, by reason (budget = arena eviction, "
    "lifecycle = region event teardown, quarantine = scrub "
    "divergence, reject = would not fit the budget, drop = explicit)",
    labels=("reason",))
DEVICE_SCRUB_COUNTER = REGISTRY.counter(
    "tikv_device_scrub_total",
    "resident device feed LINES scrubbed, by result (clean / "
    "divergence = on-device digest != recorded digest); whole-pass "
    "counts live in the /health device_state.scrub_passes rollup",
    labels=("result",))
DEVICE_QUARANTINE_COUNTER = REGISTRY.counter(
    "tikv_device_feed_quarantine_total",
    "device feed lines quarantined after a scrub divergence "
    "(the region degrades to the host backend, then rebuilds)")
COPR_BATCH_OCCUPANCY = REGISTRY.histogram(
    "tikv_coprocessor_batch_occupancy",
    "requests per coalesced device dispatch group at group close "
    "(server/coalescer.py; 1 = a window expired with a lone member)",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32))
COPR_ROUTER_COUNTER = REGISTRY.counter(
    "tikv_coprocessor_router_total",
    "cost-based admission router decisions for device-eligible "
    "coprocessor requests (device_batched / device_solo / host / shed)",
    labels=("decision",))
COPR_COALESCE_CLOSE_COUNTER = REGISTRY.counter(
    "tikv_coprocessor_coalesce_group_close_total",
    "coalescer group closes by trigger (size = max_group reached, "
    "window = collection window expired, deadline = tightest member "
    "budget pressure, pipeline = back-to-back dispatcher fed an idle "
    "device early, failpoint = copr::coalesce_window, shutdown)",
    labels=("reason",))
COPR_FASTPATH_COUNTER = REGISTRY.counter(
    "tikv_coprocessor_fastpath_total",
    "compiled request fast path outcomes (server/fastpath.py): hit = "
    "served from a learned wire template, miss = no/failed template "
    "match (full decode), bypass = ineligible shape or copr::fastpath "
    "arm, fallback = validated entry raced a generation change mid-"
    "request (served via full ceremony), invalidate = entry retired "
    "(epoch/config/generation), learn = template admitted",
    labels=("outcome", "reason"))
DEVICE_MESH_SHARDS = REGISTRY.gauge(
    "tikv_device_mesh_shards",
    "devices in the runner's (range, tile) mesh (1 = single-chip; the "
    "sharded kernels partial-agg per shard and tree-reduce on ICI)")
DEVICE_SLICE_RESIDENT_BYTES = REGISTRY.gauge(
    "tikv_device_slice_resident_bytes",
    "HBM bytes resident per placement slice (device/placement.py; the "
    "occupancy half of the hot-region placement score)",
    labels=("slice",))
DEVICE_SLICE_LOAD = REGISTRY.gauge(
    "tikv_device_slice_load",
    "decayed dispatch-rate load score per placement slice (the "
    "slow-store-style traffic half of the placement score)",
    labels=("slice",))
DEVICE_SLICE_HEALTH = REGISTRY.gauge(
    "tikv_device_slice_health_penalty",
    "per-slice failure-domain health penalty (0 healthy .. ~1 at the "
    "quarantine trip threshold; device/supervisor.py SliceHealth — "
    "strikes from dispatch/fetch faults, scrub quarantines and "
    "launch-latency outliers, decayed by served requests)",
    labels=("slice",))
DEVICE_FAILOVER_COUNTER = REGISTRY.counter(
    "tikv_device_failure_domain_total",
    "chip failure-domain events (quarantine = slice tripped, drain = "
    "anchor re-pinned off a tripped slice, failover = route-time "
    "re-pin, refused_dispatch = launch refused on a quarantined "
    "slice, mesh_downsize = sharded serving rebuilt on a smaller "
    "healthy submesh, mesh_restore = full mesh back after "
    "re-admission, rescue = in-flight request retried off a dead "
    "slice, readmit = half-open canary succeeded, probe_fail = "
    "canary failed and the cooldown restarted)",
    labels=("event",))
DEVICE_PLACEMENT_COUNTER = REGISTRY.counter(
    "tikv_device_placement_total",
    "hot-region placement decisions (place = new anchor assigned to a "
    "slice, move = rebalance dropped an anchor off a hot slice, "
    "whole_mesh = feed large enough to shard over every chip)",
    labels=("decision",))
DEVICE_FEED_MIGRATION_COUNTER = REGISTRY.counter(
    "tikv_device_feed_migration_total",
    "ICI feed migrations between slices (moved = every feed arrived, "
    "digest-verified, and the anchor flipped with zero re-mint, "
    "partial = some feeds moved and the rest fell back to re-mint, "
    "corrupt = arrival verify caught a plane diverging mid-flight — "
    "quarantine-and-rebuild, never silent corruption, no_digests = "
    "nothing migratable was resident so the move degraded to the old "
    "drop+re-mint path, split = device-side region split minted child "
    "feeds from the parent without a columnar_build, split_fallback = "
    "device::device_split armed or the parent feed unusable — that "
    "split re-minted from host truth)",
    labels=("outcome",))
DEVICE_REMINT_QUEUE_DEPTH = REGISTRY.gauge(
    "tikv_device_remint_queue_depth",
    "cold columnar_build re-mints parked in the storm-control "
    "priority queue (hot regions first, RU-debt tenants last) "
    "waiting for one of the bounded concurrency permits")
DEVICE_REPLICA_FEEDS = REGISTRY.gauge(
    "tikv_device_replica_feeds",
    "regions this store holds a live follower replica feed for — a "
    "delta-patched columnar line serving resolved-ts-gated stale "
    "coprocessor reads (demoted leaders + stale-read-minted lines)")
DEVICE_REPLICA_PROMOTION_COUNTER = REGISTRY.counter(
    "tikv_device_replica_promotion_total",
    "leader-gain promotions of an already-patched replica feed (warm "
    "= scrub-digest re-verify passed and the feed serves as leader "
    "state with zero columnar_build, rebuild = verify failed or "
    "copr::replica_promote armed — lines invalidated, next request "
    "pays the cold build)",
    labels=("outcome",))
DEVICE_JOIN_ROUTE_COUNTER = REGISTRY.counter(
    "tikv_device_join_route_total",
    "plan-IR join fragment routing outcomes (device = one-dispatch "
    "probe against the HBM-resident build dictionary, host = modeled "
    "host win or outside the device envelope, degrade = device fault "
    "fell back to the host join for that fragment only, "
    "overflow_redispatch = pair capacity re-bucketed from the exact "
    "on-device total)",
    labels=("route",))
COPR_PLAN_FRAGMENT_COUNTER = REGISTRY.counter(
    "tikv_coprocessor_plan_fragment_total",
    "plan-IR fragments by kind and routed backend (per-operator "
    "host/device routing, copr/plan_ir.py FragmentRouter)",
    labels=("kind", "backend"))
RU_CHARGE_COUNTER = REGISTRY.counter(
    "tikv_resource_metering_ru_total",
    "request units charged, by charge site (ru_model.CHARGE_SITES: "
    "device::launch / copr::coalesce_dispatch = group launch split by "
    "occupancy share / device::d2h / arena::residency / "
    "read_pool::host / copr::scan)",
    labels=("site",))
RU_TENANT_COUNTER = REGISTRY.counter(
    "tikv_resource_metering_tenant_ru_total",
    "request units charged per tenant (the resource_group half of the "
    "tag; bounded by the recorder's max_resource_groups fold — "
    "overflow and idle tags aggregate into 'other', unattributable "
    "charges into the explicit 'untagged' residual)",
    labels=("tenant",))
RU_TAG_GAUGE = REGISTRY.gauge(
    "tikv_resource_metering_tags",
    "live (resource_group, request_source) tags in the metering "
    "recorder — bounded: beyond max_resource_groups new tags fold "
    "into 'other', idle tags fold on window roll")
RU_REQUEST_HISTOGRAM = REGISTRY.histogram(
    "tikv_resource_metering_request_ru",
    "request units charged per read RPC (sealed with the trace; the "
    "resource controller's admission input — resource_control.py)",
    buckets=(0.125, 0.25, 0.5, 1, 2, 4, 8, 16, 32, 64, 128, 256,
             512, 1024))
RC_ACTION_COUNTER = REGISTRY.counter(
    "tikv_resource_control_actions_total",
    "resource-control enforcement actions per group "
    "(resource_control.py: shed = RU-priced read-pool rejection, "
    "defer = coalescer DWFQ deferral to the next window, evict = "
    "tenant-biased arena eviction)",
    labels=("group", "action"))
RC_TOKENS_GAUGE = REGISTRY.gauge(
    "tikv_resource_control_tokens",
    "resource-control token-bucket level per group (negative = RU "
    "debt; refills at the group's configured share)",
    labels=("group",))
RC_PROTECTED_BYTES_GAUGE = REGISTRY.gauge(
    "tikv_resource_control_protected_bytes",
    "under-share tenants' HBM feed bytes left resident by the last "
    "tenant-aware eviction sweep that evicted over-share state — the "
    "latency tenant's working set the share protected")
SCHED_COMMANDS = REGISTRY.counter(
    "tikv_scheduler_commands_total", "txn scheduler commands",
    labels=("type",))
ENGINE_WRITE_COUNTER = REGISTRY.counter(
    "tikv_engine_write_total", "engine write batches")
