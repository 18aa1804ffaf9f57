"""Configuration tree + online reconfig dispatch.

Reference: src/config/mod.rs (``TikvConfig`` — one serde-TOML tree
embedding every subsystem's config), components/online_config
(``OnlineConfig`` derive + ``ConfigManager`` trait, lib.rs:137) and the
``ConfigController`` that routes live changes to registered managers;
POST /config on the status server feeds it (status_server/mod.rs:699).

Python shape: dataclass tree loaded from TOML (stdlib ``tomllib``),
validated, diffed for online updates.  Fields marked in
``_ONLINE_FIELDS`` may change at runtime; everything else is rejected
with the same "not an online-config field" contract the reference
enforces.
"""

from __future__ import annotations

import dataclasses
import threading
from dataclasses import dataclass, field, fields
from typing import Callable, Optional


@dataclass
class ServerConfig:
    addr: str = "127.0.0.1:20160"
    status_addr: str = ""               # "" = status server disabled
    grpc_concurrency: int = 8


@dataclass
class StorageConfig:
    data_dir: str = ""                  # "" = in-memory engine
    scheduler_concurrency: int = 4
    # encryption at rest ([security.encryption] in the reference's
    # config): path to a 64-hex-char master key file; "" = plaintext.
    # Data keys + the encrypted file dictionary live in the data dir
    # (components/encryption manager/ + file_dict_file.rs).
    master_key_file: str = ""


@dataclass
class RaftstoreConfig:
    raft_base_tick_interval_ms: int = 10
    raft_heartbeat_ticks: int = 2
    raft_election_timeout_ticks: int = 10
    region_split_size_mb: int = 96      # split-check threshold
    region_max_size_mb: int = 144
    region_split_check_ticks: int = 10  # split check every N ticks
    raft_log_gc_threshold: int = 1024
    hibernate_regions: bool = False
    # batch-system pollers (0 = synchronous drive loop) and async
    # raft-log writer threads (store-pool-size / store-io-pool-size)
    store_pool_size: int = 0
    store_io_pool_size: int = 1
    # apply-pool size (reference apply-pool-size, fsm/apply.rs second
    # batch-system); 0 = apply inline on the raft pollers
    apply_pool_size: int = 2
    region_bucket_size_mb: float = 32.0
    # load-based splitting (split_controller.rs): a region sustaining
    # >= split_qps_threshold reads/s for split_detect_times windows
    # splits at the sampled-access median key; 0 disables
    split_qps_threshold: int = 3000
    split_detect_times: int = 3


@dataclass
class CoprocessorConfig:
    # device routing crossover — rationale at
    # copr/endpoint.py Endpoint.DEFAULT_DEVICE_ROW_THRESHOLD
    device_row_threshold: int = 131072
    # REGIONS whose columnar cache lines are kept (least recently used
    # region out first, all its lines together); a region may hold a
    # line a scan schema, up to copr/region_cache.py SCHEMAS_PER_REGION
    region_cache_capacity: int = 8
    # paged response budget (endpoint.rs paging)
    response_page_rows: int = 1 << 20
    # incremental columnar cache maintenance (copr/region_cache.py):
    # per-region committed-write delta log bounds — a data-version gap
    # wider than the retained log rebuilds instead of patching
    delta_log_entries: int = 1024
    delta_log_rows: int = 1 << 16
    # compact a delta-maintained line when pending delete tombstones
    # exceed this fraction of its rows
    tombstone_compact_ratio: float = 0.25
    # device-state integrity (device/supervisor.py): HBM budget for the
    # runner's feed arena in MiB (0 = unlimited — accounting only) and
    # the background scrub cadence in seconds (0 = scrub on demand).
    # scrub_digests records per-plane content digests at feed build
    # (one vectorized host pass per plane) and patch time (one tiny
    # device reduction per plane) — the audit the scrubber compares
    # against; disable to shave the cold-upload/patch overhead on
    # deployments that never scrub
    device_hbm_budget_mb: int = 0
    scrub_interval_s: float = 0.0
    scrub_digests: bool = True
    # cross-request device batching (server/coalescer.py): concurrent
    # requests sharing a compile class + resident feed coalesce into
    # one stacked dispatch under a bounded, deadline-aware collection
    # window.  coalesce_window_ms = 0 disables the subsystem entirely
    # (every device request dispatches solo); coalesce_max_group caps
    # group size (also the stacked kernel's largest lane bucket)
    coalesce_window_ms: float = 2.0
    coalesce_max_group: int = 16
    # cold-path kill (device/mvcc.py + copr/stream_build.py):
    # device_cold_build enables the device rung of the columnar build
    # ladder (flat-plane parse + on-device MVCC version resolution, the
    # feed born resident); cold_stream additionally parses + uploads
    # CF_WRITE planes of bulk-ingested SST chunks WHILE the load runs,
    # so the first query's build degenerates to one resolve dispatch.
    # cold_stream=None (the default) is AUTO: on iff the process has a
    # spare core to run the parse worker on — the overlap premise is a
    # second core, and on a single-CPU box the worker only steals
    # cycles from the very ingest it shadows (measured: -20% loader
    # throughput and a stalled first query).  True/False force it.
    # cold_stream_max_mb bounds the retained host planes per region
    # (device planes shed first at half the cap); 0 = unlimited
    device_cold_build: bool = True
    cold_stream: Optional[bool] = None
    cold_stream_max_mb: int = 1024
    # multi-chip scale-out (parallel/mesh.py, device/placement.py):
    # mesh_shape pins the ("range", "tile") mesh factorization
    # ("2x4"; default None lets _factor2 pick the squarest split —
    # note a PRIME device count then degenerates to 1xN).  Fixed at
    # runner construction; the live shape is visible in /health
    # device_mesh.  device_placement turns on hot-region → slice
    # routing: small regions pin to single-device slices spread by
    # load (PD's balance-region policy one level down), feeds at or
    # above placement_rows shard over the whole mesh.
    mesh_shape: Optional[str] = None
    device_placement: bool = False
    placement_rows: int = 1 << 22
    # chip failure domains (device/supervisor.py SliceHealth): strikes
    # to quarantine a mesh slice (dispatch/fetch faults and scrub
    # quarantines weigh 1.0, launch-latency outliers 0.25; served
    # requests decay 0.5), the half-open canary-probe cooldown after a
    # trip, and the round-trip latency above which a served request
    # still counts as an outlier strike (0 disables the latency feed —
    # cold compiles on slow transports would otherwise strike healthy
    # slices)
    slice_trip_strikes: float = 3.0
    slice_probe_cooldown_s: float = 0.25
    slice_latency_outlier_s: float = 0.0
    # causal request tracing (utils/trace.py): trace_sample is the
    # fraction of read RPCs recording full span trees (a client-sent
    # trace_id always samples; TimeDetail stays on the wire for every
    # request regardless), trace_buffer bounds the /debug/trace
    # retention ring (tail-biased: slowest-per-class + errored/late
    # requests pin past ring eviction), slow_log_threshold_ms fires
    # the redacted slow-query log line (TiKV slow_log! analog; 0
    # disables), flight_recorder_depth bounds the device launch ring
    trace_sample: float = 1.0
    trace_buffer: int = 256
    slow_log_threshold_ms: float = 1000.0
    flight_recorder_depth: int = 256
    # microsecond warm path (server/fastpath.py + server/coalescer.py):
    # fastpath_classes bounds the learned wire-template cache (0
    # disables the compiled request fast path entirely — every request
    # takes the full decode pipeline); dispatch_pipeline enables the
    # coalescer's back-to-back dispatcher (collection overlaps the
    # in-flight launch, and a drained device is fed the oldest open
    # group early instead of waiting out its window)
    fastpath_classes: int = 64
    dispatch_pipeline: bool = True
    # re-mint storm control (device/supervisor.py RemintGovernor):
    # remint_concurrency bounds concurrent cold columnar_build
    # re-mints after a mass invalidation (0 = unthrottled — the
    # pre-storm-control behavior); excess builds park in a priority
    # queue (hot regions first, RU-debt tenants last) of at most
    # remint_queue, past which the worst-priority waiter is shed with
    # a ServerIsBusy carrying remint_retry_after_ms
    remint_concurrency: int = 0
    remint_queue: int = 32
    remint_retry_after_ms: int = 50


@dataclass
class ReadPoolConfig:
    concurrency: int = 8


@dataclass
class ResourceMeteringConfig:
    """[resource-metering]: device-aware RU attribution
    (resource_metering.py + ru_model.py).  Every field is
    online-updatable and visible in /health.

    The windowed recorder rolls per-tag/per-region charges every
    ``window_s``; the last window's top-``topk`` hot-tenant/hot-region
    report serves /resource_metering and rides the store heartbeat to
    PD every ``report_interval_s``.  ``max_resource_groups`` bounds
    the live tag map (overflow + idle tags fold into "other").  The
    ``ru_per_*`` weights are the linear cost model — see
    ru_model.RuModel's table for the defaults' rationale."""

    window_s: float = 5.0
    topk: int = 8
    max_resource_groups: int = 64
    report_interval_s: float = 5.0
    # RU weights (0 disables an axis); None in a TOML would be odd, so
    # the dataclass carries the model defaults verbatim
    ru_per_launch_s: float = 1000.0 / 3.0
    ru_per_host_s: float = 1000.0 / 3.0
    ru_per_d2h_mb: float = 16.0
    ru_per_mb_s: float = 0.05
    ru_per_read_key: float = 1.0 / 2048.0
    ru_per_request: float = 0.125


@dataclass
class ResourceControlConfig:
    """[resource-control]: multi-tenant enforcement of the RU charges
    ``[resource-metering]`` measures (resource_control.py).  Every
    field is online-updatable and visible in /health and at
    /resource_control.

    ``groups`` maps resource-group names to ``{share, burst,
    priority}`` specs: ``share`` is the group's token-bucket refill
    rate in RU/s (the unit the ru_model prices every measured charge
    in), ``burst`` the bucket cap in RU (0 = 2× share), ``priority``
    one of low/medium/high (high never sheds at the read pool and
    never counts as throttled in the coalescer's DWFQ).  Groups not
    named here get ``default_share``/``default_burst``.  A typo'd
    group key, a non-positive share, or an unknown priority tier
    fails validation (the negative-RU-weight guard applied to group
    specs)."""

    enabled: bool = False
    default_share: float = 500.0
    default_burst: float = 0.0          # 0 = 2x share
    groups: dict = field(default_factory=dict)


@dataclass
class SecurityConfig:
    """[security]: TLS for every gRPC channel (components/security).
    The ONE definition — server/security.py builds its manager from
    this same dataclass."""

    ca_path: str = ""
    cert_path: str = ""
    key_path: str = ""

    @property
    def enabled(self) -> bool:
        return bool(self.ca_path or self.cert_path)


@dataclass
class TikvConfig:
    """The full config tree (config/mod.rs TikvConfig analog)."""

    server: ServerConfig = field(default_factory=ServerConfig)
    storage: StorageConfig = field(default_factory=StorageConfig)
    raftstore: RaftstoreConfig = field(default_factory=RaftstoreConfig)
    coprocessor: CoprocessorConfig = field(
        default_factory=CoprocessorConfig)
    readpool: ReadPoolConfig = field(default_factory=ReadPoolConfig)
    resource_metering: ResourceMeteringConfig = field(
        default_factory=ResourceMeteringConfig)
    resource_control: ResourceControlConfig = field(
        default_factory=ResourceControlConfig)
    security: SecurityConfig = field(default_factory=SecurityConfig)

    @staticmethod
    def from_file(path: str) -> "TikvConfig":
        import tomllib
        with open(path, "rb") as f:
            raw = tomllib.load(f)
        return TikvConfig.from_dict(raw)

    @staticmethod
    def from_dict(raw: dict) -> "TikvConfig":
        cfg = TikvConfig()
        for f in fields(cfg):
            sub = raw.get(f.name.replace("_", "-"), raw.get(f.name))
            if sub is None:
                continue
            target = getattr(cfg, f.name)
            for sf in fields(target):
                key = sf.name.replace("_", "-")
                if key in sub or sf.name in sub:
                    setattr(target, sf.name, sub.get(key, sub.get(sf.name)))
        cfg.validate()
        return cfg

    def validate(self) -> None:
        r = self.raftstore
        if r.raft_heartbeat_ticks >= r.raft_election_timeout_ticks:
            raise ValueError("heartbeat ticks must be < election ticks")
        if r.region_split_size_mb > r.region_max_size_mb:
            raise ValueError("region-split-size must be <= region-max-size")
        if self.readpool.concurrency < 1:
            raise ValueError("readpool concurrency must be >= 1")
        rm = self.resource_metering
        if rm.window_s <= 0:
            raise ValueError("resource-metering window-s must be > 0")
        if rm.topk < 1 or rm.max_resource_groups < 1:
            raise ValueError(
                "resource-metering topk/max-resource-groups must be "
                ">= 1")
        if rm.report_interval_s < 0:
            raise ValueError(
                "resource-metering report-interval-s must be >= 0")
        for f in dataclasses.fields(rm):
            if f.name.startswith("ru_per_") and \
                    getattr(rm, f.name) < 0:
                # a negative weight would DECREMENT RU counters and
                # corrupt every downstream total/report
                raise ValueError(
                    f"resource-metering {f.name} must be >= 0")
        rc = self.resource_control
        if rc.default_share <= 0:
            raise ValueError(
                "resource-control default-share must be > 0")
        if rc.default_burst < 0:
            raise ValueError(
                "resource-control default-burst must be >= 0")
        # group-spec vocabulary guard: a typo'd key, non-positive
        # share, or unknown priority tier fails HERE, never silently
        # mis-configures an enforcement site (resource_control.py
        # owns the one validator both paths share)
        from .resource_control import validate_group_specs
        validate_group_specs(rc.groups)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# fields changeable at runtime ("section.field" — OnlineConfig markers)
_ONLINE_FIELDS = {
    "raftstore.region_split_size_mb",
    "raftstore.region_max_size_mb",
    "raftstore.region_split_check_ticks",
    "raftstore.raft_log_gc_threshold",
    "raftstore.hibernate_regions",
    "coprocessor.device_row_threshold",
    "coprocessor.region_cache_capacity",
    "coprocessor.response_page_rows",
    "coprocessor.tombstone_compact_ratio",
    "coprocessor.device_hbm_budget_mb",
    "coprocessor.coalesce_window_ms",
    "coprocessor.coalesce_max_group",
    "coprocessor.device_cold_build",
    "coprocessor.trace_sample",
    "coprocessor.trace_buffer",
    "coprocessor.slow_log_threshold_ms",
    "coprocessor.flight_recorder_depth",
    "coprocessor.fastpath_classes",
    "coprocessor.dispatch_pipeline",
    "coprocessor.remint_concurrency",
    "readpool.concurrency",
    "resource_metering.window_s",
    "resource_metering.topk",
    "resource_metering.max_resource_groups",
    "resource_metering.report_interval_s",
    "resource_metering.ru_per_launch_s",
    "resource_metering.ru_per_host_s",
    "resource_metering.ru_per_d2h_mb",
    "resource_metering.ru_per_mb_s",
    "resource_metering.ru_per_read_key",
    "resource_metering.ru_per_request",
    "resource_control.enabled",
    "resource_control.default_share",
    "resource_control.default_burst",
    "resource_control.groups",
}


class ConfigController:
    """Live-change router (online_config ConfigController analog).

    Subsystems register a manager callback per section; ``update``
    validates the diff against _ONLINE_FIELDS, applies it to the config
    tree, and dispatches {changed field: value} to the section manager.
    """

    def __init__(self, cfg: TikvConfig):
        self.cfg = cfg
        self._managers: dict[str, Callable[[dict], None]] = {}
        self._lock = threading.Lock()

    def register(self, section: str,
                 manager: Callable[[dict], None]) -> None:
        self._managers[section] = manager

    def update(self, changes: dict) -> dict:
        """changes: {"raftstore.region-split-size-mb": 64, ...} →
        {applied field: value}.  Raises ValueError on unknown or
        non-online fields (nothing is applied)."""
        with self._lock:
            parsed = []
            for dotted, value in changes.items():
                section, _, name = dotted.replace("-", "_").partition(".")
                if not name:
                    raise ValueError(f"bad config key {dotted!r}")
                if f"{section}.{name}" not in _ONLINE_FIELDS:
                    raise ValueError(
                        f"{dotted!r} is not an online-config field")
                target = getattr(self.cfg, section, None)
                if target is None or not hasattr(target, name):
                    raise ValueError(f"unknown config field {dotted!r}")
                cur = getattr(target, name)
                if cur is not None and value is not None and \
                        not isinstance(value, type(cur)):
                    if isinstance(cur, bool) or not (
                            isinstance(cur, (int, float)) and
                            isinstance(value, (int, float))):
                        raise ValueError(
                            f"{dotted!r}: want {type(cur).__name__}")
                parsed.append((section, name, value))
            # validate the tree with changes applied before committing
            # (deep copy: replace() would share the nested sections)
            import copy
            trial = copy.deepcopy(self.cfg)
            for section, name, value in parsed:
                setattr(getattr(trial, section), name, value)
            trial.validate()
            applied: dict = {}
            by_section: dict[str, dict] = {}
            for section, name, value in parsed:
                setattr(getattr(self.cfg, section), name, value)
                applied[f"{section}.{name}"] = value
                by_section.setdefault(section, {})[name] = value
        for section, diff in by_section.items():
            mgr = self._managers.get(section)
            if mgr is not None:
                mgr(diff)
        return applied
