"""Device (TPU) coprocessor backend — fused jit/shard_map pipelines.

This is the north-star slice (SURVEY.md §7, BASELINE.md): the CPU
``BatchExecutor`` hot loop (tidb_query_executors/src/runner.rs:641 —
scan → selection → aggregation per 1024-row batch) becomes ONE fused XLA
computation per plan over the whole HBM-resident feed:

- rows are sharded over the ("range", "tile") mesh (parallel/mesh.py) —
  TiKV's region/bucket sharding mapped to mesh axes;
- the feed is a set of flat padded column arrays cached in HBM across
  requests (the region-cache-engine analog; device/feed.py owns its
  format, build ladder, patch, digests, move and split); row-validity
  for non-NULL columns and the ragged tail is synthesized on device
  from an iota compare, so it never crosses PCIe or burns HBM;
- each request is ONE dispatch: a ``lax.scan`` over row blocks folds the
  aggregation carry on device (RpnExpression evaluation, the filter
  mask, and the aggregate kernels all trace into the same jit, so XLA
  fuses selection into the aggregation's HBM pass);
- the aggregation itself (which kernel body serves a plan, the MXU
  group-by, the cross-shard merge on the interconnect, the finalize) is
  one operator module, device/aggregate.py, as selection, join and MVCC
  resolution are (selection.py, join.py, mvcc.py);
- the result returns in ONE packed uint8 buffer with the D2H transfer
  started asynchronously (r2's per-array readback paid 3+ blocking
  syncs per request; the co-located cost of one sync is ~1-2 ms —
  copr/endpoint.py DEFAULT_DEVICE_ROW_THRESHOLD).

On a 1-device mesh kernels compile as plain jit (no shard_map, no
NamedSharding transfers — a 1-device mesh gains nothing from them;
their co-located dispatch cost: not measured). A SHARDED mesh is a
first-class backend, not a degraded
one: feeds upload row-sharded and delta-PATCH in place
(GSPMD-partitioned dynamic_update_slice, feed.py ``dus``), the fused
Pallas kernel runs as per-shard partial grids psum-merged on ICI
(aggregate.py _pallas_sharded_wrap), selection mask/index routing is
shard-concatenable, and hot regions optionally pin to single-device
slices via the placement loop (device/placement.py) so a
many-small-regions mix scales OUT while a single big feed scales UP.
Host decode never appears on this path: the scan feed is a columnar
snapshot (executors/columnar.py). Small requests stay on the host numpy
path (copr/endpoint.py routing) so p99 latency never pays device
dispatch.

Routing is PER FRAGMENT, not per plan: under the plan IR
(copr/plan_ir.py) this runner serves individual leaf fragments of an
operator DAG — the same request may run its scan+selection here, its
join through the DeviceJoiner (device/join.py, reached via
``joiner()``), and its aggregation finalize on the host pipeline.  The
"whole plan picks one backend" framing this module's routing notes
used to assume holds only for the linear DAGRequest surface; any
degrade decision is now scoped to the fragment that faulted.
"""

from __future__ import annotations

import math
import os
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import native
from ..copr.dag import (
    AggregationDesc,
    DAGRequest,
    IndexScanDesc,
    SelectionDesc,
    TableScanDesc,
    TopNDesc,
)
from ..datatype import Column, ColumnBatch, EvalType
from ..datatype.tile import _device_dtype, code_width
from ..expr import build_rpn
from ..expr.eval import eval_rpn
from ..expr.rpn import RpnExpression
from ..ops.agg import AggSpec
from ..parallel import ROW_AXES, make_mesh, num_shards, row_sharding
from . import lowering, pallas_hash
from .aggregate import DeviceAggregator
from .feed import (
    FeedStore, HostPlanes, anchor as feed_anchor, generation, roll_derived,
)
from .kernels import named_program
from .request import (
    _DEVICE_ETS,
    HOST_STAGER,
    _FallbackToHost,
    _fp_degrade,
    _LanePending,
    _Pending,
    _Plan,
    _Ticket,
    _remap_rpn,
    _rpn_col_indices,
    _rpn_device_safe,
)

# scan-block granularity per kernel kind (rows per lax.scan step; the
# feed pads to a multiple of _FEED_UNIT per shard so any of these divide)
_FEED_BLOCK = 1 << 15


# persistent compile-cache traffic of THIS process, counted from JAX's
# own monitoring events (/health compile_cache): compile requests that
# consulted the cache, hits (executable loaded, compile skipped) and
# writes (entries added).  Process-wide like the cache itself.
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_cache_counts = dict.fromkeys(_CACHE_EVENTS.values(), 0)
_cache_mu = threading.Lock()
_cache_listening = False


def _count_cache_event(event: str, **_kw) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        with _cache_mu:
            _cache_counts[name] += 1


def compile_cache_stats() -> dict:
    with _cache_mu:
        return {"dir": jax.config.jax_compilation_cache_dir,
                **_cache_counts}


def _place_compile_cache() -> None:
    """Persistent XLA compile cache, placed from outside where
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads the variable itself;
    nothing is set here) and otherwise at the fixed
    ``<checkout>/.jax_cache`` — the path is part of the cache key, so it
    is resolved from this package's own location, never from a temp
    name, pid or time.  Runs before the process's first compile (the
    runner's constructor).  The CPU backend is left uncached: its
    entries are AOT results tied to the compiling machine's feature
    list, and XLA logs an error-level mismatch warning on every load
    (JAX 0.9.0), even on the machine that wrote them."""
    global _cache_listening
    if not _cache_listening:
        _cache_listening = True
        jax.monitoring.register_event_listener(_count_cache_event)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            jax.default_backend() == "cpu":
        return
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))


_TIME_ETS = (EvalType.DATETIME, EvalType.DURATION)

# TopN sort-key sentinels (float64 keys; any real data is far inside these)
_EXCLUDED_DESC = -1e308
_NULL_KEY = -1e307          # MySQL: NULL sorts below every value


class _GuardedMeta:
    """Request-scoped view of the shared lineage-anchored memo.

    Reads come from the shared dict only while it still reflects this
    request's snapshot generation (``fresh()``); writes always land in
    a request-local overlay and propagate to the shared dict only while
    fresh — a request (or deferred finalize) racing a newer
    generation's refresh must never repopulate the shared memo with
    stale derived constants (hash bounds, byte-plane widths, sparse
    recodes), which a newer request would then trust.
    """

    __slots__ = ("_meta", "_fresh", "_local")

    def __init__(self, meta: dict, fresh):
        self._meta = meta
        self._fresh = fresh
        self._local: dict = {}

    def __contains__(self, k) -> bool:
        return k in self._local or (self._fresh() and k in self._meta)

    def get(self, k, default=None):
        if k in self._local:
            return self._local[k]
        return self._meta.get(k, default) if self._fresh() else default

    def __getitem__(self, k):
        got = self.get(k, _GuardedMeta)
        if got is _GuardedMeta:
            raise KeyError(k)
        return got

    def __setitem__(self, k, v) -> None:
        self._local[k] = v
        if self._fresh():
            self._meta[k] = v

    def setdefault(self, k, v):
        got = self.get(k, _GuardedMeta)
        if got is not _GuardedMeta:
            return got
        self[k] = v
        return v


_UNSET = object()       # an argument left out, where None says something
# a lane of ``_stage_tickets`` in which a dispatch gate fired (its
# strike is taken): it goes solo, a request alone to the host rung
_FAULT = object()


class DeferredResult:
    """Handle for a device request whose D2H fetch + host finalize have
    not run yet (``DeviceRunner.handle_request(..., deferred=True)``).

    ``result()`` blocks on the transfer, runs the host finalize, and
    memoizes — safe to call from any thread, exactly-once semantics.
    The degrade contract survives deferral: a ``device::*`` failpoint
    (or any _FallbackToHost) firing inside the deferred fetch downgrades
    THIS request to the host pipeline instead of failing it, exactly as
    the synchronous path does.  Any other exception propagates to the
    caller (the endpoint applies its own degrade policy there).
    """

    __slots__ = ("_runner", "_pending", "_dag", "_storage", "_mu",
                 "_memo", "small", "_pin_anchor", "_meter_ctx", "_mesh",
                 "_launch_info")

    def __init__(self, runner, pending: _Pending, dag, storage,
                 pin_anchor=None, meter_ctx=_UNSET):
        self._runner = runner
        self._pending = pending
        self._dag = dag             # original request (host fallback)
        self._storage = storage
        self._mu = threading.Lock()
        self._memo = None
        self.small = pending.small
        # feed-arena pin taken at dispatch; released exactly once when
        # the deferred fetch resolves (eviction must not race the D2H)
        self._pin_anchor = pin_anchor
        # dispatch-time metering context: fetch-side charges (D2H
        # bytes) attribute to the dispatching request/share-group no
        # matter which completion worker runs the fetch
        # (``meter_ctx``: a hold's lanes share the one it read)
        if meter_ctx is _UNSET:
            from .. import resource_metering as rm
            meter_ctx = rm.current_context()
        self._meter_ctx = meter_ctx
        # the mesh the launch ran on: every request that joins this
        # result (a coalesced group's members resolve on their own
        # trackers) is labelled with it; None once a rescue re-served
        # the request elsewhere (that launch labelled its own tracker)
        self._mesh = runner._mesh_desc
        # the launch this thread just made for the request
        # (``_dispatch_phase``'s record): see ``launch_info``
        self._launch_info = runner._take_launch_info()

    @property
    def launch_info(self) -> Optional[dict]:
        """The ``_dispatch_phase`` record of the launch that serves this
        request: ``t0_ns`` / ``t1_ns`` and ``attrs``, the flight-recorder
        entry (plus ``lanes`` and ``lane`` for a lane of a multi-lane
        launch).  The coalescer copies it into the trace of every
        member that did not lead the dispatch, as a ``device_dispatch``
        span outside ``phases_ms``.  None where no launch was made."""
        info = getattr(self._pending, "info", None)     # a lane's
        return info if info is not None else self._launch_info

    def abandon(self) -> None:
        """Give the arena pin back without resolving: a lane whose
        launch failed (its members retry solo, each with its own)."""
        with self._mu:
            anchor, self._pin_anchor = self._pin_anchor, None
        if anchor is not None:
            try:
                self._runner._arena.unpin(anchor)
            except Exception:   # noqa: BLE001
                pass

    def result(self):
        from .. import resource_metering as rm
        from ..utils import tracker
        with self._mu:
            if self._memo is None:
                try:
                    with rm.activate(self._meter_ctx):
                        self._memo = ("ok", self._resolve())
                except BaseException as e:      # noqa: BLE001 — memoized
                    self._memo = ("err", e)
                finally:
                    if self._pin_anchor is not None:
                        try:
                            self._runner._arena.unpin(self._pin_anchor)
                        except Exception:   # noqa: BLE001
                            pass
                        self._pin_anchor = None
            kind, val = self._memo
            mesh = self._mesh
        if kind == "err":
            raise val
        if mesh is not None:
            tracker.label("mesh", mesh)
        return val

    def __del__(self):
        # backstop for an abandoned deferred (completion-pool submit
        # failure, dropped future): the arena pin must not outlive the
        # handle, or the line becomes unevictable under a budget
        if getattr(self, "_pin_anchor", None) is not None:
            try:
                self._runner._arena.unpin(self._pin_anchor)
            except Exception:   # noqa: BLE001 — interpreter teardown
                pass

    def _resolve(self):
        try:
            r = self._runner._finish(self._pending)
        except _FallbackToHost:
            # fetch-side fault: strike the slice's health score, then —
            # if the slice is actually DEAD (quarantined, or the
            # persistent slice_dead fault names it) — rescue the
            # request onto a healthy slice/submesh before falling to
            # the host rung.  The pin release in result()'s finally is
            # untouched either way: exactly-once, never doubled.  The
            # lanes of one launch share its fetch and its fault: one
            # strike for it, not one a lane.
            launch = getattr(self._pending, "launch", None)
            if launch is None or launch.strike_once():
                self._runner._note_slice_fault("fetch")
            self._mesh = None
            rescued = self._runner._rescue(self._dag, self._storage)
            if rescued is not None:
                return rescued
            return self._runner._serve_on_host(self._dag, self._storage,
                                               "fetch")
        return self._runner._apply_output_offsets(self._dag, r)


class _BatchUnavailable(Exception):
    """Raised when a cross-request batched dispatch cannot be served as
    one stacked launch (plan/feed edge case, degrade mid-dispatch).
    The coalescer catches it and retries every member as a SOLO
    dispatch — a failed group must never fail its members."""


class _GroupPending:
    """Shared fetch handle for ONE stacked group dispatch.

    Unlike :class:`DeferredResult` there is no built-in host fallback —
    the raw fetched tree serves N member resolutions, and a member-level
    failure must degrade THAT member (the endpoint's per-request
    contract), never substitute one member's answer for another's.
    ``fetch()`` blocks on the shared D2H once, memoizes, and releases
    the group's arena pin exactly once.
    """

    __slots__ = ("_runner", "_pending", "_mu", "_memo", "_pin_anchor",
                 "_meter_ctx")

    def __init__(self, runner, pending: _Pending, pin_anchor=None):
        self._runner = runner
        self._pending = pending
        self._mu = threading.Lock()
        self._memo = None
        self._pin_anchor = pin_anchor
        # group metering context captured at dispatch: the shared D2H
        # charge splits by occupancy share across member tags from
        # whichever member's completion worker joins the fetch first
        from .. import resource_metering as rm
        self._meter_ctx = rm.current_context()

    def fetch(self):
        from .. import resource_metering as rm
        with self._mu:
            if self._memo is None:
                try:
                    with rm.activate(self._meter_ctx):
                        self._memo = (
                            "ok", self._runner._finish(self._pending))
                except BaseException as e:  # noqa: BLE001 — memoized
                    if isinstance(e, _FallbackToHost):
                        # one strike for the shared fetch, not one per
                        # member resolution (the memo re-raises N times)
                        self._runner._note_slice_fault("fetch")
                    self._memo = ("err", e)
                finally:
                    self._unpin()
            kind, val = self._memo
        if kind == "err":
            raise val
        return val

    def _unpin(self) -> None:
        if self._pin_anchor is not None:
            try:
                self._runner._arena.unpin(self._pin_anchor)
            except Exception:   # noqa: BLE001
                pass
            self._pin_anchor = None

    def __del__(self):
        # abandoned group (every member solo-degraded before fetching):
        # the pin must not outlive the handle
        if getattr(self, "_pin_anchor", None) is not None:
            self._unpin()


class _BatchedSelectionGroup:
    """N per-request resolutions over one stacked selection dispatch.

    ``member_result(i)`` joins the SHARED fetch (one D2H sync for the
    whole group), slices member ``i``'s packed bitmask, seeds that
    member's selectivity EWMA, and runs the member's own host gather
    over its own snapshot — so concurrent members' gathers parallelize
    on the completion pool while the device round trip is paid once.
    """

    __slots__ = ("_runner", "_gp", "_members")

    def __init__(self, runner, gp: _GroupPending, members):
        self._runner = runner
        self._gp = gp
        self._members = members

    def __len__(self) -> int:
        return len(self._members)

    def member_result(self, i: int):
        from ..utils import tracker
        try:
            counts, packed, n = self._gp.fetch()
        except _FallbackToHost:
            # the group's slice died between dispatch and fetch: rescue
            # THIS member on a healthy slice — per member, so no member
            # ever fails (or host-degrades) for a group-mate's fault it
            # could survive; the shared pin was already released
            # exactly once inside the memoized fetch
            dag, storage = self._members[i]
            rescued = self._runner._rescue(dag, storage)
            if rescued is not None:
                return rescued
            raise       # the endpoint's per-member host degrade applies
        dag, storage = self._members[i]
        runner = self._runner
        tracker.label("mesh", runner._mesh_desc)
        plan = runner._analyze(dag)
        k = int(counts[i])
        runner._sel_observe(runner._sel_keys(dag, plan),
                            (k / n) if n else 0.0)
        mask = np.unpackbits(packed[i])[:n].astype(np.bool_)
        with tracker.phase("host_materialize"):
            if isinstance(plan.scan, TableScanDesc) and \
                    hasattr(storage, "gather_rows"):
                out = storage.gather_rows(plan.scan, dag.ranges, mask)
            else:
                b = runner._scan_batch(dag, plan, storage)
                out = b.filter(mask)
        result = runner._result(dag, out.schema, out.columns)
        return runner._apply_output_offsets(dag, result)


class DeviceRunner:
    """Executes supported DAG plans on the device mesh.

    Registered with copr.Endpoint the way coprocessor_v2 plugins register an
    alternate execution backend (coprocessor_plugin_api/src/lib.rs:5-43).
    """

    def __init__(self, mesh=None, chunk_rows: Optional[int] = None,
                 max_hash_capacity: int = 1 << 20,
                 max_topn_limit: int = 1 << 14,
                 hbm_budget_bytes: int = 0,
                 placement: bool = False,
                 placement_rows: Optional[int] = None,
                 slice_trip_strikes: Optional[float] = None,
                 slice_probe_cooldown_s: Optional[float] = None,
                 slice_latency_outlier_s: Optional[float] = None,
                 flight_recorder_depth: Optional[int] = None):
        # int64 accumulators are required for exact SUM/COUNT over 1e8
        # rows; jax defaults to 32-bit.  Values stay int32/float32 on
        # device, only accumulators widen.  (Set here, not at import, so
        # importing the package has no process-global side effect.)
        jax.config.update("jax_enable_x64", True)
        _place_compile_cache()
        # the request spans that are work on some thread go to the JAX
        # profiler as ``copr:<span>`` annotations, on the device trace's
        # clock (utils/trace.py itself imports no JAX; an annotation
        # outside a profiler session costs one flag test)
        from ..utils import trace
        trace.set_annotator(jax.profiler.TraceAnnotation)
        self._mesh = mesh if mesh is not None else make_mesh()
        self._max_hash_capacity = max_hash_capacity
        self._max_topn_limit = max_topn_limit
        self._row_sharding = row_sharding(self._mesh)
        self._repl = NamedSharding(self._mesh, P())
        # Single-device: plain jit + uncommitted arrays.  A 1-device
        # mesh gains nothing from explicit NamedSharding transfers and
        # shard_map wrappers.  On more than one device whatever a
        # sharded program takes has to lie where the program declares
        # it BEFORE the call: an uncommitted array on one chip is
        # re-laid by JAX's Python argument path on every launch, under
        # the dispatch lock (the four-chip trace: 1.2 ms an argument;
        # PERF.md sections 3 and 6, PR 30).  ``_repl`` is where the
        # cached scalars go (_scalar_cache_get), ``_row_sharding``
        # where the feeds do.
        self._single = num_shards(self._mesh) == 1
        dev0 = self._mesh.devices.flat[0]
        self._pin_device = dev0 \
            if self._single and dev0 != jax.devices()[0] else None
        # scan-block granularity (rows per shard per lax.scan step); the
        # chunk_rows override shrinks it so tests drive multi-step scans
        # on tiny fixtures
        S = num_shards(self._mesh)
        self._is_tpu = self._mesh.devices.flat[0].platform == "tpu"
        if chunk_rows is None:
            # feeds pad to the Pallas block so the fused hash kernel
            # (pallas_hash.BLOCK rows/grid step, or a power-of-two part
            # of it on a grid past 4,096 slots: pallas_hash.block_rows)
            # divides the feed — per SHARD on a sharded TPU mesh, since
            # the sharded fast path runs the same kernel per shard
            # before the tree-reduce; the XLA scan paths gcd down from
            # this.  A sharded CPU
            # mesh (virtual-device parity tests) keeps the smaller
            # unit: no Mosaic lowering exists there and 8×2^18-row
            # minimum pads would swamp the fixtures.
            from .pallas_hash import BLOCK as _PL_BLOCK
            self._block_local = _PL_BLOCK \
                if (self._single or self._is_tpu) else _FEED_BLOCK
            self._chunk_override = False
        else:
            self._block_local = max(8, ((max(chunk_rows, 8) // S) // 8) * 8)
            self._chunk_override = True
        self._init_args = {"chunk_rows": chunk_rows,
                           "max_hash_capacity": max_hash_capacity,
                           "max_topn_limit": max_topn_limit}
        # -- chip failure domains (device/supervisor.py SliceHealth) --
        # The whole-mesh runner owns ONE health board covering its
        # slices; per-slice sub-runners (placement) and degraded
        # submesh runners strike the SAME board through these links:
        #   _health          this runner IS one slice (placement slice)
        #   _failover_parent the runner whose front door serves rescues
        #   _slice_indices   the PARENT-mesh flat indices of my devices
        #                    (what device::slice_dead's argument names)
        self._health = None
        self._failover_parent = None
        self._slice_indices = tuple(range(num_shards(self._mesh)))
        from .supervisor import (
            DEFAULT_PROBE_COOLDOWN_S,
            DEFAULT_TRIP_STRIKES,
            SliceHealthBoard,
        )
        self._board = SliceHealthBoard(
            num_shards(self._mesh),
            trip_strikes=slice_trip_strikes
            if slice_trip_strikes is not None else DEFAULT_TRIP_STRIKES,
            cooldown_s=slice_probe_cooldown_s
            if slice_probe_cooldown_s is not None
            else DEFAULT_PROBE_COOLDOWN_S,
            latency_outlier_s=slice_latency_outlier_s) \
            if not self._single else None
        # elastic mesh degrade: (frozenset(dead slices), sub-runner)
        # serving whole-mesh plans on the largest healthy submesh while
        # a chip is quarantined; None = full mesh healthy
        self._degraded: Optional[tuple] = None
        self._degrade_mu = threading.Lock()
        # times whole-mesh serving was re-minted on a submesh (monotone;
        # /health device_mesh): a run that saw one did not stay on the
        # mesh it was configured with
        self._submesh_rebuilds = 0
        # keyed by const-SENSITIVE plan_key: rotating constants mint a
        # fresh analysis each, so the cache is bounded (FIFO) — the
        # const-blind kernel caches below are what keep compile classes
        # logarithmic; this only memoizes the host-side plan walk
        self._plan_cache: dict = {}
        self._plan_cache_max = 4096
        self._kernel_cache: dict = {}
        # the aggregation operator (device/aggregate.py), over this
        # runner's feeds, caches and dispatch span
        self._aggregator = DeviceAggregator(self)
        # the feeds (device/feed.py): their format, build ladder, patch,
        # digests, move and split, in this runner's arena
        self._feeds = FeedStore(self)
        # dispatch serialization: two threads launching multi-device
        # executables concurrently can interleave their per-device
        # enqueues and deadlock the mesh (launch-order inversion), and
        # the cache dicts below are not thread-safe.  The lock spans
        # enqueue AND any cold work a request needs first (feed
        # upload, kernel build/compile) — warm requests hold it for
        # ~µs, but a request that goes cold serializes its peers
        # behind the rebuild; a deliberate simplicity tradeoff, since
        # cold builds are once-per-(data version, plan).  D2H fetches —
        # the expensive part the async serving path overlaps — always
        # block OUTSIDE it.
        self._dispatch_mu = threading.Lock()
        # per thread: the last launch's record (_take_launch_info)
        self._launched = threading.local()
        from collections import OrderedDict
        self._scalar_cache: "OrderedDict" = OrderedDict()
        # per-plan observed-selectivity EWMAs + aggregate route counts
        # (selection.py routing); LRU-bounded like the scalar cache
        self._sel_mu = threading.Lock()
        self._sel_stats: "OrderedDict" = OrderedDict()
        self._sel_route_counts: dict = {}
        # HBM-resident feed cache — the TPU-native analog of TiKV's
        # in-memory region cache engine (components/
        # region_cache_memory_engine: RangeCacheMemoryEngine layered over
        # RocksDB).  Owned EXPLICITLY by the feed arena (device/
        # supervisor.py): per-anchor byte accounting, a configurable HBM
        # budget with frequency+recency eviction, and drop_feed teardown
        # driven by region lifecycle events — reclamation no longer
        # depends on GC timing.
        from .supervisor import FeedArena
        self._arena = FeedArena(budget_bytes=hbm_budget_bytes)
        # device flight recorder (device/supervisor.py): bounded ring
        # of recent launches feeding the device_dispatch span's attrs
        # and the status server's /debug/trace surface.  One ring per
        # PHYSICAL runner — slice/submesh sub-runners share it
        # (_make_slice_runner), so the chip's launch history reads in
        # order with per-entry slice ids.
        from .supervisor import (
            DEFAULT_FLIGHT_RECORDER_DEPTH,
            FlightRecorder,
        )
        self.flight_recorder = FlightRecorder(
            flight_recorder_depth if flight_recorder_depth is not None
            else DEFAULT_FLIGHT_RECORDER_DEPTH)
        self._mesh_desc = "x".join(
            str(d) for d in self._mesh.devices.shape)
        # scrub-quarantined anchors: id(anchor) -> (anchor, reason).
        # The next request for a quarantined anchor serves from the
        # host pipeline (its feeds are already dropped); the one after
        # re-uploads from host truth.  Own lock: the background scrub
        # thread quarantines while request threads consume/drop.
        self._quarantined: dict = {}
        self._quar_mu = threading.Lock()
        # record per-plane content digests at feed build/patch time so
        # the background scrubber can audit resident planes against them
        self.scrub_digests = True
        # device-side MVCC resolution (device/mvcc.py): lazily built —
        # host-only deployments and sharded meshes never pay for it
        self._mvcc_resolver = None
        # plan-IR join/sort/window kernels (device/join.py): lazily
        # built — DAG-only deployments never pay for it.  Single-device
        # by construction (the build dictionary commits to one chip);
        # multi-chip nodes reach it through their placement slices.
        self._joiner = None
        # hot-region → slice placement (device/placement.py): sharded
        # meshes opt in to scale-OUT routing — small regions pin to
        # single-device sub-runners spread by load, large feeds still
        # shard over the whole mesh.  Off by default: single-chip
        # deployments and whole-mesh benches skip the indirection.
        self._placer = None
        if placement and not self._single:
            from .placement import DEFAULT_WHOLE_MESH_ROWS, SlicePlacer
            self._placer = SlicePlacer(
                self, whole_mesh_rows=placement_rows
                if placement_rows is not None
                else DEFAULT_WHOLE_MESH_ROWS)
        from ..utils.metrics import DEVICE_MESH_SHARDS
        DEVICE_MESH_SHARDS.set(num_shards(self._mesh))

    def _make_slice_runner(self, mesh, slice_indices=None,
                           bind_health: bool = False) -> "DeviceRunner":
        """A sub-runner over a subset of this runner's chips: one
        placement slice (single device) or a degraded healthy submesh.
        Tuned like the parent (chunk override, capacities); the placer
        owns per-slice HBM budget splits.  ``slice_indices`` are the
        PARENT-mesh flat indices of ``mesh``'s devices — the identity
        ``device::slice_dead`` targets and the health board scores; the
        sub-runner strikes the parent's board, never a private one.

        ``bind_health`` (placement slices only): attribute this
        runner's per-request faults/latency to its slice's score.  A
        DEGRADED submesh runner must NOT bind even at 1 device — its
        requests are whole-mesh plans squeezed onto survivors, whose
        inherently-higher latency would strike (and eventually condemn)
        the last healthy chip for doing its job."""
        sub = DeviceRunner(mesh=mesh, **self._init_args)
        sub._failover_parent = self
        # the PARENT's flight recorder records this slice's launches
        # (entries carry the slice id) — one black box per chip
        sub.flight_recorder = self.flight_recorder
        if slice_indices is not None:
            sub._slice_indices = tuple(slice_indices)
            if bind_health and len(slice_indices) == 1 and \
                    self._board is not None:
                sub._health = self._board.slice(slice_indices[0])
        # one board per PHYSICAL mesh: the sub-runner must not route
        # its own degrade ladder — the parent owns that decision
        sub._board = None
        return sub

    @property
    def placer(self):
        return self._placer

    # ------------------------------------------------ chip failure domains
    #
    # Each mesh slice is a failure domain, scored like PR 3 scores a
    # store (device/supervisor.py SliceHealth): dispatch faults, fetch
    # faults, scrub quarantines and launch-latency outliers strike; a
    # tripped slice is quarantined — placement drains its anchors,
    # whole-mesh sharded plans rebuild on the largest healthy submesh
    # (8→4→2→1; parallel.mesh.healthy_submesh), in-flight work rescues
    # onto survivors — and a half-open canary re-admits it.  Host is
    # the degrade ladder's FINAL rung only.

    def _strike_board(self):
        """The board slice-attributable faults land on: my own for the
        whole-mesh runner, the parent's for slice/submesh runners
        (``_health`` owners strike through the outer fault handler
        instead, so one request never double-counts)."""
        if self._board is not None:
            return self._board
        p = self._failover_parent
        return p._board if p is not None else None

    def _slice_dead_targets(self, indices=None) -> tuple:
        """My slice indices the ``device::slice_dead`` failpoint
        currently names, () when unarmed.  Argument grammar:
        ``return(i)`` / ``return(i j)`` kills specific slices, a bare
        ``return`` kills every slice (whole-device death); percent
        prefixes make the chip FLAP instead of staying dead."""
        from ..utils.failpoint import fail_point
        fp = fail_point("device::slice_dead")
        if fp is None:
            return ()
        mine = tuple(indices) if indices is not None \
            else self._slice_indices
        v = getattr(fp, "value", None)
        if v is None or not str(v).strip():
            return mine
        try:
            targets = {int(t) for t in
                       str(v).replace(",", " ").split()}
        except ValueError:
            return mine
        return tuple(i for i in mine if i in targets)

    def _note_slice_fault(self, kind: str) -> None:
        if self._health is not None:
            if self._health.note_fault(kind):
                board = self._strike_board()
                if board is not None:
                    board._fire_trip(self._health.idx, kind)

    def _note_slice_ok(self, latency_s: Optional[float] = None) -> None:
        h = self._health
        if h is not None:
            if h.note_ok(latency_s):
                # a latency-outlier strike can be the tripping one:
                # the drain/degrade listeners must fire for it exactly
                # as for a hard fault
                board = self._strike_board()
                if board is not None:
                    board._fire_trip(h.idx, "latency")
            return
        # whole-mesh / degraded-submesh runner: a served sharded
        # request ran on EVERY one of my slices — decay them all, so a
        # re-admitted chip earns its score back under mesh traffic too
        # (latency stays None here: a whole-mesh round trip cannot
        # attribute an outlier to one chip, and striking all of them
        # would let one slow request condemn the entire mesh)
        board = self._strike_board()
        if board is not None:
            for i in self._slice_indices:
                board.slice(i).note_ok()

    def _refuse_if_quarantined(self) -> bool:
        """Early dispatch gate: a QUARANTINED slice refuses the request
        before it touches ANY per-slice state (no arena bucket, no feed
        upload, no launch — launching on a dead chip would hang the
        stream; check_no_quarantined_dispatch counts on this gate).
        → True when the caller must serve from the host pipeline."""
        from ..utils import metrics as m
        h = self._health
        if h is not None and h.quarantined():
            h.refusals += 1
            m.DEVICE_FAILOVER_COUNTER.labels("refused_dispatch").inc()
            return True
        return False

    def _preflight_slice(self) -> None:
        """Dispatch-site gate: a slice the ``device::slice_dead``
        failpoint names fails the dispatch the way the dead chip
        would (the quarantine refusal ran earlier, before any
        per-slice state was touched)."""
        hit = self._slice_dead_targets()
        if hit:
            if self._health is None:
                board = self._strike_board()
                if board is not None:
                    for i in hit:
                        board.note_fault(i, "dispatch")
            # _health owners strike once in the outer fault handler
            raise _FallbackToHost("device::slice_dead")

    def _canary(self, idx: int) -> bool:
        """One cheap half-open probe of slice ``idx``: a trivial
        committed computation through the real runtime, gated by the
        same slice_dead failpoint a live dispatch would hit — a
        persistently-dead chip keeps failing its canary until the
        fault lifts."""
        try:
            if self._slice_dead_targets(indices=(idx,)):
                return False
            pos = self._slice_indices.index(idx) \
                if idx in self._slice_indices else idx
            dev = self._mesh.devices.flat[pos]
            x = jax.device_put(np.arange(8, dtype=np.int64), dev)
            return int(np.asarray(jnp.sum(x))) == 28
        except Exception:   # noqa: BLE001 — any runtime error = dead
            return False

    def probe_quarantined(self) -> int:
        """Half-open probing for quarantined slices (the supervisor's
        scrub loop and the routing paths call this opportunistically;
        the board's per-slice cooldown + single-probe gate bound the
        work).  → probes run."""
        if self._board is None:
            return 0
        return self._board.maybe_probe(self._canary)

    def _degraded_sub(self) -> Optional["DeviceRunner"]:
        """Locked snapshot of the current degraded-submesh runner (the
        one surface stats/budget/teardown fold it through), or None."""
        with self._degrade_mu:
            return self._degraded[1] if self._degraded is not None \
                else None

    def _sub_runners(self) -> list:
        """The runners under this one that hold feeds of their own: the
        placer's slices and any degraded submesh runner."""
        subs = list(self._placer.slices) if self._placer is not None \
            else []
        degraded = self._degraded_sub()
        if degraded is not None:
            subs.append(degraded)
        return subs

    def _degraded_target(self) -> Optional["DeviceRunner"]:
        """The runner whole-mesh plans should use right now: a sub-
        runner over the largest healthy submesh while any slice is
        quarantined (8→4→2→1 — re-minting sharded feeds from host
        truth onto the survivors), self's own mesh when healthy.
        Raises _FallbackToHost when no healthy submesh exists or the
        rebuild itself faults (``device::mesh_rebuild``) — host is the
        final rung of the ladder, never the first."""
        board = self._board
        if board is None:
            return None
        self.probe_quarantined()
        dead = board.quarantined_set()
        from ..utils import metrics as m
        from ..utils import tracker
        with self._degrade_mu:
            if not dead:
                if self._degraded is not None:
                    # every slice re-admitted: the full mesh takes over
                    # and the submesh feeds release their HBM (the full
                    # mesh re-mints from host truth on first touch)
                    old = self._degraded[1]
                    self._degraded = None
                    old._arena.drop_all(reason="drop")
                    m.DEVICE_FAILOVER_COUNTER.labels(
                        "mesh_restore").inc()
                return None
            key = frozenset(dead)
            if self._degraded is None or self._degraded[0] != key:
                _fp_degrade("device::mesh_rebuild")
                from ..parallel import healthy_submesh
                devs = healthy_submesh(self._mesh, dead)
                if devs is None:
                    raise _FallbackToHost("no healthy submesh")
                flat = list(self._mesh.devices.flat)
                gidx = tuple(flat.index(d) for d in devs)
                with tracker.phase("mesh_rebuild"):
                    sub = self._make_slice_runner(
                        make_mesh(devs), slice_indices=gidx)
                    sub._arena.budget_bytes = self._arena.budget_bytes
                if self._degraded is not None:
                    self._degraded[1]._arena.drop_all(reason="failover")
                # the full-mesh feeds span the dead chip — useless now;
                # in-flight dispatches keep their own buffer references
                self._arena.drop_all(reason="failover")
                self._degraded = (key, sub)
                self._submesh_rebuilds += 1
                m.DEVICE_FAILOVER_COUNTER.labels("mesh_downsize").inc()
            return self._degraded[1]

    def _rescue(self, dag: DAGRequest, storage):
        """In-flight rescue: a request whose slice died between
        dispatch and fetch retries ONCE through the failover root's
        front door — the placer re-pins its anchor onto a healthy
        slice, or the degraded submesh serves it — instead of burning
        the host rung on a provably-dead chip.  → a finished
        SelectResult, or None when this runner is not actually sick
        (the ordinary host-degrade contract then applies unchanged).
        Never touches this runner's pins: the caller's exactly-once
        unpin discipline stands."""
        from ..utils import metrics as m
        from ..utils import tracker
        try:
            h = self._health
            hit = self._slice_dead_targets()
            sick = h is not None and h.quarantined()
            if hit:
                sick = True
                if h is not None:
                    # a targeted persistent death needs no three-strike
                    # deliberation: trip now so the placer drains and
                    # the retry routes around this slice
                    board = self._strike_board()
                    if h.trip("slice_dead") and board is not None:
                        board._fire_trip(h.idx, "slice_dead")
                else:
                    board = self._strike_board()
                    if board is not None:
                        for i in hit:
                            board.trip(i, "slice_dead")
            if not sick and self._board is not None and \
                    self._board.quarantined_set():
                sick = True     # mesh already degraded: reroute
            if not sick:
                return None
            target = self._failover_parent
            if target is None:
                target = self if self._board is not None else None
            if target is None:
                return None
            m.DEVICE_FAILOVER_COUNTER.labels("rescue").inc()
            tracker.label("device_rescue", "slice_failover")
            return target.handle_request(dag, storage)
        except Exception:   # noqa: BLE001 — rescue is best-effort;
            return None     # the host rung follows

    def _live_mesh(self) -> tuple:
        """(runner, dead slices) that whole-mesh plans are served by
        right now: the degraded submesh runner while a slice is
        quarantined, self and () otherwise.  The one place /health's
        ``device_mesh`` and ``device_health`` blocks read it from."""
        with self._degrade_mu:
            if self._degraded is not None:
                dead, sub = self._degraded
                return sub, tuple(sorted(dead))
        return self, ()

    def failure_domain_stats(self) -> dict:
        """Per-slice health + degrade rollup (/health device_health)."""
        out: dict = {"n_slices": len(self._slice_indices),
                     "slices": self._board.stats()
                     if self._board is not None else []}
        live, dead = self._live_mesh()
        if live is not self:
            out["degraded"] = {
                "dead_slices": list(dead),
                "healthy_devices": num_shards(live._mesh)}
        return out

    def close(self) -> None:
        """Teardown: drop every device-resident line (node.stop()
        orders this after the endpoint/completion pool drain, so pins
        are already released), retire any degraded submesh runner, and
        clear quarantine state — an in-process restart starts clean
        with no leaked HBM accounting.  Idempotent."""
        if self._placer is not None:
            for r in self._placer.slices:
                r.close()
        with self._degrade_mu:
            if self._degraded is not None:
                self._degraded[1].close()
                self._degraded = None
        self._arena.drop_all(reason="drop")
        with self._quar_mu:
            self._quarantined.clear()
        if self._board is not None:
            self._board.reset()

    def mesh_stats(self) -> dict:
        """Mesh rollup for /health ``device_mesh``: the configured
        shape, the mesh whole-mesh plans are served on right now
        (``live``: a submesh while a slice is quarantined), what ran on
        it (``sharded_launches``: launches over every device of the
        configured mesh, beside the flight recorder's ``launches``;
        ``submesh_rebuilds``; ``finalize``: Pallas hash accumulators
        finalized by the one native call or by the numpy chain, and
        whether the extension built; ``scalar_cache``: look-ups of the
        cached device scalars that found the value on the device
        (``hits``) or had to put it there (``uploads``), sub-runners'
        included: a warm launch only hits; ``agg_params``: launches
        that carried an aggregation's constants as kernel operands,
        const-blind kernel entries built, scaled-DECIMAL and int32-date
        planes cut (``FlightRecorder.agg_param_counts``); ``prepared``:
        warm whole-feed Pallas launches staged from their class's
        prepared record, ``hits`` a lane, the records written
        (``builds``) and dropped, by cause (``drops``:
        ``FlightRecorder.prepared_counts``); ``feed``: resident feeds
        a write left behind, patched forward (``patches``, the dirty
        ``patch_rows``, the widened windows by bucket length, their
        count ``patch_windows`` and the device programs that wrote them,
        ``patch_programs``: one a window) or built
        again, by cause (``rebuilds_after_delta``) and by where the rows
        came from (``rebuild_source``: the ``host``'s planes, or the
        resident feed compacted on the ``device``: ``compact_rows`` dead
        rows removed by ``compact_programs``), and both together
        (``after_delta``: ``FlightRecorder.feed_counts``), and the
        feeds held now with their planes' bytes (``resident_feeds``,
        ``resident_bytes``: ``FeedArena.feed_residency``); ``memo``:
        request memos whose derived record was rolled across a write,
        ``kept`` or ``dropped`` by cause, and their ``host_planes``
        (``deferred``: kept with the tombstones they lag by, ``cut``
        where next read, or ``dropped``;
        ``FlightRecorder.memo_counts``: feed.py ``roll_derived``);
        ``lanes``:
        this runner's
        launches of lanes, ``DeviceAggregator.lane_stats``; all
        monotone), the resident
        bytes of the live mesh's fullest shard, and the placement
        rollup."""
        shape = dict(zip(ROW_AXES,
                         (int(s) for s in self._mesh.devices.shape)))
        dev0 = self._mesh.devices.flat[0]
        live, _dead = self._live_mesh()
        out = {"shape": shape,
               "n_devices": num_shards(self._mesh),
               "platform": dev0.platform,
               "device_kind": dev0.device_kind,
               "live": {"shape": live._mesh_desc,
                        "n_devices": num_shards(live._mesh)},
               "sharded_launches": self.flight_recorder.sharded_launches,
               "finalize": {
                   **self.flight_recorder.finalize_counts(),
                   "native_available":
                       native.hash_finalize_packed is not None},
               "scalar_cache": self.flight_recorder.scalar_counts(),
               "agg_params": self.flight_recorder.agg_param_counts(),
               "prepared": self.flight_recorder.prepared_counts(),
               "feed": {**self.flight_recorder.feed_counts(),
                        **self._feed_residency()},
               "memo": self.flight_recorder.memo_counts(),
               "lanes": self.lane_stats(),
               "submesh_rebuilds": self._submesh_rebuilds,
               "feed_bytes_per_shard": max(
                   live._arena.resident_bytes_by_device().values(),
                   default=0)}
        if self._placer is not None:
            out["placement"] = self._placer.stats()
        return out

    def _feed_residency(self) -> dict:
        """``FeedArena.feed_residency`` of this runner and of its
        placement slices, added up."""
        feeds = nbytes = 0
        for r in (self, *(self._placer.slices
                          if self._placer is not None else ())):
            f, b = r._arena.feed_residency()
            feeds, nbytes = feeds + f, nbytes + b
        return {"resident_feeds": feeds, "resident_bytes": nbytes}

    def lane_stats(self) -> dict:
        """``DeviceAggregator.lane_stats`` of this runner and of its
        placement slices (a lane launch runs where its lines live),
        added up."""
        out = self._aggregator.lane_stats()
        for sub in (self._placer.slices if self._placer is not None
                    else ()):
            for k, v in sub._aggregator.lane_stats().items():
                if isinstance(v, dict):
                    for kk, n in v.items():
                        out[k][kk] = out[k].get(kk, 0) + n
                else:
                    out[k] = max(out[k], v) if k == "longest_build_s" \
                        else out[k] + v
        return out

    def mvcc_resolver(self, create: bool = True):
        """The runner's DeviceMvccResolver (the cold-path kill: flat
        CF_WRITE planes resolve newest-version-≤-read_ts on device and
        the feed is born resident).  Single-device only — a sharded
        mesh's cold builds keep the host upload pipeline (the resolve
        output is committed to one chip; re-laying it across shards
        would pay the D2H+H2D the device build exists to avoid)."""
        if self._mvcc_resolver is None and create and self._single:
            from .mvcc import DeviceMvccResolver
            self._mvcc_resolver = DeviceMvccResolver(self)
        return self._mvcc_resolver

    def joiner(self) -> "object":
        """The runner's DeviceJoiner (plan-IR join/sort/window kernels,
        device/join.py).  Single-device runners only — a whole-mesh
        sharded runner's joins route host or to a placement slice (the
        plan executor owns that choice)."""
        if self._joiner is None:
            from .join import DeviceJoiner
            self._joiner = DeviceJoiner(self)
            if self._arena.budget_bytes > 0:
                # a budget set before the joiner existed binds it too
                self._joiner.set_budget(self._arena.budget_bytes // 8)
        return self._joiner

    # ------------------------------------------------------------------ plan

    def supports(self, dag: DAGRequest) -> bool:
        return self._analyze(dag) is not None

    def profitable(self, dag: DAGRequest) -> bool:
        """Should auto-routing pick the device for this plan?

        Aggregations and TopN reduce on device (tiny D2H readback) and
        measure far above the host path.  Selections ride the device
        too since the late-materialization pass (selection.py): the
        predicate evaluates over the resident HBM feed and only a
        COMPACT selection vector crosses D2H — n/8 bytes of packed
        bitmask, 4·K bytes of compacted indices, or K rows of compacted
        low-width columns, whichever the router's cost model picks —
        so a selection's transfer now scales with SELECTED rows, not
        scanned rows.  The remaining selection→host case is
        selectivity-driven, not structural: past ~95% observed
        selectivity (per-plan EWMA seeded by the device-side count) the
        shared k-row materialization dominates both paths and the host
        pipeline answers without the dispatch round trip; periodic
        re-probes rediscover workloads whose selectivity drifts back
        down.  The SIZE crossover lives in
        Endpoint.device_row_threshold (rationale there) — and under
        concurrency it is a conservative bound, since the request
        coalescer (server/coalescer.py) amortizes the launch + D2H
        sync this gate exists to avoid paying per-request: the cost
        router in front of the device backend re-decides per request
        with the fixed tax divided by group occupancy.
        force_backend="device" still runs declined shapes for parity
        testing, and a forced/direct call always dispatches the real
        kernels regardless of the EWMA.
        """
        plan = self._analyze(dag)
        if plan is None:
            return False
        if plan.kind == "scan_sel":
            return bool(plan.sel_rpns) and \
                self._sel_allows_device(self._sel_keys(dag, plan))
        return plan.kind in ("simple_agg", "hash_agg", "topn")

    # -- cross-request batching (server/coalescer.py) --

    def batch_class(self, dag: DAGRequest, storage):
        """Coalescing identity for this request, or None if it cannot
        share a dispatch.

        Two requests grouped under the same key are served by ONE
        device launch.  ``("stack", ...)`` keys mark selections whose
        predicate constants are hoisted into traced scalar params
        (selection.split_params): differing thresholds within one
        const-blind ``shape_key`` stack as a leading axis of the params
        and evaluate in one vmapped dispatch.  ``("share", ...)`` keys
        mark byte-identical plans (same exact ``plan_key``, incl.
        output offsets): one dispatch + one fetch serves every member
        (the thundering-herd dashboard-query case) — aggregations and
        param-less selections batch this way.  Either way the members
        must target a CO-RESIDENT feed: same anchor (snapshot /
        lineage identity), same data generation, same ranges.

        The stacked kernel itself is single-device, but a sharded mesh
        is no longer excluded: with placement on, the request routes
        to its anchor's single-device SLICE and coalesces there (the
        slice id joins the key so groups never straddle chips); only
        whole-mesh sharded dispatches — already launch-amortized by
        GSPMD — stay uncoalesced.

        A key says who shares a RESULT.  Who shares a LAUNCH is wider:
        closed ``share`` groups over different anchors, versions or
        ranges whose ``launch_class`` is equal leave as the lanes of one
        program (``handle_lanes``), each with its own answer.
        """
        if not hasattr(storage, "scan_columns"):
            return None
        if self._placer is not None:
            target = self._placer.route(storage)
            if target is not self:
                key = target.batch_class(dag, storage)
                return None if key is None \
                    else ("slice", id(target)) + key
        if not self._single:
            return None
        plan = self._analyze(dag)
        if plan is None:
            return None
        anchor = feed_anchor(storage)
        _lineage, req_v = generation(storage)
        if plan.kind == "scan_sel" and plan.sel_rpns:
            if plan.sel_params is None:
                from . import selection as selmod
                plan.sel_params = selmod.split_params(
                    plan.sel_rpns, len(plan.used_cols))
            _rpns, _vals, dts = plan.sel_params
            if dts:
                from .selection import shape_key
                return ("stack", id(anchor), req_v, shape_key(plan),
                        dts, dag.ranges, dag.output_offsets)
        if plan.kind in ("simple_agg", "hash_agg", "topn", "scan_sel"):
            return ("share", id(anchor), req_v, dag.plan_key(),
                    dag.ranges)
        return None

    def launch_class(self, key, dag: DAGRequest, storage):
        """What a closed group's launch can be fused on, or None where
        it leaves alone.  ``key`` is the group's ``batch_class`` key,
        ``dag`` / ``storage`` its lead member's.

        Two groups of one launch class are LANES of one launch: the
        same runner (slice), the same plan and the same kernel compile
        class (``n_pad`` bucket, dtypes, ``capacity``, slot mode,
        ``arg_nbytes``), which is the kernel cache key of the prepared
        record the request's last whole-feed Pallas launch left in its
        memo (``DeviceAggregator._try_pallas``, ``_Prepared.key``; one
        memo a line: two generations of a line are two lanes of one
        class, and a refresh drops it until the next launch).  Only a
        ``share`` group of an aggregation (GROUP BY or not) that the
        Pallas body has already served whole has one (the class is
        const-blind: groups that differ in their constants alone are
        lanes of one launch, each with its own operands): a ``stack`` group, another plan
        kind, a mesh, a bucket-tile request (its ranges have no memo of
        their own), a cold or refreshed line take today's path.  The
        class only decides who is staged together; each lane's kernel
        is held to the cache again when it is staged, and lanes that
        turn out to differ leave as launches of their own."""
        ticket = self.launch_ticket(key, dag, storage)
        return None if ticket is None else ticket.klass

    def launch_ticket(self, key, dag: DAGRequest, storage):
        """``launch_class``, and with the class everything that was
        resolved to find it: the group's TICKET (``request._Ticket``:
        the runner, the lead's plan and operands, the line's bucket and
        memo, the record, the generation), which the coalescer keeps
        beside the class and hands back with the lane
        (``handle_lanes`` / ``handle_request``), so that a prepared
        hit is staged without a second look-up.  None where
        ``launch_class`` is None.  Nothing of the arena is touched or
        locked here (``FeedArena.peek``)."""
        prefix = ()
        if key[0] == "slice":
            prefix, key = key[:2], key[2:]
        if key[0] != "share":
            return None
        runner = self
        if self._placer is not None:
            runner = self._placer.route(storage)
            if (id(runner) != prefix[1]) if prefix else runner is not self:
                return None
        ticket = runner._ticket_of(dag, storage)
        if ticket is not None and prefix:
            ticket.klass = prefix + ticket.klass
        return ticket

    def _ticket_of(self, dag: DAGRequest, storage) -> Optional[_Ticket]:
        """The ticket of ONE request on this runner: the record its memo
        holds under ITS OWN plan, ranges and line, as sent (a tiled
        request's memo lies under the whole region's ranges, a mesh
        builds no record, a cold or refreshed line has none: no ticket),
        with what a staging from it needs of the request."""
        if not self._single:
            return None
        plan = self._analyze(dag)
        if plan is None or plan.kind not in ("hash_agg", "simple_agg"):
            return None
        anchor = feed_anchor(storage)
        bucket = self._arena.peek(anchor)
        meta = bucket.get(("meta", self._meta_key(dag, plan))) \
            if bucket is not None else None
        rec = meta.get("prepared") if meta else None
        if rec is None:
            return None
        return self._ticket(plan, anchor, bucket, meta, rec,
                            *generation(storage))

    def _ticket(self, plan, anchor, bucket, meta, rec, lineage,
                req_v) -> _Ticket:
        if rec.limbs:
            plan = self._limb_variant(plan, rec.limbs)
        _sel, _aggs, pvals, pdts = pallas_hash.plan_params(plan)
        return _Ticket(rec.key, self, tuple(pvals), tuple(pdts), anchor,
                       bucket, meta, rec, lineage, req_v)

    def lanes_ready(self, klass, storage) -> bool:
        """Whether groups of launch class ``klass`` over DIFFERENT
        feeds can leave together yet: the kernel has a built lane
        program (``DeviceAggregator.lanes_ready``: they are built
        beside the kernel itself, off the serving threads, and until
        one is there such groups leave one by one, as before).
        ``storage``:
        a lead member's, for the slice the class lives on."""
        runner = self
        if klass[0] == "slice":
            klass = klass[2:]
            if self._placer is not None:
                runner = self._placer.route(storage)
        return runner._aggregator.lanes_ready(klass)

    def handle_lanes(self, lanes, tickets=None) -> list:
        """ONE staging for ``lanes``, a list of ``(dag, storage)``
        leads of closed ``share`` groups with one ``launch_class``,
        under one hold of the dispatch lock.  ``tickets``: what
        ``launch_ticket`` resolved for each (None: it had none; the
        argument left out: they are resolved here).  The ticketed lanes
        are staged first and together, each from its class's prepared
        record (``_stage_tickets``: the runner's gates once, each
        lane's guards, operands and pin, the arena's mutex once); a lane
        without a ticket, or whose ticket no longer stands, is then
        prepared as a request of its own (``_stage_local``: its memo,
        its feed, its row bounds, its arena pin); then the prepared
        kernels leave together (``DeviceAggregator.launch_lanes``: one
        program, one Pallas call a lane, one fetch).  On the
        coalescer's dispatcher the staging is ONE ``stage_plan`` piece
        of the hold, which says ``lanes`` and ``ticket_hits``, and
        turns to ``stage_full`` where a lane stages in full.

        → one outcome a lane, in order: a ``DeferredResult`` (or a
        result that settled in line) for its members to share as a
        ``share`` group's always did, or None where the lane could not
        be staged or its launch failed: its members then retry solo
        (the coalescer's ``_solo_fallback``), as a failed group's
        always did.  Raises ``_BatchUnavailable`` where no lane can be
        staged here at all."""
        if self._placer is not None and lanes:
            target = self._placer.route(lanes[0][1])
            if target is not self:
                return target.handle_lanes(lanes, tickets)
        if not self._single:
            raise _BatchUnavailable("lanes need a single-device runner")
        from .. import resource_metering as rm
        from ..utils import tracker
        out = [None] * len(lanes)
        with self._device_scope(), self._dispatch_mu:
            with tracker.held("stage_plan") as piece:
                ticketed, full = [], {}
                for i, (dag, storage) in enumerate(lanes):
                    if self._placer is not None and \
                            self._placer.route(storage) is not self:
                        continue        # placed elsewhere: it goes solo
                    ticket = self._ticket_of(dag, storage) \
                        if tickets is None else tickets[i]
                    if ticket is None:
                        full[i] = "none"
                    else:
                        ticketed.append((i, dag, storage, ticket))
                hits = 0
                if ticketed:
                    ctx = rm.current_context()
                    staged = self._stage_tickets(
                        [lane[1:] for lane in ticketed], launch=False)
                    for (i, dag, storage, _t), got in zip(ticketed,
                                                          staged):
                        if type(got) is tuple:
                            out[i] = DeferredResult(
                                self, got[0], dag, storage,
                                pin_anchor=got[1], meter_ctx=ctx)
                            hits += 1
                        elif got is not _FAULT:
                            full[i] = got
                piece.note(lanes=len(lanes), ticket_hits=hits)
                for i in sorted(full):
                    dag, storage = lanes[i]
                    if piece.name != "stage_plan":
                        piece.turn("stage_plan")
                    try:
                        out[i] = self._stage_local(
                            dag, storage, True, None, True, piece, full[i])
                    except Exception:   # noqa: BLE001 — the lane goes solo
                        out[i] = None
            waiting = [i for i, d in enumerate(out)
                       if isinstance(d, DeferredResult) and
                       isinstance(d._pending, _LanePending)]
            failed = {id(p) for p in self._aggregator.launch_lanes(
                [out[i]._pending for i in waiting])}
            for i in waiting:
                if id(out[i]._pending) in failed:
                    out[i].abandon()
                    out[i] = None
        return out

    def handle_batched(self, members) -> "_BatchedSelectionGroup":
        """ONE stacked dispatch for ``members`` — a list of
        ``(dag, storage)`` pairs sharing a ``("stack", ...)``
        batch_class.  Returns a :class:`_BatchedSelectionGroup`; raises
        :class:`_BatchUnavailable` when the group cannot be served as
        one launch (the caller retries members solo)."""
        if self._placer is not None and members:
            target = self._placer.route(members[0][1])
            if target is not self:
                return target.handle_batched(members)
        from . import selection as selmod
        stacks = []
        for dag, _s in members:
            plan = self._analyze(dag)
            if plan is None or plan.kind != "scan_sel":
                raise _BatchUnavailable("not a stacked selection plan")
            if plan.sel_params is None:
                plan.sel_params = selmod.split_params(
                    plan.sel_rpns, len(plan.used_cols))
            stacks.append(plan.sel_params[1])
        lead_dag, lead_storage = members[0]
        got = self.handle_request(lead_dag, lead_storage, deferred=True,
                                  _stack=tuple(stacks))
        if not isinstance(got, _GroupPending):
            # the run settled synchronously (zero rows, quarantine,
            # sticky force-host) — those edges carry per-request
            # semantics the solo path owns
            raise _BatchUnavailable("batched dispatch unavailable")
        return _BatchedSelectionGroup(self, got, list(members))

    # -- selectivity-adaptive selection routing (selection.py) --

    _SEL_EWMA_ALPHA = 0.3
    _SEL_REPROBE = 16       # host-routed plans re-try the device every N

    def _sel_keys(self, dag: DAGRequest, plan: _Plan) -> tuple:
        """(exact, shape) stat keys.  Exact = the const-inclusive plan
        key: repeated identical queries get a precise per-threshold
        EWMA.  Shape = the const-blind predicate structure + table: a
        parameterized workload rotating constants (`v > ?`) still warms
        at this level instead of minting a cold stat per value."""
        if plan.sel_stat_key is None:
            from .selection import shape_key
            plan.sel_stat_key = ("shape",
                                 getattr(plan.scan, "table_id", 0),
                                 shape_key(plan))
        return dag.plan_key(), plan.sel_stat_key

    def _sel_stat(self, key, create: bool = True):
        with self._sel_mu:
            st = self._sel_stats.get(key)
            if st is None and create:
                st = self._sel_stats[key] = \
                    {"ewma": None, "n_obs": 0, "probe_tick": 0}
                while len(self._sel_stats) > 256:
                    self._sel_stats.popitem(last=False)
            elif st is not None:
                self._sel_stats.move_to_end(key)
            return st

    def _sel_observe(self, keys, sel: float) -> None:
        from ..utils import metrics as m
        for key in keys:
            st = self._sel_stat(key)
            with self._sel_mu:
                st["ewma"] = sel if st["ewma"] is None else \
                    (self._SEL_EWMA_ALPHA * sel +
                     (1 - self._SEL_EWMA_ALPHA) * st["ewma"])
                st["n_obs"] += 1
        m.DEVICE_SEL_SELECTIVITY.set(sel)

    def _sel_allows_device(self, keys) -> bool:
        from .selection import HOST_SELECTIVITY_CUTOFF
        exact, shape = keys
        st = self._sel_stat(exact, create=False)
        if st is None or st["n_obs"] < 2:
            # no exact history: the shape-level aggregate decides, at a
            # higher confidence bar (it blends thresholds)
            st = self._sel_stat(shape, create=False)
            if st is None or st["n_obs"] < 4:
                return True
        if st["ewma"] < HOST_SELECTIVITY_CUTOFF:
            return True
        with self._sel_mu:
            st["probe_tick"] += 1
            if st["probe_tick"] >= self._SEL_REPROBE:
                st["probe_tick"] = 0
                return True
        return False

    def _sel_predict(self, keys) -> Optional[float]:
        """EWMA selectivity once warm (≥3 observations; exact plan key
        preferred, const-blind shape key as fallback), else None — a
        None sends the request down the cold mask route."""
        for key in keys:
            st = self._sel_stat(key, create=False)
            if st is not None and st["n_obs"] >= 3:
                return st["ewma"]
        return None

    def selection_stats(self) -> dict:
        """Routing-decision + observed-selectivity rollup (/health).
        With placement on, slice runners' route counts fold in (the
        requests execute there)."""
        with self._sel_mu:
            plans = [{"ewma": round(st["ewma"], 4)
                      if st["ewma"] is not None else None,
                      "n_obs": st["n_obs"]}
                     for st in list(self._sel_stats.values())[-8:]]
            routes = dict(self._sel_route_counts)
        if self._placer is not None:
            for r in self._placer.slices:
                for k, v in r.selection_stats()["routes"].items():
                    routes[k] = routes.get(k, 0) + v
        return {"routes": routes, "plans": plans}

    def _analyze(self, dag: DAGRequest) -> Optional[_Plan]:
        key = dag.plan_key()
        if key in self._plan_cache:
            return self._plan_cache[key]
        plan = self._analyze_uncached(dag)
        if len(self._plan_cache) >= self._plan_cache_max:
            # unlocked callers race this FIFO evict (read-pool threads,
            # dispatcher, completion workers): pop defensively — a lost
            # race transiently overshoots the bound by a thread or two,
            # which is fine; raising on the dispatch path is not
            try:
                self._plan_cache.pop(next(iter(self._plan_cache)), None)
            except (StopIteration, KeyError, RuntimeError):
                pass
        self._plan_cache[key] = plan
        return plan

    def _analyze_uncached(self, dag: DAGRequest) -> Optional[_Plan]:
        execs = dag.executors
        # IndexScan heads are device-eligible too: a covering index scan
        # produces columnar (indexed cols, handle) tiles exactly like a
        # table scan (BASELINE config 5 — TopN via IndexScan; reference:
        # index_scan_executor.rs feeds the same BatchExecutor pipeline)
        if not execs or not isinstance(execs[0],
                                       (TableScanDesc, IndexScanDesc)):
            return None
        scan = execs[0]
        if isinstance(scan, IndexScanDesc):
            n_idx = len(scan.columns) - (
                1 if scan.columns and scan.columns[-1].is_pk_handle else 0)
            if n_idx != 1:
                return None     # multi-column index → host row path
        scan_ets = [c.field_type.eval_type for c in scan.columns]

        sel_rpns: list[RpnExpression] = []
        terminal = None
        for d in execs[1:]:
            if isinstance(d, SelectionDesc):
                if terminal is not None:
                    return None
                for cond in d.conditions:
                    sel_rpns.append(build_rpn(cond))
            elif isinstance(d, (AggregationDesc, TopNDesc)):
                if terminal is not None:
                    return None
                terminal = d
            else:
                return None     # projection/limit → host path

        rpns_to_check = list(sel_rpns)
        plan = _Plan(scan=scan, kind="scan", used_cols=[])
        date_cols: set = set()      # scan offsets on the int32 date plane
        code_cols: set = set()      # scan offsets on a CHAR code plane

        if isinstance(terminal, AggregationDesc):
            agg_rpns = []
            for a in terminal.aggs:
                if a.kind not in ("count", "count_star", "sum", "avg",
                                 "min", "max", "first", "var_pop",
                                 "var_samp", "stddev_pop", "stddev_samp"):
                    # bit_and/or/xor: no XLA scatter-bitop lowering on TPU
                    # → host (they're exact int ops; host numpy is fine)
                    return None
                agg_rpns.append(None if a.arg is None
                                else build_rpn(a.arg))
            agg_kinds = [a.kind for a in terminal.aggs]
            key_rpns = [build_rpn(e) for e in terminal.group_by]
            if lowering.needs_lowering(scan, sel_rpns, agg_rpns, key_rpns):
                # DECIMAL columns as scaled integers, a DATE column as
                # its int32 plane, a short CHAR column as its code
                # plane: decimal RPN becomes the integer RPN the kernels
                # evaluate, each SUM's scale carried beside it to the
                # finalize (device/lowering.py).  What has no exact
                # integer form is not a device plan.
                from ..utils import tracker
                with tracker.span("decimal_lower"):
                    try:
                        low = lowering.lower(
                            scan, sel_rpns, agg_rpns, agg_kinds, key_rpns)
                    except lowering.NotLowerable:
                        return None
                    tracker.annotate(fixed_consts=low.fixed_consts())
                sel_rpns, agg_rpns, key_rpns = \
                    low.sel_rpns, low.agg_rpns, low.key_rpns
                rpns_to_check = list(sel_rpns)
                plan.agg_fracs = low.agg_fracs
                plan.lowered = bool(low.dec_cols or low.date_cols)
                date_cols = low.date_cols
                code_cols = low.code_cols
                plan.key_codes = tuple(low.key_codes)
                scan_ets = [EvalType.INT if i in low.dec_cols or
                            i in low.date_cols or i in low.code_cols
                            else et for i, et in enumerate(scan_ets)]
            specs = []
            for i, (kind, r) in enumerate(zip(agg_kinds, agg_rpns)):
                if r is not None:
                    if r.ret_type in _TIME_ETS and kind not in (
                            "count", "min", "max", "first"):
                        return None     # SUM(datetime) etc. → host
                    rpns_to_check.append(r)
                    specs.append(AggSpec(kind, i, r.ret_type))
                else:
                    specs.append(AggSpec(kind, i))
            if key_rpns:
                if any(s.kind == "first" for s in specs):
                    return None     # FIRST needs source-row gather → host
                if any(r.ret_type is not EvalType.INT for r in key_rpns):
                    return None
                if len(key_rpns) > 1 and not all(
                        s.kind in ("count", "count_star", "sum", "avg")
                        for s in specs):
                    # a composite key's groups come back through the
                    # additive bodies only (aggregate.py run_hash)
                    return None
                rpns_to_check += key_rpns
                plan.kind = "hash_agg"
                plan.key_rpns = key_rpns
            else:
                plan.kind = "simple_agg"
            plan.specs = specs
            plan.agg_rpns = agg_rpns
        elif isinstance(terminal, TopNDesc):
            if len(terminal.order_by) != 1 or \
                    terminal.limit > self._max_topn_limit:
                return None
            order_expr, desc = terminal.order_by[0]
            order_rpn = build_rpn(order_expr)
            if order_rpn.ret_type not in _DEVICE_ETS:
                return None
            rpns_to_check.append(order_rpn)
            plan.kind = "topn"
            plan.order_rpn = order_rpn
            plan.order_desc = desc
            plan.limit = terminal.limit
        elif sel_rpns:
            plan.kind = "scan_sel"
        else:
            return None     # bare scan: decode-bound, no device win

        for r in rpns_to_check:
            if not _rpn_device_safe(r, scan_ets):
                return None

        used = sorted(set().union(*[_rpn_col_indices(r) for r in rpns_to_check])
                      if rpns_to_check else set())
        if plan.kind == "scan_sel" and self._single and \
                isinstance(scan, TableScanDesc):
            # late-materialized selection: when EVERY scan column
            # round-trips its device dtype losslessly (value-checked int
            # narrowing; REAL's f32 does not, unsigned BIGINT may exceed
            # int64), ship them all so the compact route can materialize
            # the k-row output on device and skip the host gather
            # entirely (selection.py).  Otherwise only the predicate
            # columns go to HBM and the mask/index routes gather on
            # host.  The mask and index routes run sharded (per-shard
            # packbits/compaction, psum'd count); only COMPACT stays
            # single-device — its gather output is committed to one
            # chip by construction, so widening a whole-mesh feed
            # would waste H2D/HBM there.  Placement-routed requests
            # land on a single-device slice and keep the route.
            lossless = (EvalType.INT, EvalType.DATETIME, EvalType.DURATION)
            if all(c.is_pk_handle or
                   (c.field_type.eval_type in lossless and
                    not c.field_type.is_unsigned)
                   for c in scan.columns):
                used = sorted(set(used) | set(range(len(scan.columns))))
                plan.compact_ok = True
        mapping = {old: new for new, old in enumerate(used)}
        plan.used_cols = used
        plan.date_planes = tuple(ci in date_cols for ci in used)
        if code_cols:
            plan.code_planes = tuple(
                code_width(scan.columns[ci].field_type)
                if ci in code_cols else 0 for ci in used)
        if not plan.agg_fracs:
            plan.agg_fracs = [None] * len(plan.agg_rpns)
        plan.sel_rpns = [_remap_rpn(r, mapping) for r in sel_rpns]
        plan.agg_rpns = [None if r is None else _remap_rpn(r, mapping)
                         for r in plan.agg_rpns]
        plan.key_rpns = [_remap_rpn(r, mapping) for r in plan.key_rpns]
        if plan.order_rpn is not None:
            plan.order_rpn = _remap_rpn(plan.order_rpn, mapping)
        return plan

    @staticmethod
    def _limb_variant(plan: _Plan, limbs: tuple) -> _Plan:
        """``plan`` with the aggregates ``lowering.fit`` named summed as
        two 16-bit limbs each (``lowering.split_limbs``): the plan a
        feed is served by whose bounds ask for it.  Made once a tuple
        and kept on the plan, so every such feed shares one variant
        (and with it one kernel)."""
        got = plan.variants.get(limbs)
        if got is None:
            import dataclasses
            from ..utils import tracker
            with tracker.span("decimal_lower"):
                rpns, kinds, fracs, recipes = lowering.split_limbs(
                    plan, limbs)
                tracker.annotate(limb_sums=len(limbs))
            got = plan.variants[limbs] = dataclasses.replace(
                plan, agg_rpns=rpns, agg_fracs=fracs,
                agg_recipes=recipes, limbs=limbs, variants={},
                specs=[AggSpec(k, i) if r is None
                       else AggSpec(k, i, r.ret_type)
                       for i, (k, r) in enumerate(zip(kinds, rpns))],
                agg_out=None, agg_params=None, ident=None)
        return got

    # ------------------------------------------------------------------ scan

    def _scan_batch(self, dag: DAGRequest, plan: _Plan, storage) -> ColumnBatch:
        if hasattr(storage, "scan_columns"):
            if plan.lowered:
                # the cache line's scaled DECIMAL columns as they lie
                return storage.scan_columns(plan.scan, dag.ranges,
                                            scaled=True)
            return storage.scan_columns(plan.scan, dag.ranges)
        from ..executors.scan import (
            BatchIndexScanExecutor,
            BatchTableScanExecutor,
        )
        cls = BatchIndexScanExecutor if isinstance(plan.scan, IndexScanDesc) \
            else BatchTableScanExecutor
        ex = cls(storage, plan.scan, dag.ranges)
        chunks = []
        while True:
            r = ex.next_batch(1024)
            if r.batch.num_rows:
                chunks.append(r.batch)
            if r.is_drained:
                break
        return ColumnBatch.concat(chunks) if chunks \
            else ColumnBatch.empty(plan.scan.schema)

    def _nshards(self) -> int:
        return 1 if self._single else num_shards(self._mesh)

    # ------------------------------------- device-state supervision
    #
    # The runner side of device/supervisor.py, fanned out over the
    # placer's slices, a degraded submesh and the joiner: explicit feed
    # teardown (drop_feed replaces GC-timed reclamation), HBM accounting
    # and the quarantine gate a scrub divergence arms.  (The feeds'
    # own digests, move and split: device/feed.py.)

    def set_hbm_budget(self, nbytes: int) -> None:
        """Set (or clear, 0) the HBM budget and enforce it NOW — an
        online shrink must not wait for the next feed admission to
        sweep resident state under the new cap.  With placement on,
        the slices split the budget evenly (each owns a disjoint
        anchor set); this whole-mesh arena keeps the full figure for
        the feeds that shard over every chip."""
        self._arena.budget_bytes = int(nbytes)
        self._arena.enforce()
        if self._joiner is not None and nbytes > 0:
            # the join build/probe cache (device/join.py) takes a fixed
            # 1/8 slice of the node budget — the operator's HBM cap
            # bounds join state too, not only the feed arena
            self._joiner.set_budget(int(nbytes) // 8)
        if self._placer is not None:
            self._placer.set_hbm_budget(int(nbytes))
        degraded = self._degraded_sub()
        if degraded is not None:
            degraded._arena.budget_bytes = int(nbytes)
            degraded._arena.enforce()

    compile_cache_stats = staticmethod(compile_cache_stats)

    def pinned_readback_stats(self) -> dict:
        """Pinned D2H staging pool rollup (/health fastpath)."""
        return HOST_STAGER.stats()

    def hbm_stats(self) -> dict:
        out = self._arena.stats()
        # join build/probe planes (device/join.py) are device-resident
        # bytes too: reported beside the arena figure (bounded by their
        # own slice of the budget, enforced in set_hbm_budget)
        out["join_cache_bytes"] = self._joiner.resident_bytes() \
            if self._joiner is not None else 0
        with self._quar_mu:
            out["quarantined"] = len(self._quarantined)
        # per-tenant residency (resource_control enforcement surface):
        # whose bytes sit in HBM right now, by owning resource group
        out["residency_by_tenant"] = self._arena.residency_by_tenant()
        # where the planes physically live, by device id (a sharded
        # feed must show every mesh device, not device 0 alone)
        out["resident_bytes_by_device"] = \
            self._arena.resident_bytes_by_device()
        # node-level rollup: the budget invariant is judged against
        # ALL device-resident bytes, wherever the anchor is pinned —
        # placement slices and any degraded submesh runner included
        for r in self._sub_runners():
            sub = r.hbm_stats()
            for k in ("resident_bytes", "resident_lines",
                      "pinned_lines", "pinned_bytes", "evictions",
                      "rejections", "drops", "quarantined",
                      "join_cache_bytes"):
                out[k] = out.get(k, 0) + sub.get(k, 0)
            for k in ("residency_by_tenant", "resident_bytes_by_device"):
                for t, b in sub.get(k, {}).items():
                    out[k][t] = out[k].get(t, 0) + b
        return out

    def arena_items(self) -> list:
        """(anchor, bucket) snapshot for the scrubber — placement
        slices and any degraded submesh runner included, so one scrub
        pass audits every resident plane on the node."""
        items = self._arena.items()
        for r in self._sub_runners():
            items.extend(r.arena_items())
        return items

    def drop_feed(self, anchor, reason: str = "drop") -> int:
        """Explicitly release every device feed and request memo
        anchored on ``anchor`` (a FeedLineage or a snapshot).  Called
        by region-lifecycle teardown; returns the HBM bytes released
        from the accounting.  An armed quarantine dies with the anchor
        too — a torn-down region must not pin the lineage (and its
        digest scalars) in the quarantine map forever."""
        with self._quar_mu:
            self._quarantined.pop(id(anchor), None)
        drop_cold = getattr(anchor, "drop_cold", None)
        if callable(drop_cold):
            # unminted cold-resolve artifacts (device version planes)
            # die with the line too
            drop_cold()
        if getattr(anchor, "split_stash", None) is not None:
            # unconsumed split-child candidates die with the lineage —
            # their device planes must not outlive the line
            anchor.split_stash = None
        freed = self._arena.drop(anchor, reason=reason)
        if self._joiner is not None:
            # join build/probe planes anchored on the same lineage die
            # with the feed — stale-epoch join state must not survive
            freed += self._joiner.drop_anchor(anchor)
        for r in self._sub_runners():
            freed += r.drop_feed(anchor, reason=reason)
        if self._placer is not None:
            self._placer.forget(anchor)
        return freed

    def quarantine(self, anchor, reason: str = "") -> None:
        """Scrub divergence: drop the anchor's feeds now and route its
        NEXT request to the host backend; the request after that
        rebuilds a fresh feed from host truth (re-admission).  A
        placed anchor quarantines on its OWNING slice — that is the
        runner its next request routes to."""
        if self._placer is not None:
            owner = self._placer.owner(anchor)
            if owner is not None:
                owner.quarantine(anchor, reason=reason)
                return
        from ..utils.metrics import DEVICE_QUARANTINE_COUNTER
        # a scrub divergence is evidence about the CHIP, not just the
        # line: strike the slice's failure-domain score too (repeated
        # corruption on one slice trips it out of placement entirely)
        self._note_slice_fault("scrub")
        self._arena.drop(anchor, reason="quarantine")
        degraded = self._degraded_sub()
        if degraded is not None:
            # while the mesh is degraded the LIVE feed sits on the
            # submesh runner — and the degrade branch routes the next
            # request there BEFORE this runner's quarantine gate can
            # fire.  The corrupt line must drop (and host-serve its
            # next request) on the sub too, or the scrubber's verdict
            # changes nothing about what keeps being served.
            degraded._arena.drop(anchor, reason="quarantine")
            with degraded._quar_mu:
                degraded._quarantined[id(anchor)] = (anchor, reason)
        with self._quar_mu:
            self._quarantined[id(anchor)] = (anchor, reason)
            # bounded: a quarantined region that is never queried again
            # (and never torn down) must not accumulate forever
            while len(self._quarantined) > 128:
                self._quarantined.pop(next(iter(self._quarantined)))
        DEVICE_QUARANTINE_COUNTER.inc()

    def _consume_quarantine(self, anchor) -> bool:
        with self._quar_mu:
            return self._quarantined.pop(id(anchor), None) is not None

    # --------------------------------------------------------------- kernels

    def _shard_kernel(self, cache_key, build):
        kern = self._kernel_cache.get(cache_key)
        if kern is None:
            kern = build()
            self._kernel_cache[cache_key] = kern
        return kern

    def _scalar_cache_get(self, key, v, dtype):
        cache = self._scalar_cache
        arr = cache.get(key)
        self.flight_recorder.note_scalar(hit=arr is not None)
        if arr is None:
            if self._single:
                arr = jnp.asarray(v, dtype)
            else:
                # committed where every sharded program declares its
                # scalars, ``P()`` over THIS runner's mesh: the jitted
                # call then takes the array as it lies
                arr = jax.device_put(np.asarray(v, dtype), self._repl)
            cache[key] = arr
            while len(cache) > 256:
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        return arr

    def _cached_scalar(self, v, dtype):
        """Device-resident scalar, uploaded once per value, so a warm
        request issues no scalar H2D; on a mesh it is committed
        replicated over this runner's devices (``_repl``), which is
        how every sharded program declares it, so a warm launch moves
        nothing between chips either.  Measured on four chips
        (PERF.md sections 3 and 6, PR 30): a scalar cached on one chip
        went through ``shard_args`` → ``DevicePutWithSharding`` on
        every launch, 1.2 ms each and two a launch, 2.4 of the 3.6 ms
        a launch held the dispatch lock.  Counted on /health
        ``device_mesh.scalar_cache``.  LRU-bounded: row counts vary
        per snapshot, so unbounded caching would leak one device
        buffer per distinct n on a live server."""
        return self._scalar_cache_get((int(v), str(dtype)), v, dtype)

    def _cached_param(self, v, dtype):
        """Device-resident predicate parameter (selection.py hoisted
        constants) — same LRU as _cached_scalar but float-capable, so a
        repeated threshold never re-pays the scalar H2D."""
        key = ("param", float(v) if isinstance(v, float) else int(v),
               str(dtype))
        return self._scalar_cache_get(key, v, dtype)

    def _eval_masked(self, plan: _Plan, pairs, n_local, row_mask):
        mask = row_mask
        for rpn in plan.sel_rpns:
            v, ok = eval_rpn(rpn, pairs, n_local, jnp)
            mask = mask & ok & (v != 0)
        return mask

    def _shard_index(self):
        if self._single:
            return jnp.asarray(0, jnp.int64)
        tile = self._mesh.shape[ROW_AXES[1]]
        return (lax.axis_index(ROW_AXES[0]) * tile
                + lax.axis_index(ROW_AXES[1])).astype(jnp.int64)

    def _psum(self, x):
        return x if self._single else lax.psum(x, ROW_AXES)

    def _topn_sort_key(self, plan: _Plan, v, ok, mask):
        """Map the order expression to one descending-top_k sort key.

        ``top_k(key2)`` must rank: real rows in requested order, then
        NULL rows per MySQL (first for ASC, last for DESC), then
        masked-out rows never. Keys stay in the narrowest exact dtype —
        f32 for REAL (the device column resolution), int32 for int32 INT
        (top_k on pair-emulated int64/f64 measures 1.5-4× slower) — and
        any boundary ambiguity is repaired by the exact host refine over
        the candidate set.
        """
        desc = plan.order_desc
        if v.dtype == jnp.float32:
            key2 = v if desc else -v
            null_key = jnp.float32(-3e38) if desc else jnp.float32(np.inf)
            excl = jnp.float32(-np.inf)
        elif v.dtype == jnp.int32:
            lo = np.iinfo(np.int32)
            vv = jnp.maximum(v, lo.min + 2)
            key2 = vv if desc else -vv
            null_key = jnp.int32(lo.min + 1) if desc else jnp.int32(lo.max)
            excl = jnp.int32(lo.min)
        elif v.dtype in (jnp.int64, jnp.uint64):
            # exact 64-bit candidate keys: an f64 key collapses values
            # within 512 of each other at DATETIME magnitudes (~2^61),
            # and top_k over collapsed ties can DROP the true top rows
            # before the host refine ever sees them.  u64 cores are
            # < 2^63 (feed guard) so the int64 view preserves order.
            lo = np.iinfo(np.int64)
            vv = jnp.maximum(v.astype(jnp.int64), lo.min + 2)
            key2 = vv if desc else -vv
            null_key = jnp.int64(lo.min + 1) if desc else jnp.int64(lo.max)
            excl = jnp.int64(lo.min)
        else:
            keyf = jnp.asarray(v, jnp.float64)
            key2 = keyf if desc else -keyf
            null_key = jnp.float64(_NULL_KEY) if desc \
                else jnp.float64(-_NULL_KEY)
            excl = jnp.float64(_EXCLUDED_DESC)
        key2 = jnp.where(ok, key2, null_key)
        return jnp.where(mask, key2, excl)

    def _build_topn_kernel(self, plan: _Plan, n_cols: int, k: int,
                           null_flags, n_pad: int, n_flat: int,
                           n_used: Optional[int] = None):
        """Whole-feed two-stage top-k — ONE dispatch, no scan.

        ``lax.top_k`` over one flat 100M-row array costs 340-530ms on v5e
        and degrades further inside lax.scan; batched over segment rows it
        runs ~3× faster. Stage 1 takes the per-segment top k over a
        (nseg, seglen) view (any global top-k row is in its segment's
        top k), stage 2 reduces the nseg·k candidates to k.

        ``n_used`` (single-device): the live seglen-rounded row prefix —
        the kernel slices the feed to it so the bucketed padding
        (feed.py ``pad_rows``) taxes only the cache key, never the top_k
        extent (an XLA prefix slice streams at HBM speed; top_k over the same
        rows costs an order of magnitude more).
        """
        S = self._nshards()
        n_local = n_pad // S
        trim = self._single and n_used is not None and n_used < n_local
        if trim:
            n_local = n_used
        seglen = math.gcd(n_local, 1 << 17)
        nseg = n_local // seglen
        kk = min(k, seglen)

        idt = jnp.int32 if n_pad <= np.iinfo(np.int32).max else jnp.int64

        def local_fn(n_scalar, *flat):
            if trim:
                flat = tuple(a[:n_local] for a in flat)
            if self._single:
                base0 = idt(0)
            else:
                base0 = (self._shard_index() * n_local).astype(idt)
            iota = jnp.arange(n_local, dtype=idt)
            row_mask = (base0 + iota) < n_scalar.astype(idt)
            args = []
            fi = 0
            for has_nulls in null_flags:
                vv = flat[fi]
                fi += 1
                if has_nulls:
                    m = flat[fi]
                    fi += 1
                else:
                    m = row_mask
                args.append((vv, m))
            mask = self._eval_masked(plan, args, n_local, row_mask)
            v, ok = eval_rpn(plan.order_rpn, args, n_local, jnp)
            v = jnp.broadcast_to(v, (n_local,))
            ok = jnp.broadcast_to(ok & mask, (n_local,))
            key2 = self._topn_sort_key(plan, v, ok, mask)
            kv1, ki1 = lax.top_k(key2.reshape(nseg, seglen), kk)
            seg_base = (jnp.arange(nseg, dtype=idt) * seglen)[:, None]
            gidx1 = (base0 + seg_base + ki1.astype(idt)).astype(jnp.int64)
            _, sel = lax.top_k(kv1.reshape(-1), min(k, nseg * kk))
            gidx = gidx1.reshape(-1)[sel]
            m1 = jnp.take_along_axis(mask.reshape(nseg, seglen), ki1, axis=1)
            o1 = jnp.take_along_axis(ok.reshape(nseg, seglen), ki1, axis=1)
            return gidx, m1.reshape(-1)[sel], o1.reshape(-1)[sel]

        local_fn = named_program(local_fn, "topn")
        if self._single:
            return jax.jit(local_fn)
        return jax.jit(jax.shard_map(
            local_fn, mesh=self._mesh,
            in_specs=(P(),) + (P(ROW_AXES),) * n_flat,
            out_specs=(P(ROW_AXES),) * 3))

    # -- dispatch span + flight-recorder feed --

    @contextmanager
    def _dispatch_locked(self):
        """The dispatch lock on a request's launch path, its acquisition
        timed: phase ``dispatch_lock_wait`` (a wait; once a launch, 0
        where nobody held it).  A lane launch takes no lock and records
        none."""
        from ..utils import tracker
        t0_ns = time.perf_counter_ns()
        self._dispatch_mu.acquire()
        try:
            tracker.add_phase("dispatch_lock_wait",
                              time.perf_counter_ns() - t0_ns)
            yield
        finally:
            self._dispatch_mu.release()

    @contextmanager
    def _dispatch_phase(self, klass: str, key=None, params: int = 0,
                        slot_mode: str = "", keys: int = 0,
                        planes: int = 0, limb_sums: int = 0,
                        slots: int = 0, block_rows: int = 0,
                        prepared: int = 0):
        """Every kernel launch site runs under this: the
        ``device_dispatch`` tracker span, plus one flight-recorder
        entry (launch wall, compile class, first-launch flag, mesh
        shape and ``shards``, slice id, arena-pinned bytes) annotated
        onto the span — the trace carries the launch's black-box record
        inline — and the request's ``mesh`` label.

        ``key`` refines the compile class (n_pad bucket / kernel cache
        key) so the ``first_launch`` flag distinguishes a real
        cold-compile launch from a warm cache hit within the same plan
        kind.  ``params``: the constants the launch carries as kernel
        operands; ``slot_mode``: the Pallas kernel's; ``keys`` /
        ``planes`` / ``limb_sums``: its GROUP BY keys, the byte planes
        it contracts and the SUMs it sums as limbs; ``slots`` /
        ``block_rows``: the grid it contracts them over and the rows a
        grid step takes (on the span and in the entry; counted on
        ``/health`` ``device_mesh.agg_params``); ``prepared``: the
        launch's lanes that were staged from their class's prepared
        record alone (``_stage_tickets``; ``device_mesh.prepared``
        ``hits``)."""
        from .. import resource_metering as rm
        from ..utils import tracker
        rec = self.flight_recorder
        # what the launch was, for whoever has to show it in a trace
        # the span above is not in (``DeferredResult.launch_info``):
        # filled when the launch is over
        info: dict = {}
        with tracker.phase("device_dispatch"):
            t0_ns = time.perf_counter_ns()
            ok = True
            try:
                yield info
            except BaseException:
                ok = False
                raise
            finally:
                t1_ns = time.perf_counter_ns()
                wall_s = (t1_ns - t0_ns) / 1e9
                # RU metering: every launch wall is charged to the
                # ambient (tag, region) — a coalesced group's shared
                # launch splits by occupancy share across member tags
                # (resource_metering.charge_launch site resolution)
                rm.charge_launch(wall_s)
                # the request's ``mesh`` label is the mesh THIS launch
                # ran on: a placement slice or a degraded submesh runner
                # states its own shape, not the node's configured one
                tracker.label("mesh", self._mesh_desc)
                if rec is not None:
                    entry = rec.note(
                        klass=klass, key=key,
                        wall_s=wall_s,
                        mesh=self._mesh_desc,
                        slice_id=self._slice_indices[0]
                        if len(self._slice_indices) == 1 else None,
                        pinned_bytes=self._arena.pinned_bytes(),
                        ok=ok, shards=num_shards(self._mesh),
                        whole_mesh=self._failover_parent is None,
                        params=params, slot_mode=slot_mode, keys=keys,
                        planes=planes, limb_sums=limb_sums, slots=slots,
                        block_rows=block_rows, prepared=prepared)
                    tracker.annotate(**entry)
                    info["attrs"] = entry
                info["t0_ns"], info["t1_ns"] = t0_ns, t1_ns
                self._launched.info = info

    def _take_launch_info(self) -> Optional[dict]:
        """The record of this thread's last ``_dispatch_phase`` on this
        runner, once."""
        info = getattr(self._launched, "info", None)
        self._launched.info = None
        return info

    # -- packed device→host readback (one transfer, one sync) --

    def _readback(self, tree):
        """Fetch a device pytree with every D2H transfer in flight at once.

        ``copy_to_host_async`` is issued for every leaf before the first
        blocking fetch, so the whole tree lands in ~one sync round-trip
        (r2's sequential per-array fetches paid one per leaf).
        Returns the same pytree as numpy.
        """
        from ..utils import tracker
        _fp_degrade("device::before_fetch")
        # a transfer-level corruption is DETECTED (link CRC) and surfaces
        # as a failed fetch: the request degrades to the host pipeline —
        # corrupted bytes never become an answer
        _fp_degrade("device::d2h_corrupt")
        # a chip that died BETWEEN dispatch and fetch fails the D2H: the
        # in-flight request rescues onto a healthy slice/submesh
        # (DeferredResult/_GroupPending catch this) or degrades to host
        hit = self._slice_dead_targets()
        if hit:
            if self._health is None:
                board = self._strike_board()
                if board is not None:
                    for i in hit:
                        board.note_fault(i, "fetch")
            raise _FallbackToHost("device::slice_dead")
        # the old monolithic "device_fetch" phase is split so a warm
        # p50 can be attributed from the artifact alone: "d2h_wait" is
        # the transfer + sync (here), "host_materialize" is the host
        # finalize that follows (_finish): fetched planes -> result
        # Columns.  For an aggregation off the Pallas kernel, with a
        # GROUP BY or without, that is one native call over the KBs of
        # accumulator, which never lets go of the GIL
        # (aggregate.finalize_packed; numpy over the same KBs for the
        # XLA bodies, no Python value made per group either way); for a
        # selection it is the host gather of the selected rows
        with tracker.phase("d2h_wait"):
            leaves, treedef = jax.tree.flatten(tree)
            for x in leaves:
                try:
                    x.copy_to_host_async()
                except Exception:   # pragma: no cover - CPU arrays
                    pass
            # span-only children: device_wait is the program not
            # finished yet (with the pinned stager its last step IS the
            # copy into pinned host memory), d2h_copy the transfer +
            # sync np.asarray still pays after it.  A blocking call
            # drops the GIL, and getting it back cost ~0.6 ms a read on
            # the v5e host (PERF.md, PR 25): so none for leaves that
            # are ready, which is_ready() says without blocking
            with tracker.span("device_wait"):
                if not all(x.is_ready() for x in leaves
                           if hasattr(x, "is_ready")):
                    jax.block_until_ready(leaves)
            with tracker.span("d2h_copy"):
                fetched = [np.asarray(x) for x in leaves]
            # RU metering: the MEASURED transfer payload, charged once
            # per physical D2H (a group's shared fetch splits across
            # its members through the captured group context)
            from .. import resource_metering as rm
            rm.charge_d2h(sum(int(a.nbytes) for a in fetched))
            return jax.tree.unflatten(treedef, fetched)

    # ------------------------------------------------------------ dispatch

    @staticmethod
    def _serve_on_host(dag: DAGRequest, storage, rung: str):
        """The runner's own host rung (a quarantined line or slice, a
        dispatch- or fetch-side fault with no healthy slice to rescue
        onto): exact answers from the host pipeline.  The endpoint
        still labels the request ``backend=device`` — it routed here —
        so the rung names itself: a ``degraded=runner:<rung>`` label
        and a ``host_exec`` span, the same marks the endpoint's own
        degrade leaves."""
        from ..executors.runner import BatchExecutorsRunner
        from ..utils import tracker
        tracker.label("degraded", f"runner:{rung}")
        with tracker.phase("host_exec"):
            return BatchExecutorsRunner(dag, storage).handle_request()

    def handle_request(self, dag: DAGRequest, storage,
                       deferred: bool = False, _stack=None,
                       _ticket=_UNSET):
        """Execute a supported plan on the device.

        ``_ticket`` (the coalescer's singleton group): what
        ``launch_ticket`` resolved for this request, None where it found
        nothing; left out, the request's ticket is resolved here.

        ``_stack`` (handle_batched only): a tuple of per-member hoisted
        predicate parameter value tuples.  The scan_sel run then builds
        the STACKED mask kernel, dispatches the whole group once, and
        the call returns a :class:`_GroupPending` (raw group arrays,
        shared fetch) instead of a per-request result; any path that
        cannot produce a group dispatch raises
        :class:`_BatchUnavailable` or returns a settled result the
        caller must treat as such.

        ``deferred=True``: return as soon as the kernel is dispatched —
        the result is a :class:`DeferredResult` whose ``result()`` runs
        the D2H fetch + host finalize (on whatever thread calls it), so
        N in-flight requests overlap dispatch/compute/fetch instead of
        serializing on the transport round trip.  Paths that never
        reach a device dispatch (host fallback, zero rows, cold kernel
        builds that validate synchronously) still return a finished
        SelectResult; callers must accept either.
        """
        if self._placer is not None and _stack is None and \
                hasattr(storage, "scan_columns"):
            # hot-region placement (device/placement.py): small feeds
            # pin to a single-device slice picked by load; large feeds
            # come back to this whole-mesh runner (scale-up)
            target = self._placer.route(storage)
            if target is not self:
                return target.handle_request(dag, storage,
                                             deferred=deferred,
                                             _ticket=_ticket)
        if self._board is not None:
            # elastic mesh degrade: a quarantined chip routes whole-
            # mesh plans to the largest healthy submesh (8→4→2→1; the
            # sharded feeds re-mint from host truth onto survivors)
            # instead of collapsing to host — host stays the FINAL
            # rung, taken only when the rebuild itself fails
            try:
                degraded = self._degraded_target()
            except _FallbackToHost:
                return self._serve_on_host(dag, storage, "no_submesh")
            if degraded is not None:
                return degraded.handle_request(dag, storage,
                                               deferred=deferred,
                                               _stack=_stack)
        with self._device_scope():
            return self._handle_local(dag, storage, deferred, _stack,
                                      _ticket=_ticket)

    def _device_scope(self):
        """Where this runner's uploads and plain-jit launches land.  A
        single-device runner uses uncommitted arrays, which JAX places
        on the process DEFAULT device — right for a one-chip node, but
        a placement slice or degraded submesh on any other chip must
        say where, or every "slice" serves from device 0 (found by
        /health resident_bytes_by_device: four placed anchors, one
        device)."""
        if self._pin_device is None:
            return nullcontext()
        return jax.default_device(self._pin_device)

    def _handle_local(self, dag: DAGRequest, storage, deferred: bool,
                      _stack, _lanes: bool = False, _ticket=_UNSET):
        """``handle_request`` on THIS runner's devices (placement and
        degrade routing already done): from the request's ticket where
        one stands (``_stage_tickets``, the one way a prepared hit is
        staged: a hold's lanes, a request alone), else in full
        (``_stage_local``).  ``_lanes``: as one lane of a staging whose
        caller holds the dispatch lock and launches the lanes' kernels
        itself; a lane that would be served on the host raises
        ``_BatchUnavailable`` instead, as a stacked group does.

        On the coalescer's dispatcher (``trace.hold``) the staging is
        two rows of the hold: ``stage_plan`` up to the branch (all of a
        hit), then ``stage_full``."""
        from ..utils import tracker
        with tracker.held("stage_plan") as piece:
            cause = "none"
            ticket = None
            if _stack is None:
                ticket = self._ticket_of(dag, storage) \
                    if _ticket is _UNSET else _ticket
            if ticket is not None:
                with nullcontext() if _lanes else self._dispatch_locked():
                    got, = self._stage_tickets([(dag, storage, ticket)],
                                               launch=not _lanes)
                hit = type(got) is tuple
                piece.note(lanes=1, ticket_hits=int(hit))
                if hit:
                    return self._hit_result(dag, storage, got, deferred)
                if got is _FAULT:
                    if _lanes:
                        raise _BatchUnavailable("degraded during batched "
                                                "dispatch")
                    return self._serve_on_host(dag, storage, "dispatch")
                cause = got
            return self._stage_local(dag, storage, deferred, _stack,
                                     _lanes, piece, cause)

    def _hit_result(self, dag, storage, staged, deferred: bool):
        """A request alone staged from its record: its handle, or its
        answer where the caller blocks."""
        lane, pin = staged
        if deferred:
            return DeferredResult(self, lane, dag, storage, pin_anchor=pin)
        try:
            try:
                result = self._finish(lane)
            finally:
                self._arena.unpin(pin)
        except _FallbackToHost:
            # (a fetch-side fault of a blocking caller: the host rung,
            # as the full staging's own handler answers it)
            self._note_slice_fault("dispatch")
            return self._serve_on_host(dag, storage, "dispatch")
        return self._apply_output_offsets(dag, result)

    def _stage_local(self, dag: DAGRequest, storage, deferred: bool,
                     _stack, _lanes: bool, piece, cause: str):
        """A request staged as one of its own, in full unless its memo
        turns out to hold a record after all (a group whose ticket was
        asked before another lane's full staging wrote it).  ``cause``:
        why it was not staged from a ticket (``supervisor.
        TICKET_MISSES``), counted here, once."""
        plan = self._analyze(dag)
        if plan is None:
            raise RuntimeError("plan not supported by device backend")
        grouped = _stack is not None or _lanes

        if self._refuse_if_quarantined():
            if grouped:
                # a group must not burn the leader's deadline on a
                # throwaway synchronous host run — the coalescer's
                # solo retries re-route each member via the placer,
                # which now excludes this slice
                raise _BatchUnavailable("slice quarantined")
            # this slice is a condemned chip: serve from the host
            # pipeline without touching any per-slice state (a racing
            # caller that bypassed the placer's exclusion lands here)
            from ..utils import tracker
            self.flight_recorder.note_tickets(miss="gate")
            tracker.label("device_feed", "slice_quarantined")
            return self._serve_on_host(dag, storage, "slice_quarantined")

        if self._quarantined and hasattr(storage, "scan_columns") and \
                self._consume_quarantine(feed_anchor(storage)):
            # scrub divergence on this line: its feeds were dropped at
            # quarantine time; serve THIS request from the host
            # pipeline, then let the next one rebuild from host truth
            from ..utils import tracker
            self.flight_recorder.note_tickets(miss="gate")
            tracker.label("device_feed", "quarantined")
            return self._serve_on_host(dag, storage, "quarantined")

        # bucket tiling (SURVEY §5.7 "region → chip, bucket → tile";
        # pd_client buckets): a hash-agg request covering a strict
        # subset of the region's rows reuses the WHOLE-region HBM feed
        # and dispatches the kernel only over the covering block spans;
        # disjoint spans' packed partials add like psum partials.
        tile_spans = None
        orig_dag = dag
        if self._single and plan.kind == "hash_agg" and dag.ranges \
                and hasattr(storage, "row_slices"):
            try:
                spans = storage.row_slices(dag.ranges)
                n_all = storage.estimated_rows()
            except Exception:   # noqa: BLE001 — storage without spans
                spans, n_all = None, 0
            covered = sum(j - i for i, j in spans) if spans else 0
            if spans and 0 < covered < n_all:
                tile_spans = tuple(spans)
                if cause == "none":
                    cause = "tile"
                # feed/meta keyed WITHOUT ranges: every tiled request
                # over this snapshot shares one region feed
                dag = dag.over_ranges(())

        self.flight_recorder.note_tickets(miss=cause)
        meta = self._request_meta(storage, self._meta_key(dag, plan))
        memo: dict = {}

        def get_batch():
            """Host ColumnBatch for this scan (built at most once; the
            warm agg path never needs it — the feed is HBM-resident and
            the row count is memoized)."""
            if "batch" not in memo:
                memo["batch"] = self._scan_batch(dag, plan, storage)
            return memo["batch"]

        def count_rows() -> int:
            if isinstance(plan.scan, TableScanDesc) and \
                    hasattr(storage, "count_rows") and \
                    hasattr(storage, "scan_columns"):
                # row count without materializing the batch — the warm
                # delta path must not pay a full columnar gather just
                # to re-learn n
                return storage.count_rows(dag.ranges)
            return get_batch().num_rows

        # (every shared-memo interaction pins to the request's generation)
        lineage, req_v = generation(storage)
        if lineage is not None:
            mv = meta.get("lineage_v", req_v)
            if mv < req_v:
                # the memo lags this snapshot: carry what provably
                # survives the gap, drop the rest
                self._refresh_meta(meta, lineage, plan, mv, req_v,
                                   count_rows)
            elif mv > req_v:
                # an older-generation read (history serve) must not
                # consume or mutate the newer shared memo: go local
                meta = {"lineage_v": req_v,
                        "force_host": meta.get("force_host", False)}
            meta.setdefault("lineage_v", req_v)
        if meta.get("force_host"):
            return self._serve_on_host(orig_dag, storage, "force_host")

        # shared-memo writes are only allowed while the memo still
        # reflects req_v — a request (or deferred finalize) racing a
        # newer generation's refresh must not repopulate the shared
        # memo with stale data; stale results stay request-local
        def memo_fresh() -> bool:
            return req_v is None or meta.get("lineage_v") == req_v

        if "n_rows" in meta and memo_fresh():
            n = meta["n_rows"]
        else:
            n = count_rows()
            if memo_fresh():
                meta["n_rows"] = n
        if n == 0:
            from ..executors.runner import BatchExecutorsRunner
            return BatchExecutorsRunner(orig_dag, storage).handle_request()

        # what a warm whole-feed Pallas launch of this class needs was
        # left in the memo by the last one (``_Prepared``).  A request
        # that finds one only HERE (it had no ticket, or its ticket's
        # record was of the generation before) is staged from it all the
        # same, by the hits' one function, the gates below already fired
        rec = meta.get("prepared") if _stack is None and \
            tile_spans is None and memo_fresh() else None
        pin_anchor = None
        try:
            _fp_degrade("device::before_dispatch")
            # chip failure domains: refuse to launch on a quarantined
            # slice, and fail the way the chip would when
            # device::slice_dead names one of mine
            self._preflight_slice()
            if rec is not None:
                anchor = feed_anchor(storage)
                ticket = self._ticket(plan, anchor,
                                      self._arena.peek(anchor), meta, rec,
                                      lineage, req_v)
                with nullcontext() if _lanes else self._dispatch_locked():
                    staged, = self._stage_tickets(
                        [(dag, storage, ticket)], launch=not _lanes,
                        gated=True)
                if type(staged) is tuple:
                    return self._hit_result(dag, storage, staged, deferred)
            # the full staging: the used columns' host halves, each
            # derived at most once
            piece.turn("stage_full")
            planes = HostPlanes(plan, meta, memo, memo_fresh, get_batch, n,
                                self.flight_recorder, self._feeds.pad_rows)
            dtypes = planes.dtypes()
            if planes.limbs:
                # this feed's bounds ask for products summed as 16-bit
                # limbs (lowering.fit): the plan's variant that does
                plan = self._limb_variant(plan, planes.limbs)

            with nullcontext() if _lanes else self._dispatch_locked():
                if not self._single:
                    # one shard's enqueue failing (device loss, ICI
                    # fault) surfaces as a whole-launch failure mid-
                    # dispatch, with the lock HELD.  The plan degrades
                    # to host WHOLE — never a partial per-shard answer
                    # — and the raise unwinds this ``with``, releasing
                    # the lock on the way out: a sharded launch fault
                    # must not wedge the serialized dispatch stream
                    # (the launch-order-inversion hazard the lock
                    # exists for — see its comment at the definition)
                    _fp_degrade("device::shard_launch")
                feed = self._feeds.get(storage, planes, dag.ranges, n,
                                       lineage, req_v)
                # derived kernel constants written inside the run
                # bodies ride the guarded view: a stale-generation
                # request keeps them request-local
                gmeta = _GuardedMeta(meta, memo_fresh)
                if plan.kind == "simple_agg":
                    result = self._aggregator.run_simple(
                        dag, plan, planes.cols, dtypes, n, feed, gmeta,
                        lanes=_lanes)
                elif plan.kind == "hash_agg":
                    result = self._aggregator.run_hash(
                        dag, plan, planes.cols, dtypes, n, feed, gmeta,
                        tile_spans=tile_spans, lanes=_lanes)
                elif plan.kind == "topn":
                    result = self._run_topn(dag, plan, planes.cols,
                                            dtypes, n, get_batch, feed)
                else:   # scan_sel
                    result = self._run_scan_sel(dag, plan, dtypes, n,
                                                get_batch, feed, storage,
                                                stack=_stack)
                if isinstance(result, _Pending) and \
                        self._health is not None and \
                        self._health.quarantined():
                    # the invariant counter chaos audits: a quarantine
                    # landing between the preflight gate and the launch
                    # means a kernel ran on a condemned chip
                    self._health.launched_quarantined += 1
                if hasattr(storage, "scan_columns"):
                    anc = feed_anchor(storage)
                    if isinstance(result, _Pending):
                        # pin the line for the in-flight dispatch:
                        # budget eviction (arena.admit, also under this
                        # lock) must never reclaim HBM a launched
                        # kernel still reads
                        pin_anchor = self._arena.pin(anc)
                    # re-account: the run may have cached new device
                    # state (sparse slot planes) in the request memo.
                    # After EVERY full staging, a first build's that
                    # settled in line too: the requests that follow are
                    # staged from its record and admit nothing
                    self._arena.admit(anc)
            if isinstance(result, _Pending) and not deferred:
                # synchronous callers block here; the before_fetch
                # failpoint inside _readback still degrades to host
                try:
                    result = self._finish(result)
                finally:
                    if pin_anchor is not None:
                        self._arena.unpin(pin_anchor)
                        pin_anchor = None
        except _FallbackToHost:
            if pin_anchor is not None:
                self._arena.unpin(pin_anchor)
            # a dispatch-side fault on a placement slice strikes its
            # health score exactly once (the failure-domain feed; the
            # whole-mesh runner's slice-attributable strikes happen at
            # the _preflight_slice / _readback sites instead)
            self._note_slice_fault("dispatch")
            if grouped:
                # a degrade mid-group must not serve the LEADER's host
                # answer to every member — the coalescer retries each
                # member as a solo dispatch (per-member degrade intact)
                raise _BatchUnavailable("degraded during batched "
                                        "dispatch")
            return self._serve_on_host(orig_dag, storage, "dispatch")
        except BaseException:
            if pin_anchor is not None:
                self._arena.unpin(pin_anchor)
            raise

        if _stack is not None:
            if isinstance(result, _Pending):
                return _GroupPending(self, result, pin_anchor)
            return result       # settled synchronously: caller bails
        if isinstance(result, _Pending):
            return DeferredResult(self, result, orig_dag, storage,
                                  pin_anchor=pin_anchor)
        return self._apply_output_offsets(orig_dag, result)

    def _stage_tickets(self, lanes, launch: bool,
                       gated: bool = False) -> list:
        """THE way a prepared hit is staged: ``lanes``, ``(dag, storage,
        ticket)`` each, from their class's prepared records in one pass
        under the caller's hold of the dispatch lock → one outcome a
        lane, in order: ``(its lane, its arena pin)``, launched where
        ``launch`` says so (a request alone; a hold launches its lanes
        together); or the cause it was not (``supervisor.
        TICKET_MISSES``), and the caller stages it in full, which writes
        the next record; or ``_FAULT`` where a dispatch gate fired in it.

        Once a hold, the runner's own gates (``gated``: the caller has
        fired them): a quarantined slice stages nothing from a record,
        and ``device::slice_dead`` naming one of mine fails every lane
        of the hold as the chip would, one strike for the one dispatch.
        Then a lane's, none of which touches the arena: the ticket is
        this runner's; the memo still stands at the request's
        generation and holds THAT record (a write since, or an
        older-generation read, goes local, and ``_stage_local`` rolls
        or shields the memo as it always did); the memo is not forced
        to the host and the line not quarantined by the scrub;
        ``device::before_dispatch``.  Then the arena, ONCE for all of
        them (``FeedArena.pin_many``: each line touched, settled and
        pinned, its bucket handed back), and each lane's record held to
        it by identity: THAT bucket still holds THAT feed under its
        key, planes untouched and at this generation (so a budget
        eviction, ``drop_feed``, a scrub quarantine, a split's or a
        move's take, a patch or a re-upload all miss), and the kernel
        cache still holds THAT entry for these operand dtypes (a failed
        launch's ``False`` misses).  The lane's operands are ITS plan's
        (the ticket's).  That the request covers its whole region is
        the record's knowledge: it was written for these ranges at this
        generation by a staging that found no tile.  No ``arena.admit``:
        a hit caches no new device state (it stays on every miss, where
        a feed or a slot column may have been added)."""
        from ..utils import tracker
        out = [None] * len(lanes)
        if not gated:
            if self._health is not None and self._health.quarantined():
                # (``_stage_local`` refuses each, and counts it)
                return ["gate"] * len(lanes)
            try:
                self._preflight_slice()
            except _FallbackToHost:
                self._note_slice_fault("dispatch")
                return [_FAULT] * len(lanes)
        live = []
        for i, (_dag, _storage, t) in enumerate(lanes):
            meta = t.meta
            if t.runner is not self:
                out[i] = "none"
            elif meta.get("prepared") is not t.rec or (
                    t.lineage is not None and
                    meta.get("lineage_v") != t.req_v):
                out[i] = "generation"
            elif meta.get("force_host") or (
                    self._quarantined and
                    id(t.anchor) in self._quarantined):
                out[i] = "gate"
            else:
                if not gated:
                    try:
                        _fp_degrade("device::before_dispatch")
                    except _FallbackToHost:
                        self._note_slice_fault("dispatch")
                        out[i] = _FAULT
                        continue
                live.append(i)
        if not live:
            return out
        pinned = self._arena.pin_many([lanes[i][2].anchor for i in live])
        # lane -> its pin, while this call answers for it
        held = {i: pin for i, (_bucket, pin) in zip(live, pinned)}
        hits = []
        try:
            for i, (bucket, pin) in zip(live, pinned):
                dag, _storage, t = lanes[i]
                rec = t.rec
                feed = rec.feed
                cause = None
                if bucket is None or bucket is not t.bucket or \
                        bucket.get(rec.feed_key) is not feed or \
                        feed["flat"] is not rec.flat or \
                        feed.get("lineage_v") != t.req_v:
                    cause = "feed"
                elif self._kernel_cache.get(rec.key) is not rec.entry or \
                        t.pdts != rec.param_dts:
                    # (a memo is a const-blind class's, and so are the
                    # operands' dtypes the kernel was built for: held,
                    # not assumed)
                    cause = "kernel"
                if cause is not None:
                    self._arena.unpin(held.pop(i))
                    if t.meta.get("prepared") is rec:
                        del t.meta["prepared"]
                        self.flight_recorder.note_prepared(cause)
                    out[i] = cause
                    continue
                out[i] = (rec.lane(dag, t.pvals, prepared=True), pin)
                hits.append(i)
            if launch and hits:
                # a launch from the record that failed (struck): the full
                # staging decides what serves the request, and writes
                # the next record
                gone = {id(p) for p in self._aggregator.launch_lanes(
                    [out[i][0] for i in hits])}
                for i in [i for i in hits if id(out[i][0]) in gone]:
                    hits.remove(i)
                    self._arena.unpin(held.pop(i))
                    t = lanes[i][2]
                    if t.meta.get("prepared") is t.rec:
                        del t.meta["prepared"]
                    out[i] = "kernel"
        except BaseException:
            for pin in held.values():
                self._arena.unpin(pin)
            raise
        if hits:
            tracker.label("device_feed", "hit")
            self.flight_recorder.note_feed_get("hit", len(hits))
            if self._health is not None and self._health.quarantined():
                # (the invariant counter chaos audits: ``_stage_local``)
                self._health.launched_quarantined += len(hits)
            if not gated:
                # (a record found only at the staging, ``gated``, was
                # no ticket's: ``prepared.hits`` alone counts that one)
                self.flight_recorder.note_tickets(hits=len(hits))
        return out

    def _finish(self, pending: _Pending):
        """Blocking fetch + host finalize for a dispatched request."""
        import time as _time

        from ..utils import tracker
        t0 = _time.perf_counter()
        # a lane of a multi-lane launch slices the launch's one readback
        fetched = pending.fetch() if isinstance(pending, _LanePending) \
            else self._readback(pending.tree)
        with tracker.phase("host_materialize"):
            out = pending.finalize(fetched)
        # a served request decays the slice's strike score (and feeds
        # the launch-latency outlier detector when configured)
        self._note_slice_ok(_time.perf_counter() - t0)
        return out

    @staticmethod
    def _apply_output_offsets(dag, result):
        if dag.output_offsets is not None:
            b = result.batch
            result.batch = ColumnBatch(
                [b.schema[i] for i in dag.output_offsets],
                [b.columns[i] for i in dag.output_offsets])
        return result

    @staticmethod
    def _meta_key(dag: DAGRequest, plan: _Plan) -> tuple:
        """What a request's memo is kept under.  Keyed on the full
        plan: hash_bounds / arg_nbytes depend on the key and argument
        expressions, not just on which columns are shipped.  For an
        aggregation the plan CONST-BLIND, but for the GROUP BY key's
        constants: what its memo holds (row count, dtypes, host planes,
        key bounds, byte-plane widths, the sparse recode) is a property
        of the data and of the key, so requests that differ in their
        predicates' and aggregates' constants share it and none of it
        is derived again for a new constant tuple."""
        if plan.kind in ("simple_agg", "hash_agg"):
            # (which DATE columns ride the int32 plane hangs on the
            # constants' VALUES, a time of day or none: device/lowering)
            return (dag.class_key(), pallas_hash.key_consts(plan),
                    plan.date_planes, dag.ranges)
        return (dag.plan_key(), dag.ranges)

    def _request_meta(self, storage, meta_key) -> dict:
        """Snapshot-lifetime memo for host-derived request constants
        (device dtypes, hash key bounds, byte-plane widths).  Anchored
        on the feed lineage when the snapshot is delta-maintained, so
        the memo survives patch generations (version-checked by
        ``_refresh_meta``)."""
        if not hasattr(storage, "scan_columns"):
            return {}
        per_storage = self._arena.bucket(feed_anchor(storage))
        if per_storage is None:         # anchor not trackable
            return {}
        return per_storage.setdefault(("meta", meta_key), {})

    def _refresh_meta(self, meta: dict, lineage, plan, from_v: int,
                      to_v: int, count_rows) -> None:
        """Roll a request memo forward across a feed-lineage gap.

        The row count drops (``count_rows`` re-learns it where the
        record's proofs ask).  The derived record — device dtypes, limbs,
        hash key bounds, byte-plane widths, the host planes — is rolled
        by ``feed.roll_derived`` from the rows the journal says the gap
        introduced: each constant is baked into a compiled kernel
        (capacity, plane count) or a value transform (dtype narrowing),
        so keeping a violated one would corrupt results while dropping a
        valid one only costs a re-derivation (still no MVCC rebuild).
        Sparse key recodes always drop: new rows have no slot ids.
        """
        meta.pop("n_rows", None)
        meta.pop("sparse_slots", None)
        if meta.pop("prepared", None) is not None:
            # (the record, and with it the line's launch class: both
            # re-learnt by the next launch, which stages in full)
            self.flight_recorder.note_prepared("refresh")
        meta.pop("key_dense", None)     # (likewise: run_hash)
        meta.pop("key_dense_tiled", None)
        from ..utils import tracker
        with tracker.held("memo_roll") as roll:
            roll.note(outcome=roll_derived(
                meta, plan, lineage.since(from_v, until=to_v), count_rows,
                self._limb_variant, self.flight_recorder, lineage.depth))
        meta["lineage_v"] = to_v

    def _result(self, dag, schema, columns) -> "SelectResult":
        from ..executors.runner import SelectResult
        return SelectResult(ColumnBatch(schema, columns), [])

    def _kern_key(self, kind, dag, feed, chunk, *extra):
        return (kind, dag.plan_key(), feed["null_flags"], feed["n_pad"],
                chunk) + extra

    # -- selection (late materialization: predicate on device, COMPACT
    #    selection vector over D2H, alive-mask-aware host gather) --

    def _sel_route_note(self, route: str) -> None:
        from ..utils import metrics as m
        from ..utils import tracker
        tracker.label("routing", route)
        m.DEVICE_SEL_ROUTE_COUNTER.labels(route).inc()
        with self._sel_mu:
            self._sel_route_counts[route] = \
                self._sel_route_counts.get(route, 0) + 1

    def _run_scan_sel(self, dag, plan, dtypes, n, get_batch, feed,
                      storage, stack=None):
        """Device selection whose D2H volume scales with SELECTED rows.

        One fused dispatch evaluates the predicates over the resident
        feed and leaves (count, packed bitmask, bool mask) on device.
        The router (selection.choose_route) then moves the cheapest
        selection vector: the packed mask (n/8 bytes), compacted row
        indices (4·K bytes, second tiny dispatch consuming the resident
        mask), or — small k on a single device — the projected columns
        themselves, compacted on device so the host gather is skipped.
        NOTHING blocks under the dispatch lock: cold requests take the
        always-correct mask route while the device-side count — a
        scalar leaf of every route's readback — rides home with the
        result and seeds the per-plan selectivity EWMA; warm requests
        route by the EWMA with capacity headroom (an undersized
        capacity surfaces as an overflow flag at fetch time and falls
        back to the still-resident packed mask — never a truncated
        result).
        """
        from . import selection as selmod
        from ..utils import tracker as _tracker
        n_pad = feed["n_pad"]
        n_local = n_pad // self._nshards()
        stat_keys = self._sel_keys(dag, plan)

        if plan.sel_params is None:
            plan.sel_params = selmod.split_params(plan.sel_rpns,
                                                  len(plan.used_cols))
        param_rpns, param_vals, param_dts = plan.sel_params
        if stack is not None:
            # cross-request STACKED dispatch (server/coalescer.py):
            # every member's hoisted constants ride a leading group
            # axis of the traced scalar params and the whole group is
            # ONE launch + ONE shared D2H.  Pow2 lane buckets keep the
            # compile classes logarithmic; dead lanes repeat lane 0's
            # params and are sliced away by the per-member resolve.
            # Always the packed-mask payload — the always-correct
            # route, since per-member counts are unknown at dispatch.
            G = len(stack)
            gb = 1 << max(0, (G - 1).bit_length())
            bkey = ("selmaskb", selmod.shape_key(plan),
                    feed["null_flags"], n_pad, tuple(dtypes),
                    param_dts, gb)
            bkern = self._shard_kernel(
                bkey, lambda: selmod.build_batched_mask_kernel(
                    param_rpns, feed["null_flags"], n_pad,
                    len(feed["flat"]), len(param_dts), gb))
            lanes = []
            for pi, dt in enumerate(param_dts):
                vals = [stack[g][pi] for g in range(G)]
                vals += [vals[0]] * (gb - G)
                lanes.append(jnp.asarray(
                    np.asarray(vals, dtype=np.dtype(dt))))
            with self._dispatch_phase("scan_sel_batched", bkey):
                counts_dev, packed_dev = bkern(
                    self._cached_scalar(n, jnp.int64), *lanes,
                    *feed["flat"])
            self._sel_route_note("batched")
            return _Pending(
                (counts_dev, packed_dev),
                lambda fetched: (np.asarray(fetched[0]),
                                 np.asarray(fetched[1]), n),
                small=False)
        # const-blind kernel key: repeated selections at differing
        # thresholds within one n_pad bucket share ONE compile class
        skey = ("selmask", selmod.shape_key(plan), feed["null_flags"],
                n_pad, tuple(dtypes), param_dts)
        kern = self._shard_kernel(skey, lambda: selmod.build_mask_kernel(
            param_rpns, feed["null_flags"], n_pad, len(feed["flat"]),
            len(param_dts), None if self._single else self._mesh))
        params = tuple(self._cached_param(v, dt)
                       for v, dt in zip(param_vals, param_dts))
        with self._dispatch_phase("scan_sel_mask", skey):
            count_dev, packed_dev, mask_dev = kern(
                self._cached_scalar(n, jnp.int64), *params, *feed["flat"])

        pred = self._sel_predict(stat_keys)
        if pred is None:
            # cold: take the always-correct mask route rather than sync
            # the count here — this runs under _dispatch_mu, and a
            # blocking D2H would serialize every in-flight dispatch
            # behind this kernel (the lock's contract: fetches block
            # OUTSIDE it).  The count leaf seeds the EWMA at finalize.
            route = selmod.ROUTE_MASK
            cap = 0
        else:
            k_est = pred * n
            cap = selmod.index_capacity(k_est * 1.5 + 64, n_local)
            # the index comparison uses the REAL transfer — per-shard
            # pow2 capacity × shard count — not 4·k, which understates
            # it several-fold near the crossover
            route = selmod.choose_route(
                n, k_est, plan.compact_ok and self._single,
                idx_bytes=4 * cap * self._nshards())
        gather_ok = isinstance(plan.scan, TableScanDesc) and \
            hasattr(storage, "gather_rows")

        def gather(sel):
            """sel: bool mask over the scan output, or ascending feed
            positions.  The columnar snapshot's alive-mask-aware
            vectorized take (ColumnarTable.gather_rows) serves both;
            storages without it (row-codec fixtures) pay the batch."""
            if gather_ok:
                out = storage.gather_rows(plan.scan, dag.ranges, sel)
            else:
                b = get_batch()
                out = b.filter(sel) if sel.dtype == np.bool_ else b.take(sel)
            return self._result(dag, out.schema, out.columns)

        def mask_from_packed(packed_np):
            return np.unpackbits(packed_np)[:n].astype(np.bool_)

        def observe(cnt) -> int:
            k = int(cnt)
            self._sel_observe(stat_keys, k / n if n else 0.0)
            return k

        def fallback_to_mask():
            # predicted capacity undersized: the packed bitmask is
            # still device-resident — fetch it instead (plain D2H, no
            # dispatch lock needed)
            self._sel_route_note("mask_fallback")
            _tracker.label("routing", selmod.ROUTE_MASK)
            return gather(mask_from_packed(np.asarray(packed_dev)))

        if route == selmod.ROUTE_COMPACT:
            ckey = ("selcompact", n_pad, cap, feed["null_flags"],
                    tuple(dtypes))
            ckern = self._shard_kernel(
                ckey, lambda: selmod.build_compact_kernel(
                    n_pad, cap, feed["null_flags"]))
            with self._dispatch_phase("scan_sel_compact", ckey):
                outs_dev, ovf_dev = ckern(mask_dev, *feed["flat"])
            self._sel_route_note(route)
            scan_cols = plan.scan.columns

            def fin_compact(fetched):
                cnt, outs, ovf = fetched
                k = observe(cnt)
                if int(ovf):
                    return fallback_to_mask()
                schema, cols = [], []
                oi = 0
                for ci, info in enumerate(scan_cols):
                    et = EvalType.INT if info.is_pk_handle \
                        else info.field_type.eval_type
                    vals = outs[oi][:k]
                    oi += 1
                    if feed["null_flags"][ci]:
                        valid = outs[oi][:k].astype(np.bool_)
                        oi += 1
                    else:
                        valid = np.ones(k, np.bool_)
                    hdt = np.uint64 if et is EvalType.DATETIME else np.int64
                    schema.append(info.field_type)
                    cols.append(Column(et, vals.astype(hdt, copy=False),
                                       valid))
                return self._result(dag, schema, cols)

            payload = cap * (sum(np.dtype(ds).itemsize for ds in dtypes)
                             + sum(feed["null_flags"]))
            return _Pending((count_dev, outs_dev, ovf_dev), fin_compact,
                            small=payload <= (1 << 16))

        if route == selmod.ROUTE_INDEX:
            # plan-independent kernels: every selection shares them
            ikey = ("selidx", n_pad, cap)
            ikern = self._shard_kernel(
                ikey, lambda: selmod.build_index_kernel(
                    n_pad, cap, None if self._single else self._mesh))
            with self._dispatch_phase("scan_sel_index", ikey):
                idx_dev, ovf_dev = ikern(mask_dev)
            self._sel_route_note(route)

            def fin_index(fetched):
                cnt, idx, ovf = fetched
                observe(cnt)
                if int(ovf):
                    return fallback_to_mask()
                ids = np.asarray(idx, dtype=np.int64)
                return gather(ids[ids >= 0])

            # "small" is a completion-pool priority hint for KB-class
            # fetches; a capacity near the 3.1% crossover can be MBs
            return _Pending((count_dev, idx_dev, ovf_dev), fin_index,
                            small=4 * cap * self._nshards() <= (1 << 16))

        self._sel_route_note(selmod.ROUTE_MASK)

        def fin_mask(fetched):
            cnt, packed = fetched
            observe(cnt)
            return gather(mask_from_packed(packed))

        return _Pending((count_dev, packed_dev), fin_mask, small=False)

    # -- top-n --

    def _run_topn(self, dag, plan, host_cols, dtypes, n, get_batch, feed):
        k = plan.limit
        n_used = None
        if self._single:
            seg = math.gcd(feed["n_pad"], 1 << 17)
            n_used = min(feed["n_pad"], -(-n // seg) * seg)
        key = self._kern_key("topn", dag, feed, 0, tuple(dtypes), k,
                             n_used)
        kern = self._shard_kernel(
            key, lambda: self._build_topn_kernel(
                plan, len(plan.used_cols), k, feed["null_flags"],
                feed["n_pad"], len(feed["flat"]), n_used=n_used))
        with self._dispatch_phase("topn", key):
            ys = kern(self._cached_scalar(n, jnp.int64), *feed["flat"])

        def fin(fetched):
            gidx_s, mask_s, ok_s = fetched
            gidx = gidx_s.reshape(-1)
            mask = mask_s.reshape(-1)
            ok = ok_s.reshape(-1)
            sel = mask & (gidx < n)
            gidx, okk = gidx[sel], ok[sel]
            # exact host ordering over <= k * n_chunks * n_shards
            # candidates: evaluate the order expression only on the
            # gathered candidate rows (plan rpns are remapped onto
            # host_cols positions)
            cand_cols = [(v[gidx], m[gidx]) for v, m in host_cols()]
            ov, _om = eval_rpn(plan.order_rpn, cand_cols, len(gidx), np)
            ov = np.broadcast_to(ov, (len(gidx),))
            if plan.order_rpn.ret_type in (EvalType.INT, EvalType.DATETIME,
                                           EvalType.DURATION):
                # exact int ordering (no f64 collapse above 2^53 — a
                # packed DATETIME core at ~2^61 loses sub-millisecond
                # bits in f64); NULL is the smallest value, so asc →
                # NULL first, desc → NULL last.  Clamp to min+2 so
                # negation cannot overflow.  DATETIME u64 cores are
                # < 2^63 (feed guard) so the int64 view is
                # order-preserving.
                lo, hi = np.iinfo(np.int64).min, np.iinfo(np.int64).max
                vals = np.maximum(np.asarray(ov).astype(np.int64), lo + 2)
                if plan.order_desc:
                    skey = np.where(okk, -vals, hi)
                else:
                    skey = np.where(okk, vals, lo)
                order = np.lexsort((gidx, skey))
            else:
                vals = np.asarray(ov, dtype=np.float64)
                keyf = np.where(okk, vals, -np.inf)     # NULL smallest
                order = np.lexsort((gidx,
                                    -keyf if plan.order_desc else keyf))
            take = gidx[order[:plan.limit]]
            out = get_batch().take(take)
            return self._result(dag, out.schema, out.columns)

        return _Pending(ys, fin, small=False)


class _AnalyzeKernels:
    """Per-(dtype, n_pad, buckets) jitted ANALYZE kernels.

    One ``jnp.sort`` per column is the whole cost — XLA's on-device sort
    runs at HBM speed, which is exactly why ANALYZE belongs on the TPU
    (SURVEY §2.4: statistics; the reference's sample collectors are a
    CPU workaround for not having a fast sort).  NULL/padding rows key
    past every real value; null count, distinct count (boundary diffs)
    and the equi-depth bucket bounds all fall out of the same sorted
    array, gathered at rank positions ON DEVICE so one packed (2B+2,)
    int64 vector comes back per column.

    Measured (v5e, 20M int32 rows): on-device sort ~4ms vs numpy 660ms
    (~160x).  End-to-end request time on a co-located chip (H2D +
    fetch sync per column, overlapped across columns): not measured.
    """

    def __init__(self):
        self._cache: dict = {}

    def get(self, dtype, n_pad: int, n_buckets: int):
        key = (str(dtype), n_pad, n_buckets)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._cache[key] = self._build(np.dtype(dtype),
                                                n_buckets)
        return fn

    @staticmethod
    def _build(dt, n_buckets: int):
        is_f = dt.kind == "f"
        # sort in the column's NATIVE dtype — an int64 up-cast would put
        # the whole sort on the pair-emulated path (measured 4x slower
        # than host numpy at 20M rows; native int32 sort beats it).
        # Int sentinel for NULL/padding = dtype max: a real value EQUAL
        # to the sentinel interleaves with the padding block, but rank
        # gathers read the same numeric value and equal values stay
        # adjacent for the distinct count — results unchanged.  Float
        # sentinel must be NaN, NOT +inf: jnp.sort puts NaNs last, so
        # an inf sentinel would sort BEFORE a column's real NaNs and
        # leak padding into the valid prefix; with NaN fills, valid
        # NaNs and padding share one tail block whose prefix slice is
        # value-identical to the host's np.sort(valid) ordering (each
        # NaN counts distinct on both paths — NaN != NaN).
        if is_f:
            sent = dt.type(np.nan)
        else:
            sent = np.iinfo(dt).max

        def kern(values, validity, n_arr):
            n_pad = values.shape[0]
            iota = jnp.arange(n_pad, dtype=jnp.int64)
            mask = (iota < n_arr) & validity
            key = jnp.where(mask, values, jnp.asarray(sent, values.dtype))
            s = jnp.sort(key)
            n_valid = jnp.sum(mask, dtype=jnp.int64)
            in_prefix = iota[1:] < n_valid
            distinct = jnp.sum((s[1:] != s[:-1]) & in_prefix,
                               dtype=jnp.int64) + \
                jnp.where(n_valid > 0, 1, 0)
            # equi-depth rank positions over the VALID prefix
            b = jnp.arange(1, n_buckets + 1, dtype=jnp.int64)
            ranks = jnp.maximum((b * n_valid) // n_buckets - 1, 0)
            bounds = jnp.take(s, ranks)
            # ONE packed int64 output → ONE D2H fetch instead of four
            # blocking fetches per column.  Floats ride bit-cast; ints
            # widen losslessly.
            if is_f:
                bits = lax.bitcast_convert_type(
                    bounds.astype(jnp.float64), jnp.int64)
            else:
                bits = bounds.astype(jnp.int64)
            return jnp.concatenate([
                bits, ranks + 1,
                jnp.stack([n_valid, distinct])])

        return jax.jit(named_program(kern, "analyze_column"))


def _analyze_on_device(runner, dag, storage, n_buckets: int):
    """DeviceRunner.handle_analyze body (module-level to keep the class
    focused on DAG execution).  Returning None routes the request to
    the host analyze path — including when a device::* failpoint fires
    inside the dispatch/fetch (the degrade contract)."""
    if runner._placer is not None and hasattr(storage, "scan_columns"):
        # placement: ANALYZE sorts are single-device kernels — run them
        # on the region's placed slice instead of declining shard-wide
        target = runner._placer.route(storage)
        if target is not runner:
            return _analyze_on_device(target, dag, storage, n_buckets)
    try:
        with runner._device_scope():
            return _analyze_on_device_impl(runner, dag, storage,
                                           n_buckets)
    except _FallbackToHost:
        return None


def _analyze_on_device_impl(runner, dag, storage, n_buckets: int):
    from ..copr.analyze import ColumnStats, analyze_columns
    if not runner._single:
        # a global sort across shards needs an all-to-all; stats merge
        # across hosts happens at the PD/stats layer instead
        return None
    scan = dag.executors[0]
    plan = _Plan(scan=scan, kind="scan", used_cols=[])
    batch = runner._scan_batch(dag, plan, storage)
    n = batch.num_rows
    if n == 0:
        return analyze_columns(batch, scan.columns, n_buckets)
    if not hasattr(runner, "_analyze_kernels"):
        runner._analyze_kernels = _AnalyzeKernels()
    # phase 1 — dispatch EVERY device column before any blocking fetch
    # so the per-column sorts and transfers overlap
    pending: dict = {}
    out_by_idx: dict = {}
    host_cols_idx: list = []
    for i, info in enumerate(scan.columns):
        col = batch.columns[i]
        et = col.eval_type
        if et not in _DEVICE_ETS or (
                col.values.dtype == np.uint64 and col.values.size
                and int(col.values.max()) >= (1 << 63)) or (
                et is EvalType.REAL and runner._is_tpu):
            # BYTES/JSON/etc, beyond-int64 cores, or REAL on a TPU
            # (exact stats need IEEE float64, which the chip does not
            # have: the float64 kernel's bitcast is UNIMPLEMENTED
            # there — found on v5e, libtpu 0.0.34): host numpy path —
            # DEFERRED until every device column has been dispatched
            # (a python-object sort here would serialize in front of
            # the device work this split exists to overlap)
            host_cols_idx.append(i)
            continue
        # stats must be EXACT: REAL keeps float64 (the f32 device column
        # resolution would collapse near-equal doubles, changing
        # distinct counts and bucket bounds)
        dt = np.dtype(np.float64) if et is EvalType.REAL \
            else _device_dtype(et, col.values)
        n_pad = runner._feeds.pad_rows(n)
        vals = np.zeros(n_pad, dtype=dt)
        vals[:n] = col.values.astype(dt, copy=False)
        valid = np.zeros(n_pad, dtype=np.bool_)
        valid[:n] = col.validity
        kern = runner._analyze_kernels.get(dt, n_pad, n_buckets)
        pending[i] = (info, et, kern(
            jnp.asarray(vals), jnp.asarray(valid),
            jnp.asarray(n, jnp.int64)))
    # host-fallback columns run while the device crunches the rest
    for i in host_cols_idx:
        out_by_idx[i] = analyze_columns(
            ColumnBatch([batch.schema[i]], [batch.columns[i]]),
            [scan.columns[i]], n_buckets)[0]
    # phase 2 — ONE batched readback for every column (copy_to_host
    # issued for all before the first blocking fetch), then unpack
    fetched = runner._readback({i: dev for i, (_info, _et, dev)
                                in pending.items()})
    for i, (info, et, _dev) in pending.items():
        packed = fetched[i]
        bits = packed[:n_buckets]
        counts = packed[n_buckets:2 * n_buckets]
        n_valid = int(packed[-2])
        distinct = int(packed[-1])
        bounds = bits.view(np.float64) if et is EvalType.REAL else bits
        buckets = []
        prev = 0
        for bnd, cnt in zip(bounds.tolist(), counts.tolist()):
            cnt = min(int(cnt), n_valid)
            if cnt <= prev:     # degenerate bucket (n_valid < buckets)
                continue
            buckets.append((float(bnd) if et is EvalType.REAL
                            else int(bnd), cnt))
            prev = cnt
        out_by_idx[i] = ColumnStats(info.col_id, n, n - n_valid,
                                    distinct, buckets)
    return [out_by_idx[i] for i in range(len(scan.columns))]


# bound as a method so the endpoint's hasattr(runner, "handle_analyze")
# routing sees it (endpoint.handle_analyze)
DeviceRunner.handle_analyze = _analyze_on_device
