"""Coprocessor endpoint — request parsing + handler dispatch.

Reference: src/coprocessor/endpoint.rs (Endpoint::parse_and_handle_unary_
request :546, request type dispatch mod.rs:57-59: DAG=103, ANALYZE=104,
CHECKSUM=105) and dag/mod.rs (DagHandlerBuilder). The endpoint owns:

- snapshot acquisition from the storage layer (here: a ScanStorage
  provider keyed by region — the MVCC snapshot feed once layers 0-4 land);
- backend routing: device (TPU) runner for plans/sizes that profit, host
  numpy runner otherwise (reference routes everything to CPU;
  SURVEY.md §7 "Latency" requires keeping the CPU fast path);
- exec summary / warning collection into the response.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from typing import TYPE_CHECKING

from .dag import DAGRequest

if TYPE_CHECKING:  # avoid circular import (executors.runner uses copr.dag)
    from ..executors.runner import SelectResult
    from ..executors.storage import ScanStorage

REQ_TYPE_DAG = 103
REQ_TYPE_ANALYZE = 104
REQ_TYPE_CHECKSUM = 105


@dataclass
class CopRequest:
    """Reference: coppb::Request (tp + data + ranges + start_ts +
    paging_size for the paged/streaming variants)."""

    tp: int
    dag: DAGRequest
    # device routing hint; None = auto (estimated row count)
    force_backend: Optional[str] = None
    # > 0: return at most ~paging_size result rows per response and a
    # resume token (endpoint.rs:760-823); always served by the host
    # pipeline (pages bound RESULT materialization; the scan itself is
    # zero-copy columnar views).  resume_token = last returned handle
    # from the previous page (stable across snapshots)
    paging_size: int = 0
    resume_token: object = None
    # resource attribution (kvrpcpb Context resource_group_tag /
    # request_source — resource_metering tag.rs)
    resource_group: str = "default"
    request_source: str = ""
    # kvproto Context.stale_read: serve from THIS replica's applied
    # state with no consensus round trip, gated at the node on
    # dag.start_ts ≤ the region's resolved-ts watermark (DataIsNotReady
    # on miss) — the follower device-serving read path
    stale_read: bool = False
    # fast-path learning channel (server/fastpath.py): when the service
    # wants to learn a wire template from this request, it installs a
    # dict here and the endpoint/node fill in what the execution
    # learned (storage, backend, route decision, batch key, region)
    fp_learn: Optional[dict] = None
    # kvproto Context.region_id / region_epoch.version: the region the
    # client cut this task's ranges for.  When set, the node refuses
    # (EpochNotMatch) to serve from any other region or epoch — a task
    # clipped to bounds that a split has since moved would otherwise be
    # answered, in silence, with only the rows the new region holds
    region_ctx: Optional[tuple] = None


@dataclass
class CopResponse:
    result: "SelectResult"
    elapsed_ns: int = 0
    backend: str = "host"
    # how the request asked for its result (``DAGRequest.encode_type``):
    # what the serving legs encode it as where they can
    # (server/wire.py ``enc_cop_body``)
    encode_type: str = "rows"

    def rows(self):
        return self.result.rows()

    @property
    def is_drained(self) -> bool:
        return self.result.is_drained

    @property
    def resume_token(self):
        return self.result.resume_token


class Endpoint:
    """Unary coprocessor endpoint over a snapshot provider.

    ``snapshot_provider()`` returns a ScanStorage view of committed data —
    the seam where MVCC snapshots plug in (reference: endpoint.rs acquires
    an engine snapshot per request, then TikvStorage adapts it).
    """

    # Default device routing threshold (overridable per deployment via
    # config coprocessor.device_row_threshold).  The crossover is
    # TRANSPORT-bound, not kernel-bound: the fused direct-index kernel
    # costs ~n / 9.4e9 s (11 µs at 100k rows — negligible), so a device
    # request's floor is its dispatch + D2H sync round trip, ~1-2 ms on
    # co-located chips.  The vectorized host pipeline runs ~40-130 M
    # rows/s on agg shapes, i.e. ~1-3 ms at 2^17 rows — the break-even
    # point — and below it the host answer arrives before the device
    # sync would.  2^17 (was 2^18 pre-recovery: the XLA scan paths also
    # paid per-step + fusion-boundary costs that the Pallas kernel
    # removed, moving the crossover down ~2×).  The same 2^17 figure
    # holds for late-materialized selections (device/selection.py): a
    # warm selection's floor is also one dispatch + one compact D2H
    # (n/8-byte mask at worst), so the break-even against the ~100 M
    # rows/s host predicate pass lands in the same bucket — the
    # selection-specific crossover that remains is SELECTIVITY, owned
    # by the runner's per-plan EWMA router, not by this row count.
    #
    # UNDER CONCURRENCY the launch-overhead side of this break-even no
    # longer belongs to one request: the coalescer
    # (server/coalescer.py) stacks co-resident same-compile-class
    # requests into one dispatch, dividing the fixed launch + D2H-sync
    # tax by the group occupancy.  This threshold therefore keeps its
    # meaning as the SOLO break-even — the zero-load anchor the cost
    # router calibrates its host model against ((n / threshold) × the
    # live launch EWMA) — while the effective device crossover at load
    # sits below it by roughly the observed occupancy.  The router owns
    # that shift per request; do not fold expected batching into this
    # constant.
    #
    # MULTI-CHIP meshes keep the same single-chip figure: a whole-mesh
    # sharded dispatch amortizes its per-launch overhead across chips
    # (the Jouppi batch-amortization argument applied to mesh axes),
    # but the sync floor it must beat is unchanged, and a
    # placement-routed request (device/placement.py) executes on ONE
    # slice anyway — so the solo break-even stays the anchor and the
    # mesh only moves the large-n end of the curve.
    DEFAULT_DEVICE_ROW_THRESHOLD = 131072

    def __init__(self, snapshot_provider: Callable[[CopRequest], "ScanStorage"],
                 device_runner: Optional[object] = None,
                 device_row_threshold: int = DEFAULT_DEVICE_ROW_THRESHOLD,
                 completion_workers: int = 8,
                 coalescer: Optional[object] = None):
        self._snapshot_provider = snapshot_provider
        self._device_runner = device_runner
        self._device_row_threshold = device_row_threshold
        # cross-request device batching (server/coalescer.py): the
        # coalescing dispatcher + cost-based admission router in front
        # of the device backend; None = every request dispatches solo
        self.coalescer = coalescer
        if coalescer is not None:
            coalescer.bind(self)
        # plan-IR executor (copr/plan_ir.py): lazily built — DAG-only
        # traffic never pays for it.  Owns the per-fragment router and
        # the join/sort/window execution (handle_plan).
        self._plan_executor = None
        self._plan_mu = threading.Lock()
        # deferred D2H fetches resolve on a small shared pool so N
        # in-flight requests overlap their transfer waits (handle_async)
        self._completion_workers = completion_workers
        self._completion_pool = None
        self._completion_mu = threading.Lock()
        # capability probe, resolved once: plugin backends registered
        # without the ``deferred`` kwarg stay unary (probing the
        # signature up front keeps execution errors out of the
        # capability decision — a TypeError raised INSIDE a run must
        # degrade, not silently re-execute the request)
        self._runner_deferred: Optional[bool] = None
        # (a device-served request's "mesh" label is set by the runner
        # that launched it: device/runner.py _dispatch_phase)

    def close(self) -> None:
        """Release the coalescer's dispatcher and the completion
        pool's worker threads.  Server nodes call this on stop;
        long-lived endpoints never need to."""
        if self.coalescer is not None:
            # before the completion pool: still-parked groups dispatch
            # on close and resolve their members through the pool
            self.coalescer.close()
        with self._completion_mu:
            if self._completion_pool is not None:
                self._completion_pool.shutdown()
                self._completion_pool = None

    def _supports_deferred(self) -> bool:
        if self._runner_deferred is None:
            import inspect
            try:
                sig = inspect.signature(self._device_runner.handle_request)
                self._runner_deferred = "deferred" in sig.parameters
            except (TypeError, ValueError):
                self._runner_deferred = False
        return self._runner_deferred

    def snapshot_for(self, req: CopRequest):
        """Public snapshot seam for streaming handlers that drive their
        own runner (copr_stream): same provider the unary path uses."""
        return self._snapshot_provider(req)

    def handle_analyze(self, areq, storage=None) -> dict:
        """tp=104 (src/coprocessor/statistics/, endpoint.rs:275-312):
        per-column equi-depth histogram + distinct/null counts.

        Device routing mirrors DAG requests: big snapshots sort on the
        TPU (XLA sort at HBM speed), small ones on numpy.
        """
        from ..copr.dag import DAGRequest
        from .analyze import analyze_columns
        dag = DAGRequest((areq.scan,), tuple(areq.ranges),
                         start_ts=areq.start_ts)
        creq = CopRequest(REQ_TYPE_ANALYZE, dag)
        if storage is None:
            storage = self._snapshot_provider(creq)
        runner = self._device_runner
        est = getattr(storage, "estimated_rows", None)
        n = est() if callable(est) else None
        if runner is not None and n is not None and \
                n >= self._device_row_threshold and \
                hasattr(runner, "handle_analyze"):
            stats = runner.handle_analyze(dag, storage, areq.buckets)
            if stats is not None:
                return {"columns": stats}
        from ..executors.runner import BatchExecutorsRunner
        result = BatchExecutorsRunner(dag, storage).handle_request()
        return {"columns": analyze_columns(result.batch,
                                           areq.scan.columns,
                                           areq.buckets)}

    def handle_checksum(self, creq, storage=None) -> dict:
        """tp=105 (src/coprocessor/checksum.rs): crc64-xz XOR-folded
        over the request range's KV pairs (native crc when compiled)."""
        from ..copr.dag import DAGRequest
        from .analyze import checksum_kv_pairs
        dag = DAGRequest((creq.scan,), tuple(creq.ranges),
                         start_ts=creq.start_ts)
        req = CopRequest(REQ_TYPE_CHECKSUM, dag)
        if storage is None:
            storage = self._snapshot_provider(req)
        if not hasattr(storage, "to_kv_pairs"):
            raise NotImplementedError(
                "checksum requires a table snapshot feed")
        # checksum over the LOGICAL rows (record key + row payload)
        # WITHIN the request's ranges: identical visible content ⇒
        # identical checksum on every replica, independent of MVCC
        # garbage — the consistency-check contract the admin command
        # needs
        pairs = storage.to_kv_pairs(tuple(creq.ranges) or None)
        keys = [k for k, _ in pairs]
        vals = [v for _, v in pairs]
        return checksum_kv_pairs(keys, vals)

    def handle(self, req: CopRequest) -> CopResponse:
        """Synchronous unary execution: dispatch + wait in one call."""
        return self.handle_async(req).wait()

    @property
    def plan_executor(self):
        with self._plan_mu:
            if self._plan_executor is None:
                from .plan_ir import PlanExecutor
                self._plan_executor = PlanExecutor(self)
            return self._plan_executor

    def handle_plan(self, preq, force_backend: Optional[str] = None,
                    resource_group: str = "default",
                    request_source: str = "") -> CopResponse:
        """Execute a plan-IR request (copr/plan_ir.py) — the operator
        superset the linear DAG path cannot express (join/sort/window,
        mixed per-fragment host/device routing).

        One snapshot is acquired PER SCAN LEAF through the same
        provider the unary path uses (a join's two sides each route by
        their own first key range), the fragment router places each
        fragment host/device, and byte-identical join plans share one
        execution through the coalescer's plan share class."""
        from ..resource_metering import (
            GLOBAL_RECORDER,
            ResourceTagFactory,
            region_of,
            set_region,
        )
        from ..utils import metrics as m
        from ..utils import tracker
        from ..utils.deadline import check_current as _dl_check
        tag = ResourceTagFactory.tag(resource_group, request_source)
        t0 = time.perf_counter_ns()
        _dl_check("plan_admission")
        with GLOBAL_RECORDER.attach(tag):
            leaves = preq.scan_leaves()
            storages = {}
            anchors = []
            for leaf in leaves:
                sub = CopRequest(REQ_TYPE_DAG, DAGRequest(
                    (leaf.scan,), tuple(leaf.ranges),
                    start_ts=preq.start_ts))
                storage = self._snapshot_provider(sub)
                storages[id(leaf)] = storage
                lineage = getattr(storage, "feed_lineage", None)
                v = getattr(storage, "feed_version", None)
                if lineage is not None and v is None:
                    v = lineage.version
                anchors.append((id(storage if lineage is None
                                   else lineage), v))
            if storages:
                # region attribution: bill the plan's device charges
                # to its FIRST scan leaf's region (a join's probe side
                # — the side that owns the big feed)
                set_region(region_of(next(iter(storages.values()))))
            ex = self.plan_executor

            def run():
                return ex.execute(preq, storages, force_backend)

            coal = self.coalescer
            if coal is not None and preq.has_join() and \
                    force_backend is None and \
                    hasattr(coal, "submit_shared"):
                # join plans get a batch class: byte-identical plans
                # over the same snapshot generations share ONE
                # execution (the thundering-herd share-group semantics
                # applied to the plan path)
                result, scanned = coal.submit_shared(
                    ("plan", preq.plan_key(), tuple(anchors)), run)
            else:
                result, scanned = run()
            GLOBAL_RECORDER.record_read_keys(scanned)
            tracker.add_scan(scanned)
        tracker.label("backend", "plan")
        elapsed = time.perf_counter_ns() - t0
        m.COPR_REQ_COUNTER.labels("plan").inc()
        m.COPR_REQ_DURATION.labels("plan").observe(elapsed / 1e9)
        return CopResponse(result, elapsed, "plan", preq.encode_type)

    def _completion(self):
        with self._completion_mu:
            if self._completion_pool is None:
                from ..server.read_pool import CompletionPool
                self._completion_pool = CompletionPool(
                    self._completion_workers)
            return self._completion_pool

    def handle_async(self, req: CopRequest) -> "CopDeferred":
        """Dispatch-now / fetch-later execution (the production serving
        path).

        Device-routed requests return as soon as the kernel is
        enqueued: the D2H fetch + host finalize run on the shared
        completion pool, and ``wait()`` joins.  The caller (the gRPC
        service) holds its read-pool slot only for the dispatch, so N
        warm requests in flight overlap dispatch/compute/fetch instead
        of serializing on the device transport's sync round trip — and
        big scans waiting on D2H never starve point reads of read-pool
        slots.  Host and paged requests execute inline and come back
        already resolved; the degrade-to-host contract (any device
        fault, unless force_backend="device") holds on both the
        dispatch and the deferred-fetch side.
        """
        from ..resource_metering import (
            GLOBAL_RECORDER,
            ResourceTagFactory,
            region_of,
            set_region,
        )
        from ..utils import tracker
        if req.tp != REQ_TYPE_DAG:
            raise NotImplementedError(f"request type {req.tp}")
        tag = ResourceTagFactory.tag(req.resource_group,
                                     req.request_source)
        t0 = time.perf_counter_ns()
        with GLOBAL_RECORDER.attach(tag):
            storage = self._snapshot_provider(req)
            # region attribution: the snapshot resolved the feed
            # anchor, so hot-region metering can bill this request's
            # device charges to its region from here on
            set_region(region_of(storage))
            backend = self._pick_backend(req, storage)
            tracker.label("backend", backend)

            def host_exec():
                from ..executors.runner import BatchExecutorsRunner
                with tracker.phase("host_exec"):
                    return BatchExecutorsRunner(
                        req.dag, storage).handle_request()

            if req.fp_learn is not None:
                req.fp_learn.update(storage=storage, backend=backend)
            if req.paging_size > 0:
                backend = "host"    # pages are a host-pipeline contract
                tracker.label("backend", "host")
                from ..executors.runner import BatchExecutorsRunner
                with tracker.phase("host_exec"):
                    result = BatchExecutorsRunner(
                        req.dag, storage,
                        resume_token=req.resume_token).handle_request(
                            max_rows=req.paging_size)
                return CopDeferred(self, req, storage, tag, t0, backend,
                                   result=result)
            if backend != "device":
                return CopDeferred(self, req, storage, tag, t0, "host",
                                   result=host_exec())
            # deadline gate before the device dispatch: enqueueing a
            # kernel for an already-expired request burns accelerator
            # time and a completion-pool slot on an unusable answer
            from ..utils.deadline import check_current as _dl_check
            _dl_check("device_dispatch")
            # cost-based admission router (server/coalescer.py): a
            # device-eligible request may batch into a coalesced group
            # dispatch, stay solo, fall back to the host pipeline, or
            # shed with a retry hint — per-request, from measured
            # launch/transfer EWMAs.  Forced-device requests (parity
            # tests) bypass it: they contract for a raw solo dispatch.
            if self.coalescer is not None and req.force_backend is None:
                decision, bkey, hint = self.coalescer.route(req.dag,
                                                            storage)
                if req.fp_learn is not None:
                    req.fp_learn.update(decision=decision, bkey=bkey)
                    if decision in ("device_batched", "device_solo"):
                        est = getattr(storage, "estimated_rows", None)
                        n = est() if callable(est) else None
                        req.fp_learn["n_est"] = n
                        try:
                            req.fp_learn["d2h_bytes"] = \
                                self.coalescer.router._d2h_bytes(
                                    req.dag, n)
                        except Exception:   # noqa: BLE001 — model only
                            pass
                if decision == "shed":
                    from ..server.read_pool import ServerIsBusy
                    raise ServerIsBusy(
                        "device router: remaining budget below modeled "
                        "request cost", retry_after_ms=hint)
                if decision == "host":
                    tracker.label("backend", "host")
                    return CopDeferred(self, req, storage, tag, t0,
                                       "host", result=host_exec())
                if decision == "device_batched" and bkey is not None:
                    fut = self.coalescer.submit(bkey, req.dag, storage,
                                                tag=tag)
                    return CopDeferred(self, req, storage, tag, t0,
                                       backend, future=fut)
                # device_solo falls through to the direct dispatch
            elif req.fp_learn is not None:
                req.fp_learn.update(decision="device_solo", bkey=None)
            return self._dispatch_device_solo(req, storage, tag, t0,
                                              backend)

    def _dispatch_device_solo(self, req: CopRequest, storage, tag,
                              t0: int, backend: str) -> "CopDeferred":
        """The direct (uncoalesced) device dispatch tail shared by
        ``handle_async`` and the fast path: enqueue the kernel, hand
        the D2H fetch to the completion pool, degrade to host on a
        dispatch fault (unless the caller forced the device)."""
        from ..resource_metering import GLOBAL_RECORDER, region_of
        from ..utils import tracker
        try:
            if self._supports_deferred():
                out = self._device_runner.handle_request(
                    req.dag, storage, deferred=True)
            else:
                out = self._device_runner.handle_request(req.dag,
                                                         storage)
        except Exception:
            # a device fault (dispatch failure, runtime error,
            # unreachable accelerator) degrades the query to the
            # host pipeline instead of failing it; only an explicit
            # force_backend="device" (parity tests) surfaces it
            if req.force_backend == "device":
                raise
            import logging
            logging.getLogger(__name__).warning(
                "device backend failed; degrading to host",
                exc_info=True)
            tracker.label("backend", "host")
            tracker.label("degraded", "dispatch")
            from ..executors.runner import BatchExecutorsRunner
            with tracker.phase("host_exec"):
                result = BatchExecutorsRunner(
                    req.dag, storage).handle_request()
            return CopDeferred(self, req, storage, tag, t0, "host",
                               result=result)
        from ..device.runner import DeferredResult
        if not isinstance(out, DeferredResult):
            # host fallback / zero rows / cold build: already done
            return CopDeferred(self, req, storage, tag, t0, backend,
                               result=out)
        # the request's tracker rides to the completion worker so
        # d2h_wait/host_materialize still land in this request's
        # TimeDetail
        cur = tracker.current()

        reg = region_of(storage)

        def fetch():
            tok = tracker.adopt(cur) if cur is not None else None
            try:
                with GLOBAL_RECORDER.attach(tag, requests=0,
                                            region=reg):
                    return out.result()
            finally:
                if tok is not None:
                    tracker.uninstall(tok)

        fut = self._completion().submit(
            fetch, priority="high" if out.small else "normal")
        return CopDeferred(self, req, storage, tag, t0, backend,
                           future=fut)

    def handle_async_fast(self, req: CopRequest, storage,
                          ent) -> "CopDeferred":
        """Fast-path dispatch (server/fastpath.py): the decode products
        are pre-bound on the class entry ``ent`` and ``storage`` is the
        already-validated warm columnar snapshot — no provider walk, no
        plan re-analysis.  Everything LIVE is still consulted: the cost
        router's measured launch/backlog figures (via ``route_fast``),
        the deadline, and the degrade-to-host contract, so a fast-path
        request sheds, overflows to host, batches, and fails over
        exactly like its slow-path twin."""
        from ..resource_metering import (
            GLOBAL_RECORDER,
            region_of,
            set_region,
        )
        from ..utils import tracker
        from ..utils.deadline import check_current as _dl_check
        t0 = time.perf_counter_ns()
        tag = ent.tag
        with GLOBAL_RECORDER.attach(tag):
            set_region(region_of(storage))
            tracker.label("backend", "device")
            _dl_check("device_dispatch")
            coal = self.coalescer
            if coal is not None:
                bkey = None
                if coal.enabled:
                    bkey = ent.bkey if ent.share_fill is None \
                        else ent.share_fill(req.dag.plan_key())
                decision, bkey, hint = coal.router.route_fast(
                    ent.n_est, ent.d2h_bytes, bkey)
                if decision == "shed":
                    from ..server.read_pool import ServerIsBusy
                    raise ServerIsBusy(
                        "device router: remaining budget below modeled "
                        "request cost", retry_after_ms=hint)
                if decision == "host":
                    # live backlog overflow: the learned-device class
                    # still diverts to the host pipeline under device
                    # pile-up, exactly as the slow path would
                    tracker.label("backend", "host")
                    from ..executors.runner import BatchExecutorsRunner
                    with tracker.phase("host_exec"):
                        result = BatchExecutorsRunner(
                            req.dag, storage).handle_request()
                    return CopDeferred(self, req, storage, tag, t0,
                                       "host", result=result)
                if decision == "device_batched" and bkey is not None:
                    fut = coal.submit(bkey, req.dag, storage, tag=tag)
                    return CopDeferred(self, req, storage, tag, t0,
                                       "device", future=fut)
            return self._dispatch_device_solo(req, storage, tag, t0,
                                              "device")

    def _finish_response(self, d: "CopDeferred", result,
                         backend: str) -> CopResponse:
        """Shared completion tail: scanned-rows accounting + metrics."""
        from ..resource_metering import (
            GLOBAL_RECORDER,
            region_of,
            scanned_rows,
        )
        from ..utils import metrics as m
        from ..utils import tracker
        with GLOBAL_RECORDER.attach(d.tag, requests=0,
                                    region=region_of(d.storage)):
            if backend == "device" and not result.exec_summaries:
                # the device feed always scans the whole snapshot; its
                # results carry no per-operator summaries
                est = getattr(d.storage, "estimated_rows", None)
                n = est() if callable(est) else None
                n_scanned = n if n is not None else result.batch.num_rows
            else:
                n_scanned = scanned_rows(result)
            GLOBAL_RECORDER.record_read_keys(n_scanned)
            tracker.add_scan(n_scanned)
        elapsed = time.perf_counter_ns() - d.t0
        m.COPR_REQ_COUNTER.labels(backend).inc()
        m.COPR_REQ_DURATION.labels(backend).observe(elapsed / 1e9)
        return CopResponse(result, elapsed, backend,
                           d.req.dag.encode_type)

    def _degrade_at_wait(self, d: "CopDeferred"):
        """Deferred-fetch failure → host pipeline (unless forced)."""
        from ..resource_metering import GLOBAL_RECORDER, region_of
        from ..executors.runner import BatchExecutorsRunner
        from ..utils import tracker
        import logging
        logging.getLogger(__name__).warning(
            "deferred device fetch failed; degrading to host",
            exc_info=True)
        tracker.label("backend", "host")
        tracker.label("degraded", "fetch")
        with GLOBAL_RECORDER.attach(d.tag, requests=0,
                                    region=region_of(d.storage)):
            with tracker.phase("host_exec"):
                return BatchExecutorsRunner(
                    d.req.dag, d.storage).handle_request()

    def _pick_backend(self, req: CopRequest, storage) -> str:
        if req.force_backend in ("host", "device"):
            if req.force_backend == "device" and self._device_runner is None:
                raise RuntimeError("no device runner registered")
            if req.force_backend == "device" and \
                    not self._device_runner.supports(req.dag):
                raise RuntimeError("plan not supported by device backend")
            return req.force_backend
        if self._device_runner is None or not self._device_runner.supports(req.dag):
            return "host"
        profit = getattr(self._device_runner, "profitable", None)
        if profit is not None and not profit(req.dag):
            return "host"
        est = getattr(storage, "estimated_rows", None)
        n = est() if callable(est) else None
        if n is not None and n >= self._device_row_threshold:
            return "device"
        return "host"


class CopDeferred:
    """An in-flight coprocessor request (Endpoint.handle_async).

    ``wait()`` joins the deferred device fetch (or returns the inline
    host result), applies the endpoint's degrade-to-host policy to any
    fetch-side failure, runs the completion accounting, and memoizes —
    idempotent and thread-safe.
    """

    __slots__ = ("_endpoint", "req", "storage", "tag", "t0", "_backend",
                 "_result", "_future", "_mu", "_resp")

    def __init__(self, endpoint, req, storage, tag, t0, backend,
                 result=None, future=None):
        self._endpoint = endpoint
        self.req = req
        self.storage = storage
        self.tag = tag
        self.t0 = t0
        self._backend = backend
        self._result = result
        self._future = future
        self._mu = threading.Lock()
        self._resp = None

    @property
    def resolved(self) -> bool:
        return self._future is None

    def wait(self) -> CopResponse:
        with self._mu:
            if self._resp is None:
                backend = self._backend
                result = self._result
                if result is None:
                    try:
                        result = self._future.result()
                    except Exception:
                        # fetch-side fault: same contract as a dispatch
                        # fault — degrade unless the caller forced the
                        # device (parity tests want the raw error)
                        if self.req.force_backend == "device":
                            raise
                        result = self._endpoint._degrade_at_wait(self)
                        backend = "host"
                self._resp = self._endpoint._finish_response(
                    self, result, backend)
            return self._resp
