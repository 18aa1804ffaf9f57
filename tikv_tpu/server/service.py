"""The Tikv gRPC service handlers.

Reference: src/server/service/kv.rs — the ``Tikv`` service:
``handle_request!``-expanded unary KV RPCs (:251-410), ``coprocessor``
(:493), raft ingress (:684,737), plus the admin surface that backs
tikv-ctl (src/server/service/debug.rs).  Handlers are transport-agnostic
callables dict → dict; server.py binds them to gRPC methods — EXCEPT
the unary Coprocessor RPC, which is bound at the RAW-BYTES level
(``handle_raw``): a repeat-shape request is served by the compiled
fast path (server/fastpath.py) without ever decoding its body, and
only a template miss pays the historical decode-per-request pipeline
(which then doubles as the template learner).  Responses may come back
pre-packed (wire.pack_response passes bytes through).  ``batch_commands``
(:921) serves the same raw bytes as commands on one stream: a fan-out's
cop tasks reach ``handle_raw`` unchanged, on a bounded pool.
"""

from __future__ import annotations

import logging
import random
import re
import threading
import time
from typing import Callable, Optional

from ..copr.dag import DAGRequest
from ..copr.endpoint import CopRequest, Endpoint, REQ_TYPE_DAG
from ..copr.storage_impl import MvccScanStorage
from ..kv.engine import SnapContext
from ..raftstore import AdminCmd, Peer, RaftCmd
from ..storage import Storage
from ..storage.mvcc.reader import MvccReader
from ..storage.txn import commands as cmds
from ..storage.txn.actions import Mutation
from ..storage.txn_types import encode_key
from ..utils import tracker
from . import wire


# read RPCs dispatched through the read pool (src/read_pool.rs: both
# Storage reads and coprocessor share the unified pool); point reads get
# high priority so scans can't starve them
_READ_METHODS = {
    "KvGet": "high", "KvBatchGet": "high", "KvScan": "normal",
    "RawGet": "high", "RawBatchGet": "high", "RawScan": "normal",
    "Coprocessor": "normal",
}

# the txn write RPCs get a tracker too, under an envelope of their own
# (``txn_rpc`` / ``txn_accept_wait`` / ``txn_reply``: the reads' rows
# and ``coprocessor.requests_served`` never hold a write)
_WRITE_METHODS = wire.TXN_WRITE_METHODS

# ``clock_ns.sent`` is believed where it is at most this far behind the
# handler pool's stamp (the mirror of client.py ``_note_wire``)
_WIRE_SHARED_NS = 60 * 1_000_000_000

# the slow-query channel (TiKV slow_log!): one redacted line per
# request over coprocessor.slow_log_threshold_ms
_slow_query_logger = logging.getLogger("tikv_tpu.slow_query")

# client-supplied trace ids: opaque but BOUNDED — url-safe charset,
# ≤64 chars (they key the retention buffer and ride logs verbatim)
_TRACE_ID_RE = re.compile(r"[0-9A-Za-z_-]{1,64}")


def _note_locked_reply(resp) -> None:
    """Count a Coprocessor reply that says ``key_is_locked`` (/health
    ``coprocessor.locked_replies``): the reader resolves or waits, then
    asks again, so each is a cop task served twice."""
    err = resp.get("error") if isinstance(resp, dict) else None
    if isinstance(err, dict) and err.get("kind") == "key_is_locked":
        from ..utils import metrics as m
        m.COPR_LOCKED_REPLY_COUNTER.inc()


class MuxStats:
    """``/health`` ``batch_commands``: what the mux has carried since
    process start.  ``commands_in`` over ``messages_in`` and
    ``responses_out`` over ``messages_out`` are the batch sizes either
    way; ``raw_commands`` over ``coprocessor.requests_served`` the share
    of cop tasks that came as raw commands; ``unary_resends`` the unary
    calls that said they re-send a command whose stream had died."""

    def __init__(self):
        self._mu = threading.Lock()
        # ``streams`` counts the streams opened, ``open`` those live
        self._n = {"streams": 0, "open": 0, "messages_in": 0,
                   "commands_in": 0, "raw_commands": 0, "messages_out": 0,
                   "responses_out": 0, "unary_resends": 0}

    def note(self, **steps: int) -> None:
        with self._mu:
            for name, step in steps.items():
                self._n[name] += step

    def stats(self) -> dict:
        with self._mu:
            return dict(self._n)


class TxnStats:
    """``/health`` ``txn``: the write RPCs this process has traced, by
    method, and those whose ``clock_ns.sent`` could not be placed on the
    store's clock (``txn_wire_request`` then holds nothing of them)."""

    def __init__(self):
        self._mu = threading.Lock()
        self._rpcs = dict.fromkeys(sorted(_WRITE_METHODS), 0)
        self._unshared = 0

    def note(self, method: str, shared: bool) -> None:
        with self._mu:
            self._rpcs[method] += 1
            self._unshared += not shared

    def stats(self) -> dict:
        with self._mu:
            return {"rpcs": dict(self._rpcs),
                    "wire_clock_unshared": self._unshared}


class KvService:
    """All RPC handlers over one node's Storage + raftstore."""

    def __init__(self, node):
        self.node = node
        self.storage: Storage = node.storage
        self.endpoint: Endpoint = node.endpoint
        self.read_pool = node.read_pool
        # partially-received chunked snapshots: key -> {seq: bytes};
        # assembled payloads: key -> bytes (src/server/snap.rs recv task)
        self._snap_parts: dict = {}
        self._snap_ready: dict = {}
        self._snap_lock = threading.Lock()
        # staged bulk-load SSTs by uuid (src/import/sst_service.rs)
        self._import_parts: dict = {}
        self._import_staged: dict = {}
        # ServiceEvent PAUSE_GRPC state (components/service)
        self.paused = False
        # what batch_commands has carried (the status server reads it
        # off the node: /health batch_commands)
        self.mux_stats = node.mux_stats = MuxStats()
        # (one a node: a second service over it counts into the same)
        self.txn_stats = getattr(node, "txn_stats", None) or TxnStats()
        node.txn_stats = self.txn_stats

    # ---------------------------------------------------------- helpers

    def _guard(self, fn: Callable[[dict], dict], req: dict) -> dict:
        try:
            return fn(req)
        except Exception as e:      # noqa: BLE001 — errors ride the wire
            return {"error": wire.enc_error(e)}

    def handle(self, method: str, req: dict) -> dict:
        if self.paused:
            # ServiceEvent.PAUSE_GRPC (components/service): reject
            # instead of queueing — clients back off and retry
            return {"error": {"kind": "server_is_busy",
                              "reason": "service paused"}}
        fn = getattr(self, method, None)
        if fn is None:
            return {"error": {"kind": "unimplemented", "method": method}}
        prio = _READ_METHODS.get(method)
        # a txn write is traced as a read is, under an envelope of its
        # own: a tracker, the accept stamp, ``time_detail`` with
        # ``clock_ns`` on the reply (the client's ``_note_wire`` then
        # places its own four stamps).  Same handler, same
        # acknowledgement: the trace watches
        write = prio is None and method in _WRITE_METHODS
        if prio is None and not write:
            return self._dispatch_rpc(method, fn, req, None)
        # per-request causal trace (components/tracker + minitrace):
        # installed BEFORE admission/decode so even a shed or
        # deadline-exceeded response carries TimeDetail + trace_id —
        # late/rejected work is debuggable from the response alone.  A
        # client-supplied trace_id forces sampling (the caller is
        # asking for this trace); otherwise coprocessor.trace_sample
        # gates span recording, and unsampled requests still pay only
        # the flat phase accumulation the old tracker cost.
        tid = req.get("trace_id") if isinstance(req, dict) else None
        if tid is not None and not (
                isinstance(tid, str) and 0 < len(tid) <= 64 and
                _TRACE_ID_RE.fullmatch(tid)):
            # a hostile/garbage client id would be stored per request,
            # echoed in every response, and printed in the slow-query
            # line — mint a server id instead of honoring it
            tid = None
        sample = getattr(self.node.config.coprocessor,
                         "trace_sample", 1.0)
        sampled = tid is not None or sample >= 1.0 or \
            (sample > 0.0 and random.random() < sample)
        if write:
            # a write's span TREE is built for whoever asks for it by id;
            # its flat phases, its reply's time_detail and the aggregate's
            # rows need none (a third of what tracing a write costs: PERF.md
            # section 6, PR 51)
            sampled = tid is not None
        tr, tok = tracker.install(
            trace_id=tid, sampled=sampled,
            envelope=tracker.TXN_ENVELOPE if write
            else tracker.READ_ENVELOPE)
        tracker.note_accept(tr)
        if write:
            self._note_txn_wire(method, req, tr)
        try:
            resp = self._dispatch_rpc(method, fn, req, prio)
        finally:
            tracker.uninstall(tok)
        if not write:
            return self._seal_traced(method, req, resp, tr)
        try:
            return self._seal_traced(method, req, resp, tr)
        except Exception:       # noqa: BLE001 — the answer stands
            # a trace that fails costs the reply its ``time_detail``,
            # never the write its answer
            logging.getLogger(__name__).warning(
                "sealing a write's trace failed", exc_info=True)
            return resp

    def _note_txn_wire(self, method: str, req: dict, tr) -> None:
        """``txn_wire_request``: the client's ``clock_ns.sent`` → the
        handler pool's stamp, where both are on one clock (``sent`` not
        ahead of ``accept`` and under a minute behind it); anything else
        adds nothing and is counted (``txn.wire_clock_unshared``)."""
        ck = req.get("clock_ns") if isinstance(req, dict) else None
        sent = ck.get("sent") if isinstance(ck, dict) else None
        accept = tr.accept_ns
        shared = isinstance(sent, int) and accept is not None and \
            0 <= accept - sent < _WIRE_SHARED_NS
        if shared:
            from ..utils.trace import AGGREGATE
            AGGREGATE.add("txn_wire_request", accept - sent)
        self.txn_stats.note(method, shared)

    def handle_raw(self, method: str, raw: bytes):
        """RAW-bytes entry for unary Coprocessor RPCs (server.py binds
        the gRPC deserializer to identity for them): the compiled fast
        path (server/fastpath.py) template-matches the bytes first —
        a hit skips ``wire.unpack`` + the DAG decode + plan
        re-analysis and returns a PRE-PACKED response body; any miss
        falls back to the full decode pipeline, which doubles as the
        template learner for the next repeat of the shape."""
        fp = getattr(self.node, "fastpath", None)
        if self.paused or method != "Coprocessor" or fp is None or \
                not fp.enabled:
            return self.handle(method, wire.unpack(raw))
        out = self._fastpath_serve(fp, raw)
        if out is not None:
            return out
        req = wire.unpack(raw)
        learnable = isinstance(req, dict) and \
            ("dag" in req or "plan" in req) and \
            req.get("force_backend") is None and \
            not req.get("paging_size") and \
            req.get("resume_token") is None and \
            not req.get("stale_read") and \
            req.get("tp", REQ_TYPE_DAG) == REQ_TYPE_DAG
        if learnable:
            # learning channel: the endpoint/node fill in what the
            # execution decides (storage, backend, route, region)
            req["__fp_learn"] = {}
        resp = self.handle(method, req)
        learn = req.pop("__fp_learn", None) if isinstance(req, dict) \
            else None
        if learn and ("dag" in learn or "plan" in learn) and \
                isinstance(resp, dict) and not resp.get("error"):
            try:
                # learn from a FRESH unpack: the executed dict was
                # mutated by the handlers (stashes popped, keys added)
                fp.learn(raw, wire.unpack(raw), learn)
            except Exception:   # noqa: BLE001 — learning is optional
                logging.getLogger(__name__).warning(
                    "fastpath learn failed", exc_info=True)
        return resp

    def _fastpath_serve(self, fp, raw: bytes):
        """One fast-path attempt → packed response bytes (hit), an
        error dict (hit that errored — the server packs it), or None
        (no template / failed validation: take the full decode path).
        """
        ent, values = fp.find(raw)
        if ent is None:
            return None
        storage = None
        if ent.tier == "dispatch":
            # pre-commit generation guard (before any RU is charged,
            # so the full-decode fallback never double-charges): the
            # learned storage must still be its cache line's NEWEST
            # generation — a delta patch, rebuild, epoch sweep or
            # eviction since learn retires the entry and this request
            # re-learns.  decode/plan tiers skip this: they replay the
            # full serving ceremony, which re-decides freshness itself
            storage = ent.storage()
            if storage is None or not self.node.copr_cache.is_current(
                    ent.base_key, storage):
                fp.drop(ent, "generation")
                return None
        consts = []
        start_ts = 0
        deadline_ms = None
        tid = None
        for slot, v in zip(ent.template.slots, values):
            k = slot.kind
            if k == "const":
                consts.append(v)
            elif k == "start_ts":
                start_ts = v
            elif k == "deadline_ms":
                deadline_ms = v
            else:
                tid = v
        # trace install mirrors handle(): a client-sent id forces
        # sampling; a garbage id is re-minted server-side
        if tid is not None and not (0 < len(tid) <= 64 and
                                    _TRACE_ID_RE.fullmatch(tid)):
            tid = None
        sample = getattr(self.node.config.coprocessor,
                         "trace_sample", 1.0)
        sampled = tid is not None or sample >= 1.0 or \
            (sample > 0.0 and random.random() < sample)
        tr, tok = tracker.install(trace_id=tid, sampled=sampled)
        tracker.note_accept(tr)
        try:
            env, resp = self._fastpath_dispatch(
                fp, ent, storage, consts, start_ts, deadline_ms)
        finally:
            tracker.uninstall(tok)
        synth = {"__trace_class": ent.trace_class}
        if ent.range_start is not None:
            synth["__trace_range_start"] = ent.range_start
        env = self._seal_traced("Coprocessor", synth, env, tr)
        if resp is None:
            return env      # error response: dict, server packs it
        from .fastpath import encode_response
        return encode_response(env, resp.result, fp, resp.encode_type)

    def _fastpath_dispatch(self, fp, ent, storage, consts,
                           start_ts: int, deadline_ms):
        """The fast leg of ``_dispatch_rpc``: pre-bound admission →
        read-pool slot → validated snapshot → coalescer/solo dispatch
        → await outside the slot.  → (response env dict, CopResponse
        or None on error)."""
        from ..utils import deadline as dl_mod
        from ..utils import metrics as m
        from ..utils.deadline import Deadline, DeadlineExceeded
        method = "Coprocessor"
        t0 = time.perf_counter()
        group = ent.resource_group
        rgm = self.node.resource_groups
        # the fastpath span is the END-TO-END umbrella of the fast leg
        # (admission template, slot, dispatch, await): finer spans —
        # snapshot, device_dispatch, await_deferred, coalesce_wait —
        # nest inside it, and a warm trace still decomposes ≥95% of a
        # now-much-shorter wall
        with tracker.span("fastpath"):
            tracker.label("fastpath",
                          "hit" if ent.tier == "dispatch" else ent.tier)
            dl = None
            if deadline_ms is not None:
                dl = Deadline.after_ms(deadline_ms)
                try:
                    dl.check("admission")
                except DeadlineExceeded as e:
                    m.GRPC_MSG_COUNTER.labels(method, "err").inc()
                    return {"error": wire.enc_error(e)}, None
            rgm.charge_request(group)
            # pre-bound MeterContext template: the tag was resolved at
            # learn time; attribution still rides the trace across
            # every thread handoff exactly as on the slow path
            from ..resource_metering import bind_request_tag
            bind_request_tag(ent.tag, group)
            if ent.tier == "plan":
                preq = ent.make_plan(start_ts)
            else:
                dag = ent.make_dag(consts, start_ts)

            def dispatch():
                if ent.tier == "plan":
                    # plan tier: the wire decode + plan re-analysis
                    # are hoisted; handle_plan runs its normal per-
                    # leaf snapshot + fragment-routing ceremony
                    fp.note_hit(ent)
                    return self.endpoint.handle_plan(
                        preq, resource_group=ent.resource_group,
                        request_source=ent.request_source)
                creq = CopRequest(REQ_TYPE_DAG, dag,
                                  resource_group=ent.resource_group,
                                  request_source=ent.request_source,
                                  region_ctx=ent.region_ctx)
                if ent.tier == "decode":
                    # decode tier: only the wire decode is skipped —
                    # the full ceremony (snapshot, routing, freshness)
                    # re-runs, so nothing snapshot-bound was captured
                    fp.note_hit(ent)
                    return self.endpoint.handle_async(creq)
                got = self.node.fastpath_snapshot(ent, start_ts)
                if got is None or got is not storage:
                    # the generation moved between the pre-commit
                    # check and the slot (a racing write/split): serve
                    # the CURRENT data through the full ceremony — the
                    # decoded DAG is in hand, so only the wire decode
                    # stays skipped — and retire the entry for
                    # re-learn
                    fp.drop(ent, "generation")
                    fp.note_fallback("generation")
                    tracker.label("fastpath", "fallback")
                    return self.endpoint.handle_async(creq)
                fp.note_hit(ent)
                return self.endpoint.handle_async_fast(creq, got, ent)

            dl_tok = dl_mod.install(dl) if dl is not None else None
            resp = None
            env = None
            try:
                try:
                    d = self.read_pool.run(
                        dispatch, "normal", deadline=dl,
                        class_key=ent.class_key, resource_group=group)
                    with tracker.span("await_deferred"):
                        # the plan tier returns a finished CopResponse
                        # (handle_plan is synchronous); dag tiers park
                        # on the deferred device completion
                        resp = d.wait() if hasattr(d, "wait") else d
                except Exception as e:  # noqa: BLE001 — ride the wire
                    env = {"error": wire.enc_error(e)}
                    _note_locked_reply(env)
            finally:
                if dl is not None:
                    dl_mod.uninstall(dl_tok)
        if resp is not None and dl is not None and dl.expired():
            # work finished past its budget: never ack expired work
            m.DEADLINE_SHED_COUNTER.labels("completion").inc()
            env = {"error": wire.enc_error(DeadlineExceeded(
                "completion", overrun_ms=-dl.remaining() * 1e3))}
            resp = None
        if resp is None:
            m.GRPC_MSG_DURATION.labels(method).observe(
                time.perf_counter() - t0)
            m.GRPC_MSG_COUNTER.labels(method, "err").inc()
            return env, None
        result = resp.result
        nbytes = 32 * result.batch.num_rows     # slow-path row estimate
        if nbytes:
            rgm.charge_request(group, bytes_touched=nbytes, requests=0)
        env = self._cop_envelope(resp)
        m.GRPC_MSG_DURATION.labels(method).observe(
            time.perf_counter() - t0)
        m.GRPC_MSG_COUNTER.labels(method, "ok").inc()
        return env, resp

    def _dispatch_rpc(self, method: str, fn, req: dict, prio) -> dict:
        from ..utils import deadline as dl_mod
        from ..utils import metrics as m
        from ..utils.deadline import Deadline, DeadlineExceeded
        # deadline admission (overload defense): the request carries its
        # REMAINING budget at send time; work that is dead on arrival is
        # shed before touching the read pool or the resource bucket
        # the admission umbrella: deadline/resource gating + compile-
        # class keying — finer spans (plan_decode) nest inside; what
        # they don't cover is still attributed, not "untracked"
        with tracker.span("admission"):
            # deadline admission (overload defense): the request
            # carries its REMAINING budget at send time; work that is
            # dead on arrival is shed before touching the read pool or
            # the resource bucket
            dl = None
            budget = req.get("deadline_ms") \
                if isinstance(req, dict) else None
            if budget is not None:
                dl = Deadline.after_ms(budget)
                try:
                    dl.check("admission")
                except DeadlineExceeded as e:
                    m.GRPC_MSG_COUNTER.labels(method, "err").inc()
                    return {"error": wire.enc_error(e)}
            # resource-control admission: the group's token bucket
            # throttles BEFORE the request runs (resource_control
            # ResourceLimiter); a second charge after the response
            # covers the bytes touched
            group = req.get("resource_group") if isinstance(req, dict) \
                else None
            rgm = self.node.resource_groups
            rgm.charge_request(group)
            # RU metering: stamp the request's (resource_group,
            # request_source) tag onto its trace at admission — every
            # downstream charge site (device launch, D2H, read-pool
            # service, arena residency ownership) resolves attribution
            # through this stamp across thread handoffs
            from ..resource_metering import bind_request
            bind_request(group, req.get("request_source", "")
                         if isinstance(req, dict) else "")
            # read-pool compile-class key: the pool's service-time EWMA
            # is keyed by the request's COST SHAPE, not just "a read" —
            # for coprocessor requests the const-blind plan class (a
            # rotating threshold shares its class; a hash-agg does not
            # share a point-select's), the RPC method otherwise.  The
            # DAG decode is reused by the Coprocessor handler below
            # (stashed on the request) so the classing costs no second
            # parse.
            class_key = method if prio is not None else None
            if method == "Coprocessor" and isinstance(req, dict) and \
                    "dag" in req:
                try:
                    with tracker.phase("plan_decode"):
                        dag_obj = wire.dec_dag(req["dag"])
                    req["__dag"] = dag_obj
                    class_key = ("copr", dag_obj.class_key())
                    # stash for the seal step: slow-log range redaction
                    # + trace-buffer class retention (__dag itself is
                    # popped by the handler)
                    req["__trace_class"] = class_key
                    if dag_obj.ranges:
                        req["__trace_range_start"] = \
                            dag_obj.ranges[0].start
                except Exception:   # noqa: BLE001 — handler reports it
                    pass
            elif method == "Coprocessor" and isinstance(req, dict) and \
                    "plan" in req:
                # plan-IR request (copr/plan_ir.py): same decode-once
                # discipline — the plan identity keys the read pool's
                # service-time EWMA and the trace-buffer class
                try:
                    with tracker.phase("plan_decode"):
                        plan_obj = wire.dec_plan(req["plan"])
                    req["__plan"] = plan_obj
                    # const-blind, ts-blind class identity — keying the
                    # EWMAs by plan_key() would mint a singleton class
                    # per (constants, tso) and churn the bounded LRUs
                    class_key = ("copr_plan", plan_obj.class_key())
                    req["__trace_class"] = class_key
                    leaves = plan_obj.scan_leaves()
                    if leaves and leaves[0].ranges:
                        req["__trace_range_start"] = \
                            leaves[0].ranges[0].start
                except Exception:   # noqa: BLE001 — handler reports it
                    pass
        t0 = time.perf_counter()
        # the deadline rides a thread-local so the executor pipeline
        # (between batches) and the device dispatch path can shed
        # without a parameter through every layer
        dl_tok = dl_mod.install(dl) if dl is not None else None
        try:
            if prio is not None:
                resp = self._guard(
                    lambda r: self.read_pool.run(
                        lambda: fn(r), prio, deadline=dl,
                        class_key=class_key,
                        resource_group=group), req)
                d = resp.pop("__deferred", None) \
                    if isinstance(resp, dict) else None
                if d is not None:
                    # async copr: the read-pool slot covered only
                    # the dispatch; the D2H fetch resolves on the
                    # endpoint's completion pool while THIS thread
                    # parks here — N in-flight requests overlap
                    # their device round trips, and point reads
                    # keep getting slots.  The await_deferred span is
                    # the umbrella the completion-side spans (d2h_wait,
                    # host_materialize, coalesce_wait) decompose.
                    def _await(_r):
                        with tracker.span("await_deferred"):
                            got = d.wait()
                        return self._enc_cop_resp(got)
                    resp = self._guard(_await, req)
            else:
                resp = self._guard(fn, req)
        finally:
            if dl is not None:
                dl_mod.uninstall(dl_tok)
        if dl is not None and dl.expired() and \
                isinstance(resp, dict) and not resp.get("error"):
            # the work finished but its deadline passed mid-flight: an
            # acknowledged response must NEVER come from already-expired
            # work — the caller has stopped waiting; ship the typed
            # error instead of a late answer
            m.DEADLINE_SHED_COUNTER.labels("completion").inc()
            resp = {"error": wire.enc_error(DeadlineExceeded(
                "completion", overrun_ms=-dl.remaining() * 1e3))}
        nbytes = resp.get("__bytes", 0) if isinstance(resp, dict) else 0
        if not nbytes and isinstance(resp, dict):
            v = resp.get("value")
            if isinstance(v, (bytes, bytearray)):
                nbytes = len(v)
            elif "rows" in resp and isinstance(resp["rows"], list):
                nbytes = 32 * len(resp["rows"])     # row estimate
            elif "chunk" in resp:
                nbytes = 32 * resp["chunk"]["n"]
        if nbytes:
            rgm.charge_request(group, bytes_touched=nbytes, requests=0)
        m.GRPC_MSG_DURATION.labels(method).observe(
            time.perf_counter() - t0)
        m.GRPC_MSG_COUNTER.labels(
            method, "err" if resp.get("error") else "ok").inc()
        if method == "Coprocessor":
            _note_locked_reply(resp)
        return resp

    def _seal_traced(self, method: str, req: dict, resp: dict,
                     tr) -> dict:
        """Completion tail for every traced RPC (a read; a txn write
        under its own envelope): freeze the trace,
        echo trace_id + TimeDetail/ScanDetail on the wire (INCLUDING
        error responses — a deadline_exceeded or ServerIsBusy answer
        must be debuggable from the response alone), fire the
        slow-query log, and hand the trace to the retention buffer."""
        tr.finish()
        # rpc_reply runs from here to the response serializer's return
        # (server.py): what the store still does for a sealed request
        tracker.reply_begin(tr)
        # RU accounting seal: the trace (and through it the slow-query
        # line and /debug/trace/<id>) answers "who paid for this" —
        # resource_group was labeled at admission, the RU total
        # accumulated across every charge site this request hit
        if method not in _WRITE_METHODS:    # (RU prices reads)
            from ..utils.metrics import RU_REQUEST_HISTOGRAM
            tr.label("ru", f"{tr.ru:.4f}")
            RU_REQUEST_HISTOGRAM.observe(tr.ru)
        if isinstance(resp, dict):
            resp.setdefault("time_detail", tr.time_detail())
            resp.setdefault("scan_detail", tr.scan_detail())
            resp.setdefault("trace_id", tr.trace_id)
        err = resp.get("error") if isinstance(resp, dict) else None
        kind = err.get("kind") if isinstance(err, dict) else None
        total_ms = tr.total_ns() / 1e6
        cc = self.node.config.coprocessor
        thr = getattr(cc, "slow_log_threshold_ms", 0.0)
        slow = thr > 0 and total_ms > thr
        if slow:
            self._slow_query_log(method, req, tr, total_ms, kind)
        buf = getattr(self.node, "trace_buffer", None)
        if buf is not None:
            buf.record(
                tr, class_key=req.get("__trace_class", method)
                if isinstance(req, dict) else method,
                error=err is not None,
                late=kind == "deadline_exceeded",
                shed=kind == "server_is_busy",
                degraded="degraded" in tr.labels, slow=slow)
        return resp

    def _slow_query_log(self, method: str, req: dict, tr,
                        total_ms: float, err_kind) -> None:
        """TiKV ``slow_log!`` analog: ONE line per over-threshold
        request, redacted (utils/log_redact.py) — keys render as
        correlatable digests, never verbatim user data."""
        from ..utils.log_redact import redact_key
        key = None
        if isinstance(req, dict):
            key = req.get("__trace_range_start") or req.get("key") or \
                req.get("start_key")
        phases = sorted(tr.phases.items(), key=lambda kv: -kv[1])[:4]
        top = " ".join(f"{k}={v / 1e6:.1f}ms" for k, v in phases)
        labels = " ".join(f"{k}={v}" for k, v in tr.labels.items())
        _slow_query_logger.warning(
            "slow-query trace_id=%s method=%s total_ms=%.1f "
            "wait_ms=%.1f scan_rows=%d key=%s err=%s [%s] [%s]",
            tr.trace_id, method, total_ms, tr.wait_ns / 1e6,
            tr.scan_rows,
            redact_key(bytes(key)) if key is not None else "-",
            err_kind or "-", top, labels)

    # ---------------------------------------------------------- txn KV

    def KvGet(self, req: dict) -> dict:
        stale = req.get("stale_read", False)
        if stale:
            # the stale-read safety rule: a follower may serve locally
            # ONLY when read_ts ≤ its resolved-ts watermark — below it
            # no new commit can appear, so the applied state answers
            # the MVCC read exactly; above it, DataIsNotReady tells the
            # client to fall back to the leader / ReadIndex path
            from ..raftstore.metapb import DataIsNotReady
            from ..storage.txn_types import encode_key
            peer = self.node.raft_store.peer_by_key(
                encode_key(req["key"]))
            rts = self.node.resolved_ts.resolver(
                peer.region.id).resolved_ts
            if req["version"] > rts:
                raise DataIsNotReady(peer.region.id, rts, req["version"])
        with tracker.phase("kv_read"):
            v = self.storage.get(req["key"], req["version"],
                                 tuple(req.get("bypass_locks", ())),
                                 replica_read=req.get("replica_read",
                                                      False),
                                 stale_read=stale)
        if v is not None:
            tracker.add_scan(1, len(v))
        return {"value": v, "not_found": v is None}

    def KvBatchGet(self, req: dict) -> dict:
        with tracker.phase("kv_read"):
            pairs = self.storage.batch_get(req["keys"], req["version"])
        tracker.add_scan(len(pairs), sum(len(v) for _, v in pairs))
        return {"pairs": [{"key": k, "value": v} for k, v in pairs]}

    def KvScan(self, req: dict) -> dict:
        with tracker.phase("kv_read"):
            pairs = self.storage.scan(req["start_key"],
                                      req.get("end_key") or None,
                                      req["limit"], req["version"],
                                      req.get("reverse", False))
        tracker.add_scan(len(pairs), sum(len(v) for _, v in pairs))
        return {"pairs": [{"key": k, "value": v} for k, v in pairs]}

    def KvPrewrite(self, req: dict) -> dict:
        muts = [Mutation(m["op"], m["key"], m.get("value"))
                for m in req["mutations"]]
        r = self.storage.sched_txn_command(cmds.Prewrite(
            muts, req["primary"], req["start_version"],
            lock_ttl=req.get("lock_ttl", 3000),
            txn_size=req.get("txn_size", 0),
            min_commit_ts=req.get("min_commit_ts", 0),
            is_pessimistic_lock=req.get("is_pessimistic_lock", ()),
            use_async_commit=req.get("use_async_commit", False),
            secondaries=req.get("secondaries", ()),
            try_one_pc=req.get("try_one_pc", False)))
        return r

    def KvCheckSecondaryLocks(self, req: dict) -> dict:
        return self.storage.sched_txn_command(cmds.CheckSecondaryLocks(
            req["keys"], req["start_version"]))

    def KvCommit(self, req: dict) -> dict:
        return self.storage.sched_txn_command(cmds.Commit(
            req["keys"], req["start_version"], req["commit_version"]))

    def KvBatchRollback(self, req: dict) -> dict:
        return self.storage.sched_txn_command(cmds.Rollback(
            req["keys"], req["start_version"]))

    def KvCleanup(self, req: dict) -> dict:
        return self.storage.sched_txn_command(cmds.Cleanup(
            req["key"], req["start_version"], req["current_ts"]))

    def KvCheckTxnStatus(self, req: dict) -> dict:
        return self.storage.sched_txn_command(cmds.CheckTxnStatus(
            req["primary_key"], req["lock_ts"], req["caller_start_ts"],
            req["current_ts"]))

    def KvResolveLock(self, req: dict) -> dict:
        if req.get("keys"):
            return self.storage.sched_txn_command(cmds.ResolveLockLite(
                req["start_version"], req.get("commit_version", 0),
                req["keys"]))
        return self.storage.sched_txn_command(cmds.ResolveLock(
            req["start_version"], req.get("commit_version", 0),
            key_hint=req.get("key_hint")))

    def KvPessimisticLock(self, req: dict) -> dict:
        return self.storage.sched_txn_command(cmds.AcquirePessimisticLock(
            req["keys"], req["primary"], req["start_version"],
            req["for_update_ts"], req.get("lock_ttl", 3000),
            req.get("return_values", False),
            wait_timeout_s=req.get("wait_timeout_s", 0.0)))

    def Detect(self, req: dict) -> dict:
        """Deadlock detector service (lock_manager/deadlock.rs): the
        cluster's detector leader answers detect/clean_up for waiters on
        other stores."""
        det = self.storage.lock_manager.detector
        op = req.get("op", "detect")
        if op == "detect":
            cycle = det.detect(req["waiter_ts"], req["holder_ts"])
            return {"deadlock": cycle is not None,
                    "wait_chain": list(cycle or ())}
        if op == "remove_edge":
            det.remove_edge(req["waiter_ts"], req["holder_ts"])
        elif op == "clean_up":
            det.clean_up(req["txn_ts"])
        return {"deadlock": False, "wait_chain": []}

    def KvPessimisticRollback(self, req: dict) -> dict:
        return self.storage.sched_txn_command(cmds.PessimisticRollback(
            req["keys"], req["start_version"], req["for_update_ts"]))

    def KvTxnHeartBeat(self, req: dict) -> dict:
        return self.storage.sched_txn_command(cmds.TxnHeartBeat(
            req["primary_key"], req["start_version"], req["advise_ttl"]))

    def KvGC(self, req: dict) -> dict:
        return {"removed": self.node.run_gc(req["safe_point"])}

    # ---------------------------------------------------------- raw KV

    def RawGet(self, req: dict) -> dict:
        v = self.storage.raw_get(req["key"])
        return {"value": v, "not_found": v is None}

    def RawBatchGet(self, req: dict) -> dict:
        return {"pairs": [{"key": k, "value": v} for k, v in
                          self.storage.raw_batch_get(req["keys"])]}

    def RawPut(self, req: dict) -> dict:
        self.storage.raw_put(req["key"], req["value"])
        return {}

    def RawBatchPut(self, req: dict) -> dict:
        self.storage.raw_batch_put(
            [(p["key"], p["value"]) for p in req["pairs"]])
        return {}

    def RawDelete(self, req: dict) -> dict:
        self.storage.raw_delete(req["key"])
        return {}

    def RawDeleteRange(self, req: dict) -> dict:
        self.storage.raw_delete_range(req["start_key"], req["end_key"])
        return {}

    def RawScan(self, req: dict) -> dict:
        pairs = self.storage.raw_scan(req["start_key"],
                                      req.get("end_key") or None,
                                      req["limit"],
                                      req.get("reverse", False))
        return {"kvs": [{"key": k, "value": v} for k, v in pairs]}

    # ---------------------------------------------------------- copr

    @staticmethod
    def _cop_envelope(resp) -> dict:
        """The non-rows response fields, shared by the slow path's
        ``_enc_cop_resp`` and the fast leg's streaming encoder — ONE
        definition of the field set and order, so the two legs cannot
        silently diverge on the byte-parity contract."""
        return {"backend": resp.backend,
                "elapsed_ns": resp.elapsed_ns,
                "is_drained": resp.is_drained,
                "resume_token": resp.resume_token,
                "exec_summaries": [
                    {"rows": s.num_produced_rows,
                     "iters": s.num_iterations,
                     "time_ns": s.time_processed_ns}
                    for s in resp.result.exec_summaries]}

    def _enc_cop_resp(self, resp) -> dict:
        """The slow leg's reply: the result as the chunk the request
        asked for where its planes make one (``wire.enc_cop_body``, the
        fast leg's too), else as rows."""
        with tracker.phase("resp_serialize"):
            body = wire.enc_cop_body(resp.result, resp.encode_type)
            if body is None:
                body = {"rows": wire.enc_rows(resp.rows())}
        return {**body, **self._cop_envelope(resp)}

    def Coprocessor(self, req: dict) -> dict:
        # umbrella span over the handler (snapshot, backend routing,
        # dispatch): endpoint overhead between the finer spans stays
        # attributed instead of falling into the untracked residual
        with tracker.span("copr_handler"):
            return self._coprocessor(req)

    def _coprocessor(self, req: dict) -> dict:
        tp = req.get("tp", REQ_TYPE_DAG)
        # handle() stashed its class-keying decode; fall back to a
        # fresh parse for direct callers (tests, batch_commands)
        predec = req.pop("__dag", None)
        if "plan" in req:
            # plan-IR request: the operator superset (join/sort/window
            # + mixed per-fragment routing, copr/plan_ir.py)
            preq = req.pop("__plan", None) or wire.dec_plan(req["plan"])
            learn = req.get("__fp_learn")
            if learn is not None:
                # plan-tier fast-path learning: the decoded request +
                # compile-class key are all the template learner needs
                # (no storage capture — hits replay the full ceremony)
                learn["plan"] = preq
                learn["class_key"] = req.get("__trace_class")
            resp = self.endpoint.handle_plan(
                preq, force_backend=req.get("force_backend"),
                resource_group=req.get("resource_group", "default"),
                request_source=req.get("request_source", ""))
            return self._enc_cop_resp(resp)
        if tp == 104:       # ANALYZE (endpoint.rs:275-312)
            from ..copr.analyze import AnalyzeReq
            dag = predec or wire.dec_dag(req["dag"])
            stats = self.endpoint.handle_analyze(AnalyzeReq(
                dag.executors[0], dag.ranges,
                req.get("buckets", 64), dag.start_ts))
            return {"columns": [
                {"col_id": s.col_id, "total": s.total,
                 "null_count": s.null_count, "distinct": s.distinct,
                 "buckets": [[b, c] for b, c in s.buckets]}
                for s in stats["columns"]]}
        if tp == 105:       # CHECKSUM (checksum.rs)
            from ..copr.analyze import ChecksumReq
            dag = predec or wire.dec_dag(req["dag"])
            return self.endpoint.handle_checksum(ChecksumReq(
                dag.executors[0], dag.ranges, dag.start_ts))
        assert tp == REQ_TYPE_DAG, tp
        dag = predec or wire.dec_dag(req["dag"])
        learn = req.get("__fp_learn")
        if learn is not None:
            # fast-path learning (server/fastpath.py): hand the
            # decoded DAG + compile-class key to the template learner;
            # the endpoint/node fill in storage/route/region below
            learn["dag"] = dag
            learn["class_key"] = req.get("__trace_class")
        creq = CopRequest(
            REQ_TYPE_DAG, dag, req.get("force_backend"),
            paging_size=req.get("paging_size", 0),
            resume_token=req.get("resume_token"),
            resource_group=req.get("resource_group", "default"),
            request_source=req.get("request_source", ""),
            stale_read=req.get("stale_read", False),
            fp_learn=learn,
            region_ctx=wire.dec_region_ctx(req.get("context")))
        # dispatch under the read-pool slot, await outside it: handle()
        # resolves the "__deferred" marker after the slot is released
        d = self.endpoint.handle_async(creq)
        if d.resolved:
            return self._enc_cop_resp(d.wait())
        return {"__deferred": d}

    def copr_stream_rpc(self, req: dict, ctx=None):
        yield from self.copr_stream(req)

    def cdc_stream(self, req: dict, ctx=None):
        """CDC event stream (components/cdc/src/service.rs): initial
        scan at the checkpoint, then live change events from the apply
        path, interleaved with resolved-ts heartbeats.  A resolved_ts
        message promises no further event at or below it."""
        import queue as _q

        from ..cdc.delegate import initial_scan
        from ..kv.engine import SnapContext
        region_id = req["region_id"]
        checkpoint_ts = req.get("checkpoint_ts") or 0
        q: "_q.Queue" = _q.Queue()
        # subscribe BEFORE fetching the scan ts: a commit landing in
        # between then appears in the live queue, the scan, or both —
        # at-least-once over (checkpoint_ts, scan_ts], never dropped
        delegate = self.node.cdc.subscribe(region_id, q.put)
        try:
            scan_ts = self.node.pd.tso()
            snap = self.node.raft_kv.snapshot(
                SnapContext(region_id=region_id))
            events = [e for e in initial_scan(snap, None, None, scan_ts)
                      if e.commit_ts > checkpoint_ts]
            yield {"events": [self._enc_event(e) for e in events],
                   "resolved_ts": 0, "snapshot_ts": scan_ts}
            last_resolved = 0
            while True:
                # read the watermark BEFORE draining: an event enqueued
                # after the drain must never trail a resolved_ts that
                # already covered its commit
                rts = self.node.resolved_ts.resolver(region_id) \
                    .resolved_ts
                batch = []
                try:
                    batch.append(q.get(timeout=0.2))
                    while True:
                        try:
                            batch.append(q.get_nowait())
                        except _q.Empty:
                            break
                except _q.Empty:
                    pass
                batch = [e for e in batch if e.commit_ts > checkpoint_ts]
                if batch or rts > last_resolved:
                    last_resolved = max(last_resolved, rts)
                    yield {"events": [self._enc_event(e) for e in batch],
                           "resolved_ts": last_resolved}
                if ctx is not None and not ctx.is_active():
                    return
        finally:
            self.node.cdc.unsubscribe(region_id, delegate)

    @staticmethod
    def _enc_event(e) -> dict:
        return {"key": e.key, "op": e.op, "commit_ts": e.commit_ts,
                "start_ts": e.start_ts, "value": e.value}

    def backup_stream(self, req: dict, ctx=None):
        """Backup RPC (components/backup/src/service.rs): stream one
        response per backed-up region."""
        from ..backup import backup_region
        from ..kv.engine import SnapContext
        backup_ts = req.get("backup_ts") or self.node.pd.tso()
        storage_url = req["storage"]
        with self.node.lock:
            rids = [p.region.id
                    for p in self.node.raft_store.peers.values()
                    if p.is_leader()]
        for rid in rids:
            try:
                snap = self.node.raft_kv.snapshot(
                    SnapContext(region_id=rid))
                meta = backup_region(snap, rid, backup_ts, storage_url)
                yield {"region_id": rid, "meta": meta,
                       "backup_ts": backup_ts}
            except Exception as e:      # noqa: BLE001
                yield {"region_id": rid, "error": wire.enc_error(e)}

    def copr_stream(self, req: dict):
        """Server-streamed coprocessor pages (service/kv.rs:632
        coprocessor_stream).  One runner instance spans the stream, so
        every page reads the SAME pinned snapshot — unlike offset-based
        unary paging, concurrent writes cannot shift page boundaries.
        """
        import time as _time

        from ..copr.endpoint import CopResponse
        from ..executors.runner import BatchExecutorsRunner
        from ..resource_metering import (
            GLOBAL_RECORDER,
            ResourceTagFactory,
            scanned_rows as _scanned_rows,
        )
        tag = ResourceTagFactory.tag(req.get("resource_group", "default"),
                                     req.get("request_source", ""))
        try:
            dag = wire.dec_dag(req["dag"])
            page = req.get("paging_size", 0) or \
                self.node.config.coprocessor.response_page_rows
            creq = CopRequest(REQ_TYPE_DAG, dag)
            storage = self.endpoint.snapshot_for(creq)
            runner = BatchExecutorsRunner(dag, storage)
            scanned_prev = 0
            while True:
                t0 = _time.perf_counter_ns()
                # per-page attribution: the stream can outlive several
                # metering windows.  Summaries are CUMULATIVE across
                # pages of one runner — record the per-page delta, not
                # the running total
                with GLOBAL_RECORDER.attach(tag):
                    result = runner.handle_request(max_rows=page)
                    scanned = _scanned_rows(result)
                    GLOBAL_RECORDER.record_read_keys(
                        max(0, scanned - scanned_prev))
                    scanned_prev = scanned
                yield self._enc_cop_resp(CopResponse(
                    result, _time.perf_counter_ns() - t0, "host"))
                if result.is_drained:
                    return
        except Exception as e:      # noqa: BLE001 — errors ride the wire
            yield {"error": wire.enc_error(e)}

    def batch_commands(self, request_iterator, raw_dispatch, pool):
        """Bidirectional mux (service/kv.rs:921): inbound messages carry
        commands in either form of ``wire.mux_command``, and responses
        stream back AS THEY COMPLETE, every one that is ready in ONE
        message — a parked command (pessimistic-lock wait) must not
        head-of-line block the very commit that would release it.

        A RAW command of a method in ``raw_dispatch`` (server.py: the
        methods bound at raw bytes, today ``Coprocessor``) is handed to
        ``pool``, the server's BOUNDED command pool (``_HandlerPool``:
        its ``submit`` stamps the hand-off, so ``rpc_accept_wait`` and
        ``clock_ns.accept`` mean on a command what they mean on a unary
        call), and runs ``fn(method, raw)`` there, i.e. ``handle_raw``
        as the unary call runs it; its open ``rpc_reply`` rides with the
        response to the serializer that packs the message holding it.
        Every other command keeps a thread of its own.  ``request_iterator``
        yields the messages still packed: they are unpacked here, on the
        stream's feeder thread, not on gRPC's ``_serve``.

        → an iterator of ``[(response, handed rpc_reply | None), ...]``,
        one list a message (server.py ``_mux_replying`` packs it)."""
        import queue as _q
        import threading as _t

        stats = self.mux_stats
        done: "_q.Queue" = _q.Queue()
        sentinel = object()
        outstanding = [0]
        drained = _t.Event()
        mu = _t.Lock()

        def run(ent, serve):
            try:
                try:
                    resp = serve(ent)
                except Exception as e:  # noqa: BLE001 — answers ITS command
                    resp = {"error": wire.enc_error(e)}
                if "raw" in ent:
                    resp = wire.pack_response(resp)
                done.put((wire.mux_response(ent["request_id"], resp),
                          tracker.reply_handoff()))
            finally:
                with mu:
                    outstanding[0] -= 1
                    last = outstanding[0] == 0 and drained.is_set()
                if last:
                    done.put(sentinel)

        def serve_raw(ent):
            return raw_dispatch[ent["method"]](ent["method"], ent["raw"])

        def serve_decoded(ent):
            return self.handle(ent["method"], wire.unpack(ent["raw"])
                               if "raw" in ent else ent.get("req") or {})

        def feeder():
            lost = False
            try:
                for msg in request_iterator:
                    ents = wire.unpack(msg).get("requests", ())
                    raws = 0
                    for ent in ents:
                        with mu:
                            outstanding[0] += 1
                        if "raw" in ent and ent["method"] in raw_dispatch:
                            raws += 1
                            try:
                                pool.submit(run, ent, serve_raw)
                                continue
                            except RuntimeError:
                                pass    # the pool is shut: the store stops
                        # one thread per in-flight command, NOT a bounded
                        # pool: N parked pessimistic-lock waits must never
                        # occupy every worker and queue the releasing
                        # commit behind themselves
                        _t.Thread(target=run, args=(ent, serve_decoded),
                                  daemon=True).start()
                    stats.note(messages_in=1, commands_in=len(ents),
                               raw_commands=raws)
            except Exception:   # noqa: BLE001 — the stream was cancelled
                lost = True
            finally:
                with mu:
                    drained.set()
                    idle = outstanding[0] == 0
                if idle or lost:
                    # (lost: nobody reads what is still to come)
                    done.put(sentinel)

        stats.note(streams=1, open=1)
        try:
            _t.Thread(target=feeder, daemon=True,
                      name="mux-stream-feeder").start()
            for out in wire.mux_batches(done, sentinel):
                # every response that is ready: one message
                stats.note(messages_out=1, responses_out=len(out))
                yield out
        finally:
            stats.note(open=-1)

    # ---------------------------------------------------------- raft

    # bound on buffered in-flight snapshots: an unclaimed payload (the
    # raft batch carrying its claim failed; the leader re-sends at a
    # NEW index/key) must not leak for the process lifetime
    _SNAP_BUF_MAX = 8

    def SnapshotChunk(self, req: dict) -> dict:
        """One chunk of a large region snapshot (src/server/snap.rs —
        the dedicated snapshot stream; here ordered unary chunks).
        The final chunk assembles the payload, which the matching raft
        message (carrying only meta + the key) then claims."""
        key = req["key"]
        with self._snap_lock:
            parts = self._snap_parts.setdefault(key, {})
            parts[req["seq"]] = req["data"]
            if len(parts) == req["total"]:
                self._snap_ready[key] = b"".join(
                    parts[i] for i in range(req["total"]))
                del self._snap_parts[key]
            # evict oldest unclaimed buffers (dict = insertion order)
            for store in (self._snap_parts, self._snap_ready):
                while len(store) > self._SNAP_BUF_MAX:
                    store.pop(next(iter(store)))
        return {}

    def Raft(self, req: dict) -> dict:
        msg = req["msg"]
        snap = msg.get("snap")
        if snap is not None and "ext_key" in snap:
            with self._snap_lock:
                data = self._snap_ready.pop(snap["ext_key"], None)
            if data is None:
                # chunks lost/incomplete: drop — raft re-sends the
                # snapshot (snap.rs treats a broken stream the same)
                from ..utils.metrics import RAFT_MSG_DROP_COUNTER
                RAFT_MSG_DROP_COUNTER.labels("snap_incomplete").inc()
                return {}
            snap = dict(snap)
            snap.pop("ext_key")
            snap["d"] = data
            msg = dict(msg)
            msg["snap"] = snap
        self.node.on_raft_message(
            req["region_id"], wire.dec_peer(req["to_peer"]),
            wire.dec_peer(req["from_peer"]),
            wire.dec_raft_msg(msg))
        return {}

    def BatchRaft(self, req: dict) -> dict:
        for m in req["msgs"]:
            self.Raft(m)
        return {}

    # ---------------------------------------------------------- admin

    def SplitRegion(self, req: dict) -> dict:
        right = self.node.split_region(req.get("region_id", 0),
                                       req["split_key"])
        return {"right": wire.enc_region(right)}

    def ChangePeer(self, req: dict) -> dict:
        self.node.change_peer(req["region_id"], req["change_type"],
                              wire.dec_peer(req["peer"]))
        return {}

    def ChangePeerV2(self, req: dict) -> dict:
        changes = [(c["type"], wire.dec_peer(c["peer"]))
                   for c in req["changes"]]
        self.node.change_peer_v2(req["region_id"], changes)
        return {}

    def TransferLeader(self, req: dict) -> dict:
        self.node.transfer_leader(req["region_id"], req["to_peer_id"])
        return {}

    def RegionApplied(self, req: dict) -> dict:
        return {"applied": self.node.region_applied(req["region_id"])}

    def MergeRegion(self, req: dict) -> dict:
        merged = self.node.merge_region(req["source_id"],
                                        req["target_id"])
        return {"region": wire.enc_region(merged)}

    def RollbackMerge(self, req: dict) -> dict:
        self.node.rollback_merge(req["region_id"])
        return {}

    def Status(self, req: dict) -> dict:
        return self.node.status()

    def CheckLeader(self, req: dict) -> dict:
        """Leader→follower resolved-ts propagation (components/
        resolved_ts/advance.rs check-leader fan-out): the leader pushes
        its published watermark plus the apply index it was computed at;
        this follower advances a region's resolver only once its OWN
        apply has caught up to that index (every commit the watermark
        covers is in its applied state) and never higher than the
        leader's value or its own pending locks — a lagging replica
        never over-promises."""
        out = {}
        for ent in req.get("regions", ()):
            rid, rts = ent["region_id"], ent["resolved_ts"]
            peer = self.node.raft_store.peers.get(rid)
            if peer is None or \
                    peer.applied_engine < ent.get("applied_index", 0):
                continue
            # str keys: wire.unpack runs msgpack's strict_map_key, so
            # an int-keyed map makes every NON-EMPTY response fail
            # client-side deserialization (the fan-out discards the
            # body, but each failed decode logged an error and counted
            # as a failed call)
            out[str(rid)] = \
                self.node.resolved_ts.resolver(rid).advance(rts)
        return {"advanced": out}

    # ---------------------------------------------- ImportSST service
    #
    # Reference: src/import/sst_service.rs — upload stages file chunks
    # by uuid, ingest lands a staged file atomically on its region,
    # switch_mode pauses housekeeping during the bulk load.

    _IMPORT_STAGE_MAX = 16

    def ImportUpload(self, req: dict) -> dict:
        uuid = req["uuid"]
        with self._snap_lock:       # reuse: small, rarely contended
            if uuid not in self._import_parts and \
                    uuid not in self._import_staged and \
                    (len(self._import_parts) +
                     len(self._import_staged)) >= self._IMPORT_STAGE_MAX:
                # refuse NEW uploads instead of silently evicting a
                # fully-staged blob someone is about to ingest
                return {"error": {"kind": "server_is_busy",
                                  "reason": "import staging full"}}
            parts = self._import_parts.setdefault(uuid, {})
            parts[req["seq"]] = req["data"]
            done = len(parts) == req["total"]
            if done:
                self._import_staged[uuid] = b"".join(
                    parts[i] for i in range(req["total"]))
                del self._import_parts[uuid]
        return {"staged": done}

    def ImportIngest(self, req: dict) -> dict:
        from ..sst_importer import is_sst_v2, read_sst
        uuid = req["uuid"]
        with self._snap_lock:
            blob = self._import_staged.get(uuid)
        if blob is None:
            return {"error": {"kind": "other",
                              "message": f"no staged sst {uuid!r}"}}
        # the staged blob survives a FAILED ingest (epoch change /
        # leadership move) so the client can retry without re-uploading
        # (sst_service keeps the file the same way)
        if is_sst_v2(blob):
            # v2 column-group container: ONE raft op carries the file,
            # apply bulk-merges sorted runs — no per-row replay
            n = self.node.ingest_sst_blob(req["region_id"], blob)
        else:
            pairs = read_sst(blob)  # ValueError on corruption → guard
            n = self.node.ingest_sst(req["region_id"], pairs)
        with self._snap_lock:
            self._import_staged.pop(uuid, None)
        return {"ingested": n}

    def ImportSwitchMode(self, req: dict) -> dict:
        self.node.import_mode = bool(req["import"])
        return {"import_mode": self.node.import_mode}

    # ------------------------------------------------- debug service
    #
    # Reference: src/server/debug.rs + service/debug.rs — the raw
    # inspection surface behind tikv-ctl: engine gets, region meta/size,
    # MVCC record dumps, raft log inspection, bad-region recovery.

    def DebugGet(self, req: dict) -> dict:
        """Raw engine read: (cf, key) exactly as stored — no MVCC."""
        snap = self.node.engine.snapshot()
        v = snap.get_value_cf(req["cf"], req["key"])
        return {"value": v}

    def DebugRegionInfo(self, req: dict) -> dict:
        peer = self.node.raft_store.peers.get(req["region_id"])
        if peer is None:
            return {"error": {"kind": "region_not_found",
                              "region_id": req["region_id"]}}
        node = peer.node
        return {
            "region": wire.enc_region(peer.region),
            "raft_state": {"term": node.term, "commit": node.commit,
                           "applied": node.applied,
                           "last_index": node.last_index(),
                           "is_leader": peer.is_leader()},
            "consistency_state": peer.consistency_state,
        }

    def DebugRegionSize(self, req: dict) -> dict:
        """Per-CF byte sizes of one region (debug.rs region_size)."""
        from ..engine.traits import CF_DEFAULT, CF_LOCK, CF_WRITE
        from ..raftstore.peer_storage import region_data_bounds
        peer = self.node.raft_store.peers.get(req["region_id"])
        if peer is None:
            return {"error": {"kind": "region_not_found",
                              "region_id": req["region_id"]}}
        lo, hi = region_data_bounds(peer.region)
        snap = self.node.engine.snapshot()
        sizes = {}
        for cf in (CF_DEFAULT, CF_LOCK, CF_WRITE):
            total = 0
            it = snap.iterator_cf(cf, lo, hi)
            ok = it.seek_to_first()
            while ok:
                total += len(it.key()) + len(it.value())
                ok = it.next()
            sizes[cf] = total
        return {"sizes": sizes}

    def DebugScanMvcc(self, req: dict) -> dict:
        """MVCC record dump for a user-key range (debug.rs mvcc scan):
        per key — lock, committed writes, default payload versions."""
        from ..storage.mvcc.reader import MvccReader
        from ..storage.txn_types import (
            Lock, Write, append_ts, encode_key, split_ts,
        )
        from ..engine.traits import CF_DEFAULT, CF_LOCK, CF_WRITE
        from ..raftstore.peer_storage import data_key
        from ..codec.keys import DATA_PREFIX
        snap = self.node.engine.snapshot()
        lo = data_key(encode_key(req["start"]))
        # open end: everything under the data prefix (b"{" — the same
        # sentinel region_data_bounds uses; data_key(b"y") would cut off
        # encoded keys starting at bytes >= 0x79)
        hi = data_key(encode_key(req["end"])) if req.get("end") else \
            bytes([DATA_PREFIX[0] + 1])
        limit = req.get("limit", 100)
        out: dict[bytes, dict] = {}

        def enc_user(enc_with_prefix: bytes, strip_ts: bool):
            from ..storage.txn_types import decode_key
            k = enc_with_prefix[1:]         # strip data prefix
            if strip_ts:
                k, _ = split_ts(k)
            return decode_key(k)

        it = snap.iterator_cf(CF_LOCK, lo, hi)
        ok = it.seek_to_first()
        while ok and len(out) < limit:
            user = enc_user(it.key(), strip_ts=False)
            lock = Lock.from_bytes(it.value())
            out.setdefault(user, {})["lock"] = {
                "type": lock.lock_type.name, "start_ts": lock.start_ts,
                "ttl": lock.ttl, "primary": lock.primary}
            ok = it.next()
        it = snap.iterator_cf(CF_WRITE, lo, hi)
        ok = it.seek_to_first()
        while ok:
            user = enc_user(it.key(), strip_ts=True)
            if user not in out and len(out) >= limit:
                ok = it.next()      # full: only existing keys may grow
                continue
            _, commit_ts = split_ts(it.key()[1:])
            w = Write.from_bytes(it.value())
            out.setdefault(user, {}).setdefault("writes", []).append({
                "type": w.write_type.name, "start_ts": w.start_ts,
                "commit_ts": commit_ts,
                "short_value": w.short_value})
            ok = it.next()
        return {"keys": [{"key": k, **v} for k, v in out.items()]}

    def DebugRaftLog(self, req: dict) -> dict:
        """One raft log entry by (region, index) — debug.rs raft_log."""
        peer = self.node.raft_store.peers.get(req["region_id"])
        if peer is None:
            return {"error": {"kind": "region_not_found",
                              "region_id": req["region_id"]}}
        try:
            entries = peer.node.storage.slice(req["index"],
                                              req["index"] + 1)
        except Exception as e:   # noqa: BLE001 — compacted/oob ride back
            return {"error": {"kind": "other", "message": str(e)}}
        if not entries:
            return {"error": {"kind": "other", "message": "no entry"}}
        e = entries[0]
        return {"entry": {"term": e.term, "index": e.index,
                          "type": e.entry_type.name,
                          "data_len": len(e.data)}}

    def DebugRecoverRegion(self, req: dict) -> dict:
        """Tombstone a bad replica on THIS store so the region can be
        re-replicated from healthy peers (debug.rs recover/bad-regions
        + tikv-ctl tombstone)."""
        rid = req["region_id"]
        peer = self.node.raft_store.peers.get(rid)
        if peer is None:
            return {"error": {"kind": "region_not_found",
                              "region_id": rid}}
        self.node.raft_store.destroy_peer(rid)
        return {"tombstoned": rid}

    def DebugCompact(self, req: dict) -> dict:
        """Force an engine compaction pass when the engine has one
        (DiskEngine LSM tiers); no-op otherwise."""
        eng = self.node.engine
        fn = getattr(eng, "compact", None)
        if callable(fn):
            fn()
            return {"compacted": True}
        return {"compacted": False}
