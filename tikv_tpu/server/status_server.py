"""HTTP status server: /metrics, /status, /config, /region, /fail_point.

Reference: src/server/status_server/mod.rs — the hyper server exposing
prometheus metrics (:666), live config GET/POST (:699-712), region
inspection (/region/{id}) and remote failpoint control (:716).  Python
shape: stdlib ThreadingHTTPServer; runs beside the gRPC server on
``server.status-addr``.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from ..utils import trace as trace_mod
from ..utils.metrics import REGISTRY


class StatusServer:
    """One node's status endpoint.

    ``config_controller``: config.ConfigController for GET/POST /config.
    ``node``: server node for /status and /region/{id}.
    """

    def __init__(self, addr: str, node=None, config_controller=None,
                 registry=REGISTRY):
        host, _, port = addr.rpartition(":")
        self._node = node
        self._controller = config_controller
        self._registry = registry
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):   # quiet
                pass

            def _reply(self, code: int, body: bytes,
                       ctype: str = "application/json") -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _json(self, code: int, obj) -> None:
                def default(o):
                    if isinstance(o, bytes):
                        return o.decode("utf-8", "backslashreplace")
                    return repr(o)
                self._reply(code, json.dumps(obj, default=default).encode())

            def do_GET(self):
                path = self.path.split("?", 1)[0].rstrip("/") or "/"
                if path == "/metrics":
                    self._reply(200, outer._registry.expose().encode(),
                                "text/plain; version=0.0.4")
                elif path == "/status":
                    st = outer._node.status() if outer._node else {}
                    self._json(200, st)
                elif path == "/health":
                    # overload-defense rollup: slow score/trend, the
                    # read pool's shedding counters, and the per-peer
                    # transport breaker states
                    node = outer._node
                    if node is None:
                        self._json(200, {"healthy": True})
                        return
                    body = dict(node.health.stats())
                    rp = getattr(node, "read_pool", None)
                    if rp is not None and hasattr(rp, "stats"):
                        body["read_pool"] = rp.stats()
                    tp = getattr(node, "transport", None)
                    if tp is not None and hasattr(tp, "breaker_states"):
                        body["peer_breakers"] = tp.breaker_states()
                    cc = getattr(node, "copr_cache", None)
                    if cc is not None and hasattr(cc, "stats"):
                        # incremental columnar cache: hit/miss/delta/
                        # rebuild counters, per-line tombstone ratio,
                        # delta-log depth
                        body["copr_cache"] = cc.stats()
                    ep = getattr(node, "endpoint", None)
                    coal = getattr(ep, "coalescer", None) \
                        if ep is not None else None
                    if coal is not None and hasattr(coal, "stats"):
                        # cross-request batching: window config, group
                        # occupancy, router decision mix, solo-degrade
                        # count
                        body["coalescer"] = coal.stats()
                    # Coprocessor RPCs this process has answered, ok
                    # or error, on either serving leg: /metrics'
                    # tikv_grpc_msg_total{method="Coprocessor"}, as one
                    # count two samples can difference (a fan-out read
                    # is one of these a region)
                    # and how the replies carried their results
                    # (wire.enc_cop_body: rows, or a chunk where the
                    # request asked for one), with the chunks' rows and
                    # buffer bytes; and how many of them were answered
                    # key_is_locked (the reader waits and asks again)
                    from ..utils import metrics as m
                    body["coprocessor"] = {"requests_served": int(sum(
                        m.GRPC_MSG_COUNTER.labels("Coprocessor", st).value
                        for st in ("ok", "err"))),
                        "locked_replies": int(
                            m.COPR_LOCKED_REPLY_COUNTER.value),
                        "replies": {
                            "rows": int(m.COPR_REPLY_COUNTER.labels(
                                "rows").value),
                            "chunk": int(m.COPR_REPLY_COUNTER.labels(
                                "chunk").value),
                            "chunk_rows_sum": int(m.COPR_CHUNK_ROWS.value),
                            "chunk_bytes_sum": int(
                                m.COPR_CHUNK_BYTES.value)}}
                    mux = getattr(node, "mux_stats", None)
                    if mux is not None:
                        # the BatchCommands mux (service.py MuxStats):
                        # commands_in / messages_in and responses_out /
                        # messages_out are the batch sizes; raw_commands
                        # over coprocessor.requests_served the share of
                        # cop tasks that came by the mux
                        body["batch_commands"] = mux.stats()
                    txn = getattr(node, "txn_stats", None)
                    if txn is not None:
                        # the txn write RPCs traced, by method, and
                        # those whose client's send stamp was not on
                        # this clock (service.py TxnStats; their spans
                        # are tracing.phases' txn_* / sched_* / raft_*)
                        body["txn"] = txn.stats()
                    fp = getattr(node, "fastpath", None)
                    if fp is not None and hasattr(fp, "stats"):
                        # microsecond warm path: learned wire-template
                        # classes, hit/miss/bypass/fallback/invalidate
                        # counts by reason, plus the pinned D2H
                        # staging pool when the backend supports it
                        body["fastpath"] = fp.stats()
                        drp = getattr(node, "device_runner", None)
                        if drp is not None and \
                                hasattr(drp, "pinned_readback_stats"):
                            body["fastpath"]["pinned_readback"] = \
                                drp.pinned_readback_stats()
                    pe = getattr(ep, "_plan_executor", None) \
                        if ep is not None else None
                    if pe is not None:
                        # plan IR: per-fragment routing decisions +
                        # wall EWMAs, join backend mix (device/host/
                        # degrade), co-location hits, device joiner
                        # cache/overflow rollup
                        body["plan_ir"] = pe.stats()
                    dr = getattr(node, "device_runner", None)
                    if dr is not None and hasattr(dr, "selection_stats"):
                        # late-materialized selection: routing-decision
                        # counts + per-plan observed-selectivity EWMAs
                        body["device_selection"] = dr.selection_stats()
                    if dr is not None and \
                            hasattr(dr, "compile_cache_stats"):
                        # persistent XLA compile cache: where it lives
                        # and this process's requests/hits/writes
                        body["compile_cache"] = dr.compile_cache_stats()
                    if dr is not None and hasattr(dr, "mesh_stats"):
                        # multi-chip rollup: mesh shape (incl. any
                        # coprocessor.mesh_shape override), and when
                        # placement is on the per-slice occupancy
                        # (arena resident bytes/lines), decayed load,
                        # and place/move/whole-mesh counters
                        body["device_mesh"] = dr.mesh_stats()
                    if dr is not None and \
                            hasattr(dr, "failure_domain_stats"):
                        # chip failure domains: per-slice health score
                        # + state (trip/drain/probe cycle), refusal and
                        # rescue counts, and the degraded-submesh shape
                        # while a chip is quarantined
                        body["device_health"] = \
                            dr.failure_domain_stats()
                    sup = getattr(node, "device_supervisor", None)
                    if sup is not None and hasattr(sup, "stats"):
                        # device-state integrity: HBM arena accounting
                        # (resident bytes/lines vs budget, evictions),
                        # scrub passes/divergences, quarantines, and
                        # lifecycle invalidation counts
                        body["device_state"] = sup.stats()
                    if sup is not None or dr is not None:
                        # elastic feed lifecycle: ICI migrations
                        # (moved/partial/failed + wall ms), device-side
                        # splits vs re-mint fallbacks, and the
                        # storm-control governor (active/depth/shed/
                        # peak concurrency)
                        fl: dict = {}
                        placer = getattr(dr, "_placer", None) \
                            if dr is not None else None
                        if placer is not None:
                            fl["migrations"] = placer.migrations
                            fl["migration_ms"] = round(
                                placer.migration_ms, 3)
                            fl["last_migration_ms"] = round(
                                placer.last_migration_ms, 3)
                            fl["migration_failures"] = \
                                placer.migration_failures
                            fl["adoptions"] = placer.adoptions
                        if sup is not None:
                            fl["splits"] = getattr(sup, "splits", 0)
                            fl["split_fallbacks"] = getattr(
                                sup, "split_fallbacks", 0)
                            gov = getattr(sup, "remint_governor", None)
                            if gov is not None:
                                fl["remint"] = gov.stats()
                        if cc is not None:
                            fl["line_splits"] = getattr(cc, "splits", 0)
                        if fl:
                            body["feed_lifecycle"] = fl
                    if hasattr(node, "replica_serving_stats"):
                        # replicated device serving: follower replica
                        # reads served/refused by the resolved-ts
                        # gate, regions with a live replica feed, PD
                        # placement hints, and the warm-promotion /
                        # rebuild / demotion counts
                        body["replica_serving"] = \
                            node.replica_serving_stats()
                    # cold-path kill rollup: device-resolve builds
                    # (mvcc_resolve/h2d_stream phases), mint counters,
                    # and the streaming ingest pipeline's parse/upload
                    # progress
                    cold: dict = {}
                    if cc is not None:
                        cold["device_builds"] = getattr(
                            cc, "device_builds", 0)
                    if dr is not None and \
                            hasattr(dr, "mvcc_resolver"):
                        res = dr.mvcc_resolver(create=False)
                        if res is not None:
                            cold["resolver"] = res.stats()
                    cs = getattr(node, "cold_stream", None)
                    if cs is not None and hasattr(cs, "stats"):
                        cold["stream"] = cs.stats()
                    if cold:
                        body["cold_build"] = cold
                    # causal tracing rollup: live knob values, the
                    # retention buffer's occupancy, slow-query count,
                    # and the device flight recorder's launch totals
                    tb = getattr(node, "trace_buffer", None)
                    if tb is not None:
                        cc = node.config.coprocessor
                        tracing = {
                            "sample": cc.trace_sample,
                            "slow_log_threshold_ms":
                                cc.slow_log_threshold_ms,
                            "buffer": tb.stats(),
                        }
                        fr = getattr(dr, "flight_recorder", None) \
                            if dr is not None else None
                        if fr is not None:
                            tracing["flight_recorder"] = fr.stats()
                        # cumulative per-span wall/cpu and the
                        # process's clocks: two samples difference
                        # into per-request means and window shares
                        tracing["phases"] = \
                            trace_mod.AGGREGATE.snapshot()
                        # the GIL probe's counters, and Python's CPU
                        # by thread role beside the process's clocks
                        tracing["gil"] = trace_mod.GIL.snapshot()
                        tracing["threads"], tracing["process"] = \
                            trace_mod.thread_cpu()
                        body["tracing"] = tracing
                    # device-aware RU metering rollup: live knobs +
                    # cost-model weights (all online-updatable), tag
                    # bound, last windowed top-k report, attribution
                    # coverage
                    from ..resource_metering import GLOBAL_RECORDER
                    body["resource_metering"] = \
                        GLOBAL_RECORDER.health_stats()
                    # multi-tenant resource control rollup: per-group
                    # tokens/debt/share, throttle + deferral + shed
                    # counters, protected-bytes (enforcement of the
                    # charges the metering rollup above measures)
                    from ..resource_control import GLOBAL_CONTROLLER
                    body["resource_control"] = \
                        GLOBAL_CONTROLLER.health_stats()
                    self._json(200, body)
                elif path == "/config":
                    if outer._controller is None:
                        self._json(404, {"error": "no config controller"})
                    else:
                        self._json(200, outer._controller.cfg.to_dict())
                elif path == "/debug/trace" or \
                        path.startswith("/debug/trace/"):
                    self._get_trace(path)
                elif path.startswith("/region/"):
                    self._get_region(path)
                elif path == "/fail_point":
                    from ..utils import failpoint
                    self._json(200, failpoint.list_cfg())
                elif path == "/resource_groups":
                    node = outer._node
                    groups = node.resource_groups.list_groups() \
                        if node is not None else []
                    self._json(200, groups)
                elif path == "/resource_metering":
                    self._get_resource_metering()
                elif path == "/resource_control":
                    self._get_resource_control()
                elif path == "/debug/pprof/profile":
                    # ?seconds=N (default 1): folded-stacks CPU profile
                    # (status_server profile.rs dump_one_cpu_profile)
                    from ..utils.profiler import profile_cpu
                    q = self.path.split("?", 1)
                    secs = 1.0
                    try:
                        if len(q) == 2:
                            for kv in q[1].split("&"):
                                if kv.startswith("seconds="):
                                    secs = min(30.0, float(kv[8:]))
                    except ValueError:
                        self._json(400, {"error": "bad seconds"})
                        return
                    self._reply(200, profile_cpu(secs).encode(),
                                "text/plain")
                elif path == "/debug/pprof/heap":
                    from ..utils.profiler import HeapProfiler
                    self._reply(200, HeapProfiler.snapshot().encode(),
                                "text/plain")
                elif path == "/debug/memory":
                    from ..utils.profiler import memory_usage
                    self._json(200, memory_usage())
                else:
                    self._json(404, {"error": f"no route {path}"})

            def _get_resource_metering(self):
                """Per-tag RU breakdown + windowed top-k hot tenants/
                regions.  Default: a human-readable text table;
                ``?format=json``: the machine shape (what PD receives,
                plus cumulative per-tag totals and the attribution
                coverage figure)."""
                from ..resource_metering import GLOBAL_RECORDER
                rec = GLOBAL_RECORDER
                # roll an overdue window so the route is live without
                # waiting for a store heartbeat (standalone servers)
                rec.roll_window()
                raw = rec.totals()      # ONE snapshot serves both the
                totals = {t: r.summary()    # table and the coverage
                          for t, r in sorted(raw.items(),
                                             key=lambda kv: -kv[1].ru)}
                body = {
                    "config": rec.stats(),
                    "coverage": round(
                        rec.attribution_coverage(totals=raw), 4),
                    "window": rec.report(),
                    "tags": totals,
                }
                fmt = ""
                q = self.path.split("?", 1)
                if len(q) == 2:
                    for kv in q[1].split("&"):
                        if kv.startswith("format="):
                            fmt = kv[len("format="):]
                if fmt == "json":
                    self._json(200, body)
                    return
                lines = ["# resource metering — per-tag RU "
                         "attribution (?format=json for the machine "
                         "shape)",
                         f"coverage={body['coverage']} "
                         f"tags={body['config']['tags']} "
                         f"window_s={body['config']['window_s']} "
                         f"topk={body['config']['topk']}",
                         "",
                         f"{'tag':<32}{'ru':>12}{'launch_ms':>12}"
                         f"{'d2h_mb':>10}{'res_mb_s':>10}"
                         f"{'host_ms':>10}{'keys':>10}{'reqs':>8}"]
                for tag, s in totals.items():
                    lines.append(
                        f"{tag:<32}{s['ru']:>12}{s['launch_ms']:>12}"
                        f"{s['d2h_mb']:>10}{s['resident_mb_s']:>10}"
                        f"{s['host_ms']:>10}{s['read_keys']:>10}"
                        f"{s['requests']:>8}")
                win = body["window"]
                if win:
                    lines.append("")
                    lines.append(f"window top-{body['config']['topk']} "
                                 f"(rolled {win.get('window_s')}s, "
                                 f"total_ru={win.get('total_ru')}):")
                    for ent in win.get("top_tenants") or ():
                        lines.append(f"  tenant {ent['tag']}: "
                                     f"ru={ent['ru']}")
                    for ent in win.get("top_regions") or ():
                        lines.append(f"  region {ent['region']}: "
                                     f"ru={ent['ru']}")
                    if win.get("untagged"):
                        lines.append(
                            f"  untagged residual: "
                            f"ru={win['untagged']['ru']}")
                self._reply(200, ("\n".join(lines) + "\n").encode(),
                            "text/plain; charset=utf-8")

            def _get_resource_control(self):
                """Per-group enforcement state: share/burst/priority,
                live token level + RU debt, recent-RU rate, throttle/
                deferral/shed/eviction counters, protected-bytes.
                Default: a text table; ``?format=json``: the machine
                shape (what /health embeds), plus the device runner's
                per-tenant HBM residency when one is attached."""
                from ..resource_control import GLOBAL_CONTROLLER
                body = GLOBAL_CONTROLLER.stats()
                node = outer._node
                dr = getattr(node, "device_runner", None) \
                    if node is not None else None
                if dr is not None and hasattr(dr, "hbm_stats"):
                    body["residency_by_tenant"] = \
                        dr.hbm_stats().get("residency_by_tenant", {})
                fmt = ""
                q = self.path.split("?", 1)
                if len(q) == 2:
                    for kv in q[1].split("&"):
                        if kv.startswith("format="):
                            fmt = kv[len("format="):]
                if fmt == "json":
                    self._json(200, body)
                    return
                lines = ["# resource control — per-group enforcement "
                         "(?format=json for the machine shape)",
                         f"enabled={body['enabled']} "
                         f"default_share={body['default_share']} "
                         f"deferrals={body['deferrals']} "
                         f"sheds={body['sheds']} "
                         f"evictions={body['evictions']} "
                         f"protected_bytes={body['protected_bytes']}",
                         "",
                         f"{'group':<24}{'share':>10}{'burst':>10}"
                         f"{'prio':>8}{'tokens':>12}{'debt':>10}"
                         f"{'ru/s':>10}{'shed':>7}{'defer':>7}"
                         f"{'evict':>7}"]
                for name, g in body["groups"].items():
                    lines.append(
                        f"{name:<24}{g['share']:>10}{g['burst']:>10}"
                        f"{g['priority']:>8}{g['tokens']:>12}"
                        f"{g['debt']:>10}{g['ru_rate_ewma']:>10}"
                        f"{g['sheds']:>7}{g['deferrals']:>7}"
                        f"{g['evictions']:>7}")
                res = body.get("residency_by_tenant") or {}
                if res:
                    lines.append("")
                    lines.append("HBM residency by tenant:")
                    for t, b in sorted(res.items(),
                                       key=lambda kv: -kv[1]):
                        lines.append(f"  {t}: {b} bytes")
                self._reply(200, ("\n".join(lines) + "\n").encode(),
                            "text/plain; charset=utf-8")

            def _get_trace(self, path: str):
                """/debug/trace — recent/slowest/flagged trace index +
                the device flight recorder; /debug/trace/<id> — full
                span tree; ?format=chrome — Chrome trace-event JSON
                (loads in Perfetto), follows-from-linked foreign spans
                included while they remain in the buffer."""
                node = outer._node
                buf = getattr(node, "trace_buffer", None) \
                    if node is not None else None
                if buf is None:
                    self._json(404, {"error": "no trace buffer"})
                    return
                if path.rstrip("/") == "/debug/trace":
                    body = buf.index()
                    dr = getattr(node, "device_runner", None)
                    fr = getattr(dr, "flight_recorder", None) \
                        if dr is not None else None
                    if fr is not None:
                        body["flight_recorder"] = {
                            **fr.stats(),
                            "recent": fr.items(limit=32)}
                    self._json(200, body)
                    return
                trace_id = path[len("/debug/trace/"):].strip("/")
                tr = buf.get(trace_id)
                if tr is None:
                    self._json(404, {
                        "error": f"trace {trace_id!r} not retained"})
                    return
                fmt = ""
                q = self.path.split("?", 1)
                if len(q) == 2:
                    for kv in q[1].split("&"):
                        if kv.startswith("format="):
                            fmt = kv[len("format="):]
                if fmt == "chrome":
                    from ..utils.trace import to_chrome
                    self._json(200, to_chrome(tr, resolve=buf.get))
                else:
                    self._json(200, tr.to_dict())

            def _get_region(self, path: str):
                if outer._node is None:
                    self._json(404, {"error": "no node"})
                    return
                try:
                    rid = int(path.rsplit("/", 1)[1])
                except ValueError:
                    self._json(400, {"error": "bad region id"})
                    return
                for r in outer._node.status().get("regions", ()):
                    if r["region"]["id"] == rid:
                        self._json(200, r)
                        return
                self._json(404, {"error": f"region {rid} not found"})

            def do_POST(self):
                path = self.path.split("?", 1)[0].rstrip("/")
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n) if n else b"{}"
                try:
                    body = json.loads(raw) if raw.strip() else {}
                except json.JSONDecodeError:
                    self._json(400, {"error": "bad json"})
                    return
                if not isinstance(body, dict):
                    self._json(400, {"error": "body must be a JSON object"})
                    return
                if path == "/config":
                    self._post_config(body)
                elif path == "/resource_groups":
                    node = outer._node
                    if node is None:
                        self._json(404, {"error": "no node"})
                        return
                    node.resource_groups.put_group(
                        body["name"], float(body["ru_per_sec"]),
                        body.get("priority", "medium"),
                        body.get("burst"))
                    self._json(200, {"ok": True})
                elif path.startswith("/fail_point/"):
                    from ..utils import failpoint
                    name = path[len("/fail_point/"):]
                    actions = body.get("actions", "")
                    if actions:
                        failpoint.cfg(name, actions)
                    else:
                        failpoint.remove(name)
                    self._json(200, {"ok": True})
                elif path == "/debug/pprof/heap_activate":
                    from ..utils.profiler import HeapProfiler
                    try:
                        frames = int(body.get("frames", 16))
                    except (TypeError, ValueError):
                        self._json(400, {"error": "bad frames"})
                        return
                    HeapProfiler.activate(frames)
                    self._json(200, {"active": True})
                elif path == "/debug/pprof/heap_deactivate":
                    from ..utils.profiler import HeapProfiler
                    HeapProfiler.deactivate()
                    self._json(200, {"active": False})
                else:
                    self._json(404, {"error": f"no route {path}"})

            def _post_config(self, body: dict):
                if outer._controller is None:
                    self._json(404, {"error": "no config controller"})
                    return
                try:
                    applied = outer._controller.update(body)
                except ValueError as e:
                    self._json(400, {"error": str(e)})
                    return
                self._json(200, {"applied": applied})

        self._httpd = ThreadingHTTPServer(
            (host or "127.0.0.1", int(port or 0)), Handler)
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="status-server")
        self._thread.start()

    def stop(self) -> None:
        if self._thread is not None:
            # shutdown() waits on an event only serve_forever sets —
            # calling it before start() would hang forever
            self._httpd.shutdown()
            self._thread.join(timeout=2)
        self._httpd.server_close()
