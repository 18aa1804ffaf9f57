"""Registered span-name vocabulary for the causal tracing subsystem.

Every ``tracker.phase(...)`` / ``add_phase(...)`` / ``add_span(...)`` /
``timed(...)`` / ``hold(...)`` / ``held(...)`` / ``.turn(...)`` /
``begin(...)`` / ``link_from(...)`` span name used
anywhere in ``tikv_tpu/`` (and each ``AGGREGATE.add(...)`` row of
utils/trace.py, its two envelopes' rows, each ``client_phase(...)`` of
server/client.py) MUST appear here (tests/test_trace.py scans the source tree both ways, like the
failpoint inventory): a typo'd phase label fails CI instead of silently
forking the latency breakdown into two names no dashboard ever joins.
The descriptions double as the README's span-vocabulary table — keep
them one line each.  Every name also has a row, zeroed from process
start, in the ``/health`` ``tracing.phases`` aggregate (utils/trace.py).
"""

from __future__ import annotations

SPAN_VOCABULARY: dict[str, str] = {
    # -- request envelope (server/service.py, utils/trace.py) --
    "rpc": "root span: the whole RPC from admission to response",
    "rpc_accept_wait": "before the root span: gRPC's hand-off to the "
                       "handler pool → tracker install (pool queue, "
                       "message receive, wait for the GIL); on a mux "
                       "command the stream's feeder's hand-off to the "
                       "command pool → install (that pool's queue); "
                       "aggregate row + root-span attribute "
                       "rpc_accept_wait_us",
    "rpc_reply": "after the root span: trace sealed → response "
                 "serializer returned (seal tail, encode_response: a "
                 "fast-path hit's rows in one native call over the "
                 "result's planes + env's pack, or the Python chain, "
                 "gRPC's hand-off, wire pack); on a mux command → the "
                 "response MESSAGE holding the reply packed (the "
                 "worker's hand-over, the generator's wake); "
                 "aggregate row only",
    "untracked": "synthesized residual: root wall no child span covers",
    "admission": "umbrella: deadline/resource gating + class keying",
    "plan_decode": "wire → DAGRequest decode (compile-class keying)",
    "decimal_lower": "span-only, inside plan analysis on a plan-cache "
                     "miss: decimal and date RPN lowered to the integer "
                     "RPN the device evaluates (device/lowering.py)",
    "copr_handler": "umbrella: coprocessor handler (snapshot, "
                    "routing, dispatch) — endpoint overhead between "
                    "finer spans",
    "read_pool_wait": "queue/slot wait inside the unified read pool",
    "fastpath": "umbrella: the compiled fast-path leg end to end — "
                "template admission, pre-bound metering, constant-"
                "stamped DAG, slot, dispatch, await (server/"
                "fastpath.py; the fastpath label names which leg — "
                "hit/fallback — served)",
    "await_deferred": "service thread parked on the deferred device "
                      "completion (decomposed by completion-side spans)",
    "resp_serialize": "SelectResult → wire response encode: rows, or "
                      "the chunk the request asked for",
    "chunk_encode": "a result's planes → a chunk reply's buffers, on "
                    "either serving leg (server/wire.py enc_cop_body; "
                    "aggregate row only: count = requests that asked "
                    "for a chunk)",
    # -- a transaction's write RPC (server/service.py _WRITE_METHODS,
    # storage/txn/scheduler.py, raftstore/raftkv.py; the names follow
    # kvproto's WriteDetail / TimeDetailV2) --
    "txn_rpc": "root span of a txn write RPC (KvPrewrite, KvCommit, "
               "KvCheckTxnStatus, KvResolveLock, ...): admission to "
               "response, as rpc is a read's; the reads' rows (rpc, "
               "rpc_accept_wait, rpc_reply) never hold a write",
    "txn_accept_wait": "before a write's root span: gRPC's hand-off to "
                       "the handler pool → tracker install (aggregate "
                       "row + root-span attribute rpc_accept_wait_us)",
    "txn_reply": "after a write's root span: trace sealed → response "
                 "serializer returned (aggregate row only)",
    "txn_wire_request": "the client's stamp right before it packed a "
                        "write's request (request's clock_ns.sent) → the "
                        "store's handler pool was handed the call: the "
                        "pack, both gRPC cores, loopback, _serve getting "
                        "the GIL; one shared clock only, else /health "
                        "txn.wire_clock_unshared (aggregate row only)",
    "sched_latch_wait": "txn scheduler: the command's key latches "
                        "acquired (FIFO behind conflicting commands)",
    "sched_snapshot": "txn scheduler: the engine snapshot the command "
                      "reads (raft lease read)",
    "sched_process": "txn scheduler: process_write, the command's MVCC "
                     "reads and buffered writes",
    "raft_write_wait": "RaftKv.write: the command handed over → its "
                       "apply callback fired",
    "raft_wake_wait": "RaftKv.write: the apply callback fired → the "
                      "caller's wait returned (the driver's poll or "
                      "condition wait, the node lock, the GIL)",
    "raft_propose_wait": "span-only child of raft_write_wait on a pooled "
                         "store: mailbox → the peer's poller proposed it "
                         "(stamped in peer.py propose)",
    "raft_apply_wait": "span-only child of raft_write_wait: proposed → "
                       "applied (stamped by the apply callback)",
    # -- the client's side (server/client.py; CLIENT_CLOCK below: on the
    # client's clock, added to the reply's phases_ms by the client) --
    "fanout_cut": "region lookup through the client's region cache and "
                  "the cut of the request's ranges into cop tasks",
    "fanout_tasks": "first cop task sent → last partial reply back",
    "fanout_straggler": "the last task's return minus the median "
                        "task's: what the read waited for its slowest "
                        "region",
    "fanout_task": "median over the read's cop tasks of send → reply",
    "fanout_lock_wait": "where a cop task met another transaction's "
                        "lock (key_is_locked): the longest any one task "
                        "of the read spent on status checks, resolves "
                        "and backoff sleeps before it was sent again "
                        "(label lock_retries counts the resends)",
    "client_route": "TxnClient.coprocessor / coprocessor_fanout entry → "
                    "StoreClient.call entry: plan encode, region "
                    "lookup, breaker, leader choice, retries (a cop "
                    "task's also holds the cut and the worker's start)",
    "client_encode": "StoreClient.call entry → its request serializer "
                     "returned (stub construction, wire.pack)",
    "wire_request": "request serializer returned → the store's handler "
                    "pool was handed the call (reply's clock_ns.accept): "
                    "the client's gRPC core, loopback, the server's "
                    "core, _serve getting the GIL; on a mux command the "
                    "MESSAGE's serializer returned → the command pool "
                    "was handed the command (_serve's one event a "
                    "message, the feeder's unpack; the queue of cop "
                    "tasks is then rpc_accept_wait's); one shared "
                    "clock only",
    "wire_reply": "root span sealed (clock_ns.t1) → the client's "
                  "response deserializer entered (a mux command: of the "
                  "message holding the reply): rpc_reply, gRPC's "
                  "send, loopback, the calling thread waking and "
                  "retaking the client's GIL; one shared clock only",
    "client_decode": "response deserializer entered → returned "
                     "(wire.unpack of the reply, and a chunk's buffers "
                     "wrapped as arrays: wire.dec_chunk); a mux "
                     "command: the message's deserializer entered → "
                     "the command's own reply unpacked on the caller's "
                     "thread, its wake included",
    # -- storage / host pipeline --
    "kv_read": "point/scan MVCC read through Storage",
    "snapshot": "raft lease read + engine snapshot acquisition",
    "columnar_cache": "RegionColumnarCache lookup (hit/patch/build)",
    "replica_patch": "follower replica-feed lookup + delta catch-up "
                     "on the stale-read serving path (node.py "
                     "_copr_snapshot, stale leg)",
    "replica_promote": "leader-gain promotion of a warm replica feed: "
                       "scrub-digest re-verify, never a "
                       "columnar_build (device/supervisor.py)",
    "columnar_build": "full columnar line build from the MVCC snapshot "
                      "(attrs: schema_cols, the columns of the line's "
                      "scan schema; decimal_cols, code_cols, "
                      "skipped_datums from the native build)",
    "delta_apply": "committed-write delta patch onto a cached line",
    "host_exec": "host (numpy) executor pipeline run",
    "host_materialize": "host finalize: fetched tree → SelectResult",
    # -- async serving stack --
    "completion_queue_wait": "wait for a completion-pool worker slot",
    "coalesce_wait": "coalescer submit → the group's launch staged: "
                     "collection window + wait for the one dispatcher "
                     "thread + the shared launch staging (the group's "
                     "leader: → the staging BEGAN; its own phases hold "
                     "the staging as the work it was)",
    "coalesce_window": "span-only child of coalesce_wait: submit → the "
                       "member's group closed (the collection window)",
    "dispatch_queue_wait": "span-only child of coalesce_wait: group "
                           "closed → the dispatcher began staging it",
    "group_dispatch": "shared dispatch of one coalesced group "
                      "(follows-from linked into every member trace); "
                      "its aggregate row is the dispatcher thread busy",
    "dispatcher_idle": "dispatcher thread parked with no closed group "
                       "to stage (aggregate row only)",
    # the hold's own vocabulary (HOLD_ROWS below): inside a hold each
    # of these keeps its SELF time, so that with the hold's leaf rows
    # (HOLD_WHOLE) and dispatch_self they add up to group_dispatch
    "group_open": "the hold, before the runner is called: the waiting "
                  "groups of the launch class taken (_take_fusable) and "
                  "each one's class and ticket asked of the runner "
                  "(runner.py launch_ticket: a hit's only look-up), "
                  "lanes merged, DWFQ selection, the group span begun, "
                  "the leader adopted, the metering scope entered",
    "stage_plan": "a hold's lanes staged up to their branch, ONE piece "
                  "a hold (attrs lanes, ticket_hits): every ticketed "
                  "lane from its prepared record in one pass "
                  "(runner.py _stage_tickets: the runner's gates once, "
                  "each lane's guards and operands, the arena's mutex "
                  "once for every pin), then, of a lane that stages as "
                  "a request of its own (_stage_local), plan analysis, "
                  "quarantine gates, the tile probe, the request memo, "
                  "the generation check, the row count",
    "memo_roll": "a written line's derived record rolled across the "
                 "journal's gap (runner.py _refresh_meta → feed.py "
                 "roll_derived; attr outcome: kept | dropped:<cause>, "
                 "as /health device_mesh.memo counts it)",
    "stage_full": "a full staging's own time: the host planes, the run "
                  "body's prologue (layouts, kernel lookup, the prepared "
                  "record written), the arena's pin and admit; less "
                  "feed_get, host_derive, arena_evict and the launch",
    "feed_get": "the feed ladder's own time between its rungs "
                "(feed.py FeedStore.get): the key, the bucket, the "
                "journal's gap read, windows and dead runs folded, "
                "digests registered; less feed_patch / feed_rebuild / "
                "feed_upload / arena_evict",
    "lanes_launch": "a launch's own time around device_dispatch "
                    "(aggregate.py launch_lanes): lanes grouped by "
                    "kernel, the lane program looked up, the output "
                    "staged toward pinned host memory, lanes bound",
    "group_complete": "the hold, after the runner returned: lane counts "
                      "noted, every member's resolution handed to the "
                      "completion pool, the follows-from links",
    "dispatch_self": "the hold's wall that no row of HOLD_ROWS covered "
                     "(aggregate row only, written where group_dispatch "
                     "closes: Σ HOLD_ROWS + dispatch_self = "
                     "group_dispatch over any window)",
    "group_fetch_wait": "member resolution joining the group's shared "
                        "(memoized) fetch",
    # -- device backend (device/runner.py) --
    "dispatch_lock_wait": "a request's launch path waiting for the "
                          "runner's dispatch lock (device/runner.py "
                          "_dispatch_locked; a lane launch takes none)",
    "device_dispatch": "kernel launch enqueue (flight-recorder attrs, "
                       "among them prepared: the launch's lanes staged "
                       "from their class's prepared record alone, "
                       "counted on /health device_mesh.prepared "
                       "{hits, builds, drops})",
    "d2h_wait": "device→host transfer + sync wait",
    "device_wait": "span-only child of d2h_wait: block_until_ready on "
                   "the result leaves (the program has not finished)",
    "d2h_copy": "span-only child of d2h_wait: np.asarray of the leaves "
                "(transfer + sync left after the program finished)",
    "feed_upload": "H2D upload of the columnar feed from the line's "
                   "host planes: casts, pad, digests, the put (attrs "
                   "bytes, planes, after_eviction: it brings back a "
                   "feed the HBM budget had taken, not a cold one; "
                   "/health device_mesh.feed uploads)",
    "arena_evict": "a sweep of the HBM budget that had to evict "
                   "(device/supervisor.py FeedArena._evict_until_locked"
                   ": each victim found by a scan of the entries under "
                   "the arena's mutex, its device state released, its "
                   "host memos kept; attrs victims, bytes); in an "
                   "admission a leaf of the dispatcher's hold, in an "
                   "unpin a phase of the request that completed",
    "feed_host_pad": "span-only child of feed_upload on a sharded "
                     "mesh: the host's padded copy of one plane",
    "feed_shard_put": "span-only child of feed_upload on a sharded "
                      "mesh: one plane handed to device_put on the row "
                      "sharding, a slice to each shard (not waited for: "
                      "the first launch waits for the transfer)",
    "feed_patch": "delta-dirty span patch of a resident feed: each "
                  "span widened on the host to a bucket length, every "
                  "window's updates gathered, then ONE program a window "
                  "over all the feed's planes, digests chained",
    "feed_rebuild": "a resident feed the journal could not patch "
                    "forward (tombstones, a repack, a crossed pad "
                    "bucket, a value outside the feed's dtypes) built "
                    "again: from the line and uploaded (feed_upload's "
                    "work, named apart because it follows a write) or, "
                    "after tombstones alone, compacted on the device "
                    "from the resident planes (feed.py "
                    "_try_compact_feed: label device_feed=compact)",
    "host_derive": "a request's device dtypes derived from the line's "
                   "rows (feed.py HostPlanes._derive: the code and date "
                   "planes cut, a lowered plan's bounds and its proof); "
                   "once a line, and again where a write left what a "
                   "memo had proved (/health device_mesh.memo dropped)",
    "shard_merge": "host-side merge of per-shard partial agg states",
    "mesh_rebuild": "elastic degrade: re-mint serving on a submesh",
    "feed_migrate": "ICI move of a resident feed between slices "
                    "(device_put across the mesh + arrival verify "
                    "against the carried scrub digests)",
    "device_split": "region split sliced on device: parent feed → two "
                    "child feeds by key range, digests re-anchored to "
                    "host truth before either child serves",
    "remint_wait": "re-mint storm control: columnar_build parked in "
                   "the priority rebuild queue for a concurrency "
                   "permit (device/supervisor.py RemintGovernor)",
    # -- plan IR (copr/plan_ir.py, device/join.py) --
    "plan_route": "per-fragment host/device routing of a plan-IR "
                  "request (FragmentRouter)",
    "join_build": "build-side dictionary sort onto the device (key "
                  "upload + one build dispatch, cached per anchor)",
    "join_probe": "probe dispatch: fused selection + dictionary probe "
                  "→ late-materialized row-index pairs D2H",
    "sort_fragment": "sort fragment execution (device permutation or "
                     "host stable sort) incl. the host gather",
    "window_fragment": "window fragment execution (segmented scans "
                       "over the partition-sorted view)",
    # -- cold path (device/mvcc.py, copr/stream_build.py) --
    "mvcc_parse": "CF_WRITE → flat plane parse (native/host)",
    "mvcc_resolve": "device segmented-argmax MVCC version resolution",
    "stream_take": "cold-stream handoff wait at build time",
    "h2d_stream": "streaming per-chunk H2D upload during the load",
    # -- the process (utils/trace.py) --
    "gc_pause": "one run of Python's cyclic collector, from "
                "gc.callbacks (aggregate row only: count, wall)",
    "gil_wait": "one sample of the GIL probe (watch_gil, 20 a second): "
                "woken from a GIL-free sleep → holding the GIL again "
                "(aggregate row only: count, wall; /health tracing.gil "
                "has the histogram)",
}

# Names a reply's ``phases_ms`` can hold that lie OUTSIDE the store's
# root span: whoever sums ``phases_ms`` against ``total_rpc_wall_ms``
# leaves this set out.  The client's nine are on the client's clock;
# ``rpc_accept_wait`` is the store's (``clock_ns.t0 - clock_ns.accept``),
# put there by the client beside its own so that one reply adds up:
# client_route + client_encode + wire_request + rpc_accept_wait +
# total_rpc_wall_ms + wire_reply + client_decode = the caller's wall.
CLIENT_CLOCK = frozenset({
    "fanout_cut", "fanout_tasks", "fanout_straggler", "fanout_task",
    "fanout_lock_wait",
    "client_route", "client_encode", "wire_request", "wire_reply",
    "client_decode"})
OUTSIDE_ROOT = CLIENT_CLOCK | {"rpc_accept_wait"}


# The dispatcher's hold (server/coalescer.py _dispatch: ``trace.hold``).
# HOLD_SELF are written by ``trace.held`` and exist inside a hold alone;
# each row keeps its SELF time (its wall less the HOLD_ROWS scopes that
# ran inside it).  HOLD_WHOLE are older phases that run there as leaves
# and keep their whole wall, as every metric that reads them expects.
# Over any window Σ Δwall(HOLD_ROWS) + Δwall(dispatch_self) =
# Δwall(group_dispatch).  HOLD_CPU: the jitted calls, which inside a hold
# take the thread CPU clock every time, so that offcpu_ms is a full sum.
HOLD_SELF = ("group_open", "stage_plan", "memo_roll", "stage_full",
             "feed_get", "lanes_launch", "group_complete")
# (arena_evict: PR 53; a metric that lists the hold's rows by name reads
# the share the older ones cover)
HOLD_WHOLE = ("device_dispatch", "feed_patch", "feed_rebuild",
              "feed_upload", "host_derive", "arena_evict")
HOLD_ROWS = frozenset(HOLD_SELF + HOLD_WHOLE)
HOLD_CPU = frozenset({"device_dispatch", "feed_patch", "feed_rebuild"})
