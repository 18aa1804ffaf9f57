"""Node — one tikv-server process: store lifecycle + drive loop + RPC.

Reference: components/server/src/server.rs (run_tikv :208,
TikvServer::init :325 — PD handshake, engine init, raftstore start,
service registration) and src/server/node.rs (store bootstrap: alloc
store id / region from PD).

Threading: one background drive thread owns raft progress (tick + ready
+ outbound raft messages, the poll-loop role of components/batch-system);
gRPC handler threads propose under the node lock and block on completion
events the drive thread fires.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Optional

import grpc

from ..engine.memory import MemoryEngine
from ..engine.traits import CF_RAFT
from ..copr.dag import TableScanDesc
from ..copr.endpoint import Endpoint
from ..copr.region_cache import RegionColumnarCache
from ..copr.storage_impl import MvccScanStorage
from ..kv.engine import SnapContext
from ..raftstore import (
    AdminCmd,
    Peer,
    RaftCmd,
    RaftKv,
    RaftStore,
    Region,
    RegionEpoch,
    Transport,
)
from ..pd.client import PdClient
from ..raftstore.metapb import Store as StoreMeta
from ..storage import Storage
from ..storage.mvcc.reader import MvccReader
from ..storage.mvcc.txn import MvccTxn
from ..storage.txn.gc import gc_range
from ..kv.engine import WriteData
from . import wire


class _StoreConn:
    """Per-peer-store connection state: bounded message queue, channel,
    exponential backoff, address rediscovery.

    Reference: src/server/raft_client.rs — ``Queue`` with overflow
    (:198-226), reconnect backoff, and re-resolving the store address
    through PD after failures (resolve.rs)."""

    MAX_QUEUE = 4096
    MAX_BATCH = 512
    BACKOFF_BASE = 0.1
    BACKOFF_MAX = 3.0
    # a raft message queued longer than this is stale — its term/index
    # have been superseded by retries; shipping it after a long backoff
    # only wastes the reconnected channel's first batches (send
    # deadline; the reference's Queue drops on overflow for the same
    # staleness reason)
    MSG_TTL = 10.0

    def __init__(self, store_id: int):
        from ..utils.backoff import Backoff
        self.store_id = store_id
        self.queue: deque = deque()     # (enqueue_monotonic, msg)
        self.lock = threading.Lock()
        self.channel = None
        self.addr = None
        self.fail_count = 0
        self.next_attempt = 0.0     # monotonic deadline while backing off
        # the tight (0.8, 1.0) jitter band keeps retries decorrelated
        # across stores while still guaranteeing exponential growth
        self._backoff = Backoff(base=self.BACKOFF_BASE,
                                cap=self.BACKOFF_MAX, jitter=(0.8, 1.0))

    def push(self, msg: dict) -> bool:
        """→ False when the queue is full (message dropped — raft
        retries; the reference drops on a full Queue the same way)."""
        with self.lock:
            if len(self.queue) >= self.MAX_QUEUE:
                return False
            self.queue.append((time.monotonic(), msg))
            return True

    def pop_batch(self, now: float) -> tuple[list, int]:
        """→ (batch, n_expired): drop queued messages past their send
        deadline, then take up to MAX_BATCH of what is still fresh."""
        with self.lock:
            expired = 0
            while self.queue and now - self.queue[0][0] > self.MSG_TTL:
                self.queue.popleft()
                expired += 1
            n = min(len(self.queue), self.MAX_BATCH)
            return [self.queue.popleft()[1] for _ in range(n)], expired

    def on_failure(self, now: float) -> None:
        self.fail_count += 1
        self._backoff.attempt = self.fail_count - 1
        self.next_attempt = now + self._backoff.next_delay()
        # force address rediscovery: the store may have moved.  Close
        # the channel (native sockets) rather than waiting for GC.
        if self.channel is not None:
            try:
                self.channel.close()
            except Exception:   # noqa: BLE001 — already broken
                pass
        self.channel = None
        self.addr = None
        self._publish_breaker()

    def on_success(self) -> None:
        self.fail_count = 0
        self.next_attempt = 0.0
        self._publish_breaker()

    def breaker_state(self) -> str:
        """The conn's backoff state read as a circuit breaker: closed
        (healthy), open (cooling off after failures), half_open (past
        the cooldown — the next flush is the probe)."""
        if self.fail_count == 0:
            return "closed"
        if time.monotonic() < self.next_attempt:
            return "open"
        return "half_open"

    def _publish_breaker(self) -> None:
        from ..utils.metrics import PEER_BREAKER_GAUGE
        PEER_BREAKER_GAUGE.labels(self.store_id).set(
            {"closed": 0, "half_open": 1, "open": 2}[
                self.breaker_state()])


class GrpcTransport(Transport):
    """Store-to-store raft transport over gRPC.

    Reference: src/server/raft_client.rs — per-store connections with
    BatchRaftMessage buffering + overflow, exponential backoff with PD
    address rediscovery on failure."""

    def __init__(self, pd: PdClient):
        self._pd = pd
        self._conns: dict[int, _StoreConn] = {}
        self._lock = threading.Lock()

    def _conn(self, store_id: int) -> _StoreConn:
        with self._lock:
            conn = self._conns.get(store_id)
            if conn is None:
                conn = self._conns[store_id] = _StoreConn(store_id)
            return conn

    def breaker_states(self) -> dict:
        """Per-peer-store transport breaker view (/health route)."""
        with self._lock:
            conns = list(self._conns.values())
        return {c.store_id: {"state": c.breaker_state(),
                             "consecutive_failures": c.fail_count,
                             "queued": len(c.queue)}
                for c in conns}

    # per-batch RPC deadline: a hung peer must not pin the flush loop
    # (and with it every region's outbound raft traffic) beyond this
    SEND_DEADLINE = 5.0

    def send(self, to_store, region_id, to_peer, from_peer, msg) -> None:
        from ..utils.failpoint import fail_point
        if fail_point("transport::grpc_drop") is not None:
            from ..utils.metrics import RAFT_MSG_DROP_COUNTER
            RAFT_MSG_DROP_COUNTER.labels("failpoint").inc()
            return
        ok = self._conn(to_store).push({
            "region_id": region_id,
            "to_peer": wire.enc_peer(to_peer),
            "from_peer": wire.enc_peer(from_peer),
            "msg": wire.enc_raft_msg(msg)})
        if not ok:
            from ..utils.metrics import RAFT_MSG_DROP_COUNTER
            RAFT_MSG_DROP_COUNTER.labels("full").inc()

    def flush(self) -> None:
        from ..utils.failpoint import fail_point
        now = time.monotonic()
        for conn in list(self._conns.values()):
            if not conn.queue:
                continue
            if now < conn.next_attempt:
                continue            # backing off; messages keep queuing
            msgs, expired = conn.pop_batch(now)
            if expired:
                from ..utils.metrics import RAFT_MSG_DROP_COUNTER
                RAFT_MSG_DROP_COUNTER.labels("expired").inc(expired)
            if not msgs:
                continue
            try:
                fail_point("transport::before_batch_send")
                chan = self._channel(conn)
                self._extract_snapshots(chan, msgs)
                call = chan.unary_unary(
                    "/tikv.Tikv/BatchRaft",
                    request_serializer=wire.pack,
                    response_deserializer=wire.unpack)
                call({"msgs": msgs}, timeout=self.SEND_DEADLINE)
                conn.on_success()
            except Exception:
                # raft tolerates the lost batch (protocol retries); the
                # conn backs off (with jitter) and re-resolves its address
                conn.on_failure(time.monotonic())
                from ..utils.metrics import RAFT_MSG_DROP_COUNTER
                RAFT_MSG_DROP_COUNTER.labels("send_fail").inc(len(msgs))

    # a snapshot payload beyond this rides the chunk stream instead of
    # the raft message (src/server/snap.rs SNAP_CHUNK_LEN = 1MiB; the
    # raft batch then stays small regardless of region size)
    SNAP_CHUNK = 256 * 1024

    def _extract_snapshots(self, chan, msgs: list) -> None:
        """Large snapshots: ship data as ordered SnapshotChunk RPCs,
        leave only meta + the claim key on the raft message."""
        for m in msgs:
            snap = m["msg"].get("snap")
            if snap is None or len(snap.get("d", b"")) <= self.SNAP_CHUNK:
                continue
            data = snap["d"]
            key = (f"{m['region_id']}/{m['to_peer']['id']}/"
                   f"{snap['i']}/{snap['t']}")
            call = chan.unary_unary(
                "/tikv.Tikv/SnapshotChunk",
                request_serializer=wire.pack,
                response_deserializer=wire.unpack)
            from ..utils.metrics import SNAP_CHUNK_COUNTER
            total = -(-len(data) // self.SNAP_CHUNK)
            for seq in range(total):
                chunk = data[seq * self.SNAP_CHUNK:
                             (seq + 1) * self.SNAP_CHUNK]
                call({"key": key, "seq": seq, "total": total,
                      "data": chunk}, timeout=10)
                SNAP_CHUNK_COUNTER.inc()
            snap["d"] = b""
            snap["ext_key"] = key

    def _channel(self, conn: _StoreConn):
        if conn.channel is None:
            conn.addr = self._pd.get_store(conn.store_id).address
            from .security import make_channel
            conn.channel = make_channel(conn.addr)
        return conn.channel


# Reference: components/keys STORE_IDENT_KEY (0x01 0x01) — the store's
# durable identity, read before talking to PD so a restarted store keeps
# its id (src/server/node.rs check_store / bootstrap_store).
STORE_IDENT_KEY = b"\x01ident"


class _DetectorProxy:
    """Routes deadlock detection to the cluster's detector leader.

    Reference: src/server/lock_manager/deadlock.rs — the leader of the
    first region hosts the authoritative wait-for graph; other stores
    forward Detect RPCs to it (client.rs).  Falls back to the local
    graph when the leader is unreachable (local-only detection still
    catches same-store cycles).
    """

    def __init__(self, node):
        from ..storage.lock_manager import DeadlockDetector
        self._node = node
        self._local = DeadlockDetector()
        self._clients: dict = {}        # addr -> StoreClient (channel reuse)

    def _leader_addr(self):
        pd = self._node.pd
        try:
            if hasattr(pd, "get_region_with_leader"):
                _region, leader = pd.get_region_with_leader(b"")
            else:
                leader = pd.leader_of(pd.get_region(b"").id)
            if leader is not None and \
                    leader.store_id != self._node.store_id:
                return pd.get_store(leader.store_id).address
        except Exception:
            pass
        return None

    def _call(self, req):
        addr = self._leader_addr()
        if addr is None:
            return None
        from .client import StoreClient
        client = self._clients.get(addr)
        if client is None:
            client = self._clients[addr] = StoreClient(addr)
        try:
            return client.call("Detect", req, timeout=2)
        except Exception:
            return None

    def detect(self, waiter_ts, holder_ts):
        r = self._call({"op": "detect", "waiter_ts": waiter_ts,
                        "holder_ts": holder_ts})
        if r is None:
            return self._local.detect(waiter_ts, holder_ts)
        return tuple(r["wait_chain"]) if r["deadlock"] else None

    def remove_edge(self, waiter_ts, holder_ts):
        if self._call({"op": "remove_edge", "waiter_ts": waiter_ts,
                       "holder_ts": holder_ts}) is None:
            self._local.remove_edge(waiter_ts, holder_ts)

    def clean_up(self, txn_ts):
        if self._call({"op": "clean_up", "txn_ts": txn_ts}) is None:
            self._local.clean_up(txn_ts)


class Node:
    def __init__(self, addr: str, pd: PdClient,
                 engine: Optional[MemoryEngine] = None,
                 store_id: Optional[int] = None,
                 data_dir: Optional[str] = None,
                 device_runner=None,
                 device_row_threshold: Optional[int] = None,
                 tick_interval: float = 0.01, config=None):
        from ..config import ConfigController, TikvConfig
        if config is None:
            config = TikvConfig()
            config.storage.data_dir = data_dir or ""
        if device_row_threshold is not None:
            # an explicit argument wins over the config file value
            config.coprocessor.device_row_threshold = device_row_threshold
        else:
            device_row_threshold = config.coprocessor.device_row_threshold
        data_dir = config.storage.data_dir or data_dir or None
        self.config = config
        self.config_controller = ConfigController(config)
        self.addr = addr
        self.pd = pd
        if engine is not None and data_dir is not None:
            raise ValueError("pass engine= or data_dir=, not both")
        # advertised GC safe point cache — feeds the engine compaction
        # filter and the auto GcManager tick (gc_worker/gc_manager.rs)
        self._gc_safe_point = 0
        self._gc_running = False
        if engine is not None:
            self.engine = engine
        elif data_dir is not None:
            from ..engine.disk import DiskEngine
            enc = None
            mk_path = getattr(getattr(config, "storage", None),
                              "master_key_file", "") if config else ""
            if mk_path:
                import os as _os

                from ..encryption import DataKeyManager, MasterKeyFile
                # data dir first: the key path may live inside it
                _os.makedirs(data_dir, exist_ok=True)
                master = MasterKeyFile(mk_path) \
                    if _os.path.exists(mk_path) \
                    else MasterKeyFile.create(mk_path)
                enc = DataKeyManager(
                    master, _os.path.join(data_dir, "ENCRYPTION_DICT"))
            from ..storage.txn.gc import MvccCompactionFilter
            self.engine = DiskEngine(
                data_dir, encryption=enc,
                compaction_filter=MvccCompactionFilter(
                    lambda: self._gc_safe_point))
        else:
            self.engine = MemoryEngine()
        self.lock = threading.RLock()
        self._tick_interval = tick_interval
        self._wake = threading.Condition(self.lock)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._operator_busy = threading.Lock()

        import struct as _struct
        ident = self.engine.get_value_cf(CF_RAFT, STORE_IDENT_KEY)
        if ident is not None:
            persisted = _struct.unpack(">Q", ident)[0]
            if store_id is not None and store_id != persisted:
                # reference: src/server/node.rs check_store — a store id
                # clashing with the durable ident is a config error, not
                # something to paper over
                raise ValueError(
                    f"store_id {store_id} != persisted ident {persisted}")
            store_id = persisted
        self.store_id = store_id if store_id is not None else pd.alloc_id()
        if ident is None:
            self.engine.put_cf(CF_RAFT, STORE_IDENT_KEY,
                               _struct.pack(">Q", self.store_id))
        pd.put_store(StoreMeta(self.store_id, addr))
        self.transport = GrpcTransport(pd)
        self.raft_store = RaftStore(
            self.store_id, self.engine, self.transport,
            election_tick=config.raftstore.raft_election_timeout_ticks,
            heartbeat_tick=config.raftstore.raft_heartbeat_ticks,
            tick_interval=tick_interval)
        # the store reads split/gc thresholds live (split checker, log
        # gc) so online raftstore changes take effect without restart
        self.raft_store.config = config.raftstore
        self.raft_store.observers = [self._report_region]
        from ..utils.quota import ResourceGroupManager
        # ONE health controller per store (health_controller crate): the
        # raftstore's per-write inspector and RaftKv's whole-command
        # inspector feed the same slow score, and the store heartbeat
        # exports it to PD for slow-store scheduling
        self.health = self.raft_store.health
        self.resource_groups = ResourceGroupManager()
        # leader→follower resolved-ts fan-out (CheckLeader) state
        self._rts_clients: dict = {}
        self._rts_fanout_busy = threading.Lock()
        # bulk-load import mode (sst_importer import_mode.rs): split
        # checks pause while set
        self.import_mode = False
        # version-gated features (pd_client feature_gate.rs); refreshed
        # on the heartbeat cadence (_refresh_feature_gate), so a PD
        # outage at boot or a later cluster upgrade is picked up
        from ..pd.feature_gate import FeatureGate
        self.feature_gate = FeatureGate()
        self._refresh_feature_gate()
        self.raft_kv = RaftKv(self.raft_store, driver=self._wait_driver,
                              lock=self.lock,
                              latency_inspector=self.health.record_write)
        # load-based splitting (split_controller.rs): hot regions shed
        # load by splitting at the sampled-access median key
        from ..raftstore.load_split import LoadSplitController
        self.load_split = LoadSplitController(
            qps_threshold=config.raftstore.split_qps_threshold,
            detect_times=config.raftstore.split_detect_times)
        if config.raftstore.split_qps_threshold > 0:
            self.raft_kv.on_read = self.load_split.record_read
        from ..storage.lock_manager import LockManager
        self.storage = Storage(
            engine=self.raft_kv,
            lock_manager=LockManager(detector=_DetectorProxy(self)))
        # async-commit integration for replica reads: a leader answering
        # ReadIndex bumps max_ts for the piggybacked read_ts and vetoes
        # while an in-flight prewrite's memory lock covers it
        self.raft_store.read_index_hook = self._read_index_check
        # §2.6 observers: CDC registers BEFORE resolved-ts so a commit
        # event is enqueued while the lock still pins the watermark —
        # the reverse order can publish a resolved_ts covering an event
        # that has not reached any subscriber queue yet
        from ..cdc import CdcObserver, ResolvedTsObserver
        self.resolved_ts = ResolvedTsObserver()
        self.cdc = CdcObserver()
        self.raft_store.coprocessor_host.register(self.cdc)
        self.raft_store.coprocessor_host.register(self.resolved_ts)
        from .read_pool import ReadPool
        self.read_pool = ReadPool(
            max_concurrency=config.readpool.concurrency)
        # incremental columnar cache maintenance: the apply path feeds
        # committed-write deltas into the sink; the cache patches lines
        # forward across data_index gaps instead of rebuilding
        from ..copr.delta import DeltaSink
        self.copr_delta_sink = DeltaSink(
            max_entries=config.coprocessor.delta_log_entries,
            max_rows=config.coprocessor.delta_log_rows)
        self.raft_store.coprocessor_host.register(self.copr_delta_sink)
        self.copr_cache = RegionColumnarCache(
            capacity=config.coprocessor.region_cache_capacity,
            delta_source=self.copr_delta_sink,
            compact_ratio=config.coprocessor.tombstone_compact_ratio,
            max_delta_rows=config.coprocessor.delta_log_rows)
        self.device_runner = device_runner      # /health selection rollup
        # replica device serving (kvproto stale_read at the copr layer):
        # follower reads this store has served from its own columnar
        # lines, regions those lines cover, and resolved-ts refusals
        self._replica_reads = 0
        self._replica_refused = 0
        self._replica_regions: set = set()
        self._replica_hint_regions: set = set()
        # cross-request device batching: the coalescing dispatcher +
        # cost-based admission router in front of the device backend
        # (server/coalescer.py); window 0 disables it
        coalescer = None
        if device_runner is not None and \
                config.coprocessor.coalesce_window_ms > 0 and \
                hasattr(device_runner, "batch_class"):
            from .coalescer import RequestCoalescer
            coalescer = RequestCoalescer(
                device_runner,
                window_ms=config.coprocessor.coalesce_window_ms,
                max_group=config.coprocessor.coalesce_max_group,
                pipeline=config.coprocessor.dispatch_pipeline)
        self.endpoint = Endpoint(self._copr_snapshot,
                                 device_runner=device_runner,
                                 device_row_threshold=device_row_threshold,
                                 coalescer=coalescer)
        # device-state supervisor: lifecycle events (split/merge/epoch
        # change/leader loss/snapshot apply/peer destroy) eagerly tear
        # down the matching columnar cache lines and device feeds, the
        # HBM feed arena enforces the configured budget, and a
        # background scrubber audits resident planes against their
        # build/patch-time digests (device/supervisor.py)
        from ..device.supervisor import DeviceStateSupervisor
        if device_runner is not None and \
                config.coprocessor.device_hbm_budget_mb > 0 and \
                hasattr(device_runner, "set_hbm_budget"):
            device_runner.set_hbm_budget(
                config.coprocessor.device_hbm_budget_mb << 20)
        if device_runner is not None and \
                hasattr(device_runner, "scrub_digests"):
            device_runner.scrub_digests = \
                config.coprocessor.scrub_digests
        self.device_supervisor = DeviceStateSupervisor(
            runner=device_runner, copr_cache=self.copr_cache,
            delta_sink=self.copr_delta_sink,
            scrub_interval=config.coprocessor.scrub_interval_s)
        self.copr_cache.on_line_retired = \
            self.device_supervisor.on_line_retired
        self.raft_store.coprocessor_host.register(self.device_supervisor)
        self.device_supervisor.start()
        # re-mint storm control: bound concurrent cold columnar_build
        # re-mints behind a hot-first priority queue (0 = unthrottled)
        if config.coprocessor.remint_concurrency > 0:
            from ..device.supervisor import RemintGovernor
            gov = RemintGovernor(
                max_concurrent=config.coprocessor.remint_concurrency,
                max_queue=config.coprocessor.remint_queue,
                retry_after_ms=config.coprocessor.remint_retry_after_ms)
            self.copr_cache.remint_gate = gov
            self.device_supervisor.remint_governor = gov
        # cold-path kill: device-side MVCC resolution as the columnar
        # build ladder's first rung, plus the streaming ingest→parse→H2D
        # pipeline that runs it during bulk loads (copr/stream_build.py)
        self.cold_stream = None
        if device_runner is not None and \
                config.coprocessor.device_cold_build and \
                hasattr(device_runner, "mvcc_resolver"):
            resolver = device_runner.mvcc_resolver()
            if resolver is not None:
                self.copr_cache.device_resolver = resolver
                stream_on = config.coprocessor.cold_stream
                if stream_on is None:
                    # AUTO: the stream's overlap premise is a spare
                    # core for the parse worker; on a single-CPU box it
                    # only steals cycles from the ingest it shadows
                    from ..utils import spare_cores
                    stream_on = spare_cores() > 1
                if stream_on:
                    from ..copr.stream_build import ColdStreamBuilder
                    self.cold_stream = ColdStreamBuilder(
                        resolver,
                        max_bytes=config.coprocessor.cold_stream_max_mb
                        << 20)
                    self.raft_store.coprocessor_host.register(
                        self.cold_stream)
                    self.copr_cache.stream_source = self.cold_stream
        # causal request tracing (utils/trace.py): per-node retention
        # buffer behind /debug/trace — tail-biased (slowest per class +
        # every errored/late/shed/degraded request pinned past the ring)
        from ..utils.trace import TraceBuffer, watch_gc, watch_gil
        self.trace_buffer = TraceBuffer(
            capacity=config.coprocessor.trace_buffer)
        watch_gc()      # gc_pause in /health tracing.phases
        watch_gil()     # gil_wait there, and /health tracing.gil
        # compiled request fast path (server/fastpath.py): per-class
        # wire templates learned from slow-path requests; repeat-shape
        # requests skip msgpack/DAG decode and jump to the coalescer.
        # Useful only in front of the device backend (learn() admits
        # device-routed classes), but constructed unconditionally —
        # capacity 0 disables
        from .fastpath import FastPathCache
        self.fastpath = FastPathCache(
            capacity=config.coprocessor.fastpath_classes
            if device_runner is not None else 0)
        if device_runner is not None and \
                hasattr(device_runner, "flight_recorder") and \
                config.coprocessor.flight_recorder_depth > 0:
            device_runner.flight_recorder.set_depth(
                config.coprocessor.flight_recorder_depth)
        # device-aware resource metering (resource_metering.py): the
        # process-global recorder adopts this node's knobs + RU
        # weights; the store-heartbeat loop paces the windowed top-k
        # hot-region/hot-tenant report to PD (maybe_report)
        self._metering_cfg(
            {f.name: getattr(config.resource_metering, f.name)
             for f in dataclasses.fields(config.resource_metering)})
        # multi-tenant resource control (resource_control.py): the
        # process-global controller adopts this node's [resource-
        # control] knobs — per-group shares/bursts/priority tiers
        # enforced at the coalescer window, the feed arena's eviction
        # sweep, and the read pool's admission gate
        self._rc_cfg(
            {f.name: getattr(config.resource_control, f.name)
             for f in dataclasses.fields(config.resource_control)})
        # online reconfig (online_config ConfigManager registrations)
        self.config_controller.register("coprocessor", self._copr_cfg)
        self.config_controller.register("resource_metering",
                                        self._metering_cfg)
        self.config_controller.register("resource_control",
                                        self._rc_cfg)

    def _fastpath_config_changed(self) -> None:
        """Any applied online-config diff retires every learned
        fast-path template (routing thresholds, windows, shares and
        tracing knobs all feed decisions a template pre-bound); one
        slow-path request per class re-learns them."""
        fp = getattr(self, "fastpath", None)
        if fp is not None:
            fp.bump_config_gen()

    def _rc_cfg(self, diff: dict) -> None:
        from ..resource_control import GLOBAL_CONTROLLER
        GLOBAL_CONTROLLER.configure(
            enabled=diff.get("enabled"),
            default_share=diff.get("default_share"),
            default_burst=diff.get("default_burst"),
            groups=diff.get("groups"))
        self._fastpath_config_changed()

    def _metering_cfg(self, diff: dict) -> None:
        from ..resource_metering import GLOBAL_RECORDER
        from ..ru_model import GLOBAL_MODEL
        GLOBAL_RECORDER.configure(
            window_s=diff.get("window_s"),
            topk=diff.get("topk"),
            max_resource_groups=diff.get("max_resource_groups"),
            report_interval_s=diff.get("report_interval_s"))
        GLOBAL_MODEL.set_weights(
            **{k: v for k, v in diff.items()
               if k.startswith("ru_per_")})
        self._fastpath_config_changed()

    def _copr_cfg(self, diff: dict) -> None:
        # tracing knobs: trace_sample / slow_log_threshold_ms are read
        # live off the config tree by the service per request; only the
        # bounded stores need an explicit poke
        if "fastpath_classes" in diff and \
                getattr(self, "fastpath", None) is not None and \
                self.device_runner is not None:
            self.fastpath.configure(capacity=int(
                diff["fastpath_classes"]))
        if "dispatch_pipeline" in diff and \
                self.endpoint.coalescer is not None:
            self.endpoint.coalescer.pipeline = \
                bool(diff["dispatch_pipeline"])
        if "trace_buffer" in diff:
            self.trace_buffer.set_capacity(int(diff["trace_buffer"]))
        if "flight_recorder_depth" in diff and \
                self.device_runner is not None and \
                hasattr(self.device_runner, "flight_recorder"):
            self.device_runner.flight_recorder.set_depth(
                int(diff["flight_recorder_depth"]))
        if "device_row_threshold" in diff:
            self.endpoint._device_row_threshold = \
                diff["device_row_threshold"]
        if "region_cache_capacity" in diff:
            self.copr_cache._capacity = diff["region_cache_capacity"]
        if "remint_concurrency" in diff:
            n = int(diff["remint_concurrency"])
            if n <= 0:
                self.copr_cache.remint_gate = None
                self.device_supervisor.remint_governor = None
            else:
                gov = self.copr_cache.remint_gate
                if gov is None:
                    from ..device.supervisor import RemintGovernor
                    gov = RemintGovernor(
                        max_concurrent=n,
                        max_queue=self.config.coprocessor.remint_queue,
                        retry_after_ms=self.config.coprocessor
                        .remint_retry_after_ms)
                    self.copr_cache.remint_gate = gov
                    self.device_supervisor.remint_governor = gov
                else:
                    gov.max_concurrent = n
        if "tombstone_compact_ratio" in diff:
            self.copr_cache._compact_ratio = \
                diff["tombstone_compact_ratio"]
        if "device_hbm_budget_mb" in diff and \
                self.device_runner is not None and \
                hasattr(self.device_runner, "set_hbm_budget"):
            self.device_runner.set_hbm_budget(
                int(diff["device_hbm_budget_mb"]) << 20)
        if "device_cold_build" in diff:
            if not diff["device_cold_build"]:
                self.copr_cache.device_resolver = None
                # the stream exists only to feed the device rung: left
                # running it would keep parsing every ingested chunk
                # (racing the apply loop) and retain host planes that
                # nothing can ever take() — tear it down with the rung
                if self.cold_stream is not None:
                    self.copr_cache.stream_source = None
                    self.raft_store.coprocessor_host.unregister(
                        self.cold_stream)
                    self.cold_stream.stop()
                    self.cold_stream = None
            elif self.device_runner is not None and \
                    hasattr(self.device_runner, "mvcc_resolver"):
                resolver = self.device_runner.mvcc_resolver()
                self.copr_cache.device_resolver = resolver
                # re-enable restores the WHOLE rung: the disable branch
                # tore the stream down, so rebuild it under the same
                # gate the constructor used
                if resolver is not None and self.cold_stream is None:
                    stream_on = self.config.coprocessor.cold_stream
                    if stream_on is None:
                        from ..utils import spare_cores
                        stream_on = spare_cores() > 1
                    if stream_on:
                        from ..copr.stream_build import ColdStreamBuilder
                        self.cold_stream = ColdStreamBuilder(
                            resolver,
                            max_bytes=self.config.coprocessor
                            .cold_stream_max_mb << 20)
                        self.raft_store.coprocessor_host.register(
                            self.cold_stream)
                        self.copr_cache.stream_source = self.cold_stream
        coal = getattr(self.endpoint, "coalescer", None)
        if coal is None and diff.get("coalesce_window_ms", 0) and \
                self.device_runner is not None and \
                hasattr(self.device_runner, "batch_class"):
            # node started with coalescing disabled (window 0 → no
            # coalescer constructed): an online 0→N enable builds and
            # wires it now instead of silently accepting the change
            from .coalescer import RequestCoalescer
            coal = RequestCoalescer(
                self.device_runner,
                window_ms=float(diff["coalesce_window_ms"]),
                max_group=diff.get(
                    "coalesce_max_group",
                    self.config.coprocessor.coalesce_max_group))
            coal.bind(self.endpoint)
            self.endpoint.coalescer = coal
        elif coal is not None and ("coalesce_window_ms" in diff or
                                   "coalesce_max_group" in diff):
            coal.configure(
                window_ms=diff.get("coalesce_window_ms"),
                max_group=diff.get("coalesce_max_group"))
        self._fastpath_config_changed()

    def _read_index_check(self, read_ts: int, region) -> bool:
        """Leader-side async-commit guard for replica reads: bump
        max_ts, veto while a memory lock IN THIS REGION covers read_ts
        (the reference forwards the same through its ReadIndex request;
        an unrelated region's in-flight prewrite must not starve the
        read)."""
        from ..storage.mvcc.errors import KeyIsLocked
        cm = self.storage.concurrency_manager
        cm.update_max_ts(read_ts)
        try:
            cm.read_region_check(region, read_ts)
        except KeyIsLocked:
            return False
        return True

    # ---------------------------------------------------------- lifecycle

    def bootstrap_or_join(self) -> None:
        """First store bootstraps region 1; later stores start empty and
        receive peers via ChangePeer (src/server/node.rs bootstrap)."""
        self.raft_store.load_peers()
        if self.raft_store.peers:
            return      # restart: state recovered from the engine
        if not self.pd.is_bootstrapped():
            region_id = 1
            peer = Peer(self.pd.alloc_id(), self.store_id)
            region = Region(region_id, b"", b"", RegionEpoch(1, 1), (peer,))
            self.raft_store.bootstrap_region(region)
            self.pd.bootstrap_cluster(StoreMeta(self.store_id, self.addr),
                                      region)
            self.raft_store.region_peer(region_id).node.campaign(force=True)

    def start(self) -> None:
        self.bootstrap_or_join()
        pool = self.config.raftstore.store_pool_size
        if pool > 0:
            # batch-system mode: pollers own peer processing + async
            # raft-log writers; the drive thread degrades to the tick /
            # heartbeat / split-check pacemaker
            self.raft_store.start_pool(
                pool, max(1, self.config.raftstore.store_io_pool_size),
                self.config.raftstore.apply_pool_size)
        self._thread = threading.Thread(target=self._drive_loop,
                                        daemon=True, name="raft-drive")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        self.raft_store.stop_pool()
        self.device_supervisor.stop()
        if self.cold_stream is not None:
            self.cold_stream.stop()
        # idle-drain both request pools: stop admitting reads and wait
        # for in-flight ones, then retire (and JOIN) the endpoint's
        # completion-pool workers — nodes restarted in-process (chaos
        # cycles, per-test servers) must not leak threads each stop.
        # Order matters for the device runner: the endpoint close
        # flushes the coalescer's parked members and drains the
        # completion pool, so every in-flight deferred has resolved
        # (and released its arena pin) before the runner teardown
        # below asserts a pin-free arena.
        self.read_pool.shutdown()
        close = getattr(self.endpoint, "close", None)
        if callable(close):
            close()
        # device teardown last: with the pools drained, no pins remain
        # — drop every resident feed line, retire any degraded submesh
        # runner, and clear quarantine state so an in-process restart
        # starts clean (no leaked HBM accounting, no stale health)
        runner_close = getattr(self.device_runner, "close", None)
        if callable(runner_close):
            runner_close()
        # the resolved-ts fan-out's cached channels hold real sockets
        for c in self._rts_clients.values():
            try:
                c._chan.close()
            except Exception:   # noqa: BLE001 — already broken
                pass
        self._rts_clients.clear()

    def _drive_loop(self) -> None:
        last_tick = time.monotonic()
        last_hb = 0.0
        ticks = 0
        while not self._stop.is_set():
            did = 0
            with self.lock:
                now = time.monotonic()
                if now - last_tick >= self._tick_interval:
                    last_tick = now
                    self.raft_store.tick()
                    ticks += 1
                    every = self.config.raftstore.region_split_check_ticks
                    if every > 0 and ticks % every == 0 and \
                            not self.import_mode:
                        # import mode suspends split checks so a bulk
                        # load isn't fighting auto-splits mid-ingest
                        # (sst_importer import_mode.rs relaxes the
                        # engine the same way)
                        try:
                            self.raft_store.split_check(self.pd)
                        except Exception:
                            pass    # PD outage: retry next interval
                due_load_splits = self.load_split.tick() \
                    if not self.import_mode else {}
                did = self.raft_store.drive()
                self._wake.notify_all()
                # periodic PD reporting (worker/pd.rs heartbeat loop)
                if now - last_hb >= self._tick_interval * 10:
                    last_hb = now
                    leaders = [(p.region, Peer(p.meta.id, self.store_id),
                                list(p.buckets), p.applied_engine)
                               for p in self.raft_store.peers.values()
                               if p.is_leader()]
                else:
                    leaders = None
            self.transport.flush()
            for rid, samples in due_load_splits.items():
                self._try_load_split(rid, samples)
            if leaders is not None:
                try:
                    for region, leader, buckets, _ai in leaders:
                        op = self.pd.region_heartbeat(region, leader,
                                                      buckets=buckets)
                        if op:
                            self._exec_operator(region.id, op)
                    hb = {"region_count": len(leaders)}
                    hb.update(self.health.stats())
                    # windowed top-k hot-region/hot-tenant RU report
                    # rides the store heartbeat to PD (the reference
                    # resource_metering reporter's PD push), paced by
                    # resource_metering.report_interval_s
                    from ..resource_metering import GLOBAL_RECORDER
                    rep = GLOBAL_RECORDER.maybe_report()
                    if rep is not None:
                        hb["resource_metering"] = rep
                    # per-store HBM figures ride the heartbeat so PD's
                    # replica-feed spread stays within device budgets
                    hbm = getattr(self.device_runner, "hbm_stats", None)
                    if callable(hbm):
                        st = hbm()
                        hb["device_hbm"] = {
                            "budget_bytes": st.get("budget_bytes", 0),
                            "resident_bytes": st.get("resident_bytes",
                                                     0)}
                    self._refresh_feature_gate()
                    self._gc_manager_tick()
                    hb_resp = self.pd.store_heartbeat(self.store_id, hb)
                    if isinstance(hb_resp, dict):
                        self._apply_replica_hints(
                            hb_resp.get("replica_feed_regions") or ())
                    # advance resolved-ts watermarks with a fresh TSO
                    # (resolved_ts advance worker cadence).  The ts is
                    # registered in the concurrency manager FIRST so any
                    # later async-commit/1PC finalizes ABOVE the
                    # published watermark (the reference's advance
                    # worker updates max_ts for exactly this reason)
                    ts = self.pd.tso()
                    self.storage.concurrency_manager.update_max_ts(ts)
                    advanced = self.resolved_ts.advance_all(
                        ts, [r.id for r, _l, _b, _ai in leaders])
                    self._fanout_resolved_ts(leaders, advanced)
                except Exception:
                    pass    # PD outages must not stall raft
            if did == 0:
                time.sleep(self._tick_interval / 4)

    def _fanout_resolved_ts(self, leaders, advanced: dict) -> None:
        """Push leader watermarks to follower stores (CheckLeader —
        resolved_ts/advance.rs fan-out) so followers can serve
        resolved-ts-gated stale reads.  Best-effort on a background
        thread: a dead peer store must not stall the drive loop's
        ticks (its timeout would outlast an election timeout)."""
        per_store: dict[int, list] = {}
        for region, _leader, _buckets, _applied_at_hb in leaders:
            rts = advanced.get(region.id, 0)
            if rts <= 0:
                continue
            # read the apply index NOW, after advance_all: a commit
            # that applied between the heartbeat snapshot and the
            # watermark computation has commit_ts < rts — pairing rts
            # with the older index would let a follower that lacks
            # that commit pass the gate and serve a stale read
            # missing it.  A fresher index only raises the bar.
            peer = self.raft_store.peers.get(region.id)
            if peer is None:
                continue
            applied = peer.applied_engine
            for p in region.peers:
                if p.store_id == self.store_id:
                    continue
                per_store.setdefault(p.store_id, []).append(
                    {"region_id": region.id, "resolved_ts": rts,
                     "applied_index": applied})
        if not per_store:
            return
        if not self._rts_fanout_busy.acquire(blocking=False):
            return      # previous fan-out still in flight: skip a beat

        def run():
            from .client import StoreClient
            try:
                for sid, regions in per_store.items():
                    try:
                        addr = self.pd.get_store(sid).address
                        c = self._rts_clients.get(addr)
                        if c is None:
                            c = self._rts_clients[addr] = \
                                StoreClient(addr)
                        c.call("CheckLeader", {"regions": regions},
                               timeout=1)
                    except Exception:   # noqa: BLE001 — next beat
                        pass
            finally:
                self._rts_fanout_busy.release()

        threading.Thread(target=run, daemon=True,
                         name="rts-fanout").start()

    def _try_load_split(self, region_id: int, samples: list) -> None:
        """Split a hot region at the sampled-access median key
        (split_controller.rs -> pd ask_split -> split admin cmd, same
        flow as the size checker).  Load splits are best-effort: any
        routing/epoch race just drops the attempt — the region stays
        hot and the next window retries."""
        from ..storage.txn_types import decode_key
        try:
            peer = self.raft_store.peers.get(region_id)
            if peer is None or not peer.is_leader() or \
                    peer.merging is not None:
                return
            region = peer.region
            enc_key = self.load_split.split_key_for(
                samples, region.start_key, region.end_key)
            if enc_key is None:
                return
            self.split_region(region_id, decode_key(enc_key))
            self.load_split.splits_proposed += 1
        except Exception:   # noqa: BLE001 — next hot window retries
            import logging
            logging.getLogger(__name__).debug(
                "load split of region %d failed", region_id,
                exc_info=True)

    def _wait_driver(self, done) -> None:
        """RaftKv blocks here while the drive thread makes progress."""
        deadline = time.monotonic() + 10.0
        if self.raft_store.pooled():
            # pollers complete the callback; just wait for it
            while not done():
                if time.monotonic() > deadline:
                    raise TimeoutError("raft command stalled")
                time.sleep(0.002)
            return
        with self.lock:
            self.raft_store.drive()
            while not done():
                if time.monotonic() > deadline:
                    raise TimeoutError("raft command stalled")
                self._wake.wait(timeout=0.05)
                self.raft_store.drive()

    # ---------------------------------------------------------- hooks

    def on_raft_message(self, region_id, to_peer, from_peer, msg) -> None:
        with self.lock:
            self.raft_store.on_raft_message(region_id, to_peer, from_peer,
                                            msg)
            self._wake.notify_all()

    def _report_region(self, store_id: int, region: Region) -> None:
        peer = self.raft_store.peers.get(region.id)
        if peer is not None and peer.is_leader():
            self.pd.region_heartbeat(region, Peer(peer.meta.id, store_id))

    def _copr_snapshot(self, req):
        """Coprocessor feed: MVCC over a region snapshot routed by the
        request's first key range (endpoint.rs snapshot acquisition).

        TableScan plans go through the per-region columnar cache so both
        the host vectorized path and the device backend see dense tiles
        with stable identity across requests (copr/region_cache.py);
        everything else falls back to the row-at-a-time MVCC adapter.

        ``req.stale_read`` is the follower device-serving path: this
        replica mints/patches its OWN columnar line from applied state
        (the DeltaSink publishes follower applies too) and serves with
        NO consensus round trip, gated on ``start_ts ≤ resolved_ts``
        (DataIsNotReady on miss — the client falls through to the
        leader leg, kvproto stale_read semantics).
        """
        start = req.dag.ranges[0].start if req.dag.ranges else b""
        key_hint = encode_first(start)
        # async-commit read protocol: bump max_ts, then check the
        # in-memory lock table scoped to the REQUEST's key ranges —
        # an unrelated table's in-flight prewrite must not fail this
        from ..utils import tracker
        cm = self.storage.concurrency_manager
        cm.update_max_ts(req.dag.start_ts)
        if req.dag.ranges:
            cm.read_ranges_check(req.dag.ranges, req.dag.start_ts)
        else:
            cm.read_range_check(None, None, req.dag.start_ts)
        stale = getattr(req, "stale_read", False)
        if stale:
            self._check_replica_freshness(key_hint, req.dag.start_ts)
        with tracker.phase("snapshot"):
            snap = self.raft_kv.snapshot(
                SnapContext(key_hint=key_hint, stale_read=stale))
        ctx = getattr(req, "region_ctx", None)
        if ctx is not None and \
                (snap.region.id, snap.region.epoch.version) != tuple(ctx):
            # a fan-out task (TxnClient.coprocessor_fanout) names the
            # region and epoch its ranges were clipped to: served from
            # another, it would silently lose the rows a split moved
            from ..raftstore.metapb import EpochNotMatch
            raise EpochNotMatch(snap.region)
        execs = req.dag.executors
        if execs and isinstance(execs[0], TableScanDesc):
            # the replica leg labels its cache access as replica_patch:
            # same lookup + delta catch-up mechanics, but the span name
            # keeps follower-feed latency separable from leader serving
            with tracker.phase("replica_patch" if stale
                               else "columnar_cache"):
                ent = self.copr_cache.get(snap, req.dag)
            if ent is not None:
                if stale:
                    self._note_replica_read(snap.region.id)
                learn = getattr(req, "fp_learn", None)
                if learn is not None:
                    # fast-path learning (server/fastpath.py): the
                    # snapshot's region identity anchors the template's
                    # pre-derived cache key — an epoch bump or split
                    # changes it and the learned class misses
                    learn["region"] = snap.region.id
                    learn["epoch_version"] = snap.region.epoch.version
                return ent
        return MvccScanStorage(MvccReader(snap), req.dag.start_ts)

    def _check_replica_freshness(self, key_hint: bytes,
                                 read_ts: int) -> int:
        """Resolved-ts gate for a follower device read: closed
        timestamps guarantee no commit at ts ≤ resolved_ts can newly
        appear, so an applied-state snapshot is exact for any read at
        or below the watermark.  Above it the replica REFUSES
        (DataIsNotReady) rather than serving a possibly-incomplete
        answer — the client's hedge falls through to the leader.  The
        ``device::replica_stale`` failpoint forces the refusal (chaos
        ``replica_lag``: exercises the fall-through leg)."""
        from ..raftstore.metapb import DataIsNotReady
        from ..utils.failpoint import fail_point
        peer = self.raft_store.peer_by_key(key_hint)
        rts = self.resolved_ts.resolver(peer.region.id).resolved_ts
        if fail_point("device::replica_stale") is not None:
            self._replica_refused += 1
            raise DataIsNotReady(peer.region.id, 0, read_ts)
        if read_ts > rts:
            self._replica_refused += 1
            raise DataIsNotReady(peer.region.id, rts, read_ts)
        return peer.region.id

    def _note_replica_read(self, region_id: int) -> None:
        """Replica-serving accounting: regions this store has served a
        follower device read for (the line is now a live replica feed,
        kept patched by the delta stream) + the /metrics gauge."""
        self._replica_reads += 1
        if region_id not in self._replica_regions:
            self._replica_regions.add(region_id)
            sup = getattr(self, "device_supervisor", None)
            if sup is not None:
                sup.note_replica_feed(region_id)

    def _apply_replica_hints(self, regions) -> None:
        """PD replica placement landed in the store-heartbeat response:
        hot regions this store should keep a warm follower feed for.
        The hint marks the region a replica-feed target — its first
        stale read mints the line OFF the failover path, and from then
        on the delta stream keeps it patched; residency is still
        arbitrated by the FeedArena's tenant-share eviction, so a hint
        is advisory, never an HBM reservation."""
        from ..utils.metrics import DEVICE_PLACEMENT_COUNTER
        for rid in regions:
            if rid in self._replica_hint_regions:
                continue
            self._replica_hint_regions.add(rid)
            DEVICE_PLACEMENT_COUNTER.labels("replica_spread").inc()

    def replica_serving_stats(self) -> dict:
        """/health ``replica_serving`` rollup source."""
        sup = getattr(self, "device_supervisor", None)
        return {
            "replica_reads": self._replica_reads,
            "refused": self._replica_refused,
            "replica_regions": sorted(self._replica_regions),
            "placement_hints": sorted(self._replica_hint_regions),
            "promotions": getattr(sup, "promotions", 0),
            "demotions": getattr(sup, "demotions", 0),
            "promotion_rebuilds": getattr(sup, "promotion_rebuilds", 0),
        }

    def fastpath_snapshot(self, ent, start_ts: int):
        """Slim per-request snapshot ceremony for a fast-path hit
        (server/fastpath.py): the same safety steps ``_copr_snapshot``
        runs — async-commit max_ts bump, in-memory lock check, raft
        LEASE read — with everything derivable pre-derived on the
        class entry (key hint, ranges, columnar cache key).  Returns
        the current warm columnar snapshot or None (cold line, epoch
        moved): the caller then takes the full ceremony with its
        already-decoded DAG — parity, never staleness."""
        from ..utils import tracker
        cm = self.storage.concurrency_manager
        cm.update_max_ts(start_ts)
        if ent.ranges:
            cm.read_ranges_check(ent.ranges, start_ts)
        else:
            cm.read_range_check(None, None, start_ts)
        with tracker.phase("snapshot"):
            snap = self.raft_kv.snapshot(
                SnapContext(key_hint=ent.key_hint))
        with tracker.phase("columnar_cache"):
            return self.copr_cache.get_fast(snap, ent.base_key,
                                            ent.ranges, start_ts)

    # ---------------------------------------------------------- admin ops

    def split_region(self, region_id: int, split_key: bytes) -> Region:
        from ..storage.txn_types import encode_key
        enc_split = encode_key(split_key)
        with self.lock:
            if not region_id:
                peer = self.raft_store.peer_by_key(enc_split)
            else:
                peer = self.raft_store.region_peer(region_id)
            new_id, new_peer_ids = self.pd.ask_split(peer.region)
            cmd = RaftCmd(peer.region.id, peer.region.epoch,
                          admin=AdminCmd("split", split_key=enc_split,
                                         new_region_id=new_id,
                                         new_peer_ids=tuple(new_peer_ids)))
            box: dict = {}
            peer.propose(cmd, lambda r: box.__setitem__("result", r))
        self._wait_driver(lambda: "result" in box)
        if isinstance(box["result"], Exception):
            raise box["result"]
        return box["result"]["right"]

    def _gc_manager_tick(self) -> None:
        """Auto-GC (gc_worker/gc_manager.rs): when PD's safe point
        advances, sweep versions below it on a BACKGROUND worker — the
        reference runs GC on a dedicated thread because a full-store
        sweep inline in the tick loop would stall raft heartbeats.
        The engine's compaction filter catches anything missed later."""
        try:
            sp = self.pd.get_gc_safe_point()
        except Exception:   # noqa: BLE001 — PD outage: next heartbeat
            return
        if sp <= self._gc_safe_point or self._gc_running:
            return
        self._gc_safe_point = sp
        self._gc_running = True

        def work():
            try:
                self.run_gc(sp)
            except Exception:   # noqa: BLE001 — retried at next advance
                self._gc_safe_point = 0
            finally:
                self._gc_running = False

        threading.Thread(target=work, daemon=True,
                         name="gc-worker").start()

    def _refresh_feature_gate(self) -> None:
        try:
            cv = getattr(self.pd, "cluster_version", None)
            if callable(cv):
                self.feature_gate.set_version(cv())
        except Exception:   # noqa: BLE001 — PD outage: next heartbeat
            pass

    def ingest_sst(self, region_id: int, pairs) -> int:
        """Atomically land pre-built SST pairs in one raft command on
        the target region (sst_importer ingest; fsm/apply.rs IngestSst).
        Keys must be engine-encoded and inside the region's range —
        range violations are refused before proposing."""
        from ..raftstore.cmd import WriteOp
        from ..raftstore.metapb import KeyNotInRegion
        from ..storage.txn_types import split_ts
        from ..utils.failpoint import fail_point
        fail_point("ingest::before_check")
        with self.lock:
            peer = self.raft_store.region_peer(region_id)
            region = peer.region
            for _cf, key, _v in pairs:
                bare = split_ts(key)[0] if len(key) > 8 else key
                if not region.contains(bare):
                    raise KeyNotInRegion(key, region)
            ops = tuple(WriteOp("put", cf, key, value)
                        for cf, key, value in pairs)
            cmd = RaftCmd(region_id, region.epoch, ops=ops)
            box: dict = {}
            fail_point("ingest::before_propose")
            peer.propose(cmd, lambda r: box.__setitem__("result", r))
        self._wait_driver(lambda: "result" in box)
        if isinstance(box["result"], Exception):
            raise box["result"]
        return len(ops)

    def ingest_sst_blob(self, region_id: int, blob: bytes) -> int:
        """Atomically land one v2 SST container with a single raft op
        (fsm/apply.rs IngestSst): the file rides the log as one blob and
        apply bulk-merges its sorted runs — the TPU-native analog of
        RocksDB's IngestExternalFile, which links the file instead of
        replaying keys.  Range check touches only each run's first/last
        key (runs are sorted)."""
        from ..raftstore.cmd import WriteOp
        from ..raftstore.metapb import KeyNotInRegion
        from ..sst_importer import read_sst_cf
        from ..storage.txn_types import split_ts
        from ..utils.failpoint import fail_point
        fail_point("ingest::before_blob_check")
        cf_map = read_sst_cf(blob)      # validates checksum + key order
        n_total = 0
        with self.lock:
            peer = self.raft_store.region_peer(region_id)
            region = peer.region
            for _cf, (keys, _vals) in cf_map.items():
                if not keys:
                    continue
                n_total += len(keys)
                for key in (keys[0], keys[-1]):
                    bare = split_ts(key)[0] if len(key) > 8 else key
                    if not region.contains(bare):
                        raise KeyNotInRegion(key, region)
            cmd = RaftCmd(region_id, region.epoch,
                          ops=(WriteOp("ingest", "", b"", blob),))
            box: dict = {}
            fail_point("ingest::before_blob_propose")
            peer.propose(cmd, lambda r: box.__setitem__("result", r))
        self._wait_driver(lambda: "result" in box)
        if isinstance(box["result"], Exception):
            raise box["result"]
        return n_total

    def change_peer(self, region_id: int, change_type: str,
                    peer_meta: Peer) -> None:
        with self.lock:
            peer = self.raft_store.region_peer(region_id)
            cmd = RaftCmd(region_id, peer.region.epoch,
                          admin=AdminCmd("change_peer",
                                         change_type=change_type,
                                         peer=peer_meta))
            box: dict = {}
            peer.propose(cmd, lambda r: box.__setitem__("result", r))
        self._wait_driver(lambda: "result" in box)
        if isinstance(box["result"], Exception):
            raise box["result"]

    def change_peer_v2(self, region_id: int, changes) -> None:
        """Atomic multi-peer change via joint consensus; ``changes`` =
        [(type, Peer)] (raftstore ChangePeerV2)."""
        from ..raftstore.cmd import encode_change_peer_v2
        with self.lock:
            peer = self.raft_store.region_peer(region_id)
            cmd = RaftCmd(region_id, peer.region.epoch, admin=AdminCmd(
                "change_peer_v2",
                extra=encode_change_peer_v2(changes)))
            box: dict = {}
            peer.propose(cmd, lambda r: box.__setitem__("result", r))
        self._wait_driver(lambda: "result" in box)
        if isinstance(box["result"], Exception):
            raise box["result"]

    def transfer_leader(self, region_id: int, to_peer_id: int) -> None:
        with self.lock:
            peer = self.raft_store.region_peer(region_id)
            peer.node.transfer_leader(to_peer_id)

    def _exec_operator(self, region_id: int, op: dict) -> None:
        """Apply one PD scheduling step (worker/pd.rs executes the
        heartbeat response).  Runs on a worker thread — conf changes
        block on apply and must never stall the heartbeat loop."""
        if not self._operator_busy.acquire(blocking=False):
            return      # one operator at a time, like the pd worker
        def run():
            try:
                try:
                    p = op.get("peer") or {}
                    peer = Peer(p.get("id", 0), p.get("store_id", 0),
                                p.get("learner", False))
                    if op["type"] == "add_peer":
                        self.change_peer(region_id, "add", peer)
                    elif op["type"] == "remove_peer":
                        self.change_peer(region_id, "remove", peer)
                    elif op["type"] == "transfer_leader":
                        self.transfer_leader(region_id, peer.id)
                except Exception:   # noqa: BLE001 — next heartbeat retries
                    pass
            finally:
                self._operator_busy.release()
        threading.Thread(target=run, daemon=True,
                         name="pd-operator").start()

    def region_applied(self, region_id: int) -> int:
        """Local peer's apply index (merge coordination probe)."""
        with self.lock:
            return self.raft_store.region_peer(region_id).node.applied

    def merge_region(self, source_id: int, target_id: int) -> Region:
        """Coordinated region merge over the network (this node must
        lead BOTH regions): PrepareMerge on the source, poll every
        source-peer store's apply index over gRPC until the prepare is
        everywhere, then CommitMerge on the target — the PD-scheduler
        protocol from the in-process fixture, lifted onto real RPC
        (testing/cluster.py merge_region)."""
        import time as _time

        from ..raftstore.peer_storage import encode_region
        from .client import StoreClient
        with self.lock:
            src = self.raft_store.region_peer(source_id)
            tgt = self.raft_store.region_peer(target_id)
            if not tgt.is_leader():
                # check BEFORE proposing PrepareMerge: discovering this
                # after the prepare would leave the source write-dead
                # until a rollback
                raise NotLeaderError(target_id, tgt.leader_peer())
            sr, tr = src.region, tgt.region
            if sorted(p.store_id for p in sr.peers) != \
                    sorted(p.store_id for p in tr.peers):
                raise ValueError("merge requires colocated replicas")
            if not ((sr.end_key and sr.end_key == tr.start_key) or
                    (tr.end_key and tr.end_key == sr.start_key)):
                raise ValueError("merge requires adjacent regions")
            box: dict = {}
            cmd = RaftCmd(source_id, sr.epoch, admin=AdminCmd(
                "prepare_merge", new_region_id=target_id))
            src.propose(cmd, lambda r: box.__setitem__("result", r))
        self._wait_driver(lambda: "result" in box)
        if isinstance(box["result"], Exception):
            raise box["result"]
        prepare_index = box["result"]["prepare_index"]
        source_region = box["result"]["region"]

        try:
            deadline = _time.monotonic() + 10.0
            pending = {p.store_id for p in source_region.peers
                       if p.store_id != self.store_id}
            while pending:
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        f"merge: stores {pending} lag the prepare")
                for sid in list(pending):
                    addr = self.pd.get_store(sid).address
                    try:
                        r = StoreClient(addr).call(
                            "RegionApplied", {"region_id": source_id})
                        if r["applied"] >= prepare_index:
                            pending.discard(sid)
                    except Exception:
                        pass
                if pending:
                    _time.sleep(0.02)

            with self.lock:
                box2: dict = {}
                cmd2 = RaftCmd(target_id, tgt.region.epoch,
                               admin=AdminCmd(
                                   "commit_merge",
                                   merge_index=prepare_index,
                                   extra=encode_region(source_region)))
                tgt.propose(cmd2, lambda r: box2.__setitem__("result", r))
            self._wait_driver(lambda: "result" in box2)
            if isinstance(box2["result"], Exception):
                raise box2["result"]
            return box2["result"]["region"]
        except Exception:
            # the merge cannot proceed: roll the source back so it is
            # not left permanently write-dead (fsm RollbackMerge)
            try:
                self.rollback_merge(source_id)
            except Exception:
                pass    # operator remedy: ctl rollback-merge
            raise

    def rollback_merge(self, region_id: int) -> None:
        """Abort an in-flight PrepareMerge (exec_rollback_merge)."""
        with self.lock:
            peer = self.raft_store.region_peer(region_id)
            box: dict = {}
            cmd = RaftCmd(region_id, peer.region.epoch, admin=AdminCmd(
                "rollback_merge", merge_index=peer.merging or 0))
            peer.propose(cmd, lambda r: box.__setitem__("result", r))
        self._wait_driver(lambda: "result" in box)
        if isinstance(box["result"], Exception):
            raise box["result"]

    def run_gc(self, safe_point: int) -> int:
        """GC every leader region on this store (gc_worker role)."""
        removed = 0
        with self.lock:
            leader_regions = [p.region.id
                              for p in self.raft_store.peers.values()
                              if p.is_leader()]
        for rid in leader_regions:
            snap = self.raft_kv.snapshot(SnapContext(region_id=rid))
            reader = MvccReader(snap)
            txn = MvccTxn(0)
            removed += gc_range(txn, reader, None, None, safe_point)
            if not txn.is_empty():
                self.raft_kv.write(SnapContext(region_id=rid),
                                   WriteData.from_txn(txn))
        return removed

    def status(self) -> dict:
        with self.lock:
            return {
                "store_id": self.store_id,
                "addr": self.addr,
                "health": self.health.stats(),
                "regions": [
                    {"region": wire.enc_region(p.region),
                     "leader": p.is_leader(),
                     "term": p.node.term,
                     "applied": p.node.applied,
                     # the split checker's last estimate (0 = not
                     # scanned yet): a loader waits on it for a layout
                     # the checker has nothing left to do with
                     "approximate_size": p.approximate_size,
                     "resolved_ts": self.resolved_ts.resolver(
                         p.region.id).resolved_ts}
                    for p in self.raft_store.peers.values()],
            }


def encode_first(start: bytes) -> bytes:
    from ..storage.txn_types import encode_key
    return encode_key(start) if start else b""
