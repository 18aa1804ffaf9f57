"""Peer — one replica of one region: raft driving + apply.

Reference: components/raftstore/src/store/peer.rs (Peer: propose :3612,
handle_raft_ready_append :2565) and fsm/apply.rs (exec_raft_cmd
:1370-1740 — write commands, and admin commands: split :1692,
change peer, compact log).  Like the reference, raft-ready handling and
apply run on SEPARATE pollers (SURVEY.md §2.8 item 3): the store's
batch-system poller drives ready/append and hands committed entries to
a second apply batch-system (batch_system.py, wired in store.py — the
fsm/apply.rs analog); a synchronous single-threaded drive mode remains
for tests and the in-process cluster harness.

Read path, fastest first: leader LEASE local reads
(store/worker/read.rs LocalReader — ``local_read`` here, served by
raftkv.py without a proposal or log barrier while the lease holds),
then follower STALE reads (``stale_snapshot`` — any replica, no
consensus round trip, gated on ``read_ts ≤ resolved_ts`` by the
service layer; the replicated device-serving path answers coprocessor
reads from the follower's own delta-patched columnar feed through this
snapshot), then ReadIndex barriers (``propose_read`` /
``replica_read`` for followers), which remain the correctness backstop
whenever neither the lease nor the watermark can vouch.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import Callable, Optional

from ..engine.traits import CF_RAFT, KvEngine
from ..raft.messages import (
    ConfChange,
    ConfChangeType,
    ConfChangeV2,
    EntryType,
    HardState,
    Message,
)
from ..raft.raw_node import LEADER, NotLeader, RawNode
from .cmd import AdminCmd, RaftCmd, WriteOp
from .metapb import (
    EpochNotMatch,
    KeyNotInRegion,
    NotLeaderError,
    Peer as PeerMeta,
    Region,
    RegionEpoch,
    RegionMerging,
)
from .peer_storage import PeerStorage, data_key


@dataclass
class Proposal:
    index: int
    term: int
    cb: Callable            # cb(result | Exception)
    is_read: bool = False   # read barrier: snapshot served at apply time


class RegionSnapshot:
    """Engine snapshot clamped to one region, with the data-key prefix
    applied transparently (reference: raftstore RegionSnapshot).

    ``data_index`` stamps the last applied *data-mutating* entry index —
    the snapshot's data version for columnar/copr caches (read barriers
    and leader noops do not bump it, so repeated reads share a version).
    """

    data_index: Optional[int] = None
    apply_index: Optional[int] = None

    def __init__(self, snap, region: Region):
        self._snap = snap
        self.region = region

    def _check(self, key: bytes) -> bytes:
        if not self.region.contains(key):
            raise KeyNotInRegion(key, self.region)
        return data_key(key)

    def get_value_cf(self, cf: str, key: bytes):
        return self._snap.get_value_cf(cf, self._check(key))

    def get_value(self, key: bytes):
        from ..engine.traits import CF_DEFAULT
        return self.get_value_cf(CF_DEFAULT, key)

    def iterator_cf(self, cf: str, lower: Optional[bytes] = None,
                    upper: Optional[bytes] = None):
        from .peer_storage import region_data_bounds
        rlo, rhi = region_data_bounds(self.region)
        lo = rlo if lower is None else max(rlo, data_key(lower))
        hi = rhi if upper is None else min(rhi, data_key(upper))
        return _PrefixStripIterator(self._snap.iterator_cf(cf, lo, hi))

    def range_cf(self, cf: str, lower: bytes, upper: bytes):
        """Bulk range read clamped to the region; keys keep the data-key
        prefix — the extra prefix_skip tells the native builder how many
        leading bytes to ignore instead of re-slicing every key."""
        rng = getattr(self._snap, "range_cf", None)
        if rng is None:
            return None
        from .peer_storage import region_data_bounds
        rlo, rhi = region_data_bounds(self.region)
        lo = max(rlo, data_key(lower))
        hi = min(rhi, data_key(upper))
        if lo >= hi:
            return [], [], 0
        keys, vals, skip = rng(cf, lo, hi)
        return keys, vals, skip + 1


class _PrefixStripIterator:
    """Strips the data-key prefix so layers above see user keys."""

    def __init__(self, it):
        self._it = it

    def valid(self):
        return self._it.valid()

    def seek(self, key: bytes):
        return self._it.seek(data_key(key))

    def seek_for_prev(self, key: bytes):
        return self._it.seek_for_prev(data_key(key))

    def seek_to_first(self):
        return self._it.seek_to_first()

    def seek_to_last(self):
        return self._it.seek_to_last()

    def next(self):
        return self._it.next()

    def prev(self):
        return self._it.prev()

    def key(self) -> bytes:
        return self._it.key()[1:]

    def value(self) -> bytes:
        return self._it.value()


class RaftPeer:
    def __init__(self, store, region: Region, peer_meta: PeerMeta,
                 engine: KvEngine, initial: bool = False, **raft_cfg):
        import threading as _threading
        # serializes poller processing against lease reads from handler
        # threads in pooled mode (the LocalReader seam); uncontended in
        # the synchronous drive mode
        self.mu = _threading.RLock()
        self.store = store
        self.meta = peer_meta
        self.engine = engine
        self.peer_storage = PeerStorage(engine, region)
        ms, applied = self.peer_storage.load()
        if initial and ms.last_index() == 0:
            # fresh bootstrap/split peer: in-memory marker matching
            # write_initial_state (the engine copy is in the same batch)
            from ..raft.messages import HardState, Snapshot, SnapshotMetadata
            from .peer_storage import RAFT_INIT_LOG_INDEX, RAFT_INIT_LOG_TERM
            meta0 = ms.snapshot.metadata
            ms.snapshot = Snapshot(SnapshotMetadata(
                RAFT_INIT_LOG_INDEX, RAFT_INIT_LOG_TERM,
                meta0.voters, meta0.learners))
            ms.set_hard_state(HardState(RAFT_INIT_LOG_TERM, 0,
                                        RAFT_INIT_LOG_INDEX))
            applied = RAFT_INIT_LOG_INDEX
        ms.snapshot_provider = self._make_snapshot
        self.node = RawNode(peer_meta.id, ms, **raft_cfg)
        self.node.applied = max(self.node.applied, applied)
        # last applied entry that mutated data; restart conservatively
        # re-stamps at applied (one-time cache invalidation per restart).
        # data_index advances while a write batch is still being BUILT;
        # data_index_engine advances only after the batch hits the
        # engine — snapshots must stamp the engine-durable version or a
        # lease read racing the apply pool could stamp a version whose
        # rows it cannot see yet (and the columnar delta cache would
        # then pin wrong data under that version forever)
        self.data_index = self.node.applied
        self.data_index_engine = self.node.applied
        self.proposals: list[Proposal] = []
        self.pending_destroy = False
        # PrepareMerge in flight: the prepare entry's apply index, or
        # None.  Persisted (merge_state_key) so a restarted source peer
        # keeps rejecting writes until commit/rollback.
        from .peer_storage import merge_state_key
        raw = engine.get_value_cf(CF_RAFT, merge_state_key(region.id))
        self.merging: Optional[int] = \
            int.from_bytes(raw, "big") if raw else None
        # sender metas seen on incoming messages — lets an uninitialized
        # peer route responses before it learns the region's peer list
        # (reference: peer.rs Peer::peer_cache)
        self.peer_cache: dict[int, PeerMeta] = {}
        # applied-but-not-yet-notified observer events + role tracking
        self._pending_obs: list = []
        self._last_role = False
        # (index, crc32) of the last applied ComputeHash
        self.consistency_state: Optional[tuple] = None
        # an async raft-log write is in flight (batch_system write pool)
        self._ready_inflight = False
        # sub-region bucket boundaries (split-check pass computes them)
        self.buckets: list = []
        # split-check bookkeeping (fsm/apply.rs size_diff_hint +
        # SplitCheckTask): apply accumulates written bytes; the checker
        # only re-scans the region once the delta crosses
        # region_split_check_diff — a full region scan per tick would
        # stall the store (and contend every lease read) at scale
        self.approximate_size = 0
        self.size_diff_hint = 0
        # apply-pool decoupling (fsm/apply.rs ApplyFsm on its own
        # batch-system): plain-write entry batches apply on a second
        # poller pool; applied_engine tracks what the ENGINE holds —
        # node.applied may run ahead while a batch is queued, and reads
        # must gate on engine state, not raft bookkeeping
        self.applied_engine = self.node.applied
        # proposals are appended by the raft poller and consumed by
        # whichever thread applies — their own lock keeps the apply
        # pool off peer.mu (the whole point of the second pool)
        self._prop_mu = _threading.Lock()
        # hibernation (store/hibernate_state.rs): quiet peers stop
        # ticking; any traffic wakes them
        self._idle_ticks = 0
        self.hibernated = False
        # replica reads (ReadIndex): ctx -> (cb, read_ts, age), plus
        # reads whose commit index the leader confirmed but we have not
        # applied up to yet
        self._replica_reads: dict[int, list] = {}
        self._replica_read_ctx = 0
        self._replica_waiting: list = []    # (index, cb)

    # ------------------------------------------------------------- props

    @property
    def region(self) -> Region:
        return self.peer_storage.region

    def is_leader(self) -> bool:
        return self.node.state == LEADER

    def leader_peer(self) -> Optional[PeerMeta]:
        lid = self.node.leader_id
        for p in self.region.peers:
            if p.id == lid:
                return p
        return None

    # ------------------------------------------------------------- propose

    def _check_header(self, cmd: RaftCmd) -> None:
        region = self.region
        if cmd.epoch.version != region.epoch.version or \
                (cmd.admin is not None and
                 cmd.epoch.conf_ver != region.epoch.conf_ver):
            raise EpochNotMatch(region)
        for op in cmd.ops:
            if op.op == "ingest":
                # the SST's sorted first/last keys were range-checked
                # against this epoch before proposing (node.
                # ingest_sst_blob); a split in between fails the epoch
                # check at apply
                continue
            if not region.contains(op.key):
                raise KeyNotInRegion(op.key, region)

    def propose(self, cmd: RaftCmd, cb: Callable) -> int:
        # (a traced write's callback keeps the instant the thread that
        # proposes took the command: raftkv.py _WriteCallback)
        stamp = getattr(cb, "proposed", None)
        if stamp is not None:
            stamp()
        with self.mu:
            self.wake()
            return self._propose_locked(cmd, cb)

    def _propose_locked(self, cmd: RaftCmd, cb: Callable) -> int:
        from ..utils.failpoint import fail_point
        fail_point("peer::before_propose")
        if not self.is_leader():
            raise NotLeaderError(self.region.id, self.leader_peer())
        if self.merging is not None and (
                cmd.admin is None or
                cmd.admin.kind not in ("rollback_merge",)):
            # a merging source accepts only the rollback; everything
            # else retries after commit/rollback (ProposalInMergingMode)
            raise RegionMerging(self.region.id)
        self._check_header(cmd)
        from ..utils.metrics import RAFT_PROPOSE_COUNTER
        RAFT_PROPOSE_COUNTER.labels(
            cmd.admin.kind if cmd.admin is not None else "write").inc()
        if cmd.admin is not None and cmd.admin.kind == "change_peer":
            a = cmd.admin
            cc_type = {"add": ConfChangeType.ADD_NODE,
                       "add_learner": ConfChangeType.ADD_LEARNER,
                       "remove": ConfChangeType.REMOVE_NODE}[a.change_type]
            index = self.node.propose_conf_change(
                ConfChange(cc_type, a.peer.id, cmd.to_bytes()))
        elif cmd.admin is not None and cmd.admin.kind == "change_peer_v2":
            from .cmd import decode_change_peer_v2
            meta = decode_change_peer_v2(cmd.admin.extra)
            tmap = {"add": ConfChangeType.ADD_NODE,
                    "add_learner": ConfChangeType.ADD_LEARNER,
                    "remove": ConfChangeType.REMOVE_NODE}
            changes = tuple((tmap[c["t"]], c["peer"]["id"])
                            for c in meta["changes"])
            index = self.node.propose_conf_change_v2(ConfChangeV2(
                changes, cmd.to_bytes(),
                leave_joint=meta.get("leave", False)))
        else:
            index = self.node.propose(cmd.to_bytes())
        with self._prop_mu:
            self.proposals.append(Proposal(index, self.node.term, cb))
        return index

    def _inspected_engine_write(self, wb) -> None:
        """Write-path latency inspector (store/async_io/write.rs:24
        LatencyInspector): every apply/persist engine write is timed
        into the store's HealthController, so a degrading disk raises
        the slow score long before it fails outright.  The store's
        fail-slow injection knob (chaos nemesis) adds its delay INSIDE
        the measured window — an injected brownout must look exactly
        like a real one to the health loop."""
        import time as _time
        from ..utils.failpoint import fail_point
        fail_point("store::write_inspect")
        t0 = _time.perf_counter()
        stall = getattr(self.store, "inject_write_delay_s", 0.0)
        if stall > 0:
            _time.sleep(stall)
        self.engine.write(wb)
        health = getattr(self.store, "health", None)
        if health is not None:
            health.record_write(_time.perf_counter() - t0)

    def stale_snapshot(self) -> RegionSnapshot:
        """Engine snapshot with NO consensus round trip — only safe for
        reads at or below the region's resolved-ts watermark (closed
        timestamps: no commit at ts ≤ resolved_ts can newly appear), a
        gate the SERVICE layer enforces before calling this.  Serves
        from any replica, leader or not (kvproto Context stale_read)."""
        with self.mu:
            snap = RegionSnapshot(self.engine.snapshot(), self.region)
            snap.data_index = self.data_index_engine
            snap.apply_index = self.applied_engine
            return snap

    def local_read(self) -> Optional[RegionSnapshot]:
        """Lease-based local read: serve an engine snapshot with NO raft
        round-trip when the leader lease is valid and this leader has
        applied into its own term (reference: store/worker/read.rs
        LocalReader + ReadDelegate — applied_term == term guarantees all
        writes acked by previous leaders are in the applied state; writes
        acked by THIS leader were applied before their ack fired)."""
        with self.mu:
            return self._local_read_locked()

    def _local_read_locked(self) -> Optional[RegionSnapshot]:
        from ..utils.failpoint import fail_point
        # a "return" action forces the lease miss path (read barrier)
        if fail_point("read::before_local_read") is not None:
            return None
        node = self.node
        if not self.is_leader() or not node.in_lease():
            return None
        # gate on what the ENGINE holds: with the apply pool,
        # node.applied may run ahead of a queued batch, and a lease
        # read must never serve a snapshot missing acked writes
        if node.storage.term(self.applied_engine) != node.term:
            return None     # fresh leader: noop not applied yet
        snap = RegionSnapshot(self.engine.snapshot(), self.region)
        snap.data_index = self.data_index_engine
        snap.apply_index = self.applied_engine
        return snap

    def replica_read(self, cb: Callable, read_ts: int = 0) -> None:
        """Follower/replica read (store read parallelism, SURVEY §2.8.4;
        reference: test_replica_read.rs flow over raft ReadIndex).  The
        snapshot is served once this peer has applied up to the commit
        index the LEADER confirmed — same consistency as a leader
        lease read, no leader load.  Dropped requests (no leader yet,
        leader lease pending, message loss) are re-sent from tick() and
        expire after ~2 election timeouts."""
        from ..utils.failpoint import fail_point
        fail_point("read::before_replica_read")
        with self.mu:
            self._replica_read_ctx += 1
            ctx = self._replica_read_ctx
            self._replica_reads[ctx] = [cb, read_ts, 0]
            self.node.request_read_index(ctx, read_ts)

    def _serve_replica_reads(self) -> None:
        """Drain ReadIndex answers + reads unblocked by new applies."""
        node = self.node
        if node.read_states:
            states, node.read_states = node.read_states, []
            for index, ctx in states:
                ent = self._replica_reads.pop(ctx, None)
                if ent is not None:
                    self._replica_waiting.append((index, ent[0]))
        if not self._replica_waiting:
            return
        still = []
        for index, cb in self._replica_waiting:
            # the ReadIndex contract is "applied up to the leader's
            # commit point IN THE ENGINE" — node.applied may run ahead
            # of a queued apply batch
            if self.applied_engine >= index:
                snap = RegionSnapshot(self.engine.snapshot(),
                                      self.region)
                snap.data_index = self.data_index_engine
                snap.apply_index = self.applied_engine
                cb(snap)
            else:
                still.append((index, cb))
        self._replica_waiting = still

    def propose_read(self, cb: Callable) -> int:
        """Read barrier through the log (see module docstring)."""
        with self.mu:
            return self._propose_read_locked(cb)

    def _propose_read_locked(self, cb: Callable) -> int:
        if not self.is_leader():
            raise NotLeaderError(self.region.id, self.leader_peer())
        index = self.node.propose(b"")

        def on_applied(_result):
            if isinstance(_result, Exception):
                cb(_result)
            else:
                snap = RegionSnapshot(self.engine.snapshot(), self.region)
                snap.data_index = self.data_index_engine
                snap.apply_index = index
                cb(snap)
        with self._prop_mu:
            self.proposals.append(Proposal(index, self.node.term,
                                           on_applied,
                                       is_read=True))
        return index

    # ------------------------------------------------------------- ready

    def handle_ready(self, async_writer=None, on_persisted=None,
                     on_persist_failed=None,
                     apply_ctx=None) -> list[Message]:
        """Persist, apply, return messages to send.  Reference:
        handle_raft_ready_append + the apply poller, collapsed.

        ``async_writer`` (store/async_io/write.rs): append-only readies
        (log entries + hard state, no apply, no snapshot) hand their
        WAL batch to the write-worker pool and return WITHOUT their
        messages — the append ack must not leave before the fsync.  The
        pool persists (group-committed across peers) then calls
        ``on_persisted(rd)`` from a poller-routed context, which sends
        the messages and advances.  While one async persist is in
        flight the peer produces no further ready (the _ready_inflight
        gate), preserving the ready/advance protocol.
        """
        from ..utils.failpoint import fail_point
        out: list[Message] = []
        while self.node.has_ready():
            if self._ready_inflight:
                break       # awaiting the async log write
            from ..utils.metrics import RAFT_READY_COUNTER
            RAFT_READY_COUNTER.inc()
            fail_point("peer::handle_ready")
            rd = self.node.ready()
            if async_writer is not None and \
                    not getattr(async_writer, "failed", False) and \
                    rd.snapshot is None and \
                    not rd.committed_entries and rd.entries:
                fail_point("raftlog::before_persist")
                wb = self.engine.write_batch()
                meta = self.node.storage.snapshot.metadata
                self.peer_storage.persist(
                    wb, rd.entries, rd.hard_state,
                    truncated=(meta.index, meta.term))
                self._ready_inflight = True
                async_writer.submit(
                    wb, lambda rd=rd: on_persisted(self.region.id, rd),
                    fail_cb=(None if on_persist_failed is None else
                             (lambda: on_persist_failed(self.region.id))))
                break
            if apply_ctx is not None and rd.snapshot is None and \
                    rd.committed_entries and \
                    all(self._is_plain_write(e)
                        for e in rd.committed_entries):
                # decoupled apply (fsm/apply.rs: ApplyFsm runs on its
                # own batch-system): persist the log, queue the
                # committed plain-write batch on the apply pool, and
                # advance — a slow apply (bulk ingest, big writes)
                # never stalls this poller's raft ticks or elections.
                # Only plain writes decouple: admin/conf-change/read
                # barriers mutate raft-side state and stay inline,
                # ordered behind the queue by the drain below.
                fail_point("raftlog::before_persist")
                wb = self.engine.write_batch()
                meta = self.node.storage.snapshot.metadata
                self.peer_storage.persist(wb, rd.entries, rd.hard_state,
                                          truncated=(meta.index,
                                                     meta.term))
                if not wb.is_empty():
                    self._inspected_engine_write(wb)
                apply_ctx.send(self.region.id, rd.committed_entries)
                out.extend(rd.messages)
                self.node.advance(rd)
                continue
            if apply_ctx is not None and (rd.committed_entries or
                                          rd.snapshot is not None):
                # complex batch OR snapshot: every queued plain apply
                # must land first — entries for commit order, snapshots
                # because a queued pre-snapshot write batch applied
                # AFTER apply_snapshot would clobber post-snapshot data
                # and regress the apply state
                apply_ctx.drain(self.region.id)
            wb = self.engine.write_batch()
            if rd.snapshot is not None:
                fail_point("snapshot::before_apply")
                region = self.peer_storage.apply_snapshot(wb, rd.snapshot)
                # a snapshot replaces all region data: stamp the data
                # version so columnar/copr caches can never serve
                # pre-snapshot entries, and tell observers the data was
                # replaced WHOLESALE — committed-write delta logs cover
                # nothing at or before this index
                self.data_index = max(self.data_index,
                                      rd.snapshot.metadata.index)
                self.applied_engine = max(self.applied_engine,
                                          rd.snapshot.metadata.index)
                self._pending_obs.append(
                    (rd.snapshot.metadata.index, None))
                self.store.on_region_changed(self, region)
                fail_point("snapshot::after_apply")
            fail_point("raftlog::before_persist")
            meta = self.node.storage.snapshot.metadata
            self.peer_storage.persist(wb, rd.entries, rd.hard_state,
                                      truncated=(meta.index, meta.term))
            fail_point("apply::before_entries")
            if rd.committed_entries:
                from ..utils.metrics import RAFT_APPLY_COUNTER
                RAFT_APPLY_COUNTER.inc(len(rd.committed_entries))
            cbs: list = []
            for entry in rd.committed_entries:
                if not entry.data and not wb.is_empty() and \
                        self._pending_read_at(entry.index, entry.term):
                    # flush the applied prefix so the read barrier's
                    # engine snapshot includes every earlier entry of
                    # this same ready batch (apply state rides along so
                    # a crash here never re-applies admin commands)
                    self.peer_storage.persist_apply(wb, entry.index - 1)
                    self.engine.write(wb)
                    self.data_index_engine = self.data_index
                    wb = self.engine.write_batch()
                elif not wb.is_empty() and self._is_compute_hash(entry):
                    # ComputeHash digests the ENGINE state: earlier
                    # writes of this same ready batch must be flushed
                    # first or replicas batching differently would
                    # digest different visible prefixes at one index
                    self.peer_storage.persist_apply(wb, entry.index - 1)
                    self.engine.write(wb)
                    self.data_index_engine = self.data_index
                    wb = self.engine.write_batch()
                self._apply_entry(wb, entry, cbs)
            if rd.committed_entries:
                self.peer_storage.persist_apply(
                    wb, rd.committed_entries[-1].index)
            fail_point("apply::before_write")
            if not wb.is_empty():
                self._inspected_engine_write(wb)
            fail_point("apply::after_write")
            if rd.committed_entries or rd.snapshot is not None:
                # only paths that actually applied may publish: these
                # drained the apply pool first, so data_index is fully
                # durable here.  A message-only ready must NOT copy a
                # data_index the apply-pool thread bumped mid-batch —
                # that would re-open the stale-stamp race the
                # data_index_engine split closes (and flush the pool's
                # pending observer events before their write lands).
                self.data_index_engine = self.data_index
                # observers run AFTER the engine write so they only ever
                # see durable state (coprocessor/mod.rs post-apply hooks)
                self._dispatch_obs()
            if rd.committed_entries:
                self.applied_engine = rd.committed_entries[-1].index
            # ACKs leave only now — after the engine write (see
            # _apply_entry)
            for prop, res in cbs:
                prop.cb(res)
            out.extend(rd.messages)
            self.node.advance(rd)
        self._serve_replica_reads()
        role = self.is_leader()
        if role != self._last_role:
            self._last_role = role
            if role and self.node.in_joint() and \
                    self.node._pending_conf_index <= self.node.applied:
                # the previous leader died between enter and leave: a
                # NEW leader re-proposes the bare leave or the cluster
                # stays joint forever (raft-rs auto transition)
                try:
                    self.node.propose_conf_change_v2(
                        ConfChangeV2((), b"", leave_joint=True),
                        force=True)
                except Exception:   # noqa: BLE001 — retried next ready
                    pass
            self.store.coprocessor_host.notify_role_change(
                self.region.id, role)
        return out

    @staticmethod
    def _is_compute_hash(entry) -> bool:
        if not entry.data or entry.entry_type is EntryType.CONF_CHANGE:
            return False
        return RaftCmd.peek_admin_kind(entry.data) == "compute_hash"

    @staticmethod
    def _is_plain_write(entry) -> bool:
        """Entries the apply pool may execute concurrently with raft
        driving: KV writes only — no admin (mutates region/raft meta),
        no conf change, no read barrier (serves an engine snapshot that
        must reflect every earlier entry)."""
        if not entry.data or entry.entry_type is EntryType.CONF_CHANGE:
            return False
        return RaftCmd.peek_admin_kind(entry.data) is None

    def apply_plain_entries(self, entries) -> None:
        """Apply one committed plain-write batch on the APPLY pool
        (fsm/apply.rs ApplyDelegate::handle_raft_committed_entries).

        Runs WITHOUT peer.mu: region meta is stable (admin entries
        execute inline behind an apply-queue drain), proposals have
        their own lock, and ``applied_engine`` advances last so reads
        gate on durable engine state."""
        from ..utils.failpoint import fail_point
        from ..utils.metrics import RAFT_APPLY_COUNTER
        RAFT_APPLY_COUNTER.inc(len(entries))
        fail_point("apply::before_entries")
        wb = self.engine.write_batch()
        cbs: list = []
        for entry in entries:
            self._apply_entry(wb, entry, cbs)
        self.peer_storage.persist_apply(wb, entries[-1].index)
        fail_point("apply::before_write")
        if not wb.is_empty():
            self._inspected_engine_write(wb)
        self.data_index_engine = self.data_index
        fail_point("apply::after_write")
        self._dispatch_obs()
        self.applied_engine = entries[-1].index
        for prop, res in cbs:
            prop.cb(res)

    def _dispatch_obs(self) -> None:
        """Flush applied-entry observer events, post-engine-write.
        ``ops is None`` marks a wholesale data replacement (snapshot
        apply) — delta subscribers must drop their coverage."""
        if not self._pending_obs:
            return
        host = self.store.coprocessor_host
        for index, ops in self._pending_obs:
            if ops is None:
                host.notify_data_replaced(self.region.id, index)
            else:
                host.notify_apply_write(self.region.id, index, ops)
        self._pending_obs.clear()

    def on_log_persisted(self, rd) -> list[Message]:
        """Async-IO completion: the log batch hit disk — now the acks
        may leave and the ready advances (write.rs persisted callback).
        Runs serialized with other peer work (poller mailbox)."""
        from ..utils.failpoint import fail_point
        fail_point("raftlog::after_persist")
        self._ready_inflight = False
        self.node.advance(rd)
        return list(rd.messages)

    # ------------------------------------------------------------- apply

    def _pending_read_at(self, index: int, term: int) -> bool:
        with self._prop_mu:
            for p in self.proposals:
                if p.index >= index:
                    return p.index == index and p.term == term \
                        and p.is_read
        return False

    def _take_proposal(self, index: int, term: int) -> Optional[Proposal]:
        stale = []
        got = None
        with self._prop_mu:
            while self.proposals and self.proposals[0].index <= index:
                p = self.proposals.pop(0)
                if p.index == index and p.term == term:
                    got = p
                    break
                stale.append(p)
        for p in stale:     # callbacks run outside the lock
            p.cb(NotLeaderError(self.region.id, self.leader_peer()))
        return got

    def _apply_entry(self, wb, entry, out_cbs: list) -> None:
        """Execute one committed entry into ``wb``; the proposal
        callback (the client's ACK) is APPENDED to ``out_cbs``, not
        fired — acks must not leave before the batch's engine write
        lands, or a concurrent lease read could miss an acked write
        (the apply pool made that window real; the reference invokes
        apply callbacks after the write batch commits the same way)."""
        prop = self._take_proposal(entry.index, entry.term)
        if not entry.data:
            if prop is not None:
                out_cbs.append((prop, {}))  # read barrier / leader noop
            return
        if entry.entry_type is EntryType.CONF_CHANGE:
            if ConfChangeV2.is_v2(entry.data):
                cc2 = ConfChangeV2.from_bytes(entry.data)
                if cc2.context:
                    cmd = RaftCmd.from_bytes(cc2.context)
                    admin = cmd.admin
                else:       # bare leave from a new leader
                    admin = AdminCmd("change_peer_v2")
                result = self._exec_change_peer_v2(wb, admin, cc2)
            else:
                cc = ConfChange.from_bytes(entry.data)
                cmd = RaftCmd.from_bytes(cc.context)
                result = self._exec_admin(wb, cmd.admin, cc=cc,
                                          index=entry.index)
        else:
            cmd = RaftCmd.from_bytes(entry.data)
            try:
                self._check_epoch_at_apply(cmd)
            except EpochNotMatch as e:
                if prop is not None:
                    out_cbs.append((prop, e))
                return
            if cmd.admin is not None:
                result = self._exec_admin(wb, cmd.admin,
                                          index=entry.index)
            else:
                # only actual KV mutations bump the data version —
                # admin commands (compact_log, change_peer) leave table
                # data untouched and splits bump epoch.version, so the
                # columnar cache key (which includes both) stays exact
                # without spurious invalidation on log GC
                self.data_index = entry.index
                result = self._exec_write(wb, cmd)
                self._pending_obs.append((entry.index, cmd.ops))
        if prop is not None:
            out_cbs.append((prop, result))

    def _check_epoch_at_apply(self, cmd: RaftCmd) -> None:
        region = self.region
        if cmd.epoch.version != region.epoch.version:
            raise EpochNotMatch(region)

    def _exec_write(self, wb, cmd: RaftCmd) -> dict:
        for op in cmd.ops:
            # size_diff_hint: written bytes accumulate until the split
            # checker consumes them (deletes count too — they change
            # the region's size estimate in the same direction the
            # reference's apply metrics do)
            self.size_diff_hint += len(op.key) + len(op.value)
            if op.op == "put":
                wb.put_cf(op.cf, data_key(op.key), op.value)
            elif op.op == "delete":
                wb.delete_cf(op.cf, data_key(op.key))
            elif op.op == "delete_range":
                wb.delete_range_cf(op.cf, data_key(op.key),
                                   data_key(op.value))
            elif op.op == "ingest":
                from ..utils.failpoint import fail_point
                fail_point("apply::before_ingest")
                # bulk SST ingest (fsm/apply.rs IngestSst): op.value is
                # a v2 SST container; whole sorted runs bulk-merge into
                # the engine instead of replaying per-key ops.  Like
                # the reference's file ingest, rows land WITHOUT
                # passing the CDC observer — BR/Lightning require
                # no-import during replication for the same reason.
                from ..sst_importer import read_sst_cf
                # memo=True: hand this decode to the streaming cold
                # pipeline's observer read of the same blob object
                for cf, (keys, vals) in read_sst_cf(
                        op.value, memo=True).items():
                    wb.ingest_cf(cf, [data_key(k) for k in keys], vals)
            else:   # pragma: no cover
                raise ValueError(op.op)
        return {}

    def _exec_admin(self, wb, admin: AdminCmd,
                    cc: Optional[ConfChange] = None,
                    index: int = 0) -> dict:
        from ..utils.failpoint import fail_point
        if admin.kind == "split":
            fail_point("apply::before_split")
            return self._exec_split(wb, admin)
        if admin.kind == "change_peer":
            fail_point("apply::before_conf_change")
            return self._exec_change_peer(wb, admin, cc)
        if admin.kind == "compact_log":
            fail_point("apply::before_compact_log")
            return self._exec_compact_log(wb, admin)
        if admin.kind == "prepare_merge":
            fail_point("apply::before_prepare_merge")
            return self._exec_prepare_merge(wb, admin, index)
        if admin.kind == "commit_merge":
            fail_point("apply::before_commit_merge")
            return self._exec_commit_merge(wb, admin)
        if admin.kind == "rollback_merge":
            return self._exec_rollback_merge(wb, admin)
        if admin.kind == "compute_hash":
            return self._exec_compute_hash(index, admin)
        if admin.kind == "verify_hash":
            return self._exec_verify_hash(admin)
        raise ValueError(admin.kind)    # pragma: no cover

    # -- consistency check (worker/consistency_check.rs + fsm/apply.rs
    #    exec_compute_hash/exec_verify_hash) --
    #
    # The leader proposes ComputeHash; EVERY replica, applying it at the
    # same log index over the same replicated data, computes an identical
    # digest of the region's data CFs.  The leader then proposes
    # VerifyHash(index, its own digest); a replica whose stored digest
    # for that index differs has diverged — the reference panics the
    # node, here InconsistentRegion surfaces through the drive loop.

    def _exec_compute_hash(self, index: int,
                           admin: Optional[AdminCmd] = None) -> dict:
        import zlib
        from ..engine.traits import CF_DEFAULT, CF_LOCK, CF_WRITE
        from .peer_storage import region_data_bounds
        # GC via each node's LOCAL compaction filter legitimately drops
        # versions at/below the safe point at node-local times — raw
        # bytes of two healthy replicas may differ below it.  The
        # leader pins its safe point into the proposal; every replica
        # hashes only versions ABOVE it, so the digest is deterministic
        # whether or not a replica has compacted yet.
        safe_point = 0
        if admin is not None and len(admin.extra) == 8:
            (safe_point,) = struct.unpack(">Q", admin.extra)
        lo, hi = region_data_bounds(self.region)
        crc = 0
        for cf in (CF_DEFAULT, CF_LOCK, CF_WRITE):
            crc = zlib.crc32(cf.encode(), crc)
            it = self.engine.iterator_cf(cf, lo, hi)
            ok = it.seek_to_first()
            while ok:
                key = it.key()
                if safe_point and cf in (CF_DEFAULT, CF_WRITE) and \
                        len(key) > 9:
                    from ..storage.txn_types import split_ts
                    _, ts = split_ts(key[1:])
                    if ts <= safe_point:
                        ok = it.next()
                        continue
                crc = zlib.crc32(key, crc)
                crc = zlib.crc32(it.value(), crc)
                ok = it.next()
        # region state participates too (apply.rs hashes the region state
        # key): replicas at the same index must agree on the epoch
        ep = self.region.epoch
        crc = zlib.crc32(struct.pack(">QII", self.region.id, ep.conf_ver,
                                     ep.version), crc)
        self.consistency_state = (index, crc)
        return {"compute_hash": {"index": index, "hash": crc}}

    def _exec_verify_hash(self, admin: AdminCmd) -> dict:
        expect_index, expect_hash = struct.unpack(">QI", admin.extra)
        st = self.consistency_state
        if st is None or st[0] != expect_index:
            # stale/missed ComputeHash (e.g. this replica restarted or
            # caught up via snapshot past the compute index): the
            # reference logs and skips — a later round re-checks
            return {"verify_hash": "skipped"}
        if st[1] != expect_hash:
            from .metapb import InconsistentRegion
            raise InconsistentRegion(
                f"region {self.region.id} hash mismatch at index "
                f"{expect_index}: local {st[1]:#x} != leader "
                f"{expect_hash:#x}")
        return {"verify_hash": "ok"}

    def _exec_prepare_merge(self, wb, admin: AdminCmd,
                            index: int) -> dict:
        """fsm/apply.rs exec_prepare_merge: epoch bump + persisted merge
        state; the source stops accepting proposals until commit or
        rollback."""
        from dataclasses import replace
        from .peer_storage import merge_state_key
        region = self.region
        new_region = replace(region, epoch=RegionEpoch(
            region.epoch.conf_ver, region.epoch.version + 1))
        self.peer_storage.persist_region(wb, new_region)
        wb.put_cf(CF_RAFT, merge_state_key(region.id),
                  index.to_bytes(8, "big"))
        self.merging = index
        self.store.on_region_changed(self, new_region)
        return {"region": new_region, "prepare_index": index}

    def _exec_rollback_merge(self, wb, admin: AdminCmd) -> dict:
        """fsm/apply.rs exec_rollback_merge: clear the merge state and
        bump the epoch so stale CommitMerge attempts epoch-fail."""
        from dataclasses import replace
        from .peer_storage import merge_state_key
        region = self.region
        new_region = replace(region, epoch=RegionEpoch(
            region.epoch.conf_ver, region.epoch.version + 1))
        self.peer_storage.persist_region(wb, new_region)
        wb.delete_cf(CF_RAFT, merge_state_key(region.id))
        self.merging = None
        self.store.on_region_changed(self, new_region)
        return {"region": new_region}

    def _exec_commit_merge(self, wb, admin: AdminCmd) -> dict:
        """fsm/apply.rs exec_commit_merge (simplified to the coordinated
        protocol): the TARGET absorbs the adjacent source region.

        Data never moves — both regions share this store's engine; only
        the region boundary and the source's raft-local state change.
        Safety precondition (the coordinator enforced it before
        proposing, node.merge_region): every source peer has applied the
        PrepareMerge, so the local source peer's data is complete up to
        the merge point.  The reference instead ships the source log
        tail inside CommitMerge — the coordinated wait is the
        in-process/PD-scheduler equivalent.
        """
        from dataclasses import replace
        from .peer_storage import decode_region
        source = decode_region(admin.extra)
        region = self.region
        speer = self.store.peers.get(source.id)
        if speer is not None:
            # drain any committed-but-unapplied source entries first
            # (messages are dropped; the group is being destroyed)
            if speer.node.applied < admin.merge_index:
                speer.handle_ready()
            if speer.node.applied < admin.merge_index:
                raise AssertionError(
                    f"commit_merge: source {source.id} applied "
                    f"{speer.node.applied} < prepare {admin.merge_index}")
        # b"" as end_key means +infinity — it must never compare equal
        # to a b"" start_key (-infinity)
        if source.end_key and source.end_key == region.start_key:
            new_start, new_end = source.start_key, region.end_key
        elif region.end_key and region.end_key == source.start_key:
            new_start, new_end = region.start_key, source.end_key
        else:
            raise AssertionError("commit_merge: regions not adjacent")
        new_region = replace(
            region, start_key=new_start, end_key=new_end,
            epoch=RegionEpoch(
                max(region.epoch.conf_ver, source.epoch.conf_ver),
                max(region.epoch.version, source.epoch.version) + 1))
        self.peer_storage.persist_region(wb, new_region)
        self.store.destroy_peer(source.id)
        self.store.on_region_changed(self, new_region)
        return {"region": new_region}

    def _exec_split(self, wb, admin: AdminCmd) -> dict:
        """fsm/apply.rs exec_batch_split: left keeps the id, right is the
        new region [split_key, end); both bump epoch.version."""
        region = self.region
        from dataclasses import replace
        new_epoch = RegionEpoch(region.epoch.conf_ver,
                                region.epoch.version + 1)
        right_peers = tuple(
            PeerMeta(pid, p.store_id, p.is_learner)
            for pid, p in zip(admin.new_peer_ids, region.peers))
        right = Region(admin.new_region_id, admin.split_key,
                       region.end_key, new_epoch, right_peers)
        left = replace(region, end_key=admin.split_key, epoch=new_epoch)
        self.peer_storage.persist_region(wb, left)
        self.store.create_split_peer(wb, right, was_leader=self.is_leader())
        # split-aware observers (delta-log carry-over, device-side
        # line/feed slicing) act BEFORE the generic region_changed
        # sweep tears the parent's cache lines down.  Admin entries
        # never bump data_index, so self.data_index IS the last
        # pre-split write — the exact stamp for both children
        right_peer = self.store.peers.get(right.id)
        self.store.coprocessor_host.notify_region_split(
            left, right, self.data_index,
            right_peer.data_index if right_peer is not None else None)
        self.store.on_region_changed(self, left)
        return {"left": left, "right": right}

    def _exec_change_peer(self, wb, admin: AdminCmd,
                          cc: Optional[ConfChange]) -> dict:
        region = self.region
        peers = list(region.peers)
        p = admin.peer
        if admin.change_type in ("add", "add_learner"):
            peers = [x for x in peers if x.id != p.id]
            peers.append(PeerMeta(p.id, p.store_id,
                                  admin.change_type == "add_learner"))
        else:
            peers = [x for x in peers if x.id != p.id]
        new_region = region.with_peers(peers)
        self.peer_storage.persist_region(wb, new_region)
        if cc is not None:
            self.node.apply_conf_change(cc)
        self.store.on_region_changed(self, new_region)
        if admin.change_type == "remove" and p.id == self.meta.id:
            self.pending_destroy = True
        return {"region": new_region}

    def _exec_change_peer_v2(self, wb, admin: AdminCmd, cc2) -> dict:
        """Joint membership change apply (fsm/apply.rs ChangePeerV2 +
        raft §6).  Enter: region carries the UNION of old and new peer
        sets while raft enforces both majorities; the leader then
        auto-proposes the LEAVE, whose apply installs the target set.
        """
        import struct as _struct

        from dataclasses import replace
        from .cmd import decode_change_peer_v2
        from .peer_storage import joint_state_key
        meta = decode_change_peer_v2(admin.extra) if admin.extra else             {"changes": [], "leave": True, "target": None}
        region = self.region
        self.node.apply_conf_change_v2(cc2)
        # persist the joint state (BOTH sets: the incoming voters can't
        # be derived from region.peers, which holds the union) so a
        # restart mid-joint keeps the both-majority rules
        node = self.node
        if node.voters_outgoing:
            out_s = sorted(node.voters_outgoing)
            in_s = sorted(node.voters)
            wb.put_cf(CF_RAFT, joint_state_key(region.id),
                      _struct.pack(">II", len(out_s), len(in_s)) +
                      b"".join(_struct.pack(">Q", v)
                               for v in out_s + in_s))
        else:
            wb.delete_cf(CF_RAFT, joint_state_key(region.id))
        if cc2.leave_joint:
            if meta.get("target"):
                target = tuple(PeerMeta(p["id"], p["store_id"],
                                        p.get("learner", False))
                               for p in meta["target"])
            else:
                # bare leave (new-leader re-proposal): the target is the
                # post-leave raft membership filtered from the union
                member = self.node.voters | self.node.learners
                target = tuple(p for p in region.peers
                               if p.id in member)
            new_region = replace(
                region, peers=target,
                epoch=RegionEpoch(region.epoch.conf_ver + 1,
                                  region.epoch.version))
            self.peer_storage.persist_region(wb, new_region)
            self.store.on_region_changed(self, new_region)
            if not any(p.id == self.meta.id for p in target):
                self.pending_destroy = True
            return {"region": new_region}
        # enter joint: union of old peers and the incoming changes
        peers = {p.id: p for p in region.peers}
        target = dict(peers)
        for c in meta["changes"]:
            p = c["peer"]
            pm = PeerMeta(p["id"], p["store_id"], c["t"] == "add_learner")
            if c["t"] == "remove":
                target.pop(p["id"], None)
            else:
                target[p["id"]] = pm
                peers[p["id"]] = pm
        new_region = replace(
            region, peers=tuple(peers.values()),
            epoch=RegionEpoch(region.epoch.conf_ver + 1,
                              region.epoch.version))
        self.peer_storage.persist_region(wb, new_region)
        self.store.on_region_changed(self, new_region)
        if self.is_leader():
            # auto-leave (raft-rs ConfChangeV2 auto transition): the
            # leave entry carries the TARGET peer set for the meta
            from .cmd import encode_change_peer_v2
            leave_cmd = RaftCmd(
                new_region.id, new_region.epoch,
                admin=AdminCmd("change_peer_v2",
                               extra=encode_change_peer_v2(
                                   leave=True,
                                   target=list(target.values()))))
            self.node.propose_conf_change_v2(
                ConfChangeV2((), leave_cmd.to_bytes(), leave_joint=True),
                force=True)
        return {"region": new_region, "joint": True}

    def _exec_compact_log(self, wb, admin: AdminCmd) -> dict:
        index = min(admin.compact_index, self.node.applied)
        if index > self.node.storage.snapshot.metadata.index:
            self.node.storage.compact(index)
            self.peer_storage.compact_log(wb, index)
            # Rewrite raft_state with the POST-compact truncated marker in
            # the same batch: handle_ready persisted it with the marker
            # captured before this apply, and a crash between the two
            # writes would leave trunc_idx pointing below log entries that
            # this batch just deleted — an unrecoverable, non-contiguous
            # log on restart (reference: fsm/apply.rs exec_compact_log
            # updates RaftTruncatedState atomically with the deletion).
            meta = self.node.storage.snapshot.metadata
            self.peer_storage.persist(
                wb, [],
                HardState(self.node.term, self.node.vote, self.node.commit),
                truncated=(meta.index, meta.term))
        return {}

    # ------------------------------------------------------------- misc

    def _make_snapshot(self, index: int, term: int):
        # Generate at the APPLIED index, not the compaction marker: the
        # engine data + region meta reflect exactly node.applied, and a
        # lower stamp would make the receiver re-apply entries (e.g. conf
        # changes double-bumping conf_ver).  Reference: peer_storage.rs
        # do_snapshot uses the apply state's applied_index.
        from ..utils.failpoint import fail_point
        fail_point("snapshot::before_generate")
        applied = self.node.applied
        t = self.node.storage.term(applied)
        if t is None:
            t = term
        # raft-level conf travels verbatim: while JOINT, a receiver
        # must apply both-majority rules — deriving voters from the
        # region's peer union would weaken elections to a single
        # union-majority (unsafe: {old majority} can outvote there)
        node = self.node
        conf = (sorted(node.voters), sorted(node.learners),
                sorted(node.voters_outgoing))
        return self.peer_storage.generate_snapshot(applied, t,
                                                   self.region, conf)

    def step(self, msg: Message) -> None:
        # heartbeat chatter is not activity — counting it would keep
        # every region awake forever; real entries/votes/snapshots wake
        from ..raft.messages import MsgType as _MT
        if msg.msg_type not in (_MT.HEARTBEAT, _MT.HEARTBEAT_RESPONSE) \
                or msg.entries:
            self.wake()
        elif self.hibernated:
            # a heartbeat reaching a hibernated peer means some peer is
            # still awake (e.g. a rejoining follower): answer it
            self.wake()
        self.node.step(msg)

    HIBERNATE_IDLE_TICKS = 30   # ~3 election timeouts of quiet

    def tick(self) -> None:
        if getattr(self.store.config, "hibernate_regions", False):
            # hibernate (store/hibernate_state.rs:88): after sustained
            # quiet the leader stops heartbeating entirely, and
            # followers SLOW their election clocks 8× instead of
            # stopping them — a crashed hibernating leader is still
            # detected (pre-vote fires eventually and wakes the region)
            # without per-tick chatter from thousands of idle regions.
            self._idle_ticks += 1
            if self._idle_ticks > self.HIBERNATE_IDLE_TICKS:
                self.hibernated = True
                if self.is_leader() or self._idle_ticks % 8 != 0:
                    return
        self.node.tick()
        if self._replica_reads:
            self._retry_replica_reads()

    def wake(self) -> None:
        self._idle_ticks = 0
        self.hibernated = False

    def _retry_replica_reads(self) -> None:
        """Re-send pending ReadIndex requests (dropped request, leader
        without a lease yet, election churn) and expire hopeless ones."""
        expire_at = 4 * self.node._election_tick
        dead = []
        for ctx, ent in self._replica_reads.items():
            ent[2] += 1
            if ent[2] >= expire_at:
                dead.append(ctx)
            elif ent[2] % 2 == 0:
                self.node.request_read_index(ctx, ent[1])
        for ctx in dead:
            cb, _ts, _age = self._replica_reads.pop(ctx)
            cb(NotLeaderError(self.region.id, self.leader_peer()))
