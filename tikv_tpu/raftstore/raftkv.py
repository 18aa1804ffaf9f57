"""RaftKv — the kv.Engine implemented by raft proposal + apply wait.

Reference: src/server/raftkv/mod.rs (RaftKv: async_snapshot :603 routes
a read through the consensus/lease path; async_write :472 proposes a
RaftCmdRequest and resolves when applied).  The synchronous surface here
blocks on a ``driver`` callable that pumps the in-process cluster (or the
standalone store loop) until the callback fires — the same shape as the
reference blocking on the apply callback.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from ..kv.engine import SnapContext, WriteData
from ..utils import tracker
from .cmd import RaftCmd, WriteOp
from .metapb import NotLeaderError
from .store import RaftStore


class _WriteCallback:
    """A proposed write's apply callback, which also stamps the command's
    way WHERE it happens: ``proposed()`` on the thread that proposes it
    (``Peer.propose``: the peer's poller on a pooled store), the call
    itself on the thread that applied it.  ``RaftKv.write`` reads the
    stamps once its wait is over."""

    __slots__ = ("box", "t_proposed", "t_applied")

    def __init__(self):
        self.box: dict = {}
        self.t_proposed = self.t_applied = None

    def proposed(self) -> None:
        self.t_proposed = time.perf_counter_ns()

    def __call__(self, result) -> None:
        self.t_applied = time.perf_counter_ns()
        self.box["result"] = result


class RaftKv:
    def __init__(self, store: RaftStore,
                 driver: Optional[Callable[[Callable[[], bool]], None]] = None,
                 lock=None, latency_inspector=None):
        self.store = store
        self._driver = driver if driver is not None else self._local_drive
        # serializes lease reads against the apply loop so the engine
        # snapshot and its data_index stamp are taken atomically
        self._lock = lock
        self.lease_reads = 0
        self.barrier_reads = 0
        self.stale_reads = 0
        # write-path latency inspector feeding the health controller's
        # slow score (store/async_io/write.rs:24 LatencyInspector)
        self._latency_inspector = latency_inspector
        # read-traffic hook feeding the load-split controller
        # (split_controller.rs: reads report their keys per region)
        self.on_read = None

    def _local_drive(self, done: Callable[[], bool]) -> None:
        for _ in range(10000):
            if done():
                return
            if self.store.drive() == 0 and done():
                return
            self.store.tick()
        raise TimeoutError("raft command did not complete")

    def _wait(self, box: dict) -> None:
        self._driver(lambda: "result" in box)
        result = box["result"]
        if isinstance(result, Exception):
            raise result

    # -- kv.Engine --

    def snapshot(self, ctx: SnapContext):
        # fail-slow injection (chaos): a browned-out store serves reads
        # slowly but correctly — the shed/hedge machinery above must
        # route around it, nothing below here misbehaves
        stall = getattr(self.store, "inject_read_delay_s", 0.0)
        if stall > 0:
            import time as _time
            _time.sleep(stall)
        peer = self._route(ctx)
        if self.on_read is not None and ctx.key_hint:
            self.on_read(peer.region.id, ctx.key_hint)
        if ctx.stale_read:
            # resolved-ts-gated local snapshot: correctness rests on the
            # caller's read_ts ≤ resolved_ts check (service layer) —
            # below the watermark no new commit can appear, so any
            # replica's applied state answers the MVCC read exactly
            self.stale_reads += 1
            return peer.stale_snapshot()
        if ctx.replica_read and not peer.is_leader():
            # follower read via ReadIndex (SURVEY §2.8.4): consistent at
            # the leader's commit point, zero leader load.  In the
            # synchronous drive mode registration must hold the node
            # lock — the drive thread touches the same read state
            # without peer.mu there.
            box: dict = {}
            cb = lambda r: box.__setitem__("result", r)  # noqa: E731
            if self._lock is not None and not self.store.pooled():
                with self._lock:
                    peer.replica_read(cb, ctx.read_ts)
            else:
                peer.replica_read(cb, ctx.read_ts)
            self._wait(box)
            return box["result"]
        # lease fast path (LocalReader): no proposal, no log barrier.
        # local_read serializes on the peer mutex; the extra node lock
        # covers the synchronous drive mode where pollers don't exist
        if self._lock is not None and not self.store.pooled():
            with self._lock:
                snap = peer.local_read()
        else:
            snap = peer.local_read()
        if snap is not None:
            self.lease_reads += 1
            return snap
        self.barrier_reads += 1
        box: dict = {}
        if self.store.pooled():
            if not self.store._route_peer_msg(
                    peer.region.id,
                    ("read", lambda r: box.__setitem__("result", r))):
                raise NotLeaderError(peer.region.id)    # mailbox gone
        else:
            peer.propose_read(lambda r: box.__setitem__("result", r))
        self._wait(box)
        return box["result"]

    def write(self, ctx: SnapContext, data: WriteData) -> None:
        key_hint = data.modifies[0][2] if data.modifies else b""
        peer = self._route(ctx, key_hint)
        ops = []
        for op, cf, key, value in data.modifies:
            if op == "put":
                ops.append(WriteOp("put", cf, key, value))
            else:
                ops.append(WriteOp("delete", cf, key))
        cmd = RaftCmd(peer.region.id, peer.region.epoch, tuple(ops))
        cb = _WriteCallback()
        t0_ns = time.perf_counter_ns()
        if self.store.pooled():
            # proposals ride the mailbox: the peer's poller serializes
            # them with ready handling (fsm/peer.rs PeerMsg::RaftCommand)
            if not self.store._route_peer_msg(peer.region.id,
                                              ("cmd", cmd, cb)):
                raise NotLeaderError(peer.region.id)    # mailbox gone
        else:
            peer.propose(cmd, cb)
        try:
            self._wait(cb.box)
        finally:
            self._note_write_wait(t0_ns, cb)
            if self._latency_inspector is not None:
                self._latency_inspector(
                    (time.perf_counter_ns() - t0_ns) / 1e9)

    @staticmethod
    def _note_write_wait(t0_ns: int, cb: _WriteCallback) -> None:
        """``raft_write_wait`` on the write RPC's tracker: the command
        handed over → its callback fired, and inside it the two stamps
        taken where they happened (a command that never got that far has
        none); then ``raft_wake_wait``, what the caller's wait still
        took after the callback (the driver's poll, the node lock, the
        GIL)."""
        if cb.t_applied is None:
            return
        now = time.perf_counter_ns()
        sp = tracker.add_phase("raft_write_wait", cb.t_applied - t0_ns,
                               cb.t_applied)
        if cb.t_proposed is not None:
            tracker.add_span("raft_propose_wait", t0_ns, cb.t_proposed, sp)
            tracker.add_span("raft_apply_wait", cb.t_proposed,
                             cb.t_applied, sp)
        tracker.add_phase("raft_wake_wait", now - cb.t_applied, now)

    def kv_engine(self):
        return self.store.engine

    # -- routing --

    def _route(self, ctx: SnapContext, key_hint: bytes = b""):
        if ctx.region_id:
            return self.store.region_peer(ctx.region_id)
        key = key_hint or ctx.key_hint
        if key:
            return self.store.peer_by_key(key)
        # single-region stores (tests / fresh clusters) route trivially
        peers = list(self.store.peers.values())
        leaders = [p for p in peers if p.is_leader()]
        if len(leaders) == 1:
            return leaders[0]
        if len(peers) == 1:
            return peers[0]
        raise NotLeaderError(0)
