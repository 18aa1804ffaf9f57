"""Clients: per-store RPC stub + the PD-routed transactional client.

Reference: the store stub mirrors what TiDB holds per TiKV
(src/server/service/kv.rs surface); ``TxnClient`` plays the client-go
role — PD region routing, 2-phase commit (primary first), lock
resolution on conflict — which the reference repo itself leaves to its
callers but its tests exercise via test fixtures.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Optional, Sequence

import grpc

from ..raftstore.metapb import Peer, Region
from ..utils.trace import client_phase
from . import wire
from .pd_server import RemotePdClient


# How long a cop task waits for a lock that is still alive before it is
# sent again: client-go's BoTxnLockFast as a TiDB session configures it
# (``tidb_backoff_lock_fast``: base 10 ms, cap 3 s, equal jitter).
LOCK_BACKOFF = {"base": 0.010, "cap": 3.0}


class StoreClient:
    """Raw method stub against one tikv-server."""

    def __init__(self, addr: str):
        self.addr = addr
        from .security import make_channel
        self._chan = make_channel(addr)
        # this store's ONE BatchCommands stream, opened at the first
        # command and again at the first after it died
        self._mux: Optional[BatchCommandsClient] = None
        self._mux_mu = threading.Lock()

    def call(self, method: str, req: dict, timeout: float = 10,
             resend: bool = False) -> dict:
        """One unary RPC.  A reply that carries a ``time_detail`` leaves
        with the RPC's path across the wire in it (:func:`_note_wire`):
        gRPC runs both serializers on the calling thread, so four
        stamps around them and the store's three make one timeline.
        A Coprocessor reply that is a chunk (the request's
        ``encode_type``) comes back with ``chunk`` decoded
        (``wire.dec_chunk``), inside ``client_decode``.  ``resend``: the
        call carries again a command whose stream died, and says so in
        its metadata (the store counts them).  A txn write's request
        leaves with ``clock_ns.sent``, this clock right before the pack:
        the store places its accept stamp behind it
        (``txn_wire_request``)."""
        t_call = time.perf_counter_ns()
        at = [0, 0, 0]      # sent, bytes_in, decoded
        stamps = method in wire.TXN_WRITE_METHODS

        def pack(obj):
            if stamps:
                obj = dict(obj, clock_ns={"sent": time.perf_counter_ns()})
            raw = wire.pack(obj)
            at[0] = time.perf_counter_ns()
            return raw

        def unpack(raw):
            at[1] = time.perf_counter_ns()
            obj = _unpack_reply(raw)
            at[2] = time.perf_counter_ns()
            return obj

        fn = self._chan.unary_unary(
            "/tikv.Tikv/" + method, request_serializer=pack,
            response_deserializer=unpack)
        resp = fn(req, timeout=timeout, metadata=(
            (wire.MUX_RESEND_KEY, "1"),) if resend else None)
        return _checked(resp, t_call, *at)

    def call_raw(self, method: str, raw: bytes,
                 timeout: float = 10) -> tuple:
        """``raw``, the bytes ``call`` would have sent, as one command on
        this store's BatchCommands stream → (the reply's bytes as
        ``call`` would have received them, ``sent`` ns, ``bytes_in`` ns:
        ``BatchCommandsClient.call_raw``).  ``MuxClosed`` where the
        stream died under the command; the next call opens another."""
        with self._mux_mu:
            mux = self._mux
            if mux is None or mux.closed:
                mux = self._mux = BatchCommandsClient(chan=self._chan)
        return mux.call_raw(method, raw, timeout)

    def call_mux(self, method: str, req: dict, timeout: float = 10) -> dict:
        """``call`` over the mux: the same bytes out, the same reply
        back, the same seven stamps to :func:`_note_wire`: ``sent`` is
        when the MESSAGE holding the command left its serializer,
        ``bytes_in`` when the message holding the reply entered its
        deserializer, ``decoded`` when the command's own reply was
        unpacked (so ``client_decode`` holds the wake of this thread
        too)."""
        t_call = time.perf_counter_ns()
        reply, sent, bytes_in = self.call_raw(method, wire.pack(req),
                                              timeout)
        resp = _unpack_reply(reply)
        return _checked(resp, t_call, sent, bytes_in,
                        time.perf_counter_ns())

    def close(self) -> None:
        with self._mux_mu:
            mux, self._mux = self._mux, None
        if mux is not None:
            mux.close()

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        return lambda req=None, **kw: self.call(name, req or kw)


def _unpack_reply(raw: bytes) -> dict:
    obj = wire.unpack(raw)
    if "chunk" in obj:
        # a chunk reply's buffers wrapped as arrays where they lie: no
        # value a cell (``wire.chunk_rows`` makes rows of them for a
        # caller who wants values)
        wire.dec_chunk(obj["chunk"])
    return obj


def _checked(resp: dict, call: int, sent: int, bytes_in: int,
             decoded: int) -> dict:
    """A decoded reply on its way to the caller: an error raised, a
    ``time_detail`` given the RPC's path across the wire."""
    if resp.get("error"):
        raise wire.RemoteError(resp["error"])
    td = resp.get("time_detail")
    if isinstance(td, dict):
        _note_wire(td, call, sent, bytes_in, decoded)
    return resp


def _note_wire(td: dict, call: int, sent: int, bytes_in: int,
               decoded: int) -> None:
    """Place the client's four ``perf_counter_ns`` stamps around the
    three the store put into the reply (``time_detail.clock_ns``:
    ``accept``, ``t0``, ``t1``).  Client and store on one machine read
    one clock (CLOCK_MONOTONIC); then ``phases_ms`` gains
    ``client_encode``, ``wire_request``, ``rpc_accept_wait`` (the
    store's own, per reply), ``wire_reply`` and ``client_decode``, which
    with ``total_rpc_wall_ms`` add up to ``decoded - call`` to the
    nanosecond, and ``clock_ns`` gains the client's stamps.  Where the
    seven are not in order (another host's clock, a store that sends
    none) nothing is guessed: the label ``wire_clock=unshared``."""
    ck = td.get("clock_ns")
    try:
        shared = sent <= ck["accept"] <= ck["t0"] <= ck["t1"] <= bytes_in
    except (TypeError, KeyError):
        shared = False
    if not shared:
        td.setdefault("labels", {})["wire_clock"] = "unshared"
        return
    phases = td.setdefault("phases_ms", {})
    client_phase("client_encode", sent - call, phases)
    client_phase("wire_request", ck["accept"] - sent, phases)
    client_phase("rpc_accept_wait", ck["t0"] - ck["accept"], phases)
    client_phase("wire_reply", bytes_in - ck["t1"], phases)
    client_phase("client_decode", decoded - bytes_in, phases)
    ck.update(call=call, sent=sent, bytes_in=bytes_in, decoded=decoded)


def _note_route(resp: dict, entry_ns: int) -> None:
    """``client_route``: the caller's entry → the entry of the
    ``StoreClient.call`` that brought ``resp`` (its ``clock_ns.call``):
    plan encode, region lookup, breaker, leader choice, and whatever
    was retried before.  Only beside the wire phases."""
    td = resp.get("time_detail")
    ck = td.get("clock_ns") if isinstance(td, dict) else None
    if isinstance(ck, dict) and "call" in ck:
        client_phase("client_route", ck["call"] - entry_ns,
                     td["phases_ms"])


class MuxClosed(RuntimeError):
    """The BatchCommands stream died before it answered the command."""


class _MuxCall:
    """One command in flight on a ``BatchCommandsClient``."""

    __slots__ = ("cmd", "ev", "sent", "bytes_in", "ent")

    def __init__(self, cmd: dict):
        self.cmd = cmd
        self.ev = threading.Event()
        self.sent = 0           # ns: its message left the serializer
        self.bytes_in = 0       # ns: its reply's message reached ours
        self.ent = None         # the response entry


def _pack_commands(batch: list) -> bytes:
    raw = wire.pack({"requests": [c.cmd for c in batch]})
    t = time.perf_counter_ns()
    for c in batch:
        c.sent = t
    return raw


def _unpack_responses(raw: bytes) -> tuple:
    return time.perf_counter_ns(), wire.unpack(raw)


class BatchCommandsClient:
    """Client side of the batch_commands mux (service/kv.rs:921 +
    service/batch.rs): ONE bidirectional stream carries every RPC,
    demultiplexed by request id — concurrent callers share the stream
    instead of a connection/HTTP2-stream each.  Two command forms
    (``wire.mux_command``): ``call`` sends a request decoded,
    ``call_raw`` the bytes the unary call would have sent."""

    def __init__(self, addr: Optional[str] = None, chan=None):
        import queue

        self.addr = addr
        if chan is None:
            from .security import make_channel
            chan = make_channel(addr)
        self._chan = chan
        self._q: "queue.Queue" = queue.Queue()
        self._pending: dict = {}
        self._mu = threading.Lock()
        self._next_id = 0
        self.closed = False
        fn = self._chan.stream_stream(
            "/tikv.Tikv/BatchCommands", request_serializer=_pack_commands,
            response_deserializer=_unpack_responses)
        self._responses = fn(self._outbound())
        self._recv = threading.Thread(target=self._recv_loop, daemon=True,
                                      name="mux-recv")
        self._recv.start()

    def _outbound(self):
        # whatever is queued: one message, many commands
        return wire.mux_batches(self._q, None)

    def _recv_loop(self):
        try:
            for bytes_in, msg in self._responses:
                for ent in msg.get("responses", ()):
                    with self._mu:
                        c = self._pending.pop(ent["request_id"], None)
                    if c is not None:
                        c.bytes_in, c.ent = bytes_in, ent
                        c.ev.set()
        except Exception:
            pass
        with self._mu:
            # stream died: later calls must fail fast, not park for
            # their full timeout against a reader that will never run
            self.closed = True
            pending, self._pending = self._pending, {}
        for c in pending.values():
            c.ev.set()          # wake waiters with no response
        self._q.put(None)       # and gRPC's thread out of _outbound

    def _roundtrip(self, method: str, req, timeout: float) -> _MuxCall:
        with self._mu:
            if self.closed:
                raise MuxClosed("mux closed")
            self._next_id += 1
            c = _MuxCall(wire.mux_command(self._next_id, method, req))
            self._pending[self._next_id] = c
        self._q.put(c)
        if not c.ev.wait(timeout):
            with self._mu:
                self._pending.pop(c.cmd["request_id"], None)
            raise TimeoutError(f"mux call {method} timed out")
        if c.ent is None:
            raise MuxClosed("mux stream closed")
        return c

    def call(self, method: str, req: dict, timeout: float = 10) -> dict:
        resp = self._roundtrip(method, req, timeout).ent["response"]
        if resp.get("error"):
            raise wire.RemoteError(resp["error"])
        return resp

    def call_raw(self, method: str, raw: bytes,
                 timeout: float = 10) -> tuple:
        """→ (the reply's bytes, ``sent`` ns, ``bytes_in`` ns): when the
        message holding the command left this end's serializer, and
        when the message holding the reply entered its deserializer."""
        c = self._roundtrip(method, raw, timeout)
        return c.ent["raw"], c.sent, c.bytes_in

    def close(self):
        with self._mu:
            self.closed = True
        self._q.put(None)


class TxnError(Exception):
    pass


class TxnClient:
    """Transactional client: PD routing + Percolator 2PC.

    Reads/writes route to the region leader by key; on KeyIsLocked the
    client resolves via CheckTxnStatus + ResolveLock (the reference's
    client-side lock resolution protocol).

    Tail tolerance (client-go shape):

    - every store's transport rides a per-store circuit breaker —
      consecutive transport failures trip it open, a half-open probe
      re-tests after the cooldown, and an open breaker fails sends fast
      instead of feeding a dead/hung store its full RPC timeout;
    - with ``hedge_reads=True``, idempotent point gets re-issue to a
      follower replica after an adaptive P95-based delay (resolved-ts
      stale read first, ReadIndex replica read as the fallback when the
      watermark lags); first response wins, the loser is abandoned.
    """

    # hedge delay bounds: never hedge inside normal jitter (floor) and
    # never wait out most of a deadline before hedging (ceiling)
    HEDGE_DELAY_MIN = 0.002
    HEDGE_DELAY_MAX = 0.5
    HEDGE_LAT_WINDOW = 128

    def __init__(self, pd_addr: str, hedge_reads: bool = False,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 1.0):
        self.pd = RemotePdClient(pd_addr)
        self._stores: dict[int, StoreClient] = {}
        # client-go RegionCache analog: region routing resolved from PD
        # once and reused until a NotLeader/EpochNotMatch invalidates it
        # — without it every mutation in a batch pays a PD RPC
        self._region_cache: dict[int, tuple[Region, Peer]] = {}
        # a fan-out's tasks re-route on threads of their own: one at a
        # time walks or drops the cached routes
        self._route_mu = threading.Lock()
        from ..utils.health import CircuitBreaker
        self.hedge_reads = hedge_reads
        self._breaker_cfg = (breaker_threshold, breaker_cooldown_s)
        self._breakers: dict[int, CircuitBreaker] = {}
        self._hedge_pool = None
        self._hedge_mu = threading.Lock()
        # the fan-out's workers, made at the first fan-out and kept (a
        # thread a task a read would be most of a small read's cost)
        self._fanout_pool = None
        # recent point-read latencies (seconds) → adaptive P95 delay
        self._read_lat: list[float] = []
        self.hedges_fired = 0
        self.hedges_won = 0

    # -- routing --

    def _store_client(self, store_id: int) -> StoreClient:
        c = self._stores.get(store_id)
        if c is None:
            c = StoreClient(self.pd.get_store(store_id).address)
            self._stores[store_id] = c
        return c

    # -- per-store circuit breaker (tail tolerance) --

    def _breaker(self, store_id: int):
        from ..utils.health import CircuitBreaker
        br = self._breakers.get(store_id)
        if br is None:
            thresh, cool = self._breaker_cfg
            br = self._breakers[store_id] = CircuitBreaker(
                threshold=thresh, cooldown_s=cool)
        return br

    def breaker_states(self) -> dict:
        return {sid: br.stats() for sid, br in self._breakers.items()}

    def _store_call(self, store_id: int, method: str, req: dict,
                    timeout: float = 10,
                    mux: Optional[list] = None) -> dict:
        """One RPC to one store through its circuit breaker.

        Only TRANSPORT failures (timeouts, channel errors) count
        against the breaker — a logical RemoteError proves the store
        answered and resets it.

        ``mux``: None for a unary call; a fan-out task's tally ``[n]``
        sends the request as a command on the store's BatchCommands
        stream.  A stream that died under the command is a transport
        failure of its own, and the request goes again ONCE, as a unary
        call inside what is left of ``timeout`` (``mux[0]`` counts
        them): a read does not fail for the transport's sake."""
        from ..utils.health import CircuitOpen
        br = self._breaker(store_id)
        if not br.allow():
            raise CircuitOpen(f"store {store_id}")
        sc = self._store_client(store_id)
        try:
            if mux is None:
                r = sc.call(method, req, timeout=timeout)
            else:
                t_end = time.monotonic() + timeout
                try:
                    r = sc.call_mux(method, req, timeout=timeout)
                except MuxClosed:
                    br.record_failure()
                    mux[0] += 1
                    r = sc.call(method, req, resend=True, timeout=max(
                        0.001, t_end - time.monotonic()))
        except wire.RemoteError:
            br.record_success()
            raise
        except Exception:
            br.record_failure()
            raise
        br.record_success()
        return r

    def _lookup_region(self, key: bytes) -> tuple[Region, Peer]:
        # region bounds live in the ENCODED keyspace (txn_types
        # encode_key) — comparing raw user keys against them routes to
        # the wrong region as soon as a split boundary sorts between
        # the raw and encoded forms
        from ..storage.txn_types import encode_key
        ek = encode_key(key)
        for region, leader in self._region_cache.values():
            if region.contains(ek):
                return region, leader
        # a split in flight leaves PD with a transient gap between the
        # shrunk parent's heartbeat and the new sibling's first one —
        # "no region" there is retryable, not fatal (client-go backs
        # off on region_not_found the same way)
        from ..utils.backoff import Backoff
        bo = Backoff(base=0.02, cap=0.2, deadline_s=3.0)
        while True:
            try:
                region, leader = self.pd.get_region_with_leader(ek)
                break
            except wire.RemoteError as e:
                if "no region" not in str(e) or not bo.sleep():
                    raise
        if leader is None:
            leader = region.peers[0]
        self._region_cache[region.id] = (region, leader)
        return region, leader

    def _invalidate_region(self, key: bytes) -> None:
        from ..storage.txn_types import encode_key
        ek = encode_key(key)
        for rid, (region, _leader) in list(self._region_cache.items()):
            if region.contains(ek):
                del self._region_cache[rid]

    def _leader_client(self, key: bytes) -> tuple[StoreClient, Region]:
        region, leader = self._lookup_region(key)
        return self._store_client(leader.store_id), region

    def _call_leader(self, key: bytes, method: str, req: dict,
                     retries: int = 8, timeout: float = 10,
                     deadline: Optional[float] = None) -> dict:
        """Retry NotLeader/EpochNotMatch with fresh routing (client-go
        region cache invalidation).

        Retries back off exponentially with jitter and the whole
        operation is budgeted by ``deadline`` (default: ``timeout``) —
        each RPC's timeout is clamped to the remaining budget, so a
        caller's patience propagates through every hop instead of
        multiplying by the attempt count."""
        from ..utils.backoff import Backoff
        from ..utils.failpoint import fail_point
        from ..utils.health import CircuitOpen
        bo = Backoff(base=0.02, cap=0.5,
                     deadline_s=deadline if deadline is not None
                     else timeout)
        last: Optional[Exception] = None
        for _ in range(retries):
            if last is not None and bo.remaining() < 0.05:
                # deadline (nearly) exhausted: surface the meaningful
                # routing error instead of firing a sliver-timeout RPC
                # whose bare TimeoutError would mask it
                break
            region, leader = self._lookup_region(key)
            try:
                return self._store_call(leader.store_id, method, req,
                                        timeout=bo.rpc_timeout(timeout))
            except CircuitOpen as e:
                # this store's breaker is open: back off and re-resolve
                # — leadership may have moved off the dead store
                last = e
                self._invalidate_region(key)
                if not bo.sleep():
                    break
                continue
            except wire.RemoteError as e:
                if e.kind == "server_is_busy":
                    # overloaded, not misrouted: honor the server's
                    # queue-depth-derived retry_after_ms over blind
                    # exponential jitter
                    last = e
                    hint = e.err.get("retry_after_ms")
                    fail_point("client::before_retry")
                    if not bo.sleep(hint_s=hint / 1000.0
                                    if hint else None):
                        break
                    continue
                if e.kind in ("not_leader", "epoch_not_match",
                              "region_not_found", "region_merging") or \
                        "KeyNotInRegion" in str(e):
                    # KeyNotInRegion: a server-initiated split (size or
                    # load checker) landed after we cached the bounds
                    last = e
                    self._invalidate_region(key)
                    fail_point("client::before_retry")
                    if not bo.sleep():
                        break       # deadline exhausted
                    continue
                raise
        raise last if last else TxnError("routing failed")

    # -- timestamps --

    def tso(self) -> int:
        return self.pd.tso()

    # -- simple point API --

    def get(self, key: bytes, version: Optional[int] = None,
            resolve: bool = True,
            deadline_ms: Optional[int] = None) -> Optional[bytes]:
        """Point read.  ``deadline_ms`` budgets the WHOLE operation:
        it rides the wire so the server sheds expired work, and the
        client's RPC timeout is clamped to it."""
        from ..utils.deadline import Deadline
        ts = version if version is not None else self.tso()
        req = {"key": key, "version": ts}
        dl = Deadline.after_ms(deadline_ms) \
            if deadline_ms is not None else None
        timeout = 10.0
        for _ in range(4):
            if dl is not None:
                # the budget covers the WHOLE get, lock-resolution
                # retries included — each attempt carries only what
                # remains, and an exhausted budget sheds client-side
                dl.check("client_retry")
                req["deadline_ms"] = dl.to_wire_ms()
                timeout = max(0.001, dl.remaining())
            try:
                t0 = time.monotonic()
                if self.hedge_reads:
                    r = self._hedged_get(key, dict(req), timeout, dl)
                else:
                    r = self._call_leader(key, "KvGet", req,
                                          timeout=timeout)
                self._note_read_latency(time.monotonic() - t0)
                return r.get("value")
            except wire.RemoteError as e:
                if resolve and e.kind == "key_is_locked":
                    self._resolve_lock(key, e.err["lock"], ts)
                    continue
                raise
        raise TxnError(f"unresolved lock on {key!r}")

    # -- hedged reads (tail tolerance) --

    def _note_read_latency(self, dt: float) -> None:
        lat = self._read_lat
        lat.append(dt)
        if len(lat) > self.HEDGE_LAT_WINDOW:
            del lat[:len(lat) - self.HEDGE_LAT_WINDOW]

    def hedge_delay(self) -> float:
        """Adaptive hedge trigger: the P95 of recent point reads — a
        read slower than 95% of its peers is likely stuck on a slow
        store, so a duplicate is cheap insurance."""
        lat = sorted(self._read_lat)
        if not lat:
            return 0.05
        p95 = lat[min(len(lat) - 1, int(len(lat) * 0.95))]
        return min(self.HEDGE_DELAY_MAX, max(self.HEDGE_DELAY_MIN, p95))

    def _hedged_get(self, key: bytes, req: dict, timeout: float,
                    dl=None) -> dict:
        """Leader read, hedged to a follower after the adaptive delay;
        first response wins, the loser is abandoned (its reply is
        discarded — gRPC unary calls cannot be recalled mid-flight).

        The hedge is only safe because a point get at a FIXED version
        is idempotent, and the follower path preserves linearizability:
        a resolved-ts stale read serves only when read_ts ≤ the
        follower's watermark, and the DataIsNotReady fallback is a
        ReadIndex replica read (consistent at the leader's commit
        point)."""
        import concurrent.futures as cf
        from ..utils.metrics import HEDGE_COUNTER
        region, leader = self._lookup_region(key)
        pool = self._hedge_executor()
        f_leader = pool.submit(self._call_leader, key, "KvGet",
                               req, 8, timeout)
        try:
            r = f_leader.result(timeout=self.hedge_delay())
            HEDGE_COUNTER.labels("leader_fast").inc()
            return r
        except cf.TimeoutError:
            pass
        except wire.RemoteError as e:
            if e.kind == "key_is_locked":
                raise   # the follower would serve the same lock —
                # resolution, not hedging, unblocks this read
            # leader shed/failed FAST (busy, deadline, breaker): the
            # follower leg below is the recovery path, not a duplicate
        followers = [p for p in region.peers
                     if (leader is None or p.store_id != leader.store_id)
                     and not p.is_learner]
        if not followers:
            return f_leader.result(timeout=timeout + 1)
        self.hedges_fired += 1
        HEDGE_COUNTER.labels("fired").inc()
        target = followers[self.hedges_fired % len(followers)]
        freq = dict(req)
        if dl is not None:
            # the follower leg carries the REMAINING budget, not the
            # original one — the hedge delay already spent part of it
            freq["deadline_ms"] = dl.to_wire_ms()
        f_follow = pool.submit(self._follower_get, target.store_id,
                               freq, timeout)
        done, _ = cf.wait({f_leader, f_follow},
                          timeout=timeout + 1,
                          return_when=cf.FIRST_COMPLETED)
        # prefer whichever finished FIRST with a usable answer; an
        # error from the early finisher falls through to (and blocks
        # on) the still-running leg
        order = sorted([f_leader, f_follow],
                       key=lambda f: (f not in done, f is f_follow))
        for fut in order:
            try:
                r = fut.result(timeout=timeout + 1)
                if fut is f_follow:
                    self.hedges_won += 1
                    HEDGE_COUNTER.labels("follower_won").inc()
                else:
                    HEDGE_COUNTER.labels("leader_won").inc()
                return r
            except Exception:   # noqa: BLE001 — try the other leg
                continue
        # both legs failed: surface the leader's error (the follower
        # error is usually the less meaningful DataIsNotReady)
        return f_leader.result(timeout=timeout + 1)

    def _follower_get(self, store_id: int, req: dict,
                      timeout: float) -> dict:
        """The hedge's follower leg: resolved-ts stale read first (no
        leader involvement at all), ReadIndex replica read when the
        follower's watermark hasn't reached read_ts yet."""
        stale = dict(req)
        stale["stale_read"] = True
        try:
            return self._store_call(store_id, "KvGet", stale,
                                    timeout=timeout)
        except wire.RemoteError as e:
            if e.kind != "data_is_not_ready":
                raise
        replica = dict(req)
        replica["replica_read"] = True
        return self._store_call(store_id, "KvGet", replica,
                                timeout=timeout)

    def _hedge_executor(self):
        import concurrent.futures as cf
        with self._hedge_mu:
            if self._hedge_pool is None:
                self._hedge_pool = cf.ThreadPoolExecutor(
                    max_workers=8, thread_name_prefix="hedge")
            return self._hedge_pool

    def close(self) -> None:
        """Release the hedge and fan-out executors' threads (tests /
        short-lived clients)."""
        with self._hedge_mu:
            if self._hedge_pool is not None:
                self._hedge_pool.shutdown(wait=False)
                self._hedge_pool = None
            if self._fanout_pool is not None:
                self._fanout_pool[1].shutdown(wait=False)
                self._fanout_pool = None
        for sc in list(self._stores.values()):
            sc.close()      # its BatchCommands stream, where one opened

    def replica_get(self, key: bytes,
                    version: Optional[int] = None,
                    stale: bool = False) -> Optional[bytes]:
        """Read from a FOLLOWER replica — consistent at the leader's
        commit point via ReadIndex (replica_read), or, with
        ``stale=True``, served locally under the resolved-ts watermark
        (raises data_is_not_ready when the watermark lags read_ts)."""
        ts = version if version is not None else self.tso()
        region, leader = self._lookup_region(key)
        followers = [p for p in region.peers
                     if leader is None or p.store_id != leader.store_id]
        target = followers[0] if followers else leader
        req = {"key": key, "version": ts}
        req["stale_read" if stale else "replica_read"] = True
        r = self._store_call(target.store_id, "KvGet", req)
        return r.get("value")

    def put(self, key: bytes, value: bytes) -> None:
        self.txn_write([("put", key, value)])

    def delete(self, key: bytes) -> None:
        self.txn_write([("delete", key, None)])

    def scan(self, start: bytes, end: Optional[bytes], limit: int,
             version: Optional[int] = None) -> list:
        ts = version if version is not None else self.tso()
        r = self._call_leader(start, "KvScan", {
            "start_key": start, "end_key": end, "limit": limit,
            "version": ts})
        return [(p["key"], p["value"]) for p in r["pairs"]]

    # -- 2PC --

    def txn_write(self, mutations: Sequence[tuple]) -> int:
        """mutations: [(op, key, value|None)].  Full 2PC: prewrite all
        keys (primary first group), then commit primary, then commit
        secondaries.  Returns commit_ts."""
        assert mutations
        from ..utils.backoff import Backoff
        start_ts = self.tso()
        primary = mutations[0][1]
        # prewrite, grouped one RPC per region leader; a stale cached
        # route (split/leader change mid-flight) re-groups and retries
        # under a jittered backoff with a whole-2PC deadline —
        # re-prewriting an already-locked key with the same start_ts is
        # idempotent (mvcc/actions prewrite lock-match rule)
        bo = Backoff(base=0.02, cap=0.5, deadline_s=20.0)
        for attempt in range(8):
            groups: dict[tuple, list] = {}
            for op, key, value in mutations:
                client, region = self._leader_client(key)
                groups.setdefault((client.addr, region.id), []).append(
                    (client, op, key, value))
            try:
                for muts in groups.values():
                    self._retryable_prewrite(muts[0][0], muts, primary,
                                             start_ts)
                break
            except wire.RemoteError as e:
                if e.kind in ("not_leader", "epoch_not_match",
                              "region_not_found",
                              "region_merging") and attempt < 7:
                    for _op, key, _v in mutations:
                        self._invalidate_region(key)
                    if not bo.sleep():
                        raise
                    continue
                raise
        # commit primary first — the txn's durability point
        commit_ts = self.tso()
        self._call_leader(primary, "KvCommit", {
            "keys": [primary], "start_version": start_ts,
            "commit_version": commit_ts})
        # then secondaries (safe to retry/resolve after the primary
        # commit), batched one KvCommit per region leader — the
        # reference's client-go commits per-region, not per-key
        by_leader: dict[tuple, tuple] = {}
        for op, key, _v in mutations:
            if key == primary:
                continue
            client, region = self._leader_client(key)
            by_leader.setdefault((client.addr, region.id),
                                 (client, []))[1].append(key)
        for client, keys in by_leader.values():
            try:
                client.call("KvCommit", {
                    "keys": keys, "start_version": start_ts,
                    "commit_version": commit_ts})
            except wire.RemoteError as e:
                if e.kind not in ("not_leader", "epoch_not_match",
                                  "region_not_found", "region_merging"):
                    raise
                # stale group route: fall back to per-key re-routing
                for key in keys:
                    self._invalidate_region(key)
                    self._call_leader(key, "KvCommit", {
                        "keys": [key], "start_version": start_ts,
                        "commit_version": commit_ts})
        return commit_ts

    def _retryable_prewrite(self, client, muts, primary, start_ts,
                            retries: int = 4) -> None:
        req = {"mutations": [{"op": op, "key": k, "value": v}
                             for _c, op, k, v in muts],
               "primary": primary, "start_version": start_ts}
        for _ in range(retries):
            try:
                client.call("KvPrewrite", req)
                return
            except wire.RemoteError as e:
                if e.kind == "key_is_locked":
                    self._resolve_lock(e.err["key"], e.err["lock"],
                                       start_ts)
                    continue
                raise
        raise TxnError("prewrite kept hitting locks")

    # -- lock resolution (client-go resolver protocol) --

    def _resolve_lock(self, key: bytes, lock: dict, caller_ts: int) -> str:
        """→ what the lock's transaction turned out to be: ``committed``
        / ``rolled_back`` / ``ttl_expired`` (the lock on ``key`` is
        resolved to match) or ``locked`` (still alive: back off)."""
        primary = lock["primary"]
        status = self._call_leader(primary, "KvCheckTxnStatus", {
            "primary_key": primary, "lock_ts": lock["start_ts"],
            "caller_start_ts": caller_ts, "current_ts": self.tso()})
        st = status["status"]
        # (``key_hint``: the store resolves the transaction's locks in
        # the region that holds the key, all in one round; without it a
        # store of several regions cannot tell where to look)
        if st == "committed":
            self._call_leader(key, "KvResolveLock", {
                "start_version": lock["start_ts"],
                "commit_version": status["ts"], "key_hint": key})
        elif st in ("rolled_back", "ttl_expired"):
            self._call_leader(key, "KvResolveLock", {
                "start_version": lock["start_ts"], "commit_version": 0,
                "key_hint": key})
        # "locked": still alive — caller retries / backs off
        return st

    # -- coprocessor --

    def coprocessor(self, dag, key_hint: Optional[bytes] = None,
                    force_backend: Optional[str] = None,
                    paging_size: int = 0, resume_token=None,
                    resource_group: str = "default",
                    request_source: str = "",
                    timeout: float = 10,
                    deadline_ms: Optional[int] = None,
                    trace_id: Optional[str] = None) -> dict:
        t_entry = time.perf_counter_ns()
        key = key_hint if key_hint is not None else \
            (dag.ranges[0].start if dag.ranges else b"")
        req = {
            "tp": 103, "dag": wire.enc_dag(dag),
            "force_backend": force_backend,
            "paging_size": paging_size, "resume_token": resume_token,
            "resource_group": resource_group,
            "request_source": request_source}
        if trace_id is not None:
            # client-propagated causal trace id (the server mints one
            # otherwise); sending it forces span sampling and the
            # response echoes it next to time_detail
            req["trace_id"] = trace_id
        if deadline_ms is not None:
            # the endpoint checks this budget at admission, between
            # executor batches, and before the device dispatch
            req["deadline_ms"] = deadline_ms
            timeout = min(timeout, deadline_ms / 1000.0)
        if self.hedge_reads and not paging_size and resume_token is None:
            # a snapshot read at a fixed start_ts is idempotent, so
            # the adaptive-P95 hedge applies — and the second leg is
            # now a WARM one: a follower replica answering from its
            # own device feed (paged requests carry resume state and
            # stay leader-only)
            resp = self._hedged_coprocessor(key, req, timeout)
        else:
            resp = self._call_leader(key, "Coprocessor", req,
                                     timeout=timeout)
        _note_route(resp, t_entry)
        return resp

    def _hedged_coprocessor(self, key: bytes, req: dict,
                            timeout: float) -> dict:
        """Leader coprocessor read, hedged to a follower REPLICA FEED
        after the adaptive delay (the `_hedged_get` machinery at the
        coprocessor layer).  The second leg used to be a cold host
        read on the leader's sibling; with replicated device serving
        it is a ``stale_read`` coprocessor call the follower answers
        from its own delta-patched columnar line — warm device work,
        not a cold rebuild.  A follower whose resolved-ts watermark
        lags the request's start_ts refuses with DataIsNotReady and
        the hedge falls through to the leader leg; per-store circuit
        breakers gate both legs unchanged."""
        import concurrent.futures as cf
        from ..utils.metrics import HEDGE_COUNTER
        region, leader = self._lookup_region(key)
        pool = self._hedge_executor()
        f_leader = pool.submit(self._call_leader, key, "Coprocessor",
                               req, 8, timeout)
        try:
            r = f_leader.result(timeout=self.hedge_delay())
            HEDGE_COUNTER.labels("copr_leader_fast").inc()
            return r
        except cf.TimeoutError:
            pass
        except wire.RemoteError as e:
            if e.kind == "key_is_locked":
                raise   # resolution, not hedging, unblocks this read
        followers = [p for p in region.peers
                     if (leader is None or p.store_id != leader.store_id)
                     and not p.is_learner]
        if not followers:
            return f_leader.result(timeout=timeout + 1)
        self.hedges_fired += 1
        HEDGE_COUNTER.labels("copr_fired").inc()
        target = followers[self.hedges_fired % len(followers)]
        stale = dict(req)
        stale["stale_read"] = True
        f_follow = pool.submit(self._store_call, target.store_id,
                               "Coprocessor", stale, timeout)
        done, _ = cf.wait({f_leader, f_follow}, timeout=timeout + 1,
                          return_when=cf.FIRST_COMPLETED)
        order = sorted([f_leader, f_follow],
                       key=lambda f: (f not in done, f is f_follow))
        for fut in order:
            try:
                r = fut.result(timeout=timeout + 1)
                if fut is f_follow:
                    self.hedges_won += 1
                    HEDGE_COUNTER.labels("copr_follower_won").inc()
                else:
                    HEDGE_COUNTER.labels("copr_leader_won").inc()
                return r
            except wire.RemoteError as e:
                if fut is f_follow and e.kind == "data_is_not_ready":
                    # lagging replica refused (resolved-ts gate): the
                    # leader leg is the consistent fallback
                    HEDGE_COUNTER.labels("copr_stale_refused").inc()
                continue
            except Exception:   # noqa: BLE001 — try the other leg
                continue
        return f_leader.result(timeout=timeout + 1)

    # -- coprocessor fan-out (client-go CopClient / copIterator) --

    # replies that say the task went to the wrong place or was cut for
    # a region that has changed: drop the route, cut again, send again
    _REROUTE_KINDS = ("not_leader", "epoch_not_match", "region_not_found",
                      "region_merging")

    def coprocessor_fanout(self, dag, concurrency: int = 15,
                           resource_group: str = "default",
                           request_source: str = "",
                           timeout: float = 60) -> dict:
        """``dag`` over every region its ranges touch, as TiDB's copr
        client reads a table: one cop task a region with the ranges
        clipped to the region's bounds, at most ``concurrency`` in
        flight (``tidb_distsql_scan_concurrency``'s default), each sent
        to its region's leader under the region and epoch it was cut
        for.  A task whose region changed under it (``epoch_not_match``,
        ``region_not_found``, ``not_leader``) has ITS ranges cut again
        and re-sent inside ``timeout``.  Where a store gets more than
        one task of the read, its tasks travel as commands on that
        ``StoreClient``'s ONE BatchCommands stream (``call_mux``: the
        same bytes, the same reply, the same wire phases); a lone task
        is a unary call, as ``coprocessor()``'s request is.  A task
        that meets a lock of a transaction started at or before the
        read's TSO (``key_is_locked``) asks the lock's primary for the
        transaction's status, resolves the lock where that is decided,
        waits under ``LOCK_BACKOFF`` where it is still alive, and is
        sent again, as client-go's copIterator does: inside the read's
        clock and ``timeout``.  Anything else is the caller's, as from
        ``coprocessor``.

        → one summary shaped like a single reply, with the partial
        replies under ``responses`` in range order (merging partial
        aggregates is the SQL layer's) and their count under ``tasks``:
        ``backend`` is ``"device"`` only if every task's was;
        ``time_detail.labels`` is the union of the tasks' (plus
        ``cop_tasks``, ``fanout_retries`` where a task was cut again,
        ``lock_retries`` where one was sent again after a lock, and
        ``unary_resends`` where a task's stream died under it);
        ``phases_ms`` / ``total_rpc_wall_ms`` / ``trace_id`` are those
        of the task that returned last, the read's critical path
        (each task has a server-minted trace id of its own: the store's
        trace buffer keeps one tracker an id), with any task's
        ``host_exec`` and the client's own phases added: ``fanout_cut``,
        ``fanout_tasks``, ``fanout_straggler``, ``fanout_task`` and,
        where a task waited for a lock, ``fanout_lock_wait``
        (utils/trace_vocab.py).  The critical task's path across the
        wire rides along (``StoreClient.call``: ``client_encode``,
        ``wire_request``, ``rpc_accept_wait``, ``wire_reply``,
        ``client_decode``, and its ``clock_ns``); its ``client_route``
        runs from THIS call's entry to the task's send, so that the
        seven, with ``total_rpc_wall_ms``, cover the read up to the
        critical reply's decode."""
        import dataclasses
        import statistics
        from ..utils import tracker
        t_entry = time.perf_counter_ns()
        # everything of the request but a task's ranges and region,
        # encoded once (wire.enc_dag's keys, in coprocessor()'s order)
        env = {"tp": 103,
               "dag": wire.enc_dag(dataclasses.replace(dag, ranges=())),
               "force_backend": None,
               "paging_size": 0, "resume_token": None,
               "resource_group": resource_group,
               "request_source": request_source}
        tr, tok = tracker.install(sampled=False)
        try:
            with tracker.phase("fanout_cut"):
                tasks = self._cut_by_region(dag.ranges)
            pool = self._fanout_executor(concurrency)
            # tasks of a store that gets more than one travel as
            # commands on that store's ONE stream; a lone task has
            # nothing to batch with and stays a unary call
            to_store = collections.Counter(
                leader.store_id for _region, leader, _ranges in tasks)
            futs = [pool.submit(self._run_cop_task, task, env, timeout,
                                t_entry, to_store[task[1].store_id] > 1,
                                dag.start_ts)
                    for task in tasks]
            try:
                ran = [f.result() for f in futs]
            except BaseException:
                for f in futs:
                    f.cancel()
                raise
            parts = [part for got, *_counts in ran for part in got]
            if not parts:
                raise TxnError("coprocessor fan-out over no ranges")
            back = [b for _r, _s, b in parts]
            crit = parts[back.index(max(back))][0]
            tracker.add_phase("fanout_tasks",
                              max(back) - min(s for _r, s, _b in parts))
            tracker.add_phase("fanout_straggler",
                              max(back) - statistics.median(back))
            tracker.add_phase("fanout_task", statistics.median(
                b - s for _r, s, b in parts))
            lock_wait = max(w for _got, _n, _m, _l, w in ran)
            if lock_wait:
                # the longest any one task of the read spent on locks:
                # status checks, resolves and backoff sleeps
                tracker.add_phase("fanout_lock_wait", lock_wait)
        finally:
            tracker.uninstall(tok)
        replies = [r for r, _s, _b in parts]
        detail = dict(crit.get("time_detail", {}))
        phases = dict(detail.get("phases_ms", {}))
        labels: dict = {}
        backends = set()
        for r in replies:
            td = r.get("time_detail", {})
            labels.update(td.get("labels", {}))
            if "host_exec" in td.get("phases_ms", {}):
                phases.setdefault("host_exec", td["phases_ms"]["host_exec"])
            backends.add(r.get("backend"))
        phases.update(tr.time_detail()["phases_ms"])
        labels["cop_tasks"] = str(len(replies))
        for label, i in (("fanout_retries", 1), ("unary_resends", 2),
                         ("lock_retries", 3)):
            n = sum(counts[i] for counts in ran)
            if n:
                labels[label] = str(n)
        detail["phases_ms"], detail["labels"] = phases, labels
        return {"responses": replies, "tasks": len(replies),
                "backend": "device" if backends == {"device"}
                else sorted(str(b) for b in backends - {"device"})[0],
                "time_detail": detail, "trace_id": crit.get("trace_id")}

    def _fanout_executor(self, concurrency: int):
        """This client's fan-out workers: ``concurrency`` of them, so
        that many of its cop tasks are in flight at most (callers that
        share a client share the bound).  Asked for another width, the
        pool is made anew; tasks the old one holds still finish."""
        import concurrent.futures as cf
        with self._hedge_mu:
            if self._fanout_pool is None or \
                    self._fanout_pool[0] != concurrency:
                if self._fanout_pool is not None:
                    self._fanout_pool[1].shutdown(wait=False)
                self._fanout_pool = (concurrency, cf.ThreadPoolExecutor(
                    max_workers=max(1, concurrency),
                    thread_name_prefix="copr-fanout"))
            return self._fanout_pool[1]

    def _cut_by_region(self, ranges) -> list:
        """``ranges`` cut at the bounds of the regions they touch, found
        through the region cache → [(region, leader, (KeyRange, ...))]
        in range order; neighbouring pieces of one region share a
        task."""
        from ..executors.ranges import KeyRange
        from ..storage.txn_types import decode_key
        tasks: list = []
        with self._route_mu:
            for r in ranges:
                start = r.start
                while start < r.end:
                    region, leader = self._lookup_region(start)
                    # region bounds are engine keys (_lookup_region)
                    bound = decode_key(region.end_key) \
                        if region.end_key else None
                    end = r.end if bound is None else min(r.end, bound)
                    if end <= start:
                        raise TxnError(f"region {region.id} does not "
                                       f"hold {start!r}")
                    if tasks and tasks[-1][0].id == region.id:
                        tasks[-1][2].append(KeyRange(start, end))
                    else:
                        # workers only read these two tables
                        self._store_client(leader.store_id)
                        self._breaker(leader.store_id)
                        tasks.append((region, leader,
                                      [KeyRange(start, end)]))
                    start = end
        return [(region, leader, tuple(pieces))
                for region, leader, pieces in tasks]

    def _run_cop_task(self, task, env: dict, timeout: float,
                      t_entry: int, mux: bool = False,
                      start_ts: int = 0) -> tuple:
        """One cop task to its region's leader → ([(reply, sent_ns,
        back_ns)], times it was cut again, times it was re-sent as a
        unary call, times it was sent again after a lock, ns it spent on
        locks): more than one reply where the region had changed under
        the task.  ``start_ts``: the read's TSO, which a lock's status
        check carries as its caller.  ``t_entry``: when the fan-out was entered,
        where each reply's ``client_route`` starts.  ``mux``: the task
        (and what it is cut into again) goes as a command on its store's
        BatchCommands stream (``_store_call``)."""
        from ..utils.backoff import Backoff
        from ..utils.failpoint import fail_point
        from ..utils.health import CircuitOpen
        bo = Backoff(base=0.02, cap=0.5, deadline_s=timeout)
        lock_bo = None
        todo, out, recuts, locked, lock_ns = [task], [], 0, 0, 0
        resent = [0] if mux else None
        while todo:
            region, leader, ranges = todo.pop(0)
            req = dict(env, dag=dict(env["dag"],
                                     ranges=wire.enc_ranges(ranges)),
                       context=wire.enc_region_ctx(region))
            sent = time.perf_counter_ns()
            try:
                resp = self._store_call(leader.store_id, "Coprocessor",
                                        req, timeout=bo.rpc_timeout(timeout),
                                        mux=resent)
            except wire.RemoteError as e:
                if e.kind == "server_is_busy":
                    # overloaded, not misrouted (_call_leader)
                    hint = e.err.get("retry_after_ms")
                    if not bo.sleep(hint_s=hint / 1000.0 if hint else None):
                        raise
                    todo.insert(0, (region, leader, ranges))
                    continue
                if e.kind == "key_is_locked":
                    # a transaction that started at or before this read
                    # holds the key: its outcome decides what the read
                    # sees, so find it out, then send the task again
                    t_lock = time.perf_counter_ns()
                    if lock_bo is None:
                        lock_bo = Backoff(deadline_s=bo.remaining(),
                                          **LOCK_BACKOFF)
                    alive = self._resolve_lock(
                        e.err["key"], e.err["lock"], start_ts) == "locked"
                    if lock_bo.expired() or \
                            (alive and not lock_bo.sleep()):
                        raise
                    locked += 1
                    lock_ns += time.perf_counter_ns() - t_lock
                    todo.insert(0, (region, leader, ranges))
                    continue
                if e.kind not in self._REROUTE_KINDS and \
                        "KeyNotInRegion" not in str(e):
                    raise
                last = e
            except CircuitOpen as e:
                last = e
            else:
                out.append((resp, sent, time.perf_counter_ns()))
                _note_route(resp, t_entry)
                continue
            recuts += 1
            with self._route_mu:
                for r in ranges:
                    self._invalidate_region(r.start)
            fail_point("client::before_retry")
            if not bo.sleep():
                raise last
            todo[:0] = self._cut_by_region(ranges)
        return out, recuts, resent[0] if mux else 0, locked, lock_ns

    def coprocessor_replica(self, dag, key_hint: Optional[bytes] = None,
                            resource_group: str = "default",
                            request_source: str = "",
                            timeout: float = 10) -> dict:
        """Direct follower device read (``stale_read`` coprocessor):
        served from the follower's own columnar line under the
        resolved-ts watermark.  Raises ``data_is_not_ready`` when the
        watermark lags the snapshot ts — callers wanting the fallback
        use the hedged path (``hedge_reads=True``)."""
        key = key_hint if key_hint is not None else \
            (dag.ranges[0].start if dag.ranges else b"")
        region, leader = self._lookup_region(key)
        followers = [p for p in region.peers
                     if (leader is None or p.store_id != leader.store_id)
                     and not p.is_learner]
        target = followers[0] if followers else leader
        req = {"tp": 103, "dag": wire.enc_dag(dag),
               "force_backend": None, "paging_size": 0,
               "resume_token": None, "resource_group": resource_group,
               "request_source": request_source, "stale_read": True}
        return self._store_call(target.store_id, "Coprocessor", req,
                                timeout=timeout)

    def coprocessor_plan(self, preq, key_hint: Optional[bytes] = None,
                         force_backend: Optional[str] = None,
                         resource_group: str = "default",
                         timeout: float = 30,
                         deadline_ms: Optional[int] = None,
                         trace_id: Optional[str] = None) -> dict:
        """Plan-IR coprocessor request (copr/plan_ir.py): the operator
        superset — join/sort/window fragments with per-operator
        host/device routing.  Routes by the FIRST scan leaf's first
        range; a join's two regions are expected co-located on one
        node (the SlicePlacer co-location loop), which the single-node
        and placement deployments guarantee."""
        leaves = preq.scan_leaves()
        key = key_hint if key_hint is not None else \
            (leaves[0].ranges[0].start
             if leaves and leaves[0].ranges else b"")
        req = {"tp": 103, "plan": wire.enc_plan(preq),
               "force_backend": force_backend,
               "resource_group": resource_group}
        if trace_id is not None:
            req["trace_id"] = trace_id
        if deadline_ms is not None:
            req["deadline_ms"] = deadline_ms
            timeout = min(timeout, deadline_ms / 1000.0)
        return self._call_leader(key, "Coprocessor", req,
                                 timeout=timeout)

    def coprocessor_paged(self, dag, paging_size: int,
                          key_hint: Optional[bytes] = None):
        """Iterate the unary paged protocol: yields one response dict
        per page until the server reports is_drained."""
        token = None
        while True:
            r = self.coprocessor(dag, key_hint=key_hint,
                                 paging_size=paging_size,
                                 resume_token=token)
            yield r
            if r.get("is_drained", True):
                return
            token = r["resume_token"]

    def analyze(self, dag, buckets: int = 64,
                key_hint: Optional[bytes] = None) -> dict:
        """ANALYZE (tp=104): per-column histogram/distinct/null stats."""
        key = key_hint if key_hint is not None else \
            (dag.ranges[0].start if dag.ranges else b"")
        return self._call_leader(key, "Coprocessor", {
            "tp": 104, "dag": wire.enc_dag(dag), "buckets": buckets})

    def checksum(self, dag, key_hint: Optional[bytes] = None) -> dict:
        """CHECKSUM (tp=105): crc64 over the range's logical rows."""
        key = key_hint if key_hint is not None else \
            (dag.ranges[0].start if dag.ranges else b"")
        return self._call_leader(key, "Coprocessor", {
            "tp": 105, "dag": wire.enc_dag(dag)})

    # -- CDC / backup (§2.6 services) --

    def cdc_stream(self, region_id: int, checkpoint_ts: int = 0,
                   key_hint: bytes = b""):
        """Subscribe to a region's change feed (cdcpb EventFeed analog):
        yields {"events": [...], "resolved_ts": ts} messages."""
        client, _region = self._leader_client(key_hint)
        fn = client._chan.unary_stream(
            "/tikv.Tikv/Cdc", request_serializer=wire.pack,
            response_deserializer=wire.unpack)
        for msg in fn({"region_id": region_id,
                       "checkpoint_ts": checkpoint_ts}, timeout=300):
            if msg.get("error"):
                raise wire.RemoteError(msg["error"])
            yield msg

    def backup(self, storage_url: str, backup_ts: int = 0,
               key_hint: bytes = b"") -> list:
        """Back up every leader region on the routed store; returns the
        per-region file metadata list (backuppb BackupResponse)."""
        client, _region = self._leader_client(key_hint)
        fn = client._chan.unary_stream(
            "/tikv.Tikv/Backup", request_serializer=wire.pack,
            response_deserializer=wire.unpack)
        out = []
        for msg in fn({"storage": storage_url,
                       "backup_ts": backup_ts}, timeout=300):
            if msg.get("error"):
                raise wire.RemoteError(msg["error"])
            out.append(msg)
        return out

    def restore(self, storage_url: str, names=None) -> int:
        """Restore backup files through the transactional write path
        (sst_importer download+ingest collapsed onto 2PC)."""
        from ..backup import create_storage, read_backup_file, \
            restore_rows
        storage = create_storage(storage_url)
        total = 0
        for name in (names if names is not None else storage.list()):
            if not name.endswith(".bak"):
                continue
            parsed = read_backup_file(storage_url, name)
            total += restore_rows(self, parsed["rows"])
        return total

    def coprocessor_stream(self, dag, paging_size: int = 0,
                           key_hint: Optional[bytes] = None):
        """Server-streamed pages over ONE snapshot (coprocessor_stream).
        Yields response dicts."""
        key = key_hint if key_hint is not None else \
            (dag.ranges[0].start if dag.ranges else b"")
        client, _region = self._leader_client(key)
        fn = client._chan.unary_stream(
            "/tikv.Tikv/CoprocessorStream", request_serializer=wire.pack,
            response_deserializer=wire.unpack)
        for msg in fn({"tp": 103, "dag": wire.enc_dag(dag),
                       "paging_size": paging_size}, timeout=60):
            if msg.get("error"):
                raise wire.RemoteError(msg["error"])
            yield msg

    # -- raw --

    def raw_put(self, key: bytes, value: bytes) -> None:
        self._call_leader(key, "RawPut", {"key": key, "value": value})

    def raw_get(self, key: bytes) -> Optional[bytes]:
        return self._call_leader(key, "RawGet", {"key": key}).get("value")

    # -- admin (ctl surface) --

    def split(self, split_key: bytes) -> Region:
        r = self._call_leader(split_key, "SplitRegion",
                              {"split_key": split_key})
        # the parent region's cached bounds are stale the moment the
        # split lands — drop them so the next lookup re-resolves
        self._invalidate_region(split_key)
        return wire.dec_region(r["right"])

    def add_peer(self, region_id: int, store_id: int) -> Peer:
        region = self.pd.get_region_by_id(region_id)
        peer = Peer(self.pd.alloc_id(), store_id)
        self._call_leader_by_region(region, "ChangePeer", {
            "region_id": region_id, "change_type": "add",
            "peer": wire.enc_peer(peer)})
        return peer

    def remove_peer(self, region_id: int, peer: Peer) -> None:
        region = self.pd.get_region_by_id(region_id)
        self._call_leader_by_region(region, "ChangePeer", {
            "region_id": region_id, "change_type": "remove",
            "peer": wire.enc_peer(peer)})

    def change_peers_joint(self, region_id: int, changes) -> None:
        """Atomic multi-peer change (joint consensus): ``changes`` =
        [("add"|"add_learner"|"remove", Peer)]."""
        region = self.pd.get_region_by_id(region_id)
        self._call_leader_by_region(region, "ChangePeerV2", {
            "region_id": region_id,
            "changes": [{"type": t, "peer": wire.enc_peer(p)}
                        for t, p in changes]})
        self._region_cache.clear()

    def merge(self, source_id: int, target_id: int) -> Region:
        """Merge the source region into its adjacent target."""
        region = self.pd.get_region_by_id(source_id)
        self._region_cache.clear()      # boundaries are about to change
        r = self._call_leader_by_region(region, "MergeRegion", {
            "source_id": source_id, "target_id": target_id})
        return wire.dec_region(r["region"])

    def _call_leader_by_region(self, region: Region, method: str,
                               req: dict, retries: int = 8,
                               deadline: float = 30.0) -> dict:
        from ..utils.backoff import Backoff
        bo = Backoff(base=0.02, cap=0.5, deadline_s=deadline)
        last = None
        for _ in range(retries):
            if last is not None and bo.remaining() < 0.05:
                break       # surface `last` over a sliver-timeout RPC
            _r = self.pd.get_region_by_id(region.id) or region
            reg, leader = self.pd.get_region_with_leader(_r.start_key)
            if reg.id != region.id or leader is None:
                leader = _r.peers[0]
            client = self._store_client(leader.store_id)
            try:
                return client.call(method, req,
                                   timeout=bo.rpc_timeout(10))
            except wire.RemoteError as e:
                if e.kind in ("not_leader", "epoch_not_match",
                              "region_merging"):
                    last = e
                    if not bo.sleep():
                        break
                    continue
                raise
        raise last if last else TxnError("routing failed")

    def status(self, store_id: int) -> dict:
        return self._store_client(store_id).call("Status", {})

    def ingest_sst(self, sst_blob: bytes, region_key: bytes,
                   chunk: int = 256 * 1024,
                   timeout: float = 120) -> int:
        """Bulk load one built SST onto the region owning ``region_key``
        (upload chunks → ingest; src/import/sst_service.rs flow).
        ``timeout`` covers the ingest RPC — the raft propose + apply of
        a multi-million-row file takes seconds, not the default 10 —
        and doubles as the whole operation's retry deadline."""
        import uuid as _uuid
        from ..utils.backoff import Backoff
        # the ingest RPC keeps its FULL caller-sized timeout on every
        # attempt (uploads must not eat its budget); the backoff
        # deadline only bounds the whole retry loop
        bo = Backoff(base=0.05, cap=1.0, deadline_s=timeout * 4)
        last = None
        for _attempt in range(4):
            region, leader = self._lookup_region(region_key)
            uuid = _uuid.uuid4().hex
            total = max(1, -(-len(sst_blob) // chunk))
            sc = self._store_client(leader.store_id)
            try:
                for seq in range(total):
                    sc.call("ImportUpload", {
                        "uuid": uuid, "seq": seq, "total": total,
                        "data": sst_blob[seq * chunk:(seq + 1) * chunk]})
                r = sc.call("ImportIngest",
                            {"uuid": uuid, "region_id": region.id},
                            timeout=timeout)
                return r["ingested"]
            except wire.RemoteError as e:
                if e.kind in ("not_leader", "epoch_not_match",
                              "region_merging", "server_is_busy") or \
                        "KeyNotInRegion" in str(e):
                    # stale routing / transient: refresh and retry
                    # (KeyNotInRegion = cached bounds predate a split).
                    # A busy server names its own drain time
                    # (retry_after_ms from read-pool queue depth) —
                    # honor it over blind exponential jitter
                    self._invalidate_region(region_key)
                    last = e
                    hint = e.err.get("retry_after_ms") \
                        if e.kind == "server_is_busy" else None
                    if not bo.sleep(hint_s=hint / 1000.0
                                    if hint else None):
                        break
                    continue
                raise
        raise last

    def import_switch_mode(self, store_id: int,
                           import_mode: bool) -> bool:
        r = self._store_client(store_id).call(
            "ImportSwitchMode", {"import": import_mode})
        return r["import_mode"]

    def debug(self, store_id: int, method: str, req: dict) -> dict:
        """Debug-service RPC against one specific store (debug.rs is
        store-local by design — it inspects that store's engine)."""
        return self._store_client(store_id).call(method, req)
