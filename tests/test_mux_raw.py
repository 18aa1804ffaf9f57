"""A fan-out's cop tasks as RAW commands on one BatchCommands stream a
``StoreClient`` (server/client.py ``call_raw`` / ``call_mux``,
server/service.py ``batch_commands``, server/server.py's command and
stream pools): a command carries the bytes the unary call would have
carried and is answered with the bytes that call would have returned,
by ``handle_raw`` on the bounded command pool.  The rig is
tests/test_regions96_served.py's: the regions cell's table at a small
size on the CPU, six regions, a device runner, the status server."""

import dataclasses
import threading
import time

import pytest

import jax

from test_regions96_served import (  # noqa: F401 — the rig and its fixtures
    N,
    ROWS,
    health,
    kind,
    params,
    read,
    serve,
    table_kind,
)
from tikv_tpu.codec.keys import table_record_key
from tikv_tpu.device import DeviceRunner
from tikv_tpu.parallel import make_mesh
from tikv_tpu.server import wire
from tikv_tpu.server.client import BatchCommandsClient, MuxClosed, StoreClient
from tikv_tpu.utils import failpoint, trace

WIRE_PHASES = ("client_route", "client_encode", "wire_request",
               "rpc_accept_wait", "wire_reply", "client_decode")
CLOCK = ("call", "sent", "accept", "t0", "t1", "bytes_in", "decoded")


@pytest.fixture(scope="module")
def store(table_kind):
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                          chunk_rows=1 << 12)
    yield from serve(table_kind, runner, ("dense", "split"))


def mux_stats(store) -> dict:
    return health(store)["batch_commands"]


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def plan(store, kind, params, name="dense", chunk=False):
    dag, _c = kind.prepare(store.ctxs[name], store.client, params)
    return dataclasses.replace(dag, encode_type="chunk") if chunk else dag


def task_reqs(store, dag, **extra) -> list:
    """The requests ``_run_cop_task`` makes of ``dag``, one a region →
    [(store id, request dict)]."""
    env = {"tp": 103,
           "dag": wire.enc_dag(dataclasses.replace(dag, ranges=())),
           "force_backend": None, "paging_size": 0, "resume_token": None,
           "resource_group": "default", "request_source": ""}
    return [(leader.store_id,
             dict(env, dag=dict(env["dag"], ranges=wire.enc_ranges(ranges)),
                  context=wire.enc_region_ctx(region), **extra))
            for region, leader, ranges
            in store.client._cut_by_region(dag.ranges)]


def unary_raw(store, sid: int, raw: bytes) -> bytes:
    """``raw`` as the body of a unary Coprocessor call → the reply's
    bytes as they crossed the wire."""
    fn = store.client._store_client(sid)._chan.unary_unary(
        "/tikv.Tikv/Coprocessor", request_serializer=lambda b: b,
        response_deserializer=lambda b: b)
    return fn(raw, timeout=60)


def mux_raw(store, sid: int, raw: bytes) -> bytes:
    return store.client._store_client(sid).call_raw(
        "Coprocessor", raw, 60)[0]


def shape(reply: bytes) -> dict:
    """A reply with what only its own run can say taken out: the ids and
    the times; the names of its phases and its labels stay."""
    r = wire.unpack(reply)
    td = r.pop("time_detail", None) or {}
    labels = dict(td.get("labels", {}))
    labels.pop("ru", None)
    err = r.get("error")
    if isinstance(err, dict):
        err.pop("overrun_ms", None)
    for summary in r.get("exec_summaries", ()):
        summary.pop("time_ns", None)
    return {"body": {k: v for k, v in r.items()
                     if k not in ("trace_id", "scan_detail", "elapsed_ns")},
            "keys": sorted(r), "labels": labels,
            "phases": sorted(td.get("phases_ms", {})),
            "detail": sorted(td)}


# ---------------------------------------------- a command against a call

def _case(store, kind, params, case: str):
    """→ (store id, the request's bytes) of one cop task of ``case``."""
    if case == "chunk":
        return [(sid, wire.pack(req)) for sid, req in task_reqs(
            store, plan(store, kind, params, chunk=True))][1]
    dag = plan(store, kind, params)
    extra = {"slow": {"force_backend": "host"},
             "shed": {"deadline_ms": 0}}.get(case, {})
    sid, req = task_reqs(store, dag, **extra)[2]
    if case == "error":
        req["context"] = dict(req["context"],
                              version=req["context"]["version"] + 1)
    return sid, wire.pack(req)


@pytest.mark.parametrize("case", ["hit", "slow", "chunk", "error", "shed"])
def test_a_raw_command_is_answered_as_the_unary_call_of_its_bytes(
        store, kind, params, case):
    sid, raw = _case(store, kind, params, case)
    if case in ("hit", "chunk"):
        for _ in range(2):      # the class is learned, then hit
            unary_raw(store, sid, raw)
    before = mux_stats(store)
    called, sent = unary_raw(store, sid, raw), mux_raw(store, sid, raw)
    assert shape(sent) == shape(called)
    got = wire.unpack(sent)
    assert got["trace_id"] and "time_detail" in got
    assert set(got["time_detail"]["clock_ns"]) == {"accept", "t0", "t1"}
    labels = got["time_detail"].get("labels", {})
    if case in ("hit", "chunk"):
        assert labels["fastpath"] == "hit" and got["backend"] == "device"
        assert ("chunk" in got) == (case == "chunk")
    elif case == "slow":
        assert got["backend"] == "host" and "fastpath" not in labels
    elif case == "error":
        assert got["error"]["kind"] == "epoch_not_match"
    else:
        assert got["error"]["kind"] == "deadline_exceeded"
    d = delta(mux_stats(store), before)
    assert (d["messages_in"], d["commands_in"], d["raw_commands"],
            d["messages_out"], d["responses_out"]) == (1, 1, 1, 1, 1)


def test_a_class_is_learned_and_hit_over_the_mux(store, kind, params):
    """A wire shape the store has not seen (a ``trace_id`` makes one):
    the first command misses and teaches the fast path, every later one
    of the class hits, one ``hit`` a command."""
    dag = plan(store, kind, params)
    sid, req = task_reqs(store, dag, trace_id="mux-learn-1")[3]
    fp = store.node.fastpath
    first = wire.unpack(mux_raw(store, sid, wire.pack(req)))
    assert "fastpath" not in first["time_detail"]["labels"]
    assert first["trace_id"] == "mux-learn-1"
    hits = fp.stats()["hit"]
    for i in range(3):
        req["trace_id"] = f"mux-learn-{i + 2}"
        again = wire.unpack(mux_raw(store, sid, wire.pack(req)))
        assert again["time_detail"]["labels"]["fastpath"] == "hit"
        assert again["trace_id"] == req["trace_id"]
        assert again["rows"] == first["rows"]
        assert fp.stats()["hit"] == hits + i + 1


# ------------------------------------------------------- a whole fan-out

def unary_fanout(store, kind, params, name, monkeypatch):
    """The same read with every task a unary call."""
    cls = type(store.client)
    real = cls._run_cop_task
    monkeypatch.setattr(
        cls, "_run_cop_task", lambda self, task, env, timeout, t_entry,
        mux=False, *rest: real(self, task, env, timeout, t_entry, False,
                               *rest))
    try:
        return read(store, kind, params, name)
    finally:
        monkeypatch.undo()


def test_a_fan_out_over_the_mux_is_the_unary_fan_out(store, kind, params,
                                                     monkeypatch):
    for _ in range(2):
        read(store, kind, params, "dense")
    m0 = mux_stats(store)
    served = health(store)["coprocessor"]["requests_served"]
    one, unary = unary_fanout(store, kind, params, "dense", monkeypatch)
    assert delta(mux_stats(store), m0)["commands_in"] == 0
    m0 = mux_stats(store)
    two, muxed = read(store, kind, params, "dense")
    d = delta(mux_stats(store), m0)
    assert d["commands_in"] == d["raw_commands"] == d["responses_out"] == N
    assert d["messages_in"] <= N and d["messages_out"] <= N
    assert d["streams"] == 0 and d["unary_resends"] == 0
    assert health(store)["coprocessor"]["requests_served"] == served + 2 * N
    assert one["ok"] and two["ok"] and one["answer"] == two["answer"] == \
        kind.reference(store.ctxs["dense"], params).tobytes()
    assert muxed["tasks"] == unary["tasks"] == N
    assert muxed["backend"] == unary["backend"] == "device"
    assert [r["rows"] for r in muxed["responses"]] == \
        [r["rows"] for r in unary["responses"]]
    drop = {"ru"}
    assert {k: v for k, v in two["labels"].items() if k not in drop} == \
        {k: v for k, v in one["labels"].items() if k not in drop}
    assert two["labels"]["cop_tasks"] == str(N)
    assert two["labels"]["fastpath"] == "hit"
    assert "unary_resends" not in two["labels"]
    assert "wire_clock" not in two["labels"]
    assert sorted(two["phases_ms"]) == sorted(one["phases_ms"])
    for phase in WIRE_PHASES + ("fanout_cut", "fanout_tasks",
                                "fanout_straggler", "fanout_task"):
        assert two["phases_ms"][phase] >= 0, phase
    # every task's reply carries the seven stamps, in order, and its
    # wire phases with the root span add up to its wall
    for r in muxed["responses"]:
        td = r["time_detail"]
        ck = td["clock_ns"]
        at = [ck[k] for k in CLOCK]
        assert at == sorted(at), ck
        ph = td["phases_ms"]
        wall = sum(ph[p] for p in WIRE_PHASES[1:]) + td["total_rpc_wall_ms"]
        assert wall == pytest.approx((ck["decoded"] - ck["call"]) / 1e6,
                                     abs=0.01)


def test_a_lone_task_and_a_plain_read_send_no_command(store, kind, params):
    """What has nothing to batch with stays a unary call: a fan-out
    whose ranges lie in one region, and ``coprocessor()``."""
    ctx = store.ctxs["dense"]
    dag = plan(store, kind, params)
    per = -(-ROWS // N)
    from tikv_tpu.executors.ranges import KeyRange
    inside = dataclasses.replace(dag, ranges=(KeyRange(
        table_record_key(ctx.table.table_id, per + 10),
        table_record_key(ctx.table.table_id, per + 900)),))
    m0 = mux_stats(store)
    served = health(store)["coprocessor"]["requests_served"]
    lone = store.client.coprocessor_fanout(inside, timeout=60)
    assert lone["tasks"] == 1 and lone["backend"] == "device"
    for phase in WIRE_PHASES:
        assert phase in lone["time_detail"]["phases_ms"], phase
    whole = store.client.coprocessor(inside, timeout=60)
    assert whole["backend"] == "device" and len(whole["rows"]) > 0
    assert health(store)["coprocessor"]["requests_served"] == served + 2
    d = delta(mux_stats(store), m0)
    assert d["raw_commands"] == d["commands_in"] == d["messages_in"] == 0


def test_epoch_not_match_on_a_command_recuts_that_task_alone(store, kind,
                                                             params):
    """A region splits after the client cached its bounds: the ONE
    command cut for the old epoch is refused, its ranges are cut again
    and sent again as commands; the other five are not."""
    ctx = store.ctxs["split"]
    stale = store.TxnClient(store.pd_addr)
    try:
        rec, _resp = read(store, kind, params, "split", stale)
        assert rec["ok"] and rec["labels"]["cop_tasks"] == str(N)
        per = -(-ROWS // N)
        store.client.split(table_record_key(ctx.table.table_id,
                                            2 * per + per // 2))
        m0 = mux_stats(store)
        rec, resp = read(store, kind, params, "split", stale)
        d = delta(mux_stats(store), m0)
    finally:
        stale.close()
    assert rec["ok"] and resp["tasks"] == N + 1
    assert rec["labels"]["fanout_retries"] == "1"
    assert "unary_resends" not in rec["labels"]
    assert rec["answer"] == kind.reference(ctx, params).tobytes()
    # N commands, the refused one's two pieces: nothing went unary
    assert d["raw_commands"] == d["responses_out"] == N + 2
    assert d["unary_resends"] == 0


# ----------------------------------------------------- a stream that dies

def test_a_killed_stream_fails_no_read_and_the_next_reopens_it(store, kind,
                                                               params):
    """The stream is cancelled under six parked commands: each waiter is
    woken at once, sends its task again as a unary call that says so,
    and the read's answer is the reference; the next fan-out opens
    another stream."""
    client = store.TxnClient(store.pd_addr)
    try:
        rec, _resp = read(store, kind, params, "dense", client)
        assert rec["ok"]
        sc = next(iter(client._stores.values()))
        first = sc._mux
        m0 = mux_stats(store)
        got = {}
        failpoint.cfg("copr::fastpath", "pause")
        try:
            t = threading.Thread(target=lambda: got.update(
                read=read(store, kind, params, "dense", client)))
            t.start()
            deadline = time.monotonic() + 20
            while delta(mux_stats(store), m0)["raw_commands"] < N:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            first._responses.cancel()
            deadline = time.monotonic() + 20
            while not first.closed:
                assert time.monotonic() < deadline
                time.sleep(0.01)
        finally:
            failpoint.teardown()
        t.join(60)
        assert not t.is_alive()
        rec, resp = got["read"]
        assert rec["ok"] and resp["tasks"] == N
        assert rec["answer"] == \
            kind.reference(store.ctxs["dense"], params).tobytes()
        assert rec["labels"]["unary_resends"] == str(N)
        for phase in WIRE_PHASES:
            assert phase in rec["phases_ms"], phase
        d = delta(mux_stats(store), m0)
        assert d["unary_resends"] == N and d["raw_commands"] == N
        # a dead stream is a transport failure, the resend's answer a
        # success: the breaker is closed again
        assert all(b["state"] == "closed"
                   for b in client.breaker_states().values())
        with pytest.raises(MuxClosed):
            first.call_raw("Coprocessor", b"", 5)
        rec, _resp = read(store, kind, params, "dense", client)
        assert rec["ok"] and "unary_resends" not in rec["labels"]
        assert sc._mux is not first and not sc._mux.closed
        d = delta(mux_stats(store), m0)
        assert d["streams"] == 1 and d["raw_commands"] == 2 * N
        assert d["unary_resends"] == N
    finally:
        client.close()


def test_a_handler_that_raises_answers_its_command_alone(store, kind,
                                                         params):
    """``handle_raw`` raising under one command of a message: that
    command is answered with the error, its neighbours with their rows,
    and the stream lives."""
    dag = plan(store, kind, params)
    reqs = task_reqs(store, dag)
    sc = store.client._store_client(reqs[0][0])
    sc.call_mux("Coprocessor", reqs[0][1], 60)      # the stream is open
    mux = sc._mux
    out, errors = {}, {}

    def send(i):
        try:
            out[i] = sc.call_mux("Coprocessor", reqs[i][1], 60)
        except wire.RemoteError as e:
            errors[i] = e

    failpoint.cfg("copr::fastpath", "1*panic(boom)")
    try:
        threads = [threading.Thread(target=send, args=(i,))
                   for i in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        failpoint.teardown()
    assert len(errors) == 1 and len(out) == N - 1, (errors, sorted(out))
    assert "boom" in str(next(iter(errors.values())).err)
    assert all(len(r["rows"]) > 0 for r in out.values())
    assert sc._mux is mux and not mux.closed
    assert "rows" in sc.call_mux("Coprocessor", reqs[0][1], 60)


def test_a_parked_lock_wait_cannot_block_a_raw_command(store, kind, params):
    """A pessimistic-lock wait parked on the stream (dict form: a thread
    of its own) holds no worker of the command pool: a raw command sent
    behind it on the SAME stream is answered while it is still parked."""
    c = store.client
    dag = plan(store, kind, params)
    sid, req = task_reqs(store, dag)[0]
    sc = c._store_client(sid)
    sc.call_mux("Coprocessor", req, 60)
    mux = sc._mux
    ts1, ts2 = c.tso(), c.tso()
    mux.call("KvPessimisticLock", {
        "keys": [b"rawlock"], "primary": b"rawlock",
        "start_version": ts1, "for_update_ts": ts1})
    got = {}

    def waiter():
        try:
            got["r"] = mux.call("KvPessimisticLock", {
                "keys": [b"rawlock"], "primary": b"rawlock",
                "start_version": ts2, "for_update_ts": ts2,
                "wait_timeout_s": 8.0}, timeout=15)
        except wire.RemoteError as e:
            got["r"] = e.kind

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.2)                     # parked in the store
    t0 = time.monotonic()
    assert "rows" in sc.call_mux("Coprocessor", req, 60)
    assert time.monotonic() - t0 < 5 and t.is_alive() and not got
    mux.call("KvPessimisticRollback", {
        "keys": [b"rawlock"], "start_version": ts1, "for_update_ts": ts1})
    t.join(12)
    assert not t.is_alive() and "r" in got


# ------------------------------------------------------------- the pools

def test_streams_and_commands_take_no_unary_handler(store, kind, params):
    """A stream's generator parks on the stream pool and its commands
    run on the command pool: with more streams open than the handler
    pool has workers, unary RPCs are still served at once."""
    addr = store.node.addr
    width = store.srv._pool._max_workers
    assert store.srv._command_pool._max_workers == width == 8
    muxes = [BatchCommandsClient(addr) for _ in range(width + 2)]
    try:
        ts = store.client.tso()
        for m in muxes:
            m.call("KvGet", {"key": b"nope", "version": ts})
        names = [t.name for t in threading.enumerate()]
        assert sum(n.startswith("mux-stream_") for n in names) >= width + 2
        assert sum(n == "mux-stream-feeder" for n in names) >= width + 2
        assert mux_stats(store)["open"] >= width + 2
        t0 = time.monotonic()
        store.client._store_client(store.node.store_id).call(
            "KvGet", {"key": b"nope", "version": ts}, timeout=10)
        assert time.monotonic() - t0 < 5
        # where a raw command runs, and who stamped its hand-off
        seen = []
        failpoint.cfg_callback("copr::fastpath", lambda: seen.append(
            threading.current_thread().name))
        try:
            sid, req = task_reqs(store, plan(store, kind, params))[0]
            store.client._store_client(sid).call_mux("Coprocessor", req, 60)
        finally:
            failpoint.teardown()
        assert seen and seen[0].startswith("mux-command_"), seen
    finally:
        for m in muxes:
            m.close()
    deadline = time.monotonic() + 10
    while mux_stats(store)["open"] > 1 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert mux_stats(store)["open"] <= 1    # the rig's own client's


def test_four_streams_of_twelve_commands_on_a_pool_of_eight(table_kind,
                                                            kind, params):
    """Four sessions' streams, twelve commands each in flight, the
    command pool at eight: every command is answered, and the store
    leaves no thread behind when it stops."""
    before = {t.ident for t in threading.enumerate()}
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                          chunk_rows=1 << 12)
    rig = serve(table_kind, runner, ("dense",))
    st = next(rig)
    try:
        read(st, kind, params, "dense")
        reqs = task_reqs(st, plan(st, kind, params))
        clients = [StoreClient(st.node.addr) for _ in range(4)]
        answers, errors = [], []

        def send(sc, i):
            try:
                answers.append(sc.call_mux(
                    "Coprocessor", reqs[i % N][1], 120)["rows"])
            except Exception as e:      # noqa: BLE001 — reported below
                errors.append(e)

        threads = [threading.Thread(target=send, args=(sc, i))
                   for sc in clients for i in range(12)]
        m0 = st.node.mux_stats.stats()
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors and len(answers) == 48, errors
        assert not any(t.is_alive() for t in threads)
        d = delta(st.node.mux_stats.stats(), m0)
        assert d["raw_commands"] == d["responses_out"] == 48
        assert d["streams"] == 4 and st.node.mux_stats.stats()["open"] == 5
        assert len(st.srv._command_pool._threads) == 8
        for sc in clients:
            sc.close()
    finally:
        with pytest.raises(StopIteration):
            next(rig)
    deadline = time.monotonic() + 10
    left = None
    while time.monotonic() < deadline:
        left = [t.name for t in threading.enumerate()
                if t.ident not in before and t.name != "gil-probe"
                and not t.name.startswith("copr-fanout")]
        if not left:
            break
        time.sleep(0.05)
    assert not left, left


# --------------------------------------------------------- the aggregate

def test_accept_wait_and_reply_rows_count_one_a_raw_command(store, kind,
                                                            params):
    """``rpc_accept_wait`` (the command pool's hand-off → the tracker's
    install) and ``rpc_reply`` (the seal → the response MESSAGE packed)
    each count once a raw command, as once a unary call."""
    read(store, kind, params, "dense")
    a = trace.AGGREGATE.snapshot()
    rec, resp = read(store, kind, params, "dense")
    assert rec["ok"]
    b = trace.AGGREGATE.snapshot()
    for row in ("rpc_accept_wait", "rpc_reply", "rpc"):
        assert b[row]["count"] - a[row]["count"] == N, row
        assert b[row]["wall_ms"] >= a[row]["wall_ms"]
    for r in resp["responses"]:
        ck = r["time_detail"]["clock_ns"]
        assert r["time_detail"]["phases_ms"]["rpc_accept_wait"] == \
            pytest.approx((ck["t0"] - ck["accept"]) / 1e6, abs=0.002)
