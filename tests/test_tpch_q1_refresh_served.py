"""TPC-H's refresh functions beside Q1 (benchmark configuration
``tpch-sf1-lineitem-q1-refresh-regions96``) at a small size on the CPU:
tests/test_tpch_q1_served.py's rig (the table kind's own data, pre-split
and loaded by the cell's table kind into a store built as
``benchmark/rig.py`` builds it, the Pallas body in interpret mode), read
AND written through gRPC with the cell's own request kind: before every
read one RF1 order (1-7 lineitems inserted at the table's tail) and one
RF2 order (the oldest order's lineitems deleted at its head), each a 2PC
transaction.

Held here: every read equals the request kind's reference at its own TSO
(the loaded table plus every transaction committed at or before it), with
inserts and deletes between reads; the tail region's feed is patched, the
head region's built again, both counted on ``/health``
``device_mesh.feed``; a written line keeps its derived constants (no
column read again); both controls (float32 products, stale by one) fail
the check; a fan-out task that meets a lock is retried and the read still
answers every region; and the whole flow of ``benchmark/loadgen.py`` as a
child process, with the cell's new layer metrics read over its window."""

import functools
import json
import os
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

import jax

from test_tpch_q1_served import (
    BENCH, BLOCK, ROOT, ROWS, SEED, SPLIT_MB, THRESHOLD, failing, health,
)
from tikv_tpu.codec.keys import table_record_key
from tikv_tpu.config import TikvConfig
from tikv_tpu.device import DeviceRunner, pallas_hash
from tikv_tpu.parallel import make_mesh

import byname  # noqa: E402 — test_tpch_q1_served put benchmark/ on the path
from pending_entries import (  # noqa: E402
    REFRESH, finite, pending_metrics, read_pending,
)

CELL = "q1-refresh-lineitem-sf1-closed4"
CONFIG = "tpch-sf1-lineitem-q1-refresh-regions96"
Q1_CONFIG = "tpch-sf1-lineitem-q1-regions96"
TABLE_IDS = {"refresh": 9946, "loadgen": 9947}


def load_config(name: str = CONFIG) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def load_traffic() -> dict:
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        return json.load(f)


N = load_config()["table"]["regions"]


@pytest.fixture(scope="module")
def kind():
    return byname.load("requests", "tpch_q1_refresh")


@pytest.fixture(scope="module")
def table_kind():
    return byname.load("tables", "lineitem_presplit")


@pytest.fixture(scope="module")
def params():
    return load_traffic()["kinds"]["tpch_q1_refresh"]["params"]


@pytest.fixture(scope="module")
def store(table_kind):
    pytest.importorskip("grpc")
    from tikv_tpu.raftstore.metapb import Store
    from tikv_tpu.server import (
        Node, PdServer, RemotePdClient, TikvServer, TxnClient,
    )
    mp = pytest.MonkeyPatch()
    mp.setattr(pallas_hash.pl, "pallas_call",
               functools.partial(pallas_hash.pl.pallas_call, interpret=True))
    mp.setattr(pallas_hash, "BLOCK", BLOCK)
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                          chunk_rows=1 << 12)
    runner._is_tpu = True           # lift the CPU gate (agg_bodies)
    runner._block_local = BLOCK
    config = TikvConfig.from_file(os.path.join(ROOT, load_config()["toml"]))
    config.raftstore.region_split_size_mb = SPLIT_MB
    config.coprocessor.device_row_threshold = THRESHOLD
    pd_server = PdServer("127.0.0.1:0")
    pd_server.start()
    pd_addr = f"127.0.0.1:{pd_server.port}"
    node = Node("127.0.0.1:0", RemotePdClient(pd_addr),
                device_runner=runner, config=config)
    srv = TikvServer(node, status_addr="127.0.0.1:0")
    node.addr = f"127.0.0.1:{srv.port}"
    node.pd.put_store(Store(node.store_id, node.addr))
    srv.start()
    client = TxnClient(pd_addr)
    spec = dict(load_config()["table"], table_id=TABLE_IDS["refresh"])
    table = table_kind.fixture(spec)
    cols = table_kind.make(spec, SEED, ROWS)
    table_kind.load(client, node.store_id, table, cols)
    ctx = types.SimpleNamespace(table=table, rows=ROWS, cols=cols)
    gate = threading.Barrier(16)    # the fan-out workers, before any test
    for _ in range(15):
        client._fanout_executor(15).submit(gate.wait)
    gate.wait()
    try:
        yield types.SimpleNamespace(
            node=node, runner=runner, client=client, pd_addr=pd_addr,
            ctx=ctx, status_port=srv.status_server.port)
    finally:
        # (a kernel's lane programs compile on daemon threads beside its
        # first build: a process that exits under one aborts)
        t_end = time.monotonic() + 180
        while time.monotonic() < t_end and any(
                v is None for k, e in runner._kernel_cache.items()
                if isinstance(k, tuple) and k[:1] == ("hashpl",)
                and isinstance(e, dict)
                for v in (e.get("lane_progs") or {}).values()):
            time.sleep(0.05)
        client.close()
        srv.stop()
        pd_server.stop()
        mp.undo()


def record(store, kind, params, request) -> dict:
    """``request`` sent and kept as ``loadgen.py request()`` keeps it."""
    resp = kind.send(store.ctx, store.client, request)
    td = resp.get("time_detail", {})
    labels, phases = td.get("labels", {}), td.get("phases_ms", {})
    rec = {"labels": labels, "phases_ms": phases,
           "ok": resp.get("backend") == "device" and
           "degraded" not in labels and "host_exec" not in phases}
    if rec["ok"]:
        rec["answer"] = kind.digest(store.ctx, resp, params)
    return rec


def refresh_and_read(store, kind, params) -> dict:
    """One turn of a session: RF1, RF2, TSO, Q1."""
    return record(store, kind, params,
                  kind.prepare(store.ctx, store.client, params))


def feed_counts(store) -> dict:
    """What the feed ladder did to resident feeds (its answers by rung,
    ``gets``, count every read and are left out)."""
    feed = health(store)["device_mesh"]["feed"]
    return {k: v for k, v in feed.items() if k != "gets"}


# ------------------------------------------------- the files of the cell


def test_the_cells_files_agree_on_the_layout(kind, params):
    config, q1 = load_config(), load_config(Q1_CONFIG)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    traffic = load_traffic()
    with open(os.path.join(BENCH, "traffic",
                           "q1-lineitem-sf1-closed4.json")) as f:
        q1_traffic = json.load(f)
    tspec = config["table"]
    # Q1's table, loader and TOML, by name and by path
    assert {k: v for k, v in tspec.items() if k != "table_id"} == \
        {k: v for k, v in q1["table"].items() if k != "table_id"}
    lineitem = [c for c in manifest["configs"] if "lineitem" in c["name"]]
    ids = {json.load(open(os.path.join(ROOT, c["file"])))["table"]["table_id"]
           for c in lineitem}
    # (five since the streams configuration, PR 48; each its own table)
    assert len(ids) == len(lineitem) >= 4 and tspec["table_id"] in ids
    assert config["toml"] == q1["toml"] and config["chips"] == 1
    assert params["regions"] == tspec["regions"]
    assert params["scale_factor"] == tspec["scale_factor"]
    # Q1's arrivals, warm-up, kernel and forbidden stand-ins
    for key in ("loop", "trace_window_s", "warm_requests", "warm_s",
                "forbidden_classes"):
        assert traffic[key] == q1_traffic[key], key
    for key in ("of", "match", "input_plane_bytes_per_row", "bound",
                "rows_per_launch"):
        assert traffic["main_kernel"][key] == \
            q1_traffic["main_kernel"][key], key
    assert [(g["count"], g["think_ms"]) for g in traffic["clients"]] == \
        [(4, 0)]
    assert traffic["first_read"] == "tpch_q1_refresh"
    entry, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == \
        ["replicas", "scale_factor"]
    assert entry["source"] == config["source"] != q1["source"]
    assert len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    # appended where the lists ended at PR 45, and entries never move
    assert manifest["workloads"][7] is cell
    assert manifest["configs"][7] is entry
    # Q1's five guarantees, freshness and isolation restated as exercised
    ours, theirs = config["guarantees"], q1["guarantees"]
    assert set(ours) == set(theirs)
    assert ours["exactness"] == theirs["exactness"]
    assert ours["durability"] == theirs["durability"]
    assert ours["freshness"].startswith("exercised")
    assert "commit_ts <= start_ts" in ours["freshness"]
    assert set(q1["assumed"]) < set(config["assumed"])
    # the ten shared layer metrics and the cell's own ten: seven of the
    # layers only it works, and three that read, under writes, what the
    # read-only cells' caches do (the fast path's learned class, the
    # prepared record, the dispatcher)
    mine = sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", ()))
    own = sorted(m["name"] for m in manifest["per_layer"]
                 if m.get("workloads") == [CELL])
    assert own == ["cache.delta_apply_ms", "cache.deltas_per_task",
                   "cop.locked_reply_share",
                   "dispatcher.busy_share.refresh",
                   "fastpath.hit_share.refresh", "feed.patch_ms",
                   "feed.patch_share", "feed.rebuild_ms",
                   "kernel.pallas_q1_refresh_region_roofline",
                   "prepared.drops_per_task"]
    # (and, PR 51, the share of the dispatcher's hold that has a name)
    assert len(mine) == 21 and "dispatcher.hold_named_share" in mine
    layers = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    assert {layers[n] for n in own} == {
        "columnar cache, feed", "client fan-out", "kernel launch",
        "RPC decode / fast path", "read pool / coalescer"}
    assert kind.CLASSES == ("pallas_hash",)


# ------------------------------------------------- answers under writes


def test_every_read_answers_the_table_as_of_its_tso(store, kind, params):
    """Inserts AND deletes between reads: each read equals the reference
    at its own TSO, the tail region's feed is patched forward and the
    head region's is built again, from the resident planes (compacted
    on the device: no host rebuild in the window)."""
    first = refresh_and_read(store, kind, params)       # cold: builds
    assert first["ok"], first
    feed0, cache0 = feed_counts(store), health(store)["copr_cache"]
    memo0 = health(store)["device_mesh"]["memo"]
    derives0 = health(store)["tracing"]["phases"]["host_derive"]["count"]
    recs = [refresh_and_read(store, kind, params) for _ in range(6)]
    assert all(r["ok"] for r in recs), [r["labels"] for r in recs]
    assert all(r["labels"]["cop_tasks"] == str(N) for r in recs)
    assert failing(kind.check(store.ctx, [first] + recs, params, None)) == []
    # twelve orders went in and out under those six reads
    log = kind._shared(store.ctx, params).log[-14:]
    assert sum(1 for e in log if e[1] > 0) == \
        sum(1 for e in log if e[1] < 0) == 7
    answers = {r["answer"][8:] for r in recs}
    assert len(answers) > 1, "the writes changed no answer"
    feed1, cache1 = feed_counts(store), health(store)["copr_cache"]
    # the tail region: a patch a read; the head: a rebuild a read
    assert feed1["patches"] - feed0["patches"] == 6
    assert feed1["patch_rows"] - feed0["patch_rows"] == sum(
        len(e[2]["l_quantity"]) for e in log[2:] if e[1] > 0)
    assert set(feed1["patch_buckets"]) == {"16"}
    # ONE device program a window: the seven planes' updates and their
    # digest chain together
    assert feed1["patch_programs"] - feed0["patch_programs"] == \
        feed1["patch_windows"] - feed0["patch_windows"] == 6
    rebuilt = {k: feed1["rebuilds_after_delta"][k] -
               feed0["rebuilds_after_delta"][k]
               for k in feed1["rebuilds_after_delta"]}
    assert rebuilt == {"structural": 6, "pad": 0, "dtype": 0, "null": 0}
    # ... each of them a compaction of the resident feed by one program
    # (an RF2 order is one run at the head), none sourced from the host
    assert feed1["rebuild_source"]["device"] - \
        feed0["rebuild_source"]["device"] == 6
    assert feed1["rebuild_source"]["host"] == feed0["rebuild_source"]["host"]
    assert feed1["compact_programs"] - feed0["compact_programs"] == 6
    assert feed1["compact_rows"] - feed0["compact_rows"] == sum(
        len(e[2]["l_quantity"]) for e in log[2:] if e[1] < 0)
    assert feed1["after_delta"] - feed0["after_delta"] == 12
    assert cache1["deltas"] - cache0["deltas"] == 12
    assert cache1["rebuilds"] == cache0["rebuilds"]
    assert cache1["misses"] == cache0["misses"]
    # both written lines' derived records rolled across every write:
    # no constant derived again, the head's host planes left as they
    # were with its tombstones noted beside them and never cut (nobody
    # read them: the tail's dropped by its first append)
    memo1 = health(store)["device_mesh"]["memo"]
    assert memo1["kept"] - memo0["kept"] == 12
    assert memo1["dropped"] == memo0["dropped"]
    assert memo1["host_planes"]["deferred"] - \
        memo0["host_planes"]["deferred"] == 6
    assert memo1["host_planes"]["cut"] == memo0["host_planes"]["cut"]
    assert memo1["host_planes"]["dropped"] - \
        memo0["host_planes"]["dropped"] <= 1
    phases = health(store)["tracing"]["phases"]
    assert phases["host_derive"]["count"] == derives0
    assert phases["feed_rebuild"]["count"] >= 6
    assert phases["feed_patch"]["count"] >= 6
    assert phases["delta_apply"]["count"] >= 12


def test_orders_committed_out_of_rowid_order_are_merged_and_patched(
        store, kind, params):
    """Two sessions take their rowids in one order and commit in the
    other: the later rowids arrive first (an append), the earlier ones
    then land among the line's last rows: merged in place of a repack,
    journalled as the span that moved, patched into the tail's feed."""
    from tikv_tpu.testing.fixture import encode_table_row
    ctx, client = store.ctx, store.client
    state = kind._shared(ctx, params)
    refresh_and_read(store, kind, params)           # the lines are warm

    def commit(rowid, order_key, cols, lo, hi):
        state.acked(client.txn_write([("put",) + encode_table_row(
            ctx.table, rowid + k, kind.row_values(cols, i, order_key))
            for k, i in enumerate(range(lo, hi))]), +1,
            kind._q1_cols(cols, lo, hi))

    def read():
        index = kind._q1.next_delta(client)
        ts = client.tso()
        return record(store, kind, params, (
            kind._q1.plan(ctx, index, ts), params["concurrency"], index,
            ts))
    first, second = state.new_order(), state.new_order()
    feed0, cache0 = feed_counts(store), health(store)["copr_cache"]
    commit(*second)
    recs = [read()]
    commit(*first)
    recs.append(read())
    assert all(r["ok"] for r in recs)
    assert failing(kind.check(ctx, recs, params, None)) == []
    feed1, cache1 = feed_counts(store), health(store)["copr_cache"]
    assert cache1["tail_merges"] - cache0["tail_merges"] == 1
    assert cache1["compactions"] == cache0["compactions"]
    assert feed1["patches"] - feed0["patches"] == 2
    assert feed1["rebuilds_after_delta"] == feed0["rebuilds_after_delta"]
    # the merge moved the second order's rows: they are patched too
    assert feed1["patch_rows"] - feed0["patch_rows"] == \
        2 * (second[4] - second[3]) + (first[4] - first[3])


def test_both_controls_are_caught(store, kind, params):
    """The reference with its products in float32, and the reference
    without each read's newest acknowledged transaction (a read served
    one transaction stale), each in the program's place, fail the check
    by the answer alone."""
    ctx = store.ctx
    recs = [refresh_and_read(store, kind, params) for _ in range(3)]
    log = list(kind._shared(ctx, params).log)
    regions = str(params["regions"])
    assert kind.wrong_answers(ctx, [dict(r) for r in recs], log,
                              regions) == (0, 0)
    for control in ({"approx": True}, {"stale": True}):
        reads = [tuple(int(v) for v in np.frombuffer(r["answer"],
                                                     np.int64)[:2])
                 for r in recs]
        served = [{"answer": a.tobytes()} for a in kind.references(
            ctx, log, reads, **control).values()]
        wrong, _off = kind.wrong_answers(ctx, served, log, regions)
        assert wrong > 0, control
    # ... and through ``check`` as benchmark/control.py calls it
    served = {"answer": kind.reference(ctx, params, approx=True).tobytes()}
    assert "tpch_q1_refresh.wrong_answers" in failing(kind.check(
        ctx, [served], params, kind.reference(ctx, params)))


def test_an_unacknowledged_write_fails_the_run(store, kind, params,
                                               monkeypatch):
    state = kind._shared(store.ctx, params)

    def refused(_muts):
        raise RuntimeError("store went away")
    monkeypatch.setattr(store.client, "txn_write", refused)
    with pytest.raises(RuntimeError):
        kind.prepare(store.ctx, store.client, params)
    monkeypatch.undo()
    assert state.unacked == 1
    assert failing(kind.check(store.ctx, [], params, None)) == \
        ["tpch_q1_refresh.unacked_writes"]
    state.unacked = 0


# ------------------------------------------------- a lock in a read's way


def test_a_task_that_meets_a_lock_is_retried(store, kind, params):
    """Another session's prewrite lies in the tail region when the read
    arrives: that cop task is answered ``key_is_locked``, the fan-out
    asks for the transaction's status, waits, sends the task again, and
    the read answers all N regions with the transaction in it once it
    has committed."""
    from tikv_tpu.testing.fixture import encode_table_row
    ctx, client = store.ctx, store.client
    state = kind._shared(ctx, params)
    refresh_and_read(store, kind, params)           # the lines are warm
    rowid, order_key, cols, lo, hi = state.new_order()
    muts = [("put",) + encode_table_row(
        ctx.table, rowid + k, kind.row_values(cols, i, order_key))
        for k, i in enumerate(range(lo, hi))]
    primary = muts[0][1]
    start_ts = client.tso()
    client._call_leader(primary, "KvPrewrite", {
        "mutations": [{"op": op, "key": k, "value": v}
                      for op, k, v in muts],
        "primary": primary, "start_version": start_ts})
    locked0 = health(store)["coprocessor"]["locked_replies"]
    index = kind._q1.next_delta(client)
    read_ts = client.tso()
    request = (kind._q1.plan(ctx, index, read_ts), params["concurrency"],
               index, read_ts)

    def commit():
        # (once the read's task has met the lock)
        t_end = time.monotonic() + 60
        while time.monotonic() < t_end and \
                health(store)["coprocessor"]["locked_replies"] == locked0:
            time.sleep(0.01)
        commit_ts = client.tso()
        client._call_leader(primary, "KvCommit", {
            "keys": [k for _op, k, _v in muts],
            "start_version": start_ts, "commit_version": commit_ts})
        state.acked(commit_ts, +1, kind._q1_cols(cols, lo, hi))
    writer = threading.Thread(target=commit)
    writer.start()
    try:
        rec = record(store, kind, params, request)
    finally:
        writer.join()
    assert rec["ok"], rec
    assert rec["labels"]["cop_tasks"] == str(N)
    assert int(rec["labels"]["lock_retries"]) >= 1
    assert rec["phases_ms"]["fanout_lock_wait"] > 0
    assert health(store)["coprocessor"]["locked_replies"] - locked0 == \
        int(rec["labels"]["lock_retries"])
    # the commit came after the read's TSO: the read is exact WITHOUT it
    assert failing(kind.check(ctx, [rec], params, None)) == []
    later = refresh_and_read(store, kind, params)
    assert failing(kind.check(ctx, [later], params, None)) == []
    assert "lock_retries" not in later["labels"]


def test_an_order_half_committed_is_read_whole(store, kind, params):
    """The writer died between its primary's commit and its
    secondaries': the order IS committed, at a ``commit_ts`` under the
    read's TSO, and some of its rows are still locks.  The fan-out finds
    the transaction committed, resolves its locks in the region where it
    met the first (one status check and one resolve, however many lines
    the order has), and the read holds the whole order."""
    from tikv_tpu.testing.fixture import encode_table_row
    ctx, client = store.ctx, store.client
    state = kind._shared(ctx, params)
    while True:         # an order of several lines
        rowid, order_key, cols, lo, hi = state.new_order()
        if hi - lo >= 3:
            break
        state.acked(client.txn_write([("put",) + encode_table_row(
            ctx.table, rowid + k, kind.row_values(cols, i, order_key))
            for k, i in enumerate(range(lo, hi))]), +1,
            kind._q1_cols(cols, lo, hi))
    muts = [("put",) + encode_table_row(
        ctx.table, rowid + k, kind.row_values(cols, i, order_key))
        for k, i in enumerate(range(lo, hi))]
    primary = muts[0][1]
    start_ts = client.tso()
    client._call_leader(primary, "KvPrewrite", {
        "mutations": [{"op": op, "key": k, "value": v}
                      for op, k, v in muts],
        "primary": primary, "start_version": start_ts})
    commit_ts = client.tso()
    client._call_leader(primary, "KvCommit", {
        "keys": [primary], "start_version": start_ts,
        "commit_version": commit_ts})
    state.acked(commit_ts, +1, kind._q1_cols(cols, lo, hi))
    index = kind._q1.next_delta(client)
    read_ts = client.tso()
    rec = record(store, kind, params, (
        kind._q1.plan(ctx, index, read_ts), params["concurrency"], index,
        read_ts))
    assert rec["ok"], rec
    assert int(rec["labels"]["lock_retries"]) == 1
    assert failing(kind.check(ctx, [rec], params, None)) == []
    # ... and it is in the answer: the stale-by-one control differs
    read = [(read_ts, index)]
    log = list(state.log)
    assert not np.array_equal(
        kind.references(ctx, log, read)[read[0]],
        kind.references(ctx, log, read, stale=True)[read[0]])


def test_a_lock_that_never_clears_is_the_callers(store, kind, params):
    """Past the read's timeout the fan-out hands ``key_is_locked`` on
    (a lock that outlives it by far, a timeout in which a busy box still
    answers every task)."""
    from tikv_tpu.server import wire
    ctx, client = store.ctx, store.client
    key = table_record_key(ctx.table.table_id, 10 ** 9)
    start_ts = client.tso()
    client._call_leader(key, "KvPrewrite", {
        "mutations": [{"op": "put", "key": key, "value": b"\x80"}],
        "primary": key, "start_version": start_ts, "lock_ttl": 600_000})
    try:
        dag = kind._q1.plan(ctx, 0, client.tso())
        with pytest.raises(wire.RemoteError) as e:
            client.coprocessor_fanout(dag, concurrency=15, timeout=3.0)
        assert e.value.kind == "key_is_locked"
    finally:
        client._call_leader(key, "KvBatchRollback", {
            "keys": [key], "start_version": start_ts})
    rec = refresh_and_read(store, kind, params)
    assert rec["ok"] and failing(
        kind.check(ctx, [rec], params, None)) == []


# ------------------------------------------------- loadgen.py, as run.py runs it


@pytest.fixture(scope="module")
def loadgen_result(store, tmp_path_factory):
    """``benchmark/loadgen.py`` itself, as a child with the ``warm`` /
    ``go`` / ``done`` hand-shake of ``run.py``, over the cell's own
    traffic file (``warm_s`` apart) and its configuration (the table's
    id apart): the load, the first read after the first two writes, the
    probes, the warm rounds, a window of one second in which four
    sessions write and read, the check of every record against the
    reference at its own TSO.  → the result file."""
    tmp_path = tmp_path_factory.mktemp("loadgen")
    config = load_config()
    config["table"]["table_id"] = TABLE_IDS["loadgen"]
    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(config))
    traffic = load_traffic()
    traffic["warm_s"] = 0.5
    traffic_file = tmp_path / "traffic.json"
    traffic_file.write_text(json.dumps(traffic))
    out = tmp_path / "result.json"
    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps({
        "pd_addr": store.pd_addr, "status_port": store.status_port,
        "seed": SEED, "seconds": 1, "rows": ROWS,
        "config_file": str(config_file),
        "traffic_file": str(traffic_file),
        "out": str(out), "on_tpu": False}))
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "loadgen.py"), str(spec_file)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        timer = threading.Timer(300, child.kill)
        timer.start()
        try:
            first = child.stdout.readline()
            assert first.startswith("warm "), (first, child.poll())
            warm = json.loads(first[len("warm "):])
            assert warm["failed"] == 0, warm
            child.stdin.write("go\n")
            child.stdin.flush()
            assert child.stdout.readline().strip() == "done"
            assert child.wait(timeout=60) == 0
        finally:
            timer.cancel()
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        child.stdin.close()
        child.stdout.close()
    return json.loads(out.read_text())


def window_data(result: dict) -> dict:
    """``data`` as ``run.py`` hands it to a reader (a copy: a test may
    cut rows out of its samples)."""
    return json.loads(json.dumps({
        "counters_go": result["counters_go"],
        "counters_end": result["counters_end"],
        "reads": [r for r in result["records"] if r["ok"]]}))


def test_loadgen_child_runs_the_cell_end_to_end(loadgen_result):
    """... and the cell's new layer metrics over that window."""
    result = loadgen_result
    assert result["warm_failed"] == 0
    assert result["checks"] == [["tpch_q1_refresh.wrong_answers", 0, 0],
                                ["regions.reads_off_the_layout", 0, 0],
                                ["tpch_q1_refresh.unacked_writes", 0, 0]]
    assert result["records"] and all(r["ok"] for r in result["records"]), \
        [r["why"] for r in result["records"] if not r["ok"]][:3]
    assert all(r["ok"] for r in result["last"])
    assert all(r["labels"]["cop_tasks"] == str(N)
               for r in result["records"])
    data = window_data(result)

    def metric(name):
        with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        return byname.load("readers", spec["reader"]).read(data, spec["args"])

    assert 0 < metric("feed.patch_share") < 100
    assert metric("feed.patch_ms") > 0
    assert metric("feed.rebuild_ms") > 0
    assert metric("cache.delta_apply_ms") > 0
    assert 0 < metric("cache.deltas_per_task") <= 2 / N + 1e-9
    assert 0 <= metric("cop.locked_reply_share") < 100
    # what the read-only cells' caches do while the data under them
    # moves: the learned class keeps hitting, a written line (and no
    # other) loses its prepared record, the dispatcher's share is read
    assert metric("fastpath.hit_share.refresh") > 50
    assert 0 < metric("prepared.drops_per_task") <= 2 / N + 1e-9
    assert 0 < metric("dispatcher.busy_share.refresh") < 100
    # ... and on a program without the counters they read nothing
    for side in (data["counters_go"], data["counters_end"]):
        del side["health"]["device_mesh"]["feed"]
        del side["health"]["coprocessor"]["locked_replies"]
        del side["health"]["tracing"]["phases"]["feed_rebuild"]
    assert metric("feed.patch_share") is None
    assert metric("feed.rebuild_ms") is None
    assert metric("cop.locked_reply_share") is None


# ------------------- the dispatcher's hold and the writes' rows (PR 51)


def test_the_holds_rows_add_up_over_the_childs_window(loadgen_result):
    """Over the window in which four sessions write and read: the rows
    of the hold's vocabulary and ``dispatch_self`` add up to
    ``group_dispatch`` (each row is rounded to a microsecond on
    ``/health``), every staging's row rose, a written region's read
    rolled its memo and staged in full, and the declared share reads
    what the program adds up."""
    from tikv_tpu.utils.trace_vocab import HOLD_CPU, HOLD_SELF, HOLD_WHOLE
    data = window_data(loadgen_result)
    go, end = (data[k]["health"]["tracing"]["phases"]
               for k in ("counters_go", "counters_end"))

    def rise(name, field="wall_ms"):
        return end[name][field] - go[name][field]

    rows = HOLD_SELF + HOLD_WHOLE
    whole = rise("group_dispatch")
    assert whole > 0
    assert sum(rise(n) for n in rows) + rise("dispatch_self") == \
        pytest.approx(whole, rel=0.01, abs=0.05)
    for name in HOLD_SELF + ("device_dispatch", "feed_patch",
                             "feed_rebuild", "dispatch_self"):
        assert rise(name, "count") > 0, name
    assert rise("dispatch_self", "count") == rise("group_dispatch", "count")
    # a write is followed by one roll and one full staging a region (a
    # read that comes back older than its line's memo stages in full
    # without a roll; a staging under way at either sample has closed
    # its ladder and not yet itself)
    assert 0 < rise("memo_roll", "count") <= rise("stage_full", "count") + 1
    assert abs(rise("stage_full", "count") - rise("feed_get", "count")) <= 1
    assert rise("stage_plan", "count") >= rise("stage_full", "count")
    # on the dispatcher the jitted calls take the CPU clock every time
    for name in HOLD_CPU:
        assert rise(name, "cpu_samples") == rise(name, "count"), name
    with open(os.path.join(BENCH, "layer_metrics",
                           "dispatcher.hold_named_share.json")) as f:
        spec = json.load(f)
    share = byname.load("readers", spec["reader"]).read(data, spec["args"])
    assert 0 < share <= 100
    assert share == pytest.approx(
        100.0 * (1 - rise("dispatch_self") / whole), abs=0.5)
    # ... and on a program from before the rows it reads a smaller share
    for side in (go, end):
        for name in HOLD_SELF:
            del side[name]
    older = byname.load("readers", spec["reader"]).read(data, spec["args"])
    assert 0 < older < share


@pytest.mark.parametrize("name", sorted(pending_metrics(REFRESH)))
def test_a_pending_metric_reads_the_writing_cells_child(loadgen_result,
                                                        name):
    """The files that wait for their entries and list this cell (and not
    the regions cell, whose child reads the others:
    tests/test_regions96_served.py), each over a window in which writes
    happen: a finite number from this program."""
    assert CELL == REFRESH
    spec = pending_metrics()[name]
    got = read_pending(name, spec, window_data(loadgen_result))
    assert finite(got), (name, got)
    if spec["pending_entry"]["unit"] == "%":
        assert got <= 100
    if name == "txn.rpcs_per_task":
        # a session's two orders are four RPCs at least (a prewrite and
        # a commit each) beside its read's twelve cop tasks
        assert got >= 4 / N / 2


def test_the_childs_writes_are_traced_on_one_clock(loadgen_result):
    """Every write of the window has its envelope's rows and its send
    stamp was believed (the aggregate is the process's: rises, and a
    write in flight at either sample is counted by one row and not yet
    by the other, one a session at most)."""
    go, end = (loadgen_result[k]["health"]
               for k in ("counters_go", "counters_end"))
    rose = {m: n - go["txn"]["rpcs"][m]
            for m, n in end["txn"]["rpcs"].items()}
    assert rose["KvPrewrite"] > 0 and rose["KvCommit"] >= rose["KvPrewrite"]
    assert end["txn"]["wire_clock_unshared"] == 0

    def rise(name):
        return end["tracing"]["phases"][name]["count"] - \
            go["tracing"]["phases"][name]["count"]

    sessions = 4        # (the traffic file's closed loops)
    assert abs(rise("txn_rpc") - sum(rose.values())) <= sessions
    for name in ("txn_wire_request", "txn_accept_wait", "txn_reply"):
        assert abs(rise(name) - rise("txn_rpc")) <= sessions, name


# ------------------------------------------------- readers that race writers


def cache_counts(store) -> dict:
    return {k: health(store)["copr_cache"][k]
            for k in ("misses", "rebuilds", "deltas", "deltas_held")}


def test_a_reader_between_two_commits_builds_no_line(store, kind, params):
    """Two sessions race: A commits and takes its TSO, B commits after
    it.  Whichever of them reads first, A's read holds A's orders and
    not B's, B's holds both, and neither builds a line at an exact TSO:
    the older reader first holds B's deltas back; the newer reader first
    applies a generation a transaction, and A finds its own in the
    line's history."""
    ctx, client = store.ctx, store.client
    refresh_and_read(store, kind, params)
    for older_first in (True, False):
        a = kind.prepare(ctx, client, params)       # A: RF1, RF2, TSO
        b = kind.prepare(ctx, client, params)       # B, after A's TSO
        before = cache_counts(store)
        recs = [record(store, kind, params, r)
                for r in ((a, b) if older_first else (b, a))]
        assert all(r["ok"] for r in recs)
        assert failing(kind.check(ctx, recs, params, None)) == []
        assert recs[0]["answer"][8:] != recs[1]["answer"][8:] or \
            a[2] != b[2]
        after = cache_counts(store)
        assert after["misses"] == before["misses"], older_first
        assert after["rebuilds"] == before["rebuilds"]
        if older_first:
            # B's two transactions (head and tail) were held back
            assert after["deltas_held"] - before["deltas_held"] >= 2
        else:
            assert after["deltas_held"] == before["deltas_held"]
    # ... and a third reader, later than both, sees everything
    rec = refresh_and_read(store, kind, params)
    assert failing(kind.check(ctx, [rec], params, None)) == []


def test_a_prewrite_in_flight_moves_no_generation(store, kind, params):
    """Another session's prewrite lies in the head and the tail region,
    ABOVE the read's TSO (its start_ts is later): the lines are bridged
    over lock deltas alone, no row changed, and nothing downstream is
    built again: no journal entry, the feeds hit, the memos stand."""
    from tikv_tpu.testing.fixture import encode_table_row
    ctx, client = store.ctx, store.client
    state = kind._shared(ctx, params)
    refresh_and_read(store, kind, params)
    refresh_and_read(store, kind, params)
    index = kind._q1.next_delta(client)
    read_ts = client.tso()
    rowid, order_key, cols, lo, hi = state.new_order()
    lo_old, hi_old = state.old_order()
    muts = [("put",) + encode_table_row(
        ctx.table, rowid + k, kind.row_values(cols, i, order_key))
        for k, i in enumerate(range(lo, hi))] + [
        ("delete", table_record_key(ctx.table.table_id, h), None)
        for h in range(lo_old, hi_old)]
    primary = muts[0][1]
    start_ts = client.tso()             # after the read's TSO
    for op, k, v in muts:               # (a region a prewrite)
        client._call_leader(k, "KvPrewrite", {
            "mutations": [{"op": op, "key": k, "value": v}],
            "primary": primary, "start_version": start_ts})
    feed0, cache0 = feed_counts(store), health(store)["copr_cache"]
    planes0 = health(store)["device_mesh"]["agg_params"]
    rec = record(store, kind, params, (
        kind._q1.plan(ctx, index, read_ts), params["concurrency"], index,
        read_ts))
    assert rec["ok"] and "lock_retries" not in rec["labels"]
    assert failing(kind.check(ctx, [rec], params, None)) == []
    feed1, cache1 = feed_counts(store), health(store)["copr_cache"]
    assert cache1["deltas"] - cache0["deltas"] == 2     # bridged, though
    assert feed1 == feed0
    assert health(store)["device_mesh"]["agg_params"] ["code_planes"] == \
        planes0["code_planes"]
    # ... then the transaction commits and the next read holds it
    commit_ts = client.tso()
    for _op, k, _v in muts:
        client._call_leader(k, "KvCommit", {
            "keys": [k], "start_version": start_ts,
            "commit_version": commit_ts})
    state.acked(commit_ts, +1, kind._q1_cols(cols, lo, hi))
    state.acked(commit_ts, -1, kind._q1_cols(ctx.cols, lo_old, hi_old))
    rec = refresh_and_read(store, kind, params)
    assert failing(kind.check(ctx, [rec], params, None)) == []
