"""Networked server tests: real gRPC servers on loopback — the
"server simulator" tier (components/test_raftstore/src/server.rs:
full gRPC servers, SURVEY.md §4 tier 3)."""

import pytest

from tikv_tpu.server import (
    Node,
    PdServer,
    RemoteError,
    RemotePdClient,
    TikvServer,
    TxnClient,
)


@pytest.fixture(scope="module")
def cluster():
    """One PD + three tikv-servers; replicas added to stores 2/3.

    Every node carries a (shared) device runner with a low routing
    threshold so coprocessor requests over enough rows exercise the
    real RPC→MVCC→device path."""
    from tikv_tpu.device.runner import DeviceRunner
    device = DeviceRunner()
    pd_server = PdServer("127.0.0.1:0")
    pd_server.start()
    pd_addr = f"127.0.0.1:{pd_server.port}"
    servers = []
    for _ in range(3):
        node = Node("127.0.0.1:0", RemotePdClient(pd_addr),
                    device_runner=device, device_row_threshold=128)
        srv = TikvServer(node)
        node.addr = f"127.0.0.1:{srv.port}"
        node.pd.put_store(
            __import__("tikv_tpu.raftstore.metapb", fromlist=["Store"])
            .Store(node.store_id, node.addr))
        srv.start()
        servers.append(srv)
    client = TxnClient(pd_addr)
    # replicate region 1 onto the other two stores
    for srv in servers[1:]:
        client.add_peer(1, srv.node.store_id)
    yield {"pd": pd_server, "servers": servers, "client": client,
           "pd_addr": pd_addr}
    for srv in servers:
        srv.stop()
    pd_server.stop()


def test_txn_put_get_over_network(cluster):
    c = cluster["client"]
    c.put(b"net-k", b"net-v")
    assert c.get(b"net-k") == b"net-v"
    # replicated to all three stores' engines
    import time
    time.sleep(0.3)
    from tikv_tpu.engine.traits import CF_WRITE
    for srv in cluster["servers"]:
        it = srv.node.engine.iterator_cf(CF_WRITE)
        assert it.seek_to_first()


def test_multi_key_2pc(cluster):
    c = cluster["client"]
    commit_ts = c.txn_write([("put", b"2pc-a", b"1"),
                             ("put", b"2pc-b", b"2"),
                             ("put", b"2pc-c", b"3")])
    assert commit_ts > 0
    assert c.get(b"2pc-a") == b"1"
    assert c.get(b"2pc-b") == b"2"
    assert c.get(b"2pc-c") == b"3"


def test_snapshot_read_versions(cluster):
    c = cluster["client"]
    c.put(b"ver-k", b"v1")
    ts1 = c.tso()
    c.put(b"ver-k", b"v2")
    assert c.get(b"ver-k") == b"v2"
    assert c.get(b"ver-k", version=ts1) == b"v1"


def test_scan_over_network(cluster):
    c = cluster["client"]
    for i in range(5):
        c.put(b"scan-%d" % i, b"%d" % i)
    got = c.scan(b"scan-", b"scan-\xff", 10)
    assert got == [(b"scan-%d" % i, b"%d" % i) for i in range(5)]


def test_lock_resolution_over_network(cluster):
    """A reader resolves an abandoned (crashed-writer) lock by TTL."""
    c = cluster["client"]
    c.put(b"lock-k", b"old")
    start_ts = c.tso()
    key = b"lock-k"
    # simulate a writer that prewrote and died (tiny TTL)
    client, _ = c._leader_client(key)
    client.call("KvPrewrite", {
        "mutations": [{"op": "put", "key": key, "value": b"orphan"}],
        "primary": key, "start_version": start_ts, "lock_ttl": 1})
    import time
    time.sleep(0.01)
    assert c.get(key) == b"old"     # resolver rolled the orphan back


def test_write_conflict_surfaces(cluster):
    c = cluster["client"]
    c.put(b"wc-k", b"v")
    stale_ts = 1    # far in the past
    client, _ = c._leader_client(b"wc-k")
    with pytest.raises(RemoteError) as ei:
        client.call("KvPrewrite", {
            "mutations": [{"op": "put", "key": b"wc-k", "value": b"x"}],
            "primary": b"wc-k", "start_version": stale_ts})
    assert ei.value.kind == "write_conflict"


def test_raw_api_over_network(cluster):
    c = cluster["client"]
    c.raw_put(b"raw-k", b"raw-v")
    assert c.raw_get(b"raw-k") == b"raw-v"


def test_coprocessor_over_network(cluster):
    """DAG request through the wire: encode plan → server executes over
    its MVCC snapshot → rows come back."""
    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.testing.fixture import encode_table_row, int_table

    c = cluster["client"]
    table = int_table(2, table_id=9001)
    for h in range(50):
        key, value = encode_table_row(table, h, {"c0": h % 5, "c1": h})
        c.put(key, value)
    sel = DagSelect.from_table(table, ["id", "c0", "c1"])
    dag = sel.where(sel.col("c0").eq(2)).aggregate(
        [], [("count_star", None), ("sum", sel.col("c1"))]
    ).build(start_ts=c.tso())
    resp = c.coprocessor(dag)
    expect = [h for h in range(50) if h % 5 == 2]
    assert resp["rows"] == [[len(expect), sum(expect)]]
    assert resp["backend"] == "host"
    assert len(resp["exec_summaries"]) >= 2


def test_coprocessor_device_backend_over_network(cluster):
    """The round-2 wiring milestone (VERDICT r1 #1): a Coprocessor gRPC
    request against the raft cluster routes to the DEVICE backend via the
    per-region columnar MVCC cache, and repeat queries hit the cache."""
    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.testing.fixture import encode_table_row, int_table

    c = cluster["client"]
    table = int_table(2, table_id=9002)
    muts = []
    for h in range(300):
        key, value = encode_table_row(table, h, {"c0": h % 7, "c1": h})
        muts.append(("put", key, value))
    c.txn_write(muts)

    def make_dag(ts):
        sel = DagSelect.from_table(table, ["id", "c0", "c1"])
        return sel.aggregate(
            [sel.col("c0")],
            [("count_star", None), ("sum", sel.col("c1"))]).build(start_ts=ts)

    resp = c.coprocessor(make_dag(c.tso()))
    assert resp["backend"] == "device", resp["backend"]
    expect = sorted(
        [sum(1 for h in range(300) if h % 7 == g),
         sum(h for h in range(300) if h % 7 == g), g]
        for g in range(7))
    assert sorted(resp["rows"]) == expect

    # parity with the forced host path over the same MVCC data
    host = c.coprocessor(make_dag(c.tso()), force_backend="host")
    assert host["backend"] == "host"
    assert sorted(host["rows"]) == expect

    # repeat query at a fresh ts: columnar cache hit (no write happened)
    hits_before = sum(s.node.copr_cache.hits for s in cluster["servers"])
    resp2 = c.coprocessor(make_dag(c.tso()))
    hits_after = sum(s.node.copr_cache.hits for s in cluster["servers"])
    assert resp2["backend"] == "device"
    assert sorted(resp2["rows"]) == expect
    assert hits_after > hits_before

    # a write to the region invalidates the cached data version
    key, value = encode_table_row(table, 300, {"c0": 0, "c1": 1000})
    c.txn_write([("put", key, value)])
    resp3 = c.coprocessor(make_dag(c.tso()))
    rows3 = {r[2]: r for r in resp3["rows"]}
    assert rows3[0][0] == sum(1 for h in range(300) if h % 7 == 0) + 1
    assert rows3[0][1] == sum(h for h in range(300) if h % 7 == 0) + 1000


def test_concurrent_coprocessor_over_network(cluster):
    """≥4 concurrent warm Coprocessor RPCs through the async serving
    path (dispatch under the read-pool slot, D2H on the completion
    pool) return the same answer as serial execution — the pipeline
    must not silently break off-TPU (CPU smoke for bench 6c)."""
    import concurrent.futures as cf

    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.testing.fixture import encode_table_row, int_table

    c = cluster["client"]
    table = int_table(2, table_id=9003)
    muts = []
    for h in range(400):
        key, value = encode_table_row(table, h, {"c0": h % 5, "c1": h})
        muts.append(("put", key, value))
    c.txn_write(muts)

    def make_dag(ts):
        sel = DagSelect.from_table(table, ["id", "c0", "c1"])
        return sel.aggregate(
            [sel.col("c0")],
            [("count_star", None),
             ("sum", sel.col("c1"))]).build(start_ts=ts)

    serial = c.coprocessor(make_dag(c.tso()))
    assert serial["backend"] == "device", serial["backend"]
    expect = sorted(serial["rows"])
    assert sorted(
        [sum(1 for h in range(400) if h % 5 == g),
         sum(h for h in range(400) if h % 5 == g), g]
        for g in range(5)) == expect

    ts = c.tso()
    with cf.ThreadPoolExecutor(6) as ex:
        futs = [ex.submit(c.coprocessor, make_dag(ts)) for _ in range(6)]
        resps = [f.result(timeout=60) for f in futs]
    for r in resps:
        assert r["backend"] == "device"
        assert sorted(r["rows"]) == expect
        # per-request attribution survives the deferred fetch
        assert "time_detail" in r


def test_split_and_routing_over_network(cluster):
    from tikv_tpu.storage.txn_types import encode_key
    c = cluster["client"]
    c.put(b"srv-a", b"1")
    c.put(b"srv-z", b"2")
    right = c.split(b"srv-m")
    import time
    # the new right region reaches PD on its next heartbeat: poll with
    # a bound instead of a fixed sleep (racy on a loaded 1-core box —
    # PD transiently answers "no region" for the carved-off range)
    deadline = time.monotonic() + 10
    region_a = region_z = None
    while time.monotonic() < deadline:
        try:
            region_a = c.pd.get_region(encode_key(b"srv-a"))
            region_z = c.pd.get_region(encode_key(b"srv-z"))
            if region_a.id != region_z.id:
                break
        except Exception:   # noqa: BLE001 — transient routing gap
            pass
        time.sleep(0.05)
    assert region_a is not None and region_z is not None
    assert region_a.id != region_z.id
    # reads/writes still route correctly across the split
    assert c.get(b"srv-a") == b"1"
    assert c.get(b"srv-z") == b"2"
    c.put(b"srv-zz", b"3")
    assert c.get(b"srv-zz") == b"3"


def test_lease_reads_and_read_pool_over_network(cluster):
    """Server tier: repeated gets ride the leader lease (no log barrier
    per read) and flow through the read pool."""
    c = cluster["client"]
    c.put(b"lease-k", b"lv")
    import time
    time.sleep(0.3)             # heartbeat acks establish leases
    before = {s.node.store_id: s.node.raft_kv.lease_reads
              for s in cluster["servers"]}
    for _ in range(10):
        assert c.get(b"lease-k") == b"lv"
    lease_gain = sum(s.node.raft_kv.lease_reads -
                     before[s.node.store_id] for s in cluster["servers"])
    assert lease_gain >= 8, lease_gain
    assert sum(s.node.read_pool.served for s in cluster["servers"]) > 0


def test_store_status(cluster):
    c = cluster["client"]
    st = c.status(cluster["servers"][0].node.store_id)
    assert st["store_id"] == cluster["servers"][0].node.store_id
    assert st["regions"]


def test_gc_rpc(cluster):
    c = cluster["client"]
    for _ in range(3):
        c.put(b"gc-k", b"x")
    from tikv_tpu.server.client import StoreClient
    total = 0
    for s in c.pd.stores():
        total += StoreClient(s.address).call(
            "KvGC", {"safe_point": c.tso()})["removed"]
    assert total >= 2       # superseded versions dropped on the leader
    assert c.get(b"gc-k") == b"x"


def test_region_meta_consistent_across_stores(cluster):
    """Peers added via snapshot must learn the full region metadata —
    log-replay shells previously diverged (missing original peers)."""
    import time
    c = cluster["client"]
    c.put(b"meta-k", b"v")
    right = c.split(b"meta-m")
    time.sleep(0.4)
    views = {}
    for srv in cluster["servers"]:
        st = srv.node.status()
        for r in st["regions"]:
            rid = r["region"]["id"]
            peers = tuple(sorted((p["id"], p["store_id"])
                          for p in r["region"]["peers"]))
            views.setdefault(rid, set()).add(
                (peers, r["region"]["conf_ver"], r["region"]["version"]))
    for rid, view_set in views.items():
        assert len(view_set) == 1, f"region {rid} diverged: {view_set}"
        peers, _cv, _v = next(iter(view_set))
        assert len(peers) == 3, f"region {rid} missing peers: {peers}"


def test_region_cache_build_does_not_block_other_hits():
    """ADVICE r2: a slow columnar build for one region must not hold the
    global cache lock — concurrent hits for other regions proceed."""
    import threading
    import time as _time
    import tikv_tpu.copr.region_cache as rc

    real_build = rc.build_region_columnar
    gate = threading.Event()
    entered = threading.Event()

    def slow_build(snap, table_id, cols, read_ts):
        if getattr(snap, "_slow", False):
            entered.set()
            assert gate.wait(5.0)
        return real_build(snap, table_id, cols, read_ts)

    cache = rc.RegionColumnarCache()

    class FakeRegion:
        def __init__(self, rid):
            self.id = rid
            self.epoch = type("E", (), {"version": 1})()

    def make_snap(rid, slow):
        from tikv_tpu.engine.memory import MemoryEngine
        eng = MemoryEngine()
        snap = eng.snapshot()
        snap.region = FakeRegion(rid)
        snap.data_index = 7
        snap._slow = slow
        return snap

    from tikv_tpu.testing.fixture import Table, TableColumn
    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.datatype import FieldType
    table = Table(5, (TableColumn("id", 1, FieldType.long(not_null=True),
                                  is_pk_handle=True),
                      TableColumn("v", 2, FieldType.long())))
    dag = DagSelect.from_table(table, ["id", "v"]).build()

    orig = rc.build_region_columnar
    rc.build_region_columnar = slow_build
    try:
        t = threading.Thread(
            target=lambda: cache.get(make_snap(1, True), dag), daemon=True)
        t.start()
        assert entered.wait(5.0)
        # while region 1 builds, region 2 requests must complete
        t0 = _time.perf_counter()
        ent2 = cache.get(make_snap(2, False), dag)
        elapsed = _time.perf_counter() - t0
        assert ent2 is not None
        assert elapsed < 1.0, "unrelated request blocked behind a build"
        gate.set()
        t.join(5.0)
        assert not t.is_alive()
    finally:
        rc.build_region_columnar = orig


def test_check_leader_response_survives_wire(cluster):
    """Regression: the CheckLeader fan-out response used int region-id
    map keys, which msgpack's strict_map_key unpack REJECTS — every
    non-empty response failed client-side deserialization (harmless to
    the fire-and-forget fan-out, but each decode error logged and the
    noise destabilized timing-sensitive brownout runs).  The handler's
    output must round-trip through the real wire codec."""
    from tikv_tpu.server import wire
    from tikv_tpu.server.service import KvService

    node = cluster["servers"][0].node
    svc = KvService(node)
    peer = node.raft_store.peers[1]
    resp = svc.CheckLeader({"regions": [
        {"region_id": 1, "resolved_ts": node.pd.tso(),
         "applied_index": peer.applied_engine}]})
    assert resp["advanced"], resp       # non-empty: the failing shape
    assert wire.unpack(wire.pack(resp)) == resp


def test_per_request_tracker_details(cluster):
    """Every read RPC returns TimeDetail/ScanDetail built by the
    per-request tracker (components/tracker/src/lib.rs:16,32-40):
    wall/wait attribution plus phase decomposition, consistent with the
    reported total."""
    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.testing.fixture import encode_table_row, int_table

    c = cluster["client"]
    table = int_table(2, table_id=9077)
    muts = []
    for h in range(300):
        key, value = encode_table_row(table, h, {"c0": h % 3, "c1": h})
        muts.append(("put", key, value))
    c.txn_write(muts)

    sel = DagSelect.from_table(table, ["id", "c0", "c1"])
    dag = sel.aggregate([sel.col("c0")],
                        [("count_star", None)]).build(start_ts=c.tso())
    resp = c.coprocessor(dag)
    td, sd = resp["time_detail"], resp["scan_detail"]
    # totals: wait + process == total; every phase fits in the total
    assert td["total_rpc_wall_ms"] > 0
    assert td["wait_wall_ms"] >= 0
    assert abs(td["wait_wall_ms"] + td["process_wall_ms"]
               - td["total_rpc_wall_ms"]) < 0.01
    phases = td["phases_ms"]
    assert "snapshot" in phases and "columnar_cache" in phases
    # (what the client adds to a reply lies outside the root span)
    from tikv_tpu.utils.trace_vocab import OUTSIDE_ROOT
    assert sum(v for k, v in phases.items() if k not in OUTSIDE_ROOT) <= \
        td["total_rpc_wall_ms"] + 0.01
    # first query at this data version built the columnar cache
    assert td["labels"]["copr_cache"] in ("build", "hit")
    assert td["labels"]["backend"] == resp["backend"]
    if resp["backend"] == "device":
        assert "device_dispatch" in phases or "host_exec" in phases
    # the scan covered every row once
    assert sd["processed_versions"] == 300

    # warm repeat: cache hit labeled, still consistent.  A lifecycle
    # event racing the repeat (PD-driven leader churn on this shared
    # cluster under full-suite load) legitimately retires the line and
    # re-labels "build" — retry a couple of times for the hit
    for attempt in range(3):
        dag2 = sel.aggregate([sel.col("c0")],
                             [("count_star", None)]).build(start_ts=c.tso())
        resp2 = c.coprocessor(dag2)
        if resp2["time_detail"]["labels"]["copr_cache"] == "hit":
            break
    assert resp2["time_detail"]["labels"]["copr_cache"] == "hit"

    # point read: kv_read phase + 1 processed version
    key, value = encode_table_row(table, 1, {"c0": 1, "c1": 1})
    r = c._call_leader(key, "KvGet", {"key": key, "version": c.tso()})
    assert "kv_read" in r["time_detail"]["phases_ms"]
    assert r["scan_detail"]["processed_versions"] == 1
