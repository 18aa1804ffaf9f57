"""Multi-chip scale-out: sharded-mesh parity + placement (tier-1).

The conftest forces an 8-device virtual CPU mesh
(xla_force_host_platform_device_count), so every sharded code path —
row-sharded feeds, per-shard partial aggregation with the psum /
all-to-all tree-reduce, shard-concatenable selection routing, sharded
delta patching — runs against the REAL shard_map lowering and is
asserted bit-identical to the single-device and host backends.  The
fused Pallas rung needs real TPU lowering: its sharded wrap is pinned
in interpret mode by tests/test_pallas_hash_interpret.py and on
hardware by chip_smoke.py; these tests pin the semantics every rung
must agree on.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tikv_tpu.datatype import Column, EvalType, FieldType
from tikv_tpu.device import DeviceRunner
from tikv_tpu.device.feed import anchor as feed_anchor
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.parallel import make_mesh, mesh_slices, parse_mesh_shape
from tikv_tpu.parallel.mesh import _factor2
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn


def _table():
    return Table(42, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("k", 2, FieldType.long()),
        TableColumn("v", 3, FieldType.long()),
    ))


def _snap(table, n, seed, key_hi=500, null_frac=0.0, sparse=False):
    rng = np.random.default_rng(seed)
    if sparse:
        domain = rng.integers(0, 1 << 62, 37).astype(np.int64)
        k = rng.choice(domain, n)
    else:
        k = rng.integers(0, key_hi, n).astype(np.int64)
    v = rng.integers(-50_000, 50_000, n).astype(np.int64)
    kok = rng.random(n) > null_frac if null_frac else np.ones(n, np.bool_)
    vok = rng.random(n) > null_frac if null_frac else np.ones(n, np.bool_)
    return ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"k": Column(EvalType.INT, k, kok),
         "v": Column(EvalType.INT, v, vok)})


@pytest.fixture(scope="module")
def r8():
    return DeviceRunner(mesh=make_mesh(jax.devices()),
                        chunk_rows=8 * 64)


@pytest.fixture(scope="module")
def r1():
    return DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                        chunk_rows=64)


def _rows(result):
    return sorted(result.rows())


def _parity(dag, snap, r8, r1):
    a = r8.handle_request(dag, snap)
    b = r1.handle_request(dag, snap)
    h = BatchExecutorsRunner(dag, snap).handle_request()
    assert _rows(a) == _rows(b) == _rows(h)
    return a


# ------------------------------------------------------------- mesh shapes


def test_factor2_shapes():
    assert _factor2(1) == (1, 1)
    assert _factor2(4) == (2, 2)
    assert _factor2(8) == (2, 4)
    assert _factor2(12) == (3, 4)
    assert _factor2(16) == (4, 4)
    # a PRIME device count has no nontrivial split: the mesh
    # degenerates to one row with every device on the tile axis
    assert _factor2(7) == (1, 7)
    assert _factor2(13) == (1, 13)


def test_parse_mesh_shape():
    assert parse_mesh_shape(None) is None
    assert parse_mesh_shape("") is None
    assert parse_mesh_shape("2x4") == (2, 4)
    assert parse_mesh_shape("2X4") == (2, 4)
    assert parse_mesh_shape("4,2") == (4, 2)
    assert parse_mesh_shape((8, 1)) == (8, 1)
    for bad in ("2x", "x4", "2x4x1", "axb", "0x8", [8]):
        with pytest.raises(ValueError):
            parse_mesh_shape(bad)


def test_make_mesh_honors_explicit_shape():
    mesh = make_mesh(jax.devices(), shape=parse_mesh_shape("4x2"))
    assert mesh.devices.shape == (4, 2)
    assert len(mesh_slices(mesh)) == 8
    with pytest.raises(ValueError):
        make_mesh(jax.devices(), shape=(3, 2))   # 6 != 8 devices


# ----------------------------------------------------- aggregation parity


def test_hash_agg_sharded_parity_randomized(r8, r1):
    """Sharded hash agg (per-shard partials + psum / all-to-all bucket
    tree-reduce for min/max) vs single-device vs host, NULL-heavy."""
    table = _table()
    for seed in range(4):
        snap = _snap(table, 9000 + 512 * seed, seed, key_hi=700,
                     null_frac=0.07 if seed % 2 else 0.0)
        sel = DagSelect.from_table(table, ["id", "k", "v"])
        dag = sel.where(sel.col("v") > 0).aggregate(
            [sel.col("k")],
            [("count_star", None), ("sum", sel.col("v")),
             ("min", sel.col("v")), ("max", sel.col("v"))]).build()
        _parity(dag, snap, r8, r1)


def test_hash_agg_sparse_keys_sharded_parity(r8, r1):
    """Dictionary-encoded sparse key domain: the recode is computed
    once from host truth (a GLOBAL dictionary — no per-shard merge
    needed) and the dense slot column rides the sharded feed."""
    table = _table()
    snap = _snap(table, 8192, 11, sparse=True)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate(
        [sel.col("k")],
        [("count_star", None), ("sum", sel.col("v")),
         ("max", sel.col("v"))]).build()
    _parity(dag, snap, r8, r1)


def test_simple_agg_and_topn_sharded_parity(r8, r1):
    table = _table()
    snap = _snap(table, 7000, 23, null_frac=0.1)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate(
        [], [("count_star", None), ("sum", sel.col("v")),
             ("min", sel.col("v")), ("max", sel.col("v")),
             ("avg", sel.col("v"))]).build()
    _parity(dag, snap, r8, r1)
    sel2 = DagSelect.from_table(table, ["id", "k", "v"])
    dag_topn = sel2.order_by(sel2.col("v"), desc=True,
                             limit=37).build()
    a = r8.handle_request(dag_topn, snap)
    b = r1.handle_request(dag_topn, snap)
    h = BatchExecutorsRunner(dag_topn, snap).handle_request()
    assert [r[-1] for r in a.rows()] == [r[-1] for r in b.rows()] == \
        [r[-1] for r in h.rows()]


def test_hash_agg_sharded_emits_shard_merge_phase(r8):
    """The cross-shard tree-reduce is observable: a sharded hash agg
    with order-sensitive states reports the shard_merge tracker
    phase."""
    from tikv_tpu.utils import tracker
    table = _table()
    snap = _snap(table, 6000, 31, key_hi=900)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate(
        [sel.col("k")],
        [("count_star", None), ("min", sel.col("v"))]).build()
    r8.handle_request(dag, snap)                 # warm
    tr, tok = tracker.install()
    try:
        r8.handle_request(dag, snap)
    finally:
        tracker.uninstall(tok)
    td = tr.time_detail()
    assert "shard_merge" in td["phases_ms"], td["phases_ms"]


# ------------------------------------------------------- selection routing


def test_selection_mask_and_index_routes_sharded(r8, r1):
    """Sharded selection routing: the always-correct packed-mask route
    cold, then the EWMA warms and a rare predicate flips to the
    on-device index compaction — per-shard nonzero with global row
    offsets — with exact parity throughout."""
    n = 1 << 17
    table = _table()
    rng = np.random.default_rng(5)
    k = rng.integers(0, 100, n).astype(np.int64)
    v = rng.integers(0, 1_000_000, n).astype(np.int64)
    ones = np.ones(n, np.bool_)
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"k": Column(EvalType.INT, k, ones),
         "v": Column(EvalType.INT, v, ones)})
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.where(sel.col("v") < 100).build()      # ~0.01% selected
    want = None
    for _ in range(5):
        a = r8.handle_request(dag, snap)
        if want is None:
            h = BatchExecutorsRunner(dag, snap).handle_request()
            want = _rows(h)
        assert _rows(a) == want
    routes = r8.selection_stats()["routes"]
    assert routes.get("mask", 0) >= 1, routes        # cold route
    assert routes.get("index", 0) >= 1, routes       # warm route
    b = r1.handle_request(dag, snap)
    assert _rows(b) == want


# -------------------------------------------------- delta-patched feeds


def _wide_table(n_cols=17, table_id=7801):
    from tikv_tpu.testing.fixture import int_table
    return int_table(n_cols, table_id=table_id)


@pytest.fixture(scope="module")
def cluster_rig():
    from tikv_tpu.copr.delta import DeltaSink
    from tikv_tpu.copr.region_cache import RegionColumnarCache
    from tikv_tpu.testing.cluster import Cluster
    c = Cluster(n_stores=1)
    c.bootstrap()
    c.start()
    sink = DeltaSink(max_entries=4096, max_rows=1 << 16)
    c.stores[1].coprocessor_host.register(sink)
    cache = RegionColumnarCache(capacity=8, delta_source=sink)
    return {"c": c, "cache": cache}


def _cluster_write(c, table, rows):
    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.codec.row import encode_row
    c.txn_write([("put", table_record_key(table.table_id, h),
                  encode_row(payload)) for h, payload in rows])


def _cluster_ent(rig, table, dag):
    from tikv_tpu.kv.engine import SnapContext
    snap = rig["c"].kvs[1].snapshot(SnapContext(region_id=1))
    return rig["cache"].get(snap, dag)


def test_sharded_delta_patched_feed_parity(cluster_rig, r8, r1):
    """Churn on a SHARDED feed rides delta_apply + feed_patch — no
    re-upload — across NULL-heavy and wide (>15 col, map16 row header)
    shapes, with parity vs single-device and host on every version."""
    from tikv_tpu.utils import tracker
    table = _wide_table(17, table_id=7801)
    cols = [f"c{i}" for i in range(17)]
    # NULL-heavy: odd handles omit the tail columns entirely
    rows = []
    for h in range(600):
        payload = {2 + i: h * (i + 1) for i in range(17 if h % 2 else 9)}
        rows.append((h, payload))
    _cluster_write(cluster_rig["c"], table, rows)

    def mk_dag(ts):
        s = DagSelect.from_table(table, ["id"] + cols)
        return s.aggregate(
            [s.col("c0")],
            [("count_star", None), ("sum", s.col("c1")),
             ("min", s.col("c16"))]).build(start_ts=ts)

    dag = mk_dag(cluster_rig["c"].pd.tso())
    ent = _cluster_ent(cluster_rig, table, dag)
    for r in (r8, r1):
        a = r.handle_request(dag, ent)
        h = BatchExecutorsRunner(dag, ent).handle_request()
        assert _rows(a) == _rows(h)

    # point append + update → both runners must PATCH, not re-upload
    _cluster_write(cluster_rig["c"], table,
                   [(600, {2 + i: 7 * (i + 1) for i in range(17)}),
                    (3, {2 + i: -5 for i in range(17)})])
    dag2 = mk_dag(cluster_rig["c"].pd.tso())
    ent2 = _cluster_ent(cluster_rig, table, dag2)
    assert ent2.feed_lineage is ent.feed_lineage
    host2 = _rows(BatchExecutorsRunner(dag2, ent2).handle_request())
    for r in (r8, r1):
        tr, tok = tracker.install()
        try:
            a = r.handle_request(dag2, ent2)
        finally:
            tracker.uninstall(tok)
        assert _rows(a) == host2
        td = tr.time_detail()
        assert td["labels"].get("device_feed") == "patch", \
            (td["labels"], "sharded feeds must delta-patch in place")
        assert "feed_upload" not in td["phases_ms"]


def test_sharded_tombstoned_feed_parity(cluster_rig, r8, r1):
    """Deletes (alive-mask tombstones) keep every backend exact; the
    sharded runner may rebuild its feed (structural patch) but must
    not produce a wrong answer."""
    from tikv_tpu.codec.keys import table_record_key
    table = _wide_table(3, table_id=7802)
    _cluster_write(cluster_rig["c"], table,
                   [(h, {2: h % 4, 3: h, 4: -h}) for h in range(300)])
    def mk_dag(ts):
        mk = DagSelect.from_table(table, ["id", "c0", "c1", "c2"])
        return mk.aggregate(
            [mk.col("c0")],
            [("count_star", None), ("sum", mk.col("c1")),
             ("max", mk.col("c2"))]).build(start_ts=ts)

    dag = mk_dag(cluster_rig["c"].pd.tso())
    ent = _cluster_ent(cluster_rig, table, dag)
    a = r8.handle_request(dag, ent)
    assert _rows(a) == _rows(
        BatchExecutorsRunner(dag, ent).handle_request())
    cluster_rig["c"].txn_write([
        ("delete", table_record_key(table.table_id, h), None)
        for h in (7, 8, 150)])
    dag2 = mk_dag(cluster_rig["c"].pd.tso())
    ent2 = _cluster_ent(cluster_rig, table, dag2)
    host = _rows(BatchExecutorsRunner(dag2, ent2).handle_request())
    for r in (r8, r1):
        assert _rows(r.handle_request(dag2, ent2)) == host


# ------------------------------------------------------------ failpoints


def test_shard_launch_failpoint_degrades_whole_plan(r8):
    """device::shard_launch (one shard's dispatch fails): the WHOLE
    plan degrades to the host pipeline — no partial per-shard answer —
    and the dispatch lock is released on the degrade path (the
    launch-order-inversion lock must not wedge; runner.py dispatch
    serialization comment)."""
    from tikv_tpu.utils import failpoint, tracker
    table = _table()
    snap = _snap(table, 5000, 77, key_hi=300)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate(
        [sel.col("k")],
        [("count_star", None), ("sum", sel.col("v"))]).build()
    host = _rows(BatchExecutorsRunner(dag, snap).handle_request())
    failpoint.cfg("device::shard_launch", "return")
    try:
        tr, tok = tracker.install()
        try:
            got = r8.handle_request(dag, snap)
        finally:
            tracker.uninstall(tok)
        assert _rows(got) == host
        # degraded request never dispatched on device
        assert "device_dispatch" not in tr.time_detail()["phases_ms"]
        # the dispatch lock was released on the degrade path
        assert r8._dispatch_mu.acquire(timeout=1), \
            "dispatch lock wedged after shard_launch degrade"
        r8._dispatch_mu.release()
    finally:
        failpoint.remove("device::shard_launch")
    # recovered: the next request rides the device again
    tr, tok = tracker.install()
    try:
        got = r8.handle_request(dag, snap)
    finally:
        tracker.uninstall(tok)
    assert _rows(got) == host
    assert "device_dispatch" in tr.time_detail()["phases_ms"]


def test_shard_launch_failpoint_with_concurrent_inflight(r8):
    """A one-shot shard_launch fault racing a healthy request: exactly
    one degrades, both answer correctly, and later dispatches are
    unaffected."""
    import threading

    from tikv_tpu.utils import failpoint
    table = _table()
    snap = _snap(table, 5000, 78, key_hi=300)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate(
        [sel.col("k")],
        [("count_star", None), ("sum", sel.col("v"))]).build()
    host = _rows(BatchExecutorsRunner(dag, snap).handle_request())
    r8.handle_request(dag, snap)                 # warm kernels
    failpoint.cfg("device::shard_launch", "1*return->off")
    results = [None, None]

    def run(i):
        results[i] = _rows(r8.handle_request(dag, snap))

    try:
        ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert results[0] == host and results[1] == host
    finally:
        failpoint.remove("device::shard_launch")
    assert _rows(r8.handle_request(dag, snap)) == host


# ------------------------------------------------------------- placement


def test_placement_spreads_anchors_and_rebalances():
    from tikv_tpu.utils import metrics as m
    runner = DeviceRunner(mesh=make_mesh(jax.devices()),
                          chunk_rows=8 * 64, placement=True,
                          placement_rows=1 << 16)
    placer = runner.placer
    assert placer is not None and len(placer) == 8
    table = _table()
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate(
        [sel.col("k")], [("count_star", None),
                         ("sum", sel.col("v"))]).build()
    snaps = [_snap(table, 2048, 200 + i, key_hi=40) for i in range(9)]
    host = [
        _rows(BatchExecutorsRunner(dag, s).handle_request())
        for s in snaps]
    for i, s in enumerate(snaps):
        assert _rows(runner.handle_request(dag, s)) == host[i]
    st = placer.stats()
    # 9 anchors over 8 slices: every slice gets at least one
    assert st["places"] == 9
    assert all(sl["placed_anchors"] >= 1 for sl in st["slices"]), st
    # ...and the bytes live where the bookkeeping says: every chip
    # holds a feed, not all nine the process default device (a single-
    # device runner's uncommitted uploads land there unless pinned)
    by_dev = runner.hbm_stats()["resident_bytes_by_device"]
    assert sorted(by_dev) == [d.id for d in jax.devices()] and \
        all(by_dev.values()), by_dev
    # two anchors share one slice (the tie-break slice); heat the one
    # that was placed FIRST, then rebalance: the COLD co-tenant moves
    doubled = max(range(8),
                  key=lambda i: st["slices"][i]["placed_anchors"])
    hot = next(i for i, s in enumerate(snaps)
               if placer.owner(feed_anchor(s)) is
               placer.slices[doubled])
    for _ in range(30):
        runner.handle_request(dag, snaps[hot])
    moved = placer.rebalance()
    assert moved and placer.stats()["moves"] == 1
    # parity survives the move (feed rebuilds on the new slice)
    for i, s in enumerate(snaps):
        assert _rows(runner.handle_request(dag, s)) == host[i]
    # a big feed bypasses placement and shards over the whole mesh
    big = _snap(table, 1 << 16, 300, key_hi=40)
    assert _rows(runner.handle_request(dag, big)) == _rows(
        BatchExecutorsRunner(dag, big).handle_request())
    assert placer.stats()["whole_mesh_routes"] >= 1
    # per-slice occupancy counters are published
    runner.placer.publish_metrics()
    assert m.DEVICE_SLICE_RESIDENT_BYTES.labels("0").value >= 0
    # drop fans out to slices and forgets the placement
    anchor = feed_anchor(snaps[0])
    assert runner.drop_feed(anchor) > 0
    assert placer.owner(anchor) is None


def test_mesh_stats_rollup():
    runner = DeviceRunner(mesh=make_mesh(jax.devices(),
                                         shape=parse_mesh_shape("4x2")),
                          chunk_rows=8 * 64, placement=True)
    ms = runner.mesh_stats()
    assert ms["shape"] == {"range": 4, "tile": 2}
    assert ms["n_devices"] == 8
    assert "placement" in ms and len(ms["placement"]["slices"]) == 8
    from tikv_tpu.utils.metrics import DEVICE_MESH_SHARDS
    assert DEVICE_MESH_SHARDS.value == 8


# ------------------------------------- the scalar cache on a mesh (PR 30)
#
# Every sharded launch site takes its scalars (row count, key base,
# predicate constants) from the runner's one cache and declares them
# ``P()``: on a mesh the cache commits them replicated over the
# runner's devices, so the jitted call takes them as they lie.


@pytest.fixture(scope="module")
def r4():
    return DeviceRunner(mesh=make_mesh(jax.devices()[:4],
                                       shape=parse_mesh_shape("2x2")),
                        chunk_rows=4 * 64)


def _plan_of(kind, table):
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    if kind == "hash_twolevel":
        return sel.aggregate([sel.col("k")], [
            ("count_star", None), ("sum", sel.col("v"))]).build()
    if kind == "hash_scatter":
        return sel.aggregate([sel.col("k")], [
            ("count_star", None), ("min", sel.col("v")),
            ("max", sel.col("v"))]).build()
    if kind == "simple":
        return sel.aggregate([], [
            ("count_star", None), ("sum", sel.col("v")),
            ("avg", sel.col("v"))]).build()
    if kind == "topn":
        return sel.order_by(sel.col("v"), desc=True, limit=37).build()
    assert kind == "scan_sel_mask"
    return sel.where(sel.col("v") < -49_000).build()


@pytest.mark.parametrize("kind", ["hash_twolevel", "hash_scatter", "simple",
                                  "topn", "scan_sel_mask"])
def test_replicated_scalars_serve_every_sharded_launch_site(kind, r4, r1):
    """The XLA stand-in bodies, TopN and a selection on a 2x2 mesh
    answer as one device and the host do; the class launched is the
    one named; whatever the request cached is replicated over the four
    devices; and a warm request launches with transfers to a device
    and between devices disallowed (the fetch is outside the guards,
    as it is outside the dispatch lock)."""
    table = _table()
    snap = _snap(table, 7000, 41, key_hi=300)
    want = _rows(BatchExecutorsRunner(_plan_of(kind, table),
                                      snap).handle_request())
    if kind == "topn":      # ties order freely: compare the sort key
        want = [r[-1] for r in want]

    def rows(result):
        got = _rows(result)
        return [r[-1] for r in got] if kind == "topn" else got
    assert rows(r1.handle_request(_plan_of(kind, table), snap)) == want
    assert rows(r4.handle_request(_plan_of(kind, table), snap)) == want
    before = r4.mesh_stats()["scalar_cache"]
    with jax.transfer_guard_device_to_device("disallow"), \
            jax.transfer_guard_host_to_device("disallow"):
        pending = r4.handle_request(_plan_of(kind, table), snap,
                                    deferred=True)
    assert rows(pending.result()) == want
    after = r4.mesh_stats()["scalar_cache"]
    assert after["uploads"] == before["uploads"]
    assert after["hits"] > before["hits"]
    assert r4.flight_recorder.items()[-1]["compile_class"] == kind
    assert r4.flight_recorder.items()[-1]["shards"] == 4
    four = set(r4._mesh.devices.flat)
    assert r4._scalar_cache
    for key, arr in r4._scalar_cache.items():
        assert arr.committed and arr.devices() == four, key
        assert arr.sharding.is_equivalent_to(r4._repl, arr.ndim), key


def test_scalar_cache_lru_evicts_at_256_on_a_mesh(r4):
    """The bound a live server needs under writes (a new row count a
    snapshot) holds on a mesh: 256 values stay, the oldest go first,
    and a value that went is put again, replicated."""
    base = 1 << 40                  # values no other test asks for
    for i in range(300):
        r4._cached_scalar(base + i, jnp.int64)
    cache = r4._scalar_cache
    assert len(cache) == 256
    key = lambda i: (base + i, str(jnp.int64))     # noqa: E731
    assert key(0) not in cache and key(43) not in cache
    assert key(44) in cache and key(299) in cache
    before = r4.mesh_stats()["scalar_cache"]
    again = r4._cached_scalar(base, jnp.int64)
    kept = r4._cached_scalar(base + 299, jnp.int64)
    after = r4.mesh_stats()["scalar_cache"]
    assert (after["uploads"], after["hits"]) == \
        (before["uploads"] + 1, before["hits"] + 1)
    assert len(cache) == 256 and key(44) not in cache
    assert int(again) == base and int(kept) == base + 299
    assert again.devices() == set(r4._mesh.devices.flat)
    assert again.sharding.is_equivalent_to(r4._repl, 0)
