"""DECIMAL and DATE columns from the row codec to the device's planes.

A DECIMAL column whose FieldType fixes its scale is held SCALED (an int64
of value x 10^scale, ``Column.frac``) from the native columnar build on;
a DATE column reaches the fused kernel as an int32 plane; decimal RPN is
lowered to integer RPN (device/lowering.py) and SUM comes back a DECIMAL.
The host pipeline, which computes on ``Decimal`` objects, is the
behavioural reference throughout.  Also here: the loader's native SST
encoder against ``codec/row.encode_row``, byte for byte, for every column
kind of TPC-H's lineitem."""

import decimal
import functools

import numpy as np
import pytest

import jax

import tikv_tpu.copr.region_cache as rc
import tikv_tpu.native as nv
from tikv_tpu import sst_importer
from tikv_tpu.codec.row import decode_row, encode_row
from tikv_tpu.datatype import (
    Column, EvalType, FieldType, FieldTypeFlag, FieldTypeTp,
)
from tikv_tpu.datatype.mydecimal import from_scaled, to_scaled
from tikv_tpu.datatype.tile import date_plane
from tikv_tpu.datatype.time import pack_datetime
from tikv_tpu.device import DeviceRunner, lowering, pallas_hash
from tikv_tpu.engine.memory import MemoryEngine
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.expr import Expr
from tikv_tpu.kv.engine import LocalEngine
from tikv_tpu.parallel import make_mesh
from tikv_tpu.storage import Storage
from tikv_tpu.storage.txn import commands as cmds
from tikv_tpu.storage.txn.actions import Mutation
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn, encode_table_row

D = decimal.Decimal
DEC2 = FieldType(tp=FieldTypeTp.NEW_DECIMAL, flen=15, decimal=2)
DEC4 = FieldType(tp=FieldTypeTp.NEW_DECIMAL, flen=12, decimal=4)
DATE = FieldType(tp=FieldTypeTp.DATE)
DATETIME = FieldType(tp=FieldTypeTp.DATETIME)
PK = TableColumn("id", 1, FieldType.long(not_null=True), is_pk_handle=True)

native_only = pytest.mark.skipif(
    nv.mvcc_build_columnar is None or nv.build_mvcc_sst is None,
    reason="native extension not compiled")


# ------------------------------------------------- the scaled form


def test_scaled_round_trip_and_its_refusals():
    assert to_scaled(D("0.06"), 2) == 6
    assert to_scaled(D("-12.5"), 2) == -1250
    assert to_scaled(D("1.230"), 2) == 123          # trailing zeros fit
    assert to_scaled(D("1.234"), 2) is None         # beyond the scale
    assert to_scaled(D("1e30"), 2) is None          # beyond int64
    assert to_scaled(D("NaN"), 2) is None
    assert from_scaled(6, 2) == D("0.06")
    assert str(from_scaled(1234500, 4)) == "123.4500"
    assert str(from_scaled(-5, 0)) == "-5"


def test_a_scaled_column_is_the_same_column_to_the_host():
    col = Column(EvalType.DECIMAL, np.array([6, -1250, 0], np.int64),
                 np.array([True, True, False]), 2)
    assert col.to_list() == [D("0.06"), D("-12.50"), None]
    host = col.unscaled()
    assert host.frac is None and host.values.dtype == object
    assert list(host.values[:2]) == [D("0.06"), D("-12.50")]
    assert col.slice(1, 3).frac == col.take(np.array([0])).frac == 2
    both = Column.concat([col, host])       # mixed forms: the host's
    assert both.frac is None and len(both) == 6
    assert Column.concat([col, col]).frac == 2


def test_date_plane_is_lossless_and_keeps_the_order():
    cores = pack_datetime(np.array([1992, 1994, 1994, 8191]),
                          np.array([1, 1, 12, 12]),
                          np.array([1, 1, 31, 31]))
    plane = date_plane(cores)
    assert plane.dtype == np.int32
    assert list(np.argsort(plane, kind="stable")) == [0, 1, 2, 3]
    assert np.array_equal(plane.astype(np.uint64) << np.uint64(41), cores)


# ------------------------------------------------- the loader's encoder


LINEITEM_KINDS = {
    "bigint": lambda n: np.array([0, 1, -7, 2 ** 31, 5999999][:n], np.int64),
    "decimal2": lambda n: sst_importer.decimal_column(
        np.array([0, 6, -1250, 1049495000, 99][:n], np.int64), 2),
    "decimal0": lambda n: sst_importer.decimal_column(
        np.array([0, 6, -1250, 10 ** 17, 99][:n], np.int64), 0),
    "date": lambda n: np.asarray(pack_datetime(
        np.array([1992, 1994, 1998, 1, 8191][:n]), 3, 7)).astype(np.int64),
    "char": lambda n: sst_importer.bytes_column(
        b"RANF" + b"x" * 300, np.array([0, 1, 2, 3, 4, 304][:n + 1])),
    "varchar": lambda n: sst_importer.bytes_column(
        b"furiously ironic" + b"" + b"a",
        np.array([0, 9, 16, 16, 17, 17][:n + 1])),
}


def _host_value(kind: str, vals, i: int):
    if isinstance(vals, tuple) and vals[0] == "decimal":
        return from_scaled(int(vals[1][i]), vals[2])
    if isinstance(vals, tuple):
        return bytes(vals[1][int(vals[2][i]):int(vals[2][i + 1])])
    return int(vals[i])


@native_only
def test_native_sst_rows_are_byte_identical_to_encode_row():
    """Every lineitem column kind, NULLs among them: the record the
    native encoder writes holds exactly ``encode_row``'s bytes."""
    n = 5
    cols, host_rows = [], [dict() for _ in range(n)]
    valid = np.array([1, 1, 0, 1, 1], bool)
    for cid, (kind, make) in enumerate(LINEITEM_KINDS.items(), start=2):
        vals = make(n)
        mask = valid if kind in ("decimal2", "varchar") else None
        cols.append((cid, vals, mask))
        for i in range(n):
            host_rows[i][cid] = None if mask is not None and not mask[i] \
                else _host_value(kind, vals, i)
    # 16 columns, as lineitem has: the map16 header
    for cid in range(8, 18):
        cols.append((cid, np.arange(n, dtype=np.int64) * cid, None))
        for i in range(n):
            host_rows[i][cid] = i * cid
    blob = sst_importer.fast_mvcc_table_sst(
        77, np.arange(n, dtype=np.int64), cols, commit_ts=100)
    cfs = sst_importer.read_sst_cf(blob)
    got = {}
    for cf, (keys, vals) in cfs.items():
        got[cf] = list(vals)
    # the long row (300 bytes of CHAR) spills to CF_DEFAULT whole
    payloads = []
    for rec in got["write"]:
        if rec[:1] == b"P" and b"v" in rec[:12]:
            payloads.append(rec[rec.index(b"v") + 2:])
    payloads += got.get("default", [])
    want = sorted(encode_row(r) for r in host_rows)
    assert sorted(bytes(p) for p in payloads) == want
    assert len(got.get("default", [])) == 1
    assert decode_row(want[0]) == decode_row(want[0])


@native_only
def test_native_and_interpreted_encoders_make_the_same_table(monkeypatch):
    n = 4
    cols = [(cid, make(n), None) for cid, (_k, make) in
            enumerate(LINEITEM_KINDS.items(), start=2)]
    native = sst_importer.read_sst_cf(sst_importer.fast_mvcc_table_sst(
        78, np.arange(n, dtype=np.int64), cols, commit_ts=50))
    monkeypatch.setattr(nv, "build_mvcc_sst", None)
    plain = sst_importer.read_sst_cf(sst_importer.fast_mvcc_table_sst(
        78, np.arange(n, dtype=np.int64), cols, commit_ts=50))
    assert {cf: (list(k), list(v)) for cf, (k, v) in native.items()} == \
        {cf: (list(k), list(v)) for cf, (k, v) in plain.items()}


def test_a_loader_can_ask_what_the_encoder_takes():
    assert {"int", "float", "decimal", "bytes"} <= \
        set(sst_importer.NATIVE_COLUMN_KINDS)


# ------------------------------------------------- the columnar build


WIDE = Table(7801, (
    PK,
    TableColumn("k", 2, FieldType.long()),
    TableColumn("qty", 3, DEC2),
    TableColumn("price", 4, DEC2),
    TableColumn("note", 5, FieldType.var_char()),
    TableColumn("ship", 6, DATE),
    TableColumn("rate", 7, DEC4)))


def _commit(storage, ts, muts):
    storage.sched_txn_command(cmds.Prewrite(muts, muts[0].key, ts))
    storage.sched_txn_command(
        cmds.Commit([m.key for m in muts], ts, ts + 1))
    return ts + 10


def _wide_engine(rows):
    eng = MemoryEngine()
    storage = Storage(LocalEngine(eng))
    muts = [Mutation("put", *encode_table_row(WIDE, h, row))
            for h, row in enumerate(rows)]
    ts = _commit(storage, 10, muts)
    return eng, ts


def _wide_rows(n=300):
    rng = np.random.default_rng(5)
    rows = []
    for h in range(n):
        rows.append({
            "k": h % 7,
            "qty": None if h % 11 == 0 else
            D(int(rng.integers(100, 5001))).scaleb(-2),
            "price": D(int(rng.integers(-10 ** 9, 10 ** 9))).scaleb(-2),
            "note": b"x" * int(rng.integers(0, 40)),
            "ship": int(pack_datetime(1992 + h % 7, 1 + h % 12,
                                      1 + h % 28)),
            "rate": D(int(rng.integers(0, 10 ** 6))).scaleb(-4)})
    return rows


@native_only
def test_native_build_scales_what_it_is_asked_for_and_skips_the_rest():
    """Two of six columns requested: the DECIMAL one comes back scaled
    with its NULLs, the datums of the other four (DECIMAL, bytes, int,
    date) are read past, and the interpreted build agrees."""
    rows = _wide_rows()
    eng, ts = _wide_engine(rows)
    snap = eng.snapshot()
    infos = [WIDE.column_info("id"), WIDE.column_info("qty"),
             WIDE.column_info("ship")]
    nat = rc._build_native(snap, WIDE.table_id, infos, ts + 100)
    assert nat is not None, "native path refused the schema"
    tbl, _safe = nat
    qty = tbl.columns[3]
    assert qty.frac == 2 and qty.values.dtype == np.int64
    saved = nv.mvcc_build_columnar
    nv.mvcc_build_columnar = None
    try:
        ref, _s, _l = rc.build_region_columnar(snap, WIDE.table_id, infos,
                                               ts + 100)
    finally:
        nv.mvcc_build_columnar = saved
    assert ref.columns[3].frac is None          # the reference: objects
    assert qty.to_list() == ref.columns[3].to_list() == \
        [r["qty"] for r in rows]
    assert np.array_equal(tbl.columns[6].values, ref.columns[6].values)
    out = nv.mvcc_build_columnar(
        *snap.range_cf("write", *_table_range())[:2], ts + 100,
        snap.range_cf("write", *_table_range())[2], (3,), (4,), (2,))
    assert out["skipped_datums"] == 5 * len(rows)


def _table_range():
    from tikv_tpu.codec.keys import table_record_range
    from tikv_tpu.storage.txn_types import encode_key
    lo, hi = table_record_range(WIDE.table_id)
    return encode_key(lo), encode_key(hi)


@native_only
def test_a_value_beyond_the_declared_scale_takes_the_interpreted_path():
    rows = _wide_rows(50)
    rows[17]["qty"] = D("1.234")        # DECIMAL(15,2) cannot hold it
    eng, ts = _wide_engine(rows)
    snap = eng.snapshot()
    infos = [WIDE.column_info("id"), WIDE.column_info("qty")]
    assert rc._build_native(snap, WIDE.table_id, infos, ts + 100) is None
    tbl, _s, _l = rc.build_region_columnar(snap, WIDE.table_id, infos,
                                           ts + 100)
    assert tbl.columns[3].frac is None
    assert tbl.columns[3].to_list()[17] == D("1.234")
    # ... in a column nobody asked for it is read past
    only_price = [WIDE.column_info("id"), WIDE.column_info("price")]
    assert rc._build_native(snap, WIDE.table_id, only_price,
                            ts + 100) is not None


def test_a_line_scales_the_rows_written_to_it_or_asks_for_a_rebuild():
    tbl = ColumnarTable.from_arrays(WIDE, np.arange(3), {
        "qty": Column(EvalType.DECIMAL, np.array([1, 2, 3], np.int64),
                      np.ones(3, bool), 2)})
    state = rc._LineState(WIDE.table_id, [WIDE.column_info("id"),
                                          WIDE.column_info("qty")],
                          tbl, 5, 5, [])
    assert state.col_frac == {3: 2}
    assert state.scaled_payload({3: D("17.00"), 2: 9}) == {3: 1700, 2: 9}
    assert state.scaled_payload({3: None}) == {3: None}
    assert state.scaled_payload({3: D("0.001")}) is None    # → rebuild
    assert state.publish()._tbl.columns[3].frac == 2


# ------------------------------------------------- the device's answers


def _table(cols: dict, n: int, seed: int = 1):
    """An in-memory snapshot: {name: (FieldType, Column)}."""
    table = Table(8800 + seed, (PK,) + tuple(
        TableColumn(name, cid, ft) for cid, (name, (ft, _c)) in
        enumerate(cols.items(), start=2)))
    snap = ColumnarTable.from_arrays(
        table, np.arange(n), {name: c for name, (_ft, c) in cols.items()})
    return table, snap


def _dec(values, frac=2, valid=None):
    v = np.asarray(values, np.int64)
    return Column(EvalType.DECIMAL, v,
                  np.ones(len(v), bool) if valid is None else valid, frac)


def _c(x):
    return Expr.const(D(x), EvalType.DECIMAL)


def _both(runner, dag, snap):
    got = runner.handle_request(dag, snap)
    want = BatchExecutorsRunner(dag, snap).handle_request()
    return got.rows(), want.rows(), got


@pytest.fixture(scope="module")
def runner():
    return DeviceRunner(mesh=make_mesh(jax.devices()[:1]))


def _launched(runner) -> list:
    return [e["compile_class"] for e in runner.flight_recorder.items()]


def test_sum_of_a_product_with_nulls_is_exact(runner):
    n = 4000
    rng = np.random.default_rng(2)
    valid = rng.random(n) > 0.1
    table, snap = _table({
        "a": (DEC2, _dec(rng.integers(-10 ** 6, 10 ** 6, n), 2, valid)),
        "b": (DEC4, _dec(rng.integers(0, 10 ** 5, n), 4)),
        "g": (FieldType.long(), Column(
            EvalType.INT, rng.integers(0, 9, n), np.ones(n, bool)))}, n)
    s = DagSelect.from_table(table, ["a", "b", "g"])
    dag = s.where(Expr.call("GtDecimal", s.col("b"), _c("1.5"))).aggregate(
        [s.col("g")],
        [("sum", Expr.call("MultiplyDecimal", s.col("a"), s.col("b"))),
         ("count", s.col("a")), ("count_star", None)]).build()
    n0 = len(_launched(runner))
    got, want, res = _both(runner, dag, snap)
    assert sorted(got) == sorted(want)
    assert all(r[0].as_tuple().exponent == -6 for r in got)
    assert res.batch.schema[0].eval_type is EvalType.DECIMAL
    assert len(_launched(runner)) > n0          # the device served it


def test_mixed_scales_are_aligned_exactly(runner):
    n = 3000
    rng = np.random.default_rng(3)
    table, snap = _table({
        "a": (DEC2, _dec(rng.integers(-10 ** 5, 10 ** 5, n), 2)),
        "b": (DEC4, _dec(rng.integers(-10 ** 7, 10 ** 7, n), 4))}, n, 2)
    s = DagSelect.from_table(table, ["a", "b"])
    dag = s.where(
        Expr.call("LtDecimal", s.col("a"), s.col("b")),
        Expr.call("GeDecimal", s.col("a"), _c("-500.125")),     # scale 3
    ).aggregate([], [
        ("sum", Expr.call("PlusDecimal", s.col("a"), s.col("b"))),
        ("sum", Expr.call("MinusDecimal", s.col("b"), _c("0.5")))]).build()
    got, want, _res = _both(runner, dag, snap)
    assert got == want and got[0][0] is not None
    assert [v.as_tuple().exponent for v in got[0]] == [-4, -4]
    plan = runner._analyze(dag)
    fixed = [n_.value for r in plan.sel_rpns + plan.agg_rpns
             for n_ in r.nodes if getattr(n_, "fixed", False)]
    assert sorted(fixed) == [10, 100, 100]      # the columns raised


def test_what_int32_cannot_hold_rides_int64_planes(runner):
    """The product of two int32 planes may leave int32: the proof fails
    at that width, holds at int64, and the XLA body serves it."""
    n = 2000
    rng = np.random.default_rng(4)
    table, snap = _table({
        "a": (DEC2, _dec(rng.integers(10 ** 6, 10 ** 8, n), 2)),
        "b": (DEC2, _dec(rng.integers(10 ** 3, 10 ** 5, n), 2))}, n, 3)
    s = DagSelect.from_table(table, ["a", "b"])
    dag = s.aggregate([], [("sum", Expr.call(
        "MultiplyDecimal", s.col("a"), s.col("b")))]).build()
    got, want, _res = _both(runner, dag, snap)
    assert got == want
    meta = [m for k, m in runner._arena.bucket(snap).items()
            if k[0] == "meta"]
    assert [m["dtypes"] for m in meta] == [("int64", "int64")]


def test_what_int64_cannot_hold_goes_to_the_host(runner):
    n = 1000
    table, snap = _table({
        "a": (DEC2, _dec(np.full(n, 4 * 10 ** 12), 2)),
        "b": (DEC2, _dec(np.full(n, 3 * 10 ** 12), 2))}, n, 4)
    s = DagSelect.from_table(table, ["a", "b"])
    dag = s.aggregate([], [("sum", Expr.call(
        "MultiplyDecimal", s.col("a"), s.col("b")))]).build()
    n0 = len(_launched(runner))
    got, want, _res = _both(runner, dag, snap)
    assert got == want == [(D(4 * 10 ** 12 * 3 * 10 ** 12 * n).scaleb(-4),)]
    assert len(_launched(runner)) == n0         # no launch: the host


def test_an_unscaled_column_goes_to_the_host(runner):
    """What the interpreted build leaves (a value beyond the declared
    scale): an object column, served by the host pipeline, exactly."""
    n = 1000
    vals = np.empty(n, dtype=object)
    vals[:] = [D(i).scaleb(-3) for i in range(n)]
    table, snap = _table({"a": (DEC2, Column(
        EvalType.DECIMAL, vals, np.ones(n, bool)))}, n, 5)
    s = DagSelect.from_table(table, ["a"])
    dag = s.where(Expr.call("GeDecimal", s.col("a"), _c("0.100"))) \
        .aggregate([], [("sum", s.col("a"))]).build()
    n0 = len(_launched(runner))
    got, want, _res = _both(runner, dag, snap)
    assert got == want == [(sum(vals[100:], D(0)),)]
    assert len(_launched(runner)) == n0


def test_what_has_no_integer_form_is_not_a_device_plan(runner):
    n = 100
    table, snap = _table({"a": (DEC2, _dec(np.arange(n), 2)),
                          "w": (FieldType.new_decimal(), _dec(
                              np.arange(n), 4))}, n, 6)
    s = DagSelect.from_table(table, ["a", "w"])
    for kind in ("min", "max", "avg", "first"):
        dag = DagSelect.from_table(table, ["a", "w"]).aggregate(
            [], [(kind, s.col("a"))]).build()
        assert runner._analyze(dag) is None, kind
    div = DagSelect.from_table(table, ["a", "w"]).aggregate([], [(
        "sum", Expr.call("DivideDecimal", s.col("a"), _c("3")))]).build()
    assert runner._analyze(div) is None
    # DECIMAL(20,4): more digits than an int64 carries
    wide = DagSelect.from_table(table, ["a", "w"]).aggregate(
        [], [("sum", s.col("w"))]).build()
    assert runner._analyze(wide) is None
    with pytest.raises(lowering.NotLowerable):
        lowering.lower(wide.executors[0], [], [
            runner_rpn(s.col("w"))], ["sum"])


def runner_rpn(expr):
    from tikv_tpu.expr import build_rpn
    return build_rpn(expr)


def test_a_date_rides_int32_and_a_datetime_keeps_its_core(runner):
    n = 3000
    rng = np.random.default_rng(7)
    cores = pack_datetime(rng.integers(1992, 1999, n),
                          rng.integers(1, 13, n), rng.integers(1, 29, n))
    stamps = cores | np.uint64(12 << 36)        # noon: low bits set
    table, snap = _table({
        "d": (DATE, Column(EvalType.DATETIME, cores, np.ones(n, bool))),
        "t": (DATETIME, Column(EvalType.DATETIME, stamps,
                               np.ones(n, bool))),
        "v": (FieldType.long(), Column(
            EvalType.INT, rng.integers(0, 100, n), np.ones(n, bool)))},
        n, 7)
    lo = int(pack_datetime(1994, 1, 1))
    hi = int(pack_datetime(1995, 1, 1))

    def dag(col, lo_const=lo):
        s = DagSelect.from_table(table, ["d", "t", "v"])
        return s.where(
            Expr.call("GeTime", s.col(col),
                      Expr.const(lo_const, EvalType.DATETIME)),
            Expr.call("LtTime", s.col(col),
                      Expr.const(hi, EvalType.DATETIME)),
        ).aggregate([], [("sum", s.col("v")), ("count_star", None)]) \
            .build()

    for col, dtype, planes in (("d", "int32", (True, False)),
                               ("t", "uint64", (False, False))):
        plan = runner._analyze(dag(col))
        assert plan.date_planes == planes, col
        got, want, _res = _both(runner, dag(col), snap)
        assert got == want and got[0][1] > 0
        metas = [m["dtypes"] for k, m in runner._arena.bucket(snap).items()
                 if k[0] == "meta" and "dtypes" in m]
        assert any(dtype in dts for dts in metas), (col, metas)
    # a bound with a time of day: the DATE column keeps its core too
    noon = lo | (12 << 36)
    assert runner._analyze(dag("d", noon)).date_planes == (False, False)
    got, want, _res = _both(runner, dag("d", noon), snap)
    assert got == want
    # MIN(date) returns the core: the column stays on it
    s = DagSelect.from_table(table, ["d", "t", "v"])
    mn = s.aggregate([], [("min", s.col("d"))]).build()
    assert runner._analyze(mn).date_planes == (False,)
    got, want, _res = _both(runner, mn, snap)
    assert got == want


def test_fits_proves_widths_from_the_columns_bounds(runner):
    n = 10
    table, _snap = _table({"a": (DEC2, _dec(np.arange(n), 2)),
                           "b": (DEC2, _dec(np.arange(n), 2))}, n, 8)
    s = DagSelect.from_table(table, ["a", "b"])
    dag = s.where(Expr.call("LtDecimal", s.col("a"), _c("24"))).aggregate(
        [], [("sum", Expr.call("MultiplyDecimal", s.col("a"),
                               s.col("b")))]).build()
    plan = runner._analyze(dag)
    assert plan.lowered and plan.agg_fracs == [4]
    small, big = (0, 10_494_950), (0, 10 ** 9)
    assert lowering.fits(plan, [small, (0, 10)], ["int32"] * 2, 10 ** 6)
    assert not lowering.fits(plan, [big, (0, 10)], ["int32"] * 2, 10)
    assert lowering.fits(plan, [big, (0, 10)], ["int64"] * 2, 10 ** 6)
    # the SUM over the rows must fit int64 too
    assert not lowering.fits(plan, [big, big], ["int64"] * 2, 10 ** 3)


# ------------------------------------------------- the kernel's operands


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(
        pallas_hash.pl, "pallas_call",
        functools.partial(pallas_hash.pl.pallas_call, interpret=True))
    monkeypatch.setattr(pallas_hash, "BLOCK", 1 << 12)


@pytest.mark.parametrize("n_devices", [1, 4])
def test_constants_are_operands_of_one_const_blind_kernel(interpret,
                                                          n_devices):
    """The Pallas body in interpret mode, on one device and sharded over
    four: three constant tuples of one class, one kernel entry, one
    build, each answer its own; a GROUP BY keeps its kernel too."""
    n = 9000
    rng = np.random.default_rng(9)
    table, snap = _table({
        "p": (DEC2, _dec(rng.integers(90000, 10 ** 7, n), 2)),
        "d": (DEC2, _dec(rng.integers(0, 11, n), 2)),
        "k": (FieldType.long(not_null=True), Column(
            EvalType.INT, rng.integers(0, 50, n), np.ones(n, bool)))},
        n, 9)
    r = DeviceRunner(mesh=make_mesh(jax.devices()[:n_devices]))
    r._is_tpu = True
    r._block_local = 1 << 12

    def dag(lo, hi, grouped):
        s = DagSelect.from_table(table, ["p", "d", "k"])
        return s.where(
            Expr.call("GeDecimal", s.col("d"), _c(lo)),
            Expr.call("LeDecimal", s.col("d"), _c(hi)),
        ).aggregate([s.col("k")] if grouped else [], [
            ("sum", Expr.call("MultiplyDecimal", s.col("p"), s.col("d"))),
            ("count_star", None)]).build()

    for grouped in (False, True):
        first0 = r.flight_recorder.stats()["first_launches"]
        classes0 = r.mesh_stats()["agg_params"]["const_classes"]
        answers = []
        for lo, hi in (("0.05", "0.07"), ("0.01", "0.03"),
                       ("0.08", "0.10"), ("0.05", "0.07")):
            got, want, _res = _both(r, dag(lo, hi, grouped), snap)
            assert sorted(got) == sorted(want)
            answers.append(sorted(got))
        assert answers[0] == answers[3] and answers[0] != answers[1]
        stats = r.flight_recorder.stats()
        assert stats["first_launches"] - first0 == 1
        assert r.mesh_stats()["agg_params"]["const_classes"] - classes0 == 1
    assert {e["compile_class"] for e in r.flight_recorder.items()} == \
        {"pallas_hash"}
    assert all(e["params"] == 2 for e in r.flight_recorder.items())
    assert {e["slot_mode"] for e in r.flight_recorder.items()} == \
        {"simple", "dense"}
    assert r.flight_recorder.stats()["faults"] == 0
    assert len([k for k in r._kernel_cache if isinstance(k, tuple)
                and k[:1] == ("hashpl",)]) == 2


# ------------------------------------------------- one rule, cold and patched


CHAR1 = FieldType(tp=FieldTypeTp.STRING, flen=1, collation=63)
_HANDLE0 = 1 << 40          # handles past int32: the plane rides int64


def _kinds_snapshot(table, handles, cols):
    ones = np.ones(len(handles), np.bool_)
    texts = np.empty(len(handles), dtype=object)
    texts[:] = cols["f"]
    return ColumnarTable.from_arrays(table, handles, {
        "a": Column(EvalType.INT, cols["a"], ones),
        "d": Column(EvalType.DATETIME, cols["d"], ones),
        "f": Column(EvalType.BYTES, texts, ones),
        "q": Column(EvalType.DECIMAL, cols["q"], ones, 2)})


# per plane kind: the one row a write leaves behind (None: appended)
# and what it holds
_WRITES = {
    "int64_handle": (None, {}),
    "int32": (11, {"a": -77777}),
    "date": (12, {"d": int(pack_datetime(1994, 7, 4))}),
    "code": (13, {"f": b"N"}),
    "decimal": (14, {"q": 99999999}),
}


@pytest.mark.parametrize("kind", list(_WRITES))
def test_a_patched_line_equals_a_cold_build_of_the_patched_data(kind):
    """ONE rule turns a column into a plane (device/feed.py
    ``plane_values``), whoever asks: a line built cold and then patched
    by a write holds, plane for plane, what a cold build of the written
    data holds, and its digests are the host truth's."""
    from tikv_tpu.copr.region_cache import FeedLineage
    from tikv_tpu.device.feed import anchor, value_plane_index
    from tikv_tpu.device.supervisor import host_plane_digest
    from tikv_tpu.utils import tracker

    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    n = 2000
    rng = np.random.default_rng(44)
    table = Table(8844, (PK, TableColumn("a", 2, FieldType.long()),
                         TableColumn("d", 3, DATE),
                         TableColumn("f", 4, CHAR1),
                         TableColumn("q", 5, DEC2)))
    handles = _HANDLE0 + np.arange(n, dtype=np.int64)
    cols = {"a": rng.integers(-10 ** 5, 10 ** 5, n),
            "d": pack_datetime(rng.integers(1993, 1996, n),
                               rng.integers(1, 13, n),
                               rng.integers(1, 29, n)),
            "f": [(b"R", b"A")[i] for i in rng.integers(0, 2, n)],
            "q": rng.integers(-10 ** 6, 10 ** 6, n)}
    old = _kinds_snapshot(table, handles, cols)

    row, written = _WRITES[kind]
    if row is None:             # an append: the new handle is the write
        row = n
        handles = np.append(handles, _HANDLE0 + n)
        cols = {"a": np.append(cols["a"], 5),
                "d": np.append(cols["d"], pack_datetime(1994, 2, 2)),
                "f": cols["f"] + [b"A"],
                "q": np.append(cols["q"], 123)}
    else:
        cols = {k: (list(v) if k == "f" else v.copy())
                for k, v in cols.items()}
        for name, v in written.items():
            cols[name][row] = v
    new = _kinds_snapshot(table, handles, cols)
    lineage = FeedLineage()
    for v, snap in enumerate((old, new)):
        snap.feed_lineage, snap.feed_version = lineage, v
    one = np.ones(1, np.bool_)
    lineage.record({"n": len(handles), "spans": [{
        "lo": row, "handles": handles[row:row + 1],
        "cols": {c.col_id: (new.columns[c.col_id].values[row:row + 1], one)
                 for c in table.columns if not c.is_pk_handle}}]})

    s = DagSelect.from_table(table, ["id", "a", "d", "f", "q"])
    dag = s.where(
        Expr.call("GeTime", s.col("d"), Expr.const(
            int(pack_datetime(1994, 1, 1)), EvalType.DATETIME)),
        Expr.call("LtTime", s.col("d"), Expr.const(
            int(pack_datetime(1995, 1, 1)), EvalType.DATETIME)),
    ).aggregate([s.col("f")], [("sum", s.col("q")), ("sum", s.col("a")),
                               ("max", s.col("id"))]).build()

    def served(snap):
        tr, tok = tracker.install()
        try:
            got = runner.handle_request(dag, snap)
        finally:
            tracker.uninstall(tok)
        assert sorted(got.rows()) == sorted(
            BatchExecutorsRunner(dag, snap).handle_request().rows())
        feed, = [v for v in runner._arena.bucket(anchor(snap)).values()
                 if isinstance(v, dict) and "flat" in v]
        return tr.time_detail()["labels"]["device_feed"], feed

    assert served(old)[0] == "upload"
    how, patched = served(new)
    assert how == "patch"
    how, built = served(_kinds_snapshot(table, handles, cols))
    assert how == "upload"

    assert patched["kinds"] == built["kinds"] == \
        (None, None, "date", 1, None)
    assert patched["null_flags"] == built["null_flags"]
    assert [str(a.dtype) for a in patched["flat"]] == \
        [str(a.dtype) for a in built["flat"]] == \
        ["int64", "int32", "int32", "int32", "int32"]
    m = len(handles)
    truth = [handles, cols["a"], cols["d"] >> np.uint64(41),
             np.array([v[0] for v in cols["f"]]), cols["q"]]
    for fi, want in zip(value_plane_index(patched["null_flags"]), truth):
        got = np.asarray(patched["flat"][fi])
        assert np.array_equal(got, np.asarray(built["flat"][fi])), fi
        assert np.array_equal(got[:m], want), fi
        assert not got[m:].any(), "the pad stays zero"
        assert int(np.asarray(patched["digests"][fi])) == \
            int(np.asarray(built["digests"][fi])) == \
            host_plane_digest(want.astype(got.dtype), m), fi
