"""A fast-path reply's rows leave as wire bytes in ONE native call.

``fastpath.encode_response`` hands the result's ``(values, validity)``
planes to ``native.encode_rows_msgpack`` (native/fastbuild.cpp), which
writes the msgpack array of rows without making a Python value for a
cell, and splices its bytes between the map header + ``"rows"`` key and
``env``'s packed items.  The Python chain (``tolist`` a column, ``zip``
to a tuple a row, ``msgpack.Packer.pack``) stays in the tree as
``encode_response_python``: the fallback for what the native call
declines, and the oracle here.  The native bytes must EQUAL the chain's
for every plane dtype, msgpack width boundary, NULL shape, row count,
column count and envelope size, and for the finalize's own planes; what
the call declines must reach the chain unchanged; and the cache must
count which of the two made each reply.
"""

import json
import sys
import threading
import types
import urllib.request
from decimal import Decimal

import numpy as np
import pytest

from tikv_tpu import native
from tikv_tpu.datatype import Column, EvalType, FieldType
from tikv_tpu.datatype.column import ColumnBatch
from tikv_tpu.executors.runner import SelectResult
from tikv_tpu.server import fastpath, wire
from tikv_tpu.server.fastpath import FastPathCache

from test_fastpath import rig  # noqa: F401 (the served gRPC stack)
from test_finalize_native import CASES as FINALIZE_CASES
from test_finalize_native import accumulator, plan_of

needs_native = pytest.mark.skipif(
    native.encode_rows_msgpack is None,
    reason="native/fastbuild.cpp did not build here (no g++?): the "
           "native encode cannot be compared with the Python chain")

ENV = {"backend": "device", "elapsed_ns": 1 << 33, "is_drained": True,
       "resume_token": None,
       "exec_summaries": [{"rows": 1024, "iters": 1, "time_ns": 7}],
       "time_detail": {"total_rpc_wall_ms": 8.69, "labels": {"a": "b"}},
       "trace_id": "t"}

# every msgpack integer width boundary, both sides of it
INTS = [-33, -32, -1, 0, 127, 128, 255, 256, 65_535, 65_536, 1 << 31,
        (1 << 32) - 1, 1 << 32, (1 << 63) - 1, 1 << 63, (1 << 64) - 1,
        -(1 << 63), -128, -129, -32_768, -32_769, -(1 << 31),
        -(1 << 31) - 1, 1, 126, 32_767, 32_768, (1 << 31) - 1]
I64 = [v for v in INTS if -(1 << 63) <= v < 1 << 63]
U64 = [v for v in INTS if v >= 0]
# a NaN with a payload and the sign set: the bits go as they are
ODD_NAN = np.frombuffer(
    np.array([0xFFF8_0000_DEAD_BEEF], np.uint64).tobytes(), np.float64)[0]
FLOATS = [0.0, -0.0, float("nan"), ODD_NAN, float("inf"), float("-inf"),
          1.5, -2.25e-308, 5e-324, 1.7976931348623157e308, 1 / 3]


def col(values, validity=None, et=EvalType.INT):
    values = np.asarray(values)
    if validity is None:
        validity = np.ones(len(values), np.bool_)
    return Column(et, values, np.asarray(validity, np.bool_))


def i64(values, validity=None):
    return col(np.array(values, np.int64), validity)


def u64(values, validity=None):
    return col(np.array(values, np.uint64), validity)


def f64(values, validity=None):
    return col(np.array(values, np.float64), validity, EvalType.REAL)


def random_cols(n_rows, n_cols, seed=0, nulls=0.1):
    """Planes of every dtype the call takes, values of every width,
    ``nulls`` of the cells NULL with garbage left in the value slot."""
    rng = np.random.default_rng(seed)
    out = []
    for c in range(n_cols):
        width = rng.integers(1, 64, n_rows).astype(np.uint64)
        mag = rng.integers(0, 1 << 63, n_rows, dtype=np.uint64) >> \
            (np.uint64(63) - width)
        validity = rng.random(n_rows) >= nulls
        if c % 3 == 0:
            sign = rng.integers(0, 2, n_rows) * 2 - 1
            out.append(i64(mag.astype(np.int64) * sign, validity))
        elif c % 3 == 1:
            out.append(u64(mag << np.uint64(1), validity))
        else:
            out.append(f64(rng.standard_normal(n_rows) *
                           10.0 ** rng.integers(-30, 30, n_rows), validity))
    return out


def finalize_columns(name):
    """One of test_finalize_native.py's accumulators through the
    finalize: the planes a served GROUP BY reply is made from."""
    from tikv_tpu.device import aggregate as agg_mod
    from tikv_tpu.device.aggregate import DeviceAggregator
    args = accumulator(FINALIZE_CASES[name])
    finalized, _was_native = agg_mod.finalize_packed(*args)
    return agg_mod._hash_columns(
        DeviceAggregator._agg_out(plan_of(args[4])), finalized)


def garbage_under_nulls(make, values, validity):
    """NULL cells whose value slot holds something that would encode
    wider than the valid cells do."""
    values = list(values)
    for i, ok in enumerate(validity):
        if not ok:
            values[i] = values[(i + 7) % len(values)]
    return make(values, validity)


def env_of(n_keys):
    return {f"k{i}": i for i in range(n_keys)}


# name -> () -> (env, columns)
ENCODE_CASES = {
    # one dtype alone, every width boundary, then mixed
    "int64-boundaries": lambda: (ENV, [i64(I64)]),
    "uint64-boundaries": lambda: (ENV, [u64(U64)]),
    "float64-values": lambda: (ENV, [f64(FLOATS)]),
    "mixed-boundaries": lambda: (ENV, [
        i64(I64[:len(FLOATS)]), u64(U64[:len(FLOATS)]), f64(FLOATS)]),
    "uint64-above-int64-max-only": lambda: (ENV, [
        u64([1 << 63, (1 << 64) - 1, (1 << 63) + 1])]),
    # NULLs: none, some, all; the value slot is never read
    "nulls-none": lambda: (ENV, random_cols(1024, 3, nulls=0.0)),
    "nulls-some": lambda: (ENV, random_cols(1024, 3, nulls=0.3)),
    "nulls-all": lambda: (ENV, random_cols(1024, 3, nulls=1.0)),
    "nulls-over-garbage-int64": lambda: (ENV, [garbage_under_nulls(
        i64, I64, [i % 3 != 0 for i in range(len(I64))])]),
    "nulls-over-garbage-uint64": lambda: (ENV, [garbage_under_nulls(
        u64, U64, [i % 2 != 0 for i in range(len(U64))])]),
    "nulls-over-nan": lambda: (ENV, [f64(
        FLOATS, [i % 2 == 0 for i in range(len(FLOATS))])]),
    "nulls-all-over-garbage": lambda: (ENV, [
        i64(I64, np.zeros(len(I64), np.bool_)),
        f64(np.full(len(I64), np.nan), np.zeros(len(I64), np.bool_))]),
    # row counts: both sides of fixarray / array16 / array32
    **{f"rows-{n}": (lambda n=n: (ENV, random_cols(n, 3, seed=n)))
       for n in (0, 1, 15, 16, 1024, 65_535, 65_536)},
    # column counts: the row's own header
    **{f"columns-{n}": (lambda n=n: (ENV, random_cols(40, n, seed=n)))
       for n in (1, 3, 15, 16, 17)},
    # above the size at which the call lets go of the GIL
    "rows-65536-columns-17": lambda: (ENV, random_cols(65_536, 17, seed=3)),
    # the envelope: rows + its items, both sides of fixmap / map16
    **{f"env-{n}-keys": (lambda n=n: (env_of(n), random_cols(20, 3)))
       for n in (0, 1, 14, 15, 16)},
    "env-with-decimal-and-bytes": lambda: (
        {"d": Decimal("1.50"), "b": b"\x00\xff", "n": None,
         "l": [1, {"x": 2.5}]}, random_cols(20, 3)),
    # planes that are not freshly made arrays
    "read-only-planes": lambda: (ENV, [read_only(c)
                                       for c in random_cols(50, 3)]),
    "slices-of-longer-planes": lambda: (ENV, [
        c.slice(5, 45) for c in random_cols(50, 3)]),
    "little-endian-spelled-out": lambda: (ENV, [col(
        np.array(I64, "<i8")), col(np.array(U64[:len(I64)] + [0] * (
            len(I64) - len(U64)), "<u8")), col(np.arange(
                len(I64), dtype="<f8"), et=EvalType.REAL)]),
    "datetime-and-duration-planes": lambda: (ENV, [
        col(np.array(U64, np.uint64), et=EvalType.DATETIME),
        col(np.array(I64[:len(U64)], np.int64), et=EvalType.DURATION)]),
    # the finalize's own planes, end to end
    **{f"finalize-{name}": (lambda name=name: (ENV, finalize_columns(name)))
       for name in FINALIZE_CASES},
}


def read_only(c):
    c.values.flags.writeable = False
    c.validity.flags.writeable = False
    return c


def result_of(columns):
    schema = [FieldType.double() if c.eval_type is EvalType.REAL
              else FieldType.long() for c in columns]
    return SelectResult(ColumnBatch(schema, list(columns)), [])


def test_the_case_list_holds_the_34_accumulators():
    assert sum(n.startswith("finalize-") for n in ENCODE_CASES) == 34


@needs_native
@pytest.mark.parametrize("name", ENCODE_CASES)
def test_native_bytes_equal_the_python_chain(name):
    env, columns = ENCODE_CASES[name]()
    result = result_of(columns)
    want = fastpath.encode_response_python(env, result)
    fp = FastPathCache()
    got = fastpath.encode_response(env, result, fp)
    assert fp.stats()["encode"] == {"native": 1, "python": 0,
                                    "native_available": True}
    assert got == want
    # the rows alone, against a plain packer over Python values
    rows = native.encode_rows_msgpack(
        [(c.values, c.validity) for c in columns])
    assert isinstance(rows, bytes)
    import msgpack
    assert rows == msgpack.packb(
        [list(r) for r in zip(*[fastpath._column_list(c) for c in columns])],
        use_bin_type=True)
    # the oracle is not vacuous: the reply decodes to the planes' cells
    back = wire.unpack(got)
    assert list(back) == ["rows", *env]
    assert len(back["rows"]) == len(columns[0])
    if len(columns[0]):
        i = len(columns[0]) // 2
        for cell, c in zip(back["rows"][i], columns):
            if not c.validity[i]:
                assert cell is None
            elif c.values.dtype == np.float64 and np.isnan(c.values[i]):
                assert np.isnan(cell)
            else:
                assert cell == c.values[i] and \
                    isinstance(cell, float if c.values.dtype == np.float64
                               else int)


# ------------------------------------------------- what the call declines

def bytes_col(n=5):
    values = np.empty(n, dtype=object)
    values[:] = [b"x" * i for i in range(n)]
    return Column(EvalType.BYTES, values, np.ones(n, np.bool_))


def decimal_col(n=5):
    return Column.from_list(
        EvalType.DECIMAL, [Decimal(i).scaleb(-2) if i else None
                           for i in range(n)])


def strided(c):
    return Column(c.eval_type, np.repeat(c.values, 2)[::2],
                  np.repeat(c.validity, 2)[::2])


def fake_batch(columns):
    """A batch ``ColumnBatch`` would refuse to build (it holds its
    columns to one length)."""
    return types.SimpleNamespace(columns=columns,
                                 num_rows=len(columns[0].values))


DECLINED = {
    "object-plane-bytes": lambda: ColumnBatch(
        [FieldType.long(), FieldType.long()], [i64(range(5)), bytes_col()]),
    "decimal-column": lambda: ColumnBatch(
        [FieldType.long(), FieldType.long()], [decimal_col(), i64(range(5))]),
    "q6-one-decimal-row": lambda: ColumnBatch(
        [FieldType.long()], [Column.from_list(
            EvalType.DECIMAL, [Decimal("123141078.2283")])]),
    "int32-plane": lambda: ColumnBatch(
        [FieldType.long()], [col(np.arange(5, dtype=np.int32))]),
    "float32-plane": lambda: ColumnBatch(
        [FieldType.long()], [col(np.arange(5, dtype=np.float32))]),
    "bool-plane": lambda: ColumnBatch(
        [FieldType.long()], [col(np.ones(5, np.bool_))]),
    "big-endian-plane": lambda: ColumnBatch(
        [FieldType.long()], [col(np.arange(5, dtype=">i8"))]),
    "strided-values-and-validity": lambda: ColumnBatch(
        [FieldType.long()] * 2, [i64(range(5)), strided(i64(range(5)))]),
    "strided-validity-alone": lambda: ColumnBatch(
        [FieldType.long()], [Column(
            EvalType.INT, np.arange(5), np.ones(10, np.bool_)[::2])]),
    "uint8-validity": lambda: fake_batch([types.SimpleNamespace(
        values=np.arange(5), validity=np.ones(5, np.uint8))]),
    "two-dimensional-plane": lambda: fake_batch([types.SimpleNamespace(
        values=np.arange(6).reshape(2, 3),
        validity=np.ones((2, 3), np.bool_))]),
    "column-lengths-differ": lambda: fake_batch(
        [i64(range(5)), i64(range(4))]),
    "validity-shorter-than-values": lambda: fake_batch(
        [types.SimpleNamespace(values=np.arange(5),
                               validity=np.ones(4, np.bool_))]),
    "no-column-at-all": lambda: ColumnBatch([], []),
}

# the chain itself cannot zip these (and no batch holds them): only the
# native call's refusal is checked
_NO_CHAIN = {"two-dimensional-plane", "validity-shorter-than-values"}


@needs_native
@pytest.mark.parametrize("name", DECLINED)
def test_what_the_call_declines_is_served_by_the_chain(name):
    batch = DECLINED[name]()
    assert native.encode_rows_msgpack(
        [(c.values, c.validity) for c in batch.columns]) is None
    if name in _NO_CHAIN:
        return
    result = types.SimpleNamespace(batch=batch)
    fp = FastPathCache()
    got = fastpath.encode_response(ENV, result, fp)
    assert got == fastpath.encode_response_python(ENV, result)
    assert fp.stats()["encode"] == {"native": 0, "python": 1,
                                    "native_available": True}
    assert list(wire.unpack(got)) == ["rows", *ENV]


def test_an_absent_extension_is_served_by_the_chain(monkeypatch):
    monkeypatch.setattr(native, "encode_rows_msgpack", None)
    result = result_of(random_cols(1024, 3))
    fp = FastPathCache()
    got = fastpath.encode_response(ENV, result, fp)
    assert got == fastpath.encode_response_python(ENV, result)
    assert fp.stats()["encode"] == {"native": 0, "python": 1,
                                    "native_available": False}
    # no cache to count on: the reply is the same
    assert fastpath.encode_response(ENV, result) == got


@needs_native
@pytest.mark.parametrize("arg", [
    7, [7], [(np.arange(3),)], [(np.arange(3), None)],
    [(None, np.ones(3, np.bool_))], [[np.arange(3), np.ones(3, np.bool_)]],
], ids=["not-a-sequence", "not-a-pair", "a-one-tuple", "validity-none",
        "values-none", "a-list-not-a-tuple"])
def test_what_is_no_plane_at_all_raises(arg):
    with pytest.raises(TypeError):
        native.encode_rows_msgpack(arg)


@needs_native
def test_the_call_keeps_no_reference_to_its_planes():
    values, validity = np.arange(100), np.ones(100, np.bool_)
    pair = (values, validity)
    before = [sys.getrefcount(o) for o in (values, validity, pair)]
    for _ in range(10):
        assert native.encode_rows_msgpack([pair]) is not None
        assert native.encode_rows_msgpack(
            [pair, (values.astype(np.int32), validity)]) is None
    assert [sys.getrefcount(o) for o in (values, validity, pair)] == before
    # and the view it took is released: the plane can be resized
    values.resize(200, refcheck=False)


@needs_native
def test_threads_encode_side_by_side_and_every_reply_is_counted():
    """More threads than cores, a short switch interval, replies on
    both sides of the size at which the call lets go of the GIL: every
    reply the oracle's bytes, no count lost."""
    small = result_of(random_cols(1024, 3, seed=1))
    large = result_of(random_cols(70_000, 3, seed=2))   # 210,000 cells
    declined = result_of([bytes_col(9)])
    want = {id(r): fastpath.encode_response_python(ENV, r)
            for r in (small, large, declined)}
    fp = FastPathCache()
    wrong = []

    def work():
        for _ in range(6):
            for r in (small, large, declined):
                if fastpath.encode_response(ENV, r, fp) != want[id(r)]:
                    wrong.append(id(r))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not wrong
    assert fp.stats()["encode"] == {"native": 16 * 6 * 2,
                                    "python": 16 * 6,
                                    "native_available": True}


# ------------------------------------------------- a served read, counted

@pytest.fixture(scope="module")
def health(rig):
    """``/health`` of the served gRPC stack (test_fastpath.py's rig)."""
    from tikv_tpu.server.status_server import StatusServer
    node = rig["node"]
    status = StatusServer("127.0.0.1:0", node=node,
                          config_controller=node.config_controller)
    status.start()
    yield f"http://127.0.0.1:{status.port}/health"
    status.stop()


def encode_counts(health):
    return json.load(urllib.request.urlopen(health))["fastpath"]["encode"]


@needs_native
def test_a_served_read_counts_one_native_encode(rig, health):
    """GROUP BY c0 COUNT SUM through gRPC: the repeat is a fast-path
    hit whose rows the native call made, counted once on ``/health``,
    and equal to the full decode path's answer (``enc_rows``)."""
    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.testing.fixture import encode_table_row, int_table
    from tikv_tpu.utils import failpoint
    c = rig["client"]
    table = int_table(2, table_id=9701)
    muts = []
    for h in range(1500):
        row = {"c1": h * 37 % 2001 - 1000}
        if h % 11:
            row["c0"] = h % 40          # the rest: the NULL group
        muts.append(("put", *encode_table_row(table, h, row)))
    c.txn_write(muts)

    def ask():
        s = DagSelect.from_table(table, ["id", "c0", "c1"])
        dag = s.aggregate([s.col("c0")], [("count_star", None),
                                          ("sum", s.col("c1"))]) \
            .build(start_ts=c.tso())
        return c.coprocessor(dag, deadline_ms=30_000, timeout=60)

    ask()                       # the slow path learns the class
    before = encode_counts(health)
    hits = rig["node"].fastpath.stats()["hit"]
    fast = ask()
    assert rig["node"].fastpath.stats()["hit"] == hits + 1
    after = encode_counts(health)
    assert after == {"native": before["native"] + 1,
                     "python": before["python"],
                     "native_available": True}
    failpoint.cfg("copr::fastpath", "return(miss)")
    try:
        slow = ask()
    finally:
        failpoint.remove("copr::fastpath")
    assert encode_counts(health) == after      # not a fast-path reply
    assert len(fast["rows"]) == 41 and fast["rows"] == slow["rows"]
    assert any(r[-1] is None for r in fast["rows"])
    assert fast["backend"] == slow["backend"] == "device"


def test_a_served_read_without_the_extension_counts_one_python_encode(
        rig, health, monkeypatch):
    """With the extension absent the same served read is the chain's,
    and says so."""
    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.testing.fixture import encode_table_row, int_table
    c = rig["client"]
    table = int_table(2, table_id=9702)
    c.txn_write([("put", *encode_table_row(
        table, h, {"c0": h % 7, "c1": h})) for h in range(600)])

    def ask():
        s = DagSelect.from_table(table, ["id", "c0", "c1"])
        dag = s.aggregate([s.col("c0")], [("sum", s.col("c1"))]) \
            .build(start_ts=c.tso())
        return c.coprocessor(dag, deadline_ms=30_000, timeout=60)

    first = ask()
    monkeypatch.setattr(native, "encode_rows_msgpack", None)
    before = encode_counts(health)
    assert before["native_available"] is False
    again = ask()
    after = encode_counts(health)
    assert (after["native"], after["python"]) == \
        (before["native"], before["python"] + 1)
    assert again["rows"] == first["rows"] and len(again["rows"]) == 7
