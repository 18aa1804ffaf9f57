"""What PR 38 added to the device's integer form of a plan
(device/lowering.py, datatype/tile.py, device/aggregate.py): the limb
split of a SUM whose argument leaves int32, which constants are operands
and which are structure, CHAR code planes, AVG of a DECIMAL, and a GROUP
BY over several keys.  The host pipeline (``BatchExecutorsRunner``:
Decimal objects, raw bytes) is the behavioural reference throughout; what
the device cannot hold exactly is refused to it."""

import decimal

import numpy as np
import pytest

import jax

from tikv_tpu.datatype import (
    Column, EvalType, FieldType, FieldTypeFlag, FieldTypeTp,
)
from tikv_tpu.datatype.collation import UTF8MB4_BIN, UTF8MB4_GENERAL_CI
from tikv_tpu.datatype.tile import code_bytes, code_plane, code_width
from tikv_tpu.device import DeviceRunner, lowering, pallas_hash
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.expr import Expr, build_rpn
from tikv_tpu.expr.rpn import RpnConst
from tikv_tpu.parallel import make_mesh
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn

D = decimal.Decimal
NN = FieldTypeFlag.NOT_NULL
DEC2 = FieldType(tp=FieldTypeTp.NEW_DECIMAL, flag=NN, flen=15, decimal=2)
DATE = FieldType(tp=FieldTypeTp.DATE, flag=NN)
PK = TableColumn("id", 1, FieldType.long(not_null=True), is_pk_handle=True)
I32 = lowering._I32


def char(n: int, collation: int = 63, tp=FieldTypeTp.STRING) -> FieldType:
    return FieldType(tp=tp, flen=n, collation=collation)


def _table(cols: dict, n: int, table_id: int):
    """An in-memory snapshot: {name: (FieldType, Column)}."""
    table = Table(table_id, (PK,) + tuple(
        TableColumn(name, cid, ft) for cid, (name, (ft, _c)) in
        enumerate(cols.items(), start=2)))
    snap = ColumnarTable.from_arrays(
        table, np.arange(n), {name: c for name, (_ft, c) in cols.items()})
    return table, snap


def _dec(values, frac=2, valid=None):
    v = np.asarray(values, np.int64)
    return Column(EvalType.DECIMAL, v,
                  np.ones(len(v), bool) if valid is None else valid, frac)


def _texts(values, valid=None):
    out = np.empty(len(values), dtype=object)
    out[:] = list(values)
    return Column(EvalType.BYTES, out,
                  np.ones(len(values), bool) if valid is None else valid)


def _c(x):
    return Expr.const(D(x), EvalType.DECIMAL)


def _both(runner, dag, snap):
    got = runner.handle_request(dag, snap)
    want = BatchExecutorsRunner(dag, snap).handle_request()
    return got.rows(), want.rows(), got


def _launched(runner) -> list:
    return [e["compile_class"] for e in runner.flight_recorder.items()]


@pytest.fixture(scope="module")
def runner():
    return DeviceRunner(mesh=make_mesh(jax.devices()[:1]))


# ------------------------------------------------- the limb split


def _product_plan(runner, table_id=8901):
    """SUM(x * (1 - d) * (1 + t)) over three DECIMAL(15,2) columns: Q1's
    charge."""
    n = 8
    table, _snap = _table({"x": (DEC2, _dec(np.arange(n))),
                           "d": (DEC2, _dec(np.arange(n))),
                           "t": (DEC2, _dec(np.arange(n)))}, n, table_id)
    s = DagSelect.from_table(table, ["x", "d", "t"])
    dag = s.aggregate([], [("sum", Expr.call(
        "MultiplyDecimal",
        Expr.call("MultiplyDecimal", s.col("x"),
                  Expr.call("MinusDecimal", _c(1), s.col("d"))),
        Expr.call("PlusDecimal", _c(1), s.col("t"))))]).build()
    return runner._analyze(dag)


def _eval_int(rpn, env: list) -> int:
    """An integer RPN over Python ints (no width anywhere)."""
    stack = []
    for node in rpn.nodes:
        if hasattr(node, "col_idx"):
            stack.append(env[node.col_idx])
        elif isinstance(node, RpnConst):
            stack.append(node.value)
        else:
            b, a = stack.pop(), stack.pop()
            stack.append({
                "PlusInt": lambda: a + b, "MinusInt": lambda: a - b,
                "MultiplyInt": lambda: a * b, "RightShift": lambda: a >> b,
                "BitAndSig": lambda: a & b}[node.meta.name]())
    (out,) = stack
    return out


@pytest.mark.parametrize("seed", range(8))
def test_the_limbs_recombine_to_the_int64_product(seed):
    """``x * m == ((x >> 16) * m << 16) + (x & 0xFFFF) * m`` for random
    bounds at the edges, either sign of either factor: the split is
    algebra, exact by construction; ``fit`` only has to prove that both
    limb products stay inside int32 (negative ``m`` is handled, not
    refused)."""
    rng = np.random.default_rng(seed)
    xs = np.concatenate([rng.integers(I32[0], I32[1], 200),
                         [I32[0], I32[1], 0, -1, 65535, 65536, -65536]])
    ms = np.concatenate([rng.integers(-32767, 32768, 200),
                         [-32767, 32767, 0, 1, -1, 108, -108]])
    rpn = build_rpn(Expr.call("MultiplyInt", Expr.column(0, EvalType.INT),
                              Expr.column(1, EvalType.INT)))
    for split_right in (False, True):
        hi, lo = lowering._limbs(rpn, split_right)
        for x, m in zip(xs.tolist(), ms.tolist()):
            env = [m, x] if split_right else [x, m]
            h, low = _eval_int(hi, env), _eval_int(lo, env)
            assert (h << 16) + low == x * m
            # |hi| <= 2^15, lo < 2^16: with |m| < 2^15 both are int32
            assert I32[0] <= h <= I32[1] and I32[0] <= low <= I32[1]


def test_fit_asks_for_limbs_where_only_the_last_product_leaves_int32(runner):
    plan = _product_plan(runner)
    assert plan.lowered and plan.agg_fracs == [6] and not plan.limbs
    q1 = [(90000, 10_494_950), (0, 10), (0, 8)]
    assert lowering.fit(plan, q1, ["int32"] * 3, 6_001_215) == ((0, False),)
    assert not lowering.fits(plan, q1, ["int32"] * 3, 6_001_215)
    # at int64 the plain form holds: nothing to split
    assert lowering.fit(plan, q1, ["int64"] * 3, 6_001_215) == ()
    # inside int32 as it stands: nothing to split either
    small = [(0, 10_000), (0, 10), (0, 8)]
    assert lowering.fit(plan, small, ["int32"] * 3, 10 ** 6) == ()
    # the LEFT factor past int32 already: one split cannot hold it
    wide = [(0, 10 ** 9), (0, 10), (0, 8)]
    assert lowering.fit(plan, wide, ["int32"] * 3, 10) is None
    # a right factor of 2^16 or more: the low limb's product leaves int32
    assert lowering.fit(plan, [(90000, 10_494_950), (0, 10), (0, 70_000)],
                        ["int32"] * 3, 10) is None
    # the SUM over the rows past int64: refused whatever the split
    assert lowering.fit(plan, q1, ["int32"] * 3, 10 ** 9) is None
    # a negative right factor is handled: |hi * m| and |lo * m| bound it
    assert lowering.fit(plan, [(90000, 10_494_950), (0, 10), (-30_000, 8)],
                        ["int32"] * 3, 10) == ((0, False),)


def test_the_limb_variant_is_built_once_and_keeps_the_recipe(runner):
    plan = _product_plan(runner, 8902)
    need = ((0, False),)
    variant = runner._limb_variant(plan, need)
    assert runner._limb_variant(plan, need) is variant      # once a plan
    assert variant.limbs == need and len(variant.specs) == 2
    assert [s.kind for s in variant.specs] == ["sum", "sum"]
    assert variant.agg_fracs == [6, 6]
    assert variant.agg_recipes == [(0, 1)]
    names = [[n.meta.name for n in r.nodes if hasattr(n, "meta")]
             for r in variant.agg_rpns]
    assert names[0].count("RightShift") == names[1].count("BitAndSig") == 1
    # both limbs fit where the plain product did not, and the finalize
    # puts them together in int64
    q1 = [(90000, 10_494_950), (0, 10), (0, 8)]
    assert lowering.fit(variant, q1, ["int32"] * 3, 6_001_215) == ()
    ivs = lowering.agg_intervals(variant, q1, ["int32"] * 3)
    assert ivs[0][1] < 2 ** 21 and ivs[1] == (0, 65535 * 108)
    hi = (np.array([16014 * 108, -5]), np.array([True, True]))
    lo = (np.array([65535 * 108, 7]), np.array([True, True]))
    (vals, ok), = lowering.recipe_planes(variant.agg_recipes, [hi, lo])
    assert vals.tolist() == [(16014 * 108 << 16) + 65535 * 108,
                             (-5 << 16) + 7]
    assert ok.all() and runner._aggregator._agg_out(variant)[2] == (6,)
    # in the kernel's identity: another kernel than the plain plan's
    assert pallas_hash.key_consts(variant) != pallas_hash.key_consts(plan)


def test_the_37_bit_sum_is_exact_off_the_kernel_too(runner):
    """On the XLA bodies (what serves off a TPU) the same plan takes the
    limbs at int32 planes and answers the host pipeline's Decimals."""
    n = 5000
    rng = np.random.default_rng(11)
    qty = rng.integers(1, 51, n)
    table, snap = _table({
        "x": (DEC2, _dec(qty * rng.integers(90000, 209900, n))),
        "d": (DEC2, _dec(rng.integers(0, 11, n))),
        "t": (DEC2, _dec(rng.integers(0, 9, n))),
        "g": (FieldType.long(not_null=True), Column(
            EvalType.INT, rng.integers(0, 4, n), np.ones(n, bool)))},
        n, 8903)
    s = DagSelect.from_table(table, ["x", "d", "t", "g"])
    charge = Expr.call(
        "MultiplyDecimal",
        Expr.call("MultiplyDecimal", s.col("x"),
                  Expr.call("MinusDecimal", _c(1), s.col("d"))),
        Expr.call("PlusDecimal", _c(1), s.col("t")))
    dag = s.aggregate([s.col("g")], [("sum", charge),
                                     ("count_star", None)]).build()
    n0 = len(_launched(runner))
    got, want, _res = _both(runner, dag, snap)
    assert sorted(got) == sorted(want)
    assert all(r[0].as_tuple().exponent == -6 for r in got)
    assert len(_launched(runner)) > n0
    # the feed's dtypes stayed int32: the limbs, not the wide planes
    plan = runner._analyze(dag)
    assert list(plan.variants) == [((0, False),)]


# ------------------------------------------------- operands and structure


def _q1_like(table, year: int, one="1", tup=None):
    s = DagSelect.from_table(table, ["x", "d", "ship"])
    return s.where(Expr.call("LeTime", s.col("ship"), Expr.const(
        (year << 50) | (6 << 46) | (15 << 41), EvalType.DATETIME))
    ).aggregate([], [("sum", Expr.call(
        "MultiplyDecimal", s.col("x"),
        Expr.call("MinusDecimal", _c(one), s.col("d"))))]).build()


def test_a_constant_under_a_product_is_structure_a_compared_one_an_operand(
        runner):
    n = 64
    table, _snap = _table({
        "x": (DEC2, _dec(np.arange(n))), "d": (DEC2, _dec(np.arange(n))),
        "ship": (DATE, Column(EvalType.DATETIME, np.full(
            n, (1995 << 50) | (1 << 46) | (1 << 41), np.uint64),
            np.ones(n, bool)))}, n, 8904)
    plans = [runner._analyze(_q1_like(table, y)) for y in range(1992, 2053)]
    assert len(plans) == 61 and all(p is not None for p in plans)
    # 61 dates: one const-blind class, one kernel identity, one operand
    assert len({d.class_key() for d in (
        _q1_like(table, y) for y in range(1992, 2053))}) == 1
    assert len({pallas_hash.key_consts(p) for p in plans}) == 1
    for p in plans[:3]:
        _sel, _aggs, vals, dts = pallas_hash.plan_params(p)
        assert len(vals) == 1 and dts == ("int32",)
    fixed = [nd.value for nd in plans[0].agg_rpns[0].nodes
             if isinstance(nd, RpnConst) and nd.fixed]
    assert fixed == [100]           # the 1 of (1 - d), at d's scale
    assert pallas_hash.key_consts(plans[0]) == (("fixed", (100,), ()),)
    # another arithmetic constant: the same const-blind class of the
    # DAG, ANOTHER kernel identity (the value is in the key)
    other = runner._analyze(_q1_like(table, 1995, one="2"))
    assert _q1_like(table, 1995, one="2").class_key() == \
        _q1_like(table, 1995).class_key()
    assert pallas_hash.key_consts(other) == (("fixed", (200,), ()),)
    # ... and bounded by the value it has: (100 - d) in [90, 100]
    bounds = [(0, 10_494_950), (0, 10), (0, 1 << 22)]
    assert lowering.fits(plans[0], bounds, ["int32"] * 3, 10 ** 6)
    # a constant that is only ADDED stays an operand (tests/
    # test_decimal_planes.py holds SUM(b - 0.5) to that)
    s = DagSelect.from_table(table, ["x", "d", "ship"])
    added = runner._analyze(s.aggregate([], [("sum", Expr.call(
        "MinusDecimal", s.col("x"), _c("0.5")))]).build())
    assert not [nd for nd in added.agg_rpns[0].nodes
                if isinstance(nd, RpnConst) and nd.fixed]
    assert len(pallas_hash.plan_params(added)[2]) == 1


def test_q6s_eighty_tuples_are_still_one_class(runner):
    """Q6's constants are all compared: nothing of it became structure."""
    import os
    import sys
    import types
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.append(bench)
    import byname
    kind = byname.load("requests", "tpch_q6")
    table = byname.load("tables", "lineitem_presplit").fixture(
        {"table_id": 8905, "regions": 12, "region_split_size_mb": 96})
    ctx = types.SimpleNamespace(table=table)
    plans = [runner._analyze(kind.plan(ctx, i, 7))
             for i in range(len(kind.TUPLES))]
    assert len({kind.plan(ctx, i, 7).class_key()
                for i in range(len(kind.TUPLES))}) == 1
    assert {pallas_hash.key_consts(p) for p in plans} == {()}
    assert all(len(pallas_hash.plan_params(p)[2]) == 5 for p in plans)
    assert all(p.agg_recipes is None and not p.limbs for p in plans)


# ------------------------------------------------- code planes


@pytest.mark.parametrize("width", [1, 2, 3, 4])
def test_code_plane_round_trip(width):
    rng = np.random.default_rng(width)
    vals = [bytes(rng.integers(1, 256, rng.integers(0, width + 1),
                               dtype=np.uint8).tolist())
            for _ in range(500)]
    vals += [b"", b"A", b"A "[:width], b"\xff" * width, b"Z"]
    col = _texts(vals)
    codes = code_plane(col.values, width)
    assert codes.dtype == np.int64 and codes.min() >= 0
    assert codes.max() < 1 << (8 * width)
    assert code_bytes(codes, width).tolist() == vals
    # the codes keep the raw bytes' order and equality: how the host
    # pipeline compares and groups a binary or _bin string
    order = np.argsort(codes, kind="stable")
    assert [vals[i] for i in order] == sorted(vals)
    assert len(set(codes.tolist())) == len(set(vals))


def test_code_plane_pad_space_null_and_refusals():
    # a trailing space is a byte of the value (the host groups raw
    # bytes): b"A" and b"A " are two codes, and both come back
    codes = code_plane(_texts([b"A", b"A ", b" "]).values, 2)
    assert codes.tolist() == [0x4100, 0x4120, 0x2000]
    assert code_bytes(codes, 2).tolist() == [b"A", b"A ", b" "]
    # a NULL is b"" under a False validity, as the build leaves it
    col = _texts([b"R", b"", b"N"], np.array([True, False, True]))
    assert code_plane(col.values, 1).tolist() == [82, 0, 78]
    # what no code gives back: a value wider than the column's bytes
    # (multi-byte UTF-8 in a CHAR(1)), the pad byte inside a value
    assert code_plane(_texts(["é".encode()]).values, 1) is None
    # (the cast to the column's width cuts such a value; the count of the
    # bytes kept finds it, beside shorter values too)
    assert code_plane(_texts([b"AB", b"", b"C"]).values, 1) is None
    assert code_plane(_texts([b"ABC", b"A", b""]).values, 2) is None
    # what is not bytes has no code, whatever the width cuts it to
    assert code_plane(np.array([12, 7, None], dtype=object), 1) is None
    assert code_plane(np.array([12, 7], dtype=object), 1) is None
    assert code_plane(_texts([b"A\x00"]).values, 2) is None
    assert code_plane(_texts([b"A\x00B"]).values, 3) is None
    assert code_plane(_texts([]).values, 1).tolist() == []
    # which columns have a code plane at all
    assert code_width(char(1)) == 1 and code_width(char(4)) == 4
    assert code_width(char(4, UTF8MB4_BIN)) == 4
    assert code_width(char(3, tp=FieldTypeTp.VAR_CHAR)) == 3
    assert code_width(char(5)) is None                      # CHAR(5)
    assert code_width(char(1, UTF8MB4_GENERAL_CI)) is None  # a _ci
    assert code_width(FieldType.var_char()) is None         # no width
    assert code_width(FieldType.long()) is None


def _flags_table(table_id: int, flag_ft, n=4000, null_at=None):
    rng = np.random.default_rng(table_id)
    flags = [(b"R", b"A", b"N")[i] for i in rng.integers(0, 3, n)]
    status = [(b"O", b"F")[i] for i in rng.integers(0, 2, n)]
    valid = np.ones(n, bool)
    if null_at is not None:
        valid[null_at] = False
        flags[null_at] = b""
    return _table({
        "flag": (flag_ft, _texts(flags, valid)),
        "status": (char(1), _texts(status)),
        "v": (DEC2, _dec(rng.integers(-10 ** 6, 10 ** 6, n)))}, n, table_id)


def _by_keys(table, keys):
    s = DagSelect.from_table(table, ["flag", "status", "v"])
    return s.aggregate([s.col(k) for k in keys], [
        ("sum", s.col("v")), ("count", s.col("flag")),
        ("count_star", None)]).build()


@pytest.mark.parametrize("keys", [("flag",), ("flag", "status")])
def test_group_by_char_keys_equals_the_host(runner, keys):
    table, snap = _flags_table(8910 + len(keys), char(1))
    n0 = len(_launched(runner))
    got, want, res = _both(runner, _by_keys(table, keys), snap)
    assert sorted(got) == sorted(want) and len(got) == 3 * len(keys)
    assert all(isinstance(k, bytes) for r in got for k in r[3:])
    assert [ft.eval_type for ft in res.batch.schema] == \
        [EvalType.DECIMAL, EvalType.INT, EvalType.INT] + \
        [EvalType.BYTES] * len(keys)
    assert len(_launched(runner)) > n0          # the device served it
    assert runner.flight_recorder.agg_param_counts()["code_planes"] >= 1


def test_a_null_char_key_is_its_own_group_and_a_composite_one_goes_host(
        runner):
    table, snap = _flags_table(8913, char(1), null_at=7)
    got, want, _res = _both(runner, _by_keys(table, ("flag",)), snap)
    assert sorted(got, key=repr) == sorted(want, key=repr)
    assert [r for r in got if r[-1] is None]            # the NULL group
    # two keys, one with a NULL: SQL keeps (NULL, O) and (NULL, F)
    # apart and the grid has one NULL slot, so the host answers
    n0 = len(_launched(runner))
    got, want, _res = _both(runner, _by_keys(table, ("flag", "status")),
                            snap)
    assert sorted(got, key=repr) == sorted(want, key=repr)
    assert len(_launched(runner)) == n0


@pytest.mark.parametrize("case", ["char5", "ci", "function", "min"])
def test_what_has_no_code_plane_is_not_a_device_plan(runner, case):
    ft = {"char5": char(5), "ci": char(1, UTF8MB4_GENERAL_CI)}.get(
        case, char(1))
    table, snap = _flags_table(8920 + len(case), ft, n=200)
    s = DagSelect.from_table(table, ["flag", "status", "v"])
    if case == "function":
        dag = s.where(Expr.call("EqString", s.col("flag"), Expr.const(
            b"R", EvalType.BYTES))).aggregate(
            [], [("count_star", None)]).build()
    elif case == "min":
        dag = s.aggregate([], [("min", s.col("flag"))]).build()
    else:
        dag = _by_keys(table, ("flag", "status"))
    assert runner._analyze(dag) is None     # the host pipeline's
    assert BatchExecutorsRunner(dag, snap).handle_request().rows()


def test_a_value_without_a_code_sends_the_plan_to_the_host(runner):
    """The type says CHAR(1), a row holds two bytes: found where the
    plane is cut, and the strings stay with the host pipeline."""
    table, snap = _flags_table(8930, char(1), n=300)
    snap.columns[table["flag"].col_id].values[5] = "é".encode()
    n0 = len(_launched(runner))
    got, want, _res = _both(runner, _by_keys(table, ("flag",)), snap)
    assert sorted(got) == sorted(want) and len(got) == 4
    assert len(_launched(runner)) == n0


# ------------------------------------------------- AVG, MIN / MAX


def test_avg_of_a_decimal_stays_with_the_host_and_its_pair_does_not(
        runner):
    """This store's AVG answers the quotient, which the host's Decimals
    divide; what a SQL layer merges across regions is TiKV's (COUNT,
    SUM) pair, and that pair is a device plan."""
    n = 4000
    rng = np.random.default_rng(12)
    valid = rng.random(n) > 0.2
    table, snap = _table({
        "a": (FieldType(tp=FieldTypeTp.NEW_DECIMAL, flen=15, decimal=2),
              _dec(rng.integers(-10 ** 6, 10 ** 6, n), 2, valid)),
        "g": (FieldType.long(), Column(
            EvalType.INT, rng.integers(0, 7, n), np.ones(n, bool)))},
        n, 8940)
    s = DagSelect.from_table(table, ["a", "g"])
    avg = DagSelect.from_table(table, ["a", "g"]).aggregate(
        [s.col("g")], [("avg", s.col("a"))]).build()
    assert runner._analyze(avg) is None
    scaled = Expr.call("MultiplyDecimal", s.col("a"), _c("1.5"))
    pair = s.aggregate([s.col("g")], [
        ("count", s.col("a")), ("sum", s.col("a")),
        ("count", scaled), ("sum", scaled)]).build()
    plan = runner._analyze(pair)
    assert [sp.kind for sp in plan.specs] == ["count", "sum"] * 2
    assert plan.agg_recipes is None and plan.agg_fracs == [None, 2, None, 3]
    n0 = len(_launched(runner))
    got, want, res = _both(runner, pair, snap)
    assert sorted(got) == sorted(want) and len(got) == 7
    # the very Decimals: value AND exponent
    for g, w in zip(sorted(got), sorted(want)):
        assert [g[1].as_tuple(), g[3].as_tuple()] == \
            [w[1].as_tuple(), w[3].as_tuple()]
    assert [ft.eval_type for ft in res.batch.schema[:4]] == \
        [EvalType.INT, EvalType.DECIMAL] * 2
    assert len(_launched(runner)) > n0
    # without GROUP BY, and over no row at all: NULL, as the host says
    s = DagSelect.from_table(table, ["a", "g"])
    none = s.where(Expr.call("GtDecimal", s.col("a"), _c("99999999"))) \
        .aggregate([], [("count", s.col("a")), ("sum", s.col("a"))]).build()
    got, want, _res = _both(runner, none, snap)
    assert got == want == [(0, None)]


def test_min_max_of_a_decimal_stay_with_the_host(runner):
    table, snap = _table({"a": (DEC2, _dec(np.arange(50)))}, 50, 8941)
    s = DagSelect.from_table(table, ["a"])
    for kind in ("min", "max"):
        dag = DagSelect.from_table(table, ["a"]).aggregate(
            [], [(kind, s.col("a"))]).build()
        assert runner._analyze(dag) is None, kind
        (row,), = BatchExecutorsRunner(dag, snap).handle_request().rows()
        assert row == (D("0.00") if kind == "min" else D("0.49"))


# ------------------------------------------------- several int keys


def _int_keys(table_id: int, n: int, spans=(9, 6, 4)):
    from tikv_tpu.testing.fixture import int_table
    rng = np.random.default_rng(table_id)
    table = int_table(4, table_id=table_id)
    names = [c.name for c in table.columns if not c.is_pk_handle]
    ones = np.ones(n, bool)
    arrays = {name: Column(EvalType.INT, rng.integers(
        -5 * (i + 1), -5 * (i + 1) + span, n).astype(np.int64), ones)
        for i, (name, span) in enumerate(zip(names, spans))}
    arrays[names[3]] = Column(
        EvalType.INT, rng.integers(-1000, 1000, n).astype(np.int64), ones)
    return table, names, ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64), arrays)


@pytest.mark.parametrize("n_keys", [2, 3])
def test_multi_key_group_by_on_int_table_equals_the_host(runner, n_keys):
    """The composite key is not Q1's alone: ``int_table``'s int keys,
    two and three of them, an expression among them."""
    table, names, snap = _int_keys(8950 + n_keys, 6000)
    s = DagSelect.from_table(table, names)
    keys = [s.col(names[0]), Expr.call("PlusInt", s.col(names[1]),
                                       Expr.const(7, EvalType.INT)),
            s.col(names[2])][:n_keys]
    dag = s.where(s.col(names[3]) > -500).aggregate(keys, [
        ("count_star", None), ("sum", s.col(names[3])),
        ("avg", s.col(names[3]))]).build()
    plan = runner._analyze(dag)
    assert plan is not None and len(plan.key_rpns) == n_keys
    n0 = len(_launched(runner))
    got, want, res = _both(runner, dag, snap)
    assert sorted(got) == sorted(want)
    assert len(got) == (9 * 6, 9 * 6 * 4)[n_keys - 2]
    assert len(res.batch.schema) == 3 + n_keys
    assert len(_launched(runner)) > n0
    # MIN over a composite key has no additive body: the host's
    dag = DagSelect.from_table(table, names)
    dag = dag.aggregate([dag.col(names[0]), dag.col(names[1])],
                        [("min", dag.col(names[3]))]).build()
    assert runner._analyze(dag) is None


def test_a_composite_keys_memo_survives_what_stays_inside_its_bounds(runner):
    """``feed.roll_derived``'s proof holds each key to ITS bounds: a
    row inside them keeps the memo, a row outside one key's drops it."""
    from tikv_tpu.device import feed
    table, names, snap = _int_keys(8960, 500, spans=(3, 3, 2))
    s = DagSelect.from_table(table, names)
    dag = s.aggregate([s.col(names[0]), s.col(names[1])],
                      [("count_star", None)]).build()
    plan = runner._analyze(dag)
    infos = [plan.scan.columns[ci] for ci in plan.used_cols]
    meta = {"dtypes": ("int32", "int32"), "hash_bounds": (0, 9, (0,)),
            "key_bounds": ((-5, 3), (-10, 3))}

    def disproved(*rows):
        return feed._disproved(meta, plan, list(rows), 500,
                               runner._limb_variant)

    def span(a, b):
        return {"handles": np.array([1]), "cols": {
            infos[0].col_id: (np.array([a]), np.array([True])),
            infos[1].col_id: (np.array([b]), np.array([True]))}}

    assert not plan.lowered
    assert disproved(span(-4, -9)) is None
    assert disproved(span(-4, -7)) == "key"
    assert disproved(span(-2, -9)) == "key"
    nulled = span(-4, -9)
    nulled["cols"][infos[1].col_id] = (np.array([0]), np.array([False]))
    assert disproved(nulled) == "null_key"
    assert disproved(span(-4, 1 << 40)) == "dtype"


def test_key_spans_over_the_grid_leave_the_fused_kernel(monkeypatch):
    """Spans whose product is over MAX_SLOTS: ``agg_bodies`` does not
    name the kernel's dense mode for the plan, as for one key of that
    span."""
    from tikv_tpu.device import aggregate as agg_mod
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    table, names, snap = _int_keys(8970, 300, spans=(200, 100, 2))
    s = DagSelect.from_table(table, names)
    dag = s.aggregate([s.col(names[0]), s.col(names[1])],
                      [("count_star", None)]).build()
    plan = runner._analyze(dag)
    feed = {"n_pad": pallas_hash.BLOCK, "null_flags": (False, False)}
    from tikv_tpu.device.kernels import build_layouts
    layouts, p8, pf = build_layouts(plan.specs, [False], (0,), [False])
    args = (plan, feed, ("int32", "int32"), layouts, p8, pf)
    over, at = 2 * pallas_hash.MAX_SLOTS, pallas_hash.MAX_SLOTS
    assert 200 * 100 > at
    assert agg_mod.agg_bodies(True, 1, *args, over, "dense", False) == \
        ("hash_twolevel",)
    assert agg_mod.agg_bodies(True, 1, *args, at, "dense", False) == \
        ("pallas_hash", "hash_twolevel")
    assert not pallas_hash.supported(plan, feed, ("int32", "int32"), 0,
                                     over, 1, "dense")
    assert pallas_hash.supported(plan, feed, ("int32", "int32"), 0,
                                 at, 1, "dense")
    got, want, _res = _both(runner, dag, snap)
    assert sorted(got) == sorted(want)


@pytest.mark.parametrize("hi", [1 << 20, 1 << 31, 1 << 62])
def test_wide_composite_keys_never_share_a_group(runner, hi):
    """The composite key's number is formed in int64: spans whose
    product stays under 2^63 ride the stand-ins recoded, one that does
    not (two BIGINT ids) leaves the device.  Either way the groups and
    their keys are the host pipeline's."""
    n = 3000
    rng = np.random.default_rng(hi % 1000)
    table, names, _snap = _int_keys(8980, n)
    ones = np.ones(n, bool)
    # few distinct tuples, far apart: a wrapped number would merge them
    pools = [rng.integers(0, hi, 5), rng.integers(-hi, 0, 4)]
    arrays = {name: Column(EvalType.INT, rng.choice(pool, n), ones)
              for name, pool in zip(names, pools)}
    arrays[names[2]] = arrays[names[3]] = Column(
        EvalType.INT, rng.integers(-1000, 1000, n), ones)
    snap = ColumnarTable.from_arrays(table, np.arange(n, dtype=np.int64),
                                     arrays)
    s = DagSelect.from_table(table, names)
    dag = s.aggregate([s.col(names[0]), s.col(names[1])], [
        ("count_star", None), ("sum", s.col(names[3]))]).build()
    assert runner._analyze(dag) is not None
    n0 = len(_launched(runner))
    got, want, _res = _both(runner, dag, snap)
    assert sorted(got) == sorted(want) and len(got) == 20
    # 2^62 x 2^62 has no int64 number: the host answered
    assert (len(_launched(runner)) > n0) == (hi < 1 << 62)
