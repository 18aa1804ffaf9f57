"""``tpch_q6``: the reference on a hand-computed table and its control
(float32 products), ``digest`` and ``check`` on hand-made replies, the walk
through the 80 substitution tuples; the table kind ``lineitem_presplit``'s
generator against Clause 4.2.3's ranges, and the same seed, the same
table."""

import datetime
import decimal
import types

import numpy as np
import pytest

import byname

D = decimal.Decimal
PARAMS = {"concurrency": 15, "regions": 2}


def day(y, m, d) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


# six lineitems by hand: quantity x 100, price in cents, discount in
# hundredths, ship date.  For the validation tuple (1994, 0.06, 24) rows
# 0 and 1 pass: 1994, discount in 0.05..0.07, quantity under 24.
COLS = {
    "l_quantity": np.array([1700, 2399, 2400, 100, 500, 2300]),
    "l_extendedprice": np.array([2116823, 10494950, 99, 500000, 7, 333]),
    "l_discount": np.array([5, 7, 6, 6, 4, 6]),
    "l_shipdate": np.array([day(1994, 1, 1), day(1994, 12, 31),
                            day(1994, 6, 1), day(1995, 1, 1),
                            day(1994, 6, 1), day(1993, 12, 31)]),
}
WANT = 2116823 * 5 + 10494950 * 7      # revenue x 10^4, by hand


def ctx(cols=COLS):
    return types.SimpleNamespace(rows=len(cols["l_quantity"]), cols=cols)


def failing(checks):
    return [name for name, value, limit in checks if value > limit]


@pytest.fixture(scope="module")
def kind():
    return byname.load("requests", "tpch_q6")


@pytest.fixture(scope="module")
def table_kind():
    return byname.load("tables", "lineitem_presplit")


def test_the_tuples_are_the_clauses(kind):
    assert len(kind.TUPLES) == len(set(kind.TUPLES)) == 80
    assert {t[0] for t in kind.TUPLES} == set(range(1993, 1998))
    assert {t[1] for t in kind.TUPLES} == set(range(2, 10))
    assert {t[2] for t in kind.TUPLES} == {24, 25}
    assert kind.TUPLES[kind.VALIDATION] == (1994, 6, 24)
    for y in range(1990, 2000):
        assert kind.days(y) == day(y, 1, 1)


def test_reference_by_hand(kind):
    index, total, exact = kind.reference(ctx(), PARAMS)
    assert (index, total, exact) == (kind.VALIDATION, WANT, 1)
    # quantity 25 lets row 2 in; another year, another answer
    assert kind.revenue(ctx(), kind.TUPLES.index((1994, 6, 25))) == \
        WANT + 99 * 6
    assert kind.revenue(ctx(), kind.TUPLES.index((1995, 6, 24))) == \
        500000 * 6
    assert kind.revenue(ctx(), 0, tup=(2005, 6, 24)) == 0


def test_the_control_is_caught_by_the_answer_alone(kind):
    served = {"answer": kind.reference(ctx(), PARAMS, approx=True).tobytes()}
    # 10494950 x 7 is past float32's 24 bits: the control is not exact
    assert np.frombuffer(served["answer"], np.int64)[1] != WANT
    checks = kind.check(ctx(), [served], PARAMS, kind.reference(ctx(), PARAMS))
    assert failing(checks) == ["tpch_q6.wrong_answers"]
    good = {"answer": kind.reference(ctx(), PARAMS).tobytes()}
    assert failing(kind.check(ctx(), [good], PARAMS, None)) == []


def reply(partials, index):
    return {"responses": [{"rows": [[p]]} for p in partials],
            "tpch_q6_tuple": index}


def test_digest_adds_the_partials_exactly(kind):
    left, right = D(2116823 * 5).scaleb(-4), D(10494950 * 7).scaleb(-4)
    got = kind.digest(ctx(), reply([left, None, right], kind.VALIDATION),
                      PARAMS)
    assert list(np.frombuffer(got, np.int64)) == [kind.VALIDATION, WANT, 1]
    rec = {"answer": got, "labels": {"cop_tasks": "2"}}
    assert failing(kind.check(ctx(), [rec], PARAMS, None)) == []
    assert "wrong" not in rec
    # a float, an integer or another scale is not the exact DECIMAL
    for bad in (float(left), int(left), left.quantize(D("0.01"))):
        got = kind.digest(ctx(), reply([bad, right], kind.VALIDATION), PARAMS)
        rec = {"answer": got}
        assert failing(kind.check(ctx(), [rec], PARAMS, None)) == \
            ["tpch_q6.wrong_answers"]
        assert rec["wrong"]
    # every record is held to the reference for ITS tuple
    other = kind.TUPLES.index((1995, 6, 24))
    rec = {"answer": kind.digest(ctx(), reply([left, right], other), PARAMS)}
    assert failing(kind.check(ctx(), [rec], PARAMS, None)) == \
        ["tpch_q6.wrong_answers"]
    # ... and to the layout
    rec = {"answer": kind.reference(ctx(), PARAMS).tobytes(),
           "labels": {"cop_tasks": "3"}}
    assert failing(kind.check(ctx(), [rec], PARAMS, None)) == \
        ["regions.reads_off_the_layout"]


def test_clients_walk_all_tuples_twenty_apart(kind):
    clients = [types.SimpleNamespace() for _ in range(4)]
    firsts = [kind.next_tuple(c) for c in clients]
    assert [(b - a) % 80 for a, b in zip(firsts, firsts[1:])] == [20] * 3
    walked = [firsts[0]] + [kind.next_tuple(clients[0]) for _ in range(79)]
    assert sorted(walked) == list(range(80))
    assert kind.next_tuple(clients[0]) == firsts[0]


SPEC = {"scale_factor": 1, "regions": 12, "region_split_size_mb": 96,
        "table_id": 9906}


def test_generator_follows_the_clause(table_kind):
    n = 200_000
    c = table_kind.make(SPEC, 2600000027, n)
    assert all(len(c[name]) == n for name, _i, _k in table_kind.COLUMNS)
    qty, price = c["l_quantity"], c["l_extendedprice"].astype(np.int64)
    assert qty.min() == 100 and qty.max() == 5000 and not (qty % 100).any()
    part = c["l_partkey"].astype(np.int64)
    retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    assert np.array_equal(price, qty // 100 * retail)
    assert price.max() <= 10_494_950 + 50 and price.max() < 2 ** 31
    assert (c["l_discount"].min(), c["l_discount"].max()) == (0, 10)
    assert (c["l_tax"].min(), c["l_tax"].max()) == (0, 8)
    assert 1 <= part.min() and part.max() <= 200_000
    assert 1 <= c["l_suppkey"].min() and c["l_suppkey"].max() <= 10_000
    # orders of 1-7 lines, numbered from 1; sparse order keys
    ln, ok = c["l_linenumber"], c["l_orderkey"]
    assert (ln.min(), ln.max()) == (1, 7)
    assert np.all(np.diff(ok) >= 0) and np.all((ok & 31) < 8)
    assert np.all((np.diff(ok) == 0) == (np.diff(ln.astype(int)) == 1))
    # the dates hang on the order date
    ship, commit, receipt = (c[k].astype(np.int64) for k in (
        "l_shipdate", "l_commitdate", "l_receiptdate"))
    assert ship.min() >= day(1992, 1, 2)
    assert ship.max() <= day(1998, 12, 31) - 151 + 121
    assert np.all((receipt - ship >= 1) & (receipt - ship <= 30))
    assert np.all(np.abs(commit - ship) <= 121)
    today = day(1995, 6, 17)
    assert np.all((c["l_linestatus"] == 0) == (ship > today))
    assert np.all((c["l_returnflag"] == 2) == (receipt > today))
    assert set(np.unique(c["l_shipinstruct"])) == set(range(4))
    assert set(np.unique(c["l_shipmode"])) == set(range(7))
    lens = {len(t) for t in c["_comments"]}
    assert min(lens) >= 10 and max(lens) <= 43
    # Q6's selectivity: about 2% of the rows for the validation tuple
    kind = byname.load("requests", "tpch_q6")
    ship_ok = (ship >= day(1994, 1, 1)) & (ship < day(1995, 1, 1))
    keep = ship_ok & (c["l_discount"] >= 5) & (c["l_discount"] <= 7) & \
        (qty < 2400)
    assert 0.012 < keep.mean() < 0.026
    assert kind.revenue(types.SimpleNamespace(cols=c), kind.VALIDATION) == \
        int((price[keep] * c["l_discount"][keep].astype(np.int64)).sum())
    # the dates as the store holds them
    y, m, d = table_kind.civil_from_days(ship[:1000])
    assert [day(int(a), int(b), int(e)) for a, b, e in zip(y, m, d)] == \
        list(ship[:1000])
    assert table_kind.days_from_civil(1995, 6, 17) == today


def test_same_seed_same_table(table_kind):
    a = table_kind.make(SPEC, 7, 5000)
    b = table_kind.make(SPEC, 7, 5000)
    c = table_kind.make(SPEC, 8, 5000)
    for name, _i, _k in table_kind.COLUMNS:
        assert np.array_equal(a[name], b[name]), name
    assert a["_comments"] == b["_comments"]
    assert not np.array_equal(a["l_extendedprice"], c["l_extendedprice"])
    # a table is a prefix of a longer one from the same seed only in its
    # order structure; what matters: exactly ``rows`` rows come back
    assert len(table_kind.make(SPEC, 7, 1)["l_orderkey"]) == 1
