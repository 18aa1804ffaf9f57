"""``tpch_q1``: the reference on a hand-computed table of a dozen rows over
all four populated groups and its control (float32 products), ``digest``
and ``check`` on hand-made replies (the group set, the scales, the
(COUNT, SUM) pairs), the walk through the 61 DELTAs, and the table kind's
generator on what Q1 reads of it."""

import datetime
import decimal
import types

import numpy as np
import pytest

import byname

D = decimal.Decimal
PARAMS = {"concurrency": 15, "regions": 2}
R, A, N = 0, 1, 2           # indices into TEXTS["l_returnflag"]
O, F = 0, 1                 # ... and ["l_linestatus"]


def day(y, m, d) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


END = day(1998, 12, 1)

# a dozen lineitems by hand: quantity x 100, price in cents, discount and
# tax in hundredths, flag, status, days shipped before 1998-12-01.  With
# DELTA 90 rows 0..9 are kept (row 6, 90 days before the end date, from
# DELTA 90 down); row 10 ships 89 days before it (kept from DELTA 89
# down), row 11 ships 60 days before it (DELTA 60 alone).  Row 1's price
# x (100 - discount) x (100 + tax) is past float32's 24 bits and past
# int32: 10494950 x 100 x 108.
ROWS = [
    # qty, price, disc, tax, flag, status, days before
    (1700, 2116823, 5, 2, A, F, 1500),
    (5000, 10494950, 0, 8, A, F, 1400),
    (100, 90100, 10, 0, A, F, 1300),
    (2400, 3388099, 6, 1, N, F, 1200),
    (300, 400000, 7, 3, N, F, 1250),
    (2800, 2856000, 9, 4, N, O, 400),
    (3600, 7244317, 2, 6, N, O, 90),
    (4400, 8211371, 1, 7, N, O, 120),
    (1000, 1000000, 4, 5, R, F, 1350),
    (2300, 5123457, 3, 8, R, F, 1450),
    (900, 1234567, 8, 2, N, O, 89),
    (5000, 9999999, 10, 8, N, O, 60),
]
COLS = {name: np.array([r[i] for r in ROWS]) for i, name in enumerate(
    ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
     "l_returnflag", "l_linestatus"))}
COLS["l_shipdate"] = np.array([END - r[6] for r in ROWS])


def by_hand(rows) -> list:
    """A group's 13 integers from its rows, in Python ints."""
    qty = sum(r[0] for r in rows)
    price = sum(r[1] for r in rows)
    disc_price = sum(r[1] * (100 - r[2]) for r in rows)
    charge = sum(r[1] * (100 - r[2]) * (100 + r[3]) for r in rows)
    disc = sum(r[2] for r in rows)
    n = len(rows)
    return [qty, price, disc_price, charge, n, qty, n, price, n, disc, n]


def want(kept) -> list:
    """``answer``'s groups for the rows ``kept`` (indices into ROWS)."""
    out = []
    for flag, status in ((b"A", b"F"), (b"N", b"F"), (b"N", b"O"),
                         (b"R", b"F")):
        rows = [ROWS[i] for i in kept
                if (b"RAN"[ROWS[i][4]], b"OF"[ROWS[i][5]]) ==
                (flag[0], status[0])]
        if rows:
            out += [flag[0], status[0]] + by_hand(rows)
    return out


def ctx(cols=COLS):
    return types.SimpleNamespace(rows=len(cols["l_quantity"]), cols=cols)


def failing(checks):
    return [name for name, value, limit in checks if value > limit]


@pytest.fixture(scope="module")
def kind():
    return byname.load("requests", "tpch_q1")


@pytest.fixture(scope="module")
def table_kind():
    return byname.load("tables", "lineitem_presplit")


def test_the_deltas_are_the_clauses(kind):
    assert kind.DELTAS == tuple(range(60, 121)) and len(kind.DELTAS) == 61
    assert kind.DELTAS[kind.VALIDATION] == 90
    assert kind.cutoff(90) == day(1998, 9, 2) == END - 90
    assert (kind.FLAGS, kind.STATUS) == ((b"R", b"A", b"N"), (b"O", b"F"))
    assert [name for name, _s in kind.ROW][:4] == [
        "sum_qty", "sum_base_price", "sum_disc_price", "sum_charge"]
    assert [s for _n, s in kind.ROW] == [2, 2, 4, 6, None, 2, None, 2,
                                         None, 2, None]


def test_reference_by_hand(kind):
    got = list(kind.reference(ctx(), PARAMS))
    assert got == [kind.VALIDATION, 1] + want(range(10))
    # all four populated groups, and the one product past int32
    assert [got[i] for i in range(2, len(got), 13)] == [65, 78, 78, 82]
    assert 10494950 * 100 * 108 > 2 ** 31 and got[2 + 5] == sum(
        r[1] * (100 - r[2]) * (100 + r[3]) for r in ROWS[:3])
    by_day = kind.sums_by_day(ctx())
    early = [0, 1, 2, 3, 4, 5, 7, 8, 9]        # row 6: from DELTA 90 down
    for delta, kept in ((120, early), (91, early), (90, range(10)),
                        (89, range(11)), (61, range(11)), (60, range(12))):
        index = kind.DELTAS.index(delta)
        assert list(kind.answer(index, by_day)) == [index, 1] + want(kept), \
            delta
    # a table no row of which ships in time has no group
    late = dict(COLS, l_shipdate=np.full(len(ROWS), END))
    assert list(kind.reference(ctx(late), PARAMS)) == [kind.VALIDATION, 1]


def test_the_control_is_caught_by_the_answer_alone(kind):
    served = {"answer": kind.reference(ctx(), PARAMS, approx=True).tobytes()}
    got = np.frombuffer(served["answer"], np.int64)
    exact = kind.reference(ctx(), PARAMS)
    # float32 products: the counts and the plain sums hold, a product
    # does not
    assert len(got) == len(exact) and not np.array_equal(got, exact)
    assert got[2 + 2] == exact[2 + 2] and got[2 + 5] != exact[2 + 5]
    checks = kind.check(ctx(), [served], PARAMS, exact)
    assert failing(checks) == ["tpch_q1.wrong_answers"]
    good = {"answer": exact.tobytes()}
    assert failing(kind.check(ctx(), [good], PARAMS, None)) == []


def rows_of(kind, kept) -> list:
    """A region's reply over the rows ``kept``: a row a group,
    aggregates then keys, as the store sends them."""
    out = []
    groups = want(kept)
    for i in range(0, len(groups), 13):
        flag, status, *vals = groups[i:i + 13]
        out.append([v if scale is None else D(v).scaleb(-scale)
                    for v, (_n, scale) in zip(vals, kind.ROW)] +
                   [bytes([flag]), bytes([status])])
    return out


def reply(kind, parts, index):
    return {"responses": [{"rows": rows_of(kind, kept)} for kept in parts],
            "tpch_q1_delta": index}


def test_digest_merges_the_partials_by_group(kind):
    v = kind.VALIDATION
    got = kind.digest(
        ctx(), reply(kind, [[0, 3, 5, 8], [], [1, 2, 4, 6, 7, 9]], v), PARAMS)
    assert list(np.frombuffer(got, np.int64)) == [v, 1] + want(range(10))
    rec = {"answer": got, "labels": {"cop_tasks": "2"}}
    assert failing(kind.check(ctx(), [rec], PARAMS, None)) == []
    assert "wrong" not in rec

    def wrong(resp) -> bool:
        rec = {"answer": kind.digest(ctx(), resp, PARAMS)}
        names = failing(kind.check(ctx(), [rec], PARAMS, None))
        assert names in ([], ["tpch_q1.wrong_answers"])
        return bool(names) and rec["wrong"]

    assert not wrong(reply(kind, [range(10)], v))
    # a missing group, an extra group, a group split under another key
    assert wrong(reply(kind, [[0, 1, 2, 3, 4, 5, 6, 7]], v))
    assert wrong(reply(kind, [range(11)], v))
    resp = reply(kind, [range(10)], v)
    resp["responses"][0]["rows"][0][-1] = b"O"
    assert wrong(resp)
    # a float, an integer or another scale is not the exact DECIMAL; a
    # key that is not the column's bytes is not the key
    for j, bad in ((3, float), (3, int), (2, lambda d: d.quantize(D("0.01"))),
                   (0, lambda d: d.quantize(D("0.001"))), (4, float),
                   (11, lambda b: b.decode()), (11, lambda b: b[0])):
        resp = reply(kind, [range(10)], v)
        row = resp["responses"][0]["rows"][1]
        row[j] = bad(row[j])
        assert wrong(resp), (j, bad)
    # every record is held to the reference for ITS delta
    assert wrong(reply(kind, [range(10)], kind.DELTAS.index(89)))
    assert not wrong(reply(kind, [range(11)], kind.DELTAS.index(89)))
    # ... and to the layout
    rec = {"answer": kind.reference(ctx(), PARAMS).tobytes(),
           "labels": {"cop_tasks": "3"}}
    assert failing(kind.check(ctx(), [rec], PARAMS, None)) == \
        ["regions.reads_off_the_layout"]


def test_clients_walk_all_deltas_fifteen_apart(kind):
    clients = [types.SimpleNamespace() for _ in range(4)]
    firsts = [kind.next_delta(c) for c in clients]
    assert [(b - a) % 61 for a, b in zip(firsts, firsts[1:])] == [15] * 3
    walked = [firsts[0]] + [kind.next_delta(clients[0]) for _ in range(60)]
    assert sorted(walked) == list(range(61))
    assert kind.next_delta(clients[0]) == firsts[0]


SPEC = {"scale_factor": 1, "regions": 12, "region_split_size_mb": 96,
        "table_id": 9908}


def test_the_generators_table_as_q1_reads_it(kind, table_kind):
    n = 200_000
    c = table_kind.make(SPEC, 2600000027, n)
    got = kind.reference(types.SimpleNamespace(cols=c), PARAMS)
    # the four groups TPC-H's population gives, A-F N-F N-O R-F
    groups = [(chr(got[i]), chr(got[i + 1])) for i in range(2, len(got), 13)]
    assert groups == [("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]
    ship = c["l_shipdate"].astype(np.int64)
    keep = ship <= END - 90
    assert 0.97 < keep.mean() < 0.995           # about 98% of the rows
    assert sum(got[i + 2 + 10] for i in range(2, len(got), 13)) == keep.sum()
    price = c["l_extendedprice"].astype(np.int64)[keep]
    charge = price * (100 - c["l_discount"][keep]) * (100 + c["l_tax"][keep])
    assert charge.max() > 2 ** 31       # what the limbs are for
    assert sum(got[i + 2 + 3] for i in range(2, len(got), 13)) == \
        int(charge.sum())
    # every DELTA keeps other rows: 61 distinct answers
    by_day = kind.sums_by_day(types.SimpleNamespace(cols=c))
    assert len({kind.answer(i, by_day).tobytes()
                for i in range(len(kind.DELTAS))}) == 61
    # the control comes out wrong on the generator's table too
    approx = kind.reference(types.SimpleNamespace(cols=c), PARAMS,
                            approx=True)
    assert not np.array_equal(approx, got)
