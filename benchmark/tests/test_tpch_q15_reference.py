"""``tpch_q15``: the reference on a hand-computed table of a dozen rows
over three suppliers and both edges of the window, and its control
(float32 products); ``digest`` and ``check`` on hand-made chunk replies
(the supplier set, the scale, the planes' kinds, a reply in rows); the
walk through the 58 DATEs; what ``prepare`` asks the program for; and the
table kind's generator on what Q15 reads of it."""

import datetime
import types

import numpy as np
import pytest

import byname

PARAMS = {"concurrency": 15, "regions": 2}


def day(y, m, d) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


# a dozen lineitems by hand: supplier, price in cents, discount in
# hundredths, ship date.  The validation window is [1996-01-01,
# 1996-04-01): rows 0..7 are in it (row 0 on its first day, row 7 on its
# last), row 8 ships the day before it, row 9 on the day it ends, rows 10
# and 11 far from it.  Row 1's price x (100 - discount) is past float32's
# 24 bits: 10494950 x 100.
ROWS = [
    (7, 2116823, 5, day(1996, 1, 1)),
    (7, 10494950, 0, day(1996, 2, 14)),
    (42, 90100, 10, day(1996, 1, 31)),
    (42, 3388099, 6, day(1996, 2, 29)),
    (42, 400000, 7, day(1996, 3, 1)),
    (9999, 2856000, 9, day(1996, 3, 15)),
    (9999, 7244317, 2, day(1996, 1, 2)),
    (7, 8211371, 1, day(1996, 3, 31)),
    (7, 1000000, 4, day(1995, 12, 31)),
    (42, 5123457, 3, day(1996, 4, 1)),
    (10_000, 1234567, 8, day(1993, 1, 1)),
    (1, 9999999, 10, day(1997, 12, 31)),
]
COLS = {name: np.array([r[i] for r in ROWS]) for i, name in enumerate(
    ("l_suppkey", "l_extendedprice", "l_discount", "l_shipdate"))}


def by_hand(kept) -> dict:
    """{supplier: revenue x 10^4} of the rows ``kept``, in Python ints."""
    out: dict = {}
    for i in kept:
        s, price, disc, _ship = ROWS[i]
        out[s] = out.get(s, 0) + price * (100 - disc)
    return out


def want(index, kept, exact=1) -> list:
    sums = by_hand(kept)
    return [index, exact] + sorted(sums) + [sums[s] for s in sorted(sums)]


def ctx(cols=COLS):
    return types.SimpleNamespace(rows=len(cols["l_suppkey"]), cols=cols)


def failing(checks):
    return [name for name, value, limit in checks if value > limit]


@pytest.fixture(scope="module")
def kind():
    return byname.load("requests", "tpch_q15")


@pytest.fixture(scope="module")
def table_kind():
    return byname.load("tables", "lineitem_presplit")


def test_the_dates_are_the_clauses(kind):
    assert len(kind.DATES) == 58 and len(set(kind.DATES)) == 58
    assert kind.DATES[0] == (1993, 1) and kind.DATES[-1] == (1997, 10)
    assert kind.DATES[kind.VALIDATION] == (1996, 1)
    assert kind.month_start(1996, 1, 3) == (1996, 4)
    assert kind.month_start(1997, 10, 3) == (1998, 1)
    assert kind.month_start(1995, 11, 2) == (1996, 1)
    assert (kind.SCALE, kind.SUPPLIERS, kind.GRID) == (4, 10_000, 16_384)
    assert kind.CLASSES == ("pallas_hash",)


def test_reference_by_hand(kind):
    v = kind.VALIDATION
    got = list(kind.reference(ctx(), PARAMS))
    assert got == want(v, range(8))
    # three suppliers, both edges of the window inside it
    assert got[2:5] == [7, 42, 9999]
    assert got[5] == 2116823 * 95 + 10494950 * 100 + 8211371 * 99
    by_month = kind.sums_by_month(ctx())
    for date, kept in (((1995, 10), [8]), ((1995, 11), [0, 2, 6, 8]),
                       ((1995, 12), [0, 1, 2, 3, 6, 8]),
                       ((1996, 1), range(8)),
                       ((1996, 2), [1, 3, 4, 5, 7, 9]),
                       ((1996, 4), [9]), ((1993, 1), [10]),
                       ((1997, 10), [11]), ((1994, 6), [])):
        index = kind.DATES.index(date)
        assert list(kind.answer(index, *kind.revenue(index, by_month))) == \
            want(index, kept), date
    # a table no row of which ships in the window has no supplier
    late = dict(COLS, l_shipdate=np.full(len(ROWS), day(1998, 6, 1)))
    assert list(kind.reference(ctx(late), PARAMS)) == [v, 1]


def test_the_control_is_caught_by_the_answer_alone(kind):
    served = {"answer": kind.reference(ctx(), PARAMS, approx=True).tobytes()}
    got = np.frombuffer(served["answer"], np.int64)
    exact = kind.reference(ctx(), PARAMS)
    # float32 products: the supplier set holds, supplier 7's sum does not
    assert len(got) == len(exact) and not np.array_equal(got, exact)
    assert list(got[:5]) == list(exact[:5]) and got[5] != exact[5]
    checks = kind.check(ctx(), [served], PARAMS, exact)
    assert failing(checks) == ["tpch_q15.wrong_answers"]
    good = {"answer": exact.tobytes()}
    assert failing(kind.check(ctx(), [good], PARAMS, None)) == []


def chunk_of(kept) -> dict:
    """A region's reply over the rows ``kept`` as the client hands it on:
    a decoded chunk, the sum plane at scale 4, then the key plane."""
    sums = by_hand(kept)
    keys = sorted(sums, reverse=True)       # a store orders by nothing
    return {"chunk": {"n": len(keys), "cols": [
        {"t": "i8", "frac": 4,
         "v": np.array([sums[k] for k in keys], np.int64)},
        {"t": "i8", "v": np.array(keys, np.int64)}]}}


def reply(parts, index):
    return {"responses": [chunk_of(kept) for kept in parts],
            "tpch_q15_date": index}


def test_digest_adds_the_partials_by_supplier(kind):
    v = kind.VALIDATION
    got = kind.digest(ctx(), reply([[0, 3, 5], [], [1, 2, 4, 6, 7]], v),
                      PARAMS)
    assert list(np.frombuffer(got, np.int64)) == want(v, range(8))
    rec = {"answer": got, "labels": {"cop_tasks": "2"}}
    assert failing(kind.check(ctx(), [rec], PARAMS, None)) == []
    assert "wrong" not in rec

    def wrong(resp) -> bool:
        rec = {"answer": kind.digest(ctx(), resp, PARAMS)}
        names = failing(kind.check(ctx(), [rec], PARAMS, None))
        assert names in ([], ["tpch_q15.wrong_answers"])
        return bool(names) and rec["wrong"]

    assert not wrong(reply([range(8)], v))
    # a missing supplier, an extra one, a line under another supplier
    assert wrong(reply([[0, 1, 2, 3, 4, 7]], v))
    assert wrong(reply([range(9)], v) | {"responses": [
        chunk_of(range(8)), chunk_of([10])]})
    resp = reply([range(8)], v)
    resp["responses"][0]["chunk"]["cols"][1]["v"][0] = 43
    assert wrong(resp)

    def spoiled(change):
        resp = reply([range(8)], v)
        change(resp["responses"][0])
        return resp

    def rows(r):
        cols = r.pop("chunk")["cols"]
        r["rows"] = [list(row) for row in zip(*(c["v"].tolist()
                                                for c in cols))]

    def floats(r):
        c = r["chunk"]["cols"][0]
        c["t"], c["v"] = "f8", c["v"].astype(np.float64)

    def scale_2(r):
        r["chunk"]["cols"][0]["frac"] = 2

    def no_scale(r):
        del r["chunk"]["cols"][0]["frac"]

    def a_null(r):
        r["chunk"]["cols"][0]["ok"] = np.array([True, True, False])

    def key_0(r):
        r["chunk"]["cols"][1]["v"][-1] = 0

    def key_10001(r):
        r["chunk"]["cols"][1]["v"][0] = 10_001

    def twice(r):
        r["chunk"]["cols"][1]["v"][1] = r["chunk"]["cols"][1]["v"][0]

    def raw_buffers(r):
        for c in r["chunk"]["cols"]:
            c["v"] = c["v"].tobytes()

    for change in (rows, floats, scale_2, no_scale, a_null, key_0,
                   key_10001, twice, raw_buffers):
        assert wrong(spoiled(change)), change.__name__
    # every record is held to the reference for ITS date
    feb = kind.DATES.index((1996, 2))
    assert wrong(reply([range(8)], feb))
    assert not wrong(reply([[1, 3, 4, 5, 7, 9]], feb))
    # ... and to the layout
    rec = {"answer": kind.reference(ctx(), PARAMS).tobytes(),
           "labels": {"cop_tasks": "3"}}
    assert failing(kind.check(ctx(), [rec], PARAMS, None)) == \
        ["regions.reads_off_the_layout"]


def test_clients_walk_all_dates_fourteen_apart(kind):
    clients = [types.SimpleNamespace() for _ in range(4)]
    firsts = [kind.next_date(c) for c in clients]
    assert [(b - a) % 58 for a, b in zip(firsts, firsts[1:])] == [14] * 3
    walked = [firsts[0]] + [kind.next_date(clients[0]) for _ in range(57)]
    assert sorted(walked) == list(range(58))
    assert kind.next_date(clients[0]) == firsts[0]


def test_prepare_asks_the_program_by_name(kind, monkeypatch):
    """A program without chunk replies, or whose fused kernel stops under
    16,384 slots, ends the run before the first read; the kernel's cap is
    read from its source, since the load generator must not import JAX."""
    import sys

    from tikv_tpu.server import wire
    assert kind.kernel_max_slots() >= kind.GRID
    if "jax" in sys.modules:
        from tikv_tpu.device import pallas_hash
        assert kind.kernel_max_slots() == pallas_hash.MAX_SLOTS
    monkeypatch.setattr(kind, "kernel_max_slots", lambda: 4096)
    with pytest.raises(SystemExit, match="4096 slots"):
        kind.prepare(ctx(), None, PARAMS)
    monkeypatch.undo()
    monkeypatch.delattr(wire, "chunk_rows")
    with pytest.raises(SystemExit, match="chunk replies"):
        kind.prepare(ctx(), None, PARAMS)


SPEC = {"scale_factor": 1, "regions": 12, "region_split_size_mb": 96,
        "table_id": 9915}


def test_the_generators_table_as_q15_reads_it(kind, table_kind):
    n = 500_102                 # one region's rows
    c = table_kind.make(SPEC, 2600000027, n)
    got = kind.reference(types.SimpleNamespace(cols=c), PARAMS)
    groups = (len(got) - 2) // 2
    keys, sums = got[2:2 + groups], got[2 + groups:]
    # the clause's key domain, and a window of three months: 3.7-3.8% of
    # the rows, over 10,000 suppliers ~8,500 groups a region
    assert c["l_suppkey"].min() == 1 and c["l_suppkey"].max() == 10_000
    ship = c["l_shipdate"].astype(np.int64)
    keep = (ship >= day(1996, 1, 1)) & (ship < day(1996, 4, 1))
    assert 0.035 < keep.mean() < 0.040
    assert 8_200 < groups < 8_700
    assert list(keys) == sorted(set(c["l_suppkey"][keep].tolist()))
    price = c["l_extendedprice"].astype(np.int64)[keep]
    rev = price * (100 - c["l_discount"][keep])
    assert rev.max() < 2 ** 31 and price.max() > 2 ** 23    # one product
    assert int(sums.sum()) == int(rev.sum())
    # every DATE keeps other rows: 58 distinct answers
    by_month = kind.sums_by_month(types.SimpleNamespace(cols=c))
    assert len({kind.answer(i, *kind.revenue(i, by_month)).tobytes()
                for i in range(len(kind.DATES))}) == 58
    # the control comes out wrong on the generator's table too
    approx = kind.reference(types.SimpleNamespace(cols=c), PARAMS,
                            approx=True)
    assert len(approx) == len(got) and not np.array_equal(approx, got)
