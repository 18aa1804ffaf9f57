"""``tpch_q1_refresh``: the reference by hand on ``tpch_q1``'s dozen rows
plus a refresh log of three transactions: an order committed after a
read's TSO is absent from its answer, one committed AT it is present, a
deleted order is gone, an order is whole or absent; both controls (the
products in float32, the read served one transaction stale) differ; the
stream of new orders and RF2's walk over the table's head."""

import types

import numpy as np
import pytest

import byname
from test_tpch_q1_reference import COLS, PARAMS, ROWS, A, F, N, O, by_hand, \
    END

KIND = byname.load("requests", "tpch_q1_refresh")
Q1 = KIND._q1
NAMES = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
         "l_returnflag", "l_linestatus")


def ctx():
    return types.SimpleNamespace(cols=COLS, rows=len(ROWS))


def order(rows) -> dict:
    cols = {name: np.array([r[i] for r in rows])
            for i, name in enumerate(NAMES)}
    cols["l_shipdate"] = np.array([END - r[6] for r in rows])
    return cols


# a new order of two lines in group (A, F), shipped long ago; the second
# line's product is past float32's 24 bits
NEW = [(700, 1500000, 3, 4, A, F, 1000), (5000, 10494950, 1, 7, A, F, 999)]
# the table's first two rows are its oldest order
OLD = ROWS[:2]
LOG = [(100, +1, order(NEW)), (200, -1, order(OLD)),
       (300, +1, order([(100, 90000, 0, 0, N, O, 500)]))]


def groups(answer) -> dict:
    """{(flag byte, status byte): the group's 11 integers}."""
    body = list(answer[3:])
    return {tuple(int(v) for v in body[i:i + 2]):
            [int(v) for v in body[i + 2:i + 13]]
            for i in range(0, len(body), 13)}


def hand(rows, delta=90) -> dict:
    out = {}
    for flag in (0, 1, 2):
        for status in (0, 1):
            mine = [r for r in rows if r[4] == flag and r[5] == status
                    and r[6] >= delta]
            if mine:
                key = (Q1.FLAGS[flag][0], Q1.STATUS[status][0])
                out[key] = by_hand(mine)
    return out


@pytest.mark.parametrize("start_ts,rows", [
    (99, ROWS),                                     # before every commit
    (100, ROWS + NEW),                              # AT the first commit
    (199, ROWS + NEW),
    (200, ROWS[2:] + NEW),                          # the old order is gone
    (10 ** 18, ROWS[2:] + NEW + [(100, 90000, 0, 0, N, O, 500)]),
])
def test_a_read_answers_the_table_as_of_its_tso(start_ts, rows):
    got = KIND.references(ctx(), LOG, [(start_ts, Q1.VALIDATION)])[
        start_ts, Q1.VALIDATION]
    assert list(got[:3]) == [start_ts, Q1.VALIDATION, 1]
    assert groups(got) == hand(rows)


def test_an_order_is_whole_or_absent():
    """Half of the new order (one line of two) is no reference's answer
    at any TSO."""
    half = hand(ROWS + NEW[:1])
    for start_ts in (0, 99, 100, 150, 200, 300):
        got = KIND.references(ctx(), LOG, [(start_ts, Q1.VALIDATION)])[
            start_ts, Q1.VALIDATION]
        assert groups(got) != half


def test_the_log_need_not_be_in_commit_order():
    reads = [(250, 0), (100, 30), (99, 60), (300, Q1.VALIDATION)]
    want = KIND.references(ctx(), LOG, reads)
    got = KIND.references(ctx(), LOG[::-1], reads[::-1])
    assert set(got) == set(want)
    assert all(np.array_equal(got[k], want[k]) for k in want)


def test_both_controls_differ_from_the_reference():
    reads = [(150, Q1.VALIDATION), (250, Q1.VALIDATION)]
    exact = KIND.references(ctx(), LOG, reads)
    approx = KIND.references(ctx(), LOG, reads, approx=True)
    stale = KIND.references(ctx(), LOG, reads, stale=True)
    for k in reads:
        assert not np.array_equal(exact[k], approx[k])
        assert not np.array_equal(exact[k], stale[k])
    # stale by one: at 150 the table as loaded, at 250 the table at 150
    assert groups(stale[reads[0]]) == hand(ROWS)
    assert groups(stale[reads[1]]) == hand(ROWS + NEW)
    # ... and ``check`` counts each
    served = [{"answer": stale[k].tobytes()} for k in reads]
    assert KIND.wrong_answers(ctx(), served, LOG, "2") == (2, 0)
    served = [{"answer": exact[k].tobytes(), "labels": {"cop_tasks": "3"}}
              for k in reads]
    assert KIND.wrong_answers(ctx(), served, LOG, "2") == (0, 2)
    assert all(r["wrong"] for r in served)


def test_digest_puts_the_tso_before_q1s_shape():
    resp = {"responses": [], "tpch_q1_delta": 7,
            "tpch_q1_refresh_start_ts": 449999999999999999}
    got = np.frombuffer(KIND.digest(ctx(), resp, PARAMS), np.int64)
    assert list(got) == [449999999999999999, 7, 1]
    assert list(KIND.reference(ctx(), PARAMS)[:3]) == [0, Q1.VALIDATION, 1]


def test_the_stream_of_new_orders_and_the_walk_over_the_head():
    """New orders are whole orders of 1-7 lines drawn as the table's
    are, take the rowids after the table's last and the order keys after
    its last; RF2 takes the table's orders from its head, each once."""
    lineitem = byname.load("tables", "lineitem_presplit")
    spec = {"scale_factor": 1}
    cols = lineitem.make(spec, 4500000011, 3000)
    state = KIND._Refresh(types.SimpleNamespace(cols=cols, rows=3000),
                          {"scale_factor": 1})
    again = KIND._Refresh(types.SimpleNamespace(cols=cols, rows=3000),
                          {"scale_factor": 1})
    keys = cols["l_orderkey"]
    next_rowid, seen_keys = 3000, set(keys.tolist())
    for _ in range(50):
        rowid, key, pool, lo, hi = state.new_order()
        assert again.new_order()[:2] == (rowid, key)    # --seed's stream
        assert rowid == next_rowid and 1 <= hi - lo <= 7
        next_rowid += hi - lo
        assert key > int(keys[-1]) and key not in seen_keys
        assert key & 31 < 8                 # Clause 4.2.3's sparse keys
        seen_keys.add(key)
        assert len(set(pool["l_orderkey"][lo:hi].tolist())) == 1
        assert list(pool["l_linenumber"][lo:hi]) == \
            list(range(1, hi - lo + 1))
        row = KIND.row_values(pool, lo, key)
        assert row["l_orderkey"] == key and \
            row["l_quantity"].as_tuple().exponent == -2 and \
            isinstance(row["l_comment"], bytes) and \
            row["l_shipdate"] >> 50 in range(1992, 1999)
    taken = []
    for _ in range(40):
        lo, hi = state.old_order()
        assert len(set(keys[lo:hi].tolist())) == 1
        assert lo == 0 or keys[lo - 1] != keys[lo]
        assert keys[hi] != keys[hi - 1]
        taken += list(range(lo, hi))
    assert taken == list(range(len(taken)))
