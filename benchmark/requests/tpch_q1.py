"""TPC-H Q1, "Pricing Summary Report" (TPC Benchmark H rev 3, Clause
2.4.1), pushed down as TiDB pushes it: every region of ``lineitem`` gets
one cop task

    TableScan(l_quantity, l_extendedprice, l_discount, l_tax,
              l_returnflag, l_linestatus, l_shipdate)
    -> Selection(l_shipdate <= DATE '1998-12-01' - DELTA days)
    -> Aggregation(GROUP BY l_returnflag, l_linestatus;
         SUM(l_quantity), SUM(l_extendedprice),
         SUM(l_extendedprice * (1 - l_discount)),
         SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
         COUNT(l_quantity), SUM(l_quantity),            -- AVG(l_quantity)
         COUNT(l_extendedprice), SUM(l_extendedprice),  -- AVG(l_extendedprice)
         COUNT(l_discount), SUM(l_discount),            -- AVG(l_discount)
         COUNT(*))

through ``TxnClient.coprocessor_fanout`` (at most ``params["concurrency"]``
tasks at once).  An AVG leaves a store as TiKV's AVG does, a (COUNT, SUM)
pair: this store's own AVG answers the quotient, which no SQL layer can
merge across regions, so the plan carries the pair as TiDB's planner
writes a partial AVG (count + sum).  The SQL layer merges the regions'
partials by group, divides and orders: in ``digest``, off the clock.  The
date constant is folded as TiDB's planner folds it.

The substitution parameter is the clause's (2.4.1.3): DELTA uniform in
[60, 120], validation value 90: ``DELTAS``, 61 of them.  Every client
object walks all of them in one fixed order, the clients starting
``STRIDE`` values apart, the same in every run.  A read's DELTA rides from
``prepare`` through the reply dict to ``digest``, and ``check`` holds every
record to the reference for ITS DELTA.

The answers are DECIMAL arithmetic, so the reference is all-integer: the
sums x 10^2, 10^2, 10^4 and 10^6.  A partial sum that is not a DECIMAL of
its scale, a count that is not an integer, a key that is not bytes, a
missing or an extra group: each is a wrong answer whatever the values."""

from __future__ import annotations

import decimal
import itertools
import threading

import numpy as np

import byname

_lineitem = byname.load("tables", "lineitem_presplit")

# the fused Pallas kernel on every region's feed, never its XLA stand-ins
CLASSES = ("pallas_hash",)

# Clause 2.4.1.3
DELTAS = tuple(range(60, 121))
VALIDATION = DELTAS.index(90)
STRIDE = 15
END = (1998, 12, 1)

COLUMNS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
           "l_returnflag", "l_linestatus", "l_shipdate")
# a reply row, aggregates then keys: what it is and the scale a DECIMAL
# of it has (None: an integer count)
ROW = (("sum_qty", 2), ("sum_base_price", 2), ("sum_disc_price", 4),
       ("sum_charge", 6), ("count_qty", None), ("avg_qty_sum", 2),
       ("count_price", None), ("avg_price_sum", 2),
       ("count_disc", None), ("avg_disc_sum", 2), ("count_order", None))
FLAGS = _lineitem.TEXTS["l_returnflag"]
STATUS = _lineitem.TEXTS["l_linestatus"]

_mu = threading.Lock()
_clients_seen = itertools.count()


def next_delta(client) -> int:
    """The client object's place in the walk, moved on by one."""
    i = getattr(client, "_tpch_q1_next", None)
    if i is None:
        with _mu:
            i = STRIDE * next(_clients_seen)
    client._tpch_q1_next = i + 1
    return i % len(DELTAS)


def cutoff(delta: int) -> int:
    """DATE '1998-12-01' - ``delta`` days, in days since 1970-01-01."""
    return _lineitem.days_from_civil(*END) - delta


def plan(ctx, index: int, start_ts: int, delta=None):
    """Q1's cop-task plan for ``DELTAS[index]`` (or ``delta``, a value
    outside the clause's range)."""
    from tikv_tpu.datatype import EvalType
    from tikv_tpu.expr import Expr
    from tikv_tpu.testing.dag import DagSelect

    y, m, d = (int(v) for v in _lineitem.civil_from_days(
        cutoff(DELTAS[index] if delta is None else delta)))
    s = DagSelect.from_table(ctx.table, COLUMNS)
    one = Expr.const(decimal.Decimal(1), EvalType.DECIMAL)
    price, disc = s.col("l_extendedprice"), s.col("l_discount")
    disc_price = Expr.call("MultiplyDecimal", price,
                           Expr.call("MinusDecimal", one, disc))
    charge = Expr.call("MultiplyDecimal", disc_price,
                       Expr.call("PlusDecimal", one, s.col("l_tax")))
    aggs = [("sum", s.col("l_quantity")), ("sum", price),
            ("sum", disc_price), ("sum", charge)]
    for col in (s.col("l_quantity"), price, disc):
        aggs += [("count", col), ("sum", col)]
    aggs.append(("count_star", None))
    return s.where(Expr.call(
        "LeTime", s.col("l_shipdate"),
        # datatype/time.py's packed core of the folded date
        Expr.const((y << 50) | (m << 46) | (d << 41), EvalType.DATETIME)),
    ).aggregate([s.col("l_returnflag"), s.col("l_linestatus")],
                aggs).build(start_ts=start_ts)


def prepare(ctx, client, params):
    """The walk's next DELTA, the TSO fetch and the plan: the SQL
    layer's, off the clock.  Before the first of them, as the table
    kind's ``load`` asks for what the table needs: a program without
    CHAR code planes answers every task of Q1 on the host, six seconds
    of Decimal objects a region, so a run of it would spend a quarter of
    an hour finding every read refused; it exits 1 here, in seconds."""
    from tikv_tpu.datatype import tile
    if not hasattr(tile, "code_plane"):
        raise SystemExit(
            "this program has no CHAR code planes (datatype/tile.py "
            "code_plane): it cannot push Q1's GROUP BY over two CHAR(1) "
            "keys down to the device")
    index = next_delta(client)
    return plan(ctx, index, client.tso()), params["concurrency"], index


def send(ctx, client, request):
    """The timed call: first task sent to last partial back.  The reply
    dict carries the read's DELTA to ``digest``."""
    dag, concurrency, index = request
    resp = client.coprocessor_fanout(dag, concurrency=concurrency,
                                     timeout=120)
    resp["tpch_q1_delta"] = index
    return resp


def sums_by_day(ctx, approx: bool = False) -> np.ndarray:
    """``[bucket, flag, status, measure]`` int64 sums over the table,
    plain numpy over its integers: bucket 0 holds the rows every DELTA
    keeps (shipped by DATE - 120 days), bucket k those shipped on the
    k-th day after it, up to DATE - 60 days; the measures are quantity
    x 10^2, price x 10^2, price (1 - discount) x 10^4, that (1 + tax)
    x 10^6, discount x 10^2 and the rows.  ``approx`` forms the two
    products in float32: the next precision down from the exact decimal
    arithmetic."""
    c = ctx.cols
    first = cutoff(DELTAS[-1])
    bucket = c["l_shipdate"].astype(np.int64) - first
    keep = bucket <= DELTAS[-1] - DELTAS[0]
    bucket = np.maximum(bucket[keep], 0)
    price, disc, tax = (c[name][keep].astype(np.int64) for name in
                        ("l_extendedprice", "l_discount", "l_tax"))
    if approx:
        disc_price = price.astype(np.float32) * \
            (100 - disc).astype(np.float32)
        charge = (disc_price * (100 + tax).astype(np.float32)) \
            .astype(np.float64)
        disc_price = disc_price.astype(np.float64)
    else:
        disc_price = price * (100 - disc)
        charge = disc_price * (100 + tax)
    measures = (c["l_quantity"][keep].astype(np.int64), price, disc_price,
                charge, disc, np.ones(len(price), np.int64))
    shape = (DELTAS[-1] - DELTAS[0] + 1, len(FLAGS), len(STATUS))
    cell = (bucket * shape[1] + c["l_returnflag"][keep]) * shape[2] + \
        c["l_linestatus"][keep]
    order = np.argsort(cell, kind="stable")
    cells, starts = np.unique(cell[order], return_index=True)
    out = np.zeros((shape[0] * shape[1] * shape[2], len(measures)),
                   np.int64)
    for j, m in enumerate(measures):
        if len(cells):
            # (a float sum is the control's: cut to an integer)
            out[cells, j] = np.add.reduceat(m[order], starts).astype(
                np.int64)
    return out.reshape(shape + (len(measures),))


def answer(index: int, by_day: np.ndarray, exact: bool = True) -> np.ndarray:
    """``digest``'s shape for ``DELTAS[index]``: [index, exact, then a
    group's 13 integers for every group with a row, by flag and status:
    the two keys' bytes, then ``ROW``]."""
    total = by_day[:DELTAS[-1] - DELTAS[index] + 1].sum(axis=0)
    out = [index, int(exact)]
    for key, (i, j) in sorted(
            ((FLAGS[i][0], STATUS[j][0]), (i, j))
            for i in range(len(FLAGS)) for j in range(len(STATUS))):
        qty, price, disc_price, charge, disc, rows = \
            (int(v) for v in total[i, j])
        if rows:
            out += [*key, qty, price, disc_price, charge, rows, qty, rows,
                    price, rows, disc, rows]
    return np.array(out, np.int64)


def reference(ctx, params, approx=False) -> np.ndarray:
    """``digest``'s shape for the validation DELTA.  ``check`` computes
    the other DELTAs' itself."""
    return answer(VALIDATION, sums_by_day(ctx, approx))


def digest(ctx, resp, params):
    """What is kept of a read: its DELTA, and the regions' partials
    merged by group, every sum x 10^scale; ``exact`` says whether every
    key was bytes, every sum a DECIMAL of its scale and every count an
    integer."""
    groups: dict = {}
    exact = True
    for r in resp["responses"]:
        for row in r["rows"]:
            *vals, flag, status = row
            if not (isinstance(flag, bytes) and isinstance(status, bytes)
                    and len(flag) == len(status) == 1):
                exact = False
                flag, status = (bytes(str(k), "latin1")[:1] or b"?"
                                for k in (flag, status))
            acc = groups.setdefault((flag[0], status[0]), [0] * len(ROW))
            for j, (v, (_name, scale)) in enumerate(zip(vals, ROW)):
                if v is None:
                    continue
                if scale is None:
                    exact = exact and type(v) is int
                    acc[j] += int(v)
                    continue
                if not isinstance(v, decimal.Decimal) or \
                        v.as_tuple().exponent != -scale:
                    exact = False
                    v = decimal.Decimal(v)
                acc[j] += int(v.scaleb(scale))
    out = [resp["tpch_q1_delta"], int(exact)]
    for key in sorted(groups):
        out += [*key, *groups[key]]
    return np.array(out, np.int64).tobytes()


def check(ctx, records, params, reference):
    """Every answer equals the reference for its own DELTA, group set
    included, as exact DECIMALs; then the layout, as
    ``hash_agg_regions.check`` holds it: a served read (a record with the
    reply's ``labels``) answered by another number of cop tasks than
    ``params["regions"]`` is off the layout.  Either marks the record
    ``wrong``.  → [(name, value, limit)]."""
    want: dict = {}
    by_day = None
    wrong = off = 0
    regions = str(params["regions"])
    for r in records:
        got = np.frombuffer(r["answer"], np.int64)
        index = int(got[0])
        if index not in want:
            if by_day is None:
                by_day = sums_by_day(ctx)
            want[index] = answer(index, by_day)
        if not np.array_equal(got, want[index]):
            r["wrong"] = True
            wrong += 1
        if "labels" in r and r["labels"].get("cop_tasks") != regions:
            r["wrong"] = True
            off += 1
    return [("tpch_q1.wrong_answers", wrong, 0),
            ("regions.reads_off_the_layout", off, 0)]
