"""TPC-H Q6, "Forecasting Revenue Change" (TPC Benchmark H rev 3, Clause
2.4.6), pushed down as TiDB pushes it: every region of ``lineitem`` gets
one cop task

    TableScan(l_quantity, l_extendedprice, l_discount, l_shipdate)
    -> Selection(l_shipdate >= DATE, l_shipdate < DATE + 1 year,
                 l_discount >= D - 0.01, l_discount <= D + 0.01,
                 l_quantity < Q)
    -> Aggregation(SUM(l_extendedprice * l_discount))

through ``TxnClient.coprocessor_fanout`` (at most ``params["concurrency"]``
tasks at once), and the SQL layer adds the partial sums: in ``digest``, off
the clock.  The substitution parameters are the clause's (2.4.6.3): DATE
the first of January of 1993..1997, DISCOUNT 0.02..0.09, QUANTITY 24 or
25: ``TUPLES``, 80 of them, in one fixed order.  Every client object
(the harness gives each client thread a ``TxnClient`` of its own) walks
all of them in that order, the clients starting ``STRIDE`` tuples apart,
the same in every run: consecutive reads of a session and concurrent
reads of different sessions differ in their constants.  A read's tuple
rides from ``prepare`` through the reply dict to ``digest``, and ``check``
holds every record to the reference for ITS tuple.

The answer is DECIMAL arithmetic, so the reference is all-integer: cents
times hundredths, revenue x 10^4.  A partial sum that is not a DECIMAL of
scale 4 (a float, an integer) is a wrong answer whatever its value."""

from __future__ import annotations

import decimal
import itertools
import threading

import numpy as np

# the fused Pallas kernel on every region's feed, never its XLA stand-ins
CLASSES = ("pallas_hash",)

# (year, discount in hundredths, quantity), Clause 2.4.6.3
TUPLES = tuple(itertools.product(range(1993, 1998), range(2, 10), (24, 25)))
# Clause 2.4.6.3's validation values: 1994-01-01, 0.06, 24
VALIDATION = TUPLES.index((1994, 6, 24))
STRIDE = 20
SCALE = 4           # DECIMAL(15,2) x DECIMAL(15,2)

COLUMNS = ("l_quantity", "l_extendedprice", "l_discount", "l_shipdate")

_mu = threading.Lock()
_clients_seen = itertools.count()


def next_tuple(client) -> int:
    """The client object's place in the walk, moved on by one."""
    i = getattr(client, "_tpch_q6_next", None)
    if i is None:
        with _mu:
            i = STRIDE * next(_clients_seen)
    client._tpch_q6_next = i + 1
    return i % len(TUPLES)


def plan(ctx, index: int, start_ts: int, tup=None):
    """Q6's cop-task plan for ``TUPLES[index]`` (or ``tup``, a tuple of
    that shape outside the clause's ranges), the constants folded as
    TiDB's planner folds them (DATE + 1 year, D -/+ 0.01)."""
    from tikv_tpu.datatype import EvalType
    from tikv_tpu.expr import Expr
    from tikv_tpu.testing.dag import DagSelect

    year, disc, qty = tup or TUPLES[index]
    s = DagSelect.from_table(ctx.table, COLUMNS)

    def date(y):        # datatype/time.py's packed core of <y>-01-01
        return Expr.const((y << 50) | (1 << 46) | (1 << 41),
                          EvalType.DATETIME)

    def dec(hundredths):
        return Expr.const(decimal.Decimal(hundredths).scaleb(-2),
                          EvalType.DECIMAL)

    return s.where(
        Expr.call("GeTime", s.col("l_shipdate"), date(year)),
        Expr.call("LtTime", s.col("l_shipdate"), date(year + 1)),
        Expr.call("GeDecimal", s.col("l_discount"), dec(disc - 1)),
        Expr.call("LeDecimal", s.col("l_discount"), dec(disc + 1)),
        Expr.call("LtDecimal", s.col("l_quantity"),
                  Expr.const(decimal.Decimal(qty), EvalType.DECIMAL)),
    ).aggregate([], [("sum", Expr.call(
        "MultiplyDecimal", s.col("l_extendedprice"), s.col("l_discount")))]
    ).build(start_ts=start_ts)


def prepare(ctx, client, params):
    """The walk's next tuple, the TSO fetch and the plan: the SQL
    layer's, off the clock."""
    index = next_tuple(client)
    return plan(ctx, index, client.tso()), params["concurrency"], index


def send(ctx, client, request):
    """The timed call: first task sent to last partial back.  The reply
    dict carries the read's tuple to ``digest``."""
    dag, concurrency, index = request
    resp = client.coprocessor_fanout(dag, concurrency=concurrency,
                                     timeout=120)
    resp["tpch_q6_tuple"] = index
    return resp


def days(y: int) -> int:
    """Days from 1970-01-01 to <y>-01-01."""
    y -= 1
    return y * 365 + y // 4 - y // 100 + y // 400 - 719162


def revenue(ctx, index: int, approx: bool = False, tup=None) -> int:
    """Q6's answer x 10^4 for ``TUPLES[index]`` (or ``tup``), plain
    numpy over the table's integers.  ``approx`` forms the products in
    float32: the next precision down from the exact decimal
    arithmetic."""
    year, disc, qty = tup or TUPLES[index]
    c = ctx.cols
    ship, d = c["l_shipdate"], c["l_discount"]
    keep = (ship >= days(year)) & (ship < days(year + 1)) & \
        (d >= disc - 1) & (d <= disc + 1) & (c["l_quantity"] < qty * 100)
    price, d = c["l_extendedprice"][keep], d[keep]
    if approx:
        return int((price.astype(np.float32) * d.astype(np.float32))
                   .astype(np.float64).sum())
    return int((price.astype(np.int64) * d.astype(np.int64)).sum())


def answer(index: int, total: int, exact: bool = True) -> np.ndarray:
    return np.array([index, total, int(exact)], np.int64)


def reference(ctx, params, approx=False) -> np.ndarray:
    """[tuple index, revenue x 10^4, 1] for the validation tuple:
    ``digest``'s shape.  ``check`` computes the other tuples' itself."""
    return answer(VALIDATION, revenue(ctx, VALIDATION, approx))


def digest(ctx, resp, params):
    """What is kept of a read: its tuple, the sum of its tasks' partial
    sums x 10^4, and whether every partial was a DECIMAL of scale 4 (a
    task no row passed in answers NULL)."""
    total, exact = decimal.Decimal(0), True
    for r in resp["responses"]:
        (v,), = r["rows"]
        if v is None:
            continue
        if not isinstance(v, decimal.Decimal) or \
                v.as_tuple().exponent != -SCALE:
            exact = False
            v = decimal.Decimal(v)
        total += v
    return answer(resp["tpch_q6_tuple"], int(total.scaleb(SCALE)),
                  exact).tobytes()


def check(ctx, records, params, reference):
    """Every answer equals the reference for its own tuple, as an exact
    DECIMAL; then the layout, as ``hash_agg_regions.check`` holds it: a
    served read (a record with the reply's ``labels``) answered by
    another number of cop tasks than ``params["regions"]`` is off the
    layout.  Either marks the record ``wrong``.
    → [(name, value, limit)]."""
    want: dict = {}
    wrong = off = 0
    regions = str(params["regions"])
    for r in records:
        index, total, exact = np.frombuffer(r["answer"], np.int64)
        if index not in want:
            want[index] = revenue(ctx, int(index))
        if total != want[index] or not exact:
            r["wrong"] = True
            wrong += 1
        if "labels" in r and r["labels"].get("cop_tasks") != regions:
            r["wrong"] = True
            off += 1
    return [("tpch_q6.wrong_answers", wrong, 0),
            ("regions.reads_off_the_layout", off, 0)]
