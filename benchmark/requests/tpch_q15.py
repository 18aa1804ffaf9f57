"""TPC-H Q15's view ``revenue`` ("Top Supplier Query", TPC Benchmark H
rev 3, Clause 2.4.15.2), pushed down as TiDB pushes it: every region of
``lineitem`` gets one cop task

    TableScan(l_suppkey, l_extendedprice, l_discount, l_shipdate)
    -> Selection(l_shipdate >= DATE, l_shipdate < DATE + 3 months)
    -> Aggregation(GROUP BY l_suppkey;
                   SUM(l_extendedprice * (1 - l_discount)))

through ``TxnClient.coprocessor_fanout`` (at most ``params["concurrency"]``
tasks at once), each reply asked for as a CHUNK (``encode_type``: a
buffer a column, the DECIMAL sum as its scaled int64 plane): a task
answers one row for every supplier with a line in the window, ~8,400 of
the 10,000.  The SQL layer merges the regions' partials by ``l_suppkey``,
takes the MAX and joins ``supplier``: in ``digest``, off the clock, from
the chunks' planes.  The second date is folded as TiDB's planner folds it.

The substitution parameter is the clause's (2.4.15.3): DATE the first day
of a month from January 1993 to October 1997, validation value
1996-01-01: ``DATES``, 58 of them.  Every client object walks all of them
in one fixed order, the clients starting ``STRIDE`` values apart, the same
in every run.  A read's DATE rides from ``prepare`` through the reply
dict to ``digest``, and ``check`` holds every record to the reference for
ITS date.

The answer is DECIMAL arithmetic, so the reference is all-integer:
revenue x 10^4 a supplier.  A reply that is rows where a chunk was asked,
a sum plane that is not int64 at scale 4, a key outside [1, 10,000 x SF],
a missing or an extra supplier: each is a wrong answer whatever the
values."""

from __future__ import annotations

import ast
import dataclasses
import decimal
import functools
import importlib.util
import itertools
import os
import threading

import numpy as np

import byname

_lineitem = byname.load("tables", "lineitem_presplit")

# the fused Pallas kernel on every region's feed, never its XLA stand-ins
CLASSES = ("pallas_hash",)

# Clause 2.4.15.3: (year, month) of the window's first day
DATES = tuple((1993 + i // 12, 1 + i % 12) for i in range(58))
VALIDATION = DATES.index((1996, 1))
STRIDE = 14
MONTHS = 3
SCALE = 4           # DECIMAL(15,2) x DECIMAL(15,2)
# the key's domain, Clause 4.2.3: 10,000 x SF, at the configuration's
# scale factor of 1; and the slots a GROUP BY over it needs (the next
# power of two), which a program must have in its fused kernel
SUPPLIERS = _lineitem.SUPPLIERS_PER_SF
GRID = 1 << 14

COLUMNS = ("l_suppkey", "l_extendedprice", "l_discount", "l_shipdate")

_mu = threading.Lock()
_clients_seen = itertools.count()


def next_date(client) -> int:
    """The client object's place in the walk, moved on by one."""
    i = getattr(client, "_tpch_q15_next", None)
    if i is None:
        with _mu:
            i = STRIDE * next(_clients_seen)
    client._tpch_q15_next = i + 1
    return i % len(DATES)


def month_start(year: int, month: int, plus: int = 0) -> tuple:
    """(year, month) ``plus`` months on."""
    m = year * 12 + month - 1 + plus
    return m // 12, m % 12 + 1


def plan(ctx, index: int, start_ts: int, date=None):
    """Q15's cop-task plan for ``DATES[index]`` (or ``date``, a (year,
    month) outside the clause's range), the reply asked as a chunk."""
    from tikv_tpu.datatype import EvalType
    from tikv_tpu.expr import Expr
    from tikv_tpu.testing.dag import DagSelect

    first = date or DATES[index]
    s = DagSelect.from_table(ctx.table, COLUMNS)

    def day(ym):        # datatype/time.py's packed core of <y>-<m>-01
        y, m = ym
        return Expr.const((y << 50) | (m << 46) | (1 << 41),
                          EvalType.DATETIME)

    one = Expr.const(decimal.Decimal(1), EvalType.DECIMAL)
    dag = s.where(
        Expr.call("GeTime", s.col("l_shipdate"), day(first)),
        Expr.call("LtTime", s.col("l_shipdate"),
                  day(month_start(*first, MONTHS))),
    ).aggregate([s.col("l_suppkey")], [("sum", Expr.call(
        "MultiplyDecimal", s.col("l_extendedprice"),
        Expr.call("MinusDecimal", one, s.col("l_discount"))))]
    ).build(start_ts=start_ts)
    return dataclasses.replace(dag, encode_type="chunk")


@functools.cache
def kernel_max_slots() -> int:
    """``tikv_tpu/device/pallas_hash.py``'s ``MAX_SLOTS``, read from its
    source, once a process: importing the module would bring JAX into
    the load generator's process, which must never hold the chip.  0
    where the program has no such name."""
    path = os.path.join(os.path.dirname(
        importlib.util.find_spec("tikv_tpu").origin),
        "device", "pallas_hash.py")
    try:
        with open(path) as f:
            tree = ast.parse(f.read())
    except OSError:
        return 0
    for node in tree.body:
        if isinstance(node, ast.Assign) and \
                getattr(node.targets[0], "id", None) == "MAX_SLOTS":
            return int(eval(compile(ast.Expression(node.value), path,
                                    "eval"), {}))
    return 0


def prepare(ctx, client, params):
    """The walk's next DATE, the TSO fetch and the plan: the SQL layer's,
    off the clock.  Before the first of them, as the table kind's
    ``load`` asks for what the table needs: a program whose fused kernel
    stops at 4,096 slots answers every task on an XLA stand-in it
    compiles anew for every DATE, and one without chunk replies makes a
    ``Decimal`` a supplier at both ends; either would spend its run
    being refused.  It exits 1 here, in seconds."""
    from tikv_tpu.server import wire
    if not hasattr(wire, "chunk_rows"):
        raise SystemExit(
            "this program has no chunk replies (server/wire.py "
            "chunk_rows): it cannot answer Q15's ~8,400 rows a cop task "
            "as buffers")
    if kernel_max_slots() < GRID:
        raise SystemExit(
            f"this program's fused kernel stops at {kernel_max_slots()} "
            f"slots (device/pallas_hash.py MAX_SLOTS): GROUP BY "
            f"l_suppkey needs {GRID}")
    index = next_date(client)
    return plan(ctx, index, client.tso()), params["concurrency"], index


def send(ctx, client, request):
    """The timed call: first task sent to last partial back.  The reply
    dict carries the read's DATE to ``digest``."""
    dag, concurrency, index = request
    resp = client.coprocessor_fanout(dag, concurrency=concurrency,
                                     timeout=120)
    resp["tpch_q15_date"] = index
    return resp


def sums_by_month(ctx, approx: bool = False) -> tuple:
    """``(sums, rows)``, each ``[month, supplier]`` int64 over the table,
    plain numpy over its integers: month 0 is January 1993, the last
    December 1997 (the last window's third month); supplier the key
    itself, 0..SUPPLIERS (entry 0 stays empty); ``sums`` revenue x 10^4,
    ``rows`` the lines.  ``approx`` forms the products in float32: the
    next precision down from the exact decimal arithmetic."""
    c = ctx.cols
    n_months = len(DATES) + MONTHS - 1
    edges = np.array([_lineitem.days_from_civil(*month_start(*DATES[0], k),
                                                1)
                      for k in range(n_months + 1)], np.int64)
    ship = c["l_shipdate"].astype(np.int64)
    keep = (ship >= edges[0]) & (ship < edges[-1])
    month = np.searchsorted(edges, ship[keep], side="right") - 1
    price = c["l_extendedprice"][keep].astype(np.int64)
    disc = c["l_discount"][keep].astype(np.int64)
    if approx:
        rev = (price.astype(np.float32) * (100 - disc).astype(np.float32)) \
            .astype(np.float64)
    else:
        rev = price * (100 - disc)
    width = SUPPLIERS + 1
    cell = month * width + c["l_suppkey"][keep].astype(np.int64)
    order = np.argsort(cell, kind="stable")
    cells, starts = np.unique(cell[order], return_index=True)
    sums = np.zeros(n_months * width, np.int64)
    rows = np.zeros(n_months * width, np.int64)
    if len(cells):
        # (a float sum is the control's: cut to an integer)
        sums[cells] = np.add.reduceat(rev[order], starts).astype(np.int64)
        rows[cells] = np.diff(np.append(starts, len(order)))
    return sums.reshape(n_months, width), rows.reshape(n_months, width)


def revenue(index: int, by_month: tuple) -> tuple:
    """``(sums, present)`` for ``DATES[index]``: the dense int64 vector
    of revenue x 10^4 by ``l_suppkey`` and the mask of the suppliers with
    a line in the window."""
    sums, rows = by_month
    return (sums[index:index + MONTHS].sum(axis=0),
            rows[index:index + MONTHS].sum(axis=0) > 0)


def answer(index: int, sums: np.ndarray, present: np.ndarray,
           exact: bool = True) -> np.ndarray:
    """``digest``'s shape: [date index, exact, the keys with a line in
    the window in order, then their sums]."""
    keys = np.nonzero(present)[0]
    return np.concatenate([np.array([index, int(exact)], np.int64),
                           keys.astype(np.int64),
                           sums[keys].astype(np.int64)])


def reference(ctx, params, approx=False) -> np.ndarray:
    """``digest``'s shape for the validation DATE.  ``check`` computes
    the other dates' itself."""
    return answer(VALIDATION, *revenue(VALIDATION,
                                       sums_by_month(ctx, approx)))


def digest(ctx, resp, params):
    """What is kept of a read: its DATE, and the regions' partials added
    by ``l_suppkey`` from the chunks' planes; ``exact`` says whether every
    reply was a chunk of two int64 planes, the sum at scale 4 and without
    a NULL, the key a supplier's."""
    width = SUPPLIERS + 1
    sums = np.zeros(width, np.int64)
    present = np.zeros(width, np.bool_)
    exact = True
    for r in resp["responses"]:
        chunk = r.get("chunk")
        cols = chunk["cols"] if isinstance(chunk, dict) else ()
        if len(cols) != 2 or any(
                c["t"] != "i8" or not isinstance(c["v"], np.ndarray)
                or "ok" in c for c in cols) or \
                cols[0].get("frac") != SCALE or "frac" in cols[1]:
            exact = False
            continue
        part, keys = cols[0]["v"], cols[1]["v"]
        inside = (keys >= 1) & (keys < width)
        if not inside.all() or len(np.unique(keys)) != len(keys):
            exact = False
            part, keys = part[inside], keys[inside]
        np.add.at(sums, keys, part)
        present[keys] = True
    return answer(resp["tpch_q15_date"], sums, present, exact).tobytes()


def check(ctx, records, params, reference):
    """Every answer equals the reference for its own DATE, the supplier
    set included, as exact scaled integers from chunks; then the layout,
    as ``hash_agg_regions.check`` holds it: a served read (a record with
    the reply's ``labels``) answered by another number of cop tasks than
    ``params["regions"]`` is off the layout.  Either marks the record
    ``wrong``.  → [(name, value, limit)]."""
    want: dict = {}
    by_month = None
    wrong = off = 0
    regions = str(params["regions"])
    for r in records:
        got = np.frombuffer(r["answer"], np.int64)
        index = int(got[0])
        if index not in want:
            if by_month is None:
                by_month = sums_by_month(ctx)
            want[index] = answer(index, *revenue(index, by_month))
        if not np.array_equal(got, want[index]):
            r["wrong"] = True
            wrong += 1
        if "labels" in r and r["labels"].get("cop_tasks") != regions:
            r["wrong"] = True
            off += 1
    return [("tpch_q15.wrong_answers", wrong, 0),
            ("regions.reads_off_the_layout", off, 0)]
