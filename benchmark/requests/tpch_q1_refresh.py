"""TPC-H's refresh functions beside Q1 (TPC Benchmark H rev 3, Clause
2.5: RF1 "New Sales", RF2 "Old Sales"; Clause 5.3: a refresh pair runs
beside the query streams).  Before every read of the pricing summary
(``tpch_q1``: the plan, the DELTAs, the all-integer sums, by import) the
session commits, each as ONE transaction (``TxnClient.txn_write``: 2PC):

- RF1, one new order: its 1-7 lineitems, drawn as the table's are
  (``tables/lineitem_presplit.py make``: Clause 4.2.3's distributions,
  numpy, from a stream seeded by the table ``--seed`` made), inserted at
  the next free ``_tidb_rowid``: the table's tail, its LAST region;
- RF2, one old order: the lineitems of the oldest order not yet deleted,
  deleted: the table's head, its FIRST region.

ORDERS is not in this store, so an order is its lineitems only.  The
writes are the session's own statements before its SELECT: they run in
``prepare``, off the read's clock, and the TSO of the read is fetched
after both are acknowledged.  ``send`` is timed as ``tpch_q1.send`` is,
the waits for another session's locks included.

What is held (``check``): a read at ``start_ts`` answers EXACTLY the
loaded table plus every refresh transaction whose ``commit_ts`` <=
``start_ts``, each order whole or absent, for the read's own DELTA.  The
refresh log is this module's, under a lock, shared by the cell's
sessions (one process); the ``start_ts`` rides from ``prepare`` through
the reply dict to ``digest`` as the DELTA does.  Under Q1 ~98% of the
rows pass and every group carries COUNT(*), so a read that misses one
acknowledged order, or sees half of one, is a wrong answer.

Of the program this module uses what ``tpch_q1`` and the table kind use
(the plan builder, the client, the row and key encoders of
``testing.fixture``) and nothing of what it checks."""

from __future__ import annotations

import decimal
import threading
import types

import numpy as np

import byname

_q1 = byname.load("requests", "tpch_q1")
_lineitem = byname.load("tables", "lineitem_presplit")

# the fused Pallas kernel on every region's feed: a feed patch is no
# ``device_dispatch`` and carries no compile class of its own
CLASSES = _q1.CLASSES
DELTAS = _q1.DELTAS
VALIDATION = _q1.VALIDATION
COLUMNS = _q1.COLUMNS

# refresh orders are drawn a pool at a time (about POOL_ROWS / 4 orders:
# more than a run commits; another pool is drawn where one runs out)
POOL_ROWS = 1 << 15

_mu = threading.Lock()
_state = None


def require_program() -> None:
    """An older program must not run this cell: its fan-out hands a
    ``key_is_locked`` to the caller (every read that meets another
    session's prewrite would fail), its feed patch compiles a program
    for every new delta length inside the window, and it has none of the
    counters the cell's metrics read.  It exits 1 here, before the first
    write and the first read, in seconds."""
    from tikv_tpu.server import client as program_client
    from tikv_tpu.utils import trace_vocab
    if not hasattr(program_client, "LOCK_BACKOFF"):
        raise SystemExit(
            "this program's fan-out does not wait for a lock "
            "(server/client.py LOCK_BACKOFF): a read beside TPC-H's "
            "refresh stream would fail on another session's prewrite")
    missing = {"feed_rebuild", "fanout_lock_wait"} - \
        set(trace_vocab.SPAN_VOCABULARY)
    if missing:
        raise SystemExit(
            f"this program has no phases {sorted(missing)} "
            f"(utils/trace_vocab.py): it cannot report what a read "
            f"after a write costs")


class _Refresh:
    """What the cell's sessions share: the stream of new orders, the
    next free rowid and order number, RF2's cursor over the table's
    head, and the log of acknowledged transactions."""

    def __init__(self, ctx, params):
        self.ctx = ctx
        self.spec = {"scale_factor": params["scale_factor"]}
        c = ctx.cols
        # the stream is --seed's: the table it made names it
        self.seed = [int(v) for v in c["l_partkey"][:4]] + [int(ctx.rows)]
        self.pools = 0
        self.pool = None
        self.at = 0                         # next order of the pool
        self.next_rowid = int(ctx.rows)
        keys = c["l_orderkey"]
        # O_ORDERKEY: the first 8 of every 32 (Clause 4.2.3); the order
        # number is the key's dense index
        last = int(keys[-1])
        self.next_order = ((last >> 5) << 3 | (last & 7)) + 1
        # RF2's cursor: the table's orders from its head, in key order
        head = keys[:min(len(keys), 1 << 16)]
        self.head_ends = np.flatnonzero(np.diff(head)) + 1
        self.deleted = 0                    # orders of the head taken
        self.log: list = []                 # (commit_ts, sign, cols)
        self.unacked = 0

    def _draw(self) -> None:
        seed = int(np.random.SeedSequence(
            self.seed + [self.pools]).generate_state(1)[0])
        cols = _lineitem.make(self.spec, seed, POOL_ROWS)
        ends = np.flatnonzero(np.diff(cols["l_orderkey"])) + 1
        self.pool = (cols, np.concatenate([[0], ends]))
        self.pools += 1
        self.at = 0

    def new_order(self) -> tuple:
        """→ (first rowid, order key, the pool's columns, lo, hi): the
        next order of the stream, its place at the table's tail."""
        with _mu:
            # (the pool's last order may be cut short: not taken)
            if self.pool is None or self.at + 2 >= len(self.pool[1]):
                self._draw()
            cols, starts = self.pool
            lo, hi = int(starts[self.at]), int(starts[self.at + 1])
            self.at += 1
            rowid = self.next_rowid
            self.next_rowid += hi - lo
            i = self.next_order
            self.next_order += 1
        return rowid, ((i >> 3) << 5) | (i & 7), cols, lo, hi

    def old_order(self) -> tuple:
        """→ (lo, hi): the rowids of the oldest order not yet taken."""
        with _mu:
            k = self.deleted
            if k >= len(self.head_ends):
                raise RuntimeError("RF2 ran out of the table's head")
            self.deleted += 1
        return (int(self.head_ends[k - 1]) if k else 0,
                int(self.head_ends[k]))

    def acked(self, commit_ts: int, sign: int, cols: dict) -> None:
        with _mu:
            self.log.append((int(commit_ts), sign, cols))


def _shared(ctx, params) -> _Refresh:
    global _state
    with _mu:
        if _state is None or _state.ctx is not ctx:
            require_program()
            _state = _Refresh(ctx, params)
        return _state


def _q1_cols(cols: dict, lo: int, hi: int) -> dict:
    """Rows [lo, hi) of what Q1 reads, as the table kind holds them."""
    return {name: np.array(cols[name][lo:hi]) for name in COLUMNS}


def row_values(cols: dict, i: int, order_key: int) -> dict:
    """Row ``i`` of a ``make`` as the values a SQL layer writes: the
    DECIMALs at scale 2, the dates as packed cores, the texts' bytes."""
    out = {}
    for name, _cid, kind in _lineitem.COLUMNS:
        v = int(cols[name][i])
        if name == "l_orderkey":
            v = order_key
        elif kind[0] == "decimal":
            v = decimal.Decimal(v).scaleb(-kind[2])
        elif kind[0] == "date":
            y, m, d = (int(x) for x in _lineitem.civil_from_days(v))
            v = (y << 50) | (m << 46) | (d << 41)
        elif kind[0] in ("char", "varchar"):
            v = (cols["_comments"] if name == "l_comment"
                 else _lineitem.TEXTS[name])[v]
        out[name] = v
    return out


def rf1(ctx, client, st: _Refresh) -> None:
    """One new order, one transaction."""
    from tikv_tpu.testing.fixture import encode_table_row
    rowid, order_key, cols, lo, hi = st.new_order()
    muts = []
    for k, i in enumerate(range(lo, hi)):
        key, value = encode_table_row(ctx.table, rowid + k,
                                      row_values(cols, i, order_key))
        muts.append(("put", key, value))
    st.acked(client.txn_write(muts), +1, _q1_cols(cols, lo, hi))


def rf2(ctx, client, st: _Refresh) -> None:
    """One old order, one transaction."""
    from tikv_tpu.codec.keys import table_record_key
    lo, hi = st.old_order()
    commit_ts = client.txn_write([
        ("delete", table_record_key(ctx.table.table_id, h), None)
        for h in range(lo, hi)])
    st.acked(commit_ts, -1, _q1_cols(ctx.cols, lo, hi))


def prepare(ctx, client, params):
    """The session's statements before its SELECT, off the clock: one
    RF1 order and one RF2 order, each acknowledged, then the walk's next
    DELTA, the TSO fetch and the plan, as ``tpch_q1.prepare``."""
    st = _shared(ctx, params)
    try:
        rf1(ctx, client, st)
        rf2(ctx, client, st)
    except BaseException:
        with _mu:
            st.unacked += 1
        raise
    index = _q1.next_delta(client)
    start_ts = client.tso()
    return (_q1.plan(ctx, index, start_ts), params["concurrency"], index,
            start_ts)


def send(ctx, client, request):
    """The timed call: first task sent to last partial back, a wait for
    another session's lock included.  The reply dict carries the read's
    DELTA and TSO to ``digest``."""
    dag, concurrency, index, start_ts = request
    resp = client.coprocessor_fanout(dag, concurrency=concurrency,
                                     timeout=120)
    resp["tpch_q1_delta"] = index
    resp["tpch_q1_refresh_start_ts"] = start_ts
    return resp


def digest(ctx, resp, params):
    """The read's TSO, then ``tpch_q1.digest``'s shape."""
    return np.array([resp["tpch_q1_refresh_start_ts"]], np.int64).tobytes() \
        + _q1.digest(ctx, resp, params)


def contribution(entry, approx: bool = False) -> np.ndarray:
    """What one acknowledged transaction adds to ``tpch_q1.sums_by_day``
    of the table: its rows' sums, taken away where it deleted them."""
    _commit_ts, sign, cols = entry
    return sign * _q1.sums_by_day(types.SimpleNamespace(cols=cols), approx)


def references(ctx, log: list, reads, approx: bool = False,
               stale: bool = False) -> dict:
    """{(start_ts, index): ``digest``'s shape} for every read of
    ``reads``: Q1 for ``DELTAS[index]`` over the loaded table plus every
    transaction of ``log`` committed at or before ``start_ts``, plain
    numpy over integers.  ``approx``: the products in float32 (as
    ``tpch_q1``'s control).  ``stale``: without the newest of those
    transactions: a read served from the table as it stood one
    acknowledged transaction earlier."""
    entries = sorted(log, key=lambda e: e[0])
    by_day = _q1.sums_by_day(ctx, approx)
    newest = None
    out = {}
    k = 0
    for start_ts, index in sorted(set(reads)):
        while k < len(entries) and entries[k][0] <= start_ts:
            newest = contribution(entries[k], approx)
            by_day = by_day + newest
            k += 1
        at = by_day - newest if stale and newest is not None else by_day
        out[start_ts, index] = np.concatenate([
            np.array([start_ts], np.int64), _q1.answer(index, at)])
    return out


def reference(ctx, params, approx=False) -> np.ndarray:
    """``digest``'s shape for the validation DELTA over the loaded table
    alone, at TSO 0: before any refresh.  ``check`` computes every
    read's own."""
    return references(ctx, [], [(0, VALIDATION)], approx)[0, VALIDATION]


def wrong_answers(ctx, records, log: list, regions: str,
                  approx: bool = False, stale: bool = False) -> tuple:
    """→ (records whose answer is not the reference's for their own TSO
    and DELTA, records off the layout); marks both ``wrong``."""
    got = [np.frombuffer(r["answer"], np.int64) for r in records]
    want = references(ctx, log, [(int(g[0]), int(g[1])) for g in got],
                      approx, stale)
    wrong = off = 0
    for r, g in zip(records, got):
        if not np.array_equal(g, want[int(g[0]), int(g[1])]):
            r["wrong"] = True
            wrong += 1
        if "labels" in r and r["labels"].get("cop_tasks") != regions:
            r["wrong"] = True
            off += 1
    return wrong, off


def check(ctx, records, params, reference):
    """Every answer equals the reference at its own TSO for its own
    DELTA (``tpch_q1.check``'s exactness, over the table as the refresh
    log says it stood at that TSO); the layout as ``tpch_q1.check`` holds
    it; and no refresh transaction failed to be acknowledged.
    → [(name, value, limit)]."""
    st = _state
    with _mu:
        log = list(st.log) if st is not None else []
        unacked = st.unacked if st is not None else 0
    wrong, off = wrong_answers(ctx, records, log, str(params["regions"]))
    return [("tpch_q1_refresh.wrong_answers", wrong, 0),
            ("regions.reads_off_the_layout", off, 0),
            ("tpch_q1_refresh.unacked_writes", unacked, 0)]
