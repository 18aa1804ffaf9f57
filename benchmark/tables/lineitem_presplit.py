"""Table kind ``lineitem_presplit``: TPC-H's LINEITEM (TPC Benchmark H
rev 3, Clause 1.4: 16 columns) at go-tpc's TiDB types, populated as
Clause 4.2.3 says and laid out in ``regions`` regions of equal row
counts BEFORE the load, as ``int_table_presplit`` lays its table out.

``make`` is numpy only and draws from ``numpy.random.default_rng``, not
from dbgen's streams: the distributions are the clause's, the rows are
not dbgen's, and the reference (requests/tpch_q6.py) is the judge, not
the spec's validation answer.  Every column comes back as integers:

- the four keys and ``l_linenumber`` as they are;
- the four DECIMAL(15,2) columns scaled by 100 (``l_quantity`` 17.00 is
  1700, ``l_extendedprice`` in cents, ``l_discount`` 0.06 is 6);
- the three dates as days since 1970-01-01;
- the five strings as indices into ``TEXTS[column]`` (for ``l_comment``
  a pool of ``COMMENT_POOL`` texts of the clause's 10-43 characters,
  drawn from ``--seed``: ``make`` returns it under ``"_comments"``).

The handle is TiDB's ``_tidb_rowid`` (0..rows-1: with the default
``INT_ONLY`` clustering the composite primary key (l_orderkey,
l_linenumber) is a non-clustered unique index, which no request of this
benchmark reads and ``load`` does not write)."""

from __future__ import annotations

import collections
import concurrent.futures as cf
import time

import numpy as np

import byname

_presplit = byname.load("tables", "int_table_presplit")

boundaries = _presplit.boundaries
pieces = _presplit.pieces
LOAD_CHUNK = 1 << 19

# Clause 4.2.3 and 4.2.2.13
ORDERS_PER_SF = 1_500_000
PARTS_PER_SF = 200_000
SUPPLIERS_PER_SF = 10_000
START_DATE = (1992, 1, 1)
END_DATE = (1998, 12, 31)
CURRENT_DATE = (1995, 6, 17)
COMMENT_POOL = 4096
TEXTS = {
    "l_returnflag": (b"R", b"A", b"N"),
    "l_linestatus": (b"O", b"F"),
    "l_shipinstruct": (b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
                       b"TAKE BACK RETURN"),
    "l_shipmode": (b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL",
                   b"FOB"),
}
# the clause's text grammar draws from word lists; what a row carries of
# it here is its length and that it is text
_WORDS = (b"furiously", b"quickly", b"carefully", b"blithely", b"slyly",
          b"ironic", b"final", b"regular", b"express", b"pending", b"bold",
          b"special", b"deposits", b"requests", b"accounts", b"packages",
          b"foxes", b"ideas", b"theodolites", b"pinto beans", b"sleep",
          b"wake", b"nag", b"haggle", b"cajole", b"above the", b"along the",
          b"among the", b"after the", b"according to the")

# column name, column id, (kind, ...): the order is Clause 1.4's
COLUMNS = (
    ("l_orderkey", 2, ("bigint",)), ("l_partkey", 3, ("bigint",)),
    ("l_suppkey", 4, ("bigint",)), ("l_linenumber", 5, ("bigint",)),
    ("l_quantity", 6, ("decimal", 15, 2)),
    ("l_extendedprice", 7, ("decimal", 15, 2)),
    ("l_discount", 8, ("decimal", 15, 2)), ("l_tax", 9, ("decimal", 15, 2)),
    ("l_returnflag", 10, ("char", 1)), ("l_linestatus", 11, ("char", 1)),
    ("l_shipdate", 12, ("date",)), ("l_commitdate", 13, ("date",)),
    ("l_receiptdate", 14, ("date",)),
    ("l_shipinstruct", 15, ("char", 25)), ("l_shipmode", 16, ("char", 10)),
    ("l_comment", 17, ("varchar", 44)),
)


def days_from_civil(y: int, m: int, d: int) -> int:
    """Days since 1970-01-01 (Hinnant's days_from_civil)."""
    y -= m <= 2
    era = (y if y >= 0 else y - 399) // 400
    yoe = y - era * 400
    doy = (153 * (m + (-3 if m > 2 else 9)) + 2) // 5 + d - 1
    return era * 146097 + yoe * 365 + yoe // 4 - yoe // 100 + doy - 719468


def civil_from_days(z: np.ndarray) -> tuple:
    """(year, month, day) arrays of days since 1970-01-01."""
    z = np.asarray(z, np.int64) + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + np.where(mp < 10, 3, -9)
    return yoe + era * 400 + (m <= 2), m, d


def comment_pool(seed: int) -> list:
    """``COMMENT_POOL`` texts of 10-43 characters, from the seed."""
    rng = np.random.default_rng([seed, 99])
    out = []
    for n in rng.integers(10, 44, COMMENT_POOL):
        words = [_WORDS[i] for i in rng.integers(0, len(_WORDS), 12)]
        out.append(b" ".join(words)[:int(n)].ljust(int(n), b"."))
    return out


def make(spec: dict, seed: int, rows: int) -> dict:
    """{column name: integer array} (module doc) of ``rows`` lineitems:
    orders of 1-7 lines until the table is full (at scale factor 1 about
    1.5 million orders), the last one cut where the table ends."""
    sf = spec["scale_factor"]
    rng = np.random.default_rng([seed, 0])
    n_orders = rows // 4 + rows // 64 + 64
    lines = rng.integers(1, 8, n_orders)
    ends = np.cumsum(lines)
    while ends[-1] < rows:              # vanishingly rare: draw more
        lines = np.concatenate([lines, rng.integers(1, 8, n_orders)])
        ends = np.cumsum(lines)
    n_orders = int(np.searchsorted(ends, rows)) + 1
    lines, ends = lines[:n_orders], ends[:n_orders]
    order = np.repeat(np.arange(n_orders), lines)[:rows]
    starts = ends - lines
    cols = {}
    # O_ORDERKEY: the first 8 keys of every 32 are populated
    i = order.astype(np.int64) + 1
    cols["l_orderkey"] = ((i >> 3) << 5) | (i & 7)
    part = rng.integers(1, int(sf * PARTS_PER_SF) + 1, rows)
    cols["l_partkey"] = part
    s = int(sf * SUPPLIERS_PER_SF)
    cols["l_suppkey"] = (part + rng.integers(0, 4, rows) *
                         (s // 4 + (part - 1) // s)) % s + 1
    cols["l_linenumber"] = np.arange(rows) - starts[order] + 1
    qty = rng.integers(1, 51, rows)
    cols["l_quantity"] = qty * 100
    # P_RETAILPRICE in cents, Clause 4.2.3
    retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    cols["l_extendedprice"] = qty * retail
    cols["l_discount"] = rng.integers(0, 11, rows)
    cols["l_tax"] = rng.integers(0, 9, rows)
    first = days_from_civil(*START_DATE)
    last = days_from_civil(*END_DATE) - 151
    odate = rng.integers(first, last + 1, n_orders)[order]
    ship = odate + rng.integers(1, 122, rows)
    cols["l_shipdate"] = ship
    cols["l_commitdate"] = odate + rng.integers(30, 91, rows)
    receipt = ship + rng.integers(1, 31, rows)
    cols["l_receiptdate"] = receipt
    today = days_from_civil(*CURRENT_DATE)
    cols["l_returnflag"] = np.where(receipt <= today,
                                    rng.integers(0, 2, rows), 2)
    cols["l_linestatus"] = np.where(ship > today, 0, 1)
    cols["l_shipinstruct"] = rng.integers(0, 4, rows)
    cols["l_shipmode"] = rng.integers(0, 7, rows)
    cols["l_comment"] = rng.integers(0, COMMENT_POOL, rows)
    small = ("l_linenumber", "l_discount", "l_tax", "l_returnflag",
             "l_linestatus", "l_shipinstruct", "l_shipmode")
    out = {name: cols[name].astype(np.int8 if name in small else
                                   np.int16 if name == "l_comment" else
                                   np.int32 if name != "l_orderkey" else
                                   np.int64)
           for name, _id, _kind in COLUMNS}
    out["_comments"] = comment_pool(seed)
    return out


def fixture(spec: dict):
    """The program's description of the table at go-tpc's DDL types,
    carrying the layout to ``load``."""
    from tikv_tpu.datatype import FieldType, FieldTypeFlag, FieldTypeTp
    from tikv_tpu.testing.fixture import Table, TableColumn

    nn = FieldTypeFlag.NOT_NULL

    def ft(kind: tuple) -> FieldType:
        if kind[0] == "bigint":
            return FieldType.long(not_null=True)
        if kind[0] == "decimal":
            return FieldType(tp=FieldTypeTp.NEW_DECIMAL, flag=nn,
                             flen=kind[1], decimal=kind[2])
        if kind[0] == "date":
            return FieldType(tp=FieldTypeTp.DATE, flag=nn)
        return FieldType(tp=FieldTypeTp.STRING if kind[0] == "char"
                         else FieldTypeTp.VAR_CHAR, flag=nn, flen=kind[1])

    presplit = type("PresplitTable", (Table,), {
        "regions": spec["regions"],
        "region_split_size_mb": spec["region_split_size_mb"]})
    return presplit(spec["table_id"], (
        TableColumn("_tidb_rowid", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
    ) + tuple(TableColumn(name, cid, ft(kind))
              for name, cid, kind in COLUMNS))


def sst_columns(cols: dict, lo: int, hi: int) -> list:
    """Rows [lo, hi) as ``fast_mvcc_table_sst``'s columns: the DECIMALs
    scaled with their scale, the dates as packed cores, the strings as
    offsets into one blob."""
    from tikv_tpu.sst_importer import bytes_column, decimal_column

    out = []
    for name, cid, kind in COLUMNS:
        a = cols[name][lo:hi]
        if kind[0] == "bigint":
            vals = a.astype(np.int64)
        elif kind[0] == "decimal":
            vals = decimal_column(a.astype(np.int64), kind[2])
        elif kind[0] == "date":
            y, m, d = civil_from_days(a)
            vals = (y << 50) | (m << 46) | (d << 41)    # time.py's core
        else:
            texts = cols["_comments"] if name == "l_comment" \
                else TEXTS[name]
            lens = np.fromiter((len(t) for t in texts), np.int64,
                               len(texts))
            padded = np.zeros((len(texts), int(lens.max())), np.uint8)
            for i, t in enumerate(texts):
                padded[i, :len(t)] = np.frombuffer(t, np.uint8)
            keep = np.arange(padded.shape[1])[None, :] < lens[a][:, None]
            offsets = np.zeros(len(a) + 1, np.int64)
            np.cumsum(lens[a], out=offsets[1:])
            vals = bytes_column(padded[a][keep].tobytes(), offsets)
        out.append((cid, vals, None))
    return out


def load(client, store_id: int, table, cols: dict) -> float:
    """``int_table_presplit.load`` for this table: ask the program for
    what the table needs, pre-split, ImportSST load cut at the
    boundaries, then the wait for a layout the checker is done with.
    → seconds."""
    from tikv_tpu import sst_importer
    from tikv_tpu.codec.keys import table_record_key

    n = len(cols["l_orderkey"])
    want = len(boundaries(n, table.regions)) + 1
    limit = int(table.region_split_size_mb * (1 << 20))
    t0 = time.perf_counter()
    # before anything is ingested: a program whose loader cannot encode
    # a DECIMAL or a string column, or whose store cannot say when its
    # split checker is done, cannot hold this table
    kinds = getattr(sst_importer, "NATIVE_COLUMN_KINDS", ())
    if not {"decimal", "bytes"} <= set(kinds):
        raise RuntimeError(
            "this program's SST encoder takes int and float columns "
            f"only (NATIVE_COLUMN_KINDS = {kinds!r}): it cannot load "
            "lineitem's DECIMAL, DATE and string columns")
    if any("approximate_size" not in r
           for r in client.status(store_id)["regions"]):
        raise RuntimeError("the store's Status RPC lists no "
                           "approximate_size: this program cannot say "
                           "when its split checker is done")
    client.import_switch_mode(store_id, True)
    for h in boundaries(n, table.regions):
        client.split(table_record_key(table.table_id, h))

    def laid_out():
        got = _presplit.table_regions(client, table)
        return (len(got) == want and all(ld is not None for _r, ld in got),
                [(r.id, ld is not None) for r, ld in got])
    _presplit.wait_for(f"{want} regions with leaders on PD", laid_out,
                       _presplit.LAYOUT_WAIT_S)

    def build(piece):
        lo, hi = piece
        return sst_importer.fast_mvcc_table_sst(
            table.table_id, np.arange(lo, hi, dtype=np.int64),
            sst_columns(cols, lo, hi), commit_ts=client.tso())

    todo = pieces(n, table.regions, min(LOAD_CHUNK, max(1 << 12, n // 4)))
    with cf.ThreadPoolExecutor(2) as pool:
        futs = collections.deque(pool.submit(build, p) for p in todo[:2])
        for i, (lo, _hi) in enumerate(todo):
            blob = futs.popleft().result()
            if i + 2 < len(todo):
                futs.append(pool.submit(build, todo[i + 2]))
            client.ingest_sst(blob, table_record_key(table.table_id, lo),
                              chunk=2 << 20, timeout=300)
    client.import_switch_mode(store_id, False)

    def settled():
        got = _presplit.table_regions(client, table)
        sizes = _presplit.store_sizes(client, store_id,
                                      {r.id for r, _ld in got})
        return (len(got) == want and len(sizes) == want and
                all(0 < s < limit for s in sizes.values()),
                {"regions_on_pd": len(got), "want": want,
                 "limit_bytes": limit, "sizes": sizes})
    _presplit.wait_for("the split checker to size every region under "
                       "the limit", settled, _presplit.LAYOUT_WAIT_S)
    return time.perf_counter() - t0
