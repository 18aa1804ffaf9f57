"""Table kind ``int_table_presplit``: ``int_table``'s data, laid out in
``regions`` regions of equal row counts BEFORE the load, as BR and TiDB
Lightning pre-split before ImportSST and as ``SPLIT TABLE ... REGIONS n``
does: the empty table is split at fixed row boundaries, every SST is cut
at them, and ``load`` returns only when the store's split checker has
sized every region under ``region_split_size_mb`` and has nothing left to
do.  The layout is then final before the first read, and the same from
run to run: handles are 0..rows-1 and the boundaries are row numbers, so
no seed moves them."""

from __future__ import annotations

import collections
import concurrent.futures as cf
import time

import numpy as np

import byname

_int_table = byname.load("tables", "int_table")

make = _int_table.make
LOAD_CHUNK = _int_table.LOAD_CHUNK
LAYOUT_WAIT_S = 120.0


def boundaries(rows: int, regions: int) -> list:
    """The first handle of each region but the first: ``regions`` runs
    of ceil(rows / regions) rows, the last one shorter."""
    per = -(-rows // regions)
    return [i * per for i in range(1, regions) if i * per < rows]


def pieces(rows: int, regions: int, chunk: int = LOAD_CHUNK) -> list:
    """[(first handle, end handle)] of the SSTs: each region's rows in
    runs of at most ``chunk``, none across a boundary."""
    edges = [0] + boundaries(rows, regions) + [rows]
    return [(s, min(s + chunk, hi)) for lo, hi in zip(edges, edges[1:])
            for s in range(lo, hi, chunk)]


def fixture(spec: dict):
    """``int_table``'s description of the table, carrying the layout to
    ``load``."""
    from tikv_tpu.testing.fixture import Table

    t = _int_table.fixture(spec)
    # the layout as class attributes: Table is frozen, and the request
    # kinds build plans from it as from any Table
    presplit = type("PresplitTable", (Table,), {
        "regions": spec["regions"],
        "region_split_size_mb": spec["region_split_size_mb"]})
    return presplit(t.table_id, t.columns)


def table_regions(client, table) -> list:
    """[(region, leader or None)] PD lists over the table's records, in
    key order (PD itself, not the client's cache)."""
    from tikv_tpu.codec.keys import table_record_range
    from tikv_tpu.storage.txn_types import encode_key

    start, end = (encode_key(k) for k in table_record_range(table.table_id))
    out, key = [], start
    while True:
        region, leader = client.pd.get_region_with_leader(key)
        out.append((region, leader))
        if not region.end_key or region.end_key >= end:
            return out
        key = region.end_key


def store_sizes(client, store_id: int, region_ids) -> dict:
    """{region id: the split checker's last estimate in bytes} from the
    store's Status RPC, for the regions it leads; 0 = not scanned since
    the region was made."""
    return {r["region"]["id"]: r["approximate_size"]
            for r in client.status(store_id)["regions"]
            if r["leader"] and r["region"]["id"] in region_ids}


def wait_for(what: str, ready, seconds: float):
    """Poll ``ready()`` (→ (done, state)) until done; past ``seconds``
    raise with the last state it saw.  An RPC that fails while the store
    is busy (the checker's scan holds the node's lock) is tried again."""
    deadline = time.monotonic() + seconds
    state = None
    while True:
        try:
            done, state = ready()
            if done:
                return state
        except Exception as e:      # noqa: BLE001 — busy store, PD gap
            state = f"{type(e).__name__}: {e}"[:200]
        if time.monotonic() > deadline:
            raise RuntimeError(f"{what}: not after {seconds:.0f}s; "
                               f"last seen {state}")
        time.sleep(0.2)


def load(client, store_id: int, table, cols: dict) -> float:
    """Pre-split, ImportSST load cut at the boundaries (the next SST's
    native encode running ahead of the wire, as ``int_table.load``),
    then the wait for a layout the checker is done with.  → seconds."""
    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.sst_importer import fast_mvcc_table_sst

    n = len(next(iter(cols.values())))
    want = len(boundaries(n, table.regions)) + 1
    limit = int(table.region_split_size_mb * (1 << 20))
    t0 = time.perf_counter()
    # a store whose Status lacks the checker's estimate predates the
    # region fan-out: say so now, not after the load
    if any("approximate_size" not in r
           for r in client.status(store_id)["regions"]):
        raise RuntimeError("the store's Status RPC lists no "
                           "approximate_size: this program cannot say "
                           "when its split checker is done")
    client.import_switch_mode(store_id, True)
    for h in boundaries(n, table.regions):
        client.split(table_record_key(table.table_id, h))

    def laid_out():
        got = table_regions(client, table)
        return (len(got) == want and all(ld is not None for _r, ld in got),
                [(r.id, ld is not None) for r, ld in got])
    wait_for(f"{want} regions with leaders on PD", laid_out, LAYOUT_WAIT_S)

    ids = {c.name: c.col_id for c in table.columns}

    def build(piece):
        lo, hi = piece
        return fast_mvcc_table_sst(
            table.table_id, np.arange(lo, hi, dtype=np.int64),
            [(ids[name], col[lo:hi], None) for name, col in cols.items()],
            commit_ts=client.tso())

    todo = pieces(n, table.regions, min(LOAD_CHUNK, max(1 << 14, n // 4)))
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
        got = table_regions(client, table)
        sizes = store_sizes(client, store_id, {r.id for r, _ld in got})
        return (len(got) == want and len(sizes) == want and
                all(0 < s < limit for s in sizes.values()),
                {"regions_on_pd": len(got), "want": want,
                 "limit_bytes": limit, "sizes": sizes})
    wait_for("the split checker to size every region under the limit",
             settled, LAYOUT_WAIT_S)
    return time.perf_counter() - t0
