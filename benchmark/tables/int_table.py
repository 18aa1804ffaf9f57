"""Table kind ``int_table``: TiKV ``test_coprocessor``'s int fixture, an
``id`` handle plus int columns in the order the configuration lists them.
What a table kind gives the harness: ``make`` (the data, from the seed,
numpy only), ``fixture`` (the program's description of the table, to
build plans against) and ``load`` (the data into the store)."""

from __future__ import annotations

import collections
import concurrent.futures as cf
import time

import numpy as np

LOAD_CHUNK = 1 << 20


def make(spec: dict, seed: int, rows: int) -> dict:
    """{column name: int64 array}; handles are 0..rows-1.  The same seed
    gives the same table; each column draws from a stream of its own."""
    cols = {}
    for i, (name, c) in enumerate(spec["columns"].items()):
        rng = np.random.default_rng([seed, i])
        if c["dist"] == "uniform":
            col = rng.integers(c["lo"], c["hi"], rows)
        elif c["dist"] == "uniform_dense":
            col = rng.integers(0, c["groups"], rows)
        elif c["dist"] == "uniform_sparse":
            # `groups` distinct keys drawn from [0, 2**domain_bits)
            dom = np.unique(rng.integers(
                0, 1 << c["domain_bits"], 2 * c["groups"]))
            if len(dom) < c["groups"]:
                raise ValueError("sparse key domain drew too few keys")
            dom = rng.permutation(dom)[:c["groups"]]
            col = dom[rng.integers(0, c["groups"], rows)]
        else:
            raise ValueError(f"unknown distribution {c['dist']!r}")
        cols[name] = col.astype(np.int64)
    return cols


def fixture(spec: dict):
    from tikv_tpu.testing.fixture import int_table
    return int_table(len(spec["columns"]), table_id=spec["table_id"])


def load(client, store_id: int, table, cols: dict) -> float:
    """ImportSST load with the next chunk's native SST encode running
    ahead of the wire (bench.py ``_bulk_load``'s shape); ingest RPCs
    stay serial and in ascending key order.  → seconds."""
    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.sst_importer import fast_mvcc_table_sst

    ids = {c.name: c.col_id for c in table.columns}
    n = len(next(iter(cols.values())))
    chunk = min(LOAD_CHUNK, max(1 << 14, n // 4))

    def build(s: int):
        hs = np.arange(s, min(s + chunk, n), dtype=np.int64)
        return hs, fast_mvcc_table_sst(
            table.table_id, hs,
            [(ids[name], col[s:s + chunk], None)
             for name, col in cols.items()],
            commit_ts=client.tso())

    starts = list(range(0, n, chunk))
    t0 = time.perf_counter()
    client.import_switch_mode(store_id, True)
    with cf.ThreadPoolExecutor(2) as pool:
        futs = collections.deque(pool.submit(build, s) for s in starts[:2])
        for i in range(len(starts)):
            hs, blob = futs.popleft().result()
            if i + 2 < len(starts):
                futs.append(pool.submit(build, starts[i + 2]))
            client.ingest_sst(blob,
                              table_record_key(table.table_id, int(hs[0])),
                              chunk=2 << 20, timeout=300)
    client.import_switch_mode(store_id, False)
    return time.perf_counter() - t0
