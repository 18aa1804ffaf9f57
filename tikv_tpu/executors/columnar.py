"""Columnar table snapshots — the vectorized scan feed.

Reference: TiKV decodes row-encoded KV pairs lazily per column
(tidb_query_datatype/src/codec/batch/lazy_column.rs:27) because its unit of
work is a CPU cache tile.  On TPU the scan feed must produce dense columnar
blocks without a per-row Python decode loop (SURVEY.md §7 "Decode on the hot
path"), so the storage layer can hand the executor a *columnar snapshot*:
sorted handle array + dense value/validity arrays per column — the moral
equivalent of the reference's Chunk encode_type
(tidb_query_executors/src/runner.rs:71-76) applied at rest.  (On the wire
the same field is a reply's form: a request whose DAG says ``encode_type
= "chunk"`` is answered a buffer a column, server/wire.py ``enc_chunk``.)

``ColumnarTable`` implements the scan feed consumed by both the host
executors (``BatchColumnarTableScanExecutor``) and the device runner, and
can also materialize row-encoded KV pairs for parity tests against the
row-codec path.
"""

from __future__ import annotations

import struct
from typing import Optional, Sequence

import numpy as np

from ..codec.keys import _RECORD_SEP, _TABLE_PREFIX, index_key_prefix  # type: ignore
from ..codec.number import decode_i64, encode_i64
from ..copr.dag import IndexScanDesc, TableScanDesc
from ..datatype import Column, ColumnBatch, EvalType, FieldType
from .interface import BatchExecuteResult, TimedExecutor
from .ranges import KeyRange

_I64_MIN = -(2**63)
_I64_MAX = 2**63 - 1


def _record_prefix(table_id: int) -> bytes:
    return _TABLE_PREFIX + encode_i64(table_id) + _RECORD_SEP


def handle_bounds(r: KeyRange, table_id: int) -> tuple[int, int]:
    """Map a record-key range to an inclusive-exclusive handle interval.

    Record keys are exactly prefix+8 bytes; longer keys sort between handle
    and handle+1, so a long start key starts *after* its handle and a long
    end key ends *after* its handle (inclusive of it).
    """
    prefix = _record_prefix(table_id)
    plen = len(prefix)

    def lo_of(k: bytes) -> int:
        if k <= prefix:
            return _I64_MIN
        if not k.startswith(prefix):
            return _I64_MAX  # starts past every record of this table
        if len(k) < plen + 8:
            # short key: pad with 0x00 → sorts before the first handle with
            # this prefix byte pattern; conservative: decode what we can
            h = decode_i64(k[plen:].ljust(8, b"\x00"), 0)
            return h
        h = decode_i64(k, plen)
        # long key sorts after its handle: python ints are unbounded, so
        # h+1 may exceed i64 (the caller treats bounds > i64::MAX as "all")
        return h if len(k) == plen + 8 else h + 1

    def hi_of(k: bytes) -> int:
        if k <= prefix:
            return _I64_MIN
        if not k.startswith(prefix):
            return _I64_MAX + 1
        if len(k) < plen + 8:
            h = decode_i64(k[plen:].ljust(8, b"\x00"), 0)
            return h
        h = decode_i64(k, plen)
        return h if len(k) == plen + 8 else h + 1

    return lo_of(r.start), hi_of(r.end)


class ColumnarTable:
    """Immutable columnar snapshot of one table's committed rows.

    ``handles`` must be sorted ascending (the physical key order of record
    keys).  ``columns`` maps col_id → Column aligned with ``handles``.

    ``alive``: optional boolean mask aligned with ``handles`` — False
    rows are delete tombstones left in place by incremental cache
    maintenance (copr/region_cache.py) and are invisible to every
    logical accessor (scans, counts, kv materialization).  ``None``
    means every row is live and scans stay zero-copy views.
    """

    def __init__(self, table, handles: np.ndarray, columns: dict,
                 alive: Optional[np.ndarray] = None):
        self.table = table
        self.handles = np.asarray(handles, dtype=np.int64)
        assert np.all(self.handles[1:] > self.handles[:-1]), \
            "handles must be strictly increasing"
        self.columns = columns
        self.alive = alive
        self._n_alive = len(self.handles) if alive is None \
            else int(alive.sum())

    @staticmethod
    def from_arrays(table, handles, named_columns: dict) -> "ColumnarTable":
        """named_columns: {column name: np.ndarray | Column}."""
        handles = np.asarray(handles, dtype=np.int64)
        order = np.argsort(handles, kind="stable")
        handles = handles[order]
        cols: dict = {}
        for name, data in named_columns.items():
            tc = table[name]
            if isinstance(data, Column):
                col = Column(data.eval_type, data.values[order],
                             data.validity[order], data.frac)
            else:
                arr = np.asarray(data)[order]
                col = Column.from_values(tc.field_type.eval_type, arr)
            cols[tc.col_id] = col
        return ColumnarTable(table, handles, cols)

    def __len__(self) -> int:
        return self._n_alive

    def estimated_rows(self) -> int:
        return self._n_alive

    # -- columnar scan -------------------------------------------------------

    def _range_slices(self, ranges: Sequence[KeyRange]) -> list[tuple[int, int]]:
        out = []
        n = len(self.handles)
        if not ranges:
            # no ranges = the whole snapshot (the device runner's
            # bucket-tile path keys its region feed this way)
            return [(0, n)] if n else []
        for r in ranges:
            lo, hi = handle_bounds(r, self.table.table_id)
            i = n if lo > _I64_MAX else \
                int(np.searchsorted(self.handles, max(lo, _I64_MIN),
                                    side="left"))
            j = n if hi > _I64_MAX else \
                int(np.searchsorted(self.handles, hi, side="left"))
            if i < j:
                out.append((i, j))
        return out

    def count_rows(self, ranges: Sequence[KeyRange]) -> int:
        if self.alive is None:
            return sum(j - i for i, j in self._range_slices(ranges))
        return sum(int(self.alive[i:j].sum())
                   for i, j in self._range_slices(ranges))

    def row_slices(self, ranges: Sequence[KeyRange]) -> list:
        """Public seam for the device runner's bucket-tile mapping.

        Spans are PHYSICAL row indices; with pending delete tombstones
        they would include dead rows the device kernels cannot skip, so
        the bucket-tile path is refused until the next compaction.
        """
        if self.alive is not None:
            raise ValueError("row spans unavailable under tombstones")
        return self._range_slices(ranges)

    def _ones(self, n: int) -> np.ndarray:
        """Cached all-true validity, grown monotonically and sliced —
        pk-handle columns are NOT NULL by construction and a fresh
        100M-row bool array per scan costs ~50ms."""
        ones = getattr(self, "_ones_validity", None)
        if ones is None or len(ones) < n:
            ones = np.ones(max(n, len(self.handles)), dtype=np.bool_)
            # slices of this buffer are handed out as Column.validity;
            # freeze it so an in-place mutation raises instead of
            # corrupting every later scan's all-true mask
            ones.flags.writeable = False
            self._ones_validity = ones
        return ones[:n]

    def scan_columns(self, desc, ranges: Sequence[KeyRange],
                     scaled: bool = False) -> ColumnBatch:
        """Vectorized range scan → ColumnBatch in ``desc.columns`` order.

        ``scaled``: hand a DECIMAL column the cache holds as a scaled
        integer (``Column.frac``) out as it lies, for the device
        runner's feed; every other caller gets the host pipeline's
        ``Decimal`` objects, made from the scanned rows alone."""
        if isinstance(desc, IndexScanDesc):
            return self._scan_index_columns(desc, ranges)
        slices = self._range_slices(ranges)
        if desc.desc:
            slices = [(i, j) for i, j in reversed(slices)]
        alive = self.alive

        def gather(values: np.ndarray, validity: np.ndarray):
            if alive is None and len(slices) == 1 and not desc.desc:
                i, j = slices[0]
                return values[i:j], validity[i:j]
            vparts, mparts = [], []
            for i, j in slices:
                v, m = values[i:j], validity[i:j]
                if alive is not None:
                    keep = alive[i:j]
                    v, m = v[keep], m[keep]
                if desc.desc:
                    v, m = v[::-1], m[::-1]
                vparts.append(v)
                mparts.append(m)
            if not vparts:
                return values[:0], validity[:0]
            if len(vparts) == 1:
                return vparts[0], mparts[0]
            return np.concatenate(vparts), np.concatenate(mparts)

        out_cols = []
        for info in desc.columns:
            if info.is_pk_handle:
                v, m = gather(self.handles, self._ones(len(self.handles)))
                out_cols.append(Column(EvalType.INT, v, m))
                continue
            col = self.columns.get(info.col_id)
            if col is None:
                # absent column → all default_value/NULL
                if alive is None:
                    n = sum(j - i for i, j in slices)
                else:
                    n = sum(int(alive[i:j].sum()) for i, j in slices)
                out_cols.append(Column.from_list(
                    info.field_type.eval_type, [info.default_value] * n))
                continue
            v, m = gather(col.values, col.validity)
            out = Column(col.eval_type, v, m, col.frac)
            out_cols.append(out if scaled else out.unscaled())
        return ColumnBatch([c.field_type for c in desc.columns], out_cols)

    # -- late-materialized gather (device selection vector → rows) ----------

    def _feed_positions(self, slices: tuple, desc: bool) -> np.ndarray:
        """Memoized map from scan-output position → physical row index,
        reproducing ``scan_columns``'s exact ordering (alive filtering,
        slice order, descending reversal).  The device selection path
        addresses rows by scan-output position, so this is the bridge
        back to the snapshot's physical arrays."""
        cache = getattr(self, "_feed_pos_cache", None)
        if cache is None:
            cache = self._feed_pos_cache = {}
        key = (slices, desc)
        pos = cache.get(key)
        if pos is None:
            parts = []
            for i, j in (reversed(slices) if desc else slices):
                ids = np.arange(i, j, dtype=np.int64)
                if self.alive is not None:
                    ids = ids[self.alive[i:j]]
                if desc:
                    ids = ids[::-1]
                parts.append(ids)
            pos = parts[0] if len(parts) == 1 else (
                np.concatenate(parts) if parts
                else np.empty(0, np.int64))
            cache[key] = pos
        return pos

    def gather_rows(self, desc, ranges: Sequence[KeyRange],
                    rows) -> ColumnBatch:
        """Vectorized take of ``rows`` from the scan output WITHOUT
        materializing the full scan first (the late-materialization
        gather: the device ships a compact selection vector, the host
        touches only the k surviving rows of the resident columnar
        snapshot).

        ``rows``: a bool mask over the scan output, or an int array of
        ascending scan-output positions.  Alive-mask tombstones and
        multi-range/descending scans are honored via the memoized
        position map; the common full-range ascending no-tombstone case
        gathers straight off the physical arrays.
        """
        if isinstance(desc, IndexScanDesc):
            raise ValueError("gather_rows serves table scans; index "
                             "scans use the sorted-view path")
        slices = tuple(self._range_slices(ranges))
        rows = np.asarray(rows)
        if self.alive is None and not desc.desc and len(slices) <= 1:
            lo = slices[0][0] if slices else 0
            phys = (np.flatnonzero(rows) + lo) if rows.dtype == np.bool_ \
                else rows + lo
        else:
            phys = self._feed_positions(slices, desc.desc)[rows]
        out_cols = []
        for info in desc.columns:
            if info.is_pk_handle:
                out_cols.append(Column(EvalType.INT, self.handles[phys],
                                       self._ones(len(phys))))
                continue
            col = self.columns.get(info.col_id)
            if col is None:
                out_cols.append(Column.from_list(
                    info.field_type.eval_type,
                    [info.default_value] * len(phys)))
                continue
            out_cols.append(Column(col.eval_type, col.values[phys],
                                   col.validity[phys],
                                   col.frac).unscaled())
        return ColumnBatch([c.field_type for c in desc.columns], out_cols)

    def _index_sorted(self, col_id: int):
        """Memoized (value, handle)-sorted view of one indexed column:
        → (svals, svalid, shandles, n_nulls).  MySQL NULLs sort first."""
        cache = getattr(self, "_index_order_cache", None)
        if cache is None:
            cache = self._index_order_cache = {}
        got = cache.get(col_id)
        if got is None:
            col = self.columns[col_id].unscaled()
            values, validity, handles = col.values, col.validity, \
                self.handles
            if self.alive is not None:
                keep = self.alive
                values, validity, handles = \
                    values[keep], validity[keep], handles[keep]
            nulls = ~validity
            order = np.lexsort((handles, values, nulls * -1))
            got = (values[order], validity[order],
                   handles[order], int(nulls.sum()))
            # single-slice scans hand out zero-copy views of these;
            # freeze so downstream mutation can't corrupt the memo
            for a in got[:3]:
                a.flags.writeable = False
            cache[col_id] = got
        return got

    def _index_bound(self, key: bytes, prefix: bytes, svals, shandles,
                     n_nulls: int) -> int:
        """Encoded index key → offset into the sorted index view.

        Index keys are ``prefix + mc_datum(value) [+ mc_datum(handle)]``;
        rows at or after the returned offset have encoded keys >= ``key``.
        """
        from ..codec.mc_datum import decode_mc_datum
        n = len(svals)
        if key <= prefix:
            return 0
        if not key.startswith(prefix):
            return 0 if key < prefix else n
        try:
            v, off = decode_mc_datum(key, len(prefix))
        except (ValueError, IndexError, struct.error):
            return n        # e.g. the 0xff… full-range sentinel: past all
        if v is None:       # NULL datum: the NULLs-first block
            i0, i1 = 0, n_nulls
        else:
            i0 = n_nulls + int(np.searchsorted(svals[n_nulls:], v, "left"))
            i1 = n_nulls + int(np.searchsorted(svals[n_nulls:], v, "right"))
        if off < len(key):  # handle datum tie-break within the value run
            try:
                h, _ = decode_mc_datum(key, off)
            except (ValueError, IndexError, struct.error):
                return i1   # junk after the value datum: past the run
            return i0 + int(np.searchsorted(shandles[i0:i1], h, "left"))
        return i0

    def _scan_index_columns(self, desc: IndexScanDesc,
                            ranges: Sequence[KeyRange]) -> ColumnBatch:
        """Covering-index scan: indexed column + handle in index order,
        range- and direction-aware (reference: index_scan_executor.rs).
        """
        infos = desc.columns
        want_handle = bool(infos) and infos[-1].is_pk_handle
        idx_infos = infos[:-1] if want_handle else infos
        if len(idx_infos) != 1:
            raise ValueError("columnar index scan supports single-column "
                             "indexes; use the row-decode path")
        info = idx_infos[0]
        col = self.columns[info.col_id]
        svals, svalid, shandles, n_nulls = self._index_sorted(info.col_id)
        prefix = index_key_prefix(self.table.table_id, desc.index_id)
        slices = []
        for r in ranges:
            i = self._index_bound(r.start, prefix, svals, shandles, n_nulls)
            j = self._index_bound(r.end, prefix, svals, shandles, n_nulls)
            if i < j:
                slices.append((i, j))
        if desc.desc:
            slices = [(i, j) for i, j in reversed(slices)]

        def gather(a: np.ndarray) -> np.ndarray:
            parts = [a[i:j][::-1] if desc.desc else a[i:j]
                     for i, j in slices]
            if not parts:
                return a[:0]
            # single-slice scans (the common full/point-range case) stay
            # zero-copy views of the memoized sorted arrays
            return parts[0] if len(parts) == 1 else np.concatenate(parts)

        out_cols = [Column(col.eval_type, gather(svals), gather(svalid))]
        if want_handle:
            gh = gather(shandles)
            out_cols.append(Column(EvalType.INT, gh, self._ones(len(gh))))
        return ColumnBatch([c.field_type for c in infos], out_cols)

    # -- row-codec materialization (parity tests only) -----------------------

    def to_kv_pairs(self, ranges=None) -> list[tuple[bytes, bytes]]:
        from ..codec import encode_row, table_record_key
        if ranges is None:
            indices = range(len(self.handles))
        else:
            indices = [i for lo, hi in self._range_slices(ranges)
                       for i in range(lo, hi)]
        if self.alive is not None:
            indices = [i for i in indices if self.alive[i]]
        pairs = []
        by_id = self.columns
        for i in indices:
            h = self.handles[i]
            payload = {}
            for col_id, col in by_id.items():
                v = col.get(i)
                if v is not None:
                    payload[col_id] = v
            pairs.append((table_record_key(self.table.table_id, int(h)),
                          encode_row(payload)))
        return pairs


class BatchColumnarTableScanExecutor(TimedExecutor):
    """Host scan executor over a ColumnarTable — no row decode.

    Slices the vectorized scan result progressively so the pull-model
    pipeline above it is unchanged (interface.rs:21 contract).
    """

    def __init__(self, snapshot: ColumnarTable, desc: TableScanDesc,
                 ranges: Sequence[KeyRange]):
        super().__init__()
        self._batch = snapshot.scan_columns(desc, ranges)
        self._pos = 0
        self._schema = list(desc.schema)
        self._src = (snapshot, desc, ranges)
        self._hcache = None

    @property
    def schema(self) -> list[FieldType]:
        return self._schema

    # -- paging hooks (endpoint.rs streaming/paged requests) --
    #
    # Unary pages resume by the LAST RETURNED HANDLE, not a row offset:
    # each page may see a fresh snapshot (writes land between pages),
    # and a key-based token stays exact while an offset silently skips
    # or duplicates rows when earlier handles appear/disappear.

    def _handles_for_batch(self):
        if getattr(self, "_hcache", None) is None:
            snap, desc, ranges = self._src
            tbl = snap if hasattr(snap, "_range_slices") else \
                getattr(snap, "_tbl", None)     # MvccColumnarSnapshot
            if tbl is None or isinstance(desc, IndexScanDesc) or \
                    desc.desc:
                self._hcache = False        # no resume token
            else:
                slices = tbl._range_slices(ranges)
                alive = getattr(tbl, "alive", None)
                parts = [tbl.handles[i:j] if alive is None
                         else tbl.handles[i:j][alive[i:j]]
                         for i, j in slices]
                self._hcache = parts[0] if len(parts) == 1 else (
                    np.concatenate(parts) if parts
                    else tbl.handles[:0])
        return None if self._hcache is False else self._hcache

    def resume_handle(self):
        """Token for the next page: the last consumed row's handle, or
        None when nothing was consumed / the scan cannot resume."""
        h = self._handles_for_batch()
        if h is None or self._pos == 0:
            return None
        return int(h[self._pos - 1])

    def skip_after_handle(self, token: int) -> None:
        h = self._handles_for_batch()
        if h is None:
            raise ValueError("scan does not support handle resume")
        self._pos = int(np.searchsorted(h, token, side="right"))

    def supports_resume(self) -> bool:
        return self._handles_for_batch() is not None

    def rows_consumed(self) -> int:
        return self._pos

    def _next_batch(self, scan_rows: int) -> BatchExecuteResult:
        start = self._pos
        stop = min(start + scan_rows, self._batch.num_rows)
        self._pos = stop
        chunk = self._batch.slice(start, stop)
        return BatchExecuteResult(chunk, stop >= self._batch.num_rows)
