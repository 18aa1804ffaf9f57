"""In-memory sorted KV engine.

Reference roles: the test/local engine (tikv_kv's BTreeEngine,
components/engine_test factories) and the template for the C++ host
engine behind the same traits.  Snapshots are O(1) copy-on-write: the
engine keeps per-CF immutable generations; a snapshot pins the current
generation, and the first write after a snapshot clones the CF's list of
CHUNKS (a few thousand sorted keys each) and then copies only the chunk
it writes into.  A write beside readers therefore costs a chunk, not the
column family: a store that serves reads between every two transactions
(an order-entry stream under analytic scans) would otherwise copy
millions of keys a commit.  The read path stays zero-copy.
"""

from __future__ import annotations

import bisect
import threading
from typing import Optional

from .traits import ALL_CFS, CF_DEFAULT

# entries a chunk is cut to; one that grows past twice this splits
_CHUNK = 4096


class _Chunk:
    """A sorted run of a CF: parallel key/value lists, never empty, and
    the generation that may write it in place."""

    __slots__ = ("keys", "vals", "gen")

    def __init__(self, keys: list, vals: list, gen: int):
        self.keys, self.vals, self.gen = keys, vals, gen


class _CfData:
    """One CF generation: its chunks in key order and each chunk's first
    key.  Copy-on-write twice over: ``clone`` copies the two short lists
    and shares every chunk; a write copies the chunk it lands in unless
    this generation made it."""

    __slots__ = ("chunks", "firsts", "gen", "n", "pinned")

    def __init__(self):
        self.chunks: list[_Chunk] = []
        self.firsts: list[bytes] = []
        self.gen = 0
        self.n = 0
        self.pinned = False     # a snapshot references this generation

    def __len__(self) -> int:
        return self.n

    def clone(self) -> "_CfData":
        c = _CfData()
        c.chunks = list(self.chunks)
        c.firsts = list(self.firsts)
        c.gen = self.gen + 1    # owns no chunk yet
        c.n = self.n
        return c

    # -- positions: (chunk index, offset), end = (len(chunks), 0) --

    def lower_bound(self, key: bytes, within=bisect.bisect_left) -> tuple:
        """The first entry at or after ``key``."""
        ci = bisect.bisect_right(self.firsts, key) - 1
        if ci < 0:
            return (0, 0)
        i = within(self.chunks[ci].keys, key)
        if i == len(self.chunks[ci].keys):
            return (ci + 1, 0)
        return (ci, i)

    def upper_bound(self, key: bytes) -> tuple:
        """The first entry after ``key``."""
        return self.lower_bound(key, bisect.bisect_right)

    def end(self) -> tuple:
        return (len(self.chunks), 0)

    def get(self, key: bytes) -> Optional[bytes]:
        ci = bisect.bisect_right(self.firsts, key) - 1
        if ci < 0:
            return None
        chunk = self.chunks[ci]
        i = bisect.bisect_left(chunk.keys, key)
        if i < len(chunk.keys) and chunk.keys[i] == key:
            return chunk.vals[i]
        return None

    def slice(self, lower: Optional[bytes],
              upper: Optional[bytes]) -> tuple[list, list]:
        """Entries in [lower, upper) as two fresh lists."""
        a, i = (0, 0) if lower is None else self.lower_bound(lower)
        b, j = self.end() if upper is None else self.lower_bound(upper)
        if a > b or (a == b and i >= j):
            return [], []
        if a == b:
            c = self.chunks[a]
            return c.keys[i:j], c.vals[i:j]
        keys, vals = self.chunks[a].keys[i:], self.chunks[a].vals[i:]
        for c in self.chunks[a + 1:b]:
            keys.extend(c.keys)
            vals.extend(c.vals)
        if j:
            keys.extend(self.chunks[b].keys[:j])
            vals.extend(self.chunks[b].vals[:j])
        return keys, vals

    def flat(self) -> tuple[list, list]:
        return self.slice(None, None)

    def set_flat(self, keys: list, vals: list) -> None:
        """Replace the whole CF by one sorted run."""
        self.chunks, self.firsts, self.n = [], [], 0
        self.append_run(keys, vals)

    # -- mutation (the engine holds its mutex; this generation is not
    # pinned) --

    def _own(self, ci: int) -> _Chunk:
        c = self.chunks[ci]
        if c.gen != self.gen:
            c = self.chunks[ci] = _Chunk(list(c.keys), list(c.vals),
                                         self.gen)
        return c

    def append_run(self, keys: list, vals: list, at: Optional[int] = None
                   ) -> None:
        """A sorted run that overlaps no chunk, as chunks of its own
        before chunk ``at`` (default: after the last)."""
        new = [_Chunk(keys[o:o + _CHUNK], vals[o:o + _CHUNK], self.gen)
               for o in range(0, len(keys), _CHUNK)]
        at = len(self.chunks) if at is None else at
        self.chunks[at:at] = new
        self.firsts[at:at] = [c.keys[0] for c in new]
        self.n += len(keys)

    def put(self, key: bytes, value: bytes) -> None:
        if not self.chunks:
            self.append_run([key], [value])
            return
        ci = max(0, bisect.bisect_right(self.firsts, key) - 1)
        c = self.chunks[ci]
        i = bisect.bisect_left(c.keys, key)
        c = self._own(ci)
        if i < len(c.keys) and c.keys[i] == key:
            c.vals[i] = value
            return
        c.keys.insert(i, key)
        c.vals.insert(i, value)
        self.n += 1
        if i == 0:
            self.firsts[ci] = key
        if len(c.keys) > 2 * _CHUNK:
            half = len(c.keys) // 2
            right = _Chunk(c.keys[half:], c.vals[half:], self.gen)
            del c.keys[half:], c.vals[half:]
            self.chunks.insert(ci + 1, right)
            self.firsts.insert(ci + 1, right.keys[0])

    def delete(self, key: bytes) -> None:
        ci = bisect.bisect_right(self.firsts, key) - 1
        if ci < 0:
            return
        c = self.chunks[ci]
        i = bisect.bisect_left(c.keys, key)
        if i == len(c.keys) or c.keys[i] != key:
            return
        self._cut(ci, i, i + 1)

    def _cut(self, ci: int, i: int, j: int) -> None:
        """Drop entries [i, j) of chunk ``ci``; an emptied chunk goes."""
        c = self.chunks[ci]
        if i == 0 and j >= len(c.keys):
            self.n -= len(c.keys)
            del self.chunks[ci], self.firsts[ci]
            return
        if i >= j:
            return
        c = self._own(ci)
        self.n -= j - i
        del c.keys[i:j], c.vals[i:j]
        if i == 0:
            self.firsts[ci] = c.keys[0]

    def delete_range(self, start: bytes, end: bytes) -> None:
        a, i = self.lower_bound(start)
        b, j = self.lower_bound(end)
        if a > b or (a == b and i >= j):
            return
        if a == b:
            self._cut(a, i, j)
            return
        # the last chunk's head, the whole chunks between, the first
        # chunk's tail: from the back, so that indices stay true
        if j:
            self._cut(b, 0, j)
        for ci in range(b - 1, a, -1):
            self._cut(ci, 0, len(self.chunks[ci].keys))
        self._cut(a, i, len(self.chunks[a].keys))


class _MemIterator:
    """Bounded iterator over a pinned CF generation."""

    def __init__(self, data: _CfData, lower: Optional[bytes],
                 upper: Optional[bytes]):
        self._chunks = data.chunks
        self._data = data
        self._lo = (0, 0) if lower is None else data.lower_bound(lower)
        self._hi = data.end() if upper is None else data.lower_bound(upper)
        self._pos = (-1, 0)         # invalid until positioned

    def valid(self) -> bool:
        return self._lo <= self._pos < self._hi

    def seek(self, key: bytes) -> bool:
        self._pos = max(self._lo, self._data.lower_bound(key))
        return self.valid()

    def seek_for_prev(self, key: bytes) -> bool:
        self._pos = self._before(min(self._hi,
                                     self._data.upper_bound(key)))
        return self.valid()

    def seek_to_first(self) -> bool:
        self._pos = self._lo
        return self.valid()

    def seek_to_last(self) -> bool:
        self._pos = self._before(self._hi)
        return self.valid()

    def _before(self, pos: tuple) -> tuple:
        ci, i = pos
        if i:
            return (ci, i - 1)
        if ci:
            return (ci - 1, len(self._chunks[ci - 1].keys) - 1)
        return (-1, 0)

    def next(self) -> bool:
        assert self.valid()
        ci, i = self._pos
        self._pos = (ci, i + 1) if i + 1 < len(self._chunks[ci].keys) \
            else (ci + 1, 0)
        return self.valid()

    def prev(self) -> bool:
        assert self.valid()
        self._pos = self._before(self._pos)
        return self.valid()

    def key(self) -> bytes:
        assert self.valid()
        return self._chunks[self._pos[0]].keys[self._pos[1]]

    def value(self) -> bytes:
        assert self.valid()
        return self._chunks[self._pos[0]].vals[self._pos[1]]


class MemorySnapshot:
    def __init__(self, cfs: dict):
        self._cfs = cfs     # cf name -> pinned _CfData generation

    def get_value_cf(self, cf: str, key: bytes) -> Optional[bytes]:
        return self._cfs[cf].get(key)

    def get_value(self, key: bytes) -> Optional[bytes]:
        return self.get_value_cf(CF_DEFAULT, key)

    def iterator_cf(self, cf: str, lower: Optional[bytes] = None,
                    upper: Optional[bytes] = None) -> _MemIterator:
        return _MemIterator(self._cfs[cf], lower, upper)

    def range_cf(self, cf: str, lower: bytes,
                 upper: bytes) -> tuple[list, list, int]:
        """Bulk range read → (keys, values, prefix_skip) for the native
        columnar builder — the chunks' slices of the pinned generation
        joined, no per-key iterator hops."""
        keys, vals = self._cfs[cf].slice(lower, upper)
        return keys, vals, 0


class MemoryWriteBatch:
    def __init__(self):
        self._ops: list[tuple] = []     # ("put"|"del"|"delr", cf, ...)

    def put_cf(self, cf: str, key: bytes, value: bytes) -> None:
        self._ops.append(("put", cf, key, value))

    def delete_cf(self, cf: str, key: bytes) -> None:
        self._ops.append(("del", cf, key))

    def delete_range_cf(self, cf: str, start: bytes, end: bytes) -> None:
        self._ops.append(("delr", cf, start, end))

    def ingest_cf(self, cf: str, keys: list, vals: list) -> None:
        """Bulk sorted-run ingest (sst_importer; see _ingest_locked)."""
        self._ops.append(("ingest", cf, keys, vals))

    def put(self, key: bytes, value: bytes) -> None:
        self.put_cf(CF_DEFAULT, key, value)

    def delete(self, key: bytes) -> None:
        self.delete_cf(CF_DEFAULT, key)

    def count(self) -> int:
        return len(self._ops)

    def is_empty(self) -> bool:
        return not self._ops

    def clear(self) -> None:
        self._ops.clear()


class MemoryEngine:
    """Sorted in-memory engine implementing the KvEngine traits."""

    def __init__(self, cfs=ALL_CFS):
        self._cfs: dict[str, _CfData] = {cf: _CfData() for cf in cfs}
        # one mutex serializes mutation vs snapshot-pinning so snapshots
        # never observe a half-applied batch (the reference gets this from
        # RocksDB; scheduler threads rely on it)
        self._mu = threading.RLock()

    # -- copy-on-write plumbing --

    def _writable(self, cf: str) -> _CfData:
        data = self._cfs[cf]
        if data.pinned:
            data = data.clone()
            self._cfs[cf] = data
        return data

    # -- KvEngine --

    def snapshot(self) -> MemorySnapshot:
        with self._mu:
            for data in self._cfs.values():
                data.pinned = True
            return MemorySnapshot(dict(self._cfs))

    def write_batch(self) -> MemoryWriteBatch:
        return MemoryWriteBatch()

    def write(self, batch: MemoryWriteBatch) -> None:
        from ..utils.metrics import ENGINE_WRITE_COUNTER
        ENGINE_WRITE_COUNTER.inc()
        with self._mu:
            self._write_locked(batch)

    def _write_locked(self, batch: MemoryWriteBatch) -> None:
        for op in batch._ops:
            if op[0] == "put":
                self._put_locked(op[1], op[2], op[3])
            elif op[0] == "del":
                self._delete_locked(op[1], op[2])
            elif op[0] == "ingest":
                self._ingest_locked(op[1], op[2], op[3])
            else:
                self._delete_range(op[1], op[2], op[3])

    def _ingest_locked(self, cf: str, keys: list, vals: list) -> None:
        """Bulk-merge one pre-sorted run (the file-ingest analog of
        RocksDB's IngestExternalFile: land a whole sorted artifact
        without replaying per-key ops; sst_importer ingest).

        A run that overlaps no chunk (an ascending bulk load, a table
        loaded beside others) becomes chunks of its own at its place,
        O(1) a key; an overlapping run falls back to a two-run sorted
        merge where the ingested value wins ties (newest file wins, as
        in the LSM)."""
        if not keys:
            return
        data = self._writable(cf)
        at, i = data.lower_bound(keys[0])
        if i == 0 and (at == len(data.chunks) or
                       keys[-1] < data.firsts[at]):
            data.append_run(keys, vals, at)
            return
        ok, ov = data.flat()
        nk, nv = keys, vals
        mk: list = []
        mv: list = []
        i = j = 0
        ln, lm = len(ok), len(nk)
        while i < ln and j < lm:
            a, b = ok[i], nk[j]
            if a < b:
                mk.append(a)
                mv.append(ov[i])
                i += 1
            elif a > b:
                mk.append(b)
                mv.append(nv[j])
                j += 1
            else:           # same key: ingested run wins
                mk.append(b)
                mv.append(nv[j])
                i += 1
                j += 1
        mk.extend(ok[i:])
        mv.extend(ov[i:])
        mk.extend(nk[j:])
        mv.extend(nv[j:])
        data.set_flat(mk, mv)

    def get_value_cf(self, cf: str, key: bytes) -> Optional[bytes]:
        return self._cfs[cf].get(key)

    def get_value(self, key: bytes) -> Optional[bytes]:
        return self.get_value_cf(CF_DEFAULT, key)

    def iterator_cf(self, cf: str, lower: Optional[bytes] = None,
                    upper: Optional[bytes] = None) -> _MemIterator:
        with self._mu:
            data = self._cfs[cf]
            data.pinned = True      # iterator sees a stable generation
            return _MemIterator(data, lower, upper)

    def range_cf(self, cf: str, lower: bytes,
                 upper: bytes) -> tuple[list, list, int]:
        """Bulk range read → (keys, values, prefix_skip); see
        MemorySnapshot.range_cf.  The returned lists are independent
        copies, so no generation pin is needed."""
        with self._mu:
            keys, vals = self._cfs[cf].slice(lower, upper)
            return keys, vals, 0

    def put_cf(self, cf: str, key: bytes, value: bytes) -> None:
        with self._mu:
            self._put_locked(cf, key, value)

    def _put_locked(self, cf: str, key: bytes, value: bytes) -> None:
        self._writable(cf).put(key, value)

    def delete_cf(self, cf: str, key: bytes) -> None:
        with self._mu:
            self._delete_locked(cf, key)

    def _delete_locked(self, cf: str, key: bytes) -> None:
        self._writable(cf).delete(key)

    def _delete_range(self, cf: str, start: bytes, end: bytes) -> None:
        self._writable(cf).delete_range(start, end)

    def flush(self) -> None:
        pass
