"""Per-region MVCC columnar cache — the scan→device feed for real data.

Reference precedents: the in-memory region cache engine layered over the
persistent store (components/region_cache_memory_engine/src/lib.rs —
RangeCacheMemoryEngine, whose write batch MIRRORS applied writes into
the cached range instead of invalidating it) and the coprocessor
response cache keyed by region epoch / apply state
(src/coprocessor/cache.rs).  The TikvStorage adapter
(src/coprocessor/dag/storage_impl.rs:36-77) hands the executor pipeline
MVCC-resolved rows; here the same resolution happens ONCE per region
data version and materializes *columnar* arrays, so both the host
vectorized path and the TPU device runner consume dense tiles instead of
a per-row Python decode loop (SURVEY.md §7 "Decode on the hot path").

The build itself is a LADDER — device → native → interpreted: when a
:class:`~tikv_tpu.device.mvcc.DeviceMvccResolver` is wired
(server/node.py), the host pass shrinks to a flat-plane PARSE and
newest-version selection runs on the accelerator at feed-mint time,
the feed born resident (``device/mvcc.py``); the streaming ingest
pipeline (``copr/stream_build.py``) can have pre-parsed those planes
while the bulk load was still running.  Out-of-envelope schemas fall
to the native C++ one-pass build, then to the interpreted reference
loop.

Cache lines are keyed (region id, epoch version, table id, columns) and
stamped with ``data_index`` — the last applied data-mutating raft entry
(raftstore/peer.py stamps it on every RegionSnapshot; read barriers and
leader noops do not bump it).  A write no longer discards the line:
**incremental view maintenance** patches it forward.  The raft apply
path publishes each applied entry's committed write deltas to a
registered :class:`~tikv_tpu.copr.delta.DeltaSink`; on a ``data_index``
gap the cache replays them onto the cached ``ColumnarTable`` —

- new handles append into reserved slack capacity (in place: published
  snapshots view only their own row prefix),
- existing rows update positionally (copy-on-write of the column
  buffers, so in-flight scans of the previous snapshot never tear),
- deletes tombstone via an alive-mask (copy-on-write of the mask),
- ``safe_ts`` advances over every new CF_WRITE version (ROLLBACK/LOCK
  records included, matching what a rebuild would observe) and
  ``blocking_locks`` refresh from CF_LOCK transitions,

and the line compacts (drops tombstones, restores slack) when the
tombstone ratio crosses ``compact_ratio`` or slack runs out.  Fallback
to a full rebuild happens on epoch change (key miss), schema mismatch
(key miss), delta-log overflow / coverage loss, out-of-envelope ops
(delete_range, SST ingest, GC write-CF deletes), oversized delta
batches, or wholesale data replacement (snapshot apply).

Entry reuse across read_ts values is safe when ``read_ts >= safe_ts``
for BOTH the build and the request — then both see the newest committed
version of every key.  Pending blocking locks do NOT affect the
committed version set, so builds and patches proceed under them and
record them; each request then checks only the locks inside ITS key
ranges against its read_ts (matching the row scanner's range-scoped
conflict semantics) and raises KeyIsLocked exactly when the row path
would.

Each line owns a :class:`FeedLineage` — a patch journal with stable
object identity across delta generations.  The device runner keys its
HBM feed cache on it (device/runner.py feed arena) and replays the
journal's dirty row spans with chunked ``device_put`` +
``dynamic_update_slice`` instead of re-uploading the whole feed, so a
point write costs a tile patch, not a cold feed.

Lines are torn down as deliberately as they are maintained (the
device-state supervisor, device/supervisor.py):

- **device-side split** — a region split no longer invalidates the
  parent line wholesale: :meth:`RegionColumnarCache.split_lines`
  slices the parent's host state by key range into two CHILD lines
  at the new epoch (fresh lineages, exact ``data_index`` stamps from
  the split point), and the runner slices the parent's resident
  device feed into digest-verified child feeds (device/feed.py
  ``split_resident_feeds``) — a load-split under churn mints zero
  ``columnar_build``s.  Only the parent lines at the superseded
  epoch retire;
- **lifecycle invalidation** — :meth:`RegionColumnarCache.
  invalidate_region` drops a region's lines on merge/epoch change
  (superseded epochs only — split children minted above survive),
  snapshot apply and peer destroy, instead of letting stale-epoch
  lines age out of the LRU.  Leader loss is NOT a teardown event:
  the demoted store's lines stay resident as replica feeds — still
  patched by the delta stream (follower applies publish too) and
  served through the resolved-ts stale-read gate — so a later leader
  transfer back is a warm promotion, not a rebuild;
- **explicit feed teardown** — every retirement path (lifecycle,
  LRU eviction, rebuild replacement, failed bridge) fires the
  ``on_line_retired`` callback with the line's FeedLineage, which the
  supervisor routes to ``DeviceRunner.drop_feed`` so the HBM feed and
  its accounting die with the line — no ``gc.collect`` timing in the
  loop;
- **scrub audit trail** — the lineage records the per-plane content
  digests the runner computes at feed build/patch time
  (``feed_digests``); a background scrubber re-hashes the resident
  device planes and quarantines any line whose planes diverge (the
  region degrades to the host backend, then rebuilds from host truth).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import Counter, OrderedDict
from typing import Optional, Sequence

import numpy as np

from ..codec import decode_record_handle, decode_row
from ..codec.keys import table_record_range
from ..datatype import Column
from ..datatype.mydecimal import to_scaled
from ..datatype.tile import code_width
from ..engine.traits import CF_DEFAULT, CF_LOCK, CF_WRITE
from ..executors.columnar import ColumnarTable
from ..storage.mvcc.reader import _PAST_VERSIONS, MvccReader, \
    check_lock_conflict
from ..storage.txn_types import (
    Lock,
    LockType,
    append_ts,
    decode_key,
    encode_key,
    split_ts,
)
from .dag import TableScanDesc


class _TableShim:
    """Minimal ``table`` carrier for ColumnarTable (table_id only)."""

    __slots__ = ("table_id",)

    def __init__(self, table_id: int):
        self.table_id = table_id


from ..datatype import EvalType

# native builder kind codes (fastbuild.cpp Col.kind).  DECIMAL (4) is
# the scaled form (Column.frac): the column's values times ten to the
# scale its FieldType declares, as int64.
_NATIVE_KINDS = {
    EvalType.INT: 0, EvalType.DURATION: 0,
    EvalType.REAL: 1,
    EvalType.BYTES: 2,
    EvalType.DATETIME: 3, EvalType.ENUM: 3, EvalType.SET: 3,
    EvalType.DECIMAL: 4,
}
# an int64 holds every value of up to 18 digits
_MAX_SCALED_DIGITS = 18


def scaled_frac(ft) -> Optional[int]:
    """The scale a DECIMAL column of FieldType ``ft`` is held scaled at,
    or None where its type does not fix one an int64 can carry (then it
    stays an object column of ``Decimal``s, as the interpreted build
    makes it)."""
    if ft.eval_type is not EvalType.DECIMAL or \
            not 0 <= ft.decimal <= _MAX_SCALED_DIGITS or \
            not ft.decimal <= ft.flen <= _MAX_SCALED_DIGITS:
        return None
    return ft.decimal


def _scan_blocking_locks(snap, lower: bytes, upper: bytes):
    blocking_locks: list[tuple[bytes, Lock]] = []
    lit = snap.iterator_cf(CF_LOCK, lower, upper)
    ok = lit.seek_to_first()
    while ok:
        lock = Lock.from_bytes(lit.value())
        if lock.lock_type in (LockType.PUT, LockType.DELETE):
            blocking_locks.append((decode_key(lit.key()), lock))
        ok = lit.next()
    return blocking_locks


def _build_native(snap, table_id: int, col_infos: Sequence, read_ts: int):
    """Native one-pass build (tikv_tpu/native/fastbuild.cpp), or None
    when the snapshot/schema is outside the native envelope."""
    from ..native import mvcc_build_columnar
    if mvcc_build_columnar is None:
        return None
    rng = getattr(snap, "range_cf", None)
    if rng is None:
        return None
    from ..utils import tracker
    ids, kinds, scales = [], [], []
    for info in col_infos:
        if info.is_pk_handle:
            continue
        ft = info.field_type
        kind = _NATIVE_KINDS.get(ft.eval_type)
        if kind is None or info.default_value is not None:
            return None     # JSON payloads or non-NULL defaults
        if kind == 0 and ft.is_unsigned:
            kind = 3        # unsigned BIGINT: values live above 2^63
        frac = 0
        if kind == 4:
            frac = scaled_frac(ft)
            if frac is None:
                return None     # a DECIMAL without a scale int64 carries
        ids.append(info.col_id)
        kinds.append(kind)
        scales.append(frac)
    lo, hi = table_record_range(table_id)
    got = rng(CF_WRITE, encode_key(lo), encode_key(hi))
    if got is None:
        return None
    keys, vals, skip = got
    try:
        out = mvcc_build_columnar(keys, vals, read_ts, skip,
                                  tuple(ids), tuple(kinds), tuple(scales))
    except ValueError:
        # a stored datum outside the native envelope: a DECIMAL beyond
        # its column's declared scale or int64, a type the column does
        # not have, an exotic tag.  The interpreted path is the
        # behavioral reference (a datum of a column nobody asked for
        # is read past, whatever it is).
        return None
    # on the columnar_build span: what the build made of the rows
    # (``code_cols``: the short CHAR columns a feed takes as code
    # planes, datatype/tile.py: built here as the strings they are)
    tracker.annotate(decimal_cols=sum(1 for k in kinds if k == 4),
                     code_cols=sum(
                         1 for info in col_infos if not info.is_pk_handle
                         and code_width(info.field_type) is not None),
                     skipped_datums=out.get("skipped_datums", 0))

    n = out["n"]
    handles = np.frombuffer(out["handles"], dtype=np.int64)
    columns: dict = {}
    np_dtypes = {0: np.int64, 1: np.float64, 3: np.uint64, 4: np.int64}
    frac_of = dict(zip(ids, scales))
    by_id = {}
    for col_id, kind, payload, validity in out["cols"]:
        valid = np.frombuffer(validity, dtype=np.bool_)
        if kind == 2:
            # one C-level pass into the object array; the builder sets a
            # bytes payload exactly where validity is set, so the NULL
            # backfill is a vectorized masked store, not a Python loop
            values = np.empty(n, dtype=object)
            values[:] = payload
            if not valid.all():
                values[~valid] = b""
        else:
            values = np.frombuffer(payload, dtype=np_dtypes[kind])
        et = next(info.field_type.eval_type for info in col_infos
                  if not info.is_pk_handle and info.col_id == col_id)
        col = Column(et, values, valid,
                     frac_of[col_id] if kind == 4 else None)
        columns[col_id] = col
        by_id[col_id] = col
    # big values (> SHORT_VALUE_MAX_LEN) live in CF_DEFAULT: batch the
    # lookups (one bulk range fetch when the spill set is large, point
    # gets otherwise) and scatter per COLUMN with fancy indexing instead
    # of a per-row × per-column Python dict loop
    need = out["need_default"]
    if need:
        fetched, missing = _fetch_default_values(snap, table_id, need)
        if missing:
            # a spilled value is gone from BOTH the bulk map and the
            # point path — the visible version's payload is unrecoverable
            # and the interpreted reference would assert on it; only now
            # does the whole build fall back
            return None
        per_col: dict = {cid: ([], []) for cid in by_id}
        for (row, _start_ts, _user_key), raw in zip(need, fetched):
            payload_row = decode_row(raw)
            for col_id, pv in payload_row.items():
                slot = per_col.get(col_id)
                if slot is not None and pv is not None:
                    if by_id[col_id].frac is not None:
                        pv = to_scaled(pv, by_id[col_id].frac)
                        if pv is None:
                            return None     # beyond the declared scale
                    slot[0].append(row)
                    slot[1].append(pv)
        for col_id, (rows_idx, vals_list) in per_col.items():
            if not rows_idx:
                continue
            col = by_id[col_id]
            idx = np.asarray(rows_idx, dtype=np.int64)
            if col.values.dtype == object:
                for i, v in zip(rows_idx, vals_list):
                    col.values[i] = v
            else:
                col.values[idx] = np.asarray(vals_list,
                                             dtype=col.values.dtype)
            col.validity[idx] = True
    tbl = ColumnarTable(_TableShim(table_id), handles, columns)
    return tbl, out["safe_ts"]


def _fetch_default_values(snap, table_id: int, need):
    """CF_DEFAULT payloads for a builder's spill rows.

    ``need``: [(row, start_ts, user_key)].  Small sets use point gets;
    large sets do ONE bulk range fetch over the table's CF_DEFAULT slice
    and index it — the per-row get path was the measured hot spot on
    spill-heavy schemas.  Returns ``(values, missing)``: a list aligned
    with ``need`` (None where no payload was found) plus the indices of
    the missing entries, so the caller can degrade PER ROW — a bulk-map
    miss retries as a point get here, and only a payload that both
    paths miss is reported, instead of one absent value silently
    discarding the caller's entire native build (the old contract).
    """
    out: list = []
    missing: list = []
    rng = getattr(snap, "range_cf", None)
    if len(need) >= 32 and rng is not None:
        lo, hi = table_record_range(table_id)
        got = rng(CF_DEFAULT, encode_key(lo), encode_key(hi))
        if got is not None:
            keys, vals, skip = got
            by_key = {bytes(k[skip:]) if skip else bytes(k): v
                      for k, v in zip(keys, vals)}
            for i, (_row, start_ts, user_key) in enumerate(need):
                enc = append_ts(encode_key(user_key), start_ts)
                v = by_key.get(enc)
                if v is None:
                    # per-row degrade: distrust the bulk index before
                    # declaring the payload gone
                    v = snap.get_value_cf(CF_DEFAULT, enc)
                    if v is None:
                        missing.append(i)
                out.append(v)
            return out, missing
    for i, (_row, start_ts, user_key) in enumerate(need):
        v = snap.get_value_cf(CF_DEFAULT,
                              append_ts(encode_key(user_key), start_ts))
        if v is None:
            missing.append(i)
        out.append(v)
    return out, missing


def _build_device(snap, table_id: int, col_infos: Sequence,
                  read_ts: int, resolver, stream=None):
    """Device-side MVCC resolution build strategy (device/mvcc.py).

    The host does a flat-plane PARSE only (or consumes planes the
    streaming ingest pipeline already parsed AND uploaded during the
    bulk load — copr/stream_build.py); newest-committed-version
    selection runs on the accelerator at feed-mint time.  The returned
    host table is a cheap numpy mirror of the same resolution
    (vectorized takes over the winner rows — the cache line, delta
    patching and scrub digests read host truth), and the
    :class:`~tikv_tpu.device.mvcc.ColdFeedBundle` carries everything
    the runner needs to mint the feed BORN RESIDENT: raw version
    planes (possibly already device-resident), the resolve read_ts,
    and the CF_DEFAULT spill rows to host-patch after the gather.

    → (ColumnarTable, safe_ts, ColdFeedBundle) or None (out of
    envelope / native parse unavailable — the native→interpreted
    ladder takes over)."""
    from ..utils.failpoint import fail_point
    if resolver is None or not resolver.available() or \
            fail_point("device::mvcc_resolve") is not None or \
            read_ts >= (1 << 63):
        return None
    from ..device.mvcc import (
        ColdFeedBundle,
        align_planes,
        host_mirror,
        parse_write_planes,
        plane_schema,
        resolve_host,
    )
    from ..utils import tracker
    if plane_schema(col_infos) is None:
        return None
    rng = getattr(snap, "range_cf", None)
    if rng is None:
        return None
    lo, hi = table_record_range(table_id)
    got = rng(CF_WRITE, encode_key(lo), encode_key(hi))
    if got is None or not got[0]:
        return None     # empty range: the native/interpreted path is free
    keys, vals, skip = got
    planes = dev = None
    region = getattr(snap, "region", None)
    data_index = getattr(snap, "data_index", None)
    if stream is not None and region is not None and \
            data_index is not None:
        with tracker.phase("stream_take"):
            st = stream.take(region.id, table_id, data_index,
                             n_ver=len(keys),
                             first_key=bytes(keys[0][skip:]),
                             last_key=bytes(keys[-1][skip:]))
        if st is not None:
            raw_planes, dev = st
            planes = align_planes(raw_planes, col_infos)
            if planes is None:
                dev = None      # schema the stream cannot serve
    if planes is None:
        with tracker.phase("mvcc_parse"):
            planes = parse_write_planes(keys, vals, skip, col_infos)
        if planes is None:
            return None
    winners = resolve_host(planes, read_ts)
    n = len(winners)
    handles, columns = host_mirror(planes, winners, col_infos)
    # CF_DEFAULT spills among the WINNERS only (a superseded version's
    # spilled payload is never fetched — late materialization on the
    # version axis)
    spill_patches: dict = {}
    if planes.need_default:
        spill_mask = planes.has_payload[winners] == 0
        spill_rows = np.nonzero(spill_mask)[0]
        if len(spill_rows):
            by_ver = {row: (sts, uk)
                      for row, sts, uk in planes.need_default}
            need = []
            for fr in spill_rows.tolist():
                ent = by_ver.get(int(winners[fr]))
                if ent is None:
                    return None     # inconsistent parse: fall back
                need.append((fr, ent[0], ent[1]))
            fetched, missing = _fetch_default_values(snap, table_id,
                                                     need)
            if missing:
                return None     # unrecoverable payload: ladder down
            for (fr, _sts, _uk), raw in zip(need, fetched):
                payload = decode_row(raw)
                for info in col_infos:
                    if info.is_pk_handle:
                        continue
                    pv = payload.get(info.col_id)
                    if pv is not None:
                        col = columns[info.col_id]
                        col.values[fr] = pv
                        col.validity[fr] = True
                spill_patches[fr] = True
    tbl = ColumnarTable(_TableShim(table_id), handles, columns)
    bundle = ColdFeedBundle(resolver, planes, dev, n, read_ts,
                            handles, columns,
                            spill_patches=spill_patches)
    return tbl, int(planes.safe_ts), bundle


def build_region_columnar(snap, table_id: int, col_infos: Sequence,
                          read_ts: int):
    """One MVCC pass over the region ∩ table record range.

    Returns (ColumnarTable, safe_ts, blocking_locks).  Pending locks are
    recorded, not raised — the committed version set is independent of
    them; per-request conflict checks happen at serve time against the
    request's own key ranges.

    Build-strategy ladder (each rung degrades to the next on any
    envelope miss): **device** — flat-plane parse + device-side version
    resolution, available through :func:`build_region_columnar_ex` when
    the caller wires a resolver (the cold build is then an H2D copy
    plus one resolve dispatch at feed-mint time, not a host decode
    pass); **native** — the one-pass C++ resolve+decode
    (fastbuild.cpp); **interpreted** — the loop below, the behavioral
    reference.  This 3-arg entry point keeps the host-only contract
    (device rung off)."""
    from ..utils import tracker
    lo, hi = table_record_range(table_id)
    lower, upper = encode_key(lo), encode_key(hi)
    blocking_locks = _scan_blocking_locks(snap, lower, upper)

    native = _build_native(snap, table_id, col_infos, read_ts)
    if native is not None:
        tbl, safe_ts = native
        tracker.label("cold_build", "native")
        return tbl, safe_ts, blocking_locks

    reader = MvccReader(snap)
    handles: list[int] = []
    rows: list[dict] = []
    safe_ts = 0
    it = snap.iterator_cf(CF_WRITE, lower, upper)
    ok = it.seek_to_first()
    while ok:
        cur, commit_ts = split_ts(it.key())
        # versions sort newest-first, so this is the key's max commit_ts
        if commit_ts > safe_ts:
            safe_ts = commit_ts
        # version visibility lives in ONE place: the MVCC reader
        value = reader._resolve(cur, read_ts)
        if value is not None:
            handles.append(decode_record_handle(decode_key(cur)))
            rows.append(decode_row(value) if value else {})
        ok = it.seek(cur + _PAST_VERSIONS)

    columns: dict = {}
    for info in col_infos:
        if info.is_pk_handle:
            continue
        vals = [row.get(info.col_id, info.default_value) for row in rows]
        columns[info.col_id] = Column.from_list(
            info.field_type.eval_type, vals,
            unsigned=info.field_type.is_unsigned)
    tbl = ColumnarTable(_TableShim(table_id),
                        np.asarray(handles, dtype=np.int64), columns)
    tracker.label("cold_build", "interpreted")
    return tbl, safe_ts, blocking_locks


def build_region_columnar_ex(snap, table_id: int, col_infos: Sequence,
                             read_ts: int, device_resolver=None,
                             stream_source=None):
    """Ladder entry WITH the device rung: → (ColumnarTable, safe_ts,
    blocking_locks, ColdFeedBundle-or-None).  Device refusal (missing
    resolver, out-of-envelope schema, failpoint) falls through to the
    module's :func:`build_region_columnar` host ladder — looked up at
    call time, so tests substituting the host builder keep their
    seam."""
    from ..utils import tracker
    if device_resolver is not None:
        dev = _build_device(snap, table_id, col_infos, read_ts,
                            device_resolver, stream=stream_source)
        if dev is not None:
            lo, hi = table_record_range(table_id)
            locks = _scan_blocking_locks(snap, encode_key(lo),
                                         encode_key(hi))
            tbl, safe_ts, bundle = dev
            tracker.label("cold_build", "device")
            return tbl, safe_ts, locks, bundle
    tbl, safe_ts, locks = build_region_columnar(
        snap, table_id, col_infos, read_ts)
    return tbl, safe_ts, locks, None


class MvccColumnarSnapshot:
    """Columnar view of one region's table slice at a pinned data version.

    Implements the columnar scan feed (scan_columns / estimated_rows)
    consumed by executors/columnar.py and device/runner.py.

    ``feed_lineage``: patch journal shared by every delta generation of
    the same cache line — the device runner's feed-cache anchor.
    """

    def __init__(self, tbl: ColumnarTable, build_ts: int, safe_ts: int,
                 blocking_locks: Sequence[tuple[bytes, Lock]]):
        self._tbl = tbl
        self.build_ts = build_ts
        self.safe_ts = safe_ts
        self.blocking_locks = tuple(blocking_locks)
        self.feed_lineage = None
        # the lineage version THIS snapshot's data reflects (a snapshot
        # served from the line's history is older than lineage.version)
        self.feed_version: Optional[int] = None
        # smallest commit_ts of any LATER data delta (None = still the
        # newest view): reads at ts BELOW it see the same visible set
        # here as in any newer generation, so a superseded snapshot
        # keeps serving them from the line's history under write churn
        self.superseded_at: Optional[int] = None

    def valid_for(self, read_ts: int) -> bool:
        if read_ts == self.build_ts:
            return True
        return read_ts >= self.safe_ts and self.build_ts >= self.safe_ts

    def serves(self, read_ts: int) -> bool:
        """``valid_for`` and not yet superseded for ``read_ts``: no data
        delta the line knows of and this view lacks (one a later
        generation applied, or one held back for a later reader) was
        committed at or below it."""
        return self.valid_for(read_ts) and (
            self.superseded_at is None or read_ts < self.superseded_at)

    def check_locks(self, ranges, read_ts: int, bypass_locks=()) -> None:
        """Range-scoped conflict check, matching MvccReader.scan's
        semantics: only locks inside the REQUEST's ranges can block it."""
        for key, lock in self.blocking_locks:
            for r in ranges:
                if r.start <= key < r.end:
                    check_lock_conflict(lock, key, read_ts, bypass_locks)
                    break

    def scan_columns(self, desc: TableScanDesc, ranges,
                     scaled: bool = False):
        return self._tbl.scan_columns(desc, ranges, scaled=scaled)

    def to_kv_pairs(self, ranges=None):
        """Logical row pairs for the CHECKSUM admin request."""
        return self._tbl.to_kv_pairs(ranges)

    def count_rows(self, ranges) -> int:
        return self._tbl.count_rows(ranges)

    def gather_rows(self, desc, ranges, rows):
        """Late-materialization seam: vectorized alive-mask-aware take
        of the device selection vector from THIS generation's columnar
        view (executors/columnar.py gather_rows).  Delta-patched lines
        are safe by construction — the device feed is lineage-anchored
        and patched/invalidated before any selection kernel runs, and
        the gather reads the same pinned-generation buffers the feed
        reflects."""
        return self._tbl.gather_rows(desc, ranges, rows)

    def row_slices(self, ranges) -> list:
        """Row-index spans covered by ``ranges`` — the device runner's
        bucket-tile mapping (request ranges → feed row spans)."""
        return self._tbl.row_slices(ranges)

    def estimated_rows(self) -> int:
        return len(self._tbl)


class FeedLineage:
    """Bounded patch journal with stable identity across delta
    generations of one cache line.

    The device runner weak-keys its HBM feed on this object and calls
    :meth:`since` to learn which row spans changed between its feed's
    version and the line's current version.  ``None`` (journal gap) or
    any ``structural`` patch (repack, compaction, tombstones pending)
    means the feed must re-upload from the logical view instead of
    patching.

    An entry (``RegionColumnarCache._apply_deltas`` step 6) says what the
    batch did.  For the device feed: ``structural``, ``n`` (the line's
    physical rows after it, tombstones included) and, on an entry that
    is not structural, ``spans`` (the row spans to write over).  For
    what the host derived from the line's rows (device/feed.py
    ``roll_derived``), on every entry:

    - ``introduced``: the rows whose VALUES the batch wrote (updates,
      revives, inserts; a repack's inserts too), as dicts of
      ``handles`` and ``cols`` ``{col_id: (values, validity)}`` scaled
      as the line holds them.  A non-structural entry's ``spans`` are
      this (the same dicts); a delete-only batch carries it empty.
    - ``dead``: the positions the batch tombstoned, ascending, in the
      numbering of the line's VIEW before the entry (its live rows in
      handle order: what a scan returns, what a feed and the host
      planes are laid out by), and ``live``, the view's rows after it.
      Present where every row the batch did not write is where it
      was in the view, less the rows of ``dead`` before it (a
      delete-only entry's view is the view before it less those rows);
      absent where the batch renumbered the view otherwise (a repack: a
      mid insert or the slack run out; compaction; a revived
      tombstone): then nothing positional survives.

    An entry WITHOUT ``introduced`` (made by hand, or by an older
    writer) says nothing of what it did: absent is never read as empty.
    """

    __slots__ = ("version", "_base", "_patches", "_max", "_mu",
                 "feed_digests", "region_hint", "cold_bundle",
                 "split_stash", "__weakref__")

    def __init__(self, max_patches: int = 64):
        self.version = 0
        self._base = 0          # version the oldest retained patch starts at
        self._patches: list = []
        self._max = max_patches
        self._mu = threading.Lock()
        # device-state integrity bookkeeping (device/supervisor.py):
        # the runner mirrors each feed's per-plane content digests here
        # at build/patch time — {feed_key: (version, digest tuple)} —
        # and region teardown uses region_hint to attribute quarantines
        self.feed_digests: dict = {}
        self.region_hint = None
        # one-shot device-resolve artifacts from a cold device build
        # (device/mvcc.py ColdFeedBundle): the runner's first feed miss
        # mints the born-resident feed from them; any delta landing
        # first releases them (the host upload path is always correct)
        self.cold_bundle = None
        # device-side region split (FeedStore.split_resident_feeds): on a
        # CHILD lineage, the digest-verified feed candidates sliced
        # from the parent's resident planes — the child's first feed
        # miss consumes a match instead of re-uploading from host
        self.split_stash = None

    @property
    def depth(self) -> int:
        """The entries the journal keeps: what lags a line by more has
        no bridge (``since``)."""
        return self._max

    def stash_cold(self, bundle) -> None:
        bundle.lineage_v = self.version
        with self._mu:
            old, self.cold_bundle = self.cold_bundle, bundle
        if old is not None:
            old.release()

    def take_cold(self, version):
        """Pop the cold bundle iff it still reflects ``version``
        (one-shot; a stale bundle is released, never served)."""
        with self._mu:
            b, self.cold_bundle = self.cold_bundle, None
        if b is None:
            return None
        if getattr(b, "lineage_v", -1) != version:
            b.release()
            return None
        return b

    def drop_cold(self) -> None:
        with self._mu:
            b, self.cold_bundle = self.cold_bundle, None
        if b is not None:
            b.release()

    def record(self, patch: dict) -> None:
        with self._mu:
            self._patches.append(patch)
            self.version += 1
            while len(self._patches) > self._max:
                self._patches.pop(0)
                self._base += 1
            stale, self.cold_bundle = self.cold_bundle, None
        if stale is not None:
            stale.release()     # the line moved on before the mint

    def since(self, version: int, until: Optional[int] = None):
        """Patches bridging ``version`` → ``until`` (default: current),
        oldest first, or None when the journal no longer covers that
        span.  ``until`` pins a consumer to ITS snapshot's generation —
        the line may advance concurrently."""
        with self._mu:
            top = self.version if until is None else until
            if top > self.version or version > top or \
                    version < self._base:
                return None
            return list(self._patches[version - self._base:
                                      top - self._base])


class _LineState:
    """Mutable slack-capacity arrays behind one cache line.

    Publish-safety invariant: rows [0, n) of every CURRENT buffer are
    never mutated in place — positional updates and tombstones swap in
    copied buffers (copy-on-write), appends write only into slack at
    [n, cap).  Published snapshots hold views of the buffers current at
    publish time, so concurrent scans never observe a torn patch.
    """

    __slots__ = ("table_id", "col_meta", "col_frac", "cap", "n", "n_dead",
                 "handles", "cols", "alive", "locks", "safe_ts",
                 "build_ts", "lineage")

    SLACK_MIN = 256

    def __init__(self, table_id: int, col_infos: Sequence, tbl,
                 safe_ts: int, build_ts: int, blocking_locks):
        self.table_id = table_id
        # col_id -> (eval_type, default_value) for non-pk columns
        self.col_meta = {info.col_id: (info.field_type.eval_type,
                                       info.default_value)
                         for info in col_infos if not info.is_pk_handle}
        # col_id -> scale, for the DECIMAL columns the build left scaled
        # (Column.frac): every later row of theirs is scaled alike
        self.col_frac = {cid: col.frac for cid, col in tbl.columns.items()
                         if col.frac is not None}
        n = len(tbl.handles)
        self.n = n
        self.n_dead = 0
        self.cap = n + max(self.SLACK_MIN, n >> 3)
        self.handles = np.empty(self.cap, np.int64)
        self.handles[:n] = tbl.handles
        self.cols: dict = {}
        for col_id, col in tbl.columns.items():
            vals = np.empty(self.cap, dtype=col.values.dtype)
            vals[:n] = col.values
            valid = np.zeros(self.cap, np.bool_)
            valid[:n] = col.validity
            self.cols[col_id] = [vals, valid]
        self.alive = None
        self.locks = {key: lock for key, lock in blocking_locks}
        self.safe_ts = safe_ts
        self.build_ts = max(build_ts, safe_ts)
        self.lineage = FeedLineage()

    # -- publishing ----------------------------------------------------

    def publish(self) -> MvccColumnarSnapshot:
        n = self.n
        columns = {cid: Column(self.col_meta[cid][0], bufs[0][:n],
                               bufs[1][:n], self.col_frac.get(cid))
                   for cid, bufs in self.cols.items()}
        alive = self.alive[:n] if self.alive is not None else None
        tbl = ColumnarTable.__new__(ColumnarTable)
        # skip the O(n) sortedness assert of __init__: the state
        # maintains it by construction on every patch
        tbl.table = _TableShim(self.table_id)
        tbl.handles = self.handles[:n]
        tbl.columns = columns
        tbl.alive = alive
        tbl._n_alive = n - self.n_dead
        snap = MvccColumnarSnapshot(
            tbl, self.build_ts, self.safe_ts,
            sorted(self.locks.items()))
        snap.feed_lineage = self.lineage
        snap.feed_version = self.lineage.version
        return snap

    # -- patch primitives ----------------------------------------------

    def _pos_of(self, handle: int):
        view = self.handles[:self.n]
        pos = int(np.searchsorted(view, handle))
        return pos, pos < self.n and int(view[pos]) == handle

    def _payload_cols(self, payload: dict):
        """Row payload → {col_id: (value, valid)} over the full schema
        (an MVCC PUT replaces the whole row: absent columns revert to
        their default/NULL)."""
        out = {}
        for cid, (_et, default) in self.col_meta.items():
            v = payload.get(cid, default)
            out[cid] = (v, v is not None)
        return out

    def scaled_payload(self, payload: dict) -> Optional[dict]:
        """``payload`` with each scaled column's ``Decimal`` as the
        integer the line holds, or None where one does not fit its
        column's scale or int64: the line cannot take the row, and the
        caller rebuilds it (the interpreted build then keeps that
        column as objects)."""
        if not self.col_frac:
            return payload
        out = dict(payload)
        for cid, frac in self.col_frac.items():
            v = out.get(cid)
            if v is None:
                continue
            v = to_scaled(v, frac) if hasattr(v, "scaleb") else None
            if v is None:
                return None
            out[cid] = v
        return out

    def rows_of(self, written) -> dict:
        """``written`` ([(handle, payload)], payloads scaled) as a
        journal entry carries rows: ``handles`` and ``cols``
        ``{col_id: (values, validity)}`` at the line's dtypes, a NULL as
        ``_set_row`` stores it."""
        k = len(written)
        cols = {cid: (np.empty(k, dtype=vals.dtype), np.empty(k, np.bool_))
                for cid, (vals, _valid) in self.cols.items()}
        for i, (_h, payload) in enumerate(written):
            for cid, (v, ok) in self._payload_cols(payload).items():
                vals, valid = cols[cid]
                vals[i] = v if ok else \
                    (b"" if vals.dtype == object else 0)
                valid[i] = ok
        return {"handles": np.fromiter((h for h, _p in written), np.int64, k),
                "cols": cols}

    def _cow_columns(self) -> None:
        for cid, bufs in self.cols.items():
            self.cols[cid] = [bufs[0].copy(), bufs[1].copy()]

    def _cow_alive(self) -> None:
        if self.alive is None:
            self.alive = np.ones(self.cap, np.bool_)
        else:
            self.alive = self.alive.copy()

    def _set_row(self, pos: int, payload: dict) -> None:
        for cid, (v, ok) in self._payload_cols(payload).items():
            vals, valid = self.cols[cid]
            vals[pos] = v if ok else \
                (b"" if vals.dtype == object else 0)
            valid[pos] = ok

    def _repack(self, inserts) -> None:
        """One vectorized pass: drop tombstones, merge ``inserts``
        ([(handle, payload)]) at their sorted positions, restore slack.
        Every buffer is fresh, so published snapshots are untouched."""
        n = self.n
        if self.alive is not None:
            keep = self.alive[:n]
            base_h = self.handles[:n][keep]
        else:
            base_h = self.handles[:n].copy()
        ins = sorted(inserts, key=lambda kv: kv[0])
        ins_h = np.asarray([h for h, _ in ins], dtype=np.int64)
        pos = np.searchsorted(base_h, ins_h)
        new_h = np.insert(base_h, pos, ins_h) if len(ins) else base_h
        new_n = len(new_h)
        cap = new_n + max(self.SLACK_MIN, new_n >> 3)
        handles = np.empty(cap, np.int64)
        handles[:new_n] = new_h
        new_cols: dict = {}
        for cid, (vals, valid) in self.cols.items():
            et, default = self.col_meta[cid]
            bv = vals[:n][keep] if self.alive is not None else vals[:n]
            bm = valid[:n][keep] if self.alive is not None else valid[:n]
            if len(ins):
                iv, im = [], []
                for _h, payload in ins:
                    v = payload.get(cid, default)
                    im.append(v is not None)
                    iv.append(v if v is not None else
                              (b"" if vals.dtype == object else 0))
                bv = np.insert(bv, pos, np.asarray(iv, dtype=vals.dtype)
                               if vals.dtype != object else
                               np.fromiter(iv, dtype=object,
                                           count=len(iv)))
                bm = np.insert(bm, pos, np.asarray(im, dtype=np.bool_))
            nv = np.empty(cap, dtype=vals.dtype)
            nv[:new_n] = bv
            nm = np.zeros(cap, np.bool_)
            nm[:new_n] = bm
            new_cols[cid] = [nv, nm]
        self.handles = handles
        self.cols = new_cols
        self.cap = cap
        self.n = new_n
        self.n_dead = 0
        self.alive = None

    def _merge_tail(self, pos: int, inserts) -> None:
        """``inserts`` ([(handle, payload)], none of them present)
        merged among the line's rows from ``pos`` on: written into the
        slack, then rows [``pos``, n + k) put in handle order.  The rows
        that move are visible to published snapshots, so the buffers
        are copied first (as a positional update copies them)."""
        n, k = self.n, len(inserts)
        ins = sorted(inserts, key=lambda kv: kv[0])
        self.handles = self.handles.copy()
        self._cow_columns()
        self.handles[n:n + k] = [h for h, _ in ins]
        for i, (_h, payload) in enumerate(ins):
            self._set_row(n + i, payload)
        order = pos + np.argsort(self.handles[pos:n + k], kind="stable")
        self.handles[pos:n + k] = self.handles[order]
        for vals, valid in self.cols.values():
            vals[pos:n + k] = vals[order]
            valid[pos:n + k] = valid[order]
        self.n = n + k

    def tombstone_ratio(self) -> float:
        return self.n_dead / self.n if self.n else 0.0


def _merge_spans(positions, gap: int = 32):
    """Sorted unique row positions → merged (lo, hi) half-open spans."""
    spans = []
    for p in positions:
        if spans and p < spans[-1][1] + gap:
            spans[-1][1] = p + 1
        else:
            spans.append([p, p + 1])
    return [(lo, hi) for lo, hi in spans]


# lines one region may hold at once: its scan schemas (a table of the
# region and the columns a plan reads), and during a split the
# superseded epoch's beside the children's.  Small and fixed: the
# operator's bound counts regions, and this keeps the worst case at
# ``capacity`` times it.  (Asked for by name by the benchmark's cell of
# three plans over one table, so that a program whose bound counts
# lines is not run on it: benchmark/requests/tpch_q1_streams.py.)
SCHEMAS_PER_REGION = 8


class RegionColumnarCache:
    """Delta-maintained columnar lines keyed by (region, epoch version,
    table, columns), bounded by REGIONS: ``capacity`` is the number of
    regions whose lines are kept, as the option's documentation tells
    the operator (``coprocessor.region-cache-capacity``).  A region's
    lines under different scan schemas (another table of the region,
    another column set: Q1's seven columns beside Q6's four) stay and
    leave TOGETHER: a region is as recently used as its most recently
    used line, and past the bound the least recently used region goes,
    every line of it.  One region holds at most ``SCHEMAS_PER_REGION``
    (the module's) lines (its least recently used line goes first), so the worst case
    stays ``capacity`` x that.

    Thread-safe: coprocessor requests arrive on concurrent gRPC handler
    threads; builds AND delta patches for one (line, data version) are
    serialized on per-version events so a slow full-region MVCC build
    never holds the global lock, and concurrent bridges of
    one line serialize on the line's own mutex.

    ``delta_source`` (a :class:`~tikv_tpu.copr.delta.DeltaSink`) supplies
    committed-write deltas; without one every data-version change falls
    back to a rebuild, which is exactly the pre-delta behavior.
    """

    # inserts that land among a line's last rows this far from its end
    # are merged in place of a repack (the largest bucket a feed patch's
    # span is widened to: device/feed.py PATCH_BUCKETS)
    TAIL_MERGE_ROWS = 4096

    def __init__(self, capacity: int = 8, delta_source=None,
                 compact_ratio: float = 0.25,
                 max_delta_rows: int = 1 << 16):
        self._lines: "OrderedDict[tuple, _Line]" = OrderedDict()
        self._capacity = capacity
        self._lock = threading.Lock()
        self._delta_source = delta_source
        self._compact_ratio = compact_ratio
        self._max_delta_rows = max_delta_rows
        # (base_key, data_index) -> threading.Event for in-flight
        # build/patch; waiters block on the event, not the global lock
        self._building: dict = {}
        self.hits = 0
        self.misses = 0         # total builds (cold misses + rebuilds)
        self.deltas = 0         # data-version gaps bridged by patching
        self.rebuilds = 0       # gaps that fell back to a full rebuild
        self.compactions = 0
        self.tail_merges = 0
        self.deltas_held = 0
        self.invalidations = 0  # lines dropped by lifecycle events
        # lines dropped by the two bounds: ``region_lru`` with their
        # whole region past ``capacity`` regions, ``schema_bound`` alone
        # past SCHEMAS_PER_REGION lines of one region
        self.evictions = {"region_lru": 0, "schema_bound": 0}
        self.device_builds = 0  # cold builds served by device resolve
        # device-side MVCC resolution (the cold-path kill): a
        # DeviceMvccResolver enables the device rung of the build
        # ladder; a ColdStreamBuilder supplies planes parsed + uploaded
        # during bulk ingest (both wired by server/node.py)
        self.device_resolver = None
        self.stream_source = None
        # epoch fence: region id -> lowest epoch version still allowed
        # to cache.  A build racing a split can otherwise re-insert a
        # superseded-epoch line AFTER invalidate_region already swept it
        self._epoch_floor: dict = {}
        # sweep-generation fence for SAME-epoch invalidations (leader
        # loss, snapshot apply, peer destroy): a build that started
        # before the sweep serves its answer but must not re-insert
        self._sweep_gen: dict = {}
        # retirement hook: called with each dropped line's FeedLineage
        # (lifecycle invalidation, LRU eviction, rebuild replacement,
        # failed bridge) — the device-state supervisor wires this to
        # DeviceRunner.drop_feed so HBM teardown is explicit
        self.on_line_retired = None
        self.splits = 0         # region splits served by line slicing
        # re-mint storm control: when set (a RemintGovernor from
        # device/supervisor.py), every columnar_build first takes a
        # concurrency permit from the priority queue — a mass
        # invalidation degrades to bounded, hot-first rebuilds instead
        # of a host-link stampede.  None = unthrottled (the default)
        self.remint_gate = None
        # decayed per-region request rate, the "hot regions first"
        # priority signal for the governor: region id -> [rate, stamp]
        self._heat: dict = {}

    # -- observability --------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            lines = [{
                "region": key[0],
                "epoch": key[1],
                "table": key[2],
                "data_index": line.data_index,
                "rows": line.state.n if line.state else 0,
                "tombstone_ratio": round(line.state.tombstone_ratio(), 4)
                if line.state else 0.0,
                "feed_version": line.state.lineage.version
                if line.state else 0,
                # the lineage's digest journal (mirrored by the device
                # runner at feed build/patch time) — the host-visible
                # audit record per line: how many feeds carry digests
                # and the newest generation they cover.  Snapshot the
                # dict ONCE (C-atomic) — the runner inserts under its
                # own lock, and iterating live would race
                **self._digest_summary(line),
            } for key, line in self._lines.items()]
            evictions = dict(self.evictions)
        per_region = Counter(ln["region"] for ln in lines)
        out = {"hits": self.hits, "misses": self.misses,
               "deltas": self.deltas, "rebuilds": self.rebuilds,
               "compactions": self.compactions,
               "tail_merges": self.tail_merges,
               "deltas_held": self.deltas_held,
               "invalidations": self.invalidations,
               "evictions": evictions,
               "device_builds": self.device_builds,
               "splits": self.splits,
               "resident_lines": len(lines), "regions": len(per_region),
               "schemas_per_region_max": max(per_region.values(),
                                             default=0),
               "lines": lines}
        if self._delta_source is not None:
            out["delta_log"] = self._delta_source.stats()
        return out

    @staticmethod
    def _digest_summary(line) -> dict:
        if line.state is None:
            return {"digest_feeds": 0, "digest_version": None}
        vals = list(line.state.lineage.feed_digests.values())
        return {
            "digest_feeds": len(vals),
            "digest_version": max((v for v, _d in vals
                                   if v is not None), default=None),
        }

    def _publish_lines(self) -> None:
        from ..utils.metrics import COPR_RESIDENT_LINES
        COPR_RESIDENT_LINES.set(len(self._lines))

    def region_resident(self, region_id: int) -> int:
        """Live lines keyed to ``region_id`` (any epoch) — the warm-
        failover precondition: a leader-gain promotion is warm only
        when this store already holds delta-patched lines for the
        region (device/supervisor.py ``on_role_change``)."""
        with self._lock:
            return sum(1 for key in self._lines if key[0] == region_id)

    # -- region heat (storm-control priority signal) ---------------------

    _HEAT_HALFLIFE_S = 30.0

    def _note_heat(self, region_id: int) -> None:
        now = time.monotonic()
        with self._lock:
            st = self._heat.get(region_id)
            if st is None:
                self._heat[region_id] = [1.0, now]
                while len(self._heat) > 4096:
                    self._heat.pop(next(iter(self._heat)))
            else:
                st[0] = st[0] * 0.5 ** ((now - st[1]) /
                                        self._HEAT_HALFLIFE_S) + 1.0
                st[1] = now

    def region_heat(self, region_id: int) -> float:
        """Decayed request rate for ``region_id`` — the rebuild-queue
        priority: after a mass invalidation the regions users are
        actually hitting re-mint first, cold tail last."""
        with self._lock:
            st = self._heat.get(region_id)
            if st is None:
                return 0.0
            return st[0] * 0.5 ** ((time.monotonic() - st[1]) /
                                   self._HEAT_HALFLIFE_S)

    # -- lifecycle teardown ---------------------------------------------

    def _retire(self, line) -> None:
        """Hand the dropped line's lineage to the retirement hook (feed
        teardown).  Never raises: teardown runs on apply/drive paths."""
        lineage = line.state.lineage if line is not None and \
            line.state is not None else None
        if lineage is not None:
            lineage.drop_cold()     # unminted resolve artifacts die too
        cb = self.on_line_retired
        if cb is not None and lineage is not None:
            try:
                cb(lineage)
            except Exception:   # noqa: BLE001 — teardown is best-effort
                import logging
                logging.getLogger(__name__).warning(
                    "cache line retirement hook failed", exc_info=True)

    def invalidate_region(self, region_id: int,
                          keep_epoch: Optional[int] = None) -> int:
        """Eagerly drop ``region_id``'s lines — the lifecycle teardown
        entry point (split/merge/epoch change pass ``keep_epoch`` =
        the surviving epoch version; snapshot apply / peer destroy /
        failed promotion drop everything — leader loss deliberately
        does NOT call this anymore: demoted lines stay resident as
        replica feeds, patched by the same delta stream and served
        through the resolved-ts stale-read gate).  Superseded-epoch
        lines can never be hit again (the key embeds the epoch), so
        without this they would linger until LRU pressure or GC."""
        dropped = []
        with self._lock:
            if keep_epoch is not None:
                # fence in-flight builds: a pre-split snapshot's build
                # finishing after this sweep must not resurrect a
                # superseded-epoch line (it serves uncached instead).
                # Re-inserting moves the key to the dict's end, so the
                # size bound below evicts the LEAST-RECENTLY-UPDATED
                # region's fence, never a hot one's
                floor = max(self._epoch_floor.pop(region_id, 0),
                            keep_epoch)
                self._epoch_floor[region_id] = floor
                while len(self._epoch_floor) > 4096:
                    self._epoch_floor.pop(next(iter(self._epoch_floor)))
            else:
                # same-epoch sweeps (leader loss / snapshot apply /
                # destroy) are fenced by generation: any build in
                # flight re-checks the gen before inserting.  Split
                # sweeps must NOT bump it — a build at the SURVIVING
                # epoch is welcome to cache (old epochs are fenced by
                # the floor above)
                gen = self._sweep_gen.pop(region_id, 0) + 1
                self._sweep_gen[region_id] = gen
                while len(self._sweep_gen) > 4096:
                    self._sweep_gen.pop(next(iter(self._sweep_gen)))
            for key in list(self._lines):
                if key[0] != region_id:
                    continue
                if keep_epoch is not None and key[1] == keep_epoch:
                    continue
                dropped.append(self._lines.pop(key))
            self.invalidations += len(dropped)
            self._publish_lines()
        for line in dropped:
            self._retire(line)
        return len(dropped)

    # -- device-side region split ----------------------------------------

    def split_lines(self, left, right, left_index: Optional[int],
                    right_index: Optional[int]) -> list:
        """Serve a region split by SLICING the parent's cached lines
        into two child lines at the split key — the C-Store
        reorganization-as-cheap-operation move: zero ``columnar_build``,
        exact ``data_index`` stamps, fresh lineages at the children's
        epochs.  The superseded parent lines are NOT retired here; the
        imminent ``invalidate_region(left.id, keep_epoch=new)`` sweep
        does that AFTER the device runner had its chance to slice the
        resident parent feeds (device/supervisor.py orders the two).

        Returns one split spec per sliced parent line for
        ``FeedStore.split_resident_feeds``: {parent_lineage,
        parent_version, pos, n_parent, left: {lineage, n}, right: ...}.
        """
        if left_index is None:
            return []
        old_epoch = left.epoch.version - 1
        with self._lock:
            parents = [(k, self._lines[k]) for k in list(self._lines)
                       if k[0] == left.id and k[1] == old_epoch]
        specs = []
        for key, line in parents:
            spec = self._split_one(key, line, left, right, left_index,
                                   right_index)
            if spec is not None:
                specs.append(spec)
                self.splits += 1
        return specs

    def _split_one(self, key, line, left, right, left_index: int,
                   right_index: Optional[int]):
        # a line lagging behind the split point bridges forward first
        # (split admin commands don't bump data_index, so left_index is
        # exactly the last pre-split write).  No snapshot is available
        # here: deltas whose payloads spilled past short_value fail the
        # bridge and the line just invalidates — rebuild fallback.
        if line.state is None or line.data_index is None or \
                line.data_index > left_index:
            return None
        if line.data_index < left_index or line.pending:
            try:
                if self._bridge(line, None, left.id, left_index) is None:
                    return None
            except Exception:   # noqa: BLE001 — any surprise: rebuild
                return None
        with line.mu:
            st = line.state
            if st is None or line.data_index != left_index:
                return None
            n = st.n
            lo_key, hi_key = table_record_range(st.table_id)
            sk = right.start_key
            if sk:
                # region boundaries hold ENGINE keys (mode prefix +
                # memcomparable); the handle comparison below needs
                # the user-key form
                try:
                    sk = decode_key(sk)
                except Exception:   # noqa: BLE001 — non-engine-form key
                    return None
            if not sk or sk <= lo_key:
                pos = 0
            elif sk >= hi_key:
                pos = n
            else:
                try:
                    pos = int(np.searchsorted(
                        st.handles[:n], decode_record_handle(sk)))
                except Exception:   # noqa: BLE001 — non-record split key
                    return None
            parent_lineage = st.lineage
            parent_version = st.lineage.version
            children = []
            for side, region, data_index in (
                    ("left", left, left_index),
                    ("right", right, right_index)):
                if data_index is None:
                    continue    # no right peer on this store
                lo, hi = (0, pos) if side == "left" else (pos, n)
                child = self._child_state(st, lo, hi, region.id)
                children.append({
                    "side": side, "lineage": child.lineage,
                    "n": child.n, "state": child,
                    "key": (region.id, region.epoch.version) + key[2:],
                    "data_index": data_index})
        # insert the child lines under the global lock.  Capacity is
        # deliberately NOT enforced here: evicting the (LRU-oldest)
        # parent now would tear down the resident feed the device split
        # is about to slice — the keep_epoch sweep right behind us
        # retires the parents and restores the bound.
        minted = []
        with self._lock:
            for ch in children:
                ckey = ch["key"]
                if ckey[1] < self._epoch_floor.get(ckey[0], 0) or \
                        ckey in self._lines:
                    continue    # a racing build won: keep its line
                snap = ch["state"].publish()
                self._lines[ckey] = _Line(ckey, ch["data_index"], snap,
                                          ch["state"])
                self._lines.move_to_end(ckey)
                minted.append(ch)
            self._publish_lines()
        if not minted:
            return None
        spec = {"parent_lineage": parent_lineage,
                "parent_version": parent_version,
                "pos": pos, "n_parent": n, "left": None, "right": None}
        for ch in minted:
            # "state" rides along for the device split's digest
            # re-anchor (child digests recompute from HOST truth);
            # the spec is consumed synchronously in the apply path,
            # so the strong ref is transient
            spec[ch["side"]] = {"lineage": ch["lineage"], "n": ch["n"],
                                "state": ch["state"]}
        return spec

    @staticmethod
    def _child_state(st: "_LineState", lo: int, hi: int,
                     region_id: int) -> "_LineState":
        """Child _LineState = parent's rows [lo, hi) with fresh slack
        buffers and a fresh FeedLineage (version 0 — the device split
        mints the matching child feed at the same version)."""
        child = _LineState.__new__(_LineState)
        child.table_id = st.table_id
        child.col_meta = dict(st.col_meta)
        child.col_frac = dict(st.col_frac)
        n = hi - lo
        child.n = n
        cap = n + max(_LineState.SLACK_MIN, n >> 3)
        child.cap = cap
        handles = np.empty(cap, np.int64)
        handles[:n] = st.handles[lo:hi]
        child.handles = handles
        child.cols = {}
        for cid, (vals, valid) in st.cols.items():
            nv = np.empty(cap, dtype=vals.dtype)
            nv[:n] = vals[lo:hi]
            nm = np.zeros(cap, np.bool_)
            nm[:n] = valid[lo:hi]
            child.cols[cid] = [nv, nm]
        if st.alive is not None:
            alive = np.ones(cap, np.bool_)
            alive[:n] = st.alive[lo:hi]
            child.n_dead = int(n - np.count_nonzero(alive[:n]))
            child.alive = alive if child.n_dead else None
        else:
            child.alive = None
            child.n_dead = 0
        # conservative: every parent lock travels to both children —
        # extra locks only over-block a read, never under-block it
        child.locks = dict(st.locks)
        child.safe_ts = st.safe_ts
        child.build_ts = st.build_ts
        child.lineage = FeedLineage()
        child.lineage.region_hint = region_id
        return child

    # -- lookup ---------------------------------------------------------

    def get(self, snap, dag) -> Optional[MvccColumnarSnapshot]:
        """Columnar snapshot for a TableScan dag over a region snapshot,
        or None when the snapshot carries no data-version stamp.  Raises
        KeyIsLocked when a pending lock inside the request's ranges
        conflicts at dag.start_ts."""
        scan = dag.executors[0]
        region = getattr(snap, "region", None)
        data_index = getattr(snap, "data_index", None)
        if region is None or data_index is None:
            return None
        base_key = (region.id, region.epoch.version, scan.table_id,
                    tuple((c.col_id, c.is_pk_handle, c.field_type.tp)
                          for c in scan.columns))
        start_ts = dag.start_ts
        self._note_heat(region.id)
        ent = lock_src = None
        while True:
            wait_ev = None
            line = None
            with self._lock:
                line = self._lines.get(base_key)
                got = self._lookup_locked(line, data_index, start_ts)
                if got is not None:
                    ent, lock_src = got
                    self._lines.move_to_end(base_key)
                    self.hits += 1
                    self._count("hit")
                    break
                bkey = (base_key, data_index)
                wait_ev = self._building.get(bkey)
                if wait_ev is None:
                    self._building[bkey] = threading.Event()
                    # generation at build start: an invalidation sweep
                    # landing while we build fences the insert
                    gen0 = self._sweep_gen.get(base_key[0], 0)
            if wait_ev is not None:
                wait_ev.wait()
                continue        # re-check: the builder's entry may serve us
            try:
                ent, lock_src = self._materialize(
                    snap, dag, base_key, line, data_index, start_ts,
                    gen0)
                break
            finally:
                with self._lock:
                    ev = self._building.pop((base_key, data_index), None)
                if ev is not None:
                    ev.set()
        lock_src.check_locks(dag.ranges, start_ts)
        return ent

    def get_fast(self, snap, base_key: tuple, ranges,
                 start_ts: int) -> Optional[MvccColumnarSnapshot]:
        """Warm-hit-only lookup for the compiled request fast path
        (server/fastpath.py): ``base_key`` was derived ONCE at class
        learn time, so a repeat request pays one dict probe instead of
        re-deriving the key from its (skipped) plan decode.  Returns
        None whenever the snapshot's region/epoch no longer matches
        the learned key or the line cannot serve warm — the caller
        falls back to the full ceremony (build/bridge/park included),
        never builds here.  Raises KeyIsLocked exactly as ``get``
        does: the fast path must see blocking locks."""
        region = getattr(snap, "region", None)
        data_index = getattr(snap, "data_index", None)
        if region is None or data_index is None or \
                (region.id, region.epoch.version) != base_key[:2]:
            return None
        self._note_heat(region.id)
        with self._lock:
            line = self._lines.get(base_key)
            got = self._lookup_locked(line, data_index, start_ts)
            if got is None:
                return None
            ent, lock_src = got
            self._lines.move_to_end(base_key)
            self.hits += 1
            self._count("hit")
        lock_src.check_locks(ranges, start_ts)
        return ent

    def is_current(self, base_key: tuple, snap) -> bool:
        """Non-building peek: is ``snap`` still the line's NEWEST
        generation?  The fast path pre-validates its learned storage
        with this before charging a request to the fast leg; any
        generation bump (delta patch, rebuild, epoch sweep) answers
        False and the class re-learns through the slow path."""
        with self._lock:
            line = self._lines.get(base_key)
            return line is not None and line.snap is snap

    def _lookup_locked(self, line, data_index: int, start_ts: int):
        """→ (entry, lock_source) or None.  ``lock_source`` carries the
        blocking-lock set to check the request against — the line's
        NEWEST set when serving a superseded snapshot from history (its
        own recorded locks are stale; the newest set is conservative:
        any lock released since was resolved either above the read's ts
        or via a data delta that already retired the old snapshot)."""
        if line is None:
            return None
        if line.data_index == data_index and line.snap.serves(start_ts):
            # (``serves``: the newest view is bounded too while deltas
            # are held back for a later reader, ``_bridge``)
            return line.snap, line.snap
        # write churn: a read whose ts predates every data commit since
        # an older generation serves that generation — same visible set,
        # no rebuild (the data_index stamp only pins WHEN the snapshot
        # was taken; visibility is pure ts resolution).  Only sound once
        # the line has applied AT LEAST up to the requested version:
        # ``superseded_at`` bounds cover applied batches only, so an
        # unapplied gap could hide a commit at or below the read's ts.
        if line.data_index is not None and line.data_index >= data_index:
            for old in line.history:
                if old.serves(start_ts):
                    return old, (line.snap if line.snap is not None
                                 else old)
        parked = line.parked.get((data_index, start_ts))
        if parked is not None:
            line.parked.move_to_end((data_index, start_ts))
            return parked, parked
        return None

    def _count(self, result: str) -> None:
        from ..utils import tracker
        from ..utils.metrics import COPR_CACHE_COUNTER
        COPR_CACHE_COUNTER.labels(result).inc()
        tracker.label("copr_cache",
                      {"hit": "hit", "delta": "delta"}.get(result,
                                                           "build"))

    # -- build / bridge -------------------------------------------------

    def _materialize(self, snap, dag, base_key, line, data_index: int,
                     start_ts: int, gen0: int = 0):
        from ..utils import tracker
        scan = dag.executors[0]
        bridged = None
        # classify before bridging: a FAILED bridge retires line.state,
        # and that fallback must still count as a rebuild, not a miss
        had_state = line is not None and line.state is not None
        if had_state and line.data_index is not None and \
                (line.data_index < data_index or
                 (line.data_index == data_index and line.pending)) and \
                self._delta_source is not None:
            with tracker.phase("delta_apply"):
                bridged = self._bridge(line, snap, base_key[0],
                                       data_index, start_ts)
        if bridged is not None:
            with self._lock:
                if base_key in self._lines:     # may have been evicted
                    self._lines.move_to_end(base_key)
                self.deltas += 1
            self._count("delta")
            self._export_gauges(base_key[0], line)
            if bridged.serves(start_ts):
                return bridged, bridged
            # the delta landed but this request reads below the new
            # safe_ts — the generation it raced past may still serve it
            # from the line's history (same visible set below the first
            # superseding commit); locks check against the NEWEST set
            with self._lock:
                got = self._lookup_locked(line, data_index, start_ts)
            if got is not None:
                return got
            # else: park an exact-ts build (rare: stale reader racing
            # a fresh commit it must not see, over a gap that also
            # contains commits it must see)
        self.misses += 1
        tracker.label("copr_cache", "build")
        # storm control: take a re-mint permit BEFORE the build.  The
        # governor parks us in its priority queue (hot regions first,
        # RU-debt tenants last) and may shed the wait with a
        # ServerIsBusy(retry_after_ms) instead — a mass invalidation
        # degrades gracefully rather than stampeding the host link.
        # Waiters on our _building event stay parked either way, so a
        # shed surfaces to exactly one request per (line, version).
        gate = self.remint_gate
        ticket = None
        if gate is not None:
            with tracker.phase("remint_wait"):
                ticket = gate.acquire(base_key[0],
                                      heat=self.region_heat(base_key[0]))
        try:
            with tracker.phase("columnar_build"):
                tracker.annotate(schema_cols=len(scan.columns))
                tbl, safe_ts, locks, bundle = build_region_columnar_ex(
                    snap, scan.table_id, scan.columns, start_ts,
                    device_resolver=self.device_resolver,
                    stream_source=self.stream_source)
        finally:
            if ticket is not None:
                gate.release(ticket)
        if bundle is not None:
            self.device_builds += 1
        ent = MvccColumnarSnapshot(tbl, start_ts, safe_ts, locks)
        lock_src = ent
        retired: list = []
        with self._lock:
            if base_key[1] < self._epoch_floor.get(base_key[0], 0) or \
                    self._sweep_gen.get(base_key[0], 0) != gen0:
                # lifecycle teardown swept this region (epoch
                # superseded, or a same-epoch sweep — leader loss /
                # snapshot apply / destroy — landed mid-build): the
                # answer is exact for THIS request, but the line must
                # not be cached — a resurrected stale line would
                # linger unreachable until LRU pressure
                if bundle is not None:
                    bundle.release()
                self._count("miss")
                return ent, lock_src
            prev = self._lines.get(base_key)
            fresh_wins = prev is None or prev.data_index is None or \
                prev.data_index <= data_index
            if start_ts < safe_ts or not fresh_wins:
                # below-safe_ts builds see an OLD version set; builds
                # raced past by a newer line serve once — both park
                # under their exact (version, ts) so they never shadow
                # the latest entry.  These are ts-scoped misses, NOT
                # line rebuilds: the delta-maintained line stays.
                result = "miss"
                if prev is None:
                    prev = _Line(base_key, None, None, None)
                    self._lines[base_key] = prev
                prev.parked[(data_index, start_ts)] = ent
                while len(prev.parked) > 4:
                    prev.parked.popitem(last=False)
            else:
                # a maintained line existed but could not be bridged —
                # THIS is the rebuild fallback the delta path exists to
                # avoid (log overflow / envelope / bridge failure)
                result = "rebuild" if had_state else "miss"
                if result == "rebuild":
                    self.rebuilds += 1
                state = _LineState(scan.table_id, scan.columns, tbl,
                                   safe_ts, start_ts, locks)
                state.lineage.region_hint = base_key[0]
                if bundle is not None:
                    # the runner's first feed miss for this line mints
                    # the born-resident feed from the resolve artifacts
                    state.lineage.stash_cold(bundle)
                    bundle = None
                ent = lock_src = state.publish()
                new_line = _Line(base_key, data_index, ent, state)
                if prev is not None:
                    new_line.parked = prev.parked
                    # the replaced line's lineage (and its device feed)
                    # is dead — tear it down now, not at GC time
                    retired.append(prev)
                self._lines[base_key] = new_line
            self._lines.move_to_end(base_key)
            retired += self._evict_locked(base_key[0])
            self._publish_lines()
        if bundle is not None:      # parked / uncached build
            bundle.release()
        for line in retired:
            self._retire(line)
        self._count(result)
        self._export_gauges(base_key[0], self._lines.get(base_key))
        return ent, lock_src

    def _evict_locked(self, region_id: int) -> list:
        """Hold both bounds after an insert into ``region_id``; → the
        lines that left, for the caller to retire outside the lock.
        ``_lines`` is in the lines' LRU order, so a region's place is
        its last line's: walking it only here, where a line was just
        built, keeps no second structure to fall out of step."""
        out = []
        mine = [k for k in self._lines if k[0] == region_id]
        for key in mine[:max(0, len(mine) - SCHEMAS_PER_REGION)]:
            out.append(self._lines.pop(key))
            self.evictions["schema_bound"] += 1
        recent_first = list(dict.fromkeys(
            key[0] for key in reversed(self._lines)))
        for rid in recent_first[max(0, self._capacity):]:
            for key in [k for k in self._lines if k[0] == rid]:
                out.append(self._lines.pop(key))
                self.evictions["region_lru"] += 1
        return out

    def _export_gauges(self, region_id: int, line) -> None:
        from ..utils.metrics import COPR_TOMBSTONE_RATIO
        if line is not None and line.state is not None:
            COPR_TOMBSTONE_RATIO.labels(str(region_id)).set(
                line.state.tombstone_ratio())

    # -- the delta patch ------------------------------------------------

    def _bridge(self, line, snap, region_id: int, data_index: int,
                start_ts: Optional[int] = None):
        """Bridge ``line`` forward to ``data_index``; returns the new
        published snapshot, or None → caller falls back to rebuild.

        ``start_ts``: the reader this bridge serves.  Deltas committed
        ABOVE it are fetched and HELD BACK (``line.pending``), not
        applied: sessions that write and read in turn race each other,
        and a reader whose TSO lies between two commits of one batch
        needs the first and must not see the second.  The view it gets
        is bounded by the earliest held commit (``superseded_at``); the
        next reader past that bound applies what is held, oldest first.
        Without it such a reader falls to an exact-ts build of the whole
        line.  None: apply everything (a split's bridge).

        The delta fetch happens INSIDE ``line.mu``: two threads bridging
        the same line toward different target versions must each replay
        exactly the gap from the line's then-current version, or a delta
        batch would apply twice."""
        with line.mu:
            cur = line.data_index
            if cur is None or cur > data_index:
                return None
            rows, locks = [], []
            if cur < data_index:
                deltas = self._delta_source.deltas_between(
                    region_id, cur, data_index)
                if deltas is None:
                    return None
                rows, locks = deltas
            elif not line.pending:
                return line.snap
            # (a key's deltas keep their apply order, which is their
            # commit order: held ones first)
            rows = line.pending + list(rows)
            if len(rows) > self._max_delta_rows:
                return None
            hold = [] if start_ts is None else \
                [d for d in rows if d.commit_ts > start_ts]
            if hold:
                rows = [d for d in rows if d.commit_ts <= start_ts]
                self.deltas_held += len(hold)
            # one generation a TRANSACTION, in commit order (a stable
            # sort: a key's own order stands): a reader that arrives
            # later with an earlier TSO finds the view it needs in the
            # line's history instead of building one
            def ts(d):
                return d.commit_ts
            steps = [list(g) for _ts, g in
                     itertools.groupby(sorted(rows, key=ts), key=ts)]
            published = None
            for i, step in enumerate(steps or [[]]):
                # (a view between two steps is bounded by the steps to
                # come as by what is held: a reader that looks the line
                # up meanwhile must not take it for the newest)
                rest = [d for later in steps[i + 1:] for d in later]
                published = self._step(line, snap, step,
                                       () if rest else locks,
                                       rest + hold, data_index)
                if published is None:
                    return None
            return published

    def _step(self, line, snap, rows, locks, hold, data_index: int):
        """Apply one transaction's deltas to ``line`` (under its ``mu``)
        and install the view; → it, or None (the line is retired)."""
        try:
            published = self._apply_deltas(line.state, snap, rows, locks)
        except Exception:   # noqa: BLE001 — any surprise: rebuild
            import logging
            logging.getLogger(__name__).warning(
                "columnar delta apply failed; falling back to "
                "rebuild", exc_info=True)
            published = None
        if published is None:
            # the state may be part-mutated: retire it so no later
            # bridge replays onto it (the rebuild replaces the
            # line), and drop its device feed with it
            self._retire(line)
            line.state = None
            return None
        published, min_data_ts = published
        if hold:
            # the new view lacks what is held; every older one lacks
            # it too, beside what was just applied
            published.superseded_at = min(d.commit_ts for d in hold)
            min_data_ts = published.superseded_at \
                if min_data_ts is None else \
                min(min_data_ts, published.superseded_at)
        prev = line.snap
        with self._lock:
            line.pending = list(hold)
            if prev is not None:
                # the outgoing generation keeps serving reads below
                # the first commit that superseded it (churn path);
                # commit_ts order is not apply order across keys, so
                # EVERY older generation's bound tightens too
                if min_data_ts is not None:
                    for h in (prev,) + tuple(line.history):
                        h.superseded_at = min_data_ts if \
                            h.superseded_at is None else \
                            min(h.superseded_at, min_data_ts)
                line.history.appendleft(prev)
            line.data_index = data_index
            line.snap = published
            line.parked.clear()
        return published

    def _apply_deltas(self, state: _LineState, snap, rows, locks):
        """→ (published snapshot, min data commit_ts of the batch) or
        None when a payload is unavailable (caller rebuilds)."""
        lo_key, hi_key = table_record_range(state.table_id)
        # 1. fold row deltas: safe_ts watermark + last-wins visible op
        pending: "OrderedDict[bytes, object]" = OrderedDict()
        min_data_ts = None
        for d in rows:
            if not (lo_key <= d.user_key < hi_key):
                continue        # index keys / other tables in the region
            if d.commit_ts > state.safe_ts:
                state.safe_ts = d.commit_ts
            if d.kind == "advance":
                continue
            if min_data_ts is None or d.commit_ts < min_data_ts:
                min_data_ts = d.commit_ts
            pending[d.user_key] = d
        state.build_ts = max(state.build_ts, state.safe_ts)

        # 2. resolve payloads + classify against the current rows
        updates: list = []      # (pos, payload)
        deletes: list = []      # pos
        inserts: list = []      # (handle, payload)
        revives: list = []      # (pos, payload) — tombstoned slot reused
        for user_key, d in pending.items():
            handle = decode_record_handle(user_key)
            pos, present = state._pos_of(handle)
            dead = present and state.alive is not None and \
                not state.alive[pos]
            if d.kind == "delete":
                if present and not dead:
                    deletes.append(pos)
                continue
            payload = self._resolve_payload(snap, d)
            if payload is not None:
                payload = state.scaled_payload(payload)
            if payload is None:
                # spilled value unavailable, or a DECIMAL beyond the
                # scale its column is held at: rebuild
                return None
            if present:
                (revives if dead else updates).append((pos, payload))
            else:
                inserts.append((handle, payload))

        n0 = state.n
        patch_spans: list = []
        structural = False
        # what the journal entry says the batch did (step 6): the rows
        # it wrote, and the view positions of the rows it tombstoned
        # (None once the view is renumbered otherwise)
        written = [(int(state.handles[pos]), payload)
                   for pos, payload in updates + revives] + inserts
        dead = None if revives else tuple(
            pos if state.alive is None else
            int(np.count_nonzero(state.alive[:pos]))
            for pos in sorted(deletes))

        # 3. inserts: slack append when strictly increasing past the
        #    current max handle; a merge into the line's TAIL where they
        #    land among its last rows and nothing else changed (sessions
        #    take their rowids in one order and commit in another: the
        #    rows that move are a span the device feed can patch); else
        #    a one-pass repack (mid-insert)
        append_only = all(
            h > int(state.handles[n0 - 1]) for h, _ in inserts) \
            if n0 else True
        merge_from = None
        if inserts and not append_only and state.alive is None and \
                not (updates or revives or deletes) and \
                n0 + len(inserts) <= state.cap:
            merge_from = int(np.searchsorted(
                state.handles[:n0], min(h for h, _ in inserts)))
            if n0 - merge_from > self.TAIL_MERGE_ROWS:
                merge_from = None
        if merge_from is not None:
            state._merge_tail(merge_from, inserts)
            self.tail_merges += 1
            patch_spans.append((merge_from, state.n))
        elif inserts and (not append_only or
                          n0 + len(inserts) > state.cap):
            # repack folds deletes/tombstones too; positional updates
            # must land first so the gather copies patched values
            if updates or revives:
                state._cow_columns()
                for pos, payload in updates + revives:
                    state._set_row(pos, payload)
                if revives:
                    state._cow_alive()
                    for pos, _ in revives:
                        state.alive[pos] = True
                        state.n_dead -= 1
            if deletes:
                state._cow_alive()
                for pos in deletes:
                    state.alive[pos] = False
                state.n_dead += len(deletes)
            state._repack(inserts)
            self.compactions += 1
            structural = True
            dead = None
        else:
            if updates or revives:
                state._cow_columns()
                for pos, payload in updates + revives:
                    state._set_row(pos, payload)
                patch_spans.extend(_merge_spans(sorted(
                    {p for p, _ in updates})))
            if revives:
                state._cow_alive()
                for pos, _ in revives:
                    state.alive[pos] = True
                state.n_dead -= len(revives)
                structural = True
            if deletes:
                state._cow_alive()
                for pos in deletes:
                    state.alive[pos] = False
                state.n_dead += len(deletes)
                structural = True
            if inserts:
                ins = sorted(inserts, key=lambda kv: kv[0])
                k = len(ins)
                state.handles[n0:n0 + k] = [h for h, _ in ins]
                if state.alive is not None:
                    state.alive[n0:n0 + k] = True
                for i, (_h, payload) in enumerate(ins):
                    state._set_row(n0 + i, payload)
                state.n += k
                patch_spans.append((n0, state.n))
            # 4. compaction: tombstone ratio crossed the threshold
            if state.alive is not None and \
                    state.tombstone_ratio() > self._compact_ratio:
                state._repack([])
                self.compactions += 1
                structural = True
                dead = None

        if state.alive is not None and state.n_dead == 0:
            # every tombstone was revived: drop the mask so scans are
            # zero-copy again (the published COW mask stays with its
            # older snapshots)
            state.alive = None

        # 5. blocking-lock refresh (range-scoped, like the build's scan)
        for ld in locks:
            if not (lo_key <= ld.user_key < hi_key):
                continue
            if ld.lock is None:
                state.locks.pop(ld.user_key, None)
            else:
                state.locks[ld.user_key] = ld.lock

        # 6. journal the patch for the device feed.  A batch that
        #    changed no row (another session's prewrite: locks alone; a
        #    rollback) journals nothing: the view it publishes reflects
        #    the same generation, so the feed, the request memos and the
        #    prepared launches all still stand
        if not (updates or revives or deletes or inserts or structural):
            return state.publish(), min_data_ts
        entry = {"n": state.n, "live": state.n - state.n_dead}
        if dead is not None:
            entry["dead"] = dead
        if structural or state.alive is not None:
            entry.update(structural=True, introduced=[
                state.rows_of(written)] if written else [])
        else:
            spans = []
            for lo, hi in patch_spans:
                spans.append({
                    "lo": lo, "hi": hi,
                    "handles": state.handles[lo:hi].copy(),
                    "cols": {cid: (bufs[0][lo:hi].copy(),
                                   bufs[1][lo:hi].copy())
                             for cid, bufs in state.cols.items()},
                })
            # (the spans ARE the rows written, a few rows of the line
            # between two updates beside them)
            entry.update(structural=False, spans=spans, introduced=spans)
        state.lineage.record(entry)
        return state.publish(), min_data_ts

    @staticmethod
    def _resolve_payload(snap, d) -> Optional[dict]:
        if d.short_value is not None:
            return decode_row(d.short_value) if d.short_value else {}
        v = snap.get_value_cf(CF_DEFAULT, append_ts(d.enc_key,
                                                    d.start_ts))
        if v is None:
            return None
        return decode_row(v)


class _Line:
    __slots__ = ("key", "data_index", "snap", "state", "parked",
                 "history", "mu", "pending")

    def __init__(self, key, data_index, snap, state):
        self.key = key
        self.data_index = data_index
        self.snap = snap
        self.state = state
        self.parked: "OrderedDict" = OrderedDict()
        # row deltas fetched up to ``data_index`` and held back: committed
        # above the TSO of the reader that fetched them (``_bridge``)
        self.pending: list = []
        # recently superseded generations, newest first: each serves
        # reads below its ``superseded_at`` without a rebuild
        from collections import deque
        self.history: "deque" = deque(maxlen=6)
        self.mu = threading.Lock()
