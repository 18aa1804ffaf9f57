"""Durable on-disk engine: WAL + in-memory working set + checkpoints.

Reference roles: components/engine_rocks/src/engine.rs (RocksEngine — the
persistent KvEngine behind the trait seam, engine_traits/src/engine.rs:13)
and the raft-log durability contract of engine_traits/src/raft_engine.rs:84.
The design is RocksDB's memtable+WAL shape with a two-level tier of
on-disk artifacts (mini-LSM):

- every committed WriteBatch appends one CRC-framed record to the WAL
  before mutating the in-memory state — crash recovery replays the WAL
  over the persisted levels and stops at the first torn/corrupt record;
- when the WAL exceeds ``checkpoint_bytes`` the engine FLUSHES ONLY THE
  DELTA since the last flush as a sorted run ``sst-<gen>`` (per-key
  final ops + range tombstones — the L0 sorted-run role), fsyncs,
  renames atomically, then starts ``wal-<gen>`` and drops the old WAL;
- when more than ``max_runs`` runs accumulate, a COMPACTION folds base +
  runs into one full-state base ``ckpt-<gen>`` (the memtable holds the
  merged view, so the dump is the merge — RocksDB's tiered L0→L1 shape
  with the same write-amplification profile: deltas per flush, full
  rewrite once per ``max_runs`` flushes);
- recovery = newest base → runs in generation order → WAL tail;
- reads (point/iterator/snapshot) are identical to MemoryEngine — the
  working set lives in sorted copy-on-write arrays, so the hot read path
  (MVCC scans feeding the columnar/TPU pipeline) never touches disk
  (the working set is memtable-resident by design; levels bound WRITE
  amplification and recovery cost, not read memory).

Durability level: ``sync=False`` (default) flushes to the OS page cache
on every write — state survives process kill (SIGKILL) but not machine
power loss; ``sync=True`` fsyncs every batch like raftstore's sync-log.
"""

from __future__ import annotations

import os
import struct
import zlib
from typing import Optional

from .memory import MemoryEngine, MemoryWriteBatch
from .traits import ALL_CFS

_CKPT_MAGIC = b"TKV1CKPT"
_CKPT_FOOTER = b"CKPTDONE"
_RUN_MAGIC = b"TKV1RUN1"
_RUN_FOOTER = b"RUN1DONE"
_OP_PUT, _OP_DEL, _OP_DELR, _OP_INGEST = 0, 1, 2, 3


class CorruptionError(RuntimeError):
    """An on-disk artifact that should be intact is not (fsynced
    checkpoint failed validation).  Recovery must not proceed silently."""


def _pack_op(op: tuple, cf_index: dict) -> bytes:
    kind = op[0]
    if kind == "put":
        _, cf, k, v = op
        return struct.pack(">BBI", _OP_PUT, cf_index[cf], len(k)) + k + \
            struct.pack(">I", len(v)) + v
    if kind == "del":
        _, cf, k = op
        return struct.pack(">BBI", _OP_DEL, cf_index[cf], len(k)) + k
    if kind == "ingest":
        # one framed record for a whole sorted run: msgpack of the
        # key/value lists round-trips at C speed, keeping bulk loads
        # off the per-key codec (sst_importer ingest durability)
        import msgpack as _mp
        _, cf, keys, vals = op
        blob = _mp.packb([keys, vals], use_bin_type=True)
        return struct.pack(">BBI", _OP_INGEST, cf_index[cf],
                           len(blob)) + blob
    _, cf, s, e = op
    return struct.pack(">BBI", _OP_DELR, cf_index[cf], len(s)) + s + \
        struct.pack(">I", len(e)) + e


def _unpack_ops(payload: bytes, cfs: tuple) -> list[tuple]:
    ops = []
    off = 0
    n = len(payload)
    while off < n:
        kind, cfi, klen = struct.unpack_from(">BBI", payload, off)
        off += 6
        k = payload[off:off + klen]
        off += klen
        cf = cfs[cfi]
        if kind == _OP_PUT:
            (vlen,) = struct.unpack_from(">I", payload, off)
            off += 4
            v = payload[off:off + vlen]
            off += vlen
            ops.append(("put", cf, k, v))
        elif kind == _OP_DEL:
            ops.append(("del", cf, k))
        elif kind == _OP_INGEST:
            import msgpack as _mp
            keys, vals = _mp.unpackb(k, raw=False)
            ops.append(("ingest", cf, keys, vals))
        else:
            (elen,) = struct.unpack_from(">I", payload, off)
            off += 4
            e = payload[off:off + elen]
            off += elen
            ops.append(("delr", cf, k, e))
    return ops


class DiskEngine(MemoryEngine):
    """KvEngine with WAL + checkpoint durability (see module docstring)."""

    def __init__(self, path: str, cfs=ALL_CFS, sync: bool = False,
                 checkpoint_bytes: int = 16 << 20, max_runs: int = 4,
                 encryption=None, compaction_filter=None):
        super().__init__(cfs)
        self.path = path
        self._cf_names = tuple(cfs)
        self._cf_index = {cf: i for i, cf in enumerate(self._cf_names)}
        self._sync = sync
        # encryption-at-rest (tikv_tpu/encryption.py DataKeyManager):
        # every artifact (WAL/ckpt/run) is AES-CTR'd under its own
        # per-file data key; None = plaintext
        self._enc = encryption
        # GC-in-compaction hook (gc_worker/compaction_filter.rs):
        # filter_cf(cf, keys, vals) -> (keys, vals) applied while the
        # compaction dumps the new base; CF_ORDER fixes cross-CF
        # decision order (write before default)
        self._compaction_filter = compaction_filter
        self._checkpoint_bytes = checkpoint_bytes
        self._max_runs = max_runs
        os.makedirs(path, exist_ok=True)
        self._gen = 0
        self._wal = None
        self._wal_bytes = 0
        # delta since the last flush: cf -> {key: ("put", v)|("del",)}
        # plus range tombstones in arrival order
        self._dirty: dict = {cf: {} for cf in self._cf_names}
        self._dirty_ranges: dict = {cf: [] for cf in self._cf_names}
        self._runs: list[int] = []      # live sst-run generations
        with self._mu:
            self._recover()

    # ------------------------------------------------------------ recovery

    def _ckpt_path(self, gen: int) -> str:
        return os.path.join(self.path, f"ckpt-{gen:012d}")

    def _run_path(self, gen: int) -> str:
        return os.path.join(self.path, f"sst-{gen:012d}")

    def _wal_path(self, gen: int) -> str:
        return os.path.join(self.path, f"wal-{gen:012d}")

    def _recover(self) -> None:
        from ..utils.failpoint import fail_point
        fail_point("recover::before_scan")
        base_gens, run_gens = [], []
        for name in os.listdir(self.path):
            if name.endswith(".tmp"):
                continue
            if name.startswith("ckpt-"):
                try:
                    base_gens.append(int(name[5:]))
                except ValueError:
                    continue
            elif name.startswith("sst-"):
                try:
                    run_gens.append(int(name[4:]))
                except ValueError:
                    continue
        base = max(base_gens) if base_gens else 0
        if base_gens:
            # A non-.tmp artifact is only ever produced by an atomic
            # rename after fsync, so a newest-generation file that fails
            # validation is real corruption.  Falling back to an older
            # generation would silently drop every write since it — that
            # generation's WAL was deleted when it was cut (ADVICE r2).
            if not self._load_checkpoint(self._ckpt_path(base)):
                raise CorruptionError(
                    f"newest checkpoint {self._ckpt_path(base)} is "
                    "corrupt; refusing to silently recover from an "
                    "older generation")
            self._gen = base
        # delta runs above the base, in generation order
        self._runs = sorted(g for g in run_gens if g > base)
        for g in self._runs:
            if not self._apply_run(self._run_path(g)):
                raise CorruptionError(
                    f"sorted run {self._run_path(g)} is corrupt; its "
                    "WAL was already dropped — cannot skip it")
            self._gen = g
        fail_point("recover::before_wal_replay")
        torn_enc = self._replay_wal(self._wal_path(self._gen))
        self._open_wal(self._wal_path(self._gen), append=True)
        if torn_enc:
            # encrypted WAL with a torn tail: appending in place would
            # reuse CTR keystream bytes at [good, old_size) that already
            # encrypted the discarded tail (two-time pad vs a
            # pre-truncation disk image), and re-encrypting the prefix
            # under a fresh key has a crash window where old ciphertext
            # meets the new key (silent total WAL loss).  Instead roll
            # the surviving records — already replayed into the dirty
            # delta — forward through a normal flush: the run write is
            # atomic under a NEW file name, the WAL rotates to a fresh
            # generation/key, and the torn segment dies with its old key
            # intact until both renames land.
            self._flush_locked()
        # sweep files a crash mid-flush/compaction may have left behind
        keep_runs = set(self._runs)
        for name in os.listdir(self.path):
            full = os.path.join(self.path, name)
            stale = name.endswith(".tmp")
            if name.startswith("ckpt-") and not stale:
                try:
                    stale = int(name[5:]) < base
                except ValueError:
                    pass
            elif name.startswith("sst-") and not stale:
                try:
                    stale = int(name[4:]) not in keep_runs
                except ValueError:
                    pass
            elif name.startswith("wal-") and not stale:
                try:
                    stale = int(name[4:]) < self._gen
                except ValueError:
                    pass
            if stale:
                self._rm(full)

    def _read_file(self, path: str):
        """Whole-file read with decryption (ckpt/run artifacts).
        An on-disk file UNKNOWN to the key dictionary raises
        MissingFileKey — fabricating a key would decrypt to garbage
        that recovery could mistake for torn data and truncate."""
        with open(path, "rb") as f:
            data = f.read()
        if self._enc is not None:
            data = self._enc.xor(os.path.basename(path), data,
                                 create=False)
        return data

    def _write_file_atomic(self, path: str, data: bytes) -> None:
        """tmp-write + fsync + rename, encrypting under a FRESH
        (key, iv) for the final name: a crash between the tmp write and
        the rename can replay this generation with different content —
        reusing the persisted iv would be a CTR two-time pad."""
        if self._enc is not None:
            from ..encryption import aes_ctr_xor
            key, iv = self._enc.renew_file(os.path.basename(path))
            data = aes_ctr_xor(key, iv, data)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def _apply_run(self, path: str) -> bool:
        """Load one sorted run: range tombstones first, then final
        per-key ops (the flush wrote them in exactly that order)."""
        try:
            data = self._read_file(path)
        except OSError:
            return False
        if not (data.startswith(_RUN_MAGIC) and
                data.endswith(_RUN_FOOTER)):
            return False
        payload = data[len(_RUN_MAGIC):-len(_RUN_FOOTER)]
        batch = MemoryWriteBatch()
        batch._ops = _unpack_ops(payload, self._cf_names)
        self._write_locked(batch)
        return True

    def _load_checkpoint(self, path: str) -> bool:
        try:
            data = self._read_file(path)
        except OSError:
            return False
        if not (data.startswith(_CKPT_MAGIC) and
                data.endswith(_CKPT_FOOTER)):
            return False        # incomplete/corrupt checkpoint: skip
        body = data[len(_CKPT_MAGIC):-len(_CKPT_FOOTER)]
        off = 0
        (n_cfs,) = struct.unpack_from(">B", body, off)
        off += 1
        for _ in range(n_cfs):
            cfi, count = struct.unpack_from(">BQ", body, off)
            off += 9
            cf = self._cf_names[cfi]
            data_cf = self._cfs[cf]
            keys, vals = [], []
            for _ in range(count):
                (klen,) = struct.unpack_from(">I", body, off)
                off += 4
                keys.append(body[off:off + klen])
                off += klen
                (vlen,) = struct.unpack_from(">I", body, off)
                off += 4
                vals.append(body[off:off + vlen])
                off += vlen
            data_cf.set_flat(keys, vals)
        return True

    def _replay_wal(self, path: str) -> bool:
        """Replay committed records; → True when an ENCRYPTED segment
        has a torn tail (caller must rotate, see _recover)."""
        import io
        try:
            if self._enc is not None:
                # CTR-decrypt the whole segment, then parse exactly as
                # plaintext: a torn tail decrypts to garbage and fails
                # the record CRC — same stop-at-tear semantics
                f = io.BytesIO(self._read_file(path))
            else:
                f = open(path, "rb")
        except OSError:
            return False
        with f:
            good = 0
            while True:
                hdr = f.read(8)
                if len(hdr) < 8:
                    break
                length, crc = struct.unpack(">II", hdr)
                payload = f.read(length)
                if len(payload) < length or \
                        (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
                    break       # torn/corrupt tail: recovery stops here
                batch = MemoryWriteBatch()
                batch._ops = _unpack_ops(payload, self._cf_names)
                self._write_locked(batch)
                # replayed records live ONLY in this WAL segment: they
                # must re-enter the dirty delta or the next flush writes
                # a run without them and deletes their WAL — silent,
                # permanent data loss on the following crash
                self._record_dirty(batch._ops)
                good = f.tell()
        # drop the torn tail so later appends don't interleave with it
        if os.path.exists(path) and good < os.path.getsize(path):
            if self._enc is not None:
                # do NOT touch the segment here — the caller rotates it
                # out via a flush (keystream-reuse + crash-window
                # rationale at the _recover call site)
                return True
            with open(path, "r+b") as f:
                f.truncate(good)
        return False

    def _open_wal(self, path: str, append: bool) -> None:
        if self._enc is not None:
            from ..encryption import MissingFileKey
            name = os.path.basename(path)
            exists = os.path.exists(path) and os.path.getsize(path) > 0
            if not append or not exists:
                # truncating write or fresh segment: new CTR stream
                self._enc.renew_file(name)
            elif not self._enc.has_file(name):
                # appending ciphertext into a plaintext-era WAL would
                # corrupt both halves — refuse (plaintext→encrypted
                # migration needs an explicit rewrite)
                raise MissingFileKey(name)
        self._wal = open(path, "ab" if append else "wb")
        if self._enc is not None:
            from ..encryption import EncryptedFile
            self._wal = EncryptedFile(self._wal, self._enc,
                                      os.path.basename(path))
        self._wal_bytes = self._wal.tell()

    # ------------------------------------------------------------ writes

    def write(self, batch: MemoryWriteBatch) -> None:
        from ..utils.failpoint import FailpointPanic, fail_point
        from ..utils.metrics import ENGINE_WRITE_COUNTER
        if batch.is_empty():
            return
        ENGINE_WRITE_COUNTER.inc()
        with self._mu:
            fail_point("wal::before_append")
            payload = b"".join(_pack_op(op, self._cf_index)
                               for op in batch._ops)
            self._wal.write(struct.pack(
                ">II", len(payload), zlib.crc32(payload) & 0xFFFFFFFF))
            # a "torn" action truncates the record mid-payload, modeling
            # power loss between the header and body hitting disk
            torn = fail_point("wal::torn_write")
            if torn is not None:
                self._wal.write(payload[:max(0, len(payload) // 2)])
                self._wal.flush()
                os.fsync(self._wal.fileno())
                raise FailpointPanic("wal::torn_write")
            self._wal.write(payload)
            # a sleep action here models a stalled fsync (slow disk):
            # the write path blocks exactly where the OS would block it
            fail_point("wal::fsync_stall")
            self._wal.flush()
            if self._sync:
                os.fsync(self._wal.fileno())
            fail_point("wal::after_append")
            self._wal_bytes += 8 + len(payload)
            self._write_locked(batch)
            self._record_dirty(batch._ops)
            if self._wal_bytes >= self._checkpoint_bytes:
                self._flush_locked()

    def put_cf(self, cf: str, key: bytes, value: bytes) -> None:
        wb = MemoryWriteBatch()
        wb.put_cf(cf, key, value)
        self.write(wb)

    def delete_cf(self, cf: str, key: bytes) -> None:
        wb = MemoryWriteBatch()
        wb.delete_cf(cf, key)
        self.write(wb)

    # ------------------------------------------------------------ checkpoint

    def flush(self) -> None:
        """Force a delta flush (engine_traits MiscExt flush analog)."""
        with self._mu:
            self._flush_locked()

    def _record_dirty(self, ops) -> None:
        """Track the delta since the last flush (the next run's body)."""
        for op in ops:
            kind = op[0]
            cf = op[1]
            if kind == "put":
                self._dirty[cf][op[2]] = ("put", op[3])
            elif kind == "del":
                self._dirty[cf][op[2]] = ("del",)
            elif kind == "ingest":
                self._dirty[cf].update(
                    zip(op[2], (("put", v) for v in op[3])))
            else:
                s_, e_ = op[2], op[3]
                # the tombstone applies BEFORE this segment's key ops on
                # recovery, so keys already dirty in the range collapse
                # to deletes and later puts still override
                d = self._dirty[cf]
                for k in [k for k in d if s_ <= k < e_]:
                    d[k] = ("del",)
                self._dirty_ranges[cf].append((s_, e_))

    def _flush_locked(self) -> None:
        """Write the dirty delta as a sorted run (L0 flush), rotate the
        WAL, and compact when runs pile up."""
        from ..utils.failpoint import fail_point
        fail_point("ckpt::before_write")
        new_gen = self._gen + 1
        parts = [_RUN_MAGIC]
        for cf in self._cf_names:
            for s_, e_ in self._dirty_ranges[cf]:
                parts.append(_pack_op(("delr", cf, s_, e_),
                                      self._cf_index))
        for cf in self._cf_names:
            for k in sorted(self._dirty[cf]):
                ent = self._dirty[cf][k]
                if ent[0] == "put":
                    parts.append(_pack_op(("put", cf, k, ent[1]),
                                          self._cf_index))
                else:
                    parts.append(_pack_op(("del", cf, k),
                                          self._cf_index))
        parts.append(_RUN_FOOTER)
        self._write_file_atomic(self._run_path(new_gen),
                                b"".join(parts))
        # crash window: the run is durable but the WAL has not rotated —
        # recovery must tolerate replaying the old WAL over the new run
        fail_point("flush::before_rotate")
        self._runs.append(new_gen)
        for cf in self._cf_names:
            self._dirty[cf] = {}
            self._dirty_ranges[cf] = []
        old_wal, old_gen = self._wal, self._gen
        self._gen = new_gen
        self._open_wal(self._wal_path(new_gen), append=False)
        if old_wal is not None:
            old_wal.close()
        self._rm(self._wal_path(old_gen))
        if len(self._runs) > self._max_runs:
            self._compact_locked()

    def _rm(self, path: str) -> None:
        try:
            os.remove(path)
        except OSError:
            return
        if self._enc is not None:
            self._enc.remove_file(os.path.basename(path))

    def _compact_locked(self) -> None:
        """Fold base + runs into one full-state base (tiered L0→L1
        compaction).  The memtable IS the merged view of base+runs at
        this point (the WAL just rotated empty), so the dump is the
        merge — one full rewrite per ``max_runs`` delta flushes."""
        from ..utils.failpoint import fail_point
        fail_point("compact::before_write")
        gen = self._gen
        filt = self._compaction_filter
        if filt is not None:
            # apply the GC filter to the LIVE memtable in the order the
            # filter dictates (write-CF decisions drive default drops);
            # the checkpoint below then persists the filtered state
            order = [cf for cf in getattr(filt, "CF_ORDER", ())
                     if cf in self._cf_names]
            order += [cf for cf in self._cf_names if cf not in order]
            for cf in order:
                live_keys, live_vals = self._cfs[cf].flat()
                keys, vals = filt.filter_cf(cf, live_keys, live_vals)
                if keys is not live_keys:
                    # respect the copy-on-write snapshot contract:
                    # pinned generations are shared with live readers
                    self._writable(cf).set_flat(list(keys), list(vals))
        parts = [_CKPT_MAGIC, struct.pack(">B", len(self._cf_names))]
        for cfi, cf in enumerate(self._cf_names):
            keys, vals = self._cfs[cf].flat()
            parts.append(struct.pack(">BQ", cfi, len(keys)))
            for k, v in zip(keys, vals):
                parts.append(struct.pack(">I", len(k)))
                parts.append(k)
                parts.append(struct.pack(">I", len(v)))
                parts.append(v)
        parts.append(_CKPT_FOOTER)
        self._write_file_atomic(self._ckpt_path(gen), b"".join(parts))
        # crash window: new base durable, superseded runs not yet gone —
        # recovery must prefer the newest base and sweep stale runs
        fail_point("compact::after_write")
        # drop everything the new base covers; ONE dict persist for the
        # whole batch of key removals
        removed = []
        for g in self._runs:
            p = self._run_path(g)
            try:
                os.remove(p)
                removed.append(os.path.basename(p))
            except OSError:
                pass
        self._runs = []
        for name in os.listdir(self.path):
            if name.startswith("ckpt-") and not name.endswith(".tmp"):
                try:
                    if int(name[5:]) < gen:
                        os.remove(os.path.join(self.path, name))
                        removed.append(name)
                except (ValueError, OSError):
                    pass
        if self._enc is not None and removed:
            self._enc.remove_files(removed)

    def close(self) -> None:
        from ..utils.failpoint import fail_point
        fail_point("engine::before_close")
        with self._mu:
            if self._wal is not None:
                self._wal.flush()
                if self._sync:
                    os.fsync(self._wal.fileno())
                self._wal.close()
                self._wal = None
