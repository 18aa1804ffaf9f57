"""Device-side MVCC version resolution — the cold-path kill.

The cold build used to be a host affair: one native pass over the
region's CF_WRITE range resolving Percolator versions AND decoding rows
(``native/fastbuild.cpp mvcc_build_columnar``, ~4s per 10M rows), then a
separate padded feed upload.  Late materialization (Abadi et al., ICDE
2007 — PAPERS.md) applies to the TIME axis too: never materialize on
the host what the device can resolve in place.  Newest-committed-version
selection is a **segmented arg-max over commit_ts** — the exact
vectorized shape the MonetDB/X100-style kernels in ``pallas_hash.py``
already handle — so the split here is:

- **host (C++, GIL released)**: a flat-plane PARSE only
  (``native.mvcc_parse_planes``) — key-ordinal segments, commit_ts /
  start_ts / write-type planes, per-column datum planes, short-value
  spill markers.  No per-key branching, no resolution.
- **device (one dispatch)**: eligibility mask
  (``commit_ts <= read_ts ∧ type ∈ {PUT, DELETE}`` — LOCK/ROLLBACK
  records are skipped exactly as the row reader skips them), segmented
  arg-max over commit_ts, DELETE suppression, then an on-device gather
  of the winning versions straight into the **columnar feed layout**
  (value plane per used column, validity plane only where NULLs exist,
  padded to the runner's bucketed ``n_pad``).  The resolved feed is
  *born resident* — there is no separate ``feed_upload`` phase.

The host keeps a cheap numpy mirror of the same resolution
(:func:`resolve_host` — ``np.maximum.reduceat`` over the segment
offsets) because the columnar cache line itself must hold host-truth
buffers (delta patching, ``gather_rows``, checksum, and the scrub
digest contract all read them); the recorded per-plane digests come
from that host truth, so a divergent device resolve is caught by the
scrubber like any other HBM corruption (device/supervisor.py).

Chunked H2D (the streaming cold pipeline, copr/stream_build.py) rides
:class:`DeviceVersionPlanes`: version planes accumulate on device in
capacity-bucketed buffers via the same jitted ``dynamic_update_slice``
span machinery the delta feed patches use, so chunk *k*'s parse/H2D
overlaps chunk *k+1*'s SST ingest and the final resolve dispatch reads
already-resident planes.

Envelope: numeric columns only (INT/DURATION → int64 planes, REAL →
float64, DATETIME/ENUM/SET and unsigned BIGINT → uint64), NULL-able
defaults only; DECIMAL/JSON/BYTES schemas and non-NULL column defaults
fall back to the native host builder (copr/region_cache.py keeps the
build ladder: device → native → interpreted).  CF_DEFAULT spill rows
(values > SHORT_VALUE_MAX_LEN) resolve on device like any other PUT and
their cells are host-patched after the kernel.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

from ..datatype import Column, EvalType

# plane kind codes (shared with fastbuild.cpp): 0=int64 1=float64 3=uint64
_PLANE_KINDS = {
    EvalType.INT: 0, EvalType.DURATION: 0,
    EvalType.REAL: 1,
    EvalType.DATETIME: 3, EvalType.ENUM: 3, EvalType.SET: 3,
}

_NP_BY_KIND = {0: np.int64, 1: np.float64, 3: np.uint64}

# write-type codes in the wtype plane
WT_PUT, WT_DELETE, WT_LOCK, WT_ROLLBACK = 0, 1, 2, 3


def plane_schema(col_infos: Sequence):
    """→ (col_ids, kinds) for the flat-plane parse, or None when the
    schema leaves the device envelope (BYTES/DECIMAL/JSON payloads or
    non-NULL defaults — the native/interpreted ladder serves those)."""
    ids, kinds = [], []
    for info in col_infos:
        if info.is_pk_handle:
            continue
        ft = info.field_type
        kind = _PLANE_KINDS.get(ft.eval_type)
        if kind is None or info.default_value is not None:
            return None
        if kind == 0 and ft.is_unsigned:
            kind = 3            # unsigned BIGINT: values live above 2^63
        ids.append(info.col_id)
        kinds.append(kind)
    return tuple(ids), tuple(kinds)


class WritePlanes:
    """Flat planes of one CF_WRITE range (or a concatenation of
    streamed chunks): one row per stored VERSION, one segment per user
    key, plus per-column datum planes decoded from short values."""

    __slots__ = ("n_ver", "n_keys", "table_id", "safe_ts", "commit_ts",
                 "start_ts", "wtype", "has_payload", "seg_id", "handles",
                 "seg_start", "cols", "need_default", "col_ids")

    def __init__(self, n_ver: int, n_keys: int, table_id: int,
                 safe_ts: int, commit_ts, start_ts, wtype, has_payload,
                 seg_id, handles, seg_start, cols: dict, need_default,
                 col_ids: tuple):
        self.n_ver = n_ver
        self.n_keys = n_keys
        self.table_id = table_id
        self.safe_ts = safe_ts
        self.commit_ts = commit_ts          # uint64[n_ver]
        self.start_ts = start_ts            # uint64[n_ver]
        self.wtype = wtype                  # uint8[n_ver]
        self.has_payload = has_payload      # uint8[n_ver]
        self.seg_id = seg_id                # int32[n_ver]
        self.handles = handles              # int64[n_keys]
        self.seg_start = seg_start          # int64[n_keys + 1]
        # col_id -> (kind, values ndarray[n_ver], valid bool[n_ver])
        self.cols = cols
        self.need_default = need_default    # [(ver_row, start_ts, ukey)]
        self.col_ids = col_ids

    def nbytes(self) -> int:
        per_ver = 8 + 8 + 1 + 1 + 4 + sum(
            9 for _ in self.cols)           # 8B value + 1B valid per col
        return self.n_ver * per_ver + self.n_keys * 8


def _parse_may_yield() -> bool:
    """Whether the build-path parse should release the GIL: only worth
    it with a spare core — on a single-CPU box yielding just hands the
    core to the node's background tick threads and the parse's wall
    time balloons (measured 3.8s → 18s at 10M versions); the host
    builder this rung replaces holds the GIL for its whole pass too."""
    from ..utils import spare_cores
    return spare_cores() > 1


def parse_write_planes(keys, vals, prefix_skip: int,
                       col_infos: Optional[Sequence],
                       release_gil: Optional[bool] = None) -> \
        Optional[WritePlanes]:
    """Native flat-plane parse of one contiguous CF_WRITE slice, or
    None when the native module is unavailable / the data is outside
    the envelope (index keys, mixed tables, exotic datums).

    ``col_infos=None`` selects DISCOVERY mode (the streaming ingest
    path, which has no query schema yet): every column id seen in a row
    payload mints a plane with its stored kind; :func:`align_planes`
    reconciles the result against a schema at build time.

    ``release_gil``: None = auto (yield only with a spare core); the
    streaming worker passes True — its entire point is letting the
    apply loop make progress while it parses."""
    from ..native import mvcc_parse_planes
    if mvcc_parse_planes is None or not keys:
        return None
    if col_infos is None:
        ids, kinds = (), ()
    else:
        schema = plane_schema(col_infos)
        if schema is None:
            return None
        ids, kinds = schema
    if release_gil is None:
        release_gil = _parse_may_yield()
    try:
        out = mvcc_parse_planes(keys, vals, prefix_skip, ids, kinds,
                                bool(release_gil))
    except ValueError:
        return None
    if out["safe_ts"] >= (1 << 63):
        return None     # commit_ts beyond int64: device compares in i64
    n = out["n_ver"]
    cols = {}
    out_ids = []
    for col_id, kind, payload, valid in out["cols"]:
        out_ids.append(col_id)
        cols[col_id] = (kind,
                        np.frombuffer(payload, _NP_BY_KIND[kind]),
                        np.frombuffer(valid, np.uint8).astype(np.bool_))
    return WritePlanes(
        n, out["n_keys"], out["table_id"], out["safe_ts"],
        np.frombuffer(out["commit_ts"], np.uint64),
        np.frombuffer(out["start_ts"], np.uint64),
        np.frombuffer(out["wtype"], np.uint8),
        np.frombuffer(out["has_payload"], np.uint8),
        np.frombuffer(out["seg_id"], np.int32),
        np.frombuffer(out["handles"], np.int64),
        np.frombuffer(out["seg_start"], np.int64),
        cols, out["need_default"], tuple(out_ids) if col_infos is None
        else ids)


def align_planes(planes: WritePlanes,
                 col_infos: Sequence) -> Optional[WritePlanes]:
    """Reconcile DISCOVERED planes (streamed chunks) with a query
    schema, or None when they cannot serve it.

    Stored int64 planes serve unsigned/time kinds by uint64 bit-view
    (msgpack encodes both through the same 8-byte integer) and REAL
    requests by numeric astype (matching the explicit parse's
    coercion); a column never seen in any payload is all-NULL and
    synthesizes an invalid zero plane.  A float-stored plane can only
    serve a REAL request."""
    schema = plane_schema(col_infos)
    if schema is None:
        return None
    ids, kinds = schema
    cols: dict = {}
    for cid, want in zip(ids, kinds):
        got = planes.cols.get(cid)
        if got is None:
            cols[cid] = (want,
                         np.zeros(planes.n_ver, _NP_BY_KIND[want]),
                         np.zeros(planes.n_ver, np.bool_))
            continue
        kind, vals, valid = got
        if kind == want:
            cols[cid] = got
        elif kind == 0 and want == 3:
            cols[cid] = (3, vals.view(np.uint64), valid)
        elif kind == 0 and want == 1:
            cols[cid] = (1, vals.astype(np.float64), valid)
        else:
            return None
    return WritePlanes(
        planes.n_ver, planes.n_keys, planes.table_id, planes.safe_ts,
        planes.commit_ts, planes.start_ts, planes.wtype,
        planes.has_payload, planes.seg_id, planes.handles,
        planes.seg_start, cols, planes.need_default, ids)


def concat_planes(chunks: Sequence[WritePlanes]) -> WritePlanes:
    """Streamed per-chunk planes → one WritePlanes.  Chunks must hold
    strictly ascending, non-overlapping user keys (the streamer's
    coverage contract), so segment ids offset by the running key count
    and version rows offset by the running version count.  Discovered
    column sets may differ per chunk (a column can first appear
    mid-stream); a chunk without a column contributes an invalid zero
    slice — exactly what its payloads said."""
    if len(chunks) == 1:
        return chunks[0]
    n_ver = sum(c.n_ver for c in chunks)
    n_keys = sum(c.n_keys for c in chunks)
    first = chunks[0]
    seg_id = np.empty(n_ver, np.int32)
    seg_start = np.empty(n_keys + 1, np.int64)
    need = []
    vb = kb = 0
    for c in chunks:
        seg_id[vb:vb + c.n_ver] = c.seg_id + kb
        seg_start[kb:kb + c.n_keys] = c.seg_start[:-1] + vb
        need.extend((row + vb, sts, uk) for row, sts, uk in
                    c.need_default)
        vb += c.n_ver
        kb += c.n_keys
    seg_start[n_keys] = n_ver
    all_ids, kinds = [], {}
    for c in chunks:
        for cid in c.col_ids:
            if cid not in kinds:
                all_ids.append(cid)
                kinds[cid] = c.cols[cid][0]
            elif kinds[cid] != c.cols[cid][0]:
                # int-stored then float-stored (or vice versa): promote
                # to float64 like the explicit parse's coercion would
                kinds[cid] = 1
    cols = {}
    for cid in all_ids:
        kind = kinds[cid]
        dt = _NP_BY_KIND[kind]
        vparts, mparts = [], []
        for c in chunks:
            got = c.cols.get(cid)
            if got is None:
                vparts.append(np.zeros(c.n_ver, dt))
                mparts.append(np.zeros(c.n_ver, np.bool_))
            else:
                vparts.append(got[1].astype(dt, copy=False))
                mparts.append(got[2])
        cols[cid] = (kind, np.concatenate(vparts),
                     np.concatenate(mparts))
    return WritePlanes(
        n_ver, n_keys, first.table_id,
        max(c.safe_ts for c in chunks),
        np.concatenate([c.commit_ts for c in chunks]),
        np.concatenate([c.start_ts for c in chunks]),
        np.concatenate([c.wtype for c in chunks]),
        np.concatenate([c.has_payload for c in chunks]),
        seg_id,
        np.concatenate([c.handles for c in chunks]),
        seg_start, cols, need, tuple(all_ids))


def resolve_host(planes: WritePlanes, read_ts: int) -> np.ndarray:
    """Numpy mirror of the device resolution: ascending version rows of
    the newest committed PUT ≤ read_ts per key (the host-truth side of
    the digest contract; also how the builder learns n before picking
    the padded output shape)."""
    if planes.n_ver == 0:
        return np.empty(0, np.int64)
    elig = (planes.commit_ts <= np.uint64(read_ts)) & \
        (planes.wtype <= WT_DELETE)
    score = np.where(elig, planes.commit_ts, np.uint64(0))
    seg_max = np.maximum.reduceat(score, planes.seg_start[:-1])
    win = elig & (score == seg_max[planes.seg_id]) & (score > 0)
    vis = win & (planes.wtype == WT_PUT)
    return np.nonzero(vis)[0]


def host_mirror(planes: WritePlanes, winners: np.ndarray,
                col_infos: Sequence):
    """Materialize the host-truth columnar arrays for the resolved rows
    (vectorized takes — the cache line, delta patching, gather_rows and
    the scrub digests all read these buffers)."""
    seg = planes.seg_id[winners]
    handles = np.ascontiguousarray(planes.handles[seg])
    columns: dict = {}
    for info in col_infos:
        if info.is_pk_handle:
            continue
        _kind, vals, valid = planes.cols[info.col_id]
        columns[info.col_id] = Column(
            info.field_type.eval_type,
            np.ascontiguousarray(vals[winners]),
            np.ascontiguousarray(valid[winners]))
    return handles, columns


def _bucket(n: int, floor: int = 256) -> int:
    """Geometric capacity bucket (k·2^s, 8 ≤ k ≤ 15 — feed.py's ``pad_rows``
    grid) so version-plane shapes, like feed shapes, mint a bounded
    number of compile classes under growth."""
    n = max(floor, n)
    if n <= 8:
        return 8
    s = max(0, n.bit_length() - 4)
    k = -(-n // (1 << s))
    if k > 15:
        s += 1
        k = -(-n // (1 << s))
    return k << s


class DeviceVersionPlanes:
    """Device-resident, capacity-bucketed version planes for one
    streamed (region, table): chunks append in place via the jitted
    ``dynamic_update_slice`` machinery, so H2D rides the load instead
    of the first query.  Zero-fill is semantically dead: padded rows
    carry commit_ts 0, which the eligibility mask (``score > 0``)
    never selects."""

    __slots__ = ("n_ver", "n_keys", "cap_ver", "cap_keys", "bufs",
                 "nbytes")

    def __init__(self):
        self.n_ver = 0
        self.n_keys = 0
        self.cap_ver = 0
        self.cap_keys = 0
        self.bufs: dict = {}        # name -> device array
        self.nbytes = 0

    def _plane_specs(self, planes: WritePlanes):
        specs = [("commit_ts", planes.commit_ts.view(np.int64), True),
                 ("wtype", planes.wtype, True),
                 ("seg_id", planes.seg_id, True),
                 ("handles", planes.handles, False)]
        for cid in planes.col_ids:
            _k, vals, valid = planes.cols[cid]
            specs.append((f"v{cid}", vals, True))
            specs.append((f"m{cid}", valid, True))
        return specs

    def append(self, resolver: "DeviceMvccResolver",
               planes: WritePlanes, key_base: int) -> None:
        import jax.numpy as jnp
        new_ver = self.n_ver + planes.n_ver
        new_keys = self.n_keys + planes.n_keys
        cap_v = _bucket(new_ver)
        cap_k = _bucket(new_keys)
        specs = self._plane_specs(planes)
        if cap_v > self.cap_ver or cap_k > self.cap_keys:
            # grow: fresh zero buffers at the next bucket, old content
            # copied on device (one dus per plane — no host round
            # trip).  EVERY resident buffer grows, including columns
            # this chunk does not carry (their new tail stays zero =
            # invalid).
            for name, old in list(self.bufs.items()):
                cap = cap_k if name == "handles" else cap_v
                self.bufs[name] = resolver.dus(
                    jnp.zeros(cap, old.dtype), old, 0)
            self.cap_ver, self.cap_keys = cap_v, cap_k
        for name, chunk, per_ver in specs:
            off = self.n_ver if per_ver else self.n_keys
            if name == "seg_id":
                chunk = chunk + np.int32(key_base)
            chunk = np.ascontiguousarray(chunk)
            buf = self.bufs.get(name)
            if buf is None:
                # first content for this plane (first chunk, or a
                # column first seen mid-stream — earlier rows stay zero
                # = invalid, exactly what their payloads said): host-pad
                # + ONE plain H2D copy, no jitted kernel, so a
                # single-chunk stream compiles nothing at all
                cap = self.cap_ver if per_ver else self.cap_keys
                p = np.zeros(cap, chunk.dtype)
                p[off:off + len(chunk)] = chunk
                buf = jnp.asarray(p)
            else:
                buf = resolver.dus(buf, jnp.asarray(chunk), off)
            self.bufs[name] = buf
        self.n_ver, self.n_keys = new_ver, new_keys
        self.nbytes = sum(int(b.nbytes) for b in self.bufs.values())


class ColdFeedBundle:
    """One cold build's device-resolve artifacts, stashed on the new
    cache line's FeedLineage until the runner's first feed miss mints
    the born-resident feed from them (device/feed.py ``FeedStore.get``).

    One-shot and version-0-only: any delta landing first (the line
    moved on) or a mint attempt (success OR failure) drops it — the
    plain host upload path is always a correct fallback.
    """

    __slots__ = ("resolver", "planes", "device", "n", "read_ts",
                 "mirror_handles", "mirror_cols", "has_nulls",
                 "spill_patches", "consumed", "lineage_v")

    def __init__(self, resolver: "DeviceMvccResolver",
                 planes: WritePlanes, device: Optional[DeviceVersionPlanes],
                 n: int, read_ts: int, mirror_handles: np.ndarray,
                 mirror_cols: dict, spill_patches: Optional[dict] = None):
        self.resolver = resolver
        self.planes = planes
        self.device = device            # streamed H2D state, or None
        self.n = n
        self.read_ts = read_ts
        self.mirror_handles = mirror_handles
        self.mirror_cols = mirror_cols  # col_id -> Column (host truth)
        self.has_nulls = {cid: not bool(col.validity.all())
                          for cid, col in mirror_cols.items()}
        # feed-row positions whose PUT payload lives in CF_DEFAULT —
        # patched after the gather from the host-truth mirror (the
        # kernel saw no short value for them)
        self.spill_patches = spill_patches or {}
        self.consumed = False
        self.lineage_v = -1     # stamped by FeedLineage.stash_cold

    def release(self) -> None:
        """Drop every device/host reference (stale bundle teardown)."""
        self.consumed = True
        self.planes = None
        self.device = None
        self.mirror_cols = {}
        self.mirror_handles = None

    # ------------------------------------------------------------ mint

    def mint(self, store, used_infos: Sequence, dtypes: Sequence,
             n: int, n_pad: int):
        """Build the feed (``store.make_feed``: the upload's layout) by
        resolving + gathering ON DEVICE.  Returns None when this bundle
        cannot serve the request (shape moved, columns missing) — the
        caller falls through to the host upload path."""
        if self.consumed or self.planes is None or n != self.n or n == 0:
            return None
        for info in used_infos:
            if not info.is_pk_handle and \
                    info.col_id not in self.mirror_cols:
                return None
        try:
            return self.resolver._mint(self, store, used_infos,
                                       dtypes, n, n_pad)
        finally:
            self.release()


class DeviceMvccResolver:
    """Owns the jitted resolve/gather kernels and the chunked-H2D
    machinery.  Single-device only (the sharded mesh path keeps the
    host upload pipeline — GSPMD re-lays feeds anyway)."""

    def __init__(self, runner):
        self._runner = runner
        self._mu = threading.Lock()
        self._kernels: dict = {}
        self._dus_fn = None
        self.mints = 0
        self.mint_failures = 0

    # -- availability ---------------------------------------------------

    def available(self) -> bool:
        from ..native import mvcc_parse_planes
        r = self._runner
        return mvcc_parse_planes is not None and r is not None and \
            getattr(r, "_single", False)

    def h2d_profitable(self) -> bool:
        """Whether streaming version planes onto the device AHEAD of
        the first query pays: only on a real accelerator.  On the CPU
        backend a device_put is a host-memory alias — there is no
        transfer to overlap, and the chunk-append ``dus`` compiles
        contend (measured: they starve both the loader and the take
        path) for the exact cores the load needs."""
        try:
            import jax
            return jax.devices()[0].platform != "cpu"
        except Exception:   # noqa: BLE001 — no jax, no device leg
            return False

    # -- shared jitted helpers -------------------------------------------

    def dus(self, arr, update, lo: int):
        """Traced-offset slice update (one compile class per
        (buffer shape, update shape, dtype) — chunk appends and buffer
        growth share it)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        with self._mu:
            fn = self._dus_fn
            if fn is None:
                def h2d_stream_patch(a, u, i):
                    return lax.dynamic_update_slice(a, u, (i,))
                fn = self._dus_fn = jax.jit(h2d_stream_patch)
        return fn(arr, update, jnp.asarray(lo, jnp.int32))

    # -- the resolve + gather kernel --------------------------------------

    def _kernel(self, nver_pad: int, nkeys_pad: int, out_pad: int,
                spec: tuple):
        """spec: per output plane —
        ("h", out_dtype)                      pk-handle column
        ("v", src_slot, out_dtype)            value plane (astype'd)
        ("m", src_slot)                       validity plane (bool)
        src_slot indexes the variadic plane inputs after the fixed
        (commit_ts, wtype, seg_id, handles) quartet."""
        key = (nver_pad, nkeys_pad, out_pad, spec)
        with self._mu:
            fn = self._kernels.get(key)
        if fn is not None:
            return fn

        import jax
        import jax.numpy as jnp

        def mvcc_resolve(read_ts, n_out, commit_ts, wtype, seg_id,
                         handles, *planes):
            i32 = jnp.int32
            elig = (commit_ts <= read_ts) & (wtype <= WT_DELETE)
            score = jnp.where(elig, commit_ts, jnp.int64(0))
            seg_max = jax.ops.segment_max(score, seg_id,
                                          num_segments=nkeys_pad)
            win = elig & (score == seg_max[seg_id]) & (score > 0)
            vis = win & (wtype == WT_PUT)
            pos = jnp.cumsum(vis.astype(i32)) - 1
            tgt = jnp.where(vis, pos, i32(out_pad))
            idx = jnp.zeros(out_pad, i32).at[tgt].set(
                jnp.arange(nver_pad, dtype=i32), mode="drop")
            live = jnp.arange(out_pad, dtype=i32) < n_out.astype(i32)
            outs = []
            for s in spec:
                if s[0] == "h":
                    v = handles[seg_id[idx]].astype(jnp.dtype(s[1]))
                    outs.append(jnp.where(live, v, 0))
                elif s[0] == "v":
                    v = planes[s[1]][idx].astype(jnp.dtype(s[2]))
                    outs.append(jnp.where(live, v,
                                          jnp.zeros((), v.dtype)))
                else:
                    outs.append(planes[s[1]][idx] & live)
            return tuple(outs)

        fn = jax.jit(mvcc_resolve)
        with self._mu:
            self._kernels[key] = fn
        return fn

    # -- feed mint ---------------------------------------------------------

    def _mint(self, bundle: ColdFeedBundle, store, used_infos,
              dtypes, n: int, n_pad: int):
        import jax.numpy as jnp

        from ..utils import tracker
        from ..utils.failpoint import fail_point
        from .feed import value_plane_index
        if fail_point("device::mvcc_resolve") is not None:
            self.mint_failures += 1
            return None
        planes = bundle.planes
        dev = bundle.device
        if dev is not None and (dev.n_ver != planes.n_ver or
                                dev.n_keys != planes.n_keys):
            dev = None          # streamed state diverged: re-upload
        # which source planes the kernel needs, in input order
        spec = []
        srcs = []               # (host array, device name)

        def slot(name: str, host_arr) -> int:
            for i, (_a, nm) in enumerate(srcs):
                if nm == name:
                    return i
            srcs.append((host_arr, name))
            return len(srcs) - 1

        null_flags = []
        for info, ds in zip(used_infos, dtypes):
            if info.is_pk_handle:
                spec.append(("h", ds))
                null_flags.append(False)
                continue
            cid = info.col_id
            _k, vals, valid = planes.cols[cid]
            spec.append(("v", slot(f"v{cid}", vals), ds))
            has_nulls = bundle.has_nulls[cid]
            null_flags.append(has_nulls)
            if has_nulls:
                spec.append(("m", slot(f"m{cid}", valid)))

        if dev is not None:
            nver_pad, nkeys_pad = dev.cap_ver, dev.cap_keys
        else:
            nver_pad = _bucket(planes.n_ver)
            nkeys_pad = _bucket(planes.n_keys)

        def pad_put(arr, cap):
            a = np.ascontiguousarray(arr)
            if len(a) != cap:
                p = np.zeros(cap, a.dtype)
                p[:len(a)] = a
                a = p
            return jnp.asarray(a)

        with tracker.phase("h2d_stream"):
            if dev is not None:
                fixed = (dev.bufs["commit_ts"], dev.bufs["wtype"],
                         dev.bufs["seg_id"], dev.bufs["handles"])
                # a column the stream never saw a datum for has no
                # resident plane: all-invalid zeros serve it (the host
                # mirror agrees — it synthesized the same)
                ins = tuple(
                    dev.bufs[nm] if nm in dev.bufs
                    else jnp.zeros(nver_pad, a.dtype)
                    for a, nm in srcs)
            else:
                fixed = (pad_put(planes.commit_ts.view(np.int64),
                                 nver_pad),
                         pad_put(planes.wtype, nver_pad),
                         pad_put(planes.seg_id, nver_pad),
                         pad_put(planes.handles, nkeys_pad))
                ins = tuple(pad_put(a, nver_pad) for a, _nm in srcs)

        with tracker.phase("mvcc_resolve"):
            fn = self._kernel(nver_pad, nkeys_pad, n_pad, tuple(spec))
            read_ts = jnp.asarray(bundle.read_ts, jnp.int64)
            n_out = jnp.asarray(n, jnp.int64)
            flat = list(fn(read_ts, n_out, *fixed, *ins))

            # CF_DEFAULT spills: the kernel gathered zero cells for
            # PUTs whose payload lives in CF_DEFAULT — patch them from
            # the host-truth values fetched at build time
            if bundle.spill_patches:
                plane_of = value_plane_index(null_flags)
                for row, payload in bundle.spill_patches.items():
                    for ci, info in enumerate(used_infos):
                        if info.is_pk_handle:
                            continue
                        col = bundle.mirror_cols[info.col_id]
                        fi = plane_of[ci]
                        upd = np.asarray(
                            [col.values[row]]).astype(
                                flat[fi].dtype, copy=False)
                        flat[fi] = store.dus(flat[fi], jnp.asarray(upd),
                                             row)
                        if null_flags[ci]:
                            m = np.asarray([bool(col.validity[row])])
                            flat[fi + 1] = store.dus(
                                flat[fi + 1], jnp.asarray(m), row)

        self.mints += 1
        mirror = [(bundle.mirror_handles, None) if i.is_pk_handle
                  else (bundle.mirror_cols[i.col_id].values,
                        bundle.mirror_cols[i.col_id].validity)
                  for i in used_infos]
        # every plane the column itself (``FeedStore.get`` keeps a date
        # or code plane away from this rung); its host truth the mirror,
        # cast only where the store reads it
        return store.make_feed(
            flat, null_flags, n_pad, (None,) * len(null_flags),
            ((np.ascontiguousarray(v.astype(np.dtype(ds), copy=False)), ok)
             for (v, ok), ds in zip(mirror, dtypes)), n)

    def stats(self) -> dict:
        with self._mu:
            return {"mints": self.mints,
                    "mint_failures": self.mint_failures,
                    "kernels": len(self._kernels)}
