"""The device feed: a scan's used columns as flat padded planes in HBM,
kept across requests (the region-cache-engine analog).

``DeviceRunner._handle_local`` asks ``FeedStore.get`` for a request's
feed under its dispatch lock; the supervisor scrubs, moves and splits
resident feeds through the same store.  Top to bottom: the FORMAT (how
a column becomes a plane, where its planes lie in ``flat``, the
request's host halves: ``HostPlanes``), the DERIVED RECORD (what a
request memo holds of a line's rows, and the one function that rolls it
across a write: ``roll_derived``), then ``FeedStore``: the shape,
the one constructor, the build ladder (``get``), the patch, the
compaction after tombstones, the digests, the move between slices and
the split of a region's line.

A feed is a dict: ``flat`` (per used column its value plane, then its
validity plane if the column holds a NULL), ``null_flags`` (which do),
``n_pad``, ``kinds`` (``plane_kinds``), ``n_live`` (the rows it holds),
where the runner records digests ``digests``; ``lineage_v`` (the
generation it reflects), ``key`` (what its bucket holds it under),
``positional`` / ``pk_flags`` (what a device split needs).

The store owns no state.  It serves through its runner's, by these
names and no others: ``_arena``, ``_kernel_cache``, ``_single``,
``_mesh``, ``_row_sharding``, ``_repl``, ``_nshards``, ``_block_local``,
``_chunk_override``, ``scrub_digests``, ``_dispatch_mu``,
``_sub_runners``, ``flight_recorder`` (its counts of patches, of
rebuilds after a delta and of where their rows came from: /health
``device_mesh.feed``).  The runner,
``aggregate.py``, ``mvcc.py`` and ``join.py`` import this module, and
it imports none of them.
"""

from __future__ import annotations

import math
import threading
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..copr.dag import TableScanDesc
from ..datatype import EvalType
from ..datatype.tile import _device_dtype, code_plane, date_plane
from ..expr.eval import eval_rpn
from ..expr.rpn import RpnColumnRef
from ..utils import tracker
from . import lowering
from .kernels import int_planes_needed, named_program
from .request import _FallbackToHost, _fp_degrade, _rpn_col_indices

# same-width unsigned views for bit-exact digest/corruption bitcasts
_UINT_BY_ITEMSIZE = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32,
                     8: jnp.uint64}


def _to_bits(x):
    """A plane's elements as the uint64 a digest sums (traced)."""
    dt = np.dtype(x.dtype)
    if dt == np.bool_ or (dt.kind in "iu" and dt.itemsize == 8):
        # 64-bit ints convert, not bitcast: the wrap mod 2^64 IS the
        # bit pattern, and the TPU compiler has no 64-bit
        # bitcast-convert (its X64 rewrite rejects it — found on v5e,
        # libtpu 0.0.34)
        return x.astype(jnp.uint64)
    # narrower ints and float32: bitcast to the same-width unsigned
    # view, then widen.  (float64 takes this branch too and lowers on
    # CPU only; feed planes are never float64 — datatype/tile.py
    # _device_dtype.)
    return lax.bitcast_convert_type(
        x, _UINT_BY_ITEMSIZE[dt.itemsize]).astype(jnp.uint64)


# ------------------------------------------------------------- the format

def anchor(storage):
    """Feed/meta cache key object.  Delta-maintained snapshots carry
    a ``feed_lineage`` whose identity is stable across patch
    generations (copr/region_cache.py FeedLineage) — anchoring on it
    keeps the HBM feed warm across writes; plain snapshots anchor on
    themselves (invalidation by identity, as before)."""
    lineage = getattr(storage, "feed_lineage", None)
    return storage if lineage is None else lineage


def generation(storage) -> tuple:
    """→ (``storage``'s feed lineage or None, the generation of the line
    THIS snapshot reflects): the line may already be further ahead, or
    this may be a history-served older generation."""
    lineage = getattr(storage, "feed_lineage", None)
    v = getattr(storage, "feed_version", None)
    if lineage is not None and v is None:
        v = lineage.version
    return lineage, v


def plane_kinds(plan) -> tuple:
    """Per used column of ``plan`` what its value plane holds: ``"date"``
    (a DATE column's ``core >> 41`` as int32), a CHAR column's width in
    bytes (its codes, datatype/tile.py), or None (the column itself: a
    scaled DECIMAL is scaled in the cache line already).  Plan analysis
    decides (device/lowering.py); a feed carries its kinds from its
    build on, so a patch or a split asks the feed."""
    codes = plan.code_planes or (0,) * len(plan.used_cols)
    return tuple("date" if d else (w or None)
                 for d, w in zip(plan.date_planes, codes))


def plane_values(kind, vals: np.ndarray):
    """A used column's host values as the values of its device plane,
    before the cast to the plane's dtype: THE rule from a column to a
    plane, for a cold build and for a patch alike.  None where a CHAR
    value has no code (wider than the column's declared bytes, or
    holding the pad byte): such strings stay with the host."""
    if kind is None:
        return vals
    if kind == "date":
        return date_plane(vals)
    return code_plane(vals, kind)


def fits_dtype(vals: np.ndarray, valid, dt: np.dtype) -> bool:
    """May ``vals`` be represented in the feed's established device
    dtype?  Floats narrow exactly like a fresh astype would; ints must
    fit the integer range (and uint64 stays below 2^63 — the same feed
    guard that routes beyond-int64 cores to the host)."""
    if dt.kind not in "iu":
        return True
    live = vals if valid is None or valid.all() else vals[valid]
    if not live.size:
        return True
    lo, hi = int(live.min()), int(live.max())
    if dt == np.dtype(np.uint64):
        return 0 <= lo and hi < (1 << 63)
    info = np.iinfo(dt)
    return info.min <= lo and hi <= info.max


def positional(scan) -> bool:
    """Do a line's row positions map straight onto the rows of a feed
    (and of the host planes) of this scan?  Only an ascending table scan
    (index scans re-sort, desc scans reverse)."""
    return isinstance(scan, TableScanDesc) and \
        not getattr(scan, "desc", False)


def value_plane_index(null_flags) -> list:
    """Per used column, the index in ``flat`` of its value plane (its
    validity plane, where it has one, is the next)."""
    out, fi = [], 0
    for has_nulls in null_flags:
        out.append(fi)
        fi += 2 if has_nulls else 1
    return out


# The lengths, in rows, a patch's span is widened to ON THE HOST before
# it is sent (a span longer than the last is cut into windows of that):
# the patch program compiles once a (length, the feed's planes' dtypes,
# n_pad), so a line that takes appends and updates of every length (an
# order of 1-7 lineitems, two sessions' orders between two reads) runs
# three programs, all built by its first patch, and none compiles later.  The
# rows a span is widened by are sent as the request's snapshot holds
# them.
PATCH_BUCKETS = (16, 256, 4096)


def patch_bucket(rows: int) -> int:
    for b in PATCH_BUCKETS:
        if rows <= b:
            return b
    return PATCH_BUCKETS[-1]


# The runs of dead rows ONE compaction removes
# (``FeedStore._compact_program``: fewer are padded with empty runs, so
# a feed's class (its planes' dtypes, n_pad) compiles once; a gap of
# more is the host rebuild's).
COMPACT_RUNS = 16


def pack_updates(updates) -> tuple:
    """A window's per-plane updates (``flat``'s order, one length) as ONE
    host array a dtype, its rows in that order: every host array of a
    jitted call is an upload of its own, and each is a place where the
    dispatcher's thread gives the GIL away."""
    by: dict = {}
    for u in updates:
        by.setdefault(u.dtype, []).append(u)
    return tuple(np.stack(rows) for rows in by.values())


def unpack_updates(planes, packed) -> list:
    """``pack_updates`` undone for ``planes`` (traced, or host arrays)."""
    rows = {np.dtype(p.dtype): iter(p) for p in packed}
    return [next(rows[np.dtype(a.dtype)]) for a in planes]


def span_planes(span, used_infos, kinds):
    """One row span of a lineage's patch journal as plane values: per
    used column ``(values, validity)``, the values None where a CHAR
    value has no code, the validity None for the pk handle."""
    for info, kind in zip(used_infos, kinds):
        vals, valid = (span["handles"], None) if info.is_pk_handle \
            else span["cols"][info.col_id]
        yield plane_values(kind, vals), valid


def truth_digests(truth, null_flags, n: int) -> tuple:
    """The scrub digests of a feed's HOST truth: per used column
    ``(values, validity)`` at the planes' dtypes, one digest a plane the
    feed holds (a validity plane where the column has NULLs), over the
    ``n`` live rows: one host pass a plane, ~12 B of temporaries a byte
    of plane (supervisor ``host_plane_digest``)."""
    from .supervisor import host_plane_digest
    digests = []
    for (v, ok), has_nulls in zip(truth, null_flags):
        digests.append(host_plane_digest(v, n))
        if has_nulls:
            digests.append(host_plane_digest(ok, n))
    return tuple(digests)


class HostPlanes:
    """One request's used columns as the host halves of their planes:
    device dtypes, then (values, validity) numpy pairs at those dtypes.

    Both are kept for the snapshot's lifetime in ``meta`` (as the feed
    is: the astype alone costs ~2s per 100M-row REAL column, and the
    TopN candidate refine reads the pairs on every request),
    version-guarded: what a request derives once the line has moved on
    (``fresh()`` false) stays request-local, in ``memo``.  ``force_host``
    alone goes to ``meta`` whatever the generation: conservative-sticky,
    so repeat requests do not rebuild columns to re-discover it."""

    __slots__ = ("plan", "infos", "kinds", "meta", "memo", "fresh",
                 "get_batch", "n", "recorder", "pad_rows")

    def __init__(self, plan, meta: dict, memo: dict, fresh, get_batch,
                 n: int, recorder, pad_rows=None):
        self.plan = plan
        self.infos = [plan.scan.columns[ci] for ci in plan.used_cols]
        self.kinds = plane_kinds(plan)
        self.meta = meta
        self.memo = memo
        self.fresh = fresh
        self.get_batch = get_batch
        self.n = n
        self.recorder = recorder
        # the rows a feed of ``n`` is padded to (``FeedStore.pad_rows``)
        self.pad_rows = pad_rows

    def dtypes(self) -> tuple:
        memo, meta = self.memo, self.meta
        if "dtypes" not in memo:
            if "dtypes" in meta and self.fresh():
                memo["dtypes"] = meta["dtypes"]
                memo["limbs"] = meta.get("limbs", ())
            else:
                with tracker.phase("host_derive"):
                    self._derive()
        return memo["dtypes"]

    @property
    def limbs(self) -> tuple:
        """The aggregates ``lowering.fit`` asks to be summed as limbs."""
        self.dtypes()
        return self.memo["limbs"]

    def _host(self, reason: str):
        self.meta["force_host"] = True
        raise _FallbackToHost(reason)

    def _values(self, pos: int, col):
        """Used column ``pos``'s plane values, made once a request."""
        made = self.memo.setdefault("plane_vals", {})
        if pos not in made:
            made[pos] = plane_values(self.kinds[pos], col.values)
        return made[pos]

    def _derive(self) -> None:
        plan, memo = self.plan, self.memo
        batch = self.get_batch()
        dts = []
        bounds = []
        for pos, ci in enumerate(plan.used_cols):
            col = batch.columns[ci]
            if col.eval_type is EvalType.DECIMAL and col.frac is None:
                # the build kept this DECIMAL column as objects (a
                # value beyond its declared scale, a store without
                # the native build): host, as before the lowering
                self._host("unscaled DECIMAL column")
            kind = self.kinds[pos]
            vals = self._values(pos, col)
            if vals is None:
                self._host("CHAR value without a code")
            if kind == "date":
                dt = np.dtype(np.int32)
                self.recorder.note_plane("date")
            elif kind:
                dt = _device_dtype(EvalType.INT, vals)
                self.recorder.note_plane("code")
            else:
                dt = _device_dtype(col.eval_type, vals)
                if col.frac is not None:
                    self.recorder.note_plane("decimal")
            if dt == np.dtype(np.uint64) and not fits_dtype(vals, None, dt):
                # packed cores above 2^63 (year >= 8192) would
                # wrap in the int64 state carries
                self._host("u64 column beyond int64")
            dts.append(str(dt))
            if plan.lowered:
                bounds.append((int(vals.min()), int(vals.max()))
                              if vals.size else (0, 0))
        limbs = ()
        if plan.lowered:
            limbs = lowering.fit(plan, bounds, dts, self.n)
            if limbs is None:
                # the integer form may wrap at the planes' natural
                # width even with its products summed as limbs: try
                # every plane at int64 (the XLA bodies serve it),
                # else the host pipeline, whose Decimals are exact
                wide = ["int64" if np.dtype(d).kind == "i" else d
                        for d in dts]
                if not lowering.fits(plan, bounds, wide, self.n):
                    self._host("lowered DECIMAL arithmetic not provably "
                               "inside int64")
                dts, limbs = wide, ()
        memo["dtypes"] = tuple(dts)
        memo["limbs"] = limbs
        if self.fresh():
            self.meta["dtypes"] = memo["dtypes"]
            self.meta["limbs"] = limbs
            if plan.lowered:
                # what the proof stood on: ``roll_derived`` widens them
                # by the rows a write introduces and proves it again
                self.meta["bounds"] = tuple(bounds)

    def stream(self):
        """Yield the pairs one column at a time, building the memo
        incrementally: the cold feed upload issues each column's
        (async) device_put as soon as that column is converted, so the
        H2D transfer of column i overlaps the astype of column i+1 —
        double-buffering the tail of a columnar build instead of
        serializing convert-all then upload-all.

        Where the cast makes a pass over a plane anyway, it is cast INTO
        a zeroed buffer of the length a feed of these planes is padded
        to (``pad_rows``, whoever reads the planes first) and
        the memo's plane is the buffer's head (a view): what an upload
        pads is what it padded the last time, so the buffer stays with
        the planes (``host_pads``, by position; :meth:`padded`) and an
        upload that brings back a feed the HBM budget took puts it as
        it lies: no fresh pages, no copy."""
        memo, meta = self.memo, self.meta
        if "host_cols" not in memo:
            held = self._held()
            if held is not None:
                memo["host_cols"] = held
        if "host_cols" in memo:
            yield from memo["host_cols"]
            return
        dts = self.dtypes()
        batch = self.get_batch()
        n_pad = self.n if self.pad_rows is None else self.pad_rows(self.n)
        built = []
        # (in the request's memo as they are made: the cold upload that
        # drives this generator puts each buffer too)
        pads = memo["host_pads"] = []
        for pos, (ci, ds) in enumerate(zip(self.plan.used_cols, dts)):
            col = batch.columns[ci]
            vals = self._values(pos, col)
            if vals is None:
                raise _FallbackToHost("CHAR value without a code")
            dt = np.dtype(ds)
            if n_pad == self.n or (
                    vals is col.values and vals.dtype == dt
                    and vals.flags.c_contiguous):
                # (a plane that IS the line's column costs no host
                # memory today: it stays so, and its upload pads a copy)
                pad, v = None, np.ascontiguousarray(
                    vals.astype(dt, copy=False))
            else:
                pad = np.zeros(n_pad, dtype=dt)
                v = pad[:self.n]
                np.copyto(v, vals, casting="unsafe")    # astype's cast
            pads.append(pad)
            pair = (v, np.ascontiguousarray(col.validity))
            built.append(pair)
            yield pair
        memo["host_cols"] = built
        with _PLANES_MU:
            if self.fresh():
                meta["host_cols"] = built
                meta["host_pads"] = tuple(pads)
                meta.pop("host_gap", None)
                meta.pop("host_digests", None)

    def padded(self, pos: int, arr, n_pad: int):
        """The zero-padded host buffer of ``n_pad`` rows whose head the
        plane ``arr`` of used column ``pos`` is, where :meth:`stream`
        made one with it, else None.  Proved by identity (the only
        views of such a buffer are its heads), so planes that replaced
        the memo's (a cut, a request's own build) are never taken for
        them."""
        pads = self.memo.get("host_pads") or self.meta.get("host_pads")
        pad = pads[pos] if pads else None
        if pad is None or pad.shape[0] != n_pad or arr.base is not pad:
            return None
        return pad

    def _held(self) -> Optional[list]:
        """The shared memo's planes AT THIS REQUEST'S GENERATION, else
        None (the caller builds its own): the rows of the delete-only
        entries ``roll_derived`` noted beside them (``host_gap``) are cut
        here, where someone reads the planes (a host rebuild, a TopN
        refine), not at every roll.

        The cut runs ~10 ms with the GIL released, and a newer
        generation's roll (no dispatch lock: ``runner._refresh_meta``) or
        a second reader may run beside it: so the pair is READ as one
        (``_PLANES_MU``), cut into this request's hands, and PUBLISHED as
        one, only where the memo still holds the very pair that was read
        and still reflects this generation; anything else leaves the memo
        to whoever moved it.  Planes of another row count than the
        request's are never served (a pair read between a roll and its
        ``lineage_v``)."""
        meta = self.meta
        with _PLANES_MU:
            planes, gap = meta.get("host_cols"), meta.get("host_gap")
        if planes is None or not self.fresh():
            return None
        if gap:
            cut = _cut_dead(planes, self.plan, gap, self.n)
            if cut is None:
                return None
            self.recorder.note_planes_cut()
            with _PLANES_MU:
                if self.fresh() and meta.get("host_cols") is planes and \
                        meta.get("host_gap") is gap:
                    meta["host_cols"] = cut
                    del meta["host_gap"]
                    meta.pop("host_digests", None)
                    meta.pop("host_pads", None)
            planes = cut
        if planes and len(planes[0][0]) != self.n:
            return None
        return planes

    def truth_digests(self, truth, null_flags, n: int) -> tuple:
        """``truth_digests`` of this request's planes, computed ONCE for
        the planes the memo holds and kept beside them
        (``host_digests``): an upload that brings back a feed the HBM
        budget took reads the same planes and hashes nothing again.
        Held to THAT list of planes by identity and to the row count:
        whatever replaces the planes (a roll, a cut, a fresh build)
        leaves the digests behind."""
        planes = self.memo.get("host_cols")
        kept = self.meta.get("host_digests")
        if kept is not None and kept[0] is planes and kept[1] == n:
            return kept[2]
        got = truth_digests(truth, null_flags, n)
        with _PLANES_MU:
            if planes is not None and self.fresh() and \
                    self.meta.get("host_cols") is planes:
                self.meta["host_digests"] = (planes, n, got)
        return got

    def cols(self) -> list:
        return list(self.stream())

    def window(self, pos: int, kind, lo: int, hi: int) -> tuple:
        """Rows [``lo``, ``hi``) of used column ``pos`` as the request's
        snapshot holds them → (the values of a plane of ``kind``, None
        where a CHAR value has no code; the validity): what a patch
        writes over a widened span."""
        col = self.get_batch().columns[self.plan.used_cols[pos]]
        return plane_values(kind, col.values[lo:hi]), col.validity[lo:hi]


# ------------------------------------------------------ the derived record
#
# What a request memo (``DeviceRunner._request_meta``) holds of a line's
# ROWS is one record, these keys of it: the ``bounds`` of each used
# column's plane values (a lowered plan's: what its proofs stand on),
# the planes' ``dtypes``, the ``limbs`` ``lowering.fit`` asks for, the
# GROUP BY key's grid and the aggregates' byte-plane widths
# (``hash_bounds`` = (base, span, widths), a composite key's
# ``key_bounds``, ``simple_arg_nbytes``), and the host planes
# (``host_cols``, and beside them ``host_gap``: the delete-only journal
# entries they lag the record by, cut where the planes are next read:
# ``HostPlanes.stream``), with the scrub digests of THOSE planes
# (``host_digests``: ``HostPlanes.truth_digests``, held to the list by
# identity) and the padded buffers they are the heads of (``host_pads``:
# ``HostPlanes.padded``, held to each plane by identity).  ``HostPlanes``
# and the run bodies of
# aggregate.py write them, each when it is first asked for;
# ``roll_derived`` alone carries them across a write.

DERIVED = ("bounds", "dtypes", "limbs", "hash_bounds", "key_bounds",
           "simple_arg_nbytes", "host_cols", "host_gap", "host_digests",
           "host_pads")

# ``host_cols`` and ``host_gap`` change together: a roll notes a gap
# beside the planes or drops both, a reader publishes the planes cut and
# the gap gone (``HostPlanes._held``), each under this lock, which is
# held for dict operations alone, never for a cut.
_PLANES_MU = threading.Lock()


def _bare_int_ref(rpn) -> Optional[int]:
    """The used column an aggregate's integer argument is a bare
    reference to, else None."""
    if rpn is None or rpn.ret_type is EvalType.REAL or \
            len(rpn.nodes) != 1 or \
            not isinstance(rpn.nodes[0], RpnColumnRef):
        return None
    return rpn.nodes[0].col_idx


def bare_int_refs(plan) -> set:
    """The used columns ``arg_byte_planes`` reads a bound of whatever
    the plan: those an aggregate's argument is a bare reference to."""
    return {c for c in map(_bare_int_ref, plan.agg_rpns) if c is not None}


def arg_byte_planes(plan, bounds, dtypes) -> tuple:
    """Byte-plane count per aggregate argument for the MXU int path,
    over a feed whose used column ``i`` holds plane values inside
    ``bounds[i]`` at ``dtypes[i]`` (a bound is read for a lowered plan's
    columns and for a column an argument is a bare reference to).

    Plain column refs use the column's value range; computed
    expressions use the device dtype width (int arithmetic wraps
    in-dtype on device — documented deviation, expr/functions.py), but
    a LOWERED plan's: there ``lowering`` proves each argument's interval
    from the columns' bounds (it proved the plan exact from the same),
    so a limb product of 21 bits rides three planes, not four."""
    proven = lowering.agg_intervals(plan, bounds, dtypes) \
        if plan.lowered else {}
    out = []
    for j, r in enumerate(plan.agg_rpns):
        if r is None or r.ret_type is EvalType.REAL:
            out.append(0)
        elif _bare_int_ref(r) is not None:
            out.append(int_planes_needed(*bounds[_bare_int_ref(r)]))
        elif j in proven:
            out.append(int_planes_needed(*proven[j]))
        else:
            out.append(max([np.dtype(dtypes[i]).itemsize
                            for i in _rpn_col_indices(r)] or [4]))
    return tuple(out)


def roll_derived(meta: dict, plan, patches, count_rows, limb_variant,
                 recorder, journal_depth: int) -> str:
    """Roll the derived record in ``meta`` across ``patches``, the
    journal entries of a lineage gap (``FeedLineage.since``; None: the
    journal no longer covers it), from the rows the entries INTRODUCED:
    every constant the record keeps is proved again for them, and where
    a proof fails, or an entry does not say what it did, the whole
    record drops and the next request derives it from the line (correct,
    and as dear as before this function: ``HostPlanes._derive``).

    Deletes never enter: a bound is an upper bound and every constant is
    valid for any data inside it, so a kept record may cut more byte
    planes than a fresh derive of the shrunken line would, never fewer.
    ``count_rows()`` is the request's row count after the gap (it enters
    ``lowering.fit``'s sum proof), ``limb_variant(plan, limbs)`` the
    plan a feed asking for limbs is served by, ``journal_depth`` the
    entries the lineage's journal keeps (``FeedLineage.depth``): the
    host planes lag the record by no more.  Counted on ``recorder``
    (/health ``device_mesh.memo``): kept, or dropped by its cause, and
    what became of the host planes it held.  → the same as a word for
    the ``memo_roll`` span: ``kept`` / ``dropped:<cause>``
    (``underived``: there was no record)."""
    if "dtypes" not in meta:
        _drop(meta)
        return "underived"      # nothing was derived yet
    if patches is None or any("introduced" not in p for p in patches) or \
            not plan.lowered and any(p.get("structural") for p in patches):
        # (a plan that is not lowered keeps its constants across row
        # patches alone, as it always has)
        cause = "unknown"
    else:
        n = meta["n_rows"] = count_rows()
        cause = _disproved(meta, plan, [rows for p in patches
                                        for rows in p["introduced"]],
                           n, limb_variant)
    fate = None
    if cause is not None:
        fate = "dropped" if "host_cols" in meta else None
        _drop(meta)
    else:
        with _PLANES_MU:
            planes = meta.get("host_cols")
            if planes is not None:
                # the host planes stay as they are and the gap's
                # tombstones are noted beside them: a resident feed is
                # compacted on the device (``FeedStore._try_compact_feed``)
                # and reads none of them; the cut runs where they are
                # next read (``HostPlanes._held``)
                gap = meta.get("host_gap", ()) + tuple(patches)
                if len(gap) <= journal_depth and positional(plan.scan) and \
                        _delete_only(gap, len(planes[0][0]) if planes else -1,
                                     n):
                    meta["host_gap"], fate = gap, "deferred"
                else:
                    # (as after any other write: a patched feed reads
                    # ``HostPlanes.window`` and needs none)
                    del meta["host_cols"]
                    meta.pop("host_gap", None)
                    meta.pop("host_digests", None)
                    meta.pop("host_pads", None)
                    fate = "dropped"
    recorder.note_memo(cause, fate)
    return "kept" if cause is None else f"dropped:{cause}"


def _drop(meta: dict) -> None:
    with _PLANES_MU:
        for k in DERIVED:
            meta.pop(k, None)


def _disproved(meta: dict, plan, introduced, n: int,
               limb_variant) -> Optional[str]:
    """Which of the record's constants the ``introduced`` rows (journal
    dicts of ``handles`` / ``cols``) leave → the /health cause, or None
    where every one is proved again; then a lowered plan's ``bounds``
    are widened in ``meta``."""
    infos = [plan.scan.columns[ci] for ci in plan.used_cols]
    kinds, dtypes = plane_kinds(plan), meta["dtypes"]
    # the rows as the plan's rpns see them: plane values (a DATE column
    # on the date plane shifted, a CHAR column as its codes)
    rows = [list(span_planes(r, infos, kinds)) for r in introduced]
    for row in rows:
        for (vals, valid), ds in zip(row, dtypes):
            if vals is None:
                return "code"
            if not fits_dtype(vals, valid, np.dtype(ds)):
                return "dtype"
    widths = [meta[k][-1] if k == "hash_bounds" else meta[k]
              for k in ("hash_bounds", "simple_arg_nbytes") if k in meta]
    if "hash_bounds" in meta:
        # every introduced key inside its (base, span); a composite
        # key's each inside its own, and never NULL (the grid has one
        # NULL slot: aggregate.py ``_key_bounds``)
        base, span, _w = meta["hash_bounds"]
        grid = meta.get("key_bounds") if len(plan.key_rpns) > 1 \
            else ((base, span),)
        if grid is None:
            return "key"
        for row in rows:
            m = len(row[0][0])
            pairs = [(v, np.ones(m, np.bool_) if ok is None else ok)
                     for v, ok in row]
            for rpn, (lo, wid) in zip(plan.key_rpns, grid):
                kv, km = eval_rpn(rpn, pairs, m, np)
                kv = np.broadcast_to(kv, (m,))
                km = np.broadcast_to(km, (m,))
                if len(grid) > 1 and not km.all():
                    return "null_key"
                live = kv[km]
                if live.size and (int(live.min()) < lo or
                                  int(live.max()) >= lo + wid):
                    return "key"
    if not plan.lowered:
        # a bare column's planes follow its values; a computed
        # argument's its dtype width, which was held above
        for kept in widths:
            for r, planes in zip(plan.agg_rpns, kept):
                if _bare_int_ref(r) is None:
                    continue
                for row in rows:
                    vals, valid = row[_bare_int_ref(r)]
                    live = vals if valid is None or valid.all() \
                        else vals[valid]
                    if live.size and int_planes_needed(
                            int(live.min()), int(live.max())) > planes:
                        return "widths"
        return None
    if "bounds" not in meta:
        return "unknown"
    bounds = list(meta["bounds"])
    for row in rows:
        for i, (vals, _valid) in enumerate(row):
            if vals.size:
                bounds[i] = (min(bounds[i][0], int(vals.min())),
                             max(bounds[i][1], int(vals.max())))
    # interval arithmetic over the plan, no data touched: the limb split
    # and every byte-plane width from the widened bounds and the new n
    limbs = lowering.fit(plan, bounds, dtypes, n)
    if limbs != meta["limbs"]:
        return "limbs"
    proved = arg_byte_planes(limb_variant(plan, limbs) if limbs else plan,
                             bounds, dtypes)
    if any(kept != proved for kept in widths):
        return "widths"
    meta["bounds"] = tuple(bounds)
    return None


def _delete_only(patches, rows: int, n: int) -> bool:
    """Do ``patches`` take a view of ``rows`` rows to one of ``n`` by
    tombstones alone, each entry saying which (``dead``)?  Then every
    row that is left lies where it lay, less the dead rows before it.
    Not where an entry wrote a row's values, renumbered the view (a
    repack, a compaction, a revive: no ``dead``), does not say what it
    did, or where ``rows`` is not the whole view's count (planes or a
    feed of part of the line's rows are not laid out by its view's
    positions: a ranged request's)."""
    for p in patches:
        dead = p.get("dead")
        if dead is None or p.get("introduced", True) or \
                rows != p["live"] + len(dead):
            return False
        rows = p["live"]
    return rows == n


def dead_runs(patches) -> list:
    """The rows delete-only ``patches`` tombstoned, entry after entry
    each in the numbering of the view before it, folded into ascending
    disjoint runs ``[start, length]`` in the numbering of the view
    BEFORE THE FIRST (an RF2 order is one run of 1-7 rows; the oldest
    orders of several sessions lie side by side and fold into one)."""
    gone = np.zeros(0, np.int64)
    for p in patches:
        pos = np.asarray(p["dead"], np.int64)
        # (``gone[i] - i`` is where the first row left behind ``gone[i]``
        # lies now: a position has as many dead rows before it as there
        # are such at or before it)
        pos = pos + np.searchsorted(gone - np.arange(gone.size), pos,
                                    side="right")
        gone = np.union1d(gone, pos)
    if not gone.size:
        return []
    first = np.flatnonzero(np.diff(gone, prepend=gone[0] - 2) != 1)
    return [[int(gone[i]), int(j - i)]
            for i, j in zip(first, [*first[1:], gone.size])]


def _cut_dead(host_cols: list, plan, patches, n: int) -> Optional[list]:
    """The host planes of the generation before ``patches`` cut to the
    rows the entries left, where every entry is delete-only and says
    which (``_delete_only``): what ``FeedStore.get``'s host rebuild
    after a tombstone then streams in place of planes made again from
    the logical view and its Python ``bytes``; None (they drop, as after
    any other write) anywhere else.  One boolean gather a plane, 9-10 ms
    at 500,102 rows of seven: paid where the planes are read
    (``HostPlanes.stream``), which a feed compacted on the device
    (``FeedStore._try_compact_feed``) does not."""
    rows = len(host_cols[0][0]) if host_cols else -1
    if not positional(plan.scan) or not _delete_only(patches, rows, n):
        return None
    keep = None
    for p in patches:
        dead = p["dead"]
        if dead:
            if keep is None:
                keep = np.ones(rows, np.bool_)
                keep[list(dead)] = False
            else:
                keep[np.flatnonzero(keep)[list(dead)]] = False
    if keep is None:
        return host_cols
    return [(v[keep], ok[keep]) for v, ok in host_cols]


# -------------------------------------------------------------- the store

class FeedStore:
    """The feeds of one ``DeviceRunner`` (module docstring)."""

    def __init__(self, runner):
        self._runner = runner

    # --------------------------------------------- the shape, the builders

    def unit(self) -> int:
        return self._runner._nshards() * self._runner._block_local

    def pad_rows(self, n: int) -> int:
        unit = self.unit()
        blocks = max(1, -(-n // unit))
        # bucket the block count into a 9/8-geometric grid: every
        # padded shape is a compile class (pallas grid + XLA scan
        # length), and live regions change size on every write — exact
        # padding would recompile the kernels on each data version.
        # Bucketing bounds the number of compile classes
        # logarithmically and taxes ONLY the cache key, never the
        # computed extent: blocks past the live rows skip their MXU /
        # aggregation work (pl.when dead-block guard in pallas_hash,
        # lax.cond guard in aggregate.py _scan_program's step), so the
        # ≤12.5% padding costs DMA + grid steps, not kernel time.
        if not self._runner._chunk_override and blocks > 8:
            # one ROW of growth headroom BEFORE bucketing: it only
            # moves sizes whose live rows exactly fill their last block
            # (ceil absorbs it everywhere else), so such a feed — e.g.
            # a power-of-two bulk load — does not change compile class
            # (XLA recompile + full re-upload) on the very first
            # appended row.  (Was one BLOCK: where the bucket grid is
            # one block wide — 9..15 blocks — n and n+1 then still
            # landed in different buckets; found on a 2x2 v5e mesh,
            # where 10,485,760 rows fill ten 2^20-row blocks exactly.)
            blocks = -(-(n + 1) // unit)
            # round up to a 4-significant-bit block count (k·2^s,
            # 8 ≤ k ≤ 15): keeps n_pad rich in powers of two so
            # pick_chunk's gcd still finds large scan chunks
            s = blocks.bit_length() - 4
            k = -(-blocks // (1 << s))
            if k > 15:
                s += 1
                k = -(-blocks // (1 << s))
            blocks = k << s
        return blocks * unit

    def pick_chunk(self, n_pad: int, desired: int) -> int:
        """Largest scan-block size ≤ desired that divides the padded feed
        and splits evenly over shards."""
        unit = self.unit()
        if self._runner._chunk_override:
            desired = unit
        desired = max(unit, (desired // unit) * unit)
        return math.gcd(n_pad, desired)

    def make_feed(self, flat, null_flags, n_pad: int, kinds, truth,
                  n: int, digests=truth_digests) -> dict:
        """THE feed dict: every builder (the upload, the device MVCC
        resolve, a split's child) comes here with its planes and the
        HOST ``truth`` they hold (per used column (values, validity) at
        the planes' dtypes; read only where the runner records digests).
        The digests anchor there, never to the planes they audit: a wrong
        resolve, slice or gather diverges at the next scrub instead of
        laundering.  ``digests(truth, null_flags, n)``: who hashes the
        truth (the upload's: ``HostPlanes.truth_digests``, which keeps
        what it hashed beside the planes)."""
        feed = {"flat": tuple(flat), "null_flags": tuple(null_flags),
                "n_pad": n_pad, "kinds": tuple(kinds), "n_live": n}
        if self._runner.scrub_digests:
            feed["digests"] = digests(truth, feed["null_flags"], n)
            self._warm_digest_kernels(feed["flat"])
        return feed

    def _warm_digest_kernels(self, flat) -> None:
        """Pre-register the planes' digest kernels now (a cold path,
        under the dispatch lock) so the scrubber, which hashes OUTSIDE
        the lock, mints no kernel cache entries beside request threads
        — compile classes stay churn-stable."""
        for a in flat:
            self.digest_kernel(a.dtype, a.shape[0])

    def _build_flat(self, host_cols, n: int, kinds,
                    digests=truth_digests, padded=None) -> dict:
        """One flat padded array per column value; a validity array only
        for columns that actually contain NULLs — all-valid columns
        reuse the on-device row mask (synthesized from iota < n), saving
        the HBM footprint and H2D bandwidth of an all-true mask.
        ``padded(pos, values, n_pad)``: the zero-padded host buffer a
        used column's value plane is the head of, where its maker kept
        one (``HostPlanes.padded``), else None."""
        r = self._runner
        n_pad = self.pad_rows(n)
        flat, flags, pairs = [], [], []

        def put_padded(arr, pad=None):
            if r._single:
                if n_pad == n:
                    return jnp.asarray(arr)
                if pad is not None:
                    # the plane's own padded buffer, kept with it and
                    # never written again: put as it lies
                    return jnp.asarray(pad)
                # pad on the HOST: a device-side concatenate would
                # compile per exact n (every data version has a new row
                # count), costing seconds per cache rebuild; a host
                # memcpy is shape-oblivious.  (A FRESH buffer a plane: the
                # put reads it after it returns, on the chip too.  PR 53
                # reused one and every plane read its successor's rows.)
                p = np.zeros(n_pad, dtype=arr.dtype)
                p[:n] = arr
                return jnp.asarray(p)
            # a sharded cold build, span by span: the host's padded
            # copy (where the plane did not bring its own), then handing
            # one slice to each shard (the put is not waited for: the
            # next plane's pad overlaps it, and the first launch waits
            # for what is left)
            p = pad
            if p is None:
                with tracker.span("feed_host_pad"):
                    p = np.zeros(n_pad, dtype=arr.dtype)
                    p[:n] = arr
            with tracker.span("feed_shard_put"):
                return jax.device_put(p, r._row_sharding)

        for pos, (v, ok) in enumerate(host_cols):
            pairs.append((v, ok))
            flat.append(put_padded(
                v, padded(pos, v, n_pad) if padded is not None else None))
            flags.append(not bool(ok.all()))
            if flags[-1]:
                flat.append(put_padded(ok))
        return self.make_feed(flat, flags, n_pad, kinds, pairs, n, digests)

    def get(self, storage, planes: HostPlanes, ranges, n: int, lineage,
            req_v) -> dict:
        """The feed of ``planes`` over ``ranges`` of ``storage``'s line
        at generation ``req_v``, from the cheapest rung that has it: the
        arena's (hit), that one patched forward, or compacted where the
        gap is tombstones alone, a split's stash, the device MVCC
        resolve's bundle, the upload.  On the dispatcher the ladder's
        own time between its rungs is the hold's row ``feed_get``."""
        with tracker.held("feed_get"):
            return self._get(storage, planes, ranges, n, lineage, req_v)

    def _get(self, storage, planes: HostPlanes, ranges, n: int, lineage,
             req_v) -> dict:
        scan, used_infos, dtypes = planes.plan.scan, planes.infos, \
            planes.dtypes()
        feed_key = (tuple(i.col_id for i in used_infos), dtypes, ranges)
        # (patching maps journal row positions straight onto feed rows)
        by_position = positional(scan)
        arena = self._runner._arena
        cache = anc = None
        if hasattr(storage, "scan_columns"):
            anc = anchor(storage)
            cache = arena.bucket(anc)
        feed = cache.get(feed_key) if cache is not None else None
        # why a resident feed that a write left behind is built again
        # instead of patched (None: no such feed)
        rebuild = None
        if feed is not None:
            fv = feed.get("lineage_v")
            if lineage is None or fv == req_v:
                self._answered("hit")
                return feed
            if fv is not None and fv > req_v:
                # an older-generation read (history serve): never
                # downgrade the shared feed — build a private one
                cache = None
            else:
                # the snapshot moved forward under the feed: replay only
                # the journal's dirty row spans into HBM instead of a
                # cold re-upload — bucketed padding keeps n_pad (the
                # compile class) stable across small deltas
                rebuild = self._try_patch_feed(
                    feed, lineage, planes, n, req_v) if by_position \
                    else "structural"
                if rebuild is None:
                    self._answered("patch")
                    self._register_digests(lineage, feed_key, feed)
                    return feed
                if rebuild == "structural" and \
                        self._try_compact_feed(feed, lineage, n, req_v):
                    # the same rebuild (every plane written anew, the
                    # feed's positions the view's), sourced from the
                    # resident planes and not from the host's
                    self._answered("compact")
                    self._register_digests(lineage, feed_key, feed)
                    return feed

        def adopt(feed: dict) -> dict:
            """A feed this ladder just made, into its line."""
            if lineage is not None:
                feed["lineage_v"] = req_v
            if by_position:
                # which planes carry the pk-handle column (sourced from
                # state.handles, not state.cols): a device-side region
                # split re-anchors child digests to host truth by it
                feed["positional"] = True
                feed["pk_flags"] = tuple(bool(i.is_pk_handle)
                                         for i in used_infos)
            if cache is not None:
                self._cache_feed(cache, feed_key, feed)
                # admission runs under the dispatch lock (get's call
                # site): the budget check may evict other, unpinned
                # anchors
                arena.admit(anc)
                self._register_digests(lineage, feed_key, feed)
            return feed

        # device-side region split (supervisor.on_region_split): the
        # parent feed was sliced by key range INTO this child lineage's
        # stash — consume it instead of re-uploading from host.  The
        # stash was digest-verified against the child's host truth at
        # split time, so serving it is as safe as serving a scrubbed
        # resident feed.
        if lineage is not None and by_position and cache is not None:
            feed = self.take_split_feed(lineage, feed_key, n)
            # (a child that moved past the stash where the journal
            # cannot bridge it falls through to the upload)
            if feed is not None and (
                    feed.get("lineage_v") == req_v or self._try_patch_feed(
                        feed, lineage, planes, n, req_v,
                        count=False) is None):
                self._answered("split")
                return adopt(feed)
        # cold-path kill (device/mvcc.py): a device build left its
        # resolve artifacts on the lineage — mint the feed BORN
        # RESIDENT (H2D of raw version planes — or nothing, if the
        # streaming ingest pipeline already uploaded them — plus ONE
        # resolve+gather dispatch) instead of the host pad/astype/upload
        # pass.  One-shot and version-pinned; any failure falls through
        # to the plain upload below, which is always correct.
        if lineage is not None and \
                getattr(lineage, "cold_bundle", None) is not None:
            if by_position and cache is not None and \
                    not any(planes.kinds):
                # (the resolver gathers the columns as they lie: a date
                # or code plane is cut from the host mirror instead)
                bundle = lineage.take_cold(req_v)
                if bundle is not None:
                    feed = bundle.mint(self, used_infos, dtypes, n,
                                       self.pad_rows(n))
                    if feed is not None:
                        self._answered("device_resolve")
                        return adopt(feed)
            else:
                # first feed build for this line cannot consume the
                # bundle (desc/index scan): release the raw planes
                # now rather than pinning ~100 bytes/version on the
                # lineage until a delta or teardown gets there
                lineage.drop_cold()
        self._answered("rebuild" if rebuild else "upload")
        _fp_degrade("device::before_feed_upload")
        recorder = self._runner.flight_recorder
        if rebuild:
            recorder.note_feed_rebuild(rebuild)
        # an upload that brings back what the budget took, or a cold one
        after = not rebuild and cache is not None and \
            arena.reclaimed(anc, feed_key)
        with tracker.phase("feed_rebuild") if rebuild \
                else tracker.phase("feed_upload"):
            feed = self._build_flat(planes.stream(), n, planes.kinds,
                                    planes.truth_digests, planes.padded)
            nbytes = sum(int(a.nbytes) for a in feed["flat"])
            tracker.annotate(bytes=nbytes, planes=len(feed["flat"]),
                             after_eviction=after)
        if not rebuild:
            recorder.note_feed_upload(nbytes, after)
        return adopt(feed)

    def _answered(self, rung: str) -> None:
        """The rung of the ladder that had the feed: the request's
        ``device_feed`` label, and /health device_mesh.feed ``gets``."""
        tracker.label("device_feed", rung)
        self._runner.flight_recorder.note_feed_get(rung)

    @staticmethod
    def _cache_feed(bucket: dict, feed_key, feed: dict) -> None:
        """``feed`` into its anchor's bucket.  A cached feed says what
        it is cached under (``key``): a prepared record holds it to
        that slot of the bucket by identity (``_stage_tickets``)."""
        feed["key"] = feed_key
        bucket[feed_key] = feed

    @staticmethod
    def _register_digests(lineage, feed_key, feed) -> None:
        """Mirror the feed's per-plane digests into the FeedLineage's
        host-visible journal — the line-level audit record the
        supervisor reports (region_cache.py FeedLineage)."""
        if lineage is not None and feed.get("digests") is not None and \
                hasattr(lineage, "feed_digests"):
            lineage.feed_digests[feed_key] = (feed.get("lineage_v"),
                                              feed["digests"])

    def take_split_feed(self, lineage, feed_key, n: int):
        """Pop the stashed split-child feed matching this request's
        shape (one-shot, like ``take_cold``): same columns and device
        dtypes, same live row count, and the pad bucket THIS runner
        would mint — a candidate sliced under a different feed unit
        must not serve here.  Mutation races are benign: production
        and consumption both run under the owning slice's dispatch
        lock (children adopt the parent's slice)."""
        stash = getattr(lineage, "split_stash", None)
        if not stash:
            return None
        col_ids, dtypes, _ranges = feed_key
        want_pad = self.pad_rows(max(n, 1))
        for i, cand in enumerate(stash):
            f = cand["feed"]
            if cand["col_ids"] == col_ids and \
                    cand["dtypes"] == tuple(dtypes) and \
                    f.get("n_live") == n and f.get("n_pad") == want_pad:
                del stash[i]
                return dict(f)
        return None

    # ---------------------------------------------------------- the patch

    def _try_patch_feed(self, feed, lineage, planes: HostPlanes, n: int,
                        req_v, count: bool = True) -> Optional[str]:
        """Apply the lineage's dirty row spans to the device feed in
        place of a cold upload → None where it did, else why it could
        not (the keys of /health ``device_mesh.feed.rebuilds_after_
        delta``).  Only sound when the patch journal covers the gap with
        pure row patches (no repack/compaction/tombstones:
        ``structural``), positions map 1:1 (full-snapshot ascending
        feed), the padded shape is unchanged (``pad``), and every
        patched value fits the feed's established device dtypes
        (``dtype``) and NULL flags (``null``).

        The journal says WHICH rows changed; their values are read from
        the request's snapshot, which holds every row of the line at
        ``req_v``: each span is widened to a bucket length
        (``PATCH_BUCKETS``) and that window written whole, so spans of
        every length share a few update programs, and a gap of several
        generations writes each window once, as it stands at ``req_v``.
        Every window's updates are gathered first, so a refusal leaves
        before anything is dispatched; then a window is one program over
        all the feed's planes (``_patch_program``), a sharded feed's
        too."""
        patches = lineage.since(feed.get("lineage_v", -1), until=req_v)
        if patches is None or any(p.get("structural") for p in patches):
            return "structural"
        if patches and patches[-1]["n"] != n:
            return "structural"     # ranged feed: positions do not map 1:1
        n_pad = feed["n_pad"]
        if self.pad_rows(max(n, 1)) != n_pad:
            return "pad"            # row count crossed a pad bucket
        dtypes = planes.dtypes()
        flat = feed["flat"]
        digests = feed["digests"] \
            if self._runner.scrub_digests and \
            feed.get("digests") is not None else None
        windows: dict = {}
        rows = 0
        for p in patches:
            for span in p["spans"]:
                lo = span["lo"]
                m = span.get("hi", lo + len(span["handles"])) - lo
                rows += m
                for at in range(lo, lo + m, PATCH_BUCKETS[-1]):
                    width = min(patch_bucket(lo + m - at), n_pad)
                    start = max(0, min(at, n_pad - width))
                    windows[start] = max(width, windows.get(start, 0))
        with tracker.phase("feed_patch"):
            # every window's updates first, in ``flat``'s order (a
            # column's values, then its validity where it has a plane):
            # a refusal leaves before anything is dispatched
            sends = []
            for lo, width in sorted(windows.items()):
                hi = min(lo + width, n)
                updates = []
                for ci, kind in enumerate(feed["kinds"]):
                    vals, valid = planes.window(ci, kind, lo, hi)
                    dt = np.dtype(dtypes[ci])
                    if vals is None or not fits_dtype(vals, valid, dt):
                        return "dtype"
                    if not valid.all() and not feed["null_flags"][ci]:
                        # first NULL in an all-valid column would
                        # change the compile class: rebuild
                        return "null"
                    # (rows past the line's end are the pad: zeros)
                    update = np.zeros(width, dt)
                    update[:hi - lo] = vals
                    updates.append(update)
                    if feed["null_flags"][ci]:
                        mask = np.zeros(width, np.bool_)
                        mask[:hi - lo] = valid
                        updates.append(mask)
                sends.append((pack_updates(updates), np.int32(lo)))
            digests = self._device_digests(digests)
            self._warm_patch_programs(flat, digests)
            program = self._patch_program()
            for updates, lo in sends:
                flat, digests = program(flat, updates, lo, digests)
        feed["flat"] = flat
        feed["lineage_v"] = req_v
        feed["n_live"] = n
        if digests is not None:
            feed["digests"] = digests
        if count:
            self._runner.flight_recorder.note_feed_patch(
                rows, list(windows.values()), programs=len(sends))
        return None

    def _device_digests(self, digests):
        """A feed's recorded digests as the device scalars a patch or a
        compaction chains (None: a store that records none).  A build
        records the host's ints, either program leaves device scalars:
        a build's are put once, together, where its scalars lie."""
        if digests is None or all(isinstance(d, jax.Array) for d in digests):
            return digests
        return jax.device_put(
            tuple(d if isinstance(d, jax.Array) else np.uint64(d)
                  for d in digests),
            None if self._runner._single else self._runner._repl)

    def _warm_patch_programs(self, flat, digests) -> None:
        """A line's first patch runs the patch program of EVERY bucket
        once for its feed's class (over the planes as they stand, the
        result dropped), so that no later span length compiles anything:
        the classes are (bucket, the planes' dtypes in order, n_pad), a
        handful a store, and what they cost is paid by the first read
        after a line's first write."""
        r = self._runner
        n_pad = flat[0].shape[0]
        key = ("feed_patch_warm", tuple(str(a.dtype) for a in flat), n_pad)
        if key not in r._kernel_cache:
            program = self._patch_program()
            for width in {min(b, n_pad) for b in PATCH_BUCKETS}:
                program(flat, pack_updates(
                    [np.zeros(width, a.dtype) for a in flat]),
                    np.int32(0), digests)
            r._kernel_cache[key] = True

    def _patch_program(self):
        """THE patch: ONE jitted program a window over every plane of a
        feed, ``(planes, their updates packed an array a dtype, the
        window's start, their digests) → (new planes, new digests)``:
        each plane's
        ``dynamic_update_slice`` (the start is traced, so windows at
        every position share one compile class a bucket length) and its
        INCREMENTAL digest maintenance, ``R' = R - H_span(old device
        plane) + H_span(new host data)`` mod 2^64 under the scrub's
        GLOBAL position weights ``2i + 1`` (``digest_kernel``),
        summed over the window's rows alone.  Never re-hashes a whole
        plane from device state — doing so would launder any HBM
        corruption that landed since the last scrub into the recorded
        digest (the recorded value must stay anchored to the host-truth
        chain, so a pre-existing corruption delta survives
        arithmetically and the next scrub still catches it, wherever it
        sits relative to the patched span).  All device scalars —
        nothing blocks under the dispatch lock; ``digests`` None (a
        store that records none): the updates alone.  The old planes
        are not donated: launches in flight and prepared records hold
        them.  On a sharded feed GSPMD partitions the updates and the
        jit's ``out_shardings`` pin the planes to the row sharding in
        the SAME dispatch (no post-hoc device_put re-lay), the digests
        replicated."""
        r = self._runner
        fn = r._kernel_cache.get("feed_patch_fn")
        if fn is None:
            def feed_patch(planes, packed, lo, digests):
                updates = unpack_updates(planes, packed)
                new = tuple(lax.dynamic_update_slice(a, u, (lo,))
                            for a, u in zip(planes, updates))
                if digests is None:
                    return new, None
                width = updates[0].shape[0]
                w = 2 * (lo.astype(jnp.uint64) +
                         jnp.arange(width, dtype=jnp.uint64)) + 1
                return new, tuple(
                    d - jnp.sum(_to_bits(
                        lax.dynamic_slice(a, (lo,), (width,))) * w) +
                    jnp.sum(_to_bits(u) * w)
                    for a, u, d in zip(planes, updates, digests))
            fn = r._kernel_cache["feed_patch_fn"] = \
                jax.jit(feed_patch) if r._single else \
                jax.jit(feed_patch,
                        out_shardings=(r._row_sharding, r._repl))
        return fn

    # ----------------------------------------- the compaction (tombstones)

    def _try_compact_feed(self, feed, lineage, n: int, req_v) -> bool:
        """Bring a resident feed across a gap of delete-only journal
        entries ON THE DEVICE → whether it did; anywhere else the host
        rung builds it again (``get``), cause and count unchanged.

        Still a rebuild: every plane is written anew and the feed's
        positions stay the view's, so later patch spans map 1:1 and the
        kernels' inputs are what a host build would hand them; only the
        SOURCE of the rows is the resident planes, not a host copy cut
        (``_cut_dead``), padded and uploaded again.  Sound where the gap
        is exactly what that cut accepts (``_delete_only``: every entry
        says which rows it tombstoned and wrote none; the feed holds the
        whole view), the padded shape is unchanged and the feed lies on
        one device (a shift across shards is a collective).  The dead
        rows are folded into ascending runs (``dead_runs``), at most
        ``COMPACT_RUNS`` of them, and sent as ONE packed int32 array
        (``_compact_program``)."""
        r = self._runner
        if not (r._single and feed.get("positional")) or \
                self.pad_rows(max(n, 1)) != feed["n_pad"]:
            return False
        patches = lineage.since(feed.get("lineage_v", -1), until=req_v)
        n_old = feed.get("n_live", -1)
        if not patches or not _delete_only(patches, n_old, n):
            return False
        runs = dead_runs(patches)
        if len(runs) > COMPACT_RUNS:
            return False
        digests = feed.get("digests") if r.scrub_digests else None
        with tracker.phase("feed_rebuild"):
            packed = np.zeros(2 * COMPACT_RUNS + 3, np.int32)
            packed[-3:] = len(runs), n_old, n
            gone = 0
            for j, (start, length) in enumerate(runs):
                # (its start in the numbering its predecessors leave)
                packed[j], packed[COMPACT_RUNS + j] = start - gone, length
                gone += length
            flat, digests = self._compact_program()(
                feed["flat"], packed, self._device_digests(digests))
        feed["flat"] = flat
        feed["lineage_v"] = req_v
        feed["n_live"] = n
        if digests is not None:
            feed["digests"] = digests
        r.flight_recorder.note_feed_rebuild(
            "structural", source="device", rows=n_old - n)
        return True

    def _compact_program(self):
        """THE compaction: ONE jitted program over every plane of a
        feed, ``(planes, up to ``COMPACT_RUNS`` runs packed as one int32
        array: their starts (each in the numbering its predecessors
        leave), their lengths, their count, the rows before and after,
        their digests) → (new planes, new digests)``.  Each plane with
        the rows of every run removed and the rows behind them moved up
        (a masked roll a run, ``_split_plane_kernel``'s form, in a loop
        over the runs there are: 1.8 ms to ready for one run where one
        ``take`` over shifted positions takes 28, PERF.md section 6,
        PR 50; the loop's body is compiled once, a third of the code
        and of the compile of sixteen rolls laid end to end), rows at
        and past the new count zero (``_build_flat``'s pad).  Compiled
        once a feed's class (its planes' dtypes, n_pad), by the line's
        first compaction.

        The digests are CHAINED, as ``_patch_program``'s are and for its
        reason (a whole-plane re-hash would launder a corruption into
        the record): with ``t`` the first dead row, ``R' = R - H(old
        rows [t, n_old)) + H(new rows [t, n_new))`` mod 2^64 under the
        scrub's weights ``2i + 1``; rows before ``t`` did not move, so
        what the device holds less what is recorded is unchanged by the
        step and the next scrub still finds a fault that sat anywhere in
        the plane (the new rows past ``n_new`` are this program's
        zeros).  ``digests`` None: the planes alone.  No donation:
        launches in flight and prepared records hold the old planes."""
        r = self._runner
        fn = r._kernel_cache.get("feed_compact_fn")
        if fn is None:
            def feed_compact(planes, packed, digests):
                k = COMPACT_RUNS
                starts, lens = packed[:k], packed[k:2 * k]
                runs, n_old, n_new = packed[-3], packed[-2], packed[-1]
                iota = jnp.arange(planes[0].shape[0], dtype=jnp.int32)

                def drop_run(i, xs):
                    return tuple(jnp.where(iota >= starts[i],
                                           jnp.roll(x, -lens[i]), x)
                                 for x in xs)

                new = tuple(
                    jnp.where(iota < n_new, x, jnp.zeros((), x.dtype))
                    for x in lax.fori_loop(0, runs, drop_run, tuple(planes)))
                if digests is None:
                    return new, None
                w = 2 * iota.astype(jnp.uint64) + 1
                moved = iota >= starts[0]
                zero = jnp.uint64(0)
                return new, tuple(
                    d - jnp.sum(jnp.where(moved & (iota < n_old),
                                          _to_bits(a) * w, zero)) +
                    jnp.sum(jnp.where(moved, _to_bits(b) * w, zero))
                    for a, b, d in zip(planes, new, digests))
            fn = r._kernel_cache["feed_compact_fn"] = jax.jit(
                named_program(feed_compact, "feed_compact"))
        return fn

    def dus(self, arr, update, lo: int):
        """Jitted slice update of ONE plane (dynamic_update_slice; the
        start index is traced, so updates at different positions share
        one compile class per update length): what the device MVCC
        resolve (device/mvcc.py) writes its planes with.  On a sharded
        plane the jit's ``out_shardings`` pins the result to the row
        sharding in the same dispatch."""
        r = self._runner
        fn = r._kernel_cache.get("feed_dus_fn")
        if fn is None:
            def feed_dus(a, u, i):
                return lax.dynamic_update_slice(a, u, (i,))
            fn = r._kernel_cache["feed_dus_fn"] = \
                jax.jit(feed_dus) if r._single else \
                jax.jit(feed_dus, out_shardings=r._row_sharding)
        return fn(arr, update, jnp.asarray(lo, jnp.int32))

    # -------------------------------------------------------- the digests
    #
    # The device half of device/supervisor.py's scrub: the on-device
    # digest leaf the scrubber re-hashes resident planes with, and the
    # fault its chaos arm injects.

    def digest_kernel(self, dtype, n_pad: int):
        """Jitted digest of a plane's live prefix, rows [0, n), with
        GLOBAL position weights: sum bits(x[i]) * (2i+1) mod 2^64 — the
        device half of the scrub formula (host half:
        supervisor.host_plane_digest; a patch chains the same sum over
        its window's rows: ``_patch_program``).  Cached per (dtype,
        n_pad) like every other kernel; on a sharded feed GSPMD
        partitions the reduction."""
        key = ("scrubr", str(np.dtype(dtype)), n_pad)
        cache = self._runner._kernel_cache
        fn = cache.get(key)
        if fn is None:
            def feed_digest(x, n_arr):
                iota = jnp.arange(n_pad, dtype=jnp.uint64)
                return jnp.sum(jnp.where(
                    iota < n_arr.astype(jnp.uint64),
                    _to_bits(x) * (2 * iota + 1), jnp.uint64(0)))

            fn = cache[key] = jax.jit(feed_digest)
        return fn

    def device_digest(self, arr, n: int):
        """Digest of one resident plane's live prefix (device scalar —
        the caller decides when to sync).  Deliberately avoids the
        LRU scalar cache: the background scrubber calls this OUTSIDE
        the dispatch lock, and the OrderedDict's move_to_end/popitem
        is not safe against concurrent request threads."""
        return self.digest_kernel(arr.dtype, arr.shape[0])(
            arr, jnp.asarray(n, jnp.int64))

    @staticmethod
    def corrupt_resident_plane(feed: dict) -> None:
        """Fault injection (device::feed_corrupt): flip one element of
        the first resident plane (a value plane: never bool) in place of
        the HBM bit-flip a real device fault would cause.  Test/chaos
        surface only."""
        arr = feed["flat"][0]
        dt = np.dtype(arr.dtype)
        if dt.kind in "iu":
            bad = arr.at[0].set(arr[0] ^ 1)     # single-bit flip
        else:
            # floats: a true single-BIT flip via bitcast → xor 1
            u = lax.bitcast_convert_type(
                arr, _UINT_BY_ITEMSIZE[dt.itemsize])
            bad = lax.bitcast_convert_type(u.at[0].set(u[0] ^ 1),
                                           arr.dtype)
        feed["flat"] = (bad,) + feed["flat"][1:]

    # ------------------------------------------- the move and the split
    #
    # Elastic stress without the host link: a placement move, a
    # quarantine drain, or a co-location pull copies the resident
    # feed between slices over the device interconnect (device_put
    # across the mesh) instead of dropping it and re-minting from
    # host truth; a region split slices the parent feed by key range
    # on device into two child feeds.  Both re-verify against the
    # scrub-digest chain before anything serves.

    def extract_feeds(self, anchor):
        """→ (migratable feeds by key, skipped count) for an ICI move
        of ``anchor`` off this slice, or (None, 0) when nothing can
        travel.  Only feeds carrying scrub digests are migratable —
        the destination re-verifies on arrival, and a feed that
        cannot be verified must re-mint from host truth instead of
        serving unaudited (skipped counts those).  Snapshot under the
        dispatch lock: (flat, digests) pairs update non-atomically on
        the patch path."""
        r = self._runner
        if not r._single:
            return None, 0
        bucket = r._arena.bucket(anchor, create=False)
        if not bucket:
            return None, 0
        out = {}
        skipped = 0
        with r._dispatch_mu:
            for k, v in bucket.items():
                if not (isinstance(v, dict) and "flat" in v):
                    continue
                if v.get("digests") is None:
                    skipped += 1
                    continue
                out[k] = dict(v)
        return (out or None), skipped

    def install_feeds(self, anchor, feeds: dict) -> str:
        """Arrival side of an ICI feed migration → ``"moved"`` or
        ``"corrupt"``.  Each plane is device_put onto this slice and
        re-hashed against the digests that traveled with it BEFORE
        anything installs — a plane diverging mid-flight (ICI fault,
        HBM corruption on either end; chaos arms
        ``device::feed_migrate``) quarantines-and-rebuilds, never
        serves silently corrupt.  A feed the destination already
        holds at the same or newer lineage generation is never
        clobbered (a request raced the move and re-minted)."""
        from ..utils.failpoint import fail_point
        r = self._runner
        dev = r._mesh.devices.flat[0]
        installed = {}
        for fkey, feed in feeds.items():
            nf = dict(feed, flat=tuple(jax.device_put(a, dev)
                                       for a in feed["flat"]))
            if fail_point("device::feed_migrate") is not None:
                # the injected mid-transfer fault: one bit flips on a
                # transferred plane; the verify below must catch it
                self.corrupt_resident_plane(nf)
            n = feed.get("n_live", 0)
            arrived = []
            for arr, want in zip(nf["flat"], feed["digests"]):
                got = int(np.asarray(self.device_digest(arr, n)))
                if got != int(np.asarray(want)):
                    return "corrupt"
                arrived.append(got)
            # the digest chain must live where its planes live: a
            # scalar still committed to the SOURCE slice would turn
            # the next incremental patch into a cross-device subtract
            nf["digests"] = tuple(
                jax.device_put(jnp.asarray(w, jnp.uint64), dev)
                for w in arrived)
            installed[fkey] = nf
            self._warm_digest_kernels(nf["flat"])
        with r._dispatch_mu:
            bucket = r._arena.bucket(anchor)
            if bucket is None:
                return "corrupt"    # untrackable anchor: caller re-mints
            for fkey, nf in installed.items():
                cur = bucket.get(fkey)
                if isinstance(cur, dict) and \
                        cur.get("lineage_v") is not None and \
                        nf.get("lineage_v") is not None and \
                        cur["lineage_v"] >= nf["lineage_v"]:
                    continue
                self._cache_feed(bucket, fkey, nf)
                self._register_digests(anchor, fkey, nf)
            r._arena.admit(anchor)
        return "moved"

    def _split_plane_kernel(self, dtype, n_pad_parent: int,
                            n_pad_child: int, right: bool):
        """Jitted key-range slice of one resident plane into a split
        child: left takes rows [0, pos), right takes [pos, pos+n) via
        a roll — the split position is traced, so every split of the
        same (side, dtype, pad buckets) shares one compile class.
        Rows past the child's live count zero out (padding invariant,
        matching _build_flat's host zeros)."""
        dt = np.dtype(dtype)
        key = ("splitp", bool(right), str(dt), n_pad_parent, n_pad_child)
        cache = self._runner._kernel_cache
        fn = cache.get(key)
        if fn is None:
            def kern(x, pos, n_child):
                y = (jnp.roll(x, -pos) if right else x)[:n_pad_child]
                iota = jnp.arange(n_pad_child)
                return jnp.where(iota < n_child, y,
                                 jnp.zeros((), y.dtype))
            fn = cache[key] = jax.jit(named_program(kern, "device_split"))
        return fn

    def split_resident_feeds(self, spec) -> str:
        """Device-side region split of every resident feed anchored on
        the parent lineage (``spec`` from RegionColumnarCache
        .split_lines) → ``"split"`` when at least one child feed was
        minted on device, else ``"none"``.  Fans out to whichever
        runner holds the parent's bucket (placement slice, degraded
        submesh, or this store's)."""
        parent = spec["parent_lineage"]
        for r in [self._runner] + self._runner._sub_runners():
            bucket = r._arena.bucket(parent, create=False)
            if bucket:
                return r._feeds._split_local_feeds(bucket, spec)
        return "none"

    def _split_local_feeds(self, bucket, spec) -> str:
        """Slice this runner's resident parent feeds into split-child
        candidates, stashed on the child lineages for their first
        request to consume (``take_split_feed``).  Child digests are
        recomputed from the children's HOST state — never derived
        from device planes, so a corruption that landed on the parent
        since its last scrub fails the verify here instead of
        laundering into the child's recorded chain."""
        if not self._runner._single:
            return "none"       # sharded whole-mesh feeds re-mint
        out = "none"
        with self._runner._dispatch_mu:
            for fkey, feed in list(bucket.items()):
                if not (isinstance(feed, dict) and "flat" in feed):
                    continue
                if not feed.get("positional") or \
                        feed.get("pk_flags") is None or \
                        feed.get("digests") is None:
                    continue
                if feed.get("lineage_v") != spec["parent_version"] or \
                        feed.get("n_live") != spec["n_parent"]:
                    continue    # stale generation: positions lie
                for side in ("left", "right"):
                    child = spec.get(side)
                    if child is None or child["n"] <= 0:
                        continue
                    cf = self._mint_split_child(feed, fkey, spec, child,
                                                right=(side == "right"))
                    if cf is not None:
                        stash = getattr(child["lineage"], "split_stash",
                                        None)
                        if stash is None:
                            stash = child["lineage"].split_stash = []
                        stash.append({"col_ids": fkey[0],
                                      "dtypes": tuple(fkey[1]),
                                      "feed": cf})
                        out = "split"
        return out

    def _mint_split_child(self, feed, fkey, spec, child, right: bool):
        """One child feed: slice every parent plane on device, anchor
        the child's digest chain to its host truth, and verify the
        sliced planes against it (the split's arrival verify) — or
        None when anything diverges (that child re-uploads)."""
        if any(feed["kinds"]):
            # the child's host truth is read here as the columns lie
            # (``plane_values`` is not applied): a line with a date or
            # code plane re-uploads (ROADMAP D17)
            return None
        n_child = child["n"]
        n_pad_child = self.pad_rows(max(n_child, 1))
        parent_pad = feed["n_pad"]
        if n_pad_child > parent_pad:
            return None
        state = child["state"]
        pairs = []              # the child's host truth, plane by plane
        for ci, pk in enumerate(feed["pk_flags"]):
            bufs = (state.handles, None) if pk \
                else state.cols.get(fkey[0][ci])
            if bufs is None:
                return None
            valid = bufs[1][:n_child] if bufs[1] is not None \
                else np.ones(n_child, np.bool_)
            pairs.append((np.ascontiguousarray(bufs[0][:n_child].astype(
                np.dtype(fkey[1][ci]), copy=False)),
                np.ascontiguousarray(valid)))
        pos_arr = jnp.asarray(spec["pos"], jnp.int32)
        n_arr = jnp.asarray(n_child, jnp.int32)
        cf = self.make_feed([self._split_plane_kernel(
            a.dtype, parent_pad, n_pad_child, right)(a, pos_arr, n_arr)
            for a in feed["flat"]], feed["null_flags"], n_pad_child,
            feed["kinds"], pairs, n_child)
        # the split's arrival verify (a store that records no digests
        # has nothing to hold the slices to)
        if cf.get("digests") is None or any(
                int(np.asarray(self.device_digest(a, n_child))) != want
                for a, want in zip(cf["flat"], cf["digests"])):
            return None
        cf.update(lineage_v=child["lineage"].version, positional=True,
                  pk_flags=feed["pk_flags"])
        return cf
