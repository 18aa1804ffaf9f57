"""Device-state supervisor — bounded, lifecycle-correct, audited HBM.

PRs 4-5 made the device path fast by keeping derived state resident:
lineage-anchored HBM feeds, patch journals, compile-class caches.  This
module defends that state along three axes the reference treats as
table stakes for any cache layered over a log (the region-cache memory
engine + ARIES-style verify-derived-state-against-the-source recovery
discipline, PAPERS.md):

- **bounded** — :class:`FeedArena` owns every device-resident feed
  explicitly (no GC-timing-dependent ``WeakKeyDictionary`` reclamation):
  per-anchor byte accounting, a configurable HBM budget, and
  frequency+recency eviction that never evicts a line pinned by an
  in-flight deferred dispatch.  ``device::hbm_oom`` squeezes the
  effective budget for fault injection.

- **lifecycle-correct** — :class:`DeviceStateSupervisor` registers on
  the raftstore's CoprocessorHost: split/merge/epoch change
  (``on_region_changed``), snapshot apply (``on_data_replaced``) and
  peer destroy (``on_peer_destroyed``) eagerly invalidate the matching
  ``RegionColumnarCache`` lines, whose retirement callback drops the
  device feeds — stale-epoch state is torn down at the event, not aged
  out.  Role flips (``on_role_change``) instead drive the REPLICA-FEED
  state machine: a demoted leader's lines stay resident as follower
  feeds (same delta stream patches them; the resolved-ts gate serves
  them), and a leader gain over a warm feed is a PROMOTION — a
  scrub-digest re-verify, never a ``columnar_build``.

- **audited** — per-plane content digests recorded at feed build/patch
  time (position-weighted sums, odd weights so any single-element
  corruption is detected) are re-checked by a low-priority scrubber
  that re-hashes the resident planes ON DEVICE and compares.  On
  divergence the line is quarantined: its feeds drop, the next request
  for that region serves from the host backend, and the one after
  rebuilds a fresh feed from host truth.  ``device::feed_corrupt``
  injects the bit-flip the scrubber exists to catch.

- **failure-domain-aware** — :class:`SliceHealth` /
  :class:`SliceHealthBoard` treat each mesh slice (one chip) the way
  the store-level slow-score loop treats a store: dispatch faults,
  fetch faults, scrub quarantines and launch-latency outliers strike a
  per-slice score that decays on success; a slice crossing the trip
  threshold is QUARANTINED — placement stops scoring it, its sticky
  anchors drain onto healthy slices, whole-mesh sharded feeds rebuild
  on the largest healthy submesh (``parallel.mesh.healthy_submesh``)
  — and a half-open canary probe re-admits it with score decay, never
  a thundering re-pin.  ``device::slice_dead`` injects the persistent
  chip death this machinery exists to survive.

This module imports no jax at module scope — a Node without a device
runner can host the supervisor (it still drives columnar cache
lifecycle teardown) without paying the accelerator runtime import.
"""

from __future__ import annotations

import math
import threading
import time
import weakref
from typing import Optional

import numpy as np

from ..raftstore.observer import Observer
from ..utils.failpoint import fail_point


# ----------------------------------------------------------- digests
#
# digest(plane, n) = sum_{i<n} (bits(plane[i]) * (2i+1)) mod 2^64.
# Odd weights make every single-position change detectable: a delta d
# at position i shifts the digest by d*(2i+1) mod 2^64, which is zero
# only when d = 0 (an odd factor cannot supply the 2^64's powers of
# two).  The same formula runs host-side (numpy, recorded at upload
# from the host truth) and device-side (the runner's jitted scrub
# kernel, recomputed after in-place patches and during scrub passes).


def host_plane_digest(arr: np.ndarray, n: int) -> int:
    """Host reference digest over the live prefix of one feed plane."""
    a = np.ascontiguousarray(arr[:n])
    if a.dtype == np.bool_:
        u = a.astype(np.uint64)
    else:
        u = a.view(np.dtype(f"u{a.dtype.itemsize}")).astype(np.uint64)
    idx = np.arange(n, dtype=np.uint64)
    with np.errstate(over="ignore"):
        return int((u * (2 * idx + 1)).sum(dtype=np.uint64))


def _bucket_arrays(bucket: dict):
    """Device arrays held by one anchor's cache bucket: feed planes plus
    cached sparse-slot columns inside request memos."""
    for v in list(bucket.values()):     # status threads race inserts
        if not isinstance(v, dict):
            continue
        yield from v.get("flat", ())
        ss = v.get("sparse_slots")
        if ss is not None:
            yield ss[3]


def _bucket_nbytes(bucket: dict) -> int:
    return sum(int(getattr(a, "nbytes", 0))
               for a in _bucket_arrays(bucket))


def _bucket_has_feed(bucket: dict) -> bool:
    return any(isinstance(v, dict) and "flat" in v
               for v in list(bucket.values()))


# what of a request memo holds device bytes or refers to a feed's: the
# sparse-slot column (with the host's recode it belongs to) and the
# prepared record (device/request.py ``_Prepared``)
_MEMO_DEVICE_FIELDS = ("sparse_slots", "prepared")


def _bucket_release(bucket: dict) -> tuple:
    """Take out of one line's bucket what the budget counts and what
    refers to it: its feeds, and of each request memo the fields of
    ``_MEMO_DEVICE_FIELDS``.  What a memo derived on the HOST stays
    (row count, dtypes, limbs, bounds, key grid, host planes, their
    padded buffers and digests): it costs no HBM and a re-upload needs
    all of it.  A memo is never changed in place (a staging on the dispatcher may hold it while a
    completion thread's unpin sweeps): a copy without those fields
    takes its slot, and the staging keeps the old one to itself, as it
    kept the whole bucket when an eviction popped the entry.
    → (the feed keys released, whether a memo is left)."""
    gone, kept = [], False
    for k, v in list(bucket.items()):
        if not isinstance(v, dict):
            continue
        if "flat" in v:
            del bucket[k]
            gone.append(k)
            continue
        if any(f in v for f in _MEMO_DEVICE_FIELDS):
            v = dict(v)
            for f in _MEMO_DEVICE_FIELDS:
                v.pop(f, None)
            bucket[k] = v
        kept = kept or bool(v)
    return gone, kept


# ----------------------------------------------------- flight recorder

DEFAULT_FLIGHT_RECORDER_DEPTH = 256

# Why a staging was not a lane staged from its group's ticket
# (``FlightRecorder.note_tickets``; /health device_mesh.prepared
# ``ticket_misses``): ``none``: it had no ticket (no record under its own
# plan, ranges and line: a cold or refreshed line, another plan kind, a
# mesh); ``tile``: none, and its ranges cover part of the region;
# ``generation``: the memo no longer stands at the request's generation
# with THAT record (a write since, or an older-generation read);
# ``feed`` / ``kernel``: the record's guards, as ``prepared.drops``
# names them (``kernel`` also a launch from the record that failed);
# ``gate``: a quarantined slice or line, or a memo forced to the host.
TICKET_MISSES = ("none", "tile", "generation", "feed", "kernel", "gate")

# The rungs of ``FeedStore.get``'s ladder (device/feed.py), as the
# ``device_feed`` label names them and /health device_mesh.feed ``gets``
# counts them.
FEED_RUNGS = ("hit", "patch", "compact", "split", "device_resolve",
              "rebuild", "upload")


class FlightRecorder:
    """Bounded ring of recent device launches — the black box an
    operator (or the /debug/trace surface) reads after a latency spike:
    per-launch wall, compile class, whether this launch was the class's
    FIRST (compile-vs-cached — the difference between a 0.6ms warm
    enqueue and a multi-second XLA compile), the shape and device count
    of the mesh the launch ran on, slice id for placement-routed
    launches, and arena-pinned bytes at dispatch.

    One recorder per PHYSICAL runner: placement slices and degraded
    submesh sub-runners share their parent's ring (their entries carry
    the slice id), so the box records the whole chip's launch history
    in order.  Entries feed the ``device_dispatch`` span's attributes,
    so a trace's launch carries its flight record inline.
    """

    CLASS_SEEN_MAX = 4096       # first-launch memory (LRU-bounded)

    def __init__(self, depth: int = DEFAULT_FLIGHT_RECORDER_DEPTH):
        from collections import OrderedDict, deque
        self._mu = threading.Lock()
        self._ring: "deque" = deque(maxlen=max(1, int(depth)))
        self._seen: "OrderedDict" = OrderedDict()
        self.launches = 0
        self.first_launches = 0
        self.faults = 0
        # launches that ran on every device of the physical runner's
        # configured mesh (not on a placement slice, not on a degraded
        # submesh): what /health device_mesh reports beside ``launches``
        self.sharded_launches = 0
        # Pallas accumulators finalized (device/aggregate.py
        # finalize_packed: a GROUP BY's grid, or the one slot of an
        # aggregation without), by what ran: the one native call that
        # holds the GIL, or the numpy chain it falls back to
        self.finalize_native = 0
        self.finalize_numpy = 0
        # look-ups of the runners' cached device scalars
        # (DeviceRunner._scalar_cache_get), by whether the value was
        # already on the device: a warm launch only hits
        self.scalar_hits = 0
        self.scalar_uploads = 0
        # aggregations whose constants are kernel OPERANDS
        # (device/aggregate.py agg_params): launches that carried some,
        # const-blind kernel entries built (one serves every constant
        # tuple of its class), and the scaled-DECIMAL / int32-date
        # planes cut for feeds (device/lowering.py), by kind
        self.param_launches = 0
        self.const_classes = 0
        self.planes = {"decimal": 0, "date": 0, "code": 0}
        # what the fused kernel's launches were made of: those whose
        # GROUP BY had several keys (the composite key), the SUMs they
        # summed as 16-bit limbs, the byte planes they contracted and
        # the slots of the grids they contracted them over (the kernel's
        # time follows rows x planes x sublanes of slots)
        self.composite_key_launches = 0
        self.limb_sums = 0
        self.planes_sum = 0
        self.slots_sum = 0
        # warm whole-feed Pallas launches staged from their class's
        # prepared record (device/request.py ``_Prepared``): lanes that
        # left staged from one (``hits``), records written, and records
        # dropped, by what a request found changed
        self.prepared_hits = 0
        self.prepared_builds = 0
        self.prepared_drops = {"refresh": 0, "feed": 0, "kernel": 0}
        # lanes staged from their group's ticket (runner.py
        # _stage_tickets), and stagings that were not, by cause
        self.ticket_hits = 0
        self.ticket_misses = dict.fromkeys(TICKET_MISSES, 0)
        # device/feed.py ``roll_derived``: request memos whose derived
        # record a write was rolled across, kept (every constant proved
        # again) or dropped, by what the written rows left; and the
        # host planes such a memo held: kept with the tombstones they
        # lag by noted beside them (``deferred``), cut to the rows those
        # left where someone read them (``cut``), or dropped
        self.memo_kept = 0
        self.memo_dropped = {"unknown": 0, "dtype": 0, "code": 0,
                             "null_key": 0, "limbs": 0, "widths": 0,
                             "key": 0}
        self.memo_host_planes = {"cut": 0, "dropped": 0, "deferred": 0}
        # device/feed.py: resident feeds brought forward by a patch
        # after a write, and those built again instead, by cause and by
        # where the rows came from (the host's planes; the resident
        # feed itself, compacted: the dead rows it removed and the
        # device programs that did)
        self.feed_patches = 0
        self.feed_patch_rows = 0
        self.feed_patch_windows = 0
        self.feed_patch_programs = 0
        self.feed_patch_buckets: dict = {}
        self.feed_rebuilds = {"structural": 0, "pad": 0, "dtype": 0,
                              "null": 0}
        self.feed_rebuild_source = {"device": 0, "host": 0}
        self.feed_compact_rows = 0
        # device/feed.py ``FeedStore.get``'s answers by rung (the
        # ``device_feed`` label, counted; a lane staged from its
        # prepared record is a ``hit``), and its uploads: of a feed the
        # arena never held (``cold``) or had held and released under
        # its budget (``evicted``), and the bytes of their planes
        self.feed_gets = dict.fromkeys(FEED_RUNGS, 0)
        self.feed_uploads = {"cold": 0, "evicted": 0, "bytes": 0}
        # cumulative measured launch wall: the resource-metering
        # attribution-coverage denominator (every _dispatch_phase wall
        # lands both here and in the RU recorder — charged wall /
        # recorded wall is the ≥95% acceptance figure)
        self.wall_s_total = 0.0

    def note(self, klass: str, key=None, wall_s: float = 0.0,
             mesh: str = "", slice_id=None, pinned_bytes: int = 0,
             ok: bool = True, shards: int = 1,
             whole_mesh: bool = False, params: int = 0,
             slot_mode: str = "", keys: int = 0, planes: int = 0,
             limb_sums: int = 0, slots: int = 0,
             block_rows: int = 0, prepared: int = 0) -> dict:
        ck = (klass, key)
        with self._mu:
            first = ck not in self._seen
            self._seen[ck] = True
            self._seen.move_to_end(ck)
            while len(self._seen) > self.CLASS_SEEN_MAX:
                self._seen.popitem(last=False)
            self.launches += 1
            self.wall_s_total += wall_s
            if first:
                self.first_launches += 1
            if not ok:
                self.faults += 1
            if whole_mesh:
                self.sharded_launches += 1
            if params:
                self.param_launches += 1
            if keys > 1:
                self.composite_key_launches += 1
            self.limb_sums += limb_sums
            self.planes_sum += planes
            self.slots_sum += slots
            if ok:
                self.prepared_hits += prepared
            entry = {"t_unix_s": round(time.time(), 6),
                     "launch_ms": round(wall_s * 1e3, 3),
                     "compile_class": klass,
                     "first_launch": first,
                     "mesh": mesh,
                     "shards": int(shards),
                     "slice": slice_id,
                     "pinned_bytes": int(pinned_bytes),
                     # constants the launch carried as operands, and
                     # the Pallas kernel's slot mode ("" elsewhere)
                     "params": int(params),
                     "slot_mode": slot_mode,
                     # its GROUP BY keys and the byte planes it
                     # contracted (0 off the fused kernel)
                     "keys": int(keys),
                     "planes": int(planes),
                     # the slot grid it contracted them over and the
                     # rows a grid step took (the step follows the grid)
                     "slots": int(slots),
                     "block_rows": int(block_rows),
                     # its lanes staged from a prepared record alone
                     "prepared": int(prepared),
                     "ok": ok}
            self._ring.append(entry)
        return entry

    def note_finalize(self, native: bool) -> None:
        with self._mu:
            if native:
                self.finalize_native += 1
            else:
                self.finalize_numpy += 1

    def note_plane(self, kind: str) -> None:
        with self._mu:
            self.planes[kind] += 1

    def note_const_class(self) -> None:
        with self._mu:
            self.const_classes += 1

    def agg_param_counts(self) -> dict:
        with self._mu:
            return {"param_launches": self.param_launches,
                    "const_classes": self.const_classes,
                    "decimal_planes": self.planes["decimal"],
                    "date_planes": self.planes["date"],
                    "code_planes": self.planes["code"],
                    "composite_key_launches": self.composite_key_launches,
                    "limb_sums": self.limb_sums,
                    "planes_sum": self.planes_sum,
                    "slots_sum": self.slots_sum}

    def note_prepared(self, event: str) -> None:
        """A prepared record written (``builds``) or dropped
        (``refresh`` / ``feed`` / ``kernel``: the generation moved, the
        arena no longer holds the feed it was cut from, the kernel
        cache no longer holds its entry)."""
        with self._mu:
            if event == "builds":
                self.prepared_builds += 1
            else:
                self.prepared_drops[event] += 1

    def note_tickets(self, hits: int = 0, miss: Optional[str] = None) -> None:
        """Lanes staged from a ticket (once a hold), or one staging
        that was not, by its cause (``TICKET_MISSES``)."""
        with self._mu:
            self.ticket_hits += hits
            if miss is not None:
                self.ticket_misses[miss] += 1

    def prepared_counts(self) -> dict:
        with self._mu:
            return {"hits": self.prepared_hits,
                    "builds": self.prepared_builds,
                    "drops": dict(self.prepared_drops),
                    "ticket_hits": self.ticket_hits,
                    "ticket_misses": dict(self.ticket_misses)}

    def note_memo(self, cause: Optional[str],
                  planes: Optional[str] = None) -> None:
        """A request memo's derived record rolled across a write: kept
        (``cause`` None) or dropped because an entry did not say what
        it did (``unknown``) or a written row left a plane's dtype
        (``dtype``), has a CHAR value without a code (``code``), a NULL
        in a composite key (``null_key``), moved the limb split
        (``limbs``), a byte-plane width (``widths``) or the key grid
        (``key``); and what became of the host ``planes`` it held
        (None: it held none): ``deferred`` or ``dropped``."""
        with self._mu:
            if cause is None:
                self.memo_kept += 1
            else:
                self.memo_dropped[cause] += 1
            if planes is not None:
                self.memo_host_planes[planes] += 1

    def note_planes_cut(self) -> None:
        """A memo's host planes READ after delete-only writes (feed.py
        ``HostPlanes._held``) and cut to the rows those left."""
        with self._mu:
            self.memo_host_planes["cut"] += 1

    def memo_counts(self) -> dict:
        with self._mu:
            return {"kept": self.memo_kept,
                    "dropped": dict(self.memo_dropped),
                    "host_planes": dict(self.memo_host_planes)}

    def note_feed_patch(self, rows: int, widths, programs: int) -> None:
        """A resident feed patched forward: the journal's dirty
        ``rows``, sent as windows of these bucket ``widths`` by that
        many device ``programs`` (one a window)."""
        with self._mu:
            self.feed_patches += 1
            self.feed_patch_rows += rows
            self.feed_patch_windows += len(widths)
            self.feed_patch_programs += programs
            for w in widths:
                self.feed_patch_buckets[w] = \
                    self.feed_patch_buckets.get(w, 0) + 1

    def note_feed_rebuild(self, why: str, source: str = "host",
                          rows: int = 0) -> None:
        """A resident feed a write left behind built again:
        ``structural`` (tombstones, a repack, a journal gap), ``pad``
        (the row count crossed a pad bucket), ``dtype`` / ``null`` (a
        value outside the feed's dtypes / its first NULL); from the
        line's ``host`` planes, or, after tombstones alone, from the
        resident feed itself (``device``: its dead ``rows`` removed by
        one program)."""
        with self._mu:
            self.feed_rebuilds[why] += 1
            self.feed_rebuild_source[source] += 1
            self.feed_compact_rows += rows

    def note_feed_get(self, rung: str, n: int = 1) -> None:
        with self._mu:
            self.feed_gets[rung] += n

    def note_feed_upload(self, nbytes: int, after_eviction: bool) -> None:
        with self._mu:
            self.feed_uploads["evicted" if after_eviction else "cold"] += 1
            self.feed_uploads["bytes"] += nbytes

    def feed_counts(self) -> dict:
        """/health ``device_mesh.feed``; ``after_delta`` = ``patches`` +
        every rebuild: what a read found a write had left behind."""
        with self._mu:
            rebuilds = dict(self.feed_rebuilds)
            return {"gets": dict(self.feed_gets),
                    "uploads": dict(self.feed_uploads),
                    "patches": self.feed_patches,
                    "patch_rows": self.feed_patch_rows,
                    "patch_windows": self.feed_patch_windows,
                    "patch_programs": self.feed_patch_programs,
                    "patch_buckets": {str(w): c for w, c in sorted(
                        self.feed_patch_buckets.items())},
                    "rebuilds_after_delta": rebuilds,
                    "rebuild_source": dict(self.feed_rebuild_source),
                    "compact_rows": self.feed_compact_rows,
                    # (one program a compaction)
                    "compact_programs": self.feed_rebuild_source["device"],
                    "after_delta": self.feed_patches +
                    sum(rebuilds.values())}

    def note_scalar(self, hit: bool) -> None:
        with self._mu:
            if hit:
                self.scalar_hits += 1
            else:
                self.scalar_uploads += 1

    def scalar_counts(self) -> dict:
        with self._mu:
            return {"hits": self.scalar_hits,
                    "uploads": self.scalar_uploads}

    def finalize_counts(self) -> dict:
        with self._mu:
            return {"native": self.finalize_native,
                    "numpy": self.finalize_numpy}

    def set_depth(self, depth: int) -> None:
        """Online-resize the ring, keeping the newest tail."""
        from collections import deque
        with self._mu:
            self._ring = deque(self._ring, maxlen=max(1, int(depth)))

    def items(self, limit: int = 0) -> list:
        with self._mu:
            out = list(self._ring)
        return out[-limit:] if limit > 0 else out

    def stats(self) -> dict:
        with self._mu:
            return {"depth": self._ring.maxlen,
                    "recorded": len(self._ring),
                    "launches": self.launches,
                    "first_launches": self.first_launches,
                    "faults": self.faults,
                    "sharded_launches": self.sharded_launches,
                    "wall_s_total": self.wall_s_total}


# ------------------------------------------------- slice failure domains
#
# The store-level control loop (utils/health.py SlowScore rise/decay +
# CircuitBreaker trip/half-open, pd/scheduler.py evict-slow-store) one
# level down: each mesh slice — one chip — is a failure domain.  The
# board is deliberately DUMB policy-wise: it scores, trips and gates
# probes; the consumers (SlicePlacer drain/exclusion, DeviceRunner's
# elastic mesh degrade) read ``quarantined_set()`` and act.

# strikes to quarantine.  1.0 per dispatch/fetch fault or scrub
# quarantine, 0.25 per launch-latency outlier; each clean fetch decays
# the score by 0.5 — a healthy slice absorbs isolated faults, a dead
# chip trips within three requests.
DEFAULT_TRIP_STRIKES = 3.0
# half-open probe cooldown after a trip (and after a failed probe)
DEFAULT_PROBE_COOLDOWN_S = 0.25

# live boards, for the tier-1 leak guard (tests/conftest.py): a test
# must not leave a slice quarantined behind for the next test to trip
# over.  WeakSet: boards die with their runners.
_LIVE_BOARDS: "weakref.WeakSet" = weakref.WeakSet()


def live_boards() -> list:
    """Snapshot of every live SliceHealthBoard (conftest leak guard)."""
    return list(_LIVE_BOARDS)


class SliceHealth:
    """Strike/recovery health score for ONE mesh slice.

    State machine (the trip/drain/probe cycle, README "Device failure
    domains"):

      healthy --(score >= trip)--> quarantined
      quarantined --(cooldown, one canary at a time)--> probing
      probing --success--> healthy (score decayed to trip-1, so the
                           placement penalty stays high and re-pinning
                           is gradual — never a thundering herd)
      probing --failure--> quarantined (cooldown restarts)

    Fault feeds: dispatch faults, fetch faults, scrub quarantines
    (weight 1.0) and launch-latency outliers (weight 0.25, only when
    the owner configures ``latency_outlier_s``).  Success decays the
    score by 0.5 — the SlowScore rise-fast/decay-slow discipline.
    """

    __slots__ = ("idx", "_mu", "score", "state", "trip_strikes",
                 "cooldown_s", "latency_outlier_s", "strikes", "trips",
                 "readmits", "refusals", "probe_failures",
                 "launched_quarantined", "_opened_at", "_probe_inflight")

    def __init__(self, idx: int,
                 trip_strikes: float = DEFAULT_TRIP_STRIKES,
                 cooldown_s: float = DEFAULT_PROBE_COOLDOWN_S,
                 latency_outlier_s: Optional[float] = None):
        self.idx = idx
        self._mu = threading.Lock()
        self.score = 0.0
        self.state = "healthy"          # healthy | quarantined
        self.trip_strikes = trip_strikes
        self.cooldown_s = cooldown_s
        self.latency_outlier_s = latency_outlier_s
        self.strikes: dict = {}
        self.trips = 0
        self.readmits = 0
        # dispatches REFUSED because the slice was quarantined (the
        # request degraded/rescued instead of launching on a dead chip)
        self.refusals = 0
        self.probe_failures = 0
        # dispatches that LAUNCHED while quarantined — the invariant
        # chaos asserts stays zero (check_no_quarantined_dispatch)
        self.launched_quarantined = 0
        self._opened_at = 0.0
        self._probe_inflight = False

    # -- fault/success feeds ------------------------------------------

    def note_fault(self, kind: str, weight: float = 1.0) -> bool:
        """One strike; → True when this strike TRIPPED the slice."""
        with self._mu:
            self.strikes[kind] = self.strikes.get(kind, 0) + 1
            self.score += weight
            return self._maybe_trip_locked()

    def trip(self, kind: str) -> bool:
        """Decisive quarantine (a targeted persistent chip death needs
        no three-strike deliberation); → True on the transition."""
        with self._mu:
            self.strikes[kind] = self.strikes.get(kind, 0) + 1
            self.score = max(self.score, self.trip_strikes)
            return self._maybe_trip_locked()

    def _maybe_trip_locked(self) -> bool:
        if self.state != "healthy" or self.score < self.trip_strikes:
            return False
        self.state = "quarantined"
        self.trips += 1
        self._opened_at = time.monotonic()
        self._probe_inflight = False
        return True

    def note_ok(self, latency_s: Optional[float] = None) -> bool:
        """A served request: decay the score — or strike fractionally
        when the launch latency is an outlier (the fail-slow feed).
        → True when the outlier strike TRIPPED the slice (the caller
        must fire the board's trip listeners, exactly as for
        note_fault — a latency-induced quarantine drains like any
        other)."""
        # a threshold of None OR <= 0 disables the latency feed (the
        # config default is 0.0 = off — cold compiles on slow
        # transports must never strike a healthy slice)
        if latency_s is not None and self.latency_outlier_s and \
                self.latency_outlier_s > 0 and \
                latency_s >= self.latency_outlier_s:
            return self.note_fault("latency", weight=0.25)
        with self._mu:
            if self.state == "healthy":
                self.score = max(0.0, self.score - 0.5)
        return False

    # -- half-open probing --------------------------------------------

    def quarantined(self) -> bool:
        return self.state == "quarantined"

    def try_probe(self) -> bool:
        """→ True when a canary probe may run NOW: quarantined, the
        cooldown elapsed, and no other probe is in flight (the
        CircuitBreaker half-open single-probe discipline)."""
        with self._mu:
            if self.state != "quarantined" or self._probe_inflight:
                return False
            if time.monotonic() - self._opened_at < self.cooldown_s:
                return False
            self._probe_inflight = True
            return True

    def probe_result(self, ok: bool) -> None:
        with self._mu:
            self._probe_inflight = False
            if self.state != "quarantined":
                return
            if ok:
                self.state = "healthy"
                # decay, don't reset: the slice re-enters scoring with
                # a high (but sub-trip) score, so placement re-pins
                # anchors gradually and one fresh fault re-trips
                self.score = max(0.0, self.trip_strikes - 1.0)
                self.readmits += 1
            else:
                self.probe_failures += 1
                self._opened_at = time.monotonic()

    def penalty(self) -> float:
        """Normalized score for the placement blend (0 healthy …
        ~1 at the trip threshold)."""
        with self._mu:
            return self.score / self.trip_strikes \
                if self.trip_strikes > 0 else 0.0

    def reset(self) -> None:
        with self._mu:
            self.score = 0.0
            self.state = "healthy"
            self._probe_inflight = False

    def stats(self) -> dict:
        with self._mu:
            return {"slice": self.idx,
                    "score": round(self.score, 3),
                    "state": self.state,
                    "strikes": dict(self.strikes),
                    "trips": self.trips,
                    "readmits": self.readmits,
                    "refusals": self.refusals,
                    "probe_failures": self.probe_failures,
                    "probe_inflight": self._probe_inflight,
                    "launched_quarantined": self.launched_quarantined}


class SliceHealthBoard:
    """Per-slice health for one device mesh.

    Owned by the mesh's whole-mesh :class:`~..runner.DeviceRunner`;
    shared with its :class:`~.placement.SlicePlacer` (the slices are
    the same chips) and struck by degraded submesh runners through
    their ``_failover_parent`` back-pointer, so every observation about
    a chip lands on ONE score wherever it was made.
    """

    def __init__(self, n_slices: int,
                 trip_strikes: float = DEFAULT_TRIP_STRIKES,
                 cooldown_s: float = DEFAULT_PROBE_COOLDOWN_S,
                 latency_outlier_s: Optional[float] = None):
        self._slices = [SliceHealth(i, trip_strikes=trip_strikes,
                                    cooldown_s=cooldown_s,
                                    latency_outlier_s=latency_outlier_s)
                        for i in range(n_slices)]
        self._mu = threading.Lock()
        self._listeners: list = []
        _LIVE_BOARDS.add(self)

    def __len__(self) -> int:
        return len(self._slices)

    def slice(self, i: int) -> SliceHealth:
        return self._slices[i]

    def add_trip_listener(self, fn) -> None:
        """``fn(idx, reason)`` fires on every healthy→quarantined
        transition, OUTSIDE any board/slice lock (listeners take their
        own — the placer drains under its placement lock)."""
        with self._mu:
            self._listeners.append(fn)

    def _fire_trip(self, idx: int, reason: str) -> None:
        from ..utils.metrics import DEVICE_FAILOVER_COUNTER
        DEVICE_FAILOVER_COUNTER.labels("quarantine").inc()
        with self._mu:
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(idx, reason)
            except Exception:   # noqa: BLE001 — a listener must not
                pass            # poison the scoring path

    def note_fault(self, idx: int, kind: str,
                   weight: float = 1.0) -> None:
        if 0 <= idx < len(self._slices) and \
                self._slices[idx].note_fault(kind, weight=weight):
            self._fire_trip(idx, kind)

    def trip(self, idx: int, reason: str) -> None:
        if 0 <= idx < len(self._slices) and \
                self._slices[idx].trip(reason):
            self._fire_trip(idx, reason)

    def quarantined_set(self) -> frozenset:
        return frozenset(i for i, s in enumerate(self._slices)
                         if s.quarantined())

    def penalty(self, i: int) -> float:
        return self._slices[i].penalty()

    def maybe_probe(self, canary) -> int:
        """Run ``canary(idx) -> bool`` for every quarantined slice
        whose cooldown elapsed (one probe per slice at a time); feed
        the results back.  → probes run.  Cheap when nothing is due —
        the callers (placement routing, mesh-degrade routing, the
        supervisor's scrub loop) invoke it opportunistically."""
        from ..utils.metrics import DEVICE_FAILOVER_COUNTER
        ran = 0
        for s in self._slices:
            if not s.try_probe():
                continue
            ran += 1
            try:
                ok = bool(canary(s.idx))
            except Exception:   # noqa: BLE001 — a crashed canary is a
                ok = False      # failed probe, not a crashed caller
            s.probe_result(ok)
            if ok:
                DEVICE_FAILOVER_COUNTER.labels("readmit").inc()
            else:
                DEVICE_FAILOVER_COUNTER.labels("probe_fail").inc()
        return ran

    def reset(self) -> None:
        for s in self._slices:
            s.reset()

    def publish_metrics(self) -> None:
        from ..utils.metrics import DEVICE_SLICE_HEALTH
        for s in self._slices:
            DEVICE_SLICE_HEALTH.labels(str(s.idx)).set(
                round(s.penalty(), 4))

    def stats(self) -> list:
        self.publish_metrics()
        return [s.stats() for s in self._slices]


class _ArenaEntry:
    __slots__ = ("ref", "bucket", "nbytes", "hits", "tick", "pins",
                 "gen", "owner_tag", "owner_region", "res_t0", "released")

    def __init__(self, ref, gen: int):
        self.ref = ref
        self.bucket: dict = {}
        self.nbytes = 0
        self.hits = 0
        self.tick = 0
        self.pins = 0
        # entry generation: pin tokens embed it so an unpin issued
        # against a dropped-and-rebuilt entry (same anchor, new entry)
        # can never strip a different dispatch's pin
        self.gen = gen
        # RU residency attribution: the (resource_group, source) tag
        # that last touched this anchor under a metering context owns
        # its bytes-resident-seconds from res_t0 forward
        self.owner_tag = None
        self.owner_region = None
        self.res_t0 = time.monotonic()
        # feed keys the budget took from this line and no upload has
        # brought back (None: never any)
        self.released = None


class FeedArena:
    """Explicitly-owned HBM feed cache with budget + eviction.

    One entry per feed anchor (a FeedLineage for delta-maintained
    lines, the snapshot itself otherwise).  The primary reclamation
    path is EXPLICIT: region cache line teardown calls the runner's
    ``drop_feed``.  A weakref finalizer is kept only as a backstop for
    anchors that never see a lifecycle event (ad-hoc test snapshots) —
    accounting never depends on it.

    Eviction: least-frequently-used first, least-recently-used among
    ties, skipping pinned entries (an in-flight deferred dispatch has
    device buffers in use; evicting its line would free HBM the
    accounting still owes).  ``budget_bytes <= 0`` disables the budget
    (accounting and gauges stay live).

    An entry's bucket holds what the budget counts (a line's feeds, a
    memo's sparse-slot column) beside what it does not: the request
    memos' host half (dtypes, limbs, bounds, host planes in their padded
    upload buffers and their scrub digests), which costs no HBM and
    which a re-upload needs whole.  So the BUDGET (an eviction,
    ``admit``'s reject, ``enforce``) releases the first and
    what refers to it (``_bucket_release``: the feeds, the slot column,
    the prepared record) and keeps the entry with its memos: the next
    read of the line pays ``feed_upload`` and no ``host_derive``.  The
    kept entry starts over in the eviction order (``hits`` 0, as the
    entry a re-upload used to create), accounts 0 bytes and is no
    resident line; a line without a memo leaves whole.  Everything
    else that ends a line takes the memos with it, as it always did:
    ``drop`` (lifecycle, quarantine, ``on_line_retired``),
    ``drop_all``, the weakref backstop.
    """

    def __init__(self, budget_bytes: int = 0):
        self._entries: dict[int, _ArenaEntry] = {}
        self._mu = threading.RLock()
        self._tick = 0
        self._gen = 0
        # residency charges settled under _mu, flushed to the metering
        # recorder OUTSIDE it: (owner_tag, owner_region, byte_seconds)
        self._pending_res: list = []
        # window-roll settlement: the recorder sweeps registered arenas
        # so an idle feed still pays rent every metering window
        from .. import resource_metering as _rm
        _rm.GLOBAL_RECORDER.register_residency_source(self)
        # running resident-byte total, maintained at admit/drop/evict:
        # the per-request paths (admit, unpin) must not pay an
        # O(anchors) sum at the thousands-of-regions scale
        self._resident = 0
        # running pinned-byte total, same discipline: the flight
        # recorder stamps it on EVERY kernel launch, so it must be
        # O(1), not an O(entries) sum under the arena mutex
        self._pinned = 0
        # entries that hold device bytes (a line the budget released
        # stays an entry, with its memos, and is none of these)
        self._lines = 0
        self.budget_bytes = int(budget_bytes)
        self.evictions = 0
        self.rejections = 0
        self.drops = 0
        # bytes evictions released, and evictions that left a host
        # memo behind
        self.evicted_bytes = 0
        self.memos_kept = 0

    # -- bucket access ------------------------------------------------

    def bucket(self, anchor, create: bool = True) -> Optional[dict]:
        """The per-anchor cache dict (feeds + request memos), or None
        when the anchor cannot be tracked (not weak-referenceable)."""
        from .. import resource_metering as _rm
        ctx = _rm.current_context()
        key = id(anchor)
        with self._mu:
            ent = self._entries.get(key)
            if ent is not None:
                self._touch_locked(ent, ctx, anchor)
                return ent.bucket
            if not create:
                return None
            try:
                ref = weakref.ref(anchor,
                                  lambda _r, k=key: self._gc_drop(k))
            except TypeError:
                return None
            self._gen += 1
            ent = _ArenaEntry(ref, self._gen)
            self._touch_locked(ent, ctx, anchor)
            self._entries[key] = ent
            return ent.bucket

    def _touch_locked(self, ent: _ArenaEntry, ctx, anchor,
                      now: Optional[float] = None) -> None:
        """One use of the entry: its frequency and recency (the
        eviction order) and its residency's owner."""
        self._tick += 1
        ent.hits += 1
        ent.tick = self._tick
        self._own_locked(ent, ctx, anchor, now)

    def peek(self, anchor) -> Optional[dict]:
        """The anchor's bucket where it is resident, else None: one dict
        read, no mutex, and nothing of the entry touched (recency,
        frequency, ownership).  For a look-up that decides nothing by
        itself (``DeviceRunner.launch_ticket``): whoever stages from
        what it found holds it to :meth:`pin_many`'s answer."""
        ent = self._entries.get(id(anchor))
        return None if ent is None else ent.bucket

    # -- residency metering -------------------------------------------

    def _own_locked(self, ent: _ArenaEntry, ctx, anchor,
                    now: Optional[float] = None) -> None:
        """A tagged toucher takes ownership of the anchor's residency;
        accrual up to now settles to the PREVIOUS owner first (the
        tag that parked the bytes pays for the parking)."""
        if ctx is None or ctx.tag is None:
            return
        if ent.owner_tag != ctx.tag:
            self._settle_entry_locked(
                ent, time.monotonic() if now is None else now)
            ent.owner_tag = ctx.tag
        region = ctx.region if ctx.region is not None else \
            getattr(anchor, "region_hint", None)
        if region is not None:
            ent.owner_region = region

    def _settle_entry_locked(self, ent: _ArenaEntry,
                             now: float) -> None:
        dt = now - ent.res_t0
        ent.res_t0 = now
        if dt > 0 and ent.nbytes > 0:
            self._pending_res.append(
                (ent.owner_tag, ent.owner_region, ent.nbytes * dt))

    def _flush_residency(self) -> None:
        """Charge settled byte-seconds OUTSIDE the arena mutex."""
        with self._mu:
            if not self._pending_res:
                return
            pending, self._pending_res = self._pending_res, []
        self._charge_residency(pending)

    @staticmethod
    def _charge_residency(pending: list) -> None:
        from .. import resource_metering as _rm
        for tag, region, byte_s in pending:
            _rm.GLOBAL_RECORDER.charge(
                "arena::residency", byte_seconds=byte_s,
                tag=tag if tag is not None else _rm.UNTAGGED,
                region=region)

    def settle_residency(self, recorder=None) -> None:
        """Settle every entry's accrued bytes-resident-seconds up to
        now — the metering window roll's sweep (``recorder`` is the
        caller's handle, unused: charges flow through the global
        recorder the arena registered with)."""
        now = time.monotonic()
        with self._mu:
            for ent in self._entries.values():
                self._settle_entry_locked(ent, now)
        self._flush_residency()

    def _gc_drop(self, key: int) -> None:
        # backstop only: anchors with lifecycle owners are dropped
        # explicitly long before their refcount hits zero
        with self._mu:
            ent = self._entries.pop(key, None)
            if ent is not None:
                self._settle_entry_locked(ent, time.monotonic())
                self._account_locked(ent, 0)
        # deliberately NO residency flush here: this is a weakref GC
        # callback and may fire on a thread already inside the
        # metering recorder's lock (an allocation-triggered collection
        # mid-charge) — the settlement stays queued in _pending_res
        # and the next pin/drop/window-roll flush charges it
        self._publish()

    # -- pinning ------------------------------------------------------

    def pin(self, anchor):
        """Pin the anchor's CURRENT entry; returns an opaque token for
        :meth:`unpin`, or None when the anchor is not resident.  The
        token embeds the entry generation: if the entry is dropped and
        rebuilt before the unpin arrives, the stale token is a no-op
        instead of stripping the new dispatch's pin."""
        with self._mu:
            ent = self._entries.get(id(anchor))
            if ent is None:
                return None
            # pin-time sampling: settle accrued residency at every
            # dispatch pin so a hot feed's rent lands in the same
            # metering window its traffic does
            self._settle_entry_locked(ent, time.monotonic())
            if ent.pins == 0:
                self._pinned += ent.nbytes
            ent.pins += 1
            token = (id(anchor), ent.gen)
        self._flush_residency()
        return token

    def pin_many(self, anchors) -> list:
        """A hold's lanes, each over its line: what :meth:`bucket` and
        :meth:`pin` do for one (the entry touched and owned, its
        residency settled, its pin) for all of them under ONE acquire of
        the mutex → ``(bucket, token)`` a lane in order, ``(None,
        None)`` where the anchor is not resident.  Residency is settled
        at one instant and flushed once, outside the mutex; a token is
        :meth:`pin`'s and :meth:`unpin` takes it."""
        from .. import resource_metering as _rm
        ctx = _rm.current_context()
        out = []
        pending = None
        with self._mu:
            now = time.monotonic()
            for anchor in anchors:
                ent = self._entries.get(id(anchor))
                if ent is None:
                    out.append((None, None))
                    continue
                self._touch_locked(ent, ctx, anchor, now)
                self._settle_entry_locked(ent, now)
                if ent.pins == 0:
                    self._pinned += ent.nbytes
                ent.pins += 1
                out.append((ent.bucket, (id(anchor), ent.gen)))
            if self._pending_res:
                pending, self._pending_res = self._pending_res, []
        if pending:
            self._charge_residency(pending)
        return out

    def unpin(self, token) -> None:
        if token is None:
            return
        key, gen = token
        with self._mu:
            ent = self._entries.get(key)
            if ent is not None and ent.gen == gen and ent.pins > 0:
                ent.pins -= 1
                if ent.pins == 0:
                    self._pinned = max(0, self._pinned - ent.nbytes)
            # a pin release may be what the budget was waiting for
            # (a pinned entry admitted over the cap): sweep now
            if self.budget_bytes > 0:
                self._evict_until_locked(self.budget_bytes)
        self._flush_residency()
        self._publish()

    # -- admission / eviction ----------------------------------------

    def admit(self, anchor) -> bool:
        """Re-account ``anchor``'s bucket and enforce the budget,
        evicting other unpinned entries (lowest frequency, then oldest
        recency) until resident bytes fit.  Returns False when the
        entry could not fit even alone — its device state is released
        (its memos stay) and the caller serves the request from its
        transient feed, uncached."""
        key = id(anchor)
        from ..utils.metrics import DEVICE_FEED_EVICTION_COUNTER
        with self._mu:
            ent = self._entries.get(key)
            if ent is None:
                return False
            if not _bucket_has_feed(ent.bucket):
                # a line whose feed the budget took (a reject, or a
                # sweep that raced this staging) keeps its memos and no
                # device state: what the staging wrote since (a record
                # of the feed it serves from, a slot column) is the
                # request's own
                _bucket_release(ent.bucket)
            fresh = _bucket_nbytes(ent.bucket)
            # settle at the OLD byte count before re-accounting: each
            # residency interval is charged at the bytes actually held
            self._settle_entry_locked(ent, time.monotonic())
            self._account_locked(ent, fresh)
            budget = self.budget_bytes
            fp = fail_point("device::hbm_oom")
            if fp is not None:
                try:
                    squeeze = int(getattr(fp, "value", None) or 0)
                except (TypeError, ValueError):
                    squeeze = 0
                budget = squeeze if budget <= 0 else min(budget, squeeze)
                # a fired squeeze always enforces: return(0) means "no
                # HBM at all", not "unlimited"
                budget = max(1, budget)
            admitted = True
            if budget > 0:
                self._evict_until_locked(budget, protect_key=key)
                if self._total_locked() > budget and ent.pins == 0:
                    # still over: either the entry exceeds the budget
                    # alone, or pinned in-flight lines hold the rest.
                    # The budget is a HARD cap on resident bytes, so
                    # the newcomer serves uncached either way (pinned
                    # space frees at fetch completion; the next access
                    # re-admits).  A PINNED newcomer is never popped —
                    # its HBM is in use by a launched kernel, so
                    # dropping the entry would only falsify the
                    # accounting (and strand the pin)
                    self._release_locked(key, ent)
                    self.rejections += 1
                    DEVICE_FEED_EVICTION_COUNTER.labels("reject").inc()
                    admitted = False
        self._flush_residency()
        self._publish()
        return admitted

    def _evict_until_locked(self, budget: int,
                            protect_key: Optional[int] = None) -> int:
        """Evict unpinned entries until resident bytes fit ``budget``.
        Caller holds ``_mu``.  Returns entries evicted.

        Victim order is lowest-frequency, then oldest-recency — unless
        multi-tenant resource control is on (resource_control.py), in
        which case the owning tag's standing is folded in FIRST: an
        entry whose tenant is OVER its HBM residency share (the
        ``arena::residency`` owners the metering records) evicts
        before any under-share tenant's entry, ranked by the owner's
        RU debt within each class — a background scanner's feeds die
        first and a latency tenant's hot set is protected up to its
        share.  Work-conserving by construction: the bias only
        engages under budget pressure, so an over-share tenant keeps
        using slack capacity until someone actually needs it."""
        if self._total_locked() <= budget:
            return 0
        from ..utils import tracker
        # (a leaf of the dispatcher's hold where an admission sweeps, a
        # phase of the request whose unpin does)
        with tracker.phase("arena_evict"):
            before = self.evicted_bytes
            evicted = self._sweep_locked(budget, protect_key)
            tracker.annotate(victims=evicted,
                             bytes=self.evicted_bytes - before)
        return evicted

    def _sweep_locked(self, budget: int,
                      protect_key: Optional[int]) -> int:
        """``_evict_until_locked``'s sweep, resident bytes over
        ``budget`` → entries evicted."""
        from ..utils.metrics import DEVICE_FEED_EVICTION_COUNTER
        from ..resource_control import GLOBAL_CONTROLLER
        from ..resource_metering import ResourceTagFactory as _rtf
        evicted = 0
        rc = tenant_bytes = standing = None
        if GLOBAL_CONTROLLER.enabled:
            rc = GLOBAL_CONTROLLER
            tenant_bytes = {}
            for e in self._entries.values():
                if e.nbytes > 0:
                    t = _rtf.tenant(e.owner_tag)
                    tenant_bytes[t] = \
                        tenant_bytes.get(t, 0) + e.nbytes
            # ONE controller-lock round trip per sweep: per-tenant
            # (byte limit, RU debt) snapshot — per-entry scoring
            # below is pure dict math under the arena mutex, and
            # only the victim's tenant needs bytes re-tallied
            standing = rc.hbm_standing(tenant_bytes, budget)
        evicted_by_tenant: dict = {}
        while self._total_locked() > budget:
            victim_key = victim = victim_rank = None
            for k, e in self._entries.items():
                if k == protect_key or e.pins > 0 or e.nbytes <= 0:
                    continue
                if standing is not None:
                    t = _rtf.tenant(e.owner_tag)
                    limit, debt = standing.get(t, (float("inf"), 0.0))
                    rank = (0 if tenant_bytes.get(t, 0) > limit
                            else 1, -debt, e.hits, e.tick)
                else:
                    rank = (e.hits, e.tick)
                if victim_rank is None or rank < victim_rank:
                    victim_key, victim, victim_rank = k, e, rank
            if victim is None:
                break
            self._settle_entry_locked(victim, time.monotonic())
            if standing is not None:
                t = _rtf.tenant(victim.owner_tag)
                tenant_bytes[t] = max(
                    0, tenant_bytes.get(t, 0) - victim.nbytes)
                evicted_by_tenant[t] = \
                    evicted_by_tenant.get(t, 0) + 1
            self.evicted_bytes += victim.nbytes
            self.memos_kept += self._release_locked(victim_key, victim)
            self.evictions += 1
            evicted += 1
            DEVICE_FEED_EVICTION_COUNTER.labels("budget").inc()
        if standing is not None and evicted:
            # one controller-lock round trip for the whole sweep's
            # eviction telemetry (mirrors the hbm_standing read side)
            rc.note_evictions(evicted_by_tenant)
            # the protection figure: under-share tenants' bytes still
            # resident after a sweep that evicted over-share state
            protected = sum(
                b for t, b in tenant_bytes.items()
                if b > 0 and b <= standing.get(
                    t, (float("inf"), 0.0))[0])
            rc.note_protected(protected)
        return evicted

    def _release_locked(self, key: int, ent: _ArenaEntry) -> bool:
        """The budget's way of taking a line (an eviction, a reject):
        its device state out of the bucket and out of the accounting,
        its memos and the entry kept, the entry where a new one would
        stand in the eviction order.  → whether a memo is left (an
        entry without one goes whole)."""
        gone, kept = _bucket_release(ent.bucket)
        self._account_locked(ent, 0)
        if not kept:
            # nothing of the host's to keep: the entry goes whole
            self._entries.pop(key, None)
            return False
        if gone:
            if ent.released is None:
                ent.released = set()
            ent.released.update(gone)
        ent.hits = 0
        return True

    def _account_locked(self, ent: _ArenaEntry, nbytes: int) -> None:
        """``ent`` accounts ``nbytes`` from here on: the running totals
        move with it (a pinned entry's pinned bytes too, or the pair of
        counters drifts apart)."""
        delta = nbytes - ent.nbytes
        self._resident += delta
        if ent.pins > 0:
            self._pinned = max(0, self._pinned + delta)
        self._lines += (nbytes > 0) - (ent.nbytes > 0)
        ent.nbytes = nbytes

    def reclaimed(self, anchor, feed_key) -> bool:
        """Whether the upload of ``feed_key`` now brings back a feed
        the budget had taken from ``anchor``'s line (asked once an
        upload: the mark goes with the answer)."""
        with self._mu:
            ent = self._entries.get(id(anchor))
            if ent is None or not ent.released or \
                    feed_key not in ent.released:
                return False
            ent.released.discard(feed_key)
            return True

    def enforce(self) -> int:
        """Eviction sweep against the CURRENT budget with no protected
        newcomer — the online budget-shrink path (set_hbm_budget).
        Returns entries evicted."""
        with self._mu:
            evicted = self._evict_until_locked(self.budget_bytes) \
                if self.budget_bytes > 0 else 0
        self._flush_residency()
        self._publish()
        return evicted

    def drop(self, anchor, reason: str = "drop") -> int:
        """Explicit teardown — the lifecycle/quarantine path.  Ignores
        pins (correctness teardown must win over budget bookkeeping;
        in-flight dispatches keep their own buffer references alive).
        Returns the bytes released from the accounting."""
        from ..utils.metrics import DEVICE_FEED_EVICTION_COUNTER
        with self._mu:
            ent = self._entries.pop(id(anchor), None)
            freed = ent.nbytes if ent is not None else 0
            if ent is not None:
                self._settle_entry_locked(ent, time.monotonic())
                self._account_locked(ent, 0)
                self.drops += 1
                DEVICE_FEED_EVICTION_COUNTER.labels(reason).inc()
        self._flush_residency()
        self._publish()
        return freed

    def drop_all(self, reason: str = "drop") -> int:
        """Drop EVERY entry, pins included — the mesh-degrade and node
        teardown path: a feed sharded over a chip that just died (or a
        runner being torn down) holds nothing worth protecting, and
        in-flight dispatches keep their own buffer references alive.
        Stale pin tokens no-op at unpin (entry gone).  → bytes freed."""
        from ..utils.metrics import DEVICE_FEED_EVICTION_COUNTER
        with self._mu:
            now = time.monotonic()
            for ent in self._entries.values():
                self._settle_entry_locked(ent, now)
            freed = self._resident
            n = len(self._entries)
            self._entries.clear()
            self._resident = 0
            self._pinned = 0
            self._lines = 0
            self.drops += n
            if n:
                DEVICE_FEED_EVICTION_COUNTER.labels(reason).inc(n)
        self._flush_residency()
        self._publish()
        return freed

    # -- observability ------------------------------------------------

    def _total_locked(self) -> int:
        return self._resident

    def resident_bytes(self) -> int:
        with self._mu:
            return self._total_locked()

    def pinned_bytes(self) -> int:
        """Bytes held by entries pinned by in-flight dispatches (the
        flight recorder stamps this per launch — O(1) running total,
        maintained at pin/unpin/re-account/drop; one int read, so no
        mutex: a launch on the dispatcher must not park for a gauge)."""
        return self._pinned

    def resident_lines(self) -> int:
        """Entries that hold device bytes."""
        return self._lines

    def feed_residency(self) -> tuple:
        """→ (feeds, bytes of their planes) resident now, read from the
        buckets: a feed is a line's planes under ONE scan schema, so
        what two schemas of a region both read is counted twice here,
        as it is held twice."""
        with self._mu:
            buckets = [e.bucket for e in self._entries.values()]
        feeds = nbytes = 0
        for b in buckets:
            for v in list(b.values()):      # status threads race inserts
                if isinstance(v, dict) and "flat" in v:
                    feeds += 1
                    nbytes += sum(int(getattr(a, "nbytes", 0))
                                  for a in v["flat"])
        return feeds, nbytes

    def residency_by_tenant(self) -> dict:
        """Resident bytes per owning tenant (the resource_group half
        of the ``arena::residency`` owner tags) — the enforcement
        surface's per-group HBM view, rolled up into the runner's
        hbm_stats and the /resource_control route."""
        from ..resource_metering import ResourceTagFactory
        with self._mu:
            out: dict = {}
            for e in self._entries.values():
                if e.nbytes <= 0:
                    continue
                t = ResourceTagFactory.tenant(e.owner_tag)
                out[t] = out.get(t, 0) + e.nbytes
            return out

    def resident_bytes_by_device(self) -> dict:
        """Resident bytes per PHYSICAL device id, read from where each
        plane's shards actually live (not from the accounting) — the
        check that a "sharded" feed is not sitting whole on device 0."""
        with self._mu:
            buckets = [e.bucket for e in self._entries.values()]
        out: dict = {}
        for b in buckets:
            for a in _bucket_arrays(b):
                sharding = getattr(a, "sharding", None)
                if sharding is None:
                    continue
                per_dev = a.dtype.itemsize * math.prod(
                    sharding.shard_shape(a.shape))
                for d in sharding.device_set:
                    out[d.id] = out.get(d.id, 0) + per_dev
        return out

    def items(self) -> list:
        """Snapshot of (anchor, bucket) pairs with live anchors — the
        scrubber's iteration surface."""
        with self._mu:
            pairs = [(e.ref(), e.bucket)
                     for e in list(self._entries.values())]
        return [(a, b) for a, b in pairs if a is not None]

    def entry_stats(self) -> list:
        """(anchor, nbytes, hits, tick, pins) snapshot with live
        anchors — the placement rebalancer's victim-selection surface
        (device/placement.py picks the coldest unpinned anchor)."""
        with self._mu:
            rows = [(e.ref(), e.nbytes, e.hits, e.tick, e.pins)
                    for e in list(self._entries.values())]
        return [(a, nb, h, t, p) for a, nb, h, t, p in rows
                if a is not None]

    def _publish(self) -> None:
        from ..utils.metrics import (
            DEVICE_FEED_LINES,
            DEVICE_HBM_RESIDENT_BYTES,
        )
        with self._mu:
            DEVICE_HBM_RESIDENT_BYTES.set(self._total_locked())
            DEVICE_FEED_LINES.set(self._lines)

    def stats(self) -> dict:
        with self._mu:
            return {
                "budget_bytes": self.budget_bytes,
                "resident_bytes": self._total_locked(),
                "resident_lines": self._lines,
                "pinned_lines": sum(1 for e in self._entries.values()
                                    if e.pins > 0),
                # bytes the budget cannot reclaim right now (in use by
                # launched kernels) — check_hbm_within_budget allows
                # resident to exceed the cap by at most this much
                "pinned_bytes": self._pinned,
                "evictions": self.evictions,
                "rejections": self.rejections,
                "drops": self.drops,
                # what evictions released, and how many of them left
                # the line's host memos behind (a re-upload then
                # derives nothing)
                "evicted_bytes": self.evicted_bytes,
                "memos_kept": self.memos_kept,
            }


class _RemintWaiter:
    __slots__ = ("key", "shed", "region_id")

    def __init__(self, key, region_id):
        self.key = key
        self.shed = False
        self.region_id = region_id


class RemintGovernor:
    """Bounded, priority-ordered admission for cold ``columnar_build``
    re-mints — the storm-control half of the elastic feed lifecycle.

    When migration/split isn't possible (total slice death, digest
    divergence, delta-envelope misses) every invalidated region wants a
    host rebuild at once, and the narrow host link is exactly where a
    recovery storm hurts.  The governor caps concurrent builds at
    ``max_concurrent`` and parks the rest in a priority queue ordered
    hot-regions-first (the cache's decayed request rate) with RU-debt
    tenants last; past ``max_queue`` waiters, the WORST-priority waiter
    is shed with ``ServerIsBusy(retry_after_ms=...)`` so cold-tail work
    backs off instead of piling onto the link.

    Wired as ``RegionColumnarCache.remint_gate`` (server/node.py);
    ``max_concurrent <= 0`` disables admission entirely (the default —
    tier-1 behavior is unchanged unless configured on).
    """

    def __init__(self, max_concurrent: int = 2, max_queue: int = 32,
                 retry_after_ms: int = 50):
        self.max_concurrent = int(max_concurrent)
        self.max_queue = max(1, int(max_queue))
        self.retry_after_ms = int(retry_after_ms)
        self._cv = threading.Condition(threading.Lock())
        self._active = 0
        self._waiters: list = []
        self._seq = 0
        self.admitted = 0
        self.queued = 0
        self.shed = 0
        self.observed_max = 0       # peak concurrent builds ever granted
        self.peak_depth = 0         # deepest the wait queue ever got

    @staticmethod
    def _ru_debt() -> bool:
        """Is the CURRENT request's tenant in RU debt?  Debtors rebuild
        last: their burst already overdrew the shared budget."""
        try:
            from .. import resource_metering
            from ..resource_control import GLOBAL_CONTROLLER, \
                ResourceTagFactory
            ctx = resource_metering.current_context()
            tag = ctx.tag if ctx is not None else None
            if tag is None:
                return False
            return GLOBAL_CONTROLLER.debt(
                ResourceTagFactory.tenant(tag)) > 0
        except Exception:   # noqa: BLE001 — priority hints never fail a build
            return False

    def acquire(self, region_id: int, heat: float = 0.0):
        """Block until a build permit is granted; raises ServerIsBusy
        (with the retry hint) when this waiter is shed.  Returns a
        ticket for :meth:`release`."""
        if self.max_concurrent <= 0:
            return None             # disabled: free admission
        from ..server.read_pool import ServerIsBusy
        from ..utils.metrics import DEVICE_REMINT_QUEUE_DEPTH
        with self._cv:
            if self._active < self.max_concurrent and not self._waiters:
                self._active += 1
                self.admitted += 1
                self.observed_max = max(self.observed_max, self._active)
                return True
            # smaller key = admitted sooner: debt-free before debtors,
            # then hottest region, then FIFO
            self._seq += 1
            w = _RemintWaiter((1 if self._ru_debt() else 0, -heat,
                               self._seq), region_id)
            self._waiters.append(w)
            self.queued += 1
            if len(self._waiters) > self.max_queue:
                worst = max(self._waiters, key=lambda x: x.key)
                self._waiters.remove(worst)
                worst.shed = True
                self.shed += 1
                self._cv.notify_all()
            DEVICE_REMINT_QUEUE_DEPTH.set(len(self._waiters))
            self.peak_depth = max(self.peak_depth, len(self._waiters))
            while True:
                if w.shed:
                    raise ServerIsBusy(
                        "re-mint queue overloaded",
                        retry_after_ms=self.retry_after_ms)
                if self._active < self.max_concurrent and \
                        min(self._waiters, key=lambda x: x.key) is w:
                    self._waiters.remove(w)
                    self._active += 1
                    self.admitted += 1
                    self.observed_max = max(self.observed_max,
                                            self._active)
                    DEVICE_REMINT_QUEUE_DEPTH.set(len(self._waiters))
                    # others re-check: more slots may still be free
                    self._cv.notify_all()
                    return True
                self._cv.wait()

    def release(self, ticket) -> None:
        if ticket is None:
            return
        with self._cv:
            self._active -= 1
            self._cv.notify_all()

    def stats(self) -> dict:
        with self._cv:
            return {
                "max_concurrent": self.max_concurrent,
                "active": self._active,
                "depth": len(self._waiters),
                "admitted": self.admitted,
                "queued": self.queued,
                "shed": self.shed,
                "observed_max": self.observed_max,
                "peak_depth": self.peak_depth,
            }


class DeviceStateSupervisor(Observer):
    """Lifecycle teardown + background scrub over device-resident state.

    Registered on the raftstore's CoprocessorHost next to CDC and the
    DeltaSink.  Also installed as the RegionColumnarCache's
    ``on_line_retired`` callback, closing the loop: any line the cache
    drops (lifecycle event, LRU eviction, rebuild replacement, failed
    bridge) explicitly drops its device feed via ``runner.drop_feed``
    instead of waiting for GC.

    ``runner`` may be None — the supervisor still drives columnar-cache
    lifecycle invalidation on host-only nodes.
    """

    def __init__(self, runner=None, copr_cache=None, delta_sink=None,
                 scrub_interval: float = 0.0, scrub_max_lines: int = 0):
        self._runner = runner
        self._cache = copr_cache
        self._sink = delta_sink
        self._interval = scrub_interval
        self._scrub_max_lines = scrub_max_lines     # 0 = unbounded
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._mu = threading.Lock()
        # rotates a bounded pass's starting point so every resident
        # line is eventually scrubbed, not just the first N
        self._scrub_cursor = 0
        self.scrub_passes = 0
        self.scrub_divergences = 0
        self.quarantines = 0
        self.lifecycle_invalidations = 0
        self._last_scrub: dict = {}
        # replica-feed state machine (warm failover): regions whose
        # lines this store keeps as follower feeds — demoted leaders
        # plus regions that served a stale device read
        self._replica_feed_regions: set = set()
        self.promotions = 0             # leader gains over a warm feed
        self.promotion_rebuilds = 0     # promotions that failed verify
        self.demotions = 0              # leader losses (feed retained)
        # device-side split state machine
        self.splits = 0                 # parent lines sliced on device
        self.split_fallbacks = 0        # splits that fell back to re-mint
        # the storm-control governor (wired by node.py onto the cache's
        # remint_gate too; kept here so /health and chaos invariants
        # read one rollup)
        self.remint_governor = None

    # -- lifecycle events (CoprocessorHost observer) ------------------
    #
    # These run inline on the apply/drive path; each is dict surgery
    # plus reference drops — no device work, no blocking fetches.

    def on_region_changed(self, region) -> None:
        """Split/merge/epoch change: lines keyed at superseded epochs
        can never be hit again — drop them (and their feeds) now."""
        if self._cache is None:
            return
        n = self._cache.invalidate_region(
            region.id, keep_epoch=region.epoch.version)
        if n:
            self._note_invalidations(n)

    def on_region_split(self, left, right, left_index,
                        right_index) -> None:
        """A split is a slice, not a rebuild: the cache slices its
        parent lines into child lines at the children's epochs (zero
        ``columnar_build``), then the runner slices the resident parent
        FEEDS into digest-verified child feeds on device (zero
        ``feed_upload``).  This runs BEFORE the generic
        ``on_region_changed`` retires the superseded parent lines —
        peer.py orders the two events — so the parent planes are still
        resident when the device split reads them.  The
        ``device::device_split`` failpoint (and any slicing failure)
        falls back to host re-mint for THIS split only."""
        from ..utils import tracker
        from ..utils.metrics import DEVICE_FEED_MIGRATION_COUNTER
        if self._cache is None or \
                not hasattr(self._cache, "split_lines"):
            return
        if fail_point("device::device_split") is not None:
            DEVICE_FEED_MIGRATION_COUNTER.labels("split_fallback").inc()
            with self._mu:
                self.split_fallbacks += 1
            return
        with tracker.phase("device_split"):
            try:
                specs = self._cache.split_lines(left, right, left_index,
                                                right_index)
            except Exception:   # noqa: BLE001 — split must never fail apply
                import logging
                logging.getLogger(__name__).warning(
                    "device-side split failed; falling back to re-mint",
                    exc_info=True)
                specs = []
            runner = self._runner
            child_anchors = []
            parent = None
            for spec in specs:
                parent = spec["parent_lineage"]
                ok = False
                if runner is not None and hasattr(runner, "_feeds"):
                    try:
                        ok = runner._feeds.split_resident_feeds(spec) \
                            == "split"
                    except Exception:   # noqa: BLE001 — same contract
                        ok = False
                DEVICE_FEED_MIGRATION_COUNTER.labels(
                    "split" if ok else "split_fallback").inc()
                with self._mu:
                    if ok:
                        self.splits += 1
                    else:
                        self.split_fallbacks += 1
                for side in ("left", "right"):
                    ch = spec.get(side)
                    if ch is not None:
                        child_anchors.append(ch["lineage"])
            # children serve where the parent lived: pin them to its
            # slice so the first child request dispatches co-located
            placer = getattr(runner, "_placer", None) \
                if runner is not None else None
            if placer is not None and parent is not None and \
                    hasattr(placer, "adopt"):
                try:
                    placer.adopt(parent, child_anchors)
                except Exception:   # noqa: BLE001 — placement is advisory
                    pass

    def on_role_change(self, region_id: int, is_leader: bool) -> None:
        """Role flips drive the replica-feed state machine, not a
        teardown.

        **Leader loss** (demotion): the region's lines STAY resident
        as replica feeds.  The DeltaSink observes follower applies
        too, so the same per-region delta stream keeps them patched,
        and they serve any coprocessor read at ``read_ts ≤
        resolved_ts`` through the stale-read gate.  (Before replicated
        serving this eagerly invalidated — a leader transfer cost a
        multi-second cold re-mint on transfer back.)

        **Leader gain** over a warm feed (promotion): resolved-ts
        catch-up already happened continuously via the delta stream,
        so promotion is only a scrub-digest re-verify of the region's
        resident planes — never a ``columnar_build``.  Only a digest
        divergence (or the ``copr::replica_promote`` failpoint) falls
        back to invalidation + cold rebuild.
        """
        if self._cache is None:
            return
        if not is_leader:
            with self._mu:
                self.demotions += 1
                self._replica_feed_regions.add(region_id)
            self._publish_replica_feeds()
            return
        with self._mu:
            was_replica = region_id in self._replica_feed_regions
            self._replica_feed_regions.discard(region_id)
        self._publish_replica_feeds()
        if was_replica or (hasattr(self._cache, "region_resident") and
                           self._cache.region_resident(region_id)):
            self.promote_region(region_id)

    def note_replica_feed(self, region_id: int) -> None:
        """A stale device read served from this store's line: the line
        is now a live replica feed (node.py ``_note_replica_read``)."""
        with self._mu:
            self._replica_feed_regions.add(region_id)
        self._publish_replica_feeds()

    def _publish_replica_feeds(self) -> None:
        from ..utils.metrics import DEVICE_REPLICA_FEEDS
        with self._mu:
            n = len(self._replica_feed_regions)
        DEVICE_REPLICA_FEEDS.set(n)

    def promote_region(self, region_id: int) -> bool:
        """Warm promotion of an already-patched replica feed to leader
        serving state.  Returns True when the feed survived verify.

        The feed's content is re-verified against the digests recorded
        at build/patch time (the same audit the background scrubber
        runs) so a leader never serves from a silently-corrupted
        replica plane.  On divergence — or when chaos arms
        ``copr::replica_promote`` — the region's lines invalidate and
        the next request pays the cold rebuild, counted separately so
        the no-cold-rebuild invariant can tell a failed verify from a
        broken warm path."""
        from ..utils import tracker
        from ..utils.metrics import DEVICE_REPLICA_PROMOTION_COUNTER
        ok = fail_point("copr::replica_promote") is None
        if ok:
            with tracker.phase("replica_promote"):
                ok = self._verify_region_digests(region_id)
        with self._mu:
            self.promotions += 1
            if not ok:
                self.promotion_rebuilds += 1
        if ok:
            DEVICE_REPLICA_PROMOTION_COUNTER.labels("warm").inc()
            return True
        DEVICE_REPLICA_PROMOTION_COUNTER.labels("rebuild").inc()
        n = self._cache.invalidate_region(region_id)
        if n:
            self._note_invalidations(n)
        return False

    def _verify_region_digests(self, region_id: int) -> bool:
        """Digest re-verify of one region's resident feeds (the scrub
        audit, targeted): snapshot each feed's (planes, digests) pair
        under the runner's dispatch lock, re-hash on device, compare.
        A diverged anchor quarantines exactly as a scrub hit would.
        No runner (host-only node) → trivially clean."""
        runner = self._runner
        if runner is None or not hasattr(runner, "arena_items"):
            return True
        dispatch_mu = getattr(runner, "_dispatch_mu", None)
        out = {"lines": 0, "planes": 0, "divergences": 0,
               "quarantined_regions": []}
        clean = True
        for anchor, bucket in runner.arena_items():
            if getattr(anchor, "region_hint", None) != region_id:
                continue
            feeds = []
            if dispatch_mu is not None:
                dispatch_mu.acquire()
            try:
                for v in list(bucket.values()):
                    if isinstance(v, dict) and "flat" in v and \
                            v.get("digests") is not None:
                        feeds.append((v["flat"], v["digests"],
                                      v.get("n_live", 0)))
            finally:
                if dispatch_mu is not None:
                    dispatch_mu.release()
            diverged = False
            for flat, digests, n in feeds:
                for arr, want in zip(flat, digests):
                    got = int(np.asarray(
                        runner._feeds.device_digest(arr, n)))
                    out["planes"] += 1
                    if got != int(np.asarray(want)):
                        diverged = True
                        break
                if diverged:
                    break
            if diverged:
                clean = False
                out["divergences"] += 1
                self._quarantine(runner, anchor, out)
        return clean

    def on_data_replaced(self, region_id: int, index: int) -> None:
        """Snapshot apply replaced the region's data wholesale: the
        DeltaSink already poisoned coverage; drop the derived lines
        eagerly too — they can only rebuild."""
        if self._cache is None:
            return
        n = self._cache.invalidate_region(region_id)
        if n:
            self._note_invalidations(n)

    def on_peer_destroyed(self, region_id: int) -> None:
        """Peer removed from this store (merge-away / conf change):
        every derived artifact for the region dies with it."""
        if self._cache is not None:
            n = self._cache.invalidate_region(region_id)
            if n:
                self._note_invalidations(n)
        if self._sink is not None and hasattr(self._sink, "drop_region"):
            self._sink.drop_region(region_id)

    def on_line_retired(self, lineage) -> None:
        """RegionColumnarCache retirement callback → explicit feed
        teardown (the drop_feed API replacing GC-timed reclamation)."""
        if self._runner is not None and lineage is not None:
            self._runner.drop_feed(lineage, reason="lifecycle")

    def _note_invalidations(self, n: int) -> None:
        with self._mu:
            self.lifecycle_invalidations += n

    # -- scrub --------------------------------------------------------

    def scrub(self, max_lines: Optional[int] = None) -> dict:
        """One scrub pass: re-hash resident device planes and compare
        against the digests recorded at build/patch time.  Divergence →
        quarantine the anchor (feeds drop; the next request for it
        degrades to host; the one after rebuilds from host truth).

        Low-priority by construction: digests are tiny reduction
        kernels over already-resident planes, dispatched one line at a
        time outside any runner lock, and ``max_lines`` bounds a pass
        so the scrubber never monopolizes the dispatch stream.
        """
        from ..utils.metrics import DEVICE_SCRUB_COUNTER
        out = {"lines": 0, "planes": 0, "divergences": 0,
               "quarantined_regions": []}
        runner = self._runner
        if runner is None or not hasattr(runner, "arena_items"):
            self._record_scrub(out, 0.0)
            return out
        limit = max_lines if max_lines is not None else \
            (self._scrub_max_lines or None)
        # the (flat, digests) pair is updated non-atomically by the
        # patch path under the runner's dispatch lock; snapshot each
        # feed's pair UNDER that lock so a concurrent patch can never
        # make a healthy line read as diverged (planes themselves are
        # immutable arrays — hashing proceeds outside the lock)
        dispatch_mu = getattr(runner, "_dispatch_mu", None)
        t0 = time.perf_counter()

        def hash_feeds(feeds) -> bool:
            diverged = False
            for flat, digests, n in feeds:
                for arr, want in zip(flat, digests):
                    got = int(np.asarray(
                        runner._feeds.device_digest(arr, n)))
                    out["planes"] += 1
                    if got != int(np.asarray(want)):
                        diverged = True
                if diverged:
                    break
            return diverged

        items = runner.arena_items()
        if limit is not None and items:
            # bounded pass: rotate the start so lines beyond the first
            # ``limit`` are reached on later passes, never starved
            start = self._scrub_cursor % len(items)
            items = items[start:] + items[:start]
            self._scrub_cursor = start + limit
        for anchor, bucket in items:
            if limit is not None and out["lines"] >= limit:
                break
            feeds = []
            diverged = injected = False
            if dispatch_mu is not None:
                dispatch_mu.acquire()
            try:
                for k, v in list(bucket.items()):
                    if isinstance(v, dict) and "flat" in v and \
                            v.get("digests") is not None:
                        if fail_point("device::feed_corrupt") \
                                is not None:
                            # the injected fault: a bit flips on a
                            # resident plane (HBM corruption); this
                            # pass must catch it
                            runner._feeds.corrupt_resident_plane(v)
                            injected = True
                        feeds.append((v["flat"], v["digests"],
                                      v.get("n_live", 0)))
                if injected:
                    # we just flipped a bit on the LIVE feed: hash and
                    # quarantine before the lock drops, so no racing
                    # query can dispatch over the corrupted plane —
                    # zero wrong results by construction
                    diverged = hash_feeds(feeds)
                    if diverged:
                        self._quarantine(runner, anchor, out)
            finally:
                if dispatch_mu is not None:
                    dispatch_mu.release()
            if not injected:
                # single-device: hash outside the lock (concurrent jit
                # launches are safe there).  Sharded mesh: multi-device
                # launch interleaving can deadlock (the dispatch
                # lock's reason to exist), so the digest dispatches
                # serialize under it — a brief, bounded hold per line.
                serialize = dispatch_mu is not None and \
                    not getattr(runner, "_single", True)
                if serialize:
                    dispatch_mu.acquire()
                try:
                    diverged = hash_feeds(feeds)
                finally:
                    if serialize:
                        dispatch_mu.release()
                if diverged:
                    self._quarantine(runner, anchor, out)
            if not feeds:
                continue
            out["lines"] += 1
            if diverged:
                out["divergences"] += 1
                DEVICE_SCRUB_COUNTER.labels("divergence").inc()
            else:
                DEVICE_SCRUB_COUNTER.labels("clean").inc()
        self._record_scrub(out, time.perf_counter() - t0)
        return out

    def _quarantine(self, runner, anchor, out: dict) -> None:
        region = getattr(anchor, "region_hint", None)
        if region is not None:
            out["quarantined_regions"].append(region)
        runner.quarantine(anchor, reason="scrub divergence")
        with self._mu:
            self.quarantines += 1

    def _record_scrub(self, out: dict, elapsed_s: float) -> None:
        out["ms"] = round(elapsed_s * 1e3, 3)
        with self._mu:
            self.scrub_passes += 1
            self.scrub_divergences += out["divergences"]
            self._last_scrub = dict(out)

    # -- background thread --------------------------------------------

    def start(self) -> None:
        if self._interval <= 0 or self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="device-scrub")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            try:
                self.scrub()
            except Exception:   # noqa: BLE001 — scrub must never crash
                import logging
                logging.getLogger(__name__).warning(
                    "device scrub pass failed", exc_info=True)
            # half-open probing for quarantined mesh slices rides the
            # same cadence: a re-admission must not wait for traffic
            # (the on-route probes) when the node has gone idle
            probe = getattr(self._runner, "probe_quarantined", None)
            if callable(probe):
                try:
                    probe()
                except Exception:   # noqa: BLE001 — same contract
                    import logging
                    logging.getLogger(__name__).warning(
                        "slice probe pass failed", exc_info=True)

    # -- observability ------------------------------------------------

    def stats(self) -> dict:
        with self._mu:
            out = {
                "scrub_passes": self.scrub_passes,
                "scrub_divergences": self.scrub_divergences,
                "quarantines": self.quarantines,
                "lifecycle_invalidations": self.lifecycle_invalidations,
                "replica_feeds": len(self._replica_feed_regions),
                "promotions": self.promotions,
                "promotion_rebuilds": self.promotion_rebuilds,
                "demotions": self.demotions,
                "splits": self.splits,
                "split_fallbacks": self.split_fallbacks,
                "last_scrub": dict(self._last_scrub),
            }
        if self.remint_governor is not None:
            out["remint"] = self.remint_governor.stats()
        if self._runner is not None and hasattr(self._runner,
                                                "hbm_stats"):
            out["hbm"] = self._runner.hbm_stats()
        return out
