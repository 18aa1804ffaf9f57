"""Hot-region → mesh-slice placement (the PD loop, one level down).

A multi-chip node has two ways to use its mesh (parallel/mesh.py):
shard one big feed over every chip (scale-up — a single request's
kernel runs as per-shard partials + tree-reduce), or pin many small
regions' feeds to single-device slices (scale-out — many concurrent
requests each run whole on one chip).  Left alone, the second mode
degenerates: every region lands wherever the runner happens to live
and one chip saturates while seven idle — exactly the hot-store
problem PD's balance-region scheduler exists to prevent.

:class:`SlicePlacer` closes that loop locally.  It owns one
single-device sub-runner per mesh slice and routes each feed anchor
(region lineage / snapshot) to a slice chosen by the PD policy
(pd/scheduler.pick_slice) over a blended score:

- **occupancy** — the slice arena's resident HBM bytes (PR 6's
  accounting), normalized across slices; and
- **load** — a decayed per-slice dispatch rate (PR 3's slow-score
  discipline: recent traffic dominates, history fades), so a Zipfian
  mix's hot regions spread by the traffic they actually draw, not
  just by bytes.

Placement is STICKY (a placed anchor keeps its slice — its HBM feed,
request memos, and compile classes live there) until the opportunistic
rebalance step (pd/scheduler.rebalance_donor) finds the spread
unjustifiable; then the hottest slice's coldest anchor MIGRATES to the
coolest slice over ICI (:meth:`SlicePlacer.migrate`): its resident
feeds travel between chips via ``device_put`` with their lineage
versions and scrub digests, the destination re-verifies every plane on
arrival before it serves, and only when migration is impossible (no
digests, arrival divergence) does the move degrade to the old
drop-and-re-mint over the narrow host link.  Feeds above ``whole_mesh_rows`` bypass
placement and shard over the full mesh (scale-up wins past the point
where one chip's HBM pass dominates the launch overhead).

A slice is NOT assumed healthy forever.  The placer shares the
runner's :class:`~.supervisor.SliceHealthBoard` (dispatch/fetch
faults, scrub quarantines and latency outliers strike per-slice
scores, PR 3's slow-store shape): a QUARANTINED slice stops being
scored — ``pick_slice`` excludes it, and its sticky anchors DRAIN
onto healthy slices (spread via ``pd.scheduler.drain_receivers``, the
evict-slow-store shape) by ICI migration first: the condemned chip's
planes usually still verify, so the drain is a device copy per feed,
not a recovery storm of host re-mints.  A feed that fails arrival
verify (or carries no digests) drops through the PR 6 retirement path
instead, and the draining slice's joiner build-side dictionaries
retire explicitly so its HBM frees immediately.  Routing that still
finds an anchor pinned to a dead slice fails it over on the spot.  Half-open canary
probes re-admit the slice with a DECAYED (not reset) score, so the
health penalty in the placement blend lets anchors trickle back —
never a thundering re-pin.

JOIN CO-LOCATION (plan IR, copr/plan_ir.py): every served join plan
records its two feed anchors as a decayed PAIR FREQUENCY
(:meth:`SlicePlacer.note_join`).  Once a pair's affinity clears
``COLOCATE_AFFINITY``, a new placement for either anchor pins to the
other's slice instead of the coolest one — "these two regions join
often" expressed in the same decayed-score vocabulary as load — so
the device hash join's build dictionary and probe feed co-reside and
the probe dispatch mints zero cross-slice transfers.

The placer is OFF by default (``DeviceRunner(placement=False)``) —
single-chip deployments and whole-mesh benches never pay the routing
indirection; ``coprocessor.device_placement`` turns it on for serving
nodes.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Optional

from ..parallel import make_mesh, mesh_slices
from ..pd.scheduler import (
    drain_receivers,
    pick_slice,
    rebalance_donor,
    slice_scores,
)
from .feed import anchor as feed_anchor

# feeds at or above this many rows shard over the WHOLE mesh instead of
# pinning to one slice: one chip's HBM pass over 4M+ rows costs more
# than the cross-chip launch + tree-reduce overhead it would save
DEFAULT_WHOLE_MESH_ROWS = 1 << 22

# decayed-load half life in seconds: recent dispatches dominate the
# traffic score, minutes-old history fades (the slow-score shape)
LOAD_HALFLIFE_S = 30.0

# run the rebalance check every N routed requests — placement decisions
# stay O(1) per request, the O(slices·anchors) scan amortizes
REBALANCE_EVERY = 64

# decayed pair-frequency (served join plans, copr/plan_ir.py) above
# which two anchors are treated as a JOIN PAIR: a new placement for
# one prefers the other's slice, so the device join's build and probe
# feeds co-reside and the probe dispatch mints zero cross-slice
# transfers.  Decays with the same half-life as the load score.
COLOCATE_AFFINITY = 2.0


class SlicePlacer:
    """Per-slice sub-runners + the placement policy over them.

    ``parent`` is the whole-mesh :class:`DeviceRunner`; sub-runners are
    built from its mesh's single-device slices with the parent's tuning
    (chunk override, capacities, per-slice share of the HBM budget).
    """

    def __init__(self, parent, whole_mesh_rows: int =
                 DEFAULT_WHOLE_MESH_ROWS):
        self._parent = parent
        self.whole_mesh_rows = whole_mesh_rows
        self._mu = threading.Lock()
        self._slices = [parent._make_slice_runner(make_mesh(devs),
                                                  slice_indices=(i,),
                                                  bind_health=True)
                        for i, devs in
                        enumerate(mesh_slices(parent._mesh))]
        if parent._arena.budget_bytes > 0:
            # a budget passed at parent CONSTRUCTION must bind the
            # slices too, not only the set_hbm_budget() path
            self.set_hbm_budget(parent._arena.budget_bytes)
        self._load = [0.0] * len(self._slices)
        self._load_t = time.monotonic()
        # id(anchor) -> slice index; weakref finalizers prune entries
        # for anchors that die without an explicit drop
        self._placed: dict[int, int] = {}
        self._refs: dict[int, object] = {}
        self._routes = 0
        self.places = 0
        self.moves = 0
        self.whole_mesh_routes = 0
        # co-location hints: decayed pair-frequency of anchors that
        # JOIN each other (note_join, fed by served join plans) —
        # placement prefers pinning a join pair to ONE slice
        self._pair_aff: dict[tuple[int, int], float] = {}
        self._pair_t = time.monotonic()
        self.colocation_pins = 0
        # chip failure domains: the parent's health board scores these
        # same slices; a trip drains the dead slice's anchors here
        self._board = parent._board
        self.failovers = 0
        self.drained = 0
        # ICI feed migration (the move path that skips the host link):
        # total moves, cumulative/last wall time, children adopted at
        # device-side splits, and moves that degraded to drop+re-mint
        self.migrations = 0
        self.migration_ms = 0.0
        self.last_migration_ms = 0.0
        self.migration_failures = 0
        self.adoptions = 0
        if self._board is not None:
            self._board.add_trip_listener(self._on_slice_trip)

    def __len__(self) -> int:
        return len(self._slices)

    @property
    def slices(self) -> list:
        return list(self._slices)

    # -- scoring ------------------------------------------------------

    def _decay_locked(self) -> None:
        now = time.monotonic()
        dt = now - self._load_t
        if dt <= 0:
            return
        f = 0.5 ** (dt / LOAD_HALFLIFE_S)
        self._load = [v * f for v in self._load]
        self._load_t = now

    def _scores_locked(self) -> list:
        self._decay_locked()
        occ = {i: r._arena.resident_bytes()
               for i, r in enumerate(self._slices)}
        mx_b = max(occ.values(), default=0) or 1
        mx_l = max(self._load, default=0.0) or 1.0
        scores = slice_scores({i: b / mx_b for i, b in occ.items()},
                              {i: v / mx_l
                               for i, v in enumerate(self._load)},
                              len(self._slices))
        if self._board is not None:
            # health penalty: a freshly-readmitted slice carries a
            # decayed-but-high strike score, so new placements trickle
            # back instead of thundering onto a chip that just flapped
            scores = [s + self._board.penalty(i)
                      for i, s in enumerate(scores)]
        return scores

    def _dead_locked(self) -> frozenset:
        return self._board.quarantined_set() \
            if self._board is not None else frozenset()

    # -- co-location hints (served join plans → pair affinity) --------

    def _decay_pairs_locked(self) -> None:
        now = time.monotonic()
        dt = now - self._pair_t
        if dt <= 0:
            return
        f = 0.5 ** (dt / LOAD_HALFLIFE_S)
        if f < 0.999:
            self._pair_aff = {k: v * f
                              for k, v in self._pair_aff.items()
                              if v * f > 0.05}
            self._pair_t = now

    def note_join(self, a, b) -> None:
        """Record one served join between anchors ``a`` and ``b`` —
        the decayed pair frequency the placement blend reads as 'these
        two regions join often, pin them together'.  The affinity
        CROSSING the co-location threshold while both anchors sit on
        different healthy slices triggers an active pull: one side's
        feeds migrate over ICI to the other's slice, so an
        already-placed hot pair co-resides without waiting for a drop
        or an LRU eviction to re-place it."""
        if a is b:
            return
        key = (min(id(a), id(b)), max(id(a), id(b)))
        pull = None
        with self._mu:
            self._decay_pairs_locked()
            old = self._pair_aff.get(key, 0.0)
            self._pair_aff[key] = old + 1.0
            if old < COLOCATE_AFFINITY <= old + 1.0:
                ia = self._placed.get(id(a))
                ib = self._placed.get(id(b))
                dead = self._dead_locked()
                if ia is not None and ib is not None and ia != ib and \
                        ia not in dead and ib not in dead:
                    pull = (a, ia, ib)
            while len(self._pair_aff) > 256:
                # drop the weakest OTHER pair — never the pair just
                # recorded, or at capacity a new hot pair would be
                # evicted in the same call forever and its affinity
                # could never accumulate past the co-location threshold
                weakest = min((k for k in self._pair_aff if k != key),
                              key=self._pair_aff.get)
                del self._pair_aff[weakest]
        if pull is not None and self.migrate(*pull, reason="colocate"):
            from ..utils import metrics as m
            m.DEVICE_PLACEMENT_COUNTER.labels("colocate").inc()
            with self._mu:
                self.colocation_pins += 1

    def _partner_slice_locked(self, key: int,
                              dead: frozenset) -> Optional[int]:
        """The strongest join partner's placed slice (affinity ≥
        COLOCATE_AFFINITY, partner placed, slice healthy) — where a
        new placement for ``key`` should land."""
        self._decay_pairs_locked()
        best, best_aff = None, COLOCATE_AFFINITY
        for (a, b), aff in self._pair_aff.items():
            if aff < best_aff:
                continue
            other = b if a == key else (a if b == key else None)
            if other is None:
                continue
            idx = self._placed.get(other)
            if idx is not None and idx not in dead:
                best, best_aff = idx, aff
        return best

    def colocated(self, a, b) -> bool:
        """Are both anchors currently pinned to ONE healthy slice?"""
        with self._mu:
            ia = self._placed.get(id(a))
            ib = self._placed.get(id(b))
            return ia is not None and ia == ib and \
                ia not in self._dead_locked()

    # -- routing ------------------------------------------------------

    def route(self, storage, n_hint: Optional[int] = None):
        """→ the runner that should serve this request: a placed slice
        sub-runner, or the whole-mesh parent for large feeds and
        untrackable anchors."""
        from ..utils import metrics as m
        anchor = feed_anchor(storage)
        if n_hint is None:
            est = getattr(storage, "estimated_rows", None)
            if callable(est):
                try:
                    n_hint = est()
                except Exception:   # noqa: BLE001 — hint only
                    n_hint = None
        if n_hint is not None and n_hint >= self.whole_mesh_rows:
            key = id(anchor)
            with self._mu:
                self.whole_mesh_routes += 1
                # an anchor that GREW past the threshold graduates to
                # the whole mesh: its stale slice feed would otherwise
                # sit unpatched (and unevicted under no budget) forever
                idx = self._placed.pop(key, None)
                self._refs.pop(key, None)
            if idx is not None:
                self._slices[idx].drop_feed(anchor, reason="placement")
            m.DEVICE_PLACEMENT_COUNTER.labels("whole_mesh").inc()
            return self._parent
        # half-open probing rides routing: a quarantined slice whose
        # cooldown elapsed gets its canary now (bounded by the board's
        # per-slice probe gate — cheap when nothing is due)
        self._parent.probe_quarantined()
        key = id(anchor)
        failover_from = None
        with self._mu:
            dead = self._dead_locked()
            idx = self._placed.get(key)
            if idx is not None and idx in dead and \
                    len(dead) < len(self._slices):
                # the anchor's slice died since it was placed (or the
                # trip-time drain raced this request): fail it over to
                # a healthy slice NOW — its feed rebuilds there.
                # Total mesh death keeps the pin instead: pick_slice's
                # all-excluded fallback would just re-pin onto another
                # dead slice every request (a failover storm in the
                # counters); the refusal gate host-serves until a
                # probe re-admits something
                failover_from = idx
                idx = None
            if idx is None:
                # co-location hint first: a join pair's new member
                # lands on its partner's slice (decayed affinity from
                # served join plans), score-blind by design — the join
                # saves more than a marginally cooler chip would
                idx = self._partner_slice_locked(key, dead)
                if idx is not None:
                    self.colocation_pins += 1
                    m.DEVICE_PLACEMENT_COUNTER.labels("colocate").inc()
                else:
                    idx = pick_slice(self._scores_locked(), exclude=dead)
                try:
                    self._refs[key] = weakref.ref(
                        anchor, lambda _r, k=key: self._forget(k))
                except TypeError:
                    return self._parent      # untrackable anchor
                self._placed[key] = idx
                if failover_from is None:
                    self.places += 1
                    m.DEVICE_PLACEMENT_COUNTER.labels("place").inc()
                else:
                    self.failovers += 1
            self._load[idx] += 1.0
            self._routes += 1
            rebalance = self._routes % REBALANCE_EVERY == 0
        if failover_from is not None:
            self._slices[failover_from].drop_feed(anchor,
                                                  reason="failover")
            m.DEVICE_FAILOVER_COUNTER.labels("failover").inc()
        if rebalance:
            self.rebalance()
            # the balance step may have moved THIS anchor: serving it
            # from the slice picked above would re-upload a copy the
            # pin no longer points at (and no drain ever finds)
            with self._mu:
                idx = self._placed.get(key, idx)
        return self._slices[idx]

    def owner(self, anchor):
        """The sub-runner currently holding ``anchor``, or None."""
        with self._mu:
            idx = self._placed.get(id(anchor))
        return None if idx is None else self._slices[idx]

    def _forget(self, key: int) -> None:
        with self._mu:
            self._placed.pop(key, None)
            self._refs.pop(key, None)
            # a dead anchor's join-pair affinities die with it: a NEW
            # object reusing the id must never inherit another
            # region's co-location hint (same id-reuse guard as the
            # joiner's weakref pruning)
            if self._pair_aff:
                self._pair_aff = {k: v
                                  for k, v in self._pair_aff.items()
                                  if key not in k}

    def forget(self, anchor) -> None:
        self._forget(id(anchor))

    # -- ICI feed migration -------------------------------------------

    def migrate(self, anchor, src: int, dst: int,
                reason: str = "placement") -> bool:
        """Move ``anchor``'s resident feeds from slice ``src`` to
        ``dst`` over the device interconnect → True when the
        destination serves the moved feeds.

        The feeds travel with their lineage versions and scrub
        digests (``extract_feeds``); the destination re-hashes every
        plane on arrival BEFORE installing (``install_feeds``) — a
        divergent plane quarantines the source copy and the move
        reports False so the caller falls back to drop+re-mint from
        host truth.  In-flight requests need no rescue choreography:
        the source feeds are not dropped until after the pin flips,
        and a request that raced onto the destination and re-minted a
        NEWER generation there is never clobbered by the arriving
        copy."""
        from ..utils import metrics as m
        from ..utils import tracker
        if src == dst or not (0 <= src < len(self._slices)) or \
                not (0 <= dst < len(self._slices)):
            return False
        src_r, dst_r = self._slices[src], self._slices[dst]
        t0 = time.perf_counter()
        with tracker.phase("feed_migrate"):
            try:
                feeds, skipped = src_r._feeds.extract_feeds(anchor)
            except Exception:   # noqa: BLE001 — migration is best-effort
                feeds, skipped = None, 0
            if not feeds:
                m.DEVICE_FEED_MIGRATION_COUNTER.labels(
                    "no_digests").inc()
                with self._mu:
                    self.migration_failures += 1
                return False
            try:
                verdict = dst_r._feeds.install_feeds(anchor, feeds)
            except Exception:   # noqa: BLE001 — same contract
                verdict = "corrupt"
            if verdict != "moved":
                # arrival verify caught divergence: never serve it —
                # drop whatever landed and condemn the source copy
                # (quarantine-and-rebuild, the scrub discipline)
                dst_r.drop_feed(anchor, reason="migrate_verify")
                try:
                    src_r.quarantine(anchor, reason="migrate divergence")
                except Exception:   # noqa: BLE001
                    pass
                m.DEVICE_FEED_MIGRATION_COUNTER.labels("corrupt").inc()
                with self._mu:
                    self.migration_failures += 1
                return False
            key = id(anchor)
            ms = (time.perf_counter() - t0) * 1e3
            with self._mu:
                if key not in self._placed:
                    try:
                        self._refs[key] = weakref.ref(
                            anchor, lambda _r, k=key: self._forget(k))
                    except TypeError:
                        pass    # untrackable: feeds moved, pin didn't
                if key in self._refs:
                    self._placed[key] = dst
                self.migrations += 1
                self.migration_ms += ms
                self.last_migration_ms = ms
        # the pin now points at dst: drop the source copy LAST so a
        # dispatch already in flight on src finishes against resident
        # planes (arena pins keep them alive through the kernel)
        src_r.drop_feed(anchor, reason=reason)
        m.DEVICE_FEED_MIGRATION_COUNTER.labels(
            "partial" if skipped else "moved").inc()
        return True

    def adopt(self, parent, children) -> None:
        """Pin device-split children to their parent's slice.  The
        child feeds were sliced from the parent's resident planes ON
        that slice (split_stash), so the children's first requests
        must route there to consume them — anywhere else re-uploads
        from host."""
        from ..utils import metrics as m
        with self._mu:
            idx = self._placed.get(id(parent))
            if idx is None or idx in self._dead_locked():
                return
            n = 0
            for ch in children:
                if ch is None:
                    continue
                k = id(ch)
                try:
                    self._refs[k] = weakref.ref(
                        ch, lambda _r, kk=k: self._forget(kk))
                except TypeError:
                    continue
                self._placed[k] = idx
                n += 1
            self.adoptions += n
        if n:
            m.DEVICE_PLACEMENT_COUNTER.labels("adopt").inc(n)

    # -- failure-domain drain -----------------------------------------

    def _on_slice_trip(self, idx: int, reason: str) -> None:
        """Board trip listener: drain every anchor stuck to the dead
        slice — MIGRATE each onto a healthy slice over ICI
        (least-loaded-first round-robin via ``drain_receivers``, the
        evict-slow-store spread, NOT a single-receiver dump).  A
        condemned chip's planes usually still verify, so the drain is
        a device copy per feed and the receivers serve warm; a feed
        that can't travel (no digests, arrival divergence) drops
        through the retirement path and its next request rebuilds cold
        — answers stay correct throughout because a rebuild is just a
        cold hit.  The dead slice's joiner build-side dictionaries
        retire explicitly too: waiting for weakref GC would strand
        HBM on a chip the budget still accounts."""
        from ..utils import metrics as m
        with self._mu:
            victims = [k for k, v in self._placed.items() if v == idx]
            if not victims:
                return
            dead = self._dead_locked() | {idx}
            targets = drain_receivers(self._scores_locked(),
                                      exclude=dead, k=len(victims))
            moves = []
            for j, k in enumerate(victims):
                tgt = targets[j] if targets else None
                # no healthy receiver (total mesh death): keep the
                # pin — route-time failover re-pins when a slice
                # re-admits — but the feeds below STILL drop: HBM
                # state on a condemned chip is garbage either way
                ref = self._refs.get(k)
                a = ref() if ref is not None else None
                if a is not None:
                    moves.append((a, tgt))
            self.drained += len(victims)
        for a, tgt in moves:
            with self._mu:
                if self._placed.get(id(a)) != idx:
                    continue    # route-time failover won the race
            moved = False
            if tgt is not None:
                try:
                    moved = self.migrate(a, idx, tgt, reason="failover")
                except Exception:   # noqa: BLE001 — drain must finish
                    moved = False
            if not moved:
                if tgt is not None:
                    with self._mu:
                        if self._placed.get(id(a)) == idx:
                            self._placed[id(a)] = tgt
                self._slices[idx].drop_feed(a, reason="failover")
        joiner = getattr(self._slices[idx], "_joiner", None)
        if joiner is not None:
            joiner.drop_all()
        m.DEVICE_FAILOVER_COUNTER.labels("drain").inc(len(victims))

    # -- rebalance ----------------------------------------------------

    def rebalance(self) -> bool:
        """One balance step: when the hottest slice carries an
        unjustifiable share of the blended score, drop its COLDEST
        anchor's feed and re-pin the anchor to the coolest slice (the
        next request rebuilds there).  Coldest-first keeps the move
        cheap — the hot anchor's warm feed and compile classes stay
        put, mirroring how PD drains a hot store by moving replicas,
        not leaders, first.  Returns True when a move happened."""
        from ..utils import metrics as m
        with self._mu:
            pair = rebalance_donor(self._scores_locked(), min_ratio=2.0,
                                   min_gap=0.25)
            if pair is None:
                return False
            hot, cool = pair
            if cool in self._dead_locked():
                # never balance ONTO a quarantined slice (its health
                # penalty usually keeps it off the cool end, but a
                # fully-loaded mesh can tie) — the drain already moved
                # its anchors the other way
                return False
            donor = self._slices[hot]
            victim = None
            v_stats = None
            for anchor, nbytes, hits, tick, pins in \
                    donor._arena.entry_stats():
                if pins > 0 or self._placed.get(id(anchor)) != hot:
                    continue
                st = (hits, tick)
                if v_stats is None or st < v_stats:
                    victim, v_stats = anchor, st
            if victim is None:
                return False
            self.moves += 1
        # outside the lock: the move itself is a device-side ICI copy
        # (verify-on-arrival), falling back to the old drop+re-pin when
        # the feeds can't travel (no digests / divergence)
        if not self.migrate(victim, hot, cool, reason="placement"):
            with self._mu:
                self._placed[id(victim)] = cool
            donor.drop_feed(victim, reason="placement")
        m.DEVICE_PLACEMENT_COUNTER.labels("move").inc()
        return True

    # -- fan-out helpers (parent delegation) --------------------------

    def set_hbm_budget(self, parent_budget: int) -> None:
        """Per-slice share of the node budget: slices split it evenly
        (each owns a disjoint anchor set), the parent keeps the full
        figure for whole-mesh feeds."""
        share = parent_budget // len(self._slices) \
            if parent_budget > 0 else 0
        for r in self._slices:
            r.set_hbm_budget(share)

    # -- observability ------------------------------------------------

    def publish_metrics(self) -> None:
        from ..utils import metrics as m
        with self._mu:
            self._decay_locked()
            loads = list(self._load)
        for i, r in enumerate(self._slices):
            m.DEVICE_SLICE_RESIDENT_BYTES.labels(str(i)).set(
                r._arena.resident_bytes())
            m.DEVICE_SLICE_LOAD.labels(str(i)).set(round(loads[i], 3))

    def stats(self) -> dict:
        self.publish_metrics()
        with self._mu:
            loads = [round(v, 3) for v in self._load]
            placed = [0] * len(self._slices)
            for idx in self._placed.values():
                if 0 <= idx < len(placed):
                    placed[idx] += 1
            dead = self._dead_locked()
            out = {
                "slices": [
                    {"resident_bytes": r._arena.resident_bytes(),
                     "resident_lines": r._arena.resident_lines(),
                     "load": loads[i],
                     "placed_anchors": placed[i],
                     "quarantined": i in dead}
                    for i, r in enumerate(self._slices)],
                "places": self.places,
                "moves": self.moves,
                "whole_mesh_routes": self.whole_mesh_routes,
                "failovers": self.failovers,
                "drained": self.drained,
                "colocation_pins": self.colocation_pins,
                "join_pairs": len(self._pair_aff),
                "migrations": self.migrations,
                "migration_ms": round(self.migration_ms, 3),
                "last_migration_ms": round(self.last_migration_ms, 3),
                "migration_failures": self.migration_failures,
                "adoptions": self.adoptions,
            }
        return out
