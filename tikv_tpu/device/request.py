"""What a device request is made of, for the runner and its operators.

The runner (device/runner.py) analyses a DAG into a ``_Plan``, hands
it to an operator module (device/aggregate.py, device/selection.py,
device/join.py), and gets back a ``_Pending``: the launched program's
output still on the device plus the host finalize for it.  Any of them
raises ``_FallbackToHost`` when a runtime property (not the plan)
forces the host path.  These types, the stager a ``_Pending`` lands
its leaves through, and the three pure RPN helpers plan analysis
shares with the join live here, below both sides: ``runner.py``
imports its operators, and no operator module imports the runner.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax

from ..copr.dag import TableScanDesc
from ..datatype import EvalType
from ..expr.rpn import RpnColumnRef, RpnConst, RpnExpression, RpnFnCall
from .kernels import named_program


class _FallbackToHost(Exception):
    """Raised when a runtime property (not the plan) forces the host path."""


def _fp_degrade(name: str) -> None:
    """Failpoint site that degrades to the host backend: a fired
    ``return`` action raises _FallbackToHost, so an injected device
    fault (or a real one steered in tests) downgrades the query instead
    of failing it — the runner's existing fallback machinery catches it.
    """
    from ..utils.failpoint import fail_point
    if fail_point(name) is not None:
        raise _FallbackToHost(name)


#  DATETIME (packed u64 core — the bit layout is order-preserving) and
#  DURATION (i64 ns) are device-native dense columns: comparisons, topN
#  and min/max/count ride the same kernels as INT.  Years >= 8192 pack
#  above 2^63 and would corrupt the int64 carries — the feed guard
#  routes such columns to host.
#  DECIMAL is not among them: a DECIMAL column whose FieldType fixes
#  its scale reaches the device as a scaled INTEGER plane, and plan
#  analysis lowers decimal RPN over it to integer RPN before this gate
#  sees it (device/lowering.py); what cannot be lowered stays host.
#  Nor is BYTES: a CHAR column of at most four bytes under a binary or
#  ``_bin`` collation reaches the device as its int32 CODE plane
#  (datatype/tile.py code_plane) the same way, typed INT by the time
#  this gate sees it; a wider string or a function of one stays host.
_DEVICE_ETS = (EvalType.INT, EvalType.REAL, EvalType.DATETIME,
               EvalType.DURATION)


def _rpn_col_indices(rpn: RpnExpression) -> set:
    return {n.col_idx for n in rpn.nodes if isinstance(n, RpnColumnRef)}


def _remap_rpn(rpn: RpnExpression, mapping: dict) -> RpnExpression:
    nodes = []
    for n in rpn.nodes:
        if isinstance(n, RpnColumnRef):
            nodes.append(RpnColumnRef(mapping[n.col_idx], n.eval_type))
        else:
            nodes.append(n)
    return RpnExpression(tuple(nodes))


def _rpn_device_safe(rpn: RpnExpression, scan_ets: Sequence[EvalType]) -> bool:
    for n in rpn.nodes:
        if isinstance(n, RpnConst):
            # (a Decimal constant the lowering took is an int by now)
            if n.value is not None and not isinstance(n.value, (int, float, bool)):
                return False
        elif isinstance(n, RpnColumnRef):
            # ``scan_ets``: the eval types of the PLANES, INT for a
            # column the lowering put on a scaled or a date plane
            if n.col_idx >= len(scan_ets) or scan_ets[n.col_idx] not in _DEVICE_ETS:
                return False
        elif isinstance(n, RpnFnCall):
            if n.meta.ret not in _DEVICE_ETS:
                return False
            if not n.meta.device_safe:
                # raw-numpy sig bodies (time extractors, string/json
                # families) crash on jit tracers — only pure-xp sigs
                # may enter a device plan; everything else runs host
                return False
    return True


@dataclass
class _Plan:
    """Analyzed device plan (rpns remapped onto ``used_cols`` positions)."""

    scan: TableScanDesc
    kind: str                        # scan | simple_agg | hash_agg | topn
    used_cols: list                  # original scan column offsets shipped to device
    sel_rpns: list = field(default_factory=list)
    specs: list = field(default_factory=list)        # AggSpec per agg
    agg_rpns: list = field(default_factory=list)     # RpnExpression | None
    order_rpn: Optional[RpnExpression] = None
    order_desc: bool = False
    limit: int = 0
    # scan_sel only: every scan column rides the feed in a lossless
    # device dtype, so the compact route may materialize the output on
    # device (selection.py routing matrix)
    compact_ok: bool = False
    # lazy (param_rpns, values, dtypes) from selection.split_params
    sel_params: Optional[tuple] = None
    # lazy const-blind stat key (runner._sel_keys)
    sel_stat_key: Optional[tuple] = None
    # lazy (result FieldTypes, container dtypes) of ``specs``
    # (aggregate.py DeviceAggregator._agg_out)
    agg_out: Optional[tuple] = None
    # device/lowering.py: per aggregate the scale its SUM comes back as
    # a DECIMAL at (None: not a lowered DECIMAL); per used column
    # whether it rides as the int32 date plane; whether anything was
    # lowered (then ``lowering.fits`` must hold for the feed)
    agg_fracs: list = field(default_factory=list)
    date_planes: tuple = ()
    lowered: bool = False
    # lazy: the aggregation's constants as kernel operands,
    # (param sel_rpns, param agg_rpns, values, dtypes)
    # (aggregate.py agg_params)
    agg_params: Optional[tuple] = None
    # every GROUP BY key (several: the composite key, aggregate.py
    # ``run_hash``); per key the bytes of its CHAR code plane (0: an
    # integer key), per used column likewise (device/lowering.py)
    key_rpns: list = field(default_factory=list)
    key_codes: tuple = ()
    code_planes: tuple = ()
    # per aggregate of the plan which of ``specs``, the DEVICE's
    # aggregates, it is: an index or a limb pair's (hi, lo), put
    # together by the finalize (lowering.recipe_planes); None: each its
    # own; the limb-split aggregates of this variant (lowering.fit);
    # and the plan's variants by that tuple (runner ``_limb_variant``)
    agg_recipes: Optional[list] = None
    limbs: tuple = ()
    variants: dict = field(default_factory=dict)
    # lazy: pallas_hash.key_consts
    ident: Optional[tuple] = None


class _PinnedStager:
    """Pre-registered pinned-host D2H landing buffers.

    On TPU the blocking half of a readback is ``np.asarray(x)``: the
    runtime allocates fresh host memory and synchronously drains the
    transfer into it, per request.  This stager instead appends a
    jitted identity program with ``out_shardings`` pinned to the
    device's ``pinned_host`` memory space to the DISPATCH stream: the
    device→host copy executes asynchronously as part of the launch
    train, lands in runtime-managed pinned (page-locked) host buffers,
    and the later ``np.asarray`` at fetch time reads settled host
    memory instead of paying the sync round trip.  One staging program
    is compiled per (shape, dtype, device) — shapes are already
    pow2/9-8-geometric capacity buckets (feed.py ``pad_rows``), so the
    registration set is bounded exactly like the feed compile classes.

    Probed once per shape class: a backend that cannot run the
    placement program disables the stager and the readback path is
    unchanged.  (The CPU backend of JAX 0.9.0 lists ``pinned_host`` and
    ``unpinned_host`` on its devices but has no
    ``annotate_device_placement`` implementation, so the probe fails
    there: ``probed: true, enabled: false``.)  Sharded leaves pass
    through, and so does a leaf past ``MAX_BYTES``: the landing buffers
    are for the KBs of an aggregation's states.  For outputs of 0.8-3 MB
    (a 16,384-slot grid's accumulator, 786 KB a lane) the runtime maps
    fresh DMA buffers inside the serving window (``MapDmaBuffer``, tens
    of ms with the device idle) and a fetch waited 8-14 ms where the
    plain transfer waits 1.4-2.1 (PERF.md section 6, PR 40).
    """

    _MAX_CLASSES = 256
    # the largest leaf that lands in pinned memory: every accumulator of
    # a grid up to 4,096 slots (a 4-lane launch of TPC-H Q1's is 221 KB)
    MAX_BYTES = 1 << 19

    def __init__(self, memory_kind: str = "pinned_host"):
        # "pinned_host" on TPU; tests pass "unpinned_host" to drive the
        # mechanics wherever the backend can run the placement program
        self.memory_kind = memory_kind
        self._mu = threading.Lock()
        self._fns: dict = {}        # class key -> jitted fn | None
        self.enabled: Optional[bool] = None     # None = unprobed
        self.probe_error = ""       # why the probe disabled the stager
        self.staged = 0
        self.staged_bytes = 0
        self.classes = 0

    def _fn_for(self, x):
        try:
            if x.nbytes > self.MAX_BYTES:
                return None         # fetched from where it lies
            sharding = x.sharding
            devices = getattr(sharding, "_device_assignment", None) or \
                tuple(sharding.device_set)
            if len(devices) != 1:
                return None         # sharded leaf: leave to GSPMD
            if getattr(sharding, "memory_kind", None) == self.memory_kind:
                return None         # its program put it there already
            dev = devices[0]
            key = (x.shape, str(x.dtype), dev.id)
        except Exception:   # noqa: BLE001 — not a jax array
            return None
        with self._mu:
            if key in self._fns:
                return self._fns[key]
            if len(self._fns) >= self._MAX_CLASSES:
                # registration full: pass the leaf through rather than
                # compiling (and immediately forgetting) a staging
                # program per request — the cap is a backstop far above
                # the bucketed shape population, so hitting it means a
                # shape explosion, not a workload to optimize
                return None
        fn = None
        try:
            from jax.sharding import SingleDeviceSharding
            out = SingleDeviceSharding(dev, memory_kind=self.memory_kind)
            fn = jax.jit(named_program(lambda a: a, "pinned_stage"),
                         out_shardings=out)
            fn(x)                   # probe: compiles + runs once
            self.enabled = True
        except Exception as e:  # noqa: BLE001 — placement unsupported
            fn = None
            if self.enabled is None:
                self.enabled = False
                self.probe_error = f"{type(e).__name__}: {e}"[:200]
        with self._mu:
            if len(self._fns) < self._MAX_CLASSES:
                self._fns[key] = fn
            if fn is not None:
                self.classes += 1
        return fn

    def stage(self, tree):
        """Stage every single-device leaf of ``tree`` to pinned host
        memory; leaves that cannot stage pass through untouched."""
        if self.enabled is False:
            return tree

        def one(x):
            fn = self._fn_for(x)
            if fn is None:
                return x
            try:
                y = fn(x)
            except Exception:   # noqa: BLE001 — degrade to direct D2H
                return x
            with self._mu:
                self.staged += 1
                self.staged_bytes += int(getattr(x, "nbytes", 0))
            return y

        return jax.tree.map(one, tree)

    def stats(self) -> dict:
        with self._mu:
            return {"enabled": bool(self.enabled),
                    "probed": self.enabled is not None,
                    "probe_error": self.probe_error,
                    "staged": self.staged,
                    "staged_bytes": self.staged_bytes,
                    "classes": self.classes}


# process-wide: pinned host memory is a per-device runtime resource,
# and the jit cache keys on the concrete device — safe to share across
# runners (slice sub-runners included)
HOST_STAGER = _PinnedStager()


class _Pending:
    """A dispatched device request: output pytree still on device plus
    the host finalize that turns the fetched numpy tree into a
    SelectResult (the ``host_materialize`` phase; for an aggregation
    after a Pallas launch, GROUP BY or not, ``finalize_packed``: ONE
    native call from the fetched accumulator parts to the result
    planes, GIL held throughout, then ``_hash_columns``'s wrap; the
    numpy chain where that call cannot serve, and ``finalize_hash`` /
    ``finalize_simple`` for the XLA bodies' states).  Leaves are staged to
    pinned host memory at construction when the backend supports it
    (:class:`_PinnedStager`)
    and ``copy_to_host_async`` is issued for every leaf, so the D2H
    transfer streams while the caller decides when (and on which
    thread) to block — the seam the async serving path pipelines on.
    ``small``: the fetch is KBs (agg states), so a completion pool may
    prioritize it over bulk candidate readbacks.
    """

    __slots__ = ("tree", "finalize", "small")

    def __init__(self, tree, finalize, small: bool = True):
        tree = HOST_STAGER.stage(tree)
        self.tree = tree
        self.finalize = finalize
        self.small = small
        for x in jax.tree.leaves(tree):
            try:
                x.copy_to_host_async()
            except Exception:   # pragma: no cover - CPU arrays
                pass


@dataclass(slots=True)
class _Prepared:
    """What a warm whole-feed Pallas launch needs, computed ONCE a
    (line, generation, const-blind class) and kept in that request
    memo as ``meta["prepared"]`` (aggregate.DeviceAggregator
    ``_try_pallas`` writes it where it has a built kernel and the whole
    feed of a single-device runner; ``DeviceRunner._refresh_meta`` drops
    it with the generation, the arena's bucket with the line).  A
    request of the class then stages from it (``DeviceRunner.
    _stage_tickets``): its guards, its own operands, its pin.

    ``key`` / ``entry``: the kernel cache key (the launch class
    ``DeviceRunner.launch_class`` tells the coalescer) and the entry it
    named when the record was written; ``run`` / ``bounds``: the built
    kernel of the one tile and ``(row_lo, row_hi, base, blk0)``;
    ``cols``: the feed's kernel inputs, the sparse slot column
    included; ``feed`` / ``flat`` / ``feed_key``: the feed they were cut
    from, its planes as they were and the key it is cached under (a hit
    holds all three to the arena's bucket by identity); ``mode`` /
    ``said``: the slot mode and what the recorder says of the launch;
    ``limbs``: the plan variant in force (``DeviceRunner.
    _limb_variant``); ``key_scalars`` / ``param_dts``: a composite
    key's bounds, which ride ahead of the request's constants, and the
    constants' dtypes the kernel was built for; ``finish(dag, parts,
    LO)`` / ``LO``: the finalize with everything but the request fixed.
    Not a dict, and none of its fields is named as a feed's are: the
    arena counts the ``flat`` and ``sparse_slots`` of a bucket's dict
    values (supervisor ``_bucket_arrays``), and a record holds no byte
    the feed and the memo do not."""

    key: tuple
    entry: dict
    run: object
    bounds: tuple
    cols: tuple
    feed: dict
    flat: tuple
    feed_key: Optional[tuple]
    mode: str
    said: dict
    limbs: tuple
    key_scalars: tuple
    param_dts: tuple
    finish: object
    LO: int

    def lane(self, dag, pvals, prepared: bool) -> "_LanePending":
        """One request of the class, ready to leave: ``pvals`` are ITS
        constants, ``prepared`` whether it was staged from the record
        alone."""
        finish, LO = self.finish, self.LO
        return _LanePending(self, self.key_scalars + tuple(pvals),
                            lambda parts: finish(dag, parts, LO), prepared)


@dataclass(slots=True)
class _Ticket:
    """What ``DeviceRunner.launch_ticket`` resolved for a closed group's
    lead, kept by the coalescer beside the group's launch class and
    handed back with the lane: a prepared hit's ONLY look-up.
    ``klass``: the launch class (``rec.key`` behind the slice prefix);
    ``runner``: the runner (slice) it was resolved on; ``pvals`` /
    ``pdts``: the operands of the request's OWN plan, in the record's
    limb variant, and their dtypes (``pallas_hash.plan_params``);
    ``anchor`` / ``bucket``: the line and the arena's bucket the memo
    was found in; ``meta`` / ``rec``: the memo under the request's OWN
    ranges and the record it held; ``lineage`` / ``req_v``: the
    generation of the request's snapshot.  It decides nothing: a staging
    (``DeviceRunner._stage_tickets``) holds every field to what stands
    then, by identity."""

    klass: tuple
    runner: object
    pvals: tuple
    pdts: tuple
    anchor: object
    bucket: dict
    meta: dict
    rec: _Prepared
    lineage: object
    req_v: Optional[int]


class _LanePending(_Pending):
    """One LANE of a launch of lanes (aggregate.DeviceAggregator
    ``launch_lanes``): an aggregation over a whole feed prepared as a
    request of its own (its own feed, row bounds, snapshot, constants
    and finalize) whose kernel call has not left yet: the dispatcher
    may be staging other closed groups of the same compile class, all
    of which leave as ONE program, and a request alone leaves as a
    launch of one lane.  Until ``launch_lanes`` binds it, it holds what
    its call needs (``rec``: the ``_Prepared`` record of its class;
    ``pvals``: the lane's OWN operands); after, ``launch`` is the
    shared fetch and ``index`` this lane's place in it.  ``prepared``:
    staged from the record alone (counted on the launch).  ``info``:
    the launch's ``_dispatch_phase`` record, for the
    ``device_dispatch`` span every member's trace gets."""

    __slots__ = ("rec", "pvals", "prepared", "launch", "index", "info")

    def __init__(self, rec, pvals, finalize, prepared: bool):
        # no tree yet, nothing to stage: the launch stages its one
        # stacked output
        self.tree = None
        self.finalize = finalize
        self.small = True
        self.rec = rec
        self.pvals = pvals
        self.prepared = prepared
        self.launch = None
        self.index = 0
        self.info = None

    def fetch(self):
        """This lane's packed parts, from the launch's one readback."""
        return [self.launch.fetch()[self.index]]
