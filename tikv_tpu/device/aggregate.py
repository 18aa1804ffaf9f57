"""The aggregation operator of the device backend: GROUP BY and plain
aggregates over a resident feed, from the choice of body to the result
Columns.

``DeviceRunner._handle_local`` builds the feed and calls
``DeviceAggregator.run_hash`` / ``run_simple`` under its dispatch lock;
what comes back is a finished result (a cold build validates in line)
or a ``_Pending`` whose finalize runs after the fetch.  A warm
whole-feed Pallas launch on one device leaves its preparation behind
(``_Prepared``, in the request memo), and the class's next requests are
staged from that without coming here.  Top to bottom:
``agg_bodies`` (which body serves which plan); ``run_hash`` (a GROUP BY
request: key bounds, the sparse recode, layouts, then the bodies in
that order); the Pallas launch; the XLA bodies with their scan program,
carries and merges; ``run_simple``; and, below the class because it
needs no runner, the finalize (``finalize_packed`` and its parts).

The operator owns no device state.  It serves through its runner's
feeds and caches, by these names and no others: ``_is_tpu``,
``_single``, ``_mesh``, ``_row_sharding``, ``_repl``, ``_nshards``,
``_feeds`` (``unit``, ``pick_chunk``), ``_kernel_cache``, ``_shard_kernel``,
``_cached_scalar``, ``_kern_key``, ``_dispatch_phase``, ``_result``,
``_max_hash_capacity``, ``_psum``, ``_shard_index``, ``_eval_masked``,
``flight_recorder``, and for a launch of lanes ``_device_scope`` and
``_readback``.  The arrows point one way: the runner imports this
module, and this module imports nothing from the runner; the types
both need are in device/request.py.

The benchmark reads this module by name: the compile classes of
``agg_bodies``, the program names they become (``jit_<class>``,
``jit_pallas_hash_sharded``), the ``psum`` of the sharded wrap, and the
``shard_merge`` span.
"""

from __future__ import annotations

import logging
import threading
import time
from contextlib import nullcontext
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from .. import native
from ..datatype import Column, EvalType, FieldType
from ..datatype.tile import code_bytes
from ..expr.eval import eval_rpn
from ..expr.rpn import RpnColumnRef
from ..ops.agg import (
    _I64_MAX,
    finalize_hash,
    finalize_simple,
    hash_agg_tile,
    simple_agg_tile,
)
from ..parallel import ROW_AXES
from ..utils import trace
from . import lowering, pallas_hash
from .feed import arg_byte_planes, bare_int_refs, value_plane_index
from .kernels import (
    build_layouts,
    make_planes,
    matmul_supported,
    named_program,
    slot_index,
    states_from_matmul,
    twolevel_dims,
    twolevel_lo,
    twolevel_partial,
    twolevel_unpack,
)
from .request import (
    HOST_STAGER,
    _FallbackToHost,
    _Pending,
    _Plan,
    _Prepared,
)
from .selection import _next_pow2

_log = logging.getLogger(__name__)

# FIRST's "no row seen yet" position
_BIG = np.iinfo(np.int64).max

# rows per lax.scan step of the XLA scatter and simple bodies
_CHUNK_AGG = 1 << 20


def agg_bodies(is_tpu: bool, n_shards: int, plan, feed, dtypes, layouts,
               p8, pf, capacity: int, mode: str, tiled: bool) -> tuple:
    """Which body serves an aggregation: its compile classes, in the
    order they are tried.  Chosen from what the code can observe (the
    platform, the plan's aggregate kinds, the feed's dtypes), never
    from an option.

    ``pallas_hash`` (device/pallas_hash.py, the fused direct-index
    kernel; the only class the benchmark's cells accept) comes first
    where all of this holds: the devices are TPUs; every aggregate is
    COUNT / SUM / AVG (``layouts`` exist: ``kernels.matmul_supported``)
    with no float plane (``pf == 0``); every column the kernel reads is
    int32 with no NULLs; the slots fit VMEM (``pallas_hash.MAX_SLOTS``)
    and the padded feed is whole kernel blocks a shard
    (``pallas_hash.supported``).  ``mode`` is the slot mode: ``dense``
    keys index the grid by ``key - base``, ``sparse`` keys (a span over
    the runner's ``max_hash_capacity``) ride as recoded slot ids,
    ``simple`` has one slot; past 4,096 slots a grid step takes fewer
    rows (``pallas_hash.block_rows``), the same body.  Bucket tiles
    (``tiled``: a request over part of a region's rows) exist on one
    device only.

    Then ONE stand-in, for what the kernel does not take and for a plan
    whose kernel build was refused (``_try_pallas`` returned None):

    - ``hash_twolevel``: COUNT / SUM / AVG whose planes fit one MXU
      lane tile (``kernels.twolevel_lo``): SUM / AVG of a REAL
      argument, nullable or int64 kernel columns, more than MAX_SLOTS
      (16,384) slots, and every such plan off a TPU (what tier-1 runs).
    - ``hash_scatter``: the rest: MIN / MAX / the variance family, or
      planes too wide for the two-level kernel.
    - ``simple``: an aggregation without GROUP BY.

    A tiled request has no stand-in: an empty answer here sends it to
    the host pipeline with its ranges.
    """
    bodies = []
    if layouts is not None and is_tpu and not (tiled and n_shards > 1) \
            and pallas_hash.supported(plan, feed, dtypes, pf, capacity,
                                      n_shards, mode):
        bodies.append("pallas_hash")
    if mode == pallas_hash.MODE_SIMPLE:
        bodies.append("simple")
    elif not tiled:
        bodies.append("hash_twolevel" if layouts is not None and
                      twolevel_lo(p8, pf) is not None else "hash_scatter")
    return tuple(bodies)


class DeviceAggregator:
    """The aggregation over one runner's devices: made by the runner
    as ``DeviceMvccResolver`` and ``DeviceJoiner`` are, one a runner
    (a placement slice or a submesh runner has its own)."""

    def __init__(self, runner):
        self._runner = runner
        # multi-lane launches (``launch_lanes``): what left, by lane
        # count, and what could not leave as one program
        self._lane_mu = threading.Lock()
        self.lanes_hist: dict[int, int] = {}
        self.unbuilt_fallbacks = 0
        self.lane_launch_failures = 0
        self.lane_builds = 0
        self.lane_build_s = 0.0     # the longest build

    # -- hash aggregation --

    def run_hash(self, dag, plan, host_cols, dtypes, n, feed, meta,
                 tile_spans=None, lanes: bool = False,
                 recode: bool = False):
        """One GROUP BY request over ``feed`` → a finished result or a
        ``_Pending``.  ``meta`` is the request's guarded memo (key
        bounds, byte-plane widths and the sparse recode live there);
        ``tile_spans`` the row intervals of a request over part of the
        region's rows (bucket tiles), else None.  ``lanes``: the caller
        stages several requests under one hold of the dispatch lock and
        launches them together (``launch_lanes``): a warm whole-feed
        Pallas launch then comes back prepared and unlaunched, a
        ``_LanePending`` (without it, launched: ``_try_pallas``).
        ``recode``: a composite key whose kernel was refused is on its
        way to a stand-in."""
        runner = self._runner
        composite = len(plan.key_rpns) > 1
        if "hash_bounds" in meta:
            base, span, arg_nbytes = meta["hash_bounds"]
        else:
            if composite:
                # every key's own bounds; the grid is indexed by the
                # keys' mixed-radix number, from 0
                meta["key_bounds"] = self._key_bounds(plan, host_cols(), n)
                base, span = 0, 1
                for _b, s in meta["key_bounds"]:
                    span *= s
            else:
                kv, km = eval_rpn(plan.key_rpns[0], host_cols(), n, np)
                kv = np.broadcast_to(kv, (n,))
                km = np.broadcast_to(km, (n,))
                valid_keys = kv[km]
                if valid_keys.size:
                    base = int(valid_keys.min())
                    span = int(valid_keys.max()) - base + 1
                else:
                    base, span = 0, 1
            arg_nbytes = self._arg_nbytes(plan, host_cols(), n)
            meta["hash_bounds"] = (base, span, arg_nbytes)
            meta.setdefault("n_rows", n)
        key_bounds = meta["key_bounds"] if composite else None
        arg_is_real = [r is not None and r.ret_type is EvalType.REAL
                       for r in plan.agg_rpns]
        # a bare reference to a NOT NULL column has validity ≡ row mask —
        # alias its plane to the mask plane instead of duplicating it
        # through the matmul (cuts config-4's W operand 4→3 planes)
        arg_ok_is_mask = self._arg_ok_is_mask(plan, feed)
        layouts = p8 = pf = None
        if matmul_supported(plan.specs):
            layouts, p8, pf = build_layouts(plan.specs, arg_is_real,
                                            arg_nbytes, arg_ok_is_mask)
        tiled = tile_spans is not None
        dense = span <= runner._max_hash_capacity
        if dense:
            # (a composite key's grid is as tight as the kernel's eight
            # sublanes of slots allow: its spans ride as operands, so a
            # wider product is another kernel only past a power of two)
            capacity = max(256 if composite else 1024, _next_pow2(span))
            if composite:
                # a composite key indexes the grid in the fused kernel
                # alone: its stand-ins take it recoded, as a sparse key
                # (which of the two, decided once a feed)
                memo = "key_dense_tiled" if tiled else "key_dense"
                if memo not in meta:
                    meta[memo] = agg_bodies(
                        runner._is_tpu, runner._nshards(), plan, feed,
                        dtypes, layouts, p8, pf, capacity,
                        pallas_hash.MODE_DENSE, tiled)[:1] == \
                        ("pallas_hash",)
                dense = meta[memo] and not recode
        # the sparse recode: the distinct keys, and each row's slot id
        # on the device
        slot_keys = slots_dev = None
        if not dense:
            # sparse key domain: direct indexing can't span it, but the
            # DISTINCT count may still be small — dictionary-encode the
            # key once per snapshot and feed dense slot ids (the
            # reference's fast_hash_aggr_executor.rs handles arbitrary
            # int keys with a hashmap, runner.rs:293-318)
            # (a composite key's number is formed in int64: where the
            # spans' product leaves it, two key tuples would share one)
            got = None if span >= 1 << 63 else \
                self._sparse_slots(plan, host_cols, n, feed, meta)
            if got is None:
                raise _FallbackToHost(f"hash key span {span}")
            slot_keys, _nd, capacity, slots_dev = got
        slots = capacity + 2
        sparse = slots_dev is not None
        # the sparse slot column rides the sharded flat inputs like any
        # other column (one extra all-valid pair after the scan columns)
        kern_flat = feed["flat"] + (slots_dev,) if sparse \
            else feed["flat"]
        kern_null_flags = feed["null_flags"] + (False,) if sparse \
            else feed["null_flags"]
        aux_arr = runner._cached_scalar(base, jnp.int64)
        n_arr = runner._cached_scalar(n, jnp.int64)
        n_cols = len(plan.used_cols)

        agg_out = self._agg_out(plan)
        shape = _result_shape(plan, key_bounds)
        schema = list(agg_out[0]) + [
            FieldType.var_char() if width else FieldType.long()
            for width in plan.key_codes or (0,) * len(plan.key_rpns)]

        def result(cols):
            return runner._result(dag, list(schema), cols)

        def hash_result(merged):
            return result(_hash_columns(agg_out, finalize_hash(
                plan.specs, merged, base, capacity, slot_keys=slot_keys),
                shape))

        mode = pallas_hash.MODE_SPARSE if sparse else pallas_hash.MODE_DENSE
        bodies = agg_bodies(runner._is_tpu, runner._nshards(), plan, feed,
                            dtypes, layouts, p8, pf, capacity, mode, tiled)
        if bodies[:1] == ("pallas_hash",):
            # the fused direct-index kernel is the default body for
            # both dense and (dictionary-encoded) sparse key domains —
            # the slot column rides as one extra int32 kernel input

            def from_packed(dag, parts, LO):
                return runner._result(dag, list(schema), self._packed_columns(
                    plan, parts, LO, p8, layouts, slots, base, capacity,
                    slot_keys, shape))

            got = self._try_pallas(dag, plan, feed, dtypes, n, base,
                                   capacity, layouts, p8, arg_nbytes,
                                   arg_ok_is_mask, mode, from_packed,
                                   spans=tile_spans, slots_dev=slots_dev,
                                   meta=meta, lanes=lanes,
                                   key_bounds=key_bounds)
            if got is not None:
                return got
            if composite and not sparse:
                return self.run_hash(dag, plan, host_cols, dtypes, n, feed,
                                     meta, tile_spans, lanes, recode=True)
            bodies = bodies[1:]
        if not bodies:
            # bucket tiles exist only on the fused-kernel path; the
            # host pipeline serves the original ranged request instead
            raise _FallbackToHost("bucket tiles need the pallas kernel")
        if bodies[0] == "hash_twolevel":
            LO, HI = twolevel_dims(slots, p8, pf)
            chunk = runner._feeds.pick_chunk(feed["n_pad"],
                                             runner._feeds.unit())
            key = runner._kern_key("hash2l", dag, feed, chunk,
                                   tuple(dtypes), capacity, arg_nbytes,
                                   tuple(arg_ok_is_mask), sparse)
            carry = self._cached_carry(key, lambda: (
                (np.zeros((HI, p8 * LO), np.int64),
                 np.zeros((HI, max(pf, 1) * LO), np.float64),
                 np.zeros((), np.int64)),
                []))
            kern = runner._shard_kernel(
                key, lambda: self._scan_program(
                    "hash_twolevel", self._build_hash_twolevel_body(
                        plan, n_cols, capacity, layouts, LO, HI,
                        sparse=sparse),
                    self._finalize_psum_summed(), kern_null_flags,
                    feed["n_pad"], chunk, carry, len(kern_flat)))
            with runner._dispatch_phase("hash_twolevel", key):
                carry = kern(carry, n_arr, aux_arr, *kern_flat)

            def fin_twolevel(fetched):
                (S8p, Sfp, ovf), _ = fetched
                assert int(ovf) == 0, "hash agg key range overflow"
                S8 = twolevel_unpack(S8p, p8, LO, slots, xp=np)
                Sf = twolevel_unpack(Sfp, pf, LO, slots, xp=np) \
                    if pf else None
                present, states = states_from_matmul(layouts, plan.specs,
                                                     S8, Sf, xp=np)
                return hash_result({"present": present, "overflow": False,
                                    "states": states})

            return _Pending(carry, fin_twolevel)
        else:
            chunk = runner._feeds.pick_chunk(feed["n_pad"], _CHUNK_AGG)
            key = runner._kern_key("hashsc", dag, feed, chunk,
                                   tuple(dtypes), capacity, sparse)
            # sharded: the order-sensitive stacked states (min/max)
            # tree-reduce on device via the all-to-all bucket merge —
            # the slot axis pads to a shard multiple so buckets split
            # evenly, and D2H shrinks from (S, slots) to (slots,)
            S = runner._nshards()
            bucket_merge = not runner._single
            slots_m = -(-slots // S) * S if bucket_merge else slots

            def build_scatter_carry():
                sm_init, st_init = self._init_agg_carry(
                    plan, slots, stacked_slots=slots_m)
                return ((sm_init, np.zeros(slots, np.int64),
                         np.zeros((), np.int64)), st_init)

            carry = self._cached_carry(key, build_scatter_carry)
            kern = runner._shard_kernel(
                key, lambda: self._scan_program(
                    "hash_scatter", self._build_hash_scatter_body(
                        plan, n_cols, capacity, sparse=sparse,
                        stack_pad=slots_m - slots),
                    self._finalize_hash_bucket_merge() if bucket_merge
                    else self._finalize_psum_summed(), kern_null_flags,
                    feed["n_pad"], chunk, carry, len(kern_flat)))
            with runner._dispatch_phase("hash_scatter", key):
                carry = kern(carry, n_arr, aux_arr, *kern_flat)

            def fin_scatter(fetched):
                (summed, present_counts, ovf), stacked = fetched
                assert int(ovf) == 0, "hash agg key range overflow"
                if bucket_merge:
                    with trace.phase("shard_merge"):
                        states = self._merge_bucketed(
                            plan.specs, summed, stacked, slots)
                else:
                    states = self._merge_stacked(plan.specs, summed,
                                                 stacked)
                return hash_result({
                    "present": present_counts > 0,
                    "overflow": False,
                    "states": states,
                })

            return _Pending(carry, fin_scatter)

    def _sparse_slots(self, plan, host_cols, n, feed, meta):
        """Host recode of a sparse GROUP BY key into dense slot ids.

        A sparse int64 key domain (user ids, hashes) cannot
        direct-index into [0, capacity).  Ranking on device was tried
        and measured: ``searchsorted``/gather per row lowers to
        scalar-gather loops on TPU (~120× slower than the dense MXU
        path).  The TPU-shaped answer is dictionary encoding OUTSIDE
        the kernel — exactly how BYTES columns reach devices — so the
        recode runs once per snapshot on host (np.unique's sort is the
        C path) and the slot column is cached in HBM next to the feed;
        warm requests then run the identical one-hot MXU kernel as the
        dense case.  Reference analog: fast_hash_aggr_executor.rs keys
        its specialised hashmap once per scan, not per batch.

        Returns (uniq_np, nd, capacity, slot device array) or None when
        the distinct count exceeds the sparse budget.
        """
        runner = self._runner
        if "sparse_slots" in meta:
            return meta["sparse_slots"]
        if len(plan.key_rpns) > 1:
            # the keys' mixed-radix number, as the kernel's dense
            # branch forms it: ``_hash_columns`` takes it apart
            kv = np.zeros(n, np.int64)
            # (``run_hash`` holds the spans' product under 2^63, so
            # neither a key's offset nor the number wraps)
            for r, (b, s) in zip(plan.key_rpns, meta["key_bounds"]):
                v, _ok = eval_rpn(r, host_cols(), n, np)
                kv = kv * s + (np.broadcast_to(v, (n,)).astype(np.int64)
                               - b)
            km = np.ones(n, np.bool_)
        else:
            kv, km = eval_rpn(plan.key_rpns[0], host_cols(), n, np)
            kv = np.broadcast_to(kv, (n,))
            km = np.broadcast_to(km, (n,))
        valid = kv[km] if not km.all() else kv
        got = None
        if valid.size:
            # keep the key dtype: casting a uint64 domain to int64 would
            # wrap keys >= 2^63 and emit wrong group values
            uniq, inv = np.unique(valid, return_inverse=True)
            nd = len(uniq)
            if nd <= runner._max_hash_capacity:
                capacity = max(1024, _next_pow2(nd))
                idx = np.full(n, capacity, np.int32)       # NULL slot
                if km.all():
                    idx[:] = inv.astype(np.int32)
                else:
                    idx[km] = inv.astype(np.int32)
                n_pad = feed["n_pad"]
                padded = np.full(n_pad, capacity + 1, np.int32)  # scrap
                padded[:n] = idx
                dev = jnp.asarray(padded) if runner._single else \
                    jax.device_put(padded, runner._row_sharding)
                got = (uniq, nd, capacity, dev)
        meta["sparse_slots"] = got
        return got

    @staticmethod
    def _key_bounds(plan: _Plan, host_cols, n: int) -> tuple:
        """``((base, span), ...)`` of a composite key's keys over the
        feed's rows.  A key with a NULL goes to the host: SQL keeps
        (NULL, 1) and (NULL, 2) apart, and the grid has one NULL slot.
        So does an unsigned one: the key's number is an int64's."""
        bounds = []
        for r in plan.key_rpns:
            kv, km = eval_rpn(r, host_cols, n, np)
            if not np.all(km):
                raise _FallbackToHost("NULL in a composite GROUP BY key")
            if np.asarray(kv).dtype.kind == "u":
                raise _FallbackToHost("unsigned composite GROUP BY key")
            kv = np.broadcast_to(kv, (n,))
            lo = int(kv.min()) if n else 0
            bounds.append((lo, int(kv.max()) - lo + 1 if n else 1))
        return tuple(bounds)

    @staticmethod
    def _arg_nbytes(plan: _Plan, host_cols, n: int) -> tuple:
        """``feed.arg_byte_planes`` over the bounds of these host planes
        (host min/max, vectorized: of a lowered plan's every column, else
        of the columns an argument is a bare reference to)."""
        bare = bare_int_refs(plan)
        bounds = [None if not (plan.lowered or i in bare) else
                  (int(v.min()), int(v.max())) if v.size else (0, 0)
                  for i, (v, _ok) in enumerate(host_cols)]
        return arg_byte_planes(plan, bounds,
                               [str(v.dtype) for v, _ok in host_cols])

    def _arg_ok_is_mask(self, plan, feed) -> list:
        """Per-agg flag: the arg's validity provably equals the row mask
        (bare NOT NULL column ref), so its plane aliases the mask plane."""
        return [r is not None and len(r.nodes) == 1 and
                isinstance(r.nodes[0], RpnColumnRef) and
                not feed["null_flags"][r.nodes[0].col_idx]
                for r in plan.agg_rpns]

    @staticmethod
    def _agg_out(plan) -> tuple:
        """(result FieldType, container dtype) lists of ``plan.specs``,
        resolved once per cached plan.  The dtype is the one
        ``Column.from_list`` gives the eval type, uint64 where the field
        type is unsigned (BIT kinds)."""
        out = plan.agg_out
        if out is None:
            from ..executors.aggregation import _agg_ret_ft
            # a lowered DECIMAL's SUM is typed as the host pipeline
            # types it (the argument was a DECIMAL before the lowering);
            # a limb pair (``plan.agg_recipes``) as its first limb
            dev_fracs = tuple(plan.agg_fracs) or (None,) * len(plan.specs)
            firsts = [src if isinstance(src, int) else src[0]
                      for src in plan.agg_recipes or range(len(plan.specs))]
            fracs = tuple(dev_fracs[j] for j in firsts)
            fts = [_agg_ret_ft(spec.kind,
                               EvalType.DECIMAL if frac is not None
                               else spec.eval_type if spec.kind not in
                               ("count", "count_star") else None)
                   for spec, frac in zip((plan.specs[j] for j in firsts),
                                         fracs)]
            out = plan.agg_out = (fts, [
                np.dtype(np.uint64) if ft.is_unsigned
                else ft.eval_type.np_dtype for ft in fts], fracs)
        return out

    def _packed_columns(self, plan, parts, LO, p8, layouts, slots, base,
                        capacity, slot_keys, shape=None):
        """An aggregation's finalize after a Pallas launch, GROUP BY
        or not (``slots`` 1): ``finalize_packed`` (one native call
        where it can, the numpy chain where it cannot), counted once on
        the physical runner's flight recorder as what it was
        (``mesh_stats`` ``finalize``), then ``_hash_columns``."""
        runner = self._runner
        finalized, was_native = finalize_packed(
            parts, LO, p8, layouts, plan.specs, slots, base, capacity,
            slot_keys)
        runner.flight_recorder.note_finalize(was_native)
        return _hash_columns(self._agg_out(plan), finalized, shape)

    # -- the Pallas launch --

    def _try_pallas(self, dag, plan, feed, dtypes, n, base, capacity,
                    layouts, p8, arg_nbytes, arg_ok_is_mask, mode, finish,
                    spans=None, slots_dev=None, meta=None,
                    lanes: bool = False, key_bounds=None):
        """Fused Pallas fast path for the direct-index aggregation
        (dense / sparse-slot / simple modes — pallas_hash module doc),
        for a plan ``agg_bodies`` gave to the kernel.

        ``spans``: row intervals to aggregate (bucket tiles); None =
        the whole feed, dispatched over the ENTIRE padded grid so the
        compile class is exactly the feed-shape cache key — the
        dead-block guard makes the bucketed padding cost DMA only.
        Span tiles keep bucketed block counts for compile-class reuse
        (block offset via prefetch scalar); the packed partials ADD —
        psum-partial merge semantics.  ``finish(dag, parts, LO)``: the
        caller's finalize of the packed partials, one a tile (they add:
        ``_sum_parts``).

        Returns None when the kernel cannot serve this request (no
        live tile, a build that was refused, a launch that failed: the
        caller then runs its XLA stand-in), else what the caller hands
        on.  A first build: the finished result (compile + validate ran
        synchronously so that Mosaic rejections fall back).  Bucket
        tiles and a mesh's sharded entry: a ``_Pending`` whose parts
        are still on the device (fetched possibly on a completion
        thread — the async serving path).  The whole feed of a
        single-device runner over a built kernel: the preparation is
        left in ``meta`` (the request's memo) as its ``_Prepared``
        record, from which the class's next requests are staged
        without coming here (``DeviceRunner._stage_tickets``; its
        ``key`` is what ``DeviceRunner.launch_class`` tells the
        coalescer), and this request is its first lane, a
        ``_LanePending``: launched here through ``launch_lanes`` as a
        launch of one lane, or, with ``lanes`` (the caller stages
        several requests under one hold of the dispatch lock), left to
        the caller's ``launch_lanes`` with the other lanes of its
        staging.

        A build or compile failure is cached so the fallback is taken
        once per plan, not per request.  SHARDED meshes ride the same
        kernel as per-shard partials (``_pallas_sharded_wrap``), and a
        build or lowering failure there falls back to the sharded XLA
        bodies exactly like the single-device case.
        """
        runner = self._runner
        sparse = mode == pallas_hash.MODE_SPARSE
        # the request's constants, operands of the const-blind kernel
        _sel, _aggs, consts, const_dts = pallas_hash.plan_params(plan)
        # a composite key's bases and spans ride ahead of them
        # (``pallas_hash.build``): a feed's own, not the kernel's
        key_scalars = tuple(v for b in key_bounds for v in b) \
            if key_bounds is not None and not sparse else ()
        pvals = key_scalars + tuple(consts)
        pdts = ("int32",) * len(key_scalars) + tuple(const_dts)
        # what the recorder says of the launch: its constants, its
        # GROUP BY keys, the byte planes it contracts and the grid it
        # contracts them over, its slots and the rows a grid step takes
        # (the kernel's time follows rows x planes x sublanes of slots:
        # PERF.md section 6, PRs 34 and 40; the step follows the grid)
        slots = pallas_hash.n_slots(plan, capacity, mode)
        B = pallas_hash.block_rows(slots)
        said = {"params": len(consts), "keys": len(plan.key_rpns),
                "planes": p8, "limb_sums": len(plan.limbs), "slots": slots,
                "block_rows": B}
        # (the feed pads to whole BLOCKs a shard, ``supported``: whole
        # steps of any grid)
        total_blocks = feed["n_pad"] // B
        tiles = []          # (row_lo, row_hi, blk0, span_blocks)
        if spans is None:
            tiles.append((0, n, 0, total_blocks))
        else:
            for lo, hi in spans:
                hi = min(hi, n)
                if hi <= lo:
                    continue
                blk0 = lo // B
                nb = self._bucket_blocks(-(-hi // B) - blk0)
                nb = min(nb, total_blocks)
                if blk0 + nb > total_blocks:
                    blk0 = total_blocks - nb  # shift left; rows mask exact
                tiles.append((lo, hi, blk0, nb))
            if not tiles:
                return None

        # kernel input selection: only columns the kernel evaluates
        # (int32, non-null ⇒ one flat entry each) plus the sparse slot
        # column; everything else (e.g. the raw int64 sparse key) stays
        # host/XLA-side
        kset = set(pallas_hash.kernel_col_ids(plan, mode))
        col_sel, col_map = [], []
        for i, fi in enumerate(value_plane_index(feed["null_flags"])):
            if i in kset:
                col_map.append(len(col_sel))
                col_sel.append(fi)
            else:
                col_map.append(-1)
        col_map = tuple(col_map)
        cols = tuple(feed["flat"][j] for j in col_sel)
        if sparse:
            cols += (slots_dev,)

        def build() -> dict:
            if not runner._single:
                # per-shard partial grids + psum tree-reduce: one
                # shard_map launch, one replicated packed result
                S = runner._nshards()
                run, LO, _HI = pallas_hash.build(
                    plan, layouts, p8, capacity,
                    feed["n_pad"] // (S * B), col_map, mode=mode)
                return {"sharded": self._pallas_sharded_wrap(
                    run, len(cols), feed["n_pad"] // S, len(pdts)),
                    "LO": LO}
            runs_by_nb = {}
            LO = None
            for nb in sorted({t[3] for t in tiles}):
                run, LO, _HI = pallas_hash.build(
                    plan, layouts, p8, capacity, nb, col_map, mode=mode)
                runs_by_nb[nb] = run
            # in_shapes: what a lane program is traced over
            # (_ask_lane_programs)
            return {"runs": runs_by_nb, "LO": LO, "in_shapes": tuple(
                (c.shape, c.dtype) for c in cols)}

        def launch(entry) -> list:
            """One launch of a built kernel → its packed parts, still
            on the device (one on a mesh, one a tile; they add)."""
            if "sharded" in entry:
                # (a plan without constants passes what it always did)
                vec = (self._param_vector(entry, pvals),) if pvals else ()
                return [entry["sharded"](
                    runner._cached_scalar(n, jnp.int64),
                    runner._cached_scalar(base, jnp.int64), *vec, *cols)]
            runs_by_nb = entry["runs"]
            return [runs_by_nb[nb](lo, hi, base, blk0, cols, pvals)
                    for lo, hi, blk0, nb in tiles]

        # const-blind, as the selection route's kernels are: the plan's
        # class (constants by device dtype bucket, ``class_key``), the
        # GROUP BY key's constants by value (the key bounds a kernel is
        # built for depend on them) and the operands' dtypes.  Every
        # constant tuple of a prepared statement shares one built
        # kernel; a constant that crosses a bucket is a new class.
        key = ("hashpl", dag.class_key(), pallas_hash.key_consts(plan), mode,
               tuple(sorted({t[3] for t in tiles})), tuple(dtypes),
               capacity, arg_nbytes, tuple(arg_ok_is_mask),
               runner._nshards(), pdts)
        cache = runner._kernel_cache
        entry = cache.get(key)
        if entry is False:
            return None
        first = entry is None
        whole = spans is None and runner._single
        if first or not whole:
            # what has no record to leave by: a first build, bucket
            # tiles, a mesh's sharded entry
            try:
                # the first build is a launch like any other: its
                # compile wall and class land in the flight recorder
                # (first_launch=True), and a rejected build counts as a
                # recorder fault before the XLA fallback serves
                with runner._dispatch_phase("pallas_hash", key,
                                            slot_mode=mode, **said):
                    if first:
                        entry = build()
                        if whole:
                            # the kernel's lane programs build beside
                            # its own compile, each on its thread: they
                            # are there when the first read is
                            # (launch_lanes)
                            self._ask_lane_programs(entry, key)
                        # compile + validate now so Mosaic / shard_map
                        # rejections fall back to the XLA bodies
                        parts = [_sum_parts(launch(entry))]
                    else:
                        parts = launch(entry)
            except Exception as e:
                # a failed build, or a failed launch of a cached
                # kernel, falls back to the XLA body for THIS request
                # and never fails the coprocessor request.  (A failure
                # surfacing later, at the possibly-deferred fetch,
                # degrades to the host pipeline via the DeferredResult
                # / endpoint contract instead.)
                self._pallas_failed(key, e, building=first)
                return None
            if first:
                cache[key] = entry
                if pdts:
                    runner.flight_recorder.note_const_class()
            # success clears the transient strike count — three
            # isolated hiccups over a process lifetime must not kill
            # the fast path
            cache.pop(("hashpl_tries", key), None)
        LO = entry["LO"]
        if whole:
            (lo, hi, blk0, nb), = tiles
            rec = _Prepared(
                key=key, entry=entry, run=entry["runs"][nb],
                bounds=(lo, hi, base, blk0), cols=cols, feed=feed,
                flat=feed["flat"], feed_key=feed.get("key"), mode=mode,
                said=said, limbs=plan.limbs, key_scalars=key_scalars,
                param_dts=tuple(const_dts), finish=finish, LO=LO)
            if meta is not None:
                meta["prepared"] = rec
                runner.flight_recorder.note_prepared("builds")
        if first:
            return finish(dag, parts, LO)
        if not whole:
            return _Pending(parts, lambda parts: finish(dag, parts, LO))
        lane = rec.lane(dag, consts, prepared=False)
        if not lanes and self.launch_lanes([lane]):
            return None     # (struck by ``launch_lanes``)
        return lane

    # -- multi-lane launches --

    # the lane counts a kernel has programs for: a staging of more
    # lanes leaves as launches of the largest first (six lanes: 4 + 2).
    # A launch costs ~2.8 ms and a lane ~1.45 on the v5e's host
    # (PERF.md section 6, PR 33), so past four lanes a second launch
    # adds little, and every count is one more Mosaic compile at start
    _LANE_COUNTS = (2, 3, 4)
    # consecutive failed launches after which a lane count's program
    # is given up (its lanes then leave in smaller launches)
    _LANE_PROGRAM_TRIES = 3

    def launch_lanes(self, lanes: list) -> list:
        """Send the prepared lanes of one staging (``_LanePending``s,
        in the caller's order, under the caller's hold of the dispatch
        lock) and bind each to its launch: the ONE way a warm
        whole-feed launch of a single-device runner leaves, a request
        alone as a launch of one lane.  Lanes of one kernel cache
        key leave as ONE jitted program that runs the built Pallas
        call once a lane, over that lane's feed and row bounds alone
        (no padded lanes, no stacked feeds: a lane is a request of its
        own, its own snapshot), and returns the packed ``(2, HI, W)``
        results stacked in pinned host memory: one
        ``_dispatch_phase("pallas_hash")`` (one flight-recorder
        launch, ``prepared``: its lanes staged from their class's
        record alone), one readback (``_LaneLaunch``).  A lane count
        without a built program (more than ``_LANE_COUNTS`` has, or one
        still building on ``_build_lanes``'s threads) leaves as the
        largest built count plus the rest, single launches at worst:
        the thread that stages never compiles.

        → the lanes whose launch FAILED (unbound; the caller releases
        their pins and their members retry solo; a failed launch of
        ONE lane is the kernel's own: ``_pallas_failed``).  On the
        dispatcher the launch's own time around ``device_dispatch`` is
        the hold's row ``lanes_launch``.
        """
        with trace.held("lanes_launch"):
            return self._launch_lanes(lanes)

    def _launch_lanes(self, lanes: list) -> list:
        runner = self._runner
        by_key: dict = {}
        for p in lanes:
            by_key.setdefault(p.rec.key, []).append(p)
        failed = []
        for key, todo in by_key.items():
            entry = todo[0].rec.entry
            while todo:
                k, prog = self._lane_program(entry, key, todo)
                batch, todo = todo[:k], todo[k:]
                rec = batch[0].rec
                try:
                    with runner._dispatch_phase(
                            "pallas_hash", key, slot_mode=rec.mode,
                            prepared=sum(p.prepared for p in batch),
                            **rec.said) as info:
                        if k > 1:
                            trace.annotate(lanes=k)
                            with jax.enable_x64(False):
                                out = prog(
                                    tuple(p.rec.run.scalars(
                                        *p.rec.bounds, p.pvals)
                                        for p in batch),
                                    tuple(p.rec.cols for p in batch))
                        else:
                            out = [rec.run(*rec.bounds, rec.cols,
                                           batch[0].pvals)]
                    launch = _LaneLaunch(runner, out)
                except Exception as e:  # noqa: BLE001 — members go solo
                    self._lane_launch_failed(entry, key, k, e)
                    failed += batch
                    continue
                with self._lane_mu:
                    self.lanes_hist[k] = self.lanes_hist.get(k, 0) + 1
                if entry.get("lane_fails", {}).pop(k, None) and k == 1:
                    # (success clears the kernel's transient strikes)
                    runner._kernel_cache.pop(("hashpl_tries", key), None)
                for i, p in enumerate(batch):
                    p.launch, p.index = launch, i
                    p.info = dict(info, attrs=dict(
                        info.get("attrs", ()), lanes=k, lane=i))
                    p.rec = p.pvals = None
        return failed

    def _lane_program(self, entry: dict, key, todo: list) -> tuple:
        """``(k, program)``: how many of ``todo``'s lanes leave in the
        next launch: as many as the largest built program takes
        (``_LANE_COUNTS``; 1: the kernel's own ``run``)."""
        want = min(len(todo), self._LANE_COUNTS[-1])
        if want == 1:
            return 1, None
        progs = self._ask_lane_programs(entry, key)
        k = max((k for k, built in progs.items() if built and k <= want),
                default=1)
        return k, progs.get(k)

    def _ask_lane_programs(self, entry: dict, key) -> dict:
        """The kernel's lane programs by lane count (None: still
        building, False: not buildable).  The first call, the kernel's
        own build, starts their builds, all counts at once and each on
        a thread of its own: no later lane count compiles, and on a
        cold compile cache they are ready about when the kernel is."""
        progs = entry.get("lane_progs")
        if progs is None:
            with self._lane_mu:
                progs = entry.get("lane_progs")
                if progs is None:
                    progs = entry["lane_progs"] = dict.fromkeys(
                        self._LANE_COUNTS)
                    (run,) = entry["runs"].values()
                    for k in self._LANE_COUNTS:
                        threading.Thread(
                            target=self._build_lanes, daemon=True,
                            args=(entry, key, run, k),
                            name="copr-lane-builder").start()
        return progs

    def lanes_ready(self, key) -> bool:
        """Whether closed groups of the kernel cached under ``key`` can
        leave together yet (``DeviceRunner.lanes_ready``): it has a
        built lane program.  Until one is there the groups leave one by
        one, as before (``unbuilt_fallbacks`` counts the asks that met
        none)."""
        entry = self._runner._kernel_cache.get(key)
        if not isinstance(entry, dict) or len(entry.get("runs", ())) != 1:
            return False
        if any(self._ask_lane_programs(entry, key).values()):
            return True
        with self._lane_mu:
            self.unbuilt_fallbacks += 1
        return False

    def _lane_launch_failed(self, entry: dict, key, k: int, e) -> None:
        with self._lane_mu:
            self.lane_launch_failures += 1
            fails = entry.setdefault("lane_fails", {})
            fails[k] = fails.get(k, 0) + 1
            give_up = k > 1 and fails[k] >= self._LANE_PROGRAM_TRIES
            if give_up:
                entry["lane_progs"][k] = False
        if k == 1:
            # one lane is the kernel's own ``run``: its strikes
            self._pallas_failed(key, e, building=False)
        _log.warning(
            "pallas hash %d-lane launch failed for plan %r (%s: %s); its "
            "members retry solo%s", k, key[1], type(e).__name__, e,
            "; the lane count is given up" if give_up else "")

    def _build_lanes(self, entry: dict, key, run, k: int) -> None:
        """Build one k-lane program off the dispatcher's thread (a
        thread a program: a build is one Mosaic compile, ~12.5 s cold
        on a v5e whatever k, PERF.md section 6, PR 33, and they do not
        wait for each other): trace, compile (the persistent compile
        cache keeps the executable for the next start) and run once
        over one dummy column with empty row bounds, so that the jitted
        program and the pinned stager's class of its stacked output are
        warm when the dispatcher first calls them.  Where the pinned
        stager runs (request.HOST_STAGER: a TPU) and would take the
        stacked output (``MAX_BYTES``), the program's own
        output lies in pinned host memory, which saves the launch the
        stager's program, a second PjRt execute (0.27 ms of the
        launching thread's CPU on a v5e's host, 0.13 more for the
        copy it starts; PERF.md section 6, PR 33)."""
        runner = self._runner
        t0 = time.perf_counter()
        prog = False
        with runner._device_scope(), jax.enable_x64(False):
            cols = tuple(jnp.zeros(shape, dtype)
                         for shape, dtype in entry["in_shapes"])
            args = (run.scalars(0, 0, 0, 0, (0,) * len(key[-1])),) * k, \
                (cols,) * k
            pinned = None
            if HOST_STAGER.enabled is None:     # not probed yet
                HOST_STAGER.stage(jnp.zeros((8,), jnp.int32))
            part = jax.eval_shape(run.call, args[0][0], *cols)
            if HOST_STAGER.enabled and k * part.size * \
                    part.dtype.itemsize <= HOST_STAGER.MAX_BYTES:
                # (past the stager's limit the stacked output stays in
                # device memory and is fetched from there)
                (dev,) = cols[0].devices()
                pinned = SingleDeviceSharding(
                    dev, memory_kind=HOST_STAGER.memory_kind)
            for out_sharding in ((pinned, None) if pinned is not None
                                 else (None,)):
                try:
                    built = _build_lane_program(run.call, k, out_sharding)
                    jax.block_until_ready(HOST_STAGER.stage(built(*args)))
                    prog = built
                    break
                except Exception as e:  # noqa: BLE001 — lanes in parts
                    _log.warning(
                        "pallas hash %d-lane program not built for plan "
                        "%r (output %s): %s: %s", k, key[1],
                        "pinned" if out_sharding is not None
                        else "on the device", type(e).__name__, e)
        with self._lane_mu:
            self.lane_builds += 1
            self.lane_build_s = max(self.lane_build_s,
                                    time.perf_counter() - t0)
        entry["lane_progs"][k] = prog

    def lane_stats(self) -> dict:
        """Multi-lane launches for ``/health`` (``device_mesh.lanes``):
        launches by lane count, asks that found a kernel's lane
        programs still building (``lanes_ready``), failed launches, the
        programs built and the longest build's seconds."""
        with self._lane_mu:
            hist = dict(sorted(self.lanes_hist.items()))
            return {
                "launches_by_lanes": {str(k): n for k, n in hist.items()},
                "multi_lane_launches": sum(
                    n for k, n in hist.items() if k > 1),
                "lanes_sum": sum(k * n for k, n in hist.items()),
                "unbuilt_fallbacks": self.unbuilt_fallbacks,
                "launch_failures": self.lane_launch_failures,
                "programs_built": self.lane_builds,
                "longest_build_s": round(self.lane_build_s, 3)}

    def _pallas_failed(self, key, e, building: bool) -> None:
        """One failed build or launch of the kernel cached under
        ``key``: logged, counted, and at the third (or at once, for a
        build the compiler refused) the plan's kernel is disabled."""
        # never silently: a swallowed genuine bug here would disguise
        # itself as the slower XLA path.
        # cache-disable deterministic build/lowering rejections
        # (Mosaic/compile errors) immediately; a transient runtime
        # failure (device OOM, runtime hiccup, any failure to launch a
        # kernel that had built) falls back without poisoning the
        # cache — but only a few times, so a deterministic failure
        # dressed as transient can't re-pay the build+compile cost on
        # every request forever
        cache = self._runner._kernel_cache
        name = type(e).__name__
        transient = not building or \
            isinstance(e, (OSError, TimeoutError)) or \
            "RESOURCE_EXHAUSTED" in str(e) or \
            name in ("XlaRuntimeError", "InternalError") and \
            "Mosaic" not in str(e)
        tries = cache.get(("hashpl_tries", key), 0) + 1
        cache[("hashpl_tries", key)] = tries
        if transient and tries < 3:
            _log.warning(
                "pallas hash kernel transient failure for plan %r "
                "(%s, attempt %d/3, falling back once): %s: %s", key[1],
                "build" if building else "launch", tries, name, e)
        else:
            _log.warning(
                "pallas hash kernel disabled (cached) for plan "
                "%r: %s: %s", key[1], name, e)
            cache[key] = False

    def _param_vector(self, entry: dict, pvals: tuple):
        """A sharded kernel's constants as one int32 vector replicated
        over the mesh, cached by value on its entry (a tuple seen
        before costs no H2D; bounded as ``pallas_hash``'s scalars)."""
        cache = entry.setdefault("param_vectors", {})
        vec = cache.get(pvals)
        if vec is None:
            if len(cache) >= 8192:
                cache.clear()
            vec = cache[pvals] = jax.device_put(
                np.asarray(pvals, np.int32), self._runner._repl)
        return vec

    def _pallas_sharded_wrap(self, run, n_in: int, n_local_pad: int,
                             n_params: int = 0):
        """shard_map wrapper for the fused kernel: each shard runs one
        grid over its LOCAL feed slice (row bounds traced from the
        shard index — the kernel's dead-block guard masks the ragged
        tail shard exactly as it masks bucket padding), then the packed
        int32 partial pairs — exact sums by construction — psum over
        both mesh axes (partial-at-shard / final-on-ICI, the TiDB
        split) and ONE replicated (2, HI, W) result crosses D2H.
        check_vma is
        off: pallas_call's out_shape carries no varying-axes type, and
        the psum makes the output replicated by construction."""
        runner = self._runner

        def pallas_hash_sharded(n_arr, base_arr, *rest):
            # the plan's constants, where it has any: one replicated
            # int32 vector before the columns (``_param_vector``)
            params = tuple(rest[0][j] for j in range(n_params)) \
                if n_params else ()
            cols_local = rest[1:] if n_params else rest
            start = runner._shard_index() * n_local_pad
            row_hi = jnp.clip(n_arr - start, 0, n_local_pad)
            packed = run(jnp.asarray(0, jnp.int32), row_hi, base_arr,
                         jnp.asarray(0, jnp.int32), cols_local, params)
            return lax.psum(packed, ROW_AXES)

        return jax.jit(jax.shard_map(
            pallas_hash_sharded, mesh=runner._mesh,
            in_specs=(P(), P()) + ((P(),) if n_params else ()) +
            (P(ROW_AXES),) * n_in,
            out_specs=P(), check_vma=False))

    def _bucket_blocks(self, blocks: int) -> int:
        """Round a grid span up to a 4-significant-bit block count —
        the compile-class grid shared with feed.py ``pad_rows``."""
        if blocks > 8:
            s = blocks.bit_length() - 4
            k = -(-blocks // (1 << s))
            if k > 15:
                s += 1
                k = -(-blocks // (1 << s))
            blocks = k << s
        return max(1, blocks)

    # -- the XLA bodies --

    def _hash_body_inputs(self, plan: _Plan, n_pairs: int, flat):
        """What both XLA hash bodies make of one scan block's columns:
        ``(pairs, n_local, mask, cols)``: the (values, validity) pairs,
        the block's rows, the selection under the row mask, and each
        aggregate's argument (zeros under the mask for COUNT(*))."""
        row_mask = flat[-1]
        pairs = [(flat[2 * i], flat[2 * i + 1]) for i in range(n_pairs)]
        n_local = row_mask.shape[0]
        mask = self._runner._eval_masked(plan, pairs, n_local, row_mask)
        cols = [(jnp.zeros((n_local,), jnp.int32), mask) if r is None
                else eval_rpn(r, pairs, n_local, jnp)
                for r in plan.agg_rpns]
        return pairs, n_local, mask, cols

    def _build_hash_twolevel_body(self, plan: _Plan, n_cols: int,
                                  capacity: int, layouts, LO: int, HI: int,
                                  sparse: bool = False):
        specs = plan.specs
        n_pairs = n_cols + (1 if sparse else 0)

        def body(carry, aux, base, *flat):
            (S8_c, Sf_c, ovf_c), _unused = carry
            pairs, n_local, mask, cols = self._hash_body_inputs(
                plan, n_pairs, flat)
            if sparse:
                # precomputed slot ids (trailing column); only the
                # request's selection/row mask is applied here
                scrap = capacity + 1
                idx = jnp.where(mask, pairs[n_cols][0].astype(jnp.int32),
                                scrap)
                overflow = jnp.zeros((), jnp.bool_)
            else:
                key_pair = eval_rpn(plan.key_rpns[0], pairs, n_local, jnp)
                idx, overflow = slot_index(key_pair, capacity, aux, mask)
            L8, Lf = make_planes(layouts, specs, cols, mask)
            S2_8, S2_f = twolevel_partial(idx, L8, Lf, LO, HI)
            S8_c = S8_c + S2_8.astype(jnp.int64)
            if S2_f is not None:
                Sf_c = Sf_c + S2_f.astype(jnp.float64)
            ovf_c = ovf_c + overflow.astype(jnp.int64)
            return (S8_c, Sf_c, ovf_c), _unused

        return body

    def _build_hash_scatter_body(self, plan: _Plan, n_cols: int,
                                 capacity: int, sparse: bool = False,
                                 stack_pad: int = 0):
        specs = plan.specs
        n_pairs = n_cols + (1 if sparse else 0)

        def body(carry, aux, base, *flat):
            (summed_c, present_c, overflow_c), stacked_c = carry
            pairs, n_local, mask, cols = self._hash_body_inputs(
                plan, n_pairs, flat)
            if sparse:
                # precomputed slot ids ride as the trailing column
                key_pair = (jnp.zeros((n_local,), jnp.int32), mask)
                tile_base = ("precomp", pairs[n_cols][0])
            else:
                key_pair = eval_rpn(plan.key_rpns[0], pairs, n_local, jnp)
                tile_base = aux
            st = hash_agg_tile(jnp, specs, key_pair, cols, capacity,
                               tile_base, row_mask=mask)
            present = present_c + st["present"].astype(jnp.int64)
            overflow = overflow_c + st["overflow"].astype(jnp.int64)
            out_sm, out_st = [], []
            for spec, s, cs, cst in zip(specs, st["states"], summed_c,
                                        stacked_c):
                sm, stk = self._split_new_state(self._canon_state(s))
                stk = self._pad_stacked(stk, stack_pad)
                out_sm.append(self._merge_summed(cs, sm))
                out_st.append(self._merge_stacked_dict(cst, stk)
                              if stk else cst)
            return (out_sm, present, overflow), out_st

        return body

    def _build_simple_body(self, plan: _Plan, n_cols: int):
        runner = self._runner
        specs = plan.specs

        def body(carry, aux, base, *flat):
            summed_c, stacked_c = carry
            row_mask = flat[-1]
            pairs = [(flat[2 * i], flat[2 * i + 1]) for i in range(n_cols)]
            n_local = row_mask.shape[0]
            mask = runner._eval_masked(plan, pairs, n_local, row_mask)
            cols = []
            for r in plan.agg_rpns:
                if r is None:
                    cols.append((jnp.zeros((n_local,), jnp.int32), mask))
                else:
                    v, ok = eval_rpn(r, pairs, n_local, jnp)
                    cols.append((v, ok & mask))
            n_valid = jnp.sum(mask, dtype="int64")
            states = simple_agg_tile(jnp, specs, cols, n_valid_rows=n_valid)
            out_sm, out_st = [], []
            for spec, s, cs, cst in zip(specs, states, summed_c, stacked_c):
                s = self._canon_state(s)
                if spec.kind == "first":
                    # globalize positions; host picks the cross-shard argmin
                    s["pos"] = jnp.where(s["pos"] == _BIG, _BIG,
                                         s["pos"] + base)
                sm, st = self._split_new_state(s)
                out_sm.append(self._merge_summed(cs, sm))
                out_st.append(self._merge_stacked_dict(cst, st)
                              if st else cst)
            return out_sm, out_st

        return body

    # -- the single-dispatch scan program --
    #
    # Every request is ONE jit call: body(carry, aux, base, *cols, row_mask)
    # folds one scan block; lax.scan drives it across the whole feed; the
    # finalize hook (cross-shard psum of the summed subtree) runs once
    # after the scan.  r2 dispatched one jit per 2^23-row chunk — enqueues
    # are cheap but the per-chunk carries defeated XLA's scheduling and
    # every chunk paid its own blocking sync.

    def _scan_program(self, klass: str, body, finalize, null_flags,
                      n_pad: int, chunk: int, carry_example, n_flat: int):
        runner = self._runner
        S = runner._nshards()
        n_local_total = n_pad // S
        chunk_local = chunk // S
        nblk = n_pad // chunk

        def local_fn(carry, n_scalar, aux, *flat):
            if not runner._single:
                # the replicated summed subtree becomes device-varying as
                # soon as local rows fold in; the scan carry type must be
                # varying from step 0
                summed0, stacked0 = carry
                carry = (jax.tree.map(
                    lambda x: lax.pcast(x, ROW_AXES, to="varying"),
                    summed0), stacked0)
            base0 = runner._shard_index() * n_local_total
            xs = tuple(a.reshape(nblk, chunk_local) for a in flat)
            steps = jnp.arange(nblk, dtype=jnp.int64)
            # the ragged-tail mask comes from an iota compare (int32 when
            # rows fit — int64 is pair-emulated on TPU), so it costs no
            # HBM reads
            idt = jnp.int32 if n_pad <= np.iinfo(np.int32).max else jnp.int64
            iota = jnp.arange(chunk_local, dtype=idt)

            def step(c, x):
                s_i = x[0]
                cols = x[1:]
                base = base0 + s_i * chunk_local

                def live(c):
                    row_mask = (base.astype(idt) + iota) < \
                        n_scalar.astype(idt)
                    args = []
                    fi = 0
                    for has_nulls in null_flags:
                        v = cols[fi]
                        fi += 1
                        if has_nulls:
                            m = cols[fi]
                            fi += 1
                        else:
                            m = row_mask
                        args.append(v)
                        args.append(m)
                    return body(c, aux, base, *args, row_mask), None

                def dead(c):
                    # block entirely past the live rows (bucketed feed
                    # padding): an all-masked body invocation is a
                    # carry no-op by construction, so skip its HBM pass
                    return c, None

                return lax.cond(base < n_scalar, live, dead, c)

            carry, _ = lax.scan(step, carry, (steps,) + xs)
            return finalize(carry)

        local_fn = named_program(local_fn, klass)
        if runner._single:
            return jax.jit(local_fn)
        # specs matching the carry pytree: stacked leaves (leading shard
        # axis) are P(ROW_AXES); everything else replicated
        summedlike, stackedlike = carry_example
        cs = (jax.tree.map(lambda _: P(), summedlike),
              jax.tree.map(lambda _: P(ROW_AXES), stackedlike))
        return jax.jit(jax.shard_map(
            local_fn, mesh=runner._mesh,
            in_specs=(cs, P(), P()) + (P(ROW_AXES),) * n_flat,
            out_specs=cs))

    # -- carry initialization (host → device once per request) --

    def _cached_carry(self, cache_key, build):
        """Device-resident initial carry, uploaded once per kernel key.
        Kernels never donate their inputs, so the same zero/identity
        buffers are safe to reuse across requests."""
        runner = self._runner
        key = ("carry0",) + cache_key
        carry = runner._kernel_cache.get(key)
        if carry is None:
            # place the (summed, stacked) pytree built from numpy
            if runner._single:
                carry = jax.tree.map(jnp.asarray, build())
            else:
                summed, stacked = build()
                repl, rows = runner._repl, runner._row_sharding
                carry = (
                    jax.tree.map(lambda x: jax.device_put(x, repl), summed),
                    jax.tree.map(lambda x: jax.device_put(x, rows), stacked))
            runner._kernel_cache[key] = carry
        return carry

    def _init_agg_carry(self, plan: _Plan, slots: Optional[int],
                        stacked_slots: Optional[int] = None):
        """Zero/identity states for the scatter-path carries.

        ``slots=None`` → simple agg (scalar states); else hash agg
        arrays.  ``stacked_slots`` widens only the per-shard stacked
        leaves (min/max/first) — the sharded tree-reduce pads their
        slot axis to a multiple of the shard count so the all-to-all
        bucket exchange splits it evenly.
        """
        runner = self._runner
        S = runner._nshards()
        shape = () if slots is None else (slots,)
        sshape = (S,) if slots is None else \
            (S, slots if stacked_slots is None else stacked_slots)
        summed, stacked = [], []
        for spec, rpn in zip(plan.specs, plan.agg_rpns):
            is_real = rpn is not None and rpn.ret_type is EvalType.REAL
            sm, st = {}, {}
            if spec.kind in ("count", "count_star"):
                sm["count"] = np.zeros(shape, np.int64)
            elif spec.kind == "sum":
                sm["sum"] = np.zeros(shape, np.float64 if is_real else np.int64)
                sm["nonnull"] = np.zeros(shape, np.int64)
            elif spec.kind == "avg":
                sm["sum"] = np.zeros(shape, np.float64 if is_real else np.int64)
                sm["count"] = np.zeros(shape, np.int64)
            elif spec.kind in ("min", "max"):
                ident = (np.inf if spec.kind == "min" else -np.inf) \
                    if is_real else \
                    (np.iinfo(np.int64).max if spec.kind == "min"
                     else np.iinfo(np.int64).min)
                st[spec.kind] = np.full(
                    sshape, ident, np.float64 if is_real else np.int64)
                sm["nonnull"] = np.zeros(shape, np.int64)
            elif spec.kind == "first":
                st["pos"] = np.full(sshape, _BIG, np.int64)
                st["value"] = np.zeros(
                    sshape, np.float64 if is_real else np.int64)
            elif spec.kind in ("var_pop", "var_samp", "stddev_pop",
                               "stddev_samp"):
                sm["sum"] = np.zeros(shape, np.float64)
                sm["sumsq"] = np.zeros(shape, np.float64)
                sm["count"] = np.zeros(shape, np.int64)
            summed.append(sm)
            stacked.append(st)
        return summed, stacked

    # -- cross-shard merges --
    #
    # Only Sum all-reduces are emitted (no pmin/pmax): the dominant
    # state fields (count/sum/nonnull — every config in BASELINE.md)
    # merge with one post-scan psum on ICI, while order-sensitive
    # fields (min/max/first-pos) come back per-shard — a
    # (n_shards, slots) stack, KBs — and reduce on host (simple agg) or
    # through the all-to-all bucket merge (hash agg).

    def _canon_state(self, s: dict) -> dict:
        """Cast state leaves to carry dtypes (int64 / float64)."""
        return {k: (v.astype(jnp.float64) if v.dtype.kind == "f"
                    else v.astype(jnp.int64)) for k, v in s.items()}

    def _split_new_state(self, s: dict):
        """→ (summed fields, per-shard stacked fields shaped [1, ...])."""
        summed, stacked = {}, {}
        for k, v in s.items():
            if k in ("count", "sum", "nonnull", "sumsq"):
                summed[k] = v
            else:
                stacked[k] = v[None] if getattr(v, "ndim", 0) else \
                    jnp.reshape(v, (1,))
        return summed, stacked

    @staticmethod
    def _merge_summed(carry: dict, new: dict) -> dict:
        return {k: carry[k] + new[k] for k in carry}

    @staticmethod
    def _merge_stacked_dict(carry: dict, new: dict) -> dict:
        d = {}
        if "pos" in carry and "value" in carry:     # FIRST (simple agg)
            take_new = new["pos"] < carry["pos"]
            d["pos"] = jnp.where(take_new, new["pos"], carry["pos"])
            d["value"] = jnp.where(take_new, new["value"], carry["value"])
            return d
        for k in carry:
            if k == "min" or k == "pos":
                d[k] = jnp.minimum(carry[k], new[k])
            elif k == "max":
                d[k] = jnp.maximum(carry[k], new[k])
            else:   # pragma: no cover
                raise ValueError(k)
        return d

    @staticmethod
    def _pad_stacked(st: dict, pad: int) -> dict:
        """Pad a new stacked state's slot axis with the merge identity
        (min/pos → +big, max → -big) so it folds into the widened
        sharded carry without perturbing any real slot."""
        if not pad:
            return st
        out = {}
        for k, v in st.items():
            if v.dtype.kind == "f":
                fill = -jnp.inf if k == "max" else jnp.inf
            else:
                fill = np.iinfo(np.int64).min if k == "max" \
                    else np.iinfo(np.int64).max
            out[k] = jnp.pad(v, ((0, 0), (0, pad)),
                             constant_values=fill)
        return out

    def _finalize_psum_summed(self):
        """Post-scan cross-shard merge: psum every summed leaf."""
        runner = self._runner

        def fin(carry):
            summed, stacked = carry
            return jax.tree.map(runner._psum, summed), stacked

        return fin

    def _finalize_hash_bucket_merge(self):
        """Sharded hash-agg tree-reduce, entirely on the interconnect:
        psum the mergeable (count/sum/nonnull/present) fields, and
        merge the order-sensitive stacked fields (min/max) with an
        ALL-TO-ALL BY KEY BUCKET — each shard sends bucket ``j`` of
        its local (1, slots_m) partial to shard ``j``, reduces the
        (S, slots_m/S) pile it receives, and returns its merged bucket.
        This is the TiDB partial-at-TiKV / final-at-TiDB split mapped
        onto mesh axes: the runtime here lowers only Sum all-reduce
        (no pmin/pmax), but an all-to-all is a pure permutation, so
        the min/max merge that used to ship a (S, slots) stack over
        D2H for a host reduce now crosses ICI once and ships (slots,)."""
        runner = self._runner

        def fin(carry):
            summed, stacked = carry
            summed = jax.tree.map(runner._psum, summed)
            out_st = []
            for st in stacked:
                d = {}
                for k, v in st.items():
                    b = lax.all_to_all(v, ROW_AXES, split_axis=1,
                                       concat_axis=0, tiled=True)
                    red = jnp.max if k == "max" else jnp.min
                    d[k] = red(b, axis=0, keepdims=True)
                out_st.append(d)
            return summed, out_st

        return fin

    @staticmethod
    def _merge_stacked(specs, summed_states, stacked_states) -> list:
        """Host-side: reduce the per-shard stacks into one state per spec."""
        out = []
        for spec, sm, st in zip(specs, summed_states, stacked_states):
            d = {k: np.asarray(v) for k, v in sm.items()}
            if spec.kind == "min":
                d["min"] = np.min(np.asarray(st["min"]), axis=0)
            elif spec.kind == "max":
                d["max"] = np.max(np.asarray(st["max"]), axis=0)
            elif spec.kind == "first":
                # simple agg only (GROUP BY + FIRST is the host's)
                pos = np.asarray(st["pos"])
                i = int(np.argmin(pos))
                d["pos"] = pos[i]
                d["value"] = np.asarray(st["value"])[i]
            out.append(d)
        return out

    @staticmethod
    def _merge_bucketed(specs, summed_states, stacked_states,
                        slots: int) -> list:
        """Host-side unpack after the device bucket merge: the fetched
        stacked leaves are (S, slots_m/S) — shard j's row IS bucket j,
        already cross-shard reduced — so the merged per-slot vector is
        just the row-major flatten, trimmed of the all-to-all pad."""
        out = []
        for spec, sm, st in zip(specs, summed_states, stacked_states):
            d = {k: np.asarray(v) for k, v in sm.items()}
            for k, v in st.items():
                d[k] = np.asarray(v).reshape(-1)[:slots]
            out.append(d)
        return out

    # -- simple aggregation --

    def run_simple(self, dag, plan, host_cols, dtypes, n, feed, meta,
                   lanes: bool = False):
        """One aggregation without GROUP BY over ``feed`` → a finished
        result or a ``_Pending`` (``lanes``: as ``run_hash``)."""
        runner = self._runner
        # the fused Pallas kernel serves simple aggregations too (r6):
        # a single-slot grid turns SUM/COUNT/AVG into one direct-index
        # pass — the XLA scan's per-step and fusion-boundary costs
        # (pallas_hash.py module doc) taxed config 3 the same way they
        # taxed config 4.  Its finalize is the GROUP BY's
        # (``_packed_columns``: one native call that holds the GIL, the
        # planes wrapped by ``_hash_columns``) over that one slot
        layouts = p8 = pf = arg_nbytes = arg_ok_is_mask = None
        if matmul_supported(plan.specs):
            arg_nbytes = meta.get("simple_arg_nbytes")
            if arg_nbytes is None:
                arg_nbytes = meta["simple_arg_nbytes"] = \
                    self._arg_nbytes(plan, host_cols(), n)
            arg_is_real = [r is not None and r.ret_type is EvalType.REAL
                           for r in plan.agg_rpns]
            arg_ok_is_mask = self._arg_ok_is_mask(plan, feed)
            layouts, p8, pf = build_layouts(plan.specs, arg_is_real,
                                            arg_nbytes, arg_ok_is_mask)
        agg_out = self._agg_out(plan)

        def result(cols):
            return runner._result(dag, list(agg_out[0]), cols)

        mode = pallas_hash.MODE_SIMPLE
        if agg_bodies(runner._is_tpu, runner._nshards(), plan, feed, dtypes,
                      layouts, p8, pf, 1, mode, False)[0] == "pallas_hash":

            def from_packed(dag, parts, LO):
                # the fetched accumulator is a grid of ONE slot: no
                # key, no NULL group, no scrap row (``slots`` 1)
                return runner._result(
                    dag, list(agg_out[0]), self._packed_columns(
                        plan, parts, LO, p8, layouts, 1, 0, 1, None,
                        _result_shape(plan)))

            got = self._try_pallas(dag, plan, feed, dtypes, n, 0, 1,
                                   layouts, p8, arg_nbytes, arg_ok_is_mask,
                                   mode, from_packed, meta=meta, lanes=lanes)
            if got is not None:
                return got

        chunk = runner._feeds.pick_chunk(feed["n_pad"], _CHUNK_AGG)
        n_cols = len(plan.used_cols)
        key = runner._kern_key("simple", dag, feed, chunk, tuple(dtypes))
        carry = self._cached_carry(key,
                                   lambda: self._init_agg_carry(plan, None))
        kern = runner._shard_kernel(
            key, lambda: self._scan_program(
                "simple", self._build_simple_body(plan, n_cols),
                self._finalize_psum_summed(), feed["null_flags"],
                feed["n_pad"], chunk, carry, len(feed["flat"])))
        with runner._dispatch_phase("simple", key):
            carry = kern(carry, runner._cached_scalar(n, jnp.int64),
                         runner._cached_scalar(0, jnp.int64),
                         *feed["flat"])

        def fin(fetched):
            summed, stacked = fetched
            # summed fields already psum-merged on ICI; only the
            # per-shard (S,) min/max/first scalars reduce here (a span
            # of its own on a mesh)
            with nullcontext() if runner._single \
                    else trace.phase("shard_merge"):
                merged = self._merge_stacked(plan.specs, summed, stacked)
            return result(_hash_columns(
                agg_out, _simple_planes(plan.specs, merged),
                _result_shape(plan)))

        return _Pending(carry, fin)


# -- the finalize: the fetched accumulator in, planes and Columns out.
#    Pure functions of numpy arrays: no runner, no device. --

def _build_lane_program(call, k: int, out_sharding=None):
    """The jitted k-lane program: the built ``pallas_call`` once a lane
    (k ``pallas_hash`` custom calls in one module run, each over its
    own lane's columns and scalars), the k packed results stacked, in
    ``out_sharding``'s memory where one is given.  Named as the single
    launch's program is (``jit_pallas_hash``)."""

    def pallas_hash(scals, cols):
        return jnp.stack([call(s, *c) for s, c in zip(scals, cols)])

    return jax.jit(pallas_hash, out_shardings=out_sharding)


class _LaneLaunch:
    """The shared fetch of one launch of lanes: the stacked output,
    staged to pinned host memory once, read back once (memoized, from
    whichever lane's completion worker joins first), its lanes'
    ``_LanePending.fetch`` slicing it.  A failed readback is memoized
    too and re-raised to every lane, each of which degrades by itself
    (``DeferredResult._resolve``); ``strike_once`` lets only the first
    of them strike the slice's health score."""

    __slots__ = ("_runner", "_tree", "_mu", "_memo", "_struck")

    def __init__(self, runner, tree):
        self._runner = runner
        # staged and set on its way to the host as any launch's output
        self._tree = _Pending(tree, None).tree
        self._mu = threading.Lock()
        self._memo = None
        self._struck = False

    def fetch(self):
        with self._mu:
            if self._memo is None:
                try:
                    self._memo = ("ok", self._runner._readback(self._tree))
                except BaseException as e:  # noqa: BLE001 — memoized
                    self._memo = ("err", e)
                self._tree = None
            kind, val = self._memo
        if kind == "err":
            raise val
        return val

    def strike_once(self) -> bool:
        with self._mu:
            first, self._struck = not self._struck, True
        return first


def finalize_packed(parts, LO, p8, layouts, specs, slots, base, capacity,
                    slot_keys):
    """The fetched Pallas accumulator → ``finalize_hash``'s planes.

    ``parts``: one (2, HI, p8·LO) int32 pair per tile (one on a
    whole-feed launch and on a mesh); they add.  Returns
    ``(((keys, key_valid), planes), native)``.  ``slots`` 1 is the grid
    of an aggregation without GROUP BY (``pallas_hash.MODE_SIMPLE``): no
    key (``keys`` and ``key_valid`` None), no NULL group, and ONE row
    whether or not a row reached the slot (COUNT 0, SUM and AVG NULL:
    ``finalize_simple``'s rule, where a GROUP BY answers no group).

    Where the extension built and the input is what the Pallas path
    produces — int32 parts, integer layouts of COUNT / SUM / AVG, a key
    domain inside int64 — this is ONE call into
    ``native.hash_finalize_packed``, which holds the GIL from entry to
    return: the numpy chain below makes ~24 array calls (~10 over one
    slot) on planes of 1k-4k elements, numpy drops the GIL around each,
    and on a serving store every drop queues behind ~10 runnable
    threads (PERF.md section 6, PRs 26, 28 and 35).  The planes are
    views of buffers sized ``capacity + 1`` (``np.empty`` and a slice
    drop no GIL).  A part that is not C-contiguous is copied so first:
    past 128 sublanes of slots the TPU hands the accumulator back with
    its sublane dimension minor-most (a result layout of ``{2,3,1,0}``
    for a lane program's ``(k, 2, 512, 192)``, which ``np.asarray``
    keeps as strides), and the chain's ~24 passes over strided planes
    cost fifty times the one copy (PERF.md section 6, PR 40).  What it
    adapts to is in its input: anything else
    takes the numpy chain (``_sum_parts`` → ``_pallas_states`` →
    ``finalize_hash`` / ``finalize_simple``), the same bytes, kept as
    the fallback and as the oracle of tests/test_finalize_native.py.
    ``native`` says which ran; the caller counts it (``/health``
    ``device_mesh.finalize``).
    """
    call = native.hash_finalize_packed
    simple = slots == 1
    keys_fit_int64 = slot_keys.dtype == np.int64 if slot_keys is not None \
        else base + capacity <= _I64_MAX
    desc = None
    if call is not None and keys_fit_int64 and all(
            p.dtype == np.int32 for p in parts):
        desc = _native_layout_desc(layouts)
    if desc is not None:
        parts = [np.ascontiguousarray(p) for p in parts]
        n = 1 if simple else capacity + 1   # + the NULL slot
        key = (None, None) if simple else \
            (np.empty(n, np.int64), np.empty(n, np.bool_))
        outs = [(np.empty(n, np.float64 if lay.kind == "avg" else np.int64),
                 np.empty(n, np.bool_)) for lay in layouts]
        k = call(parts, LO, p8, capacity, 0 if slot_keys is not None
                 else base, slot_keys, desc, *key, outs)
        return (key if simple else (key[0][:k], key[1][:k]),
                [(vals[:k], ok[:k]) for vals, ok in outs]), True
    present, states = _pallas_states(
        _sum_parts(parts), LO, p8, layouts, specs, slots)
    if simple:
        return _simple_planes(specs, [
            {k: np.asarray(v).reshape(-1)[0] for k, v in s.items()}
            for s in states]), False
    return finalize_hash(
        specs, {"present": present, "overflow": False, "states": states},
        base, capacity, slot_keys=slot_keys), False


def _simple_planes(specs, merged):
    """``finalize_simple``'s one row in ``finalize_hash``'s form, every
    plane of length one and no key: what ``_hash_columns`` wraps for an
    aggregation without GROUP BY that the native call did not serve
    (the XLA ``simple`` body's states; the Pallas body's through the
    numpy chain)."""
    return (None, None), [
        (np.array([0 if v is None else v]), np.array([v is not None]))
        for v in finalize_simple(specs, merged)]


# kernels.PlaneLayout kinds the native finalize serves, by the code
# native/fastbuild.cpp knows them by (``FinKind``)
_NATIVE_FINALIZE_KINDS = {"count_star": 0, "count": 1, "sum": 2, "avg": 3}


def _native_layout_desc(layouts):
    """``layouts`` flattened for ``native.hash_finalize_packed`` — per
    spec: kind code, ``ok_plane``, ``nb``, then the ``nb`` byte-plane
    indices — or None where one is outside what that call serves (a
    float plane, a kind outside the four)."""
    flat = []
    for lay in layouts:
        code = _NATIVE_FINALIZE_KINDS.get(lay.kind)
        if code is None or lay.f32_plane is not None:
            return None
        flat += (code, lay.ok_plane or 0, lay.nb, *lay.byte_planes)
    return np.array(flat, np.int64)


def _sum_parts(parts):
    """Merge per-tile packed partials (psum-partial semantics)."""
    packed = np.asarray(parts[0])
    for p in parts[1:]:
        packed = packed + np.asarray(p)
    return packed


def _pallas_states(packed, LO, p8, layouts, specs, slots):
    """Packed (2, HI, p8*LO) accumulator pair → (present, states).

    The tight slot grid (no scrap slot; NULL slot only when the key
    may be NULL) may hold fewer than ``slots`` rows: the dropped
    slots are zero by construction (nothing ever scatters there),
    so zero-pad back to the shared layout.
    """
    S = pallas_hash.unpack_to_int64(packed)
    have = min(slots, S.shape[0] * LO)
    S8 = twolevel_unpack(S, p8, LO, have, xp=np)
    if have < slots:
        S8 = np.pad(S8, ((0, 0), (0, slots - have)))
    return states_from_matmul(layouts, specs, S8, None, xp=np)


def _result_shape(plan, key_bounds=None):
    """What ``_hash_columns`` needs of a plan beyond ``_agg_out`` to
    turn the DEVICE's aggregates and its one key plane into the plan's
    columns, None where they are the same: ``(recipes, a composite
    key's bounds, the keys' code widths)``."""
    if plan.agg_recipes is None and key_bounds is None and \
            not any(plan.key_codes):
        return None
    return plan.agg_recipes, key_bounds, plan.key_codes


def _hash_columns(agg_out, finalized, shape=None):
    """Finalized planes → result Columns (aggregates, then the keys of
    a GROUP BY): the ONE place where planes become Columns for every
    aggregation body, whether ``ops.agg.finalize_hash``, the native
    call of ``finalize_packed`` or ``_simple_planes`` made them.  No
    Python value is made per group between the fetched accumulator and
    the wire encoder.  ``agg_out``: ``DeviceAggregator._agg_out`` of the
    plan; ``finalized``: ``finalize_hash``'s ``((keys, key_valid),
    planes)``, ``keys`` None where there is no GROUP BY; ``shape``:
    ``_result_shape`` of the plan: limb pairs put together
    (``lowering.recipe_planes``), a composite key's number
    taken apart into its keys, a code plane's codes back to bytes."""
    (keys, key_valid), planes = finalized
    fts, dts, fracs = agg_out
    recipes, key_bounds, key_codes = shape or (None,) * 3
    if recipes is not None:
        planes = lowering.recipe_planes(recipes, planes)
    cols = [Column(ft.eval_type, vals.astype(dt, copy=False), ok)
            if frac is None else
            # a lowered DECIMAL's SUM: the exact integer sums, handed
            # on as the scaled plane they were summed as (``Column.frac``:
            # a chunk reply carries the plane; ``Decimal``s are made
            # where a row is asked for)
            Column(ft.eval_type, np.asarray(vals, np.int64), ok, frac)
            for ft, dt, frac, (vals, ok) in zip(fts, dts, fracs, planes)]
    if keys is None:
        return cols
    parts = [keys]
    if key_bounds is not None:
        # (a composite key is never NULL: ``run_hash``)
        rest, parts = np.asarray(keys, np.int64), []
        for b, s in reversed(key_bounds):
            parts.append(rest % s + b)
            rest = rest // s
        parts.reverse()
    for vals, width in zip(parts, key_codes or (0,) * len(parts)):
        cols.append(Column(EvalType.BYTES, code_bytes(vals, width),
                           key_valid) if width
                    else Column(EvalType.INT, vals, key_valid))
    return cols
