"""Pallas TPU kernel for the direct-index hash aggregation.

The XLA two-level one-hot kernel (kernels.twolevel_partial) is limited by
two platform costs it cannot remove:

1. XLA materializes ``dot_general`` operands in HBM at fusion
   boundaries, so the generated one-hot planes (~136 B/row) round-trip
   through HBM — measured ~23 us per 2^16-row block, 40+ ms per 100M-row
   request against the feed-read roofline.
2. ``lax.scan`` over a large xs feed costs ~31 us per step on this
   runtime, another ~100 ms at 2^15-row chunks.

This kernel fuses one-hot generation, the MXU contraction, and the
accumulator into one ``pallas_call``: planes are generated in VMEM and
consumed immediately (never touching HBM), and the sequential grid
replaces the scan.

Three slot-id modes share the kernel body (r6 — the direct-index kernel
is the default body for every aggregation shape that qualifies):

- ``dense``  — GROUP BY over a small contiguous key domain: the key
  expression evaluates in-kernel and ``key - base`` indexes the grid
  directly (BASELINE config 4).  Several keys (a COMPOSITE key) index
  it by ``(k0 - base0) * span1 + (k1 - base1)`` and so on, each key's
  base and span an operand beside the plan's constants, the product of
  the spans inside the grid (TPC-H Q1's two CHAR(1) keys: 18 x 10).
- ``sparse`` — arbitrary int64 key domains: the host dictionary-encodes
  the keys once per snapshot (aggregate.py _sparse_slots) and the dense slot
  ids ride as ONE extra int32 input column, so the kernel never touches
  the (Mosaic-unsupported) int64 key values (config 4s).  Columns the
  kernel does not evaluate (the raw key) stay out of its input set, so
  their dtype/NULLability cannot disqualify the plan.
- ``simple`` — no GROUP BY: a single-slot grid (every masked row aims at
  slot 0), which turns SUM/COUNT/AVG into one fused HBM pass
  (config 3).

Design (r5: each choice was swept on a v5e; the kernel's time today and
its share of the HBM roofline are in PERF.md section 5, ledger-cited):

- **The MXU contraction is the binding constraint, not HBM.**  An exact
  scatter-by-matmul consumes one int8 K-element per row, so kernel time
  ~= rows / dot-rate regardless of byte width.
- **Tight slot grid.**  Rows with no destination (row-mask off,
  predicate false, key out of range) point their one-hot column at a
  sentinel ``hi`` row that does not exist (``idx = HI*LO``): the column
  is all-zero and the row contributes nothing — no scrap slot, and for
  a provably non-NULL key no NULL slot either, so 1024 groups fit
  exactly in HI=32 sublanes (was 40 with scrap+NULL: 20% more one-hot
  generation and dot).
- **Dead grid blocks skip the MXU (r6).**  The feed pads to a bucketed
  shape (feed.py ``pad_rows``: the 9/8-geometric grid bounds compile
  classes), but the bucketing must tax only the CACHE KEY, not the
  computed extent: blocks entirely outside [row_lo, row_hi) gate the
  whole one-hot + dot body behind ``pl.when``, so a masked block costs
  its input DMA and the ~10 us grid step — not the contraction that is
  the kernel's binding constraint (up to 12.5% of pass time before).
- **Per-plane dots, no concatenation.**  The weight planes
  (mask / ok / value-byte) each dot against the shared ``A`` one-hot and
  accumulate into their lane slice of the packed output; concatenating
  them first costs a (P*LO, B) VMEM copy per block (~1 ms/100M rows).
- **BLOCK = 2^18.**  Grid-step fixed cost is ~10 us on this runtime;
  halving the step count from 2^17 blocks saves ~4 ms per 100M rows.
  int8 operands with int32 accumulation are exact at any block size
  (products <= 127, per-dot sums <= 127*2^18 << 2^31), unlike bf16/f32
  whose 2^24 mantissa bounds the contraction at 2^17 rows.  **The step
  follows the grid**: past 4,096 slots (128 sublanes of A) a step takes
  fewer rows, so that A stays the 32 MB it is there (``block_rows``:
  2^16 rows at 16,384 slots, TPC-H Q15's GROUP BY l_suppkey); every
  grid up to 4,096 slots keeps 2^18.
- Everything is **lane-major**: 1-D row vectors are natively (1, B), so
  one-hots are built TRANSPOSED — ``A (HI, B)``, planes ``(LO, B)`` —
  with major-dim broadcasts, and the contraction is an NT-form
  ``dot_general`` over the lane axis.  Comparisons/selects run in int32
  (int8 compares and int8 iota are unsupported), one astype(int8) per
  operand.  The kernel call runs under ``jax.enable_x64(False)`` — with
  x64 on, Python ints in index maps trace as i64 and Mosaic rejects the
  module.
- The accumulator is an int32 pair (alo, ahi): per-block partials are
  exact in int32, and ``x == (x >> 16 << 16) + (x & 0xFFFF)`` makes the
  pair reconstruction exact in int64 on the host (int64 is unavailable
  inside Mosaic kernels).

The packed output (2, HI, P8*LO) matches twolevel_partial's layout, so
the host-side unpack (kernels.twolevel_unpack / states_from_matmul) is
shared with the XLA path; when the tight grid has fewer than
``capacity + 2`` slots the caller zero-pads the NULL/scrap rows.

Reference for the role this kernel plays: the fast hash-agg executor
(components/tidb_query_executors/src/fast_hash_aggr.rs) — BASELINE
config 4's hot path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..expr.eval import eval_rpn
from ..expr.rpn import RpnColumnRef, RpnConst
from .selection import split_params

# Rows per grid step of every grid up to 4,096 slots, and the unit the
# feeds pad to (a multiple of every smaller step: ``block_rows``).
# Swept on v5e at 100M rows (r5): 2^18 beats 2^17 by ~3.5 ms/pass
# (fewer ~10 us grid steps) and 2^19 regresses (VMEM pressure breaks
# double-buffering).
BLOCK = 1 << 18

# Low radix of the slot factorization: slot = hi*LO + lo.  32 balances
# the A one-hot (slots/LO sublane rows, the costlier operand to
# generate) against plane width (measured: LO=16 doubles A-gen cost for
# a ~2x slower kernel; LO=64 pushes multi-plane outputs past one lane
# tile).
LO = 32

# The A one-hot is (slots/LO, rows a step) int8 in VMEM: 32 MB at 4,096
# slots and BLOCK rows, which leaves headroom for the weight planes
# under the ~110 MB VMEM budget.  A wider grid keeps A at that size by
# taking fewer rows a step (``block_rows``).
A_BYTES = 1 << 25

# Slot-span cap: 16,384 slots are 512 sublanes of A and 2^16 rows a
# step.  Above this the XLA two-level path serves (up to its own 2^20
# ceiling): at the next doubling a step is 2^15 rows, four times the
# ~10 us grid steps for the same rows, the contraction twice as long
# again (it follows rows x sublanes), and the accumulator pair and the
# output are 2 MiB each a launch.
MAX_SLOTS = 1 << 14

MODE_DENSE = "dense"
MODE_SPARSE = "sparse"
MODE_SIMPLE = "simple"

_i32 = jnp.int32


def _rpn_cols(rpn) -> set:
    return {n.col_idx for n in rpn.nodes if isinstance(n, RpnColumnRef)}


def plan_params(plan) -> tuple:
    """An aggregation's predicate and aggregate constants as kernel
    OPERANDS → ``(sel_rpns, agg_rpns, values, dtypes)``: the plan's
    selection and aggregate-argument expressions with every numeric
    constant replaced by a reference to parameter ``len(used_cols) + i``
    (``selection.split_params``, the selection route's own hoisting),
    the constants' values in that order, and their device dtype
    buckets.  The expressions are the same for every constant tuple of
    the plan's const-blind class (``DAGRequest.class_key``), so ONE
    built kernel serves them all and the values ride its
    scalar-prefetch operand beside the row bounds.  The GROUP BY key's
    constants stay in its expression: the key bounds a kernel is built
    for depend on them.  Memoized on the plan."""
    got = plan.agg_params
    if got is None:
        n_sel = len(plan.sel_rpns)
        rpns, vals, dts = split_params(
            list(plan.sel_rpns) +
            [r for r in plan.agg_rpns if r is not None],
            len(plan.used_cols))
        aggs = iter(rpns[n_sel:])
        got = plan.agg_params = (
            rpns[:n_sel],
            [None if r is None else next(aggs) for r in plan.agg_rpns],
            vals, dts)
    return got


def key_consts(plan) -> tuple:
    """What of a plan stays exact in a const-blind identity (the
    kernel's cache key, the request memo's): the GROUP BY keys'
    constants by value, since the key bounds depend on them, and the
    aggregates' STRUCTURAL constants (``fixed``: never operands, so a
    kernel is built around their values; device/lowering.py) with the
    aggregates summed as limbs.  Memoized on the plan (asked twice a
    request)."""
    got = plan.ident
    if got is None:
        keys = tuple(nd.value for r in plan.key_rpns for nd in r.nodes
                     if isinstance(nd, RpnConst))
        fixed = tuple(nd.value for r in plan.agg_rpns if r is not None
                      for nd in r.nodes
                      if isinstance(nd, RpnConst) and nd.fixed)
        got = plan.ident = keys + (("fixed", fixed, plan.limbs),) \
            if fixed or plan.limbs else keys
    return got


def kernel_col_ids(plan, mode: str) -> tuple:
    """used_cols positions whose VALUES the kernel evaluates in VMEM.

    Only these columns become kernel inputs (and must therefore be int32
    and non-nullable); a sparse GROUP BY key is consumed as precomputed
    slot ids instead, so its raw (often int64 / nullable) column never
    reaches the kernel.
    """
    ids: set = set()
    for r in plan.sel_rpns:
        ids |= _rpn_cols(r)
    for r in plan.agg_rpns:
        if r is not None:
            ids |= _rpn_cols(r)
    if mode == MODE_DENSE:
        for r in plan.key_rpns:
            ids |= _rpn_cols(r)
    return tuple(sorted(ids))


def key_never_null(plan) -> bool:
    """True when the group key provably cannot be NULL: a bare column
    reference over a feed column with no validity plane.  (The
    ``supported`` gate already requires every kernel-input column be
    non-nullable; expression keys keep a NULL slot because a function
    may introduce NULL, e.g. out-of-domain casts.)"""
    return all(len(r.nodes) == 1 and isinstance(r.nodes[0], RpnColumnRef)
               for r in plan.key_rpns)


def n_slots(plan, capacity: int, mode: str = MODE_DENSE) -> int:
    """Slots the kernel actually materializes (tight grid)."""
    if mode == MODE_SIMPLE:
        return 1
    if mode == MODE_SPARSE:
        # the slot encoding (aggregate.py _sparse_slots) reserves slot
        # ``capacity`` for NULL keys; whether a given snapshot has any
        # is data-dependent, so the slot is always materialized
        return capacity + 1
    return capacity + (0 if key_never_null(plan) else 1)


def sublanes(slots: int) -> int:
    """Rows of the A one-hot a ``slots``-slot grid needs (``HI``): a
    sublane for every LO slots, in whole tiles of eight."""
    return (-(-slots // LO) + 7) // 8 * 8


def block_rows(slots: int) -> int:
    """Rows a grid step of a ``slots``-slot grid takes: BLOCK while the
    A one-hot fits ``A_BYTES`` (every grid up to 4,096 slots), else the
    largest power of two that keeps it there (2^16 at 16,384 slots), so
    that a step divides the feeds' padding whatever the grid."""
    rows = A_BYTES // sublanes(slots)
    return min(BLOCK, 1 << (rows.bit_length() - 1))


def supported(plan, feed, dtypes, pf: int, capacity: int,
              n_shards: int = 1, mode: str = MODE_DENSE) -> bool:
    """Static gate for the Pallas fast path.

    int32 kernel-input columns only (int64 is unsupported in Mosaic),
    no NULL validity planes on kernel inputs (they would need int8
    plane inputs), int byte-plane aggregates only (pf == 0), and a slot
    span whose one-hot fits VMEM.  Columns outside the kernel's input
    set (e.g. a sparse key consumed as slot ids) are exempt.

    ``n_shards > 1``: the sharded mesh runs this same kernel PER SHARD
    under shard_map — each shard's grid covers its local feed slice,
    so the padded feed must split into whole BLOCKs per shard (whole
    steps of any grid: ``block_rows`` divides BLOCK); the
    per-shard packed partials psum on ICI (aggregate.py
    _pallas_sharded_wrap).
    """
    if pf != 0:
        return False
    if any(dt != "int32" for dt in plan_params(plan)[3]):
        return False        # the prefetch operand is int32 scalars
    if n_slots(plan, capacity, mode) > MAX_SLOTS:
        return False
    if mode == MODE_DENSE and len(plan.key_rpns) > 1 and \
            not key_never_null(plan):
        # a composite key has no NULL slot: SQL keeps (NULL, 1) and
        # (NULL, 2) apart, which one slot cannot
        return False
    if feed["n_pad"] % (max(1, n_shards) * BLOCK) != 0:
        return False
    kcols = kernel_col_ids(plan, mode)
    if not kcols:
        return False        # zero-input pallas_call; XLA serves trivially
    for i in kcols:
        if feed["null_flags"][i] or dtypes[i] != "int32":
            return False
    return True


# libtpu names the custom-call instruction (a profile's 'XLA Ops' event)
# after the innermost name scope, which is pallas_call's ``name``.  It
# says which kernel this is and keeps the call target's "tpu_custom_call",
# the substring by which the benchmark's traffic files find a cell's main
# kernel (BENCHMARK.json: kernel.main_ms).
KERNEL_NAME = "pallas_hash_tpu_custom_call"

# cached prefetch-scalar vectors a built kernel keeps (``scalars``): one
# a (feed, tile, constant tuple), 16-50 bytes each on the device
_SCALARS_MAX = 8192


def build(plan, layouts, p8: int, capacity: int, nblk: int,
          col_map, mode: str = MODE_DENSE):
    """Build the pallas_call for one (plan, grid-span) pair.

    ``nblk`` is the GRID SPAN in grid steps of ``block_rows(slots)``
    rows (``run.block_rows``), not the whole feed: the
    "region → chip, bucket → tile" mapping (SURVEY §5.7, pd_client
    buckets) dispatches one kernel per covered bucket span — the
    scalar-prefetched block offset shifts the input index map, so a
    request over one bucket of a 100M-row region costs one bucket's
    blocks, and disjoint spans' packed partials merge by addition
    exactly like psum partials.

    ``col_map[i]``: input-ref position of used_cols[i], or -1 when the
    column is not a kernel input (sparse keys, columns only the host
    touches).  In ``sparse`` mode one extra int32 slot-id column rides
    after the mapped columns.

    The plan's predicate and aggregate constants are not in the
    module: they are operands (``plan_params``), int32 scalars after
    the four bounds in the scalar-prefetch vector, so the built kernel
    is that of the plan's const-blind class.

    Returns ``(run, LO, HI)`` with
    ``run(row_lo, row_hi, base, blk0, cols, params=()) ->
    (2, HI, p8*LO) int32``
    packed accumulator pair covering absolute rows
    [row_lo, row_hi) ⊆ [blk0*B, (blk0+nblk)*B), B the grid's step;
    ``cols`` is the already-selected input tuple (mapped columns, then
    slot ids when sparse), ``params`` the request's constant values.
    """
    slots = n_slots(plan, capacity, mode)
    HI = sublanes(slots)
    W = p8 * LO
    B = block_rows(slots)
    # the sentinel hi value for rows with no destination slot: outside
    # [0, HI), so the row's one-hot column is all-zero
    SENT = HI * LO
    nullable = mode != MODE_SIMPLE and (
        mode == MODE_SPARSE or not key_never_null(plan))
    sel_rpns, agg_rpns, _vals, param_dts = plan_params(plan)
    n_params = len(param_dts)
    key_rpns = plan.key_rpns
    # a composite key's (base, span) pairs ride the prefetch scalars
    # ahead of the plan's constants (``key_scalars``)
    n_keysc = 2 * len(key_rpns) if mode == MODE_DENSE and \
        len(key_rpns) > 1 else 0
    lobits = LO.bit_length() - 1
    n_cols_in = sum(1 for p in col_map if p >= 0)
    sparse = mode == MODE_SPARSE
    n_in = n_cols_in + (1 if sparse else 0)

    def kernel(sref, *refs):
        out_ref = refs[n_in]
        alo, ahi = refs[n_in + 1], refs[n_in + 2]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            alo[:] = jnp.zeros_like(alo)
            ahi[:] = jnp.zeros_like(ahi)

        row_lo = sref[0]
        row_hi = sref[1]
        base = sref[2]
        blk0 = sref[3]
        row0 = (i + blk0) * _i32(B)

        # dead-block guard: a block entirely outside [row_lo, row_hi)
        # (bucketed feed padding, bucketed tile spans) skips one-hot
        # generation and the dots — the bucketing then costs only this
        # block's DMA + grid step, never MXU time
        @pl.when((row0 < row_hi) & (row0 + _i32(B) > row_lo))
        def _():
            riota = lax.broadcasted_iota(_i32, (1, B), 1)[0]
            rows = row0 + riota
            row_mask = (rows >= row_lo) & (rows < row_hi)

            # kernel-input columns are all-valid (gated): validity ==
            # row_mask; unmapped columns never appear in these rpns
            pairs = [None if p < 0 else (refs[p][:], row_mask)
                     for p in col_map]
            # the request's constants: scalars from SMEM, valid as a
            # baked constant is (eval._const_pair)
            true0 = jnp.ones((), jnp.bool_)
            pairs += [(sref[4 + n_keysc + j], true0)
                      for j in range(n_params)]
            mask = row_mask
            for rpn in sel_rpns:
                v, ok = eval_rpn(rpn, pairs, B, jnp)
                mask = mask & ok & (v != 0)

            if mode == MODE_SIMPLE:
                # single-slot grid: every masked row lands in slot 0
                idx = jnp.where(mask, _i32(0), _i32(SENT))
            elif sparse:
                # precomputed slot ids: [0, capacity) groups, capacity
                # = NULL-key slot, capacity+1 = scrap/padding → SENT
                s = refs[n_cols_in][:].astype(_i32)
                idx = jnp.where(mask & (s < _i32(slots)), s, _i32(SENT))
            elif n_keysc:
                # composite key: every key inside its own span, the
                # slot their mixed-radix number (< the spans' product
                # <= capacity: run_hash); never NULL (``supported``)
                rel = _i32(0)
                km = in_range = jnp.ones((B,), jnp.bool_)
                for j, rpn in enumerate(key_rpns):
                    kv, ok = eval_rpn(rpn, pairs, B, jnp)
                    kv = jnp.broadcast_to(kv, (B,)).astype(_i32)
                    km = km & jnp.broadcast_to(ok, (B,))
                    span_j = sref[5 + 2 * j]
                    rel_j = kv - sref[4 + 2 * j]
                    in_range = in_range & (rel_j >= _i32(0)) & \
                        (rel_j < span_j)
                    rel = rel * span_j + rel_j
                idx = jnp.where(mask & km & in_range, rel, _i32(SENT))
            else:
                kv, km = eval_rpn(key_rpns[0], pairs, B, jnp)
                kv = jnp.broadcast_to(kv, (B,)).astype(_i32)
                km = jnp.broadcast_to(km, (B,))
                rel = kv - base
                in_range = (rel >= _i32(0)) & (rel < _i32(capacity))
                # slot layout: [0, capacity) groups, capacity = NULL-key
                # slot (only materialized for expression keys); rows
                # with no slot — masked out, out-of-range, or NULL under
                # a non-null key — aim at SENT: hi = HI, matching no
                # one-hot row, so the whole column is zero and the row
                # vanishes from every plane.
                if nullable:
                    idx = jnp.where(mask & km & in_range, rel, _i32(SENT))
                    idx = jnp.where(mask & ~km, _i32(capacity), idx)
                else:
                    idx = jnp.where(mask & km & in_range, rel, _i32(SENT))
            hi_ = idx >> lobits
            lo_ = idx & _i32(LO - 1)

            hi_iota = lax.broadcasted_iota(_i32, (HI, B), 0)
            lo_iota = lax.broadcasted_iota(_i32, (LO, B), 0)
            A8 = jnp.where(hi_[None, :] == hi_iota, _i32(1),
                           _i32(0)).astype(jnp.int8)
            cmp = lo_[None, :] == lo_iota
            zero = jnp.zeros((LO, B), _i32)
            dn = (((1,), (1,)), ((), ()))

            def accum(p, plane_i32):
                prod = lax.dot_general(A8, plane_i32.astype(jnp.int8), dn,
                                       preferred_element_type=_i32)
                sl = slice(p * LO, (p + 1) * LO)
                alo[:, sl] += prod & _i32(0xFFFF)
                ahi[:, sl] += prod >> 16

            # plane 0 = slot-presence counts; rows without a slot are
            # already dropped by their zero A column, so no mask multiply
            accum(0, jnp.where(cmp, _i32(1), zero))
            p = 1
            for lay, rpn in zip(layouts, agg_rpns):
                if lay.kind == "count_star":
                    continue
                v, ok = eval_rpn(rpn, pairs, B, jnp)
                v = jnp.broadcast_to(v, (B,)).astype(_i32)
                okb = jnp.broadcast_to(ok, (B,))
                aliased = lay.ok_plane == 0
                if not aliased:
                    ok32 = jnp.where(okb, _i32(1), _i32(0))
                    accum(p, jnp.where(cmp, ok32[None, :], zero))
                    p += 1
                if lay.byte_planes:
                    nb = lay.nb
                    # (four planes: 2^31 is int32's -2^31, and the add
                    # wraps to the same bits)
                    biased = v + _i32((1 << (8 * nb - 1)) if nb < 4
                                      else -(1 << 31))
                    if not aliased:
                        # NULL argument on a live row: bytes must not leak
                        biased = biased * ok32
                    for b in range(nb):
                        byte = ((biased >> (8 * b)) & _i32(0xFF)) - _i32(128)
                        if not aliased:
                            byte = jnp.where(okb, byte, _i32(0))
                        accum(p, jnp.where(cmp, byte[None, :], zero))
                        p += 1

        @pl.when(i == nblk - 1)
        def _():
            out_ref[0] = alo[:]
            out_ref[1] = ahi[:]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblk,),
        in_specs=[pl.BlockSpec((B,), lambda i, s: (i + s[3],))
                  for _ in range(n_in)],
        out_specs=pl.BlockSpec((2, HI, W), lambda i, s: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((HI, W), _i32),
                        pltpu.VMEM((HI, W), _i32)],
    )
    # Names for a profile: KERNEL_NAME for the op, and the jitted
    # wrapper gives the single-device program ('XLA Modules') its
    # compile class instead of pallas_call's own ``jit_wrapped``.
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((2, HI, W), _i32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 << 20),
        name=KERNEL_NAME,
    )

    @jax.jit
    def pallas_hash(scal, *cols):
        return call(scal, *cols)

    scal_cache: dict = {}

    def scalars(row_lo, row_hi, base, blk0, params=()):
        """The kernel's prefetch scalars for concrete row bounds and
        constants, on the device, cached per (feed, tile, constant
        tuple): a tuple seen before costs no H2D."""
        if mode != MODE_DENSE:
            # only the dense key expression reads ``base``; a sparse
            # domain's minimum (up to 2^62) does not fit the int32
            # prefetch scalars (numpy 2 raises instead of wrapping)
            base = 0
        key = (row_lo, int(row_hi), int(base), int(blk0)) + tuple(params)
        scal = scal_cache.get(key)
        if scal is None:
            if len(scal_cache) >= _SCALARS_MAX:
                scal_cache.clear()
            scal = jnp.asarray(np.asarray(key, np.int32))
            scal_cache[key] = scal
        return scal

    def run(row_lo, row_hi, base, blk0, cols, params=()):
        # the scalar tuple is constant per (feed, tile): cache it, on
        # the device the kernel runs on, so a warm request issues no
        # scalar H2D and the jitted call takes it as it lies (what an
        # argument that lies elsewhere costs a launch was measured on
        # four chips: 1.2 ms each, PERF.md section 6, PR 30).  Traced
        # scalars (the sharded per-shard path: row bounds depend on
        # lax.axis_index) stack instead of caching — inside shard_map
        # there is no H2D to save; theirs are the runner's cached
        # scalars, replicated over its mesh (DeviceRunner._cached_scalar).
        if isinstance(row_lo, (int, np.integer)):
            scal = scalars(row_lo, row_hi, base, blk0, params)
        else:
            with jax.enable_x64(False):
                scal = jnp.stack([
                    jnp.asarray(v).astype(jnp.int32)
                    for v in (row_lo, row_hi,
                              base if mode == MODE_DENSE else 0, blk0,
                              *params)])
        with jax.enable_x64(False):
            return pallas_hash(scal, *cols)

    # what a multi-lane program (aggregate ``_build_lane_program``) is
    # made of: the bare pallas_call, once a lane, and each lane's cached
    # scalars
    run.call = call
    run.scalars = scalars
    run.block_rows = B
    return run, LO, HI


def unpack_to_int64(packed: np.ndarray) -> np.ndarray:
    """(2, HI, W) int32 pair -> (HI, W) exact int64 sums."""
    lo = packed[0].astype(np.int64)
    hi = packed[1].astype(np.int64)
    return lo + (hi << 16)
