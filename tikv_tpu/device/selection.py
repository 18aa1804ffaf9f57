"""Late-materialized device selection & scan kernels (pallas_hash's sibling).

Bench configs 1-2 (table scan, selection) were the last shapes pinned to
the host backend: a selection materializes its FULL output through D2H,
so the old device pass (predicate mask on device, n bool bytes back,
host filter) only added transfer cost on top of the same host gather.
Late materialization (Abadi et al., column-store execution) removes
exactly that cost: evaluate the predicate on device over the resident
HBM feed, move only a COMPACT selection vector, and gather the k
surviving rows host-side from the columnar snapshot that is already
resident — the same sparse-readback discipline an inference stack uses
to avoid shipping dense activations off-chip.

D2H volume per route (n scanned rows, k selected):

  ``mask``     n/8 bytes — packed predicate bitmask (``jnp.packbits``,
               bit order compatible with ``np.unpackbits`` on host).
  ``index``    4·K bytes — on-device compaction of selected row indices
               (``nonzero`` = popcount prefix-sum + scatter under XLA),
               K = pow2 bucket ≥ k so compile classes stay logarithmic.
  ``compact``  K·Σwidth bytes — low-width projected columns gathered ON
               DEVICE at the selected indices, so the host gather is
               skipped entirely (single-device; small k only).
  ``host``     0 — the host pipeline serves; correct at ~99% selectivity
               where every device route's D2H + gather meets or exceeds
               the plain host scan.

The mask and index routes are SHARD-CONCATENABLE and run on sharded
meshes as-is: each shard packs/compacts its local rows in feed order,
the count psums on ICI, and the host sees the same byte layout
concatenated (index entries carry global row offsets via the shard
index).  Only ``compact`` stays single-device — its gathered output is
committed to one chip by construction — and placement-routed requests
(device/placement.py) land on a single-device slice where every route
applies.

Unlike the aggregation kernels there is no Mosaic/Pallas body here by
measurement, not omission: the selection pass is purely elementwise
(predicate eval) plus a segmented popcount/prefix-sum — XLA fuses it
into ONE HBM pass already (no dot_general operand materialization, no
per-step scan cost), so a hand-written kernel has no fusion boundary to
remove.  The routes above attack the actual binding constraint, the
D2H transfer.

Predicate constants are HOISTED into traced scalar parameters
(``split_params``): the kernel cache key (``shape_key``) is const-blind,
so repeated selections at differing thresholds/selectivities share ONE
compile class per (plan shape, feed shape) — the reference's plan-cache
discipline applied to the device JIT cache.  ``split_params`` is also
the hoisting discipline of the device JOIN's fused probe pass
(device/join.py): a join fragment's probe-side selection predicates
evaluate inside the probe dispatch with their constants hoisted the
same way, so rotating thresholds never mint new probe-kernel compile
classes either.
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..datatype import device_const_dtype
from ..expr.eval import eval_rpn
from ..expr.rpn import RpnColumnRef, RpnConst, RpnExpression
from ..parallel import ROW_AXES, num_shards
from .kernels import named_program

ROUTE_MASK = "mask"
ROUTE_INDEX = "index"
ROUTE_COMPACT = "compact"
ROUTE_HOST = "host"

# Selectivity above which the endpoint router sends selections back to
# the host pipeline: past it the shared cost (materializing ~n output
# rows) dominates both paths, and the device adds its dispatch + D2H
# round trip for no saved work.  Observed-EWMA-gated (runner._sel_stats)
# with periodic re-probes so a workload whose selectivity drifts back
# down is re-discovered.
HOST_SELECTIVITY_CUTOFF = 0.95

# Largest k the compact route will materialize on device (values +
# validity per projected column, K·Σwidth bytes of D2H).  Above it the
# index route's 4·K bytes win and the host gather is cheap anyway.
COMPACT_MAX_ROWS = 1 << 14


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def split_params(sel_rpns, n_cols: int):
    """Hoist numeric predicate constants into traced parameters.

    Returns ``(param_rpns, values, dtypes)`` where every int/float
    RpnConst in ``sel_rpns`` (but a ``fixed`` one, which is the plan's
    structure) is replaced by an RpnColumnRef addressing a
    scalar parameter column at position ``n_cols + i``.  The parameter
    pairs the runner feeds (0-d value array, 0-d True validity) are
    exactly what ``eval._const_pair`` would have produced for the baked
    constant, so traces are value-identical — only the jit cache key
    stops depending on the constant's VALUE.
    """
    vals: list = []
    dts: list = []
    out = []
    for rpn in sel_rpns:
        nodes = []
        for nd in rpn.nodes:
            if isinstance(nd, RpnConst) and nd.value is not None and \
                    isinstance(nd.value, (int, float)) and not nd.fixed:
                dt = device_const_dtype(nd.value)
                nodes.append(RpnColumnRef(n_cols + len(vals), nd.eval_type))
                vals.append(nd.value)
                dts.append(dt)
            else:
                nodes.append(nd)
        out.append(RpnExpression(tuple(nodes)))
    return out, tuple(vals), tuple(dts)


def shape_key(plan) -> tuple:
    """Const-blind identity of a scan_sel plan's predicate structure.

    Two plans differing only in numeric constant VALUES (same device
    dtype) map to the same key and share one compiled kernel; a constant
    crossing the int32/int64 boundary is a genuinely new trace.
    """
    def nk(nd):
        if isinstance(nd, RpnConst):
            if nd.value is None:
                return ("cN", nd.eval_type.value)
            if nd.fixed:
                return ("cF", nd.value)     # structure: never hoisted
            if isinstance(nd.value, (int, float)):
                return ("c", device_const_dtype(nd.value))
            return ("c", repr(nd.value))    # non-numeric: host-only plans
        if isinstance(nd, RpnColumnRef):
            return ("col", nd.col_idx, nd.eval_type.value)
        return ("f", nd.meta.name, nd.n_args, nd.ctx)

    return (type(plan.scan).__name__, bool(getattr(plan.scan, "desc", False)),
            tuple(tuple(nk(nd) for nd in r.nodes) for r in plan.sel_rpns))


def index_bytes(k: float, n_shards: int = 1) -> int:
    """Real D2H bytes of the index route for an expected k: the
    per-shard pow2 capacity bucket (with the runner's 1.5× headroom)
    times the shard count — NOT 4·k.  The pow2 rounding and the
    per-shard replication can inflate the transfer several-fold near
    the crossover, so the router must compare against THIS figure."""
    cap = _next_pow2(max(64, int(math.ceil(k * 1.5)) + 64))
    return 4 * cap * n_shards


def choose_route(n: int, k: float, compact_ok: bool,
                 idx_bytes: Optional[int] = None) -> str:
    """Pick the cheapest device route for ~k selected of n scanned rows.

    Pure D2H-bytes comparison (the shared host gather of k rows cancels
    between mask and index): index wins only when its REAL transfer —
    capacity buckets × shards (``idx_bytes``; the caller passes the
    exact figure, default approximates a single shard) — undercuts the
    n/8-byte mask; compact additionally skips the host gather but
    bounds its on-device materialization at COMPACT_MAX_ROWS.
    """
    if compact_ok and k <= COMPACT_MAX_ROWS:
        return ROUTE_COMPACT
    if idx_bytes is None:
        idx_bytes = index_bytes(k)
    if idx_bytes < n / 8:
        return ROUTE_INDEX
    return ROUTE_MASK


def modeled_d2h_bytes(route: str, n: int, k: int, row_bytes: int = 12,
                      n_shards: int = 1) -> int:
    """Bytes the chosen route moves over D2H (the router's cost model;
    also the bench sweep's reported figure).  ``row_bytes``: per-row
    width of the compact route's projected columns."""
    if route == ROUTE_MASK:
        return -(-n // 8)
    if route == ROUTE_INDEX:
        return index_bytes(k, n_shards)
    if route == ROUTE_COMPACT:
        return row_bytes * _next_pow2(max(64, k))
    return 0


def host_path_bytes(n: int, k: int, pred_bytes: int = 8,
                    row_bytes: int = 24) -> int:
    """Bytes the host pipeline touches for the same request: one pass
    over the predicate columns plus the k-row output gather.  Routes
    whose modeled D2H exceeds this must not be picked (the gather term
    is shared, so comparing totals is conservative for the device)."""
    return n * pred_bytes + k * row_bytes


def _shard_index(mesh):
    tile = mesh.shape[ROW_AXES[1]]
    return (lax.axis_index(ROW_AXES[0]) * tile
            + lax.axis_index(ROW_AXES[1])).astype(jnp.int64)


def _feed_pairs(flat, null_flags, row_mask):
    pairs = []
    fi = 0
    for has_nulls in null_flags:
        v = flat[fi]
        fi += 1
        if has_nulls:
            m = flat[fi]
            fi += 1
        else:
            m = row_mask
        pairs.append((v, m))
    return pairs


def build_mask_kernel(sel_rpns, null_flags, n_pad: int, n_flat: int,
                      n_params: int, mesh=None):
    """Fused predicate-eval pass → ``(count, packed bitmask, bool mask)``.

    One jit dispatch over the whole resident feed: the selection vector
    (bool mask) stays ON DEVICE for a follow-up compaction kernel, the
    packed bitmask (n/8 bytes) is the mask route's D2H payload, and the
    scalar count seeds the router.  ``sel_rpns`` must already be
    parameterized (split_params); the ``n_params`` scalar args follow
    ``n`` and precede the feed columns.  Sharded meshes psum the count
    and emit per-shard mask/packed slices in feed row order.
    """
    S = 1 if mesh is None else num_shards(mesh)
    n_local = n_pad // S
    assert n_local % 8 == 0, n_local
    idt = jnp.int32 if n_pad <= np.iinfo(np.int32).max else jnp.int64

    def local_fn(n_scalar, *args):
        params = args[:n_params]
        flat = args[n_params:]
        base0 = idt(0) if mesh is None else \
            (_shard_index(mesh) * n_local).astype(idt)
        iota = jnp.arange(n_local, dtype=idt)
        row_mask = (base0 + iota) < n_scalar.astype(idt)
        pairs = _feed_pairs(flat, null_flags, row_mask)
        one = jnp.ones((), jnp.bool_)
        for p in params:
            pairs.append((p, one))
        mask = row_mask
        for rpn in sel_rpns:
            v, ok = eval_rpn(rpn, pairs, n_local, jnp)
            mask = mask & ok & (v != 0)
        mask = jnp.broadcast_to(mask, (n_local,))
        count = jnp.sum(mask, dtype=jnp.int64)
        if mesh is not None:
            count = lax.psum(count, ROW_AXES)
        return count, jnp.packbits(mask), mask

    local_fn = named_program(local_fn, "scan_sel_mask")
    if mesh is None:
        return jax.jit(local_fn)
    return jax.jit(jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(),) * (1 + n_params) + (P(ROW_AXES),) * n_flat,
        out_specs=(P(), P(ROW_AXES), P(ROW_AXES))))


def build_batched_mask_kernel(sel_rpns, null_flags, n_pad: int,
                              n_flat: int, n_params: int, group: int):
    """Cross-request STACKED predicate pass: ``group`` requests sharing
    one compile class (same ``shape_key``, same feed) evaluate in ONE
    dispatch → ``(counts (G,), packed bitmasks (G, n_pad/8))``.

    The hoisted scalar parameters arrive with a leading group axis —
    shape ``(G,)`` per parameter — and ``jax.vmap`` maps the solo
    kernel's trace over it while the feed columns stay broadcast
    (in_axes=None): the per-request fixed cost (launch + D2H sync) is
    paid once for the whole group, which is the TPU-economics point
    (Jouppi: amortize the launch/transfer overhead across a batch).
    The feed is read once per lane by construction of the elementwise
    pass; XLA keeps the lanes in one fused HBM traversal for the common
    single-predicate shapes.  ``group`` is a pow2 bucket so compile
    classes stay logarithmic in occupancy; dead lanes (group padding)
    repeat a live lane's parameters and their outputs are discarded.

    The stacked kernel itself is single-device, but sharded meshes are
    no longer excluded from coalescing: a placement-routed request
    (device/placement.py) stacks on its anchor's single-device slice.
    Only whole-mesh sharded dispatches — whose per-shard launches GSPMD
    already amortizes — stay solo.
    """
    assert n_params >= 1, "stacked dispatch needs hoisted parameters"
    idt = jnp.int32 if n_pad <= np.iinfo(np.int32).max else jnp.int64

    def local_fn(n_scalar, *args):
        params = args[:n_params]            # each (group,)
        flat = args[n_params:]
        iota = jnp.arange(n_pad, dtype=idt)
        row_mask = iota < n_scalar.astype(idt)

        def one(*ps):
            pairs = _feed_pairs(flat, null_flags, row_mask)
            one_b = jnp.ones((), jnp.bool_)
            for p in ps:
                pairs.append((p, one_b))
            mask = row_mask
            for rpn in sel_rpns:
                v, ok = eval_rpn(rpn, pairs, n_pad, jnp)
                mask = mask & ok & (v != 0)
            mask = jnp.broadcast_to(mask, (n_pad,))
            return jnp.sum(mask, dtype=jnp.int64), jnp.packbits(mask)

        return jax.vmap(one)(*params)

    return jax.jit(named_program(local_fn, "scan_sel_batched"))


def build_index_kernel(n_pad: int, k_cap: int, mesh=None):
    """On-device compaction of selected row indices.

    ``nonzero(size=k_cap)`` lowers to the popcount prefix-sum + scatter
    pattern; indices come back ascending per shard with ``-1`` fill, so
    the host filter ``idx >= 0`` restores the exact scan order.  The
    overflow flag (any shard held more than ``k_cap`` selected rows)
    routes the caller back to the on-device packed mask — never a
    truncated result.  Keyed only on (n_pad, k_cap): every selection
    plan shares these kernels.
    """
    S = 1 if mesh is None else num_shards(mesh)
    n_local = n_pad // S
    idt = jnp.int32 if n_pad <= np.iinfo(np.int32).max else jnp.int64

    def local_fn(mask):
        cnt = jnp.sum(mask, dtype=jnp.int64)
        idx = jnp.nonzero(mask, size=k_cap, fill_value=-1)[0].astype(idt)
        base0 = idt(0) if mesh is None else \
            (_shard_index(mesh) * n_local).astype(idt)
        gidx = jnp.where(idx >= 0, idx + base0, idt(-1))
        ovf = (cnt > k_cap).astype(jnp.int64)
        if mesh is not None:
            ovf = lax.psum(ovf, ROW_AXES)
        return gidx, ovf

    local_fn = named_program(local_fn, "scan_sel_index")
    if mesh is None:
        return jax.jit(local_fn)
    return jax.jit(jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(ROW_AXES),),
        out_specs=(P(ROW_AXES), P())))


def build_compact_kernel(n_pad: int, k_cap: int, null_flags):
    """Single-device column compaction: gather every projected feed
    plane at the selected indices so the host gather is skipped — D2H
    is ``k_cap`` rows of narrow device-dtype columns, nothing else.
    Slots past the true count hold garbage (index 0 gather); the caller
    slices ``[:k]`` with the count that rides along."""
    def fn(mask, *flat):
        idx = jnp.nonzero(mask, size=k_cap, fill_value=-1)[0]
        safe = jnp.where(idx >= 0, idx, 0)
        outs = []
        fi = 0
        for has_nulls in null_flags:
            outs.append(jnp.take(flat[fi], safe))
            fi += 1
            if has_nulls:
                outs.append(jnp.take(flat[fi], safe))
                fi += 1
        ovf = (jnp.sum(mask, dtype=jnp.int64) > k_cap).astype(jnp.int64)
        return tuple(outs), ovf

    return jax.jit(named_program(fn, "scan_sel_compact"))


def index_capacity(k_hint: float, n_local: int) -> int:
    """Pow2 index/compact capacity bucket for an expected k.  Predicted
    hints get ~1.5× headroom (an undersized capacity costs an overflow
    fallback to the mask route, never a wrong answer); capacities are
    clamped to the per-shard row count."""
    need = max(64, int(math.ceil(k_hint)))
    return min(_next_pow2(need), max(64, _next_pow2(n_local)))
