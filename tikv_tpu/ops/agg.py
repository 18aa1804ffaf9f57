"""Aggregate kernels with psum-mergeable partial states.

Reference: components/tidb_query_aggr (impl_count.rs, impl_sum.rs,
impl_avg.rs, impl_max_min.rs, impl_first.rs) and the hash-agg executors
(tidb_query_executors/src/fast_hash_aggr_executor.rs,
simple_aggr_executor.rs). The reference updates per-group state structs row
by row; here a *tile* of rows is reduced at once with masked array ops, and
the state is a pytree of dense arrays so that cross-chip merging is exactly
``psum`` / ``pmax`` / ``pmin`` (SURVEY.md §5.7: partial states are
psum-mergeable by construction).

State shapes (G = group capacity; G=1 for simple agg):
- COUNT  → {"count": i64[G]}
- SUM    → {"sum": v[G], "nonnull": i64[G]}     (SUM of all-NULL is NULL)
- AVG    → {"sum": v[G], "count": i64[G]}
- MIN    → {"min": v[G] (identity-filled), "nonnull": i64[G]}
- MAX    → symmetric
- FIRST  → {"value": v[G], "pos": i64[G] (global row pos, identity MAX)}
- VAR_*  → {"sum": f64[G], "sumsq": f64[G], "count": i64[G]}
  (reference impl_variance.rs keeps the same (count, sum, square_sum)
  moment triple precisely because it merges by addition — psum-ready)
- BIT_*  → {"bits": i64[G]} (u64 bit pattern; AND identity ~0, OR/XOR 0;
  reference impl_bit_op.rs — result is never NULL)

Hash-agg fast path: when the int key range fits the capacity, the group id
is ``key - base`` (direct indexing — the reference's FastHashAgg plays the
same trick with its int-key specialised hashmap). NULL keys get their own
trailing slot (MySQL GROUP BY treats NULL as one group). Keys outside the
range raise the ``overflow`` flag and the executor routes the batch to the
host general path (dictionary-encode via np.unique).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from ..datatype import EvalType


@dataclass(frozen=True)
class AggSpec:
    """One aggregate function instance in a plan.

    ``kind``: count | sum | avg | min | max | first | count_star |
    var_pop | var_samp | stddev_pop | stddev_samp |
    bit_and | bit_or | bit_xor
    ``arg``: index of the source column pair in the kernel inputs (ignored
    for count_star).
    """

    kind: str
    arg: int = 0
    eval_type: EvalType = EvalType.INT


VAR_KINDS = ("var_pop", "var_samp", "stddev_pop", "stddev_samp")
BIT_KINDS = ("bit_and", "bit_or", "bit_xor")

# MySQL BIT_AND() of zero rows is ~0 (u64 max); OR/XOR start at 0.
_BIT_IDENT = {"bit_and": -1, "bit_or": 0, "bit_xor": 0}


def _bit_ufunc(kind: str):
    return {"bit_and": np.bitwise_and, "bit_or": np.bitwise_or,
            "bit_xor": np.bitwise_xor}[kind]


_U64 = 0xFFFFFFFFFFFFFFFF
_I64_MAX = (1 << 63) - 1


def _bit_int64(values):
    """BIT_* operand coercion: MySQL rounds REAL args to the nearest
    integer — half away from zero, so 0.5→1 and -0.5→-1 — before the bit
    op (impl_bit_op.rs casts through u64).  np.rint alone rounds ties to
    even (0.5→0); naive trunc(v+0.5) double-rounds values just below a
    tie (0.5-2^-54 + 0.5 == 1.0 in f64).  So: rint everywhere, and only
    exact .5 fractions are overridden away from zero."""
    if values.dtype.kind == "f":
        r = np.rint(values)
        frac = values - np.trunc(values)
        ties = np.abs(frac) == 0.5
        r = np.where(ties, np.trunc(values) + np.copysign(1.0, values), r)
        return r.astype(np.int64)
    return values.astype(np.int64)


def var_arrays(kind: str, s, sq, c):
    """Vectorized variance finalize over per-group moment arrays.

    → (values f64[G], validity bool[G]); MySQL NULLability: *_pop NULL
    when count=0, *_samp NULL when count<2.
    """
    s = np.asarray(s, np.float64)
    sq = np.asarray(sq, np.float64)
    c = np.asarray(c, np.float64)
    samp = kind in ("var_samp", "stddev_samp")
    validity = c >= (2 if samp else 1)
    cd = np.where(validity, c, 1.0)
    denom = cd - 1 if samp else cd
    var = np.maximum(0.0, (sq - s * s / cd) / np.where(validity, denom, 1.0))
    if kind.startswith("stddev"):
        var = np.sqrt(var)
    return np.where(validity, var, 0.0), validity


def _scatter_add(xp, target, idx, vals):
    if xp is np:
        np.add.at(target, idx, vals)
        return target
    return target.at[idx].add(vals)


def _scatter_max(xp, target, idx, vals):
    if xp is np:
        np.maximum.at(target, idx, vals)
        return target
    return target.at[idx].max(vals)


def _scatter_min(xp, target, idx, vals):
    if xp is np:
        np.minimum.at(target, idx, vals)
        return target
    return target.at[idx].min(vals)


def _acc_dtype(xp, values) -> str:
    """Accumulator dtype: int sums widen to int64; real stays float."""
    if values.dtype.kind in "iu":
        return "int64"
    return str(values.dtype)


def _minmax_identity(xp, dtype, is_min: bool):
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return dt.type(np.inf) if is_min else dt.type(-np.inf)
    info = np.iinfo(dt)
    # uint64's max would WRAP to -1 in the int64 state carries
    # (_canon_state); values are guarded < 2^63 (device feed guard), so
    # int64 max is a valid MIN identity for unsigned columns
    hi = min(info.max, np.iinfo(np.int64).max)
    return dt.type(hi) if is_min else dt.type(info.min)


# ---------------------------------------------------------------------------
# Simple (single-group) aggregation — reference: simple_aggr_executor.rs
# ---------------------------------------------------------------------------

def simple_agg_tile(xp, specs: Sequence[AggSpec], cols: Sequence[tuple],
                    n_valid_rows=None) -> list[dict]:
    """Reduce one tile to per-spec scalar partial states.

    ``cols[i]`` is the (values, validity) pair for specs referencing arg i.
    ``n_valid_rows``: logical row count (for count_star with padding, the
    validity mask of col 0 is NOT usable — padding rows must not count), so
    callers pass the tile's row-validity mask as a column or the scalar count.
    """
    states = []
    for spec in specs:
        if spec.kind == "count_star":
            assert n_valid_rows is not None
            states.append({"count": xp.asarray(n_valid_rows, dtype="int64")})
            continue
        values, validity = cols[spec.arg]
        vmask = validity
        nonnull = xp.sum(vmask, dtype="int64")
        if spec.kind == "count":
            states.append({"count": nonnull})
        elif spec.kind == "sum":
            acc = _acc_dtype(xp, values)
            s = xp.sum(xp.where(vmask, values, xp.zeros_like(values)),
                       dtype=acc)
            states.append({"sum": s, "nonnull": nonnull})
        elif spec.kind == "avg":
            acc = _acc_dtype(xp, values)
            s = xp.sum(xp.where(vmask, values, xp.zeros_like(values)),
                       dtype=acc)
            states.append({"sum": s, "count": nonnull})
        elif spec.kind in ("min", "max"):
            ident = _minmax_identity(xp, values.dtype, spec.kind == "min")
            filled = xp.where(vmask, values, xp.full_like(values, ident))
            v = xp.min(filled) if spec.kind == "min" else xp.max(filled)
            states.append({spec.kind: v, "nonnull": nonnull})
        elif spec.kind == "first":
            # position-ordered: tracked on host merge (deterministic across
            # tiles); device partial = value at first valid index in tile.
            n = values.shape[0]
            idxs = xp.arange(n, dtype="int64")
            big = xp.asarray(np.iinfo(np.int64).max, dtype="int64")
            pos = xp.min(xp.where(vmask, idxs, big))
            safe = xp.minimum(pos, n - 1)
            states.append({"value": values[safe], "pos": pos})
        elif spec.kind in VAR_KINDS:
            v64 = values.astype("float64")
            zero = xp.zeros_like(v64)
            s = xp.sum(xp.where(vmask, v64, zero))
            sq = xp.sum(xp.where(vmask, v64 * v64, zero))
            states.append({"sum": s, "sumsq": sq, "count": nonnull})
        elif spec.kind in BIT_KINDS:
            if xp is not np:
                raise ValueError(f"{spec.kind} has no device tile kernel")
            ident = np.int64(_BIT_IDENT[spec.kind])
            filled = np.where(vmask, _bit_int64(values), ident)
            states.append({"bits": _bit_ufunc(spec.kind).reduce(
                filled, initial=ident, dtype=np.int64)})
        else:
            raise ValueError(f"unknown agg kind {spec.kind}")
    return states


def merge_simple_states(xp, specs, a: list[dict], b: list[dict],
                        b_pos_offset=0) -> list[dict]:
    out = []
    for spec, sa, sb in zip(specs, a, b):
        if spec.kind in ("count", "count_star"):
            out.append({"count": sa["count"] + sb["count"]})
        elif spec.kind == "sum":
            out.append({"sum": sa["sum"] + sb["sum"],
                        "nonnull": sa["nonnull"] + sb["nonnull"]})
        elif spec.kind == "avg":
            out.append({"sum": sa["sum"] + sb["sum"],
                        "count": sa["count"] + sb["count"]})
        elif spec.kind == "min":
            out.append({"min": xp.minimum(sa["min"], sb["min"]),
                        "nonnull": sa["nonnull"] + sb["nonnull"]})
        elif spec.kind == "max":
            out.append({"max": xp.maximum(sa["max"], sb["max"]),
                        "nonnull": sa["nonnull"] + sb["nonnull"]})
        elif spec.kind == "first":
            big = np.iinfo(np.int64).max
            # "no valid row" sentinel (int64 max) must not be shifted — it
            # would wrap negative and beat real positions.
            bpos = xp.where(sb["pos"] == big, sb["pos"],
                            sb["pos"] + b_pos_offset)
            take_b = bpos < sa["pos"]
            out.append({"value": xp.where(take_b, sb["value"], sa["value"]),
                        "pos": xp.where(take_b, bpos, sa["pos"])})
        elif spec.kind in VAR_KINDS:
            out.append({"sum": sa["sum"] + sb["sum"],
                        "sumsq": sa["sumsq"] + sb["sumsq"],
                        "count": sa["count"] + sb["count"]})
        elif spec.kind in BIT_KINDS:
            out.append({"bits": _bit_ufunc(spec.kind)(sa["bits"],
                                                      sb["bits"])})
        else:
            raise ValueError(spec.kind)
    return out


def finalize_simple(specs, states: list[dict]) -> list:
    """Produce final scalar results (Python values; None = NULL)."""
    out = []
    for spec, s in zip(specs, states):
        if spec.kind in ("count", "count_star"):
            out.append(int(s["count"]))
        elif spec.kind == "sum":
            out.append(None if int(s["nonnull"]) == 0 else _item(s["sum"]))
        elif spec.kind == "avg":
            c = int(s["count"])
            out.append(None if c == 0 else float(s["sum"]) / c)
        elif spec.kind in ("min", "max"):
            out.append(None if int(s["nonnull"]) == 0 else _item(s[spec.kind]))
        elif spec.kind == "first":
            out.append(None if int(s["pos"]) == np.iinfo(np.int64).max
                       else _item(s["value"]))
        elif spec.kind in VAR_KINDS:
            out.append(_finalize_var(spec.kind, float(s["sum"]),
                                     float(s["sumsq"]), int(s["count"])))
        elif spec.kind in BIT_KINDS:
            out.append(int(s["bits"]) & _U64)
    return out


def _finalize_var(kind: str, s: float, sq: float, c: int):
    """(sum, sumsq, count) → variance/stddev; MySQL NULLability:
    *_pop NULL when count=0, *_samp NULL when count<2."""
    if kind in ("var_samp", "stddev_samp"):
        if c < 2:
            return None
        var = max(0.0, (sq - s * s / c) / (c - 1))
    else:
        if c == 0:
            return None
        var = max(0.0, sq / c - (s / c) ** 2)
    if kind.startswith("stddev"):
        return float(np.sqrt(var))
    return var


def _item(x):
    v = np.asarray(x).item()
    return v


# ---------------------------------------------------------------------------
# Hash (group-by) aggregation — reference: fast_hash_aggr_executor.rs
# ---------------------------------------------------------------------------

def hash_agg_tile(xp, specs: Sequence[AggSpec], key: tuple,
                  cols: Sequence[tuple], capacity: int, base: int,
                  row_mask=None) -> dict:
    """Direct-index group-by over one tile.

    ``key``: (values, validity) int key pair. Group id = key - base for keys
    in [base, base+capacity); NULL keys map to slot ``capacity`` (their own
    group); out-of-range keys set ``overflow`` and land in a scrap slot that
    finalize ignores.

    Returns {"present": bool[C+2], "overflow": bool, "states": [per-spec
    dict of arrays shaped (C+2,)]}. Slot layout: [0..C) groups, C = NULL
    group, C+1 = scrap.
    """
    kv, km = key
    n = kv.shape[0]
    if row_mask is None:
        row_mask = xp.ones((n,), dtype=bool)
    slots = capacity + 2
    null_slot = capacity
    scrap = capacity + 1

    if isinstance(base, tuple):
        # sparse recode: base = ("precomp", idx) — the slot per row was
        # already computed (rank among distinct keys, NULLs at the NULL
        # slot); only the request's row/selection mask is applied here
        # (device/aggregate.py run_hash sparse path)
        idx = xp.where(row_mask, base[1].astype("int32"), scrap)
        overflow = xp.zeros((), dtype=bool) if xp is not np else False
    else:
        shifted = kv.astype("int64") - base
        in_range = (shifted >= 0) & (shifted < capacity)
        idx = xp.where(km & in_range, shifted, 0).astype("int32")
        idx = xp.where(km, xp.where(in_range, idx, scrap), null_slot)
        idx = xp.where(row_mask, idx, scrap)
        overflow = xp.any(row_mask & km & ~in_range)
    present = xp.zeros((slots,), dtype=bool)
    present = _scatter_max(xp, present, idx, row_mask)

    states = []
    for spec in specs:
        if spec.kind == "count_star":
            c = _scatter_add(xp, xp.zeros((slots,), dtype="int64"), idx,
                             row_mask.astype("int64"))
            states.append({"count": c})
            continue
        values, validity = cols[spec.arg]
        ok = row_mask & validity
        oki = ok.astype("int64")
        if spec.kind == "count":
            c = _scatter_add(xp, xp.zeros((slots,), dtype="int64"), idx, oki)
            states.append({"count": c})
        elif spec.kind in ("sum", "avg"):
            acc = _acc_dtype(xp, values)
            masked = xp.where(ok, values, xp.zeros_like(values)).astype(acc)
            s = _scatter_add(xp, xp.zeros((slots,), dtype=acc), idx, masked)
            c = _scatter_add(xp, xp.zeros((slots,), dtype="int64"), idx, oki)
            states.append({"sum": s, "nonnull": c} if spec.kind == "sum"
                          else {"sum": s, "count": c})
        elif spec.kind in ("min", "max"):
            ident = _minmax_identity(xp, values.dtype, spec.kind == "min")
            filled = xp.where(ok, values, xp.full_like(values, ident))
            t = xp.full((slots,), ident, dtype=values.dtype)
            t = (_scatter_min if spec.kind == "min" else _scatter_max)(
                xp, t, idx, filled)
            c = _scatter_add(xp, xp.zeros((slots,), dtype="int64"), idx, oki)
            states.append({spec.kind: t, "nonnull": c})
        elif spec.kind == "first":
            big = np.iinfo(np.int64).max
            rowpos = xp.arange(n, dtype="int64")
            p = xp.full((slots,), big, dtype="int64")
            p = _scatter_min(xp, p, idx, xp.where(ok, rowpos, big))
            # value lookup happens at finalize on host (gather by pos)
            states.append({"pos": p})
        elif spec.kind in VAR_KINDS:
            v64 = values.astype("float64")
            zero = xp.zeros_like(v64)
            s = _scatter_add(xp, xp.zeros((slots,), dtype="float64"), idx,
                             xp.where(ok, v64, zero))
            sq = _scatter_add(xp, xp.zeros((slots,), dtype="float64"), idx,
                              xp.where(ok, v64 * v64, zero))
            c = _scatter_add(xp, xp.zeros((slots,), dtype="int64"), idx, oki)
            states.append({"sum": s, "sumsq": sq, "count": c})
        elif spec.kind in BIT_KINDS:
            if xp is not np:
                raise ValueError(f"{spec.kind} has no device tile kernel")
            ident = np.int64(_BIT_IDENT[spec.kind])
            t = np.full((slots,), ident, dtype=np.int64)
            _bit_ufunc(spec.kind).at(
                t, idx, np.where(ok, _bit_int64(values), ident))
            states.append({"bits": t})
        else:
            raise ValueError(spec.kind)
    return {"present": present, "overflow": overflow, "states": states}


def merge_hash_states(xp, specs, a: dict, b: dict) -> dict:
    out_states = []
    for spec, sa, sb in zip(specs, a["states"], b["states"]):
        if spec.kind in ("count", "count_star"):
            out_states.append({"count": sa["count"] + sb["count"]})
        elif spec.kind == "sum":
            out_states.append({"sum": sa["sum"] + sb["sum"],
                               "nonnull": sa["nonnull"] + sb["nonnull"]})
        elif spec.kind == "avg":
            out_states.append({"sum": sa["sum"] + sb["sum"],
                               "count": sa["count"] + sb["count"]})
        elif spec.kind == "min":
            out_states.append({"min": xp.minimum(sa["min"], sb["min"]),
                               "nonnull": sa["nonnull"] + sb["nonnull"]})
        elif spec.kind == "max":
            out_states.append({"max": xp.maximum(sa["max"], sb["max"]),
                               "nonnull": sa["nonnull"] + sb["nonnull"]})
        elif spec.kind == "first":
            out_states.append({"pos": xp.minimum(sa["pos"], sb["pos"])})
        elif spec.kind in VAR_KINDS:
            out_states.append({"sum": sa["sum"] + sb["sum"],
                               "sumsq": sa["sumsq"] + sb["sumsq"],
                               "count": sa["count"] + sb["count"]})
        elif spec.kind in BIT_KINDS:
            out_states.append({"bits": _bit_ufunc(spec.kind)(sa["bits"],
                                                             sb["bits"])})
        else:
            raise ValueError(spec.kind)
    return {
        "present": a["present"] | b["present"],
        "overflow": a["overflow"] | b["overflow"],
        "states": out_states,
    }


def finalize_hash(specs, state: dict, base: int, capacity: int,
                  slot_keys=None):
    """Present groups of a hash-agg state as numpy planes.

    Groups are emitted in ascending key order (deterministic), NULL group
    last — matches what the reference's tests canonicalize to.
    ``slot_keys``: sparse recode — per-slot key values (sorted distinct
    keys) instead of the dense ``slot + base`` arithmetic.
    Returns ``((keys, key_valid), planes)``: the int64 key plane with its
    validity (False on the NULL group), and one ``(values, validity)``
    pair per spec, in the state's own dtype (BIT kinds: uint64, AVG and
    the VAR kinds: float64).  A NULL result holds 0 under a False
    validity, the ``Column`` contract, so the caller wraps the planes
    without walking them.  No per-group Python runs here except
    ``_finalize_var``, whose float operation order is the host's.

    Two callers in device/aggregate.py: the XLA hash bodies (two-level,
    scatter) finalize their states here, and ``finalize_packed`` does
    for a Pallas accumulator the native call cannot serve, or in a
    process without the extension.  That native call
    (native/fastbuild.cpp ``hash_finalize_packed``) gives this
    function's planes for COUNT / SUM / AVG over an int64 key domain:
    change the contract in both, tests/test_finalize_native.py holds
    them equal.
    """
    present = np.asarray(state["present"])
    sel = np.flatnonzero(present[:capacity])
    if slot_keys is not None:
        keys = np.asarray(slot_keys)[sel]
    elif base + capacity > _I64_MAX:
        keys = sel.astype(np.uint64) + np.uint64(base)
    else:
        keys = sel + np.int64(base)
    if not (keys.dtype == np.uint64 and (keys > _I64_MAX).any()):
        # an unsigned key domain keeps uint64 only where a key needs it
        # (``Column.from_list``'s container rule)
        keys = keys.astype(np.int64, copy=False)
    key_valid = np.ones(len(sel), dtype=np.bool_)
    if present[capacity]:
        sel = np.append(sel, capacity)
        keys = np.append(keys, keys.dtype.type(0))
        key_valid = np.append(key_valid, False)

    def all_valid():
        return np.ones(len(sel), dtype=np.bool_)

    def nullable(values, counts):
        valid = counts > 0
        return np.where(valid, values, 0), valid

    planes = []
    for spec, s in zip(specs, state["states"]):
        if spec.kind in ("count", "count_star"):
            planes.append((np.asarray(s["count"])[sel], all_valid()))
        elif spec.kind == "sum":
            planes.append(nullable(np.asarray(s["sum"])[sel],
                                   np.asarray(s["nonnull"])[sel]))
        elif spec.kind == "avg":
            cnt = np.asarray(s["count"])[sel]
            valid = cnt > 0
            planes.append((np.divide(
                np.asarray(s["sum"])[sel].astype(np.float64),
                cnt.astype(np.float64),
                out=np.zeros(len(sel), dtype=np.float64), where=valid),
                valid))
        elif spec.kind in ("min", "max"):
            planes.append(nullable(np.asarray(s[spec.kind])[sel],
                                   np.asarray(s["nonnull"])[sel]))
        elif spec.kind in VAR_KINDS:
            sums = np.asarray(s["sum"])[sel]
            sqs = np.asarray(s["sumsq"])[sel]
            cnt = np.asarray(s["count"])[sel]
            out = [_finalize_var(spec.kind, float(sums[i]),
                                 float(sqs[i]), int(c))
                   for i, c in enumerate(cnt)]
            planes.append((
                np.array([0.0 if v is None else v for v in out],
                         dtype=np.float64),
                np.array([v is not None for v in out], dtype=np.bool_)))
        elif spec.kind in BIT_KINDS:
            # two's complement reinterpretation == ``& 2**64 - 1``
            planes.append((np.asarray(s["bits"])[sel].astype(np.uint64),
                           all_valid()))
        else:
            raise ValueError(f"finalize_hash: {spec.kind} unsupported here")
    return (keys, key_valid), planes
