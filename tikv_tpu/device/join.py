"""Device hash join, sort and window fragments — the plan-IR kernels.

The operator boundary the reference never crosses (copr/plan_ir.py):
these kernels serve the three fragment kinds the tipb vocabulary
omits, over the same single-device substrate the selection/topn
kernels use (padded HBM-resident planes, pow-bucketed compile
classes, hoisted constants).

JOIN — an inner equi-join between two co-located region feeds:

- The BUILD side rides the dictionary discipline of the PR 2 sparse-
  slot kernels: the key column uploads once per (anchor, data version)
  and ONE build dispatch (``join_build``) sorts it into a device-
  resident dictionary — ``(sorted keys, permutation, valid-prefix
  sums)`` — with NULL/padded rows sentineled to ``int64.max`` and
  ordered valid-first within equal keys, so duplicate and
  sentinel-colliding keys resolve EXACTLY (the valid-prefix sum bounds
  each probe run to its valid entries).  The structure is cached in
  HBM across requests and dies with the anchor (``drop_anchor`` rides
  the runner's ``drop_feed`` teardown path).

- The PROBE side fuses the probe fragment's selection predicates into
  the probe dispatch (``join_probe``): predicate RPNs evaluate over
  the uploaded probe planes with constants hoisted into traced scalar
  parameters (device/selection.split_params — the same const-blind
  compile-class discipline), the surviving rows binary-search the
  build dictionary, and pair counts prefix-sum into a capacity-
  bucketed emission — ONE dispatch total.

- The output is LATE-MATERIALIZED (Abadi et al.): row-index PAIRS
  (int32), never joined rows.  D2H ships 8 bytes/pair; the host
  gathers only the columns the parent operator demands, from the
  columnar snapshots already resident host-side.  An undersized pair
  capacity is detected by the on-device total and re-dispatched at
  the EXACT pow2 bucket — never a truncated result — and the observed
  multiplicity feeds an EWMA that sizes the next request's bucket.

SORT — the permutation, not the rows: the transformed sort keys
(plan_ir.sort_key_i64/f64, shared with the host twin so results are
bit-identical) upload, one dispatch composes stable argsorts (padding
pushed strictly last by a leading pad key), and 4·n bytes of
permutation cross D2H; the host ``take``s the resident batch.

WINDOW — shifted segmented scans over the (partition, order)-sorted
view: segment ids from boundary flags, running count/sum as
``cumsum − segment-start offset``, row_number from the segment-start
index, lag/lead as segment-bounded shifted gathers.  REAL running
sums stay host (device cumsum is an associative scan whose float
rounding forks bit-parity; integer arithmetic is exact on both).

All three are SINGLE-DEVICE by construction (the join's build
dictionary and the sort's permutation are committed to one chip);
on a multi-chip node the plan executor runs them on the SlicePlacer
slice that co-locates both feeds (the co-location hint loop,
device/placement.py).  ``device::join_dispatch`` faults the probe
dispatch for failpoint-driven per-fragment host degrade.
"""

from __future__ import annotations

import functools
import threading
import weakref
from collections import OrderedDict
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..copr.plan_ir import WindowNode, eval_order_keys
from ..datatype import EvalType
from ..expr import build_rpn
from ..expr.eval import eval_rpn
from ..utils.failpoint import fail_point
from .feed import anchor as feed_anchor, generation
from .kernels import named_program
from .request import _remap_rpn, _rpn_col_indices, _rpn_device_safe

_I64 = np.iinfo(np.int64)

# build/probe cache bounds: entries are per-(anchor, version, columns)
# device planes; the LRU keeps reruns warm while churn stays bounded
_MAX_ENTRIES = 64
_DEFAULT_CACHE_BYTES = 1 << 28

_DEVICE_KEY_ETS = (EvalType.INT,)


def _on_runner_device(method):
    """Run a joiner entry point where its runner's chip is (a
    placement slice's uploads and launches must not land on the
    process default device — runner._device_scope)."""
    @functools.wraps(method)
    def wrapped(self, *args, **kwargs):
        with self._runner._device_scope():
            return method(self, *args, **kwargs)
    return wrapped


class JoinDeviceUnavailable(Exception):
    """The device join cannot serve this fragment (failpoint, shape
    outside the envelope at dispatch time) — the plan executor degrades
    the FRAGMENT to the host join, nothing else."""


from .selection import _next_pow2  # noqa: E402 — shared pow2 bucketing


def join_supported(probe_scan, probe_conds, left_key: int,
                   build_scan, right_key: int) -> bool:
    """Static device-join envelope: ascending table scans, signed-INT
    (or pk-handle) keys, device-safe probe predicates.  The plan
    executor checks this BEFORE recording co-location affinity, so
    join pairs that can never be device-served don't earn score-blind
    placement pins."""
    from ..copr.dag import TableScanDesc
    for scan, key in ((probe_scan, left_key), (build_scan, right_key)):
        if not isinstance(scan, TableScanDesc) or scan.desc:
            return False
        if key >= len(scan.columns):
            return False
        info = scan.columns[key]
        if not info.is_pk_handle and (
                info.field_type.eval_type not in _DEVICE_KEY_ETS or
                info.field_type.is_unsigned):
            return False
    scan_ets = [c.field_type.eval_type for c in probe_scan.columns]
    for cond in probe_conds:
        if not _rpn_device_safe(build_rpn(cond), scan_ets):
            return False
    return True


class DeviceJoiner:
    """Join/sort/window kernel owner for ONE single-device runner."""

    MULT_ALPHA = 0.3

    def __init__(self, runner, cache_bytes: int = _DEFAULT_CACHE_BYTES):
        self._runner = runner
        self._mu = threading.Lock()
        self._cache: OrderedDict = OrderedDict()
        self._cache_bytes = 0
        self._cache_budget = cache_bytes
        # id(anchor) → weakref: a dead anchor's entries are pruned at
        # finalization, so a NEW object reusing the id can never be
        # served another snapshot's build dictionary (entries are
        # keyed by id, not by the object — the arena's weak-keying
        # discipline applied here)
        self._anchor_refs: dict = {}
        self._kernels: dict = {}
        # observed pairs-per-probe-row EWMA keyed by (probe table,
        # build table): sizes the emission capacity bucket
        self._mult: dict = {}
        # counters (under _mu)
        self.device_joins = 0
        self.overflow_redispatches = 0
        self.build_cache_hits = 0
        self.build_cache_builds = 0
        self.sorts = 0
        self.windows = 0

    # ------------------------------------------------------------ cache

    def _cache_get(self, key):
        with self._mu:
            ent = self._cache.get(key)
            if ent is not None:
                self._cache.move_to_end(key)
            return ent

    def _cache_put(self, key, ent, anchor=None) -> None:
        with self._mu:
            old = self._cache.pop(key, None)
            if old is not None:
                self._cache_bytes -= old["nbytes"]
            self._cache[key] = ent
            self._cache_bytes += ent["nbytes"]
            while len(self._cache) > _MAX_ENTRIES or \
                    (self._cache_bytes > self._cache_budget and
                     len(self._cache) > 1):
                _k, dead = self._cache.popitem(last=False)
                self._cache_bytes -= dead["nbytes"]
            if anchor is not None and key[1] not in self._anchor_refs:
                aid = key[1]
                try:
                    self._anchor_refs[aid] = weakref.ref(
                        anchor, lambda _r, a=aid: self._drop_id(a))
                except TypeError:
                    pass        # unweakreffable anchors keep LRU bounds

    def _drop_id(self, aid: int) -> None:
        with self._mu:
            self._anchor_refs.pop(aid, None)
            for k in [k for k in self._cache if k[1] == aid]:
                ent = self._cache.pop(k)
                self._cache_bytes -= ent["nbytes"]

    def set_budget(self, nbytes: int) -> None:
        """Bound the join cache's device-resident bytes and enforce
        NOW.  Wired from ``DeviceRunner.set_hbm_budget`` (the joiner
        takes a fixed slice of the node budget) so the operator's HBM
        cap bounds join state too, not only the feed arena."""
        with self._mu:
            self._cache_budget = max(1 << 20, int(nbytes))
            while self._cache_bytes > self._cache_budget and \
                    len(self._cache) > 0:
                _k, dead = self._cache.popitem(last=False)
                self._cache_bytes -= dead["nbytes"]

    def resident_bytes(self) -> int:
        with self._mu:
            return self._cache_bytes

    def drop_all(self) -> int:
        """Retire EVERY cached build dictionary — the quarantine-drain
        teardown (placement.py ``_on_slice_trip``): a condemned slice's
        joiner entries would otherwise die only by anchor weakref while
        the budget still accounts their HBM on a chip nothing will
        dispatch to again."""
        with self._mu:
            freed = self._cache_bytes
            self._cache.clear()
            self._anchor_refs.clear()
            self._cache_bytes = 0
        return freed

    def drop_anchor(self, anchor) -> int:
        """Feed teardown hook (runner.drop_feed): the anchor's build/
        probe planes die with its feed — stale-epoch join state must
        not survive a region lifecycle event."""
        freed = 0
        with self._mu:
            self._anchor_refs.pop(id(anchor), None)
            for k in [k for k in self._cache if k[1] == id(anchor)]:
                ent = self._cache.pop(k)
                self._cache_bytes -= ent["nbytes"]
                freed += ent["nbytes"]
        return freed

    # ---------------------------------------------------------- kernels

    def _kern(self, key, build):
        fn = self._kernels.get(key)
        if fn is None:
            fn = self._kernels[key] = build()
        return fn

    def _pad(self, n: int) -> int:
        return self._runner._feeds.pad_rows(max(1, n))

    @staticmethod
    def _pad_plane(arr: np.ndarray, n_pad: int):
        if len(arr) == n_pad:
            return jnp.asarray(np.ascontiguousarray(arr))
        p = np.zeros(n_pad, dtype=arr.dtype)
        p[:len(arr)] = arr
        return jnp.asarray(p)

    def _build_kernel(self, n_pad: int):
        def build():
            def fn(n_scalar, keys, valid):
                iota = jnp.arange(n_pad, dtype=jnp.int64)
                sv = valid & (iota < n_scalar)
                skey = jnp.where(sv, keys, _I64.max)
                # valid-first within equal keys: stable argsort
                # composition (the sentinel-collision exactness trick)
                perm0 = jnp.argsort(~sv)
                perm = perm0[jnp.argsort(skey[perm0])]
                sk = skey[perm]
                svs = sv[perm]
                prefix = jnp.concatenate(
                    [jnp.zeros(1, jnp.int64),
                     jnp.cumsum(svs.astype(jnp.int64))])
                return sk, perm.astype(jnp.int32), prefix
            return jax.jit(named_program(fn, "join_build"))
        return self._kern(("join_build", n_pad), build)

    def _probe_side_kernel(self, np_probe: int, np_build: int,
                           k_cap: int, rpns, null_like_sig, n_params: int):
        def build():
            def fn(n_scalar, sk, perm, prefix, pkeys, pvalid, *args):
                params = args[:n_params]
                flat = args[n_params:]
                iota = jnp.arange(np_probe, dtype=jnp.int64)
                rowmask = iota < n_scalar
                pmask = pvalid & rowmask
                if rpns:
                    pairs = []
                    fi = 0
                    while fi < len(flat):
                        pairs.append((flat[fi], flat[fi + 1]))
                        fi += 2
                    one = jnp.ones((), jnp.bool_)
                    for p in params:
                        pairs.append((p, one))
                    for rpn in rpns:
                        v, ok = eval_rpn(rpn, pairs, np_probe, jnp)
                        pmask = pmask & ok & (v != 0)
                lo = jnp.searchsorted(sk, pkeys, side="left")
                hi = jnp.searchsorted(sk, pkeys, side="right")
                cntv = prefix[hi] - prefix[lo]
                cnt = jnp.where(pmask, cntv, 0)
                csum = jnp.cumsum(cnt)
                total = csum[-1]
                j = jnp.arange(k_cap, dtype=jnp.int64)
                probe_of = jnp.clip(
                    jnp.searchsorted(csum, j, side="right"),
                    0, np_probe - 1)
                base = csum[probe_of] - cnt[probe_of]
                within = j - base
                bpos = jnp.clip(lo[probe_of] + within, 0, np_build - 1)
                bidx = perm[bpos]
                ok_pair = j < total
                pi = jnp.where(ok_pair, probe_of, -1).astype(jnp.int32)
                bi = jnp.where(ok_pair, bidx, -1).astype(jnp.int32)
                return pi, bi, total
            return jax.jit(named_program(fn, "join_probe"))
        return self._kern(("join_probe", np_probe, np_build, k_cap,
                           null_like_sig, n_params), build)

    # ------------------------------------------------------------- join

    def _host_key_column(self, scan, ranges, storage, offset: int):
        """One-column scan → (values int64, validity) at scan-output
        positions (the alive mask and range slicing applied by the
        snapshot, exactly like the full scan)."""
        info = scan.columns[offset]
        sub = type(scan)(scan.table_id, (info,))
        col = storage.scan_columns(sub, ranges).columns[0]
        return np.asarray(col.values, dtype=np.int64), \
            np.asarray(col.validity, dtype=np.bool_)

    def _probe_planes(self, scan, ranges, storage, used: list):
        batch = storage.scan_columns(
            type(scan)(scan.table_id,
                       tuple(scan.columns[i] for i in used)), ranges)
        return batch

    def supports_join(self, probe_scan, probe_conds, left_key: int,
                      build_scan, right_key: int) -> bool:
        return join_supported(probe_scan, probe_conds, left_key,
                              build_scan, right_key)

    @_on_runner_device
    def join(self, probe_scan, probe_ranges, probe_storage, probe_conds,
             left_key: int, build_scan, build_ranges, build_storage,
             right_key: int) -> Optional[tuple]:
        """→ ``(probe_idx, build_idx)`` numpy arrays (scan-output
        positions, probe-major order), or None when the fragment is
        outside the device envelope.  Raises on device faults — the
        plan executor owns the per-fragment host degrade."""
        from ..utils import tracker
        if not self.supports_join(probe_scan, probe_conds, left_key,
                                  build_scan, right_key):
            return None
        # ---- build side: device-resident sorted dictionary ----
        banchor, bver = feed_anchor(build_storage), \
            generation(build_storage)[1]
        bkey = ("build", id(banchor), bver, build_scan.columns[
            right_key].col_id, tuple(build_ranges))
        ent = self._cache_get(bkey)
        if ent is None:
            with tracker.phase("join_build"):
                vals, valid = self._host_key_column(
                    build_scan, build_ranges, build_storage, right_key)
                nb = len(vals)
                nb_pad = self._pad(nb)
                kfn = self._build_kernel(nb_pad)
                with self._runner._dispatch_phase(
                        "join_build", key=("join_build", nb_pad)):
                    sk, perm, prefix = kfn(
                        jnp.asarray(nb, jnp.int64),
                        self._pad_plane(vals, nb_pad),
                        self._pad_plane(valid, nb_pad))
                ent = {"sk": sk, "perm": perm, "prefix": prefix,
                       "n": nb, "n_pad": nb_pad,
                       "nbytes": int(sk.nbytes + perm.nbytes +
                                     prefix.nbytes)}
            self._cache_put(bkey, ent, anchor=banchor)
            with self._mu:
                self.build_cache_builds += 1
        else:
            with self._mu:
                self.build_cache_hits += 1
        # ---- probe side: key + fused predicate planes ----
        rpns = [build_rpn(c) for c in probe_conds]
        used = sorted({i for r in rpns
                       for i in _rpn_col_indices(r)})
        panchor, pver = feed_anchor(probe_storage), \
            generation(probe_storage)[1]
        pkey_id = probe_scan.columns[left_key].col_id
        pkey_cache = ("probe", id(panchor), pver, pkey_id,
                      tuple(probe_scan.columns[i].col_id for i in used),
                      tuple(probe_ranges))
        pent = self._cache_get(pkey_cache)
        if pent is None:
            kvals, kvalid = self._host_key_column(
                probe_scan, probe_ranges, probe_storage, left_key)
            npr = len(kvals)
            np_pad = self._pad(npr)
            planes = []
            nbytes = 0
            if used:
                batch = self._probe_planes(probe_scan, probe_ranges,
                                           probe_storage, used)
                for c in batch.columns:
                    v = self._pad_plane(
                        np.ascontiguousarray(c.values), np_pad)
                    m = self._pad_plane(
                        np.ascontiguousarray(c.validity), np_pad)
                    planes.extend((v, m))
                    nbytes += int(v.nbytes + m.nbytes)
            kv = self._pad_plane(kvals, np_pad)
            km = self._pad_plane(kvalid, np_pad)
            nbytes += int(kv.nbytes + km.nbytes)
            pent = {"keys": kv, "valid": km, "planes": tuple(planes),
                    "n": npr, "n_pad": np_pad, "nbytes": nbytes}
            self._cache_put(pkey_cache, pent, anchor=panchor)
        # hoisted predicate constants → traced scalar params (compile
        # classes stay const-blind, selection.py discipline)
        from . import selection as selmod
        remapped = [_remap_rpn(r, {old: new
                               for new, old in enumerate(used)})
                    for r in rpns]
        param_rpns, param_vals, param_dts = selmod.split_params(
            remapped, len(used))
        # ---- probe dispatch (fused selection + dictionary probe) ----
        if fail_point("device::join_dispatch") is not None:
            raise JoinDeviceUnavailable("device::join_dispatch")
        tkey = (probe_scan.table_id, build_scan.table_id)
        with self._mu:
            mult = self._mult.get(tkey, 1.0)
        k_cap = _next_pow2(int(max(
            64, min(pent["n"] * max(1.0, mult) * 1.5 + 64, 1 << 27))))
        rpn_sig = (tuple(r.fingerprint() for r in param_rpns),
                   param_dts)
        total = None
        for attempt in range(3):
            kkey = ("join_probe", pent["n_pad"], ent["n_pad"], k_cap,
                    rpn_sig, len(param_vals))
            kfn = self._probe_side_kernel(
                pent["n_pad"], ent["n_pad"], k_cap, param_rpns, rpn_sig,
                len(param_vals))
            with tracker.phase("join_probe"):
                with self._runner._dispatch_phase("join_probe",
                                                  key=kkey):
                    pi, bi, tot = kfn(
                        jnp.asarray(pent["n"], jnp.int64),
                        ent["sk"], ent["perm"], ent["prefix"],
                        pent["keys"], pent["valid"],
                        *[jnp.asarray(v, dt) for v, dt in
                          zip(param_vals, param_dts)],
                        *pent["planes"])
                total = int(tot)
                if total <= k_cap:
                    pi = np.asarray(pi)
                    bi = np.asarray(bi)
                    break
            # capacity overflow: the on-device total is exact — one
            # re-dispatch at the exact pow2 bucket, never truncation
            k_cap = _next_pow2(max(64, total))
            with self._mu:
                self.overflow_redispatches += 1
            from ..utils import metrics as m
            m.DEVICE_JOIN_ROUTE_COUNTER.labels(
                "overflow_redispatch").inc()
        else:
            raise JoinDeviceUnavailable("pair capacity did not settle")
        with self._mu:
            self.device_joins += 1
            obs = total / max(1, pent["n"])
            self._mult[tkey] = obs if tkey not in self._mult else (
                self.MULT_ALPHA * obs +
                (1 - self.MULT_ALPHA) * self._mult[tkey])
            while len(self._mult) > 128:
                self._mult.pop(next(iter(self._mult)))
        pi = pi[:total].astype(np.int64)
        bi = bi[:total].astype(np.int64)
        return pi, bi

    # ------------------------------------------------------------- sort

    @_on_runner_device
    def sort_perm(self, keys: Sequence[np.ndarray], n: int) -> np.ndarray:
        """Stable composed argsort on device → host permutation (the
        sort fragment's ONLY D2H payload); padding rows are pushed
        strictly last by a leading pad key so ``perm[:n]`` is exact."""
        n_pad = self._pad(n)
        dts = tuple(str(np.asarray(k).dtype) for k in keys)

        def build():
            def fn(n_scalar, *ks):
                iota = jnp.arange(n_pad, dtype=jnp.int64)
                pad_key = (iota >= n_scalar).astype(jnp.int8)
                perm = jnp.arange(n_pad, dtype=jnp.int64)
                for k in list(ks)[::-1] + [pad_key]:
                    perm = perm[jnp.argsort(k[perm])]
                return perm.astype(jnp.int32)
            return jax.jit(named_program(fn, "sort_perm"))
        kfn = self._kern(("sort", n_pad, dts), build)
        with self._runner._dispatch_phase("sort_perm",
                                          key=("sort", n_pad, dts)):
            perm = kfn(jnp.asarray(n, jnp.int64),
                       *[self._pad_plane(np.asarray(k), n_pad)
                         for k in keys])
            out = np.asarray(perm)[:n].astype(np.int64)
        with self._mu:
            self.sorts += 1
        return out

    # ----------------------------------------------------------- window

    @_on_runner_device
    def window(self, batch, node: WindowNode):
        """Device window fragment over a host batch: keys/args upload,
        one dispatch sorts + segmented-scans, the host gathers the
        sorted batch by the returned permutation and appends the
        returned window columns.  → ColumnBatch, or None when a func/
        arg is outside the device envelope (REAL running sums stay
        host — associative-scan rounding would fork parity)."""
        from ..datatype import Column, ColumnBatch, FieldType
        from ..copr import plan_ir as pir
        n = batch.num_rows
        cols = [(c.values, c.validity) for c in batch.columns]
        funcs = []
        for f in node.funcs:
            if f.kind == "row_number":
                funcs.append((f.kind, None, None, 0))
                continue
            if f.kind not in ("count", "sum", "avg", "lag", "lead"):
                return None
            rpn = build_rpn(f.arg)
            if rpn.ret_type is not EvalType.INT and \
                    not (f.kind in ("lag", "lead", "count") and
                         rpn.ret_type is EvalType.REAL):
                return None
            v, ok = eval_rpn(rpn, cols, n, np)
            v = np.ascontiguousarray(np.broadcast_to(v, (n,)))
            ok = np.ascontiguousarray(np.broadcast_to(ok, (n,)))
            funcs.append((f.kind, v, ok, max(1, int(f.offset))))
        part_keys = pir.eval_order_keys(
            batch, tuple((e, False) for e in node.partition_by))
        order_keys = pir.eval_order_keys(batch, node.order_by)
        n_pad = self._pad(n)
        sig = (n_pad, len(part_keys),
               tuple(str(k.dtype) for k in part_keys + order_keys),
               tuple((f[0], None if f[1] is None else str(f[1].dtype),
                      f[3]) for f in funcs))

        def build():
            n_part = len(part_keys)
            n_order = len(order_keys)
            fsig = sig[3]

            def fn(n_scalar, *args):
                pks = args[:n_part]
                oks = args[n_part:n_part + n_order]
                rest = args[n_part + n_order:]
                iota = jnp.arange(n_pad, dtype=jnp.int64)
                pad_key = (iota >= n_scalar).astype(jnp.int8)
                perm = jnp.arange(n_pad, dtype=jnp.int64)
                for k in (list(pks) + list(oks))[::-1] + [pad_key]:
                    perm = perm[jnp.argsort(k[perm])]
                if n_part:
                    boundary = jnp.zeros(n_pad, jnp.bool_).at[0].set(True)
                    for k in pks:
                        sp = k[perm]
                        boundary = boundary.at[1:].set(
                            boundary[1:] | (sp[1:] != sp[:-1]))
                else:
                    boundary = jnp.zeros(n_pad, jnp.bool_).at[0].set(True)
                seg_id = jnp.cumsum(boundary.astype(jnp.int64))
                seg_start = jnp.searchsorted(seg_id, seg_id, side="left")
                seg_end = jnp.searchsorted(seg_id, seg_id, side="right")
                rn = iota - seg_start + 1
                outs = [perm.astype(jnp.int32)]
                ai = 0
                for kind, has_arg, off in [(f[0], f[1] is not None, f[2])
                                           for f in fsig]:
                    if kind == "row_number":
                        outs.append(rn)
                        continue
                    v = rest[ai][perm]
                    ok = rest[ai + 1][perm]
                    ai += 2
                    if kind in ("count", "sum", "avg"):
                        oki = ok.astype(jnp.int64)
                        ccs = jnp.cumsum(oki)
                        ccnt = ccs - (ccs[seg_start] - oki[seg_start])
                        if kind == "count":
                            outs.append(ccnt)
                            continue
                        vv = jnp.where(ok, v, 0).astype(jnp.int64)
                        cs = jnp.cumsum(vv)
                        csum = cs - (cs[seg_start] - vv[seg_start])
                        outs.append(csum)
                        outs.append(ccnt)
                    else:       # lag / lead
                        src = iota - off if kind == "lag" else iota + off
                        in_seg = (src >= seg_start) if kind == "lag" \
                            else (src < seg_end)
                        safe = jnp.clip(src, 0, n_pad - 1)
                        valid = in_seg & (src >= 0) & (src < n_pad) & \
                            ok[safe]
                        outs.append(jnp.where(valid, v[safe],
                                              jnp.zeros((), v.dtype)))
                        outs.append(valid)
                return tuple(outs)
            return jax.jit(named_program(fn, "window"))
        kfn = self._kern(("window",) + sig, build)
        args = [self._pad_plane(np.asarray(k), n_pad)
                for k in part_keys + order_keys]
        for kind, v, ok, _off in funcs:
            if v is not None:
                args.append(self._pad_plane(v, n_pad))
                args.append(self._pad_plane(ok, n_pad))
        with self._runner._dispatch_phase("window",
                                          key=("window",) + sig):
            outs = kfn(jnp.asarray(n, jnp.int64), *args)
            outs = [np.asarray(o)[:n] for o in outs]
        perm = outs[0].astype(np.int64)
        sorted_batch = batch.take(perm)
        out_cols = list(sorted_batch.columns)
        out_schema = list(sorted_batch.schema)
        ones = np.ones(n, np.bool_)
        oi = 1
        for (kind, v, ok, _off), f in zip(funcs, node.funcs):
            if kind == "row_number":
                outs_rn = outs[oi]
                oi += 1
                out_cols.append(Column(EvalType.INT,
                                       outs_rn.astype(np.int64),
                                       ones.copy()))
                out_schema.append(FieldType.long())
            elif kind == "count":
                ccnt = outs[oi]
                oi += 1
                out_cols.append(Column(EvalType.INT,
                                       ccnt.astype(np.int64),
                                       ones.copy()))
                out_schema.append(FieldType.long())
            elif kind in ("sum", "avg"):
                csum, ccnt = outs[oi], outs[oi + 1]
                oi += 2
                if kind == "sum":
                    out_cols.append(Column(EvalType.INT,
                                           csum.astype(np.int64),
                                           ccnt > 0))
                    out_schema.append(FieldType.long())
                else:
                    with np.errstate(divide="ignore", invalid="ignore"):
                        avg = csum.astype(np.float64) / ccnt
                    out_cols.append(Column(
                        EvalType.REAL, np.where(ccnt > 0, avg, 0.0),
                        ccnt > 0))
                    out_schema.append(FieldType.double())
            else:       # lag / lead
                vals, valid = outs[oi], outs[oi + 1]
                oi += 2
                et = EvalType.INT if vals.dtype.kind in "iu" \
                    else EvalType.REAL
                out_cols.append(Column(
                    et, vals.astype(np.int64)
                    if et is EvalType.INT else vals.astype(np.float64),
                    valid.astype(np.bool_)))
                out_schema.append(FieldType.long()
                                  if et is EvalType.INT
                                  else FieldType.double())
        with self._mu:
            self.windows += 1
        return ColumnBatch(out_schema, out_cols)

    # ------------------------------------------------------------ stats

    def stats(self) -> dict:
        with self._mu:
            return {
                "device_joins": self.device_joins,
                "build_cache_hits": self.build_cache_hits,
                "build_cache_builds": self.build_cache_builds,
                "overflow_redispatches": self.overflow_redispatches,
                "sorts": self.sorts,
                "windows": self.windows,
                "cache_entries": len(self._cache),
                "cache_bytes": self._cache_bytes,
                "multiplicity_ewma": {f"{k[0]}x{k[1]}": round(v, 3)
                                      for k, v in
                                      list(self._mult.items())[-8:]},
            }


