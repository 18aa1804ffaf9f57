"""Unified plan IR — one operator-DAG representation, per-operator routing.

The tipb vocabulary the reference consumes is a LINEAR chain rooted at
one scan (copr/dag.py ``DAGRequest``) — runner.rs:139-166 deliberately
omits Join/Window/Sort/Exchange, so TiKV executes only leaf fragments
and the operator boundary is where every pushed-down plan stops.  This
module crosses it:

- :class:`PlanRequest` holds an operator DAG (:class:`ScanNode`,
  :class:`SelectNode`, …, :class:`JoinNode`, :class:`SortNode`,
  :class:`WindowNode`).  Any tipb-shaped linear chain embeds losslessly
  (:func:`from_dag` / :meth:`LeafFragment.dag` round-trip), so the IR
  is a SUPERSET: leaf fragments stay wire-compatible with the tipb
  vocabulary while join/sort/window plans are an extension the
  reference system cannot serve.

- The plan is split into FRAGMENTS (maximal linear chains, plus one
  fragment per join/sort/window operator) and routed PER FRAGMENT, not
  per plan (:class:`FragmentRouter`): a single request can run a device
  scan+join and a host aggregation finalize.  Leaf fragments reuse the
  endpoint's existing device machinery end to end (resident HBM feeds,
  late-materialized selection, coalescing); join/sort/window fragments
  ride the kernels in :mod:`tikv_tpu.device.join`.  The router anchors
  its host model on the endpoint's measured ``device_row_threshold``
  and the coalescer CostRouter's live launch EWMA — the same
  calibration discipline as PR 7 — and the ``copr::plan_route``
  failpoint forces a whole-request host route.

- Late materialization (Abadi et al.) is the cross-fragment contract:
  a device join leaves row-index PAIRS on device and ships only them
  (8 bytes/pair); a device sort ships a permutation; the host gathers
  only the columns the parent operator demands, from the columnar
  snapshots that are already resident host-side.

- Every device fragment degrades to its HOST twin per fragment on any
  fault (incl. the ``device::join_dispatch`` failpoint): a faulted
  device join falls back to the host hash join for that fragment only
  — the plan's other fragments keep their routes.

Determinism contract (parity-testable by construction): an inner join
emits pairs ordered by probe scan position, then build scan position
(NULL keys never match); SORT is a stable sort over the transformed
keys in :func:`sort_key_i64` / :func:`sort_key_f64` (MySQL NULL
ordering: first for ASC, last for DESC); WINDOW emits its rows sorted
by (partition, order) with the window columns appended.  The host and
device implementations share these transforms, so results are
bit-identical across routes.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Optional, Sequence, Union

import numpy as np

from ..datatype import Column, ColumnBatch, EvalType, FieldType
from ..expr import Expr, build_rpn
from ..expr.eval import eval_rpn
from .dag import (
    AggregationDesc,
    DAGRequest,
    IndexScanDesc,
    LimitDesc,
    PartitionTopNDesc,
    ProjectionDesc,
    SelectionDesc,
    TableScanDesc,
    TopNDesc,
)

# ------------------------------------------------------------------ nodes


@dataclass(frozen=True)
class ScanNode:
    """Leaf: one table/index scan with its OWN key ranges — a join's two
    sides each carry their own region's ranges, and the endpoint
    acquires one snapshot per leaf."""

    scan: Union[TableScanDesc, IndexScanDesc]
    ranges: tuple            # tuple[KeyRange]


@dataclass(frozen=True)
class SelectNode:
    child: "PlanNode"
    conditions: tuple        # tuple[Expr] — ANDed


@dataclass(frozen=True)
class ProjectNode:
    child: "PlanNode"
    exprs: tuple


@dataclass(frozen=True)
class AggNode:
    child: "PlanNode"
    desc: AggregationDesc


@dataclass(frozen=True)
class TopNNode:
    child: "PlanNode"
    desc: TopNDesc


@dataclass(frozen=True)
class PartTopNNode:
    child: "PlanNode"
    desc: PartitionTopNDesc


@dataclass(frozen=True)
class LimitNode:
    child: "PlanNode"
    limit: int


@dataclass(frozen=True)
class JoinNode:
    """Inner equi-join.  ``left`` is the PROBE side (large; its
    selection predicates fuse into the device probe dispatch), ``right``
    is the BUILD side (small; its key column dictionary-sorts into the
    device-resident build structure).  Keys are column OFFSETS into
    each child's output schema.  Output schema = left columns ++ right
    columns; pairs emit ordered by probe scan position, then build scan
    position."""

    left: "PlanNode"
    right: "PlanNode"
    left_key: int
    right_key: int
    join_type: str = "inner"


@dataclass(frozen=True)
class SortNode:
    """Full stable sort (no limit — TopN stays the bounded variant).
    ``order_by``: tuple of (Expr, desc) evaluated over the child's
    output; NULLs first for ASC, last for DESC (MySQL)."""

    child: "PlanNode"
    order_by: tuple          # tuple[(Expr, desc: bool)]


@dataclass(frozen=True)
class WindowFuncDesc:
    """kind ∈ row_number | count | sum | avg | lag | lead.  ``arg`` is
    required for all but row_number; ``offset`` applies to lag/lead.
    count/sum/avg are RUNNING (rows from partition start to current
    row) — the shifted-segmented-scan shapes the device kernel serves."""

    kind: str
    arg: Optional[Expr] = None
    offset: int = 1


@dataclass(frozen=True)
class WindowNode:
    child: "PlanNode"
    partition_by: tuple      # tuple[Expr]
    order_by: tuple          # tuple[(Expr, desc: bool)]
    funcs: tuple             # tuple[WindowFuncDesc]


PlanNode = Union[ScanNode, SelectNode, ProjectNode, AggNode, TopNNode,
                 PartTopNNode, LimitNode, JoinNode, SortNode, WindowNode]

_LINEAR = (SelectNode, ProjectNode, AggNode, TopNNode, PartTopNNode,
           LimitNode)


@dataclass(frozen=True)
class PlanRequest:
    """The IR request envelope (the coppb Request analog for plans)."""

    root: PlanNode
    start_ts: int = 0
    output_offsets: Optional[tuple] = None
    encode_type: str = "rows"       # as ``DAGRequest.encode_type``

    def plan_key(self) -> tuple:
        """Hashable plan identity (share-class key, jit-cache key)."""
        return (_node_key(self.root), self.start_ts, self.output_offsets)

    def class_key(self) -> tuple:
        """Const-blind COMPILE-CLASS identity — ``DAGRequest.class_key``
        for plans: numeric constant VALUES erased (device-dtype bucket
        only), start_ts and key ranges excluded.  Keys the read pool's
        per-class service-time EWMA and the trace buffer's slow-pin
        class; ``plan_key`` (which must distinguish snapshots) stays
        the coalescer's share key."""
        return ("plan", _node_key(self.root, class_blind=True),
                self.output_offsets)

    def scan_leaves(self) -> list[ScanNode]:
        out: list[ScanNode] = []

        def walk(n: PlanNode) -> None:
            if isinstance(n, ScanNode):
                out.append(n)
            elif isinstance(n, JoinNode):
                walk(n.left)
                walk(n.right)
            else:
                walk(n.child)
        walk(self.root)
        return out

    def has_join(self) -> bool:
        return any(True for _ in _iter_nodes(self.root)
                   if isinstance(_, JoinNode))


def _iter_nodes(n: PlanNode):
    yield n
    if isinstance(n, ScanNode):
        return
    if isinstance(n, JoinNode):
        yield from _iter_nodes(n.left)
        yield from _iter_nodes(n.right)
        return
    yield from _iter_nodes(n.child)


def _expr_key(e: Expr, class_blind: bool = False):
    if e.kind == "const":
        v = e.value
        if class_blind and isinstance(v, (int, float)) and \
                not isinstance(v, bool):
            from ..datatype import device_const_dtype
            return ("c?", device_const_dtype(v),
                    e.eval_type.value if e.eval_type else None)
        return ("c", repr(v),
                e.eval_type.value if e.eval_type else None)
    if e.kind == "column":
        return ("col", e.col_idx, e.eval_type.value if e.eval_type else None)
    return ("f", e.sig,
            tuple(_expr_key(c, class_blind) for c in e.children))


def _node_key(n: PlanNode, class_blind: bool = False) -> tuple:
    def nk(m):
        return _node_key(m, class_blind)

    def ek(e):
        return _expr_key(e, class_blind)

    if isinstance(n, ScanNode):
        kind = "iscan" if isinstance(n.scan, IndexScanDesc) else "tscan"
        return (kind, n.scan.table_id,
                tuple((c.col_id, c.field_type.tp, c.is_pk_handle)
                      for c in n.scan.columns),
                bool(n.scan.desc),
                # class identity is range-blind like DAGRequest's: two
                # requests over shifting ranges share one cost class
                () if class_blind else tuple(n.ranges))
    if isinstance(n, SelectNode):
        return ("sel", nk(n.child),
                tuple(ek(e) for e in n.conditions))
    if isinstance(n, ProjectNode):
        return ("proj", nk(n.child), tuple(ek(e) for e in n.exprs))
    if isinstance(n, AggNode):
        d = n.desc
        return ("agg", nk(n.child),
                tuple(ek(e) for e in d.group_by),
                tuple((a.kind, ek(a.arg) if a.arg else None)
                      for a in d.aggs), d.streamed)
    if isinstance(n, TopNNode):
        return ("topn", nk(n.child),
                tuple((ek(e), dsc) for e, dsc in n.desc.order_by),
                n.desc.limit)
    if isinstance(n, PartTopNNode):
        return ("ptopn", nk(n.child),
                tuple(ek(e) for e in n.desc.partition_by),
                tuple((ek(e), dsc) for e, dsc in n.desc.order_by),
                n.desc.limit)
    if isinstance(n, LimitNode):
        return ("limit", nk(n.child), n.limit)
    if isinstance(n, JoinNode):
        return ("join", nk(n.left), nk(n.right),
                n.left_key, n.right_key, n.join_type)
    if isinstance(n, SortNode):
        return ("sort", nk(n.child),
                tuple((ek(e), dsc) for e, dsc in n.order_by))
    if isinstance(n, WindowNode):
        return ("window", nk(n.child),
                tuple(ek(e) for e in n.partition_by),
                tuple((ek(e), dsc) for e, dsc in n.order_by),
                tuple((f.kind, ek(f.arg) if f.arg else None,
                       f.offset) for f in n.funcs))
    raise TypeError(n)


def from_dag(dag: DAGRequest) -> PlanRequest:
    """Embed a tipb-shaped linear DAGRequest into the IR (lossless)."""
    node: PlanNode = ScanNode(dag.executors[0], tuple(dag.ranges))
    for d in dag.executors[1:]:
        if isinstance(d, SelectionDesc):
            node = SelectNode(node, d.conditions)
        elif isinstance(d, ProjectionDesc):
            node = ProjectNode(node, d.exprs)
        elif isinstance(d, AggregationDesc):
            node = AggNode(node, d)
        elif isinstance(d, TopNDesc):
            node = TopNNode(node, d)
        elif isinstance(d, PartitionTopNDesc):
            node = PartTopNNode(node, d)
        elif isinstance(d, LimitDesc):
            node = LimitNode(node, d.limit)
        else:
            raise ValueError(f"unsupported executor {d}")
    return PlanRequest(node, start_ts=dag.start_ts,
                       output_offsets=dag.output_offsets,
                       encode_type=dag.encode_type)


# ------------------------------------------------------------- fragments


@dataclass
class LeafFragment:
    """Maximal linear chain rooted at a scan — exactly a DAGRequest, so
    it routes through the endpoint's existing host/device machinery."""

    chain: list              # [ScanNode, op descs...] bottom-up
    start_ts: int
    backend: str = "host"

    @property
    def scan_node(self) -> ScanNode:
        return self.chain[0]

    def dag(self) -> DAGRequest:
        descs: list = [self.scan_node.scan]
        for n in self.chain[1:]:
            if isinstance(n, SelectNode):
                descs.append(SelectionDesc(n.conditions))
            elif isinstance(n, ProjectNode):
                descs.append(ProjectionDesc(n.exprs))
            elif isinstance(n, (AggNode, TopNNode, PartTopNNode)):
                descs.append(n.desc)
            elif isinstance(n, LimitNode):
                descs.append(LimitDesc(n.limit))
        return DAGRequest(tuple(descs), tuple(self.scan_node.ranges),
                          start_ts=self.start_ts)

    def probe_shape(self):
        """→ (scan_node, sel_conditions) when this fragment is a bare
        scan or scan+selection — the shape whose predicates fuse into a
        device join's probe dispatch — else None."""
        conds: tuple = ()
        for n in self.chain[1:]:
            if isinstance(n, SelectNode):
                conds = conds + tuple(n.conditions)
            else:
                return None
        return self.scan_node, conds


@dataclass
class JoinFragment:
    left: "Fragment"
    right: "Fragment"
    node: JoinNode
    backend: str = "host"


@dataclass
class SortFragment:
    child: "Fragment"
    node: SortNode
    backend: str = "host"


@dataclass
class WindowFragment:
    child: "Fragment"
    node: WindowNode
    backend: str = "host"


@dataclass
class HostOpsFragment:
    """Host-only operator chain above a join/sort/window fragment — the
    'host finalize' half of a mixed plan.  Runs the stock executors
    (aggregation/top_n/simple) over the child fragment's batch."""

    child: "Fragment"
    ops: list                # SelectNode/ProjectNode/AggNode/... bottom-up
    backend: str = "host"


Fragment = Union[LeafFragment, JoinFragment, SortFragment, WindowFragment,
                 HostOpsFragment]


def fragmentize(preq: PlanRequest) -> Fragment:
    def walk(n: PlanNode) -> Fragment:
        if isinstance(n, ScanNode):
            return LeafFragment([n], preq.start_ts)
        if isinstance(n, JoinNode):
            return JoinFragment(walk(n.left), walk(n.right), n)
        if isinstance(n, SortNode):
            return SortFragment(walk(n.child), n)
        if isinstance(n, WindowNode):
            return WindowFragment(walk(n.child), n)
        child = walk(n.child)
        if isinstance(child, LeafFragment):
            child.chain.append(n)
            return child
        if isinstance(child, HostOpsFragment):
            child.ops.append(n)
            return child
        return HostOpsFragment(child, [n])
    return walk(preq.root)


def iter_fragments(frag: Fragment):
    yield frag
    if isinstance(frag, JoinFragment):
        yield from iter_fragments(frag.left)
        yield from iter_fragments(frag.right)
    elif isinstance(frag, (SortFragment, WindowFragment, HostOpsFragment)):
        yield from iter_fragments(frag.child)


def _frag_kind(frag: Fragment) -> str:
    return {LeafFragment: "leaf", JoinFragment: "join",
            SortFragment: "sort", WindowFragment: "window",
            HostOpsFragment: "host_ops"}[type(frag)]


# -------------------------------------------------- shared sort transforms
#
# The device and host implementations of SORT/WINDOW (and the join's
# build-side ordering) share these EXACT key transforms, so stable
# sorts over the transformed keys are bit-identical across routes.
# Values at the int64 extremes clamp by 2 to make room for the NULL
# sentinels (order is preserved except that the two lowest/highest
# representable values collapse — consistently on both routes).

_I64 = np.iinfo(np.int64)


def sort_key_i64(values, validity, desc: bool, xp=np):
    v = xp.clip(values.astype(np.int64) if xp is np
                else values.astype("int64"), _I64.min + 2, _I64.max)
    if desc:
        return xp.where(validity, -v, _I64.max)
    return xp.where(validity, v, _I64.min)


def sort_key_f64(values, validity, desc: bool, xp=np):
    v = values.astype(np.float64) if xp is np else values.astype("float64")
    if desc:
        return xp.where(validity, -v, np.inf)
    return xp.where(validity, v, -np.inf)


def eval_order_keys(batch: ColumnBatch, order_by) -> list[np.ndarray]:
    """Evaluate (Expr, desc) pairs over a host batch → transformed
    int64/float64 key arrays (ascending stable sort of these yields the
    requested order)."""
    n = batch.num_rows
    cols = [(c.values, c.validity) for c in batch.columns]
    keys = []
    for e, desc in order_by:
        rpn = build_rpn(e)
        if rpn.ret_type not in (EvalType.INT, EvalType.REAL):
            raise ValueError(f"unsupported sort key type {rpn.ret_type}")
        v, ok = eval_rpn(rpn, cols, n, np)
        v = np.broadcast_to(v, (n,))
        ok = np.broadcast_to(ok, (n,))
        if rpn.ret_type is EvalType.INT:
            keys.append(sort_key_i64(v, ok, desc))
        else:
            keys.append(sort_key_f64(v, ok, desc))
    return keys


def stable_perm(keys: Sequence[np.ndarray],
                n: Optional[int] = None) -> np.ndarray:
    """Composed stable argsort (last key least significant — lexsort
    semantics with keys[0] as the primary).  ``n`` is required when
    ``keys`` may be empty (a keyless sort is the identity — it must
    not collapse to zero rows)."""
    if n is None:
        n = len(keys[0]) if keys else 0
    perm = np.arange(n, dtype=np.int64)
    for k in reversed(keys):
        perm = perm[np.argsort(k[perm], kind="stable")]
    return perm


# ------------------------------------------------------- host join / ops


def join_pairs_host(lk, lok, rk, rok):
    """Inner equi-join pair emission — the parity reference shared by
    the host route and the degrade path.  Returns
    ``(probe_idx, build_idx)`` ordered by probe position then build
    position; NULL keys never match."""
    lk = np.asarray(lk, dtype=np.int64)
    rk = np.asarray(rk, dtype=np.int64)
    vidx = np.flatnonzero(rok)
    order = vidx[np.argsort(rk[vidx], kind="stable")]
    skeys = rk[order]
    lo = np.searchsorted(skeys, lk, side="left")
    hi = np.searchsorted(skeys, lk, side="right")
    cnt = np.where(lok, hi - lo, 0)
    total = int(cnt.sum())
    probe_idx = np.repeat(np.arange(len(lk), dtype=np.int64), cnt)
    csum = np.cumsum(cnt)
    within = np.arange(total, dtype=np.int64) - \
        np.repeat(csum - cnt, cnt)
    build_idx = order[np.repeat(lo, cnt) + within]
    return probe_idx, build_idx


def concat_schemas(left: ColumnBatch, right: ColumnBatch) -> ColumnBatch:
    return ColumnBatch(list(left.schema) + list(right.schema),
                       list(left.columns) + list(right.columns))


class _BatchFeedExecutor:
    """Adapter: serve an in-memory ColumnBatch through the
    BatchExecutor pull interface, so the stock host executors
    (selection/projection/aggregation/top_n/limit) finalize plans whose
    input is a join/sort/window fragment's output instead of a scan."""

    def __init__(self, batch: ColumnBatch):
        from ..executors.interface import ExecSummary
        self.summary = ExecSummary()
        self._batch = batch
        self._pos = 0

    @property
    def schema(self):
        return self._batch.schema

    def next_batch(self, scan_rows: int):
        from ..executors.interface import BatchExecuteResult
        start = self._pos
        stop = min(start + scan_rows, self._batch.num_rows)
        self._pos = stop
        return BatchExecuteResult(self._batch.slice(start, stop),
                                  stop >= self._batch.num_rows)


def run_host_ops(batch: ColumnBatch, ops: Sequence) -> ColumnBatch:
    """Drive the stock host executors over an in-memory batch."""
    from ..executors.aggregation import (
        BatchFastHashAggExecutor,
        BatchSimpleAggExecutor,
        BatchSlowHashAggExecutor,
        BatchStreamAggExecutor,
    )
    from ..executors.runner import _is_fast_key
    from ..executors.simple import (
        BatchLimitExecutor,
        BatchProjectionExecutor,
        BatchSelectionExecutor,
    )
    from ..executors.top_n import BatchTopNExecutor
    ex = _BatchFeedExecutor(batch)
    for n in ops:
        if isinstance(n, SelectNode):
            ex = BatchSelectionExecutor(ex, SelectionDesc(n.conditions))
        elif isinstance(n, ProjectNode):
            ex = BatchProjectionExecutor(ex, ProjectionDesc(n.exprs))
        elif isinstance(n, AggNode):
            d = n.desc
            if not d.group_by:
                ex = BatchSimpleAggExecutor(ex, d)
            elif d.streamed:
                ex = BatchStreamAggExecutor(ex, d)
            elif len(d.group_by) == 1 and _is_fast_key(d.group_by[0]):
                ex = BatchFastHashAggExecutor(ex, d)
            else:
                ex = BatchSlowHashAggExecutor(ex, d)
        elif isinstance(n, TopNNode):
            ex = BatchTopNExecutor(ex, n.desc)
        elif isinstance(n, PartTopNNode):
            from ..executors.top_n import BatchPartitionTopNExecutor
            ex = BatchPartitionTopNExecutor(ex, n.desc)
        elif isinstance(n, LimitNode):
            ex = BatchLimitExecutor(ex, LimitDesc(n.limit))
        else:
            raise ValueError(f"unsupported host op {n}")
    chunks = []
    while True:
        r = ex.next_batch(1 << 20)
        if r.batch.num_rows:
            chunks.append(r.batch)
        if r.is_drained:
            break
    return ColumnBatch.concat(chunks) if chunks \
        else ColumnBatch.empty(ex.schema)


def window_host(batch: ColumnBatch, node: WindowNode) -> ColumnBatch:
    """Host window fragment: sort by (partition, order), then running
    aggregates as segmented scans over the sorted view — the numpy twin
    of the device kernel (device/join.py), same transforms, same
    emission order (sorted)."""
    n = batch.num_rows
    part_keys = eval_order_keys(
        batch, tuple((e, False) for e in node.partition_by))
    order_keys = eval_order_keys(batch, node.order_by)
    perm = stable_perm(part_keys + order_keys, n)
    sorted_batch = batch.take(perm)
    if part_keys:
        sp = np.stack([k[perm] for k in part_keys])
        boundary = np.ones(n, np.bool_)
        if n > 1:
            boundary[1:] = (sp[:, 1:] != sp[:, :-1]).any(axis=0)
    else:
        boundary = np.zeros(n, np.bool_)
        if n:
            boundary[0] = True
    seg_start = np.maximum.accumulate(
        np.where(boundary, np.arange(n, dtype=np.int64), 0))
    out_cols, out_schema = list(sorted_batch.columns), \
        list(sorted_batch.schema)
    cols = [(c.values, c.validity) for c in sorted_batch.columns]
    rn = np.arange(n, dtype=np.int64) - seg_start + 1
    ones = np.ones(n, np.bool_)
    for f in node.funcs:
        if f.kind == "row_number":
            out_cols.append(Column(EvalType.INT, rn.copy(), ones.copy()))
            out_schema.append(FieldType.long())
            continue
        rpn = build_rpn(f.arg)
        if rpn.ret_type not in (EvalType.INT, EvalType.REAL):
            raise ValueError(f"unsupported window arg type {rpn.ret_type}")
        v, ok = eval_rpn(rpn, cols, n, np)
        v = np.broadcast_to(v, (n,))
        ok = np.broadcast_to(ok, (n,))
        if f.kind in ("count", "sum", "avg"):
            okf = ok.astype(np.int64)
            ccnt = _seg_running(okf, seg_start)
            if f.kind == "count":
                out_cols.append(Column(EvalType.INT, ccnt, ones.copy()))
                out_schema.append(FieldType.long())
                continue
            vv = np.where(ok, v, 0)
            if rpn.ret_type is EvalType.INT:
                csum = _seg_running(vv.astype(np.int64), seg_start)
            else:
                csum = _seg_running(vv.astype(np.float64), seg_start)
            if f.kind == "sum":
                et = rpn.ret_type
                out_cols.append(Column(et, csum, ccnt > 0))
                out_schema.append(FieldType.long()
                                  if et is EvalType.INT
                                  else FieldType.double())
            else:       # avg
                with np.errstate(divide="ignore", invalid="ignore"):
                    avg = csum.astype(np.float64) / ccnt
                out_cols.append(Column(EvalType.REAL,
                                       np.where(ccnt > 0, avg, 0.0),
                                       ccnt > 0))
                out_schema.append(FieldType.double())
        elif f.kind in ("lag", "lead"):
            off = max(1, int(f.offset))
            idx = np.arange(n, dtype=np.int64)
            src = idx - off if f.kind == "lag" else idx + off
            in_seg = (src >= seg_start) if f.kind == "lag" else \
                (src < _seg_end(seg_start, n))
            in_bounds = (src >= 0) & (src < n)
            safe = np.clip(src, 0, max(0, n - 1))
            valid = in_bounds & in_seg & \
                (ok[safe] if n else np.zeros(0, np.bool_))
            vals = v[safe] if n else v
            out_cols.append(Column(rpn.ret_type,
                                   np.where(valid, vals, 0), valid))
            out_schema.append(FieldType.long()
                              if rpn.ret_type is EvalType.INT
                              else FieldType.double())
        else:
            raise ValueError(f"unsupported window func {f.kind}")
    return ColumnBatch(out_schema, out_cols)


def _seg_running(vals: np.ndarray, seg_start: np.ndarray) -> np.ndarray:
    """Inclusive running reduction (sum) within segments: the classic
    'cumsum minus the segment-start offset' shifted segmented scan."""
    n = len(vals)
    if not n:
        return vals
    cs = np.cumsum(vals)
    base = cs[seg_start] - vals[seg_start]
    return cs - base


def _seg_end(seg_start: np.ndarray, n: int) -> np.ndarray:
    """Exclusive end index of each row's segment."""
    if not n:
        return seg_start
    is_start = seg_start == np.arange(n)
    starts = np.flatnonzero(is_start)
    # rows of segment i end where segment i+1 starts
    bounds = np.append(starts[1:], n)
    return bounds[np.cumsum(is_start) - 1]


# ----------------------------------------------------------- the router


class FragmentRouter:
    """Per-fragment host/device placement.

    Leaf fragments defer to the endpoint's existing verdict
    (``supports``/``profitable`` + the transport-measured row
    threshold).  Join/sort/window fragments compare a modeled device
    cost — the live launch EWMA (borrowed from the coalescer's
    CostRouter when present, PR 7's measured figure) plus the
    late-materialized D2H payload — against the host cost anchored on
    the same row threshold, exactly the calibration the CostRouter
    uses, then fold in the per-kind wall EWMAs observed on THIS node so
    a route that measures wrong corrects itself.  The
    ``copr::plan_route`` failpoint forces every fragment host."""

    D2H_BYTES_PER_S = 8e9
    EWMA_ALPHA = 0.25
    # every N EWMA-decided routes per kind, the LOSING backend serves
    # once to refresh its wall — a cold-compile-poisoned device EWMA
    # (or a workload whose costs drifted) is re-discovered instead of
    # locked out forever (the selection router's reprobe discipline)
    REPROBE_EVERY = 16

    def __init__(self, endpoint):
        self._endpoint = endpoint
        self._mu = threading.Lock()
        # per-(kind, backend) wall EWMAs (seconds)
        self._walls: dict = {}
        self._probe_ticks: dict = {}
        self.decisions: dict = {}

    # -- measurement feedback --

    def note_wall(self, kind: str, backend: str, wall_s: float) -> None:
        with self._mu:
            cur = self._walls.get((kind, backend))
            self._walls[(kind, backend)] = wall_s if cur is None else \
                (self.EWMA_ALPHA * wall_s + (1 - self.EWMA_ALPHA) * cur)

    def _wall(self, kind: str, backend: str) -> Optional[float]:
        with self._mu:
            return self._walls.get((kind, backend))

    def _launch_s(self) -> float:
        coal = getattr(self._endpoint, "coalescer", None)
        if coal is not None:
            return coal.router.launch_ewma
        return 1.5e-3

    def _threshold(self) -> int:
        return getattr(self._endpoint, "_device_row_threshold", 0) or 131072

    def _note(self, kind: str, backend: str) -> str:
        from ..utils import metrics as m
        m.COPR_PLAN_FRAGMENT_COUNTER.labels(kind, backend).inc()
        with self._mu:
            k = (kind, backend)
            self.decisions[k] = self.decisions.get(k, 0) + 1
        return backend

    def route(self, frag: Fragment, storages: dict,
              force_backend: Optional[str] = None) -> None:
        """Annotate ``frag`` (recursively) with per-fragment backends."""
        from ..utils.failpoint import fail_point
        forced_host = force_backend == "host" or \
            fail_point("copr::plan_route") is not None
        self._route_rec(frag, storages, forced_host,
                        force_dev=force_backend == "device")

    def _route_rec(self, frag, storages, forced_host: bool,
                   force_dev: bool) -> None:
        runner = getattr(self._endpoint, "_device_runner", None)
        if isinstance(frag, LeafFragment):
            frag.backend = self._route_leaf(frag, storages, forced_host,
                                            force_dev, runner)
            self._note("leaf", frag.backend)
            return
        if isinstance(frag, HostOpsFragment):
            frag.backend = "host"
            self._note("host_ops", "host")
            self._route_rec(frag.child, storages, forced_host, force_dev)
            return
        kind = _frag_kind(frag)
        children = [frag.left, frag.right] if isinstance(
            frag, JoinFragment) else [frag.child]
        for c in children:
            self._route_rec(c, storages, forced_host, force_dev)
        if forced_host or runner is None:
            frag.backend = "host"
        elif force_dev:
            frag.backend = "device"
        else:
            frag.backend = self._model(frag, storages, runner)
        self._note(kind, frag.backend)

    def _route_leaf(self, frag, storages, forced_host, force_dev,
                    runner) -> str:
        if forced_host or runner is None:
            return "host"
        dag = frag.dag()
        storage = storages.get(id(frag.scan_node))
        if storage is None or not runner.supports(dag):
            return "host"
        if force_dev:
            return "device"
        profit = getattr(runner, "profitable", None)
        if profit is not None and not profit(dag):
            return "host"
        est = getattr(storage, "estimated_rows", None)
        n = est() if callable(est) else None
        if n is not None and n >= self._threshold():
            return "device"
        return "host"

    def _rows_of(self, frag, storages) -> Optional[int]:
        if isinstance(frag, LeafFragment):
            storage = storages.get(id(frag.scan_node))
            est = getattr(storage, "estimated_rows", None)
            return est() if callable(est) else None
        if isinstance(frag, JoinFragment):
            return self._rows_of(frag.left, storages)
        return self._rows_of(frag.child, storages)

    def _model(self, frag, storages, runner) -> str:
        """Modeled device-vs-host comparison for a join/sort/window
        fragment; the observed per-kind wall EWMAs override the model
        once both routes have measurements.  All three kinds are
        single-device by construction: joins run on the runner itself
        (single-chip) or a placement slice co-locating both feeds;
        sort/window inputs are anchorless batches, so they ride the
        device only on a single-chip runner."""
        kind = _frag_kind(frag)
        single = getattr(runner, "_single", False)
        if kind == "join":
            if not single and getattr(runner, "_placer", None) is None:
                return "host"
        elif not single:
            return "host"
        dev_w, host_w = self._wall(kind, "device"), \
            self._wall(kind, "host")
        if dev_w is not None and host_w is not None:
            winner = "device" if dev_w <= host_w else "host"
            with self._mu:
                self._probe_ticks[kind] = \
                    self._probe_ticks.get(kind, 0) + 1
                if self._probe_ticks[kind] >= self.REPROBE_EVERY:
                    self._probe_ticks[kind] = 0
                    return "host" if winner == "device" else "device"
            return winner
        n = self._rows_of(frag, storages)
        if n is None:
            return "host"
        launch = self._launch_s()
        # late-materialized D2H: 8 bytes/pair for a join (capacity-
        # bucketed), 8 bytes/row of permutation for sort/window
        d2h = 8.0 * n / self.D2H_BYTES_PER_S
        ndisp = 2.0 if kind == "join" else 1.0
        cost_dev = launch * ndisp + d2h
        # host cost anchored on the operator-tuned solo break-even,
        # scaled up: a join/sort is a super-linear host pass (hash
        # build + emission / n log n), conservatively ~2× the linear
        # per-row figure the threshold calibrates
        cost_host = 2.0 * n * launch / max(1, self._threshold())
        return "device" if cost_dev < cost_host else "host"

    def stats(self) -> dict:
        with self._mu:
            return {
                "decisions": {f"{k[0]}:{k[1]}": v
                              for k, v in self.decisions.items()},
                "wall_ewma_ms": {f"{k[0]}:{k[1]}": round(v * 1e3, 3)
                                 for k, v in self._walls.items()},
            }


# --------------------------------------------------------- the executor


class PlanExecutor:
    """Executes a routed fragment tree: device fragments through the
    runner / device-join kernels with per-fragment host degrade, host
    fragments through the stock executors.  One per endpoint."""

    def __init__(self, endpoint):
        self._endpoint = endpoint
        self.router = FragmentRouter(endpoint)
        self._mu = threading.Lock()
        self.join_backends: dict = {}       # device/host/degrade counts
        self.colocation_hits = 0
        self.colocation_misses = 0
        self.plans_served = 0

    # -- stats / health --

    def stats(self) -> dict:
        runner = getattr(self._endpoint, "_device_runner", None)
        joiner = getattr(runner, "_joiner", None) \
            if runner is not None else None
        with self._mu:
            out = {
                "plans_served": self.plans_served,
                "join_backends": dict(self.join_backends),
                "colocation_hits": self.colocation_hits,
                "colocation_misses": self.colocation_misses,
                "router": self.router.stats(),
            }
        if joiner is not None:
            out["device_join"] = joiner.stats()
        return out

    def _note_join(self, backend: str) -> None:
        from ..utils import metrics as m
        m.DEVICE_JOIN_ROUTE_COUNTER.labels(backend).inc()
        with self._mu:
            self.join_backends[backend] = \
                self.join_backends.get(backend, 0) + 1

    # -- entry --

    def execute(self, preq: PlanRequest, storages: dict,
                force_backend: Optional[str] = None):
        """→ SelectResult.  ``storages``: {id(scan_node): storage}.

        ``force_backend="device"`` routes every fragment device and
        surfaces device FAULTS raw (the parity-test contract); a
        fragment outside the device ENVELOPE (non-INT join key,
        REAL running sum, whole-mesh runner without co-location, …)
        still executes on its host twin — capability, not failure.
        ``force_backend="host"`` routes everything host."""
        from ..executors.interface import ExecSummary
        from ..executors.runner import SelectResult
        from ..utils import tracker
        frag = fragmentize(preq)
        with tracker.phase("plan_route"):
            self.router.route(frag, storages, force_backend)
        ctx = {"scanned": 0}    # per-request, never on self (threads)
        batch = self._exec(frag, storages, force_backend, ctx)
        if preq.output_offsets is not None:
            batch = ColumnBatch(
                [batch.schema[i] for i in preq.output_offsets],
                [batch.columns[i] for i in preq.output_offsets])
        with self._mu:
            self.plans_served += 1
        summary = ExecSummary(num_produced_rows=batch.num_rows,
                              num_iterations=1)
        return SelectResult(batch, [summary], []), ctx["scanned"]

    # -- recursion --

    def _exec(self, frag: Fragment, storages, force, ctx) -> ColumnBatch:
        from ..utils import tracker
        t0 = time.perf_counter()
        kind = _frag_kind(frag)
        # the wall is charged to the backend the router CHOSE, not
        # whatever the fragment degraded to: a persistently faulting
        # device route must inflate the DEVICE EWMA (its choice cost
        # includes the failed attempt + host fallback) so the model
        # steers away from it, never lock onto it
        chosen = frag.backend
        try:
            if isinstance(frag, LeafFragment):
                return self._exec_leaf(frag, storages, force, ctx)
            if isinstance(frag, HostOpsFragment):
                child = self._exec(frag.child, storages, force, ctx)
                return run_host_ops(child, frag.ops)
            if isinstance(frag, JoinFragment):
                return self._exec_join(frag, storages, force, ctx)
            if isinstance(frag, SortFragment):
                with tracker.phase("sort_fragment"):
                    return self._exec_sort(frag, storages, force, ctx)
            if isinstance(frag, WindowFragment):
                with tracker.phase("window_fragment"):
                    return self._exec_window(frag, storages, force, ctx)
            raise TypeError(frag)
        finally:
            self.router.note_wall(kind, chosen,
                                  time.perf_counter() - t0)

    def _exec_leaf(self, frag: LeafFragment, storages,
                   force, ctx) -> ColumnBatch:
        from ..executors.runner import BatchExecutorsRunner
        from ..utils import tracker
        dag = frag.dag()
        storage = storages[id(frag.scan_node)]
        est = getattr(storage, "estimated_rows", None)
        if callable(est):
            try:
                ctx["scanned"] += est()
            except Exception:   # noqa: BLE001 — accounting only
                pass
        if frag.backend == "device":
            runner = self._endpoint._device_runner
            try:
                batch = runner.handle_request(dag, storage).batch
                # the operators above a leaf read values: a DECIMAL SUM
                # the device handed on as its scaled plane, unscaled
                return ColumnBatch(batch.schema, [
                    c.unscaled() for c in batch.columns])
            except Exception:   # noqa: BLE001 — per-fragment degrade
                if force == "device":
                    raise
                tracker.label("degraded", "plan_leaf")
                frag.backend = "host"
        with tracker.phase("host_exec"):
            return BatchExecutorsRunner(dag, storage).handle_request().batch

    # -- join --

    def _exec_join(self, frag: JoinFragment, storages,
                   force, ctx) -> ColumnBatch:
        from ..utils import tracker
        node = frag.node
        if node.join_type != "inner":
            # reject loudly — silently inner-joining a left/semi plan
            # would return wrong rows with no error
            raise ValueError(
                f"unsupported join_type {node.join_type!r} "
                "(the IR serves inner equi-joins)")
        counted = False
        if frag.backend == "device":
            try:
                out = self._device_join(frag, storages, ctx)
                if out is not None:
                    self._note_join("device")
                    return out
            except Exception:   # noqa: BLE001 — per-fragment degrade:
                # a faulted device join (incl. device::join_dispatch)
                # falls back to the HOST join for this fragment only —
                # sibling fragments keep their device routes
                if force == "device":
                    raise
                tracker.label("degraded", "join")
                self._note_join("degrade")
                counted = True
            frag.backend = "host"
        if not counted:
            self._note_join("host")
        left = self._exec(frag.left, storages, force, ctx)
        right = self._exec(frag.right, storages, force, ctx)
        lc, rc = left.columns[node.left_key], right.columns[node.right_key]
        pi, bi = join_pairs_host(lc.values, lc.validity,
                                 rc.values, rc.validity)
        return concat_schemas(left.take(pi), right.take(bi))

    def _device_join(self, frag: JoinFragment, storages, ctx):
        """Late-materialized device join: row-index pairs computed on
        device (build side = dictionary-sorted key structure resident
        in HBM, probe fused with the probe side's selection
        predicates), host gathers only the demanded columns.  Returns
        None when the fragment shape is outside the device envelope
        (caller host-joins)."""
        node = frag.node
        if not isinstance(frag.left, LeafFragment) or \
                not isinstance(frag.right, LeafFragment):
            return None
        probe = frag.left.probe_shape()
        build = frag.right.probe_shape()
        if probe is None or build is None or build[1]:
            return None     # build side must be a bare scan
        probe_scan, probe_conds = probe
        build_scan, _ = build
        from ..device.join import join_supported
        if not join_supported(probe_scan.scan, probe_conds,
                              node.left_key, build_scan.scan,
                              node.right_key):
            # outside the device envelope: host-join BEFORE touching
            # the placer, so never-device-servable pairs don't earn
            # co-location affinity (and forced-device capability
            # misses degrade here rather than raise — only FAULTS
            # surface under force; see execute())
            return None
        lstor = storages[id(probe_scan)]
        rstor = storages[id(build_scan)]
        runner = self._endpoint._device_runner
        joiner, colocated = self._pick_joiner(runner, lstor, rstor)
        if joiner is None:
            return None
        if colocated is not None:
            with self._mu:
                if colocated:
                    self.colocation_hits += 1
                else:
                    self.colocation_misses += 1
        pairs = joiner.join(
            probe_scan.scan, probe_scan.ranges, lstor, probe_conds,
            node.left_key,
            build_scan.scan, build_scan.ranges, rstor, node.right_key)
        if pairs is None:
            return None
        pi, bi = pairs
        for s in (lstor, rstor):
            est = getattr(s, "estimated_rows", None)
            if callable(est):
                try:
                    ctx["scanned"] += est()
                except Exception:   # noqa: BLE001 — accounting only
                    pass
        # late materialization: gather ONLY now, only the k surviving
        # rows, from the host-resident columnar snapshots
        lbatch = lstor.gather_rows(probe_scan.scan, probe_scan.ranges, pi)
        rbatch = rstor.gather_rows(build_scan.scan, build_scan.ranges, bi)
        return concat_schemas(lbatch, rbatch)

    def _pick_joiner(self, runner, lstor, rstor):
        """→ (DeviceJoiner, colocated?) — the single-device runner the
        join executes on.  On a placed multi-chip node both feeds must
        sit on ONE slice (the SlicePlacer co-location hint feeds from
        here): the join then runs where the feeds live and mints zero
        cross-slice transfers.  ``colocated`` is None on single-chip
        nodes (trivially co-located, not a placement outcome)."""
        if runner is None or not hasattr(lstor, "scan_columns") or \
                not hasattr(rstor, "scan_columns"):
            return None, None
        placer = getattr(runner, "_placer", None)
        if placer is None:
            if not getattr(runner, "_single", False):
                # whole-mesh sharded runner without placement: the join
                # build structure is committed to one chip by
                # construction — host-join rather than fake a shard
                return None, None
            return runner.joiner(), None
        from ..device.feed import anchor as feed_anchor
        la = feed_anchor(lstor)
        ra = feed_anchor(rstor)
        placer.note_join(la, ra)
        lrun = placer.route(lstor)
        rrun = placer.route(rstor)
        if lrun is rrun and lrun is not placer._parent:
            return lrun.joiner(), True
        # not co-located (yet): the decayed pair affinity just recorded
        # steers the next placement; this request serves on the probe
        # side's slice with the build key column shipped there once
        if lrun is placer._parent:
            return None, False
        return lrun.joiner(), False

    # -- sort / window --

    def _exec_sort(self, frag: SortFragment, storages,
                   force, ctx) -> ColumnBatch:
        from ..utils import tracker
        child = self._exec(frag.child, storages, force, ctx)
        keys = eval_order_keys(child, frag.node.order_by)
        if not keys:
            return child        # keyless sort is the identity
        if frag.backend == "device":
            runner = self._sortwin_runner()
            if runner is not None:
                try:
                    perm = runner.joiner().sort_perm(keys,
                                                     child.num_rows)
                    return child.take(perm)
                except Exception:   # noqa: BLE001 — per-frag degrade
                    if force == "device":
                        raise
                    tracker.label("degraded", "sort")
            frag.backend = "host"
        return child.take(stable_perm(keys, child.num_rows))

    def _exec_window(self, frag: WindowFragment, storages,
                     force, ctx) -> ColumnBatch:
        from ..utils import tracker
        child = self._exec(frag.child, storages, force, ctx)
        if frag.backend == "device":
            runner = self._sortwin_runner()
            if runner is not None:
                try:
                    out = runner.joiner().window(child, frag.node)
                    if out is not None:
                        return out
                except Exception:   # noqa: BLE001 — per-frag degrade
                    if force == "device":
                        raise
                    tracker.label("degraded", "window")
            frag.backend = "host"
        return window_host(child, frag.node)

    def _sortwin_runner(self):
        """The single-device runner sort/window kernels may run on —
        the runner itself when single-chip, else None (whole-mesh
        sharded runners route these fragments host; placement nodes'
        joins run on slices, but a sort/window input is a batch with
        no anchor to place by)."""
        runner = self._endpoint._device_runner
        if runner is not None and getattr(runner, "_single", False):
            return runner
        return None
