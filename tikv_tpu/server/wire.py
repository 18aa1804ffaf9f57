"""Wire serialization for the RPC surface.

Reference: the kvproto/tipb protobufs.  The RPC layer here rides real
gRPC (HTTP/2) with msgpack-encoded message bodies — the schema mirrors
kvproto field-for-field so a protobuf codec can replace msgpack without
touching handlers (tracked deviation: binary wire compat with kvproto).
Raft messages and DAG plans reuse the framework's own binary codecs.
"""

from __future__ import annotations

from typing import Any, Optional

import msgpack

from ..raft.messages import (
    Entry,
    EntryType,
    Message,
    MsgType,
    Snapshot,
    SnapshotMetadata,
)
from ..raftstore.metapb import Peer, Region, RegionEpoch
from ..raftstore.peer_storage import decode_entry, encode_entry


# non-native datums (DECIMAL) share the row codec's ExtType scheme.
# Hoisted to module init: pack/unpack run once per RPC on the warm
# path, and the per-call ``from ..codec.row import ...`` paid a
# sys.modules lookup + attribute fetch + local bind on EVERY request
# (measured ~0.6µs/call on this box — 1.5× the 0.38µs unpackb of a
# small body itself; two calls per RPC ≈ 1.2µs of pure overhead)
from ..codec.row import msgpack_default, msgpack_ext_hook
# (``enc_cop_body`` runs once a Coprocessor reply: hoisted alike)
from ..utils import metrics as m
from ..utils import trace


def pack(obj: Any) -> bytes:
    return msgpack.packb(obj, use_bin_type=True, default=msgpack_default)


def unpack(raw: bytes) -> Any:
    return msgpack.unpackb(raw, raw=False, ext_hook=msgpack_ext_hook)


def pack_response(obj: Any) -> bytes:
    """Response serializer for handlers that may return PRE-PACKED
    bytes (the coprocessor fast path's zero-copy encoder writes the
    body straight into a reusable buffer) — bytes pass through, dicts
    take the normal ``pack``."""
    if type(obj) is bytes:
        return obj
    return pack(obj)


# -- batch_commands (service/kv.rs:921): ONE stream, two command forms --
#
# A message either way is ``{"requests": [command, ...]}`` /
# ``{"responses": [response, ...]}``, demultiplexed by ``request_id``.
# The RAW form carries the exact bytes the unary call of the method would
# have carried (``pack(req)``), so the store's fast path template-matches
# them as it does a unary body, and is answered with the bytes that call
# would have returned (``pack_response`` of what ``handle_raw`` gave); the
# DICT form carries the request decoded, and serves every other method.

# invocation metadata of the unary call that re-sends a command whose
# stream died under it (the store counts them: /health batch_commands)
MUX_RESEND_KEY = "tikv-mux-resend"

# The txn write RPCs: the store traces each as a read is traced, under
# rows of its own (service.py ``handle``), and a client on the store's
# machine stamps ``clock_ns.sent`` into the request (client.py
# ``StoreClient.call``).
TXN_WRITE_METHODS = frozenset({
    "KvPrewrite", "KvCommit", "KvBatchRollback", "KvCleanup",
    "KvCheckTxnStatus", "KvResolveLock", "KvPessimisticLock"})


def mux_batches(q, stop):
    """What either end sends: blocks for one item of the queue ``q``,
    takes whatever else is queued behind it → one list a message, until
    ``stop`` comes out of the queue."""
    import queue
    item = q.get()
    while item is not stop:
        batch = [item]
        try:
            while (item := q.get_nowait()) is not stop:
                batch.append(item)
        except queue.Empty:
            yield batch
            item = q.get()
        else:
            yield batch


def mux_command(request_id: int, method: str, req) -> dict:
    """``req``: bytes → the raw form; a dict → the dict form."""
    if type(req) is bytes:
        return {"request_id": request_id, "method": method, "raw": req}
    return {"request_id": request_id, "method": method, "req": req}


def mux_response(request_id: int, resp) -> dict:
    """The answer to a command, in its command's form."""
    if type(resp) is bytes:
        return {"request_id": request_id, "raw": resp}
    return {"request_id": request_id, "response": resp}


# -- metapb --

def enc_peer(p: Peer) -> dict:
    return {"id": p.id, "store_id": p.store_id, "learner": p.is_learner}


def dec_peer(d: Optional[dict]) -> Optional[Peer]:
    if d is None:
        return None
    return Peer(d["id"], d["store_id"], d.get("learner", False))


def enc_region(r: Region) -> dict:
    return {"id": r.id, "start": r.start_key, "end": r.end_key,
            "conf_ver": r.epoch.conf_ver, "version": r.epoch.version,
            "peers": [enc_peer(p) for p in r.peers]}


def dec_region(d: dict) -> Region:
    return Region(d["id"], d["start"], d["end"],
                  RegionEpoch(d["conf_ver"], d["version"]),
                  tuple(dec_peer(p) for p in d["peers"]))


def enc_region_ctx(r: Region) -> dict:
    """kvproto Context.region_id + region_epoch.version of a read cut
    for region ``r`` (a conf change moves no key, so conf_ver stays
    out, as upstream's epoch check leaves it out for reads)."""
    return {"region_id": r.id, "version": r.epoch.version}


def dec_region_ctx(d) -> tuple | None:
    return None if d is None else (d["region_id"], d["version"])


# -- raft messages (eraftpb analog) --

def enc_raft_msg(m: Message) -> dict:
    out = {"t": m.msg_type.value, "to": m.to, "frm": m.frm,
           "term": m.term, "lt": m.log_term, "i": m.index,
           "c": m.commit, "rej": m.reject, "hint": m.reject_hint,
           "ctx": m.ctx, "e": [encode_entry(e) for e in m.entries]}
    if m.snapshot is not None:
        meta = m.snapshot.metadata
        out["snap"] = {"i": meta.index, "t": meta.term,
                       "v": list(meta.voters), "l": list(meta.learners),
                       "vo": list(meta.voters_outgoing),
                       "d": m.snapshot.data}
    return out


def dec_raft_msg(d: dict) -> Message:
    snap = None
    if "snap" in d:
        s = d["snap"]
        snap = Snapshot(SnapshotMetadata(s["i"], s["t"], tuple(s["v"]),
                                         tuple(s["l"]),
                                         tuple(s.get("vo", ()))), s["d"])
    return Message(MsgType(d["t"]), to=d["to"], frm=d["frm"],
                   term=d["term"], log_term=d["lt"], index=d["i"],
                   entries=tuple(decode_entry(e) for e in d["e"]),
                   commit=d["c"], reject=d["rej"], reject_hint=d["hint"],
                   ctx=d.get("ctx"), snapshot=snap)


# -- errors (kvrpcpb errorpb analog: stable identities over the wire) --

def enc_error(e: Exception) -> dict:
    d = _enc_error_body(e)
    from ..utils.error_code import code_of
    d.setdefault("code", code_of(e))    # stable KV:Subsystem:Name code
    return d


def _enc_error_body(e: Exception) -> dict:
    from ..raftstore.metapb import EpochNotMatch, NotLeaderError
    from ..storage.mvcc.errors import (
        AlreadyExist, Committed, KeyIsLocked, TxnLockNotFound, WriteConflict,
    )
    if isinstance(e, KeyIsLocked):
        lk = e.lock
        return {"kind": "key_is_locked", "key": e.key,
                "lock": {"primary": lk.primary, "start_ts": lk.start_ts,
                         "ttl": lk.ttl,
                         "min_commit_ts": lk.min_commit_ts}}
    if isinstance(e, WriteConflict):
        return {"kind": "write_conflict", "key": e.key,
                "start_ts": e.start_ts,
                "conflict_start_ts": e.conflict_start_ts,
                "conflict_commit_ts": e.conflict_commit_ts,
                "reason": e.reason}
    if isinstance(e, TxnLockNotFound):
        return {"kind": "txn_lock_not_found", "key": e.key,
                "start_ts": e.start_ts}
    if isinstance(e, Committed):
        return {"kind": "committed", "key": e.key,
                "start_ts": e.start_ts, "commit_ts": e.commit_ts}
    if isinstance(e, AlreadyExist):
        return {"kind": "already_exist", "key": e.key}
    if isinstance(e, NotLeaderError):
        return {"kind": "not_leader", "region_id": e.region_id,
                "leader": enc_peer(e.leader) if e.leader else None}
    if isinstance(e, EpochNotMatch):
        return {"kind": "epoch_not_match",
                "current": enc_region(e.current)}
    from ..raftstore.metapb import RegionMerging, RegionNotFound
    if isinstance(e, RegionMerging):
        return {"kind": "region_merging", "region_id": e.region_id}
    if isinstance(e, RegionNotFound):
        # a balanced-away or merged region: the client must re-route
        return {"kind": "region_not_found", "region_id": e.region_id}
    from .read_pool import ServerIsBusy
    if isinstance(e, ServerIsBusy):
        out = {"kind": "server_is_busy", "reason": e.reason}
        if getattr(e, "retry_after_ms", 0):
            # queue-depth-derived backoff hint: clients sleep THIS
            # long instead of blind exponential jitter
            out["retry_after_ms"] = e.retry_after_ms
        if getattr(e, "resource_group", None):
            # RU-priced per-group shed (resource_control.py): the
            # client learns WHICH group is over budget, not just
            # "the store is busy"
            out["resource_group"] = e.resource_group
        return out
    from ..utils.deadline import DeadlineExceeded
    if isinstance(e, DeadlineExceeded):
        return {"kind": "deadline_exceeded", "stage": e.stage,
                "overrun_ms": round(e.overrun_ms, 3)}
    from ..raftstore.metapb import DataIsNotReady
    if isinstance(e, DataIsNotReady):
        return {"kind": "data_is_not_ready", "region_id": e.region_id,
                "safe_ts": e.safe_ts, "read_ts": e.read_ts}
    return {"kind": "other", "message": str(e)}


class RemoteError(Exception):
    """Client-side surfacing of a wire error dict."""

    def __init__(self, err: dict):
        super().__init__(f"{err.get('kind')}: {err}")
        self.err = err

    @property
    def kind(self) -> str:
        return self.err.get("kind", "other")


# -- coprocessor DAG plans (tipb analog) --

def enc_field_type(ft) -> dict:
    return {"tp": int(ft.tp), "flag": int(ft.flag), "flen": ft.flen,
            "decimal": ft.decimal, "collation": ft.collation,
            "elems": list(ft.elems)}


def dec_field_type(d: dict):
    from ..datatype.eval_type import FieldType, FieldTypeFlag, FieldTypeTp
    return FieldType(FieldTypeTp(d["tp"]), FieldTypeFlag(d["flag"]),
                     d["flen"], d["decimal"], d["collation"],
                     tuple(d["elems"]))


def enc_expr(e) -> dict:
    if e.kind == "const":
        return {"k": "c", "v": e.value,
                "et": e.eval_type.value if e.eval_type else None}
    if e.kind == "column":
        out = {"k": "col", "i": e.col_idx,
               "et": e.eval_type.value if e.eval_type else None}
        if e.collation != 63:
            out["coll"] = e.collation
        if e.elems:
            out["elems"] = list(e.elems)
        return out
    out = {"k": "f", "sig": e.sig,
           "ch": [enc_expr(c) for c in e.children]}
    if e.collation != 63:
        out["coll"] = e.collation
    if e.elems:
        out["elems"] = list(e.elems)
    return out


def dec_expr(d: dict):
    from ..datatype import EvalType
    from ..expr import Expr
    et = EvalType(d["et"]) if d.get("et") else None
    if d["k"] == "c":
        return Expr(kind="const", value=d["v"], eval_type=et)
    if d["k"] == "col":
        return Expr(kind="column", col_idx=d["i"], eval_type=et,
                    collation=d.get("coll", 63),
                    elems=tuple(d.get("elems", ())))
    return Expr.call(d["sig"], *(dec_expr(c) for c in d["ch"]),
                     collation=d.get("coll", 63),
                     elems=tuple(d.get("elems", ())))


def enc_dag(dag) -> dict:
    from ..copr.dag import (
        AggregationDesc, IndexScanDesc, LimitDesc, PartitionTopNDesc,
        ProjectionDesc, SelectionDesc, TableScanDesc, TopNDesc,
    )
    execs = []
    for ex in dag.executors:
        if isinstance(ex, TableScanDesc):
            execs.append({"k": "tscan", "table_id": ex.table_id,
                          "desc": ex.desc,
                          "cols": [{"id": c.col_id,
                                    "ft": enc_field_type(c.field_type),
                                    "pk": c.is_pk_handle}
                                   for c in ex.columns]})
        elif isinstance(ex, IndexScanDesc):
            execs.append({"k": "iscan", "table_id": ex.table_id,
                          "index_id": ex.index_id, "desc": ex.desc,
                          "unique": ex.unique,
                          "cols": [{"id": c.col_id,
                                    "ft": enc_field_type(c.field_type),
                                    "pk": c.is_pk_handle}
                                   for c in ex.columns]})
        elif isinstance(ex, SelectionDesc):
            execs.append({"k": "sel",
                          "conds": [enc_expr(e) for e in ex.conditions]})
        elif isinstance(ex, ProjectionDesc):
            execs.append({"k": "proj",
                          "exprs": [enc_expr(e) for e in ex.exprs]})
        elif isinstance(ex, AggregationDesc):
            execs.append({"k": "agg", "streamed": ex.streamed,
                          "group_by": [enc_expr(e) for e in ex.group_by],
                          "aggs": [{"kind": a.kind,
                                    "arg": enc_expr(a.arg)
                                    if a.arg is not None else None}
                                   for a in ex.aggs]})
        elif isinstance(ex, TopNDesc):
            execs.append({"k": "topn", "limit": ex.limit,
                          "order_by": [{"e": enc_expr(e), "desc": d}
                                       for e, d in ex.order_by]})
        elif isinstance(ex, PartitionTopNDesc):
            execs.append({"k": "ptopn", "limit": ex.limit,
                          "partition_by": [enc_expr(e)
                                           for e in ex.partition_by],
                          "order_by": [{"e": enc_expr(e), "desc": d}
                                       for e, d in ex.order_by]})
        elif isinstance(ex, LimitDesc):
            execs.append({"k": "limit", "limit": ex.limit})
        else:   # pragma: no cover
            raise ValueError(ex)
    return {"execs": execs,
            "ranges": enc_ranges(dag.ranges),
            "start_ts": dag.start_ts,
            "output_offsets": list(dag.output_offsets)
            if dag.output_offsets is not None else None,
            "encode_type": dag.encode_type}


def enc_ranges(ranges) -> list:
    return [{"s": r.start, "e": r.end} for r in ranges]


def dec_dag(d: dict):
    from ..copr.dag import (
        AggExprDesc, AggregationDesc, ColumnInfo, DAGRequest, IndexScanDesc,
        LimitDesc, PartitionTopNDesc, ProjectionDesc, SelectionDesc,
        TableScanDesc, TopNDesc,
    )
    from ..executors.ranges import KeyRange
    execs = []
    for ex in d["execs"]:
        k = ex["k"]
        if k in ("tscan", "iscan"):
            cols = tuple(ColumnInfo(c["id"], dec_field_type(c["ft"]),
                                    c["pk"]) for c in ex["cols"])
            if k == "tscan":
                execs.append(TableScanDesc(ex["table_id"], cols,
                                           ex["desc"]))
            else:
                execs.append(IndexScanDesc(ex["table_id"], ex["index_id"],
                                           cols, ex["desc"], ex["unique"]))
        elif k == "sel":
            execs.append(SelectionDesc(
                tuple(dec_expr(e) for e in ex["conds"])))
        elif k == "proj":
            execs.append(ProjectionDesc(
                tuple(dec_expr(e) for e in ex["exprs"])))
        elif k == "agg":
            execs.append(AggregationDesc(
                tuple(dec_expr(e) for e in ex["group_by"]),
                tuple(AggExprDesc(a["kind"],
                                  dec_expr(a["arg"])
                                  if a["arg"] is not None else None)
                      for a in ex["aggs"]),
                ex["streamed"]))
        elif k == "topn":
            execs.append(TopNDesc(
                tuple((dec_expr(o["e"]), o["desc"])
                      for o in ex["order_by"]), ex["limit"]))
        elif k == "ptopn":
            execs.append(PartitionTopNDesc(
                tuple(dec_expr(e) for e in ex["partition_by"]),
                tuple((dec_expr(o["e"]), o["desc"])
                      for o in ex["order_by"]), ex["limit"]))
        elif k == "limit":
            execs.append(LimitDesc(ex["limit"]))
    return DAGRequest(
        executors=tuple(execs),
        ranges=tuple(KeyRange(r["s"], r["e"]) for r in d["ranges"]),
        start_ts=d["start_ts"],
        output_offsets=tuple(d["output_offsets"])
        if d["output_offsets"] is not None else None,
        encode_type=d["encode_type"])


def enc_rows(rows) -> list:
    """Result rows → wire (floats/ints/bytes/None pass through msgpack)."""
    return [list(r) for r in rows]


# -- chunk replies (tipb EncodeType::TypeChunk's part: upstream's
#    tidb_query_datatype/src/codec/chunk/) --
#
# A request whose DAG says ``encode_type = "chunk"`` is answered with its
# result's columns as they lie, a buffer a column, where rows would be:
#
#     "chunk": {"n": rows, "cols": [{"t": "i8" | "u8" | "f8",
#                                    "v": <n little-endian values>,
#                                    "frac": s,      (a DECIMAL: value x 10^s)
#                                    "ok": <n bytes>}]}   (only where a NULL is)
#
# so neither end makes a Python value a cell: the store hands msgpack the
# planes' bytes, the client wraps them (``dec_chunk``).  A DECIMAL rides
# as the scaled int64 plane it was summed as (``Column.frac``), exactly.
# What a chunk cannot carry (an object plane: BYTES and JSON values, a
# DECIMAL's ``Decimal``s) leaves as rows, whatever was asked.

_CHUNK_KINDS = {"int64": "i8", "uint64": "u8", "float64": "f8"}


def enc_chunk(batch) -> Optional[dict]:
    """``batch`` (a ColumnBatch) as a chunk, or None where a column is
    not a plane a chunk carries."""
    cols = []
    for c in batch.columns:
        kind = _CHUNK_KINDS.get(c.values.dtype.name)
        if kind is None:
            return None
        col = {"t": kind,
               "v": c.values.astype("<" + kind, copy=False).tobytes()}
        if c.frac is not None:
            col["frac"] = c.frac
        if not c.validity.all():
            col["ok"] = c.validity.tobytes()
        cols.append(col)
    return {"n": batch.num_rows, "cols": cols}


def enc_cop_body(result, encode_type: str) -> Optional[dict]:
    """How a Coprocessor reply carries ``result`` (a SelectResult), for
    both serving legs: ``{"chunk": ...}`` where the request asked for a
    chunk and the result's planes make one, else None: rows, which each
    leg encodes its own way (``service._enc_cop_resp``,
    ``fastpath.encode_response``).  Counted once a reply (``/health``
    ``coprocessor.replies``); the chunk's making is the aggregate row
    ``chunk_encode``."""
    chunk = None
    if encode_type == "chunk":
        with trace.timed("chunk_encode"):
            chunk = enc_chunk(result.batch)
    if chunk is None:
        m.COPR_REPLY_COUNTER.labels("rows").inc()
        return None
    m.COPR_REPLY_COUNTER.labels("chunk").inc()
    m.COPR_CHUNK_ROWS.inc(chunk["n"])
    m.COPR_CHUNK_BYTES.inc(sum(
        len(col["v"]) + len(col.get("ok", b"")) for col in chunk["cols"]))
    return {"chunk": chunk}


def dec_chunk(chunk: dict) -> dict:
    """A received chunk with its buffers wrapped where they lie
    (``np.frombuffer``: no copy, read-only), in place: every column's
    ``v`` an array of its kind, ``ok`` a bool array where it is
    there."""
    import numpy as np
    for col in chunk["cols"]:
        col["v"] = np.frombuffer(col["v"], "<" + col["t"])
        if "ok" in col:
            col["ok"] = np.frombuffer(col["ok"], np.bool_)
    return chunk


def chunk_rows(chunk: dict) -> list:
    """A decoded chunk as the rows a ``rows`` reply would have carried:
    a DECIMAL column's values as ``Decimal``s of its scale, a NULL as
    None.  For tests and for callers who want values; a caller who
    wants throughput reads the planes."""
    import numpy as np

    from ..datatype.mydecimal import from_scaled
    cols = []
    for col in chunk["cols"]:
        vals = col["v"].tolist()
        frac = col.get("frac")
        if frac is not None:
            vals = [from_scaled(v, frac) for v in vals]
        if "ok" in col:
            for i in np.nonzero(~col["ok"])[0].tolist():
                vals[i] = None
        cols.append(vals)
    return [list(r) for r in zip(*cols)]


# -- plan IR (copr/plan_ir.py — the operator superset of tipb) --
#
# Leaf linear fragments reuse the exact tipb-shaped executor encoding
# above (enc_dag's vocabulary is embedded per ScanNode/op), so any
# plan a DAGRequest can express round-trips through either surface;
# join/sort/window nodes are the extension.

def enc_plan(preq) -> dict:
    from ..copr import plan_ir as pir

    def enc_scan_desc(scan) -> dict:
        if isinstance(scan, pir.IndexScanDesc):
            return {"k": "iscan", "table_id": scan.table_id,
                    "index_id": scan.index_id, "desc": scan.desc,
                    "unique": scan.unique,
                    "cols": [{"id": c.col_id,
                              "ft": enc_field_type(c.field_type),
                              "pk": c.is_pk_handle}
                             for c in scan.columns]}
        return {"k": "tscan", "table_id": scan.table_id,
                "desc": scan.desc,
                "cols": [{"id": c.col_id,
                          "ft": enc_field_type(c.field_type),
                          "pk": c.is_pk_handle}
                         for c in scan.columns]}

    def enc_node(n) -> dict:
        if isinstance(n, pir.ScanNode):
            return {"k": "scan", "scan": enc_scan_desc(n.scan),
                    "ranges": [{"s": r.start, "e": r.end}
                               for r in n.ranges]}
        if isinstance(n, pir.SelectNode):
            return {"k": "sel", "child": enc_node(n.child),
                    "conds": [enc_expr(e) for e in n.conditions]}
        if isinstance(n, pir.ProjectNode):
            return {"k": "proj", "child": enc_node(n.child),
                    "exprs": [enc_expr(e) for e in n.exprs]}
        if isinstance(n, pir.AggNode):
            d = n.desc
            return {"k": "agg", "child": enc_node(n.child),
                    "streamed": d.streamed,
                    "group_by": [enc_expr(e) for e in d.group_by],
                    "aggs": [{"kind": a.kind,
                              "arg": enc_expr(a.arg)
                              if a.arg is not None else None}
                             for a in d.aggs]}
        if isinstance(n, pir.TopNNode):
            return {"k": "topn", "child": enc_node(n.child),
                    "limit": n.desc.limit,
                    "order_by": [{"e": enc_expr(e), "desc": dsc}
                                 for e, dsc in n.desc.order_by]}
        if isinstance(n, pir.PartTopNNode):
            return {"k": "ptopn", "child": enc_node(n.child),
                    "limit": n.desc.limit,
                    "partition_by": [enc_expr(e)
                                     for e in n.desc.partition_by],
                    "order_by": [{"e": enc_expr(e), "desc": dsc}
                                 for e, dsc in n.desc.order_by]}
        if isinstance(n, pir.LimitNode):
            return {"k": "limit", "child": enc_node(n.child),
                    "limit": n.limit}
        if isinstance(n, pir.JoinNode):
            return {"k": "join", "left": enc_node(n.left),
                    "right": enc_node(n.right),
                    "left_key": n.left_key, "right_key": n.right_key,
                    "join_type": n.join_type}
        if isinstance(n, pir.SortNode):
            return {"k": "sort", "child": enc_node(n.child),
                    "order_by": [{"e": enc_expr(e), "desc": dsc}
                                 for e, dsc in n.order_by]}
        if isinstance(n, pir.WindowNode):
            return {"k": "window", "child": enc_node(n.child),
                    "partition_by": [enc_expr(e)
                                     for e in n.partition_by],
                    "order_by": [{"e": enc_expr(e), "desc": dsc}
                                 for e, dsc in n.order_by],
                    "funcs": [{"kind": f.kind,
                               "arg": enc_expr(f.arg)
                               if f.arg is not None else None,
                               "offset": f.offset}
                              for f in n.funcs]}
        raise ValueError(n)

    return {"root": enc_node(preq.root), "start_ts": preq.start_ts,
            "output_offsets": list(preq.output_offsets)
            if preq.output_offsets is not None else None,
            "encode_type": preq.encode_type}


def dec_plan(d: dict):
    from ..copr import plan_ir as pir
    from ..copr.dag import (
        AggExprDesc, AggregationDesc, ColumnInfo, IndexScanDesc,
        PartitionTopNDesc, TableScanDesc, TopNDesc,
    )
    from ..executors.ranges import KeyRange

    def dec_scan_desc(s):
        cols = tuple(ColumnInfo(c["id"], dec_field_type(c["ft"]),
                                c["pk"]) for c in s["cols"])
        if s["k"] == "iscan":
            return IndexScanDesc(s["table_id"], s["index_id"], cols,
                                 s["desc"], s["unique"])
        return TableScanDesc(s["table_id"], cols, s["desc"])

    def dec_node(nd):
        k = nd["k"]
        if k == "scan":
            return pir.ScanNode(
                dec_scan_desc(nd["scan"]),
                tuple(KeyRange(r["s"], r["e"]) for r in nd["ranges"]))
        if k == "sel":
            return pir.SelectNode(
                dec_node(nd["child"]),
                tuple(dec_expr(e) for e in nd["conds"]))
        if k == "proj":
            return pir.ProjectNode(
                dec_node(nd["child"]),
                tuple(dec_expr(e) for e in nd["exprs"]))
        if k == "agg":
            return pir.AggNode(dec_node(nd["child"]), AggregationDesc(
                tuple(dec_expr(e) for e in nd["group_by"]),
                tuple(AggExprDesc(a["kind"],
                                  dec_expr(a["arg"])
                                  if a["arg"] is not None else None)
                      for a in nd["aggs"]),
                nd["streamed"]))
        if k == "topn":
            return pir.TopNNode(dec_node(nd["child"]), TopNDesc(
                tuple((dec_expr(o["e"]), o["desc"])
                      for o in nd["order_by"]), nd["limit"]))
        if k == "ptopn":
            return pir.PartTopNNode(dec_node(nd["child"]),
                                    PartitionTopNDesc(
                tuple(dec_expr(e) for e in nd["partition_by"]),
                tuple((dec_expr(o["e"]), o["desc"])
                      for o in nd["order_by"]), nd["limit"]))
        if k == "limit":
            return pir.LimitNode(dec_node(nd["child"]), nd["limit"])
        if k == "join":
            return pir.JoinNode(dec_node(nd["left"]),
                                dec_node(nd["right"]),
                                nd["left_key"], nd["right_key"],
                                nd.get("join_type", "inner"))
        if k == "sort":
            return pir.SortNode(dec_node(nd["child"]), tuple(
                (dec_expr(o["e"]), o["desc"]) for o in nd["order_by"]))
        if k == "window":
            return pir.WindowNode(
                dec_node(nd["child"]),
                tuple(dec_expr(e) for e in nd["partition_by"]),
                tuple((dec_expr(o["e"]), o["desc"])
                      for o in nd["order_by"]),
                tuple(pir.WindowFuncDesc(
                    f["kind"],
                    dec_expr(f["arg"]) if f["arg"] is not None else None,
                    f.get("offset", 1)) for f in nd["funcs"]))
        raise ValueError(nd)

    return pir.PlanRequest(
        dec_node(d["root"]), start_ts=d["start_ts"],
        output_offsets=tuple(d["output_offsets"])
        if d["output_offsets"] is not None else None,
        encode_type=d["encode_type"])
