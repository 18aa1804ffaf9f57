"""Compiled per-class request fast path — raw wire bytes → coalescer.

The microsecond-warm-path tentpole (ROADMAP): after PRs 6-14 the warm
config-6 kernel is essentially free (device_dispatch ~0.6ms) and the
per-request cost is the Python host stack — msgpack body decode, DAG
decode, plan re-analysis, response re-serialization — paid identically
for every one of the thousands of repeat-shape requests a dashboard
fleet sends.  MonetDB/X100's rule (PAPERS.md) is to amortize
interpretation over repetition; here the repeated thing is the WIRE
SHAPE of the request, so interpretation (decode) is hoisted to the
first request of a class and every repeat pays only a byte-level
template match plus constant extraction.

Mechanism
---------

On the slow path the service learns a :class:`WireTemplate` per
compile class: the raw request bytes are re-encoded (by a msgpack
encoder that is byte-compatible with ``msgpack.packb(use_bin_type=
True)`` for the scalar/container subset requests use) into FIXED
SEGMENTS — the structural bytes — interleaved with SLOTS: the msgpack
encodings of the per-request scalars (predicate/aggregate constants,
``start_ts``, ``deadline_ms``, ``trace_id``).  The template is
self-validating: it is admitted only if re-rendering it with the
original slot values reproduces the original wire bytes exactly, so a
template can be WRONG only by never matching, never by mis-extracting.

A repeat request matches by walking its raw bytes: each fixed segment
must compare equal at its position and each slot must parse as one
msgpack scalar.  A full match means the request's *full decode* would
produce exactly the learned structure with the extracted slot values
substituted (msgpack decode is a pure function of the bytes), so the
fast path can skip ``wire.unpack`` + ``dec_dag`` + plan re-analysis
and jump straight to the coalescer with hoisted constants — parity by
construction.  ANY mismatch — different structure, a constant whose
device dtype bucket changed (a new compile class by definition), a
container where a scalar should be — falls back to the full decode
path: parity, never staleness.

Invalidation (fall back to full decode, re-learn):

==========================  =============================================
event                       mechanism
==========================  =============================================
wire shape change           fixed-segment byte mismatch
const dtype bucket change   per-slot ``const_bucket`` guard
region epoch bump / split   snapshot ``base_key`` embeds the epoch —
                            ``get_fast`` misses, entry invalidated
delta patch / rebuild       generation guard: the storage object served
                            must be the captured one (a bump serves the
                            CURRENT generation via the full ceremony and
                            invalidates the entry)
online config change        node bumps ``config_gen`` on every applied
                            online diff; entries pin the gen they learned
snapshot-generation bump    same storage-identity guard as delta patch
``copr::fastpath`` arms     force-miss / force-full-decode /
                            corrupt-fingerprint (chaos ``fastpath_fault``)
==========================  =============================================

The entry also pre-binds the per-class trace/metering template: the
compile-class key for the read pool's EWMA, the resource tag for RU
attribution and the response envelope — so a hit charges RU and seals
traces exactly as the slow path does without rebuilding any of it.

Three tiers (``_ClassEntry.tier``) scale how much ceremony a hit
skips: ``dispatch`` (device-cached TableScan — decode AND snapshot/
routing hoisted), ``decode`` (host-routed IndexScan — only the wire
decode hoisted, the full serving ceremony re-runs), and ``plan``
(plan-IR — decode + plan re-analysis hoisted onto one cached
PlanRequest with the TSO re-stamped; constants are class identity).
"""

from __future__ import annotations

import dataclasses
import struct
import threading
from collections import OrderedDict
from decimal import Decimal
from typing import Callable, Optional

from .. import native
from ..datatype import const_bucket
from ..utils.failpoint import fail_point
from ..utils.metrics import COPR_FASTPATH_COUNTER
from . import wire

# slot kinds
K_CONST = "const"            # int / float / DECIMAL predicate or
#                              aggregate constant (a DATE or DATETIME
#                              constant is its packed core, an int)
K_START_TS = "start_ts"      # dag.start_ts (per-request TSO)
K_DEADLINE = "deadline_ms"   # top-level remaining-budget field
K_TRACE_ID = "trace_id"      # client-propagated trace id


class _Slot:
    """Marker substituted into the wire structure where a per-request
    scalar lives; carries the match-time guard."""

    __slots__ = ("kind", "index", "vtype", "dtype")

    def __init__(self, kind: str, index: int = -1, vtype=None,
                 dtype: Optional[str] = None):
        self.kind = kind
        self.index = index          # const ordinal (DFS order)
        self.vtype = vtype          # exact python type required
        self.dtype = dtype          # device dtype bucket (consts)

    def guard(self, v) -> bool:
        # bool is an int subclass: an exact-type check keeps a flipped
        # True from masquerading as the learned integer constant
        if self.vtype is not None and type(v) is not self.vtype:
            return False
        if self.dtype is not None and const_bucket(v) != self.dtype:
            return False
        return True


# ---------------------------------------------------------------- codec
#
# A msgpack encoder byte-compatible with msgpack.packb(use_bin_type=
# True) for the subset request bodies use (None/bool/int/float64/str/
# bytes/list/tuple/dict), emitting FIXED SEGMENTS split at _Slot
# markers.  Byte compatibility is VERIFIED per template (render ==
# original raw) — a divergence makes the class ineligible, never wrong.

def _pack_int(v: int, out: bytearray) -> None:
    if v >= 0:
        if v < 0x80:
            out.append(v)
        elif v <= 0xFF:
            out += b"\xcc" + v.to_bytes(1, "big")
        elif v <= 0xFFFF:
            out += b"\xcd" + v.to_bytes(2, "big")
        elif v <= 0xFFFFFFFF:
            out += b"\xce" + v.to_bytes(4, "big")
        else:
            out += b"\xcf" + v.to_bytes(8, "big")
    else:
        if v >= -32:
            out.append(0x100 + v)
        elif v >= -0x80:
            out += b"\xd0" + v.to_bytes(1, "big", signed=True)
        elif v >= -0x8000:
            out += b"\xd1" + v.to_bytes(2, "big", signed=True)
        elif v >= -0x80000000:
            out += b"\xd2" + v.to_bytes(4, "big", signed=True)
        else:
            out += b"\xd3" + v.to_bytes(8, "big", signed=True)


def _map_header(n: int) -> bytes:
    if n < 16:
        return bytes((0x80 | n,))
    if n <= 0xFFFF:
        return b"\xde" + n.to_bytes(2, "big")
    return b"\xdf" + n.to_bytes(4, "big")


def _pack_scalar(v, out: bytearray) -> None:
    if v is None:
        out.append(0xC0)
    elif v is True:
        out.append(0xC3)
    elif v is False:
        out.append(0xC2)
    elif type(v) is int:
        _pack_int(v, out)
    elif type(v) is float:
        out += b"\xcb" + struct.pack(">d", v)
    elif type(v) is str:
        b = v.encode("utf-8")
        n = len(b)
        if n < 32:
            out.append(0xA0 | n)
        elif n <= 0xFF:
            out += b"\xd9" + n.to_bytes(1, "big")
        elif n <= 0xFFFF:
            out += b"\xda" + n.to_bytes(2, "big")
        else:
            out += b"\xdb" + n.to_bytes(4, "big")
        out += b
    elif type(v) is bytes:
        n = len(v)
        if n <= 0xFF:
            out += b"\xc4" + n.to_bytes(1, "big")
        elif n <= 0xFFFF:
            out += b"\xc5" + n.to_bytes(2, "big")
        else:
            out += b"\xc6" + n.to_bytes(4, "big")
        out += v
    elif type(v) is Decimal:
        # codec/row.py msgpack_default: ExtType(1, text), framed as
        # msgpack frames an ext (fixext for 1/2/4/8/16 bytes)
        text = format(v, "f").encode()
        n = len(text)
        fix = _FIXEXT.get(n)
        if fix is not None:
            out.append(fix)
        elif n <= 0xFF:
            out += b"\xc7" + n.to_bytes(1, "big")
        else:
            raise _Ineligible("oversized decimal constant")
        out.append(_EXT_DECIMAL)
        out += text
    else:
        raise _Ineligible(f"unsupported wire scalar {type(v).__name__}")


_EXT_DECIMAL = 1
_FIXEXT = {1: 0xD4, 2: 0xD5, 4: 0xD6, 8: 0xD7, 16: 0xD8}
_FIXEXT_LEN = {tag: n for n, tag in _FIXEXT.items()}


class _Ineligible(Exception):
    """This request's wire shape cannot be templated (non-canonical
    encoding, unsupported type) — the class stays on the slow path."""


def _encode_segments(obj) -> tuple:
    """→ (segments, slots): fixed byte chunks interleaved with the
    _Slot markers found in ``obj`` (segments[i] precedes slots[i];
    len(segments) == len(slots) + 1)."""
    segments: list = []
    slots: list = []
    cur = bytearray()

    def walk(o):
        nonlocal cur
        if isinstance(o, _Slot):
            segments.append(bytes(cur))
            cur = bytearray()
            slots.append(o)
            return
        if isinstance(o, (list, tuple)):
            n = len(o)
            if n < 16:
                cur.append(0x90 | n)
            elif n <= 0xFFFF:
                cur += b"\xdc" + n.to_bytes(2, "big")
            else:
                cur += b"\xdd" + n.to_bytes(4, "big")
            for x in o:
                walk(x)
        elif isinstance(o, dict):
            cur += _map_header(len(o))
            for k, v in o.items():
                walk(k)
                walk(v)
        else:
            _pack_scalar(o, cur)

    walk(obj)
    segments.append(bytes(cur))
    return segments, slots


def _parse_scalar(buf: bytes, off: int):
    """Parse ONE msgpack scalar at ``off`` → (value, next_off), or None
    when the bytes are not a scalar (container/ext) or truncated."""
    try:
        b = buf[off]
    except IndexError:
        return None
    if b < 0x80:                        # positive fixint
        return b, off + 1
    if b >= 0xE0:                       # negative fixint
        return b - 0x100, off + 1
    if 0xA0 <= b <= 0xBF:               # fixstr
        n = b & 0x1F
        end = off + 1 + n
        if end > len(buf):
            return None
        return buf[off + 1:end].decode("utf-8"), end
    if b == 0xC0:
        return None, off + 1
    if b == 0xC2:
        return False, off + 1
    if b == 0xC3:
        return True, off + 1
    if b == 0xCB:                       # float64
        end = off + 9
        if end > len(buf):
            return None
        return struct.unpack(">d", buf[off + 1:end])[0], end
    if 0xCC <= b <= 0xCF:               # uint8..64
        n = 1 << (b - 0xCC)
        end = off + 1 + n
        if end > len(buf):
            return None
        return int.from_bytes(buf[off + 1:end], "big"), end
    if 0xD0 <= b <= 0xD3:               # int8..64
        n = 1 << (b - 0xD0)
        end = off + 1 + n
        if end > len(buf):
            return None
        return int.from_bytes(buf[off + 1:end], "big", signed=True), end
    if 0xD9 <= b <= 0xDB:               # str8/16/32
        ln = 1 << (b - 0xD9)
        hend = off + 1 + ln
        if hend > len(buf):
            return None
        n = int.from_bytes(buf[off + 1:hend], "big")
        end = hend + n
        if end > len(buf):
            return None
        return buf[hend:end].decode("utf-8"), end
    if 0xC4 <= b <= 0xC6:               # bin8/16/32
        ln = 1 << (b - 0xC4)
        hend = off + 1 + ln
        if hend > len(buf):
            return None
        n = int.from_bytes(buf[off + 1:hend], "big")
        end = hend + n
        if end > len(buf):
            return None
        return buf[hend:end], end
    if b in _FIXEXT_LEN or b == 0xC7:   # a DECIMAL constant's ext
        if b == 0xC7:
            if off + 2 > len(buf):
                return None
            n, hend = buf[off + 1], off + 2
        else:
            n, hend = _FIXEXT_LEN[b], off + 1
        end = hend + 1 + n
        if end > len(buf) or buf[hend] != _EXT_DECIMAL:
            return None
        try:
            return wire.msgpack_ext_hook(
                _EXT_DECIMAL, bytes(buf[hend + 1:end])), end
        except Exception:   # noqa: BLE001 — not a decimal's text
            return None
    return None                         # container / other ext / reserved


class WireTemplate:
    """Learned byte structure of one request class."""

    __slots__ = ("segments", "slots", "size_floor")

    def __init__(self, segments, slots):
        self.segments = segments
        self.slots = slots
        self.size_floor = sum(len(s) for s in segments) + len(slots)

    def render(self, values) -> bytes:
        out = bytearray()
        for i, seg in enumerate(self.segments):
            if i:
                _pack_scalar(values[i - 1], out)
            out += seg
        return bytes(out)

    def match(self, raw: bytes):
        """→ slot values list, or None on any structural mismatch."""
        if len(raw) < self.size_floor:
            return None
        segs = self.segments
        slots = self.slots
        off = len(segs[0])
        if raw[:off] != segs[0]:
            return None
        values = []
        for i, slot in enumerate(slots):
            got = _parse_scalar(raw, off)
            if got is None:
                return None
            v, off = got
            if not slot.guard(v):
                return None
            values.append(v)
            seg = segs[i + 1]
            end = off + len(seg)
            if raw[off:end] != seg:
                return None
            off = end
        if off != len(raw):
            return None
        return values


# --------------------------------------------------------- wire walking

# request keys the fast path understands end to end; anything else in
# the body carries semantics the template cannot replay — ineligible
# (stale_read deliberately absent: the dispatch tier's snapshot has no
# resolved-ts gate, so follower stale reads always take the full path)
_ALLOWED_REQ_KEYS = frozenset((
    "tp", "dag", "force_backend", "paging_size", "resume_token",
    "resource_group", "request_source", "deadline_ms", "trace_id",
    # a fan-out task's region and epoch (wire.enc_region_ctx): fixed
    # bytes of its template, so a class is learned per region and epoch
    "context"))

# plan-IR request envelope: same eligibility rules, "plan" body
_ALLOWED_PLAN_KEYS = frozenset((
    "tp", "plan", "force_backend", "paging_size", "resume_token",
    "resource_group", "request_source", "deadline_ms", "trace_id"))


def _mark_slots(req: dict):
    """Deep-copy ``req`` with per-request scalars replaced by _Slot
    markers → (marked, n_consts).  Raises _Ineligible when the shape
    cannot be fast-pathed."""
    if not isinstance(req, dict):
        raise _Ineligible("non-dict request")
    if set(req) - _ALLOWED_REQ_KEYS:
        raise _Ineligible("unknown request fields")
    if req.get("tp", 103) != 103 or req.get("force_backend") is not None \
            or req.get("paging_size", 0) or \
            req.get("resume_token") is not None:
        raise _Ineligible("non-fast request options")
    dag = req.get("dag")
    if not isinstance(dag, dict):
        raise _Ineligible("no dag body")
    n_const = 0

    def mark_expr(e):
        nonlocal n_const
        if not isinstance(e, dict) or "k" not in e:
            raise _Ineligible("malformed expr")
        if e["k"] == "c":
            v = e.get("v")
            out = dict(e)
            # only numeric constants rotate within a compile class
            # (datatype.const_bucket: int, float, DECIMAL; class_key
            # buckets them alike); str/bytes/None constants are part of
            # the class identity — they stay fixed bytes, and changing
            # one is a structural miss
            bucket = const_bucket(v)
            if bucket is not None:
                out["v"] = _Slot(K_CONST, n_const, type(v), bucket)
                n_const += 1
            return out
        if e["k"] == "f":
            out = dict(e)
            out["ch"] = [mark_expr(c) for c in e.get("ch", ())]
            return out
        return e

    def mark_exec(ex):
        if not isinstance(ex, dict):
            raise _Ineligible("malformed exec")
        out = dict(ex)
        for key in ("conds", "exprs", "group_by", "partition_by"):
            if key in out:
                out[key] = [mark_expr(e) for e in out[key]]
        if "aggs" in out:
            out["aggs"] = [
                {**a, "arg": mark_expr(a["arg"])
                 if a.get("arg") is not None else None}
                for a in out["aggs"]]
        if "order_by" in out:
            out["order_by"] = [{**o, "e": mark_expr(o["e"])}
                               for o in out["order_by"]]
        return out

    marked = dict(req)
    mdag = dict(dag)
    if "execs" in mdag:
        mdag["execs"] = [mark_exec(ex) for ex in mdag["execs"]]
    if "start_ts" not in mdag or type(mdag["start_ts"]) is not int:
        raise _Ineligible("no start_ts")
    mdag["start_ts"] = _Slot(K_START_TS, vtype=int)
    marked["dag"] = mdag
    if "deadline_ms" in marked:
        if type(marked["deadline_ms"]) is not int:
            raise _Ineligible("non-int deadline")
        marked["deadline_ms"] = _Slot(K_DEADLINE, vtype=int)
    if "trace_id" in marked:
        if type(marked["trace_id"]) is not str:
            raise _Ineligible("non-str trace id")
        marked["trace_id"] = _Slot(K_TRACE_ID, vtype=str)
    return marked, n_const


def _mark_slots_plan(req: dict):
    """Plan-IR variant of ``_mark_slots``: only the envelope scalars
    rotate (``start_ts``, ``deadline_ms``, ``trace_id``) — every plan
    constant stays FIXED BYTES, i.e. part of the class identity (a
    changed constant is a structural miss that learns a sibling
    class), so the hit path reuses ONE decoded PlanRequest with the
    TSO re-stamped instead of re-walking the nested node tree."""
    if not isinstance(req, dict):
        raise _Ineligible("non-dict request")
    if set(req) - _ALLOWED_PLAN_KEYS:
        raise _Ineligible("unknown request fields")
    if req.get("tp", 103) != 103 or req.get("force_backend") is not None \
            or req.get("paging_size", 0) or \
            req.get("resume_token") is not None:
        raise _Ineligible("non-fast request options")
    plan = req.get("plan")
    if not isinstance(plan, dict):
        raise _Ineligible("no plan body")
    if "start_ts" not in plan or type(plan["start_ts"]) is not int:
        raise _Ineligible("no start_ts")
    marked = dict(req)
    mplan = dict(plan)
    mplan["start_ts"] = _Slot(K_START_TS, vtype=int)
    marked["plan"] = mplan
    if "deadline_ms" in marked:
        if type(marked["deadline_ms"]) is not int:
            raise _Ineligible("non-int deadline")
        marked["deadline_ms"] = _Slot(K_DEADLINE, vtype=int)
    if "trace_id" in marked:
        if type(marked["trace_id"]) is not str:
            raise _Ineligible("non-str trace id")
        marked["trace_id"] = _Slot(K_TRACE_ID, vtype=str)
    return marked, 0


def _slot_originals(slots, req: dict, body: str) -> list:
    """The learned request's own slot values, in template order — the
    input of the byte-exact render round-trip self-validation."""
    orig = []
    for s in slots:
        if s.kind == K_CONST:
            orig.append(_const_at(req["dag"], s.index))
        elif s.kind == K_START_TS:
            orig.append(req[body]["start_ts"])
        elif s.kind == K_DEADLINE:
            orig.append(req["deadline_ms"])
        else:
            orig.append(req["trace_id"])
    return orig


def _dag_const_substituter(dag) -> Callable:
    """Precompiled per-class DAG constructor: → make_dag(consts,
    start_ts) rebuilding only the executor subtrees that hold rotating
    constants (everything else — columns, ranges, offsets — is shared
    with the learned template object).  Which nodes those are is a
    property of the class and is decided here, once: each expression
    compiles to itself (shared) or to a builder of its path down to
    the constants, so a hit asks ``const_bucket`` nothing.

    The substitution order is the same DFS the wire walk uses
    (executors in order, conditions/exprs/aggs/order keys in the
    enc_dag field order), and learn() verifies it by equality against
    the slow path's decoded DAG."""
    from ..copr.dag import (
        AggExprDesc, AggregationDesc, DAGRequest, PartitionTopNDesc,
        ProjectionDesc, SelectionDesc, TopNDesc,
    )
    from ..expr import Expr

    def expr(e):
        """``e`` itself, or build(it) → ``e`` with its rotating
        constants drawn from ``it``."""
        if e.kind == "const":
            if const_bucket(e.value) is None:
                return e
            et = e.eval_type
            return lambda it: Expr(kind="const", value=next(it),
                                   eval_type=et)
        subs = exprs(e.children)
        if not callable(subs):
            return e
        rest = {f.name: getattr(e, f.name) for f in dataclasses.fields(e)
                if f.name != "children"}
        return lambda it: Expr(children=subs(it), **rest)

    def exprs(es):
        """The tuple ``es`` itself, or build(it) → its members rebuilt."""
        subs = tuple(expr(e) for e in es)
        if not any(callable(s) for s in subs):
            return es
        return lambda it: tuple(
            s(it) if callable(s) else s for s in subs)

    def fill(sub, it):
        return sub(it) if callable(sub) else sub

    def ordered(order_by):
        subs = exprs([e for e, _ in order_by])
        if not callable(subs):
            return order_by
        descs = tuple(d for _, d in order_by)
        return lambda it: tuple(zip(subs(it), descs))

    builders = []
    for ex in dag.executors:
        built = ex                      # shared verbatim
        if isinstance(ex, SelectionDesc):
            conds = exprs(ex.conditions)
            if callable(conds):
                built = lambda it, c=conds: SelectionDesc(c(it))
        elif isinstance(ex, ProjectionDesc):
            out = exprs(ex.exprs)
            if callable(out):
                built = lambda it, c=out: ProjectionDesc(c(it))
        elif isinstance(ex, AggregationDesc):
            group = exprs(ex.group_by)
            args = [None if a.arg is None else expr(a.arg)
                    for a in ex.aggs]
            if callable(group) or any(callable(a) for a in args):
                aggs = tuple(zip(ex.aggs, args))
                built = lambda it, ex=ex, g=group, aggs=aggs: \
                    AggregationDesc(
                        fill(g, it),
                        tuple(AggExprDesc(a.kind, s(it)) if callable(s)
                              else a for a, s in aggs), ex.streamed)
        elif isinstance(ex, TopNDesc):
            order = ordered(ex.order_by)
            if callable(order):
                built = lambda it, ex=ex, o=order: TopNDesc(o(it), ex.limit)
        elif isinstance(ex, PartitionTopNDesc):
            part, order = exprs(ex.partition_by), ordered(ex.order_by)
            if callable(part) or callable(order):
                built = lambda it, ex=ex, p=part, o=order: \
                    PartitionTopNDesc(fill(p, it), fill(o, it), ex.limit)
        builders.append(built)

    ranges, offsets, enc = dag.ranges, dag.output_offsets, dag.encode_type

    def make(consts, start_ts: int) -> DAGRequest:
        it = iter(consts)
        return DAGRequest(
            executors=tuple(b if not callable(b) else b(it)
                            for b in builders),
            ranges=ranges, start_ts=start_ts,
            output_offsets=offsets, encode_type=enc)

    return make


def _key_template(key: tuple):
    """Compile a plan_key/share-batch-key tuple into a substituter that
    re-stamps the const VALUE leaves — ``("c", value, et)`` triples —
    in DFS order, mirroring the wire slot order.  → (fill(consts) →
    tuple, n_consts)."""
    count = 0

    def compile_node(t):
        nonlocal count
        if isinstance(t, tuple):
            if len(t) == 3 and t[0] == "c" and \
                    const_bucket(t[1]) is not None:
                count += 1
                et = t[2]
                return lambda it, et=et: ("c", next(it), et)
            subs = [compile_node(x) for x in t]
            if all(not callable(s) for s in subs):
                return t
            return lambda it, subs=tuple(subs): tuple(
                s if not callable(s) else s(it) for s in subs)
        return t

    node = compile_node(key)

    def fill(consts):
        if not callable(node):
            return key
        return node(iter(consts))

    return fill, count


# ----------------------------------------------------------- the cache

class _ClassEntry:
    """One learned request class: template + everything the hit path
    needs pre-bound.

    ``tier`` names how much of the ceremony a hit skips:

    - ``dispatch`` — the original full fast path (device-cached
      TableScan): skip decode AND snapshot/routing, jump straight to
      the coalescer against the captured storage generation;
    - ``decode`` — decode-only (host-routed IndexScan classes): skip
      ``wire.unpack`` + ``dec_dag``, then run the FULL serving
      ceremony (snapshot, routing, freshness) with the pre-built DAG,
      so correctness never depends on the cached entry;
    - ``plan`` — plan-IR classes: skip ``wire.unpack`` + ``dec_plan``
      + plan re-analysis, re-stamp the TSO on one decoded
      PlanRequest, then ``handle_plan`` runs its normal ceremony.
    """

    __slots__ = (
        "template", "make_dag", "make_plan", "tier", "class_key",
        "trace_class", "range_start", "resource_group",
        "request_source", "tag", "key_hint", "ranges", "base_key",
        "storage_ref", "config_gen", "bkey", "share_fill", "n_est",
        "d2h_bytes", "hits", "last_hit", "invalidated", "region_ctx")

    def __init__(self):
        self.hits = 0
        self.last_hit = 0           # the cache's tick at its last find
        self.invalidated = None     # reason str once dead
        self.tier = "dispatch"
        self.make_plan = None
        # the learned request's region context: a hit that falls back
        # to the full ceremony is still held to it (node._copr_snapshot)
        self.region_ctx = None

    def storage(self):
        ref = self.storage_ref
        return ref() if ref is not None else None


def _count(outcome: str, reason: str) -> None:
    COPR_FASTPATH_COUNTER.labels(outcome, reason).inc()


class FastPathCache:
    """Bounded per-class template cache (one per node).

    ``find(raw)`` → (entry, values) on a byte-level hit; ``learn()``
    admits a class from a slow-path execution.  Every TableScan request
    shares its first ~26 wire bytes and a selection's rotating constants
    come early, so no byte PREFIX tells classes apart; the END of the
    request does: a template's last fixed segment holds whatever follows
    its last slot, which for a fan-out task is its ``context`` (region
    and epoch, the request's last key).  Entries are indexed by that
    segment, and ``find`` tries only the classes whose segment ends
    ``raw``, the most recently hit first: one dict probe a distinct
    segment length (one or two), then one whole ``WireTemplate.match``
    (every fixed segment compared, every slot parsed and guarded) a
    candidate, which is what a find costs: the match of its own class,
    ~20 us on a five-constant DECIMAL selection, however many classes
    the cache holds.  Classes with one last segment (same region,
    other plans or tenants) are walked among themselves.  The index
    chooses who is tried, never what counts as a hit.  ``stats()``
    ``find`` counts both: ``probes`` (matches tried) over ``finds``."""

    def __init__(self, capacity: int = 64):
        self.capacity = max(0, int(capacity))
        self._mu = threading.Lock()
        self._entries: list = []
        # the index: (last fixed segment → its classes, the distinct
        # lengths of those segments), replaced whole under ``_mu``
        # (``_reindex``), so ``find`` reads one pair unlocked
        self._index: tuple = ({}, ())
        self._tick = 0                  # recency clock: ``ent.last_hit``
        # negative cache: compile classes whose learn attempt was
        # rejected (non-canonical client encoding, unsupported shape)
        # — without it every request of such a class would repay the
        # whole template-construction pipeline, i.e. MORE than the
        # decode overhead this cache exists to remove
        self._learn_rejects: "OrderedDict" = OrderedDict()
        self.config_gen = 0
        # counters (under _mu): outcome -> count
        self.hit = 0
        self.miss = 0
        self.bypass = 0
        self.invalidate = 0
        self.fallback = 0
        self.learned = 0
        self.reasons: dict = {}
        # lookups, and the ``WireTemplate.match`` calls they made
        self.finds = 0
        self.probes = 0
        # hits whose DAG left with its class's keys (``make_dag``), and
        # the keys such a DAG had walked off its expression tree anyway
        self.keys_carried = 0
        self.keys_walked = 0
        # replies encoded (encode_response), by what made the rows: the
        # one native call over the result's planes, or the Python chain
        self.encode_native = 0
        self.encode_python = 0

    @property
    def enabled(self) -> bool:
        return self.capacity > 0

    def _note(self, outcome: str, reason: str, carried: int = 0) -> None:
        with self._mu:
            setattr(self, outcome, getattr(self, outcome) + 1)
            k = f"{outcome}:{reason}"
            self.reasons[k] = self.reasons.get(k, 0) + 1
            self.keys_carried += carried
        _count(outcome, reason)

    # ------------------------------------------------------------ lookup

    def find(self, raw: bytes):
        """→ (entry, slot values) or (None, reason)."""
        fp = fail_point("copr::fastpath")
        if fp is not None:
            # force-miss / force-full-decode arms take the full decode
            # path outright; the corrupt-fingerprint arm flips a byte
            # in a cached template FIRST — the match below must then
            # miss (never mis-extract) and the class re-learns
            arm = getattr(fp, "value", None) or "miss"
            if arm == "corrupt":
                self._corrupt_one()
            self._note("bypass", f"failpoint_{arm}")
            return None, "failpoint"
        if not self.enabled:
            return None, "disabled"
        (by_tail, lens), gen, size = self._index, self.config_gen, len(raw)
        cands = ()
        for n in lens:
            got = by_tail.get(raw[size - n:]) if n <= size else None
            if got:
                cands = cands + got if cands else got
        if len(cands) > 1:
            cands = sorted(cands, key=lambda e: -e.last_hit)
        probes, ent, values = 0, None, None
        for cand in cands:
            if cand.invalidated is not None:
                continue
            if cand.config_gen != gen:
                self.drop(cand, "config")
                continue
            probes += 1
            values = cand.template.match(raw)
            if values is not None:
                ent = cand
                break
        with self._mu:
            self.finds += 1
            self.probes += probes
            if ent is not None:
                # the capacity bound evicts the COLDEST
                self._tick += 1
                ent.last_hit = self._tick
                return ent, values
        self._note("miss", "no_template" if not cands else "mismatch")
        return None, "mismatch"

    def _reindex(self) -> None:
        """(under ``_mu``)"""
        by_tail: dict = {}
        for e in self._entries:
            tail = e.template.segments[-1]
            by_tail[tail] = by_tail.get(tail, ()) + (e,)
        self._index = by_tail, tuple({len(t) for t in by_tail})

    def _corrupt_one(self) -> None:
        with self._mu:
            ent = max(self._entries, key=lambda e: e.last_hit,
                      default=None)
        if ent is None:
            return
        segs = ent.template.segments
        for i, s in enumerate(segs):
            if s:
                segs[i] = bytes([s[0] ^ 0xFF]) + s[1:]
                break

    # ------------------------------------------------------------- learn

    def learn(self, raw: bytes, req: dict, info: dict) -> bool:
        """Admit one class from a completed slow-path execution.

        ``req`` is a FRESH unpack of ``raw`` (the executed dict was
        mutated by the handlers); ``info`` carries what the execution
        learned: dag, class_key, storage, decision, batch key, tag
        inputs.  → True when a template was admitted."""
        if not self.enabled:
            return False
        dag = info.get("dag")
        storage = info.get("storage")
        reject_key = info.get("class_key")
        with self._mu:
            if reject_key is not None and \
                    self._learn_rejects.get(reject_key) == \
                    self.config_gen:
                # permanently-ineligible class at this config gen:
                # skip the construction pipeline entirely
                return False
        if info.get("plan") is not None:
            return self._learn_plan(raw, req, info, reject_key)
        if dag is None:
            self._note("bypass", "no_learn_info")
            return False
        if storage is None or info.get("backend") != "device" or \
                info.get("decision") not in ("device_batched",
                                             "device_solo"):
            # no device-cached storage to pin a dispatch entry to —
            # but an IndexScan class still repays hoisting the decode:
            # admit a DECODE-tier template (the hit skips wire.unpack
            # + dec_dag, the full ceremony still runs per request)
            from ..copr.dag import IndexScanDesc
            if dag.executors and \
                    isinstance(dag.executors[0], IndexScanDesc):
                return self._learn_decode(raw, req, info, reject_key)
            self._note("bypass", f"route_{info.get('decision') or 'host'}")
            return False
        lineage = getattr(storage, "feed_lineage", None)
        if lineage is None or not hasattr(storage, "scan_columns"):
            self._note("bypass", "uncached_storage")
            self._reject(reject_key)
            return False
        try:
            template, make_dag, plan_key = self._compile(raw, req, dag)
        except Exception as e:   # noqa: BLE001 — ineligible, never fatal
            self._ineligible(e, reject_key)
            return False

        ent = _ClassEntry()
        ent.template = template
        ent.make_dag = make_dag
        ent.class_key = info.get("class_key") or ("copr", dag.class_key())
        ent.trace_class = ent.class_key
        ent.range_start = dag.ranges[0].start if dag.ranges else None
        ent.resource_group = req.get("resource_group", "default")
        ent.request_source = req.get("request_source", "")
        from ..resource_metering import ResourceTagFactory
        ent.tag = ResourceTagFactory.tag(ent.resource_group or "default",
                                         ent.request_source or "")
        from .node import encode_first
        ent.key_hint = encode_first(ent.range_start or b"")
        ent.ranges = dag.ranges
        ent.region_ctx = wire.dec_region_ctx(req.get("context"))
        scan = dag.executors[0]
        region = info.get("region")
        epoch_ver = info.get("epoch_version")
        if region is None or epoch_ver is None:
            self._note("bypass", "no_region")
            return False
        ent.base_key = (region, epoch_ver, scan.table_id,
                        tuple((c.col_id, c.is_pk_handle, c.field_type.tp)
                              for c in scan.columns))
        import weakref
        ent.storage_ref = weakref.ref(storage)
        ent.config_gen = self.config_gen
        bkey = info.get("bkey")
        ent.bkey = bkey
        ent.share_fill = None
        head = bkey[0] if bkey else None
        nested = bkey[2] if head == "slice" and len(bkey) > 2 else None
        if "share" in (head, nested):
            # ("share", ...) / slice-share keys embed the const-
            # SENSITIVE plan_key, three places behind "share": a hit
            # puts the one its DAG carries there (``make_dag`` filled
            # it), so the key is filled once and the tree never walked
            at = bkey.index("share") + 3
            if len(bkey) <= at or bkey[at] != plan_key:
                # not the key this class fills — never guess
                self._note("bypass", "share_key_shape")
                self._reject(reject_key)
                return False
            ent.share_fill = lambda key, head=bkey[:at], \
                tail=bkey[at + 1:]: head + (key,) + tail
        elif bkey is not None and "stack" not in (head, nested):
            # unknown key shape: reusing it verbatim could group
            # mismatched kernels — stay on the full decode path
            self._note("bypass", "batch_key_shape")
            self._reject(reject_key)
            return False
        ent.n_est = info.get("n_est")
        ent.d2h_bytes = info.get("d2h_bytes", 0.0)
        self._admit(ent)
        return True

    def _compile(self, raw: bytes, req: dict, dag) -> tuple:
        """What a DAG class fixes, computed once → (template, make_dag,
        plan_key), or ``_Ineligible`` by a self-validation."""
        marked, n_const = _mark_slots(req)
        segments, slots = _encode_segments(marked)
        template = WireTemplate(segments, slots)
        # self-validation 1: byte-exact render round trip — the
        # template's encoder agrees with the client's msgpack for
        # THIS shape, or the class never fast-paths
        orig = _slot_originals(slots, req, "dag")
        if template.render(orig) != raw:
            raise _Ineligible("render mismatch")
        build = _dag_const_substituter(dag)
        # self-validation 2: the constructor rebuilds the decoded
        # DAG exactly from the wire-extracted values
        consts = [v for s, v in zip(slots, orig) if s.kind == K_CONST]
        if build(consts, dag.start_ts) != dag:
            raise _Ineligible("constructor mismatch")
        # the keys a hit's DAG arrives with: the class key as learned
        # (const-blind, and a hit's constants passed their buckets'
        # guards), the plan key re-stamped with the hit's constants
        walked = dataclasses.replace(dag)       # no memo: walked afresh
        class_key, plan_key = walked.class_key(), walked.plan_key()
        fill, n = _key_template(plan_key)
        note_walk = self._note_key_walk

        def make_dag(consts, start_ts: int):
            return build(consts, start_ts).carry_keys(
                class_key, fill(consts), note_walk)

        # self-validation 3: the carried keys are the walked ones
        made = make_dag(consts, dag.start_ts)
        if n != n_const or made.class_key() != class_key or \
                made.plan_key() != plan_key:
            raise _Ineligible("key mismatch")
        return template, make_dag, plan_key

    def _ineligible(self, e: Exception, reject_key) -> None:
        reason = e.args[0] if isinstance(e, _Ineligible) and e.args \
            else "learn_error"
        self._note("bypass", str(reason)[:40])
        self._reject(reject_key)

    def _note_key_walk(self) -> None:
        with self._mu:
            self.keys_walked += 1

    def _trim(self) -> None:
        """(under ``_mu``) hold the capacity bound, the coldest out."""
        if len(self._entries) > self.capacity:
            self._entries.sort(key=lambda e: -e.last_hit)
            del self._entries[self.capacity:]
        self._reindex()

    def _admit(self, ent: _ClassEntry) -> None:
        with self._mu:
            # retire dead entries and any template this one SUPERSEDES
            # — same TEMPLATE IDENTITY (fixed segments + slot kinds: it
            # would match exactly the same raw bytes, so only the new
            # one — the current generation — can ever win).  Identity
            # deliberately NOT class_key: one const-blind class over
            # two regions/tenants is two distinct templates that must
            # coexist, not mutually evict.
            kinds = [s.kind for s in ent.template.slots]
            self._entries = [
                e for e in self._entries
                if e.invalidated is None and not (
                    e.template.segments == ent.template.segments and
                    [s.kind for s in e.template.slots] == kinds)]
            self._tick += 1
            ent.last_hit = self._tick
            self._entries.append(ent)
            self._trim()
            self.learned += 1
        _count("learn", "ok")

    def _learn_common(self, ent: _ClassEntry, req: dict) -> None:
        """Envelope fields every tier pre-binds identically."""
        ent.resource_group = req.get("resource_group", "default")
        ent.request_source = req.get("request_source", "")
        from ..resource_metering import ResourceTagFactory
        ent.tag = ResourceTagFactory.tag(ent.resource_group or "default",
                                         ent.request_source or "")
        ent.key_hint = None
        ent.base_key = None
        ent.storage_ref = None
        ent.config_gen = self.config_gen
        ent.bkey = None
        ent.share_fill = None
        ent.n_est = None
        ent.d2h_bytes = 0.0

    def _learn_decode(self, raw: bytes, req: dict, info: dict,
                      reject_key) -> bool:
        """Admit a DECODE-tier class (host-routed IndexScan): the same
        two self-validations as the dispatch tier — byte-exact render
        round trip, constructor-rebuilds-the-decoded-DAG — but nothing
        snapshot-bound is captured, because the hit replays the full
        serving ceremony with only the wire decode hoisted."""
        dag = info["dag"]
        try:
            template, make_dag, _ = self._compile(raw, req, dag)
        except Exception as e:   # noqa: BLE001 — ineligible, never fatal
            self._ineligible(e, reject_key)
            return False
        ent = _ClassEntry()
        ent.tier = "decode"
        ent.template = template
        ent.make_dag = make_dag
        ent.class_key = info.get("class_key") or ("copr", dag.class_key())
        ent.trace_class = ent.class_key
        ent.range_start = dag.ranges[0].start if dag.ranges else None
        ent.ranges = dag.ranges
        ent.region_ctx = wire.dec_region_ctx(req.get("context"))
        self._learn_common(ent, req)
        self._admit(ent)
        return True

    def _learn_plan(self, raw: bytes, req: dict, info: dict,
                    reject_key) -> bool:
        """Admit a PLAN-tier class: one decoded PlanRequest is cached
        per wire shape (constants are class identity — only the TSO
        envelope rotates), so a repeat skips ``wire.unpack`` +
        ``dec_plan`` and jumps to ``handle_plan``, which runs its
        normal per-leaf snapshot + fragment-routing ceremony."""
        preq = info["plan"]
        try:
            marked, _ = _mark_slots_plan(req)
            segments, slots = _encode_segments(marked)
            template = WireTemplate(segments, slots)
            orig = _slot_originals(slots, req, "plan")
            if template.render(orig) != raw:
                raise _Ineligible("render mismatch")
            def make_plan(start_ts: int, preq=preq):
                return dataclasses.replace(preq, start_ts=start_ts)

            # self-validation: re-stamping the learned TSO reproduces
            # the decoded request exactly
            if make_plan(preq.start_ts) != preq:
                raise _Ineligible("constructor mismatch")
        except Exception as e:   # noqa: BLE001 — ineligible, never fatal
            self._ineligible(e, reject_key)
            return False
        ent = _ClassEntry()
        ent.tier = "plan"
        ent.template = template
        ent.make_dag = None
        ent.make_plan = make_plan
        ent.class_key = info.get("class_key") or \
            ("copr_plan", preq.class_key())
        ent.trace_class = ent.class_key
        leaves = preq.scan_leaves()
        ent.range_start = leaves[0].ranges[0].start \
            if leaves and leaves[0].ranges else None
        ent.ranges = tuple(r for lf in leaves for r in lf.ranges)
        self._learn_common(ent, req)
        self._admit(ent)
        return True

    # ------------------------------------------------------ invalidation

    def _reject(self, key) -> None:
        """Negative-cache one compile class's learn rejection for the
        CURRENT config generation (a config change retries it once)."""
        if key is None:
            return
        with self._mu:
            self._learn_rejects[key] = self.config_gen
            while len(self._learn_rejects) > 256:
                self._learn_rejects.popitem(last=False)

    def drop(self, ent: _ClassEntry, reason: str) -> None:
        if ent.invalidated is None:
            ent.invalidated = reason
            self._note("invalidate", reason)

    def bump_config_gen(self) -> None:
        """Any applied online-config diff retires every learned entry:
        a changed threshold/window/knob may change routing or keying,
        and re-learning one slow request per class is cheap."""
        with self._mu:
            self.config_gen += 1

    def note_fallback(self, reason: str) -> None:
        self._note("fallback", reason)

    def note_hit(self, ent: _ClassEntry) -> None:
        ent.hits += 1
        self._note("hit", "ok", carried=ent.make_dag is not None)

    def note_encode(self, native: bool) -> None:
        with self._mu:
            if native:
                self.encode_native += 1
            else:
                self.encode_python += 1

    def configure(self, capacity: Optional[int] = None) -> None:
        with self._mu:
            if capacity is not None:
                self.capacity = max(0, int(capacity))
                self._trim()

    # -------------------------------------------------------------- stats

    def stats(self) -> dict:
        with self._mu:
            total = self.hit + self.miss + self.bypass + self.fallback
            tiers: dict = {}
            for e in self._entries:
                tiers[e.tier] = tiers.get(e.tier, 0) + 1
            return {
                "enabled": self.enabled,
                "capacity": self.capacity,
                "classes": len(self._entries),
                "tiers": tiers,
                "learned": self.learned,
                "hit": self.hit, "miss": self.miss,
                "bypass": self.bypass, "fallback": self.fallback,
                "invalidate": self.invalidate,
                "hit_rate": round(self.hit / total, 4) if total else 0.0,
                "config_gen": self.config_gen,
                "reasons": dict(self.reasons),
                "find": {"finds": self.finds, "probes": self.probes},
                "keys": {"carried": self.keys_carried,
                         "walked": self.keys_walked},
                "encode": {
                    "native": self.encode_native,
                    "python": self.encode_python,
                    "native_available":
                        native.encode_rows_msgpack is not None},
            }


# ------------------------------------------------- response encoding

_PACKER_LOCAL = threading.local()


def _column_list(c) -> list:
    """One result column → a Python value list at C speed:
    ``ndarray.tolist()`` (one call) + a vectorized NULL punch-through,
    instead of the per-element ``Column.get`` walk ``enc_rows`` pays
    (an isinstance + validity probe + ``.item()`` per cell).  A device
    hash aggregation's columns are the finalize's own planes
    (``device/aggregate.py _hash_columns``), so this call is the first and only
    place its answer becomes Python values: msgpack wants them."""
    import numpy as np
    if getattr(c, "frac", None) is not None:
        # a scaled DECIMAL plane becomes its ``Decimal``s here, where
        # rows are asked for
        c = c.unscaled()
    vals = c.values.tolist()
    validity = c.validity
    if len(validity) and not validity.all():
        for i in np.nonzero(~validity)[0].tolist():
            vals[i] = None
    return vals


def encode_response_python(env: dict, result) -> bytes:
    """The Python chain of a fast-path hit's encode: result planes →
    wire bytes through ONE thread-local ``msgpack.Packer`` whose
    internal buffer is reused across requests (``autoreset=False`` —
    the preallocated response body), with rows materialized by
    columnar ``tolist`` + ``zip`` instead of the slow path's
    ``enc_rows`` row-list walk.  Byte-compatible with the slow leg:
    msgpack encodes the zipped tuples exactly as ``enc_rows``'s
    lists, and the field order matches ``_enc_cop_resp`` + the seal.
    It serves what the native call declines, and it is the oracle
    tests/test_encode_native.py holds that call's bytes to."""
    batch = result.batch
    rows = list(zip(*[_column_list(c) for c in batch.columns])) \
        if batch.num_rows else []
    return _pack({"rows": rows, **env})


def _pack(obj) -> bytes:
    p = getattr(_PACKER_LOCAL, "p", None)
    if p is None:
        import msgpack

        from ..codec.row import msgpack_default
        p = _PACKER_LOCAL.p = msgpack.Packer(
            use_bin_type=True, default=msgpack_default, autoreset=False)
    try:
        p.pack(obj)
        return p.bytes()
    finally:
        p.reset()


def _rows_native(batch) -> Optional[bytes]:
    """The msgpack array of ``batch``'s rows from ONE native call over
    its planes (native/fastbuild.cpp ``encode_rows_msgpack``: no Python
    value is made for a cell), or None where that call does not take
    them and the Python chain has to serve: an object plane (BYTES,
    JSON, a DECIMAL's ``Decimal``s), another dtype, a strided view,
    lengths that differ, the extension absent.  What the code can see
    in the planes decides, never a plan's name."""
    if native.encode_rows_msgpack is None or \
            any(getattr(c, "frac", None) is not None
                for c in batch.columns):
        # (a scaled DECIMAL's row form is a ``Decimal``, not its
        # plane's integer)
        return None
    return native.encode_rows_msgpack(
        [(c.values, c.validity) for c in batch.columns])


def encode_response(env: dict, result,
                    fp: Optional["FastPathCache"] = None,
                    encode_type: str = "rows") -> bytes:
    """A fast-path hit's response: ``{"rows": rows, **env}`` as wire
    bytes.  The rows come from the native call where it takes the
    result's planes, spliced between the map header + ``"rows"`` key
    and ``env``'s packed items (the same field order, the same bytes);
    from ``encode_response_python`` where it declines.  ``fp`` counts
    which (``/health`` ``fastpath.encode``).  A request that asked for a
    chunk (``encode_type``) gets ``{"chunk": chunk, **env}`` where the
    result's planes make one (``wire.enc_cop_body``, the slow leg's
    too): a buffer a column, no value a cell, nothing for either
    encoder of rows to do."""
    body = wire.enc_cop_body(result, encode_type)
    if body is not None:
        return _pack({**body, **env})
    rows = _rows_native(result.batch)
    if fp is not None:
        fp.note_encode(rows is not None)
    if rows is None:
        return encode_response_python(env, result)
    # env alone packs as its own map: its items follow that header
    tail = memoryview(_pack(env))[len(_map_header(len(env))):]
    return b"".join((_map_header(len(env) + 1), b"\xa4rows", rows, tail))


def _const_at(dag_dict: dict, index: int):
    """The ``index``-th rotating constant (``const_bucket``) of the wire
    dag, in the same DFS order _mark_slots assigns."""
    found = []

    def walk_expr(e):
        if e.get("k") == "c":
            v = e.get("v")
            if const_bucket(v) is not None:
                found.append(e["v"])
        elif e.get("k") == "f":
            for c in e.get("ch", ()):
                walk_expr(c)

    for ex in dag_dict.get("execs", ()):
        for key in ("conds", "exprs", "group_by", "partition_by"):
            for e in ex.get(key, ()):
                walk_expr(e)
        for a in ex.get("aggs", ()):
            if a.get("arg") is not None:
                walk_expr(a["arg"])
        for o in ex.get("order_by", ()):
            walk_expr(o["e"])
    return found[index]
