"""Causal request tracing: timestamped span trees across the async
serving stack.

Reference: TiKV ships exactly this layer — minitrace span tracing wired
through the coprocessor/raftstore stack, the ``slow_log!`` macro, and
the per-request TimeDetailV2 returned on the wire (components/tracker/
src/lib.rs).  The flat per-request ``phases_ms`` dict this module grew
out of had no timestamps, no nesting, and no visibility across the
thread handoffs where warm-path time actually hides (read-pool queue →
coalescer window → shared group dispatch → completion-pool D2H wait),
so a 127ms p50 with 0.6ms of dispatch stayed unattributable.

Model:

- a :class:`Tracker` is one request's trace: a ``trace_id`` (client-
  supplied or server-minted, echoed on the wire), a root ``rpc`` span,
  and timestamped child spans with parent links.  The active (trace,
  ambient-parent-span) pair rides a ``contextvars.ContextVar``;
  ``adopt()`` re-activates a trace on another thread (completion pool,
  coalescer dispatcher) so spans recorded there still land in the
  request's tree — the handoff survives because the span records its
  own thread id and the tree, not the thread, is the unit of identity;
- ``phase(name)`` opens a child of the ambient span and nests (the
  ambient moves for the duration); ``add_phase(name, ns)`` records a
  retroactive span ending now (used where the measured interval ended
  before a tracker context existed on the measuring thread, e.g. the
  coalescer window park);
- follows-from links (``link_from``) tie a coalesced group's single
  shared dispatch span into every member's trace with occupancy and
  lane index — "my request was slow because it stacked behind a
  10M-row group-mate" is readable from one trace;
- the TimeDetail/ScanDetail WIRE SHAPE is unchanged: ``phases_ms``
  still accumulates name → ms (tests and dashboards keep working), the
  span tree is additive.  Unsampled trackers (``coprocessor.
  trace_sample``) skip span objects entirely and cost what the flat
  tracker cost.

:class:`TraceBuffer` retains finished traces for the status server's
``/debug/trace`` surface with TAIL-BIASED retention: a bounded ring of
recent traces, plus the slowest N per request class and every errored/
late/shed/degraded request pinned past ring eviction — the traces an
operator actually asks for are the ones that survive.  ``to_chrome()``
exports one trace (plus any follows-from-linked foreign spans still in
the buffer) as Chrome trace-event JSON that loads in Perfetto.
"""

from __future__ import annotations

import bisect
import contextvars
import gc
import os
import random
import threading
import time
from collections import OrderedDict
from contextlib import contextmanager
from typing import Optional

from .trace_vocab import HOLD_CPU, HOLD_ROWS, SPAN_VOCABULARY

# (trace, ambient parent span) — the span new phases nest under
_current: contextvars.ContextVar = contextvars.ContextVar(
    "tikv_tpu_trace", default=None)

ROOT_SPAN_NAME = "rpc"
UNTRACKED_NAME = "untracked"
# An RPC's envelope: (root span, accept wait, reply), the root span's
# name and the aggregate rows of the two intervals outside it.  A txn
# write's rows are its own, so that what reads the reads' rows
# (service.accept_wait_ms, service.reply_ms) never holds a write.
READ_ENVELOPE = (ROOT_SPAN_NAME, "rpc_accept_wait", "rpc_reply")
TXN_ENVELOPE = ("txn_rpc", "txn_accept_wait", "txn_reply")


def new_trace_id() -> str:
    return os.urandom(8).hex()


class Span:
    """One timed operation in a trace.  ``t1 is None`` while open.
    ``links``: follows-from references into OTHER traces
    ({trace_id, span_id}) — causal predecessors that are not parents.
    ``cpu_ns``: the opening thread's CPU time between open and close
    (``phase``/``span`` where the clock was taken, :func:`_takes_cpu` —
    a retroactive span is a wait and carries none).  For a span that
    blocks on nothing, wall − cpu is time the thread was runnable and
    not running: the GIL, plus preemption."""

    __slots__ = ("name", "span_id", "parent_id", "t0", "t1", "tid",
                 "attrs", "links", "cpu_ns")

    def __init__(self, name: str, span_id: int, parent_id,
                 t0: int, tid: int):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = t0
        self.t1: Optional[int] = None
        self.tid = tid
        self.attrs: Optional[dict] = None
        self.links: Optional[list] = None
        self.cpu_ns: Optional[int] = None

    def to_dict(self, base_ns: int, end_ns: int) -> dict:
        t1 = self.t1 if self.t1 is not None else end_ns
        d = {"name": self.name, "span_id": self.span_id,
             "parent_id": self.parent_id,
             "start_us": round((self.t0 - base_ns) / 1e3, 1),
             "dur_us": round(max(0, t1 - self.t0) / 1e3, 1)}
        if self.cpu_ns is not None:
            d["cpu_us"] = round(self.cpu_ns / 1e3, 1)
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.links:
            d["follows_from"] = list(self.links)
        return d


class Tracker:
    """One request's cost attribution + causal span tree.

    Kept name (``Tracker``) and accumulation API so every existing
    call site — and the TimeDetailV2/ScanDetailV2 wire shape — survive
    the upgrade; the span tree is what's new.
    """

    __slots__ = ("trace_id", "sampled", "cpu_all", "t0", "wall_t0",
                 "t1", "accept_ns", "wait_ns", "phases", "scan_rows",
                 "scan_bytes",
                 "labels", "_mu", "_next_id", "spans", "root",
                 "meter_ctx", "ru", "envelope", "root_c0")

    def __init__(self, trace_id: Optional[str] = None,
                 sampled: bool = True, envelope: tuple = READ_ENVELOPE):
        self.envelope = envelope
        # a trace the caller asked for by id takes the thread CPU clock
        # on every span; the others on a sample of them (_takes_cpu)
        self.cpu_all = trace_id is not None
        self.trace_id = trace_id or new_trace_id()
        self.sampled = sampled
        # a write's root span runs on ONE thread, install to seal: on
        # the sample, its CPU beside its wall (a read's crosses threads)
        self.root_c0 = time.thread_time_ns() \
            if envelope is TXN_ENVELOPE and _takes_cpu(self) else None
        self.t0 = time.perf_counter_ns()
        self.wall_t0 = time.time()
        self.t1: Optional[int] = None       # set by finish()
        self.accept_ns: Optional[int] = None    # set by note_accept()
        self.wait_ns = 0            # read-pool queue/slot wait
        self.phases: dict[str, int] = {}    # name -> ns (wire shape)
        self.scan_rows = 0          # processed versions / rows
        self.scan_bytes = 0
        self.labels: dict[str, str] = {}    # e.g. cache: hit|build
        self._mu = threading.Lock()
        self._next_id = 0
        self.spans: list[Span] = []
        self.root: Optional[Span] = None
        # resource metering (tikv_tpu/resource_metering.py): the
        # request's MeterContext rides the tracker across adopt()
        # handoffs, and every RU charged to this request accumulates
        # here (sealed into the trace labels + slow-query line)
        self.meter_ctx = None
        self.ru = 0.0
        if sampled:
            self.root = self._new_span(envelope[0], None, self.t0)

    # -- span tree --

    def _new_span(self, name: str, parent_id, t0: Optional[int] = None
                  ) -> Span:
        with self._mu:
            self._next_id += 1
            sp = Span(name, self._next_id, parent_id,
                      t0 if t0 is not None else time.perf_counter_ns(),
                      threading.get_ident())
            self.spans.append(sp)
        return sp

    def begin(self, name: str, parent: Optional[Span] = None,
              t0: Optional[int] = None) -> Optional[Span]:
        """Open a child span (of ``parent``, default the root); the
        caller owns closing it by setting ``span.t1``.  None when the
        trace is unsampled — callers treat the span as optional."""
        if not self.sampled:
            return None
        pid = (parent.span_id if parent is not None
               else (self.root.span_id if self.root is not None
                     else None))
        return self._new_span(name, pid, t0)

    def end(self, span: Optional[Span],
            t1: Optional[int] = None) -> None:
        """Close ``span`` exactly once (idempotent: a second close is
        ignored so a handoff race can never re-open or re-time it)."""
        if span is not None and span.t1 is None:
            span.t1 = t1 if t1 is not None else time.perf_counter_ns()

    def annotate_span(self, span: Optional[Span], **attrs) -> None:
        if span is None:
            return
        with self._mu:
            if span.attrs is None:
                span.attrs = {}
            span.attrs.update(attrs)

    def link_from(self, name: str, src_trace_id: str, src_span_id: int,
                  parent: Optional[Span] = None, **attrs
                  ) -> Optional[Span]:
        """Record a follows-from link: this trace's causal predecessor
        is span ``src_span_id`` of ``src_trace_id`` (a shared group
        dispatch, typically).  Materialized as a zero-duration marker
        span carrying the link + attrs (occupancy, lane index)."""
        sp = self.begin(name, parent)
        if sp is None:
            return None
        sp.t1 = sp.t0
        sp.links = [{"trace_id": src_trace_id, "span_id": src_span_id}]
        if attrs:
            self.annotate_span(sp, **attrs)
        return sp

    def finish(self) -> None:
        """Freeze the trace: total wall stops here, the root closes,
        and any span left open (a handoff that never resolved) is
        clamped so export/breakdown see a closed tree."""
        if self.t1 is None:
            self.t1 = time.perf_counter_ns()
            AGGREGATE.add(self.envelope[0], self.t1 - self.t0,
                          None if self.root_c0 is None
                          else time.thread_time_ns() - self.root_c0)
        with self._mu:
            for sp in self.spans:
                if sp.t1 is None:
                    sp.t1 = self.t1

    # -- accumulation (the PRE-SPAN API, kept verbatim) --

    def add(self, name: str, ns: int) -> None:
        with self._mu:
            self.phases[name] = self.phases.get(name, 0) + int(ns)

    def add_wait(self, ns: int) -> None:
        self.wait_ns += int(ns)

    def add_scan(self, rows: int, nbytes: int = 0) -> None:
        self.scan_rows += int(rows)
        self.scan_bytes += int(nbytes)

    def label(self, key: str, value: str) -> None:
        self.labels[key] = value

    def add_ru(self, ru: float) -> None:
        """Accumulate request units charged to this request (called by
        the metering recorder from whichever thread measured the cost —
        the same exactly-once discipline the span handoffs follow)."""
        with self._mu:
            self.ru += float(ru)

    # -- serialization (TimeDetailV2 / ScanDetailV2 shape) --

    def total_ns(self) -> int:
        return (self.t1 if self.t1 is not None
                else time.perf_counter_ns()) - self.t0

    def time_detail(self) -> dict:
        total = self.total_ns()
        proc = total - self.wait_ns
        d = {
            "total_rpc_wall_ms": round(total / 1e6, 3),
            "wait_wall_ms": round(self.wait_ns / 1e6, 3),
            "process_wall_ms": round(proc / 1e6, 3),
            "phases_ms": {k: round(v / 1e6, 3)
                          for k, v in self.phases.items()},
        }
        if self.labels:
            d["labels"] = dict(self.labels)
        if self.accept_ns is not None and self.t1 is not None:
            # absolute perf_counter_ns stamps (CLOCK_MONOTONIC: one
            # clock for every process of a machine): a client on this
            # machine places its own send and receive around them
            # (server/client.py StoreClient.call)
            d["clock_ns"] = {"accept": self.accept_ns, "t0": self.t0,
                             "t1": self.t1}
        return d

    def scan_detail(self) -> dict:
        return {
            "processed_versions": self.scan_rows,
            "processed_versions_size": self.scan_bytes,
        }

    # -- decomposition --

    def breakdown(self) -> dict:
        """Non-overlapping decomposition of the root wall into per-name
        milliseconds + the explicit ``untracked`` residual.

        Elementary-segment sweep: every instant of the root interval is
        attributed to the INNERMOST span covering it (latest start wins
        — a ``d2h_wait`` recorded by the completion worker takes the
        segment from the service thread's ``await_deferred`` umbrella),
        so the values sum exactly to ``total_rpc_wall_ms`` and sibling
        overlap across threads cannot double-count.
        """
        end = self.t1 if self.t1 is not None else time.perf_counter_ns()
        if self.root is None:
            return {UNTRACKED_NAME: round((end - self.t0) / 1e6, 3)}
        r0 = self.root.t0
        r1 = self.root.t1 if self.root.t1 is not None else end
        with self._mu:
            spans = [s for s in self.spans if s is not self.root]
        ivs = []
        for s in spans:
            t1 = s.t1 if s.t1 is not None else r1
            a, b = max(s.t0, r0), min(t1, r1)
            if b > a:
                ivs.append((a, b, s))
        pts = sorted({r0, r1, *(a for a, _, _ in ivs),
                      *(b for _, b, _ in ivs)})
        out: dict[str, int] = {}
        covered = 0
        for a, b in zip(pts, pts[1:]):
            if b <= a:
                continue
            cover = [s for (x, y, s) in ivs if x <= a and y >= b]
            if not cover:
                continue
            s = max(cover, key=lambda sp: (sp.t0, sp.span_id))
            out[s.name] = out.get(s.name, 0) + (b - a)
            covered += b - a
        out[UNTRACKED_NAME] = max(0, (r1 - r0) - covered)
        return {k: round(v / 1e6, 3) for k, v in out.items()}

    def coverage(self) -> float:
        """Fraction of the root wall decomposed into named spans
        (1 − untracked/total); the ≥0.95 acceptance figure."""
        bd = self.breakdown()
        total = sum(bd.values())
        if total <= 0:
            return 1.0
        return 1.0 - bd.get(UNTRACKED_NAME, 0.0) / total

    def to_dict(self) -> dict:
        end = self.t1 if self.t1 is not None else time.perf_counter_ns()
        with self._mu:
            spans = [s.to_dict(self.t0, end) for s in self.spans]
        return {
            "trace_id": self.trace_id,
            "start_unix_s": round(self.wall_t0, 6),
            "total_ms": round((end - self.t0) / 1e6, 3),
            "labels": dict(self.labels),
            "time_detail": self.time_detail(),
            "scan_detail": self.scan_detail(),
            "spans": spans,
            "breakdown_ms": self.breakdown(),
        }


# ----------------------------------------------------------- aggregate

class SpanAggregate:
    """Cumulative totals per span name since process start, fed by
    every ``phase`` / ``span`` / ``add_phase`` / ``add_span`` /
    ``timed`` call, sampled or not, and by the intervals no tracker
    holds (``rpc_accept_wait``, ``rpc_reply``, ``gc_pause``).  Any two
    snapshots difference into a per-span mean (Δwall_ms / Δcount) or a
    share of the window (Δwall_ms / Δclock_ms, :func:`process_clock`).
    ``cpu_ms`` and ``offcpu_ms`` (wall − cpu) are sums over the
    ``cpu_samples`` spans of the row that took the thread CPU clock
    (:func:`_takes_cpu`), so a mean is Δcpu_ms / Δcpu_samples; a row of
    waits reads 0 in all three.  Sums, not span by span, because one
    reading is itself a sample where the clock ticks coarsely (10 ms
    under gVisor), and every counter only adds, so two snapshots
    difference."""

    def __init__(self, names=()):
        # re-entrant: an allocation under the lock can start a
        # collection, whose ``gc_pause`` callback adds on this thread
        self._mu = threading.RLock()
        # name -> [count, wall_ns, cpu samples, their cpu_ns, their wall_ns]
        self._rows: dict[str, list] = {n: [0, 0, 0, 0, 0] for n in names}

    def add(self, name: str, wall_ns: int,
            cpu_ns: Optional[int] = None) -> None:
        with self._mu:
            row = self._rows.get(name)
            if row is None:
                row = self._rows[name] = [0, 0, 0, 0, 0]
            row[0] += 1
            row[1] += wall_ns
            if cpu_ns is not None:
                row[2] += 1
                row[3] += cpu_ns
                row[4] += wall_ns

    def snapshot(self) -> dict:
        with self._mu:
            rows = {n: tuple(r) for n, r in self._rows.items()}
        return {n: {"count": count, "wall_ms": round(wall / 1e6, 3),
                    "cpu_samples": samples,
                    "cpu_ms": round(cpu_s / 1e6, 3),
                    "offcpu_ms": round((wall_s - cpu_s) / 1e6, 3)}
                for n, (count, wall, samples, cpu_s, wall_s)
                in rows.items()}


# one table a process: a row, zeroed, for every registered name from the
# start, so a path that has not run yet reads 0 and never goes missing
AGGREGATE = SpanAggregate(SPAN_VOCABULARY)
_CLOCK_T0 = time.perf_counter()
_CPU_T0 = time.process_time()

# One span in 2**_CPU_SAMPLE_BITS takes the thread CPU clock, drawn at
# random so that no span name is always or never drawn.  Taken on every
# span (~3,400 readings/s) it cost the served path 7% of its rate on the
# benchmark's machine, where one reading is a 5.8 us call into gVisor's
# kernel and ticks in 10 ms (PERF.md, PR 25); the aggregate needs sums,
# and a trace asked for by id (Tracker.cpu_all) still gets every span.
_CPU_SAMPLE_BITS = 4


def _takes_cpu(tr: Optional[Tracker] = None) -> bool:
    return (tr is not None and tr.cpu_all) or \
        random.getrandbits(_CPU_SAMPLE_BITS) == 0


def process_clock() -> dict:
    """The window's denominators: wall and CPU (every thread, XLA's
    too) of the process since this module was imported."""
    return {"clock_ms": round((time.perf_counter() - _CLOCK_T0) * 1e3, 3),
            "cpu_ms": round((time.process_time() - _CPU_T0) * 1e3, 3)}


@contextmanager
def timed(name: str, trace_id: Optional[str] = None):
    """A thread's own state outside any request (the coalescer's
    dispatcher: ``dispatcher_idle``, ``group_dispatch``): wall and CPU
    into the aggregate, and an annotation where the name is listed."""
    ann = _annotation(name, trace_id)
    c0 = time.thread_time_ns() if _takes_cpu() else None
    t0 = time.perf_counter_ns()
    try:
        yield
    finally:
        AGGREGATE.add(name, time.perf_counter_ns() - t0, None
                      if c0 is None else time.thread_time_ns() - c0)
        if ann is not None:
            ann.__exit__(None, None, None)


# ------------------------------------------------- a thread's hold
#
# The coalescer's dispatcher paces a cell that writes, and its
# ``group_dispatch`` is one wall with a few phases inside: ``hold`` opens
# it as a ledger.  While one is open on a thread, the rows of
# trace_vocab.HOLD_ROWS that run there nest: a ``held`` scope keeps its
# SELF time (its wall less the HOLD_ROWS scopes inside it) as its flat
# phase and its aggregate row, so no instant is in two of them, and the
# hold closes with ``dispatch_self``, its wall less what its outermost
# HOLD_ROWS scopes covered.  Over any window the rows and dispatch_self
# add up to the hold's own row.

class _Held(threading.local):
    # one int a HOLD_ROWS scope open on this thread, outermost first
    # (the hold's own): the wall of the HOLD_ROWS scopes that closed
    # directly inside it.  None: no hold is open here.
    frames = None


_held = _Held()


class _Hold:
    """``hold`` as a context manager (``timed``'s work, and the
    ledger)."""

    __slots__ = ("name", "self_name", "trace_id", "ann", "c0", "t0",
                 "outer")

    def __init__(self, name: str, self_name: str,
                 trace_id: Optional[str]):
        self.name = name
        self.self_name = self_name
        self.trace_id = trace_id

    def __enter__(self) -> "_Hold":
        self.ann = _annotation(self.name, self.trace_id)
        self.c0 = time.thread_time_ns() if _takes_cpu() else None
        self.outer = _held.frames
        _held.frames = [0]
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *_exc) -> bool:
        wall = time.perf_counter_ns() - self.t0
        covered = _held.frames[0]
        _held.frames = self.outer
        if self.outer is not None:
            # a hold inside a hold (a shutdown's inline dispatch): the
            # outer one's child, whole
            self.outer[-1] += wall
        AGGREGATE.add(self.name, wall, None if self.c0 is None
                      else time.thread_time_ns() - self.c0)
        AGGREGATE.add(self.self_name, max(0, wall - covered))
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        return False


def hold(name: str, self_name: str,
         trace_id: Optional[str] = None) -> _Hold:
    """:func:`timed`, and for its duration this thread's HOLD_ROWS
    scopes are accounted against it: ``self_name``'s aggregate row gets
    the wall none of them covered."""
    return _Hold(name, self_name, trace_id)


class _HeldScope:
    """``held`` as a context manager.  Outside a hold: nothing at all
    (one thread-local read).  Inside: an aggregate row, an annotation,
    and on the tracker that is active when the piece CLOSES (the
    coalescer adopts its leader inside ``group_open``) a flat phase and,
    where it is sampled, a span beside the ambient span's children (the
    ambient span does not move: a piece may outlive an ``adopt``).  The
    flat phase and the row get the piece's SELF time.

    ``turn(name)`` closes the piece that is open and opens the next
    under ``name`` at the same instant: consecutive pieces of one
    function without nesting its body; ``turn(None)`` opens none (what
    follows is somebody else's rows, or nobody's: ``dispatch_self``).
    ``traced=False``: the row and the annotation alone (a piece no
    request waits for)."""

    __slots__ = ("name", "traced", "frames", "t0", "c0", "ann", "attrs")

    def __init__(self, name: str):
        self.name = name
        self.traced = True
        self.frames = None
        self.attrs = None

    def __enter__(self) -> "_HeldScope":
        self.frames = _held.frames
        if self.frames is not None:
            self._open()
        return self

    def __exit__(self, *_exc) -> bool:
        self.turn(None)
        self.frames = None
        return False

    def turn(self, name: Optional[str], traced: bool = True) -> None:
        if self.frames is None:
            return
        if self.name is not None:
            self._close()
        self.name, self.traced = name, traced
        if name is not None:
            self._open()

    def note(self, **attrs) -> None:
        """Attributes of the open piece's span."""
        if self.frames is not None:
            self.attrs = attrs

    def _open(self) -> None:
        got = _current.get()
        self.ann = _annotation(
            self.name, got[0].trace_id if got is not None else None)
        self.frames.append(0)
        self.c0 = time.thread_time_ns() if _takes_cpu() else None
        self.t0 = time.perf_counter_ns()

    def _close(self) -> None:
        t1 = time.perf_counter_ns()
        wall = t1 - self.t0
        inside = self.frames.pop()
        own = max(0, wall - inside)
        self.frames[-1] += wall
        # on the sample, its CPU: of a piece nothing of HOLD_ROWS ran
        # inside (a hit's stage_plan, a roll, the hold's two ends), whose
        # wall is all its own
        AGGREGATE.add(self.name, own, time.thread_time_ns() - self.c0
                      if self.c0 is not None and not inside else None)
        got = _current.get() if self.traced else None
        if got is not None:
            tr, parent = got
            tr.add(self.name, own)
            if tr.sampled:
                sp = tr.begin(self.name, parent, self.t0)
                tr.end(sp, t1)
                if self.attrs:
                    tr.annotate_span(sp, **self.attrs)
        self.attrs = None
        if self.ann is not None:
            self.ann.__exit__(None, None, None)


def held(name: str) -> _HeldScope:
    """A row of trace_vocab.HOLD_SELF: exists inside a :func:`hold`
    alone (a launch a request's own thread stages records none)."""
    return _HeldScope(name)


_gc_t0 = 0


def _on_gc(phase_: str, _info: dict) -> None:
    # collections do not nest and start/stop run on the collecting
    # thread: one module slot is enough
    global _gc_t0
    if phase_ == "start":
        _gc_t0 = time.perf_counter_ns()
    elif _gc_t0:
        AGGREGATE.add("gc_pause", time.perf_counter_ns() - _gc_t0)
        _gc_t0 = 0


def watch_gc() -> None:
    """Account Python's collector in the aggregate (``gc_pause``:
    count, wall).  Idempotent; the node calls it when it is built."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


# ------------------------------------------------------ the GIL probe

GIL_HZ = 20
# fixed bucket edges: a mode at 5 ms is CPython's switch interval
_GIL_EDGES_NS = (100_000, 500_000, 1_000_000, 2_000_000, 5_000_000,
                 10_000_000)
_GIL_BUCKETS = ("le_100us", "le_500us", "le_1ms", "le_2ms", "le_5ms",
                "le_10ms", "gt_10ms")


_native_probe = False       # not looked for yet


def _native_gil_probe():
    # looked for on first use: importing the extension may compile it
    global _native_probe
    if _native_probe is False:
        from ..native import gil_probe
        _native_probe = gil_probe
    return _native_probe


def gil_mode() -> str:
    """``native``: the extension's helper stamps both sides of the GIL's
    return; ``overshoot``: ``time.sleep``'s lateness stands in (the
    timer's own lateness is in it too)."""
    return "native" if _native_gil_probe() is not None else "overshoot"


def gil_sample(sleep_ns: int, mode: Optional[str] = None) -> int:
    """One sample → ns this thread waited for the GIL after a sleep of
    ``sleep_ns`` without it: what a thread that returns from any
    blocking call (a gRPC poll, a ``PjitFunction``, a condition wait)
    waits before it runs Python again."""
    probe = _native_gil_probe() if mode != "overshoot" else None
    if probe is not None:
        woke, held = probe(int(sleep_ns))
        return max(0, held - woke)
    t0 = time.perf_counter_ns()
    time.sleep(sleep_ns / 1e9)
    return max(0, time.perf_counter_ns() - t0 - int(sleep_ns))


class _GilStats:
    """Counters that only add: two snapshots difference."""

    def __init__(self):
        self._mu = threading.Lock()
        self.samples = 0
        self.wait_ns_sum = 0
        self.wait_ns_max = 0
        self.hist = [0] * len(_GIL_BUCKETS)

    def add(self, wait_ns: int) -> None:
        b = bisect.bisect_left(_GIL_EDGES_NS, wait_ns)
        with self._mu:
            self.samples += 1
            self.wait_ns_sum += wait_ns
            self.wait_ns_max = max(self.wait_ns_max, wait_ns)
            self.hist[b] += 1
        AGGREGATE.add("gil_wait", wait_ns)

    def snapshot(self) -> dict:
        with self._mu:
            return {"mode": gil_mode(), "hz": GIL_HZ,
                    "samples": self.samples,
                    "wait_ms_sum": round(self.wait_ns_sum / 1e6, 3),
                    "wait_ms_max": round(self.wait_ns_max / 1e6, 3),
                    "hist": dict(zip(_GIL_BUCKETS, self.hist))}


GIL = _GilStats()
_gil_thread: Optional[threading.Thread] = None
_gil_mu = threading.Lock()


def _gil_loop() -> None:
    period = 1_000_000_000 // GIL_HZ
    nxt = time.perf_counter_ns()
    while True:
        nxt += period
        pause = nxt - time.perf_counter_ns()
        if pause <= 0:          # fell behind: no burst to catch up
            nxt, pause = time.perf_counter_ns() + period, period
        GIL.add(gil_sample(pause))


def watch_gil() -> None:
    """Start the probe: ONE daemon thread a process (``gil-probe``),
    :data:`GIL_HZ` samples a second into :data:`GIL` and the aggregate's
    ``gil_wait`` row.  Idempotent; the node calls it beside
    :func:`watch_gc`, and it outlives every node of the process."""
    global _gil_thread
    with _gil_mu:
        if _gil_thread is None or not _gil_thread.is_alive():
            _gil_thread = threading.Thread(
                target=_gil_loop, daemon=True, name="gil-probe")
            _gil_thread.start()


# ------------------------------------------- Python's CPU by thread role

# (role, test of a thread's name), first match wins; the rest is "other"
# (the main thread, raft and PD loops, the client's pools)
_THREAD_ROLES = (
    ("grpc_serve", lambda n: n.endswith("(_serve)")),
    ("rpc_handler", lambda n: n.startswith("rpc-handler")),
    ("mux_command", lambda n: n.startswith("mux-command")),
    ("mux_stream", lambda n: n.startswith("mux-stream")),
    ("copr-coalescer", lambda n: n == "copr-coalescer"),
    ("copr-dispatcher", lambda n: n == "copr-dispatcher"),
    ("copr-completion", lambda n: n.startswith("copr-completion")),
    ("status-server", lambda n: n == "status-server" or
     n.endswith("(process_request_thread)")),
    ("gil-probe", lambda n: n == "gil-probe"),
)
_IMPORT_THREAD = threading.get_native_id()
_IMPORT_THREAD_CPU0 = time.thread_time_ns()
_CLK_TCK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100


def _thread_role(name: str) -> str:
    for role, test in _THREAD_ROLES:
        if test(name):
            return role
    return "other"


def _thread_cpu_ns(t: threading.Thread) -> tuple:
    """→ (ns of CPU the thread has had, which source said so:
    ``thread_cpuclock`` or ``proc_stat``), or (None, None) for a thread
    that is gone."""
    try:
        # the clock id glibc's pthread_getcpuclockid returns, made from
        # the kernel's thread id: time.pthread_getcpuclockid(ident)
        # dereferences the ident, and CPython warns that an expired one
        # may segfault; a thread gone here is an EINVAL
        return int(time.clock_gettime(
            ((~t.native_id) << 3) | 6) * 1e9), "thread_cpuclock"
    except (AttributeError, OSError, OverflowError, TypeError):
        pass
    try:
        with open(f"/proc/self/task/{t.native_id}/stat") as f:
            # utime and stime, the 14th and 15th fields, counted from
            # behind the command's closing bracket
            rest = f.read().rsplit(")", 1)[1].split()
        return (int(rest[11]) + int(rest[12])) * 1_000_000_000 \
            // _CLK_TCK, "proc_stat"
    except (OSError, IndexError, ValueError):
        return None, None


class _ThreadCpu:
    """CPU of the threads Python started, summed by role when asked
    (``/health``: twice a benchmark window; nothing on the hot path).  A
    thread that has ended keeps what it was last seen with, so every
    role only rises."""

    def __init__(self):
        self._mu = threading.Lock()
        self._seen: dict = {}       # native_id -> (role, ns)
        self._retired: dict = {}    # role -> ns of threads that ended

    def snapshot(self) -> dict:
        source = None
        with self._mu:
            live = {}
            for t in threading.enumerate():
                key = t.native_id
                if key is None:         # not started yet
                    continue
                ns, src = _thread_cpu_ns(t)
                if ns is None:
                    continue
                source = source or src
                if key == _IMPORT_THREAD:
                    ns = max(0, ns - _IMPORT_THREAD_CPU0)
                old = self._seen.get(key)
                if old is not None and ns < old[1]:
                    # the id was another thread's: that one has ended
                    self._retire(old)
                live[key] = (_thread_role(t.name), ns)
            for key, old in self._seen.items():
                if key not in live:
                    self._retire(old)
            self._seen = live
            roles = {role: {"threads": 0, "cpu_ms": 0}
                     for role, _test in _THREAD_ROLES}
            roles["other"] = {"threads": 0, "cpu_ms": 0}
            for role, ns in self._retired.items():
                roles[role]["cpu_ms"] += ns
            for role, ns in live.values():
                roles[role]["threads"] += 1
                roles[role]["cpu_ms"] += ns
        python_ns = sum(r["cpu_ms"] for r in roles.values())
        for r in roles.values():
            r["cpu_ms"] = round(r["cpu_ms"] / 1e6, 3)
        return {"source": source, "roles": roles,
                "python_cpu_ms": round(python_ns / 1e6, 3)}

    def _retire(self, old: tuple) -> None:
        role, ns = old
        self._retired[role] = self._retired.get(role, 0) + ns


THREADS = _ThreadCpu()


def thread_cpu() -> tuple:
    """→ (``/health`` ``tracing.threads``, ``tracing.process``): CPU by
    thread role, their sum ``python_cpu_ms`` (bytecode under the one
    GIL, at most 1.0 s a second of it, PLUS what those threads run with
    the GIL released: the gRPC core's work on the calling thread, PjRt's
    on the launching one, numpy, and the kernel's for their system
    calls) and ``native_cpu_ms``, the rest of the process's ``cpu_ms``
    (the threads XLA, PjRt and the gRPC core started themselves), read
    after the threads so that the two add up to it."""
    threads = THREADS.snapshot()
    process = process_clock()
    threads["native_cpu_ms"] = round(
        process["cpu_ms"] - threads["python_cpu_ms"], 3)
    return threads, process


# ---------------------------------------------- profiler annotations

# Spans that are WORK on some thread, emitted as ``copr:<name>`` through
# the annotator so they land in the device profile on its clock.
# Umbrellas and parked waits (rpc, fastpath, copr_handler, admission,
# await_deferred, group_fetch_wait, coalesce_wait, read_pool_wait) stay
# out: they cover every idle gap of the device and explain none.
ANNOTATED = frozenset({
    "dispatcher_idle", "group_dispatch", "d2h_wait", "host_materialize",
    "plan_decode", "snapshot", "columnar_cache", "device_dispatch",
    "feed_patch", "feed_upload", "feed_rebuild", "host_derive",
    "arena_evict", "delta_apply",
    "resp_serialize",
    "rpc_reply",
    # the hold's own rows (trace_vocab.HOLD_SELF): inside
    # copr:group_dispatch on the dispatcher's line
    "group_open", "stage_plan", "memo_roll", "stage_full", "feed_get",
    "lanes_launch", "group_complete"})
_ANNOTATION_NAMES = {n: f"copr:{n}" for n in ANNOTATED}
_annotator = None


def set_annotator(factory) -> None:
    """``factory(name, **kwargs)`` → a context manager, or None for
    off (the default).  The device layer hands in
    ``jax.profiler.TraceAnnotation``; this module imports no JAX."""
    global _annotator
    _annotator = factory


def _annotation(name: str, trace_id: Optional[str]):
    """An ENTERED annotation for ``name``, or None: the caller exits it."""
    if _annotator is None:
        return None
    label = _ANNOTATION_NAMES.get(name)
    if label is None:
        return None
    ann = _annotator(label, trace_id=trace_id) if trace_id \
        else _annotator(label)
    ann.__enter__()
    return ann


# ------------------------------------------- outside the root span

class _RpcEnvelope(threading.local):
    """What one handler-pool task knows of its RPC outside the root
    span: when gRPC handed it to the pool, and the sealed trace whose
    reply is still to be serialized."""
    submit_ns = None    # pool submit, consumed by the first install
    reply = None        # (t_finish_ns, cpu0_ns | None, annotation, row)
    armed = False       # inside a pool task: a serializer will follow


_rpc = _RpcEnvelope()


def rpc_task_begin(submit_ns: int) -> None:
    """The handler pool's wrapper, on the worker thread, before gRPC's
    task runs."""
    _rpc.submit_ns = submit_ns
    _rpc.reply = None
    _rpc.armed = True


def rpc_task_end() -> None:
    reply_done()
    _rpc.submit_ns = None
    _rpc.armed = False


def note_accept(tr: Tracker) -> None:
    """``rpc_accept_wait`` (a write's: ``txn_accept_wait``): pool
    submit → this tracker's install (the pool's queue, the message
    receive, the wait for the GIL).  An attribute of the root span and
    a row of the aggregate; the root span and ``total_rpc_wall_ms`` do
    not move."""
    t = _rpc.submit_ns
    if t is None:
        return
    _rpc.submit_ns = None       # a streamed task's later requests: none
    tr.accept_ns = min(t, tr.t0)
    wait = max(0, tr.t0 - t)
    AGGREGATE.add(tr.envelope[1], wait)
    tr.annotate_span(tr.root, rpc_accept_wait_us=round(wait / 1e3, 1))


def reply_begin(tr: Tracker) -> None:
    """``rpc_reply`` (a write's: ``txn_reply``) opens where the trace
    was sealed (``Tracker.finish``); :func:`reply_done` closes it when
    the response serializer returns.  Aggregate only: the reply has
    left."""
    if not _rpc.armed or tr.t1 is None:
        return
    reply_done()
    _rpc.reply = (tr.t1,
                  time.thread_time_ns() if _takes_cpu(tr) else None,
                  _annotation(tr.envelope[2], tr.trace_id),
                  tr.envelope[2])


def reply_done() -> None:
    reply_close(reply_handoff())


def reply_handoff() -> Optional[tuple]:
    """A mux command's worker (``service.py`` ``batch_commands``) gives
    its open ``rpc_reply`` to the thread whose serializer packs the
    response MESSAGE holding the reply → what :func:`reply_close` takes
    there, or None.  The annotation and the CPU clock are this
    thread's, so both end here; the wall runs on."""
    got = _rpc.reply
    if got is None:
        return None
    _rpc.reply = None
    t_finish, c0, ann, row = got
    if ann is not None:
        ann.__exit__(None, None, None)
    return (t_finish, None if c0 is None else time.thread_time_ns() - c0,
            row)


def reply_close(handed: Optional[tuple]) -> None:
    if handed is not None:
        AGGREGATE.add(handed[2], time.perf_counter_ns() - handed[0],
                      handed[1])


# ------------------------------------------------------------- context

def install(trace_id: Optional[str] = None, sampled: bool = True,
            envelope: tuple = READ_ENVELOPE
            ) -> tuple[Tracker, contextvars.Token]:
    """Create + activate a tracker; pair with :func:`uninstall`."""
    tr = Tracker(trace_id=trace_id, sampled=sampled, envelope=envelope)
    return tr, _current.set((tr, tr.root))


def adopt(tr: Tracker, parent: Optional[Span] = None
          ) -> contextvars.Token:
    """Activate an EXISTING tracker on this thread; pair with
    :func:`uninstall`.  The async coprocessor path hands the request's
    tracker to a completion-pool worker so the deferred device fetch
    still attributes into the request's TimeDetail and span tree.
    ``parent``: ambient span new phases nest under (default: the
    root) — the coalescer adopts the leader under its group_dispatch
    span so the shared launch work nests where it belongs."""
    return _current.set(
        (tr, parent if parent is not None else tr.root))


def uninstall(token: contextvars.Token) -> None:
    _current.reset(token)


def current() -> Optional[Tracker]:
    got = _current.get()
    return got[0] if got is not None else None


def current_span() -> Optional[Span]:
    got = _current.get()
    return got[1] if got is not None else None


class _Scoped:
    """``phase`` / ``span`` as a context manager: opens and closes on
    ONE thread, so the thread's CPU time can be taken at both ends
    beside the wall.  A class, not a generator: a request opens a dozen
    of these under a saturated GIL."""

    __slots__ = ("name", "in_phases", "tr", "sp", "tok", "ann", "c0",
                 "t0", "frames")

    def __init__(self, name: str, in_phases: bool):
        self.name = name
        self.in_phases = in_phases
        self.tr = None

    def __enter__(self) -> Optional[Tracker]:
        got = _current.get()
        if got is None:
            return None
        tr, parent = got
        self.tr = tr
        self.ann = _annotation(self.name, tr.trace_id)
        # inside a hold (``hold``) a row of the hold's vocabulary is a
        # child of whatever ``held`` scope is open around it, and a
        # jitted call's takes the CPU clock every time (HOLD_CPU)
        frames = _held.frames
        if frames is not None and self.name in HOLD_ROWS:
            frames.append(0)
            self.frames = frames
            cpu = self.name in HOLD_CPU or _takes_cpu(tr)
        else:
            self.frames = None
            cpu = _takes_cpu(tr)
        self.c0 = time.thread_time_ns() if cpu else None
        self.t0 = time.perf_counter_ns()
        self.sp = sp = tr.begin(self.name, parent, self.t0) \
            if tr.sampled else None
        self.tok = _current.set((tr, sp)) if sp is not None else None
        return tr

    def __exit__(self, *_exc) -> bool:
        tr = self.tr
        if tr is None:
            return False
        t1 = time.perf_counter_ns()
        cpu = None if self.c0 is None else time.thread_time_ns() - self.c0
        if self.tok is not None:
            _current.reset(self.tok)
        if self.sp is not None:
            self.sp.cpu_ns = cpu
            tr.end(self.sp, t1)
        if self.in_phases:
            tr.add(self.name, t1 - self.t0)
        AGGREGATE.add(self.name, t1 - self.t0, cpu)
        if self.frames is not None:
            # its whole wall is its parent's child time; what ran inside
            # it is not subtracted again further out
            self.frames.pop()
            self.frames[-1] += t1 - self.t0
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        return False


def phase(name: str) -> _Scoped:
    """Attribute the enclosed wall time to ``name`` on the active
    tracker (no-op without one): accumulates into ``phases_ms`` AND —
    when sampled — opens a nesting child span of the ambient span.
    Sampled or not, the wall and (:func:`_takes_cpu`) the thread's CPU
    time also feed the process-wide :data:`AGGREGATE`."""
    return _Scoped(name, True)


def span(name: str) -> _Scoped:
    """Span-ONLY timing: records a child span but does NOT accumulate
    into ``phases_ms`` — for umbrella intervals that other phases
    decompose (``await_deferred`` over the completion-side spans,
    ``group_fetch_wait`` over the shared d2h), so the flat phase dict
    keeps its historical non-overlapping-sum-≤-total invariant."""
    return _Scoped(name, False)


def add_phase(name: str, ns: int,
              end_ns: Optional[int] = None) -> Optional[Span]:
    """Retroactive attribution: ``ns`` of wall ending at ``end_ns``
    (default: now; the interval was measured on a thread that had no
    tracker context).  → the span, for :func:`add_span` children (None
    when unsampled)."""
    got = _current.get()
    if got is None:
        return None
    tr, parent = got
    ns = max(0, int(ns))
    tr.add(name, ns)
    AGGREGATE.add(name, ns)
    if not tr.sampled:
        return None
    if end_ns is None:
        end_ns = time.perf_counter_ns()
    sp = tr.begin(name, parent, end_ns - ns)
    tr.end(sp, end_ns)
    return sp


def client_phase(name: str, ns: int, phases_ms: dict) -> None:
    """A phase of trace_vocab's ``OUTSIDE_ROOT``, written by the CLIENT
    into a reply's own ``phases_ms`` (server/client.py): no tracker, no
    aggregate row of the store's, and outside ``total_rpc_wall_ms``."""
    phases_ms[name] = round(ns / 1e6, 3)


def add_span(name: str, t0_ns: int, t1_ns: int,
             parent: Optional[Span] = None) -> None:
    """Retroactive span-ONLY child with both ends given (a wait that
    was over before this thread held the tracker): in the tree and the
    aggregate, not in ``phases_ms``."""
    got = _current.get()
    if got is None:
        return
    tr, ambient = got
    t1_ns = max(t0_ns, t1_ns)
    AGGREGATE.add(name, t1_ns - t0_ns)
    if tr.sampled:
        tr.end(tr.begin(name, parent if parent is not None else ambient,
                        t0_ns), t1_ns)


def add_wait(ns: int) -> None:
    got = _current.get()
    if got is None:
        return
    tr, parent = got
    tr.add_wait(ns)
    if ns > 0:
        AGGREGATE.add("read_pool_wait", int(ns))
    if tr.sampled and ns > 0:
        now = time.perf_counter_ns()
        sp = tr.begin("read_pool_wait", parent, now - int(ns))
        tr.end(sp, now)


def add_scan(rows: int, nbytes: int = 0) -> None:
    tr = current()
    if tr is not None:
        tr.add_scan(rows, nbytes)


def label(key: str, value: str) -> None:
    tr = current()
    if tr is not None:
        tr.label(key, value)


def annotate(**attrs) -> None:
    """Attach attributes to the innermost OPEN span of this context
    (the device dispatch sites hang their flight-recorder entry here)."""
    got = _current.get()
    if got is None:
        return
    tr, sp = got
    if sp is not None and sp is not tr.root:
        tr.annotate_span(sp, **attrs)


# ------------------------------------------------------- chrome export

def to_chrome(tr: Tracker, resolve=None) -> dict:
    """One trace as Chrome trace-event JSON (loads in Perfetto /
    chrome://tracing).  Spans become complete ("X") events on per-
    thread lanes; follows-from links become flow events ("s"→"f"), and
    when ``resolve(trace_id)`` finds the linked foreign trace still in
    the buffer, its target span is included on a peer process lane so
    "stacked behind a group-mate" is visible in THIS trace's export.
    Untracked residual segments are emitted as explicit slices."""
    end = tr.t1 if tr.t1 is not None else time.perf_counter_ns()
    events: list = []
    tids: dict[int, int] = {}
    with tr._mu:
        spans = list(tr.spans)
    # resolve follows-from targets FIRST: a linked foreign span (the
    # shared group dispatch in the leader's trace) may predate this
    # trace's start, and Chrome timestamps must stay non-negative — the
    # export's time base is the earliest included instant
    foreign: dict[tuple, Span] = {}
    for sp in spans:
        for link in (sp.links or ()):
            key = (link["trace_id"], link["span_id"])
            if key in foreign:
                continue
            src_tr = resolve(link["trace_id"]) if resolve is not None \
                else None
            if src_tr is None:
                continue
            with src_tr._mu:
                src = next((s for s in src_tr.spans
                            if s.span_id == link["span_id"]), None)
            if src is not None:
                foreign[key] = src
    base = min([tr.t0] + [s.t0 for s in foreign.values()])

    def lane(tid: int) -> int:
        if tid not in tids:
            tids[tid] = len(tids) + 1
        return tids[tid]

    def ts(ns: int) -> float:
        return round((ns - base) / 1e3, 3)       # µs

    events.append({"name": "process_name", "ph": "M", "pid": 1,
                   "tid": 0, "ts": 0,
                   "args": {"name": f"request {tr.trace_id}"}})
    flow_id = 0
    for sp in spans:
        t1 = sp.t1 if sp.t1 is not None else end
        args = {"span_id": sp.span_id, "parent_id": sp.parent_id}
        if sp.attrs:
            args.update(sp.attrs)
        events.append({"name": sp.name, "ph": "X", "cat": "request",
                       "pid": 1, "tid": lane(sp.tid), "ts": ts(sp.t0),
                       "dur": round(max(0, t1 - sp.t0) / 1e3, 3),
                       "args": args})
        for link in (sp.links or ()):
            flow_id += 1
            src = foreign.get((link["trace_id"], link["span_id"]))
            if src is not None:
                s1 = src.t1 if src.t1 is not None else end
                events.append({
                    "name": f"{src.name} ({link['trace_id']})",
                    "ph": "X", "cat": "linked", "pid": 2,
                    "tid": lane(src.tid), "ts": ts(src.t0),
                    "dur": round(max(0, s1 - src.t0) / 1e3, 3),
                    "args": {"trace_id": link["trace_id"],
                             "span_id": src.span_id,
                             **(src.attrs or {})}})
                events.append({"name": "follows_from", "ph": "s",
                               "cat": "link", "id": flow_id, "pid": 2,
                               "tid": lane(src.tid), "ts": ts(src.t0)})
                events.append({"name": "follows_from", "ph": "f",
                               "bp": "e", "cat": "link", "id": flow_id,
                               "pid": 1, "tid": lane(sp.tid),
                               "ts": ts(sp.t0)})
    # explicit untracked residual slices (gaps no span covers)
    if tr.root is not None:
        r0 = tr.root.t0
        r1 = tr.root.t1 if tr.root.t1 is not None else end
        ivs = sorted((max(s.t0, r0),
                      min(s.t1 if s.t1 is not None else r1, r1))
                     for s in spans if s is not tr.root)
        cursor = r0
        for a, b in ivs:
            if a > cursor:
                events.append({"name": UNTRACKED_NAME, "ph": "X",
                               "cat": "request", "pid": 1, "tid": 0,
                               "ts": ts(cursor),
                               "dur": round((a - cursor) / 1e3, 3),
                               "args": {}})
            cursor = max(cursor, b)
        if r1 > cursor:
            events.append({"name": UNTRACKED_NAME, "ph": "X",
                           "cat": "request", "pid": 1, "tid": 0,
                           "ts": ts(cursor),
                           "dur": round((r1 - cursor) / 1e3, 3),
                           "args": {}})
    return {"displayTimeUnit": "ms", "traceEvents": events,
            "otherData": {"trace_id": tr.trace_id,
                          "labels": dict(tr.labels)}}


# ------------------------------------------------------- trace buffer

class TraceBuffer:
    """Tail-biased retention of finished traces (/debug/trace).

    Three stores, one lookup: a bounded RECENT ring (every sampled
    request), the SLOWEST ``slow_keep`` per request class (pinned past
    ring eviction — the per-class latency tail an operator actually
    pages on), and every FLAGGED request (errored / late / shed /
    degraded / slow-logged), ring-bounded separately.
    """

    CLASS_MAX = 32          # distinct classes retaining slow pins

    def __init__(self, capacity: int = 256, slow_keep: int = 4):
        self._mu = threading.Lock()
        self._cap = max(4, int(capacity))
        self._slow_keep = max(1, int(slow_keep))
        self._recent: "OrderedDict[str, Tracker]" = OrderedDict()
        # class -> [(total_ns, trace_id)] ascending; LRU over classes
        self._slow: "OrderedDict[str, list]" = OrderedDict()
        self._slow_traces: dict[str, Tracker] = {}
        self._flagged: "OrderedDict[str, tuple]" = OrderedDict()
        self.recorded = 0
        self.slow_logged = 0

    def set_capacity(self, capacity: int) -> None:
        with self._mu:
            self._cap = max(4, int(capacity))
            self._shrink_locked()

    def _shrink_locked(self) -> None:
        while len(self._recent) > self._cap:
            self._recent.popitem(last=False)
        while len(self._flagged) > self._cap:
            tid, _ = self._flagged.popitem(last=False)

    def record(self, tr: Tracker, class_key=None, error: bool = False,
               late: bool = False, shed: bool = False,
               degraded: bool = False, slow: bool = False) -> None:
        if not tr.sampled:
            if slow:
                with self._mu:
                    self.slow_logged += 1
            return
        total = tr.total_ns()
        cls = str(class_key) if class_key is not None else "unclassed"
        flags = [k for k, v in (("error", error), ("late", late),
                                ("shed", shed), ("degraded", degraded),
                                ("slow", slow)) if v]
        with self._mu:
            self.recorded += 1
            if slow:
                self.slow_logged += 1
            self._recent[tr.trace_id] = tr
            self._recent.move_to_end(tr.trace_id)
            if flags:
                self._flagged[tr.trace_id] = (tr, flags)
            # slowest-N per class, classes LRU-bounded
            heap = self._slow.setdefault(cls, [])
            self._slow.move_to_end(cls)
            heap.append((total, tr.trace_id))
            heap.sort()
            self._slow_traces[tr.trace_id] = tr
            # clients may reuse a trace_id: an evicted heap entry must
            # not strip the pin another live entry still references
            while len(heap) > self._slow_keep:
                _, evict = heap.pop(0)
                if not self._slow_refs_locked(evict):
                    self._slow_traces.pop(evict, None)
            while len(self._slow) > self.CLASS_MAX:
                _, old = self._slow.popitem(last=False)
                for _, tid in old:
                    if not self._slow_refs_locked(tid):
                        self._slow_traces.pop(tid, None)
            self._shrink_locked()

    def _slow_refs_locked(self, trace_id: str) -> bool:
        """Any live slow-heap entry still referencing ``trace_id``?
        Bounded: ≤ CLASS_MAX classes × slow_keep entries."""
        return any(tid == trace_id
                   for heap in self._slow.values()
                   for _, tid in heap)

    def get(self, trace_id: str) -> Optional[Tracker]:
        with self._mu:
            tr = self._recent.get(trace_id)
            if tr is None:
                tr = self._slow_traces.get(trace_id)
            if tr is None:
                got = self._flagged.get(trace_id)
                tr = got[0] if got is not None else None
            return tr

    def index(self) -> dict:
        """Listing for /debug/trace: summaries only, newest first."""
        def summ(tr: Tracker, flags=()) -> dict:
            return {"trace_id": tr.trace_id,
                    "total_ms": round(tr.total_ns() / 1e6, 3),
                    "start_unix_s": round(tr.wall_t0, 3),
                    "labels": dict(tr.labels),
                    "spans": len(tr.spans),
                    **({"flags": list(flags)} if flags else {})}
        with self._mu:
            recent = [summ(tr)
                      for tr in reversed(self._recent.values())]
            flagged = [summ(tr, flags)
                       for tr, flags in
                       reversed(self._flagged.values())]
            slow = {cls: [{"trace_id": tid,
                           "total_ms": round(ns / 1e6, 3)}
                          for ns, tid in reversed(heap)]
                    for cls, heap in self._slow.items()}
        return {"recent": recent, "flagged": flagged,
                "slowest_per_class": slow}

    def stats(self) -> dict:
        with self._mu:
            return {"capacity": self._cap,
                    "recent": len(self._recent),
                    "flagged": len(self._flagged),
                    "slow_classes": len(self._slow),
                    "recorded": self.recorded,
                    "slow_logged": self.slow_logged}
