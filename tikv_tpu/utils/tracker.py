"""Per-request tracker: TimeDetail/ScanDetail attribution — now backed
by the causal tracing subsystem in :mod:`tikv_tpu.utils.trace`.

Reference: components/tracker/src/lib.rs:16,32-40 — TiKV allocates a
tracker per request in a slab, layers attribute wall/wait/scan costs to
the current request through a task-local handle, and the accumulated
TimeDetailV2/ScanDetailV2 return on the wire with every response, so a
slow request can be decomposed from the response alone.

This module keeps the historical import surface (every layer does
``from ..utils import tracker`` and calls ``phase``/``add_phase``/
``add_wait``/``add_scan``/``label``/``install``/``adopt``) while the
implementation lives in ``trace.py``: the same ``phase(...)`` call that
used to bump a flat name→ns dict now ALSO opens a timestamped child
span in the request's trace tree, ``adopt()`` carries the tree across
thread handoffs (completion pool, coalescer dispatcher), and the
TimeDetail wire shape is unchanged.  See trace.py for the span model,
follows-from links, and the /debug/trace retention buffer.
"""

from __future__ import annotations

from .trace import (      # noqa: F401 — re-exported compat surface
    READ_ENVELOPE,
    TXN_ENVELOPE,
    Span,
    TraceBuffer,
    Tracker,
    add_phase,
    add_scan,
    add_span,
    add_wait,
    adopt,
    annotate,
    current,
    current_span,
    held,
    hold,
    install,
    label,
    note_accept,
    phase,
    reply_begin,
    reply_close,
    reply_done,
    reply_handoff,
    rpc_task_begin,
    rpc_task_end,
    span,
    timed,
    to_chrome,
    uninstall,
)
