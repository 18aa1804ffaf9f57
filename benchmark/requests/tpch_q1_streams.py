"""``tpch_q1`` where TPC-H's query streams meet on ONE ``lineitem``
(cell ``streams-lineitem-sf1-closed4``: four sessions each cycling Q1,
Q6 and Q15's view over the same twelve regions).  The plan, the DELTAs'
walk, the reference, the digest and the check are ``tpch_q1``'s, by
import, under its own check names; what is this file's is the question
``prepare`` asks of the program before the cell's first read.

Three scan schemas over twelve regions are 36 columnar cache lines.  A
program whose ``region-cache-capacity`` counts LINES keeps 16 of them
(the cell's TOML, an operator's sizing to the regions a store leads) and
rebuilds a line on three cop tasks in four: measured on a v5e (PR 48,
PERF.md section 6) it answers every read right at 0.3 reads a second,
12 s a read, and its first device launch comes two of the traced
window's three seconds after ``go``: a run of it is five minutes that
may end without a device trace.  So it exits 1 here, in about a minute
(the load comes first), as an older program does on the Q1, Q15 and
refresh cells."""

from __future__ import annotations

import byname

_kind = byname.load("requests", "tpch_q1")

CLASSES = _kind.CLASSES
send, reference = _kind.send, _kind.reference
digest, check = _kind.digest, _kind.check


def __getattr__(name):      # ``plan``, ``DELTAS``, ...: tpch_q1's
    return getattr(_kind, name)


def require_program() -> None:
    """The cache's bound must count regions, a region's lines under its
    scan schemas kept and evicted together: the program that does has a
    bound on the schemas a region holds, by this name."""
    from tikv_tpu.copr import region_cache
    if not hasattr(region_cache, "SCHEMAS_PER_REGION"):
        raise SystemExit(
            "this program's columnar cache bounds lines, not regions "
            "(copr/region_cache.py SCHEMAS_PER_REGION): three plans over "
            "one table's twelve regions are 36 lines against its 16, and "
            "three cop tasks in four would rebuild their line")


def prepare(ctx, client, params):
    require_program()
    return _kind.prepare(ctx, client, params)
