"""``tpch_q1`` where TPC-H's query streams meet on a ``lineitem`` that
does NOT fit the feed arena (cell ``streams-hbm164-lineitem-sf1-closed4``:
the streams cell's four sessions over the same twelve regions, 360 MiB of
feeds against ``device-hbm-budget-mb = 164``).  The plan, the DELTAs'
walk, the reference, the digest and the check are ``tpch_q1``'s, by
import, under its own check names; the question of
``tpch_q1_streams`` (the cache's bound must count regions) is asked
first, and then this file's own.

Two cop tasks in three miss their feed here.  A program whose HBM budget
pops a line's whole arena entry takes the line's HOST memo with its
device planes, derives it again at every re-upload and hashes every plane
again: measured on a v5e (PR 53, PERF.md section 6, the parent of that PR
under this cell's files) it answers every read right, in TWO states, run
by run on one machine: 4.28-4.48 reads a second (p50 0.88-0.91 s) in
one, 2.44-3.48 (1.10-1.58 s) in the other, 100-220 reads a window, so
a p95 over five to eleven reads; the driver's six runs of it spread
0.73 reads/s about a median of 3.3 (22%) and 286 ms about a p50 of
1,228 (23%) against a bound of 15%.  A yardstick that wide measures
nothing, and no file of the benchmark can steady a program.  So it exits
1 here, in about a minute (the load comes first), as a program before
PR 48 does on ``streams-lineitem-sf1-closed4``."""

from __future__ import annotations

import byname

_kind = byname.load("requests", "tpch_q1_streams")

CLASSES = _kind.CLASSES
send, reference = _kind.send, _kind.reference
digest, check = _kind.digest, _kind.check


def __getattr__(name):      # ``plan``, ``DELTAS``, ...: tpch_q1's
    return getattr(_kind, name)


def require_program() -> None:
    """The HBM budget must free what it counts and no more: the program
    whose sweep releases a line's device state and keeps its host memo
    names that sweep in its span vocabulary, by this name."""
    _kind.require_program()
    from tikv_tpu.utils import trace_vocab
    if "arena_evict" not in trace_vocab.SPAN_VOCABULARY:
        raise SystemExit(
            "this program's HBM budget evicts a line's host memo with its "
            "device planes (utils/trace_vocab.py SPAN_VOCABULARY has no "
            "arena_evict): with 360 MiB of feeds against 164 MiB every "
            "re-upload would derive and hash its line again, in one of "
            "two states run by run: no yardstick")


def prepare(ctx, client, params):
    require_program()
    return _kind.prepare(ctx, client, params)
