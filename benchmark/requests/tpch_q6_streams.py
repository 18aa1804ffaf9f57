"""``tpch_q6`` where TPC-H's query streams meet on ONE ``lineitem``
(cell ``streams-lineitem-sf1-closed4``): everything is ``tpch_q6``'s,
by import, under its own check names, but the question ``prepare`` asks
of the program first, which is ``tpch_q1_streams.require_program``'s
(the cache's bound must count regions: that file says why)."""

from __future__ import annotations

import byname

_kind = byname.load("requests", "tpch_q6")
_require_program = byname.load("requests", "tpch_q1_streams").require_program

CLASSES = _kind.CLASSES
send, reference = _kind.send, _kind.reference
digest, check = _kind.digest, _kind.check


def __getattr__(name):      # ``plan``, ``VALIDATION``, ...: tpch_q6's
    return getattr(_kind, name)


def prepare(ctx, client, params):
    _require_program()
    return _kind.prepare(ctx, client, params)
