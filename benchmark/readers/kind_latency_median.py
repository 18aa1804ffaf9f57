"""Median, in ms, of the client's latency (``t1 - t0``: the call to the
store alone, as ``run.py end_to_end`` takes it) over the window's served
reads of ONE request kind, in a cell whose sessions send several.  args:
``kind``, a key of the traffic file's ``kinds``.  None where the window
served no read of that kind."""

import statistics


def read(data, args):
    vals = [(r["t1"] - r["t0"]) * 1e3 for r in data["reads"]
            if r.get("kind") == args["kind"]]
    return statistics.median(vals) if vals else None
