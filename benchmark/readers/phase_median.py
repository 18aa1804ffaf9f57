"""Median, over the window's reads that have it, of one phase of the
response's ``time_detail``.  args: ``phase``."""

import statistics


def read(data, args):
    vals = [r["phases_ms"][args["phase"]] for r in data["reads"]
            if args["phase"] in r["phases_ms"]]
    return statistics.median(vals) if vals else None
