"""Ratio of two counters' rises over the window: ``scale`` · Δnum / Δden,
each rise taken as ``counter_delta`` takes it (when the window's last
read had returned minus at ``go``).  args: ``num`` and ``den``, dotted
paths into {"health": /health, "flight_recorder": /debug/trace's block},
and optionally ``scale``.  Over ``/health`` ``tracing.phases`` and
``tracing.process`` this is a mean per occurrence (Δwall_ms / Δcount) or
a share of the window (Δwall_ms / Δclock_ms).  None where a path is
absent (a program that has no such counter) or Δden ≤ 0 (it did not
occur in the window)."""


def dig(obj, path):
    for key in path.split("."):
        if not isinstance(obj, dict) or key not in obj:
            return None
        obj = obj[key]
    return obj


def read(data, args):
    rises = []
    for path in (args["num"], args["den"]):
        go = dig(data["counters_go"], path)
        end = dig(data["counters_end"], path)
        if go is None or end is None:
            return None
        rises.append(end - go)
    num, den = rises
    if den <= 0:
        return None
    return args.get("scale", 1.0) * num / den
