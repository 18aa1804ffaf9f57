"""From a profiler trace (``.xplane.pb``) to device busy time, time per
device operation and the longest idle gaps.  Read with nothing but
``jax.profiler.ProfileData``; ``reduce_events`` takes plain tuples so
the tests run it on a recorded cut without JAX.

What the planes look like on a v5e is written down in PERF.md section 3,
from the first trace opened by hand."""

from __future__ import annotations

import glob
import json
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
# the lines of a device plane that this reads: every operation that ran
# (busy time, kernel time), and the jitted programs they belong to
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_planes(path: str) -> dict:
    """{plane name: {line name: [(name, start_ns, duration_ns)]}}."""
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return out


def describe(planes: dict, top: int = 12) -> dict:
    """What a hand reading wants: per plane and line the event count,
    the span of its times and the names with most time."""
    out = {}
    for pname, lines in planes.items():
        out[pname] = {}
        for lname, events in lines.items():
            if not events:
                out[pname][lname] = {"events": 0}
                continue
            by_name: dict = {}
            for name, _s, d in events:
                c = by_name.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] += d
            out[pname][lname] = {
                "events": len(events),
                "first_start_ns": min(e[1] for e in events),
                "last_end_ns": max(e[1] + e[2] for e in events),
                "top": [[n, c[0], c[1] / 1e9] for n, c in sorted(
                    by_name.items(), key=lambda kv: -kv[1][1])[:top]]}
    return out


def union_s(intervals, lo: float, hi: float) -> tuple:
    """Length in seconds of the union of [start, end) ns intervals
    clipped to [lo, hi], and the gaps between them inside [lo, hi]."""
    busy = 0.0
    gaps = []
    edge = lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > edge:
            gaps.append((edge, s))
        if e > edge:
            busy += e - max(s, edge)
            edge = e
    if hi > edge:
        gaps.append((edge, hi))
    return busy / 1e9, gaps


def reduce_events(device_ops: dict, host_events: list,
                  window_ns: tuple | None = None) -> dict:
    """``device_ops``: {device plane: [(name, start_ns, dur_ns)]} from
    each device's operations line.  ``host_events``: the same tuples
    from the host's planes, used only to name idle gaps.  The window is
    what the trace's own events span unless given: the device's and the
    host's events share the trace's clock, the caller's clock does not.

    → busy_s (mean over devices of the union of operation intervals),
    window_s, ops {name: [count, seconds]} summed over devices, and the
    longest gaps with the host event that covered most of each."""
    everything = [e for ops in device_ops.values() for e in ops]
    if not everything:
        raise ValueError("the trace holds no device operation")
    if window_ns is None:
        spans = everything + list(host_events)
        window_ns = (min(e[1] for e in spans),
                     max(e[1] + e[2] for e in spans))
    lo, hi = window_ns
    busy, gaps_all = [], []
    for events in device_ops.values():
        b, gaps = union_s([(s, s + d) for _n, s, d in events], lo, hi)
        busy.append(b)
        gaps_all += gaps
    ops = totals(device_ops, lo, hi)
    longest = sorted(gaps_all, key=lambda g: g[0] - g[1])[:5]
    return {"busy_s": sum(busy) / len(busy), "window_s": (hi - lo) / 1e9,
            "ops": ops,
            "idle_gaps": [[_covering(host_events, g), (g[1] - g[0]) / 1e9]
                          for g in longest]}


def totals(events_by_plane: dict, lo: float = float("-inf"),
           hi: float = float("inf")) -> dict:
    """{name: [count, seconds]} of the events that touch [lo, hi],
    summed over the planes."""
    out: dict = {}
    for events in events_by_plane.values():
        for name, s, d in events:
            if s + d <= lo or s >= hi:
                continue
            c = out.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += d / 1e9
    return out


def _covering(host_events: list, gap: tuple) -> str:
    """Name of the host event that covers at least half of the gap.
    Where none does, no XLA or PjRt call was in progress: the host was
    in the program's own Python, or waiting for a request."""
    best, best_ns = "host_outside_xla", 0.5 * (gap[1] - gap[0])
    for name, s, d in host_events:
        over = min(s + d, gap[1]) - max(s, gap[0])
        if over >= best_ns:
            best, best_ns = name, over
    return best


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO line (``%x = s32[..]
    custom-call(...)``), a program's is ``jit_f(<hash>)``: keep the part
    that is stable from run to run."""
    return name.split(" = ", 1)[0].split("(", 1)[0][:80]


def reduce_file(path: str) -> dict:
    planes = read_planes(path)
    device = {p: lines for p, lines in planes.items()
              if p.startswith(DEVICE_PLANE_PREFIX)}

    def line_of(which):
        got = {p: [(short_name(n), s, d) for n, s, d in lines.get(which, [])]
               for p, lines in device.items()}
        return {p: ev for p, ev in got.items() if ev}
    host = [e for p, lines in planes.items()
            if p.startswith(HOST_PLANE_PREFIX)
            for events in lines.values() for e in events if e[2] > 0]
    out = reduce_events(line_of(OPS_LINE), host)
    out["modules"] = totals(line_of(MODULES_LINE))
    return out


def kernel_seconds(ops: dict, match: list) -> tuple:
    """(count, seconds) of the operations (or programs) whose name holds
    any of the ``match`` strings."""
    count, seconds = 0, 0.0
    for name, (c, s) in ops.items():
        if any(m in name for m in match):
            count += c
            seconds += s
    return count, seconds


def main_kernel_ms(trace: dict | None, kernel: dict) -> float | None:
    """Mean device milliseconds of a cell's main kernel: ``kernel`` is
    the traffic file's ``main_kernel`` ({"of": "ops" | "modules",
    "match": [substrings]})."""
    if trace is None:
        return None
    count, seconds = kernel_seconds(trace[kernel["of"]], kernel["match"])
    return 1e3 * seconds / count if count else None


def plan_bytes(rows: int, planes_bytes_per_row: list) -> int:
    """Bytes the plan must read from HBM: every row of every input
    plane once.  What the algorithm needs, not what a kernel moves."""
    return rows * sum(planes_bytes_per_row)


if __name__ == "__main__":
    import sys
    print(json.dumps(describe(read_planes(sys.argv[1])), indent=1))
