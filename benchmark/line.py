"""The result line: built from the metrics the manifest declares for the
cell and validated against ``BENCHMARK.json`` before it is printed.  A
metric that is missing, null, NaN or of the wrong unit, or a traced run
without device time, raises ``LineError``: the caller prints the
diagnosis on an earlier line and exits non-zero with no result line."""

from __future__ import annotations

import json
import math
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class LineError(Exception):
    pass


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise LineError(f"BENCHMARK.json has no {what} named {name!r}")


def cell_files(manifest: dict, workload: str, root: str = ROOT) -> tuple:
    """(cell, its configuration's file, its traffic mix's file): a
    workload names both, and the harness finds the files by those names."""
    cell = find(manifest["workloads"], workload, "workload")
    entry = find(manifest["configs"], cell["config"], "config")
    return (cell, os.path.join(root, entry["file"]),
            os.path.join(root, "benchmark", "traffic",
                         f"{cell['traffic']}.json"))


def declared(manifest: dict, workload: str, section: str) -> dict:
    """{metric name: unit} that ``section`` declares for this cell."""
    return {m["name"]: m["unit"] for m in manifest[section]
            if workload in m.get("workloads", [workload])}


def build(manifest: dict, workload: str, traced: bool, values: dict,
          correct: bool, attempted: int, failed: int, device: dict,
          breakdown: dict | None = None) -> dict:
    """``values`` is {name: number} for everything measured; the line
    takes from it what the manifest declares for this cell: the
    end-to-end metrics always, the per-layer ones in a traced run."""
    want = declared(manifest, workload, "end_to_end")
    if traced:
        want.update(declared(manifest, workload, "per_layer"))
    metrics = {name: {"value": values.get(name), "unit": unit}
               for name, unit in want.items()}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if traced and breakdown:
        line["breakdown"] = breakdown
    validate(manifest, workload, traced, line)
    return line


def validate(manifest: dict, workload: str, traced: bool, line: dict) -> None:
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            raise LineError(f"line lacks {key!r}")
    if not isinstance(line["correct"], bool):
        raise LineError("correct is not a boolean")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or line[key] < 0:
            raise LineError(f"{key} is not a count: {line[key]!r}")
    if line["attempted"] == 0:
        raise LineError("nothing was attempted in the window")
    want = declared(manifest, workload, "end_to_end")
    if traced:
        want.update(declared(manifest, workload, "per_layer"))
    for name, unit in want.items():
        m = line["metrics"].get(name)
        if m is None:
            raise LineError(f"metric {name!r} is declared for {workload} "
                            f"and missing from the line")
        v = m.get("value")
        if isinstance(v, bool) or not isinstance(v, (int, float)) or \
                not math.isfinite(v):
            raise LineError(f"metric {name!r} has no finite value: {v!r}")
        if m.get("unit") != unit:
            raise LineError(f"metric {name!r} has unit {m.get('unit')!r}, "
                            f"the manifest says {unit!r}")
    dev = line["device"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if dev.get(key) in (None, ""):
            raise LineError(f"device lacks {key!r}")
    if traced:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        for key, v in (("busy_s", busy), ("window_s", window)):
            if not isinstance(v, (int, float)) or not math.isfinite(v):
                raise LineError(f"traced run: device.{key} is {v!r}")
        if not 0 < busy <= window:
            raise LineError(f"traced run: busy_s {busy!r} is not above 0 "
                            f"and at most window_s {window!r}")
    for key, rows in line.get("breakdown", {}).items():
        if key not in ("device_ops", "idle_gaps") or len(rows) > 10:
            raise LineError(f"breakdown.{key}: unknown or over 10 entries")


def dumps(line: dict) -> str:
    return json.dumps(line, separators=(", ", ": "), allow_nan=False)
