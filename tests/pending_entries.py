"""The metric files that wait for their manifest entries (PR 36's
convention), for the tests that run each reader over a load generator
child's result: tests/test_regions96_served.py, for the files whose
cells write, tests/test_tpch_q1_refresh_served.py, and for those of the
cell under an HBM budget, tests/test_streams_hbm_served.py.

A counter and the metric that reads it cannot land in one PR: line.py
refuses a traced line that lacks a declared metric, and the driver makes
the traced run on the parent too, whose program has no such counter
(PERF.md section 7, row 1a).  So a PR that brings a source brings the
metric's file complete, with its manifest entry under ``pending_entry``;
a later benchmark PR renames the key to ``per_layer_entry`` (which
tests/test_benchmark_manifest.py holds equal to the manifest) and adds
the entry."""

import glob
import json
import math
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
REGIONS = "agg-regions96-closed4"
REFRESH = "q1-refresh-lineitem-sf1-closed4"
HBM = "streams-hbm164-lineitem-sf1-closed4"


def pending_metrics(cell: str = None) -> dict:
    """{name: the file}; with ``cell``, the files that child's result is
    the one to read over: a file is taken on a cell of its OWN
    ``workloads``, the regions cell's where it lists it (or none of
    the three: the read-only child reads them as it always has)."""
    out = {}
    for path in sorted(glob.glob(os.path.join(BENCH, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if "pending_entry" not in spec:
            continue
        cells = spec["pending_entry"]["workloads"]
        home = next((c for c in (REFRESH, HBM)
                     if c in cells and REGIONS not in cells), REGIONS)
        if cell in (None, home):
            out[os.path.basename(path)[:-len(".json")]] = spec
    return out


def read_pending(name: str, spec: dict, data: dict):
    """The file's reader over ``data`` as ``run.py`` builds it from the
    load generator's result file → its value; the file is complete, its
    entry ready for the manifest and not in it."""
    import byname
    assert set(spec) == {"what", "reader", "args", "pending_entry"}
    got = byname.load("readers", spec["reader"]).read(data, spec["args"])
    entry = spec["pending_entry"]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"} and entry["name"] == name
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert set(entry["workloads"]) <= {w["name"]
                                       for w in manifest["workloads"]}
    reported = {m["name"]: m for m in manifest["end_to_end"]}
    assert entry["moves"] in reported
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in ("program_span", "program_counter")
    assert name not in {m["name"] for m in manifest["per_layer"]}
    return got


def finite(got) -> bool:
    return isinstance(got, (int, float)) and not isinstance(got, bool) \
        and math.isfinite(got) and got >= 0
