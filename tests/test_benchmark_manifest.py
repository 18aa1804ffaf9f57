"""BENCHMARK.json declares a layer metric by the entry its own file
carries: ``benchmark/layer_metrics/<name>.json``'s ``per_layer_entry``
(the ratio metrics of /health ``tracing.phases`` / ``tracing.process``,
declared one PR after the counters they read so that both sides of a
comparison have them)."""

import functools
import glob
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def entries() -> dict:
    out = {}
    for path in sorted(glob.glob(os.path.join(
            ROOT, "benchmark", "layer_metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if "per_layer_entry" in spec:
            out[os.path.basename(path)[:-len(".json")]] = spec
    return out


@pytest.mark.parametrize("name", sorted(entries()))
def test_manifest_declares_the_files_own_entry(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    declared = [m for m in manifest["per_layer"] if m["name"] == name]
    assert declared == [entries()[name]["per_layer_entry"]]
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert set(declared[0]["workloads"]) <= set(cells)
    assert declared[0]["moves"] in {m["name"] for m in manifest["end_to_end"]}
