"""The line validator on good and bad lines."""

import copy
import json

import pytest

import line

MANIFEST = {
    "end_to_end": [
        {"name": "read_p50_ms", "unit": "ms"},
        {"name": "write_p95_ms", "unit": "ms", "workloads": ["w"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "device.idle_share", "unit": "%", "workloads": ["a", "w"]},
        {"name": "feed.patch_ms", "unit": "ms", "workloads": ["w"]}]}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 498770432}
VALUES = {"read_p50_ms": 54.8, "setup_s": 50.4, "write_p95_ms": 31.0,
          "device.idle_share": 89.7, "feed.patch_ms": 3.0, "extra": 1.0}


def build(workload="a", traced=False, values=VALUES, device=DEVICE, **kw):
    return line.build(MANIFEST, workload, traced, values, True, 10, 0,
                      dict(device), **kw)


def test_untraced_line_has_the_cells_end_to_end_metrics_only():
    out = build()
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert out["metrics"] == {"read_p50_ms": {"value": 54.8, "unit": "ms"},
                              "setup_s": {"value": 50.4, "unit": "s"}}
    assert json.loads(line.dumps(out)) == out


def test_traced_line_adds_the_cells_layer_metrics_and_device_times():
    dev = {**DEVICE, "busy_s": 0.3, "window_s": 3.0}
    out = build("w", traced=True, device=dev,
                breakdown={"device_ops": [["k", 0.3]], "idle_gaps": []})
    assert set(out["metrics"]) == {"read_p50_ms", "write_p95_ms", "setup_s",
                                   "device.idle_share", "feed.patch_ms"}
    assert out["breakdown"]["device_ops"] == [["k", 0.3]]


@pytest.mark.parametrize("value", [None, float("nan"), float("inf"), "54.8",
                                   True])
def test_a_metric_without_a_finite_value_is_refused(value):
    with pytest.raises(line.LineError, match="read_p50_ms"):
        build(values={**VALUES, "read_p50_ms": value})


def test_a_missing_metric_is_refused():
    values = {k: v for k, v in VALUES.items() if k != "setup_s"}
    with pytest.raises(line.LineError, match="setup_s"):
        build(values=values)
    dev = {**DEVICE, "busy_s": 0.3, "window_s": 3.0}
    values = {k: v for k, v in VALUES.items() if k != "feed.patch_ms"}
    with pytest.raises(line.LineError, match="feed.patch_ms"):
        build("w", traced=True, values=values, device=dev)


def test_a_wrong_unit_is_refused():
    good = build()
    bad = copy.deepcopy(good)
    bad["metrics"]["read_p50_ms"]["unit"] = "s"
    with pytest.raises(line.LineError, match="unit"):
        line.validate(MANIFEST, "a", False, bad)
    line.validate(MANIFEST, "a", False, good)


@pytest.mark.parametrize("busy,window", [(0, 3.0), (-1.0, 3.0), (3.1, 3.0),
                                         (None, 3.0), (0.3, None),
                                         (float("nan"), 3.0)])
def test_traced_device_times_must_be_above_zero_and_inside_the_window(
        busy, window):
    dev = {**DEVICE, "busy_s": busy, "window_s": window}
    with pytest.raises(line.LineError, match="busy_s|window_s"):
        build(traced=True, device=dev)


def test_a_traced_run_without_device_times_is_refused():
    with pytest.raises(line.LineError):
        build(traced=True)


def test_device_block_and_counts_are_held():
    with pytest.raises(line.LineError, match="memory_peak_bytes"):
        build(device={k: v for k, v in DEVICE.items()
                      if k != "memory_peak_bytes"})
    with pytest.raises(line.LineError, match="attempted"):
        line.build(MANIFEST, "a", False, VALUES, True, 0, 0, dict(DEVICE))


def test_the_repos_manifest_names_a_file_for_every_layer_metric():
    import os
    m = line.load_manifest()
    e2e = {e["name"] for e in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    for metric in m["per_layer"]:
        assert metric["moves"] in e2e
        assert set(metric.get("workloads", cells)) <= cells
        path = os.path.join(line.ROOT, "benchmark", "layer_metrics",
                            f"{metric['name']}.json")
        with open(path) as f:       # and its file names a reader's file
            reader = json.load(f)["reader"]
        assert os.path.exists(os.path.join(
            line.ROOT, "benchmark", "readers", f"{reader}.py"))
        # every cell that reports it also reports the metric it moves
        for cell in metric.get("workloads", cells):
            assert metric["moves"] in line.declared(m, cell, "end_to_end")
