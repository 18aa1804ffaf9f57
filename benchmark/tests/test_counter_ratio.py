"""The ``counter_ratio`` reader on hand-made counters, and each layer
metric file that uses it on the counters of a CPU rehearsal."""

import glob
import json
import math
import os

import pytest

import byname
import line
import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
counter_ratio = byname.load("readers", "counter_ratio")


def counters(count, wall_ms, clock_ms):
    return {"health": {"tracing": {
        "phases": {"rpc_reply": {"count": count, "wall_ms": wall_ms}},
        "process": {"clock_ms": clock_ms}}}}


GO, END = counters(10, 5.0, 1000.0), counters(110, 55.0, 3000.0)
PHASE = "health.tracing.phases.rpc_reply."
CLOCK = "health.tracing.process.clock_ms"


@pytest.mark.parametrize("args, end, want", [
    ({"num": PHASE + "wall_ms", "den": PHASE + "count"}, END, 0.5),
    ({"num": PHASE + "wall_ms", "den": CLOCK, "scale": 100.0}, END, 2.5),
    ({"num": "health.tracing.phases.gc_pause.wall_ms", "den": CLOCK},
     END, None),
    ({"num": PHASE + "wall_ms", "den": CLOCK + ".deeper"}, END, None),
    ({"num": PHASE + "wall_ms", "den": PHASE + "count"}, GO, None),
], ids=["mean", "share", "absent_num", "absent_den", "den_zero"])
def test_counter_ratio(args, end, want):
    got = counter_ratio.read({"counters_go": GO, "counters_end": end}, args)
    assert got == want


def test_a_program_without_the_block_reads_nothing():
    old = {"health": {"tracing": {"sample": 1.0}}, "flight_recorder": {}}
    assert counter_ratio.read(
        {"counters_go": old, "counters_end": old},
        {"num": PHASE + "wall_ms", "den": PHASE + "count"}) is None


def ratio_metrics():
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] == "counter_ratio":
            out[os.path.basename(path)[:-len(".json")]] = spec
    return out


def test_rehearsal_gives_every_ratio_metric_a_finite_value(
        tmp_path, monkeypatch):
    # the store runs in this process: every span takes the CPU clock,
    # where a 2 s window's few dozen launches might draw none in 16
    from tikv_tpu.utils import trace
    monkeypatch.setattr(trace, "_CPU_SAMPLE_BITS", 0)
    out = os.path.join(str(tmp_path), "agg-closed8")
    rc = run.main(["--workload", "agg-closed8", "--seed", "2147483693",
                   "--seconds", "2", "--trace", "1", "--dry-run-cpu",
                   "--rows", "32768", "--out-dir", out])
    assert rc == 0
    with open(os.path.join(out, "loadgen_result.json")) as f:
        result = json.load(f)
    data = {"counters_go": result["counters_go"],
            "counters_end": result["counters_end"]}
    specs = ratio_metrics()
    assert len(specs) == 11
    values = {name: counter_ratio.read(data, spec["args"])
              for name, spec in specs.items()}
    bad = {n: v for n, v in values.items()
           if not isinstance(v, float) or not math.isfinite(v)}
    assert not bad, values
    # what the readings must satisfy, whatever the machine
    mean_rpc = counter_ratio.read(data, {
        "num": "health.tracing.phases.rpc.wall_ms",
        "den": "health.tracing.phases.rpc.count"})
    mean = {p: counter_ratio.read(data, {
        "num": f"health.tracing.phases.{p}.wall_ms",
        "den": f"health.tracing.phases.{p}.count"})
        for p in ("coalesce_wait", "host_materialize", "d2h_wait")}
    assert values["coalescer.window_ms"] + \
        values["coalescer.dispatch_queue_ms"] <= mean["coalesce_wait"] + 1e-3
    assert values["host.materialize_cpu_ms"] + \
        values["host.materialize_offcpu_ms"] == pytest.approx(
            mean["host_materialize"], abs=1e-2)
    assert values["d2h.device_wait_ms"] + values["d2h.copy_ms"] <= \
        mean["d2h_wait"] + 1e-3
    assert 0 <= values["dispatcher.busy_share"] <= 100
    assert 0 <= values["store.gc_pause_share"] <= 100
    assert mean_rpc > 0 and values["store.cpu_share"] > 0


def test_each_ratio_metric_carries_its_manifest_entry():
    """The entries wait in the files (PERF.md, open questions): what a
    later PR appends to ``per_layer`` is checked here against the
    manifest that is."""
    manifest = line.load_manifest()
    cells = {w["name"] for w in manifest["workloads"]}
    moves = {m["name"] for m in manifest["end_to_end"]}
    layers = {m["layer"] for m in manifest["per_layer"]} | {"store process"}
    declared = {m["name"] for m in manifest["per_layer"]}
    for name, spec in ratio_metrics().items():
        entry = spec["per_layer_entry"]
        assert entry["name"] == name and name not in declared
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["moves"] in moves and entry["layer"] in layers
        assert set(entry["workloads"]) <= cells
        assert entry["source"] in ("program_span", "program_counter")


def test_declared_without_a_value_the_line_is_refused():
    """Why they are not declared by the PR that adds the counters: on a
    program without them (that PR's parent) the reader finds nothing,
    and a declared metric without a value is a refused line."""
    manifest = line.load_manifest()
    entry = next(iter(ratio_metrics().values()))["per_layer_entry"]
    manifest["per_layer"].append(entry)
    cell = entry["workloads"][0]
    values = {n: 1.0 for n in {**line.declared(manifest, cell, "end_to_end"),
                               **line.declared(manifest, cell, "per_layer")}
              if n != entry["name"]}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1, "busy_s": 0.3, "window_s": 3.0}
    with pytest.raises(line.LineError, match=entry["name"]):
        line.build(manifest, cell, True, values, True, 10, 0, device)
