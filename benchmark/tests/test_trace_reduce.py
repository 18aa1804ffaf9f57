"""The reducer on a cut of the first trace recorded on the chip."""

import json
import os

import pytest

import trace_reduce

CUT = os.path.join(os.path.dirname(__file__), "data",
                   "agg-closed8-v5e-trace-cut.json")


@pytest.fixture(scope="module")
def cut():
    with open(CUT) as f:
        d = json.load(f)
    as_tuples = lambda evs: [tuple(e) for e in evs]     # noqa: E731
    return {"ops": {p: as_tuples(e) for p, e in d["device_ops"].items()},
            "host": as_tuples(d["host_events"]),
            "window": tuple(d["window_ns"])}


def test_busy_is_the_union_clipped_to_the_window(cut):
    r = trace_reduce.reduce_events(cut["ops"], cut["host"], cut["window"])
    assert r["window_s"] == pytest.approx(0.06)
    # 8 kernel launches of ~1 ms and their copies: read off the cut
    assert r["busy_s"] == pytest.approx(0.007982573, rel=1e-6)
    assert 0 < r["busy_s"] <= r["window_s"]
    # half the window holds less busy time, and never more than itself
    lo, hi = cut["window"]
    half = trace_reduce.reduce_events(cut["ops"], cut["host"],
                                      (lo, lo + (hi - lo) / 2))
    assert 0 < half["busy_s"] < r["busy_s"]
    assert half["busy_s"] <= half["window_s"]


def test_overlapping_operations_count_once():
    ops = {"/device:TPU:0": [("a", 0.0, 10.0), ("b", 5.0, 10.0),
                             ("c", 30.0, 5.0)]}
    r = trace_reduce.reduce_events(ops, [], (0.0, 100.0))
    assert r["busy_s"] == pytest.approx(20e-9)
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [65e-9, 15e-9])


def test_kernel_time_by_name(cut):
    r = trace_reduce.reduce_events(cut["ops"], cut["host"], cut["window"])
    count, seconds = trace_reduce.kernel_seconds(r["ops"],
                                                 ["tpu_custom_call"])
    assert count == 8
    assert seconds == pytest.approx(0.007968086, rel=1e-6)
    assert trace_reduce.kernel_seconds(r["ops"], ["no_such_kernel"]) == (0, 0.0)


def test_gaps_are_named_by_the_host_event_that_covers_them(cut):
    r = trace_reduce.reduce_events(cut["ops"], cut["host"], cut["window"])
    assert len(r["idle_gaps"]) == 5
    assert all(g[1] > 0 for g in r["idle_gaps"])
    # no XLA call lasts as long as the longest gap between launches
    assert r["idle_gaps"][0][0] == "host_outside_xla"


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({"/device:TPU:0": []}, [("h", 0.0, 1.0)])


def test_short_name_and_plan_bytes():
    assert trace_reduce.short_name(
        '%tpu_custom_call.1 = s32[2,32,96]{2,1,0} custom-call(s32[4] %a)'
    ) == "%tpu_custom_call.1"
    assert trace_reduce.short_name("jit_wrapped(1243310220)") == "jit_wrapped"
    assert trace_reduce.plan_bytes(10485760, [4, 4]) == 83886080


def test_the_manifests_device_readers_read_the_cut(cut):
    """Every device-trace metric the manifest declares for agg-closed8,
    through its layer-metric file and its reader, on the recorded cut."""
    import line
    import run
    trace = trace_reduce.reduce_events(cut["ops"], cut["host"], cut["window"])
    with open(os.path.join(line.ROOT, "benchmark", "traffic",
                           "agg-closed8.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(line.ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    counters = {"health": {"compile_cache": {"requests": 7}},
                "flight_recorder": {"launches": 40}}
    after = {"health": {"compile_cache": {"requests": 7}},
             "flight_recorder": {"launches": 48}}
    read = {"t0": 0.0, "t1": 0.04, "rpc_ms": 30.0, "labels": {},
            "phases_ms": {"coalesce_wait": 8.0, "d2h_wait": 0.1,
                          "host_materialize": 10.0}}
    data = {"reads": [read] * 12, "counters_go": counters,
            "counters_end": after, "trace": trace, "traffic": traffic,
            "rows": 10485760, "peaks": peaks,
            "stats": {"loadgen_cpu_share": 0.4},
            "setup": {"load_s": 22.0, "first_read_s": 11.0}}
    manifest = line.load_manifest()
    got = run.per_layer(manifest, "agg-closed8", data)
    assert set(got) == set(line.declared(manifest, "agg-closed8",
                                         "per_layer"))
    assert got["kernel.main_ms"] == pytest.approx(0.996, rel=0.01)
    # 83.9 MB over 819 GB/s is 0.102 ms: a tenth of the kernel's time
    assert got["kernel.pallas_hash_roofline"] == pytest.approx(
        100 * 0.10243 / got["kernel.main_ms"], rel=1e-3)
    assert 0 < got["kernel.pallas_hash_roofline"] < 100
    assert got["device.idle_share"] == pytest.approx(
        100 * (1 - trace["busy_s"] / trace["window_s"]))
    assert got["coalescer.reads_per_launch"] == pytest.approx(1.5)
    assert got["compile.in_window"] == 0
    assert got["service.untracked_ms"] == pytest.approx(10.0)
    # without a trace the device readers find nothing and say so
    data["trace"] = None
    assert "kernel.main_ms" not in run.per_layer(manifest, "agg-closed8",
                                                 data)
