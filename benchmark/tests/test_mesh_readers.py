"""What ``agg-mesh4-closed8`` adds to the harness, on a synthetic
reduction of four device planes: the per-shard mean of an operation or a
program (``trace_op_ms``), the roofline of one chip's share of the rows,
the ``counter_ratio`` data file (the mean ``device_dispatch`` a launch),
the request kind's layout check, and the whole of the cell's traced line
through ``run.per_layer``, on a store with this PR's counters and on one
without them."""

import json
import os

import pytest

import byname
import line
import run
import trace_reduce

ROWS = 10485760
KERNEL = "%pallas_hash_tpu_custom_call.1"
PSUM = "%psum.6"
PROGRAM = "jit_pallas_hash_sharded"
# per plane: two launches; the ragged last shard's kernel is shorter
KERNEL_US = {0: 300.0, 1: 300.0, 2: 300.0, 3: 200.0}
PSUM_US = {0: 20.0, 1: 20.0, 2: 20.0, 3: 120.0}    # the early shard waits


def four_planes() -> dict:
    ops, modules = {}, {}
    for dev in range(4):
        plane = f"/device:TPU:{dev}"
        ops[plane], modules[plane] = [], []
        for launch in range(2):
            t0 = 1e6 * (1 + 10 * launch)
            k, p = 1e3 * KERNEL_US[dev], 1e3 * PSUM_US[dev]
            ops[plane] += [(KERNEL, t0, k), (PSUM, t0 + k, p)]
            modules[plane].append((PROGRAM, t0, k + p))
    return ops, modules


@pytest.fixture
def trace():
    ops, modules = four_planes()
    out = trace_reduce.reduce_events(ops, [("copr:dispatcher_idle", 0.0, 3e7)])
    out["modules"] = trace_reduce.totals(modules)
    return out


def traffic() -> dict:
    with open(os.path.join(line.ROOT, "benchmark", "traffic",
                           "agg-mesh4-closed8.json")) as f:
        return json.load(f)


def peaks() -> dict:
    with open(os.path.join(line.ROOT, "benchmark", "peaks.json")) as f:
        return json.load(f)["TPU v5 lite"]


def phases(group_dispatch, device_dispatch) -> dict:
    row = lambda c, w: {"count": c, "wall_ms": w}      # noqa: E731
    return {"tracing": {"phases": {
        "group_dispatch": row(*group_dispatch),
        "device_dispatch": row(*device_dispatch)},
        "process": {"clock_ms": 0.0, "cpu_ms": 0.0}}}


def counters(launches, group=(0, 0.0), solo=(0, 0.0), new=True) -> dict:
    """``new``: with what PR 27 added to ``/health`` ``device_mesh``;
    without, the block as PR 26's store gives it."""
    mesh = {"shape": {"range": 2, "tile": 2}, "n_devices": 4}
    if new:
        mesh.update(live={"shape": "2x2", "n_devices": 4},
                    sharded_launches=launches, submesh_rebuilds=0,
                    feed_bytes_per_shard=23068672)
    return {"health": {"device_mesh": mesh,
                       "compile_cache": {"requests": 7},
                       **phases(group, solo)},
            "flight_recorder": {"launches": launches, "first_launches": 1,
                                "faults": 0}}


def data_of(trace, go, end) -> dict:
    read = {"t0": 0.0, "t1": 0.034, "rpc_ms": 24.0, "labels": {"mesh": "2x2"},
            "phases_ms": {"coalesce_wait": 7.0, "d2h_wait": 0.2,
                          "host_materialize": 8.0}}
    return {"reads": [read] * 12, "counters_go": go, "counters_end": end,
            "trace": trace, "traffic": traffic(), "rows": ROWS,
            "peaks": peaks(), "stats": {"loadgen_cpu_share": 0.5},
            "setup": {"load_s": 22.0, "first_read_s": 18.0}}


# ----------------------------------------------------------- the readers


def test_trace_op_ms_is_the_mean_over_shards_and_launches(trace):
    read = byname.load("readers", "trace_op_ms").read
    data = {"trace": trace}
    assert read(data, {"of": "ops", "match": ["tpu_custom_call"]}) == \
        pytest.approx((3 * 0.300 + 0.200) / 4)
    assert read(data, {"of": "ops", "match": ["psum", "all-reduce"]}) == \
        pytest.approx((3 * 0.020 + 0.120) / 4)
    assert read(data, {"of": "modules", "match": [PROGRAM]}) == \
        pytest.approx(0.320)
    # eight events each: four planes, two launches
    assert trace["ops"][KERNEL][0] == trace["modules"][PROGRAM][0] == 8
    assert read(data, {"of": "ops", "match": ["no_such_op"]}) is None
    assert read({"trace": None}, {"of": "ops", "match": ["psum"]}) is None


def test_the_per_chip_roofline_uses_a_quarter_of_the_rows(trace):
    data = data_of(trace, counters(40), counters(48))
    per_chip = byname.load("readers", "trace_roofline_share_per_chip").read
    whole = byname.load("readers", "trace_roofline_share").read
    # 21 MB over 819 GB/s is 25.6 us, of a mean 275 us per shard
    want = 100 * (ROWS / 4 * 8 / 819e9) / 0.275e-3
    assert per_chip(data, {}) == pytest.approx(want)
    assert 9.0 < want < 9.6
    # the one-chip reader charges every shard the whole table's bytes
    assert whole(data, {}) == pytest.approx(4 * want)
    # no device_mesh block, no trace: nothing to read, and no raise
    bare = dict(data, counters_go={"health": {}})
    assert per_chip(bare, {}) is None
    assert per_chip(dict(data, trace=None), {}) is None


def layer_metric(name: str):
    """(counter_ratio's read, the args of the metric's data file)"""
    with open(os.path.join(line.ROOT, "benchmark", "layer_metrics",
                           f"{name}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_ratio"
    return byname.load("readers", "counter_ratio").read, spec["args"]


def test_dispatch_ms_is_the_mean_device_dispatch_a_launch():
    read, args = layer_metric("mesh.dispatch_ms")
    go = counters(40, group=(100, 400.0), solo=(100, 90.0))
    end = counters(48, group=(100, 400.0), solo=(108, 98.0))
    assert read(data_of(None, go, end), args) == pytest.approx(1.0)
    assert read(data_of(None, go, go), args) is None
    assert read(data_of(None, {"health": {}}, {"health": {}}), args) is None


# ------------------------------------------------------ the request kind


def test_hash_agg_mesh_is_hash_agg_plus_the_layout():
    mesh, plain = (byname.load("requests", k)
                   for k in ("hash_agg_mesh", "hash_agg"))
    assert mesh.CLASSES == ("pallas_hash",)
    for name in ("prepare", "send", "reference", "digest"):
        # taken by import (each byname.load executes the file anew)
        assert getattr(mesh, name).__code__.co_filename == plain.__file__
        assert getattr(mesh, name).__code__.co_code == \
            getattr(plain, name).__code__.co_code


def test_hash_agg_mesh_check_counts_reads_off_the_mesh():
    import numpy as np
    import types
    mod = byname.load("requests", "hash_agg_mesh")
    ctx = types.SimpleNamespace(rows=6, cols={
        "c0": np.array([3, 1, 3, 2, 1, 3], dtype=np.int64),
        "c1": np.array([10, -5, 7, 961, 960, -1000], dtype=np.int64)})
    params = {"group_by": "c0", "sum": "c1", "mesh": "2x2"}
    ref = mod.reference(ctx, params)
    right = ref.tobytes()
    on = {"answer": right, "labels": {"mesh": "2x2", "backend": "device"}}
    sub = {"answer": right, "labels": {"mesh": "1x1"}}
    bare = {"answer": right, "labels": {"backend": "device"}}
    off_by_one = ref.copy()
    off_by_one[0, 1] += 1
    wrong = {"answer": off_by_one.tobytes(), "labels": {"mesh": "2x2"}}
    checks = mod.check(ctx, [on, sub, bare, wrong, dict(on)], params, ref)
    assert checks == [("hash_agg.wrong_answers", 1, 0),
                      ("mesh.reads_off_the_mesh", 2, 0)]
    assert "wrong" not in on
    assert sub["wrong"] and bare["wrong"] and wrong["wrong"]
    # control.py's record is an answer alone: it is held to the answer
    assert mod.check(ctx, [{"answer": right}], params, ref) == \
        [("hash_agg.wrong_answers", 0, 0), ("mesh.reads_off_the_mesh", 0, 0)]


# --------------------------------------------------- the cell's whole line


@pytest.mark.parametrize("new", [True, False],
                         ids=["this_store", "a_store_before_pr27"])
def test_every_declared_metric_reads_a_value_on_the_cell(trace, new):
    """``line.py`` refuses a traced line that lacks a declared metric,
    and the cell's traced run is made on the parent commit too: each
    metric declared for the cell has to find a value on a store that
    launches whole-mesh reads from the request's thread (no
    ``coalesce_wait`` phase, no ``group_dispatch`` in the window), with
    and without the ``device_mesh`` counters PR 27 added (which is why
    no metric of the cell reads ``sharded_launches`` yet)."""
    manifest = line.load_manifest()
    declared = set(line.declared(manifest, "agg-mesh4-closed8", "per_layer"))
    assert len(declared) == 14 and "coalescer.wait_ms" not in declared
    read = {"t0": 0.0, "t1": 0.05, "rpc_ms": 40.0, "labels": {"mesh": "2x2"},
            "phases_ms": {"d2h_wait": 0.2, "host_materialize": 8.0}}
    go = counters(40, solo=(100, 400.0), new=new)
    end = counters(52, solo=(112, 448.0), new=new)
    data = dict(data_of(trace, go, end), reads=[read] * 12)
    got = run.per_layer(manifest, "agg-mesh4-closed8", data)
    assert set(got) == declared
    assert got["kernel.main_ms"] == pytest.approx(0.275)
    assert 0 < got["kernel.pallas_hash_sharded_roofline"] < 105
    assert got["mesh.allreduce_ms"] == pytest.approx(0.045)
    assert got["mesh.program_ms"] == pytest.approx(0.320)
    assert got["mesh.dispatch_ms"] == pytest.approx(4.0)
    assert got["coalescer.reads_per_launch"] == pytest.approx(1.0)
