"""TPC-H Q1 over ``lineitem`` (benchmark configuration
``tpch-sf1-lineitem-q1-regions96``) at a small size on the CPU: the table
kind's own data from a seed, pre-split and loaded by the cell's own table
kind into a store built as ``benchmark/rig.py`` builds it, read through
gRPC by ``TxnClient.coprocessor_fanout`` with the cell's own request kind.
The store serves with the Pallas body in interpret mode (as
tests/test_tpch_q6_served.py runs Q6), so what the chip does with a cop
task is what runs here: two CHAR(1) code planes as a composite key in the
kernel's dense branch, four scaled DECIMAL planes, the int32 date plane,
eleven aggregates of which one is summed as two 16-bit limbs, the date an
operand.

Held here: the fanned-out answer against the numpy reference AND the host
pipeline, exactly, at DELTA 60, 90 and 120 and where no row passes; a
reply's key columns bytes and its sums DECIMALs of scales 2 / 2 / 4 / 6;
61 DELTAs, one kernel build; the fast path's hit on a second DELTA; the
control; the new counters on ``/health`` and in the flight recorder; and
the whole flow of ``benchmark/loadgen.py`` as a child process, with the
cell's three new layer metrics read over its window."""

import decimal
import functools
import json
import os
import subprocess
import sys
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

import jax

from tikv_tpu.config import TikvConfig
from tikv_tpu.datatype import Column, EvalType
from tikv_tpu.device import DeviceRunner, pallas_hash
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:       # the table and request kinds import ``byname``
    sys.path.append(BENCH)

import byname  # noqa: E402

ROWS = 7200
SEED = 2600000027           # the driver's seeds are this large
THRESHOLD = 256             # a toy region must still route to the device
SPLIT_MB = 1                # ... and the split checker must still size it
BLOCK = 1 << 10             # a region's 600 rows are one of these
CELL = "q1-lineitem-sf1-closed4"
CONFIG = "tpch-sf1-lineitem-q1-regions96"
Q6_CONFIG = "tpch-sf1-lineitem-regions96"
TABLE_IDS = {"q1": 9918, "loadgen": 9919}
D = decimal.Decimal


def load_config(name: str = CONFIG) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def table_spec(name: str) -> dict:
    spec = json.loads(json.dumps(load_config()["table"]))
    spec["table_id"] = TABLE_IDS[name]
    return spec


N = load_config()["table"]["regions"]


@pytest.fixture(scope="module")
def kind():
    return byname.load("requests", "tpch_q1")


@pytest.fixture(scope="module")
def table_kind():
    return byname.load("tables", "lineitem_presplit")


@pytest.fixture(scope="module")
def params():
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        return json.load(f)["kinds"]["tpch_q1"]["params"]


@pytest.fixture(scope="module")
def store(table_kind):
    pytest.importorskip("grpc")
    from tikv_tpu.raftstore.metapb import Store
    from tikv_tpu.server import (
        Node, PdServer, RemotePdClient, TikvServer, TxnClient,
    )
    mp = pytest.MonkeyPatch()
    mp.setattr(pallas_hash.pl, "pallas_call",
               functools.partial(pallas_hash.pl.pallas_call, interpret=True))
    mp.setattr(pallas_hash, "BLOCK", BLOCK)
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                          chunk_rows=1 << 12)
    runner._is_tpu = True           # lift the CPU gate (agg_bodies)
    runner._block_local = BLOCK
    config = TikvConfig.from_file(os.path.join(ROOT, load_config()["toml"]))
    config.raftstore.region_split_size_mb = SPLIT_MB
    config.coprocessor.device_row_threshold = THRESHOLD
    pd_server = PdServer("127.0.0.1:0")
    pd_server.start()
    pd_addr = f"127.0.0.1:{pd_server.port}"
    node = Node("127.0.0.1:0", RemotePdClient(pd_addr),
                device_runner=runner, config=config)
    srv = TikvServer(node, status_addr="127.0.0.1:0")
    node.addr = f"127.0.0.1:{srv.port}"
    node.pd.put_store(Store(node.store_id, node.addr))
    srv.start()
    client = TxnClient(pd_addr)
    spec = table_spec("q1")
    table = table_kind.fixture(spec)
    cols = table_kind.make(spec, SEED, ROWS)
    table_kind.load(client, node.store_id, table, cols)
    ctx = types.SimpleNamespace(table=table, rows=ROWS, cols=cols)
    gate = threading.Barrier(16)    # the fan-out workers, before any test
    for _ in range(15):
        client._fanout_executor(15).submit(gate.wait)
    gate.wait()
    try:
        yield types.SimpleNamespace(
            node=node, runner=runner, client=client, pd_addr=pd_addr,
            ctx=ctx, status_port=srv.status_server.port)
    finally:
        # (a kernel's lane programs compile on daemon threads beside its
        # first build: a process that exits under one aborts)
        t_end = time.monotonic() + 180
        while time.monotonic() < t_end and any(
                v is None for k, e in runner._kernel_cache.items()
                if isinstance(k, tuple) and k[:1] == ("hashpl",)
                and isinstance(e, dict)
                for v in (e.get("lane_progs") or {}).values()):
            time.sleep(0.05)
        client.close()
        srv.stop()
        pd_server.stop()
        mp.undo()


def read(store, kind, params, index: int, delta=None) -> tuple:
    """One read of ``DELTAS[index]`` (or ``delta``) as ``loadgen.py
    request()`` records it → (record, reply)."""
    ctx, client = store.ctx, store.client
    dag = kind.plan(ctx, index, client.tso(), delta)
    resp = kind.send(ctx, client, (dag, params["concurrency"], index))
    td = resp.get("time_detail", {})
    labels, phases = td.get("labels", {}), td.get("phases_ms", {})
    rec = {"labels": labels, "phases_ms": phases,
           "ok": resp.get("backend") == "device" and
           "degraded" not in labels and "host_exec" not in phases}
    if rec["ok"]:
        rec["answer"] = kind.digest(ctx, resp, params)
    return rec, resp


def host_rows(store, kind, index: int, delta=None) -> list:
    """The host pipeline's rows over the same table in one piece: the
    behavioural reference, Decimal objects all the way."""
    ctx = store.ctx
    c = ctx.cols
    ones = np.ones(ROWS, np.bool_)
    y, m, d = byname.load("tables", "lineitem_presplit").civil_from_days(
        c["l_shipdate"])

    def texts(name, pool):
        out = np.empty(ROWS, dtype=object)
        out[:] = [pool[i] for i in c[name]]
        return Column(EvalType.BYTES, out, ones)

    snap = ColumnarTable.from_arrays(ctx.table, np.arange(ROWS), dict(
        {name: Column(EvalType.DECIMAL, c[name].astype(np.int64), ones, 2)
         for name in ("l_quantity", "l_extendedprice", "l_discount",
                      "l_tax")},
        l_returnflag=texts("l_returnflag", kind.FLAGS),
        l_linestatus=texts("l_linestatus", kind.STATUS),
        l_shipdate=Column(
            EvalType.DATETIME,
            ((y << 50) | (m << 46) | (d << 41)).astype(np.uint64), ones)))
    return BatchExecutorsRunner(kind.plan(ctx, index, 0, delta),
                                snap).handle_request().rows()


def failing(checks) -> list:
    return [name for name, value, limit in checks if value > limit]


def health(store) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{store.status_port}/health", timeout=30) as r:
        return json.loads(r.read())


def kernel_entries(store) -> list:
    return [k for k, e in store.runner._kernel_cache.items()
            if isinstance(k, tuple) and k and k[0] == "hashpl"
            and isinstance(e, dict)]


# ------------------------------------------------- the files of the cell


def test_the_cells_files_agree_on_the_layout(table_kind, params, kind):
    config, q6 = load_config(), load_config(Q6_CONFIG)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        traffic = json.load(f)
    tspec = config["table"]
    # the Q6 configuration's table, loader and TOML, by name and by path
    assert {k: v for k, v in tspec.items() if k != "table_id"} == \
        {k: v for k, v in q6["table"].items() if k != "table_id"}
    assert tspec["table_id"] != q6["table"]["table_id"]
    assert config["toml"] == q6["toml"] and config["chips"] == 1
    assert params["regions"] == tspec["regions"]
    assert traffic["main_kernel"]["rows_per_launch"] == \
        -(-tspec["rows"] // tspec["regions"])
    assert traffic["main_kernel"]["input_plane_bytes_per_row"] == \
        [4] * len(kind.COLUMNS) == [4] * 7
    entry, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == \
        ["replicas", "scale_factor"]
    assert entry["source"] == config["source"] != q6["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    ours, theirs = config["guarantees"], q6["guarantees"]
    assert set(ours) == set(theirs)
    for key in ("isolation", "freshness", "durability", "layout"):
        assert ours[key] == theirs[key]
    assert ours["exactness"] != theirs["exactness"]
    assert set(theirs := q6["assumed"]) < set(config["assumed"])
    # the cell reports the ten shared layer metrics, its own three and
    # (PR 51) the share of the dispatcher's hold that has a name
    mine = sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", ()))
    assert len(mine) == 14 and "dispatcher.hold_named_share" in mine
    assert [m for m in mine if "q1" in m or "planes" in m or
            "composite" in m] == ["kernel.composite_key_launch_share",
                                  "kernel.pallas_q1_region_roofline",
                                  "kernel.planes_per_launch"]
    for m in manifest["per_layer"]:
        if m["name"] in ("kernel.composite_key_launch_share",
                         "kernel.pallas_q1_region_roofline",
                         "kernel.planes_per_launch"):
            assert m["workloads"] == [CELL] and m["layer"] == "kernel launch"
    # Q1's seven columns at go-tpc's types
    table = table_kind.fixture(tspec)
    by_name = {c.name: c.field_type for c in table.columns}
    assert [by_name[n].eval_type for n in kind.COLUMNS] == \
        [EvalType.DECIMAL] * 4 + [EvalType.BYTES] * 2 + [EvalType.DATETIME]
    assert by_name["l_returnflag"].flen == by_name["l_linestatus"].flen == 1


# ------------------------------------------------- answers


CASES = {"delta_60": (60, None), "delta_90": (90, None),
         "delta_120": (120, None), "no_row_passes": (None, 4000)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_q1_equals_the_reference_and_the_host_pipeline(store, kind, params,
                                                       case):
    named, delta = CASES[case]
    index = kind.DELTAS.index(named) if named else 0
    rec, resp = read(store, kind, params, index, delta)
    assert rec["ok"], rec
    assert resp["tasks"] == N and rec["labels"]["cop_tasks"] == str(N)
    got = np.frombuffer(rec["answer"], np.int64)
    host = host_rows(store, kind, index, delta)
    if case == "no_row_passes":
        assert list(got) == [index, 1] and host == []
        assert all(r["rows"] == [] for r in resp["responses"])
    else:
        want = kind.answer(index, kind.sums_by_day(store.ctx))
        assert np.array_equal(got, want), (list(got), list(want))
        assert len(want) == 2 + 4 * 13          # TPC-H's four groups
        # the host pipeline over the table in one piece says the same
        whole = kind.digest(store.ctx, {
            "responses": [{"rows": [list(r) for r in host]}],
            "tpch_q1_delta": index}, params)
        assert whole == rec["answer"]
        # a reply's row: sums DECIMALs of scales 2, 2, 4, 6, the pairs'
        # sums of scale 2, counts integers, keys the columns' bytes
        rows = [row for r in resp["responses"] for row in r["rows"]]
        assert rows
        for row in rows:
            assert [v.as_tuple().exponent if isinstance(v, D) else type(v)
                    for v in row] == [-2, -2, -4, -6, int, -2, int, -2,
                                      int, -2, int, bytes, bytes]
            assert row[-2] in kind.FLAGS and row[-1] in kind.STATUS
    # every task on the Pallas body: a composite key in slot mode dense,
    # the date its one operand, one SUM as limbs
    recent = store.runner.flight_recorder.items()[-N:]
    assert {e["compile_class"] for e in recent} == {"pallas_hash"}
    assert all(e["params"] == 1 and e["slot_mode"] == "dense" and
               e["keys"] == 2 and e["planes"] >= 20 for e in recent), recent
    assert store.runner.flight_recorder.stats()["faults"] == 0


def test_the_control_is_caught(store, kind, params):
    """The reference with its products in float32 in the program's
    place fails the cell's check, by the answer alone."""
    ctx = store.ctx
    served = {"answer": kind.reference(ctx, params, approx=True).tobytes()}
    checks = kind.check(ctx, [served], params, kind.reference(ctx, params))
    assert failing(checks) == ["tpch_q1.wrong_answers"], checks
    rec, _resp = read(store, kind, params, kind.VALIDATION)
    assert failing(kind.check(ctx, [rec], params, None)) == []


def test_a_float_sum_or_a_text_key_is_a_wrong_answer(store, kind, params):
    """Exactness is the type too."""
    for spoil in (lambda row: [float(row[0])] + row[1:],
                  lambda row: row[:-1] + [row[-1].decode()]):
        rec, resp = read(store, kind, params, kind.VALIDATION)
        for r in resp["responses"]:
            r["rows"] = [spoil(list(row)) for row in r["rows"]]
        rec["answer"] = kind.digest(store.ctx, resp, params)
        assert failing(kind.check(store.ctx, [rec], params, None)) == \
            ["tpch_q1.wrong_answers"]


# ------------------------------------------------- one kernel, many DELTAs


def test_sixty_one_deltas_share_one_kernel_build(store, kind, params):
    """A new DELTA builds nothing: one kernel-cache entry for the feed's
    compile class whatever the date, and the planes are cut once a
    region."""
    read(store, kind, params, 0)
    entries = kernel_entries(store)
    assert len(entries) == 1, entries
    before = health(store)["device_mesh"]["agg_params"]
    stats0 = store.runner.flight_recorder.stats()
    for index in (1, 17, 42, 60):
        rec, _resp = read(store, kind, params, index)
        assert rec["ok"]
        assert failing(kind.check(store.ctx, [rec], params, None)) == []
    after = health(store)["device_mesh"]["agg_params"]
    stats = store.runner.flight_recorder.stats()
    assert kernel_entries(store) == entries
    assert after["const_classes"] == before["const_classes"] == 1
    assert stats["first_launches"] == stats0["first_launches"]
    launched = stats["launches"] - stats0["launches"]
    assert launched >= 1
    for name in ("param_launches", "composite_key_launches", "limb_sums"):
        assert after[name] - before[name] == launched, name
    planes = after["planes_sum"] - before["planes_sum"]
    assert planes % launched == 0 and 20 <= planes // launched <= 32
    assert after["decimal_planes"] == before["decimal_planes"] == 4 * N
    assert after["code_planes"] == before["code_planes"] == 2 * N
    assert after["date_planes"] == before["date_planes"] == N


def test_a_second_delta_hits_the_fast_path(store, kind, params):
    """The date is a template slot: a region's class is learnt once, a
    task of another DELTA hits it, and the answer is that DELTA's (its
    reply's bytes keys and DECIMALs through the Python encode)."""
    read(store, kind, params, 20)
    fp0 = health(store)["fastpath"]
    served0 = health(store)["coprocessor"]["requests_served"]
    rec, _resp = read(store, kind, params, 55)
    assert rec["ok"] and rec["labels"].get("fastpath") == "hit"
    assert failing(kind.check(store.ctx, [rec], params, None)) == []
    fp1 = health(store)["fastpath"]
    served = health(store)["coprocessor"]["requests_served"] - served0
    assert fp1["hit"] - fp0["hit"] == served == N
    assert fp1["learned"] == fp0["learned"]
    assert fp1["encode"]["python"] - fp0["encode"]["python"] == N


def test_warm_reads_find_in_one_probe_and_walk_no_key(store, kind, params):
    """The largest plan the cells send (eleven aggregates, a two-column
    key): a warm task's class is found in ONE ``match`` among the twelve
    regions', and its DAG arrives with its keys, so neither the handler
    nor the dispatcher walks that plan again."""
    for index in (30, 31):
        read(store, kind, params, index)
    fp0 = health(store)["fastpath"]
    for index in (32, 60, 0):
        rec, _resp = read(store, kind, params, index)
        assert rec["ok"] and rec["labels"].get("fastpath") == "hit"
        assert failing(kind.check(store.ctx, [rec], params, None)) == []
    fp1 = health(store)["fastpath"]
    finds = fp1["find"]["finds"] - fp0["find"]["finds"]
    assert finds == fp1["hit"] - fp0["hit"] == 3 * N
    assert fp1["find"]["probes"] - fp0["find"]["probes"] == finds
    assert fp1["keys"]["carried"] - fp0["keys"]["carried"] == finds
    assert fp1["keys"]["walked"] == fp0["keys"]["walked"]


def test_a_traced_reply_carries_the_new_span_attributes(store, kind, params,
                                                        table_kind):
    """``decimal_lower`` says what it made structure and what it split,
    ``columnar_build`` the code columns, every ``device_dispatch`` the
    keys and planes of its launch: on a table of its own, so that the
    plan is analysed and the lines are built under this read's trace."""
    spec = table_spec("loadgen")
    spec["table_id"] = 9920
    table = table_kind.fixture(spec)
    cols = table_kind.make(spec, SEED + 1, ROWS)
    table_kind.load(store.client, store.node.store_id, table, cols)
    ctx = types.SimpleNamespace(table=table, rows=ROWS, cols=cols)
    dag = kind.plan(ctx, kind.VALIDATION, store.client.tso())
    resp = store.client.coprocessor_fanout(dag, concurrency=15, timeout=120)
    assert resp["backend"] == "device"
    attrs: dict = {}
    for r in resp["responses"]:     # every task's own trace (sample 1.0)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{store.status_port}/debug/trace/"
                f"{r['trace_id']}", timeout=30) as f:
            for s in json.loads(f.read())["spans"]:
                attrs.setdefault(s["name"], []).append(s.get("attrs", {}))
    lowers = attrs["decimal_lower"]
    assert any(a.get("fixed_consts", 0) >= 2 for a in lowers)
    assert any(a.get("limb_sums") == 1 for a in lowers)
    assert all(a.get("code_cols") == 2 for a in attrs["columnar_build"])
    assert len(attrs["columnar_build"]) == N
    assert all(a["keys"] == 2 and a["planes"] >= 20
               for a in attrs["device_dispatch"])


# ------------------------------------------------- loadgen.py, as run.py runs it


def test_loadgen_child_runs_the_cell_end_to_end(store, tmp_path):
    """``benchmark/loadgen.py`` itself, as a child with the ``warm`` /
    ``go`` / ``done`` hand-shake of ``run.py``, over the cell's own
    traffic file (``warm_s`` apart) and its configuration (the table's
    id apart): the table kind's load, the first read, the probes, the
    warm rounds, a window of one second in which four sessions walk the
    DELTAs, the check of every record against the reference for its own
    DELTA, and the cell's new layer metrics over the window."""
    config = load_config()
    config["table"]["table_id"] = TABLE_IDS["loadgen"]
    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(config))
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        traffic = json.load(f)
    traffic["warm_s"] = 0.5
    traffic_file = tmp_path / "traffic.json"
    traffic_file.write_text(json.dumps(traffic))
    out = tmp_path / "result.json"
    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps({
        "pd_addr": store.pd_addr, "status_port": store.status_port,
        "seed": SEED, "seconds": 1, "rows": ROWS,
        "config_file": str(config_file),
        "traffic_file": str(traffic_file),
        "out": str(out), "on_tpu": False}))
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "loadgen.py"), str(spec_file)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        timer = threading.Timer(300, child.kill)
        timer.start()
        try:
            first = child.stdout.readline()
            assert first.startswith("warm "), (first, child.poll())
            warm = json.loads(first[len("warm "):])
            assert warm["failed"] == 0, warm
            child.stdin.write("go\n")
            child.stdin.flush()
            assert child.stdout.readline().strip() == "done"
            assert child.wait(timeout=60) == 0
        finally:
            timer.cancel()
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        child.stdin.close()
        child.stdout.close()
    result = json.loads(out.read_text())
    assert result["warm_failed"] == 0
    assert result["checks"] == [["tpch_q1.wrong_answers", 0, 0],
                                ["regions.reads_off_the_layout", 0, 0]]
    assert result["records"] and all(r["ok"] for r in result["records"]), \
        [r["why"] for r in result["records"] if not r["ok"]][:3]
    assert all(r["ok"] for r in result["last"])
    assert all(r["labels"]["cop_tasks"] == str(N)
               for r in result["records"])
    data = {"counters_go": result["counters_go"],
            "counters_end": result["counters_end"]}

    def metric(name):
        with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        return byname.load("readers", spec["reader"]).read(data, spec["args"])

    assert metric("kernel.composite_key_launch_share") == 100.0
    assert 20 <= metric("kernel.planes_per_launch") <= 32
    assert metric("kernel.first_launches_in_window") == 0
    # ... and on a program without the counters they read nothing
    for side in data.values():
        for key in ("planes_sum", "composite_key_launches"):
            del side["health"]["device_mesh"]["agg_params"][key]
    assert metric("kernel.composite_key_launch_share") is None
    assert metric("kernel.planes_per_launch") is None
