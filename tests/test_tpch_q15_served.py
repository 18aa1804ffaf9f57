"""TPC-H Q15's view ``revenue`` over ``lineitem`` (benchmark configuration
``tpch-sf1-lineitem-q15-regions96``) at a small size on the CPU: the table
kind's own data from a seed (``l_suppkey`` over the clause's 10,000 values
whatever the row count), pre-split and loaded by the cell's own table kind
into a store built as ``benchmark/rig.py`` builds it, read through gRPC by
``TxnClient.coprocessor_fanout`` with the cell's own request kind.  The
store serves with the Pallas body in interpret mode (as
tests/test_tpch_q1_served.py runs Q1), so what the chip does with a cop
task is what runs here: GROUP BY ``l_suppkey`` in the kernel's dense branch
over a grid of 16,384 slots whose step follows the grid, two date
operands, one int32 product summed exactly, the finalize's scaled plane,
and the reply as a chunk on both serving legs.

Held here: the fanned-out answer against the numpy reference AND the host
pipeline, exactly, at the first, the validation and the last DATE and
for a window no row falls in; every launch ``pallas_hash`` over more than
4,096 slots; 58 DATEs, one kernel build; the fast path's hit on a second
DATE, its reply a chunk; the control; what else is a wrong answer; the
new counters on ``/health`` and in the flight recorder; and the whole flow
of ``benchmark/loadgen.py`` as a child process, with the cell's four new
layer metrics read over its window."""

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
from tikv_tpu.server import wire

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
A_BYTES = 1 << 18           # BLOCK rows up to 4,096 slots, 512 at 16,384
CELL = "q15-lineitem-sf1-closed4"
CONFIG = "tpch-sf1-lineitem-q15-regions96"
Q6_CONFIG = "tpch-sf1-lineitem-regions96"
TABLE_IDS = {"q15": 9925, "loadgen": 9926}
NEW_METRICS = ["kernel.pallas_q15_region_roofline",
               "kernel.slots_per_launch", "reply.chunk_encode_ms",
               "reply.chunk_share"]
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
    return byname.load("requests", "tpch_q15")


@pytest.fixture(scope="module")
def table_kind():
    return byname.load("tables", "lineitem_presplit")


@pytest.fixture(scope="module")
def params():
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        return json.load(f)["kinds"]["tpch_q15"]["params"]


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
    # ... and the one-hot's budget with it, so that the step follows the
    # grid here as it does at full size: 512 rows at 16,384 slots
    mp.setattr(pallas_hash, "A_BYTES", A_BYTES)
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
    spec = table_spec("q15")
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


def read(store, kind, params, index: int, date=None) -> tuple:
    """One read of ``DATES[index]`` (or ``date``) as ``loadgen.py
    request()`` records it → (record, reply)."""
    ctx, client = store.ctx, store.client
    dag = kind.plan(ctx, index, client.tso(), date)
    resp = kind.send(ctx, client, (dag, params["concurrency"], index))
    td = resp.get("time_detail", {})
    labels, phases = td.get("labels", {}), td.get("phases_ms", {})
    rec = {"labels": labels, "phases_ms": phases,
           "ok": resp.get("backend") == "device" and
           "degraded" not in labels and "host_exec" not in phases}
    if rec["ok"]:
        rec["answer"] = kind.digest(ctx, resp, params)
    return rec, resp


def host_rows(store, kind, index: int, date=None) -> list:
    """The host pipeline's rows over the same table in one piece: the
    behavioural reference, Decimal objects all the way."""
    ctx = store.ctx
    c = ctx.cols
    ones = np.ones(ROWS, np.bool_)
    y, m, d = byname.load("tables", "lineitem_presplit").civil_from_days(
        c["l_shipdate"])
    snap = ColumnarTable.from_arrays(ctx.table, np.arange(ROWS), dict(
        {name: Column(EvalType.DECIMAL, c[name].astype(np.int64), ones, 2)
         for name in ("l_extendedprice", "l_discount")},
        l_suppkey=Column(EvalType.INT, c["l_suppkey"].astype(np.int64),
                         ones),
        l_shipdate=Column(
            EvalType.DATETIME,
            ((y << 50) | (m << 46) | (d << 41)).astype(np.uint64), ones)))
    return BatchExecutorsRunner(kind.plan(ctx, index, 0, date),
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
        [4] * len(kind.COLUMNS) == [4] * 4
    entry, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == \
        ["replicas", "scale_factor"]
    assert entry["source"] == config["source"] != q6["source"]
    assert len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    ours, theirs = config["guarantees"], q6["guarantees"]
    assert set(ours) == set(theirs)
    for key in ("isolation", "freshness", "durability", "layout"):
        assert ours[key] == theirs[key]
    assert ours["exactness"] != theirs["exactness"]
    assert set(q6["assumed"]) < set(config["assumed"])
    # the cell reports the ten shared layer metrics and its own four
    mine = sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", ()))
    assert len(mine) == 14
    assert [m for m in mine if m in NEW_METRICS] == NEW_METRICS
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
    # the clause's parameter space and the key's domain
    assert len(kind.DATES) == 58 and kind.DATES[0] == (1993, 1) and \
        kind.DATES[-1] == (1997, 10)
    assert kind.DATES[kind.VALIDATION] == (1996, 1)
    assert kind.SUPPLIERS == 10_000 * tspec["scale_factor"] <= kind.GRID
    # Q15's four columns at go-tpc's types
    table = table_kind.fixture(tspec)
    by_name = {c.name: c.field_type for c in table.columns}
    assert [by_name[n].eval_type for n in kind.COLUMNS] == \
        [EvalType.INT, EvalType.DECIMAL, EvalType.DECIMAL,
         EvalType.DATETIME]


# ------------------------------------------------- answers


CASES = {"first_date": ((1993, 1), None), "validation": ((1996, 1), None),
         "last_date": ((1997, 10), None), "no_row_in_it": (None, (2001, 5))}


@pytest.mark.parametrize("case", sorted(CASES))
def test_q15_equals_the_reference_and_the_host_pipeline(store, kind, params,
                                                        case):
    named, date = CASES[case]
    index = kind.DATES.index(named) if named else 0
    rec, resp = read(store, kind, params, index, date)
    assert rec["ok"], rec
    assert resp["tasks"] == N and rec["labels"]["cop_tasks"] == str(N)
    got = np.frombuffer(rec["answer"], np.int64)
    host = host_rows(store, kind, index, date)
    # every reply a chunk of two int64 planes, the sum at scale 4
    for r in resp["responses"]:
        assert "rows" not in r
        total, key = r["chunk"]["cols"]
        assert (total["t"], total["frac"], key["t"]) == ("i8", 4, "i8")
        assert "frac" not in key and "ok" not in total and "ok" not in key
        assert len(total["v"]) == len(key["v"]) == r["chunk"]["n"]
    if case == "no_row_in_it":
        assert list(got) == [index, 1] and host == []
        assert all(r["chunk"]["n"] == 0 for r in resp["responses"])
    else:
        want = kind.answer(index, *kind.revenue(
            index, kind.sums_by_month(store.ctx)))
        assert np.array_equal(got, want), (list(got), list(want))
        groups = (len(want) - 2) // 2
        assert groups > 100         # ~270 of the 7,200 lines fall in it
        # the host pipeline over the table in one piece says the same,
        # as Decimals of scale 4
        assert len(host) == groups
        assert all(isinstance(v, D) and v.as_tuple().exponent == -4
                   for v, _k in host)
        by_key = {int(k): int(v.scaleb(4)) for v, k in host}
        keys = got[2:2 + groups]
        assert [by_key[int(k)] for k in keys] == list(got[2 + groups:])
        # ... and so do the chunks, turned into rows
        rows = [row for r in resp["responses"]
                for row in wire.chunk_rows(r["chunk"])]
        assert all(isinstance(v, D) and v.as_tuple().exponent == -4 and
                   type(k) is int for v, k in rows)
        merged: dict = {}
        for v, k in rows:
            merged[k] = merged.get(k, 0) + v
        assert merged == {int(k): v for v, k in host}
    # every task on the Pallas body: one key in slot mode dense over a
    # grid past 4,096 slots, its step the grid's, the two dates operands
    recent = store.runner.flight_recorder.items()[-N:]
    assert {e["compile_class"] for e in recent} == {"pallas_hash"}
    assert all(e["params"] == 2 and e["slot_mode"] == "dense" and
               e["keys"] == 1 and 4096 < e["slots"] <= 16384 and
               e["block_rows"] == pallas_hash.block_rows(e["slots"]) < BLOCK
               for e in recent), recent
    assert store.runner.flight_recorder.stats()["faults"] == 0


def test_the_control_is_caught(store, kind, params):
    """The reference with its products in float32 in the program's
    place fails the cell's check, by the answer alone."""
    ctx = store.ctx
    served = {"answer": kind.reference(ctx, params, approx=True).tobytes()}
    checks = kind.check(ctx, [served], params, kind.reference(ctx, params))
    assert failing(checks) == ["tpch_q15.wrong_answers"], checks
    rec, _resp = read(store, kind, params, kind.VALIDATION)
    assert failing(kind.check(ctx, [rec], params, None)) == []


def _as_rows(r):
    r["rows"] = wire.chunk_rows(r.pop("chunk"))


def _float_plane(r):
    total = r["chunk"]["cols"][0]
    total["t"], total["v"] = "f8", total["v"].astype(np.float64)


def _other_scale(r):
    r["chunk"]["cols"][0]["frac"] = 2


def _a_stranger(r):
    key = r["chunk"]["cols"][1]
    key["v"] = np.where(np.arange(len(key["v"])) == 0, 10_001, key["v"])


def _one_missing(r):
    for col in r["chunk"]["cols"]:
        col["v"] = col["v"][1:]


@pytest.mark.parametrize("spoil", [_as_rows, _float_plane, _other_scale,
                                   _a_stranger, _one_missing])
def test_what_else_is_a_wrong_answer(store, kind, params, spoil):
    """Exactness is the form too: a reply in rows where a chunk was
    asked, a float plane, another scale, a key no supplier has, a
    supplier missing, in ONE of the twelve partials."""
    rec, resp = read(store, kind, params, kind.VALIDATION)
    assert failing(kind.check(store.ctx, [rec], params, None)) == []
    spoil(next(r for r in resp["responses"] if r["chunk"]["n"]))
    rec["answer"] = kind.digest(store.ctx, resp, params)
    assert failing(kind.check(store.ctx, [rec], params, None)) == \
        ["tpch_q15.wrong_answers"]


# ------------------------------------------------- one kernel, many DATEs


def test_fifty_eight_dates_share_one_kernel_build(store, kind, params):
    """A new DATE builds nothing: one kernel-cache entry for the feed's
    compile class whatever the window, and the planes are cut once a
    region."""
    read(store, kind, params, 0)
    entries = kernel_entries(store)
    assert len(entries) == 1, entries
    before = health(store)["device_mesh"]["agg_params"]
    stats0 = store.runner.flight_recorder.stats()
    for index in (1, 17, 42, 57):
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
    assert after["param_launches"] - before["param_launches"] == launched
    assert after["slots_sum"] - before["slots_sum"] == 16384 * launched
    assert after["decimal_planes"] == before["decimal_planes"] == 2 * N
    assert after["date_planes"] == before["date_planes"] == N
    # every finalize the one native call (where the extension built)
    fin = health(store)["device_mesh"]["finalize"]
    assert fin["numpy" if fin["native_available"] else "native"] == 0


def test_a_second_date_hits_the_fast_path_and_its_reply_is_a_chunk(
        store, kind, params):
    """The dates are template slots: a region's class is learnt once, a
    task of another DATE hits it, the answer is that DATE's, and the hit
    leaves as a chunk: neither encoder of rows runs."""
    read(store, kind, params, 20)
    h0 = health(store)
    rec, resp = read(store, kind, params, 55)
    assert rec["ok"] and rec["labels"].get("fastpath") == "hit"
    assert failing(kind.check(store.ctx, [rec], params, None)) == []
    assert all("chunk" in r and "rows" not in r for r in resp["responses"])
    h1 = health(store)
    fp0, fp1 = h0["fastpath"], h1["fastpath"]
    served = h1["coprocessor"]["requests_served"] - \
        h0["coprocessor"]["requests_served"]
    assert fp1["hit"] - fp0["hit"] == served == N
    assert fp1["learned"] == fp0["learned"]
    assert fp1["encode"] == fp0["encode"]
    r0, r1 = h0["coprocessor"]["replies"], h1["coprocessor"]["replies"]
    assert r1["chunk"] - r0["chunk"] == N and r1["rows"] == r0["rows"]
    rows = sum(r["chunk"]["n"] for r in resp["responses"])
    assert r1["chunk_rows_sum"] - r0["chunk_rows_sum"] == rows > 0
    assert r1["chunk_bytes_sum"] - r0["chunk_bytes_sum"] == 16 * rows
    enc0, enc1 = (h["tracing"]["phases"]["chunk_encode"] for h in (h0, h1))
    assert enc1["count"] - enc0["count"] == N
    # the client's side of it: the chunk's wrap is inside client_decode
    assert rec["phases_ms"]["client_decode"] >= 0


def test_the_slow_leg_answers_the_same_chunk(store, kind, params):
    """A task that misses the fast path (here: sent with a trace id,
    another wire shape) is answered by the slow leg with the same chunk,
    buffer for buffer, as the fast leg's hit."""
    ctx, client = store.ctx, store.client
    read(store, kind, params, 30)
    ts = client.tso()
    dag = kind.plan(ctx, 31, ts)
    fast = client.coprocessor_fanout(dag, concurrency=15, timeout=120)
    assert fast["time_detail"]["labels"].get("fastpath") == "hit"
    first = dag.ranges[0]
    region = fast["responses"][0]
    slow = client.coprocessor(
        kind.plan(ctx, 31, ts), key_hint=first.start,
        trace_id="q15slowleg")
    assert slow["time_detail"]["labels"].get("fastpath") != "hit"
    assert slow["backend"] == "device" and "rows" not in slow
    # the whole table through one task's plan is refused by no one: the
    # leader of the first region serves its own part
    got = {(c["t"], c.get("frac"), c["v"].tobytes())
           for c in slow["chunk"]["cols"]}
    want = {(c["t"], c.get("frac"), c["v"].tobytes())
            for c in region["chunk"]["cols"]}
    assert got == want and slow["chunk"]["n"] == region["chunk"]["n"] > 0


# ------------------------------------------------- loadgen.py, as run.py runs it


def test_loadgen_child_runs_the_cell_end_to_end(store, tmp_path):
    """``benchmark/loadgen.py`` itself, as a child with the ``warm`` /
    ``go`` / ``done`` hand-shake of ``run.py``, over the cell's own
    traffic file (``warm_s`` apart) and its configuration (the table's
    id apart): the table kind's load, the first read, the probes, the
    warm rounds, a window of one second in which four sessions walk the
    DATEs, the check of every record against the reference for its own
    DATE, and the cell's new layer metrics over the window."""
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
    assert result["checks"] == [["tpch_q15.wrong_answers", 0, 0],
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

    assert metric("reply.chunk_share") == 100.0
    assert metric("kernel.slots_per_launch") == 16384.0
    assert 0 < metric("reply.chunk_encode_ms") < 50
    assert metric("kernel.first_launches_in_window") == 0
    assert metric("fastpath.hit_share") == 100.0
    # the fourth reads the device trace, which only the chip writes: on a
    # window without one it reads nothing and does not raise
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        traced = dict(data, trace=None, traffic=json.load(f),
                      peaks={"hbm_bytes_per_s": 819e9})
    with open(os.path.join(BENCH, "layer_metrics",
                           "kernel.pallas_q15_region_roofline.json")) as f:
        spec = json.load(f)
    assert byname.load("readers", spec["reader"]).read(
        traced, spec["args"]) is None
    # ... and on a program without the counters the others read nothing
    for side in data.values():
        del side["health"]["device_mesh"]["agg_params"]["slots_sum"]
        del side["health"]["coprocessor"]["replies"]
        del side["health"]["tracing"]["phases"]["chunk_encode"]
    for name in NEW_METRICS[1:]:
        assert metric(name) is None
