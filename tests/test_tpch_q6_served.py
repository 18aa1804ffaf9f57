"""TPC-H Q6 over ``lineitem`` (benchmark configuration
``tpch-sf1-lineitem-regions96``) at a small size on the CPU: the table
kind's own data from a seed, pre-split and loaded by the cell's own table
kind (all 16 columns, through the native SST encoder) into a store built
as ``benchmark/rig.py`` builds it, read through gRPC by
``TxnClient.coprocessor_fanout`` with the cell's own request kind.  The
store serves with the Pallas body in interpret mode (as
tests/test_pallas_hash_interpret.py runs it: no product knob), so what the
chip does with a cop task is what runs here: scaled DECIMAL planes, the
int32 date plane, five predicates and a product in the kernel, the
constants as its operands.

Held here: the fanned-out answer against the numpy reference AND the host
pipeline, exactly, at the clause's validation tuple, at both ends of every
parameter's range and where no row passes; two tuples, one kernel build;
lanes of one launch that carry different tuples; the fast path's hit on a
second tuple with the constants it extracted; the control; every task's
finalize in the one native call (PR 35), counted on ``/health``; and the
whole flow of ``benchmark/loadgen.py`` as a child process."""

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
from tikv_tpu import native
from tikv_tpu.device import DeviceRunner, pallas_hash
from tikv_tpu.device import aggregate as agg_mod
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.parallel import make_mesh
from tikv_tpu.server import fastpath, wire

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
CELL = "q6-lineitem-sf1-closed4"
CONFIG = "tpch-sf1-lineitem-regions96"
TABLE_IDS = {"q6": 9916, "loadgen": 9917}


def load_config() -> dict:
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def table_spec(name: str) -> dict:
    spec = json.loads(json.dumps(load_config()["table"]))
    spec["table_id"] = TABLE_IDS[name]
    return spec


N = load_config()["table"]["regions"]


@pytest.fixture(scope="module")
def kind():
    return byname.load("requests", "tpch_q6")


@pytest.fixture(scope="module")
def table_kind():
    return byname.load("tables", "lineitem_presplit")


@pytest.fixture(scope="module")
def params():
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        return json.load(f)["kinds"]["tpch_q6"]["params"]


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
    assert config.raftstore.region_split_size_mb == \
        load_config()["table"]["region_split_size_mb"] == 96
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
    spec = table_spec("q6")
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
        client.close()
        srv.stop()
        pd_server.stop()
        mp.undo()


def read(store, kind, params, index: int, tup=None) -> tuple:
    """One read of ``TUPLES[index]`` (or ``tup``) as ``loadgen.py
    request()`` records it → (record, reply)."""
    ctx, client = store.ctx, store.client
    resp = kind.send(ctx, client, (
        kind.plan(ctx, index, client.tso(), tup), params["concurrency"],
        index))
    td = resp.get("time_detail", {})
    labels, phases = td.get("labels", {}), td.get("phases_ms", {})
    rec = {"labels": labels, "phases_ms": phases,
           "ok": resp.get("backend") == "device" and
           "degraded" not in labels and "host_exec" not in phases}
    if rec["ok"]:
        rec["answer"] = kind.digest(ctx, resp, params)
    return rec, resp


def host_answer(store, kind, index: int, tup=None) -> decimal.Decimal:
    """The host pipeline's answer over the same rows: the behavioural
    reference, Decimal objects all the way."""
    ctx = store.ctx
    c = ctx.cols
    ones = np.ones(ROWS, np.bool_)
    y, m, d = byname.load("tables", "lineitem_presplit").civil_from_days(
        c["l_shipdate"])
    snap = ColumnarTable.from_arrays(ctx.table, np.arange(ROWS), {
        "l_quantity": Column(EvalType.DECIMAL,
                             c["l_quantity"].astype(np.int64), ones, 2),
        "l_extendedprice": Column(
            EvalType.DECIMAL, c["l_extendedprice"].astype(np.int64),
            ones, 2),
        "l_discount": Column(EvalType.DECIMAL,
                             c["l_discount"].astype(np.int64), ones, 2),
        "l_shipdate": Column(
            EvalType.DATETIME,
            ((y << 50) | (m << 46) | (d << 41)).astype(np.uint64), ones)})
    (v,), = BatchExecutorsRunner(kind.plan(ctx, index, 0, tup),
                                 snap).handle_request().rows()
    return v


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


def test_the_cells_files_agree_on_the_layout(table_kind, params):
    config = load_config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        traffic = json.load(f)
    tspec = config["table"]
    assert params["regions"] == tspec["regions"] == \
        len(config["measured"]["region_bytes"])
    limit = tspec["region_split_size_mb"] << 20
    assert max(config["measured"]["region_bytes"]) < limit
    assert -(-config["measured"]["table_bytes"] // limit) == tspec["regions"]
    assert traffic["main_kernel"]["rows_per_launch"] == \
        -(-tspec["rows"] // tspec["regions"])
    assert traffic["main_kernel"]["input_plane_bytes_per_row"] == [4] * 4
    entry, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == \
        ["replicas", "scale_factor"]
    assert entry["source"] == config["source"]
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    with open(os.path.join(BENCH, "configs",
                           "int3-10m-regions96.json")) as f:
        theirs = json.load(f)["guarantees"]
    ours = config["guarantees"]
    for key in ("isolation", "freshness", "durability"):
        assert ours[key] == theirs[key]
    assert ours["exactness"].startswith(theirs["exactness"])
    assert set(ours) == set(theirs)
    # all 16 columns at go-tpc's types are in the table
    table = table_kind.fixture(tspec)
    assert len(table.columns) == 17 and table.columns[0].is_pk_handle
    assert [c.field_type.eval_type for c in table.columns[5:9]] == \
        [EvalType.DECIMAL] * 4


# ------------------------------------------------- answers


CASES = {
    "validation": (None, (1994, 6, 24)),
    "lowest": (None, (1993, 2, 24)),
    "highest": (None, (1997, 9, 25)),
    "no_row_passes": ((2005, 6, 24), None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_q6_equals_the_reference_and_the_host_pipeline(store, kind, params,
                                                       case):
    tup, named = CASES[case]
    index = kind.TUPLES.index(named) if named else 0
    fin0 = health(store)["device_mesh"]["finalize"]
    rec, resp = read(store, kind, params, index, tup)
    assert rec["ok"], rec
    # a task's one-slot accumulator is finalized once, natively where
    # the extension built (where no row passes too: one row, NULL)
    fin1 = health(store)["device_mesh"]["finalize"]
    built = native.hash_finalize_packed is not None
    assert fin1["native_available"] is built
    assert fin1["native"] - fin0["native"] == (N if built else 0)
    assert fin1["numpy"] - fin0["numpy"] == (0 if built else N)
    assert resp["tasks"] == N and rec["labels"]["cop_tasks"] == str(N)
    got_index, total, exact = np.frombuffer(rec["answer"], np.int64)
    want = kind.revenue(store.ctx, index, tup=tup)
    assert (got_index, total, exact) == (index, want, 1)
    host = host_answer(store, kind, index, tup)
    if case == "no_row_passes":
        assert want == 0 and host is None
        assert all(r["rows"] == [[None]] for r in resp["responses"])
    else:
        assert want > 0
        assert host == decimal.Decimal(want).scaleb(-4)
        assert host.as_tuple().exponent == -4
    # every task on the Pallas body, its constants operands
    recent = store.runner.flight_recorder.items()[-N:]
    assert {e["compile_class"] for e in recent} == {"pallas_hash"}
    assert all(e["params"] == 5 and e["slot_mode"] == "simple"
               for e in recent)
    assert store.runner.flight_recorder.stats()["faults"] == 0


def test_the_control_is_caught(store, kind, params):
    """The reference with its products in float32 in the program's
    place fails the cell's check, by the answer alone."""
    ctx = store.ctx
    served = {"answer": kind.reference(ctx, params, approx=True).tobytes()}
    checks = kind.check(ctx, [served], params, kind.reference(ctx, params))
    assert failing(checks) == ["tpch_q6.wrong_answers"], checks
    rec, _resp = read(store, kind, params, kind.VALIDATION)
    assert failing(kind.check(ctx, [rec], params, None)) == []


def test_a_float_partial_is_a_wrong_answer(store, kind, params):
    """Exactness is the type too: the right value as a float fails."""
    rec, resp = read(store, kind, params, kind.VALIDATION)
    for r in resp["responses"]:
        r["rows"] = [[None if v is None else float(v) for v in row]
                     for row in r["rows"]]
    rec["answer"] = kind.digest(store.ctx, resp, params)
    assert failing(kind.check(store.ctx, [rec], params, None)) == \
        ["tpch_q6.wrong_answers"]


@pytest.mark.skipif(native.hash_finalize_packed is None,
                    reason="native/fastbuild.cpp did not build here")
def test_no_numpy_runs_over_a_served_tasks_accumulator(store, kind, params,
                                                       monkeypatch):
    """The served finalize is the native call alone: with the numpy
    chain's first step made to raise, every task of a read (whole-feed
    launches and lanes alike) still answers from the device, undegraded."""
    def chain(_parts):
        raise AssertionError("the numpy chain on the served path")
    monkeypatch.setattr(agg_mod, "_sum_parts", chain)
    fin0 = health(store)["device_mesh"]["finalize"]
    for index in (7, 33):
        rec, _resp = read(store, kind, params, index)
        assert rec["ok"], rec
        assert failing(kind.check(store.ctx, [rec], params, None)) == []
    fin1 = health(store)["device_mesh"]["finalize"]
    assert fin1["native"] - fin0["native"] == 2 * N
    assert fin1["numpy"] == fin0["numpy"]


# ------------------------------------------------- one kernel, many tuples


def test_eighty_tuples_share_one_kernel_build(store, kind, params):
    """A new tuple builds nothing: one kernel-cache entry for the feed's
    compile class whatever the constants, ``const_classes`` and
    ``first_launches`` rise once for the class and never again."""
    read(store, kind, params, 0)
    entries = kernel_entries(store)
    assert len(entries) == 1, entries
    before = health(store)["device_mesh"]["agg_params"]
    first0 = store.runner.flight_recorder.stats()["first_launches"]
    launches0 = store.runner.flight_recorder.stats()["launches"]
    for index in (1, 17, 42, 79):
        rec, _resp = read(store, kind, params, index)
        assert rec["ok"]
        assert failing(kind.check(store.ctx, [rec], params, None)) == []
    after = health(store)["device_mesh"]["agg_params"]
    stats = store.runner.flight_recorder.stats()
    assert kernel_entries(store) == entries
    assert after["const_classes"] == before["const_classes"] >= 1
    assert stats["first_launches"] == first0
    launched = stats["launches"] - launches0
    assert after["param_launches"] - before["param_launches"] == launched
    # the planes were cut once a region, not once a tuple
    assert after["decimal_planes"] == before["decimal_planes"] == 3 * N
    assert after["date_planes"] == before["date_planes"] == N


def test_lanes_of_one_launch_carry_their_own_tuples(store, kind, params):
    """Closed groups of one compile class leave as lanes of one launch
    (PR 33) whatever their constants: two reads of DIFFERENT tuples, the
    dispatcher held until their tasks' groups have closed, each get
    their own answer."""
    coal = store.node.endpoint.coalescer
    for index in (3, 4):            # warm: classes learnt, lanes built
        read(store, kind, params, index)
    t_end = time.monotonic() + 120
    while time.monotonic() < t_end:
        progs = [e.get("lane_progs") for k, e in
                 store.runner._kernel_cache.items()
                 if isinstance(k, tuple) and k[:1] == ("hashpl",)
                 and isinstance(e, dict)]
        if progs and all(p and all(v is not None for v in p.values())
                         for p in progs):
            break
        time.sleep(0.05)
    gate = threading.Event()
    take = coal._take_fusable

    def gated(g):
        gate.wait(30)
        return take(g)

    lanes0 = health(store)["device_mesh"]["lanes"]
    coal._take_fusable = gated
    out = {}
    try:
        threads = [threading.Thread(
            target=lambda i=i: out.__setitem__(
                i, read(store, kind, params, i))) for i in (10, 55)]
        for t in threads:
            t.start()
        # (eight handlers: at most seven groups can close behind the
        # one the dispatcher holds; four are lanes enough)
        t_end = time.monotonic() + 10
        while len(coal._ready) < 4 and time.monotonic() < t_end:
            time.sleep(0.01)
        gate.set()
        for t in threads:
            t.join()
    finally:
        gate.set()
        coal._take_fusable = take
    lanes1 = health(store)["device_mesh"]["lanes"]
    assert lanes1["multi_lane_launches"] > lanes0["multi_lane_launches"]
    assert lanes1["launch_failures"] == 0
    for index in (10, 55):
        rec, _resp = out[index]
        assert rec["ok"], rec
        got_index, total, exact = np.frombuffer(rec["answer"], np.int64)
        assert (got_index, total, exact) == \
            (index, kind.revenue(store.ctx, index), 1)
    assert kind.revenue(store.ctx, 10) != kind.revenue(store.ctx, 55)
    assert store.runner._arena.pinned_bytes() == 0


# ------------------------------------------------- the fast path


def test_a_second_tuple_hits_the_fast_path(store, kind, params):
    """DECIMAL and DATE constants are template slots: a region's class
    is learnt once, a task of another tuple hits it, and the answer is
    that tuple's."""
    read(store, kind, params, 20)
    fp0 = health(store)["fastpath"]
    served0 = health(store)["coprocessor"]["requests_served"]
    rec, _resp = read(store, kind, params, 61)
    assert rec["ok"] and rec["labels"].get("fastpath") == "hit"
    assert failing(kind.check(store.ctx, [rec], params, None)) == []
    fp1 = health(store)["fastpath"]
    served = health(store)["coprocessor"]["requests_served"] - served0
    assert fp1["hit"] - fp0["hit"] == served == N
    assert fp1["learned"] == fp0["learned"]


def test_warm_reads_find_in_one_probe_and_walk_no_key(store, kind, params):
    """Twelve region classes that share every byte up to their ranges:
    a warm task's class is found by its ``context`` in ONE ``match``,
    and the DAG it builds arrives with its class's keys, its plan key
    re-stamped with the tuple's constants: nothing downstream walks the
    expression tree (``/health`` ``fastpath.find`` / ``keys``)."""
    for index in (30, 31):
        read(store, kind, params, index)
    fp0 = health(store)["fastpath"]
    for index in (32, 70, 5):
        rec, _resp = read(store, kind, params, index)
        assert rec["ok"] and rec["labels"].get("fastpath") == "hit"
        assert failing(kind.check(store.ctx, [rec], params, None)) == []
    fp1 = health(store)["fastpath"]
    finds = fp1["find"]["finds"] - fp0["find"]["finds"]
    assert finds == fp1["hit"] - fp0["hit"] == 3 * N
    assert fp1["find"]["probes"] - fp0["find"]["probes"] == finds
    assert fp1["keys"]["carried"] - fp0["keys"]["carried"] == finds
    assert fp1["keys"]["walked"] == fp0["keys"]["walked"]
    assert fp1["classes"] >= N


def test_the_template_extracts_the_constants_it_renders(store, kind):
    """The wire template of one tuple matches another's bytes, extracts
    its five constants, and renders them back to the same bytes."""
    ctx = store.ctx

    def raw(index):
        dag = kind.plan(ctx, index, 1000 + index)
        return wire.pack({"tp": 103, "dag": wire.enc_dag(dag)})

    learnt, other = raw(0), raw(79)
    marked, n_const = fastpath._mark_slots(wire.unpack(learnt))
    assert n_const == 5
    template = fastpath.WireTemplate(*fastpath._encode_segments(marked))
    values = template.match(other)
    year, disc, qty = kind.TUPLES[79]
    D = decimal.Decimal
    assert values[2:5] == [D(disc - 1).scaleb(-2), D(disc + 1).scaleb(-2),
                           D(qty)]
    assert [v >> 50 for v in values[:2]] == [year, year + 1]
    assert template.render(values) == other
    assert template.render(template.match(learnt)) == learnt
    # another scale is another class: a miss, never a mis-extraction
    dag = kind.plan(ctx, 0, 5, tup=(1994, 6, 24))
    d = wire.enc_dag(dag)
    d["execs"][1]["conds"][4]["ch"][1]["v"] = D("24.0")
    assert template.match(wire.pack({"tp": 103, "dag": d})) is None


# ------------------------------------------------- loadgen.py, as run.py runs it


def test_loadgen_child_runs_the_cell_end_to_end(store, tmp_path):
    """``benchmark/loadgen.py`` itself, as a child with the ``warm`` /
    ``go`` / ``done`` hand-shake of ``run.py``, over the cell's own
    traffic file (``warm_s`` apart) and its configuration (the table's
    id apart): the table
    kind's load, the first read, the probes, the warm rounds, a window
    of one second in which four sessions walk the tuples, the check of
    every record against the reference for its own tuple."""
    config = load_config()
    config["table"]["table_id"] = TABLE_IDS["loadgen"]
    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(config))
    # the cell's own traffic, its warm rounds cut short: four sessions
    # of twelve tasks each saturate this box's cores, and other tests'
    # clocks run beside this one
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
    assert result["checks"] == [["tpch_q6.wrong_answers", 0, 0],
                                ["regions.reads_off_the_layout", 0, 0]]
    assert result["records"] and all(r["ok"] for r in result["records"]), \
        [r["why"] for r in result["records"] if not r["ok"]][:3]
    assert all(r["ok"] for r in result["last"])
    assert all(r["labels"]["cop_tasks"] == str(N)
               for r in result["records"])
    go, end = result["counters_go"], result["counters_end"]
    # what the cell's four new layer metrics read, over the window
    data = {"counters_go": go, "counters_end": end}
    for name, want in (("kernel.first_launches_in_window", 0),
                       ("kernel.param_launch_share", 100.0)):
        with open(os.path.join(BENCH, "layer_metrics",
                               f"{name}.json")) as f:
            metric = json.load(f)
        got = byname.load("readers", metric["reader"]).read(
            data, metric["args"])
        assert got == want, (name, got)
    with open(os.path.join(BENCH, "layer_metrics",
                           "fastpath.hit_share.json")) as f:
        metric = json.load(f)
    share = byname.load("readers", metric["reader"]).read(
        data, metric["args"])
    assert share is not None and share >= 90.0, share
