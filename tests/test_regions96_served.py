"""The table in upstream's regions (benchmark configuration
``int3-10m-regions96``) at a small size on the CPU: ``int_table`` data
from a seed, pre-split and loaded by the cell's own table kind into a
store built as ``benchmark/rig.py`` builds it, read by
``TxnClient.coprocessor_fanout`` through the cell's own request kind.
The fanned-out answer against the numpy reference, the control, the
share test (the regions' partials, each computed alone on its rows, add
up to the whole table's), a split landing between the cut and the send,
what the summary of a read carries, the layout wait's refusal, and the
whole flow of ``benchmark/loadgen.py`` as a child process (``run.py
--dry-run-cpu`` cannot rehearse this cell: its threshold of rows // 4
sends every region to the host)."""

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

from tikv_tpu.codec.keys import table_record_key, table_record_range
from tikv_tpu.config import TikvConfig
from tikv_tpu.device import DeviceRunner
from tikv_tpu.parallel import make_mesh
from tikv_tpu.server import wire
from tikv_tpu.utils import failpoint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:       # the table and request kinds import ``byname``
    sys.path.append(BENCH)

import byname  # noqa: E402
from pending_entries import (  # noqa: E402
    REGIONS, finite, pending_metrics, read_pending,
)

ROWS = 20000
SEED = 2600000027           # the driver's seeds are this large
THRESHOLD = 256             # a toy region must still route to the device
SPLIT_MB = 1                # ... and the split checker must still size it
KEYS = {
    "dense": {"dist": "uniform_dense", "groups": 1024},
    "sparse": {"dist": "uniform_sparse", "groups": 1024,
               "domain_bits": 62},
}
# one table for each test that changes its table's layout
TABLE_IDS = {"dense": 9903, "sparse": 9904, "split": 9905, "tiny": 9906,
             "unsettled": 9907, "loadgen": 9908, "lanes": 9909}
# the Pallas body in interpret mode (``lane_store``): a region's ~3,300
# rows are four of these blocks
LANE_BLOCK = 1 << 10
CELL = "agg-regions96-closed4"


def load_config() -> dict:
    with open(os.path.join(BENCH, "configs", "int3-10m-regions96.json")) as f:
        return json.load(f)


def table_spec(name: str) -> dict:
    spec = json.loads(json.dumps(load_config()["table"]))
    spec["table_id"] = TABLE_IDS[name]
    spec["columns"]["c0"] = KEYS.get(name, KEYS["dense"])
    return spec


N = load_config()["table"]["regions"]


@pytest.fixture(scope="module")
def kind():
    return byname.load("requests", "hash_agg_regions")


@pytest.fixture(scope="module")
def table_kind():
    return byname.load("tables", "int_table_presplit")


@pytest.fixture(scope="module")
def params():
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        return json.load(f)["kinds"]["hash_agg_regions"]["params"]


@pytest.fixture(scope="module")
def store(table_kind):
    """The store as ``benchmark/rig.py`` builds it from the
    configuration's TOML (one chip, the status server beside it), with PD
    in process; the region size limit is cut with the table, so that the
    split checker sizes a region of a few thousand rows as it sizes one
    of 96 MiB."""
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                          chunk_rows=1 << 12)
    yield from serve(table_kind, runner, ("dense", "sparse", "split", "tiny"))


@pytest.fixture(scope="module")
def lane_store(table_kind):
    """The same store with the Pallas hash body serving, in interpret
    mode as tests/test_pallas_hash_interpret.py runs it (``pallas_call``
    patched, the runner's TPU gate lifted on the instance, BLOCK shrunk;
    no product knob): what the chip's store does with a read's six
    tasks, closed groups of one compile class leaving as the lanes of
    one launch, on the CPU."""
    import functools

    from tikv_tpu.device import pallas_hash
    mp = pytest.MonkeyPatch()
    mp.setattr(pallas_hash.pl, "pallas_call",
               functools.partial(pallas_hash.pl.pallas_call, interpret=True))
    mp.setattr(pallas_hash, "BLOCK", LANE_BLOCK)
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                          chunk_rows=1 << 12)
    runner._is_tpu = True           # lift the CPU gate (agg_bodies)
    runner._block_local = LANE_BLOCK
    try:
        yield from serve(table_kind, runner, ("lanes",))
    finally:
        mp.undo()


def serve(table_kind, runner, names):
    pytest.importorskip("grpc")
    from tikv_tpu.raftstore.metapb import Store
    from tikv_tpu.server import (
        Node, PdServer, RemotePdClient, TikvServer, TxnClient,
    )
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
    ctxs = {}
    for name in names:
        spec = table_spec(name)
        table = table_kind.fixture(spec)
        cols = table_kind.make(spec, SEED, ROWS)
        table_kind.load(client, node.store_id, table, cols)
        ctxs[name] = types.SimpleNamespace(table=table, rows=ROWS,
                                           cols=cols)
    # the client's fan-out workers are made as its fan-outs need them
    # and kept: all of them before the first test, for conftest's
    # thread-leak guard
    gate = threading.Barrier(16)
    for _ in range(15):
        client._fanout_executor(15).submit(gate.wait)
    gate.wait()
    yield types.SimpleNamespace(
        node=node, srv=srv, runner=runner, client=client, pd_addr=pd_addr,
        ctxs=ctxs, TxnClient=TxnClient,
        status_port=srv.status_server.port)
    client.close()
    srv.stop()
    pd_server.stop()


def read(store, kind, params, name, client=None) -> tuple:
    """One read as ``benchmark/loadgen.py request()`` records it →
    (record, reply)."""
    client = client or store.client
    ctx = store.ctxs[name]
    resp = kind.send(ctx, client, kind.prepare(ctx, client, params))
    td = resp.get("time_detail", {})
    labels, phases = td.get("labels", {}), td.get("phases_ms", {})
    rec = {"labels": labels, "phases_ms": phases,
           "trace_id": resp.get("trace_id"),
           "ok": resp.get("backend") == "device" and
           "degraded" not in labels and "host_exec" not in phases}
    if rec["ok"]:
        rec["answer"] = kind.digest(ctx, resp, params)
    return rec, resp


def failing(checks) -> list:
    return [name for name, value, limit in checks if value > limit]


def health(store) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{store.status_port}/health", timeout=30) as r:
        return json.loads(r.read())


# ------------------------------------------------- the files of the cell


def test_the_cells_files_agree_on_the_layout(table_kind, params):
    config = load_config()
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        traffic = json.load(f)
    rows = config["table"]["rows"]
    assert params["regions"] == N == \
        len(table_kind.boundaries(rows, N)) + 1
    assert params["concurrency"] == 15
    assert traffic["main_kernel"]["rows_per_launch"] == -(-rows // N)
    # N is what the store's own estimate of the loaded table gives at
    # the split size, and every region reads under it
    measured = config["measured"]
    assert N == -(-measured["table_bytes"] // (96 << 20))
    assert max(measured["region_bytes"]) < 96 << 20
    assert len(measured["region_bytes"]) == N
    with open(os.path.join(BENCH, "configs", "int3-10m.json")) as f:
        base = json.load(f)
    for key in ("isolation", "exactness", "freshness", "durability"):
        assert config["guarantees"][key] == base["guarantees"][key]
    assert "layout" in config["guarantees"]
    assert sorted(config["reduced"]) == ["replicas", "rows"]
    assert {k: v for k, v in config["table"].items()
            if k in ("rows", "columns")} == \
        {k: v for k, v in base["table"].items() if k in ("rows", "columns")}


def test_presplit_layout_is_what_the_store_holds(store, table_kind):
    """PD lists N regions over each table, cut at the row boundaries,
    each led and sized under the limit by the store's split checker."""
    from tikv_tpu.storage.txn_types import encode_key
    for name in ("dense", "sparse"):
        ctx = store.ctxs[name]
        got = table_kind.table_regions(store.client, ctx.table)
        assert len(got) == N and all(ld is not None for _r, ld in got)
        cuts = [encode_key(table_record_key(ctx.table.table_id, h))
                for h in table_kind.boundaries(ROWS, N)]
        assert [r.start_key for r, _ld in got[1:]] == cuts
        assert [r.end_key for r, _ld in got[:-1]] == cuts
        sizes = table_kind.store_sizes(store.client, store.node.store_id,
                                       {r.id for r, _ld in got})
        assert len(sizes) == N
        assert all(0 < s < SPLIT_MB << 20 for s in sizes.values()), sizes


# ------------------------------------------------- served path vs reference


@pytest.mark.parametrize("name", ["dense", "sparse"])
def test_fanned_out_answers_equal_the_numpy_reference(store, kind, params,
                                                      name):
    """Every answer of four concurrent closed-loop sessions equals the
    whole-table reference exactly, each read answered by N cop tasks,
    every one on the device path."""
    ctx = store.ctxs[name]
    records, errors = [], []

    def session():
        client = store.TxnClient(store.pd_addr)
        try:
            for _ in range(3):
                records.append(read(store, kind, params, name, client)[0])
        except Exception as e:      # noqa: BLE001 — reported below
            errors.append(e)
        finally:
            client.close()

    threads = [threading.Thread(target=session) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(records) == 12 and all(r["ok"] for r in records), \
        [r["labels"] for r in records if not r["ok"]]
    checks = kind.check(ctx, records, params, kind.reference(ctx, params))
    assert checks == [("hash_agg.wrong_answers", 0, 0),
                      ("regions.reads_off_the_layout", 0, 0)]
    assert not any(r.get("wrong") for r in records)
    assert {r["labels"]["cop_tasks"] for r in records} == {str(N)}
    want = kind.reference(ctx, params)
    assert len(want) == 1024
    assert np.frombuffer(records[0]["answer"], np.int64).reshape(-1, 3) \
        .tolist() == want.tolist()


@pytest.mark.parametrize("name", ["dense", "sparse"])
def test_the_control_is_caught(store, kind, params, name):
    """Sums served in bfloat16 (``benchmark/control.py``'s record: an
    answer alone) fail the cell by the answer, not by the layout."""
    ctx = store.ctxs[name]
    served = {"answer": kind.reference(ctx, params, approx=True).tobytes()}
    checks = kind.check(ctx, [served], params, kind.reference(ctx, params))
    assert failing(checks) == ["hash_agg.wrong_answers"]
    assert served["wrong"] is True


@pytest.mark.parametrize("name", ["dense", "sparse"])
def test_share_test_partials_add_up_to_the_whole(store, kind, table_kind,
                                                 params, name):
    """Each region's partial, computed alone over its rows in numpy, is
    what its cop task served (the replies come in range order); merged,
    they are the whole-table reference."""
    ctx = store.ctxs[name]
    edges = [0] + table_kind.boundaries(ROWS, N) + [ROWS]
    alone = []
    for lo, hi in zip(edges, edges[1:]):
        part = types.SimpleNamespace(
            rows=hi - lo, cols={c: v[lo:hi] for c, v in ctx.cols.items()})
        alone.append(kind.reference(part, params))
    assert kind.merge(alone).tolist() == \
        kind.reference(ctx, params).tolist()
    _rec, resp = read(store, kind, params, name)
    assert resp["tasks"] == len(resp["responses"]) == N
    for task, want in zip(resp["responses"], alone):
        got = np.array(task["rows"], dtype=np.int64).reshape(-1, 3)
        assert got[np.argsort(got[:, 2], kind="stable")].tolist() == \
            want.tolist()


# ------------------------------------------------- what a read's summary says


def test_summary_is_shaped_like_one_reply(store, kind, params):
    before = health(store)["coprocessor"]["requests_served"]
    rec, resp = read(store, kind, params, "dense")
    assert rec["ok"]
    assert resp["backend"] == "device" and resp["tasks"] == N
    td = resp["time_detail"]
    for phase in ("fanout_cut", "fanout_tasks", "fanout_straggler",
                  "fanout_task", "device_dispatch"):
        assert phase in td["phases_ms"], (phase, td["phases_ms"])
    assert td["phases_ms"]["fanout_tasks"] >= \
        td["phases_ms"]["fanout_task"] > 0
    assert td["phases_ms"]["fanout_straggler"] >= 0
    assert td["total_rpc_wall_ms"] > 0
    assert td["labels"]["cop_tasks"] == str(N)
    assert "fanout_retries" not in td["labels"]
    # the summary's trace is the critical task's: one the store's buffer
    # retains, with the launch and its compile class in it
    # (loadgen.py probe() fetches it and takes a 404 for a crash)
    ids = {r["trace_id"] for r in resp["responses"]}
    assert len(ids) == N and resp["trace_id"] in ids
    with urllib.request.urlopen(
            f"http://127.0.0.1:{store.status_port}/debug/trace/"
            f"{resp['trace_id']}", timeout=30) as r:
        trace = json.loads(r.read())
    launches = [s for s in trace["spans"] if s["name"] == "device_dispatch"]
    assert launches and "compile_class" in launches[0]["attrs"]
    assert health(store)["coprocessor"]["requests_served"] == before + N
    from tikv_tpu.utils.metrics import GRPC_MSG_COUNTER
    assert health(store)["coprocessor"]["requests_served"] == sum(
        GRPC_MSG_COUNTER.labels("Coprocessor", st).value
        for st in ("ok", "err"))


def test_warm_tasks_take_the_compiled_fast_path(store, kind, params):
    """A task's region context is fixed bytes of its wire template: the
    repeats of a region's task hit the fast path as a one-region read
    does, a class a region."""
    for _ in range(3):
        read(store, kind, params, "dense")
    fp = store.node.fastpath.stats()
    before = fp["hit"]
    rec, _resp = read(store, kind, params, "dense")
    assert rec["ok"] and rec["labels"].get("fastpath") == "hit"
    assert store.node.fastpath.stats()["hit"] == before + N


@pytest.mark.parametrize("name", ["dense", "sparse"])
def test_warm_tasks_find_in_one_probe_and_walk_no_key(store, kind, params,
                                                      name):
    """Six region classes a table, two tables in the cache: a warm
    task's class is found by its ``context`` in ONE ``match`` and its
    DAG arrives with its keys (``/health`` ``fastpath.find`` /
    ``keys``)."""
    for _ in range(3):
        read(store, kind, params, name)
    fp0 = health(store)["fastpath"]
    for _ in range(3):
        rec, _resp = read(store, kind, params, name)
        assert rec["ok"] and rec["labels"].get("fastpath") == "hit"
    fp1 = health(store)["fastpath"]
    finds = fp1["find"]["finds"] - fp0["find"]["finds"]
    assert finds == fp1["hit"] - fp0["hit"] == 3 * N
    assert fp1["find"]["probes"] - fp0["find"]["probes"] == finds
    assert fp1["keys"]["carried"] - fp0["keys"]["carried"] == finds
    assert fp1["keys"]["walked"] == fp0["keys"]["walked"]


def test_a_task_on_the_host_fails_the_whole_read(store, kind, params):
    """One task that the runner's host rung served shows in the summary
    (label ``degraded``, phase ``host_exec``), so ``loadgen.py
    request()`` does not count the read."""
    read(store, kind, params, "dense")
    failpoint.cfg("device::before_dispatch", "1*return->off")
    try:
        rec, resp = read(store, kind, params, "dense")
    finally:
        failpoint.teardown()
    assert not rec["ok"]
    assert "degraded" in rec["labels"] and "host_exec" in rec["phases_ms"]
    # the answer is still exact: the host rung serves the same rows
    ctx = store.ctxs["dense"]
    assert kind.digest(ctx, resp, params) == \
        kind.reference(ctx, params).tobytes()


def test_a_stale_region_context_is_refused(store, kind, params):
    """The store serves a task only from the region and epoch it was
    cut for; without a context it serves as it always did."""
    ctx = store.ctxs["dense"]
    dag, _c = kind.prepare(ctx, store.client, params)
    region, leader = store.client._lookup_region(dag.ranges[0].start)
    req = {"tp": 103, "dag": wire.enc_dag(dag), "force_backend": None,
           "paging_size": 0, "resume_token": None,
           "resource_group": "default", "request_source": ""}
    stale = dict(req, context={"region_id": region.id,
                               "version": region.epoch.version + 1})
    with pytest.raises(wire.RemoteError) as e:
        store.client._store_call(leader.store_id, "Coprocessor", stale, 30)
    assert e.value.kind == "epoch_not_match"
    assert e.value.err["current"]["id"] == region.id
    other = dict(req, context={"region_id": region.id + 1000,
                               "version": region.epoch.version})
    with pytest.raises(wire.RemoteError) as e:
        store.client._store_call(leader.store_id, "Coprocessor", other, 30)
    assert e.value.kind == "epoch_not_match"
    ok = dict(req, context=wire.enc_region_ctx(region))
    assert "rows" in store.client._store_call(
        leader.store_id, "Coprocessor", ok, 30)
    assert "rows" in store.client._store_call(
        leader.store_id, "Coprocessor", req, 30)


# ------------------------------------------------- a split under a read


def test_split_between_cut_and_send_is_recut_and_exact(store, kind, params):
    """A region splits after the client cached its bounds: the task cut
    for the old epoch is refused (``epoch_not_match``), its ranges are
    cut again, the answer is exact and the read took N + 1 tasks, which
    the cell's check then fails: the layout is part of the result."""
    ctx = store.ctxs["split"]
    stale = store.TxnClient(store.pd_addr)
    rec, _resp = read(store, kind, params, "split", stale)
    assert rec["ok"] and rec["labels"]["cop_tasks"] == str(N)
    # another client splits the third region in its middle
    per = -(-ROWS // N)
    store.client.split(table_record_key(ctx.table.table_id,
                                        2 * per + per // 2))
    rec, resp = read(store, kind, params, "split", stale)
    assert rec["ok"], rec
    assert resp["tasks"] == N + 1
    assert rec["labels"]["cop_tasks"] == str(N + 1)
    assert rec["labels"]["fanout_retries"] == "1"
    want = kind.reference(ctx, params)
    assert rec["answer"] == want.tobytes()
    checks = kind.check(ctx, [rec], params, want)
    assert checks == [("hash_agg.wrong_answers", 0, 0),
                      ("regions.reads_off_the_layout", 1, 0)]
    assert rec["wrong"] is True
    # the client has learned the new layout: no task is refused again
    rec, _resp = read(store, kind, params, "split", stale)
    stale.close()
    assert rec["labels"]["cop_tasks"] == str(N + 1)
    assert "fanout_retries" not in rec["labels"]


def test_a_region_under_the_row_threshold_fails_the_read(store, kind,
                                                         params):
    """Every region must clear ``device-row-threshold``: a task whose
    region holds fewer rows is served by the host pipeline, and the
    summary's backend is then not ``device``."""
    ctx = store.ctxs["tiny"]
    store.client.split(table_record_key(ctx.table.table_id,
                                        ROWS - THRESHOLD // 2))
    rec, resp = read(store, kind, params, "tiny")
    assert resp["tasks"] == N + 1
    assert resp["backend"] == "host" and not rec["ok"]
    assert [r["backend"] for r in resp["responses"]] == \
        ["device"] * N + ["host"]
    assert kind.digest(ctx, resp, params) == \
        kind.reference(ctx, params).tobytes()


def test_an_abandoned_lock_is_waited_out(store, kind, params):
    """``key_is_locked`` no longer rises to the caller: the fan-out asks
    for the transaction's status, waits while it is alive, and sends the
    task again.  A prewrite nobody commits is rolled back when its TTL
    (3 s) has run out, and the read answers without it."""
    from tikv_tpu.testing.fixture import encode_table_row
    ctx = store.ctxs["dense"]
    key, value = encode_table_row(ctx.table, 7, {"c0": 1, "c1": 1})
    client = store.client
    start_ts = client.tso()
    client._call_leader(key, "KvPrewrite", {
        "mutations": [{"op": "put", "key": key, "value": value}],
        "primary": key, "start_version": start_ts})
    t0 = time.monotonic()
    rec, _resp = read(store, kind, params, "dense")
    assert rec["ok"] and int(rec["labels"]["lock_retries"]) >= 1
    assert rec["phases_ms"]["fanout_lock_wait"] > 100
    assert 1.0 < time.monotonic() - t0 < 30
    assert kind.check(ctx, [rec], params, kind.reference(ctx, params))[0][1] \
        == 0
    rec, _resp = read(store, kind, params, "dense")
    assert rec["ok"] and "lock_retries" not in rec["labels"]


# ------------------------------------------------- the layout wait


def test_layout_wait_raises_with_the_sizes(store, table_kind, monkeypatch):
    """A region that stays over the limit the layout was cut for ends
    the load, loudly and inside its bound, with the sizes it saw."""
    spec = table_spec("unsettled")
    spec["region_split_size_mb"] = 0.05     # ~52 KB: under a region's size
    table = table_kind.fixture(spec)
    cols = table_kind.make(spec, SEED, ROWS)
    monkeypatch.setattr(table_kind, "LAYOUT_WAIT_S", 2.0)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError) as e:
        table_kind.load(store.client, store.node.store_id, table, cols)
    assert time.monotonic() - t0 < 60
    said = str(e.value)
    assert "split checker" in said and "sizes" in said and \
        "limit_bytes" in said, said


def test_a_store_without_the_size_estimate_fails_at_once(store, table_kind,
                                                         monkeypatch):
    """The parent commit's Status lists no ``approximate_size``: the load
    says so before it splits or ingests anything."""
    real = store.client.status

    def old_status(store_id):
        st = real(store_id)
        for r in st["regions"]:
            del r["approximate_size"]
        return st
    monkeypatch.setattr(store.client, "status", old_status)
    spec = table_spec("unsettled")
    table = table_kind.fixture(spec)
    regions = len(real(store.node.store_id)["regions"])
    with pytest.raises(RuntimeError, match="approximate_size"):
        table_kind.load(store.client, store.node.store_id, table,
                        table_kind.make(spec, SEED, 1000))
    assert len(real(store.node.store_id)["regions"]) == regions


# ------------------------------------------------- loadgen.py, as run.py runs it


@pytest.fixture(scope="module")
def loadgen_result(store, tmp_path_factory):
    """``benchmark/loadgen.py`` itself, as a child with the ``warm`` /
    ``go`` / ``done`` hand-shake of ``run.py``, over the cell's own
    traffic file and its configuration (the table's id apart): the table
    kind's load, the first read, the probes (each fetches its trace),
    the warm rounds, a window of one second, the check.  → (the ``warm``
    line, the result file)."""
    tmp_path = tmp_path_factory.mktemp("loadgen")
    config = load_config()
    config["table"]["table_id"] = TABLE_IDS["loadgen"]
    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(config))
    out = tmp_path / "result.json"
    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps({
        "pd_addr": store.pd_addr, "status_port": store.status_port,
        "seed": SEED, "seconds": 1, "rows": ROWS,
        "config_file": str(config_file),
        "traffic_file": os.path.join(BENCH, "traffic", f"{CELL}.json"),
        "out": str(out), "on_tpu": False}))
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "loadgen.py"), str(spec_file)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        timer = threading.Timer(240, child.kill)
        timer.start()
        try:
            first = child.stdout.readline()
            assert first.startswith("warm "), (first, child.poll())
            warm = json.loads(first[len("warm "):])
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
    return warm, json.loads(out.read_text())


def test_loadgen_child_runs_the_cell_end_to_end(loadgen_result):
    warm, result = loadgen_result
    assert warm["failed"] == 0, warm
    assert result["warm_failed"] == 0
    assert result["checks"] == [["hash_agg.wrong_answers", 0, 0],
                                ["regions.reads_off_the_layout", 0, 0]]
    assert result["records"] and all(r["ok"] for r in result["records"]), \
        [r["why"] for r in result["records"] if not r["ok"]][:3]
    assert all(r["ok"] for r in result["last"])
    assert all(r["labels"]["cop_tasks"] == str(N)
               for r in result["records"])
    go, end = result["counters_go"], result["counters_end"]
    tasks = end["health"]["coprocessor"]["requests_served"] - \
        go["health"]["coprocessor"]["requests_served"]
    assert tasks >= N * len(result["records"])
    assert end["flight_recorder"]["launches"] > \
        go["flight_recorder"]["launches"]


# --------------------------- the metrics that wait for their entries (PR 36)
#
# tests/pending_entries.py says why they wait.  Each file is read over a
# child of a cell of its OWN ``workloads``: this one's, or (the files of
# the cell that writes, PR 51) tests/test_tpch_q1_refresh_served.py's.

@pytest.mark.parametrize("name", sorted(pending_metrics(REGIONS)))
def test_a_pending_metric_reads_the_loadgen_childs_result(loadgen_result,
                                                          name):
    """Its reader, over ``data`` as ``run.py`` builds it from the load
    generator's result file, finds its source in this program and gives
    a finite number; its entry is ready for the manifest and not in it."""
    assert CELL == REGIONS
    _warm, result = loadgen_result
    spec = pending_metrics()[name]
    with open(os.path.join(BENCH, "traffic", f"{CELL}.json")) as f:
        traffic = json.load(f)
    data = {"reads": [r for r in result["records"] if r["ok"]],
            "counters_go": result["counters_go"],
            "counters_end": result["counters_end"], "trace": None,
            "traffic": traffic, "rows": ROWS, "peaks": None,
            "stats": {"loadgen_cpu_share": result["loadgen_cpu_share"]},
            "setup": {"load_s": result["load_s"],
                      "first_read_s": result["first_read_s"]}}
    got = read_pending(name, spec, data)
    if name == "mesh.dispatch_lock_wait_ms" and got is None:
        # "did not occur": off a mesh only a launch the coalescer did
        # not stage takes the lock on a request's path
        for path in spec["args"].values():
            assert isinstance(path, str) and \
                path.startswith("health.tracing.phases.dispatch_lock_wait.")
        assert "dispatch_lock_wait" in \
            result["counters_end"]["health"]["tracing"]["phases"]
    else:
        assert finite(got), (name, got)


# ------------------------------------------- a read's tasks as lanes


def held_read(store, kind, params, name="lanes"):
    """One read with the store's dispatcher HELD until the groups of all
    its tasks but the first have closed behind it: the state a busy
    dispatcher finds them in.  → (record, reply)."""
    coal = store.node.endpoint.coalescer
    gate = threading.Event()
    take = coal._take_fusable

    def gated(g):
        gate.wait(30)
        return take(g)

    coal._take_fusable = gated
    out = []
    try:
        t = threading.Thread(
            target=lambda: out.append(read(store, kind, params, name)))
        t.start()
        t_end = time.monotonic() + 20
        while len(coal._ready) < N - 1 and time.monotonic() < t_end:
            time.sleep(0.002)
        gate.set()
        t.join()
    finally:
        gate.set()
        coal._take_fusable = take
    return out[0]


def warm_lanes(store, kind, params, name="lanes"):
    """Reads until every region's kernel class is learnt and the lane
    programs a held read asks for are built (off the dispatcher)."""
    for _ in range(3):
        rec, _resp = read(store, kind, params, name)
        assert rec["ok"], rec
    rec, _resp = held_read(store, kind, params, name)
    assert rec["ok"], rec
    t_end = time.monotonic() + 120
    while time.monotonic() < t_end:
        lanes = health(store)["device_mesh"]["lanes"]
        asked = [e["lane_progs"] for k, e in
                 store.runner._kernel_cache.items()
                 if k[0] == "hashpl" and isinstance(e, dict)
                 and "lane_progs" in e]
        if asked and all(p is not None for d in asked for p in d.values()):
            return lanes
        time.sleep(0.05)
    raise AssertionError(f"lane programs not built: {lanes}")


def test_a_reads_six_tasks_leave_as_lanes_and_health_counts_them(
        lane_store, kind, params):
    """The cell's read on the Pallas body: exact against the numpy
    reference, every task on the fast path, and the six closed groups
    leave as lanes of fewer launches than tasks; ``/health`` says so
    (``coalescer`` block, ``device_mesh.lanes``)."""
    store = lane_store
    warm_lanes(store, kind, params)
    ctx = store.ctxs["lanes"]
    h0 = health(store)
    launches0 = store.runner.flight_recorder.stats()["launches"]
    rec, resp = held_read(store, kind, params)
    assert rec["ok"] and resp["tasks"] == N
    assert rec["labels"].get("fastpath") == "hit"
    checks = kind.check(ctx, [rec], params, kind.reference(ctx, params))
    assert failing(checks) == [], checks
    h1 = health(store)
    c0, c1 = h0["coalescer"], h1["coalescer"]
    # the first task left alone or led the others; the rest were merged
    assert c1["groups_merged"] - c0["groups_merged"] >= N - 2, (c0, c1)
    assert c1["multi_lane_launches"] > c0["multi_lane_launches"]
    assert c1["lanes_sum"] - c0["lanes_sum"] == N
    assert c1["lane_class_mismatch"] == 0 and c1["solo_degrade"] == 0
    assert c1["unbuilt_fallbacks"] == c0["unbuilt_fallbacks"]
    launches = store.runner.flight_recorder.stats()["launches"] - launches0
    assert launches < N, launches
    l0, l1 = h0["device_mesh"]["lanes"], h1["device_mesh"]["lanes"]
    assert l1["lanes_sum"] - l0["lanes_sum"] == N
    assert l1["launch_failures"] == 0
    assert store.runner.flight_recorder.stats()["faults"] == 0
    assert store.runner._arena.pinned_bytes() == 0


def test_a_critical_task_that_did_not_lead_still_shows_its_launch(
        lane_store, kind, params):
    """``loadgen.py probe()`` fetches the trace of the read's critical
    task, the one that returned last, and fails the run unless it holds
    a ``device_dispatch`` span of the compile class the plan is meant to
    take.  In a merged launch that task is as a rule not the leader: it
    has the span all the same, outside ``phases_ms``, so its phases do
    not outgrow its wall."""
    store = lane_store
    warm_lanes(store, kind, params)
    followers = 0
    for _ in range(6):
        rec, resp = held_read(store, kind, params)
        assert rec["ok"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{store.status_port}/debug/trace/"
                f"{resp['trace_id']}", timeout=30) as r:
            trace = json.loads(r.read())
        classes = sorted({s["attrs"]["compile_class"]
                          for s in trace["spans"]
                          if s["name"] == "device_dispatch"
                          and "compile_class" in s.get("attrs", {})})
        assert classes and set(classes) <= set(kind.CLASSES), classes
        td = trace["time_detail"]
        if "device_dispatch" in td["phases_ms"]:
            continue                    # this one led its launch
        followers += 1
        span, = [s for s in trace["spans"]
                 if s["name"] == "device_dispatch"]
        assert span["attrs"]["lanes"] >= 2 and \
            0 <= span["attrs"]["lane"] < span["attrs"]["lanes"]
        assert "coalesce_wait" in td["phases_ms"]
        assert sum(td["phases_ms"].values()) <= \
            td["total_rpc_wall_ms"] + 0.01, td
    assert followers, "every critical task led its launch"
