"""TPC-H's concurrent query streams over ONE ``lineitem`` (benchmark
configuration ``tpch-sf1-lineitem-streams-regions96``, cell
``streams-lineitem-sf1-closed4``) at a small size on the CPU: the table
kind's own data from a seed, pre-split and loaded by the cell's own table
kind into ONE store built as ``benchmark/rig.py`` builds it (the cell's
TOML by path: ``region-cache-capacity = 16``), read through gRPC by
``TxnClient.coprocessor_fanout`` with the cell's three request kinds,
Q1, Q6 and Q15's view, INTERLEAVED over the same twelve regions.  The
store serves with the Pallas body in interpret mode (as the three
single-plan served tests run it), so three shapes of the one kernel, three
scan schemas and three reply forms meet here as they do on the chip.

Held here: every answer of every kind against its own numpy reference
while the three are in flight together; each kind on its own launch
class beside the others; 36 cache lines and 36 feeds over 12 regions
with the cache's bound at the region count, no eviction and no line
built after the first round; a bound under the region count evicting
whole regions, their feeds with them, answers still exact; each kind's
float32 control caught through the mixed traffic file; the new counters
on ``/health``; and the whole flow of ``benchmark/loadgen.py`` as a
child process, with the cell's eight new layer metrics read over its
window."""

import functools
import json
import os
import subprocess
import sys
import threading
import time
import types
import urllib.request

import pytest

import jax

from tikv_tpu.config import TikvConfig
from tikv_tpu.copr import region_cache
from tikv_tpu.device import DeviceRunner, pallas_hash
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
A_BYTES = 1 << 18           # BLOCK rows up to 4,096 slots, 512 at 16,384
CELL = "streams-lineitem-sf1-closed4"
CONFIG = "tpch-sf1-lineitem-streams-regions96"
SOURCES = {"tpch_q1": ("tpch-sf1-lineitem-q1-regions96",
                       "q1-lineitem-sf1-closed4"),
           "tpch_q6": ("tpch-sf1-lineitem-regions96",
                       "q6-lineitem-sf1-closed4"),
           "tpch_q15": ("tpch-sf1-lineitem-q15-regions96",
                        "q15-lineitem-sf1-closed4")}
KINDS = sorted(SOURCES)
TABLE_IDS = {"streams": 9949, "loadgen": 9950}
NEW_METRICS = ["cache.line_builds_in_window.streams",
               "coalescer.class_mismatch_per_launch.streams",
               "coalescer.tasks_per_launch.streams",
               "dispatcher.busy_share.streams",
               "fastpath.hit_share.streams",
               "read.q15_p50_ms.streams", "read.q1_p50_ms.streams",
               "read.q6_p50_ms.streams"]
# what a flight-recorder entry says of a task of each kind
LAUNCH = {"tpch_q1": {"slot_mode": "dense", "keys": 2, "params": 1},
          "tpch_q6": {"slot_mode": "simple", "params": 5},
          "tpch_q15": {"slot_mode": "dense", "keys": 1, "params": 2}}


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_config(name: str = CONFIG) -> dict:
    return load_json("configs", f"{name}.json")


def load_traffic(name: str = CELL) -> dict:
    return load_json("traffic", f"{name}.json")


N = load_config()["table"]["regions"]


@pytest.fixture(scope="module")
def kinds():
    """{kind: (its module, its params)} as ``loadgen.py Driver`` loads
    them from the cell's traffic file."""
    return {name: (byname.load("requests", k["module"]), k["params"])
            for name, k in load_traffic()["kinds"].items()}


@pytest.fixture(scope="module")
def table_kind():
    return byname.load("tables", "lineitem_presplit")


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
    # ... and the one-hot's budget with it, so that Q15's step follows
    # its grid here as it does at full size
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
    spec = dict(load_config()["table"], table_id=TABLE_IDS["streams"])
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
            ctx=ctx, status_port=srv.status_server.port,
            TxnClient=TxnClient)
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


def read(store, kinds, kind: str, index: int, client=None) -> dict:
    """One read of ``kind``'s ``index``-th substitution value as
    ``loadgen.py request()`` records it."""
    mod, params = kinds[kind]
    client = client or store.client
    dag = mod.plan(store.ctx, index, client.tso())
    resp = mod.send(store.ctx, client, (dag, params["concurrency"], index))
    td = resp.get("time_detail", {})
    labels, phases = td.get("labels", {}), td.get("phases_ms", {})
    rec = {"kind": kind, "labels": labels, "phases_ms": phases,
           "ok": resp.get("backend") == "device" and
           "degraded" not in labels and "host_exec" not in phases}
    if rec["ok"]:
        rec["answer"] = mod.digest(store.ctx, resp, params)
    return rec


def wrong(store, kinds, records) -> list:
    """The checks that fail over ``records``, each kind's held to its
    own reference as ``loadgen.py check`` holds them."""
    out = []
    for kind, (mod, params) in kinds.items():
        mine = [r for r in records if r["kind"] == kind]
        out += [name for name, value, limit in
                mod.check(store.ctx, mine, params, None) if value > limit]
    return out


def health(store) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{store.status_port}/health", timeout=30) as r:
        return json.loads(r.read())


def a_round(store, kinds, at: int) -> list:
    """Three sessions at once, each cycling the three kinds from a start
    of its own (``loadgen.py run_clients``: client i at i mod 3), one
    cycle each: at any time reads of different kinds are in flight."""
    out, errors = [], []

    def session(i):
        client = store.TxnClient(store.pd_addr)
        try:
            for step in range(3):
                kind = KINDS[(i + step) % 3]
                out.append(read(store, kinds, kind, at + 7 * i + step,
                                client))
        except Exception as e:      # noqa: BLE001 — shown by the assert
            errors.append(repr(e))
        finally:
            client.close()

    threads = [threading.Thread(target=session, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    return out


# ------------------------------------------------- the files of the cell


def test_the_cells_files_agree_with_their_three_sources():
    config, traffic = load_config(), load_traffic()
    manifest = load_json("..", "BENCHMARK.json")
    q6 = load_config(SOURCES["tpch_q6"][0])
    # ONE table of the lineitem cells' kind and size, a table id of its
    # own, and the store's TOML by path, untouched
    ids = set()
    for kind, (source, cell) in SOURCES.items():
        theirs = load_config(source)
        assert {k: v for k, v in config["table"].items()
                if k != "table_id"} == \
            {k: v for k, v in theirs["table"].items() if k != "table_id"}
        ids.add(theirs["table"]["table_id"])
        assert config["toml"] == theirs["toml"]
        # each kind's params are its own cell's, word for word, and its
        # module that cell's by import behind the cell's one question
        assert traffic["kinds"][kind] == dict(
            load_traffic(cell)["kinds"][kind], module=f"{kind}_streams")
        assert traffic["forbidden_classes"] == \
            load_traffic(cell)["forbidden_classes"]
        for key in ("isolation", "freshness", "durability", "layout"):
            assert config["guarantees"][key] == theirs["guarantees"][key]
        assert f"{kind}.wrong_answers" in config["guarantees"]["exactness"]
        assert set(theirs["assumed"]) <= set(config["assumed"])
    assert config["table"]["table_id"] not in ids
    assert set(config["guarantees"]) == set(q6["guarantees"])
    with open(os.path.join(ROOT, config["toml"])) as f:
        assert "region-cache-capacity = 16" in f.read()
    assert config["chips"] == 1 and sorted(traffic["kinds"]) == KINDS
    # four sessions, one cycle, the starts a kind apart
    assert traffic["clients"] == [{"count": 4, "think_ms": 0, "pattern":
                                   ["tpch_q1", "tpch_q6", "tpch_q15"]}]
    assert traffic["first_read"] == "tpch_q1"
    assert (traffic["warm_requests"], traffic["warm_s"],
            traffic["trace_window_s"]) == (3, 2.0, 3)
    # kernel.main_ms is the mix's mean: no roofline is taken of it
    kernel = traffic["main_kernel"]
    assert (kernel["of"], kernel["match"]) == ("ops", ["tpu_custom_call"])
    assert "rows_per_launch" not in kernel and \
        "input_plane_bytes_per_row" not in kernel
    entry, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == list(config["reduced"]) == \
        ["scale_factor", "replicas", "queries", "refresh_stream"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["source"] not in {load_config(s)["source"]
                                   for s, _c in SOURCES.values()}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # appended where the lists ended at PR 48, and entries never move
    assert manifest["configs"][8] is entry
    cell = manifest["workloads"][8]
    assert cell["name"] == CELL and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    for key in ("stream_orders", "sessions", "substitution_parameters"):
        assert key in config["assumed"]
    assert set(config["memory"]) >= {"reckoned", "measured"}
    # the cell reports the ten shared layer metrics and its own eight,
    # which list it alone and stand where the manifest ended at PR 48,
    # and (PR 51) the share of the dispatcher's hold that has a name
    mine = sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", ()))
    assert len(mine) == 19 and "dispatcher.hold_named_share" in mine
    assert [m for m in mine if m in NEW_METRICS] == NEW_METRICS
    assert sorted(m["name"] for m in manifest["per_layer"][52:60]) == \
        NEW_METRICS
    for m in manifest["per_layer"][52:60]:
        assert m["workloads"] == [CELL]
    # no roofline share is declared for the cell
    assert not [m for m in mine if "roofline" in m or "mfu" in m]


# ------------------------------------------------- answers, interleaved


def test_three_plans_interleaved_each_equal_their_reference(store, kinds):
    """Three rounds of three sessions cycling Q1, Q6 and Q15's view at
    once against the one table.  With the cache's bound at the REGION
    count: every answer its own reference's; after the first round 36
    lines and 36 feeds over 12 regions, no eviction, and no line built
    again however the kinds interleave."""
    cache = store.node.copr_cache
    cap0 = cache._capacity
    assert cap0 == 16               # the cell's TOML, as it stands
    cache._capacity = N
    try:
        records = a_round(store, kinds, 0)
        first = health(store)
        for at in (20, 40):
            records += a_round(store, kinds, at)
        last = health(store)
    finally:
        cache._capacity = cap0
    assert len(records) == 27 and all(r["ok"] for r in records), \
        [r for r in records if not r["ok"]][:2]
    assert wrong(store, kinds, records) == []
    assert all(r["labels"]["cop_tasks"] == str(N) for r in records)
    for h in (first, last):
        cc = h["copr_cache"]
        assert (cc["resident_lines"], cc["regions"],
                cc["schemas_per_region_max"]) == (3 * N, N, 3)
        assert cc["evictions"] == {"region_lru": 0, "schema_bound": 0}
        feed = h["device_mesh"]["feed"]
        assert feed["resident_feeds"] == 3 * N
        # a feed a line: 7 + 4 + 4 int32 planes a region, padded
        assert feed["resident_bytes"] == N * 15 * 4 * BLOCK
    assert last["copr_cache"]["misses"] == first["copr_cache"]["misses"] \
        == 3 * N
    assert last["copr_cache"]["invalidations"] == 0
    # the second and third round's tasks all hit the fast path's 36
    # learned classes
    served = last["coprocessor"]["requests_served"] - \
        first["coprocessor"]["requests_served"]
    assert served == 18 * N
    assert last["fastpath"]["hit"] - first["fastpath"]["hit"] == served
    assert last["fastpath"]["learned"] == first["fastpath"]["learned"]
    # launches left under at most the three kinds' classes
    assert 0 <= last["coalescer"]["launch_classes"] <= 3
    assert store.runner.flight_recorder.stats()["faults"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_a_kind_takes_its_own_launch_class_beside_the_others(store, kinds,
                                                             kind):
    """Each plan's tasks leave on the one Pallas body in the plan's own
    shape, whatever the store served just before."""
    other = KINDS[(KINDS.index(kind) + 1) % 3]
    assert read(store, kinds, other, 3)["ok"]
    recorder = store.runner.flight_recorder
    before = recorder.stats()["launches"]
    rec = read(store, kinds, kind, kinds[kind][0].VALIDATION)
    assert rec["ok"] and wrong(store, kinds, [rec]) == []
    # (twelve tasks leave as the lanes of fewer launches)
    launched = recorder.stats()["launches"] - before
    assert 1 <= launched <= N
    recent = recorder.items()[-launched:]
    assert {e["compile_class"] for e in recent} == {"pallas_hash"}
    for e in recent:
        assert {k: e[k] for k in LAUNCH[kind]} == LAUNCH[kind], e
    if kind == "tpch_q15":
        assert all(4096 < e["slots"] <= 16384 for e in recent)
    # one kernel entry a plan, whatever the substitution value
    entries = [k for k, e in store.runner._kernel_cache.items()
               if isinstance(k, tuple) and k and k[0] == "hashpl"
               and isinstance(e, dict)]
    assert len(entries) == 3, entries


def test_a_bound_under_the_regions_evicts_whole_regions(store, kinds):
    """With the bound set two under the region count (online, as
    ``server/node.py`` sets it) the cache keeps ten regions from the next
    build on: the least recently read leave with all their lines, their
    feeds follow (``on_line_retired``), lines are built again as they
    are asked, and every answer is still its reference's."""
    cache = store.node.copr_cache
    cap0 = cache._capacity
    before = health(store)
    cache._capacity = N - 2
    try:
        # (the bound holds where a line is built, as it always did: one
        # region swept, as a lifecycle event sweeps it, starts it)
        assert cache.invalidate_region(
            before["copr_cache"]["lines"][0]["region"]) == 3
        records = [read(store, kinds, kind, 11) for kind in KINDS]
        after = health(store)
    finally:
        cache._capacity = cap0
    assert all(r["ok"] for r in records)
    assert wrong(store, kinds, records) == []
    cc0, cc = before["copr_cache"], after["copr_cache"]
    assert cc["regions"] <= N - 2
    assert cc["evictions"]["schema_bound"] == 0
    evicted = cc["evictions"]["region_lru"] - cc0["evictions"]["region_lru"]
    assert evicted >= 2 and cc["misses"] - cc0["misses"] >= 2
    assert cc["resident_lines"] <= 3 * (N - 2)
    # no feed outlives its line
    assert after["device_mesh"]["feed"]["resident_feeds"] <= \
        cc["resident_lines"]
    # ... and at the cell's own bound the twelve regions come back whole
    records = [read(store, kinds, kind, 12) for kind in KINDS]
    assert wrong(store, kinds, records) == []
    cc = health(store)["copr_cache"]
    assert (cc["resident_lines"], cc["regions"]) == (3 * N, N)


# ------------------------------------------------- the controls


@functools.lru_cache(maxsize=None)
def controls() -> dict:
    import control
    return control.controls(CELL, SEED, ROWS)


@pytest.mark.parametrize("kind", KINDS)
def test_each_kinds_control_is_caught_through_the_mixed_traffic_file(kind):
    """``benchmark/control.py`` over the cell's own traffic file: each
    kind's reference with its products in float32 in the program's
    place fails that kind's check, by the answer alone."""
    got = controls()
    assert sorted(got) == [f"{k}.bfloat16" for k in KINDS]
    failing = [name for name, value, limit in got[f"{kind}.bfloat16"]
               if value > limit]
    assert failing == [f"{kind}.wrong_answers"], got


# ------------------------------------------------- loadgen.py, as run.py runs it


def test_loadgen_child_runs_the_cell_end_to_end(store, tmp_path):
    """``benchmark/loadgen.py`` itself, as a child with the ``warm`` /
    ``go`` / ``done`` hand-shake of ``run.py``, over the cell's own
    traffic file (``warm_s`` apart) and its configuration (the table's
    id apart): the table kind's load, the first read, three probes a
    kind, the warm rounds, a window in which four sessions cycle the
    three kinds, the check of every record against its kind's reference,
    and the cell's new layer metrics over the window."""
    config = load_config()
    config["table"]["table_id"] = TABLE_IDS["loadgen"]
    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(config))
    traffic = load_traffic()
    traffic["warm_s"] = 0.5
    traffic_file = tmp_path / "traffic.json"
    traffic_file.write_text(json.dumps(traffic))
    out = tmp_path / "result.json"
    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps({
        "pd_addr": store.pd_addr, "status_port": store.status_port,
        "seed": SEED, "seconds": 2, "rows": ROWS,
        "config_file": str(config_file),
        "traffic_file": str(traffic_file),
        "out": str(out), "on_tpu": False}))
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "loadgen.py"), str(spec_file)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        timer = threading.Timer(400, child.kill)
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
    # three check lines of one name, each its own kind's count
    assert result["checks"] == [
        [name, 0, 0] for kind in ("tpch_q1", "tpch_q6", "tpch_q15")
        for name in (f"{kind}.wrong_answers",
                     "regions.reads_off_the_layout")]
    records = result["records"]
    assert records and all(r["ok"] for r in records), \
        [r["why"] for r in records if not r["ok"]][:3]
    assert all(r["ok"] for r in result["last"])
    assert [r["kind"] for r in result["last"]] == \
        ["tpch_q1", "tpch_q6", "tpch_q15"]
    assert {r["kind"] for r in records} == set(KINDS)
    assert all(r["labels"]["cop_tasks"] == str(N) for r in records)
    go, end = (result[k]["health"] for k in ("counters_go", "counters_end"))
    # (this table's twelve regions beside the module's twelve are 24
    # against a bound of 16: the warm-up evicted the other table's, least
    # recently read; the window must build and evict nothing)
    assert end["copr_cache"]["evictions"]["schema_bound"] == 0
    data = {"reads": records, "counters_go": result["counters_go"],
            "counters_end": result["counters_end"], "trace": None,
            "traffic": traffic}

    def metric(name):
        spec = load_json("layer_metrics", f"{name}.json")
        return byname.load("readers", spec["reader"]).read(data, spec["args"])

    assert metric("cache.line_builds_in_window.streams") == 0
    assert end["copr_cache"]["evictions"] == go["copr_cache"]["evictions"]
    assert metric("fastpath.hit_share.streams") == 100.0
    assert metric("coalescer.tasks_per_launch.streams") >= 1.0
    assert metric("coalescer.class_mismatch_per_launch.streams") >= 0.0
    assert 0 < metric("dispatcher.busy_share.streams") < 100
    for short in ("q1", "q6", "q15"):
        assert metric(f"read.{short}_p50_ms.streams") > 0
    assert metric("coalescer.wait_ms") is not None
    # the pending metric reads the new counter here, and nothing (not an
    # error) on a program without it; so do the others without theirs
    assert metric("cache.evictions_per_task") == 0.0
    for side in (data["counters_go"], data["counters_end"]):
        del side["health"]["copr_cache"]["evictions"]
        del side["health"]["coalescer"]["lane_class_mismatch"]
    assert metric("cache.evictions_per_task") is None
    assert metric("coalescer.class_mismatch_per_launch.streams") is None
    # a window that served no read of a kind has no median of it
    data["reads"] = [r for r in records if r["kind"] != "tpch_q6"]
    assert metric("read.q6_p50_ms.streams") is None
    assert metric("read.q1_p50_ms.streams") > 0


def test_the_schema_bound_is_above_the_cells_three():
    assert region_cache.SCHEMAS_PER_REGION >= 3
