"""TPC-H's query streams over a ``lineitem`` that does NOT fit the feed
arena (benchmark configuration ``tpch-sf1-lineitem-streams-hbm164``, cell
``streams-hbm164-lineitem-sf1-closed4``) at a small size on the CPU, on
``test_streams_served.py``'s store: the same table kind, loader, three
request kinds and traffic (a file of the cell's own name, the streams
file's but for Q1's module), the arena's budget set online to 164/360
of what the 36 warm feeds hold, as the cell's TOML holds the chip's 164 MiB
to the table's 360 MiB.

Held here: the new files agree with their sources (the TOML is the streams
cell's plus ONE key, the JSON's table, schema, queries and guarantees are
the streams file's); under the budget, rounds of three interleaved sessions
answer every read exactly while lines are evicted and uploaded again, the
accounted bytes less the pinned never pass the budget at any sample, a
re-upload derives nothing on the host (the memo stayed), the counters and
the sweep's span are on ``/health``; a line's retirement still takes its
memo; and ``benchmark/loadgen.py`` as a child runs the cell's own files
end to end, its nine layer metrics finite on the program's counters and
on counters shaped as a parent's."""

import json
import math
import os
import subprocess
import sys
import threading
import tomllib

import pytest

from tikv_tpu.config import TikvConfig
from tikv_tpu.utils.trace_vocab import HOLD_ROWS, SPAN_VOCABULARY

from test_streams_served import (  # noqa: F401 — the store and its fixtures
    BENCH,
    KINDS,
    N,
    ROOT,
    ROWS,
    SEED,
    a_round,
    health,
    kinds,
    load_config,
    load_json,
    load_traffic,
    read,
    store,
    table_kind,
    wrong,
)

import byname  # noqa: E402 — on sys.path since test_streams_served
from pending_entries import (  # noqa: E402
    HBM,
    finite,
    pending_metrics,
    read_pending,
)

CELL = "streams-hbm164-lineitem-sf1-closed4"
CONFIG = "tpch-sf1-lineitem-streams-hbm164"
STREAMS_CELL = "streams-lineitem-sf1-closed4"
STREAMS_CONFIG = "tpch-sf1-lineitem-streams-regions96"
LOADGEN_TABLE_ID = 9952
BUDGET_MB, FEEDS_MB = 164, 360
NEW_METRICS = ["arena.evictions_per_task.hbm",
               "arena.rejections_per_task.hbm",
               "dispatcher.busy_share.hbm", "feed.upload_ms.hbm",
               "feed.upload_share.hbm", "host.derive_per_task_ms.hbm",
               "read.q15_p50_ms.hbm", "read.q1_p50_ms.hbm",
               "read.q6_p50_ms.hbm"]
PENDING = ["arena.evicted_mb_per_task", "feed.hit_share",
           "feed.upload_mb_per_s"]
SHARED_TEN = ["client.cpu_share", "coalescer.wait_ms", "compile.in_window",
              "d2h.wait_ms", "device.idle_share", "host.materialize_ms",
              "kernel.main_ms", "service.untracked_ms",
              "setup.first_read_s", "setup.load_s"]


# ------------------------------------------------- the files of the cell


def test_the_toml_is_the_streams_cells_plus_one_key():
    ours = os.path.join(ROOT, load_config(CONFIG)["toml"])
    theirs = os.path.join(ROOT, load_config(STREAMS_CONFIG)["toml"])
    assert ours != theirs
    with open(ours) as f:
        text = f.read()
    with open(theirs) as f:
        assert text.startswith(f.read())    # word for word, then the key
    with open(ours, "rb") as f:
        mine = tomllib.load(f)
    with open(theirs, "rb") as f:
        base = tomllib.load(f)
    assert mine["coprocessor"].pop("device-hbm-budget-mb") == BUDGET_MB
    assert mine == base
    cc = TikvConfig.from_file(ours).coprocessor
    assert (cc.device_hbm_budget_mb, cc.region_cache_capacity) == \
        (BUDGET_MB, 16)
    assert TikvConfig.from_file(theirs).coprocessor.device_hbm_budget_mb == 0


def test_the_json_is_the_streams_files_table_schema_and_guarantees():
    config, streams = load_config(CONFIG), load_config(STREAMS_CONFIG)
    assert config["name"] == CONFIG and config["chips"] == 1
    for key in ("schema", "queries", "measured", "pd"):
        assert config[key] == streams[key], key
    assert {k: v for k, v in config["table"].items() if k != "table_id"} \
        == {k: v for k, v in streams["table"].items() if k != "table_id"}
    ids = {load_json("configs", f)["table"]["table_id"]
           for f in os.listdir(os.path.join(BENCH, "configs"))
           if f.endswith(".json") and f != f"{CONFIG}.json"}
    assert config["table"]["table_id"] not in ids
    # the streams file's guarantees by value, and residency
    extra = dict(config["guarantees"])
    assert "resident_bytes - pinned_bytes <= budget_bytes" in \
        extra.pop("residency")
    assert extra == streams["guarantees"]
    assert list(config["reduced"]) == [
        "scale_factor", "device_hbm_budget_mb", "replicas", "queries",
        "refresh_stream"]
    for key in ("replicas", "queries", "refresh_stream"):
        assert config["reduced"][key] == streams["reduced"][key]
    assert "164" in config["reduced"]["device_hbm_budget_mb"]
    assert set(streams["assumed"]) < set(config["assumed"])
    assert set(config["assumed"]) - set(streams["assumed"]) == \
        {"arena_share", "host_lines", "region_skew"}
    for key in set(streams["assumed"]) - {"coprocessor"}:
        assert config["assumed"][key] == streams["assumed"][key], key
    assert "device-hbm-budget-mb = 164" in config["assumed"]["coprocessor"]
    assert set(config["memory"]) == {"reckoned", "measured"}


def test_the_manifest_gains_one_config_one_cell_and_nine_metrics():
    manifest = load_json("..", "BENCHMARK.json")
    config = load_config(CONFIG)
    entry = manifest["configs"][-1]
    assert entry == {"name": CONFIG, "source": config["source"],
                     "file": f"benchmark/configs/{CONFIG}.json",
                     "reduced": list(config["reduced"]),
                     "why": entry["why"]}
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["source"] not in {c["source"]
                                   for c in manifest["configs"][:-1]}
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, CELL, 1)      # a traffic file of its own
    assert len(cell["why"]) <= 200
    assert len(manifest["workloads"]) == 10
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] \
        == ["agg-mesh4-closed8"]
    # its nine stand where the manifest ended at PR 52 and list it alone;
    # the ten every one-chip lineitem cell reports gained it LAST
    assert sorted(m["name"] for m in manifest["per_layer"][-9:]) == \
        NEW_METRICS
    for m in manifest["per_layer"][-9:]:
        assert m["workloads"] == [CELL]
    mine = sorted(m["name"] for m in manifest["per_layer"]
                  if CELL in m.get("workloads", ()))
    assert mine == sorted(NEW_METRICS + SHARED_TEN)
    for m in manifest["per_layer"]:
        if m["name"] in SHARED_TEN:
            assert m["workloads"][-2:] == [STREAMS_CELL, CELL]
    assert not [m for m in mine if "roofline" in m or "mfu" in m]
    for name in PENDING:
        spec = load_json("layer_metrics", f"{name}.json")
        assert "per_layer_entry" not in spec
        assert spec["pending_entry"]["workloads"] == [CELL]
        assert name not in {m["name"] for m in manifest["per_layer"]}


# ------------------------------------------------- under the budget


@pytest.fixture(scope="module")
def squeezed(store, kinds):
    """The 36 feeds warm, then the arena's budget set online (as
    ``server/node.py`` sets it from the TOML) to 164/360 of what they
    hold; lifted again after the module's tests."""
    records = a_round(store, kinds, 0)
    assert all(r["ok"] for r in records) and wrong(store, kinds, records) \
        == []
    warm = health(store)
    resident = warm["device_state"]["hbm"]["resident_bytes"]
    assert warm["device_mesh"]["feed"]["resident_feeds"] == 3 * N
    assert resident == warm["device_mesh"]["feed"]["resident_bytes"]
    budget = resident * BUDGET_MB // FEEDS_MB
    store.runner.set_hbm_budget(budget)
    try:
        yield {"warm": warm, "resident": resident, "budget": budget}
    finally:
        store.runner.set_hbm_budget(0)


def phase_counts(h: dict) -> dict:
    return {name: row["count"] for name, row in h["tracing"]["phases"].items()}


def test_every_read_is_exact_and_a_reupload_derives_nothing(store, kinds,
                                                            squeezed):
    """Six rounds of three sessions cycling Q1, Q6 and Q15's view at
    once, 648 cop tasks over feeds 2.2 times the budget."""
    budget = squeezed["budget"]
    set_to = health(store)
    hbm = set_to["device_state"]["hbm"]
    # the online shrink swept at once, and kept every line's memo
    assert hbm["budget_bytes"] == budget and hbm["evictions"] > 0
    assert hbm["resident_bytes"] <= budget
    assert hbm["memos_kept"] == hbm["evictions"]
    assert hbm["evicted_bytes"] == squeezed["resident"] - \
        hbm["resident_bytes"]
    assert 0 < hbm["resident_lines"] < 3 * N
    assert set_to["device_mesh"]["feed"]["resident_feeds"] == \
        hbm["resident_lines"]
    samples, stop = [], threading.Event()

    def sample():
        while not stop.wait(0.002):
            st = store.runner.hbm_stats()
            samples.append(st["resident_bytes"] - st["pinned_bytes"])

    sampler = threading.Thread(target=sample)
    sampler.start()
    try:
        records = []
        for rnd in range(6):      # (a kind's walk has 58 values or more)
            records += a_round(store, kinds, 1 + 7 * rnd)
    finally:
        stop.set()
        sampler.join()
    after = health(store)
    assert len(records) == 54 and all(r["ok"] for r in records), \
        [r for r in records if not r["ok"]][:2]
    assert wrong(store, kinds, records) == []
    assert all(r["labels"]["cop_tasks"] == str(N) for r in records)
    assert len(samples) > 20 and max(samples) <= budget
    for h in (set_to, after):
        st = h["device_state"]["hbm"]
        assert st["resident_bytes"] - st["pinned_bytes"] <= budget
    served = after["coprocessor"]["requests_served"] - \
        set_to["coprocessor"]["requests_served"]
    assert served == 54 * N
    go, end = phase_counts(set_to), phase_counts(after)
    uploads = end["feed_upload"] - go["feed_upload"]
    assert uploads > 0 and end["host_derive"] == go["host_derive"]
    assert end["arena_evict"] > go["arena_evict"]
    hbm0, hbm1 = (h["device_state"]["hbm"] for h in (set_to, after))
    evicted = hbm1["evictions"] - hbm0["evictions"]
    assert evicted > 0
    assert hbm1["memos_kept"] - hbm0["memos_kept"] == evicted
    assert hbm1["evicted_bytes"] > hbm0["evicted_bytes"]
    # every upload brought back a feed the budget had taken, every task
    # was answered from a resident feed or an upload, and the host's 36
    # lines and the fast path's classes never noticed
    feed0, feed1 = (h["device_mesh"]["feed"] for h in (set_to, after))
    rise = {k: feed1["gets"][k] - feed0["gets"][k] for k in feed1["gets"]}
    assert rise["upload"] == uploads and rise["hit"] > 0
    assert rise["hit"] + rise["upload"] == served == sum(rise.values())
    assert feed1["uploads"]["evicted"] - feed0["uploads"]["evicted"] == \
        uploads
    assert feed1["uploads"]["cold"] == feed0["uploads"]["cold"]
    assert feed1["uploads"]["bytes"] > feed0["uploads"]["bytes"]
    assert after["copr_cache"]["misses"] == set_to["copr_cache"]["misses"]
    assert after["fastpath"]["hit"] - set_to["fastpath"]["hit"] == served
    prepared0, prepared1 = (h["device_mesh"]["prepared"]
                            for h in (set_to, after))
    assert prepared1["builds"] - prepared0["builds"] == uploads
    assert store.runner.flight_recorder.stats()["faults"] == 0


def test_the_sweep_is_a_row_of_the_hold_and_in_the_vocabulary():
    assert "arena_evict" in SPAN_VOCABULARY and "arena_evict" in HOLD_ROWS
    for attr in ("bytes", "planes", "after_eviction"):
        assert attr in SPAN_VOCABULARY["feed_upload"]
    for attr in ("victims", "bytes"):
        assert attr in SPAN_VOCABULARY["arena_evict"]
    # the accepted metric that names the hold's rows does not know the
    # new one: on a cell that evicts nothing it covers what it covered
    named = load_json("layer_metrics", "dispatcher.hold_named_share.json")
    assert set(named["args"]["parts"]) == HOLD_ROWS - {"arena_evict"}
    assert CELL not in named["per_layer_entry"]["workloads"]


def test_a_lines_retirement_still_takes_its_memo(store, kinds, squeezed):
    """Under the budget some of the 36 arena entries hold a memo and no
    feed; a region's lifecycle sweep takes all three of its entries,
    memos and all, at once (no ``gc.collect``), and the next reads build
    them again."""
    arena = store.runner._arena
    entries0 = len(arena._entries)
    assert entries0 == 3 * N > arena.resident_lines()
    region = health(store)["copr_cache"]["lines"][0]["region"]

    def of_region():
        return [a for a, _bucket in arena.items()
                if getattr(a, "region_hint", None) == region]

    assert len(of_region()) == 3
    derives = phase_counts(health(store))["host_derive"]
    assert store.node.copr_cache.invalidate_region(region) == 3
    assert len(arena._entries) == entries0 - 3 and of_region() == []
    st = store.runner.hbm_stats()
    assert st["resident_bytes"] - st["pinned_bytes"] <= squeezed["budget"]
    records = [read(store, kinds, kind, 13) for kind in KINDS]
    assert all(r["ok"] for r in records)
    assert wrong(store, kinds, records) == []
    assert len(arena._entries) == entries0 and len(of_region()) == 3
    # (a retired line's memo is gone: its next read derives again)
    assert phase_counts(health(store))["host_derive"] == derives + 3


# ------------------------------------------------- loadgen.py, as run.py runs it


# what this PR adds to /health: a parent's sample has none of it
PARENT_LACKS = [("device_state", "hbm", "evicted_bytes"),
                ("device_state", "hbm", "memos_kept"),
                ("device_mesh", "feed", "gets"),
                ("device_mesh", "feed", "uploads"),
                ("tracing", "phases", "arena_evict")]


@pytest.fixture(scope="module")
def loadgen_result(store, squeezed, tmp_path_factory):
    """``benchmark/loadgen.py`` itself, as a child with ``run.py``'s
    hand-shake, over the cell's own configuration (the table's id apart)
    and traffic file (``warm_s`` apart), the store's arena under the
    squeezed budget → (its result file, the traffic)."""
    tmp_path = tmp_path_factory.mktemp("hbm_loadgen")
    manifest = load_json("..", "BENCHMARK.json")
    import line
    _cell, config_file, traffic_file = line.cell_files(manifest, CELL, ROOT)
    assert traffic_file.endswith(f"{CELL}.json")
    with open(config_file) as f:
        config = json.load(f)
    config["table"]["table_id"] = LOADGEN_TABLE_ID
    (tmp_path / "config.json").write_text(json.dumps(config))
    traffic = load_traffic(CELL)
    streams = load_traffic(STREAMS_CELL)
    assert {k: v for k, v in traffic.items() if k not in ("what", "kinds")} \
        == {k: v for k, v in streams.items() if k not in ("what", "kinds")}
    assert traffic["kinds"]["tpch_q1"]["module"] == "tpch_q1_streams_hbm"
    traffic["warm_s"] = 0.5
    (tmp_path / "traffic.json").write_text(json.dumps(traffic))
    out = tmp_path / "result.json"
    (tmp_path / "spec.json").write_text(json.dumps({
        "pd_addr": store.pd_addr, "status_port": store.status_port,
        "seed": SEED, "seconds": 2, "rows": ROWS,
        "config_file": str(tmp_path / "config.json"),
        "traffic_file": str(tmp_path / "traffic.json"),
        "out": str(out), "on_tpu": False}))
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "loadgen.py"),
         str(tmp_path / "spec.json")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        timer = threading.Timer(400, child.kill)
        timer.start()
        try:
            first = child.stdout.readline()
            assert first.startswith("warm "), (first, child.poll())
            assert json.loads(first[len("warm "):])["failed"] == 0
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
    return json.loads(out.read_text()), traffic


def data_of(loadgen_result, as_a_parent: bool = False) -> dict:
    """``data`` as ``run.py`` hands it to a reader; ``as_a_parent``: the
    counters shaped as the parent's program gives them."""
    result, traffic = loadgen_result
    sides = json.loads(json.dumps(
        [result["counters_go"], result["counters_end"]]))
    if as_a_parent:
        for side in sides:
            for *path, leaf in PARENT_LACKS:
                at = side["health"]
                for key in path:
                    at = at[key]
                del at[leaf]
    return {"reads": [r for r in result["records"] if r["ok"]],
            "counters_go": sides[0], "counters_end": sides[1],
            "trace": None, "traffic": traffic, "rows": ROWS, "peaks": None,
            "stats": {"loadgen_cpu_share": result["loadgen_cpu_share"]},
            "setup": {"load_s": result["load_s"],
                      "first_read_s": result["first_read_s"]}}


def test_loadgen_child_runs_the_cell_end_to_end(loadgen_result, squeezed):
    """Every record exact, every read twelve device cop tasks, and the
    arena inside its budget at go, at the window's end and at done."""
    result, _traffic = loadgen_result
    assert result["warm_failed"] == 0
    assert all(value == 0 and limit == 0
               for _name, value, limit in result["checks"])
    assert sorted({name for name, _v, _l in result["checks"]}) == \
        ["regions.reads_off_the_layout"] + \
        [f"{kind}.wrong_answers" for kind in KINDS]
    records = result["records"]
    assert records and all(r["ok"] for r in records), \
        [r["why"] for r in records if not r["ok"]][:3]
    assert all(r["ok"] for r in result["last"])
    assert {r["kind"] for r in records} == set(KINDS)
    assert all(r["labels"]["cop_tasks"] == str(N) for r in records)
    budget = squeezed["budget"]
    for side in ("counters_go", "counters_end", "counters_done"):
        st = result[side]["health"]["device_state"]["hbm"]
        assert st["budget_bytes"] == budget
        assert st["resident_bytes"] - st["pinned_bytes"] <= budget
    go, end = (result[k]["health"] for k in ("counters_go", "counters_end"))
    assert end["device_state"]["hbm"]["evictions"] > \
        go["device_state"]["hbm"]["evictions"]
    assert end["copr_cache"]["evictions"] == go["copr_cache"]["evictions"]


# what each of the cell's nine reads over the child's window
HOLDS = {"feed.upload_share.hbm": lambda v: 0 < v <= 100,
         "feed.upload_ms.hbm": lambda v: v > 0,
         # the memo stayed: a window of re-uploads derives nothing
         "host.derive_per_task_ms.hbm": lambda v: v == 0.0,
         "arena.evictions_per_task.hbm": lambda v: v > 0,
         "arena.rejections_per_task.hbm": lambda v: v >= 0.0,
         "dispatcher.busy_share.hbm": lambda v: 0 < v < 100,
         "read.q1_p50_ms.hbm": lambda v: v > 0,
         "read.q6_p50_ms.hbm": lambda v: v > 0,
         "read.q15_p50_ms.hbm": lambda v: v > 0}


@pytest.mark.parametrize("as_a_parent", [False, True],
                         ids=["the_change", "a_parent"])
@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_metric_of_the_cell_reads_a_changes_and_a_parents_counters(
        loadgen_result, name, as_a_parent):
    """Each of the nine reads a finite value from what a parent's
    ``/health`` already has: the parent of PR 53 ran the cell to a whole
    traced line under these readers (``line.py`` refuses a line that
    lacks a declared metric: PERF.md section 7, row 1a) before the cell's
    Q1 module came to refuse that program (section 6)."""
    assert sorted(HOLDS) == NEW_METRICS
    spec = load_json("layer_metrics", f"{name}.json")
    assert set(spec) == {"what", "reader", "args", "per_layer_entry"}
    got = byname.load("readers", spec["reader"]).read(
        data_of(loadgen_result, as_a_parent), spec["args"])
    assert isinstance(got, float) and math.isfinite(got), (name, got)
    assert HOLDS[name](got), (name, got)


@pytest.mark.parametrize("name", sorted(pending_metrics(HBM)))
def test_a_pending_metric_reads_the_loadgen_childs_result(loadgen_result,
                                                          name):
    """The three that read what this PR adds to ``/health`` wait as
    files (tests/pending_entries.py): a finite value here, nothing (and
    no error) over a parent's counters."""
    assert sorted(pending_metrics(HBM)) == PENDING and CELL == HBM
    spec = pending_metrics()[name]
    got = read_pending(name, spec, data_of(loadgen_result))
    assert finite(got) and got > 0, (name, got)
    assert byname.load("readers", spec["reader"]).read(
        data_of(loadgen_result, as_a_parent=True), spec["args"]) is None
