"""BENCHMARK.json declares a layer metric by the entry its own file
carries: ``benchmark/layer_metrics/<name>.json``'s ``per_layer_entry``
(the ratio metrics of /health ``tracing.phases`` / ``tracing.process``,
declared one PR after the counters they read so that both sides of a
comparison have them)."""

import functools
import glob
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def entries() -> dict:
    out = {}
    for path in sorted(glob.glob(os.path.join(
            ROOT, "benchmark", "layer_metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if "per_layer_entry" in spec:
            out[os.path.basename(path)[:-len(".json")]] = spec
    return out


@pytest.mark.parametrize("name", sorted(entries()))
def test_manifest_declares_the_files_own_entry(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    declared = [m for m in manifest["per_layer"] if m["name"] == name]
    assert declared == [entries()[name]["per_layer_entry"]]
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert set(declared[0]["workloads"]) <= set(cells)
    assert declared[0]["moves"] in {m["name"] for m in manifest["end_to_end"]}


# ------------------------------------------------ names the benchmark reads
#
# The benchmark finds the program by name: a traffic file's
# ``forbidden_classes`` are flight-recorder compile classes (what
# ``_dispatch_phase`` is given), and ``mesh.program_ms`` matches an XLA
# module, which is ``jit_`` + the name of the Python function that was
# jitted.  A rename in tikv_tpu/device/ would make a check or a metric
# read nothing, in silence.

DEVICE = os.path.join(ROOT, "tikv_tpu", "device")


@functools.lru_cache(maxsize=None)
def device_sources() -> dict:
    import ast
    out = {}
    for path in sorted(glob.glob(os.path.join(DEVICE, "*.py"))):
        with open(path) as f:
            out[os.path.basename(path)] = ast.parse(f.read())
    return out


def names_the_benchmark_reads() -> list:
    names = set()
    for path in glob.glob(os.path.join(ROOT, "benchmark", "traffic",
                                       "*.json")):
        with open(path) as f:
            names |= set(json.load(f).get("forbidden_classes", ()))
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "mesh.program_ms.json")) as f:
        for module in json.load(f)["args"]["match"]:
            assert module.startswith("jit_"), module
            names.add(module[len("jit_"):])
    return sorted(names)


@pytest.mark.parametrize("name", names_the_benchmark_reads())
def test_the_device_layer_has_the_name_the_benchmark_reads(name):
    import ast
    classes, functions = set(), set()
    for tree in device_sources().values():
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef):
                functions.add(node.name)
            elif isinstance(node, ast.Call) and node.args and \
                    getattr(node.func, "attr", None) == "_dispatch_phase" \
                    and isinstance(node.args[0], ast.Constant):
                classes.add(node.args[0].value)
    assert name in classes | functions, (sorted(classes), name)


# the operator modules of device/ and what they share: the runner
# imports them, never the other way, at module level or inside a function
OPERATORS = ("aggregate.py", "feed.py", "join.py", "mvcc.py",
             "request.py", "selection.py")


@pytest.mark.parametrize("module", OPERATORS)
def test_no_operator_module_imports_the_runner(module):
    import ast
    for node in ast.walk(device_sources()[module]):
        if isinstance(node, ast.ImportFrom):
            imported = [node.module or ""] + [a.name for a in node.names]
        elif isinstance(node, ast.Import):
            imported = [a.name for a in node.names]
        else:
            continue
        # (executors/runner.py is the host pipeline's, another module)
        assert not any("runner" in dotted.split(".") and
                       "executors" not in dotted.split(".")
                       for dotted in imported), \
            (module, node.lineno, imported)


# A request kind or a table kind may ask the program for a capability by
# name before it sends anything, so that a program without it ends a run
# in seconds (``tables/lineitem_presplit.py`` ``load``:
# ``sst_importer.NATIVE_COLUMN_KINDS``; ``requests/tpch_q1.py``
# ``prepare``: ``datatype/tile.py`` ``code_plane``).  A rename in the
# program would make the cell refuse the program that has it.

def capabilities_the_benchmark_asks_for() -> list:
    import ast
    out = []
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "*",
                                              "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        modules = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and \
                    (node.module or "").startswith("tikv_tpu"):
                for a in node.names:
                    modules[a.asname or a.name] = f"{node.module}.{a.name}"
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "id", None) in ("hasattr", "getattr") \
                    and len(node.args) >= 2 \
                    and getattr(node.args[0], "id", None) in modules \
                    and isinstance(node.args[1], ast.Constant):
                out.append((modules[node.args[0].id], node.args[1].value))
    return sorted(set(out))


@pytest.mark.parametrize("module,name", capabilities_the_benchmark_asks_for())
def test_the_program_has_the_capability_the_benchmark_asks_for(module, name):
    import importlib
    assert hasattr(importlib.import_module(module), name), (module, name)


def test_the_q1_cell_asks_for_code_planes_by_that_name():
    assert ("tikv_tpu.datatype.tile", "code_plane") in \
        capabilities_the_benchmark_asks_for()


def test_the_q15_cell_asks_for_the_chunk_decoder_and_the_wide_grid():
    """``requests/tpch_q15.py`` ``prepare``: ``server/wire.py``
    ``chunk_rows`` by ``hasattr``, and the fused kernel's ``MAX_SLOTS``
    read from ``device/pallas_hash.py``'s source (the load generator
    must not import JAX): the name it finds there is the module's."""
    import sys
    assert ("tikv_tpu.server.wire", "chunk_rows") in \
        capabilities_the_benchmark_asks_for()
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.append(bench)
    import byname
    from tikv_tpu.device import pallas_hash
    kind = byname.load("requests", "tpch_q15")
    assert kind.kernel_max_slots() == pallas_hash.MAX_SLOTS >= kind.GRID


def test_the_refresh_cell_asks_for_the_lock_wait_and_the_rebuild_phase():
    """``requests/tpch_q1_refresh.py`` ``require_program``: the client's
    ``LOCK_BACKOFF`` by ``hasattr``, and the two phases this cell's
    metrics and labels read, by their names in the vocabulary: an older
    program exits 1 before the cell's first write."""
    import sys
    assert ("tikv_tpu.server.client", "LOCK_BACKOFF") in \
        capabilities_the_benchmark_asks_for()
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.append(bench)
    import byname
    from tikv_tpu.server import client
    from tikv_tpu.utils import trace_vocab
    assert client.LOCK_BACKOFF == {"base": 0.010, "cap": 3.0}
    assert {"feed_rebuild", "fanout_lock_wait", "feed_patch",
            "delta_apply"} <= set(trace_vocab.SPAN_VOCABULARY)
    assert "fanout_lock_wait" in trace_vocab.CLIENT_CLOCK
    byname.load("requests", "tpch_q1_refresh").require_program()


@pytest.mark.parametrize("missing", ["LOCK_BACKOFF", "feed_rebuild"])
def test_the_refresh_cell_refuses_an_older_program(missing, monkeypatch):
    import sys
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.append(bench)
    import byname
    from tikv_tpu.server import client
    from tikv_tpu.utils import trace_vocab
    if missing == "LOCK_BACKOFF":
        monkeypatch.delattr(client, "LOCK_BACKOFF")
    else:
        monkeypatch.delitem(trace_vocab.SPAN_VOCABULARY, missing)
    with pytest.raises(SystemExit) as e:
        byname.load("requests", "tpch_q1_refresh").require_program()
    assert missing in str(e.value)


# ------------------------------------------------ the cell of three plans
#
# ``streams-lineitem-sf1-closed4`` (PR 48) is measured on its parent too,
# traced, under this benchmark's files: a metric that read a counter the
# parent lacks would leave its line without a declared metric, and
# ``line.py validate`` refuses such a line (PERF.md section 7, row 1a).
# So every metric the cell declares reads the window's records or one of
# these, which the program had before the cell.

STREAMS = "streams-lineitem-sf1-closed4"
WHAT_A_PARENT_HAS = {
    "health.copr_cache.misses", "health.coalescer.lane_class_mismatch",
    "health.fastpath.hit", "health.coprocessor.requests_served",
    "flight_recorder.launches",
    "health.tracing.phases.group_dispatch.wall_ms",
    "health.tracing.process.clock_ms",
    # the dispatcher's phases from before PR 51 (trace_vocab.HOLD_WHOLE)
    "health.tracing.phases.device_dispatch.wall_ms",
    "health.tracing.phases.feed_patch.wall_ms",
    "health.tracing.phases.feed_rebuild.wall_ms",
    "health.tracing.phases.feed_upload.wall_ms",
    "health.tracing.phases.host_derive.wall_ms"}


def streams_metrics() -> list:
    return sorted(name for name, spec in entries().items()
                  if spec["per_layer_entry"]["workloads"] == [STREAMS])


@pytest.mark.parametrize("name", streams_metrics())
def test_a_streams_metric_reads_only_what_its_parent_had(name):
    spec = entries()[name]
    reader = os.path.join(ROOT, "benchmark", "readers",
                          f"{spec['reader']}.py")
    assert os.path.isfile(reader), reader
    paths = {v for k, v in spec["args"].items()
             if k in ("num", "den", "counter")}
    assert paths <= WHAT_A_PARENT_HAS, (name, paths - WHAT_A_PARENT_HAS)
    if spec["reader"] == "kind_latency_median":
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               f"{STREAMS}.json")) as f:
            assert spec["args"]["kind"] in json.load(f)["kinds"]
    else:
        assert paths, name
    assert name.endswith(".streams")


def test_the_streams_cell_declares_eight_metrics_and_one_waits():
    """Eight metrics of its own, and ``cache.evictions_per_task``, which
    reads the counter this PR brings, waits as a file without an entry
    (PR 36's convention) until a parent has the counter."""
    assert len(streams_metrics()) == 8
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "cache.evictions_per_task.json")) as f:
        waiting = json.load(f)
    assert "per_layer_entry" not in waiting
    assert waiting["pending_entry"]["workloads"] == [STREAMS]
    assert waiting["args"]["num"].startswith("health.copr_cache.evictions.")
    assert "cache.evictions_per_task" not in \
        {m["name"] for m in manifest["per_layer"]}
    # the counters it and the hand readings of PERF.md take from /health
    from tikv_tpu.copr.region_cache import RegionColumnarCache
    st = RegionColumnarCache().stats()
    assert st["evictions"] == {"region_lru": 0, "schema_bound": 0}
    assert (st["resident_lines"], st["regions"],
            st["schemas_per_region_max"]) == (0, 0, 0)


def test_the_streams_cell_runs_the_manifests_files():
    import sys
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.append(bench)
    import line
    manifest = line.load_manifest(ROOT)
    cell, config_file, traffic_file = line.cell_files(manifest, STREAMS,
                                                      ROOT)
    assert os.path.isfile(config_file) and os.path.isfile(traffic_file)
    assert cell["chips"] == 1
    with open(config_file) as f:
        config = json.load(f)
    assert os.path.isfile(os.path.join(ROOT, config["toml"]))
    assert config["name"] == cell["config"]
    want = line.declared(manifest, STREAMS, "per_layer")
    assert len(want) == 19 and set(streams_metrics()) < set(want)
    assert set(line.declared(manifest, STREAMS, "end_to_end")) == \
        {"read_p50_ms", "read_p95_ms", "reads_per_s", "setup_s"}
    for name in want:
        assert os.path.isfile(os.path.join(
            bench, "layer_metrics", f"{name}.json")), name


# The cell under an HBM budget (PR 53): each of its nine reads the
# window's records or what the program had before the PR (the arena's
# counters since PR 6), so a parent's line under these files is whole: it
# was measured so (PERF.md section 6).  The driver found that parent too
# unsteady to admit a cell against (two states a process keeps, 22% of
# spread), so the cell's Q1 module now asks one more question and the
# parent exits 1 at its first read, as a program before PR 48 does on the
# streams cell; the readers stay as they were.

HBM = "streams-hbm164-lineitem-sf1-closed4"
WHAT_A_PARENT_OF_PR_53_HAS = WHAT_A_PARENT_HAS | {
    "health.tracing.phases.feed_upload.count",
    "health.device_state.hbm.evictions",
    "health.device_state.hbm.rejections"}


def hbm_metrics() -> list:
    return sorted(name for name, spec in entries().items()
                  if spec["per_layer_entry"]["workloads"] == [HBM])


@pytest.mark.parametrize("name", hbm_metrics())
def test_an_hbm_metric_reads_only_what_its_parent_had(name):
    assert len(hbm_metrics()) == 9 and name.endswith(".hbm")
    spec = entries()[name]
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "readers", f"{spec['reader']}.py"))
    paths = {v for k, v in spec["args"].items() if k in ("num", "den")}
    assert paths <= WHAT_A_PARENT_OF_PR_53_HAS, name
    if spec["reader"] == "kind_latency_median":
        assert spec["args"]["kind"] in hbm_traffic()[0]["kinds"]
    else:
        assert spec["reader"] == "counter_ratio" and len(paths) == 2
    # what the PR adds to /health is read by files that WAIT
    from tikv_tpu.device.supervisor import FeedArena, FlightRecorder
    assert {"evicted_bytes", "memos_kept", "evictions", "rejections"} <= \
        set(FeedArena().stats())
    assert {"gets", "uploads"} <= set(FlightRecorder().feed_counts())


def hbm_traffic() -> tuple:
    """(the cell's traffic file, the streams cell's)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell, = [w for w in json.load(f)["workloads"] if w["name"] == HBM]
    assert cell["traffic"] == HBM
    out = []
    for name in (HBM, STREAMS):
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               f"{name}.json")) as f:
            out.append(json.load(f))
    return tuple(out)


def test_the_hbm_cells_traffic_is_the_streams_cells_but_for_one_module():
    """A file of its own for one word: Q1's module, which asks the
    program one more question; every parameter is the streams file's."""
    mine, theirs = hbm_traffic()
    assert mine.pop("what") != theirs.pop("what")
    assert mine["kinds"]["tpch_q1"].pop("module") == "tpch_q1_streams_hbm"
    assert theirs["kinds"]["tpch_q1"].pop("module") == "tpch_q1_streams"
    assert mine == theirs


def test_the_hbm_cells_q1_is_the_streams_kind_behind_one_more_question():
    """``requests/tpch_q1_streams_hbm.py``: ``tpch_q1``'s classes, send,
    reference, digest and check, the streams kind's question asked
    first, then ``arena_evict`` of the span vocabulary."""
    mine, theirs = streams_kind("tpch_q1_streams_hbm"), streams_kind("tpch_q1")
    assert mine.CLASSES == theirs.CLASSES == ("pallas_hash",)
    for name in ("send", "reference", "digest", "check", "plan"):
        assert getattr(mine, name).__code__ == getattr(theirs, name).__code__
    assert mine.DELTAS == theirs.DELTAS
    assert mine.prepare.__code__ != theirs.prepare.__code__
    from tikv_tpu.utils import trace_vocab
    assert "arena_evict" in trace_vocab.SPAN_VOCABULARY
    assert "arena_evict" in trace_vocab.HOLD_ROWS
    mine.require_program()


@pytest.mark.parametrize("missing", ["arena_evict", "SCHEMAS_PER_REGION"])
def test_the_hbm_cell_refuses_a_program_whose_budget_takes_the_memo(
        missing, monkeypatch):
    """The parent of PR 53 (no ``arena_evict``) and a program before
    PR 48 exit 1 from ``prepare``, before the cell's first read, and
    say what they lack."""
    from tikv_tpu.copr import region_cache
    from tikv_tpu.utils import trace_vocab
    if missing == "arena_evict":
        monkeypatch.delitem(trace_vocab.SPAN_VOCABULARY, missing)
    else:
        monkeypatch.delattr(region_cache, missing)
    with pytest.raises(SystemExit) as e:
        streams_kind("tpch_q1_streams_hbm").prepare(None, None, {})
    assert missing in str(e.value)


def streams_kind(name: str):
    import sys
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.append(bench)
    import byname
    return byname.load("requests", name)


@pytest.mark.parametrize("kind", ["tpch_q1", "tpch_q6", "tpch_q15"])
def test_a_streams_kind_is_its_original_behind_one_question(kind):
    """``requests/<kind>_streams.py``: the original's classes, send,
    reference, digest and check (so its check names), its plan and walk
    by ``__getattr__``, and a ``prepare`` of its own that asks
    ``copr/region_cache.py`` for ``SCHEMAS_PER_REGION`` first."""
    assert ("tikv_tpu.copr.region_cache", "SCHEMAS_PER_REGION") in \
        capabilities_the_benchmark_asks_for()
    mine, theirs = streams_kind(f"{kind}_streams"), streams_kind(kind)
    assert mine.CLASSES == theirs.CLASSES == ("pallas_hash",)
    for name in ("send", "reference", "digest", "check", "plan"):
        assert getattr(mine, name).__code__ == getattr(theirs, name).__code__
    assert mine.VALIDATION == theirs.VALIDATION
    assert mine.prepare.__code__ != theirs.prepare.__code__
    from tikv_tpu.copr import region_cache
    assert region_cache.SCHEMAS_PER_REGION == 8
    streams_kind("tpch_q1_streams").require_program()


@pytest.mark.parametrize("kind", ["tpch_q1", "tpch_q6", "tpch_q15"])
def test_a_streams_kind_refuses_a_program_that_bounds_lines(kind,
                                                             monkeypatch):
    """An older program exits 1 from ``prepare``, before the cell's
    first read of any kind, and says what it lacks."""
    from tikv_tpu.copr import region_cache
    monkeypatch.delattr(region_cache, "SCHEMAS_PER_REGION")
    with pytest.raises(SystemExit) as e:
        streams_kind(f"{kind}_streams").prepare(None, None, {})
    assert "SCHEMAS_PER_REGION" in str(e.value)


# ------------------------------------------ the dispatcher's hold (PR 51)
#
# ``dispatcher.hold_named_share`` is declared in the PR that brings the
# hold's own rows, so its reader has to give a true value on a program
# that lacks them: the parent's five phases there are in its ``parts``
# first, and a part a sample does not hold adds nothing.

HOLD_METRIC = "dispatcher.hold_named_share"


def bench_module(directory: str, name: str):
    import sys
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.append(bench)
    import byname
    return byname.load(directory, name)


def test_the_hold_metric_reads_a_parent_and_names_the_holds_rows():
    from tikv_tpu.utils.trace_vocab import (
        HOLD_SELF, HOLD_WHOLE, SPAN_VOCABULARY,
    )
    spec = entries()[HOLD_METRIC]
    assert spec["reader"] == "phase_cover"
    whole, parts = spec["args"]["whole"], spec["args"]["parts"]
    path = "health.tracing.phases.{}.wall_ms".format
    assert path(whole) in WHAT_A_PARENT_HAS
    assert all(path(p) in WHAT_A_PARENT_HAS for p in parts[:5])
    # ... and then the hold's own rows, each once: what the program adds
    # up (utils/trace.py hold) is what the metric reads
    # (arena_evict, PR 53's leaf, is not in the accepted file: on the
    # cells it lists the budget is 0 and no sweep evicts)
    assert tuple(parts) == tuple(
        r for r in HOLD_WHOLE if r != "arena_evict") + HOLD_SELF
    assert set(parts) | {whole, "dispatch_self"} <= set(SPAN_VOCABULARY)
    assert "dispatch_self" not in parts
    entry = spec["per_layer_entry"]
    assert (entry["unit"], entry["better"], entry["moves"]) == \
        ("%", "higher", "reads_per_s")
    assert entry["workloads"] == ["q1-refresh-lineitem-sf1-closed4",
                                  "q1-lineitem-sf1-closed4", STREAMS]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        # (where the manifest ended at PR 52)
        assert json.load(f)["per_layer"][60] == entry


def _sample(rows: dict) -> dict:
    return {"health": {"tracing": {"phases": {
        name: {"count": 1, "wall_ms": wall} for name, wall in rows.items()}}}}


PARENT_GO = {"group_dispatch": 1000.0, "device_dispatch": 100.0,
             "feed_patch": 50.0, "feed_rebuild": 20.0, "feed_upload": 300.0,
             "host_derive": 10.0}
PARENT_END = {"group_dispatch": 43000.0, "device_dispatch": 7300.0,
              "feed_patch": 5450.0, "feed_rebuild": 2320.0,
              "feed_upload": 1500.0, "host_derive": 3910.0}
OWN_GO = {"group_open": 5.0, "stage_plan": 40.0, "memo_roll": 7.0,
          "stage_full": 60.0, "feed_get": 9.0, "lanes_launch": 11.0,
          "group_complete": 30.0, "dispatch_self": 338.0}
OWN_END = {"group_open": 905.0, "stage_plan": 4040.0, "memo_roll": 3007.0,
           "stage_full": 9060.0, "feed_get": 1009.0, "lanes_launch": 2011.0,
           "group_complete": 1930.0, "dispatch_self": 538.0}


@pytest.mark.parametrize("shape, want", [
    ("parent", 100.0 * 20000.0 / 42000.0),
    ("change", 100.0 * (42000.0 - 200.0) / 42000.0),
    ("change_lacking_a_row", 100.0 * (42000.0 - 200.0 - 3000.0) / 42000.0),
    ("idle", None), ("no_such_row", None)])
def test_phase_cover_over_a_parents_sample_and_a_changes(shape, want):
    read = bench_module("readers", "phase_cover").read
    args = entries()[HOLD_METRIC]["args"]
    go, end = dict(PARENT_GO), dict(PARENT_END)
    if shape.startswith("change"):
        go.update(OWN_GO)
        end.update(OWN_END)
    if shape == "change_lacking_a_row":
        del go["memo_roll"]         # (held by one sample alone: adds 0)
    if shape == "idle":
        end["group_dispatch"] = go["group_dispatch"]
    if shape == "no_such_row":
        del end["group_dispatch"]
    got = read({"counters_go": _sample(go), "counters_end": _sample(end)},
               args)
    if want is None:
        assert got is None
        return
    assert got == pytest.approx(want) and 0 < got <= 100
    if shape == "change":
        # the rows the program adds up: the share and dispatch_self's
        # make the whole
        own = 100.0 * (OWN_END["dispatch_self"] - OWN_GO["dispatch_self"]) \
            / 42000.0
        assert got + own == pytest.approx(100.0)
