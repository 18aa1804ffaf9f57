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
