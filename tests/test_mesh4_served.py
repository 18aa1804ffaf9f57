"""The four-chip store (benchmark configuration ``int3-10m-mesh4``) at a
small size on the virtual CPU devices, the first four as the 2x2 mesh
its TOML configures: the served path (gRPC client → store → reply)
against the benchmark's own numpy reference on ``int_table`` data from a
seed, the control (sums served in bfloat16 must come out wrong), the
share test (the four shards' partials, each computed alone on its rows,
add up to the unsharded reference), and what the deployment's spans and
counters say: the ``mesh`` label of the runner that launched, ``/health``
``device_mesh``'s counts, and both after a submesh rebuild."""

import contextlib
import json
import os
import sys
import threading
import time
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tikv_tpu.config import TikvConfig
from tikv_tpu.datatype import Column, EvalType
from tikv_tpu.device import DeviceRunner
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.parallel import make_mesh, parse_mesh_shape
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.utils import failpoint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:       # the request kind imports ``byname``
    sys.path.append(BENCH)

import byname  # noqa: E402

ROWS = 20000
SEED = 2600000027           # the driver's seeds are this large
THRESHOLD = 256             # a toy table must still route to the device
KEYS = {
    "dense": {"dist": "uniform_dense", "groups": 1024},
    "sparse": {"dist": "uniform_sparse", "groups": 1024,
               "domain_bits": 62},
}
TABLE_IDS = {"dense": 9900, "sparse": 9901}


def load_config() -> dict:
    with open(os.path.join(BENCH, "configs", "int3-10m-mesh4.json")) as f:
        return json.load(f)


def table_spec(keys: str) -> dict:
    spec = json.loads(json.dumps(load_config()["table"]))
    spec["table_id"] = TABLE_IDS[keys]
    spec["columns"]["c0"] = KEYS[keys]
    return spec


@pytest.fixture(scope="module")
def kind():
    return byname.load("requests", "hash_agg_mesh")


@pytest.fixture(scope="module")
def params():
    with open(os.path.join(BENCH, "traffic", "agg-mesh4-closed8.json")) as f:
        return json.load(f)["kinds"]["hash_agg_mesh"]["params"]


@pytest.fixture(scope="module")
def store():
    """The store as ``benchmark/rig.py`` builds it from the
    configuration's TOML, with PD in process and both key kinds of the
    table loaded through ImportSST."""
    pytest.importorskip("grpc")
    from tikv_tpu.raftstore.metapb import Store
    from tikv_tpu.server import (
        Node, PdServer, RemotePdClient, TikvServer, TxnClient,
    )
    config = TikvConfig.from_file(os.path.join(ROOT, load_config()["toml"]))
    cc = config.coprocessor
    assert cc.mesh_shape == "2x2" and not cc.device_placement
    cc.device_row_threshold = THRESHOLD
    runner = DeviceRunner(
        mesh=make_mesh(jax.devices()[:4],
                       shape=parse_mesh_shape(cc.mesh_shape)),
        chunk_rows=1 << 12, slice_probe_cooldown_s=0.05)
    pd_server = PdServer("127.0.0.1:0")
    pd_server.start()
    pd_addr = f"127.0.0.1:{pd_server.port}"
    node = Node("127.0.0.1:0", RemotePdClient(pd_addr),
                device_runner=runner, config=config)
    srv = TikvServer(node)
    node.addr = f"127.0.0.1:{srv.port}"
    node.pd.put_store(Store(node.store_id, node.addr))
    srv.start()
    client = TxnClient(pd_addr)
    int_table = byname.load("tables", "int_table")
    ctxs = {}
    for keys in KEYS:
        spec = table_spec(keys)
        table = int_table.fixture(spec)
        cols = int_table.make(spec, SEED, ROWS)
        int_table.load(client, node.store_id, table, cols)
        ctxs[keys] = types.SimpleNamespace(table=table, rows=ROWS,
                                           cols=cols)
    yield types.SimpleNamespace(node=node, runner=runner, client=client,
                                pd_addr=pd_addr, ctxs=ctxs,
                                TxnClient=TxnClient)
    srv.stop()
    pd_server.stop()


def served(store, kind, params, keys, client=None) -> dict:
    """One read as ``benchmark/loadgen.py`` records it."""
    client = client or store.client
    ctx = store.ctxs[keys]
    resp = kind.send(ctx, client, kind.prepare(ctx, client, params))
    td = resp["time_detail"]
    assert resp["backend"] == "device", resp.get("backend")
    assert "degraded" not in td["labels"], td["labels"]
    return {"labels": td["labels"], "phases_ms": td["phases_ms"],
            "answer": kind.digest(ctx, resp, params)}


def failing(checks) -> list:
    return [name for name, value, limit in checks if value > limit]


# ------------------------------------------------- served path vs reference


@pytest.mark.parametrize("keys", sorted(KEYS))
def test_served_answers_equal_the_numpy_reference(store, kind, params, keys):
    """Every answer of eight concurrent closed-loop clients equals the
    reference exactly, each read labelled with the mesh that launched
    it, and every launch ran on all four devices (a whole-mesh read is
    a launch of its own from its request's thread: no coalescer)."""
    ctx = store.ctxs[keys]
    before = store.runner.mesh_stats()
    launches0 = store.runner.flight_recorder.stats()["launches"]
    records = [served(store, kind, params, keys)]   # cold build
    mu = threading.Lock()

    def client_loop():
        client = store.TxnClient(store.pd_addr)
        mine = [served(store, kind, params, keys, client) for _ in range(4)]
        with mu:
            records.extend(mine)
    threads = [threading.Thread(target=client_loop) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(records) == 33
    checks = kind.check(ctx, records, params, kind.reference(ctx, params))
    assert [c[0] for c in checks] == ["hash_agg.wrong_answers",
                                      "mesh.reads_off_the_mesh"]
    assert failing(checks) == [], checks
    assert not any(r.get("wrong") for r in records)
    assert {r["labels"]["mesh"] for r in records} == {"2x2"}
    after = store.runner.mesh_stats()
    launches = store.runner.flight_recorder.stats()["launches"] - launches0
    assert launches == len(records)
    assert after["sharded_launches"] - before["sharded_launches"] == launches
    assert after["submesh_rebuilds"] == before["submesh_rebuilds"]
    assert after["live"] == {"shape": "2x2", "n_devices": 4}
    assert after["feed_bytes_per_shard"] > 0
    for e in store.runner.flight_recorder.items()[-launches:]:
        assert (e["mesh"], e["shards"], e["slice"]) == ("2x2", 4, None), e


@pytest.mark.parametrize("keys", sorted(KEYS))
def test_sums_served_in_bfloat16_are_caught(store, kind, params, keys,
                                            monkeypatch):
    """The control on the mesh: the runner's finalize hands on its
    sums rounded to bfloat16, the next precision down."""
    from tikv_tpu.device import aggregate as agg_mod
    sound = agg_mod._hash_columns
    to_bf16 = byname.load("requests", "hash_agg").to_bf16

    def lower_precision(*args, **kw):
        cols = sound(*args, **kw)       # [count, sum, key]
        cols[1] = Column(cols[1].eval_type, to_bf16(cols[1].values),
                         cols[1].validity)
        return cols
    monkeypatch.setattr(agg_mod, "_hash_columns", lower_precision)
    ctx = store.ctxs[keys]
    records = [served(store, kind, params, keys) for _ in range(3)]
    checks = dict((n, v) for n, v, _lim in kind.check(
        ctx, records, params, kind.reference(ctx, params)))
    # by one of the cell's limits, and not by each: the layout held
    assert checks == {"hash_agg.wrong_answers": 3,
                      "mesh.reads_off_the_mesh": 0}
    assert all(r["wrong"] for r in records)


def test_control_py_fails_the_new_cell_by_the_answer_alone():
    """``benchmark/control.py`` puts the reference, one precision down,
    in the program's place: an answer alone, which says nothing of the
    layout."""
    import control
    for name, checks in control.controls(
            "agg-mesh4-closed8", SEED, rows=4096).items():
        assert failing(checks) == ["hash_agg.wrong_answers"], (name, checks)


# ------------------------------------------------------------- share test


def shard_snapshot(table, cols, lo, hi):
    ones = np.ones(hi - lo, np.bool_)
    return ColumnarTable.from_arrays(
        table, np.arange(lo, hi, dtype=np.int64),
        {c.name: Column(EvalType.INT, cols[c.name][lo:hi], ones)
         for c in table.columns if c.name in cols})


@pytest.mark.parametrize("keys", sorted(KEYS))
def test_the_four_shards_partials_add_up_to_the_whole(store, kind, params,
                                                      keys):
    """model-configs §4's share test for rows sharded over the mesh:
    the plan run alone on each shard's rows (the row bounds of the
    sharded feed, on a one-device runner) gives four partial grids
    whose COUNT and SUM per key add up to the unsharded reference,
    which is also what the mesh serves."""
    ctx = store.ctxs[keys]
    runner = store.runner
    n_local = runner._feeds.pad_rows(ROWS) // 4
    assert 3 * n_local < ROWS <= 4 * n_local    # four live shards
    one = DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                       chunk_rows=1 << 12)
    total: dict = {}
    for shard in range(4):
        lo, hi = shard * n_local, min((shard + 1) * n_local, ROWS)
        sel = DagSelect.from_table(ctx.table,
                                   [c.name for c in ctx.table.columns])
        dag = sel.aggregate(
            [sel.col(params["group_by"])],
            [("count_star", None), ("sum", sel.col(params["sum"]))]).build()
        part = one.handle_request(
            dag, shard_snapshot(ctx.table, ctx.cols, lo, hi)).rows()
        assert sum(r[0] for r in part) == hi - lo
        for cnt, sm, key in part:
            c = total.setdefault(key, [0, 0])
            c[0] += cnt
            c[1] += sm
    whole = kind.reference(ctx, params)
    assert sorted([c, s, k] for k, (c, s) in total.items()) == \
        sorted(whole.tolist())
    assert served(store, kind, params, keys)["answer"] == whole.tobytes()


# -------------------------------------------- the label after a rebuild


def test_a_submesh_rebuild_changes_the_label_and_the_count(store, kind,
                                                           params):
    """A slice tripped the way the failure-domain tests trip one: the
    reads served after the rebuild carry the submesh's shape (so the
    cell's check counts them off the mesh, right answers and all),
    ``submesh_rebuilds`` rises and ``sharded_launches`` stands still;
    healed, reads are back on 2x2."""
    ctx = store.ctxs["dense"]
    runner = store.runner
    reference = kind.reference(ctx, params)
    assert served(store, kind, params, "dense")["labels"]["mesh"] == "2x2"
    before = runner.mesh_stats()
    failpoint.cfg("device::slice_dead", "return(1)")
    try:
        # strikes are served on the host rung (``degraded``, which the
        # load generator counts as failed) until the slice trips
        deadline = time.monotonic() + 20
        while not runner._board.quarantined_set():
            assert time.monotonic() < deadline, runner._board.stats()
            client = store.client
            kind.send(ctx, client, kind.prepare(ctx, client, params))
        after_trip = [served(store, kind, params, "dense")
                      for _ in range(3)]
        mid = runner.mesh_stats()
    finally:
        failpoint.teardown()
        end = time.monotonic() + 5
        while runner._board.quarantined_set() and time.monotonic() < end:
            runner.probe_quarantined()
            time.sleep(0.02)
        assert not runner._board.quarantined_set()
        runner._degraded_target()       # the full mesh takes over again
    assert {r["labels"]["mesh"] for r in after_trip} == {"1x2"}
    checks = dict((n, v) for n, v, _lim in kind.check(
        ctx, after_trip, params, reference))
    assert checks == {"hash_agg.wrong_answers": 0,
                      "mesh.reads_off_the_mesh": 3}
    assert mid["submesh_rebuilds"] == before["submesh_rebuilds"] + 1
    assert mid["live"] == {"shape": "1x2", "n_devices": 2}
    assert mid["sharded_launches"] == before["sharded_launches"]
    assert runner.failure_domain_stats()["slices"][1]["state"] == "healthy"
    healed = served(store, kind, params, "dense")
    assert healed["labels"]["mesh"] == "2x2"
    assert healed["answer"] == reference.tobytes()
    now = runner.mesh_stats()
    assert now["live"] == {"shape": "2x2", "n_devices": 4}
    assert now["sharded_launches"] > mid["sharded_launches"]


# ------------------------- the cached scalars lie where the program wants


def replicated_over(arr, runner) -> bool:
    """Committed, with the sharding every sharded program of ``runner``
    declares for a scalar (``P()`` over its mesh), one copy a device."""
    return (arr.committed
            and arr.sharding.is_equivalent_to(runner._repl, arr.ndim)
            and arr.devices() == set(runner._mesh.devices.flat)
            and len(arr.addressable_shards) == runner._nshards())


def cached_values(runner) -> dict:
    return {"scalar": runner._cached_scalar(ROWS, jnp.int64),
            "int param": runner._cached_param(960, jnp.int32),
            "float param": runner._cached_param(0.5, jnp.float32)}


def test_a_mesh_runners_cached_scalars_are_replicated_over_its_mesh(store):
    """What ``_cached_scalar`` / ``_cached_param`` hand a sharded
    program lies on all four devices, committed with ``runner._repl``:
    the jitted call takes it as it lies; the value and dtype are what
    was asked for, and the second look-up is the same array."""
    runner = store.runner
    assert len(set(runner._mesh.devices.flat)) == 4
    for name, arr in cached_values(runner).items():
        assert replicated_over(arr, runner), (name, arr.sharding)
    got = cached_values(runner)
    assert (int(got["scalar"]), str(got["scalar"].dtype)) == (ROWS, "int64")
    assert (int(got["int param"]), str(got["int param"].dtype)) == \
        (960, "int32")
    assert (float(got["float param"]), str(got["float param"].dtype)) == \
        (0.5, "float32")
    assert all(a is b for a, b in zip(got.values(),
                                      cached_values(runner).values()))


@pytest.mark.parametrize("device", [0, 2])
def test_a_one_device_runners_cached_scalars_stay_on_its_device(device):
    """The one-chip cells' runner and a placement slice make theirs as
    before: uncommitted, on the single device they run on (a slice's
    requests run under its ``_device_scope``)."""
    one = DeviceRunner(mesh=make_mesh(jax.devices()[device:device + 1]),
                       chunk_rows=1 << 12)
    assert one._single
    with one._device_scope():
        got = cached_values(one)
    for name, arr in got.items():
        assert arr.devices() == {jax.devices()[device]}, name
        assert not arr.committed, name
        assert len(arr.addressable_shards) == 1, name
    assert one.mesh_stats()["scalar_cache"] == {"hits": 0, "uploads": 3}


@contextlib.contextmanager
def launches_guarded():
    """Every launch of every runner, on whatever thread serves it, runs
    with device-to-device and host-to-device transfers disallowed
    around the jitted call (JAX's guards are per thread, so they are
    set inside ``_dispatch_phase``, where the request's thread is).
    → the compile classes launched so."""
    inner = DeviceRunner._dispatch_phase
    launched = []

    @contextlib.contextmanager
    def guarded(self, klass, key=None):
        with inner(self, klass, key), \
                jax.transfer_guard_device_to_device("disallow"), \
                jax.transfer_guard_host_to_device("disallow"):
            launched.append(klass)
            yield
    DeviceRunner._dispatch_phase = guarded
    try:
        yield launched
    finally:
        DeviceRunner._dispatch_phase = inner


@contextlib.contextmanager
def slice_one_tripped(store, kind, params):
    """Slice 1 dead until the block ends (the way
    ``test_a_submesh_rebuild_changes_the_label_and_the_count`` trips
    it); inside, whole-mesh reads are served by the 1x2 sub-runner."""
    ctx = store.ctxs["dense"]
    runner = store.runner
    failpoint.cfg("device::slice_dead", "return(1)")
    try:
        deadline = time.monotonic() + 20
        while not runner._board.quarantined_set():
            assert time.monotonic() < deadline, runner._board.stats()
            kind.send(ctx, store.client,
                      kind.prepare(ctx, store.client, params))
        yield
    finally:
        failpoint.teardown()
        end = time.monotonic() + 5
        while runner._board.quarantined_set() and time.monotonic() < end:
            runner.probe_quarantined()
            time.sleep(0.02)
        assert not runner._board.quarantined_set()
        runner._degraded_target()       # the full mesh takes over again


def test_a_degraded_sub_runner_replicates_over_its_own_two_devices(
        store, kind, params):
    """The 1x2 submesh runner is a ``DeviceRunner`` of its own: its
    cache and its ``_repl`` are its own, over the two devices it
    serves on, nothing shared with the parent's four; only the counts
    go to the parent's ``/health`` block, as its launches do."""
    runner = store.runner
    reference = kind.reference(store.ctxs["dense"], params)
    with slice_one_tripped(store, kind, params):
        assert served(store, kind, params, "dense")["labels"]["mesh"] == "1x2"
        sub, dead = runner._live_mesh()
        assert sub is not runner and dead == (1,)
        assert sub._scalar_cache is not runner._scalar_cache
        assert sub.flight_recorder is runner.flight_recorder
        assert len(sub._scalar_cache) >= 2      # the read's n and base
        two = set(sub._mesh.devices.flat)
        assert len(two) == 2 and two < set(runner._mesh.devices.flat)
        for name, arr in {**cached_values(sub),
                          **dict(sub._scalar_cache)}.items():
            assert replicated_over(arr, sub), (name, arr.sharding)
            assert arr.devices() == two, name
        before = runner.mesh_stats()["scalar_cache"]
        with launches_guarded() as launched:
            warm = served(store, kind, params, "dense")
        after = runner.mesh_stats()["scalar_cache"]
        assert launched and warm["answer"] == reference.tobytes()
        assert warm["labels"]["mesh"] == "1x2"
        assert after["uploads"] == before["uploads"]
        assert after["hits"] >= before["hits"] + 2
    for name, arr in cached_values(runner).items():
        assert replicated_over(arr, runner), name


def test_the_guards_refuse_a_scalar_that_lies_on_one_device(store):
    """The control for the test below: handed a scalar on one device,
    as ``_cached_scalar`` made it before, a sharded program's call is
    refused by the device-to-device guard on this backend too."""
    from jax.sharding import PartitionSpec as P
    runner = store.runner
    prog = jax.jit(jax.shard_map(
        lambda n, x: x + n, mesh=runner._mesh,
        in_specs=(P(), P()), out_specs=P(), check_vma=False))
    x = runner._cached_scalar(1, jnp.int64)
    assert int(prog(runner._cached_scalar(ROWS, jnp.int64), x)) == ROWS + 1
    with jax.transfer_guard_device_to_device("disallow"), \
            jax.transfer_guard_host_to_device("disallow"):
        assert int(prog(runner._cached_scalar(ROWS, jnp.int64), x)) == \
            ROWS + 1
        with pytest.raises(Exception, match="[Dd]isallowed"):
            prog(jnp.asarray(ROWS, jnp.int64), x)


@pytest.mark.parametrize("keys", sorted(KEYS))
def test_a_warm_whole_mesh_read_moves_nothing_to_or_between_devices(
        store, kind, params, keys):
    """After one warm-up read, served reads launch with every argument
    where the program wants it: no transfer to a device or between
    devices is allowed around the launch and none is asked for, the
    answers are the reference's, the cache uploads nothing and is hit
    at least twice a launch."""
    ctx = store.ctxs[keys]
    runner = store.runner
    served(store, kind, params, keys)
    before = runner.mesh_stats()
    launches0 = runner.flight_recorder.stats()["launches"]
    with launches_guarded() as launched:
        records = [served(store, kind, params, keys) for _ in range(3)]
    after = runner.mesh_stats()
    launches = runner.flight_recorder.stats()["launches"] - launches0
    assert launches == len(launched) == 3
    assert failing(kind.check(ctx, records, params,
                              kind.reference(ctx, params))) == []
    assert {r["labels"]["mesh"] for r in records} == {"2x2"}
    assert "host_exec" not in {p for r in records for p in r["phases_ms"]}
    assert after["sharded_launches"] - before["sharded_launches"] == 3
    assert after["scalar_cache"]["uploads"] == \
        before["scalar_cache"]["uploads"]
    assert after["scalar_cache"]["hits"] >= \
        before["scalar_cache"]["hits"] + 2 * launches
    assert runner.flight_recorder.stats()["faults"] == 0
