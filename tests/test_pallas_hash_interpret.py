"""The fused Pallas hash-agg kernel (device/pallas_hash.py) against
plain numpy, in Pallas interpret mode on the virtual CPU mesh.

Mosaic lowering needs a real TPU (chip_smoke.py proves it there); what
CAN rot unseen on CPU is everything before lowering — the kernel body's
tracing, the three slot-id modes, the dead-block guard over bucketed
padding, and the shard_map wrap of the per-shard grid.  The sharded
case is the JAX 0.9 regression: the wrap must pass ``check_vma=False``
(a default-checked shard_map rejects pallas_call's untyped out_shape,
and the runner then silently served the XLA two-level body).

No product knob: the test monkeypatches ``pl.pallas_call`` to
``interpret=True``, lifts the runner's "Mosaic needs a TPU" gate on the
instance, and shrinks BLOCK so interpreted grids stay fast.
"""

import functools

import numpy as np
import pytest

import jax

from tikv_tpu import native
from tikv_tpu.datatype import Column, EvalType, FieldType
from tikv_tpu.device import DeviceRunner, pallas_hash
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.parallel import make_mesh
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn

BLOCK = 1 << 12


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(
        pallas_hash.pl, "pallas_call",
        functools.partial(pallas_hash.pl.pallas_call, interpret=True))
    monkeypatch.setattr(pallas_hash, "BLOCK", BLOCK)


def _runner(n_devices: int) -> DeviceRunner:
    r = DeviceRunner(mesh=make_mesh(jax.devices()[:n_devices]))
    r._is_tpu = True            # lift the CPU gate (aggregate.agg_bodies)
    r._block_local = BLOCK      # feeds pad to whole (patched) blocks
    return r


def _snapshot(n: int, keys: np.ndarray, seed: int):
    rng = np.random.default_rng(seed)
    table = Table(7300 + seed, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("k", 2, FieldType.long(not_null=True)),
        TableColumn("v", 3, FieldType.long(not_null=True))))
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    ones = np.ones(n, np.bool_)
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"k": Column(EvalType.INT, keys, ones),
         "v": Column(EvalType.INT, v, ones)})
    return table, snap, v


def _served_by_pallas(runner: DeviceRunner) -> None:
    """Cold build + warm launch both recorded as pallas_hash, nothing
    cache-disabled, nothing faulted."""
    classes = [e["compile_class"] for e in runner.flight_recorder.items()]
    assert classes == ["pallas_hash", "pallas_hash"], classes
    assert runner.flight_recorder.stats()["faults"] == 0
    disabled = [k for k, v in runner._kernel_cache.items()
                if k[0] == "hashpl" and v is False]
    assert not disabled, disabled


def _finalized_natively(runner: DeviceRunner) -> None:
    """Both requests' accumulators (the cold build's, fetched in line,
    and the warm launch's parts) became planes in the one native call
    (native/fastbuild.cpp ``hash_finalize_packed``) where the extension
    built, and in the numpy chain where it did not: counted either way,
    once a finalize, with GROUP BY and without (the tier-1 path through
    both ``from_packed``s)."""
    built = native.hash_finalize_packed is not None
    assert runner.mesh_stats()["finalize"] == {
        "native": 2 if built else 0, "numpy": 0 if built else 2,
        "native_available": built}


def _group_rows(result) -> dict:
    return {r[-1]: tuple(r[:-1]) for r in result.rows()}


def _want_groups(keys, v, mask) -> dict:
    want = {}
    for key in np.unique(keys[mask]):
        vv = v[mask & (keys == key)]
        want[int(key)] = (len(vv), int(vv.sum()))
    return want


# 16 full blocks + a ragged tail → 17 live blocks, bucketed to 18 (the
# 4-significant-bit grid): the last grid step is a dead block behind
# the pl.when guard
N_ROWS = 16 * BLOCK + 1234


@pytest.mark.parametrize("n_devices", [1, 4])
def test_dense_mode_matches_numpy(interpret, n_devices):
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 1024, N_ROWS).astype(np.int64)
    table, snap, v = _snapshot(N_ROWS, keys, seed=3)
    runner = _runner(n_devices)

    def dag():
        sel = DagSelect.from_table(table, ["id", "k", "v"])
        return sel.where(sel.col("v") > 0).aggregate(
            [sel.col("k")],
            [("count_star", None), ("sum", sel.col("v"))]).build()

    want = _want_groups(keys, v, v > 0)
    assert _group_rows(runner.handle_request(dag(), snap)) == want
    assert _group_rows(runner.handle_request(dag(), snap)) == want
    _served_by_pallas(runner)
    _finalized_natively(runner)
    if n_devices == 1:
        feed_pad = {f["n_pad"] for b in (e.bucket for e in
                    runner._arena._entries.values())
                    for f in b.values()
                    if isinstance(f, dict) and "n_pad" in f}
        assert feed_pad == {18 * BLOCK}, feed_pad   # dead block exists


@pytest.mark.parametrize("n_devices", [1, 4])
def test_sparse_mode_matches_numpy(interpret, n_devices):
    rng = np.random.default_rng(4)
    domain = rng.integers(0, 1 << 62, 1000, dtype=np.int64)
    keys = domain[rng.integers(0, domain.size, N_ROWS)]
    table, snap, v = _snapshot(N_ROWS, keys, seed=4)
    runner = _runner(n_devices)

    def dag():
        sel = DagSelect.from_table(table, ["id", "k", "v"])
        return sel.aggregate(
            [sel.col("k")],
            [("count_star", None), ("sum", sel.col("v"))]).build()

    want = _want_groups(keys, v, np.ones(N_ROWS, np.bool_))
    assert _group_rows(runner.handle_request(dag(), snap)) == want
    assert _group_rows(runner.handle_request(dag(), snap)) == want
    _served_by_pallas(runner)
    _finalized_natively(runner)


@pytest.mark.parametrize("n_devices", [1, 4])
def test_simple_mode_matches_numpy(interpret, n_devices):
    keys = np.zeros(N_ROWS, np.int64)
    table, snap, v = _snapshot(N_ROWS, keys, seed=5)
    runner = _runner(n_devices)

    def dag():
        sel = DagSelect.from_table(table, ["id", "k", "v"])
        return sel.where(sel.col("v") < 500).aggregate(
            [], [("sum", sel.col("v")), ("count", sel.col("v")),
                 ("avg", sel.col("v"))]).build()

    vv = v[v < 500]
    for _ in range(2):
        (row,) = runner.handle_request(dag(), snap).rows()
        assert row[0] == int(vv.sum()) and row[1] == len(vv), row
        assert row[2] == int(vv.sum()) / len(vv), row
    _served_by_pallas(runner)
    _finalized_natively(runner)


@pytest.mark.parametrize("keys", ["dense", "sparse"])
def test_a_warm_sharded_launch_takes_its_arguments_as_they_lie(interpret,
                                                               keys):
    """The benchmark's four-chip launch site (``_try_pallas``'s
    ``launch`` of the ``shard_map`` wrap): warm, it hands the jitted
    program the row count and the key base from the runner's cache,
    committed replicated over the mesh, beside the row-sharded feed,
    so the call passes with transfers to a device and between devices
    disallowed and the cache uploads nothing (on the tree before PR 30
    the device-to-device guard refused the two scalars' re-lay)."""
    rng = np.random.default_rng(6)
    if keys == "dense":
        k = rng.integers(0, 1024, N_ROWS).astype(np.int64)
    else:
        domain = rng.integers(0, 1 << 62, 1000, dtype=np.int64)
        k = domain[rng.integers(0, domain.size, N_ROWS)]
    table, snap, v = _snapshot(N_ROWS, k, seed=6 if keys == "dense" else 7)
    runner = _runner(4)

    def dag():
        sel = DagSelect.from_table(table, ["id", "k", "v"])
        return sel.aggregate(
            [sel.col("k")],
            [("count_star", None), ("sum", sel.col("v"))]).build()

    want = _want_groups(k, v, np.ones(N_ROWS, np.bool_))
    assert _group_rows(runner.handle_request(dag(), snap)) == want
    before = runner.mesh_stats()["scalar_cache"]
    with jax.transfer_guard_device_to_device("disallow"), \
            jax.transfer_guard_host_to_device("disallow"):
        pending = runner.handle_request(dag(), snap, deferred=True)
    assert _group_rows(pending.result()) == want
    _served_by_pallas(runner)
    after = runner.mesh_stats()["scalar_cache"]
    assert after["uploads"] == before["uploads"]
    assert after["hits"] >= before["hits"] + 2
    four = set(runner._mesh.devices.flat)
    for key, arr in runner._scalar_cache.items():
        assert arr.committed and arr.devices() == four, key
        assert arr.sharding.is_equivalent_to(runner._repl, arr.ndim), key
