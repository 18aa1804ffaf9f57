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


# ------------------------------------------------- composite keys, limbs


def _two_key_snapshot(n: int, seed: int, spans=(7, 5)):
    """``int_table``'s shape with two int keys: ``a`` in [100, 100 +
    spans[0]), ``b`` in [-3, -3 + spans[1]), ``v`` as ``_snapshot``'s."""
    rng = np.random.default_rng(seed)
    table = Table(7350 + seed, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("a", 2, FieldType.long(not_null=True)),
        TableColumn("b", 3, FieldType.long(not_null=True)),
        TableColumn("v", 4, FieldType.long(not_null=True))))
    a = rng.integers(100, 100 + spans[0], n).astype(np.int64)
    b = rng.integers(-3, -3 + spans[1], n).astype(np.int64)
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    ones = np.ones(n, np.bool_)
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {name: Column(EvalType.INT, arr, ones)
         for name, arr in (("a", a), ("b", b), ("v", v))})
    return table, snap, a, b, v


def _lane_builds_done(runner: DeviceRunner) -> None:
    """Wait for the kernels' lane programs (``_build_lanes``: daemon
    threads that compile beside a first build): a process that exits
    while one still traces aborts."""
    import time
    t_end = time.monotonic() + 180
    while time.monotonic() < t_end:
        progs = [e.get("lane_progs") for k, e in
                 runner._kernel_cache.items()
                 if isinstance(k, tuple) and k[:1] == ("hashpl",)
                 and isinstance(e, dict)]
        if all(p is None or all(v is not None for v in p.values())
               for p in progs):
            return
        time.sleep(0.05)
    raise AssertionError("lane programs still building")


def _want_pairs(a, b, v, mask) -> dict:
    want = {}
    for ka, kb in {(int(x), int(y)) for x, y in zip(a[mask], b[mask])}:
        vv = v[mask & (a == ka) & (b == kb)]
        want[ka, kb] = (len(vv), int(vv.sum()))
    return want


@pytest.mark.parametrize("n_devices", [1, 4])
def test_composite_key_matches_numpy(interpret, n_devices):
    """GROUP BY two int keys in the kernel's dense branch: the slot is
    the keys' mixed-radix number, each key's base and span an operand,
    and the finalize takes it apart again."""
    table, snap, a, b, v = _two_key_snapshot(N_ROWS, seed=8)
    runner = _runner(n_devices)

    def dag():
        sel = DagSelect.from_table(table, ["id", "a", "b", "v"])
        return sel.where(sel.col("v") > 0).aggregate(
            [sel.col("a"), sel.col("b")],
            [("count_star", None), ("sum", sel.col("v"))]).build()

    want = _want_pairs(a, b, v, v > 0)
    assert len(want) == 35
    for _ in range(2):
        got = {(r[-2], r[-1]): tuple(r[:-2])
               for r in runner.handle_request(dag(), snap).rows()}
        assert got == want
    _served_by_pallas(runner)
    _finalized_natively(runner)
    recent = runner.flight_recorder.items()
    assert all(e["keys"] == 2 and e["slot_mode"] == "dense" and
               e["planes"] >= 3 for e in recent), recent
    assert runner.flight_recorder.agg_param_counts()[
        "composite_key_launches"] == 2
    _lane_builds_done(runner)


def test_a_composite_key_past_the_grid_takes_the_sparse_recode(interpret):
    """Key spans whose product is over MAX_SLOTS do not index the grid:
    the keys' number is recoded on the host as a sparse key is, and the
    same kernel serves the slot ids."""
    n = 2 * BLOCK + 77      # few enough rows that the pairs fit a grid
    table, snap, a, b, v = _two_key_snapshot(n, seed=9, spans=(9000, 2))
    assert 9000 * 2 > pallas_hash.MAX_SLOTS
    runner = _runner(1)

    def dag():
        sel = DagSelect.from_table(table, ["id", "a", "b", "v"])
        return sel.aggregate(
            [sel.col("a"), sel.col("b")],
            [("count_star", None), ("sum", sel.col("v"))]).build()

    want = _want_pairs(a, b, v, np.ones(n, np.bool_))
    assert 4096 < len(want) < 8192
    got = {(r[-2], r[-1]): tuple(r[:-2])
           for r in runner.handle_request(dag(), snap).rows()}
    assert got == want
    recent = runner.flight_recorder.items()
    assert recent and all(
        e["compile_class"] == "pallas_hash" and e["slot_mode"] == "sparse"
        and e["slots"] == 8193 for e in recent), recent
    _lane_builds_done(runner)


def test_a_refused_composite_build_is_served_recoded(interpret, monkeypatch):
    """A composite key whose kernel the compiler refuses rides the XLA
    stand-in with the keys' number recoded, this request and the next."""
    table, snap, a, b, v = _two_key_snapshot(N_ROWS, seed=11)
    runner = _runner(1)

    def refuse(*_a, **_k):
        raise NotImplementedError("Mosaic says no")

    monkeypatch.setattr(pallas_hash, "build", refuse)

    def dag():
        sel = DagSelect.from_table(table, ["id", "a", "b", "v"])
        return sel.aggregate(
            [sel.col("a"), sel.col("b")],
            [("count_star", None), ("sum", sel.col("v"))]).build()

    want = _want_pairs(a, b, v, np.ones(N_ROWS, np.bool_))
    for _ in range(2):
        got = {(r[-2], r[-1]): tuple(r[:-2])
               for r in runner.handle_request(dag(), snap).rows()}
        assert got == want
    # the dense kernel, then the sparse one over the recoded number,
    # both refused once; then the stand-in, twice
    classes = [e["compile_class"] for e in runner.flight_recorder.items()]
    assert classes == ["pallas_hash"] * 2 + ["hash_twolevel"] * 2
    assert runner.flight_recorder.stats()["faults"] == 2


def test_a_product_past_int32_is_summed_as_limbs(interpret):
    """SUM(a * b) over DECIMAL planes whose product needs 37 bits: the
    kernel sums ``(a >> 16) * b`` and ``(a & 0xFFFF) * b``, the finalize
    puts them together, and the answer is the int64 sum, as the host
    pipeline's Decimals say."""
    import decimal
    from tikv_tpu.datatype import FieldTypeTp
    from tikv_tpu.executors.runner import BatchExecutorsRunner
    from tikv_tpu.expr import Expr
    n = 3 * BLOCK + 77
    rng = np.random.default_rng(10)
    dec = FieldType(tp=FieldTypeTp.NEW_DECIMAL, flen=15, decimal=2)
    table = Table(7390, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("x", 2, dec), TableColumn("m", 3, dec),
        TableColumn("g", 4, FieldType.long(not_null=True))))
    # both signs of both factors; the largest product 1.1e11
    x = rng.integers(-10 ** 9, 10 ** 9, n).astype(np.int64)
    m = rng.integers(-108, 109, n).astype(np.int64)
    x[:4], m[:4] = (10 ** 9 - 1, -10 ** 9, 65535, -65536), (108, -108, 108, 1)
    g = rng.integers(0, 5, n).astype(np.int64)
    ones = np.ones(n, np.bool_)
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"x": Column(EvalType.DECIMAL, x, ones, 2),
         "m": Column(EvalType.DECIMAL, m, ones, 2),
         "g": Column(EvalType.INT, g, ones)})
    runner = _runner(1)

    def dag():
        sel = DagSelect.from_table(table, ["id", "x", "m", "g"])
        return sel.aggregate([sel.col("g")], [
            ("sum", Expr.call("MultiplyDecimal", sel.col("x"),
                              sel.col("m"))),
            ("count_star", None)]).build()

    want = {int(k): int((x[g == k] * m[g == k]).sum()) for k in range(5)}
    assert max(abs(x * m)) > 2 ** 31
    for _ in range(2):
        got = {r[-1]: r for r in runner.handle_request(dag(), snap).rows()}
        assert {k: int(r[0].scaleb(4)) for k, r in got.items()} == want
        assert all(r[0].as_tuple().exponent == -4 for r in got.values())
    host = {r[-1]: r for r in BatchExecutorsRunner(
        dag(), snap).handle_request().rows()}
    assert host == got and isinstance(got[0][0], decimal.Decimal)
    _served_by_pallas(runner)
    assert runner.flight_recorder.agg_param_counts()["limb_sums"] == 2
    _lane_builds_done(runner)


# ------------------------------------------------- grids past 4,096 slots


@pytest.fixture
def wide(interpret, monkeypatch):
    """The one-hot's budget shrunk with BLOCK, so that the step follows
    the grid here as at full size: BLOCK rows up to 4,096 slots (128
    sublanes), a quarter of it at 16,384."""
    monkeypatch.setattr(pallas_hash, "A_BYTES", 128 * BLOCK)


def test_the_step_is_the_block_for_every_grid_up_to_4096_slots():
    """No cell of the benchmark before Q15's changes its kernel: 2^18
    rows a step whatever the grid, and past it a power of two that
    divides the feeds' padding."""
    assert pallas_hash.BLOCK == 1 << 18 and pallas_hash.MAX_SLOTS == 1 << 14
    for slots in (1, 2, 180, 1024, 1025, 4095, 4096):
        assert pallas_hash.block_rows(slots) == 1 << 18, slots
    assert pallas_hash.block_rows(4097) == 1 << 17
    assert pallas_hash.block_rows(8192) == 1 << 17
    assert pallas_hash.block_rows(8193) == 1 << 16
    assert pallas_hash.block_rows(10_000) == 1 << 16      # 320 sublanes
    assert pallas_hash.block_rows(16_384) == 1 << 16      # 512
    for slots in range(1, pallas_hash.MAX_SLOTS + 1, 97):
        step = pallas_hash.block_rows(slots)
        hi = pallas_hash.sublanes(slots)
        assert pallas_hash.BLOCK % step == 0
        assert hi * step <= pallas_hash.A_BYTES < 2 * hi * step or \
            step == pallas_hash.BLOCK


WIDE_ROWS = 4 * BLOCK + 77


def _wide_dag(table, key=None):
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    return sel.where(sel.col("v") > 0).aggregate(
        [key(sel) if key else sel.col("k")],
        [("count_star", None), ("sum", sel.col("v"))]).build()


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("span", [4097, 10_000, 16_384])
def test_a_grid_past_4096_slots_matches_numpy(wide, n_devices, span):
    """GROUP BY a key of ``span`` values (TPC-H Q15's ``l_suppkey`` has
    10,000): the same kernel body over a wider grid, fewer rows a step,
    on one device and psummed over four."""
    rng = np.random.default_rng(span)
    keys = rng.integers(1, 1 + span, WIDE_ROWS).astype(np.int64)
    keys[:2] = 1, span
    table, snap, v = _snapshot(WIDE_ROWS, keys, seed=span % 40 + 20)
    runner = _runner(n_devices)
    want = _want_groups(keys, v, v > 0)
    assert len(want) > 3000
    for _ in range(2):
        assert _group_rows(runner.handle_request(_wide_dag(table),
                                                 snap)) == want
    _served_by_pallas(runner)
    _finalized_natively(runner)
    capacity = 8192 if span == 4097 else 16_384
    recent = runner.flight_recorder.items()
    assert all(e["slots"] == capacity and e["slot_mode"] == "dense" and
               e["block_rows"] == pallas_hash.block_rows(capacity) < BLOCK
               for e in recent), recent
    assert runner.flight_recorder.agg_param_counts()["slots_sum"] == \
        2 * capacity
    _lane_builds_done(runner)


@pytest.mark.parametrize("n_devices", [1, 4])
def test_a_span_over_the_grid_is_served_by_the_stand_in(wide, n_devices):
    """16,385 values do not fit the grid: ``hash_twolevel`` serves, as
    it served past 4,096, and no kernel build is tried."""
    span = pallas_hash.MAX_SLOTS + 1
    rng = np.random.default_rng(5)
    keys = rng.integers(1, 1 + span, WIDE_ROWS).astype(np.int64)
    keys[:2] = 1, span
    table, snap, v = _snapshot(WIDE_ROWS, keys, seed=45)
    runner = _runner(n_devices)
    want = _want_groups(keys, v, v > 0)
    for _ in range(2):
        assert _group_rows(runner.handle_request(_wide_dag(table),
                                                 snap)) == want
    recent = runner.flight_recorder.items()
    assert [e["compile_class"] for e in recent] == ["hash_twolevel"] * 2
    assert all(e["slots"] == 0 for e in recent)
    assert runner.flight_recorder.stats()["faults"] == 0


def test_a_key_that_may_be_null_keeps_its_slot_on_a_wide_grid(wide):
    """An expression key keeps a NULL slot beside its groups: 5,000
    values are 8,192 + 1 slots on the kernel; at 16,384 + 1 the grid is
    full and the stand-in serves."""
    from tikv_tpu.expr import Expr

    def shifted(sel):
        return Expr.call("PlusInt", sel.col("k"),
                         Expr.const(7, EvalType.INT))

    for span, klass, slots in ((5000, "pallas_hash", 8193),
                               (10_000, "hash_twolevel", 0)):
        rng = np.random.default_rng(span)
        keys = rng.integers(1, 1 + span, WIDE_ROWS).astype(np.int64)
        keys[:2] = 1, span
        table, snap, v = _snapshot(WIDE_ROWS, keys, seed=46 + span % 3)
        runner = _runner(1)
        want = _want_groups(keys + 7, v, v > 0)
        assert _group_rows(runner.handle_request(
            _wide_dag(table, shifted), snap)) == want
        recent = runner.flight_recorder.items()
        assert [(e["compile_class"], e["slots"]) for e in recent] == \
            [(klass, slots)], recent
        _lane_builds_done(runner)
