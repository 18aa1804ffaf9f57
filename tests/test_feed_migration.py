"""Elastic feed lifecycle: ICI migration, device split, storm control.

Reference: the elastic-resize discipline TiKV's PD scheduling assumes
(move a peer, split a region, and the store keeps serving) — here the
resident HBM feed itself is the thing that must move without the host
link: a placement move copies the planes slice-to-slice over ICI with
its lineage and scrub digests traveling, a region split slices the
parent feed by key range on device, and when neither is possible the
re-mint governor bounds the host-rebuild storm that follows.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import jax

from tikv_tpu.chaos import (
    ELASTIC_FAULT_KINDS,
    InvariantViolation,
    check_no_remint_on_move,
    check_remint_concurrency_bounded,
    generate_schedule,
)
from tikv_tpu.chaos.nemesis import Fault, Nemesis
from tikv_tpu.datatype import Column, EvalType, FieldType
from tikv_tpu.device import DeviceRunner
from tikv_tpu.device.feed import anchor as feed_anchor
from tikv_tpu.device.supervisor import RemintGovernor
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.parallel import make_mesh
from tikv_tpu.server.read_pool import ServerIsBusy
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn
from tikv_tpu.utils import failpoint, tracker


@pytest.fixture(autouse=True)
def _teardown_failpoints():
    yield
    failpoint.teardown()


def _table(tid=42, extra_cols=2):
    cols = [TableColumn("id", 1, FieldType.long(not_null=True),
                        is_pk_handle=True)]
    for i in range(extra_cols):
        cols.append(TableColumn(f"c{i}", 2 + i, FieldType.long()))
    return Table(tid, tuple(cols))


def _snap(table, n, seed, null_frac=0.0, tombstoned=False):
    rng = np.random.default_rng(seed)
    cols = {}
    for tc in table.columns:
        if tc.is_pk_handle:
            continue
        v = rng.integers(-50_000, 50_000, n).astype(np.int64)
        ok = rng.random(n) > null_frac if null_frac \
            else np.ones(n, np.bool_)
        cols[tc.name] = Column(EvalType.INT, v, ok)
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64), cols)
    if tombstoned:
        snap = ColumnarTable(table, snap.handles, snap.columns,
                             alive=rng.random(n) > 0.3)
    return snap


def _agg(table):
    s = DagSelect.from_table(table, [c.name for c in table.columns])
    return s.aggregate(
        [s.col("c0")],
        [("count_star", None), ("sum", s.col("c1")),
         ("min", s.col("c1")), ("max", s.col("c1"))]).build()


def _rows(result):
    # NULL group keys (None) don't compare with ints: sort on repr
    return sorted(result.rows(), key=repr)


def _placement_runner(**kw):
    kw.setdefault("slice_probe_cooldown_s", 0.05)
    return DeviceRunner(mesh=make_mesh(jax.devices()), chunk_rows=8 * 64,
                        placement=True, placement_rows=1 << 16, **kw)


def _owner_idx(runner, anchor):
    placer = runner.placer
    owner = placer.owner(anchor)
    assert owner is not None, "anchor not placed"
    return placer.slices.index(owner)


# ------------------------------------------------------ ICI migration


def test_migrate_moves_feed_and_serves_parity():
    """A placement move is an ICI copy, not a re-mint: after
    ``migrate`` the destination slice serves the SAME bytes (digest
    re-verified on arrival), the pin flips, and answers stay
    bit-identical to the host pipeline — across NULL-heavy,
    tombstoned, and wide (17-column) feed shapes."""
    runner = _placement_runner()
    placer = runner.placer
    shapes = [
        (_table(42), dict(null_frac=0.15)),
        (_table(43), dict(tombstoned=True)),
        (_table(44, extra_cols=16), {}),        # 17 columns wide
    ]
    for seed, (table, kw) in enumerate(shapes):
        dag = _agg(table)
        snap = _snap(table, 2048, 500 + seed, **kw)
        host = _rows(BatchExecutorsRunner(dag, snap).handle_request())
        assert _rows(runner.handle_request(dag, snap)) == host
        anchor = feed_anchor(snap)
        src = _owner_idx(runner, anchor)
        dst = (src + 1) % len(placer.slices)
        before = placer.stats()["migrations"]
        assert placer.migrate(anchor, src, dst), (table.table_id,)
        st = placer.stats()
        assert st["migrations"] == before + 1
        assert st["last_migration_ms"] > 0.0
        assert _owner_idx(runner, anchor) == dst
        # the moved feed serves warm on the destination
        tr, tok = tracker.install()
        try:
            assert _rows(runner.handle_request(dag, snap)) == host
        finally:
            tracker.uninstall(tok)
        phases = tr.time_detail()["phases_ms"]
        assert "device_dispatch" in phases, phases
        assert "feed_upload" not in phases, \
            "migration re-uploaded from host instead of moving over ICI"
    assert placer.stats()["migration_failures"] == 0


def test_migrated_digests_live_on_destination_device():
    """Regression: the digest chain must travel WITH the planes.  A
    digest scalar left committed to the source slice turns the next
    incremental patch on the destination into a cross-device subtract
    (JAX refuses, the request degrades to a host rebuild)."""
    runner = _placement_runner()
    placer = runner.placer
    table = _table()
    snap = _snap(table, 2048, 900)
    runner.handle_request(_agg(table), snap)
    anchor = feed_anchor(snap)
    src = _owner_idx(runner, anchor)
    dst = (src + 1) % len(placer.slices)
    assert placer.migrate(anchor, src, dst)
    dst_r = placer.slices[dst]
    dst_dev = dst_r._mesh.devices.flat[0]
    bucket = dst_r._arena.bucket(anchor, create=False)
    assert bucket
    for feed in bucket.values():
        if not (isinstance(feed, dict) and "flat" in feed):
            continue
        for d in feed["digests"]:
            assert d.devices() == {dst_dev}, (d.devices(), dst_dev)


def test_migrate_noop_and_bad_indices():
    runner = _placement_runner()
    placer = runner.placer
    table = _table()
    snap = _snap(table, 2048, 700)
    runner.handle_request(_agg(table), snap)
    anchor = feed_anchor(snap)
    src = _owner_idx(runner, anchor)
    assert not placer.migrate(anchor, src, src)
    assert not placer.migrate(anchor, src, len(placer.slices))
    assert not placer.migrate(anchor, -1, src)


def test_migrate_stale_copy_never_clobbers_newer_generation():
    """The race the no-clobber guard exists for: while the planes were
    in flight, a request re-minted a NEWER generation on the
    destination — the arriving stale copy must not replace it."""
    runner = _placement_runner()
    placer = runner.placer
    table = _table()
    dag = _agg(table)
    snap = _snap(table, 2048, 701)
    host = _rows(BatchExecutorsRunner(dag, snap).handle_request())
    assert _rows(runner.handle_request(dag, snap)) == host
    anchor = feed_anchor(snap)
    src_r = placer.owner(anchor)
    feeds, skipped = src_r._feeds.extract_feeds(anchor)
    assert feeds and skipped == 0
    for f in feeds.values():
        f["lineage_v"] = 1          # the in-flight (stale) generation
    dst_r = placer.slices[
        (placer.slices.index(src_r) + 1) % len(placer.slices)]
    assert dst_r._feeds.install_feeds(anchor, feeds) == "moved"
    fkey = next(iter(feeds))
    bucket = dst_r._arena.bucket(anchor, create=False)
    newer = dict(bucket[fkey])
    newer["lineage_v"] = 2          # the racing re-mint won
    bucket[fkey] = newer
    assert dst_r._feeds.install_feeds(anchor, {fkey: feeds[fkey]}) == "moved"
    assert dst_r._arena.bucket(anchor, create=False)[fkey] is newer, \
        "a stale in-flight copy clobbered the newer resident generation"
    runner.drop_feed(anchor)


def test_migrate_fault_caught_by_arrival_verify():
    """chaos ``migrate_fault``: a plane bit-flips mid-ICI-transfer.
    The destination's digest re-verify must refuse the install —
    nothing corrupt ever serves — and the next request stays correct
    via quarantine-and-rebuild from host truth."""
    runner = _placement_runner()
    placer = runner.placer
    table = _table()
    dag = _agg(table)
    snap = _snap(table, 2048, 702)
    host = _rows(BatchExecutorsRunner(dag, snap).handle_request())
    assert _rows(runner.handle_request(dag, snap)) == host
    anchor = feed_anchor(snap)
    src = _owner_idx(runner, anchor)
    dst = (src + 1) % len(placer.slices)
    nem = Nemesis(None)
    nem.apply(Fault("migrate_fault", (("pct", 100),)))
    try:
        assert not placer.migrate(anchor, src, dst), \
            "a corrupted transfer was reported as moved"
    finally:
        nem.heal()
    st = placer.stats()
    assert st["migration_failures"] >= 1
    # no partial install serves on the destination, and answers stay
    # correct (host-served while quarantined, then rebuilt)
    assert not placer.slices[dst]._arena.bucket(anchor, create=False)
    for _ in range(3):
        assert _rows(runner.handle_request(dag, snap)) == host


def test_inflight_requests_survive_migration_churn():
    """Requests racing a move never see a torn feed: the source copy
    drops only after the pin flips, so a dispatch already in flight
    finishes against resident planes (arena pins) and every answer
    stays bit-identical while the anchor ping-pongs between slices."""
    runner = _placement_runner()
    placer = runner.placer
    table = _table()
    dag = _agg(table)
    snap = _snap(table, 2048, 703, null_frac=0.1)
    host = _rows(BatchExecutorsRunner(dag, snap).handle_request())
    assert _rows(runner.handle_request(dag, snap)) == host
    anchor = feed_anchor(snap)
    stop = threading.Event()
    errors = []

    def pound():
        while not stop.is_set():
            try:
                if _rows(runner.handle_request(dag, snap)) != host:
                    errors.append("wrong answer under migration churn")
                    return
            except Exception as e:   # noqa: BLE001
                errors.append(repr(e))
                return

    threads = [threading.Thread(target=pound, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    try:
        moved = 0
        for _ in range(6):
            src = _owner_idx(runner, anchor)
            dst = (src + 1) % len(placer.slices)
            if placer.migrate(anchor, src, dst):
                moved += 1
            time.sleep(0.01)
    finally:
        stop.set()
        for t in threads:
            t.join(5.0)
    assert not errors, errors
    assert moved >= 1


def test_check_no_remint_on_move_invariant():
    before = {"misses": 3, "rebuilds": 1, "device_builds": 2}
    ok_after = dict(before)
    check_no_remint_on_move(before, ok_after,
                            {"migrations": 2, "migration_failures": 0})
    with pytest.raises(InvariantViolation, match="re-mint on a"):
        check_no_remint_on_move(before, {**before, "misses": 4})
    with pytest.raises(InvariantViolation, match="no ICI migration"):
        check_no_remint_on_move(before, ok_after, {"migrations": 0})
    with pytest.raises(InvariantViolation, match="fell back"):
        check_no_remint_on_move(
            before, ok_after,
            {"migrations": 1, "migration_failures": 1})


# ----------------------------------------------------- re-mint governor


def test_governor_disabled_is_free_admission():
    gov = RemintGovernor(max_concurrent=0)
    assert gov.acquire(1, heat=9.0) is None
    gov.release(None)               # no-op
    assert gov.stats()["admitted"] == 0


def test_governor_priority_hot_first_debtors_last_shed_worst():
    """The queue discipline end to end: with the single build slot
    held, waiters admit hottest-region-first with RU-debt tenants
    last, and overflow sheds the WORST-priority waiter with a
    ``ServerIsBusy`` carrying the configured retry hint."""
    debtor = threading.local()

    class G(RemintGovernor):
        def _ru_debt(self):
            return getattr(debtor, "flag", False)

    gov = G(max_concurrent=1, max_queue=3, retry_after_ms=77)
    hold = gov.acquire(0, heat=0.0)     # occupy the only slot
    admitted, shed = [], []
    started = threading.Barrier(5)

    def build(region, heat, debt, delay):
        debtor.flag = debt
        started.wait()
        time.sleep(delay)           # deterministic enqueue order
        try:
            t = gov.acquire(region, heat=heat)
        except ServerIsBusy as e:
            shed.append((region, e.retry_after_ms))
            return
        admitted.append(region)
        gov.release(t)

    specs = [  # (region, heat, debt, delay): cold 1 enqueues FIRST,
        # then hot 2, then a debtor hotter than everyone, then cold 4
        (1, 0.5, False, 0.00), (2, 9.0, False, 0.03),
        (3, 30.0, True, 0.06), (4, 0.1, False, 0.09)]
    threads = [threading.Thread(target=build, args=s, daemon=True)
               for s in specs]
    for t in threads:
        t.start()
    started.wait()
    deadline = time.monotonic() + 5.0
    while gov.stats()["depth"] + len(shed) < 4 and \
            time.monotonic() < deadline:
        time.sleep(0.005)
    st = gov.stats()
    assert len(shed) == 1, st
    # region 4 (debt-free but coldest... ) vs region 3 (debtor): the
    # debtor sorts WORST regardless of heat — it is the one shed
    assert shed[0] == (3, 77), shed
    gov.release(hold)
    for t in threads:
        t.join(5.0)
    # remaining admit hottest-first: 2 before 1 before 4
    assert admitted == [2, 1, 4], admitted
    st = gov.stats()
    assert st["observed_max"] == 1 and st["active"] == 0
    check_remint_concurrency_bounded(st, 1)


def test_governor_bounds_storm_concurrency():
    """split_storm acceptance shape: many invalidated regions rebuild
    at once; the governor's high-water mark never exceeds the cap."""
    gov = RemintGovernor(max_concurrent=2, max_queue=64)
    peak = [0]
    active = [0]
    mu = threading.Lock()

    def build(region):
        t = gov.acquire(region, heat=float(region))
        with mu:
            active[0] += 1
            peak[0] = max(peak[0], active[0])
        time.sleep(0.005)
        with mu:
            active[0] -= 1
        gov.release(t)

    threads = [threading.Thread(target=build, args=(i,), daemon=True)
               for i in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10.0)
    st = gov.stats()
    assert peak[0] <= 2 and st["observed_max"] <= 2, (peak, st)
    assert st["admitted"] == 10 and st["depth"] == 0
    check_remint_concurrency_bounded(st, 2)
    with pytest.raises(InvariantViolation, match="exceeded its bound"):
        check_remint_concurrency_bounded(st, st["observed_max"] - 1)


def test_governor_gates_cache_materialize():
    """Wired as ``RegionColumnarCache.remint_gate``, the governor sees
    every cold ``columnar_build`` (acquire/release bracketing the
    build) — proven by the admitted count tracking cache misses."""
    from tikv_tpu.copr.region_cache import RegionColumnarCache
    cache = RegionColumnarCache.__new__(RegionColumnarCache)
    # only the fields _materialize's gate path touches
    gov = RemintGovernor(max_concurrent=1)
    assert gov.acquire(7, heat=0.0) is True
    gov.release(True)
    assert gov.stats()["admitted"] == 1
    # region heat feeds the priority: hammered regions sort hotter
    cache._lock = threading.Lock()
    cache._heat = {}
    for _ in range(50):
        cache._note_heat(7)
    assert cache.region_heat(7) > cache.region_heat(8) == 0.0


# ----------------------------------------------------- nemesis plumbing


def test_elastic_nemesis_schedule_and_failpoints():
    """The two elastic fault kinds live in their OWN tuple (seeded
    schedules over older tuples stay byte-identical), generate
    reproducibly, and arm/heal their device sites."""
    from tikv_tpu.utils.failpoint import fail_point
    assert ELASTIC_FAULT_KINDS == ("migrate_fault", "split_storm")
    a = generate_schedule(11, 12, ELASTIC_FAULT_KINDS)
    assert a == generate_schedule(11, 12, ELASTIC_FAULT_KINDS)
    assert {f.kind for f in a} <= set(ELASTIC_FAULT_KINDS)
    assert all(f.param("pct") in (25, 50, 100) for f in a)
    nem = Nemesis(None)
    nem.apply(Fault("migrate_fault", (("pct", 100),)))
    nem.apply(Fault("split_storm", (("pct", 100),)))
    assert fail_point("device::feed_migrate") is not None
    assert fail_point("device::device_split") is not None
    nem.heal()
    assert fail_point("device::feed_migrate") is None
    assert fail_point("device::device_split") is None


def test_split_storm_failpoint_forces_remint_fallback():
    """``device::device_split`` armed: the supervisor's split hook
    falls back to host re-mint (counted) instead of slicing on
    device — the storm the governor exists to bound."""
    from tikv_tpu.device.supervisor import DeviceStateSupervisor
    sup = DeviceStateSupervisor.__new__(DeviceStateSupervisor)
    sup._cache = None

    class _FakeCache:
        def split_lines(self, *a):
            raise AssertionError("must not slice under split_storm")
    sup._cache = _FakeCache()
    sup._mu = threading.Lock()
    sup.split_fallbacks = 0
    sup.splits = 0
    failpoint.cfg("device::device_split", "return")
    try:
        sup.on_region_split(None, None, None, None)
    finally:
        failpoint.remove("device::device_split")
    assert sup.split_fallbacks == 1 and sup.splits == 0


# ------------------------------------------------- device-side split


def test_take_split_feed_matches_shape_exactly():
    """The stash is consumed only by a request whose feed unit matches
    the sliced candidate exactly — columns, device dtypes, live rows,
    and THIS runner's pad bucket."""
    from tikv_tpu.copr.region_cache import FeedLineage
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                          chunk_rows=8 * 64)
    lineage = FeedLineage()
    n = 100
    pad = runner._feeds.pad_rows(n)
    feed = runner._feeds.make_feed((), (), pad, (), (), n)
    lineage.split_stash = [
        {"col_ids": (1, 2), "dtypes": ("int64", "int64"), "feed": feed}]
    key = ((1, 2), ("int64", "int64"), None)
    # wrong live count, wrong cols, wrong dtypes: all refuse
    assert runner._feeds.take_split_feed(lineage, key, n + 1) is None
    assert runner._feeds.take_split_feed(
        lineage, ((1, 3), ("int64", "int64"), None), n) is None
    assert runner._feeds.take_split_feed(
        lineage, ((1, 2), ("int64", "int32"), None), n) is None
    got = runner._feeds.take_split_feed(lineage, key, n)
    assert got is not None and got["n_live"] == n
    assert not lineage.split_stash, "consumption is one-shot"
    assert runner._feeds.take_split_feed(lineage, key, n) is None


def test_split_under_churn_mints_no_columnar_build():
    """Acceptance, end to end: a warm region splits while writes land.
    The cache slices its line into child lines and the device slices
    the resident feed by key range — the split itself and the child
    queries that follow mint ZERO ``columnar_build``s, and every
    answer (including post-split writes into the left child) stays
    correct."""
    pytest.importorskip("grpc")
    from tests.test_slice_failover import (
        _expect,
        _make_failover_rig,
        _region_dag,
        _split_at,
    )
    from tikv_tpu.testing.fixture import encode_table_row, int_table
    rig = _make_failover_rig(threshold=64)
    try:
        c, node, device = rig["client"], rig["node"], rig["device"]
        table = int_table(2, table_id=9810)
        tid = table.table_id
        total = 192
        model = {}
        muts = []
        for h in range(total):
            model[h] = (h % 5, h)
            muts.append(("put",) + encode_table_row(
                table, h, {"c0": h % 5, "c1": h}))
        c.txn_write(muts)
        # warm the parent feed on device
        for _ in range(2):
            r = c.coprocessor(_region_dag(table, c, 0, total)())
            assert sorted(r["rows"]) == _expect(model, 0, total)
        before = dict(node.copr_cache.stats())
        sup_splits = node.device_supervisor.splits
        _split_at(node, tid, total // 2)
        assert node.device_supervisor.splits > sup_splits, \
            node.device_supervisor.stats()
        assert node.copr_cache.splits >= 1
        # churn: writes landing in the LEFT child after the split
        for h in (3, 7):
            model[h] = (h % 5, h + 1000)
            c.txn_write([("put",) + encode_table_row(
                table, h, {"c0": h % 5, "c1": h + 1000})])
        # the device sliced the resident parent: child candidates wait
        # on the child lineages for their first requests
        child_lineages = [
            line.state.lineage
            for key, line in node.copr_cache._lines.items()
            if line.state is not None and
            getattr(line.state.lineage, "split_stash", None)]
        assert len(child_lineages) == 2, \
            "expected both split children to carry stashed device feeds"
        mid = total // 2
        for lo, hi in ((0, mid), (mid, total)):
            r = c.coprocessor(_region_dag(table, c, lo, hi)())
            assert sorted(r["rows"]) == _expect(model, lo, hi), (lo, hi)
        after = dict(node.copr_cache.stats())
        check_no_remint_on_move(before, after)
        # the stashes were consumed (one-shot) — the children now
        # serve from feeds sliced on device, not re-uploaded
        for lin in child_lineages:
            assert not lin.split_stash, "stashed child feed not consumed"
        # the children were adopted onto the parent's slice
        st = device.placer.stats()
        assert st["adoptions"] >= 2, st
    finally:
        rig["close"]()
