"""Chip failure domains: slice health, elastic mesh degrade, rescue.

The full trip/drain/probe lifecycle (device/supervisor.py SliceHealth,
device/placement.py drain, runner._degraded_target, README "Device
failure domains") on the 8-device virtual CPU mesh:

- unit: the SliceHealth state machine (strike/decay/trip/half-open
  probe/decayed re-admission, latency outliers) and the
  healthy_submesh 8→4→2→1 ladder;
- slice trip → anchor drain → healthy-slice parity, randomized against
  the host pipeline incl. NULL-heavy and tombstoned feeds;
- sharded-feed mesh downsize 4→2 with zero wrong results, the
  mesh_rebuild tracker phase, and full-mesh restore after re-admission;
- half-open re-admission: probes fail while the fault persists, succeed
  after heal, and the score decays instead of resetting;
- in-flight rescue: DeferredResult and coalesced groups racing slice
  death retry per-member on a healthy slice — no wedged dispatch lock,
  no double-unpin, no member failed for a group-mate's fault;
- flapping-chip chaos schedules (fast tier-1 twin + slow full) over
  the slice_dead / chip_flap / device_degrade nemesis kinds with the
  check_no_quarantined_dispatch invariant;
- the end-to-end acceptance rig: a live gRPC node with placement,
  persistent mid-churn chip death — zero wrong results, zero late
  acks, warm queries stay on the DEVICE backend while the dead slice
  is quarantined (check_mesh_serves_degraded), re-admission after the
  fault lifts;
- stop-under-load: node.stop() while requests are in flight leaves no
  pinned arena lines, no parked coalescer members, and (enforced by
  the conftest leak guard) no non-daemon worker threads.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import jax

from tikv_tpu.chaos import (
    Nemesis,
    check_mesh_serves_degraded,
    check_no_quarantined_dispatch,
    generate_schedule,
)
from tikv_tpu.datatype import Column, EvalType, FieldType
from tikv_tpu.device import DeviceRunner
from tikv_tpu.device.feed import anchor as feed_anchor
from tikv_tpu.device.supervisor import SliceHealth, SliceHealthBoard
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.parallel import healthy_submesh, make_mesh
from tikv_tpu.pd.scheduler import drain_receivers
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn
from tikv_tpu.utils import failpoint, tracker


@pytest.fixture(autouse=True)
def _teardown_failpoints():
    yield
    failpoint.teardown()


def _table(tid=42):
    return Table(tid, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("k", 2, FieldType.long()),
        TableColumn("v", 3, FieldType.long()),
    ))


def _snap(table, n, seed, null_frac=0.0, tombstoned=False):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 60, n).astype(np.int64)
    v = rng.integers(-50_000, 50_000, n).astype(np.int64)
    kok = rng.random(n) > null_frac if null_frac \
        else np.ones(n, np.bool_)
    vok = rng.random(n) > null_frac if null_frac \
        else np.ones(n, np.bool_)
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"k": Column(EvalType.INT, k, kok),
         "v": Column(EvalType.INT, v, vok)})
    if tombstoned:
        snap = ColumnarTable(table, snap.handles, snap.columns,
                             alive=rng.random(n) > 0.3)
    return snap


def _agg(table):
    s = DagSelect.from_table(table, ["id", "k", "v"])
    return s.aggregate(
        [s.col("k")],
        [("count_star", None), ("sum", s.col("v")),
         ("min", s.col("v")), ("max", s.col("v"))]).build()


def _sel(table, thr):
    s = DagSelect.from_table(table, ["id", "k", "v"])
    return s.where(s.col("v") > int(thr)).build()


def _rows(result):
    return sorted(result.rows())


def _heal(runner, deadline_s=3.0):
    """Remove chip faults and drive probes until every slice is
    re-admitted and the full mesh is restored — every test leaves the
    board clean (the conftest leak guard enforces it)."""
    failpoint.teardown()
    board = runner._board
    if board is None:
        return
    end = time.monotonic() + deadline_s
    while board.quarantined_set() and time.monotonic() < end:
        runner.probe_quarantined()
        time.sleep(0.02)
    assert not board.quarantined_set(), board.stats()
    if not runner._single:
        # restore the full mesh (drops the degraded runner's feeds)
        runner._degraded_target()


# --------------------------------------------------------------- units


def test_slice_health_state_machine():
    h = SliceHealth(0, trip_strikes=3.0, cooldown_s=0.01)
    assert h.state == "healthy" and not h.quarantined()
    # isolated faults decay away under traffic
    assert not h.note_fault("dispatch")
    h.note_ok()
    h.note_ok()
    assert h.score == 0.0
    # three strikes trip
    assert not h.note_fault("dispatch")
    assert not h.note_fault("fetch")
    assert h.note_fault("scrub")        # the tripping strike
    assert h.quarantined() and h.trips == 1
    # no probe before the cooldown; exactly one at a time after it
    assert not h.try_probe()
    time.sleep(0.012)
    assert h.try_probe()
    assert not h.try_probe(), "half-open admits ONE probe"
    h.probe_result(False)
    assert h.quarantined() and h.probe_failures == 1
    assert not h.try_probe(), "cooldown restarts after a failed probe"
    time.sleep(0.012)
    assert h.try_probe()
    h.probe_result(True)
    # re-admitted with a DECAYED score, not a reset one
    assert not h.quarantined() and h.readmits == 1
    assert h.score == pytest.approx(2.0)
    assert h.penalty() == pytest.approx(2.0 / 3.0)
    # one fresh fault re-trips immediately (half-open discipline)
    assert h.note_fault("dispatch")
    assert h.quarantined() and h.trips == 2


def test_slice_health_latency_outliers():
    h = SliceHealth(0, trip_strikes=1.0, latency_outlier_s=0.5)
    h.note_ok(0.1)
    assert h.score == 0.0
    for _ in range(4):
        h.note_ok(0.9)              # outliers strike fractionally
    assert h.quarantined(), h.stats()
    assert h.strikes["latency"] == 4
    # disabled feed: None AND the config default 0.0 both mean OFF —
    # outliers never strike (0.0 reaching the comparison would make
    # EVERY served request a strike; review regression)
    for off in (None, 0.0):
        h2 = SliceHealth(0, trip_strikes=1.0, latency_outlier_s=off)
        h2.note_ok(100.0)
        assert h2.score == 0.0, off


def test_latency_trip_fires_drain_listeners():
    """A latency-outlier strike that TRIPS must fire the board's trip
    listeners exactly like a hard fault — a latency-quarantined slice
    drains, it doesn't silently rot (review regression)."""
    runner = _placement_runner(slice_latency_outlier_s=0.5,
                               slice_trip_strikes=0.5)
    table = _table()
    dag = _agg(table)
    snap = _snap(table, 2048, 1234)
    assert _rows(runner.handle_request(dag, snap)) == _rows(
        BatchExecutorsRunner(dag, snap).handle_request())
    oidx = runner.placer.slices.index(
        runner.placer.owner(feed_anchor(snap)))
    trips = []
    runner._board.add_trip_listener(lambda i, r: trips.append((i, r)))
    # feed outlier latencies straight into the slice's ok path (the
    # seam _finish drives); two 0.25 strikes cross the 0.5 trip
    owner = runner.placer.slices[oidx]
    owner._note_slice_ok(9.9)
    owner._note_slice_ok(9.9)
    assert (oidx, "latency") in trips, trips
    assert oidx in runner._board.quarantined_set()
    # the drain ran: no feed bytes left on the condemned slice
    check_no_quarantined_dispatch(runner)
    runner._board.reset()


def test_mesh_serving_decays_board_scores():
    """Whole-mesh (non-placement) serving decays EVERY slice's strike
    score — a re-admitted chip earns its way back to 0 under mesh
    traffic instead of sitting one strike from re-quarantine forever
    (review regression)."""
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:4]),
                          chunk_rows=8 * 64)
    table = _table()
    dag = _agg(table)
    snap = _snap(table, 5000, 4321)
    board = runner._board
    board.note_fault(2, "dispatch")
    board.note_fault(2, "dispatch")
    assert board.slice(2).stats()["score"] == pytest.approx(2.0)
    for _ in range(4):
        runner.handle_request(dag, snap)
    assert board.slice(2).stats()["score"] == pytest.approx(0.0), \
        board.stats()


def test_board_trip_listener_and_reset():
    board = SliceHealthBoard(4, trip_strikes=2.0)
    trips = []
    board.add_trip_listener(lambda i, r: trips.append((i, r)))
    board.note_fault(2, "dispatch")
    assert not trips
    board.note_fault(2, "dispatch")
    assert trips == [(2, "dispatch")]
    assert board.quarantined_set() == frozenset({2})
    board.reset()
    assert board.quarantined_set() == frozenset()


def test_healthy_submesh_ladder():
    mesh = make_mesh(jax.devices())
    flat = list(mesh.devices.flat)
    assert healthy_submesh(mesh, ()) == flat
    # one dead chip: 7 survivors truncate to the pow2 ladder rung 4
    got = healthy_submesh(mesh, {0})
    assert len(got) == 4 and flat[0] not in got
    assert len(healthy_submesh(mesh, {0, 1, 2, 3, 4})) == 2
    assert len(healthy_submesh(mesh, set(range(7)))) == 1
    assert healthy_submesh(mesh, set(range(8))) is None


def test_drain_receivers_spread():
    scores = [0.1, 0.9, 0.3, 0.5]
    # round-robin over healthy slices, least-loaded first — never a
    # single-receiver dump, never an excluded slice
    got = drain_receivers(scores, exclude={1}, k=5)
    assert got == [0, 2, 3, 0, 2]
    assert drain_receivers(scores, exclude={0, 1, 2, 3}, k=2) == []


# ------------------------------------------- slice trip → drain → parity


def _placement_runner(**kw):
    kw.setdefault("slice_probe_cooldown_s", 0.05)
    return DeviceRunner(mesh=make_mesh(jax.devices()), chunk_rows=8 * 64,
                        placement=True, placement_rows=1 << 16, **kw)


def test_slice_trip_drains_anchors_healthy_slice_parity():
    """Persistent chip death on a placed slice: its anchors drain onto
    healthy slices and every answer — NULL-heavy and tombstoned feeds
    included — stays bit-identical to the host pipeline through the
    strike, drain, quarantine and re-admission phases."""
    runner = _placement_runner()
    placer = runner.placer
    table = _table()
    dag = _agg(table)
    snaps = [
        _snap(table, 2048, 300 + i,
              null_frac=0.15 if i % 3 == 0 else 0.0,
              tombstoned=(i % 3 == 1))
        for i in range(9)]
    hosts = [_rows(BatchExecutorsRunner(dag, s).handle_request())
             for s in snaps]
    for i, s in enumerate(snaps):
        assert _rows(runner.handle_request(dag, s)) == hosts[i]
    victim = next(i for i, sl in enumerate(placer.stats()["slices"])
                  if sl["placed_anchors"])
    failpoint.cfg("device::slice_dead", f"return({victim})")
    try:
        # strikes (host-served, still exact) → trip → drain → every
        # later answer comes from a HEALTHY slice's rebuilt feed
        for rounds in range(4):
            for i, s in enumerate(snaps):
                assert _rows(runner.handle_request(dag, s)) == \
                    hosts[i], (rounds, i)
        st = placer.stats()
        sl = st["slices"][victim]
        assert sl["quarantined"], st
        assert sl["placed_anchors"] == 0, \
            "anchors were not drained off the dead slice"
        assert sl["resident_lines"] == 0, \
            "the dead slice still holds feed lines"
        assert st["drained"] >= 1
        check_no_quarantined_dispatch(runner)
        # warm serving during quarantine is DEVICE serving: the drained
        # anchors' requests dispatch on their new slices
        tr, tok = tracker.install()
        try:
            for i, s in enumerate(snaps):
                assert _rows(runner.handle_request(dag, s)) == hosts[i]
        finally:
            tracker.uninstall(tok)
        assert "device_dispatch" in tr.time_detail()["phases_ms"]
    finally:
        _heal(runner)
    # re-admitted: the victim serves again
    st = runner.failure_domain_stats()["slices"][victim]
    assert st["state"] == "healthy" and st["readmits"] >= 1
    for i, s in enumerate(snaps):
        assert _rows(runner.handle_request(dag, s)) == hosts[i]


def test_quarantined_slice_refuses_dispatch():
    """A request that still reaches a quarantined slice runner is
    REFUSED at the dispatch gate (counted, host-degraded) — a kernel
    never launches on a condemned chip."""
    runner = _placement_runner()
    table = _table()
    dag = _agg(table)
    snap = _snap(table, 2048, 999)
    host = _rows(BatchExecutorsRunner(dag, snap).handle_request())
    assert _rows(runner.handle_request(dag, snap)) == host
    owner = runner.placer.owner(feed_anchor(snap))
    oidx = runner.placer.slices.index(owner)
    runner._board.trip(oidx, "test")
    try:
        # direct hit on the slice runner, bypassing the placer's
        # exclusion — the gate must refuse, not launch
        assert _rows(owner.handle_request(dag, snap)) == host
        st = runner._board.slice(oidx).stats()
        assert st["refusals"] >= 1
        assert st["launched_quarantined"] == 0
        check_no_quarantined_dispatch(runner)
    finally:
        _heal(runner)


# --------------------------------------------- elastic mesh degrade


def test_mesh_downsize_parity_and_readmission():
    """Whole-mesh sharded serving survives a chip death by REBUILDING
    at the largest healthy shape (4→2 here): zero wrong results
    through strike, downsize and restore, the mesh_rebuild phase is
    observable, and the full mesh returns after re-admission."""
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:4]),
                          chunk_rows=8 * 64,
                          slice_probe_cooldown_s=0.05)
    table = _table()
    dag = _agg(table)
    snap = _snap(table, 9000, 41, null_frac=0.05)
    host = _rows(BatchExecutorsRunner(dag, snap).handle_request())
    assert _rows(runner.handle_request(dag, snap)) == host
    failpoint.cfg("device::slice_dead", "return(1)")
    try:
        # 3 strikes (host rung, exact) ...
        for _ in range(3):
            assert _rows(runner.handle_request(dag, snap)) == host
        # ... then the degraded submesh serves, re-minting the sharded
        # feed from host truth onto the 2 survivors
        tr, tok = tracker.install()
        try:
            assert _rows(runner.handle_request(dag, snap)) == host
        finally:
            tracker.uninstall(tok)
        td = tr.time_detail()
        assert "mesh_rebuild" in td["phases_ms"], td["phases_ms"]
        assert "device_dispatch" in td["phases_ms"], \
            "degraded mesh must SERVE from devices, not host"
        fd = runner.failure_domain_stats()
        assert fd["degraded"] == {"dead_slices": [1],
                                  "healthy_devices": 2}, fd
        # warm degraded serving: no further rebuilds, still exact
        for _ in range(3):
            assert _rows(runner.handle_request(dag, snap)) == host
        check_no_quarantined_dispatch(runner)
    finally:
        _heal(runner)
    fd = runner.failure_domain_stats()
    assert "degraded" not in fd, fd
    assert fd["slices"][1]["state"] == "healthy"
    # full mesh re-mints and serves
    tr, tok = tracker.install()
    try:
        assert _rows(runner.handle_request(dag, snap)) == host
    finally:
        tracker.uninstall(tok)
    assert "device_dispatch" in tr.time_detail()["phases_ms"]


def test_mesh_rebuild_fault_falls_to_host_rung():
    """device::mesh_rebuild faults the degrade path itself: the ladder
    lands on its FINAL rung (host, exact answers, lock not wedged);
    lifting just the rebuild fault lets the downsize proceed."""
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:4]),
                          chunk_rows=8 * 64,
                          slice_probe_cooldown_s=0.05)
    table = _table()
    dag = _agg(table)
    snap = _snap(table, 6000, 43)
    host = _rows(BatchExecutorsRunner(dag, snap).handle_request())
    assert _rows(runner.handle_request(dag, snap)) == host
    failpoint.cfg("device::slice_dead", "return(0)")
    failpoint.cfg("device::mesh_rebuild", "return")
    try:
        for _ in range(6):
            assert _rows(runner.handle_request(dag, snap)) == host
        assert "degraded" not in runner.failure_domain_stats()
        assert runner._dispatch_mu.acquire(timeout=1), \
            "dispatch lock wedged by the faulted rebuild"
        runner._dispatch_mu.release()
        # the rebuild fault lifts; the chip is still dead → downsize
        failpoint.remove("device::mesh_rebuild")
        assert _rows(runner.handle_request(dag, snap)) == host
        assert runner.failure_domain_stats()["degraded"][
            "healthy_devices"] == 2
    finally:
        _heal(runner)


def test_scrub_quarantine_reaches_degraded_submesh():
    """A scrub divergence on a feed the DEGRADED submesh serves must
    drop the corrupt line THERE and host-serve its next request — the
    degrade branch routes around the parent's quarantine gate, so the
    verdict must land on the sub (review regression: corrupted bytes
    must never keep becoming answers while the mesh is degraded)."""
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:4]),
                          chunk_rows=8 * 64,
                          slice_probe_cooldown_s=0.05)
    table = _table()
    dag = _agg(table)
    snap = _snap(table, 6000, 91)
    host = _rows(BatchExecutorsRunner(dag, snap).handle_request())
    assert _rows(runner.handle_request(dag, snap)) == host
    failpoint.cfg("device::slice_dead", "return(3)")
    try:
        for _ in range(4):
            assert _rows(runner.handle_request(dag, snap)) == host
        sub = runner._degraded_sub()
        assert sub is not None
        anchor = feed_anchor(snap)
        assert sub._arena.resident_bytes() > 0
        # the scrubber's verdict, delivered to the TOP runner
        runner.quarantine(anchor, reason="scrub divergence")
        assert sub._arena.resident_bytes() == 0, \
            "corrupt feed left resident on the degraded submesh"
        # next request host-serves (quarantine consumed ON THE SUB)...
        tr, tok = tracker.install()
        try:
            assert _rows(runner.handle_request(dag, snap)) == host
        finally:
            tracker.uninstall(tok)
        td = tr.time_detail()
        assert td["labels"].get("device_feed") == "quarantined", \
            td["labels"]
        # ...and the one after rebuilds from host truth on the sub
        tr, tok = tracker.install()
        try:
            assert _rows(runner.handle_request(dag, snap)) == host
        finally:
            tracker.uninstall(tok)
        assert "device_dispatch" in tr.time_detail()["phases_ms"]
    finally:
        _heal(runner)


def test_batched_refusal_raises_batch_unavailable():
    """The quarantine refusal gate inside a GROUP dispatch raises
    _BatchUnavailable instead of computing a throwaway host answer for
    the leader (review regression: the coalescer's solo retries own
    the members; a synchronous host run here burns the group's
    deadline budget twice)."""
    from tikv_tpu.device.runner import _BatchUnavailable
    runner = _placement_runner()
    table = _table()
    snap = _snap(table, 4096, 93)
    d1, d2 = _sel(table, -10_000), _sel(table, 10_000)
    assert runner.batch_class(d1, snap) is not None   # place + warm
    owner = runner.placer.owner(feed_anchor(snap))
    oidx = runner.placer.slices.index(owner)
    runner._board.trip(oidx, "test")
    try:
        with pytest.raises(_BatchUnavailable):
            owner.handle_batched([(d1, snap), (d2, snap)])
    finally:
        _heal(runner)


def test_half_open_readmission_decays_score():
    """Probes fail while the chip stays dead (cooldown restarts each
    time); after heal ONE canary re-admits with a decayed score, so
    the placement penalty keeps the slice expensive until it earns
    traffic back."""
    runner = _placement_runner()
    table = _table()
    dag = _agg(table)
    snap = _snap(table, 2048, 77)
    runner.handle_request(dag, snap)
    oidx = runner.placer.slices.index(
        runner.placer.owner(feed_anchor(snap)))
    failpoint.cfg("device::slice_dead", f"return({oidx})")
    try:
        for _ in range(3):
            runner.handle_request(dag, snap)
        board = runner._board
        assert oidx in board.quarantined_set()
        time.sleep(0.06)
        runner.probe_quarantined()      # canary fails: fault persists
        st = board.slice(oidx).stats()
        assert st["probe_failures"] >= 1 and st["state"] == "quarantined"
    finally:
        failpoint.teardown()
    time.sleep(0.06)
    runner.probe_quarantined()
    st = runner._board.slice(oidx).stats()
    assert st["state"] == "healthy" and st["readmits"] == 1
    # decayed, not reset: one strike shy of the trip threshold
    assert st["score"] == pytest.approx(2.0)
    assert runner._board.penalty(oidx) > 0.5
    _heal(runner)


# --------------------------------------------------- in-flight rescue


def test_inflight_deferred_rescue_races_slice_death():
    """A DeferredResult whose slice dies between dispatch and fetch
    retries on a healthy slice: exact answer, rescue counted, the
    arena pin released exactly once, the dispatch lock free."""
    from tikv_tpu.utils.metrics import DEVICE_FAILOVER_COUNTER
    runner = _placement_runner()
    table = _table()
    dag = _agg(table)
    snap = _snap(table, 2048, 55, null_frac=0.1)
    host = _rows(BatchExecutorsRunner(dag, snap).handle_request())
    assert _rows(runner.handle_request(dag, snap)) == host   # warm
    owner = runner.placer.owner(feed_anchor(snap))
    oidx = runner.placer.slices.index(owner)
    before = DEVICE_FAILOVER_COUNTER.labels("rescue").value
    d = runner.handle_request(dag, snap, deferred=True)
    from tikv_tpu.device.runner import DeferredResult
    assert isinstance(d, DeferredResult)
    failpoint.cfg("device::slice_dead", f"return({oidx})")
    try:
        assert _rows(d.result()) == host
        assert DEVICE_FAILOVER_COUNTER.labels("rescue").value > before
        # exactly-once unpin: nothing stays pinned anywhere
        st = runner.hbm_stats()
        assert st["pinned_lines"] == 0, st
        assert owner._dispatch_mu.acquire(timeout=1), \
            "dead slice's dispatch lock wedged"
        owner._dispatch_mu.release()
        # memoized: a second result() call returns the same rescue
        assert _rows(d.result()) == host
    finally:
        _heal(runner)


def test_inflight_group_rescue_races_slice_death():
    """A coalesced stacked group whose slice dies between dispatch and
    fetch rescues PER MEMBER on a healthy slice — both members exact,
    neither failed for the shared fault, the group pin released
    exactly once."""
    from tikv_tpu.utils.metrics import DEVICE_FAILOVER_COUNTER
    runner = _placement_runner()
    table = _table()
    snap = _snap(table, 4096, 66)
    d1, d2 = _sel(table, -20_000), _sel(table, 20_000)
    hosts = [_rows(BatchExecutorsRunner(d, snap).handle_request())
             for d in (d1, d2)]
    # both members must share a stacked batch class on the SAME slice
    k1 = runner.batch_class(d1, snap)
    k2 = runner.batch_class(d2, snap)
    assert k1 is not None and k1[0] == "slice" and k1 == k2, (k1, k2)
    owner = runner.placer.owner(feed_anchor(snap))
    oidx = runner.placer.slices.index(owner)
    group = runner.handle_batched([(d1, snap), (d2, snap)])
    before = DEVICE_FAILOVER_COUNTER.labels("rescue").value
    failpoint.cfg("device::slice_dead", f"return({oidx})")
    try:
        assert _rows(group.member_result(0)) == hosts[0]
        assert _rows(group.member_result(1)) == hosts[1]
        assert DEVICE_FAILOVER_COUNTER.labels("rescue").value >= \
            before + 2, "each member rescues individually"
        assert runner.hbm_stats()["pinned_lines"] == 0, \
            "the group's shared pin leaked (or double-released)"
    finally:
        _heal(runner)


# ------------------------------------------------------ chaos schedules


_CHIP_KINDS = ("slice_dead", "chip_flap", "device_degrade")


def _chaos_round(runner, nem, schedule, snaps, hosts, dag,
                 queries_per_step=2):
    for fault in schedule:
        nem.apply(fault)
        for _ in range(queries_per_step):
            for i, s in enumerate(snaps):
                got = _rows(runner.handle_request(dag, s))
                assert got == hosts[i], \
                    f"WRONG RESULT under {fault.kind} for snap {i}"
        check_no_quarantined_dispatch(runner)
        nem.heal()
        for i, s in enumerate(snaps):
            assert _rows(runner.handle_request(dag, s)) == hosts[i]


def test_flapping_chip_chaos_fast():
    """Tier-1 twin of the chip-death chaos schedule: 3 seeded steps of
    persistent death / flapping chip / degrade faults against a
    placement mesh — zero wrong results, no dispatch ever launched on
    a quarantined slice, every slice re-admitted by the end."""
    runner = _placement_runner()
    table = _table()
    dag = _agg(table)
    snaps = [_snap(table, 1536, 700 + i,
                   null_frac=0.1 if i % 2 else 0.0) for i in range(4)]
    hosts = [_rows(BatchExecutorsRunner(dag, s).handle_request())
             for s in snaps]
    for i, s in enumerate(snaps):
        assert _rows(runner.handle_request(dag, s)) == hosts[i]
    nem = Nemesis(None, seed=1010)
    schedule = generate_schedule(1010, 3, kinds=_CHIP_KINDS)
    assert {f.kind for f in schedule} <= set(_CHIP_KINDS)
    try:
        _chaos_round(runner, nem, schedule, snaps, hosts, dag)
    finally:
        nem.heal()
        _heal(runner)
    st = runner.failure_domain_stats()
    assert all(s["state"] == "healthy" for s in st["slices"]), st


@pytest.mark.slow
def test_flapping_chip_chaos_full():
    """The full schedule: 8 steps, more regions, deeper churn — the
    same invariants at scale, plus drains/rescues actually observed."""
    runner = _placement_runner()
    table = _table()
    dag = _agg(table)
    snaps = [_snap(table, 2560, 800 + i,
                   null_frac=0.12 if i % 3 == 0 else 0.0,
                   tombstoned=(i % 3 == 1)) for i in range(8)]
    hosts = [_rows(BatchExecutorsRunner(dag, s).handle_request())
             for s in snaps]
    for i, s in enumerate(snaps):
        assert _rows(runner.handle_request(dag, s)) == hosts[i]
    nem = Nemesis(None, seed=2020)
    schedule = generate_schedule(2020, 8, kinds=_CHIP_KINDS)
    try:
        _chaos_round(runner, nem, schedule, snaps, hosts, dag,
                     queries_per_step=3)
    finally:
        nem.heal()
        _heal(runner)
    st = runner.failure_domain_stats()
    assert all(s["state"] == "healthy" for s in st["slices"]), st
    trips = sum(s["trips"] for s in st["slices"])
    assert trips >= 1, "the schedule never tripped a slice — it " \
        "proved nothing"


# ------------------------------------------- end-to-end (live server)


def _make_failover_rig(threshold=64):
    import grpc       # noqa: F401 — importorskip at the call sites
    from tikv_tpu.raftstore.metapb import Store
    from tikv_tpu.server import (
        Node, PdServer, RemotePdClient, TikvServer, TxnClient,
    )
    device = DeviceRunner(chunk_rows=1 << 12, placement=True,
                          placement_rows=1 << 20,
                          slice_probe_cooldown_s=0.05)
    pd_server = PdServer("127.0.0.1:0")
    pd_server.start()
    pd_addr = f"127.0.0.1:{pd_server.port}"
    node = Node("127.0.0.1:0", RemotePdClient(pd_addr),
                device_runner=device, device_row_threshold=threshold)
    srv = TikvServer(node)
    node.addr = f"127.0.0.1:{srv.port}"
    node.pd.put_store(Store(node.store_id, node.addr))
    srv.start()
    client = TxnClient(pd_addr)

    def close():
        srv.stop()
        pd_server.stop()

    return {"srv": srv, "node": node, "client": client,
            "device": device, "close": close}


def _split_at(node, tid, handle, timeout_s=5.0):
    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.raftstore.metapb import NotLeaderError
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            return node.split_region(0, table_record_key(tid, handle))
        except NotLeaderError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.02)


def _region_dag(table, c, lo, hi):
    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.executors.ranges import KeyRange

    def build():
        sel = DagSelect.from_table(table, ["id", "c0", "c1"])
        sel._ranges = [KeyRange(
            table_record_key(table.table_id, lo),
            table_record_key(table.table_id, hi))]
        return sel.aggregate(
            [sel.col("c0")],
            [("count_star", None), ("sum", sel.col("c1"))],
        ).build(start_ts=c.tso())

    return build


def _expect(model, lo, hi):
    out = {}
    for h, (c0, c1) in model.items():
        if lo <= h < hi:
            cnt, sm = out.get(c0, (0, 0))
            out[c0] = (cnt + 1, sm + c1)
    return sorted([cnt, sm, g] for g, (cnt, sm) in out.items())


def test_chip_death_end_to_end_acceptance():
    """The acceptance criterion end to end, tier-1: a live gRPC node
    with placement takes a PERSISTENT mid-churn chip death — zero
    wrong results, zero late acks, warm queries keep serving from
    surviving slices (copr backend=device, not host) while the dead
    slice is quarantined, /health + /metrics show the failure domain,
    and the slice re-admits after the fault lifts."""
    pytest.importorskip("grpc")
    import json
    import random
    import urllib.request

    from tikv_tpu.server.status_server import StatusServer
    from tikv_tpu.testing.fixture import encode_table_row, int_table
    rig = _make_failover_rig(threshold=64)
    try:
        c, node, device = rig["client"], rig["node"], rig["device"]
        table = int_table(2, table_id=9800)
        tid = table.table_id
        rows_per, n_regions = 96, 6
        total = rows_per * n_regions
        model = {}
        muts = []
        for h in range(total):
            model[h] = (h % 5, h)
            muts.append(("put",) + encode_table_row(
                table, h, {"c0": h % 5, "c1": h}))
        c.txn_write(muts)
        bounds = [0]
        for i in range(1, n_regions):
            _split_at(node, tid, i * rows_per)
            bounds.append(i * rows_per)
        bounds.append(total)
        rng = random.Random(31337)

        def query(i, deadline_ms=5000):
            lo, hi = bounds[i], bounds[i + 1]
            t0 = time.monotonic()
            r = c.coprocessor(_region_dag(table, c, lo, hi)(),
                              deadline_ms=deadline_ms)
            elapsed = time.monotonic() - t0
            wrong = sorted(r["rows"]) != _expect(model, lo, hi)
            late = elapsed > deadline_ms / 1000.0
            return {"backend": r["backend"], "wrong": wrong,
                    "late": late}

        # warm every region onto its placed slice
        for i in range(n_regions):
            r = query(i)
            assert not r["wrong"]
        placer = device.placer
        victim = next(i for i, sl in
                      enumerate(placer.stats()["slices"])
                      if sl["placed_anchors"])

        # ---- the chip dies, PERSISTENTLY, mid-churn ----
        failpoint.cfg("device::slice_dead", f"return({victim})")
        board = device._board
        # strike phase: churn + queries across EVERY region until the
        # slice trips (each touch of the dead slice strikes once;
        # answers stay exact throughout)
        for step in range(6):
            if victim in board.quarantined_set():
                break
            h = rng.randrange(total)
            model[h] = (h % 5, rng.randrange(1 << 16))
            c.txn_write([("put",) + encode_table_row(
                table, h, {"c0": model[h][0], "c1": model[h][1]})])
            for i in range(n_regions):
                assert not query(i)["wrong"]
        assert victim in board.quarantined_set(), board.stats()

        # ---- quarantined: warm churn keeps serving FROM DEVICES ----
        records = []
        for _ in range(3):
            h = rng.randrange(total)
            model[h] = (h % 5, rng.randrange(1 << 16))
            c.txn_write([("put",) + encode_table_row(
                table, h, {"c0": model[h][0], "c1": model[h][1]})])
            for i in range(n_regions):
                records.append(query(i))
        check_mesh_serves_degraded(records, device_floor=0.9)
        check_no_quarantined_dispatch(device)
        st = placer.stats()
        assert st["slices"][victim]["placed_anchors"] == 0
        assert st["slices"][victim]["resident_lines"] == 0

        # ---- observability while degraded ----
        ss = StatusServer("127.0.0.1:0", node=node,
                          config_controller=node.config_controller)
        ss.start()
        try:
            base = f"http://127.0.0.1:{ss.port}"
            body = json.load(urllib.request.urlopen(f"{base}/health"))
            dh = body["device_health"]
            assert dh["slices"][victim]["state"] == "quarantined", dh
            assert dh["slices"][victim]["trips"] >= 1
            metrics = urllib.request.urlopen(
                f"{base}/metrics").read().decode()
            assert "tikv_device_slice_health_penalty" in metrics
            assert "tikv_device_failure_domain_total" in metrics
            assert 'event="quarantine"' in metrics
        finally:
            ss.stop()

        # ---- the fault lifts: half-open canary re-admits ----
        failpoint.remove("device::slice_dead")
        deadline = time.monotonic() + 3.0
        while victim in board.quarantined_set() and \
                time.monotonic() < deadline:
            device.probe_quarantined()
            time.sleep(0.02)
        st = device.failure_domain_stats()["slices"][victim]
        assert st["state"] == "healthy" and st["readmits"] >= 1, st
        for i in range(n_regions):
            r = query(i)
            assert not r["wrong"] and r["backend"] == "device", r
    finally:
        rig["close"]()


def test_stop_under_load_clean_shutdown():
    """node.stop() while requests are in flight: the coalescer window
    flushes (parked members resolve, never abandon), the completion
    pool drains, and teardown leaves no pinned arena lines and no
    resident device state — the conftest leak guard additionally
    asserts no non-daemon worker thread survives."""
    pytest.importorskip("grpc")
    from tikv_tpu.testing.fixture import encode_table_row, int_table
    rig = _make_failover_rig(threshold=64)
    stopped = threading.Event()
    errors: list = []
    try:
        c, node, device = rig["client"], rig["node"], rig["device"]
        table = int_table(2, table_id=9801)
        muts = [("put",) + encode_table_row(
            table, h, {"c0": h % 5, "c1": h}) for h in range(256)]
        c.txn_write(muts)
        dag = _region_dag(table, c, 0, 256)
        # warm so the in-flight load exercises the device path
        assert c.coprocessor(dag())["backend"] == "device"

        def pound():
            while not stopped.is_set():
                try:
                    c.coprocessor(dag(), timeout=2)
                except Exception:   # noqa: BLE001 — a stopping server
                    return          # refusing requests is the point

        threads = [threading.Thread(target=pound, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.4)             # requests genuinely in flight
    except BaseException:
        stopped.set()
        rig["close"]()
        raise
    rig["close"]()                  # stop UNDER load
    stopped.set()
    for t in threads:
        t.join(timeout=5)
        assert not t.is_alive(), "client thread wedged by shutdown"
    assert not errors
    st = rig["device"].hbm_stats()
    assert st["pinned_lines"] == 0, st
    assert st["resident_lines"] == 0, \
        "runner.close() left resident device state behind"
    coal = rig["node"].endpoint.coalescer
    if coal is not None:
        assert not coal._open, "parked members abandoned at stop"
