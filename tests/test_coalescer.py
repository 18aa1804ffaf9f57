"""Cross-request device batching (server/coalescer.py + the runner's
stacked dispatch path).

Covers the coalescing dispatcher end to end on the CPU mesh (tier-1
safe — the stacked kernels are plain jit/vmap, platform-independent):

- randomized batched-vs-solo parity: mixed predicate constants within
  one compile class, NULL-heavy and tombstoned feeds, selections AND
  aggregations — every member's answer is bit-identical to the host
  pipeline's;
- group-member fault isolation: a ``device::*`` failpoint inside the
  SHARED fetch degrades every member to the host pipeline
  individually (correct answers, never a group-wide failure), and
  ``copr::coalesce_dispatch`` (batched launch failure) retries every
  member as a solo dispatch;
- router decision coverage: all four outcomes (device_batched /
  device_solo / host / shed) reachable, shed carries retry_after_ms;
- deadline-pressure group close: a member with a tight budget closes
  its group before the window, and no response is served after its
  deadline because it waited in a coalesce window;
- the fast gRPC smoke twin of bench 6b: concurrent warm clients over
  rotating constants, ≥2 requests share one dispatch, zero
  deadline_exceeded, /health + /metrics observability.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from tikv_tpu.copr.endpoint import CopRequest, Endpoint, REQ_TYPE_DAG
from tikv_tpu.datatype import Column, EvalType, FieldType
from tikv_tpu.device import DeviceRunner
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.server.coalescer import (
    DEVICE_BATCHED,
    DEVICE_SOLO,
    HOST,
    SHED,
    RequestCoalescer,
)
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn
from tikv_tpu.utils import deadline as dl_mod
from tikv_tpu.utils import failpoint


@pytest.fixture(scope="module")
def runner():
    import jax

    from tikv_tpu.parallel import make_mesh
    return DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                        chunk_rows=1 << 12)


@pytest.fixture(autouse=True)
def _teardown_failpoints():
    yield
    failpoint.teardown()


def make_snapshot(n=16_000, seed=0, tombstoned=False, null_heavy=False):
    rng = np.random.default_rng(seed)
    table = Table(8600 + seed, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("k", 2, FieldType.long()),
        TableColumn("v", 3, FieldType.long())))
    named = {
        "k": Column(EvalType.INT,
                    rng.integers(0, 40, n).astype(np.int64),
                    np.ones(n, np.bool_)),
        "v": Column(EvalType.INT,
                    rng.integers(-1000, 1000, n).astype(np.int64),
                    rng.random(n) > (0.5 if null_heavy else 0.1)),
    }
    snap = ColumnarTable.from_arrays(table, np.arange(n, dtype=np.int64),
                                     named)
    if tombstoned:
        alive = rng.random(n) > 0.3
        snap = ColumnarTable(table, snap.handles, snap.columns,
                             alive=alive)
    return table, snap


def sel_dag(table, thr, extra=None):
    s = DagSelect.from_table(table, ["id", "k", "v"])
    conds = [s.col("v") > int(thr)]
    if extra is not None:
        conds.append(s.col("k") < int(extra))
    return s.where(*conds).build()


def agg_dag(table, bias=0):
    s = DagSelect.from_table(table, ["id", "k", "v"])
    aggs = [("count_star", None), ("sum", s.col("v"))]
    if bias:
        # a differing agg-side constant: its own exact plan (share
        # groups key on the exact plan) but the same read-pool class
        return s.where(s.col("v") > bias).aggregate(
            [s.col("k")], aggs).build()
    return s.aggregate([s.col("k")], aggs).build()


def make_endpoint(runner, snap, window_ms=200.0, max_group=8,
                  idle_bypass=False, threshold=1):
    coal = RequestCoalescer(runner, window_ms=window_ms,
                            max_group=max_group)
    coal.idle_bypass = idle_bypass
    ep = Endpoint(lambda req: snap, device_runner=runner,
                  device_row_threshold=threshold, coalescer=coal)
    return ep, coal


def run_concurrent(ep, dags):
    """Submit every dag on its own thread; → CopResponse list."""
    out = [None] * len(dags)
    errs = []

    def one(i):
        try:
            out[i] = ep.handle(CopRequest(REQ_TYPE_DAG, dags[i]))
        except Exception as e:      # noqa: BLE001 — surfaced below
            errs.append((i, e))

    ts = [threading.Thread(target=one, args=(i,))
          for i in range(len(dags))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
    return out


# ----------------------------------------------------- randomized parity


def test_randomized_batched_vs_solo_parity(runner):
    """Mixed constants within one compile class over plain, NULL-heavy
    and tombstoned feeds — every coalesced member bit-matches the host
    pipeline (and the solo device path, transitively via PR 5's parity
    suite)."""
    shapes = [make_snapshot(seed=1), make_snapshot(seed=2, null_heavy=True),
              make_snapshot(seed=3, tombstoned=True)]
    rng = np.random.default_rng(77)
    rounds = 0
    for cycle in range(4):
        for table, snap in shapes:
            ep, coal = make_endpoint(runner, snap, max_group=8)
            try:
                thrs = rng.integers(-1100, 1100, 4).tolist()
                if cycle % 2:       # conjunction shape: its own class
                    dags = [sel_dag(table, t, extra=rng.integers(0, 40))
                            for t in thrs]
                else:
                    dags = [sel_dag(table, t) for t in thrs]
                results = run_concurrent(ep, dags)
                for dag, got in zip(dags, results):
                    want = BatchExecutorsRunner(dag, snap).handle_request()
                    assert got.rows() == want.rows()
                    rounds += 1
                st = coal.stats()
                assert st["requests_coalesced"] == len(dags), st
            finally:
                ep.close()
    assert rounds >= 48, rounds


def test_aggregation_share_mode_parity(runner):
    """Identical aggregation plans coalesce in share mode: one
    dispatch + one fetch serves every member, results exact."""
    table, snap = make_snapshot(seed=5)
    ep, coal = make_endpoint(runner, snap, max_group=4)
    try:
        dags = [agg_dag(table)] * 4
        results = run_concurrent(ep, dags)
        want = BatchExecutorsRunner(dags[0], snap).handle_request()
        for got in results:
            assert sorted(got.rows()) == sorted(want.rows())
            assert got.backend == "device"
        st = coal.stats()
        assert st["groups_dispatched"] == 1, st
        assert st["mean_occupancy"] == 4.0, st
        # differing agg-side constants: distinct share groups, still
        # exact per member
        dags2 = [agg_dag(table, bias=b) for b in (10, 500, 10)]
        for got, dag in zip(run_concurrent(ep, dags2), dags2):
            want = BatchExecutorsRunner(dag, snap).handle_request()
            assert sorted(got.rows()) == sorted(want.rows())
    finally:
        ep.close()


def test_stacked_group_occupancy_and_route_label(runner):
    """A full group runs as ONE stacked dispatch: occupancy equals the
    member count and the selection route counter records 'batched'."""
    table, snap = make_snapshot(seed=6)
    ep, coal = make_endpoint(runner, snap, max_group=4)
    try:
        before = dict(runner._sel_route_counts)
        dags = [sel_dag(table, t) for t in (-2000, 0, 250, 2000)]
        run_concurrent(ep, dags)
        st = coal.stats()
        assert st["groups_dispatched"] == 1 and \
            st["max_occupancy"] == 4, st
        got = runner._sel_route_counts.get("batched", 0) - \
            before.get("batched", 0)
        assert got == 1, runner._sel_route_counts
    finally:
        ep.close()


# ------------------------------------------------------- fault isolation


def test_group_fetch_fault_degrades_members_to_host(runner):
    """A device fault inside the group's SHARED fetch
    (device::before_fetch) must degrade every member to the host
    pipeline individually — exact answers, no group-wide failure."""
    table, snap = make_snapshot(seed=7)
    ep, coal = make_endpoint(runner, snap, max_group=3)
    try:
        failpoint.cfg("device::before_fetch", "1*return")
        dags = [sel_dag(table, t) for t in (-500, 0, 500)]
        results = run_concurrent(ep, dags)
        for dag, got in zip(dags, results):
            want = BatchExecutorsRunner(dag, snap).handle_request()
            assert got.rows() == want.rows()
            assert got.backend == "host", got.backend
        st = coal.stats()
        assert st["groups_dispatched"] == 1, st
    finally:
        ep.close()


def test_coalesce_dispatch_failpoint_retries_members_solo(runner):
    """copr::coalesce_dispatch: the batched LAUNCH fails — members
    must retry as solo device dispatches (not fail, not silently share
    a wrong answer)."""
    table, snap = make_snapshot(seed=8)
    ep, coal = make_endpoint(runner, snap, max_group=3)
    try:
        # warm the solo path once so the retry dispatches cleanly
        ep.handle(CopRequest(REQ_TYPE_DAG, sel_dag(table, 123)))
        failpoint.cfg("copr::coalesce_dispatch", "1*return")
        dags = [sel_dag(table, t) for t in (-400, 100, 900)]
        results = run_concurrent(ep, dags)
        for dag, got in zip(dags, results):
            want = BatchExecutorsRunner(dag, snap).handle_request()
            assert got.rows() == want.rows()
            assert got.backend == "device", got.backend
        st = coal.stats()
        assert st["solo_degrade"] == 3, st
    finally:
        ep.close()


def test_forced_immediate_close_failpoint(runner):
    """copr::coalesce_window forces groups closed at submit — every
    member dispatches alone (occupancy 1) but still correctly."""
    table, snap = make_snapshot(seed=9)
    ep, coal = make_endpoint(runner, snap, max_group=8)
    try:
        failpoint.cfg("copr::coalesce_window", "return")
        dags = [sel_dag(table, t) for t in (-100, 400)]
        results = run_concurrent(ep, dags)
        for dag, got in zip(dags, results):
            want = BatchExecutorsRunner(dag, snap).handle_request()
            assert got.rows() == want.rows()
        st = coal.stats()
        assert st["closes"].get("failpoint", 0) >= 2, st
        assert st["max_occupancy"] == 1, st
    finally:
        ep.close()


# -------------------------------------------------------------- routing


def test_router_all_four_outcomes(runner):
    table, snap = make_snapshot(seed=10)
    ep, coal = make_endpoint(runner, snap)
    try:
        # device_batched: batchable, no deadline
        d, key, _ = coal.route(sel_dag(table, 5), snap)
        assert d == DEVICE_BATCHED and key is not None

        # device_solo: batching disabled in place
        coal.set_enabled(False)
        d, key, _ = coal.route(sel_dag(table, 5), snap)
        assert d == DEVICE_SOLO and key is None
        coal.set_enabled(True)

        # host: the threshold (the calibrated break-even) says this
        # row count is far below the device crossover
        ep._device_row_threshold = 1 << 22
        d, _k, _ = coal.route(sel_dag(table, 5), snap)
        assert d == HOST
        ep._device_row_threshold = 1

        # shed: remaining budget below the modeled cost of EVERY
        # option — rejected with a retry hint
        coal.router.launch_ewma = 0.5       # a 500ms modeled launch
        dl = dl_mod.Deadline.after_ms(20)
        tok = dl_mod.install(dl)
        try:
            d, _k, hint = coal.route(sel_dag(table, 5), snap)
        finally:
            dl_mod.uninstall(tok)
        assert d == SHED and hint >= 1, (d, hint)
        st = coal.stats()["router"]["decisions"]
        for want in (DEVICE_BATCHED, DEVICE_SOLO, HOST, SHED):
            assert st.get(want, 0) >= 1, st
    finally:
        ep.close()


def test_shed_rides_the_wire_as_server_is_busy(runner):
    """An endpoint-level shed surfaces as ServerIsBusy with a
    retry_after_ms hint (the same contract read-pool shedding uses)."""
    from tikv_tpu.server.read_pool import ServerIsBusy
    table, snap = make_snapshot(seed=11)
    ep, coal = make_endpoint(runner, snap)
    try:
        coal.router.launch_ewma = 0.5
        dl = dl_mod.Deadline.after_ms(20)
        tok = dl_mod.install(dl)
        try:
            with pytest.raises(ServerIsBusy) as ei:
                ep.handle(CopRequest(REQ_TYPE_DAG, sel_dag(table, 5)))
        finally:
            dl_mod.uninstall(tok)
        assert ei.value.retry_after_ms >= 1
    finally:
        ep.close()


def test_router_respects_forced_backend(runner):
    """force_backend='device' bypasses the router: parity suites
    contract for a raw solo dispatch even under a coalescer."""
    table, snap = make_snapshot(seed=12)
    ep, coal = make_endpoint(runner, snap)
    try:
        before = coal.stats()["router"]["decisions"]
        r = ep.handle(CopRequest(REQ_TYPE_DAG, sel_dag(table, 5),
                                 force_backend="device"))
        want = BatchExecutorsRunner(sel_dag(table, 5),
                                    snap).handle_request()
        assert r.rows() == want.rows()
        assert coal.stats()["router"]["decisions"] == before
    finally:
        ep.close()


# ----------------------------------------------------- deadline pressure


def test_deadline_pressure_closes_group_early(runner):
    """A member whose budget cannot survive the window forces the
    group closed early — the response lands BEFORE its deadline even
    though the configured window is far longer."""
    table, snap = make_snapshot(seed=13)
    # a 10-second window: only deadline pressure can close the group
    ep, coal = make_endpoint(runner, snap, window_ms=10_000.0,
                             max_group=8)
    try:
        # warm the feed + kernels OUTSIDE the coalescer so the group's
        # post-close latency is the true warm cost
        runner.handle_request(sel_dag(table, 77), snap)
        expired = []
        out = []

        def one(thr, budget_ms):
            dl = dl_mod.Deadline.after_ms(budget_ms) \
                if budget_ms else None
            tok = dl_mod.install(dl) if dl is not None else None
            try:
                r = ep.handle(CopRequest(REQ_TYPE_DAG,
                                         sel_dag(table, thr)))
                out.append((thr, r))
                if dl is not None:
                    expired.append(dl.expired())
            finally:
                if tok is not None:
                    dl_mod.uninstall(tok)

        # one patient member + one with a 2s budget: the group must
        # close on the TIGHT member's pressure, not the 10s window
        ts = [threading.Thread(target=one, args=(321, None)),
              threading.Thread(target=one, args=(654, 2_000))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=8.0)
        assert not any(t.is_alive() for t in ts), \
            "group never closed under deadline pressure"
        assert len(out) == 2
        for thr, got in out:
            want = BatchExecutorsRunner(sel_dag(table, thr),
                                        snap).handle_request()
            assert got.rows() == want.rows()
        assert expired == [False], "served past its deadline"
        st = coal.stats()
        assert st["closes"].get("deadline", 0) >= 1, st
    finally:
        ep.close()


def test_idle_bypass_skips_the_window(runner):
    """A lone request on an idle coalescer dispatches immediately —
    serial workloads never pay the collection window."""
    import time
    table, snap = make_snapshot(seed=14)
    ep, coal = make_endpoint(runner, snap, window_ms=5_000.0,
                             idle_bypass=True)
    try:
        ep.handle(CopRequest(REQ_TYPE_DAG, sel_dag(table, 5)))  # warm
        t0 = time.perf_counter()
        ep.handle(CopRequest(REQ_TYPE_DAG, sel_dag(table, 6)))
        assert time.perf_counter() - t0 < 2.0
        assert coal.stats()["closes"].get("idle", 0) >= 1
    finally:
        ep.close()


# ------------------------------------------------- gRPC smoke (6b twin)


@pytest.fixture(scope="module")
def rig():
    import jax

    from tikv_tpu.parallel import make_mesh
    from tikv_tpu.raftstore.metapb import Store
    from tikv_tpu.server import (
        Node, PdServer, RemotePdClient, TikvServer, TxnClient,
    )
    # single-device mesh: cross-request batching is single-device by
    # design (batch_class), and the real bench chip is one device
    device = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    pd_server = PdServer("127.0.0.1:0")
    pd_server.start()
    pd_addr = f"127.0.0.1:{pd_server.port}"
    node = Node("127.0.0.1:0", RemotePdClient(pd_addr),
                device_runner=device, device_row_threshold=128)
    srv = TikvServer(node)
    node.addr = f"127.0.0.1:{srv.port}"
    node.pd.put_store(Store(node.store_id, node.addr))
    srv.start()
    client = TxnClient(pd_addr)
    yield {"srv": srv, "node": node, "client": client, "device": device}
    srv.stop()
    pd_server.stop()


def test_smoke_concurrent_serving_coalesces(rig):
    """Fast tier-1 twin of bench 6b: concurrent warm gRPC clients over
    rotating predicate constants — ≥2 requests actually share one
    dispatch, zero deadline_exceeded from coalesce wait, and the
    observability surfaces report the subsystem."""
    import json
    import urllib.request

    from tikv_tpu.server.status_server import StatusServer
    from tikv_tpu.testing.fixture import encode_table_row, int_table
    c, node = rig["client"], rig["node"]
    coal = node.endpoint.coalescer
    assert coal is not None, "node wired without a coalescer"
    table = int_table(2, table_id=9450)
    muts = []
    for h in range(3000):
        key, value = encode_table_row(
            table, h, {"c0": h % 11, "c1": (h * 37) % 2000 - 1000})
        muts.append(("put", key, value))
    c.txn_write(muts)

    def make_sel(ts, thr):
        s = DagSelect.from_table(table, ["id", "c0", "c1"])
        return s.where(s.col("c1") > thr).build(start_ts=ts)

    # warm: feed + solo kernel + columnar cache
    warm = c.coprocessor(make_sel(c.tso(), 0))
    assert warm["backend"] == "device", warm.get("backend")

    # collect deterministically for the burst (the idle bypass would
    # let the very first arrival skip the window)
    coal.configure(window_ms=150.0)
    coal.idle_bypass = False
    base = coal.stats()
    thrs = [-500, 0, 500]
    errors = []
    lat_ok = []

    def one(i):
        try:
            r = c.coprocessor(make_sel(c.tso(), thrs[i % 3]),
                              deadline_ms=30_000, timeout=60)
            lat_ok.append(r["backend"])
        except Exception as e:      # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    coal.idle_bypass = True
    assert not errors, errors      # zero deadline_exceeded / sheds
    assert len(lat_ok) == 8
    st = coal.stats()
    assert st["max_occupancy"] >= 2, st     # ≥2 shared one dispatch
    assert st["requests_coalesced"] - base["requests_coalesced"] >= 8

    status = StatusServer("127.0.0.1:0", node=node,
                          config_controller=node.config_controller)
    status.start()
    try:
        base_url = f"http://127.0.0.1:{status.port}"
        body = json.load(urllib.request.urlopen(f"{base_url}/health"))
        assert "coalescer" in body, sorted(body)
        roll = body["coalescer"]
        assert roll["groups_dispatched"] >= 1
        assert "router" in roll and "decisions" in roll["router"]
        metrics = urllib.request.urlopen(
            f"{base_url}/metrics").read().decode()
        assert "tikv_coprocessor_batch_occupancy" in metrics
        assert "tikv_coprocessor_router_total" in metrics
    finally:
        status.stop()


def test_coalesce_wait_phase_attributed(rig):
    """The window time a member spent parked is split out as the
    coalesce_wait tracker phase on its OWN TimeDetail."""
    c, node = rig["client"], rig["node"]
    from tikv_tpu.testing.dag import DagSelect as DS
    from tikv_tpu.testing.fixture import int_table
    coal = node.endpoint.coalescer
    coal.configure(window_ms=120.0)
    coal.idle_bypass = False
    try:
        table = int_table(2, table_id=9450)

        def make_sel(ts, thr):
            s = DS.from_table(table, ["id", "c0", "c1"])
            return s.where(s.col("c1") > thr).build(start_ts=ts)

        out = []

        def one(thr):
            out.append(c.coprocessor(make_sel(c.tso(), thr),
                                     timeout=60))

        ts = [threading.Thread(target=one, args=(t,))
              for t in (-123, 456)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        phases = [r.get("time_detail", {}).get("phases_ms", {})
                  for r in out]
        assert any("coalesce_wait" in p for p in phases), phases
    finally:
        coal.idle_bypass = True
        coal.configure(window_ms=2.0)


def test_online_enable_from_disabled(rig):
    """A node started with coalesce_window_ms=0 has no coalescer; an
    online 0→N config change must construct and wire one (the field is
    advertised as online-tunable — silently accepting the change while
    batching stays off is the bug)."""
    node = rig["node"]
    orig = node.endpoint.coalescer
    node.endpoint.coalescer = None
    try:
        node._copr_cfg({"coalesce_window_ms": 3.0,
                        "coalesce_max_group": 5})
        coal = node.endpoint.coalescer
        assert coal is not None and coal is not orig
        st = coal.stats()
        assert st["window_ms"] == 3.0 and st["max_group"] == 5, st
        assert coal._endpoint is node.endpoint     # bound
        # N→0 disables in place
        node._copr_cfg({"coalesce_window_ms": 0.0})
        assert not coal.enabled
        coal.close()
    finally:
        node.endpoint.coalescer = orig


def test_readpool_class_keyed_ewma(rig):
    """The read pool keys its service-time EWMA by compile class:
    distinct plan shapes get distinct figures, rotating constants
    share one."""
    c, node = rig["client"], rig["node"]
    from tikv_tpu.testing.dag import DagSelect as DS
    from tikv_tpu.testing.fixture import int_table
    table = int_table(2, table_id=9450)

    def make_sel(ts, thr):
        s = DS.from_table(table, ["id", "c0", "c1"])
        return s.where(s.col("c1") > thr).build(start_ts=ts)

    def make_agg(ts):
        s = DS.from_table(table, ["id", "c0", "c1"])
        return s.aggregate([s.col("c0")],
                           [("count_star", None)]).build(start_ts=ts)

    for thr in (1, 2, 3):
        c.coprocessor(make_sel(c.tso(), thr))
    c.coprocessor(make_agg(c.tso()))
    c.get(b"nonexistent-key-xyz", c.tso())
    rp = node.read_pool
    sel_key = ("copr", make_sel(0, 99).class_key())
    agg_key = ("copr", make_agg(0).class_key())
    assert rp.class_ema(sel_key) > 0.0      # rotating consts: one class
    assert rp.class_ema(agg_key) > 0.0
    with rp._mu:
        assert rp._class_ema[sel_key][1] >= 3, \
            dict(rp._class_ema)[sel_key]
    assert rp.class_ema("KvGet") > 0.0
    assert rp.stats()["ema_classes"] >= 3


# ---------------------------------------------------- multi-lane launches
#
# Closed share groups of one launch class leave as the LANES of one
# launch (module doc of server/coalescer.py).  The Pallas body runs here
# in interpret mode, as in tests/test_pallas_hash_interpret.py: the
# tests patch ``pl.pallas_call``, lift the runner's TPU gate on the
# instance and shrink BLOCK; no product knob.

LANE_BLOCK = 1 << 12


@pytest.fixture
def lane_runner(monkeypatch):
    import functools

    import jax

    from tikv_tpu.device import pallas_hash
    from tikv_tpu.parallel import make_mesh
    monkeypatch.setattr(
        pallas_hash.pl, "pallas_call",
        functools.partial(pallas_hash.pl.pallas_call, interpret=True))
    monkeypatch.setattr(pallas_hash, "BLOCK", LANE_BLOCK)
    r = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    r._is_tpu = True            # lift the CPU gate (aggregate.agg_bodies)
    r._block_local = LANE_BLOCK
    return r


def lane_table():
    return Table(8700, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("k", 2, FieldType.long(not_null=True)),
        TableColumn("v", 3, FieldType.long(not_null=True))))


def lane_snapshot(seed, n=3 * LANE_BLOCK + 100, groups=40, sparse=False):
    """One 'region' of the lane table: its own handles, its own rows."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, groups, n).astype(np.int64)
    if sparse:
        keys = rng.integers(0, 1 << 62, groups, dtype=np.int64)[keys]
    ones = np.ones(n, np.bool_)
    return ColumnarTable.from_arrays(
        lane_table(), np.arange(seed * 100_000, seed * 100_000 + n,
                                dtype=np.int64),
        {"k": Column(EvalType.INT, keys, ones),
         "v": Column(EvalType.INT,
                     rng.integers(-1000, 1000, n).astype(np.int64), ones)})


def lane_dag(i, ranges=()):
    """The cell's plan; ``start_ts`` says which snapshot it reads (the
    rig's storage provider reads it back: a region id by other means)."""
    import dataclasses
    s = DagSelect.from_table(lane_table(), ["id", "k", "v"])
    dag = s.aggregate([s.col("k")],
                      [("count_star", None), ("sum", s.col("v"))]).build()
    return dataclasses.replace(dag, start_ts=i + 1, ranges=tuple(ranges))


class LaneRig:
    """An endpoint over several snapshots whose dispatcher can be HELD:
    while held, closed groups pile up in ``_ready`` exactly as they do
    behind a busy dispatcher, and ``release`` lets it take them."""

    def __init__(self, runner, snaps, window_ms=20.0, idle_bypass=False):
        self.runner, self.snaps = runner, snaps
        self.coal = RequestCoalescer(runner, window_ms=window_ms,
                                     max_group=8)
        self.coal.idle_bypass = idle_bypass
        self.ep = Endpoint(lambda req: snaps[req.dag.start_ts - 1],
                           device_runner=runner, device_row_threshold=1,
                           coalescer=self.coal)
        self._gate = threading.Event()
        self._gate.set()
        take = self.coal._take_fusable

        def gated(g):
            self._gate.wait(30)
            return take(g)

        self.coal._take_fusable = gated

    def one(self, dag):
        return self.ep.handle(CopRequest(REQ_TYPE_DAG, dag))

    def warm(self):
        """One read a snapshot: every line's kernel class is learnt."""
        for i in range(len(self.snaps)):
            self.one(lane_dag(i))

    def together(self, dags, n_groups):
        """Send ``dags`` at once with the dispatcher held until all
        ``n_groups`` groups have closed behind the one it popped."""
        import time
        self._gate.clear()
        out, errs = [None] * len(dags), []

        def one(i):
            try:
                out[i] = self.one(dags[i])
            except Exception as e:      # noqa: BLE001 — surfaced below
                errs.append((i, e))

        ts = [threading.Thread(target=one, args=(i,))
              for i in range(len(dags))]
        for t in ts:
            t.start()
        t_end = time.monotonic() + 10
        while len(self.coal._ready) < n_groups - 1 and \
                time.monotonic() < t_end:
            time.sleep(0.002)
        self._gate.set()
        for t in ts:
            t.join()
        assert not errs, errs
        return out

    def wait_built(self):
        """Until the builder threads have the kernel's lane programs."""
        import time
        t_end = time.monotonic() + 60
        while time.monotonic() < t_end:
            progs = [e.get("lane_progs") for key, e in
                     self.runner._kernel_cache.items()
                     if key[0] == "hashpl" and isinstance(e, dict)]
            if progs and all(p and all(p.values()) for p in progs):
                return
            time.sleep(0.01)
        raise AssertionError("lane programs not built")

    def solo(self, i, ranges=()):
        dag = lane_dag(i, ranges)
        return sorted(BatchExecutorsRunner(
            dag, self.snaps[i]).handle_request().rows())

    def close(self):
        self.ep.close()
        assert self.runner._arena.pinned_bytes() == 0   # every pin, once


def lanes_of(rig) -> dict:
    st = rig.coal.stats()
    return {k: st[k] for k in (
        "lanes_hist", "multi_lane_launches", "lanes_sum", "groups_merged",
        "same_lane_merges", "lane_class_mismatch", "launch_classes",
        "unbuilt_fallbacks", "solo_degrade")}


@pytest.mark.parametrize("k,sparse", [(2, False), (3, False), (4, False),
                                      (5, False), (6, False), (3, True)])
def test_k_lane_answers_equal_the_solo_answers(lane_runner, k, sparse):
    """k closed groups over k feeds of one compile class leave as ONE
    launch (one flight-recorder entry, one Pallas call a lane) and every
    lane's answer is its own snapshot's, exactly; two members of one key
    share a lane.  Sparse slot-mode lanes fuse too (the slot plane is
    one more input a lane)."""
    rig = LaneRig(lane_runner, [lane_snapshot(s, sparse=sparse)
                                for s in range(k)])
    try:
        rig.warm()
        rig.wait_built()
        dags = [lane_dag(i) for i in range(k)] + [lane_dag(1)]
        rec = lane_runner.flight_recorder
        before = rec.stats()["launches"]
        out = rig.together(dags, k)
        for got, i in zip(out, list(range(k)) + [1]):
            assert sorted(got.rows()) == rig.solo(i), i
            assert got.backend == "device"
        # a kernel has programs of 2, 3 and 4 lanes: up to four lanes
        # ONE launch, five and six two (4 + 1, 4 + 2)
        first, rest = min(k, 4), k - min(k, 4)
        assert rec.stats()["launches"] == before + (2 if rest else 1)
        assert rec.items()[-1]["compile_class"] == "pallas_hash"
        assert rec.stats()["faults"] == 0
        st = lanes_of(rig)
        assert st["lanes_hist"][str(first)] == (2 if rest == 4 else 1), st
        assert st["multi_lane_launches"] == (2 if rest > 1 else 1), st
        assert st["groups_merged"] == k - 1, st
        assert st["solo_degrade"] == 0, st
        ag = lane_runner.mesh_stats()["lanes"]
        assert ag["launches_by_lanes"][str(first)] >= 1 and \
            ag["launch_failures"] == 0 and ag["programs_built"] == 3, ag
    finally:
        rig.close()


def test_lane_programs_never_compile_on_the_dispatcher(
        lane_runner, monkeypatch):
    """A kernel's lane programs are built beside the kernel, on builder
    threads; while they are building, groups of its class leave one by
    one, by launches that are built, and no staging waits for them."""
    from tikv_tpu.device import aggregate
    built_on = []
    hold = threading.Event()
    build = aggregate._build_lane_program

    def spy(call, k, *rest):
        built_on.append((threading.current_thread().name, k))
        hold.wait(30)
        return build(call, k, *rest)

    monkeypatch.setattr(aggregate, "_build_lane_program", spy)
    rig = LaneRig(lane_runner, [lane_snapshot(s) for s in range(3)])
    try:
        rig.warm()
        rec = lane_runner.flight_recorder
        before = rec.stats()["launches"]
        rig.together([lane_dag(i) for i in range(3)], 3)
        # three groups, three single launches, nothing merged yet
        assert rec.stats()["launches"] == before + 3
        st = lanes_of(rig)
        assert st["multi_lane_launches"] == 0 and \
            st["groups_merged"] == 0 and st["unbuilt_fallbacks"] >= 1, st
        hold.set()
        rig.wait_built()
        assert sorted(built_on) == [("copr-lane-builder", k)
                                    for k in (2, 3, 4)], built_on
        # two lanes next: the largest built count that fits
        rig.together([lane_dag(i) for i in range(2)], 2)
        assert lanes_of(rig)["lanes_hist"]["2"] == 1
    finally:
        hold.set()
        rig.close()


def test_lanes_at_two_versions_of_one_line(lane_runner):
    """Two generations of ONE line are two lanes: each is its own
    snapshot (its own ``req_v``, rows, feed) and gets its own answer."""
    from tikv_tpu.copr.region_cache import FeedLineage
    old, new = lane_snapshot(11), lane_snapshot(12)
    lineage = FeedLineage()
    # what lies between the two is no row patch: the feed re-uploads
    lineage.record({"structural": True, "spans": []})
    for snap, v in ((old, 0), (new, 1)):
        snap.feed_lineage, snap.feed_version = lineage, v
    rig = LaneRig(lane_runner, [old, new])
    try:
        rig.warm()
        rig.wait_built()
        want = [rig.solo(0), rig.solo(1)]
        assert want[0] != want[1]
        got = rig.together([lane_dag(0), lane_dag(1)], 2)
        assert [sorted(g.rows()) for g in got] == want
        st = lanes_of(rig)
        assert st["groups_merged"] == 1 and st["lanes_hist"]["2"] == 1, st
        assert st["launch_classes"] == 1, st
    finally:
        rig.close()


@pytest.mark.parametrize("what", ["n_pad", "capacity", "tile"])
def test_other_launch_classes_are_not_fused(lane_runner, what):
    """A feed in another ``n_pad`` bucket, another ``capacity``, or a
    bucket-tile request (ranges over part of a region) leaves alone,
    today's path; answers exact."""
    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.executors.ranges import KeyRange
    other = {"n_pad": lane_snapshot(1, n=9 * LANE_BLOCK + 7),
             "capacity": lane_snapshot(1, groups=2000),
             "tile": lane_snapshot(1)}[what]
    rig = LaneRig(lane_runner, [lane_snapshot(0), other])
    try:
        rig.warm()
        ranges = ()
        if what == "tile":
            ranges = (KeyRange(table_record_key(8700, 100_000 + 256),
                               table_record_key(8700, 100_000 + 9000)),)
            assert other.row_slices(ranges) == [(256, 9000)]
            rig.one(lane_dag(1, ranges))    # its kernel, warm
        before = lane_runner.flight_recorder.stats()["launches"]
        got = rig.together([lane_dag(0), lane_dag(1, ranges)], 2)
        assert sorted(got[0].rows()) == rig.solo(0)
        assert sorted(got[1].rows()) == rig.solo(1, ranges)
        assert all(g.backend == "device" for g in got)
        assert lane_runner.flight_recorder.stats()["launches"] == before + 2
        st = lanes_of(rig)
        assert st["groups_merged"] == 0 and \
            st["multi_lane_launches"] == 0, st
        # a tile request has no launch class at all; another class is
        # counted as what kept the two apart (whichever was popped
        # first saw the other behind it)
        assert st["lane_class_mismatch"] == (0 if what == "tile" else 1), st
        # ... and the launch that left it behind left under ONE class
        # (the second found nothing waiting and was asked for none)
        assert st["launch_classes"] == 1 if what != "tile" else \
            st["launch_classes"] <= 1, st
    finally:
        rig.close()


@pytest.mark.parametrize("fault", ["failpoint", "launch_raises",
                                   "fetch_fault"])
def test_a_failed_lane_launch_never_fails_its_members(lane_runner, fault):
    """``copr::coalesce_dispatch`` and a raising multi-lane program:
    every member retries solo on the device.  A fault in the launch's
    one fetch: every lane degrades by itself to the host pipeline.
    Exact answers, every pin released once."""
    rig = LaneRig(lane_runner, [lane_snapshot(s) for s in range(3)])
    try:
        rig.warm()
        dags = [lane_dag(i) for i in range(3)] + [lane_dag(2)]
        rig.together(dags, 3)
        rig.wait_built()
        if fault == "failpoint":
            failpoint.cfg("copr::coalesce_dispatch", "1*return")
        elif fault == "fetch_fault":
            failpoint.cfg("device::before_fetch", "1*return")
        else:
            def boom(*_a, **_k):
                raise RuntimeError("injected lane launch failure")
            for key, e in lane_runner._kernel_cache.items():
                if key[0] == "hashpl":
                    e["lane_progs"][3] = boom
        got = rig.together(dags, 3)
        for g, i in zip(got, (0, 1, 2, 2)):
            assert sorted(g.rows()) == rig.solo(i), i
            # (a lane's fetch fault is served by the runner's own host
            # rung, as a share group's always was: still "device" here)
            assert g.backend == "device"
        st = lanes_of(rig)
        assert st["solo_degrade"] == \
            (0 if fault == "fetch_fault" else 4), st
        if fault == "launch_raises":
            ag = lane_runner.mesh_stats()["lanes"]
            assert ag["launch_failures"] == 1, ag
    finally:
        rig.close()


def test_same_key_groups_that_closed_in_turn_share_a_lane(lane_runner):
    """Two groups of ONE key, closed one after the other, wait behind
    the dispatcher: they leave as one lane of one launch, one result."""
    import time
    rig = LaneRig(lane_runner, [lane_snapshot(0)])
    try:
        rig.warm()
        before = lane_runner.flight_recorder.stats()["launches"]
        rig._gate.clear()
        out = []
        ts = [threading.Thread(
            target=lambda: out.append(rig.one(lane_dag(0))))
            for _ in range(3)]
        ts[0].start()
        t_end = time.monotonic() + 10
        closes = rig.coal.stats()["closes"].get("window", 0)
        while rig.coal.stats()["closes"].get("window", 0) == closes and \
                time.monotonic() < t_end:
            time.sleep(0.002)       # the first group closed, and is held
        for t in ts[1:]:
            t.start()
        while not rig.coal._ready and time.monotonic() < t_end:
            time.sleep(0.002)       # the second closed behind it
        rig._gate.set()
        for t in ts:
            t.join()
        assert [sorted(g.rows()) for g in out] == [rig.solo(0)] * 3
        assert lane_runner.flight_recorder.stats()["launches"] == before + 1
        st = lanes_of(rig)
        assert st["same_lane_merges"] == 1 and st["groups_merged"] == 0 \
            and st["multi_lane_launches"] == 0, st
    finally:
        rig.close()


def test_an_open_group_of_the_class_leaves_with_the_launch(lane_runner):
    """A group still collecting when a launch of its class leaves is
    closed early (``lanes``) and goes with it: it waits less, never
    longer."""
    import time
    rig = LaneRig(lane_runner, [lane_snapshot(0), lane_snapshot(1)],
                  window_ms=20.0)
    try:
        rig.warm()
        rig.wait_built()
        rig.coal.configure(window_ms=5000.0)
        rig._gate.clear()
        out = {}
        first = threading.Thread(
            target=lambda: out.setdefault(0, rig.one(lane_dag(0))))
        first.start()
        t_end = time.monotonic() + 10
        while not rig.coal._open and time.monotonic() < t_end:
            time.sleep(0.002)
        # close the first group now; the dispatcher pops it and is held
        with rig.coal._cv:
            for g in list(rig.coal._open.values()):
                rig.coal._close_locked(g, "window")
        rig.coal.configure(window_ms=5000.0)
        second = threading.Thread(
            target=lambda: out.setdefault(1, rig.one(lane_dag(1))))
        second.start()
        while not rig.coal._open and time.monotonic() < t_end:
            time.sleep(0.002)       # the second collects, 5 s to go
        t0 = time.monotonic()
        rig._gate.set()
        first.join()
        second.join()
        assert time.monotonic() - t0 < 2.0      # not its 5 s window
        assert sorted(out[0].rows()) == rig.solo(0)
        assert sorted(out[1].rows()) == rig.solo(1)
        st = rig.coal.stats()
        assert st["closes"].get("lanes") == 1, st
        assert st["groups_merged"] == 1 and st["lanes_hist"]["2"] == 1, st
    finally:
        rig.close()


def _prepared(runner) -> dict:
    return runner.mesh_stats()["prepared"]


@pytest.mark.parametrize("max_group", [8, 2])
def test_the_take_is_what_it_was_and_a_ticket_rides_every_group_asked(
        lane_runner, max_group):
    """Behind a held dispatcher wait, in this order, a closed group of
    another class, a closed group of the lead's class and an OPEN group
    of it.  The take is what it was: oldest first, the other class left
    behind and counted, the open group closed early (``lanes``), no more
    keys than ``max_group``; and what the runner resolved to tell each
    asked group's class stays with the group as its ticket, from which
    its lane is staged, in this hold or a later one."""
    import time
    from tikv_tpu.server import coalescer as coal_mod
    snaps = [lane_snapshot(0), lane_snapshot(1, n=9 * LANE_BLOCK + 7),
             lane_snapshot(2), lane_snapshot(3)]
    rig = LaneRig(lane_runner, snaps)
    try:
        rig.warm()
        rig.wait_built()
        rig.coal.max_group = max_group
        seen = []
        inner = rig.coal._take_fusable

        def recording(g):
            rig._gate.wait(30)
            with rig.coal._mu:
                waiting = list(rig.coal._ready) + \
                    list(rig.coal._open.values())
            take = inner(g)
            seen.append((g, waiting, take))
            return take

        rig.coal._take_fusable = recording
        before = _prepared(lane_runner)
        rig._gate.clear()
        out, ts = {}, []
        t_end = time.monotonic() + 10

        def send(i):
            t = threading.Thread(
                target=lambda: out.setdefault(i, rig.one(lane_dag(i))))
            t.start()
            ts.append(t)

        def until(cond):
            while not cond() and time.monotonic() < t_end:
                time.sleep(0.002)
            assert cond()

        send(0)     # closes by its window; the dispatcher pops it, is held
        until(lambda: rig.coal.stats()["closes"].get("window", 0) >= 1 and
              not rig.coal._ready and not rig.coal._open)
        for i in (1, 2):
            send(i)
            until(lambda: len(rig.coal._ready) == i)
        rig.coal.configure(window_ms=1500.0)
        send(3)
        until(lambda: len(rig.coal._open) == 1)     # collecting
        rig._gate.set()
        for t in ts:
            t.join()
        for i in range(4):
            assert sorted(out[i].rows()) == rig.solo(i), i
        g, waiting, take = seen[0]
        g1, g2, g3 = waiting
        assert [og.members[0].dag.start_ts for og in waiting] == [2, 3, 4]
        # oldest first, one lane a key, the lead's key among max_group
        assert take == ([g2, g3] if max_group == 8 else [g2])
        st = rig.coal.stats()
        # (the group of the other class, popped next, finds the open
        # one of the lead's class waiting where max_group left it)
        assert st["lane_class_mismatch"] == (1 if max_group == 8 else 2), st
        assert st["closes"].get("lanes", 0) == (max_group == 8), st
        assert st["groups_merged"] == len(take), st
        # every group asked carries what the runner resolved for it
        asked = [g, g1] + take
        for og in asked:
            lead = og.members[0]
            t = og.ticket
            assert t is not None and t.klass == og.klass
            assert t.klass == lane_runner.launch_class(
                og.key, lead.dag, lead.storage)
            assert t.runner is lane_runner and \
                t.anchor is lead.storage and t.rec.key == t.klass
        assert g1.klass != g.klass and \
            all(og.klass == g.klass for og in take)
        if max_group == 2:
            # the third key was not asked in that turn; by the time it
            # left it had been, in its own
            assert g3 not in take and g3.ticket is not None and \
                g3.klass is not coal_mod._UNASKED
        # and each of the four lanes was staged from its group's ticket
        after = _prepared(lane_runner)
        assert after["ticket_hits"] == before["ticket_hits"] + 4
        assert after["ticket_misses"] == before["ticket_misses"]
        assert after["hits"] == before["hits"] + 4
    finally:
        rig.close()


@pytest.mark.parametrize("what", ["drop_feed", "kernel_false"])
def test_a_group_whose_ticket_went_stale_while_it_waited_still_launches(
        lane_runner, what):
    """Between the take (every group's ticket asked) and the staging,
    what one group's ticket stood on goes: that lane is staged in full
    inside the same hold, the others from their tickets, nobody retries
    solo and every answer is right."""
    snaps = [lane_snapshot(s) for s in range(3)]
    rig = LaneRig(lane_runner, snaps)
    try:
        rig.warm()
        rig.wait_built()
        inner = rig.coal._take_fusable
        stale = []

        def then_stale(g):
            take = inner(g)
            if take and not stale:
                assert all(og.ticket is not None for og in [g] + take)
                victim = take[0]
                stale.append(victim)
                if what == "drop_feed":
                    assert lane_runner.drop_feed(
                        victim.members[0].storage) > 0
                else:
                    lane_runner._kernel_cache[victim.ticket.rec.key] = False
            return take

        rig.coal._take_fusable = then_stale
        before = _prepared(lane_runner)
        launches = lane_runner.flight_recorder.stats()["launches"]
        got = rig.together([lane_dag(i) for i in range(3)], 3)
        for i, g in enumerate(got):
            assert sorted(g.rows()) == rig.solo(i), i
            assert g.backend == "device"
        assert len(stale) == 1
        after = _prepared(lane_runner)
        st = lanes_of(rig)
        assert st["solo_degrade"] == 0 and st["groups_merged"] == 2, st
        if what == "drop_feed":
            # its lane: one full staging (a new feed, a new record) that
            # left with the two ticketed lanes, in one launch
            assert after["ticket_misses"]["feed"] == \
                before["ticket_misses"]["feed"] + 1
            assert after["ticket_hits"] == before["ticket_hits"] + 2
            assert after["builds"] == before["builds"] + 1
            assert lane_runner.flight_recorder.stats()["launches"] == \
                launches + 1
        else:
            # the class's kernel gone, every lane of it misses there and
            # the stand-in body serves each
            assert after["ticket_misses"]["kernel"] == \
                before["ticket_misses"]["kernel"] + 3
            assert after["ticket_hits"] == before["ticket_hits"]
            del lane_runner._kernel_cache[stale[0].ticket.rec.key]
        assert sum(after["ticket_misses"].values()) + after["ticket_hits"] \
            == sum(before["ticket_misses"].values()) + \
            before["ticket_hits"] + 3
    finally:
        rig.close()


def test_a_lone_request_on_an_idle_store_leaves_at_once(lane_runner):
    """Nothing parked, nothing in flight: the group closes ``idle``
    and is one lane; merging adds no window and no wait."""
    import time
    rig = LaneRig(lane_runner, [lane_snapshot(0)], window_ms=2000.0,
                  idle_bypass=True)
    try:
        rig.warm()
        closes = dict(rig.coal.stats()["closes"])
        t0 = time.monotonic()
        got = rig.one(lane_dag(0))
        assert time.monotonic() - t0 < 1.0      # never the 2 s window
        assert sorted(got.rows()) == rig.solo(0)
        st = rig.coal.stats()
        assert st["closes"]["idle"] == closes.get("idle", 0) + 1, st
        assert st["groups_merged"] == st["same_lane_merges"] == 0, st
        assert set(st["lanes_hist"]) == {"1"}, st
    finally:
        rig.close()


def test_every_member_of_a_lane_launch_can_show_its_launch(lane_runner):
    """Each member's OWN trace holds a ``device_dispatch`` span with the
    launch's flight record, its lane count and its lane; only the
    leader carries it as a phase, so no member's phases outgrow it."""
    from tikv_tpu.utils import tracker
    k = 3
    rig = LaneRig(lane_runner, [lane_snapshot(s) for s in range(k)])
    try:
        rig.warm()
        dags = [lane_dag(i) for i in range(k)] + [lane_dag(0)]
        rig.together(dags, k)
        rig.wait_built()
        trackers = [None] * len(dags)
        one = rig.one

        def traced(dag, _i=iter(range(len(dags)))):
            tr, tok = tracker.install(sampled=True)
            trackers[dags.index(dag) if trackers[dags.index(dag)] is None
                     else len(dags) - 1] = tr
            try:
                return one(dag)
            finally:
                tr.finish()
                tracker.uninstall(tok)

        rig.one = traced
        rig.together(dags, k)
        seen = []
        with_phase = 0
        for tr in trackers:
            spans = [s for s in tr.spans if s.name == "device_dispatch"]
            assert len(spans) == 1, [s.name for s in tr.spans]
            attrs = spans[0].attrs
            assert attrs["compile_class"] == "pallas_hash", attrs
            assert attrs["lanes"] == k
            if "device_dispatch" in tr.phases:
                with_phase += 1
            else:
                seen.append(attrs["lane"])
                assert sum(tr.phases.values()) <= tr.total_ns()
        assert with_phase == 1          # the leader, as before
        assert len(seen) == k and set(seen) <= set(range(k)), seen
    finally:
        rig.close()


# ------------------------------------------- lanes that differ in constants


def const_dag(i, threshold, grouped):
    """The lane plan under a predicate whose constant differs from
    request to request: one const-blind compile class."""
    import dataclasses
    s = DagSelect.from_table(lane_table(), ["id", "k", "v"])
    dag = s.where(s.col("v") > threshold).aggregate(
        [s.col("k")] if grouped else [],
        [("count_star", None), ("sum", s.col("v"))]).build()
    return dataclasses.replace(dag, start_ts=i + 1)


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["group_by", "no_group_by"])
def test_lanes_of_one_class_carry_their_own_constants(lane_runner, grouped):
    """An aggregation's constants are operands of its kernel, so closed
    groups that differ in them ALONE (and in their feeds) have one launch
    class: they leave as lanes of one launch, each lane's scalars its own
    constants, each answer its own.  One kernel entry serves them all,
    GROUP BY or not."""
    thresholds = (-500, 0, 250, 900)
    rig = LaneRig(lane_runner, [lane_snapshot(s) for s in range(4)])
    try:
        for i, c in enumerate(thresholds):      # every line learns the class
            rig.one(const_dag(i, thresholds[0], grouped))
        rig.wait_built()
        entries = [k for k in lane_runner._kernel_cache if k[0] == "hashpl"]
        assert len(entries) == 1, entries
        first0 = lane_runner.flight_recorder.stats()["first_launches"]
        launches0 = lane_runner.flight_recorder.stats()["launches"]
        dags = [const_dag(i, c, grouped) for i, c in enumerate(thresholds)]
        classes = {lane_runner.launch_class(
            lane_runner.batch_class(d, rig.snaps[i]), d, rig.snaps[i])
            for i, d in enumerate(dags)}
        assert len(classes) == 1 and None not in classes
        out = rig.together(dags, 4)
        for i, (dag, got) in enumerate(zip(dags, out)):
            want = BatchExecutorsRunner(
                dag, rig.snaps[i]).handle_request().rows()
            assert sorted(got.rows()) == sorted(want), i
        assert len({tuple(sorted(r.rows())) for r in out}) == 4
        stats = lane_runner.flight_recorder.stats()
        assert stats["launches"] - launches0 < 4        # fused
        assert stats["first_launches"] == first0        # nothing built
        assert [k for k in lane_runner._kernel_cache
                if k[0] == "hashpl"] == entries
        assert lanes_of(rig)["multi_lane_launches"] >= 1
        assert lanes_of(rig)["lane_class_mismatch"] == 0
        assert all(e["params"] == 1 for e in
                   lane_runner.flight_recorder.items())
    finally:
        rig.close()


def test_a_memo_is_shared_by_constants_and_split_by_the_key(runner):
    """What a request's memo holds is a property of the data and of the
    GROUP BY key: two constant tuples share one, another key constant
    has its own (its key bounds differ)."""
    def dag(threshold, shift):
        s = DagSelect.from_table(lane_table(), ["id", "k", "v"])
        return s.where(s.col("v") > threshold).aggregate(
            [s.col("k") + shift],
            [("count_star", None), ("sum", s.col("v"))]).build()

    def key(d):
        return runner._meta_key(d, runner._analyze(d))

    assert key(dag(5, 1)) == key(dag(700, 1))
    assert key(dag(5, 1)) != key(dag(5, 2))
    assert dag(5, 1).plan_key() != dag(700, 1).plan_key()
    snap = lane_snapshot(11)
    for threshold, shift in ((5, 1), (700, 1), (5, 2)):
        d = dag(threshold, shift)
        assert sorted(runner.handle_request(d, snap).rows()) == sorted(
            BatchExecutorsRunner(d, snap).handle_request().rows())
    metas = [k for k in runner._arena.bucket(snap) if k[0] == "meta"]
    assert len(metas) == 2


# ------------------------------------- a group's phases never outgrow its wall


class SlowStaging:
    """A stub in front of a runner: every staging holds one phase for
    ``ms`` before the real launch, long against everything else a toy
    request does."""

    def __init__(self, runner, ms):
        self._runner, self._s = runner, ms / 1e3

    def __getattr__(self, name):
        return getattr(self._runner, name)

    def handle_request(self, dag, storage, **kw):
        import time

        from tikv_tpu.utils import tracker
        with tracker.phase("feed_upload"):
            time.sleep(self._s)
        return self._runner.handle_request(dag, storage, **kw)


@pytest.fixture(scope="module")
def group_of_three(runner):
    """Three identical aggregations as ONE share group over a staging of
    40 ms → {"leader": its tracker, "member": another's}."""
    from tikv_tpu.utils import tracker
    table, snap = make_snapshot(seed=11)
    ep, coal = make_endpoint(SlowStaging(runner, 40.0), snap, max_group=3,
                             window_ms=2000.0)
    trackers = [None] * 3

    def traced(i):
        tr, tok = tracker.install(sampled=True)
        trackers[i] = tr
        try:
            got = ep.handle(CopRequest(REQ_TYPE_DAG, agg_dag(table)))
            assert got.backend == "device"
        finally:
            tr.finish()
            tracker.uninstall(tok)

    try:
        ep.handle(CopRequest(REQ_TYPE_DAG, agg_dag(table)))     # warm
        before = coal.stats()["groups_dispatched"]
        ts = [threading.Thread(target=traced, args=(i,)) for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert coal.stats()["groups_dispatched"] == before + 1
    finally:
        ep.close()
    led = [any(s.name == "group_dispatch" and not s.links for s in tr.spans)
           for tr in trackers]
    assert sorted(led) == [False, False, True], led
    return {"leader": trackers[led.index(True)],
            "member": trackers[led.index(False)]}


@pytest.mark.parametrize("who", ["leader", "member"])
def test_no_members_phases_sum_past_its_wall(group_of_three, who):
    """ROADMAP D12: the leader's tracker is adopted over the shared
    staging, so its phases hold the staging as the work it was; its
    coalesce_wait therefore ends where the staging began (it used to run
    to the staging's end: the staging twice).  Every other member waited
    for the staging, and its coalesce_wait says so."""
    from tikv_tpu.utils.trace_vocab import OUTSIDE_ROOT
    tr, led = group_of_three[who], who == "leader"
    in_root = {k: v for k, v in tr.phases.items() if k not in OUTSIDE_ROOT}
    assert sum(in_root.values()) <= tr.total_ns(), (in_root, tr.total_ns())
    wait_ms = tr.phases["coalesce_wait"] / 1e6
    by = {s.name: s for s in tr.spans}
    if led:
        assert tr.phases["feed_upload"] >= 40e6
        # window + dispatcher queue, exactly: its span-only children
        kids = sum(by[n].t1 - by[n].t0 for n in
                   ("coalesce_window", "dispatch_queue_wait"))
        assert tr.phases["coalesce_wait"] == kids
        assert by["coalesce_wait"].t1 <= by["feed_upload"].t0
    else:
        assert "feed_upload" not in tr.phases
        assert wait_ms >= 40.0          # it waited for the staging
    # the span sits where the wait was, its children inside it
    cw = by["coalesce_wait"]
    assert cw.t0 <= by["coalesce_window"].t0 and \
        by["dispatch_queue_wait"].t1 <= cw.t1


# ------------------------------------------------- the dispatch lock's wait


def test_dispatch_lock_wait_is_a_phase_of_a_contended_launch(runner):
    """A request that finds the runner's dispatch lock held says how
    long it waited for it, on its own launch path."""
    import time

    from tikv_tpu.utils import trace as trace_mod
    from tikv_tpu.utils import tracker
    table, snap = make_snapshot(seed=12)
    dag = agg_dag(table)
    runner.handle_request(dag, snap)        # warm
    row0 = trace_mod.AGGREGATE.snapshot()["dispatch_lock_wait"]
    out = {}

    def launch():
        tr, tok = tracker.install()
        try:
            runner.handle_request(dag, snap)
        finally:
            tr.finish()
            tracker.uninstall(tok)
        out["tr"] = tr

    runner._dispatch_mu.acquire()
    try:
        t = threading.Thread(target=launch)
        t.start()
        time.sleep(0.3)     # long against its way to the lock, loaded or not
    finally:
        runner._dispatch_mu.release()
    t.join(timeout=60)
    tr = out["tr"]
    assert 100.0 <= tr.time_detail()["phases_ms"]["dispatch_lock_wait"]
    assert tr.phases["dispatch_lock_wait"] + tr.phases["device_dispatch"] \
        <= tr.total_ns()
    row = trace_mod.AGGREGATE.snapshot()["dispatch_lock_wait"]
    assert row["count"] == row0["count"] + 1
    assert row["wall_ms"] >= row0["wall_ms"] + 100.0
    assert "dispatch_lock_wait" not in trace_mod.ANNOTATED      # a wait


def test_a_lane_launch_records_no_dispatch_lock_wait(lane_runner):
    """``handle_lanes`` stages its lanes under one hold of the lock it
    took itself: a lane's launch path takes none and records none."""
    from tikv_tpu.utils import tracker
    k = 2
    rig = LaneRig(lane_runner, [lane_snapshot(s) for s in range(k)])
    try:
        rig.warm()
        rig.together([lane_dag(i) for i in range(k)], k)
        rig.wait_built()
        tr, tok = tracker.install()
        try:
            outcomes = lane_runner.handle_lanes(
                [(lane_dag(i), rig.snaps[i]) for i in range(k)])
            assert all(d is not None for d in outcomes)
            for d in outcomes:
                d.result()
        finally:
            tr.finish()
            tracker.uninstall(tok)
        assert "device_dispatch" in tr.phases
        assert "dispatch_lock_wait" not in tr.phases
    finally:
        rig.close()
