"""Device-aware resource metering: RU charge sites, group occupancy
splits, bounded tag maps, windowed top-k PD reports, trace annotation.

The PR 13 acceptance bars live here: every RU charge-site label
resolves to the registered :data:`~tikv_tpu.ru_model.CHARGE_SITES`
vocabulary (two-way source scan, the failpoint/span-inventory
discipline); a coalesced group's shared launch splits by occupancy
share across member tags and a group that fails at
``copr::coalesce_dispatch`` (members retrying solo) never double-
charges the wall; chaos failover (slice death mid-group) charges each
member exactly once; per-tag attribution covers ≥95% of the measured
device launch wall with the residual reported as an explicit
``untagged`` entry; and the windowed top-k hot regions are visible at
PD and ``/resource_metering``.
"""

import json
import logging
import re
import threading
import time
import urllib.request

import pytest

from tikv_tpu import resource_metering as rm
from tikv_tpu.resource_metering import (
    GLOBAL_RECORDER,
    MeterContext,
    Recorder,
    ResourceTagFactory,
    TagRecord,
    coverage_from,
)
from tikv_tpu.ru_model import CHARGE_SITES, GLOBAL_MODEL, RuModel
from tikv_tpu.utils import failpoint


@pytest.fixture(autouse=True)
def _fp_teardown():
    yield
    failpoint.teardown()


# ------------------------------------------- charge-site vocabulary CI


def test_charge_site_vocabulary_inventory():
    """Every RU charge-site literal used in tikv_tpu/ resolves to the
    registered CHARGE_SITES table — and the table carries no dead
    sites — so an unregistered or typo'd charge site fails tier-1
    (the failpoint-inventory discipline applied to metering)."""
    import pathlib

    import tikv_tpu

    root = pathlib.Path(tikv_tpu.__file__).parent
    pat = re.compile(
        r'(?:\bcharge|\b_land)\(\s*\n?\s*"([a-z0-9_]+::[a-z0-9_]+)"')
    used = set()
    for p in root.rglob("*.py"):
        used |= set(pat.findall(p.read_text()))
    assert len(used) >= 5, f"charge-site scan found only {sorted(used)}"
    unknown = used - set(CHARGE_SITES)
    assert not unknown, \
        f"charge sites missing from ru_model.CHARGE_SITES: " \
        f"{sorted(unknown)}"
    dead = set(CHARGE_SITES) - used
    assert not dead, f"CHARGE_SITES entries no code charges: " \
        f"{sorted(dead)}"
    assert all(isinstance(v, str) and v for v in CHARGE_SITES.values())


# --------------------------------------------------------- RU model


def test_ru_model_linear_pricing_and_online_weights():
    m = RuModel()
    assert m.ru() == 0.0
    # 3ms of device wall ≈ 1 RU at the default price
    assert m.ru(launch_s=0.003) == pytest.approx(1.0, rel=1e-6)
    # 64 KiB of D2H ≈ 1 RU
    assert m.ru(d2h_bytes=64 * 1024) == pytest.approx(1.0, rel=1e-6)
    base = m.ru(launch_s=0.01, d2h_bytes=1 << 20, host_s=0.01,
                byte_seconds=20 * (1 << 20), read_keys=2048,
                requests=8)
    # linear: doubling every axis doubles the figure
    assert m.ru(launch_s=0.02, d2h_bytes=2 << 20, host_s=0.02,
                byte_seconds=40 * (1 << 20), read_keys=4096,
                requests=16) == pytest.approx(2 * base, rel=1e-6)
    m.set_weights(ru_per_d2h_mb=32.0)
    assert m.ru(d2h_bytes=1 << 20) == pytest.approx(32.0)
    with pytest.raises(ValueError):
        m.set_weights(ru_per_bogus=1.0)
    assert set(m.describe()["weights"]) == set(RuModel.DEFAULTS)


# ----------------------------------------------------- recorder units


def test_group_split_by_occupancy_share():
    """A shared launch under a group scope splits evenly across member
    tags — never dumped on the leader — and the shares sum exactly to
    the measured wall."""
    rec = Recorder()
    members = [("t|a", 1, None), ("t|b", 2, None), ("t|c", 3, None)]
    with rec.group_scope(members):
        rm_ctx = rm.current_context()
        assert rm_ctx.members == tuple(members)
        rec.charge("copr::coalesce_dispatch", launch_s=0.3, split=True)
        rec.charge("device::d2h", d2h_bytes=3 << 20, split=True)
    tot = rec.totals()
    for tag in ("t|a", "t|b", "t|c"):
        assert tot[tag].launch_s == pytest.approx(0.1, rel=1e-9)
        assert tot[tag].d2h_bytes == pytest.approx(1 << 20)
    assert sum(r.launch_s for r in tot.values()) == \
        pytest.approx(0.3, rel=1e-9)
    # per-region mirror landed too
    regs = rec.region_totals()
    assert regs[1].launch_s == pytest.approx(0.1, rel=1e-9)
    # outside the scope a plain charge goes to the single ambient tag
    with rec.attach("solo", requests=0):
        rec.charge("device::launch", launch_s=0.05)
    assert rec.totals()["solo"].launch_s == pytest.approx(0.05)


def test_untagged_residual_is_explicit():
    rec = Recorder()
    rec.charge("device::launch", launch_s=0.2)     # no ambient context
    with rec.attach("named", requests=0):
        rec.charge("device::launch", launch_s=0.8)
    tot = rec.totals()
    assert tot[rm.UNTAGGED].launch_s == pytest.approx(0.2)
    cov = coverage_from(tot)
    assert cov == pytest.approx(0.8, abs=0.01)
    rep = rec.roll_window(force=True)
    assert rep["untagged"] is not None
    assert rep["untagged"]["launch_ms"] == pytest.approx(200.0)
    # coverage with a base snapshot diffs correctly
    base = rec.totals()
    with rec.attach("named", requests=0):
        rec.charge("device::launch", launch_s=1.0)
    assert coverage_from(rec.totals(), base) == pytest.approx(1.0)


def test_tag_map_bounded_fold_and_idle_eviction():
    """Rotating request_source strings cannot grow the map without
    bound: beyond the hard cap new tags aggregate into 'other', and
    idle tags fold into 'other' on window roll."""
    rec = Recorder(max_tags=8)
    cap = rec._hard_cap()
    for i in range(cap + 40):
        rec.charge("device::launch", launch_s=0.001,
                   tag=f"rg|src-{i}")
    tot = rec.totals()
    assert len(tot) <= cap + 1          # named tags + "other"
    assert tot[rm.OTHER_TAG].launch_s > 0
    # sum-exact: nothing was dropped by the fold
    assert sum(r.launch_s for r in tot.values()) == \
        pytest.approx(0.001 * (cap + 40), rel=1e-6)
    # idle eviction: a tag silent for IDLE_WINDOWS rolls folds away
    assert "rg|src-0" in tot
    for _ in range(rm.IDLE_WINDOWS + 1):
        rec.roll_window(force=True)
    tot = rec.totals()
    assert "rg|src-0" not in tot
    assert sum(r.launch_s for r in tot.values()) == \
        pytest.approx(0.001 * (cap + 40), rel=1e-6)


def test_windowed_topk_report_shape():
    rec = Recorder(topk=2)
    for i, ru_ms in enumerate((30, 10, 20)):
        rec.charge("device::launch", launch_s=ru_ms / 1e3,
                   tag=f"tenant{i}", region=100 + i)
    rep = rec.roll_window(force=True)
    assert [e["tag"] for e in rep["top_tenants"]] == \
        ["tenant0", "tenant2"]
    assert [e["region"] for e in rep["top_regions"]] == [100, 102]
    assert rep["total_ru"] == pytest.approx(
        GLOBAL_MODEL.ru(launch_s=0.06), rel=1e-3)
    # the rolled report serves report() until the next roll
    assert rec.report()["top_tenants"] == rep["top_tenants"]
    # maybe_report paces by report_interval_s (push far enough into
    # the monotonic past — 0.0 only works once uptime > interval)
    rec.report_interval_s = 3600.0
    rec._last_push = time.monotonic() - 7200.0
    first = rec.maybe_report()
    assert first is not None and "top_tenants" in first
    assert rec.maybe_report() is None       # interval not elapsed


def test_exactly_once_under_group_failure_unit():
    """The ISSUE's exactly-once shape at the unit level: a group whose
    dispatch fails before launching charges NOTHING; the members' solo
    retries are the only launches billed — totals match the walls
    actually measured, never doubled."""
    rec = Recorder()
    members = [("a", None, None), ("b", None, None)]
    with rec.group_scope(members):
        pass        # dispatch failed before any launch: no charge
    for tag in ("a", "b"):
        with rec.attach(tag, requests=0):
            rec.charge("device::launch", launch_s=0.05)   # solo retry
    tot = rec.totals()
    assert sum(r.launch_s for r in tot.values()) == \
        pytest.approx(0.1, rel=1e-9)
    assert tot["a"].launch_s == pytest.approx(0.05)


def test_meter_context_rides_trace_adopt():
    """Attribution survives thread handoffs the way spans do: the
    context stamped on the Tracker resolves on an adopting thread."""
    from tikv_tpu.utils import tracker
    rec = Recorder()
    tr, tok = tracker.install()
    try:
        rm.bind_request("rg-x", "point")
        out = {}

        def worker():
            t = tracker.adopt(tr)
            try:
                ctx = rm.current_context()
                out["tag"] = ctx.tag if ctx else None
                rec.charge("device::launch", launch_s=0.01)
            finally:
                tracker.uninstall(t)

        th = threading.Thread(target=worker)
        th.start()
        th.join(5)
    finally:
        tracker.uninstall(tok)
    assert out["tag"] == ResourceTagFactory.tag("rg-x", "point")
    assert rec.totals()[out["tag"]].launch_s == pytest.approx(0.01)
    # the RU charged on the worker landed on the request's trace
    assert tr.ru > 0
    assert tr.labels["resource_group"] == "rg-x"


def test_arena_residency_owner_and_pin_sampling():
    """FeedArena charges bytes-resident-seconds to the owning tag via
    pin-time sampling + settle sweeps, with the region riding along."""
    from tikv_tpu.device.supervisor import FeedArena

    class Anchor:
        region_hint = 77

    base = GLOBAL_RECORDER.totals()
    arena = FeedArena()
    anchor = Anchor()
    with GLOBAL_RECORDER.attach("resident-tenant", requests=0):
        bucket = arena.bucket(anchor)
    bucket["feed"] = {"flat": ()}
    # fake accounting: pretend 2 MiB resident
    with arena._mu:
        ent = arena._entries[id(anchor)]
        ent.nbytes = 2 << 20
        arena._resident += ent.nbytes
    t0 = time.monotonic()
    time.sleep(0.05)
    arena.pin(anchor)               # pin-time sample settles rent
    dt = time.monotonic() - t0
    tot = GLOBAL_RECORDER.totals()
    got = tot["resident-tenant"].byte_seconds - \
        base.get("resident-tenant", TagRecord()).byte_seconds
    assert got >= (2 << 20) * 0.04
    assert got <= (2 << 20) * (dt + 0.05)
    regs = GLOBAL_RECORDER.region_totals()
    assert regs[77].byte_seconds >= (2 << 20) * 0.04
    # drop settles the final interval, and the window-roll sweep runs
    # through the registered residency source without error
    arena.drop(anchor)
    GLOBAL_RECORDER.roll_window(force=True)


# ------------------------------------------------------- gRPC rig (e2e)


@pytest.fixture(scope="module")
def rig():
    import jax

    from tikv_tpu.device import DeviceRunner
    from tikv_tpu.parallel import make_mesh
    from tikv_tpu.raftstore.metapb import Store
    from tikv_tpu.server import (
        Node, PdServer, RemotePdClient, TikvServer, TxnClient,
    )
    from tikv_tpu.server.status_server import StatusServer
    from tikv_tpu.testing.fixture import encode_table_row, int_table

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
    status = StatusServer("127.0.0.1:0", node=node,
                          config_controller=node.config_controller)
    status.start()
    client = TxnClient(pd_addr)
    table = int_table(2, table_id=9470)
    muts = []
    for h in range(4000):
        key, value = encode_table_row(
            table, h, {"c0": h % 13, "c1": (h * 41) % 2000 - 1000})
        muts.append(("put", key, value))
    client.txn_write(muts)
    yield {"node": node, "client": client, "table": table,
           "base_url": f"http://127.0.0.1:{status.port}",
           "device": device, "pd_client": RemotePdClient(pd_addr)}
    status.stop()
    srv.stop()
    pd_server.stop()


def _agg_dag(rig_d, ts):
    from tikv_tpu.testing.dag import DagSelect
    s = DagSelect.from_table(rig_d["table"], ["id", "c0", "c1"])
    return s.aggregate([s.col("c0")],
                       [("count_star", None), ("sum", s.col("c1"))]
                       ).build(start_ts=ts)


def _sel_dag(rig_d, ts, thr):
    from tikv_tpu.testing.dag import DagSelect
    s = DagSelect.from_table(rig_d["table"], ["id", "c0", "c1"])
    return s.where(s.col("c1") > thr).build(start_ts=ts)


def _metering(rig_d) -> dict:
    return json.load(urllib.request.urlopen(
        f"{rig_d['base_url']}/resource_metering?format=json"))


def test_e2e_attribution_covers_launch_wall(rig):
    """The acceptance bar: per-tag RU attribution covers ≥95% of the
    total measured device launch wall (flight-recorder denominator),
    with the residual as an explicit untagged entry, per-tag device
    axes live on /resource_metering, and per-region attribution."""
    c = rig["client"]
    fr = rig["device"].flight_recorder
    c.coprocessor(_agg_dag(rig, c.tso()), timeout=120,
                  resource_group="warm")       # cold compiles here
    base_tot = GLOBAL_RECORDER.totals()
    base_wall = fr.stats()["wall_s_total"]
    for i in range(4):
        r = c.coprocessor(_agg_dag(rig, c.tso()), timeout=60,
                          resource_group="fg",
                          request_source="dash")
        assert r["backend"] == "device"
    for i in range(2):
        r = c.coprocessor(_sel_dag(rig, c.tso(), 900), timeout=120,
                          resource_group="bg",
                          request_source="scan")
    wall = fr.stats()["wall_s_total"] - base_wall
    assert wall > 0
    tot = GLOBAL_RECORDER.totals()

    def delta(tag, field):
        prev = base_tot.get(tag, TagRecord())
        cur = tot.get(tag, TagRecord())
        return getattr(cur, field) - getattr(prev, field)

    fg, bg = ResourceTagFactory.tag("fg", "dash"), \
        ResourceTagFactory.tag("bg", "scan")
    assert delta(fg, "requests") == 4
    assert delta(bg, "requests") == 2
    assert delta(fg, "launch_s") > 0
    assert delta(bg, "launch_s") > 0
    assert delta(fg, "d2h_bytes") > 0
    assert delta(fg, "read_keys") == 4 * 4000
    # charged wall == measured wall (same instrument, exactly once)
    charged = sum(delta(t, "launch_s") for t in tot)
    assert charged == pytest.approx(wall, rel=1e-6)
    tagged = charged - delta(rm.UNTAGGED, "launch_s")
    assert tagged / wall >= 0.95
    # the status route shows it, coverage figure included (the
    # route's figure is CUMULATIVE since process start — under the
    # full suite other tests drive the runner tagless, so only the
    # phase-delta coverage above carries the ≥95% bar)
    body = _metering(rig)
    assert body["tags"][fg]["launch_ms"] > 0
    assert body["tags"][fg]["ru"] > 0
    assert 0.0 <= body["coverage"] <= 1.0
    # region attribution flowed through the feed anchor
    regs = GLOBAL_RECORDER.region_totals()
    assert any(isinstance(k, int) and r.launch_s > 0
               for k, r in regs.items()), regs.keys()
    # /metrics carries the RU_* families
    metrics = urllib.request.urlopen(
        f"{rig['base_url']}/metrics").read().decode()
    assert "tikv_resource_metering_ru_total" in metrics
    assert 'tenant="fg"' in metrics
    assert "tikv_resource_metering_tags" in metrics
    assert "tikv_resource_metering_request_ru_bucket" in metrics


def test_e2e_group_launch_splits_by_occupancy(rig):
    """A coalesced group's shared launch splits by occupancy share
    across member tags — and the total charged equals the wall
    measured, exactly once."""
    c, node = rig["client"], rig["node"]
    coal = node.endpoint.coalescer
    fr = rig["device"].flight_recorder
    c.coprocessor(_sel_dag(rig, c.tso(), 0), timeout=120,
                  resource_group="warm")
    coal.configure(window_ms=200.0)
    coal.idle_bypass = False
    base_tot = GLOBAL_RECORDER.totals()
    base_wall = fr.stats()["wall_s_total"]
    base_groups = coal.stats()["groups_dispatched"]
    errors = []

    def one(i):
        try:
            c.coprocessor(_sel_dag(rig, c.tso(), 100 * i), timeout=60,
                          resource_group=f"tenant{i}")
        except Exception as e:      # noqa: BLE001
            errors.append(e)

    try:
        ts = [threading.Thread(target=one, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        coal.idle_bypass = True
        coal.configure(window_ms=2.0)
    assert not errors, errors
    assert coal.stats()["groups_dispatched"] > base_groups
    wall = fr.stats()["wall_s_total"] - base_wall
    tot = GLOBAL_RECORDER.totals()

    def delta(tag):
        prev = base_tot.get(tag, TagRecord())
        return tot.get(tag, TagRecord()).launch_s - prev.launch_s

    shares = [delta(f"tenant{i}") for i in range(4)]
    assert all(s > 0 for s in shares), shares
    # not dumped on the leader: one member's share must not exceed the
    # whole group wall minus the others (even split within a group)
    charged = sum(delta(t) for t in tot)
    assert charged == pytest.approx(wall, rel=1e-6)
    grouped = [s for s in shares if s > 0]
    assert max(grouped) < charged, (shares, charged)


def test_e2e_coalesce_failpoint_retries_charge_exactly_once(rig):
    """The ISSUE's exactly-once bar: a coalesced group hits
    copr::coalesce_dispatch and members retry solo — the total charged
    wall equals the wall actually measured (the failed group launched
    nothing), each member's request counts once, to ITS tag."""
    c, node = rig["client"], rig["node"]
    coal = node.endpoint.coalescer
    fr = rig["device"].flight_recorder
    c.coprocessor(_sel_dag(rig, c.tso(), 0), timeout=120,
                  resource_group="warm")
    coal.configure(window_ms=200.0)
    coal.idle_bypass = False
    base_tot = GLOBAL_RECORDER.totals()
    base_wall = fr.stats()["wall_s_total"]
    base_solo = coal.stats()["solo_degrade"]
    errors = []

    def one(i):
        try:
            c.coprocessor(_sel_dag(rig, c.tso(), 50 + 100 * i),
                          timeout=60, resource_group=f"retry{i}")
        except Exception as e:      # noqa: BLE001
            errors.append(e)

    failpoint.cfg("copr::coalesce_dispatch", "1*return->off")
    try:
        ts = [threading.Thread(target=one, args=(i,)) for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        coal.idle_bypass = True
        coal.configure(window_ms=2.0)
        failpoint.teardown()
    assert not errors, errors
    assert coal.stats()["solo_degrade"] > base_solo
    wall = fr.stats()["wall_s_total"] - base_wall
    tot = GLOBAL_RECORDER.totals()

    def delta(tag, field="launch_s"):
        prev = base_tot.get(tag, TagRecord())
        return getattr(tot.get(tag, TagRecord()), field) - \
            getattr(prev, field)

    charged = sum(delta(t) for t in tot)
    # no double charge: total charged == total measured, and each
    # member's request counted exactly once on its own tag
    assert charged == pytest.approx(wall, rel=1e-6)
    for i in range(3):
        assert delta(f"retry{i}", "requests") == 1
        assert delta(f"retry{i}") > 0


def test_e2e_chaos_fetch_fault_charges_each_member_once(rig):
    """Chaos failover: the group's shared fetch dies mid-flight (the
    slice-death shape), members degrade/rescue per the endpoint
    contract — each member's request still counts exactly once and
    the charged launch wall still matches the measured wall."""
    c, node = rig["client"], rig["node"]
    coal = node.endpoint.coalescer
    fr = rig["device"].flight_recorder
    c.coprocessor(_sel_dag(rig, c.tso(), 0), timeout=120,
                  resource_group="warm")
    coal.configure(window_ms=200.0)
    coal.idle_bypass = False
    base_tot = GLOBAL_RECORDER.totals()
    base_wall = fr.stats()["wall_s_total"]
    errors = []

    def one(i):
        try:
            c.coprocessor(_sel_dag(rig, c.tso(), -600 + 400 * i),
                          timeout=60, resource_group=f"chaos{i}")
        except Exception as e:      # noqa: BLE001
            errors.append(e)

    failpoint.cfg("device::before_fetch", "1*return->off")
    try:
        ts = [threading.Thread(target=one, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        coal.idle_bypass = True
        coal.configure(window_ms=2.0)
        failpoint.teardown()
    assert not errors, errors
    wall = fr.stats()["wall_s_total"] - base_wall
    tot = GLOBAL_RECORDER.totals()

    def delta(tag, field="launch_s"):
        prev = base_tot.get(tag, TagRecord())
        return getattr(tot.get(tag, TagRecord()), field) - \
            getattr(prev, field)

    for i in range(2):
        assert delta(f"chaos{i}", "requests") == 1
    charged = sum(delta(t) for t in tot)
    assert charged == pytest.approx(wall, rel=1e-6)


def test_e2e_trace_and_slow_log_answer_who_paid(rig, caplog):
    """Satellite: /debug/trace/<id> and the slow-query line carry
    resource_group + RU charged."""
    c, node = rig["client"], rig["node"]
    cc = node.config.coprocessor
    old = cc.slow_log_threshold_ms
    try:
        cc.slow_log_threshold_ms = 0.001
        with caplog.at_level(logging.WARNING,
                             logger="tikv_tpu.slow_query"):
            r = c.coprocessor(_agg_dag(rig, c.tso()), timeout=60,
                              resource_group="payer",
                              request_source="audit")
    finally:
        cc.slow_log_threshold_ms = old
    doc = json.load(urllib.request.urlopen(
        f"{rig['base_url']}/debug/trace/{r['trace_id']}"))
    assert doc["labels"]["resource_group"] == "payer"
    assert float(doc["labels"]["ru"]) > 0
    # the wire response's time_detail carries the same labels
    assert r["time_detail"]["labels"]["resource_group"] == "payer"
    recs = [x for x in caplog.records
            if x.name == "tikv_tpu.slow_query" and
            r["trace_id"] in x.getMessage()]
    assert recs, "slow-query line did not fire"
    msg = recs[0].getMessage()
    assert "resource_group=payer" in msg
    assert "ru=" in msg


def test_e2e_hot_regions_visible_at_pd(rig):
    """The windowed top-k hot-region/hot-tenant report rides the store
    heartbeat to PD, where hot_regions() merges it cluster-wide (the
    RemotePdClient RPC included)."""
    c, node = rig["client"], rig["node"]
    ctl = node.config_controller
    applied = ctl.update({"resource-metering.window-s": 0.2,
                          "resource-metering.report-interval-s": 0.0})
    assert applied["resource_metering.window_s"] == 0.2
    try:
        for i in range(3):
            c.coprocessor(_agg_dag(rig, c.tso()), timeout=60,
                          resource_group="hot-tenant")
        deadline = time.monotonic() + 15
        got = {}
        while time.monotonic() < deadline:
            GLOBAL_RECORDER.roll_window()
            got = rig["pd_client"].hot_regions(topk=4)
            # the first report PD merges can be the window that closed
            # under the tests before this one: their tags fill the top
            # four, so wait for a window that holds this tenant's reads
            if got.get("regions") and any(
                    e["tag"] == "hot-tenant"
                    for e in got.get("tenants") or ()):
                break
            c.coprocessor(_agg_dag(rig, c.tso()), timeout=60,
                          resource_group="hot-tenant")
            time.sleep(0.2)
        assert got.get("regions"), got
        assert got.get("tenants"), got
        top = got["regions"][0]
        assert top["ru"] > 0 and top["stores"], top
        assert any(e["tag"] == "hot-tenant" for e in got["tenants"])
        # the same report is on /resource_metering and in /health
        body = _metering(rig)
        assert body["window"].get("top_regions") is not None
        health = json.load(urllib.request.urlopen(
            f"{rig['base_url']}/health"))
        roll = health["resource_metering"]
        assert roll["window_s"] == 0.2
        assert "weights" in roll["model"]
        assert "last_report" in roll
    finally:
        ctl.update({"resource-metering.window-s": 5.0,
                    "resource-metering.report-interval-s": 5.0})


def test_e2e_metering_knobs_online_updatable(rig):
    """Satellite: window_s/topk/max_resource_groups/report_interval +
    every RU weight flow through POST /config end to end."""
    base = rig["base_url"]
    body = json.dumps({
        "resource-metering.topk": 3,
        "resource-metering.max-resource-groups": 32,
        "resource-metering.ru-per-d2h-mb": 64.0,
    }).encode()
    req = urllib.request.Request(f"{base}/config", data=body,
                                 method="POST")
    resp = json.load(urllib.request.urlopen(req, timeout=10))
    try:
        assert resp["applied"]["resource_metering.topk"] == 3
        assert GLOBAL_RECORDER.topk == 3
        assert GLOBAL_RECORDER.max_tags == 32
        assert GLOBAL_MODEL.weights()["ru_per_d2h_mb"] == 64.0
        health = json.load(urllib.request.urlopen(f"{base}/health"))
        roll = health["resource_metering"]
        assert roll["topk"] == 3
        assert roll["model"]["weights"]["ru_per_d2h_mb"] == 64.0
        # non-online fields still reject
        bad = urllib.request.Request(
            f"{base}/config",
            data=json.dumps({"resource-metering.bogus": 1}).encode(),
            method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=10)
        assert ei.value.code == 400
    finally:
        req = urllib.request.Request(
            f"{base}/config",
            data=json.dumps({
                "resource-metering.topk": 8,
                "resource-metering.max-resource-groups": 64,
                "resource-metering.ru-per-d2h-mb": 16.0,
            }).encode(), method="POST")
        urllib.request.urlopen(req, timeout=10)
