"""Multi-tenant resource control — enforcement of the PR 13 RU ledger
(tikv_tpu/resource_control.py).

The ISSUE's acceptance bars live here: token-bucket / DWFQ units
(shares sum-exact, burst caps, work-conserving slack), coalescer
fairness under a flooding group (an fg member never waits past its
deadline reserve, a throttled member is deferred — never dropped,
never late), tenant-aware arena eviction protecting the under-share
tenant's anchor (incl. under a ``device::hbm_oom`` squeeze), RU-priced
read-pool shed with a group-derived ``retry_after_ms`` and the group
name on the ``ServerIsBusy``, online share re-config without restart,
the ``copr::rc_throttle`` failpoint + ``tenant_storm`` nemesis +
``check_fg_latency_bounded`` / ``check_bg_not_starved`` invariants,
and a gRPC e2e two-tenant throttle run (zero late acks, bg
progresses).  The metering follow-up rides along: a deferred
coalescer member's request-base RU charges exactly once and its
MeterContext survives the deferral re-queue.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tikv_tpu import resource_metering as rm
from tikv_tpu.resource_control import (
    GLOBAL_CONTROLLER,
    GroupState,
    ResourceController,
    validate_group_specs,
)
from tikv_tpu.resource_metering import (
    GLOBAL_RECORDER,
    ResourceTagFactory,
    TagRecord,
)
from tikv_tpu.utils import failpoint


@pytest.fixture(autouse=True)
def _rc_teardown():
    """The controller is process-global: one test's shares/debts must
    not leak into the next (or into the rest of tier-1)."""
    yield
    failpoint.teardown()
    GLOBAL_CONTROLLER.reset()


class _FakeMember:
    def __init__(self, tag, deadline_at=None):
        self.tag = tag
        self.deadline_at = deadline_at
        self.rc_defers = 0

    def __repr__(self):
        return f"<{self.tag}>"


# ------------------------------------------------- token-bucket units


def test_token_bucket_refill_burst_and_debt():
    g = GroupState("g", share=100.0, burst=50.0)
    assert g.tokens == 50.0                 # starts at the burst cap
    now = time.monotonic()
    g.debit(80.0, now)
    assert g.tokens == pytest.approx(-30.0)
    assert g.debt(now) == pytest.approx(30.0, abs=1e-3)
    # refill at share, capped at burst
    g._refill(now + 0.5)
    assert g.tokens == pytest.approx(20.0, abs=1.0)
    g._refill(now + 100.0)
    assert g.tokens == 50.0                 # burst cap holds
    # debt floor: a slack binge cannot owe more than DEBT_BURSTS caps
    g.debit(1e9, now + 100.0)
    assert g.tokens == -GroupState.DEBT_BURSTS * 50.0
    # refill_ms derives from the share rate
    ms = g.refill_ms(0.0, now + 100.0)
    assert ms == pytest.approx(
        1000.0 * GroupState.DEBT_BURSTS * 50.0 / 100.0, rel=0.05)
    # burst=0 means 2x share
    assert GroupState("h", share=10.0).burst_cap() == 20.0


def test_charge_stream_debits_the_paying_group():
    """GLOBAL_RECORDER charges drain GLOBAL_CONTROLLER buckets — the
    measurement half and the enforcement half share one ledger."""
    GLOBAL_CONTROLLER.configure(
        enabled=True, groups={"payer": {"share": 100.0}})
    with GLOBAL_RECORDER.attach("payer|src", requests=0):
        GLOBAL_RECORDER.charge("device::launch", launch_s=3.0)
    # 3s of launch wall at 333.3 RU/s ≈ 1000 RU ≫ the 200-RU burst
    assert GLOBAL_CONTROLLER.debt("payer") > 100.0
    st = GLOBAL_CONTROLLER.stats()["groups"]["payer"]
    assert st["consumed_ru"] > 900.0
    assert st["ru_rate_ewma"] > 0


def test_disabled_controller_is_inert():
    GLOBAL_CONTROLLER.reset()
    with GLOBAL_RECORDER.attach("anyone", requests=0):
        GLOBAL_RECORDER.charge("device::launch", launch_s=10.0)
    assert GLOBAL_CONTROLLER.debt("anyone") == 0.0
    ok, hint, _ = GLOBAL_CONTROLLER.admit("anyone", pool_busy=True)
    assert ok and hint == 0
    ms = [_FakeMember("a|x"), _FakeMember("b|x")]
    sel, deferred = GLOBAL_CONTROLLER.select_stacked(
        ms, 1, window_s=0.1)
    assert sel == ms and deferred == []
    # disabled standing: every tenant's HBM limit is infinite
    st = GLOBAL_CONTROLLER.hbm_standing({"anyone": 1 << 30}, 1 << 20)
    assert st["anyone"][0] == float("inf")


# ------------------------------------------------------- DWFQ units


def test_dwfq_shares_sum_exact():
    """Two always-backlogged solvent groups split lanes exactly by
    share over many windows (±1 rounding)."""
    rc = ResourceController(enabled=True)
    rc.configure(groups={"a": {"share": 300.0}, "b": {"share": 100.0}})
    counts = {"a": 0, "b": 0}
    for _ in range(100):
        ms = [_FakeMember("a|x") for _ in range(8)] + \
            [_FakeMember("b|x") for _ in range(8)]
        sel, _ = rc.select_stacked(ms, 4, window_s=0.1)
        for m in sel:
            counts[m.tag[0]] += 1
    total = counts["a"] + counts["b"]
    assert total == 400
    assert abs(counts["a"] - 300) <= 1, counts
    assert abs(counts["b"] - 100) <= 1, counts


def test_dwfq_throttled_group_capped_at_quota_never_starved():
    rc = ResourceController(enabled=True)
    rc.configure(groups={"fg": {"share": 1000.0, "priority": "high"},
                         "bg": {"share": 100.0, "priority": "low"}})
    # drive bg into debt through the charge stream
    now = time.monotonic()
    with rc._mu:
        rc._group_locked("bg").debit(1000.0, now)
    ms = [_FakeMember("bg|s") for _ in range(6)] + [_FakeMember("fg|p")]
    sel, deferred = rc.select_stacked(ms, 8, window_s=0.2,
                                      reserve_s=0.05)
    tags = [m.tag for m in sel]
    # fg always rides; bg capped at its share-proportional quota (>=1
    # — throttled, not starved); the surplus is deferred, not dropped
    assert "fg|p" in tags
    assert tags.count("bg|s") == 1
    assert len(deferred) == 5
    assert all(m.rc_defers == 1 for m in deferred)
    assert rc.stats()["deferrals"] == 5
    # deadline-urgent members bypass fairness entirely
    urgent = _FakeMember("bg|s", deadline_at=time.monotonic() + 0.1)
    sel, deferred = rc.select_stacked(
        [urgent] + [_FakeMember("fg|p")], 8,
        window_s=0.2, reserve_s=0.05)
    assert urgent in sel
    # a member deferred MAX_DEFERS times is force-selected
    tired = _FakeMember("bg|s")
    tired.rc_defers = ResourceController.MAX_DEFERS
    sel, deferred = rc.select_stacked(
        [tired] + [_FakeMember("fg|p")], 8,
        window_s=0.2, reserve_s=0.05)
    assert tired in sel


def test_dwfq_work_conserving_slack():
    """A single-tenant group — even one deep in debt — takes every
    lane: with nobody to protect, deferral would only waste the
    dispatch (work-conserving)."""
    rc = ResourceController(enabled=True)
    rc.configure(groups={"bg": {"share": 10.0, "priority": "low"}})
    with rc._mu:
        rc._group_locked("bg").debit(1e6, time.monotonic())
    ms = [_FakeMember("bg|s") for _ in range(6)]
    sel, deferred = rc.select_stacked(ms, 8, window_s=0.2)
    assert len(sel) == 6 and not deferred
    # and a mixed group where EVERY tenant is solvent dispatches
    # whole — fairness caps nobody who paid
    rc2 = ResourceController(enabled=True)
    rc2.configure(groups={"bg": {"share": 10.0}, "fg": {"share": 1.0}})
    ms = [_FakeMember("bg|s") for _ in range(4)] + \
        [_FakeMember("fg|p") for _ in range(3)]
    sel, deferred = rc2.select_stacked(ms, 8, window_s=0.2)
    assert len(sel) == 7 and not deferred


def test_configured_group_starts_with_its_own_burst():
    """Regression: a freshly configured group opens with ITS full
    burst in hand, not the default cap — a big-burst analytics group
    must be able to absorb its configured backlog from request one."""
    rc = ResourceController(enabled=True)
    rc.configure(groups={"analytics": {"share": 500.0,
                                       "burst": 10000.0}})
    st = rc.stats()["groups"]["analytics"]
    assert st["tokens"] == 10000.0


def test_solvent_group_never_sheds_even_above_rate():
    """Regression: burst exists to absorb above-share spikes — a
    group with tokens in hand is never shed no matter how fast its
    recent RU rate runs (only DEBT sheds)."""
    rc = ResourceController(enabled=True)
    rc.configure(groups={"bg": {"share": 500.0, "burst": 10000.0},
                         "fg": {"share": 1000.0}})
    now = time.monotonic()
    with rc._mu:
        rc._group_locked("bg").debit(2000.0, now)   # rate ~1000 > 500
        rc._group_locked("fg").debit(100.0, now)    # second active
    ok, _, _ = rc.admit("bg", pool_busy=True)
    assert ok       # tokens ~8000 > 0: solvent, within burst
    with rc._mu:
        rc._group_locked("bg").debit(9000.0, now)   # now in debt
    ok, _, reason = rc.admit("bg", pool_busy=True)
    assert not ok
    assert "-" not in reason.split("RU debt")[0], reason


def test_single_tenant_lane_bound_on_merged_group():
    """Regression: a deferral-merged single-tenant group that outgrew
    the lane capacity dispatches at most ``capacity`` members — the
    max_group lane bound survives enforcement — but deadline-urgent
    and MAX_DEFERS-exhausted members at the BACK of the queue are
    exempt from the trim (a re-parked member must never be starved
    behind fresh arrivals window after window, nor ack late)."""
    rc = ResourceController(enabled=True)
    rc.configure(groups={"bg": {"share": 10.0}})
    ms = [_FakeMember("bg|s") for _ in range(14)]
    sel, deferred = rc.select_stacked(ms, 8, window_s=0.2)
    assert len(sel) == 8 and len(deferred) == 6
    assert all(m.rc_defers == 1 for m in deferred)
    # urgency overrides the trim even at the tail of the queue
    tired = _FakeMember("bg|s")
    tired.rc_defers = ResourceController.MAX_DEFERS
    tight = _FakeMember("bg|s", deadline_at=time.monotonic() + 0.1)
    ms = [_FakeMember("bg|s") for _ in range(10)] + [tired, tight]
    sel, deferred = rc.select_stacked(ms, 8, window_s=0.2,
                                      reserve_s=0.05)
    assert tired in sel and tight in sel
    assert len(deferred) == 4


def test_rc_throttle_named_action_not_burned_by_other_groups():
    """Regression: a count-limited ``1*return(bg)`` must not be
    consumed by some other group's request reaching the gate first —
    the target filter runs on a non-firing peek."""
    rc = ResourceController()
    failpoint.cfg("copr::rc_throttle", "1*return(bg)->off")
    for _ in range(5):      # fg traffic must not burn the action
        ok, _, _ = rc.admit("fg", pool_busy=True)
        assert ok
    ok, _, reason = rc.admit("bg", pool_busy=False)
    assert not ok and "force-throttled" in reason
    # the single shot is now spent; bg flows again
    ok, _, _ = rc.admit("bg", pool_busy=False)
    assert ok


# ------------------------------------------------- read-pool admission


def test_admit_ru_priced_shed_with_group_derived_hint():
    rc = ResourceController(enabled=True)
    rc.configure(groups={"bg": {"share": 100.0, "priority": "low"},
                         "fg": {"share": 1000.0, "priority": "high"}})
    now = time.monotonic()
    with rc._mu:
        rc._group_locked("bg").debit(300.0, now)     # 100 RU of debt
    ok, hint, reason = rc.admit("bg", pool_busy=True)
    assert not ok
    assert "bg" in reason and "over budget" in reason
    # the hint is the BUCKET's refill time for the debt (~1s at 100
    # RU/s), not a queue-depth figure
    assert 500 <= hint <= 2500, hint
    # work-conserving: no pool contention and no second ACTIVE group
    # (only bg has a live RU rate) -> even the indebted group admits
    ok, _, _ = rc.admit("bg", pool_busy=False)
    assert ok
    # high-priority groups never shed here, debt or not
    with rc._mu:
        rc._group_locked("fg").debit(1e6, now)
    ok, _, _ = rc.admit("fg", pool_busy=True)
    assert ok
    # with fg now active too (two live groups = contention for the
    # serialized device stream), bg sheds even on an idle pool
    ok, _, _ = rc.admit("bg", pool_busy=False)
    assert not ok
    assert rc.stats()["sheds"] >= 2


def test_read_pool_shed_carries_group_and_hint():
    from tikv_tpu.server.read_pool import ReadPool, ServerIsBusy
    from tikv_tpu.server.wire import enc_error
    GLOBAL_CONTROLLER.configure(
        enabled=True,
        groups={"bg": {"share": 50.0, "priority": "low"}})
    with GLOBAL_RECORDER.attach("bg|scan", requests=0):
        GLOBAL_RECORDER.charge("device::launch", launch_s=3.0)
    # a second ACTIVE group = contention (the scarce resources are
    # device-side; free pool slots don't mean free capacity)
    with GLOBAL_RECORDER.attach("fg|point", requests=0):
        GLOBAL_RECORDER.charge("read_pool::host", host_s=0.05)
    pool = ReadPool(max_concurrency=1)
    with pytest.raises(ServerIsBusy) as ei:
        pool.run(lambda: "x", resource_group="bg")
    e = ei.value
    assert e.resource_group == "bg"
    assert e.retry_after_ms >= 1
    err = enc_error(e)
    assert err["kind"] == "server_is_busy"
    assert err["resource_group"] == "bg"
    assert err["retry_after_ms"] == e.retry_after_ms
    assert pool.stats()["rc_shed"] == 1
    # an unthrottled group flows through the same pool untouched
    assert pool.run(lambda: "y", resource_group="fg") == "y"


def test_rc_throttle_failpoint_forces_named_group():
    from tikv_tpu.server.read_pool import ReadPool, ServerIsBusy
    pool = ReadPool(max_concurrency=4)
    failpoint.cfg("copr::rc_throttle", "return(bg)")
    # fires even with the controller DISABLED — fault injection must
    # not need a config edit
    with pytest.raises(ServerIsBusy) as ei:
        pool.run(lambda: "x", resource_group="bg")
    assert "force-throttled" in str(ei.value)
    assert ei.value.resource_group == "bg"
    assert pool.run(lambda: "y", resource_group="fg") == "y"
    failpoint.remove("copr::rc_throttle")
    # bare return = every group
    failpoint.cfg("copr::rc_throttle", "return")
    with pytest.raises(ServerIsBusy):
        pool.run(lambda: "x", resource_group="fg")
    assert GLOBAL_CONTROLLER.stats()["forced_throttles"] >= 2


# ---------------------------------------------- config + online update


def test_group_spec_vocabulary_validation():
    validate_group_specs({"ok": {"share": 1.0, "burst": 0.0,
                                 "priority": "low"}})
    with pytest.raises(ValueError, match="unknown key"):
        validate_group_specs({"g": {"shares": 1.0}})
    with pytest.raises(ValueError, match="share must be"):
        validate_group_specs({"g": {"share": -1.0}})
    with pytest.raises(ValueError, match="share must be"):
        validate_group_specs({"g": {"share": 0}})
    with pytest.raises(ValueError, match="burst must be"):
        validate_group_specs({"g": {"burst": -1.0}})
    with pytest.raises(ValueError, match="priority must be"):
        validate_group_specs({"g": {"priority": "urgent"}})
    with pytest.raises(ValueError, match="must be a table"):
        validate_group_specs({"g": 5})
    with pytest.raises(ValueError):
        validate_group_specs("nope")


def test_config_tree_validates_resource_control():
    from tikv_tpu.config import ConfigController, TikvConfig
    cfg = TikvConfig.from_dict({"resource-control": {
        "enabled": True, "default-share": 250.0,
        "groups": {"oltp": {"share": 4000.0, "priority": "high"}}}})
    assert cfg.resource_control.enabled
    assert cfg.resource_control.groups["oltp"]["share"] == 4000.0
    with pytest.raises(ValueError, match="unknown key"):
        TikvConfig.from_dict({"resource-control": {
            "groups": {"g": {"sahre": 1.0}}}})
    with pytest.raises(ValueError, match="default-share"):
        TikvConfig.from_dict({"resource-control": {
            "default-share": -1.0}})
    # online update routes through _ONLINE_FIELDS and re-validates
    ctl = ConfigController(cfg)
    applied = ctl.update({"resource-control.groups":
                          {"bg": {"share": 10.0}}})
    assert applied["resource_control.groups"]["bg"]["share"] == 10.0
    with pytest.raises(ValueError):
        ctl.update({"resource-control.groups": {"bg": {"share": -3}}})


def test_online_share_reconfig_takes_effect_without_restart():
    GLOBAL_CONTROLLER.configure(
        enabled=True, groups={"bg": {"share": 1000.0}})
    now = time.monotonic()
    with GLOBAL_CONTROLLER._mu:
        g = GLOBAL_CONTROLLER._group_locked("bg")
        assert g.burst_cap() == 2000.0
    # a live share cut re-clamps the bucket immediately
    GLOBAL_CONTROLLER.configure(groups={"bg": {"share": 10.0,
                                               "priority": "low"}})
    with GLOBAL_CONTROLLER._mu:
        g = GLOBAL_CONTROLLER._group_locked("bg")
        assert g.share == 10.0
        assert g.tokens <= g.burst_cap() == 20.0
    # de-configuring reverts to defaults but keeps history
    g.debit(100.0, now)
    GLOBAL_CONTROLLER.configure(groups={})
    st = GLOBAL_CONTROLLER.stats()["groups"]["bg"]
    assert st["share"] == GLOBAL_CONTROLLER.default_share
    assert not st["configured"]
    assert st["consumed_ru"] > 0        # counters survive


def test_group_map_bounded_by_overflow_fold():
    rc = ResourceController(enabled=True)
    for i in range(ResourceController.MAX_GROUPS + 32):
        rc.on_charge("device::launch", f"tenant-{i}|x", 1.0)
    assert len(rc.stats()["groups"]) <= \
        ResourceController.MAX_GROUPS + 1
    assert ResourceController.OVERFLOW in rc.stats()["groups"]


# ------------------------------------------- tenant-aware arena eviction


class _Anchor:
    def __init__(self, region=None):
        if region is not None:
            self.region_hint = region


def _arena_with_tenants(fg_mb=1, bg_mb=3):
    """An arena holding one fg-owned and one bg-owned entry with REAL
    plane bytes; the fg entry is COLDER (plain LFU would evict it
    first) so protection is observable against the baseline."""
    from tikv_tpu.device.supervisor import FeedArena
    arena = FeedArena()
    fg_anchor, bg_anchor = _Anchor(1), _Anchor(2)
    with GLOBAL_RECORDER.attach("fg|point", requests=0):
        arena.bucket(fg_anchor)["feed"] = {
            "flat": (np.zeros((fg_mb << 20) // 8, np.int64),)}
    arena.admit(fg_anchor)
    with GLOBAL_RECORDER.attach("bg|scan", requests=0):
        b = arena.bucket(bg_anchor)
    b["feed"] = {"flat": (np.zeros((bg_mb << 20) // 8, np.int64),)}
    arena.admit(bg_anchor)
    # make bg HOTTER than fg: under plain LFU fg is the victim
    for _ in range(5):
        with GLOBAL_RECORDER.attach("bg|scan", requests=0):
            arena.bucket(bg_anchor)
    return arena, fg_anchor, bg_anchor


def test_plain_lfu_would_evict_the_cold_fg_anchor():
    arena, fg_anchor, bg_anchor = _arena_with_tenants()
    arena.budget_bytes = int(3.5 * (1 << 20))
    arena.enforce()
    assert arena.bucket(fg_anchor, create=False) is None     # evicted
    assert arena.bucket(bg_anchor, create=False) is not None


def test_tenant_aware_eviction_protects_under_share_anchor():
    """With resource control on, the over-share background scanner's
    (hotter!) feed evicts first and the under-share latency tenant's
    anchor survives — up to its share, not beyond."""
    GLOBAL_CONTROLLER.configure(
        enabled=True,
        groups={"fg": {"share": 1000.0, "priority": "high"},
                "bg": {"share": 100.0, "priority": "low"}})
    arena, fg_anchor, bg_anchor = _arena_with_tenants()
    arena.budget_bytes = int(3.5 * (1 << 20))
    evicted = arena.enforce()
    assert evicted == 1
    assert arena.bucket(bg_anchor, create=False) is None     # bg died
    assert arena.bucket(fg_anchor, create=False) is not None  # fg kept
    st = GLOBAL_CONTROLLER.stats()
    assert st["groups"]["bg"]["evictions"] == 1
    assert st["protected_bytes"] >= (1 << 20)
    assert st["protect_events"] >= 1
    assert arena.residency_by_tenant() == {"fg": 1 << 20}


def test_tenant_aware_eviction_under_hbm_squeeze_failpoint():
    """The hbm_squeeze chaos shape: a ``device::hbm_oom`` budget
    squeeze fires through admit() — the tenant bias still picks the
    over-share victim, protecting the fg anchor."""
    GLOBAL_CONTROLLER.configure(
        enabled=True,
        groups={"fg": {"share": 1000.0, "priority": "high"},
                "bg": {"share": 100.0, "priority": "low"}})
    arena, fg_anchor, bg_anchor = _arena_with_tenants()
    failpoint.cfg("device::hbm_oom", f"return({int(3.5 * (1 << 20))})")
    try:
        with GLOBAL_RECORDER.attach("fg|point", requests=0):
            arena.bucket(fg_anchor)
        assert arena.admit(fg_anchor)
    finally:
        failpoint.remove("device::hbm_oom")
    assert arena.bucket(fg_anchor, create=False) is not None
    assert arena.bucket(bg_anchor, create=False) is None


def test_over_share_tenant_still_uses_slack():
    """Work-conserving: with no budget pressure the over-share tenant
    keeps every byte — the bias engages only when someone needs the
    capacity."""
    GLOBAL_CONTROLLER.configure(
        enabled=True,
        groups={"fg": {"share": 1000.0}, "bg": {"share": 10.0}})
    arena, fg_anchor, bg_anchor = _arena_with_tenants()
    arena.budget_bytes = 1 << 30
    assert arena.enforce() == 0
    assert arena.bucket(bg_anchor, create=False) is not None


# --------------------------------------- chaos: storm + invariants


def test_tenant_storm_nemesis_floods_the_ledger():
    from tikv_tpu.chaos import (
        TENANT_FAULT_KINDS,
        Nemesis,
        generate_schedule,
    )
    GLOBAL_CONTROLLER.configure(
        enabled=True, groups={"fg": {"share": 1000.0,
                                     "priority": "high"}})
    base = GLOBAL_RECORDER.totals().get(
        ResourceTagFactory.tag("storm", "storm"), TagRecord()).ru
    sched = generate_schedule(7, 4, kinds=TENANT_FAULT_KINDS)
    assert all(f.kind == "tenant_storm" for f in sched)
    nem = Nemesis(cluster=None, seed=7)
    nem.apply(sched[0])
    nem.heal()
    # the storm group's ledger took the flood...
    got = GLOBAL_RECORDER.totals()[
        ResourceTagFactory.tag("storm", "storm")].ru - base
    assert got >= 1000.0
    # ...its bucket is deep in debt, and (with the fg group active)
    # the admission gate throttles it while fg flows
    with GLOBAL_RECORDER.attach("fg|point", requests=0):
        GLOBAL_RECORDER.charge("read_pool::host", host_s=0.02)
    assert GLOBAL_CONTROLLER.debt("storm") > 100.0
    ok, hint, _ = GLOBAL_CONTROLLER.admit("storm", pool_busy=True)
    assert not ok and hint > 0
    ok, _, _ = GLOBAL_CONTROLLER.admit("fg", pool_busy=True)
    assert ok


def test_fg_bg_invariants():
    from tikv_tpu.chaos import (
        InvariantViolation,
        check_bg_not_starved,
        check_fg_latency_bounded,
    )
    fg_ok = [{"ok": True, "elapsed": 0.011} for _ in range(50)]
    check_fg_latency_bounded(fg_ok, baseline_p99_s=0.010,
                             factor=1.5, slack_s=0.01)
    with pytest.raises(InvariantViolation, match="exceeds"):
        check_fg_latency_bounded(
            [{"ok": True, "elapsed": 0.200}] * 50,
            baseline_p99_s=0.010, factor=1.5, slack_s=0.01)
    with pytest.raises(InvariantViolation, match="starved outright"):
        check_fg_latency_bounded([{"ok": False, "elapsed": 1.0}], 0.01)
    check_bg_not_starved([{"ok": True}] * 3 + [{"ok": False}] * 7)
    with pytest.raises(InvariantViolation, match="starvation"):
        check_bg_not_starved([{"ok": False}] * 10)
    with pytest.raises(InvariantViolation, match="starvation"):
        check_bg_not_starved([{"ok": True}] + [{"ok": False}] * 9,
                             min_served_fraction=0.2)


# --------------------------------- coalescer fairness (device rig)


@pytest.fixture(scope="module")
def runner():
    import jax

    from tikv_tpu.device import DeviceRunner
    from tikv_tpu.parallel import make_mesh
    return DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                        chunk_rows=1 << 12)


def _make_snapshot(n=12_000, seed=3):
    from tikv_tpu.datatype import Column, EvalType, FieldType
    from tikv_tpu.executors.columnar import ColumnarTable
    from tikv_tpu.testing.fixture import Table, TableColumn
    rng = np.random.default_rng(seed)
    table = Table(8900 + seed, (
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
                    np.ones(n, np.bool_)),
    }
    snap = ColumnarTable.from_arrays(table,
                                     np.arange(n, dtype=np.int64),
                                     named)
    return table, snap


def _sel_dag(table, thr):
    from tikv_tpu.testing.dag import DagSelect
    s = DagSelect.from_table(table, ["id", "k", "v"])
    return s.where(s.col("v") > int(thr)).build()


def test_coalescer_fairness_flood_defers_throttled_never_late(runner):
    """The enforcement-site-1 e2e: a throttled bg group floods one
    stacked batch class while an fg member with a real deadline rides
    the same window.  The fg member dispatches in the FIRST window
    (never waits past its deadline reserve), the bg surplus defers to
    later windows — every answer correct, none late, none dropped —
    and the metering follow-up holds: each deferred member's
    request-base RU charged exactly once, its MeterContext surviving
    the re-queue (its launch charges land on ITS tag)."""
    from tikv_tpu.copr.endpoint import CopRequest, Endpoint, \
        REQ_TYPE_DAG
    from tikv_tpu.executors.runner import BatchExecutorsRunner
    from tikv_tpu.server.coalescer import RequestCoalescer
    from tikv_tpu.utils import deadline as dl_mod
    table, snap = _make_snapshot()
    coal = RequestCoalescer(runner, window_ms=150.0, max_group=8)
    coal.idle_bypass = False
    ep = Endpoint(lambda req: snap, device_runner=runner,
                  device_row_threshold=1, coalescer=coal)
    try:
        # warm the stacked class OUTSIDE the metering bracket
        warm = ep.handle(CopRequest(REQ_TYPE_DAG, _sel_dag(table, 0),
                                    resource_group="warm"))
        assert warm.backend == "device"
        # the warm launch's wall is a compile: folded into the router's
        # launch EWMA it prices a solo dispatch behind six parked bg
        # members above fg's budget, and fg then goes solo instead of
        # riding the window whenever its thread arrives last
        coal.router.launch_ewma = coal.router.LAUNCH_SEED_S
        GLOBAL_CONTROLLER.configure(
            enabled=True,
            groups={"fg": {"share": 1000.0, "priority": "high"},
                    "bg": {"share": 50.0, "priority": "low"}})
        with GLOBAL_RECORDER.attach("bg|flood", requests=0):
            GLOBAL_RECORDER.charge("device::launch", launch_s=3.0)
        base_tot = GLOBAL_RECORDER.totals()
        fr = runner.flight_recorder
        base_wall = fr.stats()["wall_s_total"]
        results: dict = {}
        errors: list = []

        def one(i, group, thr, deadline_ms=None):
            try:
                tok = None
                if deadline_ms is not None:
                    dl = dl_mod.Deadline.after_ms(deadline_ms)
                    tok = dl_mod.install(dl)
                try:
                    t0 = time.perf_counter()
                    r = ep.handle(CopRequest(
                        REQ_TYPE_DAG, _sel_dag(table, thr),
                        resource_group=group,
                        request_source="flood"))
                    results[i] = (r, time.perf_counter() - t0)
                finally:
                    if tok is not None:
                        dl_mod.uninstall(tok)
            except Exception as e:      # noqa: BLE001
                errors.append((i, e))

        threads = [threading.Thread(target=one,
                                    args=(i, "bg", 100 + 10 * i))
                   for i in range(6)]
        threads.append(threading.Thread(
            target=one, args=(99, "fg", 500, 1500)))
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errors, errors
        assert len(results) == 7
        # fg answered inside its budget (never parked past the
        # deadline reserve) and every answer matches the host pipeline
        fg_resp, fg_elapsed = results[99]
        assert fg_elapsed < 1.5, fg_elapsed
        for i, (resp, _el) in results.items():
            thr = 500 if i == 99 else 100 + 10 * i
            want = BatchExecutorsRunner(
                _sel_dag(table, thr), snap).handle_request()
            assert resp.result.batch.num_rows == want.batch.num_rows
        # the flood actually exercised the deferral path
        assert coal.stats()["rc_deferrals"] >= 1
        assert GLOBAL_CONTROLLER.stats()["deferrals"] >= 1
        # metering follow-up: exactly-once across the deferral
        # re-queue — each tag's request base charged once per request,
        # the charged launch wall equal to the measured wall, and the
        # deferred members' charges landing on THEIR tag (the
        # MeterContext survived the re-queue)
        tot = GLOBAL_RECORDER.totals()

        def delta(tag, field):
            prev = base_tot.get(tag, TagRecord())
            return getattr(tot.get(tag, TagRecord()), field) - \
                getattr(prev, field)

        assert delta("bg|flood", "requests") == 6
        assert delta("fg|flood", "requests") == 1
        assert delta("bg|flood", "launch_s") > 0
        assert delta("fg|flood", "launch_s") > 0
        wall = fr.stats()["wall_s_total"] - base_wall
        charged = sum(delta(t, "launch_s") for t in tot)
        assert charged == pytest.approx(wall, rel=1e-6)
    finally:
        ep.close()


# --------------------------------------------- gRPC e2e (device rig)


@pytest.fixture(scope="module")
def rig(runner):
    from tikv_tpu.raftstore.metapb import Store
    from tikv_tpu.server import (
        Node, PdServer, RemotePdClient, TikvServer, TxnClient,
    )
    from tikv_tpu.server.status_server import StatusServer
    from tikv_tpu.testing.fixture import encode_table_row, int_table

    pd_server = PdServer("127.0.0.1:0")
    pd_server.start()
    pd_addr = f"127.0.0.1:{pd_server.port}"
    node = Node("127.0.0.1:0", RemotePdClient(pd_addr),
                device_runner=runner, device_row_threshold=128)
    srv = TikvServer(node)
    node.addr = f"127.0.0.1:{srv.port}"
    node.pd.put_store(Store(node.store_id, node.addr))
    srv.start()
    status = StatusServer("127.0.0.1:0", node=node,
                          config_controller=node.config_controller)
    status.start()
    client = TxnClient(pd_addr)
    table = int_table(2, table_id=9770)
    muts = []
    for h in range(4000):
        key, value = encode_table_row(
            table, h, {"c0": h % 13, "c1": (h * 37) % 2000 - 1000})
        muts.append(("put", key, value))
    client.txn_write(muts)
    yield {"node": node, "client": client, "table": table,
           "base_url": f"http://127.0.0.1:{status.port}"}
    GLOBAL_CONTROLLER.reset()
    status.stop()
    srv.stop()
    pd_server.stop()


def _fg_dag(rig_d, ts, thr):
    from tikv_tpu.testing.dag import DagSelect
    s = DagSelect.from_table(rig_d["table"], ["id", "c0", "c1"])
    return s.where(s.col("c1") > thr).build(start_ts=ts)


def _bg_dag(rig_d, ts):
    from tikv_tpu.testing.dag import DagSelect
    s = DagSelect.from_table(rig_d["table"], ["id", "c0", "c1"])
    return s.aggregate([s.col("c0")],
                       [("count_star", None), ("sum", s.col("c1"))]
                       ).build(start_ts=ts)


def test_e2e_two_tenant_throttle(rig):
    """The gRPC acceptance run: resource control enabled ONLINE (POST
    /config), a bg scan flood against fg point selections — the bg
    group sheds with group-named busy responses and retries on the
    hint (throttled), every bg request eventually completes (not
    starved), fg takes zero errors, zero late acks anywhere, and the
    /resource_control + /health + /metrics surfaces show it."""
    from tikv_tpu.server.wire import RemoteError
    c, node = rig["client"], rig["node"]
    base = rig["base_url"]
    # warm both plan shapes (cold compiles out of the bracket)
    c.coprocessor(_fg_dag(rig, c.tso(), 900), timeout=120,
                  resource_group="warm")
    c.coprocessor(_bg_dag(rig, c.tso()), timeout=120,
                  resource_group="warm")
    req = urllib.request.Request(
        f"{base}/config",
        data=json.dumps({
            "resource-control.enabled": True,
            "resource-control.groups": {
                "fg": {"share": 4000.0, "priority": "high"},
                # far below a scan's RU cost with a one-scan burst:
                # the second bg admission finds the bucket in debt
                "bg": {"share": 1.0, "burst": 1.0,
                       "priority": "low"}},
        }).encode(), method="POST")
    resp = json.load(urllib.request.urlopen(req, timeout=10))
    assert resp["applied"]["resource_control.enabled"] is True
    assert GLOBAL_CONTROLLER.enabled
    rc_shed_base = node.read_pool.stats()["rc_shed"]
    fg_res, bg_res = [], []
    sheds_seen = []
    errors = []
    bg_done = threading.Event()

    def fg_worker(ci):
        # a SUSTAINED foreground stream: fg keeps serving for as long
        # as bg is still working (+ a floor of 8 requests), so the
        # two-tenant contention the enforcement acts on is live at
        # every bg admission — the scenario, not a race
        i = 0
        while i < 8 or not bg_done.is_set():
            t0 = time.perf_counter()
            try:
                c.coprocessor(_fg_dag(rig, c.tso(), 900 + ci + i % 16),
                              timeout=60, resource_group="fg",
                              request_source="point")
            except RemoteError as e:
                errors.append(("fg", e.kind))
                i += 1
                continue
            fg_res.append({"ok": True,
                           "elapsed": time.perf_counter() - t0})
            i += 1
            if time.perf_counter() - t0 < 0.05:
                time.sleep(0.05)    # pace: a dashboard, not a flood

    def bg_worker(ci):
        for i in range(2):
            t0 = time.perf_counter()
            give_up = t0 + 45.0
            while True:
                try:
                    c.coprocessor(_bg_dag(rig, c.tso()), timeout=60,
                                  resource_group="bg",
                                  request_source="scan")
                except RemoteError as e:
                    if e.kind == "server_is_busy" and \
                            time.perf_counter() < give_up:
                        sheds_seen.append(e.err)
                        time.sleep(min(
                            1.0, e.err.get("retry_after_ms", 20)
                            / 1e3))
                        continue
                    errors.append(("bg", e.kind))
                    bg_res.append({"ok": False})
                    break
                bg_res.append({"ok": True,
                               "elapsed": time.perf_counter() - t0})
                break

    bg_threads = [threading.Thread(target=bg_worker, args=(ci,))
                  for ci in range(2)]
    fg_threads = [threading.Thread(target=fg_worker, args=(ci,))
                  for ci in range(3)]
    for t in fg_threads + bg_threads:
        t.start()
    for t in bg_threads:
        t.join(90)
    bg_done.set()
    for t in fg_threads:
        t.join(90)
    # fg untouched, zero late acks anywhere
    assert not any(g == "fg" for g, _ in errors), errors
    assert not any(k == "deadline_exceeded" for _, k in errors)
    assert len(fg_res) >= 24
    # bg throttled: the read pool's RU-priced gate shed it (the
    # TxnClient's built-in busy-backoff may absorb sheds transparently
    # before the test-side retry loop sees them — production behavior:
    # the hint IS honored — so the authoritative count is the pool's)
    assert node.read_pool.stats()["rc_shed"] > rc_shed_base, \
        "bg was never throttled"
    for s in sheds_seen:        # any that did surface carried shape
        assert s.get("resource_group") == "bg"
        assert s.get("retry_after_ms", 0) >= 1
    # the WIRE shape, observed via a raw retry-free client: put bg
    # deep in debt, keep fg active, and the busy response names the
    # group and derives its hint from bg's own bucket
    from tikv_tpu.server import wire as wire_mod
    from tikv_tpu.server.client import StoreClient
    with GLOBAL_RECORDER.attach("bg|scan", requests=0):
        GLOBAL_RECORDER.charge("read_pool::host", host_s=0.1)
    c.coprocessor(_fg_dag(rig, c.tso(), 950), timeout=60,
                  resource_group="fg", request_source="point")
    with pytest.raises(RemoteError) as ei:
        StoreClient(node.addr).call("Coprocessor", {
            "tp": 103, "dag": wire_mod.enc_dag(_bg_dag(rig, c.tso())),
            "resource_group": "bg", "request_source": "scan"})
    err = ei.value.err
    assert err["kind"] == "server_is_busy", err
    assert err["resource_group"] == "bg"
    assert err["retry_after_ms"] >= 1
    # ...but NOT starved: every bg request eventually completed
    from tikv_tpu.chaos import check_bg_not_starved
    assert len(bg_res) == 4
    check_bg_not_starved(bg_res, min_served_fraction=0.99)
    # surfaces: /resource_control (text + json), /health, /metrics
    txt = urllib.request.urlopen(
        f"{base}/resource_control").read().decode()
    assert "bg" in txt and "enabled=True" in txt
    doc = json.load(urllib.request.urlopen(
        f"{base}/resource_control?format=json"))
    assert doc["enabled"] is True
    assert doc["groups"]["bg"]["sheds"] >= 1
    assert doc["groups"]["bg"]["priority"] == "low"
    assert doc["groups"]["fg"]["priority"] == "high"
    health = json.load(urllib.request.urlopen(f"{base}/health"))
    roll = health["resource_control"]
    assert roll["enabled"] is True and "bg" in roll["groups"]
    metrics = urllib.request.urlopen(f"{base}/metrics").read().decode()
    assert "tikv_resource_control_actions_total" in metrics
    assert 'group="bg",action="shed"' in metrics
    assert "tikv_resource_control_tokens" in metrics
    # disable ONLINE: the next bg request flows freely again
    req = urllib.request.Request(
        f"{base}/config",
        data=json.dumps({"resource-control.enabled": False}).encode(),
        method="POST")
    urllib.request.urlopen(req, timeout=10)
    assert not GLOBAL_CONTROLLER.enabled
    c.coprocessor(_bg_dag(rig, c.tso()), timeout=60,
                  resource_group="bg", request_source="scan")
