"""A warm launch is staged from its class's prepared record
(device/request.py ``_Prepared``; ``DeviceRunner._stage_tickets``).

What a warm whole-feed Pallas launch needs is computed once a (line,
generation, const-blind class) and left in the request memo by the
launch that computed it; a request of the class then stages from it: a
look-up, its guards, its own operands, its pin.  Held here, on the CPU
with the Pallas body in interpret mode (tests/test_coalescer.py's
``lane_runner`` rig: the tests patch ``pl.pallas_call`` and lift the
runner's TPU gate on the instance; no product knob):

- answers staged from a record equal the first (unprepared) staging's
  and the host pipeline's, for dense / sparse / simple / composite-key
  plans, a request alone and two to four lanes, constants differing a
  lane;
- a hit runs neither ``run_hash`` / ``run_simple`` nor ``arena.admit``,
  counts one ``prepared.hits`` a lane, pins its line;
- the record is missed, and rebuilt by the full staging that follows,
  after everything that can change what it stands on: a write (patched
  in place or re-uploaded), a budget eviction, ``drop_feed``, a scrub
  quarantine, a moved copy installed over the feed, planes replaced in
  place, a patch under another class's record, a kernel entry turned
  ``False``; an older-generation read never touches it;
- ``device::before_dispatch``, ``device::slice_dead`` and
  ``copr::coalesce_dispatch`` fire on a hit as they did, and members
  still retry solo;
- the arena reads the same resident bytes with and without a record;
  a tiled request and a mesh runner never build one.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from test_coalescer import (  # noqa: F401 — the rig and its fixture
    LANE_BLOCK,
    LaneRig,
    lane_runner,
    lane_snapshot,
    lane_table,
)
from tikv_tpu.datatype import Column, EvalType, FieldType
from tikv_tpu.device.feed import anchor as feed_anchor
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn
from tikv_tpu.utils import failpoint

N_ROWS = 3 * LANE_BLOCK + 100


@pytest.fixture(autouse=True)
def _teardown_failpoints():
    yield
    failpoint.teardown()


# ------------------------------------------------------------ the shapes


def two_key_table():
    return Table(8710, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("a", 2, FieldType.long(not_null=True)),
        TableColumn("b", 3, FieldType.long(not_null=True)),
        TableColumn("v", 4, FieldType.long(not_null=True))))


def two_key_snapshot(seed, n=N_ROWS):
    rng = np.random.default_rng(seed)
    ones = np.ones(n, np.bool_)
    return ColumnarTable.from_arrays(
        two_key_table(), np.arange(seed * 100_000, seed * 100_000 + n,
                                   dtype=np.int64),
        {"a": Column(EvalType.INT,
                     rng.integers(100, 107, n).astype(np.int64), ones),
         "b": Column(EvalType.INT,
                     rng.integers(-3, 2, n).astype(np.int64), ones),
         "v": Column(EvalType.INT,
                     rng.integers(-1000, 1000, n).astype(np.int64), ones)})


def _at(dag, i):
    """``start_ts`` says which of the rig's snapshots the plan reads."""
    return dataclasses.replace(dag, start_ts=i + 1)


def dense_dag(i, c):
    s = DagSelect.from_table(lane_table(), ["id", "k", "v"])
    return _at(s.where(s.col("v") > c).aggregate(
        [s.col("k")],
        [("count_star", None), ("sum", s.col("v"))]).build(), i)


def simple_dag(i, c):
    s = DagSelect.from_table(lane_table(), ["id", "k", "v"])
    return _at(s.where(s.col("v") > c).aggregate(
        [], [("count_star", None), ("sum", s.col("v"))]).build(), i)


def composite_dag(i, c):
    s = DagSelect.from_table(two_key_table(), ["id", "a", "b", "v"])
    return _at(s.where(s.col("v") > c).aggregate(
        [s.col("a"), s.col("b")],
        [("count_star", None), ("sum", s.col("v"))]).build(), i)


# shape -> (a snapshot of a seed, the plan over snapshot i with constant c)
SHAPES = {
    "dense": (lane_snapshot, dense_dag),
    "sparse": (lambda seed: lane_snapshot(seed, sparse=True), dense_dag),
    "simple": (lane_snapshot, simple_dag),
    "composite": (two_key_snapshot, composite_dag),
}


def host_rows(dag, snap):
    return sorted(BatchExecutorsRunner(dag, snap).handle_request().rows())


def prepared(runner) -> dict:
    return runner.mesh_stats()["prepared"]


NO_MISSES = {"none": 0, "tile": 0, "generation": 0, "feed": 0, "kernel": 0,
             "gate": 0}


def record_of(runner, dag, snap):
    """The prepared record in ``dag``'s memo over ``snap``, or None."""
    bucket = runner._arena.bucket(feed_anchor(snap), create=False)
    meta = (bucket or {}).get(
        ("meta", runner._meta_key(dag, runner._analyze(dag))))
    return (meta or {}).get("prepared")


class Spies:
    """Counts what a hit must not run."""

    def __init__(self, runner, monkeypatch):
        self.calls = {"run_hash": 0, "run_simple": 0, "admit": 0}
        for obj, name in ((runner._aggregator, "run_hash"),
                          (runner._aggregator, "run_simple"),
                          (runner._arena, "admit")):
            monkeypatch.setattr(obj, name, self._counting(
                getattr(obj, name), name))

    def _counting(self, fn, name):
        def spy(*a, **kw):
            self.calls[name] += 1
            return fn(*a, **kw)
        return spy

    def reset(self):
        for k in self.calls:
            self.calls[k] = 0


# ---------------------------------------------------------------- answers


@pytest.mark.parametrize("k", [1, 2, 3, 4])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_answers_from_a_record_equal_the_first_stagings_and_the_hosts(
        lane_runner, monkeypatch, shape, k):
    """k requests of one class over k feeds, through the endpoint and
    the coalescer: alone (the singleton branch) or as the k lanes of one
    launch, each lane with its own constant.  Every answer staged from a
    record equals the host pipeline's and, at the first staging's
    constant, the first staging's; none of them ran the operator or the
    arena's admission, each counted one hit, and the launch says so."""
    snap_of, dag_of = SHAPES[shape]
    snaps = [snap_of(s) for s in range(k)]
    rig = LaneRig(lane_runner, snaps)
    spies = Spies(lane_runner, monkeypatch)
    try:
        # the first, unprepared stagings: a kernel build, then one full
        # staging a feed, each of which leaves its record
        first = [sorted(rig.one(dag_of(i, 5)).rows()) for i in range(k)]
        assert prepared(lane_runner)["builds"] == k
        assert all(record_of(lane_runner, dag_of(i, 5), snaps[i])
                   is not None for i in range(k))
        rig.wait_built()
        for consts in ([5] * k, [7 + 31 * i for i in range(k)]):
            dags = [dag_of(i, c) for i, c in enumerate(consts)]
            before = prepared(lane_runner)
            launches = lane_runner.flight_recorder.stats()["launches"]
            spies.reset()
            got = [rig.one(dags[0])] if k == 1 else rig.together(dags, k)
            for i, g in enumerate(got):
                assert g.backend == "device"
                assert sorted(g.rows()) == host_rows(dags[i], snaps[i]), i
                if consts[i] == 5:
                    assert sorted(g.rows()) == first[i], i
            assert spies.calls == {"run_hash": 0, "run_simple": 0,
                                   "admit": 0}
            after = prepared(lane_runner)
            assert after["hits"] == before["hits"] + k
            assert after["builds"] == before["builds"]
            assert after["drops"] == before["drops"]
            rec = lane_runner.flight_recorder
            assert rec.stats()["launches"] == launches + 1
            last = rec.items()[-1]
            assert last["compile_class"] == "pallas_hash" and \
                last["prepared"] == k and last["ok"], last
        assert lane_runner.flight_recorder.stats()["faults"] == 0
        lanes = lane_runner.mesh_stats()["lanes"]["launches_by_lanes"]
        assert lanes[str(k)] >= 2, lanes
    finally:
        rig.close()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_lanes_over_one_feed_differ_in_their_constants_alone(lane_runner,
                                                             shape):
    """Three sessions read ONE region with three constant tuples at
    once: three lanes of one launch over the same feed, staged from the
    one record, each with its own operands and its own answer."""
    snap_of, dag_of = SHAPES[shape]
    snaps = [snap_of(5)]
    rig = LaneRig(lane_runner, snaps)
    try:
        rig.one(dag_of(0, 5))
        rig.wait_built()
        dags = [dag_of(0, c) for c in (-500, 0, 500)]
        before = prepared(lane_runner)
        got = rig.together(dags, 3)
        rows = [sorted(g.rows()) for g in got]
        assert rows == [host_rows(d, snaps[0]) for d in dags]
        assert rows[0] != rows[1] != rows[2]
        after = prepared(lane_runner)
        assert after["hits"] == before["hits"] + 3 and \
            after["builds"] == before["builds"] == 1
        last = lane_runner.flight_recorder.items()[-1]
        assert last["prepared"] == 3, last
    finally:
        rig.close()


@pytest.mark.parametrize("deferred", [False, True])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_request_alone_is_staged_from_the_record_and_pins_its_line(
        lane_runner, monkeypatch, shape, deferred):
    """``handle_request`` itself, blocking and deferred: constants that
    change from call to call hit one record; a deferred hit holds its
    line's pin until it resolves, exactly once."""
    snap_of, dag_of = SHAPES[shape]
    snap = snap_of(3)
    lane_runner.handle_request(dag_of(0, 5), snap)      # build + record
    spies = Spies(lane_runner, monkeypatch)
    for c in (5, -40, 300, 999):
        dag = dag_of(0, c)
        before = prepared(lane_runner)["hits"]
        got = lane_runner.handle_request(dag, snap, deferred=deferred)
        if deferred:
            assert lane_runner.hbm_stats()["pinned_lines"] == 1
            assert got.launch_info["attrs"]["prepared"] == 1
            got = got.result()
        assert lane_runner.hbm_stats()["pinned_lines"] == 0
        assert sorted(got.rows()) == host_rows(dag, snap), c
        assert prepared(lane_runner)["hits"] == before + 1
    assert spies.calls == {"run_hash": 0, "run_simple": 0, "admit": 0}
    assert lane_runner._arena.pinned_bytes() == 0


def test_the_span_and_the_health_rollup_carry_prepared(lane_runner):
    """``/health`` ``device_mesh.prepared`` and the ``prepared``
    attribute of the ``device_dispatch`` span and of the flight-recorder
    entry: 0 on the staging that wrote the record, 1 on a hit."""
    from tikv_tpu.utils import tracker
    snap = lane_snapshot(4)
    seen = []
    for c in (5, 6):
        tr, tok = tracker.install(sampled=True)
        try:
            lane_runner.handle_request(dense_dag(0, c), snap)
        finally:
            tracker.uninstall(tok)
        span, = [s for s in tr.spans if s.name == "device_dispatch"]
        seen.append(span.attrs["prepared"])
    assert seen == [0, 1]
    assert [e["prepared"] for e in lane_runner.flight_recorder.items()] \
        == [0, 1]
    assert prepared(lane_runner) == {
        "hits": 1, "builds": 1,
        "drops": {"refresh": 0, "feed": 0, "kernel": 0},
        "ticket_hits": 1, "ticket_misses": dict(NO_MISSES, none=1)}


# ------------------------------------------------- missed, and rebuilt


def _line(snaps):
    """``snaps`` as the generations 0.. of ONE line."""
    from tikv_tpu.copr.region_cache import FeedLineage
    lineage = FeedLineage()
    for v, snap in enumerate(snaps):
        snap.feed_lineage, snap.feed_version = lineage, v
    return lineage


def _patched(snap, row, v_new):
    """``snap`` with one row's ``v`` rewritten → (the new snapshot, the
    journal's row patch for it)."""
    h = snap.handles
    k = snap.columns[2].values
    v = snap.columns[3].values.copy()
    v[row] = v_new
    ones = np.ones(len(h), np.bool_)
    new = ColumnarTable.from_arrays(
        lane_table(), h, {"k": Column(EvalType.INT, k, ones),
                          "v": Column(EvalType.INT, v, ones)})
    one = np.ones(1, np.bool_)
    return new, {"n": len(h), "spans": [{
        "lo": row, "handles": h[row:row + 1],
        "cols": {2: (k[row:row + 1], one), 3: (v[row:row + 1], one)}}]}


def _after_write(runner, how):
    old = lane_snapshot(21)
    new, patch = _patched(old, 7, 123)
    lineage = _line([old, new])
    # what lies between the two generations: one row patched in place,
    # or nothing the feed can replay (it re-uploads)
    lineage.record(patch if how == "patch"
                   else {"structural": True, "spans": []})
    return old, new, "refresh"


def _evicted(runner, snap) -> bool:
    """The budget took ``snap``'s line: its feed and its record are
    gone, its host memo stays (PR 53)."""
    bucket = runner._arena.bucket(snap, create=False)
    return bool(bucket) and not any(
        "flat" in v or "prepared" in v for v in bucket.values())


def _budget_eviction(runner):
    snap, other = lane_snapshot(22), lane_snapshot(23)
    for s in (snap, other):
        runner.handle_request(dense_dag(0, 5), s)
    # the other line is the more used: the budget takes ``snap``'s
    runner.handle_request(dense_dag(0, 6), other)
    one = runner.hbm_stats()["resident_bytes"] // 2
    runner.set_hbm_budget(one + one // 2)
    assert runner.hbm_stats()["evictions"] == 1
    assert _evicted(runner, snap)
    runner.set_hbm_budget(0)
    return snap


CAUSES = ["write_patched", "write_reuploaded", "budget_eviction",
          "drop_feed", "quarantine", "moved_copy_installed",
          "planes_replaced", "kernel_false"]


@pytest.mark.parametrize("cause", CAUSES)
def test_the_record_is_missed_and_rebuilt_after(lane_runner, monkeypatch,
                                                cause):
    """Whatever changes what a record stands on is followed by ONE full
    staging (the operator runs, the arena admits, a new record is
    written) with the right answer, and the read after that is staged
    from the new record."""
    runner = lane_runner
    dag = dense_dag(0, 5)
    drop = None             # the drop this cause counts, if it counts one
    host_first = False      # (a quarantine serves its next read on the host)
    if cause.startswith("write"):
        snap0, snap, drop = _after_write(runner, cause[6:-2])
        runner.handle_request(dag, snap0)
        assert record_of(runner, dag, snap0) is not None
    elif cause == "budget_eviction":
        snap = _budget_eviction(runner)
    else:
        snap = lane_snapshot(24)
        runner.handle_request(dag, snap)
        rec = record_of(runner, dag, snap)
        assert rec is not None
        if cause == "drop_feed":
            assert runner.drop_feed(snap) > 0
        elif cause == "quarantine":
            runner.quarantine(snap, reason="test")
            host_first = True
        elif cause == "moved_copy_installed":
            feeds, skipped = runner._feeds.extract_feeds(snap)
            assert feeds and not skipped
            assert runner._feeds.install_feeds(snap, feeds) == "moved"
            drop = "feed"
        elif cause == "planes_replaced":
            runner._feeds.corrupt_resident_plane(rec.feed)
            runner._feeds.corrupt_resident_plane(rec.feed)     # (and back)
            drop = "feed"
        elif cause == "kernel_false":
            runner._kernel_cache[rec.key] = False
            drop = "kernel"
    want = host_rows(dag, snap)
    if host_first:
        got = runner.handle_request(dag, snap)
        assert sorted(got.rows()) == want
        assert prepared(runner)["hits"] == 0
    spies = Spies(runner, monkeypatch)
    before = prepared(runner)
    assert sorted(runner.handle_request(dag, snap).rows()) == want
    after = prepared(runner)
    assert after["hits"] == before["hits"]                  # a miss
    assert spies.calls["run_hash"] == 1
    if drop is not None:
        assert after["drops"][drop] == before["drops"][drop] + 1
    assert sum(after["drops"].values()) == \
        sum(before["drops"].values()) + (drop is not None)
    if cause == "kernel_false":
        # the stand-in served and wrote no record; the kernel back, the
        # next read builds both again
        assert runner.flight_recorder.items()[-1]["compile_class"] == \
            "hash_twolevel"
        assert after["builds"] == before["builds"]
        del runner._kernel_cache[rec.key]
        assert sorted(runner.handle_request(dag, snap).rows()) == want
        after = prepared(runner)
    else:
        assert spies.calls["admit"] >= 1
    assert after["builds"] == before["builds"] + 1          # rebuilt
    assert record_of(runner, dag, snap) is not None
    spies.reset()
    assert sorted(runner.handle_request(dense_dag(0, 9), snap).rows()) == \
        host_rows(dense_dag(0, 9), snap)
    assert prepared(runner)["hits"] == after["hits"] + 1    # and hit
    assert spies.calls == {"run_hash": 0, "run_simple": 0, "admit": 0}
    assert runner.hbm_stats()["pinned_lines"] == 0


def test_an_older_generation_read_never_touches_the_record(lane_runner):
    """A history read of a generation the line has left goes local: it
    neither hits the newer record nor replaces it, and its answer is its
    own snapshot's."""
    old, new, _ = _after_write(lane_runner, "patch")
    dag = dense_dag(0, 5)
    for snap in (old, new):
        lane_runner.handle_request(dag, snap)
    rec = record_of(lane_runner, dag, new)
    assert rec is not None and rec.feed["lineage_v"] == 1
    before = prepared(lane_runner)
    got = lane_runner.handle_request(dag, old)
    assert sorted(got.rows()) == host_rows(dag, old) != host_rows(dag, new)
    after = prepared(lane_runner)
    assert after["hits"] == before["hits"] and \
        after["drops"] == before["drops"]
    assert record_of(lane_runner, dag, new) is rec
    assert sorted(lane_runner.handle_request(dag, new).rows()) == \
        host_rows(dag, new)
    assert prepared(lane_runner)["hits"] == before["hits"] + 1


def test_a_patch_under_another_classs_record_is_seen(lane_runner):
    """Two classes share one feed.  A write read by ONE of them patches
    the feed in place under the other's record, whose memo still stands
    at the old generation: a read of the old generation by that class
    finds the feed moved on and stages in full, on a feed of its own."""
    old, new, _ = _after_write(lane_runner, "patch")
    s = DagSelect.from_table(lane_table(), ["id", "k", "v"])
    plain = _at(s.where(s.col("v") > 5).aggregate(
        [], [("sum", s.col("k")), ("sum", s.col("v"))]).build(), 0)
    grouped = dense_dag(0, 5)
    for dag in (grouped, plain):
        lane_runner.handle_request(dag, old)
    rec = record_of(lane_runner, plain, old)
    assert rec is not None and rec.feed is \
        record_of(lane_runner, grouped, old).feed
    lane_runner.handle_request(grouped, new)        # patches the feed
    assert rec.feed["lineage_v"] == 1 and rec.feed["flat"] is not rec.flat
    before = prepared(lane_runner)
    got = lane_runner.handle_request(plain, old)
    assert sorted(got.rows()) == host_rows(plain, old) != \
        host_rows(plain, new)
    after = prepared(lane_runner)
    assert after["hits"] == before["hits"]
    assert after["drops"]["feed"] == before["drops"]["feed"] + 1
    assert sorted(lane_runner.handle_request(plain, new).rows()) == \
        host_rows(plain, new)


def test_a_failed_launch_from_a_record_leaves_the_request_to_the_full_staging(
        lane_runner):
    """The record's kernel raises at launch: the pin is given back, the
    launch is a fault and no hit, and the full staging serves the
    request."""
    snap = lane_snapshot(25)
    dag = dense_dag(0, 5)
    lane_runner.handle_request(dag, snap)
    rec = record_of(lane_runner, dag, snap)

    def boom(*_a, **_k):
        raise RuntimeError("injected launch failure")

    rec.run = boom
    before = prepared(lane_runner)
    got = lane_runner.handle_request(dag, snap)
    assert sorted(got.rows()) == host_rows(dag, snap)
    after = prepared(lane_runner)
    assert after["hits"] == before["hits"]
    assert after["builds"] == before["builds"] + 1
    assert record_of(lane_runner, dag, snap) is not rec
    assert lane_runner.mesh_stats()["lanes"]["launch_failures"] == 1
    assert lane_runner.flight_recorder.stats()["faults"] == 1
    # (the full staging's clean launch cleared the kernel's strike)
    assert ("hashpl_tries", rec.key) not in lane_runner._kernel_cache
    assert lane_runner.hbm_stats()["pinned_lines"] == 0


# ------------------------------------------------------------- the guards


@pytest.mark.parametrize("fp", ["device::before_dispatch",
                                "device::slice_dead"])
def test_a_dispatch_guard_fires_on_a_hit(lane_runner, fp):
    """The failpoint of the dispatch site degrades THE request it fires
    in to the host pipeline, record or no record; the next is a hit."""
    snap = lane_snapshot(26)
    dag = dense_dag(0, 5)
    lane_runner.handle_request(dag, snap)
    assert record_of(lane_runner, dag, snap) is not None
    launches = lane_runner.flight_recorder.stats()["launches"]
    before = prepared(lane_runner)
    failpoint.cfg(fp, "1*return")
    got = lane_runner.handle_request(dag, snap)
    assert sorted(got.rows()) == host_rows(dag, snap)
    assert lane_runner.flight_recorder.stats()["launches"] == launches
    assert prepared(lane_runner) == before
    got = lane_runner.handle_request(dense_dag(0, 6), snap)
    assert sorted(got.rows()) == host_rows(dense_dag(0, 6), snap)
    assert prepared(lane_runner)["hits"] == before["hits"] + 1
    assert lane_runner.hbm_stats()["pinned_lines"] == 0


@pytest.mark.parametrize("fp", ["copr::coalesce_dispatch",
                                "device::before_dispatch"])
def test_members_still_retry_solo_from_their_records(lane_runner, fp):
    """A staging of three lanes with the failpoint armed once: the whole
    staging (``copr::coalesce_dispatch``) or the lane it fired in
    (``device::before_dispatch``) goes through ``_solo_fallback``, every
    answer is right, and the solo retries are staged from the records."""
    snaps = [lane_snapshot(s) for s in range(3)]
    rig = LaneRig(lane_runner, snaps)
    try:
        for i in range(3):
            rig.one(dense_dag(i, 5))
        rig.wait_built()
        dags = [dense_dag(i, 40 + i) for i in range(3)]
        before = prepared(lane_runner)
        failpoint.cfg(fp, "1*return")
        got = rig.together(dags, 3)
        for i, g in enumerate(got):
            assert sorted(g.rows()) == host_rows(dags[i], snaps[i]), i
            assert g.backend == "device"
        solo = rig.coal.stats()["solo_degrade"]
        assert solo == (3 if fp.startswith("copr") else 1)
        after = prepared(lane_runner)
        assert after["hits"] == before["hits"] + 3
        assert after["builds"] == before["builds"]
    finally:
        rig.close()


# ------------------------------------------------------------ accounting


@pytest.mark.parametrize("shape", ["dense", "sparse"])
def test_resident_bytes_equal_with_and_without_a_record(lane_runner, shape):
    """The record holds no byte the feed and the memo do not, and the
    arena must not count one twice: ``hbm_stats()`` reads the feed's
    planes (and the sparse slot column) with the record in the memo and
    after it is taken out and the line re-admitted."""
    snap_of, dag_of = SHAPES[shape]
    snap = snap_of(27)
    dag = dag_of(0, 5)
    lane_runner.handle_request(dag, snap)
    rec = record_of(lane_runner, dag, snap)
    assert rec is not None
    by_hand = sum(int(a.nbytes) for a in rec.feed["flat"])
    if shape == "sparse":
        by_hand += int(rec.cols[-1].nbytes)     # the slot column
    with_record = lane_runner.hbm_stats()
    assert with_record["resident_bytes"] == by_hand
    lane_runner.handle_request(dag_of(0, 6), snap)      # a hit: no admit
    assert lane_runner.hbm_stats()["resident_bytes"] == by_hand
    bucket = lane_runner._arena.bucket(snap)
    meta, = [v for k, v in bucket.items() if k[0] == "meta"]
    del meta["prepared"]
    assert lane_runner._arena.admit(snap)
    without = lane_runner.hbm_stats()
    assert without["resident_bytes"] == by_hand
    assert without["resident_bytes_by_device"] == \
        with_record["resident_bytes_by_device"]


# -------------------------------------------------------------- bypasses


def test_a_tiled_request_never_builds_a_record(lane_runner):
    """Ranges over part of a region's rows leave by the tile path: no
    record under the region's memo, no hit, answers exact; the whole
    region's own record is not theirs to use."""
    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.executors.ranges import KeyRange
    snap = lane_snapshot(1)
    ranges = (KeyRange(table_record_key(8700, 100_000 + 256),
                       table_record_key(8700, 100_000 + 9000)),)
    assert snap.row_slices(ranges) == [(256, 9000)]
    tiled = dataclasses.replace(dense_dag(0, 5), ranges=ranges)
    for _ in range(2):
        got = lane_runner.handle_request(tiled, snap)
        assert sorted(got.rows()) == host_rows(tiled, snap)
    assert prepared(lane_runner)["builds"] == 0
    whole = dataclasses.replace(dense_dag(0, 5), ranges=())
    lane_runner.handle_request(whole, snap)
    assert prepared(lane_runner)["builds"] == 1
    got = lane_runner.handle_request(tiled, snap)
    assert sorted(got.rows()) == host_rows(tiled, snap)
    assert prepared(lane_runner)["hits"] == 0
    assert [e["prepared"] for e in lane_runner.flight_recorder.items()] \
        == [0] * 4


def test_a_mesh_runner_never_builds_a_record(monkeypatch):
    """Sharded launches leave from the request's thread as they did:
    no record, no hit."""
    import functools

    import jax

    from tikv_tpu.device import DeviceRunner, pallas_hash
    from tikv_tpu.parallel import make_mesh
    monkeypatch.setattr(
        pallas_hash.pl, "pallas_call",
        functools.partial(pallas_hash.pl.pallas_call, interpret=True))
    monkeypatch.setattr(pallas_hash, "BLOCK", LANE_BLOCK)
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:4]))
    runner._is_tpu = True
    runner._block_local = LANE_BLOCK
    snap = lane_snapshot(2, n=16 * LANE_BLOCK - 37)
    for c in (5, 6, 7):
        dag = dense_dag(0, c)
        got = runner.handle_request(dag, snap)
        assert sorted(got.rows()) == host_rows(dag, snap)
    assert {e["compile_class"] for e in runner.flight_recorder.items()} \
        == {"pallas_hash"}
    assert prepared(runner) == {
        "hits": 0, "builds": 0,
        "drops": {"refresh": 0, "feed": 0, "kernel": 0},
        "ticket_hits": 0, "ticket_misses": dict(NO_MISSES, none=3)}
    assert record_of(runner, dense_dag(0, 5), snap) is None
    assert runner.launch_ticket(runner.batch_class(dense_dag(0, 5), snap) or
                                ("share",), dense_dag(0, 5), snap) is None


# ------------------------------------------------------------ the ticket
#
# What ``launch_ticket`` resolved to tell a group's launch class rides
# with the group, and its lane is staged from it (``_stage_tickets``):
# a hold's lanes in one pass, a request alone through the same function.


def ticket_of(runner, dag, snap):
    return runner.launch_ticket(runner.batch_class(dag, snap), dag, snap)


def whole_ranges(snap, table_id=8700):
    """Ranges, as a client sends them, that cover every row of ``snap``."""
    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.executors.ranges import KeyRange
    h = snap.handles
    return (KeyRange(table_record_key(table_id, int(h[0])),
                     table_record_key(table_id, int(h[-1]) + 1)),)


def rows_of(outcome):
    got = outcome.result() if hasattr(outcome, "result") else outcome
    return sorted(got.rows())


def staged(runner, lanes, tickets, via):
    """``lanes`` staged from ``tickets``: as the lanes of one hold, or
    (one lane) as a request alone."""
    if via == "lanes":
        return runner.handle_lanes(lanes, tickets)
    (dag, snap), = lanes
    return [runner.handle_request(dag, snap, deferred=True,
                                  _ticket=tickets[0])]


@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_lane_staged_from_a_ticket_answers_as_the_full_staging_and_the_host(
        lane_runner, monkeypatch, shape, k):
    """The full staging writes the record; the ticket that finds it
    carries the class ``launch_class`` tells, and k lanes staged from
    their tickets (one alone: ``handle_request``; three: one hold) give
    the rows the full staging gave and the host gives, each lane under
    its own constant, with nothing of the full staging run."""
    snap_of, dag_of = SHAPES[shape]
    runner = lane_runner
    snaps = [snap_of(s) for s in range(k)]
    first = [sorted(runner.handle_request(dag_of(i, 5), snaps[i]).rows())
             for i in range(k)]
    spies = Spies(runner, monkeypatch)
    for consts in ([5] * k, [7 + 31 * i for i in range(k)]):
        dags = [dag_of(i, c) for i, c in enumerate(consts)]
        tickets = [ticket_of(runner, d, s) for d, s in zip(dags, snaps)]
        for t, d, s in zip(tickets, dags, snaps):
            assert t is not None and t.rec is record_of(runner, d, s)
            assert t.klass == runner.launch_class(
                runner.batch_class(d, s), d, s) == t.rec.key
        before = prepared(runner)
        out = staged(runner, list(zip(dags, snaps)), tickets,
                     "alone" if k == 1 else "lanes")
        assert runner.hbm_stats()["pinned_lines"] == k
        rows = [rows_of(o) for o in out]
        assert rows == [host_rows(d, s) for d, s in zip(dags, snaps)]
        if consts[0] == 5:
            assert rows == first
        after = prepared(runner)
        assert after["ticket_hits"] == before["ticket_hits"] + k
        assert after["hits"] == before["hits"] + k
        assert after["ticket_misses"] == before["ticket_misses"]
        assert after["builds"] == before["builds"] and \
            after["drops"] == before["drops"]
    assert spies.calls == {"run_hash": 0, "run_simple": 0, "admit": 0}
    assert runner._arena.pinned_bytes() == 0


def _two_generations(runner, how="patch"):
    old, new, _ = _after_write(runner, how)
    return old, new


@pytest.mark.parametrize("via", ["lanes", "alone"])
@pytest.mark.parametrize("how", [
    "feed:eviction", "feed:drop_feed", "feed:patch_under_another_class",
    "kernel:entry_false", "generation:a_write_since_the_ticket",
    "generation:the_line_moved_on", "generation:an_older_read"])
def test_a_ticket_misses_as_the_record_did_and_says_why(lane_runner, how,
                                                        via):
    """Every guard a record was held to holds its ticket: what changed
    between the ask and the hold sends the lane to ``_stage_local``
    inside the same staging, the answer is its own snapshot's, and
    ``ticket_misses`` says why."""
    runner = lane_runner
    cause, what = how.split(":")
    dag = dense_dag(0, 5)
    keeps = None        # a record this staging must leave as it is
    if what == "eviction":
        snap, other = lane_snapshot(22), lane_snapshot(23)
        for s in (snap, other):
            runner.handle_request(dag, s)
        runner.handle_request(dense_dag(0, 6), other)
        ticket = ticket_of(runner, dag, snap)
        one = runner.hbm_stats()["resident_bytes"] // 2
        runner.set_hbm_budget(one + one // 2)
        assert _evicted(runner, snap)
        runner.set_hbm_budget(0)
    elif what == "drop_feed":
        snap = lane_snapshot(24)
        runner.handle_request(dag, snap)
        ticket = ticket_of(runner, dag, snap)
        assert runner.drop_feed(snap) > 0
    elif what == "patch_under_another_class":
        snap, new = _two_generations(runner)
        s = DagSelect.from_table(lane_table(), ["id", "k", "v"])
        dag = _at(s.where(s.col("v") > 5).aggregate(
            [], [("sum", s.col("k")), ("sum", s.col("v"))]).build(), 0)
        for d in (dense_dag(0, 5), dag):
            runner.handle_request(d, snap)
        ticket = ticket_of(runner, dag, snap)
        runner.handle_request(dense_dag(0, 5), new)     # patches the feed
        assert ticket.rec.feed["lineage_v"] == 1
    elif what == "entry_false":
        snap = lane_snapshot(24)
        runner.handle_request(dag, snap)
        ticket = ticket_of(runner, dag, snap)
        runner._kernel_cache[ticket.rec.key] = False
    elif what == "a_write_since_the_ticket":
        # the group was asked at the generation before its snapshot's:
        # the memo, and the record, still stood there
        old, snap = _two_generations(runner)
        runner.handle_request(dag, old)
        ticket = ticket_of(runner, dag, snap)
        assert ticket.rec is record_of(runner, dag, old) and \
            ticket.req_v == 1 and ticket.meta["lineage_v"] == 0
    elif what == "the_line_moved_on":
        # asked while its generation was the line's; a newer read has
        # rolled the memo since
        snap, new = _two_generations(runner)
        runner.handle_request(dag, snap)
        ticket = ticket_of(runner, dag, snap)
        runner.handle_request(dag, new)
        keeps = record_of(runner, dag, new)
        assert keeps is not None and keeps is not ticket.rec
    else:
        # an older-generation read finds the NEWER record under its
        # key (and so its class, as before): it never stages from it
        snap, new = _two_generations(runner)
        for s in (snap, new):
            runner.handle_request(dag, s)
        keeps = record_of(runner, dag, new)
        ticket = ticket_of(runner, dag, snap)
        assert ticket.rec is keeps and ticket.req_v == 0
    before = prepared(runner)
    out, = staged(runner, [(dag, snap)], [ticket], via)
    assert rows_of(out) == host_rows(dag, snap)
    after = prepared(runner)
    assert after["ticket_hits"] == before["ticket_hits"]
    assert after["hits"] == before["hits"]
    assert after["ticket_misses"] == dict(
        before["ticket_misses"],
        **{cause: before["ticket_misses"][cause] + 1})
    if keeps is not None:
        assert record_of(runner, dag, new) is keeps
        assert after["drops"] == before["drops"]
        assert rows_of(runner.handle_request(dag, new)) == \
            host_rows(dag, new) != host_rows(dag, snap)
        assert prepared(runner)["ticket_hits"] == before["ticket_hits"] + 1
    if what == "entry_false":
        del runner._kernel_cache[ticket.rec.key]
    assert runner.hbm_stats()["pinned_lines"] == 0
    assert runner._arena.pinned_bytes() == 0


class CountingLock:
    """The arena's mutex, its acquires counted."""

    def __init__(self, inner):
        self.inner, self.acquires = inner, 0

    def __enter__(self):
        self.acquires += 1
        return self.inner.__enter__()

    def __exit__(self, *exc):
        return self.inner.__exit__(*exc)

    def acquire(self, *a, **kw):
        self.acquires += 1
        return self.inner.acquire(*a, **kw)

    def release(self):
        self.inner.release()


@pytest.mark.parametrize("asked", ["by_the_coalescer", "at_the_staging"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_a_ticketed_hold_probes_no_rows_and_takes_the_arenas_mutex_once(
        lane_runner, monkeypatch, k, asked):
    """k lanes whose ranges cover their regions: the full staging that
    wrote each record probed the region's rows and found no tile; a hold
    staged from tickets asks neither ``row_slices`` nor
    ``estimated_rows`` nor ``searchsorted`` again, takes the arena's
    mutex ONCE for all k pins, and the k unpins balance them."""
    runner = lane_runner
    snaps = [lane_snapshot(s) for s in range(k)]

    def ranged(i, c):
        return dataclasses.replace(dense_dag(i, c),
                                   ranges=whole_ranges(snaps[i]))

    probes = {"row_slices": 0, "estimated_rows": 0, "searchsorted": 0}

    def counting(fn, name):
        def spy(*a, **kw):
            probes[name] += 1
            return fn(*a, **kw)
        return spy

    for name in ("row_slices", "estimated_rows"):
        monkeypatch.setattr(ColumnarTable, name,
                            counting(getattr(ColumnarTable, name), name))
    monkeypatch.setattr(np, "searchsorted",
                        counting(np.searchsorted, "searchsorted"))
    for i in range(k):
        runner.handle_request(ranged(i, 5), snaps[i])
    assert probes["row_slices"] == probes["estimated_rows"] == k
    assert probes["searchsorted"] >= 2 * k
    dags = [ranged(i, 40 + i) for i in range(k)]
    tickets = [ticket_of(runner, d, s) for d, s in zip(dags, snaps)]
    assert None not in tickets
    for name in probes:
        probes[name] = 0
    lock = CountingLock(runner._arena._mu)
    monkeypatch.setattr(runner._arena, "_mu", lock)
    out = runner.handle_lanes(
        list(zip(dags, snaps)),
        tickets if asked == "by_the_coalescer" else None)
    assert lock.acquires == 1
    assert probes == {"row_slices": 0, "estimated_rows": 0,
                      "searchsorted": 0}
    assert runner._arena.pinned_bytes() > 0
    assert runner.hbm_stats()["pinned_lines"] == k
    assert [rows_of(o) for o in out] == \
        [host_rows(d, s) for d, s in zip(dags, snaps)]
    assert runner.hbm_stats()["pinned_lines"] == 0
    assert runner._arena.pinned_bytes() == 0
    assert prepared(runner)["ticket_hits"] == k


def test_a_tiled_request_gets_no_ticket_and_a_whole_one_does(lane_runner):
    """The ticket is asked under the request's ranges AS SENT: a tiled
    request's memo lies under the region's whole ranges, so it finds
    none (as it finds no class) beside the whole region's record, and
    says ``tile``; ranges that cover the region are a request of their
    own memo, probed once, then ticketed."""
    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.executors.ranges import KeyRange
    runner = lane_runner
    snap = lane_snapshot(1)
    whole = dataclasses.replace(dense_dag(0, 5), ranges=())
    runner.handle_request(whole, snap)
    assert ticket_of(runner, whole, snap) is not None
    tiled = dataclasses.replace(dense_dag(0, 5), ranges=(
        KeyRange(table_record_key(8700, 100_000 + 256),
                 table_record_key(8700, 100_000 + 9000)),))
    for n in (1, 2):
        assert ticket_of(runner, tiled, snap) is None
        assert runner.launch_class(runner.batch_class(tiled, snap), tiled,
                                   snap) is None
        assert rows_of(runner.handle_request(tiled, snap)) == \
            host_rows(tiled, snap)
        assert prepared(runner)["ticket_misses"]["tile"] == n
    covers = dataclasses.replace(dense_dag(0, 5),
                                 ranges=whole_ranges(snap))
    assert ticket_of(runner, covers, snap) is None
    runner.handle_request(covers, snap)
    assert ticket_of(runner, covers, snap).rec is \
        record_of(runner, covers, snap)
    got = prepared(runner)
    assert got["ticket_misses"] == dict(NO_MISSES, none=2, tile=2)
    assert got["ticket_hits"] == got["hits"] == 0


@pytest.mark.parametrize("via", ["lanes", "alone"])
@pytest.mark.parametrize("fp", ["device::before_dispatch",
                                "device::slice_dead"])
def test_a_dispatch_guard_fires_on_a_ticketed_hit(lane_runner, fp, via):
    """Armed once, the dispatch site's failpoint fires in a staging from
    tickets as it did in a staging from the record: a request alone is
    served by the host; of a hold's three lanes, the lane
    ``device::before_dispatch`` fired in goes solo and the others leave,
    and ``device::slice_dead``, the runner's, fails the one dispatch
    whole.  Nothing stays pinned and the next staging is a hit."""
    runner = lane_runner
    k = 1 if via == "alone" else 3
    snaps = [lane_snapshot(30 + s) for s in range(k)]
    for i in range(k):
        runner.handle_request(dense_dag(i, 5), snaps[i])
    dags = [dense_dag(i, 40 + i) for i in range(k)]
    lanes = list(zip(dags, snaps))
    tickets = [ticket_of(runner, d, s) for d, s in lanes]
    launches = runner.flight_recorder.stats()["launches"]
    before = prepared(runner)
    failpoint.cfg(fp, "1*return")
    out = staged(runner, lanes, tickets, via)
    after = prepared(runner)
    if via == "alone":
        assert rows_of(out[0]) == host_rows(dags[0], snaps[0])
        assert runner.flight_recorder.stats()["launches"] == launches
        assert after == before
    else:
        gone = [o is None for o in out]
        assert gone == ([True, False, False] if fp.endswith("before_dispatch")
                        else [True] * 3)
        assert after["ticket_hits"] == before["ticket_hits"] + \
            gone.count(False)
        assert after["ticket_misses"] == before["ticket_misses"]
        for o, (d, s) in zip(out, lanes):
            if o is not None:
                assert rows_of(o) == host_rows(d, s)
    assert runner.hbm_stats()["pinned_lines"] == 0
    out = staged(runner, lanes, [ticket_of(runner, d, s) for d, s in lanes],
                 via)
    assert [rows_of(o) for o in out] == [host_rows(d, s) for d, s in lanes]
    assert prepared(runner)["ticket_hits"] == after["ticket_hits"] + k
