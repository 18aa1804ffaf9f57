"""Incremental columnar cache maintenance (copr/region_cache.py +
copr/delta.py): a delta-patched snapshot must be bit-identical to a
full rebuild after any interleaving of inserts / updates / deletes /
rollbacks, including lock-conflict parity, compaction, and the
fallback-to-rebuild paths.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from tikv_tpu.codec.keys import table_record_key
from tikv_tpu.codec.row import encode_row
from tikv_tpu.copr import region_cache
from tikv_tpu.copr.delta import DeltaSink, decode_entry_ops
from tikv_tpu.copr.region_cache import (
    RegionColumnarCache,
    _LineState,
    build_region_columnar,
)
from tikv_tpu.kv.engine import SnapContext
from tikv_tpu.raftstore import RaftKv
from tikv_tpu.raftstore.metapb import RegionEpoch
from tikv_tpu.raftstore.peer import RegionSnapshot
from tikv_tpu.storage import Storage
from tikv_tpu.storage.mvcc.errors import KeyIsLocked
from tikv_tpu.storage.txn import commands as cmds
from tikv_tpu.storage.txn.actions import Mutation
from tikv_tpu.testing.cluster import Cluster
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import int_table


@pytest.fixture
def rig():
    c = Cluster(n_stores=1)
    c.bootstrap()
    c.start()
    sink = DeltaSink(max_entries=4096, max_rows=1 << 16)
    c.stores[1].coprocessor_host.register(sink)
    cache = RegionColumnarCache(capacity=4, delta_source=sink)
    table = int_table(2, table_id=7700)
    return {"c": c, "sink": sink, "cache": cache, "table": table}


def _row_key(table, h):
    return table_record_key(table.table_id, h)


def _mut(table, h, payload):
    return ("put", _row_key(table, h), encode_row(payload))


def _snap(c):
    return c.kvs[1].snapshot(SnapContext(region_id=1))


def _dag(c, table, ts=None):
    return DagSelect.from_table(table, ["id", "c0", "c1"]).build(
        start_ts=ts if ts is not None else c.pd.tso())


def _storage(c):
    return Storage(RaftKv(c.stores[1], driver=c._drive_until))


def _logical(ent_or_tbl, table, dag):
    """(handles, values, validity per col) via a full-range scan."""
    scan = dag.executors[0]
    src = ent_or_tbl if hasattr(ent_or_tbl, "scan_columns") else None
    batch = src.scan_columns(scan, dag.ranges)
    return [(c.values.tolist(), c.validity.tolist())
            for c in batch.columns]


def _assert_parity(c, cache, table, rig_snap=None):
    """Delta-maintained snapshot == fresh full rebuild, bit for bit."""
    ts = c.pd.tso()
    dag = _dag(c, table, ts)
    snap = _snap(c)
    ent = cache.get(snap, dag)
    scan = dag.executors[0]
    tbl, safe_ts, locks = build_region_columnar(
        snap, table.table_id, scan.columns, ts)
    assert ent.safe_ts == safe_ts, (ent.safe_ts, safe_ts)
    assert tuple(ent.blocking_locks) == tuple(locks)
    got = ent.scan_columns(scan, dag.ranges)
    want = tbl.scan_columns(scan, dag.ranges)
    assert got.num_rows == want.num_rows
    for gc, wc in zip(got.columns, want.columns):
        assert gc.values.tolist() == wc.values.tolist()
        assert gc.validity.tolist() == wc.validity.tolist()
    assert ent.estimated_rows() == len(tbl)
    return ent


# ---------------------------------------------------------------- unit


def test_delta_append_patches_without_rebuild(rig):
    c, cache, table = rig["c"], rig["cache"], rig["table"]
    c.txn_write([_mut(table, h, {2: h % 5, 3: h * 10})
                 for h in range(40)])
    ent0 = _assert_parity(c, cache, table)
    assert cache.misses == 1 and cache.deltas == 0

    c.txn_write([_mut(table, 40, {2: 1, 3: 400})])
    ent1 = _assert_parity(c, cache, table)
    assert cache.deltas == 1 and cache.misses == 1, \
        "a point append must patch, not rebuild"
    # stable lineage identity: the device feed cache anchors on it
    assert ent1.feed_lineage is ent0.feed_lineage
    assert ent1.feed_lineage.version == 1
    # the old published snapshot still serves its own version
    assert ent0.estimated_rows() == 40
    assert ent1.estimated_rows() == 41


def test_delta_update_delete_and_revive(rig):
    c, cache, table = rig["c"], rig["cache"], rig["table"]
    c.txn_write([_mut(table, h, {2: h, 3: h}) for h in range(20)])
    _assert_parity(c, cache, table)
    # positional update
    c.txn_write([_mut(table, 7, {2: 70, 3: 700})])
    ent = _assert_parity(c, cache, table)
    assert cache.deltas == 1
    # delete → tombstone (no rebuild)
    c.txn_write([("delete", _row_key(table, 3), None)])
    ent = _assert_parity(c, cache, table)
    assert cache.deltas == 2 and cache.misses == 1
    assert ent.estimated_rows() == 19
    # re-insert the deleted handle → revives the tombstoned slot
    c.txn_write([_mut(table, 3, {2: 33, 3: 333})])
    ent = _assert_parity(c, cache, table)
    assert ent.estimated_rows() == 20
    assert cache.misses == 1


def test_mid_insert_repacks_and_stays_exact(rig):
    c, cache, table = rig["c"], rig["cache"], rig["table"]
    c.txn_write([_mut(table, h, {2: h, 3: h}) for h in range(0, 40, 2)])
    _assert_parity(c, cache, table)
    c.txn_write([_mut(table, 7, {2: 7, 3: 7})])    # between 6 and 8
    ent = _assert_parity(c, cache, table)
    assert cache.deltas == 1 and cache.misses == 1
    assert 7 in ent._tbl.handles.tolist()


def test_lock_conflict_parity_under_delta(rig):
    c, cache, table = rig["c"], rig["cache"], rig["table"]
    c.txn_write([_mut(table, h, {2: h, 3: h}) for h in range(10)])
    _assert_parity(c, cache, table)
    # a blocking prewrite arrives THROUGH the delta path
    st = _storage(c)
    key = _row_key(table, 4)
    lock_ts = c.pd.tso()
    st.sched_txn_command(cmds.Prewrite(
        [Mutation("put", key, encode_row({2: 1, 3: 1}))], key, lock_ts))
    dag = _dag(c, table)
    snap = _snap(c)
    with pytest.raises(KeyIsLocked):
        cache.get(snap, dag)
    # commit resolves the lock; the delta path clears it and serves
    st.sched_txn_command(cmds.Commit([key], lock_ts, c.pd.tso()))
    _assert_parity(c, cache, table)


def test_rollback_advances_safe_ts_like_a_rebuild(rig):
    c, cache, table = rig["c"], rig["cache"], rig["table"]
    c.txn_write([_mut(table, h, {2: h, 3: h}) for h in range(8)])
    _assert_parity(c, cache, table)
    st = _storage(c)
    key = _row_key(table, 2)
    lock_ts = c.pd.tso()
    st.sched_txn_command(cmds.Prewrite(
        [Mutation("put", key, encode_row({2: 9, 3: 9}))], key, lock_ts))
    st.sched_txn_command(cmds.Rollback([key], lock_ts))
    ent = _assert_parity(c, cache, table)   # includes safe_ts parity
    assert cache.misses == 1, "rollback must ride the delta path"


def test_slack_exhaustion_compacts(rig, monkeypatch):
    monkeypatch.setattr(_LineState, "SLACK_MIN", 4)
    c, cache, table = rig["c"], rig["cache"], rig["table"]
    c.txn_write([_mut(table, h, {2: h, 3: h}) for h in range(10)])
    _assert_parity(c, cache, table)
    for start in range(10, 40, 3):
        c.txn_write([_mut(table, h, {2: h, 3: h})
                     for h in range(start, start + 3)])
        _assert_parity(c, cache, table)
    assert cache.misses == 1, "growth must compact in place, not rebuild"
    assert cache.compactions >= 1


def test_tombstone_ratio_triggers_compaction(rig):
    c, cache, table = rig["c"], rig["cache"], rig["table"]
    cache._compact_ratio = 0.2
    c.txn_write([_mut(table, h, {2: h, 3: h}) for h in range(20)])
    _assert_parity(c, cache, table)
    for h in range(0, 10, 2):
        c.txn_write([("delete", _row_key(table, h), None)])
        ent = _assert_parity(c, cache, table)
    assert cache.compactions >= 1
    assert ent._tbl.alive is None, "compaction must clear the mask"
    assert cache.misses == 1


def test_delta_log_overflow_falls_back_to_rebuild(rig):
    c, table = rig["c"], rig["table"]
    sink = DeltaSink(max_entries=2, max_rows=1 << 16)
    c.stores[1].coprocessor_host.register(sink)
    cache = RegionColumnarCache(capacity=4, delta_source=sink)
    c.txn_write([_mut(table, h, {2: h, 3: h}) for h in range(10)])
    dag = _dag(c, table)
    cache.get(_snap(c), dag)
    for h in range(10, 16):     # 6 entries through a 2-entry log
        c.txn_write([_mut(table, h, {2: h, 3: h})])
    _ent = cache.get(_snap(c), _dag(c, table))
    assert cache.rebuilds == 1 and cache.deltas == 0
    # and the rebuilt line bridges again afterwards
    c.txn_write([_mut(table, 99, {2: 9, 3: 9})])
    cache.get(_snap(c), _dag(c, table))
    assert cache.deltas == 1


def test_out_of_envelope_ops_poison_coverage():
    class Op:
        def __init__(self, op, cf, key=b"k", value=b""):
            self.op, self.cf, self.key, self.value = op, cf, key, value

    assert decode_entry_ops([Op("delete_range", "write")]) is None
    assert decode_entry_ops([Op("ingest", "default")]) is None
    assert decode_entry_ops([Op("delete", "write")]) is None
    # CF_DEFAULT traffic alone is inert
    rows, locks = decode_entry_ops([Op("put", "default"),
                                    Op("delete", "default")])
    assert rows == [] and locks == []


def test_epoch_change_falls_back_to_fresh_line(rig):
    c, cache, table = rig["c"], rig["cache"], rig["table"]
    c.txn_write([_mut(table, h, {2: h, 3: h}) for h in range(30)])
    _assert_parity(c, cache, table)
    from tikv_tpu.storage.txn_types import encode_key
    c.split_region(1, encode_key(_row_key(table, 15)))
    # region 1 now covers only the low half; its epoch bumped → the old
    # line's key never matches again, a fresh build serves correctly
    ts = c.pd.tso()
    dag = _dag(c, table, ts)
    snap = _snap(c)
    ent = cache.get(snap, dag)
    scan = dag.executors[0]
    tbl, safe_ts, _locks = build_region_columnar(
        snap, table.table_id, scan.columns, ts)
    assert ent.estimated_rows() == len(tbl) == 15
    assert cache.misses == 2 and cache.deltas == 0


def test_big_value_delta_fetches_default_cf(rig):
    """Rows whose payload spills to CF_DEFAULT (> SHORT_VALUE_MAX_LEN)
    arrive through the delta path with short_value=None — the patcher
    must fetch the spilled payload from the snapshot it bridges to."""
    from tikv_tpu.testing.fixture import product_table
    c, cache = rig["c"], rig["cache"]
    table = product_table()

    def prow(h, name: bytes, count: int):
        return ("put", _row_key(table, h),
                encode_row({2: name, 3: count}))

    def check():
        ts = c.pd.tso()
        dag = DagSelect.from_table(
            table, ["id", "name", "count"]).build(start_ts=ts)
        snap = _snap(c)
        ent = cache.get(snap, dag)
        scan = dag.executors[0]
        tbl, safe_ts, _ = build_region_columnar(
            snap, table.table_id, scan.columns, ts)
        got = ent.scan_columns(scan, dag.ranges)
        want = tbl.scan_columns(scan, dag.ranges)
        assert got.num_rows == want.num_rows
        for gc, wc in zip(got.columns, want.columns):
            assert gc.values.tolist() == wc.values.tolist()
        assert ent.safe_ts == safe_ts
        return ent

    c.txn_write([prow(h, b"n%d" % h, h) for h in range(10)])
    check()
    big = b"x" * 600                            # > SHORT_VALUE_MAX_LEN
    c.txn_write([prow(10, big, 10)])            # spilled append
    c.txn_write([prow(3, big + b"y", 33)])      # spilled update
    ent = check()
    assert cache.deltas >= 1 and cache.misses == 1
    assert ent._tbl.columns[2].values[3] == big + b"y"


# ------------------------------------------------------------ property


@pytest.mark.parametrize("seed", [0, 1])
def test_delta_vs_rebuild_randomized(rig, monkeypatch, seed):
    """>= 200 randomized rounds (2 seeds x 100): random interleavings of
    multi-row inserts/updates/deletes plus rollbacks, under forced
    small slack (growth/compaction) and an aggressive tombstone ratio.
    Every round's delta-maintained view must be bit-identical to a
    fresh rebuild."""
    monkeypatch.setattr(_LineState, "SLACK_MIN", 8)
    c, cache, table = rig["c"], rig["cache"], rig["table"]
    cache._compact_ratio = 0.3
    rng = np.random.default_rng(seed)
    live: set = set()

    # seed rows + first build
    first = [int(h) for h in rng.choice(200, size=30, replace=False)]
    c.txn_write([_mut(table, h, {2: h % 7, 3: h}) for h in first])
    live.update(first)
    prev = _assert_parity(c, cache, table)
    said = 0

    st = _storage(c)
    for _round in range(100):
        muts = []
        kind = rng.random()
        if kind < 0.45 or not live:
            # insert burst: mix of appends (above max) and mid-inserts
            base = max(live) + 1 if live and rng.random() < 0.5 else 0
            for _ in range(int(rng.integers(1, 4))):
                h = int(base + rng.integers(0, 300))
                if h not in live:
                    muts.append(_mut(table, h, {2: h % 7, 3: h}))
                    live.add(h)
        elif kind < 0.7:
            for h in rng.choice(sorted(live),
                                size=min(len(live),
                                         int(rng.integers(1, 4))),
                                replace=False):
                v = int(rng.integers(0, 1000))
                muts.append(_mut(table, int(h), {2: v % 7, 3: v}))
        elif kind < 0.9:
            for h in rng.choice(sorted(live),
                                size=min(len(live),
                                         int(rng.integers(1, 3))),
                                replace=False):
                muts.append(("delete", _row_key(table, int(h)), None))
                live.discard(int(h))
        else:
            # prewrite + rollback: no visible change, safe_ts advances
            h = int(rng.choice(sorted(live)))
            key = _row_key(table, h)
            ts = c.pd.tso()
            st.sched_txn_command(cmds.Prewrite(
                [Mutation("put", key, encode_row({2: 0, 3: 0}))],
                key, ts))
            st.sched_txn_command(cmds.Rollback([key], ts))
        if muts:
            c.txn_write(muts)
        ent = _assert_parity(c, cache, table)
        assert ent.estimated_rows() == len(live)
        # every journal entry says what its batch did (a round is one
        # transaction: one entry, or none where no row changed)
        if ent.feed_lineage is prev.feed_lineage:
            for entry in ent.feed_lineage.since(prev.feed_version,
                                                until=ent.feed_version):
                _assert_entry_says_what_the_batch_did(
                    _view(prev), _view(ent), entry)
                said += 1
        prev = ent
    assert said >= 60
    # the overwhelming majority of rounds must ride the delta path
    assert cache.deltas >= 80, (cache.deltas, cache.misses,
                                cache.rebuilds)


# ------------------------------------------------- what the journal says


def _row(pairs, i: int) -> tuple:
    """Row ``i`` of some columns' (values, validity), as Python values."""
    return tuple((v[i].item() if hasattr(v[i], "item") else v[i],
                  bool(ok[i])) for v, ok in pairs)


def _view(ent) -> dict:
    """{handle: ((value, valid) per column)} of a published line's view,
    in handle order (a dict keeps it)."""
    tbl = ent._tbl
    n = len(tbl.handles)
    keep = np.ones(n, np.bool_) if tbl.alive is None else tbl.alive
    pairs = [(tbl.columns[cid].values, tbl.columns[cid].validity)
             for cid in sorted(tbl.columns)]
    return {int(h): _row(pairs, i)
            for i, h in enumerate(tbl.handles) if keep[i]}


def _introduced(entry) -> dict:
    out = {}
    for rows in entry["introduced"]:
        pairs = [rows["cols"][cid] for cid in sorted(rows["cols"])]
        for i, h in enumerate(rows["handles"]):
            out[int(h)] = _row(pairs, i)
    return out


def _assert_entry_says_what_the_batch_did(before: dict, after: dict,
                                          entry: dict) -> None:
    """One journal entry between two views of a line: every row whose
    values the batch wrote is ``introduced`` as the line now holds it;
    ``dead``, where present, names the rows that left by their position
    in the view before, and every row the batch did not write is where
    it was, less the rows that left before it."""
    wrote = _introduced(entry)
    for h, row in wrote.items():
        assert after[h] == row, (h, after[h], row)
    changed = {h for h, row in after.items() if before.get(h) != row}
    assert changed <= set(wrote), (changed, set(wrote))
    assert entry["live"] == len(after)
    if entry["structural"]:
        assert "spans" not in entry
    else:
        assert entry["introduced"] is entry["spans"]
        assert entry["dead"] == ()
    if "dead" not in entry:
        return
    order = list(before)
    gone = [order[i] for i in entry["dead"]]
    assert gone == sorted(set(before) - set(after))
    assert list(entry["dead"]) == sorted(entry["dead"])
    left = [h for h in order if h not in gone]
    if not wrote:
        assert list(after) == left
    # the rows the batch did not write: each where it was, less the
    # rows that left before it
    kept = [(i, h) for i, h in enumerate(left) if h not in wrote]
    assert kept == [(i, h) for i, h in enumerate(after) if h not in wrote]


# per cause of ``_apply_deltas``: the line before it (handles), the
# batch, and what its entry must say
def _put(h, v=None):
    return ("put", h, v if v is not None else h)


JOURNAL_CAUSES = {
    "tail append": (
        range(0, 40, 2), [_put(40), _put(44)],
        dict(structural=False, dead=(), wrote=[40, 44])),
    "merge into the tail": (
        range(0, 40, 2), [_put(35), _put(41)],
        dict(structural=False, dead=(), wrote=[35, 36, 38, 41])),
    "update": (
        range(0, 40, 2), [_put(6, 600)],
        dict(structural=False, dead=(), wrote=[6])),
    "delete": (
        range(0, 40, 2), [("delete", 4), ("delete", 30)],
        dict(structural=True, dead=(2, 15), wrote=[])),
    "delete on a line with tombstones": (
        range(0, 40, 2), [("delete", 10), ("delete", 30)],
        dict(structural=True, dead=(3, 13), wrote=[]), [("delete", 2),
                                                       ("delete", 8)]),
    "update beside a delete": (
        range(0, 40, 2), [_put(6, 600), ("delete", 30)],
        dict(structural=True, dead=(15,), wrote=[6])),
    "append on a line with tombstones": (
        range(0, 40, 2), [_put(50)],
        dict(structural=True, dead=(), wrote=[50]), [("delete", 2)]),
    "revive": (
        range(0, 40, 2), [_put(2, 222)],
        dict(structural=True, dead=None, wrote=[2]), [("delete", 2)]),
    "mid insert: a repack": (
        range(0, 40, 2), [_put(7)],
        dict(structural=True, dead=None, wrote=[7])),
    "a repack beside an update and a delete": (
        range(0, 40, 2), [_put(7), _put(12, 1200), ("delete", 20)],
        dict(structural=True, dead=None, wrote=[7, 12])),
    "compaction at 25%": (
        range(0, 14, 2), [("delete", 6)],
        dict(structural=True, dead=None, wrote=[]), [("delete", 2)]),
}


@pytest.mark.parametrize("cause", sorted(JOURNAL_CAUSES))
def test_every_cause_journals_what_it_introduced_and_what_left(rig, cause):
    """copr/region_cache.py ``FeedLineage``: ``introduced`` and ``dead``
    on every entry ``_apply_deltas`` writes, whatever made it."""
    c, cache, table = rig["c"], rig["cache"], rig["table"]
    cache.TAIL_MERGE_ROWS = 4       # (a mid insert further in repacks)
    handles, batch, want, *before = JOURNAL_CAUSES[cause]

    def muts(ops):
        return [("delete", _row_key(table, op[1]), None)
                if op[0] == "delete"
                else _mut(table, op[1], {2: op[2] % 7, 3: op[2]})
                for op in ops]

    c.txn_write(muts([_put(h) for h in handles]))
    ent = _assert_parity(c, cache, table)
    if before:
        c.txn_write(muts(before[0]))
        ent = _assert_parity(c, cache, table)
    v0, view0, compactions = ent.feed_version, _view(ent), cache.compactions
    c.txn_write(muts(batch))
    ent = _assert_parity(c, cache, table)
    assert cache.misses == 1, "the batch must ride the delta path"
    entry, = ent.feed_lineage.since(v0, until=ent.feed_version)
    _assert_entry_says_what_the_batch_did(view0, _view(ent), entry)
    assert entry["structural"] == want["structural"]
    assert entry.get("dead") == want["dead"]
    assert sorted(_introduced(entry)) == want["wrote"]
    assert (cache.compactions > compactions) == (want["dead"] is None and
                                                 cause != "revive")
    assert cache.tail_merges == (cause == "merge into the tail")


def test_a_lock_only_batch_still_journals_nothing(rig):
    c, cache, table = rig["c"], rig["cache"], rig["table"]
    c.txn_write([_mut(table, h, {2: h, 3: h}) for h in range(8)])
    ent = _assert_parity(c, cache, table)
    v0 = ent.feed_version
    st = _storage(c)
    key = _row_key(table, 2)
    lock_ts = c.pd.tso()
    st.sched_txn_command(cmds.Prewrite(
        [Mutation("put", key, encode_row({2: 9, 3: 9}))], key, lock_ts))
    st.sched_txn_command(cmds.Rollback([key], lock_ts))
    ent = _assert_parity(c, cache, table)
    assert cache.deltas >= 1 and cache.misses == 1
    assert ent.feed_version == v0 and ent.feed_lineage.since(v0) == []


# ------------------------------------------------ the bound counts REGIONS
#
# ``RegionColumnarCache``'s bound counts REGIONS, as the option's
# documentation tells the operator (``coprocessor.region-cache-capacity``):
# a region's lines under different scan schemas stay and leave together,
# the least recently used region first, every dropped line through
# ``on_line_retired``; one region holds at most ``SCHEMAS_PER_REGION``
# lines, its least recently used line out first; lifecycle sweeps behave
# as before and are counted apart (``invalidations``, not ``evictions``).
#
# One small table in one real region; the other "regions" are the same
# engine snapshot under another region id (the cache keys a line by the
# snapshot's region and epoch and never asks what else the region is).

BOUND_ROWS = 24
# three scan schemas over one table, as Q1, Q6 and Q15 are over lineitem
SCHEMAS = {"wide": ["id", "c0", "c1"], "left": ["id", "c0"],
           "right": ["id", "c1"]}


@pytest.fixture(scope="module")
def bound_rig():
    c = Cluster(n_stores=1)
    c.bootstrap()
    c.start()
    table = int_table(2, table_id=7790)
    c.txn_write([("put", table_record_key(table.table_id, h),
                  encode_row({2: h % 5, 3: h * 10})) for h in range(BOUND_ROWS)])
    return {"c": c, "table": table}


class BoundedCache:
    """A cache of ``capacity`` regions with its retirement hook
    recorded, read as the store reads it."""

    def __init__(self, rig, capacity: int):
        self.rig = rig
        self.cache = RegionColumnarCache(capacity=capacity)
        self.retired: list = []
        self.cache.on_line_retired = self.retired.append

    def snap(self, region_id: int, version: int = 1):
        real = self.rig["c"].kvs[1].snapshot(SnapContext(region_id=1))
        region = dataclasses.replace(
            real.region, id=region_id,
            epoch=RegionEpoch(real.region.epoch.conf_ver, version))
        out = RegionSnapshot(real._snap, region)
        out.data_index = real.data_index
        return out

    def read(self, region_id: int, schema: str, version: int = 1):
        c, table = self.rig["c"], self.rig["table"]
        dag = DagSelect.from_table(table, SCHEMAS[schema]).build(
            start_ts=c.pd.tso())
        ent = self.cache.get(self.snap(region_id, version), dag)
        assert ent.estimated_rows() == BOUND_ROWS
        return ent

    def resident(self) -> dict:
        """{region: its lines' column counts, in LRU order}."""
        out: dict = {}
        for key in self.cache._lines:
            out.setdefault(key[0], []).append(len(key[3]))
        return out

    def stats(self) -> dict:
        return self.cache.stats()


def test_the_bound_counts_regions_not_lines(bound_rig):
    """Two regions under three schemas are six lines inside a bound of
    two: nothing leaves, and a second round builds nothing."""
    c = BoundedCache(bound_rig, capacity=2)
    for _round in range(2):
        for schema in SCHEMAS:
            for region in (11, 12):
                c.read(region, schema)
    st = c.stats()
    assert st["resident_lines"] == 6 and st["regions"] == 2
    assert st["schemas_per_region_max"] == 3
    assert st["misses"] == 6 and st["hits"] == 6
    assert st["evictions"] == {"region_lru": 0, "schema_bound": 0}
    assert c.retired == []


def test_a_regions_lines_leave_together_and_each_is_retired(bound_rig):
    """A third region evicts the least recently used region with every
    line it has, each through ``on_line_retired`` with its own lineage;
    the other region keeps all of its."""
    c = BoundedCache(bound_rig, capacity=2)
    lineages = {}
    for region in (11, 12):
        for schema in SCHEMAS:
            lineages[region, schema] = c.read(region, schema).feed_lineage
    c.read(13, "left")
    assert c.resident() == {12: [3, 2, 2], 13: [2]}
    assert sorted(map(id, c.retired)) == sorted(
        id(lineages[11, s]) for s in SCHEMAS)
    st = c.stats()
    assert st["evictions"] == {"region_lru": 3, "schema_bound": 0}
    assert st["invalidations"] == 0 and st["regions"] == 2
    # the evicted region is built again when it is asked, as a miss
    c.read(11, "wide")
    assert c.stats()["misses"] == 8
    assert set(c.resident()) == {13, 11}


@pytest.mark.parametrize("touched", sorted(SCHEMAS))
def test_a_region_is_as_recent_as_its_most_recent_line(bound_rig, touched):
    """A hit on ANY one line of a region keeps the whole region: the
    other region, read later but not since, is the one that leaves."""
    c = BoundedCache(bound_rig, capacity=2)
    for region in (11, 12):
        for schema in SCHEMAS:
            c.read(region, schema)
    c.read(11, touched)                     # a hit: builds nothing
    assert c.stats()["misses"] == 6
    c.read(13, "wide")
    assert set(c.resident()) == {11, 13}
    assert len(c.resident()[11]) == 3 and len(c.retired) == 3


def test_the_schema_bound_evicts_the_oldest_schemas_line_only(
        bound_rig, monkeypatch):
    """Past ``SCHEMAS_PER_REGION`` lines of ONE region its least
    recently used line leaves, alone: the region stays, so do its other
    lines and every other region's."""
    monkeypatch.setattr(region_cache, "SCHEMAS_PER_REGION", 2)
    c = BoundedCache(bound_rig, capacity=4)
    wide = c.read(11, "wide").feed_lineage
    c.read(11, "left")
    c.read(12, "wide")
    c.read(11, "wide")                      # "left" is now 11's oldest
    left = next(ln for key, ln in c.cache._lines.items()
                if key[0] == 11 and len(key[3]) == 2).state.lineage
    c.read(11, "right")
    assert c.resident() == {12: [3], 11: [3, 2]}
    assert c.retired == [left] and left is not wide
    st = c.stats()
    assert st["evictions"] == {"region_lru": 0, "schema_bound": 1}
    assert st["schemas_per_region_max"] == 2 and st["regions"] == 2


def test_the_default_schema_bound_is_small_and_fixed():
    assert region_cache.SCHEMAS_PER_REGION == 8


def test_a_smaller_capacity_set_online_holds_at_the_next_build(bound_rig):
    """``server/node.py`` sets ``_capacity`` in place; the next line
    built brings the cache under it, whole regions at a time."""
    c = BoundedCache(bound_rig, capacity=4)
    for region in (11, 12, 13, 14):
        for schema in ("wide", "left"):
            c.read(region, schema)
    assert c.stats()["regions"] == 4 and c.stats()["resident_lines"] == 8
    c.cache._capacity = 2
    c.read(14, "right")
    assert c.resident() == {13: [3, 2], 14: [3, 2, 2]}
    assert c.stats()["evictions"]["region_lru"] == 4
    assert len(c.retired) == 4


@pytest.mark.parametrize("keep_epoch", [None, 1, 2])
def test_lifecycle_sweeps_behave_as_before(bound_rig, keep_epoch):
    """``invalidate_region`` drops a region's lines (all of them, or
    the superseded epochs'), retires each, and counts them as
    ``invalidations``: the bound's evictions are another counter."""
    c = BoundedCache(bound_rig, capacity=4)
    for schema in SCHEMAS:
        c.read(11, schema, version=1)
    c.read(11, "wide", version=2)
    c.read(12, "wide")
    want = {None: 4, 1: 1, 2: 3}[keep_epoch]
    assert c.cache.invalidate_region(11, keep_epoch=keep_epoch) == want
    assert len(c.retired) == want
    st = c.stats()
    assert st["invalidations"] == want
    assert st["evictions"] == {"region_lru": 0, "schema_bound": 0}
    assert st["resident_lines"] == 5 - want
    assert c.resident()[12] == [3]
    if keep_epoch is None:
        assert 11 not in c.resident() and st["regions"] == 1
    else:
        assert {key[1] for key in c.cache._lines if key[0] == 11} == \
            {keep_epoch}
    # an epoch below the floor is served and not cached, as before
    if keep_epoch == 2:
        c.read(11, "left", version=1)
        assert all(key[1] == 2 for key in c.cache._lines if key[0] == 11)


def test_two_epochs_of_one_region_are_one_region(bound_rig):
    """A split's superseded lines beside the children's count as ONE
    region until the sweep behind the split retires them."""
    c = BoundedCache(bound_rig, capacity=2)
    c.read(11, "wide", version=1)
    c.read(11, "wide", version=2)
    c.read(12, "wide")
    st = c.stats()
    assert st["regions"] == 2 and st["resident_lines"] == 3
    assert st["evictions"] == {"region_lru": 0, "schema_bound": 0}


def test_a_capacity_of_zero_keeps_nothing(bound_rig):
    c = BoundedCache(bound_rig, capacity=0)
    c.read(11, "wide")
    c.read(11, "wide")
    st = c.stats()
    assert st["resident_lines"] == 0 and st["misses"] == 2
    assert st["evictions"]["region_lru"] == 2
