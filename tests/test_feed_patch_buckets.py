"""A feed patch's span is widened on the host to a bucket length
(device/feed.py ``PATCH_BUCKETS``): spans of every length share a few
update programs, all run once by a line's first patch, and a line patched
through a widened span holds, plane for plane and digest for digest, what
a cold build of the patched data holds: for an int64 handle plane, an
int32 plane, a date plane, a CHAR(1) code plane and a scaled DECIMAL
plane; for an update inside the line and for an append at its tail.  The
rig is tests/test_decimal_planes.py's ``one rule, cold and patched``."""

import numpy as np
import pytest

import jax

from test_decimal_planes import (
    CHAR1, DATE, DEC2, PK, _HANDLE0, _kinds_snapshot,
)
from tikv_tpu.copr.region_cache import FeedLineage
from tikv_tpu.datatype import EvalType, FieldType
from tikv_tpu.datatype.time import pack_datetime
from tikv_tpu.device import DeviceRunner
from tikv_tpu.device.feed import (
    PATCH_BUCKETS, anchor, patch_bucket, value_plane_index,
)
from tikv_tpu.device.supervisor import host_plane_digest
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.expr import Expr
from tikv_tpu.parallel import make_mesh
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn
from tikv_tpu.utils import tracker

TABLE = Table(8845, (PK, TableColumn("a", 2, FieldType.long()),
                     TableColumn("d", 3, DATE),
                     TableColumn("f", 4, CHAR1),
                     TableColumn("q", 5, DEC2)))


def test_the_buckets():
    assert PATCH_BUCKETS == tuple(sorted(PATCH_BUCKETS))
    assert [patch_bucket(n) for n in (1, 7, 16, 17, 28, 256, 257, 4096)] \
        == [16, 16, 16, 256, 256, 256, 4096, 4096]
    # past the last bucket: windows of the last, and no other length
    assert [patch_bucket(n) for n in (4097, 8192, 8193)] == \
        [PATCH_BUCKETS[-1]] * 3


def make_cols(rng, n: int) -> dict:
    return {"a": rng.integers(-10 ** 5, 10 ** 5, n),
            "d": pack_datetime(rng.integers(1993, 1996, n),
                               rng.integers(1, 13, n),
                               rng.integers(1, 29, n)),
            "f": [(b"R", b"A", b"N")[i] for i in rng.integers(0, 3, n)],
            "q": rng.integers(-10 ** 6, 10 ** 6, n)}


def dag():
    s = DagSelect.from_table(TABLE, ["id", "a", "d", "f", "q"])
    return s.where(
        Expr.call("GeTime", s.col("d"), Expr.const(
            int(pack_datetime(1994, 1, 1)), EvalType.DATETIME)),
    ).aggregate([s.col("f")], [("sum", s.col("q")), ("sum", s.col("a")),
                               ("max", s.col("id"))]).build()


class Line:
    """A delta-maintained line by hand: snapshots of one lineage, each
    write journalled as the region cache journals it."""

    def __init__(self, n: int, seed: int = 45):
        self.rng = np.random.default_rng(seed)
        self.handles = _HANDLE0 + np.arange(n, dtype=np.int64)
        self.cols = make_cols(self.rng, n)
        self.lineage = FeedLineage()
        self.v = 0

    def snapshot(self):
        snap = _kinds_snapshot(TABLE, self.handles, self.cols)
        snap.feed_lineage, snap.feed_version = self.lineage, self.v
        return snap

    def _journal(self, lo: int, hi: int) -> None:
        new = _kinds_snapshot(TABLE, self.handles, self.cols)
        ones = np.ones(hi - lo, np.bool_)
        self.lineage.record({"n": len(self.handles), "spans": [{
            "lo": lo, "hi": hi, "handles": self.handles[lo:hi],
            "cols": {c.col_id: (new.columns[c.col_id].values[lo:hi], ones)
                     for c in TABLE.columns if not c.is_pk_handle}}]})
        self.v += 1

    def append(self, k: int) -> None:
        n = len(self.handles)
        more = make_cols(self.rng, k)
        self.handles = np.append(self.handles,
                                 _HANDLE0 + n + np.arange(k))
        self.cols = {name: (self.cols[name] + more[name] if name == "f"
                            else np.append(self.cols[name], more[name]))
                     for name in self.cols}
        self._journal(n, n + k)

    def update(self, lo: int, k: int) -> None:
        more = make_cols(self.rng, k)
        cols = {name: (list(v) if name == "f" else v.copy())
                for name, v in self.cols.items()}
        for name in cols:
            cols[name][lo:lo + k] = more[name]
        self.cols = cols
        self._journal(lo, lo + k)


def serve(runner, snap) -> tuple:
    tr, tok = tracker.install()
    try:
        got = runner.handle_request(dag(), snap)
    finally:
        tracker.uninstall(tok)
    assert sorted(got.rows()) == sorted(
        BatchExecutorsRunner(dag(), snap).handle_request().rows())
    feed, = [v for v in runner._arena.bucket(anchor(snap)).values()
             if isinstance(v, dict) and "flat" in v]
    return tr.time_detail()["labels"]["device_feed"], feed


def assert_feed_is_the_cold_build(line: Line, feed: dict) -> None:
    cold_runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    how, built = serve(cold_runner, _kinds_snapshot(
        TABLE, line.handles, line.cols))
    assert how == "upload"
    assert feed["kinds"] == built["kinds"] == (None, None, "date", 1, None)
    m = len(line.handles)
    truth = [line.handles, line.cols["a"],
             line.cols["d"] >> np.uint64(41),
             np.array([v[0] for v in line.cols["f"]]), line.cols["q"]]
    for fi, want in zip(value_plane_index(feed["null_flags"]), truth):
        got = np.asarray(feed["flat"][fi])
        assert np.array_equal(got, np.asarray(built["flat"][fi])), fi
        assert np.array_equal(got[:m], want), fi
        assert not got[m:].any(), "the pad stays zero"
        assert int(np.asarray(feed["digests"][fi])) == \
            int(np.asarray(built["digests"][fi])) == \
            host_plane_digest(want.astype(got.dtype), m), fi


@pytest.mark.parametrize("write", ["append_1", "append_7", "append_40",
                                   "update_3", "update_300",
                                   "update_at_the_end", "two_generations"])
def test_a_line_patched_through_a_widened_span_equals_a_cold_build(write):
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    line = Line(2000)
    assert serve(runner, line.snapshot())[0] == "upload"
    if write == "two_generations":
        # a gap of two patches whose windows overlap: each window is
        # written once, as the line stands at the later generation
        line.update(100, 5)
        line.append(3)
    elif write == "update_at_the_end":
        line.update(1995, 5)    # the window runs into the pad
    else:
        op, k = write.split("_")
        getattr(line, op)(*((int(k),) if op == "append"
                            else (50, int(k))))
    how, patched = serve(runner, line.snapshot())
    assert how == "patch"
    assert_feed_is_the_cold_build(line, patched)
    counts = runner.flight_recorder.feed_counts()
    assert counts["patches"] == counts["after_delta"] == 1
    assert sum(counts["rebuilds_after_delta"].values()) == 0
    assert set(map(int, counts["patch_buckets"])) <= \
        set(PATCH_BUCKETS) | {patched["n_pad"]}


def test_patches_of_every_length_run_three_programs():
    """Forty patches of forty different lengths (appends of 1-28 rows as
    a refresh stream sends them, updates up to nine thousand rows):
    the first runs every bucket's program, and no later one adds a
    compile class: at most one a (bucket, plane dtype)."""
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    line = Line(40000)
    assert serve(runner, line.snapshot())[0] == "upload"
    line.append(1)
    assert serve(runner, line.snapshot())[0] == "patch"
    fn = runner._kernel_cache["feed_patch_fn"]
    dtypes = {"int64", "int32"}         # the handle plane, the four others
    warm = fn._cache_size()
    assert warm == len(PATCH_BUCKETS) * len(dtypes)
    kernels = len(runner._kernel_cache)
    # (past the last bucket a span is cut into windows of it: 4097 is
    # one of 4,096 rows and one of 16, 9000 two and one of 808 → 4,096)
    lengths = list(range(2, 29)) + [33, 100, 255, 256, 257, 1000, 2048,
                                    3000, 4000, 4096, 4097, 9000]
    for i, k in enumerate(lengths):
        if i % 2:
            line.update(7 * i, k)
        else:
            line.append(k)
        how, feed = serve(runner, line.snapshot())
        assert how == "patch", (k, how)
    assert fn._cache_size() == warm
    assert len(runner._kernel_cache) == kernels
    assert_feed_is_the_cold_build(line, feed)
    counts = runner.flight_recorder.feed_counts()
    assert counts["patches"] == 1 + len(lengths)
    assert counts["patch_rows"] == 1 + sum(lengths)
    assert set(map(int, counts["patch_buckets"])) == set(PATCH_BUCKETS)


@pytest.mark.parametrize("why", ["structural", "pad", "null"])
def test_a_rebuild_after_a_delta_is_counted_by_its_cause(why):
    """(``dtype`` has no case: a written value that leaves a plane's
    dtype makes the request memo derive the dtypes again first, the
    dtypes are part of the feed's key, and the wider feed is a cold
    ``upload`` under a key of its own.)"""
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    line = Line(2000)
    assert serve(runner, line.snapshot())[0] == "upload"
    if why == "structural":
        line.append(2)
        line.lineage._patches[-1] = {"structural": True,
                                     "n": len(line.handles)}
    elif why == "pad":
        line.append(runner._feeds.pad_rows(2000) - 2000 + 1)
    else:
        line.update(10, 1)
    snap = line.snapshot()
    if why == "null":
        snap.columns[2].validity[10] = False    # the column's first NULL
    tr, tok = tracker.install()
    try:
        runner.handle_request(dag(), snap)
    finally:
        tracker.uninstall(tok)
    td = tr.time_detail()
    assert td["labels"]["device_feed"] == "rebuild"
    assert "feed_rebuild" in td["phases_ms"]
    assert "feed_upload" not in td["phases_ms"]
    counts = runner.flight_recorder.feed_counts()
    assert counts["rebuilds_after_delta"] == {
        k: int(k == why) for k in ("structural", "pad", "dtype", "null")}
    assert counts["patches"] == 0 and counts["after_delta"] == 1
