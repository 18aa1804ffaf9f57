"""A feed patch's span is widened on the host to a bucket length
(device/feed.py ``PATCH_BUCKETS``): spans of every length share a few
update programs, all run once by a line's first patch, and a line patched
through a widened span holds, plane for plane and digest for digest, what
a cold build of the patched data holds: for an int64 handle plane, an
int32 plane, a date plane, a CHAR(1) code plane and a scaled DECIMAL
plane; for an update inside the line and for an append at its tail.  A
window is ONE program over every plane of the feed, updates and digest
chain together (``FeedStore._patch_program``): held here for a NULL-flagged
column's two planes, windows clipped at ``n_pad``, a gap of generations,
a store that records no digests, a corrupted plane (the chain is not
laundered) and a sharded feed.  The rig is tests/test_decimal_planes.py's
``one rule, cold and patched``."""

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
from tikv_tpu.device.feed import PATCH_BUCKETS, anchor, patch_bucket
from tikv_tpu.device.supervisor import (
    DeviceStateSupervisor, host_plane_digest,
)
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
    write journalled as the region cache journals it.  ``nulls``: column
    ``a`` holds NULLs from the start (its feed carries a validity
    plane), and a write brings more."""

    def __init__(self, n: int, seed: int = 45, nulls: bool = False):
        self.rng = np.random.default_rng(seed)
        self.handles = _HANDLE0 + np.arange(n, dtype=np.int64)
        self.cols = make_cols(self.rng, n)
        self.valid = self._validity(n) if nulls else None
        self.lineage = FeedLineage()
        self.v = 0

    def _validity(self, k: int) -> np.ndarray:
        return self.rng.random(k) > 0.2

    def view(self):
        """The line's rows as a plain snapshot (a cold build's)."""
        snap = _kinds_snapshot(TABLE, self.handles, self.cols)
        if self.valid is not None:
            snap.columns[2].validity[:] = self.valid
        return snap

    def snapshot(self):
        snap = self.view()
        snap.feed_lineage, snap.feed_version = self.lineage, self.v
        return snap

    def _journal(self, lo: int, hi: int) -> None:
        new = self.view()
        self.lineage.record({"n": len(self.handles), "spans": [{
            "lo": lo, "hi": hi, "handles": self.handles[lo:hi],
            "cols": {c.col_id: (new.columns[c.col_id].values[lo:hi],
                                new.columns[c.col_id].validity[lo:hi])
                     for c in TABLE.columns if not c.is_pk_handle}}]})
        self.v += 1

    def append(self, k: int) -> None:
        n = len(self.handles)
        more = make_cols(self.rng, k)
        self.handles = np.append(self.handles,
                                 self.handles[-1] + 1 + np.arange(k))
        self.cols = {name: (self.cols[name] + more[name] if name == "f"
                            else np.append(self.cols[name], more[name]))
                     for name in self.cols}
        if self.valid is not None:
            self.valid = np.append(self.valid, self._validity(k))
        self._journal(n, n + k)

    def update(self, lo: int, k: int) -> None:
        more = make_cols(self.rng, k)
        cols = {name: (list(v) if name == "f" else v.copy())
                for name, v in self.cols.items()}
        for name in cols:
            cols[name][lo:lo + k] = more[name]
        self.cols = cols
        if self.valid is not None:
            self.valid = self.valid.copy()
            self.valid[lo:lo + k] = self._validity(k)
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


def one_device():
    return DeviceRunner(mesh=make_mesh(jax.devices()[:1]))


def host_truth(line: Line) -> list:
    """Per plane of the line's feed, in ``flat``'s order, what the host
    holds: a column's plane values, then its validity where the column
    holds a NULL."""
    truth = [line.handles, line.cols["a"]]
    if line.valid is not None:
        truth.append(line.valid)
    return truth + [line.cols["d"] >> np.uint64(41),
                    np.array([v[0] for v in line.cols["f"]]),
                    line.cols["q"]]


def assert_feed_is_the_cold_build(line: Line, feed: dict,
                                  make_runner=one_device,
                                  but=None) -> None:
    """``feed`` holds, array for array and digest for digest, what
    ``make_runner()`` builds cold from the line's rows, and both are the
    host truth's; ``but`` = (plane, row): the one element a fault was
    injected at, which the planes may differ in and the digests not."""
    cold_runner = make_runner()
    cold_runner.scrub_digests = "digests" in feed
    how, built = serve(cold_runner, line.view())
    assert how == "upload"
    assert feed["kinds"] == built["kinds"] == (None, None, "date", 1, None)
    assert feed["null_flags"] == built["null_flags"] == \
        (False, line.valid is not None, False, False, False)
    assert feed["n_pad"] == built["n_pad"]
    m = len(line.handles)
    truth = host_truth(line)
    assert len(feed["flat"]) == len(built["flat"]) == len(truth)
    for fi, want in enumerate(truth):
        got = np.asarray(feed["flat"][fi])
        cold = np.asarray(built["flat"][fi])
        assert got.dtype == cold.dtype, fi
        if but is not None and but[0] == fi:
            assert got[but[1]] != cold[but[1]], "the fault is still there"
            got = got.copy()
            got[but[1]] = cold[but[1]]
        assert np.array_equal(got, cold), fi
        assert np.array_equal(got[:m], want), fi
        assert not got[m:].any(), "the pad stays zero"
        if "digests" in feed:
            assert int(np.asarray(feed["digests"][fi])) == \
                int(np.asarray(built["digests"][fi])) == \
                host_plane_digest(want.astype(got.dtype), m), fi
    assert ("digests" in feed) == ("digests" in built)


@pytest.mark.parametrize("write", ["append_1", "append_7", "append_40",
                                   "update_3", "update_300",
                                   "update_at_the_end", "two_generations"])
def test_a_line_patched_through_a_widened_span_equals_a_cold_build(write):
    runner = one_device()
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
    the first runs every bucket's program for the feed's class (its
    planes' dtypes in order, ``n_pad``), and no later one adds a compile
    class or a kernel-cache entry."""
    runner = one_device()
    line = Line(40000)
    assert serve(runner, line.snapshot())[0] == "upload"
    line.append(1)
    assert serve(runner, line.snapshot())[0] == "patch"
    fn = runner._kernel_cache["feed_patch_fn"]
    warm = fn._cache_size()
    assert warm == len(PATCH_BUCKETS)   # one program a bucket: five planes
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
    # 4097 rows are two windows, 9000 three: one program each
    assert counts["patch_programs"] == counts["patch_windows"] == \
        sum(counts["patch_buckets"].values()) == 1 + len(lengths) + 3
    # and the digest chain forty windows long is what the device holds
    scrubbed = DeviceStateSupervisor(runner=runner).scrub()
    assert scrubbed["lines"] == 1 and scrubbed["divergences"] == 0
    assert set(map(int, counts["patch_buckets"])) == set(PATCH_BUCKETS)


def small_blocks():
    """Feeds pad to whole blocks of 4,096 rows, so a line can fill its
    last block to within a few rows."""
    return DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                        chunk_rows=1 << 12)


def whole_mesh():
    return DeviceRunner(mesh=make_mesh(jax.devices()))


@pytest.mark.parametrize("case", [
    "null_plane", "date_and_code_planes", "two_windows",
    "clipped_at_n_pad", "the_whole_plane", "no_digests",
    "corruption_survives", "sharded"])
def test_a_window_is_one_program_over_every_plane(case):
    """Whatever the feed's planes and wherever the window lies, a patch
    sends ONE program a window (the flight recorder counts them at the
    call), and the line then holds the cold build array for array, its
    digests the host truth's."""
    make_runner = {"clipped_at_n_pad": small_blocks,
                   "the_whole_plane": small_blocks,
                   "sharded": whole_mesh}.get(case, one_device)
    runner = make_runner()
    if case == "no_digests":
        runner.scrub_digests = False
    n = 4093 if make_runner is small_blocks else 2000
    line = Line(n, nulls=case == "null_plane")
    how, feed = serve(runner, line.snapshot())
    assert how == "upload"
    windows, but = 1, None
    if case == "null_plane":
        # a value plane and its validity plane leave together; the
        # written rows bring NULLs to rows that had none, and back
        assert feed["null_flags"][1] and len(feed["flat"]) == 6
        line.update(40, 12)
    elif case == "date_and_code_planes":
        line.update(50, 20)     # 256 rows of every plane, date and code too
    elif case == "two_windows":
        # three generations between two reads: the two updates share a
        # start and so a window, the wider one's; the append is another
        line.update(100, 5)
        line.update(100, 30)
        line.append(3)
        windows = 2
    elif case == "clipped_at_n_pad":
        # rows 4,088-4,092 of 4,096: the window of 16 starts at 4,080
        assert feed["n_pad"] == 4096
        line.update(4088, 5)
    elif case == "the_whole_plane":
        # a bucket as long as the plane: the window is the plane
        assert feed["n_pad"] == PATCH_BUCKETS[-1]
        line.update(7, 300)
    elif case == "no_digests":
        assert "digests" not in feed
        line.append(2)
    elif case == "corruption_survives":
        # an HBM fault in a plane a later patch writes ELSEWHERE: the
        # recorded digest stays the host truth's (asserted below with
        # every other), so the device's own re-hash still differs from
        # it and the next scrub finds the fault
        runner._feeds.corrupt_resident_plane(feed)
        line.update(500, 3)
        but = (0, 0)
    else:
        assert len(jax.devices()) == 8
        assert all(a.sharding.is_equivalent_to(runner._row_sharding, 1)
                   for a in feed["flat"])
        line.update(60, 7)
        line.append(2)
        windows = 2
    how, patched = serve(runner, line.snapshot())
    assert how == "patch"
    assert patched is feed
    assert_feed_is_the_cold_build(line, patched, make_runner, but)
    counts = runner.flight_recorder.feed_counts()
    assert counts["patches"] == 1
    assert counts["patch_windows"] == counts["patch_programs"] == windows
    assert sum(counts["patch_buckets"].values()) == windows
    if case == "the_whole_plane":
        assert counts["patch_buckets"] == {str(PATCH_BUCKETS[-1]): 1}
    scrubbed = DeviceStateSupervisor(runner=runner).scrub()
    assert scrubbed["lines"] == ("digests" in patched)
    assert scrubbed["divergences"] == (case == "corruption_survives")
    if case == "sharded":
        assert all(a.sharding.is_equivalent_to(runner._row_sharding, 1)
                   for a in patched["flat"])
        assert all(d.sharding.is_fully_replicated
                   for d in patched["digests"])


def test_a_refused_patch_dispatches_nothing():
    """Every window's updates are gathered on the host before the first
    program leaves: a gap whose LAST window holds a column's first NULL
    is refused before its first window was sent."""
    runner = one_device()
    line = Line(2000)
    assert serve(runner, line.snapshot())[0] == "upload"
    line.update(10, 3)
    line.update(1500, 3)
    snap = line.snapshot()
    snap.columns[2].validity[1501] = False
    calls = []
    program = runner._feeds._patch_program
    runner._feeds._patch_program = lambda: calls.append(1) or program()
    assert serve(runner, snap)[0] == "rebuild"
    assert not calls
    counts = runner.flight_recorder.feed_counts()
    assert counts["rebuilds_after_delta"]["null"] == 1
    assert counts["patch_windows"] == counts["patch_programs"] == 0


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
