"""A tombstone's rebuild is sourced from the resident feed (device/feed.py
``FeedStore._try_compact_feed``): a gap of delete-only journal entries is
applied to the device planes by ONE compaction program of
``COMPACT_RUNS`` runs, digests chained, and the line then holds,
plane for plane and digest for digest, what a host rebuild of the same
generation holds: ``n_live``, ``null_flags``, the zero pad and
``host_plane_digest`` of the view; a later append patches the compacted
feed; everything the rung does not take (an entry that does not say
which rows died, a written row in the gap, a crossed pad bucket, a
sharded feed, more runs than the program takes) is built again from the
host with the same answer; and a line's first compaction compiles the
one program it will ever run.  The rig
is tests/test_feed_patch_buckets.py's hand-made line."""

import numpy as np
import pytest

from test_feed_patch_buckets import (
    Line, assert_feed_is_the_cold_build, one_device, serve, small_blocks,
    whole_mesh,
)
from tikv_tpu.device.feed import COMPACT_RUNS, dead_runs
from tikv_tpu.device.supervisor import DeviceStateSupervisor


class DeletedLine(Line):
    """``Line`` that also takes deletes, journalled as the region cache
    journals a tombstone batch (``structural``, ``dead`` in the view's
    numbering before it, ``live``, nothing ``introduced``)."""

    def delete(self, rows, said: bool = True) -> None:
        n = len(self.handles)
        keep = np.ones(n, np.bool_)
        keep[list(rows)] = False
        self.handles = self.handles[keep]
        self.cols = {name: ([x for x, k in zip(v, keep) if k]
                            if name == "f" else v[keep])
                     for name, v in self.cols.items()}
        if self.valid is not None:
            self.valid = self.valid[keep]
        entry = {"n": n, "live": len(self.handles), "structural": True,
                 "introduced": []}
        if said:
            entry["dead"] = tuple(sorted(rows))
        self.lineage.record(entry)
        self.v += 1

    def append_beside_tombstones(self, k: int) -> None:
        """An append as a line with tombstones journals it: the rows it
        wrote, no spans."""
        n = len(self.handles)
        self.append(k)
        entry = self.lineage._patches[-1]
        self.lineage._patches[-1] = {
            "n": entry["n"], "live": n + k, "dead": (),
            "structural": True, "introduced": entry["spans"]}


def gap_of(line: DeletedLine, entries: int) -> None:
    """``entries`` delete-only batches: runs at the head, at the head
    again (adjacent to the first, in the numbering before it), in the
    middle, the last row; then short runs at the head (an RF2 order
    each), behind one row that stays, and at four places further in,
    turn by turn: several lie side by side and fold."""
    if entries == 1:
        line.delete([0, 1, 2, 700, 701, len(line.handles) - 1])
        return
    line.delete([0, 1, 2, 3])
    line.delete([0, 1])
    line.delete([500, 501, len(line.handles) - 1])
    for i in range(entries - 3):
        at = (0, 1, 900, 1200, 1500, 1800)[i % 6]
        line.delete(range(at, at + 1 + i % 3))


def counts(runner) -> dict:
    return runner.flight_recorder.feed_counts()


@pytest.mark.parametrize("entries", [1, 3, 20])
@pytest.mark.parametrize("nulls", [False, True],
                         ids=["date_code_decimal", "nullable"])
def test_a_compacted_feed_equals_a_host_rebuild(nulls, entries):
    runner = one_device()
    line = DeletedLine(2000, nulls=nulls)
    how, feed = serve(runner, line.snapshot())
    assert how == "upload" and feed["n_live"] == 2000
    gap_of(line, entries)
    runs = dead_runs(line.lineage.since(0))
    assert sum(length for _s, length in runs) == 2000 - len(line.handles)
    assert runs == sorted(runs) and all(
        a + la < b for (a, la), (b, _l) in zip(runs, runs[1:]))
    if entries == 3:
        # the two head batches fold into one run
        assert runs == [[0, 6], [506, 2], [1999, 1]]
    how, compacted = serve(runner, line.snapshot())
    assert how == "compact"
    assert compacted is feed, "the feed dict keeps its identity"
    assert feed["n_live"] == len(line.handles)
    assert feed["lineage_v"] == line.v
    assert_feed_is_the_cold_build(line, feed)
    if entries == 20:
        # twenty batches fold into fewer runs: one program's
        assert 8 < len(runs) <= COMPACT_RUNS
    got = counts(runner)
    assert got["rebuilds_after_delta"]["structural"] == 1
    assert got["rebuild_source"] == {"device": 1, "host": 0}
    assert got["compact_rows"] == 2000 - len(line.handles)
    assert got["compact_programs"] == 1
    assert got["patches"] == 0 and got["after_delta"] == 1
    # the line's audit record follows, as after a patch
    (_v, mirrored), = line.lineage.feed_digests.values()
    assert [int(np.asarray(d)) for d in mirrored] == \
        [int(np.asarray(d)) for d in feed["digests"]]
    scrubbed = DeviceStateSupervisor(runner=runner).scrub()
    assert scrubbed["lines"] == 1 and scrubbed["divergences"] == 0
    # a later append patches the compacted feed: positions still map
    line.append(5)
    how, patched = serve(runner, line.snapshot())
    assert how == "patch" and patched is feed
    assert_feed_is_the_cold_build(line, feed)
    assert counts(runner)["patches"] == 1


def test_a_store_that_records_no_digests_compacts_the_planes_alone():
    runner = one_device()
    runner.scrub_digests = False
    line = DeletedLine(2000)
    how, feed = serve(runner, line.snapshot())
    assert how == "upload" and "digests" not in feed
    gap_of(line, 3)
    assert serve(runner, line.snapshot())[0] == "compact"
    assert "digests" not in feed and feed["n_live"] == len(line.handles)
    assert_feed_is_the_cold_build(line, feed)


@pytest.mark.parametrize("why", [
    "an_entry_without_dead", "an_insert_in_the_gap",
    "a_crossed_pad_bucket", "a_sharded_runner",
    "more_runs_than_the_program_takes"])
def test_the_rung_declines_to_the_host_rebuild(why):
    make_runner = {"a_crossed_pad_bucket": small_blocks,
                   "a_sharded_runner": whole_mesh}.get(why, one_device)
    runner = make_runner()
    line = DeletedLine(4100 if why == "a_crossed_pad_bucket" else 2000)
    how, feed = serve(runner, line.snapshot())
    assert how == "upload"
    line.delete([0, 1, 2])
    if why == "an_entry_without_dead":
        line.delete([7], said=False)    # (a compaction, a revive)
    elif why == "an_insert_in_the_gap":
        line.append_beside_tombstones(2)
    elif why == "a_crossed_pad_bucket":
        assert feed["n_pad"] == 8192
        line.delete(range(100, 110))    # 4,087 rows: one block
    elif why == "more_runs_than_the_program_takes":
        line.delete(range(10, 10 + 2 * COMPACT_RUNS, 2))
    calls = []
    program = runner._feeds._compact_program
    runner._feeds._compact_program = lambda: calls.append(1) or program()
    how, rebuilt = serve(runner, line.snapshot())
    assert how == "rebuild" and not calls
    assert rebuilt is not feed
    assert_feed_is_the_cold_build(line, rebuilt, make_runner)
    got = counts(runner)
    assert got["rebuilds_after_delta"] == {
        "structural": 1, "pad": 0, "dtype": 0, "null": 0}
    assert got["rebuild_source"] == {"device": 0, "host": 1}
    assert got["compact_rows"] == got["compact_programs"] == 0


def test_no_program_is_built_after_a_lines_first_compaction():
    """The first compaction compiles the program for the feed's class
    (its planes' dtypes in order, ``n_pad``); ten further gaps of
    different lengths and run counts add no compile class and no
    kernel-cache entry."""
    runner = one_device()
    line = DeletedLine(40000)
    assert serve(runner, line.snapshot())[0] == "upload"
    line.delete([0])
    how, feed = serve(runner, line.snapshot())
    assert how == "compact"
    fn = runner._kernel_cache["feed_compact_fn"]
    warm = fn._cache_size()
    assert warm == 1
    kernels = set(runner._kernel_cache)
    for i in range(10):
        # 1-7 rows at the head (an RF2 order), then i more runs
        line.delete(range(1 + i % 7))
        for j in range(i):
            line.delete(range(1000 * (j + 1), 1000 * (j + 1) + 1 + j % 4))
        how, again = serve(runner, line.snapshot())
        assert how == "compact" and again is feed, (i, how)
    assert fn._cache_size() == warm
    assert set(runner._kernel_cache) == kernels
    assert_feed_is_the_cold_build(line, feed)
    got = counts(runner)
    assert got["rebuild_source"] == {"device": 11, "host": 0}
    assert got["compact_programs"] == 11
    scrubbed = DeviceStateSupervisor(runner=runner).scrub()
    assert scrubbed["lines"] == 1 and scrubbed["divergences"] == 0
