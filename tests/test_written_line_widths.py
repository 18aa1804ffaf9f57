"""A written line's kernel constants under a LOWERED plan (DECIMAL
planes, a SUM of a product): the byte planes a SUM's argument is cut
into and the limb split are proven from the columns' BOUNDS
(device/lowering.py ``agg_intervals`` / ``fit``), which a written row
may leave while every value still fits its plane's dtype.  The request
memo of such a line derives them again after a write (device/runner.py
``_refresh_meta``), whether the feed under it was patched forward or
built again; the answer is numpy's and the host pipeline's, on the
Pallas body in interpret mode (the only one that cuts byte planes).

Where the journal entry SAYS what the write did (``introduced`` /
``dead``: copr/region_cache.py ``FeedLineage``), the memo's derived
record is rolled across it instead (device/feed.py ``roll_derived``):
kept where every constant is proved again from the widened bounds and
the new row count, dropped by its cause where one is not, its host
planes kept with the tombstones they lag by and cut to the rows those
left where they are next read (``HostPlanes.stream``: the feed itself is
compacted on the device and reads none); counted on
``FlightRecorder.memo_counts`` (/health ``device_mesh.memo``)."""

import numpy as np
import pytest

import jax  # noqa: F401 — the interpret rig's runner needs its devices

from test_pallas_hash_interpret import (  # noqa: F401 — fixture
    BLOCK, _lane_builds_done, _runner, interpret,
)
from tikv_tpu.copr.region_cache import FeedLineage
from tikv_tpu.datatype import (
    Column, EvalType, FieldType, FieldTypeFlag, FieldTypeTp,
)
from tikv_tpu.device import feed as feed_mod
from tikv_tpu.device.feed import HostPlanes, anchor
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.expr import Expr
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn
from tikv_tpu.utils import tracker

DEC2 = FieldType(tp=FieldTypeTp.NEW_DECIMAL, flag=FieldTypeFlag.NOT_NULL,
                 flen=15, decimal=2)
TABLE = Table(8846, (
    TableColumn("id", 1, FieldType.long(not_null=True), is_pk_handle=True),
    TableColumn("x", 2, DEC2), TableColumn("m", 3, DEC2),
    TableColumn("g", 4, FieldType.long(not_null=True))))
N = 2 * BLOCK + 55


def dag():
    s = DagSelect.from_table(TABLE, ["id", "x", "m", "g"])
    return s.aggregate([s.col("g")], [
        ("sum", Expr.call("MultiplyDecimal", s.col("x"), s.col("m"))),
        ("count_star", None)]).build()


class Line:
    """One lineage by hand: small values at first (a product of 14
    bits: two byte planes), each write journalled as the region cache
    journals it."""

    def __init__(self):
        rng = np.random.default_rng(45)
        self.cols = {"x": rng.integers(0, 100, N).astype(np.int64),
                     "m": rng.integers(0, 100, N).astype(np.int64),
                     "g": rng.integers(0, 5, N).astype(np.int64)}
        self.lineage = FeedLineage()
        self.v = 0

    def snapshot(self):
        n = len(self.cols["g"])
        ones = np.ones(n, np.bool_)
        snap = ColumnarTable.from_arrays(
            TABLE, np.arange(n, dtype=np.int64),
            {"x": Column(EvalType.DECIMAL, self.cols["x"], ones, 2),
             "m": Column(EvalType.DECIMAL, self.cols["m"], ones, 2),
             "g": Column(EvalType.INT, self.cols["g"], ones)})
        snap.feed_lineage, snap.feed_version = self.lineage, self.v
        return snap

    def write(self, row: int, x: int, m: int, structural: bool) -> None:
        n = len(self.cols["g"])
        if row == n:
            self.cols = {k: np.append(v, 0) for k, v in self.cols.items()}
        else:
            self.cols = {k: v.copy() for k, v in self.cols.items()}
        self.cols["x"][row], self.cols["m"][row] = x, m
        n = len(self.cols["g"])
        one = np.ones(1, np.bool_)
        self.lineage.record(
            {"structural": True, "n": n} if structural else
            {"n": n, "spans": [{
                "lo": row, "handles": np.array([row], np.int64),
                "cols": {c.col_id: (self.cols[c.name][row:row + 1], one)
                         for c in TABLE.columns if not c.is_pk_handle}}]})
        self.v += 1


def serve(runner, line: Line) -> tuple:
    snap = line.snapshot()
    limbs0 = runner.flight_recorder.agg_param_counts()["limb_sums"]
    tr, tok = tracker.install()
    try:
        got = runner.handle_request(dag(), snap)
    finally:
        tracker.uninstall(tok)
    x, m, g = (line.cols[k] for k in ("x", "m", "g"))
    want = {k: (int((x[g == k] * m[g == k]).sum()), int((g == k).sum()))
            for k in range(5)}
    assert {r[-1]: (int(r[0].scaleb(4)), r[1]) for r in got.rows()} == want
    assert sorted(got.rows()) == sorted(
        BatchExecutorsRunner(dag(), snap).handle_request().rows())
    launch = dict(runner.flight_recorder.items()[-1])
    assert launch["compile_class"] == "pallas_hash", launch
    launch["limb_sums"] = \
        runner.flight_recorder.agg_param_counts()["limb_sums"] - limbs0
    return tr.time_detail()["labels"]["device_feed"], launch


# (x, m): a product that needs one more byte plane than the line's (22
# bits against 14) inside the planes' dtypes; a product past int32,
# which only a limb split sums
ONE_MORE_PLANE = (30_000, 100)
LIMBS = (10 ** 9 - 1, 108)


@pytest.mark.parametrize("structural", [False, True],
                         ids=["patched", "rebuilt"])
@pytest.mark.parametrize("where", ["update", "append"])
def test_a_write_past_the_proven_widths_is_summed_exactly(
        interpret, structural, where):
    runner = _runner(1)
    line = Line()
    assert runner._analyze(dag()).lowered
    how, small = serve(runner, line)
    assert how == "upload" and small["limb_sums"] == 0
    serve(runner, line)                 # warm: the memo stands
    for (x, m), limb_sums in ((ONE_MORE_PLANE, 0), (LIMBS, 1)):
        row = 7 if where == "update" else len(line.cols["g"])
        line.write(row, x, m, structural)
        how, launch = serve(runner, line)
        assert how in ("patch", "rebuild", "upload"), how
        assert launch["limb_sums"] == limb_sums, launch
        # (more byte planes than the line's small values were cut into)
        assert launch["planes"] > small["planes"], (launch, small)
        how, again = serve(runner, line)        # ... and warm after it
        assert again["planes"] == launch["planes"]
    assert runner.flight_recorder.stats()["faults"] == 0
    _lane_builds_done(runner)


# ------------------------------- entries that say what the write did


CHAR1 = FieldType(tp=FieldTypeTp.STRING, flen=1, collation=63)
NOT_NULL_CHAR1 = FieldType(tp=FieldTypeTp.STRING, flen=1, collation=63,
                           flag=FieldTypeFlag.NOT_NULL)
KEYED = Table(8847, (
    TableColumn("id", 1, FieldType.long(not_null=True), is_pk_handle=True),
    TableColumn("x", 2, DEC2), TableColumn("m", 3, DEC2),
    TableColumn("f", 4, CHAR1), TableColumn("s", 5, NOT_NULL_CHAR1)))


class SaidLine:
    """One lineage by hand whose journal entries are the region
    cache's (``_apply_deltas`` step 6): ``introduced`` and ``dead`` on
    every one.  ``table`` is TABLE (GROUP BY the int ``g``) or KEYED
    (GROUP BY two CHAR(1) columns, the composite key)."""

    def __init__(self, table=TABLE, n: int = N, x_hi: int = 100):
        rng = np.random.default_rng(46)
        self.table = table
        self.handles = np.arange(n, dtype=np.int64)
        self.cols = {"x": rng.integers(0, x_hi, n).astype(np.int64),
                     "m": rng.integers(0, 100, n).astype(np.int64)}
        self.valid = {}
        if table is TABLE:
            self.cols["g"] = rng.integers(0, 5, n).astype(np.int64)
        else:
            for name, texts in (("f", (b"A", b"N", b"R")),
                                ("s", (b"F", b"O"))):
                col = np.empty(n, dtype=object)
                col[:] = [texts[i] for i in rng.integers(0, len(texts), n)]
                self.cols[name] = col
        self.lineage = FeedLineage()
        self.v = 0

    def dag(self):
        s = DagSelect.from_table(self.table,
                                 [c.name for c in self.table.columns])
        keys = [s.col("g")] if self.table is TABLE \
            else [s.col("f"), s.col("s")]
        return s.aggregate(keys, [
            ("sum", Expr.call("MultiplyDecimal", s.col("x"), s.col("m"))),
            ("count_star", None)]).build()

    def _column(self, name: str) -> Column:
        vals = self.cols[name]
        ok = self.valid.get(name, np.ones(len(vals), np.bool_))
        if name in ("x", "m"):
            return Column(EvalType.DECIMAL, vals, ok, 2)
        return Column(EvalType.INT if name == "g" else EvalType.BYTES,
                      vals, ok)

    def snapshot(self, lineage=None):
        snap = ColumnarTable.from_arrays(
            self.table, self.handles,
            {name: self._column(name) for name in self.cols})
        if lineage is not False:
            snap.feed_lineage, snap.feed_version = self.lineage, self.v
        return snap

    def batch(self, writes=(), deletes=(), structural: bool = False):
        """``writes``: ``(row, {column: value})`` (``row`` == the line's
        length: an append; None: a NULL); ``deletes``: rows of the view
        before the batch.  Journalled as the cache journals it: row
        patches as ``spans`` unless ``structural`` (a line with
        tombstones) or a row left."""
        n = len(self.handles)
        dead = tuple(sorted(deletes))
        cols = {k: v.copy() for k, v in self.cols.items()}
        valid = {k: self.valid.get(k, np.ones(n, np.bool_)).copy()
                 for k in cols}
        handles = self.handles
        rows = []
        for row, values in writes:
            if row == len(handles):
                handles = np.append(handles, handles[-1] + 1)
                for k in cols:
                    blank = np.empty(1, dtype=cols[k].dtype)
                    blank[0] = cols[k][0]
                    cols[k] = np.append(cols[k], blank)
                    valid[k] = np.append(valid[k], True)
            for k, v in values.items():
                valid[k][row] = v is not None
                cols[k][row] = (b"" if cols[k].dtype == object else 0) \
                    if v is None else v
            rows.append(row)
        wrote = [{"lo": r, "hi": r + 1, "handles": handles[r:r + 1].copy(),
                  "cols": {c.col_id: (cols[c.name][r:r + 1].copy(),
                                      valid[c.name][r:r + 1].copy())
                           for c in self.table.columns
                           if not c.is_pk_handle}} for r in rows]
        keep = np.ones(len(handles), np.bool_)
        keep[list(dead)] = False
        self.handles = handles[keep]
        self.cols = {k: v[keep] for k, v in cols.items()}
        self.valid = {k: v[keep] for k, v in valid.items()}
        entry = {"n": len(handles), "live": len(self.handles), "dead": dead}
        if structural or dead:
            entry.update(structural=True, introduced=wrote)
        else:
            entry.update(structural=False, spans=wrote, introduced=wrote)
        self.lineage.record(entry)
        self.v += 1

    def want(self) -> list:
        return sorted(BatchExecutorsRunner(
            self.dag(), self.snapshot(False)).handle_request().rows())

    def numpy_sums(self) -> dict:
        x, m, g = (self.cols[k] for k in ("x", "m", "g"))
        return {int(k): (int((x[g == k] * m[g == k]).sum()),
                         int((g == k).sum())) for k in np.unique(g)}


def said(runner, line: SaidLine) -> dict:
    """One read of ``line`` → what it went through: the memo's counts it
    moved, its ``device_feed`` label, its phases, its launch (None where
    the host served it); its rows held to the host pipeline's and, on
    TABLE, to numpy's."""
    rec = runner.flight_recorder
    memo0, limbs0 = rec.memo_counts(), rec.agg_param_counts()["limb_sums"]
    launches0 = len(rec.items())
    tr, tok = tracker.install()
    try:
        got = runner.handle_request(line.dag(), line.snapshot())
    finally:
        tracker.uninstall(tok)
    assert sorted(got.rows()) == line.want()
    if line.table is TABLE:
        assert {r[-1]: (int(r[0].scaleb(4)), r[1])
                for r in got.rows()} == line.numpy_sums()
    memo1, td = rec.memo_counts(), tr.time_detail()
    moved = {"kept": memo1["kept"] - memo0["kept"]}
    moved.update({k: v - memo0["dropped"][k]
                  for k, v in memo1["dropped"].items()})
    moved.update({f"planes_{k}": v - memo0["host_planes"][k]
                  for k, v in memo1["host_planes"].items()})
    launch = None
    if len(rec.items()) > launches0:
        launch = dict(rec.items()[-1])
        launch["limb_sums"] = rec.agg_param_counts()["limb_sums"] - limbs0
    return {"memo": {k: v for k, v in moved.items() if v},
            "feed": td["labels"].get("device_feed"),
            "derived": "host_derive" in td["phases_ms"], "launch": launch,
            "degraded": td["labels"].get("degraded")}


def memo_of(runner, line: SaidLine) -> dict:
    meta, = [v for k, v in runner._arena.bucket(
        anchor(line.snapshot())).items() if k[:1] == ("meta",)]
    return meta


# what the batch is, what the roll makes of the memo, and of the launch
# that follows against the line's first (``planes`` / ``limb_sums``)
INSIDE = {"x": 99, "m": 99}
SAID = {
    "inside, patched": (dict(writes=[(7, INSIDE)]),
                        {"kept": 1, "planes_dropped": 1}, "patch", "same"),
    "inside, appended": (dict(writes=[(N, dict(INSIDE, g=4))]),
                         {"kept": 1, "planes_dropped": 1}, "patch", "same"),
    "inside, on a line with tombstones": (
        dict(writes=[(7, INSIDE)], structural=True),
        {"kept": 1, "planes_dropped": 1}, "rebuild", "same"),
    "one more plane": (dict(writes=[(7, dict(zip("xm", ONE_MORE_PLANE)))]),
                       {"widths": 1, "planes_dropped": 1}, "patch", "more"),
    "one more plane, appended beside a delete": (
        dict(writes=[(N, dict(zip("xm", ONE_MORE_PLANE), g=1))],
             deletes=[3]),
        {"widths": 1, "planes_dropped": 1}, "rebuild", "more"),
    "limbs": (dict(writes=[(7, dict(zip("xm", LIMBS)))]),
              {"limbs": 1, "planes_dropped": 1}, "patch", "limbs"),
    "a key outside the grid": (dict(writes=[(7, {"g": 7})]),
                               {"key": 1, "planes_dropped": 1}, "patch",
                               "same"),
    "a delete": (dict(deletes=[0, 5, N - 1]),
                 {"kept": 1, "planes_deferred": 1}, "compact", "same"),
}


@pytest.mark.parametrize("case", sorted(SAID))
def test_a_write_that_says_what_it_did_rolls_the_memo(interpret, case):
    batch, memo, feed, planes = SAID[case]
    runner = _runner(1)
    line = SaidLine()
    first = said(runner, line)
    assert first["feed"] == "upload" and first["derived"]
    assert first["launch"]["compile_class"] == "pallas_hash"
    warm = said(runner, line)
    assert warm["memo"] == {} and not warm["derived"]
    line.batch(**batch)
    got = said(runner, line)
    assert got["memo"] == memo, got
    assert got["feed"] == feed, got
    # a kept record: nothing derived again; a dropped one: in full
    assert got["derived"] == ("kept" not in memo), got
    launch, small = got["launch"], first["launch"]
    assert launch["compile_class"] == "pallas_hash", launch
    assert launch["limb_sums"] == (1 if planes == "limbs" else 0), launch
    if planes == "same":
        assert launch["planes"] == small["planes"], (launch, small)
    else:
        assert launch["planes"] > small["planes"], (launch, small)
    again = said(runner, line)          # ... and warm after it
    assert again["memo"] == {} and not again["derived"]
    assert again["launch"]["planes"] == launch["planes"]
    assert runner.flight_recorder.stats()["faults"] == 0
    _lane_builds_done(runner)


def reader(runner, line: SaidLine) -> HostPlanes:
    """A request of the line's generation as it is NOW, about to read the
    memo's host planes; it writes the shared memo by the memo's rule
    (runner.py ``memo_fresh``)."""
    dag, snap, meta = line.dag(), line.snapshot(), memo_of(runner, line)
    plan, v = runner._analyze(dag), line.v
    return HostPlanes(plan, meta, {}, lambda: meta.get("lineage_v") == v,
                      lambda: runner._scan_batch(dag, plan, snap),
                      len(line.handles), runner.flight_recorder)


def streamed(runner, line: SaidLine) -> list:
    """The memo's host planes as a request of the line's generation
    reads them (``HostPlanes.stream``: a host rebuild, a TopN refine)."""
    return reader(runner, line).cols()


def cold_memo(line: SaidLine) -> dict:
    """What a cold build of the line as it stands derives."""
    cold_runner = _runner(1)
    cold = SaidLine()
    cold.handles, cold.cols, cold.valid = line.handles, line.cols, line.valid
    assert said(cold_runner, cold)["feed"] == "upload"
    fresh = memo_of(cold_runner, cold)
    _lane_builds_done(cold_runner)
    return fresh


def cold_planes(line: SaidLine) -> list:
    return cold_memo(line)["host_cols"]


def assert_planes_equal(planes, fresh) -> None:
    assert len(planes) == len(fresh) == 3
    for (v, ok), (fv, fok) in zip(planes, fresh):
        assert v.dtype == fv.dtype and np.array_equal(v, fv)
        assert ok.dtype == fok.dtype and np.array_equal(ok, fok)


def test_a_delete_cuts_the_host_planes_to_a_fresh_derives(interpret):
    """Delete-only entries, two in one gap: the memo is kept, the feed
    is compacted on the device, and the memo's host planes, left as they
    were with the two entries noted beside them, are cut where they are
    next read to the planes a cold build of what is left derives, array
    for array."""
    runner = _runner(1)
    line = SaidLine()
    said(runner, line)
    bounds = memo_of(runner, line)["bounds"]
    line.batch(deletes=[0, 1, 2, 77])
    line.batch(deletes=[0, N - 6])      # (in the view the first left)
    got = said(runner, line)
    assert got["memo"] == {"kept": 1, "planes_deferred": 1}
    assert got["feed"] == "compact" and not got["derived"]
    kept = memo_of(runner, line)
    assert kept["bounds"] == bounds and kept["n_rows"] == N - 6
    # the cut deferred, not changed: the planes are the generation's
    # before the gap until someone reads them
    assert len(kept["host_cols"][0][0]) == N and len(kept["host_gap"]) == 2
    counts0 = runner.flight_recorder.memo_counts()["host_planes"]
    planes = streamed(runner, line)
    assert "host_gap" not in kept and planes == kept["host_cols"]
    counts1 = runner.flight_recorder.memo_counts()["host_planes"]
    assert counts1 == dict(counts0, cut=counts0["cut"] + 1)
    fresh = cold_memo(line)
    assert_planes_equal(planes, fresh["host_cols"])
    assert kept["dtypes"] == fresh["dtypes"]
    assert kept["hash_bounds"] == fresh["hash_bounds"]
    # an update beside a delete: the planes drop, the record stays
    line.batch(writes=[(9, INSIDE)], deletes=[4])
    got = said(runner, line)
    assert got["memo"] == {"kept": 1, "planes_dropped": 1}
    assert got["feed"] == "rebuild" and not got["derived"]
    _lane_builds_done(runner)


@pytest.mark.parametrize("beside", [
    "a_roll_across_a_delete", "a_roll_across_an_update", "a_second_reader"])
def test_a_cut_that_something_runs_beside_leaves_the_memo_to_it(
        interpret, monkeypatch, beside):
    """The deferred cut takes ~10 ms with the GIL released and holds no
    lock: a newer generation's roll, or a second reader of the same
    generation, may run INSIDE it.  The reader then keeps its cut planes
    to itself and the memo is what the other left: never the older
    generation's planes under the newer generation's name."""
    runner = _runner(1)
    line = SaidLine()
    said(runner, line)
    line.batch(deletes=[0, 1, 2])
    assert said(runner, line)["memo"] == {"kept": 1, "planes_deferred": 1}
    meta = memo_of(runner, line)
    uncut, gap = meta["host_cols"], meta["host_gap"]
    first, want = reader(runner, line), cold_planes(line)
    cut_dead, inside = feed_mod._cut_dead, []

    def cut_with_company(*args):
        if not inside:
            inside.append(True)
            if beside == "a_second_reader":
                inside.append(streamed(runner, line))
            else:
                line.batch(**(dict(deletes=[5]) if "delete" in beside
                              else dict(writes=[(9, INSIDE)])))
                said(runner, line)      # (rolls the memo, sets its v)
        return cut_dead(*args)

    monkeypatch.setattr(feed_mod, "_cut_dead", cut_with_company)
    assert_planes_equal(first.cols(), want)
    if beside == "a_second_reader":
        # its planes were published; the first's were not (one of a pair)
        assert_planes_equal(inside[1], want)
        assert all(a is b for a, b in zip(meta["host_cols"], inside[1]))
        assert "host_gap" not in meta
    elif "delete" in beside:
        assert meta["host_cols"] is uncut
        assert meta["host_gap"][:-1] == gap and len(meta["host_gap"]) == 2
    else:
        assert "host_cols" not in meta and "host_gap" not in meta
    # ... and a reader of the line as it stands now reads ITS planes
    assert_planes_equal(streamed(runner, line), cold_planes(line))
    _lane_builds_done(runner)


def test_more_pending_entries_than_the_journal_keeps_drop_the_planes(
        interpret):
    """The host planes lag the record by at most the journal's own depth
    of delete-only entries (``FeedLineage.depth``): one more and they
    drop, as after any other write, while the record and the compacted
    feed stand."""
    runner = _runner(1)
    depth = FeedLineage().depth
    rows = N + 2 * depth                # (the deletes cross no pad bucket)
    line = SaidLine(n=rows)
    said(runner, line)
    half = depth // 2
    for _ in range(half):
        line.batch(deletes=[0])
    got = said(runner, line)
    assert got["memo"] == {"kept": 1, "planes_deferred": 1}
    assert got["feed"] == "compact"
    for _ in range(half):
        line.batch(deletes=[1])
    got = said(runner, line)
    assert got["memo"] == {"kept": 1, "planes_deferred": 1}
    kept = memo_of(runner, line)
    assert len(kept["host_gap"]) == depth
    line.batch(deletes=[2])
    got = said(runner, line)
    assert got["memo"] == {"kept": 1, "planes_dropped": 1}
    assert got["feed"] == "compact" and not got["derived"]
    assert "host_cols" not in kept and "host_gap" not in kept
    # ... and a reader of the planes makes them again from the line
    planes = streamed(runner, line)
    assert len(planes[0][0]) == rows - depth - 1
    _lane_builds_done(runner)


def test_an_append_the_row_count_alone_pushes_past_the_sum_bound():
    """``lowering.fit`` proves a SUM inside int64 from the argument's
    bound TIMES the row count: a row inside every bound can still leave
    it.  (int64 planes: an XLA body serves them, then the host.)"""
    from tikv_tpu.device import DeviceRunner
    from tikv_tpu.parallel import make_mesh
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    rows = 500
    x_hi = ((1 << 63) - 1) // (99 * rows)
    line = SaidLine(n=rows, x_hi=1000)
    line.cols["x"][3], line.cols["m"][3] = x_hi, 99
    first = said(runner, line)
    assert first["feed"] == "upload" and first["launch"] is not None
    assert memo_of(runner, line)["dtypes"][0] == "int64"
    line.batch(writes=[(7, {"x": x_hi, "m": 99})])     # inside, same n
    assert said(runner, line)["memo"] == {"kept": 1, "planes_dropped": 1}
    line.batch(writes=[(rows, {"x": 5, "m": 5, "g": 2})])
    got = said(runner, line)
    assert got["memo"] == {"limbs": 1}, got
    assert got["derived"] and got["launch"] is None
    assert got["degraded"] == "runner:dispatch"


@pytest.mark.parametrize("case", ["a NULL key", "a CHAR value without a code",
                                  "inside"])
def test_a_written_key_the_code_planes_cannot_hold(interpret, case):
    """A composite key of two CHAR(1) code planes: a NULL in one, or a
    value of two bytes, in an introduced row drops the record by its
    cause and the host serves the line from then on; a row inside the
    grid keeps it."""
    runner = _runner(1)
    line = SaidLine(KEYED)
    first = said(runner, line)
    assert first["launch"]["compile_class"] == "pallas_hash"
    assert first["launch"]["keys"] == 2
    value, memo = {"a NULL key": (None, {"null_key": 1}),
                   "a CHAR value without a code": (b"NO", {"code": 1}),
                   "inside": (b"R", {"kept": 1})}[case]
    line.batch(writes=[(11, {"f": value, "x": 3})])
    got = said(runner, line)
    assert got["memo"] == dict(memo, planes_dropped=1), got
    if case == "inside":
        assert got["launch"]["compile_class"] == "pallas_hash"
        assert not got["derived"] and got["feed"] == "patch"
    else:
        assert got["launch"] is None and got["derived"]
        assert got["degraded"] == "runner:dispatch"
    _lane_builds_done(runner)
