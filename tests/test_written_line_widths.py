"""A written line's kernel constants under a LOWERED plan (DECIMAL
planes, a SUM of a product): the byte planes a SUM's argument is cut
into and the limb split are proven from the columns' BOUNDS
(device/lowering.py ``agg_intervals`` / ``fit``), which a written row
may leave while every value still fits its plane's dtype.  The request
memo of such a line derives them again after a write (device/runner.py
``_refresh_meta``), whether the feed under it was patched forward or
built again; the answer is numpy's and the host pipeline's, on the
Pallas body in interpret mode (the only one that cuts byte planes)."""

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
