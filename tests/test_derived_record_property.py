"""The derived record of a (line, plan class) rolled across random
writes (device/feed.py ``roll_derived``): update / append / delete /
mid-insert batches through ``RegionColumnarCache._apply_deltas`` on a
small DECIMAL + CHAR(1) + DATE line under a lowered plan with a
composite key, each followed by a read on the Pallas body in interpret
mode.  After every read: the answer is the host pipeline's; the
record's bounds CONTAIN the bounds a fresh derive of the line finds; and
its dtypes, limbs, byte-plane widths and key grid are what the proofs
give over THOSE bounds and the line's row count (so a kept record may
cut more planes than a fresh derive would, never fewer); and where the
memo holds host planes, whatever delete-only entries they lag by, a
reader of them (``HostPlanes.stream``: the cut deferred, not changed)
gets the planes of the line as it stands."""

from __future__ import annotations

import decimal

import numpy as np
import pytest

import jax  # noqa: F401 — the interpret rig's runner needs its devices

from test_pallas_hash_interpret import (  # noqa: F401 — fixture
    _lane_builds_done, _runner, interpret,
)
from test_region_cache_delta import _snap, rig  # noqa: F401 — fixture
from tikv_tpu.datatype import EvalType, FieldType, FieldTypeFlag, FieldTypeTp
from tikv_tpu.datatype.time import pack_datetime
from tikv_tpu.device import lowering
from tikv_tpu.device.feed import (
    HostPlanes, anchor, arg_byte_planes, fits_dtype, plane_kinds,
    plane_values,
)
from tikv_tpu.executors.runner import BatchExecutorsRunner
from tikv_tpu.expr import Expr
from tikv_tpu.expr.eval import eval_rpn
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn, encode_table_row

NN = FieldTypeFlag.NOT_NULL
DEC2 = FieldType(tp=FieldTypeTp.NEW_DECIMAL, flag=NN, flen=15, decimal=2)
CHAR1 = FieldType(tp=FieldTypeTp.STRING, flag=NN, flen=1, collation=63)
DATE = FieldType(tp=FieldTypeTp.DATE, flag=NN)
TABLE = Table(8848, (
    TableColumn("id", 1, FieldType.long(not_null=True), is_pk_handle=True),
    TableColumn("x", 2, DEC2), TableColumn("m", 3, DEC2),
    TableColumn("f", 4, CHAR1), TableColumn("s", 5, CHAR1),
    TableColumn("d", 6, DATE)))
ROWS = 300
CUTOFF = int(pack_datetime(1995, 6, 1))


def plan_dag(start_ts: int):
    s = DagSelect.from_table(TABLE, [c.name for c in TABLE.columns])
    return s.where(
        Expr.call("LeTime", s.col("d"),
                  Expr.const(CUTOFF, EvalType.DATETIME)),
    ).aggregate([s.col("f"), s.col("s")], [
        ("sum", s.col("x")),
        ("sum", Expr.call("MultiplyDecimal", s.col("x"), s.col("m"))),
        ("count_star", None)]).build(start_ts=start_ts)


WIDER = (70_000, 3_000_000, 200_000_000, 10 ** 9 - 1)


def row(rng, wide: float = 0.0, tiers: int = 1) -> dict:
    """One row's values; ``wide``: how often a value leaves what the
    line was loaded with (one of the first ``tiers`` of WIDER: one more
    byte plane of a column, of a product, a limb split; or a key outside
    the grid)."""
    x = int(rng.integers(0, 10_000))
    if rng.random() < wide:
        x = int(rng.choice(WIDER[:tiers]))
    f = (b"A", b"N", b"R")[int(rng.integers(0, 3))]
    if rng.random() < wide / 4:
        f = (b"Z", b"0")[int(rng.integers(0, 2))]
    return {"x": decimal.Decimal(x).scaleb(-2),
            "m": decimal.Decimal(int(rng.integers(0, 110))).scaleb(-2),
            "f": f, "s": (b"F", b"O")[int(rng.integers(0, 2))],
            "d": int(pack_datetime(int(rng.integers(1993, 1998)),
                                   int(rng.integers(1, 13)),
                                   int(rng.integers(1, 29))))}


def put(h: int, values: dict) -> tuple:
    return ("put",) + encode_table_row(TABLE, h, values)


def assert_record_is_what_its_bounds_prove(runner, plan, dag, ent) -> bool:
    """→ whether the line's memo holds a derived record; if so, held to
    the line and to the proofs over its own bounds."""
    metas = [v for k, v in runner._arena.bucket(anchor(ent)).items()
             if k[:1] == ("meta",)]
    assert len(metas) == 1
    meta, = metas
    if meta.get("force_host") or "bounds" not in meta:
        return False
    assert meta["lineage_v"] == ent.feed_version
    n = ent.count_rows(dag.ranges)
    assert meta["n_rows"] == n
    batch = ent.scan_columns(plan.scan, dag.ranges, scaled=True)
    planes = []
    for pos, (ci, kind) in enumerate(zip(plan.used_cols, plane_kinds(plan))):
        col = batch.columns[ci]
        vals = plane_values(kind, col.values)
        lo, hi = meta["bounds"][pos]
        assert lo <= int(vals.min()) and int(vals.max()) <= hi, \
            (pos, meta["bounds"][pos], int(vals.min()), int(vals.max()))
        assert fits_dtype(vals, None, np.dtype(meta["dtypes"][pos]))
        planes.append((vals, col.validity))
    if "host_cols" in meta:
        # (on a copy: the memo's planes stay as the roll left them)
        held = HostPlanes(plan, dict(meta), {}, lambda: True, None, n,
                          runner.flight_recorder).cols()
        assert len(held) == len(planes)
        for (v, ok), (fv, fok), ds in zip(held, planes, meta["dtypes"]):
            assert v.dtype == np.dtype(ds) and np.array_equal(v, fv)
            assert np.array_equal(ok, fok)
    bounds, dtypes, limbs = meta["bounds"], meta["dtypes"], meta["limbs"]
    assert lowering.fit(plan, bounds, dtypes, n) == limbs
    served_by = runner._limb_variant(plan, limbs) if limbs else plan
    _base, span, widths = meta["hash_bounds"]
    assert arg_byte_planes(served_by, bounds, dtypes) == widths
    grid = meta["key_bounds"]
    assert span == grid[0][1] * grid[1][1]
    for rpn, (lo, wid) in zip(plan.key_rpns, grid):
        kv, km = eval_rpn(rpn, planes, n, np)
        assert np.all(km)
        assert lo <= int(np.min(kv)) and int(np.max(kv)) < lo + wid
    return True


@pytest.mark.parametrize("seed", [0, 1])
def test_the_rolled_record_is_what_a_derive_over_its_bounds_proves(
        rig, interpret, seed):
    c, cache = rig["c"], rig["cache"]
    cache.TAIL_MERGE_ROWS = 8       # (a mid insert further in repacks)
    cache._compact_ratio = 0.1
    rng = np.random.default_rng(seed)
    runner = _runner(1)
    live = list(range(0, 2 * ROWS, 2))
    c.txn_write([put(h, row(rng)) for h in live])
    rec = runner.flight_recorder
    held = compared = 0
    for rnd in range(36):
        kind = rnd % 6 if rnd < 6 else int(rng.integers(0, 6))
        wide = 0.25 if rnd >= 6 else 0.0
        tiers = 1 + (rnd - 6) // 8
        muts = []
        if rnd == 0:
            pass                                    # the cold build
        elif kind in (0, 1):                        # update
            for h in rng.choice(live, size=int(rng.integers(1, 4)),
                                replace=False):
                muts.append(put(int(h), row(rng, wide, tiers)))
        elif kind == 2:                             # append
            for _ in range(int(rng.integers(1, 5))):
                live.append(live[-1] + 2)
                muts.append(put(live[-1], row(rng, wide, tiers)))
        elif kind in (3, 4):                        # delete
            for h in rng.choice(live, size=int(rng.integers(1, 4)),
                                replace=False):
                live.remove(int(h))
                muts.append(("delete", encode_table_row(
                    TABLE, int(h), {})[0], None))
        else:                                       # mid insert: repack
            h = int(rng.choice(live[:len(live) // 2])) + 1
            if h not in live:
                live.append(h)
                live.sort()
                muts.append(put(h, row(rng, wide, tiers)))
        if muts:
            c.txn_write(muts)
        dag = plan_dag(c.pd.tso())
        ent = cache.get(_snap(c), dag)
        assert ent.estimated_rows() == len(live)
        plan = runner._analyze(dag)
        assert plan.lowered and len(plan.key_rpns) == 2
        got = runner.handle_request(dag, ent)
        want = BatchExecutorsRunner(dag, ent).handle_request()
        assert sorted(got.rows()) == sorted(want.rows()), (seed, rnd)
        compared += 1
        held += assert_record_is_what_its_bounds_prove(
            runner, plan, dag, ent)
    memo = rec.memo_counts()
    assert cache.misses == 1, "every batch must ride the delta path"
    assert compared == 36 and held >= 30
    # both outcomes were exercised, and the journal always said enough
    assert memo["kept"] >= 10, memo
    assert sum(memo["dropped"].values()) >= 3, memo
    assert memo["dropped"]["widths"] >= 1, memo
    assert memo["dropped"]["unknown"] == 0, memo
    # host planes a delete-only batch found in the memo stayed, with the
    # batch noted beside them, and were cut where they were read (above)
    assert memo["host_planes"]["deferred"] >= 1, memo
    assert memo["host_planes"]["cut"] >= memo["host_planes"]["deferred"]
    feeds = rec.feed_counts()
    assert feeds["rebuild_source"]["device"] >= 1, feeds
    assert feeds["rebuild_source"]["host"] >= 1, feeds
    assert rec.stats()["faults"] == 0
    _lane_builds_done(runner)
