"""The hash aggregation's host finalize hands numpy planes to the wire.

``ops.agg.finalize_hash`` used to build one Python list per result column
and ``runner.hash_result`` turned each back into arrays through
``Column.from_list``.  Both now work on planes.  The list-building pair is
kept here, verbatim, as the oracle: the new finalize must give the same
values, the same validity and the same container dtype for every aggregate
kind, key mode and NULL shape, the fast path must encode the same bytes,
and a served device aggregation must build no list at all.
"""

from typing import Optional

import numpy as np
import pytest

from tikv_tpu.datatype import Column, EvalType, FieldType
from tikv_tpu.device import DeviceRunner
from tikv_tpu.device import aggregate as agg_mod
from tikv_tpu.device.aggregate import DeviceAggregator
from tikv_tpu.device.request import _Plan
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.executors.runner import BatchExecutorsRunner, SelectResult
from tikv_tpu.datatype.column import ColumnBatch
from tikv_tpu.ops.agg import (
    _U64,
    BIT_KINDS,
    VAR_KINDS,
    AggSpec,
    _finalize_var,
    finalize_hash,
)
from tikv_tpu.server import fastpath
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn


# --------------------------------------------------------------- the oracle
# ops/agg.py's finalize_hash and the runner's hash_result (today
# device/aggregate.py's) as they stood before the planes (PR 25's
# tree), unchanged.

def oracle_finalize_hash(specs, state: dict, base: int, capacity: int,
                         slot_keys=None):
    """Produce (group_keys, per-spec result columns) for present groups.

    Groups are emitted in ascending key order (deterministic), NULL group
    last — matches what the reference's tests canonicalize to.
    ``slot_keys``: sparse recode — per-slot key values (sorted distinct
    keys) instead of the dense ``slot + base`` arithmetic.
    Returns (keys: list[Optional[int]], results: list[list]).
    """
    present = np.asarray(state["present"])
    slots = np.nonzero(present[:capacity])[0]
    has_null = bool(present[capacity])
    if slot_keys is not None:
        keys: list[Optional[int]] = [int(slot_keys[s]) for s in slots]
    else:
        keys = [int(s) + base for s in slots]
    all_slots = list(slots)
    if has_null:
        keys.append(None)
        all_slots.append(capacity)
    sel = np.asarray(all_slots, dtype=np.int64)

    results = []
    for spec, s in zip(specs, state["states"]):
        if spec.kind in ("count", "count_star"):
            results.append([int(x) for x in np.asarray(s["count"])[sel]])
        elif spec.kind == "sum":
            sums = np.asarray(s["sum"])[sel]
            nn = np.asarray(s["nonnull"])[sel]
            results.append([None if c == 0 else sums[i].item()
                            for i, c in enumerate(nn)])
        elif spec.kind == "avg":
            sums = np.asarray(s["sum"])[sel]
            cnt = np.asarray(s["count"])[sel]
            results.append([None if c == 0 else float(sums[i]) / int(c)
                            for i, c in enumerate(cnt)])
        elif spec.kind in ("min", "max"):
            vals = np.asarray(s[spec.kind])[sel]
            nn = np.asarray(s["nonnull"])[sel]
            results.append([None if c == 0 else vals[i].item()
                            for i, c in enumerate(nn)])
        elif spec.kind in VAR_KINDS:
            sums = np.asarray(s["sum"])[sel]
            sqs = np.asarray(s["sumsq"])[sel]
            cnt = np.asarray(s["count"])[sel]
            results.append([_finalize_var(spec.kind, float(sums[i]),
                                          float(sqs[i]), int(c))
                            for i, c in enumerate(cnt)])
        elif spec.kind in BIT_KINDS:
            results.append([int(x) & _U64
                            for x in np.asarray(s["bits"])[sel]])
        else:
            raise ValueError(f"finalize_hash: {spec.kind} unsupported here")
    return keys, results


def oracle_hash_result(specs, merged, base, capacity, slot_keys=None):
    keys, results = oracle_finalize_hash(specs, merged, base,
                                         capacity, slot_keys=slot_keys)
    from tikv_tpu.executors.aggregation import _agg_ret_ft
    schema, cols = [], []
    for spec, vals in zip(specs, results):
        ft = _agg_ret_ft(spec.kind,
                         spec.eval_type if spec.kind not in
                         ("count", "count_star") else None)
        schema.append(ft)
        cols.append(Column.from_list(ft.eval_type, vals))
    schema.append(FieldType.long())
    cols.append(Column.from_list(EvalType.INT, keys))
    return schema, cols


# ------------------------------------------------------------ the new path

def new_hash_result(specs, merged, base, capacity, slot_keys=None):
    plan = _Plan(scan=None, kind="hash_agg", used_cols=[],
                            specs=list(specs))
    agg_out = DeviceAggregator._agg_out(plan)
    assert DeviceAggregator._agg_out(plan) is agg_out    # once per plan
    cols = agg_mod._hash_columns(agg_out, finalize_hash(
        specs, merged, base, capacity, slot_keys=slot_keys))
    return agg_out[0] + [FieldType.long()], cols


CAPACITY = 64
SLOTS = CAPACITY + 2            # + the NULL slot + the scrap slot
I64 = np.iinfo(np.int64)

# name → (AggSpec, state planes of SLOTS entries from (rng, counts)).
# ``counts`` is the per-slot number of non-NULL arguments: 0 makes the
# result NULL, and the state then holds the kind's identity (not 0), as
# the kernels leave it.
KINDS = {
    "count_star": (AggSpec("count_star", 0), lambda rng, c: {
        "count": c + 1}),
    "count": (AggSpec("count", 0), lambda rng, c: {"count": c}),
    "sum_int": (AggSpec("sum", 0, EvalType.INT), lambda rng, c: {
        "sum": np.where(c > 0, rng.integers(-1 << 62, 1 << 62, SLOTS), 0),
        "nonnull": c}),
    "sum_real": (AggSpec("sum", 0, EvalType.REAL), lambda rng, c: {
        "sum": np.where(c > 0, rng.normal(0, 1e9, SLOTS), 0.0),
        "nonnull": c}),
    "avg": (AggSpec("avg", 0, EvalType.INT), lambda rng, c: {
        # beyond 2**53: the int → float64 rounding must be the oracle's
        "sum": np.where(c > 0, rng.integers(-1 << 62, 1 << 62, SLOTS), 0),
        "count": c}),
    "min": (AggSpec("min", 0, EvalType.INT), lambda rng, c: {
        "min": np.where(c > 0, rng.integers(I64.min, I64.max, SLOTS),
                        I64.max),
        "nonnull": c}),
    "max": (AggSpec("max", 0, EvalType.REAL), lambda rng, c: {
        "max": np.where(c > 0, rng.normal(0, 1e6, SLOTS), -np.inf),
        "nonnull": c}),
    "var_samp": (AggSpec("var_samp", 0, EvalType.REAL), lambda rng, c: {
        "sum": np.where(c > 0, rng.normal(0, 100, SLOTS), 0.0),
        "sumsq": np.where(c > 0, rng.uniform(1e4, 1e6, SLOTS), 0.0),
        "count": c}),
    "bit_and": (AggSpec("bit_and", 0, EvalType.INT), lambda rng, c: {
        # no argument: the identity ~0, 2**64 - 1 on the wire
        "bits": np.where(c > 0, rng.integers(0, I64.max, SLOTS), -1)}),
}


def make_state(kind, seed, *, null_group, zero_group, empty):
    """One merged state for one aggregate: 20 present groups (none when
    ``empty``), the NULL group per ``null_group``, and with
    ``zero_group`` a present key (and the NULL group) whose every
    argument was NULL.  Counts run 1, 2, 3..: a count of 1 is NULL for
    the *_samp kinds."""
    rng = np.random.default_rng(seed)
    spec, states = KINDS[kind]
    present = np.zeros(SLOTS, dtype=np.bool_)
    if not empty:
        present[rng.choice(CAPACITY, 20, replace=False)] = True
        present[CAPACITY] = null_group
    counts = np.arange(1, SLOTS + 1, dtype=np.int64)
    rng.shuffle(counts)
    if zero_group and not empty:
        counts[np.flatnonzero(present)[3]] = 0
        counts[CAPACITY] = 0
    return spec, {"present": present, "overflow": False,
                  "states": [states(rng, counts)]}


def key_mode(mode, seed):
    """(base, slot_keys): dense keys off a non-zero base, or 64 sorted
    distinct sparse keys drawn from [0, 2**62]."""
    if mode == "dense":
        return -12345, None
    rng = np.random.default_rng(seed + 1)
    keys = np.unique(np.append(rng.integers(0, 1 << 62, CAPACITY * 2),
                               1 << 62))[-CAPACITY:]
    assert len(keys) == CAPACITY and keys[-1] == 1 << 62
    return 0, keys


def assert_same_column(new: Column, old: Column, ft: FieldType):
    assert new.eval_type is old.eval_type
    # the container follows the field type: an UNSIGNED result is uint64
    # whatever values appear (``from_list`` without its ``unsigned`` flag,
    # as hash_result called it, chose uint64 only on seeing a value
    # >= 2**63: the same numbers either way)
    want = np.dtype(np.uint64) if ft.is_unsigned else old.values.dtype
    assert new.values.dtype == want
    assert new.validity.dtype == np.bool_
    assert np.array_equal(new.validity, old.validity)
    assert np.array_equal(new.values, old.values.astype(want))
    assert new.to_list() == old.to_list()
    # the Column contract: a harmless 0 under a False validity
    assert not new.values[~new.validity].any()


SHAPES = {
    "null_group+zero": dict(null_group=True, zero_group=True, empty=False),
    "null_group": dict(null_group=True, zero_group=False, empty=False),
    "zero": dict(null_group=False, zero_group=True, empty=False),
    "plain": dict(null_group=False, zero_group=False, empty=False),
    "empty": dict(null_group=False, zero_group=False, empty=True),
}


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("mode", ["dense", "sparse"])
@pytest.mark.parametrize("kind", KINDS)
def test_planes_equal_the_list_oracle(kind, mode, shape):
    seed = sorted(KINDS).index(kind) * 10 + sorted(SHAPES).index(shape)
    spec, merged = make_state(kind, seed, **SHAPES[shape])
    base, slot_keys = key_mode(mode, seed)
    old_schema, old_cols = oracle_hash_result(
        [spec], merged, base, CAPACITY, slot_keys)
    new_schema, new_cols = new_hash_result(
        [spec], merged, base, CAPACITY, slot_keys)
    assert new_schema == old_schema
    assert len(new_cols) == len(old_cols) == 2
    for new, old, ft in zip(new_cols, old_cols, old_schema):
        assert_same_column(new, old, ft)

    n_groups = 0 if SHAPES[shape]["empty"] else \
        20 + SHAPES[shape]["null_group"]
    assert len(new_cols[0]) == n_groups
    keys = new_cols[-1]
    assert keys.values.dtype == np.int64
    real = keys.values[keys.validity]
    assert (np.diff(real) > 0).all()             # ascending, NULL last
    if SHAPES[shape]["null_group"]:
        assert not keys.validity[-1] and keys.validity[:-1].all()
    if SHAPES[shape]["zero_group"] and kind not in (
            "count_star", "count", "bit_and"):
        assert not new_cols[0].validity[3]
        assert new_cols[0].values[3] == 0


@pytest.mark.parametrize("domain", ["sparse_above", "sparse_below",
                                    "dense_straddle"])
def test_unsigned_key_domain_keeps_the_oracles_container(domain):
    """A uint64 key column (aggregate.py ``_sparse_slots`` keeps its dtype): the
    key plane is uint64 exactly where a present key is >= 2**63."""
    spec, merged = make_state("count", 7, null_group=True,
                              zero_group=False, empty=False)
    if domain == "dense_straddle":
        base, slot_keys = (1 << 63) - 10, None
        merged["present"][[9, 10, 11]] = True   # 2**63 - 1, 2**63, + 1
    else:
        base = 0
        lo = (1 << 63) - CAPACITY // 2 if domain == "sparse_above" \
            else 1 << 40
        slot_keys = np.arange(lo, lo + CAPACITY, dtype=np.uint64)
        merged["present"][CAPACITY - 1] = True
    _, old_cols = oracle_hash_result([spec], merged, base, CAPACITY,
                                     slot_keys)
    _, new_cols = new_hash_result([spec], merged, base, CAPACITY,
                                  slot_keys)
    want = np.int64 if domain == "sparse_below" else np.uint64
    assert old_cols[-1].values.dtype == want
    for new, old in zip(new_cols, old_cols):
        assert_same_column(new, old, FieldType.long())


def test_finalize_hash_rejects_first():
    spec, merged = make_state("count", 1, null_group=False,
                              zero_group=False, empty=False)
    with pytest.raises(ValueError, match="first unsupported"):
        finalize_hash([AggSpec("first", 0)], merged, 0, CAPACITY)


# ------------------------------------------------------------- on the wire

def wire_bytes(schema, cols):
    env = {"backend": "device", "trace_id": "t"}
    return fastpath.encode_response_python(
        env, SelectResult(ColumnBatch(list(schema), list(cols)), []))


@pytest.mark.parametrize("mode", ["dense", "sparse"])
def test_fastpath_encodes_the_same_bytes(mode):
    """int64 sums and keys, a uint64 BIT column holding 2**64 - 1, a
    float64 average and NULL cells in each: one reply, the same bytes
    from the planes as from the lists."""
    # one seed, so every kind's state shares ``present`` and the counts
    specs, states = [], []
    for i, kind in enumerate(["count_star", "sum_int", "bit_and", "avg",
                              "min"]):
        spec, merged = make_state(kind, 40, null_group=True,
                                  zero_group=True, empty=False)
        specs.append(AggSpec(spec.kind, i, spec.eval_type))
        states.extend(merged["states"])
    merged["states"] = states
    base, slot_keys = key_mode(mode, 40)
    old = oracle_hash_result(specs, merged, base, CAPACITY, slot_keys)
    new = new_hash_result(specs, merged, base, CAPACITY, slot_keys)
    assert new[1][2].values.dtype == np.uint64
    assert int(new[1][2].values.max()) == _U64
    assert not new[1][1].validity.all() and not new[1][-1].validity.all()
    assert wire_bytes(*new) == wire_bytes(*old)
    # and over the slow leg's row walk
    assert SelectResult(ColumnBatch(*new), []).rows() == \
        SelectResult(ColumnBatch(*old), []).rows()


# ------------------------------------------------- served: no list is built

def snapshot(mode, n=30_000):
    rng = np.random.default_rng(11)
    table = Table(7900 + (mode == "sparse"), (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("k", 2, FieldType.long()),
        TableColumn("v", 3, FieldType.long())))
    if mode == "sparse":
        doms = np.unique(rng.integers(0, 1 << 62, 499))
        k = doms[rng.integers(0, len(doms), n)]
    else:
        k = rng.integers(-50, 50, n).astype(np.int64)
    kvalid = (np.arange(n) % 23) != 7            # a NULL group
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    vvalid = k % 5 != 0                          # groups whose SUM is NULL
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"k": Column(EvalType.INT, k, kvalid),
         "v": Column(EvalType.INT, v, vvalid)})
    return table, snap


@pytest.mark.parametrize("mode", ["dense", "sparse"])
def test_served_device_hash_agg_builds_no_list(mode, monkeypatch):
    """A hash aggregation through DeviceRunner (the CPU bodies, as
    tests/test_device_runner.py) answers with ``Column.from_list``
    raising: nothing between the fetch and the Columns builds a list."""
    table, snap = snapshot(mode)
    sel = DagSelect.from_table(table, ["id", "k", "v"])
    dag = sel.aggregate(
        [sel.col("k")],
        [("count_star", None), ("count", sel.col("v")),
         ("sum", sel.col("v")), ("avg", sel.col("v")),
         ("min", sel.col("v")), ("max", sel.col("v"))]).build()
    host = BatchExecutorsRunner(dag, snap).handle_request()
    runner = DeviceRunner(chunk_rows=1 << 12)
    assert runner.supports(dag)

    def no_lists(*a, **kw):
        raise AssertionError("Column.from_list on the device hash path")

    monkeypatch.setattr(Column, "from_list", staticmethod(no_lists))
    cold = runner.handle_request(dag, snap)
    warm = runner.handle_request(dag, snap)
    monkeypatch.undo()

    def canon(rows):
        return sorted(rows, key=lambda r: (r[-1] is None, r[-1] or 0))

    want = canon(host.rows())
    assert any(r[2] is None for r in want) and want[-1][-1] is None
    for got in (cold, warm):
        assert got.rows() == want       # device order: ascending, NULL last
        for c in got.batch.columns:
            assert c.values.dtype == c.eval_type.np_dtype
