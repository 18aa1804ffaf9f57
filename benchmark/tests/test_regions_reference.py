"""``hash_agg_regions``: the merge of partial aggregates on hand-computed
partials of a hand-made table, its control, and the layout check; the
table kind ``int_table_presplit``'s boundaries and SST pieces, and its
data against ``int_table``'s; the one-region roofline reader on a
synthetic trace."""

import types

import numpy as np
import pytest

import byname

# handles 0..5, in two regions of three rows
COLS = {"c0": np.array([3, 1, 3, 2, 1, 3], dtype=np.int64),
        "c1": np.array([10, -5, 7, 961, 960, -1000], dtype=np.int64)}
PARAMS = {"group_by": "c0", "sum": "c1", "concurrency": 15, "regions": 2}
# [count, sum, key] of rows 0..2 and of rows 3..5, by hand, in the order
# a store may serve them (any)
LEFT = [[2, 17, 3], [1, -5, 1]]
RIGHT = [[1, 961, 2], [1, 960, 1], [1, -1000, 3]]
SPEC = {"columns": {"c0": {"dist": "uniform_dense", "groups": 1024},
                    "c1": {"dist": "uniform", "lo": -1000, "hi": 1000}}}


def ctx(cols=COLS):
    return types.SimpleNamespace(rows=len(cols["c0"]), cols=cols)


def failing(checks):
    return [name for name, value, limit in checks if value > limit]


@pytest.fixture(scope="module")
def kind():
    return byname.load("requests", "hash_agg_regions")


@pytest.fixture(scope="module")
def table_kind():
    return byname.load("tables", "int_table_presplit")


def summary(partials, tasks=None):
    """A fan-out read's summary as ``loadgen.py request()`` sees it."""
    n = len(partials) if tasks is None else tasks
    return {"responses": [{"rows": p} for p in partials], "tasks": n,
            "time_detail": {"labels": {"cop_tasks": str(n)}}}


def test_merge_of_hand_computed_partials_is_the_reference(kind):
    want = [[2, 955, 1], [1, 961, 2], [3, -983, 3]]
    assert kind.merge([LEFT, RIGHT]).tolist() == want
    assert kind.merge([RIGHT, LEFT]).tolist() == want
    assert kind.reference(ctx(), PARAMS).tolist() == want
    # one task, no task
    assert kind.merge([LEFT]).tolist() == [[1, -5, 1], [2, 17, 3]]
    assert kind.merge([]).shape == (0, 3)


def test_digest_merges_and_check_passes_the_exact_read(kind):
    resp = summary([LEFT, RIGHT])
    rec = {"answer": kind.digest(ctx(), resp, PARAMS),
           "labels": resp["time_detail"]["labels"]}
    checks = kind.check(ctx(), [rec], PARAMS, kind.reference(ctx(), PARAMS))
    assert checks == [("hash_agg.wrong_answers", 0, 0),
                      ("regions.reads_off_the_layout", 0, 0)]
    assert "wrong" not in rec


def test_a_partial_left_out_is_a_wrong_answer(kind):
    resp = summary([LEFT], tasks=2)
    rec = {"answer": kind.digest(ctx(), resp, PARAMS),
           "labels": resp["time_detail"]["labels"]}
    checks = kind.check(ctx(), [rec], PARAMS, kind.reference(ctx(), PARAMS))
    assert failing(checks) == ["hash_agg.wrong_answers"]
    assert rec["wrong"] is True


def test_a_read_off_the_layout_is_marked_whatever_its_answer(kind):
    # three tasks where the layout has two regions: the answer is right
    resp = summary([LEFT, RIGHT[:1], RIGHT[1:]])
    rec = {"answer": kind.digest(ctx(), resp, PARAMS),
           "labels": resp["time_detail"]["labels"]}
    good = {"answer": rec["answer"], "labels": {"cop_tasks": "2"}}
    checks = kind.check(ctx(), [rec, good], PARAMS,
                        kind.reference(ctx(), PARAMS))
    assert checks == [("hash_agg.wrong_answers", 0, 0),
                      ("regions.reads_off_the_layout", 1, 0)]
    assert rec["wrong"] is True and "wrong" not in good
    # a reply without the label is off the layout too
    bare = {"answer": rec["answer"], "labels": {}}
    assert failing(kind.check(ctx(), [bare], PARAMS,
                              kind.reference(ctx(), PARAMS))) == \
        ["regions.reads_off_the_layout"]


def test_control_fails_by_the_answer_alone(kind):
    """``control.py``'s record: the reference one precision down, an
    answer with no labels.  961 and 960 are not bfloat16 values."""
    served = {"answer": kind.reference(ctx(), PARAMS, approx=True).tobytes()}
    checks = kind.check(ctx(), [served], PARAMS,
                        kind.reference(ctx(), PARAMS))
    assert failing(checks) == ["hash_agg.wrong_answers"]


def test_prepare_carries_the_concurrency_to_send(kind):
    sent = {}

    class Client:
        def coprocessor_fanout(self, dag, concurrency, timeout):
            sent.update(dag=dag, concurrency=concurrency)
            return {"responses": []}

    kind.send(ctx(), Client(), ("the plan", 15))
    assert sent == {"dag": "the plan", "concurrency": 15}


# ------------------------------------------------- the table kind


@pytest.mark.parametrize("rows, regions", [
    (10485760, 6), (10485760, 11), (20000, 6), (12, 4), (13, 4), (5, 8)])
def test_boundaries_are_row_numbers_and_no_piece_straddles_one(
        table_kind, rows, regions):
    cuts = table_kind.boundaries(rows, regions)
    assert cuts == sorted(set(cuts)) and all(0 < c < rows for c in cuts)
    assert len(cuts) <= regions - 1
    per = -(-rows // regions)
    edges = [0] + cuts + [rows]
    assert all(hi - lo <= per for lo, hi in zip(edges, edges[1:]))
    for chunk in (1 << 20, 1000, 3):
        got = table_kind.pieces(rows, regions, chunk)
        # every row once, in order
        assert got[0][0] == 0 and got[-1][1] == rows
        assert all(a[1] == b[0] for a, b in zip(got, got[1:]))
        assert all(0 < hi - lo <= chunk for lo, hi in got)
        # no piece holds a boundary inside it
        assert not any(lo < c < hi for lo, hi in got for c in cuts)


def test_the_cells_layout(table_kind):
    cuts = table_kind.boundaries(10485760, 6)
    assert cuts == [1747627 * i for i in range(1, 6)]
    got = table_kind.pieces(10485760, 6)
    # a region holds more rows than one SST of LOAD_CHUNK: two a region
    assert len(got) == 12 and table_kind.LOAD_CHUNK == 1 << 20


def test_same_seed_same_table_as_int_table(table_kind):
    plain = byname.load("tables", "int_table")
    a = table_kind.make(SPEC, 2600000027, 4096)
    b = plain.make(SPEC, 2600000027, 4096)
    assert sorted(a) == sorted(b)
    assert all((a[c] == b[c]).all() for c in a)


def test_wait_for_raises_with_what_it_saw(table_kind):
    with pytest.raises(RuntimeError, match=r"a thing.*last seen \{'n': 3\}"):
        table_kind.wait_for("a thing", lambda: (False, {"n": 3}), 0.3)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise OSError("busy")
        return True, "ready"
    assert table_kind.wait_for("a thing", flaky, 5) == "ready"


# ------------------------------------------------- the roofline reader


def data(ops, rows_per_launch=1747627):
    kernel = {"of": "ops", "match": ["tpu_custom_call"],
              "input_plane_bytes_per_row": [4, 4]}
    if rows_per_launch:
        kernel["rows_per_launch"] = rows_per_launch
    return {"trace": None if ops is None else {"ops": ops},
            "traffic": {"main_kernel": kernel}, "rows": 10485760,
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_region_roofline_reckons_one_regions_bytes_a_launch():
    reader = byname.load("readers", "trace_roofline_region")
    # 100 launches of 0.2 ms each
    ops = {"%pallas_hash_tpu_custom_call.1": [100, 0.02], "%copy": [5, 1.0]}
    least_s = 1747627 * 8 / 819e9
    assert reader.read(data(ops), {}) == \
        pytest.approx(100.0 * least_s / 0.0002)
    # the whole table's reader charges every launch all the rows
    whole = byname.load("readers", "trace_roofline_share")
    assert whole.read(data(ops), {}) == \
        pytest.approx(reader.read(data(ops), {}) * 10485760 / 1747627)


def test_region_roofline_reads_nothing_where_there_is_nothing():
    reader = byname.load("readers", "trace_roofline_region")
    assert reader.read(data(None), {}) is None              # untraced
    assert reader.read(data({"%copy": [5, 1.0]}), {}) is None
    # an older traffic file gives no rows a launch
    ops = {"%pallas_hash_tpu_custom_call.1": [100, 0.02]}
    assert reader.read(data(ops, rows_per_launch=0), {}) is None
