"""Each request kind's reference on a hand-computed tiny table, its
control (a lower-precision answer put in the program's place has to
come out as not correct), and the table kind's generator."""

import types

import numpy as np
import pytest

import byname

# handles 0..5
COLS = {"c0": np.array([3, 1, 3, 2, 1, 3], dtype=np.int64),
        "c1": np.array([10, -5, 7, 961, 960, -1000], dtype=np.int64)}
PARAMS = {"group_by": "c0", "sum": "c1"}
SPEC = {"columns": {"c0": {"dist": "uniform_dense", "groups": 1024},
                    "c1": {"dist": "uniform", "lo": -1000, "hi": 1000}}}
SPARSE = {"columns": {"c0": {"dist": "uniform_sparse", "groups": 1024,
                             "domain_bits": 62},
                      "c1": {"dist": "uniform", "lo": -1000, "hi": 1000}}}


def ctx(cols=COLS):
    return types.SimpleNamespace(rows=len(cols["c0"]), cols=cols)


def failing(checks):
    return [name for name, value, limit in checks if value > limit]


def test_hash_agg_reference_by_hand():
    mod = byname.load("requests", "hash_agg")
    assert mod.reference(ctx(), PARAMS).tolist() == \
        [[2, 955, 1], [1, 961, 2], [3, -983, 3]]
    # grouped the other way round: the columns come from the params
    assert mod.reference(ctx(), {"group_by": "c1", "sum": "c0"})[:, 2] \
        .tolist() == sorted(COLS["c1"].tolist())


def test_hash_agg_digest_sorts_by_key_and_check_passes_exact_answers():
    mod = byname.load("requests", "hash_agg")
    resp = {"rows": [[3, -983, 3], [2, 955, 1], [1, 961, 2]]}
    rec = {"answer": mod.digest(ctx(), resp, PARAMS)}
    assert np.frombuffer(rec["answer"], np.int64).reshape(-1, 3).tolist() \
        == [[2, 955, 1], [1, 961, 2], [3, -983, 3]]
    assert failing(mod.check(ctx(), [rec], PARAMS,
                             mod.reference(ctx(), PARAMS))) == []
    assert "wrong" not in rec


def test_hash_agg_check_marks_the_wrong_record():
    mod = byname.load("requests", "hash_agg")
    good = {"answer": mod.reference(ctx(), PARAMS).tobytes()}
    off = mod.reference(ctx(), PARAMS)
    off[0, 1] += 1          # one sum off by one
    bad = {"answer": off.tobytes()}
    checks = mod.check(ctx(), [good, bad, dict(good)], PARAMS,
                       mod.reference(ctx(), PARAMS))
    assert checks == [("hash_agg.wrong_answers", 1, 0)]
    assert bad["wrong"] is True and "wrong" not in good


@pytest.mark.parametrize("spec", [SPEC, SPARSE], ids=["dense", "sparse"])
@pytest.mark.parametrize("seed", [1, 2, 2147483999])
def test_hash_agg_control_lower_precision_is_not_correct(seed, spec):
    """Sums served in bfloat16, at a size a test can hold."""
    mod = byname.load("requests", "hash_agg")
    c = ctx(byname.load("tables", "int_table").make(spec, seed, 1 << 18))
    exact = {"answer": mod.reference(c, PARAMS).tobytes()}
    approx = {"answer": mod.reference(c, PARAMS, approx=True).tobytes()}
    assert failing(mod.check(c, [exact], PARAMS,
                             mod.reference(c, PARAMS))) == []
    assert failing(mod.check(c, [approx], PARAMS,
                             mod.reference(c, PARAMS))) \
        == ["hash_agg.wrong_answers"]


def test_tables_repeat_from_the_seed_and_sparse_keys_are_sparse():
    make = byname.load("tables", "int_table").make
    a = make(SPARSE, 2**31 + 5, 50000)
    b = make(SPARSE, 2**31 + 5, 50000)
    assert list(a) == ["c0", "c1"]
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert len(np.unique(a["c0"])) == 1024 and a["c0"].max() > 1 << 40
    assert a["c1"].min() >= -1000 and a["c1"].max() < 1000
    other = make(SPARSE, 2**31 + 6, 50000)
    assert not np.array_equal(a["c0"], other["c0"])
    dense = make(SPEC, 7, 50000)
    assert dense["c0"].min() == 0 and dense["c0"].max() == 1023


def test_a_missing_file_is_named():
    with pytest.raises(FileNotFoundError, match="requests/ has no nope.py"):
        byname.load("requests", "nope")
