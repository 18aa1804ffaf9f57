"""An aggregation's finalize after a Pallas launch is ONE native call.

``aggregate.finalize_packed`` hands the fetched ``(2, HI, W)`` int32
accumulator parts to ``native.hash_finalize_packed``
(native/fastbuild.cpp), which holds the GIL from entry to return, where
the numpy chain (``_sum_parts`` → ``_pallas_states`` → ``finalize_hash``)
made ~24 array calls and dropped the GIL around each.  The chain stays
in the tree as the fallback and is the oracle here: the native planes
must be array-equal in values, validity, dtype and length, and the reply
the same bytes, for every aggregate, key mode, NULL shape, grid shape
and width the Pallas hash path can produce.  What the native call cannot
serve must reach the chain unchanged, and the runner must count which of
the two ran.

An aggregation without GROUP BY takes the same call since PR 35: its
accumulator is a grid of ONE slot (``slots`` 1: no key, no NULL group,
no scrap row) that is one row whether or not a row reached it.  Its
oracle is the finalize as ``run_simple`` ran it before:
``_pallas_states`` → ``ops.agg.finalize_simple`` → ``from_scaled`` →
``Column.from_list``.

Off a TPU no plan reaches ``_try_pallas``, so the accumulators here are
synthetic: plane sums drawn at random, packed as the kernel packs them.
tests/test_pallas_hash_interpret.py drives the same code from a real
(interpreted) launch.
"""

import re
from pathlib import Path

import numpy as np
import pytest

import jax

from tikv_tpu import native
from tikv_tpu.datatype import Column, EvalType, FieldType
from tikv_tpu.datatype.column import ColumnBatch
from tikv_tpu.device import DeviceRunner
from tikv_tpu.device import aggregate as agg_mod
from tikv_tpu.device.aggregate import DeviceAggregator
from tikv_tpu.device.request import _Plan
from tikv_tpu.device.kernels import PlaneLayout, build_layouts
from tikv_tpu.executors.runner import SelectResult
from tikv_tpu.datatype.mydecimal import from_scaled
from tikv_tpu.ops.agg import AggSpec, finalize_hash, finalize_simple
from tikv_tpu.parallel import make_mesh
from tikv_tpu.server import fastpath

needs_native = pytest.mark.skipif(
    native.hash_finalize_packed is None,
    reason="native/fastbuild.cpp did not build here (no g++?): the "
           "native finalize cannot be compared with the numpy chain")

I64_MAX = (1 << 63) - 1


# ------------------------------------------------- a synthetic accumulator

def make_case(aggs=(("count_star", 0), ("sum", 2)), *, mode="dense",
              base=0, capacity=1024, groups=700, null_group=False,
              zero_nonnull=False, parts=1, tight=False, big=False,
              ok_is_mask=False, LO=32, seed=0, fracs=()):
    return dict(aggs=aggs, mode=mode, base=base, capacity=capacity,
                groups=groups, null_group=null_group,
                zero_nonnull=zero_nonnull, parts=parts, tight=tight,
                big=big, ok_is_mask=ok_is_mask, LO=LO, seed=seed,
                fracs=fracs)


def simple_case(aggs=(("count_star", 0), ("sum", 2)), *, rows=True, **kw):
    """An aggregation without GROUP BY: a grid of one slot, reached by
    some row (``rows``) or by none."""
    return make_case(aggs, mode="simple", base=0, capacity=1,
                     groups=int(rows), **kw)


def split(rng, total, n_parts):
    """``total`` (int64, inside int32) as ``n_parts`` int32 addends."""
    out = [rng.integers(-1 << 20, 1 << 20, total.shape)
           for _ in range(n_parts - 1)]
    out.append(total - sum(out))
    assert all(np.abs(p).max(initial=0) < 1 << 31 for p in out)
    return [p.astype(np.int32) for p in out]


def accumulator(case):
    """→ (parts, LO, p8, layouts, specs, slots, base, capacity,
    slot_keys): what ``run_hash`` (``run_simple`` in mode ``simple``)
    hands ``finalize_packed`` after a Pallas launch, with plane sums
    drawn at random."""
    rng = np.random.default_rng(case["seed"])
    capacity, LO = case["capacity"], case["LO"]
    specs = [AggSpec(kind, i, EvalType.INT)
             for i, (kind, _nb) in enumerate(case["aggs"])]
    layouts, p8, pf = build_layouts(
        specs, [False] * len(specs), [nb for _k, nb in case["aggs"]],
        [case["ok_is_mask"]] * len(specs))
    assert pf == 0
    simple = case["mode"] == "simple"
    slots = 1 if simple else capacity + 2       # + NULL + scrap
    # the kernel's tight grid: no scrap row, and no NULL row for a key
    # that cannot be NULL, so HI·LO may be under ``slots``
    grid = capacity if case["tight"] else slots
    # (pallas_hash.build rounds HI up to whole sublane tiles of 8)
    HI = 8 if simple else -(-grid // LO)
    if case["tight"]:
        assert HI * LO < slots and not case["null_group"]

    slot_keys = None
    key_slots = capacity
    if case["mode"] == "sparse":
        # sorted distinct keys, fewer than ``capacity`` of them
        slot_keys = np.unique(rng.integers(-1 << 62, 1 << 62,
                                           max(case["groups"], 1) + 7))
        key_slots = len(slot_keys)
    present = np.zeros(HI * LO, np.bool_)
    present[rng.choice(key_slots, case["groups"], replace=False)] = True
    if case["null_group"]:
        present[capacity] = True
    if simple:
        present[1:] = True      # nothing scatters past slot 0: never read
    elif not case["tight"]:
        present[capacity + 1] = True            # the scrap slot: ignored

    cmax = 1 << 37 if case["big"] else 1 << 12
    S8 = np.zeros((p8, HI * LO), np.int64)
    S8[0] = np.where(present, rng.integers(1, cmax, HI * LO), 0)
    zeroed = np.flatnonzero(present)[:3] if case["zero_nonnull"] else []
    for lay in layouts:
        if lay.kind == "count_star":
            continue
        if lay.ok_plane != 0:
            ok = rng.integers(0, S8[0] + 1)     # 0 ≤ ok ≤ rows
            ok[zeroed] = 0
            S8[lay.ok_plane] = ok
        ok = S8[lay.ok_plane]
        for p in lay.byte_planes:               # Σ (byte − 128) over ok
            S8[p] = rng.integers(-128 * ok, 127 * ok + 1)
    # (p8, HI·LO) planes → the packed (HI, p8·LO) layout → an int32
    # pair with lo + (hi << 16) == the sum, lo not held under 2^16
    packed = S8.reshape(p8, HI, LO).transpose(1, 0, 2).reshape(HI, p8 * LO)
    carry = rng.integers(0, 4, packed.shape)
    lo = (packed & 0xFFFF) + (carry << 16)
    hi = (packed >> 16) - carry
    assert np.array_equal(lo + (hi << 16), packed)
    parts = [np.stack(pair) for pair in zip(split(rng, lo, case["parts"]),
                                            split(rng, hi, case["parts"]))]
    return (parts, LO, p8, layouts, specs, slots, case["base"], capacity,
            slot_keys)


def plan_of(specs, fracs=()):
    return _Plan(scan=None, kind="hash_agg", used_cols=[],
                 specs=list(specs), agg_fracs=list(fracs))


def numpy_chain(parts, LO, p8, layouts, specs, slots, base, capacity,
                slot_keys):
    """The finalize as it stood before the native call: the oracle."""
    present, states = agg_mod._pallas_states(
        agg_mod._sum_parts(parts), LO, p8, layouts, specs, slots)
    merged = {"present": present, "overflow": False, "states": states}
    return agg_mod._hash_columns(
        DeviceAggregator._agg_out(plan_of(specs)),
        finalize_hash(specs, merged, base, capacity, slot_keys=slot_keys))


def simple_chain(parts, LO, p8, layouts, specs, slots, _base, _capacity,
                 _slot_keys, fracs=()):
    """``run_simple``'s ``from_packed`` and ``_simple_result`` as they
    stood before PR 35, Python scalars and all: the oracle of the
    one-slot grid."""
    assert slots == 1
    _present, states = agg_mod._pallas_states(
        agg_mod._sum_parts(parts), LO, p8, layouts, specs, 1)
    merged = [{k: np.asarray(v).reshape(-1)[0] for k, v in s.items()}
              for s in states]
    finals = finalize_simple(specs, merged)
    fts, _dts, fracs = DeviceAggregator._agg_out(plan_of(specs, fracs))
    return [Column.from_list(
        ft.eval_type,
        [val if frac is None or val is None else from_scaled(val, frac)])
        for ft, val, frac in zip(fts, finals, fracs)]


def wire_bytes(specs, cols, fracs=(), keyed=True):
    schema = DeviceAggregator._agg_out(plan_of(specs, fracs))[0] + \
        [FieldType.long()] * keyed
    return fastpath.encode_response_python(
        {"backend": "device", "trace_id": "t"},
        SelectResult(ColumnBatch(schema, list(cols)), []))


def assert_same_columns(new, old):
    assert len(new) == len(old)
    for got, want in zip(new, old):
        # a DECIMAL SUM leaves ``_hash_columns`` as its scaled plane;
        # the oracle's form is the host's, made where rows are asked for
        assert (got.frac is None) == (want.frac is None) or \
            got.values.dtype == np.int64
        got, want = got.unscaled(), want.unscaled()
        assert got.eval_type is want.eval_type
        assert got.values.dtype == want.values.dtype
        assert got.validity.dtype == want.validity.dtype == np.bool_
        assert len(got.values) == len(want.values) == len(got.validity)
        assert np.array_equal(got.validity, want.validity)
        assert np.array_equal(got.values, want.values)
        # the Column contract: a harmless 0 under a False validity
        assert not got.values[~got.validity].any()


ALL_FOUR = (("count_star", 0), ("count", 0), ("sum", 2), ("avg", 3))

CASES = {
    # each kind alone, then together; own validity planes and aliased
    "count_star": make_case((("count_star", 0),)),
    "count": make_case((("count", 0),), zero_nonnull=True),
    "sum": make_case((("sum", 2),), zero_nonnull=True),
    "avg": make_case((("avg", 2),), zero_nonnull=True),
    "together": make_case(ALL_FOUR, zero_nonnull=True, null_group=True),
    "together-ok-is-mask": make_case(ALL_FOUR, ok_is_mask=True),
    "the-cells-plan": make_case((("count", 0), ("sum", 2)),
                                ok_is_mask=True, tight=True),
    # keys
    "negative-base": make_case(base=-(1 << 40), null_group=True),
    "base-at-int64-min": make_case(base=-(1 << 63)),
    "base-under-int64-max": make_case(base=I64_MAX - 1024),
    "sparse": make_case(ALL_FOUR, mode="sparse", zero_nonnull=True),
    "sparse-null-group": make_case(mode="sparse", null_group=True,
                                   groups=1000),
    # NULL shapes
    "null-group-only": make_case(groups=0, null_group=True),
    "null-group-whose-sum-is-null": make_case(
        (("sum", 1), ("avg", 1)), groups=0, null_group=True,
        zero_nonnull=True),
    "no-group-at-all": make_case(ALL_FOUR, groups=0),
    "no-group-at-all-sparse": make_case(mode="sparse", groups=0),
    # parts and grid
    "three-parts": make_case(ALL_FOUR, parts=3, null_group=True),
    "three-parts-sparse-big": make_case(ALL_FOUR, mode="sparse", parts=3,
                                        big=True),
    "grid-under-slots": make_case(ALL_FOUR, tight=True, groups=1024),
    "grid-under-slots-three-parts": make_case(tight=True, parts=3),
    "LO-8": make_case(ALL_FOUR, LO=8, capacity=64, groups=40,
                      null_group=True),
    # widths: negative sums come out of the bias term
    **{f"nb-{nb}": make_case((("sum", nb), ("avg", nb)), null_group=True,
                             zero_nonnull=True, seed=nb)
       for nb in (1, 2, 3, 4, 8)},
    "nb-mixed": make_case((("sum", 1), ("sum", 8), ("avg", 4), ("sum", 3))),
    # cells past 2^32: the ``hi << 16`` carry; nb 8 wraps int64 as numpy
    **{f"past-2^32-nb-{nb}": make_case((("count", 0), ("sum", nb),
                                        ("avg", nb)), big=True, seed=nb)
       for nb in (2, 4, 8)},
    # group counts
    "one-group": make_case(ALL_FOUR, groups=1),
    "1024-groups": make_case(ALL_FOUR, groups=1024, null_group=True),
    "65536-groups": make_case(ALL_FOUR, capacity=65536, groups=65536,
                              null_group=True, zero_nonnull=True),
    "65536-groups-sparse-three-parts": make_case(
        mode="sparse", capacity=65536, groups=65529, parts=3),
}


@needs_native
@pytest.mark.parametrize("name", CASES)
def test_native_planes_equal_the_numpy_chain(name):
    case = CASES[name]
    args = accumulator(case)
    specs = args[4]
    want = numpy_chain(*args)
    finalized, was_native = agg_mod.finalize_packed(*args)
    assert was_native
    got = agg_mod._hash_columns(
        DeviceAggregator._agg_out(plan_of(specs)), finalized)
    assert_same_columns(got, want)
    assert wire_bytes(specs, got) == wire_bytes(specs, want)
    # the oracle is not vacuous: every group the case planted is there
    n_groups = case["groups"] + case["null_group"]
    assert len(want[-1].values) == n_groups
    assert int((~want[-1].validity).sum()) == case["null_group"]
    if case["zero_nonnull"] and n_groups:
        # a group none of whose arguments was non-NULL: COUNT 0, SUM and
        # AVG NULL
        for col, (kind, _nb) in zip(want, case["aggs"]):
            if kind == "count":
                assert col.validity.all() and (col.values == 0).any()
            elif kind in ("sum", "avg"):
                assert not col.validity.all()
    if any(nb for _k, nb in case["aggs"]) and n_groups > 10:
        sums = [c for c, (k, _nb) in zip(want, case["aggs"]) if k == "sum"]
        assert all((c.values < 0).any() for c in sums)
        if case["big"]:
            assert all((np.abs(c.values) > 1 << 32).any() for c in sums)


Q6 = (("sum", 4),)            # one SUM of a 4-byte product, p8 6

SIMPLE_CASES = {
    # each kind alone, then together; own validity planes and aliased
    "count_star": simple_case((("count_star", 0),)),
    "count": simple_case((("count", 0),)),
    "sum": simple_case((("sum", 2),)),
    "avg": simple_case((("avg", 2),)),
    "together": simple_case(ALL_FOUR),
    "together-ok-is-mask": simple_case(ALL_FOUR, ok_is_mask=True),
    "q6": simple_case(Q6),
    "q6-decimal-frac-4": simple_case(Q6, fracs=(4,)),
    "decimal-frac-beside-int": simple_case(
        (("count_star", 0), ("sum", 8), ("sum", 3), ("avg", 2)),
        fracs=(None, 2, None, None), big=True),
    # tiles add
    "three-parts": simple_case(ALL_FOUR, parts=3),
    "three-parts-ok-is-mask-big": simple_case(ALL_FOUR, parts=3, big=True,
                                              ok_is_mask=True),
    "LO-128": simple_case(ALL_FOUR, LO=128),
    # widths: negative sums come out of the bias term; nb 8 wraps int64
    **{f"nb-{nb}": simple_case((("sum", nb), ("avg", nb)), seed=nb)
       for nb in range(1, 9)},
    **{f"big-nb-{nb}": simple_case((("count", 0), ("sum", nb), ("avg", nb)),
                                   big=True, seed=nb) for nb in (2, 4, 8)},
    # ONE row whatever reached the slot: COUNT 0, SUM and AVG NULL
    "no-row": simple_case(ALL_FOUR, rows=False),
    "no-row-ok-is-mask": simple_case(ALL_FOUR, rows=False, ok_is_mask=True),
    "no-row-three-parts": simple_case(ALL_FOUR, rows=False, parts=3),
    "no-row-decimal-frac": simple_case(Q6, rows=False, fracs=(4,)),
    # rows, none of whose arguments was non-NULL
    "rows-whose-arguments-are-all-null": simple_case(ALL_FOUR,
                                                     zero_nonnull=True),
}


@needs_native
@pytest.mark.parametrize("name", SIMPLE_CASES)
def test_a_one_slot_grid_finalizes_as_finalize_simple(name, monkeypatch):
    case = SIMPLE_CASES[name]
    fracs = case["fracs"]
    args = accumulator(case)
    specs, slots = args[4], args[5]
    assert slots == 1
    want = simple_chain(*args, fracs=fracs)
    finalized, was_native = agg_mod.finalize_packed(*args)
    assert was_native
    (keys, key_valid), planes = finalized
    assert keys is None and key_valid is None
    assert all(len(vals) == len(ok) == 1 for vals, ok in planes)
    got = agg_mod._hash_columns(
        DeviceAggregator._agg_out(plan_of(specs, fracs)), finalized)
    assert_same_columns(got, want)
    assert wire_bytes(specs, got, fracs, keyed=False) == \
        wire_bytes(specs, want, fracs, keyed=False)
    # the chain through ``_simple_planes`` (what the native call
    # declines) wraps to the same columns
    monkeypatch.setattr(native, "hash_finalize_packed", None)
    chained, was_native = agg_mod.finalize_packed(*args)
    assert not was_native
    assert_same_columns(agg_mod._hash_columns(
        DeviceAggregator._agg_out(plan_of(specs, fracs)), chained), want)
    # the oracle is not vacuous
    for col, (kind, nb), frac in zip(want, case["aggs"],
                                     fracs or (None,) * len(want)):
        if kind in ("count_star", "count"):
            assert col.validity[0]
            reached = case["groups"] and not (
                kind == "count" and case["zero_nonnull"]
                and not case["ok_is_mask"])
            assert (col.values[0] > 0) == bool(reached)
        else:
            assert col.validity[0] == bool(
                case["groups"] and not case["zero_nonnull"])
            if frac is not None:
                assert col.eval_type is EvalType.DECIMAL
                assert not col.validity[0] or \
                    col.values[0].as_tuple().exponent == -frac
            if kind == "sum" and case["big"] and nb >= 4 and frac is None:
                assert abs(int(col.values[0])) > 1 << 32


@needs_native
def test_the_native_finalize_never_lets_go_of_the_gil():
    """It is one call because nothing inside it hands the GIL on."""
    src = Path(native.__file__).with_name("fastbuild.cpp").read_text()
    body = re.search(r"\nPyObject\* hash_finalize_packed\(.*?\n}\n", src,
                     re.S).group(0)
    assert "return PyLong_FromSsize_t(k);" in body
    assert "ALLOW_THREADS" not in body


# -------------------------------------------------- what it must decline

@pytest.fixture(scope="module")
def runner():
    return DeviceRunner(mesh=make_mesh(jax.devices()[:1]))


def outcome(fn, *args):
    try:
        return "columns", fn(*args)
    except Exception as e:                      # the chain's own error
        return "raised", type(e)


def f32_layout(args):
    """A real SUM rides a float plane (never on the Pallas path:
    ``pallas_hash.supported`` wants pf == 0)."""
    layouts, _p8, pf = build_layouts(args[4], [False, True], [0, 0], None)
    assert pf == 1 and layouts[1].f32_plane == 0
    return args[:3] + (layouts,) + args[4:]


def unknown_kind(args):
    lay = args[3][1]
    strange = PlaneLayout("min", ok_plane=lay.ok_plane,
                          byte_planes=lay.byte_planes, nb=lay.nb)
    specs = [args[4][0], AggSpec("min", 1, EvalType.INT)]
    return args[:3] + ([args[3][0], strange], specs) + args[5:]


def uint64_domain(args):
    return args[:6] + (I64_MAX - 5,) + args[7:]         # base


def uint64_slot_keys(args):
    keys = np.arange(args[7], dtype=np.uint64) + np.uint64(I64_MAX - 5)
    return args[:8] + (keys,)


def int64_parts(args):
    return ([p.astype(np.int64) for p in args[0]],) + args[1:]


def strided_parts(args):
    wide = [np.repeat(p, 2, axis=2) for p in args[0]]
    return ([w[:, :, ::2] for w in wide],) + args[1:]


def sublane_minor_parts(args):
    """The accumulator as a TPU hands it back past 128 sublanes of slots
    (a result layout of ``{2,3,1,0}``: the sublane dimension minor-most,
    kept by ``np.asarray`` as strides)."""
    return ([np.asfortranarray(p.transpose(1, 2, 0)).transpose(2, 0, 1)
             for p in args[0]],) + args[1:]


NOT_CONTIGUOUS = {"strided": strided_parts,
                  "sublane-minor": sublane_minor_parts}


@needs_native
@pytest.mark.parametrize("one_slot", [False, True], ids=["grid", "one-slot"])
@pytest.mark.parametrize("name", NOT_CONTIGUOUS)
def test_parts_that_are_not_contiguous_are_copied_and_served_natively(
        name, one_slot, runner):
    """PR 28 sent them to the numpy chain; on a 16,384-slot grid that
    was every task's finalize, ~24 strided passes (PERF.md section 6,
    PR 40).  One copy, then the one native call, the same planes."""
    aggs = (("count_star", 0), ("sum", 2))
    plain = accumulator(
        simple_case(aggs, parts=2, seed=5) if one_slot else
        make_case(aggs, null_group=True, parts=2, seed=5))
    args = NOT_CONTIGUOUS[name](plain)
    assert not any(p.flags.c_contiguous for p in args[0])
    assert all(np.array_equal(a, b) for a, b in zip(args[0], plain[0]))
    want = (simple_chain if one_slot else numpy_chain)(*plain)
    before = runner.mesh_stats()["finalize"]
    (parts, LO, p8, layouts, specs, slots, base, capacity, slot_keys) = args
    got = runner._aggregator._packed_columns(
        plan_of(specs), parts, LO, p8, layouts, slots, base, capacity,
        slot_keys)
    after = runner.mesh_stats()["finalize"]
    assert_same_columns(got, want)
    assert wire_bytes(specs, got, keyed=not one_slot) == \
        wire_bytes(specs, want, keyed=not one_slot)
    assert after["native"] - before["native"] == 1
    assert after["numpy"] == before["numpy"]


DECLINED = {
    "f32-plane": f32_layout,
    "kind-outside-the-four": unknown_kind,
    "uint64-key-domain": uint64_domain,
    "uint64-slot-keys": uint64_slot_keys,
    "int64-parts": int64_parts,
    "extension-absent": lambda args: args,
}
# a grid of one slot has no key domain to be outside int64
ONE_SLOT = "one-slot-"
DECLINED_GRIDS = [*DECLINED, *(ONE_SLOT + name for name in DECLINED
                               if "uint64" not in name)]


@pytest.mark.parametrize("name", DECLINED_GRIDS)
def test_what_the_native_call_declines_takes_the_numpy_chain(
        name, runner, monkeypatch):
    one_slot = name.startswith(ONE_SLOT)
    name = name.removeprefix(ONE_SLOT)
    aggs = (("count_star", 0), ("sum", 2))
    args = DECLINED[name](accumulator(
        simple_case(aggs, parts=2, seed=5) if one_slot else
        make_case(aggs, null_group=True, parts=2, seed=5)))
    if name == "extension-absent":
        monkeypatch.setattr(native, "hash_finalize_packed", None)
    else:
        def never(*_a):
            raise AssertionError(f"{name}: handed to the native call")
        monkeypatch.setattr(native, "hash_finalize_packed", never)
    want = outcome(simple_chain if one_slot else numpy_chain, *args)
    before = runner.mesh_stats()["finalize"]
    (parts, LO, p8, layouts, specs, slots, base, capacity, slot_keys) = args
    got = outcome(runner._aggregator._packed_columns, plan_of(specs), parts, LO, p8,
                  layouts, slots, base, capacity, slot_keys)
    after = runner.mesh_stats()["finalize"]
    assert got[0] == want[0]
    if want[0] == "columns":
        assert_same_columns(got[1], want[1])
        assert [len(c.values) for c in got[1]] == \
            ([1, 1] if one_slot else [701] * 3)
        if "uint64" in name:
            assert got[1][-1].values.dtype == np.uint64
        assert wire_bytes(specs, got[1], keyed=not one_slot) == \
            wire_bytes(specs, want[1], keyed=not one_slot)
    else:
        # never on the Pallas path: the chain fails as it did before
        assert name in ("f32-plane", "kind-outside-the-four")
        assert got[1] is want[1]
    # counted once a finalize, as what it was
    assert after["native"] == before["native"]
    assert after["numpy"] - before["numpy"] == (want[0] == "columns")
    assert after["native_available"] is (name != "extension-absent")


@needs_native
def test_the_runner_counts_a_native_finalize(runner):
    args = accumulator(make_case(ALL_FOUR, mode="sparse", parts=3))
    (parts, LO, p8, layouts, specs, slots, base, capacity, slot_keys) = args
    before = runner.mesh_stats()["finalize"]
    cols = runner._aggregator._packed_columns(plan_of(specs), parts, LO, p8, layouts,
                                  slots, base, capacity, slot_keys)
    assert_same_columns(cols, numpy_chain(*args))
    after = runner.mesh_stats()["finalize"]
    assert after == {"native": before["native"] + 1,
                     "numpy": before["numpy"], "native_available": True}


# ----------------------------------------- the one-slot grid, on the runner


@needs_native
@pytest.mark.parametrize("rows", [True, False], ids=["rows", "no-row"])
def test_the_runner_counts_a_native_finalize_of_one_slot(runner, rows):
    args = accumulator(simple_case(ALL_FOUR, rows=rows, parts=3))
    (parts, LO, p8, layouts, specs, slots, base, capacity, slot_keys) = args
    before = runner.mesh_stats()["finalize"]
    cols = runner._aggregator._packed_columns(
        plan_of(specs), parts, LO, p8, layouts, slots, base, capacity,
        slot_keys)
    assert_same_columns(cols, simple_chain(*args))
    assert [c.validity[0] for c in cols] == [True, True, rows, rows]
    after = runner.mesh_stats()["finalize"]
    assert after == {"native": before["native"] + 1,
                     "numpy": before["numpy"], "native_available": True}


@needs_native
@pytest.mark.parametrize("fault", ["validity-without-a-key", "empty-grid"])
def test_the_native_call_refuses_a_keyless_grid_it_cannot_read(fault):
    parts, LO, p8, layouts, *_ = accumulator(simple_case())
    desc = agg_mod._native_layout_desc(layouts)
    outs = [(np.empty(1, np.int64), np.empty(1, np.bool_)) for _ in layouts]
    if fault == "empty-grid":
        args, err = ([p[:, :0] for p in parts], None, None), ValueError
    else:
        args, err = (parts, None, np.empty(1, np.bool_)), TypeError
    with pytest.raises(err):
        native.hash_finalize_packed(args[0], LO, p8, 1, 0, None, desc,
                                    args[1], args[2], outs)
