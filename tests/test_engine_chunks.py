"""The in-memory engine keeps a CF as CHUNKS of sorted keys
(engine/memory.py): a snapshot pins a generation, the first write after
it copies the list of chunks and then only the chunk it writes into.
Held here against a dict model with small chunks: every operation, the
frozen view of every snapshot taken on the way, the iterator in both
directions under bounds, and that a write beside a snapshot copies one
chunk and shares the rest."""

import bisect
import random

import pytest

from tikv_tpu.engine import memory
from tikv_tpu.engine.memory import MemoryEngine
from tikv_tpu.engine.traits import CF_DEFAULT, CF_WRITE


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    monkeypatch.setattr(memory, "_CHUNK", 8)


def key(i: int) -> bytes:
    return b"k%06d" % i


def check_view(view, model: dict, rng) -> None:
    """``view`` (an engine or a snapshot) holds exactly ``model``."""
    keys = sorted(model)
    got_k, got_v, _skip = view.range_cf(CF_WRITE, b"", b"\xff")
    assert got_k == keys and got_v == [model[k] for k in keys]
    for _ in range(20):
        k = key(rng.randrange(0, 400))
        assert view.get_value_cf(CF_WRITE, k) == model.get(k)
    lo, hi = sorted((key(rng.randrange(0, 400)), key(rng.randrange(0, 400))))
    inside = [k for k in keys if lo <= k < hi]
    assert view.range_cf(CF_WRITE, lo, hi)[0] == inside
    it = view.iterator_cf(CF_WRITE, lo, hi)
    walked = []
    ok = it.seek_to_first()
    while ok:
        walked.append((it.key(), it.value()))
        ok = it.next()
    assert walked == [(k, model[k]) for k in inside]
    back = []
    ok = it.seek_to_last()
    while ok:
        back.append(it.key())
        ok = it.prev()
    assert back == inside[::-1]
    probe = key(rng.randrange(0, 400))
    assert it.seek(probe) == any(k >= probe for k in inside)
    if it.valid():
        assert it.key() == inside[bisect.bisect_left(inside, probe)]
    assert it.seek_for_prev(probe) == any(k <= probe for k in inside)
    if it.valid():
        assert it.key() == inside[bisect.bisect_right(inside, probe) - 1]


@pytest.mark.parametrize("seed", range(6))
def test_the_engine_is_its_dict_model_and_snapshots_stay_frozen(seed):
    rng = random.Random(seed)
    eng = MemoryEngine()
    model: dict = {}
    frozen = []
    for step in range(400):
        op = rng.random()
        if op < 0.45:
            k, v = key(rng.randrange(0, 400)), b"v%d" % step
            eng.put_cf(CF_WRITE, k, v)
            model[k] = v
        elif op < 0.65:
            k = key(rng.randrange(0, 400))
            eng.delete_cf(CF_WRITE, k)
            model.pop(k, None)
        elif op < 0.72:
            lo, hi = sorted((rng.randrange(0, 400), rng.randrange(0, 400)))
            wb = eng.write_batch()
            wb.delete_range_cf(CF_WRITE, key(lo), key(hi))
            eng.write(wb)
            for k in [k for k in model if key(lo) <= k < key(hi)]:
                del model[k]
        elif op < 0.80:
            # an ingested run: sometimes into a gap, sometimes overlapping
            start = rng.randrange(0, 380)
            run = sorted({key(start + rng.randrange(0, 20))
                          for _ in range(rng.randrange(1, 12))})
            vals = [b"i%d" % step] * len(run)
            wb = eng.write_batch()
            wb.ingest_cf(CF_WRITE, run, vals)
            eng.write(wb)
            model.update(zip(run, vals))
        elif op < 0.88:
            frozen.append((eng.snapshot(), dict(model)))
        if step % 25 == 0:
            check_view(eng, model, rng)
            for snap, was in frozen[-4:]:
                check_view(snap, was, rng)
        data = eng._cfs[CF_WRITE]
        assert data.n == len(model)
        assert data.firsts == [c.keys[0] for c in data.chunks]
        assert all(0 < len(c.keys) == len(c.vals) <= 2 * memory._CHUNK
                   for c in data.chunks) or op >= 0.72
    check_view(eng, model, rng)
    for snap, was in frozen:
        check_view(snap, was, rng)


def test_a_write_beside_a_snapshot_copies_one_chunk():
    eng = MemoryEngine()
    wb = eng.write_batch()
    keys = [key(i) for i in range(0, 2000, 2)]
    wb.ingest_cf(CF_WRITE, keys, [b"v"] * len(keys))
    eng.write(wb)
    before = eng._cfs[CF_WRITE]
    assert len(before.chunks) == len(keys) // memory._CHUNK
    snap = eng.snapshot()
    eng.put_cf(CF_WRITE, key(501), b"new")
    after = eng._cfs[CF_WRITE]
    assert after is not before and after.gen == before.gen + 1
    copied = [i for i, (a, b) in enumerate(zip(before.chunks, after.chunks))
              if a is not b]
    assert len(copied) == 1 and len(after.chunks) == len(before.chunks)
    assert snap.get_value_cf(CF_WRITE, key(501)) is None
    assert eng.get_value_cf(CF_WRITE, key(501)) == b"new"
    # a second write into the same chunk copies nothing more
    chunk = after.chunks[copied[0]]
    eng.put_cf(CF_WRITE, key(503), b"new")
    assert eng._cfs[CF_WRITE] is after and after.chunks[copied[0]] is chunk
    # the other CFs were pinned too and are untouched
    assert eng.get_value_cf(CF_DEFAULT, key(1)) is None


def test_a_chunk_splits_and_an_emptied_one_goes():
    eng = MemoryEngine()
    for i in range(100):
        eng.put_cf(CF_WRITE, key(i), b"v")
    data = eng._cfs[CF_WRITE]
    assert len(data.chunks) > 1
    assert max(len(c.keys) for c in data.chunks) <= 2 * memory._CHUNK
    for i in range(100):
        eng.delete_cf(CF_WRITE, key(i))
    assert data.chunks == [] and data.firsts == [] and data.n == 0
    eng.put_cf(CF_WRITE, key(7), b"again")
    assert eng.range_cf(CF_WRITE, b"", b"\xff")[0] == [key(7)]


def test_a_run_ingested_into_a_gap_lands_as_its_own_chunks():
    """A table loaded beside another: no merge of the whole CF."""
    eng = MemoryEngine()
    for lo in (1000, 0, 500):      # out of key order, none overlapping
        wb = eng.write_batch()
        run = [key(lo + i) for i in range(40)]
        wb.ingest_cf(CF_WRITE, run, [b"t%d" % lo] * 40)
        eng.write(wb)
    keys, vals, _ = eng.range_cf(CF_WRITE, b"", b"\xff")
    assert keys == [key(lo + i) for lo in (0, 500, 1000) for i in range(40)]
    assert vals[0] == b"t0" and vals[40] == b"t500" and vals[-1] == b"t1000"
