"""What a learned fast-path class fixes is computed once and carried by
its entry (server/fastpath.py, copr/dag.py): ``find`` reaches a request's
class in one probe through the index on the templates' last fixed
segment, ``make_dag`` rebuilds only the paths compiled at learn, and the
DAG it makes arrives with its class key and its re-stamped plan key.

Unit cases drive ``FastPathCache`` with the wire bytes the benchmark's
four request kinds send (``TxnClient.coprocessor`` /
``coprocessor_fanout``'s request dicts); the served cases go through the
gRPC stack of tests/test_fastpath.py."""

import dataclasses
import json
import os
import sys
import threading
import types

import pytest

from tikv_tpu.codec.keys import table_record_key
from tikv_tpu.executors.ranges import KeyRange
from tikv_tpu.server import fastpath, wire
from tikv_tpu.server.fastpath import FastPathCache
from tikv_tpu.testing.fixture import int_table
from tikv_tpu.utils import failpoint

from test_fastpath import _fp, _load, _sel, rig  # noqa: F401 — the fixture

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:       # the table and request kinds import ``byname``
    sys.path.append(BENCH)

import byname  # noqa: E402

# request kind → (configuration, traffic file): what the cells send
CELLS = {
    "hash_agg": ("int3-10m", "agg-closed8"),
    "hash_agg_regions": ("int3-10m-regions96", "agg-regions96-closed4"),
    "tpch_q6": ("tpch-sf1-lineitem-regions96", "q6-lineitem-sf1-closed4"),
    "tpch_q1": ("tpch-sf1-lineitem-q1-regions96", "q1-lineitem-sf1-closed4"),
}
ROWS = 1 << 16


class Storage:
    """What ``learn`` asks of a dispatch-tier class's storage."""
    feed_lineage = object()
    scan_columns = None


STORAGE = Storage()


class Client:
    """The request kinds' ``prepare`` wants a TSO and a place to keep
    its walk over the tuples."""

    def __init__(self):
        self.ts = 1 << 40

    def tso(self) -> int:
        self.ts += 1
        return self.ts


@pytest.fixture(scope="module")
def cells():
    """kind name → (module, ctx, params)."""
    out = {}
    for name, (config, traffic) in CELLS.items():
        with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
            spec = json.load(f)["table"]
        with open(os.path.join(BENCH, "traffic", f"{traffic}.json")) as f:
            params = json.load(f)["kinds"][name]["params"]
        table = byname.load("tables", spec["kind"]).fixture(spec)
        out[name] = (byname.load("requests", name),
                     types.SimpleNamespace(table=table, rows=ROWS), params)
    return out


def plans(cells, name: str) -> list:
    """Every plan the kind's walk sends: Q6's 80 tuples, Q1's 61 DELTAs,
    the one GROUP BY of the two int cells."""
    kind, ctx, params = cells[name]
    client = Client()
    if name == "tpch_q6":
        return [kind.plan(ctx, i, client.tso())
                for i in range(len(kind.TUPLES))]
    if name == "tpch_q1":
        return [kind.plan(ctx, i, client.tso())
                for i in range(len(kind.DELTAS))]
    dag = kind.prepare(ctx, client, params)
    return [dag[0] if isinstance(dag, tuple) else dag]


def task_raw(dag, region=None, regions: int = 1, **extra) -> bytes:
    """The bytes of one request: ``TxnClient.coprocessor``'s where
    ``region`` is None, else those of ``_run_cop_task`` for the task of
    region ``region`` of ``regions`` (its piece of the table's handles
    and its ``context``, the last key)."""
    req = {"tp": 103, "dag": wire.enc_dag(dag), "force_backend": None,
           "paging_size": 0, "resume_token": None,
           "resource_group": "default", "request_source": "", **extra}
    if region is not None:
        table_id = dag.executors[0].table_id
        step = ROWS // regions
        piece = KeyRange(table_record_key(table_id, region * step),
                         table_record_key(table_id, (region + 1) * step))
        req["dag"] = dict(req["dag"], ranges=wire.enc_ranges((piece,)))
        req["context"] = {"region_id": 1000 + region, "version": 7}
    return wire.pack(req)


def learn(fp: FastPathCache, raw: bytes, storage=STORAGE) -> bool:
    """Admit ``raw``'s class as the slow path would after serving it on
    the device: the decoded DAG, its class, its share key."""
    req = wire.unpack(raw)
    dag = wire.dec_dag(req["dag"])
    ctx = req.get("context") or {"region_id": 1, "version": 1}
    return fp.learn(raw, wire.unpack(raw), {
        "dag": dag, "class_key": ("copr", dag.class_key()),
        "storage": storage, "backend": "device",
        "decision": "device_batched", "region": ctx["region_id"],
        "epoch_version": ctx["version"],
        "bkey": ("share", id(storage), 1, dag.plan_key(), dag.ranges)})


def old_walk(fp: FastPathCache, raw: bytes):
    """The lookup as it was: every entry's whole ``match``, one by one."""
    for ent in fp._entries:
        values = ent.template.match(raw)
        if values is not None:
            return ent, values
    return None, None


def hit_dag(ent, values):
    """What ``_fastpath_serve`` builds of a hit."""
    consts = [v for s, v in zip(ent.template.slots, values)
              if s.kind == fastpath.K_CONST]
    start_ts, = [v for s, v in zip(ent.template.slots, values)
                 if s.kind == fastpath.K_START_TS]
    return ent.make_dag(consts, start_ts)


def walked(dag):
    """``dag`` again with no memo: its keys walked off the tree."""
    return dataclasses.replace(dag)


# ------------------------------------------------- find


@pytest.mark.parametrize("regions", [12, 64])
def test_region_classes_hit_round_robin_are_found_in_one_probe(cells,
                                                              regions):
    """A fan-out's worst case for a move-to-front walk: the classes
    share every byte up to their ranges, and the tasks arrive region
    after region."""
    dags = plans(cells, "tpch_q6")
    fp = FastPathCache(capacity=64)
    for r in range(regions):
        assert learn(fp, task_raw(dags[0], r, regions))
    assert fp.stats()["classes"] == regions
    for dag in dags[1:7]:
        for r in range(regions):
            raw = task_raw(dag, r, regions)
            ent, values = fp.find(raw)
            assert ent is not None and ent.region_ctx == (1000 + r, 7)
            assert hit_dag(ent, values) == wire.dec_dag(
                wire.unpack(raw)["dag"])
    found = fp.stats()["find"]
    assert found["probes"] == found["finds"] == 6 * regions
    assert fp.stats()["miss"] == 0


def test_two_classes_with_one_discriminator_are_both_found(cells):
    """One region and tenant, two plans: two templates with one last
    segment, walked among themselves, the one hit last first.  A second
    tenant's bytes lie behind the last slot too: a segment of its own."""
    q6, q1 = plans(cells, "tpch_q6"), plans(cells, "tpch_q1")
    fp = FastPathCache(capacity=64)
    raws = [task_raw(q6[0], 3, 12), task_raw(q1[0], 3, 12),
            task_raw(q6[0], 3, 12, resource_group="tenant-b")]
    for raw in raws:
        assert learn(fp, raw)
    tails = [e.template.segments[-1] for e in fp._entries]
    assert tails[0] == tails[1] != tails[2] and len(fp._index[0]) == 2
    found = [fp.find(raw)[0] for raw in raws]
    assert all(e is not None for e in found) and len(set(found)) == 3
    assert [e.resource_group for e in found] == \
        ["default", "default", "tenant-b"]
    # walked most recently hit (or learned) first: Q1's before Q6's,
    # then Q6's before Q1's
    assert fp.stats()["find"] == {"finds": 3, "probes": 2 + 2 + 1}
    assert fp.find(task_raw(q1[5], 3, 12))[0] is found[1]
    assert fp.stats()["find"] == {"finds": 4, "probes": 5 + 1}
    assert fp.find(task_raw(q6[9], 3, 12))[0] is found[0]
    assert fp.find(task_raw(q6[10], 3, 12))[0] is found[0]
    assert fp.stats()["find"] == {"finds": 6, "probes": 6 + 2 + 1}


def test_the_capacity_bound_still_evicts_the_coldest(cells):
    dag = plans(cells, "hash_agg_regions")[0]
    fp = FastPathCache(capacity=4)
    raws = [task_raw(dag, r, 8) for r in range(6)]
    for raw in raws[:4]:
        assert learn(fp, raw)
    for raw in (raws[0], raws[2], raws[3]):     # region 1 goes cold
        assert fp.find(raw)[0] is not None
    assert learn(fp, raws[4])
    assert fp.stats()["classes"] == 4
    assert fp.find(raws[1])[0] is None
    assert all(fp.find(raws[r])[0] is not None for r in (0, 2, 3, 4))
    # a smaller bound keeps the most recently hit
    fp.configure(capacity=2)
    assert [fp.find(raws[r])[0] is not None for r in range(5)] == \
        [False, False, False, True, True]
    fp.configure(capacity=0)
    assert fp.find(raws[4]) == (None, "disabled")


def test_sixteen_threads_find_side_by_side(cells):
    """Sixteen finders over twelve classes while a seventeenth thread
    learns four more against a bound of fourteen, so the index is rebuilt
    and the coldest evicted under them: a find is its own class's or a
    miss, never another's, and no count is lost."""
    dags = plans(cells, "tpch_q6")
    fp = FastPathCache(capacity=14)
    for r in range(12):
        assert learn(fp, task_raw(dags[0], r, 16))
    raws = [[task_raw(dags[1 + t], r, 16) for r in range(12)]
            for t in range(16)]
    extra = [task_raw(dags[0], r, 16) for r in (12, 13, 14, 15)]
    wrong, found = [], [0] * 16
    gate = threading.Barrier(17)

    def session(t):
        gate.wait(30)
        for _ in range(5):
            for r, raw in enumerate(raws[t]):
                ent, values = fp.find(raw)
                if ent is None:
                    continue
                found[t] += 1
                if ent.region_ctx != (1000 + r, 7) or \
                        hit_dag(ent, values).plan_key() != \
                        dags[1 + t].plan_key():
                    wrong.append((t, r))

    def learner():
        gate.wait(30)
        for _ in range(5):
            for raw in extra:
                learn(fp, raw)

    threads = [threading.Thread(target=session, args=(t,))
               for t in range(16)] + [threading.Thread(target=learner)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not wrong
    st = fp.stats()
    assert st["classes"] == 14 and st["learned"] == 12 + 20
    assert st["find"]["finds"] == 16 * 5 * 12
    assert st["find"]["probes"] == sum(found) == \
        st["find"]["finds"] - st["miss"]
    assert sum(found) >= 16 * 5 * 10        # two classes at most are out


@pytest.mark.parametrize("name", sorted(CELLS))
def test_find_gives_what_the_old_walk_gave(cells, name):
    """On every template of the kind's classes, for every plan of its
    walk: the same entry, the same values, and a request no class
    matches is a miss for both."""
    dags = plans(cells, name)
    regions = cells[name][2].get("regions")
    tasks = [None] if regions is None else range(regions)
    fp = FastPathCache(capacity=64)
    for r in tasks:
        assert learn(fp, task_raw(dags[0], r, regions or 1))
    for dag in dags:
        for r in tasks:
            raw = task_raw(dag, r, regions or 1)
            ent, values = fp.find(raw)
            assert ent is not None and (ent, values) == old_walk(fp, raw)
    found = fp.stats()["find"]
    assert found["probes"] == found["finds"] == len(dags) * len(tasks)
    # a region nobody learned: no candidate, no probe
    stranger = task_raw(dags[0], 77, 100)
    assert fp.find(stranger) == (None, "mismatch") and \
        old_walk(fp, stranger) == (None, None)
    assert fp.stats()["find"]["probes"] == found["probes"]
    if regions is not None:
        # a known region over other ranges: its class is tried, whole,
        # and refuses
        moved = task_raw(dags[0], 0, 5)
        assert fp.find(moved) == (None, "mismatch") and \
            old_walk(fp, moved) == (None, None)
        assert fp.stats()["find"]["probes"] == found["probes"] + 1
    assert fp.stats()["reasons"] == {
        "miss:no_template": 1,
        **({} if regions is None else {"miss:mismatch": 1})}


# ------------------------------------------------- the keys a hit carries


def carried_and_walked(cells, name: str):
    """Every (hit's DAG, the same request's decoded DAG) of the kind's
    walk, and the cache that built the first."""
    dags = plans(cells, name)
    regions = cells[name][2].get("regions")
    fp = FastPathCache(capacity=64)
    region = None if regions is None else 2
    assert learn(fp, task_raw(dags[0], region, regions or 1))
    pairs = []
    for dag in dags:
        raw = task_raw(dag, region, regions or 1)
        ent, values = fp.find(raw)
        pairs.append((hit_dag(ent, values),
                      wire.dec_dag(wire.unpack(raw)["dag"])))
    return fp, pairs


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_carried_keys_equal_the_walked_ones(cells, name):
    fp, pairs = carried_and_walked(cells, name)
    assert len(pairs) == {"tpch_q6": 80, "tpch_q1": 61}.get(name, 1)
    for hit, decoded in pairs:
        assert hit == decoded
        assert "_plan_key" in hit.__dict__ and \
            "_plan_key" not in decoded.__dict__
        assert hit.class_key() == decoded.class_key() == \
            walked(hit).class_key()
        assert hit.plan_key() == decoded.plan_key() == \
            walked(hit).plan_key()
    assert len({hit.plan_key() for hit, _ in pairs}) == len(pairs)
    assert len({hit.class_key() for hit, _ in pairs}) == 1
    assert fp.stats()["keys"]["walked"] == 0
    # the counter counts: a hit's DAG made to walk says so
    hit = pairs[0][0]
    del hit.__dict__["_plan_key"]
    assert hit.plan_key() == pairs[0][1].plan_key()
    assert fp.stats()["keys"]["walked"] == 1


def test_a_limb_variants_memo_and_kernel_keys_come_off_the_carried_keys(
        cells):
    """Q1's plan with its charge summed as limbs: the plan cache, the
    request memo's key and the kernel's take a hit's carried keys for
    the walked ones."""
    import jax

    from tikv_tpu.device import DeviceRunner, pallas_hash
    from tikv_tpu.parallel import make_mesh
    fp, pairs = carried_and_walked(cells, "tpch_q1")
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    (hit, decoded), (other, _) = pairs[0], pairs[1]
    plan = runner._analyze(decoded)
    assert plan is not None and runner._analyze(hit) is plan
    assert runner._analyze(other) is not plan
    variant = runner._limb_variant(plan, ((3, False),))
    assert runner._limb_variant(runner._analyze(hit), ((3, False),)) \
        is variant
    assert runner._meta_key(hit, variant) == \
        runner._meta_key(decoded, variant)
    assert runner._meta_key(other, variant) == \
        runner._meta_key(decoded, variant)        # const-blind
    assert pallas_hash.key_consts(variant) != pallas_hash.key_consts(plan)
    assert fp.stats()["keys"]["walked"] == 0


def test_a_tiled_requests_dag_keeps_the_keys_it_came_with(cells):
    """The tile path's DAG over no ranges (device/runner.py
    ``_handle_local``) is the request's own plan: ``over_ranges`` hands
    it the keys, carried or walked, and walks nothing."""
    fp, pairs = carried_and_walked(cells, "hash_agg_regions")
    hit, decoded = pairs[0]
    tiled = hit.over_ranges(())
    assert tiled.ranges == () and tiled == dataclasses.replace(
        decoded, ranges=())
    assert tiled.__dict__["_plan_key"] is hit.__dict__["_plan_key"]
    assert tiled.class_key() == decoded.class_key()
    assert tiled.plan_key() == decoded.plan_key()
    assert fp.stats()["keys"]["walked"] == 0
    # a DAG nobody prebound walks once and hands that on
    fresh = walked(decoded)
    fresh.plan_key()
    again = fresh.over_ranges(())
    assert again.__dict__["_plan_key"] is fresh.__dict__["_plan_key"]
    assert "_class_key" not in again.__dict__
    assert again.class_key() == decoded.class_key()


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_dag_request_is_blind_to_its_memo(cells, name):
    """``==``, ``hash`` and ``repr`` read the fields alone."""
    _fp_cache, pairs = carried_and_walked(cells, name)
    hit, decoded = pairs[-1]
    assert not any(k.startswith("_") for k in decoded.__dict__)
    before = repr(decoded), hash(decoded)
    assert (repr(hit), hash(hit)) == before and hit == decoded
    decoded.plan_key(), decoded.class_key()
    assert (repr(decoded), hash(decoded)) == before and hit == decoded
    assert decoded.plan_key() is decoded.plan_key()     # kept, not rewalked
    assert {f.name for f in dataclasses.fields(hit)} == {
        "executors", "ranges", "start_ts", "output_offsets", "encode_type"}
    # a copy with another field changed starts with no memo
    assert not any(k.startswith("_") for k in
                   dataclasses.replace(hit, start_ts=5).__dict__)


@pytest.mark.parametrize("which", ["plan_key", "class_key"])
def test_a_class_whose_carried_key_differs_is_refused(cells, monkeypatch,
                                                      which):
    """Self-validation 3: the keys ``make_dag`` carries are compared with
    the decoded DAG's walked ones when the class is learned."""
    dag = plans(cells, "tpch_q6")[0]
    raw = task_raw(dag, 0, 12)
    fp = FastPathCache(capacity=64)
    if which == "plan_key":
        compiled = fastpath._key_template

        def skewed(key):
            fill, n = compiled(key)
            return (lambda consts: fill(consts[::-1])), n
        monkeypatch.setattr(fastpath, "_key_template", skewed)
    else:
        carry = type(dag).carry_keys
        monkeypatch.setattr(
            type(dag), "carry_keys",
            lambda self, class_key, plan_key, on_walk=None: carry(
                self, class_key + ("?",), plan_key, on_walk))
    assert not learn(fp, raw)
    st = fp.stats()
    assert st["classes"] == 0 and st["learned"] == 0
    assert st["reasons"] == {"bypass:key mismatch": 1}
    # ... and stays refused at this configuration, without a second try
    assert not learn(fp, raw)
    assert fp.stats()["reasons"] == {"bypass:key mismatch": 1}
    monkeypatch.undo()
    fp.bump_config_gen()
    assert learn(fp, raw) and fp.find(raw)[0] is not None


# ------------------------------------------------- served


def ask(rig, dag, **kw):
    return rig["client"].coprocessor(dag, deadline_ms=30_000, timeout=60,
                                     **kw)


def test_a_constant_outside_its_bucket_misses_and_is_fully_decoded(rig):
    """A known ``context``-less class, one constant past int32: the
    guard refuses the probe, the request takes the full decode, answers
    exactly, and is learned as a class of its own."""
    c = rig["client"]
    table = int_table(2, table_id=9801)
    _load(rig, table, [(h, {"c0": h % 3, "c1": h % 40})
                       for h in range(600)])
    ask(rig, _sel(table, 7, ts=c.tso()))
    st0 = _fp(rig).stats()
    assert ask(rig, _sel(table, 11, ts=c.tso()))["rows"]
    st1 = _fp(rig).stats()
    assert st1["hit"] - st0["hit"] == 1 and st1["miss"] == st0["miss"]
    wide = ask(rig, _sel(table, -(1 << 40), ts=c.tso()))
    st2 = _fp(rig).stats()
    assert len(wide["rows"]) == 600 and wide["backend"] == "device"
    assert st2["miss"] - st1["miss"] == 1 and st2["hit"] == st1["hit"]
    assert st2["reasons"].get("miss:mismatch", 0) - \
        st1["reasons"].get("miss:mismatch", 0) == 1
    assert st2["find"]["probes"] - st1["find"]["probes"] >= 1
    assert st2["learned"] - st1["learned"] == 1
    # the class it learned serves its bucket's constants from here on
    ask(rig, _sel(table, -(1 << 41), ts=c.tso()))
    assert _fp(rig).stats()["hit"] - st2["hit"] == 1


def test_the_corrupt_arm_misses_and_the_class_relearns(rig):
    c = rig["client"]
    table = int_table(2, table_id=9802)
    _load(rig, table, [(h, {"c0": h % 3, "c1": h % 40})
                       for h in range(600)])
    control = ask(rig, _sel(table, 7, ts=c.tso()))["rows"]
    assert ask(rig, _sel(table, 7, ts=c.tso()))["rows"] == control
    st0 = _fp(rig).stats()
    failpoint.cfg("copr::fastpath", "return(corrupt)")
    try:
        assert ask(rig, _sel(table, 7, ts=c.tso()))["rows"] == control
    finally:
        failpoint.remove("copr::fastpath")
    st1 = _fp(rig).stats()
    # the faulted request took the full decode, which learned the class
    # again beside the flipped template: that one can only miss
    assert st1["hit"] == st0["hit"]
    assert st1["reasons"]["bypass:failpoint_corrupt"] - \
        st0["reasons"].get("bypass:failpoint_corrupt", 0) == 1
    assert st1["learned"] - st0["learned"] == 1
    flipped, fresh = [e for e in _fp(rig)._entries
                      if e.base_key[2] == table.table_id]
    assert flipped.template.segments[1:] == fresh.template.segments[1:]
    assert flipped.template.segments[0] != fresh.template.segments[0]
    assert ask(rig, _sel(table, 8, ts=c.tso()))["rows"] == \
        [r for r in control if r[-1] > 8]
    st2 = _fp(rig).stats()
    assert st2["hit"] - st1["hit"] == 1 and st2["miss"] == st1["miss"]
    assert (flipped.hits, fresh.hits) == (1, 1)
    # the newer class is tried first: one probe
    assert st2["find"]["probes"] - st1["find"]["probes"] == 1


def test_a_config_gen_bump_drops_and_the_class_relearns(rig):
    c = rig["client"]
    table = int_table(2, table_id=9803)
    _load(rig, table, [(h, {"c0": h % 3, "c1": h % 40})
                       for h in range(600)])
    ask(rig, _sel(table, 7, ts=c.tso()))
    ask(rig, _sel(table, 8, ts=c.tso()))
    st0 = _fp(rig).stats()
    _fp(rig).bump_config_gen()
    rows = ask(rig, _sel(table, 9, ts=c.tso()))["rows"]
    st1 = _fp(rig).stats()
    assert len(rows) == sum(1 for h in range(600) if h % 40 > 9)
    assert st1["hit"] == st0["hit"]
    assert st1["reasons"].get("invalidate:config", 0) - \
        st0["reasons"].get("invalidate:config", 0) >= 1
    assert st1["learned"] - st0["learned"] == 1
    ask(rig, _sel(table, 10, ts=c.tso()))
    assert _fp(rig).stats()["hit"] - st1["hit"] == 1


def test_a_warm_served_window_walks_no_key(rig):
    """Through the whole stack (handler, read pool, coalescer,
    dispatcher, runner): rotating constants over one class, every
    request a hit found in one probe, no key walked."""
    c = rig["client"]
    table = int_table(2, table_id=9804)
    _load(rig, table, [(h, {"c0": h % 3, "c1": h % 40})
                       for h in range(600)])
    for thr in (1, 2, 3):
        ask(rig, _sel(table, thr, ts=c.tso()))
    st0 = _fp(rig).stats()
    for thr in range(4, 24):
        rows = ask(rig, _sel(table, thr, ts=c.tso()))["rows"]
        assert len(rows) == sum(1 for h in range(600) if h % 40 > thr)
    st1 = _fp(rig).stats()
    assert st1["hit"] - st0["hit"] == 20
    assert st1["find"]["finds"] - st0["find"]["finds"] == 20
    assert st1["find"]["probes"] - st0["find"]["probes"] == 20
    assert st1["keys"]["carried"] - st0["keys"]["carried"] == 20
    assert st1["keys"]["walked"] == st0["keys"]["walked"]
