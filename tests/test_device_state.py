"""Device-state integrity: HBM budget/eviction, lifecycle teardown,
background scrub + quarantine, and the device-fault chaos schedule.

Covers the device-state supervisor (tikv_tpu/device/supervisor.py):

- the feed arena's explicit ownership — per-anchor byte accounting,
  budget eviction (frequency+recency, pinned lines exempt), and
  ``drop_feed`` returning accounting to baseline with NO ``gc.collect``
  in the loop (the old WeakKeyDictionary relied on GC timing);
- lifecycle-driven teardown — split/epoch change, leader loss and peer
  destroy invalidate columnar cache lines and device feeds eagerly;
- scrub: ``device::feed_corrupt`` bit-flips a resident plane, the
  scrubber detects the digest divergence, quarantines the line, the
  next request degrades to host, the one after rebuilds (re-admission);
- a seeded chaos schedule mixing write churn, splits, leader transfers
  and ``device::*`` faults on a live single-node server, asserting
  delta-vs-rebuild parity and read correctness throughout with zero
  wrong results.

JAX_PLATFORMS=cpu: the device runner runs its XLA paths on the CPU
backend; digests, the arena, and quarantine behave identically.
"""

import json
import random
import urllib.request

import numpy as np
import pytest

from tikv_tpu.chaos import (
    DEVICE_FAULT_KINDS,
    Nemesis,
    check_hbm_within_budget,
    check_no_stale_epoch,
    check_scrub_clean,
    generate_schedule,
)
from tikv_tpu.datatype import Column, EvalType, FieldType
from tikv_tpu.device import DeviceRunner
from tikv_tpu.device.supervisor import (
    DeviceStateSupervisor,
    FeedArena,
    host_plane_digest,
)
from tikv_tpu.executors.columnar import ColumnarTable
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import Table, TableColumn
from tikv_tpu.utils import failpoint


@pytest.fixture(autouse=True)
def _teardown():
    yield
    failpoint.teardown()


def _snap(table_id: int, n: int = 4096, seed: int = 0):
    rng = np.random.default_rng(seed)
    table = Table(table_id, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("v", 2, FieldType.long()),
    ))
    vals = rng.integers(0, 1 << 20, n).astype(np.int64)
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"v": Column(EvalType.INT, vals, np.ones(n, bool))})
    sel = DagSelect.from_table(table)
    dag = sel.sum(sel.col("v")).build()
    return snap, dag, int(vals.sum())


def _runner(**kw):
    return DeviceRunner(chunk_rows=1 << 12, **kw)


# ------------------------------------------------------ digest formula


def test_host_digest_detects_any_single_position_change():
    arr = np.arange(1000, dtype=np.int64)
    base = host_plane_digest(arr, 1000)
    for pos in (0, 1, 500, 999):
        for bit in (0, 31, 63):
            bad = arr.copy()
            bad[pos] = np.int64(np.uint64(bad[pos]) ^ np.uint64(1 << bit))
            assert host_plane_digest(bad, 1000) != base, (pos, bit)
    # changes past the live prefix are invisible (padding)
    tail = arr.copy()
    tail[999] ^= 1
    assert host_plane_digest(tail, 999) == host_plane_digest(arr, 999)


def test_host_and_device_digests_agree():
    runner = _runner()
    for dtype, data in (
            (np.int64, np.arange(-50, 4046, dtype=np.int64)),
            (np.int32, np.arange(-50, 4046, dtype=np.int32)),
            (np.float64, np.linspace(-1.0, 1.0, 4096)),
            (np.bool_, (np.arange(4096) % 3 == 0)),
    ):
        arr = np.ascontiguousarray(data.astype(dtype))
        n = 4000
        import jax.numpy as jnp
        dev = jnp.asarray(arr)
        got = int(np.asarray(runner._feeds.device_digest(dev, n)))
        assert got == host_plane_digest(arr, n), dtype


# ------------------------------------------- arena accounting / budget


def test_drop_feed_returns_accounting_to_baseline_without_gc():
    runner = _runner()
    snap, dag, want = _snap(8100)
    assert runner.hbm_stats()["resident_bytes"] == 0
    assert int(runner.handle_request(dag, snap).rows()[0][0]) == want
    st = runner.hbm_stats()
    assert st["resident_bytes"] > 0 and st["resident_lines"] == 1
    # explicit ownership: teardown is drop_feed, not gc.collect timing
    freed = runner.drop_feed(snap)
    assert freed == st["resident_bytes"]
    st2 = runner.hbm_stats()
    assert st2["resident_bytes"] == 0 and st2["resident_lines"] == 0
    # the evicted feed transparently rebuilds on next access
    assert int(runner.handle_request(dag, snap).rows()[0][0]) == want
    assert runner.hbm_stats()["resident_bytes"] == freed


def test_budget_eviction_lfu_and_transparent_rebuild():
    runner = _runner()
    fixtures = [_snap(8200 + i, seed=i) for i in range(3)]
    snap0, dag0, want0 = fixtures[0]
    assert int(runner.handle_request(dag0, snap0).rows()[0][0]) == want0
    per_feed = runner.hbm_stats()["resident_bytes"]
    assert per_feed > 0
    # budget fits exactly two feeds
    runner.set_hbm_budget(per_feed * 2)
    for snap, dag, want in fixtures[1:]:
        assert int(runner.handle_request(dag, snap).rows()[0][0]) == want
        check_hbm_within_budget(runner)
    st = runner.hbm_stats()
    assert st["evictions"] >= 1
    assert st["resident_bytes"] <= per_feed * 2
    # the evicted line (the coldest) serves again via a fresh upload
    from tikv_tpu.utils import tracker
    for snap, dag, want in fixtures:
        assert int(runner.handle_request(dag, snap).rows()[0][0]) == want
        check_hbm_within_budget(runner)


def test_pinned_inflight_deferred_dispatch_is_never_evicted():
    runner = _runner()
    snap0, dag0, want0 = _snap(8300, seed=3)
    snap1, dag1, want1 = _snap(8301, seed=4)
    deferred = runner.handle_request(dag0, snap0, deferred=True)
    from tikv_tpu.device.runner import DeferredResult
    assert isinstance(deferred, DeferredResult)
    st = runner.hbm_stats()
    assert st["pinned_lines"] == 1
    per_feed = st["resident_bytes"]
    # a budget with room for ONE feed: admitting snap1's feed would
    # normally evict snap0's — but it is pinned by the in-flight fetch,
    # so snap1's feed is the one that cannot be retained
    runner.set_hbm_budget(per_feed)
    assert int(runner.handle_request(dag1, snap1).rows()[0][0]) == want1
    st = runner.hbm_stats()
    assert st["pinned_lines"] == 1
    assert st["rejections"] >= 1          # snap1 served uncached
    assert runner._arena.bucket(snap0, create=False) is not None
    # resolving the deferred fetch unpins; the line becomes evictable
    assert int(deferred.result().rows()[0][0]) == want0
    assert runner.hbm_stats()["pinned_lines"] == 0
    assert int(runner.handle_request(dag1, snap1).rows()[0][0]) == want1
    # ... and is evicted: its planes go, its host memo stays (PR 53)
    kept = runner._arena.bucket(snap0, create=False)
    assert kept and not any("flat" in v for v in kept.values())
    assert runner.hbm_stats()["resident_lines"] == 1


@pytest.mark.parametrize("how", ["evict", "drop"])
def test_the_budget_keeps_a_lines_host_memo_and_a_drop_does_not(
        how, monkeypatch):
    """What the HBM budget takes from a line (here a squeeze under
    ``device::hbm_oom``) is its device state: the next read of the line
    pays ``feed_upload`` (``after_eviction``) and derives nothing on the
    host, nor hashes the host planes again (their scrub digests stay in
    the memo beside them).  ``drop_feed`` (lifecycle, quarantine) takes
    the memo too, as it always did: the next read derives and hashes
    again.  Both count where /health shows them."""
    from tikv_tpu.device import supervisor
    from tikv_tpu.utils import tracker
    runner = _runner()
    assert runner.scrub_digests
    hashed = []
    digest = supervisor.host_plane_digest
    monkeypatch.setattr(supervisor, "host_plane_digest",
                        lambda arr, n: hashed.append(n) or digest(arr, n))
    snap, dag, want = _snap(8450, seed=6)
    snap1, dag1, want1 = _snap(8451, seed=7)

    def serve(snap, dag):
        tr, tok = tracker.install(sampled=True)
        try:
            rows = runner.handle_request(dag, snap).rows()
        finally:
            tracker.uninstall(tok)
        return int(rows[0][0]), {s.name: s.attrs for s in tr.spans}

    got, spans = serve(snap, dag)
    assert got == want and "host_derive" in spans
    assert spans["feed_upload"] == {"bytes": 16384, "planes": 1,
                                    "after_eviction": False}
    per_feed = runner.hbm_stats()["resident_bytes"]
    assert per_feed == 16384 and len(hashed) == 1
    cold, = (v["digests"] for v in
             runner._arena.bucket(snap, create=False).values()
             if "flat" in v)
    if how == "evict":
        # room for one feed: admitting the second line's evicts the first
        failpoint.cfg("device::hbm_oom", f"return({per_feed})")
        got, spans = serve(snap1, dag1)
        failpoint.remove("device::hbm_oom")
        assert got == want1
        assert spans["arena_evict"] == {"victims": 1, "bytes": per_feed}
        st = runner.hbm_stats()
        assert (st["evictions"], st["evicted_bytes"], st["memos_kept"]) \
            == (1, per_feed, 1)
        assert (st["resident_bytes"], st["resident_lines"]) == (per_feed, 1)
        bucket = runner._arena.bucket(snap, create=False)
        memo, = bucket.values()
        assert "dtypes" in memo and "prepared" not in memo
        assert memo["host_digests"][0] is memo["host_cols"]
        hashed.clear()      # (the other line's upload hashed its own)
    else:
        assert runner.drop_feed(snap) == per_feed
        assert runner._arena.bucket(snap, create=False) is None
        assert runner.hbm_stats()["memos_kept"] == 0
    got, spans = serve(snap, dag)
    assert got == want
    assert ("host_derive" in spans) == (how == "drop")
    assert spans["feed_upload"]["after_eviction"] == (how == "evict")
    # the same truth, the same digests; hashed again after a drop alone
    assert len(hashed) == (0 if how == "evict" else 2)
    again, = (v["digests"] for v in
              runner._arena.bucket(snap, create=False).values()
              if "flat" in v)
    assert again == cold
    feed = runner.mesh_stats()["feed"]
    assert feed["gets"]["upload"] == sum(feed["gets"].values()) == \
        feed["uploads"]["cold"] + feed["uploads"]["evicted"]
    assert feed["uploads"]["evicted"] == (how == "evict")
    assert feed["uploads"]["bytes"] == per_feed * feed["gets"]["upload"]
    # a warm read is a hit, and the memo it reads is the kept one
    got, spans = serve(snap, dag)
    assert got == want and "feed_upload" not in spans
    assert runner.mesh_stats()["feed"]["gets"]["hit"] == 1
    check_hbm_within_budget(runner)


@pytest.mark.parametrize("shards", [1, 8])
def test_an_upload_puts_the_padded_buffer_kept_with_the_host_plane(
        shards, monkeypatch):
    """A plane that is cast on its way into the memo is cast INTO a
    zeroed buffer of the feed's padded length, the memo's plane its head
    (``HostPlanes.stream`` / ``padded``): the cold upload and the one
    that brings back a feed the budget took put THAT buffer, and make no
    padded copy.  Planes that replace the memo's are never taken for
    them (identity), and pad a copy as before."""
    import jax
    from tikv_tpu.device import feed as feed_mod
    from tikv_tpu.parallel import make_mesh
    runner = _runner(mesh=make_mesh(jax.devices()[:shards]))
    assert runner._single == (shards == 1)
    n, n_pad = 4000, 4096
    snap, dag, want = _snap(8460, n=n, seed=8)
    snap1, dag1, want1 = _snap(8461, n=n, seed=9)
    put = []

    class Puts:
        """``jnp`` / ``jax`` as feed.py sees them, the host arrays it
        puts noted (the tests' mesh has eight shards: ``device_put``)."""

        def __init__(self, mod):
            self._mod = mod

        def __getattr__(self, name):
            return getattr(self._mod, name)

        def asarray(self, a, *args, **kw):
            put.append(a)
            return self._mod.asarray(a, *args, **kw)

        def device_put(self, a, *args, **kw):
            put.append(a)
            return self._mod.device_put(a, *args, **kw)

    monkeypatch.setattr(feed_mod, "jnp", Puts(feed_mod.jnp))
    monkeypatch.setattr(feed_mod, "jax", Puts(feed_mod.jax))
    assert int(runner.handle_request(dag, snap).rows()[0][0]) == want
    memo, = (v for v in runner._arena.bucket(snap, create=False).values()
             if "flat" not in v)
    (plane, _ok), = memo["host_cols"]
    pad, = memo["host_pads"]
    assert pad.shape == (n_pad,) and pad.dtype == plane.dtype == np.int32
    assert plane.base is pad and plane.shape == (n,)
    assert not pad[n:].any()
    assert [a is pad for a in put] == [True]         # the cold upload too
    per_feed = runner.hbm_stats()["resident_bytes"]
    assert per_feed == n_pad * 4
    failpoint.cfg("device::hbm_oom", f"return({per_feed})")
    assert int(runner.handle_request(dag1, snap1).rows()[0][0]) == want1
    failpoint.remove("device::hbm_oom")
    assert runner.hbm_stats()["evictions"] == 1
    del put[:]
    assert int(runner.handle_request(dag, snap).rows()[0][0]) == want
    assert [a is pad for a in put] == [True]
    assert runner.mesh_stats()["feed"]["uploads"]["evicted"] == 1
    # planes of another make (a cut's, a request's own) are not the
    # buffer's head: no buffer is taken for theirs
    planes = feed_mod.HostPlanes.__new__(feed_mod.HostPlanes)
    planes.memo, planes.meta = {}, memo
    assert planes.padded(0, plane, n_pad) is pad
    assert planes.padded(0, plane.copy(), n_pad) is None
    assert planes.padded(0, plane, 2 * n_pad) is None
    check_hbm_within_budget(runner)


def test_hbm_oom_failpoint_squeezes_budget():
    runner = _runner()        # unlimited budget
    snap, dag, want = _snap(8400, seed=5)
    failpoint.cfg("device::hbm_oom", "return(0)")
    # squeeze to zero: nothing may be retained, the request still serves
    assert int(runner.handle_request(dag, snap).rows()[0][0]) == want
    st = runner.hbm_stats()
    assert st["resident_bytes"] == 0
    assert st["rejections"] >= 1
    failpoint.remove("device::hbm_oom")
    # healed: the next request admits normally
    assert int(runner.handle_request(dag, snap).rows()[0][0]) == want
    assert runner.hbm_stats()["resident_bytes"] > 0


def test_arena_weakref_backstop_only_for_untracked_anchors():
    arena = FeedArena()
    class Anchor:       # noqa: E301
        pass
    a = Anchor()
    bucket = arena.bucket(a)
    bucket["x"] = {"flat": (np.zeros(8, np.int64),)}
    arena.admit(a)
    assert arena.resident_bytes() == 64
    del a               # backstop: entry dies with the anchor
    assert arena.resident_lines() == 0


def test_pin_many_and_peek_beside_drops_and_admits_balance_every_pin():
    """A hold's lanes are pinned together (``pin_many``) and their
    buckets looked up without the mutex (``peek``) while other threads
    drop, re-admit and unpin the same lines: a token of a dropped entry
    is a no-op, a bucket handed back is the entry's own, and once every
    token is back nothing stays pinned."""
    import sys
    import threading
    import time
    arena = FeedArena()

    class Anchor:
        pass

    anchors = [Anchor() for _ in range(6)]

    def admit(a):
        arena.bucket(a)["x"] = {"flat": (np.zeros(8, np.int64),)}
        arena.admit(a)

    for a in anchors:
        admit(a)
    stop = threading.Event()
    errors = []
    holds = [0]

    def churn(i):
        try:
            while not stop.is_set():
                a = anchors[i % len(anchors)]
                arena.drop(a)
                admit(a)
                i += 1
        except Exception as e:          # noqa: BLE001 — surfaced below
            errors.append(e)

    def stage():
        try:
            while not stop.is_set():
                for a in anchors:
                    b = arena.peek(a)
                    assert b is None or isinstance(b, dict)
                got = arena.pin_many(anchors)
                assert len(got) == len(anchors)
                for a, (bucket, token) in zip(anchors, got):
                    assert (bucket is None) == (token is None)
                    if token is not None:
                        assert token[0] == id(a)
                for _bucket, token in got:
                    arena.unpin(token)
                holds[0] += 1
        except Exception as e:          # noqa: BLE001
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=churn, args=(i,)) for i in range(3)] \
        + [threading.Thread(target=stage) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        time.sleep(1.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=20)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert holds[0] > 0
    assert arena.pinned_bytes() == 0
    assert all(e.pins == 0 for e in arena._entries.values())


# ----------------------------------------- scrub → quarantine → rebuild


def test_scrub_detects_corruption_quarantines_then_rebuilds():
    """The fast tier-1 leg of the acceptance criterion: an injected
    device::feed_corrupt is detected by the scrubber and quarantined
    with zero wrong query results returned."""
    from tikv_tpu.utils.metrics import DEVICE_SCRUB_COUNTER
    runner = _runner()
    sup = DeviceStateSupervisor(runner=runner)
    snap, dag, want = _snap(8500, seed=6)
    assert int(runner.handle_request(dag, snap).rows()[0][0]) == want
    clean = sup.scrub()
    assert clean["lines"] == 1 and clean["divergences"] == 0

    failpoint.cfg("device::feed_corrupt", "1*return")
    out = sup.scrub()
    assert out["divergences"] == 1
    assert runner.hbm_stats()["quarantined"] == 1
    assert runner.hbm_stats()["resident_bytes"] == 0    # feeds dropped

    # quarantined: the next request serves from the HOST pipeline —
    # the corrupted plane can never reach an answer
    res = runner.handle_request(dag, snap)
    assert int(res.rows()[0][0]) == want
    assert runner.hbm_stats()["quarantined"] == 0

    # re-admission: a fresh feed uploads from host truth and scrubs
    # clean again
    assert int(runner.handle_request(dag, snap).rows()[0][0]) == want
    assert runner.hbm_stats()["resident_bytes"] > 0
    check_scrub_clean(sup)
    st = sup.stats()
    assert st["quarantines"] == 1 and st["scrub_divergences"] == 1


def test_d2h_corrupt_degrades_to_host():
    """Detected transfer corruption = a failed fetch: the request
    degrades to the host pipeline instead of answering with bad bytes."""
    runner = _runner()
    snap, dag, want = _snap(8600, seed=7)
    failpoint.cfg("device::d2h_corrupt", "return")
    res = runner.handle_request(dag, snap)
    assert int(res.rows()[0][0]) == want
    assert failpoint.hits("device::d2h_corrupt") >= 1


def test_corruption_before_patch_survives_patch_and_is_caught():
    """The patch-time digest update is INCREMENTAL (R' = R - H_span(old)
    + H_span(new)): a bit flip that landed before the patch must not be
    laundered into the recorded digest by the refresh — the next scrub
    still quarantines the line."""
    pytest.importorskip("grpc")
    rig = _make_server_rig(threshold=64)
    try:
        c, node, device, sup = (rig["client"], rig["node"],
                                rig["device"], rig["sup"])
        from tikv_tpu.testing.fixture import encode_table_row, int_table
        table = int_table(2, table_id=9502)
        model = {h: (h % 5, h * 3) for h in range(300)}
        c.txn_write([("put",) + encode_table_row(
            table, h, {"c0": h % 5, "c1": h * 3}) for h in range(300)])
        dag = _agg_dag(table, c)
        cold = c.coprocessor(dag())
        if cold["backend"] != "device":
            pytest.skip("device backend unavailable")
        assert sorted(cold["rows"]) == _expect(model)
        # corrupt a resident plane directly (a real HBM fault, not the
        # scrubber's self-injection)
        feed = next(v for _a, b in device.arena_items()
                    for v in b.values()
                    if isinstance(v, dict) and "flat" in v)
        device._feeds.corrupt_resident_plane(feed)
        # a write now patches the feed in place, refreshing digests
        model[300] = (1, 7)
        c.txn_write([("put",) + encode_table_row(
            table, 300, {"c0": 1, "c1": 7})])
        r = c.coprocessor(dag())
        if r["time_detail"]["labels"].get("device_feed") == "patch":
            # the corruption predates the patch and sits outside the
            # patched span: the refreshed digest must still disagree
            out = sup.scrub()
            assert out["divergences"] == 1, \
                "patch-time digest refresh laundered the corruption"
            # quarantine → host → rebuild: exact again
            assert sorted(c.coprocessor(dag())["rows"]) == \
                _expect(model)
            assert sorted(c.coprocessor(dag())["rows"]) == \
                _expect(model)
            check_scrub_clean(sup)
        else:
            # the write forced a re-upload from host truth — the
            # corruption is gone by construction; scrub reads clean
            check_scrub_clean(sup)
    finally:
        rig["close"]()


@pytest.mark.parametrize("corrupted", [True, False],
                         ids=["corrupted", "clean"])
def test_corruption_before_compaction_survives_the_chain(corrupted):
    """A tombstone's rebuild compacts the resident planes and CHAINS
    their digests (R' = R - H(old rows from the first dead one on) + H(new
    rows from there on): device/feed.py ``_compact_program``), never
    re-hashes them: a bit that flipped in a plane before the compaction,
    with rows deleted before AND after it, moves up with its row, what
    the device holds less what is recorded is unchanged by the step, and
    the next scrub fails the feed; without the fault it passes."""
    from test_feed_compact import DeletedLine
    from test_feed_patch_buckets import (
        assert_feed_is_the_cold_build, one_device, serve,
    )
    runner = one_device()
    sup = DeviceStateSupervisor(runner=runner)
    line = DeletedLine(2000)
    how, feed = serve(runner, line.snapshot())
    assert how == "upload"

    def off_by() -> int:
        """The first plane's device digest less the recorded one."""
        return (int(np.asarray(runner._feeds.device_digest(
            feed["flat"][0], feed["n_live"]))) -
            int(np.asarray(feed["digests"][0]))) % (1 << 64)

    at = 1000
    if corrupted:
        # an HBM fault in the first value plane (int64 handles)
        arr = feed["flat"][0]
        feed["flat"] = (arr.at[at].set(arr[at] ^ 1),) + feed["flat"][1:]
    before = off_by()
    assert (before != 0) == corrupted
    line.delete([0, 1, 2, 400])
    line.delete([at - 4 + 10, 1990])    # (row ``at`` is now at - 4)
    how, compacted = serve(runner, line.snapshot())
    assert how == "compact" and compacted is feed
    assert off_by() == before, \
        "the compaction laundered the corruption into the digest"
    if corrupted:
        # the planes are the host's but for the flipped bit, moved up
        got = np.asarray(feed["flat"][0])[:len(line.handles)]
        assert list(np.flatnonzero(got != line.handles)) == [at - 4]
    else:
        assert_feed_is_the_cold_build(line, feed)
    out = sup.scrub()
    assert out["lines"] == 1 and out["divergences"] == int(corrupted)
    assert runner.hbm_stats()["quarantined"] == int(corrupted)
    if corrupted:
        # quarantine (the feed dropped, the host serves one read) →
        # rebuilt from host truth: clean again
        assert runner.hbm_stats()["resident_bytes"] == 0
        from test_feed_patch_buckets import dag
        want = sorted(runner.handle_request(dag(), line.view()).rows())
        assert sorted(runner.handle_request(
            dag(), line.snapshot()).rows()) == want
        how, rebuilt = serve(runner, line.snapshot())
        assert how == "upload" and rebuilt is not feed
        assert_feed_is_the_cold_build(line, rebuilt)
    check_scrub_clean(sup)


def test_patch_refreshes_digests_and_scrub_stays_clean():
    """Delta-patched feeds keep their recorded digests in sync: after
    an in-place span patch the scrubber must still read clean (a stale
    digest would quarantine a healthy line)."""
    pytest.importorskip("grpc")
    _srv_rig = _make_server_rig()
    try:
        c, node, device, sup = (_srv_rig["client"], _srv_rig["node"],
                                _srv_rig["device"], _srv_rig["sup"])
        from tikv_tpu.testing.fixture import encode_table_row, int_table
        table = int_table(2, table_id=9500)
        muts = [("put",) + encode_table_row(
            table, h, {"c0": h % 5, "c1": h * 3}) for h in range(300)]
        c.txn_write(muts)
        dag = _agg_dag(table, c)
        cold = c.coprocessor(dag())
        if cold["backend"] != "device":
            pytest.skip("device backend unavailable")
        # a point write → delta patch on the resident feed
        c.txn_write([("put",) + encode_table_row(
            table, 300, {"c0": 1, "c1": 7})])
        resp = c.coprocessor(dag())
        assert resp["time_detail"]["labels"].get("device_feed") in \
            ("patch", "rebuild")
        check_scrub_clean(sup)
    finally:
        _srv_rig["close"]()


# --------------------------------------------- lifecycle (live server)


def _make_server_rig(budget_mb: int = 0, threshold: int = 128):
    import grpc       # noqa: F401 — skip via importorskip at call site
    from tikv_tpu.raftstore.metapb import Store
    from tikv_tpu.server import (
        Node, PdServer, RemotePdClient, TikvServer, TxnClient,
    )
    device = DeviceRunner(chunk_rows=1 << 12)
    pd_server = PdServer("127.0.0.1:0")
    pd_server.start()
    pd_addr = f"127.0.0.1:{pd_server.port}"
    node = Node("127.0.0.1:0", RemotePdClient(pd_addr),
                device_runner=device, device_row_threshold=threshold)
    if budget_mb:
        device.set_hbm_budget(budget_mb << 20)
    srv = TikvServer(node)
    node.addr = f"127.0.0.1:{srv.port}"
    node.pd.put_store(Store(node.store_id, node.addr))
    srv.start()
    client = TxnClient(pd_addr)

    def close():
        srv.stop()
        pd_server.stop()

    return {"srv": srv, "node": node, "client": client, "device": device,
            "sup": node.device_supervisor, "pd": pd_server,
            "close": close}


def _agg_dag(table, c, lo=None, hi=None):
    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.executors.ranges import KeyRange

    def build():
        sel = DagSelect.from_table(table, ["id", "c0", "c1"])
        if lo is not None:
            sel._ranges = [KeyRange(
                table_record_key(table.table_id, lo),
                table_record_key(table.table_id, hi))]
        return sel.aggregate(
            [sel.col("c0")],
            [("count_star", None), ("sum", sel.col("c1"))],
        ).build(start_ts=c.tso())

    return build


def _split_at(node, tid, handle, timeout_s=5.0):
    """Split the region containing ``handle`` at it, retrying while the
    owning (possibly freshly-created) peer finishes its election."""
    import time as _time

    from tikv_tpu.codec.keys import table_record_key
    from tikv_tpu.raftstore.metapb import NotLeaderError
    deadline = _time.monotonic() + timeout_s
    while True:
        try:
            return node.split_region(0, table_record_key(tid, handle))
        except NotLeaderError:
            if _time.monotonic() > deadline:
                raise
            _time.sleep(0.02)


def _expect(rows_by_handle, lo=None, hi=None):
    out = {}
    for h, (c0, c1) in rows_by_handle.items():
        if lo is not None and not (lo <= h < hi):
            continue
        cnt, sm = out.get(c0, (0, 0))
        out[c0] = (cnt + 1, sm + c1)
    return sorted([cnt, sm, g] for g, (cnt, sm) in out.items())


def test_lifecycle_teardown_split_and_role_change():
    """Split (epoch change) eagerly invalidates the region's columnar
    lines AND device feeds; leader loss DEMOTES the line to a replica
    feed (kept resident + delta-patched for stale serving) and leader
    gain promotes it warm — and the accounting shows all of it on
    /health and /metrics."""
    pytest.importorskip("grpc")
    rig = _make_server_rig()
    try:
        c, node, device = rig["client"], rig["node"], rig["device"]
        from tikv_tpu.codec.keys import table_record_key
        from tikv_tpu.testing.fixture import encode_table_row, int_table
        table = int_table(2, table_id=9600)
        model = {}
        muts = []
        for h in range(400):
            model[h] = (h % 7, h)
            muts.append(("put",) + encode_table_row(
                table, h, {"c0": h % 7, "c1": h}))
        c.txn_write(muts)
        warm = c.coprocessor(_agg_dag(table, c)())
        assert sorted(warm["rows"]) == _expect(model)
        assert node.copr_cache.stats()["resident_lines"] == 1
        resident0 = device.hbm_stats()["resident_bytes"]
        if resident0:
            # the lineage's digest journal mirrors the resident feed's
            # build-time digests (the host-visible audit record)
            ln = node.copr_cache.stats()["lines"][0]
            assert ln["digest_feeds"] >= 1

        # SPLIT: the epoch bumps.  With the elastic lifecycle a
        # load-split SLICES the parent line into two child lines at
        # the children's epochs (no teardown); only a split that fell
        # back to re-mint drops everything.  Either way nothing at a
        # stale EPOCH may survive the event, aged out or otherwise.
        node.split_region(1, table_record_key(table.table_id, 200))
        st = node.copr_cache.stats()
        if st.get("splits", 0):
            assert st["resident_lines"] == 2, \
                "split sliced but the child lines are missing"
        else:
            assert st["resident_lines"] == 0, \
                "stale-epoch line survived the split"
            if resident0:
                assert device.hbm_stats()["resident_bytes"] == 0, \
                    "stale-epoch device feed survived the split"
        check_no_stale_epoch(node)

        # both halves serve exactly on access (warm from the sliced
        # children, or rebuilt after a fallback)
        left = c.coprocessor(_agg_dag(table, c, 0, 200)())
        right = c.coprocessor(_agg_dag(table, c, 200, 400)())
        assert sorted(left["rows"]) == _expect(model, 0, 200)
        assert sorted(right["rows"]) == _expect(model, 200, 400)
        check_no_stale_epoch(node)

        # LEADER LOSS on one region: with replicated device serving
        # the line is NOT torn down — it demotes to a replica feed
        # (kept resident, still delta-patched, serving stale reads),
        # and a later leader gain promotes it back WARM (scrub-digest
        # re-verify, no columnar_build)
        lines = node.copr_cache.stats()["resident_lines"]
        assert lines >= 1
        rid = node.copr_cache.stats()["lines"][0]["region"]
        sup = node.device_supervisor
        demo0, promo0 = sup.demotions, sup.promotions
        node.raft_store.coprocessor_host.notify_role_change(rid, False)
        assert node.copr_cache.stats()["resident_lines"] == lines, \
            "demotion must keep the line resident as a replica feed"
        assert sup.demotions == demo0 + 1
        node.raft_store.coprocessor_host.notify_role_change(rid, True)
        assert sup.promotions == promo0 + 1
        assert sup.promotion_rebuilds == 0
        assert node.copr_cache.stats()["resident_lines"] == lines, \
            "warm promotion must not invalidate the line"
        # the split's stale-epoch teardown above is the lifecycle
        # invalidation the rollup accounts
        assert node.device_supervisor.stats()[
            "lifecycle_invalidations"] >= 1

        # observability: gauges ride /metrics, the rollup rides /health
        from tikv_tpu.server.status_server import StatusServer
        ss = StatusServer("127.0.0.1:0", node=node,
                          config_controller=node.config_controller)
        ss.start()
        try:
            base = f"http://127.0.0.1:{ss.port}"
            metrics = urllib.request.urlopen(
                f"{base}/metrics").read().decode()
            assert "tikv_coprocessor_region_cache_resident_lines" in \
                metrics
            assert "tikv_device_hbm_resident_bytes" in metrics
            assert "tikv_device_feed_evictions_total" in metrics
            body = json.load(urllib.request.urlopen(f"{base}/health"))
            ds = body["device_state"]
            assert ds["lifecycle_invalidations"] >= 1
            assert "hbm" in ds and "resident_bytes" in ds["hbm"]
        finally:
            ss.stop()
    finally:
        rig["close"]()


# -------------------------------------------------- the chaos schedule


@pytest.mark.slow
def test_device_fault_chaos_schedule():
    """Acceptance: an HBM budget sized to ~4 of 16 regions under a
    churning write mix with splits, leader transfers and device::*
    faults — resident HBM stays ≤ budget, evicted regions rebuild
    transparently, injected corruption is quarantined, and ZERO wrong
    results are returned (delta-vs-rebuild parity at the end)."""
    pytest.importorskip("grpc")
    rig = _make_server_rig(threshold=64)
    try:
        c, node, device, sup = (rig["client"], rig["node"],
                                rig["device"], rig["sup"])
        from tikv_tpu.codec.keys import table_record_key
        from tikv_tpu.testing.fixture import encode_table_row, int_table
        table = int_table(2, table_id=9700)
        tid = table.table_id
        rows_per = 96
        n_regions = 16
        total = rows_per * n_regions
        model = {}
        muts = []
        for h in range(total):
            model[h] = (h % 5, h)
            muts.append(("put",) + encode_table_row(
                table, h, {"c0": h % 5, "c1": h}))
        c.txn_write(muts)
        # carve 16 regions on handle boundaries
        bounds = [0]
        for i in range(1, n_regions):
            _split_at(node, tid, i * rows_per)
            bounds.append(i * rows_per)
        bounds.append(total)

        rng = random.Random(616)
        next_h = total

        def query(i, expect_ok=True):
            lo, hi = bounds[i], bounds[i + 1]
            r = c.coprocessor(_agg_dag(table, c, lo, hi)())
            # ZERO wrong results: every acknowledged answer matches
            # the model, whatever fault is armed
            assert sorted(r["rows"]) == _expect(model, lo, hi), \
                f"wrong result for region slice [{lo},{hi})"
            return r

        # warm every region once, then size the budget to ~4 feeds
        for i in range(n_regions):
            query(i)
        resident = device.hbm_stats()["resident_bytes"]
        lines = max(1, device.hbm_stats()["resident_lines"])
        per_feed = max(1, resident // lines)
        device.set_hbm_budget(4 * per_feed + per_feed // 2)

        nem = Nemesis(None, seed=616)
        schedule = generate_schedule(616, 6, kinds=DEVICE_FAULT_KINDS)
        assert {f.kind for f in schedule} <= set(DEVICE_FAULT_KINDS)
        for step, fault in enumerate(schedule):
            nem.apply(fault)
            # write churn: updates + appends across random slices
            for _ in range(4):
                h = rng.randrange(total) if rng.random() < 0.7 \
                    else next_h
                if h == next_h:
                    next_h += 1
                    # appends land in the LAST slice
                    bounds[-1] = next_h
                row = (h % 5, rng.randrange(1 << 16))
                model[h] = row
                c.txn_write([("put",) + encode_table_row(
                    table, h, {"c0": row[0], "c1": row[1]})])
            # a scrub pass mid-fault: feed_corrupt trips HERE and must
            # quarantine before any query can read the bad plane
            sup.scrub()
            # queries across a skewed mix of regions
            for _ in range(6):
                query(rng.randrange(len(bounds) - 1))
            # leader transfer (the role-change event a real transfer
            # fires): teardown + rebuild must stay exact
            if step % 2 == 0:
                rid = rng.choice([ln["region"] for ln in
                                  node.copr_cache.stats()["lines"]]
                                 or [1])
                node.raft_store.coprocessor_host.notify_role_change(
                    rid, False)
            # one more split mid-churn (epoch change under fire)
            if step == 2:
                i = rng.randrange(len(bounds) - 1)
                lo, hi = bounds[i], bounds[i + 1]
                if hi - lo >= 2:
                    mid = (lo + hi) // 2
                    _split_at(node, tid, mid)
                    bounds.insert(i + 1, mid)
            check_hbm_within_budget(device)
            nem.heal()
            query(rng.randrange(len(bounds) - 1))
            check_hbm_within_budget(device)

        # healed + quiesced: no stale-epoch lines, budget held, scrub
        # clean, and the supervisor counted the quarantine(s)
        check_no_stale_epoch(node)
        check_hbm_within_budget(device)
        check_scrub_clean(sup)
        st = sup.stats()
        assert st["hbm"]["evictions"] + st["hbm"]["rejections"] >= 1, \
            "the budget never bit — schedule proved nothing"

        # delta-vs-rebuild parity: a delta-maintained answer equals a
        # from-scratch rebuild of the same slice
        i = rng.randrange(len(bounds) - 1)
        maintained = query(i)
        for ln in node.copr_cache.stats()["lines"]:
            node.copr_cache.invalidate_region(ln["region"])
        rebuilt = query(i)
        assert sorted(maintained["rows"]) == sorted(rebuilt["rows"])
    finally:
        rig["close"]()


def test_device_fault_chaos_schedule_fast():
    """Tier-1 twin of the full schedule: 4 regions, 2 steps — the same
    invariants (budget, zero wrong results, scrub clean) on a footprint
    small enough for the fast suite."""
    pytest.importorskip("grpc")
    rig = _make_server_rig(threshold=64)
    try:
        c, node, device, sup = (rig["client"], rig["node"],
                                rig["device"], rig["sup"])
        from tikv_tpu.codec.keys import table_record_key
        from tikv_tpu.testing.fixture import encode_table_row, int_table
        table = int_table(2, table_id=9701)
        tid = table.table_id
        rows_per, n_regions = 96, 4
        total = rows_per * n_regions
        model = {}
        muts = []
        for h in range(total):
            model[h] = (h % 5, h)
            muts.append(("put",) + encode_table_row(
                table, h, {"c0": h % 5, "c1": h}))
        c.txn_write(muts)
        bounds = [0]
        for i in range(1, n_regions):
            _split_at(node, tid, i * rows_per)
            bounds.append(i * rows_per)
        bounds.append(total)
        rng = random.Random(99)

        def query(i):
            lo, hi = bounds[i], bounds[i + 1]
            r = c.coprocessor(_agg_dag(table, c, lo, hi)())
            assert sorted(r["rows"]) == _expect(model, lo, hi)
            return r

        for i in range(n_regions):
            query(i)
        per_feed = max(1, device.hbm_stats()["resident_bytes"] //
                       max(1, device.hbm_stats()["resident_lines"]))
        device.set_hbm_budget(2 * per_feed + per_feed // 2)

        nem = Nemesis(None, seed=99)
        for fault in generate_schedule(99, 2, kinds=DEVICE_FAULT_KINDS):
            nem.apply(fault)
            for _ in range(2):
                h = rng.randrange(total)
                row = (h % 5, rng.randrange(1 << 16))
                model[h] = row
                c.txn_write([("put",) + encode_table_row(
                    table, h, {"c0": row[0], "c1": row[1]})])
            sup.scrub()
            for _ in range(3):
                query(rng.randrange(n_regions))
            check_hbm_within_budget(device)
            nem.heal()
        check_no_stale_epoch(node)
        check_hbm_within_budget(device)
        check_scrub_clean(sup)
    finally:
        rig["close"]()
