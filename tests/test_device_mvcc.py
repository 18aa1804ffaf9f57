"""Device-side MVCC version resolution (the cold-path kill).

Reference test model: the native-builder parity suite
(test_native_build.py) — the device build rung must agree with the
host ladder on every visibility case — plus the streaming cold
pipeline's coverage contract: a chunked ingest→parse→H2D stream must
produce BYTE-IDENTICAL feeds and digests to the one-shot
parse-at-build path, with zero new resolve compile classes.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

import tikv_tpu.copr.region_cache as rc
import tikv_tpu.native as nv
from tikv_tpu.codec.keys import data_key, table_record_key
from tikv_tpu.engine.memory import MemoryEngine
from tikv_tpu.engine.traits import CF_WRITE
from tikv_tpu.kv.engine import LocalEngine
from tikv_tpu.sst_importer import fast_mvcc_table_sst, read_sst_cf
from tikv_tpu.storage import Storage
from tikv_tpu.storage.txn import commands as cmds
from tikv_tpu.storage.txn.actions import Mutation
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import (
    Table,
    TableColumn,
    encode_table_row,
    int_table,
)
from tikv_tpu.datatype import FieldType
from tikv_tpu.utils import failpoint, tracker

pytestmark = pytest.mark.skipif(
    nv.mvcc_parse_planes is None, reason="native parse not compiled")


@pytest.fixture(scope="module")
def runner():
    import jax

    from tikv_tpu.device.runner import DeviceRunner
    from tikv_tpu.parallel import make_mesh

    # device-side MVCC resolution is single-device only (the sharded
    # mesh keeps the host upload pipeline) — pin to one device under
    # the CI's 8-device virtual mesh
    return DeviceRunner(mesh=make_mesh(jax.devices()[:1]))


@pytest.fixture(scope="module")
def resolver(runner):
    res = runner.mvcc_resolver()
    if res is None or not res.available():
        pytest.skip("device MVCC resolver unavailable")
    return res


def _commit(storage, ts, muts):
    storage.sched_txn_command(cmds.Prewrite(muts, muts[0].key, ts))
    storage.sched_txn_command(
        cmds.Commit([m.key for m in muts], ts, ts + 1))
    return ts + 10


def _infos(table, names):
    dag = DagSelect.from_table(table, names).build()
    return dag.executors[0].columns


def _assert_tables_equal(a, b, ctx=""):
    assert np.array_equal(a.handles, b.handles), ctx
    assert set(a.columns) == set(b.columns), ctx
    for cid, cb in b.columns.items():
        ca = a.columns[cid]
        assert np.array_equal(ca.validity, cb.validity), (ctx, cid)
        av, bv = ca.values[ca.validity], cb.values[cb.validity]
        assert len(av) == len(bv) and \
            all(x == y for x, y in zip(av, bv)), (ctx, cid)


def _parity(eng, table_id, infos, read_ts, resolver, ctx=""):
    """Device rung vs native vs interpreted on one snapshot: all three
    must agree on rows, safe_ts and blocking locks.  → the device
    build's (table, bundle)."""
    snap = eng.snapshot()
    tr, tok = tracker.install()
    try:
        tbl_d, safe_d, locks_d, bundle = rc.build_region_columnar_ex(
            snap, table_id, infos, read_ts, device_resolver=resolver)
    finally:
        labels = tr.time_detail().get("labels", {})
        tracker.uninstall(tok)
    assert labels.get("cold_build") == "device", (ctx, labels)
    assert bundle is not None, ctx
    tbl_n, safe_n, locks_n = rc.build_region_columnar(
        snap, table_id, infos, read_ts)
    saved = nv.mvcc_build_columnar
    nv.mvcc_build_columnar = None
    try:
        tbl_i, safe_i, locks_i = rc.build_region_columnar(
            snap, table_id, infos, read_ts)
    finally:
        nv.mvcc_build_columnar = saved
    assert safe_d == safe_n == safe_i, ctx
    assert [(k, l.start_ts) for k, l in locks_d] == \
        [(k, l.start_ts) for k, l in locks_n] == \
        [(k, l.start_ts) for k, l in locks_i], ctx
    _assert_tables_equal(tbl_d, tbl_n, ctx)
    _assert_tables_equal(tbl_d, tbl_i, ctx)
    return tbl_d, bundle


def _mint_feed(bundle, runner, infos, dtypes):
    n = bundle.n
    return bundle.mint(runner._feeds, list(infos), list(dtypes), n,
                       runner._feeds.pad_rows(n))


def _feed_vs_host(feed, tbl, infos, dtypes, n):
    """Minted device feed must equal the host-truth table plane for
    plane (``FeedStore.make_feed``'s layout contract)."""
    assert feed is not None
    assert feed["kinds"] == (None,) * len(infos)
    flat = feed["flat"]
    fi = 0
    for info, ds in zip(infos, dtypes):
        arr = np.asarray(flat[fi])[:n]
        if info.is_pk_handle:
            assert np.array_equal(arr, tbl.handles.astype(np.dtype(ds)))
            fi += 1
            continue
        col = tbl.columns[info.col_id]
        has_nulls = not bool(col.validity.all())
        if has_nulls:
            m = np.asarray(flat[fi + 1])[:n]
            assert np.array_equal(m, col.validity), info.col_id
            assert np.array_equal(
                arr[m], col.values[col.validity].astype(np.dtype(ds))), \
                info.col_id
            fi += 2
        else:
            assert np.array_equal(
                arr, col.values.astype(np.dtype(ds))), info.col_id
            fi += 1


# ------------------------------------------------------ randomized parity


def test_randomized_version_history_parity(runner, resolver):
    """Seeded random version histories: multiple versions per key
    straddling read_ts, deletes, rollbacks, NULLs, updates — the device
    resolve must match both host rungs at every sampled read_ts."""
    rng = np.random.default_rng(20260804)
    for rnd in range(10):
        eng = MemoryEngine()
        storage = Storage(LocalEngine(eng))
        tid = 7000 + rnd
        n_cols = int(rng.integers(2, 5))
        table = int_table(n_cols, table_id=tid)
        names = ["id"] + [f"c{i}" for i in range(n_cols)]
        ts = 10
        commit_tss = []
        live = {}
        for _gen in range(int(rng.integers(2, 5))):
            handles = rng.choice(200, size=int(rng.integers(20, 80)),
                                 replace=False)
            muts = []
            for h in sorted(int(x) for x in handles):
                if rng.random() < 0.15 and h in live:
                    muts.append(Mutation(
                        "delete", encode_table_row(table, h, {})[0],
                        None))
                    live.pop(h, None)
                else:
                    row = {f"c{i}": (None if rng.random() < 0.3
                                     else int(rng.integers(-50, 50)))
                           for i in range(n_cols)}
                    muts.append(Mutation(
                        "put", *encode_table_row(table, h, row)))
                    live[h] = row
            commit_tss.append(ts + 1)
            ts = _commit(storage, ts, muts)
        # a rollback record on one key
        k = encode_table_row(table, 3, {})[0]
        storage.sched_txn_command(cmds.Rollback([k], ts))
        ts += 10
        infos = _infos(table, names)
        for read_ts in (5, commit_tss[0], commit_tss[-1] // 2 + 3,
                        10 ** 9):
            tbl, bundle = _parity(eng, tid, infos, read_ts, resolver,
                                  ctx=f"round {rnd} ts {read_ts}")
            if read_ts == 10 ** 9 and len(tbl) > 0:
                dtypes = ["int64"] * len(infos)
                feed = _mint_feed(bundle, runner, infos, dtypes)
                _feed_vs_host(feed, tbl, infos, dtypes, len(tbl))
            else:
                bundle.release()


def test_wide_schema_nulls_and_default_cf_spills(runner, resolver):
    """>15 columns (map16 row header), NULL-heavy, with big int rows
    spilling past SHORT_VALUE_MAX_LEN into CF_DEFAULT — spilled cells
    must be host-patched into the minted feed."""
    eng = MemoryEngine()
    storage = Storage(LocalEngine(eng))
    n_cols = 28     # >15 (map16 row header) AND 28 × ~10B > 255B
    cols = [TableColumn("id", 1, FieldType.long(not_null=True),
                        is_pk_handle=True)]
    for i in range(n_cols):
        cols.append(TableColumn(f"c{i}", 2 + i, FieldType.long()))
    table = Table(777, tuple(cols))
    ts = 10
    muts = []
    for h in range(120):
        if h % 3 == 0:      # big rows spill past SHORT_VALUE_MAX_LEN
            row = {f"c{i}": (1 << 40) + h * 100 + i
                   for i in range(n_cols)}
        else:
            row = {f"c{i}": (None if (h + i) % 4 == 0 else h - i)
                   for i in range(n_cols)}
        muts.append(Mutation("put", *encode_table_row(table, h, row)))
    ts = _commit(storage, ts, muts)
    infos = _infos(table, ["id"] + [f"c{i}" for i in range(n_cols)])
    tbl, bundle = _parity(eng, 777, infos, 10 ** 9, resolver,
                          ctx="wide spill")
    assert bundle.spill_patches, "expected CF_DEFAULT spill rows"
    dtypes = ["int64"] * len(infos)
    feed = _mint_feed(bundle, runner, infos, dtypes)
    _feed_vs_host(feed, tbl, infos, dtypes, len(tbl))


def test_unsigned_and_real_columns(runner, resolver):
    """uint64 beyond 2^63 rides the u64 plane; REAL rides float64."""
    eng = MemoryEngine()
    storage = Storage(LocalEngine(eng))
    table = Table(778, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("u", 2, FieldType.long(unsigned=True)),
        TableColumn("r", 3, FieldType.double()),
    ))
    ts = 10
    muts = [Mutation("put", *encode_table_row(
        table, h, {"u": (1 << 63) + h, "r": h * 0.5}))
        for h in range(60)]
    _commit(storage, ts, muts)
    infos = _infos(table, ["id", "u", "r"])
    tbl, bundle = _parity(eng, 778, infos, 10 ** 9, resolver,
                          ctx="u64/real")
    dtypes = ["uint64", "uint64", "float64"]
    feed = _mint_feed(bundle, runner, infos, dtypes)
    _feed_vs_host(feed, tbl, infos, dtypes, len(tbl))


def test_blocking_lock_and_safe_ts_agreement(resolver):
    """An uncommitted prewrite inside the range must surface as the
    same blocking lock through every rung, with the same safe_ts."""
    eng = MemoryEngine()
    storage = Storage(LocalEngine(eng))
    table = int_table(2, table_id=779)
    ts = 10
    muts = [Mutation("put", *encode_table_row(table, h, {"c0": h,
                                                         "c1": h}))
            for h in range(50)]
    ts = _commit(storage, ts, muts)
    # prewrite WITHOUT commit: a live lock
    key, value = encode_table_row(table, 7, {"c0": -1, "c1": -1})
    storage.sched_txn_command(
        cmds.Prewrite([Mutation("put", key, value)], key, ts))
    infos = _infos(table, ["id", "c0", "c1"])
    _tbl, bundle = _parity(eng, 779, infos, 10 ** 9, resolver,
                           ctx="locks")
    bundle.release()
    _t, _s, locks = rc.build_region_columnar(
        eng.snapshot(), 779, infos, 10 ** 9)
    assert locks, "expected the live prewrite to surface"


def test_bytes_schema_stays_on_host_ladder(resolver):
    """BYTES columns leave the device envelope: the ladder must fall
    straight to the native rung."""
    eng = MemoryEngine()
    storage = Storage(LocalEngine(eng))
    table = Table(780, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("b", 2, FieldType.var_char()),
    ))
    _commit(storage, 10, [Mutation("put", *encode_table_row(
        table, h, {"b": b"x" * h})) for h in range(20)])
    infos = _infos(table, ["id", "b"])
    snap = eng.snapshot()
    _tbl, _s, _l, bundle = rc.build_region_columnar_ex(
        snap, 780, infos, 10 ** 9, device_resolver=resolver)
    assert bundle is None


# --------------------------------------------------- failpoint degrade


def test_mvcc_resolve_failpoint_degrades_down_the_ladder(resolver):
    """device::mvcc_resolve → device rung refuses → native serves;
    native gone too → interpreted. Same rows each rung."""
    eng = MemoryEngine()
    storage = Storage(LocalEngine(eng))
    table = int_table(2, table_id=781)
    _commit(storage, 10, [Mutation("put", *encode_table_row(
        table, h, {"c0": h % 3, "c1": h})) for h in range(80)])
    infos = _infos(table, ["id", "c0", "c1"])
    snap = eng.snapshot()

    def build():
        tr, tok = tracker.install()
        try:
            out = rc.build_region_columnar_ex(
                snap, 781, infos, 10 ** 9, device_resolver=resolver)
        finally:
            labels = tr.time_detail().get("labels", {})
            tracker.uninstall(tok)
        return out, labels

    (tbl_dev, _s, _l, bundle), labels = build()
    assert labels.get("cold_build") == "device" and bundle is not None
    bundle.release()

    failpoint.cfg("device::mvcc_resolve", "return")
    try:
        (tbl_nat, _s, _l, bundle), labels = build()
        assert labels.get("cold_build") == "native", labels
        assert bundle is None
        saved = nv.mvcc_build_columnar
        nv.mvcc_build_columnar = None
        try:
            (tbl_int, _s, _l, bundle), labels = build()
        finally:
            nv.mvcc_build_columnar = saved
        assert labels.get("cold_build") == "interpreted", labels
        assert bundle is None
    finally:
        failpoint.remove("device::mvcc_resolve")
    _assert_tables_equal(tbl_dev, tbl_nat, "native degrade")
    _assert_tables_equal(tbl_dev, tbl_int, "interpreted degrade")


def test_mvcc_resolve_failpoint_at_mint_falls_back_to_upload(runner,
                                                             resolver):
    """The failpoint firing INSIDE the mint (after the build chose the
    device rung) must make mint return None — the caller's host upload
    path serves."""
    eng = MemoryEngine()
    storage = Storage(LocalEngine(eng))
    table = int_table(2, table_id=782)
    _commit(storage, 10, [Mutation("put", *encode_table_row(
        table, h, {"c0": h, "c1": h})) for h in range(40)])
    infos = _infos(table, ["id", "c0", "c1"])
    snap = eng.snapshot()
    _t, _s, _l, bundle = rc.build_region_columnar_ex(
        snap, 782, infos, 10 ** 9, device_resolver=resolver)
    assert bundle is not None
    failpoint.cfg("device::mvcc_resolve", "1*return->off")
    try:
        feed = _mint_feed(bundle, runner, infos, ["int64"] * len(infos))
    finally:
        failpoint.remove("device::mvcc_resolve")
    assert feed is None
    assert bundle.consumed     # one-shot even on failure


# ------------------------------------------------- streaming cold twin


class _IngestOp:
    def __init__(self, blob):
        self.op = "ingest"
        self.value = blob


class _SnapShim:
    """Minimal region-snapshot shim over a raw MemoryEngine snapshot
    (data_key prefix, region/data_index attrs for the stream take)."""

    class _R:
        def __init__(self, rid):
            self.id = rid

    def __init__(self, snap, region_id, data_index):
        self._s = snap
        self.region = self._R(region_id)
        self.data_index = data_index

    def range_cf(self, cf, lo, hi):
        k, v, _ = self._s.range_cf(cf, data_key(lo), data_key(hi))
        return k, v, 1

    def get_value_cf(self, cf, key):
        return self._s.get_value_cf(cf, data_key(key))

    def iterator_cf(self, cf, lower=None, upper=None):
        return self._s.iterator_cf(cf, lower, upper)


def _ingest_chunks(n, tid, n_chunks, commit_ts=100):
    hs = np.arange(n, dtype=np.int64)
    sub = -(-n // n_chunks)
    blobs = []
    for s in range(0, n, sub):
        h = hs[s:s + sub]
        blobs.append(fast_mvcc_table_sst(
            tid, h, [(2, h % 7, None), (3, h % 13, None)],
            commit_ts=commit_ts))
    return blobs


def _engine_with_blobs(blobs):
    eng = MemoryEngine()
    for blob in blobs:
        wb = eng.write_batch()
        for cf, (keys, vals) in read_sst_cf(blob).items():
            wb.ingest_cf(cf, [data_key(k) for k in keys], vals)
        eng.write(wb)
    return eng


def _drain(stream, timeout=20.0):
    end = time.monotonic() + timeout
    while stream._inflight and time.monotonic() < end:
        time.sleep(0.01)
    assert not stream._inflight, "stream worker did not drain"


def test_chunked_stream_feed_byte_identical(runner, resolver):
    """1-chunk vs 3-chunk streamed builds vs parse-at-build: identical
    host tables, BYTE-identical minted feeds and digests, and no new
    resolve compile classes for the chunked shapes."""
    from tikv_tpu.copr.stream_build import ColdStreamBuilder

    n, tid = 3000, 8800
    infos = _infos(int_table(2, table_id=tid), ["id", "c0", "c1"])
    dtypes = ["int64"] * len(infos)
    feeds, tables = [], []
    kernel_counts = []
    for n_chunks in (0, 1, 3):      # 0 = no stream: parse at build
        blobs = _ingest_chunks(n, tid, max(1, n_chunks))
        eng = _engine_with_blobs(blobs)
        snap = _SnapShim(eng.snapshot(), region_id=5,
                         data_index=9 + len(blobs))
        stream = None
        if n_chunks:
            stream = ColdStreamBuilder(resolver)
            for i, blob in enumerate(blobs):
                stream.on_apply_write(5, 10 + i, [_IngestOp(blob)])
            _drain(stream)
        try:
            out = rc.build_region_columnar_ex(
                snap, tid, infos, 10 ** 9, device_resolver=resolver,
                stream_source=stream)
            tbl, _safe, _locks, bundle = out
            assert bundle is not None
            if n_chunks:
                assert stream.takes == 1 and stream.take_misses == 0
            feed = _mint_feed(bundle, runner, infos, dtypes)
            assert feed is not None
            feeds.append(feed)
            tables.append(tbl)
        finally:
            if stream is not None:
                stream.stop()
        kernel_counts.append(len(resolver._kernels))

    base = feeds[0]
    for other in feeds[1:]:
        assert len(base["flat"]) == len(other["flat"])
        for a, b in zip(base["flat"], other["flat"]):
            na, nb = np.asarray(a), np.asarray(b)
            assert na.dtype == nb.dtype and na.shape == nb.shape
            assert na.tobytes() == nb.tobytes()
        assert base["null_flags"] == other["null_flags"]
        assert base.get("digests") == other.get("digests")
    _assert_tables_equal(tables[0], tables[1], "stream 1-chunk")
    _assert_tables_equal(tables[0], tables[2], "stream 3-chunk")
    # chunk-count must not mint new resolve kernels: capacity buckets
    # land on the same padded shapes as the one-shot build
    assert kernel_counts[0] == kernel_counts[1] == kernel_counts[2]


def test_device_plane_leg_forced_matches_host_path(runner, resolver,
                                                   monkeypatch):
    """The accelerator-only H2D leg (DeviceVersionPlanes chunk appends)
    forced ON: the resolve over pre-resident planes must produce the
    same feed bytes as the pad-at-mint upload path."""
    from tikv_tpu.copr.stream_build import ColdStreamBuilder

    monkeypatch.setattr(type(resolver), "h2d_profitable", lambda s: True)
    n, tid = 2500, 8802
    infos = _infos(int_table(2, table_id=tid), ["id", "c0", "c1"])
    dtypes = ["int64"] * len(infos)
    blobs = _ingest_chunks(n, tid, 3)
    eng = _engine_with_blobs(blobs)
    stream = ColdStreamBuilder(resolver)
    try:
        for i, blob in enumerate(blobs):
            stream.on_apply_write(5, 10 + i, [_IngestOp(blob)])
        _drain(stream)
        st = stream.stats()["regions"][5]
        assert st["device"], "H2D leg not engaged"
        snap = _SnapShim(eng.snapshot(), region_id=5, data_index=12)
        tbl, _s, _l, bundle = rc.build_region_columnar_ex(
            snap, tid, infos, 10 ** 9, device_resolver=resolver,
            stream_source=stream)
        assert bundle is not None and bundle.device is not None
        feed_dev = _mint_feed(bundle, runner, infos, dtypes)
        _feed_vs_host(feed_dev, tbl, infos, dtypes, len(tbl))
    finally:
        stream.stop()

    # reference: same snapshot, no stream → pad-at-mint upload
    snap = _SnapShim(eng.snapshot(), region_id=5, data_index=12)
    _t, _s, _l, bundle = rc.build_region_columnar_ex(
        snap, tid, infos, 10 ** 9, device_resolver=resolver)
    assert bundle.device is None
    feed_up = _mint_feed(bundle, runner, infos, dtypes)
    assert len(feed_dev["flat"]) == len(feed_up["flat"])
    for a, b in zip(feed_dev["flat"], feed_up["flat"]):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert feed_dev.get("digests") == feed_up.get("digests")


def test_stream_drops_on_write_and_mismatch(resolver):
    """A plain data write poisons the stream (coverage broken); a take
    against a different data_index misses; both degrade to None."""
    from tikv_tpu.copr.stream_build import ColdStreamBuilder

    blobs = _ingest_chunks(500, 8801, 2)
    stream = ColdStreamBuilder(resolver)
    try:
        stream.on_apply_write(6, 10, [_IngestOp(blobs[0])])
        _drain(stream)

        class _Put:
            op, cf, key, value = "put", "write", b"k", b"v"

        stream.on_apply_write(6, 11, [_Put()])
        _drain(stream)
        assert stream.take(6, 8801, 11, 1, b"a", b"b") is None

        stream.on_apply_write(6, 12, [_IngestOp(blobs[0])])
        stream.on_apply_write(6, 13, [_IngestOp(blobs[1])])
        _drain(stream)
        # wrong data_index: exact-mirror check must refuse
        assert stream.take(6, 8801, 999, 500, b"a", b"b") is None
        assert stream.take_misses >= 1
    finally:
        stream.stop()


def test_stream_rejects_key_versions_straddling_chunks(resolver):
    """Two versions of ONE user key split across ingest chunks: the raw
    CF_WRITE keys still ascend (inverted commit_ts), but concat would
    mint a duplicate segment and the resolve would emit the key twice —
    the stream must reject the straddling chunk and miss cleanly."""
    from tikv_tpu.copr.stream_build import ColdStreamBuilder

    tid = 8803
    blob1 = fast_mvcc_table_sst(tid, np.arange(100, dtype=np.int64),
                                [(2, np.zeros(100, np.int64), None)],
                                commit_ts=200)
    # an OLDER version of the last key in blob1: raw key sorts AFTER
    # every key of blob1, so a pure ascending fence would admit it
    blob2 = fast_mvcc_table_sst(tid, np.asarray([99], dtype=np.int64),
                                [(2, np.ones(1, np.int64), None)],
                                commit_ts=100)
    stream = ColdStreamBuilder(resolver)
    try:
        stream.on_apply_write(7, 10, [_IngestOp(blob1)])
        stream.on_apply_write(7, 11, [_IngestOp(blob2)])
        _drain(stream)
        assert stream.chunks_rejected >= 1
        # the stream is gone: any take misses (never a corrupt serve)
        assert stream.take(7, tid, 11, 101, b"a", b"b") is None
    finally:
        stream.stop()


def test_grpc_cold_stream_production_twin():
    """Fast tier-1 twin of bench config 6: bulk-ingest through the live
    gRPC path in chunks, then assert the cold query is served by the
    device build (mvcc_resolve phase, feed born resident), results stay
    exact, warm queries hit, and /health + tracker expose the new
    cold-build observability."""
    import jax

    from tikv_tpu.device.runner import DeviceRunner
    from tikv_tpu.parallel import make_mesh
    from tikv_tpu.raftstore.metapb import Store
    from tikv_tpu.server import (
        Node, PdServer, RemotePdClient, TikvServer, TxnClient,
    )
    from tikv_tpu.server.status_server import StatusServer

    from tikv_tpu.config import TikvConfig

    device = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
    pd_server = PdServer("127.0.0.1:0")
    pd_server.start()
    pd_addr = f"127.0.0.1:{pd_server.port}"
    cfg = TikvConfig()
    # force the stream past the AUTO core gate: CI boxes may be
    # single-CPU, and this twin exists to exercise the stream path
    cfg.coprocessor.cold_stream = True
    cfg.coprocessor.device_row_threshold = 128
    node = Node("127.0.0.1:0", RemotePdClient(pd_addr),
                device_runner=device, config=cfg)
    srv = TikvServer(node)
    node.addr = f"127.0.0.1:{srv.port}"
    node.pd.put_store(Store(node.store_id, node.addr))
    srv.start()
    status = StatusServer("127.0.0.1:0", node=node,
                          config_controller=node.config_controller)
    status.start()
    try:
        assert node.cold_stream is not None, "stream not wired"
        c = TxnClient(pd_addr)
        n, tid = 4096, 9700
        table = int_table(2, table_id=tid)
        c.import_switch_mode(node.store_id, True)
        for blob in _ingest_chunks(n, tid, 4, commit_ts=c.tso()):
            k, _v = read_sst_cf(blob)[CF_WRITE][0][0], None
            c.ingest_sst(blob, table_record_key(tid, 0), chunk=1 << 20)
        c.import_switch_mode(node.store_id, False)
        # let the stream worker drain before the cold query (the
        # bounded take-wait would otherwise make this timing-dependent)
        end = time.monotonic() + 20
        while node.cold_stream._inflight and time.monotonic() < end:
            time.sleep(0.02)

        sel = DagSelect.from_table(table, ["id", "c0", "c1"])
        dag = sel.aggregate(
            [sel.col("c0")],
            [("count_star", None), ("sum", sel.col("c1"))]
        ).build(start_ts=c.tso())
        cold = c.coprocessor(dag, timeout=120)
        hs = np.arange(n)
        want = sorted([int((hs % 7 == g).sum()),
                       int((hs % 13)[hs % 7 == g].sum()), g]
                      for g in range(7))
        assert sorted(cold["rows"]) == want
        td = cold["time_detail"]
        assert td["labels"].get("cold_build") == "device", td["labels"]
        assert td["labels"].get("device_feed") == "device_resolve", \
            td["labels"]
        assert "mvcc_resolve" in td["phases_ms"], td["phases_ms"]
        assert "h2d_stream" in td["phases_ms"], td["phases_ms"]
        assert "feed_upload" not in td["phases_ms"], td["phases_ms"]
        assert node.cold_stream.takes >= 1

        sel = DagSelect.from_table(table, ["id", "c0", "c1"])
        dag = sel.aggregate(
            [sel.col("c0")],
            [("count_star", None), ("sum", sel.col("c1"))]
        ).build(start_ts=c.tso())
        warm = c.coprocessor(dag, timeout=120)
        assert sorted(warm["rows"]) == want
        assert warm["time_detail"]["labels"].get("device_feed") == "hit"

        base = f"http://127.0.0.1:{status.port}"
        body = json.load(urllib.request.urlopen(f"{base}/health"))
        cold_roll = body.get("cold_build", {})
        assert cold_roll.get("device_builds", 0) >= 1, cold_roll
        assert cold_roll.get("resolver", {}).get("mints", 0) >= 1
        assert cold_roll.get("stream", {}).get("chunks_parsed", 0) >= 4
        assert cold_roll["stream"]["takes"] >= 1
    finally:
        status.stop()
        srv.stop()
        pd_server.stop()
