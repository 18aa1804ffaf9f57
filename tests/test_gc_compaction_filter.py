"""GC folded into engine compaction + the auto GcManager tick.

Reference: src/server/gc_worker/compaction_filter.rs (write-CF filter,
default-CF payload cleanup) and gc_manager.rs (safe-point driven
auto-GC).
"""

import time

import pytest

from tikv_tpu.engine.disk import DiskEngine
from tikv_tpu.engine.traits import CF_DEFAULT, CF_WRITE
from tikv_tpu.storage.txn.gc import MvccCompactionFilter
from tikv_tpu.storage.txn_types import (
    Write,
    WriteType,
    append_ts,
    encode_key,
)


def _wkey(user: bytes, commit_ts: int) -> bytes:
    return b"z" + append_ts(encode_key(user), commit_ts)


def _dkey(user: bytes, start_ts: int) -> bytes:
    return b"z" + append_ts(encode_key(user), start_ts)


def put_version(eng, user, start_ts, commit_ts, value):
    wb = eng.write_batch()
    if len(value) <= 255:
        rec = Write(WriteType.PUT, start_ts, short_value=value)
    else:
        rec = Write(WriteType.PUT, start_ts)
        wb.put_cf(CF_DEFAULT, _dkey(user, start_ts), value)
    wb.put_cf(CF_WRITE, _wkey(user, commit_ts), rec.to_bytes())
    eng.write(wb)


def delete_version(eng, user, start_ts, commit_ts):
    wb = eng.write_batch()
    wb.put_cf(CF_WRITE, _wkey(user, commit_ts),
              Write(WriteType.DELETE, start_ts).to_bytes())
    eng.write(wb)


def test_compaction_filter_gc(tmp_path):
    safe = {"sp": 0}
    eng = DiskEngine(str(tmp_path / "d"), max_runs=0,
                     compaction_filter=MvccCompactionFilter(
                         lambda: safe["sp"]))
    big = b"B" * 300
    # key a: three PUT versions, newest above safe point
    put_version(eng, b"a", 10, 20, b"v1")
    put_version(eng, b"a", 30, 40, big)         # payload in default CF
    put_version(eng, b"a", 50, 60, b"v3")
    # key b: deleted at/below the safe point → whole key erased
    put_version(eng, b"b", 10, 20, b"bv")
    delete_version(eng, b"b", 30, 40)
    # key c: single live PUT at/below safe point → kept (newest)
    put_version(eng, b"c", 10, 20, b"cv")
    safe["sp"] = 45
    eng.flush()     # max_runs=0 → every flush compacts

    # a@60 (above sp) and a@40 (newest <= sp, PUT) survive; a@20 dies
    assert eng.get_value_cf(CF_WRITE, _wkey(b"a", 60))
    assert eng.get_value_cf(CF_WRITE, _wkey(b"a", 40))
    assert eng.get_value_cf(CF_WRITE, _wkey(b"a", 20)) is None
    assert eng.get_value_cf(CF_DEFAULT, _dkey(b"a", 30)) == big
    # b fully erased (DELETE at/below sp + older version)
    assert eng.get_value_cf(CF_WRITE, _wkey(b"b", 40)) is None
    assert eng.get_value_cf(CF_WRITE, _wkey(b"b", 20)) is None
    # c kept
    assert eng.get_value_cf(CF_WRITE, _wkey(b"c", 20))
    eng.close()


def test_compaction_filter_drops_orphaned_default(tmp_path):
    safe = {"sp": 100}
    eng = DiskEngine(str(tmp_path / "d"), max_runs=0,
                     compaction_filter=MvccCompactionFilter(
                         lambda: safe["sp"]))
    big = b"X" * 300
    put_version(eng, b"k", 10, 20, big)     # old big version
    put_version(eng, b"k", 30, 40, b"new")
    eng.flush()
    # the dropped PUT@20's default payload went with it
    assert eng.get_value_cf(CF_WRITE, _wkey(b"k", 20)) is None
    assert eng.get_value_cf(CF_DEFAULT, _dkey(b"k", 10)) is None
    assert eng.get_value_cf(CF_WRITE, _wkey(b"k", 40))
    eng.close()


def test_filter_inactive_without_safe_point(tmp_path):
    eng = DiskEngine(str(tmp_path / "d"), max_runs=0,
                     compaction_filter=MvccCompactionFilter(lambda: 0))
    put_version(eng, b"a", 10, 20, b"v1")
    put_version(eng, b"a", 30, 40, b"v2")
    eng.flush()
    assert eng.get_value_cf(CF_WRITE, _wkey(b"a", 20))
    assert eng.get_value_cf(CF_WRITE, _wkey(b"a", 40))
    eng.close()


def test_auto_gc_manager_over_network():
    from tikv_tpu.raftstore.metapb import Store as StoreMeta
    from tikv_tpu.server.client import TxnClient
    from tikv_tpu.server.node import Node
    from tikv_tpu.server.pd_server import PdServer, RemotePdClient
    from tikv_tpu.server.server import TikvServer

    pd_server = PdServer("127.0.0.1:0")
    pd_server.start()
    pd_addr = f"127.0.0.1:{pd_server.port}"
    node = Node("127.0.0.1:0", RemotePdClient(pd_addr),
                tick_interval=0.02)
    srv = TikvServer(node)
    node.addr = f"127.0.0.1:{srv.port}"
    node.pd.put_store(StoreMeta(node.store_id, node.addr))
    srv.start()
    client = TxnClient(pd_addr)
    try:
        client.put(b"g", b"old")
        client.put(b"g", b"mid")
        ts_mid = client.tso()
        client.put(b"g", b"new")
        # advance the PD safe point past the first two versions; the
        # node's GcManager tick must sweep them WITHOUT any KvGC RPC
        client.pd.set_gc_safe_point(ts_mid)
        from tikv_tpu.raftstore.peer_storage import data_key
        eng = node.engine

        def version_count():
            n = 0
            it = eng.snapshot().iterator_cf(
                CF_WRITE, data_key(encode_key(b"g")),
                data_key(encode_key(b"g")) + b"\xff" * 9)
            ok = it.seek_to_first()
            while ok:
                n += 1
                ok = it.next()
            return n

        deadline = time.time() + 10
        while time.time() < deadline and version_count() > 2:
            time.sleep(0.1)
        # versions: new (above sp) + mid (newest <= sp) survive; old dies
        assert version_count() == 2, \
            f"gc never ran ({version_count()} versions left)"
        assert client.get(b"g") == b"new"
    finally:
        srv.stop()
        pd_server.stop()


def test_compaction_preserves_pinned_snapshots(tmp_path):
    """A snapshot taken before compaction must keep seeing the GC'd
    versions (copy-on-write contract)."""
    safe = {"sp": 0}
    eng = DiskEngine(str(tmp_path / "d"), max_runs=0,
                     compaction_filter=MvccCompactionFilter(
                         lambda: safe["sp"]))
    put_version(eng, b"a", 10, 20, b"v1")
    put_version(eng, b"a", 30, 40, b"v2")
    snap = eng.snapshot()
    safe["sp"] = 45
    eng.flush()
    # live view: old version gone
    assert eng.get_value_cf(CF_WRITE, _wkey(b"a", 20)) is None
    # pinned snapshot: still there
    assert snap.get_value_cf(CF_WRITE, _wkey(b"a", 20))
    assert snap.get_value_cf(CF_WRITE, _wkey(b"a", 40))
    eng.close()


def test_consistency_check_immune_to_gc_divergence():
    """One replica compacted with the safe point, another not: the
    pinned-safe-point hash must still agree (no false positives)."""
    from tikv_tpu.testing.cluster import Cluster

    c = Cluster(3)
    c.bootstrap()
    c.start()
    region = c.region_for(b"k").region
    # real MVCC versions in the write CF (3 rounds of overwrites)
    for round_ in range(3):
        for i in range(10):
            ts = c.pd.tso()
            rec = Write(WriteType.PUT, ts - 1,
                        short_value=b"r%d" % round_)
            c.must_put(append_ts(encode_key(b"k%02d" % i), ts),
                       rec.to_bytes(), cf=CF_WRITE)
    # advance the safe point, then run the COMPACTION FILTER on one
    # replica's engine only — exactly the node-local divergence a
    # locally-timed compaction produces
    sp = c.pd.tso()
    c.pd.set_gc_safe_point(sp)
    victim = sorted(c.stores)[0]
    eng = c.engines[victim]
    filt = MvccCompactionFilter(lambda: sp)
    dropped = 0
    with eng._mu:
        for cf in filt.CF_ORDER:
            data = eng._writable(cf)
            live_keys, live_vals = data.flat()
            keys, vals = filt.filter_cf(cf, live_keys, live_vals)
            dropped += len(live_keys) - len(keys)
            data.set_flat(list(keys), list(vals))
    assert dropped > 0      # the replica really diverged in raw bytes
    # the safe-point-pinned hash still agrees across all replicas
    c.check_consistency(region.id)
