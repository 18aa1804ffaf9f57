"""Write-churn serving path (fast tier-1 guard for bench 6w):
after a point write, the next coprocessor query must serve via the
columnar cache's DELTA path — no full ``columnar_build`` phase, no
device feed re-upload, no kernel recompile — and results stay exact.
"""

import json
import urllib.request

import pytest

from tikv_tpu.server import Node, PdServer, RemotePdClient, TikvServer, \
    TxnClient
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import encode_table_row, int_table


@pytest.fixture(scope="module")
def rig():
    from tikv_tpu.device.runner import DeviceRunner
    from tikv_tpu.raftstore.metapb import Store
    device = DeviceRunner()
    pd_server = PdServer("127.0.0.1:0")
    pd_server.start()
    pd_addr = f"127.0.0.1:{pd_server.port}"
    node = Node("127.0.0.1:0", RemotePdClient(pd_addr),
                device_runner=device, device_row_threshold=128)
    srv = TikvServer(node)
    node.addr = f"127.0.0.1:{srv.port}"
    node.pd.put_store(Store(node.store_id, node.addr))
    srv.start()
    client = TxnClient(pd_addr)
    yield {"srv": srv, "node": node, "client": client,
           "device": device, "pd": pd_server}
    srv.stop()
    pd_server.stop()


def _agg_dag(table, ts):
    sel = DagSelect.from_table(table, ["id", "c0", "c1"])
    return sel.aggregate(
        [sel.col("c0")],
        [("count_star", None), ("sum", sel.col("c1"))]).build(start_ts=ts)


def _expect(rows_by_handle):
    out = {}
    for h, (c0, c1) in rows_by_handle.items():
        cnt, sm = out.get(c0, (0, 0))
        out[c0] = (cnt + 1, sm + c1)
    return sorted([cnt, sm, g] for g, (cnt, sm) in out.items())


def test_single_write_serves_via_delta_path(rig):
    c, node, device = rig["client"], rig["node"], rig["device"]
    table = int_table(2, table_id=9400)
    model = {}
    muts = []
    for h in range(400):
        row = (h % 5, h * 3)
        model[h] = row
        key, value = encode_table_row(table, h,
                                      {"c0": row[0], "c1": row[1]})
        muts.append(("put", key, value))
    c.txn_write(muts)

    cold = c.coprocessor(_agg_dag(table, c.tso()))
    assert sorted(cold["rows"]) == _expect(model)
    assert cold["time_detail"]["labels"]["copr_cache"] == "build"
    assert "columnar_build" in cold["time_detail"]["phases_ms"]
    kernels_warm = len(device._kernel_cache)

    # ONE point write (append), then query: the delta path must serve
    model[400] = (1, 99)
    key, value = encode_table_row(table, 400, {"c0": 1, "c1": 99})
    c.txn_write([("put", key, value)])
    resp = c.coprocessor(_agg_dag(table, c.tso()))
    assert sorted(resp["rows"]) == _expect(model)
    td = resp["time_detail"]
    assert td["labels"]["copr_cache"] == "delta", td["labels"]
    assert "columnar_build" not in td["phases_ms"], td["phases_ms"]
    assert "delta_apply" in td["phases_ms"]
    if td["labels"]["backend"] == "device":
        # feed patched in place, not re-uploaded; compile classes stable
        assert td["labels"].get("device_feed") == "patch", td["labels"]
        assert "feed_upload" not in td["phases_ms"]
        assert "feed_patch" in td["phases_ms"]
        # only the one shared patch program (and the marks that its
        # bucket lengths are warm for a feed's class) may appear — a
        # point write must not mint new kernel compile classes
        minted = len([k for k in device._kernel_cache
                      if not (isinstance(k, tuple) and
                              k[0] == "feed_patch_warm")])
        assert minted - kernels_warm <= 1
    assert node.copr_cache.deltas >= 1

    # churn: updates and appends keep riding the delta path
    builds_before = node.copr_cache.misses
    for i in range(5):
        h = 100 + i if i % 2 else 450 + i       # update | append
        row = (i % 5, 1000 + i)
        model[h] = row
        key, value = encode_table_row(table, h,
                                      {"c0": row[0], "c1": row[1]})
        c.txn_write([("put", key, value)])
        resp = c.coprocessor(_agg_dag(table, c.tso()))
        assert sorted(resp["rows"]) == _expect(model)
        assert resp["time_detail"]["labels"]["copr_cache"] == "delta"
    assert node.copr_cache.misses == builds_before, \
        "churn must not trigger columnar rebuilds"


def test_delete_churn_stays_exact(rig):
    c, node = rig["client"], rig["node"]
    table = int_table(2, table_id=9401)
    model = {}
    muts = []
    for h in range(300):
        model[h] = (h % 3, h)
        key, value = encode_table_row(table, h, {"c0": h % 3, "c1": h})
        muts.append(("put", key, value))
    c.txn_write(muts)
    r = c.coprocessor(_agg_dag(table, c.tso()))
    assert sorted(r["rows"]) == _expect(model)
    from tikv_tpu.codec.keys import table_record_key
    for h in (7, 8, 9, 150):
        del model[h]
        c.txn_write([("delete", table_record_key(table.table_id, h),
                      None)])
        r = c.coprocessor(_agg_dag(table, c.tso()))
        assert sorted(r["rows"]) == _expect(model), f"after delete {h}"
        assert r["time_detail"]["labels"]["copr_cache"] == "delta"


def test_health_route_exposes_cache_and_delta_observability(rig):
    node = rig["node"]
    from tikv_tpu.server.status_server import StatusServer
    srv = StatusServer("127.0.0.1:0", node=node,
                       config_controller=node.config_controller)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = json.load(urllib.request.urlopen(f"{base}/health"))
        cc = body["copr_cache"]
        assert cc["deltas"] >= 1 and cc["hits"] >= 0
        assert "delta_log" in cc and cc["delta_log"]["entries"] >= 0
        assert any("tombstone_ratio" in ln for ln in cc["lines"])
        metrics = urllib.request.urlopen(
            f"{base}/metrics").read().decode()
        assert "tikv_coprocessor_delta_log_depth" in metrics
        assert "tikv_coprocessor_region_cache_tombstone_ratio" in metrics
        assert 'result="delta"' in metrics
    finally:
        srv.stop()
