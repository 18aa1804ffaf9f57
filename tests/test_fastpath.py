"""Microsecond warm path: compiled request fast path + back-to-back
dispatcher + pinned D2H staging (server/fastpath.py, coalescer
pipeline, request._PinnedStager).

Covers: wire-template codec units (every msgpack int width, floats,
structural-mismatch safety); randomized fast-vs-full-decode parity
over rotating constants, NULL-heavy rows, wide >15-col tables and
tombstones through the real gRPC stack; every invalidation edge
(delta patch, region split / epoch bump, online config change);
exactly-once request RU on the fast leg; the ``copr::fastpath``
failpoint arms (miss/full/corrupt — wrong answers impossible); the
pipeline close; and the pinned-stager mechanics on CPU's
``unpinned_host`` space.
"""

import threading
import time

import numpy as np
import pytest

from tikv_tpu.server import wire
from tikv_tpu.server.fastpath import (
    FastPathCache,
    WireTemplate,
    _const_at,
    _dag_const_substituter,
    _encode_segments,
    _key_template,
    _mark_slots,
    _parse_scalar,
)
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import encode_table_row, int_table
from tikv_tpu.utils import failpoint


# ---------------------------------------------------------------- units


def _pack_req(dag, deadline_ms=None, trace_id=None, **extra):
    req = {"tp": 103, "dag": wire.enc_dag(dag), "force_backend": None,
           "paging_size": 0, "resume_token": None,
           "resource_group": "default", "request_source": "", **extra}
    if deadline_ms is not None:
        req["deadline_ms"] = deadline_ms
    if trace_id is not None:
        req["trace_id"] = trace_id
    return wire.pack(req)


def _learn_template(raw):
    req = wire.unpack(raw)
    marked, n_const = _mark_slots(req)
    segments, slots = _encode_segments(marked)
    tpl = WireTemplate(segments, slots)
    orig = []
    for s in slots:
        if s.kind == "const":
            orig.append(_const_at(req["dag"], s.index))
        elif s.kind == "start_ts":
            orig.append(req["dag"]["start_ts"])
        elif s.kind == "deadline_ms":
            orig.append(req["deadline_ms"])
        else:
            orig.append(req["trace_id"])
    assert tpl.render(orig) == raw, "template must be byte-exact"
    return tpl, slots, n_const


def _sel(table, thr, ts=7, cols=None):
    s = DagSelect.from_table(
        table, cols or [c.name for c in table.columns])
    return s.where(s.col("c1" if cols is None else cols[-1]) > thr) \
        .build(start_ts=ts)


def test_parse_scalar_every_width():
    """The match-time scalar parser agrees with msgpack for every
    encoding width the packer can choose."""
    import msgpack
    vals = [0, 1, 127, 128, 255, 256, 65535, 65536, 2**32 - 1, 2**32,
            2**63 - 1, -1, -32, -33, -128, -129, -32768, -32769,
            -2**31, -2**31 - 1, -2**63, 1.5, -0.25, "x", "y" * 40,
            b"bin", b"b" * 300, True, False, None]
    for v in vals:
        raw = msgpack.packb(v, use_bin_type=True)
        got = _parse_scalar(raw + b"\x01", 0)
        assert got is not None, v
        parsed, off = got
        assert parsed == v and off == len(raw), v
    # containers are NOT scalars: the walk must refuse, never guess
    for v in ([1], {"k": 1}):
        raw = msgpack.packb(v, use_bin_type=True)
        assert _parse_scalar(raw, 0) is None or raw[0] in (0x91, 0x81)
        # (fix headers parse as smallints only if misaligned — the
        # template's following fixed segment then mismatches)


def test_template_match_and_rebuild_across_widths():
    """One learned class serves constants/timestamps at ANY msgpack
    width, and the precompiled constructor rebuilds the exact DAG the
    full decode would produce."""
    table = int_table(2, table_id=501)
    raw = _pack_req(_sel(table, 981, ts=12345), deadline_ms=60000)
    tpl, slots, n_const = _learn_template(raw)
    make = _dag_const_substituter(_sel(table, 981, ts=12345))
    for thr, ts, dl in [(5, 1, 1), (127, 128, 10**6), (-2**31, 2**40, 7),
                        (2**31 - 1, 2**63 - 1, 2**31)]:
        dag2 = _sel(table, thr, ts=ts)
        raw2 = _pack_req(dag2, deadline_ms=dl)
        vals = tpl.match(raw2)
        assert vals is not None, (thr, ts, dl)
        consts = [v for s, v in zip(slots, vals) if s.kind == "const"]
        ts_got = [v for s, v in zip(slots, vals)
                  if s.kind == "start_ts"][0]
        assert make(consts, ts_got) == dag2


def test_template_structural_mismatch_is_a_miss():
    """Anything but a same-shape repeat misses: different column,
    extra condition, different table, different ranges, float-for-int
    constant, dtype-bucket crossing, truncated body."""
    table = int_table(2, table_id=502)
    raw = _pack_req(_sel(table, 50), deadline_ms=1000)
    tpl, _, _ = _learn_template(raw)
    s = DagSelect.from_table(table, ["id", "c0", "c1"])
    other_col = s.where(s.col("c0") > 50).build(start_ts=7)
    s2 = DagSelect.from_table(table, ["id", "c0", "c1"])
    two_conds = s2.where(s2.col("c1") > 50,
                         s2.col("c0") > 1).build(start_ts=7)
    cases = [
        _pack_req(other_col, deadline_ms=1000),
        _pack_req(two_conds, deadline_ms=1000),
        _pack_req(_sel(int_table(2, table_id=503), 50),
                  deadline_ms=1000),
        _pack_req(_sel(table, 50), deadline_ms=1000,
                  resource_group="other"),
        _pack_req(_sel(table, 2**40), deadline_ms=1000),   # dtype bump
        _pack_req(_sel(table, 50)),                        # no deadline
    ]
    for c in cases:
        assert tpl.match(c) is None
    assert tpl.match(raw[:-3]) is None
    # float where the learned class saw an int
    sf = DagSelect.from_table(table, ["id", "c0", "c1"])
    fdag = sf.where(sf.col("c1") > 50.5).build(start_ts=7)
    assert tpl.match(_pack_req(fdag, deadline_ms=1000)) is None
    # ...and the untouched original still matches
    assert tpl.match(raw) is not None


def test_share_key_template_restamps_consts():
    """The cached share-batch-key template re-stamps constant leaves
    in slot order — a rotated constant yields the same key the slow
    path's plan_key() would."""
    table = int_table(2, table_id=504)
    d1, d2 = _sel(table, 10, ts=1), _sel(table, 77, ts=1)
    fill, n = _key_template(("share", 123, 4, d1.plan_key(),
                             d1.ranges))
    assert n == 1
    assert fill([77]) == ("share", 123, 4, d2.plan_key(), d2.ranges)
    assert fill([10]) == ("share", 123, 4, d1.plan_key(), d1.ranges)


def test_learn_rejects_unknown_fields_and_nonfast_options():
    from tikv_tpu.server.fastpath import _Ineligible
    table = int_table(2, table_id=505)
    dag = _sel(table, 5)
    for extra in ({"mystery": 1}, {"paging_size": 10},
                  {"force_backend": "device"},
                  {"resume_token": 3}, {"tp": 104}):
        req = wire.unpack(_pack_req(dag))
        req.update(extra)
        with pytest.raises(_Ineligible):
            _mark_slots(req)


# ------------------------------------------------------------- gRPC rig


@pytest.fixture(scope="module")
def rig():
    import jax

    from tikv_tpu.device.runner import DeviceRunner
    from tikv_tpu.parallel import make_mesh
    from tikv_tpu.raftstore.metapb import Store
    from tikv_tpu.server import (
        Node, PdServer, RemotePdClient, TikvServer, TxnClient,
    )
    device = DeviceRunner(mesh=make_mesh(jax.devices()[:1]))
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
    yield {"srv": srv, "node": node, "client": client, "device": device}
    srv.stop()
    pd_server.stop()


def _load(rig, table, rows):
    muts = []
    for h, row in rows:
        key, value = encode_table_row(table, h, row)
        muts.append(("put", key, value))
    rig["client"].txn_write(muts)


def _strip_volatile(resp):
    return {k: v for k, v in resp.items()
            if k not in ("elapsed_ns", "time_detail", "scan_detail",
                         "trace_id", "exec_summaries")}


def _fp(rig):
    return rig["node"].fastpath


def test_e2e_fastpath_parity_rotating_constants(rig):
    """Randomized fast-vs-full-decode parity through the real gRPC
    stack: rotating constants within one class over a NULL-heavy
    table, every response equal to a failpoint-forced full-decode
    control of the same request."""
    c = rig["client"]
    table = int_table(2, table_id=9601)
    rng = np.random.default_rng(0)
    rows = []
    for h in range(2500):
        row = {}
        if rng.random() > 0.5:                  # ~50% NULL c0
            row["c0"] = int(rng.integers(-500, 500))
        if rng.random() > 0.2:
            row["c1"] = int(rng.integers(-1000, 1000))
        rows.append((h, row))
    _load(rig, table, rows)

    def ask(thr):
        return c.coprocessor(_sel(table, thr, ts=c.tso()),
                             deadline_ms=30_000, timeout=60)

    ask(0)          # learn
    base = _fp(rig).stats()
    for thr in rng.integers(-900, 900, 12).tolist():
        fast = ask(int(thr))
        failpoint.cfg("copr::fastpath", "return(miss)")
        try:
            slow = ask(int(thr))
        finally:
            failpoint.remove("copr::fastpath")
        assert fast["rows"] == slow["rows"], thr
        assert _strip_volatile(fast) == _strip_volatile(slow), thr
        assert fast["backend"] == "device"
    st = _fp(rig).stats()
    assert st["hit"] - base["hit"] >= 12, (base, st)


def test_e2e_fastpath_wide_and_tombstoned(rig):
    """Wide (>15 col, map16 row header) and tombstoned (deleted rows)
    shapes ride the fast path with full parity."""
    c = rig["client"]
    table = int_table(17, table_id=9602)
    cols = [col.name for col in table.columns]
    rows = [(h, {f"c{i}": (h * 31 + i) % 400 - 200 for i in range(17)})
            for h in range(1500)]
    _load(rig, table, rows)
    # tombstones: delete a third of the rows
    from tikv_tpu.testing.fixture import table_record_key
    dels = [("delete", table_record_key(table.table_id, h), None)
            for h in range(0, 1500, 3)]
    c.txn_write(dels)

    def ask(thr):
        return c.coprocessor(_sel(table, thr, ts=c.tso(), cols=cols),
                             deadline_ms=30_000, timeout=60)

    ask(0)      # learn (also absorbs the delete-delta invalidation)
    ask(1)      # re-learn on the settled generation
    for thr in (-150, -5, 42, 199):
        fast = ask(thr)
        failpoint.cfg("copr::fastpath", "return(miss)")
        try:
            slow = ask(thr)
        finally:
            failpoint.remove("copr::fastpath")
        assert fast["rows"] == slow["rows"], thr
        assert len(fast["rows"]) > 0 or thr == 199


def test_e2e_invalidation_delta_epoch_config(rig):
    """Each staleness source invalidates the learned class: a delta
    write, a region split (epoch bump), and an online config change —
    every post-event answer reflects CURRENT data (parity, never
    staleness) and the class re-learns."""
    c, node = rig["client"], rig["node"]
    table = int_table(2, table_id=9603)
    _load(rig, table, [(h, {"c0": h % 7, "c1": h % 100})
                       for h in range(1200)])

    def ask(thr):
        return c.coprocessor(_sel(table, thr, ts=c.tso()),
                             deadline_ms=30_000, timeout=60)

    ask(50)
    r0 = ask(50)
    st = _fp(rig).stats()
    assert st["hit"] >= 1

    # -- delta patch: the write must be visible in the very next answer
    k, v = encode_table_row(table, 50_000, {"c0": 1, "c1": 99})
    c.txn_write([("put", k, v)])
    r1 = ask(50)
    assert len(r1["rows"]) == len(r0["rows"]) + 1, \
        "fast path served stale data across a delta"
    st = _fp(rig).stats()
    assert st["invalidate"] + st["fallback"] >= 1, st

    # -- re-learn, then online config change retires the class
    ask(50)
    hit0 = _fp(rig).stats()["hit"]
    ask(50)
    assert _fp(rig).stats()["hit"] == hit0 + 1
    node.config_controller.update({"coprocessor.trace-sample": 1.0})
    ask(50)     # config gen moved: this request re-learns
    st = _fp(rig).stats()
    assert any(k.startswith("invalidate:config") or
               k.startswith("miss") for k in st["reasons"])

    # -- region split: epoch bump / new region boundary
    ask(50)
    hit1 = _fp(rig).stats()["hit"]
    ask(50)
    assert _fp(rig).stats()["hit"] == hit1 + 1
    from tikv_tpu.testing.fixture import table_record_key
    c.split(table_record_key(table.table_id, 600))
    time.sleep(0.2)
    r2 = ask(50)        # must not serve the pre-split line
    failpoint.cfg("copr::fastpath", "return(miss)")
    try:
        r3 = ask(50)
    finally:
        failpoint.remove("copr::fastpath")
    assert r2["rows"] == r3["rows"]


def test_e2e_ru_exactly_once_on_fast_leg(rig):
    """A fast-path hit charges its request-base RU exactly once and
    still attributes launch/D2H charges to its (learned, pre-bound)
    tag — same ledger discipline as the slow path."""
    from tikv_tpu.resource_metering import GLOBAL_RECORDER
    c = rig["client"]
    table = int_table(2, table_id=9604)
    _load(rig, table, [(h, {"c0": h % 5, "c1": h % 50})
                       for h in range(800)])

    def ask(thr):
        return c.coprocessor(_sel(table, thr, ts=c.tso()),
                             deadline_ms=30_000, timeout=60,
                             resource_group="fp-tenant")

    ask(1)      # learn (slow leg, counted once there)
    base = GLOBAL_RECORDER.totals().get("fp-tenant")
    base_req = base.requests if base is not None else 0
    base_hits = _fp(rig).stats()["hit"]
    for i in range(5):
        ask(i)
    assert _fp(rig).stats()["hit"] - base_hits >= 5
    tot = GLOBAL_RECORDER.totals()["fp-tenant"]
    assert tot.requests - base_req == 5, \
        (base_req, tot.requests)       # exactly once per fast hit
    assert tot.ru > 0


def test_e2e_failpoint_arms_never_wrong(rig):
    """All three copr::fastpath arms (force-miss / force-full-decode /
    corrupt-fingerprint): answers stay byte-equal to the unfaulted
    control, and the corrupt arm can only force a re-learn."""
    c = rig["client"]
    table = int_table(2, table_id=9605)
    _load(rig, table, [(h, {"c0": h % 3, "c1": h % 40})
                       for h in range(600)])

    def ask(thr):
        return c.coprocessor(_sel(table, thr, ts=c.tso()),
                             deadline_ms=30_000, timeout=60)

    ask(7)
    control = ask(7)["rows"]
    for arm in ("miss", "full", "corrupt"):
        failpoint.cfg("copr::fastpath", f"return({arm})")
        try:
            got = ask(7)["rows"]
        finally:
            failpoint.remove("copr::fastpath")
        assert got == control, arm
        # post-fault: the path heals (corrupt forces one re-learn)
        healed = ask(7)
        assert healed["rows"] == control, arm
    st = _fp(rig).stats()
    assert any(k.startswith("bypass:failpoint") for k in st["reasons"])


def test_e2e_trace_and_health_surfaces(rig):
    """Observability: the served leg reads from the trace label, the
    fastpath span decomposes the wall, /health carries the rollup and
    /metrics the counter — and repeat hits mint ZERO new device
    compile classes."""
    import json
    import urllib.request

    from tikv_tpu.server.status_server import StatusServer
    c, node, device = rig["client"], rig["node"], rig["device"]
    table = int_table(2, table_id=9606)
    _load(rig, table, [(h, {"c0": h % 9, "c1": h % 60})
                       for h in range(700)])

    def ask(thr):
        return c.coprocessor(_sel(table, thr, ts=c.tso()),
                             deadline_ms=30_000, timeout=60)

    ask(3)
    ask(4)      # first hit warms the stacked/solo kernels
    kernel_classes = len(device._kernel_cache)
    r = ask(5)
    assert len(device._kernel_cache) == kernel_classes, \
        "a repeat-shape fast hit minted a new compile class"
    tr = node.trace_buffer.get(r["trace_id"])
    assert tr is not None
    assert tr.labels.get("fastpath") == "hit", tr.labels
    names = {s.name for s in tr.spans}
    assert "fastpath" in names, names
    status = StatusServer("127.0.0.1:0", node=node,
                          config_controller=node.config_controller)
    status.start()
    try:
        url = f"http://127.0.0.1:{status.port}"
        body = json.load(urllib.request.urlopen(f"{url}/health"))
        assert "fastpath" in body, sorted(body)
        roll = body["fastpath"]
        assert roll["hit"] >= 1 and roll["classes"] >= 1
        assert "pinned_readback" in roll
        metrics = urllib.request.urlopen(
            f"{url}/metrics").read().decode()
        assert "tikv_coprocessor_fastpath_total" in metrics
    finally:
        status.stop()


def test_e2e_deadline_admission_on_fast_leg(rig):
    """A hopeless budget sheds on the fast leg with the typed error +
    trace_id/time_detail on the wire — never a late ack.  Driven at
    the raw service entry so client-side gRPC timeouts stay out of
    the picture."""
    c, svc = rig["client"], rig["srv"].service
    table = int_table(2, table_id=9607)
    _load(rig, table, [(h, {"c0": 1, "c1": h % 10})
                       for h in range(400)])

    def ask(thr, dl_ms=30_000):
        raw = wire.pack({
            "tp": 103, "dag": wire.enc_dag(_sel(table, thr,
                                                ts=c.tso())),
            "force_backend": None, "paging_size": 0,
            "resume_token": None, "resource_group": "default",
            "request_source": "", "deadline_ms": dl_ms})
        out = svc.handle_raw("Coprocessor", raw)
        return wire.unpack(out) if isinstance(out, bytes) else out

    ok = ask(1)
    assert not ok.get("error"), ok
    hit0 = _fp(rig).stats()["hit"]
    ok = ask(2)
    assert not ok.get("error") and _fp(rig).stats()["hit"] > hit0
    shed = ask(3, dl_ms=0)
    err = shed.get("error")
    assert err and err["kind"] in ("deadline_exceeded",
                                   "server_is_busy"), shed
    assert shed.get("trace_id") and "time_detail" in shed


def test_e2e_many_classes_and_tenants_coexist(rig):
    """More classes than any single index bucket could hold (the old
    prefix map collapsed every TableScan class into one 8-entry
    bucket) plus one class split across two resource groups (same
    const-blind class_key, distinct templates) — all must hit
    concurrently, none may mutually evict.  The columnar cache must
    hold every table's line at once (the ten tables lie in ONE region,
    ten lines of it against ``SCHEMAS_PER_REGION`` = 8 — an evicted
    line is a GENERATION change, which correctly invalidates its
    template; that lower-layer bound is not what this test measures)."""
    c, node = rig["client"], rig["node"]
    cap0 = node.copr_cache._capacity
    node.copr_cache._capacity = 32
    from tikv_tpu.copr import region_cache
    per_region0 = region_cache.SCHEMAS_PER_REGION
    region_cache.SCHEMAS_PER_REGION = 32
    tables = []
    for i in range(10):
        t = int_table(2, table_id=9700 + i)
        _load(rig, t, [(h, {"c0": h % 3, "c1": h % 30})
                       for h in range(300)])
        tables.append(t)

    def ask(t, thr, group="default"):
        return c.coprocessor(_sel(t, thr, ts=c.tso()),
                             deadline_ms=30_000, timeout=60,
                             resource_group=group)

    try:
        for t in tables:
            ask(t, 1)           # learn one class per table
        ask(tables[0], 2, group="tenant-b")     # same class, 2nd tenant
        hit0 = _fp(rig).stats()["hit"]
        for t in tables:
            ask(t, 5)
        ask(tables[0], 6, group="tenant-b")
        st = _fp(rig).stats()
        assert st["hit"] - hit0 >= 11, st   # every class + both tenants
        assert st["classes"] >= 11, st
    finally:
        node.copr_cache._capacity = cap0
        region_cache.SCHEMAS_PER_REGION = per_region0


# ------------------------------------------- back-to-back dispatcher


def test_pipeline_close_feeds_drained_device():
    """With the persistent dispatcher on, a group parked behind an
    in-flight dispatch closes the moment the device runs dry instead
    of waiting out its (here: very long) window."""
    from tests.test_coalescer import (      # reuse the in-process rig
        make_endpoint,
        make_snapshot,
        sel_dag,
    )
    from tikv_tpu.device.runner import DeviceRunner
    import jax
    from tikv_tpu.parallel import make_mesh
    runner = DeviceRunner(mesh=make_mesh(jax.devices()[:1]),
                          chunk_rows=1 << 12)
    table, snap = make_snapshot(seed=21)
    ep, coal = make_endpoint(runner, snap, window_ms=30_000.0,
                             idle_bypass=True)
    assert coal.pipeline
    try:
        from tikv_tpu.copr.endpoint import CopRequest, REQ_TYPE_DAG
        runner.handle_request(sel_dag(table, 5), snap)      # warm
        out = []
        errs = []

        def one(thr):
            try:
                out.append(ep.handle(
                    CopRequest(REQ_TYPE_DAG, sel_dag(table, thr))))
            except Exception as e:      # noqa: BLE001
                errs.append(e)

        # burst: the first idle-bypasses; stragglers park behind the
        # in-flight dispatch and MUST be fed by the pipeline close
        # (30s window — a timer close would hang the join)
        ts = [threading.Thread(target=one, args=(t,))
              for t in (6, 7, 8, 9)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=20.0)
        assert not any(t.is_alive() for t in ts), \
            "pipeline close never fed the drained device"
        assert time.perf_counter() - t0 < 20.0
        assert not errs, errs
        assert len(out) == 4
        st = coal.stats()
        assert st["closes"].get("pipeline", 0) >= 1 or \
            st["closes"].get("idle", 0) >= 2, st
    finally:
        ep.close()


# ------------------------------------------------- pinned D2H staging


def test_pinned_stager_disabled_on_cpu_default():
    """The CPU backend cannot run the host-placement program (JAX
    0.9.0: no annotate_device_placement there): the stager probes
    once, disables itself, and readback is byte-identical."""
    import jax.numpy as jnp

    from tikv_tpu.device.request import _PinnedStager
    st = _PinnedStager()            # default pinned_host
    x = jnp.arange(512, dtype=jnp.int32)
    tree = st.stage({"x": x})
    assert st.enabled is False
    assert tree["x"] is x


def test_pinned_stager_mechanics_on_host_space():
    """The staging mechanics — jit identity with host-space
    out_shardings, per-(shape,dtype) registration, stats — exercised
    on CPU via its ``unpinned_host`` memory space; fetched bytes are
    identical to the direct readback."""
    import jax.numpy as jnp

    from tikv_tpu.device.request import _PinnedStager
    st = _PinnedStager(memory_kind="unpinned_host")
    x = jnp.arange(1024, dtype=jnp.int64) * 3
    y = jnp.linspace(0.0, 1.0, 256)
    tree = st.stage({"x": x, "y": y})
    if st.enabled:      # jax version exposes the memories API on CPU
        assert st.staged == 2 and st.classes == 2
        assert st.staged_bytes == x.nbytes + y.nbytes
        np.testing.assert_array_equal(np.asarray(tree["x"]),
                                      np.asarray(x))
        np.testing.assert_array_equal(np.asarray(tree["y"]),
                                      np.asarray(y))
        # repeat shapes reuse the registered program: no new class
        st.stage({"x": x + 1, "y": y})
        assert st.classes == 2
    else:               # pragma: no cover - older jax
        assert tree["x"] is x


@pytest.mark.parametrize("memory_kind", ["pinned_host", "unpinned_host"])
def test_a_leaf_past_the_stagers_limit_is_fetched_from_where_it_lies(
        memory_kind):
    """The landing buffers are for the KBs of an aggregation's states:
    a leaf past ``MAX_BYTES`` (a 16,384-slot grid's accumulator is 786 KB
    a lane) passes through untouched, registers no program and is not
    counted, beside a small leaf of the same tree that is staged where
    the backend can."""
    import jax.numpy as jnp

    from tikv_tpu.device.request import _PinnedStager
    st = _PinnedStager(memory_kind=memory_kind)
    assert st.MAX_BYTES == 1 << 19
    at = jnp.zeros((2, 8, st.MAX_BYTES // 64), jnp.int32)
    over = jnp.zeros((2, 512, 192), jnp.int32)      # Q15's, one lane
    assert at.nbytes == st.MAX_BYTES < over.nbytes == 786_432
    assert st._fn_for(over) is None and st.classes == 0
    tree = st.stage({"over": over, "at": at})
    assert tree["over"] is over
    assert st.staged == (1 if st.enabled else 0)
    assert st.staged_bytes == (at.nbytes if st.enabled else 0)
    np.testing.assert_array_equal(np.asarray(tree["at"]), np.asarray(at))


# -------------------------------------------- decode / plan tiers


def test_e2e_fastpath_indexscan_decode_tier(rig):
    """IndexScan classes learn a DECODE-tier template: a repeat skips
    ``wire.unpack`` + ``dec_dag`` but replays the FULL serving
    ceremony, so parity holds against a forced full-decode control
    and fresh writes are visible without any invalidation (nothing
    snapshot-bound is cached)."""
    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.testing.fixture import index_entries
    c = rig["client"]
    table = int_table(1, table_id=9611)
    muts = []
    for h in range(800):
        row = {"c0": (h * 7) % 300 - 150}
        key, value = encode_table_row(table, h, row)
        muts.append(("put", key, value))
        muts.extend(("put", k, v) for k, v in index_entries(
            table, h, row))
    c.txn_write(muts)

    def ask(thr):
        s = DagSelect.from_index(table, "c0", with_handle=True)
        dag = s.where(s.col("c0") > thr).build(start_ts=c.tso())
        return c.coprocessor(dag, deadline_ms=30_000, timeout=60)

    ask(0)          # learn (host route → decode tier)
    base = _fp(rig).stats()
    assert base["tiers"].get("decode", 0) >= 1, base
    for thr in (-100, -3, 57, 120):
        fast = ask(thr)
        failpoint.cfg("copr::fastpath", "return(miss)")
        try:
            slow = ask(thr)
        finally:
            failpoint.remove("copr::fastpath")
        assert fast["rows"] == slow["rows"], thr
        assert len(fast["rows"]) > 0
    st = _fp(rig).stats()
    assert st["hit"] - base["hit"] >= 4, (base, st)


def test_e2e_fastpath_plan_tier(rig):
    """Plan-IR classes learn a PLAN-tier template: one decoded
    PlanRequest is cached per wire shape, repeats re-stamp only the
    TSO — parity against the full decode path, and a CHANGED plan
    constant is a structural miss (constants are class identity),
    never a mis-extraction."""
    from tikv_tpu.codec.keys import table_record_range
    from tikv_tpu.copr import plan_ir as pir
    from tikv_tpu.copr.dag import TableScanDesc
    from tikv_tpu.datatype import EvalType
    from tikv_tpu.executors.ranges import KeyRange
    from tikv_tpu.expr import Expr
    c = rig["client"]
    table = int_table(2, table_id=9612)
    rows = [(h, {"c0": h % 97, "c1": (h * 31) % 500 - 250})
            for h in range(1200)]
    _load(rig, table, rows)
    start, end = table_record_range(table.table_id)
    scan = pir.ScanNode(
        TableScanDesc(table.table_id,
                      tuple(table.column_info(col.name)
                            for col in table.columns)),
        (KeyRange(start, end),))

    def plan(thr):
        return pir.PlanRequest(pir.SelectNode(scan, (
            Expr.column(2, EvalType.INT) >
            Expr.const(thr, EvalType.INT),)), start_ts=c.tso())

    def ask(thr):
        return c.coprocessor_plan(plan(thr), deadline_ms=30_000,
                                  timeout=60)

    ask(40)         # learn the thr=40 shape
    base = _fp(rig).stats()
    assert base["tiers"].get("plan", 0) >= 1, base
    # repeats of the SAME shape (only the TSO rotates) hit
    for _ in range(3):
        fast = ask(40)
        failpoint.cfg("copr::fastpath", "return(miss)")
        try:
            slow = ask(40)
        finally:
            failpoint.remove("copr::fastpath")
        assert fast["rows"] == slow["rows"]
        assert len(fast["rows"]) > 0
    st = _fp(rig).stats()
    assert st["hit"] - base["hit"] >= 3, (base, st)
    # a different constant is a DIFFERENT class: first ask misses
    # (learns a sibling), answers stay correct
    other = ask(-10)
    assert len(other["rows"]) > len(fast["rows"])
    st2 = _fp(rig).stats()
    assert st2["tiers"].get("plan", 0) >= 2, st2
