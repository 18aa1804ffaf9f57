"""A Coprocessor reply as a chunk (tipb's ``EncodeType::TypeChunk``).

A request whose DAG says ``encode_type = "chunk"`` is answered with its
result's columns as buffers (server/wire.py ``enc_chunk``: int64 /
uint64 / float64 little-endian, a DECIMAL as its scaled int64 plane and
its scale, the validity only where a NULL is), on both serving legs
through ONE function (``enc_cop_body``); the client wraps the buffers
(``dec_chunk``) and ``chunk_rows`` turns them into the rows a ``rows``
reply would have carried.  Held here: the round trip for every plane
kind; that what a chunk cannot carry leaves as rows; that a request which
does not ask gets the bytes it always got; that the fast leg's hit and
the slow leg answer the same chunk; that the counters on ``/health``
count each reply once; and that paged and streamed replies are what they
were."""

import dataclasses
import types
from decimal import Decimal

import numpy as np
import pytest

from tikv_tpu.datatype import Column, EvalType, FieldType
from tikv_tpu.datatype.column import ColumnBatch
from tikv_tpu.executors.runner import SelectResult
from tikv_tpu.server import fastpath, wire
from tikv_tpu.testing.dag import DagSelect
from tikv_tpu.testing.fixture import int_table
from tikv_tpu.utils import failpoint, metrics
from tikv_tpu.utils import trace as trace_mod

from test_fastpath import _load, rig  # noqa: F401 (the served gRPC stack)

ENV = {"backend": "device", "elapsed_ns": 1 << 33, "is_drained": True,
       "resume_token": None,
       "exec_summaries": [{"rows": 9, "iters": 1, "time_ns": 7}],
       "trace_id": "t"}
ON = np.ones(4, np.bool_)


def col(values, et=EvalType.INT, validity=None, frac=None):
    values = np.asarray(values)
    return Column(et, values, np.ones(len(values), np.bool_)
                  if validity is None else np.asarray(validity, np.bool_),
                  frac)


def dec(scaled, frac, validity=None):
    return col(np.array(scaled, np.int64), EvalType.DECIMAL, validity, frac)


def batch_of(*cols) -> ColumnBatch:
    return ColumnBatch([FieldType.long()] * len(cols), list(cols))


CASES = {
    "int64": [col(np.array([-(1 << 63), -1, 0, (1 << 63) - 1], np.int64))],
    "uint64": [col(np.array([0, 1, 1 << 63, (1 << 64) - 1], np.uint64))],
    "float64": [col(np.array([0.0, -0.0, 1 / 3, -2.5e300], np.float64),
                    EvalType.REAL)],
    "decimal_negative_zero_scale_4": [dec([-12345, 0, 5, 10 ** 17], 4)],
    "decimal_scale_0": [dec([-7, 0, 7, 1 << 62], 0)],
    "decimal_scale_6": [dec([-1, 0, 1_000_000, -999_999_999_999], 6)],
    "a_null_in_each": [
        col(np.array([5, 0, 7, 8], np.int64), validity=[1, 0, 1, 1]),
        dec([100, 250, 0, -3], 2, validity=[1, 1, 0, 1]),
        col(np.array([1.5, 0.0, 2.5, 0.0], np.float64), EvalType.REAL,
            validity=[1, 1, 1, 0])],
    "q15_shaped": [dec([439568019227, 1, 99, 0], 4),
                   col(np.array([1, 77, 9999, 10_000], np.int64))],
    "no_row": [dec([], 4), col(np.array([], np.int64))],
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_chunk_round_trips_to_the_rows_it_stands_for(name):
    batch = batch_of(*CASES[name])
    chunk = wire.enc_chunk(batch)
    assert chunk["n"] == batch.num_rows == len(chunk["cols"][0]["v"]) // 8
    # over the wire: buffers in, arrays out, no value a cell
    back = wire.unpack(wire.pack({"chunk": chunk}))["chunk"]
    assert all(type(c["v"]) is bytes for c in back["cols"])
    wire.dec_chunk(back)
    for c, src in zip(back["cols"], batch.columns):
        assert isinstance(c["v"], np.ndarray) and not c["v"].flags.writeable
        assert c["v"].dtype == src.values.dtype
        assert np.array_equal(c["v"], src.values, equal_nan=True)
        assert c.get("frac") == src.frac
        # the validity rides only where a NULL is
        assert ("ok" in c) == (not src.validity.all())
    rows = wire.chunk_rows(back)
    want = [list(r) for r in batch.rows()]
    assert repr(rows) == repr(want)     # (repr: -0.0, and Decimal scales)
    for row in rows:
        for v, src in zip(row, batch.columns):
            if v is not None and src.frac is not None:
                assert isinstance(v, Decimal) and \
                    v.as_tuple().exponent == -src.frac


def objects(values, et):
    out = np.empty(len(values), dtype=object)
    out[:] = values
    return col(out, et)


REFUSED = {
    "bytes_keys": [col(np.array([1, 2], np.int64)),
                   objects([b"A", b"N"], EvalType.BYTES)],
    "decimal_objects": [objects([Decimal("1.50"), Decimal("-2.25")],
                                EvalType.DECIMAL)],
    "a_narrow_plane": [col(np.array([1, 2], np.int32))],
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_what_a_chunk_cannot_carry_leaves_as_rows(name):
    """An object plane (BYTES keys as Q1's, a DECIMAL past a scaled
    int64's 18 digits, in the host pipeline's form) or another dtype:
    rows, whatever was asked, counted as rows."""
    batch = batch_of(*REFUSED[name])
    assert wire.enc_chunk(batch) is None
    result = SelectResult(batch, [])
    rows0 = metrics.COPR_REPLY_COUNTER.labels("rows").value
    chunk0 = metrics.COPR_REPLY_COUNTER.labels("chunk").value
    assert wire.enc_cop_body(result, "chunk") is None
    assert metrics.COPR_REPLY_COUNTER.labels("rows").value == rows0 + 1
    assert metrics.COPR_REPLY_COUNTER.labels("chunk").value == chunk0
    if name != "a_narrow_plane":
        got = fastpath.encode_response(ENV, result, None, "chunk")
        assert got == fastpath.encode_response_python(ENV, result)
        assert "rows" in wire.unpack(got)


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_request_that_does_not_ask_gets_rows(name):
    """``encode_type`` is the request's: without it the reply is the
    rows it always was, a scaled DECIMAL plane as ``Decimal``s through
    the Python chain, byte for byte what the slow leg's ``enc_rows``
    packs."""
    batch = batch_of(*CASES[name])
    result = SelectResult(batch, [])
    assert wire.enc_cop_body(result, "rows") is None
    got = fastpath.encode_response(ENV, result)
    want = wire.pack({"rows": wire.enc_rows(batch.rows()), **ENV})
    assert got == want
    if any(c.frac is not None for c in batch.columns):
        # the native encode declines a scaled plane: its row form is a
        # Decimal, not the plane's integer
        assert fastpath._rows_native(batch) is None


@pytest.mark.parametrize("name", sorted(CASES))
def test_both_legs_pack_the_same_chunk_reply(name):
    """The fast leg's ``encode_response`` and the slow leg's dict through
    ``wire.pack`` (``service._enc_cop_resp`` + the server's serializer):
    one function makes the chunk, and the bytes are equal."""
    result = SelectResult(batch_of(*CASES[name]), [])
    agg = trace_mod.AGGREGATE.snapshot()["chunk_encode"]["count"]
    fast = fastpath.encode_response(ENV, result, None, "chunk")
    slow = wire.pack({**wire.enc_cop_body(result, "chunk"), **ENV})
    assert fast == slow
    reply = wire.unpack(fast)
    assert list(reply)[0] == "chunk" and "rows" not in reply
    assert trace_mod.AGGREGATE.snapshot()["chunk_encode"]["count"] == agg + 2


def test_the_default_is_rows_and_the_field_is_the_requests():
    from tikv_tpu.copr.dag import DAGRequest
    from tikv_tpu.copr.endpoint import CopResponse
    from tikv_tpu.copr.plan_ir import PlanRequest
    assert DAGRequest((), ()).encode_type == "rows"
    assert PlanRequest(None).encode_type == "rows"
    assert CopResponse(None).encode_type == "rows"
    table = int_table(2, table_id=9650)
    dag = DagSelect.from_table(table, ["id", "c0"]).build()
    assert dag.encode_type == "rows"
    asked = dataclasses.replace(dag, encode_type="chunk")
    assert wire.dec_dag(wire.enc_dag(asked)).encode_type == "chunk"
    # no key of a plan reads it: one kernel, one class, either way
    assert asked.plan_key() == dag.plan_key()
    assert asked.class_key() == dag.class_key()


# ------------------------------------------------- through the served stack


def _agg(table, ts, thr=0, chunk=True):
    s = DagSelect.from_table(table, ["id", "c0", "c1"])
    dag = s.where(s.col("c1") > thr).aggregate(
        [s.col("c0")], [("count_star", None), ("sum", s.col("c1"))]
    ).build(start_ts=ts)
    return dataclasses.replace(dag, encode_type="chunk") if chunk else dag


@pytest.fixture(scope="module")
def loaded(rig):
    table = int_table(2, table_id=9651)
    rng = np.random.default_rng(9651)
    _load(rig, table, [(h, {"c0": int(rng.integers(0, 40)),
                            "c1": int(rng.integers(-500, 500))})
                       for h in range(2000)])
    return table


def _replies(rig) -> dict:
    import json
    import urllib.request
    srv = rig["srv"]
    if getattr(srv, "status_server", None) is None:
        return {k: int(v) for k, v in (
            ("rows", metrics.COPR_REPLY_COUNTER.labels("rows").value),
            ("chunk", metrics.COPR_REPLY_COUNTER.labels("chunk").value),
            ("chunk_rows_sum", metrics.COPR_CHUNK_ROWS.value),
            ("chunk_bytes_sum", metrics.COPR_CHUNK_BYTES.value))}
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.status_server.port}/health") as r:
        return json.loads(r.read())["coprocessor"]["replies"]


def test_e2e_the_hit_and_the_slow_leg_answer_one_chunk(rig, loaded):
    """Rotating constants: the first request (slow leg) learns the
    class, the next ones hit; each hit's chunk equals the slow leg's of
    the same request (``copr::fastpath`` forced to miss) buffer for
    buffer, and, turned into rows, the answer the same plan gets when it
    asks for rows."""
    c = rig["client"]
    first = c.coprocessor(_agg(loaded, c.tso(), 1), timeout=60)
    assert first["backend"] == "device" and "rows" not in first
    before = _replies(rig)
    hits = 0
    for thr in (-200, 0, 137, 499):
        ts = c.tso()
        fast = c.coprocessor(_agg(loaded, ts, thr), timeout=60)
        hits += fast["time_detail"]["labels"].get("fastpath") == "hit"
        failpoint.cfg("copr::fastpath", "return(miss)")
        try:
            slow = c.coprocessor(_agg(loaded, ts, thr), timeout=60)
        finally:
            failpoint.remove("copr::fastpath")
        assert slow["time_detail"]["labels"].get("fastpath") != "hit"
        assert "rows" not in fast and "rows" not in slow
        assert fast["chunk"]["n"] == slow["chunk"]["n"]
        for a, b in zip(fast["chunk"]["cols"], slow["chunk"]["cols"]):
            assert a["t"] == b["t"] == "i8" and "frac" not in a
            assert a["v"].tobytes() == b["v"].tobytes()
            assert ("ok" in a) == ("ok" in b)
        asked_rows = c.coprocessor(_agg(loaded, ts, thr, chunk=False),
                                   timeout=60)
        assert "chunk" not in asked_rows
        assert sorted(wire.chunk_rows(fast["chunk"])) == \
            sorted(asked_rows["rows"])
    assert hits == 4
    after = _replies(rig)
    assert after["chunk"] - before["chunk"] == 8
    assert after["rows"] - before["rows"] == 4
    assert after["chunk_rows_sum"] > before["chunk_rows_sum"]
    assert after["chunk_bytes_sum"] - before["chunk_bytes_sum"] >= \
        8 * 3 * (after["chunk_rows_sum"] - before["chunk_rows_sum"])


def test_e2e_the_host_pipeline_answers_a_chunk_too_and_bytes_as_rows(
        rig, loaded):
    """The reply's form follows the result's planes, not who made them:
    the host pipeline's int columns leave as a chunk where one is asked;
    a result with a BYTES column leaves as rows."""
    from tikv_tpu.testing.fixture import product_table
    c = rig["client"]
    ts = c.tso()
    host = c.coprocessor(_agg(loaded, ts), force_backend="host", timeout=60)
    dev = c.coprocessor(_agg(loaded, ts), force_backend="device", timeout=60)
    assert host["backend"] == "host" and dev["backend"] == "device"
    assert sorted(wire.chunk_rows(host["chunk"])) == \
        sorted(wire.chunk_rows(dev["chunk"]))
    table = product_table()
    _load(rig, table, [(1, {"name": b"a", "count": 10}),
                       (2, {"name": b"b", "count": 20})])
    s = DagSelect.from_table(table)
    named = dataclasses.replace(s.build(start_ts=c.tso()),
                                encode_type="chunk")
    got = c.coprocessor(named, timeout=60)
    assert "chunk" not in got and len(got["rows"]) == 2


def test_e2e_paged_and_streamed_replies_are_what_they_were(rig, loaded):
    """``coprocessor_paged`` and ``coprocessor_stream`` with a DAG as
    every caller builds it: pages of rows; the stream answers rows
    whatever is asked; a paged request that asks gets its pages as
    chunks of the same rows."""
    c = rig["client"]
    s = DagSelect.from_table(loaded, ["id", "c0", "c1"])
    dag = s.build(start_ts=c.tso())
    whole = c.coprocessor(dag, force_backend="host", timeout=60)["rows"]
    assert len(whole) == 2000
    pages = list(c.coprocessor_paged(dag, paging_size=300))
    assert len(pages) > 1 and all("chunk" not in p for p in pages)
    assert [r for p in pages for r in p["rows"]] == whole
    asked = dataclasses.replace(dag, encode_type="chunk")
    for d in (dag, asked):
        msgs = list(c.coprocessor_stream(d, paging_size=300))
        assert all("chunk" not in m for m in msgs)
        assert [r for m in msgs for r in m["rows"]] == whole
    chunked = list(c.coprocessor_paged(asked, paging_size=300))
    assert len(chunked) == len(pages)
    assert all("rows" not in p for p in chunked)
    assert [r for p in chunked for r in wire.chunk_rows(p["chunk"])] == whole
    assert [p["resume_token"] for p in chunked] == \
        [p["resume_token"] for p in pages]
