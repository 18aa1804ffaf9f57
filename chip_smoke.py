#!/usr/bin/env python3
"""chip_smoke.py — the served coprocessor path, end to end, on the chip.

The quickest proof that the system still starts on a TPU and that the
device path — not one of its fallbacks — is what answers.  Run from the
root of a checkout:

    python3 chip_smoke.py                  # needs a TPU; exit 0 = every check held
    python3 chip_smoke.py --allow-cpu --rows 65536     # CPU dry run, labelled so

Phase A — the served path at BASELINE config 6's size.  The README's
entry points (``python -m tikv_tpu.server pd`` / ``tikv --with-device``)
run as child processes; 10,485,760 seeded rows of ``int_table(2)`` load
through ImportSST; hash-agg, simple agg, selection, TopN and a plan-IR
join are answered over gRPC at fresh timestamps and compared with plain
numpy; one acknowledged ``txn_write`` must be in the next answer via the
delta/patch path.  Every response is held to ``backend=device``, no
``degraded`` label, no ``host_exec`` span, and the compile class its plan
is meant to take on a TPU.  The table sits in ONE region (a fan-out over
several is ROADMAP W7, done: the benchmark's regions cells and the
pre-split table of Phase A's mux check; reads under a refresh stream are
the cell q1-refresh-lineitem-sf1-closed4).

Phase B — the north-star shape at full size: 104,857,600 rows, GROUP BY
1024 groups + COUNT/SUM through ``DeviceRunner().handle_request``
against ``np.bincount``.

On a four-chip host Phase A runs on the whole 2x2 mesh (per-device feed
residency asserted) and adds a placement leg (four small tables spread
over slices).

One process holds the chip at a time: this parent never imports JAX (it
asserts so before exiting) and runs its children one after another.
Any failed check, child exit code or exception exits non-zero naming
the check.  A run that held every check on the chip ends its stdout with
two JSON lines: the summary (versions, rows, seconds, compile classes,
compile-cache counts — SMOKE READINGS of one run, compile included where
labelled, not benchmark results), then the result line
``{"ok": true, "device": {"platform", "kind", "count"}}`` with the device
as JAX reports it.  A dry run prints the summary (``"dry_run": true``)
and no result line; a failed run prints neither.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
from importlib import metadata

import numpy as np

ROWS_A = 10 * (1 << 20)         # BASELINE config 6
ROWS_B = 100 * (1 << 20)        # BASELINE config 4
GROUPS = 1024
BUILD_ROWS = 1024               # join build side
PLACEMENT_ROWS = 1 << 18        # per table, placement leg (4 chips)
TABLE_ID = 9900
MUX_ROWS = 1 << 19              # the fan-out's table: two regions of half
DEFAULT_ROW_THRESHOLD = 131072  # etc/config-template.toml
LOAD_CHUNK = 1 << 20
SEL_FLOOR = 960                 # c1 >= 960: 2% of [-1000, 1000)
JOIN_FLOOR = 800                # c1 > 800: ~10% of the probe side
TOPN_LIMIT = 1000

# The compile class each plan is meant to take on a TPU
# (device_dispatch span attr == flight-recorder entry).  The hash-agg
# and the simple agg are the fused Pallas kernel, never its XLA
# stand-ins (hash_twolevel / hash_scatter / simple).
CLASS_PALLAS = {"pallas_hash"}
CLASS_SELECTION = {"scan_sel_mask", "scan_sel_index", "scan_sel_compact"}
CLASS_TOPN = {"topn"}
CLASS_JOIN = {"join_build", "join_probe"}

FORBIDDEN_LOG_LINES = ("pallas hash kernel disabled", "degrading to host")

TOML = """\
# chip_smoke.py — cut from etc/config-template.toml
[server]
addr = "127.0.0.1:{kv_port}"
status-addr = "127.0.0.1:{status_port}"

[raftstore]
# one region holds every table (the bench rigs' setting): this run
# proves the request path, not the split machinery
region-split-size-mb = 1048576
region-max-size-mb = 1048576

[coprocessor]
device-row-threshold = {threshold}
{extra}
"""


class SmokeFailure(Exception):
    """A named check did not hold."""


T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


class Checks:
    """Every assertion of the run, by name.  A check that the dry run
    cannot make is recorded as skipped — never as passed."""

    def __init__(self, dry_run: bool):
        self.dry_run = dry_run
        self.passed: list = []
        self.skipped: list = []

    def require(self, name: str, ok: bool, detail="") -> None:
        """``detail`` may be a callable, built only on failure."""
        if not ok:
            raise SmokeFailure(
                f"{name}: {detail() if callable(detail) else detail}")
        self.passed.append(name)

    def on_chip(self, name: str, ok: bool, detail="") -> None:
        """A platform / kernel-class assertion: only a TPU run can make
        it."""
        if self.dry_run:
            self.skipped.append(name)
        else:
            self.require(name, ok, detail)


# ------------------------------------------------------------ children


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One child process with its stdout/stderr in files."""

    def __init__(self, name: str, argv: list, workdir: str):
        self.name = name
        self.out_path = os.path.join(workdir, f"{name}.out")
        self.err_path = os.path.join(workdir, f"{name}.err")
        self._out = open(self.out_path, "wb")
        self._err = open(self.err_path, "wb")
        self.proc = subprocess.Popen(
            argv, stdout=self._out, stderr=self._err,
            cwd=os.path.dirname(os.path.abspath(__file__)))

    def read(self, which: str = "out") -> str:
        path = self.out_path if which == "out" else self.err_path
        with open(path, "rb") as f:
            return f.read().decode(errors="replace")

    def alive(self) -> bool:
        return self.proc.poll() is None

    def wait(self, timeout: float) -> int:
        """Wait for the child's own exit; returns the exit code."""
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            raise SmokeFailure(
                f"{self.name}: did not exit within {timeout}s")
        finally:
            self._close()

    def terminate(self, timeout: float = 60.0) -> int:
        """SIGTERM → wait; returns the exit code."""
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
        return self.wait(timeout)

    def kill(self) -> None:
        if self.alive():
            self.proc.kill()
            self.proc.wait()
        self._close()

    def _close(self) -> None:
        self._out.close()
        self._err.close()

    def tail(self, n: int = 30) -> str:
        return "\n".join(self.read("err").splitlines()[-n:])


def wait_for(what: str, pred, child: Child, timeout: float):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not child.alive():
            raise SmokeFailure(
                f"{what}: {child.name} exited rc={child.proc.returncode}"
                f"\n{child.tail()}")
        got = pred()
        if got:
            return got
        time.sleep(0.2)
    raise SmokeFailure(f"{what}: not within {timeout}s\n{child.tail()}")


def listening(port: int) -> bool:
    try:
        socket.create_connection(("127.0.0.1", port), 0.2).close()
        return True
    except OSError:
        return False


def http_json(port: int, path: str) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return json.loads(r.read())


def one_probe_no_walk(checks: Checks, name: str, fp0: dict,
                      fp1: dict) -> dict:
    """Over a window of warm reads, ``/health`` ``fastpath`` before and
    after: every request a hit whose class was found in one ``match``
    (``find.probes == finds``) and whose DAG arrived with its keys
    (``keys.walked`` rose by 0).  → what the counters rose by."""
    d = {k: fp1[a][b] - fp0[a][b] for k, a, b in (
        ("finds", "find", "finds"), ("probes", "find", "probes"),
        ("carried", "keys", "carried"), ("walked", "keys", "walked"))}
    d["hit"] = fp1["hit"] - fp0["hit"]
    checks.require(
        f"{name}: found in one probe, no key walked",
        d["hit"] > 0 and d["finds"] == d["probes"] == d["carried"] ==
        d["hit"] and d["walked"] == 0, d)
    return d


# ------------------------------------------------------------ the data


def table_data(seed: int, stream: int, n: int):
    """(c0, c1) of one seeded ``int_table(2)``: c0 in [0, GROUPS),
    c1 in [-1000, 1000); handles are 0..n-1."""
    rng = np.random.default_rng([seed, stream])
    return (rng.integers(0, GROUPS, n).astype(np.int64),
            rng.integers(-1000, 1000, n).astype(np.int64))


def build_data(seed: int):
    """Join build side: c0 a permutation of [0, BUILD_ROWS) (the join
    key), c1 the group it maps to (64 groups)."""
    rng = np.random.default_rng([seed, 99])
    return (rng.permutation(BUILD_ROWS).astype(np.int64),
            rng.integers(0, 64, BUILD_ROWS).astype(np.int64))


def ref_hash_agg(c0, c1) -> list:
    """Sorted [count, sum, key] rows, numpy only."""
    cnt = np.bincount(c0, minlength=GROUPS)
    sm = np.zeros(GROUPS, np.int64)
    np.add.at(sm, c0, c1)
    return sorted([int(cnt[g]), int(sm[g]), g]
                  for g in range(GROUPS) if cnt[g])


# TPC-H Q1's shape (benchmark/requests/tpch_q1.py) on a table of its
# seven columns: a GROUP BY over two CHAR(1) keys, DECIMAL products of
# scales 4 and 6 (the second one past int32: summed as limbs), a date
# predicate.  Days are 1..28 of June 1995..1998; the cut is 1998-06-15.
Q1_ROWS = 1 << 18
Q1_FLAGS, Q1_STATUS = (b"R", b"A", b"N"), (b"O", b"F")
Q1_CUT = (1998, 6, 15)


def q1_table():
    from tikv_tpu.datatype import FieldType, FieldTypeFlag, FieldTypeTp
    from tikv_tpu.testing.fixture import Table, TableColumn
    nn = FieldTypeFlag.NOT_NULL
    dec = FieldType(tp=FieldTypeTp.NEW_DECIMAL, flag=nn, flen=15, decimal=2)
    ch1 = FieldType(tp=FieldTypeTp.STRING, flag=nn, flen=1)
    return Table(TABLE_ID + 2, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("qty", 2, dec), TableColumn("price", 3, dec),
        TableColumn("disc", 4, dec), TableColumn("tax", 5, dec),
        TableColumn("flag", 6, ch1), TableColumn("status", 7, ch1),
        TableColumn("ship", 8, FieldType(tp=FieldTypeTp.DATE, flag=nn))))


def q1_data(seed: int, n: int) -> dict:
    rng = np.random.default_rng([seed, 7])
    qty = rng.integers(1, 51, n)
    return {"qty": qty * 100, "price": qty * rng.integers(90000, 209900, n),
            "disc": rng.integers(0, 11, n), "tax": rng.integers(0, 9, n),
            "flag": rng.integers(0, 3, n), "status": rng.integers(0, 2, n),
            "year": rng.integers(1995, 1999, n),
            "day": rng.integers(1, 29, n)}


def ref_q1(d: dict) -> list:
    """Sorted [sum_qty, sum_price, sum_disc_price, sum_charge, count,
    flag, status] rows, the sums x 10^2, 10^2, 10^4, 10^6: numpy and
    Python ints only."""
    keep = (d["year"] < Q1_CUT[0]) | ((d["year"] == Q1_CUT[0]) &
                                      (d["day"] <= Q1_CUT[2]))
    price = d["price"].astype(np.int64)
    disc_price = price * (100 - d["disc"])
    charge = disc_price * (100 + d["tax"])
    out = []
    for i, flag in enumerate(Q1_FLAGS):
        for j, status in enumerate(Q1_STATUS):
            m = keep & (d["flag"] == i) & (d["status"] == j)
            if m.any():
                out.append([int(d["qty"][m].sum()), int(price[m].sum()),
                            int(disc_price[m].sum()), int(charge[m].sum()),
                            int(m.sum()), flag, status])
    return sorted(out, key=lambda r: r[-2:])


# TPC-H Q15's shape (benchmark/requests/tpch_q15.py): GROUP BY a BIGINT
# key of 10,000 values (a grid of 16,384 slots on the one Pallas body,
# sixteen times Q1's), one DECIMAL product, a window of two dates, the
# reply asked as a chunk.  Days as ``q1_data``'s; the window is
# [1996-06-01, 1996-06-15).
Q15_SUPPLIERS = 10_000
Q15_WINDOW = ((1996, 6, 1), (1996, 6, 15))


def q15_table():
    from tikv_tpu.datatype import FieldType, FieldTypeFlag, FieldTypeTp
    from tikv_tpu.testing.fixture import Table, TableColumn
    nn = FieldTypeFlag.NOT_NULL
    dec = FieldType(tp=FieldTypeTp.NEW_DECIMAL, flag=nn, flen=15, decimal=2)
    return Table(TABLE_ID + 3, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("supp", 2, FieldType.long(not_null=True)),
        TableColumn("price", 3, dec), TableColumn("disc", 4, dec),
        TableColumn("ship", 5, FieldType(tp=FieldTypeTp.DATE, flag=nn))))


def q15_data(seed: int, n: int) -> dict:
    rng = np.random.default_rng([seed, 15])
    supp = rng.integers(1, Q15_SUPPLIERS + 1, n)
    supp[:2] = 1, Q15_SUPPLIERS
    return {"supp": supp,
            "price": rng.integers(1, 51, n) * rng.integers(90000, 209900, n),
            "disc": rng.integers(0, 11, n),
            "year": rng.integers(1995, 1999, n),
            "day": rng.integers(1, 29, n)}


def ref_q15(d: dict) -> list:
    """Sorted [revenue x 10^4, supplier] rows: numpy and Python ints."""
    keep = (d["year"] == 1996) & (d["day"] < Q15_WINDOW[1][2])
    rev = d["price"].astype(np.int64) * (100 - d["disc"])
    sums = np.zeros(Q15_SUPPLIERS + 1, np.int64)
    np.add.at(sums, d["supp"][keep], rev[keep])
    seen = np.zeros(Q15_SUPPLIERS + 1, np.bool_)
    seen[d["supp"][keep]] = True
    return sorted([int(sums[k]), int(k)] for k in np.nonzero(seen)[0])


# ---------------------------------------------------------- phase A


class ServedLeg:
    """One pd + tikv --with-device pair and the client driving it."""

    def __init__(self, args, checks: Checks, workdir: str, name: str,
                 extra_toml: str = ""):
        self.args, self.checks, self.name = args, checks, name
        self.pd_port, self.kv_port = free_port(), free_port()
        self.status_port = free_port()
        toml = os.path.join(workdir, f"{name}.toml")
        threshold = min(DEFAULT_ROW_THRESHOLD, max(64, args.rows // 4))
        with open(toml, "w") as f:
            f.write(TOML.format(kv_port=self.kv_port,
                                status_port=self.status_port,
                                threshold=threshold, extra=extra_toml))
        py = [sys.executable, "-m", "tikv_tpu.server"]
        self.pd = Child(f"{name}-pd", py + [
            "pd", "--addr", f"127.0.0.1:{self.pd_port}"], workdir)
        self.kv = None
        try:
            wait_for("pd listening", lambda: listening(self.pd_port),
                     self.pd, 60)
            self.kv = Child(f"{name}-tikv", py + [
                "tikv", "--addr", f"127.0.0.1:{self.kv_port}",
                "--pd", f"127.0.0.1:{self.pd_port}", "--with-device",
                "--config", toml,
                "--status-addr", f"127.0.0.1:{self.status_port}"],
                workdir)
            self.device = self._startup_line()
        except BaseException:
            self.kill()
            raise

    def _startup_line(self) -> dict:
        """Parse the store's ``device runner:`` start-up line and hold
        it to the platform check before anything is loaded."""
        def line():
            for ln in self.kv.read().splitlines():
                if ln.startswith("device runner: "):
                    return ln
            return None
        try:
            ln = wait_for("device runner start-up line", line, self.kv, 300)
        except SmokeFailure as e:
            raise SmokeFailure(f"platform check: store did not bring up "
                               f"a device runner — {e}")
        log(ln)
        m = re.fullmatch(r"device runner: platform=(\S+) "
                         r"device_kind='(.*)' n_devices=(\d+) mesh=(\S+) "
                         r"native_finalize=(yes|no) "
                         r"native_encode=(yes|no) "
                         r"gil_probe=(native|overshoot) mux=(\S+)",
                         ln.strip())
        if m is None:
            raise SmokeFailure(f"platform check: cannot parse {ln!r}")
        # the store says itself whether its hash-agg finalize is the one
        # native call or the numpy chain (no silent fallback)
        self.checks.require("store says native_finalize=yes", m[5] == "yes",
                            "the store's extension lacks "
                            "hash_finalize_packed")
        # ... and whether a fast-path reply's rows are the one native
        # call's or the Python chain's
        self.checks.require("store says native_encode=yes", m[6] == "yes",
                            "the store's extension lacks "
                            "encode_rows_msgpack")
        dev = {"platform": m[1], "kind": m[2], "count": int(m[3]),
               "mesh": m[4]}
        if dev["platform"] != "tpu" and not self.args.allow_cpu:
            raise SmokeFailure(
                f"platform check: the store serves on "
                f"{dev['platform']!r}, not a TPU (pass --allow-cpu for "
                f"a dry run)")
        self.checks.on_chip("platform==tpu", dev["platform"] == "tpu")
        wait_for("tikv listening",
                 lambda: listening(self.kv_port) and
                 listening(self.status_port), self.kv, 120)
        return dev

    # -- client side --

    def connect(self):
        from tikv_tpu.server import TxnClient
        self.client = TxnClient(f"127.0.0.1:{self.pd_port}")
        stores = wait_for("store registered with PD",
                          lambda: self.client.pd.stores(), self.kv, 60)
        self.store_id = stores[0].id
        return self.client

    def load(self, table, handles, c0, c1) -> float:
        """ImportSST bulk load (benchmark/tables/int_table.py's shape):
        native SST encode, chunked upload, raft ingest, import mode."""
        from tikv_tpu.codec.keys import table_record_key
        from tikv_tpu.sst_importer import fast_mvcc_table_sst
        c = self.client
        t0 = time.perf_counter()
        c.import_switch_mode(self.store_id, True)
        for s in range(0, len(handles), LOAD_CHUNK):
            hs = handles[s:s + LOAD_CHUNK]
            blob = fast_mvcc_table_sst(
                table.table_id, hs,
                [(2, c0[s:s + LOAD_CHUNK], None),
                 (3, c1[s:s + LOAD_CHUNK], None)], commit_ts=c.tso())
            c.ingest_sst(blob, table_record_key(table.table_id,
                                                int(hs[0])),
                         chunk=2 << 20, timeout=300)
        c.import_switch_mode(self.store_id, False)
        return time.perf_counter() - t0

    def load_q1(self, table, d: dict) -> None:
        """``q1_data``'s rows through the native SST encoder's DECIMAL
        and bytes column kinds (benchmark/tables/lineitem_presplit.py's
        shape)."""
        from tikv_tpu.sst_importer import bytes_column, decimal_column
        n = len(d["qty"])

        def chars(idx, texts):      # one byte a row
            return bytes_column(
                np.frombuffer(b"".join(texts), np.uint8)[idx].tobytes(),
                np.arange(n + 1, dtype=np.int64))

        cols = [(cid, decimal_column(d[name].astype(np.int64), 2), None)
                for cid, name in ((2, "qty"), (3, "price"), (4, "disc"),
                                  (5, "tax"))]
        cols += [(6, chars(d["flag"], Q1_FLAGS), None),
                 (7, chars(d["status"], Q1_STATUS), None),
                 (8, (d["year"].astype(np.int64) << 50) | (6 << 46) |
                  (d["day"].astype(np.int64) << 41), None)]
        self._ingest(table, n, cols)

    def _ingest(self, table, n: int, cols: list) -> None:
        """``cols`` (the native SST encoder's column triples) as ``n``
        rows of ``table`` at handles 0..n-1, in one ImportSST."""
        from tikv_tpu.codec.keys import table_record_key
        from tikv_tpu.sst_importer import fast_mvcc_table_sst
        c = self.client
        c.import_switch_mode(self.store_id, True)
        c.ingest_sst(fast_mvcc_table_sst(
            table.table_id, np.arange(n, dtype=np.int64), cols,
            commit_ts=c.tso()), table_record_key(table.table_id, 0),
            chunk=2 << 20, timeout=300)
        c.import_switch_mode(self.store_id, False)

    def load_q15(self, table, d: dict) -> None:
        """``q15_data``'s rows, as ``load_q1`` loads Q1's."""
        from tikv_tpu.sst_importer import decimal_column
        self._ingest(table, len(d["supp"]), [
            (2, d["supp"].astype(np.int64), None),
            (3, decimal_column(d["price"].astype(np.int64), 2), None),
            (4, decimal_column(d["disc"].astype(np.int64), 2), None),
            (5, (d["year"].astype(np.int64) << 50) | (6 << 46) |
             (d["day"].astype(np.int64) << 41), None)])

    def request(self, name: str, send, classes, backend="device",
                spans=()) -> dict:
        """Send one coprocessor request, fetch its trace, and hold the
        response to the device path.  → {"resp", "wall_s", "classes"}."""
        ck = self.checks
        t0 = time.perf_counter()
        resp = send()
        wall = time.perf_counter() - t0
        td = resp.get("time_detail", {})
        labels = td.get("labels", {})
        trace = http_json(self.status_port,
                          f"/debug/trace/{resp['trace_id']}")
        names = [s["name"] for s in trace["spans"]]
        ck.require(f"{name}: backend", resp["backend"] == backend,
                   f"{resp['backend']!r}, labels={labels}")
        ck.require(f"{name}: not degraded",
                   "degraded" not in labels and
                   "degraded" not in trace["labels"],
                   f"labels={labels} trace={trace['labels']}")
        ck.require(f"{name}: no host_exec span",
                   "host_exec" not in names, f"spans={names}")
        for want in spans:
            ck.require(f"{name}: {want} span", want in names,
                       f"spans={names}")
        got = sorted({s["attrs"]["compile_class"]
                      for s in trace["spans"]
                      if s["name"] == "device_dispatch"
                      and "compile_class" in s.get("attrs", {})})
        ck.on_chip(f"{name}: compile class",
                   bool(got) and set(got) <= classes,
                   f"{got}, want a subset of {sorted(classes)}; store "
                   f"warnings: {self.warnings()}")
        top = sorted(trace["breakdown_ms"].items(),
                     key=lambda kv: -kv[1])[:5]
        log(f"{name}: {wall * 1e3:.1f} ms classes={got} "
            f"breakdown_ms={dict(top)} labels={labels}")
        return {"resp": resp, "wall_s": wall, "classes": got,
                "labels": labels, "spans": names,
                "phases_ms": td.get("phases_ms", {})}

    def warnings(self) -> list:
        """The store's recent WARNING-level lines (why a kernel was
        refused is there, not in the response)."""
        return [ln[:600] for ln in self.kv.read("err").splitlines()
                if "disabled" in ln or "degrading" in ln or
                "failure" in ln or "Error" in ln][-6:]

    # -- teardown --

    def health_checks(self) -> dict:
        ck, name = self.checks, self.name
        health = http_json(self.status_port, "/health")
        index = http_json(self.status_port, "/debug/trace")
        mesh = health["device_mesh"]
        ck.on_chip(f"{name}: /health device_mesh.platform",
                   mesh["platform"] == "tpu", mesh)
        ck.require(f"{name}: mesh covers every device",
                   mesh["n_devices"] == self.device["count"],
                   f"{mesh} vs start-up {self.device}")
        fin = mesh["finalize"]
        ck.on_chip(f"{name}: every Pallas hash accumulator finalized by "
                   f"the native call", fin["native"] > 0 and
                   fin["numpy"] == 0, fin)
        bad = [s for s in health["device_health"]["slices"]
               if s.get("state") not in (None, "healthy")]
        ck.require(f"{name}: no quarantined slice", not bad, bad)
        fr = index["flight_recorder"]
        ck.require(f"{name}: flight-recorder faults == 0",
                   fr["faults"] == 0, fr)
        pinned = health["fastpath"]["pinned_readback"]
        log(f"{name}: fastpath.pinned_readback={pinned}")
        return {"pinned_readback": pinned,
                "flight_recorder": {k: fr[k] for k in
                                    ("launches", "first_launches",
                                     "faults")},
                "hbm": health["device_state"]["hbm"],
                "compile_cache": health["compile_cache"],
                "mesh": mesh}

    def stop(self) -> None:
        """SIGTERM both servers; both must exit 0 with a clean log."""
        ck, name = self.checks, self.name
        rc_kv = self.kv.terminate(120)
        rc_pd = self.pd.terminate(60)
        ck.require(f"{name}: tikv exit code 0", rc_kv == 0,
                   f"rc={rc_kv}\n{self.kv.tail()}")
        ck.require(f"{name}: pd exit code 0", rc_pd == 0,
                   f"rc={rc_pd}\n{self.pd.tail()}")
        logs = self.kv.read("err") + self.kv.read("out")
        for needle in FORBIDDEN_LOG_LINES:
            ck.require(f"{name}: log has no {needle!r}",
                       needle not in logs,
                       [ln for ln in logs.splitlines() if needle in ln][:3])

    def kill(self) -> None:
        for ch in (self.kv, self.pd):
            if ch is not None:
                ch.kill()


def served_leg(args, checks: Checks, workdir: str) -> dict:
    """Phase A's main leg: single chip (1x1) or the whole 2x2 mesh."""
    from tikv_tpu.codec.keys import table_record_key, table_record_range
    from tikv_tpu.copr import plan_ir as pir
    from tikv_tpu.copr.dag import (
        AggExprDesc,
        AggregationDesc,
        TableScanDesc,
    )
    from tikv_tpu.datatype import EvalType
    from tikv_tpu.executors.ranges import KeyRange
    from tikv_tpu.expr import Expr
    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.testing.fixture import encode_table_row, int_table

    n = args.rows
    leg = ServedLeg(args, checks, workdir, "served")
    try:
        c = leg.connect()
        whole_mesh = leg.device["count"] > 1
        table = int_table(2, table_id=TABLE_ID)
        build_t = int_table(2, table_id=TABLE_ID + 1)
        c0, c1 = table_data(args.seed, 0, n)
        b0, b1 = build_data(args.seed)
        handles = np.arange(n, dtype=np.int64)
        load_s = leg.load(table, handles, c0, c1)
        leg.load(build_t, np.arange(BUILD_ROWS, dtype=np.int64), b0, b1)
        # (every table before the first read: one region holds them all,
        # and an ingest under a warm line would re-upload its feed)
        q1_t = q1_table()
        qd = q1_data(args.seed, min(n, Q1_ROWS))
        leg.load_q1(q1_t, qd)
        q15_t = q15_table()
        q15d = q15_data(args.seed, min(n, Q1_ROWS))
        leg.load_q15(q15_t, q15d)
        # the fan-out's table (PR 43), the highest id: its second half
        # is a region of its own, cut BEFORE its rows arrive, so every
        # other table still sits in one region
        mux_t = int_table(2, table_id=TABLE_ID + 40)
        half = min(n, MUX_ROWS) // 2
        m0, m1 = table_data(args.seed, 40, 2 * half)
        c.split(table_record_key(mux_t.table_id, half))
        for lo in (0, half):
            leg.load(mux_t, np.arange(lo, lo + half, dtype=np.int64),
                     m0[lo:lo + half], m1[lo:lo + half])
        log(f"loaded {n} rows in {load_s:.1f}s")

        def select():
            # fresh builder per request: DagSelect mutates
            return DagSelect.from_table(table, ["id", "c0", "c1"])

        def hash_agg():
            s = select()
            dag = s.aggregate(
                [s.col("c0")],
                [("count_star", None), ("sum", s.col("c1"))]
            ).build(start_ts=c.tso())
            return c.coprocessor(dag, timeout=900)

        def same_rows(name, got, want):
            got, want = sorted(got), sorted(want)
            checks.require(f"{name}: equals numpy reference", got == want,
                           lambda: f"{len(got)} rows vs {len(want)}; "
                                   f"first {got[:2]} vs {want[:2]}")

        # -- hash-agg: one cold, then warm --
        want = ref_hash_agg(c0, c1)
        cold = leg.request("hash_agg cold", hash_agg, CLASS_PALLAS)
        same_rows("hash_agg cold", cold["resp"]["rows"], want)
        # the device MVCC resolver is single-device by design
        # (device/mvcc.py): a whole-mesh runner's cold build is the
        # native C++ rung, and says so
        want_cold = ("native", "upload") if whole_mesh else \
            ("device", "device_resolve")
        checks.require(
            "hash_agg cold: cold_build / device_feed rung",
            (cold["labels"].get("cold_build"),
             cold["labels"].get("device_feed")) == want_cold,
            f"{cold['labels']}, want cold_build/device_feed={want_cold}")
        warm = []
        fp0 = http_json(leg.status_port, "/health")["fastpath"]
        for i in range(6):
            w = leg.request(f"hash_agg warm {i}", hash_agg, CLASS_PALLAS)
            same_rows(f"hash_agg warm {i}", w["resp"]["rows"], want)
            checks.require(f"hash_agg warm {i}: device_feed=hit",
                           w["labels"].get("device_feed") == "hit",
                           w["labels"])
            warm.append(w)
        # the warm GROUP BY replies were fast-path hits over int64
        # planes: the native call made their rows, the chain none
        fp1 = http_json(leg.status_port, "/health")["fastpath"]
        enc = fp1["encode"]
        checks.require("hash_agg warm: fast-path replies encoded by the "
                       "native call", enc["native"] > 0 and
                       enc["python"] == 0, enc)
        found = {"hash_agg": one_probe_no_walk(
            checks, "hash_agg warm", fp0, fp1)}
        # and each was staged from its class's prepared record (a mesh
        # has none: its launches leave from the request's thread)
        prep = http_json(leg.status_port,
                         "/health")["device_mesh"]["prepared"]
        checks.on_chip("hash_agg warm: staged from the prepared record",
                       prep["hits"] == 0 if whole_mesh
                       else prep["hits"] >= len(warm), prep)

        # -- simple agg --
        def simple_agg():
            s = select()
            dag = s.aggregate([], [
                ("sum", s.col("c1")), ("count", s.col("c1")),
                ("avg", s.col("c1"))]).build(start_ts=c.tso())
            return c.coprocessor(dag, timeout=900)

        total = int(c1.sum())
        simple = []
        for i in range(2):
            simple.append(leg.request(f"simple_agg {i}", simple_agg,
                                      CLASS_PALLAS))
            (row,) = simple[-1]["resp"]["rows"]
            checks.require(
                f"simple_agg {i}: equals numpy reference",
                row[0] == total and row[1] == n and
                abs(row[2] - total / n) <= 1e-9 * max(1.0, abs(total / n)),
                f"{row} vs {(total, n, total / n)}")

        # -- TPC-H Q1's shape: a composite key of two CHAR(1) code
        #    planes, a 37-bit DECIMAL product summed as limbs (PR 38) --
        def q1():
            import decimal
            s = DagSelect.from_table(
                q1_t, ["qty", "price", "disc", "tax", "flag", "status",
                       "ship"])
            one = Expr.const(decimal.Decimal(1), EvalType.DECIMAL)
            disc_price = Expr.call(
                "MultiplyDecimal", s.col("price"),
                Expr.call("MinusDecimal", one, s.col("disc")))
            y, m, d = Q1_CUT
            dag = s.where(Expr.call("LeTime", s.col("ship"), Expr.const(
                (y << 50) | (m << 46) | (d << 41), EvalType.DATETIME))
            ).aggregate([s.col("flag"), s.col("status")], [
                ("sum", s.col("qty")), ("sum", s.col("price")),
                ("sum", disc_price),
                ("sum", Expr.call(
                    "MultiplyDecimal", disc_price,
                    Expr.call("PlusDecimal", one, s.col("tax")))),
                ("count_star", None)]).build(start_ts=c.tso())
            return c.coprocessor(dag, timeout=900)

        want_q1 = ref_q1(qd)
        q1s = []
        for i in range(2):      # cold (the kernel's build), then warm
            q1s.append(leg.request(f"q1 {i}", q1, CLASS_PALLAS))
            rows = q1s[-1]["resp"]["rows"]
            got_q1 = sorted(
                ([int(v.scaleb(e)) for v, e in zip(r[:4], (2, 2, 4, 6))] +
                 list(r[4:]) for r in rows), key=lambda r: r[-2:])
            checks.require(f"q1 {i}: equals numpy reference",
                           got_q1 == want_q1,
                           lambda: f"{got_q1[:1]} vs {want_q1[:1]}")
            checks.require(
                f"q1 {i}: bytes keys, DECIMALs of scales 2 2 4 6",
                all([-v.as_tuple().exponent for v in r[:4]] == [2, 2, 4, 6]
                    and isinstance(r[5], bytes) and isinstance(r[6], bytes)
                    for r in rows), rows[:1])
            if i == 0:          # the warm read's window opens here
                fp0 = http_json(leg.status_port, "/health")["fastpath"]
        found["q1"] = one_probe_no_walk(checks, "q1 warm", fp0, http_json(
            leg.status_port, "/health")["fastpath"])
        params = http_json(leg.status_port,
                           "/health")["device_mesh"]["agg_params"]
        checks.on_chip("q1: a composite key, a limb sum, code planes",
                       params["composite_key_launches"] >= 2 and
                       params["limb_sums"] >= 2 and
                       params["code_planes"] >= 2, params)

        # -- TPC-H Q15's shape: a key span over 4,096 on the one Pallas
        #    body (a grid of 16,384 slots, fewer rows a step), the reply
        #    a chunk whose DECIMAL stays its scaled plane (PR 40) --
        def q15(chunk=True, backend=None):
            import dataclasses
            import decimal
            s = DagSelect.from_table(q15_t, ["supp", "price", "disc",
                                             "ship"])

            def day(ymd):
                y, m, d = ymd
                return Expr.const((y << 50) | (m << 46) | (d << 41),
                                  EvalType.DATETIME)

            dag = s.where(
                Expr.call("GeTime", s.col("ship"), day(Q15_WINDOW[0])),
                Expr.call("LtTime", s.col("ship"), day(Q15_WINDOW[1])),
            ).aggregate([s.col("supp")], [("sum", Expr.call(
                "MultiplyDecimal", s.col("price"),
                Expr.call("MinusDecimal", Expr.const(
                    decimal.Decimal(1), EvalType.DECIMAL),
                    s.col("disc"))))]).build(start_ts=c.tso())
            if chunk:
                dag = dataclasses.replace(dag, encode_type="chunk")
            return c.coprocessor(dag, force_backend=backend, timeout=900)

        from tikv_tpu.server import wire
        want_q15 = ref_q15(q15d)
        host_q15 = sorted(q15(chunk=False, backend="host")["rows"],
                          key=lambda r: r[1])
        checks.require("q15: the host pipeline equals the numpy reference",
                       [[int(v.scaleb(4)), k] for v, k in host_q15] ==
                       sorted(want_q15, key=lambda r: r[1]) and
                       len(host_q15) > 4096, host_q15[:1])
        q15s = []
        for i in range(2):      # cold (the kernel's build), then warm
            q15s.append(leg.request(f"q15 {i}", q15, CLASS_PALLAS))
            resp = q15s[-1]["resp"]
            cols = resp.get("chunk", {}).get("cols", [{}, {}])
            checks.require(
                f"q15 {i}: the reply is a chunk, the sum an int64 plane "
                f"at scale 4",
                "rows" not in resp and [
                    (col.get("t"), col.get("frac")) for col in cols] ==
                [("i8", 4), ("i8", None)], {k: v for k, v in resp.items()
                                            if k in ("rows", "backend")})
            got_q15 = sorted(wire.chunk_rows(resp["chunk"]),
                             key=lambda r: r[1])
            checks.require(f"q15 {i}: the chunk's rows equal the host "
                           f"pipeline's", got_q15 == host_q15,
                           lambda: f"{got_q15[:1]} vs {host_q15[:1]}")
        health = http_json(leg.status_port, "/health")
        params = health["device_mesh"]["agg_params"]
        checks.on_chip("q15: two launches over a grid of 16,384 slots",
                       params["slots_sum"] >= 2 * 16384, params)
        checks.require("q15: two chunk replies counted",
                       health["coprocessor"]["replies"]["chunk"] >= 2,
                       health["coprocessor"])

        # -- selection (2%: a unary gRPC response is capped at 4 MB by
        #    the client's default, ~350k rows of this table) --
        def selection():
            s = select()
            dag = s.where(s.col("c1") >= SEL_FLOOR).build(
                start_ts=c.tso())
            return c.coprocessor(dag, timeout=900)

        hit = np.nonzero(c1 >= SEL_FLOOR)[0]
        want_sel = [[int(h), int(c0[h]), int(c1[h])] for h in hit]
        sel = []
        for i in range(3):
            sel.append(leg.request(f"selection {i}", selection,
                                   CLASS_SELECTION))
            same_rows(f"selection {i}", sel[-1]["resp"]["rows"], want_sel)

        # -- TopN LIMIT 1000 (ties break by scan position) --
        def topn():
            s = select()
            dag = s.order_by(s.col("c1"), desc=True,
                             limit=TOPN_LIMIT).build(start_ts=c.tso())
            return c.coprocessor(dag, timeout=900)

        order = np.argsort(-c1, kind="stable")[:TOPN_LIMIT]
        want_top = [[int(h), int(c0[h]), int(c1[h])] for h in order]
        top = []
        for i in range(2):
            top.append(leg.request(f"topn {i}", topn, CLASS_TOPN))
            got_top = top[-1]["resp"]["rows"]
            checks.require(f"topn {i}: equals numpy reference",
                           got_top == want_top,
                           f"{got_top[:2]} vs {want_top[:2]}")

        # -- plan-IR join: scan+select (probe) ⋈ build → host group-by --
        def scan_node(t):
            s, e = table_record_range(t.table_id)
            return pir.ScanNode(
                TableScanDesc(t.table_id, tuple(
                    t.column_info(col.name) for col in t.columns)),
                (KeyRange(s, e),))

        def join(force):
            probe = pir.SelectNode(scan_node(table), (
                Expr.column(2, EvalType.INT) >
                Expr.const(JOIN_FLOOR, EvalType.INT),))
            plan = pir.AggNode(
                pir.JoinNode(probe, scan_node(build_t), 1, 1),
                AggregationDesc(
                    (Expr.column(5, EvalType.INT),),    # build c1
                    (AggExprDesc("count_star", None),
                     AggExprDesc("sum", Expr.column(2, EvalType.INT))),
                    False))
            return c.coprocessor_plan(
                pir.PlanRequest(plan, start_ts=c.tso()),
                force_backend=force, timeout=900)

        m = c1 > JOIN_FLOOR
        group_of = np.empty(BUILD_ROWS, np.int64)
        group_of[b0] = b1                       # join key → build c1
        w = group_of[c0[m]]
        jc = np.bincount(w, minlength=64)
        js = np.zeros(64, np.int64)
        np.add.at(js, w, c1[m])
        want_join = [[int(jc[g]), int(js[g]), g]
                     for g in range(64) if jc[g]]
        # a whole-mesh runner without placement has no single-chip
        # joiner: its joins are host joins by design (plan_ir._model),
        # so there only exactness is held
        if whole_mesh:
            jn = {"wall_s": 0.0, "classes": "host join (whole mesh)"}
            t0 = time.perf_counter()
            same_rows("join (host, whole mesh)", join(None)["rows"],
                      want_join)
            jn["wall_s"] = time.perf_counter() - t0
        else:
            # unforced first — the request a user sends; if the
            # fragment router kept the join on the host (its choice is
            # a finding, not a check) the device join is forced once
            jn = leg.request("join", lambda: join(None), CLASS_JOIN,
                             backend="plan")
            same_rows("join", jn["resp"]["rows"], want_join)
            if "join_probe" not in jn["spans"]:
                jn = leg.request("join forced device",
                                 lambda: join("device"), CLASS_JOIN,
                                 backend="plan", spans=("join_probe",))
                same_rows("join forced device", jn["resp"]["rows"],
                          want_join)
        health = http_json(leg.status_port, "/health")
        routed = health["plan_ir"]["router"]["decisions"]

        # -- one acknowledged write must be in the next answer --
        key, value = encode_table_row(table, n, {"c0": 7, "c1": 123})
        c.txn_write([("put", key, value)])
        c0w = np.append(c0, 7)
        c1w = np.append(c1, 123)
        post = leg.request("hash_agg after write", hash_agg, CLASS_PALLAS)
        same_rows("hash_agg after write", post["resp"]["rows"],
                  ref_hash_agg(c0w, c1w))
        checks.require(
            "hash_agg after write: copr_cache=delta, device_feed=patch",
            post["labels"].get("copr_cache") == "delta" and
            post["labels"].get("device_feed") == "patch", post["labels"])

        # -- a fan-out's cop tasks ride the store's ONE BatchCommands
        #    stream as raw commands; coprocessor() stays a unary call --
        def mux_plan():
            s = DagSelect.from_table(mux_t, ["id", "c0", "c1"])
            return s.aggregate(
                [s.col("c0")],
                [("count_star", None), ("sum", s.col("c1"))]
            ).build(start_ts=c.tso())

        def carried() -> dict:
            h = http_json(leg.status_port, "/health")
            return dict(h["batch_commands"],
                        served=h["coprocessor"]["requests_served"])

        fan = []
        for i in range(3):
            before = carried()
            fan.append(leg.request(
                f"fanout {i}", lambda: c.coprocessor_fanout(
                    mux_plan(), timeout=900), CLASS_PALLAS))
            after = carried()
            merged: dict = {}
            for part in fan[-1]["resp"]["responses"]:
                for cnt, sm, key in part["rows"]:
                    acc = merged.setdefault(key, [0, 0])
                    acc[0] += cnt
                    acc[1] += sm
            same_rows(f"fanout {i}",
                      [[v[0], v[1], k] for k, v in merged.items()],
                      ref_hash_agg(m0, m1))
            checks.require(
                f"fanout {i}: two cop tasks, both raw commands on the mux",
                fan[-1]["resp"]["tasks"] == 2 and
                after["raw_commands"] - before["raw_commands"] ==
                after["served"] - before["served"] == 2 and
                after["unary_resends"] == 0 and
                "unary_resends" not in fan[-1]["labels"],
                f"{before} -> {after}, labels={fan[-1]['labels']}")
        checks.require("fanout warm: fast-path hit over the mux, the seven "
                       "wire phases on the reply",
                       fan[-1]["labels"].get("fastpath") == "hit" and
                       all(k in fan[-1]["phases_ms"] for k in (
                           "client_route", "client_encode", "wire_request",
                           "rpc_accept_wait", "wire_reply",
                           "client_decode")),
                       f"{fan[-1]['labels']} {fan[-1]['phases_ms']}")
        before = carried()
        lone = leg.request("left half by coprocessor()", lambda: c.coprocessor(
            dataclasses.replace(mux_plan(), ranges=(KeyRange(
                table_record_range(mux_t.table_id)[0],
                table_record_key(mux_t.table_id, half)),)), timeout=900),
            CLASS_PALLAS)
        after = carried()
        same_rows("left half by coprocessor()", lone["resp"]["rows"],
                  ref_hash_agg(m0[:half], m1[:half]))
        checks.require(
            "coprocessor() sends no command",
            after["raw_commands"] == before["raw_commands"] and
            after["commands_in"] == before["commands_in"] and
            after["served"] - before["served"] == 1,
            f"{before} -> {after}")
        log(f"mux: {after}")

        rollup = leg.health_checks()
        by_dev = rollup["hbm"]["resident_bytes_by_device"]
        checks.require(
            "feed resident on every mesh device",
            len(by_dev) == leg.device["count"] and
            all(b > 0 for b in by_dev.values()), by_dev)
        leg.stop()
    except BaseException:
        leg.kill()
        raise
    walls = sorted(w["wall_s"] for w in warm)

    def family(reqs) -> dict:
        """Compile classes seen + first (cold: compile, feed upload)
        and last (warm) wall seconds of one query family."""
        return {"classes": sorted({k for r in reqs for k in r["classes"]}),
                "first_s": round(reqs[0]["wall_s"], 3),
                "last_s": round(reqs[-1]["wall_s"], 3)}

    return {
        "leg": "whole_mesh" if whole_mesh else "single_chip",
        "device": leg.device, "rows": n, "load_s": round(load_s, 2),
        "cold_s": round(cold["wall_s"], 3),
        "cold_labels": cold["labels"],
        "cold_phases_ms": cold["phases_ms"],
        "warm_p50_s": walls[len(walls) // 2], "warm_n": len(walls),
        "warm_phases_ms": warm[-1]["phases_ms"],
        "fastpath_warm": found,
        "queries": {
            "hash_agg": family([cold] + warm),
            "simple_agg": family(simple),
            "q1": family(q1s),
            "q15": family(q15s),
            "selection": {**family(sel), "rows": len(want_sel),
                          "routing": sel[-1]["labels"].get("routing")},
            "topn": family(top),
            "join": {"classes": jn["classes"],
                     "first_s": round(jn["wall_s"], 3), "router": routed},
            "after_write": {**family([post]),
                            "device_feed":
                            post["labels"].get("device_feed")},
            "fanout_mux": family(fan)},
        "batch_commands": {k: v for k, v in after.items() if k != "served"},
        "resident_bytes_by_device": by_dev,
        "pinned_readback": rollup["pinned_readback"],
        "flight_recorder": rollup["flight_recorder"],
        "compile_cache": rollup["compile_cache"],
    }


def placement_leg(args, checks: Checks, workdir: str) -> dict:
    """Four chips only: ``device-placement = true`` and four small
    tables — anchors must spread over more than one slice, answers
    exact, each slice's kernel the fused one."""
    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.testing.fixture import int_table

    n = min(PLACEMENT_ROWS, args.rows)
    leg = ServedLeg(args, checks, workdir, "placement",
                    extra_toml="device-placement = true")
    try:
        c = leg.connect()
        handles = np.arange(n, dtype=np.int64)
        tables = []
        for i in range(4):
            t = int_table(2, table_id=TABLE_ID + 10 + i)
            c0, c1 = table_data(args.seed, 10 + i, n)
            leg.load(t, handles, c0, c1)
            tables.append((t, c0, c1))
        for rnd in range(2):        # cold, then warm
            for i, (t, c0, c1) in enumerate(tables):
                def agg(t=t):
                    s = DagSelect.from_table(t, ["id", "c0", "c1"])
                    dag = s.aggregate(
                        [s.col("c0")],
                        [("count_star", None), ("sum", s.col("c1"))]
                    ).build(start_ts=c.tso())
                    return c.coprocessor(dag, timeout=900)
                r = leg.request(f"placement table {i} round {rnd}", agg,
                                CLASS_PALLAS)
                checks.require(
                    f"placement table {i} round {rnd}: equals numpy "
                    f"reference",
                    sorted(r["resp"]["rows"]) == ref_hash_agg(c0, c1))
        rollup = leg.health_checks()
        placed = [s["placed_anchors"]
                  for s in rollup["mesh"]["placement"]["slices"]]
        checks.require("placement: anchors on more than one slice",
                       sum(1 for k in placed if k > 0) > 1, placed)
        by_dev = rollup["hbm"]["resident_bytes_by_device"]
        checks.require("placement: feeds resident on more than one device",
                       sum(1 for b in by_dev.values() if b > 0) > 1, by_dev)
        leg.stop()
    except BaseException:
        leg.kill()
        raise
    return {"leg": "placement", "rows_per_table": n,
            "placed_anchors": placed,
            "resident_bytes_by_device": by_dev}


# ---------------------------------------------------------- phase B


def phase_b_child(args) -> int:
    """Runs in its own process (the only one holding the chip): the
    config-4 shape through ``DeviceRunner().handle_request``.  Prints
    one JSON line for the parent."""
    import jax

    from tikv_tpu.datatype import Column, EvalType, FieldType
    from tikv_tpu.device import DeviceRunner
    from tikv_tpu.executors.columnar import ColumnarTable
    from tikv_tpu.testing.dag import DagSelect
    from tikv_tpu.testing.fixture import Table, TableColumn
    from tikv_tpu.utils import tracker

    checks = Checks(args.allow_cpu)
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    if dev0.platform != "tpu" and not args.allow_cpu:
        raise SmokeFailure(f"platform check: jax.devices()[0].platform "
                           f"is {dev0.platform!r}, not 'tpu'")
    checks.on_chip("phase B: platform==tpu", dev0.platform == "tpu")

    n = args.rows
    t0 = time.perf_counter()
    k, v = table_data(args.seed, 1, n)
    table = Table(99, (
        TableColumn("id", 1, FieldType.long(not_null=True),
                    is_pk_handle=True),
        TableColumn("k", 2, FieldType.long()),
        TableColumn("v", 3, FieldType.long())))
    ones = np.ones(n, np.bool_)
    snap = ColumnarTable.from_arrays(
        table, np.arange(n, dtype=np.int64),
        {"k": Column(EvalType.INT, k, ones),
         "v": Column(EvalType.INT, v, ones)})
    want = ref_hash_agg(k, v)
    build_s = time.perf_counter() - t0
    log(f"phase B: built {n} rows + reference in {build_s:.1f}s")

    runner = DeviceRunner()

    def one(name, snap=snap, want=want):
        s = DagSelect.from_table(table, ["id", "k", "v"])
        dag = s.aggregate([s.col("k")], [("count_star", None),
                                        ("sum", s.col("v"))]).build()
        tr, tok = tracker.install()
        try:
            t0 = time.perf_counter()
            result = runner.handle_request(dag, snap)
            wall = time.perf_counter() - t0
        finally:
            tracker.uninstall(tok)
        td = tr.time_detail()
        labels = td.get("labels", {})
        checks.require(f"phase B {name}: not degraded",
                       "degraded" not in labels and
                       "host_exec" not in td["phases_ms"], td)
        rows = [list(r) for r in result.rows()]
        checks.require(f"phase B {name}: equals numpy reference",
                       sorted(rows) == want,
                       f"{len(rows)} rows; {sorted(rows)[:2]} vs {want[:2]}")
        log(f"phase B {name}: {wall * 1e3:.1f} ms "
            f"phases_ms={td['phases_ms']}")
        return wall, td["phases_ms"]

    cold_s, _ = one("cold")
    warm = [one(f"warm {i}") for i in range(3)]

    # the kernel's sparse slot mode (BASELINE 4s: 1k distinct keys
    # drawn from [0, 2^62)), with a ragged last block
    ns = min(n, ROWS_A) + 4097
    rng = np.random.default_rng([args.seed, 2])
    domain = np.unique(rng.integers(0, 1 << 62, 1000, dtype=np.int64))
    slot = rng.integers(0, domain.size, ns)
    vs = rng.integers(-1000, 1000, ns).astype(np.int64)
    sparse_snap = ColumnarTable.from_arrays(
        table, np.arange(ns, dtype=np.int64),
        {"k": Column(EvalType.INT, domain[slot], np.ones(ns, np.bool_)),
         "v": Column(EvalType.INT, vs, np.ones(ns, np.bool_))})
    cnt = np.bincount(slot, minlength=domain.size)
    sm = np.zeros(domain.size, np.int64)
    np.add.at(sm, slot, vs)
    sparse_want = sorted([int(cnt[i]), int(sm[i]), int(domain[i])]
                         for i in range(domain.size) if cnt[i])
    sparse_s = [one(f"sparse {i}", sparse_snap, sparse_want)[0]
                for i in range(2)]
    fr = runner.flight_recorder
    classes = sorted({e["compile_class"] for e in fr.items()})
    checks.on_chip("phase B: every launch is pallas_hash",
                   set(classes) == CLASS_PALLAS, classes)
    checks.require("phase B: flight-recorder faults == 0",
                   fr.stats()["faults"] == 0, fr.stats())
    disabled = [str(key)[:120] for key, val in
                runner._kernel_cache.items()
                if key[0] == "hashpl" and val is False]
    checks.require("phase B: no cache-disabled pallas plan",
                   not disabled, disabled)
    by_dev = runner.hbm_stats()["resident_bytes_by_device"]
    checks.require("phase B: feed resident on every device",
                   len(by_dev) == device["count"] and
                   all(b > 0 for b in by_dev.values()), by_dev)
    walls = sorted(w for w, _ in warm)
    print(json.dumps({
        "device": device, "rows": n, "build_s": round(build_s, 2),
        "cold_s": round(cold_s, 3),
        "warm_p50_s": walls[len(walls) // 2], "warm_n": len(walls),
        "warm_phases_ms": warm[-1][1], "classes": classes,
        "sparse": {"rows": ns, "first_s": round(sparse_s[0], 3),
                   "last_s": round(sparse_s[1], 3)},
        "resident_bytes_by_device": by_dev,
        "compile_cache": runner.compile_cache_stats(),
        "versions": {"jax": jax.__version__},
        "passed": checks.passed, "skipped": checks.skipped},
        separators=(",", ":")), flush=True)
    return 0


def phase_b(args, checks: Checks, workdir: str) -> dict:
    argv = [sys.executable, os.path.abspath(__file__), "--child",
            "phase_b", "--seed", str(args.seed), "--rows",
            str(args.rows if args.allow_cpu else ROWS_B)]
    if args.allow_cpu:
        argv.append("--allow-cpu")
    child = Child("phase_b", argv, workdir)
    rc = child.wait(900)
    sys.stderr.write(child.read("err"))
    checks.require("phase B: child exit code 0", rc == 0,
                   f"rc={rc}\n{child.tail()}")
    out = json.loads(child.read().strip().splitlines()[-1])
    checks.passed += out.pop("passed")
    checks.skipped += out.pop("skipped")
    return out


# -------------------------------------------------------------- main


def cache_entries(path: str) -> int:
    try:
        return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))
    except FileNotFoundError:
        return 0


def version_of(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "absent"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="dry run: skip the platform and kernel-class "
                         "checks and say so in the summary")
    ap.add_argument("--rows", type=int, default=None,
                    help="rows per phase (dry run only)")
    ap.add_argument("--child", choices=["phase_b"], help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.rows is not None and not args.allow_cpu and not args.child:
        ap.error("--rows cuts the run to a dry-run size: it needs "
                 "--allow-cpu")
    if args.rows is None:
        args.rows = ROWS_A
    if args.child == "phase_b":
        return phase_b_child(args)

    try:
        import tikv_tpu
        from tikv_tpu import native
    except ImportError as e:
        raise SmokeFailure(f"checkout check: chip_smoke.py drives the "
                           f"tikv_tpu package beside it — {e}")
    checks = Checks(args.allow_cpu)
    # a store that has silently lost its C++ loader, or the native
    # hash-agg finalize and reply encode that share its extension, is a
    # failure, not a slow run
    for fn in ("mvcc_build_columnar", "build_mvcc_sst",
               "mvcc_parse_planes", "hash_finalize_packed",
               "encode_rows_msgpack"):
        checks.require(f"native.{fn} built",
                       getattr(native, fn) is not None,
                       "g++ build of native/fastbuild.cpp failed")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(tikv_tpu.__file__))), ".jax_cache")
    cache_before = cache_entries(cache_dir)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        legs = [served_leg(args, checks, workdir)]
        device = legs[0]["device"]
        if device["count"] == 4:
            legs.append(placement_leg(args, checks, workdir))
        b = phase_b(args, checks, workdir)
    checks.require(
        "phase B saw the device phase A saw",
        (b["device"]["platform"], b["device"]["kind"],
         b["device"]["count"]) ==
        (device["platform"], device["kind"], device["count"]),
        f"{b['device']} vs {device}")
    served_cache = legs[0]["compile_cache"]["dir"]
    checks.on_chip("compile cache placed where expected",
                   served_cache == cache_dir == b["compile_cache"]["dir"],
                   f"store {served_cache!r}, phase B "
                   f"{b['compile_cache']['dir']!r}, want {cache_dir!r}")
    checks.require("parent never imported jax",
                   "jax" not in sys.modules)

    summary = {
        "smoke_readings_not_benchmark_results": True,
        "platform": device["platform"], "device_kind": device["kind"],
        "n_devices": device["count"], "mesh": device["mesh"],
        "versions": {"jax": b["versions"]["jax"],
                     "jaxlib": version_of("jaxlib"),
                     "libtpu": version_of("libtpu")},
        "seed": args.seed,
        "phase_a": legs, "phase_b": b,
        "compile_cache": {"dir": cache_dir, "entries_before": cache_before,
                          "entries_after": cache_entries(cache_dir)},
        "checks_passed": len(checks.passed),
        "checks_skipped": checks.skipped,
    }
    if args.allow_cpu:
        summary["dry_run"] = True
    summary["claim"] = None
    print(json.dumps(summary, separators=(",", ":")), flush=True)
    # The result line — last, and only from a run that held every check
    # on an accelerator, with the device as phase B's child read it from
    # JAX (checked equal to the store's above).  A dry run ends on its
    # labelled summary: it is not a result.
    if not args.allow_cpu:
        print(json.dumps({"ok": True, "device": b["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr, flush=True)
        sys.exit(1)
