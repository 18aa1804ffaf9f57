"""The client's side of gRPC, as one child process that never imports
JAX: loads the table through ImportSST, sends the first read, warms
every request kind of the cell, waits for ``go`` on stdin, drives the
window with the cell's clients as threads, samples the status server's
counters at both ends of it, checks every answer against the request
kind's numpy reference, and writes one result file.

    python benchmark/loadgen.py <spec.json>

stdout carries the hand-shake (``warm {...}``, ``done``); everything
else goes to stderr."""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import byname  # noqa: E402


def log(msg: str) -> None:
    print(f"[loadgen] {msg}", file=sys.stderr, flush=True)


class Ctx:
    """What a request kind may see: the program's description of the
    table, and the data the benchmark made for it."""

    def __init__(self, table, rows: int, cols: dict):
        self.table, self.rows, self.cols = table, rows, cols


class Driver:
    def __init__(self, spec: dict):
        from tikv_tpu.server import TxnClient

        self.spec = spec
        self.TxnClient = TxnClient
        with open(spec["config_file"]) as f:
            self.config = json.load(f)
        with open(spec["traffic_file"]) as f:
            self.traffic = json.load(f)
        self.kinds = {name: (byname.load("requests", k["module"]),
                             k.get("params", {}))
                      for name, k in self.traffic["kinds"].items()}
        tspec = self.config["table"]
        self.table_kind = byname.load("tables", tspec["kind"])
        rows = spec["rows"] or tspec["rows"]
        self.ctx = Ctx(self.table_kind.fixture(tspec), rows,
                       self.table_kind.make(tspec, spec["seed"], rows))
        self.client = TxnClient(spec["pd_addr"])
        self._mu = threading.Lock()
        self._answers: dict = {}

    # -- one request --

    def request(self, client, kind: str) -> dict:
        """Send one request; a record with its latency on this clock,
        its time_detail, and what the product checks said."""
        mod, params = self.kinds[kind]
        rec = {"kind": kind, "ok": False, "why": ""}
        # the TSO fetch (PD) and the plan's construction are the SQL
        # layer's: the clock runs over the call to the store alone
        rec["t0"] = rec["t1"] = time.perf_counter()
        try:
            req = mod.prepare(self.ctx, client, params)
            rec["t0"] = time.perf_counter()
            resp = mod.send(self.ctx, client, req)
        except Exception as e:     # a refused or failed request is a result
            rec["t1"] = time.perf_counter()
            rec["why"] = f"{type(e).__name__}: {e}"[:300]
            return rec
        rec["t1"] = time.perf_counter()
        td = resp.get("time_detail", {})
        labels, phases = td.get("labels", {}), td.get("phases_ms", {})
        rec["phases_ms"], rec["labels"] = phases, labels
        rec["rpc_ms"] = td.get("total_rpc_wall_ms")
        rec["trace_id"] = resp.get("trace_id")
        # a reading taken on a fallback is not a reading of the product
        if resp.get("backend") != "device":
            rec["why"] = f"backend={resp.get('backend')!r}"
        elif "degraded" in labels:
            rec["why"] = f"degraded={labels['degraded']!r}"
        elif "host_exec" in phases:
            rec["why"] = "host_exec phase"
        else:
            rec["ok"] = True
            answer = mod.digest(self.ctx, resp, params)
            with self._mu:      # identical answers share one object
                rec["answer"] = self._answers.setdefault(answer, answer)
        return rec

    def probe(self, kind: str) -> dict:
        """One request whose full span tree is fetched and held to the
        compile class its plan is meant to take (chip_smoke's check)."""
        rec = self.request(self.client, kind)
        if not rec["ok"]:
            return rec
        trace = self.http_json(f"/debug/trace/{rec['trace_id']}")
        names = [s["name"] for s in trace["spans"]]
        classes = sorted({s["attrs"]["compile_class"]
                          for s in trace["spans"]
                          if s["name"] == "device_dispatch"
                          and "compile_class" in s.get("attrs", {})})
        rec["classes"] = classes
        want = self.kinds[kind][0].CLASSES
        if "host_exec" in names or "degraded" in trace["labels"]:
            rec["ok"], rec["why"] = False, f"fallback spans={names}"
        elif self.spec["on_tpu"] and not (
                classes and set(classes) <= set(want)):
            rec["ok"] = False
            rec["why"] = f"compile classes {classes}, want within {want}"
        return rec

    # -- the status server's public surface --

    def http_json(self, path: str) -> dict:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{self.spec['status_port']}{path}",
                timeout=30) as r:
            return json.loads(r.read())

    def counters(self) -> dict:
        """The counts a layer metric may difference over the window."""
        fr = self.http_json("/debug/trace")["flight_recorder"]
        return {"health": self.http_json("/health"),
                "flight_recorder": {k: fr[k] for k in
                                    ("launches", "first_launches",
                                     "faults")},
                "flight_recent": fr.get("recent", [])}

    def compile_requests(self) -> int:
        return self.http_json("/health")["compile_cache"]["requests"]

    # -- phases --

    def setup(self) -> dict:
        stores = []
        deadline = time.monotonic() + 60
        while not stores and time.monotonic() < deadline:
            stores = self.client.pd.stores()
            if not stores:
                time.sleep(0.1)
        ctx = self.ctx
        load_s = self.table_kind.load(self.client, stores[0].id, ctx.table,
                                      ctx.cols)
        log(f"loaded {ctx.rows} rows in {load_s:.1f}s")
        t0 = time.perf_counter()
        first = self.probe(self.traffic["first_read"])
        first_s = time.perf_counter() - t0
        log(f"first read {first_s:.2f}s labels={first.get('labels')} "
            f"classes={first.get('classes')}")
        warm = [first]
        t0 = time.perf_counter()
        for kind in self.kinds:
            for _ in range(self.traffic.get("warm_requests", 3)):
                warm.append(self.probe(kind))
        # then every client at once, in rounds, until a round compiles
        # nothing: what only concurrency reaches (coalesced groups of
        # each size) is warm too
        compiles = self.compile_requests()
        for rnd in range(self.traffic.get("warm_rounds_max", 6)):
            warm += self.run_clients(self.traffic["warm_s"])
            now = self.compile_requests()
            log(f"warm round {rnd}: {now - compiles} compile requests")
            if now == compiles:
                break
            compiles = now
        log(f"warm-up {time.perf_counter() - t0:.2f}s, "
            f"{len(warm)} requests")
        return {"load_s": load_s, "first_read_s": first_s, "warm": warm}

    def run_clients(self, seconds: float) -> list:
        """Closed loop: each client sends its next request when the
        last one has returned (plus its think time), until the window
        closes; a request in flight then is allowed to finish."""
        out: list = []
        threads = []
        t_end = time.perf_counter() + seconds
        idx = 0
        for group in self.traffic["clients"]:
            pattern = group["pattern"]
            for _ in range(group["count"]):
                # clients start evenly staggered through the cycle, the
                # same in every run: in a closed loop the phases persist,
                # so a seed that turned them would change the work
                offset = idx % len(pattern)
                threads.append(threading.Thread(
                    target=self._client, daemon=True,
                    args=(pattern, offset, group.get("think_ms", 0) / 1e3,
                          t_end, out)))
                idx += 1
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out

    def _client(self, pattern, offset, think_s, t_end, out):
        client = self.TxnClient(self.spec["pd_addr"])
        mine = []
        i = offset
        while time.perf_counter() < t_end:
            mine.append(self.request(client, pattern[i % len(pattern)]))
            i += 1
            if think_s:
                time.sleep(think_s)
        with self._mu:
            out.extend(mine)

    def check(self, records: list) -> list:
        """After the window: each kind's answers against its reference.
        A record whose answer is wrong stops being ``ok``: it counts as
        failed and in no latency.  → [(name, value, limit)]."""
        checks = []
        for kind, (mod, params) in self.kinds.items():
            mine = [r for r in records if r["kind"] == kind and r["ok"]]
            checks += mod.check(self.ctx, mine, params,
                                mod.reference(self.ctx, params))
        for r in records:
            if r.pop("wrong", False):
                r["ok"], r["why"] = False, "wrong answer"
        return checks


def main(argv) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    d = Driver(spec)
    setup = d.setup()
    bad = [r for r in setup["warm"] if not r["ok"]]
    print("warm " + json.dumps({
        "load_s": setup["load_s"], "first_read_s": setup["first_read_s"],
        "failed": len(bad), "why": [r["why"] for r in bad][:5]}),
        flush=True)
    if sys.stdin.readline().strip() != "go":
        log("no go: parent went away")
        return 1
    counters_go = d.counters()
    t_go = time.perf_counter()
    cpu0 = time.process_time()
    records = d.run_clients(spec["seconds"])
    window_s = time.perf_counter() - t_go
    # CPU seconds this process burned per second of the window: near 1
    # means the generator, not the store, set the pace
    loadgen_cpu_share = (time.process_time() - cpu0) / window_s
    # the window's last reply is in: what the counters rose by until
    # here is the window's, what the probes and the check add is not
    counters_end = d.counters()
    last = [d.probe(k) for k in d.kinds]
    counters_done = d.counters()
    t0 = time.perf_counter()
    checks = d.check(setup["warm"] + records + last)
    check_s = time.perf_counter() - t0
    for r in records + last + setup["warm"]:
        r.pop("answer", None)
        r["t0"] -= t_go
        r["t1"] -= t_go
    with open(spec["out"], "w") as f:
        json.dump({"records": records, "last": last,
                   "warm_failed": sum(1 for r in setup["warm"]
                                      if not r["ok"]),
                   "checks": checks, "window_s": window_s,
                   "counters_go": counters_go, "counters_end": counters_end,
                   "counters_done": counters_done,
                   "loadgen_cpu_share": loadgen_cpu_share,
                   "check_s": check_s, "load_s": setup["load_s"],
                   "first_read_s": setup["first_read_s"]}, f)
    if "jax" in sys.modules:
        log("the load generator imported JAX: it may have held the chip")
        return 1
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
