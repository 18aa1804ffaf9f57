#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the served coprocessor path.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This is the only process that touches JAX.  It builds the store in
process from the configuration's TOML with PD beside it (rig.py), starts
the load generator as a child that never imports JAX (loadgen.py), and,
in a traced run, holds the profiler over the first seconds of the window.
The last line of stdout is the result line (line.py); a run that finds
no TPU, or cannot fill a declared metric, prints why on an earlier line
and exits non-zero without one.

    python3 benchmark/run.py --workload agg-closed8 --seed 1 --seconds 3 \
        --trace 1 --dry-run-cpu --rows 65536

rehearses the whole control flow on the CPU at a toy size: it prints a
labelled summary and NO result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import byname  # noqa: E402
import line as result_line  # noqa: E402
import trace_reduce  # noqa: E402


class RunFailure(Exception):
    """The run cannot produce an honest line."""


def log(msg: str) -> None:
    print(f"[run +{time.perf_counter() - T_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def percentile(sorted_vals: list, q: float) -> float:
    """Nearest rank: the smallest value with at least q of the sample at
    or below it."""
    return sorted_vals[max(0, math.ceil(q * len(sorted_vals)) - 1)]


class Child:
    """The load generator and its hand-shake over pipes."""

    def __init__(self, spec_path: str):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"), spec_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=ROOT)
        self.lines: queue.Queue = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for ln in self.proc.stdout:
            self.lines.put(ln.rstrip("\n"))
        self.lines.put(None)

    def expect(self, word: str, timeout: float) -> str:
        try:
            ln = self.lines.get(timeout=timeout)
        except queue.Empty:
            raise RunFailure(f"load generator: no {word!r} in {timeout:.0f}s")
        if ln is None or not ln.startswith(word):
            raise RunFailure(f"load generator: wanted {word!r}, got {ln!r} "
                             f"(exit code {self.proc.poll()})")
        return ln[len(word):].strip()

    def say(self, word: str) -> None:
        self.proc.stdin.write(word + "\n")
        self.proc.stdin.flush()

    def close(self) -> int | None:
        if self.proc.poll() is None:
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self.proc.wait()
        self.proc.stdin.close()
        self._reader.join(timeout=5)
        self.proc.stdout.close()
        return self.proc.returncode


def end_to_end(result: dict, seconds: float, setup_s: float) -> tuple:
    """→ (values, attempted, failed) from the window's records: a
    latency is taken over every read that the device path served with
    the right answer, a rate over the whole window."""
    records = result["records"]
    reads = [r for r in records if r["ok"]]
    values = {"setup_s": setup_s}
    if reads:
        lat = sorted((r["t1"] - r["t0"]) * 1e3 for r in reads)
        values["read_p50_ms"] = statistics.median(lat)
        values["read_p95_ms"] = percentile(lat, 0.95)
        values["reads_per_s"] = sum(
            1 for r in reads if r["t1"] <= seconds) / seconds
        if len(lat) < 200:
            log(f"only {len(lat)} reads: fewer than ten samples lie beyond "
                f"the 95th percentile")
    return values, len(records), len(records) - len(reads)


def per_layer(manifest: dict, workload: str, data: dict) -> dict:
    """Each per-layer metric is the file ``layer_metrics/<name>.json``:
    a reader's name (the file ``readers/<reader>.py``) and its
    arguments."""
    values = {}
    for name in result_line.declared(manifest, workload, "per_layer"):
        spec = load_json(HERE, "layer_metrics", f"{name}.json")
        got = byname.load("readers", spec["reader"]).read(
            data, spec.get("args", {}))
        if got is not None:
            values[name] = got
    return values


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="rehearse on the CPU: summary, no result line")
    ap.add_argument("--rows", type=int, default=0,
                    help="table rows, with --dry-run-cpu only")
    ap.add_argument("--out-dir", default=None,
                    help="where the trace and the result file go "
                         "(default .bench_out/<workload>)")
    args = ap.parse_args(argv)
    if args.rows and not args.dry_run_cpu:
        ap.error("--rows changes the cell: only with --dry-run-cpu")
    try:
        return run(args)
    except (RunFailure, result_line.LineError) as e:
        print(f"benchmark/run.py: no result line: {e}", flush=True)
        return 1


def run(args) -> int:
    manifest = result_line.load_manifest(ROOT)
    cell, config_file, traffic_file = result_line.cell_files(
        manifest, args.workload, ROOT)
    config = load_json(config_file)
    traffic = load_json(traffic_file)
    traced = bool(args.trace)
    out_dir = os.path.join(ROOT, args.out_dir or os.path.join(
        ".bench_out", args.workload))
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)

    try:
        import jax

        import tikv_tpu  # noqa: F401
        from rig import Rig
    except ImportError as e:
        raise RunFailure(f"the program is not in this checkout: {e}")
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.dry_run_cpu:
        print(f"benchmark/run.py: JAX found no TPU ({device}); "
              f"a measurement does not fall back to the CPU", flush=True)
        return 4
    if on_tpu and device["count"] < cell["chips"]:
        print(f"benchmark/run.py: {cell['chips']} chips wanted, "
              f"{device['count']} found", flush=True)
        return 4
    peaks = load_json(HERE, "peaks.json").get(device["kind"])
    if peaks is None and not args.dry_run_cpu:
        raise RunFailure(f"peaks.json has no device kind {device['kind']!r}")
    rows = args.rows or config["table"]["rows"]
    log(f"{args.workload}: {device}, {rows} rows, seed {args.seed}")

    threshold = None
    if args.rows:      # a toy table must still route to the device
        threshold = max(64, args.rows // 4)
    rig = Rig(os.path.join(ROOT, config["toml"]), ROOT,
              row_threshold=threshold)
    child = None
    try:
        spec_path = os.path.join(out_dir, "loadgen_spec.json")
        result_path = os.path.join(out_dir, "loadgen_result.json")
        if os.path.exists(result_path):
            os.remove(result_path)
        with open(spec_path, "w") as f:
            json.dump({"pd_addr": rig.pd_addr, "status_port": rig.status_port,
                       "seed": args.seed, "seconds": args.seconds,
                       "rows": args.rows, "config_file": config_file,
                       "traffic_file": traffic_file, "out": result_path,
                       "on_tpu": on_tpu}, f)
        child = Child(spec_path)
        warm = json.loads(child.expect("warm", 1100))
        setup_s = time.perf_counter() - T_START
        log(f"warm: setup {setup_s:.1f}s {warm}")
        if traced:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # device and XLA host events
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        child.say("go")
        if traced:
            time.sleep(min(traffic["trace_window_s"], args.seconds))
            jax.profiler.stop_trace()
            log("trace stopped")
        child.expect("done", args.seconds + 240)
        device["memory_peak_bytes"] = rig.memory_peak_bytes()
        result = load_json(result_path)
        # a CPU rehearsal has no device plane to reduce
        trace = trace_reduce.reduce_file(
            trace_reduce.newest_xplane(trace_dir)) \
            if traced and on_tpu else None
        rc = child.close()
    except BaseException:
        if child is not None:
            child.proc.kill()
            child.close()
        raise
    finally:
        rig.stop()
    if rc != 0:
        raise RunFailure(f"load generator exit code {rc}")

    values, attempted, failed = end_to_end(result, args.seconds, setup_s)
    data = {"reads": [r for r in result["records"] if r["ok"]],
            "counters_go": result["counters_go"],
            "counters_end": result["counters_end"], "trace": trace,
            "traffic": traffic, "rows": rows, "peaks": peaks,
            "stats": {"loadgen_cpu_share": result["loadgen_cpu_share"]},
            "setup": {"load_s": result["load_s"],
                      "first_read_s": result["first_read_s"]}}
    values.update(per_layer(manifest, args.workload, data))

    checks = [tuple(c) for c in result["checks"]]
    # over the window and the probes after it
    go, done = result["counters_go"], result["counters_done"]
    checks.append(("device.flight_recorder_faults",
                   done["flight_recorder"]["faults"] -
                   go["flight_recorder"]["faults"], 0))
    stand_ins = sorted({e.get("compile_class") for e in
                        done["flight_recent"]} &
                       set(traffic.get("forbidden_classes", ())))
    checks.append(("device.stand_in_kernel_launches",
                   len(stand_ins) if on_tpu else 0, 0))
    checks.append(("warmup.failed_requests", result["warm_failed"], 0))
    checks.append(("after_window.failed_probes",
                   sum(1 for r in result["last"] if not r["ok"]), 0))
    checks.append(("window.failed_requests", failed, 0))
    for name, value, limit in checks:
        print(f"check {name} value={value} limit={limit}", flush=True)
    for r in [r for r in result["records"] + result["last"]
              if not r["ok"]][:5]:
        log(f"failed {r['kind']}: {r['why']}")
    correct = all(value <= limit for _n, value, limit in checks)

    breakdown = None
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        top = sorted(trace["ops"].items(), key=lambda kv: -kv[1][1])[:10]
        breakdown = {"device_ops": [[n, c[1]] for n, c in top],
                     "idle_gaps": trace["idle_gaps"]}
    summary = {"workload": args.workload, "seed": args.seed,
               "correct": correct, "attempted": attempted, "failed": failed,
               "values": values, "device": device,
               "check_s": result["check_s"],
               "loadgen_cpu_share": result["loadgen_cpu_share"],
               "window_s": result["window_s"]}
    log("summary " + json.dumps(summary))
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump({**summary, "breakdown": breakdown,
                   "trace_ops": trace["ops"] if trace else None}, f)
    if args.dry_run_cpu:
        print("DRY RUN on the CPU (no result line): " + json.dumps(summary),
              flush=True)
        return 0
    out = result_line.build(manifest, args.workload, traced, values, correct,
                            attempted, failed, device, breakdown)
    print(result_line.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
