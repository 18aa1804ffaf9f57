#!/usr/bin/env python3
"""The control of ``correct``, at the cell's own size: each request
kind's reference put in the program's place, computed one precision
down (bfloat16).  Every control has to fail a check of its cell.  Numpy
only; the benchmark's own runs do not run it.

    python3 benchmark/control.py --workload agg-closed8 --seeds 1 2 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import byname  # noqa: E402
import line  # noqa: E402


def controls(workload: str, seed: int, rows: int = 0) -> dict:
    """{control name: [(check, value, limit)]} for one seed."""
    _cell, config_file, traffic_file = line.cell_files(
        line.load_manifest(), workload)
    with open(config_file) as f:
        tspec = json.load(f)["table"]
    with open(traffic_file) as f:
        traffic = json.load(f)
    rows = rows or tspec["rows"]
    ctx = types.SimpleNamespace(
        rows=rows, cols=byname.load("tables", tspec["kind"]).make(
            tspec, seed, rows))
    out = {}
    for name, k in traffic["kinds"].items():
        mod, params = byname.load("requests", k["module"]), k.get("params", {})
        served = {"answer": mod.reference(ctx, params, approx=True).tobytes()}
        out[f"{name}.bfloat16"] = mod.check(
            ctx, [served], params, mod.reference(ctx, params))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=0)
    args = ap.parse_args(argv)
    caught = True
    for seed in args.seeds:
        for control, checks in controls(args.workload, seed,
                                        args.rows).items():
            failed = [c for c in checks if c[1] > c[2]]
            caught = caught and bool(failed)
            print(f"control {args.workload} seed={seed} {control}: " +
                  " ".join(f"{n}={v}(limit {lim})" for n, v, lim in checks) +
                  f" -> {'not correct' if failed else 'CORRECT (control passed!)'}")
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
