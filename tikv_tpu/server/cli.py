"""Command-line entrypoints.

Reference: cmd/tikv-server/src/main.rs (server binary: config + flags →
run_tikv) and cmd/tikv-ctl (ops CLI: region inspect, split, peer ops,
KV ops, GC).  Usage:

    python -m tikv_tpu.server pd --addr 127.0.0.1:2379
    python -m tikv_tpu.server tikv --addr 127.0.0.1:20160 --pd 127.0.0.1:2379
    python -m tikv_tpu.server ctl --pd 127.0.0.1:2379 put k v
    python -m tikv_tpu.server ctl --pd 127.0.0.1:2379 get k
    python -m tikv_tpu.server ctl --pd 127.0.0.1:2379 region --key k
    python -m tikv_tpu.server ctl --pd 127.0.0.1:2379 split k
    python -m tikv_tpu.server ctl --pd 127.0.0.1:2379 add-peer 1 2
    python -m tikv_tpu.server ctl --pd 127.0.0.1:2379 store-status 1
    python -m tikv_tpu.server ctl --pd 127.0.0.1:2379 gc --safe-point 42
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tikv_tpu.server")
    sub = p.add_subparsers(dest="cmd", required=True)

    pd_p = sub.add_parser("pd", help="run the placement driver")
    pd_p.add_argument("--addr", default="127.0.0.1:2379")

    kv_p = sub.add_parser("tikv", help="run a tikv store server")
    kv_p.add_argument("--addr", default="127.0.0.1:20160")
    kv_p.add_argument("--pd", required=True)
    kv_p.add_argument("--data-dir", default=None,
                      help="durable storage directory (WAL + checkpoints); "
                           "omit for an in-memory store")
    kv_p.add_argument("--with-device", action="store_true",
                      help="register the TPU device runner on the "
                           "coprocessor endpoint")
    kv_p.add_argument("--config", default=None,
                      help="TOML config file (config-template.toml shape)")
    kv_p.add_argument("--status-addr", default=None,
                      help="HTTP status server bind "
                           "(/metrics /status /config)")

    ctl = sub.add_parser("ctl", help="ops CLI (tikv-ctl analog)")
    ctl.add_argument("--pd", required=True)
    ctl_sub = ctl.add_subparsers(dest="op", required=True)
    sp = ctl_sub.add_parser("put")
    sp.add_argument("key")
    sp.add_argument("value")
    gp = ctl_sub.add_parser("get")
    gp.add_argument("key")
    scn = ctl_sub.add_parser("scan")
    scn.add_argument("start")
    scn.add_argument("--limit", type=int, default=16)
    rg = ctl_sub.add_parser("region")
    rg.add_argument("--key", required=True)
    spl = ctl_sub.add_parser("split")
    spl.add_argument("key")
    ap = ctl_sub.add_parser("add-peer")
    ap.add_argument("region_id", type=int)
    ap.add_argument("store_id", type=int)
    mg = ctl_sub.add_parser("merge")
    mg.add_argument("source_id", type=int)
    mg.add_argument("target_id", type=int)
    rb = ctl_sub.add_parser("rollback-merge")
    rb.add_argument("region_id", type=int)
    st = ctl_sub.add_parser("store-status")
    st.add_argument("store_id", type=int)
    gc = ctl_sub.add_parser("gc")
    gc.add_argument("--safe-point", type=int, required=True)
    ctl_sub.add_parser("stores")
    ctl_sub.add_parser("tso")
    # debug service (src/server/debug.rs surface; tikv-ctl raft/mvcc/
    # size/recover subcommands)
    dg = ctl_sub.add_parser("debug-get")
    dg.add_argument("store_id", type=int)
    dg.add_argument("cf")
    dg.add_argument("key")
    di = ctl_sub.add_parser("region-info")
    di.add_argument("store_id", type=int)
    di.add_argument("region_id", type=int)
    ds = ctl_sub.add_parser("region-size")
    ds.add_argument("store_id", type=int)
    ds.add_argument("region_id", type=int)
    dm = ctl_sub.add_parser("mvcc")
    dm.add_argument("store_id", type=int)
    dm.add_argument("start")
    dm.add_argument("--end", default="")
    dm.add_argument("--limit", type=int, default=20)
    dl = ctl_sub.add_parser("raft-log")
    dl.add_argument("store_id", type=int)
    dl.add_argument("region_id", type=int)
    dl.add_argument("index", type=int)
    dr = ctl_sub.add_parser("tombstone")
    dr.add_argument("store_id", type=int)
    dr.add_argument("region_id", type=int)
    dc = ctl_sub.add_parser("compact")
    dc.add_argument("store_id", type=int)

    args = p.parse_args(argv)

    if args.cmd == "pd":
        import signal
        import threading

        from .pd_server import PdServer
        server = PdServer(args.addr)
        print(f"pd listening on {args.addr}", flush=True)
        server.start()
        # graceful shutdown on SIGTERM/SIGINT, like the store below
        stop = threading.Event()
        signal.signal(signal.SIGTERM, lambda *_: stop.set())
        signal.signal(signal.SIGINT, lambda *_: stop.set())
        stop.wait()
        server.stop()
        return 0

    if args.cmd == "tikv":
        from .node import Node
        from .pd_server import RemotePdClient
        from .server import TikvServer
        config = None
        if args.config:
            from ..config import TikvConfig
            config = TikvConfig.from_file(args.config)
            if config.security.enabled:
                from .security import set_default
                set_default(config.security)
        device_runner = None
        if args.with_device:
            from ..device import DeviceRunner
            if config is not None:
                # multi-chip: honor the explicit mesh factorization and
                # the hot-region placement opt-in (config rationale at
                # CoprocessorConfig.mesh_shape)
                from ..parallel import make_mesh, parse_mesh_shape
                cc = config.coprocessor
                device_runner = DeviceRunner(
                    mesh=make_mesh(
                        shape=parse_mesh_shape(cc.mesh_shape)),
                    placement=cc.device_placement,
                    placement_rows=cc.placement_rows,
                    slice_trip_strikes=cc.slice_trip_strikes,
                    slice_probe_cooldown_s=cc.slice_probe_cooldown_s,
                    slice_latency_outlier_s=cc.slice_latency_outlier_s,
                    flight_recorder_depth=cc.flight_recorder_depth)
            else:
                device_runner = DeviceRunner()
            import os

            import jax

            from .. import native
            from ..utils.trace import gil_mode
            ms = device_runner.mesh_stats()
            print(f"device runner: platform={ms['platform']} "
                  f"device_kind={ms['device_kind']!r} "
                  f"n_devices={len(jax.devices())} "
                  f"mesh={'x'.join(str(v) for v in ms['shape'].values())} "
                  f"native_finalize="
                  f"{'yes' if ms['finalize']['native_available'] else 'no'}"
                  f" native_encode="
                  f"{'no' if native.encode_rows_msgpack is None else 'yes'}"
                  f" gil_probe={gil_mode()} mux=raw",
                  flush=True)
            if ms["platform"] == "cpu" and "cpu" not in os.environ.get(
                    "JAX_PLATFORMS", "").split(","):
                # --with-device asked for an accelerator and JAX found
                # none: refuse to serve every "device" request from
                # XLA's CPU backend unless the operator chose it
                print("--with-device found no accelerator (JAX fell "
                      "back to cpu); set JAX_PLATFORMS=cpu to serve the "
                      "device path on the CPU backend deliberately",
                      file=sys.stderr, flush=True)
                return 1
        if args.status_addr and config is not None:
            config.server.status_addr = args.status_addr
        node = Node(args.addr, RemotePdClient(args.pd),
                    data_dir=args.data_dir, device_runner=device_runner,
                    config=config)
        server = TikvServer(node, status_addr=args.status_addr)
        server.start()
        # graceful shutdown on SIGTERM/SIGINT through the service-event
        # channel (cmd/tikv-server main.rs signal handler)
        import signal

        from ..service_event import (
            ServiceEvent,
            ServiceEventChannel,
            attach,
        )
        events = ServiceEventChannel()
        dispatcher = attach(events, server)

        def _on_signal(signum, _frame):
            print(f"received signal {signum}; shutting down", flush=True)
            events.post(ServiceEvent.EXIT)

        signal.signal(signal.SIGTERM, _on_signal)
        signal.signal(signal.SIGINT, _on_signal)
        if server.status_server is not None:
            print(f"status server on port {server.status_server.port}",
                  flush=True)
        print(f"tikv store {node.store_id} listening on {args.addr}",
              flush=True)
        server.wait()
        # wait() returns as soon as the gRPC server begins stopping;
        # the dispatcher thread is still inside server.stop() (node
        # teardown, pool joins).  Returning before it finishes lets
        # interpreter finalization race the teardown — with device
        # state live that aborts the process instead of exiting 0.
        dispatcher.join()
        return 0

    # ctl
    from .client import TxnClient
    c = TxnClient(args.pd)
    enc = lambda s: s.encode()          # noqa: E731

    if args.op == "put":
        c.put(enc(args.key), enc(args.value))
        print("OK")
    elif args.op == "get":
        v = c.get(enc(args.key))
        print(v.decode(errors="replace") if v is not None else "(nil)")
    elif args.op == "scan":
        for k, v in c.scan(enc(args.start), None, args.limit):
            print(k, v)
    elif args.op == "region":
        region, leader = c.pd.get_region_with_leader(enc(args.key))
        print(json.dumps({
            "id": region.id,
            "start": region.start_key.decode(errors="replace"),
            "end": region.end_key.decode(errors="replace"),
            "epoch": [region.epoch.conf_ver, region.epoch.version],
            "peers": [[pr.id, pr.store_id] for pr in region.peers],
            "leader": leader.id if leader else None}))
    elif args.op == "split":
        right = c.split(enc(args.key))
        print(f"new region {right.id} at {args.key!r}")
    elif args.op == "add-peer":
        peer = c.add_peer(args.region_id, args.store_id)
        print(f"added peer {peer.id} on store {peer.store_id}")
    elif args.op == "merge":
        merged = c.merge(args.source_id, args.target_id)
        print(f"merged region {args.source_id} into {merged.id}")
    elif args.op == "rollback-merge":
        region = c.pd.get_region_by_id(args.region_id)
        c._call_leader_by_region(region, "RollbackMerge",
                                 {"region_id": args.region_id})
        print(f"rolled back merge on region {args.region_id}")
    elif args.op == "store-status":
        print(json.dumps(c.status(args.store_id), default=repr, indent=2))
    elif args.op == "gc":
        total = 0
        for s in c.pd.stores():
            from .client import StoreClient
            total += StoreClient(s.address).call(
                "KvGC", {"safe_point": args.safe_point})["removed"]
        c.pd.set_gc_safe_point(args.safe_point)
        print(f"gc removed {total} versions")
    elif args.op == "stores":
        for s in c.pd.stores():
            print(s.id, s.address)
    elif args.op == "tso":
        print(c.tso())
    elif args.op == "debug-get":
        r = c.debug(args.store_id, "DebugGet",
                    {"cf": args.cf, "key": args.key.encode()})
        print(json.dumps(r, default=repr))
    elif args.op == "region-info":
        r = c.debug(args.store_id, "DebugRegionInfo",
                    {"region_id": args.region_id})
        print(json.dumps(r, default=repr, indent=2))
    elif args.op == "region-size":
        r = c.debug(args.store_id, "DebugRegionSize",
                    {"region_id": args.region_id})
        print(json.dumps(r, default=repr))
    elif args.op == "mvcc":
        r = c.debug(args.store_id, "DebugScanMvcc",
                    {"start": args.start.encode(),
                     "end": args.end.encode() if args.end else None,
                     "limit": args.limit})
        print(json.dumps(r, default=repr, indent=2))
    elif args.op == "raft-log":
        r = c.debug(args.store_id, "DebugRaftLog",
                    {"region_id": args.region_id, "index": args.index})
        print(json.dumps(r, default=repr))
    elif args.op == "tombstone":
        r = c.debug(args.store_id, "DebugRecoverRegion",
                    {"region_id": args.region_id})
        print(json.dumps(r, default=repr))
    elif args.op == "compact":
        r = c.debug(args.store_id, "DebugCompact", {})
        print(json.dumps(r, default=repr))
    return 0


if __name__ == "__main__":
    sys.exit(main())
