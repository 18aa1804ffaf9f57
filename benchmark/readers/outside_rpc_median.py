"""Median of the client's latency minus the server's own wall for the
RPC: gRPC both ways, wire pack/unpack, the client's decode, and waiting
for the store's GIL outside the handler."""

import statistics


def read(data, args):
    vals = [(r["t1"] - r["t0"]) * 1e3 - r["rpc_ms"]
            for r in data["reads"] if r.get("rpc_ms") is not None]
    return statistics.median(vals) if vals else None
