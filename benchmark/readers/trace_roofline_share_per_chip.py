"""``trace_roofline_share`` for a feed sharded by rows over a mesh: the
least time ONE chip could take for its share of the plan (HBM-bound:
the bytes of the input planes of rows / n_devices rows, over one chip's
peak bandwidth) over the main kernel's mean time per shard, in %: the
whole table's share, which charges every shard all the rows, over
``n_devices``, what the store's ``/health`` ``device_mesh`` said at
``go``.  None without a trace or without that block."""

import byname

_whole = byname.load("readers", "trace_roofline_share").read


def read(data, args):
    mesh = data["counters_go"].get("health", {}).get("device_mesh") or {}
    share = _whole(data, args)
    if share is None or not mesh.get("n_devices"):
        return None
    return share / mesh["n_devices"]
