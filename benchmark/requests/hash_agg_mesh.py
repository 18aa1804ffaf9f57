"""``hash_agg``'s plan, reference and answer check, sent to a store whose
feed is sharded over a mesh: every served read must also carry the
``mesh`` label of the whole mesh (``params["mesh"]``, ``"2x2"``).  A read
that a degraded submesh served after a slice was quarantined, or that
carries no such label, is off the mesh: its answer may be right, and it
is not a reading of the four-chip store."""

import byname

_agg = byname.load("requests", "hash_agg")

# the fused Pallas kernel as per-shard partials, never the sharded XLA
# stand-ins
CLASSES = ("pallas_hash",)

prepare = _agg.prepare
send = _agg.send
reference = _agg.reference
digest = _agg.digest


def check(ctx, records, params, reference):
    """``hash_agg``'s checks, then the layout: a served read (a record
    with the reply's ``labels``) whose ``mesh`` label is not the
    configured shape is marked ``wrong`` (it counts as failed and in no
    latency).  ``control.py``'s record is an answer alone and says
    nothing of the layout.  → [(name, value, limit)]."""
    checks = _agg.check(ctx, records, params, reference)
    want = params.get("mesh", "2x2")
    off = 0
    for r in records:
        if "labels" in r and r["labels"].get("mesh") != want:
            r["wrong"] = True
            off += 1
    return checks + [("mesh.reads_off_the_mesh", off, 0)]
