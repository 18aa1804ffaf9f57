"""``hash_agg``'s plan and reference, read as TiDB's copr client reads a
table that spans regions: ``TxnClient.coprocessor_fanout`` sends one cop
task a region, at most ``params["concurrency"]`` at once, and hands back
the partial aggregates; merging them is the SQL layer's, as the TSO fetch
and the plan are, and happens in ``digest``, off the clock.  Every served
read must also have been answered by exactly ``params["regions"]`` tasks
(the reply's ``cop_tasks`` label): more or fewer, and the table was not
in the layout the configuration states, whatever the answer."""

import numpy as np

import byname

_agg = byname.load("requests", "hash_agg")

# the fused Pallas kernel on every region's feed, never its XLA stand-ins
CLASSES = ("pallas_hash",)

reference = _agg.reference


def prepare(ctx, client, params):
    """``hash_agg``'s TSO fetch and plan, with the concurrency the read
    is to be fanned out at."""
    return _agg.prepare(ctx, client, params), params["concurrency"]


def send(ctx, client, request):
    """The timed call: first task sent to last partial back."""
    dag, concurrency = request
    return client.coprocessor_fanout(dag, concurrency=concurrency,
                                     timeout=120)


def merge(partials) -> np.ndarray:
    """(groups, 3) int64 [count, sum, key] sorted by key from the tasks'
    rows: COUNT and SUM added by key, plain numpy."""
    rows = [np.array(p, dtype=np.int64).reshape(-1, 3) for p in partials]
    a = np.concatenate(rows) if rows else np.zeros((0, 3), np.int64)
    keys, inv = np.unique(a[:, 2], return_inverse=True)
    out = np.zeros((len(keys), 3), np.int64)
    np.add.at(out[:, 0], inv, a[:, 0])
    np.add.at(out[:, 1], inv, a[:, 1])
    out[:, 2] = keys
    return out


def digest(ctx, resp, params):
    """What is kept of a read: its tasks' partial aggregates merged and
    sorted by key, as bytes (``hash_agg.digest``'s shape)."""
    return merge(r["rows"] for r in resp["responses"]).tobytes()


def check(ctx, records, params, reference):
    """``hash_agg``'s check of the merged answer, then the layout: a
    served read (a record with the reply's ``labels``) that was not
    answered by ``params["regions"]`` cop tasks is marked ``wrong`` (it
    counts as failed and in no latency).  ``control.py``'s record is an
    answer alone and says nothing of the layout.
    → [(name, value, limit)]."""
    checks = _agg.check(ctx, records, params, reference)
    want = str(params["regions"])
    off = 0
    for r in records:
        if "labels" in r and r["labels"].get("cop_tasks") != want:
            r["wrong"] = True
            off += 1
    return checks + [("regions.reads_off_the_layout", off, 0)]
