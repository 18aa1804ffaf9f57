"""GROUP BY <key>, COUNT(*), SUM(<value>) at a fresh TSO: the north-star
plan.  ``params``: ``group_by`` and ``sum``, column names of the table."""

import numpy as np

# the fused Pallas kernel, never its XLA stand-ins
CLASSES = ("pallas_hash",)


def prepare(ctx, client, params):
    """The TSO fetch and the plan: the SQL layer's, off the clock."""
    from tikv_tpu.testing.dag import DagSelect
    s = DagSelect.from_table(ctx.table, [c.name for c in ctx.table.columns])
    return s.aggregate([s.col(params["group_by"])],
                       [("count_star", None), ("sum", s.col(params["sum"]))]
                       ).build(start_ts=client.tso())


def send(ctx, client, dag):
    """The timed call."""
    return client.coprocessor(dag, timeout=60)


def to_bf16(x) -> np.ndarray:
    """int64 values rounded to bfloat16 (eight bits of mantissa) and
    back: what the next precision down would serve."""
    bits = np.asarray(x).astype(np.float32).view(np.uint32)
    return ((bits + np.uint32(0x8000)) & np.uint32(0xFFFF0000)).view(
        np.float32).astype(np.int64)


def reference(ctx, params, approx=False) -> np.ndarray:
    """(groups, 3) int64 [count, sum, key] sorted by key, plain numpy.
    ``approx`` serves the sums rounded to bfloat16: the control."""
    keys, inv = np.unique(ctx.cols[params["group_by"]], return_inverse=True)
    cnt = np.bincount(inv, minlength=len(keys)).astype(np.int64)
    # float64 weights are exact here: |sum| stays far under 2**53
    sums = np.bincount(inv, weights=ctx.cols[params["sum"]].astype(
        np.float64), minlength=len(keys)).astype(np.int64)
    return np.stack([cnt, to_bf16(sums) if approx else sums, keys], axis=1)


def digest(ctx, resp, params):
    """What is kept of a reply: its rows sorted by key, as bytes."""
    a = np.array(resp["rows"], dtype=np.int64).reshape(-1, 3)
    return a[np.argsort(a[:, 2], kind="stable")].tobytes()


def check(ctx, records, params, reference):
    """Every answer equals the reference exactly; a record that does not
    is marked ``wrong``.  → [(name, value, limit)]."""
    want = reference.tobytes()
    wrong = 0
    for r in records:
        if r["answer"] != want:
            r["wrong"] = True
            wrong += 1
    return [("hash_agg.wrong_answers", wrong, 0)]
