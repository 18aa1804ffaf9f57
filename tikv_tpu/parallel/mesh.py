"""Device mesh construction + sharding helpers.

TiKV's parallelism axes (SURVEY.md §2.8) map onto a 2-D TPU mesh:

- ``range``  — range sharding: a region (contiguous key range) pins to one
  mesh slice the way TiKV pins a region to a store
  (components/raftstore/src/store/worker/split_check.rs drives splits,
  worker/pd.rs balances).  Coarse axis; rides DCN between hosts.
- ``tile``   — in-region buckets: finer-grained parallelism inside one
  region (pd_client/src/lib.rs:118-240 buckets give the coprocessor
  sub-region parallel units).  Fine axis; rides ICI between chips.

Row blocks are sharded over the *flattened* ("range", "tile") product; the
psum-mergeable aggregation states (ops/agg.py) are merged over both axes
and the order-sensitive hash-agg states tree-reduce over an all-to-all by
key bucket (device/runner.py `_finalize` hooks).  This is the
scaling-book recipe: name the axes, annotate shardings, let XLA place
collectives on ICI.

Two ways a multi-chip node uses the mesh (device/placement.py):

- **scale-up** — one large region's feed shards over the whole mesh and
  a single request's kernel runs as per-shard partials + tree-reduce
  (the TiDB partial-at-TiKV / final-at-TiDB split mapped onto ICI);
- **scale-out** — many small hot regions each pin to ONE single-device
  slice (``mesh_slices``), and PD-style placement spreads them across
  chips by load instead of saturating chip 0.

The default shape comes from ``_factor2`` (as square as possible; note a
PRIME device count necessarily degenerates to ``(1, n)`` — every row
block then rides the ``tile`` axis).  Deployments pin an explicit shape
via ``coprocessor.mesh_shape`` ("2x4"), parsed by ``parse_mesh_shape``
and surfaced in ``/health`` (``device_mesh``: the configured shape, the
live one, and what was launched on it).

No rate or latency of this path is quoted here: ``PERF.md`` has every
measured number with its origin, and the scale-up mode is the
benchmark's four-chip cell (``python3 benchmark/run.py --workload
agg-mesh4-closed8 ...`` on a four-chip host, deployment TOML
``benchmark/configs/int3-10m-mesh4.toml``).

A configured device is NOT assumed healthy forever: the failure-domain
supervisor (device/supervisor.py) scores each slice and quarantines a
sick chip, and ``healthy_submesh`` gives the runner the largest
power-of-two survivor set (8→4→2→1) to rebuild sharded serving on —
the degrade ladder is slice → submesh → host, with host only the final
rung (the host link cannot absorb a whole mesh's traffic; Jouppi cost
model, PAPERS.md).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

RANGE_AXIS = "range"
TILE_AXIS = "tile"
ROW_AXES = (RANGE_AXIS, TILE_AXIS)


def _factor2(n: int) -> tuple[int, int]:
    """Split n into (a, b), a*b == n, as square as possible, a <= b.

    ``a`` is the largest divisor of ``n`` not above ``isqrt(n)``, so a
    prime ``n`` (no such divisor but 1) yields ``(1, n)`` — a flat
    single-row mesh, valid but with every device on the ``tile`` axis.
    """
    a = int(math.isqrt(n))
    while a > 1 and n % a:
        a -= 1
    return a, n // a


def parse_mesh_shape(shape: Union[str, Sequence[int], None]
                     ) -> Optional[tuple[int, int]]:
    """Parse an explicit mesh-shape override (``coprocessor.mesh_shape``).

    Accepts ``"RxT"`` / ``"R,T"`` strings or a 2-sequence of ints;
    ``None``/empty means "no override" (``_factor2`` decides).  Raises
    ``ValueError`` on malformed input — a bad config must fail loudly at
    construction, not produce a silently mis-shaped mesh.
    """
    if shape is None:
        return None
    if isinstance(shape, str):
        s = shape.strip().lower()
        if not s:
            return None
        for sep in ("x", ",", "*"):
            if sep in s:
                parts = s.split(sep)
                break
        else:
            raise ValueError(f"mesh_shape {shape!r}: expected 'RxT'")
        if len(parts) != 2:
            raise ValueError(f"mesh_shape {shape!r}: expected 2 factors")
        try:
            r, t = int(parts[0]), int(parts[1])
        except ValueError:
            raise ValueError(f"mesh_shape {shape!r}: non-integer factor")
    else:
        if len(shape) != 2:
            raise ValueError(f"mesh_shape {shape!r}: expected 2 factors")
        r, t = int(shape[0]), int(shape[1])
    if r < 1 or t < 1:
        raise ValueError(f"mesh_shape {shape!r}: factors must be >= 1")
    return r, t


def make_mesh(devices: Optional[Sequence] = None,
              shape: Optional[tuple[int, int]] = None) -> Mesh:
    """Build the ("range", "tile") mesh over the given (default: all)
    devices.  ``shape`` must multiply out to the device count exactly
    (checked) — pass ``parse_mesh_shape(cfg.mesh_shape)`` for the
    config override path."""
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if shape is None:
        shape = _factor2(n)
    if shape[0] * shape[1] != n:
        raise ValueError(
            f"mesh shape {shape} does not cover {n} devices")
    arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, ROW_AXES)


def mesh_slices(mesh: Mesh) -> list:
    """Per-chip placement slices, in flattened ("range", "tile") order.

    Each entry is the device list of ONE single-device slice — the unit
    the placement loop (device/placement.py) assigns hot regions to.
    Slice index ``i`` corresponds to shard index ``i`` of the full
    mesh's row sharding, so per-slice occupancy lines up with the
    sharded kernels' shard numbering in /health — and with the
    failure-domain supervisor's per-slice health scores
    (device/supervisor.py SliceHealthBoard), which use the same
    numbering to quarantine a chip out of both serving modes at once.
    """
    return [[d] for d in mesh.devices.flat]


def healthy_submesh(mesh: Mesh, dead_slices) -> Optional[list]:
    """Devices of the largest healthy power-of-two submesh, or None
    when every slice is dead.

    The elastic-degrade ladder (8→4→2→1, README "Device failure
    domains"): ``dead_slices`` holds flattened slice indices the
    failure-domain supervisor quarantined; the survivors keep their
    flat order and are truncated to the largest power of two, so the
    rebuilt mesh's ``_factor2`` shape stays a clean (R, T) split and
    sharded feeds re-pad to a familiar per-shard unit.  Host fallback
    is the caller's FINAL rung, taken only when this returns None.
    """
    dead = set(dead_slices)
    devs = [d for i, d in enumerate(mesh.devices.flat) if i not in dead]
    if not devs:
        return None
    k = 1 << (len(devs).bit_length() - 1)
    return devs[:k]


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Rows sharded across every device (leading axis)."""
    return NamedSharding(mesh, P(ROW_AXES))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def num_shards(mesh: Mesh) -> int:
    return mesh.devices.size


def pad_rows_for(mesh: Mesh, n_rows: int, multiple: int = 8) -> int:
    """Smallest row count >= n_rows divisible by n_shards * multiple."""
    unit = num_shards(mesh) * multiple
    return max(unit, ((n_rows + unit - 1) // unit) * unit)
