"""Device tiles: static-shape padded column blocks.

XLA wants static shapes (SURVEY.md §7 "Dynamic shapes": reference batches
grow 32→1024 and the last batch is ragged — tidb_query_executors/src/
runner.rs:38-45). The device representation is therefore a *tile*: a dense
value array padded to a fixed row count plus a validity mask that doubles as
the ragged-tail mask. All device kernels take (values, validity) pairs and
are jit-compiled once per (tile_rows, dtype) bucket.

Device dtype policy (TPU v5e):
- INT  → int32 when the column fits, else int64 (XLA pair-emulates i64);
  aggregation accumulators are always int64.
- REAL → float32 values, float64 *not* used on device; SUM/AVG accumulate
  in float64-emulated pairs on host merge, and in f32 + compensation on
  device (see ops/agg.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from .collation import _PAD_BIN, BINARY, normalize_id
from .column import Column, ColumnBatch
from .eval_type import EvalType, FieldTypeTp

# Default device tile: 1 Mi rows. The reference's BATCH_MAX_SIZE is 1024
# (runner.rs:45) because its unit of work is a CPU cache tile; on TPU the
# unit of work must amortize dispatch + HBM latency, so tiles are large and
# the 8×128 VPU lanes are filled by reshaping to (rows/128, 128) internally.
TILE_ROWS = 1 << 20


# The packed datetime core (datatype/time.py) keeps hour, minute,
# second and microsecond in its low 41 bits.  A column whose FieldType
# is DATE has them all zero, so ``core >> DATE_SHIFT`` (year, month, day:
# under 2**23 for any year below 8192) is a lossless, order-preserving
# int32 plane: the form in which a DATE column reaches the Pallas kernel,
# which takes int32 inputs only.
DATE_SHIFT = 41


def date_plane(values: np.ndarray) -> np.ndarray:
    """A DATE column's packed cores as its int32 plane."""
    return (values >> np.uint64(DATE_SHIFT)).astype(np.int32)


# A CHAR(n) column of at most CODE_MAX_LEN bytes rides the device as its
# CODE plane: a value's bytes as one big-endian integer of the column's
# ``flen`` bytes, NUL-padded on the right (CHAR(1) b"A" is 65; CHAR(2)
# b"A" is 0x4100 and b"AB" 0x4142).  Codes keep the order and the
# equality of the raw bytes, which is how the host pipeline compares and
# groups a binary or ``_bin`` string, so a GROUP BY over codes is exact
# and nothing (no dictionary) has to be kept in step with writes.
CODE_MAX_LEN = 4


def code_width(ft) -> Optional[int]:
    """The bytes a CHAR column of FieldType ``ft`` has on its code
    plane, or None where it has none: wider than ``CODE_MAX_LEN``
    characters, of unknown width, or under a collation whose order is
    not the bytes' (``_ci``: the host pipeline is where a collation is
    applied).  A value of more bytes than characters (multi-byte UTF-8)
    is found where the plane is cut (``code_plane``)."""
    if ft.tp not in (FieldTypeTp.STRING, FieldTypeTp.VAR_CHAR,
                     FieldTypeTp.VAR_STRING) or \
            not 0 < ft.flen <= CODE_MAX_LEN:
        return None
    coll = normalize_id(ft.collation)
    if coll != BINARY and coll not in _PAD_BIN:
        return None
    return ft.flen


def code_plane(values: np.ndarray, width: int) -> Optional[np.ndarray]:
    """An object array of ``bytes`` as the int64 codes of a column
    ``width`` bytes wide, or None where a value has no code that gives
    it back: longer than ``width``, or holding a NUL byte (the pad)."""
    n = len(values)
    if not n:
        return np.zeros(0, np.int64)
    try:
        # (straight to the column's width, one pass: a cast to ``S``
        # first sizes itself by a pass of its own, three times as dear
        # at 500,102 rows; a value the width cuts fails the count below)
        fixed = values.astype(f"S{width}")
        held = sum(map(len, values.tolist()))
    except (TypeError, ValueError):
        return None             # not bytes
    u8 = np.ascontiguousarray(fixed).view(np.uint8).reshape(n, width)
    nz = u8 != 0
    # NULs only as the pad: a suffix of every row, and as many bytes
    # kept as the values hold (``S`` drops trailing NULs unseen, and
    # the cast the bytes past the width)
    if width > 1 and not (nz[:, 1:] <= nz[:, :-1]).all():
        return None
    if int(nz.sum()) != held:
        return None
    codes = np.zeros(n, np.int64)
    for k in range(width):
        codes |= u8[:, k].astype(np.int64) << (8 * (width - 1 - k))
    return codes


def code_bytes(codes: np.ndarray, width: int) -> np.ndarray:
    """``code_plane``'s inverse: the object array of ``bytes``."""
    codes = np.asarray(codes, np.int64)
    u8 = np.empty((len(codes), width), np.uint8)
    for k in range(width):
        u8[:, k] = (codes >> (8 * (width - 1 - k))) & 0xFF
    out = np.empty(len(codes), dtype=object)
    out[:] = u8.view(f"S{width}").reshape(-1).tolist() if len(codes) \
        else []
    return out


def _device_dtype(eval_type: EvalType, values: np.ndarray) -> np.dtype:
    if eval_type is EvalType.DECIMAL:
        # the scaled form only (Column.frac): an int64 array narrowed
        # by its values as any INT column is
        if values.dtype.kind != "i":
            raise ValueError("an unscaled DECIMAL column has no "
                             "device-native representation")
        eval_type = EvalType.INT
    if eval_type in (EvalType.INT, EvalType.DURATION):
        if values.size and (values.min() < -(2**31) or values.max() >= 2**31):
            return np.dtype(np.int64)
        return np.dtype(np.int32)
    if eval_type is EvalType.REAL:
        return np.dtype(np.float32)
    if eval_type in (EvalType.DATETIME, EvalType.ENUM, EvalType.SET):
        return np.dtype(np.uint32) if not values.size or values.max() < 2**32 \
            else np.dtype(np.uint64)
    raise ValueError(f"{eval_type} has no device-native representation")


def pad_to_tile(values: np.ndarray, validity: np.ndarray,
                tile_rows: int = TILE_ROWS,
                dtype: Optional[np.dtype] = None) -> tuple[np.ndarray, np.ndarray]:
    """Pad a ragged column to ``tile_rows`` with invalid zero rows."""
    n = len(values)
    assert n <= tile_rows, (n, tile_rows)
    out_dtype = dtype if dtype is not None else values.dtype
    v = np.zeros(tile_rows, dtype=out_dtype)
    v[:n] = values.astype(out_dtype, copy=False)
    m = np.zeros(tile_rows, dtype=np.bool_)
    m[:n] = validity
    return v, m


@dataclass
class Tile:
    """One device-ready column block: padded values + validity mask.

    ``n_rows`` is the logical (unpadded) row count; rows >= n_rows have
    validity False.
    """

    eval_type: EvalType
    values: np.ndarray      # shape (tile_rows,), device dtype
    validity: np.ndarray    # shape (tile_rows,), bool
    n_rows: int

    @staticmethod
    def from_column(col: Column, tile_rows: int = TILE_ROWS,
                    dtype: Optional[np.dtype] = None) -> "Tile":
        dt = dtype if dtype is not None else _device_dtype(col.eval_type, col.values)
        v, m = pad_to_tile(col.values, col.validity, tile_rows, dt)
        return Tile(col.eval_type, v, m, len(col))


@dataclass
class TileBatch:
    """A batch of tiles sharing one row dimension — the unit shipped to
    device kernels. Mirrors ColumnBatch at device granularity."""

    tiles: list[Tile]
    n_rows: int
    tile_rows: int

    @staticmethod
    def from_batch(batch: ColumnBatch, tile_rows: int = TILE_ROWS) -> list["TileBatch"]:
        """Split a ColumnBatch into tile-sized chunks (last one padded).

        The device dtype is decided once per *column* (whole-column range),
        not per chunk — otherwise one column's tiles could mix int32/int64
        and defeat the per-(shape, dtype) jit cache.
        """
        dtypes = [_device_dtype(c.eval_type, c.values) for c in batch.columns]
        out = []
        for start in range(0, max(batch.num_rows, 1), tile_rows):
            chunk = batch.slice(start, min(start + tile_rows, batch.num_rows))
            tiles = [Tile.from_column(c, tile_rows, dtype=dt)
                     for c, dt in zip(chunk.columns, dtypes)]
            out.append(TileBatch(tiles, chunk.num_rows, tile_rows))
        return out


def column_chunks(values: np.ndarray, validity: np.ndarray,
                  tile_rows: int = TILE_ROWS):
    """Yield (padded_values, padded_validity, n) chunks for streaming feeds."""
    n_total = len(values)
    for start in range(0, max(n_total, 1), tile_rows):
        stop = min(start + tile_rows, n_total)
        if stop - start == tile_rows:
            yield values[start:stop], validity[start:stop], tile_rows
        else:
            v, m = pad_to_tile(values[start:stop], validity[start:stop], tile_rows)
            yield v, m, stop - start
