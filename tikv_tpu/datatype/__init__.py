"""Type system + columnar containers.

Rebuild of the reference's ``components/tidb_query_datatype`` (46k LoC Rust):
``EvalType``/``FieldType`` (eval_type.rs, field_type.rs), the columnar
containers ``VectorValue``/``LazyBatchColumn``/``LazyBatchColumnVec``
(codec/data_type/vector.rs:14, codec/batch/lazy_column.rs:27,
codec/batch/lazy_column_vec.rs:15) — redesigned device-first: a column is a
dense numpy/jax value array plus a validity mask, padded to static tile
shapes so XLA sees fixed shapes (SURVEY.md §7 "Dynamic shapes").
"""

from .eval_type import (EvalType, FieldType, FieldTypeFlag, FieldTypeTp,
                        const_bucket, device_const_dtype)
from .column import Column, ColumnBatch
from .tile import Tile, TileBatch, pad_to_tile, TILE_ROWS

__all__ = [
    "EvalType",
    "FieldType",
    "FieldTypeFlag",
    "FieldTypeTp",
    "Column",
    "ColumnBatch",
    "Tile",
    "TileBatch",
    "pad_to_tile",
    "TILE_ROWS",
]
