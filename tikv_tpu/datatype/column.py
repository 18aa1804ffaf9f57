"""Host-side columnar containers.

Reference: components/tidb_query_datatype/src/codec/data_type/vector.rs:14
(``VectorValue`` — an enum of ChunkedVec per eval type, each a value vec +
null bitmap) and codec/batch/lazy_column.rs:27 (``LazyBatchColumn`` — raw
encoded datums OR decoded vector). The TPU-first redesign drops the per-value
chunked encoding in favour of dense numpy arrays + boolean validity mask —
the layout the device consumes directly — and keeps the raw-vs-decoded split
at batch granularity: a column is either ``raw`` (list of undecoded datum
bytes, produced by scans) or ``decoded`` (dense arrays).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Optional, Sequence

import numpy as np

from .eval_type import EvalType, FieldType


class Column:
    """A dense column: value array + validity mask.

    ``values`` is a numpy array of the eval type's host dtype; entries where
    ``validity`` is False are NULL (their value slot is unspecified but must
    be a *harmless* value — 0 — so device kernels never see NaN/garbage).

    For BYTES/JSON, ``values`` is a 1-D object array of ``bytes``.

    A DECIMAL column has two forms.  The host pipeline's is an object
    array of ``decimal.Decimal`` (``frac`` None).  Where the column's
    FieldType fixes its scale and every value fits it, the columnar
    cache holds it SCALED: ``values`` is an int64 array of
    ``value * 10**frac`` (DECIMAL(15,2) 0.06 is 6) and ``frac`` that
    scale, the form the device feeds are cut from.  ``unscaled()``
    gives the object form; the two never mix in one column.
    """

    __slots__ = ("eval_type", "values", "validity", "frac")

    def __init__(self, eval_type: EvalType, values: np.ndarray,
                 validity: np.ndarray, frac: Optional[int] = None):
        assert values.shape == validity.shape, (values.shape, validity.shape)
        self.eval_type = eval_type
        self.values = values
        self.validity = validity
        self.frac = frac

    # -- constructors -------------------------------------------------------

    @staticmethod
    def empty(eval_type: EvalType) -> "Column":
        return Column(
            eval_type,
            np.empty(0, dtype=eval_type.np_dtype),
            np.empty(0, dtype=np.bool_),
        )

    @staticmethod
    def from_list(eval_type: EvalType, items: Sequence,
                  unsigned: bool = False) -> "Column":
        """Build from a Python list where ``None`` means NULL.

        ``unsigned``: the column is declared UNSIGNED (FieldType flag) —
        the container is uint64 regardless of which values appear, so
        per-batch builds of the same column never mix int64/uint64
        (np.concatenate would silently promote that mix to float64).
        """
        n = len(items)
        validity = np.fromiter((x is not None for x in items), dtype=np.bool_, count=n)
        dtype = eval_type.np_dtype
        if dtype == np.dtype(object):
            # NULL slots hold a harmless same-type value so vectorized
            # object ops never mix representations (frompyfunc sigs run
            # over masked slots too)
            if eval_type is EvalType.DECIMAL:
                from .mydecimal import ZERO as fill
            elif eval_type is EvalType.JSON:
                fill = None     # the JSON null literal
            else:
                fill = b""
            values = np.empty(n, dtype=object)
            for i, x in enumerate(items):
                values[i] = x if x is not None else fill
        else:
            if dtype == np.int64 and (unsigned or any(
                    x is not None and x >= 1 << 63 for x in items)):
                # unsigned BIGINT domain lives above 2^63: keep the
                # container uint64 — INT columns carry signedness via
                # FieldType
                dtype = np.dtype(np.uint64)
            values = np.zeros(n, dtype=dtype)
            for i, x in enumerate(items):
                if x is not None:
                    values[i] = x
        return Column(eval_type, values, validity)

    @staticmethod
    def from_values(eval_type: EvalType, values: np.ndarray,
                    validity: Optional[np.ndarray] = None) -> "Column":
        if validity is None:
            validity = np.ones(values.shape, dtype=np.bool_)
        return Column(eval_type, values, validity)

    # -- accessors ----------------------------------------------------------

    def __len__(self) -> int:
        return len(self.values)

    def unscaled(self) -> "Column":
        """The host pipeline's form of this column: a scaled DECIMAL as
        an object array of ``Decimal``s at its scale (exact: an int
        and a power of ten); any other column is itself."""
        if self.frac is None:
            return self
        from .mydecimal import from_scaled
        values = np.empty(len(self.values), dtype=object)
        frac = self.frac
        values[:] = [from_scaled(v, frac) for v in self.values.tolist()]
        return Column(self.eval_type, values, self.validity)

    def get(self, i: int):
        """Scalar accessor: value or None."""
        if not self.validity[i]:
            return None
        v = self.values[i]
        if self.frac is not None:
            from .mydecimal import from_scaled
            return from_scaled(int(v), self.frac)
        if isinstance(v, np.generic):
            return v.item()
        return v

    def to_list(self) -> list:
        return [self.get(i) for i in range(len(self))]

    def null_count(self) -> int:
        return int(len(self) - self.validity.sum())

    # -- mutation (builder-style; used by executors assembling output) ------

    def take(self, indices: np.ndarray) -> "Column":
        return Column(self.eval_type, self.values[indices],
                      self.validity[indices], self.frac)

    def filter(self, mask: np.ndarray) -> "Column":
        return Column(self.eval_type, self.values[mask],
                      self.validity[mask], self.frac)

    def slice(self, start: int, stop: int) -> "Column":
        return Column(self.eval_type, self.values[start:stop],
                      self.validity[start:stop], self.frac)

    @staticmethod
    def concat(cols: Sequence["Column"]) -> "Column":
        assert cols
        et = cols[0].eval_type
        if len({c.frac for c in cols}) > 1:
            cols = [c.unscaled() for c in cols]
        return Column(
            et,
            np.concatenate([c.values for c in cols]),
            np.concatenate([c.validity for c in cols]),
            cols[0].frac,
        )

    def __repr__(self) -> str:
        return f"Column<{self.eval_type.value}>[{len(self)}]"


@dataclass
class ColumnBatch:
    """A batch of rows in columnar form.

    Reference: codec/batch/lazy_column_vec.rs:15 (``LazyBatchColumnVec``).
    ``schema`` gives each column's FieldType; ``columns`` the data. Executors
    hand these down the pipeline (pull model, reference
    tidb_query_executors/src/interface.rs:21).
    """

    schema: list[FieldType]
    columns: list[Column]

    def __post_init__(self):
        assert len(self.schema) == len(self.columns)
        if self.columns:
            n = len(self.columns[0])
            assert all(len(c) == n for c in self.columns), \
                [len(c) for c in self.columns]

    @property
    def num_rows(self) -> int:
        return len(self.columns[0]) if self.columns else 0

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    @staticmethod
    def empty(schema: Iterable[FieldType]) -> "ColumnBatch":
        schema = list(schema)
        return ColumnBatch(schema, [Column.empty(ft.eval_type) for ft in schema])

    def filter(self, mask: np.ndarray) -> "ColumnBatch":
        return ColumnBatch(self.schema, [c.filter(mask) for c in self.columns])

    def take(self, indices: np.ndarray) -> "ColumnBatch":
        return ColumnBatch(self.schema, [c.take(indices) for c in self.columns])

    def slice(self, start: int, stop: int) -> "ColumnBatch":
        return ColumnBatch(self.schema, [c.slice(start, stop) for c in self.columns])

    @staticmethod
    def concat(batches: Sequence["ColumnBatch"]) -> "ColumnBatch":
        assert batches
        return ColumnBatch(
            batches[0].schema,
            [Column.concat([b.columns[i] for b in batches])
             for i in range(batches[0].num_cols)],
        )

    def rows(self) -> list[tuple]:
        """Materialize as Python rows (tests / response encoding)."""
        return [tuple(c.get(i) for c in self.columns) for i in range(self.num_rows)]
