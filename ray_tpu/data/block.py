"""Blocks — the unit of data movement.

Role-equivalent of python/ray/data/block.py :: Block / BlockAccessor /
BlockMetadata (SURVEY §2.7). A Block is an Arrow table (canonical), a
pandas DataFrame, or a dict of numpy columns; BlockAccessor normalizes
access. Blocks live in the object store between operators — Arrow's
columnar buffers serialize as out-of-band pickle-5 buffers, so hand-off is
zero-copy on the read side (the same economics as the reference's plasma
blocks).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Iterator, Optional

import numpy as np
import pyarrow as pa


@dataclass
class BlockMetadata:
    num_rows: int
    size_bytes: int
    schema: Optional[Any] = None
    input_files: list[str] = field(default_factory=list)
    exec_stats: Optional[dict] = None


@dataclass
class DataContext:
    """Global knobs — reference: python/ray/data/context.py :: DataContext.
    target_max_block_size mirrors the ~128 MiB default."""

    target_max_block_size: int = 128 * 1024 * 1024
    target_min_block_size: int = 1 * 1024 * 1024
    read_op_min_num_blocks: int = 8
    actor_pool_min_size: int = 1
    actor_pool_max_size: int = 4
    streaming_max_inflight_tasks: int = 8
    # Object-store BYTE budget for streaming admission (the reference's
    # ReservationOpResourceAllocator role): no new task launches while
    # store usage exceeds this fraction of arena capacity — a task-count
    # window alone lets a large-block pipeline overrun the arena.
    # Progress is always guaranteed (>=1 task stays admitted). Counts
    # TOTAL usage including results the consumer retains: a caller
    # holding more than the budget deliberately degrades the pipeline
    # toward serial (spill-pressure beats arena overrun).
    streaming_store_budget_fraction: float = 0.75
    eager_free: bool = True

    _current: "DataContext | None" = None

    @classmethod
    def get_current(cls) -> "DataContext":
        if cls._current is None:
            cls._current = cls()
        return cls._current


class BlockAccessor:
    """Normalized view over any block representation."""

    def __init__(self, block: Any):
        self._block = block

    @staticmethod
    def for_block(block: Any) -> "BlockAccessor":
        return BlockAccessor(_normalize(block))

    @property
    def block(self) -> pa.Table:
        return self._block

    def num_rows(self) -> int:
        return self._block.num_rows

    def size_bytes(self) -> int:
        return self._block.nbytes

    def schema(self):
        return self._block.schema

    def metadata(self, input_files: list[str] | None = None) -> BlockMetadata:
        return BlockMetadata(
            num_rows=self.num_rows(),
            size_bytes=self.size_bytes(),
            schema=self.schema(),
            input_files=input_files or [],
        )

    def to_arrow(self) -> pa.Table:
        return self._block

    def to_pandas(self):
        return self._block.to_pandas()

    def to_numpy(self, columns: list[str] | None = None) -> dict[str, np.ndarray]:
        table = self._block
        names = columns or table.column_names
        out = {}
        for name in names:
            col = table.column(name)
            try:
                out[name] = _chunked_to_numpy(col)
            except (pa.ArrowInvalid, ValueError):
                out[name] = np.asarray(col.to_pylist(), dtype=object)
        return out

    def iter_rows(self) -> Iterator[dict]:
        yield from self._block.to_pylist()

    def slice(self, start: int, end: int) -> pa.Table:
        return self._block.slice(start, end - start)

    def take(self, indices) -> pa.Table:
        return self._block.take(pa.array(indices))

    def select(self, columns: list[str]) -> pa.Table:
        return self._block.select(columns)

    def sample(self, n: int, rng: np.random.Generator) -> pa.Table:
        n = min(n, self.num_rows())
        idx = rng.choice(self.num_rows(), size=n, replace=False)
        return self.take(np.sort(idx))

    @staticmethod
    def concat(blocks: list[Any]) -> pa.Table:
        tables = [_normalize(b) for b in blocks if _normalize(b).num_rows > 0]
        if not tables:
            return pa.table({})
        return pa.concat_tables(tables, promote_options="permissive")

    @staticmethod
    def builder() -> "BlockBuilder":
        return BlockBuilder()


class BlockBuilder:
    """Accumulate rows/batches, emit blocks at a target size."""

    def __init__(self):
        self._tables: list[pa.Table] = []
        self._rows: list[dict] = []
        self._size = 0

    def add_row(self, row: dict) -> None:
        self._rows.append(row)
        self._size += sum(_rough_size(v) for v in row.values())

    def add_block(self, block: Any) -> None:
        table = _normalize(block)
        if table.num_rows:
            self._tables.append(table)
            self._size += table.nbytes

    def size_bytes(self) -> int:
        return self._size

    def num_rows(self) -> int:
        return sum(t.num_rows for t in self._tables) + len(self._rows)

    def build(self) -> pa.Table:
        if self._rows:
            self._tables.append(_rows_to_table(self._rows))
            self._rows = []
        if not self._tables:
            return pa.table({})
        out = pa.concat_tables(self._tables, promote_options="permissive")
        self._tables = [out]
        return out


def _chunked_to_numpy(col: pa.ChunkedArray) -> np.ndarray:
    if col.num_chunks == 1:
        chunk = col.chunk(0)
        if isinstance(chunk, (pa.FixedSizeListArray, pa.ListArray)):
            return _list_array_to_numpy(chunk)
        return chunk.to_numpy(zero_copy_only=False)
    if col.num_chunks and isinstance(
        col.chunk(0), (pa.FixedSizeListArray, pa.ListArray)
    ):
        return np.concatenate([_list_array_to_numpy(c) for c in col.chunks])
    return col.to_numpy()


def _list_array_to_numpy(arr) -> np.ndarray:
    """Tensor columns stored as nested fixed-size list arrays → stacked
    ndarray with the original trailing shape restored."""
    if isinstance(arr, pa.FixedSizeListArray):
        shape = []
        atype = arr.type
        values = arr
        while pa.types.is_fixed_size_list(atype):
            shape.append(atype.list_size)
            # flatten(), not .values: a batch is a SLICE of its block, and
            # .values ignores the slice's offset and length (the reshape
            # below then failed and every batch fell back to to_pylist()).
            values = values.flatten()
            atype = atype.value_type
        flat = values.to_numpy(zero_copy_only=False)
        return flat.reshape((len(arr), *shape))
    return np.asarray(arr.to_pylist(), dtype=object)


def _rows_to_table(rows: list[dict]) -> pa.Table:
    if not rows:
        return pa.table({})
    columns: dict[str, list] = {k: [] for k in rows[0]}
    for row in rows:
        for key in columns:
            columns[key].append(row.get(key))
    return _normalize(columns)


def _normalize(block: Any) -> pa.Table:
    """Canonicalize to Arrow. ndarray values become tensor (list) columns."""
    if isinstance(block, pa.Table):
        return block
    if isinstance(block, dict):
        arrays = {}
        for name, values in block.items():
            arrays[name] = _column_to_arrow(values)
        return pa.table(arrays)
    if isinstance(block, list):
        return _rows_to_table(block)
    try:
        import pandas as pd

        if isinstance(block, pd.DataFrame):
            return pa.Table.from_pandas(block, preserve_index=False)
    except ImportError:
        pass
    raise TypeError(f"cannot treat {type(block).__name__} as a block")


def _column_to_arrow(values: Any) -> pa.Array:
    if isinstance(values, pa.Array):
        return values
    arr = np.asarray(values)
    if arr.ndim > 1:
        out = pa.array(arr.reshape(-1))
        for dim in reversed(arr.shape[1:]):
            out = pa.FixedSizeListArray.from_arrays(out, dim)
        return out
    if arr.dtype == object:
        return pa.array(list(values))
    return pa.array(arr)


def _rough_size(value: Any) -> int:
    if isinstance(value, np.ndarray):
        return value.nbytes
    if isinstance(value, (bytes, str)):
        return len(value)
    return 8


@dataclass
class ExecStats:
    """Per-task execution stats feeding DatasetStats (SURVEY §2.7)."""

    wall_s: float = 0.0
    rows: int = 0
    blocks: int = 0

    @staticmethod
    def timer():
        return time.perf_counter()
