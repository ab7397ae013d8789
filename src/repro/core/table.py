"""Host-side column table: the in-memory object the writer/reader exchange."""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.schema import (Field, LogicalType, PhysicalType, Schema,
                               physical_of_numpy)


@dataclasses.dataclass
class StringColumn:
    """Arrow-style string column: int64 offsets (n+1) + utf-8 payload."""

    offsets: np.ndarray  # int64, shape (n+1,)
    payload: np.ndarray  # uint8, shape (offsets[-1],)

    def __post_init__(self) -> None:
        self.offsets = np.ascontiguousarray(self.offsets, dtype=np.int64)
        self.payload = np.ascontiguousarray(self.payload, dtype=np.uint8)
        if self.offsets.ndim != 1 or self.offsets[0] != 0:
            raise ValueError("offsets must be 1-D and start at 0")
        if int(self.offsets[-1]) != self.payload.shape[0]:
            raise ValueError("payload length mismatch with offsets")

    def __len__(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def nbytes(self) -> int:
        return int(self.offsets.nbytes + self.payload.nbytes)

    def lengths(self) -> np.ndarray:
        return np.diff(self.offsets).astype(np.int64)

    def to_pylist(self) -> list[bytes]:
        pay = self.payload.tobytes()
        off = self.offsets
        return [pay[off[i]:off[i + 1]] for i in range(len(self))]

    @staticmethod
    def from_pylist(values: list[str | bytes]) -> "StringColumn":
        bs = [v.encode("utf-8") if isinstance(v, str) else bytes(v)
              for v in values]
        lengths = np.fromiter((len(b) for b in bs), dtype=np.int64,
                              count=len(bs))
        offsets = np.zeros(len(bs) + 1, dtype=np.int64)
        np.cumsum(lengths, out=offsets[1:])
        payload = np.frombuffer(b"".join(bs), dtype=np.uint8).copy()
        return StringColumn(offsets, payload)

    def take(self, idx: np.ndarray) -> "StringColumn":
        idx = np.asarray(idx, dtype=np.int64)
        lens = self.lengths()[idx]
        offsets = np.zeros(len(idx) + 1, dtype=np.int64)
        np.cumsum(lens, out=offsets[1:])
        src = (np.repeat(self.offsets[idx] - offsets[:-1], lens)
               + np.arange(offsets[-1]))
        return StringColumn(offsets, self.payload[src])

    def slice(self, start: int, stop: int) -> "StringColumn":
        off = self.offsets[start:stop + 1]
        pay = self.payload[int(off[0]):int(off[-1])]
        return StringColumn(off - off[0], pay.copy())


ColumnData = np.ndarray | StringColumn


class Table:
    """An ordered mapping of column name -> data with a derived schema."""

    def __init__(self, columns: dict[str, ColumnData],
                 schema: Schema | None = None):
        if not columns:
            raise ValueError("empty table")
        self.columns: dict[str, ColumnData] = {}
        n = None
        for name, col in columns.items():
            if isinstance(col, StringColumn):
                self.columns[name] = col
                m = len(col)
            else:
                arr = np.ascontiguousarray(col)
                if arr.ndim != 1:
                    raise ValueError(f"column {name!r} must be 1-D")
                self.columns[name] = arr
                m = arr.shape[0]
            if n is None:
                n = m
            elif n != m:
                raise ValueError(
                    f"column {name!r} has {m} rows, expected {n}")
        self.num_rows = int(n)
        self.schema = schema if schema is not None else self._infer_schema()
        if set(self.schema.names) != set(self.columns):
            raise ValueError("schema names do not match columns")

    def _infer_schema(self) -> Schema:
        fields = []
        for name, col in self.columns.items():
            if isinstance(col, StringColumn):
                fields.append(Field(name, PhysicalType.BYTE_ARRAY,
                                    LogicalType.STRING))
            else:
                fields.append(Field(name, physical_of_numpy(col.dtype)))
        return Schema(fields)

    def __getitem__(self, name: str) -> ColumnData:
        return self.columns[name]

    def __contains__(self, name: str) -> bool:
        return name in self.columns

    @property
    def names(self) -> list[str]:
        return list(self.columns)

    @property
    def nbytes(self) -> int:
        """Logical raw size — the numerator of *effective bandwidth*."""
        return sum(int(c.nbytes) for c in self.columns.values())

    def select(self, names: list[str]) -> "Table":
        return Table({n: self.columns[n] for n in names},
                     Schema([self.schema.field(n) for n in names]))

    def slice(self, start: int, stop: int) -> "Table":
        stop = min(stop, self.num_rows)
        cols: dict[str, ColumnData] = {}
        for n, c in self.columns.items():
            cols[n] = (c.slice(start, stop) if isinstance(c, StringColumn)
                       else c[start:stop])
        return Table(cols, self.schema)

    def equals(self, other: "Table") -> bool:
        if self.names != other.names or self.num_rows != other.num_rows:
            return False
        for n in self.names:
            a, b = self.columns[n], other.columns[n]
            if isinstance(a, StringColumn) != isinstance(b, StringColumn):
                return False
            if isinstance(a, StringColumn):
                if not (np.array_equal(a.offsets, b.offsets)
                        and np.array_equal(a.payload, b.payload)):
                    return False
            else:
                if a.dtype != b.dtype or not np.array_equal(a, b):
                    return False
        return True

    @staticmethod
    def concat(tables: list["Table"]) -> "Table":
        if not tables:
            raise ValueError("nothing to concat")
        names = tables[0].names
        cols: dict[str, ColumnData] = {}
        for n in names:
            parts = [t.columns[n] for t in tables]
            if isinstance(parts[0], StringColumn):
                lens = np.concatenate([p.lengths() for p in parts])
                offsets = np.zeros(lens.shape[0] + 1, dtype=np.int64)
                np.cumsum(lens, out=offsets[1:])
                payload = np.concatenate([p.payload for p in parts])
                cols[n] = StringColumn(offsets, payload)
            else:
                cols[n] = np.concatenate(parts)
        return Table(cols, tables[0].schema)
