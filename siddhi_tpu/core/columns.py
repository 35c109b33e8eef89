"""Columnar chunk payloads and parsers for the zero-object edge.

The columnar interior (``tpu/host_exec.py``, PR 5) and the fleet lanes
(PR 6) outrun the per-event Python edge: every source payload used to cross
``SourceMapper.map`` → per-event list → ``InputHandler.send``, and every
sink emission re-materialized scalar ``Event`` objects. This module is the
shared vocabulary that closes the gap (Hazelcast Jet's lesson, PAPERS.md
2103.10169 — saturation-grade engines win or lose at the edge):

- :class:`RowsChunk` — the columnar transport payload (one dict of numpy
  columns + an int64 timestamp column), accepted end-to-end by
  ``InputHandler.send_columns``, the in-memory broker, and rows-capable
  sinks;
- :class:`DictColumn` — a dictionary-encoded string column (int32 codes +
  a shared append-only value table) with cached code translation into an
  engine ``StringDictionary``, so strings cross the edge as integers;
- :class:`CsvColumnParser` — raw CSV line bytes → columns, through the
  ``native/ingress.cpp`` C ABI when a toolchain exists (parse,
  dictionary-encode and SoA staging all native) with a pure-Python
  fallback;
- :class:`ColumnsOut` — a query's columnar output chunk (decoded lazily;
  rows materialize only when a consumer genuinely needs per-event shape);
- ``unpack_columns`` — the DCN ``pack_rows`` SoA wire format decoded
  straight into columns (the socket source shares that format, see
  DISTRIBUTED.md).

Zero-object contract: none of the hot functions here construct ``Event`` /
``StreamEvent`` objects (pinned by ``scripts/check_rows_path.py``); rows
materialize only in explicit fallback helpers.
"""

from __future__ import annotations

import struct
import time
from typing import Any, Optional

import numpy as np

from ..query_api.definition import DataType, StreamDefinition

# host-side CSV type chars → numpy host policy (NP_HOST): INT/LONG parse as
# int64, FLOAT/DOUBLE as float64 (full precision — the native path uses the
# wide emit, sp_emit_lane_wide), STRING dictionary-encodes, BOOL is uint8
TYPE_CHARS = {
    DataType.STRING: "s",
    DataType.INT: "l",
    DataType.LONG: "l",
    DataType.FLOAT: "d",
    DataType.DOUBLE: "d",
    DataType.BOOL: "b",
}

_CHAR_NP = {"s": np.int32, "l": np.int64, "d": np.float64, "b": np.bool_}


def type_chars(definition: StreamDefinition) -> str:
    """Per-attribute parse type chars for a stream definition."""
    chars = []
    for a in definition.attributes:
        c = TYPE_CHARS.get(a.type)
        if c is None:
            raise TypeError(
                f"attribute '{a.name}': {a.type.value} columns cannot cross "
                f"the columnar edge (host-only)")
        chars.append(c)
    return "".join(chars)


class DictColumn:
    """Dictionary-encoded string column: int32 ``codes`` into an append-only
    ``values`` table (index 0 = None). ``source`` identifies the table owner
    (e.g. the parser) so translations into engine dictionaries cache there.
    """

    __slots__ = ("codes", "values", "source")

    def __init__(self, codes: np.ndarray, values: list, source: Any = None):
        self.codes = np.asarray(codes, dtype=np.int32)
        self.values = values
        self.source = source if source is not None else self

    def __len__(self) -> int:
        return int(self.codes.shape[0])

    def __getitem__(self, item) -> "DictColumn":
        return DictColumn(self.codes[item], self.values, self.source)

    def materialize(self) -> np.ndarray:
        """→ object array of the decoded values (None for code 0)."""
        table = np.empty(len(self.values), dtype=object)
        table[:] = self.values
        return table[np.clip(self.codes, 0, len(self.values) - 1)]

    def tolist(self) -> list:
        vals = self.values
        return [vals[c] for c in self.codes.tolist()]


def encode_dict_column(col: DictColumn, dictionary) -> np.ndarray:
    """Translate a :class:`DictColumn`'s codes into ``dictionary`` codes via
    a cached per-(source, dictionary) translation table — one ``np.take``
    per chunk, no per-row Python."""
    src = col.source
    cache = getattr(src, "_dict_trans", None)
    if cache is None:
        cache = {}
        try:
            src._dict_trans = cache
        except AttributeError:      # pragma: no cover — frozen source
            pass
    key = id(dictionary)
    gen = getattr(dictionary, "generation", 0)
    got = cache.get(key)
    trans = got[1] if got is not None and got[0] == gen else None
    # a dictionary RESTORE remaps values→codes in place (generation bump):
    # a cached translation would then silently emit the old codes, so a
    # generation mismatch drops the cache wholesale
    nv = len(col.values)
    if trans is None or trans.shape[0] < nv:
        old = 0 if trans is None else trans.shape[0]
        ext = np.empty(nv, dtype=np.int32)
        if old:
            ext[:old] = trans
        for i in range(old, nv):
            ext[i] = dictionary.encode(col.values[i])
        trans = ext
        cache[key] = (gen, trans)
    return trans[np.clip(col.codes, 0, nv - 1)]


def column_length(col) -> int:
    if isinstance(col, DictColumn):
        return len(col)
    if isinstance(col, np.ndarray):
        return int(col.shape[0])
    return len(col)


def column_tolist(col) -> list:
    if isinstance(col, DictColumn):
        return col.tolist()
    if isinstance(col, np.ndarray):
        return col.tolist()
    return list(col)


def columns_to_rows(cols: dict, names: list, n: int) -> list[list]:
    """Materialize per-event row lists from a columns dict — the explicit
    fallback for non-columnar consumers (NOT the hot path)."""
    if n == 0:
        return []
    py = [column_tolist(cols[name]) for name in names]
    return [list(r) for r in zip(*py)]


class RowsChunk:
    """One columnar transport chunk: ``cols`` maps attribute name →
    numpy array / :class:`DictColumn`; ``ts`` is int64 per-row event time
    (None → the engine stamps ingestion time at ``send_columns``)."""

    __slots__ = ("cols", "ts", "count")

    def __init__(self, cols: dict, ts: Optional[np.ndarray] = None,
                 count: Optional[int] = None):
        self.cols = cols
        self.ts = None if ts is None else np.asarray(ts, dtype=np.int64)
        if count is None:
            count = int(self.ts.shape[0]) if self.ts is not None \
                else (column_length(next(iter(cols.values()))) if cols else 0)
        self.count = count

    def __len__(self) -> int:
        return self.count

    def rows(self, names: list) -> list[list]:
        return columns_to_rows(self.cols, names, self.count)

    def __repr__(self) -> str:
        return f"RowsChunk({self.count} rows x {len(self.cols)} cols)"


class ColumnsOut:
    """A query's columnar output chunk: raw plan columns (strings still
    dictionary codes) + the specs/dictionaries that decode them. Decoding
    and row materialization are lazy — the zero-object egress hands
    ``decoded()`` columns to rows-capable sinks and never builds rows.

    The unit of egress of every batched tier: the host runtimes' ``process``
    and the device runtimes' ``collect`` both return one. ``ts`` is the
    per-row event time (the host tier's), or None until the device bridge
    stamps the batch's emit time on it (``stamp``). ``nulls`` maps a column
    name to a bool mask of its NULL cells (outer joins, absent pattern
    states). An empty chunk is falsy."""

    __slots__ = ("ts", "cols", "n", "specs", "dictionaries", "nulls",
                 "_decoded", "_rows")

    def __init__(self, ts: Optional[np.ndarray], cols: dict, n: int,
                 specs: list, dictionaries: dict,
                 nulls: Optional[dict] = None):
        self.ts = ts
        self.cols = cols
        self.n = n
        self.specs = specs              # [(name, fn, DataType)]
        self.dictionaries = dictionaries
        self.nulls = nulls
        self._decoded = None
        self._rows = None

    def __len__(self) -> int:
        return self.n

    @classmethod
    def empty(cls, specs: list, dictionaries: dict) -> "ColumnsOut":
        return cls(None, {name: np.empty(0, dtype=object)
                          for (name, _fn, _t) in specs}, 0, specs,
                   dictionaries)

    @classmethod
    def concat(cls, chunks: list) -> "ColumnsOut":
        """The chunks' rows in order as one chunk (a hopping window's
        drain steps behind their batch's own step). Unstamped chunks only."""
        first = chunks[0]
        if len(chunks) == 1:
            return first
        names = [name for (name, _fn, _t) in first.specs]
        cols = {name: np.concatenate([c.cols[name] for c in chunks])
                for name in names}
        nulls = None
        if first.nulls is not None:
            nulls = {name: np.concatenate([c.nulls[name] for c in chunks])
                     for name in first.nulls}
        return cls(None, cols, sum(c.n for c in chunks), first.specs,
                   first.dictionaries, nulls)

    def stamp(self, ts: int) -> None:
        """One emit time on every row (the device tier's batch stamp)."""
        self.ts = np.full(self.n, ts, dtype=np.int64)

    def decoded(self) -> dict:
        """{name: numpy column} with dictionary codes decoded to value
        object arrays and NULL cells set to None — the payload
        ``StreamJunction.deliver_columns`` carries to rows-capable
        receivers."""
        if self._decoded is None:
            out = {}
            table = next(iter(self.dictionaries.values()), None)
            vals = None         # the dictionary's value table, built once
            nulls = self.nulls or {}
            for (name, _fn, t) in self.specs:
                v = self.cols[name]
                if t == DataType.STRING and table is not None:
                    if vals is None:
                        vals = np.empty(len(table._values), dtype=object)
                        vals[:] = table._values
                    v = vals[np.clip(np.asarray(v, np.int64), 0,
                                     len(vals) - 1)]
                else:
                    v = np.asarray(v)
                null = nulls.get(name)
                if null is not None and null.any():
                    v = v.astype(object)
                    v[null] = None
                out[name] = v
            self._decoded = out
        return self._decoded

    def rows(self) -> list[list]:
        """Host rows of Python scalars: whole columns through ``tolist()``
        (C-side), one ``zip`` — no per-cell Python."""
        if self._rows is None:
            self._rows = columns_to_rows(
                self.decoded(), [name for (name, _fn, _t) in self.specs],
                self.n)
        return self._rows

    def ts_list(self) -> list:
        return np.asarray(self.ts).tolist()


# ---------------------------------------------------------------------------
# CSV → columns parsers
# ---------------------------------------------------------------------------

def _py_bool(field: bytes) -> bool:
    return field.lower() == b"true" or field == b"1"


class CsvColumnParser:
    """Raw CSV line bytes → :class:`RowsChunk` list.

    Native path (``native/ingress.cpp`` via ctypes): parse, dictionary
    encode and SoA staging run in C++; Python only wraps the emitted numpy
    arrays (wide emit — doubles keep float64 for interpreter parity).
    Pure-Python fallback when no toolchain exists: same column layout, same
    malformed-line accounting, built from per-line splits.

    ``ts_last=True`` reads a trailing int64 event-time field per line
    (the DCN convention); otherwise ``ts`` is None and the
    engine stamps arrival time.
    """

    def __init__(self, definition: StreamDefinition, ts_last: bool = False,
                 capacity: int = 65536):
        self.definition = definition
        self.types = type_chars(definition)
        self.names = definition.attribute_names
        self.ts_last = ts_last
        self.capacity = int(capacity)
        self.rows_out = 0
        self.bytes_in = 0
        self.parse_seconds = 0.0
        self._t_first = None
        self._py_errors = 0
        self._ning = None
        self._values: list = [None]     # native dict mirror (code 0 = None)
        self.ingress = "python"
        try:
            from ..native import NativeIngress, native_available
            if native_available():
                self._ning = NativeIngress(self.types, key_col=-1,
                                           n_lanes=1, capacity=self.capacity)
                self.ingress = "native"
        except Exception:   # noqa: BLE001 — toolchain probe; python fallback
            self._ning = None

    @property
    def parse_errors(self) -> int:
        if self._ning is not None:
            return int(self._ning.parse_errors) + self._py_errors
        return self._py_errors

    @property
    def rows_per_s(self) -> float:
        return self.rows_out / self.parse_seconds if self.parse_seconds \
            else 0.0

    def parse(self, payload: bytes) -> list[RowsChunk]:
        """Whole lines only (the caller frames torn tails); returns the
        parsed chunks (several when a payload overflows one staging
        buffer)."""
        t0 = time.perf_counter()
        self.bytes_in += len(payload)
        if self._ning is not None:
            chunks = self._parse_native(payload)
        else:
            chunks = self._parse_python(payload)
        self.parse_seconds += time.perf_counter() - t0
        for ch in chunks:
            self.rows_out += ch.count
        return chunks

    # -- native ------------------------------------------------------------
    def _sync_values(self) -> None:
        ning = self._ning
        ds = int(ning.dict_size())
        vals = self._values
        while len(vals) < ds:
            vals.append(ning.decode(len(vals)))

    def _parse_native(self, payload: bytes) -> list[RowsChunk]:
        ning = self._ning
        chunks: list[RowsChunk] = []
        pos, total = 0, len(payload)
        while pos < total:
            consumed = ning.ingest_csv(payload, ts_last=self.ts_last,
                                       final=True, offset=pos)
            pos += consumed
            n = int(ning.lane_len(0))
            if n == 0:
                if consumed == 0:
                    break               # nothing staged, nothing consumed
                continue
            b = ning.emit_lane(0, wide=True)
            self._sync_values()
            cols: dict[str, Any] = {}
            for i, (name, t) in enumerate(zip(self.names, self.types)):
                arr = b["cols"][i][:n]
                if t == "s":
                    cols[name] = DictColumn(arr, self._values, source=self)
                else:
                    cols[name] = arr
            chunks.append(RowsChunk(
                cols, b["ts"][:n] if self.ts_last else None, n))
        return chunks

    # -- pure python -------------------------------------------------------
    def _parse_python(self, payload: bytes) -> list[RowsChunk]:
        names, types = self.names, self.types
        ncols = len(types)
        expected = ncols + (1 if self.ts_last else 0)
        raw_cols: list[list] = [[] for _ in range(ncols)]
        tss: list[int] = []
        for line in payload.split(b"\n"):
            if line.endswith(b"\r"):
                line = line[:-1]
            if not line:
                continue
            fields = line.split(b",")
            if len(fields) != expected:
                self._py_errors += 1
                continue
            try:
                vals = []
                for f, t in zip(fields, types):
                    if t == "s":
                        vals.append(f.decode() if f else None)
                    elif not f:
                        vals.append(0 if t != "d" else 0.0)
                    elif t == "d":
                        vals.append(float(f))
                    elif t == "l":
                        vals.append(int(f))
                    else:                   # 'b'
                        vals.append(_py_bool(f))
                ts = int(fields[ncols]) if self.ts_last else 0
            except ValueError:
                self._py_errors += 1
                continue
            for c, v in zip(raw_cols, vals):
                c.append(v)
            tss.append(ts)
        n = len(tss)
        if n == 0:
            return []
        cols: dict[str, Any] = {}
        for name, t, vals in zip(names, types, raw_cols):
            if t == "s":
                arr = np.empty(n, dtype=object)
                arr[:] = vals
                cols[name] = arr
            else:
                cols[name] = np.asarray(vals, dtype=_CHAR_NP[t])
        out = [RowsChunk(cols, np.asarray(tss, np.int64)
                         if self.ts_last else None, n)]
        return out


# ---------------------------------------------------------------------------
# DCN pack_rows wire format → columns (shared with tpu/dcn.py; layout pinned
# by tests/test_edge_rows.py round-trip against dcn.pack_rows/unpack_rows)
# ---------------------------------------------------------------------------

_NUM_DT = {"f": ">f4", "d": ">f8", "i": ">i4", "l": ">i8", "b": ">u1"}


def unpack_columns(payload: bytes) -> tuple[dict, np.ndarray, int, str]:
    """Decode one ``tpu/dcn.py pack_rows`` SoA payload straight into
    positional columns: returns ``({index: column}, ts, n, types)``. Numeric
    columns are zero-copy ``np.frombuffer`` views converted to host dtypes;
    string columns decode through their offset table."""
    n, ncols = struct.unpack_from(">IB", payload, 0)
    off = 5
    types = payload[off:off + ncols].decode("ascii")
    off += ncols
    ts = np.frombuffer(payload, dtype=">i8", count=n, offset=off) \
        .astype(np.int64)
    off += 8 * n
    cols: dict[int, Any] = {}
    for ci, t in enumerate(types):
        nulls = np.frombuffer(payload, dtype=np.uint8, count=n, offset=off) \
            .astype(bool)
        off += n
        if t == "s":
            offs = np.frombuffer(payload, dtype=">u4", count=n + 1,
                                 offset=off).astype(np.int64)
            off += 4 * (n + 1)
            blob = payload[off:off + int(offs[-1])]
            off += int(offs[-1])
            vals = np.empty(n, dtype=object)
            for i in range(n):          # string decode is inherently per-row
                vals[i] = None if nulls[i] \
                    else blob[offs[i]:offs[i + 1]].decode()
            cols[ci] = vals
        else:
            arr = np.frombuffer(payload, dtype=_NUM_DT[t], count=n,
                                offset=off)
            off += arr.dtype.itemsize * n
            host = arr.astype(_CHAR_NP["d" if t in ("f", "d") else
                                       ("l" if t in ("i", "l") else "b")])
            if nulls.any():
                host = host.copy()
                host[nulls] = 0
            cols[ci] = host
    return cols, ts, int(n), types
