"""Batching ingress: host rows → columnar (SoA) device micro-batches.

The TPU-native replacement for the reference's per-event ``StreamEvent`` pooling
(``event/stream/StreamEvent.java``) and the Disruptor ring ingress
(``StreamJunction.java:279``): events pack into fixed-capacity dense columns
(one array per attribute, dtype per ``DataType``), plus a timestamp column and a
validity mask for padding. Strings dictionary-encode to int32 codes host-side.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from ..query_api.definition import DataType, StreamDefinition


class StringDictionary:
    """Host-side string→code dictionary (per attribute).

    Code 0 is reserved for None/unknown so device comparisons against missing
    values are always false for real codes.
    """

    def __init__(self):
        self._codes: dict[str, int] = {}
        self._values: list[Optional[str]] = [None]
        # sorted lookup cache for encode_array (rebuilt when values grow)
        self._cache_len = 0
        self._sorted_vals = None
        self._sorted_codes = None
        # bumped on every in-place restore(): external translation caches
        # (columns.encode_dict_column) key on it — append-only growth keeps
        # cached prefixes valid, a restore invalidates them wholesale
        self.generation = 0

    def encode(self, s: Optional[str]) -> int:
        if s is None:
            return 0
        c = self._codes.get(s)
        if c is None:
            c = len(self._values)
            self._codes[s] = c
            self._values.append(s)
        return c

    def decode(self, code: int) -> Optional[str]:
        if 0 <= code < len(self._values):
            return self._values[code]
        return None

    def add(self, code: int, value: str) -> None:
        """Registers an externally minted (code, value) pair — used to sync
        entries assigned by the native ingress dictionary. Codes must arrive
        in sequence."""
        if code != len(self._values):
            raise ValueError(
                f"out-of-sequence dictionary code {code} (next is {len(self._values)})")
        self._codes[value] = code
        self._values.append(value)

    def __len__(self) -> int:
        return len(self._values)

    def encode_list(self, values: list) -> "np.ndarray":
        """Codes of a list of strings through the dictionary's own hash
        table: one C-level ``map`` over the list, and a Python call only
        for a value not seen before (a new key, not an event). The cost is
        the chunk's, whatever the dictionary holds: ``encode_array``
        rebuilds a sorted copy of every value each time one was added,
        which a stream of 10^5 keys pays for chunk after chunk."""
        import itertools
        import numpy as np
        codes = np.fromiter(
            map(self._codes.get, values, itertools.repeat(-1)),
            dtype=np.int32, count=len(values))
        for i in np.flatnonzero(codes < 0).tolist():
            codes[i] = self.encode(values[i])     # None -> 0
        return codes

    def encode_array(self, values) -> "np.ndarray":
        """Vectorized encode of a string array via a sorted lookup cache:
        ``searchsorted`` against the known values (O(n log u) C-side string
        compares), with only UNSEEN values taking the Python ``encode``
        path. The per-event ``encode`` loop is the measured ingest
        bottleneck; ``np.unique`` over the full array does more work than
        this for low-cardinality streams."""
        import numpy as np
        arr = np.asarray(values)
        nulls = None
        if arr.dtype == object:
            # None must stay code 0 (encode()'s null semantics) — astype("U")
            # would mint a real code for the literal string 'None'
            if any(x is None for x in arr.flat):
                nulls = np.array([x is None for x in arr.flat],
                                 dtype=bool).reshape(arr.shape)
                arr = np.where(nulls, "", arr).astype("U")
            else:
                arr = arr.astype("U")
        sv, sc = self._sorted_lookup()
        pos = np.searchsorted(sv, arr)
        posc = np.clip(pos, 0, max(sv.size - 1, 0))
        hit = (sv[posc] == arr) if sv.size else np.zeros(arr.shape, bool)
        miss = ~hit if nulls is None else (~hit & ~nulls)
        if miss.any():
            for u in np.unique(arr[miss]):
                self.encode(str(u))
            sv, sc = self._sorted_lookup()
            pos = np.searchsorted(sv, arr)
            posc = np.clip(pos, 0, sv.size - 1)
        codes = sc[posc]
        if nulls is not None:
            codes = np.where(nulls, np.int32(0), codes)
        return codes

    def _sorted_lookup(self):
        import numpy as np
        if self._cache_len != len(self._values):
            known = np.array(self._values[1:], dtype="U")
            order = np.argsort(known)
            self._sorted_vals = known[order]
            self._sorted_codes = (order + 1).astype(np.int32)
            self._cache_len = len(self._values)
        return self._sorted_vals, self._sorted_codes

    def snapshot(self) -> list:
        """Code-ordered value table (code 0 = None elided)."""
        return list(self._values[1:])

    def restore(self, values: list) -> None:
        self._values = [None] + list(values)
        self._codes = {v: i + 1 for i, v in enumerate(values)}
        self._cache_len = 0          # sorted lookup rebuilt on next encode
        self.generation += 1         # external translation caches drop


def snapshot_dictionaries(dictionaries: dict) -> dict:
    """Serializes a column→dictionary map, emitting each shared dictionary
    object once (under its first column name)."""
    out, seen = {}, set()
    for name, d in dictionaries.items():
        if id(d) in seen:
            continue
        seen.add(id(d))
        out[name] = d.snapshot()
    return out


def device_state_snapshot(state, dict_owner) -> dict:
    """Canonical device-runtime checkpoint: host-fetched pytree + the string
    dictionary that decodes its codes (advisor r2 finding: codes without the
    dictionary are meaningless in a fresh process). ``dict_owner`` is any
    object with snapshot_dictionaries()/restore_dictionaries()."""
    import jax
    return {"device": jax.device_get(state),
            "dict": dict_owner.snapshot_dictionaries()}


def device_state_restore(snap, dict_owner):
    """Inverse of device_state_snapshot; accepts the pre-round-3 bare-pytree
    shape too. Returns the device state to assign."""
    import jax
    if isinstance(snap, dict) and "device" in snap:
        dict_owner.restore_dictionaries(snap.get("dict", {}))
        return jax.device_put(snap["device"])
    return jax.device_put(snap)      # pre-round-3 snapshot shape


def restore_dictionaries(dictionaries: dict, snap: dict) -> None:
    """Restores in-place; sharing structure comes from the live schema, so
    each snapshotted table lands in (and via aliasing, propagates to) every
    column that shares it."""
    for name, values in snap.items():
        d = dictionaries.get(name)
        if d is not None:
            d.restore(values)


@dataclass
class BatchSchema:
    """Column layout for one stream."""

    definition: StreamDefinition
    dictionaries: dict[str, StringDictionary] = field(default_factory=dict)

    def __post_init__(self):
        # one shared dictionary: codes comparable across string columns
        shared = None
        for a in self.definition.attributes:
            if a.type == DataType.STRING:
                if shared is None:
                    shared = self.dictionaries.get(a.name) or StringDictionary()
                self.dictionaries.setdefault(a.name, shared)

    @property
    def names(self) -> list[str]:
        return self.definition.attribute_names

    def np_dtype(self, name: str) -> np.dtype:
        t = self.definition.attribute_type(name)
        if t == DataType.OBJECT:
            raise TypeError(
                f"attribute '{name}': OBJECT attributes are host-only and cannot "
                "enter the device path")
        from .dtypes import NP
        return np.dtype(NP[t])

    def encode_value(self, name: str, v: Any):
        enc = self.encoders.get(name)
        if enc is not None:                    # string column
            return enc(v)
        if v is None:
            return 0
        return v

    @property
    def encoders(self) -> dict:
        """Per-attribute string encoders, resolved ONCE per schema (the
        per-event append loop previously re-looked-up attribute type and
        dictionary for every value)."""
        e = self.__dict__.get("_encoders")
        if e is None:
            e = self.__dict__["_encoders"] = {
                a.name: self.dictionaries[a.name].encode
                for a in self.definition.attributes
                if a.type == DataType.STRING}
        return e

    def snapshot_dictionaries(self) -> dict:
        return snapshot_dictionaries(self.dictionaries)

    def restore_dictionaries(self, snap: dict) -> None:
        restore_dictionaries(self.dictionaries, snap)


class BatchBuilder:
    """Accumulates rows into numpy staging buffers; emits padded micro-batches.

    The double-buffered host ring of the reference's async junction maps to: fill
    one staging buffer while the device consumes the previous batch.
    """

    def __init__(self, schema: BatchSchema, capacity: int):
        self.schema = schema
        self.capacity = capacity
        self._cols = {
            n: np.zeros(capacity, dtype=schema.np_dtype(n)) for n in schema.names
        }
        self._ts = np.zeros(capacity, dtype=np.int64)
        self._n = 0
        # wall-clock of the first append since the last emit: the packing
        # span the async driver charges to the pack phase (overlap
        # accounting) and checks against the latency-mode flush deadline
        self._pack_t0 = None

    def __len__(self) -> int:
        return self._n

    @property
    def full(self) -> bool:
        return self._n >= self.capacity

    def append(self, row: list, ts: int) -> None:
        if self._n >= self.capacity:
            raise OverflowError("micro-batch full; call emit() first")
        i = self._n
        if self._pack_t0 is None:
            import time
            self._pack_t0 = time.perf_counter()
        for name, v in zip(self.schema.names, row):
            self._cols[name][i] = self.schema.encode_value(name, v)
        self._ts[i] = ts
        self._n += 1

    def append_rows(self, rows: list[list], ts_list) -> None:
        for row, ts in zip(rows, ts_list):
            self.append(row, ts)

    @property
    def column_names(self) -> list:
        """The chunk columns ``append_columns`` reads, by raw attribute name
        (what a guard's shadow of a columnar chunk keeps)."""
        return self.schema.names

    def append_columns(self, cols: dict, ts, start: int = 0) -> int:
        """Bulk slice-copy of a columnar chunk (``{name: numpy array |
        DictColumn}``) into the staging buffers, starting at row ``start``
        of the chunk; returns how many rows fit (the caller emits and
        resumes past them). The device-tier twin of
        ``HostRowStager.append_columns`` — no per-row Python.

        Wired end-to-end since the mesh round: single-stream device
        bridges expose ``receive_columns`` (``core/device_bridge.py``
        ``on_columns_chunk`` → ``DeviceStreamRuntime.send_columns``), with the
        probe/trace FIFO stamped per CHUNK and the DeviceGuard shadow
        captured as lazy column slices — columnar chunks reach the device
        tier with zero per-event appends on the DCN-ingest → device
        path."""
        ts = np.asarray(ts, dtype=np.int64)
        n = int(ts.shape[0]) - start
        if n <= 0:
            return 0
        take = min(n, self.capacity - self._n)
        if take <= 0:
            return 0
        if self._pack_t0 is None:
            import time
            self._pack_t0 = time.perf_counter()
        i = self._n
        from ..core.columns import DictColumn, encode_dict_column
        for name in self.schema.names:
            col = cols[name]
            dst = self._cols[name]
            if isinstance(col, DictColumn):
                dic = self.schema.dictionaries.get(name)
                part = col[start:start + take]
                dst[i:i + take] = encode_dict_column(part, dic) \
                    if dic is not None else part.codes
            else:
                arr = col[start:start + take]
                if not isinstance(arr, np.ndarray) or arr.dtype == object:
                    enc = self.schema.dictionaries.get(name)
                    if enc is not None:
                        dst[i:i + take] = enc.encode_array(
                            np.asarray(arr, dtype=object))
                    else:
                        dst[i:i + take] = [
                            self.schema.encode_value(name, v) for v in arr]
                else:
                    dst[i:i + take] = arr
        self._ts[i:i + take] = ts[start:start + take]
        self._n += take
        return take

    def emit(self) -> dict:
        """Returns {'cols': {name: np[capacity]}, 'ts', 'valid', 'count'} and
        resets. Arrays are padded to capacity (static shapes for jit).
        ``pack_s`` carries the wall span from first append to emit (pack
        phase in the driver's overlap accounting; extra keys never reach the
        jitted step — it indexes the batch dict by name)."""
        import time
        t_emit0 = time.perf_counter()
        valid = np.zeros(self.capacity, dtype=bool)
        valid[: self._n] = True
        out = {
            "cols": {n: self._cols[n].copy() for n in self.schema.names},
            "ts": self._ts.copy(),
            "valid": valid,
            "count": self._n,
            "last_ts": int(self._ts[self._n - 1]) if self._n else 0,
            "pack_s": (t_emit0 - self._pack_t0
                       if self._pack_t0 is not None else 0.0),
        }
        # X-Ray waterfall stamps: SoA staging cost (the `pack` phase) and
        # the emit instant, from which the driver derives ring-queue wait
        t_emit = time.perf_counter()
        out["pack_exec_s"] = t_emit - t_emit0
        out["_t_emit"] = t_emit
        self._n = 0
        self._pack_t0 = None
        return out

    def snapshot(self) -> dict:
        """Staged-but-unemitted rows (checkpointing the async ingest gap)."""
        n = self._n
        return {
            "cols": {k: v[:n].copy() for k, v in self._cols.items()},
            "ts": self._ts[:n].copy(),
            "n": n,
        }

    def restore(self, snap: dict) -> None:
        n = snap["n"]
        self._n = n
        for k, v in snap["cols"].items():
            self._cols[k][:n] = v
        self._ts[:n] = snap["ts"]
        if n:                   # restored rows re-arm the flush deadline
            import time
            self._pack_t0 = time.perf_counter()


def columns_from_rows(schema: BatchSchema, rows: list[list],
                      ts_list: list[int], capacity: Optional[int] = None) -> dict:
    """One-shot convenience: rows → padded column batch."""
    cap = capacity or len(rows)
    b = BatchBuilder(schema, cap)
    b.append_rows(rows, ts_list)
    return b.emit()
