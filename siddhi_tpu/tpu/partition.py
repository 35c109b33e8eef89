"""Partitioned execution: per-key NFA/aggregation state sharded over a mesh.

The reference's ``partition with (key of Stream)`` clones per-key query state
inside one JVM (``PartitionStreamReceiver.java:82-117``). TPU-native redesign:

- keys hash to P partition *lanes*; each lane owns fixed-capacity match tables
  (the same pytree the single-lane NFA carries);
- the step is ``vmap``'d over lanes, then ``shard_map``'d over a
  ``jax.sharding.Mesh`` axis so lanes spread across chips. Events are routed
  host-side to their lane's sub-batch (the reference's key→instance dispatch);
  on device nothing crosses lanes, so no collectives are needed in steady state
  — ICI traffic appears only if lanes rebalance (not needed this round).

This module serves a partition's pattern. A partition's keyed sliding
``window.length(N)`` with aggregates is served by ``tpu/keyed_window.py``
(no lanes: every key's window one row of one table, the key given a stable
slot on the host); what keeps the host tiers is listed in
``core/device_bridge.py`` ``try_build_device_partition``.
"""

from __future__ import annotations

import logging
import math
import time
import zlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compiler import parse as _parse
from ..query_api.definition import DataType
from .expr_compile import DeviceCompileError
from .nfa import DeviceNFACompiler, MergedBatchBuilder, decode_rows
from .step_runtime import StepRuntime

log = logging.getLogger("siddhi_tpu.device")


def _hash_key(v) -> int:
    # stable across processes (hash() randomization would break resumed
    # checkpoints whose lane assignment must match)
    return zlib.crc32(str(v).encode()) & 0x7FFFFFFF


def lane_capacity_for(batch: int, lanes: int) -> int:
    """Events one lane of a served batch holds: the next multiple of 64 at
    or above 2.5 x ``batch / lanes`` (the fullest lane of a Zipf stream
    reads about twice the mean; ``PERF.md`` section 7's sizing table)."""
    return max(64, math.ceil(2.5 * batch / lanes / 64) * 64)


def _inject_key_equality(query, key_attr: str):
    """Per-KEY pattern semantics on shared lanes.

    A lane owns every key hashing to it, so the lane-local NFA sees several
    keys' events interleaved — the reference's ``partition with`` clones
    state PER KEY (``PartitionStreamReceiver.java:82-117``). Equivalent
    device semantics: every state after the first carries an implicit
    ``key == e1.key`` filter, so a partial only advances on its own key's
    events. (Without this, rising chains stitched across different
    device ids.)

    Sequences (strict continuity is per-key) and patterns whose first state
    binds no alias (absent/logical starts) can't be expressed this way —
    they take the host path.
    """
    import copy

    from ..query_api import (
        Compare,
        CompareOp,
        CountStateElement,
        EveryStateElement,
        Filter,
        LogicalStateElement,
        NextStateElement,
        StateInputStream,
        StateInputStreamType,
        StreamStateElement,
        Variable,
    )

    ist = query.input_stream
    if not isinstance(ist, StateInputStream):
        return query
    if ist.type == StateInputStreamType.SEQUENCE:
        raise DeviceCompileError(
            "partitioned sequences need per-key strictness (host path)")
    query = copy.deepcopy(query)
    ist = query.input_stream

    elements: list = []

    def walk(el):
        if isinstance(el, NextStateElement):
            walk(el.first)
            walk(el.next)
        elif isinstance(el, EveryStateElement):
            walk(el.inner)
        elif isinstance(el, (StreamStateElement, CountStateElement,
                             LogicalStateElement)):
            elements.append(el)
        else:
            raise DeviceCompileError(
                f"partitioned {type(el).__name__} needs the host path")

    walk(ist.state)
    first = elements[0]
    if isinstance(first, LogicalStateElement):
        raise DeviceCompileError(
            "partitioned pattern starting with a logical state needs the "
            "host path")
    stream0 = first.stream if isinstance(first, StreamStateElement) \
        else first.stream.stream
    anchor = stream0.alias
    if anchor is None:
        raise DeviceCompileError(
            "partitioned pattern needs an alias on its first state")

    def constrain(stream):
        stream.handlers.append(Filter(Compare(
            Variable(key_attr), CompareOp.EQ,
            Variable(key_attr, stream_id=anchor))))

    for el in elements[1:]:
        if isinstance(el, StreamStateElement):
            constrain(el.stream)
        elif isinstance(el, CountStateElement):
            constrain(el.stream.stream)
        else:                       # logical: both branches
            for sub in (el.first, el.second):
                constrain(sub.stream)
    return query


class LaneFull(Exception):
    """The event's lane holds its capacity: seal the batch, then append."""


class RoutedChunk(dict):
    """A columnar chunk's raw columns (what a guard shadows) with what
    routing made of them once: ``enc`` = the staged columns by wire key,
    ``lanes`` = the lane of every row. A chunk split over batches is routed
    once, not once a piece."""

    __slots__ = ("enc", "lanes")


class LaneBatchBuilder(MergedBatchBuilder):
    """One FLAT batch of up to ``capacity`` events in arrival order, with
    the lane of every event beside it and a running count per lane.

    The seal rule of the served partition: the batch is full when it holds
    ``capacity`` events OR when the next event would be its lane's
    ``lane_capacity + 1``-th. ``append`` raises :class:`LaneFull` for such
    an event and ``append_columns`` takes a chunk only as far as it, so the
    caller seals and resumes: nothing is dropped, and a key's events keep
    their order because they stay in arrival order within one lane. The
    layout into ``[lanes, lane_capacity]`` happens at ``dispatch``."""

    def __init__(self, schema, capacity: int, stream_defs: dict,
                 used_cols, stream_id: str, lanes: int, lane_capacity: int,
                 route_row: Callable, route_chunk: Callable):
        super().__init__(schema, capacity, stream_defs, used_cols=used_cols)
        self.stream_id = stream_id
        self.lanes = lanes
        self.lane_capacity = lane_capacity
        # raw attribute names of the one input stream: what a guard's shadow
        # of a columnar chunk keeps (``_ShadowBuilder.append_columns``)
        self.column_names = [a.name
                             for a in stream_defs[stream_id].attributes]
        self._route_row = route_row         # row -> lane
        self._route_chunk = route_chunk     # (stream, cols, n) -> RoutedChunk
        self._lane = np.zeros(capacity, dtype=np.int32)
        self._lane_n = np.zeros(lanes, dtype=np.int64)
        self.lane_full = False      # the next event met a full lane

    @property
    def full(self) -> bool:
        return self._n >= self.capacity or self.lane_full

    def append(self, stream_id: str, row: list, ts: int) -> None:
        lane = self._route_row(row)
        if self._lane_n[lane] >= self.lane_capacity:
            self.lane_full = True
            raise LaneFull(lane)
        i = self._n
        super().append(stream_id, row, ts)
        self._lane[i] = lane
        self._lane_n[lane] += 1

    def append_columns(self, cols: dict, ts, start: int = 0) -> int:
        """Rows ``[start, start + take)`` of a columnar chunk, ``take``
        being what fits the batch and every lane; returns ``take``. No
        per-row Python: one ``bincount`` of the slice's lanes, and where a
        lane would overflow one stable argsort to find the first row that
        does."""
        ts = np.asarray(ts, dtype=np.int64)
        if not isinstance(cols, RoutedChunk):
            cols = self._route_chunk(self.stream_id, cols, int(ts.shape[0]))
        take = min(int(ts.shape[0]) - start, self.capacity - self._n)
        if take <= 0:
            return 0
        lanes = cols.lanes[start:start + take]
        counts = np.bincount(lanes, minlength=self.lanes)
        room = self.lane_capacity - self._lane_n
        if (counts > room).any():
            order = np.argsort(lanes, kind="stable")
            ls = lanes[order]
            rank = np.arange(take) - (np.cumsum(counts) - counts)[ls]
            take = int(order[rank >= room[ls]].min())
            self.lane_full = True
            if take == 0:
                return 0
            lanes = lanes[:take]
            counts = np.bincount(lanes, minlength=self.lanes)
        if self._pack_t0 is None:
            self._pack_t0 = time.perf_counter()
        i = self._n
        for key, col in self._cols.items():
            col[i:i + take] = cols.enc[key][start:start + take]
        self._tag[i:i + take] = self.schema.stream_index[self.stream_id]
        self._ts[i:i + take] = ts[start:start + take]
        self._lane[i:i + take] = lanes
        self._lane_n += counts
        self._n += take
        return take

    def emit(self) -> dict:
        n = self._n
        out = super().emit()
        valid = np.zeros(self.capacity, dtype=bool)
        valid[:n] = True
        out["valid"] = valid
        out["lane"] = self._lane.copy()
        self._lane_n[:] = 0
        self.lane_full = False
        return out

    def snapshot(self) -> dict:
        snap = super().snapshot()
        snap["lane"] = self._lane[:self._n].copy()
        return snap

    def restore(self, snap: dict) -> None:
        super().restore(snap)
        n = snap["n"]
        self._lane[:n] = snap["lane"]
        self._lane_n[:] = np.bincount(snap["lane"], minlength=self.lanes)
        self.lane_full = False


class PartitionedNFARuntime(StepRuntime):
    """P-lane partitioned pattern matching, optionally sharded over a mesh.

    ``partition with (<key> of <stream>)`` over a pattern query: every lane runs
    the compiled NFA independently on its key subset.

    Two ways in. **Served** (``batch=`` given; what ``@device`` on a
    ``partition with`` block builds, ``core/device_bridge.py``
    ``try_build_device_partition``): one flat :class:`LaneBatchBuilder` of
    ``batch`` events and ``StepRuntime``'s protocol (``send`` /
    ``send_columns`` in front, ``snapshot_state`` / ``restore_state``) under
    the bridge's driver, probe and guard. **Direct** (no ``batch``;
    ``chip_smoke.py`` S3/S4 and ``tpu/dcn.py``): one builder a lane in
    ``builders``, ``send`` / ``ingest_csv`` and a synchronous
    ``flush(decode=)``.
    Both step the same jitted ``vstep`` over ``[P, lane_batch]`` and decode
    its stacked outputs in one pass (``decode_stacked``). The step is
    whichever kernel the compiler chose for the pattern (``kernel``): the
    blocked one for chains of stream states under ``every``, the per-event
    scan for count (``<m:n>``), logical and absent states; both hand out
    the lanes' row counts and one ``[P, M]`` row table, and that table is
    what a batch's decode fetches (beside it on the device, read only for a
    batch in which a lane emitted more than ``M`` rows: the blocked
    kernel's whole ``[P, (S-1)C + B]`` candidate table, the scan kernel's
    table of the plan's bound, packed at that size for such a batch alone).
    """

    def __init__(self, app_or_text, num_partitions: int,
                 key_attr: str,
                 slot_capacity: int = 32,
                 lane_batch: Optional[int] = None,
                 mesh: Optional[Mesh] = None,
                 axis: str = "p",
                 query_index: int = 0,
                 creation_cap: Optional[int] = None,
                 batch: Optional[int] = None,
                 query=None, stream_defs: Optional[dict] = None):
        if query is None:
            app = _parse(app_or_text) if isinstance(app_or_text, str) \
                else app_or_text
            # partition queries may live inside a `partition with` block
            if app.queries:
                query = app.queries[query_index]
            else:
                query = app.partitions[0].queries[query_index]
            stream_defs = app.stream_definitions
        if lane_batch is None:
            lane_batch = 256 if batch is None \
                else lane_capacity_for(batch, num_partitions)
        self.P = num_partitions
        self.key_attr = key_attr
        self.lane_batch = lane_batch
        self.mesh = mesh
        self.axis = axis
        # per-key semantics on shared lanes: every later state carries an
        # implicit `key == e1.key` filter (see _inject_key_equality)
        query = _inject_key_equality(query, key_attr)
        self.stream_defs = dict(stream_defs)
        self.compiler = DeviceNFACompiler(
            query, self.stream_defs, slot_capacity, lane_batch,
            creation_cap=creation_cap)
        self.fence_key = self.compiler.fence_key
        merged = self.compiler.merged
        # the dictionary a string key is coded in (ONE shared by every
        # string column of the merged schema); None for other key types
        self._key_dict = next(
            (merged.dictionaries[merged.col_key(sid, key_attr)]
             for sid in merged.stream_ids
             if self.stream_defs[sid].attribute_type(key_attr)
             == DataType.STRING), None)
        self._lane_by_code = np.zeros(1, np.int32)     # dictionary code -> lane
        self._lane_by_key: dict = {}                   # non-string key -> lane
        self.builders: list = []
        self.builder: Optional[LaneBatchBuilder] = None
        if batch is None:
            self.builders = [
                MergedBatchBuilder(merged, lane_batch, self.stream_defs,
                                   used_cols=self.compiler.used_cols)
                for _ in range(num_partitions)
            ]
        else:
            if len(merged.stream_ids) != 1:
                raise DeviceCompileError(
                    "a served partition routes one input stream")
            sid = merged.stream_ids[0]
            self._key_pos = self.stream_defs[sid].attribute_position(key_attr)
            self.builder = LaneBatchBuilder(
                merged, batch, self.stream_defs, self.compiler.used_cols,
                sid, num_partitions, lane_batch,
                route_row=self._lane_of_row, route_chunk=self.route_chunk)

        # the step over the lane axis (under a mesh, over a shard's lanes)
        vstep = self.compiler.make_step(stacked=True)
        if mesh is not None:
            spec = P(axis)
            specs6 = (spec, spec, spec, spec, spec, spec)
            vstep = jax.shard_map(
                vstep, mesh=mesh, in_specs=specs6,
                out_specs=(spec, spec), check_vma=False)
            self._sharding = NamedSharding(mesh, spec)
        else:
            self._sharding = None
        # public jittable step over [P, ...]-stacked lane state and batches
        # (what __graft_entry__ drives; donates the carried state)
        self.vstep = jax.jit(vstep, donate_argnums=(0,))
        self._vstep = self.vstep      # what the direct flush steps

        self.state = self.init_state()
        # direct use sets ``callback`` to an fn(rows); served, the bridge
        # sets it to its fn(chunk, emit_ts)
        # read at drain points only (on_drained), never per step
        self.lane_gauges = {"fullest_table_share": 0.0,
                            "fullest_lane_events": 0, "drops": 0}
        self._warned_drops = 0

    def init_state(self):
        """Fresh [P, ...]-stacked lane state (sharded if a mesh was given)."""
        single = self.compiler.init_state()
        state = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (self.P,) + x.shape).copy(),
            single)
        if self._sharding is not None:
            state = jax.device_put(
                state, jax.tree_util.tree_map(
                    lambda _: self._sharding, state,
                    is_leaf=lambda x: hasattr(x, "shape")))
        return state

    def lane_of(self, key) -> int:
        """Lane of one key: ``crc32(key) mod P``, computed once a key. A
        string key's lane sits in a table by its dictionary code (the code
        the builder stages anyway), any other key's in a dict."""
        dic = self._key_dict
        if dic is None or not isinstance(key, str):
            lane = self._lane_by_key.get(key)
            if lane is None:
                lane = self._lane_by_key[key] = _hash_key(key) % self.P
            return lane
        code = dic.encode(key)
        if code >= len(self._lane_by_code):
            self._grow_lane_table(dic)
        return int(self._lane_by_code[code])

    def _lane_of_row(self, row: list) -> int:
        return self.lane_of(row[self._key_pos])

    def _grow_lane_table(self, dic) -> np.ndarray:
        """``_lane_by_code`` extended to the dictionary's size: one crc32
        for each code minted since (a new key, not an event)."""
        tbl = self._lane_by_code
        if len(tbl) < len(dic):
            ext = np.fromiter(
                ((_hash_key(dic.decode(c)) % self.P)
                 for c in range(len(tbl), len(dic))),
                dtype=np.int32, count=len(dic) - len(tbl))
            tbl = self._lane_by_code = np.concatenate([tbl, ext])
        return tbl

    # -- native (C++) CSV ingress ------------------------------------------
    def enable_native_ingress(self) -> None:
        """Routes raw CSV bytes through the C++ data-loader (no Python in the
        per-event loop): parse → dict-encode → crc32 lane routing → SoA pack.
        Single-input-stream patterns only (the north-star shape)."""
        from ..native import NativeIngress

        if len(self.compiler.merged.stream_ids) != 1:
            raise ValueError("native CSV ingress supports single-stream patterns")
        sid = self.compiler.merged.stream_ids[0]
        d = self.stream_defs[sid]
        chars = {DataType.STRING: "s", DataType.INT: "i", DataType.LONG: "l",
                 DataType.FLOAT: "f", DataType.DOUBLE: "d", DataType.BOOL: "b"}
        types = "".join(chars[a.type] for a in d.attributes)
        self._ning = NativeIngress(
            types, key_col=d.attribute_position(self.key_attr),
            n_lanes=self.P, capacity=self.lane_batch)
        # replay already-assigned codes (compile-time string constants) so the
        # native dictionary assigns identical codes from here on
        self._shared_dict = next(
            iter(self.compiler.merged.dictionaries.values()), None)
        if self._shared_dict is not None:
            for code in range(1, len(self._shared_dict)):
                self._ning.encode(self._shared_dict.decode(code))
        self._col_keys = [f"s0_{a.name}" for a in d.attributes]
        self._bool_cols = [a.type == DataType.BOOL for a in d.attributes]

    def ingest_csv(self, data: bytes, base_ts: int = 0, ts_last: bool = False,
                   decode: bool = False) -> list:
        """Feeds raw CSV bytes end-to-end; flushes full lanes as it goes."""
        decode = decode or self.callback is not None
        rows: list = []
        pos = 0
        n = len(data)
        while pos < n:
            consumed = self._ning.ingest_csv(
                data, base_ts=base_ts, ts_last=ts_last, offset=pos)
            pos += consumed
            if pos < n:  # a lane filled: drain to device and resume
                out = self.flush_native(decode=decode)
                if decode and out:
                    rows.extend(out)
        return rows

    def flush_native(self, decode: bool = False):
        """Drains all native lanes into ONE stacked [P, ...] wire feed
        (cols/tag/ts/ts_base/counts) and steps it."""
        decode = decode or self.callback is not None
        if all(self._ning.lane_len(ln) == 0 for ln in range(self.P)):
            return [] if decode else None
        batches = [self._ning.emit_lane(ln) for ln in range(self.P)]
        used = self.compiler.used_cols
        cols = {}
        for ci, key in enumerate(self._col_keys):
            if key not in used:
                continue
            stacked = np.stack([bt["cols"][ci] for bt in batches])
            if self._bool_cols[ci]:
                stacked = stacked.astype(bool)
            cols[key] = stacked
        tag = np.stack([bt["tag"] for bt in batches]).astype(np.int8)
        # wire format from the C++ int64 lane timestamps
        ts64 = np.stack([bt["ts"] for bt in batches])
        counts = np.array([bt["count"] for bt in batches], dtype=np.int32)
        base = np.array(
            [int(t[:n].min()) if n else 0 for t, n in zip(ts64, counts)],
            dtype=np.int64)
        deltas = ts64 - base[:, None]
        over = int(np.sum(deltas > 2**31 - 1))
        if over:
            # same loud-overflow policy as MergedBatchBuilder.emit
            self.ts_clamped = getattr(self, "ts_clamped", 0) + over
            log.warning("native lane ts span exceeds int32 ms; %d clamped",
                        self.ts_clamped)
        ts = np.clip(deltas, 0, 2**31 - 1).astype(np.int32)
        if decode:
            self._sync_dict_from_native()
        return self._step_and_decode(cols, tag, ts, base, counts, decode)

    def _sync_dict_from_native(self) -> None:
        # pull strings the C++ dict minted during ingest into the Python
        # shared dictionary so decode_outputs can render them
        d = self._shared_dict
        if d is None:
            return
        for code in range(len(d), self._ning.dict_size()):
            d.add(code, self._ning.decode(code))

    def send(self, stream_id: str, row: list, timestamp: int) -> None:
        if getattr(self, "_ning", None) is not None:
            # host append would mint dictionary codes the C++ dict doesn't
            # know about, silently corrupting decode — one ingress owns codes
            raise RuntimeError(
                "native ingress enabled: use ingest_csv(), not send()")
        if self.builder is not None:
            try:
                self.builder.append(stream_id, row, timestamp)
            except LaneFull:
                self._maybe_flush()         # seals: cause `lane_full`
                self.builder.append(stream_id, row, timestamp)
            self._maybe_flush()
            return
        d = self.stream_defs[stream_id]
        b = self.builders[
            self.lane_of(row[d.attribute_position(self.key_attr)])]
        b.append(stream_id, row, timestamp)
        if b.full:
            self.flush()

    def send_columns(self, cols: dict, ts) -> None:
        """Served columnar ingress: the chunk is routed once (strings
        coded by one C-level map over the chunk, lanes by code) and
        slice-copied into the flat batch across as many batches as it
        spans; a batch seals at its capacity or where a lane would
        overflow (``LaneBatchBuilder``)."""
        ts = np.asarray(ts, dtype=np.int64)
        n = int(ts.shape[0])
        chunk = self.route_chunk(self.builder.stream_id, cols, n)
        start = 0
        while start < n:
            start += self.builder.append_columns(chunk, ts, start)
            self._maybe_flush()

    def route_chunk(self, stream_id: str, cols: dict, n: int) -> RoutedChunk:
        """Encode and route a columnar chunk once: every staged column by
        its wire key, and every row's lane."""
        from ..core.columns import DictColumn, encode_dict_column
        d = self.stream_defs[stream_id]
        merged = self.compiler.merged
        si = merged.stream_index[stream_id]
        used = self.compiler.used_cols
        chunk = RoutedChunk(cols)
        chunk.enc = {}
        key_vals = None
        for a in d.attributes:
            wire = f"s{si}_{a.name}"
            if wire not in used and a.name != self.key_attr:
                continue
            col = cols[a.name]
            if a.type == DataType.STRING:
                dic = merged.dictionaries[wire]
                vals = encode_dict_column(col, dic) \
                    if isinstance(col, DictColumn) \
                    else dic.encode_list(np.asarray(col).tolist())
            else:
                vals = np.asarray(col)
            chunk.enc[wire] = vals
            if a.name == self.key_attr:
                key_vals = vals
        chunk.lanes = self._lanes_for(stream_id, cols,
                                      {self.key_attr: key_vals})
        return chunk

    def _maybe_flush(self) -> None:
        """``StepRuntime``'s rule plus the served partition's second seal: a
        batch that stopped short of its capacity because a lane is full
        flushes with the cause ``lane_full``."""
        b = self.builder
        if b.lane_full and len(b) < b.capacity:
            self._count_flush("lane_full")
            self.flush()
            return
        super()._maybe_flush()

    def route_lanes(self, keys) -> np.ndarray:
        """Vectorized key→lane routing: crc32 runs once per DISTINCT key,
        cached in a sorted lookup (searchsorted per batch — np.unique over
        the full array is 20× slower for low-cardinality key streams)."""
        arr = np.asarray(keys)
        if arr.dtype == object:
            arr = arr.astype("U")
        sv = getattr(self, "_route_vals", None)
        if sv is None:
            sv = np.array([], dtype=arr.dtype)
            self._route_vals, self._route_lanes = sv, np.array([], np.int32)
        pos = np.searchsorted(sv, arr)
        posc = np.clip(pos, 0, max(sv.size - 1, 0))
        hit = (sv[posc] == arr) if sv.size else np.zeros(arr.shape, bool)
        if not hit.all():
            fresh = np.unique(arr[~hit])
            fresh_lanes = np.fromiter(
                ((_hash_key(str(u)) % self.P) for u in fresh),
                dtype=np.int32, count=len(fresh))
            allv = np.concatenate([sv, fresh])
            lanes_all = np.concatenate([self._route_lanes, fresh_lanes])
            order = np.argsort(allv, kind="stable")
            self._route_vals = allv[order]
            self._route_lanes = lanes_all[order]
            sv = self._route_vals
            pos = np.searchsorted(sv, arr)
            posc = np.clip(pos, 0, sv.size - 1)
        return self._route_lanes[posc]

    def _lanes_for(self, stream_id: str, cols: dict, enc: dict) -> np.ndarray:
        """Lane array for a bulk send: string keys route via their already-
        computed dictionary CODES (one code→lane table lookup; no second
        string search), other key types via the sorted route cache."""
        d = self.stream_defs[stream_id]
        if d.attribute_type(self.key_attr) == DataType.STRING and \
                self.key_attr in enc:
            si = self.compiler.merged.stream_index[stream_id]
            dic = self.compiler.merged.dictionaries[f"s{si}_{self.key_attr}"]
            return self._grow_lane_table(dic)[enc[self.key_attr]]
        return self.route_lanes(cols[self.key_attr])

    def flush(self, decode: bool = False):
        """Served: ``StepRuntime.flush``. Direct: every lane's builder
        emitted, stacked and stepped here, decoded when asked or when a
        callback waits."""
        if self.builder is not None:
            return super().flush()
        # a registered callback implies decode — without this, the
        # auto-flush on a filled lane would silently discard every match
        # row found mid-stream (fuzz regression: match_count advanced while
        # the callback saw nothing)
        decode = decode or self.callback is not None
        if all(len(b) == 0 for b in self.builders):
            return [] if decode else None
        batches = [b.emit() for b in self.builders]
        cols = {
            k: np.stack([bt["cols"][k] for bt in batches])
            for k in batches[0]["cols"]
        }
        tag = np.stack([bt["tag"] for bt in batches])
        ts = np.stack([bt["ts"] for bt in batches])
        ts_base = np.array([bt["ts_base"] for bt in batches], dtype=np.int64)
        counts = np.array([bt["count"] for bt in batches], dtype=np.int32)
        return self._step_and_decode(cols, tag, ts, ts_base, counts, decode)

    def _step_and_decode(self, cols, tag, ts, ts_base, counts, decode: bool):
        self.state, ys = self._vstep(self.state, cols, tag, ts, ts_base,
                                     counts)
        if not decode:
            return ys
        rows = self.decode_stacked(ys).rows()
        if self.callback is not None and rows:
            self.callback(rows)
        return rows

    def decode_stacked(self, ys):
        """One step's lane-stacked row tables ``[P, M]`` -> ONE
        ``ColumnsOut``, lanes in order and a lane's rows as its own decode
        orders them (by match event ``j``, then table order), in one pass
        over the whole: no loop over lanes, whichever kernel stepped (its
        ``full`` tables where a lane emitted more than ``M`` rows:
        ``decode_rows``)."""
        return decode_rows(self, ys, lane_batch=self.lane_batch)

    @property
    def kernel(self) -> str:
        """Which NFA kernel the lanes step: ``'blocked'`` or ``'scan'``."""
        return "blocked" if self.compiler.blocked else "scan"

    # -- the served interface: StepRuntime's, with these supplied --------------
    _decode = decode_stacked

    def dispatch(self, batch: dict):
        """Fire-and-forget step of one FLAT batch (arrival order, scalar
        ``count``, prefix ``valid``, a ``lane`` per event): laid out into
        ``[P, lane_batch]`` here, on the driver's thread, by one stable
        argsort by lane (a key's events keep their order), then ``vstep``
        on donated state. Returns the un-fenced outputs."""
        with self.measure("route", batch):
            feed = self._lay_out(batch)
        self.state, ys = self.vstep(self.state, *feed)
        return ys

    def _lay_out(self, batch: dict):
        lanes_n, width = self.P, self.lane_batch
        n = int(batch["count"])
        valid = batch["valid"][:n]
        src = np.arange(n) if valid.all() else np.flatnonzero(valid)
        lane = batch["lane"][src]
        order = np.argsort(lane, kind="stable")
        src, lane = src[order], lane[order]
        counts = np.bincount(lane, minlength=lanes_n)
        fullest = int(counts.max()) if n else 0
        if fullest > width:
            raise OverflowError(
                f"lane holds {fullest} events of a batch, capacity {width}")
        if fullest > self.lane_gauges["fullest_lane_events"]:
            self.lane_gauges["fullest_lane_events"] = fullest
        dst = lane * width + (np.arange(src.size)
                              - (np.cumsum(counts) - counts)[lane])

        def spread(flat):
            out = np.zeros(lanes_n * width, dtype=flat.dtype)
            out[dst] = flat[src]
            return out.reshape(lanes_n, width)

        cols = {k: spread(v) for k, v in batch["cols"].items()}
        ts_base = np.full(lanes_n, batch["ts_base"], dtype=np.int64)
        return (cols, spread(batch["tag"]), spread(batch["ts"]), ts_base,
                counts.astype(np.int32))

    def on_drained(self) -> None:
        """Drain point (nothing in flight, or every 64th batch under load):
        the one place that reads device state back. Overflow is warned of,
        never silent; the gauges say how near the tables are to it."""
        st = self.state
        drops = int(np.sum(jax.device_get(st["drops"])))
        # the waiting tables: the blocked kernel's or the scan kernel's
        tables = st["tables"] if "tables" in st else st["pending"]
        fullest = max((int(np.asarray(t["valid"]).sum(axis=-1).max())
                       for t in tables.values()), default=0)
        self.lane_gauges["drops"] = drops
        self.lane_gauges["fullest_table_share"] = fullest / self.compiler.C
        if drops > self._warned_drops:
            log.warning(
                "query '%s': %d partial matches dropped from full lane "
                "tables (raise @device(slots=) or lanes=)",
                self.query_name, drops)
            self._warned_drops = drops

    def snapshot_state(self):
        from .batch import device_state_snapshot
        return device_state_snapshot(self.state, self.compiler.merged)

    def restore_state(self, state) -> None:
        from .batch import device_state_restore
        self.state = device_state_restore(state, self.compiler.merged)
        # a restored dictionary may code the keys anew
        self._lane_by_code = np.zeros(1, np.int32)

    @property
    def match_count(self) -> int:
        return int(np.sum(jax.device_get(self.state["matches"])))

    @property
    def drop_count(self) -> int:
        return int(np.sum(jax.device_get(self.state["drops"])))

    def block_until_ready(self) -> None:
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), self.state)
