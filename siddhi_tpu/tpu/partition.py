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
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compiler import parse as _parse
from .expr_compile import DeviceCompileError
from .nfa import DeviceNFACompiler, MergedBatchBuilder


def _hash_key(v) -> int:
    import zlib
    # stable across processes (hash() randomization would break resumed
    # checkpoints whose lane assignment must match)
    return zlib.crc32(str(v).encode()) & 0x7FFFFFFF


def _inject_key_equality(query, key_attr: str):
    """Per-KEY pattern semantics on shared lanes.

    A lane owns every key hashing to it, so the lane-local NFA sees several
    keys' events interleaved — the reference's ``partition with`` clones
    state PER KEY (``PartitionStreamReceiver.java:82-117``). Equivalent
    device semantics: every state after the first carries an implicit
    ``key == e1.key`` filter, so a partial only advances on its own key's
    events. (Found by the bench oracle cross-check: without this, rising
    chains stitched across different device ids.)

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


class PartitionedNFARuntime:
    """P-lane partitioned pattern matching, optionally sharded over a mesh.

    ``partition with (<key> of <stream>)`` over a pattern query: every lane runs
    the compiled NFA independently on its key subset.
    """

    def __init__(self, app_or_text, num_partitions: int,
                 key_attr: str,
                 slot_capacity: int = 32,
                 lane_batch: int = 256,
                 mesh: Optional[Mesh] = None,
                 axis: str = "p",
                 query_index: int = 0,
                 creation_cap: Optional[int] = None):
        app = _parse(app_or_text) if isinstance(app_or_text, str) else app_or_text
        # partition queries may live inside a `partition with` block
        if app.queries:
            query = app.queries[query_index]
        else:
            query = app.partitions[0].queries[query_index]
        self.P = num_partitions
        self.key_attr = key_attr
        self.lane_batch = lane_batch
        self.mesh = mesh
        self.axis = axis
        # per-key semantics on shared lanes: every later state carries an
        # implicit `key == e1.key` filter (see _inject_key_equality)
        query = _inject_key_equality(query, key_attr)
        self.compiler = DeviceNFACompiler(
            query, dict(app.stream_definitions), slot_capacity, lane_batch,
            creation_cap=creation_cap)
        self.stream_defs = dict(app.stream_definitions)
        self.builders = [
            MergedBatchBuilder(self.compiler.merged, lane_batch,
                               self.stream_defs,
                               used_cols=self.compiler.used_cols)
            for _ in range(num_partitions)
        ]

        # vmap the single-lane step over the lane axis
        step = self.compiler.make_step()
        vstep = jax.vmap(step, in_axes=(0, 0, 0, 0, 0, 0))
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
        # (the API bench/__graft_entry__ drive; donates the carried state)
        self.vstep = jax.jit(vstep, donate_argnums=(0,))
        self._vstep = self.vstep      # backwards-compat alias

        self.state = self.init_state()
        self.callback: Optional[Callable[[list[list]], None]] = None

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
        return _hash_key(key) % self.P

    # -- native (C++) CSV ingress ------------------------------------------
    def enable_native_ingress(self) -> None:
        """Routes raw CSV bytes through the C++ data-loader (no Python in the
        per-event loop): parse → dict-encode → crc32 lane routing → SoA pack.
        Single-input-stream patterns only (the bench/north-star shape)."""
        from ..query_api.definition import DataType
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

    def emit_native_feed(self) -> dict:
        """Drains all native lanes into ONE stacked [P, ...] wire feed
        (cols/tag/ts/ts_base/counts/count) WITHOUT stepping the device —
        the packing half of ``flush_native``, exposed so a producer thread
        (bench / AsyncDeviceDriver) can overlap C++ packing with device
        compute."""
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
            import logging
            logging.getLogger("siddhi_tpu.device").warning(
                "native lane ts span exceeds int32 ms; %d clamped",
                self.ts_clamped)
        ts = np.clip(deltas, 0, 2**31 - 1).astype(np.int32)
        return {"cols": cols, "tag": tag, "ts": ts, "ts_base": base,
                "counts": counts, "count": int(counts.sum())}

    def flush_native(self, decode: bool = False):
        decode = decode or self.callback is not None
        if all(self._ning.lane_len(ln) == 0 for ln in range(self.P)):
            return [] if decode else None
        b = self.emit_native_feed()
        if decode:
            self._sync_dict_from_native()
        return self._step_and_decode(b["cols"], b["tag"], b["ts"],
                                     b["ts_base"], b["counts"], decode)

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
        d = self.stream_defs[stream_id]
        key = row[d.attribute_position(self.key_attr)]
        lane = self.lane_of(key)
        b = self.builders[lane]
        b.append(stream_id, row, timestamp)
        if b.full:
            self.flush()

    def encode_columns(self, stream_id: str, cols: dict) -> dict:
        """Dictionary-encode string columns on their DISTINCT values (the
        per-event ``encode`` loop is the measured pack bottleneck)."""
        from ..query_api.definition import DataType
        d = self.stream_defs[stream_id]
        si = self.compiler.merged.stream_index[stream_id]
        enc = {}
        for a in d.attributes:
            v = cols.get(a.name)
            if v is None:
                continue
            if a.type == DataType.STRING:
                dic = self.compiler.merged.dictionaries[f"s{si}_{a.name}"]
                enc[a.name] = dic.encode_array(v)
            else:
                enc[a.name] = np.asarray(v)
        return enc

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
        from ..query_api.definition import DataType
        d = self.stream_defs[stream_id]
        if d.attribute_type(self.key_attr) == DataType.STRING and \
                self.key_attr in enc:
            si = self.compiler.merged.stream_index[stream_id]
            dic = self.compiler.merged.dictionaries[f"s{si}_{self.key_attr}"]
            tbl = getattr(self, "_lane_by_code", None)
            if tbl is None:
                tbl = np.zeros(1, np.int32)
            if len(tbl) < len(dic):
                ext = np.fromiter(
                    ((_hash_key(dic.decode(c)) % self.P)
                     for c in range(len(tbl), len(dic))),
                    dtype=np.int32, count=len(dic) - len(tbl))
                tbl = np.concatenate([tbl, ext])
                self._lane_by_code = tbl
            return tbl[enc[self.key_attr]]
        return self.route_lanes(cols[self.key_attr])

    def partition_columns(self, stream_id: str, cols: dict, timestamps):
        """The vectorized ingest front half: encode strings per distinct
        value, route all rows with ONE stable argsort, return per-lane
        column/timestamp views. ``send_many`` and the bench packer share
        this path (no duplicate routing logic to drift)."""
        ts = np.asarray(timestamps, dtype=np.int64)
        enc = self.encode_columns(stream_id, cols)
        lanes = self._lanes_for(stream_id, cols, enc)
        order = np.argsort(lanes, kind="stable")
        lanes_sorted = lanes[order]
        enc_sorted = {k: v[order] for k, v in enc.items()}
        ts_sorted = ts[order]
        bounds = np.searchsorted(lanes_sorted, np.arange(self.P + 1))
        lane_cols, lane_ts = [], []
        for lane in range(self.P):
            lo, hi = int(bounds[lane]), int(bounds[lane + 1])
            lane_cols.append({k: v[lo:hi] for k, v in enc_sorted.items()})
            lane_ts.append(ts_sorted[lo:hi])
        return lane_cols, lane_ts

    def send_many(self, stream_id: str, cols: dict, timestamps,
                  decode: bool = False):
        """Bulk ingest: route with ``partition_columns``, bulk-copy per-lane
        slices into the wire builders, flushing as lanes fill. ``cols`` maps
        attribute name to an array of values. Replaces the per-event
        ``send`` loop on the hot path (reference analog:
        ``StreamJunction.java:279-316``)."""
        if getattr(self, "_ning", None) is not None:
            raise RuntimeError(
                "native ingress enabled: use ingest_csv(), not send_many()")
        lane_cols, lane_ts = self.partition_columns(
            stream_id, cols, timestamps)
        out: list = []
        for lane in range(self.P):
            n = len(lane_ts[lane])
            if n == 0:
                continue
            b = self.builders[lane]
            pos = 0
            while pos < n:
                pos += b.append_many(stream_id, lane_cols[lane],
                                     lane_ts[lane], start=pos)
                if b.full:
                    r = self.flush(decode=decode)
                    if decode and r:
                        out.extend(r)
        return out if decode else None

    def flush(self, decode: bool = False):
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
        rows = []
        for lane in range(self.P):
            lane_ys = jax.tree_util.tree_map(lambda x: x[lane], ys)
            rows.extend(self.compiler.decode_outputs(lane_ys).rows())
        if self.callback is not None and rows:
            self.callback(rows)
        return rows

    @property
    def match_count(self) -> int:
        return int(np.sum(jax.device_get(self.state["matches"])))

    @property
    def drop_count(self) -> int:
        return int(np.sum(jax.device_get(self.state["drops"])))

    def block_until_ready(self) -> None:
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), self.state)
