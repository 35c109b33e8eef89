"""Compiled single-stream queries: filter → window → aggregate, fully vectorized.

The TPU-native replacement for the hot path the reference interprets per event
(``FilterProcessor.process`` → ``LengthWindowProcessor.process`` →
``QuerySelector.process``; see SURVEY §3.2). Design:

- All mutable runtime state is a pytree carried through the jitted step
  (checkpoint = ``jax.device_get(state)``, restore = ``device_put``).
- Sliding ``lengthWindow(N)``: keep the last-N accepted values as a carried
  *tail buffer*; per-event window sums are ``cumsum(concat(tail, batch))``
  differences — one fused elementwise pipeline on the VPU.
- Sliding min/max (non-invertible) use a log-doubling sparse table over the
  same concat axis: O((N+B)·log N) work, no per-event scan
  (reference: ``MinAttributeAggregatorExecutor``'s deque has no batch analog).
- stdDev carries RAW values and computes shifted moments per batch
  (``var = E[(x-c)²] − (E[x-c])²`` holds for any c; centering at a per-batch
  mean keeps f32 conditioning; running/group-by variants center at the
  carried mean — Welford merged at batch granularity).
- ``lengthBatch(N)`` (tumbling) carries the open batch's events (aggregate
  args *and* projected columns) as a remainder buffer.
- Group-by (multi-key: codes mixed into one bucket id mod K) uses one-hot
  [B,K] cumulative contributions with carried dense per-key state [K].
- ``hopping(D, H)`` with group-by keeps the window as EVENTS and reduces it
  by key at a boundary (``_hopping_grouped``): one sort of the time-ordered
  concat by (keys, lane), suffix reductions over the sorted segments, rows
  in first-seen key order; exact for as many keys as the window holds
  events, nothing reads ``group_capacity``. The selector's tail (``order
  by`` / ``offset`` / ``limit``) runs on that flush chunk, on the device.
- ``having`` compiles over the materialized output columns and masks
  emission (reference ``QuerySelector`` having executor).
- Masked events (filter rejections, padding) are *compacted* so window
  semantics see only accepted events, front-packed in their order: every
  column a kind reads goes through ONE ``rowpack.compact_front`` at the head
  of the step, which looks at the mask it is handed: a prefix (nothing was
  filtered: the bridge's ``valid``) is masked where it stands, anything else
  moves as one gather of rows of 32-bit words; no scatter either way. The
  state scalar ``compact_moves`` counts the steps that moved.

What keeps the host path (``DeviceCompileError``, never a silent
difference): ``order by`` / ``limit`` / ``offset`` anywhere but on a grouped
hopping flush (``selector_tail_refusal`` says which: a sliding window, no
window, an ungrouped hopping flush, a join or a pattern); group-by with
``lengthBatch`` / ``timeBatch`` / ``session`` / ``batch`` / ``sort`` /
``frequent``; ``having`` or ``stdDev`` on a grouped hopping flush, ``order
by`` a string; group-by with sliding-window min / max / stdDev;
``distinctCount``; hopping without aggregates; stream functions.

Numeric policy (dtypes.py): integer-argument sums/avgs accumulate in int64 —
exact, like the reference's Java longs — float aggregates in float32 with
Kahan compensation on unbounded carried bases.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..query_api import (
    AttributeFunction,
    Filter,
    Query,
    SingleInputStream,
    Variable,
    Window,
)
from ..query_api.definition import DataType, StreamDefinition
from .batch import BatchSchema
from .dtypes import FACC, JNP as _JNP_DTYPES, NP as _NP_DTYPES
from .expr_compile import ColumnResolver, DeviceCompileError, compile_expression
from .rowpack import compact_front

# event-time sentinels bounding every real timestamp (keep searchsorted input
# sorted: empty tail slots sit at the front, batch padding at the back)
_TS_NEG = -(2 ** 62)
_TS_POS = 2 ** 62

_IACC = jnp.int64        # exact integer accumulator


@dataclass
class _Spec:
    name: str           # output name
    kind: str           # 'value' | 'sum' | 'count' | 'avg' | 'min' | 'max' | 'stdDev'
    fn: Optional[Callable] = None      # projection or aggregate-arg program
    dtype: DataType = DataType.DOUBLE
    source_attr: Optional[str] = None  # raw column name for string decode
    acc_int: bool = False              # accumulate exactly in int64


def _kahan_add(base, comp, add):
    """One compensated accumulation step: returns (new_base, new_comp)."""
    y = add - comp
    t = base + y
    return t, (t - base) - y


def _avalanche(x):
    """splitmix64 finalizer (shared definition in ``backend.py``)."""
    from .backend import avalanche
    return avalanche(x, jnp)


def _ident(dtype, is_min: bool):
    """Reduction identity for min/max lanes (shared with
    ``aggregation_compile`` via ``backend.py``)."""
    from .backend import reduce_identity
    return reduce_identity(dtype, is_min, jnp)


def _range_reduce(z, lo, j, is_min: bool):
    """min/max of ``z`` over inclusive index ranges [lo_b, j_b], vectorized.

    Log-doubling sparse table: T_k[i] covers [i−2^k+1, i]; a range of length m
    is the overlap of two 2^⌊log2 m⌋ spans. O(M log M) build, O(B) query."""
    M = z.shape[0]
    red = jnp.minimum if is_min else jnp.maximum
    ident = _ident(z.dtype, is_min)
    tables = [z]
    span = 1
    while span < M:
        prev = tables[-1]
        shifted = jnp.concatenate(
            [jnp.full((min(span, M),), ident, z.dtype), prev[:M - span]])
        tables.append(red(prev, shifted))
        span *= 2
    T = jnp.stack(tables)                              # [KK, M]
    m = jnp.maximum(j - lo + 1, 1).astype(jnp.int32)
    kk = 31 - jax.lax.clz(m)                           # floor(log2 m)
    p2 = (1 << kk).astype(jnp.int32)
    return red(T[kk, j], T[kk, jnp.clip(lo + p2 - 1, 0, M - 1)])


def selector_tail_refusal(query: Query) -> Optional[str]:
    """Why this query's ``order by`` / ``limit`` / ``offset`` keep the host
    path, or None where the device serves them (or there are none). The
    selector orders and limits a CHUNK: a batching window's flush is a chunk
    of its own whatever the micro-batch, so a grouped ``hopping`` flush is
    compiled with the tail (``_hopping_grouped``); everywhere else the
    device's chunk is the micro-batch, which is not the selector's."""
    sel = query.selector
    if sel is None or not (sel.order_by or sel.limit is not None
                           or sel.offset is not None):
        return None
    ist = query.input_stream
    windows = [h.name for h in getattr(ist, "handlers", [])
               if isinstance(h, Window)]
    if isinstance(ist, SingleInputStream) and windows == ["hopping"] \
            and sel.group_by:
        return None
    if not isinstance(ist, SingleInputStream):
        where = "a join or a pattern"
    elif not windows or windows == [""]:
        where = "a query without a window"
    elif windows == ["hopping"]:
        where = "an ungrouped hopping flush (one row a chunk)"
    elif windows[0] in ("timeBatch", "lengthBatch", "externalTimeBatch",
                        "session", "batch", "sort"):
        where = f"window '{windows[0]}' (no grouped device flush yet)"
    else:
        where = f"sliding window '{windows[0]}'"
    return (f"order by / limit / offset on {where} take the host path: the "
            f"device serves them on a grouped hopping flush only, a chunk "
            f"of the window's own; elsewhere device micro-batch chunking "
            f"would change their per-chunk semantics")


class _OutputResolver:
    """Resolves ``having`` variables against the select list's output names."""

    def __init__(self, specs: list[_Spec], schema: BatchSchema):
        self.specs = {s.name: s for s in specs}
        self.schema = schema

    def resolve(self, var: Variable) -> tuple[str, DataType]:
        s = self.specs.get(var.attribute)
        if s is None:
            raise DeviceCompileError(
                f"having references '{var.attribute}', not an output "
                f"attribute")
        return s.name, s.dtype

    def encode_string(self, key: str, value: str) -> int:
        s = self.specs[key]
        if s.source_attr and s.source_attr in self.schema.dictionaries:
            return self.schema.dictionaries[s.source_attr].encode(value)
        raise DeviceCompileError(f"no dictionary for having key '{key}'")


class CompiledStreamQuery:
    """Compiles a supported Query AST to a jitted (state, batch) -> (state, out)
    step. Raises DeviceCompileError for shapes the device path doesn't cover
    (the host interpreter is the fallback, mirroring the reference's CPU
    QueryRuntime role)."""

    def __init__(self, query: Query, definition: StreamDefinition,
                 batch_capacity: int = 4096, group_capacity: int = 1024,
                 window_capacity: int = 4096, backend: str = "jax"):
        ist = query.input_stream
        if not isinstance(ist, SingleInputStream):
            raise DeviceCompileError("device path covers single-stream queries")
        self.query = query
        self.definition = definition
        self.B = batch_capacity
        self.K = group_capacity
        # backend="numpy": the SAME lowering pass (handler walk, spec build,
        # validation) emits numpy closures for the columnar host engine
        # (tpu/host_exec.py) — no jit, f64/i64 policy, dynamic shapes
        self.backend = backend
        self.xp = np if backend == "numpy" else None
        self.schema = BatchSchema(definition)
        resolver = ColumnResolver(self.schema, xp=self.xp)
        self.resolver = resolver

        # handlers: filters + at most one window
        self.filter_fns: list[Callable] = []
        self.window_kind: Optional[str] = None
        self.window_n = 0
        self.window_ms = 0
        self.time_key: Optional[str] = None     # externalTime ts column
        for h in ist.handlers:
            if isinstance(h, Filter):
                fn, _ = compile_expression(h.expr, resolver)
                self.filter_fns.append(fn)
            elif isinstance(h, Window):
                if self.window_kind is not None:
                    raise DeviceCompileError("multiple windows not supported")
                def const_param(idx: int) -> int:
                    if len(h.params) <= idx or \
                            not hasattr(h.params[idx], "value"):
                        raise DeviceCompileError(
                            f"window '{h.name}' needs a constant parameter "
                            f"at position {idx}")
                    return int(h.params[idx].value)

                if h.name in ("length", "lengthBatch"):
                    self.window_kind = h.name
                    self.window_n = const_param(0)
                elif h.name == "time":
                    # sliding event-time window; the device clock IS event time
                    # (watermark ingress), so time == externalTime on arrival ts
                    self.window_kind = "time"
                    self.window_ms = const_param(0)
                    self.window_n = window_capacity
                elif h.name == "externalTime":
                    if len(h.params) != 2 or not isinstance(h.params[0], Variable):
                        raise DeviceCompileError(
                            "externalTime needs (timestamp attribute, duration)")
                    key, kt = resolver.resolve(h.params[0])
                    if kt not in (DataType.LONG, DataType.INT):
                        raise DeviceCompileError(
                            "externalTime attribute must be long/int")
                    self.window_kind = "time"
                    self.time_key = key
                    self.window_ms = const_param(1)
                    self.window_n = window_capacity
                elif h.name == "timeBatch":
                    # tumbling event-time window; flushes are event-driven on
                    # device (an arrival at/past the boundary closes the
                    # bucket — the host does the same inline, plus timers)
                    if len(h.params) > 1:
                        raise DeviceCompileError(
                            "timeBatch start-time parameter takes the host "
                            "path")
                    self.window_kind = "timeBatch"
                    self.window_ms = const_param(0)
                    self.window_n = window_capacity
                elif h.name == "externalTimeBatch":
                    # timeBatch segmented on an event-time ATTRIBUTE — the
                    # same kernel with the segment clock read from a column
                    if len(h.params) != 2 or not isinstance(h.params[0],
                                                            Variable):
                        raise DeviceCompileError(
                            "externalTimeBatch start-time/timeout take the "
                            "host path")
                    key, kt = resolver.resolve(h.params[0])
                    if kt not in (DataType.LONG, DataType.INT):
                        raise DeviceCompileError(
                            "externalTimeBatch attribute must be long/int")
                    self.window_kind = "timeBatch"
                    self.time_key = key
                    self.window_ms = const_param(1)
                    self.window_n = window_capacity
                elif h.name == "timeLength":
                    # sliding window bounded by BOTH time and count: the
                    # sliding-time kernel with the live range clamped to the
                    # newest N events
                    self.window_kind = "timeLength"
                    self.window_ms = const_param(0)
                    self.window_n = const_param(1)
                elif h.name == "delay":
                    self.window_kind = "delay"
                    self.window_ms = const_param(0)
                    self.window_n = window_capacity
                elif h.name == "session":
                    if len(h.params) > 1:
                        raise DeviceCompileError(
                            "session key / allowedLatency take the host path")
                    self.window_kind = "session"
                    self.window_ms = const_param(0)
                    self.window_n = window_capacity
                elif h.name == "batch":
                    # per-chunk tumbling window (reference
                    # BatchWindowProcessor): the device batch IS the chunk
                    if h.params:
                        raise DeviceCompileError(
                            "batch(length) takes the host path")
                    self.window_kind = "batch"
                elif h.name == "":
                    # #window() pass-through (reference EmptyWindowProcessor):
                    # never expires, so aggregates run exactly like the
                    # unwindowed path — compile as no-window
                    pass
                elif h.name == "sort":
                    # sort(N, key[, order]): carried sorted top-N buffer with
                    # a masked-insertion scan (reference SortWindowProcessor
                    # keeps a sorted list and evicts the per-order worst)
                    if len(h.params) < 2 or \
                            not isinstance(h.params[1], Variable):
                        raise DeviceCompileError(
                            "sort window needs (N, key attribute)")
                    if len(h.params) > 3:
                        raise DeviceCompileError(
                            "multi-key sort takes the host path")
                    order = "asc"
                    if len(h.params) == 3:
                        v = getattr(h.params[2], "value", None)
                        if not isinstance(v, str) or \
                                v.lower() not in ("asc", "desc"):
                            raise DeviceCompileError(
                                "sort order must be 'asc'|'desc'")
                        order = v.lower()
                    skey, skt = resolver.resolve(h.params[1])
                    if skt not in (DataType.INT, DataType.LONG,
                                   DataType.FLOAT, DataType.DOUBLE):
                        raise DeviceCompileError(
                            "sort key must be numeric on device (string "
                            "collation takes the host path)")
                    self.window_kind = "sort"
                    self.window_n = const_param(0)
                    self.sort_key = skey
                    self.sort_key_type = skt
                    self.sort_desc = order == "desc"
                elif h.name in ("frequent", "lossyFrequent"):
                    # Misra-Gries / lossy-counting heavy hitters: a carried
                    # key-counter table walked by a lax.scan (every event's
                    # behavior depends on the table its predecessors left)
                    def fconst(idx: int) -> float:
                        if len(h.params) <= idx or \
                                not hasattr(h.params[idx], "value"):
                            raise DeviceCompileError(
                                f"window '{h.name}' needs a constant "
                                f"parameter at position {idx}")
                        return float(h.params[idx].value)

                    if h.name == "frequent":
                        cap = const_param(0)
                        if cap < 1:
                            # a zero-capacity Misra-Gries table never emits
                            # on the host; the generic max(N,1) clamp would
                            # silently turn it into a 1-slot table
                            raise DeviceCompileError(
                                "frequent window count must be >= 1")
                        key_params = list(h.params[1:])
                    else:
                        from ..query_api import Constant as _Konst
                        self.lossy_support = fconst(0)
                        nxt = 1
                        if len(h.params) > 1 \
                                and isinstance(h.params[1], _Konst) \
                                and not isinstance(h.params[1].value, str):
                            self.lossy_error = fconst(1)
                            nxt = 2
                        else:
                            self.lossy_error = self.lossy_support / 10.0
                        if self.lossy_error <= 0:
                            raise DeviceCompileError(
                                "lossyFrequent error bound must be positive")
                        # the host dict is unbounded; worst-case live
                        # entries exceed 1/error, so honor the
                        # @device(window='N') capacity knob (the overflow
                        # warning tells operators to raise exactly that)
                        cap = min(65536,
                                  max(int(1.0 / self.lossy_error) + 64,
                                      window_capacity))
                        key_params = list(h.params[nxt:])
                    if not key_params:
                        from ..query_api import Variable as _Var
                        key_params = [
                            _Var(attribute=a.name)
                            for a in definition.attributes]
                    if len(key_params) > 2:
                        raise DeviceCompileError(
                            f"{h.name} with >2 key attributes takes the "
                            f"host path")
                    self.hh_keys = []
                    for kp in key_params:
                        if not isinstance(kp, Variable):
                            raise DeviceCompileError(
                                f"{h.name} key must be an attribute")
                        kk, kt = resolver.resolve(kp)
                        allowed = (DataType.STRING, DataType.INT) \
                            if len(key_params) == 2 \
                            else (DataType.STRING, DataType.INT,
                                  DataType.LONG)
                        if kt not in allowed:
                            # exact key identity is required (hash
                            # collisions would corrupt counts)
                            raise DeviceCompileError(
                                f"{h.name} key '{kk}' type takes the host "
                                f"path")
                        self.hh_keys.append(kk)
                    self.window_kind = h.name
                    self.window_n = cap
                elif h.name == "hopping":
                    # hopping(duration, hop): overlapping tumbling buckets;
                    # flushes are event-driven on device like timeBatch
                    self.window_kind = "hopping"
                    self.window_ms = const_param(0)
                    self.hop_ms = const_param(1)
                    if self.hop_ms <= 0 or self.window_ms <= 0:
                        raise DeviceCompileError(
                            "hopping needs positive duration and hop")
                    self.window_n = window_capacity
                else:
                    raise DeviceCompileError(
                        f"window '{h.name}' has no device kernel yet")
            else:
                raise DeviceCompileError("stream functions not on device path")

        # group-by: one or more key columns (string codes / ints), mixed into
        # a single bucket id modulo K (same dense-table design as the
        # reference's per-group aggregator map, bounded for static shapes)
        self.group_keys: list[str] = []
        self.group_key_types: list[DataType] = []
        for gb in (query.selector.group_by or []):
            key, kt = resolver.resolve(gb)
            if kt not in (DataType.STRING, DataType.INT, DataType.LONG):
                raise DeviceCompileError("group key must be string/int")
            self.group_keys.append(key)
            self.group_key_types.append(kt)
        if self.group_keys and self.window_kind in (
                "lengthBatch", "timeBatch", "session", "batch", "sort",
                "frequent", "lossyFrequent"):
            raise DeviceCompileError(
                f"group-by with {self.window_kind} windows takes the host "
                f"path (a grouped flush is served for hopping only)")
        # a hopping flush reduced BY KEY (``_hopping_grouped``): exact for as
        # many keys as the window holds events, no bucket grid
        self.grouped_flush = self.window_kind == "hopping" and \
            bool(self.group_keys)
        refusal = selector_tail_refusal(query)
        if refusal is not None:
            raise DeviceCompileError(refusal)

        # select list
        self.specs: list[_Spec] = []
        sel = query.selector
        attrs = sel.attributes
        if sel.select_all or not attrs:
            from ..query_api import OutputAttribute
            attrs = [OutputAttribute(None, Variable(attribute=n))
                     for n in definition.attribute_names]
        for oa in attrs:
            e = oa.expr
            if isinstance(e, AttributeFunction) and e.namespace is None \
                    and e.name in ("sum", "count", "avg", "min", "max",
                                   "distinctCount", "stdDev"):
                if e.name == "distinctCount":
                    raise DeviceCompileError(
                        "aggregator 'distinctCount' needs the host path")
                arg_fn, at = (None, DataType.LONG)
                if e.args:
                    arg_fn, at = compile_expression(e.args[0], resolver)
                    if at not in (DataType.INT, DataType.LONG,
                                  DataType.FLOAT, DataType.DOUBLE):
                        # e.g. min(sym): the host compares strings
                        # lexicographically; dictionary codes are arrival-
                        # ordered, so aggregating them would silently diverge
                        raise DeviceCompileError(
                            f"{e.name}() over non-numeric arguments needs "
                            f"the host path")
                elif e.name != "count":
                    raise DeviceCompileError(f"{e.name}() needs an argument")
                int_arg = at in (DataType.INT, DataType.LONG)
                if e.name == "count":
                    dt = DataType.LONG
                elif e.name in ("avg", "stdDev"):
                    dt = DataType.DOUBLE
                elif e.name in ("min", "max"):
                    dt = at          # reference: min/max keep the arg type
                else:
                    dt = DataType.LONG if int_arg else DataType.DOUBLE
                self.specs.append(_Spec(oa.name, e.name, arg_fn, dt,
                                        acc_int=int_arg and
                                        e.name in ("sum", "avg")))
            else:
                fn, t = compile_expression(e, resolver)
                src = e.attribute if isinstance(e, Variable) and t == DataType.STRING \
                    else None
                self.specs.append(_Spec(oa.name, "value", fn, t, src))

        # the select list as ColumnsOut / decode_columns read it
        self.out_specs = [(s.name, s.fn, s.dtype) for s in self.specs]
        self.value_idx = [i for i, s in enumerate(self.specs) if s.kind == "value"]
        # aggregate lanes: counts ride the ones/cnts axis; sums/avgs split
        # into an exact-int stack and a float stack; min/max keep individual
        # policy-dtype lanes; stdDev lanes carry raw float values
        self.iagg_idx = [i for i, s in enumerate(self.specs)
                         if s.kind in ("sum", "avg") and s.acc_int]
        self.fagg_idx = [i for i, s in enumerate(self.specs)
                         if s.kind in ("sum", "avg") and not s.acc_int]
        self.magg_idx = [i for i, s in enumerate(self.specs)
                         if s.kind in ("min", "max")]
        self.sagg_idx = [i for i, s in enumerate(self.specs)
                         if s.kind == "stdDev"]
        self.agg_idx = [i for i, s in enumerate(self.specs) if s.kind != "value"]
        if self.group_keys and self.window_kind is not None and \
                (self.sagg_idx or (self.magg_idx and not self.grouped_flush)):
            # per-key windowed min/max/stdDev would need a [M,K] sparse table
            # per lane — not worth the HBM; host path covers it (a grouped
            # hopping flush reduces sorted segments, so min/max are served)
            raise DeviceCompileError(
                "group-by with windowed stdDev (and, on a sliding window, "
                "min/max) takes the host path")
        if self.window_kind == "delay" and (self.agg_idx or self.group_keys):
            # the delay kernel re-times value projections only; aggregates
            # over a delayed stream keep host semantics
            raise DeviceCompileError(
                "aggregates/group-by over a delay window take the host path")
        if self.window_kind in ("frequent", "lossyFrequent") and \
                (self.magg_idx or self.sagg_idx):
            # heavy-hitter evictions retract via the evicted key's LAST
            # value — sums/counts/avgs roll back exactly, but min/max/stdDev
            # would need the host's multiset bookkeeping
            raise DeviceCompileError(
                f"min/max/stdDev over {self.window_kind} windows take the "
                f"host path")
        if self.window_kind == "hopping" and not self.agg_idx:
            # non-aggregated hopping re-emits every buffered event per flush
            # (output cardinality ~ duration/hop per event) — host path
            raise DeviceCompileError(
                "hopping without aggregates takes the host path")

        # having: post-filter over materialized output columns (reference
        # ``QuerySelector``'s havingConditionExecutor)
        self.having_fn: Optional[Callable] = None
        if self.grouped_flush:
            self._plan_grouped_flush(query, resolver)
        if query.selector.having is not None:
            hres = _OutputResolver(self.specs, self.schema)
            if self.xp is not None:
                hres.xp = self.xp
            self.having_fn, _ = compile_expression(query.selector.having, hres)
        self._step = None if backend == "numpy" \
            else jax.jit(self._make_step(), donate_argnums=(0,))

    def _plan_grouped_flush(self, query: Query, resolver) -> None:
        """The static plan of a grouped hopping flush: which value columns
        ARE group keys (read from the sorted keys, no payload), the
        selector's tail, the boundaries a step resolves and the rows a
        boundary may emit."""
        sel = query.selector
        if sel.having is not None:
            # the interpreter tests `having` on every event's RUNNING row
            # and collapses to the last one that passed; a flush has the
            # final rows only
            raise DeviceCompileError(
                "having on a grouped hopping flush takes the host path")
        attrs = sel.attributes
        # value column -> position among the group keys, where it is one
        self.key_of_value: dict[int, int] = {}
        if not sel.select_all and attrs:
            for i in self.value_idx:
                e = attrs[i].expr
                if isinstance(e, Variable) and e.stream_id is None:
                    key, _ = resolver.resolve(e)
                    if key in self.group_keys:
                        self.key_of_value[i] = self.group_keys.index(key)
        names = [sp.name for sp in self.specs]
        self.order_by: list[tuple[int, bool]] = []
        from ..query_api import OrderByOrder
        for ob in sel.order_by:
            if ob.variable.attribute not in names:
                raise DeviceCompileError(
                    f"order by unknown output attribute "
                    f"'{ob.variable.attribute}'")
            i = names.index(ob.variable.attribute)
            if self.specs[i].dtype not in (DataType.INT, DataType.LONG,
                                           DataType.FLOAT, DataType.DOUBLE):
                # dictionary codes are arrival-ordered, not collated
                raise DeviceCompileError(
                    "order by a non-numeric attribute takes the host path")
            self.order_by.append((i, ob.order == OrderByOrder.DESC))
        M = max(self.window_n, 1) + self.B
        self.limit = sel.limit
        self.offset = min(sel.offset or 0, M)
        # a step resolves the boundaries its own events can fire at one
        # tick an event, plus the one carried in; later ones are deferred
        # to the next step (drained by empty steps at a flush)
        self.flush_cap = -(-self.B // self.hop_ms) + 1
        # rows a boundary emits: the limit, else every key of the window
        self.flush_rows = M if self.limit is None \
            else max(1, min(self.limit, M))

    def _mdtype(self, i: int):
        return _JNP_DTYPES[self.specs[i].dtype]

    # ------------------------------------------------------------------ state
    def init_state(self) -> dict:
        N = max(self.window_n, 1)
        AF, AI = len(self.fagg_idx), len(self.iagg_idx)
        AS = len(self.sagg_idx)
        # steps whose batch the compaction had to move (every kind's step
        # runs it; `rowpack.compact_front`)
        state: dict[str, Any] = {"compact_moves": jnp.zeros((), jnp.int64)}
        if self.grouped_flush:
            # the window kept as events: timestamp, key(s) and aggregate
            # arguments of the newest N, reduced by key at a boundary
            state["tail_ts"] = jnp.full((N,), _TS_NEG, dtype=jnp.int64)
            for n, t in enumerate(self.group_key_types):
                state[f"tail_gk{n}"] = jnp.zeros((N,), _JNP_DTYPES[t])
            state["tail_fvals"] = jnp.zeros((AF, N), dtype=FACC)
            state["tail_ivals"] = jnp.zeros((AI, N), dtype=_IACC)
            for i in self.magg_idx:
                dt = self._mdtype(i)
                state[f"tail_m{i}"] = jnp.full(
                    (N,), _ident(dt, self.specs[i].kind == "min"), dt)
            for i in self.value_idx:
                if i not in self.key_of_value:
                    state[f"tail_proj_{i}"] = jnp.zeros(
                        (N,), dtype=_JNP_DTYPES[self.specs[i].dtype])
            state["hop_next"] = jnp.asarray(_TS_NEG, dtype=jnp.int64)
            state["last_ts"] = jnp.asarray(_TS_NEG, dtype=jnp.int64)
            state["window_drops"] = jnp.zeros((), dtype=jnp.int64)
            state["ts_regressions"] = jnp.zeros((), dtype=jnp.int64)
            # gauges, read at drain points: events held, and the distinct
            # keys of the newest boundary's window
            state["window_held"] = jnp.zeros((), dtype=jnp.int32)
            state["window_live_keys"] = jnp.zeros((), dtype=jnp.int32)
            return state
        windowed = self.window_kind in ("length", "lengthBatch", "time",
                                        "timeBatch", "session", "timeLength",
                                        "hopping")
        if windowed:
            state["tail_fvals"] = jnp.zeros((AF, N), dtype=FACC)
            state["tail_ivals"] = jnp.zeros((AI, N), dtype=_IACC)
            state["tail_svals"] = jnp.zeros((AS, N), dtype=FACC)
            state["tail_ones"] = jnp.zeros((N,), dtype=jnp.int32)
            for i in self.magg_idx:
                dt = self._mdtype(i)
                state[f"tail_m{i}"] = jnp.full(
                    (N,), _ident(dt, self.specs[i].kind == "min"), dt)
        if self.window_kind in ("time", "timeLength"):
            # sentinel = long-expired; keeps the concat ts array sorted
            state["tail_ts"] = jnp.full((N,), _TS_NEG, dtype=jnp.int64)
            state["window_drops"] = jnp.zeros((), dtype=jnp.int64)
            state["last_ts"] = jnp.asarray(_TS_NEG, dtype=jnp.int64)
            state["ts_regressions"] = jnp.zeros((), dtype=jnp.int64)
        if self.window_kind in ("lengthBatch", "timeBatch", "session",
                                "delay"):
            state["rem_count"] = jnp.zeros((), dtype=jnp.int32)
            state["rem_ts"] = jnp.zeros((N,), dtype=jnp.int64)
            for i in self.value_idx:
                state[f"rem_proj_{i}"] = jnp.zeros(
                    (N,), dtype=_JNP_DTYPES[self.specs[i].dtype])
        if self.window_kind == "delay":
            state["window_drops"] = jnp.zeros((), dtype=jnp.int64)
            state["ts_regressions"] = jnp.zeros((), dtype=jnp.int64)
        if self.window_kind == "timeBatch":
            state["batch_base"] = jnp.asarray(_TS_NEG, dtype=jnp.int64)
        if self.window_kind in ("timeBatch", "session"):
            state["window_drops"] = jnp.zeros((), dtype=jnp.int64)
            state["ts_regressions"] = jnp.zeros((), dtype=jnp.int64)
        if self.window_kind == "hopping":
            state["tail_ts"] = jnp.full((N,), _TS_NEG, dtype=jnp.int64)
            state["hop_next"] = jnp.asarray(_TS_NEG, dtype=jnp.int64)
            state["window_drops"] = jnp.zeros((), dtype=jnp.int64)
            state["last_ts"] = jnp.asarray(_TS_NEG, dtype=jnp.int64)
            state["ts_regressions"] = jnp.zeros((), dtype=jnp.int64)
            for i in self.value_idx:
                state[f"tail_proj_{i}"] = jnp.zeros(
                    (N,), dtype=_JNP_DTYPES[self.specs[i].dtype])
        if self.window_kind in ("frequent", "lossyFrequent"):
            C = N
            state["hh_keys"] = jnp.zeros((C,), dtype=jnp.int64)
            state["hh_counts"] = jnp.zeros((C,), dtype=jnp.int64)
            state["hh_fvals"] = jnp.zeros((AF, C), dtype=FACC)
            state["hh_ivals"] = jnp.zeros((AI, C), dtype=_IACC)
            state["hh_run_f"] = jnp.zeros((AF,), dtype=FACC)
            state["hh_run_i"] = jnp.zeros((AI,), dtype=_IACC)
            state["hh_run_cnt"] = jnp.zeros((), dtype=jnp.int64)
            if self.window_kind == "lossyFrequent":
                state["hh_delta"] = jnp.zeros((C,), dtype=jnp.int64)
                state["hh_total"] = jnp.zeros((), dtype=jnp.int64)
                state["window_drops"] = jnp.zeros((), dtype=jnp.int64)
        if self.window_kind == "sort":
            kdt = _JNP_DTYPES[self.sort_key_type]
            # empty slots sort at +inf (after every real key, desc keys are
            # stored negated so ascending order IS the sort order)
            state["sort_keys"] = jnp.full((N,), _ident(kdt, True), dtype=kdt)
            state["sort_n"] = jnp.zeros((), dtype=jnp.int32)
            state["sort_fvals"] = jnp.zeros((AF, N), dtype=FACC)
            state["sort_ivals"] = jnp.zeros((AI, N), dtype=_IACC)
            state["sort_svals"] = jnp.zeros((AS, N), dtype=FACC)
            for i in self.magg_idx:
                dt = self._mdtype(i)
                state[f"sort_m{i}"] = jnp.full(
                    (N,), _ident(dt, self.specs[i].kind == "min"), dt)
        if self.group_keys and windowed:
            # windowed group-by carries no per-key sums — aggregates are
            # recomputed from window contents; only the bucket id per tail
            # slot and the collision-ownership map persist
            state["tail_gkey"] = jnp.zeros((N,), dtype=jnp.int32)
            state["key_owner"] = jnp.zeros((self.K,), dtype=jnp.int64)
            state["key_owned"] = jnp.zeros((self.K,), dtype=jnp.bool_)
            state["group_collisions"] = jnp.zeros((), dtype=jnp.int64)
        elif self.group_keys:
            K = self.K
            state["key_fsums"] = jnp.zeros((AF, K), dtype=FACC)
            state["key_fcomp"] = jnp.zeros((AF, K), dtype=FACC)
            state["key_isums"] = jnp.zeros((AI, K), dtype=_IACC)
            state["key_counts"] = jnp.zeros((K,), dtype=jnp.int64)
            state["key_owner"] = jnp.zeros((K,), dtype=jnp.int64)
            state["key_owned"] = jnp.zeros((K,), dtype=jnp.bool_)
            state["group_collisions"] = jnp.zeros((), dtype=jnp.int64)
            for i in self.magg_idx:
                dt = self._mdtype(i)
                state[f"key_m{i}"] = jnp.full(
                    (K,), _ident(dt, self.specs[i].kind == "min"), dt)
            state["key_smean"] = jnp.zeros((AS, K), dtype=FACC)
            state["key_sm2"] = jnp.zeros((AS, K), dtype=FACC)
            state["key_scnt"] = jnp.zeros((AS, K), dtype=FACC)
        if self.window_kind is None and not self.group_keys:
            state["run_fsums"] = jnp.zeros((AF,), dtype=FACC)
            state["run_fcomp"] = jnp.zeros((AF,), dtype=FACC)
            state["run_isums"] = jnp.zeros((AI,), dtype=_IACC)
            state["run_count"] = jnp.zeros((), dtype=jnp.int64)
            for i in self.magg_idx:
                dt = self._mdtype(i)
                state[f"run_m{i}"] = _ident(dt, self.specs[i].kind == "min")
            state["run_smean"] = jnp.zeros((AS,), dtype=FACC)
            state["run_sm2"] = jnp.zeros((AS,), dtype=FACC)
            state["run_scnt"] = jnp.zeros((AS,), dtype=FACC)
        return state

    # ------------------------------------------------------------------- step
    def _make_step(self):
        B = self.B
        filter_fns = list(self.filter_fns)
        specs = self.specs
        value_idx = self.value_idx
        fagg_idx, iagg_idx = self.fagg_idx, self.iagg_idx
        magg_idx, sagg_idx = self.magg_idx, self.sagg_idx
        window_kind, N = self.window_kind, max(self.window_n, 1)
        window_ms, time_key = self.window_ms, self.time_key
        hop_ms = getattr(self, "hop_ms", 0)
        hh_keys = getattr(self, "hh_keys", [])
        hh_support = getattr(self, "lossy_support", 0.0)
        hh_error = getattr(self, "lossy_error", 0.0)
        sort_key = getattr(self, "sort_key", None)
        sort_desc = getattr(self, "sort_desc", False)
        sort_kdt = _JNP_DTYPES[self.sort_key_type] \
            if window_kind == "sort" else None
        has_agg = bool(self.agg_idx)
        group_keys = list(self.group_keys)
        group_key_types = list(self.group_key_types)
        K = self.K
        having_fn = self.having_fn
        mdt = {i: self._mdtype(i) for i in magg_idx}
        m_ident = {i: _ident(mdt[i], specs[i].kind == "min") for i in magg_idx}
        m_ismin = {i: specs[i].kind == "min" for i in magg_idx}
        grouped_flush = self.grouped_flush
        kernel_scope = f"window.{window_kind}" if window_kind is not None \
            else "groupby" if group_keys else "aggregate"

        def step(state, cols, ts, valid):
            cols = dict(cols)
            cols["__ts__"] = ts
            with jax.named_scope("filter"):
                mask = valid
                for fn in filter_fns:
                    mask = jnp.logical_and(mask, fn(cols))

            # what the kernels read compacted (window semantics see accepted
            # events only, front-packed in their order): every column of
            # every kind is collected here, so that ONE `compact_front`
            # moves them all, and only where the mask has something to move
            if grouped_flush:
                gk = [cols[g].astype(_JNP_DTYPES[t])
                      for g, t in zip(group_keys, group_key_types)]
            else:
                # the dense table's packed key, a heavy-hitter window's
                gk = [cols[g].astype(jnp.int64)
                      for g in group_keys or hh_keys]
            raw = {
                "ts": ts,
                "proj": {i: specs[i].fn(cols) for i in value_idx},
                # fleet per-tenant parameter columns (injected by the caller,
                # not part of the schema): compacted so having programs over
                # hoisted constants stay row-aligned with the output columns
                "p": {kk: cols[kk] for kk in cols
                      if kk.startswith("__fleet_p")},
                "gk": gk,
                "f": [specs[i].fn(cols).astype(FACC) for i in fagg_idx],
                "i": [specs[i].fn(cols).astype(_IACC) for i in iagg_idx],
                "s": [specs[i].fn(cols).astype(FACC) for i in sagg_idx],
                "m": {i: specs[i].fn(cols).astype(mdt[i]) for i in magg_idx},
            }
            if window_kind in ("time", "timeLength", "timeBatch", "session",
                               "hopping"):
                # the window's clock (externalTime / externalTimeBatch read
                # it from a column)
                raw["wts"] = cols[time_key].astype(jnp.int64) if time_key \
                    else ts
            if window_kind == "sort":
                kv = cols[sort_key].astype(sort_kdt)
                if sort_desc:
                    # stored negated: ascending order IS the sort order and
                    # the evicted slot (N-1) is the per-order worst; int
                    # min would wrap under negation (it has no positive
                    # counterpart), so clamp it one up first
                    if not jnp.issubdtype(sort_kdt, jnp.floating):
                        lowest = jnp.iinfo(sort_kdt).min
                        kv = jnp.where(kv == lowest, lowest + 1, kv)
                    kv = -kv
                raw["skey"] = kv
            # behind the accepted events: zero, but the reduction's identity
            # for min / max, and what sorts behind every real time or key
            fills = {**jax.tree.map(lambda _: 0, raw), "m": m_ident}
            if "wts" in raw:
                fills["wts"] = _TS_POS
            if "skey" in raw:
                fills["skey"] = _ident(sort_kdt, True)

            with jax.named_scope("compact"):
                front, k, moved = compact_front(mask, raw, fills)
                cts, proj_c, pcols = front["ts"], front["proj"], front["p"]
                av_m = front["m"]

                def stack(rows, dt):
                    return jnp.stack(rows) if rows else jnp.zeros((0, B), dt)

                av_f, av_i = stack(front["f"], FACC), stack(front["i"], _IACC)
                av_s = stack(front["s"], FACC)                # raw values
                out_valid = jnp.arange(B) < k
                ones_c = out_valid.astype(jnp.int32)

            def make_keys():
                """Bucket id [B] + exact packed key [B] for the group-by
                columns (compacted). Single narrow keys (dictionary codes /
                small ints) mod K directly — collision-free while #groups<=K;
                wider combinations avalanche-mix."""
                k64 = front["gk"]
                narrow = all(t in (DataType.STRING, DataType.INT)
                             for t in group_key_types)
                if len(group_keys) == 1:
                    packed = k64[0]
                    if narrow:
                        keys = ((packed & 0x7FFFFFFFFFFFFFFF) % K).astype(
                            jnp.int32)
                    else:
                        keys = (_avalanche(packed) % K).astype(jnp.int32)
                elif len(group_keys) == 2 and narrow:
                    packed = (k64[0] << 32) | (k64[1] & 0xFFFFFFFF)
                    keys = (_avalanche(packed) % K).astype(jnp.int32)
                else:
                    packed = k64[0]
                    for kx in k64[1:]:
                        packed = packed * jnp.int64(0x100000001B3) ^ kx
                    keys = (_avalanche(packed) % K).astype(jnp.int32)
                return keys, packed

            def finish(state, sums_f, sums_i, cnts, mins, svars,
                       ovalid=out_valid, ots=cts, proj=proj_c, count=None):
                with jax.named_scope("select"):
                    out = _materialize(specs, value_idx, fagg_idx, iagg_idx,
                                       magg_idx, sagg_idx, proj, sums_f,
                                       sums_i, cnts, mins, svars)
                    if having_fn is not None:
                        ovalid = ovalid & jnp.broadcast_to(
                            having_fn({**pcols, **out} if pcols else out),
                            ovalid.shape)
                return state, {"out": out, "valid": ovalid, "ts": ots,
                               "count": k if count is None else count}

            def hop_clock():
                """The newest timestamp of the batch, filtered events
                included: the interpreter's boundary timer fires on the
                playback clock, which every event of the stream advances,
                whether or not it passes the filter into the window."""
                return jnp.max(jnp.where(valid, ts, _TS_NEG))

            def kernel():
                if window_kind in ("length", "time", "timeLength"):
                    if window_kind == "length":
                        z_f, z_i, z_s, zo, zm = _length_concat(
                            state, av_f, av_i, av_s, av_m, magg_idx, ones_c)
                        j = jnp.arange(B) + N
                        n_tail = jnp.sum(state["tail_ones"])
                        lo = jnp.maximum(j - N + 1, N - n_tail)
                        new_state = _slide_tails(state, z_f, z_i, z_s, zo, zm,
                                                 k, N)
                    else:
                        (z_f, z_i, z_s, zo, zm, j, lo, new_state) = \
                            _time_window_bounds(state, av_f, av_i, av_s, av_m,
                                                magg_idx, ones_c,
                                                front["wts"], k, N, B,
                                                window_ms)
                        if window_kind == "timeLength":
                            # the live range is ALSO bounded by the newest
                            # window_n events; evicting past the length bound is
                            # the window's own semantics (host TimeLengthWindow
                            # pops the oldest), not a capacity overflow — the
                            # tail is sized to window_n, so un-count the drops
                            lo = jnp.maximum(lo, j - N + 1)
                            new_state["window_drops"] = state["window_drops"]
                    if group_keys:
                        # per-key aggregates over the live window range: one-hot
                        # [M,K] cumulative grids; output j reads its own bucket at
                        # the range bounds (reference: per-group aggregator map
                        # fed by CURRENT+EXPIRED window events — here expiry is
                        # the range lower bound, no retraction needed)
                        with jax.named_scope("groupby"):
                            keys_b, packed = make_keys()
                            zk = jnp.concatenate([state["tail_gkey"], keys_b])
                            sums_f = _keyed_range_sums(z_f, zk, K, lo, j, keys_b)
                            sums_i = _keyed_range_sums(z_i, zk, K, lo, j, keys_b)
                            ohz = jax.nn.one_hot(zk, K, dtype=jnp.int32) \
                                * zo[:, None]
                            csk = jnp.concatenate(
                                [jnp.zeros((1, K), jnp.int32),
                                 jnp.cumsum(ohz, axis=0)])
                            cnts = (csk[j + 1, keys_b] - csk[lo, keys_b]).astype(
                                jnp.int64)
                            new_state["tail_gkey"] = jax.lax.dynamic_slice(
                                zk, (k,), (N,))
                            # collision accounting (carried ownership, same policy as
                            # the unwindowed dense table)
                            onehot_b = (jax.nn.one_hot(keys_b, K, dtype=jnp.int32)
                                        * out_valid[:, None].astype(jnp.int32))
                            first_occ = (jnp.cumsum(onehot_b, axis=0) == 1) & \
                                onehot_b.astype(bool)
                            batch_first = jnp.sum(
                                jnp.where(first_occ, packed[:, None], 0), axis=0)
                            owned = state["key_owned"]
                            claimed = jnp.where(owned, state["key_owner"],
                                                batch_first)
                            coll = out_valid & (packed != claimed[keys_b])
                            new_state["key_owner"] = claimed
                            new_state["key_owned"] = owned | jnp.any(
                                first_occ, axis=0)
                            new_state["group_collisions"] = \
                                state["group_collisions"] + jnp.sum(
                                    coll.astype(jnp.int64))
                        return finish(new_state, sums_f, sums_i, cnts, {},
                                      jnp.zeros((0, B), FACC))
                    sums_f = _range_sums(z_f, lo, j)
                    sums_i = _range_sums(z_i, lo, j)
                    cso = jnp.concatenate(
                        [jnp.zeros((1,), jnp.int32), jnp.cumsum(zo)])
                    cnts = (cso[j + 1] - cso[lo]).astype(jnp.int64)
                    mins = {i: _range_reduce(zm[i], lo, j, m_ismin[i])
                            for i in magg_idx}
                    svars = _window_svars(z_s, zo, lo, j, cnts, k, N, B)
                    return finish(new_state, sums_f, sums_i, cnts, mins, svars)

                if window_kind == "lengthBatch":
                    return _length_batch(state, specs, value_idx, fagg_idx,
                                         iagg_idx, magg_idx, sagg_idx, m_ismin,
                                         proj_c, av_f, av_i, av_s, av_m, ones_c,
                                         cts, k, N, B, finish,
                                         agg_collapse=has_agg)

                if window_kind in ("timeBatch", "session"):
                    return _segmented_batch(state, value_idx, fagg_idx, iagg_idx,
                                            magg_idx, sagg_idx, m_ismin, proj_c,
                                            av_f, av_i, av_s, av_m, ones_c,
                                            front["wts"], k, N, B, finish,
                                            window_kind, window_ms,
                                            agg_collapse=has_agg)

                if window_kind == "batch":
                    # the accepted sub-batch IS the chunk (reference
                    # BatchWindowProcessor expires the previous chunk + RESET,
                    # so aggregates restart per step); with aggregates the chunk
                    # collapses to ONE row — the last accepted slot (reference
                    # QuerySelector.processInBatchNoGroupBy keeps lastEvent)
                    j = jnp.arange(B)
                    lo0 = jnp.zeros((B,), jnp.int32)
                    sums_f = _range_sums(av_f, lo0, j)
                    sums_i = _range_sums(av_i, lo0, j)
                    cnts = jnp.cumsum(ones_c).astype(jnp.int64)
                    mins = {i: _range_reduce(av_m[i], lo0, j, m_ismin[i])
                            for i in magg_idx}
                    svars = _window_svars(av_s, ones_c, lo0, j, cnts, k, 0, B)
                    ovalid = out_valid
                    if has_agg:
                        ovalid = ovalid & (j == k - 1)
                    return finish(state, sums_f, sums_i, cnts, mins, svars,
                                  ovalid=ovalid,
                                  count=jnp.sum(ovalid.astype(jnp.int32)))

                if window_kind == "sort":
                    new_state, sums_f, sums_i, cnts, mins, svars = _sort_window(
                        state, front["skey"], av_f, av_i, av_s, av_m,
                        magg_idx, m_ismin, k, N, B)
                    return finish(new_state, sums_f, sums_i, cnts, mins, svars)

                if grouped_flush:
                    return _hopping_grouped(
                        self, state, front["gk"], av_f, av_i, av_m, m_ismin,
                        m_ident, proj_c, front["wts"], k, hop_clock())

                if window_kind == "hopping":
                    return _hopping_flushes(
                        state, value_idx, av_f, av_i, av_s, av_m, magg_idx,
                        m_ismin, ones_c, proj_c, front["wts"], k, N, B,
                        window_ms, hop_ms, finish, hop_clock())

                if window_kind in ("frequent", "lossyFrequent"):
                    k64 = front["gk"]
                    if len(k64) == 2:
                        kcode = (k64[0] << 32) | (k64[1] & 0xFFFFFFFF)
                    else:
                        kcode = k64[0]
                    new_state, emit, sums_f, sums_i, cnts = _heavy_hitters(
                        state, kcode, av_f, av_i, k, N, B,
                        lossy=(window_kind == "lossyFrequent"),
                        support=hh_support, error=hh_error)
                    return finish(new_state, sums_f, sums_i, cnts, {},
                                  jnp.zeros((0, B), FACC),
                                  ovalid=out_valid & emit,
                                  count=jnp.sum((out_valid & emit)
                                                .astype(jnp.int32)))

                if window_kind == "delay":
                    # pass-through after a fixed delay: hold rows until the
                    # newest arrival passes held_ts + delay; emitted rows carry
                    # ts = held_ts + delay (the host's timer fires then, before
                    # the surfacing event is processed)
                    r = state["rem_count"]
                    M = N + B
                    total = r + k
                    zm_mask = jnp.concatenate(
                        [jnp.arange(N) < r, jnp.arange(B) < k])
                    zrank = jnp.cumsum(zm_mask.astype(jnp.int32)) - 1
                    zpos = jnp.where(zm_mask, zrank, M - 1)

                    def zc(x_rem, x_batch, fill=None):
                        x = jnp.concatenate([x_rem, x_batch])
                        f = jnp.zeros((), x.dtype) if fill is None else fill
                        outv = jnp.full((M,), f, dtype=x.dtype)
                        return outv.at[zpos].set(
                            jnp.where(zm_mask, x, f), mode="drop")

                    j2 = jnp.arange(M)
                    zts_raw = zc(state["rem_ts"], cts,
                                 fill=jnp.asarray(_TS_POS, jnp.int64))
                    # monotonize (same loud clamp as every time kernel): the
                    # release mask must be a PREFIX, or a held out-of-order row
                    # gets silently discarded by the newest-N remainder slice
                    zts = jax.lax.cummax(zts_raw)
                    regressions = jnp.sum(((zts > zts_raw) & (j2 < total))
                                          .astype(jnp.int64))
                    zproj = {i: zc(state[f"rem_proj_{i}"], proj_c[i])
                             for i in value_idx}
                    newest = jnp.where(
                        total > 0, zts[jnp.clip(total - 1, 0, M - 1)], _TS_NEG)
                    release = (j2 < total) & (zts + window_ms <= newest)
                    n_rel = jnp.sum(release.astype(jnp.int32))
                    rem_n = jnp.minimum(total - n_rel, N)
                    dropped = (total - n_rel - rem_n).astype(jnp.int64)
                    slice_from = jnp.maximum(total - rem_n, 0)

                    def rem_slice(row):
                        padded = jnp.concatenate(
                            [row, jnp.zeros((N,), row.dtype)])
                        return jax.lax.dynamic_slice(padded, (slice_from,), (N,))

                    keep = jnp.arange(N) < rem_n
                    new_state = {**state,
                                 "rem_count": rem_n.astype(jnp.int32),
                                 "window_drops": state["window_drops"] + dropped,
                                 "ts_regressions":
                                     state["ts_regressions"] + regressions}
                    new_state["rem_ts"] = jnp.where(keep, rem_slice(zts), 0)
                    for i in value_idx:
                        z_p = zproj[i]
                        new_state[f"rem_proj_{i}"] = jnp.where(
                            keep, rem_slice(z_p), jnp.zeros((), z_p.dtype))
                    out = {specs[i].name: zproj[i] for i in value_idx}
                    ovalid = release
                    if having_fn is not None:
                        ovalid = ovalid & jnp.broadcast_to(
                            having_fn(out), ovalid.shape)
                    return new_state, {"out": out, "valid": ovalid,
                                       "ts": zts + window_ms,
                                       "count": jnp.sum(
                                           release.astype(jnp.int32))}

                if group_keys:
                    # exact packed key (for collision detection) + bucket id —
                    # see make_keys(). A bucket claimed by a different packed key
                    # is COUNTED (group_collisions) — loud, bounded-table
                    # overflow policy like window/slot drops.
                    keys, packed = make_keys()
                    onehot = (jax.nn.one_hot(keys, K, dtype=jnp.int32)
                              * out_valid[:, None].astype(jnp.int32))     # [B,K]
                    first_occ = (jnp.cumsum(onehot, axis=0) == 1) & \
                        onehot.astype(bool)                               # [B,K]

                    # collision accounting: the bucket's owner is its carried
                    # claimant or, if empty, the first claimant in this batch
                    # (ownership validity is a separate flag: any int64 is a
                    # legal packed key, so no value can serve as a sentinel)
                    batch_first = jnp.sum(
                        jnp.where(first_occ, packed[:, None], 0), axis=0)  # [K]
                    has_batch = jnp.any(first_occ, axis=0)
                    owned = state["key_owned"]
                    claimed = jnp.where(owned, state["key_owner"], batch_first)
                    coll = out_valid & (packed != claimed[keys])
                    new_owner = claimed
                    new_owned = owned | has_batch

                    def per_key(av, base, dt):
                        contrib = onehot[None].astype(dt) * av[:, :, None]  # [A,B,K]
                        ccum = jnp.cumsum(contrib, axis=1)
                        per_ev = jnp.take_along_axis(
                            ccum, keys[None, :, None], axis=2)[:, :, 0] \
                            + base[:, keys]
                        return per_ev, contrib.sum(axis=1)

                    sums_f, add_f = per_key(av_f, state["key_fsums"], FACC) \
                        if len(fagg_idx) else (jnp.zeros((0, B), FACC),
                                               jnp.zeros((0, K), FACC))
                    sums_i, add_i = per_key(av_i, state["key_isums"], _IACC) \
                        if len(iagg_idx) else (jnp.zeros((0, B), _IACC),
                                               jnp.zeros((0, K), _IACC))
                    ocum = jnp.cumsum(onehot, axis=0)
                    cnts = (jnp.take_along_axis(ocum, keys[:, None], axis=1)[:, 0]
                            .astype(jnp.int64) + state["key_counts"][keys])
                    nf, nc = _kahan_add(state["key_fsums"], state["key_fcomp"],
                                        add_f)
                    new_state = {**state, "key_fsums": nf, "key_fcomp": nc,
                                 "key_isums": state["key_isums"] + add_i,
                                 "key_counts": state["key_counts"]
                                 + onehot.sum(axis=0).astype(jnp.int64),
                                 "key_owner": new_owner,
                                 "key_owned": new_owned,
                                 "group_collisions": state["group_collisions"]
                                 + jnp.sum(coll.astype(jnp.int64))}

                    # min/max per key: cumulative reduction over one-hot grids
                    mins = {}
                    for i in magg_idx:
                        ident = m_ident[i]
                        grid = jnp.where(onehot.astype(bool),
                                         av_m[i][:, None], ident)          # [B,K]
                        red = jax.lax.cummin if m_ismin[i] else jax.lax.cummax
                        g = red(grid, axis=0)
                        per_ev = jnp.take_along_axis(g, keys[:, None], axis=1)[:, 0]
                        carried = state[f"key_m{i}"][keys]
                        mins[i] = jnp.minimum(per_ev, carried) if m_ismin[i] \
                            else jnp.maximum(per_ev, carried)
                        new_state[f"key_m{i}"] = (
                            jnp.minimum(state[f"key_m{i}"], g[-1]) if m_ismin[i]
                            else jnp.maximum(state[f"key_m{i}"], g[-1]))

                    # stdDev per key: shifted moments centered at the key's
                    # carried mean (Welford merged at batch granularity)
                    svars = jnp.zeros((len(sagg_idx), B), FACC)
                    for si in range(len(sagg_idx)):
                        # center at the key's carried mean; for a never-seen key
                        # use its first value in this batch — centering at 0 would
                        # cancel catastrophically in f32 for near-equal values
                        firstval = jnp.sum(
                            jnp.where(first_occ, av_s[si][:, None], 0.0), axis=0)
                        c_key = jnp.where(state["key_scnt"][si] > 0,
                                          state["key_smean"][si], firstval)  # [K]
                        c_ev = c_key[keys]                                # [B]
                        d = (av_s[si] - c_ev) * onehot.sum(axis=1).astype(FACC)
                        d2 = d * d
                        grid1 = onehot.astype(FACC) * d[:, None]
                        grid2 = onehot.astype(FACC) * d2[:, None]
                        cs1 = jnp.cumsum(grid1, axis=0)
                        cs2 = jnp.cumsum(grid2, axis=0)
                        s1 = jnp.take_along_axis(cs1, keys[:, None], axis=1)[:, 0]
                        s2 = jnp.take_along_axis(cs2, keys[:, None], axis=1)[:, 0]
                        m2p = state["key_sm2"][si][keys]
                        # per-key event count at this row (aggregates share the
                        # accepted-event axis)
                        nsc = state["key_scnt"][si][keys] + \
                            jnp.take_along_axis(ocum, keys[:, None],
                                                axis=1)[:, 0].astype(FACC)
                        var = jnp.maximum(
                            (m2p + s2) / jnp.maximum(nsc, 1.0)
                            - ((s1) / jnp.maximum(nsc, 1.0)) ** 2, 0.0)
                        svars = svars.at[si].set(jnp.sqrt(var))
                        # state update: recenter to the new mean
                        add1 = cs1[-1]                                     # [K]
                        add2 = cs2[-1]
                        addn = onehot.sum(axis=0).astype(FACC)
                        n_new = state["key_scnt"][si] + addn
                        mean_new = c_key + add1 / jnp.maximum(n_new, 1.0)
                        m2_new = state["key_sm2"][si] + add2 - \
                            jnp.maximum(n_new, 1.0) * (mean_new - c_key) ** 2
                        new_state["key_smean"] = new_state["key_smean"].at[si].set(
                            mean_new)
                        new_state["key_sm2"] = new_state["key_sm2"].at[si].set(
                            jnp.maximum(m2_new, 0.0))
                        new_state["key_scnt"] = new_state["key_scnt"].at[si].set(
                            n_new)
                    return finish(new_state, sums_f, sums_i, cnts, mins, svars)

                # running aggregates, no window/grouping
                cs_f = jnp.cumsum(av_f, axis=1)
                cs_i = jnp.cumsum(av_i, axis=1)
                cso = jnp.cumsum(ones_c).astype(jnp.int64)
                sums_f = cs_f + state["run_fsums"][:, None]
                sums_i = cs_i + state["run_isums"][:, None]
                cnts = cso + state["run_count"]
                nf, nc = _kahan_add(state["run_fsums"], state["run_fcomp"],
                                    av_f.sum(axis=1))
                new_state = {**state, "run_fsums": nf, "run_fcomp": nc,
                             "run_isums": state["run_isums"] + av_i.sum(axis=1),
                             "run_count": state["run_count"]
                             + ones_c.sum().astype(jnp.int64)}
                mins = {}
                for i in magg_idx:
                    red = jax.lax.cummin if m_ismin[i] else jax.lax.cummax
                    pre = red(av_m[i])
                    carried = state[f"run_m{i}"]
                    mins[i] = jnp.minimum(pre, carried) if m_ismin[i] \
                        else jnp.maximum(pre, carried)
                    new_state[f"run_m{i}"] = mins[i][-1]
                svars = jnp.zeros((len(sagg_idx), B), FACC)
                for si in range(len(sagg_idx)):
                    # center at the carried mean; on the very first events use the
                    # first accepted value (0-centering cancels catastrophically)
                    c = jnp.where(state["run_scnt"][si] > 0,
                                  state["run_smean"][si], av_s[si][0])
                    occ = ones_c.astype(FACC)
                    d = (av_s[si] - c) * occ
                    d2 = d * d
                    s1 = jnp.cumsum(d)
                    s2 = jnp.cumsum(d2)
                    nsc = state["run_scnt"][si] + jnp.cumsum(occ)
                    var = jnp.maximum(
                        (state["run_sm2"][si] + s2) / jnp.maximum(nsc, 1.0)
                        - (s1 / jnp.maximum(nsc, 1.0)) ** 2, 0.0)
                    svars = svars.at[si].set(jnp.sqrt(var))
                    n_new = state["run_scnt"][si] + occ.sum()
                    mean_new = c + s1[-1] / jnp.maximum(n_new, 1.0)
                    m2_new = state["run_sm2"][si] + s2[-1] - \
                        jnp.maximum(n_new, 1.0) * (mean_new - c) ** 2
                    new_state["run_smean"] = new_state["run_smean"].at[si].set(
                        mean_new)
                    new_state["run_sm2"] = new_state["run_sm2"].at[si].set(
                        jnp.maximum(m2_new, 0.0))
                    new_state["run_scnt"] = new_state["run_scnt"].at[si].set(n_new)
                return finish(new_state, sums_f, sums_i, cnts, mins, svars)

            # one scope per kernel: the window, else the dense group-by table,
            # else the running aggregates (scopes are metadata on the
            # compiled operations; a trace names device time by them)
            with jax.named_scope(kernel_scope):
                new_state, out = kernel()
            # steps whose mask was not a prefix, so that rows were moved: a
            # state scalar like the overflow counters, read at drain points
            # (a snapshot from before PR 38 restores without it)
            moves = state.get("compact_moves", jnp.zeros((), jnp.int64))
            return {**new_state,
                    "compact_moves": moves + moved.astype(jnp.int64)}, out

        return step

    # stdDev's event axis is the same accepted-event axis as counts

    # -------------------------------------------------------------- execution
    def step(self, state, batch: dict):
        """batch: output of BatchBuilder.emit() (numpy); returns (state, out)."""
        return self._step(state, batch["cols"], batch["ts"], batch["valid"])

    def decode_outputs(self, out):
        """One step's outputs → a :class:`~siddhi_tpu.core.columns.ColumnsOut`:
        the copies to the host, then every column masked by ``valid`` in one
        NumPy index each (string codes stay codes until ``decoded()`` /
        ``rows()``)."""
        from ..core.columns import ColumnsOut
        if self.grouped_flush:
            # rows sit at the front of each boundary's slot: the row counts
            # first (the fence has fetched them), and the columns are copied
            # only where a boundary left rows, whole: [flush_cap, limit]
            # each, no program of its own to slice them on the device
            nrows = np.asarray(out["nrows"])
            fired = np.flatnonzero(nrows)
            cols = {}
            for s in self.specs:
                if fired.size:
                    host = np.asarray(out["out"][s.name])
                    cols[s.name] = np.concatenate(
                        [host[f, :nrows[f]] for f in fired])
                else:
                    cols[s.name] = np.zeros((0,), _NP_DTYPES[s.dtype])
            return ColumnsOut(None, cols, int(nrows.sum()), self.out_specs,
                              self.schema.dictionaries)
        idx = np.flatnonzero(np.asarray(out["valid"]))
        cols = {s.name: np.asarray(out["out"][s.name])[idx]
                for s in self.specs}
        return ColumnsOut(None, cols, int(idx.size), self.out_specs,
                          self.schema.dictionaries)


# ---------------------------------------------------------------------------
# window kernels
# ---------------------------------------------------------------------------

def _slide_tails(state, z_f, z_i, z_s, zo, zm, k, N):
    take = lambda row: jax.lax.dynamic_slice(row, (k,), (N,))
    new = {
        **state,
        "tail_fvals": jax.vmap(take)(z_f) if z_f.shape[0] else state["tail_fvals"],
        "tail_ivals": jax.vmap(take)(z_i) if z_i.shape[0] else state["tail_ivals"],
        "tail_svals": jax.vmap(take)(z_s) if z_s.shape[0] else state["tail_svals"],
        "tail_ones": take(zo),
    }
    for i, z in zm.items():
        new[f"tail_m{i}"] = take(z)
    return new


def _range_sums(z, lo, j):
    """Sums of z over inclusive ranges [lo, j] (leading-zero cumsum diff)."""
    if not z.shape[0]:
        return jnp.zeros((0, j.shape[0]), z.dtype)
    cs = jnp.concatenate(
        [jnp.zeros((z.shape[0], 1), z.dtype), jnp.cumsum(z, axis=1)], axis=1)
    return cs[:, j + 1] - cs[:, lo]


def _keyed_range_sums(z, zk, K, lo, j, keys_b):
    """Per-key sums over inclusive ranges [lo, j]: one-hot [M,K] cumulative
    grid per lane; output event b reads its own bucket column at both range
    bounds. O(M·K) HBM per lane — the windowed-group-by trade for zero
    retraction bookkeeping."""
    if not z.shape[0]:
        return jnp.zeros((0, j.shape[0]), z.dtype)
    oh = jax.nn.one_hot(zk, K, dtype=z.dtype)                  # [M, K]
    outs = []
    for a in range(z.shape[0]):
        cs = jnp.concatenate(
            [jnp.zeros((1, K), z.dtype),
             jnp.cumsum(oh * z[a][:, None], axis=0)])
        outs.append(cs[j + 1, keys_b] - cs[lo, keys_b])
    return jnp.stack(outs)


def _window_svars(z_s, zo, lo, j, cnts, k, N, B):
    """stdDev over inclusive ranges: shifted second moments, centered at the
    current batch's mean, ACCUMULATED IN f64 — the prefix-sum differences
    cancel catastrophically (a single-element range's variance is the
    difference of two near-equal slab totals; f32 there leaves ~1e-2
    absolute noise on 1e2-scale values, measured by the differential fuzz)."""
    AS = z_s.shape[0]
    if not AS:
        return jnp.zeros((0, B), FACC)
    occ = (zo > 0).astype(jnp.float64)
    out = jnp.zeros((AS, B), FACC)
    n = jnp.maximum(cnts.astype(jnp.float64), 1.0)
    for si in range(AS):
        raw = z_s[si].astype(jnp.float64)
        c = jnp.sum(raw * occ) / jnp.maximum(jnp.sum(occ), 1.0)
        d = (raw - c) * occ
        cs1 = jnp.concatenate([jnp.zeros((1,), jnp.float64), jnp.cumsum(d)])
        cs2 = jnp.concatenate([jnp.zeros((1,), jnp.float64),
                               jnp.cumsum(d * d)])
        s1 = cs1[j + 1] - cs1[lo]
        s2 = cs2[j + 1] - cs2[lo]
        var = jnp.maximum(s2 / n - (s1 / n) ** 2, 0.0)
        out = out.at[si].set(jnp.sqrt(var).astype(FACC))
    return out


def _length_concat(state, av_f, av_i, av_s, av_m, magg_idx, ones_c):
    z_f = jnp.concatenate([state["tail_fvals"], av_f], axis=1)
    z_i = jnp.concatenate([state["tail_ivals"], av_i], axis=1)
    z_s = jnp.concatenate([state["tail_svals"], av_s], axis=1)
    zo = jnp.concatenate([state["tail_ones"], ones_c])
    zm = {i: jnp.concatenate([state[f"tail_m{i}"], av_m[i]])
          for i in magg_idx}
    return z_f, z_i, z_s, zo, zm


def _monotone_ts(wts, k, B, last_ts):
    """Event times of the accepted sub-batch clamped to be non-decreasing
    (from ``last_ts`` on), padding at ``_TS_POS``; and how many were
    clamped (``ts_regressions``)."""
    valid = jnp.arange(B) < k
    raw = jnp.where(valid, wts, _TS_POS)
    mono = jnp.maximum(jax.lax.cummax(raw), last_ts)
    regressed = jnp.sum(jnp.where(valid & (raw < mono), 1, 0)) \
        .astype(jnp.int64)
    return jnp.where(valid, mono, _TS_POS), mono, regressed


def _time_window_bounds(state, av_f, av_i, av_s, av_m, magg_idx, ones_c,
                        wts, k, N, B, D):
    """Time-window variant: monotonicity clamp, searchsorted lower bounds,
    overflow accounting. Returns concat lanes + (j, lo) ranges + new state."""
    wts_s, mono, regressed = _monotone_ts(wts, k, B, state["last_ts"])
    z_f, z_i, z_s, zo, zm = _length_concat(
        state, av_f, av_i, av_s, av_m, magg_idx, ones_c)
    zts = jnp.concatenate([state["tail_ts"], wts_s])               # [N+B]
    j = jnp.arange(B) + N
    lo = jnp.searchsorted(zts, wts_s - D, side="right")            # [B]

    newest = zts[jnp.maximum(N + k - 1, 0)]
    sliced = jnp.arange(N + B) < k
    drops = jnp.sum(jnp.where(sliced & (zts > newest - D), zo, 0)
                    ).astype(jnp.int64)

    new_state = _slide_tails(state, z_f, z_i, z_s, zo, zm, k, N)
    new_state.update({
        "tail_ts": jax.lax.dynamic_slice(zts, (k,), (N,)),
        "window_drops": state["window_drops"] + drops,
        "last_ts": jnp.maximum(state["last_ts"],
                               jnp.where(k > 0, mono[jnp.maximum(k - 1, 0)],
                                         state["last_ts"])),
        "ts_regressions": state["ts_regressions"] + regressed,
    })
    return z_f, z_i, z_s, zo, zm, j, lo, new_state


def _length_batch(state, specs, value_idx, fagg_idx, iagg_idx, magg_idx,
                  sagg_idx, m_ismin, proj_c, av_f, av_i, av_s, av_m, ones_c,
                  cts, k, N, B, finish, agg_collapse=False):
    """Tumbling window: carried remainder (projections + agg args), outputs over
    [N+B] slots covering remainder + current arrivals."""
    r = state["rem_count"]
    M = N + B
    total = r + k
    # contiguous accepted sequence: remainder (first r of N) then batch (first k)
    zm_mask = jnp.concatenate([jnp.arange(N) < r, jnp.arange(B) < k])
    zrank = jnp.cumsum(zm_mask.astype(jnp.int32)) - 1
    zpos = jnp.where(zm_mask, zrank, M - 1)

    def zc(x_rem, x_batch, fill=None):
        x = jnp.concatenate([x_rem, x_batch])
        f = jnp.zeros((), x.dtype) if fill is None else fill
        out = jnp.full((M,), f, dtype=x.dtype)
        return out.at[zpos].set(jnp.where(zm_mask, x, f), mode="drop")

    z_f = jax.vmap(zc)(state["tail_fvals"], av_f) if len(fagg_idx) \
        else jnp.zeros((0, M), FACC)
    z_i = jax.vmap(zc)(state["tail_ivals"], av_i) if len(iagg_idx) \
        else jnp.zeros((0, M), _IACC)
    z_s = jax.vmap(zc)(state["tail_svals"], av_s) if len(sagg_idx) \
        else jnp.zeros((0, M), FACC)
    zm = {i: zc(state[f"tail_m{i}"], av_m[i],
                fill=_ident(av_m[i].dtype, m_ismin[i])) for i in magg_idx}
    zts = zc(state["rem_ts"], cts)
    zproj = {i: zc(state[f"rem_proj_{i}"], proj_c[i]) for i in value_idx}
    zo = zc(jnp.where(jnp.arange(N) < r, state["tail_ones"], 0), ones_c)

    j2 = jnp.arange(M)
    batch_start = (j2 // N) * N
    sums_f = _range_sums(z_f, batch_start, j2)
    sums_i = _range_sums(z_i, batch_start, j2)
    cnts = (j2 % N + 1).astype(jnp.int64)
    mins = {i: _range_reduce(zm[i], batch_start, j2, m_ismin[i])
            for i in magg_idx}
    svars = _window_svars(z_s, zo, batch_start, j2, cnts, k, N, M)

    full_batches = total // N
    out_valid = (j2 < full_batches * N) & (j2 < total)
    if agg_collapse:
        # aggregated batch chunks collapse to ONE row per flush — the last
        # slot of each completed batch (reference
        # QuerySelector.processInBatchNoGroupBy:271)
        out_valid = out_valid & (j2 % N == N - 1)

    rem_n = total - full_batches * N
    def rem_slice(row):
        # start can exceed M-N (e.g. batch capacity < N): pad so the slice
        # never clamps back into emitted slots — padded values land past
        # rem_n and are masked by `keep` below
        padded = jnp.concatenate([row, jnp.zeros((N,), row.dtype)])
        return jax.lax.dynamic_slice(padded, (full_batches * N,), (N,))
    keep = jnp.arange(N) < rem_n
    new_state = {**state, "rem_count": rem_n.astype(jnp.int32)}
    new_state["tail_fvals"] = jnp.where(
        keep[None, :], jax.vmap(rem_slice)(z_f), 0.0) if len(fagg_idx) \
        else state["tail_fvals"]
    new_state["tail_ivals"] = jnp.where(
        keep[None, :], jax.vmap(rem_slice)(z_i), 0) if len(iagg_idx) \
        else state["tail_ivals"]
    new_state["tail_svals"] = jnp.where(
        keep[None, :], jax.vmap(rem_slice)(z_s), 0.0) if len(sagg_idx) \
        else state["tail_svals"]
    for i in magg_idx:
        ident = _ident(zm[i].dtype, m_ismin[i])
        new_state[f"tail_m{i}"] = jnp.where(keep, rem_slice(zm[i]), ident)
    new_state["tail_ones"] = jnp.where(keep, rem_slice(zo), 0)
    new_state["rem_ts"] = jnp.where(keep, rem_slice(zts), 0)
    for i in value_idx:
        z_p = zproj[i]
        new_state[f"rem_proj_{i}"] = jnp.where(
            keep, rem_slice(z_p), jnp.zeros((), z_p.dtype))

    return finish(new_state, sums_f, sums_i, cnts, mins, svars,
                  ovalid=out_valid, ots=zts, proj=zproj,
                  count=full_batches * N)


def _segmented_batch(state, value_idx, fagg_idx, iagg_idx, magg_idx,
                     sagg_idx, m_ismin, proj_c, av_f, av_i, av_s, av_m,
                     ones_c, cts_pos, k, N, B, finish, mode, window_ms,
                     agg_collapse=False):
    """timeBatch (tumbling time buckets) and session (gap-separated runs) as
    one segmented kernel over [remainder + batch] slots.

    - ``timeBatch``: segment id = (ts − base)//duration; only CLOSED buckets
      (a later bucket's event exists) emit, each slot with running aggregates
      over its own bucket — the host flushes inline the same way when an
      event at/past the boundary arrives (``TimeBatchWindow.process``).
    - ``session``: segments break where the inter-event gap exceeds the gap
      parameter; every NEW event emits immediately (host SessionWindow passes
      currents through) with aggregates over its open session so far.

    The open (last) segment carries to the next step, capped at N newest
    events with ``window_drops`` counting evictions.
    """
    r = state["rem_count"]
    M = N + B
    total = r + k
    zm_mask = jnp.concatenate([jnp.arange(N) < r, jnp.arange(B) < k])
    zrank = jnp.cumsum(zm_mask.astype(jnp.int32)) - 1
    zpos = jnp.where(zm_mask, zrank, M - 1)

    def zc(x_rem, x_batch, fill=None):
        x = jnp.concatenate([x_rem, x_batch])
        f = jnp.zeros((), x.dtype) if fill is None else fill
        out = jnp.full((M,), f, dtype=x.dtype)
        return out.at[zpos].set(jnp.where(zm_mask, x, f), mode="drop")

    z_f = jax.vmap(zc)(state["tail_fvals"], av_f) if len(fagg_idx) \
        else jnp.zeros((0, M), FACC)
    z_i = jax.vmap(zc)(state["tail_ivals"], av_i) if len(iagg_idx) \
        else jnp.zeros((0, M), _IACC)
    z_s = jax.vmap(zc)(state["tail_svals"], av_s) if len(sagg_idx) \
        else jnp.zeros((0, M), FACC)
    zm = {i: zc(state[f"tail_m{i}"], av_m[i],
                fill=_ident(av_m[i].dtype, m_ismin[i])) for i in magg_idx}
    # padding slots carry +inf timestamps: they sort after every real event
    # and land in their own far-future segment
    zts = zc(state["rem_ts"], cts_pos, fill=jnp.asarray(_TS_POS, jnp.int64))
    zproj = {i: zc(state[f"rem_proj_{i}"], proj_c[i]) for i in value_idx}
    zo = zc(jnp.where(jnp.arange(N) < r, state["tail_ones"], 0), ones_c)

    j2 = jnp.arange(M)
    last_idx = jnp.clip(total - 1, 0, M - 1)
    # segments need nondecreasing time: out-of-order arrivals are clamped to
    # the running max (counted — same loud policy as the sliding time window;
    # the host buckets by arrival within the open bucket, which this matches)
    zts_m = jax.lax.cummax(zts)
    regressions = jnp.sum(((zts_m > zts) & (j2 < total)).astype(jnp.int64))
    if mode == "timeBatch":
        armed = state["batch_base"] > _TS_NEG
        base = jnp.where(armed, state["batch_base"], zts_m[0])
        seg = (zts_m - base) // jnp.int64(window_ms)
        seg_last = seg[last_idx]
        out_valid = (j2 < total) & (seg < seg_last)
        if agg_collapse:
            # aggregated batch chunks collapse to ONE row per closed
            # bucket — its last slot (reference
            # QuerySelector.processInBatchNoGroupBy:271)
            nxt = jnp.clip(j2 + 1, 0, M - 1)
            last_in_seg = (j2 + 1 >= total) | (seg[nxt] != seg)
            out_valid = out_valid & last_in_seg
        open_mask = (j2 < total) & (seg == seg_last)
    else:                                   # session
        prev_ts = jnp.concatenate([zts_m[:1], zts_m[:-1]])
        # a gap of EXACTLY the parameter closes the session (host timer fires
        # at last_ts + gap before the arrival is processed)
        brk = ((zts_m - prev_ts) >= window_ms).at[0].set(False)
        seg = jnp.cumsum(brk.astype(jnp.int64))
        seg_last = seg[last_idx]
        out_valid = (j2 >= r) & (j2 < total)      # currents pass through once
        open_mask = (j2 < total) & (seg == seg_last)

    seg_start = jnp.searchsorted(seg, seg, side="left")
    sums_f = _range_sums(z_f, seg_start, j2)
    sums_i = _range_sums(z_i, seg_start, j2)
    cso = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(zo)])
    cnts = (cso[j2 + 1] - cso[seg_start]).astype(jnp.int64)
    mins = {i: _range_reduce(zm[i], seg_start, j2, m_ismin[i])
            for i in magg_idx}
    svars = _window_svars(z_s, zo, seg_start, j2, cnts, k, N, M)

    # carry the open segment, capped at the N NEWEST events
    open_count = jnp.sum(open_mask.astype(jnp.int32))
    rem_n = jnp.minimum(open_count, N)
    dropped = (open_count - rem_n).astype(jnp.int64)
    # slice start can exceed M - N (dynamic_slice would silently clamp and
    # misalign) — pad the slab so a length-N slice fits at any start ≤ M
    slice_from = jnp.maximum(total - rem_n, 0)

    def rem_slice(row):
        padded = jnp.concatenate(
            [row, jnp.zeros((N,), row.dtype)])
        return jax.lax.dynamic_slice(padded, (slice_from,), (N,))

    keep = jnp.arange(N) < rem_n
    new_state = {**state, "rem_count": rem_n.astype(jnp.int32),
                 "window_drops": state["window_drops"] + dropped,
                 "ts_regressions": state["ts_regressions"] + regressions}
    new_state["tail_fvals"] = jnp.where(
        keep[None, :], jax.vmap(rem_slice)(z_f), 0.0) if len(fagg_idx) \
        else state["tail_fvals"]
    new_state["tail_ivals"] = jnp.where(
        keep[None, :], jax.vmap(rem_slice)(z_i), 0) if len(iagg_idx) \
        else state["tail_ivals"]
    new_state["tail_svals"] = jnp.where(
        keep[None, :], jax.vmap(rem_slice)(z_s), 0.0) if len(sagg_idx) \
        else state["tail_svals"]
    for i in magg_idx:
        ident = _ident(zm[i].dtype, m_ismin[i])
        new_state[f"tail_m{i}"] = jnp.where(keep, rem_slice(zm[i]), ident)
    new_state["tail_ones"] = jnp.where(keep, rem_slice(zo), 0)
    # carry the monotonized time so segmentation stays consistent across
    # steps (emitted rows keep their original timestamps)
    new_state["rem_ts"] = jnp.where(keep, rem_slice(zts_m), 0)
    for i in value_idx:
        z_p = zproj[i]
        new_state[f"rem_proj_{i}"] = jnp.where(
            keep, rem_slice(z_p), jnp.zeros((), z_p.dtype))
    if mode == "timeBatch":
        new_state["batch_base"] = jnp.where(
            total > 0, base, state["batch_base"])

    count = jnp.sum(out_valid.astype(jnp.int32))
    return finish(new_state, sums_f, sums_i, cnts, mins, svars,
                  ovalid=out_valid, ots=zts, proj=zproj, count=count)


def _sort_window(state, skey_c, av_f, av_i, av_s, av_m, magg_idx, m_ismin,
                 k, N, B):
    """Top-N-by-key window (reference ``SortWindowProcessor``): a carried
    sorted buffer of the N best keys with aligned aggregate lanes. Each
    accepted event inserts at its rank (stable: after equal keys, matching
    the host's stable append-then-sort) and the worst slot falls off; its
    running aggregates are the buffer reduction AFTER its insertion.

    A ``lax.scan`` over the batch axis: per-event O(N) shift-insert — the
    per-event sequential dependence (each output sees the buffer as of its
    own arrival) makes this inherently a scan, not a cumsum."""
    idx = jnp.arange(N)

    def insert(row, pos, v):
        shifted = jnp.concatenate([row[:1], row[:-1]])
        return jnp.where(idx < pos, row,
                         jnp.where(idx == pos, v, shifted))

    carry0 = {
        "keys": state["sort_keys"], "n": state["sort_n"],
        "f": state["sort_fvals"], "i": state["sort_ivals"],
        "s": state["sort_svals"],
    }
    for i in magg_idx:
        carry0[f"m{i}"] = state[f"sort_m{i}"]
    m_ident = {i: _ident(state[f"sort_m{i}"].dtype, m_ismin[i])
               for i in magg_idx}

    xs = {
        "accept": jnp.arange(B) < k,
        "key": skey_c,
        "f": av_f.T, "i": av_i.T, "s": av_s.T,
    }
    for i in magg_idx:
        xs[f"m{i}"] = av_m[i]

    def body(carry, x):
        # outputs FIRST, over (carried buffer + the arriving event): the
        # host chunk is [current, expired-evicted] in that order, so the
        # emitted current row still includes the about-to-be-evicted value
        # (the removal only lands on the NEXT row)
        n_old = carry["n"]
        occ = idx < n_old
        sums_f = (jnp.sum(jnp.where(occ[None], carry["f"], 0.0), axis=1)
                  + x["f"]) if carry["f"].shape[0] \
            else jnp.zeros((0,), FACC)
        sums_i = (jnp.sum(jnp.where(occ[None], carry["i"], 0), axis=1)
                  + x["i"]) if carry["i"].shape[0] \
            else jnp.zeros((0,), _IACC)
        cnt = (n_old + 1).astype(jnp.int64)
        mins = {}
        for i in magg_idx:
            lane = jnp.where(occ, carry[f"m{i}"], m_ident[i])
            red = jnp.min if m_ismin[i] else jnp.max
            mins[i] = red(jnp.concatenate([lane, x[f"m{i}"][None]]))
        nf64 = jnp.maximum(cnt, 1).astype(FACC)
        svs = []
        for si in range(carry["s"].shape[0]):
            v = jnp.where(occ, carry["s"][si], 0.0)
            c = (jnp.sum(v) + x["s"][si]) / nf64
            d = jnp.where(occ, v - c, 0.0)
            dx = x["s"][si] - c
            s1 = (jnp.sum(d) + dx) / nf64
            s2 = (jnp.sum(d * d) + dx * dx) / nf64
            svs.append(jnp.sqrt(jnp.maximum(s2 - s1 * s1, 0.0)))
        svar = jnp.stack(svs) if svs else jnp.zeros((0,), FACC)

        # then insert (and implicitly evict slot N-1, the per-order worst).
        # Clamp to the occupied prefix: a key equal to the empty-slot
        # sentinel (+inf / int max) would searchsorted past the fill slots
        # and silently vanish from a non-full buffer; with a FULL buffer
        # pos == N means the new event is the worst and evicts itself —
        # exactly the host's append-sort-pop.
        pos = jnp.minimum(
            jnp.searchsorted(carry["keys"], x["key"], side="right"), n_old)
        ins_lane = lambda row, v: insert(row, pos, v)
        nk = insert(carry["keys"], pos, x["key"])
        nf = jax.vmap(ins_lane)(carry["f"], x["f"]) \
            if carry["f"].shape[0] else carry["f"]
        ni = jax.vmap(ins_lane)(carry["i"], x["i"]) \
            if carry["i"].shape[0] else carry["i"]
        ns = jax.vmap(ins_lane)(carry["s"], x["s"]) \
            if carry["s"].shape[0] else carry["s"]
        nm = {i: insert(carry[f"m{i}"], pos, x[f"m{i}"]) for i in magg_idx}
        nn = jnp.minimum(n_old + 1, N)

        acc = x["accept"]
        sel = lambda new, old: jnp.where(acc, new, old)
        new_carry = {
            "keys": sel(nk, carry["keys"]), "n": sel(nn, carry["n"]),
            "f": sel(nf, carry["f"]), "i": sel(ni, carry["i"]),
            "s": sel(ns, carry["s"]),
        }
        for i in magg_idx:
            new_carry[f"m{i}"] = sel(nm[i], carry[f"m{i}"])
        return new_carry, (sums_f, sums_i, cnt, mins, svar)

    carry, (ys_f, ys_i, ys_c, ys_m, ys_s) = jax.lax.scan(body, carry0, xs)
    new_state = {**state, "sort_keys": carry["keys"], "sort_n": carry["n"],
                 "sort_fvals": carry["f"], "sort_ivals": carry["i"],
                 "sort_svals": carry["s"]}
    for i in magg_idx:
        new_state[f"sort_m{i}"] = carry[f"m{i}"]
    return (new_state, ys_f.T, ys_i.T, ys_c,
            {i: ys_m[i] for i in magg_idx}, ys_s.T)


def _hopping_flushes(state, value_idx, av_f, av_i, av_s, av_m, magg_idx,
                     m_ismin, ones_c, proj_c, wts, k, N, B, D, H, finish,
                     clock):
    """hopping(duration D, hop H) — overlapping tumbling buckets (reference
    ``HopingWindowProcessor``): every H ms emit ONE aggregated row over the
    events of the last D ms (strictly before the boundary; an arrival AT the
    boundary flushes first, then joins the buffer — host processes the
    boundary before appending). Flushes are event-driven like the device
    timeBatch kernel; boundaries with no live events emit nothing, exactly
    like the host's RESET-only flush.

    Kernel: time-sorted concat [tail(N) + batch(B)] lanes; the f-th flush
    boundary reads its bucket (t_f - D, t_f) as cumsum/sparse-table range
    reductions — all flushes in the batch resolve in parallel."""
    wts_s, _mono, regressed = _monotone_ts(wts, k, B, state["last_ts"])
    zts = jnp.concatenate([state["tail_ts"], wts_s])                # [N+B]
    zo = jnp.concatenate([state["tail_ones"], ones_c])
    z_f = jnp.concatenate([state["tail_fvals"], av_f], axis=1)
    z_i = jnp.concatenate([state["tail_ivals"], av_i], axis=1)
    z_s = jnp.concatenate([state["tail_svals"], av_s], axis=1)
    zm = {i: jnp.concatenate([state[f"tail_m{i}"], av_m[i]])
          for i in magg_idx}
    zproj = {i: jnp.concatenate([state[f"tail_proj_{i}"], proj_c[i]])
             for i in value_idx}

    # a boundary fires on the stream's clock (``clock``: filtered events
    # advance it too), whatever the window was handed
    newest = jnp.maximum(jnp.where(k > 0, zts[jnp.maximum(N + k - 1, N)],
                                   state["last_ts"]), clock)
    armed = state["hop_next"] > _TS_NEG
    # unarmed ⇒ empty tail ⇒ the first real event sits at slot N
    b0 = jnp.where(armed, state["hop_next"], zts[N] + H)
    has_any = armed | (k > 0)
    n_flush_raw = jnp.where(has_any & (newest >= b0),
                            (newest - b0) // jnp.int64(H) + 1, 0)
    F = B                         # flush capacity per step; overflow is loud
    n_flush = jnp.minimum(n_flush_raw, F).astype(jnp.int32)
    f = jnp.arange(F)
    t_f = b0 + f.astype(jnp.int64) * jnp.int64(H)
    lo_f = jnp.searchsorted(zts, t_f - jnp.int64(D), side="right")
    hi_f = jnp.searchsorted(zts, t_f, side="left") - 1
    hi_c = jnp.maximum(hi_f, lo_f - 1)            # empty bucket → zero range
    sums_f = _range_sums(z_f, lo_f, hi_c)
    sums_i = _range_sums(z_i, lo_f, hi_c)
    cso = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(zo)])
    cnts = (cso[hi_c + 1] - cso[lo_f]).astype(jnp.int64)
    mins = {i: _range_reduce(zm[i], lo_f, hi_c, m_ismin[i])
            for i in magg_idx}
    svars = _window_svars(z_s, zo, lo_f, hi_c, cnts, k, N, B)
    # non-aggregate columns of a collapsed row read the bucket's last event
    proj_fl = {i: zproj[i][jnp.clip(hi_c, 0, N + B - 1)] for i in value_idx}
    ovalid = (f < n_flush) & (cnts > 0)

    # boundaries past the flush capacity are NOT dropped: hop_next advances
    # only by the processed count, so they fire on the next step (the
    # runtime's flush() drains trailing ones with empty steps)
    b_last = b0 + (n_flush.astype(jnp.int64) - 1) * jnp.int64(H)
    live_cut = jnp.where(n_flush > 0, b_last - jnp.int64(D),
                         jnp.int64(_TS_NEG))
    sliced = jnp.arange(N + B) < k        # slots pushed out by the slide
    drops = jnp.sum(jnp.where(sliced & (zts > live_cut), zo, 0)) \
        .astype(jnp.int64)

    take = lambda row: jax.lax.dynamic_slice(row, (k,), (N,))
    new_state = {
        **state,
        "tail_fvals": jax.vmap(take)(z_f) if z_f.shape[0]
        else state["tail_fvals"],
        "tail_ivals": jax.vmap(take)(z_i) if z_i.shape[0]
        else state["tail_ivals"],
        "tail_svals": jax.vmap(take)(z_s) if z_s.shape[0]
        else state["tail_svals"],
        "tail_ones": take(zo),
        "tail_ts": take(zts),
        "hop_next": jnp.where(n_flush > 0,
                              b0 + n_flush.astype(jnp.int64) * jnp.int64(H),
                              jnp.where(has_any, b0,
                                        jnp.int64(_TS_NEG))),
        "window_drops": state["window_drops"] + drops,
        "last_ts": jnp.maximum(state["last_ts"], newest),
        "ts_regressions": state["ts_regressions"] + regressed,
    }
    for i in magg_idx:
        new_state[f"tail_m{i}"] = take(zm[i])
    for i in value_idx:
        new_state[f"tail_proj_{i}"] = take(zproj[i])

    return finish(new_state, sums_f, sums_i, cnts, mins, svars,
                  ovalid=ovalid, ots=t_f, proj=proj_fl,
                  count=jnp.sum(ovalid.astype(jnp.int32)))


def _seg_suffix(end, vals, ops):
    """Per-segment suffix reductions of several lanes in one pass: position
    ``i`` reads ``op`` over its lane from ``i`` to the end of its segment
    (``end`` flags a segment's last position). A reverse segmented
    ``associative_scan``: no prefix-sum differences, so float sums do not
    cancel, and min / max / "the later one" ride the same pass."""
    def comb(later, here):
        f_l, v_l = later
        f_h, v_h = here
        return f_l | f_h, tuple(jnp.where(f_h, h, op(l, h))
                                for l, h, op in zip(v_l, v_h, ops))
    return jax.lax.associative_scan(comb, (end, tuple(vals)),
                                    reverse=True)[1]


def _hopping_grouped(plan, state, gk_c, av_f, av_i, av_m, m_ismin, m_ident,
                     proj_c, wts, k, clock):
    """hopping(D, H) with ``group by``: at a boundary ``t`` one row per key
    live in (t - D, t), in the order of each key's first event there, a
    non-aggregate column reading the key's last event; then the selector's
    tail (``order by`` / ``offset`` / ``limit``) on that chunk, as the
    interpreter's selector applies it to a flush chunk.

    The window is kept as events (the newest N: timestamp, keys, aggregate
    arguments). A boundary sorts the time-ordered concat [tail(N) +
    batch(B)] by (keys, lane): a key's events become one segment, its live
    ones a contiguous run inside it because lanes ascend with time; suffix
    reductions over the segments leave every key's totals at its first live
    lane, which is the row. Exact for every key (full-width compares, no
    bucket); a window of N events holds at most N keys, the only bound.
    Boundaries resolve one after another in a loop that runs as often as
    boundaries fired (a batch without one pays nothing), at most
    ``flush_cap`` a step; windows known to be empty are skipped by
    arithmetic, so a gap of many hops costs no step."""
    B, N = plan.B, max(plan.window_n, 1)
    M = N + B
    D, H = jnp.int64(plan.window_ms), jnp.int64(plan.hop_ms)
    F, R = plan.flush_cap, plan.flush_rows
    specs, value_idx = plan.specs, plan.value_idx
    fagg_idx, iagg_idx, magg_idx = plan.fagg_idx, plan.iagg_idx, plan.magg_idx
    key_of_value = plan.key_of_value
    carried = [i for i in value_idx if i not in key_of_value]
    nk = len(gk_c)

    wts_s, _mono, regressed = _monotone_ts(wts, k, B, state["last_ts"])
    zts = jnp.concatenate([state["tail_ts"], wts_s])                # [M]
    zk = [jnp.concatenate([state[f"tail_gk{n}"], gk_c[n]])
          for n in range(nk)]
    z_f = jnp.concatenate([state["tail_fvals"], av_f], axis=1)
    z_i = jnp.concatenate([state["tail_ivals"], av_i], axis=1)
    zm = {i: jnp.concatenate([state[f"tail_m{i}"], av_m[i]])
          for i in magg_idx}
    zproj = {i: jnp.concatenate([state[f"tail_proj_{i}"], proj_c[i]])
             for i in carried}

    # a boundary fires on the stream's clock (``clock``: filtered events
    # advance it too), whatever the window was handed
    newest = jnp.maximum(jnp.where(k > 0, zts[jnp.maximum(N + k - 1, N)],
                                   state["last_ts"]), clock)
    armed = state["hop_next"] > _TS_NEG
    # unarmed ⇒ empty tail ⇒ the first real event sits at slot N
    b0 = jnp.where(armed, state["hop_next"], zts[N] + H)
    has_any = armed | (k > 0)
    lane = jnp.arange(M, dtype=jnp.int32)
    big = jnp.int32(2 ** 31 - 1)

    def next_live(t):
        """``t``, or where its window is empty the first boundary that may
        hold something: past the next event, or past ``newest`` where no
        event is left. The boundaries between have fired and hold nothing
        (every event up to ``newest`` is in the concat)."""
        nxt = jnp.searchsorted(zts, t - D, side="right")
        e = jnp.where(nxt < M, zts[jnp.minimum(nxt, M - 1)], _TS_POS)
        e = jnp.where(e < _TS_POS, e, jnp.maximum(newest, t - 1))
        return jnp.where(e >= t, t + ((e - t) // H + 1) * H, t)

    def flush(t):
        """(output columns [R], rows, distinct keys) of boundary ``t``."""
        lo = jnp.searchsorted(zts, t - D, side="right").astype(jnp.int32)
        hi = jnp.searchsorted(zts, t, side="left").astype(jnp.int32) - 1
        with jax.named_scope("groupby.sort"):
            payload = [z_f[a] for a in range(len(fagg_idx))] \
                + [z_i[a] for a in range(len(iagg_idx))] \
                + [zm[i] for i in magg_idx]
            srt = jax.lax.sort((*zk, lane, *payload), num_keys=nk + 1)
            sk, sidx, rest = srt[:nk], srt[nk], list(srt[nk + 1:])
        with jax.named_scope("groupby.reduce"):
            live = (sidx >= lo) & (sidx <= hi)
            differs = sk[0][1:] != sk[0][:-1]
            for n in range(1, nk):
                differs = differs | (sk[n][1:] != sk[n][:-1])
            one = jnp.ones((1,), jnp.bool_)
            first = jnp.concatenate([one, differs])     # of its key
            last = jnp.concatenate([differs, one])
            # lanes ascend inside a key, so its live events are one run:
            # the row sits at the run's first position
            row = live & (first | ~jnp.concatenate([~one, live[:-1]]))
            vals = [live.astype(jnp.int32)]
            ops = [jnp.add]
            n_sums = len(fagg_idx) + len(iagg_idx)
            for v in rest[:n_sums]:
                vals.append(jnp.where(live, v, jnp.zeros((), v.dtype)))
                ops.append(jnp.add)
            for i, v in zip(magg_idx, rest[n_sums:]):
                vals.append(jnp.where(live, v, m_ident[i]))
                ops.append(jnp.minimum if m_ismin[i] else jnp.maximum)
            if carried:
                # a carried column reads the key's LAST live event: its
                # lane, then the column itself in time order
                vals.append(jnp.where(live, sidx, -1))
                ops.append(jnp.maximum)
            red = iter(_seg_suffix(last, vals, ops))
            cnts = next(red).astype(jnp.int64)
            sums_f = [next(red) for _ in fagg_idx]
            sums_i = [next(red) for _ in iagg_idx]
            mins = {i: next(red) for i in magg_idx}
            proj_last = {}
            if carried:
                last_lane = jnp.maximum(next(red), 0)
                proj_last = {i: zproj[i][last_lane] for i in carried}
            proj = {i: sk[key_of_value[i]] if i in key_of_value
                    else proj_last[i] for i in value_idx}
            cols_m = _materialize(specs, value_idx, fagg_idx, iagg_idx,
                                  magg_idx, [], proj, sums_f, sums_i, cnts,
                                  mins, [])
            n_keys = jnp.sum(row, dtype=jnp.int32)
        with jax.named_scope("select.order"):
            if plan.order_by and plan.limit == 1 and not plan.offset:
                # `limit 1` is the argmax it is: the best of each order key
                # in turn among those still tied, then the first seen
                cand = row
                for i, desc in plan.order_by:
                    v = cols_m[specs[i].name]
                    worst = _ident(v.dtype, not desc)
                    pick = jnp.max if desc else jnp.min
                    cand = cand & (v == pick(jnp.where(cand, v, worst)))
                take = jnp.argmin(jnp.where(cand, sidx, big))[None]
            else:
                # a stable order: dead lanes last, the order keys, then the
                # first-seen lane (unique among rows)
                okeys = []
                for i, desc in plan.order_by:
                    v = cols_m[specs[i].name]
                    if desc:
                        v = -v if jnp.issubdtype(v.dtype, jnp.floating) \
                            else ~v
                    okeys.append(v)
                order = jax.lax.sort(
                    ((~row).astype(jnp.int32), *okeys, sidx, lane),
                    num_keys=2 + len(okeys))[-1]
                take = jnp.concatenate(
                    [order, jnp.zeros((R,), jnp.int32)]
                )[plan.offset:plan.offset + R]
        with jax.named_scope("select.limit"):
            n_rows = jnp.maximum(n_keys - jnp.int32(plan.offset), 0)
            if plan.limit is not None:
                n_rows = jnp.minimum(n_rows, jnp.int32(plan.limit))
            return ({name: c[take] for name, c in cols_m.items()},
                    n_rows, n_keys)

    zero_cols = {s.name: jnp.zeros((F, R), _JNP_DTYPES[s.dtype])
                 for s in specs}

    def fire(c):
        t, f = c["t"], c["f"]
        rows, n_rows, n_keys = flush(t)
        out = {name: jax.lax.dynamic_update_slice(
            c["out"][name], rows[name].astype(c["out"][name].dtype)[None],
            (f, jnp.int32(0))) for name in c["out"]}
        return {"t": next_live(t + H), "f": f + 1, "out": out,
                "nrows": c["nrows"].at[f].set(n_rows), "keys": n_keys}

    done = jax.lax.while_loop(
        lambda c: (c["f"] < F) & has_any & (c["t"] <= newest), fire,
        {"t": next_live(b0), "f": jnp.int32(0), "out": zero_cols,
         "nrows": jnp.zeros((F,), jnp.int32),
         "keys": state["window_live_keys"]})

    # an event pushed out by the slide is lost only if a boundary still to
    # fire would have read it
    hop_next = jnp.where(has_any, done["t"], jnp.int64(_TS_NEG))
    sliced = (lane < k) & (zts > _TS_NEG)
    drops = jnp.sum((sliced & (zts > hop_next - D)).astype(jnp.int64))
    take_n = lambda z: jax.lax.dynamic_slice(z, (k,), (N,))
    new_state = {
        **state,
        "tail_ts": take_n(zts),
        "tail_fvals": jax.vmap(take_n)(z_f) if z_f.shape[0]
        else state["tail_fvals"],
        "tail_ivals": jax.vmap(take_n)(z_i) if z_i.shape[0]
        else state["tail_ivals"],
        "hop_next": hop_next,
        "last_ts": jnp.maximum(state["last_ts"], newest),
        "window_drops": state["window_drops"] + drops,
        "ts_regressions": state["ts_regressions"] + regressed,
        "window_held": jnp.minimum(state["window_held"] + k, N)
        .astype(jnp.int32),
        "window_live_keys": done["keys"],
    }
    for n in range(nk):
        new_state[f"tail_gk{n}"] = take_n(zk[n])
    for i in magg_idx:
        new_state[f"tail_m{i}"] = take_n(zm[i])
    for i in carried:
        new_state[f"tail_proj_{i}"] = take_n(zproj[i])
    # rows sit at the front of each boundary's slot, `nrows` of them
    return new_state, {"out": done["out"], "nrows": done["nrows"]}


def _heavy_hitters(state, kcode, av_f, av_i, k, C, B, lossy, support, error):
    """frequent / lossyFrequent device kernels (reference
    ``FrequentWindowProcessor`` — classic Misra-Gries — and
    ``LossyFrequentWindowProcessor``): a carried [C]-slot key/counter table
    walked by a ``lax.scan`` over the batch.

    Aggregation semantics match the host exactly: every EMITTED current
    event adds to the running aggregates; an eviction/prune retracts the
    evicted key's LAST event values (the host expires that StreamEvent).
    The emitted row shows the aggregates after its own add, before any
    same-event evictions land — the selector builds the current row before
    processing the expired chunk."""
    carry0 = {
        "keys": state["hh_keys"], "counts": state["hh_counts"],
        "f": state["hh_fvals"], "i": state["hh_ivals"],
        "run_f": state["hh_run_f"], "run_i": state["hh_run_i"],
        "run_cnt": state["hh_run_cnt"],
    }
    if lossy:
        carry0["delta"] = state["hh_delta"]
        carry0["total"] = state["hh_total"]
        carry0["drops"] = state["window_drops"]

    slots = jnp.arange(C)

    def set_slot(table, idx, v):
        return jnp.where(slots == idx, v, table)

    def set_lane(table, idx, vals):            # [A, C] ← [A]
        if not table.shape[0]:
            return table
        return jnp.where(slots[None, :] == idx, vals[:, None], table)

    def body(carry, x):
        accept, key, vf, vi = x["accept"], x["key"], x["f"], x["i"]
        occ = carry["counts"] > 0
        hit = occ & (carry["keys"] == key)
        has = jnp.any(hit)
        has_space = jnp.any(~occ)

        # shared hit/insert bookkeeping (the branches differ only in the
        # full-table miss handling: decrement-all vs drop)
        insert = (~has) & has_space
        idx = jnp.where(has, jnp.argmax(hit), jnp.argmax(~occ))
        upd = accept & (has | insert)
        counts = carry["counts"]
        counts = jnp.where(accept & has & hit, counts + 1, counts)
        counts = jnp.where((accept & insert) & (slots == idx), 1, counts)

        if not lossy:
            # Misra-Gries decrement-all; slots reaching zero evict and
            # retract their last event from the running aggregates. If the
            # pass freed a slot, the NEW key takes the first evicted one
            # and emits (reference FrequentWindowProcessor tentatively
            # inserts and only drops the arrival when nothing evicted)
            dec = accept & (~has) & (~has_space)
            dec_counts = jnp.maximum(counts - 1, 0)
            evicted = dec & occ & (dec_counts == 0)
            dec_ins = dec & jnp.any(evicted)
            idx = jnp.where(dec_ins, jnp.argmax(evicted), idx)
            upd = upd | dec_ins
            emit = accept & (has | insert) | dec_ins
            counts = jnp.where(dec, jnp.where(occ, dec_counts, counts),
                               counts)
            counts = jnp.where(dec_ins & (slots == idx), 1, counts)
            new_total = carry.get("total")
            new_delta = carry.get("delta")
            new_drops = carry.get("drops")
        else:
            total = carry["total"] + jnp.where(accept, 1, 0)
            bucket = (total.astype(jnp.float64) * error).astype(jnp.int64) + 1
            dropped = accept & (~has) & (~has_space)
            delta = jnp.where((accept & insert) & (slots == idx),
                              bucket - 1, carry["delta"])
            entry_f = counts[idx]
            entry_d = delta[idx]
            emit = accept & (has | insert) & (
                (entry_f + entry_d).astype(jnp.float64)
                >= total.astype(jnp.float64) * support)
            # prune pass (host prunes after the emission decision): every
            # entry with f + delta <= bucket-1 expires and retracts
            evicted = occ & accept & ((counts + delta) <= bucket - 1)
            # the slot being updated this event is occupied NOW even if it
            # was free before — include it in the occupancy for pruning
            evicted = evicted | (upd & (slots == idx)
                                 & ((counts + delta) <= bucket - 1))
            counts = jnp.where(evicted, 0, counts)
            new_total = total
            new_delta = delta
            new_drops = carry["drops"] + jnp.where(dropped, 1, 0)

        # last-event value lanes for the touched slot
        nf = jnp.where(upd, set_lane(carry["f"], idx, vf), carry["f"]) \
            if carry["f"].shape[0] else carry["f"]
        ni = jnp.where(upd, set_lane(carry["i"], idx, vi), carry["i"]) \
            if carry["i"].shape[0] else carry["i"]

        # running aggregates. Chunk order differs per window: the frequent
        # host appends evictions BEFORE the dec-inserted current (retract
        # the evicted keys' OLD last values, then add — the emitted row
        # sees the post-retraction state), while the lossy host emits the
        # current FIRST and prunes after (the row sees pre-prune state, and
        # a prune can expire the just-updated entry, so it retracts the
        # post-update lanes).
        n_evicted = jnp.sum(evicted.astype(jnp.int64))
        if not lossy:
            run_f, run_i = carry["run_f"], carry["run_i"]
            if carry["f"].shape[0]:
                run_f = run_f - jnp.sum(
                    jnp.where(evicted[None, :], carry["f"], 0.0), axis=1)
            if carry["i"].shape[0]:
                run_i = run_i - jnp.sum(
                    jnp.where(evicted[None, :], carry["i"], 0), axis=1)
            run_cnt = carry["run_cnt"] - n_evicted
            run_f = run_f + jnp.where(emit, vf, 0.0)
            run_i = run_i + jnp.where(emit, vi, 0)
            run_cnt = run_cnt + jnp.where(emit, 1, 0)
            out_f, out_i, out_cnt = run_f, run_i, run_cnt
        else:
            run_f = carry["run_f"] + jnp.where(emit, vf, 0.0)
            run_i = carry["run_i"] + jnp.where(emit, vi, 0)
            run_cnt = carry["run_cnt"] + jnp.where(emit, 1, 0)
            out_f, out_i, out_cnt = run_f, run_i, run_cnt
            if carry["f"].shape[0]:
                run_f = run_f - jnp.sum(
                    jnp.where(evicted[None, :], nf, 0.0), axis=1)
            if carry["i"].shape[0]:
                run_i = run_i - jnp.sum(
                    jnp.where(evicted[None, :], ni, 0), axis=1)
            run_cnt = run_cnt - n_evicted

        new_carry = {"keys": set_slot(carry["keys"], idx,
                                      jnp.where(upd, key,
                                                carry["keys"][idx])),
                     "counts": counts, "f": nf, "i": ni,
                     "run_f": run_f, "run_i": run_i, "run_cnt": run_cnt}
        if lossy:
            new_carry["delta"] = new_delta
            new_carry["total"] = new_total
            new_carry["drops"] = new_drops
        return new_carry, (emit, out_f, out_i, out_cnt)

    xs = {"accept": jnp.arange(B) < k, "key": kcode,
          "f": av_f.T, "i": av_i.T}
    carry, (emit, ys_f, ys_i, ys_c) = jax.lax.scan(body, carry0, xs)
    new_state = {**state, "hh_keys": carry["keys"],
                 "hh_counts": carry["counts"], "hh_fvals": carry["f"],
                 "hh_ivals": carry["i"], "hh_run_f": carry["run_f"],
                 "hh_run_i": carry["run_i"], "hh_run_cnt": carry["run_cnt"]}
    if lossy:
        new_state["hh_delta"] = carry["delta"]
        new_state["hh_total"] = carry["total"]
        new_state["window_drops"] = carry["drops"]
    return new_state, emit, ys_f.T, ys_i.T, ys_c


def _materialize(specs, value_idx, fagg_idx, iagg_idx, magg_idx, sagg_idx,
                 proj, sums_f, sums_i, cnts, mins, svars):
    outputs = {}
    for i in value_idx:
        outputs[specs[i].name] = proj[i]
    fpos = {i: p for p, i in enumerate(fagg_idx)}
    ipos = {i: p for p, i in enumerate(iagg_idx)}
    spos = {i: p for p, i in enumerate(sagg_idx)}
    for i, s in enumerate(specs):
        if s.kind == "value":
            continue
        if s.kind == "count":
            outputs[s.name] = cnts
        elif s.kind == "sum":
            outputs[s.name] = sums_i[ipos[i]] if s.acc_int else sums_f[fpos[i]]
        elif s.kind in ("min", "max"):
            outputs[s.name] = mins[i]
        elif s.kind == "stdDev":
            outputs[s.name] = svars[spos[i]]
        else:  # avg (always emitted as double → policy float)
            num = sums_i[ipos[i]].astype(FACC) if s.acc_int \
                else sums_f[fpos[i]]
            outputs[s.name] = num / jnp.maximum(cnts, 1).astype(FACC)
    return outputs
