"""Compiled NFA: vectorized pattern/sequence matching on device.

The north-star kernel (SURVEY §7 phase 3). The reference's per-event,
per-partial-match interpretation (``StreamPreStateProcessor.processAndReturn``,
unbounded cloned ``StateEvent`` lists) becomes:

- the state-element tree compiles (reusing the host ``PatternCompiler``) to a
  *linear chain* of stream/count states with per-state predicate programs;
- partial matches live in **fixed-capacity match tables** — one slot table per
  state, holding the bound attribute values the downstream predicates/output
  actually reference, plus first-bind timestamps and (for ``<m:n>``) counters;
- one jitted ``lax.scan`` walks the micro-batch; each step updates every state's
  table with vectorized slot math (predicates evaluate over all C slots at
  once), states processed in reverse order so one event can't advance a partial
  twice;
- ``every`` is a carried seed counter (replenished when its scope completes),
  ``within`` is a timestamp mask that also reclaims expired slots, slot
  exhaustion is an explicit drop-newest policy with an overflow counter;
- the rows a batch emits leave the step as their count ``n`` and ONE table
  of ``M`` = the batch's event capacity rows (``mask``, ``j`` = the closing
  event's index, a column per output and per null mask), packed on the
  device after the scan (``pack_rows``): the per-event ``[2, C]`` emit grids
  never leave it. A batch in which a lane emitted more is packed at the
  size of the plan's bound, into ``full`` beside it (``_row_capacity``);
  which of the two packs runs is one branch on the device, for all lanes. The
  blocked kernel (``nfa_block.py``) hands out the same three, so one decode
  (``decode_rows``, one ``device_get`` a batch) serves both.

Scope — 104/104 of the untimed reference pattern corpus compiles and
matches the host oracle (pinned by ``tests/test_pattern_corpus.py::
test_device_corpus_coverage``): linear chains of stream/count/logical/
absent states, patterns and sequences, ``every`` scopes starting at any
stream state (incl. mid-pattern and group scopes), ``within``, ``e[k]``
occurrence indexing up to ``_MAX_OCC_INDEX``, zero-min and final count
states, absent-start patterns, and ``not X for t`` (per-slot arrival
clocks; expiry evaluates in a pre-pass on the next arriving event — host
timers fire before event delivery, so observable timing matches under the
event-driven clock). Logical ``and``/``or`` (incl. ``X and not Y`` without
``for``) use per-slot done flags + masked side binds.
Still host-only (each raises ``DeviceCompileError`` and the bridge falls
back): timer-driven emission after the stream ends (``for t`` expiring with
no later arrival), absent without ``for``, absent/logical-for states inside
sequences, mid-pattern ``every`` in sequences or ending at a non-stream
state, count-after-count chains, non-immediate logical/absent directly
after a count state, logical/absent into a zero-min final count,
``select *`` over pattern outputs, and ``e[k]`` beyond ``_MAX_OCC_INDEX``.
Outputs referencing an OR state's unmatched side, an absent branch, or a
zero-occurrence count emit NULL via carried validity flags (host parity).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.pattern import CompiledPattern, PatternCompiler
from ..query_api import (
    Query,
    StateInputStream,
    Variable,
)
from ..query_api.definition import DataType, StreamDefinition
from .batch import StringDictionary
from .dtypes import JNP as _JNP, NP as _NP
from .expr_compile import DeviceCompileError, compile_expression
from .step_runtime import StepRuntime

# Highest statically-referenced occurrence index `e[k]` a count state carries
# on device. Each referenced k costs one bound column + set flag per slot; the
# reference keeps the whole occurrence list per partial
# (StreamPreStateProcessor pending StateEvents), so any k is legal there —
# larger indexes fall back to the host path.
_MAX_OCC_INDEX = 15


def _occ_flag(q: int, k: int) -> str:
    """Bound flag for occurrence k of count state q ("flag" appended to the
    digits with no '#', so it can't collide with a value key's '#attr')."""
    return f"b{q}#occ{k}flag"


def _has_flag(q: int) -> str:
    """"at least one occurrence" flag for a zero-min count state."""
    return f"b{q}#has"


# ---------------------------------------------------------------------------
# merged multi-stream batches
# ---------------------------------------------------------------------------

class MergedBatchSchema:
    """Union columns over the pattern's streams + a stream tag per event."""

    def __init__(self, stream_defs: dict[str, StreamDefinition], stream_ids: list[str]):
        self.stream_ids = stream_ids
        self.stream_index = {sid: i for i, sid in enumerate(stream_ids)}
        self.columns: dict[str, DataType] = {}       # "s{i}_{attr}" -> dtype
        # ONE dictionary shared by every string column: cross-column equality
        # (`e2.sym == e1.sym` across streams) must compare comparable codes
        shared = StringDictionary()
        self.dictionaries: dict[str, StringDictionary] = {}
        for i, sid in enumerate(stream_ids):
            d = stream_defs[sid]
            for a in d.attributes:
                key = f"s{i}_{a.name}"
                self.columns[key] = a.type
                if a.type == DataType.STRING:
                    self.dictionaries[key] = shared

    def col_key(self, stream_id: str, attr: str) -> str:
        return f"s{self.stream_index[stream_id]}_{attr}"

    def snapshot_dictionaries(self) -> dict:
        from .batch import snapshot_dictionaries
        return snapshot_dictionaries(self.dictionaries)

    def restore_dictionaries(self, snap: dict) -> None:
        from .batch import restore_dictionaries
        restore_dictionaries(self.dictionaries, snap)


class MergedBatchBuilder:
    """Stages events and emits device micro-batches in the WIRE format:

    ``{"cols": {key: [B]}, "tag": int8 [B], "ts": int32 [B] (deltas),
    "ts_base": int64 scalar, "count": int}``

    Only columns in ``used_cols`` (those the compiled program reads) are
    staged/transferred; timestamps travel as int32 deltas against the batch
    minimum; validity is the prefix ``[0, count)`` — the wire carries
    ~10B/event instead of ~21B of host-to-device transfer."""

    def __init__(self, schema: MergedBatchSchema, capacity: int,
                 stream_defs: dict[str, StreamDefinition],
                 used_cols: Optional[set] = None):
        self.schema = schema
        self.capacity = capacity
        self.stream_defs = stream_defs
        keys = schema.columns.keys() if used_cols is None \
            else [k for k in schema.columns if k in used_cols]
        self._cols = {
            key: np.zeros(capacity, dtype=_NP[schema.columns[key]])
            for key in keys
        }
        self._tag = np.zeros(capacity, dtype=np.int8)
        self._ts = np.zeros(capacity, dtype=np.int64)
        self._n = 0
        self.ts_clamped = 0        # events whose in-batch ts delta overflowed
        # wall-clock of the first append since the last emit (pack-phase
        # span for the async driver's overlap accounting + flush deadline)
        self._pack_t0 = None

    def __len__(self):
        return self._n

    @property
    def full(self) -> bool:
        return self._n >= self.capacity

    def append(self, stream_id: str, row: list, ts: int) -> None:
        i = self._n
        if self._pack_t0 is None:
            self._pack_t0 = time.perf_counter()
        si = self.schema.stream_index[stream_id]
        d = self.stream_defs[stream_id]
        for a, v in zip(d.attributes, row):
            key = f"s{si}_{a.name}"
            col = self._cols.get(key)
            if col is None:
                continue               # column unused by the compiled program
            if a.type == DataType.STRING:
                v = self.schema.dictionaries[key].encode(v)
            col[i] = 0 if v is None else v
        self._tag[i] = si
        self._ts[i] = ts
        self._n += 1

    def append_many(self, stream_id: str, attr_cols: dict, ts,
                    start: int = 0) -> int:
        """Bulk-append pre-encoded column arrays (string columns already
        dictionary codes — see ``StringDictionary.encode_array``). Copies
        rows ``[start, start+take)`` where ``take`` fits the remaining
        capacity; returns ``take`` (caller emits/flushes and resumes). This
        replaces the per-event ``append`` loop on the hot ingest path
        (reference analog: ``StreamJunction.java:279-316`` — the Disruptor
        existed to make ingest cheap)."""
        import numpy as _np
        n_rows = len(ts) - start
        take = min(n_rows, self.capacity - self._n)
        if take <= 0:
            return 0
        if self._pack_t0 is None:
            self._pack_t0 = time.perf_counter()
        i = self._n
        si = self.schema.stream_index[stream_id]
        d = self.stream_defs[stream_id]
        for a in d.attributes:
            key = f"s{si}_{a.name}"
            col = self._cols.get(key)
            src = attr_cols.get(a.name)
            if col is None or src is None:
                continue
            col[i:i + take] = src[start:start + take]
        self._tag[i:i + take] = si
        self._ts[i:i + take] = _np.asarray(ts)[start:start + take]
        self._n += take
        return take

    def emit(self) -> dict:
        t_emit0 = time.perf_counter()
        n = self._n
        base = int(self._ts[:n].min()) if n else 0
        deltas = self._ts - base
        deltas[n:] = 0
        if n and deltas[:n].max() > 2**31 - 1:
            # an in-batch event-time span over ~24.8 days: clamp + count
            # (callers should flush long-idle builders before this occurs)
            self.ts_clamped += int(np.sum(deltas[:n] > 2**31 - 1))
            log = __import__("logging").getLogger("siddhi_tpu.device")
            log.warning("batch ts span exceeds int32 ms; %d clamped",
                        self.ts_clamped)
            np.clip(deltas, 0, 2**31 - 1, out=deltas)
        out = {
            "cols": {k: v.copy() for k, v in self._cols.items()},
            "tag": self._tag.copy(),
            "ts": deltas.astype(np.int32),
            "ts_base": np.int64(base),
            "count": n,
            "last_ts": int(self._ts[n - 1]) if n else 0,
            "pack_s": (t_emit0 - self._pack_t0
                       if self._pack_t0 is not None else 0.0),
        }
        # X-Ray waterfall stamps (see BatchBuilder.emit)
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
            "tag": self._tag[:n].copy(),
            "ts": self._ts[:n].copy(),
            "n": n,
        }

    def restore(self, snap: dict) -> None:
        n = snap["n"]
        self._n = n
        for k, v in snap["cols"].items():
            self._cols[k][:n] = v
        self._tag[:n] = snap["tag"]
        self._ts[:n] = snap["ts"]
        if n:                   # restored rows re-arm the flush deadline
            self._pack_t0 = time.perf_counter()


# ---------------------------------------------------------------------------
# the scan kernel's rows, packed on the device
# ---------------------------------------------------------------------------

_GROUP = 128        # cells of one emit-grid group: a vector register's lanes
_TILE = 8           # lanes of one tile of a scanned grid: its sublanes


def pack_rows(mask, cols: dict):
    """The rows a scanned batch emitted, out of its emit grids and into a
    table a lane: ``mask`` ``[B, P, R, C]`` (event, lane, source,
    candidate: the order the scanned, lane-mapped body leaves them in; R =
    the emit sources the plan uses, 1 or 2) marks them, ``cols`` holds a
    ``[B, P, R, C]`` grid per output column and null mask. Returns ``(n,
    table)``: ``n`` ``[P]`` the rows each lane emitted, known from the
    mask's counts before any row is located, and ``table(n_rows)`` ->
    ``{"mask", "j", <col>..: [P, n_rows]}``, where a lane's row r is its
    (r+1)-th marked cell in row-major order (match event, then source, then
    candidate: the order a boolean index over the lane's grids walks) and
    ``j`` its event. Cells past ``n_rows`` are in no row: ``n`` says how
    many.

    Every level of ``table`` costs by ``n_rows``, not by the rows there
    are, so the caller sizes it by ``n`` (``_make_step``: the batch's event
    capacity where no lane emitted more, else the plan's bound). Three
    levels, each a compare-and-count against running totals, because one
    ``searchsorted`` over the ``B x RC`` cells would compare every row with
    every cell (and a two-level one would hold ``n_rows x RC`` running
    counts a lane: gigabytes at 256 lanes): a row's event from the events'
    running row counts ``[n_rows, B]``; its group of ``_GROUP`` cells (of
    one source) from the event's running group counts ``[n_rows, R x G]``;
    its cell from the group's own cells ``[n_rows, _GROUP]``. The second
    and third fetch one short row per table row, and so does every column:
    the cell's group of values, of which a select-and-sum keeps the cell's.
    On a v5e a gathered row of 128 f32 costs 17 ns where a single element
    out of the flat grid costs 23 (PERF.md section 6, PR 33). Nothing is
    scattered.

    Nothing passes over a whole grid but the mask's count, either: the
    groups are fetched from where the scan left them. The scanned,
    lane-mapped body writes a grid event by event, and a v5e keeps each
    event's ``[P, C]`` slab of a source in tiles of ``_TILE`` lanes by
    ``_GROUP`` candidates, a lane's group one contiguous row of a tile. So
    ``groups`` numbers the rows in that order (event, source, tile of
    lanes, group, lane in the tile), which the compiler takes for the same
    bytes, where the order (event, lane, group) cost a copy of every grid
    (16.8 ms of PR 33's step). A lane count that ``_TILE`` does not divide
    (one lane) is numbered plainly; the rows are the same rows either way.
    """
    B, P, R, C = mask.shape
    G = -(-C // _GROUP)             # groups of one source's cells
    T = _TILE if P % _TILE == 0 else 1
    pad = ((0, 0), (0, 0), (0, 0), (0, G * _GROUP - C))

    def groups(grid):
        """A ``[B, P, R, C]`` grid as rows of ``_GROUP`` cells: group ``g``
        of source ``r`` of event ``j`` in lane ``p`` is row ``((((j * R + r)
        * P/T + p // T) * G + g) * T + p % T``."""
        return jnp.pad(grid, pad).reshape(
            B, P // T, T, R, G, _GROUP).transpose(0, 3, 1, 4, 2, 5).reshape(
                -1, _GROUP)

    def locate(rank, totals, size):
        """Where the (rank+1)-th row falls among ``size`` running totals
        ``[P, n_rows | 1, size]`` (as many lie at or below rank as precede
        it), and its rank inside."""
        before = totals <= rank[..., None]
        at = jnp.minimum(jnp.sum(before, axis=-1, dtype=jnp.int32), size - 1)
        each = totals - jnp.pad(totals, ((0, 0), (0, 0), (1, 0)))[..., :-1]
        return at, rank - jnp.sum(jnp.where(before, each, 0), axis=-1)

    cells = groups(mask)
    # rows of the groups 0..g of an event in its lane: [B * P, R * G]
    upto_g = jnp.cumsum(
        jnp.sum(cells, axis=1, dtype=jnp.int32).reshape(
            B, R, P // T, G, T).transpose(0, 2, 4, 1, 3).reshape(
                B * P, R * G), axis=1)
    # rows of events 0..b, lane by lane: [P, B]
    upto = jnp.cumsum(upto_g[:, -1].reshape(B, P), axis=0).T
    n = upto[:, -1]
    lane = jnp.arange(P, dtype=jnp.int32)[:, None]

    def table(n_rows: int) -> dict:
        r = jnp.broadcast_to(jnp.arange(n_rows, dtype=jnp.int32), (P, n_rows))
        taken = r < n[:, None]
        j, rank = locate(r, upto[:, None, :], B)
        g, rank = locate(rank, upto_g[j * P + lane], R * G)
        at = ((((j * R + g // G) * (P // T) + lane // T) * G + g % G) * T
              + lane % T)                           # the row's group of cells
        x, _ = locate(rank, jnp.cumsum(cells[at].astype(jnp.int32), axis=-1),
                      _GROUP)
        here = (jnp.arange(_GROUP, dtype=jnp.int32) == x[..., None]) \
            & taken[..., None]                      # [P, n_rows, _GROUP]
        out = {"mask": taken, "j": jnp.where(taken, j, 0)}
        for name, grid in cols.items():
            # the cell's group again, then its lane by select-and-sum over
            # the value's bits (moved, never computed with: exact for every
            # dtype); the rows fetched are turned into bits, not the grid
            near = groups(grid)[at]
            bits = near.astype(jnp.uint8) if grid.dtype == jnp.bool_ else \
                jax.lax.bitcast_convert_type(
                    near, jnp.dtype(f"uint{8 * grid.dtype.itemsize}"))
            got = jnp.sum(jnp.where(here, bits, 0), axis=-1,
                          dtype=bits.dtype)
            out[name] = got != 0 if grid.dtype == jnp.bool_ else \
                jax.lax.bitcast_convert_type(got, grid.dtype)
        return out

    return n, table


# ---------------------------------------------------------------------------
# compilation
# ---------------------------------------------------------------------------

@dataclass
class _DevBranch:
    stream_idx: int
    alias: str
    predicate: Optional[Callable] = None   # fn(env) -> bool/[C]
    is_absent: bool = False


@dataclass
class _DevState:
    index: int
    kind: str                    # 'stream' | 'count' | 'logical' | 'absent'
    branches: "list[_DevBranch]"
    logical_type: Optional[str] = None     # 'and' | 'or'
    waiting_ms: Optional[int] = None       # absent `for`
    min_count: int = 1
    max_count: int = 1
    ends_every: bool = False     # reseed scope [0..index]
    within_ms: Optional[int] = None        # element-level within
    reseed_to: Optional[int] = None        # every-scope start this state ends

    # single-branch conveniences (stream/count states)
    @property
    def stream_idx(self) -> int:
        return self.branches[0].stream_idx

    @property
    def alias(self) -> str:
        return self.branches[0].alias

    @property
    def predicate(self):
        return self.branches[0].predicate


class _NFAResolver:
    """Resolves Variables inside predicates/output of the device NFA.

    Namespace env keys:
      ``ev_{attr-key}``    — candidate event scalar (merged column key)
      ``b{q}_{attr}``      — bound value arrays of prior state q  [C]
      ``b{q}_first_{attr}`` / ``b{q}_last_{attr}`` — count-state variants
    """

    def __init__(self, nfa: "DeviceNFACompiler", current_state: Optional[int],
                 current_alias: Optional[str] = None):
        self.nfa = nfa
        self.current = current_state
        self.current_alias = current_alias
        self.touched: list = []        # (state, variant) bound refs resolved
        self.ev_read: list = []        # ev_ keys resolved (candidate event)
        # backend the compiled predicate/output closures execute on (numpy
        # for the columnar host engine; default lazy jax.numpy)
        xp = getattr(nfa, "xp", None)
        if xp is not None:
            self.xp = xp

    def resolve(self, var: Variable) -> tuple[str, DataType]:
        nfa = self.nfa
        alias = var.stream_id
        cur = nfa.states[self.current] if self.current is not None else None
        cur_aliases = [b.alias for b in cur.branches] if cur is not None else []
        if alias is None or (cur is not None and alias in cur_aliases):
            # candidate-event reference: the state currently being matched.
            # A logical branch predicate only sees its own event — sibling
            # references need the host path (unbound-side semantics).
            if cur is None:
                raise DeviceCompileError("bare attribute outside a state context")
            a = alias or self.current_alias or cur.branches[0].alias
            if self.current_alias is not None and a != self.current_alias:
                raise DeviceCompileError(
                    "sibling alias reference inside a logical state needs "
                    "the host path")
            sid = nfa.compiled.alias_defs[a].id
            key = nfa.merged.col_key(sid, var.attribute)
            if var.attribute not in nfa.compiled.alias_defs[a].attribute_names:
                raise DeviceCompileError(f"unknown attribute '{var.attribute}'")
            nfa.used_ev_cols.add(key)
            self.ev_read.append(f"ev_{key}")
            return f"ev_{key}", nfa.merged.columns[key]
        if alias not in nfa.alias_branch:
            raise DeviceCompileError(f"unknown alias '{alias}'")
        q, bi = nfa.alias_branch[alias]
        d = nfa.compiled.alias_defs[alias]
        if var.attribute not in d.attribute_names:
            raise DeviceCompileError(f"unknown attribute '{var.attribute}'")
        t = d.attribute_type(var.attribute)
        if nfa.states[q].kind == "count":
            # count variants use '#' separators — '#' cannot occur in an
            # attribute identifier, so names like "occupancy" or "last_x"
            # can never collide with the variant markers
            from ..query_api.expression import LAST_INDEX as _LAST
            if var.stream_index == 0:
                variant = f"b{q}#first#{var.attribute}"
            elif var.stream_index in (None, _LAST):
                variant = f"b{q}#last#{var.attribute}"
            else:
                # e2[k]: the slot table carries one bound column per
                # statically-referenced occurrence index (+ a set flag for
                # NULL when the count never reached k+1) — the reference
                # keeps the whole occurrence list per partial
                # (StreamPreStateProcessor pending StateEvents)
                k = var.stream_index
                if not isinstance(k, int) or k < 0 or k > _MAX_OCC_INDEX:
                    raise DeviceCompileError(
                        f"count e[k] index {k!r} out of device range "
                        f"(0..{_MAX_OCC_INDEX})")
                variant = f"b{q}#occ{k}#{var.attribute}"
                nfa.referenced.add((q, _occ_flag(q, k), DataType.BOOL))
        elif nfa.states[q].kind == "logical":
            variant = f"b{q}x{bi}_{var.attribute}"
        else:
            if var.stream_index not in (None,):
                from ..query_api.expression import LAST_INDEX
                if var.stream_index not in (0, LAST_INDEX):
                    raise DeviceCompileError("e[k] indexing needs host path")
            variant = f"b{q}_{var.attribute}"
        nfa.referenced.add((q, variant, t))
        self.touched.append((q, variant))
        return variant, t

    def param_key(self, p) -> str:
        # fleet per-tenant parameter slots ride the event-column namespace
        # (every cols entry is ev_-prefixed in the step env); they are
        # injected at step time, never staged, so they are NOT used_ev_cols
        self.ev_read.append(f"ev_{p.key}")
        return f"ev_{p.key}"

    def encode_string(self, key: str, value: str) -> int:
        # key may be ev_{merged} or b{q}_...: map back to the merged dictionary
        if key.startswith("ev_"):
            mk = key[3:]
        else:
            # bound col: find source merged key via alias
            parts = key.split("_", 1)
            q = int(parts[0].lstrip("b").split("_")[0]) if False else None
            mk = self._bound_to_merged(key)
        dic = self.nfa.merged.dictionaries.get(mk)
        if dic is None:
            raise DeviceCompileError(f"no dictionary for '{key}'")
        return dic.encode(value)

    def _bound_to_merged(self, key: str) -> str:
        # b{q}x{bi}_{attr} | b{q}_{attr} | b{q}#first|last|occ{k}#{attr}
        body = key[1:]
        if "#" in body:                             # count variant
            q_str, rest = body.split("#", 1)
            if rest.startswith("first#"):
                rest = rest[len("first#"):]
            elif rest.startswith("last#"):
                rest = rest[len("last#"):]
            elif rest.startswith("occ"):            # occ{k}#{attr}
                rest = rest.split("#", 1)[1]
            alias = self.nfa.states[int(q_str)].alias
            sid = self.nfa.compiled.alias_defs[alias].id
            return self.nfa.merged.col_key(sid, rest)
        q_str, rest = body.split("_", 1)
        if "x" in q_str:
            q_part, bi_part = q_str.split("x")
            alias = self.nfa.states[int(q_part)].branches[int(bi_part)].alias
        else:
            alias = self.nfa.states[int(q_str)].alias
        sid = self.nfa.compiled.alias_defs[alias].id
        return self.nfa.merged.col_key(sid, rest)


def _null_strict(e) -> bool:
    """True if a NULL input anywhere makes the whole expression falsy —
    i.e. the expression is built only of comparisons/math/AND over
    variables and constants (host executors propagate null through math
    and evaluate null comparisons/conjunctions to false)."""
    from ..query_api.expression import (
        And,
        Compare,
        Constant,
        MathExpr,
        Minus,
        Variable,
    )
    if isinstance(e, (Variable, Constant)):
        return True
    if isinstance(e, (Compare, And, MathExpr)):
        return _null_strict(e.left) and _null_strict(e.right)
    if isinstance(e, Minus):
        return _null_strict(e.expr)
    return False


class DeviceNFACompiler:
    def __init__(self, query: Query, stream_defs: dict[str, StreamDefinition],
                 slot_capacity: int = 64, batch_capacity: int = 1024,
                 creation_cap: Optional[int] = None,
                 backend: str = "jax"):
        ist = query.input_stream
        if not isinstance(ist, StateInputStream):
            raise DeviceCompileError("not a pattern/sequence query")
        # backend="numpy": compile the SAME plan (states, predicates, output
        # programs) against plain numpy for the columnar host engine
        # (tpu/host_exec.py) — no jit, no device, f64/i64 dtype policy
        self.backend = backend
        if backend == "numpy":
            self.xp = np
        self.query = query
        self.C = slot_capacity
        self.B = batch_capacity
        self.compiled: CompiledPattern = PatternCompiler(ist, stream_defs).compile()
        self.is_sequence = self.compiled.is_sequence
        self.within = self.compiled.within_ms
        self.merged = MergedBatchSchema(stream_defs, self.compiled.stream_ids)
        self.stream_defs = stream_defs

        # validate + lower nodes
        self.states: list[_DevState] = []
        self.alias_branch: dict[str, tuple[int, int]] = {}   # alias → (state, branch)
        self.referenced: set[tuple[int, str, DataType]] = set()
        nodes = self.compiled.nodes
        has_element_within = any(n.within_ms is not None for n in nodes)
        for node in nodes:
            if node.kind not in ("stream", "count", "logical", "absent"):
                raise DeviceCompileError(
                    f"'{node.kind}' states need the host path")
            if node.reseed_to not in (None, 0) and node.kind != "stream":
                # mid-pattern scope-end reseeds are implemented only at the
                # stream-state advance site
                raise DeviceCompileError(
                    "mid-pattern `every` ending at a non-stream state needs "
                    "the host path")
            if node.kind == "logical" and node.waiting_time_ms is not None \
                    and self.is_sequence:
                raise DeviceCompileError(
                    "`and/or not X for t` in sequences needs the host path")
            if node.kind == "absent" and node.waiting_time_ms is None:
                raise DeviceCompileError(
                    "absent without `for` needs the host path")
            if node.kind in ("logical", "absent") and node.index > 0 \
                    and nodes[node.index - 1].kind == "count":
                # the count-prev eligibility source exists only for
                # immediate-advance logical shapes (no per-slot wait state
                # to carry on the shared count partial): `X and not Y`
                # without `for`, or a pure OR
                has_absent = any(b.is_absent for b in node.branches)
                lt = node.logical_type.value if node.logical_type else None
                immediate = (
                    node.kind == "logical"
                    and node.waiting_time_ms is None
                    and ((lt == "and" and has_absent)
                         or (lt == "or" and not has_absent)))
                if not immediate:
                    raise DeviceCompileError(
                        "logical/absent after a count state needs the host "
                        "path")
            if node.kind == "count" and node.index > 0 \
                    and nodes[node.index - 1].kind == "count":
                # only the stream-state advance path pulls eligible partials
                # out of a count table — back-to-back counts have no advance
                # edge on device
                raise DeviceCompileError(
                    "count directly after a count state needs the host path")
            if node.kind == "absent" and self.is_sequence:
                raise DeviceCompileError(
                    "absent in sequences needs the host path")
            branches = [
                _DevBranch(stream_idx=self.merged.stream_index[b.stream_id],
                           alias=b.alias, is_absent=b.is_absent)
                for b in node.branches
            ]
            st = _DevState(
                index=node.index, kind=node.kind, branches=branches,
                logical_type=(node.logical_type.value
                              if node.logical_type is not None else None),
                waiting_ms=node.waiting_time_ms,
                min_count=node.min_count, max_count=node.max_count,
                ends_every=node.reseed_to == 0,
                within_ms=node.within_ms,
                reseed_to=node.reseed_to,
            )
            self.states.append(st)
            for bi, b in enumerate(node.branches):
                self.alias_branch[b.alias] = (node.index, bi)
        final = self.states[-1]
        if final.kind == "count" and len(self.states) >= 2 \
                and self.states[-2].kind in ("logical", "absent") \
                and final.min_count == 0:
            # zero-min final counts emit at ARRIVAL; only the stream-advance
            # and seed paths implement that emit
            raise DeviceCompileError(
                "logical/absent into a zero-min final count needs the host "
                "path")

        self.S = len(self.states)
        self.always_seed = self.states[0].ends_every and self.S == 1 or \
            (self.states[0].ends_every)
        # group-every: scope end j > 0 → seeds replenished on state j advance
        self.every_end = next(
            (s.index for s in self.states if s.ends_every), None)
        if self.is_sequence and self.every_end not in (None, 0):
            # strict kills inside a group `every (...)` scope must return the
            # scope seed (host _reseed_on_expiry); the kernel's seed counter
            # only models state-0 scopes
            raise DeviceCompileError(
                "group `every` scopes in sequences need the host path")
        # mid-pattern `every` scopes [r..k], r > 0: the scope-end advance
        # re-places a clone at p{r} (scope bindings cleared) that becomes
        # visible on the NEXT event (host `_created` skip)
        self.reseed_targets = sorted({st.reseed_to for st in self.states
                                      if st.reseed_to not in (None, 0)})
        for r in self.reseed_targets:
            if self.states[r].kind != "stream":
                raise DeviceCompileError(
                    "mid-pattern `every` starting at a non-stream state "
                    "needs the host path")
        if self.is_sequence and self.reseed_targets:
            raise DeviceCompileError(
                "mid-pattern `every` in sequences needs the host path")
        s0 = self.states[0]
        # absent-start / `X and-or not Y`-start patterns carry a PRE-PLACED
        # seed slot (host places one partial at start(); its non-occurrence
        # clock begins at the runtime start time)
        self.preseeded = s0.kind == "absent" or (
            s0.kind == "logical" and any(b.is_absent for b in s0.branches))
        if self.preseeded and self.every_end not in (None, 0):
            raise DeviceCompileError(
                "group `every` over an absent-start scope needs the host "
                "path")

        # compile predicates (after alias map ready) from the original ASTs
        self.used_ev_cols: set[str] = set()
        self._compile_predicates(ist)
        # output programs
        self._compile_output(query)
        # merged columns the compiled program actually reads — the builders
        # stage and TRANSFER only these (unreferenced columns like partition
        # keys cost 4B/event of host-to-device transfer for nothing)
        resolver = _NFAResolver(self, None)
        self.used_cols = set(self.used_ev_cols)
        for (q, key, t) in self.referenced:
            if key.endswith("__set") or key == _has_flag(q) or \
                    (key.startswith(f"b{q}#occ") and key.endswith("flag")
                     and "#" not in key[len(f"b{q}#occ"):]):
                continue               # synthetic null-tracking flags
            self.used_cols.add(resolver._bound_to_merged(key))
        # kernel selection: stream-state chains with `every` take the blocked
        # batch-parallel kernel (sequential depth S, not B — nfa_block.py);
        # count/logical/absent states use the per-event scan
        from .nfa_block import blocked_eligible
        self.blocked = blocked_eligible(self)
        # blocked-kernel creation budget: compacts per-batch creations to K
        # entries, capping every stage grid at [B, C+K] instead of the
        # quadratic [B, C+B] (measured: the quadratic term dominates the
        # step at B >= 1024; overflow drops are counted, drop-newest)
        self.creation_cap = creation_cap
        # which scan-kernel tables can ever hold a partial: ``p0`` where
        # state 0 keeps its own (a stream start seeds straight into ``p1``),
        # ``p<s>`` where state s-1 pushes (a count's partials are pulled
        # out of its own table), and mid-pattern ``every`` targets. A table
        # that holds nothing is no candidate source (``_make_step``) and
        # takes no room in the row table (``_row_capacity``).
        self.table_holds = [
            (s == 0 and st.kind != "stream")
            or (s > 0 and self.states[s - 1].kind != "count")
            or s in self.reseed_targets
            for s, st in enumerate(self.states)]
        # rows of the table a step hands out: the batch's event capacity,
        # whichever kernel. The batch that emits more in some lane is read
        # from ``full`` beside it (``decode_rows``): the blocked kernel's
        # whole candidate table, the scan kernel's table of
        # ``_row_capacity`` rows
        self.M = self.B
        if has_element_within and not self.blocked:
            # the blocked kernel masks per-state gaps on its grids; the scan
            # kernel's tables don't carry last-bind times
            raise DeviceCompileError(
                "element-level within outside stream-chain patterns needs "
                "the host path")
        if backend == "numpy":
            # the columnar host engine (tpu/host_exec.py) executes the plan
            # eagerly with dynamic shapes; it only covers the blocked shape
            if not self.blocked:
                raise DeviceCompileError(
                    "count/logical/absent states have no columnar host "
                    "kernel — scalar interpreter path")
            self._step = None
        else:
            self._step = jax.jit(self._make_step(), donate_argnums=(0,))

    def _row_capacity(self) -> int:
        """Rows of the scan kernel's ``full`` table, a bound on what one
        batch can emit, from the plan alone: no ``@device`` key sizes it.
        The pack runs at this size, and the decode reads ``full``, only
        for a batch in which some lane emitted more than the ``M`` rows of
        the packed table (``_make_step`` ``hand_out``); the blocked kernel's
        ``full`` is its whole candidate table.

        Every emit site consumes the partial it emits (its slot's ``valid``
        goes, or a start slot is re-armed as a NEW partial), so a batch
        emits at most one row per partial that existed in it: those alive
        in a table when the batch began plus those created by its events.
        A table holds partials only if something inserts into it: ``p0``
        where state 0 keeps its partial (count, logical, absent starts;
        a stream start seeds straight into ``p1``), ``p<s>`` where state
        ``s-1`` pushes (anything but a count, whose partials are pulled
        from its own table), and mid-pattern ``every`` targets. An event
        creates at most one partial (the seed, or a start slot's re-arm),
        two where a pre-placed start can fire in the expiry pre-pass and
        again in the main pass. So ``live x C + creations x B``:
        ``C + B`` for ``every A -> B<m:n> -> C``, whose only populated table
        is the count state's. A mid-pattern ``every`` CLONES a partial at
        each scope end, so its rows have no bound below the emit grids
        themselves; it gets room for every partial to emit twice. Whatever
        the bound, rows past it are counted into ``drops`` (never silent),
        and the emit grids' own size ``2 x C x B`` caps it."""
        C, B = self.C, self.B
        live = sum(self.table_holds)
        once = max(live, 1) * C + (2 if self.preseeded else 1) * B
        return min(2 * once if self.reseed_targets else once, 2 * C * B)

    def _compile_predicates(self, ist: StateInputStream) -> None:
        # recover filter ASTs from the host compiler's branch filters is not
        # possible (already closures), so re-walk the AST tree in node order
        from ..query_api import (
            AbsentStreamStateElement,
            CountStateElement,
            EveryStateElement,
            Filter,
            LogicalStateElement,
            NextStateElement,
            StreamStateElement,
        )
        filters: list[list[Any]] = []     # per node, per branch

        def walk(el):
            if isinstance(el, NextStateElement):
                walk(el.first)
                walk(el.next)
            elif isinstance(el, EveryStateElement):
                walk(el.inner)
            elif isinstance(el, StreamStateElement):
                filters.append([_filter_of(el.stream)])
            elif isinstance(el, CountStateElement):
                filters.append([_filter_of(el.stream.stream)])
            elif isinstance(el, LogicalStateElement):
                row = []
                for sub in (el.first, el.second):
                    row.append(_filter_of(sub.stream))
                filters.append(row)
            elif isinstance(el, AbsentStreamStateElement):
                filters.append([_filter_of(el.stream)])
            else:
                raise DeviceCompileError(
                    f"{type(el).__name__} needs the host path")

        def _filter_of(stream):
            ast = None
            from ..query_api import And
            for h in stream.handlers:
                if isinstance(h, Filter):
                    ast = h.expr if ast is None else And(ast, h.expr)
                else:           # windows / stream functions inside a pattern
                    raise DeviceCompileError(
                        f"pattern stream handler "
                        f"{type(h).__name__} needs the host path")
            return ast

        walk(ist.state)
        assert len(filters) == self.S
        for s, asts in zip(self.states, filters):
            assert len(asts) == len(s.branches)
            for b, ast in zip(s.branches, asts):
                if ast is None:
                    b.predicate = None
                else:
                    resolver = _NFAResolver(self, s.index, b.alias)
                    fn, _ = compile_expression(ast, resolver)
                    b.predicate = self._guard_predicate(ast, fn,
                                                        resolver.touched)

    def _guard_predicate(self, ast, fn, touched):
        """Null-guard a predicate whose refs may be unbound at eval time.

        The host evaluates comparisons over NULL to false (executor null
        propagation); the device carries ZEROS in unbound slot fields, so a
        null-strict predicate is ANDed with per-slot "bound" flags instead
        (zero-min count bindings, ``e[k]`` occurrences). Shapes where NULL
        does not simply poison the result (or/not/isNull/functions) over
        such refs — and refs whose flags aren't carried (OR/absent sides)
        — fall back to the host path."""
        flags: set[tuple[int, str]] = set()
        for (q, key) in touched:
            st = self.states[q]
            if key.startswith(f"b{q}x"):
                bi = int(key[len(f"b{q}x"):].split("_", 1)[0])
                if st.logical_type == "or" or st.branches[bi].is_absent:
                    raise DeviceCompileError(
                        "predicate referencing an OR/absent side needs the "
                        "host path")
            elif key.startswith(f"b{q}#occ"):
                k = int(key[len(f"b{q}#occ"):].split("#", 1)[0])
                flags.add((q, _occ_flag(q, k)))
            elif st.kind == "absent":
                raise DeviceCompileError(
                    "predicate referencing an absent alias needs the host "
                    "path")
            elif st.kind == "count" and st.min_count == 0:
                flags.add((q, _has_flag(q)))
        if not flags:
            return fn
        if not _null_strict(ast):
            raise DeviceCompileError(
                "non-null-strict predicate over possibly-unbound bindings "
                "needs the host path")
        for (q, flag) in flags:
            self.referenced.add((q, flag, DataType.BOOL))
        guard_keys = tuple(sorted(flag for (_, flag) in flags))

        def guarded(env, _fn=fn, _keys=guard_keys):
            r = _fn(env)
            for fkey in _keys:
                r = r & env[fkey]
            return r

        return guarded

    def _compile_output(self, query: Query) -> None:
        sel = query.selector
        self.out_specs: list[tuple[str, Callable, DataType]] = []
        attrs = sel.attributes
        if sel.select_all or not attrs:
            raise DeviceCompileError("pattern select * needs the host path")
        final = self.S - 1
        # logical/absent finals emit from slot-bound values (possibly with no
        # candidate event at all), so bare/candidate references must not bind
        out_ctx = final if self.states[final].kind == "stream" else None
        # per-output null dependencies: an output referencing an OR state's
        # unmatched side / an absent branch / a zero-min count's bindings is
        # NULL when that side never bound — a zero VALUE is legal data, so a
        # carried boolean flag travels with the partial instead (host parity;
        # formerly a documented divergence)
        self.out_null_deps: list[set] = []
        # ev_ keys the outputs read off the matching event: all that the
        # blocked kernel's last stage fetches of it (nfa_block.py)
        self.out_ev_keys: set[str] = set()
        for oa in attrs:
            resolver = _NFAResolver(self, out_ctx)
            fn, t = compile_expression(oa.expr, resolver)
            self.out_ev_keys.update(resolver.ev_read)
            deps = set()
            for (q, key) in resolver.touched:
                if key.startswith(f"b{q}x"):        # logical branch binding
                    bi = int(key[len(f"b{q}x"):].split("_", 1)[0])
                    st = self.states[q]
                    if st.logical_type == "or" or st.branches[bi].is_absent:
                        deps.add((q, f"b{q}x{bi}__set"))
                elif key.startswith(f"b{q}#occ"):
                    # e[k] is NULL when the count never reached k+1
                    k = int(key[len(f"b{q}#occ"):].split("#", 1)[0])
                    deps.add((q, _occ_flag(q, k)))
                elif self.states[q].kind == "count" \
                        and self.states[q].min_count == 0:
                    deps.add((q, _has_flag(q)))
            self.out_specs.append((oa.name, fn, t))
            self.out_null_deps.append(deps)
        for deps in self.out_null_deps:
            for (q, flag) in deps:
                self.referenced.add((q, flag, DataType.BOOL))

    # ------------------------------------------------------------------ state
    def init_state(self, start_ts: int = 0) -> dict:
        if self.blocked:
            from .nfa_block import block_init_state
            return block_init_state(self)
        C, S = self.C, self.S
        pend = {}
        for s in range(S):
            st = self.states[s]
            fields: dict[str, Any] = {
                "valid": jnp.zeros((C,), jnp.bool_),
                # -1 = unset: ts 0 is a legal event time (same sentinel rule
                # as arrive_ts below)
                "first_ts": jnp.full((C,), -1, jnp.int64),
            }
            if st.kind == "count":
                fields["count"] = jnp.zeros((C,), jnp.int32)
                fields["closed"] = jnp.zeros((C,), jnp.bool_)
            if st.kind == "logical" and st.logical_type == "and":
                for bi in range(len(st.branches)):
                    fields[f"done{bi}"] = jnp.zeros((C,), jnp.bool_)
            if st.kind == "logical" and st.logical_type == "or":
                # `X or not Y [for t]`: Y's arrival kills only the absent
                # ALTERNATIVE, not the partial
                for bi, br in enumerate(st.branches):
                    if br.is_absent:
                        fields[f"absdead{bi}"] = jnp.zeros((C,), jnp.bool_)
            if st.kind == "absent" or (st.kind == "logical" and
                                       st.waiting_ms is not None):
                # -1 = unarmed: ts 0 is a legal event time, so 0 cannot be
                # the "no arrival yet" sentinel (advisor round-1 finding)
                fields["arrive_ts"] = jnp.full((C,), -1, jnp.int64)
            if s in self.reseed_targets:
                # clones placed by a scope-end advance, invisible until the
                # next event (host `_created` skip)
                fields["fresh"] = jnp.zeros((C,), jnp.bool_)
            for (q, key, t) in self.referenced:
                if q < s or (q == s and st.kind in ("count", "logical")):
                    fields[key] = jnp.zeros((C,), _JNP[t])
            if s == 0 and self.preseeded:
                # the host places ONE partial at start(); its non-occurrence
                # clock starts at the runtime start time
                fields["valid"] = fields["valid"].at[0].set(True)
                if "arrive_ts" in fields:
                    fields["arrive_ts"] = fields["arrive_ts"].at[0].set(
                        start_ts)
            pend[f"p{s}"] = fields
        return {
            "pending": pend,
            "seeds": jnp.array(0 if self.preseeded else 1, jnp.int64),
            "drops": jnp.array(0, jnp.int64),
            "matches": jnp.array(0, jnp.int64),
        }

    # ------------------------------------------------------------------- step
    def _make_step(self, form: str = "step"):
        """``form``: ``'step'`` one lane's step, ``'stacked'`` the same over
        ``[P, ...]``-stacked lanes, ``'scan'`` the scan kernel's first half
        alone (``make_step`` / ``make_scan`` say what each hands out)."""
        if self.blocked:
            from .nfa_block import make_block_step
            step = make_block_step(self)
            return jax.vmap(step) if form == "stacked" else step
        C, S, B, M_full = self.C, self.S, self.B, self._row_capacity()
        states = self.states
        within = self.within
        is_seq = self.is_sequence
        always_seed = self.states[0].ends_every
        every_end = self.every_end
        out_specs = self.out_specs
        out_null_deps = self.out_null_deps
        referenced = sorted(self.referenced)
        n_out = len(out_specs)

        def _clocked(stx) -> bool:
            """State whose slots carry a non-occurrence clock."""
            return stx.kind == "absent" or (
                stx.kind == "logical" and stx.waiting_ms is not None)

        def bound_keys_for(level: int):
            st = states[level]
            return [key for (q, key, t) in referenced
                    if q < level or (q == level and st.kind == "count")]

        def insert(slots: dict, ins_mask, values: dict, ts_new, counts_new=None):
            """Scatter candidates (ins_mask over [C]) into free slots. Returns
            (new_slots, n_dropped)."""
            free = ~slots["valid"]
            free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1     # rank among free
            ins_rank = jnp.cumsum(ins_mask.astype(jnp.int32)) - 1  # rank among inserts
            n_free = jnp.sum(free.astype(jnp.int32))
            n_ins = jnp.sum(ins_mask.astype(jnp.int32))
            # map free_rank -> slot index so insert j targets the j-th free slot
            slot_of_rank = jnp.zeros((C,), jnp.int32).at[
                jnp.where(free, free_rank, C - 1)].set(
                jnp.where(free, jnp.arange(C, dtype=jnp.int32), 0), mode="drop")
            ok = ins_mask & (ins_rank < n_free)
            tgt = jnp.where(ok, slot_of_rank[jnp.clip(ins_rank, 0, C - 1)], C)
            new = dict(slots)
            new["valid"] = slots["valid"].at[tgt].set(
                jnp.where(ok, True, False), mode="drop")
            new["first_ts"] = slots["first_ts"].at[tgt].set(
                jnp.where(ok, ts_new, -1), mode="drop")
            if "count" in slots:
                cnew = counts_new if counts_new is not None else jnp.ones((C,), jnp.int32)
                new["count"] = slots["count"].at[tgt].set(
                    jnp.where(ok, cnew, 0), mode="drop")
                new["closed"] = slots["closed"].at[tgt].set(False, mode="drop")
            # every field is written for inserted slots: either the provided
            # value or a reset — a freed slot must not leak stale bound
            # values / done flags into the partial that reuses it
            for key in slots:
                if key in ("valid", "first_ts", "count", "closed"):
                    continue
                reset = jnp.asarray(-1 if key == "arrive_ts" else 0,
                                    slots[key].dtype)
                arr = values.get(key)
                if arr is None:
                    new[key] = slots[key].at[tgt].set(reset, mode="drop")
                else:
                    new[key] = slots[key].at[tgt].set(
                        jnp.where(ok, arr, reset), mode="drop")
            dropped = jnp.maximum(n_ins - n_free, 0)
            inserted = jnp.zeros((C,), jnp.bool_).at[tgt].set(ok, mode="drop")
            return new, dropped, inserted

        def insert_seed(slots: dict, want, values: dict, ts_new,
                        counts_new=None):
            """``insert`` for ONE candidate, the event's own seed (``want``
            a scalar; ``values`` / ``ts_new`` / ``counts_new`` [C] arrays
            whose entry 0 is the candidate's): it takes the first free slot,
            which is where ``insert`` ranks candidate 0, found by one
            argmax and written by selects. Nothing is scattered: with the
            seed scattered like any candidate, one event's turn of the 256
            x 1,408 deployment took 43 ms on a v5e (13.8 s a batch), 24 of
            them the int64 ``first_ts`` alone; like this 0.1 ms (PERF.md
            section 6, PR 33)."""
            free = ~slots["valid"]
            room = jnp.any(free)
            hit = (jnp.arange(C, dtype=jnp.int32)
                   == jnp.argmax(free).astype(jnp.int32)) & want & room
            new = dict(slots)
            new["valid"] = slots["valid"] | hit
            new["first_ts"] = jnp.where(hit, ts_new[0], slots["first_ts"])
            if "count" in slots:
                new["count"] = jnp.where(
                    hit, 1 if counts_new is None else counts_new[0],
                    slots["count"])
                new["closed"] = slots["closed"] & ~hit
            for key in slots:
                if key in ("valid", "first_ts", "count", "closed"):
                    continue
                arr = values.get(key)
                put = jnp.asarray(-1 if key == "arrive_ts" else 0,
                                  slots[key].dtype) if arr is None \
                    else arr[0].astype(slots[key].dtype)
                new[key] = jnp.where(hit, put, slots[key])
            return new, (want & ~room).astype(jnp.int32), hit

        def step_event(carry, ev):
            pend = dict(carry["pending"])
            seeds = carry["seeds"]
            drops = carry["drops"]
            n_match = carry["matches"]
            ev_ts = ev["ts"]
            ev_tag = ev["tag"]
            ev_ok = ev["valid"]

            # within-expiry reclaims slots
            for s in range(S if within is not None else 0):
                with jax.named_scope("nfa.expire"):
                    slots = dict(pend[f"p{s}"])
                    has_first = slots["first_ts"] >= 0
                    alive = ~(has_first & (ev_ts - slots["first_ts"] > within))
                    if not always_seed and every_end is not None \
                            and s <= every_end:
                        # an expired in-scope instance re-initializes the
                        # `every` scope start: its seed returns, usable by
                        # THIS event (reference re-inits start states during
                        # expiry; WithinPatternTestCase.testQuery4)
                        expired = slots["valid"] & ~alive
                        seeds = seeds + jnp.sum(expired.astype(jnp.int64))
                    slots["valid"] = slots["valid"] & alive
                    pend[f"p{s}"] = slots

            # zero-min count scope start: maintain a pre-seeded EMPTY partial
            # (count=0, no first-bind time) whenever a seed is available —
            # the successor's eligibility path (count >= min == 0) then
            # advances it with zero occurrences, matching the host's
            # "immediately eligible at the successor" rule
            # (core/pattern.py). Extensions bind occurrences in place, so
            # the ordinary seed path is disabled for this state below.
            if states[0].kind == "count" and states[0].min_count == 0:
                # gate on "no OPEN instance": the host reseeds a count scope
                # only when the active instance closes (maxes out) or
                # advances — never while one is still absorbing events
                # (CountPreStateProcessor max-reach reseed)
                p0 = pend["p0"]
                has_open = jnp.any(p0["valid"] & ~p0["closed"])
                want = ev_ok & ~has_open & (
                    jnp.array(True) if always_seed else seeds > 0)
                new0, dropped0, replenish_ins = insert_seed(
                    p0, want, {},
                    jnp.full((C,), -1, jnp.int64),
                    jnp.zeros((C,), jnp.int32))
                pend["p0"] = new0
                drops = drops + dropped0.astype(jnp.int64)
                if not always_seed:
                    seeds = seeds - want.astype(jnp.int64)
            else:
                replenish_ins = None

            # seeds available to THIS event: replenishments from scope
            # completions during this event become usable only on the NEXT
            # event (the reference re-seeds via the post-state processor,
            # after the completing event is done; EveryPatternTestCase
            # testQuery7 — the completing event must not immediately reuse
            # the seed it just returned). Expiry returns (above) ARE visible.
            seeds0 = seeds

            # the event's emit grids, one [C] row per emit source the plan
            # USES (row 0: a state's own table, row 1: the count table before
            # it), kept by row so that a plan with one source stacks and
            # packs one: {row: [C]} for the mask and for every column
            out_mask = {}
            out_cols = [{} for _ in out_specs]
            # per-output null masks (OR-unmatched side / absent branch /
            # zero-occurrence count refs emit NULL, not the zero value)
            out_nulls = [{} if out_null_deps[oi] else None
                         for oi in range(n_out)]
            touched = {s: jnp.zeros((C,), jnp.bool_) for s in range(S)}
            if replenish_ins is not None:
                # a partial placed this event is exempt from sequence strict
                # kill until the NEXT event (host `_created` set)
                touched[0] = touched[0] | replenish_ins

            def emit_rows(out_mask, out_cols, n_match, mask, row, emit_env):
                """Accumulate matched slots into output row `row`."""
                with jax.named_scope("nfa.emit"):
                    out_mask[row] = out_mask.get(row, False) | mask
                    for oi, (_, fn, t) in enumerate(out_specs):
                        val = jnp.broadcast_to(fn(emit_env), (C,)).astype(
                            _JNP[t])
                        out_cols[oi][row] = jnp.where(
                            mask, val,
                            out_cols[oi].get(row, jnp.zeros((), _JNP[t])))
                        if out_null_deps[oi]:
                            nm = jnp.zeros((C,), jnp.bool_)
                            for (q, flag) in sorted(out_null_deps[oi]):
                                got = emit_env.get(flag)
                                if got is None:      # flag not carried → unbound
                                    nm = jnp.ones((C,), jnp.bool_)
                                else:
                                    nm = nm | ~jnp.broadcast_to(got, (C,))
                            out_nulls[oi][row] = jnp.where(
                                mask, nm, out_nulls[oi].get(row, False))
                return out_mask, out_cols, \
                    n_match + jnp.sum(mask.astype(jnp.int64))

            # ---- expiry pre-pass (absent + logical-`for` states): host
            # timers fire BEFORE the event is delivered, so established
            # non-occurrences advance first (the arriving event can then
            # match the successor state). Ascending order lets a partial hop
            # a chain of expired absents in one step. An always-seed start
            # state re-arms instead of dying (host reseeds during the
            # advance); several establishments inside ONE inter-event gap
            # collapse to a single advance per event (documented divergence:
            # the host fires one timer per `for` interval).
            def expire_state(s):
                nonlocal seeds, drops, n_match, out_mask, out_cols
                st = states[s]
                slots = pend[f"p{s}"]
                estab = slots["valid"] & ev_ok & (slots["arrive_ts"] >= 0) & \
                    (ev_ts >= slots["arrive_ts"] + st.waiting_ms)
                if st.kind == "absent":
                    adv = estab
                elif st.logical_type == "and":
                    # AND: advance only partials whose present side bound
                    adv = estab
                    for bi, br in enumerate(st.branches):
                        if not br.is_absent:
                            adv = adv & slots[f"done{bi}"]
                else:
                    # OR: established non-occurrence completes the state
                    # with the present side unbound (NULL) — unless the
                    # forbidden event spoiled the wait
                    adv = estab
                    for bi, br in enumerate(st.branches):
                        if br.is_absent:
                            adv = adv & ~slots[f"absdead{bi}"]
                ns = dict(slots)
                if s == 0 and always_seed:
                    # re-arm the start seed: clock jumps to the established
                    # boundary, binding state resets (host places a fresh
                    # seed during the advance, usable by THIS event)
                    ns["arrive_ts"] = jnp.where(
                        adv, slots["arrive_ts"] + st.waiting_ms,
                        slots["arrive_ts"])
                    ns["first_ts"] = jnp.where(adv, -1, slots["first_ts"])
                    for key in list(ns):
                        if key.startswith(("done", "absdead", "b0")):
                            ns[key] = jnp.where(
                                adv, jnp.zeros((C,), ns[key].dtype), ns[key])
                else:
                    ns["valid"] = ns["valid"] & ~adv
                pend[f"p{s}"] = ns
                touched[s] = touched[s] | adv
                n_adv = jnp.sum(adv.astype(jnp.int64))
                if s == S - 1:
                    emit_env = {f"ev_{k}": ev["cols"][k] for k in ev["cols"]}
                    for (q, key, t) in referenced:
                        if key in slots:
                            emit_env[key] = slots[key]
                    out_mask, out_cols, n_match = emit_rows(
                        out_mask, out_cols, n_match, adv, 0, emit_env)
                else:
                    values = {key: slots[key] for (q, key, t) in referenced
                              if key in slots and q <= s}
                    if _clocked(states[s + 1]):
                        # the successor's non-occurrence clock starts at THIS
                        # state's established expiry time, not at the event
                        # that surfaced it — host chains timers back-to-back
                        values["arrive_ts"] = (
                            slots["arrive_ts"] + st.waiting_ms).astype(jnp.int64)
                    new_tgt, dropped, inserted = insert(
                        pend[f"p{s+1}"], adv, values,
                        jnp.where(slots["first_ts"] >= 0,
                                  slots["first_ts"], ev_ts),
                        jnp.zeros((C,), jnp.int32))
                    pend[f"p{s+1}"] = new_tgt
                    touched[s + 1] = touched[s + 1] | inserted
                    drops = drops + dropped.astype(jnp.int64)
                if every_end == s:
                    seeds = seeds + n_adv

            for s in [i for i, stx in enumerate(states) if _clocked(stx)]:
                with jax.named_scope("nfa.expire"):
                    expire_state(s)

            def env_for(level: int, ev):
                env = {f"ev_{k}": ev["cols"][k] for k in ev["cols"]}
                env.update({key: pend[f"p{level}"][key]
                            for key in bound_keys_for(level)
                            if key in pend[f"p{level}"]})
                return env

            seed_pred_cache = {}

            def logical_state(s, st, pend, seeds, drops, n_match, out_mask,
                              out_cols, touched, ev, ev_ts, ev_tag, ev_ok,
                              env_for):
                pres = [bi for bi, br in enumerate(st.branches)
                        if not br.is_absent]
                absent_bis = [bi for bi, br in enumerate(st.branches)
                              if br.is_absent]
                slots = pend[f"p{s}"]
                env = env_for(s, ev)
                bm = []
                for br in st.branches:
                    g = ev_ok & (ev_tag == br.stream_idx)
                    p_ = jnp.ones((C,), jnp.bool_) if br.predicate is None \
                        else jnp.broadcast_to(br.predicate(env), (C,))
                    bm.append(slots["valid"] & g & p_)
                if absent_bis:
                    ymatch = jnp.zeros((C,), jnp.bool_)
                    for bi in absent_bis:
                        ymatch = ymatch | bm[bi]
                    ns = dict(slots)
                    if s == 0 and st.waiting_ms is not None:
                        # start-state `X and/or not Y for t`: the forbidden
                        # event RESTARTS the wait (host keeps start states
                        # live; LogicalAbsentPatternTestCase
                        # testQueryAbsent8_2/10); bindings are kept
                        ns["arrive_ts"] = jnp.where(
                            ymatch, ev_ts, slots["arrive_ts"])
                    elif st.logical_type == "or":
                        # `X or not Y [for t]`: Y kills only the absent
                        # ALTERNATIVE — the present side can still match
                        # (testQueryAbsent15)
                        for bi in absent_bis:
                            ns[f"absdead{bi}"] = ns[f"absdead{bi}"] | bm[bi]
                    else:
                        # `X and not Y`: Y's arrival kills the partial
                        ns["valid"] = ns["valid"] & ~ymatch
                    pend[f"p{s}"] = ns
                    touched[s] = touched[s] | ymatch
                    bm = [m & ~ymatch for m in bm]
                    slots = pend[f"p{s}"]

                def side_bind(values, bi, mask, into=None):
                    """Masked bind of branch bi's event columns into values."""
                    br = st.branches[bi]
                    sid = self.compiled.alias_defs[br.alias].id
                    for (q, key, t) in referenced:
                        if q == s and key.startswith(f"b{s}x{bi}_"):
                            base = into[key] if into is not None else \
                                jnp.zeros((C,), _JNP[t])
                            if key == f"b{s}x{bi}__set":
                                values[key] = mask | base
                                continue
                            attr = key[len(f"b{s}x{bi}_"):]
                            mk = self.merged.col_key(sid, attr)
                            values[key] = jnp.where(
                                mask, ev["cols"][mk].astype(_JNP[t]), base)

                def rearm0(ns, advance):
                    """Reseed a pre-placed start slot in place (host places a
                    fresh seed during the scope-completion advance)."""
                    if "arrive_ts" in ns:
                        ns["arrive_ts"] = jnp.where(
                            advance, ev_ts, ns["arrive_ts"])
                    ns["first_ts"] = jnp.where(advance, -1, ns["first_ts"])
                    for key in list(ns):
                        if key.startswith(("done", "absdead", "b0")):
                            ns[key] = jnp.where(
                                advance, jnp.zeros((C,), ns[key].dtype),
                                ns[key])

                if st.logical_type == "and" and not absent_bis:
                    # both sides must arrive (any order) — and ONE event may
                    # satisfy both (reference LogicalPatternTestCase
                    # testQuery5: the same IBM event binds e2 and e3)
                    m0 = bm[0]
                    m1 = bm[1]
                    ns = dict(slots)
                    for bi, ap in ((0, m0), (1, m1)):
                        ns[f"done{bi}"] = ns[f"done{bi}"] | ap
                        side_bind(ns, bi, ap, into=ns)
                    complete = ns["valid"] & ns["done0"] & ns["done1"]
                    ns["valid"] = ns["valid"] & ~complete
                    touched[s] = touched[s] | m0 | m1
                    pend[f"p{s}"] = ns
                    advance, adv_src = complete, ns
                    values = {key: ns[key] for (q, key, t) in referenced
                              if key in ns and q <= s}
                elif st.logical_type == "and" and st.waiting_ms is not None:
                    # `X and not Y for t`: X binds and waits for the
                    # established non-occurrence (host: the timer decides
                    # later) — unless already established, then X advances
                    # immediately
                    bi0 = pres[0]
                    m0 = bm[bi0]
                    estab_now = slots["valid"] & (slots["arrive_ts"] >= 0) & \
                        (ev_ts >= slots["arrive_ts"] + st.waiting_ms)
                    advance = m0 & estab_now
                    ns = dict(slots)
                    ns[f"done{bi0}"] = ns[f"done{bi0}"] | m0
                    side_bind(ns, bi0, m0, into=ns)
                    touched[s] = touched[s] | m0
                    adv_src = dict(ns)          # post-bind, pre-reset
                    values = {key: adv_src[key] for (q, key, t) in referenced
                              if key in adv_src and q <= s}
                    if s == 0 and always_seed:
                        rearm0(ns, advance)
                    else:
                        ns["valid"] = ns["valid"] & ~advance
                    pend[f"p{s}"] = ns
                else:
                    # OR — or `X and not Y` (present match advances)
                    m0 = bm[pres[0]]
                    m1 = (bm[pres[1]] & ~m0) if len(pres) > 1 \
                        else jnp.zeros((C,), jnp.bool_)
                    advance = m0 | m1
                    touched[s] = touched[s] | advance
                    ns = dict(slots)
                    if s == 0 and always_seed and absent_bis:
                        rearm0(ns, advance)
                    else:
                        ns["valid"] = ns["valid"] & ~advance
                    pend[f"p{s}"] = ns
                    adv_src = slots
                    values = {key: slots[key] for (q, key, t) in referenced
                              if key in slots and q < s}
                    side_bind(values, pres[0], m0)
                    if len(pres) > 1:
                        side_bind(values, pres[1], m1)

                first_ts_new = jnp.where(adv_src["first_ts"] >= 0,
                                         adv_src["first_ts"], ev_ts)
                n_adv = jnp.sum(advance.astype(jnp.int64))
                if s == S - 1:
                    emit_env = {f"ev_{k}": ev["cols"][k] for k in ev["cols"]}
                    for (q, key, t) in referenced:
                        if key in values:
                            emit_env[key] = values[key]
                        elif key in adv_src:
                            emit_env[key] = adv_src[key]
                    out_mask, out_cols, n_match = emit_rows(
                        out_mask, out_cols, n_match, advance, 0, emit_env)
                else:
                    if _clocked(states[s + 1]):
                        values["arrive_ts"] = jnp.broadcast_to(
                            ev_ts, (C,)).astype(jnp.int64)
                    new_tgt, dropped, inserted = insert(
                        pend[f"p{s+1}"], advance, values, first_ts_new,
                        jnp.zeros((C,), jnp.int32))
                    pend[f"p{s+1}"] = new_tgt
                    touched[s + 1] = touched[s + 1] | inserted
                    drops = drops + dropped.astype(jnp.int64)
                if every_end == s:
                    seeds = seeds + n_adv

                # ---- eligible candidates from a min-reached PREV count
                # (host shares the partial into this state's pending via
                # _make_eligible; immediate-advance shapes only — gated at
                # compile time)
                if s > 0 and states[s - 1].kind == "count" and \
                        st.waiting_ms is None:
                    prev = pend[f"p{s-1}"]
                    env_p = env_for(s - 1, ev)
                    elig = prev["valid"] & (
                        prev["count"] >= states[s - 1].min_count)
                    bmp = []
                    for br in st.branches:
                        g = ev_ok & (ev_tag == br.stream_idx)
                        p_ = jnp.ones((C,), jnp.bool_) if br.predicate is None \
                            else jnp.broadcast_to(br.predicate(env_p), (C,))
                        bmp.append(elig & g & p_)
                    if absent_bis:
                        # `X and not Y`: Y kills the shared partial
                        killp = jnp.zeros((C,), jnp.bool_)
                        for bi in absent_bis:
                            killp = killp | bmp[bi]
                        np1 = dict(prev)
                        np1["valid"] = np1["valid"] & ~killp
                        pend[f"p{s-1}"] = np1
                        touched[s - 1] = touched[s - 1] | killp
                        bmp = [m & ~killp for m in bmp]
                        prev = np1
                    m0p = bmp[pres[0]]
                    m1p = (bmp[pres[1]] & ~m0p) if len(pres) > 1 \
                        else jnp.zeros((C,), jnp.bool_)
                    advp = m0p | m1p
                    touched[s - 1] = touched[s - 1] | advp
                    np2 = dict(pend[f"p{s-1}"])
                    np2["valid"] = np2["valid"] & ~advp
                    pend[f"p{s-1}"] = np2
                    valuesp = {key: prev[key] for (q, key, t) in referenced
                               if key in prev and q < s}
                    side_bind(valuesp, pres[0], m0p)
                    if len(pres) > 1:
                        side_bind(valuesp, pres[1], m1p)
                    first_p = jnp.where(prev["first_ts"] >= 0,
                                        prev["first_ts"], ev_ts)
                    if s == S - 1:
                        emit_env = {f"ev_{k}": ev["cols"][k]
                                    for k in ev["cols"]}
                        for (q, key, t) in referenced:
                            if key in valuesp:
                                emit_env[key] = valuesp[key]
                            elif key in prev:
                                emit_env[key] = prev[key]
                        out_mask, out_cols, n_match = emit_rows(
                            out_mask, out_cols, n_match, advp, 1, emit_env)
                    else:
                        if _clocked(states[s + 1]):
                            valuesp["arrive_ts"] = jnp.broadcast_to(
                                ev_ts, (C,)).astype(jnp.int64)
                        new_tgt, dropped, inserted = insert(
                            pend[f"p{s+1}"], advp, valuesp, first_p,
                            jnp.zeros((C,), jnp.int32))
                        pend[f"p{s+1}"] = new_tgt
                        touched[s + 1] = touched[s + 1] | inserted
                        drops = drops + dropped.astype(jnp.int64)

                # ---- seeding at a logical state 0 (absent-bearing logicals
                # are PRE-seeded at init and re-armed in place instead)
                if s == 0 and not absent_bis:
                    env0 = {f"ev_{k}": ev["cols"][k] for k in ev["cols"]}
                    # AND seeds linger half-bound, so `every` must NOT seed on
                    # each event (host keeps ONE seed, rebinding sides, until
                    # completion replenishes) — gate on the seed counter; OR
                    # consumes its seed immediately, so always_seed is safe
                    is_and0 = st.logical_type == "and"
                    seeds_ok = jnp.array(True) if (always_seed and not is_and0) \
                        else seeds0 > 0
                    cans = {}
                    taken = jnp.asarray(False)
                    for bi in pres:
                        br = st.branches[bi]
                        g0 = ev_ok & (ev_tag == br.stream_idx)
                        p0 = jnp.asarray(True) if br.predicate is None \
                            else jnp.asarray(br.predicate(env0))
                        if st.logical_type == "and":
                            c = g0 & p0         # one event may bind BOTH sides
                        else:
                            c = g0 & p0 & ~taken    # OR: first side wins
                        taken = taken | c
                        cans[bi] = c & seeds_ok
                    can_any = taken & seeds_ok
                    if st.logical_type == "and":
                        seed_vals = {}
                        for bi in pres:
                            seed_vals[f"done{bi}"] = jnp.broadcast_to(
                                cans[bi], (C,))
                            side_bind(seed_vals, bi, cans[bi])
                        # one event satisfying BOTH sides completes the state
                        # on the spot (matching the host path) — a half-done
                        # seed would otherwise sit complete in p0 until the
                        # next event, or forever if none arrives
                        seed_done = can_any
                        for bi in pres:
                            seed_done = seed_done & cans[bi]
                        ins_pend = can_any & ~seed_done
                        if S == 1:
                            ins0 = jnp.zeros((C,), jnp.bool_).at[0].set(
                                seed_done)
                            emit_env = {f"ev_{k}": ev["cols"][k]
                                        for k in ev["cols"]}
                            for (q, key, t) in referenced:
                                if q == 0:
                                    emit_env[key] = seed_vals.get(
                                        key, jnp.zeros((C,), _JNP[t]))
                            out_mask, out_cols, n_match = emit_rows(
                                out_mask, out_cols, n_match, ins0, 0,
                                emit_env)
                        else:
                            cvals = {key: seed_vals[key]
                                     for key in seed_vals
                                     if not key.startswith("done")}
                            if _clocked(states[1]):
                                cvals["arrive_ts"] = jnp.broadcast_to(
                                    ev_ts, (C,)).astype(jnp.int64)
                            newc, droppedc, insertedc = insert_seed(
                                pend["p1"], seed_done, cvals,
                                jnp.broadcast_to(ev_ts, (C,)),
                                jnp.zeros((C,), jnp.int32))
                            pend["p1"] = newc
                            touched[1] = touched[1] | insertedc
                            drops = drops + droppedc.astype(jnp.int64)
                        new0, dropped, inserted = insert_seed(
                            pend["p0"], ins_pend, seed_vals,
                            jnp.broadcast_to(ev_ts, (C,)))
                        pend["p0"] = new0
                        touched[0] = touched[0] | inserted
                        drops = drops + dropped.astype(jnp.int64)
                        if every_end == 0:
                            # same-event scope completion replenishes `every`
                            seeds = seeds + seed_done.astype(jnp.int64)
                    else:    # OR seed completes the state immediately
                        seed_vals = {key: jnp.zeros((C,), _JNP[t])
                                     for (q, key, t) in referenced if q == 0}
                        for bi in pres:
                            side_bind(seed_vals, bi, cans[bi], into=seed_vals)
                        if S == 1:
                            ins0 = jnp.zeros((C,), jnp.bool_).at[0].set(can_any)
                            emit_env = {f"ev_{k}": ev["cols"][k]
                                        for k in ev["cols"]}
                            for (q, key, t) in referenced:
                                if q == 0:
                                    emit_env[key] = seed_vals[key]
                            out_mask, out_cols, n_match = emit_rows(
                                out_mask, out_cols, n_match, ins0, 0, emit_env)
                        else:
                            if _clocked(states[1]):
                                seed_vals["arrive_ts"] = jnp.broadcast_to(
                                    ev_ts, (C,)).astype(jnp.int64)
                            new1, dropped, inserted = insert_seed(
                                pend["p1"], can_any, seed_vals,
                                jnp.broadcast_to(ev_ts, (C,)),
                                jnp.zeros((C,), jnp.int32))
                            pend["p1"] = new1
                            touched[1] = touched[1] | inserted
                            drops = drops + dropped.astype(jnp.int64)
                    if not always_seed or is_and0:
                        seeds = seeds - can_any.astype(jnp.int64)

                return pend, seeds, drops, n_match, out_mask, out_cols

            # openness of a state-0 count BEFORE this event's extensions,
            # fires, and advances: a slot this event consumes frees its scope
            # seed on the NEXT event only (host reseeds post-event)
            count0_open_pre = None
            if states[0].kind == "count":
                p0pre = pend["p0"]
                count0_open_pre = jnp.any(p0pre["valid"] & ~p0pre["closed"])

            def advance_state(s):
                nonlocal pend, seeds, drops, n_match, out_mask, out_cols
                st = states[s]
                if st.kind == "absent":
                    # expiry ran in the pre-pass; here the forbidden event
                    # kills still-waiting partials — except on a START
                    # state, where it RESTARTS the wait (host keeps start
                    # states live; AbsentPatternTestCase.testQueryAbsent6/8)
                    br = st.branches[0]
                    g = ev_ok & (ev_tag == br.stream_idx)
                    env = env_for(s, ev)
                    p_ = jnp.ones((C,), jnp.bool_) if br.predicate is None \
                        else jnp.broadcast_to(br.predicate(env), (C,))
                    cur = pend[f"p{s}"]
                    kill = cur["valid"] & g & p_
                    ns = dict(cur)
                    if s == 0:
                        ns["arrive_ts"] = jnp.where(
                            kill, ev_ts, cur["arrive_ts"])
                    else:
                        ns["valid"] = ns["valid"] & ~kill
                    pend[f"p{s}"] = ns
                    touched[s] = touched[s] | kill
                    return
                if st.kind == "logical":
                    (pend, seeds, drops, n_match, out_mask, out_cols) = \
                        logical_state(s, st, pend, seeds, drops, n_match,
                                      out_mask, out_cols, touched, ev, ev_ts,
                                      ev_tag, ev_ok, env_for)
                    return
                gate = ev_ok & (ev_tag == st.stream_idx)
                # ---- candidate source A: pending[s]
                slots = pend[f"p{s}"]
                env = env_for(s, ev)
                pred = jnp.ones((C,), jnp.bool_) if st.predicate is None \
                    else jnp.broadcast_to(st.predicate(env), (C,))
                if st.kind == "count":
                    ext = slots["valid"] & ~slots["closed"] & pred & gate
                    first_ext = ext & (slots["count"] == 0)
                    new_slots = dict(slots)
                    new_slots["count"] = slots["count"] + ext.astype(jnp.int32)
                    # a pre-seeded empty partial (zero-min count scope start)
                    # has no first-bind time until its first occurrence
                    new_slots["first_ts"] = jnp.where(
                        first_ext & (slots["first_ts"] < 0), ev_ts,
                        slots["first_ts"])
                    # update bound values for extended slots: last on every
                    # extension, first only on the 0→1 transition (slots
                    # inserted with count=0 have no binding yet — reference
                    # e1[0] refs; CountPatternTestCase.testQuery9)
                    for (q, key, t) in referenced:
                        if q == s and key.startswith(f"b{s}#last#"):
                            attr = key[len(f"b{s}#last#"):]
                            mk = self.merged.col_key(
                                self.compiled.alias_defs[st.alias].id, attr)
                            new_slots[key] = jnp.where(
                                ext, ev["cols"][mk].astype(slots[key].dtype),
                                slots[key])
                        elif q == s and key.startswith(f"b{s}#first#"):
                            attr = key[len(f"b{s}#first#"):]
                            mk = self.merged.col_key(
                                self.compiled.alias_defs[st.alias].id, attr)
                            new_slots[key] = jnp.where(
                                first_ext,
                                ev["cols"][mk].astype(slots[key].dtype),
                                slots[key])
                        elif q == s and key.startswith(f"b{s}#occ"):
                            # e[k]: this extension is occurrence index
                            # `old count` (0-based, predicate-gated)
                            rest = key[len(f"b{s}#occ"):]
                            if rest.endswith("flag") and "#" not in rest:
                                hit = ext & (slots["count"] == int(rest[:-4]))
                                new_slots[key] = slots[key] | hit
                            else:
                                kstr, attr = rest.split("#", 1)
                                hit = ext & (slots["count"] == int(kstr))
                                mk = self.merged.col_key(
                                    self.compiled.alias_defs[st.alias].id,
                                    attr)
                                new_slots[key] = jnp.where(
                                    hit,
                                    ev["cols"][mk].astype(slots[key].dtype),
                                    slots[key])
                        elif q == s and key == _has_flag(s):
                            new_slots[key] = slots[key] | ext
                    if st.max_count != -1:
                        new_slots["closed"] = new_slots["closed"] | (
                            new_slots["count"] >= st.max_count)
                    if s == S - 1:
                        # final count: emit ONCE at min-reach and consume
                        # (host rule; reference CountPatternTestCase
                        # .testQuery13 — further extensions don't re-emit)
                        fire = ext & (new_slots["count"] >= st.min_count)
                        emit_env = {f"ev_{k}": ev["cols"][k]
                                    for k in ev["cols"]}
                        for (q, key, t) in referenced:
                            if key in new_slots:
                                emit_env[key] = new_slots[key]
                        out_mask, out_cols, n_match = emit_rows(
                            out_mask, out_cols, n_match, fire, 0, emit_env)
                        new_slots["valid"] = new_slots["valid"] & ~fire
                        if every_end == s:
                            seeds = seeds + jnp.sum(fire.astype(jnp.int64))
                    pend[f"p{s}"] = new_slots
                    touched[s] = touched[s] | ext
                else:
                    # stream state: sources = pending[s] and (if prev is count)
                    # its eligible slots; freshly re-placed scope clones are
                    # invisible this event
                    # (emit row, table, candidates); a table nothing ever
                    # inserts into (``table_holds``) is no source
                    sources = []
                    if self.table_holds[s]:
                        cand = slots["valid"] & pred & gate
                        if "fresh" in slots:
                            cand = cand & ~slots["fresh"]
                        sources.append((0, s, cand))
                    if s > 0 and states[s - 1].kind == "count":
                        prev = pend[f"p{s-1}"]
                        env_p = env_for(s - 1, ev)
                        pred_p = jnp.ones((C,), jnp.bool_) if st.predicate is None \
                            else jnp.broadcast_to(st.predicate(env_p), (C,))
                        elig = prev["valid"] & (
                            prev["count"] >= states[s - 1].min_count)
                        sources.append((1, s - 1, elig & pred_p & gate))

                    for (src_i, lvl, matched) in sources:
                        src = pend[f"p{lvl}"]
                        touched[lvl] = touched[lvl] | matched
                        # gather advanced values: all bound cols + new binding
                        values = {}
                        for (q, key, t) in referenced:
                            if key in src and (q < s):
                                values[key] = src[key]
                        sid = self.compiled.alias_defs[st.alias].id
                        for (q, key, t) in referenced:
                            if q == s:
                                attr = key[len(f"b{s}_"):]
                                mk = self.merged.col_key(sid, attr)
                                values[key] = jnp.broadcast_to(
                                    ev["cols"][mk].astype(_JNP[t]), (C,))
                        first_ts_new = jnp.where(
                            src["first_ts"] >= 0, src["first_ts"], ev_ts)
                        # a zero-min FINAL count target completes at ARRIVAL:
                        # the partial is already a match with the count empty
                        # (host rule; reference SequenceTestCase.testQuery3)
                        tgt_final_min0 = (
                            s + 1 == S - 1 and states[S - 1].kind == "count"
                            and states[S - 1].min_count == 0)
                        if s == S - 1 or tgt_final_min0:
                            # emit matches
                            emit_env = {f"ev_{k}": ev["cols"][k]
                                        for k in ev["cols"]}
                            for (q, key, t) in referenced:
                                if key in src:
                                    emit_env[key] = src[key]
                                elif q == s:
                                    emit_env[key] = values[key]
                                elif q == S - 1:   # unreached count: NULL
                                    emit_env[key] = jnp.zeros((C,), _JNP[t])
                            out_mask, out_cols, n_match = emit_rows(
                                out_mask, out_cols, n_match, matched, src_i,
                                emit_env)
                            n_adv = jnp.sum(matched.astype(jnp.int64))
                            if tgt_final_min0 and every_end == S - 1:
                                # arrival at the zero-min final count also
                                # completes an `every` scope ending there —
                                # replenish (the lvl-based site below only
                                # sees source states)
                                seeds = seeds + n_adv
                        else:
                            # a count target starts with 0 occurrences (its own
                            # events arrive later via the extension path); an
                            # absent target's non-occurrence clock starts now
                            if _clocked(states[s + 1]):
                                values["arrive_ts"] = jnp.broadcast_to(
                                    ev_ts, (C,)).astype(jnp.int64)
                            new_tgt, dropped, inserted = insert(
                                pend[f"p{s+1}"], matched, values, first_ts_new,
                                jnp.zeros((C,), jnp.int32))
                            pend[f"p{s+1}"] = new_tgt
                            touched[s + 1] = touched[s + 1] | inserted
                            drops = drops + dropped.astype(jnp.int64)
                            n_adv = jnp.sum(matched.astype(jnp.int64))
                        # kill advanced source slots
                        src_new = dict(pend[f"p{lvl}"])
                        src_new["valid"] = src_new["valid"] & ~matched
                        pend[f"p{lvl}"] = src_new
                        # mid-pattern every: the scope-end advance re-places
                        # a clone at the scope start (pre-scope bindings
                        # kept, scope bindings cleared, fresh until next
                        # event — host _do_reseed/_build_seed/_created)
                        r = states[lvl].reseed_to
                        if r not in (None, 0):
                            cvals = {key: src[key]
                                     for (q, key, t) in referenced
                                     if key in src and q < r}
                            cvals["fresh"] = jnp.ones((C,), jnp.bool_)
                            ts_clone = src["first_ts"] if any(
                                states[q].kind != "absent"
                                for q in range(r)) \
                                else jnp.full((C,), -1, jnp.int64)
                            newr, droppedr, _insr = insert(
                                pend[f"p{r}"], matched, cvals, ts_clone,
                                jnp.zeros((C,), jnp.int32))
                            pend[f"p{r}"] = newr
                            drops = drops + droppedr.astype(jnp.int64)
                        # every-scope completion replenishes seeds; the scope
                        # ends either at this stream state (lvl == s) or at the
                        # count state this advance consumed (lvl == s-1)
                        if every_end == lvl:
                            seeds = seeds + n_adv

                # ---- seeding at state 0 (zero-min count states are seeded
                # by the empty-partial replenish pre-pass instead; their
                # occurrences bind via the extension path)
                if s == 0 and not (st.kind == "count" and st.min_count == 0):
                    env0 = {f"ev_{k}": ev["cols"][k] for k in ev["cols"]}
                    pred0 = True if st.predicate is None else st.predicate(env0)
                    can_seed = gate & jnp.asarray(pred0) & (
                        jnp.array(True) if always_seed else seeds0 > 0)
                    if st.kind == "count":
                        # a count scope re-seeds only when its active
                        # instance closed or advanced, not per event — and a
                        # slot this event consumed frees its seed on the
                        # NEXT event only (host max-reach/advance reseed;
                        # every+<m:n> parity)
                        can_seed = can_seed & ~count0_open_pre
                    # seed advances directly into pending[1] (binding ev) or,
                    # for count state 0, into pending[0] with count=1 — count
                    # state 0 extension handled above won't double-fire because
                    # it ran before this insert in the same event
                    sid = self.compiled.alias_defs[st.alias].id
                    seed_vals = {}
                    for (q, key, t) in referenced:
                        if q == 0:
                            if key == _has_flag(0):
                                # count state 0 seeds with its first
                                # occurrence already bound
                                seed_vals[key] = jnp.ones((C,), jnp.bool_)
                                continue
                            if key.startswith("b0#occ"):
                                # seed binds occurrence 0 only; higher
                                # indexes arrive via the extension path
                                rest = key[len("b0#occ"):]
                                if rest.endswith("flag") and "#" not in rest:
                                    seed_vals[key] = jnp.full(
                                        (C,), rest[:-4] == "0", jnp.bool_)
                                    continue
                                kstr, attr = rest.split("#", 1)
                                if kstr != "0":
                                    seed_vals[key] = jnp.zeros((C,), _JNP[t])
                                    continue
                            elif key.startswith(("b0#first#", "b0#last#")):
                                attr = key.split("#", 2)[2]
                            else:
                                attr = key[len("b0_"):]
                            mk = self.merged.col_key(sid, attr)
                            seed_vals[key] = jnp.broadcast_to(
                                ev["cols"][mk].astype(_JNP[t]), (C,))
                    ins_mask = jnp.zeros((C,), jnp.bool_).at[0].set(can_seed)
                    if st.kind == "count":
                        if S == 1 and st.min_count <= 1:
                            # single count state with min ≤ 1: the seed's
                            # first occurrence already reaches min — emit
                            # once and consume (host min-reach rule)
                            emit_env = {f"ev_{k}": ev["cols"][k]
                                        for k in ev["cols"]}
                            for (q, key, t) in referenced:
                                if q == 0:
                                    emit_env[key] = seed_vals.get(
                                        key, jnp.zeros((C,), _JNP[t]))
                            out_mask, out_cols, n_match = emit_rows(
                                out_mask, out_cols, n_match, ins_mask, 0,
                                emit_env)
                        else:
                            new0, dropped, inserted = insert_seed(
                                pend["p0"], can_seed, seed_vals,
                                jnp.broadcast_to(ev_ts, (C,)),
                                jnp.ones((C,), jnp.int32))
                            pend["p0"] = new0
                            touched[0] = touched[0] | inserted
                            # count 1 may already satisfy min → eligibility
                            # handled as later events arrive
                            drops = drops + dropped.astype(jnp.int64)
                    else:
                        seed_final_min0 = (
                            S == 2 and states[1].kind == "count"
                            and states[1].min_count == 0)
                        if S == 1 or seed_final_min0:
                            # single-state pattern — or a seed arriving at a
                            # zero-min FINAL count (already complete, count
                            # empty): immediate match
                            emit_env = {f"ev_{k}": ev["cols"][k] for k in ev["cols"]}
                            for (q, key, t) in referenced:
                                if q == 0:
                                    emit_env[key] = seed_vals[key]
                                elif q == 1:        # unreached count: NULL
                                    emit_env[key] = jnp.zeros((C,), _JNP[t])
                            out_mask, out_cols, n_match = emit_rows(
                                out_mask, out_cols, n_match, ins_mask, 0,
                                emit_env)
                            if seed_final_min0 and every_end == S - 1:
                                # the seed's arrival-emit completes the
                                # `every` scope ending at the final count
                                seeds = seeds + can_seed.astype(jnp.int64)
                        else:
                            if _clocked(states[1]):
                                seed_vals["arrive_ts"] = jnp.broadcast_to(
                                    ev_ts, (C,)).astype(jnp.int64)
                            new1, dropped, inserted = insert_seed(
                                pend["p1"], can_seed, seed_vals,
                                jnp.broadcast_to(ev_ts, (C,)),
                                jnp.zeros((C,), jnp.int32))
                            pend["p1"] = new1
                            touched[1] = touched[1] | inserted
                            drops = drops + dropped.astype(jnp.int64)
                    if not always_seed:
                        seeds = seeds - can_seed.astype(jnp.int64)

            # states in reverse order, so one event can't advance a partial
            # twice
            for s in range(S - 1, -1, -1):
                with jax.named_scope(f"nfa.state{s}"):
                    advance_state(s)

            # scope clones become visible from the next event on
            for r in self.reseed_targets:
                slots_r = dict(pend[f"p{r}"])
                slots_r["fresh"] = jnp.zeros((C,), jnp.bool_)
                pend[f"p{r}"] = slots_r

            # sequence strictness: untouched partials die on any event
            if is_seq:
                for s in range(S):
                    slots = dict(pend[f"p{s}"])
                    slots["valid"] = slots["valid"] & jnp.where(
                        ev_ok, touched[s], slots["valid"])
                    pend[f"p{s}"] = slots

            new_carry = {"pending": pend, "seeds": seeds, "drops": drops,
                         "matches": n_match}
            rows = sorted(out_mask)     # static: the plan's emit sources

            def grid(by_row, dtype):
                return jnp.stack([by_row[r] for r in rows]) if rows \
                    else jnp.zeros((1, C), dtype)

            ys = {"mask": grid(out_mask, jnp.bool_)}
            for oi, (name, _, t) in enumerate(out_specs):
                ys[name] = grid(out_cols[oi], _JNP[t])
                if out_nulls[oi] is not None:
                    ys[f"null__{name}"] = grid(out_nulls[oi], jnp.bool_)
            return new_carry, ys

        def scan(state, cols, tag, ts, ts_base, nvalid):
            # wire format: int32 ts deltas + per-batch base, prefix validity
            nB = ts.shape[0]
            ts64 = ts_base.astype(jnp.int64) + ts.astype(jnp.int64)
            valid = jnp.arange(nB, dtype=jnp.int32) < nvalid

            def body(carry, xs):
                ev = {"cols": {k: xs[f"c_{k}"] for k in cols},
                      "tag": xs["tag"], "ts": xs["ts"], "valid": xs["valid"]}
                return step_event(carry, ev)

            xs = {f"c_{k}": v for k, v in cols.items()}
            xs.update({"tag": tag, "ts": ts64, "valid": valid})
            with jax.named_scope("nfa.scan"):
                return jax.lax.scan(body, state, xs)

        def hand_out(grids):
            """The lanes' emit grids ``[B, P, R, C]`` -> ``(ys, lost)``:
            the rows packed by what the batch emitted. ONE branch for all
            the lanes stepped together, on one scalar (a branch a lane
            would lower to a select under ``vmap`` and run both packs).
            ``full`` holds every lane's rows whichever pack ran (the packed
            table's, padded out, where that sufficed): under a mesh the
            shards branch apart and the decode reads ``full`` for all."""
            with jax.named_scope("nfa.compact"):
                n, table = pack_rows(grids.pop("mask"), grids)

                def packed():
                    rows = table(B)
                    return rows, {k: jnp.pad(v, ((0, 0), (0, M_full - B)))
                                  for k, v in rows.items()}

                def whole():
                    rows = table(M_full)
                    return {k: v[:, :B] for k, v in rows.items()}, rows

                rows, full = jax.lax.cond(jnp.max(n) <= B, packed, whole)
                lost = jnp.maximum(n.astype(jnp.int64) - M_full, 0)
            return {"n": n, **rows, "full": full}, lost

        def step(state, *feed):
            state, grids = scan(state, *feed)
            ys, lost = hand_out({k: v[:, None] for k, v in grids.items()})
            state["drops"] = state["drops"] + lost[0]
            return state, jax.tree_util.tree_map(lambda x: x[0], ys)

        def stacked_step(state, *feed):
            # the grids stay in the scan's own order, the lane second
            state, grids = jax.vmap(scan, out_axes=(0, 1))(state, *feed)
            ys, lost = hand_out(grids)
            state["drops"] = state["drops"] + lost
            return state, ys

        return {"scan": scan, "step": step, "stacked": stacked_step}[form]

    # -------------------------------------------------------------- execution
    def make_step(self, stacked: bool = False):
        """Public builder for the un-jitted step function ``(state, cols,
        tag, ts, ts_base, nvalid) -> (state, ys)`` in the wire format (int32
        ts deltas + int64 base scalar, validity = prefix ``[0, nvalid)``):
        one lane's, the composable surface of ``__graft_entry__`` (and of
        ``self.step``, the jitted convenience over it), or with
        ``stacked`` the same step over ``[P, ...]``-stacked lanes, which the
        partition runtime jits and ``shard_map``s. Both kernels hand out
        ``ys = {"n", "mask", "j", <col>.., "full": {..}}`` (``decode_rows``).
        The stacked scan step is NOT ``jax.vmap`` of the lane's: it maps the
        scan and packs the rows of all lanes after ONE ``lax.cond`` on the
        largest ``n`` among them (under ``shard_map``, among the shard's),
        where a ``cond`` a lane lowers to a select that runs both packs."""
        return self._make_step("stacked" if stacked else "step")

    def make_scan(self):
        """The scan kernel's first half alone, one lane: ``(state, cols,
        tag, ts, ts_base, nvalid) -> (state, grids)`` with the emit grids
        ``{"mask", <col>..: [B, R, C]}`` the pack reads (event, the emit
        sources the plan uses, candidate). The carried state is whole after
        it but for the ``drops`` of rows past ``full``."""
        return self._make_step("scan")

    def step(self, state, batch: dict):
        return self._step(state, batch["cols"], batch["tag"], batch["ts"],
                          batch["ts_base"], np.int32(batch["count"]))

    # the step output the decode reads first, which ``StepRuntime._fence``
    # fetches: the row count ``n``, 4 bytes a lane, whichever kernel stepped
    fence_key = "n"

    def decode_outputs(self, ys, lane_batch: Optional[int] = None):
        """One step's row table → a :class:`~siddhi_tpu.core.columns.
        ColumnsOut` (string codes stay codes; NULL cells ride as masks).
        Both kernels hand out the same table: ``mask`` and ``j`` (the match
        event's index in its batch) ``[M]`` with a column per output and
        per null mask; rows go out by match event, a match event's rows in
        table order (the scan kernel: source, then candidate; the blocked
        kernel: candidate rank, in its packed table and in its whole
        candidate table ``full`` alike). ``lane_batch`` given, the table is
        lane-stacked ``[P, M]`` and decoded in one pass, lanes in order:
        no loop over lanes. The table's leaves cross to the host in ONE
        ``jax.device_get``: every copy is started before any is waited
        for; whatever else ``ys`` holds stays where it is."""
        from ..core.columns import ColumnsOut
        names = [name for (name, _, _) in self.out_specs]
        keys = ["mask", "j", *names,
                *(f"null__{name}" for name in names
                  if f"null__{name}" in ys)]
        host = dict(zip(keys, jax.device_get([ys[k] for k in keys])))
        mask = host["mask"]
        idx = np.flatnonzero(mask)
        if not idx.size:
            return ColumnsOut.empty(self.out_specs, self.merged.dictionaries)
        j = host["j"].reshape(-1)[idx].astype(np.int64)
        if lane_batch is not None:
            j += (idx // mask.shape[-1]) * lane_batch
        idx = idx[np.argsort(j, kind="stable")]
        cols = {name: host[name].reshape(-1)[idx] for name in names}
        nulls = {name: host[f"null__{name}"].reshape(-1)[idx]
                 for name in names if f"null__{name}" in host}
        return ColumnsOut(None, cols, int(idx.size), self.out_specs,
                          self.merged.dictionaries, nulls or None)


def decode_rows(rt, ys, lane_batch: Optional[int] = None):
    """``_decode`` of both NFA runtimes: one step's outputs as one
    ``ColumnsOut``, through ``decode_outputs`` whichever table is read.
    Both kernels hand out their rows packed into ``M`` a lane and the count
    ``n`` (the fence has fetched it): where no lane emitted more than
    ``M``, the packed table holds every row; else ``full`` is decoded (the
    blocked kernel's whole candidate table, as every batch was before
    PR 34; the scan kernel's table of ``_row_capacity`` rows, which its
    step packs at that size for such a batch alone and else fills with
    the packed table's rows): no row is lost, and none is counted as a
    drop that was not one before. That decode is timed apart
    (``decode_full``): how often it runs is what the packed
    table's size is judged by, and for the scan kernel how often the step
    took the whole pack. A single-state blocked plan has no ``full``."""
    nfa = rt.compiler
    full = ys.get("full")
    if full is None or int(np.max(jax.device_get(ys["n"]))) <= nfa.M:
        return nfa.decode_outputs(ys, lane_batch)
    with rt.measure("decode_full"):
        return nfa.decode_outputs(full, lane_batch)


class DeviceNFARuntime(StepRuntime):
    """The pattern / sequence query's runtime: a ``MergedBatchBuilder`` in
    front of one compiled NFA. Built from a compiler by the bridge
    (``compiler=``), or from app text when used by itself. NFA state carries
    no host-sync bookkeeping, so dispatch N+1 overlaps collect N."""

    def __init__(self, app_or_text=None, slot_capacity: int = 64,
                 batch_capacity: int = 1024, query_index: int = 0,
                 start_time: int = 0, compiler=None):
        if compiler is None:
            from ..compiler import parse as _parse
            app = _parse(app_or_text) if isinstance(app_or_text, str) \
                else app_or_text
            compiler = DeviceNFACompiler(
                app.queries[query_index], dict(app.stream_definitions),
                slot_capacity, batch_capacity)
        self.compiler = compiler
        self.fence_key = compiler.fence_key
        self.builder = MergedBatchBuilder(
            compiler.merged, compiler.B, dict(compiler.stream_defs),
            used_cols=compiler.used_cols)
        # absent-start patterns arm their non-occurrence clock at the
        # runtime start time (host: seed placed at start() with the playback
        # clock's current value)
        self.state = compiler.init_state(start_time)

    def send(self, stream_id: str, row: list, timestamp: int) -> None:
        self.builder.append(stream_id, row, timestamp)
        self._maybe_flush()

    def dispatch(self, batch: dict):
        self.state, ys = self.compiler.step(self.state, batch)
        return ys

    def _decode(self, ys):
        return decode_rows(self, ys)

    @property
    def match_count(self) -> int:
        return int(jax.device_get(self.state["matches"]))

    @property
    def drop_count(self) -> int:
        return int(jax.device_get(self.state["drops"]))

    def snapshot_state(self):
        from .batch import device_state_snapshot
        return device_state_snapshot(self.state, self.compiler.merged)

    def restore_state(self, state) -> None:
        from .batch import device_state_restore
        self.state = device_state_restore(state, self.compiler.merged)
