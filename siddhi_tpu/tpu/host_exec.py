"""Columnar host execution engine: the compile plans, run as plain NumPy.

The middle execution tier (device ≻ **columnar host** ≻ scalar interpreter).
TiLT and CORE (PAPERS.md) both show stream/CEP queries compiled to batched
vectorized kernels beating per-tuple interpreters by an order of magnitude on
CPUs — this module is that path for this engine. It executes the SAME lowered
plans the device compiler produces (``CompiledStreamQuery`` specs/filters,
``DeviceNFACompiler`` blocked-NFA states/predicates — both compiled with
``backend="numpy"``) over SoA micro-batches, eagerly, with *dynamic* shapes:

- no padding, no static slot capacities: tables hold exactly the live
  partials, grids are ``[events, live_candidates]`` — on typical workloads
  orders of magnitude smaller than the device's padded ``[B, C+K]`` grids,
  which is what makes the NumPy path fast enough to matter on a CPU;
- no capacity drops: unlike the device kernels (bounded tables, drop
  counters), the host engine matches the scalar interpreter **exactly** —
  it is the engine behind DeviceGuard's quarantine/shadow-replay fallback,
  where parity with the interpreter is the contract;
- f64/i64 numeric policy (``backend.NP_HOST``) — interpreter-exact, no f32
  tolerance band.

Null policy (shared with the device path, documented in PARITY.md): columns
encode ``None`` as 0/code-0. Queries relying on SQL-ish null comparison
semantics keep the scalar interpreter.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import numpy as np

from ..query_api.definition import DataType
from .backend import NP_HOST, avalanche
from .expr_compile import DeviceCompileError

_TS_NEG = -(2 ** 62)


# ---------------------------------------------------------------------------
# shared small kernels (numpy ports of the query_compile helpers)
# ---------------------------------------------------------------------------

def _np_ident(dtype, is_min: bool):
    from .backend import reduce_identity
    return reduce_identity(np.dtype(dtype), is_min, np)


def _range_reduce_np(z: np.ndarray, lo: np.ndarray, j: np.ndarray,
                     is_min: bool) -> np.ndarray:
    """min/max of ``z`` over inclusive ranges [lo_b, j_b] — the same
    log-doubling sparse table as ``query_compile._range_reduce``, eager."""
    M = z.shape[0]
    if M == 0 or j.size == 0:
        return np.empty((j.size,), z.dtype)
    red = np.minimum if is_min else np.maximum
    ident = _np_ident(z.dtype, is_min)
    tables = [z]
    span = 1
    while span < M:
        prev = tables[-1]
        shifted = np.concatenate(
            [np.full((min(span, M),), ident, z.dtype), prev[:M - span]])
        tables.append(red(prev, shifted))
        span *= 2
    T = np.stack(tables)                               # [KK, M]
    m = np.maximum(j - lo + 1, 1).astype(np.int64)
    kk = np.frexp(m.astype(np.float64))[1] - 1         # floor(log2 m), exact
    p2 = (np.int64(1) << kk.astype(np.int64))
    return red(T[kk, j], T[kk, np.clip(lo + p2 - 1, 0, M - 1)])


def _segment_starts(sorted_gid: np.ndarray) -> np.ndarray:
    if sorted_gid.size == 0:
        return np.zeros((0,), bool)
    return np.r_[True, sorted_gid[1:] != sorted_gid[:-1]]


# ---------------------------------------------------------------------------
# row staging: host rows → SoA micro-batch (dynamic length, host dtypes)
# ---------------------------------------------------------------------------

class HostRowStager:
    """Accumulates raw rows; emits a dynamic-length SoA batch in host dtypes.

    The host analog of ``MergedBatchBuilder``: same dictionary encoding (per
    distinct value via ``StringDictionary.encode_array``), no padding, no ts
    delta compression (absolute int64 — there is no wire to save). Handles
    both the single-stream and merged multi-stream (tagged) layouts.
    """

    def __init__(self, schema, stream_defs: dict, capacity: int,
                 used_cols: Optional[set] = None):
        # schema: MergedBatchSchema (has .stream_index/.columns/.col_key) or
        # BatchSchema (single stream, bare attribute keys)
        self.schema = schema
        self.stream_defs = stream_defs
        self.capacity = capacity
        self.used_cols = used_cols
        self.merged = hasattr(schema, "stream_index")
        self._rows: list = []          # (stream_idx, row)
        self._ts: list = []
        # zero-object staging: whole column chunks (si, cols, ts, n) — the
        # stager holds EITHER row entries OR column chunks, never both
        # (mixing materializes in arrival order, see append_columns /
        # ensure_rows), so guards that walk _rows stay correct
        self._col_chunks: list = []
        self._cn = 0
        if self.merged:
            self._sids = list(schema.stream_index)

    def __len__(self) -> int:
        return len(self._ts) + self._cn

    @property
    def full(self) -> bool:
        return len(self) >= self.capacity

    def append(self, stream_id: str, row: list, ts: int) -> None:
        if self._col_chunks:
            self.ensure_rows()
        si = self.schema.stream_index[stream_id] if self.merged else 0
        self._rows.append((si, row))
        self._ts.append(ts)

    def append_events(self, stream_id: str, events: list) -> None:
        """Bulk-append StreamEvents (chunked junction delivery)."""
        if self._col_chunks:
            self.ensure_rows()
        si = self.schema.stream_index[stream_id] if self.merged else 0
        self._rows.extend((si, ev.data) for ev in events)
        self._ts.extend(ev.timestamp for ev in events)

    def append_rows(self, stream_id: str, rows: list, timestamps) -> None:
        """Bulk-append raw rows (zero-wrap ``deliver_rows`` path)."""
        if self._col_chunks:
            self.ensure_rows()
        si = self.schema.stream_index[stream_id] if self.merged else 0
        self._rows.extend((si, r) for r in rows)
        self._ts.extend(timestamps)

    def append_columns(self, stream_id: str, cols: dict, ts) -> None:
        """Zero-object staging: one columnar chunk ({attr: numpy array |
        DictColumn}, int64 ts) goes in whole — no per-row Python objects.
        A chunk arriving while per-row entries are staged materializes
        immediately so arrival order is preserved."""
        ts = np.asarray(ts, dtype=np.int64)
        n = int(ts.shape[0])
        if n == 0:
            return
        si = self.schema.stream_index[stream_id] if self.merged else 0
        if self._rows:
            from ..core.columns import columns_to_rows
            d = self.stream_defs[stream_id] if self.merged \
                else self.schema.definition
            self._rows.extend(
                (si, r) for r in columns_to_rows(
                    cols, d.attribute_names, n))
            self._ts.extend(ts.tolist())
            return
        self._col_chunks.append((si, cols, ts, n))
        self._cn += n

    def ensure_rows(self) -> None:
        """Materialize pending column chunks into per-row entries (guards /
        snapshots / mixed staging need the row view; NOT the hot path)."""
        if not self._col_chunks:
            return
        from ..core.columns import columns_to_rows
        chunks, self._col_chunks = self._col_chunks, []
        self._cn = 0
        sids = self._sids if self.merged else [self.schema.definition.id]
        pre_rows: list = []
        pre_ts: list = []
        for si, cols, ts, n in chunks:
            d = self.stream_defs[sids[si]] if self.merged \
                else self.schema.definition
            pre_rows.extend(
                (si, r) for r in columns_to_rows(cols, d.attribute_names, n))
            pre_ts.extend(ts.tolist())
        # chunks only accumulate while no row entries are staged, so they
        # strictly precede whatever _rows currently holds
        self._rows = pre_rows + self._rows
        self._ts = pre_ts + self._ts

    def shadow(self) -> dict:
        """Cheap pre-emit capture for guards (pointer copies only); feed to
        :meth:`shadow_rows` to materialize on the failure path."""
        if self._col_chunks:
            return {"chunks": list(self._col_chunks)}
        return {"rows": list(self._rows), "ts": list(self._ts)}

    def shadow_rows(self, shadow: dict) -> tuple[list, list]:
        """(rows as (si, row), ts) of a :meth:`shadow` capture."""
        if "chunks" not in shadow:
            return shadow.get("rows", []), shadow.get("ts", [])
        from ..core.columns import columns_to_rows
        sids = self._sids if self.merged else [self.schema.definition.id]
        rows: list = []
        tss: list = []
        for si, cols, ts, n in shadow["chunks"]:
            d = self.stream_defs[sids[si]] if self.merged \
                else self.schema.definition
            rows.extend(
                (si, r) for r in columns_to_rows(cols, d.attribute_names, n))
            tss.extend(ts.tolist())
        return rows, tss

    def clear(self) -> None:
        self._rows = []
        self._ts = []
        self._col_chunks = []
        self._cn = 0

    def _col_key(self, si: int, attr: str) -> str:
        return f"s{si}_{attr}" if self.merged else attr

    def _dictionary(self, si: int, attr: str):
        return self.schema.dictionaries.get(self._col_key(si, attr))

    def _convert_column(self, col, si: int, attr, n: int) -> np.ndarray:
        """One staged chunk column → the engine's host dtype (strings
        dictionary-encode: cached code translation for DictColumns, one
        vectorized encode for value arrays)."""
        from ..core.columns import DictColumn, encode_dict_column
        if attr.type == DataType.STRING:
            dic = self._dictionary(si, attr.name)
            if isinstance(col, DictColumn):
                enc = encode_dict_column(col, dic)
            else:
                arr = col if isinstance(col, np.ndarray) \
                    else np.asarray(col, dtype=object)
                enc = dic.encode_array(arr)
            out = enc.astype(np.int32, copy=False)
        else:
            arr = np.asarray(col)
            if arr.dtype == object:
                dt = NP_HOST[attr.type]
                arr = np.asarray([0 if v is None else v for v in arr],
                                 dtype=dt)
            out = arr.astype(NP_HOST[attr.type], copy=False)
        if out.shape[0] != n:
            raise ValueError(
                f"column '{attr.name}': {out.shape[0]} values in a chunk "
                f"of {n} rows")
        return out

    def _emit_columns(self) -> dict:
        """Columnar fast-path emit: staged chunks concatenate straight into
        the SoA micro-batch — zero per-row Python, and ONE dtype/dictionary
        conversion per column however many (fine-grained) chunks staged
        (fleet multiplexed ingress stages hundreds of 16-row chunks per
        window — per-chunk conversion there was the measured cost). Chunks
        reset only on success (guards re-drive a failed emit)."""
        from ..core.columns import DictColumn
        chunks = self._col_chunks
        n = self._cn
        sids = self._sids if self.merged else [self.schema.definition.id]
        ts = np.empty(n, dtype=np.int64)
        tag = np.zeros(n, dtype=np.int8)
        # pass 1: gather per-key raw pieces (+ offsets) and stamp ts/tag
        pieces: dict[str, list] = {}
        attr_of: dict[str, tuple] = {}
        off = 0
        for si, ccols, cts, cn in chunks:
            ts[off:off + cn] = cts
            if si:
                tag[off:off + cn] = si
            d = self.stream_defs[sids[si]] if self.merged \
                else self.schema.definition
            for a in d.attributes:
                key = self._col_key(si, a.name)
                if self.used_cols is not None and key not in self.used_cols:
                    continue
                col = ccols[a.name]
                cl = len(col) if isinstance(col, DictColumn) \
                    else np.shape(col)[0] if isinstance(col, np.ndarray) \
                    else len(col)
                if cl != cn:
                    raise ValueError(
                        f"column '{a.name}': {cl} values in a chunk of "
                        f"{cn} rows")
                pieces.setdefault(key, []).append((off, cn, col))
                attr_of[key] = (si, a)
            off += cn
        # pass 2: one conversion per key — concat raw pieces first when
        # they share a representation, then encode/astype once
        cols: dict[str, np.ndarray] = {}
        for key, parts in pieces.items():
            si, a = attr_of[key]
            covered = sum(cn for _o, cn, _c in parts)
            raw = [c for _o, _cn, c in parts]
            if covered == n:
                conv = self._convert_pieces(raw, si, a, n)
                if conv is not None:
                    cols[key] = conv
                    continue
            # sparse (multi-stream: this stream absent from some chunks)
            # or mixed representations: piecewise into a zeroed column
            full = None
            for o, cn, c in parts:
                conv = self._convert_column(c, si, a, cn)
                if full is None:
                    full = cols[key] = np.zeros(n, conv.dtype)
                full[o:o + cn] = conv
        # streams absent from every chunk still get zero-filled columns
        # (same contract as the row path: predicates read every used column)
        for si, sid in enumerate(sids):
            d = self.stream_defs[sid] if self.merged \
                else self.schema.definition
            for a in d.attributes:
                key = self._col_key(si, a.name)
                if self.used_cols is not None and key not in self.used_cols:
                    continue
                if key not in cols:
                    cols[key] = np.zeros(n, NP_HOST[a.type])
        out = {"cols": cols, "tag": tag, "ts": ts, "count": n,
               "last_ts": int(ts[-1]) if n else 0}
        self._col_chunks = []
        self._cn = 0
        return out

    def _convert_pieces(self, raw: list, si: int, attr,
                        n: int) -> Optional[np.ndarray]:
        """Contiguous same-representation pieces → ONE converted column;
        None when representations mix (caller converts piecewise)."""
        from ..core.columns import DictColumn
        first = raw[0]
        if isinstance(first, DictColumn):
            if not all(isinstance(c, DictColumn)
                       and c.values is first.values for c in raw):
                return None
            joined = DictColumn(
                first.codes if len(raw) == 1
                else np.concatenate([c.codes for c in raw]),
                first.values, source=first.source)
            return self._convert_column(joined, si, attr, n)
        if not all(isinstance(c, np.ndarray) and not isinstance(
                c, DictColumn) for c in raw):
            return None
        joined = first if len(raw) == 1 else np.concatenate(raw)
        return self._convert_column(joined, si, attr, n)

    def emit(self) -> dict:
        """→ {"cols": {key: np[n] host-dtype}, "tag": int8[n], "ts": int64[n],
        "count": n, "last_ts": int}. Resets the stager."""
        if self._col_chunks:
            return self._emit_columns()
        n = len(self._ts)
        ts = np.asarray(self._ts, dtype=np.int64)
        tag = np.zeros(n, dtype=np.int8)
        cols: dict[str, np.ndarray] = {}
        sids = self._sids if self.merged else [self.schema.definition.id]
        single = len(sids) == 1
        for si, sid in enumerate(sids):
            d = self.stream_defs[sid] if self.merged else self.schema.definition
            if self.merged and not single:
                idx = np.fromiter((i for i, (s, _) in enumerate(self._rows)
                                   if s == si), dtype=np.int64)
                if si:
                    tag[idx] = si
                rows = [self._rows[i][1] for i in idx]
            else:
                idx = None
                rows = [r for _, r in self._rows]
            # NOTE: a stream with zero rows in this batch still gets its
            # zero-filled columns below — predicates read every used column
            # even when the chunk carried only the OTHER stream's events
            for pos, a in enumerate(d.attributes):
                key = self._col_key(si, a.name)
                if self.used_cols is not None and key not in self.used_cols:
                    continue
                vals = [r[pos] for r in rows]
                if a.type == DataType.STRING:
                    dic = self._dictionary(si, a.name)
                    enc = dic.encode_array(np.asarray(vals, dtype=object)) \
                        if vals else np.zeros(0, np.int32)
                    col_vals = enc.astype(np.int32)
                else:
                    dt = NP_HOST[a.type]
                    col_vals = np.asarray(
                        [0 if v is None else v for v in vals], dtype=dt)
                if idx is None:
                    cols[key] = col_vals
                else:
                    full = cols.get(key)
                    if full is None:
                        full = cols[key] = np.zeros(n, col_vals.dtype)
                    full[idx] = col_vals
        out = {"cols": cols, "tag": tag, "ts": ts, "count": n,
               "last_ts": int(ts[-1]) if n else 0}
        self._rows = []
        self._ts = []
        return out

    def snapshot(self) -> dict:
        self.ensure_rows()      # snapshots carry the row view
        return {"rows": [(s, list(r)) for s, r in self._rows],
                "ts": list(self._ts)}

    def restore(self, snap: dict) -> None:
        self._rows = [(s, list(r)) for s, r in snap["rows"]]
        self._ts = list(snap["ts"])
        self._col_chunks = []
        self._cn = 0


# ---------------------------------------------------------------------------
# vectorized output decode (codes → strings, np scalars → Python scalars)
# ---------------------------------------------------------------------------

def decode_columns(out_specs, cols: dict, dictionaries: dict) -> list[list]:
    """{name: np[n]} → host rows, with dictionary-encoded strings decoded:
    the package's one decode of codes to rows, ``ColumnsOut.rows``."""
    from ..core.columns import ColumnsOut
    n = len(cols[out_specs[0][0]]) if out_specs else 0
    return ColumnsOut(None, cols, n, out_specs, dictionaries).rows()


# ---------------------------------------------------------------------------
# blocked NFA, numpy execution (dynamic shapes, no capacity drops)
# ---------------------------------------------------------------------------

class HostBlockNFA:
    """Eager executor for the blocked NFA plan (``nfa_block.py`` stage
    semantics) with dynamic tables. Stateless w.r.t. lanes: the caller holds
    one ``state`` per lane and passes it through ``step``."""

    def __init__(self, nfa):
        if getattr(nfa, "backend", "jax") != "numpy":
            raise DeviceCompileError("HostBlockNFA needs a numpy-backend plan")
        if not nfa.blocked:
            raise DeviceCompileError(
                "count/logical/absent states have no columnar host kernel")
        self.nfa = nfa
        self.S = nfa.S
        self.states = nfa.states
        self.within = nfa.within
        self.is_seq = nfa.is_sequence
        self.referenced = sorted(nfa.referenced)
        self.out_specs = nfa.out_specs
        self.has_ew = any(st.within_ms is not None for st in nfa.states)
        self.single_stream = len(nfa.merged.stream_ids) == 1
        self._key_dtype = {}
        for (q, key, t) in self.referenced:
            self._key_dtype[key] = NP_HOST[t]
        # merged column each binding key reads from, resolved once
        from .nfa import _NFAResolver
        res = _NFAResolver(nfa, None)
        self._bind_src = {key: res._bound_to_merged(key)
                          for (q, key, t) in self.referenced}
        # bindings carried by a partial AT state s live in TWO dtype-grouped
        # 2-D slabs ([rows, m] float64 + int64) instead of per-key arrays —
        # concat/compress/gather are O(1) numpy calls per stage rather than
        # O(#bindings) (the per-batch call count is what bounds the numpy
        # path, not element throughput). Precomputed per stage:
        #   _stage_rows[s]: key → ('f'|'i', row)
        #   _stage_carry[s]: rows of stage s-1's slabs carried into stage s
        #   _stage_mint[s]:  (group, row, src column) minted at state s-1
        self._stage_rows: list = [None] * self.S
        self._stage_carry: list = [None] * self.S
        self._stage_mint: list = [None] * self.S
        for s in range(1, self.S):
            keys = [key for (q, key, t) in self.referenced if q < s]
            rows = {}
            nf = ni = 0
            for key in keys:
                if np.issubdtype(self._key_dtype[key], np.floating):
                    rows[key] = ("f", nf)
                    nf += 1
                else:
                    rows[key] = ("i", ni)
                    ni += 1
            self._stage_rows[s] = (rows, nf, ni)
            if s > 1:
                prev = self._stage_rows[s - 1][0]
                carry_f = [None] * nf
                carry_i = [None] * ni
                mint = []
                for key, (grp, row) in rows.items():
                    if key in prev:
                        pg, pr = prev[key]
                        (carry_f if grp == "f" else carry_i)[row] = pr
                    else:
                        mint.append((grp, row, self._bind_src[key]))
                self._stage_carry[s] = (carry_f, carry_i)
                self._stage_mint[s] = mint
        # seed bindings (q == 0) for stage 1, and final-state mints for emit
        self._seed_keys = [(key, self._bind_src[key])
                           for (q, key, t) in self.referenced if q == 0]
        self._final_mint = [(key, self._bind_src[key])
                            for (q, key, t) in self.referenced
                            if q == self.S - 1]

    # -- state -----------------------------------------------------------
    def init_state(self) -> dict:
        tables = {}
        for s in range(1, self.S):
            _rows, nf, ni = self._stage_rows[s]
            fields = {"first_ts": np.zeros(0, np.int64),
                      "bf": np.zeros((nf, 0), np.float64),
                      "bi": np.zeros((ni, 0), np.int64)}
            if self.has_ew:
                fields["last_ts"] = np.zeros(0, np.int64)
            tables[f"t{s}"] = fields
        return {"tables": tables, "matches": 0}

    def _slab_env(self, s: int, bf, bi) -> dict:
        """Binding env views over the dtype slabs for stage ``s``'s
        predicate ({key: [1, m] row view})."""
        rows, _nf, _ni = self._stage_rows[s]
        return {key: (bf if grp == "f" else bi)[row][None, :]
                for key, (grp, row) in rows.items()}

    def _seed_slabs(self, cols: dict, idx) -> tuple:
        """Stage-1 binding slabs for seeds created at state 0."""
        _rows, nf, ni = self._stage_rows[1] if self.S > 1 else ({}, 0, 0)
        bf = np.empty((nf, idx.size), np.float64)
        bi = np.empty((ni, idx.size), np.int64)
        rows = self._stage_rows[1][0] if self.S > 1 else {}
        for key, src in self._seed_keys:
            grp, row = rows[key]
            (bf if grp == "f" else bi)[row] = cols[src][idx]
        return bf, bi

    # -- step ------------------------------------------------------------
    def step(self, state: dict, cols: dict, tag: np.ndarray,
             ts: np.ndarray) -> tuple[dict, dict]:
        """One micro-batch through all S stages. Returns (state, matches)
        where matches = {"j": [M] event index, "ts": [M], <out>: [M]}."""
        with np.errstate(all="ignore"):
            return self._step(state, cols, tag, ts)

    def _step(self, state: dict, cols: dict, tag: np.ndarray,
              ts: np.ndarray) -> tuple[dict, dict]:
        n = ts.shape[0]
        # per-tenant override (fleet shared plans): `within N` is a runtime
        # parameter of the shape, carried in the state dict
        within = state.get("within", self.within)
        tables = state["tables"]
        ev_env = {f"ev_{k}": v for k, v in cols.items()}
        jidx = np.arange(n, dtype=np.int64)
        vidx = jidx + 1 if self.single_stream \
            else np.arange(1, n + 1, dtype=np.int64)
        ts_last = int(ts[-1]) if n else _TS_NEG

        def gate_idx(st):
            if self.single_stream:
                return jidx
            return np.nonzero(tag == st.stream_idx)[0]

        # ---- seeds -----------------------------------------------------
        st0 = self.states[0]
        g0 = gate_idx(st0)
        if st0.predicate is not None:
            env0 = {k: v[g0] for k, v in ev_env.items()}
            p0 = np.broadcast_to(np.asarray(st0.predicate(env0)),
                                 (g0.size,)).astype(bool)
            seed = g0[p0]
        else:
            seed = g0

        empty = {"j": np.zeros(0, np.int64), "ts": np.zeros(0, np.int64)}
        for (name, _fn, t) in self.out_specs:
            empty[name] = np.zeros(0, NP_HOST[t])

        if self.S == 1:
            # single-state every-pattern: each matching event IS a match
            if seed.size == 0:
                return state, empty
            emit_env = {k: v[seed] for k, v in ev_env.items()}
            emit_env.update({key: cols[src][seed]
                             for key, src in self._seed_keys})
            out = {"j": seed, "ts": ts[seed]}
            for (name, fn, t) in self.out_specs:
                out[name] = np.broadcast_to(
                    np.asarray(fn(emit_env)), (seed.size,)).astype(NP_HOST[t])
            return {**state, "tables": tables,
                    "matches": state["matches"] + int(seed.size)}, out

        seed_bf, seed_bi = self._seed_slabs(cols, seed)
        cre = {
            "born": seed,
            "vb": vidx[seed] if seed.size else np.zeros(0, np.int64),
            "first_ts": ts[seed],
            "bf": seed_bf, "bi": seed_bi,
        }
        if self.has_ew:
            cre["last_ts"] = ts[seed]

        matches = state["matches"]
        out = empty
        new_tables = {}
        for s in range(1, self.S):
            st = self.states[s]
            tbl = tables[f"t{s}"]
            n_old = tbl["first_ts"].shape[0]
            n_new = cre["born"].shape[0]
            m = n_old + n_new
            if m == 0:
                # no candidates at this state: nothing advances, the empty
                # table carries, and downstream stages only see creations
                new_tables[f"t{s}"] = tbl
                if s < self.S - 1:
                    _rows, nf, ni = self._stage_rows[s + 1]
                    cre = {"born": np.zeros(0, np.int64),
                           "vb": np.zeros(0, np.int64),
                           "first_ts": np.zeros(0, np.int64),
                           "bf": np.zeros((nf, 0), np.float64),
                           "bi": np.zeros((ni, 0), np.int64)}
                    if self.has_ew:
                        cre["last_ts"] = np.zeros(0, np.int64)
                continue
            if n_old:
                cand_born = np.concatenate(
                    [np.full(n_old, -1, np.int64), cre["born"]])
                cand_first = np.concatenate(
                    [tbl["first_ts"], cre["first_ts"]])
                cand_bf = np.concatenate([tbl["bf"], cre["bf"]], axis=1)
                cand_bi = np.concatenate([tbl["bi"], cre["bi"]], axis=1)
                cand_vb = np.concatenate(
                    [np.zeros(n_old, np.int64), cre["vb"]]) \
                    if self.is_seq else None
                cand_last = np.concatenate(
                    [tbl["last_ts"], cre["last_ts"]]) if self.has_ew \
                    else None
            else:
                cand_born = cre["born"]
                cand_first = cre["first_ts"]
                cand_bf, cand_bi = cre["bf"], cre["bi"]
                cand_vb = cre["vb"] if self.is_seq else None
                cand_last = cre.get("last_ts") if self.has_ew else None

            gi = gate_idx(st)                      # global event indices
            g = gi.size
            whole = gi is jidx                     # single-stream fast path
            ts_g = ts if whole else ts[gi]
            if g == 0:
                grid = np.zeros((0, m), bool)
            else:
                if st.predicate is not None:
                    env = {k: v[:, None] for k, v in ev_env.items()} \
                        if whole \
                        else {k: v[gi][:, None] for k, v in ev_env.items()}
                    env.update(self._slab_env(s, cand_bf, cand_bi))
                    grid = np.broadcast_to(
                        np.asarray(st.predicate(env)), (g, m))
                else:
                    grid = np.ones((g, m), bool)
                if within is not None:
                    grid = grid & ((ts_g[:, None] - cand_first[None, :])
                                   <= within)
                if st.within_ms is not None:
                    grid = grid & ((ts_g[:, None] - cand_last[None, :])
                                   <= st.within_ms)
                if self.is_seq:
                    vidx_g = vidx if whole else vidx[gi]
                    grid = grid & (vidx_g[:, None]
                                   == cand_vb[None, :] + 1)
                else:
                    jidx_g = jidx if whole else jidx[gi]
                    grid = grid & (jidx_g[:, None] > cand_born[None, :])

            adv = grid.any(axis=0)                 # [m]
            adv_idx = np.nonzero(adv)[0]
            jstar = gi[grid[:, adv_idx].argmax(axis=0)] \
                if adv_idx.size else np.zeros(0, np.int64)

            if s == self.S - 1:
                if adv_idx.size:
                    emit_env = {k: v[jstar] for k, v in ev_env.items()}
                    rows, _nf, _ni = self._stage_rows[s]
                    for key, (grp, row) in rows.items():
                        emit_env[key] = (cand_bf if grp == "f"
                                         else cand_bi)[row][adv_idx]
                    for key, src in self._final_mint:
                        emit_env[key] = cols[src][jstar]
                    out = {"j": jstar, "ts": ts[jstar]}
                    for (name, fn, t) in self.out_specs:
                        out[name] = np.broadcast_to(
                            np.asarray(fn(emit_env)),
                            (adv_idx.size,)).astype(NP_HOST[t])
                    matches += int(adv_idx.size)
            else:
                carry_f, carry_i = self._stage_carry[s + 1]
                _rows, nf, ni = self._stage_rows[s + 1]
                nbf = np.empty((nf, adv_idx.size), np.float64)
                nbi = np.empty((ni, adv_idx.size), np.int64)
                for row, pr in enumerate(carry_f):
                    if pr is not None:
                        nbf[row] = cand_bf[pr][adv_idx]
                for row, pr in enumerate(carry_i):
                    if pr is not None:
                        nbi[row] = cand_bi[pr][adv_idx]
                for grp, row, src in self._stage_mint[s + 1]:
                    (nbf if grp == "f" else nbi)[row] = cols[src][jstar]
                cre = {
                    "born": jstar,
                    "vb": vidx[jstar] if jstar.size
                    else np.zeros(0, np.int64),
                    "first_ts": cand_first[adv_idx],
                    "bf": nbf, "bi": nbi,
                }
                if self.has_ew:
                    cre["last_ts"] = ts[jstar]

            # survivors (no capacity truncation on the host)
            surv = ~adv
            if within is not None and n:
                surv &= (ts_last - cand_first) <= within
            if st.within_ms is not None and n:
                surv &= (ts_last - cand_last) <= st.within_ms
            if self.is_seq:
                n_valid = vidx[-1] if n else 0
                surv &= cand_vb == n_valid
            sidx = np.nonzero(surv)[0]
            ntbl = {"first_ts": cand_first[sidx],
                    "bf": cand_bf[:, sidx], "bi": cand_bi[:, sidx]}
            if self.has_ew:
                ntbl["last_ts"] = cand_last[sidx]
            new_tables[f"t{s}"] = ntbl

        return {**state, "tables": new_tables, "matches": matches}, out

    # -- snapshots -------------------------------------------------------
    def snapshot_state(self, state: dict) -> dict:
        return {"tables": {k: {f: v.copy() for f, v in t.items()}
                           for k, t in state["tables"].items()},
                "matches": state["matches"],
                "dict": self.nfa.merged.snapshot_dictionaries()}

    def restore_state(self, snap: dict) -> dict:
        self.nfa.merged.restore_dictionaries(snap.get("dict", {}))
        return {"tables": {k: {f: np.asarray(v) for f, v in t.items()}
                           for k, t in snap["tables"].items()},
                "matches": snap["matches"]}


class HostPartitionedNFA:
    """Lane-partitioned blocked NFA on the numpy backend.

    The host analog of ``tpu/partition.py``'s ``PartitionedNFARuntime``:
    per-KEY pattern semantics via the same ``_inject_key_equality`` rewrite,
    keys spread over P lanes (block-diagonal grids — an event only meets
    partials of keys sharing its lane), one dynamic-table state per lane.

    ``workers > 1`` shards the LANE SPACE across a persistent thread pool
    (``@app:host_batch(workers=N)``): each worker steps a contiguous lane
    shard against the shared read-only sorted batch view, per-lane states
    stay exclusively owned, and the emit merges shard outputs in lane order
    before the stable by-event sort — byte-identical to the sequential
    loop, so interpreter parity is preserved per lane. NumPy releases the
    GIL inside its ufunc/sort loops, which is where the step time goes.
    """

    def __init__(self, query, stream_defs: dict, key_attr: str,
                 num_partitions: int = 32, query_index: int = 0,
                 compiler=None, engine=None, workers: int = 1,
                 workers_mode: str = "thread", source=None):
        # a prebuilt (compiler, engine) pair shares ONE compiled plan across
        # runtimes (fleet shared compilation) — the caller already injected
        # the key-equality rewrite; otherwise compile from the query AST
        if compiler is None:
            from .nfa import DeviceNFACompiler
            from .partition import _inject_key_equality
            query = _inject_key_equality(query, key_attr)
            compiler = DeviceNFACompiler(
                query, dict(stream_defs), backend="numpy")
        self.compiler = compiler
        if len(self.compiler.merged.stream_ids) != 1:
            raise DeviceCompileError(
                "partitioned columnar host path covers single-stream "
                "patterns")
        self.engine = engine if engine is not None \
            else HostBlockNFA(self.compiler)
        self.P = max(1, int(num_partitions))
        self.key_attr = key_attr
        sid = self.compiler.merged.stream_ids[0]
        self.key_col = self.compiler.merged.col_key(sid, key_attr)
        d = stream_defs[sid]
        self.key_is_string = d.attribute_type(key_attr) == DataType.STRING
        self.lane_states = [self.engine.init_state() for _ in range(self.P)]
        self.workers = max(1, int(workers))
        self.workers_mode = workers_mode
        # child-rebuild identity for mode='process' (app source + the
        # partition/query position; host_bridge supplies it)
        self._source = source
        self._pool = None
        self._proc_pool = None          # ProcessLanePool, spawned lazily
        # process-backed lane shards (procmesh lanepool): children spawn
        # on the FIRST batch — a deployed-but-idle app must not pay worker
        # boot. Shard count stays `workers` and the merge order is the
        # thread path's, so outputs stay byte-identical.
        self._proc_armed = (self.workers > 1 and workers_mode == "process"
                            and source is not None)
        if self.workers > 1 and not self._proc_armed:
            import os
            from concurrent.futures import ThreadPoolExecutor
            # pool capped at the machine's cores: numpy threads beyond the
            # core count only contend (measured 0.56x at 4 threads on a
            # 2-cpu container) — shard count stays `workers`, so the
            # OUTPUT is identical whatever the pool size
            self._pool = ThreadPoolExecutor(
                max_workers=min(self.workers, os.cpu_count() or 1),
                thread_name_prefix="host-nfa")

    @property
    def match_count(self) -> int:
        if self._proc_pool is not None:
            return self._proc_pool.match_count()
        return sum(st["matches"] for st in self.lane_states)

    def _lane_pool(self):
        """The process lane pool, spawned on first use — seeded with the
        CURRENT parent lane snapshots so a restore that landed before the
        first batch carries over."""
        if self._proc_pool is None:
            from ..procmesh.lanepool import ProcessLanePool
            self._proc_pool = ProcessLanePool(
                self._source, self.P, self.workers,
                [self.engine.snapshot_state(st) for st in self.lane_states])
        return self._proc_pool

    def close(self) -> None:
        """Shut the worker pool down (bridge finalize / app shutdown):
        pool threads are non-daemon and would otherwise outlive the
        runtime. Late flushes after close() fall back to the sequential
        loop — identical outputs either way (the process pool first syncs
        its lane states back so nothing is lost)."""
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)
        ppool, self._proc_pool = self._proc_pool, None
        if ppool is not None:
            try:
                self.lane_states = [self.engine.restore_state(s)
                                    for s in ppool.snapshot_lanes()]
            except Exception:   # noqa: BLE001 — children already gone:
                pass            # parent states stay the last known good
            self._proc_armed = False
            ppool.close()

    def lanes_of(self, key_codes: np.ndarray) -> np.ndarray:
        if self.key_is_string:
            # dictionary codes are dense small ints — direct modulo spreads
            return (key_codes.astype(np.int64) % self.P).astype(np.int32)
        return (avalanche(key_codes.astype(np.int64), np) % self.P) \
            .astype(np.int32)

    def _run_lanes(self, lane_lo: int, lane_hi: int, bounds, cols_sorted,
                   ts_sorted, order) -> list:
        """Step one contiguous lane shard (per-shard stager view: slices of
        the shared sorted batch). Lane states are exclusively owned by
        their shard, so this is thread-safe without locks."""
        outs = []
        for lane in range(lane_lo, lane_hi):
            lo, hi = int(bounds[lane]), int(bounds[lane + 1])
            if lo == hi:
                continue
            lcols = {k: v[lo:hi] for k, v in cols_sorted.items()}
            self.lane_states[lane], m = self.engine.step(
                self.lane_states[lane], lcols, None, ts_sorted[lo:hi])
            if m and m["j"].size:
                # lane-local j → global event position (pre-sort order)
                m = dict(m)
                m["j"] = order[lo + m["j"]]
                outs.append(m)
        return outs

    def process(self, batch: dict) -> tuple[np.ndarray, dict]:
        """One SoA batch (HostRowStager.emit shape) through every lane.
        Returns (global_j, outs) with outs columns ordered by match event."""
        cols, ts = batch["cols"], batch["ts"]
        n = batch["count"]
        if n == 0:
            return np.zeros(0, np.int64), {}
        key_codes = cols[self.key_col]
        lanes = self.lanes_of(key_codes)
        order = np.argsort(lanes, kind="stable")
        lanes_sorted = lanes[order]
        bounds = np.searchsorted(lanes_sorted, np.arange(self.P + 1))
        cols_sorted = {k: v[order] for k, v in cols.items()}
        ts_sorted = ts[order]
        if self._proc_armed and self.P >= 2:
            # process-backed shards: ship each child its slice of the
            # lane-sorted batch; children return shard-relative match
            # positions the pool maps through `order` — same merge, same
            # stable sort, byte-identical outputs
            outs = self._lane_pool().step(bounds, cols_sorted, ts_sorted,
                                          order)
        elif self._pool is not None and self.P >= 2:
            # lane-space sharding: W contiguous shards step concurrently;
            # merge keeps lane order so the by-event sort below is
            # byte-identical to the sequential loop
            W = min(self.workers, self.P)
            cuts = [self.P * w // W for w in range(W + 1)]
            futs = [self._pool.submit(self._run_lanes, cuts[w], cuts[w + 1],
                                      bounds, cols_sorted, ts_sorted, order)
                    for w in range(W)]
            outs = [m for f in futs for m in f.result()]
        else:
            outs = self._run_lanes(0, self.P, bounds, cols_sorted,
                                   ts_sorted, order)
        if not outs:
            return np.zeros(0, np.int64), {}
        j = np.concatenate([m["j"] for m in outs])
        osort = np.argsort(j, kind="stable")
        merged = {k: np.concatenate([m[k] for m in outs])[osort]
                  for k in outs[0]}
        return merged["j"], merged

    def decode(self, outs: dict) -> list[list]:
        if not outs:
            return []
        return decode_columns(self.engine.out_specs, outs,
                              self.compiler.merged.dictionaries)

    # -- snapshots -------------------------------------------------------
    def snapshot_state(self) -> dict:
        if self._proc_pool is not None:
            # the shard owners hold the live states
            return {"lanes": self._proc_pool.snapshot_lanes()}
        return {"lanes": [self.engine.snapshot_state(st)
                          for st in self.lane_states]}

    def restore_state(self, snap: dict) -> None:
        self.lane_states = [self.engine.restore_state(s)
                            for s in snap["lanes"]]
        if self._proc_pool is not None:
            self._proc_pool.restore_lanes(snap["lanes"])


# ---------------------------------------------------------------------------
# compiled single-stream queries, numpy execution
# ---------------------------------------------------------------------------

_HOST_WINDOWS = (None, "length", "time")


class HostStreamQuery:
    """Eager numpy executor over a ``CompiledStreamQuery`` plan (compiled
    with ``backend="numpy"``).

    Coverage (everything else raises ``DeviceCompileError`` → the caller
    keeps that query on the scalar interpreter, per query):
    filters + projections; running sum/count/avg/min/max; group-by (exact
    keys, no hashed buckets → no collision caveat) without a window; sliding
    ``length``/``time``/``externalTime`` windows with sum/count/avg/min/max;
    ``having``. Outputs are CURRENT rows per accepted event, interpreter
    semantics (aggregates reflect the window AFTER the event's arrival and
    expiry at its timestamp)."""

    def __init__(self, compiled):
        if getattr(compiled, "backend", "jax") != "numpy":
            raise DeviceCompileError("HostStreamQuery needs a numpy plan")
        c = compiled
        self.c = c
        if c.window_kind not in _HOST_WINDOWS:
            raise DeviceCompileError(
                f"window '{c.window_kind}' has no columnar host kernel")
        self.has_agg = bool(c.agg_idx)
        if c.sagg_idx:
            raise DeviceCompileError(
                "stdDev keeps the scalar interpreter on the host fast path")
        if c.group_keys and c.window_kind is not None and self.has_agg:
            raise DeviceCompileError(
                "windowed group-by keeps the scalar interpreter on the "
                "host fast path")
        self.windowed = c.window_kind is not None and self.has_agg
        self.N = c.window_n
        self.W = c.window_ms
        self.time_key = c.time_key
        # aggregate lanes: (spec_idx, fn, acc dtype)
        self.flanes = [(i, c.specs[i].fn) for i in c.fagg_idx]
        self.ilanes = [(i, c.specs[i].fn) for i in c.iagg_idx]
        self.mlanes = [(i, c.specs[i].fn, c.specs[i].kind == "min",
                        NP_HOST[c.specs[i].dtype]) for i in c.magg_idx]
        self.out_specs = c.out_specs

    # -- state -----------------------------------------------------------
    def init_state(self) -> dict:
        st: dict[str, Any] = {}
        if self.windowed:
            st["tail_ts"] = np.zeros(0, np.int64)
            st["tail_f"] = np.zeros((len(self.flanes), 0), np.float64)
            st["tail_i"] = np.zeros((len(self.ilanes), 0), np.int64)
            st["tail_m"] = {i: np.zeros(0, dt)
                            for (i, _f, _m, dt) in self.mlanes}
            st["ts_regressions"] = 0
        elif self.c.group_keys:
            st["key_slots"] = {}          # exact key tuple → slot
            st["key_f"] = np.zeros((len(self.flanes), 0), np.float64)
            st["key_i"] = np.zeros((len(self.ilanes), 0), np.int64)
            st["key_cnt"] = np.zeros(0, np.int64)
            st["key_m"] = {i: np.zeros(0, dt)
                           for (i, _f, _m, dt) in self.mlanes}
        elif self.has_agg:
            st["run_f"] = np.zeros(len(self.flanes), np.float64)
            st["run_i"] = np.zeros(len(self.ilanes), np.int64)
            st["run_cnt"] = 0
            st["run_m"] = {i: _np_ident(dt, m)
                           for (i, _f, m, dt) in self.mlanes}
        return st

    # -- step ------------------------------------------------------------
    def step(self, state: dict, cols: dict, ts: np.ndarray
             ) -> tuple[dict, dict]:
        """→ (state, {"ts": [k], "out": {name: [k]}, "j": [k] row index})
        for accepted events."""
        cols = dict(cols)
        cols["__ts__"] = ts
        n = ts.shape[0]
        mask = np.ones(n, bool)
        with np.errstate(all="ignore"):
            for fn in self.c.filter_fns:
                mask &= np.broadcast_to(np.asarray(fn(cols)), (n,))
            k = int(mask.sum())
            if k == n:                       # nothing rejected: no compaction
                ccols, cts = cols, ts
                keep = np.arange(n, dtype=np.int64)
            else:
                keep = np.nonzero(mask)[0]
                ccols = {kk: np.asarray(v)[keep] if np.ndim(v) else v
                         for kk, v in cols.items()}
                cts = ts[keep]
            out: dict[str, np.ndarray] = {}
            specs = self.c.specs
            for i in self.c.value_idx:
                v = specs[i].fn(ccols)
                out[specs[i].name] = np.broadcast_to(
                    np.asarray(v), (k,)).astype(NP_HOST[specs[i].dtype]) \
                    if k else np.zeros(0, NP_HOST[specs[i].dtype])
            if self.has_agg:
                # externalTime reads the window clock from a column; the
                # plain time window uses arrival timestamps
                wts = np.asarray(ccols[self.time_key]).astype(np.int64) \
                    if self.time_key is not None else cts
                state = self._aggregate(state, ccols, cts, wts, k, out)
            hv = self.c.having_fn
            if hv is not None and k:
                # fleet param slots are visible to the having program too
                # (hoisted constants in `having` clauses): compacted per-row
                # param columns merge under the output columns
                hv_env = out
                pkeys = [kk for kk in ccols if kk.startswith("__fleet_p")]
                if pkeys:
                    hv_env = {**{kk: np.asarray(ccols[kk]) for kk in pkeys},
                              **out}
                hmask = np.broadcast_to(np.asarray(hv(hv_env)),
                                        (k,)).astype(bool)
                out = {nm: v[hmask] for nm, v in out.items()}
                cts = cts[hmask]
                keep = keep[hmask]
        return state, {"ts": cts, "out": out, "j": keep}

    # -- aggregation paths ----------------------------------------------
    def _args(self, lanes, ccols, k, dt):
        if not lanes or k == 0:
            return np.zeros((len(lanes), k), dt)
        return np.stack([
            np.broadcast_to(np.asarray(fn(ccols)), (k,)).astype(dt)
            for (_i, fn) in lanes])

    def _aggregate(self, state, ccols, cts, wts, k, out) -> dict:
        c = self.c
        av_f = self._args(self.flanes, ccols, k, np.float64)
        av_i = self._args(self.ilanes, ccols, k, np.int64)
        av_m = {i: (np.broadcast_to(np.asarray(fn(ccols)), (k,)).astype(dt)
                    if k else np.zeros(0, dt))
                for (i, fn, _m, dt) in self.mlanes}

        if self.windowed:
            return self._window_agg(state, av_f, av_i, av_m, wts, k, out)
        if c.group_keys:
            return self._group_agg(state, av_f, av_i, av_m, ccols, k, out)

        # running, no grouping
        sums_f = np.cumsum(av_f, axis=1) + state["run_f"][:, None]
        sums_i = np.cumsum(av_i, axis=1) + state["run_i"][:, None]
        cnts = np.arange(1, k + 1, dtype=np.int64) + state["run_cnt"]
        new = dict(state)
        if k:
            new["run_f"] = sums_f[:, -1].copy()
            new["run_i"] = sums_i[:, -1].copy()
            new["run_cnt"] = int(cnts[-1])
        mins = {}
        new_m = dict(state["run_m"])
        for (i, _fn, is_min, dt) in self.mlanes:
            red = np.minimum if is_min else np.maximum
            acc = red.accumulate(av_m[i]) if k else av_m[i]
            mins[i] = red(acc, state["run_m"][i])
            if k:
                new_m[i] = mins[i][-1]
        new["run_m"] = new_m
        self._materialize(out, sums_f, sums_i, cnts, mins, k)
        return new

    def _window_agg(self, state, av_f, av_i, av_m, wts, k, out) -> dict:
        c = self.c
        # per-tenant overrides (fleet shared plans): window sizes are runtime
        # parameters of the shape, carried in the state dict
        N = state.get("window_n", self.N)
        W = state.get("window_ms", self.W)
        z_ts_raw = np.concatenate([state["tail_ts"], wts])
        z_ts = np.maximum.accumulate(z_ts_raw) if z_ts_raw.size \
            else z_ts_raw
        regress = int(np.sum(z_ts != z_ts_raw))
        z_f = np.concatenate([state["tail_f"], av_f], axis=1)
        z_i = np.concatenate([state["tail_i"], av_i], axis=1)
        z_m = {i: np.concatenate([state["tail_m"][i], av_m[i]])
               for i in state["tail_m"]}
        n_tail = state["tail_ts"].shape[0]
        j = n_tail + np.arange(k, dtype=np.int64)
        if c.window_kind == "length":
            lo = np.maximum(j - N + 1, 0)
            keep_from = max(z_ts.shape[0] - N, 0)
        else:       # sliding time window: live iff ts > now - W
            lo = np.searchsorted(z_ts, z_ts[j] - W, side="right") \
                if k else np.zeros(0, np.int64)
            newest = int(z_ts[-1]) if z_ts.size else _TS_NEG
            keep_from = int(np.searchsorted(z_ts, newest - W,
                                            side="right"))
        cs_f = np.concatenate(
            [np.zeros((z_f.shape[0], 1), np.float64),
             np.cumsum(z_f, axis=1)], axis=1)
        cs_i = np.concatenate(
            [np.zeros((z_i.shape[0], 1), np.int64),
             np.cumsum(z_i, axis=1)], axis=1)
        sums_f = cs_f[:, j + 1] - cs_f[:, lo]
        sums_i = cs_i[:, j + 1] - cs_i[:, lo]
        cnts = (j - lo + 1).astype(np.int64)
        mins = {i: _range_reduce_np(z_m[i], lo, j, is_min)
                for (i, _fn, is_min, dt) in self.mlanes}
        new = dict(state)
        new["tail_ts"] = z_ts[keep_from:]
        new["tail_f"] = z_f[:, keep_from:]
        new["tail_i"] = z_i[:, keep_from:]
        new["tail_m"] = {i: v[keep_from:] for i, v in z_m.items()}
        new["ts_regressions"] = state["ts_regressions"] + regress
        self._materialize(out, sums_f, sums_i, cnts, mins, k)
        return new

    def _group_agg(self, state, av_f, av_i, av_m, ccols, k, out) -> dict:
        c = self.c
        if k == 0:
            self._materialize(out, av_f, av_i,
                              np.zeros(0, np.int64), {}, 0)
            return state
        kcols = [np.asarray(ccols[gk]).astype(np.int64)
                 for gk in c.group_keys]
        stackk = np.stack(kcols, axis=1)              # [k, nk]
        ukeys, gid = np.unique(stackk, axis=0, return_inverse=True)
        # exact key tuple → carried slot (python loop over UNIQUE keys only)
        slots = state["key_slots"]
        lane_f, lane_i = state["key_f"], state["key_i"]
        lane_cnt, lane_m = state["key_cnt"], dict(state["key_m"])
        slot_of = np.empty(len(ukeys), np.int64)
        grow = 0
        for u, row in enumerate(ukeys):
            tup = tuple(int(x) for x in row)
            sl = slots.get(tup)
            if sl is None:
                sl = slots[tup] = len(slots)
                grow += 1
            slot_of[u] = sl
        if grow:
            lane_f = np.concatenate(
                [lane_f, np.zeros((lane_f.shape[0], grow), np.float64)],
                axis=1)
            lane_i = np.concatenate(
                [lane_i, np.zeros((lane_i.shape[0], grow), np.int64)],
                axis=1)
            lane_cnt = np.concatenate([lane_cnt, np.zeros(grow, np.int64)])
            for (i, _fn, is_min, dt) in self.mlanes:
                lane_m[i] = np.concatenate(
                    [lane_m[i], np.full(grow, _np_ident(dt, is_min), dt)])
        ev_slot = slot_of[gid]                         # [k]
        order = np.argsort(ev_slot, kind="stable")
        s_sorted = ev_slot[order]
        starts = _segment_starts(s_sorted)
        seg_id = np.cumsum(starts) - 1
        start_pos = np.nonzero(starts)[0]
        seg_len = np.diff(np.r_[start_pos, k])
        seg_slot = s_sorted[start_pos]

        def seg_cumsum(vals):                          # [A, k] sorted axis
            if vals.shape[0] == 0:
                return vals
            cs = np.cumsum(vals, axis=1)
            base = cs[:, start_pos] - vals[:, start_pos]
            return cs - np.repeat(base, seg_len, axis=1)

        within_f = seg_cumsum(av_f[:, order])
        within_i = seg_cumsum(av_i[:, order])
        ones = np.ones(k, np.int64)
        within_c = seg_cumsum(ones[None, :])[0]
        sums_f = np.empty_like(within_f)
        sums_i = np.empty_like(within_i)
        cnts = np.empty(k, np.int64)
        sums_f[:, order] = within_f + lane_f[:, s_sorted]
        sums_i[:, order] = within_i + lane_i[:, s_sorted]
        cnts[order] = within_c + lane_cnt[s_sorted]
        mins = {}
        for (i, _fn, is_min, dt) in self.mlanes:
            red = np.minimum if is_min else np.maximum
            v_sorted = av_m[i][order]
            accs = np.empty(k, dt)
            for p, ln in zip(start_pos, seg_len):
                accs[p:p + ln] = red(
                    red.accumulate(v_sorted[p:p + ln]),
                    lane_m[i][s_sorted[p]])
            vv = np.empty(k, dt)
            vv[order] = accs
            mins[i] = vv
            upd = lane_m[i].copy()
            ends = start_pos + seg_len - 1
            upd[seg_slot] = accs[ends]
            lane_m[i] = upd
        # carried updates: segment totals land on their slots
        ends = start_pos + seg_len - 1
        lane_f = lane_f.copy()
        lane_i = lane_i.copy()
        lane_cnt = lane_cnt.copy()
        lane_f[:, seg_slot] += within_f[:, ends]
        lane_i[:, seg_slot] += within_i[:, ends]
        lane_cnt[seg_slot] += within_c[ends]
        new = dict(state)
        new["key_f"], new["key_i"] = lane_f, lane_i
        new["key_cnt"], new["key_m"] = lane_cnt, lane_m
        self._materialize(out, sums_f, sums_i, cnts, mins, k)
        return new

    def _materialize(self, out, sums_f, sums_i, cnts, mins, k) -> None:
        specs = self.c.specs
        for li, (i, _fn) in enumerate(self.flanes):
            s = specs[i]
            v = sums_f[li] if k else np.zeros(0, np.float64)
            if s.kind == "avg":
                v = v / np.maximum(cnts, 1)
            out[s.name] = v.astype(NP_HOST[s.dtype])
        for li, (i, _fn) in enumerate(self.ilanes):
            s = specs[i]
            v = sums_i[li] if k else np.zeros(0, np.int64)
            if s.kind == "avg":
                v = v.astype(np.float64) / np.maximum(cnts, 1)
            out[s.name] = v.astype(NP_HOST[s.dtype])
        for i, s in enumerate(specs):
            if s.kind == "count":
                out[s.name] = np.asarray(cnts, np.int64)
        for (i, _fn, _m, dt) in self.mlanes:
            out[specs[i].name] = (mins[i] if k else np.zeros(0, dt)) \
                .astype(NP_HOST[specs[i].dtype])

    def decode(self, res: dict) -> tuple[list[int], list[list]]:
        cols = res["out"]
        rows = decode_columns(self.out_specs, cols,
                              self.c.schema.dictionaries)
        return np.asarray(res["ts"]).tolist(), rows
