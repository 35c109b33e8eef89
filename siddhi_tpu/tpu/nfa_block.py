"""Blocked NFA step: batch-level parallel pattern matching.

The per-event ``lax.scan`` kernel (``nfa.py``) walks a batch one event at a
time: B sequential iterations of some 300 tiny [C]-wide operations, which is
launch latency and no arithmetic (its time on a v5e is a ledger row since
PR 33: ``partitioned-kleene-sat``, 256 lanes under ``vmap``, C 1,408, 320
events deep). This module is the reformulation the north star asks for:
sequential depth **S (number of NFA states)** instead of **B (events per
batch)**. Its step on the chip is a ledger row: ``step.device_ms_per_batch``
of ``pattern-chain8-sat`` (C 1,024, B 2,048) and ``partitioned-chain-sat``
(256 lanes under ``vmap``, C 896, B 320) in ``PERF_LEDGER.jsonl``.

Key insight: for linear chains of *stream* states with ``every`` at the start
(the dominant pattern shape — BASELINE configs #2/#3/#5), advancement is
*consuming* and *deterministic*: a partial at state ``s`` advances on the
FIRST later event matching state ``s``'s predicate, and then leaves the
state. So the number of partials created at any state during a batch is
bounded by ``C + B`` (old slots + one per source partial), NOT exponential,
and the whole batch resolves in S data-parallel stages:

  stage s: grid[j, p] = valid[j] & gate_s[j] & (d[j] <= lim_p)
                         & (j > born_p)  & pred_s(event_j, bindings_p)
           j*(p) = first j with grid[j, p]     (vectorized argmax)
           advanced partials become stage s+1's candidates with
           born' = j*, first_ts' = first_ts (the seed event's, carried),
           bindings' = bindings + what the plan reads of event_{j*}:
           state s's referenced attributes (plus its time under
           element-level ``within``, its rank in a sequence, the outputs'
           columns at the last stage), fetched ONCE a stage
           (``first_hit``) and nothing else of the event.

Each stage is one [B, P] masked grid — exactly the "candidate×event pairs as
one grid per state per batch" shape the verdict names. Sequences add the
strict-continuity constraint ``vidx[j] == vidx[born]+1`` (``vidx`` = running
count of valid events). ``within`` is one int32 compare a cell: each
candidate computes once, in int64 over ``[P]``, the largest wire delta it
admits, ``lim_p = clip(first_ts_p - ts_base + within, -1, 2^31-1)``, and the
grid tests the batch's own int32 deltas against it (``d[j] <= lim_p``, exact
for every delta the wire carries); element-level ``within`` likewise from the
previous element's bind time. No operation of a ``[B, P]`` grid is 64-bit.

What leaves the step: the last stage's candidates that advanced are the
batch's rows. They are packed on the device (``pack_first``, the survivor
pack's tool) to the front of a row table of ``M = B`` rows, the layout the
scan kernel hands out (``mask``, ``j``, a column per output), with their
count ``n``: that table and the count are what the host fetches, ``B x`` a
row's bytes a batch instead of ``P x``. Every one of the ``P`` candidates may
emit in one batch, so no table under ``P`` rows is a bound: the whole
candidate table stays among the outputs (``full``; on the device, it costs no
copy), and a batch whose ``n`` passes ``M`` is decoded from it
(``nfa.decode_rows``). No emitted row is ever dropped or merely counted.

Capacity semantics (documented divergence from the per-event kernel): within
a batch the partial population grows exactly (static shapes, ``sC + B``; an
optional ``creation_cap`` budget compacts each stage to ``[B, C+K]`` for very
long patterns, overflow counted); match tables truncate to C entries at
*batch boundaries* (keep-oldest: old slots first, then in-batch creations in
candidate order, counted in ``drops``). Under capacity pressure this kernel
finds a SUPERSET of the per-event kernel's matches (closer to the host
oracle, which never drops); with no pressure the two are identical.

Scope: every state ``kind == 'stream'``, ``every`` scope = whole pattern
(``always_seed``); patterns and sequences; stream-level ``within`` AND
element-level ``within`` (per-state gap masks against the previous
element's bind time). Count/logical/absent states use the per-event scan
kernel (``nfa.py``).

Reference semantics: ``StreamPreStateProcessor.processAndReturn``
(``query/input/stream/state/StreamPreStateProcessor.java:364-403``), expiry
``isExpired:118``; the blocked formulation is original to this framework.

The same compiled plan (``DeviceNFACompiler`` states/predicates/outputs,
``backend="numpy"``) has a second executor: ``host_exec.HostBlockNFA`` runs
these stage semantics eagerly in NumPy with DYNAMIC tables — no padding, no
slot capacities, no drop counters — as the columnar host fast path and the
DeviceGuard quarantine engine. Semantic changes to the stage algorithm here
must be mirrored there (the parity fuzz in ``tests/test_host_batch.py``
pins both against the scalar interpreter).
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from .dtypes import JNP as _JNP
from .rowpack import leaves_from_rows, pack_first, to_words

if TYPE_CHECKING:
    from .nfa import DeviceNFACompiler


def blocked_eligible(nfa: "DeviceNFACompiler") -> bool:
    """True when the pattern fits the blocked kernel's shape: a chain of
    stream states whose ``every`` scope is the whole pattern (always-seed)."""
    return all(s.kind == "stream" for s in nfa.states) \
        and nfa.states[0].ends_every


def block_init_state(nfa: "DeviceNFACompiler") -> dict:
    """Tables for states 1..S-1 (seeds enter state 1; state 0 holds nothing
    for stream chains) + counters.

    Invariant: table slots are packed in creation order (oldest first) — the
    per-batch survivor pack preserves candidate order, and candidates are
    [old slots (already ordered), creations (born ascending)]. Drop-newest
    truncation is therefore just "keep the first C survivors", which is what
    ``pack_first`` does: slot c gathers the (c+1)-th surviving candidate, so
    the order is the candidates' own, empty slots (all at the end) hold the
    fills of this function, and survivors past the C-th are counted in
    ``drops`` and gone."""
    C = nfa.C
    has_ew = any(st.within_ms is not None for st in nfa.states)
    tables = {}
    for s in range(1, nfa.S):
        fields = {
            "valid": jnp.zeros((C,), jnp.bool_),
            "first_ts": jnp.full((C,), -1, jnp.int64),
        }
        if has_ew:
            # time of the binding that brought the partial here (element-
            # level `within` measures gaps between consecutive elements) —
            # carried only when some state needs it
            fields["last_ts"] = jnp.full((C,), -1, jnp.int64)
        for (q, key, t) in nfa.referenced:
            if q < s:
                fields[key] = jnp.zeros((C,), _JNP[t])
        tables[f"t{s}"] = fields
    return {
        "tables": tables,
        "matches": jnp.array(0, jnp.int64),
        "drops": jnp.array(0, jnp.int64),
    }


def first_hit(grid, vals):
    """What a stage takes from its [B, P] grid, in ONE pass over it:
    ``adv`` [P] (some event advances the candidate), ``jstar`` [P] i32 (the
    first that does; 0 where none) and ``{k: vals[k][jstar]}`` for the [B]
    leaves of ``vals``, exactly as NumPy's ``any`` / ``argmax`` / index
    would give them.

    No gather: the leaves ride the reduce. It is the argmax's own variadic
    reduce over the events, the carried tuple ``(j, word, ...)`` with the
    smaller ``j`` winning, ``j = B`` where the grid is off; a leaf rides as
    its 32-bit words (moved, never computed with: exact for every dtype, NaN
    payloads and -0.0 included). On a v5e a gathered element costs 8.6-10.2
    ns whatever the shape (17.2 ms for ``256 x 6592`` of them) where the
    whole reduce of a ``256 x 320 x 6592`` grid, ``any`` and argmax
    included, takes 2.6 ms with one word riding (PERF.md section 6, PR 32)."""
    B = grid.shape[0]
    leaves = list(vals.values())
    words = [to_words(v) for v in leaves]                  # [B, 1 or 2] each
    cols = [c for w in words for c in w.T]                 # W of [B]
    jidx = jnp.arange(B, dtype=jnp.int32)

    def earlier(a, b):
        first = a[0] <= b[0]
        return tuple(jnp.where(first, x, y) for x, y in zip(a, b))

    j, *rows = jax.lax.reduce(
        [jnp.where(grid, jidx[:, None], B)]
        + [jnp.broadcast_to(c[:, None], grid.shape) for c in cols],
        [jnp.int32(B)] + [jnp.int32(0)] * len(cols), earlier, (0,))
    adv = j < B
    jstar = jnp.where(adv, j, 0)
    # no event: event 0's words, what ``leaf[argmax]`` reads (never used: the
    # candidate did not advance)
    rows = [jnp.where(adv, r, c[0]) for r, c in zip(rows, cols)]
    if not rows:
        return adv, jstar, {}
    return adv, jstar, dict(zip(vals, leaves_from_rows(
        jnp.stack(rows, axis=1), words, leaves)))


def make_block_step(nfa: "DeviceNFACompiler"):
    """Returns step(state, cols, tag, ts, ts_base, nvalid) -> (state, ys)
    in the wire format (int32 ts deltas + int64 base, prefix validity).

    ys: {"n": i32, the rows this batch emitted,
         "mask": [M] bool, "j": [M] i32 (match event index, for ordering),
         <out-name>: [M] ...,
         "full": {"mask", "j", <out-name>: [P]}        (S > 1 only)}
    ``n``, the row table and ``full`` are what the scan kernel hands out
    too (``nfa.py`` ``_make_step``), and what ``nfa.decode_rows`` reads:
    ``mask`` / ``j`` / the columns hold the emitted rows packed to the front
    in candidate order, ``M = B``; ``full`` is read only for a batch with
    ``n > M``. Here it is the last stage's whole candidate table in the same
    layout, ``P = (S-1)*C + B``, which costs no copy (the module text says
    why; the scan kernel packs at the size of its ``full`` for such a batch
    alone). ``S == 1`` emits ``[B]`` as it is. A match's timestamp is the
    batch's ``ts[j]``, which the host holds: it never leaves the device.
    """
    C, S, B = nfa.C, nfa.S, nfa.B
    states = nfa.states
    within = nfa.within
    is_seq = nfa.is_sequence
    referenced = sorted(nfa.referenced)
    out_specs = nfa.out_specs
    out_ev_keys = sorted(nfa.out_ev_keys)
    # optional creation budget: partials entering a state within one batch
    # are compacted to K entries (order-preserving; overflow counted in
    # `drops`), capping every stage's grid at [B, C+K]. Off by default —
    # exact growth is [B, sC+B], fine for realistic S — but long patterns
    # (large S) can opt in via ``DeviceNFACompiler.creation_cap``.
    K = getattr(nfa, "creation_cap", None)
    has_ew = any(st.within_ms is not None for st in states)

    def binding_keys(s: int) -> list:
        """Referenced bound-value keys carried by a partial AT state s."""
        return [key for (q, key, t) in referenced if q < s]

    def key_dtype(key: str):
        for (q, k, t) in referenced:
            if k == key:
                return _JNP[t]
        raise KeyError(key)

    def new_binding_cols(s: int, cols):
        """Bindings minted when state ``s`` consumes an event: b{s}_attr,
        as [B] columns of the batch (a stage fetches them by ``jstar``)."""
        out = {}
        sid = nfa.compiled.alias_defs[states[s].alias].id
        for (q, key, t) in referenced:
            if q == s:
                attr = key[len(f"b{s}_"):]
                mk = nfa.merged.col_key(sid, attr)
                out[key] = cols[mk].astype(_JNP[t])
        return out

    def step(state, cols, tag, ts, ts_base, nvalid):
        tables = dict(state["tables"])
        matches = state["matches"]
        drops = state["drops"]

        jidx = jnp.arange(B, dtype=jnp.int32)
        # wire format: int32 ts deltas + per-batch base, prefix validity.
        # The deltas lie in [0, 2^31-1] (``MergedBatchBuilder.emit``); the
        # `within` tests compare them as they came, and the int64 times are
        # built for what a partial carries
        d = ts
        base = ts_base.astype(jnp.int64)
        ts = base + d.astype(jnp.int64)
        valid = jidx < nvalid
        ev_env = {f"ev_{k}": cols[k] for k in cols}
        n_valid = jnp.sum(valid.astype(jnp.int32))
        vidx = jnp.cumsum(valid.astype(jnp.int32))        # 1-based at valids
        # the newest event's delta; -1 (under every limit) in an empty batch
        d_last = jnp.max(jnp.where(valid, d, -1))

        def limit(t, w):
            """[P] i32: the largest delta ``d`` with ``ts_base + d - t <=
            w``, clipped to [-1, 2^31-1], which keeps ``d <= limit`` exact
            for every delta the wire carries: -1 admits none of them and
            2^31-1 all. Once a stage over ``[P]`` in int64, so the ``[B, P]``
            grid compares int32 (a v5e has no 64-bit integer unit)."""
            return jnp.clip(t - base + w, -1, 2**31 - 1).astype(jnp.int32)

        with jax.named_scope("nfa.admit"):
            # ---- seeds: state-0 predicate over the raw batch --------------
            st0 = states[0]
            gate0 = valid & (tag == st0.stream_idx)
            if st0.predicate is not None:
                p0 = jnp.broadcast_to(jnp.asarray(st0.predicate(ev_env)), (B,))
                gate0 = gate0 & p0

        if S == 1:
            # single-state every-pattern: each matching event IS a match
            n = jnp.sum(gate0, dtype=jnp.int32)
            out = {"n": n, "mask": gate0, "j": jidx}
            emit_env = dict(ev_env)
            for (q, key, t) in referenced:
                if q == 0:
                    emit_env[key] = new_binding_cols(0, cols)[key]
            for (name, fn, t) in out_specs:
                out[name] = jnp.broadcast_to(
                    jnp.asarray(fn(emit_env)), (B,)).astype(_JNP[t])
            new_state = {"tables": tables, "drops": drops,
                         "matches": matches + n.astype(jnp.int64)}
            return new_state, out

        def compact(cre):
            """Order-preserving compaction of a creations dict to K slots;
            returns (creations, n_dropped). Identity when no budget is set."""
            ex = cre["exists"]
            n = ex.shape[0]
            if K is None or n <= K:
                return cre, jnp.int64(0)
            vals = {k: v for k, v in cre.items() if k != "exists"}
            fills = {"born": 0, "first_ts": -1,
                     "bind": {k: 0 for k in cre["bind"]}}
            if is_seq:
                fills["vb"] = 0
            if has_ew:
                fills["last_ts"] = -1
            exists, out, dropped = pack_first(ex, K, vals, fills)
            out["exists"] = exists
            return out, dropped

        with jax.named_scope("nfa.admit"):
            # creations entering state 1
            cre0 = {
                "exists": gate0,
                "born": jidx,                                  # batch position
                "first_ts": ts,
                "bind": new_binding_cols(0, cols),             # b0_* [B]
            }
            if is_seq:
                cre0["vb"] = vidx                              # vidx[born]
            if has_ew:
                cre0["last_ts"] = ts
            creations, dropped = compact(cre0)
            drops = drops + dropped

        ys = None

        for s in range(1, S):
            with jax.named_scope(f"nfa.stage{s}"):
                st = states[s]
                tbl = tables[f"t{s}"]
                Pc = creations["exists"].shape[0]
                P = C + Pc

                # candidates: old slots first, then creations (born order)
                cand_exists = jnp.concatenate(
                    [tbl["valid"], creations["exists"]])
                cand_born = jnp.concatenate(
                    [jnp.full((C,), -1, jnp.int32), creations["born"]])
                cand_vb = jnp.concatenate(
                    [jnp.zeros((C,), jnp.int32), creations["vb"]]) \
                    if is_seq else None
                cand_first = jnp.concatenate(
                    [tbl["first_ts"], creations["first_ts"]])
                cand_last = jnp.concatenate(
                    [tbl["last_ts"], creations["last_ts"]]) if has_ew else None
                cand_bind = {}
                for key in binding_keys(s):
                    dt = key_dtype(key)
                    old = tbl[key]
                    new = creations["bind"].get(key)
                    if new is None:
                        new = jnp.zeros((Pc,), dt)
                    cand_bind[key] = jnp.concatenate(
                        [old.astype(dt), new.astype(dt)])

                # ---- the [B, P] grid ----------------------------------------
                gate = valid & (tag == st.stream_idx)          # [B]
                grid = gate[:, None] & cand_exists[None, :]
                if st.predicate is not None:
                    env = {k: v[:, None] for k, v in ev_env.items()}
                    env.update({k: v[None, :] for k, v in cand_bind.items()})
                    pred = jnp.asarray(st.predicate(env))
                    grid = grid & jnp.broadcast_to(pred, (B, P))
                if within is not None:
                    lim = limit(cand_first, within)
                    grid = grid & (d[:, None] <= lim[None, :])
                if st.within_ms is not None:
                    # element-level: the gap since the PREVIOUS element's bind
                    lim_e = limit(cand_last, st.within_ms)
                    grid = grid & (d[:, None] <= lim_e[None, :])
                if is_seq:
                    grid = grid & (vidx[:, None] == cand_vb[None, :] + 1)
                else:
                    grid = grid & (jidx[:, None] > cand_born[None, :])

                # ---- what the plan reads of the advancing event ---------
                # a state's new bindings; its time under element-level
                # `within`; its rank in a sequence; at the last stage the
                # columns the outputs read. Nothing else is fetched.
                live = new_binding_cols(s, cols)               # b{s}_* [B]
                if s == S - 1:
                    live.update({k: ev_env[k] for k in out_ev_keys})
                else:
                    if has_ew:
                        live["last_ts"] = ts
                    if is_seq:
                        live["vb"] = vidx
                adv, jstar, got = first_hit(grid, live)        # [P]
                carried = {**cand_bind, **got}

                if s == S - 1:
                    with jax.named_scope("nfa.emit"):
                        # ---- emission ------------------------------------
                        rows = {"j": jstar}
                        for (name, fn, t) in out_specs:
                            rows[name] = jnp.broadcast_to(
                                jnp.asarray(fn(carried)), (P,)).astype(
                                    _JNP[t])
                        n = jnp.sum(adv, dtype=jnp.int32)
                        matches = matches + n.astype(jnp.int64)
                        # the rows that matched, packed to the front of a
                        # [B] table; rows past it are NOT dropped: the
                        # decode reads ``full`` when n says there are any
                        taken, packed, _ = pack_first(
                            adv, B, rows, {k: 0 for k in rows})
                        ys = {"n": n, "mask": taken, **packed,
                              "full": {"mask": adv, **rows}}
                else:
                    # ---- creations for state s+1 -------------------------
                    # an advanced candidate existed, so it carries its seed
                    # event's time already: first_ts is the candidate's own
                    cre_n = {
                        "exists": adv,
                        "born": jstar,
                        "first_ts": cand_first,
                        "bind": {key: carried[key]
                                 for key in binding_keys(s + 1)},
                    }
                    if is_seq:
                        cre_n["vb"] = got["vb"]
                    if has_ew:
                        cre_n["last_ts"] = got["last_ts"]
                    creations, dropped = compact(cre_n)
                    drops = drops + dropped

                with jax.named_scope("nfa.compact"):
                    # ---- survivors → new table s (truncate to C,
                    # drop-newest) ----
                    surv = cand_exists & ~adv
                    if within is not None:
                        surv = surv & (d_last <= lim)
                    if st.within_ms is not None:
                        # an element-window that lapsed against the newest
                        # event can never match again (monotonic time) —
                        # prune, or dead partials wedge the keep-oldest slots
                        # (review finding)
                        surv = surv & (d_last <= lim_e)
                    if is_seq:
                        # strict continuity: survive only if no valid event
                        # followed
                        surv = surv & (cand_vb == n_valid)
                    # candidates are already in creation order (see
                    # block_init_state invariant): the first C survivors
                    # are the table, the rest drop off
                    vals = {"first_ts": cand_first, **cand_bind}
                    fills = {"first_ts": -1, **{k: 0 for k in cand_bind}}
                    if has_ew:
                        vals["last_ts"], fills["last_ts"] = cand_last, -1
                    kept, ntbl, dropped = pack_first(surv, C, vals, fills)
                    ntbl["valid"] = kept
                    tables[f"t{s}"] = ntbl
                    drops = drops + dropped

        new_state = {"tables": tables, "matches": matches, "drops": drops}
        return new_state, ys

    return step
