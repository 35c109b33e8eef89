"""A sliding ``window.length(W)`` per key of a value partition, served from
the chip.

``partition with (k of S) begin from S#window.length(W) select ..., agg(x)
... end`` gives every key its own window of its last W events (Siddhi
clones the query per key, ``PartitionStreamReceiver.java:82-117``). Here
every key's window is one row of one table on the device, and one jitted
step serves a whole batch of any keys:

- the host maps a key (a long, an int, a string's dictionary code) to a
  stable int32 slot once (``KeyDirectory``: a hash table searched as whole
  arrays once a batch, new keys given the next slots; a key past the
  table's capacity gets no slot and its events are counted, ``drops``).
  The search runs where the batch is sealed, on the thread that seals it
  (the client's, inside its ``send`` / ``send_columns``, engine lock held),
  and the slots ride on the batch: the driver's thread only launches the
  step;
- the table ``windows`` [K, 1 + (W-1) x words] int32 holds a slot's fill
  (readings held, at most W-1) and its newest W-1 readings of every
  aggregate argument, newest first, bit for bit as 32-bit words, so a
  key's window moves as one row gather and one row scatter;
- the step sorts the batch by (slot, arrival) (``keyed.sort``): a key's
  events are then one segment in their order, and event ``e`` at rank
  ``r`` of its segment sees its ``min(r, W-1)`` predecessors in the
  segment (static shifts of the sorted arguments) and the newest
  ``W-1-r`` readings its key carried in (``keyed.window``); the last event
  of each segment writes its key's newest W-1 back (``keyed.store``); the
  aggregates go back to arrival order as one row scatter
  (``keyed.unsort``); then ``select``, ``having`` and the rows a batch
  emitted packed to the front (``compact``), the first
  ``rows_capacity(B)`` of them handed out beside the whole table.

Why this form (PERF.md section 6, PR 39; one v5e, B 32,768, K 1,310,720,
W 10): the step 2.47 ms, where the same kernel under ``vmap`` over 256
lanes of per-lane tables took 6.72 and its host lane layout 2.7 more, and
where ``_range_reduce``'s sparse table for the batch part cost 1.03 ms more
than the nine shifts; and the key directory a hash table, 1.75 ms a batch
on the chip's host where one ``searchsorted`` against the sorted keys took
5.03 and bounded the cell. What keeps the host tiers (``DeviceCompileError``):
any window but ``length``, no aggregate, group-by, ``stdDev``, W over
``MAX_LENGTH``, a FLOAT / DOUBLE key.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..core.columns import ColumnsOut
from ..query_api.definition import DataType
from .batch import BatchBuilder
from .dtypes import FACC
from .expr_compile import DeviceCompileError
from .query_compile import CompiledStreamQuery, _ident, _materialize
from .rowpack import compact_front, leaves_from_rows, to_words
from .step_runtime import StepRuntime

log = logging.getLogger("siddhi_tpu.device")

# the longest window a key carries: the step costs B x W per aggregate (the
# shifts) and the table W - 1 readings a key
MAX_LENGTH = 64
_IACC = jnp.int64


def rows_capacity(batch: int) -> int:
    """Rows of the packed table a step hands out (the decode fetches it; a
    batch that emitted more fetches the whole one): a sixteenth of the
    batch, at least 256."""
    return min(batch, max(256, batch // 16))


class KeyDirectory:
    """Key -> stable slot, on the host: an open-addressing table (linear
    probing, Fibonacci hashing) of at least twice ``capacity`` entries,
    searched as whole arrays once a batch. A key is given the next slot the
    first time it is seen and keeps it; a key past ``capacity`` gets
    ``capacity`` (no slot), and the step counts its events. At the cell's
    sizes a batch's search costs a third of one ``searchsorted`` against
    the sorted keys on the chip's host (PERF.md section 6, PR 39)."""

    _FIB = np.uint64(0x9E3779B97F4A7C15)

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        bits = max(4, (2 * self.capacity - 1).bit_length())
        self._shift = np.uint64(64 - bits)
        self._mask = (1 << bits) - 1
        self._keys = np.zeros(1 << bits, np.int64)
        self._slots = np.full(1 << bits, -1, np.int32)     # -1: empty
        self._n = 0

    def __len__(self) -> int:
        return self._n

    def _home(self, keys: np.ndarray) -> np.ndarray:
        return ((keys.view(np.uint64) * self._FIB) >> self._shift) \
            .astype(np.int64)

    def slots_of(self, keys) -> np.ndarray:
        """The slot of every key, admitting the keys not seen before in
        order of first appearance while there is room."""
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        out = self._find(keys)
        miss = out < 0
        if miss.any():
            room = self.capacity - self._n
            if room > 0:
                fresh = keys[miss]
                new, first = np.unique(fresh, return_index=True)
                new = new[np.argsort(first, kind="stable")[:room]]
                self._insert(new, np.arange(self._n, self._n + new.size,
                                            dtype=np.int32))
                self._n += new.size
                out[miss] = self._find(fresh)
            out[out < 0] = self.capacity
        return out

    def _find(self, keys: np.ndarray) -> np.ndarray:
        """The slot of every key, -1 where the table lacks it: every key
        probes its home at once, the few that meet another key probe on."""
        pos = self._home(keys)
        s = self._slots[pos]
        eq = self._keys[pos] == keys
        out = np.where(eq & (s >= 0), s, -1).astype(np.int32)
        go = np.flatnonzero((s >= 0) & ~eq)
        pos = pos[go]
        while go.size:
            pos = (pos + 1) & self._mask
            s = self._slots[pos]
            eq = (s >= 0) & (self._keys[pos] == keys[go])
            out[go[eq]] = s[eq]
            on = (s >= 0) & ~eq
            go, pos = go[on], pos[on]
        return out

    def _insert(self, keys: np.ndarray, slots: np.ndarray) -> None:
        """Distinct keys the table lacks: each round, of the keys whose
        probe reached an empty entry the first one an entry takes it, and
        every other key probes on."""
        pos = self._home(keys)
        while keys.size:
            free = np.flatnonzero(self._slots[pos] < 0)
            _, first = np.unique(pos[free], return_index=True)
            won = free[first]
            self._keys[pos[won]] = keys[won]
            self._slots[pos[won]] = slots[won]
            on = np.ones(keys.size, bool)
            on[won] = False
            keys, slots = keys[on], slots[on]
            pos = (pos[on] + 1) & self._mask

    def snapshot(self) -> dict:
        held = np.flatnonzero(self._slots >= 0)
        return {"keys": self._keys[held].copy(),
                "slots": self._slots[held].copy()}

    def restore(self, snap: dict) -> None:
        self.__init__(self.capacity)
        slots = np.asarray(snap["slots"], np.int32)
        self._insert(np.asarray(snap["keys"], np.int64), slots)
        self._n = int(slots.size)


class _KeyedBatchBuilder(BatchBuilder):
    """The flat batch of a served partition: a ``BatchBuilder`` that takes
    ``append(stream_id, row, ts)`` as the bridge and the guard's shadow
    call it for kind ``'partition'``."""

    def append(self, stream_id, row, ts) -> None:
        super().append(row, ts)


def _shift(z, d: int, fill):
    """``z`` [B] moved ``d`` places on: ``out[i] = z[i - d]``."""
    return z if d == 0 else jnp.concatenate(
        [jnp.full((d,), fill, z.dtype), z[:-d]])


def _later(x, t, bits: int):
    """Row ``i`` of ``x`` [B, R] moved ``t_i`` places towards its end
    (``out[i, q] = x[i, q - t_i]``), as ``bits`` conditional static shifts
    (a barrel shifter: no per-element gather). What moves in is zero."""
    for b in range(bits):
        s = 1 << b
        moved = jnp.zeros_like(x) if s >= x.shape[1] else jnp.concatenate(
            [jnp.zeros((x.shape[0], s), x.dtype), x[:, :-s]], axis=1)
        x = jnp.where(((t >> b) & 1)[:, None] == 1, moved, x)
    return x


class CompiledKeyedWindow:
    """The plan and the jitted step of one keyed length window. The select
    list, filters and ``having`` are ``CompiledStreamQuery``'s (the same
    query as an unkeyed window compiles to); the window is this module's."""

    def __init__(self, query, definition, key_attr: str, batch: int,
                 key_capacity: int):
        plan = CompiledStreamQuery(query, definition, batch_capacity=batch)
        if plan.window_kind != "length":
            raise DeviceCompileError(
                "a partition query other than a sliding window.length(N) "
                "keeps the host tiers")
        if plan.group_keys or plan.sagg_idx or not plan.agg_idx:
            raise DeviceCompileError(
                "a keyed window.length with group-by, stdDev or no aggregate "
                "keeps the host tiers")
        if not 1 <= plan.window_n <= MAX_LENGTH:
            raise DeviceCompileError(
                f"a keyed window.length({plan.window_n}) keeps the host "
                f"tiers (served up to {MAX_LENGTH})")
        kt = definition.attribute_type(key_attr)
        if kt not in (DataType.STRING, DataType.INT, DataType.LONG,
                      DataType.BOOL):
            raise DeviceCompileError(
                f"a partition keyed by a {kt.name} attribute keeps the host "
                f"tiers (keys are compared exactly)")
        self.plan = plan
        self.B, self.K, self.W = batch, int(key_capacity), plan.window_n
        self.M = rows_capacity(batch)
        specs = plan.specs
        # the carried arguments: one a sum / avg / min / max, in the dtype
        # it accumulates in; count() carries nothing but the fill
        self.carried = []
        for i in plan.agg_idx:
            s = specs[i]
            if s.kind == "count":
                continue
            dt = _IACC if s.acc_int else FACC if s.kind in ("sum", "avg") \
                else plan._mdtype(i)
            self.carried.append((i, dt))
        self.row_words = 1 + (self.W - 1) * sum(
            jnp.dtype(dt).itemsize // 4 for _, dt in self.carried)
        self.step = jax.jit(self._make_step(), donate_argnums=(0,))

    def init_state(self) -> dict:
        return {"windows": jnp.zeros((self.K, self.row_words), jnp.int32),
                "drops": jnp.zeros((), jnp.int64)}

    def _make_step(self):
        plan, B, K, W = self.plan, self.B, self.K, self.W
        R, M = W - 1, self.M
        specs, carried = plan.specs, self.carried
        bits = int(R).bit_length()

        def step(state, cols, ts, valid, slot):
            cols = dict(cols)
            cols["__ts__"] = ts
            with jax.named_scope("filter"):
                mask = valid
                for fn in plan.filter_fns:
                    mask = jnp.logical_and(mask, fn(cols))
            held = slot < K
            drops = state["drops"] + jnp.sum((mask & ~held).astype(jnp.int64))
            mask = mask & held
            iota = jnp.arange(B, dtype=jnp.int32)
            args = {i: specs[i].fn(cols).astype(dt) for i, dt in carried}

            with jax.named_scope("keyed.sort"):
                # a key's events one segment, in arrival order; what no
                # window takes sorts behind every slot
                skey, perm = jax.lax.sort(
                    (jnp.where(mask, slot, K), iota), num_keys=1,
                    is_stable=True)
                z = {i: a[perm] for i, a in args.items()}
                diff = skey[1:] != skey[:-1]
                first = jnp.concatenate([jnp.ones((1,), bool), diff])
                last = jnp.concatenate([diff, jnp.ones((1,), bool)])
                r = iota - jax.lax.cummax(jnp.where(first, iota, 0))

            with jax.named_scope("keyed.window"):
                rows = state["windows"][jnp.minimum(skey, K - 1)]  # [B, 1+]
                fill = rows[:, 0]
                prev, at = {}, 1
                for i, dt in carried:
                    k = jnp.dtype(dt).itemsize // 4
                    w = rows[:, at:at + R * k]
                    at += R * k
                    prev[i] = jax.lax.bitcast_convert_type(
                        w.reshape(B, R, k) if k > 1 else w, dt)
                d = jnp.arange(W)[:, None]
                m = jnp.arange(R)[None, :]
                # the batch's own part: the event and its predecessors in
                # the segment; the carried part: the key's newest W-1-r
                in_batch = d <= r[None, :]                          # [W, B]
                in_carry = (m < fill[:, None]) & (m < (W - 1 - r)[:, None])
                cnts = jnp.minimum(r + 1 + fill, W).astype(jnp.int64)
                agg, carry = {}, {}
                for i, dt in carried:
                    zs = jnp.stack([_shift(z[i], s, 0) for s in range(W)])
                    kind = specs[i].kind
                    if kind in ("min", "max"):
                        ident = _ident(dt, kind == "min")
                        red = jnp.min if kind == "min" else jnp.max
                        both = jnp.minimum if kind == "min" else jnp.maximum
                        agg[i] = both(
                            red(jnp.where(in_batch, zs, ident), axis=0),
                            red(jnp.where(in_carry, prev[i], ident), axis=1))
                    else:
                        agg[i] = (jnp.sum(jnp.where(in_batch, zs, 0), axis=0)
                                  + jnp.sum(jnp.where(in_carry, prev[i], 0),
                                            axis=1))
                    # what the key carries out: its newest W-1, newest first
                    carry[i] = jnp.where(
                        m <= r[:, None], zs[:R].T,
                        _later(prev[i], jnp.minimum(r + 1, R), bits))

            with jax.named_scope("keyed.store"):
                new_rows = jnp.concatenate(
                    [jnp.minimum(fill + r + 1, R)[:, None]]
                    + [jax.lax.bitcast_convert_type(carry[i], jnp.int32)
                       .reshape(B, -1) for i, _ in carried], axis=1)
                # the last event of each segment writes its key's row; every
                # other index lies past the table, each its own: dropped
                idx = jnp.where(last & (skey < K), skey, K + iota)
                windows = state["windows"].at[idx].set(
                    new_rows, mode="drop", unique_indices=True)

            with jax.named_scope("keyed.unsort"):
                vals = {"c": cnts, **{f"a{i}": v for i, v in agg.items()}}
                leaves, tree = jax.tree.flatten(vals)
                words = [to_words(v) for v in leaves]
                back = jnp.zeros((B, sum(w.shape[1] for w in words)),
                                 jnp.int32).at[perm].set(
                    jnp.concatenate(words, axis=1), unique_indices=True)
                vals = jax.tree.unflatten(
                    tree, leaves_from_rows(back, words, leaves))

            with jax.named_scope("select"):
                mins = {i: vals[f"a{i}"] for i in plan.magg_idx}
                stack = lambda idx, dt: jnp.stack(      # noqa: E731
                    [vals[f"a{i}"] for i in idx]) if idx \
                    else jnp.zeros((0, B), dt)
                out = _materialize(
                    specs, plan.value_idx, plan.fagg_idx, plan.iagg_idx,
                    plan.magg_idx, plan.sagg_idx,
                    {i: specs[i].fn(cols) for i in plan.value_idx},
                    stack(plan.fagg_idx, FACC), stack(plan.iagg_idx, _IACC),
                    vals["c"], mins, jnp.zeros((0, B), FACC))
                emit = mask
                if plan.having_fn is not None:
                    emit = emit & jnp.broadcast_to(plan.having_fn(out),
                                                   emit.shape)
            with jax.named_scope("compact"):
                front, n, _ = compact_front(
                    emit, out, jax.tree.map(lambda _: 0, out))
            return ({"windows": windows, "drops": drops},
                    {"n": n, "rows": {k: v[:M] for k, v in front.items()},
                     "full": front})

        return step


class KeyedWindowRuntime(StepRuntime):
    """The served runtime of a keyed window: a flat ``BatchBuilder`` of
    ``batch`` events in arrival order, the host's ``KeyDirectory`` and the
    device's table of windows; ``StepRuntime``'s protocol for the rest.
    The slot lookup runs as a batch is sealed (``_sealing``, inside
    ``seal.pack``), on the thread that seals it: the client's for a
    capacity flush, whoever flushes otherwise (tracker ``key_lookup``,
    nested in ``pack``; span ``siddhi:seal.key_lookup``). The batch carries
    its ``slot`` column to ``dispatch``, which only launches the step, so
    the driver's thread never touches the directory."""

    fence_key = "n"

    def __init__(self, query, stream_defs: dict, key_attr: str, batch: int,
                 key_capacity: int):
        sid = query.input_stream.stream_id
        self.stream_id = sid
        self.key_attr = key_attr
        self.compiled = CompiledKeyedWindow(query, stream_defs[sid], key_attr,
                                            batch, key_capacity)
        plan = self.compiled.plan
        self.schema = plan.schema
        self.out_specs = plan.out_specs
        self.builder = _KeyedBatchBuilder(plan.schema, batch)
        self.directory = KeyDirectory(key_capacity)
        self.state = self.compiled.init_state()
        # read at drain points only (on_drained): the keys held, and the
        # share of the table they fill
        self.step_gauges = {"keyed_live_keys": 0, "key_table_fill_share": 0.0}
        self._warned_drops = 0

    def send(self, stream_id: str, row: list, timestamp: int) -> None:
        self.builder.append(stream_id, row, timestamp)
        self._maybe_flush()

    def send_columns(self, cols: dict, ts) -> None:
        ts = np.asarray(ts, dtype=np.int64)
        start, n = 0, int(ts.shape[0])
        while start < n:
            start += self.builder.append_columns(cols, ts, start)
            self._maybe_flush()

    def _sealing(self, batch: dict) -> None:
        """The batch's keys to slots as it is sealed (the directory admits
        new ones in order of first appearance): ``batch["slot"]``, int32,
        the padding 0. The lookup is a part of ``pack``: the batch's
        ``pack_exec_s`` and ``_t_emit`` are moved to its end, so the serial
        sum reads every second once."""
        with self.measure("key_lookup", batch) as lookup:
            valid = batch["valid"]
            live = np.flatnonzero(valid)
            slot = np.zeros(valid.shape[0], np.int32)
            slot[live] = self.directory.slots_of(
                batch["cols"][self.key_attr][live])
        batch["slot"] = slot
        batch["pack_exec_s"] += lookup.end - batch["_t_emit"]
        batch["_t_emit"] = lookup.end

    def dispatch(self, batch: dict):
        """The step on donated state, over the slots the batch was sealed
        with. Returns the un-fenced outputs."""
        self.state, out = self.compiled.step(
            self.state, batch["cols"], batch["ts"], batch["valid"],
            batch["slot"])
        return out

    def _decode(self, out):
        """The packed rows (one ``device_get``), or the whole table for a
        batch that emitted more than it holds (``decode_full``)."""
        n = int(np.asarray(out["n"]))
        if n <= self.compiled.M:
            cols = jax.device_get(out["rows"])
        else:
            with self.measure("decode_full"):
                cols = jax.device_get(out["full"])
        # copies: the host's arrays of a fetch are read-only views
        return ColumnsOut(None, {k: np.array(v[:n]) for k, v in
                                 cols.items()}, n, self.out_specs,
                          self.schema.dictionaries)

    def on_drained(self) -> None:
        drops = int(jax.device_get(self.state["drops"]))
        self.step_gauges["keyed_live_keys"] = len(self.directory)
        self.step_gauges["key_table_fill_share"] = \
            len(self.directory) / self.compiled.K
        if drops > self._warned_drops:
            log.warning("query '%s': %d events of keys past the table's "
                        "capacity dropped (raise @device(keys=))",
                        self.query_name, drops)
            self._warned_drops = drops

    @property
    def drop_count(self) -> int:
        return int(jax.device_get(self.state["drops"]))

    def snapshot_state(self):
        from .batch import device_state_snapshot
        snap = device_state_snapshot(self.state, self.schema)
        snap["directory"] = self.directory.snapshot()
        return snap

    def restore_state(self, state) -> None:
        from .batch import device_state_restore
        self.state = device_state_restore(state, self.schema)
        self.directory.restore(state["directory"])
