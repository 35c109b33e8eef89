"""Order-preserving packs of table rows, shared by the kernels.

A table is a pytree of ``[P]`` leaves (one row = one element of every leaf).
Both packs here move rows the same way: the source index of every output
slot is computed ONCE, then the leaves go as one gather of rows over their
32-bit words stacked ``[P, W]``. On a v5e a gathered or scattered element
costs 7-12 ns whatever the shape (a scattered int64 one 13 times that), a
gathered row hardly more than one element (PERF.md section 6, PR 30), so a
pack costs its rows, not its columns. Words are moved, never computed with:
exact for bool, int32, int64, f32 and f64, NaN payloads and -0.0 included.

- ``pack_first``: the rows a mask marks, into ``n`` slots (the blocked NFA's
  survivor pack and row table, ``nfa_block.py``).
- ``compact_front``: the rows a mask marks, to the front of their own ``[B]``
  (the batch compaction at the head of a windowed step,
  ``query_compile.py``), which costs nothing to move where the mask is a
  prefix already.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def to_words(v):
    """A [P] leaf as [P, k] int32 words, bit for bit (k = 2 for the 64-bit
    types, which the TPU holds as word pairs anyway)."""
    if v.dtype == jnp.bool_:
        return v.astype(jnp.int32)[:, None]
    w = jax.lax.bitcast_convert_type(v, jnp.int32)
    return w if w.ndim == 2 else w[:, None]


def from_words(w, dtype):
    """[n, k] int32 words back to the [n] leaf they were cut from."""
    if dtype == jnp.bool_:
        return w[:, 0] != 0
    return jax.lax.bitcast_convert_type(w if w.shape[1] > 1 else w[:, 0],
                                        dtype)


def leaves_from_rows(rows, words, leaves):
    """[n, W] rows of stacked words back to the leaves ``words`` were cut
    from (``words[i]`` = ``to_words(leaves[i])``)."""
    parts = jnp.split(rows, np.cumsum([w.shape[1] for w in words])[:-1],
                      axis=1)
    return [from_words(part, v.dtype) for part, v in zip(parts, leaves)]


def take_rows(src, taken, vals, fills):
    """Rows ``src`` [n] of the table ``vals`` (a pytree of [P] leaves) as ONE
    gather over the leaves' stacked words; slots where ``taken`` [n] is off
    hold ``fills`` (the same tree of scalars)."""
    leaves, tree = jax.tree.flatten(vals)
    P = leaves[0].shape[0]
    words = [to_words(v) for v in leaves]
    rows = jnp.concatenate(words, axis=1)[jnp.minimum(src, P - 1)]   # [n, W]
    packed = [
        jnp.where(taken, got, jnp.asarray(fill, got.dtype))
        for got, fill in zip(leaves_from_rows(rows, words, leaves),
                             tree.flatten_up_to(fills))]
    return jax.tree.unflatten(tree, packed)


def pack_first(mask, n: int, vals, fills):
    """Order-preserving pack of the rows ``mask`` [P] marks into ``n`` slots:
    slot c takes the (c+1)-th marked row, slots past the last marked row
    take ``fills``, marked rows past the n-th drop off and are counted.

    Index once, gather n. ``src[c]``, the position of the (c+1)-th set bit
    (``P`` where fewer are set), is how many prefix counts lie below c+1: one
    fused [n, P] compare-and-count, a grid of the kernel's own [B, P] kind
    that is never materialised. Then the leaves of ``vals`` (a pytree of [P]
    arrays; ``fills`` the same tree of scalars) go as one gather of n rows
    (``take_rows``).
    Returns ``(taken [n] bool, packed leaves, dropped i64)``."""
    P = mask.shape[0]
    count = jnp.cumsum(mask.astype(jnp.int32))
    src = jnp.searchsorted(count, jnp.arange(1, n + 1, dtype=jnp.int32),
                           side="left", method="compare_all")
    taken = src < P
    packed = take_rows(src, taken, vals, fills)
    dropped = jnp.maximum(count[-1].astype(jnp.int64) - n, 0)
    return taken, packed, dropped


def compact_front(mask, vals, fills):
    """Stable compaction of a batch: the rows ``mask`` [B] marks go to the
    front of their own [B] in their order (marked row i to slot rank_i), the
    slots behind them hold ``fills`` (the same tree of scalars as ``vals``,
    a pytree of [B] leaves). What it costs follows what the mask asks:

    - a prefix (no marked row behind an unmarked one: a batch nothing was
      filtered from, ``BatchBuilder.emit``'s ``valid``): every row is where
      it belongs, so the leaves are masked where they stand, elementwise
      (scope ``compact.keep``);
    - anything else: the source row of each slot ONCE (a sort of the marked
      rows' numbers), then one gather of rows over all leaves
      (``take_rows``; scope ``compact.move``).

    One ``lax.cond`` on one scalar read from the mask itself chooses, so a
    trace says which ran. Under a ``vmap`` the ``cond`` lowers to a select
    and both branches run: correct, and no dearer than the moved branch.
    Returns ``(compacted, k i32: rows marked, moved bool)``."""
    B = mask.shape[0]
    k = jnp.sum(mask.astype(jnp.int32))
    prefix = ~jnp.any(mask[1:] & ~mask[:-1])

    def keep(vals):
        with jax.named_scope("compact.keep"):
            return jax.tree.map(
                lambda x, fill: jnp.where(mask, x, jnp.asarray(fill, x.dtype)),
                vals, fills)

    def move(vals):
        with jax.named_scope("compact.move"):
            # slot c reads the (c+1)-th marked row: the marked rows' numbers
            # in ascending order are ONE one-operand int32 sort (unmarked
            # rows sort behind them as B). On a v5e 0.018 / 0.032 ms at B
            # 8,192 / 32,768, where one int32 scatter of the row numbers
            # takes 0.053 / 0.169 and `pack_first`'s [B, B] compare-and-
            # count 0.059 / 1.17 (PERF.md section 6, PR 38)
            lane = jnp.arange(B, dtype=jnp.int32)
            src = jnp.sort(jnp.where(mask, lane, B))
            return take_rows(src, lane < k, vals, fills)

    return jax.lax.cond(prefix, keep, move, vals), k, ~prefix
