"""Device dtype policy: TPU-safe representations for every ``DataType``.

TPU (v5e) has no native float64 and the VPU/MXU want f32/bf16; f64 and
uint64 HLOs do lower on a v5e (libtpu 0.0.34, ``chip_smoke.py`` stage S5, PR
21) but run emulated, at a cost nobody has measured. int64 lowers (as paired
s32 words: a subtract with a borrow, a compare of two words), fine for
timestamp arithmetic over ``[B]`` or ``[P]`` and costly per cell of a ``[B,
P]`` grid: on a v5e the blocked NFA's stage-7 reduce (``[256, 320, 6592]`` cells) took 2.48
ms with ``(ts[j] - first_ts[p]) <= within`` in int64 and 1.69 ms with the
int32 ``d[j] <= lim[p]`` (1.41 ms with no test at all), so a grid compares
int32 deltas against a limit computed once over ``[P]``
(``nfa_block.make_block_step``). Policy:

- ``DOUBLE``/``FLOAT`` → float32 on device (host interpreter keeps Python
  float64 semantics; parity tests compare with f32 tolerances).
- ``INT``/string codes → int32.
- ``LONG`` and event timestamps → int64 (emulated on TPU; used only for
  compares, min/max and additions — never in hot elementwise math, and never
  per cell of a grid).
- Aggregation accumulators (sums/counts) → float32 (``FACC``). Sliding-window
  sums use cumsum *differences* over bounded buffers, so error stays at
  O(sqrt(N)·eps·magnitude), well inside the engine's advertised precision.

``jax_enable_x64`` stays on so int64 arrays are representable. Columns,
window buffers, group tables and match tables never hold float64 (reference
contrast: ``io.siddhi.query.api.definition.Attribute.Type`` keeps Java's
8-byte long/double everywhere — fine for a JVM, hostile to a TPU). Four places
inside jitted steps do step outside the policy, each for exactness the
interpreter's doubles set the bar for, and each compiles and matches the
interpreter on the chip (S5):

- ``query_compile._window_svars``: windowed stdDev moments in f64 (prefix-sum
  differences of near-equal totals cancel catastrophically in f32);
- ``query_compile`` lossyFrequent: the ``total * error`` / ``total * support``
  thresholds in f64 (the host compares in doubles; an f32 product flips
  emissions at the boundary);
- ``aggregation_compile``: float sums, counts and stdDev moments of the
  incremental-aggregation partials in f64 (they merge into host doubles);
- ``backend.avalanche``: the splitmix64 multiply in uint64, once per event
  when a device group-by buckets a LONG key or more than one key column.

A new kernel stays inside the policy; moving these four inside it is a change
to their numerics and needs the parity fuzz, not just a cast.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..query_api.definition import DataType

# device (jnp) representation per declared attribute type
JNP = {
    DataType.STRING: jnp.int32,   # dictionary codes
    DataType.INT: jnp.int32,
    DataType.LONG: jnp.int64,
    DataType.FLOAT: jnp.float32,
    DataType.DOUBLE: jnp.float32,
    DataType.BOOL: jnp.bool_,
}

# host staging (numpy) representation — must mirror JNP so device_put never
# materializes a 64-bit float on device
NP = {
    DataType.STRING: np.int32,
    DataType.INT: np.int32,
    DataType.LONG: np.int64,
    DataType.FLOAT: np.float32,
    DataType.DOUBLE: np.float32,
    DataType.BOOL: np.bool_,
}

FACC = jnp.float32        # aggregation accumulator float
TS = jnp.int64            # event-time representation
NP_TS = np.int64
