"""TPU compiled path: columnar ingress, vectorized query programs, NFA kernels.

Everything here is jit-compiled XLA; all mutable state lives in pytrees
carried through the step functions, so checkpointing is ``device_get`` and
multi-chip scaling is ``shard_map`` over a ``jax.sharding.Mesh``
(see ``partition.py``). The pattern engine has two kernels: a batch-parallel
blocked formulation for stream-state chains (``nfa_block.py``, sequential
depth = number of NFA states) and a per-event scan fallback covering
count/logical/absent states (``nfa.py``).
"""

import jax

# x64 is enabled ONLY so int64 timestamps/LONG columns are representable
# (TPU lowers s64 as paired s32 — fine for the compares/adds event time
# needs). Float compute is pinned to float32 by the dtype policy
# (``dtypes.py``, which also lists the four places that step outside it).
jax.config.update("jax_enable_x64", True)

from .batch import BatchBuilder, BatchSchema, StringDictionary, columns_from_rows
from .expr_compile import ColumnResolver, DeviceCompileError, compile_expression
from .query_compile import CompiledStreamQuery
from .runtime import DeviceStreamRuntime
