"""Room on CPython's frame stack for JAX's deep recursions.

CPython 3.11+ keeps a thread's Python frames in 16 KiB "data stack"
chunks: a call that does not fit the current chunk maps a new one, and the
return that empties it unmaps it again.  JAX's jaxpr -> MLIR lowering
recurses some hundred frames deep from wherever a task thread happens to
stand, so one of those chunk edges always lies inside it -- and when it
lies under a call made once per lowered equation, every equation pays an
mmap and a munmap.  Measured on the TPU's host (PERF.md, PR 26): lowering
the fused Q1 program took 0.22 to 0.57 s depending on nothing but how many
frames lay below it (one added wrapper frame moved it from the low to the
high figure), and 0.047 s with room.

`roomy(fn)` calls `fn` from a frame so large that CPython gives it a
chunk of its own with a quarter of a megabyte to spare: everything `fn`
calls is pushed into that chunk and no edge is crossed.  Thread entry
points that trace, lower or load JAX programs run their bodies through it
(worker tasks, the statement executor, the streaming drain, the exchange
and stage threads)."""
from __future__ import annotations

import types

FRAME_SLOTS = 1 << 15           # 8 bytes each: a 256 KiB frame, 512 KiB chunk


def _roomy(fn, *args, **kwargs):
    return fn(*args, **kwargs)


# the same code with an evaluation stack declared FRAME_SLOTS deep: the
# slots are never used, only reserved (a frame's size is its locals plus
# its declared stack), which costs one larger chunk and nothing per slot
roomy = types.FunctionType(
    _roomy.__code__.replace(co_stacksize=FRAME_SLOTS, co_name="roomy"),
    globals(), "roomy")
roomy.__doc__ = ("Call fn(*args, **kwargs) from a frame with a data-stack "
                 "chunk of its own (see the module's note).")
