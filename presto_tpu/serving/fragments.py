"""The process-wide cache of jitted programs.

Every jitted program a PlanCompiler builds for a plan node goes through
`PlanCompiler.shared_jit` (exec/pipeline.py), and -- unless the
in-process batch scheduler installed its per-stage cache, or the
`fragment_share` config knob is off -- lands here: ONE `jax.jit` object
per STRUCTURE, whoever compiles it.  The stage's other task, the next
query with the same text, a pooled compiler rebuilt after eviction and a
different plan sharing a scan->filter->agg subchain all get the same
object back and dispatch through JAX's fast path: no trace, no lowering,
no executable load.  A worker task builds a new PlanCompiler every time,
so on coordinator -> worker this cache is what keeps a warm query from
re-tracing its programs a task.

A fused chain's shape probe (`FusedChain.shape_probe`, exec/fused.py:
the abstract output its callers read before they pick a program) is an
entry like the programs, through `PlanCompiler.shared_entry`: a
`ShapeProbe` under the purpose `chain_shape_probe` and the chain's own
key, with one eviction and one invalidation.  It keeps its results by
the aux pytree's treedef and avals, so a warm execution traces nothing
at all (`shapeProbeHits` / `shapeProbeMisses`, counted where the probe
is asked).

The key is `(purpose, structural key of the subtree, its real variable
names, extras, config fingerprint)` (`spi.plan.named_structural_key`:
node ids blanked, literals kept; `sql.canonical.config_fingerprint`).
What a key must hold: EVERY host constant the traced closure bakes in.
The subtree and the config cover the plan's own constants (literals,
expressions, table identity and scale factor, chunk capacity); `extras`
carry what the site derived at run time -- join fanouts, the leaf
capacity, the `_counted` (operator stats) variant, the direct/span
mode's G, strides and domains, the shape probe's column signature, hash
slots and salt, and the task index where the chain assigns unique ids.
Split assignment, bound parameters, chunk positions, HBM-resident
columns and build tables are NOT baked: they ride as traced arguments,
and jax.jit's own per-aval / per-treedef retracing (column names,
dictionaries and nullability are part of a Batch's treedef) handles
their drift inside the one object.  A false share executes the wrong
program; a missed share costs one retrace.  The build tables themselves
-- a join's materialised build side and its lookup table -- live in the
sibling cache `serving/builds.py`, bounded by bytes, reached through
`PlanCompiler.shared_build` and cleared together with this one
(`builds.invalidate_compiled`).

What a cached callable must NOT hold: the task that built it.  Nothing
reachable from the traced function may reach a TaskContext (memory
context, runtime stats, exchange clients, dynamic filters) or the
PlanCompiler; fused chains hand their closures a `ChainProgram`
(exec/fused.py) for that reason.  The LRU bounds the plan nodes and
expressions the closures do keep alive.

Each lookup counts `programCacheHits` / `programCacheMisses` into the
RuntimeStats that owns the calling thread (rolled up task -> query like
every other key) and `SERVING_METRICS.fragment_jit_hits/misses`
process-wide.  A callable a compiler already holds stays valid through
eviction and `invalidate_all`.  DDL clears the cache alongside the plan
cache (runner._invalidate_plans): generated-connector fragments are
immutable, but a dropped-and-recreated stored table must not resurrect
callables probed against the old data's encodings.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable

from ..common.locks import OrderedLock
from ..utils.runtime_stats import current_stats
from .metrics import SERVING_METRICS

DEFAULT_FRAGMENT_ENTRIES = 512


class FragmentJitCache:
    def __init__(self, max_entries: int = DEFAULT_FRAGMENT_ENTRIES):
        # rank 95: SERVING_METRICS (100) is bumped while held; taken from
        # inside compiler step construction with no serving lock held
        self._lock = OrderedLock("serving-fragments", 95)  # lint: guarded-by(_lock)
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()
        self.max_entries = int(max_entries)

    def get_or_build(self, key: tuple, build: Callable):
        """Return the cached jitted callable for `key`, building (and
        LRU-inserting) it on first sight.  Building under the lock is
        fine: jax.jit is lazy — tracing and compilation happen at first
        CALL, outside this lock."""
        with self._lock:
            fn = self._entries.get(key)
            hit = fn is not None
            if hit:
                self._entries.move_to_end(key)
            else:
                fn = self._entries[key] = build()
                while len(self._entries) > self.max_entries:
                    self._entries.popitem(last=False)
            SERVING_METRICS.incr(
                "fragment_jit_hits" if hit else "fragment_jit_misses")
        stats = current_stats()
        if stats is not None:
            stats.add("programCacheHits" if hit else "programCacheMisses", 1)
        return fn

    def invalidate_all(self) -> int:
        with self._lock:
            n = len(self._entries)
            self._entries.clear()
            return n

    def info(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries),
                    "maxEntries": self.max_entries}


FRAGMENT_JIT_CACHE = FragmentJitCache()
