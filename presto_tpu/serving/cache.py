"""Canonical plan/executable cache.

Entries are keyed by `sql.canonical.plan_cache_key` — catalog + schema +
execution-config fingerprint + the structural key of the PARAMETERIZED
pre-optimizer plan — and hold the optimized template plus a small pool of
PlanCompiler instances.  Compilers are checked out exclusively (a
TaskContext holds per-execution state: params, memory pool, runtime
stats) and returned after a successful drain, mirroring the pop/recache
discipline of the old exact-SQL-text cache it replaces
(exec/runner.py:53 before this change).

Why a pool and not one compiler: the statement path executes concurrent
queries against one runner; two executions sharing a compiler would race
on ctx.params.  When the pool is empty a hit still returns the optimized
template — the caller rebuilds only the compiler (cheap construction;
XLA executables re-specialize lazily), never re-running
parse→plan→optimize.

Invalidation: DDL (tables changed) clears everything; session-property /
config / catalog changes need no invalidation because they are part of
the key.  Eviction is LRU by last checkout/insert.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from typing import List, Optional, Tuple

from ..common.locks import OrderedLock
from ..utils.runtime_stats import current_stats
from .metrics import SERVING_METRICS

DEFAULT_PLAN_CACHE_ENTRIES = 128
_POOL_PER_ENTRY = 4             # compilers retained per entry


class _Entry:
    __slots__ = ("template", "slot_types", "pool", "out", "out_peak")

    def __init__(self, template, slot_types):
        self.template = template          # optimized OutputNode
        self.slot_types = slot_types      # parameter slot types, in order
        self.pool: List[object] = []      # idle PlanCompiler instances
        self.out = 0                      # compilers currently checked out
        self.out_peak = 0                 # high-water concurrent checkouts


class PlanCache:
    def __init__(self, max_entries: int = DEFAULT_PLAN_CACHE_ENTRIES):
        # rank 50: SERVING_METRICS (a rank-100 registry) is bumped while
        # this is held; nothing engine-side nests inside it
        self._lock = OrderedLock("serving-cache", 50)  # lint: guarded-by(_lock)
        self._entries: "OrderedDict[str, _Entry]" = OrderedDict()
        self.max_entries = max_entries
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0
        self.pool_exhausted = 0

    # -- configuration ----------------------------------------------------
    def set_max_entries(self, n: int) -> None:
        with self._lock:
            self.max_entries = max(1, int(n))
            self._evict_locked()

    # -- lookup -----------------------------------------------------------
    def checkout(self, key: str) -> Optional[Tuple[object, list, object]]:
        """Hit -> (optimized template, slot types, compiler-or-None); the
        compiler, when present, is exclusively owned until checkin()."""
        t0 = time.perf_counter_ns()  # lint: allow-wall-clock
        with self._lock:
            # lock-acquisition wall = how long concurrent executions
            # queued behind the cache (the "checkout wait" of a
            # contended serving plane): process-wide below, and into the
            # query that waited
            wait = time.perf_counter_ns() - t0  # lint: allow-wall-clock
            owner = current_stats()
            if owner is not None:
                owner.record("compilerCheckoutWait", t0, wait)
            ent = self._entries.get(key)
            if ent is None:
                self.misses += 1
                SERVING_METRICS.incr("plan_cache_misses")
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            SERVING_METRICS.incr("plan_cache_hits")
            SERVING_METRICS.incr("compiler_checkouts")
            if wait:
                SERVING_METRICS.incr("compiler_checkout_wait_nanos", wait)
            compiler = ent.pool.pop() if ent.pool else None
            ent.out += 1
            if ent.out > ent.out_peak:
                ent.out_peak = ent.out
            SERVING_METRICS.max_update("compiler_checkout_depth_peak",
                                       ent.out)
            if compiler is None:
                # exhausted pool: the caller rebuilds a compiler — that
                # fallback used to be silent; now it is the contention
                # signal the admission layer can watch
                self.pool_exhausted += 1
                SERVING_METRICS.incr("compiler_pool_exhausted")
            return ent.template, ent.slot_types, compiler

    def insert(self, key: str, template, slot_types, compiler) -> None:
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                ent = _Entry(template, slot_types)
                self._entries[key] = ent
            self._entries.move_to_end(key)
            if compiler is not None \
                    and len(ent.pool) < _POOL_PER_ENTRY:
                ent.pool.append(compiler)
            self._evict_locked()

    def checkin(self, key: str, compiler) -> None:
        """Return a compiler after a successful execution; dropped when the
        entry was evicted/invalidated meanwhile (a stale compiler must not
        resurrect a dead key)."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                return
            # the checkout is over whether or not the compiler survives
            # (pool-full drops still end the exclusive ownership window)
            if ent.out > 0:
                ent.out -= 1
            if compiler is not None and len(ent.pool) < _POOL_PER_ENTRY:
                ent.pool.append(compiler)

    def contains(self, key: str) -> bool:
        with self._lock:
            return key in self._entries

    # -- invalidation -----------------------------------------------------
    def invalidate_all(self) -> int:
        """Drop every entry (DDL changed table contents: any cached plan —
        and any compiler-internal materialization — may be stale)."""
        with self._lock:
            n = len(self._entries)
            self._entries.clear()
            if n:
                self.invalidations += n
                SERVING_METRICS.incr("plan_cache_invalidations", n)
            return n

    def _evict_locked(self) -> None:
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)
            self.evictions += 1
            SERVING_METRICS.incr("plan_cache_evictions")

    # -- observability ----------------------------------------------------
    def info(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "maxEntries": self.max_entries,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "poolExhausted": self.pool_exhausted,
                "checkedOut": sum(e.out for e in self._entries.values()),
                "checkoutDepthPeak": max(
                    (e.out_peak for e in self._entries.values()),
                    default=0),
            }


# One cache per process (the statement path builds ≤16 runners per
# coordinator but the same shapes flow through all of them; config /
# catalog / schema live in the key so sharing is safe).
GLOBAL_PLAN_CACHE = PlanCache()
