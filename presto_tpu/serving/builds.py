"""The process-wide cache of join build sides.

A join's build side over immutable tables -- the materialised batch AND
the lookup table made from it (`exec/fused.py` `JoinBuild`) -- is the
same on every execution: the resident columns it is computed from never
change, and the split assignment of a task index is the same every
query.  `PlanCompiler.shared_build` (exec/pipeline.py) is the one door:
the unfused join, the unfused semi join and a fused chain's
`build_lookup` all ask it, and where the plan says the subtree may be
shared the entry lives HERE, so a worker task's new PlanCompiler finds
what the last task with the same subtree and the same splits built, as
it finds its programs in `serving/fragments.py`.  A hit launches nothing
and fetches nothing for the build.

The key (built by the door) holds everything that shapes the entry: the
subtree's structural key with its literals, the splits fingerprint, the
bound parameters where the subtree holds one, the config fingerprint,
the build keys by position, the table kind (join or semi), whether
operator statistics ride along, the task index where the subtree assigns
unique ids, and the device a pinned task runs on.  What may never be an
entry is decided from the plan, by the door: a subtree fed by a
`RemoteSourceNode` (what flows through it is in no key), one whose scan
a dynamic filter prunes, a table a connector can rewrite.

Bounded by BYTES (the batch, its masks and the table's slots, each
array once), least recently used out first; an entry larger than the
whole bound is handed back unkept.  One build lock a key, as
`storage/store.py` `get_or_build`: two queries in flight build one entry
once, the second waits and hits.  Cleared wherever `FRAGMENT_JIT_CACHE`
is (DDL through `runner._invalidate_plans`, a worker's table commit); a
build that was in flight across a clear is handed to its asker and not
kept.

An entry must hold nothing that reaches a TaskContext or a PlanCompiler
(the rule of serving/fragments.py): device arrays, host scalars and
plain dicts only.  A task holds its entry by reference while it probes,
so eviction never pulls arrays from under a running join.

Each lookup counts `joinBuildCacheHits` / `joinBuildCacheMisses` and
`joinBuildCacheBytes` (the resident bytes at the lookup) into the
RuntimeStats of the task that asked; a subtree the door declines counts
neither.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Dict

from ..common.locks import OrderedLock

DEFAULT_BUILD_CACHE_BYTES = 1 << 31


class JoinBuildCache:
    def __init__(self, max_bytes: int = DEFAULT_BUILD_CACHE_BYTES):
        # rank 94: a leaf but for the build locks, which are taken first
        # and never under it
        self._lock = OrderedLock("serving-builds", 94)  # lint: guarded-by(_lock)
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()
        self._bytes = 0
        self._generation = 0
        self.max_bytes = int(max_bytes)
        # key -> the lock its builder holds
        self._build_locks: Dict[tuple, threading.Lock] = {}

    def _lookup(self, key: tuple):
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
            return ent, self._bytes, self._generation

    def get_or_build(self, key: tuple, build: Callable, stats=None):
        """(entry, hit): the entry under `key`, built by `build()` (an
        object with `nbytes`) on a miss and kept if it fits."""
        ent, resident, generation = self._lookup(key)
        hit = ent is not None
        if not hit:
            with self._build_locks.setdefault(key, threading.Lock()):
                # a second asker waited out the first one's build: it hits
                ent, resident, generation = self._lookup(key)
                hit = ent is not None
                if not hit:
                    ent = build()
                    self._keep(key, ent, generation)
                    self._build_locks.pop(key, None)
        if stats is not None:
            stats.add("joinBuildCacheHits" if hit else "joinBuildCacheMisses",
                      1)
            stats.add("joinBuildCacheBytes", resident, "BYTE")
        return ent, hit

    def _keep(self, key: tuple, ent, generation: int) -> None:
        nb = int(ent.nbytes)
        with self._lock:
            if generation != self._generation or nb > self.max_bytes:
                return
            while self._entries and self._bytes + nb > self.max_bytes:
                _old, gone = self._entries.popitem(last=False)
                self._bytes -= int(gone.nbytes)
            self._entries[key] = ent
            self._bytes += nb

    def invalidate_all(self) -> int:
        with self._lock:
            n = len(self._entries)
            self._entries.clear()
            self._bytes = 0
            self._generation += 1
            return n

    def info(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries), "bytes": self._bytes,
                    "maxBytes": self.max_bytes}


JOIN_BUILD_CACHE = JoinBuildCache()


def invalidate_compiled() -> None:
    """A table changed: every cached program probed against its old
    contents and every build side materialised from them is stale."""
    from .fragments import FRAGMENT_JIT_CACHE
    FRAGMENT_JIT_CACHE.invalidate_all()
    JOIN_BUILD_CACHE.invalidate_all()
