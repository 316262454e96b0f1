"""Intra-task local exchange: repartition batches between pipelines
inside one task.

The analog of the reference's LocalExchange
(presto-main-base/.../operator/exchange/LocalExchange.java:62 with
PartitioningExchanger / BroadcastExchanger / round-robin) plus the
`task_concurrency` driver model (SqlTaskExecution.java:548 enqueues one
driver per split; TaskExecutor time-slices them).  Here a "driver" is a
Python thread draining one sub-pipeline: device dispatches are async, so
threads overlap HOST work (page serialization, split staging, host
string generation) with DEVICE work and with each other — the useful
concurrency on a single chip, where the accelerator itself serializes
kernels anyway.

LocalExchange is the single producer/consumer mechanism: bounded queues,
producer-finished accounting (LocalExchangeMemoryManager's bounded-buffer
role), and a close() path that unblocks producers when the consumer
stops early (downstream LIMIT, task cancellation, error) — producers use
timed puts and observe the stop flag, so no thread is ever left blocked
on a full queue.  background_drain and parallel_drain are thin drivers
over it.
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Callable, Iterator, List, Optional

import jax.numpy as jnp

from . import operators as ops
from ..utils.runtime_stats import current_span, current_stats
from ..utils.stack import roomy


class LocalExchange:
    """Bounded multi-producer multi-consumer batch router.

    partitioning: "ROUND_ROBIN" | "HASH" | "BROADCAST"
    (LocalPartitioningExchanger / BroadcastExchanger shapes).  HASH
    routes by key-hash % M so downstream consumers see disjoint key
    sets, the contract grouped consumers rely on.

    Exceptions may be pushed as items; consumers re-raise them.  close()
    stops producers (their next push returns False) and drains the
    queues so a blocked producer wakes up."""

    _DONE = object()

    def __init__(self, n_consumers: int, partitioning: str = "ROUND_ROBIN",
                 keys: Optional[List[str]] = None, capacity: int = 4):
        self.n_consumers = n_consumers
        self.partitioning = partitioning
        self.keys = keys or []
        self.queues = [queue.Queue(maxsize=capacity)
                       for _ in range(n_consumers)]
        self._rr = 0
        self._lock = threading.Lock()
        self._producers = 0
        self._finished = False
        self._stop = threading.Event()

    # -- producer side ----------------------------------------------------
    def add_producer(self) -> None:
        with self._lock:
            self._producers += 1

    def producer_finished(self) -> None:
        with self._lock:
            self._producers -= 1
            if self._producers == 0 and not self._finished:
                self._finished = True
                for q in self.queues:
                    self._put(q, self._DONE)

    def _put(self, q: "queue.Queue", item) -> bool:
        """Timed put observing the stop flag; False = exchange closed."""
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def push(self, batch) -> bool:
        """Route one batch; returns False when the exchange was closed
        (the producer should stop draining its pipeline)."""
        if self.partitioning == "BROADCAST":
            ok = True
            for q in self.queues:
                ok = self._put(q, batch) and ok
            return ok
        if self.partitioning == "HASH" and self.keys:
            import numpy as np
            cols = [batch.columns[k] for k in self.keys]
            h = np.asarray(ops.hash_columns(cols, 0x10CA1)) \
                % np.uint64(self.n_consumers)
            mask = np.asarray(batch.mask)
            ok = True
            for p in range(self.n_consumers):
                keep = jnp.asarray(mask & (h == p))
                ok = self._put(self.queues[p],
                               batch.with_mask(batch.mask & keep)) and ok
            return ok
        with self._lock:
            p = self._rr
            self._rr = (self._rr + 1) % self.n_consumers
        return self._put(self.queues[p], batch)

    # -- consumer side ----------------------------------------------------
    def consume(self, consumer: int) -> Iterator:
        q = self.queues[consumer]
        while True:
            item = q.get()
            if item is self._DONE:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    def close(self) -> None:
        """Consumer-side shutdown: stop producers and drain the queues so
        any producer blocked on a full queue wakes up and exits."""
        self._stop.set()
        for q in self.queues:
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break


def _owned(owner, parent_span: str):
    """The caller's RuntimeStats as owner of a helper thread's work (a
    no-op scope when the caller had none)."""
    if owner is None:
        return contextlib.nullcontext()
    return owner.activate(parent_span=parent_span)


def background_drain(it: Iterator, wall_out: Optional[list] = None,
                     capacity: int = 4):
    """Drain `it` on a background thread, yielding items as they arrive —
    the two-pipeline producer/consumer shape (pipeline drain overlapping
    serialization).  The producer's wall lands in wall_out[0] BEFORE the
    done signal, so a consumer that observed completion also observes the
    wall.  Closing the returned generator (early exit, cancellation)
    stops and unblocks the producer."""
    ex = LocalExchange(1, "ROUND_ROBIN", capacity=capacity)
    ex.add_producer()
    # the producer works for whoever asked: launches, host syncs and JAX
    # events on its thread belong to the caller's RuntimeStats
    owner, parent_span = current_stats(), current_span()

    def producer():
        t0 = time.perf_counter()  # lint: allow-wall-clock
        try:
            with _owned(owner, parent_span):
                for item in it:
                    if not ex.push(item):
                        return
        except BaseException as e:     # relayed to the consumer
            ex.push(e)
        finally:
            if wall_out is not None:
                wall_out[0] = time.perf_counter() - t0  # lint: allow-wall-clock
            ex.producer_finished()

    threading.Thread(target=roomy, args=(producer,), daemon=True,
                     name="local-exchange-drain").start()

    def gen():
        try:
            yield from ex.consume(0)
        finally:
            ex.close()
    return gen()


def parallel_drain(sources: List[Callable[[], Iterator]],
                   concurrency: int, stats: Optional[dict] = None):
    """Drain `sources` (thunks returning batch iterators) on up to
    `concurrency` driver threads through one LocalExchange, yielding
    batches as they arrive.

    Per-driver wall times land in stats["driver_walls"] (each written
    before its driver signals completion); sum(driver walls) - consumer
    wall > 0 is the measured overlap surfaced in EXPLAIN ANALYZE /
    TaskInfo, the same per-driver accounting TaskStats carries."""
    if concurrency <= 1 or len(sources) <= 1:
        for thunk in sources:
            yield from thunk()
        return
    n_threads = min(concurrency, len(sources))
    owner, parent_span = current_stats(), current_span()
    ex = LocalExchange(1, "ROUND_ROBIN", capacity=concurrency * 2)
    walls = [0.0] * len(sources)
    idx_q: "queue.Queue" = queue.Queue()
    for i in range(len(sources)):
        idx_q.put(i)

    def driver():
        while True:
            try:
                i = idx_q.get_nowait()
            except queue.Empty:
                return
            t0 = time.perf_counter()  # lint: allow-wall-clock
            try:
                for b in sources[i]():
                    if not ex.push(b):
                        return
            except BaseException as e:
                ex.push(e)
                return
            finally:
                walls[i] = time.perf_counter() - t0  # lint: allow-wall-clock

    for _ in range(n_threads):
        ex.add_producer()

    def run_driver():
        try:
            with _owned(owner, parent_span):
                driver()
        finally:
            ex.producer_finished()

    threads = []
    for _ in range(n_threads):
        t = threading.Thread(target=roomy, args=(run_driver,), daemon=True,
                             name="local-exchange-driver")
        threads.append(t)
        t.start()
    try:
        yield from ex.consume(0)
    finally:
        ex.close()
        if stats is not None:
            # drivers observe the stop flag within one timed-put window;
            # join briefly so every wall entry is final before snapshot
            for t in threads:
                t.join(timeout=1.0)
            stats["driver_walls"] = [round(w, 4) for w in walls]
