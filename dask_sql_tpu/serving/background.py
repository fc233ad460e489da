"""Background recompilation: ladder recompiles off the critical path.

The compiled rungs cache their programs keyed on the pair (family, bucket)
(physical/programs.py: plan shape; table uid and row bucket) — when a table
is replaced or grows past its pow2 bucket, the key misses and the next
query pays a full foreground XLA compile on the serving path.  This module
moves that recompile off the critical path: when a *known plan family*
(same shape, new bucket) misses, the query is served on the interpreted
rung while a bounded background thread rebuilds and compiles the new
pipeline, then swaps it into the rung's `ProgramCache` under the plan-cache
lock (`Context._plan_lock`).  Subsequent queries hit the fresh executable.

Discipline: one daemon thread, a bounded pending queue (past the bound
submissions are dropped and the query simply compiles in the foreground
next time), per-family dedup so a hot family enqueues once, and every
compile inside a task runs through `timed_jit_call` — so the compile
watchdog (resilience/watchdog.py) and the persistent executable cache
(compile_cache.py) apply to background compiles exactly as they do to
foreground ones.  A failed task un-marks its family: the next query takes
the foreground path and the degradation ladder handles the failure with
its normal taxonomy/breaker policy.

Off by default (``serving.bg_compile.enabled``): trading the first
post-growth query's latency for an interpreted-rung execution is a
serving-fleet tradeoff, not a notebook default.
"""
from __future__ import annotations

import atexit
import logging
import threading
import time
import weakref
from collections import deque
from typing import Callable, Optional, Set, Tuple

logger = logging.getLogger(__name__)

#: live compilers drained at interpreter exit — a daemon thread killed by
#: teardown mid-XLA segfaults the process (same hazard as warmup.py)
_live: "weakref.WeakSet[BackgroundCompiler]" = weakref.WeakSet()
_ATEXIT_JOIN_S = 10.0


@atexit.register
def _drain_at_exit() -> None:
    compilers = list(_live)
    for c in compilers:
        c.cancel()
    for c in compilers:
        c.join(_ATEXIT_JOIN_S)


class BackgroundCompiler:
    """Single bounded daemon worker running compile-and-swap tasks."""

    def __init__(self, metrics=None, max_pending: int = 8,
                 suspended: Optional[Callable[[], bool]] = None):
        self.metrics = metrics
        self.max_pending = max(1, int(max_pending))
        #: pressure gate (resilience/pressure.py): when this returns True
        #: (YELLOW band or worse) submissions are deferred — the caller
        #: falls back to the foreground path and re-submits on a later
        #: miss, so background compiles resume once headroom recovers
        self.suspended = suspended
        self._cv = threading.Condition()
        self._queue: "deque[Tuple[object, Callable[[], None]]]" = deque()
        self._pending: Set[object] = set()
        self._shutdown = False
        self._thread: Optional[threading.Thread] = None

    @classmethod
    def from_config(cls, config, metrics=None,
                    suspended=None) -> "BackgroundCompiler":
        return cls(metrics=metrics,
                   max_pending=int(config.get(
                       "serving.bg_compile.max_pending", 8)),
                   suspended=suspended)

    # ------------------------------------------------------------- submit
    def submit(self, key, task: Callable[[], None]) -> bool:
        """Enqueue ``task`` under dedup key; False = dropped (full, dup,
        shut down, or deferred under HBM pressure) — the caller should
        fall back to the foreground path."""
        if self.suspended is not None and self.suspended():
            if self.metrics is not None:
                self.metrics.inc("resilience.pressure.suspended")
            return False
        with self._cv:
            if self._shutdown or key in self._pending:
                return False
            if len(self._queue) >= self.max_pending:
                if self.metrics is not None:
                    self.metrics.inc("serving.bg_compile.dropped")
                return False
            self._pending.add(key)
            self._queue.append((key, task))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="dsql-bg-compile")
                _live.add(self)
                self._thread.start()
            self._cv.notify()
        if self.metrics is not None:
            self.metrics.inc("serving.bg_compile.submitted")
        return True

    def pending(self, key) -> bool:
        with self._cv:
            return key in self._pending

    # ------------------------------------------------------------- worker
    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._shutdown:
                    self._cv.wait()
                if self._shutdown:
                    return
                key, task = self._queue.popleft()
            t0 = time.perf_counter()
            try:
                task()
            except Exception:  # dsql: allow-broad-except — a background
                # compile failure must not kill the worker; the family is
                # un-marked by the task's own cleanup and the next query
                # takes the foreground path where the ladder applies policy
                if self.metrics is not None:
                    self.metrics.inc("serving.bg_compile.failed")
                logger.warning("background compile failed", exc_info=True)
            else:
                if self.metrics is not None:
                    self.metrics.inc("serving.bg_compile.completed")
                    self.metrics.observe(
                        "serving.bg_compile.ms",
                        (time.perf_counter() - t0) * 1000.0)
            finally:
                with self._cv:
                    self._pending.discard(key)
                    self._cv.notify_all()

    # ----------------------------------------------------------- lifecycle
    def cancel(self) -> None:
        """Drop queued tasks and stop the worker after the in-flight one."""
        with self._cv:
            self._shutdown = True
            self._queue.clear()
            self._pending.clear()
            self._cv.notify_all()

    def join(self, timeout: Optional[float] = None) -> None:
        t = self._thread
        if t is not None:
            t.join(timeout)

    def wait_idle(self, timeout: float = 30.0) -> bool:
        """Block until every submitted task finished (tests/bench)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._pending or self._queue:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cv.wait(remaining)
        return True
