"""The program cache of the compiled rungs.

Every compiled rung (physical/compiled*.py, spmd/, streaming/) keeps its
compiled programs in one `ProgramCache`, and everything that is the same
for all of them lives here: how a program is found, built once when
several requests miss together (single-flight), rebuilt off the request's
path when its table changed (`defer_rebuild`), counted as a family hit and
handed to the family batcher.  A rung module keeps what is its own:
eligibility, the two halves of its key and its constructor
(docs/architecture.md, "What a compiled rung module contains").

The key is a PAIR the rung builds apart, never one tuple cut by position:

- ``family``: everything that shapes the program (plan text after
  parameterisation, projection, mesh, the configured segment-sum mode,
  model shape, sort and limit windows);
- ``bucket``: the identity of the table(s) it was built against
  (``(uid, num_rows, padded_rows)``; the joins add their build rows).

A miss for a family this context compiled under ANOTHER bucket means the
table grew or was replaced: that is the background-recompile trigger.

The caches are process-wide (one per rung module) and guarded by the
calling context's ``_plan_lock``; constructions and compiles happen
outside it, serialised per key by `_building`.
"""
from __future__ import annotations

import logging
import threading
import time
import uuid
from collections import OrderedDict
from typing import Callable, Dict, Hashable, List, Optional, Tuple

logger = logging.getLogger(__name__)

#: cap on the per-context compiled-family map (context._compiled_families)
_FAMILY_CAP = 256
#: cap on a cache's memo of plan shapes known ineligible (`decline`)
_DECLINED_CAP = 256

#: in-flight constructions, key -> Event: concurrent same-family misses
#: wait for the first builder instead of paying duplicate XLA compiles
#: (cold fan-in of a family is exactly the batcher's target workload)
_building: Dict[Tuple, threading.Event] = {}
_building_lock = threading.Lock()
_BUILD_WAIT_S = 300.0


def singleflight_begin(key: Tuple):
    """(is_builder, event) for a compiled-cache miss; a non-builder should
    ``event.wait`` then re-check the cache.  Builders MUST call
    `singleflight_done(key)` in a finally."""
    with _building_lock:
        ev = _building.get(key)
        if ev is None:
            ev = _building[key] = threading.Event()
            return True, ev
        return False, ev


def singleflight_done(key: Tuple) -> None:
    with _building_lock:
        ev = _building.pop(key, None)
    if ev is not None:
        ev.set()


def singleflight_get_or_build(ctx, cache: "ProgramCache", key: Tuple, build):
    """THE miss-handling protocol of every compiled rung: lock-guarded
    lookup; on a miss, one builder constructs while concurrent same-key
    misses wait and reuse; a waiter whose builder failed or declined falls
    through and builds under its own query's policy.  `build()` constructs,
    inserts into `cache` and returns the program — or None to decline (the
    background-recompile deferral).  Returns (program_or_None, built_here):
    built_here=False means this query REUSED an executable another query
    paid for (the family-hit accounting hook)."""
    program = cache.lookup(ctx, key)
    if program is not None:
        return program, False
    token = (cache.rung, key)
    # builder=False means no token was taken; the builder path settles in
    # the shared finally below — flag-correlated, invisible to the CFG
    # dsql: allow-unpaired-effect — settled in the finally when builder
    builder, build_ev = singleflight_begin(token)
    if not builder:
        build_ev.wait(_BUILD_WAIT_S)
        program = cache.lookup(ctx, key)
        if program is not None:
            return program, False
        # the builder failed or declined; build here so the failure
        # surfaces under this query's own policy
        # dsql: allow-unpaired-effect — settled in the finally when builder
        builder, build_ev = singleflight_begin(token)
    try:
        return build(), True
    finally:
        if builder:
            singleflight_done(token)


def defer_rebuild(ctx, cache: "ProgramCache", family: Hashable, bucket: Tuple,
                  build_and_warm) -> bool:
    """THE background-recompile deferral of every compiled rung that warms
    (single-chip and SPMD alike), beside the single-flight protocol so the
    two halves of the miss-handling policy cannot drift: a SEEN family
    whose table bucket changed (growth / replacement) rebuilds and compiles
    on the background thread while the triggering query serves on a lower
    rung, instead of paying a foreground XLA compile on the serving path.

    ``build_and_warm()`` constructs the program, runs it once to compile
    and returns it with its table references dropped; it executes under
    the captured per-query config view and a metrics compile sink.  Returns
    True when deferred (the caller then declines the rung)."""
    bg = ctx.background_compiler()
    if bg is None:
        return False
    rung = cache.rung
    remembered = (rung, family)
    with ctx._plan_lock:
        stored = ctx._compiled_families.get(remembered)
    if stored is None or stored == bucket:
        # first sight of the family, or plain LRU eviction of an unchanged
        # table: foreground compile as before — deferral is only for
        # actual growth/replacement
        return False
    # thread-local per-query config overlays are invisible on the bg
    # thread; capture the effective view so the rebuild matches its key
    effective = dict(ctx.config.effective_items())
    # causality: the background recompile points back at the query whose
    # cache miss triggered it — a flow link from the trigger's deferral
    # event into the recompile span the bg thread appends, plus a
    # flight-recorder event carrying the trigger's qid
    from ..observability import current_trace, trace_event

    trigger_trace = current_trace()
    flow_id = f"bg:{rung}:{uuid.uuid4().hex[:12]}"

    def task():
        t0 = time.perf_counter()
        try:
            from .. import observability

            with ctx.config.set(effective), \
                    observability.compile_sink(ctx.metrics):
                program = build_and_warm()
            with ctx._plan_lock:
                cache.insert_locked(ctx, family, bucket, program,
                                    remember=True)
            observability.flight.record(
                "bg.recompile", rung=rung,
                qid=trigger_trace.qid if trigger_trace is not None
                else None)
            if trigger_trace is not None:
                # append the recompile to the TRIGGERING query's trace (it
                # may already be finished — spans still append), with the
                # flow arrow from its deferral event
                trigger_trace.add_span(
                    f"bg_recompile:{rung}", t0, time.perf_counter(),
                    kind="detail", parent="execute", rung=rung,
                    flow_in=flow_id)
        except BaseException:
            # un-mark the family: the next query takes the foreground path
            # where the ladder/breaker apply their normal failure policy
            with ctx._plan_lock:
                ctx._compiled_families.pop(remembered, None)
            raise

    task_key = (rung, family, bucket)
    # while the compile is pending, every query of the family keeps
    # declining (still served on a lower rung) instead of compiling anyway
    if not bg.pending(task_key) and not bg.submit(task_key, task):
        return False
    ctx.metrics.inc("serving.bg_compile.deferred")
    trace_event(f"bg_compile_deferred:{rung}", flow_out=flow_id)
    logger.debug("%s family bucket changed; compiling in background and "
                 "serving a lower rung", rung)
    return True


def _remember_family_locked(ctx, family: Hashable, bucket: Tuple) -> None:
    """Record a compiled plan family -> table bucket on the context
    (caller holds the plan lock); bounded crudely — family memory is an
    optimization hint only.  The bucket is the growth EVIDENCE: a later
    cache miss defers to background only when the table identity actually
    changed, so plain LRU eviction of an unchanged plan recompiles in the
    foreground as before instead of being misread as growth."""
    if len(ctx._compiled_families) >= _FAMILY_CAP:
        ctx._compiled_families.clear()
    ctx._compiled_families[family] = bucket


class ProgramCache:
    """The bounded LRU of one rung's compiled programs, keyed on the pair
    ``(family, bucket)`` (module docstring).  One per rung module, process
    wide: ``PROGRAMS = ProgramCache("spmd_aggregate", 16)``."""

    def __init__(self, rung: str, cap: int):
        self.rung = rung
        self.cap = cap
        self._programs: "OrderedDict[Tuple[Hashable, Tuple], object]" = \
            OrderedDict()
        self._declined: set = set()

    # ------------------------------------------------------------ the miss
    def get_or_build(self, ctx, family: Hashable, bucket: Tuple,
                     construct: Callable[[], object], *,
                     warm: Optional[Callable[[object], object]] = None,
                     params: Tuple = ()) -> Tuple[Optional[object], bool]:
        """The program of ``(family, bucket)`` and whether THIS call built
        it.  A hit is one lookup under the plan lock.  A miss runs the
        single-flight: ``construct()`` returns the program with its table
        references dropped (cached programs must not pin a table's HBM),
        the cache inserts it and evicts down to `cap`.

        Only a rung that passes ``warm`` (``lambda program: program.run(
        table, params)``) remembers ``family -> bucket`` on the context
        and, for a family seen under another bucket, hands the construction
        and the warming run to the background compiler: that call returns
        ``(None, False)`` and the query is served on a lower rung.

        A reuse with `params` is the family discipline at work (executable
        reuse across literals): it counts ``families.hit``."""
        program = self.lookup(ctx, (family, bucket))
        built_here = False
        if program is None:
            program, built_here = self._miss(ctx, family, bucket, construct,
                                             warm)
            if program is None:
                return None, False
        if not built_here and params:
            ctx.metrics.inc("families.hit")
            from ..observability import trace_event

            trace_event("family_hit", rung=self.rung, params=len(params))
        return program, built_here

    def _miss(self, ctx, family, bucket, construct, warm):
        def build_and_warm():
            program = construct()
            # compiles every kernel with the triggering query's params as
            # runtime arguments; the result is discarded
            warm(program)
            return program

        def build():
            if warm is not None and defer_rebuild(ctx, self, family, bucket,
                                                  build_and_warm):
                return None  # served on a lower rung this time
            program = construct()
            with ctx._plan_lock:
                self.insert_locked(ctx, family, bucket, program,
                                   remember=warm is not None)
            return program

        return singleflight_get_or_build(ctx, self, (family, bucket), build)

    def lookup(self, ctx, key: Tuple) -> Optional[object]:
        with ctx._plan_lock:
            program = self._programs.get(key)
            if program is not None:
                self._programs.move_to_end(key)
            return program

    def insert_locked(self, ctx, family: Hashable, bucket: Tuple, program,
                      remember: bool) -> None:
        """Insert and evict to `cap` (caller holds the plan lock: server
        worker threads and the background compiler share the dict)."""
        self._programs[(family, bucket)] = program
        while len(self._programs) > self.cap:
            self._programs.popitem(last=False)
        if remember:
            _remember_family_locked(ctx, (self.rung, family), bucket)

    # ---------------------------------------------------------- the launch
    def run(self, ctx, family: Hashable, bucket: Tuple, program, params,
            solo: Callable[[], object],
            batched: Optional[Callable[[List], List]] = None):
        """Launch through the family batcher when there is one, the query
        has parameters to stack, the rung gave a `batched` launch and the
        program does not rule it out (``batchable``); else ``solo()``."""
        if batched is not None and params \
                and getattr(program, "batchable", True):
            from .. import families

            batcher = families.batcher_of(ctx)
            if batcher is not None:
                return batcher.run((self.rung, family, bucket), params,
                                   solo=solo, batched=batched)
        return solo()

    # ------------------------------------------------- the decline memo
    def declined(self, key: Hashable) -> bool:
        """Plan shapes the rung found ineligible (the join rungs: checked
        before any build-side execution)."""
        return key in self._declined

    def decline(self, key: Hashable) -> None:
        # keys carry per-version table uids, so long sessions with
        # refreshed tables would grow the memo forever; reset wholesale at
        # a small cap (re-declining is cheap: one plan walk)
        if len(self._declined) >= _DECLINED_CAP:
            self._declined.clear()
        self._declined.add(key)

    # ------------------------------------- for tests, smokes and DROP MODEL
    def items(self) -> List[Tuple[Tuple[Hashable, Tuple], object]]:
        """``((family, bucket), program)``, least recently used first."""
        return list(self._programs.items())

    def values(self) -> List[object]:
        return list(self._programs.values())

    def clear(self) -> None:
        self._programs.clear()
        self._declined.clear()

    def evict(self, ctx, predicate: Callable[[Hashable, Tuple], bool]) -> None:
        """Drop every program whose ``(family, bucket)`` the predicate
        names.  The snapshot retries if a concurrent insert under ANOTHER
        context's plan lock mutates the dict mid-iteration (ROADMAP D13)."""
        with ctx._plan_lock:
            stale: List[Tuple] = []
            for _ in range(8):
                try:
                    stale = [k for k in self._programs if predicate(*k)]
                    break
                except RuntimeError:  # another context's insert raced us
                    continue
            for k in stale:
                self._programs.pop(k, None)
