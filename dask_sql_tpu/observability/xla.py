"""Every XLA compile and persistent-cache load, as spans and histograms.

ONE process-wide JAX monitoring listener (registered at import, whether or
not the persistent executable cache is enabled) reads the three events JAX
stamps around each compile, on the compiling thread:

- ``/jax/core/compile/jaxpr_trace_duration`` and
  ``/jax/core/compile/jaxpr_to_mlir_module_duration``: merged into ONE
  ``xla:lower`` span per lowering (the trace of the same function that
  precedes it, nested traces included, plus the lowering);
- ``/jax/core/compile/backend_compile_duration``: an ``xla:compile`` span.
  It wraps ``compile_or_get_cached``, so the cache events fired inside it
  (``compile_requests_use_cache``, ``cache_hits``) set per-thread flags that
  the compile's end consumes: ``cache`` is ``hit`` (read, deserialized and
  loaded from disk), ``miss`` (the cache was consulted and XLA compiled) or
  ``off`` (no cache directory).

Each span lands on the trace active in the compiling thread (a query's, or
the ``load:<schema>.<table>`` trace of a `create_table`), as a DETAIL span
with attrs ``fun`` (the jitted function, or the primitive of an eager op:
``sort``, ``scatter-add``), ``cache`` and, inside `timed_jit_call`, ``rung``;
``parent`` is ``compile:<rung>`` there, else the innermost span open at the
time (``join:build``, ``execute``, ``load:encode`` ..).  JAX's wall stamps
are moved onto ``time.perf_counter()`` once per event (end = now), the clock
of every other span.

Tracing on or off, each event is observed into the registry of the query's
`compile_sink`, else the trace's, else the load's context:
``xla.lower_ms``, ``xla.compile_ms`` (compiles XLA ran: ``cache`` != hit)
and ``xla.cache_load_ms`` (``cache`` == hit).  Process totals (`totals`)
count every compile, attributed or not; ``compile_cache.stats()`` reports
them.  The flight recorder gets ``compile.start`` / ``compile.end`` for
every backend compile, at JAX's own stamps.
"""
from __future__ import annotations

import re
import threading
import time
from typing import Dict, Optional

import jax
from jax._src import monitoring

from . import flight
from .spans import DETAIL, _load, _rung_call, _sink, current_trace

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
USE_CACHE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: the three histograms every Context creates empty (`declare`), so a run
#: in which everything came from the cache reads 0, never an absent metric
HISTOGRAMS = ("xla.lower_ms", "xla.compile_ms", "xla.cache_load_ms")

_lock = threading.Lock()
_totals: Dict[str, float] = {"compiles": 0, "hits": 0, "misses": 0,
                             "compile_s": 0.0, "lower_s": 0.0,
                             "cache_load_s": 0.0}
#: per thread: the newest jaxpr trace not yet lowered, and the cache flags
#: of the backend compile running on this thread
_local = threading.local()
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def _fun(name) -> str:
    """``jit(_program)`` -> ``_program``: the lowering and the compile name
    the module after the function the trace event names bare."""
    name = str(name or "")
    m = _WRAPPED.match(name)
    return m.group(1) if m else name


def _on_event(event: str, **kwargs) -> None:
    if event == USE_CACHE_EVENT:
        _local.consulted = True
    elif event == HIT_EVENT:
        _local.hit = True


def _on_time_span(event: str, start: float, end: float, **kwargs) -> None:
    if event == TRACE_EVENT:
        # nested traces report first: the newest is the outermost one
        _local.traced = (start, end, _fun(kwargs.get("fun_name")))
        return
    if event not in (LOWER_EVENT, COMPILE_EVENT):
        return
    fun = _fun(kwargs.get("fun_name"))
    if event == LOWER_EVENT:
        traced = getattr(_local, "traced", None)
        _local.traced = None
        if traced is not None and traced[2] == fun and traced[1] <= end:
            start = min(start, traced[0])
        _record("xla:lower", fun, None, start, end)
        return
    hit = getattr(_local, "hit", False)
    consulted = getattr(_local, "consulted", False)
    _local.hit = _local.consulted = False
    # JAX consults its cache object even with no directory set: that is "off"
    cache = "hit" if hit else ("miss" if consulted
                               and jax.config.jax_compilation_cache_dir
                               else "off")
    _record("xla:compile", fun, cache, start, end)


#: cache verdict -> (histogram, process total) an event's seconds go to
_SINKS = {None: ("xla.lower_ms", "lower_s"),
          "hit": ("xla.cache_load_ms", "cache_load_s"),
          "miss": ("xla.compile_ms", "compile_s"),
          "off": ("xla.compile_ms", "compile_s")}


def _parent(tr, load, call) -> Optional[str]:
    if call is not None:
        return f"compile:{call.rung}"
    phase = load.phase() if load is not None else None
    if phase is not None:
        return f"load:{phase}"
    return tr.innermost_open() if tr is not None else None


def _record(name: str, fun: str, cache: Optional[str], start: float,
            end: float) -> None:
    """One event, stamped ``start``..``end`` on JAX's wall clock: moved
    onto `perf_counter` (it ended now), counted, observed, and spanned."""
    t1 = time.perf_counter()
    seconds = end - start
    t0 = t1 - seconds
    hist, total = _SINKS[cache]
    with _lock:
        _totals[total] += seconds
        if cache is not None:
            _totals["compiles"] += 1
        if cache == "hit":
            _totals["hits"] += 1
        elif cache == "miss":
            _totals["misses"] += 1
    call, load, sink = _rung_call.get(), _load.get(), _sink.get()
    tr = current_trace()
    if tr is None and load is not None:
        tr = load.trace
    metrics = sink[0] if sink is not None else None
    if metrics is None:
        metrics = tr.metrics if tr is not None and tr.metrics is not None \
            else (load.metrics if load is not None else None)
    if metrics is not None:
        metrics.observe(hist, seconds * 1e3)
        if cache == "hit":
            metrics.inc("resilience.compile_cache.hit")
        elif cache == "miss":
            metrics.inc("resilience.compile_cache.miss")
    rung = call.rung if call is not None else None
    if cache is not None and call is not None:
        call.caches.append(cache)
    if tr is not None:
        attrs = {"fun": fun}
        if rung is not None:
            attrs["rung"] = rung
        if cache is not None:
            attrs["cache"] = cache
        tr.add_span(name, t0, t1, kind=DETAIL,
                    parent=_parent(tr, load, call), **attrs)
    if cache is None:
        return
    qid = tr.qid if tr is not None else None
    if qid is None:
        from ..serving.runtime import current_ticket

        ticket = current_ticket()
        qid = ticket.qid if ticket is not None else None
    flight.record("compile.start", qid=qid, ts=start, fun=fun, cache=cache,
                  rung=rung)
    flight.record("compile.end", qid=qid, ts=end, fun=fun, cache=cache,
                  rung=rung, ms=round(seconds * 1e3, 3))


def totals() -> Dict[str, float]:
    """Process totals since import: ``compiles`` (every backend compile:
    XLA's or a cache load), ``hits`` / ``misses`` (of the persistent cache;
    the rest ran with none), and the seconds of ``compile_s`` (XLA),
    ``lower_s`` (trace + lowering) and ``cache_load_s``."""
    with _lock:
        return dict(_totals)


def declare(metrics) -> None:
    """Create the three histograms empty on a fresh registry."""
    for name in HISTOGRAMS:
        metrics.declare(name)


monitoring.register_event_listener(_on_event)
monitoring.register_event_time_span_listener(_on_time_span)
