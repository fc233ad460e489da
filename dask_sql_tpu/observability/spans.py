"""Query-lifecycle span model.

The executor's per-plan-node `Tracer` (tracing.py) answers "where inside
the plan did device time go?" — but a served query spends most of its
*lifecycle* outside the plan walk: queue wait, parse, bind, verify,
estimate, per-rung XLA compiles, d2h transfer, wire serialization.  TQP
(arXiv:2203.01877) and Flare (arXiv:1703.08219) both lean on staged
instrumentation of compiled pipelines to attribute tensor-runtime time;
this module is that instrumentation for the whole engine:

- `QueryTrace`: one trace per query (Context API or Presto server), a flat
  list of `Span`s — sequential lifecycle *stages* that tile the request
  (queue_wait, plan_lookup, parse, bind, optimize, verify, estimate,
  cache_lookup, reuse, admit, execute, account, d2h, result_wait,
  serialize), *detail* spans nested inside a stage (per-rung XLA compiles,
  every jitted `launch` and every blocking device->host `fetch`, the
  executor's per-node tree), and zero-duration *events* (resilience-ladder
  degradations, breaker skips, estimator rung-proof skips).
- `load_trace` / `load_span`: the same model for `Context.create_table` —
  a ``load:<schema>.<table>`` trace whose ``load:convert`` / ``load:encode``
  / ``load:h2d`` / ``load:shard`` / ``load:register`` spans tile the
  registration, summed into the ``load.*_ms`` histograms.
- A `contextvars` activation scope: `activate(trace)` installs the trace
  for the current thread of control, so the planner, the ladder and the
  compiled pipelines can attach spans without threading a handle through
  every signature — and 8 Presto worker threads each see only their own
  trace (contextvars are per-thread for `threading.Thread` workers).
- Chrome-trace export (`to_chrome_trace`): the JSON the `trace event
  profiling` format of chrome://tracing / Perfetto loads directly,
  downloadable at ``/v1/trace/{qid}`` and emitted by
  ``EXPLAIN ANALYZE FORMAT JSON``.
- `timed_jit_call`: wraps a `jax.jit` callable invocation and records a
  ``compile:<rung>`` span + ``resilience.compile_ms.<rung>`` histogram +
  per-fingerprint profile entry whenever the call triggered a fresh XLA
  compile (detected via the jit cache-size delta).  The recorded wall time
  is the first-call time — trace + lower + XLA compile + first dispatch —
  which is the cost a cold fingerprint actually pays; warm calls record
  only their ``launch`` detail span.  What the first call spends in JAX's
  lowering, in XLA and in persistent-cache loads is observability/xla.py's:
  its ``xla:lower`` / ``xla:compile`` spans nest under ``compile:<rung>``.

Span clocks: `time.perf_counter()` (monotonic, process-wide comparable);
each trace also carries an epoch anchor so exported timestamps are
wall-clock meaningful.
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time
import uuid
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

#: span kinds: "stage" spans are the sequential lifecycle phases (disjoint
#: by construction), "detail" spans nest inside a stage (compiles, plan
#: nodes), "event" spans are zero-duration markers
STAGE, DETAIL, EVENT = "stage", "detail", "event"


@dataclass
class Span:
    name: str
    t0: float  # perf_counter seconds
    t1: Optional[float] = None  # None while open
    kind: str = STAGE
    parent: Optional[str] = None  # enclosing stage name for detail spans
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def dur_ms(self) -> Optional[float]:
        return None if self.t1 is None else (self.t1 - self.t0) * 1000.0


class QueryTrace:
    """All spans of one query, id'd and exportable.

    Spans are appended under a lock: the HTTP status-poll thread appends
    the serialize span while the trace already lives in the store."""

    def __init__(self, sql: Optional[str] = None, qid: Optional[str] = None,
                 metrics=None, profiles=None):
        self.trace_id = uuid.uuid4().hex[:16]
        self.qid = qid or self.trace_id
        self.sql = (sql or "").strip()[:500]
        #: the context's MetricsRegistry / ProfileStore, so span recorders
        #: deep in the engine (timed_jit_call) reach them without a Context
        self.metrics = metrics
        self.profiles = profiles
        self.fingerprint: Optional[str] = None
        self.spans: List[Span] = []
        #: qids of causally linked queries (a batch member links its
        #: leader, the leader links its members): /v1/trace/{qid} merges
        #: linked traces into one multi-process Chrome export so the flow
        #: arrows have both endpoints loaded
        self.links: List[str] = []
        self._lock = threading.Lock()
        self.created_perf = time.perf_counter()
        #: epoch - perf offset: export wall-clock timestamps from perf spans
        self.epoch_offset = time.time() - self.created_perf
        self.finished = False
        self.slow_logged = False
        #: rung of the newest `launch` span: the `fetch` that pulls its
        #: output carries the same ``rung`` attr
        self.last_rung: Optional[str] = None

    # ------------------------------------------------------------- writes
    def add_span(self, name: str, t0: float, t1: Optional[float],
                 kind: str = STAGE, parent: Optional[str] = None,
                 **attrs) -> Span:
        span = Span(name, t0, t1, kind, parent, dict(attrs))
        with self._lock:
            self.spans.append(span)
        return span

    @contextlib.contextmanager
    def span(self, name: str, kind: str = STAGE,
             parent: Optional[str] = None, **attrs):
        """Scoped span, appended OPEN at entry (t1=None) so a reader that
        renders mid-span — EXPLAIN ANALYZE reporting from inside its own
        execute stage — sees it as "(open)"; closed in the finally.  A
        failure inside is recorded on the span and re-raised unchanged."""
        span = self.add_span(name, time.perf_counter(), None, kind, parent,
                             **attrs)
        try:
            yield span.attrs  # callers may add attrs while the span is open
        except BaseException as exc:
            span.attrs["error"] = type(exc).__name__
            raise
        finally:
            span.t1 = time.perf_counter()

    def add_span_once(self, name: str, t0: float, t1: Optional[float],
                      kind: str = STAGE, parent: Optional[str] = None,
                      **attrs) -> bool:
        """Append unless a span of this name exists — one atomic
        check-and-add, so concurrent recorders (two status polls both
        serializing the same finished query) cannot duplicate a stage."""
        with self._lock:
            if any(s.name == name for s in self.spans):
                return False
            self.spans.append(Span(name, t0, t1, kind, parent, dict(attrs)))
            return True

    @contextlib.contextmanager
    def span_once(self, name: str, kind: str = STAGE,
                  parent: Optional[str] = None, **attrs):
        """`span` with `add_span_once`'s rule: the scoped span is recorded
        (open, under the same atomic check) only when no span of this name
        exists yet; otherwise the body runs unrecorded.  Yields whether it
        recorded."""
        with self._lock:
            span = None
            if not any(s.name == name for s in self.spans):
                span = Span(name, time.perf_counter(), None, kind, parent,
                            dict(attrs))
                self.spans.append(span)
        try:
            yield span is not None
        finally:
            if span is not None:
                span.t1 = time.perf_counter()

    def open_stage(self) -> Optional[str]:
        """Name of the stage open right now (stages never nest), if any."""
        with self._lock:
            for s in reversed(self.spans):
                if s.kind == STAGE and s.t1 is None:
                    return s.name
        return None

    def innermost_open(self) -> Optional[str]:
        """Name of the newest span still open (a stage or a detail)."""
        with self._lock:
            for s in reversed(self.spans):
                if s.t1 is None and s.kind != EVENT:
                    return s.name
        return None

    def event(self, name: str, **attrs) -> Span:
        t = time.perf_counter()
        return self.add_span(name, t, t, EVENT, **attrs)

    def link(self, qid: Optional[str]) -> None:
        """Record a causal link to another query's trace (idempotent)."""
        if not qid or qid == self.qid:
            return
        with self._lock:
            if qid not in self.links:
                self.links.append(qid)

    def finish(self, config=None, metrics=None) -> None:
        """Idempotent end-of-lifecycle hook: first call wins and runs the
        slow-query check (observability/slowlog.py)."""
        with self._lock:
            if self.finished:
                return
            self.finished = True
        if config is not None:
            from .slowlog import maybe_log_slow

            maybe_log_slow(self, config, metrics or self.metrics)

    # -------------------------------------------------------------- reads
    def has_span(self, name: str) -> bool:
        with self._lock:
            return any(s.name == name for s in self.spans)

    def stage_spans(self) -> List[Span]:
        """Closed lifecycle stages, sorted by start time."""
        with self._lock:
            out = [s for s in self.spans if s.kind == STAGE
                   and s.t1 is not None]
        return sorted(out, key=lambda s: s.t0)

    def total_ms(self) -> float:
        with self._lock:
            closed = [s for s in self.spans if s.t1 is not None]
        if not closed:
            return 0.0
        return (max(s.t1 for s in closed) - min(s.t0 for s in closed)) * 1e3

    def attach_node_tree(self, root, parent: str = "execute") -> None:
        """Fold an executor `NodeTrace` tree (tracing.py) in as detail
        spans — real timestamps (NodeTrace records its start), so the
        Chrome trace nests them inside the execute stage."""
        if root is None:
            return
        stack = [root]
        while stack:
            node = stack.pop()
            self.add_span(
                node.node_type, node.t0, node.t0 + node.wall_ms / 1e3,
                kind=DETAIL, parent=parent, label=node.label,
                rows=(node.rows if node.rows >= 0 else None))
            stack.extend(node.children)

    # ------------------------------------------------------------- export
    def chrome_events(self, pid: int = 1) -> List[Dict[str, Any]]:
        """This trace's Chrome-trace event list under process id ``pid``.
        Spans/events carrying ``flow_out`` / ``flow_in`` attrs (cross-query
        causality: batch member -> leader launch, background recompile ->
        trigger) additionally emit flow events (ph=s / ph=f) sharing a
        stable numeric id, so Perfetto draws the arrow — across processes
        when linked traces are merged into one export."""
        import zlib

        with self._lock:
            spans = list(self.spans)
        events: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": pid,
            "args": {"name": f"dask-sql-tpu query {self.qid}"},
        }, {
            "name": "thread_name", "ph": "M", "pid": pid, "tid": 1,
            "args": {"name": "query lifecycle"},
        }]
        for s in spans:
            ts = (s.t0 + self.epoch_offset) * 1e6
            args = {k: v for k, v in s.attrs.items() if v is not None}
            if s.parent:
                args["stage"] = s.parent
            if s.kind == EVENT:
                events.append({"name": s.name, "ph": "i", "ts": ts,
                               "pid": pid, "tid": 1, "s": "t", "args": args})
            else:
                dur = 0.0 if s.t1 is None else (s.t1 - s.t0) * 1e6
                events.append({"name": s.name, "ph": "X", "ts": ts,
                               "dur": dur, "cat": s.kind, "pid": pid,
                               "tid": 1, "args": args})
            for key, ph in (("flow_out", "s"), ("flow_in", "f")):
                flow = s.attrs.get(key)
                if flow is None:
                    continue
                ev = {"name": s.name, "cat": "dsql.flow", "ph": ph,
                      "id": zlib.crc32(str(flow).encode()), "ts": ts,
                      "pid": pid, "tid": 1}
                if ph == "f":
                    ev["bp"] = "e"  # bind to the enclosing slice
                events.append(ev)
        return events

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The Chrome `trace event profiling` JSON object (ph=X complete
        events, microsecond timestamps) chrome://tracing and Perfetto load
        directly.  Stages and their nested details share tid 1 (nesting by
        containment); events become ph=i instants."""
        with self._lock:
            links = list(self.links)
        return {
            "displayTimeUnit": "ms",
            "traceEvents": self.chrome_events(),
            "otherData": {
                "traceId": self.trace_id,
                "qid": self.qid,
                "sql": self.sql,
                "fingerprint": self.fingerprint,
                "links": links,
            },
        }

    def format_lines(self) -> List[str]:
        """The lifecycle header EXPLAIN ANALYZE prints above the node
        tree: one line per stage in start order, events inline."""
        with self._lock:
            spans = sorted(self.spans, key=lambda s: s.t0)
        lines = [f"-- query lifecycle (trace {self.trace_id}"
                 + (f", fingerprint {self.fingerprint}" if self.fingerprint
                    else "") + ") --"]
        for s in spans:
            if s.kind == DETAIL and not s.name.startswith("compile:"):
                continue  # the node tree renders itself below the header
            if s.kind == EVENT:
                lines.append(f"  !! {s.name}")
                continue
            dur = "(open)" if s.t1 is None else f"{s.dur_ms:10.2f} ms"
            pad = "    " if s.kind == DETAIL else "  "
            lines.append(f"{pad}{s.name:<14} {dur}")
        return lines


def merge_chrome_traces(traces: List["QueryTrace"]) -> Dict[str, Any]:
    """One Chrome-trace JSON over several causally linked traces — each
    query its own process row, flow arrows crossing between them (the
    ``/v1/trace/{qid}`` export when the trace carries links)."""
    events: List[Dict[str, Any]] = []
    for i, tr in enumerate(traces):
        events.extend(tr.chrome_events(pid=i + 1))
    head = traces[0]
    return {
        "displayTimeUnit": "ms",
        "traceEvents": events,
        "otherData": {
            "traceId": head.trace_id,
            "qid": head.qid,
            "sql": head.sql,
            "fingerprint": head.fingerprint,
            "merged": [tr.qid for tr in traces],
        },
    }


class TraceStore:
    """Bounded qid -> QueryTrace LRU; the backing store of
    ``/v1/trace/{qid}`` and `Context.last_trace`."""

    def __init__(self, keep: int = 256):
        self.keep = max(1, int(keep))
        self._lock = threading.Lock()
        self._traces: "OrderedDict[str, QueryTrace]" = OrderedDict()

    def put(self, qid: str, trace: QueryTrace) -> None:
        with self._lock:
            self._traces[qid] = trace
            self._traces.move_to_end(qid)
            while len(self._traces) > self.keep:
                self._traces.popitem(last=False)

    def get(self, qid: str) -> Optional[QueryTrace]:
        with self._lock:
            return self._traces.get(qid)

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)


# ---------------------------------------------------------------------------
# activation scope
# ---------------------------------------------------------------------------
_current: "contextvars.ContextVar[Optional[QueryTrace]]" = \
    contextvars.ContextVar("dsql_query_trace", default=None)


def current_trace() -> Optional[QueryTrace]:
    """The QueryTrace of the query running on this thread, if any."""
    return _current.get()


@contextlib.contextmanager
def activate(trace: Optional[QueryTrace]):
    """Install `trace` as the current trace for the dynamic extent."""
    token = _current.set(trace)
    try:
        yield trace
    finally:
        _current.reset(token)


def stage(name: str, **attrs):
    """Scoped stage span on the active trace — a no-op context manager
    when no trace is active, so instrumented code never branches.  Also
    stamps the stage onto the in-flight query table (live.py), which works
    with tracing disabled too."""
    from . import live

    live.update(stage=name)
    tr = current_trace()
    if tr is None:
        return contextlib.nullcontext({})
    return tr.span(name, kind=STAGE, **attrs)


def detail(name: str, parent: str = "execute", **attrs):
    """Scoped DETAIL span nested under ``parent`` on the active trace —
    a no-op context manager without one.  The streaming drive loop uses
    this so each partition renders as a child of the execute stage."""
    tr = current_trace()
    if tr is None:
        return contextlib.nullcontext({})
    return tr.span(name, kind=DETAIL, parent=parent, **attrs)


def trace_event(name: str, **attrs) -> None:
    """Zero-duration marker on the active trace (ladder degradations,
    breaker skips, rung-proof skips, admission sheds); no-op without one."""
    tr = current_trace()
    if tr is not None:
        tr.event(name, **attrs)


def fetch(nbytes: Optional[int] = None):
    """Scoped ``fetch`` DETAIL span around ONE blocking device->host pull
    (`utils.d2h_fetch` opens it at every pull site), nested under the stage
    open at the time: ``execute`` for a rung's packed-result pull, ``d2h``
    for the result's own `to_pandas`.  ``rung`` is the newest launch's;
    ``bytes`` where the size is known before the pull."""
    tr = current_trace()
    if tr is None:
        return contextlib.nullcontext({})
    return tr.span("fetch", kind=DETAIL, parent=tr.open_stage(),
                   rung=tr.last_rung, bytes=nbytes)


# ---------------------------------------------------------------------------
# load traces (Context.create_table)
# ---------------------------------------------------------------------------
LOAD_PHASES = ("convert", "encode", "h2d", "shard", "register")


class _LoadSink:
    """Where the ``load:*`` spans of one `create_table` go.  Phases nest in
    code (an ``h2d`` inside an ``encode`` inside a ``convert``) but are
    recorded as EXCLUSIVE segments: entering a child closes the parent's
    running segment and leaving it reopens one, so the recorded spans are
    sequential, disjoint and tile the call by construction."""

    def __init__(self, trace: Optional[QueryTrace], kind: str,
                 parent: Optional[str], metrics=None):
        self.trace, self.kind, self.parent = trace, kind, parent
        #: the loading context's registry (observability/xla.py observes a
        #: compile inside the load here, tracing on or off)
        self.metrics = metrics
        self.seconds = dict.fromkeys(LOAD_PHASES, 0.0)
        self.h2d_bytes = 0
        #: (phase, attrs, segments recorded so far) of the open phases
        self._stack: List[tuple] = []
        self._since = 0.0  # start of the running segment

    def phase(self) -> Optional[str]:
        """The innermost phase open right now, if any."""
        return self._stack[-1][0] if self._stack else None

    def _close_segment(self, now: float) -> None:
        if not self._stack or now <= self._since:
            return
        phase, _, segments = self._stack[-1]
        self.seconds[phase] += now - self._since
        if self.trace is not None:
            segments.append(self.trace.add_span(
                f"load:{phase}", self._since, now, kind=self.kind,
                parent=self.parent))

    def enter(self, phase: str, attrs: dict) -> None:
        now = time.perf_counter()
        self._close_segment(now)
        if self._stack and "column" not in attrs \
                and "column" in self._stack[-1][1]:
            attrs["column"] = self._stack[-1][1]["column"]
        self._stack.append((phase, attrs, []))
        self._since = now

    def leave(self) -> None:
        now = time.perf_counter()
        self._close_segment(now)
        phase, attrs, segments = self._stack.pop()
        for span in segments:  # every segment carries the phase's attrs
            span.attrs.update(attrs)
        if phase == "h2d":
            self.h2d_bytes += int(attrs.get("bytes") or 0)
        self._since = now


_load: "contextvars.ContextVar[Optional[_LoadSink]]" = \
    contextvars.ContextVar("dsql_load_sink", default=None)


@contextlib.contextmanager
def load_trace(context, schema_name: str, table_name: str):
    """The dynamic extent of one `Context.create_table`: `load_span`s inside
    record onto a trace of the load's own (``qid="load:<schema>.<table>"``,
    kept in ``context.traces``) as stages — or, when the registration runs
    inside a statement that already has a trace (``CREATE TABLE ... WITH``),
    onto that trace as DETAIL spans under ``execute``.  On exit the five
    phase sums land in the ``load.*_ms`` histograms and the bytes the ``h2d``
    spans carried in ``load.h2d_bytes`` (tracing on or off)."""
    tr, kind, parent, owned = current_trace(), DETAIL, "execute", False
    if tr is None and context._trace_enabled():
        name = f"{schema_name}.{table_name}"
        tr = QueryTrace(sql=f"create_table {name}", qid=f"load:{name}",
                        metrics=context.metrics, profiles=context.profiles)
        context.traces.put(tr.qid, tr)
        kind, parent, owned = STAGE, None, True
    sink = _LoadSink(tr, kind, parent, context.metrics)
    token = _load.set(sink)
    try:
        yield
    finally:
        _load.reset(token)
        metrics, seconds = context.metrics, sink.seconds
        metrics.observe("load.convert_ms", seconds["convert"] * 1e3)
        metrics.observe("load.encode_ms", seconds["encode"] * 1e3)
        metrics.observe("load.h2d_ms", seconds["h2d"] * 1e3)
        # 0 on an unsharded load: the five always tile the call
        metrics.observe("load.shard_ms", seconds["shard"] * 1e3)
        metrics.observe("load.register_ms", seconds["register"] * 1e3)
        metrics.inc("load.h2d_bytes", sink.h2d_bytes)
        if owned:
            tr.finish()


@contextlib.contextmanager
def load_span(phase: str, **attrs):
    """One phase of the running load (``convert`` / ``encode`` / ``h2d`` /
    ``shard`` / ``register``), exclusive of the phases nested inside it; a
    no-op outside `load_trace`, so `Column.from_numpy` at query time records
    nothing.
    Yields the span's attrs (``column``, ``encoding``, ``distinct``,
    ``bytes``, ``devices``) for the body to fill in."""
    sink = _load.get()
    if sink is None:
        yield attrs
        return
    sink.enter(phase, attrs)
    try:
        yield attrs
    finally:
        sink.leave()


# ---------------------------------------------------------------------------
# per-rung compile timing
# ---------------------------------------------------------------------------
#: (metrics, profiles, fingerprint, sql) of the executing query — installed
#: by TpuFrame.execute for EVERY execution, trace enabled or not, so
#: compile histograms and profiles never go dark when tracing is off
_sink: "contextvars.ContextVar[Optional[tuple]]" = \
    contextvars.ContextVar("dsql_compile_sink", default=None)


@contextlib.contextmanager
def compile_sink(metrics, profiles=None, fingerprint: Optional[str] = None,
                 sql: Optional[str] = None, family: Optional[str] = None):
    """Install the metric/profile destinations for `timed_jit_call` over
    the dynamic extent of one query execution.  `family` is the query's
    literal-stripped family fingerprint (families/), recorded on the
    profile entry so SHOW PROFILES can group and warm-up can dedupe."""
    token = _sink.set((metrics, profiles, fingerprint, sql, family))
    try:
        yield
    finally:
        _sink.reset(token)


class _RungCall:
    """The extent of one `timed_jit_call`: its rung, and the cache verdict
    (``hit`` / ``miss`` / ``off``) of each backend compile the listener of
    observability/xla.py saw inside it."""

    __slots__ = ("rung", "caches")

    def __init__(self, rung: str):
        self.rung = rung
        self.caches: List[str] = []


#: set for a `timed_jit_call`'s extent; the compile watchdog's helper thread
#: inherits it (resilience/watchdog.py copies the caller's context)
_rung_call: "contextvars.ContextVar[Optional[_RungCall]]" = \
    contextvars.ContextVar("dsql_rung_call", default=None)


def _jit_cache_size(fn) -> Optional[int]:
    try:
        return fn._cache_size()
    except Exception:  # dsql: allow-broad-except — jit internals are
        # version-dependent introspection; no size just means no timing
        return None


def timed_jit_call(rung: str, fn, *args, may_compile: Optional[bool] = None,
                   launch_attrs: Optional[dict] = None, **kwargs):
    """Invoke a `jax.jit` callable, recording the call as a fresh XLA
    compile for `rung` when the jit's executable cache grew.

    Recorded on EVERY call when a trace is active: a ``launch`` detail span
    (attr ``rung``, plus the caller's ``launch_attrs``: the sharded rungs
    give ``devices`` and ``rows_per_device``) around the call.  Recorded
    only on a compile: a ``resilience.compile_ms.<rung>``
    histogram observation and a per-fingerprint ProfileStore entry (via the
    installed `compile_sink` — independent of tracing, so SHOW METRICS and
    the pre-warm input stay populated with tracing disabled), plus a
    ``compile:<rung>`` detail span under ``launch``, over the same interval,
    when a trace is active.  The call's extent is a `_RungCall`: the
    ``xla:lower`` / ``xla:compile`` spans of observability/xla.py carry its
    ``rung`` and nest under ``compile:<rung>``, and the cache verdicts of
    its compiles give the span's ``persistent_hit`` (True: loaded from the
    persistent cache, False: XLA compiled, None: no cache directory).

    ``may_compile`` is the caller's hint about whether THIS call can
    trigger a fresh compile (False = the shape is known-warm).  When a
    compile is possible and ``resilience.compile_timeout_ms`` is set, the
    call runs under the compile watchdog (resilience/watchdog.py): a hung
    or exploding compile raises a degradable `CompileTimeoutError` instead
    of wedging the serving worker."""
    metrics = profiles = fingerprint = sql = family = None
    sink = _sink.get()
    if sink is not None:
        metrics, profiles, fingerprint, sql, family = sink
    tr = current_trace()
    if tr is not None and metrics is None:
        metrics = tr.metrics
    before = _jit_cache_size(fn)
    call = _RungCall(rung)
    token = _rung_call.set(call)
    t0 = time.perf_counter()
    try:
        deadline_ms = None
        if may_compile is not False:
            from ..config import config as _config
            from ..resilience import faults, watchdog

            deadline_ms = watchdog.timeout_ms(_config)
        if deadline_ms is not None:
            out = watchdog.watched_call(
                rung, fn, args, kwargs, deadline_ms=deadline_ms,
                hang_s=faults.hang_duration("compile_hang", _config),
                metrics=metrics)
        else:
            out = fn(*args, **kwargs)
    finally:
        _rung_call.reset(token)
    t1 = time.perf_counter()
    if tr is not None:
        # every call, warm or cold: the host's side of the dispatch (a warm
        # call returns before the device is done; the wait shows in `fetch`)
        tr.add_span("launch", t0, t1, kind=DETAIL,
                    parent=tr.open_stage() or "execute", rung=rung,
                    **(launch_attrs or {}))
        tr.last_rung = rung
    if before is None:
        return out
    after = _jit_cache_size(fn)
    if after is None or after <= before:
        return out
    ms = (t1 - t0) * 1000.0
    persistent_hit = (False if "miss" in call.caches
                      else True if "hit" in call.caches else None)
    if tr is not None:
        fingerprint = tr.fingerprint or fingerprint
        tr.add_span(f"compile:{rung}", t0, t1, kind=DETAIL, parent="launch",
                    rung=rung, fingerprint=fingerprint,
                    persistent_hit=persistent_hit)
        profiles = profiles if profiles is not None else tr.profiles
        sql = sql or tr.sql
    if metrics is not None:
        metrics.observe(f"resilience.compile_ms.{rung}", ms)
    if profiles is not None and fingerprint:
        profiles.record_compile(fingerprint, rung, ms, sql=sql,
                                family=family)
    return out
