"""Graceful-degradation ladder: compiled -> interpreted -> CPU backend.

TQP (arXiv:2203.01877) and Flare (arXiv:1703.08219) both observe that a
compiled/native execution path needs an explicit fallback ladder to stay as
robust as the interpreted engine it replaced.  This engine already had the
*shape* of a ladder — every compiled planner returns None to decline — but a
compile crash or device OOM inside a rung surfaced as a raw traceback.  This
module makes stepping down an explicit, observable policy:

- `attempt` wraps one rung (compiled select/aggregate/join pipeline, the
  distributed collectives engine): a *degradable* taxonomy error steps down
  to the next rung instead of failing the query, and the step is recorded in
  the MetricsRegistry (``resilience.degraded.<rung>``) and the executor's
  tracer, so `SHOW METRICS LIKE 'resilience.%'` and EXPLAIN ANALYZE show
  every degradation.
- A per-(plan-fingerprint, rung) circuit breaker (resilience/retry.py) skips
  a rung that repeatedly fails for the same query shape — the next
  submission goes straight to its known-good rung instead of re-failing.
- `execute_interpreted` is the bottom of the device ladder: if even the
  per-op interpreted path hits a degradable failure (device OOM), it
  re-executes the plan on the CPU backend — host DRAM instead of HBM —
  before giving up.

Rung names wired through the engine (sharded SPMD rungs sit ABOVE their
single-chip counterparts and fire only for mesh-sharded scans; each is its
own breaker entity per (family, rung), so a flaky SPMD path degrades to
single-chip without poisoning the family):

    streamed_select         streaming/select.py chunked root select chain
                            (fires only for admission-routed oversize plans)
    streamed_aggregate      streaming/aggregate.py morsel partial-state
                            aggregation with time-axis combines (ditto)
    compiled_predict        physical/compiled_predict.py fused PREDICT:
                            model inference in the scan's executable
                            (fires only for root PredictModelNode plans;
                            steps down to the host predict path)
    spmd_select             spmd/select.py shard_map root select chain
    spmd_aggregate          spmd/aggregate.py psum tree-reduce aggregation
    spmd_join_aggregate     spmd/join.py broadcast-join SPMD pipeline
    compiled_select         physical/compiled_select.py one-kernel root chain
    compiled_join_aggregate physical/compiled_join.py scan->joins->aggregate
    compiled_aggregate      physical/compiled.py whole-pipeline aggregate jit
    dist_aggregate          parallel/dist_plan.py collectives engine
    dist_sort               parallel/dist_plan.py range-partition sort
    interpreted             the eager per-op converter walk
    cpu                     the same walk under jax.default_device(cpu)
"""
from __future__ import annotations

import hashlib
import logging
import time
from typing import Callable, Optional, TypeVar

from ..observability import trace_event
from .errors import QueryError, ResourceExhaustedError, classify
from . import faults

logger = logging.getLogger(__name__)

T = TypeVar("T")

#: rung-name prefixes whose FIRST run for a family pays an XLA compile —
#: the candidates for cost-based selection (interpreted / cpu / dist rungs
#: never pre-pay a compile worth skipping)
_COMPILE_RUNG_PREFIXES = ("compiled_", "spmd_")

#: rungs that write their own richer ``rung:<name>`` span where they answer
#: (streaming/select.py, streaming/aggregate.py, physical/compiled_predict.py)
_SELF_REPORTING_RUNGS = ("streamed_", "compiled_predict")


def plan_fingerprint(rel) -> str:
    """Stable identity of a plan shape for breaker keys: dataclass reprs
    include every semantic field recursively (same property the result
    cache relies on), hashed down to 16 hex chars."""
    return hashlib.sha1(repr(rel).encode()).hexdigest()[:16]


def _fingerprint_of(executor, rel) -> str:
    """Breaker/trace identity of the executing (sub)plan: the literal-
    stripped FAMILY fingerprint when plan families are enabled — a rung
    that dies for ``user_id = 17`` is the same hazard for ``user_id = 404``,
    so verdicts, skips and cooldowns apply family-wide — else the exact
    literal-baked plan fingerprint."""
    fp = getattr(executor, "_resilience_fp", None)
    if fp is None:
        from ..families import family_of

        info = family_of(rel, executor.config,
                         metrics=executor.context.metrics)
        fp = info.fingerprint if info is not None else plan_fingerprint(rel)
        executor._resilience_fp = fp
    return fp


def _breaker_of(executor):
    if not executor.config.get("resilience.breaker.enabled", True):
        return None
    return getattr(executor.context, "breaker", None)


def cost_skip(executor, rung: str, rel) -> bool:
    """Cost-based rung selection (``resilience.ladder.cost_based``): skip a
    compile-bearing rung whose predicted compile cost can never amortize
    for this family — choosing the predicted-cheapest viable rung instead
    of only skipping provably doomed ones (TQP's cost-model-as-scheduler
    argument, arXiv:2203.01877).

    The decision is evidence-gated so it can never regress a cold engine:

    - the family must have OBSERVED exec history (it already ran on a lower
      rung) — a first-seen family always gets its compile attempt;
    - the rung must not have compiled for this family yet, nor answered it
      (an existing executable is nearly free to run: never skip it; one
      executable may serve several fingerprints, as the join rung's does
      for every string literal of a build side it keeps whole);
    - a per-rung compile-cost prior must exist — the p50 of the context's
      ``resilience.compile_ms.<rung>`` history (PR 5's compile histograms);
      no prior, no claim.

    Skip when ``predicted_compile_ms > amortize_factor * observed_hits *
    observed_exec_ms_p50``: compiling costs more than running the family
    the way it already runs `amortize_factor x` its observed popularity.  A
    family that keeps getting hit grows ``observed_hits`` until the compile
    amortizes and is then taken — one-shot families never pay it.  A skip
    is a *choice*, not a failure: no degradation count, no breaker charge
    (``resilience.degraded`` stays 0)."""
    try:
        config = executor.config
        if not config.get("resilience.ladder.cost_based", True):
            return False
        if not rung.startswith(_COMPILE_RUNG_PREFIXES):
            return False
        profiles = getattr(executor.context, "profiles", None)
        if profiles is None:
            return False
        entry = profiles.get(_fingerprint_of(executor, rel))
        if entry is None:
            return False
        if entry["compile"].get(rung) or entry.get("rungs", {}).get(rung):
            return False
        exec_hist = entry.get("exec_ms") or []
        if not exec_hist:
            return False
        compile_pred = executor.context.metrics.hist_percentile(
            f"resilience.compile_ms.{rung}", 0.5)
        if compile_pred is None:
            return False
        observed = sorted(exec_hist)[len(exec_hist) // 2]
        hits = max(1, int(entry.get("hits", 0)))
        factor = float(
            config.get("resilience.ladder.cost.amortize_factor", 4.0))
        return compile_pred > factor * hits * max(observed, 1e-3)
    except Exception:  # dsql: allow-broad-except — the selector is an
        # advisory optimization: a bug here must mean "no skip", never a
        # failed query
        logger.debug("cost-based rung selection failed open", exc_info=True)
        return False


def attempt(executor, rung: str, fn: Callable[[], Optional[T]],
            rel=None, inject_site: Optional[str] = None) -> Optional[T]:
    """Run one ladder rung; None means "step down to the next rung".

    The rung callable keeps the engine's existing convention: return None to
    decline (ineligible shape — not an error, not recorded).  What this
    wrapper adds: a *degradable* failure inside the rung also steps down —
    recorded as ``resilience.degraded.<rung>`` and fed to the breaker — and
    a breaker already open for (plan fingerprint, rung) skips the rung
    without paying the failure again.  Non-degradable errors propagate."""
    if not executor.config.get("resilience.ladder.enabled", True):
        if inject_site is not None:
            faults.maybe_inject(inject_site, executor.config)
        return fn()
    metrics = executor.context.metrics
    # static plan-verifier verdict (analysis/verifier.py): a rung proven
    # doomed at bind time (e.g. radix-domain overflow of the 1<<22 gate) is
    # skipped outright — no trace attempt, no breaker charge, no recompile
    skip_rungs = getattr(rel, "_dsql_skip_rungs", None)
    if skip_rungs and rung in skip_rungs:
        metrics.inc("analysis.rung_skip")
        metrics.inc(f"analysis.rung_skip.{rung}")
        trace_event(f"rung_proof_skip:{rung}")
        logger.debug("plan verifier marked rung %s doomed: skipping", rung)
        return None
    breaker = _breaker_of(executor)
    key = None
    if breaker is not None and rel is not None:
        key = (_fingerprint_of(executor, rel), rung)
        # a declined/skipped rung leaves the half-open trial pending by
        # design; the breaker cooldown re-arms it (see retry.py)
        # dsql: allow-unpaired-effect — cooldown re-arms a pending trial
        if not breaker.allow(key):
            metrics.inc("resilience.breaker.skip")
            metrics.inc(f"resilience.breaker.skip.{rung}")
            trace_event(f"breaker_skip:{rung}", fingerprint=key[0])
            logger.debug("breaker open for rung %s: skipping", rung)
            return None
    if rel is not None and cost_skip(executor, rung, rel):
        # predicted-cost choice, not a failure: the rung is viable, just
        # predicted more expensive than staying on the rung the family
        # already runs on — no degradation count, no breaker charge
        metrics.inc("serving.scheduler.cost_rung_skip")
        metrics.inc(f"serving.scheduler.cost_rung_skip.{rung}")
        trace_event(f"cost_rung_skip:{rung}")
        logger.debug("cost model predicts rung %s cannot amortize: "
                     "skipping", rung)
        return None
    t0 = time.perf_counter()
    reclaim_tried = False
    retried = False
    while True:
        try:
            if inject_site is not None:
                faults.maybe_inject(inject_site, executor.config)
            out = fn()
            break
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as exc:  # dsql: allow-broad-except — degradable
            # taxonomy errors are MEANT to be absorbed here (that is the
            # ladder); classify() re-raises everything non-degradable below
            # classify() maps raw runtime failures (e.g. an XlaRuntimeError
            # whose message leads with RESOURCE_EXHAUSTED) into the taxonomy;
            # only *degradable* results step down — everything else re-raises
            # as-is so non-ladder failure behavior is unchanged
            err = classify(exc)
            if not err.degradable:
                raise
            if not reclaim_tried and isinstance(err, ResourceExhaustedError):
                # reclaim-before-degrade (resilience/pressure.py): a
                # RESOURCE_EXHAUSTED mid-execute first reclaims cold bytes
                # (result cache -> stems -> idle model params) and retries
                # the SAME rung once — a reclaimable OOM must not charge
                # the breaker or degrade the query.  Nothing reclaimable
                # (freed == 0) steps down exactly as before.
                reclaim_tried = True
                from .pressure import reclaim_for_oom

                if reclaim_for_oom(executor.context, executor.config) > 0:
                    metrics.inc("resilience.pressure.rung_retry")
                    trace_event(f"pressure_retry:{rung}", code=err.code)
                    retried = True
                    continue
            metrics.inc("resilience.degraded")
            metrics.inc(f"resilience.degraded.{rung}")
            trace_event(f"degraded:{rung}", code=err.code)
            from ..observability import flight
            from ..serving.runtime import current_ticket

            ticket = current_ticket()
            flight.record("ladder.degrade",
                          qid=ticket.qid if ticket is not None else None,
                          rung=rung, code=err.code)
            if executor.tracer.enabled:
                executor.tracer.event(f"degraded: {rung} [{err.code}]")
            if key is not None and breaker.record_failure(key):
                metrics.inc("resilience.breaker.trip")
                flight.record("breaker.trip", rung=rung, fingerprint=key[0],
                              code=err.code)
                logger.warning(
                    "breaker tripped for rung %s (plan %s): %s",
                    rung, key[0], err)
            logger.info("rung %s degraded (%s); stepping down", rung,
                        err.code)
            return None
    if out is not None:
        if retried:
            # the post-reclaim retry of the SAME rung answered: the OOM
            # was reclaimable pressure, not a doomed rung
            metrics.inc("resilience.pressure.rung_retry_ok")
        metrics.inc(f"resilience.rung.{rung}")
        from ..observability import live

        live.update(rung=rung)
        if not rung.startswith(_SELF_REPORTING_RUNGS):
            # the acceptance-visible marker of which rung answered: a
            # zero-duration span, flagged spmd for the sharded rungs
            trace_event(f"rung:{rung}", rung=rung,
                        spmd=rung.startswith("spmd_"))
        if key is not None and breaker.record_success(key):
            # an OPEN circuit just closed on its half-open trial: the
            # rung is healthy again for this family
            from ..observability import flight

            flight.record("breaker.restore", rung=rung,
                          fingerprint=key[0])
        if rel is not None:
            # per-(family, rung) exec evidence for the cost-based selector
            # and SHOW PROFILES (wall time includes any compile this rung
            # paid — that IS the cost a scheduler-visible run charges)
            profiles = getattr(executor.context, "profiles", None)
            if profiles is not None:
                profiles.record_rung_exec(
                    key[0] if key is not None
                    else _fingerprint_of(executor, rel),
                    rung, (time.perf_counter() - t0) * 1000.0)
    return out


def execute_interpreted(executor, rel):
    """The bottom of the device ladder: the eager per-op walk, with one
    last CPU-backend rung under it for degradable failures.

    The CPU rung re-runs the *whole* plan with jax steering NEW array
    placement to host devices and every distributed/compiled path disabled
    (should_distribute would otherwise pick the same mesh off the sharded
    inputs and re-fail identically) — slower, but host DRAM is orders of
    magnitude larger than HBM.  Honest limitation: operands already
    committed to device HBM still execute their ops there (jax does not
    migrate committed buffers on default_device), so the rung fully
    rescues capacity-ladder/compile-shape failures and partially rescues
    allocation OOMs; if the rerun fails again, that failure propagates."""
    if not executor.config.get("resilience.ladder.enabled", True):
        faults.maybe_inject("exec_oom", executor.config)
        return executor.execute(rel)
    try:
        faults.maybe_inject("exec_oom", executor.config)
        return executor.execute(rel)
    except (KeyboardInterrupt, SystemExit):
        raise
    except BaseException as exc:  # dsql: allow-broad-except — only
        # degradable taxonomy errors are absorbed (CPU re-run); the rest
        # re-raises right below
        err = classify(exc)
        if not err.degradable:
            raise
        metrics = executor.context.metrics
        if isinstance(err, ResourceExhaustedError):
            # reclaim-before-degrade (resilience/pressure.py): before the
            # CPU rung, free reclaimable cold bytes and retry the
            # interpreted walk once on device — host DRAM is the LAST
            # resort, reclaimed HBM the better first answer
            from .pressure import reclaim_for_oom

            if reclaim_for_oom(executor.context, executor.config) > 0:
                metrics.inc("resilience.pressure.rung_retry")
                trace_event("pressure_retry:interpreted", code=err.code)
                executor._memo.clear()  # drop the failed walk's partials
                try:
                    faults.maybe_inject("exec_oom", executor.config)
                    out = executor.execute(rel)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except BaseException as exc2:  # dsql: allow-broad-except —
                    # the retried walk failed again: re-classify and fall
                    # through to the CPU rung (or re-raise non-degradable)
                    err = classify(exc2)
                    if not err.degradable:
                        raise
                else:
                    metrics.inc("resilience.pressure.rung_retry_ok")
                    return out
        if not executor.config.get("resilience.ladder.cpu_fallback", True):
            raise
        import jax

        try:
            cpu = jax.devices("cpu")[0]
        except RuntimeError:
            raise  # no CPU backend registered: out of rungs, no step taken
        # only now is the step-down real — count it (degraded == steps
        # actually taken; a failure with no rung left must not inflate it)
        metrics.inc("resilience.degraded")
        metrics.inc("resilience.degraded.interpreted")
        trace_event("degraded:interpreted", code=err.code)
        from ..observability import flight
        from ..serving.runtime import current_ticket

        _ticket = current_ticket()
        flight.record("ladder.degrade",
                      qid=_ticket.qid if _ticket is not None else None,
                      rung="interpreted", code=err.code)
        if executor.tracer.enabled:
            executor.tracer.event(f"degraded: interpreted [{err.code}]")
        logger.warning("interpreted path failed degradably (%s); "
                       "re-executing on the CPU backend", err.code)
        executor._memo.clear()  # drop partial results of the failed walk
        with executor.config.set({
                "sql.distributed.aggregate": "off",
                "sql.distributed.join": "off",
                "sql.distributed.sort": "off",
                "sql.compile": False}), jax.default_device(cpu):
            out = executor.execute(rel)
        metrics.inc("resilience.rung.cpu")
        trace_event("rung:cpu", rung="cpu", spmd=False)
        from ..observability import live

        live.update(rung="cpu")
        return out


def wrap_boundary(fn: Callable[[], T], query_id: Optional[str] = None) -> T:
    """Run `fn` and re-raise any failure as a taxonomy QueryError — the
    executor-boundary contract TpuFrame.execute and the server rely on."""
    try:
        return fn()
    except QueryError:
        raise
    except (KeyboardInterrupt, SystemExit):
        raise
    except BaseException as exc:
        raise classify(exc, query_id=query_id) from exc
