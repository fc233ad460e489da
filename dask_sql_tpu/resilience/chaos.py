"""Chaos campaign harness: seeded fault storms under concurrent mixed load.

Every resilience mechanism in this package — retry/backoff, the
degradation ladder, circuit breakers, mid-stream repartition, the compile
watchdog, pressure reclaim — was proven by a *targeted* test that arms one
fault site and asserts one recovery path.  Real incidents are not
targeted: a wedged compile, a transient transfer drop and a device OOM
land in the same minute, on different queries, while clients cancel and
the admission queue backs up.  This module is the composition proof: a
deterministic (seeded) campaign arms rotating subsets of EVERY fault-
injection site (resilience/faults.py) as probability specs, drives a
concurrent mixed workload — interactive aggregates, batch scans, streamed
partitioned queries, PREDICT inference, exact-repeat cache hits, random
mid-flight cancels, checkpoint writes — through a real `ServingRuntime`,
and then asserts GLOBAL invariants that must hold after drain no matter
which faults fired in which order:

- every in-flight query table entry reached a terminal state;
- the packing scheduler's byte reservations and the HBM ledger's reserved
  gauge are back to idle (zero) — no leaked reservation on any path;
- every breaker left OPEN admits its half-open trial once its cooldown
  elapses (no permanently-wedged circuit);
- no zombie engine threads survive ``shutdown(wait=True)``;
- the flight-recorder event sequence is causally consistent per query
  (an admit precedes any finish; at most one finish per qid).

Individual query outcomes are free — success, degraded success, retryable
failure, shed, cancel are all acceptable under chaos; what is NOT
acceptable is corrupted engine state after the storm passes.  Exposed as
``bench.py --chaos`` (exits 1 on any violation) and the ``chaos``-marked
test module (tests/unit/test_chaos.py).
"""
from __future__ import annotations

import itertools
import logging
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ..runtime import locks as runtime_locks

logger = logging.getLogger(__name__)

#: process-unique campaign nonce folded into every qid: the flight
#: recorder is process-global, so a SECOND campaign in the same process
#: must not see the first campaign's ``query.finish`` events under its
#: own qids when checking per-query causality
_campaign_nonce = itertools.count()

#: every error-raising inject site plus the hang site — the campaign
#: rotates probability-armed subsets of this list (ISSUE 17)
ALL_SITES = ("compile", "oom", "exec_oom", "execute", "checkpoint",
             "spmd", "predict", "partition", "d2h", "compile_hang")

#: base config for a campaign: fast backoff, short breaker cooldown, a
#: short injected hang with a compile deadline it trips, a flight ring
#: big enough that a campaign's events are never evicted mid-run
_BASE_CONFIG = {
    "resilience.retry.max_attempts": 2,
    "resilience.retry.base_s": 0.01,
    "resilience.retry.max_s": 0.05,
    "resilience.breaker.threshold": 2,
    "resilience.breaker.cooldown_s": 0.2,
    "resilience.compile_timeout_ms": 2000.0,
    "resilience.inject.hang_s": 0.05,
    "serving.stream.min_chunk_rows": 64,
    "serving.stream.launch_timeout_ms": 5000.0,
    "observability.flight.capacity": 65536,
}


@dataclass
class ChaosReport:
    """Outcome of one campaign: per-query tallies plus the invariant
    violations (empty = the engine state survived the storm intact)."""

    seed: int
    rounds: int = 0
    submitted: int = 0
    completed: int = 0
    failed: int = 0
    cancelled: int = 0
    shed: int = 0
    violations: List[str] = field(default_factory=list)
    #: per-round armed specs, for reproducing a failure: (round, spec, seed)
    armed: List[Tuple[int, str, int]] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def summary(self) -> str:
        return (f"chaos seed={self.seed}: {self.submitted} queries over "
                f"{self.rounds} rounds ({self.completed} ok, {self.failed} "
                f"failed, {self.cancelled} cancelled, {self.shed} shed); "
                f"{len(self.violations)} invariant violation(s)")


def _build_context(rng: random.Random):
    """A fresh Context with the chaos fixture data: a small table (fast
    interactive aggregates + cache hits), a bigger table sized to force
    streamed routing under the per-query byte gate, and a trained model
    for PREDICT traffic."""
    import numpy as np
    import pandas as pd

    from ..context import Context

    c = Context()
    n_small, n_big = 200, 4096
    c.create_table("t_small", pd.DataFrame({
        "a": np.arange(n_small, dtype=np.float64),
        "b": np.arange(n_small) % 7,
    }))
    c.create_table("t_big", pd.DataFrame({
        "k": np.arange(n_big) % 13,
        "v": rng.random() + np.arange(n_big, dtype=np.float64),
    }))
    df = pd.DataFrame({
        "x": np.linspace(0.0, 1.0, n_small),
        "y": np.linspace(1.0, 0.0, n_small),
    })
    df["target"] = (df.x * 2 + df.y > 1.2).astype(np.int64)
    c.create_table("train", df)
    c.sql("""CREATE MODEL chaos_model WITH (
                 model_class = 'LinearRegression',
                 target_column = 'target'
             ) AS (SELECT x, y, target FROM train)""")
    return c


def _query_mix(stream_budget: int) -> List[Tuple[str, str, Dict]]:
    """(sql, priority_class, per-query config overrides) templates; the
    campaign cycles through them so every round carries every shape."""
    stream_opts = {"serving.admission.max_estimated_bytes": stream_budget}
    return [
        ("SELECT b, SUM(a) AS s FROM t_small GROUP BY b",
         "interactive", {}),
        # exact repeat of the query above: result-cache / reuse traffic
        ("SELECT b, SUM(a) AS s FROM t_small GROUP BY b",
         "interactive", {}),
        ("SELECT COUNT(*) AS n, SUM(a) AS s FROM t_small", "batch", {}),
        # the per-query byte gate forces this one onto a streamed rung
        ("SELECT k, SUM(v) AS s FROM t_big GROUP BY k",
         "interactive", stream_opts),
        ("SELECT k, SUM(v) AS s FROM t_big GROUP BY k",
         "batch", stream_opts),
        ("SELECT * FROM PREDICT(MODEL chaos_model, "
         "SELECT x, y FROM t_small_pred)", "interactive", {}),
        ("SELECT a, b FROM t_small WHERE a > 50", "interactive", {}),
    ]


def run_campaign(seed: int, queries: int = 40, rounds: int = 4,
                 workers: int = 4,
                 state_dir: Optional[str] = None) -> ChaosReport:
    """Run one seeded chaos campaign; deterministic per (seed, queries,
    rounds, workers) in which faults arm where (individual interleavings
    still race — that is the point — but the invariants are
    order-independent).  ``state_dir`` additionally exercises the
    ``checkpoint`` site with one ``save_state`` per round."""
    from .. import config as config_module
    from ..observability import flight
    from ..serving.cache import table_nbytes
    from ..serving.runtime import ServingRuntime
    from ..serving.scheduler import QueryCost
    from . import faults

    rng = random.Random(seed)
    report = ChaosReport(seed=seed)
    saved = list(config_module.config.effective_items())
    faults.reset()
    lock_baseline = runtime_locks.violation_count()
    # threads alive before the campaign are not its zombies: another engine
    # in this process (an earlier test's parked background compiler) would
    # otherwise fail the thread invariant whatever this campaign did
    thread_baseline = frozenset(t.ident for t in threading.enumerate())
    try:
        config_module.config.update(dict(_BASE_CONFIG))
        context = _build_context(rng)
        big_bytes = table_nbytes(
            context.schema["root"].tables["t_big"].table)
        # per-query gate a third of the big table: full scans exceed it,
        # chunks fit — the streamed templates route instead of shedding
        stream_budget = max(4096, big_bytes // 3)
        # PREDICT input table (left out of _build_context so its name
        # telegraphs its purpose in SHOW QUERIES output)
        context.sql("CREATE TABLE t_small_pred AS "
                    "(SELECT a / 200.0 AS x, b / 7.0 AS y FROM t_small)")
        # device budget for the pressure bands: roomy enough to sit GREEN
        # at idle, tight enough that concurrent reservations + cache
        # growth can push it into YELLOW/RED during a storm
        total_bytes = sum(table_nbytes(dc.table) for dc in
                          context.schema["root"].tables.values())
        config_module.config.update({
            "serving.scheduler.device_budget_bytes": total_bytes * 3,
        })
        runtime = ServingRuntime(workers=workers, metrics=context.metrics,
                                 scheduler_budget_bytes=total_bytes * 2)
        context.serving = runtime
        context.metrics.inc("chaos.campaigns")
        templates = _query_mix(stream_budget)
        qids: List[str] = []
        nonce = next(_campaign_nonce)
        try:
            per_round = max(1, queries // max(1, rounds))
            for rnd in range(rounds):
                n_armed = rng.randint(2, max(2, len(ALL_SITES) // 2))
                sites = rng.sample(ALL_SITES, n_armed)
                spec = ",".join(
                    f"{s}:{rng.choice(('0.2', '0.4', 'once'))}"
                    for s in sites)
                round_seed = rng.randint(0, 1 << 30)
                overrides = {"resilience.inject": spec,
                             "resilience.inject.seed": round_seed}
                report.armed.append((rnd, spec, round_seed))
                context.metrics.inc("chaos.rounds")
                flight.record("chaos.arm", round=rnd, spec=spec,
                              seed=round_seed)
                logger.info("chaos round %d arming %r (seed %d)",
                            rnd, spec, round_seed)
                futures = []
                for i in range(per_round):
                    sql, cls, qopts = templates[
                        (rnd * per_round + i) % len(templates)]
                    qid = f"chaos-{seed}.{nonce}-{rnd}-{i}"

                    def job(ticket, _sql=sql, _opts=dict(qopts)):
                        # overlays are thread-local: armed INSIDE the
                        # worker thread, for this job's extent only
                        with config_module.config.set({**overrides,
                                                       **_opts}):
                            return context.sql(_sql).compute()

                    # dsql: allow-unpaired-effect — settled by _finisher
                    entry = context.live_queries.begin(qid, sql=sql,
                                                       priority_class=cls)
                    try:
                        _, fut, ticket = runtime.submit(
                            job, qid=qid, priority_class=cls,
                            cost=QueryCost(bytes_lo=rng.randint(1024,
                                                                65536)))
                    except Exception:  # dsql: allow-broad-except — a
                        # queue-full shed is a legitimate chaos outcome
                        context.live_queries.discard(qid)
                        report.shed += 1
                        continue
                    entry.ticket = ticket
                    fut.add_done_callback(
                        _finisher(context, qid))
                    report.submitted += 1
                    context.metrics.inc("chaos.queries")
                    qids.append(qid)
                    futures.append((qid, fut, ticket))
                if state_dir is not None and futures:
                    # exercise the checkpoint site mid-storm (failure is
                    # an acceptable outcome; corrupted CURRENT is not —
                    # save_state repoints atomically)
                    try:
                        with config_module.config.set(overrides):
                            context.save_state(state_dir)
                    except Exception:  # dsql: allow-broad-except — the
                        # injected write error is the expected outcome
                        logger.info("chaos checkpoint failed (expected "
                                    "under injection)", exc_info=True)
                # cancel a random ~15% slice mid-flight: the cooperative
                # checkpoints must release reservations exactly once
                for qid, _fut, ticket in futures:
                    if rng.random() < 0.15:
                        flight.record("query.cancel", qid=qid)
                        ticket.cancel()
                for qid, fut, _ticket in futures:
                    try:
                        fut.result(60.0)
                        report.completed += 1
                    except Exception as exc:  # dsql: allow-broad-except —
                        # every failure taxonomy is an acceptable chaos
                        # outcome; the invariants below are the real check
                        from ..serving.admission import QueryCancelledError

                        if isinstance(exc, QueryCancelledError):
                            report.cancelled += 1
                        else:
                            report.failed += 1
                report.rounds += 1
                faults.reset()  # re-arm `once` budgets for the next round
            # drain FIRST: the thread/ledger/reservation invariants are
            # statements about the engine's state after a clean shutdown
            runtime.shutdown(wait=True)
            _check_invariants(report, context, runtime, qids,
                              lock_baseline=lock_baseline,
                              thread_baseline=thread_baseline)
        finally:
            runtime.shutdown(wait=True)
    finally:
        # every key the campaign touched exists in the defaults, so
        # re-applying the saved effective items restores them all
        config_module.config.update(dict(saved))
        faults.reset()
    for v in report.violations:
        logger.error("chaos invariant violation: %s", v)
    return report


def _finisher(context, qid: str):
    """Done-callback mirroring the server front-end: the submitter owns
    the live entry's terminal state (the worker may retry attempts)."""

    def done(fut):
        from ..serving.admission import QueryCancelledError

        if fut.cancelled():
            context.live_queries.finish(qid, "cancelled")
            return
        exc = fut.exception()
        if exc is None:
            context.live_queries.finish(qid, "done")
        elif isinstance(exc, QueryCancelledError):
            context.live_queries.finish(qid, "cancelled",
                                        getattr(exc, "code", None))
        else:
            context.live_queries.finish(
                qid, "failed",
                getattr(exc, "code", None) or type(exc).__name__)

    return done


def _check_invariants(report: ChaosReport, context, runtime,
                      qids: List[str], lock_baseline: int = 0,
                      thread_baseline: frozenset = frozenset()) -> None:
    """The global post-drain invariants; appends human-readable violation
    strings to the report (and counts ``chaos.violations``)."""
    from ..observability import flight

    def violate(msg: str) -> None:
        report.violations.append(msg)
        context.metrics.inc("chaos.violations")

    # 1. every live-table entry terminal
    live = context.live_queries.live_entries()
    if live:
        violate(f"non-terminal live entries after drain: "
                f"{[(e.qid, e.state) for e in live]}")

    # 2. reservations and ledger back to idle (poll briefly: the last
    # worker's _release runs after its future resolves)
    deadline = time.monotonic() + 5.0
    reserved = context.ledger.reserved_bytes()
    while reserved and time.monotonic() < deadline:
        time.sleep(0.01)
        reserved = context.ledger.reserved_bytes()
    if reserved:
        violate(f"scheduler still holds {reserved} reserved bytes "
                f"after drain")
    snap = context.ledger.snapshot()
    if snap["reservedBytes"] != 0:
        violate(f"ledger reservedBytes={snap['reservedBytes']} != 0 "
                f"after drain")
    if snap["inflightMeasuredBytes"] != 0:
        violate(f"ledger inflightMeasuredBytes="
                f"{snap['inflightMeasuredBytes']} != 0 after drain")

    # 3. every OPEN breaker admits its half-open trial after cooldown
    state = context.breaker.snapshot_state()
    if state["open"]:
        time.sleep(context.breaker.cooldown_s + 0.05)
        for entry in state["open"]:
            key = tuple(entry["key"])
            # invariant probe: the granted trial is intentionally left
            # unsettled — the campaign ends here
            # dsql: allow-unpaired-effect — probe-only grant
            if not context.breaker.allow(key):
                violate(f"breaker {key} still refuses its half-open "
                        f"trial after cooldown")

    # 4. no zombie engine threads past shutdown(wait=True); watchdog
    # helper threads get a grace window to finish their bounded hangs
    for t in runtime._threads:
        if t.is_alive():
            violate(f"serving worker {t.name} alive after "
                    f"shutdown(wait=True)")
    deadline = time.monotonic() + 3.0
    while time.monotonic() < deadline:
        strays = [t.name for t in threading.enumerate()
                  if t.name.startswith(("dsql-warmup", "dsql-bg-compile",
                                        "dsql-compile-watchdog"))
                  and t.ident not in thread_baseline and t.is_alive()]
        if not strays:
            break
        time.sleep(0.05)
    else:
        violate(f"zombie background threads after drain: {strays}")

    # 5. flight-recorder causality per submitted qid
    events = flight.RECORDER.events()
    by_qid: Dict[str, List[dict]] = {}
    for e in events:
        q = e.get("qid")
        if q is not None:
            by_qid.setdefault(q, []).append(e)
    for qid in qids:
        evs = by_qid.get(qid, [])
        admits = [e for e in evs if e["event"] == "query.admit"]
        finishes = [e for e in evs if e["event"] == "query.finish"]
        if len(finishes) > 1:
            violate(f"{qid}: {len(finishes)} query.finish events "
                    f"(expected at most 1)")
        if finishes and not admits:
            violate(f"{qid}: query.finish with no query.admit")
        if finishes and admits and admits[0]["ts"] > finishes[0]["ts"]:
            violate(f"{qid}: query.admit after query.finish")

    # 6. no lock-order violation observed (runtime/locks.py sanitizer —
    # a no-op unless the suite armed it; the storm IS the stress test
    # for the declared rank order)
    excess = runtime_locks.violation_count() - lock_baseline
    if excess:
        details = "; ".join(
            f"{v['kind']}: holding {v['holding']} acquiring "
            f"{v['acquiring']} on {v['thread']}"
            for v in runtime_locks.violations()[-excess:])
        violate(f"{excess} lock-order violation(s) during the storm "
                f"({details})")


# ===================================================================== fleet
@dataclass
class FleetChaosReport(ChaosReport):
    """Outcome of one replica-kill campaign across a fleet (ISSUE 18)."""

    kills: int = 0
    promoted: int = 0
    inserts: int = 0
    retried: int = 0

    def summary(self) -> str:
        return (f"fleet chaos seed={self.seed}: {self.submitted} queries "
                f"over {self.rounds} rounds ({self.completed} ok, "
                f"{self.retried} client retries, {self.failed} failed, "
                f"{self.shed} shed), {self.kills} replicas killed, "
                f"{self.promoted} standby promoted, {self.inserts} inserts; "
                f"{len(self.violations)} invariant violation(s)")


def run_fleet_campaign(seed: int, queries: int = 30, rounds: int = 3,
                       replicas: int = 3, clients: int = 4,
                       sync_dir: Optional[str] = None) -> FleetChaosReport:
    """Replica-kill chaos across a router-fronted fleet (ISSUE 18): drive
    the concurrent mixed workload THROUGH the fleet router, kill -9 one
    replica per round mid-workload (round 0 stays clean to warm profiles
    and sync the standby), and assert the fleet-level invariants:

    - ZERO lost queries: every routed statement reaches a terminal state
      with success or a structured retryable outcome (a non-retryable
      failure under pure replica-kill chaos is a violation);
    - INSERT INTO applies exactly once per surviving replica no matter
      how many times failover retried it (epoch fencing): every
      survivor's row count equals base rows + successful inserts, and
      all survivors agree;
    - the promoted standby serves reads (it was promoted, it is READY,
      and it converged to the same row count);
    - router + survivor ledgers reconcile to idle after drain.

    Deterministic per (seed, queries, rounds, replicas) in what is
    submitted and which replica dies when; interleavings race — that is
    the point — but the invariants are order-independent."""
    from concurrent.futures import ThreadPoolExecutor

    from .. import config as config_module
    from ..fleet import READY, build_fleet
    from ..serving.cache import table_nbytes
    from . import faults

    rng = random.Random(seed)
    report = FleetChaosReport(seed=seed)
    saved = list(config_module.config.effective_items())
    faults.reset()
    nonce = next(_campaign_nonce)
    lock_baseline = runtime_locks.violation_count()
    try:
        config_module.config.update({
            **_BASE_CONFIG,
            "fleet.failover.max_attempts": 4,
            "fleet.failover.base_s": 0.01,
            "fleet.result_timeout_s": 30.0,
        })

        def factory():
            c = _build_context(random.Random(seed))
            c.sql("CREATE TABLE t_small_pred AS "
                  "(SELECT a / 200.0 AS x, b / 7.0 AS y FROM t_small)")
            return c

        router, members, replicator = build_fleet(
            factory, replicas=replicas, standby=True, sync_dir=sync_dir)
        base_rows = 200  # t_small rows in the fixture
        big_bytes = table_nbytes(
            members[0].context.schema["root"].tables["t_big"].table)
        templates = _query_mix(max(4096, big_bytes // 3))
        per_round = max(2, queries // max(1, rounds))
        ok_inserts = 0
        lock = threading.Lock()

        def client(sql, cls, qopts, qid, is_insert):
            nonlocal ok_inserts
            delay = 0.02
            for attempt in range(6):
                try:
                    router.execute(sql, qid=qid, priority_class=cls,
                                   config_options=qopts)
                    if is_insert:
                        with lock:
                            ok_inserts += 1
                    return "ok"
                except Exception as exc:  # dsql: allow-broad-except —
                    # outcome taxonomy IS what the campaign classifies
                    if getattr(exc, "retryable", False):
                        if attempt < 5:
                            with lock:
                                report.retried += 1
                            time.sleep(delay)
                            delay *= 2
                            continue
                        return "retryable"
                    return (f"fatal:{getattr(exc, 'code', None) or type(exc).__name__}"
                            f" {exc}")
            return "retryable"

        try:
            with ThreadPoolExecutor(max_workers=clients,
                                    thread_name_prefix="fleet-client") as pool:
                for rnd in range(rounds):
                    tasks = []
                    for i in range(per_round):
                        sql, cls, qopts = templates[
                            (rnd * per_round + i) % len(templates)]
                        qid = f"fleet-{seed}.{nonce}-{rnd}-{i}"
                        tasks.append((sql, cls, qopts, qid, False))
                    for j in range(2):
                        # the router's write log dedupes on the client qid
                        # (retries below re-use it); the per-(round, slot)
                        # tag keeps inserted rows distinguishable
                        tag = 10000 + rnd * 100 + j
                        tasks.append((
                            f"INSERT INTO t_small SELECT a + {tag}, b "
                            f"FROM t_small WHERE a < 1",
                            "interactive", {},
                            f"fleet-ins-{seed}.{nonce}-{rnd}-{j}", True))
                    rng.shuffle(tasks)
                    futures = [pool.submit(client, *t) for t in tasks]
                    report.submitted += len(tasks)
                    if rnd > 0 and rnd < len(members):
                        # kill -9 one replica mid-workload; the standby
                        # absorbs the first death via promotion
                        time.sleep(0.05)
                        victim = members[rnd]
                        if victim.state == READY:
                            logger.info("fleet chaos round %d killing %s",
                                        rnd, victim.name)
                            router.kill(victim.name)
                            report.kills += 1
                    for f in futures:
                        status = f.result(180.0)
                        if status == "ok":
                            report.completed += 1
                        elif status == "retryable":
                            report.shed += 1
                        else:
                            report.failed += 1
                            report.violations.append(
                                f"round {rnd}: non-retryable outcome under "
                                f"replica-kill chaos: {status}")
                    if rnd == 0 and replicator is not None:
                        # quiet window: warm the standby off round-0 state
                        # (snapshot carries table epochs + profiles; the
                        # process compile cache is shared in-process)
                        replicator.sync()
                    report.rounds += 1

            report.inserts = ok_inserts
            promoted = [r for r in router.replicas
                        if r.name == "standby" and r.state == READY]
            report.promoted = len(promoted)
            if report.kills and not promoted:
                report.violations.append(
                    "standby was never promoted despite replica kills")

            # exactly-once INSERT: every surviving replica agrees on
            # base + successful-inserts rows, no more (a double apply
            # would overshoot), no fewer (a lost write would undershoot)
            survivors = [r for r in router.replicas if r.state == READY]
            if not survivors:
                report.violations.append("no surviving replica after chaos")
            expect = base_rows + ok_inserts
            for r in survivors:
                out = r.context.sql("SELECT COUNT(*) AS n FROM t_small",
                                    return_futures=False)
                n = int(out["n"][0])
                if n != expect:
                    report.violations.append(
                        f"{r.name}: t_small has {n} rows, expected "
                        f"{expect} (base {base_rows} + {ok_inserts} "
                        f"inserts applied exactly once)")

            # drain the fleet, then every ledger must reconcile to idle
            for r in survivors:
                r.drain(wait=True)
            checked = list(dict.fromkeys(
                members + list(router.replicas)
                + ([router.standby] if router.standby else [])))
            for r in checked:
                deadline = time.monotonic() + 5.0
                reserved = r.context.ledger.reserved_bytes()
                while reserved and time.monotonic() < deadline:
                    time.sleep(0.01)
                    reserved = r.context.ledger.reserved_bytes()
                if reserved:
                    report.violations.append(
                        f"{r.name}: ledger still holds {reserved} reserved "
                        f"bytes after fleet drain")

            # no lock-order violation observed (runtime/locks.py): the
            # kill/failover/promotion storm exercises the full declared
            # rank order — router apply -> router state -> replica
            # state/write -> plan cache -> registry -> metrics/flight
            excess = runtime_locks.violation_count() - lock_baseline
            if excess:
                details = "; ".join(
                    f"{v['kind']}: holding {v['holding']} acquiring "
                    f"{v['acquiring']} on {v['thread']}"
                    for v in runtime_locks.violations()[-excess:])
                report.violations.append(
                    f"{excess} lock-order violation(s) during the "
                    f"fleet storm ({details})")
        finally:
            router.shutdown()
    finally:
        config_module.config.update(dict(saved))
        faults.reset()
    for v in report.violations:
        logger.error("fleet chaos invariant violation: %s", v)
    return report
