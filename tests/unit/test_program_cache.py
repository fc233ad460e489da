"""`physical/programs.py`: the one program cache of the nine compiled rungs.

Against fakes: a program here is a plain object and a context is the five
attributes the cache reads, so nothing compiles.  What is pinned: the key is
the PAIR (family, bucket); the LRU; the single-flight; the deferral to the
background compiler (only with `warm`, only for a family seen under another
bucket, only with a compiler); the batcher dispatch; the decline memo; and
that every rung module holds a `ProgramCache` and none of the old names.
"""
import importlib
import os
import re
import threading
import types

import pytest

import dask_sql_tpu
from dask_sql_tpu import config as config_module
from dask_sql_tpu.physical import programs
from dask_sql_tpu.physical.programs import ProgramCache
from dask_sql_tpu.serving.background import BackgroundCompiler
from dask_sql_tpu.serving.metrics import MetricsRegistry


class FakeContext:
    """What the cache reads of a Context."""

    def __init__(self, background: bool = False, batcher=None):
        self._plan_lock = threading.RLock()
        self._compiled_families = {}
        self.metrics = MetricsRegistry()
        self.config = config_module.config
        self.serving = types.SimpleNamespace(batcher=batcher)
        self._bg = BackgroundCompiler(metrics=self.metrics) \
            if background else None

    def background_compiler(self):
        return self._bg


class Program:
    def __init__(self, tag=None, batchable=None):
        self.tag = tag
        self.warmed = 0
        if batchable is not None:
            self.batchable = batchable


class Constructor:
    """Counts its calls and returns a fresh Program each time."""

    def __init__(self, tag=None):
        self.calls = 0
        self.tag = tag

    def __call__(self):
        self.calls += 1
        return Program(self.tag)


def _warm(program):
    program.warmed += 1


@pytest.fixture
def ctx():
    context = FakeContext(background=True)
    compiler = context._bg
    yield context
    compiler.cancel()
    compiler.join(10)


# ---------------------------------------------------------------------------
# the key is a pair
# ---------------------------------------------------------------------------
def test_the_key_is_the_pair_family_and_bucket():
    ctx, cache = FakeContext(), ProgramCache("r", 8)
    make = Constructor()
    scatter = ("t", ("sum(x)",), "scatter")
    matmul = ("t", ("sum(x)",), "matmul")  # differs in ONE part of family
    a, built = cache.get_or_build(ctx, scatter, (1, 100, 128), make)
    assert built and make.calls == 1
    again, built = cache.get_or_build(ctx, scatter, (1, 100, 128), make)
    assert again is a and not built and make.calls == 1
    b, built = cache.get_or_build(ctx, matmul, (1, 100, 128), make)
    assert built and b is not a
    # two buckets of one family are two programs
    c, built = cache.get_or_build(ctx, scatter, (2, 200, 256), make)
    assert built and c is not a and make.calls == 3
    assert [key for key, _ in cache.items()] == [
        (scatter, (1, 100, 128)), (matmul, (1, 100, 128)),
        (scatter, (2, 200, 256))]
    assert cache.values() == [a, b, c]


def test_lru_evicts_the_least_recently_used_at_cap():
    ctx, cache = FakeContext(), ProgramCache("r", 3)
    for i in range(3):
        cache.get_or_build(ctx, ("f", i), (1,), Constructor(i))
    cache.get_or_build(ctx, ("f", 0), (1,), Constructor())  # a hit: refreshed
    cache.get_or_build(ctx, ("f", 3), (1,), Constructor(3))
    assert [p.tag for p in cache.values()] == [2, 0, 3]
    make = Constructor(1)
    cache.get_or_build(ctx, ("f", 1), (1,), make)  # was evicted: rebuilt
    assert make.calls == 1 and len(cache.values()) == 3


def test_a_reuse_with_params_counts_a_family_hit():
    from dask_sql_tpu.observability import spans

    ctx, cache = FakeContext(), ProgramCache("some_rung", 4)
    make = Constructor()
    with spans.activate(spans.QueryTrace(sql="select 1")) as trace:
        cache.get_or_build(ctx, "f", (1,), make, params=(7,))
        assert ctx.metrics.counter("families.hit") == 0  # built here
        cache.get_or_build(ctx, "f", (1,), make, params=())
        assert ctx.metrics.counter("families.hit") == 0  # nothing to share
        cache.get_or_build(ctx, "f", (1,), make, params=(8, 9))
    assert ctx.metrics.counter("families.hit") == 1
    hits = [s for s in trace.spans if s.name == "family_hit"]
    assert [(s.attrs["rung"], s.attrs["params"]) for s in hits] \
        == [("some_rung", 2)]


# ---------------------------------------------------------------------------
# single-flight
# ---------------------------------------------------------------------------
def test_concurrent_misses_of_one_key_construct_once():
    ctx, cache = FakeContext(), ProgramCache("r", 4)
    inside, release = threading.Event(), threading.Event()
    calls = []

    def construct():
        calls.append(threading.current_thread().name)
        inside.set()
        assert release.wait(30)
        return Program()

    results = []

    def query():
        results.append(cache.get_or_build(ctx, "f", (1,), construct,
                                          params=(1,)))

    threads = [threading.Thread(target=query) for _ in range(5)]
    threads[0].start()
    assert inside.wait(30)
    for t in threads[1:]:
        t.start()
    release.set()
    for t in threads:
        t.join(30)
    assert len(calls) == 1
    assert len({id(program) for program, _ in results}) == 1
    assert sorted(built for _, built in results) == [False] * 4 + [True]
    assert ctx.metrics.counter("families.hit") == 4
    assert not programs._building  # every token settled


def test_a_waiter_whose_builder_raised_builds_under_its_own_call(monkeypatch):
    ctx, cache = FakeContext(), ProgramCache("r", 4)
    inside, release, second = (threading.Event(), threading.Event(),
                               threading.Event())
    begin = programs.singleflight_begin
    begun = []

    def counted_begin(key):
        got = begin(key)
        begun.append(got[0])
        if len(begun) == 2:
            second.set()
        return got

    monkeypatch.setattr(programs, "singleflight_begin", counted_begin)

    def failing():
        inside.set()
        assert release.wait(30)
        raise MemoryError("the builder's own failure")

    outcome = {}

    def builder():
        try:
            cache.get_or_build(ctx, "f", (1,), failing)
        except MemoryError as e:
            outcome["builder"] = e

    def waiter():
        outcome["waiter"] = cache.get_or_build(ctx, "f", (1,),
                                               Constructor("mine"))

    a, b = threading.Thread(target=builder), threading.Thread(target=waiter)
    a.start()
    assert inside.wait(30)
    b.start()
    assert second.wait(30)  # the waiter holds the builder's event now
    release.set()
    a.join(30)
    b.join(30)
    assert isinstance(outcome["builder"], MemoryError)
    program, built_here = outcome["waiter"]
    assert built_here and program.tag == "mine"
    assert begun == [True, False, True]
    assert not programs._building


# ---------------------------------------------------------------------------
# deferral to the background compiler
# ---------------------------------------------------------------------------
OLD, NEW = (1, 100, 128), (2, 1000, 1024)


@pytest.mark.parametrize("case", ["no_warm", "no_compiler", "first_sight",
                                  "same_bucket_after_eviction"])
def test_what_does_not_defer(case, ctx):
    """Only a rung that warms, only with a compiler, only for a family seen
    under ANOTHER bucket: everything else compiles in the foreground."""
    if case == "no_compiler":
        ctx._bg = None
    cache = ProgramCache("r", 4)
    warm = None if case == "no_warm" else _warm
    make = Constructor()
    if case != "first_sight":
        cache.get_or_build(ctx, "f", OLD, make, warm=warm)
    if case == "same_bucket_after_eviction":
        cache.clear()
    bucket = OLD if case == "same_bucket_after_eviction" else NEW
    program, built_here = cache.get_or_build(ctx, "f", bucket, make,
                                             warm=warm)
    assert program is not None and built_here
    assert program.warmed == 0  # a foreground build is warmed by its run
    assert ctx.metrics.counter("serving.bg_compile.deferred") == 0
    remembered = ctx._compiled_families.get(("r", "f"))
    assert remembered == (None if case == "no_warm" else bucket)


def test_a_seen_family_under_another_bucket_builds_in_the_background(ctx):
    cache = ProgramCache("r", 4)
    make = Constructor()
    first, _ = cache.get_or_build(ctx, "f", OLD, make, warm=_warm)
    gate = threading.Event()

    def slow_warm(program):
        assert gate.wait(30)
        _warm(program)

    got = cache.get_or_build(ctx, "f", NEW, make, warm=slow_warm)
    assert got == (None, False)  # served on a lower rung this time
    assert ctx.metrics.counter("serving.bg_compile.deferred") == 1
    # while the compile is pending the family keeps declining, once
    assert cache.get_or_build(ctx, "f", NEW, make, warm=slow_warm) \
        == (None, False)
    assert ctx.metrics.counter("serving.bg_compile.submitted") == 1
    gate.set()
    assert ctx._bg.wait_idle(30)
    assert ctx.metrics.counter("serving.bg_compile.completed") == 1
    program, built_here = cache.get_or_build(ctx, "f", NEW, make,
                                             warm=slow_warm)
    assert program is not None and not built_here
    assert program.warmed == 1 and program is not first
    assert ctx._compiled_families[("r", "f")] == NEW
    assert make.calls == 2  # one constructor, foreground and background


def test_a_failed_background_build_unmarks_the_family(ctx):
    cache = ProgramCache("r", 4)
    make = Constructor()
    cache.get_or_build(ctx, "f", OLD, make, warm=_warm)

    def broken_warm(program):
        raise RuntimeError("compile failed off the request's path")

    assert cache.get_or_build(ctx, "f", NEW, make, warm=broken_warm) \
        == (None, False)
    assert ctx._bg.wait_idle(30)
    assert ctx.metrics.counter("serving.bg_compile.failed") == 1
    assert ("r", "f") not in ctx._compiled_families
    # the next query takes the foreground path, under its own policy
    program, built_here = cache.get_or_build(ctx, "f", NEW, make,
                                             warm=broken_warm)
    assert program is not None and built_here


# ---------------------------------------------------------------------------
# the batcher dispatch
# ---------------------------------------------------------------------------
class FakeBatcher:
    max_queries = 4

    def __init__(self):
        self.keys = []

    def run(self, key, params, solo, batched):
        self.keys.append(key)
        return batched([params])[0]


@pytest.mark.parametrize("batchable,params,give_batched,has_batcher,want", [
    (True, (1,), True, True, "batched"),
    (None, (1,), True, True, "batched"),   # no attribute: batches (select)
    (False, (1,), True, True, "solo"),
    (True, (), True, True, "solo"),
    (True, (1,), False, True, "solo"),
    (True, (1,), True, False, "solo"),
])
def test_run_batches_only_what_is_batchable(batchable, params, give_batched,
                                            has_batcher, want):
    batcher = FakeBatcher() if has_batcher else None
    ctx, cache = FakeContext(batcher=batcher), ProgramCache("r", 4)
    program = Program(batchable=batchable)
    got = cache.run(
        ctx, "f", (1,), program, params, solo=lambda: "solo",
        batched=(lambda members: ["batched"] * len(members))
        if give_batched else None)
    assert got == want
    if has_batcher:
        assert batcher.keys == ([("r", "f", (1,))] if want == "batched"
                                else [])


def test_a_batcher_of_one_is_no_batcher():
    batcher = FakeBatcher()
    batcher.max_queries = 1
    ctx, cache = FakeContext(batcher=batcher), ProgramCache("r", 4)
    assert cache.run(ctx, "f", (1,), Program(), (1,), solo=lambda: "solo",
                     batched=lambda members: ["batched"]) == "solo"


# ---------------------------------------------------------------------------
# decline memo, evict, clear
# ---------------------------------------------------------------------------
def test_the_decline_memo_resets_at_its_cap():
    cache = ProgramCache("r", 4)
    assert not cache.declined("shape")
    cache.decline("shape")
    assert cache.declined("shape")
    for i in range(programs._DECLINED_CAP):
        cache.decline(i)
    assert not cache.declined("shape")  # reset wholesale, then refilled
    assert cache.declined(programs._DECLINED_CAP - 1)


def test_evict_by_named_parts_and_clear():
    ctx, cache = FakeContext(), ProgramCache("r", 8)
    for model in ("m", "n"):
        for uid in (1, 2):
            cache.get_or_build(ctx, ("root", model), (uid,), Constructor())
    cache.evict(ctx, lambda family, bucket: family[1] == "m")
    assert [key for key, _ in cache.items()] == [
        (("root", "n"), (1,)), (("root", "n"), (2,))]
    cache.evict(ctx, lambda family, bucket: bucket == (2,))
    assert [key for key, _ in cache.items()] == [(("root", "n"), (1,))]
    cache.clear()
    assert cache.values() == []


# ---------------------------------------------------------------------------
# the nine rung modules
# ---------------------------------------------------------------------------
RUNGS = [
    ("dask_sql_tpu.physical.compiled", "compiled_aggregate", 32),
    ("dask_sql_tpu.physical.compiled_select", "compiled_select", 32),
    ("dask_sql_tpu.physical.compiled_join", "compiled_join_aggregate", 16),
    ("dask_sql_tpu.physical.compiled_predict", "compiled_predict", 16),
    ("dask_sql_tpu.spmd.aggregate", "spmd_aggregate", 16),
    ("dask_sql_tpu.spmd.select", "spmd_select", 16),
    ("dask_sql_tpu.spmd.join", "spmd_join_aggregate", 8),
    ("dask_sql_tpu.streaming.aggregate", "streamed_aggregate", 8),
    ("dask_sql_tpu.streaming.select", "streamed_select", 8),
]


@pytest.mark.parametrize("module,rung,cap", RUNGS,
                         ids=[r[1] for r in RUNGS])
def test_every_rung_module_keeps_one_program_cache(module, rung, cap):
    mod = importlib.import_module(module)
    assert isinstance(mod.PROGRAMS, ProgramCache)
    assert (mod.PROGRAMS.rung, mod.PROGRAMS.cap) == (rung, cap)
    for gone in ("_cache", "_CACHE_CAP", "_family_of", "_bucket_of",
                 "_defer_to_background", "_declined",
                 "singleflight_get_or_build", "defer_rebuild"):
        assert not hasattr(mod, gone), f"{module}.{gone}"


def test_the_miss_protocol_is_written_in_one_module():
    root = os.path.dirname(dask_sql_tpu.__file__)
    pattern = re.compile(r"singleflight_get_or_build|defer_rebuild\(|"
                         r"_remember_family_locked")
    found = set()
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                with open(path, encoding="utf-8") as fh:
                    if pattern.search(fh.read()):
                        found.add(os.path.relpath(path, root))
    assert found == {os.path.join("physical", "programs.py")}
