"""Persistent executable cache: XLA compiles that survive the process.

A process restart is the one fault the engine otherwise handles badly —
every rung of every hot query recompiles on the critical path of a
recovering fleet (ROADMAP item 3).  Flare (arXiv:1703.08219) and TQP
(arXiv:2203.01877) both argue the compiled artifact, not the plan, is the
unit of serving; this module applies that discipline by enabling the JAX
persistent compilation cache under ``serving.compile_cache.path``:

- executables are keyed by the lowered HLO (which embeds the plan-family
  shape, the pow2 bucket shapes, and the rung's kernel structure), so a
  restarted process that re-plans the same query family deserializes the
  executable from disk instead of re-running XLA;
- a half-written entry (crash mid-write) is a cache MISS, never an error:
  ``jax_raise_persistent_cache_errors`` stays False, so corruption degrades
  to a recompile (tests/unit/test_coldstart.py proves it);
- hit/miss attribution reaches the engine's own metrics: the process's
  one jax monitoring listener (observability/xla.py) gives every compile
  its cache verdict on the compiling thread, counted as
  ``resilience.compile_cache.hit`` / ``.miss``, stamped on the
  ``xla:compile`` span and, for a rung's compile, as ``persistent_hit`` on
  its ``compile:<rung>`` span; `stats` reads its process totals.

The JAX cache directory is process-global state: one path per process.
`enable` is idempotent for the same path and logs (rather than flips) on a
conflicting second path — the first serving Context wins.

The directory is placed from OUTSIDE where ``JAX_COMPILATION_CACHE_DIR`` is
set: jax itself reads that variable, so this module adopts the directory
(listener, counters and the cache knobs still attach) and never sets a
directory in code — a configured ``serving.compile_cache.path`` that differs
is logged once and ignored.  Where the variable is unset, `bench.py` and
`chip_smoke.py` use `checkout_path()`: one fixed directory inside the
checkout (the path is part of the cache key, so a directory that moves
never hits).
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Any, Dict, Optional

logger = logging.getLogger(__name__)

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
CONFIG_PATH_KEY = "serving.compile_cache.path"
CONFIG_MIN_COMPILE_KEY = "serving.compile_cache.min_compile_time_s"

_lock = threading.Lock()
_state: Dict[str, Any] = {"path": None, "adopted": False,
                          "ignored_logged": False}


def env_path() -> Optional[str]:
    """The cache directory placed from outside the program, if any."""
    return os.environ.get(ENV_DIR) or None


def checkout_path() -> str:
    """The fixed in-checkout cache directory (``<repo>/.jax_cache``) the
    bench and the chip smoke enable when the environment places none."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, ".jax_cache")


def enable(path: Optional[str], min_compile_time_s: float = 0.0) -> bool:
    """Point the JAX persistent compilation cache at `path` (idempotent).

    Returns True when the cache is active after the call — on `path`, or on
    the directory ``JAX_COMPILATION_CACHE_DIR`` names, which always wins
    (`enabled_path()` reports which).  The floor defaults to 0 seconds so
    even fast CPU-backend compiles persist (a restarted process pays
    trace+lower either way; the XLA compile is the part worth skipping)."""
    import jax

    adopted = env_path()
    if adopted is not None:
        path = adopted
    if not path:
        return False
    with _lock:
        current = _state["path"]
        if current == path:
            return True
        if current is not None:
            # jax holds ONE cache dir per process; flipping it mid-flight
            # would orphan the first Context's entries silently
            logger.warning(
                "persistent compile cache already enabled at %r; "
                "ignoring second path %r", current, path)
            return False
        try:
            if adopted is None:
                os.makedirs(path, exist_ok=True)
                # jax latches its cache-used decision at the FIRST compile
                # of the process: without a reset, enabling after any
                # compile has happened (earlier Context, notebook warm-up)
                # silently never persists anything
                from jax.experimental.compilation_cache import (
                    compilation_cache as jax_cc,
                )

                jax_cc.reset_cache()
                jax.config.update("jax_compilation_cache_dir", path)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              float(min_compile_time_s))
            # -1 disables the entry-size floor (0 would auto-raise it to the
            # jax default and drop the small CPU-test executables)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
            # a torn/corrupt cache entry must degrade to a recompile, never
            # fail the query that tripped over it
            jax.config.update("jax_raise_persistent_cache_errors", False)
        except Exception:  # dsql: allow-broad-except — the cache is an
            # optimization; a jax without these knobs serves cold
            logger.warning("could not enable the persistent compile cache",
                           exc_info=True)
            return False
        _state["path"] = path
        _state["adopted"] = adopted is not None
        logger.info("persistent compile cache %s at %s",
                    "adopted from the environment" if adopted is not None
                    else "enabled", path)
        return True


def disable() -> None:
    """Turn the persistent cache off (tests: undo process-global state).
    Resets jax's lazily-initialized cache object too — without that, a
    later enable() on a different path would keep writing to the old
    directory (jax binds the cache object on first use)."""
    import jax

    with _lock:
        if _state["path"] is None:
            return
        if not _state["adopted"]:
            # an adopted directory is the environment's: never unset it
            try:
                jax.config.update("jax_compilation_cache_dir", None)
                from jax.experimental.compilation_cache import (
                    compilation_cache as jax_cc,
                )

                jax_cc.reset_cache()
            except Exception:  # dsql: allow-broad-except — best-effort teardown
                logger.debug("could not reset jax compilation cache",
                             exc_info=True)
        _state["path"] = None
        _state["adopted"] = False


def maybe_enable(config, metrics=None) -> bool:
    """Enable from the ``serving.compile_cache.*`` config keys, or adopt the
    directory ``JAX_COMPILATION_CACHE_DIR`` places; no-op when neither is
    set.  Called from Context.__init__ so any serving process that sets
    the path gets restart-surviving executables."""
    path = config.get(CONFIG_PATH_KEY)
    adopted = env_path()
    if not path and adopted is None:
        return False
    if path and adopted is not None \
            and os.path.abspath(str(path)) != os.path.abspath(adopted):
        with _lock:
            first = not _state["ignored_logged"]
            _state["ignored_logged"] = True
        if first:
            logger.warning(
                "%s=%r places the persistent compile cache; ignoring "
                "%s=%r", ENV_DIR, adopted, CONFIG_PATH_KEY, path)
    ok = enable(str(path) if path else None,
                float(config.get(CONFIG_MIN_COMPILE_KEY, 0.0) or 0.0))
    if ok and metrics is not None:
        metrics.gauge("resilience.compile_cache.enabled", 1.0)
    return ok


def enabled_path() -> Optional[str]:
    with _lock:
        return _state["path"]


def stats() -> Dict[str, float]:
    """The process's compiles so far (observability/xla.py `totals`):
    ``hits`` and ``misses`` of the persistent cache, ``compiles`` (every
    executable built by XLA or loaded from the cache), and the seconds of
    ``compile_s`` (XLA), ``lower_s`` (trace + lowering) and
    ``cache_load_s`` (persistent-cache loads)."""
    from ..observability import xla

    return xla.totals()
