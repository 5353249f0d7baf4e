"""One keyed compile cache for every engine's jitted step functions.

Before this module each layer kept its own memo (``_SOLVER_MEMO`` /
``_DRAIN_MEMO`` / ``_BP_ROUND_MEMO`` in solve.py) — and the layers that kept
*none* (``run_sharded`` re-wrapped a fresh closure in ``jax.jit`` per call)
recompiled their whole program on every invocation, which is exactly the
per-round cost the composed engines were drowning in (ISSUE 7 /
BENCH_multidevice.json ``compose/*``).  Centralizing the memo does three
things the scattered dicts could not:

* one *miss counter* — ``SolveStats.recompiles`` is a before/after snapshot
  of :func:`misses` around an engine run, so "no recompiles across BP
  rounds" is a testable contract (tests/test_runstate.py);
* one invalidation seam — ``repro.ops.on_spec_change`` drops every entry
  built from a replaced op spec, regardless of which layer built it;
* one place to express the build-once-reuse-forever rule that the
  persistent RunState carrier (DESIGN.md §2.6) depends on.

Keys are plain hashable tuples.  By convention the first element is a short
string naming the builder site (``"tiled-drain"``, ``"sharded-fn"``, ...)
and the second the op class, so invalidation by op never has to guess at
key layouts — but any hashable tuple works.

Across processes, :func:`enable_persistent_cache` points JAX's own
persistent compilation cache at a fixed directory.  Entry points
(``chip_smoke.py``, the benchmarks) call it; importing a library module
never does.
"""

from __future__ import annotations

import os
import pathlib
import threading
from typing import Any, Callable, Dict, Optional, Tuple

# <checkout>/.jax_cache: a fixed path (the path is part of the cache key, so
# a directory named after a tmp dir, a pid or the time would never hit).
PERSISTENT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to
    :data:`PERSISTENT_CACHE_DIR`.  Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(PERSISTENT_CACHE_DIR))
    return str(PERSISTENT_CACHE_DIR)

_LOCK = threading.RLock()
_CACHE: Dict[tuple, Any] = {}
_MISSES: int = 0
_HITS: int = 0


def get(key: tuple, build: Callable[[], Any]) -> Any:
    """Return the cached value for ``key``, building (and counting a miss)
    on first use.  ``build`` runs under the cache lock: concurrent workers
    asking for the same compiled step share one trace instead of racing
    (the scheduler/hybrid claim loops hit this from N threads at once)."""
    global _MISSES, _HITS
    with _LOCK:
        if key in _CACHE:
            _HITS += 1
            return _CACHE[key]
        _MISSES += 1
        value = build()
        _CACHE[key] = value
        return value


def misses() -> int:
    """Total cache misses (= compiled-step builds) so far in this process."""
    with _LOCK:
        return _MISSES


def hits() -> int:
    with _LOCK:
        return _HITS


def contains(key: tuple) -> bool:
    with _LOCK:
        return key in _CACHE


def invalidate(pred: Callable[[tuple], bool]) -> int:
    """Drop every entry whose key satisfies ``pred``; returns the count."""
    with _LOCK:
        dead = [k for k in _CACHE if pred(k)]
        for k in dead:
            del _CACHE[k]
        return len(dead)


def invalidate_op_class(op_cls: type) -> int:
    """Drop entries built for ``op_cls`` or any subclass (keys carry the op
    class — or an op *instance* — as their second element by convention)."""
    def pred(key: tuple) -> bool:
        if len(key) < 2:
            return False
        tagged = key[1]
        cls = tagged if isinstance(tagged, type) else type(tagged)
        return isinstance(cls, type) and issubclass(cls, op_cls)
    return invalidate(pred)


def clear() -> None:
    """Drop everything (counters included) — test isolation only."""
    global _MISSES, _HITS
    with _LOCK:
        _CACHE.clear()
        _MISSES = 0
        _HITS = 0


class MissSnapshot:
    """Context helper: ``recompiles`` = misses that happened inside.

    >>> with MissSnapshot() as snap:
    ...     run_engine(...)
    >>> stats = dataclasses.replace(stats, recompiles=snap.count)
    """

    def __enter__(self) -> "MissSnapshot":
        self._before = misses()
        return self

    def __exit__(self, *exc) -> Optional[bool]:
        self.count = misses() - self._before
        return None
