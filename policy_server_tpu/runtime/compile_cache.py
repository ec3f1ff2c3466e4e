"""Where compiled programs are kept, and how many were compiled.

One rule for the persistent XLA compilation cache, applied by
:func:`configure` before the first compile of a process:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself and
  this program sets no directory in code (a launcher, a container image or
  a chip tool that places the cache must win);
* unset — the cache lives at ``<checkout>/.jax_cache``, a fixed path next
  to the package, so that a second run of the same checkout finds what
  the first one compiled. Never a temporary, pid- or time-derived path: a
  directory that moves never hits.

Either way every compiled program is kept (both size/time thresholds 0):
a boot compiles dozens of sub-second programs whose sum is the cold-boot
time.

:class:`CompileCounter` counts what the compiler actually did, from JAX's
own monitoring events — the boot report and ``/metrics`` read it, so a
warm cache shows as fewer programs compiled, not as a guess.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# jax._src.dispatch.BACKEND_COMPILE_EVENT / jax._src.compiler: one duration
# event per program handed to the backend (persistent-cache hits included),
# one plain event per persistent-cache hit
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def configure() -> dict:
    """Place the persistent compilation cache (see module docstring).
    Returns ``{"dir", "from_env", "populated_on_entry"}`` for the boot
    report. Idempotent; touches no backend."""
    import jax

    from_env = os.environ.get(CACHE_DIR_ENV)
    if from_env:
        cache_dir = from_env
    else:
        cache_dir = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        with os.scandir(cache_dir) as entries:
            populated = next(entries, None) is not None
    except OSError:  # not created yet
        populated = False
    return {
        "dir": cache_dir,
        "from_env": bool(from_env),
        "populated_on_entry": populated,
    }


class CompileCounter:
    """Process-wide counts of programs handed to the XLA backend, split
    into persistent-cache hits and real compiles."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._programs = 0  # guarded-by: _lock
        self._cache_hits = 0  # guarded-by: _lock
        self._seconds = 0.0  # guarded-by: _lock

    def install(self) -> "CompileCounter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self._programs += 1
                self._seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self._cache_hits += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "programs": self._programs,
                "cache_hits": self._cache_hits,
                "compiled": self._programs - self._cache_hits,
                "seconds": round(self._seconds, 3),
            }


_counter_lock = threading.Lock()
_counter: CompileCounter | None = None


def counter() -> CompileCounter:
    """The process's one installed :class:`CompileCounter` (JAX's
    listeners are process-global, so is this)."""
    global _counter
    with _counter_lock:
        if _counter is None:
            _counter = CompileCounter().install()
        return _counter
