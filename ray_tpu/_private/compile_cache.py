"""Where the program keeps JAX's persistent compilation cache.

One rule for every process that runs JAX for the program: if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no code names
another directory; if it is not, the cache lives at a fixed path inside the
checkout.  The path is part of the cache's use: a directory that moves
between runs (a temporary name, a pid, a timestamp) never hits.

Daemons and workers get the directory through their environment
(`node.child_env`); a process that runs JAX in-process — a benchmark, the
test session — calls `enable_compile_cache()` before its first compile.
"""

from __future__ import annotations

import os
from typing import Dict

ENV = "JAX_COMPILATION_CACHE_DIR"

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_counts: Dict[str, int] = {}


def compile_cache_dir() -> str:
    """The directory in force: the variable's value, else
    ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV) or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point this process's JAX at the cache directory (only when the
    variable did not already do so) and start counting hits and misses.
    Idempotent.  Returns the directory."""
    import jax
    from jax import monitoring

    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    if not _counts:
        _counts.update(dict.fromkeys(_EVENTS.values(), 0))

        def _on_event(event: str, **_kw) -> None:
            name = _EVENTS.get(event)
            if name:
                _counts[name] += 1

        monitoring.register_event_listener(_on_event)
    return compile_cache_dir()


def compile_cache_stats() -> Dict[str, object]:
    """Since `enable_compile_cache()`: compiles that consulted the
    persistent cache, those it answered, and entries written to it (a
    compile shorter than JAX's write threshold is neither)."""
    return {"dir": compile_cache_dir(),
            **{name: _counts.get(name, 0) for name in _EVENTS.values()}}
