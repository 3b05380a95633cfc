"""JAX's persistent compilation cache, placed from outside the program, and
the time the process spends compiling.

Call :func:`enable_compile_cache` at the top of an entry point's ``main()``
(never at import). If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps
its cache there and nothing is set in code. Otherwise the cache goes to the
fixed directory ``<repo>/.jax_cache``: the path is part of the cache's key, so
it is never derived from a temporary name, a pid or the time.

From that call on, :func:`compile_seconds` says how long the process has
spent tracing, lowering and compiling (or loading from the cache), from
JAX's own monitoring events; a job's start-up is mostly that and its first
chunk.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import List, Optional, Tuple

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"

# JAX's monitoring events for the three phases of making a program; the last
# holds the cache load on a hit
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

_spans: List[Tuple[float, float]] = []
_listening = False


def _on_span(event: str, start: float, end: float, **_) -> None:
    if event in COMPILE_EVENTS:
        _spans.append((start, end))


def listen_for_compiles() -> None:
    """Start keeping the spans of the compile events (once per process)."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_time_span_listener(_on_span)
        _listening = True


def compile_seconds() -> Optional[float]:
    """Wall seconds since :func:`listen_for_compiles` in which the process
    was tracing, lowering or compiling: the union of the compile events'
    spans, so a function traced inside another's trace, or a kernel lowered
    inside its caller's lowering, counts once. None before
    :func:`listen_for_compiles`."""
    if not _listening:
        return None
    total, end = 0.0, float("-inf")
    for a, b in sorted(_spans):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and start counting compile
    time; returns the cache directory in use."""
    listen_for_compiles()
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
