"""Seconds the run spent tracing, lowering and compiling (or loading from
the compile cache): ``compile_seconds()`` of the program's
``repro.launch.compile_cache``, which counts JAX's ``/jax/core/compile/*``
monitoring events from ``enable_compile_cache`` on. Read after the window,
so it holds set-up and whatever compiled later: the window compiles
nothing, and the chunk program's second load for its memory analysis hits
the in-process cache. None for a program that keeps no such count."""


def read(ctx):
    try:
        from repro.launch import compile_cache
    except ImportError:
        return None
    seconds = getattr(compile_cache, "compile_seconds", None)
    return seconds() if seconds is not None else None
