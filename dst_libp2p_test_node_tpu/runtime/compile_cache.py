"""One rule for JAX's persistent compilation cache, for every entry point.

A chip call starts on a fresh machine and a checkout is run many times, so
whatever a program compiles should be found again by the next run in the
same place. cli.main, chip_smoke.py and bench_configs.py call
`enable_compile_cache()` before their first compile:

  - JAX_COMPILATION_CACHE_DIR set: JAX reads it itself; nothing is set here.
  - otherwise: <checkout>/.jax_cache (git-ignored), with the thresholds the
    test harness uses (tests/conftest.py): entries that took >= 1 s to
    compile, of any size.

The directory is part of the cache key, so it is never built from a temp
name, a pid or a time.

It also starts the compile ledger (runtime/profiling.py): from here on every
program jax traces, lowers, compiles or loads from this cache is counted in
`profiling.process_record()` and in the turn's `stats<i>.json` "compile".
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Apply the rule above; returns the cache directory in effect."""
    from .profiling import register_compile_listeners

    register_compile_listeners()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
