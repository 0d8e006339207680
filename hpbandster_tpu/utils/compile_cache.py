"""Persistent XLA compile cache — one switch, every process tier.

The fused tiers' first-run cost is dominated by XLA compiles; jax can
persist compiled executables to disk so the SECOND process on a machine
pays none of it. Every startup path that is about to build device
programs (``FusedBOHB``, ``BatchedExecutor``, ``Worker``,
``TPUBatchedWorker``, ``ServePool``, the test suite) calls
the one function here.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads the directory from its own
  environment variable and this module sets none in code — whoever runs
  the program places the cache.
* unset: ``<checkout>/.jax_compilation_cache``, resolved from this
  package's own location. The directory is part of the cache key's
  surroundings, so it is a fixed path: never a temp name, a pid or a time.

Either way the min-compile-time threshold is set in code, to zero: every
program persists. With jax's default of one second, a program that
compiles in about a second is written by whichever run happens to take
1.01 s, so what a second run finds would depend on timing noise (seen on
the chip: a warm ``chip_smoke.py`` run added two entries). ``HPB_XLA_CACHE=0``
disables the cache entirely (e.g. hermetic CI).
"""

from __future__ import annotations

import os

__all__ = ["enable_persistent_compile_cache", "DEFAULT_CACHE_DIR"]

#: min compile seconds worth persisting: none — see the module docstring
_MIN_COMPILE_TIME_S = 0.0

#: the in-checkout default (gitignored), used when the environment names
#: no directory
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compilation_cache",
)

_enabled_dir: str = ""


def enable_persistent_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns the directory
    in use ('' when disabled). Safe to call from any tier, any number of
    times; only the first effective call touches jax config."""
    global _enabled_dir
    if os.environ.get("HPB_XLA_CACHE", "") == "0":
        return ""
    if _enabled_dir:
        return _enabled_dir
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", _MIN_COMPILE_TIME_S
    )
    _enabled_dir = cache_dir
    return _enabled_dir
