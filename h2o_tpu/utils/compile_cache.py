"""Persistent XLA compilation cache placement — ONE rule, shared by the
server entry point, cluster init, `h2o.init()`, the first `train_model` of
a process, `bench.py` and `chip_smoke.py`.

The cache directory is part of the cache's key, so it must not move
between processes that are meant to share it:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module sets
  NO directory in code (the deployment placed the cache — honour it, on any
  backend, CPU included).
- otherwise, on an accelerator backend: ``<checkout>/.xla_cache`` — a fixed
  path beside the package (git-ignored), never ``~/.cache``, a temp name, a
  pid or a time.
- otherwise (CPU, variable unset): off. jax 0.9.0's CPU executable
  serializer segfaulted once mid-suite, so the test mesh opts in only
  through the environment variable.

An unusable directory is an error, not a warning: a process that was
meant to replay its compiles and silently recompiles them instead is the
failure the chip budget cannot afford to hide."""

from __future__ import annotations

import os

_ENSURED = False
_LOC: str | None = None

#: ``<checkout>/.xla_cache`` — fixed relative to the package, so every
#: process started from one checkout shares one cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")


def ensure() -> str | None:
    """Apply the placement rule once per process (idempotent); returns the
    directory in effect, or None when the cache is off. Raises OSError when
    the directory cannot be created or written."""
    global _ENSURED, _LOC
    if _ENSURED:
        return _LOC
    import jax

    loc = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not loc and jax.default_backend() != "cpu":
        loc = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", loc)
    if loc:
        os.makedirs(loc, exist_ok=True)
        if not os.access(loc, os.W_OK | os.X_OK):
            raise PermissionError(
                f"compile cache dir {loc!r} is not writable")
        # cache EVERYTHING: the cold start is the sum of dozens of small
        # programs, and JAX's default 1 s floor would leave every one of
        # them recompiling in the "warm" process
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        from .log import info

        info(f"persistent XLA compile cache at {loc}")
    _ENSURED, _LOC = True, loc or None
    return _LOC
