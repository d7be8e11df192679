"""Where JAX's persistent compilation cache lives.

A cold ResNet-50 step compiles for tens of seconds per process, and a
launcher job pays that once per rank.  JAX reads
``JAX_COMPILATION_CACHE_DIR`` into ``jax_compilation_cache_dir`` itself;
where the operator set it, that is the cache and nothing here names
another.  Otherwise the cache goes to one fixed directory derived from
this package's own path (``<checkout>/.jax_cache``, git-ignored): the
directory is part of what makes a run find the previous run's entries,
so it never depends on the cwd, the pid or the time.  A process pinned to
the CPU platform gets no default cache.
"""

from __future__ import annotations

import os
from typing import Optional

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> Optional[str]:
    """Place the cache (see module docstring) and return its directory,
    or None when there is none.

    Call before the process compiles anything: JAX decides once, at its
    first compilation, whether a cache is in use."""
    import jax

    path = jax.config.jax_compilation_cache_dir
    if not path:
        if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
            # Pinned to the CPU (tests, dry runs): compiles are cheap, and
            # jaxlib 0.9.0's XLA:CPU loader logs a machine-feature
            # mismatch error for every entry it reads back.
            return None
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX's default skips programs that compile in under a second.  The
    # eager plane is made of such programs (fuse, unfuse, one optimizer op
    # per parameter shape), several hundred per ResNet-50 process, so
    # cache them all unless the operator chose a threshold.
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
