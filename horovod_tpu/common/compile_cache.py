"""Where JAX's persistent compilation cache lives, and who writes it.

A cold ResNet-50 step compiles for tens of seconds per process, and a
launcher job pays that once per rank.  JAX reads
``JAX_COMPILATION_CACHE_DIR`` into ``jax_compilation_cache_dir`` itself;
where the operator set it, that is the cache and nothing here names
another.  Otherwise the cache goes to one fixed directory derived from
this package's own path (``<checkout>/.jax_cache``, git-ignored): the
directory is part of what makes a run find the previous run's entries,
so it never depends on the cwd, the pid or the time.  A process pinned to
the CPU platform gets no default cache.

**Who writes what.**  JAX writes entries from ``jax.distributed`` process
0 alone (``compiler._cache_write``: "contention for writes on some
filesystems"), and on a TPU the key holds the device assignment.  So
under one process per chip the entries process 0 wrote for the programs
of its own chip (the model, the optimizer, fuse and unfuse) are of no use
to the other processes, which recompiled theirs in every run.  :func:`configure_compile_cache` therefore wraps
``compiler._compile_and_write_cache``, the one place that holds the
compiled program, its key and its devices: a process other than 0 also
writes the entry of a program *all of whose devices are its own*, through
JAX's ``put_executable_and_time`` and under the rules ``_cache_write``
applies (no host callbacks, ``jax_persistent_cache_min_compile_time_secs``).
Those keys differ from process to process, so no two processes write one
file.  A program that spans processes has one key everywhere, and the
cache's ``put`` is a plain ``write_bytes``: two writers could hand a
reader a torn entry.  Such programs keep JAX's rule: process 0 writes,
everyone reads.  Nothing about keys or reads changes; a job whose
process-to-chip binding differs from the last run's misses and compiles.
On disk that is one set of local entries per chip of a host.

The wrapped symbols are private to one JAX version (written against
0.9.0).  They are checked before anything is touched; where one is
missing or takes other parameters, one warning names the JAX version,
JAX is left as it is, and processes other than 0 recompile their local
programs as they did before.
"""

from __future__ import annotations

import functools
import inspect
import os
import time
import warnings
from typing import Optional

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: What the write hook calls, with the parameters it passes by position.
_COMPILE_AND_WRITE_PARAMS = (
    "backend", "computation", "executable_devices", "compile_options",
    "host_callbacks", "module_name", "cache_key")
_PUT_PARAMS = (
    "cache_key", "module_name", "executable", "backend", "compile_time")


def _params(owner, name) -> Optional[tuple]:
    fn = getattr(owner, name, None)
    return tuple(inspect.signature(fn).parameters) if callable(fn) else None


@functools.cache
def _install_write_hook() -> bool:
    """Wrap ``compiler._compile_and_write_cache`` (module docstring), once
    per process; False, with one warning, where this JAX is not the one
    the hook was written against."""
    import jax
    from jax._src import compilation_cache, compiler, config, distributed
    from jax._src.lib import xla_client

    if not (_params(compiler, "_compile_and_write_cache")
            == _COMPILE_AND_WRITE_PARAMS
            and _params(compilation_cache, "put_executable_and_time")
            == _PUT_PARAMS
            and hasattr(distributed.global_state, "process_id")
            and hasattr(config, "persistent_cache_min_compile_time_secs")
            and hasattr(config, "raise_persistent_cache_errors")
            and hasattr(xla_client.DeviceList, "is_fully_addressable")):
        from .logging_util import get_logger

        get_logger("horovod_tpu.compile_cache").warning(
            "jax %s: compiler._compile_and_write_cache or "
            "compilation_cache.put_executable_and_time is not what the "
            "compile-cache write hook was written against (0.9.0); JAX is "
            "left as it is, and processes other than 0 recompile their "
            "local programs in every run", jax.__version__)
        return False
    upstream = compiler._compile_and_write_cache

    @functools.wraps(upstream)
    def compile_and_write_cache(backend, computation, executable_devices,
                                compile_options, host_callbacks, module_name,
                                cache_key):
        start = time.monotonic()
        executable = upstream(backend, computation, executable_devices,
                              compile_options, host_callbacks, module_name,
                              cache_key)
        compile_secs = time.monotonic() - start
        # Process 0 (also: no jax.distributed) wrote inside `upstream`.
        # Elsewhere `upstream` compiled and wrote nothing; the rules below
        # are `_cache_write`'s own.
        if (distributed.global_state.process_id != 0
                and executable_devices.is_fully_addressable
                and not host_callbacks
                and compile_secs
                >= config.persistent_cache_min_compile_time_secs.value):
            try:
                compilation_cache.put_executable_and_time(
                    cache_key, module_name, executable, backend,
                    int(compile_secs))
            except Exception as e:  # noqa: BLE001 - as `_cache_write`
                if config.raise_persistent_cache_errors.value:
                    raise
                warnings.warn(
                    f"Error writing persistent compilation cache entry for "
                    f"'{module_name}': {type(e).__name__}: {e}")
            else:
                from ..core import metrics

                metrics.inc("compile_cache_entries_written")
        return executable

    compiler._compile_and_write_cache = compile_and_write_cache
    return True


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
    _install_write_hook()
    return path
