"""Persistent XLA compilation cache — the FFTW-wisdom analog.

The reference persists FFTW plans to ``fft_fftw_wisdom_path`` so later
runs skip planning (ref: fft/fftw_wrapper.hpp:196-238, config.hpp:176).
The TPU equivalent of "planning" is XLA compilation (tens of seconds for
the big fused segment program); JAX's on-disk compilation cache plays
the role of the wisdom file, so a restarted observation resumes at full
speed.

Where the cache lives, in order: ``JAX_COMPILATION_CACHE_DIR`` if the
environment sets it (JAX reads the variable itself; nothing here sets a
directory then), else the explicit ``path``, else ``<checkout>/.jax_cache``.
The path is part of the cache key, so it is always a fixed one.
"""

from __future__ import annotations

import os

from srtb_tpu.utils.logging import log

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(path: str = "") -> str | None:
    """Turn on JAX's persistent compilation cache and return the
    directory it uses.  A cache that cannot be enabled raises: on the
    chip that is a finding, not a warning.

    CPU backends are excluded (returns None): the cache exists for the
    TPU pipeline's long compiles, while XLA:CPU caches AOT *machine
    code* keyed without the host's CPU features — after a host swap a
    stale entry loads with a SIGILL warning ("Machine type used for
    XLA:CPU compilation doesn't match") and can crash mid-run.  CPU
    compiles are cheap; correctness across host swaps is not."""
    import jax

    if jax.default_backend() == "cpu":
        log.debug("[compile_cache] skipped on CPU (host-fragile AOT)")
        return None
    # cache everything, however small — streaming restart latency is
    # what matters, not disk
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # key the cache on the programs' metadata too.  JAX leaves it out by
    # default, and says what that costs: "executables loaded from the
    # cache may have stale metadata, which may show up in, e.g.,
    # profiles".  The stage scopes (ops/scopes.py) ARE metadata: a cache
    # written before they existed served all seven programs of the
    # served plan to the build that has them (7 hits, 0 misses, my chip
    # run, PR 27) and its trace named no stage.  The price is one
    # compile after a source edit that moves a traced line.
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        log.debug(f"[compile_cache] JAX_COMPILATION_CACHE_DIR={env_dir}")
        return env_dir
    path = path or DEFAULT_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    log.debug(f"[compile_cache] enabled at {path}")
    return path
