"""Stable names for the device stages.

Every stage function the plans are composed of runs under one
``jax.named_scope`` from this table, put on it once, where it is defined:
``SegmentProcessor``'s fused, ring, staged and batch plans and
``parallel/segment_dist.py``'s step inherit the names by calling the
functions.  A scope is HLO metadata (an operation's ``op_name`` reads
``jit(_process_ring)/jit(main)/srtb.fft_r2c/...``): it changes no
arithmetic, shape, dtype or fusion boundary and costs nothing at run
time.  JAX leaves metadata out of its persistent-cache key by default,
so a cache written before a scope existed serves executables without
it; ``utils/compile_cache.enable_compile_cache`` therefore keys this
repo's cache on the metadata too, and an edit that moves a traced line
recompiles once.

A device operation belongs to the INNERMOST ``srtb.`` component of its
``op_name`` (scopes nest: the sub-byte R2C unpacks inside
``srtb.fft_r2c``, and those operations are ``srtb.unpack``'s).  A fusion
is one operation and carries its root's name: where XLA fuses two
stages, the time goes to the root's stage.  The ingest ring's
concatenate and carry slice (``pipeline/segment.py``'s ring variants)
run under ``srtb.ring``; where XLA folds the concatenate into the R2C's
first pass only the slice is left to carry the name.  What runs under
no scope (copies the compiler made, the grid's collectives over ``dm``)
is reported as unscoped.
``benchmark/reducers/scopes.py`` sums a trace by these names.
"""

from __future__ import annotations

import functools

UNPACK = "srtb.unpack"        # bytes -> windowed float32 samples
FFT_R2C = "srtb.fft_r2c"      # the whole segment R2C: pack, DFT stages,
#                               twiddles, transposes, Hermitian post
RFI_S1 = "srtb.rfi_s1"        # mean-power zap + normalize + manual mask
CHIRP = "srtb.chirp"          # the chirp multiply and, where the chirp is
#                               made in the step, the df64 phase + sin/cos
WATERFALL = "srtb.waterfall"  # the per-channel backward C2C (+ de-window)
DETECT = "srtb.detect"        # SK zap, time series, boxcars, S/N
QUALITY = "srtb.quality"      # the data-quality epilogue
RING = "srtb.ring"            # the ingest ring's own work: carry ++ new
#                               assembled, the next carry sliced off


def scoped(name: str):
    """Decorator: run the function's trace under ``jax.named_scope(name)``
    (jax is imported at trace time: the host-only tools that import a
    decorated module for its NumPy half stay free of it)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
