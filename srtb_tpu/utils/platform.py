"""Device/platform facts shared by the pipeline: the sanctioned D2H
fetch and the TPU test that gates Pallas interpret mode.  The platform
itself is whatever ``JAX_PLATFORMS`` (or JAX's default) selects —
nothing here overrides it.
"""

from __future__ import annotations

import functools


def to_host(x):
    """Explicit device->host fetch for possibly-device arrays — the
    single home of the sanctioned D2H spelling.  ``np.asarray`` on a
    ``jax.Array`` is an *implicit* transfer (srtb-lint sync-hot-path;
    the runtime sanitizer's tripwire raises on it), so every sink/GUI
    fetch funnels through here."""
    import jax
    import numpy as np

    if isinstance(x, jax.Array):
        return jax.device_get(x)
    return np.asarray(x)


def to_host_rows(x, lo: int, hi: int):
    """Rows ``[..., lo:hi, :]`` of a possibly-device array on the host,
    fetched on their own: a consumer that takes a multi-GB array a block
    at a time (the candidate writer, a 2^30-sample segment's 4.29 GB
    waterfall) never holds the whole host copy that :func:`to_host`
    makes and the array memoizes.  A lazy handle with a ``rows`` method
    (``pipeline/runtime._DeadlineArray``) fetches under its own
    deadline."""
    import jax
    import numpy as np

    if hasattr(x, "rows"):
        return x.rows(lo, hi)
    if isinstance(x, jax.Array):
        return jax.device_get(_row_block(x, lo, hi - lo))
    return np.asarray(x)[..., lo:hi, :]


@functools.lru_cache(maxsize=None)
def _row_block_program():
    """The one jitted slicer (made once: ``jax`` is imported late in
    this module)."""
    import jax

    def rows(a, start, count):
        return jax.lax.dynamic_slice_in_dim(a, start, count, a.ndim - 2)

    return jax.jit(rows, static_argnums=2)


def _row_block(x, lo: int, count: int):
    """``count`` rows from ``lo`` of the device array ``x``, sliced on
    the device by one program whatever ``lo`` is."""
    return _row_block_program()(x, lo, count)


def on_accelerator() -> bool:
    """Whether the default JAX backend is TPU hardware — the single home
    of the test that gates Pallas interpret-mode downgrades."""
    import jax

    return jax.default_backend() == "tpu"


def device_bytes_limit() -> int | None:
    """``bytes_limit`` of the first local device's ``memory_stats()``:
    what the runtime lets a process allocate on one chip; None where the
    platform reports none (the CPU backend)."""
    import jax

    stats = jax.local_devices()[0].memory_stats()
    return (stats or {}).get("bytes_limit")
