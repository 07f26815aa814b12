"""Device/platform facts shared by the pipeline: the sanctioned D2H
fetch and the TPU test that gates Pallas interpret mode.  The platform
itself is whatever ``JAX_PLATFORMS`` (or JAX's default) selects —
nothing here overrides it.
"""

from __future__ import annotations


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
