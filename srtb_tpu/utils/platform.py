"""Device/platform facts shared by the pipeline: the sanctioned D2H
fetch, the TPU test that gates Pallas interpret mode, and the per-device
HBM peak table the roofline share divides by.  The platform itself is
whatever ``JAX_PLATFORMS`` (or JAX's default) selects — nothing here
overrides it.
"""

from __future__ import annotations

# Peak HBM bandwidth in GB/s, keyed by ``jax.Device.device_kind``.
# v5e (JAX calls it "TPU v5 lite"): 819 GB/s (Google Cloud documentation,
# "TPU v5e").  A kind that is not listed has no roofline: callers get
# None, never another chip's peak.
HBM_PEAK_GBPS = {
    "TPU v5 lite": 819.0,
}


def hbm_peak_gbps(device_kind: str | None = None) -> float | None:
    """Peak HBM GB/s of ``device_kind`` (default: device 0 of the
    default backend), or None when the kind is not in the table."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return HBM_PEAK_GBPS.get(device_kind)


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
