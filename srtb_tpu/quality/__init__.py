"""Science observatory: on-device data-quality statistics and the
end-to-end pulse-injection canary.

The observability stack (tracing, incidents, SLO burn, device time) says
the engine is *fast and alive*; this package says the science is
*right*:

- :mod:`srtb_tpu.quality.stats` — per-segment data-quality statistics
  (zapped fraction, coarse RFI occupancy, spectral-kurtosis summary,
  bandpass mean/variance, dead/hot channels) computed on device as a
  cheap epilogue of the existing segment plans, plus the host-side
  EWMA bandpass-drift detector and the QualityMonitor that turns the
  packed vector into gauges and journal fields.
- :mod:`srtb_tpu.quality.canary` — a deterministic synthetic dispersed
  pulse injected into the raw uint8 stream every
  ``Config.canary_every_segments`` segments, recovered S/N checked at
  the detection stage; the sensitivity ratio drives detection health
  (/healthz, SLO) and canary segments are quarantined from science
  outputs.
"""

from srtb_tpu.quality.canary import CanaryController
from srtb_tpu.quality.stats import (
    EWMADrift,
    QualityMonitor,
    quality_stats_device,
    quality_stats_oracle,
    unpack_stats,
)

__all__ = [
    "CanaryController",
    "EWMADrift",
    "QualityMonitor",
    "quality_stats_device",
    "quality_stats_oracle",
    "unpack_stats",
]
