"""On-device per-segment data-quality statistics.

Everything the pipeline already knows about the signal quality of a
segment — which bins the RFI stages zapped, how the bandpass is shaped,
whether a subband died or went hot, how non-Gaussian each channel is —
lives in device buffers the segment plan is about to throw away.  This
module packs those answers into ONE small ``[S, N_SCALARS + 2*B]``
float32 vector as a cheap epilogue of the existing plans
(:meth:`SegmentProcessor._waterfall_detect` calls
:func:`quality_stats_device` right before the boundary stack), so
quality telemetry costs two extra reads of buffers already resident —
no new plan, no extra HBM pass of the big baseband buffers.

Packed layout per stream (``B = quality_coarse_bins``)::

    [0]            zap_frac        fraction of spectrum bins zeroed
                                   (RFI s1 + manual mask; the chirp is
                                   unit-modulus, so zeros survive;
                                   sampled per Config.quality_subsample)
    [1]            bandpass_mean   mean of the coarse bandpass vector
    [2]            bandpass_var    population variance of the same
    [3]            sk_mean         mean spectral-kurtosis estimate
                                   over waterfall channels (M = T)
    [4]            sk_max          max SK estimate over channels
    [5]            dead_frac       channels with mean power below
                                   quality_dead_threshold x median
    [6]            hot_frac        channels with mean power above
                                   quality_hot_threshold x median
    [7 : 7+B]      occupancy map   zero-fraction per coarse spectrum
                                   bin (the RFI occupancy heat row)
    [7+B : 7+2B]   bandpass        mean |spec|^2 per coarse bin

The host side (:class:`QualityMonitor`) unpacks the vector into
``quality_*`` gauges (flat + per-stream labeled), feeds the EWMA
bandpass-drift detector, and returns the dict the segment span
journals (telemetry schema v9).  :func:`quality_stats_oracle` is the
float64 NumPy golden model the parity tests pin every plan family
against.
"""

from __future__ import annotations

import collections
import math

import numpy as np

from srtb_tpu.ops import scopes as S
from srtb_tpu.utils.metrics import metrics

# scalar slots ahead of the two coarse maps (see module docstring)
IDX_ZAP_FRAC = 0
IDX_BANDPASS_MEAN = 1
IDX_BANDPASS_VAR = 2
IDX_SK_MEAN = 3
IDX_SK_MAX = 4
IDX_DEAD_FRAC = 5
IDX_HOT_FRAC = 6
N_SCALARS = 7

DEFAULT_COARSE_BINS = 64

# gauge names (the single home; metrics._HELP and the report reference
# these semantics)
SCALAR_GAUGES = (
    ("quality_zap_fraction", IDX_ZAP_FRAC),
    ("quality_bandpass_mean", IDX_BANDPASS_MEAN),
    ("quality_bandpass_var", IDX_BANDPASS_VAR),
    ("quality_sk_mean", IDX_SK_MEAN),
    ("quality_sk_max", IDX_SK_MAX),
    ("quality_dead_frac", IDX_DEAD_FRAC),
    ("quality_hot_frac", IDX_HOT_FRAC),
)


def vector_length(coarse_bins: int) -> int:
    return N_SCALARS + 2 * int(coarse_bins)


def _coarse_split(n_spec: int, coarse_bins: int) -> tuple[int, int]:
    """(B, bins_per_coarse): clamp B to the spectrum length and round
    the spectrum down to an exact tiling (the truncated remainder —
    at most B-1 bins — is outside every statistic, zap_frac
    included: all stats share the one sampled coarse grid)."""
    b = max(1, min(coarse_bins, n_spec))
    return b, n_spec // b


@S.scoped(S.QUALITY)
def quality_stats_device(spec, wf, coarse_bins: int,
                         dead_threshold: float, hot_threshold: float,
                         subsample: int = 1):
    """Pack the per-stream quality vector on device.

    ``spec [S, n_spec]`` complex: the dedispersed spectrum AFTER RFI
    stage 1 + the manual mask (zapped bins are exactly zero — the
    chirp multiply is unit-modulus and preserves them).
    ``wf [S, F, T]`` complex: the waterfall AFTER the SK zap (zapped
    channels are zero rows).  Returns ``[S, N_SCALARS + 2*B]`` f32.

    ``subsample = k`` reads every k-th bin within each coarse bin and
    every k-th time sample of each waterfall channel: the statistics
    become sampled estimators (exact at k=1).  This is the overhead
    lever — XLA computes a strided slice of an elementwise producer
    per-element, so BOTH the honest read volume and any producer
    recompute the backend chooses scale down by k.  Telemetry does
    not need every bin; the science path always reads all of them.

    Plain jnp on purpose: the inputs are already HBM-resident and tiny
    next to the segment FFT traffic, and a jnp epilogue rides inside
    every plan family (monolithic / fused / staged / skzap)
    without new kernels.
    """
    import jax.numpy as jnp

    # coarse_bins/subsample are static Python ints (trace-time plan
    # constants sanitized by Config) — no int() coercion here, the
    # epilogue body must stay free of concretizing calls
    n_streams, n_spec = spec.shape[0], spec.shape[-1]
    b, per = _coarse_split(n_spec, coarse_bins)
    k = max(1, subsample)

    spec_s = spec[..., :b * per].reshape(n_streams, b, per)[..., ::k]
    p_spec = jnp.real(spec_s) ** 2 + jnp.imag(spec_s) ** 2  # [S, B, per/k]
    zero = (p_spec == 0).astype(jnp.float32)

    bandpass = jnp.mean(p_spec, axis=-1)                 # [S, B]
    occupancy = jnp.mean(zero, axis=-1)                  # [S, B]
    # coarse bins all hold the same sampled width, so the global zero
    # fraction is exactly the mean of the occupancy row — one big
    # reduction instead of two
    zap_frac = jnp.mean(occupancy, axis=-1)              # [S]
    bp_mean = jnp.mean(bandpass, axis=-1)                # [S]
    bp_var = jnp.mean((bandpass - bp_mean[:, None]) ** 2, axis=-1)

    # spectral kurtosis per waterfall channel, M sampled accumulations:
    # SK = ((M+1)/(M-1)) * (mean(p^2)/mean(p)^2 - 1); a zapped (zero)
    # channel reads 0 by convention, not NaN
    wf_s = wf[..., ::k]
    p_wf = jnp.real(wf_s) ** 2 + jnp.imag(wf_s) ** 2     # [S, F, T/k]
    m = wf_s.shape[-1]
    mean_p = jnp.mean(p_wf, axis=-1)                     # [S, F]
    mean_p2 = jnp.mean(p_wf * p_wf, axis=-1)
    denom = jnp.where(mean_p > 0, mean_p * mean_p, jnp.float32(1.0))
    sk = jnp.where(
        mean_p > 0,
        ((m + 1.0) / max(m - 1.0, 1.0)) * (mean_p2 / denom - 1.0),
        jnp.float32(0.0))
    sk_mean = jnp.mean(sk, axis=-1)
    sk_max = jnp.max(sk, axis=-1)

    med = jnp.median(mean_p, axis=-1, keepdims=True)     # [S, 1]
    dh = jnp.mean(jnp.stack([
        (mean_p < dead_threshold * med).astype(jnp.float32),
        (mean_p > hot_threshold * med).astype(jnp.float32)]), axis=-1)
    dead_frac, hot_frac = dh[0], dh[1]                   # [S]

    scalars = jnp.stack([zap_frac, bp_mean, bp_var, sk_mean, sk_max,
                         dead_frac, hot_frac], axis=-1)  # [S, 7]
    return jnp.concatenate(
        [scalars, occupancy, bandpass], axis=-1).astype(jnp.float32)


def quality_stats_oracle(spec: np.ndarray, wf: np.ndarray,
                         coarse_bins: int, dead_threshold: float,
                         hot_threshold: float,
                         subsample: int = 1) -> np.ndarray:
    """Float64 NumPy mirror of :func:`quality_stats_device` — the
    golden model tests/test_quality.py pins every plan family against
    (``subsample`` must match the device call's)."""
    spec = np.asarray(spec)
    wf = np.asarray(wf)
    n_streams, n_spec = spec.shape[0], spec.shape[-1]
    b, per = _coarse_split(n_spec, coarse_bins)
    k = max(1, int(subsample))

    spec_s = spec[..., :b * per].reshape(n_streams, b, per)[..., ::k]
    p_spec = np.abs(spec_s.astype(np.complex128)) ** 2
    zero = (p_spec == 0).astype(np.float64)
    bandpass = p_spec.mean(axis=-1)
    occupancy = zero.mean(axis=-1)
    zap_frac = occupancy.mean(axis=-1)
    bp_mean = bandpass.mean(axis=-1)
    bp_var = ((bandpass - bp_mean[:, None]) ** 2).mean(axis=-1)

    wf_s = wf[..., ::k]
    p_wf = np.abs(wf_s.astype(np.complex128)) ** 2
    m = wf_s.shape[-1]
    mean_p = p_wf.mean(axis=-1)
    mean_p2 = (p_wf * p_wf).mean(axis=-1)
    with np.errstate(divide="ignore", invalid="ignore"):
        sk = np.where(
            mean_p > 0,
            ((m + 1.0) / max(m - 1.0, 1.0))
            * (mean_p2 / np.where(mean_p > 0, mean_p ** 2, 1.0) - 1.0),
            0.0)
    sk_mean = sk.mean(axis=-1)
    sk_max = sk.max(axis=-1)
    med = np.median(mean_p, axis=-1, keepdims=True)
    dead_frac = (mean_p < dead_threshold * med).mean(axis=-1)
    hot_frac = (mean_p > hot_threshold * med).mean(axis=-1)

    scalars = np.stack([zap_frac, bp_mean, bp_var, sk_mean, sk_max,
                        dead_frac, hot_frac], axis=-1)
    return np.concatenate([scalars, occupancy, bandpass],
                          axis=-1).astype(np.float32)


def unpack_stats(vec: np.ndarray) -> dict:
    """Packed vector (``[S, 7+2B]`` or ``[7+2B]``) -> named arrays.
    B is recovered from the length (the layout is self-describing
    given N_SCALARS)."""
    v = np.asarray(vec)
    if v.ndim == 1:
        v = v[None, :]
    b = (v.shape[-1] - N_SCALARS) // 2
    return {
        "zap_frac": v[:, IDX_ZAP_FRAC],
        "bandpass_mean": v[:, IDX_BANDPASS_MEAN],
        "bandpass_var": v[:, IDX_BANDPASS_VAR],
        "sk_mean": v[:, IDX_SK_MEAN],
        "sk_max": v[:, IDX_SK_MAX],
        "dead_frac": v[:, IDX_DEAD_FRAC],
        "hot_frac": v[:, IDX_HOT_FRAC],
        "occupancy": v[:, N_SCALARS:N_SCALARS + b],
        "bandpass": v[:, N_SCALARS + b:N_SCALARS + 2 * b],
    }


class EWMADrift:
    """Exponentially-weighted drift detector on one scalar series.

    Tracks an EWMA mean and an EWM variance; an observation scoring
    more than ``threshold`` sigmas from the running mean is a drift
    alert.  The first ``warmup`` observations only train the
    estimates (score 0): the detector must learn THIS deployment's
    bandpass before judging it.  The estimates keep updating through
    an alert, so a persistent level shift is absorbed (and stops
    alerting) after ~1/alpha segments — the alert marks the
    *transition*, the gauges carry the new level."""

    def __init__(self, alpha: float = 0.05, threshold: float = 4.0,
                 warmup: int = 8):
        self.alpha = float(alpha)
        self.threshold = float(threshold)
        self.warmup = int(warmup)
        self.mean = 0.0
        self.var = 0.0
        self.n = 0

    def observe(self, x: float) -> tuple[float, bool]:
        """(drift score in sigmas, alert?) — then fold ``x`` in."""
        x = float(x)
        if self.n == 0:
            # seed the mean AT the first observation: starting from 0
            # would fold the series' DC level into the variance and
            # blind the detector for ~1/alpha segments
            self.mean = x
        if self.n < self.warmup:
            score, alert = 0.0, False
        else:
            # sigma floor: a perfectly constant warmup (synthetic
            # data) must not make the first real fluctuation infinite
            sigma = max(math.sqrt(max(self.var, 0.0)),
                        1e-12 + 1e-6 * abs(self.mean))
            score = abs(x - self.mean) / sigma
            alert = score > self.threshold
        d = x - self.mean
        self.mean += self.alpha * d
        self.var = (1.0 - self.alpha) * (self.var + self.alpha * d * d)
        self.n += 1
        return score, alert


TIMELINE_SPANS = 64


class QualityMonitor:
    """Host-side consumer of the packed quality vector: gauges, the
    bandpass drift detector, the journal dict, and a bounded timeline
    an incident bundle can attach (the quality context of a canary
    sensitivity regression).  ``None`` when ``Config.quality_stats``
    is off — the zero-cost-off None-hook pattern."""

    def __init__(self, drift_alpha: float = 0.05,
                 drift_threshold: float = 4.0, stream: str = ""):
        self.drift = EWMADrift(alpha=drift_alpha,
                               threshold=drift_threshold)
        self.stream = str(stream or "")
        self._timeline: collections.deque = collections.deque(
            maxlen=TIMELINE_SPANS)

    @classmethod
    def from_config(cls, cfg) -> "QualityMonitor | None":
        if not getattr(cfg, "quality_stats", False):
            return None
        return cls(
            drift_alpha=float(getattr(cfg, "quality_drift_alpha",
                                      0.05)),
            drift_threshold=float(getattr(cfg, "quality_drift_threshold",
                                          4.0)),
            stream=str(getattr(cfg, "stream_name", "") or ""))

    def observe(self, qvec, segment: int = -1) -> dict:
        """One drained segment's vector -> the journal dict.  Multi-
        datastream segments are averaged across S for the gauges and
        the drift series (per-datastream detail stays recoverable
        from the packed vector a test holds; spans carry the
        average)."""
        v = np.asarray(qvec, dtype=np.float64)
        if v.ndim == 1:
            v = v[None, :]
        mean = v.mean(axis=0)
        score, alert = self.drift.observe(mean[IDX_BANDPASS_MEAN])
        lbl = {"stream": self.stream} if self.stream else None
        for gname, idx in SCALAR_GAUGES:
            metrics.set(gname, float(mean[idx]))
            if lbl:
                metrics.set(gname, float(mean[idx]), labels=lbl)
        metrics.set("quality_drift_score", score)
        if lbl:
            metrics.set("quality_drift_score", score, labels=lbl)
        if alert:
            metrics.add("quality_drift_alerts")
            if lbl:
                metrics.add("quality_drift_alerts", labels=lbl)
        b = (mean.shape[0] - N_SCALARS) // 2
        # vectorized rounding: this runs once per drained segment in
        # the pipeline's span path, so 2*B Python-level round() calls
        # would be the most expensive part of the whole quality
        # epilogue (the device side is reduction-fused and subsampled)
        out = {
            "zap_frac": round(float(mean[IDX_ZAP_FRAC]), 5),
            "bandpass_mean": round(float(mean[IDX_BANDPASS_MEAN]), 5),
            "bandpass_var": round(float(mean[IDX_BANDPASS_VAR]), 5),
            "sk_mean": round(float(mean[IDX_SK_MEAN]), 5),
            "sk_max": round(float(mean[IDX_SK_MAX]), 5),
            "dead_frac": round(float(mean[IDX_DEAD_FRAC]), 5),
            "hot_frac": round(float(mean[IDX_HOT_FRAC]), 5),
            "drift_score": round(score, 3),
            "drift_alert": bool(alert),
            "occupancy": np.round(
                mean[N_SCALARS:N_SCALARS + b], 4).tolist(),
            "bandpass": np.round(
                mean[N_SCALARS + b:N_SCALARS + 2 * b], 5).tolist(),
        }
        self._timeline.append(dict(out, segment=int(segment)))
        return out

    def timeline(self) -> list[dict]:
        """Recent per-segment quality dicts, oldest first (bounded:
        the incident-bundle attachment)."""
        return list(self._timeline)
