"""RFI mitigation kernels.

Three methods, mirroring the reference:
- stage 1: average-intensity threshold zap with normalization fused in
  (ref: pipeline/rfi_mitigation_pipe.hpp:50-80);
- manual frequency-range zap from a "a-b, c-d" config string
  (ref: spectrum/rfi_mitigation.hpp:63-158);
- stage 2: spectral-kurtosis zap over the dynamic spectrum
  (ref: spectrum/rfi_mitigation.hpp:290-341,
  mitigate_rfi_spectral_kurtosis_method_2).

All are pure jittable functions over the whole spectrum — the reference's
map_average / multi_mapreduce reductions become jnp.mean/sum that XLA maps
onto the VPU reduction trees.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from srtb_tpu.ops import scopes as S
from srtb_tpu.utils.logging import log


def _norm(c: jnp.ndarray) -> jnp.ndarray:
    """|c|^2 like srtb::norm (ref: math.hpp:58-70)."""
    return jnp.real(c) ** 2 + jnp.imag(c) ** 2


@S.scoped(S.RFI_S1)
def mitigate_rfi_average_and_normalize(
        spectrum: jnp.ndarray, threshold: float,
        normalization_coefficient) -> jnp.ndarray:
    """Zap channels whose power exceeds ``threshold * mean power``; scale the
    survivors by the normalization coefficient
    (ref: rfi_mitigation_pipe.hpp:50-80).

    The coefficient is ``(N^2 / spectrum_channel_count)^(-1/2)`` computed by
    the caller — it undoes the two unnormalized FFTs' N-growth
    (ref: rfi_mitigation_pipe.hpp:61-65).
    """
    power = _norm(spectrum)
    mean_power = jnp.mean(power, axis=-1, keepdims=True)
    return mitigate_rfi_s1_given_mean(spectrum, mean_power, threshold,
                                      normalization_coefficient)


@S.scoped(S.RFI_S1)
def mitigate_rfi_s1_given_mean(spectrum: jnp.ndarray, mean_power,
                               threshold: float,
                               normalization_coefficient) -> jnp.ndarray:
    """The elementwise half of RFI stage 1, with the mean power supplied
    by the caller — the form the fused spectrum tail folds into the
    forward FFT's final pass (the mean then comes from
    :func:`mean_power_packed` over the packed C2C output instead of a
    separate sweep over the materialized spectrum)."""
    power = _norm(spectrum)
    zap = power > threshold * mean_power
    return jnp.where(zap, jnp.zeros((), dtype=spectrum.dtype),
                     spectrum * normalization_coefficient)


@S.scoped(S.RFI_S1)
def s1_zap(spectrum: jnp.ndarray, mean_power, threshold: float,
           zap_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Which bins RFI stage 1 and the manual mask zap, as booleans: the
    decisions of :func:`mitigate_rfi_s1_given_mean` and
    :func:`mitigate_rfi_manual` for a caller that applies them in a
    select of its own (the fused tail's final one)."""
    zap = _norm(spectrum) > threshold * mean_power
    return zap if zap_mask is None else zap | zap_mask


@S.scoped(S.RFI_S1)
def mean_power_packed(zf: jnp.ndarray) -> jnp.ndarray:
    """Mean ``|X_k|^2`` over the m dropped-Nyquist rfft bins, computed
    from the packed half-size C2C output ``zf [..., m]`` WITHOUT forming
    the spectrum (keepdims ``[..., 1]``).

    Parseval: with z[t'] = x[2t'] + i·x[2t'+1] and F = FFT_m(z)
    (unnormalized), sum_t x^2 = (1/m)·sum_k |F_k|^2, and the real-input
    Hermitian symmetry of the full 2m-point transform gives

        sum_{k=0}^{m-1} |X_k|^2 = sum_k |F_k|^2 + 2·Re(F_0)·Im(F_0)

    (X_0 = Re F_0 + Im F_0, X_m = Re F_0 - Im F_0, so X_0^2 - X_m^2 =
    4·Re F_0·Im F_0).  This lets the RFI stage-1 threshold be evaluated
    inside the same pass that writes the spectrum: the mean is a
    reduction over the FFT's already-materialized input, not a re-read
    of its output.  Agrees with the direct ``jnp.mean(|spec|^2)`` to
    float32 rounding (pinned in tests/test_fusion.py); decision flips
    are only possible for bins within ~1 ulp of threshold·mean.
    """
    m = zf.shape[-1]
    p = _norm(zf)
    total = jnp.sum(p, axis=-1, keepdims=True)
    f0 = zf[..., :1]
    return (total + 2.0 * jnp.real(f0) * jnp.imag(f0)) / m


def normalization_coefficient(n_channels: int,
                              spectrum_channel_count: int) -> float:
    """(N^2/spectrum_channel_count)^-0.5 in f32, matching the reference's
    float evaluation (ref: rfi_mitigation_pipe.hpp:61-65)."""
    n = np.float32(n_channels)
    return float(np.power(n * n / np.float32(spectrum_channel_count),
                          np.float32(-0.5)))


# ----------------------------------------------------------------
# manual frequency-range zap
# ----------------------------------------------------------------

def eval_rfi_ranges(mitigate_rfi_freq_list: str) -> list[tuple[float, float]]:
    """Parse "11-12, 15-90" into (low, high) MHz pairs
    (ref: spectrum/rfi_mitigation.hpp:63-88)."""
    ranges = []
    text = mitigate_rfi_freq_list.strip()
    if not text:
        return ranges
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        pieces = [p for p in part.split("-") if p.strip()]
        if len(pieces) != 2:
            log.warning(f"[eval_rfi_ranges] cannot parse {part!r}")
            continue
        ranges.append((float(pieces[0]), float(pieces[1])))
    return ranges


def rfi_ranges_to_bins(ranges, n_channels: int, baseband_freq_low: float,
                       baseband_bandwidth: float) -> list[tuple[int, int]]:
    """Host-side: frequency ranges -> inclusive ``(lo, hi)`` bin ranges.

    Bin mapping matches the reference: bin = round((f - f_low) / bw * (N-1)),
    inclusive on both ends, with range order flipped when the band is
    inverted (ref: spectrum/rfi_mitigation.hpp:102-143).  A range that
    leaves the band is warned about and left out.
    """
    bins = []
    bw_sign = np.signbit(baseband_bandwidth)
    freq_high = baseband_freq_low + baseband_bandwidth
    for rfi_low, rfi_high in ranges:
        if np.signbit(rfi_high - rfi_low) != bw_sign:
            rfi_low, rfi_high = rfi_high, rfi_low
        lo = int(round((rfi_low - baseband_freq_low) / baseband_bandwidth
                       * (n_channels - 1)))
        hi = int(round((rfi_high - baseband_freq_low) / baseband_bandwidth
                       * (n_channels - 1)))
        if 0 <= lo <= hi < n_channels:
            bins.append((lo, hi))
        else:
            log.warning(
                f"[mitigate_rfi_manual] RFI range {rfi_low} - {rfi_high} MHz "
                f"out of baseband range {baseband_freq_low} - {freq_high} MHz")
    return bins


def rfi_ranges_to_mask(ranges, n_channels: int, baseband_freq_low: float,
                       baseband_bandwidth: float) -> np.ndarray | None:
    """Host-side: turn frequency ranges into a boolean zap mask over bins
    (:func:`rfi_ranges_to_bins`).  Returns None when there is nothing to
    zap (lets jit skip the multiply).
    """
    bins = rfi_ranges_to_bins(ranges, n_channels, baseband_freq_low,
                              baseband_bandwidth)
    if not bins:
        return None
    mask = np.zeros(n_channels, dtype=bool)
    for lo, hi in bins:
        mask[lo:hi + 1] = True
    return mask


@S.scoped(S.RFI_S1)
def mitigate_rfi_manual(spectrum: jnp.ndarray,
                        zap_mask: jnp.ndarray | None) -> jnp.ndarray:
    """Apply a precomputed zap mask (ref: rfi_mitigation.hpp:97-158)."""
    if zap_mask is None:
        return spectrum
    return jnp.where(zap_mask, jnp.zeros((), dtype=spectrum.dtype), spectrum)


# ----------------------------------------------------------------
# spectral kurtosis (stage 2)
# ----------------------------------------------------------------

def sk_decision_thresholds(m: int, sk_threshold: float):
    """(low, high) acceptance bounds for the SK estimator over M samples:
    the configured threshold symmetrized around 2, rescaled by
    (M-1)/(M+1) (ref: spectrum/rfi_mitigation.hpp:290-341).  Shared by
    the jnp op and the fused Pallas kernel so their zap decisions cannot
    drift apart."""
    thr_high = max(sk_threshold, 2.0 - sk_threshold)
    thr_low = min(sk_threshold, 2.0 - sk_threshold)
    scale = (m - 1.0) / (m + 1.0)
    return (np.float32(thr_low * scale + 1.0),
            np.float32(thr_high * scale + 1.0))


@S.scoped(S.DETECT)
def mitigate_rfi_spectral_kurtosis(waterfall: jnp.ndarray,
                                   sk_threshold: float) -> jnp.ndarray:
    """Zap frequency rows of the dynamic spectrum whose spectral kurtosis
    falls outside [2 - thr, thr] rescaled by (M-1)/(M+1)
    (ref: spectrum/rfi_mitigation.hpp:290-341).

    ``waterfall`` is frequency-major ``[..., freq, time]``; SK is computed
    per frequency row over the M time samples.
    """
    m = waterfall.shape[-1]
    thr_low_, thr_high_ = sk_decision_thresholds(m, sk_threshold)

    x2 = _norm(waterfall)
    s2 = jnp.sum(x2, axis=-1)
    s4 = jnp.sum(x2 * x2, axis=-1)
    sk = m * s4 / (s2 * s2)
    zap = (sk > thr_high_) | (sk < thr_low_)
    return jnp.where(zap[..., None], jnp.zeros((), dtype=waterfall.dtype),
                     waterfall)


@S.scoped(S.RFI_S1)
def mitigate_rfi_manual_bins(spectrum: jnp.ndarray, bins, k0) -> jnp.ndarray:
    """:func:`mitigate_rfi_manual` for a block of the spectrum whose first
    bin is the (traced) global index ``k0``: the zap ranges are compared
    with the bins' own indices, so a loop over blocks holds no mask the
    spectrum's size."""
    if not bins:
        return spectrum
    k = k0 + jnp.arange(spectrum.shape[-1], dtype=jnp.int32)
    zap = jnp.zeros(k.shape, dtype=bool)
    for lo, hi in bins:
        zap = zap | ((k >= lo) & (k <= hi))
    return jnp.where(zap, jnp.zeros((), dtype=spectrum.dtype), spectrum)
