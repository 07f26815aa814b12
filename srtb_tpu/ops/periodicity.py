"""Periodicity search: harmonic-summed power spectrum + phase folding.

The module set of the FPGA pulsar-search composition paper (PAPERS.md,
*Combining Multiple Optimised FPGA-based Pulsar Search Modules Using
OpenCL*): after dedispersion, a pulsar's pulse train concentrates its
power at the spin frequency and its harmonics of the time-series power
spectrum.  The classic search (also PRESTO's accelsearch shape) is:

1. **power spectrum** of the (mean-subtracted) dedispersed time
   series — one rFFT of ``T`` samples (``T = n_spectrum /
   channel_count``; tiny next to the segment FFTs);
2. **incoherent harmonic summing**: for each fundamental bin ``k``,
   sum the power at ``j*k`` for ``j = 1..h`` over a ladder of harmonic
   counts ``h = 1, 2, 4, ...`` — a narrow pulse spreads power over
   many harmonics, and the matched ``h`` maximizes detection SNR;
3. **candidate selection**: normalize each harmonic level to unit
   variance (sum of ``h`` approximately-exponential powers has mean
   ``h * mean(P)`` and sigma ``sqrt(h) * sigma(P)``), take the best
   level per bin, top-K bins overall;
4. **phase folding** at each candidate's period: average the time
   series into ``n_bins`` phase bins — the folded pulse profile a
   human (or a downstream classifier) vets.

Everything is static-shape and jit-clean (the "count then
conditionally copy" discipline of ops/detect.py): candidates are a
fixed top-K per stream, folding is a scatter-add over a fixed bin
count, and the host decides what to write.  All arrays here are
time-series-sized — ``T`` is ``2^11``-``2^15`` at production shapes —
so the mode's HBM cost is noise next to the segment FFTs (the plan
audit pins the spectrum-sized pass count).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np


class PeriodicityCandidates(NamedTuple):
    """Static-shape periodicity result for one data stream."""
    bins: jnp.ndarray        # [K] int32: fundamental bin per candidate
    snr: jnp.ndarray         # [K] f32: harmonic-summed, normalized SNR
    harmonics: jnp.ndarray   # [K] int32: harmonic count that maximized
    profiles: jnp.ndarray    # [K, n_bins] f32: folded pulse profiles


def harmonic_levels(max_harmonics: int) -> tuple:
    """Static harmonic-count ladder 1, 2, 4, ... <= max (>= (1,))."""
    levels = [1]
    h = 2
    while h <= int(max_harmonics):
        levels.append(h)
        h *= 2
    return tuple(levels)


def power_spectrum(ts: jnp.ndarray) -> jnp.ndarray:
    """Time series [T] (already mean-subtracted) -> power [M], the
    one-sided rFFT power with the DC bin zeroed (mean subtraction
    leaves it ~0 anyway; zeroing makes the exclusion exact)."""
    spec = jnp.fft.rfft(ts.astype(jnp.float32))
    power = (jnp.real(spec) ** 2 + jnp.imag(spec) ** 2) \
        .astype(jnp.float32)
    return power.at[..., 0].set(0.0)


def harmonic_sum(power: jnp.ndarray, levels: tuple) -> jnp.ndarray:
    """Incoherent harmonic sums ``[n_levels, M]``: row ``i`` holds
    ``sum_{j=1..levels[i]} power[min(j*k, M-1)]`` per fundamental bin
    ``k``.  Gathers only — static shapes, no host sync.  Clamping to
    the last bin slightly over-counts fundamentals whose harmonics
    fall off the spectrum; those bins are the top fraction ``1/h`` of
    the band, where a real detection would have been found at a lower
    level anyway."""
    m = power.shape[-1]
    k = jnp.arange(m)
    rows = []
    acc = power
    j = 1
    for h in levels:
        while j < h:
            j += 1
            idx = jnp.minimum(k * j, m - 1)
            acc = acc + power[..., idx]
        rows.append(acc)
    return jnp.stack(rows)


def candidate_search(ts: jnp.ndarray, levels: tuple, top_k: int,
                     min_bin: int = 2):
    """Harmonic-summed candidate selection over one stream's time
    series.  Returns ``(bins [K] i32, snr [K] f32, harm [K] i32)``
    ranked by normalized SNR; bins below ``min_bin`` (DC + red-noise
    leakage) are excluded."""
    power = power_spectrum(ts)
    m = power.shape[-1]
    sums = harmonic_sum(power, levels)                 # [L, M]
    # normalization per level: the valid-bin population's mean/sigma
    # (exclude the masked low bins so a strong red-noise ramp cannot
    # deflate every real candidate's SNR)
    valid = (jnp.arange(m) >= min_bin).astype(jnp.float32)
    n_valid = jnp.maximum(valid.sum(), 1.0)
    mean = (sums * valid).sum(axis=-1, keepdims=True) / n_valid
    var = (((sums - mean) * valid) ** 2).sum(axis=-1,
                                             keepdims=True) / n_valid
    snr_l = (sums - mean) / jnp.sqrt(jnp.maximum(var, 1e-30))
    snr_l = jnp.where(valid > 0, snr_l, -jnp.inf)
    best = jnp.max(snr_l, axis=0)                      # [M]
    best_level = jnp.argmax(snr_l, axis=0)             # [M]
    k = min(int(top_k), m)
    import jax
    snr, bins = jax.lax.top_k(best, k)
    harm = jnp.asarray(levels, dtype=jnp.int32)[best_level[bins]]
    return bins.astype(jnp.int32), snr.astype(jnp.float32), harm


def fold(ts: jnp.ndarray, bin_k: jnp.ndarray, n_bins: int) -> jnp.ndarray:
    """Phase-fold one stream's time series at the period of power-
    spectrum bin ``bin_k`` (``bin_k`` cycles per ``T`` samples):
    phase_i = (i * k mod T) / T, averaged into ``n_bins`` phase bins.
    Returns the folded profile ``[n_bins] f32`` (bins no sample lands
    in read 0)."""
    t = ts.shape[-1]
    # uint32 phase product: i * k <= T * M ~ T^2 / 2.  A power-of-two
    # T is ALWAYS exact (t divides 2^32, so mod-2^32 wraparound
    # commutes with % t); a non-power-of-two T is exact only while the
    # product stays under 2^32 — beyond that the wrapped phases would
    # silently corrupt the folded profiles, so refuse loudly at trace
    # time (x64 is globally disabled, so int64 is not an option).
    # Production T = n_spectrum / channel_count is 2^11-2^15.
    if (t & (t - 1)) and t * (t // 2) >= (1 << 32):
        raise ValueError(
            f"fold: time series length {t} is non-power-of-two and "
            "long enough that uint32 phase products wrap — reduce "
            "the series (spectrum_channel_count) below 2^16 samples "
            "or make it a power of two")
    i = jnp.arange(t, dtype=jnp.uint32)
    phase_idx = (((i * bin_k.astype(jnp.uint32)) % t)
                 * n_bins) // t                         # [T] in [0, nb)
    sums = jnp.zeros((n_bins,), jnp.float32).at[phase_idx].add(ts)
    counts = jnp.zeros((n_bins,), jnp.float32).at[phase_idx].add(1.0)
    return sums / jnp.maximum(counts, 1.0)


def periodicity_search(ts: jnp.ndarray, max_harmonics: int, top_k: int,
                       n_bins: int,
                       min_bin: int = 2) -> PeriodicityCandidates:
    """Full periodicity module for one stream: harmonic-summed
    candidate selection + a folded profile per candidate."""
    import jax
    levels = harmonic_levels(max_harmonics)
    bins, snr, harm = candidate_search(ts, levels, top_k,
                                       min_bin=min_bin)
    profiles = jax.vmap(lambda b: fold(ts, b, n_bins))(bins)
    return PeriodicityCandidates(bins=bins, snr=snr, harmonics=harm,
                                 profiles=profiles)


# ----------------------------------------------------------------
# numpy golden model (for tests)
# ----------------------------------------------------------------

def periodicity_oracle(ts: np.ndarray, max_harmonics: int, top_k: int,
                       n_bins: int, min_bin: int = 2):
    """Reference-faithful numpy recomputation of the search above."""
    spec = np.fft.rfft(ts.astype(np.float32))
    power = (spec.real ** 2 + spec.imag ** 2).astype(np.float32)
    power[0] = 0.0
    m = power.shape[-1]
    levels = harmonic_levels(max_harmonics)
    k = np.arange(m)
    rows, acc, j = [], power.copy(), 1
    for h in levels:
        while j < h:
            j += 1
            acc = acc + power[np.minimum(k * j, m - 1)]
        rows.append(acc.copy())
    sums = np.stack(rows)
    valid = k >= min_bin
    mean = sums[:, valid].mean(axis=-1, keepdims=True)
    sig = np.maximum(sums[:, valid].std(axis=-1, keepdims=True), 1e-15)
    snr_l = (sums - mean) / sig
    snr_l[:, ~valid] = -np.inf
    best = snr_l.max(axis=0)
    order = np.argsort(-best, kind="stable")[:top_k]
    t = ts.shape[-1]
    profiles = []
    for b in order:
        idx = (((np.arange(t) * int(b)) % t) * n_bins) // t
        sums_b = np.zeros(n_bins, np.float32)
        counts = np.zeros(n_bins, np.float32)
        np.add.at(sums_b, idx, ts)
        np.add.at(counts, idx, 1.0)
        profiles.append(sums_b / np.maximum(counts, 1.0))
    harm = np.asarray(levels)[snr_l.argmax(axis=0)[order]]
    return (order.astype(np.int32), best[order].astype(np.float32),
            harm.astype(np.int32), np.stack(profiles))
