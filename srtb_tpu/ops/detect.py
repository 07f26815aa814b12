"""Single-pulse signal detection.

Mirrors signal_detect_pipe_2 (ref: pipeline/signal_detect_pipe.hpp:244-443)
and count_signal (ref: signal_detect.hpp:32-72), re-shaped for jit: instead
of data-dependent host branching and dynamic result lists, everything is
computed with static shapes — a ``[n_boxcars]`` vector of detection counts
plus the (fixed-size) candidate time series — and the host decides what to
write out.  This is the "count then conditionally copy" pattern of the
reference made jit-clean (SURVEY.md §7 hard part #5).

Pipeline per segment, waterfall ``[freq, time]``:
1. zapped-channel count: channels whose time-0 sample is exactly zero
   (ref: signal_detect_pipe.hpp:262-284);
2. trim the reserved tail: T = time - nsamps_reserved/freq_bins
   (ref: signal_detect_pipe.hpp:287-299);
3. time series = sum over frequency of |x|^2 (ref: 305-316);
4. subtract mean (ref: 321-334);
5. sigma-threshold count at boxcar length 1 (ref: 347-366);
6. boxcar matched filtering: prefix sum, sliding-window difference for
   lengths 2, 4, ..., max_boxcar_length, re-detect each (ref: 368-424).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from srtb_tpu.ops import scopes as S


def _norm(c):
    return jnp.real(c) ** 2 + jnp.imag(c) ** 2


@S.scoped(S.DETECT)
def tree_sum_freq(power: jnp.ndarray) -> jnp.ndarray:
    """Sum ``power [..., K, T]`` over the frequency axis (-2) with an
    explicit pairwise (binary-tree) reduction: K -> K/2 -> ... -> 1.

    Why not ``jnp.sum``: XLA's reduction order is implementation-defined
    — on XLA:CPU the 2^15-channel production sum accumulates mostly
    sequentially, and the measured time-series error at the flagship
    geometry was 3.1e-4 (artifacts/production_oracle.json, round 4),
    ~600x the waterfall error feeding it.  The reference does the same
    sum naively in f32 (ref: signal_detect_pipe.hpp:305-316) and
    inherits the same growth; this beats it instead of matching it.

    The pairwise tree makes the rounding bound deterministic and
    backend-independent: ceil(log2 K) + 1 levels, each contributing at
    most one ulp of the running partial per element, so for nonnegative
    summands

        |err[t]| <= (ceil(log2 K) + 1) * eps * sum_k power[k, t]

    (eps = 2^-24); at K = 2^15 that is ~1e-6 relative to the raw series
    — vs the O(K * eps) = 2e-3 worst case of a sequential sum.  Cost:
    the level arrays form a geometric series, ~2x the HBM traffic of a
    single fused reduce — noise next to the segment FFTs.  Asserted
    against a float64 oracle in tests/test_reference_crosscheck.py.
    """
    k = power.shape[-2]
    t = power.shape[-1]
    lead = power.shape[:-2]
    carry = None
    while k > 1:
        if k % 2:
            last = power[..., -1:, :]
            carry = last if carry is None else carry + last
            power = power[..., :-1, :]
            k -= 1
        power = power.reshape(*lead, k // 2, 2, t)
        power = power[..., 0, :] + power[..., 1, :]
        k //= 2
    out = power[..., 0, :]
    if carry is not None:
        out = out + carry[..., 0, :]
    return out


class DetectResult(NamedTuple):
    """Static-shape detection result for one segment / one data stream."""
    zero_count: jnp.ndarray          # [] int32: zapped frequency channels
    time_series: jnp.ndarray         # [T] f32, mean-subtracted, boxcar 1
    boxcar_lengths: tuple            # static: (1, 2, 4, ..., max)
    signal_counts: jnp.ndarray       # [n_boxcars] int32: samples over threshold
    boxcar_series: jnp.ndarray       # [n_boxcars, T] f32 (rows zero-padded at tail)
    snr_peaks: jnp.ndarray           # [n_boxcars] f32: max SNR per boxcar
    # data-quality epilogue side-output (srtb_tpu/quality/stats.py
    # packed [S, N_SCALARS + 2*B] vector; None unless
    # Config.quality_stats armed the epilogue — None is an empty
    # pytree subtree, so every existing consumer is unaffected)
    quality: jnp.ndarray | None = None


def time_series_error_gates(k_ch: int, t_len: int, ts_raw_max: float,
                            wf_err_abs: float) -> tuple:
    """Derived absolute error bounds for the detection time series vs a
    float64 oracle, decomposed by cause (single home of the formulas:
    tools/production_oracle.py gates the flagship geometry with these
    and tests/test_reference_crosscheck.py pins them in CI).

    Returns ``(ts_sum_gate, ts_prop_gate)``:

    - ``ts_sum_gate`` bounds the f32 summation error of
      :func:`tree_sum_freq` + the tree mean-subtract vs exact f64 on
      the *same* f32 waterfall: (ceil(lg K) + ceil(lg T) + 5) pairwise
      levels, each <= eps of the running nonnegative partial, times the
      raw (un-mean-subtracted) series max; factor 2 for the mean's few
      extra ulps.  Deterministic and backend-independent — measured
      4.2e-5 relative at K = 2^15 vs 1.8e-3 for a sequential f32 sum
      (round-5 A/B).
    - ``ts_prop_gate`` bounds the waterfall's own f32 error
      ``wf_err_abs`` propagated through |.|^2 and the channel sum:
      per time sample |sum_k(|x+d|^2 - |x|^2)| <= 2*wf_err*sum_k|x| +
      K*wf_err^2 <= 2*wf_err*sqrt(K*ts_raw_max) + K*wf_err^2 —
      worst-case coherent alignment, no statistical assumption.  The
      comparison happens on *mean-subtracted* series, and subtracting
      the (equally perturbed) mean can double the per-sample
      difference, hence the outer factor 2.
    """
    eps = 2.0 ** -24
    levels = (int(np.ceil(np.log2(max(k_ch, 2))))
              + int(np.ceil(np.log2(max(t_len, 2)))) + 5)
    ts_sum_gate = 2.0 * levels * eps * ts_raw_max
    ts_prop_gate = 2.0 * (
        2.0 * wf_err_abs * float(np.sqrt(k_ch * ts_raw_max))
        + k_ch * wf_err_abs ** 2)
    return ts_sum_gate, ts_prop_gate


@S.scoped(S.DETECT)
def tree_mean(ts: jnp.ndarray) -> jnp.ndarray:
    """Mean over the last axis via the pairwise tree (shape [..., 1]) —
    the single home of the mean-subtract spelling whose rounding the
    ``time_series_error_gates`` bound accounts for; used by the
    single-chip detect tail and the distributed step body."""
    return tree_sum_freq(ts[..., :, None]) / ts.shape[-1]


def boxcar_lengths(max_boxcar_length: int, time_series_count: int) -> tuple:
    """Static list of boxcar lengths: 1 then 2,4,... while <= max and < T
    (ref: signal_detect_pipe.hpp:387-389)."""
    lengths = [1]
    b = 2
    while b <= max_boxcar_length and b < time_series_count:
        lengths.append(b)
        b *= 2
    return tuple(lengths)


@S.scoped(S.DETECT)
def count_signal(x: jnp.ndarray, snr_threshold: float):
    """Count samples with x > threshold*sqrt(mean(x^2)), assuming mean(x)=0
    (ref: signal_detect.hpp:32-72).  Returns (count, peak_snr)."""
    sigma = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True))
    thr = snr_threshold * sigma
    count = jnp.sum((x > thr).astype(jnp.int32), axis=-1)
    peak_snr = (jnp.max(x, axis=-1, keepdims=True)
                / jnp.maximum(sigma, jnp.float32(1e-30)))[..., 0]
    return count, peak_snr


def trimmed_length(time_samples: int, time_reserved_count: int) -> int:
    """Usable time samples after dropping the reserved (dedispersion-
    corrupted) tail; keeps everything when the segment is too short
    (ref: signal_detect_pipe.hpp:291-296 warns and keeps all)."""
    if time_samples <= time_reserved_count:
        return time_samples
    return time_samples - time_reserved_count


@S.scoped(S.DETECT)
def detect(waterfall: jnp.ndarray, time_reserved_count: int,
           snr_threshold: float, max_boxcar_length: int) -> DetectResult:
    """Full detection chain on a frequency-major dynamic spectrum."""
    t = trimmed_length(waterfall.shape[-1], time_reserved_count)

    # zapped channels: first time sample exactly zero (ref: 262-284)
    zero_count = jnp.sum(
        (_norm(waterfall[..., 0]) == 0).astype(jnp.int32), axis=-1)

    # time series: sum power over frequency for the first t samples
    # (ref: 305-316) — pairwise tree, not jnp.sum: see tree_sum_freq
    ts = tree_sum_freq(_norm(waterfall[..., :t]))
    return detect_from_time_series(ts, zero_count, snr_threshold,
                                   max_boxcar_length)


@S.scoped(S.DETECT)
def detect_from_time_series(ts: jnp.ndarray, zero_count: jnp.ndarray,
                            snr_threshold: float,
                            max_boxcar_length: int) -> DetectResult:
    """Boxcar detection ladder from a (not yet mean-subtracted) power time
    series ``ts [..., t]`` — the tail of :func:`detect`, split out so fused
    kernels that already produced the time series (Pallas SK+sum pass) can
    reuse it."""
    t = ts.shape[-1]
    # mean subtraction (ref: 321-334) with the same pairwise-tree
    # discipline as the frequency sum: the series sits at K*mean_power
    # scale, so an order-unspecified sum over T = 2^14 samples could
    # contribute more error than the whole frequency reduction
    ts = ts - tree_mean(ts)

    lengths = boxcar_lengths(max_boxcar_length, t)
    n_box = len(lengths)

    # prefix sum once, sliding-window differences per length (ref: 368-399)
    acc = jnp.cumsum(ts, axis=-1)

    counts = []
    peaks = []
    series_rows = []
    for b in lengths:
        if b == 1:
            series = ts
        else:
            # d_accumulated[i + b] - d_accumulated[i] for i in [0, t-b)
            series = acc[..., b:] - acc[..., :-b]
        c, p = count_signal(series, snr_threshold)
        counts.append(c)
        peaks.append(p)
        pad = t - series.shape[-1]
        if pad:
            series = jnp.pad(series,
                             [(0, 0)] * (series.ndim - 1) + [(0, pad)])
        series_rows.append(series)
    del n_box
    return DetectResult(
        zero_count=zero_count,
        time_series=ts,
        boxcar_lengths=lengths,
        signal_counts=jnp.stack(counts, axis=-1),
        boxcar_series=jnp.stack(series_rows, axis=-2),
        snr_peaks=jnp.stack(peaks, axis=-1),
    )


# ----------------------------------------------------------------
# numpy golden model
# ----------------------------------------------------------------

def detect_oracle(waterfall: np.ndarray, time_reserved_count: int,
                  snr_threshold: float, max_boxcar_length: int):
    """Reference-faithful numpy recomputation (for tests)."""
    time_samples = waterfall.shape[-1]
    t = time_samples - time_reserved_count \
        if time_samples > time_reserved_count else time_samples
    power = np.abs(waterfall) ** 2
    zero_count = int(np.sum(power[..., 0] == 0))
    ts = power[:, :t].sum(axis=0)
    ts = ts - ts.mean()
    lengths = boxcar_lengths(max_boxcar_length, t)
    acc = np.cumsum(ts)
    counts = []
    for b in lengths:
        if b == 1:
            series = ts
        else:
            series = acc[b:] - acc[:-b]
            series = series[: t - b]
        thr = snr_threshold * np.sqrt(np.mean(series * series))
        counts.append(int(np.sum(series > thr)))
    return zero_count, ts, lengths, counts
