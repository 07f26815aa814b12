"""Multi-chip DM-trial search.

The reference dedisperses at a single configured DM (config.hpp:129-132
"TODO: DM search list for unknown source").  On TPU a DM search is the
natural scale-out axis: every trial applies a different chirp to the *same*
spectrum — pure data parallelism.  The spectrum is broadcast over ICI once
per segment; the chirp bank lives sharded over the ``dm`` mesh axis
(precomputed once, reused for every segment); each chip runs
chirp-multiply -> waterfall FFT -> spectral kurtosis -> detection on its
local trials and only tiny per-trial summaries leave the chips.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from srtb_tpu.ops import dedisperse as dd
from srtb_tpu.ops import detect as det
from srtb_tpu.ops import fft as F
from srtb_tpu.ops import rfi


class DMTrialResult(NamedTuple):
    dm_list: np.ndarray          # [n_dm] host
    zero_count: jnp.ndarray      # [n_dm]
    signal_counts: jnp.ndarray   # [n_dm, n_boxcars]
    snr_peaks: jnp.ndarray       # [n_dm, n_boxcars]
    time_series: jnp.ndarray     # [n_dm, T] mean-subtracted boxcar-1 series


def build_chirp_bank(dm_list, n_spectrum: int, f_min: float, df: float,
                     f_c: float, mesh: Mesh | None = None,
                     on_device: bool = False,
                     exact: bool = False) -> jnp.ndarray:
    """[n_dm, 2, n_spectrum] (re, im) float32 chirp bank, optionally sharded
    over the mesh's ``dm`` axis.  ``on_device=True`` computes each chirp
    with df64 two-float arithmetic directly on the owning chip (no
    host->device transfer of the bank, SURVEY.md §7 step 6).

    The on-device path defaults to the anchored-Taylor evaluation: k is
    linear in dm, so dm-independent anchor coefficients (validated once
    at the grid's max |dm|) are scaled by each trial's dm on device —
    one df64 multiply per anchor instead of ~3 df64 divisions per
    channel per trial.  ``exact=True`` (the Config.chirp_exact escape
    hatch) restores the per-element division chains."""
    dm_list = np.asarray(dm_list, dtype=np.float64)
    if on_device and mesh is not None:
        from srtb_tpu.ops import df64 as ds
        dm_hi, dm_lo = ds.from_float64(dm_list)  # keep full f64 precision
        dm_absmax = float(np.max(np.abs(dm_list))) if dm_list.size else 0.0
        consts = None if exact else dd.anchored_chirp_consts(
            n_spectrum, f_min, df, f_c, dm_absmax or 1.0, unit_dm=True)

        def gen(hi_block, lo_block):
            return jax.vmap(lambda h, l: dd.chirp_factor_df64_ri(
                n_spectrum, f_min, df, f_c, h, dm_lo=l,
                anchor_consts=consts))(hi_block, lo_block)
        fn = jax.jit(shard_map(gen, mesh=mesh, in_specs=(P("dm"), P("dm")),
                               out_specs=P("dm")))
        return fn(jnp.asarray(dm_hi), jnp.asarray(dm_lo))
    bank = np.stack([dd.chirp_factor_host_ri(n_spectrum, f_min, df, f_c, dm)
                     for dm in dm_list])
    if mesh is not None:
        sharding = NamedSharding(mesh, P("dm", None, None))
        return jax.device_put(bank, sharding)
    return jnp.asarray(bank)


def _trial_body(spec_ri, chirp_block, *, channel_count, time_reserved_count,
                snr_threshold, max_boxcar_length, sk_threshold,
                dewindow=None, len_cap=None):
    """Per-device: run all local DM trials on the replicated spectrum."""
    spec = jax.lax.complex(spec_ri[0], spec_ri[1])

    def one(chirp_ri):
        chirp = jax.lax.complex(chirp_ri[0], chirp_ri[1])
        s = dd.dedisperse(spec, chirp)
        wf = F.waterfall_c2c(s, channel_count, dewindow, len_cap=len_cap)
        wf = rfi.mitigate_rfi_spectral_kurtosis(wf, sk_threshold)
        r = det.detect(wf, time_reserved_count, snr_threshold,
                       max_boxcar_length)
        return r.zero_count, r.signal_counts, r.snr_peaks, r.time_series

    return jax.vmap(one)(chirp_block)


def dm_trial_search(spectrum_ri: jnp.ndarray, chirp_bank: jnp.ndarray,
                    dm_list, mesh: Mesh, *, channel_count: int,
                    time_reserved_count: int, snr_threshold: float,
                    max_boxcar_length: int, sk_threshold: float,
                    dewindow=None, len_cap: int | None = None
                    ) -> DMTrialResult:
    """Run the DM grid on one segment's (RFI-cleaned) spectrum.

    ``spectrum_ri`` [2, n_spectrum] (re, im) is replicated (XLA broadcasts
    it over ICI); ``chirp_bank`` [n_dm, 2, n_spectrum] is sharded over the
    ``dm`` axis.  ``dewindow``: pre-sanitized watfft-window divisors
    (window.dewindow_coefficients) when the spectrum was produced with a
    non-rectangle window — keeps this path consistent with the single-chip
    and DistSegmentProcessor paths.
    """
    body = partial(_trial_body, channel_count=channel_count,
                   time_reserved_count=time_reserved_count,
                   snr_threshold=snr_threshold,
                   max_boxcar_length=max_boxcar_length,
                   sk_threshold=sk_threshold,
                   dewindow=None if dewindow is None
                   else jnp.asarray(dewindow),
                   len_cap=len_cap)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(), P("dm", None, None)),
                   out_specs=P("dm"))
    zero_count, counts, peaks, ts = jax.jit(fn)(spectrum_ri, chirp_bank)
    return DMTrialResult(
        dm_list=np.asarray(dm_list),
        zero_count=zero_count,
        signal_counts=counts,
        snr_peaks=peaks,
        time_series=ts,
    )


def best_trial(result: DMTrialResult) -> tuple[int, float]:
    """(index, peak SNR) of the strongest trial across all boxcars."""
    peaks = np.asarray(result.snr_peaks)
    idx = int(np.argmax(peaks.max(axis=-1)))
    return idx, float(peaks[idx].max())
