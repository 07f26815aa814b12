"""Coherent dedispersion: frequency-domain chirp multiply.

Physics follows the reference exactly (ref: coherent_dedispersion.hpp):
``D = 4.148808e3`` MHz^2 pc^-1 cm^3 s (line 67), per-channel phase turns

    k = D * 1e6 * dm / f * ((f - f_c) / f_c)^2        (phase_factor_v3, line 141)
    factor = exp(-2*pi*i * frac(k))                   (lines 142-148)

with ``frac`` extracted before the trig because k reaches ~1e9 at high DM
(line 49), far beyond f32 mantissa range.

TPU-native design: the chirp depends only on (n, f_min, df, f_c, dm) — it is
**constant across segments** — so the primary path precomputes it once on
host in f64 and keeps it resident in HBM (one complex64 array the size of
the spectrum).  For DM-search grids where a per-trial host precompute would
bottleneck, ``chirp_factor_df64`` computes the same thing on device with
two-float arithmetic (the reference's dsmath df64 trick, proven there on
fp64-less GPUs); it is pure elementwise VPU work that XLA fuses.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from srtb_tpu.ops import df64 as ds
from srtb_tpu.ops import scopes as S

# dispersion constant, MHz^2 pc^-1 cm^3 s (ref: coherent_dedispersion.hpp:67)
D = 4.148808e3


def dispersion_delay_time(f, f_c, dm):
    """Delay relative to f_c, seconds; positive for f > f_c
    (ref: coherent_dedispersion.hpp:75-78)."""
    return -D * dm * (1.0 / (f * f) - 1.0 / (f_c * f_c))


def max_delay_time(freq_low: float, bandwidth: float, dm: float) -> float:
    """Max dispersion delay across the band
    (ref: coherent_dedispersion.hpp:81-85)."""
    return dispersion_delay_time(freq_low + bandwidth, freq_low, dm)


def nsamps_reserved(cfg) -> int:
    """Real samples reserved (overlapped) between consecutive segments to
    mask dedispersion edge corruption (ref: coherent_dedispersion.hpp:103-128).

    The non-reserved portion is rounded down to a multiple of
    2 * spectrum_channel_count so the waterfall FFT tiles exactly.
    """
    if not cfg.baseband_reserve_sample:
        return 0
    minimal = 2 * round(
        max_delay_time(cfg.baseband_freq_low, cfg.baseband_bandwidth, cfg.dm)
        * cfg.baseband_sample_rate)
    per_bin = cfg.spectrum_channel_count * 2
    n = cfg.baseband_input_count
    refft_total = (n - minimal) // per_bin * per_bin
    if refft_total > 0:
        return n - refft_total
    return 0


# ----------------------------------------------------------------
# chirp generation
# ----------------------------------------------------------------

def chirp_factor_host(n: int, f_min: float, df: float, f_c: float,
                      dm: float) -> np.ndarray:
    """Chirp factors for n channels at f = f_min + df*i, computed on host in
    float64 (numpy), returned as complex64.

    Bit-comparable to phase_factor_v3 with phase_real = double
    (ref: coherent_dedispersion.hpp:134-150).
    """
    i = np.arange(n, dtype=np.float64)
    f = f_min + df * i
    delta_f = f - f_c
    k = (D * 1e6) * dm / f * ((delta_f / f_c) * (delta_f / f_c))
    k_frac = np.modf(k)[0]
    delta_phi = -2.0 * np.pi * k_frac
    return (np.cos(delta_phi) + 1j * np.sin(delta_phi)).astype(np.complex64)


@S.scoped(S.CHIRP)
def chirp_factor_df64(n: int, f_min: float, df: float, f_c: float, dm,
                      dtype=jnp.complex64, i0: int = 0,
                      dm_lo=None, exact: bool = False) -> jnp.ndarray:
    """Same chirp computed on device with two-float (df64) arithmetic —
    jittable, dm may be a traced scalar (DM-search grids).  ``i0``
    generates the block of channels starting at that global index.
    ``exact=True`` forces the per-element df64 division chains instead
    of the anchored-Taylor fast path (Config.chirp_exact escape hatch).

    Mirrors phase_factor_v3 with phase_real = dsmath::df64
    (ref: coherent_dedispersion.hpp:31-53,134-150).
    """
    delta_phi = _chirp_phase_df64(n, f_min, df, f_c, dm, i0=i0,
                                  dm_lo=dm_lo, exact=exact)
    return (jnp.cos(delta_phi) + 1j * jnp.sin(delta_phi)).astype(dtype)


def chirp_factor_host_ri(n: int, f_min: float, df: float, f_c: float,
                         dm: float) -> np.ndarray:
    """Chirp as stacked (real, imag) float32 [2, n].

    TPU-native boundary representation: some TPU runtimes don't transfer
    complex buffers across the host<->device boundary, and splitting
    re/im is the natural layout for the VPU anyway; complex exists only
    inside jit.
    """
    c = chirp_factor_host(n, f_min, df, f_c, dm)
    return np.stack([c.real, c.imag]).astype(np.float32)


@S.scoped(S.CHIRP)
def chirp_factor_df64_ri(n: int, f_min: float, df: float, f_c: float,
                         dm, i0: int = 0, dm_lo=None,
                         anchor_consts=None,
                         exact: bool = False) -> jnp.ndarray:
    """df64 on-device chirp as stacked (cos, sin) float32 [2, n] — jit-safe
    output dtype on complex-less runtimes.  ``exact=True`` forces the
    per-element division chains (Config.chirp_exact escape hatch)."""
    phase = _chirp_phase_df64(n, f_min, df, f_c, dm, i0=i0, dm_lo=dm_lo,
                              anchor_consts=anchor_consts, exact=exact)
    return jnp.stack([jnp.cos(phase), jnp.sin(phase)])


# ---- anchored-Taylor fast path for the on-device df64 phase ----
#
# The exact per-element df64 evaluation of k spends ~3 df64 divisions per
# channel (measured 6.6x the precomputed-bank multiply at 2^27 on a v5e;
# paid per segment only in-step: staged, Pallas, a DM grid past its bank
# rule).  But k is an extremely smooth function of the channel index:
#
#     k(f) = A (f - f_c)^2 / (f_c^2 f) = C1*f - C2 + A/f,
#     A = D*1e6*dm,  C1 = A/f_c^2,  C2 = 2A/f_c
#
# and Taylor-expanding in the channel offset d around an anchor channel,
# the cubic remainder over a block of B channels is bounded by
# |A| (|df| B)^4 / min|f|^5 turns — ~1e-10 for the flagship config at
# B = 32768.  So one df64 anchor evaluation per block (amortized to
# nothing) plus a cheap per-element update replaces the division chains:
#
#     k(i_a + d) ~ k0 + k1*d + k2*d^2 + k3*d^3
#     k1 = df*(C1 - A/f_a^2)   [df64, reduced mod 1 — d is an integer,
#                               so frac(k1*d) == frac(frac(k1)*d)]
#     k2 = df^2 A/f_a^3, k3 = -df^3 A/f_a^4   [f32: the terms are < ~0.1
#                               turns, so f32's 1e-7 relative is plenty]
#
# d <= B stays exact in f32, and the df64 k1f*d product keeps absolute
# error ~B*2^-48.  The mod-1 value matches the exact path to ~1e-9
# turns — far inside the ~k*2^-48 ~ 5e-6-turn precision both paths
# inherit from df64 itself.  Precision validated against the f64 host
# chirp in tests/test_dedisperse.py and tests/test_df64.py.

_ANCHOR_BLOCK = 4096
_ANCHOR_REMAINDER_TOL = 1e-6


def anchored_chirp_consts(n: int, f_min, df, f_c, dm, i0: int = 0,
                          block: int = _ANCHOR_BLOCK,
                          allow_shrink: bool = True,
                          unit_dm: bool = False):
    """Host-side f64 constants for the anchored-Taylor chirp phase, or
    None when the expansion isn't applicable: traced dm/i0 (DM-search
    trials), a band touching f = 0, or a cubic-Taylor remainder over
    ``block`` channels above tolerance.

    ``unit_dm=True``: validate the bound at the given |dm| (the max of a
    DM-search grid) but store dm-independent coefficients (dm = 1) — k
    is linear in dm, so per-trial traced dm values scale the anchor
    coefficients on device (_chirp_phase_df64_anchored_dm)."""
    try:
        f_min64 = float(f_min)
        df64_ = float(df)
        f_c64 = float(f_c)
        dm64 = float(dm)
        i0 = int(i0)
    except (TypeError, ValueError):
        return None  # traced scalar: caller keeps the exact path
    # the last block's Taylor extension may be evaluated (then sliced
    # off) up to the padded end, so bound over the padded range
    n_pad = -(-n // block) * block
    f_at_start = f_min64 + df64_ * i0
    f_at_end = f_min64 + df64_ * (i0 + n_pad)
    if not (np.isfinite(f_at_start) and np.isfinite(f_at_end)) \
            or f_at_start * f_at_end <= 0 or f_c64 == 0:
        return None
    min_f = min(abs(f_at_start), abs(f_at_end))
    A = np.float64(D) * 1e6 * dm64
    # shrink the block until the cubic-Taylor remainder fits tolerance
    # (smaller blocks = more anchors, still amortized); below 32
    # channels per anchor the scheme stops paying for itself.  Callers
    # whose anchor span is fixed by kernel geometry (the Pallas per-row
    # anchors) pass allow_shrink=False: valid at `block` or not at all.
    denom = abs(A) * abs(df64_) ** 4
    if denom > 0:
        block_max = (_ANCHOR_REMAINDER_TOL * min_f ** 5 / denom) ** 0.25
        while allow_shrink and block > 32 and block > block_max:
            block //= 2
        if block > block_max:
            return None
    if unit_dm:
        A = np.float64(D) * 1e6
    return {
        "A": ds.from_float64(A),
        "C1": ds.from_float64(A / (f_c64 * f_c64)),
        "f_c": ds.from_float64(f_c64),
        "f_min": ds.from_float64(f_min64),
        "df": ds.from_float64(df64_),
        "df2A": np.float32(df64_ * df64_ * A),
        "df3A": np.float32(df64_ ** 3 * A),
        "block": block,
    }


def _anchor_values_raw(consts, ia_hi, ia_lo):
    """Unreduced per-anchor Taylor coefficients from exact hi/lo-split
    anchor channel indices: (k0 [df64], k1 [df64], k2 [f32], k3 [f32]).
    With unit_dm consts these are the per-unit-dm coefficients g0..g3."""
    df_d = ds.df64(*consts["df"])
    f_a = ds.add(ds.df64(*consts["f_min"]),
                 ds.add(ds.mul(df_d, ds.df64(ia_hi)),
                        ds.mul(df_d, ds.df64(ia_lo))))
    u = ds.div(ds.df64(*consts["A"]), f_a)            # A / f_a
    # anchor value via the original product form u * r^2: the expanded
    # form C1*f - C2 + A/f cancels ~1e9-turn terms down to ~1e6 and
    # loses 3 digits of the fraction (measured 1.4e-5 turns); u*r^2
    # keeps every factor's error *relative*, ~k * 2^-48
    f_c_d = ds.df64(*consts["f_c"])
    r = ds.div(ds.sub(f_a, f_c_d), f_c_d)
    k = ds.mul(u, ds.mul(r, r))
    w = ds.div(u, f_a)                                # A / f_a^2
    k1 = ds.mul(df_d, ds.sub(ds.df64(*consts["C1"]), w))
    fa32 = f_a[0]
    fa2 = fa32 * fa32
    k2 = consts["df2A"] / (fa2 * fa32)
    k3 = -consts["df3A"] / (fa2 * fa2)
    return k, k1, k2, k3


def _reduce_mod1(k):
    """Reduce a df64 value mod 1 keeping the pair's precision:
    hi - trunc(hi) is exact, then renormalize (two_sum — hi may be
    integral, leaving the whole fraction in lo, so quick_two_sum's
    |a| >= |b| precondition doesn't hold)."""
    return ds.two_sum(k[0] - jnp.trunc(k[0]), k[1])


def _anchor_values(consts, ia_hi, ia_lo):
    """Mod-1-reduced anchor coefficients:
    (k0f [f32], k1f [df64 pair], k2 [f32], k3 [f32])."""
    k, k1, k2, k3 = _anchor_values_raw(consts, ia_hi, ia_lo)
    return ds.frac(k), _reduce_mod1(k1), k2, k3


def _taylor_phase(k0f, k1f, k2, k3, delta):
    """-2*pi*frac(k0f + k1f*delta + k2*delta^2 + k3*delta^3), the
    anchored per-element update (all inputs broadcast against delta,
    which must be exact in f32)."""
    p = ds.mul(k1f, ds.df64(delta))
    v_hi, v_lo = ds.add((k0f, jnp.zeros_like(k0f)), p)
    poly = (delta * delta) * (k2 + k3 * delta)
    r = (v_hi - jnp.trunc(v_hi)) + v_lo + poly
    r = r - jnp.trunc(r)
    return jnp.float32(-2.0 * np.pi) * r


def _chirp_phase_df64_anchored(n: int, consts, i0=0, dm_d=None):
    """Anchored-Taylor delta_phi [n]: one df64 anchor per `block` channels
    (vectorized over anchors), cheap Taylor update within blocks.  i0 may
    be traced (shard-local offsets) — validity was bounded for the global
    range by anchored_chirp_consts.

    ``dm_d`` (a df64 hi/lo pair, may be traced — DM-search trials): k is
    linear in dm, so the dm-independent per-anchor coefficients g0..g3
    (consts built with unit_dm=True; validity bounded at the grid's max
    |dm|) are scaled by this trial's dm on device, then reduced mod 1
    exactly as the concrete path — ~3 df64 divisions per channel *per
    trial* become one df64 multiply per anchor."""
    block = min(consts["block"], n)
    nb = -(-n // block)
    ia = jnp.arange(nb, dtype=jnp.int32) * block + jnp.int32(i0)
    ia_hi = (ia & ~0xFFF).astype(jnp.float32)
    ia_lo = (ia & 0xFFF).astype(jnp.float32)
    if dm_d is None:
        k0f, k1f, k2, k3 = _anchor_values(consts, ia_hi, ia_lo)
    else:
        g0, g1, g2, g3 = _anchor_values_raw(consts, ia_hi, ia_lo)
        k0f = ds.frac(ds.mul(dm_d, g0))
        k1f = _reduce_mod1(ds.mul(dm_d, g1))
        k2 = dm_d[0] * g2
        k3 = dm_d[0] * g3
    delta = jnp.arange(block, dtype=jnp.float32)[None, :]
    phase = _taylor_phase(k0f[:, None], (k1f[0][:, None], k1f[1][:, None]),
                          k2[:, None], k3[:, None], delta)
    return phase.reshape(-1)[:n]


def _chirp_phase_df64(n: int, f_min: float, df: float, f_c: float, dm,
                      i0: int = 0, dm_lo=None, anchor_consts=None,
                      exact: bool = False):
    """delta_phi [n] in f32 via df64 arithmetic (shared by the complex and
    split-ri chirp generators).

    ``i0`` offsets the channel index (shard-local generation on a
    sequence-sharded spectrum).  Indices are split hi/lo from *integers*:
    a float32 arange is exact only below 2^24, and a channel-index error
    of even a few samples at 2^27 channels shifts the phase by whole
    turns (k ~ 1e9 turns scales as ~k/f per MHz).

    Concrete (non-traced) dm takes the anchored-Taylor fast path (see
    above).  Traced dm — DM-search trials — takes it too when the caller
    passes ``anchor_consts`` (built once with unit_dm=True at the grid's
    max |dm|); otherwise the exact per-element evaluation runs.
    ``exact=True`` skips the anchored path entirely — the
    Config.chirp_exact escape hatch and the hardware A/B knob.
    """
    if exact:
        anchor_consts = None
    if anchor_consts is not None:
        if dm_lo is None and isinstance(dm, (int, float, np.floating)):
            # same guard as the exact path below: a concrete dm must be
            # split hi/lo — one f32's 3e-8 relative error shifts
            # k ~ 1e9 turns by ~25 turns
            dm_arr = jnp.float32(np.float32(dm))
            dm_lo_arr = jnp.float32(np.float64(dm) - np.float32(dm))
        else:
            dm_arr = jnp.asarray(dm, dtype=jnp.float32)
            dm_lo_arr = jnp.zeros_like(dm_arr) if dm_lo is None \
                else jnp.asarray(dm_lo, dtype=jnp.float32)
        return _chirp_phase_df64_anchored(
            n, anchor_consts, i0=i0, dm_d=(dm_arr, dm_lo_arr))
    if dm_lo is None and not exact:
        consts = anchored_chirp_consts(n, f_min, df, f_c, dm, i0=i0)
        if consts is not None:
            return _chirp_phase_df64_anchored(n, consts, i0=i0)
    # int32 channel indices: silently wrong at/beyond 2^31 channels.
    # i0 may be traced (shard-local offset); guard what is static here.
    if isinstance(i0, (int, np.integer)):
        if i0 + n > 2**31 - 1:
            raise ValueError(
                f"channel index i0+n = {i0 + n} overflows int32")
    elif n > 2**31 - 1:
        raise ValueError(f"n = {n} overflows int32 channel indices")
    i_int = jnp.arange(n, dtype=jnp.int32) + jnp.int32(i0)
    # hi is a multiple of 2^12 (exact in f32 up to 2^36), lo < 2^12
    i_hi = (i_int & ~0xFFF).astype(jnp.float32)
    i_lo = (i_int & 0xFFF).astype(jnp.float32)
    f_min_d = ds.df64(jnp.float32(np.float32(f_min)),
                      jnp.float32(np.float64(f_min) - np.float32(f_min)))
    df_d = ds.df64(jnp.float32(np.float32(df)),
                   jnp.float32(np.float64(df) - np.float32(df)))
    f_c_d = ds.df64(jnp.float32(np.float32(f_c)),
                    jnp.float32(np.float64(f_c) - np.float32(f_c)))
    df_i = ds.add(ds.mul(df_d, ds.df64(i_hi)), ds.mul(df_d, ds.df64(i_lo)))
    f = ds.add(f_min_d, df_i)

    # dm must be split hi/lo too: truncating e.g. -478.80 to one f32
    # (2.5e-8 relative) shifts k ~ 1e9 turns by ~25 turns
    if isinstance(dm, (int, float, np.floating)):
        dm_d = ds.df64(jnp.float32(np.float32(dm)),
                       jnp.float32(np.float64(dm) - np.float32(dm)))
    else:
        dm_arr = jnp.asarray(dm, dtype=jnp.float32)
        dm_lo_arr = jnp.zeros_like(dm_arr) if dm_lo is None \
            else jnp.asarray(dm_lo, dtype=jnp.float32)
        dm_d = ds.df64(dm_arr, dm_lo_arr)
    D_ = np.float64(D * 1e6)
    D_d = ds.df64(jnp.float32(np.float32(D_)),
                  jnp.float32(D_ - np.float32(D_)))

    delta_f = ds.sub(f, f_c_d)
    ratio = ds.div(delta_f, f_c_d)
    k = ds.mul(ds.div(ds.mul(D_d, dm_d), f), ds.mul(ratio, ratio))
    k_frac = ds.frac(k)
    return jnp.float32(-2.0 * np.pi) * k_frac


def spectrum_frequencies(cfg, n: int):
    """(f_min, f_c, df) for the n-channel spectrum of one segment, matching
    dedisperse_pipe (ref: pipeline/dedisperse_pipe.hpp:31-47)."""
    f_min = cfg.baseband_freq_low
    f_c = f_min + cfg.baseband_bandwidth
    df = cfg.baseband_bandwidth / n
    return f_min, f_c, df


@S.scoped(S.CHIRP)
def dedisperse(spectrum: jnp.ndarray, chirp: jnp.ndarray) -> jnp.ndarray:
    """Apply the chirp: one complex multiply per channel
    (ref: coherent_dedispersion.hpp:223-248)."""
    return spectrum * chirp


@S.scoped(S.CHIRP)
def chirp_block_df64_ri(n_block: int, n_total: int, f_min: float,
                        df: float, f_c: float, dm, i0,
                        exact: bool = False) -> jnp.ndarray:
    """:func:`chirp_factor_df64_ri` for the ``n_block`` channels from
    global index ``i0`` of an ``n_total``-channel spectrum, ``i0`` a
    traced scalar: the body of a loop over blocks of channels (the staged
    plan's stage (c)), whose df64 hi/lo planes are then a block's size
    and not the spectrum's.  The anchored-Taylor constants are validated
    over the whole spectrum, once, on the host; a block that starts on an
    anchor (``i0`` a multiple of the anchor spacing, which ``n_block``
    being one guarantees) evaluates exactly what the whole-spectrum call
    evaluates there.  Float64-accurate phase either way."""
    consts = None if exact else anchored_chirp_consts(
        n_total, f_min, df, f_c, dm)
    if consts is not None and n_block % min(consts["block"], n_block) == 0:
        phase = _chirp_phase_df64_anchored(n_block, consts, i0=i0)
    else:
        phase = _chirp_phase_df64(n_block, f_min, df, f_c, dm, i0=i0,
                                  exact=True)
    return jnp.stack([jnp.cos(phase), jnp.sin(phase)])
