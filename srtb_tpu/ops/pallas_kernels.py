"""Pallas TPU kernels for the hot elementwise ops.

Kernels where explicit VMEM control beats relying on XLA fusion:

1. ``dedisperse_df64`` (and ``rfi_s1_dedisperse_df64``, the same pass
   with RFI s1's zap in it): chirp multiply with the phase computed **on
   the fly** inside the kernel using df64 two-float arithmetic.  The
   baseline path streams a precomputed chirp bank from HBM (8
   bytes/channel/trial); computing the phase in-register turns the op
   from memory-bound (3 arrays in, 2 out) into 2-in/2-out — and for DM
   search it removes the [n_dm, 2, n] chirp bank from HBM entirely.
   (Same math as ops.dedisperse.chirp_factor_df64 / ref:
   coherent_dedispersion.hpp phase_factor_v3 with dsmath df64.)

2. ``sk_zap_timeseries``: the spectral-kurtosis statistics, the zap and
   the time series over the waterfall in two kernel passes.

They are validated against the jnp implementations in tests via
``interpret=True``.  (The sub-byte unpack is XLA's: a kernel for it
needs a lane interleave Mosaic does not lower, and XLA fuses the
shift/mask chain into the FFT's input anyway, ops/unpack.py.)
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
import numpy as np

from srtb_tpu.ops import dedisperse as dd
from srtb_tpu.ops import scopes as S

# lane-friendly tile: rows x 128 lanes; f32 min tile is (8, 128)
_LANES = 128
_ROWS = 256  # 256*128 = 32768 elements per grid step, 128 KiB f32 in VMEM


def _pallas_available() -> bool:
    try:
        from jax.experimental import pallas as pl  # noqa: F401
        return True
    except ImportError:  # pragma: no cover
        return False


# ----------------------------------------------------------------
# df64 helpers usable inside kernels (f32-only, no tuples of refs).
#
# The error-free transforms only survive a compiler that won't rewrite
# (a + b) - a to b.  Which guard that takes depends on who compiles the
# kernel body:
#   * interpret=True runs the kernel as ordinary XLA ops, and XLA's
#     algebraic simplifier DOES that rewrite — optimization_barrier is
#     required (same as ops/df64.py; dropping it measurably zeroes every
#     lo component, test_dedisperse_df64_kernel_high_channel_offset).
#   * interpret=False lowers via Mosaic, which does not implement
#     optimization_barrier (NotImplementedError on a real chip) and does
#     not need it: its MLIR arith lowering keeps IEEE semantics.
#     Verified empirically on a v5e — the non-interpret kernel matches
#     the float64 chirp oracle at |k| ~ 1e9 turns, which would be off by
#     whole turns if any lo component were simplified away
#     (tests/test_pallas_kernels.py "mosaic" cases).
# The switch is a kernel-build argument: each pallas_call wrapper scopes
# it with ``_ob_mode(interpret)`` around kernel tracing (tracing happens
# inside pl.pallas_call, so the scope is exact).  It is a ContextVar,
# not a module global, so two threads building kernels concurrently
# (e.g. two SegmentProcessors) cannot see each other's setting.
# ----------------------------------------------------------------

_USE_OB: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "srtb_pallas_use_ob", default=True)


@contextlib.contextmanager
def _ob_mode(interpret: bool):
    """Scope the EFT-barrier decision for one kernel build: barriers on
    under interpret (XLA simplifier would rewrite the EFTs away), off
    under Mosaic (unimplemented there, and unneeded — see block comment
    above)."""
    token = _USE_OB.set(bool(interpret))
    try:
        yield
    finally:
        _USE_OB.reset(token)


def _ob(x):
    return jax.lax.optimization_barrier(x) if _USE_OB.get() else x


def _two_sum(a, b):
    s = _ob(a + b)
    v = _ob(s - a)
    return s, (a - (s - v)) + (b - v)


def _split(a):
    t = _ob(jnp.float32(4097.0) * a)
    hi = _ob(t - (t - a))
    return hi, a - hi


def _two_prod(a, b):
    p = _ob(a * b)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    return p, ((a_hi * b_hi - p) + a_hi * b_lo + a_lo * b_hi) + a_lo * b_lo


def _df_add(x_hi, x_lo, y_hi, y_lo):
    s, e = _two_sum(x_hi, y_hi)
    e = e + x_lo + y_lo
    s2 = _ob(s + e)
    return s2, e - (s2 - s)


def _df_mul(x_hi, x_lo, y_hi, y_lo):
    p, e = _two_prod(x_hi, y_hi)
    e = e + x_hi * y_lo + x_lo * y_hi
    s = _ob(p + e)
    return s, e - (s - p)


def _df_div(x_hi, x_lo, y_hi, y_lo):
    q1 = x_hi / y_hi
    p_hi, p_lo = _df_mul(q1, jnp.zeros_like(q1), y_hi, y_lo)
    r_hi, r_lo = _df_add(x_hi, x_lo, -p_hi, -p_lo)
    q2 = r_hi / y_hi
    s = _ob(q1 + q2)
    return s, q2 - (s - q1)


def _chirp_phase_block(i_hi, i_lo, f_min, df, f_c, dm):
    """delta_phi for channel indices i = i_hi + i_lo (both exact f32;
    split from integers by the caller — a float32 index is exact only
    below 2^24 and phase errors scale by whole turns beyond it) — df64
    arithmetic on split constants, mirroring
    ops.dedisperse._chirp_phase_df64."""
    def c(v):
        hi = np.float32(v)
        return jnp.float32(hi), jnp.float32(np.float64(v) - np.float64(hi))

    f_min_hi, f_min_lo = c(f_min)
    df_hi, df_lo = c(df)
    f_c_hi, f_c_lo = c(f_c)
    d_hi, d_lo = c(dd.D * 1e6)
    dm_hi, dm_lo = c(dm)

    i = i_hi + i_lo  # only used for shape/fill helpers below
    a_hi, a_lo = _df_mul(df_hi, df_lo, i_hi, jnp.zeros_like(i_hi))
    b_hi, b_lo = _df_mul(df_hi, df_lo, i_lo, jnp.zeros_like(i_lo))
    fi_hi, fi_lo = _df_add(a_hi, a_lo, b_hi, b_lo)
    f_hi, f_lo = _df_add(f_min_hi, jnp.full_like(i, f_min_lo), fi_hi, fi_lo)

    ddm_hi, ddm_lo = _df_mul(d_hi, d_lo, dm_hi, dm_lo)
    q_hi, q_lo = _df_div(jnp.full_like(i, ddm_hi), jnp.full_like(i, ddm_lo),
                         f_hi, f_lo)
    delf_hi, delf_lo = _df_add(f_hi, f_lo, -f_c_hi,
                               jnp.full_like(i, -f_c_lo))
    r_hi, r_lo = _df_div(delf_hi, delf_lo, jnp.full_like(i, f_c_hi),
                         jnp.full_like(i, f_c_lo))
    r2_hi, r2_lo = _df_mul(r_hi, r_lo, r_hi, r_lo)
    k_hi, k_lo = _df_mul(q_hi, q_lo, r2_hi, r2_lo)

    # frac with modf semantics (sign of the value)
    int_hi = jnp.trunc(k_hi)
    frac = (k_hi - int_hi) + k_lo
    frac = frac - jnp.trunc(frac)
    positive = k_hi >= 0
    frac = jnp.where(positive & (frac < 0), frac + 1.0, frac)
    frac = jnp.where((~positive) & (frac > 0), frac - 1.0, frac)
    return jnp.float32(-2.0 * np.pi) * frac


def _channel_index_split(rows: int, i0: int):
    """Global channel index of every element of this grid step's
    [rows, _LANES] block, as an exact hi/lo float32 split (hi a multiple
    of 2^12, f32-exact to 2^36; lo < 2^12) — the one preamble every
    per-channel kernel shares."""
    from jax.experimental import pallas as pl

    step = pl.program_id(0)
    base = i0 + step * (rows * _LANES)
    row_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)
    lane_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    i_int = jnp.int32(base) + row_idx * _LANES + lane_idx
    return ((i_int & ~0xFFF).astype(jnp.float32),
            (i_int & 0xFFF).astype(jnp.float32))


def _df_frac32(hi, lo):
    """Single-f32 fraction of a df64 value (mod-1 representative; the
    final cos/sin only sees the phase mod one turn)."""
    t = jnp.trunc(hi)
    f = (hi - t) + lo
    return f - jnp.trunc(f)


def _chirp_phase_block_anchored(rows, i0, consts):
    """Anchored-Taylor chirp phase for this grid step's [rows, _LANES]
    block: one df64 anchor evaluation PER ROW (a [rows, 1] vector —
    1/128th of the per-element work) plus a cheap per-lane Taylor
    update — replacing the exact path's ~3 df64 divisions *per element*
    (measured 6.6x the bank-multiply cost at 2^27).  Derivation, error
    budget and the validity bound live with ops.dedisperse
    .anchored_chirp_consts; the builders only pass ``consts`` when the
    cubic remainder over one row's 128 channels is < 1e-6 turns (true
    for every physical config — 128-channel spans are tiny)."""
    from jax.experimental import pallas as pl

    blk = rows * _LANES
    step = pl.program_id(0)
    row_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    base = jnp.int32(i0) + step * jnp.int32(blk) + row_idx * _LANES
    b_hi = (base & ~0xFFF).astype(jnp.float32)        # [rows, 1]
    b_lo = (base & 0xFFF).astype(jnp.float32)

    def c(pair):
        return jnp.float32(pair[0]), jnp.float32(pair[1])

    df_hi, df_lo = c(consts["df"])
    fm_hi, fm_lo = c(consts["f_min"])
    A_hi, A_lo = c(consts["A"])
    C1_hi, C1_lo = c(consts["C1"])
    fc_hi, fc_lo = c(consts["f_c"])
    zero = jnp.float32(0)

    # f at each row anchor, then k via the product form u * r^2 (the
    # expanded C1*f - C2 + u form cancels ~1e9-turn terms and loses 3
    # digits of the fraction — measured 1.4e-5 turns)
    a1 = _df_mul(df_hi, df_lo, b_hi, zero)
    a2 = _df_mul(df_hi, df_lo, b_lo, zero)
    fi = _df_add(*a1, *a2)
    fa = _df_add(fm_hi, fm_lo, *fi)
    u = _df_div(A_hi, A_lo, *fa)              # A / f_a
    dfc = _df_add(*fa, -fc_hi, -fc_lo)
    r = _df_div(*dfc, fc_hi, fc_lo)
    k = _df_mul(*u, *_df_mul(*r, *r))
    k0f = _df_frac32(*k)                      # [rows, 1]

    # dk/d(channel) = df * (C1 - A/f^2), reduced mod 1 (delta is an
    # integer, so frac(k1*delta) == frac(frac(k1)*delta)), kept df64
    w = _df_div(*u, *fa)                      # A / f_a^2
    s = _df_add(C1_hi, C1_lo, -w[0], -w[1])
    k1 = _df_mul(df_hi, df_lo, *s)
    k1f = _two_sum(k1[0] - jnp.trunc(k1[0]), k1[1])

    # quadratic/cubic Taylor terms are < ~1e-4 turns over one row:
    # plain f32 suffices
    fa32 = fa[0]
    fa2 = fa32 * fa32
    k2 = jnp.float32(consts["df2A"]) / (fa2 * fa32)
    k3 = -jnp.float32(consts["df3A"]) / (fa2 * fa2)

    delta = jax.lax.broadcasted_iota(
        jnp.int32, (1, _LANES), 1).astype(jnp.float32)  # lane offset
    p_hi, p_lo = _df_mul(k1f[0], k1f[1],
                         jnp.broadcast_to(delta, (rows, _LANES)),
                         jnp.zeros((rows, _LANES), jnp.float32))
    v_hi, v_lo = _df_add(k0f, zero, p_hi, p_lo)
    poly = (delta * delta) * (k2 + k3 * delta)
    frac = (v_hi - jnp.trunc(v_hi)) + v_lo + poly
    frac = frac - jnp.trunc(frac)
    return jnp.float32(-2.0 * np.pi) * frac


def _chirp_consts(n, f_min, df, f_c, dm, i0, exact: bool = False):
    """Builder-side consts for the anchored in-kernel chirp; ``exact``
    (the Config.chirp_exact escape hatch) or the
    SRTB_PALLAS_CHIRP_EXACT=1 env knob forces the exact per-element
    path (hardware A/B of the round-3 anchored rewrite)."""
    import os
    if exact or os.environ.get("SRTB_PALLAS_CHIRP_EXACT", "") == "1":
        return None
    return dd.anchored_chirp_consts(n, f_min, df, f_c, dm, i0=int(i0),
                                    block=_LANES, allow_shrink=False)


def _chirp_phase(rows, i0, f_min, df, f_c, dm, consts):
    """Dispatch: anchored-Taylor when the builder proved it valid,
    exact per-element df64 otherwise."""
    if consts is not None:
        return _chirp_phase_block_anchored(rows, i0, consts)
    i_hi, i_lo = _channel_index_split(rows, i0)
    return _chirp_phase_block(i_hi, i_lo, f_min, df, f_c, dm)


def _spectrum_tiling(n: int):
    """(rows_total, rows, grid) for a [2, n] spectrum kernel launch —
    shared by every elementwise spectrum kernel here."""
    if n % _LANES:
        raise ValueError(f"n must be a multiple of {_LANES}")
    rows_total = n // _LANES
    rows = min(_ROWS, rows_total)
    if rows_total % rows:
        raise ValueError(f"{rows_total} rows not divisible by block {rows}")
    return rows_total, rows, (rows_total // rows,)


def _dedisperse_kernel(re_ref, im_ref, out_re_ref, out_im_ref, *,
                       f_min, df, f_c, dm, rows, i0, consts=None):
    phase = _chirp_phase(rows, i0, f_min, df, f_c, dm, consts)
    c = jnp.cos(phase)
    s = jnp.sin(phase)
    re = re_ref[:]
    im = im_ref[:]
    out_re_ref[:] = re * c - im * s
    out_im_ref[:] = re * s + im * c


def _rfi_dedisperse_kernel(re_ref, im_ref, thr_ref, mask_ref, out_re_ref,
                           out_im_ref, *, f_min, df, f_c, dm, rows, i0,
                           norm, has_mask, consts=None):
    """Fused RFI stage-1 (avg-threshold zap + normalize + manual mask,
    ref: rfi_mitigation_pipe.hpp:50-94) feeding the df64 chirp multiply:
    the spectrum crosses HBM once instead of once per stage."""
    re = re_ref[:]
    im = im_ref[:]
    # RFI s1: zap where power exceeds threshold*mean (thr_ref holds the
    # precomputed product), scale survivors by the normalization
    # coefficient (ref: rfi_mitigation_pipe.hpp:61-78)
    power = re * re + im * im
    keep = power <= thr_ref[0]
    scale = jnp.where(keep, jnp.float32(norm), 0.0)
    if has_mask:
        scale = scale * mask_ref[:]
    re = re * scale
    im = im * scale

    phase = _chirp_phase(rows, i0, f_min, df, f_c, dm, consts)
    c = jnp.cos(phase)
    s = jnp.sin(phase)
    out_re_ref[:] = re * c - im * s
    out_im_ref[:] = re * s + im * c


@S.scoped(S.CHIRP)
def rfi_s1_dedisperse_df64(spec_ri: jnp.ndarray, threshold: float,
                           norm: float, f_min: float, df: float,
                           f_c: float, dm: float,
                           mask: jnp.ndarray | None = None,
                           interpret: bool = False,
                           i0: int = 0, exact: bool = False) -> jnp.ndarray:
    """spec_ri [2, n] -> RFI-s1-zapped, normalized, manually-masked and
    dedispersed [2, n] in ONE kernel pass (the mean-power reduce runs as
    a jnp pass first; everything elementwise is fused here).

    Matches rfi.mitigate_rfi_average_and_normalize +
    rfi.mitigate_rfi_manual + the chirp multiply applied in sequence.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = spec_ri.shape[-1]
    rows_total, rows, grid = _spectrum_tiling(n)

    re = spec_ri[0].reshape(rows_total, _LANES)
    im = spec_ri[1].reshape(rows_total, _LANES)
    power_mean = jnp.mean(spec_ri[0] ** 2 + spec_ri[1] ** 2)
    thr = (jnp.float32(threshold) * power_mean).reshape(1)

    has_mask = mask is not None
    block = pl.BlockSpec((rows, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    if has_mask:
        # ``mask`` is a ZAP mask (True/1 = zero this bin, matching
        # rfi.mitigate_rfi_manual); the kernel multiplies by keep = 1-zap
        keep = 1.0 - mask.astype(jnp.float32)
        mask2d = keep.reshape(rows_total, _LANES)
        mask_block = block
    else:  # placeholder tile, never read by the kernel
        mask2d = jnp.zeros((1, _LANES), jnp.float32)
        mask_block = pl.BlockSpec((1, _LANES), lambda i: (0, 0),
                                  memory_space=pltpu.VMEM)
    kernel = functools.partial(_rfi_dedisperse_kernel, f_min=f_min, df=df,
                               f_c=f_c, dm=dm, rows=rows, i0=int(i0),
                               norm=float(norm), has_mask=has_mask,
                               consts=_chirp_consts(
                                   n, f_min, df, f_c, dm, i0, exact))
    with _ob_mode(interpret):
        out_re, out_im = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[block, block,
                      pl.BlockSpec(memory_space=pltpu.SMEM),
                      mask_block],
            out_specs=[block, block],
            out_shape=[jax.ShapeDtypeStruct((rows_total, _LANES),
                                            jnp.float32)] * 2,
            interpret=interpret,
        )(re, im, thr, mask2d)
    return jnp.stack([out_re.reshape(n), out_im.reshape(n)])


@S.scoped(S.CHIRP)
def dedisperse_df64(spec_ri: jnp.ndarray, f_min: float, df: float,
                    f_c: float, dm: float,
                    interpret: bool = False, i0: int = 0,
                    exact: bool = False) -> jnp.ndarray:
    """spec_ri [2, n] -> dedispersed [2, n], chirp generated in-kernel;
    ``i0`` is the global index of the first channel (sequence shards).

    n must be a multiple of 128; grid steps cover _ROWS*128 channels each.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = spec_ri.shape[-1]
    rows_total, rows, grid = _spectrum_tiling(n)

    re = spec_ri[0].reshape(rows_total, _LANES)
    im = spec_ri[1].reshape(rows_total, _LANES)
    kernel = functools.partial(_dedisperse_kernel, f_min=f_min, df=df,
                               f_c=f_c, dm=dm, rows=rows, i0=int(i0),
                               consts=_chirp_consts(
                                   n, f_min, df, f_c, dm, i0, exact))
    block = pl.BlockSpec((rows, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    with _ob_mode(interpret):
        out_re, out_im = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[block, block],
            out_specs=[block, block],
            out_shape=[jax.ShapeDtypeStruct((rows_total, _LANES),
                                            jnp.float32),
                       jax.ShapeDtypeStruct((rows_total, _LANES),
                                            jnp.float32)],
            interpret=interpret,
        )(re, im)
    return jnp.stack([out_re.reshape(n), out_im.reshape(n)])


# ----------------------------------------------------------------
# fused 2-bit unpack + window
# ----------------------------------------------------------------

# ----------------------------------------------------------------
# fused waterfall post-pass: spectral-kurtosis stats, zap, time series
# ----------------------------------------------------------------

def _sk_stats_kernel(re_ref, im_ref, s2_ref, s4_ref, fs_ref):
    from jax.experimental import pallas as pl

    t = pl.program_id(1)  # inner grid dim: time tiles

    @pl.when(t == 0)
    def _init():
        s2_ref[:] = jnp.zeros_like(s2_ref)
        s4_ref[:] = jnp.zeros_like(s4_ref)

    re = re_ref[:]
    im = im_ref[:]
    p = re * re + im * im                      # [R, TB]
    rows, tb = p.shape
    # keep 128 lanes through the reduction; final lane-sum happens outside
    p3 = p.reshape(rows, tb // _LANES, _LANES)
    s2_ref[:] += jnp.sum(p3, axis=1)           # [R, 128]
    s4_ref[:] += jnp.sum(p3 * p3, axis=1)

    @pl.when(t == 0)
    def _first_samples():
        fs_ref[:] = p[:, :_LANES]              # power of the first lanes


def _sk_apply_kernel(re_ref, im_ref, keep_ref, out_re_ref, out_im_ref,
                     ts_ref):
    from jax.experimental import pallas as pl

    f = pl.program_id(1)  # inner grid dim: frequency tiles
    keep = keep_ref[:, 0:1] != 0.0             # [R, 1] row mask
    # select, not multiply: a zapped row carrying Inf/NaN must become
    # exactly zero, matching the jnp path's jnp.where
    re = jnp.where(keep, re_ref[:], 0.0)
    im = jnp.where(keep, im_ref[:], 0.0)
    out_re_ref[:] = re
    out_im_ref[:] = im
    p = re * re + im * im                      # [R, TB]

    @pl.when(f == 0)
    def _init():
        ts_ref[:] = jnp.zeros_like(ts_ref)

    rows, tb = p.shape
    ts_ref[:] += jnp.sum(p, axis=0).reshape(tb // _LANES, _LANES)


def _sk_tiles(nfreq: int, ntime: int):
    """(rows, time_block) tiling for the fused SK kernels, or None when
    the waterfall shape cannot tile (single source of truth for both the
    capability check and the kernels).  tb is capped at 256 lanes-rows:
    512 puts the [rows, tb] f32 blocks at 16.25 MB of scoped VMEM, just
    over the 16 MB Mosaic stack limit on v5e."""
    rows = min(8, nfreq)
    tb = min(256 * _LANES, ntime)
    if nfreq % rows or ntime % _LANES or ntime % tb or tb % _LANES:
        return None
    return rows, tb


def sk_tiling_ok(nfreq: int, ntime: int) -> bool:
    """Whether the fused SK kernels can tile this waterfall (callers fall
    back to the jnp ops otherwise, e.g. tiny test/bench shapes)."""
    return _sk_tiles(nfreq, ntime) is not None


@S.scoped(S.DETECT)
def sk_zap_timeseries(wf_ri: jnp.ndarray, sk_threshold: float,
                      interpret: bool = False):
    """Fused spectral-kurtosis zap + detection front half in two HBM
    passes over the waterfall ``wf_ri [2, F, T]`` (re, im):

    pass 1 reads the waterfall once, producing per-row ``s2``/``s4``
    partial sums and first-sample powers; the tiny SK decision
    (ref: spectrum/rfi_mitigation.hpp:290-341 thresholds) happens in jnp;
    pass 2 reads the waterfall again, writes the zapped waterfall and
    accumulates the frequency-summed power time series
    (ref: signal_detect_pipe.hpp:305-316) in the same read.

    The jnp path costs ~3 reads + 1 write of the waterfall (SK stats,
    zap rewrite, time-series sum); this costs 2 reads + 1 write, and the
    time series comes out "for free" with the zap.

    Returns ``(wf_zapped_ri [2, F, T], zero_count [], ts [T])`` with
    ``zero_count``/``ts`` matching ops.detect semantics (zapped rows and
    first-sample-zero rows both count; ts is not yet mean-subtracted).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, nfreq, ntime = wf_ri.shape
    m = ntime
    tiles = _sk_tiles(nfreq, ntime)
    if tiles is None:
        raise ValueError(f"bad waterfall tiling [{nfreq}, {ntime}]")
    rows, tb = tiles

    re, im = wf_ri[0], wf_ri[1]

    # ---- pass 1: stats (grid: freq outer, time inner for accumulation)
    grid1 = (nfreq // rows, ntime // tb)
    in_block = pl.BlockSpec((rows, tb), lambda f, t: (f, t),
                            memory_space=pltpu.VMEM)
    row_block = pl.BlockSpec((rows, _LANES), lambda f, t: (f, 0),
                             memory_space=pltpu.VMEM)
    s2, s4, fs = pl.pallas_call(
        _sk_stats_kernel,
        grid=grid1,
        in_specs=[in_block, in_block],
        out_specs=[row_block, row_block, row_block],
        out_shape=[jax.ShapeDtypeStruct((nfreq, _LANES), jnp.float32)] * 3,
        interpret=interpret,
    )(re, im)

    # ---- tiny per-row decision in jnp, thresholds shared with
    # rfi.mitigate_rfi_spectral_kurtosis ----
    zap = sk_zap_decision(jnp.sum(s2, axis=-1), jnp.sum(s4, axis=-1), m,
                          sk_threshold)
    zero_count = jnp.sum(
        (zap | (fs[:, 0] == 0)).astype(jnp.int32))

    out_ri, ts = sk_apply_timeseries(wf_ri, zap, interpret)
    return out_ri, zero_count, ts


@S.scoped(S.DETECT)
def sk_zap_decision(s2_sum, s4_sum, m: int, sk_threshold: float):
    """Per-row zap verdict from the power moments (thresholds shared with
    rfi.mitigate_rfi_spectral_kurtosis)."""
    from srtb_tpu.ops.rfi import sk_decision_thresholds
    thr_low_, thr_high_ = sk_decision_thresholds(m, sk_threshold)
    sk = m * s4_sum / (s2_sum * s2_sum)
    return (sk > thr_high_) | (sk < thr_low_)


@S.scoped(S.DETECT)
def sk_apply_timeseries(wf_ri: jnp.ndarray, zap: jnp.ndarray,
                        interpret: bool = False):
    """Pass 2 of the fused SK chain, standalone: zap the verdict rows and
    accumulate the frequency-summed power time series in the same read.
    ``zap`` is the [F] boolean verdict (e.g. from
    :func:`sk_zap_decision` over stats collected by the waterfall FFT's
    fused epilogue, ops/pallas_fft.fft_rows_stats_ri — in that pairing
    the waterfall is never re-read for statistics at all).

    Returns ``(wf_zapped_ri [2, F, T], ts [T])``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, nfreq, ntime = wf_ri.shape
    tiles = _sk_tiles(nfreq, ntime)
    if tiles is None:
        raise ValueError(f"bad waterfall tiling [{nfreq}, {ntime}]")
    rows, tb = tiles
    re, im = wf_ri[0], wf_ri[1]
    keep = jnp.broadcast_to((~zap).astype(jnp.float32)[:, None],
                            (nfreq, _LANES))
    grid2 = (ntime // tb, nfreq // rows)
    in_block2 = pl.BlockSpec((rows, tb), lambda t, f: (f, t),
                             memory_space=pltpu.VMEM)
    keep_block = pl.BlockSpec((rows, _LANES), lambda t, f: (f, 0),
                              memory_space=pltpu.VMEM)
    ts_block = pl.BlockSpec((tb // _LANES, _LANES), lambda t, f: (t, 0),
                            memory_space=pltpu.VMEM)
    out_re, out_im, ts2d = pl.pallas_call(
        _sk_apply_kernel,
        grid=grid2,
        in_specs=[in_block2, in_block2, keep_block],
        out_specs=[in_block2, in_block2, ts_block],
        out_shape=[jax.ShapeDtypeStruct((nfreq, ntime), jnp.float32),
                   jax.ShapeDtypeStruct((nfreq, ntime), jnp.float32),
                   jax.ShapeDtypeStruct((ntime // _LANES, _LANES),
                                        jnp.float32)],
        interpret=interpret,
    )(re, im, keep)

    return jnp.stack([out_re, out_im]), ts2d.reshape(ntime)
