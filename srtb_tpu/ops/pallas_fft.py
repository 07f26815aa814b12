"""Pallas row FFT: batched C2C transforms computed entirely in VMEM.

XLA's TPU FFT moves each point through HBM several times per transform
(measured: 14.6 ms for the [2048, 2^16] waterfall backward C2C — ~6x the
one-read-one-write floor, PERF.md).  For rows that fit VMEM, the whole
transform instead runs inside one Pallas grid step: DMA a block of rows
in, run a two-level Cooley-Tukey split L = La*Lb where *both* levels are
DFT-matrix matmuls on the MXU, DMA the result out.  One HBM read + one
write per point.

Why two explicit matmul levels instead of the radix-128 recursion of
ops/mxu_fft: inside VMEM every array's minor dimension pads to the
128-lane tile, so the recursion's deep [..., 128, 4]-shaped base cases
would blow the block up 32x and OOM the ~16 MB VMEM.  The two-level
split keeps every intermediate's minor dimension at La, Lb or rows*Lb
(>= 64 lanes throughout):

    x[rows, La(j1), Lb(j2)]
      -> transpose [La, rows*Lb]            (VMEM relayout)
      -> Wa^T @ x          : A[k1, j2]      (MXU, contraction La)
      -> * tw[k1, j2]                       (VPU; table passed in, no
                                             in-kernel transcendentals)
      -> @ Wb              : B[k1, k2]      (MXU, contraction Lb)
      -> transpose/reshape [rows, Lb*La]    (natural order: k = k2*La+k1)

It spends La+Lb MACs per point where a true FFT spends 5*log2(L) flops —
deliberately: MXU FLOPs are the cheap resource, HBM passes the scarce
one (scaling-book roofline).  DFT matrices and twiddles are computed in
float64 on host / via the exact-phase generator and passed as kernel
inputs (Pallas forbids captured constants).

This is the TPU answer to the reference's per-vendor FFT wrappers for
the *batched* transforms (ref: fft/fft.hpp:54-160, fft_pipe.hpp:295-311
watfft batch): srtb's waterfall FFT and the four-step legs of the big
segment FFT are all batched rows of length <= 2^16.

Complex values cross the kernel boundary as separate re/im f32 planes
(Mosaic has no complex dtype).  Correctness is held to the same oracles
as every other FFT backend (tests/test_pallas_fft.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from srtb_tpu.ops import fft as F

# Default row-block plan: 1 MB planes (v5e VMEM is 128 MiB/core, but
# small blocks keep the pipeline's working set comfortably inside the
# 100 MiB scoped limit _call_kwargs sets; SRTB_PALLAS_VMEM_MB scales
# both).  Live per grid step: in + out + stage intermediates (all
# [rows, *] f32 pairs) + matrices + twiddle.
_VMEM_BLOCK_ELEMS = 1 << 18  # 256K f32 = 1 MB per plane

# Matmul precision for the DFT contractions.  HIGHEST (6-pass bf16,
# f32-accurate) is the only accurate option real Mosaic accepts: the
# round-5 acceptance run rejected 3-pass bf16 outright
# ("NotImplementedError: Unsupported dot precision: HIGH"; not
# re-measured on this JAX) — an error CPU interpret mode cannot
# surface.  The extra passes run on VMEM-resident blocks; what
# HIGHEST costs end to end is not measured on the chip.
_PRECISION = jax.lax.Precision.HIGHEST


def _split_la_lb(length: int):
    """Factor L = La*Lb with La pinned to 128: the final natural-order
    assembly transposes to a [rows, Lb, La] view, so La is the one minor
    dimension that must stay a full 128-lane tile.  Lb = L/128 lands in
    [32, 512] over the supported range ([Lb, Lb] tail matrix <= 1 MB per
    plane; Lb < 128 pads its stage intermediates up to 4x in VMEM, paid
    only on the small end)."""
    if length & (length - 1) or not (1 << 12) <= length <= (1 << 16):
        return None
    return 128, length // 128


def supported(length: int, batch: int) -> bool:
    """Whether the Pallas row FFT handles [batch, length]."""
    return _split_la_lb(length) is not None and batch >= 1


def vmem_fft_rows(xr, xi, war, wai, wbr, wbi, twr, twi, *, la, lb, rows):
    """The in-VMEM two-level row FFT on value arrays: [rows, L] f32
    (re, im) -> length-L C2C along each row, L = la*lb, la = 128.
    Returns the natural-order result as a 3D ``[rows, la, lb]`` view
    whose row-major flatten IS the natural-order row (element
    ``[r, ka, kb]`` is bin ``k = ka*lb + kb``) — kernels store it to a
    matching 3D ref and callers flatten OUTSIDE the pallas_call, where
    the contiguous reshape is free metadata.  Pure function of
    VMEM-resident values, shared by the kernels here.

    This is the one spelling real Mosaic accepted (round-5 acceptance
    probes; not re-measured on this JAX): in-kernel lane-dim reshapes
    compile only when the minor dim is a multiple of 128 on both sides,
    which rules out the historical ``[rows, la, lb]`` input split and
    any in-kernel flatten of the assembled result.  Decimation here is
    ``j = jb*la + ja`` (ja the 128-lane minor digit), so the only input
    reshape is the supported minor-128 split, both DFT contractions are
    3D dot_generals against the middle axis, and the assembly is one
    supported 3D transpose."""
    dg = dot_mid
    # j = jb*la + ja: the minor-128 split Mosaic accepts
    x3r = xr.reshape(rows, lb, la)
    x3i = xi.reshape(rows, lb, la)
    # stage 1, contract jb: A[r, ja, kb] = sum_jb Wb[jb, kb] x[r, jb, ja]
    ar = dg(x3r, wbr, 1) - dg(x3i, wbi, 1)      # [rows, la, lb]
    ai = dg(x3r, wbi, 1) + dg(x3i, wbr, 1)
    # twiddle tw[ja, kb] = exp(-+2*pi*i*ja*kb/L), broadcast over rows
    twr3 = twr.reshape(1, la, lb)
    twi3 = twi.reshape(1, la, lb)
    br = ar * twr3 - ai * twi3
    bi = ar * twi3 + ai * twr3
    # stage 2, contract ja: C[r, kb, ka] = sum_ja Wa[ja, ka] B[r, ja, kb]
    cr = dg(br, war, 1) - dg(bi, wai, 1)        # [rows, lb, la]
    ci = dg(br, wai, 1) + dg(bi, war, 1)
    # natural order k = ka*lb + kb: one 3D transpose to [r, ka, kb]
    yr = jnp.transpose(cr, (0, 2, 1))           # [rows, la, lb]
    yi = jnp.transpose(ci, (0, 2, 1))
    return yr, yi


def dot_mid(a, b, dim):
    """dot_general contracting ``a``'s axis ``dim`` with ``b``'s axis 0
    under the module's DFT precision discipline — the single home of
    that convention for the spellings here and in pallas_fft2."""
    return jax.lax.dot_general(
        a, b, (((dim,), (0,)), ((), ())),
        precision=_PRECISION, preferred_element_type=jnp.float32)


def _fft_rows_kernel(re_ref, im_ref, war_ref, wai_ref, wbr_ref, wbi_ref,
                     twr_ref, twi_ref, out_re_ref, out_im_ref, *,
                     la, lb, rows):
    out_re_ref[:], out_im_ref[:] = vmem_fft_rows(
        re_ref[:], im_ref[:], war_ref[:], wai_ref[:], wbr_ref[:],
        wbi_ref[:], twr_ref[:], twi_ref[:], la=la, lb=lb, rows=rows)


def _fft_rows_stats_kernel(re_ref, im_ref, war_ref, wai_ref, wbr_ref,
                           wbi_ref, twr_ref, twi_ref, dwr_ref,
                           out_re_ref, out_im_ref, s2_ref, s4_ref, *,
                           la, lb, rows, apply_dewindow):
    """fft_rows kernel + fused epilogue: optional de-window multiply and
    per-row power moments (sum |x|^2, sum |x|^4 as 128-lane partials) —
    the spectral-kurtosis statistics collected while the waterfall rows
    are still in VMEM, so the SK stage never re-reads the waterfall from
    HBM (ref: spectrum/rfi_mitigation.hpp:290-341 computes them in a
    separate pass).  All values here carry the helper's 3D
    ``[rows, la, lb]`` natural-flat view: the de-window vector arrives
    pre-shaped ``[la, lb]`` from the host (an in-kernel [1, L] ->
    [la, lb] split would be the unsupported minor-lb reshape) and the
    moment partials reduce over kb, leaving [rows, la=128] lane
    partials — a different partial grouping than the flat kernel's
    historical L/128 chunks, same finished sums."""
    _fft_rows_kernel(re_ref, im_ref, war_ref, wai_ref, wbr_ref, wbi_ref,
                     twr_ref, twi_ref, out_re_ref, out_im_ref,
                     la=la, lb=lb, rows=rows)
    yr = out_re_ref[:]
    yi = out_im_ref[:]
    if apply_dewindow:
        dw = dwr_ref[:].reshape(1, la, lb)  # reciprocal de-window coeffs
        yr = yr * dw
        yi = yi * dw
        out_re_ref[:] = yr
        out_im_ref[:] = yi
    p = yr * yr + yi * yi
    s2_ref[:] = jnp.sum(p, axis=2)
    s4_ref[:] = jnp.sum(p * p, axis=2)


def _fft_rows_skzap_kernel(re_ref, im_ref, war_ref, wai_ref, wbr_ref,
                           wbi_ref, twr_ref, twi_ref, dwr_ref,
                           out_re_ref, out_im_ref, zap_ref, fs_ref,
                           ts_ref, *, la, lb, rows, apply_dewindow,
                           m, thr_low, thr_high):
    """The whole waterfall tail in ONE kernel: backward C2C + de-window
    + spectral-kurtosis decision + zap + detection time-series
    accumulation, all while the rows are VMEM-resident.

    The key structural fact making this legal: each waterfall row is
    transformed *entirely within one grid step* (the row fits VMEM), so
    its SK moments — which the two-kernel chain
    (fft_rows_stats_ri + pallas_kernels.sk_apply_timeseries) must round
    -trip through HBM to globalize — are complete before the row is
    ever written.  The zap verdict (thresholds precomputed by
    rfi.sk_decision_thresholds, ref: spectrum/rfi_mitigation.hpp:
    290-341) applies in-register, the zapped row is written once, and
    the row's contribution to the frequency-summed power time series
    (ref: signal_detect_pipe.hpp:305-316) accumulates into a single
    [la, lb] block revisited across grid steps — the detect stage never
    reads the waterfall back from HBM at all.

    Outputs beyond the zapped rows: ``zap_ref``/``fs_ref`` are
    [rows, 128] lane-broadcast per-row flags (zap verdict; first-time-
    sample power) for the zero-channel count, ``ts_ref`` the [la, lb]
    natural-flat time series (flatten outside the call)."""
    from jax.experimental import pallas as pl

    yr, yi = vmem_fft_rows(
        re_ref[:], im_ref[:], war_ref[:], wai_ref[:], wbr_ref[:],
        wbi_ref[:], twr_ref[:], twi_ref[:], la=la, lb=lb, rows=rows)
    if apply_dewindow:
        dw = dwr_ref[:].reshape(1, la, lb)  # reciprocal de-window coeffs
        yr = yr * dw
        yi = yi * dw
    p = yr * yr + yi * yi                       # [rows, la, lb]
    # complete per-row SK moments (the row is fully resident): reduce
    # lanes last so every intermediate keeps a 128-wide minor dim
    s2 = jnp.sum(jnp.sum(p, axis=2), axis=1, keepdims=True)   # [rows, 1]
    s4 = jnp.sum(jnp.sum(p * p, axis=2), axis=1, keepdims=True)
    sk = jnp.float32(m) * s4 / (s2 * s2)
    zap = (sk > thr_high) | (sk < thr_low)      # [rows, 1]
    # select, not multiply: a zapped row carrying Inf/NaN must become
    # exactly zero (same contract as rfi.mitigate_rfi_spectral_kurtosis)
    zap3 = zap[:, :, None]
    out_re_ref[:] = jnp.where(zap3, 0.0, yr)
    out_im_ref[:] = jnp.where(zap3, 0.0, yi)
    zap_ref[:] = jnp.broadcast_to(
        jnp.where(zap, 1.0, 0.0), zap_ref.shape)
    # natural-flat bin t=0 is [r, ka=0, kb=0]: first-sample power,
    # pre-zap (zapped rows count through the zap flag, matching the
    # jnp chain's `zap | (first == 0)` zero-channel accounting)
    fs_ref[:] = jnp.broadcast_to(p[:, 0:1, 0], fs_ref.shape)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        ts_ref[:] = jnp.zeros_like(ts_ref)

    ts_ref[:] += jnp.sum(jnp.where(zap3, 0.0, p), axis=0)


def fft_rows_skzap_ri(re: jnp.ndarray, im: jnp.ndarray,
                      sk_threshold: float,
                      inverse: bool = True,
                      dewindow: jnp.ndarray | None = None,
                      interpret: bool = False):
    """Fully-fused waterfall tail over split re/im rows ``[..., F, L]``
    (leading dims flattened to batch; callers run one data stream per
    call so the time series stays per-stream): one HBM read of the
    dedispersed spectrum rows, one write of the zapped waterfall, and
    the SK verdict + zero-channel flags + detection time series come
    out with the write.

    Returns ``(re, im, zapf, fs0, ts)``: zapped waterfall planes
    [..., F, L]; ``zapf``/``fs0`` [..., F, 128] lane-broadcast per-row
    zap flag and first-sample power (finish the zero-channel count with
    ``(zapf[..., 0] != 0) | (fs0[..., 0] == 0)``); ``ts`` [L] the
    not-yet-mean-subtracted power time series over kept rows.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from srtb_tpu.ops.rfi import sk_decision_thresholds

    lc = _Launch(re, im, inverse)
    thr_low, thr_high = sk_decision_thresholds(lc.length, sk_threshold)
    apply_dewindow = dewindow is not None
    if apply_dewindow:
        dwr = (1.0 / dewindow.astype(jnp.float32)).reshape(lc.la, lc.lb)
    else:  # placeholder tile, never read by the kernel
        dwr = jnp.ones((lc.la, lc.lb), jnp.float32)

    stat_block = pl.BlockSpec((lc.rows, 128), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)
    ts_block = pl.BlockSpec((lc.la, lc.lb), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    kernel = functools.partial(
        _fft_rows_skzap_kernel, la=lc.la, lb=lc.lb, rows=lc.rows,
        apply_dewindow=apply_dewindow, m=lc.length,
        thr_low=float(thr_low), thr_high=float(thr_high))
    out_re, out_im, zapf, fs0, ts = pl.pallas_call(
        kernel,
        grid=lc.grid,
        in_specs=[lc.block, lc.block] + lc.const_specs
                 + [lc.const_spec((lc.la, lc.lb))],
        out_specs=[lc.out_block, lc.out_block, stat_block, stat_block,
                   ts_block],
        out_shape=[lc.out_shape(), lc.out_shape(),
                   jax.ShapeDtypeStruct((lc.pbatch, 128), jnp.float32),
                   jax.ShapeDtypeStruct((lc.pbatch, 128), jnp.float32),
                   jax.ShapeDtypeStruct((lc.la, lc.lb), jnp.float32)],
        interpret=interpret,
        **_call_kwargs(interpret),
    )(lc.re2, lc.im2, *lc.consts, dwr)
    return (lc.unpad(out_re).reshape(lc.shape),
            lc.unpad(out_im).reshape(lc.shape),
            lc.unpad(zapf).reshape(*lc.shape[:-1], 128),
            lc.unpad(fs0).reshape(*lc.shape[:-1], 128),
            ts.reshape(lc.length))


def _vmem_mb() -> int | None:
    """Single parse + validation of SRTB_PALLAS_VMEM_MB (None = the
    proven default plan).  Both readers — the block sizing and the
    Mosaic vmem limit — branch on this one value, so a degenerate
    setting cannot make the two halves of the plan disagree."""
    import os

    env = os.environ.get("SRTB_PALLAS_VMEM_MB")
    if not env:
        return None
    try:
        mb = int(env)
    except ValueError:
        mb = 0
    if mb <= 0:
        raise ValueError(
            f"SRTB_PALLAS_VMEM_MB={env!r} must be a positive integer "
            "(MiB of VMEM the row-FFT plan may assume)")
    return mb


def _rows_budget_padded(length: int, budget_bytes: int) -> int:
    """Largest rows whose PADDED footprint fits the budget:
    2x-pipelined in/out block refs at rows*length f32 each (the 3D
    output block's minor dim lb
    lane-pads to 128, up to 4x on the small-length end — which a flat
    per-plane divisor would undercount exactly where it hurts), plus
    the helper's live stages ([rows, la, lb] intermediates, lb
    lane-padded)."""
    la, lb = _split_la_lb(length)
    plb = max(lb, 128)
    # 2x pipeline x (2 input refs at length + 2 output refs at la*plb)
    per_row_refs = 2 * 2 * (length + la * plb) * 4
    per_row_live = 6 * la * plb * 4
    consts = 4 * (2 * la * la + 2 * lb * plb + 2 * la * plb)
    per_row = per_row_refs + per_row_live
    return max(1, (budget_bytes - consts) // per_row)


def _row_block(length: int, batch: int) -> int:
    mb = _vmem_mb()
    if mb is None:
        elems = _VMEM_BLOCK_ELEMS
    else:
        rows = _rows_budget_padded(length, mb << 20)
        elems = rows * length
    return _row_block_for(length, batch, elems)


def _call_kwargs(interpret: bool) -> dict:
    """Extra pallas_call kwargs: an explicit scoped-vmem limit, always.
    Mosaic's *default* limit is far below the v5e's physical 128 MiB,
    and the L=2^16 leg overflows it.  100 MiB leaves headroom for
    Mosaic internal scratch; SRTB_PALLAS_VMEM_MB overrides (and then
    also drives the block sizing above)."""
    if interpret:
        return {}
    mb = _vmem_mb() or 100
    return {"compiler_params": tpu_compiler_params(
        vmem_limit_bytes=mb << 20)}


def tpu_compiler_params(**kwargs):
    """Mosaic compiler-params — shared by the pallas2 kernels."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


@functools.lru_cache(maxsize=None)
def _row_block_for(length: int, padded_batch: int, elems: int) -> int:
    """Row block for a batch already padded to a multiple of 8: real
    Mosaic requires the block's sublane dim divisible by 8 (round-5
    acceptance run), so rows is the largest multiple-of-8 divisor of
    the padded batch within the VMEM element target, floor 8."""
    target = max(8, elems // length)
    rows = (target // 8) * 8
    while rows > 8 and padded_batch % rows:
        rows -= 8
    return max(8, rows)


def _pad_batch(batch: int) -> int:
    """Smallest multiple of 8 >= batch (the Mosaic sublane-tile floor);
    padded rows are transformed and discarded — pure overhead only for
    batches < 8 or odd batches, which no production shape uses."""
    return -(-batch // 8) * 8


@functools.lru_cache(maxsize=None)
def _dft_matrix_np(r: int, inverse: bool):
    j = np.arange(r, dtype=np.float64)[:, None]
    k = np.arange(r, dtype=np.float64)[None, :]
    w = np.exp((2.0 if inverse else -2.0) * 1j * np.pi * j * k / r)
    return (np.ascontiguousarray(w.real.astype(np.float32)),
            np.ascontiguousarray(w.imag.astype(np.float32)))


def leg_consts(length: int, inverse: bool):
    """(la, lb, const arrays) for a two-level in-VMEM row FFT of this
    length — the DFT matrices and inner twiddle every kernel using
    :func:`vmem_fft_rows` must pass in (``_Launch`` with
    :func:`leg_const_specs`)."""
    split = _split_la_lb(length)
    if split is None:
        raise ValueError(f"row-FFT length {length} unsupported")
    la, lb = split
    war, wai = _dft_matrix_np(la, inverse)
    wbr, wbi = _dft_matrix_np(lb, inverse)
    # tw[k1, j2] = exp(+-2*pi*i*k1*j2/L): exact integer residues
    # through the hi/lo phase split (ops.fft._twiddle discipline)
    tw = F._twiddle(la, lb, inverse)
    return la, lb, (jnp.asarray(war), jnp.asarray(wai),
                    jnp.asarray(wbr), jnp.asarray(wbi),
                    jnp.real(tw), jnp.imag(tw))


def leg_const_specs(la: int, lb: int):
    """BlockSpecs matching :func:`leg_consts`'s arrays, in order."""
    return [_Launch.const_spec(s) for s in
            [(la, la), (la, la), (lb, lb), (lb, lb), (la, lb), (la, lb)]]


class _Launch:
    """Shared launch recipe for the row-FFT kernels: shape checks, the
    La/Lb split, VMEM block sizing, and the DFT/twiddle constants — one
    home, so the plain and stats variants can never drift apart."""

    def __init__(self, re, im, inverse):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        self.shape = re.shape
        self.length = self.shape[-1]
        self.batch = (int(np.prod(self.shape[:-1]))
                      if len(self.shape) > 1 else 1)
        if not supported(self.length, self.batch):
            raise ValueError(f"unsupported row FFT shape {self.shape}")
        self.la, self.lb = _split_la_lb(self.length)
        # pad the batch to the Mosaic sublane-tile floor (multiple of 8)
        self.pbatch = _pad_batch(self.batch)
        re2 = re.reshape(self.batch, self.length)
        im2 = im.reshape(self.batch, self.length)
        if self.pbatch != self.batch:
            pad = ((0, self.pbatch - self.batch), (0, 0))
            re2 = jnp.pad(re2, pad)
            im2 = jnp.pad(im2, pad)
        self.re2, self.im2 = re2, im2
        self.rows = _row_block(self.length, self.pbatch)
        self.grid = (self.pbatch // self.rows,)
        self.block = pl.BlockSpec((self.rows, self.length),
                                  lambda i: (i, 0),
                                  memory_space=pltpu.VMEM)
        # the kernels write the helper's 3D [rows, la, lb] natural-flat
        # view; callers flatten the [batch, la, lb] result outside the
        # pallas_call (contiguous row-major -> free metadata reshape)
        self.out_block = pl.BlockSpec((self.rows, self.la, self.lb),
                                      lambda i: (i, 0, 0),
                                      memory_space=pltpu.VMEM)
        _, _, self.consts = leg_consts(self.length, inverse)
        self.const_specs = leg_const_specs(self.la, self.lb)

    @staticmethod
    def const_spec(shp):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        return pl.BlockSpec(shp, lambda i: tuple(0 for _ in shp),
                            memory_space=pltpu.VMEM)

    def out_shape(self):
        return jax.ShapeDtypeStruct((self.pbatch, self.la, self.lb),
                                    jnp.float32)

    def unpad(self, out):
        """Drop the batch padding rows (no-op slice when unpadded)."""
        return out[:self.batch] if self.pbatch != self.batch else out


def fft_rows_ri(re: jnp.ndarray, im: jnp.ndarray, inverse: bool = False,
                interpret: bool = False):
    """C2C FFT along the last axis of split re/im f32 [..., L] arrays
    (leading dims batch), one grid step per VMEM-sized row block.
    Unnormalized both directions (same conventions as ops.fft
    c2c_forward / c2c_backward)."""
    from jax.experimental import pallas as pl

    lc = _Launch(re, im, inverse)
    kernel = functools.partial(_fft_rows_kernel, la=lc.la, lb=lc.lb,
                               rows=lc.rows)
    out_re, out_im = pl.pallas_call(
        kernel,
        grid=lc.grid,
        in_specs=[lc.block, lc.block] + lc.const_specs,
        out_specs=[lc.out_block, lc.out_block],
        out_shape=[lc.out_shape()] * 2,
        interpret=interpret,
        **_call_kwargs(interpret),
    )(lc.re2, lc.im2, *lc.consts)
    return (lc.unpad(out_re).reshape(lc.shape),
            lc.unpad(out_im).reshape(lc.shape))


def fft_rows(x: jnp.ndarray, inverse: bool = False,
             interpret: bool = False) -> jnp.ndarray:
    """Complex convenience wrapper over :func:`fft_rows_ri`."""
    yr, yi = fft_rows_ri(jnp.real(x), jnp.imag(x), inverse, interpret)
    return jax.lax.complex(yr, yi)


def fft_rows_stats_ri(re: jnp.ndarray, im: jnp.ndarray,
                      inverse: bool = True,
                      dewindow: jnp.ndarray | None = None,
                      interpret: bool = False):
    """Waterfall form of :func:`fft_rows_ri`: C2C rows plus a fused
    epilogue computing the optional de-window multiply (``dewindow`` is
    the [L] coefficient vector to divide out, ref: fft_pipe.hpp:346-359)
    and the per-row power moments for spectral kurtosis.

    Returns ``(re, im, s2, s4)`` where s2/s4 are [B, 128] lane-partial
    sums of |x|^2 / |x|^4 per row (finish with ``.sum(-1)``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lc = _Launch(re, im, inverse)
    shape, length, batch = lc.shape, lc.length, lc.batch
    rows = lc.rows
    apply_dewindow = dewindow is not None
    if apply_dewindow:
        # pre-shaped [la, lb] on the host: the natural-flat [r, ka, kb]
        # element is bin ka*lb + kb, and an in-kernel [1, L] -> [la, lb]
        # split would be the unsupported minor-lb reshape
        dwr = (1.0 / dewindow.astype(jnp.float32)).reshape(lc.la, lc.lb)
    else:  # placeholder tile, never read by the kernel
        dwr = jnp.ones((lc.la, lc.lb), jnp.float32)

    stat_block = pl.BlockSpec((rows, 128), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)
    kernel = functools.partial(_fft_rows_stats_kernel, la=lc.la, lb=lc.lb,
                               rows=rows, apply_dewindow=apply_dewindow)
    out_re, out_im, s2, s4 = pl.pallas_call(
        kernel,
        grid=lc.grid,
        in_specs=[lc.block, lc.block] + lc.const_specs
                 + [lc.const_spec((lc.la, lc.lb))],
        out_specs=[lc.out_block, lc.out_block, stat_block, stat_block],
        out_shape=[lc.out_shape(), lc.out_shape(),
                   jax.ShapeDtypeStruct((lc.pbatch, 128), jnp.float32),
                   jax.ShapeDtypeStruct((lc.pbatch, 128), jnp.float32)],
        interpret=interpret,
        **_call_kwargs(interpret),
    )(lc.re2, lc.im2, *lc.consts, dwr)
    return (lc.unpad(out_re).reshape(shape),
            lc.unpad(out_im).reshape(shape),
            lc.unpad(s2).reshape(*shape[:-1], 128),
            lc.unpad(s4).reshape(*shape[:-1], 128))
