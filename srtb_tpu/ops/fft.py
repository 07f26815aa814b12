"""FFT layer.

Replaces the reference's vendor FFT dispatcher (ref: fft/fft.hpp:54-160 with
cuFFT/hipFFT/muFFT/FFTW/naive wrappers) with XLA's TPU FFT behind the same
conventions, plus a four-step (Bailey) decomposition for sizes where a
single monolithic 1-D FFT is slow or unsupported.

Conventions reproduced from the reference:
- forward transforms are unnormalized (cuFFT style);
- "backward" C2C means unnormalized inverse, i.e. numpy's
  ``ifft(..., norm="forward")``;
- the R2C output drops the Nyquist bin so the usable spectrum has exactly
  n/2 channels (ref: fft_pipe.hpp:75-77);
- the waterfall FFT reshapes the n/2-channel dedispersed spectrum to
  ``[spectrum_channel_count, watfft_len]`` (each row = one coarse frequency
  sub-band, contiguous) and runs an unnormalized backward C2C per row
  (ref: fft_pipe.hpp:295-311), giving a frequency-major dynamic spectrum.

The plan cache of the reference (fft_wrapper.hpp set_size / shared work
area) maps to the XLA compilation cache: a given (shape, kind) compiles
once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from srtb_tpu.ops import scopes as S


@S.scoped(S.FFT_R2C)
def rfft_drop_nyquist(x: jnp.ndarray) -> jnp.ndarray:
    """R2C FFT of the whole segment, highest bin dropped: n real samples ->
    n/2 complex channels (ref: fft_pipe.hpp:44-78)."""
    return jnp.fft.rfft(x)[..., :-1]


def c2c_forward(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    return jnp.fft.fft(x, axis=axis)


def c2c_backward(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Unnormalized inverse C2C (cuFFT BACKWARD semantics)."""
    return jnp.fft.ifft(x, axis=axis, norm="forward")


@S.scoped(S.WATERFALL)
def waterfall_c2c(spectrum: jnp.ndarray, channel_count: int,
                  dewindow: jnp.ndarray | None = None,
                  len_cap: int | None = None) -> jnp.ndarray:
    """Dedispersed spectrum (n/2 complex) -> dynamic spectrum
    ``[channel_count, watfft_len]`` via per-row unnormalized backward C2C
    (ref: fft_pipe.hpp:285-372).  Rows are coarse frequency channels; columns
    are time samples within the segment.

    ``dewindow``: watfft_len divisors to de-apply after the backward
    transform, as the reference does for non-rectangle windows
    (ref: fft_pipe.hpp:346-359).  Callers must pass *pre-sanitized*
    coefficients from ``window.dewindow_coefficients`` (zero hann edges
    already replaced by 1 — the single home of that guard).
    """
    n = spectrum.shape[-1]
    watfft_len = n // channel_count
    x = spectrum[..., :channel_count * watfft_len]
    x = x.reshape(*spectrum.shape[:-1], channel_count, watfft_len)
    # row lengths beyond the XLA cap (coarse channelizations of long
    # segments, e.g. [2048, 2^17]) go through the four-step path
    wf = _fft_minor(x, inverse=True, len_cap=len_cap)
    if dewindow is not None:
        wf = wf / dewindow
    return wf


@S.scoped(S.WATERFALL)
def ifft_refft_waterfall(spectrum: jnp.ndarray, channel_count: int,
                         nsamps_reserved_complex: int = 0,
                         window: jnp.ndarray | None = None,
                         len_cap: int | None = None) -> jnp.ndarray:
    """The reference's alternate channelization path (currently disabled in
    its main(), ref: main.cpp:182-186): full unnormalized inverse C2C back
    to the (dedispersed) complex time domain, trim the reserved tail, then
    forward C2C in chunks of ``channel_count``
    (ref: fft_pipe.hpp:88-170 ifft_1d_c2c_pipe, 183-278 refft_1d_c2c_pipe).

    Output is time-major: [n_chunks(time), channel_count(freq)] — the
    orientation consumed by signal_detect_pipe variant 1.
    """
    td = _fft_minor(spectrum, inverse=True, len_cap=len_cap)
    n = td.shape[-1]
    if 0 < nsamps_reserved_complex < n:
        td = td[..., : n - nsamps_reserved_complex]
    refft_length = min(channel_count, td.shape[-1])
    batch = td.shape[-1] // refft_length
    td = td[..., : batch * refft_length]
    td = td.reshape(*td.shape[:-1], batch, refft_length)
    if window is not None:
        td = td * window
    return c2c_forward(td, axis=-1)


# ----------------------------------------------------------------
# four-step (Bailey) decomposition for very large 1-D FFTs
# ----------------------------------------------------------------
#
# FFT_n = transpose . FFT_rows(n2) . twiddle . FFT_cols(n1) with n = n1*n2.
# On TPU this turns one huge 1-D FFT (which XLA may refuse or handle with a
# poor plan) into two large *batched* FFTs plus elementwise twiddles —
# exactly the shape XLA tiles well.  This is hard part #1 of SURVEY.md §7.

def _phase_exp(r: jnp.ndarray, n: int, sign: float) -> jnp.ndarray:
    """exp(i*sign*2*pi*r/n) for an int32 residue array r (0 <= r < ~n).

    The residue is split into high/low halves so each converts to float32
    exactly; the two sin/cos arguments are combined by angle addition.
    This keeps the phase accurate for n far beyond f32's 24-bit mantissa
    without materializing any host-side table.
    """
    half = 1 << max(n.bit_length() // 2, 1)
    scale = jnp.float32(sign * 2.0 * np.pi / n)
    a = ((r // half) * half).astype(jnp.float32) * scale  # exact multiples
    b = (r % half).astype(jnp.float32) * scale            # < half: exact
    # exp(i(a+b)) = exp(ia) * exp(ib)
    return (jax.lax.complex(jnp.cos(a), jnp.sin(a))
            * jax.lax.complex(jnp.cos(b), jnp.sin(b)))


def _iota_phase(m: int, n: int, sign: float,
                block: int = 256) -> jnp.ndarray:
    """exp(i*sign*2*pi*k/n) for k = 0..m-1, as the outer product of two
    small tables: k = block*q + r, so w[k] = W[q] * V[r] with
    W[q] = exp(i*s*block*q/n), V[r] = exp(i*s*r/n).

    Computing the phase per element costs ~4 transcendentals for each of
    m points (the dominant cost of the Hermitian post-process at
    m = 2^26, measured); the factored form needs m/block + block of them
    plus one complex multiply per point, and its [m/block, block] shape
    is lane-dense.  Accuracy: q*block and r are f32-exact (both well
    under 2^24), so each factor's phase argument is exact — same
    discipline as `_phase_exp`, via the structure of k instead of a
    hi/lo split."""
    if m % block or m < block:
        return _phase_exp(jax.lax.iota(jnp.int32, m), n, sign)
    scale = jnp.float32(sign * 2.0 * np.pi / n)
    q = jax.lax.iota(jnp.int32, m // block)[:, None].astype(jnp.float32) \
        * (block * scale)
    r = jax.lax.iota(jnp.int32, block)[None, :].astype(jnp.float32) * scale
    w = (jax.lax.complex(jnp.cos(q), jnp.sin(q))
         * jax.lax.complex(jnp.cos(r), jnp.sin(r)))
    return w.reshape(m)


def _twiddle(n1: int, n2: int, inverse: bool) -> jnp.ndarray:
    """w[j1, j2] = exp(+-2*pi*i*j1*j2/n), generated inside the trace.

    Materializing this as a host-side constant would bake an n-element
    complex64 literal into the compiled program (512 MB at n = 2^26), so the
    table is built from iota on device.  The phase j1*j2/n is reduced mod 1
    with *integer* arithmetic first — j1*j2 < n fits int32 exactly.
    """
    n = n1 * n2
    sign = 1.0 if inverse else -1.0
    j1 = jax.lax.iota(jnp.int32, n1)[:, None]
    block = 256
    if n2 % block or n2 < block:
        j2 = jax.lax.iota(jnp.int32, n2)[None, :]
        r = (j1 * j2) % n                  # exact, < n
        return _phase_exp(r, n, sign)
    # Factored form: j2 = block*q + s, so w[j1, j2] = A[j1, q] * C[j1, s]
    # with A = exp(i*sign*2*pi*j1*q*block/n), C = exp(.. j1*s/n).  Same
    # exact-integer-residue precision (both arguments go through
    # _phase_exp's hi/lo split), but n1*n2/block + n1*block
    # transcendentals instead of n — the per-element cost collapses to
    # one complex multiply (same trick as _iota_phase, extended to the
    # outer-product index j1*j2).
    q = jax.lax.iota(jnp.int32, n2 // block)[None, :]
    s = jax.lax.iota(jnp.int32, block)[None, :]
    a = _phase_exp((j1 * (q * block)) % n, n, sign)   # [n1, n2/block]
    c = _phase_exp((j1 * s) % n, n, sign)             # [n1, block]
    return (a[:, :, None] * c[:, None, :]).reshape(n1, n2)


def _split_factor(n: int) -> int:
    """n1 of the four-step split n = n1 * n2 (n a power of two).

    Where the other leg then fits XLA's own transform (n / 128 at most
    ``_XLA_FFT_LEN_CAP``: rows of 2^17 to 2^23 points) n1 = 128: one
    DFT-matrix pass on the MXU and one direct transform.  The balanced
    split of 2^18 is 512 x 512, and XLA transforms 512 points as
    128 x 4 with the 4 in the minor dimension, twice.  Measured on a
    v5e on the 2^29 points of a 2^30-sample segment, in blocks of 64
    rows of 2^18 (PERF.md section 6, PR 40; ms a segment, forward in
    stage (a) / backward in stage (c)): 128 x 2048 **234.6 / 343.8**,
    2048 x 128 228.1 / 405.3, 512 x 512 337.5 / 476.0, 2^14 x 16
    267.6 / 623.6, 16 x 2^14 300.2 / 583.5, 4 x 2^16 319.6 / 1027.8.
    Longer transforms keep n1 ~ sqrt(n)."""
    log2n = n.bit_length() - 1
    if 128 < n // 128 <= _XLA_FFT_LEN_CAP:
        return 128
    return 1 << (log2n // 2)


# Longest 1-D (possibly batched) FFT handed to XLA's TPU FFT directly.
# Measured on a v5e: batched rows of 2^17+ decompose internally to a
# [..., 128, 128, 8] form whose minor dim pads 8 -> 128 lanes, a 16x HBM
# blowup that OOMs the chip at pipeline sizes (e.g. waterfall
# [2048, 2^17] wants 2x16 GB of scratch); 2^16 and below tile cleanly.
# Default for the ``len_cap`` parameter below — a constant, never
# mutated: callers that need a different cap (tiny-shape multichip
# dryruns forcing the in-shard recursion; future hardware A/Bs) pass it
# explicitly / via Config.fft_len_cap.
_XLA_FFT_LEN_CAP = 1 << 16
# ... and the longest FORWARD row handed to it where the rows are a block
# of a loop (a few dozen rows, not a plane): the 8x-padded [..., 128,
# 128, 16] form of 2^18 points is then a block's size.  Stage (a) of the
# staged plan in blocks of 64 rows of 2^18 points, v5e: 207.8 ms a
# segment direct against 234.6 through the 128 x 2048 four-step (the
# backward transform of stage (c) reads the other way, 441.4 against
# 343.8, and keeps the four-step; PERF.md section 6, PR 40)
_XLA_FFT_BLOCK_LEN_CAP = 1 << 18


def _fft_minor(x: jnp.ndarray, inverse: bool,
               rows_impl: str = "xla",
               len_cap: int | None = None) -> jnp.ndarray:
    """FFT along the minor (last) axis, recursing into the four-step
    decomposition for lengths XLA's TPU FFT handles badly.

    ``rows_impl``: "xla" | "pallas" | "pallas_interpret" — who executes
    the batched row transforms.  "pallas" runs rows that fit VMEM through
    ops/pallas_fft (one HBM read+write per point, MXU DFT-matmul stages);
    out-of-range rows fall back to XLA.

    ``len_cap``: longest row length handed to XLA's FFT directly
    (default _XLA_FFT_LEN_CAP); longer rows recurse into four_step_fft.
    """
    length = x.shape[-1]
    if length > (len_cap or _XLA_FFT_LEN_CAP):
        return four_step_fft(x, inverse, rows_impl, len_cap)
    batch = 1
    for s in x.shape[:-1]:
        batch *= s
    if rows_impl != "xla":
        from srtb_tpu.ops import pallas_fft as _pf
        if _pf.supported(length, batch):
            return _pf.fft_rows(x, inverse,
                                interpret=rows_impl == "pallas_interpret")
    # flatten batch dims: a major-dims-only reshape is free, and the TPU
    # FFT planner is only ever handed the one proven [batch, L] form
    # (a [2, 16384, 16384] batched FFT SIGSEGVed the XLA TPU compiler
    # where [32768, 16384] compiles fine)
    x2 = x.reshape(batch, length) if x.ndim > 2 else x
    if inverse:
        y = jnp.fft.ifft(x2, axis=-1, norm="forward")
    else:
        y = jnp.fft.fft(x2, axis=-1)
    return y.reshape(x.shape) if x.ndim > 2 else y


def four_step_stage1(x: jnp.ndarray, inverse: bool = False,
                     rows_impl: str = "xla",
                     len_cap: int | None = None) -> jnp.ndarray:
    """First half of the four-step FFT: [..., n] -> A[..., n2, k1].

    Splitting the decomposition in two lets very large segments run the
    two halves as *separate XLA programs* (pipeline/segment.py staged
    mode), freeing each program's transpose/FFT scratch before the next
    starts — the difference between fitting and OOMing a 2^30-sample
    segment in 16 GB of HBM.
    """
    n = x.shape[-1]
    if n & (n - 1):
        raise ValueError("four_step_fft requires power-of-two length")
    n1 = _split_factor(n)
    n2 = n // n1
    # view as [n1, n2] row-major: x[j1*n2 + j2]
    a = x.reshape(*x.shape[:-1], n1, n2)
    # step 1: FFT_n1 over j1 for each j2 — transpose so n1 is minor
    a = jnp.swapaxes(a, -1, -2)            # [j2, j1]
    return _fft_minor(a, inverse, rows_impl, len_cap)   # A[j2, k1]


def four_step_stage2(a: jnp.ndarray, inverse: bool = False,
                     rows_impl: str = "xla",
                     len_cap: int | None = None) -> jnp.ndarray:
    """Second half of the four-step FFT: A[..., n2, k1] -> X[..., n]."""
    n2, n1 = a.shape[-2], a.shape[-1]
    n = n1 * n2
    # step 2: twiddle w[j2, k1] = exp(-+2*pi*i*j2*k1/n); generated from
    # iota inside the trace (fuses into the multiply, nothing materialized)
    a = a * _twiddle(n2, n1, inverse)
    # step 3: FFT_n2 over j2 for each k1 — transpose so n2 is minor
    a = jnp.swapaxes(a, -1, -2)            # [k1, j2]
    a = _fft_minor(a, inverse, rows_impl, len_cap)      # C[k1, k2]
    # result index k = k2*n1 + k1 -> [k2, k1] then flatten
    a = jnp.swapaxes(a, -1, -2)
    return a.reshape(*a.shape[:-2], n)


def four_step_fft(x: jnp.ndarray, inverse: bool = False,
                  rows_impl: str = "xla",
                  len_cap: int | None = None) -> jnp.ndarray:
    """1-D C2C FFT of power-of-two length via the four-step algorithm.
    Unnormalized in both directions (matching c2c_forward / c2c_backward).
    Leading dims batch.

    Every sub-FFT runs along the *minor* axis with explicit transposes
    between steps — XLA's TPU FFT on a non-minor axis (and any row
    length > 2^16, see _XLA_FFT_LEN_CAP) triggers internal padded
    reshapes that are both slow and HBM-hungry, so the decomposition
    keeps the layout work visible: transpose -> batched FFT -> twiddle ->
    transpose -> batched FFT -> transpose, all row lengths <= 2^16.
    """
    return four_step_stage2(four_step_stage1(x, inverse, rows_impl,
                                             len_cap),
                            inverse, rows_impl, len_cap)


@S.scoped(S.FFT_R2C)
def rfft_via_c2c(x: jnp.ndarray, use_four_step: bool = False,
                 drop_nyquist: bool = False,
                 len_cap: int | None = None,
                 epilogue=None, premul=None) -> jnp.ndarray:
    """R2C FFT of 2m reals via one m-point C2C plus Hermitian post-process,
    returning m+1 bins (like rfft), or exactly m bins with
    ``drop_nyquist`` (the pipeline convention, ref: fft_pipe.hpp:75-77).
    This is the half-size C2C trick the reference implements in
    fft/fft_1d_r2c_post_process.hpp:33-82 and naive_fft.hpp:219-261;
    combined with four_step_fft it covers segment sizes beyond what a
    monolithic XLA R2C handles.

    ``drop_nyquist`` is not just a convenience: at segment sizes the
    m+1-bin form concatenates edge bins onto three 2m-byte arrays, and
    those odd-length copies put the peak HBM of a 2^30-sample compile
    over a v5e's capacity.  The m-bin form keeps every array exactly
    length m: F[(m-k) mod m] is a flip + roll that XLA fuses into the
    elementwise Hermitian combine."""
    z = pack_even_odd(x)
    zf = four_step_fft(z, len_cap=len_cap) if use_four_step \
        else jnp.fft.fft(z)
    return hermitian_rfft_post(zf, drop_nyquist, epilogue=epilogue,
                               premul=premul)


@S.scoped(S.FFT_R2C)
def pack_even_odd(x: jnp.ndarray) -> jnp.ndarray:
    """Pack 2m reals into m complex (even -> re, odd -> im) for the
    half-size C2C trick.  NOT x.reshape(m, 2): a materialized [m, 2] f32
    pads its minor dim 2 -> 128 lanes on TPU (T(8,128) layout), a 64x HBM
    blowup that OOMs compiles at segment sizes (observed: 128 GB scratch
    for n = 2^29).  Slicing even/odd lanes out of 256-lane rows keeps
    every intermediate lane-dense."""
    n = x.shape[-1]
    if n % 2:
        raise ValueError("even length required")
    m = n // 2
    if n % 256 == 0:
        x2 = x.reshape(*x.shape[:-1], n // 256, 256)
        re = x2[..., 0::2].reshape(*x.shape[:-1], m)
        im = x2[..., 1::2].reshape(*x.shape[:-1], m)
    else:  # tiny inputs (tests); layout padding is harmless here
        x2 = x.reshape(*x.shape[:-1], m, 2)
        re, im = x2[..., 0], x2[..., 1]
    return jax.lax.complex(re, im)


@S.scoped(S.FFT_R2C)
def hermitian_rfft_post(zf: jnp.ndarray,
                        drop_nyquist: bool = False,
                        epilogue=None,
                        premul=None) -> jnp.ndarray:
    """Hermitian post-process of the packed half-size C2C: F[m] -> X of
    the 2m-real rfft (ref: fft/fft_1d_r2c_post_process.hpp:33-82).
    X[k] = F[k] + conj(F[m-k]) pieces; the m-k indexing is a reverse +
    shift, written as flip/roll/concat (not a gather, which TPUs handle
    poorly at this size).

    ``epilogue``: optional ``f(zf, spec) -> spec`` applied to the
    assembled spectrum *inside the same elementwise producer*, so XLA
    writes the post-processed spectrum exactly once — the hook the
    fused spectrum tail (RFI s1 + chirp, pipeline/segment.py) hangs
    off.  ``zf`` is passed along so the epilogue can evaluate global
    reductions (the RFI mean power, via ``rfi.mean_power_packed``)
    against the FFT's already-materialized input instead of re-reading
    the spectrum.

    ``premul``: optional ``(c, cw)`` complex arrays [.., m] implementing
    the chirp·twiddle precombination: the output becomes
    ``c·even + cw·odd`` where ``cw = c·w`` was combined with the
    Hermitian twiddle ahead of time — the chirp multiply costs no extra
    pass and no in-trace trig when a chirp bank exists.  Requires
    ``drop_nyquist`` (the pipeline convention; the m+1-bin form has no
    precombined bank).
    """
    m = zf.shape[-1]
    n = 2 * m
    if drop_nyquist:
        f_k = zf                                           # k in [0, m)
        # [(m-0)%m, m-1, ..., 1] = roll(flip(zf), 1)
        f_mk = jnp.conj(jnp.roll(jnp.flip(zf, axis=-1), 1, axis=-1))
        w = None if premul is not None else _iota_phase(m, n, -1.0)
    else:
        if premul is not None:
            raise ValueError("premul requires drop_nyquist=True")
        f_k = jnp.concatenate([zf, zf[..., :1]], axis=-1)  # F[m] = F[0]
        rev = jnp.flip(zf, axis=-1)                        # [m-1, ..., 0]
        f_mk = jnp.conj(jnp.concatenate([zf[..., :1], rev], axis=-1))
        # w[k] = exp(-2*pi*i*k/n), k in [0, m] — exact hi/lo phase split
        # (avoids both a baked constant and f32 rounding of k)
        w = _phase_exp(jax.lax.iota(jnp.int32, m + 1), n, -1.0)
    even = 0.5 * (f_k + f_mk)
    odd = -0.5j * (f_k - f_mk)
    if premul is not None:
        c, cw = premul
        out = c * even + cw * odd
    else:
        out = even + w * odd
    if epilogue is not None:
        out = epilogue(zf, out)
    return out


def window_planes(window: np.ndarray, count: int) -> np.ndarray:
    """A sample-order window [n] dealt out to ``count`` planes [count,
    n / count], plane j = coefficients j, j + count, ... (host-side
    numpy: the strided reshape would be a pathological layout on
    device)."""
    return np.ascontiguousarray(np.asarray(window).reshape(-1, count).T)


def subbyte_window_planes(window: np.ndarray, nbits: int) -> np.ndarray:
    """:func:`window_planes` matching the blocked field planes of
    `unpack_subbyte_planes`."""
    return window_planes(window, 8 // nbits)


@S.scoped(S.FFT_R2C)
def rfft_subbyte(data: jnp.ndarray, nbits: int, strategy: str = "four_step",
                 window_planes: jnp.ndarray | None = None,
                 drop_nyquist: bool = True,
                 len_cap: int | None = None,
                 epilogue=None, premul=None) -> jnp.ndarray:
    """Fused unpack + even/odd pack + R2C for 1/2/4-bit baseband bytes,
    with every intermediate lane-dense.

    The sample-order composition (unpack -> pack_even_odd -> C2C) forces
    a [bytes, count]-shaped interleave whose TPU layout pads count -> 128
    lanes — materialized, that is a 16 GB copy at n = 2^27 (measured).
    This path never builds sample order at all:

    - `unpack_subbyte_planes` emits blocked field planes [count, M]
      (plane k = field k of every byte, sample count*b + k);
    - with count even, even-indexed samples are exactly the even field
      planes, so the packed half-size sequence z[t] = x[2t] + i*x[2t+1]
      is plane pairs: z[p*b + k'] = planes[2k'][b] + i*planes[2k'+1][b]
      — a [p, M] complex array, p = count/2, no interleave;
    - z is blocked over p planes, i.e. already in the [j2, j1] layout the
      four-step uses *after* its first transpose: FFT_M each plane, then
      the twiddle exp(-2pi*i*j2*k1/m) and a p-point cross-plane butterfly
      finish the m = p*M transform, and the [p(k2), M(k1)] result *is*
      natural order flattened — the blocked->natural permutation has been
      absorbed into the decimation for free;
    - Hermitian post-process as usual (ref fft_1d_r2c_post_process.hpp).

    ``window_planes``: optional [count, M] from `subbyte_window_planes`.
    ``strategy``: "four_step" (XLA batched FFTs) or "mxu" (DFT-matmul
    stages) for the M-point plane FFTs.
    """
    from srtb_tpu.ops import unpack as _U
    count = 8 // nbits
    if count < 2:
        raise ValueError("rfft_subbyte requires 1/2/4-bit input")
    planes = _U.unpack_subbyte_planes(data, nbits)        # [..., count, M]
    if window_planes is not None:
        planes = planes * window_planes
    z = subbyte_planes_to_packed(planes)
    if strategy == "mxu":
        from srtb_tpu.ops.mxu_fft import mxu_fft
        a = mxu_fft(z)                                    # [..., p, M]
    elif strategy == "monolithic":
        a = jnp.fft.fft(z, axis=-1)  # one batched XLA FFT over the planes
    elif strategy in ("pallas", "pallas_interpret"):
        a = _fft_minor(z, inverse=False, rows_impl=strategy,
                       len_cap=len_cap)
    elif strategy in ("pallas2", "pallas2_interpret"):
        a = _pallas2_or_fallback(z, strategy, len_cap)
    else:
        a = _fft_minor(z, inverse=False, len_cap=len_cap)
    return finish_rfft_subbyte(a, drop_nyquist, epilogue=epilogue,
                               premul=premul)


def own_tail_shape(n: int, nbits: int):
    """(p, n1, n2) where the repo's own transform takes a segment of n
    samples whole, Hermitian post included (ops/pallas_fft2: the
    column-native passes and :func:`~srtb_tpu.ops.pallas_fft2.post_spectrum`),
    else None: p pairs of planes of n / (2p) = n1 * n2 points each, the
    post pass joining the pairs.  Sub-byte samples go as the p = 4/nbits
    pairs of blocked field planes they unpack to (``unpack_subbyte_planes``).
    Whole bytes go as one packed transform of n/2 points where that
    length has a leg pair (2^25 to 2^27 samples), else as two pairs of
    n/4 (:func:`deal_planes`: every fourth sample a plane; 2^28 samples,
    legs 8192 x 8192, where one transform would need a leg of 2^14)."""
    from srtb_tpu.ops import pallas_fft2 as pf2
    nbits = abs(nbits)
    if nbits not in (2, 4, 8):
        return None
    for p in (4 // nbits,) if nbits < 8 else (1, 2):
        if n % (2 * p):
            continue
        fac = pf2.cols_factor(n // (2 * p))
        if fac is not None and pf2.post_supported(p, *fac):
            return (p, *fac)
    return None


# Lanes of a row of the deal-out (:func:`deal_planes`)
_DEAL_LANES = 128


@S.scoped(S.FFT_R2C)
def deal_planes(x: jnp.ndarray, count: int) -> tuple:
    """Samples in order ``[..., n]`` (the own plan hands in the bytes
    of one-byte samples and casts the planes) dealt out to ``count``
    planes ``[..., n / count]``, plane j = samples j, j + count, ...:
    what the blocked field planes of a sub-byte unpack are, for whole
    bytes.  With count 2 the planes are the real and imaginary part of
    :func:`pack_even_odd`; with count 4 they are two such pairs, z[2b +
    p'] = x[4b + 2p'] + i x[4b + 2p' + 1], which the post pass joins
    (``own_tail_shape``).  Lane-dense as there: every ``count``-th lane
    of rows ``count`` x 128 wide, never ``reshape(-1, count)`` (a minor
    dimension of 4 pads to 128 lanes on the chip).  ``rows[..., j::count]``
    lowers to a gather, as in ``ops/unpack.stream_bytes``: on a v5e, 2^28
    bytes to four float planes with the cast read 10.5 ms so and 65.6 ms
    as a strided ``lax.slice`` (the 1 GSa/s ring program whole 93.5
    against 148.3 ms), the same on the cast floats 27.4 and 73.2,
    which is why samples wider than a byte stay with XLA's plan
    (PERF.md section 6, PR 48)."""
    n = x.shape[-1]
    row = count * _DEAL_LANES
    if n % row:
        raise ValueError(f"{n} samples do not deal out in rows of {row}")
    rows = x.reshape(*x.shape[:-1], n // row, row)
    return tuple(rows[..., j::count].reshape(*x.shape[:-1], n // count)
                 for j in range(count))


@S.scoped(S.FFT_R2C)
def own_spectrum(planes: jnp.ndarray, legs: tuple, bank: jnp.ndarray, *,
                 threshold: float, norm: float, bins,
                 interpret: bool = False) -> jnp.ndarray:
    """Planes ``[2p, ..., M]`` of M = n1 * n2 points, ``legs`` (n1, n2)
    of :func:`own_tail_shape` (transform b's real part in plane 2b, its
    imaginary part in 2b+1) -> the dedispersed, zapped spectrum
    ``[p*M]`` complex: two kernel passes and the post pass, no XLA FFT
    and no spectrum-sized XLA pass but RFI s1's mean power.
    ``bank``: ``pallas_fft2.post_bank``'s; ``threshold`` and ``norm`` of
    RFI s1, ``bins`` of the manual zap."""
    from srtb_tpu.ops import pallas_fft2 as pf2
    p = planes.shape[0] // 2
    n1, n2 = legs
    y_re, y_im = pf2.fft2_cols_planes(planes.reshape(2 * p, n1, n2),
                                      interpret=interpret)
    s_re, s_im = pf2.post_spectrum(
        y_re.reshape(p, n2, n1), y_im.reshape(p, n2, n1), bank,
        threshold=threshold, norm=norm, bins=bins, interpret=interpret)
    return jax.lax.complex(s_re, s_im)


# Points from which "pallas2" is the column-native passes or nothing
_PALLAS2_MIN_POINTS = 1 << 24


def _pallas2_or_fallback(z: jnp.ndarray, strategy: str,
                         len_cap: int | None = None) -> jnp.ndarray:
    """The two-pass Pallas C2C (ops/pallas_fft2) on [..., L] complex z:
    the column-native passes at the lengths they take (2^24 to 2^26),
    and the four-step-with-Pallas-legs form below 2^24 (tiny test
    configs).  Longer transforms are an error on every backend: no
    kernel here factors them (Mosaic refused the one that did for a
    v5e; PERF.md section 6, PRs 43 and 50).  A served plan never asks
    for them: its own transform takes 2^28 samples as two transforms of
    2^26 points, ``own_tail_shape``."""
    from srtb_tpu.ops import pallas_fft2 as pf2
    interp = strategy.endswith("interpret")
    length = z.shape[-1]
    if pf2.cols_factor(length) is not None:
        return pf2.fft2_cols(z, inverse=False, interpret=interp)
    if length >= _PALLAS2_MIN_POINTS:
        raise ValueError(
            f"fft_strategy pallas2 has no transform of {length} points: "
            f"the column-native passes take 2^24 to 2^26 points.  A "
            f"served plan splits a longer segment into plane pairs of "
            f"2^26 points (ops/fft.own_tail_shape)")
    return _fft_minor(z, inverse=False,
                      rows_impl="pallas_interpret" if interp else "pallas",
                      len_cap=len_cap)


@S.scoped(S.FFT_R2C)
def subbyte_planes_to_packed(planes: jnp.ndarray) -> jnp.ndarray:
    """Blocked field planes [..., count, M] -> packed complex plane pairs
    z[..., p, M] (p = count/2): z[p*b + k'] = x[2t] + i*x[2t+1] of the
    sample-order sequence, held blocked."""
    return jax.lax.complex(planes[..., 0::2, :], planes[..., 1::2, :])


@S.scoped(S.FFT_R2C)
def finish_rfft_subbyte(a: jnp.ndarray,
                        drop_nyquist: bool = True,
                        epilogue=None, premul=None) -> jnp.ndarray:
    """Finish `rfft_subbyte` from the per-plane FFTs a[..., p, M]:
    twiddle + p-point cross-plane butterfly + Hermitian post-process.
    Split out so the staged execution plan (pipeline/segment.py) can run
    the plane FFTs and the finish in separate XLA programs."""
    p, m_bytes = a.shape[-2], a.shape[-1]
    m = p * m_bytes
    if p > 1:
        # w[j2, k1] = exp(-2*pi*i*j2*k1/m) is _twiddle(p, M) exactly —
        # reuse its factored form (m/256 + 256 transcendentals per row
        # instead of 4 per point on this hot path)
        a = a * _twiddle(p, m_bytes, inverse=False)
        # p-point DFT across the plane axis (p <= 4: a handful of
        # complex-scalar multiply-adds, fused elementwise by XLA)
        wp = np.exp(-2j * np.pi * np.outer(np.arange(p), np.arange(p))
                    / p).astype(np.complex64)
        rows = [sum(complex(wp[k2, j]) * a[..., j, :] for j in range(p))
                for k2 in range(p)]
        a = jnp.stack(rows, axis=-2)
    zf = a.reshape(*a.shape[:-2], m)
    return hermitian_rfft_post(zf, drop_nyquist, epilogue=epilogue,
                               premul=premul)


# Packed C2C length (= n/2) above which the segment R2C is the four-step
# path of the staged plan: the monolithic XLA R2C of a 2^30-sample
# segment does not compile for a v5e (PERF.md section 4).
LARGE_FFT_THRESHOLD = 1 << 28

# Where a v5e has read the repo's own transform faster than XLA's,
# parent beside change, in a cell, and what the chip held at its peak
# there (``device.peak_hbm_gb``: the banks, two segments in flight,
# the largest program's temporaries).  The key is what was read and no
# more: the samples' bits, the streams, the shape
# (:func:`own_tail_shape`).  ``auto`` gives the own transform at these
# keys alone, and only on a chip whose ``bytes_limit`` holds the
# reading; everywhere else XLA's plan, which holds less (9.76 GB where
# the last row reads 12.02).
# - 2-bit samples, 2^27 a stream, two pairs of blocked planes through
#   legs 4096 x 8192, 2^11 channels: one stream busy 65.28 -> 33.93 ms
#   a segment, two 134.88 -> 74.76 (PERF.md section 5, PR 43);
# - 8-bit samples, 2^28 of them dealt out to two plane pairs
#   (:func:`deal_planes` on the bytes) through legs 8192 x 8192, 2^15
#   channels, the 1 GSa/s segment: busy 157.51 -> 89.27 ms,
#   ``rt_factor`` 1.238 -> 1.852 (PERF.md section 5, PR 48).  The
#   programs hold 9.53 GB of it with two in flight and the chirp bank
#   1.07 (``tests/test_tpu_compile.py``).
# The other shapes the kernels take (whole bytes and 4-bit samples
# through one plane pair, 2^25 to 2^27 samples; 2-bit 2^25, 2^26 and
# 2^28; 8-bit 2^28 in two streams) compile for a described v5e or run
# the same kernels and no chip has run them: ``auto`` leaves them to
# XLA until one has.
OWN_R2C_READ_FASTER = {
    # (bits, streams, p, n1, n2): bytes on the chip at the peak
    (2, 1, 2, 4096, 8192): 4_510_000_000,     # ledger, PR 45: 4.5099
    (2, 2, 2, 4096, 8192): 7_196_000_000,     # ledger, PR 45: 7.1957
    (8, 1, 2, 8192, 8192): 12_017_000_000,    # my chip runs, PR 48
}


def resolve_strategy(n: int, strategy: str, bits: int = 8,
                     streams: int = 1, on_tpu: bool = False,
                     bytes_limit: int | None = None,
                     own_plan: bool = True) -> str:
    """Resolve "auto" to a concrete segment-R2C strategy for segments of
    n samples of ``bits`` bits in ``streams`` streams.

    Above ``LARGE_FFT_THRESHOLD`` "four_step" (the staged plan).  Below
    it the repo's own transform, "pallas2" with the tail in its post
    pass, where all of this holds: the backend is a TPU (``on_tpu``),
    the caller's plan runs that transform whole when it is given
    "pallas2" (``own_plan``: ``pipeline/segment.own_r2c_hostable``), a
    chip has read these bits, streams and shape faster
    (``OWN_R2C_READ_FASTER``: 2^27 2-bit samples a stream in one or two
    streams, and 2^28 samples of 8 bits in one, the 1 GSa/s segment)
    and the chip holds what that chip held at its peak (``bytes_limit``
    of ``memory_stats``; None where the platform reports none).  Else
    "monolithic", XLA's own R2C: off the chip, on a chip too small, and
    wherever the kernel has not been read faster (PERF.md section 6,
    PRs 43 and 48, has the tables)."""
    if strategy != "auto":
        return strategy
    if n // 2 > LARGE_FFT_THRESHOLD:
        return "four_step"
    shape = own_tail_shape(n, bits) if on_tpu and own_plan else None
    held = shape and OWN_R2C_READ_FASTER.get((abs(bits), streams, *shape))
    if held and (not bytes_limit or held <= bytes_limit):
        return "pallas2"
    return "monolithic"


@S.scoped(S.FFT_R2C)
def segment_rfft(x: jnp.ndarray, strategy: str = "auto",
                 len_cap: int | None = None,
                 epilogue=None, premul=None) -> jnp.ndarray:
    """The segment-sized R2C with the drop-Nyquist convention.

    ``epilogue``/``premul`` fold elementwise spectrum work into the
    final (Hermitian post-process) pass — see
    :func:`hermitian_rfft_post`.  The monolithic strategy cannot host
    them (the spectrum is produced inside XLA's R2C custom call) and
    raises rather than silently running unfused.

    strategy (what a v5e read for each at 2^27 samples is the table of
    PERF.md section 6, PR 43):
    - "auto": here, with no plan around it, "monolithic" below the
      staged plan's sizes and "four_step" from there; a served plan
      resolves it before it calls (:func:`resolve_strategy`);
    - "monolithic": one XLA R2C op;
    - "four_step": half-size packed C2C via the Bailey decomposition +
      Hermitian post-process — two large *batched* XLA FFTs instead of
      one huge 1-D FFT;
    - "mxu": the packed C2C executed as radix-128 DFT-matrix matmuls on
      the systolic array (ops/mxu_fft.py);
    - "pallas" ("pallas_interpret" off-TPU): the four-step decomposition
      with its batched row FFTs executed by the VMEM Pallas kernel
      (ops/pallas_fft) — one HBM read+write per point per leg;
    - "pallas2" ("pallas2_interpret" off-TPU): two kernel passes for the
      whole C2C and no XLA FFT op anywhere (ops/pallas_fft2), the
      Hermitian post in XLA; a served plan that gives the post to a
      third kernel pass with RFI s1 and the chirp in it calls
      :func:`own_spectrum` and not this function.
    """
    strategy = resolve_strategy(x.shape[-1], strategy)
    if strategy == "monolithic" and (epilogue is not None
                                     or premul is not None):
        raise ValueError(
            "the monolithic XLA R2C cannot host a spectrum epilogue")
    if strategy in ("pallas2", "pallas2_interpret"):
        zf = _pallas2_or_fallback(pack_even_odd(x), strategy, len_cap)
        return hermitian_rfft_post(zf, drop_nyquist=True,
                                   epilogue=epilogue, premul=premul)
    if strategy in ("pallas", "pallas_interpret"):
        z = pack_even_odd(x)
        zf = four_step_fft(z, rows_impl=strategy, len_cap=len_cap)
        return hermitian_rfft_post(zf, drop_nyquist=True,
                                   epilogue=epilogue, premul=premul)
    if strategy == "four_step":
        return rfft_via_c2c(x, use_four_step=True, drop_nyquist=True,
                            len_cap=len_cap, epilogue=epilogue,
                            premul=premul)
    if strategy == "mxu":
        from srtb_tpu.ops.mxu_fft import mxu_fft
        z = pack_even_odd(x)
        return hermitian_rfft_post(mxu_fft(z), drop_nyquist=True,
                                   epilogue=epilogue, premul=premul)
    if strategy == "monolithic":
        return rfft_drop_nyquist(x)
    raise ValueError(f"unknown fft strategy {strategy!r}")


# Points a block of the staged plan's loops holds (stage (b), stage (c))
# and twice that (stage (a)): measured on a v5e on the 2^29 points of a
# 2^30-sample segment, boundary [2, 1, 2048, 2^18] (PERF.md section 6,
# PR 40; ms a segment by points a block, 2^24 / 2^23 / 2^22 / 2^21 /
# 2^20): stage (a) 207.9 / **191.0** / 229.5 / 361.7 / 816.5; stage (b)
# 210.9 / 179.8 / **134.9** / 153.9 / 343.5 (its column blocks; 2^22:
# 2048 columns); stage (c) 343.9 / 233.9 / **176.4** / 197.4 / 275.3.
# A block of 2^22 points is 32 MB as (re, im) float32.
BLOCK_POINTS = 1 << 22


def block_count(extent: int, points: int, block_points: int | None = None,
                pairs: bool = False, unit: int = 1) -> int:
    """How many blocks a loop walks ``extent`` rows (or columns) of an
    array of ``points`` points in: about ``block_points`` a block
    (``BLOCK_POINTS`` where none is given), and
    at least eight where the extent allows (a small shape still takes
    the loop its big relative takes); the blocks divide the extent,
    each a multiple of ``unit`` (the vector lanes, for columns), an even
    number of them where the loop takes them in ``pairs``.  0 where the
    extent cannot be walked so (one row, for pairs): the caller keeps
    the whole-plane spelling."""
    blocks = min(max(8, points // (block_points or BLOCK_POINTS)),
                 extent // unit)
    while blocks > 1 and (extent % blocks or (extent // blocks) % unit
                          or (pairs and blocks % 2)):
        blocks -= 1
    return blocks if blocks >= (2 if pairs else 1) else 0


@S.scoped(S.FFT_R2C)
def hermitian_rfft_post_rows(z_ri: jnp.ndarray, blocks: int) -> jnp.ndarray:
    """:func:`hermitian_rfft_post` (drop-Nyquist form) over mirrored
    pairs of row blocks, in place.

    ``z_ri`` is the packed half-size C2C's output as stacked (re, im)
    float32 ``[2, ..., R, C]``, bin ``k = r*C + c``; the result is the
    spectrum in the same shape.  Bin ``k`` needs ``F[k]`` and
    ``F[(m-k) mod m]``, and ``m - k = (R-1-r)*C + (C-c)`` for ``c >= 1``:
    row ``r``'s partner is row ``R-1-r`` reversed and rolled by one
    along its own axis; only column 0 reaches into another row,
    ``(R-r) mod R``: the next row of the partner block, and for a
    block's first row the first row of the block behind the partner,
    one number kept from the iteration before.  So block ``p`` of
    ``R/blocks`` rows and block ``blocks-1-p`` are read, combined and
    written back together, one pair an iteration: what is alive beside
    the carried buffer is a few blocks, where the whole-plane spelling
    holds the reversed, rolled and negated planes at once (six planes of
    2 GB at 2^30 samples: the program was refused by 258 MB).  The
    arithmetic is the whole-plane function's; the twiddle
    ``exp(-2*pi*i*k/n)`` is the product of a row factor
    ``exp(-2*pi*i*r*C/n)`` and a column table (both with exact integer
    arguments, as :func:`_iota_phase`)."""
    rows, cols = z_ri.shape[-2], z_ri.shape[-1]
    m = rows * cols
    n = 2 * m
    if blocks < 2 or blocks % 2 or rows % blocks:
        raise ValueError(f"{rows} rows do not pair into {blocks} blocks")
    rb = rows // blocks
    axis_r = z_ri.ndim - 2
    v = _iota_phase(cols, n, -1.0)
    v_re, v_im = jnp.real(v), jnp.imag(v)
    col0 = jax.lax.iota(jnp.int32, cols) == 0

    def half(x, y, fix, r0):
        """The spectrum of the rows ``r0 .. r0+rb`` from their own block
        ``x``, their partner block ``y`` and their column-0 partners."""
        y = jnp.roll(jnp.flip(y, axis=(-2, -1)), 1, axis=-1)
        y = jnp.where(col0, fix[..., None], y)
        fm_re, fm_im = y[0], -y[1]                       # conj(F[m-k])
        even_re, even_im = 0.5 * (x[0] + fm_re), 0.5 * (x[1] + fm_im)
        # odd = -0.5j * (F[k] - conj(F[m-k]))
        odd_re, odd_im = 0.5 * (x[1] - fm_im), -0.5 * (x[0] - fm_re)
        r = (r0 + jax.lax.iota(jnp.int32, rb)) * cols      # exact, < m
        u = _phase_exp(r, n, -1.0)[:, None]
        w_re = jnp.real(u) * v_re - jnp.imag(u) * v_im
        w_im = jnp.real(u) * v_im + jnp.imag(u) * v_re
        return jnp.stack([even_re + (w_re * odd_re - w_im * odd_im),
                          even_im + (w_re * odd_im + w_im * odd_re)])

    def partners0(own0, first):
        """Column 0's partners of a block's rows: row ``i >= 1`` pairs
        with row ``rb - i`` of the partner block (``own0`` is that
        block's column 0), row 0 with the first row of the block behind
        the partner (``first``)."""
        rolled = jnp.roll(jnp.flip(own0, axis=-1), 1, axis=-1)
        return jnp.where(jax.lax.iota(jnp.int32, rb) == 0,
                         first[..., None], rolled)

    def body(p, carry):
        buf, behind = carry
        q = blocks - 1 - p
        r_p, r_q = p * rb, q * rb
        x = jax.lax.dynamic_slice_in_dim(buf, r_p, rb, axis_r)
        y = jax.lax.dynamic_slice_in_dim(buf, r_q, rb, axis_r)
        x0, y0 = x[..., 0], y[..., 0]
        # behind block q lies block q + 1, written an iteration ago: its
        # first row's F[., 0] was kept then (block 0's own where p = 0:
        # bin 0 pairs with itself); ahead of block p lies block p + 1,
        # still as the transform left it (the partner itself at the end)
        behind = jnp.where(p == 0, x0[..., 0], behind)
        ahead = jax.lax.dynamic_slice_in_dim(
            buf, r_p + rb, 1, axis_r)[..., 0, 0]
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, half(x, y, partners0(y0, behind), r_p), r_p, axis_r)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, half(y, x, partners0(x0, ahead), r_q), r_q, axis_r)
        return buf, y0[..., 0]

    buf, _ = jax.lax.fori_loop(
        0, blocks // 2, body, (z_ri, jnp.zeros(z_ri.shape[:-2], z_ri.dtype)))
    return buf


@S.scoped(S.FFT_R2C)
def four_step_stage2_cols(a_ri: jnp.ndarray, blocks: int,
                          len_cap: int | None = None) -> jnp.ndarray:
    """:func:`four_step_stage2` in place, over blocks of columns.

    ``a_ri`` is ``A[j2, k1]`` of the first half as stacked (re, im)
    float32 ``[2, ..., n2, n1]``; the result is ``X[k]`` in the same
    shape, ``k = k2*n1 + k1`` at ``[k2, k1]``.  Column ``k1`` of A is
    transformed along ``j2`` into column ``k1`` of the result, so a
    block of ``n1/blocks`` columns is read, twiddled, transposed,
    transformed, transposed back and written where it was read: beside
    the carried buffer a few blocks are alive, where the whole-plane
    spelling holds the twiddled, the transposed and the transformed
    plane (10.7 GB of temporaries at 2^29 points).  The twiddle
    ``exp(-2*pi*i*j2*k1/n)`` of a block is the product of its first
    column's (a vector) and a table of the offsets inside a block made
    once ahead of the loop, both from exact integer residues as
    :func:`_phase_exp` takes them."""
    n2, n1 = a_ri.shape[-2], a_ri.shape[-1]
    if n1 % blocks:
        raise ValueError(f"{n1} columns do not divide into {blocks} blocks")
    n = n1 * n2
    w = n1 // blocks
    axis_c = a_ri.ndim - 1
    j2 = jax.lax.iota(jnp.int32, n2)
    # exp(-2*pi*i*j2*kk/n) for the offsets kk inside a block
    inner = _phase_exp((j2[:, None] * jax.lax.iota(jnp.int32, w)[None, :])
                       % n, n, -1.0)

    def body(q, buf):
        x = jax.lax.dynamic_slice_in_dim(buf, q * w, w, axis_c)
        first = _phase_exp((j2 * (q * w)) % n, n, -1.0)[:, None]
        x = jax.lax.complex(x[0], x[1]) * (first * inner)
        y = _fft_minor(jnp.swapaxes(x, -1, -2), inverse=False,
                       len_cap=len_cap)                   # [.., w, k2]
        y = jnp.swapaxes(y, -1, -2)                       # [.., k2, w]
        return jax.lax.dynamic_update_slice_in_dim(
            buf, jnp.stack([jnp.real(y), jnp.imag(y)]), q * w, axis_c)

    return jax.lax.fori_loop(0, blocks, body, a_ri)


@S.scoped(S.FFT_R2C)
def four_step_stage1_cols(z_cols: jnp.ndarray,
                          len_cap: int | None = None) -> jnp.ndarray:
    """:func:`four_step_stage1` for a block of the columns of its
    ``[n1, n2]`` view: ``z_cols[..., j1, j]`` = ``x[j1*n2 + j2_0 + j]``
    -> ``A[j2_0 + j, k1]`` as ``[..., j, k1]``, the rows ``j2_0 ...`` of
    the whole transform's ``A[j2, k1]``.  Meant for a block of rows: a
    row goes to XLA's own transform up to ``_XLA_FFT_BLOCK_LEN_CAP``
    points where the caller sets no cap."""
    return _fft_minor(jnp.swapaxes(z_cols, -1, -2), inverse=False,
                      len_cap=len_cap or _XLA_FFT_BLOCK_LEN_CAP)

