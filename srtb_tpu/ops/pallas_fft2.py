"""The segment C2C in two Pallas kernel passes, and the Hermitian post
as a third: the repo's own R2C.

An XLA four-step is transpose, leg FFT, twiddle multiply, transpose,
leg FFT, transpose, each arrow a pass over HBM.  Here a transform of
``m = n1 * n2`` points viewed ``[n1, n2]`` is two passes
(:func:`fft2_cols_planes`):

  pass 1 (grid over blocks of 128 columns): the column FFT over j1 of a
    ``[n1, 128]`` block held in VMEM, then the four-step twiddle
    ``exp(-+2*pi*i*k1*j2/m)`` from a table by column times two factors
    made in the kernel from exact integer residues (no table of m
    entries exists anywhere), out ``B[k1, j2]``;

  pass 2 (grid over blocks of 128 rows): the block transposed in VMEM,
    the same column body without the twiddle, out ``[n2, n1]``, which
    read row-major is the natural order.

:func:`post_spectrum` is what follows them in the served plan, written
once: the cross-plane butterfly, the Hermitian post with the chirp and
chirp*twiddle banks, RFI s1's zap and normalisation, the manual zap.

No XLA FFT op appears anywhere in this path.  Like every FFT backend
here it is unnormalized in both directions and held to float64
(tests/test_pallas_fft2.py, in interpret mode at small legs); the TPU
answer to the reference's single-call vendor FFTs for full segments
(ref: fft/fft.hpp:54-160, fft_pipe.hpp:44-78).  A v5e reads 3.4 + 4.0 ms
for the two passes at 2^27 2-bit samples and 4.4 for the post (PERF.md
section 5, PR 43).  Legs are 4096 and 8192 (``_COL_LEGS``), so a
transform is 2^24 to 2^26 points; a longer segment goes as several
(``ops/fft.own_tail_shape``).  An earlier spelling of the two passes,
which factored up to 2^29 points and which Mosaic refused for a v5e at
every length only it took, went in PR 50 with the staged variants that
stood on it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from srtb_tpu.ops import pallas_fft as PF
from srtb_tpu.ops import scopes as S


def _phase_cos_sin(r, m: int, sign: float):
    """(cos, sin) of sign*2*pi*r/m for an int32 residue array r < m
    <= 2^29, via the hi/lo split so each cos/sin argument is f32-exact
    (the ops.fft._phase_exp discipline, in-register).  Single home of
    the split: pass 1's twiddle factors and the post pass's row factor
    (tests/test_pallas_fft2.py holds it to float64 at the largest
    residues a transform of 2^26 points makes)."""
    half = 1 << 15
    scale = jnp.float32(sign * 2.0 * np.pi / m)
    a = (r // half).astype(jnp.float32) * (half * scale)
    b = (r % half).astype(jnp.float32) * scale
    ca, sa = jnp.cos(a), jnp.sin(a)
    cb, sb = jnp.cos(b), jnp.sin(b)
    return ca * cb - sa * sb, sa * cb + ca * sb


# ==================================================================
# column-native passes (PR 43).  Both passes are ONE kernel body, a
# column FFT of a [L, 128]-lane block held in VMEM, L = R*C: level 1
# gathers the rows r*C + c of one c (a sublane-strided load) and
# multiplies them by a [2R, 2R] real matrix
# that is the stacked complex DFT_R with the level's twiddle
# exp(-+2*pi*i*kr*c/L) folded in on the host in float64; level 2
# gathers the rows of one kr, multiplies by the stacked DFT_C and
# stores them to the rows kc*R + kr.  The stacked form [[Wr, -Wi],
# [Wi, Wr]] @ [xr; xi] is one contraction of depth 2R = 128 where
# four real ones of depth 64 would each fill half the array.
# ==================================================================

# L -> (R, C): the legs a v5e's VMEM holds beside the double-buffered
# blocks (matrices 16*R*L bytes: 4 MB at 4096, 8 MB at 8192)
_COL_LEGS = {4096: (64, 64), 8192: (64, 128)}
_COLS_VMEM_BYTES = 100 << 20
_COLS_LANES = 128


# Iterations a loop's body holds (``_blocks_loop``)
_COLS_UNROLL = 4
_POST_UNROLL = 2


def _blocks_loop(count: int, unroll: int, body) -> None:
    """``body(i)`` for i < count as a loop whose body holds ``unroll``
    iterations: a body is traced and lowered once (fully unrolled, the
    kernels cost every first dispatch seconds of tracing), and with a
    few iterations in it the compiler overlaps one's loads and stores
    with another's contraction (2 x 2^25 points on a v5e, PERF.md
    section 6, PR 43: one a body reads 6.7 + 6.8 ms for the two passes,
    four 4.6 + 5.1, sixteen 4.0 + 4.7 behind first dispatches of
    3 + 9 s against 1 + 2)."""
    unroll = min(unroll, count)
    assert count % unroll == 0

    def block(i, carry):
        for u in range(unroll):
            body(i * unroll + u)
        return carry
    jax.lax.fori_loop(0, count // unroll, block, 0)


def cols_factor(m: int):
    """(n1, n2) of the column-native transform of m points, or None
    where a leg is outside ``_COL_LEGS``: m = 2^24, 2^25, 2^26."""
    for n1 in _COL_LEGS:
        if m % n1 == 0 and m // n1 in _COL_LEGS:
            return n1, m // n1
    return None


def _col_leg(length: int):
    """(R, C) of a leg: the production table, else (the CPU tests'
    small shapes) the balanced power-of-two split, both at least 8."""
    if length in _COL_LEGS:
        return _COL_LEGS[length]
    log2 = length.bit_length() - 1
    if length & (length - 1) or log2 < 6:
        raise ValueError(f"column leg {length} unsupported")
    return 1 << (log2 // 2), 1 << (log2 - log2 // 2)


def _stacked(g: np.ndarray) -> np.ndarray:
    """Complex [..., a, b] -> real [..., 2a, 2b], [[re, -im], [im, re]]."""
    return np.concatenate(
        [np.concatenate([g.real, -g.imag], axis=-1),
         np.concatenate([g.imag, g.real], axis=-1)], axis=-2
    ).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _col_leg_consts(length: int, r_len: int, c_len: int, inverse: bool):
    """Level 1's matrices [C, 2R, 2R] (DFT_R with the twiddle of column
    c folded in) and level 2's [2C, 2C], float64 rounded once."""
    sgn = 2j * np.pi * (1.0 if inverse else -1.0)
    kr = np.arange(r_len, dtype=np.float64)
    kc = np.arange(c_len, dtype=np.float64)
    w_r = np.exp(sgn * np.outer(kr, kr) / r_len)            # [kr, r]
    tw = np.exp(sgn * np.outer(kc, kr) / length)            # [c, kr]
    return (_stacked(tw[:, :, None] * w_r[None]),
            _stacked(np.exp(sgn * np.outer(kc, kc) / c_len)))


@functools.lru_cache(maxsize=None)
def _col_twiddle_table(n1: int, r_len: int, c_len: int, bb: int, m: int,
                       inverse: bool):
    """exp(-+2*pi*i*k1*d/m) for d < bb, rows in the order level 2 makes
    them (kr major, kc minor; k1 = kc*R + kr), float64 rounded once."""
    sgn = 2j * np.pi * (1.0 if inverse else -1.0)
    k1 = (np.arange(c_len)[None, :] * r_len
          + np.arange(r_len)[:, None]).reshape(n1).astype(np.float64)
    t = np.exp(sgn * np.outer(k1, np.arange(bb, dtype=np.float64)) / m)
    return t.real.astype(np.float32), t.imag.astype(np.float32)


def _cols_kernel(x_re_ref, x_im_ref, m1_ref, m2_ref, *rest, r_len, c_len,
                 bb, m, sign, twiddle, transpose_in):
    from jax.experimental import pallas as pl

    rest = list(rest)
    t_re_ref = t_im_ref = None
    if twiddle:
        t_re_ref, t_im_ref = rest[:2]
        rest = rest[2:]
    o_re_ref, o_im_ref, a_ref = rest[:3]
    src_re, src_im = x_re_ref, x_im_ref
    if transpose_in:
        # pass 2: the block arrives as rows [128, L]; the transform runs
        # down columns
        src_re, src_im = rest[3:5]
        src_re[...] = x_re_ref[...].T
        src_im[...] = x_im_ref[...].T
    two_r = 2 * r_len

    def level1(c):
        s1 = jnp.concatenate(
            [src_re[pl.ds(c, r_len, stride=c_len), :],
             src_im[pl.ds(c, r_len, stride=c_len), :]], axis=0)
        a_ref[pl.ds(pl.multiple_of(c * two_r, two_r), two_r), :] = \
            PF.dot_mid(m1_ref[c], s1, 1)

    _blocks_loop(c_len, _COLS_UNROLL, level1)
    if twiddle:
        # w[k1, j2_0 + d] = T[k1, d] * exp(k1 * j2_0), k1 = kc*R + kr: a
        # table, a factor by kc and a factor by kr, the two made here
        # from exact integer residues (k1 * j2_0 < m fits int32)
        j2_0 = pl.program_id(1) * bb
        kc = jax.lax.broadcasted_iota(jnp.int32, (c_len, bb), 0)
        v_re, v_im = _phase_cos_sin(kc * (r_len * j2_0), m, sign)

    def level2(kr):
        s2 = jnp.concatenate(
            [a_ref[pl.ds(kr, c_len, stride=two_r), :],
             a_ref[pl.ds(r_len + kr, c_len, stride=two_r), :]], axis=0)
        y = PF.dot_mid(m2_ref[...], s2, 1)             # [2C, bb]
        y_re, y_im = y[:c_len], y[c_len:]
        if twiddle:
            s_re, s_im = _phase_cos_sin(
                jnp.full((1, bb), kr * j2_0, jnp.int32), m, sign)
            f_re = v_re * s_re - v_im * s_im
            f_im = v_re * s_im + v_im * s_re
            rows = pl.ds(pl.multiple_of(kr * c_len, c_len), c_len)
            t_re, t_im = t_re_ref[rows, :], t_im_ref[rows, :]
            w_re = f_re * t_re - f_im * t_im
            w_im = f_re * t_im + f_im * t_re
            y_re, y_im = y_re * w_re - y_im * w_im, y_re * w_im + y_im * w_re
        o_re_ref[pl.ds(kr, c_len, stride=r_len), :] = y_re
        o_im_ref[pl.ds(kr, c_len, stride=r_len), :] = y_im

    _blocks_loop(r_len, _COLS_UNROLL, level2)


def _cols_call(x_re, x_im, re_plane, im_plane, batch: int, *, twiddle: bool,
               transpose_in: bool, m: int, inverse: bool, interpret: bool):
    """One pass over ``batch`` transforms.  ``x_re`` / ``x_im`` are
    ``[P, rows, cols]`` float32 (the same array where the planes lie
    side by side in it), ``re_plane(b)`` / ``im_plane(b)`` say which
    plane holds transform b's parts.  Pass 1 (``twiddle``): column FFT
    of ``[n1, n2]`` with the four-step twiddle, out ``[B, n1, n2]``.
    Pass 2 (``transpose_in``): row FFT of ``[n1, n2]``, out ``[B, n2,
    n1]``, which read row-major is the natural order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, cols = x_re.shape[-2:]
    bb = _COLS_LANES
    length = cols if transpose_in else rows
    r_len, c_len = _col_leg(length)
    if (rows if transpose_in else cols) % bb:
        raise ValueError(f"a {rows} x {cols} plane has no {bb}-wide blocks")
    m1, m2 = _col_leg_consts(length, r_len, c_len, inverse)

    def const(shape):
        return pl.BlockSpec(shape, lambda b, i: (0,) * len(shape))

    if transpose_in:
        def block(plane):
            return pl.BlockSpec((None, bb, cols),
                                lambda b, i: (plane(b), i, 0))
        grid = (batch, rows // bb)
        out_dims = (cols, rows)
    else:
        def block(plane):
            return pl.BlockSpec((None, rows, bb),
                                lambda b, i: (plane(b), 0, i))
        grid = (batch, cols // bb)
        out_dims = (rows, cols)
    operands = [x_re, x_im, jnp.asarray(m1), jnp.asarray(m2)]
    in_specs = [block(re_plane), block(im_plane), const(m1.shape),
                const(m2.shape)]
    if twiddle:
        operands += [jnp.asarray(t) for t in _col_twiddle_table(
            length, r_len, c_len, bb, m, inverse)]
        in_specs += [const((length, bb))] * 2
    out_block = pl.BlockSpec((None, length, bb), lambda b, i: (b, 0, i))
    out = jax.ShapeDtypeStruct((batch, *out_dims), jnp.float32)
    scratch = [pltpu.VMEM((2 * length, bb), jnp.float32)]
    if transpose_in:
        scratch += [pltpu.VMEM((length, bb), jnp.float32)] * 2
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = PF.tpu_compiler_params(
            vmem_limit_bytes=_COLS_VMEM_BYTES)
    return pl.pallas_call(
        functools.partial(
            _cols_kernel, r_len=r_len, c_len=c_len, bb=bb, m=m,
            sign=1.0 if inverse else -1.0, twiddle=twiddle,
            transpose_in=transpose_in),
        grid=grid, in_specs=in_specs, out_specs=[out_block, out_block],
        out_shape=[out, out], scratch_shapes=scratch, interpret=interpret,
        **kwargs)(*operands)


@S.scoped(S.FFT_R2C)
def fft2_cols_planes(planes: jnp.ndarray, inverse: bool = False,
                     interpret: bool = False):
    """B C2C transforms of ``m = n1 * n2`` points in two kernel passes,
    from planes ``[2B, n1, n2]`` (transform b's real part in plane 2b,
    its imaginary part in 2b+1: the blocked field planes of a sub-byte
    unpack as they lie) to ``(re, im)`` float32 ``[B, m]`` in natural
    order.  Unnormalized in both directions, ``Precision.HIGHEST`` in
    every contraction."""
    batch = planes.shape[0] // 2
    n1, n2 = planes.shape[-2:]
    m = n1 * n2
    b_re, b_im = _cols_call(planes, planes, lambda b: 2 * b,
                            lambda b: 2 * b + 1, batch,
                            twiddle=True, transpose_in=False, m=m,
                            inverse=inverse, interpret=interpret)
    y_re, y_im = _cols_call(b_re, b_im, lambda b: b, lambda b: b, batch,
                            twiddle=False, transpose_in=True, m=m,
                            inverse=inverse, interpret=interpret)
    return y_re.reshape(batch, m), y_im.reshape(batch, m)


def fft2_cols(z: jnp.ndarray, inverse: bool = False,
              interpret: bool = False, factor=None) -> jnp.ndarray:
    """:func:`fft2_cols_planes` on complex ``[..., m]``."""
    m = z.shape[-1]
    n1, n2 = factor or cols_factor(m)
    lead = z.shape[:-1]
    z3 = z.reshape(-1, n1, n2)
    y_re, y_im = fft2_cols_planes(
        jnp.stack([jnp.real(z3), jnp.imag(z3)], axis=1).reshape(-1, n1, n2),
        inverse, interpret)
    return jax.lax.complex(y_re, y_im).reshape(*lead, m)


# ---- the Hermitian post as a third kernel pass -------------------
#
# What follows the two passes in the served plan: the p-plane butterfly
# of ops.fft.finish_rfft_subbyte (p = 1: none), the Hermitian post with
# the chirp and chirp*twiddle banks (hermitian_rfft_post(premul=)), RFI
# s1's zap and normalisation and the manual zap ranges, written once.
# F[(m-k) mod m] lies mirrored in rows AND lanes of the [n2, n1] planes;
# Mosaic has no lane reversal, so both mirrors are contractions with a
# permutation matrix (exact under Precision.HIGHEST: one term a sum).

_POST_ROWS = 64


def post_supported(p: int, n1: int, n2: int) -> bool:
    return p in (1, 2) and n1 % _COLS_LANES == 0 and n2 % _POST_ROWS == 0


def post_bank(c_ri: jnp.ndarray, cw_ri: jnp.ndarray) -> jnp.ndarray:
    """The banks as :func:`post_spectrum` reads them: (c_re, c_im,
    cw_re, cw_im) ``[4, n_spectrum / 128, 128]``, made once."""
    return jnp.concatenate([c_ri, cw_ri]).reshape(4, -1, _COLS_LANES)


def _post_kernel(own_re, own_im, mir_re, mir_im, c0_re, c0_im, bank,
                 colfac, jmat, pmat, thr, o_re, o_im, w_ref, *, p, rb, n1,
                 n2, norm_half, bins):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    lanes = _COLS_LANES
    g = n1 // lanes
    big_m = n1 * n2
    shape = (rb, lanes)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + i * rb
    lane0 = lane == 0
    # the mirror block with its rows reversed: row r's partner is row
    # n2-1-r (column 0's is (n2-r) mod n2: ``c0``, made outside)
    for j in range(p):
        w_ref[2 * j] = PF.dot_mid(jmat[...], mir_re[j], 1)
        w_ref[2 * j + 1] = PF.dot_mid(jmat[...], mir_im[j], 1)
    if p == 2:
        # exp(-2*pi*i*k1/m), k1 = r*n1 + c, m = p*M: a factor by row,
        # made here from exact integers, times a table by column
        rr, ri = _phase_cos_sin(row, p * n2, -1.0)
    thr4 = thr[0]

    def tile(t):
        return pl.ds(pl.multiple_of(t * lanes, lanes), lanes)

    def body(t):
        own = tile(t)
        # the lanes 127..1 of tile g-1-t pair with lanes 1..127 of this
        # one, lane 0 with lane 0 of tile (g-t) mod g
        mt, zt = tile(g - 1 - t), tile(jnp.where(t == 0, 0, g - t))
        mir = []
        for q, c0 in enumerate((c0_re, c0_im) * p):
            v = PF.dot_mid(w_ref[q, :, mt], pmat[...], 1)
            z = jnp.where(t == 0, c0[q // 2], w_ref[q, :, zt])
            mir.append(jnp.where(lane0, z, v))
        a0r, a0i = own_re[0, :, own], own_im[0, :, own]
        b0r, b0i = mir[0], -mir[1]                  # conj(F[m-k])
        if p == 2:
            cr_, ci_ = colfac[0:1, own], colfac[1:2, own]
            twr = rr * cr_ - ri * ci_
            twi = rr * ci_ + ri * cr_
            a1r, a1i = own_re[1, :, own], own_im[1, :, own]
            b1r, b1i = mir[2], -mir[3]
            a1r, a1i = a1r * twr - a1i * twi, a1r * twi + a1i * twr
            b1r, b1i = b1r * twr - b1i * twi, b1r * twi + b1i * twr
            halves = [(a0r + a1r, a0i + a1i, b0r + b1r, b0i + b1i),
                      (a0r - a1r, a0i - a1i, b0r - b1r, b0i - b1i)]
        else:
            halves = [(a0r, a0i, b0r, b0i)]
        for h, (fr, fi, gr, gi) in enumerate(halves):
            # twice even and twice odd = -i (F[k] - conj(F[m-k]))
            er, ei = fr + gr, fi + gi
            dr, di = fi - gi, gr - fr
            rows = pl.ds(t, rb, stride=g)
            cr, ci = bank[0, h, rows, :], bank[1, h, rows, :]
            cwr, cwi = bank[2, h, rows, :], bank[3, h, rows, :]
            xr = (cr * er - ci * ei) + (cwr * dr - cwi * di)
            xi = (cr * ei + ci * er) + (cwr * di + cwi * dr)
            zap = xr * xr + xi * xi > thr4
            if bins:
                k = h * big_m + row * n1 + (t * lanes + lane)
                for lo, hi in bins:
                    zap = zap | ((k >= lo) & (k <= hi))
            o_re[h, rows, :] = jnp.where(zap, 0.0, xr * norm_half)
            o_im[h, rows, :] = jnp.where(zap, 0.0, xi * norm_half)

    _blocks_loop(g, _POST_UNROLL, body)


@functools.lru_cache(maxsize=None)
def _post_consts(rb: int, n1: int, m: int):
    lanes = _COLS_LANES
    jmat = np.eye(rb, dtype=np.float32)[::-1].copy()
    pmat = np.zeros((lanes, lanes), np.float32)
    pmat[lanes - np.arange(1, lanes), np.arange(1, lanes)] = 1.0
    ph = -2.0 * np.pi * np.arange(n1, dtype=np.float64) / m
    return jmat, pmat, np.stack([np.cos(ph), np.sin(ph)]).astype(np.float32)


def mean_power_planes(a_re: jnp.ndarray, a_im: jnp.ndarray) -> jnp.ndarray:
    """:func:`srtb_tpu.ops.rfi.mean_power_packed` from the p planes
    ``[p, n2, n1]`` the passes leave, without the butterfly: its
    twiddles have unit modulus, so sum |F|^2 = p * sum |A|^2, and
    F[0] = sum_j A_j[0]."""
    with jax.named_scope(S.RFI_S1):
        p = a_re.shape[0]
        m = a_re.size
        total = p * (jnp.sum(a_re * a_re) + jnp.sum(a_im * a_im))
        f0_re, f0_im = jnp.sum(a_re[:, 0, 0]), jnp.sum(a_im[:, 0, 0])
        return (total + 2.0 * f0_re * f0_im) / m


def post_spectrum(a_re: jnp.ndarray, a_im: jnp.ndarray, bank: jnp.ndarray,
                  *, threshold: float, norm: float, bins=(),
                  interpret: bool = False):
    """From the planes of :func:`fft2_cols_planes` viewed ``[p, n2,
    n1]`` to the dedispersed, zapped, normalised spectrum: (re, im)
    float32 ``[p * n2 * n1]`` in natural order.  ``bank`` is
    :func:`post_bank`'s; ``threshold`` RFI s1's multiple of the mean
    power; ``bins`` the manual zap's inclusive bin ranges."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p, n2, n1 = a_re.shape
    lanes, rb = _COLS_LANES, _POST_ROWS
    g = n1 // lanes
    steps = n2 // rb
    jmat, pmat, colfac = _post_consts(rb, n1, p * n1 * n2)
    thr4 = (4.0 * threshold) * mean_power_planes(a_re, a_im)
    with jax.named_scope(S.FFT_R2C):
        def col0(x):
            # column 0 pairs with row (n2 - r) mod n2 of itself
            c = jnp.roll(jnp.flip(x[:, :, 0], axis=-1), 1, axis=-1)
            return jnp.broadcast_to(c[:, :, None], (p, n2, lanes))
        c0_re, c0_im = col0(a_re), col0(a_im)

    def const(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    plane = pl.BlockSpec((p, rb, n1), lambda i: (0, i, 0))
    mirror = pl.BlockSpec((p, rb, n1), lambda i: (0, steps - 1 - i, 0))
    edge = pl.BlockSpec((p, rb, lanes), lambda i: (0, i, 0))
    dense = pl.BlockSpec((p, rb * g, lanes), lambda i: (0, i, 0))
    out = jax.ShapeDtypeStruct((p, n2 * g, lanes), jnp.float32)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = PF.tpu_compiler_params(
            vmem_limit_bytes=_COLS_VMEM_BYTES)
    with jax.named_scope(S.CHIRP):
        s_re, s_im = pl.pallas_call(
            functools.partial(
                _post_kernel, p=p, rb=rb, n1=n1, n2=n2,
                norm_half=np.float32(0.5 * norm),
                bins=tuple((int(lo), int(hi)) for lo, hi in bins)),
            grid=(steps,),
            in_specs=[plane, plane, mirror, mirror, edge, edge,
                      pl.BlockSpec((4, p, rb * g, lanes),
                                   lambda i: (0, 0, i, 0)),
                      const(colfac.shape), const(jmat.shape),
                      const(pmat.shape),
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=[dense, dense], out_shape=[out, out],
            scratch_shapes=[pltpu.VMEM((2 * p, rb, n1), jnp.float32)],
            interpret=interpret, **kwargs,
        )(a_re, a_im, a_re, a_im, c0_re, c0_im,
          bank.reshape(4, p, n2 * g, lanes), jnp.asarray(colfac),
          jnp.asarray(jmat), jnp.asarray(pmat),
          jnp.asarray(thr4, jnp.float32).reshape(1))
    return s_re.reshape(-1), s_im.reshape(-1)
