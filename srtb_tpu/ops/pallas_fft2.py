"""Fused two-pass Pallas four-step C2C: the whole large-m transform in
two kernel passes plus one fusable transpose.

The "pallas" strategy runs the four-step legs (ops/pallas_fft) inside
XLA's decomposition: transpose, leg FFT, twiddle multiply, transpose,
leg FFT, transpose — each arrow a full HBM pass.  This module puts each
leg's surrounding layout work *into the leg's kernel* so the C2C is two
passes total.

Two spellings of the two passes live here.  **What a chip runs is the
column-native one further down** (``fft2_cols_planes``, with the
Hermitian post as a third kernel pass, ``post_spectrum``: PR 43; a v5e
reads 3.4 + 4.0 ms for the two passes at 2^27 2-bit samples and 4.4
for the post, PERF.md section 5, PR 43).  The first spelling, described
next, is what the staged variants and the CPU tests trace in interpret
mode; compiled for a v5e its pass 1 asks for 96 MB of scoped VMEM
against the 80 MB it sets itself and its pass 2 for 128.15 MB of 128
(``pass1_2d`` / ``pass2_2d`` at 4096 x 8192), so no chip has run it:

  pass 1 (grid over j2 column blocks of z viewed [n1, n2] row-major):
    DMA a strided [n1, bb] column block into VMEM and run the two-level
    DFT decimation over j1 *column-natively*: both contractions are
    dot_generals against the j1 axes of the [la, lb, bb] view in place
    (no 2D transpose, every intermediate lane-dense), then the
    four-step twiddle w[k1, j2] = exp(s*2*pi*i*k1*j2/m) computed
    *in-kernel* from iota with the exact hi/lo phase split (no m-sized
    table exists anywhere), and DMA out: intermediate B[k1, j2] laid
    out [n1, n2].  (A transpose-to-rows spelling existed for hardware
    A/B until round 5's real-Mosaic acceptance run: its in-kernel
    flatten of the assembled row is a minor-lb reshape Mosaic rejects,
    so the column-native form is now the one spelling.)

  pass 2 (grid over k1 row blocks):
    DMA a contiguous [rb, n2] row block, run the row FFT over j2, store
    C[k1, k2] row-major.  The k1-major blocked order is deliberate: a
    natural-order [n2, rb] output block would lane-pad rb -> 128 in
    VMEM (8-32 MB/plane at production n2), so the blocked->natural
    permutation is instead an XLA transpose (``unblock``) that fuses
    into the consumer's next pass — the Hermitian post-process here.

Two kernel passes plus one fusable transpose, versus ~6 separate HBM
round trips for the XLA-orchestrated form.

No XLA FFT op appears anywhere in this path — which also makes it a
workaround candidate for the XLA TPU compiler SIGSEGV on the 2^30
staged blocked shape (PERF.md).  Like every FFT backend here it is
unnormalized in both directions and held to the same float64 oracle
tests (tests/test_pallas_fft2.py); the TPU answer to the reference's
single-call vendor FFTs for full segments (ref: fft/fft.hpp:54-160,
fft_pipe.hpp:44-78).

Front fusion (the ``staged_ffuse`` plan family, pipeline/segment.py):

  * :func:`pass1_front` takes the **raw uint8 segment** as its operand:
    each grid step DMAs its column block of packed bytes, unpacks
    (1/2/4/8-bit, simple or 2-pol byte-interleaved), applies the window
    and the even/odd pack in VMEM, runs the pass-1 column FFT +
    four-step twiddle, and writes the blocked intermediate exactly once
    — HBM pass 1 is one raw-byte read plus one blocked write, with the
    Parseval pieces of the RFI-s1 mean power accumulated on the side.
  * :func:`pass2_spectrum` appends the whole spectrum tail to pass 2's
    epilogue (the slot the skzap tail occupies on the waterfall side):
    row FFT, the Hermitian R2C post-process assembled in-kernel from
    mirrored row blocks, RFI-s1 zap/normalize/manual-mask, and the
    dedispersion chirp — the df64 in-register phase in production
    (staged plans are always bankless; the precombined
    ``(c, cw = c·w)`` blocked premul operands stay available for
    tests and non-staged callers) — so pass 2 emits the dedispersed
    spectrum directly.

  This is the traffic-minimizing move of the PIM-FFT literature
  (PAPERS.md: *Collaborative Acceleration for FFT on PIM*, *Near Memory
  Acceleration on Radio Astronomy Imaging*): do the format conversion
  where the data already is, never re-read what a kernel just wrote.
  Below the production leg window the passes fall back to single-stage
  DFT-matrix legs (``_leg``) so the family stays auditable/testable at
  CPU/CI shapes; Mosaic acceptance of the unpack lane interleave is
  gated like ops/pallas_kernels.UNPACK_MOSAIC_OK (see FFUSE_MOSAIC_OK).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from srtb_tpu.ops import fft as F
from srtb_tpu.ops import pallas_fft as PF
from srtb_tpu.ops import scopes as S


def _factor(m: int, strict: bool = True):
    """m = n1 * n2 with n1 the resident-column length (the whole n1 axis
    of a [n1, bb] block must fit VMEM, so n1 stays small) and n2 a row
    length the two-level kernel handles.  Both need la=128 splits with
    lb >= 32 to bound sublane padding, hence n1 in {4096, 8192} and
    n2 in [4096, 65536]: m in [2^24, 2^29] — exactly the segment sizes
    where monolithic XLA falters (PERF.md).  SRTB_PALLAS2_N1 pins n1
    for hardware A/B (a smaller n1 halves the padded pass-1 block refs
    — the fallback axis if the default plan misses VMEM on chip)."""
    if m & (m - 1):
        return None
    env = os.environ.get("SRTB_PALLAS2_N1")
    if env:
        try:
            n1 = int(env)
        except ValueError:
            n1 = 0
        if n1 <= 0 or n1 & (n1 - 1):
            raise ValueError(
                f"SRTB_PALLAS2_N1={env!r} must be a positive power of two")
        if PF._split_la_lb(n1) is None:
            # as loud as the parse error: a pow2 outside the leg range
            # must not masquerade as an "unsupported size" downstream
            raise ValueError(
                f"SRTB_PALLAS2_N1={n1} outside the leg-FFT range "
                "[4096, 65536]")
        cands = (n1,)
    else:
        cands = (4096, 8192)
    for n1 in cands:
        n2 = m // n1
        if m % n1 == 0 and PF._split_la_lb(n1) and 4096 <= n2 <= 65536:
            return n1, n2
    if env and strict:
        # the pin passed the pow2/leg-range checks above but fails for
        # THIS m — at kernel-build time an explicit knob must not
        # silently degrade to "unsupported size" (and thence the xla
        # fallback).  Boolean probes (``supported``) pass strict=False:
        # dispatchers ask about many sizes and a pin that doesn't fit a
        # probed size just means "not this path for this size".
        n1 = cands[0]
        if m % n1:
            raise ValueError(
                f"SRTB_PALLAS2_N1={n1} does not divide m={m}")
        raise ValueError(
            f"SRTB_PALLAS2_N1={n1} leaves n2={m // n1} outside the "
            "row-FFT range [4096, 65536] "
            f"for m={m}")
    return None


def supported(m: int) -> bool:
    return _factor(m, strict=False) is not None


def require_pin_fit(m: int) -> None:
    """Dispatchers call this in their not-supported fallback branch:
    when SRTB_PALLAS2_N1 is set and is the *reason* ``m`` is
    unsupported, raise the strict pin error instead of letting the
    operator's explicit A/B knob silently measure the fallback path.
    No-op when the pin is unset (the documented tiny-config fallback)
    or when m is unsupported for pin-independent reasons (non-pow2)."""
    if os.environ.get("SRTB_PALLAS2_N1"):
        _factor(m, strict=True)


def _vmem_budget() -> int:
    """Total VMEM bytes each kernel's plan may assume.  The round-2
    measurements ran on v5e, whose physical VMEM is 128 MiB/core;
    Mosaic's *default* scoped-vmem limit is far lower, so both
    pallas_calls pass an explicit ``vmem_limit_bytes`` alongside blocks
    sized by the padded-footprint model below.  Default 80 MiB leaves
    headroom for Mosaic internal scratch; SRTB_PALLAS2_VMEM_MB is the
    hardware A/B knob (a 16 MiB-era budget cannot fit ANY pass-1 block:
    the padded minimum 2*4*n1*128*4 B is 16 MiB at n1=4096 alone).
    Parsed + validated once, like pallas_fft._vmem_mb: a degenerate
    setting must fail loudly here, not as floor-zero blocks plus a
    nonpositive vmem_limit_bytes handed to Mosaic."""
    env = os.environ.get("SRTB_PALLAS2_VMEM_MB", "80")
    try:
        mb = int(env)
    except ValueError:
        mb = 0
    if mb <= 0:
        raise ValueError(
            f"SRTB_PALLAS2_VMEM_MB={env!r} must be a positive integer "
            "(MiB of VMEM the two-pass plan may assume)")
    return mb << 20


def _leg_const_bytes(la: int, lb: int) -> int:
    """Padded VMEM bytes of the six leg-FFT constant refs
    (war/wai [la,la], wbr/wbi [lb,lb], twr/twi [la,lb]) — lb < 128
    lane-pads its minor dim."""
    plb = max(lb, 128)
    return 4 * (2 * la * la + 2 * lb * plb + 2 * la * plb)


def _pass1_bytes(n1: int, bb: int) -> int:
    """Padded-VMEM footprint model for one pass-1 grid step: the four
    [n1, bb] block refs are double-buffered by the Pallas pipeline and
    lane-pad bb -> 128 (the round-3 review catch: logical-words sizing
    undercounted small-bb blocks 4x at n1=8192), plus the peak live
    column-native kernel intermediates, plus the leg consts."""
    la, lb = PF._split_la_lb(n1)
    refs = 2 * 4 * n1 * max(bb, 128) * 4
    # dense [lb, bb, la]/[bb, la, lb] stages; stage-2 outputs carry
    # minor dim lb (pads to 128), the final relayout minor dim bb
    live = (4 * la * lb * bb * 4
            + 2 * bb * la * max(lb, 128) * 4
            + 2 * n1 * max(bb, 128) * 4)
    return refs + live + _leg_const_bytes(la, lb)


def _pass2_bytes(n2: int, rb: int) -> int:
    """Same model for one pass-2 grid step: the [rb, n2] input blocks
    are lane-dense (rb is the sublane dim, min tile 8); the 3D output
    blocks and helper stages carry minor dim lb = n2/128, which pads to
    128 on the small-n2 end."""
    la, lb = PF._split_la_lb(n2)
    plb = max(lb, 128)
    refs = 2 * 2 * max(rb, 8) * (n2 + la * plb) * 4
    live = 6 * la * rb * plb * 4
    return refs + live + _leg_const_bytes(la, lb)


def _pick_block(candidates, fits, floor: int) -> int:
    """Largest candidate whose modeled footprint fits the budget; the
    floor (the minimum meaningful block) when none does — shrinking
    below it cannot reduce the padded refs, so a non-fitting floor is a
    hardware question for vmem_limit_bytes, not a sizing one."""
    for c in candidates:
        if fits(c):
            return c
    return floor


def _choose_block(env_var: str, cands, fallback: int, small: bool,
                  bytes_fn, floor: int) -> int:
    """Shared block-chooser rule of the four pass pickers below: the
    env pin overrides absolutely (hardware tuning); small-leg
    (sub-production) shapes take the largest candidate — the whole
    block is tiny and the padded-footprint model doesn't apply;
    otherwise the largest candidate whose modeled footprint fits the
    VMEM budget, or the floor."""
    env = os.environ.get(env_var)
    if env:
        return int(env)
    if small or not cands:
        return cands[0] if cands else fallback
    budget = _vmem_budget()
    return _pick_block(cands, lambda c: bytes_fn(c) <= budget, floor)


def _block_cols(n1: int, n2: int) -> int:
    """Pass-1 column-block width (= rows of the in-kernel leg FFT):
    largest power-of-two divisor of n2 in [128, 1024] that fits the
    padded-footprint budget.  bb >= 128 always — below that the block's
    lane padding keeps VMEM cost flat while throwing away strided-DMA
    width.  SRTB_PALLAS2_BB overrides absolutely (hardware tuning)."""
    return _choose_block(
        "SRTB_PALLAS2_BB",
        [c for c in (1024, 512, 256, 128) if n2 % c == 0],
        min(n2, 128), PF._split_la_lb(n1) is None,
        lambda c: _pass1_bytes(n1, c), 128)


def _block_rows(n2: int, n1: int) -> int:
    """Pass-2 row-block height: largest power-of-two divisor of n1 in
    [8, 256] that fits the budget (rb is the sublane dim — lane-dense
    at any size, so small rb is cheap and correct here)."""
    return _choose_block(
        "SRTB_PALLAS2_RB",
        [c for c in (256, 128, 64, 32, 16, 8) if n1 % c == 0],
        min(n1, 8), PF._split_la_lb(n2) is None,
        lambda c: _pass2_bytes(n2, c), 8)


def _pass1_front_bytes(n1: int, bb: int, streams: int, nbits: int,
                       windowed: bool) -> int:
    """:func:`_pass1_bytes` extended for the front-fused kernel
    (:func:`pass1_front`): the double-buffered raw-byte tile, the
    optional (w_even, w_odd) window blocks and the 2S output blocks +
    3S accumulators replace the classic 2-in/2-out ref model; the
    in-kernel unpack adds its int32 byte view plus the widened f32
    sample planes as live scratch; the per-stream column FFT keeps the
    classic live-intermediate term (streams are processed serially, so
    one stream's FFT intermediates are live at a time)."""
    la, lb = PF._split_la_lb(n1)
    blk_bytes = bb * 2 * streams * abs(nbits) // 8
    refs = 2 * n1 * max(blk_bytes, 128)               # u8 byte tile
    if windowed:
        refs += 2 * 2 * n1 * max(bb, 128) * 4         # (w_even, w_odd)
    refs += 2 * 2 * streams * n1 * max(bb, 128) * 4   # output blocks
    refs += 2 * 3 * streams * 8 * 128 * 4             # accumulators
    # unpack scratch: the int32 byte view plus ~2 widened f32 sample
    # planes covering all streams (field stack + lane de-interleave)
    scratch = (n1 * max(blk_bytes, 128) * 4
               + 2 * n1 * 2 * streams * max(bb, 128) * 4)
    live = (4 * la * lb * bb * 4 + 2 * bb * la * max(lb, 128) * 4
            + 2 * n1 * max(bb, 128) * 4)
    return refs + scratch + live + _leg_const_bytes(la, lb)


def _block_cols_front(n1: int, n2: int, streams: int, nbits: int,
                      windowed: bool) -> int:
    """Pass-1 column-block width for the front-fused kernel — the
    :func:`_block_cols` rule with the fused footprint model (the
    raw-byte tile + unpack scratch + per-stream outputs all count).
    SRTB_PALLAS2_BB still overrides absolutely."""
    return _choose_block(
        "SRTB_PALLAS2_BB",
        [c for c in (1024, 512, 256, 128) if n2 % c == 0],
        min(n2, 128), PF._split_la_lb(n1) is None,
        lambda c: _pass1_front_bytes(n1, c, streams, nbits, windowed),
        128)


def _pass2_spec_bytes(n2: int, rb: int, has_mask: bool,
                      has_premul: bool) -> int:
    """:func:`_pass2_bytes` extended for the fused-epilogue kernel
    (:func:`pass2_spectrum`): SIX streamed [rb, n2] input blocks (row
    + mirror + next pairs) plus the mask/premul operand blocks, two
    row FFTs live per step (the block's own rows and its mirror rows),
    and the Hermitian/zap/chirp elementwise planes."""
    la, lb = PF._split_la_lb(n2)
    plb = max(lb, 128)
    prb = max(rb, 8)
    nin = 6 + (1 if has_mask else 0) + (4 if has_premul else 0)
    refs = 2 * (nin + 2) * prb * n2 * 4        # lane-dense [rb, n2] refs
    live = (2 * 6 * la * rb * plb * 4          # two row-FFT bodies
            + 10 * prb * n2 * 4)               # hermitian/zap/chirp planes
    return refs + live + _leg_const_bytes(la, lb)


def _block_rows_spec(n2: int, n1: int, has_mask: bool,
                     has_premul: bool) -> int:
    """Pass-2 row-block height for the fused-epilogue kernel — the
    :func:`_block_rows` rule with the fused footprint model.
    SRTB_PALLAS2_RB still overrides absolutely."""
    return _choose_block(
        "SRTB_PALLAS2_RB",
        [c for c in (256, 128, 64, 32, 16, 8) if n1 % c == 0],
        min(n1, 8), PF._split_la_lb(n2) is None,
        lambda c: _pass2_spec_bytes(n2, c, has_mask, has_premul), 8)


# ------------------------------------------------------------------
# in-kernel DFT "legs".  The production window runs the two-level
# 128-lane VMEM leg (ops/pallas_fft); below it — the front-fuse
# family's CI/audit shapes — a leg is a single DFT-matrix contraction,
# so the same kernels stay lowerable at any power-of-two >= 8.

_SMALL_LEG_MAX = 512  # [L, L] f32 DFT-matrix pair tops out at 2 MB


def _leg(length: int, inverse: bool):
    """(kind, la, lb, const arrays) for the in-kernel DFT along one
    axis: kind "two" = the two-level 128-lane leg (PF.leg_consts),
    kind "one" = one [L, L] DFT-matrix dot_general (small lengths)."""
    if PF._split_la_lb(length) is not None:
        la, lb, consts = PF.leg_consts(length, inverse)
        return "two", la, lb, consts
    if length & (length - 1) or not 8 <= length <= _SMALL_LEG_MAX:
        raise ValueError(f"leg length {length} unsupported")
    wr, wi = PF._dft_matrix_np(length, inverse)
    return "one", length, 1, (jnp.asarray(wr), jnp.asarray(wi))


def _leg_specs(kind: str, la: int, lb: int):
    if kind == "two":
        return PF.leg_const_specs(la, lb)
    return [PF._Launch.const_spec((la, la)),
            PF._Launch.const_spec((la, la))]


def leg_supported(length: int) -> bool:
    return PF._split_la_lb(length) is not None or (
        not length & (length - 1) and 8 <= length <= _SMALL_LEG_MAX)


def ffuse_factor(m):
    """[n1, n2] factorization for the front-fused kernels: the standard
    production window (:func:`_factor`) first; below it a small-leg
    split so the ``staged_ffuse`` plan family stays auditable and
    testable at CPU/CI shapes.  None when ``m`` has no usable split."""
    fac = _factor(m, strict=False)
    if fac is not None:
        return fac
    if m & (m - 1) or m < (1 << 10):
        return None

    def ok(n1):
        if not 8 <= n1 <= _SMALL_LEG_MAX or m % n1:
            return False
        return leg_supported(m // n1) and m // n1 >= 128

    n1 = min(1 << ((m.bit_length() - 1) // 2), _SMALL_LEG_MAX)
    for cand in (n1, m // 4096, m // 128):
        if ok(cand):
            return cand, m // cand
    return None


def _phase_cos_sin(r, m: int, sign: float):
    """(cos, sin) of sign*2*pi*r/m for an int32 residue array r < m
    <= 2^29, via the hi/lo split so each cos/sin argument is f32-exact
    (the ops.fft._phase_exp discipline, in-register).  Single home of
    the split for both twiddle orientations — the window-edge
    precision test pins this one body."""
    half = 1 << 15
    scale = jnp.float32(sign * 2.0 * np.pi / m)
    a = (r // half).astype(jnp.float32) * (half * scale)
    b = (r % half).astype(jnp.float32) * scale
    ca, sa = jnp.cos(a), jnp.sin(a)
    cb, sb = jnp.cos(b), jnp.sin(b)
    return ca * cb - sa * sb, sa * cb + ca * sb


def _col_fft_block(x2r, x2i, cref, *, kind, n1, bb, la, lb):
    """Column-axis leg DFT of one [n1(j1), bb(j2)] value-block pair
    (contract j1) — the column-native body shared by the packed
    (:func:`pass1_2d`) and raw-front (:func:`pass1_front`) pass-1
    kernels.  Returns the y[k1, d] pair [n1, bb]."""
    dg = PF.dot_mid
    if kind == "one":
        # small-leg: one DFT-matrix contraction over j1
        war, wai = cref[0][:], cref[1][:]
        yr = dg(war, x2r, 0) - dg(wai, x2i, 0)  # [n1(k1), bb]
        yi = dg(war, x2i, 0) + dg(wai, x2r, 0)
        return yr, yi
    # column-native two-level leg: both DFT contractions run against
    # the j1 axes of the block in place — no input transpose, no padded
    # intermediate, one dense 3D relayout at the end
    war_ref, wai_ref, wbr_ref, wbi_ref, twr_ref, twi_ref = cref
    x3r = x2r.reshape(la, lb, bb)
    x3i = x2i.reshape(la, lb, bb)
    war, wai = war_ref[:], wai_ref[:]
    # stage 1, contract j1a: A[j2, d, k1]
    ar = dg(x3r, war, 0) - dg(x3i, wai, 0)      # [lb, bb, la]
    ai = dg(x3r, wai, 0) + dg(x3i, war, 0)
    # inner twiddle tw[k1, j2] at [j2, 1, k1] orientation
    twr2 = twr_ref[:].T.reshape(lb, 1, la)
    twi2 = twi_ref[:].T.reshape(lb, 1, la)
    br = ar * twr2 - ai * twi2
    bi = ar * twi2 + ai * twr2
    # stage 2, contract j1b(lb): C[d, k1, k2]
    wbr, wbi = wbr_ref[:], wbi_ref[:]
    cr = dg(br, wbr, 0) - dg(bi, wbi, 0)        # [bb, la, lb]
    ci = dg(br, wbi, 0) + dg(bi, wbr, 0)
    # leg-natural index k = k2*la + k1 -> [k2, k1, d] -> [n1, bb]
    yr = jnp.transpose(cr, (2, 1, 0)).reshape(n1, bb)
    yi = jnp.transpose(ci, (2, 1, 0)).reshape(n1, bb)
    return yr, yi


def _pass1_kernel(re_ref, im_ref, *rest, n1, bb, la, lb, m, sign, kind):
    from jax.experimental import pallas as pl

    cref = rest[:-2]
    out_re_ref, out_im_ref = rest[-2:]
    j2_0 = pl.program_id(0) * bb
    yr, yi = _col_fft_block(re_ref[:], im_ref[:], cref, kind=kind,
                            n1=n1, bb=bb, la=la, lb=lb)
    # four-step twiddle at [k, d] orientation
    wr, wi = _fourstep_twiddle_t(n1, bb, m, sign, j2_0)
    out_re_ref[:] = yr * wr - yi * wi
    out_im_ref[:] = yr * wi + yi * wr


def _fourstep_twiddle_t(n1: int, cols_j2: int, m: int, sign: float, j2_0):
    """Four-step twiddle w[k1, d] = exp(sign*2*pi*i*k1*(j2_0 + d)/m) for
    k1 < n1, d < cols_j2 — the [n1, bb] layout the column-native pass-1
    writes — computed in-kernel from iota (k1*j2 < m <= 2^29 is exact in
    int32)."""
    k1 = jax.lax.broadcasted_iota(jnp.int32, (n1, cols_j2), 0)
    d = jax.lax.broadcasted_iota(jnp.int32, (n1, cols_j2), 1) + j2_0
    return _phase_cos_sin(d * k1, m, sign)


def _row_fft_block(xr, xi, cref, *, kind, n2, rb, la, lb):
    """Row-axis leg DFT of one [rb, n2] value-block pair (length-n2
    C2C along each row), natural order, as a flat [rb, n2] pair.  The
    two-level kind flattens the helper's [rb, la, lb] view in-kernel —
    a minor-lb reshape real Mosaic rejects, sanctioned here because
    every caller is either interpret-mode (CPU CI) or behind the
    FFUSE_MOSAIC_OK hardware-probe gate; the classic
    :func:`_pass2_kernel` path keeps the 3D-out-ref spelling."""
    dg = PF.dot_mid
    if kind == "one":
        wr, wi = cref[0][:], cref[1][:]
        yr = dg(xr, wr, 1) - dg(xi, wi, 1)      # [rb, n2]
        yi = dg(xr, wi, 1) + dg(xi, wr, 1)
        return yr, yi
    yr3, yi3 = PF.vmem_fft_rows(xr, xi, *[r[:] for r in cref],
                                la=la, lb=lb, rows=rb)
    return yr3.reshape(rb, n2), yi3.reshape(rb, n2)


def _pass2_kernel(re_ref, im_ref, *rest, n2, rb, la, lb, kind):
    cref = rest[:-2]
    out_re_ref, out_im_ref = rest[-2:]
    if kind == "one":
        yr, yi = _row_fft_block(re_ref[:], im_ref[:], cref, kind=kind,
                                n2=n2, rb=rb, la=la, lb=lb)
        out_re_ref[:] = yr
        out_im_ref[:] = yi
        return
    # output stays k1-major blocked (a natural-order [n2, rb] column
    # block would lane-pad rb -> 128 in VMEM, 8-32 MB per plane at
    # production n2) — callers restore order with unblock(), an XLA
    # transpose the next elementwise pass absorbs.  The helper returns
    # its [rb, la, lb] natural-flat view; the 3D out refs match and the
    # caller's flatten to [rb, n2] happens outside the pallas_call.
    yr, yi = PF.vmem_fft_rows(re_ref[:], im_ref[:], *[r[:] for r in cref],
                              la=la, lb=lb, rows=rb)
    out_re_ref[:] = yr
    out_im_ref[:] = yi




def pass1_2d(re2, im2, inverse: bool = False, interpret: bool = False):
    """Fused pass 1 on one [n1, n2]-viewed transform: column FFTs over
    j1 + four-step twiddle, intermediate B[k1, j2] as an [n1, n2] f32
    pair.  Split out so the staged 2^30 plan can run each pass as its
    own XLA program (pipeline/segment.py)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n1, n2 = re2.shape
    m = n1 * n2
    sign = 1.0 if inverse else -1.0
    bb = _block_cols(n1, n2)
    if n2 % bb:
        raise ValueError(f"pass-1 block {bb} must divide n2={n2}")
    kind1, la1, lb1, consts1 = _leg(n1, inverse)
    col_block = pl.BlockSpec((n1, bb), lambda i: (0, i),
                             memory_space=pltpu.VMEM)
    k1 = functools.partial(_pass1_kernel, n1=n1, bb=bb, la=la1, lb=lb1,
                           m=m, sign=sign, kind=kind1)
    mid_shape = jax.ShapeDtypeStruct((n1, n2), jnp.float32)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = PF.tpu_compiler_params(
            vmem_limit_bytes=_vmem_budget())
    return pl.pallas_call(
        k1,
        grid=(n2 // bb,),
        in_specs=[col_block, col_block] + _leg_specs(kind1, la1, lb1),
        out_specs=[col_block, col_block],
        out_shape=[mid_shape, mid_shape],
        interpret=interpret,
        **kwargs,
    )(re2, im2, *consts1)


def pass2_2d(br, bi, inverse: bool = False, interpret: bool = False):
    """Fused pass 2 on the [n1, n2] intermediate: row FFTs over j2.
    Output is [n1, n2] k1-major blocked (C[k1, k2]; the true transform
    index is k2*n1 + k1) — callers restore natural order with
    :func:`unblock`, whose XLA transpose fuses into their next
    elementwise pass."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n1, n2 = br.shape
    rb = _block_rows(n2, n1)
    if n1 % rb:
        raise ValueError(f"pass-2 block {rb} must divide n1={n1}")
    kind2, la2, lb2, consts2 = _leg(n2, inverse)
    row_block = pl.BlockSpec((rb, n2), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    if kind2 == "two":
        out_block = pl.BlockSpec((rb, la2, lb2), lambda i: (i, 0, 0),
                                 memory_space=pltpu.VMEM)
        out_shape = jax.ShapeDtypeStruct((n1, la2, lb2), jnp.float32)
    else:  # small-leg: the row block is already the natural-flat form
        out_block = row_block
        out_shape = jax.ShapeDtypeStruct((n1, n2), jnp.float32)
    k2 = functools.partial(_pass2_kernel, n2=n2, rb=rb, la=la2, lb=lb2,
                           kind=kind2)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = PF.tpu_compiler_params(
            vmem_limit_bytes=_vmem_budget())
    yr3, yi3 = pl.pallas_call(
        k2,
        grid=(n1 // rb,),
        in_specs=[row_block, row_block] + _leg_specs(kind2, la2, lb2),
        out_specs=[out_block, out_block],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
        **kwargs,
    )(br, bi, *consts2)
    # contiguous [n1, la2, lb2] -> [n1, n2]: free metadata reshape
    return yr3.reshape(n1, n2), yi3.reshape(n1, n2)


def _fft2_2d(re2, im2, n1, n2, inverse, natural, interpret):
    """The two fused passes on one [n1, n2]-viewed transform; with
    ``natural`` the blocked result is unblocked by an XLA transpose
    (fused into the caller's consumer pass)."""
    br, bi = pass1_2d(re2, im2, inverse, interpret)
    yr, yi = pass2_2d(br, bi, inverse, interpret)
    if natural:
        return yr.T, yi.T
    return yr, yi


@S.scoped(S.FFT_R2C)
def pass1_ri(re: jnp.ndarray, im: jnp.ndarray, inverse: bool = False,
             interpret: bool = False):
    """Batched pass 1: [..., m] f32 pair -> [..., n1, n2] intermediate
    pair (the staged plan's (a)/(b) boundary representation)."""
    m = re.shape[-1]
    n1, n2 = _factor(m)
    lead = re.shape[:-1]
    re2 = re.reshape(-1, m)
    im2 = im.reshape(-1, m)
    outs = [pass1_2d(re2[b].reshape(n1, n2), im2[b].reshape(n1, n2),
                     inverse, interpret) for b in range(re2.shape[0])]
    br = jnp.stack([o[0] for o in outs]).reshape(*lead, n1, n2)
    bi = jnp.stack([o[1] for o in outs]).reshape(*lead, n1, n2)
    return br, bi


@S.scoped(S.FFT_R2C)
def pass2_ri(br: jnp.ndarray, bi: jnp.ndarray, inverse: bool = False,
             interpret: bool = False):
    """Batched pass 2: [..., n1, n2] intermediate pair -> [..., m]
    natural-order f32 pair."""
    n1, n2 = br.shape[-2], br.shape[-1]
    m = n1 * n2
    lead = br.shape[:-2]
    br2 = br.reshape(-1, n1, n2)
    bi2 = bi.reshape(-1, n1, n2)
    outs = [pass2_2d(br2[b], bi2[b], inverse, interpret)
            for b in range(br2.shape[0])]
    # unblock: C[k1, k2] -> natural k2*n1 + k1 (XLA transpose, fused
    # into the Hermitian post-process that consumes this)
    yr = jnp.stack([o[0].T.reshape(m) for o in outs]).reshape(*lead, m)
    yi = jnp.stack([o[1].T.reshape(m) for o in outs]).reshape(*lead, m)
    return yr, yi


def fft2_c2c_ri(re: jnp.ndarray, im: jnp.ndarray, inverse: bool = False,
                natural: bool = True, interpret: bool = False):
    """C2C FFT along the last axis of split re/im f32 [..., m] arrays in
    two fused Pallas passes.  Unnormalized both directions (ops.fft
    conventions).  ``natural=False`` returns the result in [n1, n2]
    k1-major blocked order (flatten index k1*n2 + k2; true index is
    k2*n1 + k1) for consumers that absorb the permutation — use
    :func:`unblock` to restore natural order.
    """
    m = re.shape[-1]
    fac = _factor(m)
    if fac is None:
        raise ValueError(f"pallas2 unsupported length {m}")
    n1, n2 = fac
    lead = re.shape[:-1]
    re2 = re.reshape(-1, m)
    im2 = im.reshape(-1, m)
    outs = [_fft2_2d(re2[b].reshape(n1, n2), im2[b].reshape(n1, n2),
                     n1, n2, inverse, natural, interpret)
            for b in range(re2.shape[0])]
    yr = jnp.stack([o[0].reshape(m) for o in outs])
    yi = jnp.stack([o[1].reshape(m) for o in outs])
    return yr.reshape(*lead, m), yi.reshape(*lead, m)


def fft2_c2c(x: jnp.ndarray, inverse: bool = False, natural: bool = True,
             interpret: bool = False) -> jnp.ndarray:
    """Complex convenience wrapper over :func:`fft2_c2c_ri`."""
    yr, yi = fft2_c2c_ri(jnp.real(x), jnp.imag(x), inverse, natural,
                         interpret)
    return jax.lax.complex(yr, yi)


def unblock(y: jnp.ndarray, m: int) -> jnp.ndarray:
    """[..., m] in k1-major blocked order (from ``natural=False``) ->
    natural order, as an XLA transpose the consumer's next elementwise
    pass can fuse with."""
    n1, n2 = _factor(m)
    y2 = y.reshape(*y.shape[:-1], n1, n2)
    return jnp.swapaxes(y2, -1, -2).reshape(*y.shape[:-1], m)


# ==================================================================
# column-native passes: the spelling a chip runs (PR 43).  Both passes
# are ONE kernel body, a column FFT of a [L, 128]-lane block held in
# VMEM, L = R*C: level 1 gathers the rows r*C + c of one c (a
# sublane-strided load) and multiplies them by a [2R, 2R] real matrix
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


# ==================================================================
# front fusion: unpack -> window -> even/odd pack -> pass 1 in ONE
# kernel (raw bytes in, blocked intermediate out), and the whole
# spectrum tail (Hermitian + RFI s1 + chirp) as pass 2's epilogue.
# ==================================================================

# Pending on-chip Mosaic validation (then flip to True): the front kernels use the sub-byte
# lane interleave ops/pallas_kernels.UNPACK_MOSAIC_OK documents as
# unlowerable today, plus strided lane de-interleaves, an in-kernel
# minor-lb flatten (_row_fft_block) and a lane flip/roll — every one
# fine under interpret (CPU CI), each a real-Mosaic question.
# SRTB_PALLAS_FFUSE=1 opts in before the probe; front_fuse="on"
# (Config) forces regardless — the hardware A/B spelling.
FFUSE_MOSAIC_OK = False

# unpack variants the front kernel can spell in-register, and the
# sample widths each supports (ops/unpack.py semantics: positive =
# unsigned, negative = signed int8)
FFUSE_VARIANT_BITS = {
    "simple": (1, 2, 4, 8, -8),
    "interleaved_samples_2": (8, -8),
}


def ffuse_enabled() -> bool:
    """Whether front_fuse="auto" may resolve ON: the Mosaic probe flag
    or the env opt-in.  Deliberately NOT true merely under interpret —
    "auto" flipping every existing pallas2-staged config (and its
    pinned plan card) onto the new megakernel the moment the code
    landed would be a silent plan change; the staged_ffuse family,
    tests and ci force front_fuse="on" instead."""
    return FFUSE_MOSAIC_OK or \
        os.environ.get("SRTB_PALLAS_FFUSE", "") == "1"


def _front_unpack(b32, variant: str, nbits: int):
    """int32 byte block [n1, BB] -> per-stream (re, im) f32 sample
    blocks [n1, bb] in even/odd-packed order — the in-kernel mirror of
    ops.unpack + ops.fft.pack_even_odd.  Every value is a small exact
    integer, so any op order is value-identical to the XLA path; the
    lane interleave/de-interleave spellings are what FFUSE_MOSAIC_OK
    gates on real chips."""
    if nbits in (8, -8):
        vals = b32
        if nbits == -8:
            vals = vals - 2 * (vals & 0x80)  # u8 bits -> s8 value
        vals = vals.astype(jnp.float32)
    else:
        count = 8 // nbits
        mask = (1 << nbits) - 1
        # MSB-first fields (ref: unpack.hpp:43-140), interleaved back
        # to sample order along the lane axis
        fields = [((b32 >> (8 - nbits * (j + 1))) & mask)
                  .astype(jnp.float32) for j in range(count)]
        vals = jnp.stack(fields, axis=-1).reshape(
            b32.shape[0], b32.shape[1] * count)
    if variant == "interleaved_samples_2":
        # "1212" byte interleave: z_s[j] = x[4j+s] + i*x[4j+2+s]
        return [(vals[:, s::4], vals[:, 2 + s::4]) for s in range(2)]
    return [(vals[:, 0::2], vals[:, 1::2])]


def _pass1_front_kernel(byte_ref, *rest, n1, bb, la, lb, m, sign, kind,
                        variant, nbits, streams, windowed):
    from jax.experimental import pallas as pl

    idx = 0
    win = None
    if windowed:
        win = (rest[0], rest[1])
        idx = 2
    ncon = 6 if kind == "two" else 2
    cref = rest[idx:idx + ncon]
    outs = rest[idx + ncon:]
    step = pl.program_id(0)
    j2_0 = step * bb
    b32 = byte_ref[:].astype(jnp.int32)
    pairs = _front_unpack(b32, variant, nbits)
    wr4, wi4 = _fourstep_twiddle_t(n1, bb, m, sign, j2_0)
    for s, (re, im) in enumerate(pairs):
        if windowed:
            re = re * win[0][:]
            im = im * win[1][:]
        yr, yi = _col_fft_block(re, im, cref, kind=kind, n1=n1, bb=bb,
                                la=la, lb=lb)
        br = yr * wr4 - yi * wi4
        bi = yr * wi4 + yi * wr4
        outs[2 * s][:] = br
        outs[2 * s + 1][:] = bi
        # RFI-s1 mean-power pieces, accumulated while the block is in
        # VMEM (TPU grids are sequential): sum |B|^2 over the whole
        # intermediate plus the DC-bin partials F0 = sum_j2 B[0, j2],
        # as 128-lane partial vectors (finished in front_mean_power)
        s2_ref, f0r_ref, f0i_ref = outs[2 * streams + 3 * s:
                                        2 * streams + 3 * s + 3]

        @pl.when(step == 0)
        def _init(s2_ref=s2_ref, f0r_ref=f0r_ref, f0i_ref=f0i_ref):
            s2_ref[:] = jnp.zeros_like(s2_ref)
            f0r_ref[:] = jnp.zeros_like(f0r_ref)
            f0i_ref[:] = jnp.zeros_like(f0i_ref)

        p = br * br + bi * bi
        s2_ref[:] += p.sum(axis=0).reshape(bb // 128, 128).sum(axis=0,
                                                               keepdims=True)
        f0r_ref[:] += br[0:1, :].reshape(bb // 128, 128).sum(
            axis=0, keepdims=True)
        f0i_ref[:] += bi[0:1, :].reshape(bb // 128, 128).sum(
            axis=0, keepdims=True)


@S.scoped(S.FFT_R2C)
def pass1_front(raw: jnp.ndarray, *, m: int, streams: int, variant: str,
                nbits: int, window_eo=None, inverse: bool = False,
                interpret: bool = False):
    """Front-fused pass 1: the RAW uint8 segment is the kernel operand.

    Each grid step DMAs its column block of packed bytes, unpacks
    (``FFUSE_VARIANT_BITS``), multiplies the window, performs the
    even/odd half-size pack and the pass-1 column FFT + four-step
    twiddle in VMEM, and writes the blocked intermediate exactly once:
    HBM pass 1 = one raw-byte read + one blocked write.  The Parseval
    pieces of the RFI stage-1 mean power ride along as per-stream
    128-lane accumulators so stage (b) never re-reads anything
    spectrum-sized to evaluate the zap threshold.

    ``raw``: uint8 [streams * 2m * |nbits| / 8] (all streams
    interleaved, as read from file/UDP).  ``window_eo``: optional
    (w_even, w_odd) f32 [n1, n2] pair — the per-stream sample window
    split even/odd and viewed blocked (SegmentProcessor precomputes
    it).  Returns ``(br, bi, aux)``: [S, n1, n2] intermediate pair +
    [S, 3, 128] accumulators.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from srtb_tpu.ops import pallas_kernels as pk

    if nbits not in FFUSE_VARIANT_BITS.get(variant, ()):
        raise ValueError(
            f"front fuse unsupported for variant {variant!r} at "
            f"{nbits}-bit")
    fac = ffuse_factor(m)
    if fac is None:
        raise ValueError(f"front fuse unsupported length {m}")
    n1, n2 = fac
    sign = 1.0 if inverse else -1.0
    bb = _block_cols_front(n1, n2, streams, nbits,
                           window_eo is not None)
    if n2 % bb:
        raise ValueError(f"pass-1 block {bb} must divide n2={n2}")
    if bb % 128:
        # the accumulator reduction reshapes each block to
        # [bb // 128, 128] lanes
        raise ValueError(f"pass-1 front block {bb} must be a multiple "
                         "of 128")
    bits_per_col = 2 * streams * abs(nbits)  # one packed column = 2S samples
    if (n2 * bits_per_col) % 8 or (bb * bits_per_col) % 8:
        raise ValueError(f"byte-misaligned ffuse block {bb}x{bits_per_col}b")
    row_bytes = n2 * bits_per_col // 8
    blk_bytes = bb * bits_per_col // 8
    if raw.shape != (n1 * row_bytes,):
        raise ValueError(
            f"raw must be {n1 * row_bytes} bytes, got {raw.shape}")
    raw2 = raw.reshape(n1, row_bytes)
    kind, la, lb, consts = _leg(n1, inverse)

    byte_block = pl.BlockSpec((n1, blk_bytes), lambda i: (0, i),
                              memory_space=pltpu.VMEM)
    col_block = pl.BlockSpec((n1, bb), lambda i: (0, i),
                             memory_space=pltpu.VMEM)
    acc_block = pl.BlockSpec((1, 128), lambda i: (0, 0),
                             memory_space=pltpu.VMEM)
    in_specs = [byte_block]
    operands = [raw2]
    windowed = window_eo is not None
    if windowed:
        in_specs += [col_block, col_block]
        operands += [window_eo[0], window_eo[1]]
    in_specs += _leg_specs(kind, la, lb)
    operands += list(consts)
    mid = jax.ShapeDtypeStruct((n1, n2), jnp.float32)
    acc = jax.ShapeDtypeStruct((1, 128), jnp.float32)
    out_specs = [col_block] * (2 * streams) + [acc_block] * (3 * streams)
    out_shape = [mid] * (2 * streams) + [acc] * (3 * streams)
    kernel = functools.partial(
        _pass1_front_kernel, n1=n1, bb=bb, la=la, lb=lb, m=m, sign=sign,
        kind=kind, variant=variant, nbits=nbits, streams=streams,
        windowed=windowed)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = PF.tpu_compiler_params(
            vmem_limit_bytes=_vmem_budget())
    with pk._ob_mode(interpret):
        outs = pl.pallas_call(
            kernel,
            grid=(n2 // bb,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            **kwargs,
        )(*operands)
    br = jnp.stack([outs[2 * s] for s in range(streams)])
    bi = jnp.stack([outs[2 * s + 1] for s in range(streams)])
    aux = jnp.stack([
        jnp.concatenate(outs[2 * streams + 3 * s:
                             2 * streams + 3 * s + 3], axis=0)
        for s in range(streams)])
    return br, bi, aux


@S.scoped(S.FFT_R2C)
def front_mean_power(aux: jnp.ndarray, n2: int, m: int) -> jnp.ndarray:
    """Per-stream RFI-s1 mean |X_k|^2 from the pass-1 accumulators
    ``aux [S, 3, 128]`` — rfi.mean_power_packed with the reduction
    moved one FFT level earlier: Parseval along the row transform
    gives sum|F|^2 = n2 * sum|B|^2, and F0 = sum_j2 B[0, j2].  Agrees
    with the packed form to f32 rounding (same ~1-ulp decision-flip
    caveat rfi.mean_power_packed documents)."""
    s2 = aux[:, 0, :].sum(axis=-1)
    f0r = aux[:, 1, :].sum(axis=-1)
    f0i = aux[:, 2, :].sum(axis=-1)
    return (n2 * s2 + 2.0 * f0r * f0i) / m


def _pass2_spec_kernel(*refs, n1, n2, rb, la, lb, m, kind, norm,
                       has_mask, has_premul, chirp):
    from jax.experimental import pallas as pl
    from srtb_tpu.ops import pallas_kernels as pk

    i = pl.program_id(0)
    a_re, a_im, b_re, b_im, c_re, c_im = refs[:6]
    pos = 6
    ncon = 6 if kind == "two" else 2
    cref = refs[pos:pos + ncon]
    pos += ncon
    thr_ref = refs[pos]
    mask_ref = refs[pos + 1]
    pos += 2
    pm = refs[pos:pos + 4] if has_premul else None
    out_re_ref, out_im_ref = refs[-2:]

    # row FFT of this step's k1 block
    zar, zai = _row_fft_block(a_re[:], a_im[:], cref, kind=kind,
                              n2=n2, rb=rb, la=la, lb=lb)
    # ... and of the MIRROR rows {n1-k1}: rows B[1:] of the reflected
    # block plus the first row of the next one ((G-i) mod G, which for
    # i == 0 wraps to this block's own row 0 — exactly the k1 = 0
    # self-mirror), reversed so Zm[t] is row n1-a-t
    mr = jnp.flip(jnp.concatenate([b_re[1:, :], c_re[0:1, :]], axis=0),
                  axis=0)
    mi = jnp.flip(jnp.concatenate([b_im[1:, :], c_im[0:1, :]], axis=0),
                  axis=0)
    zmr, zmi = _row_fft_block(mr, mi, cref, kind=kind, n2=n2, rb=rb,
                              la=la, lb=lb)
    # Hermitian mirror F[(m-k) mod m], k = k2*n1 + k1 blocked: a lane
    # flip (k2 -> n2-1-k2) for every k1 >= 1 row; the one global
    # k1 == 0 row additionally rolls by one (its mirror column is
    # (n2-k2) mod n2) — the blocked spelling of hermitian_rfft_post's
    # roll(flip(zf), 1)
    fmr = jnp.flip(zmr, axis=-1)
    fmi = jnp.flip(zmi, axis=-1)
    row0 = (jax.lax.broadcasted_iota(jnp.int32, (rb, 1), 0) == 0) \
        & (i == 0)
    fmr = jnp.where(row0, jnp.roll(fmr, 1, axis=-1), fmr)
    fmi = jnp.where(row0, jnp.roll(fmi, 1, axis=-1), fmi)
    fmi = -fmi  # conj
    even_re = 0.5 * (zar + fmr)
    even_im = 0.5 * (zai + fmi)
    odd_re = 0.5 * (zai - fmi)
    odd_im = -0.5 * (zar - fmr)
    if pm is not None:
        cr_, ci_, cwr, cwi = [r[:] for r in pm]
        xr = (cr_ * even_re - ci_ * even_im) \
            + (cwr * odd_re - cwi * odd_im)
        xi = (cr_ * even_im + ci_ * even_re) \
            + (cwr * odd_im + cwi * odd_re)
        k_int = None
    else:
        # true bin index of each blocked element (int32-exact, m <= 2^29)
        k_int = (i * rb
                 + jax.lax.broadcasted_iota(jnp.int32, (rb, n2), 0)) \
            + jax.lax.broadcasted_iota(jnp.int32, (rb, n2), 1) * n1
        wtr, wti = _phase_cos_sin(k_int, 2 * m, -1.0)
        xr = even_re + (wtr * odd_re - wti * odd_im)
        xi = even_im + (wtr * odd_im + wti * odd_re)
    # RFI stage 1 (rfi.mitigate_rfi_s1_given_mean): zap bins whose
    # power exceeds threshold*mean (thr holds the product), scale
    # survivors by the normalization coefficient, manual mask
    power = xr * xr + xi * xi
    scale = jnp.where(power <= thr_ref[0], jnp.float32(norm), 0.0)
    if has_mask:
        scale = scale * mask_ref[:]
    xr = xr * scale
    xi = xi * scale
    if chirp is not None and pm is None:
        # bankless: exact per-element df64 chirp phase in-register —
        # the blocked lanes stride k by n1, so the anchored-Taylor
        # fast path's contiguous-span premise doesn't hold here
        i_hi = (k_int & ~0xFFF).astype(jnp.float32)
        i_lo = (k_int & 0xFFF).astype(jnp.float32)
        ph = pk._chirp_phase_block(i_hi, i_lo, chirp["f_min"],
                                   chirp["df"], chirp["f_c"],
                                   chirp["dm"])
        c = jnp.cos(ph)
        s = jnp.sin(ph)
        xr, xi = xr * c - xi * s, xr * s + xi * c
    out_re_ref[:] = xr
    out_im_ref[:] = xi


@S.scoped(S.FFT_R2C)
def pass2_spectrum(br: jnp.ndarray, bi: jnp.ndarray, *, thr, norm: float,
                   mask_blocked=None, premul_blocked=None, chirp=None,
                   interpret: bool = False):
    """Pass 2 with the whole spectrum tail as its epilogue: row FFT
    over the [n1, n2] intermediate, the Hermitian R2C post-process
    assembled in-kernel (each grid step also transforms its mirror
    rows — ~2x the pass-2 FLOPs, which the dispatch-bound pipeline has
    headroom for, in exchange for never materializing the packed C2C
    spectrum), RFI-s1 zap/normalize/manual-mask against ``thr`` =
    threshold·mean (from :func:`front_mean_power`), and the
    dedispersion chirp — ``premul_blocked`` = (c_re, c_im, cw_re,
    cw_im) blocked [n1, n2] banks (the SegmentProcessor._premul_bank
    precombination), or ``chirp`` = dict(f_min, df, f_c, dm) for the
    bankless in-register df64 phase.  Emits the dedispersed spectrum
    directly, in k1-major blocked order (the consumer unblocks with a
    metadata-view transpose fused into its first read).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from srtb_tpu.ops import pallas_kernels as pk

    n1, n2 = br.shape
    m = n1 * n2
    has_mask = mask_blocked is not None
    has_premul = premul_blocked is not None
    rb = _block_rows_spec(n2, n1, has_mask, has_premul)
    if n1 % rb:
        raise ValueError(f"pass-2 block {rb} must divide n1={n1}")
    grid_n = n1 // rb
    kind, la, lb, consts = _leg(n2, inverse=False)
    row_block = pl.BlockSpec((rb, n2), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    mirror_block = pl.BlockSpec((rb, n2), lambda i: (grid_n - 1 - i, 0),
                                memory_space=pltpu.VMEM)
    next_block = pl.BlockSpec((rb, n2),
                              lambda i: ((grid_n - i) % grid_n, 0),
                              memory_space=pltpu.VMEM)
    in_specs = [row_block, row_block, mirror_block, mirror_block,
                next_block, next_block]
    operands = [br, bi, br, bi, br, bi]
    in_specs += _leg_specs(kind, la, lb)
    operands += list(consts)
    in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)]
    operands += [jnp.asarray(thr, jnp.float32).reshape(1)]
    if has_mask:
        in_specs += [row_block]
        operands += [mask_blocked]
    else:  # placeholder tile, never read by the kernel
        in_specs += [pl.BlockSpec((1, n2), lambda i: (0, 0),
                                  memory_space=pltpu.VMEM)]
        operands += [jnp.zeros((1, n2), jnp.float32)]
    if has_premul:
        in_specs += [row_block] * 4
        operands += list(premul_blocked)
    kernel = functools.partial(
        _pass2_spec_kernel, n1=n1, n2=n2, rb=rb, la=la, lb=lb, m=m,
        kind=kind, norm=np.float32(norm), has_mask=has_mask,
        has_premul=has_premul,
        chirp=None if chirp is None else dict(chirp))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = PF.tpu_compiler_params(
            vmem_limit_bytes=_vmem_budget())
    out = jax.ShapeDtypeStruct((n1, n2), jnp.float32)
    with pk._ob_mode(interpret):
        sr, si = pl.pallas_call(
            kernel,
            grid=(grid_n,),
            in_specs=in_specs,
            out_specs=[row_block, row_block],
            out_shape=[out, out],
            interpret=interpret,
            **kwargs,
        )(*operands)
    return sr, si
