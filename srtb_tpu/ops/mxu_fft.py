"""FFT on the MXU: radix-128 DFT stages as systolic-array matmuls.

XLA's TPU FFT runs the segment R2C far off the HBM roof (a v5e: 44.7 ms
for 2^27 samples alone, 1.3 ms of traffic; PERF.md section 6, PR 43).
The FLOPs of an FFT are tiny (5 n log2 n), so on a machine
whose matmul throughput is nearly free relative to HBM bandwidth, the
TPU-native formulation is the classic one from the supercomputing
literature: factor the DFT into radix-r stages and execute each stage as
a batched [r, r] DFT-matrix multiply on the MXU,

    DFT_n = (DFT_r tensor I_{n/r}) . twiddle . (I_r tensor DFT_{n/r}),

recursing on n/r.  With r = 128 each stage contracts a 128-point axis
against a constant [128, 128] DFT matrix — exactly the shape the MXU
tiles natively — and an n = 2^26 transform is 3 matmul stages plus one
small base case instead of one opaque XLA FFT op.

Complex arithmetic is split re/im (4 real matmuls per stage;
``jax.lax.Precision.HIGHEST`` keeps f32 accuracy through the bf16 MXU
passes).  Twiddle phases are generated from *integer* index products
reduced mod n and split hi/lo before the float conversion (same
precision discipline as ops/fft.py `_phase_exp` — a plain f32 phase at
n = 2^26 is wrong by whole turns).

This file implements the C2C transform (`mxu_fft`) with the same
unnormalized forward/backward conventions as ops/fft.py; `segment_rfft`
exposes it as ``fft_strategy="mxu"`` through the same half-size packed
C2C + Hermitian post-process used by the four-step path.

Reference roles covered: the vendor-FFT dispatcher's "another backend"
slot (ref: fft/fft.hpp:54-160) — this is a backend XLA does not
provide, not a wrapper over one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Radix: the MXU's native tile edge.  The recursion bottoms out at
# lengths <= _RADIX with a single DFT-matrix contraction.
_RADIX = 128

# 6-pass bf16 by default; SRTB_MXU_PRECISION=high selects 3-pass bf16
# (pallas_fft runs 3-pass at even longer contractions with ~1e-6
# relative error on chip) — the accuracy x throughput A/B at this
# radix needs a hardware probe before any default flip.  Read at trace time.
def _precision():
    import os
    return (jax.lax.Precision.HIGH
            if os.environ.get("SRTB_MXU_PRECISION", "") == "high"
            else jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _dft_matrix(r: int, inverse: bool):
    """Constant [r, r] DFT matrix as (re, im) float32 numpy arrays,
    computed in float64.  W[j, k] = exp(-+2*pi*i*j*k/r)."""
    j = np.arange(r, dtype=np.float64)[:, None]
    k = np.arange(r, dtype=np.float64)[None, :]
    sign = 2.0 if inverse else -2.0
    w = np.exp(sign * 1j * np.pi * j * k / r)
    return (w.real.astype(np.float32), w.imag.astype(np.float32))


def _phase_ri(r: jnp.ndarray, n: int, inverse: bool):
    """(cos, sin) of sign*2*pi*r/n for int32 residues r in [0, n) with
    the hi/lo split keeping the phase exact beyond f32's 24-bit range
    (mirrors ops/fft.py `_phase_exp`, but on split planes)."""
    half = 1 << max(n.bit_length() // 2, 1)
    sign = 1.0 if inverse else -1.0
    scale = jnp.float32(sign * 2.0 * np.pi / n)
    a = ((r // half) * half).astype(jnp.float32) * scale
    b = (r % half).astype(jnp.float32) * scale
    ca, sa = jnp.cos(a), jnp.sin(a)
    cb, sb = jnp.cos(b), jnp.sin(b)
    return ca * cb - sa * sb, sa * cb + ca * sb


def _dft_contract(ar: jnp.ndarray, ai: jnp.ndarray, r: int, inverse: bool):
    """DFT over the length-r axis -2 of [..., r, t]: four real matmuls
    against the constant [r, r] matrix, MXU-shaped (the t axis provides
    the systolic array's streaming dimension)."""
    wr_np, wi_np = _dft_matrix(r, inverse)
    wr, wi = jnp.asarray(wr_np), jnp.asarray(wi_np)
    # y[..., k, t] = sum_j W[j, k] * a[..., j, t]
    def mm(w, x):
        return jnp.einsum("jk,...jt->...kt", w, x, precision=_precision())
    yr = mm(wr, ar) - mm(wi, ai)
    yi = mm(wr, ai) + mm(wi, ar)
    return yr, yi


def _fft_ri(ar: jnp.ndarray, ai: jnp.ndarray, inverse: bool,
            radix: int = _RADIX):
    """Recursive radix C2C over the last axis of (re, im) planes."""
    n = ar.shape[-1]
    if n <= radix:
        # single contraction: y[..., k] = sum_j a[..., j] W[j, k]
        wr_np, wi_np = _dft_matrix(n, inverse)
        wr, wi = jnp.asarray(wr_np), jnp.asarray(wi_np)
        def mm(x, w):
            return jnp.einsum("...j,jk->...k", x, w, precision=_precision())
        return (mm(ar, wr) - mm(ai, wi), mm(ai, wr) + mm(ar, wi))
    n1 = radix
    n2 = n // n1
    # x[j1*n2 + j2] viewed as [j1, j2]
    ar = ar.reshape(*ar.shape[:-1], n1, n2)
    ai = ai.reshape(*ai.shape[:-1], n1, n2)
    # stage: A[k1, j2] = sum_j1 W_n1[j1, k1] a[j1, j2]  (MXU contraction)
    ar, ai = _dft_contract(ar, ai, n1, inverse)
    # twiddle W_n^{k1*j2}: integer residue mod n stays exact in int32
    k1 = jax.lax.iota(jnp.int32, n1)[:, None]
    j2 = jax.lax.iota(jnp.int32, n2)[None, :]
    tw_r, tw_i = _phase_ri((k1 * j2) % n, n, inverse)
    ar, ai = ar * tw_r - ai * tw_i, ai * tw_r + ar * tw_i
    # recurse over j2 (last axis), batched over k1
    br, bi = _fft_ri(ar, ai, inverse, radix)
    # X[k2*n1 + k1] = B[k1, k2] -> [k2, k1] then flatten
    br = jnp.swapaxes(br, -1, -2).reshape(*br.shape[:-2], n)
    bi = jnp.swapaxes(bi, -1, -2).reshape(*bi.shape[:-2], n)
    return br, bi


def mxu_fft(x: jnp.ndarray, inverse: bool = False,
            radix: int = _RADIX) -> jnp.ndarray:
    """1-D C2C FFT of power-of-two length via MXU DFT-matmul stages.
    Unnormalized both directions (same conventions as four_step_fft);
    leading dims batch."""
    n = x.shape[-1]
    if n & (n - 1):
        raise ValueError("mxu_fft requires power-of-two length")
    if radix < 2 or radix & (radix - 1) or radix > 2048:
        raise ValueError("radix must be a power of two in [2, 2048]")
    yr, yi = _fft_ri(jnp.real(x), jnp.imag(x), inverse, radix)
    return jax.lax.complex(yr, yi)
