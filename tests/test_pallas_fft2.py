"""Fused two-pass Pallas four-step C2C (ops/pallas_fft2) vs numpy.

CPU CI runs interpret mode at the smallest supported size (m = 2^24 —
the module deliberately only covers the segment sizes where monolithic
XLA falters); on a real TPU the same cases lower through Mosaic.
The tolerance is looser than the single-level row kernel's: the value
passes through four bf16x3 DFT-matmul levels plus two twiddle stages.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from srtb_tpu.ops import fft as F
from srtb_tpu.ops import pallas_fft2 as PF2

ON_TPU = jax.default_backend() == "tpu"
INTERPRET = not ON_TPU

M = 1 << 24  # smallest pallas2 size (n1=4096, n2=4096)


def _rand_c64(shape, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def test_factorization_window():
    assert PF2._factor(M) == (4096, 4096)
    assert PF2._factor(1 << 26) == (4096, 1 << 14)
    assert PF2._factor(1 << 29) == (8192, 1 << 16)
    assert not PF2.supported(1 << 23)   # below the window
    assert not PF2.supported(1 << 30)   # above the window
    assert not PF2.supported(3 * (1 << 22))  # not a power of two


@pytest.mark.parametrize("inverse", [False, True])
def test_fft2_matches_numpy(inverse):
    x = _rand_c64(M, 7 + inverse)
    want = (np.fft.ifft(x.astype(np.complex128), norm="forward") if inverse
            else np.fft.fft(x.astype(np.complex128)))
    got = np.asarray(PF2.fft2_c2c(jnp.asarray(x), inverse=inverse,
                                  interpret=INTERPRET))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() / scale < 2e-5


# (the pass-1 row spelling and the rows-helper A/B knobs were retired in
# round 5: real Mosaic rejects their in-kernel minor-lb reshapes, so the
# column-native pass 1 + the single vmem_fft_rows spelling are the one
# lowering — covered by every other oracle test in this file)


def test_fft2_blocked_output_unblocks():
    x = _rand_c64(M, 3)
    want = np.fft.fft(x.astype(np.complex128))
    raw = PF2.fft2_c2c(jnp.asarray(x), natural=False, interpret=INTERPRET)
    got = np.asarray(PF2.unblock(raw, M))
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-5


def test_fft2_leading_dims():
    x = _rand_c64((2, M), 5)
    want = np.fft.fft(x.astype(np.complex128))
    got = np.asarray(PF2.fft2_c2c(jnp.asarray(x), interpret=INTERPRET))
    assert got.shape == x.shape
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-5


def test_segment_rfft_pallas2_strategy():
    """End-to-end R2C through the pallas2 strategy (pack + two-pass C2C +
    Hermitian post) against the monolithic rfft at n = 2^25."""
    n = 2 * M
    rng = np.random.default_rng(11)
    x = rng.standard_normal(n).astype(np.float32)
    want = np.fft.rfft(x.astype(np.float64))[:-1]
    got = np.asarray(F.segment_rfft(
        jnp.asarray(x), "pallas2_interpret" if INTERPRET else "pallas2"))
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-5


def test_rfft_subbyte_pallas2_blocked_planes():
    """The blocked-plane sub-byte R2C with pallas2 plane FFTs (the
    production 2^30 ingest composition) against the f64 oracle: 4-bit
    (count=2, one packed plane of length n/2 = 2^24)."""
    from srtb_tpu.ops import unpack as U

    n = 2 * M
    rng = np.random.default_rng(17)
    raw = rng.integers(0, 256, n // 2, dtype=np.uint8)
    x = np.asarray(U.unpack(jnp.asarray(raw), 4, None)).astype(np.float64)
    want = np.fft.rfft(x)[:-1]
    got = np.asarray(F.rfft_subbyte(
        jnp.asarray(raw), 4,
        "pallas2_interpret" if INTERPRET else "pallas2"))
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-5


def test_segment_rfft_pallas2_small_falls_back():
    """Below the pallas2 window the strategy silently takes the
    pallas-legs four-step — tiny configs must not crash."""
    n = 1 << 16
    rng = np.random.default_rng(13)
    x = rng.standard_normal(n).astype(np.float32)
    want = np.fft.rfft(x.astype(np.float64))[:-1]
    got = np.asarray(F.segment_rfft(
        jnp.asarray(x), "pallas2_interpret" if INTERPRET else "pallas2"))
    assert np.abs(got - want).max() / np.abs(want).max() < 5e-6


def test_fourstep_twiddle_precision_at_window_edge():
    """The in-kernel hi/lo phase split must stay accurate at the top of
    the window (m = 2^29, residues up to 2^29 — far beyond f32's 24-bit
    mantissa), where an end-to-end CPU-interpret test is impractical.
    Checked against float64 on the worst blocks: the highest j2 rows
    (largest residues) and a mid-spectrum block."""
    m = 1 << 29
    n1, n2 = PF2._factor(m)
    for j2_0 in (n2 - 8, n2 // 2):
        wr, wi = jax.jit(
            lambda j0: PF2._fourstep_twiddle_t(n1, 8, m, -1.0, j0),
            static_argnums=0)(j2_0)
        k1 = np.arange(n1)[:, None]
        d = np.arange(8)[None, :] + j2_0
        want = np.exp(-2j * np.pi * (d * k1).astype(np.float64) / m)
        err = np.abs((np.asarray(wr) + 1j * np.asarray(wi)) - want).max()
        assert err < 2e-6, (j2_0, err)


def test_fft2_asymmetric_factorization():
    """m = 2^25 factors 4096 x 8192 (n2 != n1, lb2=64) — the asymmetric
    shape every production size [2^25, 2^29] uses; the symmetric
    m = 2^24 tests alone would never exercise distinct leg lengths or
    the rectangular four-step twiddle."""
    m = 1 << 25
    assert PF2._factor(m) == (4096, 8192)
    x = _rand_c64(m, 41)
    want = np.fft.fft(x.astype(np.complex128))
    got = np.asarray(PF2.fft2_c2c(jnp.asarray(x), interpret=INTERPRET))
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-5


def test_block_sizing_budgets_padded_footprint(monkeypatch):
    """Round-3 advisor catch: blocks must be sized from the PADDED VMEM
    footprint (bb < 128 lane-pads to 128 across 2x-pipelined in/out
    refs), not logical f32 words.  Pins: lane-dense pass-1 blocks at
    every supported factorization, the modeled footprint staying inside
    the budget, and the absolute env overrides surviving."""
    monkeypatch.delenv("SRTB_PALLAS2_BB", raising=False)
    monkeypatch.delenv("SRTB_PALLAS2_RB", raising=False)
    monkeypatch.delenv("SRTB_PALLAS2_VMEM_MB", raising=False)
    budget = PF2._vmem_budget()
    for log2m in range(24, 30):
        n1, n2 = PF2._factor(1 << log2m)
        bb = PF2._block_cols(n1, n2)
        rb = PF2._block_rows(n2, n1)
        assert bb >= 128 and n2 % bb == 0, (log2m, bb)
        assert rb >= 8 and n1 % rb == 0, (log2m, rb)
        assert PF2._pass1_bytes(n1, bb) <= budget, log2m
        assert PF2._pass2_bytes(n2, rb) <= budget, log2m
    # refs alone at the padded minimum exceed a 16 MiB-era budget: the
    # floor is returned (a vmem_limit question, not a sizing one)
    monkeypatch.setenv("SRTB_PALLAS2_VMEM_MB", "14")
    assert PF2._block_cols(8192, 1 << 16) == 128
    monkeypatch.setenv("SRTB_PALLAS2_BB", "64")
    monkeypatch.setenv("SRTB_PALLAS2_RB", "16")
    assert PF2._block_cols(4096, 4096) == 64
    assert PF2._block_rows(4096, 4096) == 16
