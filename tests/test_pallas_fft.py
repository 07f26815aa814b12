"""Pallas row-FFT kernel (ops/pallas_fft) vs numpy oracles.

CPU CI runs interpret mode; on a real TPU the same
cases lower through Mosaic (layouts/tiling differ from interpret — the
round-1 lesson is that only a hardware run proves a Pallas kernel).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from srtb_tpu.ops import pallas_fft as PF

ON_TPU = jax.default_backend() == "tpu"
INTERPRET = not ON_TPU


@pytest.mark.parametrize("batch,length", [(16, 1 << 13), (4, 1 << 15),
                                          (2, 1 << 16)])
@pytest.mark.parametrize("inverse", [False, True])
def test_fft_rows_matches_numpy(batch, length, inverse):
    rng = np.random.default_rng(length + inverse)
    x = (rng.standard_normal((batch, length))
         + 1j * rng.standard_normal((batch, length))).astype(np.complex64)
    want = (np.fft.ifft(x, norm="forward") if inverse
            else np.fft.fft(x.astype(np.complex128)))
    got = np.asarray(PF.fft_rows(jnp.asarray(x), inverse=inverse,
                                 interpret=INTERPRET))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() / scale < 5e-6


def test_fft_rows_leading_dims_and_support():
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((2, 3, 1 << 13))
         + 1j * rng.standard_normal((2, 3, 1 << 13))).astype(np.complex64)
    got = np.asarray(PF.fft_rows(jnp.asarray(x), interpret=INTERPRET))
    want = np.fft.fft(x)
    assert np.abs(got - want).max() / np.abs(want).max() < 5e-6
    assert not PF.supported(1 << 11, 4)   # below the supported range
    assert not PF.supported(3 * 1024, 4)  # not a power of two
    assert PF.supported(1 << 16, 1)


def test_fft_rows_matches_waterfall_convention():
    """The waterfall backward C2C convention (unnormalized inverse,
    ops.fft.c2c_backward) must be reproduced exactly by inverse mode."""
    from srtb_tpu.ops import fft as F

    rng = np.random.default_rng(1)
    x = (rng.standard_normal((4, 1 << 13))
         + 1j * rng.standard_normal((4, 1 << 13))).astype(np.complex64)
    want = np.asarray(F.c2c_backward(jnp.asarray(x)))
    got = np.asarray(PF.fft_rows(jnp.asarray(x), inverse=True,
                                 interpret=INTERPRET))
    assert np.abs(got - want).max() / np.abs(want).max() < 5e-6


def test_pallas_waterfall_in_pipeline_matches_jnp():
    """use_pallas with a supported watfft length takes the Pallas row-FFT
    waterfall branch (pipeline/segment._spectrum_tail); output must match
    the XLA waterfall path."""
    from srtb_tpu.config import Config
    from srtb_tpu.pipeline.segment import SegmentProcessor

    n = 1 << 16  # n_spectrum 2^15, 4 channels -> watfft_len 2^13
    rng = np.random.default_rng(3)
    raw = rng.integers(0, 256, size=n // 4, dtype=np.uint8)
    base = dict(
        baseband_input_count=n, baseband_input_bits=2,
        baseband_format_type="simple", baseband_freq_low=1405.0,
        baseband_bandwidth=64.0, baseband_sample_rate=128e6, dm=5.0,
        spectrum_channel_count=4,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        signal_detect_max_boxcar_length=16,
        baseband_reserve_sample=False)
    ref = SegmentProcessor(Config(**base))
    pal = SegmentProcessor(Config(use_pallas=True, **base))
    fused = SegmentProcessor(Config(use_pallas=True, use_pallas_sk=True,
                                    **base))
    assert PF.supported(pal.watfft_len, pal.channel_count)
    wf_a, res_a = ref.process(raw)
    wf_a = np.asarray(wf_a)
    scale = np.abs(wf_a).max()
    for name, proc in (("wf", pal), ("wf+sk", fused)):
        wf_b, res_b = proc.process(raw)
        np.testing.assert_allclose(np.asarray(wf_b), wf_a,
                                   atol=5e-3 * scale, rtol=0,
                                   err_msg=name)
        assert np.array_equal(np.asarray(res_a.signal_counts),
                              np.asarray(res_b.signal_counts)), name
        assert np.array_equal(np.asarray(res_a.zero_count),
                              np.asarray(res_b.zero_count)), name


def test_pallas_fft_strategy_matches_monolithic():
    """fft_strategy='pallas' (four-step with Pallas row legs) through the
    full segment processor must match the monolithic XLA path."""
    from srtb_tpu.config import Config
    from srtb_tpu.pipeline.segment import SegmentProcessor

    n = 1 << 16
    rng = np.random.default_rng(5)
    raw = rng.integers(0, 256, size=n // 4, dtype=np.uint8)
    base = dict(
        baseband_input_count=n, baseband_input_bits=2,
        baseband_format_type="simple", baseband_freq_low=1405.0,
        baseband_bandwidth=64.0, baseband_sample_rate=128e6, dm=5.0,
        spectrum_channel_count=8,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        signal_detect_max_boxcar_length=16,
        baseband_reserve_sample=False)
    ref = SegmentProcessor(Config(fft_strategy="monolithic", **base))
    pal = SegmentProcessor(Config(fft_strategy="pallas", **base))
    wf_a, res_a = ref.process(raw)
    wf_b, res_b = pal.process(raw)
    wf_a, wf_b = np.asarray(wf_a), np.asarray(wf_b)
    scale = np.abs(wf_a).max()
    np.testing.assert_allclose(wf_b, wf_a, atol=5e-3 * scale, rtol=0)
    assert np.array_equal(np.asarray(res_a.signal_counts),
                          np.asarray(res_b.signal_counts))


@pytest.mark.parametrize("length", [1 << 12, 1 << 13])
def test_fft_rows_small_lengths(length):
    rng = np.random.default_rng(length)
    x = (rng.standard_normal((8, length))
         + 1j * rng.standard_normal((8, length))).astype(np.complex64)
    got = np.asarray(PF.fft_rows(jnp.asarray(x), interpret=INTERPRET))
    want = np.fft.fft(x.astype(np.complex128))
    assert np.abs(got - want).max() / np.abs(want).max() < 5e-6


def test_fft_rows_stats_matches_jnp():
    """fft_rows_stats_ri: inverse FFT + de-window + power moments must
    match the jnp sequence (c2c_backward -> divide -> |x|^2 sums)."""
    from srtb_tpu.ops import fft as F

    rng = np.random.default_rng(11)
    B, L = 6, 1 << 13
    x = (rng.standard_normal((B, L))
         + 1j * rng.standard_normal((B, L))).astype(np.complex64)
    dewin = (0.5 + rng.random(L)).astype(np.float32)
    wr, wi, s2p, s4p = PF.fft_rows_stats_ri(
        jnp.asarray(x.real), jnp.asarray(x.imag), inverse=True,
        dewindow=jnp.asarray(dewin), interpret=INTERPRET)
    want = np.asarray(F.c2c_backward(jnp.asarray(x))) / dewin
    got = np.asarray(wr) + 1j * np.asarray(wi)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 5e-5 * scale
    p = np.abs(want) ** 2
    np.testing.assert_allclose(np.asarray(s2p).sum(-1), p.sum(-1),
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s4p).sum(-1), (p * p).sum(-1),
                               rtol=1e-3)


def test_fft_rows_stats_no_dewindow():
    """The stats variant without a de-window vector (the placeholder-tile
    branch) is the same transform as the plain inverse FFT, with correct
    finished moment sums regardless of the partials' lane grouping."""
    import numpy as np

    rng = np.random.default_rng(77)
    x = (rng.standard_normal((8, 1 << 13))
         + 1j * rng.standard_normal((8, 1 << 13))).astype(np.complex64)
    re, im, s2, s4 = PF.fft_rows_stats_ri(
        jnp.real(jnp.asarray(x)), jnp.imag(jnp.asarray(x)),
        inverse=True, interpret=INTERPRET)
    want = np.asarray(jnp.fft.ifft(x, norm="forward"))
    got2 = np.asarray(re) + 1j * np.asarray(im)
    assert np.abs(got2 - want).max() / np.abs(want).max() < 5e-6
    p = np.abs(got2) ** 2
    np.testing.assert_allclose(np.asarray(s2).sum(-1), p.sum(-1),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s4).sum(-1), (p * p).sum(-1),
                               rtol=1e-4)


def test_row_block_vmem_budget_knob(monkeypatch):
    """SRTB_PALLAS_VMEM_MB scales the row-block plan for hardware A/B;
    unset keeps the proven 1 MB-plane default bit-identical."""
    from srtb_tpu.ops import pallas_fft as PF

    monkeypatch.delenv("SRTB_PALLAS_VMEM_MB", raising=False)
    base = PF._row_block(1 << 14, 1 << 11)      # 2^18/2^14 = 16 rows
    assert base == 16
    # unset: the block plan keeps the proven default, but the Mosaic
    # scoped-vmem limit is ALWAYS set (100 MiB; the compiler default is
    # far below the v5e's 128 MiB and the L=2^16 leg overflows it)
    kw0 = PF._call_kwargs(interpret=False)
    assert kw0["compiler_params"].vmem_limit_bytes == 100 << 20
    monkeypatch.setenv("SRTB_PALLAS_VMEM_MB", "56")
    big = PF._row_block(1 << 14, 1 << 11)
    assert big > base and (1 << 11) % big == 0
    kw = PF._call_kwargs(interpret=False)
    assert kw["compiler_params"].vmem_limit_bytes == 56 << 20
    assert PF._call_kwargs(interpret=True) == {}
    # padded accounting: the helper's lb<128 stage/output padding must
    # shrink the block on the small-length end (lb=32 pads 4x)
    for length in (1 << 12, 1 << 13, 1 << 16):
        rows = PF._rows_budget_padded(length, 56 << 20)
        la, lb = PF._split_la_lb(length)
        plb = max(lb, 128)
        refs = 2 * 2 * rows * (length + la * plb) * 4
        live = 6 * la * rows * plb * 4
        assert refs + live <= 56 << 20, (length, rows)
    # degenerate values fail loudly and identically for both readers
    monkeypatch.setenv("SRTB_PALLAS_VMEM_MB", "0")
    with pytest.raises(ValueError):
        PF._row_block(1 << 14, 1 << 11)
    with pytest.raises(ValueError):
        PF._call_kwargs(interpret=False)
