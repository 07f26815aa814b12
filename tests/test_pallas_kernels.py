"""Pallas kernel tests validated against the jnp reference ops — the same
generic-vs-handwritten self-consistency strategy as the reference's unpack
tests.

Every case runs in interpret mode (CPU CI) and, when the default
backend is a real TPU, again non-interpret so the Mosaic lowering itself
is exercised — interpret mode routinely accepts kernels Mosaic rejects
(layouts, unsupported primitives).  tests/test_tpu_compile.py asks the
chip's compiler without a chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from srtb_tpu.ops import dedisperse as dd
from srtb_tpu.ops import pallas_kernels as pk


@pytest.fixture(params=["interpret", "mosaic"])
def interpret(request):
    if request.param == "mosaic":
        if jax.default_backend() != "tpu":
            pytest.skip("the Mosaic leg needs a chip")
        return False
    return True


def test_dedisperse_df64_kernel_matches_host_chirp(interpret):
    n = 1 << 15
    f_min, bw, dm = 1405.0, 64.0, 150.0
    f_c = f_min + bw
    df = bw / n
    rng = np.random.default_rng(0)
    spec = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(
        np.complex64)
    spec_ri = jnp.stack([jnp.asarray(spec.real), jnp.asarray(spec.imag)])

    out_ri = np.asarray(pk.dedisperse_df64(spec_ri, f_min, df, f_c, dm,
                                           interpret=interpret))
    got = out_ri[0] + 1j * out_ri[1]
    expected = spec * dd.chirp_factor_host(n, f_min, df, f_c, dm)
    # df64 phase error ~1e-5 turns; compare phasors
    err = np.abs(got - expected)
    assert np.max(err) < 5e-3 * np.max(np.abs(spec))


def test_dedisperse_df64_kernel_high_dm(interpret):
    """|k| ~ 1e9 regime (J1644-style high DM)."""
    n = 1 << 12
    f_min, bw, dm = 1437.0, -64.0, -478.80
    f_c = f_min + bw
    df = bw / n
    spec = np.ones(n, dtype=np.complex64)
    spec_ri = jnp.stack([jnp.ones(n, jnp.float32), jnp.zeros(n, jnp.float32)])
    out_ri = np.asarray(pk.dedisperse_df64(spec_ri, f_min, df, f_c, dm,
                                           interpret=interpret))
    got = out_ri[0] + 1j * out_ri[1]
    expected = np.asarray(dd.chirp_factor_host(n, f_min, df, f_c, dm))
    # unit-magnitude phasors with df64-level phase accuracy
    np.testing.assert_allclose(np.abs(got), 1.0, atol=1e-5)
    phase_err = np.abs(np.angle(got * np.conj(expected)))
    assert np.percentile(phase_err, 99) < 2e-2
    del spec


def test_sk_zap_timeseries_matches_jnp(interpret):
    """Fused SK kernel vs an independent float64 numpy oracle.

    Deliberately no complex device arrays: the kernel's own boundary
    is (re, im) f32, so the test honors it too.
    Threshold 1.2 keeps every row's SK decision >= 0.1 from a boundary
    (at 1.05 a clean row sat 1.4e-4 from the cut: f32-reorder flaky).
    """
    from srtb_tpu.ops import detect as det
    from srtb_tpu.ops import rfi

    nfreq, ntime = 32, 1024
    rng = np.random.default_rng(5)
    wf = (rng.standard_normal((nfreq, ntime))
          + 1j * rng.standard_normal((nfreq, ntime))).astype(np.complex64)
    # make some rows RFI-like so SK zaps them, and one row exactly zero
    wf[3] *= np.exp(1j * 0.1) * (1 + 10 * (rng.random(ntime) < 0.01))
    wf[7] = 0.0
    wf[12] *= 5.0 * np.sin(np.arange(ntime) * 0.3) ** 2

    sk_threshold = 1.2
    wf_ri = jnp.stack([jnp.asarray(wf.real.copy()),
                       jnp.asarray(wf.imag.copy())])
    out_ri, zero_count, ts = pk.sk_zap_timeseries(wf_ri, sk_threshold,
                                                  interpret=interpret)

    # float64 oracle of the SK decision (formula:
    # spectrum/rfi_mitigation.hpp:290-341, thresholds shared via
    # sk_decision_thresholds so the decision rule cannot drift)
    x2 = np.abs(wf.astype(np.complex128)) ** 2
    s2 = x2.sum(-1)
    s4 = (x2 * x2).sum(-1)
    with np.errstate(invalid="ignore"):
        sk = ntime * s4 / (s2 * s2)
    thr_low, thr_high = rfi.sk_decision_thresholds(ntime, sk_threshold)
    zap = (sk > thr_high) | (sk < thr_low)
    margin = np.nanmin(np.minimum(np.abs(sk - thr_low),
                                  np.abs(sk - thr_high)))
    assert margin > 0.05, f"borderline SK row (margin {margin})"
    expected_wf = np.where(zap[:, None], 0, wf).astype(np.complex64)
    # some but not all rows must be zapped for the test to mean anything
    assert 0 < int(zap.sum()) < nfreq

    got_wf = np.asarray(out_ri[0]) + 1j * np.asarray(out_ri[1])
    np.testing.assert_allclose(got_wf, expected_wf, rtol=1e-5, atol=1e-5)

    expected_zero = int((zap | (x2[:, 0] == 0)).sum())
    assert int(zero_count) == expected_zero
    expected_ts = np.abs(expected_wf) ** 2
    np.testing.assert_allclose(np.asarray(ts), expected_ts.sum(axis=0),
                               rtol=1e-4, atol=1e-4)

    # chained through the split-out ladder: DetectResult consistency on
    # real-only inputs (no complex crosses the device boundary)
    got_det = det.detect_from_time_series(
        jnp.asarray(ts)[None], jnp.asarray([zero_count]), 8.0, 64)
    ref_det = det.detect_from_time_series(
        jnp.asarray(expected_ts.sum(axis=0).astype(np.float32))[None],
        jnp.asarray([expected_zero]), 8.0, 64)
    np.testing.assert_allclose(np.asarray(got_det.time_series),
                               np.asarray(ref_det.time_series),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(got_det.signal_counts),
                          np.asarray(ref_det.signal_counts))


def test_dedisperse_df64_kernel_high_channel_offset(interpret):
    """The in-kernel chirp must stay phase-accurate when the global
    channel index exceeds float32's exact-integer range (2^24)."""
    n = 1 << 12
    i0 = (1 << 26) + 1024
    n_spec = 1 << 27
    f_min, bw, dm = 1405.0 + 32.0, -64.0, -478.80
    f_c = f_min + bw
    df = bw / n_spec
    rng = np.random.default_rng(1)
    spec = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(
        np.complex64)
    spec_ri = jnp.stack([jnp.asarray(spec.real), jnp.asarray(spec.imag)])
    out_ri = np.asarray(pk.dedisperse_df64(spec_ri, f_min, df, f_c, dm,
                                           interpret=interpret, i0=i0))
    got = out_ri[0] + 1j * out_ri[1]

    i = np.arange(i0, i0 + n, dtype=np.float64)
    f = f_min + df * i
    delta_f = f - f_c
    k = (dd.D * 1e6) * dm / f * (delta_f / f_c) ** 2
    chirp = np.exp(-2j * np.pi * np.modf(k)[0]).astype(np.complex64)
    err = np.abs(got - spec * chirp)
    assert err.max() < 5e-3 * np.abs(spec).max(), err.max()


@pytest.mark.parametrize("with_mask", [False, True])
def test_rfi_s1_dedisperse_fused_matches_jnp_sequence(interpret, with_mask):
    """The fused RFI-s1 + chirp kernel must reproduce the jnp sequence
    mitigate_rfi_average_and_normalize -> mitigate_rfi_manual -> chirp
    multiply (ref: rfi_mitigation_pipe.hpp:50-94 + dedisperse_pipe)."""
    from srtb_tpu.ops import rfi

    n = 1 << 15
    f_min, bw, dm = 1405.0, 64.0, 150.0
    f_c = f_min + bw
    df = bw / n
    threshold, norm = 1.8, 0.125
    rng = np.random.default_rng(7)
    spec = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(
        np.complex64)
    spec[100] *= 30.0  # guarantee at least one zapped channel
    mask = None
    if with_mask:  # zap mask: True = zero the bin (rfi.rfi_ranges_to_mask)
        mask_np = np.zeros(n, bool)
        mask_np[2048:4096] = True
        mask = jnp.asarray(mask_np)
    spec_ri = jnp.stack([jnp.asarray(spec.real), jnp.asarray(spec.imag)])

    out_ri = np.asarray(pk.rfi_s1_dedisperse_df64(
        spec_ri, threshold, norm, f_min, df, f_c, dm, mask=mask,
        interpret=interpret))
    got = out_ri[0] + 1j * out_ri[1]

    want = rfi.mitigate_rfi_average_and_normalize(
        jnp.asarray(spec)[None, :], threshold, norm)
    want = rfi.mitigate_rfi_manual(want, mask)[0]
    want = np.asarray(want) * dd.chirp_factor_host(n, f_min, df, f_c, dm)
    assert np.max(np.abs(got - want)) < 5e-3 * np.max(np.abs(want))


def test_pallas_chirp_exact_fallback_path(monkeypatch):
    """The exact per-element in-kernel chirp (the anchored rewrite's
    fallback, forced via SRTB_PALLAS_CHIRP_EXACT=1) must still match the
    f64 host chirp — a regression here would ship silently since every
    physical config otherwise takes the anchored path."""
    from srtb_tpu.ops import dedisperse as dd

    monkeypatch.setenv("SRTB_PALLAS_CHIRP_EXACT", "1")
    n = 1 << 12
    f_min, bw, dm = 1405.0 + 32.0, -64.0, -478.80
    f_c = f_min + bw
    df = bw / (1 << 22)  # flagship-scale df; i0=0 slice of it
    rng = np.random.default_rng(5)
    spec = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(
        np.complex64)
    spec_ri = jnp.stack([jnp.asarray(spec.real), jnp.asarray(spec.imag)])
    assert pk._chirp_consts(n, f_min, df, f_c, dm, 0) is None  # knob works
    out_ri = np.asarray(pk.dedisperse_df64(spec_ri, f_min, df, f_c, dm,
                                           interpret=True))
    got = out_ri[0] + 1j * out_ri[1]
    host = dd.chirp_factor_host(n, f_min, df, f_c, dm)
    err = np.abs(got - spec * host)
    assert err.max() < 5e-3 * np.abs(spec).max(), err.max()


@pytest.mark.parametrize("nbits", [1, 2, 4])
def test_use_pallas_on_subbyte_samples_unpacks_in_xla(nbits):
    """`use_pallas` on the blocked sub-byte path: the kernels are the
    chirp's and the waterfall's, the unpack is XLA's blocked planes
    (`ops/unpack.unpack_subbyte_planes`; the Pallas unpack kernels went
    in PR 50, no chip compiled them), and the windowed waterfall is the
    plan's without the kernels."""
    from srtb_tpu.config import Config
    from srtb_tpu.pipeline.segment import SegmentProcessor, \
        waterfall_to_numpy

    cfg = Config(
        baseband_input_count=1 << 14,
        baseband_input_bits=nbits,
        baseband_format_type="simple",
        baseband_freq_low=1405.0,
        baseband_bandwidth=64.0,
        baseband_sample_rate=128e6,
        dm=30.0,
        spectrum_channel_count=1 << 5,
        mitigate_rfi_average_method_threshold=1e9,
        mitigate_rfi_spectral_kurtosis_threshold=1e9,
        baseband_reserve_sample=False,
        fft_strategy="four_step",
    )
    rng = np.random.default_rng(4 + nbits)
    raw = rng.integers(0, 256, cfg.segment_bytes(1), dtype=np.uint8)
    base = SegmentProcessor(cfg, window_name="hamming")
    with_kernels = SegmentProcessor(cfg.replace(use_pallas=True),
                                    window_name="hamming")
    assert base._blocked_subbyte and with_kernels._blocked_subbyte
    assert with_kernels.chirp is None and base.chirp is not None
    assert not [name for name in dir(pk) if "unpack" in name]
    want = waterfall_to_numpy(base.process(raw)[0])
    got = waterfall_to_numpy(with_kernels.process(raw)[0])
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-4 * np.abs(want).max())
