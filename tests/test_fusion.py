"""Spectrum-pass fusion: fused vs unfused plans.

The fused spectrum tail (Config.fused_tail) folds RFI stage 1 + the
dedispersion chirp into the forward FFT's final (Hermitian post) pass,
and — with both Pallas knobs — the SK zap + detection time series into
the waterfall FFT's write (ops/pallas_fft.fft_rows_skzap_ri).  These
tests pin:

- numeric parity of fused vs unfused plans on synthetic dispersed
  pulses for the fused (four-step), blocked-subbyte, and staged plan
  families.  Tolerances are the documented fusion deltas, not slop:
  the RFI s1 mean comes from the Parseval identity over the packed C2C
  output (rfi.mean_power_packed, f32-rounding-level difference from the
  direct mean), the chirp·twiddle precombination reassociates one
  complex multiply, and the epilogue's df64 chirp uses the XLA
  anchored-Taylor evaluation (~1e-9 turns from the Pallas in-kernel
  one).  Detection *decisions* (signal counts, zero-channel counts)
  must match exactly at test thresholds.
- the Parseval mean-power identity itself against the direct mean;
- the in-kernel SK decision of the skzap kernel against the jnp chain,
  including a deliberately-zapped row;
- plan_signature changes whenever fusion toggles (AOT cache safety).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.ops import fft as F
from srtb_tpu.ops import rfi
from srtb_tpu.pipeline.segment import SegmentProcessor, waterfall_to_numpy

N = 1 << 16


def _cfg(n=N, channels=1 << 5, nbits=2, **kw):
    base = dict(
        baseband_input_count=n,
        baseband_input_bits=nbits,
        baseband_format_type="simple",
        baseband_freq_low=1405.0,
        baseband_bandwidth=64.0,
        baseband_sample_rate=128e6,
        dm=30.0,
        spectrum_channel_count=channels,
        signal_detect_signal_noise_threshold=5.0,
        signal_detect_max_boxcar_length=8,
        mitigate_rfi_average_method_threshold=25.0,
        mitigate_rfi_spectral_kurtosis_threshold=1e9,
        mitigate_rfi_freq_list="1450-1460",
        baseband_reserve_sample=False,
        fft_strategy="four_step",
    )
    base.update(kw)
    return Config(**base)


def _pulse_bytes(cfg):
    return make_dispersed_baseband(
        cfg.baseband_input_count, cfg.baseband_freq_low,
        cfg.baseband_bandwidth, cfg.dm,
        pulse_positions=cfg.baseband_input_count // 2, pulse_amp=30.0,
        nbits=cfg.baseband_input_bits)


def _run(cfg, staged=None):
    proc = SegmentProcessor(cfg, staged=staged)
    raw = _pulse_bytes(cfg)
    wf_ri, res = proc.process(raw)
    return proc, waterfall_to_numpy(wf_ri), res


def _assert_parity(off, on, atol_scale=2e-4):
    """Fused vs unfused: identical decisions, documented-tolerance
    values."""
    _, wf_off, res_off = off
    _, wf_on, res_on = on
    np.testing.assert_array_equal(np.asarray(res_off.signal_counts),
                                  np.asarray(res_on.signal_counts))
    np.testing.assert_array_equal(np.asarray(res_off.zero_count),
                                  np.asarray(res_on.zero_count))
    scale = max(np.abs(wf_off).max(), 1e-30)
    np.testing.assert_allclose(wf_on, wf_off, atol=atol_scale * scale,
                               rtol=0)
    ts_off = np.asarray(res_off.time_series)
    ts_scale = max(np.abs(ts_off).max(), 1e-30)
    np.testing.assert_allclose(np.asarray(res_on.time_series), ts_off,
                               atol=5e-4 * ts_scale, rtol=0)


@pytest.mark.parametrize("n", [1 << 16, 1 << 18, 1 << 20])
def test_fused_vs_unfused_four_step(n):
    """Fused plan family: the bank + chirp·twiddle-precombination
    epilogue vs the legacy three-sweep tail, 2-bit blocked-subbyte
    composition (the production format)."""
    off = _run(_cfg(n=n, fused_tail="off"))
    on = _run(_cfg(n=n, fused_tail="on"))
    assert not off[0].fused_tail and on[0].fused_tail
    assert on[0].plan_name == "fused:four_step+ftail"
    assert off[0].plan_name == "fused:four_step"
    _assert_parity(off, on)


def test_fused_vs_unfused_int8_bank_premul():
    """Non-blocked unpack (8-bit) through segment_rfft: the bank premul
    path on the sample-order composition."""
    off = _run(_cfg(nbits=8, fused_tail="off"))
    on = _run(_cfg(nbits=8, fused_tail="on"))
    assert on[0].chirp_w is not None  # precombined bank exists
    _assert_parity(off, on)


def test_fused_vs_unfused_staged(monkeypatch):
    """Staged plan family: the epilogue folds into stage (b)'s Hermitian
    write (df64 in-trace chirp, no bank)."""
    off = _run(_cfg(fused_tail="off"), staged=True)
    on = _run(_cfg(fused_tail="on"), staged=True)
    assert off[0].staged and on[0].staged
    assert not off[0].fused_tail and on[0].fused_tail
    # the unfused staged plan walks its boundary in blocks of rows
    # ("+rows"); the fused tail keeps the whole-plane stage (b)
    assert off[0].plan_name.endswith("+rows")
    assert on[0].plan_name == off[0].plan_name[:-len("+rows")] + "+ftail"
    assert on[0].chirp is None and on[0].chirp_w is None
    _assert_parity(off, on, atol_scale=1e-3)


def test_fused_skzap_vs_unfused(caplog):
    """Fully-fused waterfall tail (one kernel: C2C + dewindow + SK +
    zap + ts) vs the legacy jnp chain."""
    kw = dict(channels=8, use_pallas=True, use_pallas_sk=True)
    off = _run(_cfg(fused_tail="off", **kw))
    on = _run(_cfg(fused_tail="on", **kw))
    assert on[0]._skzap and on[0].fused_tail
    assert not off[0]._skzap and not off[0].fused_tail
    assert on[0].plan_name.endswith("+ftail+skzap")
    _assert_parity(off, on, atol_scale=1e-3)


def test_skzap_kernel_zaps_like_jnp_chain():
    """In-kernel SK decision parity, including a row the threshold
    really zaps: a constant-amplitude row has SK ~ 1 < thr_low and must
    come out exactly zero, excluded from the time series, and counted
    as a zero channel — matching rfi.mitigate_rfi_spectral_kurtosis +
    detect on the same spectrum rows."""
    from srtb_tpu.ops import detect as det
    from srtb_tpu.ops import pallas_fft as pf

    nfreq, t_len = 16, 1 << 12
    rng = np.random.default_rng(3)
    spec = (rng.standard_normal((nfreq, t_len))
            + 1j * rng.standard_normal((nfreq, t_len))).astype(np.complex64)
    spec[5] = 0.7 + 0.2j  # constant row -> SK = m*T*p^2/(T*p)^2 « thr_low
    sk_thr = 1.05

    wr, wi, zapf, fs0, ts = pf.fft_rows_skzap_ri(
        jnp.real(jnp.asarray(spec)), jnp.imag(jnp.asarray(spec)),
        sk_thr, inverse=True, interpret=True)
    wf_fused = np.asarray(wr) + 1j * np.asarray(wi)

    wf_ref = np.asarray(jnp.fft.ifft(jnp.asarray(spec), axis=-1,
                                     norm="forward"))
    wf_ref_zap = np.asarray(rfi.mitigate_rfi_spectral_kurtosis(
        jnp.asarray(wf_ref), sk_thr))
    zapped_rows = np.abs(wf_ref_zap).sum(-1) == 0
    assert zapped_rows[5] and zapped_rows.sum() >= 1

    got_zap = np.asarray(zapf)[:, 0] != 0
    np.testing.assert_array_equal(got_zap, zapped_rows)
    assert np.all(wf_fused[5] == 0)
    scale = np.abs(wf_ref_zap).max()
    np.testing.assert_allclose(wf_fused, wf_ref_zap, atol=2e-4 * scale,
                               rtol=0)
    # time series over kept rows only
    ts_ref = np.asarray(det.tree_sum_freq(
        jnp.asarray(np.abs(wf_ref_zap).astype(np.float32) ** 2)))
    np.testing.assert_allclose(np.asarray(ts), ts_ref,
                               rtol=1e-4, atol=1e-3 * ts_ref.max())
    # zero-count inputs: zap flag OR first-sample power == 0
    zc = int(((np.asarray(zapf)[:, 0] != 0)
              | (np.asarray(fs0)[:, 0] == 0)).sum())
    assert zc == int(zapped_rows.sum())


def test_mean_power_packed_matches_direct_mean():
    """The Parseval identity over the packed C2C output equals the
    direct mean |spec|^2 over the dropped-Nyquist spectrum."""
    rng = np.random.default_rng(11)
    x = rng.standard_normal(1 << 14).astype(np.float32) * 3.0
    zf = jnp.fft.fft(F.pack_even_odd(jnp.asarray(x)))
    spec = F.hermitian_rfft_post(zf, drop_nyquist=True)
    direct = float(jnp.mean(jnp.abs(spec) ** 2))
    parseval = float(rfi.mean_power_packed(zf)[..., 0])
    np.testing.assert_allclose(parseval, direct, rtol=1e-5)


def test_rfi_s1_zap_decisions_match_through_parseval_mean():
    """At a real (non-degenerate) threshold the fused path's zap set
    must equal the unfused one's on representative data."""
    rng = np.random.default_rng(12)
    x = rng.standard_normal(1 << 14).astype(np.float32)
    x[64:96] += np.sin(np.arange(32) * 0.7).astype(np.float32) * 40.0
    zf = jnp.fft.fft(F.pack_even_odd(jnp.asarray(x)))
    spec = F.hermitian_rfft_post(zf, drop_nyquist=True)
    thr = 10.0
    unfused = np.asarray(rfi.mitigate_rfi_average_and_normalize(
        spec, thr, 0.5))
    fused = np.asarray(rfi.mitigate_rfi_s1_given_mean(
        spec, rfi.mean_power_packed(zf), thr, 0.5))
    np.testing.assert_array_equal(unfused == 0, fused == 0)
    np.testing.assert_allclose(fused, unfused, rtol=1e-6, atol=0)


def test_plan_signature_changes_when_fusion_toggles():
    """AOT cache safety: toggling fused_tail (or the skzap fusion) must
    change plan_signature so a restarted process misses cleanly."""
    sig_off = SegmentProcessor(_cfg(fused_tail="off")).plan_signature()
    sig_on = SegmentProcessor(_cfg(fused_tail="on")).plan_signature()
    assert sig_off != sig_on
    kw = dict(channels=8, use_pallas=True, use_pallas_sk=True)
    sig_sk_on = SegmentProcessor(
        _cfg(fused_tail="on", **kw)).plan_signature()
    sig_sk_off = SegmentProcessor(
        _cfg(fused_tail="off", **kw)).plan_signature()
    assert sig_sk_on != sig_sk_off != sig_off
    # chirp_exact shapes the traced chirp evaluation -> new signature
    assert SegmentProcessor(
        _cfg(fused_tail="on", chirp_exact=True)).plan_signature() != sig_on


def test_fused_tail_auto_gates_bankless_sizes(monkeypatch):
    """auto keeps bankless plans (in-trace df64 chirp) unfused above
    the proven size range; bank plans carry no gate; "on" overrides
    (the hardware-queue staged legs)."""
    import srtb_tpu.pipeline.segment as seg
    monkeypatch.setattr(seg, "FUSED_TAIL_DF64_MAX_SPECTRUM", 1 << 10)
    gated = SegmentProcessor(_cfg(use_pallas=True))   # n_spec 2^15 > 2^10
    assert not gated.fused_tail and "+ftail" not in gated.plan_name
    bank = SegmentProcessor(_cfg())                   # bank plan: no gate
    assert bank.fused_tail
    forced = SegmentProcessor(_cfg(use_pallas=True, fused_tail="on"))
    assert forced.fused_tail


def test_fused_tail_on_monolithic_raises():
    with pytest.raises(ValueError, match="monolithic"):
        SegmentProcessor(_cfg(fft_strategy="monolithic", fused_tail="on"))
    # and segment_rfft itself refuses an epilogue it cannot host
    with pytest.raises(ValueError, match="monolithic"):
        F.segment_rfft(jnp.zeros(256), "monolithic",
                       epilogue=lambda zf, s: s)


def test_chirp_exact_escape_hatch_matches_anchored():
    """Config.chirp_exact flips every df64 chirp to the per-element
    division chains; results must agree with the anchored default to
    the documented ~1e-9-turn phase budget."""
    on = _run(_cfg(fused_tail="on"))
    exact = _run(_cfg(fused_tail="on", chirp_exact=True))
    scale = np.abs(on[1]).max()
    np.testing.assert_allclose(exact[1], on[1], atol=1e-5 * scale, rtol=0)
    np.testing.assert_array_equal(np.asarray(on[2].signal_counts),
                                  np.asarray(exact[2].signal_counts))
