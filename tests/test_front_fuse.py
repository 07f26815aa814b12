"""Front-fused staged megakernel tests (the staged_ffuse plan family:
ops/pallas_fft2 pass1_front / pass2_spectrum + pipeline/segment.py
front_fuse wiring + the registry's front_fuse demotion rung).

Acceptance coverage of ISSUE 15:
- detections bit-identical ffuse vs the staged plan across unpack
  variants (1/2/4/8-bit simple, 2-pol byte-interleaved) x ring/cold x
  skzap, with float outputs at the documented fused-plan tolerance
  (test_fusion.py precedent — the two plans run different FFT
  factorizations at CI shapes, so decision equality is the bitwise
  contract and the waterfall/time series are allclose);
- the kernel-level bitwise contract: pass1_front == XLA unpack +
  window + pack_even_odd + pass1_2d, bit for bit (same DFT body on
  identical values);
- the ring-carry alias surviving the fusion, both in the checked-in
  plan cards and in a live audit;
- the ladder demoting ffuse -> today's staged plan on an injected
  Mosaic compile fault;
- signature / cache key / plan name distinguishing the family.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from srtb_tpu.config import Config
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.ops import fft as F
from srtb_tpu.ops import pallas_fft2 as pf2
from srtb_tpu.ops import rfi
from srtb_tpu.ops import unpack as U
from srtb_tpu.ops import window as W
from srtb_tpu.pipeline.segment import (SegmentProcessor,
                                       front_fuse_resolves,
                                       waterfall_to_numpy)
from srtb_tpu.utils.metrics import metrics

N = 1 << 16
M = N // 2


@pytest.fixture(autouse=True)
def _pallas2_rows(monkeypatch):
    """Every test in this file runs the staged plan on pallas2 rows —
    the front-fuse prerequisite."""
    monkeypatch.setenv("SRTB_STAGED_ROWS_IMPL", "pallas2")


def _base(**extra):
    cfg = dict(baseband_input_count=N, baseband_input_bits=2,
               baseband_format_type="simple", baseband_freq_low=1405.0,
               baseband_bandwidth=64.0, baseband_sample_rate=128e6,
               dm=30.0, spectrum_channel_count=8,
               mitigate_rfi_average_method_threshold=25.0,
               mitigate_rfi_spectral_kurtosis_threshold=5.0,
               signal_detect_signal_noise_threshold=5.0,
               signal_detect_max_boxcar_length=8,
               mitigate_rfi_freq_list="1410-1412",
               baseband_reserve_sample=False,
               fft_strategy="four_step", fused_tail="on")
    cfg.update(extra)
    return Config(**cfg)


def _raw(nbits, streams=1, seed=0, amp=8.0):
    if streams == 1:
        return make_dispersed_baseband(
            N, 1405.0, 64.0, 30.0, pulse_positions=N // 2,
            pulse_amp=amp, nbits=nbits, seed=seed)
    # 2-pol byte interleave: two independent 8-bit streams, bytes
    # alternating "1212" (ops/unpack.unpack_interleaved_2pol)
    a = make_dispersed_baseband(N, 1405.0, 64.0, 30.0,
                                pulse_positions=N // 2, pulse_amp=amp,
                                nbits=nbits, seed=seed)
    b = make_dispersed_baseband(N, 1405.0, 64.0, 30.0,
                                pulse_positions=N // 3, pulse_amp=amp,
                                nbits=nbits, seed=seed + 1)
    out = np.empty(a.size + b.size, dtype=np.uint8)
    out[0::2] = a
    out[1::2] = b
    return out


def _assert_parity(proc_a, proc_b, raw, ts_atol=1e-3):
    wf_a, res_a = proc_a.process(raw)
    wf_b, res_b = proc_b.process(raw)
    np.testing.assert_array_equal(np.asarray(res_a.signal_counts),
                                  np.asarray(res_b.signal_counts))
    np.testing.assert_array_equal(np.asarray(res_a.zero_count),
                                  np.asarray(res_b.zero_count))
    a = waterfall_to_numpy(wf_b)
    b = waterfall_to_numpy(wf_a)
    scale = float(np.abs(a).max())
    assert scale > 0, "all waterfall rows zapped — test data too hot"
    np.testing.assert_allclose(b, a, atol=ts_atol * scale, rtol=0)
    ts_a = np.asarray(res_a.time_series)
    ts_b = np.asarray(res_b.time_series)
    np.testing.assert_allclose(
        ts_a, ts_b, rtol=0,
        atol=ts_atol * (float(np.abs(ts_b).max()) or 1.0))
    return res_a, res_b


# -------------------------------------------------- plan-level parity


@pytest.mark.parametrize("nbits", [1, 2, 4, 8])
def test_parity_vs_staged_simple(nbits):
    cfg = _base(baseband_input_bits=nbits)
    ff = SegmentProcessor(Config(**{**cfg.__dict__,
                                    "front_fuse": "on"}), staged=True)
    st = SegmentProcessor(Config(**{**cfg.__dict__,
                                    "front_fuse": "off"}), staged=True)
    assert ff.front_fuse and not st.front_fuse
    assert ff.fused_tail and ff.plan_name.endswith("+ffuse")
    _assert_parity(ff, st, _raw(nbits))


def test_parity_vs_staged_interleaved_2pol():
    cfg = _base(baseband_input_bits=8,
                baseband_format_type="interleaved_samples_2")
    ff = SegmentProcessor(Config(**{**cfg.__dict__,
                                    "front_fuse": "on"}), staged=True)
    st = SegmentProcessor(Config(**{**cfg.__dict__,
                                    "front_fuse": "off"}), staged=True)
    assert ff.data_stream_count == 2
    res_f, _ = _assert_parity(ff, st, _raw(8, streams=2))
    assert np.asarray(res_f.signal_counts).shape[0] == 2


def test_parity_windowed():
    """Windowed front: the even/odd window operands reach the kernel
    and stage (b)'s dedispersed spectrum matches the staged plan's.
    (Compared at the spectrum boundary: at this tiny shape the hann
    dewindow's near-zero edges blow up every waterfall row's kurtosis
    and BOTH plans SK-zap the whole waterfall — a data artifact, not a
    plan difference, so downstream decisions are vacuously equal.)"""
    cfg = _base()
    ff = SegmentProcessor(Config(**{**cfg.__dict__,
                                    "front_fuse": "on"}),
                          window_name="hann", staged=True)
    st = SegmentProcessor(Config(**{**cfg.__dict__,
                                    "front_fuse": "off"}),
                          window_name="hann", staged=True)
    assert ff._ffuse_window is not None
    raw = _raw(2)
    spec_f = np.asarray(ff._run_stage_b(ff._jit_stage_a(
        ff._as_device_bytes(raw))))
    n1, n2 = ff._ffuse_fac
    # unblock ffuse's k1-major spectrum to natural order
    spec_f = np.swapaxes(spec_f.reshape(2, -1, n1, n2), -1, -2) \
        .reshape(2, -1, M)
    spec_s = np.asarray(st._run_stage_b(st._jit_stage_a(
        st._as_device_bytes(raw)))).reshape(2, -1, M)
    scale = np.abs(spec_s).max()
    assert scale > 0
    np.testing.assert_allclose(spec_f, spec_s, atol=1e-4 * scale,
                               rtol=0)


def test_parity_skzap_combo():
    """The fully front-AND-back-fused staged plan: ffuse front + the
    one-kernel skzap waterfall tail; decisions match the non-skzap
    ffuse plan."""
    cfg = _base(use_pallas=True, use_pallas_sk=True)
    ff_sk = SegmentProcessor(Config(**{**cfg.__dict__,
                                       "front_fuse": "on"}),
                             staged=True)
    ff = SegmentProcessor(Config(**{**_base().__dict__,
                                    "front_fuse": "on"}), staged=True)
    assert ff_sk._skzap and ff_sk.plan_name.endswith("+ffuse+skzap")
    assert ff_sk.front_fuse and not ff._skzap
    _assert_parity(ff_sk, ff, _raw(2))


# ------------------------------------------------ kernel-level checks


def test_pass1_front_bitwise_vs_xla_pack():
    """The in-kernel unpack + window + even/odd pack feeds the SAME
    column-DFT body as the packed path — on identical exact-integer
    inputs the blocked intermediate must match BIT FOR BIT."""
    n1, n2 = pf2.ffuse_factor(M)
    rng = np.random.default_rng(3)
    raw = rng.integers(0, 256, size=N * 2 // 8, dtype=np.uint8)
    win = W.window_coefficients("hamming", N)
    x = U.unpack(jnp.asarray(raw), 2, jnp.asarray(win))
    z = F.pack_even_odd(x)
    br_ref, bi_ref = pf2.pass1_2d(jnp.real(z).reshape(n1, n2),
                                  jnp.imag(z).reshape(n1, n2),
                                  interpret=True)
    w_eo = (jnp.asarray(np.ascontiguousarray(win[0::2].reshape(n1, n2))),
            jnp.asarray(np.ascontiguousarray(win[1::2].reshape(n1, n2))))
    br, bi, _ = pf2.pass1_front(jnp.asarray(raw), m=M, streams=1,
                                variant="simple", nbits=2,
                                window_eo=w_eo, interpret=True)
    np.testing.assert_array_equal(np.asarray(br[0]), np.asarray(br_ref))
    np.testing.assert_array_equal(np.asarray(bi[0]), np.asarray(bi_ref))


def test_front_mean_matches_packed():
    """The pass-1 accumulators' Parseval mean agrees with
    rfi.mean_power_packed over the materialized C2C to f32 rounding."""
    rng = np.random.default_rng(5)
    raw = rng.integers(0, 256, size=N * 2 // 8, dtype=np.uint8)
    _, _, aux = pf2.pass1_front(jnp.asarray(raw), m=M, streams=1,
                                variant="simple", nbits=2,
                                interpret=True)
    z = F.pack_even_odd(U.unpack(jnp.asarray(raw), 2, None))
    ref = float(rfi.mean_power_packed(jnp.fft.fft(z))[0])
    got = float(pf2.front_mean_power(aux, pf2.ffuse_factor(M)[1], M)[0])
    assert abs(got - ref) <= 1e-5 * abs(ref)


def test_pass2_premul_matches_reference():
    """The chirp-twiddle premul bank (SegmentProcessor._premul_bank
    cw = c*w) folded into pass 2's epilogue matches
    hermitian_rfft_post(premul=...) + s1 on the same intermediate."""
    from srtb_tpu.ops import dedisperse as dd
    n1, n2 = pf2.ffuse_factor(M)
    rng = np.random.default_rng(7)
    zr = jnp.asarray(rng.standard_normal(M).astype(np.float32))
    zi = jnp.asarray(rng.standard_normal(M).astype(np.float32))
    br, bi = pf2.pass1_2d(zr.reshape(n1, n2), zi.reshape(n1, n2),
                          interpret=True)
    yr, yi = pf2.pass2_2d(br, bi, interpret=True)
    zf = jnp.asarray((np.asarray(yr) + 1j * np.asarray(yi))
                     .T.reshape(M).astype(np.complex64))
    mean = float(rfi.mean_power_packed(zf[None])[0, 0])
    c_ri = dd.chirp_factor_df64_ri(M, 1405.0, 64.0 / M, 1437.0, 30.0)
    c = (np.asarray(c_ri[0]) + 1j * np.asarray(c_ri[1]))
    cw = c * np.asarray(F._iota_phase(M, 2 * M, -1.0))

    def blocked(a):
        return jnp.asarray(np.ascontiguousarray(
            a.astype(np.float32).reshape(n2, n1).T))

    pm = (blocked(c.real), blocked(c.imag),
          blocked(cw.real), blocked(cw.imag))
    sr, si = pf2.pass2_spectrum(br, bi, thr=jnp.float32(1.5 * mean),
                                norm=0.125, premul_blocked=pm,
                                interpret=True)
    got = (np.asarray(sr) + 1j * np.asarray(si)).T.reshape(M)
    ref = F.hermitian_rfft_post(
        zf, drop_nyquist=True,
        premul=(jnp.asarray(c.astype(np.complex64)),
                jnp.asarray(cw.astype(np.complex64))))
    ref = np.asarray(rfi.mitigate_rfi_s1_given_mean(
        ref, jnp.float32(mean), 1.5, 0.125))
    scale = float(np.abs(ref).max())
    np.testing.assert_allclose(got, ref, atol=2e-5 * scale, rtol=0)


# ------------------------------------------------------- ring variants


def _ring_cfg(front_fuse):
    # small dm keeps 0 < reserved_bytes < segment_bytes at this shape
    return _base(dm=0.1, baseband_input_bits=8,
                 baseband_reserve_sample=True, front_fuse=front_fuse)


def test_ring_warm_cold_bit_identical_to_direct():
    """The ffuse ring variants reassemble bit-identically: a cold
    dispatch then a warm carry ++ stride dispatch reproduce the
    direct full-segment runs exactly (same programs inside)."""
    ff = SegmentProcessor(_ring_cfg("on"), staged=True)
    assert ff.ring and ff.front_fuse
    raw0 = _raw(8, seed=0)
    # overlap-save stream: segment 1 starts at stride offset
    stream = np.concatenate([raw0, _raw(8, seed=1)])
    seg0 = stream[:ff._segment_bytes]
    seg1 = stream[ff.stride_bytes:ff.stride_bytes + ff._segment_bytes]
    (wf0, res0), carry = ff.run_device_cold(jax.device_put(seg0))
    (wf1, res1), _ = ff.run_device_ring(
        carry, jax.device_put(seg1[ff.reserved_bytes:]))
    dwf0, dres0 = ff.run_device(jax.device_put(seg0))
    dwf1, dres1 = ff.run_device(jax.device_put(seg1))
    np.testing.assert_array_equal(np.asarray(wf0), np.asarray(dwf0))
    np.testing.assert_array_equal(np.asarray(wf1), np.asarray(dwf1))
    np.testing.assert_array_equal(np.asarray(res1.signal_counts),
                                  np.asarray(dres1.signal_counts))
    np.testing.assert_array_equal(np.asarray(res0.time_series),
                                  np.asarray(dres0.time_series))


def test_ring_cards_pin_carry_alias():
    """The checked-in ffuse cards: the plan name is pinned, and the
    ring family's warm assemble proves the carry alias survived the
    fusion (aliased param 0, alias_bytes > 0)."""
    from srtb_tpu.analysis.hlo_audit import DEFAULT_BASELINE
    cards = json.load(open(DEFAULT_BASELINE))["cards"]
    for key in ("staged_ffuse", "staged_ffuse_ring"):
        card = cards[key]
        assert card["plan_name"].startswith("staged:four_step+ftail"
                                            "+ffuse"), key
        assert card["checks"]["donation_ok"], key
    ring = cards["staged_ffuse_ring"]
    assert ring["ingest"] == "ring-v1"
    warm = ring["programs"]["stage_a_ring"]
    assert 0 in warm["donation"]["aliased"]
    assert warm["alias_bytes"] > 0
    assert ring["checks"]["ring_alias_ok"]


def test_ring_alias_proven_live():
    """Live audit of a freshly built ffuse+ring processor: every
    invariant check green, incl. the carry alias (the PR-7 aval
    lesson surviving the front fusion)."""
    from srtb_tpu.analysis.hlo_audit import audit_processor
    proc = SegmentProcessor(_ring_cfg("on"), staged=True,
                            donate_input=True)
    card = audit_processor(proc)
    assert all(card["checks"].values()), card["checks"]
    assert card["total_spectrum_passes"] >= 2  # the two kernel sweeps


# ------------------------------------------------- ladder + identity


def test_ladder_first_rung_drops_front_fuse():
    from srtb_tpu.resilience.demote import ladder_rungs
    cfg = _ring_cfg("on")
    rungs = ladder_rungs(cfg, base_staged=True)
    assert rungs[0].step == "front_fuse"
    assert rungs[0].cfg.front_fuse == "off"
    demoted = SegmentProcessor(rungs[0].cfg, staged=rungs[0].staged)
    assert not demoted.front_fuse
    assert "+ffuse" not in demoted.plan_name  # today's staged plan


def test_compile_fault_demotes_ffuse_to_staged(tmp_path):
    """An injected Mosaic compile fault at dispatch demotes the ffuse
    plan down its rung onto today's staged plan mid-run, with the
    faulted segment re-dispatched from its retained host buffer and
    decisions identical to a fault-free run."""
    from srtb_tpu.pipeline.runtime import Pipeline

    segs = 3
    path = tmp_path / "bb.bin"
    np.concatenate([_raw(8, seed=i) for i in range(segs)]).tofile(path)

    def cfg(tag, **extra):
        return Config(**{
            **_base(baseband_input_bits=8, front_fuse="on").__dict__,
            "input_file_path": str(path),
            "baseband_output_file_prefix": str(tmp_path / f"{tag}_"),
            "writer_thread_count": 0, "inflight_segments": 2,
            "retry_backoff_base_s": 0.001, **extra})

    class Sink:
        def __init__(self):
            self.out = []

        def push(self, work, positive):
            self.out.append(
                (np.asarray(work.detect.signal_counts).copy(),
                 np.asarray(work.detect.zero_count).copy()))

    metrics.reset()
    clean = Sink()
    c0 = cfg("clean", plan_ladder="off")
    with Pipeline(c0, sinks=[clean],
                  processor=SegmentProcessor(c0, staged=True)) as pipe:
        assert pipe.processor.front_fuse
        pipe.run()
    metrics.reset()
    sink = Sink()
    c1 = cfg("cfail", fault_plan="dispatch:compile_fail@1")
    with Pipeline(c1, sinks=[sink],
                  processor=SegmentProcessor(c1, staged=True)) as pipe:
        stats = pipe.run()
        assert pipe.faults.unfired() == []
        assert pipe.healer.level == 1
        assert pipe.healer.active_step == "front_fuse"
        assert not pipe.processor.front_fuse
        assert "+ffuse" not in pipe.processor.plan_name
    assert stats.segments == len(clean.out)
    assert metrics.get("plan_demotions") == 1
    assert metrics.get("segments_dropped") == 0
    for (sc_a, zc_a), (sc_b, zc_b) in zip(sink.out, clean.out):
        np.testing.assert_array_equal(sc_a, sc_b)
        np.testing.assert_array_equal(zc_a, zc_b)
    metrics.reset()


def test_signature_cache_key_and_name_distinguish():
    on_cfg = _base(front_fuse="on")
    off_cfg = _base(front_fuse="off")
    ff = SegmentProcessor(on_cfg, staged=True)
    st = SegmentProcessor(off_cfg, staged=True)
    assert ff.plan_signature() != st.plan_signature()
    assert SegmentProcessor.plan_cache_key(on_cfg) \
        != SegmentProcessor.plan_cache_key(off_cfg)
    assert '"front_fuse": true' in ff.plan_signature()
    assert ff.plan_name == st.plan_name.replace("+ftail",
                                                "+ftail+ffuse")
    # "auto" without the probe flag / env opt-in keeps today's plan
    # (the raw knob still enters the cfg projection, like fused_tail's
    # auto/on — only the RESOLVED plan must stay the staged one)
    auto = SegmentProcessor(_base(front_fuse="auto"), staged=True)
    assert not auto.front_fuse
    assert auto.plan_name == st.plan_name
    assert '"front_fuse": false' in auto.plan_signature()


def test_auto_resolves_on_with_env_opt_in(monkeypatch):
    monkeypatch.setenv("SRTB_PALLAS_FFUSE", "1")
    proc = SegmentProcessor(_base(front_fuse="auto"), staged=True)
    assert proc.front_fuse


def test_front_fuse_on_requires_prerequisites(monkeypatch):
    # not staged
    with pytest.raises(ValueError, match="front_fuse=on"):
        SegmentProcessor(_base(front_fuse="on"), staged=False)
    # wrong rows impl
    monkeypatch.setenv("SRTB_STAGED_ROWS_IMPL", "pallas")
    with pytest.raises(ValueError, match="front_fuse=on"):
        SegmentProcessor(_base(front_fuse="on"), staged=True)
    monkeypatch.setenv("SRTB_STAGED_ROWS_IMPL", "pallas2")
    # unfusable tail (monolithic strategy)
    with pytest.raises(ValueError):
        SegmentProcessor(_base(front_fuse="on", fused_tail="off"),
                         staged=True)
    # unsupported format variant
    with pytest.raises(ValueError, match="front_fuse=on"):
        SegmentProcessor(
            _base(front_fuse="on", baseband_input_bits=-8,
                  baseband_format_type="naocpsr_snap1"), staged=True)
    # pure predicate agrees (the ladder-rung / resolver shared home)
    assert not front_fuse_resolves(_base(front_fuse="auto"), False)
    assert front_fuse_resolves(_base(front_fuse="on"), True)


def test_sanitize_run_handles_tuple_boundary():
    cfg = Config(**{**_base(front_fuse="on").__dict__,
                    "sanitize": True})
    proc = SegmentProcessor(cfg, staged=True, donate_input=True)
    wf, res = proc.process(_raw(2))
    assert np.asarray(res.zero_count).shape == (1,)


def test_ffuse_factor_windows():
    # production window delegates to the standard factorization
    assert pf2.ffuse_factor(1 << 26) == (4096, 1 << 14)
    # CI window gets a small-leg split with n2 >= 128
    n1, n2 = pf2.ffuse_factor(M)
    assert n1 * n2 == M and n2 >= 128
    assert pf2.ffuse_factor(3 * (1 << 12)) is None  # not a power of 2
    assert pf2.ffuse_factor(1 << 6) is None         # too small
