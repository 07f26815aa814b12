"""Science observatory tests (ISSUE 16): the data-quality epilogue and
the pulse-injection canary.

Unit layer: packed-vector parity of the device epilogue against the
float64 oracle (direct and through every plan family — monolithic,
fused, staged, front-fused), the EWMA drift detector on a synthetic
bandpass ramp, canary delta determinism and quarantine-by-construction
(reserved spans zeroed), and strict Prometheus exposition for the new
metric families.

E2E layer: canary recovery bit-identical across checkpoint resume;
quarantine proven end to end (canary segments absent from science
outputs, flagged in journal + manifest, ``baseband_write_all`` output
bit-identical to a canary-off run); the sensitivity gate's teeth (a
band-zapped run fails the check, degrades detection health and
escalates an incident bundle carrying the quality timeline)."""

import hashlib
import json
import os

import numpy as np
import pytest
from oracle_utils import oracle_unpack

from srtb_tpu.config import Config
from srtb_tpu.ops import rfi
from srtb_tpu.ops.dedisperse import D, spectrum_frequencies
from srtb_tpu.pipeline.segment import SegmentProcessor
from srtb_tpu.quality import (CanaryController, EWMADrift,
                              QualityMonitor, quality_stats_oracle,
                              unpack_stats)
from srtb_tpu.quality import stats as QS
from srtb_tpu.utils import slo
from srtb_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True)
def _fresh_registry():
    metrics.reset()
    slo.reset()
    yield
    metrics.reset()
    slo.reset()


# ------------------------------------------------------- oracle parity


def _proc_cfg(**extra) -> Config:
    return Config(**{**dict(
        baseband_input_count=1 << 14, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=30.0,
        spectrum_channel_count=1 << 5,
        signal_detect_signal_noise_threshold=5.0,
        signal_detect_max_boxcar_length=8,
        mitigate_rfi_average_method_threshold=10.0,
        mitigate_rfi_spectral_kurtosis_threshold=3.0,
        baseband_reserve_sample=False, quality_stats=True,
        quality_coarse_bins=16), **extra})


def _oracle_spec_wf(x: np.ndarray, cfg: Config):
    """The float64 chain of oracle_utils.oracle_stream_chain, returning
    the two intermediates the quality epilogue reads: the zapped/
    normalized/chirped spectrum and the SK-zapped waterfall."""
    n = x.size
    n_spec = n // 2
    spec = np.fft.rfft(x)[:-1]
    power = spec.real ** 2 + spec.imag ** 2
    zap1 = power > (cfg.mitigate_rfi_average_method_threshold
                    * power.mean())
    coeff = rfi.normalization_coefficient(n_spec,
                                          cfg.spectrum_channel_count)
    spec = np.where(zap1, 0.0, spec * coeff)
    f_min, f_c, df = spectrum_frequencies(cfg, n_spec)
    f = f_min + df * np.arange(n_spec, dtype=np.float64)
    k = D * 1e6 * cfg.dm / f * ((f - f_c) / f_c) ** 2
    spec = spec * np.exp(-2j * np.pi * np.modf(k)[0])
    ch = min(cfg.spectrum_channel_count, n_spec)
    wlen = n_spec // ch
    wf = np.fft.ifft(spec.reshape(ch, wlen), axis=-1) * wlen
    lo, hi = rfi.sk_decision_thresholds(
        wlen, cfg.mitigate_rfi_spectral_kurtosis_threshold)
    p = wf.real ** 2 + wf.imag ** 2
    s2, s4 = p.sum(axis=-1), (p * p).sum(axis=-1)
    sk = wlen * s4 / (s2 * s2)
    wf = np.where(((sk > hi) | (sk < lo))[:, None], 0.0, wf)
    return spec, wf


def _assert_quality_parity(proc: SegmentProcessor, cfg: Config,
                           raw: np.ndarray, tag: str):
    _, res = proc.process(raw)
    assert res.quality is not None
    q_dev = np.asarray(res.quality)
    spec_o, wf_o = _oracle_spec_wf(oracle_unpack(raw, 8), cfg)
    q_or = quality_stats_oracle(spec_o[None], wf_o[None],
                                cfg.quality_coarse_bins,
                                cfg.quality_dead_threshold,
                                cfg.quality_hot_threshold,
                                subsample=cfg.quality_subsample)
    assert q_dev.shape == q_or.shape == (
        1, QS.vector_length(cfg.quality_coarse_bins))
    scale = np.maximum(np.abs(q_or), 1e-9)
    np.testing.assert_allclose(q_dev, q_or, rtol=1e-4,
                               atol=1e-4 * scale.max(),
                               err_msg=f"plan {tag}")


@pytest.mark.parametrize("plan", ["monolithic", "fused", "staged"])
def test_epilogue_oracle_parity(plan):
    """result.quality vs the float64 oracle, per plan family."""
    cfg = _proc_cfg()
    if plan == "monolithic":
        cfg = cfg.replace(fft_strategy="monolithic", fused_tail="off")
    raw = np.random.default_rng(7).integers(
        0, 256, size=cfg.segment_bytes(1), dtype=np.uint8)
    proc = SegmentProcessor(cfg, staged=(plan == "staged"))
    _assert_quality_parity(proc, cfg, raw, plan)


def test_quality_off_is_none():
    """quality_stats off: the epilogue is an exact no-op and existing
    consumers see the None pytree subtree."""
    cfg = _proc_cfg(quality_stats=False)
    raw = np.random.default_rng(7).integers(
        0, 256, size=cfg.segment_bytes(1), dtype=np.uint8)
    _, res = SegmentProcessor(cfg).process(raw)
    assert res.quality is None


def test_unpack_stats_roundtrip():
    """The packed layout is self-describing: unpack_stats recovers the
    coarse-bin count from the vector length."""
    rng = np.random.default_rng(3)
    spec = (rng.normal(size=(2, 256))
            + 1j * rng.normal(size=(2, 256)))
    spec[0, :32] = 0.0  # an eighth of stream 0 zapped
    wf = (rng.normal(size=(2, 16, 16))
          + 1j * rng.normal(size=(2, 16, 16)))
    q = quality_stats_oracle(spec, wf, 8, 0.1, 10.0)
    u = unpack_stats(q)
    assert u["occupancy"].shape == u["bandpass"].shape == (2, 8)
    assert u["zap_frac"][0] == pytest.approx(32 / 256)
    assert u["zap_frac"][1] == pytest.approx(0.0)
    # occupancy localizes the zap to the first bin of stream 0
    assert u["occupancy"][0, 0] == pytest.approx(1.0)
    assert u["occupancy"][0, 1:].max() == pytest.approx(0.0)


# ------------------------------------------------------ drift detector


def test_ewma_drift_triggers_on_ramp():
    """Steady bandpass: no alert.  A bandpass ramp setting in after
    warmup: the alert marks the transition onset (a slow creep within
    the noise is absorbed by design — the EWM variance tracks it)."""
    rng = np.random.default_rng(5)
    steady = EWMADrift(alpha=0.05, threshold=4.0, warmup=8)
    for _ in range(200):
        _, alert = steady.observe(100.0 + rng.normal(0, 1.0))
        assert not alert
    ramp = EWMADrift(alpha=0.05, threshold=4.0, warmup=8)
    alerts = []
    for i in range(200):
        x = 100.0 + rng.normal(0, 1.0) + (max(0, i - 100) * 5.0)
        _, alert = ramp.observe(x)
        alerts.append(alert)
    assert not any(alerts[:101])
    assert any(alerts[101:])


def test_quality_monitor_gauges_and_drift_alert():
    """QualityMonitor.observe exports the gauges (flat + labeled) and
    a ramped bandpass bumps quality_drift_alerts."""
    mon = QualityMonitor(drift_alpha=0.05, drift_threshold=4.0,
                         stream="beamQ")
    b = 4
    rng = np.random.default_rng(9)

    def vec(bp_mean):
        v = np.zeros(QS.N_SCALARS + 2 * b, dtype=np.float32)
        v[QS.IDX_ZAP_FRAC] = 0.25
        v[QS.IDX_BANDPASS_MEAN] = bp_mean
        v[QS.IDX_SK_MEAN] = 1.0
        return v

    for i in range(120):
        bp = 50.0 + rng.normal(0, 0.5) + (max(0, i - 60) * 5.0)
        out = mon.observe(vec(bp), segment=i)
    assert metrics.get("quality_zap_fraction") == pytest.approx(0.25)
    assert metrics.get("quality_zap_fraction",
                       labels={"stream": "beamQ"}) == pytest.approx(0.25)
    assert metrics.get("quality_drift_alerts") >= 1
    assert out["drift_score"] > 0
    tl = mon.timeline()
    assert tl and tl[-1]["segment"] == 119
    assert len(tl) <= QS.TIMELINE_SPANS


def test_quality_monitor_from_config_none_hook():
    assert QualityMonitor.from_config(Config(quality_stats=False)) \
        is None
    assert QualityMonitor.from_config(Config(quality_stats=True)) \
        is not None


# ---------------------------------------------- prometheus exposition


def test_prometheus_quality_canary_families_strict():
    """Satellite 1: the science-observatory families render with real
    (non-generic) HELP text, exactly one HELP + one TYPE each, HELP
    first, samples contiguous — a strict expfmt parser accepts the
    whole page."""
    mon = QualityMonitor(drift_alpha=0.05, drift_threshold=4.0,
                         stream="beam0")
    mon.observe(np.zeros(QS.N_SCALARS + 8, dtype=np.float32))
    cfg = Config(baseband_input_count=1 << 12,
                 canary_every_segments=4, canary_expected_snr=10.0,
                 stream_name="beam0")
    can = CanaryController.from_config(cfg)
    can.check(3, np.array([8.0]))
    text = metrics.prometheus()
    lines = text.strip().split("\n")
    seen_help, seen_type, current, order = {}, {}, None, []
    for ln in lines:
        if ln.startswith("# HELP "):
            name = ln.split()[2]
            seen_help[name] = seen_help.get(name, 0) + 1
            assert len(ln.split(" ", 3)) == 4 and ln.split(" ", 3)[3]
        elif ln.startswith("# TYPE "):
            name = ln.split()[2]
            seen_type[name] = seen_type.get(name, 0) + 1
            assert seen_help.get(name) == seen_type[name]
            current = name
            order.append(name)
        else:
            sample = ln.split("{")[0].split(" ")[0]
            assert sample == current or sample.startswith(
                current + "_"), (sample, current)
            float(ln.rpartition(" ")[2])
    assert seen_help == seen_type
    assert all(v == 1 for v in seen_type.values())
    assert len(order) == len(set(order))  # no re-opened family
    generic = "srtb_tpu runtime metric"
    for fam in ("quality_zap_fraction", "quality_sk_max",
                "quality_drift_score", "canary_checked",
                "canary_sensitivity_ratio", "detection_health_state"):
        help_ln = [ln for ln in lines
                   if ln.startswith(f"# HELP srtb_{fam} ")]
        assert len(help_ln) == 1, fam
        assert generic not in help_ln[0], fam
        # the labeled twin rides the same family block
        assert any(ln.startswith(f"srtb_{fam}{{") for ln in lines), fam


# ------------------------------------------------------- canary units


def _canary_cfg(**extra) -> Config:
    kw = dict(baseband_input_count=1 << 12,
              baseband_input_bits=8, baseband_freq_low=1405.0,
              baseband_bandwidth=64.0, baseband_sample_rate=128e6,
              canary_every_segments=3)
    kw.update(extra)
    return Config(**kw)


def test_canary_delta_deterministic_and_quarantined():
    """Two controllers build the identical int16 delta (bit-identical
    across resume by construction), zeroed over the head/tail reserved
    spans so the pulse can never leak through overlap or ring carry."""
    cfg = _canary_cfg()
    a = CanaryController(cfg, n_samples=1 << 12, reserved_samples=256)
    b = CanaryController(cfg, n_samples=1 << 12, reserved_samples=256)
    da, db = a._build_delta(), b._build_delta()
    np.testing.assert_array_equal(da, db)
    assert da.dtype == np.int16 and len(da) == 1 << 12
    assert np.abs(da[256:-256]).max() > 0  # pulse present...
    assert not da[:256].any() and not da[-256:].any()  # ...quarantined
    # schedule: never the cold first segment, every `every`-th after
    assert [a.is_canary(i) for i in range(7)] == [
        False, False, True, False, False, True, False]


def test_canary_prepare_pristine_and_size_gate():
    cfg = _canary_cfg()
    can = CanaryController.from_config(cfg)
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, size=1 << 12, dtype=np.uint8)
    before = data.copy()
    out, mark = can.prepare(2, data)
    np.testing.assert_array_equal(data, before)  # input untouched
    assert mark is not None and out is not data
    assert out.dtype == np.uint8 and (out != data).any()
    # non-canary index: passthrough, no copy
    same, no_mark = can.prepare(3, data)
    assert same is data and no_mark is None
    # a partial tail segment skips injection loudly
    tail = data[: 1 << 10]
    short, m2 = can.prepare(5, tail)
    assert m2 is None and short is tail


def test_canary_from_config_gates():
    assert CanaryController.from_config(Config()) is None
    assert CanaryController.from_config(
        _canary_cfg(baseband_input_bits=2)) is None
    assert CanaryController.from_config(
        _canary_cfg(baseband_format_type="naocpsr_snap1",
                    baseband_input_bits=-8)) is None
    assert CanaryController.from_config(_canary_cfg()) is not None


def test_canary_check_autocalibrate_and_slo():
    """First check calibrates; a later weak recovery fails the ratio
    gate, flips detection health and feeds the SLO sensitivity
    objective."""
    slo.configure(Config(slo_sensitivity_budget=0.1,
                         stream_name="beamC"))
    can = CanaryController.from_config(
        _canary_cfg(stream_name="beamC"))
    v1 = can.check(2, np.array([12.0]))
    assert v1["calibrated"] and v1["ok"] and v1["ratio"] == 1.0
    assert metrics.get("detection_health_state") == 0
    v2 = can.check(5, np.array([3.0]))
    assert not v2["ok"] and v2["ratio"] == pytest.approx(0.25)
    assert metrics.get("detection_health_state") == 1
    assert metrics.get("detection_health_state",
                       labels={"stream": "beamC"}) == 1
    assert metrics.get("canary_failed") == 1
    assert "sensitivity" in slo.tracker.objectives


# --------------------------------------------------------- e2e helpers


def _noise_file(tmp_path, n, segments, seed=7):
    rng = np.random.default_rng(seed)
    path = str(tmp_path / f"noise{seed}.bin")
    (rng.normal(128, 8, n * segments)
     ).clip(0, 255).astype(np.uint8).tofile(path)
    return path


def _e2e_cfg(tmp_path, tag, n=1 << 14, segments=6, **extra):
    return Config(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6,
        input_file_path=_noise_file(tmp_path, n, segments),
        baseband_output_file_prefix=str(tmp_path / f"{tag}_"),
        spectrum_channel_count=1 << 6,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        dm=0.0, baseband_reserve_sample=False,
        writer_thread_count=0, retry_backoff_base_s=0.001,
        inflight_segments=3, **extra)


def _journal_spans(path):
    out = []
    for line in open(path):
        line = line.strip()
        if line.startswith("{"):
            rec = json.loads(line)
            if rec.get("type") == "segment_span":
                out.append(rec)
    return out


# ---------------------------------------------------------- e2e canary


def test_canary_recovery_bit_identical_across_resume(tmp_path):
    """An interrupted + resumed run injects the same pulses on the
    same absolute segments and recovers bit-identical S/N (the
    resume-continuous ``_canary_base`` schedule + the deterministic
    delta)."""
    from srtb_tpu.pipeline.runtime import Pipeline

    def verdicts(journal):
        return [(r["segment"], r["canary"].get("snr"),
                 r["canary"].get("ok"))
                for r in _journal_spans(journal) if "canary" in r]

    j_full = str(tmp_path / "full.jsonl")
    cfg = _e2e_cfg(tmp_path, "full", canary_every_segments=2,
                   telemetry_journal_path=j_full)
    with Pipeline(cfg, sinks=[]) as pipe:
        assert pipe.run().segments == 6
    full = verdicts(j_full)
    assert len(full) == 3 and all(v[1] is not None for v in full)

    j_res = str(tmp_path / "resumed.jsonl")
    cfg2 = _e2e_cfg(tmp_path, "res", canary_every_segments=2,
                    telemetry_journal_path=j_res,
                    checkpoint_path=str(tmp_path / "ck.json"))
    with Pipeline(cfg2, sinks=[]) as pipe:
        pipe.run(max_segments=3)  # "crash" after an odd count
    with Pipeline(cfg2, sinks=[]) as pipe:
        pipe.run()
    assert verdicts(j_res) == full  # same segments, bit-equal S/N


def test_canary_quarantine_e2e(tmp_path):
    """The injected pulse IS loud enough to cross the detection
    threshold, yet no science artifact appears: the candidate sink
    never sees a canary segment, the journal + manifest carry the
    flags, and detection health stays OK."""
    from srtb_tpu.io.manifest import scan_manifest
    from srtb_tpu.pipeline.runtime import Pipeline

    journal = str(tmp_path / "q.jsonl")
    mfile = str(tmp_path / "manifest.jsonl")
    cfg = _e2e_cfg(tmp_path, "quar", canary_every_segments=2,
                   signal_detect_signal_noise_threshold=6.0,
                   telemetry_journal_path=journal,
                   run_manifest_path=mfile)
    with Pipeline(cfg) as pipe:  # default WriteSignalSink
        stats = pipe.run()
    assert stats.segments == 6
    assert metrics.get("canary_checked") == 3
    assert metrics.get("canary_failed") == 0
    # recovered S/N crossed the science threshold -> without the
    # quarantine these segments would have dumped candidates
    assert metrics.get("canary_last_snr") > 6.0
    assert stats.signals == 0
    produced = [f for f in os.listdir(tmp_path)
                if f.startswith("quar_") and not f.endswith(".bin")]
    assert produced == []
    spans = _journal_spans(journal)
    flagged = {r["segment"] for r in spans if "canary" in r}
    assert flagged == {1, 3, 5}
    assert all(r["canary"]["ok"] for r in spans if "canary" in r)
    # run manifest carries the canary records (tolerated by scan)
    recs = [json.loads(ln) for ln in open(mfile)
            if ln.strip().startswith("{")]
    canaries = [r for r in recs if r.get("t") == "canary"]
    assert {r["abs"] for r in canaries} == {1, 3, 5}
    assert all(r["ok"] for r in canaries)
    scan_manifest(mfile)  # unknown-record tolerance


def test_write_all_bit_identical_with_canary(tmp_path):
    """Tentpole acceptance: the contiguous baseband output of a
    canary-on run is byte-identical to a canary-off run — the sinks
    only ever see the pristine bytes (canary_exempt appender)."""
    from srtb_tpu.pipeline.runtime import Pipeline

    digests = {}
    for tag, every in [("coff", 0), ("con", 2)]:
        cfg = _e2e_cfg(tmp_path, tag, segments=4,
                       baseband_write_all=True,
                       canary_every_segments=every)
        with Pipeline(cfg) as pipe:
            assert pipe.run().segments == 4
        outs = sorted(f for f in os.listdir(tmp_path)
                      if f.startswith(f"{tag}_"))
        assert len(outs) == 1
        digests[tag] = hashlib.sha256(
            open(os.path.join(tmp_path, outs[0]), "rb").read()
        ).hexdigest()
    assert digests["con"] == digests["coff"]


def test_canary_gate_teeth_incident_and_health(tmp_path):
    """A run whose RFI config zaps the band out from under the pulse
    fails the sensitivity check: detection health degrades, /healthz
    grows the detection section, and an incident bundle lands with
    the canary verdict + quality timeline as extra.json."""
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.utils import telemetry

    clean = _e2e_cfg(tmp_path, "clean", segments=4,
                     canary_every_segments=2)
    with Pipeline(clean, sinks=[]) as pipe:
        pipe.run()
    expected = metrics.get("canary_last_snr")
    assert expected > 5.0
    metrics.reset()

    inc_dir = str(tmp_path / "incidents")
    degraded = _e2e_cfg(
        tmp_path, "deg", segments=4,
        canary_every_segments=2, quality_stats=True,
        canary_expected_snr=expected,
        mitigate_rfi_freq_list="1405-1466",
        incident_dir=inc_dir, incident_min_interval_s=0.0)
    with Pipeline(degraded, sinks=[]) as pipe:
        pipe.run()
    assert metrics.get("canary_failed") >= 1
    assert metrics.get("detection_health_state") == 1
    assert metrics.get("canary_sensitivity_ratio") < 0.5
    health = telemetry.health()
    assert health["detection"]["state"] == "degraded"
    assert health["detection"]["sensitivity_ratio"] < 0.5
    bundles = [d for d in os.listdir(inc_dir)
               if "canary_sensitivity" in d]
    assert bundles
    extra = json.load(open(os.path.join(
        inc_dir, bundles[0], "extra.json")))
    assert extra["canary"]["ok"] is False
    assert extra["canary"]["ratio"] < 0.5
    assert isinstance(extra["quality_timeline"], list)
    assert extra["quality_timeline"]  # quality rode along


def test_quality_journal_and_report_tools(tmp_path, capsys):
    """quality_stats journals the v9 extra and both report tools
    render it; empty journals exit 0 with a note (satellite 2)."""
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import quality_report as QR
    from srtb_tpu.tools import telemetry_report as TR

    journal = str(tmp_path / "j.jsonl")
    cfg = _e2e_cfg(tmp_path, "rep", segments=4, quality_stats=True,
                   canary_every_segments=2,
                   telemetry_journal_path=journal)
    with Pipeline(cfg, sinks=[]) as pipe:
        pipe.run()
    spans = _journal_spans(journal)
    assert all(r["v"] == 13 and "quality" in r for r in spans)
    q = spans[0]["quality"]
    assert set(q) >= {"zap_frac", "bandpass_mean", "sk_max",
                      "drift_score", "occupancy", "bandpass"}
    assert len(q["occupancy"]) == Config().quality_coarse_bins

    assert QR.main([journal, "--format", "json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["records"] == 4
    assert rep["canary"][""]["checked"] == 2
    assert rep["quality"][""]["records"] == 4
    assert QR.main([journal]) == 0
    md = capsys.readouterr().out
    assert "Data quality" in md and "Canary" in md
    # the general report still summarizes v9 spans
    assert TR.main([journal, "--format", "json"]) == 0
    assert json.loads(capsys.readouterr().out)["records"] == 4

    # satellite 2: empty / missing journals exit 0 with a note
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    for tool in (TR, QR):
        assert tool.main([empty, "--format", "json"]) == 0
        assert json.loads(capsys.readouterr().out)["records"] == 0
        assert tool.main([str(tmp_path / "missing.jsonl")]) == 0
        capsys.readouterr()


def test_quality_ladder_rung_first_and_family_registered():
    """The registry integration: the quality rung sheds the epilogue
    before any science, is a no-op when the epilogue is off, and the
    audited plan family exists (ladder=False: never demoted INTO)."""
    from srtb_tpu.pipeline import registry as R
    from srtb_tpu.resilience.demote import ladder_rungs

    assert R.ladder_order()[0] == "quality"
    fam = R.family("four_step_ftail_quality")
    assert fam is not None and not fam.ladder
    assert fam.cfg["quality_stats"] is True

    on = _proc_cfg()
    rungs = ladder_rungs(on, base_staged=False)
    assert rungs[0].step == "quality"
    assert rungs[0].cfg.quality_stats is False
    off = _proc_cfg(quality_stats=False)
    assert [r.step for r in ladder_rungs(off, base_staged=False)
            if r.step == "quality"] == []
