"""Device-time accounting (ISSUE 14, cut to what is measured in PR 33):
``device_ms`` on the journal spans, the ``device_seconds`` histogram
with its per-stream twin, the compile / AOT-cache books, and the
on-demand jax.profiler capture hook."""

import json
import os

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.utils.metrics import metrics


def _obs_cfg(tmp_path, n, **kw):
    from srtb_tpu.io.synth import make_dispersed_baseband
    bb = str(tmp_path / "bb.bin")
    segs = kw.pop("segments", 3)
    make_dispersed_baseband(n * segs, 1405.0, 64.0, 0.0,
                            pulse_positions=n // 2,
                            nbits=8).tofile(bb)
    return Config(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=0.0, input_file_path=bb,
        baseband_output_file_prefix=str(tmp_path / "out_"),
        spectrum_channel_count=kw.pop("spectrum_channel_count", 32),
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=False, writer_thread_count=0, **kw)


# the second yardstick's names: no span key and no /metrics family
GONE = ("roofline_frac", "achieved_msamps", "achieved_gbps")


def test_device_accounting_v8_spans_and_gauges(tmp_path):
    """Every drained segment of the async engine journals device_ms
    (v8) plus the cumulative compile/cache books, and the
    device_seconds histogram lands on /metrics — with a per-stream
    labeled twin for a named lane.  Nothing modelled rides along."""
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import telemetry_report as TR
    n = 1 << 13
    journal = str(tmp_path / "j.jsonl")
    cfg = _obs_cfg(tmp_path, n, segments=4, inflight_segments=2,
                   telemetry_journal_path=journal,
                   stream_name="beam7")
    metrics.reset()
    with Pipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    assert stats.segments == 4
    recs = TR.load(journal)
    assert len(recs) == 4
    for r in recs:
        assert r["v"] == 13
        assert r["device_ms"] > 0
        assert not set(GONE) & set(r), r
        assert r["aot_cache_hits"] == 0 and r["aot_cache_misses"] == 0
    # first dispatch = the run's one (lazy-jit) compile event, and the
    # named span carries the stream's OWN labeled books
    assert recs[-1]["plan_compiles"] == 1
    assert recs[-1]["compile_ms"] > 0
    assert metrics.get("plan_compiles",
                       labels={"stream": "beam7"}) == 1
    # device_ms is concurrent, never inside the host stage sum
    assert "device" not in recs[0]["stages_ms"]
    # the histogram and its labeled twin saw every segment
    assert metrics.histogram("device_seconds").count == 4
    assert metrics.histogram(
        "device_seconds", labels={"stream": "beam7"}).count == 4
    prom = metrics.prometheus()
    assert "# TYPE srtb_device_seconds histogram" in prom
    assert 'srtb_device_seconds_count{stream="beam7"} 4' in prom
    assert 'srtb_plan_compiles{stream="beam7"}' in prom
    for name in GONE:
        assert f"srtb_{name}" not in prom, name
    # report surfaces the device section
    rep = TR.report(journal)
    assert rep["device"]["records"] == 4
    assert rep["device"]["plan_compiles"] == 1
    assert rep["device"]["device_p50_ms"] > 0
    assert not [k for k in rep["device"] if k.startswith(GONE)]
    md = TR._md(rep)
    assert "## Device time (performance observatory)" in md


def test_serial_device_time_is_exact_fetch_wall(tmp_path):
    """inflight_segments=1: device_ms is the dispatch->blocking-fetch
    wall — it must be >= the fetch stage and bounded by the segment's
    host wall + fetch (no queue-wait inflation in serial mode)."""
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import telemetry_report as TR
    n = 1 << 13
    journal = str(tmp_path / "j.jsonl")
    cfg = _obs_cfg(tmp_path, n, segments=3, inflight_segments=1,
                   telemetry_journal_path=journal)
    metrics.reset()
    with Pipeline(cfg, sinks=[]) as pipe:
        pipe.run()
    for r in TR.load(journal):
        assert r["device_ms"] >= r["stages_ms"]["fetch"] * 0.99
        # serial: nothing else runs between dispatch and fetch
        total = sum(r["stages_ms"].values())
        assert r["device_ms"] <= total + 50.0


def test_threaded_pipeline_omits_unmeasured_device_time(tmp_path):
    """ThreadedPipeline does not measure the dispatch->ready wall: its
    spans must OMIT device_ms (never journal a fake 0), while the
    compile/cache books still ride along."""
    from srtb_tpu.pipeline.runtime import ThreadedPipeline
    from srtb_tpu.tools import telemetry_report as TR
    n = 1 << 13
    journal = str(tmp_path / "j.jsonl")
    cfg = _obs_cfg(tmp_path, n, segments=3,
                   telemetry_journal_path=journal)
    metrics.reset()
    with ThreadedPipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    recs = TR.load(journal)
    assert len(recs) == stats.segments >= 2
    for r in recs:
        assert r["v"] == 13
        assert "device_ms" not in r
        assert "compile_ms" in r and "plan_compiles" in r


def test_aot_cache_hit_miss_counters(tmp_path, monkeypatch):
    """The AOT protocol's cache economics are counters now: a cold
    build records misses + exact compile seconds, a warm restart
    records hits and no new compile."""
    from srtb_tpu.pipeline.segment import SegmentProcessor
    monkeypatch.setenv("SRTB_AOT_ALLOW_CPU", "1")
    n = 1 << 12
    cfg = Config(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=0.0,
        spectrum_channel_count=16,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=False, fft_strategy="four_step",
        aot_plan_path=str(tmp_path / "aot"))
    metrics.reset()
    p1 = SegmentProcessor(cfg)
    assert p1.aot_active
    assert metrics.get("aot_cache_misses") >= 1
    assert metrics.get("aot_cache_hits") == 0
    assert metrics.get("compile_seconds") > 0
    compiles0 = metrics.get("plan_compiles")
    # warm restart: loads, compiles nothing
    p2 = SegmentProcessor(cfg)
    assert p2.aot_active
    assert metrics.get("aot_cache_hits") >= 1
    assert metrics.get("plan_compiles") == compiles0
    # an AOT-active first dispatch is NOT a lazy-jit compile event
    rng = np.random.default_rng(0)
    raw = rng.integers(0, 256, size=cfg.segment_bytes(1),
                       dtype=np.uint8)
    p2.process(raw)
    assert metrics.get("plan_compiles") == compiles0


def test_profile_capture_hook(tmp_path):
    """Config.profile_capture_segments records a real jax.profiler
    trace of the first N segments with a capture.json sidecar whose
    trace_ids join the journal spans."""
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import telemetry_report as TR
    n = 1 << 12
    cap = str(tmp_path / "prof")
    journal = str(tmp_path / "j.jsonl")
    cfg = _obs_cfg(tmp_path, n, segments=3, inflight_segments=1,
                   spectrum_channel_count=16,
                   telemetry_journal_path=journal,
                   profile_capture_segments=2,
                   profile_capture_dir=cap)
    metrics.reset()
    with Pipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    assert stats.segments == 3
    side = os.path.join(cap, "capture.json")
    if not os.path.exists(side):
        pytest.skip("jax.profiler unavailable on this backend")
    doc = json.load(open(side))
    assert doc["segments"] == 2
    assert doc["first_segment"] == 0 and doc["last_segment"] == 1
    # the sidecar's trace_ids are the journal's — the join key between
    # the device timeline and the causal-event/journal timeline
    recs = TR.load(journal)
    tids = [r.get("trace_id") for r in recs[:2]]
    assert [doc["first_trace_id"], doc["last_trace_id"]] == tids
    assert metrics.get("profile_captures") == 1
    # the capture wrote actual profiler artifacts next to the sidecar
    files = [f for _, _, fs in os.walk(cap) for f in fs
             if f != "capture.json"]
    assert files, "no profiler trace files written"
