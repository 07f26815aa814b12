"""Async in-flight segment engine tests (pipeline/runtime.py).

Covers the acceptance criteria of the overlap engine:
- determinism: the overlapped engine produces bit-identical detect
  outputs and identical journal segment ordering vs the serial path;
- the CPU A/B harness (slow source + sleep-stub device + slow sink)
  shows the overlapped engine beating the serial path by >= 25%
  segments/s while journaling overlap_hidden_ms > 0;
- backpressure with a full in-flight window surfaces as *accounted*
  loss (segments_dropped) with a clean exit, never a stall;
- micro-batch mode (B segments in one vmapped jit call) matches the
  single-segment plan's detections;
- /metrics exposes the srtb_inflight_depth gauge;
- the telemetry report tolerates mixed v1/v2 journals.
"""

import json
import time
from typing import NamedTuple

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.io.backpressure import DropOldestSegmentBuffer
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.pipeline.work import SegmentWork
from srtb_tpu.utils.metrics import metrics


# ------------------------------------------------------------- fixtures


@pytest.fixture(scope="module")
def synth_file(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("overlap")
    n = 1 << 16
    data = make_dispersed_baseband(n * 4, 1405.0, 64.0, 1.0,
                                   pulse_positions=n // 2, nbits=8)
    path = str(tmp / "bb.bin")
    data.tofile(path)
    return path, n


def _cfg(path, n, tmp_path, tag, **extra):
    return Config(
        baseband_input_count=n,
        baseband_input_bits=8,
        baseband_freq_low=1405.0,
        baseband_bandwidth=64.0,
        baseband_sample_rate=128e6,
        dm=1.0,
        input_file_path=path,
        baseband_output_file_prefix=str(tmp_path / f"{tag}_"),
        spectrum_channel_count=1 << 8,
        signal_detect_max_boxcar_length=64,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=False,
        writer_thread_count=0,
        telemetry_journal_path=str(tmp_path / f"{tag}.jsonl"),
        **extra)


class _CaptureSink:
    """Records each drained segment's detect outputs as host arrays."""

    def __init__(self):
        self.detects = []
        self.positives = []

    def push(self, work, positive):
        det = work.detect
        self.detects.append((
            np.asarray(det.signal_counts).copy(),
            np.asarray(det.zero_count).copy(),
            np.asarray(det.time_series).copy()))
        self.positives.append(bool(positive))


def _run(cfg, sink=None):
    sinks = [sink] if sink is not None else []
    with Pipeline(cfg, sinks=sinks) as pipe:
        stats = pipe.run()
    return stats


# ----------------------------------------------------- determinism A/B


def test_overlapped_engine_bit_identical_to_serial(synth_file, tmp_path):
    """Same jit program either way: the in-flight window must change
    scheduling only, never results or journal ordering."""
    from srtb_tpu.tools import telemetry_report as TR

    path, n = synth_file
    out = {}
    for tag, w in (("serial", 1), ("overlap", 3)):
        metrics.reset()
        sink = _CaptureSink()
        cfg = _cfg(path, n, tmp_path, tag, inflight_segments=w)
        stats = _run(cfg, sink)
        recs = TR.load(cfg.telemetry_journal_path)
        out[tag] = (stats, sink, recs)
    s_stats, s_sink, s_recs = out["serial"]
    o_stats, o_sink, o_recs = out["overlap"]
    assert s_stats.segments == o_stats.segments == 4
    assert s_stats.signals == o_stats.signals >= 1
    assert len(s_sink.detects) == len(o_sink.detects) == 4
    for (sc_a, zc_a, ts_a), (sc_b, zc_b, ts_b) in zip(
            s_sink.detects, o_sink.detects):
        np.testing.assert_array_equal(sc_a, sc_b)
        np.testing.assert_array_equal(zc_a, zc_b)
        np.testing.assert_array_equal(ts_a, ts_b)
    assert s_sink.positives == o_sink.positives
    # journal ordering identical and monotonic in both modes
    assert [r["segment"] for r in s_recs] == list(range(4))
    assert [r["segment"] for r in o_recs] == list(range(4))
    # v2+v3+v4 schema fields present (v4 adds the compute-health
    # counters)
    for r in o_recs:
        assert r["v"] == 13
        assert "overlap_hidden_ms" in r
        assert r["inflight_depth"] >= 1
        assert r["degrade_level"] == 0 and r["retries"] == 0
    metrics.reset()


def test_micro_batch_matches_single_segment(synth_file, tmp_path):
    """B segments stacked into one vmapped jit call must yield the same
    detections as the single-segment plan (different XLA program, so
    counts exact + time series allclose, not bitwise)."""
    path, n = synth_file
    metrics.reset()
    sink_1 = _CaptureSink()
    _run(_cfg(path, n, tmp_path, "mb1", inflight_segments=1), sink_1)
    sink_b = _CaptureSink()
    cfg_b = _cfg(path, n, tmp_path, "mb2", inflight_segments=4,
                 micro_batch_segments=2)
    stats_b = _run(cfg_b, sink_b)
    assert stats_b.segments == 4
    assert len(sink_b.detects) == len(sink_1.detects) == 4
    for (sc_a, zc_a, ts_a), (sc_b, zc_b, ts_b) in zip(
            sink_1.detects, sink_b.detects):
        np.testing.assert_array_equal(sc_a, sc_b)
        np.testing.assert_array_equal(zc_a, zc_b)
        np.testing.assert_allclose(ts_a, ts_b, rtol=1e-5,
                                   atol=1e-4 * np.abs(ts_a).max())
    assert sink_1.positives == sink_b.positives
    # batch dispatches are admission-gated on the whole unit fitting:
    # in-flight depth never exceeds the configured window
    from srtb_tpu.tools import telemetry_report as TR
    depths = [r["inflight_depth"]
              for r in TR.load(cfg_b.telemetry_journal_path)]
    assert depths and max(depths) <= cfg_b.inflight_segments
    metrics.reset()


def test_micro_batch_validation():
    """Config errors must be loud: a batch larger than the window, and
    micro-batching the staged plan, both raise."""
    from srtb_tpu.pipeline.segment import SegmentProcessor

    cfg = Config(baseband_input_count=1 << 12,
                 baseband_reserve_sample=False,
                 inflight_segments=2, micro_batch_segments=4)
    proc = SegmentProcessor(cfg)

    class _NoSource:
        def __iter__(self):
            return iter(())

    pipe = Pipeline(cfg, source=_NoSource(), sinks=[], processor=proc)
    with pytest.raises(ValueError, match="exceeds"):
        pipe.run()
    staged = SegmentProcessor(cfg, staged=True)
    with pytest.raises(ValueError, match="fused plan"):
        staged.process_batch(np.zeros((2, 1 << 12), np.uint8))
    # run() rejects the staged+micro-batch combination up front, before
    # any segment is ingested or stacked
    cfg_ok = cfg.replace(inflight_segments=4)
    staged_pipe = Pipeline(cfg_ok, source=_NoSource(), sinks=[],
                           processor=staged)
    with pytest.raises(ValueError, match="fused plan"):
        staged_pipe.run()
    with pytest.raises(ValueError, match="batch must be"):
        proc.process_batch(np.zeros((2, 7), np.uint8))


def test_micro_batch_checkpoint_offsets_are_per_segment(synth_file,
                                                        tmp_path):
    """Each drained segment must checkpoint the source offset after ITS
    OWN ingest, not the post-batch offset: a crash after a partially
    drained batch must resume at the first undrained segment."""
    path, n = synth_file
    cfg = _cfg(path, n, tmp_path, "ckpt", inflight_segments=4,
               micro_batch_segments=2,
               checkpoint_path=str(tmp_path / "ckpt.json"))
    pipe = Pipeline(cfg, sinks=[])
    updates = []
    orig = pipe.checkpoint.update
    pipe.checkpoint.update = lambda done, off: (
        updates.append((done, off)), orig(done, off))
    with pipe:
        stats = pipe.run(max_segments=3)  # one full batch + a tail
    assert stats.segments == 3
    seg_bytes = cfg.segment_bytes(1)
    # reserve_sample=False: offsets advance one whole segment per drain
    assert updates == [(1, seg_bytes), (2, 2 * seg_bytes),
                       (3, 3 * seg_bytes)]


# ------------------------------------------------- sleep-stub A/B rig


class _StubDetect(NamedTuple):
    signal_counts: object
    zero_count: object
    time_series: object


class _AsyncStub:
    """Async device-array stand-in: ready at ``t_done``; a host fetch
    blocks until then (like a blocking device sync)."""

    def __init__(self, value, t_done):
        self._value = np.asarray(value)
        self._t_done = t_done

    def is_ready(self) -> bool:
        return time.perf_counter() >= self._t_done

    def __array__(self, dtype=None, copy=None):
        while time.perf_counter() < self._t_done:
            time.sleep(0.001)
        return self._value


class _SleepStubProcessor:
    """Device stub: dispatch returns immediately, results materialize
    ``device_s`` later; the device executes segments serially (segment
    k+1 starts only when k finishes), like a real accelerator queue."""

    def __init__(self, device_s: float):
        self.device_s = device_s
        self._free_at = 0.0

    def process(self, raw):
        t_done = max(time.perf_counter(), self._free_at) + self.device_s
        self._free_at = t_done
        det = _StubDetect(
            signal_counts=_AsyncStub(np.zeros((1, 4), np.int64), t_done),
            zero_count=_AsyncStub(np.asarray(0), t_done),
            time_series=_AsyncStub(np.zeros(8, np.float32), t_done))
        return None, det


class _SlowSource:
    """N segments, each costing ``ingest_s`` of host time to produce."""

    def __init__(self, n_segments: int, ingest_s: float,
                 seg_bytes: int = 64):
        self.n = n_segments
        self.ingest_s = ingest_s
        self.seg_bytes = seg_bytes
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self) -> SegmentWork:
        if self._i >= self.n:
            raise StopIteration
        time.sleep(self.ingest_s)
        self._i += 1
        return SegmentWork(data=np.zeros(self.seg_bytes, np.uint8),
                           timestamp=self._i)


class _SlowSink:
    def __init__(self, sink_s: float):
        self.sink_s = sink_s
        self.count = 0

    def push(self, work, positive):
        time.sleep(self.sink_s)
        self.count += 1


def _stub_pipeline(tmp_path, tag, n_seg, window, ingest_s, device_s,
                   sink_s):
    cfg = Config(baseband_input_count=64,
                 baseband_reserve_sample=False,
                 inflight_segments=window, writer_thread_count=0,
                 telemetry_journal_path=str(tmp_path / f"{tag}.jsonl"))
    sink = _SlowSink(sink_s)
    pipe = Pipeline(cfg, source=_SlowSource(n_seg, ingest_s), sinks=[sink],
                    processor=_SleepStubProcessor(device_s))
    stats = pipe.run()
    pipe.close()
    return cfg, stats, sink


def test_overlap_ab_harness_hides_host_time(tmp_path):
    """The acceptance A/B: slow source + sleep-stub device + slow sink.
    Serial pays ingest + device + sink per segment; the overlapped
    engine hides ingest and sink under device compute, so segments/s
    must improve by >= 25% (the modeled win here is ~2x) and the
    journal must show overlap_hidden_ms > 0."""
    from srtb_tpu.tools import telemetry_report as TR

    metrics.reset()
    n_seg, ingest_s, device_s, sink_s = 10, 0.02, 0.04, 0.02
    _, s_stats, s_sink = _stub_pipeline(
        tmp_path, "ab_serial", n_seg, 1, ingest_s, device_s, sink_s)
    cfg_o, o_stats, o_sink = _stub_pipeline(
        tmp_path, "ab_overlap", n_seg, 3, ingest_s, device_s, sink_s)
    assert s_stats.segments == o_stats.segments == n_seg
    assert s_sink.count == o_sink.count == n_seg
    serial_rate = n_seg / s_stats.elapsed_s
    overlap_rate = n_seg / o_stats.elapsed_s
    assert overlap_rate >= 1.25 * serial_rate, (
        f"overlap {overlap_rate:.2f} seg/s vs serial "
        f"{serial_rate:.2f} seg/s")
    recs = TR.load(cfg_o.telemetry_journal_path)
    assert len(recs) == n_seg
    assert [r["segment"] for r in recs] == list(range(n_seg))
    # most segments' host work hid under device compute
    hidden = [r["overlap_hidden_ms"] for r in recs]
    assert sum(1 for h in hidden if h > 0) >= n_seg - 2
    rep = TR.report(cfg_o.telemetry_journal_path)
    assert rep["overlap"]["efficiency"] > 0.3
    assert rep["stages"]["overlap"]["count"] == n_seg
    # the inflight gauge is exposed to Prometheus
    assert "srtb_inflight_depth" in metrics.prometheus()
    metrics.reset()


# ------------------------------------------------ backpressure / loss


def test_full_window_backpressure_is_accounted_loss(tmp_path):
    """A source faster than the device with a full in-flight window:
    the excess must surface as accounted segments_dropped (drop-oldest
    buffer), the engine must keep draining, and the run must exit
    cleanly with ordered journal records — never stall."""
    from srtb_tpu.tools import telemetry_report as TR

    metrics.reset()
    n_seg = 24
    src = DropOldestSegmentBuffer(_SlowSource(n_seg, 0.001), capacity=3)
    cfg = Config(baseband_input_count=64,
                 baseband_reserve_sample=False,
                 inflight_segments=2, writer_thread_count=0,
                 telemetry_journal_path=str(tmp_path / "bp.jsonl"))
    pipe = Pipeline(cfg, source=src, sinks=[],
                    processor=_SleepStubProcessor(0.02))
    stats = pipe.run()
    pipe.close()
    src.close()
    dropped = metrics.get("segments_dropped")
    assert dropped > 0, "overload must surface as accounted loss"
    assert src.dropped == dropped
    # nothing lost silently: every produced segment was either drained
    # or accounted as dropped
    assert stats.segments + src.dropped == n_seg
    recs = TR.load(cfg.telemetry_journal_path)
    assert len(recs) == stats.segments
    segs = [r["segment"] for r in recs]
    assert segs == sorted(segs)
    # the journal's cumulative drop counter caught the loss
    assert recs[-1]["segments_dropped"] == dropped
    metrics.reset()


def test_drop_oldest_buffer_clean_passthrough():
    """No overload -> no drops, all segments delivered in order."""
    metrics.reset()
    src = DropOldestSegmentBuffer(_SlowSource(5, 0.0), capacity=8)
    got = [seg.timestamp for seg in src]
    assert got == [1, 2, 3, 4, 5]
    assert src.dropped == 0
    src.close()
    metrics.reset()


def test_drop_oldest_buffer_propagates_source_error():
    class _Boom:
        def __iter__(self):
            return self

        def __next__(self):
            raise OSError("receiver died")

    src = DropOldestSegmentBuffer(_Boom(), capacity=2)
    with pytest.raises(OSError, match="receiver died"):
        next(iter(src))
    src.close()


def test_sink_failure_propagates_from_pipe(tmp_path):
    """A crashing sink on the off-critical-path pipe must fail the run
    loudly, not hang the engine or lose the exception."""

    class _BoomSink:
        def push(self, work, positive):
            raise RuntimeError("sink exploded")

    metrics.reset()
    cfg = Config(baseband_input_count=64, baseband_reserve_sample=False,
                 inflight_segments=3, writer_thread_count=0)
    pipe = Pipeline(cfg, source=_SlowSource(6, 0.0), sinks=[_BoomSink()],
                    processor=_SleepStubProcessor(0.001))
    with pytest.raises(RuntimeError, match="sink exploded"):
        pipe.run()
    pipe.close()
    metrics.reset()


# ------------------------------------------------ mixed-schema journal


def test_telemetry_report_tolerates_mixed_v1_v2(tmp_path):
    """Rotation can leave a v1 tail next to v2 records: the report must
    summarize both without KeyError, and overlap stats must cover only
    the records that carry the v2 fields."""
    from srtb_tpu.tools import telemetry_report as TR

    path = tmp_path / "mixed.jsonl"
    with open(path, "w") as f:
        # v1 record: no overlap_hidden_ms / inflight_depth / samples
        f.write(json.dumps({
            "type": "segment_span", "v": 1, "ts": 1000.0, "segment": 0,
            "stages_ms": {"dispatch": 2.0, "fetch": 1.0},
            "queue_depth": 1, "detections": 0, "dump": False}) + "\n")
        # degenerate v1 record: no stages_ms at all
        f.write(json.dumps({
            "type": "segment_span", "v": 1, "ts": 1000.5,
            "segment": 1}) + "\n")
        # v2 record
        f.write(json.dumps({
            "type": "segment_span", "v": 2, "ts": 1001.0, "segment": 2,
            "stages_ms": {"dispatch": 2.0, "fetch": 1.0, "sink": 1.0},
            "queue_depth": 2, "detections": 1, "dump": True,
            "samples": 64, "overlap_hidden_ms": 3.0,
            "inflight_depth": 2}) + "\n")
    rep = TR.report(str(path))
    assert rep["records"] == 3
    assert rep["stages"]["dispatch"]["count"] == 2
    # overlap section: only the v2 record qualifies
    ov = rep["overlap"]
    assert ov["records"] == 1
    assert ov["hidden_mean_ms"] == 3.0
    assert ov["efficiency"] == 0.75  # 3 hidden vs 1 blocked fetch
    assert ov["inflight_depth_max"] == 2
    # overlap pseudo-stage present but excluded from the segment sum
    assert rep["stages"]["overlap"]["count"] == 1
    assert rep["stages"]["segment"]["max_ms"] == 4.0
    md = TR._md(rep)
    assert "Overlap (async engine)" in md
    assert TR.main([str(path), "--format", "json"]) == 0


def test_timeline_stall_shows_zero_bins(tmp_path):
    """A mid-run stall (no journal records for a stretch) must render
    as explicit 0-seg/s bins, not silently missing rows."""
    from srtb_tpu.tools import telemetry_report as TR

    path = tmp_path / "stall.jsonl"
    with open(path, "w") as f:
        for ts in (1000.0, 1001.0, 1035.0):  # 30+ s gap mid-run
            f.write(json.dumps({"type": "segment_span", "v": 2,
                                "ts": ts, "segment": 0,
                                "stages_ms": {"sink": 1.0},
                                "samples": 1}) + "\n")
    tl = TR.timeline(TR.load(str(path)), bin_s=10.0)
    assert [b["t_start_s"] for b in tl] == [0.0, 10.0, 20.0, 30.0]
    assert tl[1]["segments"] == 0 and tl[1]["segments_per_sec"] == 0.0
    assert tl[2]["segments"] == 0
    assert tl[0]["segments"] == 2 and tl[3]["segments"] == 1
