"""End-to-end pipeline tests on synthetic baseband.

The reference has no automated end-to-end test (integration was manual on
the J1644-4559 file, SURVEY.md §4); here we go further: synthesize a
dispersed pulse in quantized baseband, run the full file -> unpack -> FFT
-> RFI -> dedisperse -> waterfall -> detect -> write chain, and assert the
pulse is recovered and the output files are format-compatible.
"""

import glob
import os

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.runtime import Pipeline, has_signal
from srtb_tpu.pipeline.segment import SegmentProcessor

from slow_source import SlowDevice, SlowFile


@pytest.fixture(scope="module")
def synthetic_cfg(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("e2e")
    n = 1 << 18
    f_min, bw, dm = 1405.0, 64.0, 60.0
    data = make_dispersed_baseband(n * 2, f_min, bw, dm,
                                   pulse_positions=n // 2, nbits=8)
    path = str(tmp / "baseband.bin")
    data.tofile(path)
    cfg = Config(
        baseband_input_count=n,
        baseband_input_bits=8,
        baseband_format_type="simple",
        baseband_freq_low=f_min,
        baseband_bandwidth=bw,
        baseband_sample_rate=128e6,
        dm=dm,
        input_file_path=path,
        baseband_output_file_prefix=str(tmp / "out_"),
        spectrum_channel_count=1 << 8,
        signal_detect_signal_noise_threshold=6.0,
        signal_detect_max_boxcar_length=64,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=True,
    )
    return cfg


def test_segment_processor_shapes(synthetic_cfg):
    cfg = synthetic_cfg
    proc = SegmentProcessor(cfg)
    raw = np.fromfile(cfg.input_file_path, dtype=np.uint8,
                      count=cfg.baseband_input_count)
    wf_ri, res = proc.process(raw)
    n_spec = cfg.baseband_input_count // 2
    assert wf_ri.shape == (2, 1, cfg.spectrum_channel_count,
                           n_spec // cfg.spectrum_channel_count)
    assert np.asarray(res.signal_counts).shape[0] == 1


def test_pipeline_detects_dispersed_pulse(synthetic_cfg):
    cfg = synthetic_cfg
    pipe = Pipeline(cfg)
    stats = pipe.run()
    assert stats.segments >= 2  # overlap-save re-reads the tail
    assert stats.signals >= 1, "dispersed pulse must be detected"
    # candidate files written in reference-compatible formats
    sink = pipe.sinks[0]
    assert sink.written, "no candidates written"
    files = sink.written[0]
    assert os.path.exists(files.bin_path)
    assert files.npy_paths
    wf = np.load(files.npy_paths[0])
    assert wf.dtype == np.complex64
    assert wf.shape[0] == cfg.spectrum_channel_count
    assert files.tim_paths
    ts = np.fromfile(files.tim_paths[0], dtype="<f4")
    assert ts.size > 0


def test_pipeline_without_dedispersion_misses_pulse(synthetic_cfg, tmp_path):
    """Sanity: with dm=0 the pulse stays smeared below threshold — the
    detection in the previous test is genuinely due to coherent
    dedispersion."""
    cfg = synthetic_cfg.replace(
        dm=0.0, baseband_output_file_prefix=str(tmp_path / "nodm_"))
    pipe = Pipeline(cfg)
    stats = pipe.run()
    assert stats.signals == 0


def test_hamming_window_waterfall_matches_numpy_oracle():
    """Non-rectangle windows must be applied at unpack AND divided back out
    of the dynamic spectrum after the backward C2C (ref: fft_pipe.hpp:
    346-359) — a float64 numpy transliteration of the whole chain is the
    oracle."""
    from srtb_tpu.ops import rfi as R
    from srtb_tpu.ops import window as W
    from srtb_tpu.pipeline.segment import waterfall_to_numpy

    n, channels = 1 << 12, 1 << 5
    rng = np.random.default_rng(3)
    raw = rng.integers(0, 256, size=n, dtype=np.uint8)
    cfg = Config(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_format_type="simple", baseband_freq_low=1405.0,
        baseband_bandwidth=64.0, baseband_sample_rate=128e6, dm=0.0,
        spectrum_channel_count=channels,
        signal_detect_max_boxcar_length=8,
        mitigate_rfi_average_method_threshold=1e9,
        mitigate_rfi_spectral_kurtosis_threshold=1e9,
        baseband_reserve_sample=False)
    proc = SegmentProcessor(cfg, window_name="hamming")
    wf = waterfall_to_numpy(proc.process(raw)[0])[0]

    # numpy float64 oracle (dm=0 -> unit chirp; RFI thresholds disabled)
    x = raw.astype(np.float64) * W.window_coefficients(
        "hamming", n, dtype=np.float64)
    spec = np.fft.rfft(x)[:-1] * R.normalization_coefficient(
        n // 2, channels)
    wlen = (n // 2) // channels
    expect = np.fft.ifft(spec.reshape(channels, wlen), axis=-1) * wlen
    expect = expect / W.window_coefficients("hamming", wlen,
                                            dtype=np.float64)
    np.testing.assert_allclose(wf, expect.astype(np.complex64),
                               rtol=1e-3, atol=1e-3)


def test_hann_window_zero_edges_stay_finite():
    """Hann coefficients are exactly zero at the row edges; the de-apply
    must not produce inf/nan there (guarded division — the one deliberate
    deviation from the reference's raw divide)."""
    n, channels = 1 << 12, 1 << 5
    raw = np.random.default_rng(4).integers(0, 256, size=n, dtype=np.uint8)
    cfg = Config(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_format_type="simple", baseband_freq_low=1405.0,
        baseband_bandwidth=64.0, baseband_sample_rate=128e6, dm=5.0,
        spectrum_channel_count=channels,
        signal_detect_max_boxcar_length=8,
        baseband_reserve_sample=False)
    proc = SegmentProcessor(cfg, window_name="hann")
    wf_ri, res = proc.process(raw)
    assert np.isfinite(np.asarray(wf_ri)).all()
    assert np.isfinite(np.asarray(res.time_series)).all()


def test_has_signal_channel_threshold_gate():
    """When too many channels are zapped the segment must be ignored
    (ref: signal_detect_pipe.hpp:343-345)."""
    class FakeDetect:
        zero_count = np.asarray(250)
        signal_counts = np.asarray([5, 2, 0])
    cfg = Config(spectrum_channel_count=256,
                 signal_detect_channel_threshold=0.9)
    assert has_signal(cfg, FakeDetect()) is False
    FakeDetect.zero_count = np.asarray(10)
    assert has_signal(cfg, FakeDetect()) is True


def test_threaded_pipeline_matches_serial(synthetic_cfg, tmp_path):
    """ThreadedPipeline (thread-per-host-stage over bounded queues) must
    find the same signals as the serial loop."""
    from srtb_tpu.pipeline.runtime import ThreadedPipeline
    cfg = synthetic_cfg.replace(
        baseband_output_file_prefix=str(tmp_path / "thr_"))
    pipe = ThreadedPipeline(cfg)
    stats = pipe.run()
    assert stats.segments >= 2
    assert stats.signals >= 1


def test_pipeline_pallas_path_matches(synthetic_cfg, tmp_path):
    """use_pallas (fused df64 chirp multiply in a Pallas kernel) must give
    the same detections as the precomputed-chirp path."""
    cfg2 = synthetic_cfg.replace(
        use_pallas=True,
        baseband_output_file_prefix=str(tmp_path / "pl_"))
    pipe = Pipeline(cfg2)
    stats = pipe.run()
    assert stats.signals >= 1


def test_pallas_path_multi_stream_matches(tmp_path):
    """use_pallas with a 2-polarization format must match the jnp path's
    detections stream for stream."""
    n = 1 << 14
    rng = np.random.default_rng(9)
    raw = rng.integers(0, 256, size=2 * n, dtype=np.uint8)
    base = dict(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_format_type="naocpsr_snap1", baseband_freq_low=1405.0,
        baseband_bandwidth=64.0, baseband_sample_rate=128e6, dm=20.0,
        spectrum_channel_count=1 << 6,
        signal_detect_max_boxcar_length=16,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=False)
    p_ref = SegmentProcessor(Config(**base))
    p_pal = SegmentProcessor(Config(**base, use_pallas=True,
                                    use_pallas_sk=True))
    wf_a, res_a = p_ref.process(raw)
    wf_b, res_b = p_pal.process(raw)
    assert np.asarray(res_a.signal_counts).shape == \
        np.asarray(res_b.signal_counts).shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(res_a.zero_count),
                                  np.asarray(res_b.zero_count))
    np.testing.assert_allclose(np.asarray(res_a.time_series),
                               np.asarray(res_b.time_series),
                               rtol=1e-3, atol=1e-2)
    np.testing.assert_allclose(np.asarray(wf_a), np.asarray(wf_b),
                               rtol=1e-3, atol=1e-2)


def test_staged_matches_fused(synthetic_cfg):
    """The staged three-program plan (used for 2^30-class segments, with
    the chirp generated in-step) must reproduce the fused plan's output.
    The chirp differs by construction (host f64 bank vs in-trace df64),
    so tolerances are df64-level, not bitwise."""
    cfg = synthetic_cfg
    fused = SegmentProcessor(cfg)
    staged = SegmentProcessor(cfg, staged=True)
    assert staged.chirp is None  # no bank materialized
    raw = np.fromfile(cfg.input_file_path, dtype=np.uint8,
                      count=cfg.baseband_input_count)
    wf_f, res_f = fused.process(raw)
    wf_s, res_s = staged.process(raw)
    wf_f, wf_s = np.asarray(wf_f), np.asarray(wf_s)
    scale = np.abs(wf_f).max()
    np.testing.assert_allclose(wf_s, wf_f, atol=5e-3 * scale, rtol=0)
    assert np.array_equal(np.asarray(res_f.signal_counts),
                          np.asarray(res_s.signal_counts))
    ts_f = np.asarray(res_f.time_series)
    np.testing.assert_allclose(np.asarray(res_s.time_series), ts_f,
                               rtol=0, atol=5e-3 * np.abs(ts_f).max())


def test_staged_multistream_and_window(tmp_path):
    """Staged plan with a 2-stream interleaved format and a hann window:
    the window must be applied at unpack and de-applied after the
    waterfall C2C in stage (c), identically to the fused plan."""
    from srtb_tpu.io.synth import make_dispersed_baseband

    n = 1 << 16
    f_min, bw, dm = 1405.0, 64.0, 30.0
    one = make_dispersed_baseband(n, f_min, bw, dm,
                                  pulse_positions=n // 2, nbits=8)
    # byte-interleave two copies ("1212", ref: unpack.hpp:214-244)
    raw = np.empty(2 * n, dtype=np.uint8)
    raw[0::2] = one
    raw[1::2] = one
    cfg = Config(
        baseband_input_count=n,
        baseband_input_bits=8,
        baseband_format_type="interleaved_samples_2",
        baseband_freq_low=f_min,
        baseband_bandwidth=bw,
        baseband_sample_rate=128e6,
        dm=dm,
        spectrum_channel_count=1 << 7,
        signal_detect_signal_noise_threshold=6.0,
        baseband_reserve_sample=False,
    )
    fused = SegmentProcessor(cfg, window_name="hann")
    staged = SegmentProcessor(cfg, window_name="hann", staged=True)
    wf_f, res_f = fused.process(raw)
    wf_s, res_s = staged.process(raw)
    wf_f, wf_s = np.asarray(wf_f), np.asarray(wf_s)
    assert wf_f.shape[1] == 2  # two data streams
    scale = np.abs(wf_f).max()
    np.testing.assert_allclose(wf_s, wf_f, atol=5e-3 * scale, rtol=0)
    assert np.array_equal(np.asarray(res_f.signal_counts),
                          np.asarray(res_s.signal_counts))


def test_blocked_subbyte_strategies_and_staged_match():
    """Sub-byte simple-format segments run the fused blocked-plane R2C
    (ops/fft.rfft_subbyte: unpack + pack + FFT with no sample-order
    interleave).  Every strategy and the staged plan must agree with the
    classic monolithic path, window included."""
    from srtb_tpu.io.synth import make_dispersed_baseband

    n = 1 << 16
    f_min, bw, dm = 1405.0, 64.0, 30.0
    raw = make_dispersed_baseband(n, f_min, bw, dm,
                                  pulse_positions=n // 2, nbits=2)
    base = dict(
        baseband_input_count=n,
        baseband_input_bits=2,
        baseband_format_type="simple",
        baseband_freq_low=f_min,
        baseband_bandwidth=bw,
        baseband_sample_rate=128e6,
        dm=dm,
        spectrum_channel_count=1 << 7,
        signal_detect_signal_noise_threshold=6.0,
        baseband_reserve_sample=False,
    )
    ref = SegmentProcessor(Config(fft_strategy="monolithic", **base),
                           window_name="hann")
    assert ref._blocked_subbyte
    wf_ref, res_ref = ref.process(raw)
    wf_ref = np.asarray(wf_ref)
    scale = np.abs(wf_ref).max()
    variants = {
        "four_step": SegmentProcessor(
            Config(fft_strategy="four_step", **base), window_name="hann"),
        "mxu": SegmentProcessor(
            Config(fft_strategy="mxu", **base), window_name="hann"),
        "staged": SegmentProcessor(
            Config(fft_strategy="four_step", **base), window_name="hann",
            staged=True),
        "four_step+pallas": SegmentProcessor(
            Config(fft_strategy="four_step", use_pallas=True, **base),
            window_name="hann"),
    }
    for name, proc in variants.items():
        wf, res = proc.process(raw)
        np.testing.assert_allclose(
            np.asarray(wf), wf_ref, atol=5e-3 * scale, rtol=0,
            err_msg=name)
        assert np.array_equal(np.asarray(res.signal_counts),
                              np.asarray(res_ref.signal_counts)), name


def test_segment_deadline_fires_and_cancels(synthetic_cfg):
    """segment_deadline_s: the watchdog must fire on a wedged device sync
    and must NOT fire on a healthy one (cancel on success)."""
    import time as _time

    from srtb_tpu.pipeline.runtime import Pipeline

    cfg = synthetic_cfg.replace(segment_deadline_s=0.2,
                                writer_thread_count=0)
    p = Pipeline(cfg)
    fired = []
    p._on_segment_deadline = lambda: fired.append(True)
    # healthy: a fast fetch must not trip the timer
    assert p._sync_with_deadline(lambda: 42) == 42
    _time.sleep(0.3)
    assert not fired
    # wedged: a fetch slower than the deadline trips it
    p._sync_with_deadline(lambda: _time.sleep(0.4))
    assert fired
    p.close()


def test_staged_pallas_rows_impl_matches_default(monkeypatch):
    """SRTB_STAGED_ROWS_IMPL=pallas (the 2^30 SIGSEGV workaround
    candidate: Pallas leg FFTs instead of XLA's batched FFT) must
    produce the same staged-plan waterfall, blocked and classic.

    CPU-sized segments have four-step legs below pallas_fft.supported's
    2^12 minimum, so the kernel itself can't fire here (its numerics
    are pinned at supported sizes by tests/test_pallas_fft.py); this
    test asserts the *dispatch* — the env knob reaches _fft_minor as
    rows_impl='pallas_interpret' — plus numeric parity of the plan."""
    import numpy as np

    from srtb_tpu.config import Config
    from srtb_tpu.ops import fft as F
    from srtb_tpu.pipeline.segment import SegmentProcessor, \
        waterfall_to_numpy

    cfg = Config(
        baseband_input_count=1 << 14,
        baseband_input_bits=2,
        baseband_format_type="simple",
        baseband_freq_low=1405.0,
        baseband_bandwidth=64.0,
        baseband_sample_rate=128e6,
        dm=30.0,
        spectrum_channel_count=1 << 5,
        mitigate_rfi_average_method_threshold=1e9,
        mitigate_rfi_spectral_kurtosis_threshold=1e9,
        baseband_reserve_sample=False,
    )
    rng = np.random.default_rng(9)
    raw = rng.integers(0, 256, cfg.segment_bytes(1), dtype=np.uint8)
    impls_seen = []
    orig = F._fft_minor

    def spy(x, inverse, rows_impl="xla", len_cap=None):
        impls_seen.append(rows_impl)
        return orig(x, inverse, rows_impl, len_cap)

    for blocked in ("0", "1"):
        monkeypatch.setenv("SRTB_STAGED_BLOCKED", blocked)
        monkeypatch.delenv("SRTB_STAGED_ROWS_IMPL", raising=False)
        base = waterfall_to_numpy(
            SegmentProcessor(cfg, staged=True).process(raw)[0])
        monkeypatch.setenv("SRTB_STAGED_ROWS_IMPL", "pallas")
        monkeypatch.setattr(F, "_fft_minor", spy)
        impls_seen.clear()
        got = waterfall_to_numpy(
            SegmentProcessor(cfg, staged=True).process(raw)[0])
        monkeypatch.setattr(F, "_fft_minor", orig)
        assert "pallas_interpret" in impls_seen, impls_seen
        np.testing.assert_allclose(got, base, rtol=2e-3, atol=2e-4)
    # a typo'd knob value must raise, not silently fall back to XLA
    monkeypatch.setenv("SRTB_STAGED_ROWS_IMPL", "palas")
    import pytest
    with pytest.raises(ValueError, match="'xla' or 'pallas'"):
        SegmentProcessor(cfg, staged=True).process(raw)


@pytest.mark.parametrize("impl", ["pallas2", "mxu"])
def test_staged_rows_impl_is_xla_or_pallas(monkeypatch, impl):
    """`SRTB_STAGED_ROWS_IMPL` chooses between XLA's legs and the VMEM
    row-FFT kernel's; `pallas2` (the first spelling of the two passes,
    gone in PR 50: no v5e compiled it) and a name that used to run the
    Pallas legs unsaid are refused when the plan is built, naming the
    two that are left.  A plan that is not staged never reads it."""
    from srtb_tpu.config import Config
    from srtb_tpu.pipeline.segment import SegmentProcessor

    cfg = Config(baseband_input_count=1 << 14, baseband_input_bits=2,
                 baseband_format_type="simple", baseband_freq_low=1405.0,
                 baseband_bandwidth=64.0, baseband_sample_rate=128e6,
                 dm=30.0, spectrum_channel_count=1 << 5,
                 baseband_reserve_sample=False)
    monkeypatch.setenv("SRTB_STAGED_ROWS_IMPL", impl)
    with pytest.raises(ValueError, match="'xla' or 'pallas'"):
        SegmentProcessor(cfg, staged=True)
    assert not SegmentProcessor(cfg, staged=False).staged


# ------------------------------------------- the served loop's reader
# pulls one segment ahead, on a thread of its own, where the pull holds
# the device (pipeline/runtime.py, `_run_engine`)


class _BytesSink:
    def __init__(self):
        self.seen = []

    def push(self, work, positive):
        det = work.detect
        self.seen.append((work.segment.timestamp,
                          np.array(work.segment.data, copy=True),
                          np.asarray(det.time_series).copy(),
                          bool(positive)))


def _ahead_cfg(tmp_path, tag, segments=8, **extra):
    n = 1 << 12
    path = str(tmp_path / "ahead.bin")
    if not os.path.exists(path):
        np.random.default_rng(7).integers(
            0, 256, size=segments * n, dtype=np.uint8).tofile(path)
    # (128 MSa/s: a stream the file reader copies into pooled blocks,
    # whose counts the cases below hold; a view case passes 1e9)
    extra.setdefault("baseband_sample_rate", 128e6)
    return Config(
        baseband_input_count=n, baseband_input_bits=8,
        input_file_path=path,
        baseband_output_file_prefix=str(tmp_path / f"{tag}_"),
        spectrum_channel_count=1 << 4,
        signal_detect_max_boxcar_length=8,
        signal_detect_signal_noise_threshold=99.0,  # never trigger
        baseband_reserve_sample=False, writer_thread_count=0,
        deterministic_timestamps=True,
        telemetry_journal_path=str(tmp_path / f"{tag}.jsonl"), **extra)


def _no_reader_thread():
    import threading

    return not [t for t in threading.enumerate() if t.name == "reader"]


def _journal(cfg):
    from srtb_tpu.tools import telemetry_report as TR

    return TR.load(cfg.telemetry_journal_path)


def test_reader_ahead_matches_the_loop_that_pulls_by_itself(
        tmp_path, monkeypatch):
    """A source slower than the device: the reader engages, and the
    segments, their order, the journal and the sinks' bytes are those
    of the loop that pulls every segment itself."""
    from srtb_tpu.pipeline import runtime
    from srtb_tpu.utils import telemetry
    from srtb_tpu.utils.metrics import metrics

    out = {}
    # both legs by construction, not by this machine's load: a share of
    # inf never engages the reader, a share of 0 engages it at the
    # first period the loop can read
    for tag, share in (("self", float("inf")), ("ahead", 0.0)):
        metrics.reset()
        monkeypatch.setattr(runtime, "_PULL_AHEAD_SHARE", share)
        cfg = _ahead_cfg(tmp_path, tag)
        source, sink = SlowFile(cfg), _BytesSink()
        with Pipeline(cfg, source=source, sinks=[sink]) as pipe:
            stats = pipe.run()
        assert stats.segments == 8 and _no_reader_thread()
        out[tag] = (source, sink, _journal(cfg))
    metrics.reset()
    (src_a, sink_a, recs_a), (src_b, sink_b, recs_b) = \
        out["self"], out["ahead"]
    assert set(src_a.threads) == {"MainThread"}
    # the first takes of a run fill the window and say nothing of the
    # period: the run starts as the loop that pulls by itself
    assert src_b.threads[:3] == ["MainThread"] * 3
    assert set(src_b.threads[3:]) == {"reader"}
    assert [r["ingest_ahead"] for r in recs_a] == [0] * 8
    # cumulative when the record is written, as every counter there
    ahead = [r["ingest_ahead"] for r in recs_b]
    assert ahead == sorted(ahead) and ahead[0] == 0 and ahead[-1] == 5
    for key in ("segment", "timestamp_ns", "samples", "detections"):
        assert [r[key] for r in recs_a] == [r[key] for r in recs_b]
    assert len(sink_a.seen) == len(sink_b.seen) == 8
    for (ts_a, data_a, series_a, pos_a), (ts_b, data_b, series_b, pos_b) \
            in zip(sink_a.seen, sink_b.seen):
        assert ts_a == ts_b and pos_a == pos_b
        np.testing.assert_array_equal(data_a, data_b)
        np.testing.assert_array_equal(series_a, series_b)
    for rec in recs_a + recs_b:
        ms = rec["stages_ms"]
        # the pull's span holds the stub's sleep, which never returns
        # early, wherever the pull ran (no limit on this machine's
        # clock: what a loaded host adds has no bound, and `< ingest +
        # 5` on the wait failed beside five busy workers, PR 50)
        assert ms["ingest_wait"] >= 0
        assert ms["ingest"] >= 1e3 * src_a.pull_s - 1e-3
        # the wait is the tail of the segment's own pull: the pull is
        # counted once
        assert telemetry.segment_wall(ms) == pytest.approx(
            ms["ingest"] + ms["dispatch"] + ms["fetch"] + ms["sink"])
    assert all(r["stages_ms"]["ingest_wait"] == 0 for r in recs_a)
    # a wait is journalled where the reader made the pull and nowhere
    # else: the three the loop pulled by itself waited for nobody
    assert all(r["stages_ms"]["ingest_wait"] == 0 for r in recs_b[:3])


def test_max_segments_bounds_the_pulls_of_a_reader_ahead(tmp_path,
                                                         monkeypatch):
    from srtb_tpu.pipeline import runtime
    from srtb_tpu.utils.bufferpool import BufferPool
    from srtb_tpu.utils.metrics import metrics

    # engaged at the first period the loop reads, by construction (the
    # count below is of pulls 3, 4, 5), not by this machine's load
    monkeypatch.setattr(runtime, "_PULL_AHEAD_SHARE", 0.0)
    metrics.reset()
    cfg = _ahead_cfg(tmp_path, "bound")
    pool = BufferPool("test_ahead")
    source, sink = SlowFile(cfg, pool=pool), _BytesSink()
    with Pipeline(cfg, source=source, sinks=[sink]) as pipe:
        stats = pipe.run(max_segments=6)
    assert stats.segments == 6 == len(sink.seen)
    assert metrics.get("ingest_ahead") == 3
    # exactly six pulls: the file stands behind the sixth segment and
    # every block is back in the pool
    assert source.pulled == 6
    assert source.logical_offset == 6 * cfg.baseband_input_count
    assert source.reader._file.tell() == 6 * cfg.baseband_input_count
    assert pool.stats()["in_use"] == 0
    # one ahead, not two: the window's two blocks and the reader's
    assert pool.stats()["new_blocks"] <= 3
    metrics.reset()


@pytest.mark.parametrize("sanitize", [False, True])
def test_two_runs_on_one_pipeline_leave_no_reader_thread(tmp_path,
                                                         sanitize,
                                                         monkeypatch):
    """The benchmark's shape: warm-up and window are two ``run()`` calls
    on one ``Pipeline``.  The reader ends with each run (the sanitizer's
    leaked-thread check is armed in the second case), and each run
    starts as the loop that pulls by itself."""
    from srtb_tpu.pipeline import runtime
    from srtb_tpu.utils.metrics import metrics

    # engaged by construction, as in the case above: beside five busy
    # workers this CPU's step outlasts twice the 50 ms pull
    monkeypatch.setattr(runtime, "_PULL_AHEAD_SHARE", 0.0)
    metrics.reset()
    cfg = _ahead_cfg(tmp_path, f"twice{int(sanitize)}", segments=20,
                     sanitize=sanitize)
    source, sink = SlowFile(cfg), _BytesSink()
    with Pipeline(cfg, source=source, sinks=[sink]) as pipe:
        # (the sanitizer's first checks compile inside the first period
        # the loop reads: the median needs a few more)
        assert pipe.run(max_segments=10).segments == 10
        first = metrics.get("ingest_ahead")
        assert _no_reader_thread() and first > 0
        assert pipe.run(max_segments=6).segments == 16
        assert _no_reader_thread()
        assert metrics.get("ingest_ahead") == first + 3
    for run in (source.threads[:10], source.threads[10:]):
        assert run[:3] == ["MainThread"] * 3 and run[-1] == "reader"
    assert source.threads[10:] == ["MainThread"] * 3 + ["reader"] * 3
    assert [s[0] for s in sink.seen] == sorted(s[0] for s in sink.seen)
    assert len(sink.seen) == 16
    metrics.reset()


def test_a_pull_that_raises_on_the_reader_raises_out_of_run(tmp_path,
                                                           monkeypatch):
    from srtb_tpu.pipeline import runtime

    monkeypatch.setattr(runtime, "_PULL_AHEAD_SHARE", 0.0)
    cfg = _ahead_cfg(tmp_path, "raises")
    source, sink = SlowFile(cfg, raise_at=5), _BytesSink()
    with Pipeline(cfg, source=source, sinks=[sink]) as pipe:
        with pytest.raises(RuntimeError, match="disk gone"):
            pipe.run()
    assert source.threads[-1] == "reader" and _no_reader_thread()
    assert source.pulled == 5 and len(sink.seen) <= 5


def test_mapped_segments_are_the_copied_ones_through_the_loop(
        tmp_path, monkeypatch):
    """One file through the served loop with the ring on, its segments
    pooled blocks in one run and read-only views of the file's mapping
    in the other: the same detections, journal and sinks' bytes; the
    views take no block out of the pool, giving them back warns of
    nothing, and the journal counts them."""
    import io

    from srtb_tpu.io import file_input
    from srtb_tpu.utils.bufferpool import BufferPool
    from srtb_tpu.utils.logging import log
    from srtb_tpu.utils.metrics import metrics

    n, out = 1 << 16, {}
    for tag, floor in (("copy", float("inf")), ("view", 0.0)):
        metrics.reset()
        monkeypatch.setattr(file_input, "_VIEW_MIN_BYTES_PER_SKY_SECOND",
                            floor)
        cfg = _ahead_cfg(tmp_path, tag, segments=5 * 16).replace(
            baseband_input_count=n, baseband_freq_low=1405.0,
            baseband_bandwidth=64.0, dm=0.1, baseband_reserve_sample=True)
        pool, sink = BufferPool(tag), _BytesSink()
        source = file_input.make_file_source(cfg, buffer_pool=pool)
        stream, log.stream = log.stream, io.StringIO()
        try:
            with Pipeline(cfg, source=source, sinks=[sink]) as pipe:
                stats = pipe.run()
            said = log.stream.getvalue()
        finally:
            log.stream = stream
        assert "already-freed" not in said
        assert pool.stats()["in_use"] == 0
        out[tag] = (stats.segments, sink.seen, _journal(cfg), pool.stats())
    metrics.reset()
    (n_a, seen_a, recs_a, pool_a), (n_b, seen_b, recs_b, pool_b) = \
        out["copy"], out["view"]
    assert n_a == n_b == len(seen_a) == len(seen_b) >= 5
    for (ts_a, data_a, series_a, pos_a), (ts_b, data_b, series_b, pos_b) \
            in zip(seen_a, seen_b):
        assert ts_a == ts_b and pos_a == pos_b
        np.testing.assert_array_equal(data_a, data_b)
        # (the same bytes through the same programs; beside busy
        # workers the CPU backend splits a sum of 2^16 samples another
        # way from run to run, a few float32 steps)
        np.testing.assert_allclose(series_a, series_b, rtol=0,
                                   atol=1e-5 * np.abs(series_a).max())
    for key in ("segment", "timestamp_ns", "samples", "detections"):
        assert [r[key] for r in recs_a] == [r[key] for r in recs_b], key
    # (cumulative when a record is written, which is a dispatch or two
    # after the segment's own: the last record has them all)
    for key in ("h2d_bytes", "ring_carry_bytes", "ring_cold_dispatches"):
        assert recs_a[-1][key] == recs_b[-1][key] > 0, key
    assert [r["ingest_mapped"] for r in recs_a] == [0] * n_a
    # every whole segment a view; the short last one a pooled block
    assert recs_b[-1]["ingest_mapped"] == n_b - 1
    assert pool_a["acquires"] == n_a and pool_b["acquires"] == 1


def test_a_source_faster_than_the_device_never_engages_the_reader(
        tmp_path):
    """A pull of microseconds beside a device step of milliseconds (the
    cells the chip paces): no reader, no thread, ``ingest_ahead`` 0."""
    from srtb_tpu.utils.metrics import metrics

    metrics.reset()
    cfg = _ahead_cfg(tmp_path, "fast")
    source, sink = SlowFile(cfg, pull_s=0.0), _BytesSink()
    with Pipeline(cfg, source=source, sinks=[sink],
                  processor=SlowDevice(SegmentProcessor(cfg),
                                       0.02)) as pipe:
        assert pipe.run().segments == 8
    assert set(source.threads) == {"MainThread"}
    recs = _journal(cfg)
    assert [r["ingest_ahead"] for r in recs] == [0] * 8
    assert all(r["stages_ms"]["ingest_wait"] == 0 for r in recs)
    metrics.reset()


def test_threaded_pipeline_checkpoints_each_segments_own_offset(tmp_path):
    """``ThreadedPipeline``'s source thread runs ahead of its device
    thread: the offset a checkpoint records after segment k is the one
    the source stood at after segment k, carried with the work item."""

    from srtb_tpu.pipeline.runtime import ThreadedPipeline

    cfg = _ahead_cfg(tmp_path, "threaded",
                     checkpoint_path=str(tmp_path / "threaded.json"))
    pipe = ThreadedPipeline(
        cfg, sinks=[_BytesSink()],
        processor=SlowDevice(SegmentProcessor(cfg), 0.03))
    seen = []
    update = pipe.checkpoint.update
    pipe.checkpoint.update = lambda done, offset: (
        seen.append((done, offset)), update(done, offset))
    with pipe:
        assert pipe.run().segments == 8
    n = cfg.baseband_input_count
    assert seen == [(k + 1, (k + 1) * n) for k in range(8)]
