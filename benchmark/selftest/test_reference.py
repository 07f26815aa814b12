"""The float64 reference: its fast paths equal NumPy's, it agrees with
the program, and its lower-precision controls do not agree with it.

The controls run at a size a test can hold (2^16) but at the
deployment's DM: the chirp's phase count (k ~ 1e7 turns) is what a
float32 cannot carry, and it does not shrink with the segment.
"""

import numpy as np
import pytest

from benchmark import check
from benchmark.reference import chain

OPTIONS = {
    "baseband_input_count": "2 ** 16", "baseband_input_bits": 2,
    "baseband_format_type": "simple",
    "baseband_freq_low": "1405 + 32", "baseband_bandwidth": -64,
    "baseband_sample_rate": "128e6", "dm": -478.80,
    "spectrum_channel_count": "2 ** 6",
    "mitigate_rfi_average_method_threshold": 1.5,
    "mitigate_rfi_spectral_kurtosis_threshold": 1.4,
    "signal_detect_signal_noise_threshold": 8,
    "signal_detect_max_boxcar_length": 16,
    "mitigate_rfi_freq_list": "1418-1422",
    "baseband_reserve_sample": 0,
}
# sound runs of the program read ~4e-6 here; the controls read > 1
LIMIT = 1e-3


@pytest.fixture(scope="module")
def raw():
    return np.random.default_rng(7).integers(
        0, 256, size=(1 << 16) // 4, dtype=np.uint8)


def test_four_step_r2c_equals_numpy():
    x = np.random.default_rng(1).standard_normal(1 << 18)
    a = chain.rfft_drop_nyquist(x, workers=3, four_step_min=1 << 10)
    b = np.fft.rfft(x)[:-1]
    assert np.abs(a - b).max() <= 1e-12 * np.abs(b).max()


def test_unpack_is_msb_first():
    got = chain.unpack(np.array([0b11000001], dtype=np.uint8), 2)
    assert got.tolist() == [3.0, 0.0, 0.0, 1.0]


@pytest.mark.parametrize("bits, want", [(8, [0.0, 127.0, 128.0, 255.0]),
                                        (-8, [0.0, 127.0, -128.0, -1.0])])
def test_unpack_8_bit_unsigned_and_twos_complement(bits, want):
    raw = np.array([0, 127, 128, 255], dtype=np.uint8)
    assert chain.unpack(raw, bits).tolist() == want


@pytest.mark.parametrize("fmt, want", [
    ("simple", [[0, 1, 2, 3, 4, 5, 6, 7]]),
    ("interleaved_samples_2", [[0, 2, 4, 6], [1, 3, 5, 7]]),     # "1212"
    ("naocpsr_snap1", [[0, 1, 4, 5], [2, 3, 6, 7]]),             # "1122"
])
def test_deinterleave_by_the_formats_table(fmt, want):
    p = chain.params_from_config(dict(OPTIONS, baseband_input_bits=8,
                                      baseband_format_type=fmt))
    got = chain.deinterleave(np.arange(8, dtype=np.uint8), p)
    assert [g.tolist() for g in got] == want


@pytest.mark.parametrize("fmt", ["simple", "interleaved_samples_2"])
def test_child_runs_the_chain_on_every_stream(tmp_path, fmt):
    """Stream 0 keeps the keys a one-stream file always had
    (``s<k>.t<i>.*``); stream s > 0 writes ``s<k>.p<s>.t<i>.*``."""
    from benchmark.reference import child

    opts = dict(OPTIONS, baseband_input_count="2 ** 12",
                baseband_format_type=fmt, spectrum_channel_count=16)
    p = chain.params_from_config(opts)
    data = np.random.default_rng(3).integers(
        0, 256, size=2 * chain.segment_bytes(p), dtype=np.uint8)
    path = str(tmp_path / "two_segments.bin")
    data.tofile(path)
    out = child.compute({"file": path, "params": p, "segments": [
        {"file_seg": 1, "offset_bytes": chain.segment_bytes(p),
         "dms": [p["dm"]]}]})
    streams = chain.deinterleave(data[chain.segment_bytes(p):], p)
    assert len(streams) == p["streams"]
    for s, raw in enumerate(streams):
        want = chain.segment(raw, p, dms=[p["dm"]])[0]
        tag = "s1" if s == 0 else f"s1.p{s}"
        assert np.array_equal(out[f"{tag}.t0.series"], want["time_series"])
        assert out[f"{tag}.t0.snr_peaks"].tolist() == want["snr_peaks"]
    assert ("s1.p1.t0.series" in out) == (p["streams"] == 2)
    assert float(out["s1.seconds"]) > 0


def test_reference_agrees_with_the_program(raw):
    from srtb_tpu.config import Config
    from srtb_tpu.pipeline.segment import SegmentProcessor

    cfg = Config.from_args([f"--{k}={v}" for k, v in OPTIONS.items()])
    _, res = SegmentProcessor(cfg).process(raw)
    p = chain.params_from_config(OPTIONS)
    want = chain.segment(raw, p, workers=2)[0]
    got = np.asarray(res.time_series)[0]
    assert check.series_gap(got, want["time_series"]) < LIMIT
    assert int(np.asarray(res.zero_count)[0]) == want["zero_count"]


@pytest.mark.parametrize("low", ["chirp_f32", "bf16"])
def test_lower_precision_control_fails(raw, low):
    p = chain.params_from_config(OPTIONS)
    want = chain.segment(raw, p)[0]["time_series"]
    got = chain.segment(raw, p, low=low)[0]["time_series"]
    assert check.series_gap(got, want) > 3 * LIMIT
