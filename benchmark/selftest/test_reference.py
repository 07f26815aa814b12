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
