"""The device generator's bytes equal ``io/synth.quantize`` +
``pack_subbyte`` on the same floats."""

import numpy as np


def test_bytes_equal_io_synth_on_the_same_floats():
    import jax

    from benchmark import gen
    from srtb_tpu.io import synth

    n, bits = 1 << 16, 2
    per_byte = 8 // bits
    sig = np.asarray(jax.random.normal(jax.random.key(1),
                                       (per_byte, n // per_byte)))
    # io/synth normalizes by the sample deviation; give the device
    # quantizer that deviation as its (otherwise fixed) gain
    flat = np.ascontiguousarray(sig.T).reshape(-1)   # sample order
    sigma = flat.std()
    levels = 1 << bits
    gain = np.float32((levels / 2 - 0.5) / 3.0) / sigma
    got = np.asarray(gen.quantize_pack(jax.numpy.asarray(sig), bits,
                                       gain, levels / 2))
    want = synth.quantize(flat, bits)
    assert got.dtype == np.uint8 and got.shape == want.shape
    # a float32 product rounded in another order may move a sample that
    # sits within an ulp of a quantizer step: none is expected at 2^16
    assert int(np.sum(got != want)) <= 1


def test_same_seed_same_file_and_large_seeds(tmp_path):
    from benchmark import gen
    from benchmark.reference import chain

    opts = {"baseband_input_count": "2 ** 14", "baseband_input_bits": 2,
            "baseband_freq_low": 1437, "baseband_bandwidth": -64,
            "baseband_sample_rate": 128e6, "dm": -0.05,
            "spectrum_channel_count": 16,
            "mitigate_rfi_average_method_threshold": 10,
            "mitigate_rfi_spectral_kurtosis_threshold": 1.4,
            "signal_detect_signal_noise_threshold": 8,
            "signal_detect_max_boxcar_length": 16,
            "baseband_reserve_sample": 1}
    p = chain.params_from_config(opts)
    wl = {"warmup": {"segments": ["pulse", "quiet"]},
          "source": {"file_segments": 6},
          "pulses": {"every": 4, "dm": -0.05, "amp": 10.0, "width": 8,
                     "template_log2": 12}}
    out = []
    for seed in (5, 5, 2 ** 31 + 12345, 2 ** 33 + 5):
        lay = gen.Layout(p, wl, seed)
        path = str(tmp_path / f"f{len(out)}.bin")
        info = gen.write_file(path, p, lay, seed)
        assert info["bytes"] == lay.bytes_of(lay.total)
        out.append(open(path, "rb").read())
    assert out[0] == out[1]
    assert out[0] != out[2] and out[0] != out[3] and out[2] != out[3]
