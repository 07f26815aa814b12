"""The device generator's bytes equal ``io/synth.quantize`` +
``pack_subbyte`` on the same floats; every sample format the benchmark
knows is unpacked alike by the program and by the reference; the files of
the cells the benchmark had before the formats are byte for byte what
they were."""

import hashlib
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")

OPTIONS = {"baseband_input_count": "2 ** 14", "baseband_input_bits": 2,
           "baseband_format_type": "simple",
           "baseband_freq_low": 1437, "baseband_bandwidth": -64,
           "baseband_sample_rate": 128e6, "dm": -0.05,
           "spectrum_channel_count": 16,
           "mitigate_rfi_average_method_threshold": 10,
           "mitigate_rfi_spectral_kurtosis_threshold": 1.4,
           "signal_detect_signal_noise_threshold": 8,
           "signal_detect_max_boxcar_length": 16,
           "baseband_reserve_sample": 1}
WORKLOAD = {"warmup": {"segments": ["pulse", "quiet"]},
            "source": {"file_segments": 6},
            "pulses": {"every": 4, "dm": -0.05, "amp": 10.0, "width": 8,
                       "template_log2": 12}}
# (bits, format): what the cells have, what the queued deployments need
FORMATS = [(2, "simple"), (8, "simple"), (-8, "simple"),
           (-8, "naocpsr_snap1"), (2, "interleaved_samples_2"),
           (8, "interleaved_samples_2")]


def make_file(tmp_path, bits, fmt, seed=5, name="f.bin"):
    from benchmark import gen
    from benchmark.reference import chain

    p = chain.params_from_config(dict(
        OPTIONS, baseband_input_bits=bits, baseband_format_type=fmt))
    lay = gen.Layout(p, WORKLOAD, seed)
    path = str(tmp_path / name)
    info = gen.write_file(path, p, lay, seed)
    assert info["bytes"] == lay.bytes_of(lay.total) \
        == os.path.getsize(path)
    return p, lay, np.fromfile(path, dtype=np.uint8)


@pytest.mark.parametrize("bits", [2, 8])
def test_bytes_equal_io_synth_on_the_same_floats(bits):
    import jax

    from benchmark import gen
    from srtb_tpu.io import synth

    n = 1 << 16
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


@pytest.mark.parametrize("bits, fmt", [(2, "simple"),
                                       (8, "interleaved_samples_2")])
def test_same_seed_same_file_and_large_seeds(tmp_path, bits, fmt):
    out = [make_file(tmp_path, bits, fmt, seed, f"f{i}.bin")[2].tobytes()
           for i, seed in enumerate((5, 5, 2 ** 31 + 12345, 2 ** 33 + 5))]
    assert out[0] == out[1]
    assert out[0] != out[2] and out[0] != out[3] and out[2] != out[3]


@pytest.mark.parametrize("bits, fmt", FORMATS)
def test_program_and_reference_unpack_the_file_alike(tmp_path, bits, fmt):
    """The program's ``unpack_streams`` on one segment of the generated
    file equals the reference's de-interleave + ``unpack``, sample for
    sample, and a segment is as many bytes as the program's reader
    takes."""
    import jax.numpy as jnp

    from benchmark.reference import chain
    from srtb_tpu.config import Config
    from srtb_tpu.io import formats
    from srtb_tpu.pipeline.segment import unpack_streams

    p, lay, data = make_file(tmp_path, bits, fmt)
    cfg = Config.from_args([f"--{k}={v}" for k, v in dict(
        OPTIONS, baseband_input_bits=bits,
        baseband_format_type=fmt).items()])
    f = formats.resolve(cfg.baseband_format_type)
    assert lay.streams == f.data_stream_count
    assert lay.segment_bytes == chain.segment_bytes(p) \
        == cfg.segment_bytes(f.data_stream_count)
    k = 2                                   # a pulsed replay segment
    raw = data[k * lay.stride_bytes:k * lay.stride_bytes
               + lay.segment_bytes]
    got = np.asarray(unpack_streams(jnp.asarray(raw), f.unpack_variant,
                                    bits, None))
    want = np.stack([chain.unpack(b, bits)
                     for b in chain.deinterleave(raw, p)])
    assert got.shape == want.shape == (lay.streams, lay.n)
    assert np.array_equal(got.astype(np.float64), want)
    # the digitizer fills its range and is centred
    mid = 0.0 if bits < 0 else (1 << bits) / 2
    assert abs(want.mean() - mid) < 0.05 * (1 << abs(bits))
    assert want.max() - want.min() == (1 << abs(bits)) - 1


@pytest.mark.parametrize("bits, fmt", [(8, "interleaved_samples_2"),
                                       (-8, "naocpsr_snap1")])
def test_streams_are_independent_and_stream_0_is_the_one_stream_file(
        tmp_path, bits, fmt):
    from benchmark.reference import chain

    p, _lay, data = make_file(tmp_path, bits, fmt)
    _p1, _l1, single = make_file(tmp_path, bits, "simple", name="one.bin")
    s0, s1 = chain.deinterleave(data, p)
    assert np.array_equal(s0, single)
    assert s0.shape == s1.shape and np.mean(s0 != s1) > 0.9


def test_signed_8_bit_is_the_unsigned_level_in_twos_complement(tmp_path):
    from benchmark.reference import chain

    _p, _lay, u = make_file(tmp_path, 8, "simple", name="u.bin")
    _p, _lay, s = make_file(tmp_path, -8, "simple", name="s.bin")
    assert np.array_equal(chain.unpack(s, -8), chain.unpack(u, 8) - 128)


def test_unknown_formats_are_refused():
    from benchmark.reference import chain

    for change in ({"baseband_input_bits": 16},
                   {"baseband_format_type": "gznupsr_a1"},
                   {"baseband_input_bits": 2,
                    "baseband_format_type": "naocpsr_snap1"}):
        with pytest.raises(ValueError):
            chain.params_from_config(dict(OPTIONS, **change))


# sha256 of ``baseband.bin`` of the two tiny cells the benchmark had before
# it took the formats, taken on the parent tree (PR 28, 9a49de2) on the CPU
PARENT_FILES = {
    ("tiny_j1644.replay_quiet", 11): (
        907648,
        "8b135d477f76baaa00f96f3b6f965ceadbff962e423f6a16ad7163ed12ad4433"),
    ("tiny_j1644.replay_quiet", 2147483659): (
        907648,
        "dfe277a969e7f0849059be32f0084343ceeb434affa2f278626174b1488303be"),
    ("tiny_dmgrid8.replay", 11): (
        557056,
        "01d490a206a2ccc93e622bdb597bd9b16526c08309da622c6029fe063dc0212f"),
    ("tiny_dmgrid8.replay", 2147483659): (
        557056,
        "f0c59910d59f64490a9a1ef7b2fbc612409813ca3bf6786e7ffc403b30e70863"),
}


@pytest.mark.parametrize("cell, seed", sorted(PARENT_FILES))
def test_the_old_cells_files_are_byte_for_byte_the_parents(tmp_path, cell,
                                                           seed):
    from benchmark import gen, spec as spec_mod
    from benchmark.reference import chain

    sp = spec_mod.Spec(TINY, cell)
    p = chain.params_from_config(sp.config["options"])
    lay = gen.Layout(p, sp.workload, seed)
    path = str(tmp_path / "baseband.bin")
    info = gen.write_file(path, p, lay, seed)
    with open(path, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    assert (info["bytes"], digest) == PARENT_FILES[cell, seed]
