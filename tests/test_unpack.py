"""Unpack tests.

Oracle style follows the reference's test-unpack.cpp: hand-computed bit
patterns for sub-byte widths (test-unpack.cpp:63-139) plus random-data
self-consistency against an independent numpy model (test-unpack.cpp:236-253).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from srtb_tpu.ops import unpack as U


def test_unpack_1bit_pattern():
    # 0b10110001 -> 1,0,1,1,0,0,0,1 (MSB first, ref: unpack.hpp:91-98)
    data = jnp.asarray(np.array([0b10110001], dtype=np.uint8))
    out = np.asarray(U.unpack(data, 1))
    np.testing.assert_array_equal(out, [1, 0, 1, 1, 0, 0, 0, 1])


def test_unpack_2bit_pattern():
    # 0b10110001 -> 0b10, 0b11, 0b00, 0b01 (ref: unpack.hpp:116-119)
    data = jnp.asarray(np.array([0b10110001], dtype=np.uint8))
    out = np.asarray(U.unpack(data, 2))
    np.testing.assert_array_equal(out, [2, 3, 0, 1])


def test_unpack_4bit_pattern():
    data = jnp.asarray(np.array([0xA7, 0x3C], dtype=np.uint8))
    out = np.asarray(U.unpack(data, 4))
    np.testing.assert_array_equal(out, [0xA, 0x7, 0x3, 0xC])


@pytest.mark.parametrize("nbits", [1, 2, 4, 8, -8, 16, -16, 32])
def test_unpack_random_vs_oracle(nbits):
    rng = np.random.default_rng(42)
    data = rng.integers(0, 256, size=1 << 12, dtype=np.uint8)
    expected = U.unpack_oracle(data, nbits)
    got = np.asarray(U.unpack(jnp.asarray(data), nbits))
    np.testing.assert_array_equal(got, expected)


def test_unpack_window_fusion():
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=256, dtype=np.uint8)
    window = rng.random(256 * 4).astype(np.float32)
    expected = U.unpack_oracle(data, 2) * window
    got = np.asarray(U.unpack(jnp.asarray(data), 2, jnp.asarray(window)))
    np.testing.assert_allclose(got, expected, rtol=1e-6)


def test_unpack_interleaved_2pol():
    # "1212" layout (ref: unpack.hpp:214-244)
    data = np.array([1, 101, 2, 102, 3, 103, 4, 104], dtype=np.uint8)
    out1, out2 = U.unpack_interleaved_2pol(jnp.asarray(data), 8)
    np.testing.assert_array_equal(np.asarray(out1), [1, 2, 3, 4])
    np.testing.assert_array_equal(np.asarray(out2), [101, 102, 103, 104])


def test_unpack_naocpsr_snap1():
    # "1122" layout (ref: unpack.hpp:253-283)
    data = np.array([1, 2, 101, 102, 3, 4, 103, 104], dtype=np.uint8)
    out1, out2 = U.unpack_naocpsr_snap1(jnp.asarray(data), 8)
    np.testing.assert_array_equal(np.asarray(out1), [1, 2, 3, 4])
    np.testing.assert_array_equal(np.asarray(out2), [101, 102, 103, 104])


def test_unpack_gznupsr_a1():
    # 4-way word interleave with XOR 0x80 (ref: unpack.hpp:291-328)
    word = np.arange(16, dtype=np.uint8)  # streams of 4 words each
    data = np.concatenate([word, word + 16])
    outs = U.unpack_gznupsr_a1(jnp.asarray(data))
    assert len(outs) == 4
    for i, out in enumerate(outs):
        expected_bytes = np.concatenate([
            (word[4 * i:4 * i + 4] ^ 0x80).view(np.int8),
            ((word + 16)[4 * i:4 * i + 4] ^ 0x80).view(np.int8)])
        np.testing.assert_array_equal(np.asarray(out),
                                      expected_bytes.astype(np.float32))


def test_unpack_gznupsr_a1_v2_1():
    # 2-way word interleave, signed (ref: unpack.hpp:336-369)
    data = np.arange(16, dtype=np.uint8)
    out1, out2 = U.unpack_gznupsr_a1_v2_1(jnp.asarray(data))
    np.testing.assert_array_equal(np.asarray(out1),
                                  [0, 1, 2, 3, 8, 9, 10, 11])
    np.testing.assert_array_equal(np.asarray(out2),
                                  [4, 5, 6, 7, 12, 13, 14, 15])


def test_unpack_float64_bit_decode_without_x64():
    """64-bit float ingest (ref: config.hpp:92-97 allows 32/64-bit
    floating input) decoded from the raw bit pattern: without x64,
    jnp's .view(float64) silently truncates to a float32 view (doubling
    the sample count and corrupting every value — the round-3 stress
    sweep caught exactly that), so the double is reassembled from its
    uint32 halves with an exact bitcast power of two."""
    rng = np.random.default_rng(2)
    with np.errstate(over="ignore"):
        vals = np.concatenate([
            rng.standard_normal(512) * 10 ** rng.uniform(-38, 38, 512),
            [0.0, -0.0, 1.0, -1.0, np.inf, -np.inf, np.nan,
             np.finfo(np.float64).max, np.finfo(np.float64).tiny],
        ]).astype(np.float64)
        want = vals.astype(np.float32)
    raw = jnp.asarray(np.frombuffer(vals.tobytes(), dtype=np.uint8))
    got = np.asarray(U.unpack(raw, 64))
    assert got.shape == want.shape
    for i in range(vals.size):
        w, g = want[i], got[i]
        if (w == g) or (np.isnan(w) and np.isnan(g)):
            continue
        if np.isfinite(w) and np.isfinite(g) \
                and abs(g - w) <= abs(np.spacing(w)):
            continue  # 1-ulp rounding-mode difference
        if g == 0.0 and abs(float(vals[i])) < 2.0 ** -126:
            continue  # f32-subnormal doubles flush to 0 (documented)
        raise AssertionError((i, vals[i], w, g))


# ------------------------------------------------------------------
# the plane forms the plans really call: sub-byte samples as blocked
# field planes (`unpack_subbyte_planes`, with `subbyte_window_planes`),
# whole bytes dealt out every count-th sample (`ops/fft.deal_planes`,
# with `window_planes`).  The width table of the Pallas unpack kernels
# (gone in PR 50: no chip compiled them), on what a chip runs.

@pytest.mark.parametrize("windowed", [False, True])
@pytest.mark.parametrize("nbits", [1, 2, 4])
def test_subbyte_planes_hold_field_k_of_every_byte(nbits, windowed):
    from srtb_tpu.ops import fft as F

    count = 8 // nbits
    m = 1 << 10
    data = np.random.default_rng(30 + nbits).integers(
        0, 256, size=m, dtype=np.uint8)
    planes = U.unpack_subbyte_planes(jnp.asarray(data), nbits)
    assert planes.shape == (count, m) and planes.dtype == jnp.float32
    samples = U.unpack_oracle(data, nbits)             # sample order
    want = samples.reshape(m, count).T                 # [k, b]
    if windowed:
        window = np.hamming(count * m).astype(np.float32)
        w_planes = F.subbyte_window_planes(window, nbits)
        assert w_planes.shape == (count, m) and w_planes.flags.c_contiguous
        planes = planes * jnp.asarray(w_planes)
        want = (samples * window).reshape(m, count).T
        np.testing.assert_allclose(np.asarray(planes), want, rtol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(planes), want)


def test_subbyte_planes_keep_a_leading_axis():
    data = np.random.default_rng(34).integers(
        0, 256, size=(3, 256), dtype=np.uint8)
    planes = np.asarray(U.unpack_subbyte_planes(jnp.asarray(data), 2))
    assert planes.shape == (3, 4, 256)
    for s in range(3):
        np.testing.assert_array_equal(
            planes[s], U.unpack_oracle(data[s], 2).reshape(256, 4).T)


@pytest.mark.parametrize("count,lead,dtype", [
    (2, (), np.uint8),          # the even/odd pack's two parts, as bytes
    (4, (), np.uint8),          # two plane pairs (2^28 one-byte samples)
    (2, (3,), np.float32),      # floats, a leading axis
    (4, (2,), np.float32),
])
def test_deal_planes_hands_out_every_count_th_sample(count, lead, dtype):
    from srtb_tpu.ops import fft as F

    n = 8 * count * 128
    x = np.random.default_rng(40 + count).integers(
        0, 256, size=(*lead, n)).astype(dtype)
    planes = F.deal_planes(jnp.asarray(x), count)
    assert len(planes) == count
    for j, plane in enumerate(planes):
        assert plane.shape == (*lead, n // count) and plane.dtype == dtype
        np.testing.assert_array_equal(np.asarray(plane), x[..., j::count])
    # the window dealt out the same way multiplies the same samples
    window = np.hamming(n).astype(np.float32)
    w_planes = F.window_planes(window, count)
    for j in range(count):
        np.testing.assert_array_equal(w_planes[j], window[j::count])


def test_deal_planes_refuses_what_fills_no_whole_rows():
    from srtb_tpu.ops import fft as F

    with pytest.raises(ValueError, match="rows of 512"):
        F.deal_planes(jnp.zeros(4 * 128 + 4, jnp.uint8), 4)
