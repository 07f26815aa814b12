"""The staged plan's overlap-save ring, assembled by strips (ISSUE 44).

The deployment is ``benchmark/configs/naoc_crab_2p30.json``: the upstream
defaults at the Crab's DM, 8-bit samples at 1 GSa/s, 2^30-sample
segments, 2^15 channels, 24.38 % of every segment overlapped.  There the
program picks ``staged:four_step+rows+ring`` by itself, and stage (a)
takes the carry and the new bytes as whole rows of its ``[T, bytes a
row]`` view (3994 and 12390 rows of 65536 bytes): a block of boundary
rows reads its strip from each and the next carry is the new bytes' last
rows, so no ``u8[segment_bytes]`` join and no relayout of a whole
segment's bytes is made.  Here, at sizes the CPU holds (``SHAPES``: the
segment and the channels cut, the DM scaled so that the overlap stays a
quarter and is NOT a whole number of blocks), with
``segment.STAGED_MIN_N`` and ``FUSED_TAIL_DF64_MAX_SPECTRUM`` patched
down as ``tests/test_staged_rows.py`` patches them (no option chooses
the plan):

(i)  the strips against the whole-plane staged ring they stand in for
     (``_assemble`` + ``_stage_a`` + ``_next_carry``, the parent's
     spelling, which a reserve of no whole rows still takes): boundary
     and carry bit for bit, warm and cold;
(ii) two overlapped segments through the ring's two entry points against
     ``oracle_utils``'s float64 chain on the same seeded bytes.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from oracle_utils import oracle_stream_chain, oracle_unpack

from srtb_tpu.config import Config
from srtb_tpu.pipeline import segment
from srtb_tpu.pipeline.segment import SegmentProcessor, waterfall_to_numpy

with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "naoc_crab_2p30.json")) as _f:
    FULL = json.load(_f)["options"]

# log2 samples, log2 channels, DM: 8-bit samples, the deployment's band
# and rate; rows of carry / of new bytes / a block's boundary rows
SHAPES = {
    "2p16_c7": (16, 7, 0.0033),        # 60 + 196 rows of 256 B, blocks of 16
    "2p17_c8": (17, 8, 0.0068),        # 62 + 194 rows of 512 B, blocks of 32
    "2p17_c7": (17, 7, 0.0066),        # 120 + 392 rows of 256 B, blocks of 16
}


def _config(shape: str, **extra) -> Config:
    """The deployment's own options with the shape's cuts (and boxcars
    that fit the short series)."""
    log2n, log2c, dm = SHAPES[shape]
    cuts = dict(baseband_input_count=f"2 ** {log2n}",
                spectrum_channel_count=f"2 ** {log2c}", dm=dm,
                signal_detect_max_boxcar_length=16)
    return Config.from_args([f"--{k}={v}" for k, v in
                             dict(FULL, **cuts, **extra).items()])


@pytest.fixture
def staged_here(monkeypatch):
    """What a 2^30 segment meets, at these sizes: the staged plan by the
    size rule, its tail unfused by the bankless rule."""
    monkeypatch.setattr(segment, "STAGED_MIN_N", 1 << 16)
    monkeypatch.setattr(segment, "FUSED_TAIL_DF64_MAX_SPECTRUM", 1 << 10)


def _bytes(proc: SegmentProcessor, segments: int, seed: int) -> np.ndarray:
    """``segments`` overlapped segments of seeded 8-bit noise around
    mid-scale, as one byte stream."""
    total = proc.stride_bytes * segments + proc.reserved_bytes
    x = np.random.default_rng(seed).normal(128.0, 20.0, total)
    return np.clip(np.rint(x), 0, 255).astype(np.uint8)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_plan_takes_the_bytes_as_rows(shape, staged_here):
    proc = SegmentProcessor(_config(shape))
    assert proc.plan_name == "staged:monolithic+rows+ring"
    row = proc.ring_row_bytes
    assert row == 2 * proc.channel_count            # 8-bit samples
    rows_carry, rows_new = (proc.reserved_bytes // row,
                            proc.stride_bytes // row)
    assert rows_carry + rows_new == proc.watfft_len
    assert 0.2 < rows_carry / proc.watfft_len < 3 / 11
    # the reserve is no whole number of blocks, nor of eight rows
    assert rows_carry % proc._stage_a_block_rows() and rows_carry % 8
    avals = {name: tuple(a.shape for a in args)
             for name, _fn, args, _d in proc.lowerables()}
    assert avals["stage_a_ring"] == ((rows_carry, row), (rows_new, row))
    assert avals["stage_a_cold"] == ((proc.watfft_len, row),)
    donated = {name: d for name, _fn, _a, d in proc.lowerables()}
    assert donated["stage_a_ring"] == (0,)          # the carry alone


@pytest.mark.parametrize("how", ["warm", "cold"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_strips_against_the_whole_plane_staged_ring(shape, how, staged_here):
    """Boundary and next carry, bit for bit."""
    proc = SegmentProcessor(_config(shape))
    raw = _bytes(proc, 1, seed=44)
    row, reserved = proc.ring_row_bytes, proc.reserved_bytes

    @jax.jit
    def whole_plane(carry, new):
        joined = proc._assemble(carry, new)
        return proc._stage_a(joined), proc._next_carry(joined)

    want_a, want_carry = whole_plane(jnp.asarray(raw[:reserved]),
                                     jnp.asarray(raw[reserved:]))
    if how == "warm":
        got_a, got_carry = jax.jit(proc._stage_a_ring)(
            jnp.asarray(raw[:reserved].reshape(-1, row)),
            jnp.asarray(raw[reserved:].reshape(-1, row)))
    else:
        got_a, got_carry = jax.jit(proc._stage_a_cold)(
            jnp.asarray(raw.reshape(-1, row)))
    assert got_a.shape == (2, 1, proc.channel_count, proc.watfft_len)
    assert got_carry.shape == (reserved // row, row)
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))
    np.testing.assert_array_equal(np.asarray(got_carry).reshape(-1),
                                  np.asarray(want_carry))
    np.testing.assert_array_equal(np.asarray(want_carry),
                                  raw[proc.stride_bytes:])


def test_several_streams_in_one_byte_stream_keep_the_flat_ring(staged_here):
    """The rows are stage (a)'s blocks': where the plan's stage (a) walks
    none (two streams byte-interleaved) the bytes cross flat and are
    joined in the program, as before."""
    proc = SegmentProcessor(_config(
        "2p16_c7", baseband_format_type="interleaved_samples_2"))
    assert proc.ring and proc.staged and proc.ring_row_bytes == 0
    avals = {name: tuple(a.shape for a in args)
             for name, _fn, args, _d in proc.lowerables()}
    assert avals["stage_a_ring"] == ((proc.reserved_bytes,),
                                     (proc.stride_bytes,))


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_two_ring_segments_against_the_float64_chain(shape, staged_here):
    """A cold and then a warm dispatch through the processor's own entry
    points (``stage_input``, ``run_device_cold``, ``run_device_ring``)
    against the float64 oracle on each assembled segment's bytes."""
    cfg = _config(shape)
    proc = SegmentProcessor(cfg)
    stream = _bytes(proc, 2, seed=7)
    seg_bytes, stride = proc._segment_bytes, proc.stride_bytes
    segs = [stream[k * stride:k * stride + seg_bytes] for k in (0, 1)]
    (wf0, det0), carry = proc.run_device_cold(proc.stage_input(segs[0]))
    assert carry.shape == (proc.reserved_bytes // proc.ring_row_bytes,
                           proc.ring_row_bytes)
    (wf1, det1), carry = proc.run_device_ring(
        carry, proc.stage_input(segs[1], stride_only=True))
    np.testing.assert_array_equal(np.asarray(carry).reshape(-1),
                                  segs[1][stride:])
    for raw, wf_ri, det in ((segs[0], wf0, det0), (segs[1], wf1, det1)):
        wf_o, ts_o, zapped = oracle_stream_chain(oracle_unpack(raw, 8), cfg)
        wf = waterfall_to_numpy(wf_ri)[0]
        assert int(np.asarray(det.zero_count).reshape(-1)[0]) == zapped
        scale = np.abs(wf_o).max()
        np.testing.assert_allclose(wf, wf_o.astype(np.complex64),
                                   atol=2e-4 * scale, rtol=2e-3)
        ts = np.asarray(det.time_series, np.float64)[0]
        assert ts.size == ts_o.size
        np.testing.assert_allclose(ts, ts_o, atol=2e-4 * np.abs(ts_o).max(),
                                   rtol=2e-3)


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_a_warm_dispatch_is_the_cold_one_bit_for_bit(bits, staged_here):
    """The second of two overlapped segments through the ring (the
    carry of the first and its own new bytes, by strips) and as a whole
    upload: the same waterfall, series, counts and next carry, bit for
    bit, at every width the blocks take (a row of the bytes' view is
    ``2 * channels * bits / 8`` bytes; the sub-byte widths had no ring
    case)."""
    cfg = _config("2p17_c7").replace(baseband_input_bits=bits)
    proc = SegmentProcessor(cfg)
    assert proc.plan_name == "staged:monolithic+rows+ring"
    row = proc.ring_row_bytes
    assert row == 2 * proc.channel_count * bits // 8
    assert proc.reserved_bytes % row == 0 < proc.reserved_bytes
    stream = np.random.default_rng(60 + bits).integers(
        0, 256, proc.stride_bytes * 2 + proc.reserved_bytes, dtype=np.uint8)
    seg_bytes, stride = proc._segment_bytes, proc.stride_bytes
    segs = [stream[k * stride:k * stride + seg_bytes] for k in (0, 1)]
    _first, carry = proc.run_device_cold(proc.stage_input(segs[0]))
    (wf_w, det_w), carry_w = proc.run_device_ring(
        carry, proc.stage_input(segs[1], stride_only=True))
    (wf_c, det_c), carry_c = proc.run_device_cold(proc.stage_input(segs[1]))
    np.testing.assert_array_equal(np.asarray(wf_w), np.asarray(wf_c))
    assert np.abs(np.asarray(wf_c)).max() > 0
    np.testing.assert_array_equal(np.asarray(carry_w), np.asarray(carry_c))
    np.testing.assert_array_equal(np.asarray(carry_w).reshape(-1),
                                  segs[1][stride:])
    for got, want in zip(det_w, det_c):
        if isinstance(got, jax.Array):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
