"""File reader tests: offset skip, overlap-save positions, zero-padded
tail (ref semantics: read_file_pipe.hpp:38-117)."""

import tracemalloc

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.io.file_input import BasebandFileReader
from srtb_tpu.ops import dedisperse as dd
from srtb_tpu.utils.bufferpool import BufferPool
from srtb_tpu.utils.metrics import metrics


def _write(tmp_path, data):
    path = str(tmp_path / "in.bin")
    np.asarray(data, dtype=np.uint8).tofile(path)
    return path


def test_offset_skip(tmp_path):
    data = np.arange(64, dtype=np.uint8)
    cfg = Config(baseband_input_count=16, baseband_input_bits=8,
                 input_file_path=_write(tmp_path, data),
                 input_file_offset_bytes=10,
                 baseband_reserve_sample=False)
    reader = BasebandFileReader(cfg)
    seg = next(reader)
    np.testing.assert_array_equal(seg.data, data[10:26])


def test_overlap_save_positions(tmp_path):
    """With reserve enabled, consecutive segments must overlap by exactly
    nsamps_reserved samples."""
    n = 1 << 18
    cfg = Config(baseband_input_count=n, baseband_input_bits=8,
                 baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                 baseband_sample_rate=128e6, dm=0.5,
                 spectrum_channel_count=1 << 4,
                 baseband_reserve_sample=True)
    reserved = dd.nsamps_reserved(cfg)
    assert 0 < reserved < n
    data = np.arange(3 * n, dtype=np.uint64).astype(np.uint8)  # wrapping ramp
    data = np.arange(3 * n) % 251
    data = data.astype(np.uint8)
    cfg = cfg.replace(input_file_path=_write(tmp_path, data))
    reader = BasebandFileReader(cfg)
    seg1 = next(reader)
    seg2 = next(reader)
    np.testing.assert_array_equal(seg1.data, data[:n])
    start2 = n - reserved
    np.testing.assert_array_equal(seg2.data, data[start2:start2 + n])


def test_zero_padded_tail(tmp_path):
    data = np.full(24, 7, dtype=np.uint8)
    cfg = Config(baseband_input_count=16, baseband_input_bits=8,
                 input_file_path=_write(tmp_path, data),
                 baseband_reserve_sample=False)
    reader = BasebandFileReader(cfg)
    seg1 = next(reader)
    seg2 = next(reader)
    np.testing.assert_array_equal(seg1.data, 7)
    np.testing.assert_array_equal(seg2.data[:8], 7)
    np.testing.assert_array_equal(seg2.data[8:], 0)  # memset-style padding
    try:
        next(reader)
        raised = False
    except StopIteration:
        raised = True
    assert raised


def test_sub_byte_segment_bytes(tmp_path):
    """2-bit samples: segment bytes = count/4."""
    data = np.arange(32, dtype=np.uint8)
    cfg = Config(baseband_input_count=64, baseband_input_bits=2,
                 input_file_path=_write(tmp_path, data),
                 baseband_reserve_sample=False)
    reader = BasebandFileReader(cfg)
    seg = next(reader)
    assert seg.data.shape == (16,)
    np.testing.assert_array_equal(seg.data, data[:16])


# ------------------------------------------- in-place fill (readinto)
#
# The reader fills a pooled block in place and zeroes only what a short
# read leaves, so a recycled block's old bytes must never reach a
# segment.  Every case below reads from a pool whose blocks come back
# full of 0xFF and compares against plain slicing of the file.

N = 1 << 16
RINGS = ["auto", "off"]
BITS = [8, 2]


def _overlapped(tmp_path, bits, ring, nbytes):
    """A reader's configuration with overlap-save on, over a file of
    ``nbytes(segment bytes, stride bytes)`` seeded bytes, none of them 0
    or 0xFF; returns (cfg, the file's bytes, segment bytes, reserved
    bytes)."""
    cfg = Config(baseband_input_count=N, baseband_input_bits=bits,
                 baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                 baseband_sample_rate=128e6, dm=0.1,
                 spectrum_channel_count=1 << 4,
                 baseband_reserve_sample=True, ingest_ring=ring)
    seg = N * bits // 8
    reserved = dd.nsamps_reserved(cfg) * bits // 8
    assert 0 < reserved < seg // 2
    data = np.random.default_rng(5).integers(
        1, 255, nbytes(seg, seg - reserved), dtype=np.uint8)
    return (cfg.replace(input_file_path=_write(tmp_path, data)), data,
            seg, reserved)


def _sliced(data, seg, reserved, start=0):
    """The segments the seek-back rules give, by slicing: a short chunk
    is zero-padded and the last, an empty one ends the stream."""
    out, pos = [], start
    while len(data[pos:pos + seg]):
        chunk = data[pos:pos + seg]
        out.append(np.pad(chunk, (0, seg - len(chunk))))
        if len(chunk) < seg:
            break
        pos += seg - reserved
    return out


def _poisoned_pool(seg, blocks=2):
    pool = BufferPool("t")
    held = [pool.acquire(seg, zero=False) for _ in range(blocks)]
    for buf in held:
        buf[:] = 0xFF
        pool.release(buf)
    return pool


def _drain(reader):
    """Every segment's bytes; each block goes back poisoned, as a sink
    that scribbled on it would leave it."""
    out = []
    for work in reader:
        out.append(work.data.copy())
        work.data[:] = 0xFF
        reader.pool.release(work.data)
    return out


def _assert_stream(got, want):
    assert len(got) == len(want)
    for k, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"segment {k}")


class _ShortReads:
    """A raw file that hands out at most ``limit`` bytes a call and has
    no ``read``: a pull that still asks for one fails."""

    def __init__(self, raw, limit=4096):
        self.raw, self.limit, self.calls = raw, limit, 0

    def readinto(self, view):
        self.calls += 1
        return self.raw.readinto(memoryview(view)[:self.limit])

    def seek(self, *args):
        return self.raw.seek(*args)

    def close(self):
        self.raw.close()


class _FailingOnce(_ShortReads):
    """Hands out ``limit`` bytes, then raises once, then behaves."""

    def __init__(self, raw, fail_at_call):
        super().__init__(raw, limit=1000)
        self.fail_at_call = fail_at_call

    def readinto(self, view):
        if self.calls == self.fail_at_call:
            self.calls += 1
            raise OSError("transient read failure")
        return super().readinto(view)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("ring", RINGS)
def test_stale_block_never_reaches_a_segment(tmp_path, ring, bits):
    """(a) recycled 0xFF blocks, a file that ends mid-segment: cold, warm
    and seek-back pulls give the file's bytes and a zero tail."""
    cfg, data, seg, reserved = _overlapped(
        tmp_path, bits, ring, lambda seg, stride: 3 * seg + seg // 3)
    reader = BasebandFileReader(cfg, buffer_pool=_poisoned_pool(seg))
    got = _drain(reader)
    want = _sliced(data, seg, reserved)
    _assert_stream(got, want)
    assert len(got) >= 4 and not got[-1][-(seg // 8):].any()
    assert reader.pool.stats()["new_blocks"] == 2   # only the poisoned
    reader.close()
    # a resume mid-file has no retained tail: a cold pull at an offset
    start = seg - reserved + 7
    resumed = BasebandFileReader(cfg, buffer_pool=_poisoned_pool(seg),
                                 start_offset_bytes=start)
    _assert_stream(_drain(resumed), _sliced(data, seg, reserved, start))
    resumed.close()


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("ring", RINGS)
def test_end_of_file_at_a_stride_boundary(tmp_path, ring, bits):
    """(b) the file ends where a pull would start reading: the reader
    still emits the retained tail plus zeros, then stops."""
    cfg, data, seg, reserved = _overlapped(
        tmp_path, bits, ring, lambda seg, stride: seg + 2 * stride)
    reader = BasebandFileReader(cfg, buffer_pool=_poisoned_pool(seg))
    got = _drain(reader)
    assert len(got) == 4
    _assert_stream(got, _sliced(data, seg, reserved))
    np.testing.assert_array_equal(got[-1][:reserved], data[-reserved:])
    assert not got[-1][reserved:].any()
    with pytest.raises(StopIteration):
        next(reader)
    assert reader.logical_offset == 4 * seg - 3 * reserved
    reader.close()


@pytest.mark.parametrize("ring", RINGS)
def test_no_segment_sized_temporary(tmp_path, ring):
    """(c) ten pulls allocate nothing of half a segment or more beyond
    the pool's first block, and never call ``read``."""
    cfg, data, seg, reserved = _overlapped(
        tmp_path, 8, ring, lambda seg, stride: 12 * seg)
    pool = BufferPool("t")
    reader = BasebandFileReader(cfg, buffer_pool=pool)
    reader._file = _ShortReads(reader._file, limit=1 << 30)
    pool.release(next(reader).data)     # the pool's first (and only) block
    metrics.reset()
    tracemalloc.start()
    try:
        before, _ = tracemalloc.get_traced_memory()
        tracemalloc.reset_peak()
        for _ in range(10):
            pool.release(next(reader).data)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak - before < seg // 2, (peak - before, seg)
    assert metrics.get("segment_pool_new_blocks") == 1
    assert metrics.get("segment_pool_acquires") == 11
    reader.close()
    metrics.reset()


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("ring", RINGS)
def test_short_raw_reads_are_not_the_files_end(tmp_path, ring, bits):
    """(d) a raw file that hands out 4096 bytes a call gives the same
    stream, to the same end."""
    cfg, data, seg, reserved = _overlapped(
        tmp_path, bits, ring, lambda seg, stride: 2 * seg + seg // 2 + 11)
    reader = BasebandFileReader(cfg, buffer_pool=_poisoned_pool(seg))
    reader._file = _ShortReads(reader._file)
    got = _drain(reader)
    _assert_stream(got, _sliced(data, seg, reserved))
    assert reader._file.calls > len(data) // 4096
    reader.close()


@pytest.mark.parametrize("failing_pull", [0, 1])
@pytest.mark.parametrize("ring", RINGS)
def test_a_read_that_raises_returns_the_block(tmp_path, ring,
                                              failing_pull):
    """(e) the read fails part-way through a cold or a warm pull: the
    block is back in the pool, the file stands where the pull found it,
    and the retried pull gives the right segment."""
    cfg, data, seg, reserved = _overlapped(
        tmp_path, 8, ring, lambda seg, stride: 3 * seg)
    pool = _poisoned_pool(seg)
    reader = BasebandFileReader(cfg, buffer_pool=pool)
    want = _sliced(data, seg, reserved)
    got = []
    for _ in range(failing_pull):
        got.append(next(reader).data.copy())
    in_use = pool.stats()["in_use"]
    offset = reader.logical_offset
    raw = reader._file
    reader._file = _FailingOnce(raw, fail_at_call=3)
    with pytest.raises(OSError):
        next(reader)
    assert pool.stats()["in_use"] == in_use
    assert reader.logical_offset == offset
    got.append(next(reader).data.copy())        # the ingest guard's retry
    reader._file = raw
    got.extend(work.data.copy() for work in reader)
    _assert_stream(got, want)
    assert [w.seq for w in BasebandFileReader(cfg, buffer_pool=pool)] \
        == list(range(len(want)))
    reader.close()


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("ring", RINGS)
def test_zero_fill_counter(tmp_path, ring, bits):
    """(f) ``file_zero_fill_bytes``: 0 over full segments, the padded
    length after the last."""
    cfg, data, seg, reserved = _overlapped(
        tmp_path, bits, ring, lambda seg, stride: 3 * seg + seg // 3 + 5)
    metrics.reset()
    reader = BasebandFileReader(cfg, buffer_pool=_poisoned_pool(seg))
    want = _sliced(data, seg, reserved)
    for k in range(len(want) - 1):
        reader.pool.release(next(reader).data)
        assert metrics.snapshot()["file_zero_fill_bytes"] == 0, k
    last = next(reader).data
    padded = seg - int(np.flatnonzero(last)[-1]) - 1    # file bytes are 1..254
    assert padded == int((want[-1] == 0).sum()) > 0
    assert metrics.get("file_zero_fill_bytes") == padded
    assert metrics.get("file_bytes_read") == (
        len(data) if ring == "auto"
        else len(data) + (len(want) - 1) * reserved)
    reader.close()
    metrics.reset()


def test_a_closed_readers_tail_serves_the_next_reader(tmp_path):
    """A file opened again and again (a replay's passes, an archive's
    files): the reader's retained overlap tail goes back to its pool at
    ``close()`` and the next reader's tail is the same pages, so a pass
    does not fault a fresh tail; the streams are the same bytes."""
    from srtb_tpu.io import file_input

    cfg, data, seg, reserved = _overlapped(
        tmp_path, 8, "auto", lambda seg, stride: seg + 3 * stride)
    want = _sliced(data, seg, reserved)
    first = BasebandFileReader(cfg)
    got = [next(first).data.copy() for _ in range(2)]
    tail = first._carry._tail
    assert tail is not None and tail.nbytes == reserved
    before = file_input.host_tail_pool.stats()
    first.close()
    assert first._carry._tail is None and not first._carry.warm
    first.close()                       # closing twice releases once
    after = file_input.host_tail_pool.stats()
    assert after["in_use"] == before["in_use"] - 1
    second = BasebandFileReader(cfg)
    got = _drain(second)
    assert second._carry._tail is tail
    assert file_input.host_tail_pool.stats()["new_blocks"] \
        == before["new_blocks"]
    _assert_stream(got, want)
    second.close()
