"""Writer tests: the async writer pool (native C++ + Python fallback) and
the candidate sink's piggybank policy / file formats.

Oracle style mirrors the reference's (SURVEY.md §4): byte-level comparison
against synchronously-written files and hand-computed expectations.
"""

import os

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.io import native_writer
from srtb_tpu.io.native_writer import AsyncWriterPool
from srtb_tpu.io.writers import WriteSignalSink
from srtb_tpu.ops.detect import DetectResult
from srtb_tpu.pipeline.work import SegmentResultWork, SegmentWork


@pytest.fixture(params=["native", "python"])
def pool(request):
    if request.param == "native" and not native_writer.native_available():
        pytest.skip("libsrtb_writer.so not built")
    p = AsyncWriterPool(n_threads=3,
                        prefer_native=(request.param == "native"))
    assert p.is_native == (request.param == "native")
    yield p
    p.close()


def test_pool_writes_bytes_and_arrays(pool, tmp_path):
    rng = np.random.default_rng(0)
    blobs = {str(tmp_path / f"f{i}.bin"): rng.integers(
        0, 256, size=rng.integers(1, 4096), dtype=np.uint8)
        for i in range(16)}
    for path, blob in blobs.items():
        pool.submit(path, blob, fsync=(hash(path) % 2 == 0))
    pool.drain()
    for path, blob in blobs.items():
        with open(path, "rb") as f:
            assert f.read() == blob.tobytes()
    stats = pool.stats()
    assert stats["jobs_done"] == len(blobs)
    assert stats["errors"] == 0
    assert stats["bytes_written"] == sum(b.size for b in blobs.values())


def test_pool_caller_buffer_reusable(pool, tmp_path):
    # submission copies: mutating the source after submit must not change
    # what lands on disk (the reference passes shared_ptr-owned copies)
    buf = np.full(1 << 16, 7, dtype=np.uint8)
    path = str(tmp_path / "reuse.bin")
    pool.submit(path, buf)
    buf[:] = 0
    pool.drain()
    assert np.all(np.fromfile(path, dtype=np.uint8) == 7)


def test_pool_append_single_thread(tmp_path):
    # ordered appends need a 1-thread pool (like the reference's dedicated
    # per-purpose pools)
    for native in ([True] if native_writer.native_available() else []) + [False]:
        p = AsyncWriterPool(n_threads=1, prefer_native=native)
        path = str(tmp_path / f"append_{native}.bin")
        for i in range(8):
            p.submit(path, np.full(4, i, dtype=np.uint8), append=True)
        p.drain()
        got = np.fromfile(path, dtype=np.uint8)
        assert got.tolist() == sum(([i] * 4 for i in range(8)), [])
        p.close()
    # append on a multi-thread pool would reorder: must be rejected
    with AsyncWriterPool(n_threads=2, prefer_native=False) as p:
        with pytest.raises(ValueError):
            p.submit(str(tmp_path / "bad.bin"), b"x", append=True)


def test_write_all_sink_async(tmp_path):
    from srtb_tpu.io.writers import WriteAllSink
    cfg = _mk_cfg(tmp_path, "writeall")
    with AsyncWriterPool(n_threads=1) as pool:
        sink = WriteAllSink(cfg, reserved_bytes=64, writer_pool=pool)
        works = [_mk_work(counter=i) for i in range(4)]
        for w in works:
            sink.push(w)
        sink.drain()
        expected = b"".join(
            np.ascontiguousarray(w.segment.data[:-64]).tobytes()
            for w in works)
        with open(sink.path, "rb") as f:
            assert f.read() == expected
    with pytest.raises(ValueError):
        WriteAllSink(cfg, 0, writer_pool=AsyncWriterPool(
            n_threads=2, prefer_native=False))


def test_pool_backpressure_bounded_queue(tmp_path):
    # with a tiny byte bound, submit must block-and-release rather than
    # deadlock or drop jobs (the reference's bounded-queue backpressure)
    for native in ([True] if native_writer.native_available() else []) + [False]:
        p = AsyncWriterPool(n_threads=2, prefer_native=native,
                            max_queued_bytes=1 << 12)
        blob = np.arange(1 << 10, dtype=np.uint8) % 251
        for i in range(64):  # 64 KiB through a 4 KiB window
            p.submit(str(tmp_path / f"bp_{native}_{i}.bin"), blob)
        big = np.full(1 << 14, 3, dtype=np.uint8)  # oversized single job
        p.submit(str(tmp_path / f"bp_{native}_big.bin"), big)
        p.drain()
        assert p.stats()["jobs_done"] == 65
        assert p.stats()["errors"] == 0
        got = np.fromfile(str(tmp_path / f"bp_{native}_63.bin"),
                          dtype=np.uint8)
        assert np.array_equal(got, blob)
        p.close()


def test_pool_error_accounting(pool, tmp_path):
    pool.submit(str(tmp_path / "no" / "such" / "dir" / "x.bin"),
                np.zeros(4, dtype=np.uint8))
    pool.drain()
    assert pool.stats()["errors"] == 1
    with pytest.raises(RuntimeError, match="1 async write"):
        pool.raise_new_errors("test")
    pool.raise_new_errors("test")  # already reported: no raise


def test_signal_sink_drain_raises_on_failed_write(tmp_path):
    cfg = _mk_cfg(tmp_path, "errs")
    with AsyncWriterPool(n_threads=1) as pool:
        sink = WriteSignalSink(cfg, fdatasync=False, writer_pool=pool)
        sink.push(_mk_work(), has_signal=True)
        sink.drain()  # fine
        import shutil
        shutil.rmtree(os.path.dirname(cfg.baseband_output_file_prefix))
        sink.push(_mk_work(counter=99), has_signal=True)
        with pytest.raises(RuntimeError, match="async write"):
            sink.drain()


# ----------------------------------------------------------------------
# WriteSignalSink with an async pool must produce byte-identical files to
# the synchronous path.
# ----------------------------------------------------------------------

def _mk_cfg(tmp_path, name):
    d = tmp_path / name
    d.mkdir()
    return Config(
        baseband_input_count=1 << 10, baseband_input_bits=8,
        baseband_format_type="simple", baseband_freq_low=1000.0,
        baseband_bandwidth=16.0, baseband_sample_rate=32e6, dm=5.0,
        spectrum_channel_count=1 << 4,
        baseband_output_file_prefix=str(d) + "/cand_")


def _mk_work(counter=1234):
    rng = np.random.default_rng(42)
    seg = SegmentWork(
        data=rng.integers(0, 256, size=1 << 10, dtype=np.uint8),
        timestamp=10 ** 15, udp_packet_counter=counter)
    wf = (rng.normal(size=(1, 16, 32)) +
          1j * rng.normal(size=(1, 16, 32))).astype(np.complex64)
    t = 32
    detect = DetectResult(
        zero_count=np.int32(0),
        time_series=rng.normal(size=(1, t)).astype(np.float32),
        boxcar_lengths=(1, 2, 4),
        signal_counts=np.array([[3, 0, 1]], dtype=np.int32),
        boxcar_series=rng.normal(size=(1, 3, t)).astype(np.float32),
        snr_peaks=np.array([[9.0, 1.0, 8.5]], dtype=np.float32))
    return SegmentResultWork(segment=seg, waterfall=wf, detect=detect)


def test_signal_sink_async_matches_sync(tmp_path):
    work = _mk_work()

    sync_sink = WriteSignalSink(_mk_cfg(tmp_path, "sync"), fdatasync=False)
    sync_sink.push(work, has_signal=True)

    with AsyncWriterPool(n_threads=2) as pool:
        async_sink = WriteSignalSink(_mk_cfg(tmp_path, "async"),
                                     fdatasync=False, writer_pool=pool)
        async_sink.push(work, has_signal=True)
        async_sink.drain()

    assert len(sync_sink.written) == len(async_sink.written) == 1
    s, a = sync_sink.written[0], async_sink.written[0]
    for sp, ap in zip([s.bin_path] + s.npy_paths + s.tim_paths,
                      [a.bin_path] + a.npy_paths + a.tim_paths):
        with open(sp, "rb") as f1, open(ap, "rb") as f2:
            assert f1.read() == f2.read(), (sp, ap)
    # npy round-trip sanity: plot_spectrum.py-compatible payload
    arr = np.load(a.npy_paths[0])
    assert arr.dtype == np.complex64 and arr.shape == (16, 32)


def test_signal_sink_async_npy_index_collision(tmp_path):
    # queued-but-unwritten .npy paths must count as taken when picking the
    # next free index (ref picks first non-existing name, 230-235)
    cfg = _mk_cfg(tmp_path, "collide")
    with AsyncWriterPool(n_threads=1) as pool:
        sink = WriteSignalSink(cfg, fdatasync=False, writer_pool=pool)
        sink.push(_mk_work(counter=7), has_signal=True)
        sink.push(_mk_work(counter=7), has_signal=True)  # same counter
        sink.drain()
    paths = sorted(p for w in sink.written for p in w.npy_paths)
    assert len(paths) == len(set(paths)) == 2


def test_piggybank_other_polarization_capture(tmp_path):
    # a negative segment whose timestamp overlaps (±0.45 segment) a recent
    # positive must still be written (ref: write_signal_pipe.hpp:102-115);
    # piggybank applies in real-time (UDP) mode only
    cfg = _mk_cfg(tmp_path, "piggy")
    assert cfg.input_file_path == ""
    sink = WriteSignalSink(cfg, fdatasync=False)
    seg_ns = 1e9 * cfg.baseband_input_count / cfg.baseband_sample_rate

    pos = _mk_work(counter=100)
    sink.push(pos, has_signal=True)
    near = _mk_work(counter=101)
    near.segment.timestamp = pos.segment.timestamp + int(0.2 * seg_ns)
    sink.push(near, has_signal=False)
    far = _mk_work(counter=102)
    far.segment.timestamp = pos.segment.timestamp + int(10 * seg_ns)
    sink.push(far, has_signal=False)

    counters = [os.path.basename(w.bin_path) for w in sink.written]
    assert counters == ["cand_100.bin", "cand_101.bin"]


# ----------------------------------------------------------------------
# The stacked waterfall is made into each stream's file from the device
# a block of rows at a time, and a file over the writer pool's bound
# (the 4.29 GB of a 2^30-sample segment) is written by the sink's own
# thread (ISSUE 44): the same bytes, without the whole host copy of the
# planes and without the pool's copy.
# ----------------------------------------------------------------------

def _planes_work(streams=2):
    work = _mk_work()
    rng = np.random.default_rng(7)
    work.waterfall = rng.normal(size=(2, streams, 16, 32)) \
        .astype(np.float32)
    work.detect = None
    return work


def _npy_files(planes):
    """Each stream's file, spelled out."""
    from srtb_tpu.io import writers
    return [np.frombuffer(writers._npy_bytes(
        (planes[0, s] + 1j * planes[1, s]).astype(np.complex64)), np.uint8)
        for s in range(planes.shape[1])]


@pytest.mark.parametrize("where", ["host", "device", "lazy"])
def test_npy_by_blocks_is_the_whole_planes_file(where, monkeypatch):
    import jax

    from srtb_tpu.io import writers
    from srtb_tpu.pipeline.runtime import _DeadlineArray

    planes = _planes_work().waterfall
    want = _npy_files(planes)
    # blocks of 3 rows: the last one is short
    monkeypatch.setattr(writers, "NPY_BLOCK_BYTES", 3 * 8 * 2 * 32)
    src = planes if where == "host" else jax.device_put(planes)
    if where == "lazy":
        src = _DeadlineArray(src, lambda fn: fn())
    got = writers._npy_complex64_by_blocks(src)
    assert len(got) == 2
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    if where == "lazy":
        assert not src._fetched          # the whole was never fetched


def test_an_oversized_waterfall_is_written_by_the_sink(tmp_path,
                                                       monkeypatch):
    from srtb_tpu.io import writers

    work = _planes_work()
    want = _npy_files(work.waterfall)
    monkeypatch.setattr(writers, "NPY_BLOCK_BYTES", 4096)
    with AsyncWriterPool(n_threads=2, max_queued_bytes=2048) as pool:
        sink = WriteSignalSink(_mk_cfg(tmp_path, "blocks"),
                               fdatasync=False, writer_pool=pool)
        sink.push(work, has_signal=True)
        sink.drain()
        assert pool.stats()["jobs_done"] == 1        # the .bin alone
    written = sink.written[0]
    assert len(written.npy_paths) == 2
    for path, w in zip(written.npy_paths, want):
        np.testing.assert_array_equal(np.fromfile(path, np.uint8), w)
