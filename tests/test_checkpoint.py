"""Checkpoint/resume tests: a restarted file-mode pipeline continues from
the recorded logical offset and produces the same total segment coverage
as an uninterrupted run."""

import os

import numpy as np

from srtb_tpu.config import Config
from srtb_tpu.pipeline.checkpoint import StreamCheckpoint
from srtb_tpu.pipeline.runtime import Pipeline

from slow_source import SlowFile


def _cfg(tmp_path, n=1 << 12):
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=4 * n, dtype=np.uint8)
    path = str(tmp_path / "in.bin")
    data.tofile(path)
    return Config(
        baseband_input_count=n,
        baseband_input_bits=8,
        input_file_path=path,
        baseband_output_file_prefix=str(tmp_path / "out_"),
        spectrum_channel_count=1 << 4,
        signal_detect_max_boxcar_length=8,
        signal_detect_signal_noise_threshold=99.0,  # never trigger
        baseband_reserve_sample=False,
        checkpoint_path=str(tmp_path / "ckpt.json"),
    )


def test_checkpoint_file_roundtrip(tmp_path):
    p = str(tmp_path / "s.json")
    ck = StreamCheckpoint(p)
    assert ck.segments_done == 0
    ck.update(3, 12345)
    ck2 = StreamCheckpoint(p)
    assert ck2.segments_done == 3
    assert ck2.file_offset_bytes == 12345
    ck2.clear()
    assert not os.path.exists(p)


def test_pipeline_resume(tmp_path):
    cfg = _cfg(tmp_path)
    # run only 2 of the 4 segments, then "crash"
    pipe1 = Pipeline(cfg)
    pipe1.run(max_segments=2)
    ck = StreamCheckpoint(cfg.checkpoint_path)
    assert ck.segments_done == 2
    assert ck.file_offset_bytes == 2 * cfg.baseband_input_count

    # resume: should process exactly the remaining 2 segments
    pipe2 = Pipeline(cfg)
    stats = pipe2.run()
    assert stats.segments == 2
    ck = StreamCheckpoint(cfg.checkpoint_path)
    assert ck.segments_done == 4
    assert ck.file_offset_bytes == 4 * cfg.baseband_input_count


class _Bytes:
    def __init__(self):
        self.seen = []

    def push(self, work, positive):
        self.seen.append(bytes(work.segment.data))


def test_checkpoint_offset_is_the_segments_own_with_the_reader_ahead(
        tmp_path, monkeypatch):
    """The offset recorded after segment k is where the source stood
    after segment k, not where the reader, one segment on, stands when
    the sinks return: a resume re-reads nothing and skips nothing."""
    from srtb_tpu.pipeline import runtime

    # ahead by construction, not by this machine's load
    monkeypatch.setattr(runtime, "_PULL_AHEAD_SHARE", 0.0)
    cfg = _cfg(tmp_path)
    n = cfg.baseband_input_count
    rng = np.random.default_rng(1)
    rng.integers(0, 256, size=10 * n, dtype=np.uint8).tofile(
        cfg.input_file_path)
    sink = _Bytes()
    source = SlowFile(cfg)
    pipe1 = Pipeline(cfg, source=source, sinks=[sink])
    seen = []
    update = pipe1.checkpoint.update
    pipe1.checkpoint.update = lambda done, offset: (
        seen.append((done, offset)), update(done, offset))
    assert pipe1.run(max_segments=7).segments == 7
    assert source.threads[3:] == ["reader"] * 4     # it did run ahead
    assert seen == [(k + 1, (k + 1) * n) for k in range(7)]
    ck = StreamCheckpoint(cfg.checkpoint_path)
    assert (ck.segments_done, ck.file_offset_bytes) == (7, 7 * n)

    # resume from the checkpoint, the reader ahead again: the rest, once
    source2 = SlowFile(cfg, start=ck.file_offset_bytes)
    pipe2 = Pipeline(cfg, source=source2, sinks=[sink])
    assert pipe2.run().segments == 3
    data = np.fromfile(cfg.input_file_path, dtype=np.uint8)
    assert sink.seen == [data[k * n:(k + 1) * n].tobytes()
                         for k in range(10)]
    ck = StreamCheckpoint(cfg.checkpoint_path)
    assert (ck.segments_done, ck.file_offset_bytes) == (10, 10 * n)
