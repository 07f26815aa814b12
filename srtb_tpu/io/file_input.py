"""Baseband file reader with overlap-save seek-back.

Mirrors read_file_pipe (ref: pipeline/read_file_pipe.hpp:31-127):
- skip ``input_file_offset_bytes`` first;
- each call reads ``baseband_input_count * |bits|/8 * data_stream_count``
  bytes straight into a pooled block (``readinto``: no intermediate
  ``bytes``, no whole-block zero fill); only what a short final read
  leaves is zeroed, counted in ``file_zero_fill_bytes``;
- then seeks back ``nsamps_reserved`` samples' worth of bytes so
  consecutive segments overlap (the overlap-save "long-context" mechanism);
- a logical byte counter, not the stream position, tracks progress because
  the final partial segment reads past EOF.

Skip-read fast path (ingest ring, ``Config.ingest_ring`` != "off"):
once a segment has been emitted, its reserved tail is retained in host
memory, so the next segment reads only the stride's NEW bytes from disk
— no seek-back, no re-read of bytes the reader just delivered — and the
head is a host memcpy of the retained tail.  The emitted byte stream is
bit-identical to the legacy seek-back path, and the ``reserved_bytes``
bookkeeping (``logical_offset`` advancing by ``segment - reserved`` per
segment) is UNCHANGED, so checkpoints written either way resume
identically; a resume (or any start) has no retained tail and takes the
full-read path as the cold fallback.
"""

from __future__ import annotations

import time

import numpy as np

from srtb_tpu.config import Config
from srtb_tpu.io import formats
from srtb_tpu.ops import dedisperse as dd
from srtb_tpu.pipeline.work import SegmentWork
from srtb_tpu.utils.bufferpool import BufferPool
from srtb_tpu.utils.logging import log
from srtb_tpu.utils.metrics import metrics

# process-wide segment-buffer pool (ref: srtb::host_allocator singleton,
# global_variables.hpp:49-61)
host_buffer_pool = BufferPool("segments")
# ... and the readers' retained overlap tails: a reader gives its tail
# back when it closes, and the next reader opened in the process (a
# replay's next pass, the next file of an archive) takes the same pages
host_tail_pool = BufferPool("overlap_tails")


class BasebandFileReader:
    """Iterates SegmentWork items from a raw baseband file."""

    def __init__(self, cfg: Config, buffer_pool: BufferPool | None = None,
                 start_offset_bytes: int | None = None):
        self.cfg = cfg
        self.fmt = formats.resolve(cfg.baseband_format_type)
        self.segment_bytes = cfg.segment_bytes(self.fmt.data_stream_count)
        nsamps = dd.nsamps_reserved(cfg)
        self.reserved_bytes = int(nsamps * abs(cfg.baseband_input_bits)
                                  // 8 * self.fmt.data_stream_count)
        self.pool = buffer_pool or host_buffer_pool
        self._file = open(cfg.input_file_path, "rb")
        start = (start_offset_bytes if start_offset_bytes is not None
                 else cfg.input_file_offset_bytes)
        self._file.seek(start)
        # logical byte counter (ref: read_file_pipe.hpp:47-55): tracks where
        # the next segment starts, even past EOF zero-padding
        self.logical_offset = start
        self._exhausted = False
        # skip-read fast path: the retained reserved tail of the last
        # emitted segment (None = cold, take the full-read + seek-back
        # path).  Gated on the ingest-ring knob so "off" restores the
        # reference's exact read pattern.
        self._skip_read = (
            str(getattr(cfg, "ingest_ring", "auto")).lower() != "off"
            and 0 < self.reserved_bytes < self.segment_bytes)
        # shared tail-retention + seq-stamping contract (io/overlap.py);
        # seek-back segments overlap too, so seq is always stamped —
        # only the tail retention is gated on the skip-read path
        from srtb_tpu.io.overlap import OverlapTailCarry
        self._carry = OverlapTailCarry(self.reserved_bytes,
                                       pool=host_tail_pool)

    def __iter__(self):
        return self

    def __next__(self) -> SegmentWork:
        if self._exhausted:
            raise StopIteration
        # not zeroed: every byte of the segment is written below, by the
        # retained tail, the file, or the zeroing of a short read's rest
        buf = self.pool.acquire(self.segment_bytes, zero=False)
        warm = self._skip_read and self._carry.warm
        reserved = self.reserved_bytes if warm else 0
        if warm:
            # head = retained tail (host memcpy replaces the legacy
            # seek-back disk re-read, bit-identically); with 0 new
            # bytes this still emits the tail + zeros final segment
            # the seek-back path would have produced
            self._carry.head_into(buf)
        new = memoryview(buf)[reserved:]
        got = 0
        try:
            # straight into the block behind the head; a raw file may
            # hand out fewer bytes than asked for, only 0 is its end
            while got < len(new):
                n = self._file.readinto(new[got:])
                if not n:
                    break
                got += n
        except BaseException:
            # a failed read may be retried by the pipeline's ingest
            # guard, which calls __next__ again and acquires a fresh
            # buffer — this one must go back or every retried
            # transient strands a segment-sized block in the pool,
            # and the file must stand where this pull found it
            self.pool.release(buf)
            self._file.seek(-got, 1)
            raise
        if got == 0 and not warm:
            self.pool.release(buf)
            log.info(f"[read_file] {self.cfg.input_file_path} has been read")
            self._exhausted = True
            raise StopIteration
        buf[reserved + got:] = 0  # empty on a full segment
        metrics.add("file_zero_fill_bytes", len(new) - got)
        # ingest telemetry: windowed read throughput + pool occupancy
        # gauges (the host-buffer analog of the receiver ring gauges)
        metrics.add("file_bytes_read", got)
        metrics.window("file_bytes_read").add(got)
        pool_stats = self.pool.stats()
        metrics.set("segment_pool_cached_blocks",
                    pool_stats["cached_blocks"])
        metrics.set("segment_pool_cached_bytes",
                    pool_stats["cached_bytes"])
        metrics.set("segment_pool_in_use", pool_stats["in_use"])
        metrics.set("segment_pool_acquires", pool_stats["acquires"])
        metrics.set("segment_pool_new_blocks", pool_stats["new_blocks"])
        self.logical_offset += self.segment_bytes
        if got < len(new):
            # final partial segment: emit zero-padded, then stop
            # (ref: read_file_pipe.hpp:76-77 memset + short read).
            # Warm short reads land here too: a file ending exactly at
            # a segment boundary still yields the same trailing
            # tail-plus-zeros segment the seek-back path emits.
            self._exhausted = True
        elif 0 < self.reserved_bytes < self.segment_bytes:
            # overlap-save: the next segment reprocesses the
            # dedispersion-corrupted tail (ref: read_file_pipe.hpp:86-99)
            # — by retaining it in host memory (skip-read: the next
            # read starts at the stride boundary, where the file
            # position already is) or by the legacy seek-back re-read.
            # logical_offset bookkeeping is identical either way.
            self.logical_offset -= self.reserved_bytes
            if self._skip_read:
                self._carry.retain(buf)
            else:
                self._file.seek(-self.reserved_bytes, 1)
        return SegmentWork(
            data=buf,
            timestamp=time.time_ns(),
            seq=self._carry.next_seq(),
        )

    def close(self):
        self._file.close()
        self._carry.release()


# fixed epoch the deterministic stamps count from (an arbitrary 2023
# instant): stamps must be stable across processes, so the wall clock
# can play no part
DETERMINISTIC_EPOCH_NS = 1_700_000_000_000_000_000


class DeterministicTimestampReader(BasebandFileReader):
    """File reader stamping ``timestamp`` from the segment's STREAM
    OFFSET instead of the wall clock: the same segment gets the same
    stamp in every run and every resume, so file-mode artifact names
    (timestamp-derived when no UDP counter exists) are reproducible
    across runs.  This is what makes an archive replay's output set
    (paths + SHA-256) comparable byte-for-byte against a golden run —
    and what the crash/archive soaks' exactly-once equality gates are
    built on.  Promoted from the crash-soak tool (PR 10) to a
    first-class reader option (``Config.deterministic_timestamps``)
    so the soaks and the archive replay engine share ONE
    implementation."""

    def __next__(self) -> SegmentWork:
        offset = self.logical_offset
        work = super().__next__()
        work.timestamp = DETERMINISTIC_EPOCH_NS + offset
        return work


def make_file_source(cfg: Config,
                     buffer_pool: BufferPool | None = None,
                     start_offset_bytes: int | None = None
                     ) -> BasebandFileReader:
    """The config-selected file source: the deterministic-timestamp
    reader when ``Config.deterministic_timestamps`` is set, the
    wall-clock reader otherwise.  The single construction point the
    Pipeline, the archive replay engine and the soak harnesses all
    use."""
    cls = (DeterministicTimestampReader
           if getattr(cfg, "deterministic_timestamps", False)
           else BasebandFileReader)
    return cls(cfg, buffer_pool=buffer_pool,
               start_offset_bytes=start_offset_bytes)
