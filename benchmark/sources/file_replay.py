"""``file_replay``: the program's own file reader, started again on the
same file at its end, as fast as the program pulls (closed loop).

The wrapper owns nothing of the read path: every segment comes out of
``srtb_tpu.io.file_input.make_file_source`` — the reader ``Pipeline``
builds for ``--input_file_path`` — so ingest, the host-side overlap tail
and the buffer pool are the program's.  It stamps hand-over times and the
pull that follows each hand-over, keeps the order of hand-over for the
drivers' completion stamps (``handed``), switches between the warm-up
segments and the replayed ones, and stops at the deadline.  It stamps no
completion: that is the driver's, taken where the results arrive.

A pass ends at the last FULL segment: the reader's zero-padded tail is a
step from noise to zeros, which fires a detection and writes a candidate
(PERF.md 5, smoke readings) — an artifact of a file's end that a night
of sky does not have.  Each pass starts a new reader, so its first
segment is one cold ring dispatch (whole segment uploaded).
"""

from __future__ import annotations

import collections
import time

from benchmark.record import SegRec


class FileReplay:
    def __init__(self, cfg, layout, record, params: dict):
        from srtb_tpu.io.file_input import make_file_source

        self._make = lambda offset: make_file_source(
            cfg, start_offset_bytes=offset)
        self.lay = layout
        self.record = record
        self.handed = collections.deque()   # handed over, not yet done
        self._last = None         # the segment handed over last
        self.phase = None
        self.deadline = None
        self.tick = None          # called at every pull of the window
        self._reader = None
        self._count = 0           # segments of this phase
        self._file_seg = 0
        self._pass_left = 0

    # what Pipeline reads off its source besides the segments
    @property
    def pool(self):
        return getattr(self._reader, "pool", None)

    @property
    def logical_offset(self):
        return getattr(self._reader, "logical_offset", 0)

    def begin(self, phase: str, deadline: float | None = None) -> None:
        self._close_reader()
        self.phase = phase
        self.deadline = deadline
        self._count = 0
        self._start_pass()

    def _start_pass(self) -> None:
        self._close_reader()
        lay = self.lay
        first = 0 if self.phase == "warmup" else lay.n_warmup
        self._pass_left = lay.n_warmup if self.phase == "warmup" \
            else lay.n_replay
        self._file_seg = first
        self._reader = self._make(first * lay.stride_bytes)

    def _close_reader(self) -> None:
        if self._reader is not None:
            self._reader.close()
            self._reader = None

    def _mark_pull(self, now: float) -> None:
        """The program asks for the next segment (or its loop returned):
        stamped on the segment handed over before."""
        if self._last is not None:
            self._last.next_pull = now
            self._last = None

    def end_phase(self) -> None:
        """After the program's loop returned: the reader is closed."""
        self._mark_pull(time.perf_counter())
        self._close_reader()

    def __iter__(self):
        return self

    def __next__(self):
        now = time.perf_counter()
        self._mark_pull(now)
        if self.phase == "window":
            if self.tick is not None:
                self.tick(now)
            if now >= self.deadline:
                raise StopIteration
        if self._pass_left == 0:
            if self.phase == "warmup":
                raise StopIteration
            self._start_pass()
        seg = next(self._reader)
        lay = self.lay
        rec = SegRec(index=self._count, phase=self.phase,
                     file_seg=self._file_seg,
                     pulsed=lay.pulsed[self._file_seg],
                     new_samples=lay.stride, segment=seg)
        self._count += 1
        self._file_seg += 1
        self._pass_left -= 1
        data = getattr(seg, "data", None)
        if hasattr(data, "ctypes"):
            rec.buffer_address = int(data.ctypes.data)
        self.record.segs.append(rec)
        self.handed.append(rec)
        self._last = rec
        rec.handover = time.perf_counter()
        return seg


KINDS = {"file_replay": FileReplay}
