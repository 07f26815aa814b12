"""Source-side half of the ingest-ring contract, shared by the file
reader and the UDP source.

Both sources emit segments that overlap by ``reserved_bytes`` (the
overlap-save tail) and stamp ``SegmentWork.seq`` so the engine's
adjacency guard (pipeline/runtime.py ``_ring_adjacent``) can prove a
segment is the stream-adjacent successor of the last dispatched one —
the precondition for warm carry assembly.  This helper owns BOTH
invariants in one place:

- **tail retention**: the reserved tail of the last emitted segment is
  kept in ONE persistent host buffer (``np.copyto``, never a fresh
  allocation per segment — at high DM the tail is a large fraction of
  the segment) and memcpy'd into the next segment's head; a source
  that is opened again and again (a replayed file) hands in a pool, and
  ``release()`` gives the buffer back when the source closes: fresh
  pages cost the chip machine's host 3.3 us each to fault, 0.2 s for the
  262 MB tail of a 2^30-sample segment at the Crab's DM;
- **seq stamping**: a per-source monotonically increasing emission
  counter, or ``-1`` (never warm-assembled) when the source cannot
  guarantee the overlap — the misaligned-UDP fallback, hand-built
  segments.

A future change to either rule lands here once, for every source.
"""

from __future__ import annotations

import numpy as np


class OverlapTailCarry:
    """Retained reserved-tail + emission-seq bookkeeping for one
    segment source (one instance per receiver/reader)."""

    def __init__(self, reserved_bytes: int, stamp_seq: bool = True,
                 pool=None):
        self.reserved_bytes = int(reserved_bytes)
        self._stamp_seq = bool(stamp_seq)
        self._pool = pool
        self._tail: np.ndarray | None = None
        self._seq = 0

    @property
    def warm(self) -> bool:
        """Whether a retained tail exists to head the next segment."""
        return self._tail is not None

    def head_into(self, buf: np.ndarray) -> int:
        """Copy the retained tail into ``buf[:reserved_bytes]`` when
        warm; returns the number of head bytes filled (0 when cold —
        the caller must produce the full segment itself)."""
        if self._tail is None:
            return 0
        buf[:self.reserved_bytes] = self._tail
        return self.reserved_bytes

    def retain(self, buf: np.ndarray) -> None:
        """Retain ``buf``'s reserved tail for the next segment's head
        (persistent buffer; no per-segment allocation)."""
        if self._tail is None:
            self._tail = (np.empty(self.reserved_bytes, np.uint8)
                          if self._pool is None else
                          self._pool.acquire(self.reserved_bytes,
                                             zero=False))
        np.copyto(self._tail, buf[buf.shape[0] - self.reserved_bytes:])

    def release(self) -> None:
        """The source closes: the retained tail goes back to the pool
        it came from (the next source opened on it takes pages that are
        already mapped), and the carry is cold."""
        if self._tail is not None and self._pool is not None:
            self._pool.release(self._tail)
        self._tail = None

    def next_seq(self) -> int:
        """The emitted segment's ``SegmentWork.seq``: adjacent stamps
        for overlap-capable sources, -1 (never warm) otherwise."""
        if not self._stamp_seq:
            return -1
        self._seq += 1
        return self._seq - 1
