"""Host buffer pool — the cached-allocator analog.

The reference caches device/host allocations in size-bucketed free lists
because raw (pinned) allocation costs 0.5-5 s/GB (ref: memory/
cached_allocator.hpp:38-235, main.cpp:57).  On the TPU side HBM is managed
by XLA (buffer reuse inside jit; donation at boundaries), so what remains
worth pooling is the *host* side: the big per-segment numpy byte buffers
the readers fill.  Same policy as the reference: exact-or-larger reuse
with a 0.5 threshold (a cached block at least the requested size but no
more than 2x is reused, cached_allocator.hpp:75-121), explicit
``free_all``, and double-release diagnostics.

Two cumulative counters say whether reuse engages: ``acquires`` (every
``acquire``) and ``new_blocks`` (those no cached block could serve, so a
fresh one was allocated).  A loop that returns its buffers holds
``new_blocks`` at the depth of its window however long it runs; one that
forgets to adds a block per segment.  ``stats()`` hands them out.
"""

from __future__ import annotations

import threading

import numpy as np

from srtb_tpu.utils.logging import log


class BufferPool:
    def __init__(self, name: str = "host"):
        self.name = name
        self._free: dict[int, list[np.ndarray]] = {}
        self._out: set[int] = set()
        self._acquires = 0
        self._new_blocks = 0
        self._lock = threading.Lock()

    def acquire(self, nbytes: int, zero: bool = True) -> np.ndarray:
        """Get a uint8 buffer of exactly nbytes (a view of a possibly
        larger cached block)."""
        with self._lock:
            self._acquires += 1
            best_size = None
            for size in self._free:
                if nbytes <= size <= 2 * nbytes:  # the 0.5 reuse threshold
                    if best_size is None or size < best_size:
                        best_size = size
            if best_size is not None:
                block = self._free[best_size].pop()
                if not self._free[best_size]:
                    del self._free[best_size]
            else:
                log.debug(f"[buffer_pool {self.name}] new block "
                          f"{nbytes} bytes")
                self._new_blocks += 1
                block = np.empty(nbytes, dtype=np.uint8)
            self._out.add(id(block))
        if zero:
            block[:nbytes] = 0
        return block[:nbytes] if block.nbytes != nbytes else block

    def release(self, buf: np.ndarray) -> None:
        base = buf.base if buf.base is not None else buf
        with self._lock:
            if id(base) not in self._out:
                log.warning(f"[buffer_pool {self.name}] releasing unknown "
                            "or already-freed buffer")
                return
            self._out.discard(id(base))
            self._free.setdefault(base.nbytes, []).append(base)

    def stats(self) -> dict:
        """Snapshot for the buffer gauges (telemetry): cached block
        count/bytes, buffers currently out, and the two cumulative
        counters (acquires, new blocks)."""
        with self._lock:
            cached = sum(len(v) for v in self._free.values())
            cached_bytes = sum(size * len(v)
                               for size, v in self._free.items())
            return {"cached_blocks": cached,
                    "cached_bytes": cached_bytes,
                    "in_use": len(self._out),
                    "acquires": self._acquires,
                    "new_blocks": self._new_blocks}

    def free_all(self) -> int:
        """Drop all cached blocks (ref: deallocate_all_free_ptrs); returns
        count of buffers still in use (leak diagnostic,
        ref: cached_allocator.hpp:230-233)."""
        with self._lock:
            self._free.clear()
            in_use = len(self._out)
        if in_use:
            log.warning(f"[buffer_pool {self.name}] {in_use} buffers still "
                        "in use")
        return in_use
