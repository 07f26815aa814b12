"""Asynchronous writer pool.

Python-side interface over the native C++ writer-thread pool
(``srtb_tpu/native/file_writer.cpp``, built to ``libsrtb_writer.so``), with
a pure-Python daemon-thread pool fallback implementing the same
(path, bytes, fsync) job semantics.

The reference writes candidates asynchronously from two
boost::asio::thread_pools so the pipeline never blocks on disk — baseband
``.bin`` blobs are fdatasync'd, spectrum ``.npy``/``.tim`` files are not
(ref: pipeline/write_signal_pipe.hpp:159-280).  An ``AsyncWriterPool`` is
the srtb_tpu equivalent: submission copies the payload so the caller can
reuse its buffer immediately; ``drain()`` blocks until everything queued
has hit the filesystem.
"""

from __future__ import annotations

import ctypes
import os
import queue
import threading
import weakref
from concurrent.futures import Future

import numpy as np

from srtb_tpu.utils import termination
from srtb_tpu.utils.logging import log
from srtb_tpu.utils.tracing import span

_LIB_PATH = os.path.join(os.path.dirname(__file__), "..", "native",
                         "libsrtb_writer.so")


def _load_native():
    try:
        lib = ctypes.CDLL(os.path.abspath(_LIB_PATH))
    except OSError:
        return None
    lib.srtb_writer_create.restype = ctypes.c_void_p
    lib.srtb_writer_create.argtypes = [ctypes.c_int32, ctypes.c_uint64]
    lib.srtb_writer_submit.restype = ctypes.c_int32
    lib.srtb_writer_submit.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_uint64, ctypes.c_int32, ctypes.c_int32]
    lib.srtb_writer_drain.argtypes = [ctypes.c_void_p]
    try:
        for name in ("srtb_writer_jobs_done", "srtb_writer_bytes_written",
                     "srtb_writer_errors", "srtb_writer_write_ns"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_uint64
            fn.argtypes = [ctypes.c_void_p]
    except AttributeError:
        # a library built from an older file_writer.cpp: the Python
        # pool serves until ``make -C srtb_tpu/native`` rebuilds it
        log.warning(f"[writer_pool] {_LIB_PATH} is stale (no "
                    "srtb_writer_write_ns): using the Python pool")
        return None
    lib.srtb_writer_destroy.argtypes = [ctypes.c_void_p]
    return lib


_NATIVE = _load_native()


class _DaemonWriterPool:
    """Minimal Future-based thread pool with DAEMON workers, lazily
    spawned on first submit (like the executor it replaces).

    ``concurrent.futures`` executors use non-daemon threads, which
    ``threading._shutdown`` joins at interpreter exit no matter what —
    dropping them from that module's own exit registry only skips *its*
    join, so a wedged write abandoned by ``close(drain=False)`` would
    still hang process exit.  Daemon workers actually die with the
    process; a ``weakref.finalize`` in ``AsyncWriterPool`` (mirroring
    the native pool's) keeps the flush-at-exit behavior for pools that
    are never explicitly closed."""

    def __init__(self, n_threads: int, name_prefix: str = "srtb-writer"):
        self.n_threads = n_threads
        self.name_prefix = name_prefix
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        self._threads: list[threading.Thread] = []

    def _work(self):
        while True:
            job = self._jobs.get()
            if job is None:
                return
            fut, fn, args = job
            if not fut.set_running_or_notify_cancel():
                continue  # cancelled while still queued
            try:
                fut.set_result(fn(*args))
            except BaseException as e:  # noqa: BLE001 - delivered via result()
                fut.set_exception(e)

    def submit(self, fn, *args) -> Future:
        if not self._threads:  # lazy spawn; callers serialize submits
            self._threads = [
                threading.Thread(target=self._work, daemon=True,
                                 name=f"{self.name_prefix}_{i}")
                for i in range(self.n_threads)]
            for t in self._threads:
                termination.tag_thread(t)
                t.start()
        fut = Future()
        self._jobs.put((fut, fn, args))
        return fut

    def shutdown(self, wait: bool = True,
                 cancel_futures: bool = False) -> None:
        if cancel_futures:
            while True:
                try:
                    job = self._jobs.get_nowait()
                except queue.Empty:
                    break
                if job is not None:
                    job[0].cancel()
        for _ in self._threads:
            self._jobs.put(None)
        if wait:
            for t in self._threads:
                t.join()


def native_available() -> bool:
    return _NATIVE is not None


class AsyncWriterPool:
    """Thread-pool writer for (path, bytes, fsync, append) jobs.

    Uses the native C++ pool when ``libsrtb_writer.so`` is built (run
    ``make -C srtb_tpu/native``), otherwise a Python daemon-thread pool
    with identical semantics.
    """

    DEFAULT_MAX_QUEUED_BYTES = 1 << 30  # 1 GiB of queued payload copies

    def __init__(self, n_threads: int = 2, prefer_native: bool = True,
                 max_queued_bytes: int | None = None):
        self.n_threads = max(1, n_threads)
        if max_queued_bytes is None:
            max_queued_bytes = self.DEFAULT_MAX_QUEUED_BYTES
        self.max_queued_bytes = max_queued_bytes
        self._lock = threading.Lock()
        self._space = threading.Condition(self._lock)
        self._queued_bytes = 0
        self._errors_raised = 0
        self._py_errors = 0
        self._py_jobs = 0
        self._py_bytes = 0
        self._py_file_s = 0.0
        # native pool only: manifest commit callbacks deferred to the
        # drain barrier (see submit); _done_err_base is the error
        # count the pending batch started from
        self._pending_done: list = []
        self._done_err_base = 0
        if prefer_native and _NATIVE is not None:
            self._lib = _NATIVE
            self._h = self._lib.srtb_writer_create(self.n_threads,
                                                   max_queued_bytes)
            self._pool = None
            if not self._h:
                raise MemoryError("srtb_writer_create failed")
            # drain+destroy the native pool even if close() is never
            # called (srtb_writer_destroy joins the C++ threads)
            self._finalizer = weakref.finalize(
                self, self._lib.srtb_writer_destroy, self._h)
        else:
            self._lib = None
            self._h = None
            self._pool = _DaemonWriterPool(self.n_threads)
            self._futures = []
            # flush-at-exit / at-GC for pools never close()d, like the
            # native pool's drain+destroy finalizer (queued jobs finish
            # before the sentinel; daemon workers would otherwise die
            # mid-queue with the process)
            self._finalizer = weakref.finalize(self, self._pool.shutdown)

    @property
    def is_native(self) -> bool:
        return self._h is not None

    # ------------------------------------------------------------------

    def submit(self, path: str, data, *, fsync: bool = False,
               append: bool = False, on_done=None,
               pre_publish=None, timer=None, trace_id: int = 0) -> None:
        """Queue one write. ``data`` is bytes or a numpy array; it is
        copied at submission, so the caller may reuse its buffer.

        ``append`` requires a single-thread pool: with more workers the
        append order would be nondeterministic.

        ``on_done`` (the manifest commit hook, io/manifest.py) fires
        after the write durably landed: the Python pool calls it from
        the worker thread right after the successful atomic rename /
        append; the native C++ pool has no per-job completion hook, so
        callbacks are deferred to the next ``drain()`` barrier.  When
        that drain observed new write errors, the native counter
        cannot say WHICH job failed — so each pending ATOMIC job is
        attributed through the filesystem instead (the C++ pool's
        temp+rename is all-or-nothing: the final file exists at the
        submitted size iff the job succeeded) and commits fire only
        for verified jobs; append commits in an errored batch are
        dropped wholesale (a failed append can leave partial bytes a
        later append papers over, so per-range verification is
        unsound — the committed-prefix truncation heals them on
        resume).  An uncommitted-but-written artifact is rolled back
        and regenerated on resume; a committed-but-failed one would be
        silent loss — every ambiguity errs on the recoverable side.

        ``pre_publish`` (the manifest's publish barrier,
        ``RunManifest.sync``) runs between the worker's temp write and
        its atomic rename on the Python pool; the native C++ pool
        renames in C++, so the barrier runs AT SUBMIT instead — the
        intent is durable before the job exists.

        ``timer`` / ``trace_id`` (the submitting pipeline's StageTimer
        and the segment's causal id) go to the span ``file`` that the
        Python pool's thread opens around the job's open, write, flush,
        fdatasync and rename.  The native pool's threads open no span:
        they add their seconds to a counter, and ``stats()`` gives the
        sum under ``file_seconds`` for either pool."""
        if append and self.n_threads > 1:
            raise ValueError(
                "append=True needs n_threads=1 (ordered appends)")
        buf = np.ascontiguousarray(data).view(np.uint8).reshape(-1) \
            if isinstance(data, np.ndarray) else \
            np.frombuffer(bytes(data), dtype=np.uint8)
        if self._h is not None:
            if pre_publish is not None:
                pre_publish()
            ptr = buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
            rc = self._lib.srtb_writer_submit(
                self._h, path.encode(), ptr, buf.size,
                1 if fsync else 0, 1 if append else 0)
            if rc != 0:
                raise RuntimeError(f"srtb_writer_submit failed for {path}")
            if on_done is not None:
                with self._lock:
                    self._pending_done.append(
                        (on_done, path, int(buf.size), append))
            return
        payload = buf.tobytes()  # copy-at-submit, like the native pool
        with self._space:
            # backpressure: bound the RAM held by queued copies (oversized
            # payloads wait for an empty queue)
            if self.max_queued_bytes > 0:
                self._space.wait_for(
                    lambda: (self._queued_bytes + len(payload)
                             <= self.max_queued_bytes)
                    or self._queued_bytes == 0)
            self._queued_bytes += len(payload)
            # prune cleanly-completed futures so a long checkpoint-less run
            # doesn't accumulate them until the final drain; keep failed
            # ones so drain() can still surface their exception
            self._futures = [f for f in self._futures
                             if not f.done() or f.exception() is not None]
            fut = self._pool.submit(self._py_write, path, payload, fsync,
                                    append, on_done, pre_publish,
                                    span("file", timer, trace_id))
            self._futures.append(fut)

    def _py_write(self, path: str, payload: bytes, fsync: bool,
                  append: bool, on_done, pre_publish, file_span) -> None:
        # accounting must run for ANY exception type, or the backpressure
        # window shrinks permanently and later submits block forever
        ok = False
        try:
            with file_span:
                if append:
                    with open(path, "ab") as f:
                        f.write(payload)
                        f.flush()
                        if fsync:
                            os.fdatasync(f.fileno())
                else:
                    # crash-consistent like the synchronous writer path
                    # (shared helper: temp + flush (+ fdatasync) + atomic
                    # rename, torn temp dropped on failure) so a worker
                    # dying mid-write leaves an orphan temp (swept at
                    # startup by io.writers.recover_orphan_temps), not a
                    # torn file.  Appends stay in-place by nature.
                    from srtb_tpu.io.writers import atomic_write
                    atomic_write(path, payload, fsync=fsync,
                                 pre_rename=pre_publish)
            # manifest commit, only once the bytes durably landed; a
            # failing commit (the WAL append itself errored) leaves
            # the artifact uncommitted — rolled back + regenerated on
            # resume, never silently trusted
            if on_done is not None:
                on_done()
            ok = True
        except OSError:
            # counted below; surfaced via raise_new_errors().  Anything
            # non-OSError (MemoryError, a bad payload) propagates to
            # the future instead.
            pass
        finally:
            with self._space:
                self._py_jobs += 1
                self._py_file_s += file_span.seconds
                if ok:
                    self._py_bytes += len(payload)
                else:
                    self._py_errors += 1
                self._queued_bytes -= len(payload)
                self._space.notify_all()

    # ------------------------------------------------------------------

    def drain(self) -> None:
        """Block until every submitted job has been written (or failed)."""
        if self._h is not None:
            self._lib.srtb_writer_drain(self._h)
            with self._lock:
                pending, self._pending_done = self._pending_done, []
                errors = int(self._lib.srtb_writer_errors(self._h))
                base, self._done_err_base = self._done_err_base, errors
            if pending:
                if errors > base:
                    # per-job attribution through the filesystem (see
                    # submit): atomic jobs verify final-file size,
                    # append commits drop wholesale
                    fired = dropped = 0
                    for cb, path, size, append in pending:
                        ok = False
                        if not append:
                            try:
                                ok = os.path.getsize(path) == size
                            except OSError:
                                ok = False
                        if ok:
                            cb()
                            fired += 1
                        else:
                            dropped += 1
                    log.warning(
                        f"[writer_pool] {errors - base} native write "
                        f"error(s) in this drain: {fired} commit(s) "
                        f"verified on disk, {dropped} dropped "
                        "(uncommitted artifacts regenerate on resume)")
                else:
                    for cb, _path, _size, _append in pending:
                        cb()
            return
        with self._lock:
            futures, self._futures = self._futures, []
        for fut in futures:
            fut.result()

    def raise_new_errors(self, context: str) -> None:
        """Raise if writes failed since the last call.  The counter is
        pool-wide: with several sinks sharing one pool, whichever drains
        first reports the failure (with its own context string)."""
        errors = self.stats()["errors"]
        new_errors = errors - self._errors_raised
        self._errors_raised = errors
        if new_errors:
            raise RuntimeError(
                f"{new_errors} async write(s) failed ({context})")

    def stats(self) -> dict:
        if self._h is not None:
            return {
                "jobs_done": self._lib.srtb_writer_jobs_done(self._h),
                "bytes_written": self._lib.srtb_writer_bytes_written(self._h),
                "errors": self._lib.srtb_writer_errors(self._h),
                "file_seconds":
                    self._lib.srtb_writer_write_ns(self._h) * 1e-9,
            }
        with self._lock:
            return {"jobs_done": self._py_jobs,
                    "bytes_written": self._py_bytes,
                    "errors": self._py_errors,
                    "file_seconds": self._py_file_s}

    def close(self, drain: bool = True) -> None:
        """``drain=False`` abandons queued/stuck writes instead of
        waiting for them: the bounded-shutdown path uses it when a
        writer is known-wedged (e.g. an NFS-stalled write) — waiting
        would hang exactly the shutdown the caller just bounded.  The
        native pool is deliberately leaked in that case (its destroy
        joins the stuck C++ threads); the Python pool's workers are
        left to die with the process."""
        if self._h is not None:
            if drain:
                if self._pending_done:
                    self.drain()  # fire deferred manifest commits
                self._finalizer()  # idempotent drain + destroy
            else:
                self._finalizer.detach()
                log.warning("[writer_pool] abandoning native pool "
                            "without drain (wedged writes)")
            self._h = None
        elif self._pool is not None:
            if drain:
                self.drain()
                self._finalizer()  # idempotent sentinel + join
            else:
                # cancel still-queued jobs (idle workers exit on the
                # sentinel) and let the DAEMON workers die with the
                # process: a wedged write must not hang the very
                # shutdown this path exists to bound
                self._finalizer.detach()
                self._pool.shutdown(wait=False, cancel_futures=True)
                log.warning("[writer_pool] abandoning queued writes "
                            "without drain (wedged writes)")
            self._pool = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
