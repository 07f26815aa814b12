"""Output writers: candidate capture (.bin/.npy/.tim), write-all mode, and
the sigproc filterbank header.

File formats are byte-compatible with the reference so its offline plot
helpers (src/plot_spectrum.py, plot_tim.py) work unmodified:
- ``<prefix><counter>.bin``      raw baseband bytes of the segment
  (ref: write_signal_pipe.hpp:159-206);
- ``<prefix><counter>.<i>.npy``  complex64 spectrum waterfall, shape
  [freq_bins, time_samples] (ref: write_signal_pipe.hpp:209-246);
- ``<prefix><counter>.<boxcar>.tim``  raw float32 time series
  (ref: write_signal_pipe.hpp:249-280); batched multi-polarization
  results add a stream index: ``<prefix><counter>.s<stream>.<boxcar>.tim``
  (no reference equivalent — its streams are separate work items);
- the "piggybank" logic keeps recent negatives and writes them when they
  overlap (within 0.45 segment) a recent positive in another polarization
  (ref: write_signal_pipe.hpp:77-140).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
import zlib
from collections import deque
from dataclasses import dataclass

import numpy as np

from srtb_tpu.config import Config
from srtb_tpu.pipeline.work import (NO_UDP_PACKET_COUNTER, SegmentResultWork)
from srtb_tpu.utils.logging import log
from srtb_tpu.utils.metrics import metrics
from srtb_tpu.utils.tracing import span

# crash consistency: candidate files are written to <path>.srtb_tmp
# and atomically renamed into place, so a reader (or a restarted run)
# never sees a torn half-written candidate; a crash between write and
# rename leaves only an orphan temp, removed by the startup sweep
TMP_SUFFIX = ".srtb_tmp"


def recover_orphan_temps(prefix: str,
                         min_age_s: float = 60.0) -> list[str]:
    """Startup recovery sweep: remove ``<prefix>*.srtb_tmp`` orphans
    left by a run that died between a temp write and its atomic
    rename.  Returns the removed paths; every removal is counted
    (``orphan_temps_removed``) and logged — an interrupted dump is a
    data-loss event, not housekeeping.

    Only temps whose mtime is older than ``min_age_s`` are swept: a
    fresh temp may belong to a LIVE writer sharing the output prefix
    (a concurrent pipeline process, or the previous run's async pool
    still flushing), and unlinking it mid-write would turn that
    healthy atomic write into a failure.  A true orphan missed by the
    age guard (crash + restart within the window) is swept on the
    next startup and is harmless meanwhile."""
    d = os.path.dirname(prefix) or "."
    base = os.path.basename(prefix)
    removed = []
    try:
        names = os.listdir(d)
    except OSError:
        return removed
    now = time.time()
    for name in names:
        if name.startswith(base) and name.endswith(TMP_SUFFIX):
            p = os.path.join(d, name)
            try:
                if now - os.path.getmtime(p) < min_age_s:
                    log.warning(f"[recover] leaving fresh temp {p} "
                                "(possibly a live writer's)")
                    continue
                os.unlink(p)
                removed.append(p)
            except OSError as e:
                log.warning(f"[recover] cannot remove orphan {p}: {e}")
    if removed:
        metrics.add("orphan_temps_removed", len(removed))
        log.warning(f"[recover] removed {len(removed)} orphaned temp "
                    f"file(s) from an interrupted run: "
                    f"{[os.path.basename(p) for p in removed]}")
    return removed


def fsync_dir(path: str) -> None:
    """fsync the PARENT DIRECTORY of ``path``: an ``os.replace`` makes
    the rename atomic but not durable — the directory entry itself can
    vanish on power loss until the directory inode is synced.  Best
    effort: filesystems that refuse directory fds (some network
    mounts) degrade to the rename-only guarantee."""
    d = os.path.dirname(path) or "."
    try:
        fd = os.open(d, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    except OSError as e:
        log.debug(f"[writers] cannot open dir {d} for fsync: {e}")
        return
    try:
        os.fsync(fd)
    except OSError as e:
        log.debug(f"[writers] dir fsync of {d} failed: {e}")
    finally:
        os.close(fd)


# crash-window steering hook for the durability harnesses
# (tools/crash_soak.py, tests/test_durability.py): when set, called
# with the destination path after the temp write and BEFORE the atomic
# rename — a SIGKILL landing inside the hook is a deterministic
# mid-rename crash.  None in production (one global read per write).
_PRE_RENAME_HOOK = None


def atomic_write(path: str, payload, *, fsync: bool = False,
                 pre_rename=None) -> None:
    """Crash-consistent write: temp + flush (+ optional fdatasync) +
    atomic rename (+ parent-directory fsync, so the rename survives
    power loss — opt out via the same ``fsync`` knob).  A crash
    mid-write leaves only the orphan temp for the startup sweep; a
    *failed* write from a live run drops its temp so it cannot read as
    an interrupted-run orphan next startup.  The native C++ pool
    implements the same sequence with the same suffix
    (native/file_writer.cpp).

    ``pre_rename`` is the manifest's publish barrier
    (``RunManifest.sync``): invoked between the temp write and the
    rename, so no artifact reaches its final name before the WAL
    durably holds its intent."""
    tmp = path + TMP_SUFFIX
    try:
        with open(tmp, "wb") as f:
            f.write(payload)
            f.flush()
            if fsync:
                os.fdatasync(f.fileno())
        if pre_rename is not None:
            pre_rename()
        if _PRE_RENAME_HOOK is not None:
            _PRE_RENAME_HOOK(path)
        os.replace(tmp, path)
        if fsync:
            fsync_dir(path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass  # never created, or the disk is truly gone
        raise


def manifest_stage(manifest, key, path: str, data: np.ndarray):
    """Stage one atomic artifact write against the run manifest: log
    the intent NOW — before any byte reaches the temp file — and
    return the commit callback to fire once the atomic rename has
    published the artifact (synchronously, or from a writer-pool
    thread via ``AsyncWriterPool.submit(on_done=...)``).  The intent
    append is buffered; the durability point is the PUBLISH BARRIER
    (``manifest.sync``), which the writer runs between the temp write
    and the rename — see io/manifest.py.  None when no manifest is
    bound (zero cost)."""
    if manifest is None or key is None:
        return None
    buf = np.ascontiguousarray(data)
    length = int(buf.nbytes)
    # content CRC is the deep fsck check, ~1 ms per dumped MB;
    # Config.manifest_hash=0 drops to existence+size verification
    crc = zlib.crc32(buf) if getattr(manifest, "hash_content", True) \
        else None
    manifest.intent(key, path)

    def commit():
        manifest.commit(key, path, length, crc)

    return commit


def stage_write(path: str, payload, *, fsync: bool = False) -> str:
    """First half of :func:`atomic_write`: write the temp (+ optional
    fdatasync) WITHOUT publishing it.  Returns the temp path; the
    caller renames after its publish barrier — letting one barrier
    cover a whole segment's artifacts (see
    ``WriteSignalSink._publish_staged``)."""
    tmp = path + TMP_SUFFIX
    try:
        with open(tmp, "wb") as f:
            f.write(payload)
            f.flush()
            if fsync:
                os.fdatasync(f.fileno())
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass  # never created, or the disk is truly gone
        raise
    return tmp


def _npy_bytes(arr: np.ndarray) -> np.ndarray:
    """Serialize an array in .npy format to a uint8 buffer (cnpy analog —
    the reference writes .npy via cnpy, write_signal_pipe.hpp:243-244)."""
    import io as _io
    bio = _io.BytesIO()
    np.save(bio, arr)
    return np.frombuffer(bio.getvalue(), dtype=np.uint8)


def _payload(data: np.ndarray):
    """What ``file.write`` takes of an array without a copy of it (a
    4.29 GB waterfall's ``tobytes()`` is one more 4.29 GB on the host)."""
    return memoryview(np.ascontiguousarray(data)).cast("B")


# the stacked waterfall crosses to the host this many bytes of rows at
# a time
NPY_BLOCK_BYTES = 1 << 26


def _npy_complex64_by_blocks(planes) -> list:
    """``_npy_bytes(re + 1j * im)`` as complex64, byte for byte, of each
    stream of the stacked waterfall ``planes`` ``[2, S, F, T]`` wherever
    it lives: ONE buffer of the file's size a stream, the ``.npy``
    header and then the values, written in place from blocks of rows
    fetched on their own (``utils/platform.to_host_rows``).  Neither
    the whole host copy of the planes nor ``re + 1j * im``, its cast
    and the stream's copy exist: those were 6.5 times the file, 27 GB
    for the 4.29 GB waterfall of a 2^30-sample segment, beside whatever
    else the host holds.  A small waterfall is one block."""
    import io as _io

    from srtb_tpu.utils.platform import to_host_rows
    _two, streams, rows, cols = planes.shape
    fmt = np.lib.format
    head = _io.BytesIO()
    fmt.write_array_header_1_0(head, {
        "descr": fmt.dtype_to_descr(np.dtype(np.complex64)),
        "fortran_order": False, "shape": (rows, cols)})
    header = np.frombuffer(head.getvalue(), dtype=np.uint8)
    files = []
    for _ in range(streams):
        buf = np.empty(header.size + 8 * rows * cols, dtype=np.uint8)
        buf[:header.size] = header
        files.append(buf)
    bodies = [buf[header.size:].view(np.complex64).reshape(rows, cols)
              for buf in files]
    step = max(1, NPY_BLOCK_BYTES // (8 * streams * cols))
    for lo in range(0, rows, step):
        block = to_host_rows(planes, lo, min(rows, lo + step))
        for s, body in enumerate(bodies):
            body[lo:lo + step].real = block[0, s]
            body[lo:lo + step].imag = block[1, s]
    return files


@dataclass
class CandidateFiles:
    """Paths written for one positive segment."""
    bin_path: str
    npy_paths: list
    tim_paths: list
    # periodicity mode only: <base>[.sN].fold.npy folded profiles +
    # <base>[.sN].cand.json candidate metadata
    fold_paths: list = dataclasses.field(default_factory=list)


class WriteSignalSink:
    """Candidate writer with the reference's piggybank capture policy.

    When ``writer_pool`` (an :class:`AsyncWriterPool`) is given, file
    writes are queued to its (native C++) thread pool and this sink never
    blocks on disk — the reference's async thread-pool behavior
    (write_signal_pipe.hpp:159-206 submits to boost thread pools).  Call
    ``drain()`` before reading the files back.
    """

    # degradation ladder level >= 2 skips this sink entirely (shed
    # baseband/candidate dumps before shedding whole segments)
    sheddable = True

    def __init__(self, cfg: Config, fdatasync: bool = True,
                 writer_pool=None):
        self.cfg = cfg
        self.fdatasync = fdatasync
        self.pool = writer_pool
        self._assigned_paths: set[str] = set()
        self.recent_positive_timestamps: deque[int] = deque()
        self.recent_negative_works: deque[SegmentResultWork] = deque()
        self.written: list[CandidateFiles] = []
        # retry re-entry state (see push/_write): the pipeline's
        # sink_write retry calls push() again after a transient
        # mid-write failure, and the replay must be idempotent — no
        # duplicated deque entries, and the partially written segment
        # keeps its already-picked .npy paths instead of spilling the
        # same waterfall under fresh indices.  Keyed on the SEGMENT
        # (identity + metadata): each retry attempt wraps it in a
        # fresh SegmentResultWork (runtime._push_sinks), so the work
        # object itself is not stable across attempts
        self._inflight_key: tuple | None = None
        self._inflight_npy: dict[int, str] = {}
        # durable exactly-once (io/manifest.py): when bound, every
        # artifact logs intent before its temp write and commit after
        # the atomic rename; the runtime sets the (stream, seg, sink)
        # key per push.  None = manifest off, zero cost.
        self.manifest = None
        self._manifest_key = None
        # segment-transaction staging (synchronous path only): with a
        # manifest bound, one segment's artifacts are temp-written
        # first, then published together behind ONE publish barrier —
        # one fdatasync per segment instead of one per artifact.  None
        # when no transaction is open.
        self._tx_staged = None
        # whether the LAST push wrote any artifact: the runtime skips
        # the durable done record for empty pushes (a replayed
        # negative segment recomputes the same decision and writes
        # nothing — nothing to protect, and the common all-negative
        # observation keeps its WAL one record per segment)
        self.last_push_wrote = False
        # the candidate path's spans (utils/tracing.span): the
        # pipeline binds its StageTimer and takes, after each push,
        # the seconds this sink spent in ``d2h`` / ``write`` /
        # ``publish`` for the segment's journal record, and inside
        # ``write`` in its children: ``format`` (making each payload),
        # then with a pool ``submit`` (the pool's copy of the payload
        # and its wait for queue space) and ``drain`` (the wait for the
        # pool's threads), without one ``file`` (temp, write, flush,
        # fdatasync, rename: the pool's threads open the same span
        # where they write).  A quiet push opens none
        self.stage_timer = None
        self._spans: dict[str, float] = {}
        self._span_tid = 0
        # bytes this push handed to the writers, and the pool's summed
        # ``file`` seconds when it began to (take_candidate)
        self._candidate_bytes = 0
        self._file_s_mark = 0.0
        # ... and the seconds this thread wrote files itself
        self._own_file_s = 0.0
        # check directory writability up front (ref: write_signal_pipe.hpp:62-75)
        check_path = cfg.baseband_output_file_prefix + ".check"
        with open(check_path, "wb"):
            pass
        os.unlink(check_path)

    # ------------------------------------------------------------------

    def bind_manifest(self, manifest) -> None:
        self.manifest = manifest

    def set_manifest_key(self, key) -> None:
        self._manifest_key = key

    def bind_stage_timer(self, timer) -> None:
        self.stage_timer = timer

    @contextlib.contextmanager
    def _span(self, name: str):
        with span(name, self.stage_timer, self._span_tid) as sp:
            yield
        self._spans[name] = self._spans.get(name, 0.0) + sp.seconds

    def take_spans(self) -> dict:
        """Seconds per candidate-path stage since the last call."""
        spans, self._spans = self._spans, {}
        return spans

    def take_candidate(self) -> dict:
        """For the dumping segment's journal record, beside its
        ``stages_ms``: ``candidate_bytes``, the bytes this push handed
        to the writers, and with a pool ``writer_file_ms``, the seconds
        its threads spent writing files since this push began to hand
        them over, summed over the threads (concurrent with the
        segment's stages, like ``device_ms``; complete where something
        drained the pool before the record was taken, else what has
        landed so far), and the seconds this thread wrote in the pool's
        stead (a payload over the pool's bound: ``_write_bytes``).  {}
        after a quiet push."""
        if not self._candidate_bytes:
            return {}
        out = {"candidate_bytes": self._candidate_bytes}
        self._candidate_bytes = 0
        own, self._own_file_s = self._own_file_s, 0.0
        if self.pool is not None:
            out["writer_file_ms"] = 1e3 * (
                self.pool.stats()["file_seconds"] - self._file_s_mark
                + own)
        return out

    # ------------------------------------------------------------------

    def _overlap_window_ns(self) -> float:
        # 0.45 of a segment duration, in ns (ref: write_signal_pipe.hpp:84-86)
        return (0.45 * 1e9 * self.cfg.baseband_input_count
                / self.cfg.baseband_sample_rate)

    def _overlaps_recent_positive(self, timestamp: int) -> bool:
        w = self._overlap_window_ns()
        return any(abs(timestamp - t) < w
                   for t in self.recent_positive_timestamps)

    def push(self, work: SegmentResultWork, has_signal: bool) -> None:
        """Feed one processed segment; writes to disk when warranted."""
        self.last_push_wrote = False
        # a wait that a drain outside any push left behind (the
        # checkpoint's flush) is in the timer, not in a later record
        self._spans = {}
        self._candidate_bytes = 0
        self._span_tid = getattr(work.segment, "trace_id", 0)
        real_time = self.cfg.input_file_path == ""
        w = self._overlap_window_ns()
        ts = work.segment.timestamp

        # clean outdated positives (ref: write_signal_pipe.hpp:88-94)
        while (real_time and self.recent_positive_timestamps
               and ts - self.recent_positive_timestamps[0] > 5 * w):
            self.recent_positive_timestamps.popleft()

        to_write = None
        if has_signal:
            # idempotent under retry re-entry: the same segment pushed
            # again (transient failure later in this push) must not
            # stamp the overlap window twice
            if not self.recent_positive_timestamps \
                    or self.recent_positive_timestamps[-1] != ts:
                self.recent_positive_timestamps.append(ts)
            to_write = work
        elif real_time and self._overlaps_recent_positive(ts):
            # other-polarization piggyback (ref: write_signal_pipe.hpp:102-115)
            to_write = work
        elif real_time:
            # segment identity, not work identity: a pipeline retry
            # re-enters with a fresh SegmentResultWork around the SAME
            # segment, and the piggyback deque must not hold it twice
            if not self.recent_negative_works \
                    or self.recent_negative_works[-1].segment \
                    is not work.segment:
                self.recent_negative_works.append(work)

        # re-check old negatives against new positives (ref: 122-140).
        # Peek, don't pop: a transient _write failure re-enters this
        # push via the pipeline's sink_write retry, and a popped-but-
        # unwritten piggyback candidate would be silently lost (the
        # retry would pop — and mis-schedule — the NEXT negative)
        popped_negative = False
        if real_time and to_write is None and self.recent_negative_works:
            work_2 = self.recent_negative_works[0]
            if self._overlaps_recent_positive(work_2.segment.timestamp):
                to_write = work_2
                popped_negative = True
            else:
                self.recent_negative_works.popleft()

        if to_write is not None:
            self._write(to_write)
            if popped_negative:
                self.recent_negative_works.popleft()

        # bound the negative queue (the reference relies on deque churn; we
        # cap explicitly to one overlap window's worth of segments)
        while len(self.recent_negative_works) > 16:
            self.recent_negative_works.popleft()

    # ------------------------------------------------------------------

    def _write(self, work: SegmentResultWork) -> None:
        counter = work.segment.udp_packet_counter
        if counter == NO_UDP_PACKET_COUNTER:
            counter = work.segment.timestamp
        base = self.cfg.baseband_output_file_prefix + str(counter)
        # a retry of this same segment (transient failure partway
        # through) must reuse the .npy paths the first attempt picked
        # — the find-first-free scan below would otherwise see its own
        # partial output and assign the same waterfall a fresh index.
        # The key is the segment's identity + metadata (each retry
        # attempt builds a fresh work wrapper; the metadata guards the
        # freak case of a recycled id after an abandoned failure)
        key = (id(work.segment), work.segment.timestamp,
               work.segment.udp_packet_counter)
        if self._inflight_key != key:
            self._inflight_key = key
            self._inflight_npy = {}
        self.last_push_wrote = True
        log.info(f"[write_signal] begin writing, file_counter = {counter}")
        if self.pool is not None:
            self._file_s_mark = self.pool.stats()["file_seconds"]

        # open the segment transaction: synchronous manifest-armed
        # writes stage temps and publish together after one barrier
        # (the pool path self-batches worker-side instead)
        if self.manifest is not None and self._manifest_key is not None \
                and self.pool is None:
            self._tx_staged = []
        try:
            wf = None
            if work.waterfall is not None:
                # the waterfall may still be device-resident (lazy
                # sink-side transfer): fetch via the explicit D2H
                # spelling so the sanitizer's transfer tripwire stays
                # quiet on this sanctioned sync
                from srtb_tpu.utils.platform import to_host
                with self._span("d2h"):
                    # the stacked (re, im) boundary representation
                    # [2, S, F, T] arrives as each stream's file, made
                    # as its blocks of rows are fetched
                    wf = _npy_complex64_by_blocks(work.waterfall) \
                        if len(work.waterfall.shape) == 4 \
                        else to_host(work.waterfall)
            with self._span("write"):
                self._write_artifacts(work, base, wf)
            with self._span("publish"):
                self._publish_staged()
        except BaseException:
            self._tx_abort()
            raise
        # completed: the next _write (even for a same-counter
        # piggyback) must pick fresh indices, not reuse these
        self._inflight_key = None
        self._inflight_npy = {}
        log.info(f"[write_signal] finished writing, file_counter = {counter}")

    def _write_artifacts(self, work: SegmentResultWork, base: str,
                         wf: "np.ndarray | list | None") -> None:
        """``wf``: the segment's waterfall already on the host (a list:
        each stream's ``.npy`` file as ``_npy_complex64_by_blocks`` made
        it), or None where the segment has none."""
        bin_path = base + ".bin"
        with self._span("format"):
            payload = np.ascontiguousarray(work.segment.data)
        self._write_bytes(bin_path, payload, fsync=self.fdatasync)

        npy_paths = []
        if wf is not None:
            files = isinstance(wf, list)
            if not files and wf.ndim == 2:
                wf = wf[None]
            for i in range(len(wf)):
                path = self._inflight_npy.get(i)
                if path is None:
                    # pick first non-existing index (ref: 230-235);
                    # with an async pool queued-but-unwritten paths
                    # count as taken, as do staged-but-unpublished
                    # ones inside the open segment transaction
                    staged_paths = {p for p, *_ in self._tx_staged} \
                        if self._tx_staged else set()
                    j = i
                    while (os.path.exists(f"{base}.{j}.npy")
                           or f"{base}.{j}.npy" in self._assigned_paths
                           or f"{base}.{j}.npy" in staged_paths):
                        j += 1
                    path = f"{base}.{j}.npy"
                    self._inflight_npy[i] = path
                with self._span("format"):
                    payload = wf[i] if files \
                        else _npy_bytes(wf[i].astype(np.complex64))
                self._write_bytes(path, payload)
                npy_paths.append(path)

        tim_paths = []
        if work.detect is not None:
            counts = np.asarray(work.detect.signal_counts)
            series = np.asarray(work.detect.boxcar_series)
            if counts.ndim == 1:
                counts = counts[None]
                series = series[None]
            lengths = work.detect.boxcar_lengths
            multi = counts.shape[0] > 1
            for s in range(counts.shape[0]):
                for bi, b in enumerate(lengths):
                    if counts[s, bi] > 0:
                        # single-stream keeps the reference's exact name;
                        # batched multi-polarization results need a stream
                        # index or the streams would overwrite each other
                        path = (f"{base}.s{s}.{b}.tim" if multi
                                else f"{base}.{b}.tim")
                        valid = series.shape[-1] - (b if b > 1 else 0)
                        with self._span("format"):
                            payload = series[s, bi, :valid].astype("<f4")
                        self._write_bytes(path, payload)
                        tim_paths.append(path)

        # registered-mode hook (the registry contract): a detect
        # result carrying its own extra artifacts (e.g. the
        # periodicity mode's folded profiles + candidate table,
        # pipeline/periodicity.py) hands (path, array) pairs here and
        # they ride the same temp+rename(+manifest) machinery as
        # every other artifact — this writer stays mode-blind.
        fold_paths = []
        extra = (getattr(work.detect, "extra_artifacts", None)
                 if work.detect is not None else None)
        if extra is not None:
            for path, payload in extra(base):
                if path.endswith(".npy"):
                    with self._span("format"):
                        payload = _npy_bytes(payload)
                self._write_bytes(path, payload)
                fold_paths.append(path)

        self.written.append(CandidateFiles(bin_path, npy_paths,
                                           tim_paths, fold_paths))

    def _publish_staged(self) -> None:
        """Close the segment transaction: ONE publish barrier (all
        pending intents durable), then rename + commit every staged
        artifact.  A crash before the barrier leaves only temps
        (rolled back); between barrier and a rename, temps with
        durable intents (rolled back); after a rename, a committed or
        regenerable artifact — never an untracked final file."""
        staged, self._tx_staged = self._tx_staged, None
        if not staged:
            return
        self.manifest.sync()
        try:
            for path, tmp, fsync, commit in staged:
                if _PRE_RENAME_HOOK is not None:
                    _PRE_RENAME_HOOK(path)
                os.replace(tmp, path)
                if fsync:
                    fsync_dir(path)
                if commit is not None:
                    commit()
        except BaseException:
            for _path, tmp, _fsync, _commit in staged:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass  # already renamed, or the disk is truly gone
            raise

    def _tx_abort(self) -> None:
        staged, self._tx_staged = self._tx_staged, None
        for _path, tmp, _fsync, _commit in staged or ():
            try:
                os.unlink(tmp)
            except OSError:
                pass  # this artifact never reached its temp write

    def _write_bytes(self, path: str, data: np.ndarray, *,
                     fsync: bool = False) -> None:
        self._candidate_bytes += int(data.nbytes)
        commit = manifest_stage(self.manifest, self._manifest_key,
                                path, data)
        barrier = self.manifest.sync if commit is not None else None
        if self._tx_staged is not None:
            with self._span("file"):
                tmp = stage_write(path, _payload(data), fsync=fsync)
            self._tx_staged.append((path, tmp, fsync, commit))
            return
        pool = self.pool
        if pool is not None and 0 < pool.max_queued_bytes < data.nbytes:
            # a payload over the pool's whole bound on queued copies
            # (the 4.29 GB waterfall of a 2^30-sample segment, where the
            # bound is 1 GiB) is not queued: the pool's copy would be a
            # second 4.29 GB beside the file's bytes and the fetched
            # planes, which with a 1 GiB ``.bin`` and the float64
            # reference beside them ended a run on a 40 GiB host.  This
            # thread writes it, as without a pool
            pool = None
        if pool is not None:
            if path in self._assigned_paths:
                # same target queued again (e.g. a piggybacked segment
                # sharing a packet counter): flush first so the later
                # write deterministically wins instead of racing
                with self._span("drain"):
                    pool.drain()
                self._assigned_paths.clear()
            self._assigned_paths.add(path)
            with self._span("submit"):
                pool.submit(path, data, fsync=fsync, on_done=commit,
                            pre_publish=barrier, timer=self.stage_timer,
                            trace_id=self._span_tid)
            return
        # crash-consistent: a crash mid-write leaves an orphan temp
        # (swept at startup), never a torn candidate file
        t0 = time.perf_counter()
        with self._span("file"):
            atomic_write(path, _payload(data), fsync=fsync,
                         pre_rename=barrier)
        self._own_file_s += time.perf_counter() - t0
        if commit is not None:
            commit()

    def drain(self) -> None:
        """Wait for queued async writes to land (no-op when synchronous).

        Raises ``RuntimeError`` if any queued write failed — the
        synchronous path would have raised at the failing ``open``/
        ``write``, and a silently lost candidate defeats the writer's
        purpose.
        """
        if self.pool is not None:
            # the wait for the pool's writers is the candidate's
            # write time too (its child ``drain``): it goes to the
            # segment whose push (or a later sink of the same push)
            # drains.  Nothing queued, no span: quiet segments pay
            # nothing
            if self._assigned_paths:
                with self._span("write"), self._span("drain"):
                    self.pool.drain()
            else:
                self.pool.drain()
            self._assigned_paths.clear()
            self.pool.raise_new_errors(
                f"candidate prefix {self.cfg.baseband_output_file_prefix}")


class WriteAllSink:
    """Unconditional append of baseband minus the reserved tail to one file
    per stream (ref: pipeline/write_file_pipe.hpp:41-94, selected when
    ``baseband_write_all``).

    Synchronous by default, as in the reference (the write happens inline
    in the pipe body).  Passing a **single-thread** ``writer_pool`` makes
    appends asynchronous while keeping their order.
    """

    sheddable = True  # degradation ladder: baseband dumps shed at L2
    last_push_wrote = True  # every push appends: always seal done
    # canary quarantine (pipeline/runtime._push_sinks): this sink
    # appends the PRISTINE seg.data — the injected pulse never reaches
    # it — and its output is a contiguous byte stream, so skipping a
    # canary segment would corrupt the append continuity, not protect
    # anything.  Science-product sinks (waterfall writers) stay
    # non-exempt and are skipped for canary segments.
    canary_exempt = True

    def __init__(self, cfg: Config, reserved_bytes: int,
                 data_stream_id: int = 0, writer_pool=None):
        self.reserved_bytes = reserved_bytes
        path = (cfg.baseband_output_file_prefix
                + f"stream{data_stream_id}.bin")
        self.path = path
        self.pool = writer_pool
        if writer_pool is not None and writer_pool.n_threads != 1:
            raise ValueError("WriteAllSink needs a 1-thread pool "
                             "(ordered appends)")
        self._f = None if writer_pool is not None else open(path, "ab")
        # durable exactly-once (io/manifest.py): appends log an intent
        # carrying the pre-append file length, so recovery can
        # truncate a torn append back to the committed prefix.
        # _append_off tracks the SUBMITTED length (appends are
        # ordered); the manifest's committed length only advances at
        # each commit record.
        self.manifest = None
        self._manifest_key = None
        self._append_off = 0

    def bind_manifest(self, manifest) -> None:
        self.manifest = manifest
        try:
            # manifest recovery already truncated any torn tail, so
            # the current size IS the durable committed prefix
            self._append_off = os.path.getsize(self.path)
        except OSError:
            self._append_off = 0

    def set_manifest_key(self, key) -> None:
        self._manifest_key = key

    def push(self, work: SegmentResultWork, has_signal: bool = False) -> None:
        data = work.segment.data
        end = len(data) - self.reserved_bytes
        if end <= 0:
            end = len(data)
        chunk = np.ascontiguousarray(data[:end])
        m, key = self.manifest, self._manifest_key
        commit = None
        if m is not None and key is not None:
            off = self._append_off
            length = int(chunk.nbytes)
            crc = zlib.crc32(chunk) \
                if getattr(m, "hash_content", True) else None
            m.intent(key, self.path, mode="append", offset=off)

            def commit(m=m, key=key, path=self.path, length=length,
                       crc=crc, off=off):
                m.commit(key, path, length, crc, offset=off)

            self._append_off = off + length
        if self.pool is not None:
            self.pool.submit(self.path, chunk, append=True,
                             on_done=commit)
            return
        self._f.write(chunk.tobytes())
        self._f.flush()
        if commit is not None:
            commit()

    def drain(self) -> None:
        if self.pool is not None:
            self.pool.drain()
            self.pool.raise_new_errors(f"append to {self.path}")

    def close(self):
        if self._f is not None:
            self._f.close()


# ----------------------------------------------------------------
# sigproc filterbank header (ref: io/sigproc_filterbank.hpp)
# ----------------------------------------------------------------

def _fb_string(key: str) -> bytes:
    b = key.encode()
    return np.int32(len(b)).tobytes() + b


def _fb_int(key: str, value: int) -> bytes:
    return _fb_string(key) + np.int32(value).tobytes()


def _fb_double(key: str, value: float) -> bytes:
    return _fb_string(key) + np.float64(value).tobytes()


def encode_angle_dms(d: int, m: int, s: float) -> float:
    """Pack degrees/minutes/seconds as ddmmss.s, the sigproc convention
    (ref: io/sigproc_filterbank.hpp:59-70)."""
    sign = -1.0 if d < 0 else 1.0
    return sign * (abs(d) * 10000.0 + m * 100.0 + s)


def write_filterbank_header(f, *, telescope_id: int = 0, machine_id: int = 0,
                            data_type: int = 1, fch1: float = 0.0,
                            foff: float = 0.0, nchans: int = 0,
                            tsamp: float = 0.0, nbits: int = 32,
                            nifs: int = 1, tstart: float = 0.0,
                            src_raj: float = 0.0, src_dej: float = 0.0,
                            source_name: str = "unknown") -> None:
    """Serialize a sigproc filterbank header (keys as in the reference's
    io/sigproc_filterbank.hpp writer)."""
    f.write(_fb_string("HEADER_START"))
    f.write(_fb_string("source_name"))
    f.write(_fb_string(source_name))
    f.write(_fb_int("telescope_id", telescope_id))
    f.write(_fb_int("machine_id", machine_id))
    f.write(_fb_int("data_type", data_type))
    f.write(_fb_double("fch1", fch1))
    f.write(_fb_double("foff", foff))
    f.write(_fb_int("nchans", nchans))
    f.write(_fb_int("nbits", nbits))
    f.write(_fb_double("tstart", tstart))
    f.write(_fb_double("tsamp", tsamp))
    f.write(_fb_int("nifs", nifs))
    f.write(_fb_double("src_raj", src_raj))
    f.write(_fb_double("src_dej", src_dej))
    f.write(_fb_string("HEADER_END"))
