"""Streaming runtime: reader -> device segment processor -> sinks.

The reference's thread-per-pipe/bounded-queue machinery
(ref: pipeline/framework/pipe.hpp, pipe_io.hpp) exists to overlap GPU
kernels of consecutive segments.  Under JAX, async dispatch provides the
device-side half for free; the host-side half is the **async in-flight
segment engine** in :meth:`Pipeline.run`:

- a bounded window of ``Config.inflight_segments`` segments is
  dispatched before the oldest result is drained, so segment k+1's
  ingest, sub-byte unpack, and H2D staging run while the device
  computes segment k (the double-buffer AstroAccelerate builds with
  CUDA streams, arXiv:2101.00941);
- where the source's pull is long enough to keep the device waiting,
  a reader stage pulls exactly one segment ahead on a thread of its
  own and the loop takes the ready segment (``Pipeline``'s docstring:
  who pulls, when, and what ``ingest`` / ``ingest_wait`` /
  ``ingest_ahead`` mean in a journal);
- fetch is non-blocking where possible: the drain loop polls device
  readiness (``jax.Array.is_ready``) and drains completed segments in
  order, blocking only when the window is full or the source is done;
- sink work (writers, lazy waterfall transfer, journal, checkpoint)
  runs on a dedicated framework Pipe, off the dispatch critical path;
- per segment, the wall clock between dispatch returning and fetch
  starting is journaled as ``overlap_hidden_ms`` (+ the ``overlap``
  stage histogram and the ``inflight_depth`` gauge), so overlap
  efficiency is measurable, not assumed;
- optional micro-batching (``Config.micro_batch_segments`` = B > 1)
  stacks B segments into ONE vmapped jit call, amortizing per-dispatch
  host overhead over B segments.

``inflight_segments = 1`` is the fully serial reference leg (ingest ->
dispatch -> blocking fetch -> sink per segment) used by the A/B
harness.  Work accounting (ref: main.cpp:146-162
work_in_pipeline_count) and orderly shutdown
(ref: framework/exit_handler.hpp) carry over from the reference.

Fault tolerance (srtb_tpu/resilience/, PR 4): six named fault sites —
``ingest``, ``h2d``, ``dispatch``, ``fetch``, ``sink_write``,
``checkpoint`` — run under a retry policy (transient failures back off
and re-run; fatal ones escalate), an in-flight segment whose fetch
never becomes ready within ``segment_deadline_s`` is cancelled and
re-dispatched by the watchdog (``segment_watchdog_requeues``), a
crashed sink pipe is restarted with a bounded budget
(``supervisor_max_restarts``), and sustained sink backlog walks the
graceful-degradation ladder (shed waterfall dumps, then baseband
dumps, then accounted whole-segment loss).  Every recovery is a
counter and a journal field; ``Config.fault_plan`` injects
deterministic faults at any site for CI.

Self-healing compute (resilience/demote.py, PR 9): failures the
accelerator side raises — device OOM, Pallas/Mosaic compile faults,
device halts — are classified from the real jax exception strings and
recovered instead of escalating: OOM/compile faults demote the plan
down an audited ladder (micro_batch -> ring -> skzap -> fused_tail ->
staged -> monolithic) and re-dispatch the faulted segment cold from
its retained host buffer; halts reinitialize the backend (clear
caches, rebuild the processor, re-dispatch the in-flight window)
under a bounded reinit budget; ``promote_after_segments`` probes back
up after a healthy stretch.  Counters: ``plan_demotions``,
``plan_promotions``, ``device_reinits``; gauge ``plan_ladder_level``;
journal field ``active_plan`` (schema v4).
"""

from __future__ import annotations

import collections
import functools
import itertools
import os
import statistics
import threading
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from srtb_tpu.config import Config
from srtb_tpu.io import formats
from srtb_tpu.io.file_input import BasebandFileReader
from srtb_tpu.io.writers import WriteAllSink, WriteSignalSink
from srtb_tpu.pipeline.segment import SegmentProcessor
from srtb_tpu.pipeline.work import SegmentResultWork, SegmentWork
from srtb_tpu.resilience.errors import DEVICE_HALT, WatchdogEscalation
from srtb_tpu.resilience.faults import FaultInjector
from srtb_tpu.resilience.retry import RetryPolicy, retry_call
from srtb_tpu.utils import events, slo, telemetry
from srtb_tpu.utils.logging import log
from srtb_tpu.utils.metrics import metrics
from srtb_tpu.utils.tracing import StageTimer, span as stage_span


# the served loop's reader pulls one segment ahead, on a thread of its
# own, while the source's pull takes more than this share of the period
# between the loop's takes (``Pipeline._run_engine``).  Read on the
# chip (PERF.md section 6, PR 45): the cells whose chip waited for the
# pull stand at 0.59-0.96, the cells paced by the chip at 0.19-0.37,
# where pulling a step early would only add a step to every segment's
# latency.  Both are medians over the last ``_PACE_SAMPLES`` segments:
# a replay's cold pass is one pull in six or eight.
_PULL_AHEAD_SHARE = 0.5
_PACE_SAMPLES = 8


def _metrics_stage_timer() -> StageTimer:
    """A StageTimer whose every completed timing also lands in a bounded
    histogram, so /metrics carries live p50/p95/p99 per stage."""
    return StageTimer(
        on_stage=lambda name, dt: metrics.histogram(
            "stage_seconds", labels={"stage": name}).observe(dt))


def _under_construct_span(init):
    """An ``__init__`` that runs whole under the span ``construct`` of a
    timer made first (``self.stage_timer``: every completed timing also
    lands in a bounded histogram, so /metrics carries live p50/p95/p99
    per stage), so what the constructor builds can open spans of its
    own inside it: the processor's ``chirp_bank``."""
    @functools.wraps(init)
    def construct(self, *args, **kwargs):
        self.stage_timer = _metrics_stage_timer()
        self._setup_logged = False
        with stage_span("construct", self.stage_timer):
            init(self, *args, **kwargs)
    return construct


def _log_setup_once(pipe) -> None:
    """At the end of the first ``run()``, ``[setup] construct X s
    (chirp_bank Y s); first dispatches: ring_cold A s, ring B s``: the
    line an operator reads after a restart to see which program
    recompiled.  The seconds are the timer's ``construct`` /
    ``chirp_bank`` spans and the processor's books of its programs'
    first dispatches; they add up to the journal's ``compile_ms`` where
    one processor served the run."""
    if pipe._setup_logged:
        return
    pipe._setup_logged = True
    timer = pipe.stage_timer
    bank = timer.totals.get("chirp_bank")
    first = getattr(pipe.processor, "first_dispatch_s", {})
    log.info(
        f"[setup] construct {timer.totals.get('construct', 0.0):.2f} s ("
        + ("no chirp_bank: the plan makes its chirp in the step"
           if bank is None else f"chirp_bank {bank:.2f} s")
        + "); first dispatches: "
        + (", ".join(f"{k} {v:.2f} s" for k, v in first.items())
           or "none"))


def _stamp_trace_id(seg) -> int:
    """The segment's causal trace id, stamped at its birth (a source
    that pre-stamped its own keeps it)."""
    tid = getattr(seg, "trace_id", 0)
    if not tid:
        tid = events.next_trace_id()
        try:
            seg.trace_id = tid
        except AttributeError:  # read-only stub segments
            pass
    return tid


@dataclass
class PipelineStats:
    segments: int = 0
    samples: int = 0
    signals: int = 0
    elapsed_s: float = 0.0
    extras: dict = field(default_factory=dict)

    @property
    def msamples_per_sec(self) -> float:
        return self.samples / self.elapsed_s / 1e6 if self.elapsed_s else 0.0


def has_signal(cfg: Config, detect_result, stream: int | None = None,
               frequency_bin_count: int | None = None) -> bool:
    """The reference's gating: skip when too many channels are zapped
    (ref: signal_detect_pipe.hpp:343-345), else positive when any boxcar
    fired.

    ``frequency_bin_count`` is the *actual* row count of the waterfall the
    detection ran on (the reference reads it off the work item,
    signal_detect_pipe.hpp:343-345); callers that have the waterfall should
    pass its shape so a trimmed or alternate-path spectrum doesn't silently
    mis-scale the gate.  Falls back to the configured channel count.
    """
    zero_count = np.asarray(detect_result.zero_count)
    counts = np.asarray(detect_result.signal_counts)
    if zero_count.ndim == 0:
        zero_count = zero_count[None]
        counts = counts[None]
    freq_bins = (frequency_bin_count if frequency_bin_count is not None
                 else cfg.spectrum_channel_count)
    ok = zero_count < cfg.signal_detect_channel_threshold * freq_bins
    fired = counts.sum(axis=-1) > 0
    # registered-mode hook (pipeline/registry.py contract): a result
    # type carrying its own positive rule (e.g. the periodicity
    # mode's trials-corrected candidate gate) extends the verdict —
    # the engine stays mode-blind, the mode owns its statistics
    gate = getattr(detect_result, "positive_gate", None)
    if gate is not None:
        # the hook runs drain-side on fetched host
        # data  # srtb-lint: disable=sync-hot-path
        fired = fired | np.asarray(gate(cfg)).reshape(fired.shape)
    per_stream = ok & fired
    if stream is not None:
        return bool(per_stream[stream])
    return bool(per_stream.any())


def _abort_on_deadline(deadline_s: float) -> None:  # pragma: no cover
    import os
    import signal

    log.error(
        f"[pipeline] device sync exceeded segment_deadline_s={deadline_s}: "
        "accelerator runtime wedged; aborting")
    os.kill(os.getpid(), signal.SIGABRT)


def sync_with_deadline(deadline_s: float, fn, on_deadline=None):
    """Run a blocking device fetch under a fail-fast deadline (seconds,
    <= 0 disables).  A wedged accelerator runtime otherwise hangs the
    observation silently; on expiry the default handler aborts through the installed
    termination handlers for a loud stacktrace."""
    if not deadline_s or deadline_s <= 0:
        return fn()
    import threading

    timer = threading.Timer(deadline_s,
                            on_deadline or
                            (lambda: _abort_on_deadline(deadline_s)))
    timer.daemon = True
    timer.start()
    try:
        return fn()
    finally:
        timer.cancel()


class _DeadlineArray:
    """Lazy device-array handle whose host fetch runs under the pipeline's
    fail-fast deadline, however late a consumer triggers it.  Sinks fetch
    the waterfall via ``np.asarray`` and only for segments they actually
    write, so eagerly transferring the (multi-GB) waterfall per segment
    in drain would tax every segment; this keeps the fetch lazy while
    still arming the watchdog around the device transfer."""

    __slots__ = ("_arr", "_sync", "_fetched")

    def __init__(self, dev, sync_with_deadline):
        self._arr = dev
        self._sync = sync_with_deadline
        self._fetched = False

    @property
    def shape(self):
        return self._arr.shape

    @property
    def ndim(self):
        return self._arr.ndim

    @property
    def dtype(self):
        return self._arr.dtype

    @property
    def nbytes(self):
        return self._arr.nbytes

    def __len__(self):
        return len(self._arr)

    def __getitem__(self, idx):
        return self.__array__()[idx]

    def rows(self, lo: int, hi: int):
        """``[..., lo:hi, :]`` on the host, fetched on its own under the
        deadline and not kept (utils/platform.to_host_rows): a sink that
        takes the waterfall a block at a time never makes the whole
        host copy."""
        if self._fetched:
            return self._arr[..., lo:hi, :]
        from srtb_tpu.utils.platform import to_host_rows
        dev = self._arr
        return self._sync(lambda: to_host_rows(dev, lo, hi))

    def __array__(self, dtype=None, copy=None):
        if not self._fetched:
            dev = self._arr
            # explicit device_get, not np.asarray: the lazy waterfall
            # transfer is a *sanctioned* D2H (sink side), and the
            # sanitizer's transfer tripwire only exempts the explicit
            # spelling (srtb-lint sync-hot-path true positive, PR 3)
            self._arr = self._sync(lambda: jax.device_get(dev))
            self._fetched = True  # drop the device handle; memoize host
        a = self._arr
        if dtype is not None and np.dtype(dtype) != a.dtype:
            a = a.astype(dtype)
        elif copy:
            a = a.copy()
        return a


class Pipeline:
    """File (or any SegmentWork iterator) to sinks.

    One loop (:meth:`_run_engine`) between two stages on threads of
    their own, both built from ``pipeline/framework`` parts.  The loop
    takes a segment, stages its bytes and enqueues its program(s)
    (``dispatch``: ``h2d``, ``enqueue``), and with ``inflight_segments``
    in flight blocks on the oldest result (``fetch``); the sink pipe
    behind it gates detections, writes candidates, journals and
    checkpoints (``sink``).  Who pulls: the loop itself, on its own
    thread, as long as the chip paces it; where the source's pull takes
    over half the period between the loop's takes (a pull that long
    keeps the chip waiting for pull + dispatch + upload) the loop asks a
    reader stage for segment k+1 the moment it has taken segment k, and
    the pull runs under the loop's dispatch and fetch.  Exactly one
    segment ahead (one more pooled block out), never more; dispatch
    stays gated by the window; ``max_segments`` bounds pulls; the reader
    starts with the first pull it is asked for and ends with the run.
    Every run starts with the loop pulling (nothing is measured yet).

    In a journal record ``stages_ms.ingest`` is the pull's own seconds
    wherever it ran: where the reader ran ahead it is concurrent with
    the loop's stages of the segments before.  ``stages_ms.ingest_wait``
    is what the loop then waited for the reader, the tail of that same
    pull (0 where the loop pulled by itself or the segment lay ready),
    and the cumulative ``ingest_ahead`` counts the segments taken from
    the reader ahead.  The pull's seconds, the source's offset after it
    (what a checkpoint records), the trace id and the ``("ingest",
    index)`` fault and retry site are taken where the pull runs
    (:meth:`_pull`) and travel with the segment.
    """

    @_under_construct_span
    def __init__(self, cfg: Config, source=None, sinks=None,
                 keep_waterfall: bool = True, processor=None):
        self.cfg = cfg
        if processor is None:
            # donate the per-segment input buffer on accelerators: the
            # engine stages a fresh device array per segment and never
            # reuses it, so XLA may recycle its HBM as program scratch
            # (steady state does no net fresh device allocation).  Kept
            # off on CPU where donation is a no-op.  Built through the
            # plan registry so Config.search_mode selects the
            # registered mode's processor class.
            from srtb_tpu.pipeline import registry
            from srtb_tpu.utils.platform import on_accelerator
            processor = registry.build_processor(
                cfg, donate_input=on_accelerator(),
                stage_timer=self.stage_timer)
        elif getattr(processor, "stage_timer", False) is None:
            # a processor built elsewhere with no timer of its own: its
            # first dispatches are this pipeline's to account
            processor.stage_timer = self.stage_timer
        self.processor = processor
        metrics.set("data_streams", formats.resolve(
            cfg.baseband_format_type).data_stream_count)
        self._owned_writer_pool = None
        # causal tracing + flight recorder (utils/events.py): arm the
        # process-global hub from this config and hold the None-hook
        # handle — every hot-path emit below is one attribute read +
        # None check when disabled.  Incident bundles + SLO burn-rate
        # tracking follow the same zero-cost-off contract.
        events.configure(
            enabled=bool(getattr(cfg, "events_enable", True)),
            ring_size=int(getattr(cfg, "events_ring_size", 0)
                          or events.DEFAULT_RING_SIZE))
        self._events_enabled = bool(getattr(cfg, "events_enable",
                                            True))
        from srtb_tpu.utils.incidents import IncidentRecorder
        self.incidents = IncidentRecorder.from_config(cfg)
        self._slo_armed = slo.configure(cfg) is not None
        # durable exactly-once outputs (io/manifest.py): opening the
        # manifest RUNS RECOVERY — torn WAL tail truncated,
        # uncommitted artifact groups rolled back, the done-set of
        # committed (stream, segment, sink) groups rebuilt so the
        # replay below skips them.  Must happen before sinks open the
        # prefix and before the checkpoint loads (recovery may
        # truncate files the sinks are about to append to).
        self.manifest = None
        if getattr(cfg, "run_manifest_path", ""):
            from srtb_tpu.io.manifest import RunManifest
            from srtb_tpu.pipeline.checkpoint import StreamCheckpoint
            # peek the checkpoint FILE (the resume authority) before
            # recovery: a WAL that lost its ckpt records to corruption
            # must not roll back artifacts in segments the checkpoint
            # says are done — the resume would never regenerate them
            hint = 0
            if cfg.checkpoint_path:
                state = (StreamCheckpoint._load(cfg.checkpoint_path)
                         or StreamCheckpoint._load(
                             cfg.checkpoint_path + ".bak") or {})
                hint = int(state.get("segments_done", 0))
            loss0 = metrics.get("manifest_loss_flags")
            self.manifest = RunManifest.open(
                cfg.run_manifest_path,
                fsync=bool(getattr(cfg, "manifest_fsync", True)),
                hash_content=bool(getattr(cfg, "manifest_hash", True)),
                checkpoint_floor_hint=hint)
            if self.incidents is not None and \
                    metrics.get("manifest_loss_flags") > loss0:
                # fsck-grade LOSS surfaced during startup recovery:
                # bundle the evidence before the run overwrites the
                # recent past (the recovery events are on the ring)
                self.incidents.dump(
                    "manifest_loss",
                    reason="manifest recovery flagged unrecoverable "
                           "data loss (see events.jsonl)",
                    stream=str(getattr(cfg, "stream_name", "") or ""),
                    cfg=cfg, processor=self.processor,
                    journal_path=getattr(cfg, "telemetry_journal_path",
                                         ""))
        self.checkpoint = None
        if cfg.checkpoint_path:
            from srtb_tpu.pipeline.checkpoint import StreamCheckpoint
            self.checkpoint = StreamCheckpoint(cfg.checkpoint_path,
                                               manifest=self.manifest)
        if source is None:
            if not cfg.input_file_path:
                raise ValueError("no input_file_path and no source given")
            start = None
            if self.checkpoint and self.checkpoint.segments_done:
                start = self.checkpoint.file_offset_bytes
            # make_file_source honors Config.deterministic_timestamps
            # (offset-derived stamps -> reproducible artifact names)
            from srtb_tpu.io.file_input import make_file_source
            source = make_file_source(cfg, start_offset_bytes=start)
        self.source = source
        if sinks is None:
            if cfg.baseband_write_all:
                from srtb_tpu.ops import dedisperse as dd
                reserved_bytes = int(
                    dd.nsamps_reserved(cfg) * cfg.bytes_per_sample
                    * self.processor.data_stream_count)
                sinks = [WriteAllSink(cfg, reserved_bytes)]
            else:
                if cfg.writer_thread_count > 0:
                    from srtb_tpu.io.native_writer import AsyncWriterPool
                    self._owned_writer_pool = AsyncWriterPool(
                        cfg.writer_thread_count)
                    log.info(
                        "[writer_pool] candidates go to "
                        f"{self._owned_writer_pool.n_threads} "
                        + ("native" if self._owned_writer_pool.is_native
                           else "python") + " writer thread(s)")
                sinks = [WriteSignalSink(
                    cfg, writer_pool=self._owned_writer_pool)]
        self.sinks = sinks
        # manifest sink names must be stable across process restarts
        # (the done-set keys on them): position + class, both
        # config-determined
        self._sink_names = [f"{i}:{type(s).__name__}"
                            for i, s in enumerate(sinks)]
        if self.manifest is not None:
            for s in sinks:
                bind = getattr(s, "bind_manifest", None)
                if bind is not None:
                    bind(self.manifest)
        self.keep_waterfall = keep_waterfall
        self.stats = PipelineStats()
        # set when a bounded shutdown gave up on a wedged sink: close()
        # must then abandon the owned writer pool instead of draining
        # it (the drain would block on the very writes that are stuck)
        self._sink_wedged = False
        # opt-in runtime sanitizer: None when off, so every hook site
        # below is a single `is not None` check (zero-cost disabled)
        self.sanitizer = None
        if getattr(cfg, "sanitize", False):
            from srtb_tpu.analysis.sanitizer import Sanitizer
            self.sanitizer = Sanitizer()
        # multi-tenant stream identity (pipeline/fleet.py): the fleet
        # names each lane's config; solo runs are unnamed and every
        # labeled-twin bump below is a single None check
        self.stream = str(getattr(cfg, "stream_name", "") or "")
        self._stream_labels = ({"stream": self.stream}
                               if self.stream else None)
        # resilience hooks, each None when off (same zero-cost-disabled
        # contract as the sanitizer): deterministic fault injection,
        # the retry policy for the six guarded sites, and the
        # graceful-degradation ladder.  Fault-plan entries carrying a
        # stream selector arm only in the matching lane.
        self.faults = FaultInjector.from_plan(
            getattr(cfg, "fault_plan", ""), stream=self.stream)
        self.retry = RetryPolicy.from_config(cfg)
        # self-healing compute (resilience/demote.py): plan demotion
        # for device OOM/compile faults, bounded backend reinit for
        # halts.  None when both are configured off; when armed it is
        # consulted only from the dispatch/fetch exception handlers
        # plus one counter bump per drained segment — a healthy run
        # pays nothing measurable (PERF.md round 13 A/B).
        from srtb_tpu.resilience.demote import ComputeHealer
        self.healer = ComputeHealer.from_config(cfg, self._plan_factory)
        if self.healer is not None:
            self.healer.bind_base(getattr(self.processor, "staged",
                                          None))
        # sink-side liveness heartbeat: bumped after every completed
        # per-sink push (not per drained item), so the engine's wedge
        # detectors see progress through a slow multi-sink flush
        self._sink_heartbeat = 0
        # device-resident carry of the ingest ring (None = cold): the
        # reserved tail of the last dispatched segment, threaded from
        # one dispatch into the next (pipeline/segment.py ring plans),
        # plus the (data_stream_id, seq) of that segment — warm
        # assembly is only valid against the stream-adjacent successor
        self._ring_carry = None
        self._ring_prev = None
        # serializes the accounted/abandoned handoff between a wedged
        # sink worker and the bounded shutdown: _drain_body's
        # "abandoned? else account" decision and the shutdown's
        # "unaccounted? then abandon" decision must be atomic with
        # respect to each other, or a worker unwedging at exactly the
        # join expiry gets the segment BOTH drained and dropped
        self._handoff_lock = threading.Lock()
        self._ladder = None
        if getattr(cfg, "degrade_enable", False):
            from srtb_tpu.resilience.degrade import DegradationLadder
            self._ladder = DegradationLadder.from_config(cfg)
        # startup recovery sweep (crash consistency): a run that died
        # between a writer's temp write and its atomic rename leaves
        # orphaned <name>.srtb_tmp files; remove them before sinks
        # re-open the prefix, then resume from the checkpoint (above)
        if cfg.baseband_output_file_prefix:
            from srtb_tpu.io.writers import recover_orphan_temps
            recover_orphan_temps(cfg.baseband_output_file_prefix)
        for s in self.sinks:
            bind = getattr(s, "bind_stage_timer", None)
            if bind is not None:
                bind(self.stage_timer)
        # ---- performance observatory (always-on) ----
        # pre-register the compile/cache families so /metrics exposes
        # them from the first scrape (a counter that was never bumped
        # is still an answer: zero compiles so far), and the labeled
        # twins for a named fleet lane
        for fam in ("compile_seconds", "plan_compiles",
                    "aot_cache_hits", "aot_cache_misses"):
            metrics.add(fam, 0.0)
            if self._stream_labels is not None:
                metrics.add(fam, 0.0, labels=self._stream_labels)
        # on-demand jax.profiler capture of the first N segments
        # (Config.profile_capture_segments; None = off, zero-cost)
        from srtb_tpu.utils.tracing import ProfileCapture
        self.profile_capture = ProfileCapture.from_config(cfg)
        self.journal = telemetry.SpanJournal.from_config(cfg)
        # ---- science observatory (srtb_tpu/quality/) ----
        # data-quality monitor (gauges + drift detector + journal
        # payload for the plans' quality epilogue) and the pulse-
        # injection canary; both are the zero-cost-off None hook
        from srtb_tpu.quality import QualityMonitor
        self.quality = QualityMonitor.from_config(cfg)
        self.canary = None
        if int(getattr(cfg, "canary_every_segments", 0) or 0) > 0:
            from srtb_tpu.ops import dedisperse as dd
            from srtb_tpu.quality import CanaryController
            self.canary = CanaryController.from_config(
                cfg, n_samples=cfg.baseband_input_count,
                reserved_samples=dd.nsamps_reserved(cfg))
        # canary schedule base: the engines set this to the
        # checkpoint's resume-continuous drain count at run start, so
        # "every N-th segment" means the same segments across resumes
        self._canary_base = 0

    def _op(self, site: str, index: int, fn):
        """One guarded pipeline operation: the fault-injection hook
        fires first (a scheduled raise/stall/corrupt at exactly
        (site, index)), then the retry policy re-runs transient
        failures with backoff.  With faults unarmed and retries off
        this is a plain call — the hot path pays two attribute reads.
        Retried operations must be idempotent at their site: an ingest
        retry re-runs a read that never happened, a fetch retry
        re-fetches the same device arrays, a sink retry may re-push
        (sinks are at-least-once under recovery, like the reference's
        piggybacked rewrites)."""
        faults = self.faults
        if faults is not None and faults.armed(site):
            inner = fn

            def fn():
                faults.fire(site, index)
                return inner()
        if self.retry is None:
            return fn()
        return retry_call(fn, self.retry, site)

    def _timed_ingest(self, it, index: int = 0):
        """One source read as the "ingest" stage; the terminal failed
        read (source exhausted — for a UDP source, a receive blocked
        until shutdown) is NOT recorded, so the ingest histogram holds
        exactly one sample per segment like every other stage.  The
        read runs under the "ingest" fault site: transient receiver
        errors (interrupted syscalls, connection churn) retry with
        backoff instead of killing the run."""
        with stage_span("ingest", self.stage_timer) as sp:
            seg = self._op("ingest", index, lambda: next(it, None))
            if seg is None:
                sp.cancel()
        if seg is not None:
            dt = sp.seconds
            if self.events is not None:
                # stamp the causal trace id at the segment's birth (a
                # source that pre-stamped its own keeps it) and bind
                # the ambient context so retry/fault events attribute
                tid = _stamp_trace_id(seg)
                events.set_current(tid, self.stream)
                self.events.emit("stage.ingest", trace=tid,
                                 stream=self.stream, seg=index, dur=dt)
        return seg

    def _pull(self, it, index: int):
        """One pull with what belongs to its segment, taken where the
        pull runs (the loop's thread, or the reader's when it runs one
        segment ahead): ``(seg, ingest_seconds,
        offset_after_this_segment)``, or None at the source's end.  Read
        later, on another thread, the timer's ``last`` and the source's
        ``logical_offset`` are the NEXT segment's, and a checkpoint
        written from that offset skips a segment on resume."""
        seg = self._timed_ingest(it, index)
        if seg is None:
            return None
        return (seg, self.stage_timer.last["ingest"],
                getattr(self.source, "logical_offset", 0))

    def _record_segment(self, index: int, seg, det_res, positive: bool,
                        span: dict, queue_depth: int,
                        n_samples: int,
                        overlap_hidden_s: float | None = None,
                        inflight_depth: int | None = None,
                        device_s: float | None = None,
                        candidate: dict | None = None) -> None:
        """Per-drained-segment telemetry: lifetime counters, sliding
        window rates (segments/s and samples/s over the last 10 s — a
        stall is visible immediately, unlike the lifetime average), the
        /healthz liveness stamp, the ``device_seconds`` histogram, and
        one journal span record."""
        metrics.add("segments")
        metrics.add("samples", n_samples)
        if positive:
            metrics.add("signals")
        metrics.window("segments").add(1)
        metrics.window("samples").add(n_samples)
        if self._stream_labels is not None:
            metrics.add("segments", labels=self._stream_labels)
            metrics.add("samples", n_samples,
                        labels=self._stream_labels)
        telemetry.mark_segment(self.stream or None)
        if device_s is not None:
            metrics.histogram("device_seconds").observe(device_s)
            if self._stream_labels is not None:
                metrics.histogram(
                    "device_seconds",
                    labels=self._stream_labels).observe(device_s)
        if self.profile_capture is not None:
            # counts drained segments and auto-stops after N; the
            # sidecar records the covered trace_ids so the device
            # trace joins the causal-event timeline
            self.profile_capture.note_segment(
                index, getattr(seg, "trace_id", 0))
        if self.slo is not None:
            # the latency objective scores the segment's HOST wall
            # clock (the span's summed stages — what the journal's
            # synthetic 'segment' stage reports); overlap-hidden time
            # is concurrent and deliberately excluded
            self.slo.note_segment(self.stream,
                                  telemetry.segment_wall(span))
        det_count = 0
        det_by_stream = None
        counts = getattr(det_res, "signal_counts", None)
        if counts is not None:
            counts = np.asarray(counts)
            det_count = int(counts.sum())
            if counts.ndim == 2:   # [streams, boxcars]
                det_by_stream = counts.sum(axis=-1)
        # quality epilogue -> gauges + drift detector (journal or not:
        # /metrics must carry the quality state of a journal-less run)
        quality_extra = None
        if self.quality is not None:
            qvec = getattr(det_res, "quality", None)
            if qvec is not None:
                # drain-side on a fetched result: the blocking fetch
                # already materialized every det_res leaf
                host_q = np.asarray(qvec)  # srtb-lint: disable=sync-hot-path
                quality_extra = self.quality.observe(
                    host_q, segment=index)
        if self.journal is not None:
            # registered-mode hook: a result type with its own span
            # payload (e.g. the periodicity candidate table) journals
            # it on every segment — search outcomes survive even when
            # the positive gate withholds the file dumps
            span_extra = getattr(det_res, "span_extra", None)
            extra = span_extra() if span_extra is not None else None
            if quality_extra is not None:
                extra = dict(extra or {}, quality=quality_extra)
            # canary flag: the full verdict when the drain scored one
            # this life; the bare injection mark on a replayed drain
            # (exactly-once check already done by a previous life)
            verdict = getattr(seg, "canary_verdict", None)
            if verdict is None and getattr(seg, "canary",
                                           None) is not None:
                verdict = {"injected": True,
                           "segment": seg.canary["segment"]}
            if verdict is not None:
                extra = dict(extra or {}, canary=verdict)
            self.journal.write(telemetry.segment_span(
                index, span, queue_depth, det_count, positive, n_samples,
                timestamp_ns=getattr(seg, "timestamp", 0),
                extra=extra,
                overlap_hidden_s=overlap_hidden_s,
                inflight_depth=inflight_depth,
                active_plan=getattr(self.processor, "plan_name", None),
                stream=self.stream or None,
                trace_id=getattr(seg, "trace_id", 0) or None,
                device_s=device_s,
                # v10: stamped by the fleet's cross-stream batch
                # former (pipeline/fleet._BatchFormer); absent on
                # every solo dispatch — the span omits them
                batch_size=getattr(seg, "batch_size", None),
                batch_wait_ms=(
                    None if getattr(seg, "batch_wait_s", None) is None
                    else seg.batch_wait_s * 1e3),
                # v11: the pool member this lane dispatches through
                # (stamped by the fleet at placement and re-stamped
                # by a live migration); absent outside a fleet
                device=getattr(self, "device_label", None),
                detections_by_stream=det_by_stream,
                # v13: ``candidate_bytes`` / ``writer_file_ms``, what
                # the candidate writer counted on a segment that dumped
                # (_take_sink_spans); {} on a quiet one
                **(candidate or {})))

    # ---------------------------------------------- async segment engine

    @staticmethod
    def _result_ready(det_res) -> bool:
        """True when every device array in the detect result has
        materialized (``jax.Array.is_ready``) — the non-blocking fetch
        probe.  Objects without a readiness probe (host arrays, test
        stubs that choose not to implement one) count as ready.  A
        *failing* probe also counts as ready — the blocking fetch path
        surfaces the real error with full context — but is logged so a
        flaky probe never degrades the engine to serial silently."""
        try:
            leaves = jax.tree_util.tree_leaves(det_res)
        except Exception as e:
            log.debug(f"[pipeline] readiness probe: tree_leaves failed "
                      f"({e!r}); treating result as ready")
            return True
        for leaf in leaves:
            probe = getattr(leaf, "is_ready", None)
            if probe is None:
                continue
            try:
                if not probe():
                    return False
            except Exception as e:
                log.debug(f"[pipeline] is_ready probe failed ({e!r}); "
                          "deferring to the blocking fetch")
                return True
        return True

    # ------------------------------------------- self-healing compute

    def _plan_factory(self, cfg, staged):
        """Build a replacement segment plan for the self-healing
        ladder (a demotion rung, the promotion probe, or a device
        reinit).  Mirrors the constructor-relevant state of the
        CURRENT processor — donation policy and window — so the only
        thing that changes is the plan itself; the rung's config
        changes trace-relevant knobs, so ``plan_signature()`` differs
        and any AOT cache (``cfg.aot_plan_path``, re-enabled by the
        constructor) misses cleanly and re-lowers.  Built through the
        plan registry: the search_mode rung demotes by CHANGING the
        mode, so the replacement may be a different processor class."""
        from srtb_tpu.ops import window as W
        from srtb_tpu.pipeline import registry
        return registry.build_processor(
            cfg,
            window_name=getattr(self.processor, "_window_name",
                                W.DEFAULT_WINDOW),
            staged=staged,
            donate_input=bool(getattr(self.processor, "_donate_input",
                                      False)),
            stage_timer=self.stage_timer)

    def _swap_processor(self, newp) -> None:
        """Install a replacement plan (demotion / promotion / reinit).
        The warm ingest-ring carry belongs to the OLD plan's programs
        and carry-aval contract, so it is invalidated — the next
        dispatch goes cold from its retained host buffer — and the
        old processor is retired: its compiled handles (including any
        in-memory AOT executables bound to a dead backend after a
        reinit) raise loudly on any stray dispatch instead of running
        stale."""
        old, self.processor = self.processor, newp
        self._ring_invalidate()
        retire = getattr(old, "retire", None)
        if retire is not None and old is not newp:
            # a fleet-SHARED processor no-ops its retire (other
            # tenants still dispatch through it; segment.py guards)
            retire()

    def _account_dropped(self, n: int = 1,
                         trace: int | None = None) -> None:
        """Account ``n`` whole shed segments: the process-wide counter
        + loss window, plus the per-stream labeled twin when this
        pipeline is a named fleet lane (loss must be attributable to
        its tenant).  ``trace`` is the SHED segment's own causal id —
        callers that hold the work item pass it; the ambient context
        belongs to the most recently dispatched segment and would
        blame the wrong one."""
        metrics.add("segments_dropped", n)
        metrics.window("segments_dropped").add(n)
        if self._stream_labels is not None:
            metrics.add("segments_dropped", n,
                        labels=self._stream_labels)
        if self.slo is not None:
            self.slo.note_dropped(self.stream, n)
        ev = self.events
        if ev is not None:
            ev.emit("shed.segment",
                    trace=(trace if trace is not None
                           else events.current()[0]),
                    stream=self.stream, info=f"n={n}")

    @property
    def events(self):
        """The LIVE process-global hub (or None).  Deliberately not
        cached at construction: a later pipeline may reconfigure the
        global hub (different ring size), and a stale handle would
        silently split one process's causal story across two
        recorders — half in this pipeline's orphaned hub, half (the
        module-level emits) in the new one.  The disabled path stays
        one property call + global read + None check."""
        return events.hub if self._events_enabled else None

    @property
    def slo(self):
        """The LIVE process-global SLO tracker (or None) — same
        no-stale-handle rule as :attr:`events`: a later pipeline
        reconfiguring the global tracker must not leave this one
        feeding an orphan that /healthz and /metrics never read."""
        return slo.tracker if self._slo_armed else None

    def _incident(self, kind: str, reason: str = "",
                  trace: int | None = None,
                  extra: dict | None = None) -> None:
        """Dump an incident bundle (None-hook off; best-effort,
        rate-limited and bounded by the recorder).  ``extra`` is an
        arbitrary JSON-able payload landing as ``extra.json`` in the
        bundle — e.g. the canary verdict + quality timeline."""
        if self.incidents is not None:
            self.incidents.dump(
                kind, reason=reason, trace=trace, stream=self.stream,
                cfg=self.cfg, processor=self.processor,
                journal_path=getattr(self.cfg,
                                     "telemetry_journal_path", ""),
                extra=extra)

    # ------------------------------------------------- ingest ring state

    @property
    def _ring_live(self) -> bool:
        """Whether the device-resident carry ring is active for this
        run: the processor resolved Config.ingest_ring on AND it speaks
        the staging protocol (duck-typed stub processors don't)."""
        return bool(getattr(self.processor, "ring", False)) \
            and getattr(self.processor, "stage_input", None) is not None

    def _ring_invalidate(self) -> None:
        """Drop the device carry: the NEXT dispatch goes cold (full
        upload from its retained host buffer).  Called whenever carry
        continuity breaks — watchdog requeue, shed segment — and at
        run start/end (a checkpoint resume is a fresh run, so resume
        re-dispatch is cold by construction)."""
        if self._ring_carry is not None and self.events is not None:
            # a live carry is being dropped: the warm chain breaks
            # here and the next dispatch pays a full upload
            self.events.emit("ring.invalidate",
                             trace=events.current()[0],
                             stream=self.stream)
        self._ring_carry = None
        self._ring_prev = None

    def _ring_adjacent(self, seg) -> bool:
        """Whether ``seg`` is the stream-adjacent successor of the last
        dispatched segment — the precondition for warm assembly: its
        overlap head must BE the carry.  Unstamped segments (seq < 0,
        e.g. hand-built SegmentWork) are never warm; a seq gap (a
        dropped segment upstream) or a different data_stream_id (an
        interleaved multi-receiver stream) goes cold rather than
        assembling against a foreign tail."""
        prev = self._ring_prev
        return (prev is not None
                and getattr(seg, "seq", -1) >= 0
                and seg.seq == prev[1] + 1
                and getattr(seg, "data_stream_id", 0) == prev[0])

    def _dispatch_ring(self, seg, index: int, requeue: bool,
                       span: dict) -> tuple:
        """Ring-mode device dispatch of one segment.  Warm when a
        carry is live: upload stride bytes only and run the two-input
        assemble plan.  Cold (no carry / requeue): full upload through
        the carry-emitting cold plan, so the ring re-arms with no
        extra H2D bytes.  A dispatch RETRY always re-stages cold from
        the retained host buffer — the first attempt donated both the
        carry and the staged stride bytes — and stays bit-identical.
        ``requeue`` isolates the dispatch from the ring: the live
        carry belongs to a LATER segment (the caller invalidated it),
        and the requeued segment's own carry is already history."""
        proc = self.processor
        stage_in = proc.stage_input
        tid = getattr(seg, "trace_id", 0)
        # canary-injected copy when attached (the delta is zero over
        # the head/tail reserved spans, so the warm stride slice and
        # the adopted carry stay consistent with a cold dispatch)
        data = self._device_bytes(seg)
        # a requeue that lands on a FULLY invalidated ring (processor
        # swap, device reinit, live migration) is the stream's new
        # frontier: its cold full upload emits a valid carry, and
        # adopting it re-arms the ring in the same dispatch — the
        # follow-up segment warm-assembles instead of paying a second
        # full upload.  A requeue with ring state still live (watchdog
        # cancel of a mid-window segment) must NOT anchor: the ring
        # has moved past it, and adjacency would lie.
        ring_down = self._ring_prev is None and self._ring_carry is None
        carry = None if requeue or not self._ring_adjacent(seg) \
            else self._ring_carry
        if carry is not None:
            self._ring_carry = None  # consumed below (donated)
            staged = self._h2d(span, index, tid,
                               lambda: stage_in(data,
                                                stride_only=True))
            attempt = [0]

            def run_it():
                attempt[0] += 1
                if attempt[0] == 1:
                    return proc.run_device_ring(carry, staged)
                # the failed warm attempt consumed the carry: go cold
                return proc.run_device_cold(stage_in(data))

            out, next_carry = self._enqueue(span, index, tid, run_it)
        else:
            if self.events is not None:
                self.events.emit("ring.cold", trace=tid,
                                 stream=self.stream, seg=index,
                                 info="requeue" if requeue else "")
            staged = self._h2d(span, index, tid, lambda: stage_in(data))
            first = [True]

            def run_it():
                if first[0]:
                    first[0] = False
                    return proc.run_device_cold(staged)
                return proc.run_device_cold(stage_in(data))

            out, next_carry = self._enqueue(span, index, tid, run_it)
        if not requeue or ring_down:
            # adopt the carry for the next dispatch; a requeued
            # segment's carry is stale (the ring has moved past it)
            # UNLESS the ring was down at entry — then this requeue
            # IS the re-arm (see ring_down above)
            self._ring_carry = next_carry
            seq = getattr(seg, "seq", -1)
            # an unstamped segment cannot anchor adjacency: the next
            # dispatch stays cold
            self._ring_prev = ((getattr(seg, "data_stream_id", 0), seq)
                               if seq >= 0 else None)
        return out

    # ------------------------------------------- pulse-injection canary

    def _canary_prepare(self, seg, index: int) -> None:
        """Dispatch-side canary hook: on a scheduled segment, attach
        the injected COPY (``seg.canary_data``) and the injection mark
        (``seg.canary``).  Device staging reads the copy through
        :meth:`_device_bytes`; every sink keeps seeing the pristine
        ``seg.data``, so science outputs stay bit-identical to a
        canary-off run.  Idempotent: a watchdog requeue or healed
        re-dispatch reuses the already-attached copy (same bytes —
        the delta is deterministic — and the injected counter stays
        exactly-once)."""
        c = self.canary
        if c is None or getattr(seg, "canary", None) is not None:
            return
        data, mark = c.prepare(self._canary_base + index, seg.data)
        if mark is None:
            return
        try:
            seg.canary = mark
            seg.canary_data = data
        except AttributeError:  # read-only stub segments: no canary
            log.warning("[canary] segment cannot carry the injection "
                        "mark; skipping")

    def _device_bytes(self, seg):
        """The host bytes the DEVICE stages: the canary-injected copy
        when one is attached, else the segment's pristine buffer.
        Also the staging-release key — the staging registry keys on
        ``id()`` of whatever buffer was staged."""
        d = getattr(seg, "canary_data", None)
        return seg.data if d is None else d

    def _canary_drain(self, seg, mark: dict, det_res,
                      sinks_done: set, drain_index: int) -> bool:
        """Drain-side canary handling: score the recovered S/N
        against the expected reference, flag the segment in the run
        manifest, and escalate a sensitivity regression as an
        incident bundle with the recent quality timeline attached.
        Exactly-once under sink retry / supervisor replay via the
        "canary" marker in ``sinks_done`` (sink entries are ints, no
        collision).  Returns the QUARANTINED positive verdict —
        always False: a synthetic pulse must never count as science
        (no ``signals`` bump, no candidate dumps)."""
        if "canary" in sinks_done:
            return False
        sinks_done.add("canary")
        verdict = None
        if self.canary is not None:
            # drain-side on a fetched result (same sanction as the
            # quality observe in _record_segment)
            peaks = np.asarray(  # srtb-lint: disable=sync-hot-path
                getattr(det_res, "snr_peaks", 0.0))
            verdict = self.canary.check(mark["segment"], peaks)
        try:
            seg.canary_verdict = verdict  # journaled by _record_segment
        except AttributeError:
            pass
        if self.manifest is not None:
            self.manifest.canary(
                getattr(seg, "data_stream_id", 0), drain_index,
                mark["segment"],
                ok=bool(verdict.get("ok", True)) if verdict else True)
        if verdict is not None and not verdict.get("ok", True):
            if self.events is not None:
                self.events.emit(
                    "canary.regression",
                    trace=getattr(seg, "trace_id", 0),
                    stream=self.stream, seg=mark["segment"],
                    info=f"ratio={verdict.get('ratio')}")
            self._incident(
                "canary_sensitivity",
                reason=(f"canary segment {mark['segment']}: recovered "
                        f"S/N {verdict.get('snr')} is "
                        f"{verdict.get('ratio')}x the expected "
                        f"{verdict.get('expected')}"),
                trace=getattr(seg, "trace_id", 0),
                extra={"canary": dict(mark, **verdict),
                       "quality_timeline":
                           (self.quality.timeline()
                            if self.quality is not None else [])})
        return False

    def _h2d(self, span: dict, index: int, tid: int, stage):
        """The "h2d" child span of "dispatch": ``stage`` hands one
        segment's bytes to ``jax.device_put`` (SegmentProcessor.
        stage_input, which also counts ``h2d_bytes``), under the "h2d"
        fault site."""
        with stage_span("h2d", self.stage_timer, tid) as sp:
            staged = self._op("h2d", index, stage)
        span["h2d"] = sp.seconds
        return staged

    def _enqueue(self, span: dict, index: int, tid: int, run_it):
        """The "enqueue" child span of "dispatch": the jit call that
        hands the segment's program to the device, under the "dispatch"
        fault site (a retry re-stages inside it)."""
        with stage_span("enqueue", self.stage_timer, tid) as sp:
            out = self._op("dispatch", index, run_it)
        span["enqueue"] = sp.seconds
        # a plan of several programs says what each jit call took
        # (the staged plan: enqueue_a / _b / _c)
        take = getattr(self.processor, "take_stage_spans", None)
        if take is not None:
            span.update(take())
        return out

    def _dispatch_segment(self, seg, ingest_s: float,
                          offset_after: int, index: int = 0,
                          requeue: bool = False,
                          ingest_wait_s: float = 0.0) -> tuple:
        """Stage one segment's bytes to the device (async H2D) and
        enqueue its program; both run under the "dispatch" stage, and
        under the "h2d" / "dispatch" fault sites respectively.
        ``offset_after`` is the source's logical offset captured right
        after THIS segment's ingest (not at dispatch time — with
        batching, later ingests have already advanced the source).
        ``ingest_wait_s`` is what the loop waited for a reader that
        pulled this segment ahead (0 where the caller pulled it itself).
        Returns the in-flight record (the trailing ``index`` is the
        dispatch-order segment index, which the watchdog uses to bound
        requeues and the fault injector to schedule)."""
        tid = getattr(seg, "trace_id", 0)
        if self.events is not None:
            events.set_current(tid, self.stream)
        self._canary_prepare(seg, index)
        data = self._device_bytes(seg)
        span = {"ingest": ingest_s, "ingest_wait": ingest_wait_s}
        with stage_span("dispatch", self.stage_timer, tid) as sp:
            stage_in = getattr(self.processor, "stage_input", None)
            if self._ring_live:
                wf, det_res = self._dispatch_ring(seg, index, requeue,
                                                  span)
            elif stage_in is not None:
                staged = self._h2d(span, index, tid,
                                   lambda: stage_in(data))
                first = [True]

                def run_it():
                    # a donated plan consumes the staged buffer the
                    # moment the first attempt dispatches, so a RETRY
                    # must re-stage from the retained host bytes —
                    # reusing the donated handle would fail "deleted"
                    if first[0]:
                        first[0] = False
                        return self.processor.run_device(staged)
                    return self.processor.run_device(
                        stage_in(data))

                wf, det_res = self._enqueue(span, index, tid, run_it)
            else:  # duck-typed stub processors (tests)
                wf, det_res = self._op(
                    "dispatch", index,
                    lambda: self.processor.process(data))
        span["dispatch"] = sp.seconds
        if self.events is not None:
            self.events.emit("stage.dispatch", trace=tid,
                             stream=self.stream, seg=index,
                             dur=span["dispatch"],
                             info="requeue" if requeue else "")
        return (seg, wf, det_res, offset_after, span,
                time.perf_counter(), index)

    def _dispatch_micro_batch(self, segs: list, ingests: list,
                              offsets: list, first_index: int = 0,
                              ingest_waits: list | None = None) \
            -> list:
        """Stack B ingested segments into ONE vmapped jit call; each
        segment's results are lazy device slices of the batch outputs.
        The batch dispatch cost is amortized evenly across the spans;
        each item keeps its OWN post-ingest source offset so a
        checkpoint written after a partially drained batch resumes at
        the first undrained segment, not past the whole batch.  The
        whole batch dispatch runs under the first segment's "dispatch"
        fault site (one jit call = one failure domain)."""
        # one span for the batch (no timer: its cost is amortized over
        # the segments' "dispatch" samples below)
        with stage_span("dispatch",
                        trace_id=getattr(segs[0], "trace_id", 0)) as sp:
            for i, s in enumerate(segs):
                self._canary_prepare(s, first_index + i)
            if self._ring_live:
                wf_b, det_b = self._dispatch_batch_ring(segs, first_index)
            else:
                stack = getattr(self.processor, "stack_batch", None)
                # host byte buffers, never device arrays: the
                # contiguous wrap is a no-op for the sources' ndarrays
                datas = [self._device_bytes(s) for s in segs]
                stacked = (stack(datas)
                           if stack is not None else
                           np.stack([np.ascontiguousarray(d)
                                     for d in datas]))
                wf_b, det_b = self._op(
                    "dispatch", first_index,
                    lambda: self.processor.process_batch(stacked))
        per_seg = sp.seconds / len(segs)
        items = []
        for i, seg in enumerate(segs):
            self.stage_timer.record("dispatch", per_seg)
            det_i = jax.tree_util.tree_map(
                lambda x, j=i: x[j], det_b)
            span = {"ingest": ingests[i],
                    "ingest_wait": ingest_waits[i] if ingest_waits
                    else 0.0,
                    "dispatch": per_seg}
            if self.events is not None:
                self.events.emit("stage.dispatch",
                                 trace=getattr(seg, "trace_id", 0),
                                 stream=self.stream,
                                 seg=first_index + i, dur=per_seg,
                                 info=f"batch={len(segs)}")
            items.append((seg, wf_b[i], det_i, offsets[i], span,
                          time.perf_counter(), first_index + i))
        return items

    def _dispatch_batch_ring(self, segs: list, first_index: int):
        """Ring-mode micro-batch dispatch: warm batches upload B stride
        slices (pooled stack) against the live carry; cold batches
        upload B full segments through the carry-emitting cold batch
        plan.  Retries go cold from the retained host buffers, exactly
        like the single-segment path."""
        proc = self.processor
        # warm needs the whole batch stream-adjacent: segs[0] continues
        # the carry, and each member continues its predecessor
        chain_ok = self._ring_adjacent(segs[0]) and all(
            getattr(b, "seq", -1) == getattr(a, "seq", -2) + 1
            and getattr(b, "data_stream_id", 0)
            == getattr(a, "data_stream_id", 0)
            for a, b in zip(segs, segs[1:]))
        carry = self._ring_carry if chain_ok else None
        datas = [self._device_bytes(s) for s in segs]
        if carry is not None:
            self._ring_carry = None  # consumed below (donated)
            attempt = [0]

            def run_it():
                attempt[0] += 1
                if attempt[0] == 1:
                    return proc.process_batch_ring(
                        carry, proc.stack_batch(datas, stride_only=True))
                return proc.process_batch_cold(proc.stack_batch(datas))

            out, next_carry = self._op("dispatch", first_index, run_it)
        else:
            out, next_carry = self._op(
                "dispatch", first_index,
                lambda: proc.process_batch_cold(proc.stack_batch(datas)))
        self._ring_carry = next_carry
        seq = getattr(segs[-1], "seq", -1)
        self._ring_prev = ((getattr(segs[-1], "data_stream_id", 0), seq)
                           if seq >= 0 else None)
        return out

    def _fetch_inflight(self, item: tuple, depth: int,
                        live_depth: int) -> tuple:
        """Resolve one in-flight record to host data.  The gap between
        dispatch returning and this fetch starting is host time the
        engine hid under device compute — journaled as
        ``overlap_hidden_ms`` and observed into the ``overlap`` stage
        histogram."""
        seg, wf, det_res, offset_after, span, t_dispatched, index = item
        hidden = max(0.0, time.perf_counter() - t_dispatched)
        self.stage_timer.record("overlap", hidden)
        seg, wf, det_res, offset_after, span = self._fetch_device(
            (seg, wf, det_res, offset_after, span), index)
        # device-time accounting (always-on): dispatch-return ->
        # fetch-complete wall for THIS segment.  The blocking fetch
        # proves device completion, so this is an UPPER bound on the
        # segment's device busy time — exact in serial mode, inflated
        # by drain-queue wait when the window runs deep.
        device_s = max(0.0, time.perf_counter() - t_dispatched)
        # the dispatch-order index rides along so the sink-side fault
        # sites (sink_write, checkpoint) address segments in the SAME
        # index space as ingest/h2d/dispatch/fetch — the drain counter
        # starts at the checkpoint on resume and skips shed segments,
        # so one fault_plan index would otherwise mean different
        # segments at different sites
        return (seg, wf, det_res, offset_after, span, hidden, device_s,
                depth, live_depth, index)

    def _drain_body(self, item: tuple, drained: list) -> None:
        """Sink-side half of one segment: detection gate, sink pushes,
        buffer-pool release, journal record, checkpoint.  Runs on the
        sink pipe thread in overlapped mode (off the dispatch critical
        path), inline in serial mode."""
        cfg = self.cfg
        (seg, wf, det_res, offset_after, span, hidden, device_s, depth,
         live, index, degrade_level, sinks_done) = item
        if self.events is not None:
            # bind the causal context on the SINK thread: manifest
            # intent/commit/done records and sink-side retries emitted
            # below attribute to this segment's trace
            events.set_current(getattr(seg, "trace_id", 0),
                               self.stream)
        san = self.sanitizer
        if san is not None:
            # the sink side is single-owner too: either the sink pipe
            # thread (overlapped) or the main thread (serial), never
            # both within one run
            san.assert_owner("sink_drain")
            self._sanitize_check(wf, det_res)
        positive = has_signal(
            cfg, det_res,
            frequency_bin_count=(wf.shape[-2] if wf is not None
                                 else None))
        cmark = getattr(seg, "canary", None)
        if cmark is not None:
            # quarantine: the canary's recovered S/N is scored and
            # journaled, then the segment is forced NEGATIVE — the
            # synthetic pulse never counts as science
            positive = self._canary_drain(seg, cmark, det_res,
                                          sinks_done, drained[0])
        # the "stats" marker rides in sinks_done (sink entries are
        # ints, no collision): a supervisor replay of a crashed drain
        # re-enters this body, and the first attempt may already have
        # counted the signal — stats must stay exactly-once too
        if positive and "stats" not in sinks_done:
            sinks_done.add("stats")
            self.stats.signals += 1
            # drained[0] is the index this segment journals as; the
            # dispatch counter runs ahead of the drain in overlapped
            # mode and would name the wrong segment
            log.info("[pipeline] signal detected in segment "
                     f"{drained[0]}")
        # fault/retry sites address segments by dispatch-order index
        # (the space ingest/h2d/dispatch/fetch already use); the
        # JOURNAL keeps the drain counter below, which is resume-
        # continuous across checkpointed runs
        seg_index = index
        # durable exactly-once key: the RESUME-CONTINUOUS drain index
        # (what the checkpoint counts), not the per-run dispatch
        # index — a replayed segment after a crash+resume must land on
        # the same manifest key its first life used
        mkey = (None if self.manifest is None
                else (getattr(seg, "data_stream_id", 0), drained[0]))
        with stage_span("sink", self.stage_timer,
                        getattr(seg, "trace_id", 0)) as sp:
            # ``sinks_done`` rides with the item: a retry (or a
            # supervisor replay) re-enters _push_sinks but skips the
            # sinks that already succeeded — exactly-once per sink,
            # which in-place appenders (WriteAllSink) require
            self._op("sink_write", seg_index,
                     lambda: self._push_sinks(seg, wf, det_res,
                                              positive, degrade_level,
                                              done=sinks_done,
                                              seg_key=mkey))
        span["sink"] = sp.seconds
        candidate = self._take_sink_spans(span)
        if self.events is not None:
            self.events.emit("stage.sink",
                             trace=getattr(seg, "trace_id", 0),
                             stream=self.stream, seg=index,
                             dur=span["sink"],
                             info="dump" if positive else "")
        # host staging-buffer pool: copies staged for this segment
        # (micro-batch stacks, non-contiguous inputs) are reusable once
        # the segment drained — the device program that consumed the
        # transfer has completed.  MUST run BEFORE the reader-pool
        # release below: the registry keys on id(seg.data), and once
        # the reader can reacquire that exact buffer object a fresh
        # registration under the same id could be popped here instead,
        # returning a staging buffer whose transfer is still in flight
        rel = getattr(self.processor, "release_staging", None)
        if rel is not None:
            # the staging registry keys on id() of the STAGED buffer
            # — the canary-injected copy when one was attached
            rel(self._device_bytes(seg))
        # file mode: sinks never retain segments (no piggybank deque),
        # so the host buffer can go back to the pool for the reader
        pool = getattr(self.source, "pool", None)
        if pool is not None and cfg.input_file_path:
            pool.release(seg.data)
        with self._handoff_lock:
            if "abandoned" in sinks_done:
                # the bounded shutdown accounted this segment as
                # dropped while this thread was wedged mid-push; a
                # late completion must not also journal/count it
                return
            # claiming the drain count INSIDE the lock is what makes
            # the handoff race-free: once drained advances, the
            # shutdown's drained == progress check can no longer
            # abandon this item
            drained[0] += 1
        self._record_segment(drained[0] - 1, seg, det_res, positive,
                             span, queue_depth=depth,
                             n_samples=cfg.baseband_input_count,
                             overlap_hidden_s=hidden,
                             inflight_depth=live,
                             device_s=device_s, candidate=candidate)
        if self.checkpoint is not None:
            # a checkpointed segment must be durable: flush queued
            # async candidate writes before recording it as done.
            # Both run under the "checkpoint" fault site: the flush
            # and the atomic state rewrite are idempotent.
            self._op("checkpoint", seg_index,
                     lambda: (self._drain_sinks(),
                              self.checkpoint.update(drained[0],
                                                     offset_after)))

    def run(self, max_segments: int | None = None) -> PipelineStats:
        """The async in-flight engine (see module docstring).  With
        ``inflight_segments = 1`` this degenerates to the fully serial
        reference loop; the default window of 2 reproduces the
        reference's queue-capacity-2 pipe graph with sink work off the
        critical path.

        With ``Config.sanitize`` the whole run executes inside the
        sanitizer scope: implicit-transfer tripwire armed, thread
        owners tracked, and a leaked-thread check after the sink pipe
        joins."""
        try:
            if self.sanitizer is None:
                return self._run_engine(max_segments)
            with self.sanitizer.run_scope():
                return self._run_engine(max_segments)
        finally:
            _log_setup_once(self)

    def _run_engine(self, max_segments: int | None = None) \
            -> PipelineStats:
        from srtb_tpu.pipeline import framework as fw

        cfg = self.cfg
        window = max(1, int(getattr(cfg, "inflight_segments", 2) or 1))
        batch = max(1, int(getattr(cfg, "micro_batch_segments", 1) or 1))
        if batch > window:
            raise ValueError(
                f"micro_batch_segments={batch} exceeds "
                f"inflight_segments={window}: a batch dispatch must fit "
                "the in-flight window")
        if batch > 1 and getattr(self.processor, "staged", False):
            # fail before any ingest/compile happens: process_batch
            # would reject this anyway, but only after B multi-GB
            # segments were read and stacked
            raise ValueError(
                "micro_batch_segments > 1 requires the fused plan "
                "(staged segments are already dispatch-amortized)")
        start = time.perf_counter()
        if self.profile_capture is not None:
            # arm the on-demand XLA trace BEFORE the first dispatch so
            # the capture covers compile + the first N segments
            self.profile_capture.start()
        n_samples_per_seg = cfg.baseband_input_count
        drained = [self.checkpoint.segments_done if self.checkpoint else 0]
        # resume-continuous canary schedule: dispatch indices restart
        # at 0 every run, so the absolute index is base + index
        self._canary_base = drained[0]
        # ring carry starts cold every run: a checkpoint-resumed (or
        # simply restarted) process has no device-resident tail, so the
        # first dispatch is a full upload that re-arms the ring
        self._ring_invalidate()

        # sink work runs on a framework Pipe in overlapped mode so
        # writers + the lazy waterfall transfer cannot serialize into
        # the next segment's ingest/dispatch; serial mode keeps it
        # inline (the honest A/B reference leg)
        use_sink_pipe = window > 1
        stop = fw.StopToken()
        q_sink = fw.WorkQueue(capacity=window)
        # a segment is "in flight" from dispatch until its SINK
        # completes: the admission gate below bounds this count by the
        # window, so at most W waterfalls are device-resident at once.
        # Without sink accounting, fetched-but-unsunk items in the
        # queue would stack up to ~2W waterfalls — an HBM regression
        # at multi-GB waterfall sizes the old 2-deep loop never risked.
        live_lock = threading.Lock()
        live = [0]

        def live_count() -> int:
            with live_lock:
                return live[0]

        def live_add(n: int) -> None:
            with live_lock:
                live[0] += n
                metrics.set("inflight_depth", live[0])
                if self._stream_labels is not None:
                    metrics.set("inflight_depth", live[0],
                                labels=self._stream_labels)

        # bounded-restart supervision of the sink pipe: a transient
        # crash restarts the worker (the failed item is replayed
        # inline first, preserving journal order); fatal crashes and
        # exhausted budgets escalate exactly like today.  Disabled
        # under the sanitizer (its claim-on-first-use thread-ownership
        # guard is incompatible with a replacement sink thread).
        supervisor = None
        if use_sink_pipe and self.sanitizer is None \
                and int(getattr(cfg, "supervisor_max_restarts", 0)) > 0:
            from srtb_tpu.resilience.supervisor import Supervisor
            supervisor = Supervisor(
                "sink_drain",
                max_restarts=cfg.supervisor_max_restarts,
                window_s=getattr(cfg, "supervisor_window_s", 60.0))
        current = [None]   # item the sink worker is processing
        progress = [0]     # drained[0] when that item started

        def sink_f(_stop, item):
            current[0] = item
            progress[0] = drained[0]
            try:
                self._drain_body(item, drained)
            finally:
                # an item abandoned by the bounded shutdown had its
                # live slot released (and the drop counted) there
                if "abandoned" not in item[-1]:
                    live_add(-1)
            current[0] = None

        sink_pipe = None
        if use_sink_pipe:
            sink_pipe = fw.start_pipe(sink_f, q_sink, None, stop,
                                      "sink_drain")

        def sink_alive() -> bool:
            """True while the sink side can make progress; restarts a
            supervised crashed pipe as a side effect."""
            nonlocal sink_pipe
            if sink_pipe is None or sink_pipe.exception is None:
                return True
            if supervisor is None or \
                    not supervisor.should_restart(sink_pipe.exception):
                return False
            failed, current[0] = current[0], None
            if failed is not None and failed is not fw.SENTINEL:
                if drained[0] == progress[0]:
                    # the crash hit BEFORE the item was accounted:
                    # replay it inline BEFORE the new pipe starts
                    # popping, preserving journal order (its live slot
                    # was already released by sink_f's finally; sink
                    # pushes are at-least-once under recovery); a
                    # second failure here propagates = escalation
                    self._drain_body(failed, drained)
                else:
                    # the crash hit AFTER accounting (e.g. in the
                    # checkpoint flush): the segment is already
                    # counted, journaled and pushed — replaying
                    # _drain_body would double-count it.  A missed
                    # checkpoint update self-heals: update() writes
                    # absolute state, so the next segment's
                    # checkpoint covers this one.
                    log.warning(
                        "[supervisor] sink_drain crashed after its "
                        "segment was accounted; skipping replay (the "
                        "next checkpoint covers it)")
            sink_pipe = fw.start_pipe(sink_f, q_sink, None, stop,
                                      "sink_drain")
            return True

        watchdog_max = int(getattr(cfg, "segment_watchdog_requeues",
                                   0) or 0)
        deadline_s = float(cfg.segment_deadline_s or 0.0)
        watchdog = watchdog_max > 0 and deadline_s > 0
        # ladder pressure flag: the engine waited on the sink since
        # the last emit (set by push_sink and the parked-window wait)
        sink_wait = [False]

        # shedding (watchdog shed + degradation ladder) is a LIVENESS
        # mechanism: it only applies to a real-time source (UDP), where
        # a stalled engine turns into receiver loss.  A file-mode run
        # throttles losslessly by design — backpressure on the reader
        # is the correct outcome, not a reason to drop science output —
        # so there a slow or even wedged sink stalls (bounded by
        # shutdown_join_timeout_s / the fetch deadline), never sheds.
        real_time = not cfg.input_file_path

        def shed_segment(seg, in_flight: bool) -> None:
            """Account one shed segment as explicit loss (counter +
            loss window) and return its host buffer to the reader pool
            (file mode — sinks never retained it); ``in_flight`` frees
            the window slot the sink will never release.  A shed also
            breaks ring-carry continuity: the next dispatched
            segment's overlap head is no longer the tail of the last
            DISPATCHED segment, so the carry is invalidated and the
            next dispatch re-arms cold (an undispatched shed breaks
            the source-adjacency chain; an in-flight shed is just
            conservative hygiene, at one full upload's cost)."""
            self._account_dropped(trace=getattr(seg, "trace_id", 0))
            self._ring_invalidate()
            if in_flight:
                live_add(-1)
            # staging release first, reader pool second — same id-reuse
            # ordering rule as _drain_body.  Releasing is safe on every
            # shed path: an undispatched shed never staged (no-op), and
            # every in-flight shed (wedged-sink / bounded-shutdown)
            # sheds a FETCHED item, so the program that consumed the
            # staged transfer has provably completed.
            rel = getattr(self.processor, "release_staging", None)
            if rel is not None:
                rel(self._device_bytes(seg))
            pool = getattr(self.source, "pool", None)
            if pool is not None and cfg.input_file_path:
                pool.release(seg.data)

        def push_sink(item) -> bool:
            """Bounded push to the sink pipe: blocks while the queue is
            full (the engine's backpressure point — sinks falling
            behind transitively stalls ingest, which a lossy source
            surfaces as accounted loss), but bails out if the sink
            thread crashed while the queue was full — WorkQueue.push's
            stop-token loop cannot see a dead consumer.  With the
            watchdog armed, a sink pipe *wedged* (alive but stuck, ZERO
            drain progress) past the segment deadline sheds this
            segment as accounted loss instead of stalling the engine
            forever (the ladder's whole-segment rung).  Drain progress
            resets the clock, at per-sink-push granularity (the
            heartbeat), not per drained item: a slow-but-healthy
            multi-sink flush keeps showing progress, and only a SINGLE
            write stalled past the deadline reads as a wedge — size
            ``segment_deadline_s`` above the largest expected single
            flush.  Same rule as the parked-window wait below."""
            t0 = time.perf_counter()
            progress0 = (drained[0], self._sink_heartbeat)
            while not q_sink.push_lossy(item):
                sink_wait[0] = True
                if not sink_alive() or stop.stop_requested:
                    return False
                if watchdog and real_time and item is not fw.SENTINEL:
                    cur = (drained[0], self._sink_heartbeat)
                    if cur != progress0:
                        t0, progress0 = time.perf_counter(), cur
                    elif time.perf_counter() - t0 > deadline_s:
                        log.error(
                            "[watchdog] sink pipe wedged past "
                            f"{deadline_s:g}s with no drain progress: "
                            "shedding segment as accounted loss")
                        self._incident(
                            "sink_wedge",
                            trace=getattr(item[0], "trace_id", 0),
                            reason=f"sink pipe wedged > {deadline_s:g}s"
                                   " with no drain progress")
                        # sink_f will never see this item
                        shed_segment(item[0], in_flight=True)
                        return True
                time.sleep(0.002)
            return True

        def emit(fetched) -> bool:
            # graceful degradation: one ladder observation per emitted
            # segment, on the ENGINE side.  The pressure signal is
            # "the engine had to wait on the sink since the last emit"
            # (a full queue at push, or the whole window parked in the
            # sink backlog) — queue size alone reads 0 the instant the
            # sink pops, hiding a sink-bound pipeline — plus whether
            # accounted segment loss is currently happening.  The
            # level rides with the item so the sink side sheds
            # consistently with what was observed.
            level = 0
            if self._ladder is not None:
                if not real_time:
                    occupancy = 0.0
                elif sink_wait[0]:
                    occupancy = 1.0
                else:
                    occupancy = (q_sink.qsize() / window
                                 if sink_pipe is not None else 0.0)
                sink_wait[0] = False
                level = self._ladder.observe(
                    occupancy,
                    metrics.window("segments_dropped").sum() > 0)
            # level + the per-item sinks-done set (see _drain_body)
            fetched = fetched + (level, set())
            if sink_pipe is None:
                try:
                    self._drain_body(fetched, drained)
                finally:
                    live_add(-1)
                return True
            return push_sink(fetched)

        pending: collections.deque = collections.deque()
        it = iter(self.source)
        dispatched = [0]
        exhausted = [False]

        def want_more() -> bool:
            return (not exhausted[0]
                    and (max_segments is None
                         or dispatched[0] < max_segments))

        # ---- the reader stage: the sink pipe's mirror at the source's
        # end.  Where the pull holds the chip (below) segment k+1 is
        # pulled on a thread of its own while the loop dispatches and
        # fetches, and the loop takes the ready segment.  Exactly one
        # ahead: the loop asks for pull k+1 when it has taken segment k,
        # so one more pooled block is out and never two; dispatch stays
        # gated by the window.  The thread starts where the rule first
        # engages and ends with the run.
        q_pull = fw.WorkQueue(capacity=1)
        q_ready = fw.WorkQueue(capacity=1)
        reader = [None]
        reader_stop = fw.StopToken()
        asked = [None]     # index of the pull the reader holds
        fetches = [0]      # results fetched by this loop, this run
        pull_s: collections.deque = collections.deque(
            maxlen=_PACE_SAMPLES)
        take_gap_s: collections.deque = collections.deque(
            maxlen=_PACE_SAMPLES)
        last_take = [None]  # (clock, fetches[0]) at the previous take

        def reader_f(_stop, index):
            # a pull that raises ends the pipe, which hands the loop
            # the sentinel; (None,) is the source's end
            return self._pull(it, index) or (None,)

        def pull_ahead_pays(index: int) -> bool:
            """Whether the reader should pull segment ``index + 1``
            now.  It pays where pull + dispatch + upload outlast the
            device's step, and costs a step of latency where they do
            not; the loop sees the pull's seconds and the period
            between its own takes, both the same whether it pulls ahead
            or not, so the rule does not flap: ahead while the pull is
            over ``_PULL_AHEAD_SHARE`` of the period (medians of the
            last few; a period is counted only across a fetch, so the
            takes that fill the window at a run's start say nothing
            and every run starts as the serial loop).  ``max_segments``
            bounds pulls, not only dispatches."""
            if max_segments is not None and index + 1 >= max_segments:
                return False
            return bool(take_gap_s) and statistics.median(pull_s) \
                > _PULL_AHEAD_SHARE * statistics.median(take_gap_s)

        def ingest_one(index: int):
            """The next segment, pulled here or taken from the reader
            that pulled it ahead; returns (seg, ingest_seconds,
            offset_after_this_segment, seconds_waited_for_the_reader)
            or None when exhausted."""
            wait_s = 0.0
            if asked[0] is None:
                one = self._pull(it, index)
            else:
                asked[0] = None
                with stage_span("ingest_wait", self.stage_timer) as sp:
                    one = q_ready.pop()
                    if one is fw.SENTINEL or one[0] is None:
                        sp.cancel()
                if one is fw.SENTINEL:
                    # the pull raised on the reader's thread: out of
                    # run() as if this thread had made it
                    raise reader[0].exception
                if one[0] is None:
                    one = None
                else:
                    wait_s = sp.seconds
                    metrics.add("ingest_ahead")
            if one is None:
                exhausted[0] = True
                return None
            now = time.perf_counter()
            pull_s.append(one[1])
            if last_take[0] is not None and fetches[0] > last_take[0][1]:
                take_gap_s.append(now - last_take[0][0])
            last_take[0] = (now, fetches[0])
            if pull_ahead_pays(index):
                if reader[0] is None:
                    reader[0] = fw.start_pipe(reader_f, q_pull, q_ready,
                                              reader_stop, "reader")
                asked[0] = index + 1
                q_pull.push(index + 1)
            return one + (wait_s,)

        # dispatch granularity: a micro-batch lands B segments at once,
        # so admission is gated on the whole unit fitting the window —
        # in-flight depth never exceeds inflight_segments.  The unit is
        # DYNAMIC: the self-healing ladder's first rung drops the
        # micro-batch, and the engine's admission/dispatch unit must
        # follow the active plan (the demoted processor has no batch
        # programs).
        def cur_unit() -> int:
            if self.healer is not None:
                return min(window, self.healer.micro_batch)
            return batch

        san = self.sanitizer

        # ---- self-healing compute: the dispatch/fetch fault handlers.
        # heal() is called ONLY from exception handlers — a healthy run
        # never reaches any of this.

        def reinit_and_redispatch(exc) -> bool:
            """Device-halt recovery: every in-flight device buffer and
            compiled handle on the halted backend is suspect.  Budget-
            checked by the healer's device_reinit supervisor; on
            approval: drop the jit/compile caches bound to the old
            backend handle (jax.clear_caches), swap in a freshly built
            processor at the current rung (no loaded AOT executables,
            no warm state; the swap also invalidates the warm
            ingest-ring carry, so the next warm-eligible dispatch goes
            COLD instead of assembling against a dead device buffer),
            then re-dispatch every in-flight segment cold from its
            retained host buffer, in dispatch order — journal order
            and checkpoint resume offsets are unchanged, exactly like
            a watchdog requeue."""
            h = self.healer
            newp = h.reinit(exc)
            if newp is None:
                return False  # budget spent: escalate
            try:
                jax.clear_caches()
            except Exception as e:  # version drift must not block
                log.warning(f"[selfheal] jax.clear_caches failed "
                            f"({e!r}); proceeding with the rebuild")
            self._swap_processor(newp)
            for i in range(len(pending)):
                pending[i] = redispatch(pending[i])
            return True

        def heal(exc) -> bool:
            """True when a device-classified fault was recovered (the
            active processor may have been swapped).  False propagates
            the ORIGINAL failure (not a device fault / healing off).
            A spent budget raises the typed FATAL escalation instead —
            the escaped exception must classify FATAL, not DEVICE, or
            an outer supervisor would keep restarting a permanently
            OOMing run."""
            from srtb_tpu.resilience.errors import (LadderExhausted,
                                                    ReinitBudgetExceeded)
            h = self.healer
            if h is None:
                return False
            kind = h.classify(exc)
            if kind is None:
                return False
            events.emit("fault.device",
                        info=f"{kind}:{type(exc).__name__}")
            if kind == DEVICE_HALT:
                if reinit_and_redispatch(exc):
                    return True
                self._incident(
                    "reinit_budget_exceeded",
                    reason=f"device halt beyond reinit budget: {exc}")
                raise ReinitBudgetExceeded(
                    "device halt beyond reinit recovery "
                    "(device_reinit_max budget spent or disabled): "
                    f"{exc}") from exc
            newp = h.demote(exc, kind)
            if newp is None:
                self._incident(
                    "ladder_exhausted",
                    reason=f"device fault survived every rung: {exc}")
                raise LadderExhausted(
                    f"device fault survived every demotion rung: "
                    f"{exc}") from exc
            self._swap_processor(newp)
            return True

        def dispatch_one(seg, ingest_s, offset_after, index,
                         requeue=False, ingest_wait_s=0.0):
            """One segment dispatch with self-healing: a device-
            classified failure demotes/reinits and re-dispatches the
            SAME segment from its retained host buffer; anything else
            propagates.  The replacement dispatch is carry-isolated
            (``requeue=True``): the swap invalidated the ring, and a
            re-dispatched segment must never warm-assemble."""
            while True:
                try:
                    return self._dispatch_segment(
                        seg, ingest_s, offset_after, index,
                        requeue=requeue, ingest_wait_s=ingest_wait_s)
                except BaseException as e:  # noqa: BLE001 — classified
                    if not heal(e):
                        raise
                    requeue = True

        def redispatch(item):
            """An in-flight record dispatched again from its retained
            host buffer, cold and carry-isolated; what its pull took
            stays on its span."""
            seg, _wf, _det, offset_after, span, _t0, index = item
            return dispatch_one(seg, span["ingest"], offset_after,
                                index, requeue=True,
                                ingest_wait_s=span.get("ingest_wait",
                                                       0.0))

        def maybe_promote() -> None:
            """Promotion probe: after promote_after_segments healthy
            drains on a demoted plan, step one rung back up before
            admitting the next segment — the next dispatch probes the
            richer plan; a recurring fault demotes again via heal()."""
            h = self.healer
            if h is not None and h.promote_due():
                newp = h.promote()
                if newp is not None:
                    self._swap_processor(newp)

        def fill_window() -> None:
            if san is not None:
                # dispatch-window state (pending deque, dispatch
                # counters) is owned by the run() thread
                san.assert_owner("inflight_window")
            while live_count() + cur_unit() <= window and want_more() \
                    and sink_alive():
                maybe_promote()
                b = cur_unit()
                if live_count() + b > window:
                    # the promotion probe restored the micro-batch and
                    # the bigger unit no longer fits: drain first (the
                    # in-flight depth bound holds across promotions)
                    return
                if b > 1:
                    budget = b if max_segments is None else \
                        min(b, max_segments - dispatched[0])
                    got = []
                    while len(got) < budget:
                        one = ingest_one(dispatched[0] + len(got))
                        if one is None:
                            break
                        got.append(one)
                    if not got:
                        return
                    segs, ingests, offsets, waits = map(list, zip(*got))
                    if len(segs) == b:
                        try:
                            items = self._dispatch_micro_batch(
                                segs, ingests, offsets, dispatched[0],
                                ingest_waits=waits)
                        except BaseException as e:  # noqa: BLE001
                            if not heal(e):
                                raise
                            # the healed plan may no longer micro-
                            # batch: finish these segments as single
                            # cold dispatches (the tail path below
                            # proves the single-segment plan is
                            # result-compatible)
                            items = [dispatch_one(s, dt, off,
                                                  dispatched[0] + i,
                                                  requeue=True,
                                                  ingest_wait_s=w)
                                     for i, (s, dt, off, w)
                                     in enumerate(got)]
                    else:  # tail shorter than B: single-segment plan
                        items = [dispatch_one(s, dt, off,
                                              dispatched[0] + i,
                                              ingest_wait_s=w)
                                 for i, (s, dt, off, w)
                                 in enumerate(got)]
                    pending.extend(items)
                    live_add(len(segs))
                    dispatched[0] += len(segs)
                    self.stats.segments += len(segs)
                    self.stats.samples += n_samples_per_seg * len(segs)
                else:
                    one = ingest_one(dispatched[0])
                    if one is None:
                        return
                    seg, dt, off, wait_s = one
                    pending.append(
                        dispatch_one(seg, dt, off, dispatched[0],
                                     ingest_wait_s=wait_s))
                    live_add(1)
                    dispatched[0] += 1
                    self.stats.segments += 1
                    self.stats.samples += n_samples_per_seg

        def sink_slot_about_to_free() -> bool:
            """The segment just fetched is still with the sink thread,
            whose return frees its window slot: for a quiet segment
            tens of microseconds after the push.  Wait for it as long
            as the oldest segment in flight has not finished on the
            device, before blocking on that fetch: the fetch lasts what
            is left of a device period with nothing queued behind it,
            so the device then idles for the next segment's pull and
            upload (+145 ms on a 759 ms period at 2^30 samples, four or
            five times in a 20 s window whenever this thread won the
            race; PERF.md section 6, PR 40), and both slots free
            together afterwards, so the window runs in pairs from then
            on.  Until PR 49 the wait was 5 ms flat; a sink's thread
            that drops a segment's mapping (6.6 ms for 268 MB the chip
            has read, io/file_input.py) outlasts that, and every pair
            of steps then cost an upload's 70 ms of idle device
            (PERF.md section 6, PR 49, call H).  Waiting costs nothing
            while the device works on the oldest: its fetch could not
            return sooner.  A sink that takes longer than that (a
            candidate's files) leaves the loop as it was: block on the
            oldest, the in-order point; so does a deadline that runs
            out with neither the sink nor the device moving (the fetch
            is what raises it)."""
            t_wait = time.perf_counter()
            while live_count() > len(pending) and sink_alive() \
                    and not self._result_ready(pending[0][2]) \
                    and (deadline_s <= 0
                         or time.perf_counter() - t_wait < deadline_s):
                time.sleep(0.0002)
            return live_count() + cur_unit() <= window

        requeue_counts: dict[int, int] = {}

        def watchdog_wait() -> bool:
            """Segment watchdog: poll the oldest in-flight segment's
            readiness up to the deadline, measured from when the
            engine starts WAITING on it here (becoming the drain
            head) — not from its dispatch: with a deep window or a
            micro-batch, a segment healthily queues behind earlier
            in-flight work for several compute times, and charging
            that queue wait against the deadline would fire spurious
            requeues (and eventually escalate) on a perfectly healthy
            device.  On expiry, cancel it (drop the device handles —
            JAX cannot abort an enqueued program, but the results are
            never read) and re-dispatch from the retained host
            buffer, up to ``segment_watchdog_requeues`` times, then
            escalate.  Every requeue is accounted
            (``watchdog_requeues``).  Returns False when the sink
            died while waiting."""
            item = pending[0]
            waited_since = time.perf_counter()
            while not self._result_ready(item[2]):
                if not sink_alive() or stop.stop_requested:
                    return False
                if time.perf_counter() - waited_since >= deadline_s:
                    index = item[6]
                    used = requeue_counts.get(index, 0)
                    tid = getattr(item[0], "trace_id", 0)
                    if used >= watchdog_max:
                        events.emit("watchdog.escalate", trace=tid,
                                    stream=self.stream, seg=index,
                                    info=f"requeues={used}")
                        self._incident(
                            "watchdog_escalation", trace=tid,
                            reason=f"segment {index} wedged through "
                                   f"{used} requeue(s)")
                        raise WatchdogEscalation(
                            f"segment {index} fetch still not ready "
                            f"after {deadline_s:g}s at the drain head "
                            f"and {used} requeue(s): device wedged")
                    requeue_counts[index] = used + 1
                    metrics.add("watchdog_requeues")
                    events.emit("watchdog.requeue", trace=tid,
                                stream=self.stream, seg=index,
                                info=f"attempt={used + 1}")
                    log.warning(
                        f"[watchdog] segment {index} in-flight past "
                        f"{deadline_s:g}s (fetch never ready): "
                        f"cancelling and re-dispatching "
                        f"({used + 1}/{watchdog_max})")
                    # ring: the wedged device may never materialize the
                    # in-flight carry chain — invalidate so the next
                    # FRESH dispatch goes cold too, and re-dispatch
                    # this segment cold + carry-isolated from its
                    # retained full host buffer (bit-identical)
                    self._ring_invalidate()
                    # healed re-dispatch: a requeue onto a faulty plan
                    # (the wedge WAS an OOM in disguise, or the probe
                    # plan broke) demotes and retries instead of
                    # re-wedging through the whole requeue budget
                    item = redispatch(item)
                    pending[0] = item
                    waited_since = time.perf_counter()
                else:
                    time.sleep(min(0.005, deadline_s / 20))
            return True

        def drain_oldest() -> bool:
            if san is not None:
                san.assert_owner("inflight_window")
            if watchdog and not watchdog_wait():
                return False
            # journaled depths, both captured AT drain time including
            # the item being drained (a full window journals as W, not
            # a perpetual W-1): queue_depth = dispatched-not-yet-
            # fetched, inflight_depth = dispatched-through-sink (the
            # gauge's definition — fetched-but-unsunk items on the
            # sink pipe still hold device waterfalls)
            depth = len(pending)
            live_now = live_count()
            item = pending.popleft()
            while True:
                try:
                    fetched = self._fetch_inflight(item, depth,
                                                   live_now)
                    break
                except BaseException as e:  # noqa: BLE001 — classified
                    if not heal(e):
                        raise
                    # the faulted segment's device results died with
                    # the fault: re-dispatch it cold from the retained
                    # host buffer under the (possibly demoted /
                    # reinitialized) plan, then fetch again
                    item = redispatch(item)
            fetches[0] += 1
            h = self.healer
            if h is not None:
                h.note_healthy()
            return emit(fetched)

        # watchdog state for a fully-parked window: [since, progress
        # marker] — same per-sink-push progress rule as push_sink
        parked = [None, (drained[0], self._sink_heartbeat)]

        def shed_ingest() -> bool:
            """Wedged sink with the whole window parked: keep draining
            the source (the never-stall-on-loss property) and account
            each undispatched segment as loss.  False = source done.

            The shed segment still consumes its dispatch index: a
            ``max_segments``-bounded run (soak harness, tests) must
            terminate even while shedding, and an indexed fault plan
            must keep addressing later segments — only the window
            slot and the stats/samples counters (it was never
            processed) are skipped."""
            one = ingest_one(dispatched[0])
            if one is None:
                return False
            dispatched[0] += 1
            log.error("[watchdog] sink wedged with a full in-flight "
                      "window: shedding ingested segment as accounted "
                      "loss")
            self._incident(
                "sink_wedge",
                trace=getattr(one[0], "trace_id", 0),
                reason="whole window parked behind a wedged sink; "
                       "shedding ingest as accounted loss")
            events.emit("shed.ingest",
                        trace=getattr(one[0], "trace_id", 0),
                        stream=self.stream, seg=dispatched[0] - 1)
            # never dispatched, so it holds no window slot
            shed_segment(one[0], in_flight=False)
            return True

        sink_wedged = False
        try:
            while sink_alive():
                fill_window()
                if not pending:
                    if want_more() and live_count() > 0 and sink_alive():
                        # the whole window is parked in the sink
                        # backlog: wait for the sink to free a slot —
                        # bounded by the watchdog (when armed): zero
                        # drain progress past the deadline means a
                        # wedged sink, and the source must keep
                        # draining with accounted loss, never stall
                        sink_wait[0] = True
                        if watchdog and real_time:
                            now = time.perf_counter()
                            cur = (drained[0], self._sink_heartbeat)
                            if parked[0] is None or cur != parked[1]:
                                parked[0], parked[1] = now, cur
                            elif now - parked[0] > deadline_s:
                                if not shed_ingest():
                                    break
                                continue
                        time.sleep(0.002)
                        continue
                    break
                parked[0] = None
                # non-blocking drain: everything already materialized
                # goes straight to the sink side, in order
                while pending and sink_alive() \
                        and self._result_ready(pending[0][2]):
                    if not drain_oldest():
                        break
                if not pending:
                    continue
                # window too full to admit the next dispatch unit (or
                # source done): block on the oldest — the in-order
                # point where overlap is actually earned
                if live_count() + cur_unit() > window \
                        or not want_more():
                    if want_more() and sink_slot_about_to_free():
                        continue
                    if not drain_oldest():
                        break
            while pending and sink_alive():
                if not drain_oldest():
                    break
        finally:
            join_s = float(getattr(cfg, "shutdown_join_timeout_s", 0)
                           or 0)
            if reader[0] is not None:
                # the reader ends with the run.  A run that returns has
                # taken every pull it asked for (an asked pull keeps
                # want_more() true), so only a run that raises can leave
                # one behind: its block goes back to the pool.  The join
                # is bounded like the sink's: a real-time source may
                # block in its pull until its stream ends.
                reader_stop.request_stop()
                q_pull.push_lossy(fw.SENTINEL)   # wakes an idle reader
                if not reader[0].join(join_s if join_s > 0 else None):
                    from srtb_tpu.utils import termination
                    termination.report_wedged(
                        [reader[0].thread],
                        f"pipeline shutdown ({join_s:g}s join timeout)")
                left = q_ready.try_pop()
                if left is not None and left is not fw.SENTINEL \
                        and left[0] is not None:
                    log.warning("[pipeline] the run ended with a segment "
                                "pulled ahead and never dispatched")
                    pool = getattr(self.source, "pool", None)
                    if pool is not None and cfg.input_file_path:
                        pool.release(left[0].data)
            if sink_pipe is not None:
                # bounded sentinel push: a sink wedged with a full
                # queue can never accept the sentinel — give up after
                # the join budget instead of hanging shutdown on it
                t_sent = time.perf_counter()
                while not q_sink.push_lossy(fw.SENTINEL):
                    if not sink_alive() or stop.stop_requested:
                        break
                    if join_s > 0 and \
                            time.perf_counter() - t_sent > join_s:
                        break
                    time.sleep(0.002)
                # bounded join: the sink may legitimately be flushing
                # a multi-GB waterfall (hence a generous default), but
                # a *wedged* pipe must not hang shutdown forever — on
                # expiry the thread is reported (name + stack) via
                # utils.termination and shutdown proceeds (it is a
                # daemon thread).  A *crashed* sink thread has already
                # exited, so this returns immediately in every failure
                # path.  0 keeps the legacy wait-forever behavior.
                sink_pipe.join(join_s if join_s > 0 else None)
                if sink_pipe.thread.is_alive():
                    sink_wedged = True
                    self._incident(
                        "sink_wedge_shutdown",
                        reason=f"sink pipe still alive after the "
                               f"{join_s:g}s shutdown join budget")
                    # flagged HERE, inside the finally: an exception
                    # escaping run() (fatal fault, watchdog
                    # escalation) still reaches close(), which must
                    # skip the wedged pool's drain or shutdown hangs
                    # on the very writes the bounded join gave up on
                    self._sink_wedged = True
                    from srtb_tpu.utils import termination
                    termination.report_wedged(
                        [sink_pipe.thread],
                        f"pipeline shutdown ({join_s:g}s join timeout)")
                    # items still parked on the sink queue will never
                    # reach a sink: account them as dropped (not
                    # silent loss) and return their host buffers
                    while True:
                        leftover = q_sink.try_pop()
                        if leftover is None:
                            break
                        if leftover is fw.SENTINEL:
                            continue
                        shed_segment(leftover[0], in_flight=True)
                    # the item the wedged worker holds mid-drain is
                    # loss too if it never reached accounting
                    # (sink_f's finally never runs): count it, or it
                    # vanishes — dispatched but neither journaled nor
                    # dropped.  Same already-accounted rule as the
                    # supervisor replay; its host buffer stays with
                    # the wedged thread, never back to the pool.  The
                    # "abandoned" marker in its sinks-done set hands
                    # the accounting over: should the worker unwedge
                    # during teardown and finish the drain, it must
                    # not ALSO journal/count the segment (and sink_f's
                    # finally must not re-release the live slot).
                    held = current[0]
                    if held is not None and held is not fw.SENTINEL:
                        # atomic with _drain_body's accounted/abandoned
                        # decision (self._handoff_lock): a worker
                        # unwedging at exactly this moment either
                        # claims the drain count first (drained moves
                        # past progress — no abandonment here) or sees
                        # the marker and skips its own accounting —
                        # never both
                        with self._handoff_lock:
                            if drained[0] == progress[0]:
                                held[-1].add("abandoned")
                                self._account_dropped(
                                    trace=getattr(held[0], "trace_id",
                                                  0))
                                live_add(-1)
                    log.error("[pipeline] wedged sink: still-queued "
                              "segments accounted as segments_dropped")
                stop.request_stop()
            metrics.set("inflight_depth", 0)
            # drop the carry's device buffer at run end (a retained
            # reserved-tail array would pin HBM between runs)
            self._ring_invalidate()
            if self.profile_capture is not None:
                # a run shorter than N segments (or one that raised)
                # still flushes a valid trace + sidecar
                self.profile_capture.stop()
        if sink_pipe is not None and sink_pipe.exception is not None:
            raise sink_pipe.exception
        if sink_wedged:
            # the bounded join already gave up on the wedged sink —
            # draining its writer pools would block on the very writes
            # that are stuck, hanging shutdown after promising not to
            # (self._sink_wedged was flagged in the finally above)
            log.error("[pipeline] skipping sink drain: sink pipe "
                      "wedged (queued async writes were NOT flushed)")
        else:
            self._drain_sinks()
        self.stats.elapsed_s = time.perf_counter() - start
        self.stats.extras["stages"] = self.stage_timer.summary()
        log.info(f"[pipeline] {self.stats.segments} segments, "
                 f"{self.stats.msamples_per_sec:.1f} Msamples/s")
        return self.stats

    def _sanitize_check(self, wf, det_res) -> None:
        """Per-segment sanitizer checks at the drain boundary: NaN/Inf
        tripwires plus the stacked-(re, im) waterfall contract."""
        from srtb_tpu.analysis import sanitizer as S
        S.check_finite("detect result", det_res)
        if wf is not None:
            S.check_contract("drained waterfall", wf, ndim=4, lead=2,
                             dtype=np.float32)
            S.check_finite("drained waterfall", wf)

    # overridable for tests; the default aborts through the installed
    # signal/termination handlers for a loud stacktrace (the reference's
    # fail-fast philosophy, ref: util/termination_handler.hpp:38-113)
    def _push_sinks(self, seg, wf, det_res, positive,
                    degrade_level: int = 0,
                    done: set | None = None,
                    seg_key: tuple | None = None) -> None:
        """Push to every sink, handing the waterfall only to sinks
        entitled to it: all of them under ``keep_waterfall``, else only
        sinks declaring ``wants_waterfall`` (a lossy GUI tap must not
        make every OTHER sink — e.g. the candidate writer, which dumps
        a multi-GB .npy per positive segment — start seeing
        waterfalls the plan chose not to keep).

        Degradation ladder: at level >= 1 the waterfall is withheld
        from every sink (the multi-GB dumps and GUI frames go first);
        at level >= 2 sinks marked ``sheddable`` (the candidate /
        baseband writers) are skipped entirely.  Both sheds are
        counted — degraded output must be visible on /metrics, never
        silent.

        ``done`` (when given) records the indices of sinks that
        already received this segment, and completed ones are skipped
        on re-entry: a retried or replayed push is exactly-once per
        sink, never a duplicate — an in-place appender
        (``WriteAllSink``) would otherwise corrupt its stream.

        ``seg_key`` is the durable half of the same guarantee: the
        ``(data_stream_id, drain index)`` the run manifest keys on.
        A sink whose group the manifest recovered as committed is
        skipped entirely (``replayed_skips`` — the in-memory done-set
        died with the crashed process, the manifest did not); every
        completed push seals a durable ``done`` record, and the sink
        logs intent/commit per artifact in between (io/manifest.py)."""
        if degrade_level >= 1 and wf is not None:
            wf = None
            # the "wf" marker in ``done`` (sink entries are ints, no
            # collision) keeps the counter exactly-once when a retried
            # or replayed push re-enters with the original waterfall
            if done is None or "wf" not in done:
                metrics.add("shed_waterfalls")
                if self._stream_labels is not None:
                    metrics.add("shed_waterfalls",
                                labels=self._stream_labels)
                if done is not None:
                    done.add("wf")
        full = SegmentResultWork(segment=seg, waterfall=wf,
                                 detect=det_res)
        light = full if self.keep_waterfall else SegmentResultWork(
            segment=seg, waterfall=None, detect=det_res)
        m = self.manifest
        canary = getattr(seg, "canary", None) is not None
        for i, sink in enumerate(self.sinks):
            if done is not None and i in done:
                continue
            if canary and not getattr(sink, "canary_exempt", False):
                # quarantine: results derived from the injected bytes
                # (the waterfall, the detect series) must never become
                # science artifacts — not even through the candidate
                # writer's negative piggybank.  Only sinks declaring
                # ``canary_exempt`` still receive the segment: the
                # contiguous baseband appender (WriteAllSink) sees the
                # PRISTINE seg.data and must keep its byte-stream
                # continuity (skipping it would corrupt the output,
                # not protect it).
                if done is not None:
                    done.add(i)
                continue
            key = None
            if m is not None and seg_key is not None:
                key = (seg_key[0], seg_key[1], self._sink_names[i])
                if m.is_done(key):
                    # committed by a previous life of this run: the
                    # crash landed between this sink's commit and the
                    # covering checkpoint, and replaying the push
                    # would duplicate the artifacts under fresh names
                    metrics.add("replayed_skips")
                    log.info(f"[manifest] segment {seg_key[1]} sink "
                             f"{self._sink_names[i]}: already "
                             "committed, skipping replay")
                    if done is not None:
                        done.add(i)
                    continue
            if degrade_level >= 2 and getattr(sink, "sheddable", False):
                metrics.add("shed_baseband")
                if self._stream_labels is not None:
                    metrics.add("shed_baseband",
                                labels=self._stream_labels)
                if done is not None:
                    done.add(i)
                continue
            if key is not None:
                setk = getattr(sink, "set_manifest_key", None)
                if setk is not None:
                    setk(key)
            give = self.keep_waterfall or getattr(
                sink, "wants_waterfall", False)
            sink.push(full if give else light, positive)
            if key is not None and getattr(sink, "last_push_wrote",
                                           True):
                # empty pushes skip the durable done record: a
                # replayed negative segment recomputes the same
                # decision and writes nothing — nothing to protect,
                # and the common all-negative observation keeps its
                # WAL to one record per segment
                m.sink_done(key)
            self._sink_heartbeat += 1
            if done is not None:
                done.add(i)

    def _take_sink_spans(self, span: dict) -> dict:
        """Add what the sinks timed inside this segment's "sink" stage
        (a candidate writer's ``d2h`` / ``write`` / ``publish`` and the
        children of ``write``) to its ``stages_ms``, and return what a
        candidate writer counted beside them (``candidate_bytes``,
        ``writer_file_ms``) for the record.  Only a sink that wrote has
        any: a quiet segment's record carries none."""
        candidate: dict = {}
        for sink in self.sinks:
            take = getattr(sink, "take_spans", None)
            if take is None:
                continue
            for name, seconds in take().items():
                span[name] = span.get(name, 0.0) + seconds
            for name, value in sink.take_candidate().items():
                candidate[name] = candidate.get(name, 0) + value
        return candidate

    def _on_segment_deadline(self) -> None:  # pragma: no cover - aborts
        _abort_on_deadline(self.cfg.segment_deadline_s)

    def _sync_with_deadline(self, fn):
        """Run a blocking device fetch under cfg.segment_deadline_s."""
        return sync_with_deadline(self.cfg.segment_deadline_s, fn,
                                  self._on_segment_deadline)

    def _fetch_device(self, item, index: int = 0):
        """Resolve one (seg, wf, det_res, offset) drain item's device
        handles to host data, with the fail-fast deadline scoped to the
        *device fetches only*: those are what a wedged accelerator
        blocks.  Sink pushes and checkpoint flushes are host disk I/O —
        a slow-but-healthy disk flush of a multi-GB waterfall must not
        SIGABRT the observation — so they run with no timer armed.

        The detect results (a few KB) are fetched eagerly.  The waterfall
        can be multi-GB and most sinks never read it (WriteSignalSink only
        touches it for written segments), so it is wrapped in a lazy proxy
        whose eventual ``np.asarray`` still runs under the deadline.

        The timed "fetch" stage therefore covers the blocking detect
        fetch (= device completion of the whole segment program); a lazy
        waterfall transfer lands in the consuming sink's time."""
        seg, wf, det_res, offset_after, span = item
        if self.events is not None:
            events.set_current(getattr(seg, "trace_id", 0),
                               self.stream)
        with stage_span("fetch", self.stage_timer,
                        getattr(seg, "trace_id", 0)) as sp:
            # explicit D2H (device_get) — this is the engine's one
            # sanctioned blocking fetch; implicit np.asarray here
            # would trip the sanitizer's transfer guard.  Under the
            # "fetch" fault site: device_get of the same handles is
            # idempotent, so a transient failure simply re-fetches.
            det_res = self._op(
                "fetch", index,
                lambda: self._sync_with_deadline(
                    lambda: jax.device_get(det_res)))
        span["fetch"] = sp.seconds
        if self.events is not None:
            self.events.emit("stage.fetch",
                             trace=getattr(seg, "trace_id", 0),
                             stream=self.stream, seg=index,
                             dur=span["fetch"])
        if wf is not None and self.cfg.segment_deadline_s > 0:
            wf = _DeadlineArray(wf, self._sync_with_deadline)
        return seg, wf, det_res, offset_after, span

    def _drain_sinks(self) -> None:
        for sink in self.sinks:
            if hasattr(sink, "drain"):
                sink.drain()  # async writer pool: wait for disk

    def close(self) -> None:
        """Release runtime resources (the owned writer-pool threads).
        The pool also self-finalizes at GC, so forgetting this leaks
        nothing — but explicit close gives deterministic shutdown.
        After a bounded shutdown gave up on a wedged sink, the pool is
        abandoned instead of drained (same bounded-exit contract)."""
        if self.profile_capture is not None:
            # idempotent: a crashed threaded run may not have reached
            # its engine-side stop
            self.profile_capture.stop()
        if self._owned_writer_pool is not None:
            self._owned_writer_pool.close(drain=not self._sink_wedged)
            self._owned_writer_pool = None
        if self.manifest is not None:
            self.manifest.close()
            self.manifest = None
        if self.journal is not None:
            self.journal.close()
            self.journal = None
        dump_path = getattr(self.cfg, "events_dump_path", "")
        if dump_path and self.events is not None:
            # persist the flight recorder's view of this run (ring-
            # bounded: the LAST events_ring_size events per thread) —
            # the input of `python -m srtb_tpu.tools.trace_export`
            try:
                n = self.events.dump_jsonl(dump_path)
                log.info(f"[events] {n} flight-recorder events -> "
                         f"{dump_path}")
            except OSError as e:
                log.warning(f"[events] dump to {dump_path} failed: "
                            f"{e}")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


@dataclass
class _GridStep:
    """One step of the ``--dm_list`` loop between its pull and its
    record: the loop owns ``seg``'s host buffer for as long as the step
    is in ``DMSearchPipeline.run``'s window."""
    index: int
    seg: SegmentWork
    pool: object            # where seg.data goes back to; None: nowhere
    trace_id: int
    stages: dict            # this segment's own five spans, seconds
    result: object = None   # device handles, once the step is enqueued

    def release(self) -> None:
        if self.pool is not None:
            self.pool.release(self.seg.data)


class DMSearchPipeline:
    """Streaming DM search: every segment runs the full multi-chip
    (dm x seq)-sharded step (parallel.segment_dist) over a DM trial grid
    (its chirp bank is made once, at construction); per-trial summaries go
    to ``<prefix>dm_trials.jsonl``, the best is logged.  The capability the
    reference leaves as a TODO ("DM search list for unknown source",
    ref: config.hpp:129-132), made practical by chip-parallel trials.

    The loop keeps a window of ``Config.inflight_segments`` steps (the
    option ``Pipeline`` reads for its own window; 2 by default): segment
    k+1 is pulled, uploaded and its step enqueued while the chips run
    step k, and only then are step k's results fetched, its record
    written and flushed, its span journalled.  The jit call returns with
    the step still running, so the pull (``readinto`` gives up the
    interpreter lock) and the upload run under the chips' time on the
    loop's own thread.  Records come one a segment, in hand-over order,
    each flushed as it is written.  ``inflight_segments = 1`` is the
    serial loop, step for step: ingest -> h2d -> enqueue -> fetch ->
    record with nothing overlapped.

    Who owns a segment's host buffer: the loop, from the pull to the
    end of *that segment's* fetch, so up to ``inflight_segments``
    buffers are out at once.  ``stage_input`` returns with the uploads
    pending and they read the buffer; once the step's results are back
    every chip has consumed its input, and the loop returns the buffer
    to the source's pool (file input with a pooled reader, the
    condition ``Pipeline`` uses) so a later pull fills a warm block.
    Every way out gives every buffer back: the segment ``max_segments``
    drops, the steps in flight at the source's end or at
    ``max_segments`` (retired: fetched and recorded), and the steps in
    flight when any of them raises (abandoned).
    """

    @_under_construct_span
    def __init__(self, cfg: Config, source=None, mesh=None):
        import jax as _jax

        from srtb_tpu.parallel import mesh as M
        from srtb_tpu.parallel.segment_dist import DistSegmentProcessor

        self.cfg = cfg
        self.dm_list = list(cfg.dm_list) or [cfg.dm]
        if mesh is None:
            n_dev = len(_jax.devices()) if cfg.n_devices == 0 \
                else cfg.n_devices
            # largest dm-axis size that divides both trials and devices
            n_dm = 1
            for d in range(min(n_dev, len(self.dm_list)), 0, -1):
                if len(self.dm_list) % d == 0 and n_dev % d == 0:
                    n_dm = d
                    break
            mesh = M.make_mesh(n_dm=n_dm, n_seq=1)
        self.mesh = mesh
        self.processor = DistSegmentProcessor(
            cfg, mesh, self.dm_list, stage_timer=self.stage_timer)
        if source is None:
            source = BasebandFileReader(cfg)
        self.source = source
        self.trials_path = cfg.baseband_output_file_prefix + \
            "dm_trials.jsonl"
        self.stats = PipelineStats()
        # the same spans, timer and journal as Pipeline: ``construct``
        # around this constructor (the grid processor's ``chirp_bank``
        # inside it), then each segment's own five host stages (ingest,
        # h2d, enqueue, fetch, record), flat siblings on the loop's
        # thread; what overlaps is the chips' step k with the first
        # three of segment k+1
        self.journal = telemetry.SpanJournal.from_config(cfg)

    def run(self, max_segments: int | None = None) -> PipelineStats:
        cfg = self.cfg
        timer = self.stage_timer
        proc = self.processor
        start = time.perf_counter()
        depth = max(1, int(cfg.inflight_segments))
        # multi-controller runs: summaries are replicated, so only the
        # first process records them (all write identical content);
        # every process makes the same device calls in the same order
        write_records = jax.process_index() == 0
        it = iter(self.source)
        window: collections.deque[_GridStep] = collections.deque()
        before = self.stats.segments
        ahead = 0
        pool = None
        with open(self.trials_path if write_records else os.devnull,
                  "a") as trials_file:
            try:
                for i in itertools.count():
                    with stage_span("ingest", timer) as sp:
                        seg = next(it, None)
                        if seg is None:
                            sp.cancel()
                    if seg is None:
                        break
                    # looked up at the pull: a source may swap its
                    # reader between passes and drop it when it ends
                    pool = getattr(self.source, "pool", None) \
                        if cfg.input_file_path else None
                    step = _GridStep(i, seg, pool, _stamp_trace_id(seg),
                                     {"ingest": sp.seconds})
                    if max_segments is not None and i >= max_segments:
                        step.release()
                        break
                    window.append(step)
                    tid = step.trace_id
                    with stage_span("h2d", timer, tid) as sp:
                        staged = proc.stage_input(seg.data)
                    step.stages["h2d"] = sp.seconds
                    with stage_span("enqueue", timer, tid) as sp:
                        step.result = proc.process(staged)
                    step.stages["enqueue"] = sp.seconds
                    if len(window) > 1:
                        # the chips still hold an earlier step: this
                        # one waits in their queue, not for the host
                        ahead += 1
                        metrics.add("grid_steps_ahead")
                    while len(window) >= depth:
                        self._retire(window.popleft(), trials_file)
                while window:
                    self._retire(window.popleft(), trials_file)
            finally:
                # the steps in flight when one raised: abandoned (a
                # step _retire took has had its buffer given back)
                for step in window:
                    step.release()
        self.stats.elapsed_s = time.perf_counter() - start
        line = (f"[dm_search] {self.stats.segments} segments, "
                f"{self.stats.segments - before} of them in this run at "
                f"window {depth}: grid_steps_ahead {ahead}")
        if pool is not None:
            ps = pool.stats()
            line += (f"; reader pool {pool.name}: {ps['acquires']} "
                     f"acquires, {ps['new_blocks']} new blocks")
        log.info(line)
        _log_setup_once(self)
        return self.stats

    def _retire(self, step: _GridStep, trials_file) -> None:
        """The oldest step of the window: its results fetched, its host
        buffer given back, its record written and flushed, its span
        journalled."""
        import json

        cfg = self.cfg
        timer = self.stage_timer
        seg, tid, stages, res = (step.seg, step.trace_id, step.stages,
                                 step.result)
        try:
            # reduce over (stream, boxcar) axes -> per-dm quantities;
            # every device transfer runs under the fail-fast deadline
            # (a wedged device blocks transfers, not just compute)
            with stage_span("fetch", timer, tid) as sp:
                peaks, counts, zero = sync_with_deadline(
                    cfg.segment_deadline_s,
                    lambda: (jax.device_get(res.snr_peaks),
                             jax.device_get(res.signal_counts),
                             jax.device_get(res.zero_count)))
            stages["fetch"] = sp.seconds
        finally:
            # not before its own fetch: until the results are back a
            # pending upload may still read the buffer, and a later
            # pull would refill it
            step.release()
        n_dm = len(self.dm_list)
        peaks = peaks.reshape(n_dm, -1)
        counts = counts.reshape(n_dm, -1)
        zero = zero.reshape(n_dm, -1).max(axis=-1)
        ok = zero < (cfg.signal_detect_channel_threshold
                     * cfg.spectrum_channel_count)
        fired = counts.sum(axis=-1) > 0
        # rank trials by raw peak SNR: a matched trial concentrates
        # the pulse and may trip the SK zap gate, which only means
        # "be cautious", not "not the best DM"
        best = int(np.argmax(peaks.max(axis=-1)))
        record = {
            "segment": step.index,
            "timestamp": seg.timestamp,
            "best_dm": self.dm_list[best],
            "best_snr": float(peaks[best].max()),
            "dm_list": self.dm_list,
            "peak_snr": peaks.max(axis=-1).tolist(),
            "signal_counts": counts.sum(axis=-1).tolist(),
            "zero_counts": zero.tolist(),
        }
        with stage_span("record", timer, tid) as sp:
            trials_file.write(json.dumps(record) + "\n")
            trials_file.flush()
        stages["record"] = sp.seconds
        positive = bool((ok & fired).any())
        if positive:
            self.stats.signals += 1
            log.info(f"[dm_search] segment {step.index}: best dm "
                     f"{record['best_dm']} "
                     f"snr {record['best_snr']:.1f}")
        self.stats.segments += 1
        self.stats.samples += cfg.baseband_input_count
        metrics.add("segments")
        metrics.add("samples", cfg.baseband_input_count)
        metrics.window("segments").add(1)
        metrics.window("samples").add(cfg.baseband_input_count)
        telemetry.mark_segment()  # /healthz liveness
        if self.journal is not None:
            self.journal.write(telemetry.segment_span(
                step.index, stages, 0, int(counts.sum()), positive,
                cfg.baseband_input_count,
                timestamp_ns=getattr(seg, "timestamp", 0),
                trace_id=tid))

    def close(self) -> None:
        if self.journal is not None:
            self.journal.close()
            self.journal = None


class ThreadedPipeline(Pipeline):
    """Thread-per-host-stage variant using the framework module: ingest,
    device dispatch and result draining run concurrently over bounded
    queues — the closest analog of the reference's full pipe graph, useful
    when ingest (UDP parsing, disk reads) must overlap drain (writers).
    """

    def run(self, max_segments: int | None = None) -> PipelineStats:
        # Config.sanitize arms the same run scope as Pipeline.run
        # (transfer tripwire + leaked-thread check); the per-stage
        # thread-ownership guards don't apply to this engine — every
        # stage owning its own thread IS the design here
        try:
            if self.sanitizer is None:
                return self._run_threaded(max_segments)
            with self.sanitizer.run_scope():
                return self._run_threaded(max_segments)
        finally:
            _log_setup_once(self)

    def _run_threaded(self, max_segments: int | None = None) \
            -> PipelineStats:
        from srtb_tpu.pipeline import framework as fw

        cfg = self.cfg
        start_t = time.perf_counter()
        if self.profile_capture is not None:
            self.profile_capture.start()
        it = iter(self.source)
        count = [0]
        drained = [self.checkpoint.segments_done if self.checkpoint else 0]
        # same resume-continuous canary schedule as the async engine
        self._canary_base = drained[0]

        def source_f(stop_token, _):
            if max_segments is not None and count[0] >= max_segments:
                raise StopIteration
            one = self._pull(it, count[0])
            if one is None:
                raise StopIteration
            count[0] += 1
            # carry the ingest time, the source's offset after THIS
            # segment AND the ingest-order index with the work item:
            # the span is assembled across three threads, this one may
            # be any number of segments on when the others read, and
            # every fault/retry site downstream must address this
            # segment by the same index ingest used
            return one + (count[0] - 1,)

        def device_f(stop_token, item):
            from srtb_tpu.resilience.errors import LadderExhausted
            seg, ingest_dt, offset_after, index = item
            h = self.healer
            if h is not None and h.promote_due():
                # promotion probe, same pacing as the async engine
                # (note_healthy is bumped by the drain thread; an
                # off-by-one-segment probe is acceptable pacing slack)
                newp = h.promote()
                if newp is not None:
                    self._swap_processor(newp)
            if self.events is not None:
                events.set_current(getattr(seg, "trace_id", 0),
                                   self.stream)
            self._canary_prepare(seg, index)
            data = self._device_bytes(seg)
            with stage_span("dispatch", self.stage_timer,
                            getattr(seg, "trace_id", 0)) as sp:
                while True:
                    try:
                        wf, det_res = self._op(
                            "dispatch", index,
                            lambda: self.processor.process(data))
                        break
                    except BaseException as e:  # noqa: BLE001
                        # plan demotion works here exactly like the
                        # async engine: rebuild cheaper, re-dispatch
                        # the retained segment.  Device-HALT recovery
                        # does not — results already queued on q_res
                        # belong to the dead backend and this engine
                        # has no retained in-flight window to
                        # re-dispatch them from — so halts escalate
                        # (use the async engine for reinit coverage).
                        kind = h.classify(e) if h is not None else None
                        if kind is None or kind == DEVICE_HALT:
                            raise
                        newp = h.demote(e, kind)
                        if newp is None:
                            raise LadderExhausted(
                                "device fault survived every demotion "
                                f"rung: {e}") from e
                        self._swap_processor(newp)
            span = {"ingest": ingest_dt, "dispatch": sp.seconds}
            if self.events is not None:
                self.events.emit("stage.dispatch",
                                 trace=getattr(seg, "trace_id", 0),
                                 stream=self.stream, seg=index,
                                 dur=span["dispatch"])
            self.stats.segments += 1
            self.stats.samples += cfg.baseband_input_count
            return (seg, wf, det_res, offset_after, span, index)

        drain_busy = [False]

        def drain_f(stop_token, item):
            drain_busy[0] = True
            index = item[-1]
            try:
                fetched = self._fetch_device(item[:-1], index)
                if self.healer is not None:
                    # healthy-segment pacing for the promotion probe
                    # (consumed by device_f; an int bump under the GIL)
                    self.healer.note_healthy()
                return _drain_body(stop_token, fetched, index)
            finally:
                drain_busy[0] = False

        def _drain_body(stop_token, item, index):
            seg, wf, det_res, offset_after, span = item
            if self.events is not None:
                events.set_current(getattr(seg, "trace_id", 0),
                                   self.stream)
            if self.sanitizer is not None:
                self._sanitize_check(wf, det_res)
            positive = has_signal(
                cfg, det_res,
                frequency_bin_count=(wf.shape[-2] if wf is not None
                                     else None))
            done = set()  # retries stay exactly-once per sink
            cmark = getattr(seg, "canary", None)
            if cmark is not None:
                # same quarantine as the async engine's _drain_body
                positive = self._canary_drain(seg, cmark, det_res,
                                              done, drained[0])
            if positive:
                self.stats.signals += 1
            # ingest-order index for the fault/retry sites (the drain
            # counter below stays the journal's resume-continuous
            # numbering, same split as the async engine)
            seg_index = index
            mkey = (None if self.manifest is None
                    else (getattr(seg, "data_stream_id", 0),
                          drained[0]))
            with stage_span("sink", self.stage_timer,
                            getattr(seg, "trace_id", 0)) as sp:
                self._op("sink_write", seg_index,
                         lambda: self._push_sinks(seg, wf, det_res,
                                                  positive, done=done,
                                                  seg_key=mkey))
            span["sink"] = sp.seconds
            candidate = self._take_sink_spans(span)
            if self.events is not None:
                self.events.emit("stage.sink",
                                 trace=getattr(seg, "trace_id", 0),
                                 stream=self.stream, seg=index,
                                 dur=span["sink"],
                                 info="dump" if positive else "")
            pool = getattr(self.source, "pool", None)
            if pool is not None and cfg.input_file_path:
                pool.release(seg.data)
            drained[0] += 1
            # +1: the item being drained was already popped from q_res,
            # so qsize() alone would understate the in-flight depth
            self._record_segment(drained[0] - 1, seg, det_res, positive,
                                 span, queue_depth=q_res.qsize() + 1,
                                 n_samples=cfg.baseband_input_count,
                                 candidate=candidate)
            if self.checkpoint is not None:
                self._op("checkpoint", seg_index,
                         lambda: (self._drain_sinks(),
                                  self.checkpoint.update(drained[0],
                                                         offset_after)))
            return None

        stop = fw.StopToken()
        q_seg = fw.WorkQueue()
        q_res = fw.WorkQueue()
        pipes = [
            fw.start_pipe(source_f, None, q_seg, stop, "source"),
            fw.start_pipe(device_f, q_seg, q_res, stop, "device"),
            fw.start_pipe(drain_f, q_res, None, stop, "drain"),
        ]
        # wait for the drain pipe to see the sentinel.  This is the
        # COMPLETION wait — it lasts the whole observation, so it must
        # not itself be bounded by shutdown_join_timeout_s (that would
        # silently truncate any healthy run longer than the timeout).
        # The bound applies only to a WEDGE: the drain worker busy on
        # one item with zero per-sink-push progress (the heartbeat,
        # same rule as the async engine) for the whole budget.  An
        # idle drain waiting on a quiet source is healthy and waits
        # forever; a crashed source/device pipe propagates a sentinel
        # from its finally, so the drain still exits.
        join_s = float(getattr(cfg, "shutdown_join_timeout_s", 0) or 0)
        if join_s <= 0:
            pipes[2].join(None)
        else:
            last = (drained[0], self._sink_heartbeat)
            t0 = time.perf_counter()
            while not pipes[2].join(min(0.1, join_s / 10)):
                cur = (drained[0], self._sink_heartbeat)
                if not drain_busy[0] or cur != last:
                    last, t0 = cur, time.perf_counter()
                elif time.perf_counter() - t0 > join_s:
                    break
        wedged = fw.on_exit(stop, pipes)
        if pipes[2] in wedged:
            # same contract as the async engine: the wedged DRAIN
            # pipe's writer pools would block the final drain on the
            # stuck writes.  Only the drain pipe owns sink/writer
            # work — a wedged source or device (on_exit reported it)
            # must not cost the healthy sink side its final flush.
            # Flagged BEFORE the exception re-raise below so close()
            # still skips the wedged pool's drain when another pipe
            # crashed the run.
            self._sink_wedged = True
            log.error("[pipeline threaded] skipping sink drain: "
                      f"{[p.name for p in wedged]} wedged (queued "
                      "async writes were NOT flushed)")
        for p in pipes:
            if p.exception is not None:
                raise p.exception
        if not self._sink_wedged:
            self._drain_sinks()
        if self.profile_capture is not None:
            self.profile_capture.stop()
        self.stats.elapsed_s = time.perf_counter() - start_t
        self.stats.extras["stages"] = self.stage_timer.summary()
        log.info(f"[pipeline threaded] {self.stats.segments} segments, "
                 f"{self.stats.msamples_per_sec:.1f} Msamples/s")
        return self.stats
