"""Profiling/tracing hooks.

The reference has only ad-hoc timing (per-pipe debug logs, benchmark
harness in test-fft_wrappers, hand-recorded kernel timings — SURVEY.md
§5.1).  On TPU the native story is better: ``jax.profiler`` traces
(viewable in xprof/tensorboard) plus lightweight wall-clock stage timers.
"""

from __future__ import annotations

import threading
import time

from srtb_tpu.utils.logging import log


class span:
    """The one way to open a host span: a ``jax.profiler.TraceAnnotation``
    named ``srtb:<name>`` (so the span lands on the profiler's host
    plane, on the device trace's clock) plus one ``StageTimer`` record
    when ``timer`` is given.  ``trace_id`` (the segment's causal id,
    utils/events.py) rides along as an argument of the annotation, so
    the spans of one segment share an identifier in the trace as they do
    in the journal; 0 = not known yet (the ingest read stamps it after).

    ``seconds`` holds the duration once the block is left: callers put
    it into the segment's ``stages_ms`` instead of reading the timer's
    ``last`` back.  ``cancel()`` inside the block keeps the sample out
    of the timer (the terminal failed source read is no ingest).

    Outside a profiler session this costs two ``perf_counter`` reads and
    an inactive annotation (tests/test_stage_tracing.py holds it under
    20 us).  jax is imported on first use only, so pure-host tools that
    import this module stay free of the jax import cost."""

    __slots__ = ("name", "timer", "trace_id", "seconds", "_ann", "_t0")
    _annotation = None  # jax.profiler.TraceAnnotation, bound lazily

    def __init__(self, name: str, timer: "StageTimer | None" = None,
                 trace_id: int = 0):
        self.name = name
        self.timer = timer
        self.trace_id = trace_id
        self.seconds = 0.0

    def cancel(self) -> None:
        self.timer = None

    def __enter__(self) -> "span":
        ann = span._annotation
        if ann is None:
            import jax

            ann = span._annotation = jax.profiler.TraceAnnotation
        self._ann = ann(f"srtb:{self.name}", trace_id=self.trace_id) \
            if self.trace_id else ann(f"srtb:{self.name}")
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self.timer is not None:
            self.timer.record(self.name, self.seconds)


def first_dispatch(books: dict, program: str, fn,
                   timer: "StageTimer | None" = None,
                   stream_labels: dict | None = None):
    """``fn()``, with first-call accounting: ``books`` (a processor's
    ``first_dispatch_s``) maps each jitted program family already
    dispatched to the seconds its first call took, and a family in it
    pays one membership check.  The first call of ``program`` (where
    lazy jit traces, compiles or loads from the persistent cache, and
    enqueues the first run) runs under a span ``first_dispatch`` and
    its wall clock is booked: the unlabelled ``compile_seconds`` /
    ``plan_compiles`` / ``last_compile_ms`` as ever, the same two
    counters under ``{program=...}`` (their sum is the unlabelled total
    less the AOT cache's exact compiles), and the per-stream twins of a
    named fleet lane.  The span goes to ``timer``
    (``stage_seconds{stage="first_dispatch"}``) and not into a record's
    ``stages_ms``: the journal's cumulative ``compile_ms`` already says
    which segment paid.  A call that raises is neither booked nor
    marked: the retry, where the compile completes, is."""
    if program in books:
        return fn()
    from srtb_tpu.utils.metrics import metrics

    with span("first_dispatch", timer) as sp:
        try:
            out = fn()
        except BaseException:
            sp.cancel()
            raise
    books[program] = dt = sp.seconds
    metrics.set("last_compile_ms", dt * 1e3)
    series = [None, {"program": program}]
    if stream_labels:
        series.append(stream_labels)
    for labels in series:
        metrics.add("plan_compiles", labels=labels)
        metrics.add("compile_seconds", dt, labels=labels)
    return out


class ProfileCapture:
    """On-demand ``jax.profiler`` capture of the first N drained
    segments of a run (``Config.profile_capture_segments``): a REAL
    XLA/device trace recorded into ``Config.profile_capture_dir``,
    next to the Perfetto event export (tools/trace_export.py), so the
    device-level timeline and the causal-event timeline line up — the
    sidecar ``capture.json`` records the first/last trace_id and
    segment index covered, and the journal spans carry the same
    trace_ids.

    Lifecycle: :meth:`start` at run begin (tolerates a profiler-less
    backend or an already-running trace — capture is best-effort
    observability, never a run-killer), :meth:`note_segment` per
    drained segment until N, then auto-stop; :meth:`stop` is
    idempotent and also runs from the engine's ``finally`` so a short
    or crashed run still flushes a valid trace."""

    def __init__(self, out_dir: str, n_segments: int):
        self.out_dir = out_dir
        self.n_segments = int(n_segments)
        self.active = False
        self.first_trace_id = 0
        self.last_trace_id = 0
        self.first_segment = -1
        self.last_segment = -1
        self._seen = 0
        self._t0 = 0.0

    @classmethod
    def from_config(cls, cfg) -> "ProfileCapture | None":
        n = int(getattr(cfg, "profile_capture_segments", 0) or 0)
        if n <= 0:
            return None
        return cls(getattr(cfg, "profile_capture_dir",
                           "artifacts/profile") or "artifacts/profile",
                   n)

    def start(self) -> bool:
        import os
        try:
            import jax
            os.makedirs(self.out_dir, exist_ok=True)
            jax.profiler.start_trace(self.out_dir)
        except Exception as e:  # profiler-less backend / double start
            log.warning(f"[tracing] profile capture unavailable: {e}")
            return False
        self.active = True
        self._t0 = time.time()
        log.info(f"[tracing] profiling first {self.n_segments} "
                 f"segment(s) -> {self.out_dir}")
        return True

    def note_segment(self, segment: int, trace_id: int = 0) -> None:
        """One drained segment; stops the capture once N are in."""
        if not self.active:
            return
        if self._seen == 0:
            self.first_segment = int(segment)
            self.first_trace_id = int(trace_id)
        self.last_segment = int(segment)
        self.last_trace_id = int(trace_id)
        self._seen += 1
        if self._seen >= self.n_segments:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        self.active = False
        import json
        import os
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:  # pragma: no cover - backend quirk
            log.warning(f"[tracing] profiler stop failed: {e}")
            return
        # the trace_id join key: device timeline <-> causal events /
        # journal spans.  Written last so a capture.json implies a
        # complete capture.
        sidecar = {
            "type": "profile_capture",
            "dir": self.out_dir,
            "segments": self._seen,
            "first_segment": self.first_segment,
            "last_segment": self.last_segment,
            "first_trace_id": self.first_trace_id,
            "last_trace_id": self.last_trace_id,
            "wall_start": self._t0,
            "wall_end": time.time(),
        }
        try:
            with open(os.path.join(self.out_dir, "capture.json"),
                      "w") as f:
                json.dump(sidecar, f, indent=1, sort_keys=True)
                f.write("\n")
        except OSError as e:
            log.warning(f"[tracing] capture sidecar failed: {e}")
        from srtb_tpu.utils.metrics import metrics
        metrics.add("profile_captures")
        log.info(f"[tracing] profile capture complete: {self._seen} "
                 f"segment(s), trace_ids {self.first_trace_id}.."
                 f"{self.last_trace_id} -> {self.out_dir}")


class StageTimer:
    """Accumulates wall-clock per named stage; the per-pipe-timestamp logs
    of the reference, queryable instead of grep-able.

    Fed by ``span`` (the one way in: ``Pipeline`` and the DM-search
    loop hand their timer to every span they open, and to the processor
    and the sinks they own, from ``construct`` to a candidate's
    ``file``): ``last`` holds the most recent duration per stage, and
    ``on_stage(name, seconds)`` (when set) feeds every completed timing
    to the metrics histograms.  Thread-safe: the sink thread and the
    writer pool's threads record beside the loop's.
    """

    def __init__(self, on_stage=None):
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self.last: dict[str, float] = {}
        self.on_stage = on_stage
        self._lock = threading.Lock()

    def record(self, name: str, dt: float) -> None:
        """Record one externally timed stage duration (used where the
        caller must decide *after* timing whether the sample counts —
        e.g. the terminal failed source read must not pollute the
        ingest histogram)."""
        with self._lock:
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1
            self.last[name] = dt
        if self.on_stage is not None:
            self.on_stage(name, dt)

    def summary(self) -> dict:
        with self._lock:
            return {name: {"total_s": round(t, 6),
                           "count": self.counts[name],
                           "mean_ms": round(1e3 * t / self.counts[name],
                                            3)}
                    for name, t in sorted(self.totals.items())}
