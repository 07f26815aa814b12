"""Fault-tolerant streaming supervisor tests (srtb_tpu/resilience/).

Covers the acceptance criteria of the resilience subsystem:
- a transient fault injected at each of the six named sites (ingest,
  h2d, dispatch, fetch, sink_write, checkpoint) retries to success
  with detect output bit-identical to a fault-free run and
  ``segments_dropped == 0``;
- fatal faults escalate to a clean, loud shutdown;
- the segment watchdog cancels and re-dispatches a wedged in-flight
  segment (fetch never ready) with bit-identical output, and
  escalates when the requeue budget is exhausted;
- the supervisor restarts a crashed sink pipe with bounded budget and
  no lost segments, and escalates past the budget;
- degradation steps (shed waterfall dumps, shed baseband dumps) are
  accounted — no silent loss;
- restart-after-crash resumes from the checkpoint and completes the
  remainder bit-identically;
- file outputs are crash-consistent (temp + atomic rename, orphan
  sweep at startup) and shutdown joins are bounded with a wedged-
  thread report.
"""

import json
import os
import threading
import time
from typing import NamedTuple

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.pipeline.work import SegmentWork
from srtb_tpu.resilience import errors as E
from srtb_tpu.resilience.degrade import DegradationLadder
from srtb_tpu.resilience.faults import (FaultInjector, InjectedFatal,
                                        parse_plan)
from srtb_tpu.resilience.retry import RetryPolicy, retry_call
from srtb_tpu.resilience.supervisor import Supervisor
from srtb_tpu.utils.metrics import metrics

SITES = ("ingest", "h2d", "dispatch", "fetch", "sink_write",
         "checkpoint")


# ------------------------------------------------------------ taxonomy


def test_classify_taxonomy():
    assert E.classify(E.TransientError("x")) == E.TRANSIENT
    assert E.classify(E.DataLossError("x")) == E.DATA_LOSS
    assert E.classify(E.FatalError("x")) == E.FATAL
    # stdlib momentary conditions are transient
    assert E.classify(TimeoutError()) == E.TRANSIENT
    assert E.classify(InterruptedError()) == E.TRANSIENT
    assert E.classify(ConnectionResetError()) == E.TRANSIENT
    import errno
    assert E.classify(OSError(errno.EAGAIN, "x")) == E.TRANSIENT
    # unknown failures stay fatal: retrying unclassified errors hides bugs
    assert E.classify(RuntimeError("bug")) == E.FATAL
    assert E.classify(ValueError("bug")) == E.FATAL
    assert E.classify(OSError(errno.ENOENT, "x")) == E.FATAL


# --------------------------------------------------------------- retry


def test_retry_policy_backoff_deterministic():
    p = RetryPolicy(max_attempts=5, backoff_base_s=0.1,
                    backoff_max_s=0.5, jitter=0.25)
    seq = [p.backoff("ingest", a) for a in range(1, 5)]
    # deterministic: same site+attempt, same delay
    assert seq == [p.backoff("ingest", a) for a in range(1, 5)]
    # exponential-with-jitter, bounded by max*(1+jitter)
    assert all(d <= 0.5 * 1.25 for d in seq)
    assert seq[1] > seq[0] * 1.2  # grows despite jitter
    # different sites jitter differently
    assert p.backoff("fetch", 1) != p.backoff("ingest", 1)


def test_retry_call_transient_then_success():
    metrics.reset()
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise E.TransientError("hiccup")
        return "ok"

    p = RetryPolicy(max_attempts=3, backoff_base_s=0.001)
    assert retry_call(flaky, p, "t", sleep=lambda s: None) == "ok"
    assert len(calls) == 3
    assert metrics.get("retries_total") == 2
    assert metrics.get("retries_t") == 2
    metrics.reset()


def test_retry_call_fatal_immediate_and_budget_exhausted():
    p = RetryPolicy(max_attempts=3, backoff_base_s=0.001)
    calls = []

    def fatal():
        calls.append(1)
        raise RuntimeError("bug")

    with pytest.raises(RuntimeError):
        retry_call(fatal, p, "t", sleep=lambda s: None)
    assert len(calls) == 1  # fatal: no retry

    calls.clear()

    def always():
        calls.append(1)
        raise E.TransientError("down")

    with pytest.raises(E.TransientError):
        retry_call(always, p, "t", sleep=lambda s: None)
    assert len(calls) == 3  # budget spent


def test_retry_call_data_loss_is_accounted():
    metrics.reset()
    calls = []

    def torn():
        calls.append(1)
        if len(calls) < 2:
            raise E.DataLossError("torn block")
        return "ok"

    p = RetryPolicy(max_attempts=3, backoff_base_s=0.001)
    assert retry_call(torn, p, "t", sleep=lambda s: None) == "ok"
    # the retry succeeded but the loss event itself was counted
    assert metrics.get("data_loss_total") == 1
    metrics.reset()


def test_retry_deadline_bounds_total_time():
    p = RetryPolicy(max_attempts=50, backoff_base_s=0.05,
                    deadline_s=0.01)

    def always():
        raise E.TransientError("down")

    t0 = time.monotonic()
    with pytest.raises(E.TransientError):
        retry_call(always, p, "t")
    assert time.monotonic() - t0 < 1.0  # gave up at the deadline


# ---------------------------------------------------------- fault plan


def test_fault_plan_parse_roundtrip():
    specs = parse_plan("ingest:raise@1, fetch:stall=0.25@2,"
                       "sink_write:corrupt@3,dispatch:fatal@0")
    assert [str(s) for s in specs] == [
        "ingest:raise@1", "fetch:stall=0.25@2",
        "sink_write:corrupt@3", "dispatch:fatal@0"]
    inj = FaultInjector.from_plan("")
    assert inj is None  # zero-cost off
    inj = FaultInjector.from_plan("ingest:raise@1")
    assert inj.armed("ingest") and not inj.armed("fetch")
    inj.fire("ingest", 0)  # wrong index: nothing
    with pytest.raises(E.TransientError):
        inj.fire("ingest", 1)
    inj.fire("ingest", 1)  # fires once only
    assert inj.unfired() == []


@pytest.mark.parametrize("bad", [
    "nosuchsite:raise@1", "ingest:explode@1", "ingest:raise",
    "ingest:stall@1", "ingest:stall=-1@1", "ingest:raise@x"])
def test_fault_plan_parse_rejects(bad):
    with pytest.raises(ValueError):
        parse_plan(bad)


def test_fault_plan_rejects_duplicate_site_index():
    """Two entries at the same (site, index) would silently shadow one
    another; the fail-at-startup contract must catch the typo."""
    with pytest.raises(ValueError, match="duplicate"):
        FaultInjector.from_plan("ingest:raise@1,ingest:fatal@1")


# ------------------------------------------------------------- ladder


def test_degradation_ladder_steps_and_recovers():
    metrics.reset()
    lad = DegradationLadder(high=0.8, low=0.2, hold=2)
    assert lad.observe(0.5, False) == 0     # mid-band: hold
    assert lad.observe(0.9, False) == 0     # 1st above
    assert lad.observe(0.9, False) == 1     # hold reached: step up
    assert lad.observe(0.9, False) == 1
    assert lad.observe(0.9, False) == 2     # again
    # loss alone is pressure even with an empty queue
    assert lad.observe(0.0, True) == 2
    assert lad.observe(0.0, True) == 3
    assert lad.observe(0.0, True) == 3      # top rung is sticky
    # recovery needs `hold` consecutive clear observations
    assert lad.observe(0.1, False) == 3
    assert lad.observe(0.1, False) == 2
    assert metrics.get("degrade_level") == 2
    assert metrics.get("degrade_steps") == 3
    assert metrics.get("degrade_recoveries") == 1
    metrics.reset()


def test_degradation_ladder_validates():
    with pytest.raises(ValueError):
        DegradationLadder(high=0.2, low=0.5)


# ---------------------------------------------------------- supervisor


def test_supervisor_budget_and_escalation():
    metrics.reset()
    t = [0.0]
    sup = Supervisor("w", max_restarts=2, window_s=10.0,
                     clock=lambda: t[0])
    exc = E.TransientError("crash")
    assert sup.should_restart(exc)
    assert sup.should_restart(exc)
    assert not sup.should_restart(exc)  # budget spent
    t[0] = 20.0  # window slides: budget recovers
    assert sup.should_restart(exc)
    assert metrics.get("worker_restarts") == 3
    assert metrics.get("worker_restarts_w") == 3
    # fatal crashes never restart (unless restart_fatal)
    assert not sup.should_restart(RuntimeError("bug"))
    assert Supervisor("g", restart_fatal=True).should_restart(
        RuntimeError("bug"))
    metrics.reset()


# ===================================================== pipeline fixtures


@pytest.fixture(scope="module")
def synth_file(tmp_path_factory):
    from srtb_tpu.io.synth import make_dispersed_baseband

    tmp = tmp_path_factory.mktemp("resilience")
    n = 1 << 14
    data = make_dispersed_baseband(n * 4, 1405.0, 64.0, 0.0,
                                   pulse_positions=n // 2, nbits=8)
    path = str(tmp / "bb.bin")
    data.tofile(path)
    return path, n


def _cfg(path, n, tmp_path, tag, **extra):
    return Config(
        baseband_input_count=n,
        baseband_input_bits=8,
        baseband_freq_low=1405.0,
        baseband_bandwidth=64.0,
        baseband_sample_rate=128e6,
        input_file_path=path,
        baseband_output_file_prefix=str(tmp_path / f"{tag}_"),
        spectrum_channel_count=1 << 8,
        signal_detect_max_boxcar_length=64,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=False,
        writer_thread_count=0,
        retry_backoff_base_s=0.001,
        **extra)


@pytest.fixture(scope="module")
def shared_processor(synth_file):
    """One compiled segment plan shared across pipelines (the fault
    knobs are not trace-relevant, so every run uses the same jits)."""
    from srtb_tpu.pipeline.segment import SegmentProcessor

    path, n = synth_file
    cfg = Config(baseband_input_count=n, baseband_input_bits=8,
                 baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                 baseband_sample_rate=128e6,
                 spectrum_channel_count=1 << 8,
                 signal_detect_max_boxcar_length=64,
                 mitigate_rfi_average_method_threshold=100.0,
                 mitigate_rfi_spectral_kurtosis_threshold=2.0,
                 baseband_reserve_sample=False)
    return SegmentProcessor(cfg)


class _CaptureSink:
    def __init__(self):
        self.detects = []
        self.positives = []

    def push(self, work, positive):
        det = work.detect
        self.detects.append((
            np.asarray(det.signal_counts).copy(),
            np.asarray(det.zero_count).copy(),
            np.asarray(det.time_series).copy()))
        self.positives.append(bool(positive))


def _run_real(cfg, processor, sink=None):
    sinks = [sink] if sink is not None else []
    with Pipeline(cfg, sinks=sinks, processor=processor) as pipe:
        stats = pipe.run()
    return stats


def _assert_same_detects(a: _CaptureSink, b: _CaptureSink):
    assert len(a.detects) == len(b.detects)
    for (sc_a, zc_a, ts_a), (sc_b, zc_b, ts_b) in zip(a.detects,
                                                      b.detects):
        np.testing.assert_array_equal(sc_a, sc_b)
        np.testing.assert_array_equal(zc_a, zc_b)
        np.testing.assert_array_equal(ts_a, ts_b)
    assert a.positives == b.positives


@pytest.fixture(scope="module")
def fault_free_baseline(synth_file, shared_processor,
                        tmp_path_factory):
    """Detect outputs of a run with no faults — the bit-identity
    reference every recovery test compares against."""
    path, n = synth_file
    tmp = tmp_path_factory.mktemp("baseline")
    metrics.reset()
    sink = _CaptureSink()
    stats = _run_real(_cfg(path, n, tmp, "base", inflight_segments=2),
                      shared_processor, sink)
    metrics.reset()
    assert stats.segments == 4
    return stats, sink


# --------------------------------------- transient faults at every site


@pytest.mark.parametrize("site", SITES)
def test_transient_fault_retries_to_success(site, synth_file,
                                            shared_processor, tmp_path,
                                            fault_free_baseline):
    """One injected transient fault at each named site: the pipeline
    must complete with detect output bit-identical to the fault-free
    run, zero dropped segments, and the retry accounted."""
    path, n = synth_file
    base_stats, base_sink = fault_free_baseline
    metrics.reset()
    sink = _CaptureSink()
    extra = {}
    if site == "checkpoint":
        extra["checkpoint_path"] = str(tmp_path / f"{site}.json")
    cfg = _cfg(path, n, tmp_path, site, inflight_segments=2,
               fault_plan=f"{site}:raise@1", **extra)
    pipe = Pipeline(cfg, sinks=[sink], processor=shared_processor)
    with pipe:
        stats = pipe.run()
    assert stats.segments == base_stats.segments
    _assert_same_detects(base_sink, sink)
    assert pipe.faults.unfired() == [], "fault never fired"
    assert metrics.get("retries_total") == 1
    assert metrics.get(f"retries_{site}") == 1
    assert metrics.get("segments_dropped") == 0
    metrics.reset()


def test_all_six_sites_one_run_acceptance(synth_file, shared_processor,
                                          tmp_path,
                                          fault_free_baseline):
    """The acceptance case: one transient fault at each of the six
    sites in a SINGLE run — bit-identical output, segments_dropped ==
    0, and every recovery counter visible in the Prometheus exposition
    and the v3 journal."""
    from srtb_tpu.tools import telemetry_report as TR

    path, n = synth_file
    base_stats, base_sink = fault_free_baseline
    metrics.reset()
    sink = _CaptureSink()
    plan = ("ingest:raise@1,h2d:raise@1,dispatch:raise@2,"
            "fetch:raise@2,sink_write:raise@3,checkpoint:raise@3")
    cfg = _cfg(path, n, tmp_path, "all6", inflight_segments=2,
               fault_plan=plan,
               checkpoint_path=str(tmp_path / "all6.json"),
               telemetry_journal_path=str(tmp_path / "all6.jsonl"))
    pipe = Pipeline(cfg, sinks=[sink], processor=shared_processor)
    with pipe:
        stats = pipe.run()
    assert stats.segments == base_stats.segments
    _assert_same_detects(base_sink, sink)
    assert pipe.faults.unfired() == []
    assert metrics.get("retries_total") == 6
    assert metrics.get("segments_dropped") == 0
    # counters visible in /metrics (Prometheus text exposition)
    prom = metrics.prometheus()
    assert "srtb_retries_total 6" in prom
    assert "srtb_faults_injected 6" in prom
    assert "srtb_degrade_level" in prom
    # ... and in the journal (schema v4 since the self-healing PR)
    recs = TR.load(cfg.telemetry_journal_path)
    assert len(recs) == stats.segments
    for r in recs:
        assert r["v"] == 13
        for key in ("degrade_level", "retries", "requeues", "restarts",
                    "shed_waterfalls", "shed_baseband"):
            assert key in r, (key, r)
    # the checkpoint-site retry of the LAST segment lands after that
    # segment's journal write, so the final record carries 5 of the 6
    assert recs[-1]["retries"] == 5
    assert recs[-1]["segments_dropped"] == 0
    rep = TR.report(cfg.telemetry_journal_path)
    assert rep["resilience"]["retries"] == 5
    assert rep["resilience"]["degrade_level_max"] == 0
    metrics.reset()


def test_fatal_fault_escalates_cleanly(synth_file, shared_processor,
                                       tmp_path):
    """A fatal fault must not be retried: the run raises it, and the
    engine shuts down cleanly (no hang, close() fine)."""
    path, n = synth_file
    metrics.reset()
    cfg = _cfg(path, n, tmp_path, "fatal", inflight_segments=2,
               fault_plan="dispatch:fatal@1")
    pipe = Pipeline(cfg, sinks=[], processor=shared_processor)
    with pipe:
        with pytest.raises(InjectedFatal):
            pipe.run()
    assert metrics.get("retries_total") == 0
    metrics.reset()


def test_corrupt_fault_retried_and_accounted(synth_file,
                                             shared_processor,
                                             tmp_path,
                                             fault_free_baseline):
    """A data-loss fault retries to success like a transient, but the
    loss occurrence itself is counted."""
    path, n = synth_file
    base_stats, base_sink = fault_free_baseline
    metrics.reset()
    sink = _CaptureSink()
    cfg = _cfg(path, n, tmp_path, "corrupt", inflight_segments=2,
               fault_plan="ingest:corrupt@2")
    stats = _run_real(cfg, shared_processor, sink)
    assert stats.segments == base_stats.segments
    _assert_same_detects(base_sink, sink)
    assert metrics.get("data_loss_total") == 1
    assert metrics.get("retries_total") == 1
    metrics.reset()


# ----------------------------------------------------- watchdog requeue


class _StubDetect(NamedTuple):
    signal_counts: object
    zero_count: object
    time_series: object


class _NeverReady:
    """Device-array stand-in that never materializes (a wedged fetch)."""

    def is_ready(self) -> bool:
        return False

    def __array__(self, dtype=None, copy=None):
        raise AssertionError("a cancelled segment's results were read")


class _WedgeProcessor:
    """First ``wedge_times`` dispatches return never-ready results;
    later dispatches (including the watchdog's re-dispatch of the same
    segment) return deterministic host values derived from the input."""

    def __init__(self, wedge_times: int):
        self.wedge_times = wedge_times
        self.dispatches = 0

    def process(self, raw):
        self.dispatches += 1
        if self.dispatches <= self.wedge_times:
            det = _StubDetect(_NeverReady(), _NeverReady(),
                              _NeverReady())
            return None, det
        val = float(np.asarray(raw, dtype=np.float32).sum())
        det = _StubDetect(
            signal_counts=np.zeros((1, 4), np.int64),
            zero_count=np.asarray(0),
            time_series=np.asarray([val], np.float32))
        return None, det


class _CountingSource:
    def __init__(self, n_segments: int, seg_bytes: int = 64):
        self.n = n_segments
        self.seg_bytes = seg_bytes
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self) -> SegmentWork:
        if self._i >= self.n:
            raise StopIteration
        self._i += 1
        return SegmentWork(
            data=np.full(self.seg_bytes, self._i, np.uint8),
            timestamp=self._i)


def _watchdog_cfg(tmp_path, tag, **extra):
    return Config(baseband_input_count=64,
                  baseband_reserve_sample=False,
                  writer_thread_count=0,
                  retry_backoff_base_s=0.001,
                  telemetry_journal_path=str(tmp_path / f"{tag}.jsonl"),
                  **extra)


def test_watchdog_requeues_wedged_segment(tmp_path):
    """Segment 0's first dispatch never becomes ready: the watchdog
    must cancel it at the deadline, re-dispatch from the retained host
    buffer, and drain bit-identical output vs a run that never wedged
    — with the requeue accounted and nothing dropped."""
    from srtb_tpu.tools import telemetry_report as TR

    metrics.reset()
    outs = {}
    for tag, wedge in (("clean", 0), ("wedged", 1)):
        cfg = _watchdog_cfg(tmp_path, tag, inflight_segments=2,
                            segment_deadline_s=0.12,
                            segment_watchdog_requeues=2)
        sink = _CaptureSink()
        pipe = Pipeline(cfg, source=_CountingSource(4), sinks=[sink],
                        processor=_WedgeProcessor(wedge))
        with pipe:
            stats = pipe.run()
        outs[tag] = (stats, sink)
        assert stats.segments == 4
    _assert_same_detects(outs["clean"][1], outs["wedged"][1])
    assert metrics.get("watchdog_requeues") == 1
    assert metrics.get("segments_dropped") == 0
    recs = TR.load(str(tmp_path / "wedged.jsonl"))
    assert [r["segment"] for r in recs] == list(range(4))
    assert recs[-1]["requeues"] == 1
    metrics.reset()


def test_watchdog_escalates_after_requeue_budget(tmp_path):
    """A segment that stays wedged through every allowed requeue must
    escalate fatally (the device is gone), not loop forever."""
    metrics.reset()
    cfg = _watchdog_cfg(tmp_path, "esc", inflight_segments=2,
                        segment_deadline_s=0.08,
                        segment_watchdog_requeues=1)
    pipe = Pipeline(cfg, source=_CountingSource(3), sinks=[],
                    processor=_WedgeProcessor(10))
    with pipe:
        with pytest.raises(E.WatchdogEscalation):
            pipe.run()
    assert metrics.get("watchdog_requeues") == 1
    metrics.reset()


# ------------------------------------------------- supervisor restarts


class _InstantProcessor:
    def process(self, raw):
        val = float(np.asarray(raw, dtype=np.float32).sum())
        return None, _StubDetect(
            signal_counts=np.zeros((1, 4), np.int64),
            zero_count=np.asarray(0),
            time_series=np.asarray([val], np.float32))


class _CrashingSink:
    """Raises a transient-classified error on the first ``crashes``
    pushes, then records."""

    def __init__(self, crashes: int):
        self.left = crashes
        self.pushed = []

    def push(self, work, positive):
        if self.left > 0:
            self.left -= 1
            raise ConnectionResetError("sink backend lost")
        self.pushed.append(int(work.segment.timestamp))


def test_supervisor_restarts_crashed_sink_pipe(tmp_path):
    """Retry disabled, so the sink crash kills the pipe worker: the
    supervisor must restart it, replay the failed item (no segment
    lost, order kept), and account the restart."""
    from srtb_tpu.tools import telemetry_report as TR

    metrics.reset()
    n_seg = 6
    cfg = _watchdog_cfg(tmp_path, "restart", inflight_segments=3,
                        retry_max_attempts=1,  # crash reaches the pipe
                        supervisor_max_restarts=2)
    sink = _CrashingSink(crashes=1)
    pipe = Pipeline(cfg, source=_CountingSource(n_seg), sinks=[sink],
                    processor=_InstantProcessor())
    with pipe:
        stats = pipe.run()
    assert stats.segments == n_seg
    # every segment reached the sink exactly once, in order
    assert sink.pushed == list(range(1, n_seg + 1))
    assert metrics.get("worker_restarts") == 1
    assert metrics.get("worker_restarts_sink_drain") == 1
    recs = TR.load(str(tmp_path / "restart.jsonl"))
    assert [r["segment"] for r in recs] == list(range(n_seg))
    assert recs[-1]["restarts"] == 1
    metrics.reset()


def test_supervisor_replay_counts_signal_once(tmp_path):
    """A replayed drain re-runs the detection gate: a positive segment
    whose first attempt crashed in the sink stage (after the signal
    was already counted) must not inflate ``stats.signals``."""

    class _PositiveProcessor(_InstantProcessor):
        def process(self, raw):
            _, det = super().process(raw)
            return None, det._replace(
                signal_counts=np.ones((1, 4), np.int64))

    metrics.reset()
    n_seg = 4
    cfg = _watchdog_cfg(tmp_path, "replay_sig", inflight_segments=3,
                        retry_max_attempts=1,  # crash reaches the pipe
                        supervisor_max_restarts=2)
    sink = _CrashingSink(crashes=1)
    pipe = Pipeline(cfg, source=_CountingSource(n_seg), sinks=[sink],
                    processor=_PositiveProcessor())
    with pipe:
        stats = pipe.run()
    assert metrics.get("worker_restarts") == 1
    assert stats.segments == n_seg
    assert sink.pushed == list(range(1, n_seg + 1))
    # every segment is positive; the replayed one counts exactly once
    assert stats.signals == n_seg
    metrics.reset()


def test_sink_retry_is_exactly_once_per_sink(tmp_path):
    """A transient failure in one sink must not re-push the sinks that
    already succeeded: an in-place appender (WriteAllSink) would
    otherwise duplicate its stream bytes on every retry."""

    class _Appender:
        def __init__(self):
            self.got = []

        def push(self, work, positive):
            self.got.append(int(work.segment.timestamp))

    class _FlakySink:
        def __init__(self):
            self.fails = 1
            self.got = []

        def push(self, work, positive):
            if self.fails:
                self.fails -= 1
                raise ConnectionResetError("sink hiccup")
            self.got.append(int(work.segment.timestamp))

    metrics.reset()
    appender, flaky = _Appender(), _FlakySink()
    cfg = _watchdog_cfg(tmp_path, "once", inflight_segments=2)
    pipe = Pipeline(cfg, source=_CountingSource(3),
                    sinks=[appender, flaky],
                    processor=_InstantProcessor())
    with pipe:
        stats = pipe.run()
    assert stats.segments == 3
    assert metrics.get("retries_total") == 1
    # the appender saw every segment exactly once despite the retry
    assert appender.got == [1, 2, 3]
    assert flaky.got == [1, 2, 3]
    metrics.reset()


class _DrainCrashSink:
    """push always succeeds; drain() — reached via the checkpoint
    flush, i.e. AFTER the segment was accounted — crashes once."""

    def __init__(self, crashes: int = 1):
        self.left = crashes
        self.pushed = []

    def push(self, work, positive):
        self.pushed.append(int(work.segment.timestamp))

    def drain(self):
        if self.left > 0:
            self.left -= 1
            raise ConnectionResetError("flush lost")


def test_supervisor_skips_replay_after_accounting(tmp_path):
    """A crash landing AFTER the segment was accounted (here: in the
    checkpoint flush) must NOT be replayed — a replay would
    double-count the segment and shift every later journal index."""
    from srtb_tpu.tools import telemetry_report as TR

    metrics.reset()
    n_seg = 5
    cfg = _watchdog_cfg(tmp_path, "postacct", inflight_segments=3,
                        retry_max_attempts=1,
                        supervisor_max_restarts=2,
                        checkpoint_path=str(tmp_path / "pa.json"))
    sink = _DrainCrashSink(crashes=1)
    pipe = Pipeline(cfg, source=_CountingSource(n_seg), sinks=[sink],
                    processor=_InstantProcessor())
    with pipe:
        stats = pipe.run()
    assert stats.segments == n_seg
    assert metrics.get("worker_restarts") == 1
    # exactly-once accounting: no duplicate pushes, no duplicate or
    # shifted journal indices, checkpoint covers every segment
    assert sink.pushed == list(range(1, n_seg + 1))
    recs = TR.load(str(tmp_path / "postacct.jsonl"))
    assert [r["segment"] for r in recs] == list(range(n_seg))
    assert json.load(open(tmp_path / "pa.json"))["segments_done"] \
        == n_seg
    metrics.reset()


def test_supervisor_escalates_past_budget(tmp_path):
    """A sink that keeps crashing exhausts the restart budget and the
    original error escalates to the caller."""
    metrics.reset()
    cfg = _watchdog_cfg(tmp_path, "budget", inflight_segments=3,
                        retry_max_attempts=1,
                        supervisor_max_restarts=1)
    pipe = Pipeline(cfg, source=_CountingSource(8),
                    sinks=[_CrashingSink(crashes=100)],
                    processor=_InstantProcessor())
    with pipe:
        with pytest.raises(ConnectionResetError):
            pipe.run()
    assert metrics.get("worker_restarts") == 1
    metrics.reset()


def test_supervision_disabled_propagates_immediately(tmp_path):
    """supervisor_max_restarts = 0 restores the crash-propagation-only
    behavior."""
    metrics.reset()
    cfg = _watchdog_cfg(tmp_path, "nosup", inflight_segments=3,
                        retry_max_attempts=1,
                        supervisor_max_restarts=0)
    pipe = Pipeline(cfg, source=_CountingSource(4),
                    sinks=[_CrashingSink(crashes=1)],
                    processor=_InstantProcessor())
    with pipe:
        with pytest.raises(ConnectionResetError):
            pipe.run()
    assert metrics.get("worker_restarts") == 0
    metrics.reset()


# ----------------------------------------------------- degradation


class _WaterfallProcessor:
    def process(self, raw):
        det = _StubDetect(
            signal_counts=np.ones((1, 4), np.int64),  # always positive
            zero_count=np.asarray(0),
            time_series=np.zeros(4, np.float32))
        return np.zeros((2, 1, 4, 4), np.float32), det


class _SlowSheddableSink:
    sheddable = True

    def __init__(self, sink_s: float):
        self.sink_s = sink_s
        self.pushed = 0
        self.waterfalls = 0

    def push(self, work, positive):
        time.sleep(self.sink_s)
        self.pushed += 1
        if work.waterfall is not None:
            self.waterfalls += 1


def test_degradation_sheds_accounted(tmp_path):
    """Sustained sink backlog must walk the ladder: waterfall dumps
    shed first, then the sheddable sink skipped entirely — every shed
    counted, every segment still journaled (no silent loss)."""
    from srtb_tpu.tools import telemetry_report as TR

    metrics.reset()
    n_seg = 12
    cfg = _watchdog_cfg(tmp_path, "degrade", inflight_segments=2,
                        degrade_queue_high=0.4, degrade_queue_low=0.1,
                        degrade_hold_segments=2)
    sink = _SlowSheddableSink(0.02)
    pipe = Pipeline(cfg, source=_CountingSource(n_seg), sinks=[sink],
                    processor=_WaterfallProcessor())
    with pipe:
        stats = pipe.run()
    assert stats.segments == n_seg
    shed_wf = metrics.get("shed_waterfalls")
    shed_bb = metrics.get("shed_baseband")
    assert shed_wf > 0, "ladder never reached level 1"
    # every segment accounted: pushed to the sink or counted as shed
    assert sink.pushed + shed_bb == n_seg
    assert sink.waterfalls + shed_wf == n_seg
    assert metrics.get("degrade_steps") >= 1
    recs = TR.load(str(tmp_path / "degrade.jsonl"))
    assert len(recs) == n_seg  # no silent loss: all journaled
    assert max(r["degrade_level"] for r in recs) >= 1
    assert recs[-1]["shed_waterfalls"] == shed_wf
    rep = TR.report(str(tmp_path / "degrade.jsonl"))
    assert rep["resilience"]["degrade_level_max"] >= 1
    assert rep["resilience"]["segments_degraded"] >= 1
    metrics.reset()


def test_shed_waterfall_counted_once_across_retries(tmp_path):
    """A retried/replayed sink push re-enters _push_sinks with the
    original waterfall: the shed must not be counted twice."""
    metrics.reset()
    cfg = _watchdog_cfg(tmp_path, "shedonce")
    pipe = Pipeline(cfg, source=_CountingSource(1), sinks=[],
                    processor=_WaterfallProcessor())
    wf = np.zeros((2, 1, 4, 4), np.float32)
    det = _StubDetect(signal_counts=np.zeros((1, 4), np.int64),
                      zero_count=np.asarray(0),
                      time_series=np.zeros(4, np.float32))
    done: set = set()
    pipe._push_sinks(None, wf, det, False, degrade_level=1, done=done)
    pipe._push_sinks(None, wf, det, False, degrade_level=1, done=done)
    assert metrics.get("shed_waterfalls") == 1
    metrics.reset()


# ------------------------------------ restart-after-crash + checkpoint


def test_restart_after_crash_resumes_from_checkpoint(
        synth_file, shared_processor, tmp_path, fault_free_baseline):
    """A fatal fault mid-run kills the pipeline after two checkpointed
    segments; a fresh pipeline on the same config must resume at the
    checkpoint and complete the remainder bit-identically."""
    path, n = synth_file
    base_stats, base_sink = fault_free_baseline
    ck = str(tmp_path / "resume.json")
    metrics.reset()
    cfg = _cfg(path, n, tmp_path, "crash", inflight_segments=1,
               checkpoint_path=ck, fault_plan="dispatch:fatal@2")
    sink_a = _CaptureSink()
    pipe = Pipeline(cfg, sinks=[sink_a], processor=shared_processor)
    with pipe:
        with pytest.raises(InjectedFatal):
            pipe.run()
    assert len(sink_a.detects) == 2  # segments 0, 1 drained + durable
    state = json.load(open(ck))
    assert state["segments_done"] == 2

    # "restart the process": same config, faults cleared
    metrics.reset()
    sink_b = _CaptureSink()
    cfg2 = _cfg(path, n, tmp_path, "crash", inflight_segments=1,
                checkpoint_path=ck)
    with Pipeline(cfg2, sinks=[sink_b],
                  processor=shared_processor) as pipe2:
        stats2 = pipe2.run()
    assert stats2.segments == base_stats.segments - 2
    # the union of both runs is bit-identical to the fault-free run
    combined = _CaptureSink()
    combined.detects = sink_a.detects + sink_b.detects
    combined.positives = sink_a.positives + sink_b.positives
    _assert_same_detects(base_sink, combined)
    metrics.reset()


# ------------------------------------------- crash-consistent outputs


def test_write_bytes_atomic_and_orphan_sweep(tmp_path):
    from srtb_tpu.io.writers import (TMP_SUFFIX, WriteSignalSink,
                                     recover_orphan_temps)

    prefix = str(tmp_path / "cand_")
    cfg = Config(baseband_output_file_prefix=prefix)
    sink = WriteSignalSink(cfg, writer_pool=None)
    path = prefix + "42.bin"
    sink._write_bytes(path, np.arange(16, dtype=np.uint8), fsync=True)
    assert os.path.exists(path)
    assert not os.path.exists(path + TMP_SUFFIX)
    assert np.fromfile(path, np.uint8).tolist() == list(range(16))

    # STALE orphans from an interrupted run are swept; real files and
    # FRESH temps (possibly a live concurrent writer's) survive
    metrics.reset()
    orphan = prefix + "7.npy" + TMP_SUFFIX
    with open(orphan, "wb") as f:
        f.write(b"torn")
    os.utime(orphan, (time.time() - 3600, time.time() - 3600))
    fresh = prefix + "8.npy" + TMP_SUFFIX
    with open(fresh, "wb") as f:
        f.write(b"live writer mid-flush")
    other = str(tmp_path / ("unrelated.bin" + TMP_SUFFIX))
    with open(other, "wb") as f:
        f.write(b"not ours")
    os.utime(other, (time.time() - 3600, time.time() - 3600))
    removed = recover_orphan_temps(prefix)
    assert removed == [orphan]
    assert not os.path.exists(orphan)
    assert os.path.exists(fresh)      # younger than min_age_s: kept
    assert os.path.exists(other)      # different prefix: untouched
    assert os.path.exists(path)       # completed file: untouched
    assert metrics.get("orphan_temps_removed") == 1
    metrics.reset()


def test_pipeline_init_runs_recovery_sweep(tmp_path):
    prefix = str(tmp_path / "out_")
    orphan = prefix + "3.bin.srtb_tmp"
    with open(orphan, "wb") as f:
        f.write(b"torn")
    os.utime(orphan, (time.time() - 3600, time.time() - 3600))
    cfg = Config(baseband_input_count=64,
                 baseband_reserve_sample=False,
                 baseband_output_file_prefix=prefix,
                 writer_thread_count=0)
    pipe = Pipeline(cfg, source=_CountingSource(0), sinks=[],
                    processor=_InstantProcessor())
    pipe.close()
    assert not os.path.exists(orphan)


def test_async_pool_python_fallback_atomic(tmp_path):
    from srtb_tpu.io.native_writer import AsyncWriterPool
    from srtb_tpu.io.writers import TMP_SUFFIX

    path = str(tmp_path / "pool.bin")
    with AsyncWriterPool(1, prefer_native=False) as pool:
        pool.submit(path, np.arange(8, dtype=np.uint8), fsync=True)
        pool.drain()
        assert np.fromfile(path, np.uint8).tolist() == list(range(8))
        assert not os.path.exists(path + TMP_SUFFIX)
        # appends stay in place (no tmp+rename possible)
        with AsyncWriterPool(1, prefer_native=False) as p2:
            p2.submit(path, b"\xff", append=True)
            p2.drain()
        assert os.path.getsize(path) == 9


def test_tmp_suffix_matches_native_pool_literal():
    # native/file_writer.cpp hardcodes ".srtb_tmp": if TMP_SUFFIX ever
    # moved, native-pool temps would silently stop matching the
    # startup sweep and interrupted-run orphans would never be cleaned
    from srtb_tpu.io import writers
    assert writers.TMP_SUFFIX == ".srtb_tmp"
    cpp = os.path.join(os.path.dirname(writers.__file__), "..",
                       "native", "file_writer.cpp")
    with open(cpp) as f:
        assert '".srtb_tmp"' in f.read()


def test_python_fallback_pool_workers_are_daemon(tmp_path):
    # close(drain=False) abandons wedged writes; only DAEMON workers
    # actually die with the process (threading._shutdown joins every
    # non-daemon thread at exit, whatever concurrent.futures does)
    from srtb_tpu.io.native_writer import AsyncWriterPool

    pool = AsyncWriterPool(1, prefer_native=False)
    try:
        pool.submit(str(tmp_path / "d.bin"), b"\x01")
        pool.drain()
        workers = [t for t in threading.enumerate()
                   if t.name.startswith("srtb-writer")]
        assert workers and all(t.daemon for t in workers)
    finally:
        pool.close()
    for t in workers:
        t.join(5.0)
        assert not t.is_alive()


def test_checkpoint_orphan_tmp_removed(tmp_path):
    from srtb_tpu.pipeline.checkpoint import StreamCheckpoint

    ck = str(tmp_path / "ck.json")
    sc = StreamCheckpoint(ck)
    sc.update(3, 300)
    # simulate a crash mid-update: stale tmp next to good state
    with open(ck + ".tmp", "w") as f:
        f.write("{torn")
    sc2 = StreamCheckpoint(ck)
    assert not os.path.exists(ck + ".tmp")
    assert sc2.segments_done == 3 and sc2.file_offset_bytes == 300


# ------------------------------------------------- bounded shutdown


def test_on_exit_bounded_join_reports_wedged():
    from srtb_tpu.pipeline import framework as fw

    metrics.reset()
    release = threading.Event()

    def stuck(stop_token, _):
        release.wait()  # ignores the stop token: a wedged pipe

    stop = fw.StopToken()
    pipe = fw.start_pipe(stuck, None, None, stop, "wedged_pipe")
    t0 = time.monotonic()
    wedged = fw.on_exit(stop, [pipe], timeout=0.25)
    assert time.monotonic() - t0 < 5.0  # bounded, not hanging
    assert wedged == [pipe]
    assert metrics.get("wedged_threads") == 1
    release.set()
    assert pipe.join(5.0)
    metrics.reset()


def test_file_mode_slow_sink_never_sheds(synth_file, shared_processor,
                                         tmp_path):
    """A slow-but-healthy sink flush longer than segment_deadline_s
    must NOT trip the watchdog shed in file mode: shedding is a
    liveness mechanism for real-time sources, while a file-mode run
    throttles losslessly by design (the ladder's documented rule)."""

    class _SlowSink:
        def __init__(self):
            self.pushed = 0

        def push(self, work, positive):
            time.sleep(0.15)  # > deadline: 'slow' must not read 'wedged'
            self.pushed += 1

    path, n = synth_file
    metrics.reset()
    sink = _SlowSink()
    cfg = _cfg(path, n, tmp_path, "slowsink", inflight_segments=2,
               segment_deadline_s=0.05, segment_watchdog_requeues=2)
    pipe = Pipeline(cfg, sinks=[sink], processor=shared_processor)
    with pipe:
        stats = pipe.run()
    assert stats.segments == 4
    assert sink.pushed == 4
    assert metrics.get("segments_dropped") == 0
    metrics.reset()


def test_realtime_slow_multi_sink_flush_is_not_a_wedge(tmp_path):
    """Real-time wedge detection is per-sink-push (the heartbeat), not
    per drained item: two healthy sinks whose COMBINED flush time
    exceeds segment_deadline_s must not be declared wedged — each
    completed push is progress, only a single write stalled past the
    deadline reads as a wedge."""

    class _SlowSink:
        def __init__(self):
            self.pushed = 0

        def push(self, work, positive):
            time.sleep(0.15)  # per-sink < deadline, per-item > deadline
            self.pushed += 1

    metrics.reset()
    sinks = [_SlowSink(), _SlowSink()]
    cfg = _watchdog_cfg(tmp_path, "slowmulti", inflight_segments=2,
                        segment_deadline_s=0.2,
                        segment_watchdog_requeues=2)
    pipe = Pipeline(cfg, source=_CountingSource(4), sinks=sinks,
                    processor=_InstantProcessor())
    with pipe:
        stats = pipe.run()
    assert stats.segments == 4
    assert all(s.pushed == 4 for s in sinks)
    assert metrics.get("segments_dropped") == 0
    metrics.reset()


def test_write_signal_sink_retry_reentry_is_idempotent(tmp_path):
    """A transient failure partway through WriteSignalSink's write makes
    the pipeline's sink_write retry call push() again with the same
    work: the replay must not stamp the overlap window twice nor spill
    the same waterfall under a fresh .npy index."""
    from srtb_tpu.io.writers import WriteSignalSink
    from srtb_tpu.pipeline.work import SegmentResultWork

    class _TimDetect(NamedTuple):
        signal_counts: object
        boxcar_series: object
        boxcar_lengths: tuple

    cfg = Config(baseband_input_count=64, baseband_reserve_sample=False,
                 writer_thread_count=0,
                 baseband_output_file_prefix=str(tmp_path / "idem_"))
    sink = WriteSignalSink(cfg, fdatasync=False)
    # the retried attempt wraps the SAME segment in a FRESH work
    # object, exactly like runtime._push_sinks rebuilding full/light
    # per attempt — idempotency must key on the segment
    seg = SegmentWork(data=np.zeros(64, np.uint8), timestamp=7)

    def mk_work():
        return SegmentResultWork(
            segment=seg,
            # stacked (re, im) x 2 streams -> two .npy files
            waterfall=np.zeros((2, 2, 4, 8), np.float32),
            detect=_TimDetect(
                signal_counts=np.array([[3, 0]], np.int64),
                boxcar_series=np.zeros((1, 2, 8), np.float32),
                boxcar_lengths=(1, 2)))

    # fail the SECOND .npy write (after .bin and the first .npy
    # landed), then let the re-entered push run clean — without the
    # segment-keyed path memo the retry's find-first-free scan sees
    # its own partial output and duplicates stream 0 as .1.npy
    orig = sink._write_bytes
    state = {"fails_left": 1}

    def flaky(path, data, **kw):
        if path.endswith(".1.npy") and state["fails_left"]:
            state["fails_left"] -= 1
            raise TimeoutError("transient disk hiccup")
        return orig(path, data, **kw)

    sink._write_bytes = flaky
    with pytest.raises(TimeoutError):
        sink.push(mk_work(), True)
    sink.push(mk_work(), True)  # the retry re-entry
    assert list(sink.recent_positive_timestamps) == [7]
    npys = sorted(p.name for p in tmp_path.glob("idem_*.npy"))
    assert npys == ["idem_7.0.npy", "idem_7.1.npy"]  # no .2.npy spill
    assert len(sink.written) == 1


def test_write_signal_sink_retry_keeps_piggyback_candidate(tmp_path):
    """A transient failure writing a piggybacked negative (popped off
    the re-check deque) must not lose it: the retry re-entry has to
    find it still scheduled, write it exactly once, and leave the
    OTHER queued negatives for their own turn."""
    from srtb_tpu.io.writers import WriteSignalSink
    from srtb_tpu.pipeline.work import SegmentResultWork

    cfg = Config(baseband_input_count=64, baseband_reserve_sample=False,
                 writer_thread_count=0,
                 baseband_output_file_prefix=str(tmp_path / "piggy_"))
    sink = WriteSignalSink(cfg, fdatasync=False)
    w = sink._overlap_window_ns()

    def negative(ts, counter):
        return SegmentResultWork(
            segment=SegmentWork(data=np.zeros(64, np.uint8),
                                timestamp=ts, udp_packet_counter=counter),
            waterfall=None, detect=None)

    # a positive at ts=10*w anchors the overlap window; work_2 (within
    # the window) is the piggyback candidate, work_3 is not
    base_ts = int(10 * w)
    sink.recent_positive_timestamps.append(base_ts)
    work_2 = negative(base_ts + int(0.5 * w), 21)
    work_3 = negative(base_ts + int(3 * w), 22)
    sink.recent_negative_works.extend([work_2, work_3])

    orig = sink._write_bytes
    state = {"fails_left": 1}

    def flaky(path, data, **kw):
        if state["fails_left"]:
            state["fails_left"] -= 1
            raise TimeoutError("transient disk hiccup")
        return orig(path, data, **kw)

    sink._write_bytes = flaky
    trigger = negative(base_ts + int(2 * w), 23)  # outside the window
    with pytest.raises(TimeoutError):
        sink.push(trigger, False)
    # retry re-entry, fresh work wrapper around the same segment
    sink.push(SegmentResultWork(segment=trigger.segment,
                                waterfall=None, detect=None), False)
    assert [c.bin_path for c in sink.written] \
        == [str(tmp_path / "piggy_21.bin")]
    remaining = [wk.segment.udp_packet_counter
                 for wk in sink.recent_negative_works]
    assert 22 in remaining  # work_3 was not mis-scheduled by the retry
    metrics.reset()


def test_pipeline_shutdown_join_is_bounded(tmp_path):
    """A sink wedged on an external resource must not hang run()'s
    shutdown forever: the bounded join expires, reports, and returns
    (the watchdog shed already accounted the stuck segment)."""

    class _WedgedSink:
        def __init__(self):
            self.release = threading.Event()
            self.entered = threading.Event()

        def push(self, work, positive):
            self.entered.set()
            self.release.wait()

    metrics.reset()
    sink = _WedgedSink()
    cfg = _watchdog_cfg(tmp_path, "wedge", inflight_segments=2,
                        segment_deadline_s=0.12,
                        segment_watchdog_requeues=1,
                        shutdown_join_timeout_s=0.25)
    pipe = Pipeline(cfg, source=_CountingSource(4), sinks=[sink],
                    processor=_InstantProcessor())
    t0 = time.monotonic()
    with pipe:
        stats = pipe.run()
    assert time.monotonic() - t0 < 20.0
    assert sink.entered.is_set()
    # full accounting, no silent loss: of the 4 produced segments, the
    # one wedged inside the sink (never journaled) and the one parked
    # on the sink queue were accounted as dropped at shutdown, and the
    # two the engine could no longer admit were shed at ingest as
    # accounted loss (the never-stall property); the join stayed
    # bounded throughout
    from srtb_tpu.tools import telemetry_report as TR

    dropped = metrics.get("segments_dropped")
    journaled = len(TR.load(str(tmp_path / "wedge.jsonl")))
    assert stats.segments == 2      # A, B dispatched before the wedge
    assert dropped == 4             # A (wedged), B (queued), C, D (shed)
    assert journaled == 0           # nothing fully drained
    assert journaled + dropped == 4  # every produced segment accounted
    assert metrics.get("wedged_threads") >= 1
    # handoff: the wedged worker unwedging AFTER shutdown accounted
    # its segment as dropped must not ALSO journal/count it (double
    # account) or re-release the live slot (gauge going negative)
    sink.release.set()
    deadline = time.monotonic() + 5.0
    while any(t.name == "sink_drain" and t.is_alive()
              for t in threading.enumerate()) \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert metrics.get("segments_dropped") == 4
    assert len(TR.load(str(tmp_path / "wedge.jsonl"))) == 0
    assert metrics.get("inflight_depth") == 0
    metrics.reset()


def test_threaded_completion_join_not_truncated_by_budget(tmp_path):
    """ThreadedPipeline's wait-for-completion lasts the whole run: a
    healthy observation longer than shutdown_join_timeout_s must NOT
    be cut short — the budget bounds only a wedged drain (busy on one
    item with zero per-sink progress), not slow-but-steady work."""
    from srtb_tpu.pipeline.runtime import ThreadedPipeline

    class _SlowSink:
        def __init__(self):
            self.pushed = 0

        def push(self, work, positive):
            time.sleep(0.1)
            self.pushed += 1

    metrics.reset()
    sink = _SlowSink()
    cfg = _watchdog_cfg(tmp_path, "tcomplete",
                        shutdown_join_timeout_s=0.3)
    pipe = ThreadedPipeline(cfg, source=_CountingSource(8), sinks=[sink],
                            processor=_InstantProcessor())
    with pipe:
        stats = pipe.run()  # total sink time ~0.8s > the 0.3s budget
    assert stats.segments == 8
    assert sink.pushed == 8
    assert metrics.get("segments_dropped") == 0
    metrics.reset()


def test_threaded_shutdown_join_is_bounded_on_wedged_sink(tmp_path):
    """...but a ThreadedPipeline drain wedged inside one sink write
    still must not hang run() forever."""
    from srtb_tpu.pipeline.runtime import ThreadedPipeline

    class _WedgedSink:
        def __init__(self):
            self.release = threading.Event()
            self.entered = threading.Event()

        def push(self, work, positive):
            self.entered.set()
            self.release.wait()

    metrics.reset()
    sink = _WedgedSink()
    cfg = _watchdog_cfg(tmp_path, "twedge",
                        shutdown_join_timeout_s=0.25)
    pipe = ThreadedPipeline(cfg, source=_CountingSource(3), sinks=[sink],
                            processor=_InstantProcessor())
    t0 = time.monotonic()
    with pipe:
        pipe.run()
    assert time.monotonic() - t0 < 20.0
    assert sink.entered.is_set()
    sink.release.set()
    metrics.reset()


# ------------------------------------------------- mixed v2/v3 journal


def test_telemetry_report_tolerates_mixed_v2_v3(tmp_path):
    """Rotation can leave a v2 tail next to v3 records: stages cover
    both, the resilience section only the v3 ones."""
    from srtb_tpu.tools import telemetry_report as TR

    path = tmp_path / "mixed23.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({
            "type": "segment_span", "v": 2, "ts": 1000.0, "segment": 0,
            "stages_ms": {"dispatch": 2.0, "fetch": 1.0},
            "queue_depth": 1, "detections": 0, "dump": False,
            "samples": 64, "overlap_hidden_ms": 3.0,
            "inflight_depth": 2}) + "\n")
        f.write(json.dumps({
            "type": "segment_span", "v": 3, "ts": 1001.0, "segment": 1,
            "stages_ms": {"dispatch": 2.0, "fetch": 1.0},
            "queue_depth": 1, "detections": 0, "dump": False,
            "samples": 64, "overlap_hidden_ms": 3.0,
            "inflight_depth": 2, "degrade_level": 1, "retries": 4,
            "requeues": 1, "restarts": 0, "shed_waterfalls": 2,
            "shed_baseband": 0}) + "\n")
    rep = TR.report(str(path))
    assert rep["records"] == 2
    assert rep["stages"]["dispatch"]["count"] == 2
    assert rep["overlap"]["records"] == 2
    rs = rep["resilience"]
    assert rs["records"] == 1
    assert rs["retries"] == 4 and rs["requeues"] == 1
    assert rs["degrade_level_max"] == 1 and rs["segments_degraded"] == 1
    md = TR._md(rep)
    assert "## Resilience" in md
    assert TR.main([str(path), "--format", "json"]) == 0


def test_telemetry_report_tolerates_mixed_v2_v3_v4(tmp_path):
    """A v4 upgrade mid-rotation: stages cover every record, the
    resilience section the v3+v4 ones, the compute-health section
    only the v4 ones — and the active-plan timeline reads change
    points off the v4 tail."""
    from srtb_tpu.tools import telemetry_report as TR

    path = tmp_path / "mixed234.jsonl"
    base = {"type": "segment_span", "queue_depth": 1, "detections": 0,
            "dump": False, "samples": 64,
            "stages_ms": {"dispatch": 2.0, "fetch": 1.0},
            "overlap_hidden_ms": 3.0, "inflight_depth": 2}
    with open(path, "w") as f:
        f.write(json.dumps({**base, "v": 2, "ts": 1000.0,
                            "segment": 0}) + "\n")
        f.write(json.dumps({**base, "v": 3, "ts": 1001.0, "segment": 1,
                            "degrade_level": 0, "retries": 2,
                            "requeues": 0, "restarts": 0,
                            "shed_waterfalls": 0,
                            "shed_baseband": 0}) + "\n")
        for seg, plan, dem, lvl in ((2, "fused:four_step+ring", 0, 0),
                                    (3, "fused:four_step", 1, 1),
                                    (4, "fused:four_step", 1, 1)):
            f.write(json.dumps({
                **base, "v": 4, "ts": 1002.0 + seg, "segment": seg,
                "degrade_level": 0, "retries": 2, "requeues": 0,
                "restarts": 0, "shed_waterfalls": 0,
                "shed_baseband": 0, "plan_demotions": dem,
                "plan_promotions": 0, "device_reinits": 0,
                "plan_ladder_level": lvl,
                "active_plan": plan}) + "\n")
    rep = TR.report(str(path))
    assert rep["records"] == 5
    assert rep["stages"]["dispatch"]["count"] == 5
    assert rep["resilience"]["records"] == 4  # v3 + v4
    cs = rep["compute"]
    assert cs["records"] == 3  # v4 only
    assert cs["plan_demotions"] == 1 and cs["device_reinits"] == 0
    assert cs["ladder_level_max"] == 1 and cs["segments_demoted"] == 2
    assert cs["plan_timeline"] == [
        {"segment": 2, "plan": "fused:four_step+ring"},
        {"segment": 3, "plan": "fused:four_step"}]
    md = TR._md(rep)
    assert "## Compute health" in md
    assert "fused:four_step+ring" in md
    assert TR.main([str(path), "--format", "json"]) == 0


# (the repo-wide swallowed-except acceptance rides the existing
# test_lint.py::test_repo_lints_clean_against_baseline, which runs
# EVERY rule — including the new one — against the checked-in
# baseline; no duplicate whole-repo lint pass here)


# ----------------------------- the ingest site with the reader ahead


class _SlowCountingSource(_CountingSource):
    """A pull slower than the stub device's step: the served loop asks
    its reader for segment k+1 while it works on k."""

    def __next__(self) -> SegmentWork:
        time.sleep(0.02)
        return super().__next__()


def test_ingest_fault_addresses_the_same_segment_with_the_reader_ahead(
        tmp_path, monkeypatch):
    """``("ingest", index)`` is the site of the pull that makes segment
    ``index``, wherever that pull runs: the fault fires on the reader's
    thread with ``index`` segments handed out, the retry re-runs that
    pull, and the sinks see every segment once, in order."""
    from srtb_tpu.pipeline import runtime
    from srtb_tpu.tools import telemetry_report as TR

    # the reader engaged at the first period the loop reads (segments
    # 3 to 7 are its pulls), not where this machine's load lets the
    # 20 ms pull outlast half a period
    monkeypatch.setattr(runtime, "_PULL_AHEAD_SHARE", 0.0)
    metrics.reset()
    cfg = _watchdog_cfg(tmp_path, "ahead_fault", inflight_segments=2,
                        fault_plan="ingest:raise@5")
    source = _SlowCountingSource(8)
    sink = _CrashingSink(crashes=0)
    fired = []
    fire = FaultInjector.fire

    def spying(self, site, index):
        pending = not self._by_site[site][index].fired \
            if index in self._by_site.get(site, {}) else False
        if pending:
            fired.append((site, index, source._i,
                          threading.current_thread().name))
        return fire(self, site, index)

    FaultInjector.fire = spying
    try:
        with Pipeline(cfg, source=source, sinks=[sink],
                      processor=_InstantProcessor()) as pipe:
            stats = pipe.run()
            assert pipe.faults.unfired() == []
    finally:
        FaultInjector.fire = fire
    assert stats.segments == 8
    assert fired == [("ingest", 5, 5, "reader")]
    assert sink.pushed == list(range(1, 9))
    assert metrics.get("retries_ingest") == 1
    assert metrics.get("segments_dropped") == 0
    assert metrics.get("ingest_ahead") == 5
    recs = TR.load(cfg.telemetry_journal_path)
    assert [r["segment"] for r in recs] == list(range(8))
    assert [r["timestamp_ns"] for r in recs] == list(range(1, 9))
    metrics.reset()
