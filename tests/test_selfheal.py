"""Self-healing compute tests (resilience/demote.py + the engine
wiring in pipeline/runtime.py).

Covers the acceptance criteria of the self-healing subsystem:
- device-fault classification from the REAL exception strings jax
  raises (RESOURCE_EXHAUSTED / Mosaic compile / device halt) plus the
  typed shortcut classes, and the retry policy never retrying them;
- the demotion ladder: rung order, resolution-aware skipping,
  cumulative configs, distinct plan signatures per rung;
- recovery end-to-end on a real plan: an injected OOM or compile
  fault demotes and re-dispatches the faulted segment from its
  retained host buffer with detection decisions identical to a
  fault-free run; an injected device halt reinitializes the backend
  (fresh processor, invalidated ring carry — the post-reinit dispatch
  goes COLD instead of assembling against a dead device buffer);
- budget escalation: the ladder exhausts, the reinit budget expires,
  and disabled healing all escalate loudly;
- the promotion probe steps back up after N healthy segments;
- interplay with the existing machinery: demotion of a segment the
  watchdog just requeued, demotion while the degradation ladder is
  active, checkpoint resume offsets unchanged by demotion;
- the chaos soak harness (tools/chaos_soak.py) gate + selftest;
- the plan-audit ladder-target guard (every demotion target is a
  carded plan family).
"""

import json
import os
import time
from typing import NamedTuple

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.pipeline.runtime import Pipeline, ThreadedPipeline
from srtb_tpu.pipeline.work import SegmentWork
from srtb_tpu.resilience import errors as E
from srtb_tpu.resilience.demote import (ComputeHealer, ladder_rungs,
                                        parse_ladder)
from srtb_tpu.resilience.faults import parse_plan
from srtb_tpu.resilience.retry import RetryPolicy, retry_call
from srtb_tpu.utils.metrics import metrics


class _FakeXla(Exception):
    """Local stand-in with jaxlib's type name — classification must
    key on name + message, exactly as for the real class."""


_FakeXla.__name__ = "XlaRuntimeError"

_OOM_MSG = ("RESOURCE_EXHAUSTED: Out of memory while trying to "
            "allocate 68719476736 bytes.")
_COMPILE_MSG = "INTERNAL: Mosaic failed to compile TPU kernel: oops"
_HALT_MSG = ("INTERNAL: Accelerator device halted prematurely, "
             "perhaps due to an on-device check-failure.")


# ------------------------------------------------------ classification


def test_classify_device_real_strings():
    assert E.classify_device(_FakeXla(_OOM_MSG)) == E.DEVICE_OOM
    assert E.classify_device(_FakeXla(_COMPILE_MSG)) == E.DEVICE_COMPILE
    assert E.classify_device(_FakeXla(_HALT_MSG)) == E.DEVICE_HALT
    # CPU allocator phrasing
    assert E.classify_device(
        _FakeXla("Out of memory allocating 1024 bytes.")) == E.DEVICE_OOM
    # unrecognized XLA error: NOT a device fault (stays fatal)
    assert E.classify_device(_FakeXla("INVALID_ARGUMENT: bad")) is None
    assert E.classify(_FakeXla("INVALID_ARGUMENT: bad")) == E.FATAL
    # marker strings inside a NON-XLA exception must stay fatal: a
    # ValueError from user code mentioning OOM is not a device fault
    assert E.classify_device(ValueError(_OOM_MSG)) is None
    assert E.classify(ValueError(_OOM_MSG)) == E.FATAL
    # device classification feeds the DEVICE category
    assert E.classify(_FakeXla(_OOM_MSG)) == E.DEVICE


def test_classify_device_typed_and_compile_type_names():
    assert E.classify_device(E.DeviceOOM("x")) == E.DEVICE_OOM
    assert E.classify_device(E.CompileFault("x")) == E.DEVICE_COMPILE
    assert E.classify_device(E.DeviceHalt("x")) == E.DEVICE_HALT
    assert E.classify(E.DeviceHalt("x")) == E.DEVICE
    # typed non-device pipeline errors keep their category
    assert E.classify_device(E.FatalError(_OOM_MSG)) is None

    class MosaicError(Exception):
        pass

    assert E.classify_device(MosaicError("bad lowering")) \
        == E.DEVICE_COMPILE
    # escalation types are fatal
    assert E.classify(E.LadderExhausted("x")) == E.FATAL
    assert E.classify(E.ReinitBudgetExceeded("x")) == E.FATAL


def test_retry_never_retries_device_faults():
    metrics.reset()
    calls = []

    def oom():
        calls.append(1)
        raise _FakeXla(_OOM_MSG)

    p = RetryPolicy(max_attempts=5, backoff_base_s=0.001)
    with pytest.raises(_FakeXla):
        retry_call(oom, p, "t", sleep=lambda s: None)
    assert len(calls) == 1  # no retry: verbatim re-run OOMs verbatim
    assert metrics.get("retries_total") == 0
    metrics.reset()


def test_fault_plan_device_actions():
    specs = parse_plan("dispatch:oom@1,fetch:compile_fail@2,"
                       "h2d:device_halt@3")
    assert [s.action for s in specs] == ["oom", "compile_fail",
                                        "device_halt"]
    # device actions only at device sites
    with pytest.raises(ValueError, match="device site"):
        parse_plan("ingest:oom@1")
    with pytest.raises(ValueError, match="device site"):
        parse_plan("sink_write:device_halt@0")


# ------------------------------------------------------------- ladder


def _featured_cfg(n=1 << 16, **extra):
    base = dict(baseband_input_count=n, baseband_input_bits=2,
                baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                baseband_sample_rate=128e6, dm=0.1,
                spectrum_channel_count=8,
                mitigate_rfi_average_method_threshold=25.0,
                mitigate_rfi_spectral_kurtosis_threshold=1.05,
                signal_detect_max_boxcar_length=8,
                fft_strategy="four_step", fused_tail="on",
                use_pallas=True, use_pallas_sk=True,
                micro_batch_segments=2, baseband_reserve_sample=True)
    base.update(extra)
    return Config(**base)


def test_ladder_rungs_order_and_cumulative():
    rungs = ladder_rungs(_featured_cfg())
    assert [r.step for r in rungs] == [
        "micro_batch", "ring", "skzap", "fused_tail", "staged",
        "monolithic"]
    # cumulative: the last rung carries every earlier demotion
    last = rungs[-1].cfg
    assert last.micro_batch_segments == 1
    assert last.ingest_ring == "off"
    assert not last.use_pallas_sk and not last.use_pallas
    assert last.fused_tail == "off"
    assert last.fft_strategy == "monolithic"
    assert rungs[-1].staged is False and rungs[-2].staged is True


def test_ladder_skips_unresolvable_rungs():
    # minimal config: no micro-batch, no reserved tail (ring dead), no
    # pallas, auto strategy resolves monolithic at small n, fused_tail
    # auto resolves off on monolithic -> only staged + monolithic left
    cfg = Config(baseband_input_count=1 << 12,
                 baseband_reserve_sample=False)
    assert [r.step for r in ladder_rungs(cfg)] == ["staged",
                                                   "monolithic"]
    # a processor ALREADY running staged skips the staged rung — but
    # gains the fused_tail rung (auto resolves ON for a staged plan,
    # which hosts the epilogue even where the strategy is monolithic)
    steps = [r.step for r in ladder_rungs(cfg, base_staged=True)]
    assert steps == ["fused_tail", "monolithic"]


def test_parse_ladder_modes():
    assert parse_ladder("auto") == parse_ladder("") \
        == parse_ladder(None)
    assert parse_ladder("off") == ()
    assert parse_ladder("ring, monolithic") == ("ring", "monolithic")
    with pytest.raises(ValueError, match="plan_ladder step"):
        parse_ladder("ring,warp_drive")


def test_ladder_rung_signatures_all_distinct():
    from srtb_tpu.pipeline.segment import SegmentProcessor
    cfg = _featured_cfg()
    sigs = {SegmentProcessor(cfg, donate_input=True).plan_signature()}
    for rung in ladder_rungs(cfg):
        proc = SegmentProcessor(rung.cfg, staged=rung.staged,
                                donate_input=True)
        sig = proc.plan_signature()
        # every rung's AOT/plan signature differs from every other
        # plan's: a demotion can never load a stale executable
        assert sig not in sigs, rung.step
        sigs.add(sig)


def test_config_knobs_parse():
    cfg = Config()
    assert cfg.set_option("plan_ladder", "ring,monolithic")
    assert cfg.plan_ladder == "ring,monolithic"
    assert cfg.set_option("promote_after_segments", "4")
    assert cfg.promote_after_segments == 4
    assert cfg.set_option("device_reinit_max", "0")
    assert cfg.device_reinit_max == 0
    assert cfg.set_option("device_reinit_window_s", "60")
    assert cfg.device_reinit_window_s == 60.0


# ------------------------------------------- real-plan recovery (e2e)

N_SEG = 1 << 13
SEGMENTS = 4


@pytest.fixture(scope="module")
def synth_file(tmp_path_factory):
    from srtb_tpu.io.synth import make_dispersed_baseband
    tmp = tmp_path_factory.mktemp("selfheal")
    path = tmp / "bb.bin"
    make_dispersed_baseband(
        N_SEG * SEGMENTS, 1405.0, 64.0, 0.05,
        pulse_positions=[N_SEG // 2 + i * N_SEG
                         for i in range(SEGMENTS)],
        pulse_amp=30.0, nbits=8).tofile(path)
    return str(path)


def _cfg(path, tmp_path, tag, **extra):
    return Config(
        baseband_input_count=N_SEG, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=0.05,
        input_file_path=path,
        baseband_output_file_prefix=str(tmp_path / f"{tag}_"),
        spectrum_channel_count=32,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=True,  # the ring rung is live
        writer_thread_count=0, fft_strategy="four_step",
        inflight_segments=2, retry_backoff_base_s=0.001, **extra)


class _CaptureSink:
    def __init__(self):
        self.out = []
        self.positives = []

    def push(self, work, positive):
        det = work.detect
        self.out.append((np.asarray(det.signal_counts).copy(),
                         np.asarray(det.zero_count).copy(),
                         np.asarray(det.time_series).copy()))
        self.positives.append(bool(positive))


def _assert_decisions_equal(a: _CaptureSink, b: _CaptureSink,
                            ts_exact=True):
    assert len(a.out) == len(b.out)
    for (sc_a, zc_a, ts_a), (sc_b, zc_b, ts_b) in zip(a.out, b.out):
        np.testing.assert_array_equal(sc_a, sc_b)
        np.testing.assert_array_equal(zc_a, zc_b)
        if ts_exact:
            np.testing.assert_array_equal(ts_a, ts_b)
        else:  # demoted-plan documented tolerance (test_fusion.py)
            scale = float(np.abs(ts_b).max()) or 1.0
            np.testing.assert_allclose(ts_a, ts_b, rtol=0,
                                       atol=1e-3 * scale)
    assert a.positives == b.positives


@pytest.fixture(scope="module")
def clean_baseline(synth_file, tmp_path_factory):
    """Fault-free run with self-healing OFF: the parity reference."""
    tmp = tmp_path_factory.mktemp("clean")
    metrics.reset()
    sink = _CaptureSink()
    with Pipeline(_cfg(synth_file, tmp, "clean", plan_ladder="off",
                       device_reinit_max=0), sinks=[sink]) as pipe:
        stats = pipe.run()
    counters = {k: metrics.get(k) for k in ("h2d_bytes",
                                            "ring_cold_dispatches")}
    metrics.reset()
    assert stats.segments >= SEGMENTS  # overlap-save adds a tail seg
    return stats, sink, counters


def test_clean_run_with_ladder_armed_is_bit_identical(
        synth_file, tmp_path, clean_baseline):
    """Zero-cost off: arming the full self-healing stack on a healthy
    run changes nothing, bit for bit."""
    stats0, sink0, c0 = clean_baseline
    metrics.reset()
    sink = _CaptureSink()
    with Pipeline(_cfg(synth_file, tmp_path, "armed",
                       promote_after_segments=2),
                  sinks=[sink]) as pipe:
        stats = pipe.run()
        assert pipe.healer is not None
        assert [r.step for r in pipe.healer.rungs]  # rungs resolved
    assert stats.segments == stats0.segments
    _assert_decisions_equal(sink, sink0, ts_exact=True)
    assert metrics.get("plan_demotions") == 0
    assert metrics.get("device_reinits") == 0
    assert metrics.get("plan_ladder_level") == 0
    # identical H2D traffic too: healing must not perturb the ring
    assert metrics.get("h2d_bytes") == c0["h2d_bytes"]
    metrics.reset()


def test_oom_at_dispatch_demotes_and_recovers(synth_file, tmp_path,
                                              clean_baseline):
    _, sink0, _ = clean_baseline
    from srtb_tpu.tools import telemetry_report as TR
    metrics.reset()
    jpath = str(tmp_path / "oom.jsonl")
    sink = _CaptureSink()
    with Pipeline(_cfg(synth_file, tmp_path, "oom",
                       fault_plan="dispatch:oom@1",
                       telemetry_journal_path=jpath),
                  sinks=[sink]) as pipe:
        stats = pipe.run()
        assert pipe.faults.unfired() == []
        assert pipe.healer.level == 1
        assert pipe.healer.active_step == "ring"
    assert stats.segments == len(sink0.out)
    # ring rung drops the ring only — outputs stay BIT-identical
    _assert_decisions_equal(sink, sink0, ts_exact=True)
    assert metrics.get("plan_demotions") == 1
    assert metrics.get("segments_dropped") == 0
    assert metrics.get("plan_ladder_level") == 1
    # v4 journal: counters + the active-plan timeline
    recs = TR.load(jpath)
    assert recs and all(r["v"] == 13 for r in recs)
    assert recs[-1]["plan_demotions"] == 1
    assert recs[-1]["plan_ladder_level"] == 1
    plans = {r.get("active_plan") for r in recs}
    assert all(p is not None for p in plans)
    rep = TR.report(jpath)
    assert rep["compute"]["plan_demotions"] == 1
    assert rep["compute"]["ladder_level_max"] == 1
    metrics.reset()


def test_compile_fault_at_fetch_demotes_and_recovers(
        synth_file, tmp_path, clean_baseline):
    """A compile fault surfacing at the FETCH site (lazy compile /
    execution error materializing at the blocking device_get): the
    segment's device results are gone — it must be re-dispatched from
    the retained host buffer under the demoted plan."""
    _, sink0, _ = clean_baseline
    metrics.reset()
    sink = _CaptureSink()
    with Pipeline(_cfg(synth_file, tmp_path, "cfail",
                       fault_plan="fetch:compile_fail@2"),
                  sinks=[sink]) as pipe:
        stats = pipe.run()
        assert pipe.faults.unfired() == []
    assert stats.segments == len(sink0.out)
    _assert_decisions_equal(sink, sink0, ts_exact=True)
    assert metrics.get("plan_demotions") == 1
    assert metrics.get("segments_dropped") == 0
    metrics.reset()


def test_device_halt_reinit_goes_cold_and_rebuilds(
        synth_file, tmp_path, clean_baseline):
    """The reinit regression satellite: after a device halt the warm
    ingest-ring carry and the old processor's program handles are
    dead.  Recovery must rebuild the processor, and every post-reinit
    dispatch must go COLD (full upload) instead of warm-assembling
    against the dead carry."""
    _, sink0, c0 = clean_baseline
    metrics.reset()
    sink = _CaptureSink()
    with Pipeline(_cfg(synth_file, tmp_path, "halt",
                       fault_plan="dispatch:device_halt@2"),
                  sinks=[sink]) as pipe:
        proc0 = pipe.processor
        assert proc0.ring
        stats = pipe.run()
        assert pipe.faults.unfired() == []
        proc1 = pipe.processor
    assert stats.segments == len(sink0.out)
    _assert_decisions_equal(sink, sink0, ts_exact=True)
    assert metrics.get("device_reinits") == 1
    assert metrics.get("plan_demotions") == 0  # same rung, new backend
    assert metrics.get("plan_ladder_level") == 0
    # the processor was rebuilt, and the old one is retired: a stray
    # dispatch against the dead handles raises instead of running
    assert proc1 is not proc0
    with pytest.raises(RuntimeError, match="retired"):
        proc0.run_device(np.zeros(proc1._segment_bytes, np.uint8))
    # post-reinit dispatches went cold: strictly more cold uploads
    # than the clean run's single ring-arming one
    assert metrics.get("ring_cold_dispatches") \
        > c0["ring_cold_dispatches"]
    assert metrics.get("h2d_bytes") > c0["h2d_bytes"]
    metrics.reset()


def test_reinit_budget_escalates(synth_file, tmp_path):
    metrics.reset()
    with Pipeline(_cfg(synth_file, tmp_path, "flap",
                       fault_plan=("dispatch:device_halt@1,"
                                   "fetch:device_halt@2"),
                       device_reinit_max=1), sinks=[]) as pipe:
        # the escaped exception is the TYPED FatAL escalation (an
        # outer supervisor must see FATAL, never a restartable
        # DEVICE), still carrying the original device error text
        with pytest.raises(E.ReinitBudgetExceeded, match="halted"):
            pipe.run()
    assert metrics.get("device_reinits") == 1  # budget spent, then loud
    # reinit budgeting must NOT masquerade as worker restarts
    assert metrics.get("worker_restarts") == 0
    metrics.reset()


def test_ladder_exhausted_escalates(synth_file, tmp_path):
    """plan_ladder restricted to ONE rung: the second oom has nowhere
    to go and must escalate with the original device error."""
    metrics.reset()
    with Pipeline(_cfg(synth_file, tmp_path, "exh",
                       plan_ladder="monolithic",
                       fault_plan="dispatch:oom@1,dispatch:oom@2"),
                  sinks=[]) as pipe:
        assert [r.step for r in pipe.healer.rungs] == ["monolithic"]
        with pytest.raises(E.LadderExhausted,
                           match="RESOURCE_EXHAUSTED"):
            pipe.run()
    assert metrics.get("plan_demotions") == 1
    metrics.reset()


def test_healing_disabled_escalates(synth_file, tmp_path):
    metrics.reset()
    with Pipeline(_cfg(synth_file, tmp_path, "off",
                       plan_ladder="off", device_reinit_max=0,
                       fault_plan="dispatch:oom@1"),
                  sinks=[]) as pipe:
        assert pipe.healer is None
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            pipe.run()
    assert metrics.get("plan_demotions") == 0
    metrics.reset()


def test_promotion_probe_returns_to_full_plan(synth_file, tmp_path,
                                              clean_baseline):
    _, sink0, _ = clean_baseline
    metrics.reset()
    sink = _CaptureSink()
    with Pipeline(_cfg(synth_file, tmp_path, "promo",
                       fault_plan="dispatch:oom@1",
                       promote_after_segments=1),
                  sinks=[sink]) as pipe:
        stats = pipe.run()
        assert pipe.healer.level == 0  # probed back up and stayed
    assert stats.segments == len(sink0.out)
    _assert_decisions_equal(sink, sink0, ts_exact=True)
    assert metrics.get("plan_demotions") == 1
    assert metrics.get("plan_promotions") >= 1
    assert metrics.get("plan_ladder_level") == 0
    metrics.reset()


def test_threaded_pipeline_demotes_on_oom(synth_file, tmp_path,
                                          clean_baseline):
    _, sink0, _ = clean_baseline
    metrics.reset()
    sink = _CaptureSink()
    with ThreadedPipeline(_cfg(synth_file, tmp_path, "thr",
                               fault_plan="dispatch:oom@1"),
                          sinks=[sink]) as pipe:
        stats = pipe.run()
        assert pipe.faults.unfired() == []
    assert stats.segments == len(sink0.out)
    _assert_decisions_equal(sink, sink0, ts_exact=True)
    assert metrics.get("plan_demotions") == 1
    metrics.reset()


# --------------------------------- interplay with existing machinery


class _StubDetect(NamedTuple):
    signal_counts: object
    zero_count: object
    time_series: object


class _NeverReady:
    def is_ready(self) -> bool:
        return False

    def __array__(self, dtype=None, copy=None):
        raise AssertionError("a cancelled segment's results were read")


def _stub_result(raw):
    val = float(np.asarray(raw, dtype=np.float32).sum())
    return None, _StubDetect(
        signal_counts=np.zeros((1, 4), np.int64),
        zero_count=np.asarray(0),
        time_series=np.asarray([val], np.float32))


class _InstantProcessor:
    def process(self, raw):
        return _stub_result(raw)


class _WedgeThenOOMProcessor:
    """Segment 0's first dispatch: never-ready -> watchdog requeue.
    Segment 0's SECOND dispatch (the requeue) raises a device OOM ->
    demotion.  Keyed on the segment's bytes, not a global dispatch
    counter: other in-flight segments dispatch in between."""

    def __init__(self):
        self.seg0_dispatches = 0

    def process(self, raw):
        if int(np.asarray(raw)[0]) == 1:  # _CountingSource segment 0
            self.seg0_dispatches += 1
            if self.seg0_dispatches == 1:
                return None, _StubDetect(_NeverReady(), _NeverReady(),
                                         _NeverReady())
            if self.seg0_dispatches == 2:
                raise _FakeXla(_OOM_MSG)
        return _stub_result(raw)


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


def _stub_cfg(tmp_path, tag, **extra):
    return Config(baseband_input_count=64,
                  baseband_reserve_sample=False,
                  writer_thread_count=0,
                  retry_backoff_base_s=0.001,
                  telemetry_journal_path=str(tmp_path / f"{tag}.jsonl"),
                  **extra)


def test_demotion_of_watchdog_requeued_segment(tmp_path):
    """The watchdog cancels a wedged segment and re-dispatches it;
    the re-dispatch hits an OOM.  The heal path inside the requeue
    must demote and retry the SAME segment — requeue and demotion
    compose, neither mechanism loses the segment."""
    metrics.reset()
    cfg = _stub_cfg(tmp_path, "wdheal", inflight_segments=2,
                    segment_deadline_s=0.12,
                    segment_watchdog_requeues=2)
    sink = _CaptureSink()
    pipe = Pipeline(cfg, source=_CountingSource(4), sinks=[sink],
                    processor=_WedgeThenOOMProcessor())
    # the demoted "plan" for a stub pipeline is another stub
    pipe.healer._factory = lambda cfg, staged: _InstantProcessor()
    with pipe:
        stats = pipe.run()
    assert stats.segments == 4 and len(sink.out) == 4
    assert metrics.get("watchdog_requeues") == 1
    assert metrics.get("plan_demotions") == 1
    assert metrics.get("segments_dropped") == 0
    # decisions: every segment's stub value is the sum of its bytes —
    # segment 0 (wedged, then demoted) included
    vals = [float(ts[0]) for _, _, ts in sink.out]
    assert vals == [64.0 * (i + 1) for i in range(4)]
    metrics.reset()


class _OOMOnceProcessor:
    def __init__(self, fault_at: int):
        self.fault_at = fault_at
        self.dispatches = 0
        self.faulted = False

    def process(self, raw):
        self.dispatches += 1
        if self.dispatches == self.fault_at and not self.faulted:
            self.faulted = True
            raise _FakeXla(_OOM_MSG)
        return _stub_result(raw)


class _SlowSink:
    """Real-time-slow sheddable sink: every push stalls long enough
    that the engine observes sink pressure and walks the degradation
    ladder."""

    sheddable = True

    def __init__(self, sink_s: float):
        self.sink_s = sink_s
        self.pushed = 0

    def push(self, work, positive):
        self.pushed += 1
        time.sleep(self.sink_s)


def test_demotion_under_active_degrade_ladder(tmp_path):
    """Both ladders at once: a real-time source with a slow sink
    drives the DEGRADATION ladder up while a device OOM demotes the
    COMPUTE ladder — independent state machines, both accounted, and
    the journal carries both levels."""
    from srtb_tpu.tools import telemetry_report as TR
    metrics.reset()
    n_seg = 10
    cfg = _stub_cfg(tmp_path, "dual", inflight_segments=2,
                    degrade_enable=True, degrade_queue_high=0.5,
                    degrade_hold_segments=1)
    proc = _OOMOnceProcessor(fault_at=4)
    pipe = Pipeline(cfg, source=_CountingSource(n_seg),
                    sinks=[_SlowSink(0.05)], processor=proc)
    pipe.healer._factory = lambda cfg, staged: _InstantProcessor()
    with pipe:
        stats = pipe.run()
    assert stats.segments == n_seg
    assert metrics.get("plan_demotions") == 1
    assert metrics.get("degrade_steps") >= 1
    recs = TR.load(str(tmp_path / "dual.jsonl"))
    assert any(r["degrade_level"] > 0 and r["plan_ladder_level"] > 0
               for r in recs), "both ladders never active together"
    metrics.reset()


def test_checkpoint_resume_after_demotion_offsets_unchanged(
        synth_file, tmp_path, clean_baseline):
    """A run that demoted mid-stream checkpoints the same offsets as
    one that never faulted — the demoted plan changes the compute,
    never the stream bookkeeping — and a resume completes the
    remainder with decision-identical output."""
    _, sink0, _ = clean_baseline
    ck_clean = str(tmp_path / "ck_clean.json")
    ck_heal = str(tmp_path / "ck_heal.json")
    # clean checkpointed run, first 2 segments
    metrics.reset()
    with Pipeline(_cfg(synth_file, tmp_path, "ckc",
                       checkpoint_path=ck_clean), sinks=[]) as pipe:
        pipe.run(max_segments=2)
    with open(ck_clean) as f:
        state_clean = json.load(f)
    # demoted run, same 2 segments (oom at segment 1)
    metrics.reset()
    sink = _CaptureSink()
    with Pipeline(_cfg(synth_file, tmp_path, "ckh",
                       checkpoint_path=ck_heal,
                       fault_plan="dispatch:oom@1"),
                  sinks=[sink]) as pipe:
        pipe.run(max_segments=2)
        assert pipe.healer.level == 1
    with open(ck_heal) as f:
        state_heal = json.load(f)
    assert state_heal == state_clean  # resume offsets unchanged
    # resume the demoted run to completion: a fresh process starts at
    # ladder level 0 (full plan) and finishes the stream
    metrics.reset()
    with Pipeline(_cfg(synth_file, tmp_path, "ckh",
                       checkpoint_path=ck_heal),
                  sinks=[sink]) as pipe:
        assert pipe.healer.level == 0
        pipe.run()
    assert len(sink.out) == len(sink0.out)
    _assert_decisions_equal(sink, sink0, ts_exact=True)
    metrics.reset()


def test_micro_batch_demotion_drops_batch_unit(tmp_path):
    """The first rung of a micro-batching run drops the batch: the
    engine's dispatch unit must follow (the demoted plan has no batch
    programs), and every segment still drains exactly once."""

    class _BatchOOMProcessor:
        """Stub micro-batch processor whose FIRST batch dispatch
        OOMs; the healed (stub) replacement is single-segment."""

        def __init__(self):
            self.batches = 0

        def process(self, raw):
            return _stub_result(raw)

        def process_batch(self, raws):
            self.batches += 1
            raise _FakeXla(_OOM_MSG)

        def stack_batch(self, datas, stride_only=False):
            return np.stack([np.ascontiguousarray(d) for d in datas])

    metrics.reset()
    cfg = _stub_cfg(tmp_path, "mb", inflight_segments=2,
                    micro_batch_segments=2)
    sink = _CaptureSink()
    pipe = Pipeline(cfg, source=_CountingSource(5), sinks=[sink],
                    processor=_BatchOOMProcessor())
    assert pipe.healer.micro_batch == 2
    pipe.healer._factory = lambda cfg, staged: _InstantProcessor()
    with pipe:
        stats = pipe.run()
    assert stats.segments == 5 and len(sink.out) == 5
    assert metrics.get("plan_demotions") == 1
    assert pipe.healer.active_step == "micro_batch"
    assert pipe.healer.micro_batch == 1  # the engine unit followed
    vals = [float(ts[0]) for _, _, ts in sink.out]
    assert vals == [64.0 * (i + 1) for i in range(5)]
    metrics.reset()


class _BatchStub:
    """Working micro-batch stub (the promoted plan)."""

    def process(self, raw):
        return _stub_result(raw)

    def process_batch(self, raws):
        vals = raws.astype(np.float32).sum(axis=1)
        det = _StubDetect(
            signal_counts=np.zeros((len(raws), 1, 4), np.int64),
            zero_count=np.zeros(len(raws), np.int64),
            time_series=vals.reshape(-1, 1).astype(np.float32))
        return [None] * len(raws), det


class _BatchOOMFirstStub(_BatchStub):
    """The initial plan: its FIRST batch dispatch OOMs."""

    def __init__(self):
        self.batches = 0

    def process_batch(self, raws):
        self.batches += 1
        if self.batches == 1:
            raise _FakeXla(_OOM_MSG)
        return super().process_batch(raws)


def test_promotion_restores_micro_batch_within_window(tmp_path):
    """Promotion restores the micro-batch rung mid-run: the engine's
    dispatch unit grows back to B, and the in-flight window bound
    must hold across the transition (the probe re-checks admission
    with the PROMOTED unit — regression for the probe dispatching a
    unit that overflows the window)."""
    from srtb_tpu.tools import telemetry_report as TR
    metrics.reset()
    window = 2
    cfg = _stub_cfg(tmp_path, "promo_mb", inflight_segments=window,
                    micro_batch_segments=2, promote_after_segments=1)
    sink = _CaptureSink()
    pipe = Pipeline(cfg, source=_CountingSource(8), sinks=[sink],
                    processor=_BatchOOMFirstStub())

    def factory(c, staged):
        mb = int(getattr(c, "micro_batch_segments", 1) or 1)
        return _BatchStub() if mb > 1 else _InstantProcessor()

    pipe.healer._factory = factory
    with pipe:
        stats = pipe.run()
    assert stats.segments == 8 and len(sink.out) == 8
    assert metrics.get("plan_demotions") == 1
    assert metrics.get("plan_promotions") >= 1
    assert pipe.healer.micro_batch == 2  # promoted plan batches again
    vals = [float(ts.ravel()[0]) for _, _, ts in sink.out]
    assert vals == [64.0 * (i + 1) for i in range(8)]
    # the window bound held through demotion AND promotion: no drain
    # ever observed more than `window` segments in flight
    recs = TR.load(str(tmp_path / "promo_mb.jsonl"))
    depths = [r["inflight_depth"] for r in recs
              if "inflight_depth" in r]
    assert depths and max(depths) <= window
    metrics.reset()


# ------------------------------------------------- chaos soak harness


def test_chaos_soak_gate_passes_on_seeded_plan(tmp_path):
    from srtb_tpu.tools import chaos_soak as CS
    report = CS.run_soak(seed=11, segments=3, faults=2, log2n=12,
                         tmpdir=str(tmp_path))
    assert report["ok"]
    assert report["drained"] + report["dropped"] == report["segments"]


def test_chaos_soak_plan_generator_is_seeded_and_capped():
    from srtb_tpu.tools import chaos_soak as CS
    a = CS.generate_plan(5, segments=8, faults=6, max_demotions=2,
                         max_halts=1)
    assert a == CS.generate_plan(5, segments=8, faults=6,
                                 max_demotions=2, max_halts=1)
    specs = parse_plan(a)
    assert sum(1 for s in specs
               if s.action in ("oom", "compile_fail")) <= 2
    assert sum(1 for s in specs if s.action == "device_halt") <= 1
    assert all(0 < s.index < 8 for s in specs)


@pytest.mark.slow
def test_chaos_soak_selftest_is_sharp():
    from srtb_tpu.tools import chaos_soak as CS
    assert CS.selftest(log2n=12) == []


# ------------------------------------------ plan-audit ladder targets


def test_audit_ladder_targets_are_carded():
    """Every demotion-ladder rung from the fully-featured audit config
    resolves to a checked-in plan card; an empty baseline makes the
    gate fire for every rung."""
    from srtb_tpu.analysis import hlo_audit as HA
    baseline = HA.CardBaseline.load(HA.DEFAULT_BASELINE)
    assert baseline.cards, "checked-in plan_cards.json missing"
    assert HA.audit_ladder(baseline) == []
    missing = HA.audit_ladder(HA.CardBaseline())
    assert missing and all("UNAUDITED" in m for m in missing)
