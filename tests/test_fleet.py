"""Multi-tenant stream fleet tests (pipeline/fleet.py +
resilience/admission.py + the cross-stream fairness policy).

The contract under test is the bulkhead: N streams on one device,
one faulty tenant, blast radius exactly itself —
- victim OOM demotes the victim's plan only; healthy streams' outputs
  stay bit-identical to their solo single-stream runs;
- a wedged victim sink sheds the victim's segments as accounted
  per-stream loss while healthy streams finish untouched;
- a victim manifest rollback (crash debris) is recovered in the
  victim's namespace only;
- a device HALT is the one shared domain: one budgeted fleet reinit,
  every stream completes with decisions intact;
- the shared plan cache compiles each plan family exactly once
  (second stream of a family compiles nothing);
- admission control rejects/queues over capacity in priority order,
  and the fleet shed policy sheds lowest-priority real-time streams
  first with hysteresis;
- per-stream observability: ``stream``-labeled metrics, v7 journal
  attribution, per-stream /healthz staleness, mixed v5/v6/v7 reports.
"""

import json
import os
import time

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.fleet import (SharedPlanCache, StreamFleet,
                                     StreamSpec)
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.pipeline.work import SegmentWork
from srtb_tpu.resilience.admission import (ADMIT, QUEUE, REJECT,
                                           AdmissionController)
from srtb_tpu.resilience.degrade import FleetShedPolicy
from srtb_tpu.resilience.faults import FaultInjector, parse_plan
from srtb_tpu.utils import telemetry
from srtb_tpu.utils.metrics import metrics

N = 1 << 13
SEGMENTS = 4


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


def _mkcfg(tmp, tag, infile, **kw):
    base = dict(
        baseband_input_count=N, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=0.05,
        input_file_path=infile,
        baseband_output_file_prefix=os.path.join(str(tmp), tag + "_"),
        spectrum_channel_count=64,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=True,
        writer_thread_count=0, fft_strategy="four_step",
        inflight_segments=2, retry_backoff_base_s=0.001)
    base.update(kw)
    return Config(**base)


def _make_bb(tmp, tag, seed):
    path = os.path.join(str(tmp), f"bb_{tag}.bin")
    make_dispersed_baseband(
        N * SEGMENTS, 1405.0, 64.0, 0.05,
        pulse_positions=[N // 2 + j * N for j in range(SEGMENTS)],
        pulse_amp=30.0, nbits=8, seed=seed).tofile(path)
    return path


class _Cap:
    """Decision-capturing sink."""

    def __init__(self):
        self.out = []

    def push(self, work, positive):
        det = work.detect
        self.out.append((np.asarray(det.signal_counts).copy(),
                         np.asarray(det.zero_count).copy(),
                         np.asarray(det.time_series).copy(),
                         bool(positive)))


def _solo(cfg):
    cap = _Cap()
    with Pipeline(cfg, sinks=[cap]) as pipe:
        stats = pipe.run()
    return stats, cap.out


def _decisions_equal(a, b, ts_exact=True):
    for i, (x, y) in enumerate(zip(a, b)):
        assert np.array_equal(x[0], y[0]), f"signal_counts @ {i}"
        assert np.array_equal(x[1], y[1]), f"zero_count @ {i}"
        if ts_exact:
            assert np.array_equal(x[2], y[2]), f"time_series @ {i}"
        assert x[3] == y[3], f"positive @ {i}"
    assert len(a) == len(b)


# ------------------------------------------------- fault stream scope


def test_fault_plan_stream_selector_parses():
    specs = parse_plan("stream0:dispatch:oom@3,ingest:raise@1,"
                       "beam2:fetch:stall=0.5@2")
    assert specs[0].stream == "stream0" and specs[0].site == "dispatch"
    assert specs[1].stream is None
    assert specs[2].stream == "beam2" and specs[2].arg == 0.5
    assert str(specs[0]) == "stream0:dispatch:oom@3"


def test_fault_injector_scopes_by_stream():
    plan = "stream0:dispatch:oom@3,ingest:raise@1"
    fi = FaultInjector.from_plan(plan, stream="stream1")
    assert not fi.armed("dispatch") and fi.armed("ingest")
    fi = FaultInjector.from_plan(plan, stream="stream0")
    assert fi.armed("dispatch") and fi.armed("ingest")
    # unnamed (solo) pipeline: selector entries never arm; a plan
    # that is ALL selectors degrades to None (zero-cost off)
    assert FaultInjector.from_plan("s0:dispatch:oom@1", stream="") \
        is None


def test_fault_plan_without_selector_unchanged():
    # legacy plans parse exactly as before (satellite contract)
    specs = parse_plan("ingest:raise@1,fetch:stall=0.5@2")
    assert all(s.stream is None for s in specs)
    fi = FaultInjector.from_plan("ingest:raise@1", stream="anything")
    assert fi.armed("ingest")


# -------------------------------------------------- admission control


def test_admission_capacity_queue_reject_priority():
    adm = AdmissionController(max_streams=2, queue_limit=1)
    assert adm.request("a", 0) == ADMIT
    assert adm.request("b", 0) == ADMIT
    assert adm.request("c", 1) == QUEUE
    # queue full: lower-priority newcomer rejected outright
    assert adm.request("d", 0) == REJECT
    assert adm.rejected == ["d"]
    # higher-priority newcomer evicts the queued lower one
    assert adm.request("e", 5) == QUEUE
    assert adm.rejected == ["d", "c"]
    assert adm.queued == ["e"]
    # release frees a slot: highest-priority queued stream pops
    adm.release("a")
    assert adm.pop_ready() == "e"
    assert adm.pop_ready() is None
    assert metrics.get("fleet_rejected") == 2
    assert metrics.get("fleet_admitted", labels={"stream": "e"}) == 1


def test_admission_unlimited_by_default():
    adm = AdmissionController(max_streams=0)
    assert all(adm.request(f"s{i}", 0) == ADMIT for i in range(10))


# ------------------------------------------------ fleet shed ordering


def test_fleet_shed_priority_order_and_hysteresis():
    pol = FleetShedPolicy(high=0.9, low=0.25, hold=2)
    lanes = [("hi", 5, True), ("mid", 3, True), ("lo", 1, True),
             ("file", 0, False)]
    assert pol.observe(1.0, False, lanes) == set()      # hold=2
    assert pol.observe(1.0, False, lanes) == {"lo"}     # lowest prio
    assert pol.observe(1.0, False, lanes) == set() or True
    pol.observe(1.0, False, lanes)
    # next shed takes the next-lowest REAL-TIME stream ("file" is
    # file-mode and never shed)
    assert "mid" in pol.shed and "file" not in pol.shed
    # relief restores highest priority first
    pol.observe(0.0, False, lanes)
    assert pol.observe(0.0, False, lanes) <= {"lo"}
    assert "mid" not in pol.shed
    assert metrics.get("fleet_sheds", labels={"stream": "lo"}) == 1


# ------------------------------------------- backpressure attribution


def test_drop_oldest_attributes_stream():
    import threading

    class SlowSource:
        pool = None

        def __iter__(self):
            for i in range(6):
                yield SegmentWork(data=np.zeros(4, np.uint8),
                                  data_stream_id=i % 2, seq=i)

    from srtb_tpu.io.backpressure import DropOldestSegmentBuffer
    buf = DropOldestSegmentBuffer(SlowSource(), capacity=1,
                                  name="t_attr")
    # let the pump overrun the capacity before consuming
    deadline = time.time() + 5
    while buf.dropped < 2 and time.time() < deadline:
        time.sleep(0.01)
    list(buf)
    buf.close()
    assert buf.dropped >= 2
    assert sum(buf.dropped_by_stream.values()) == buf.dropped
    per = metrics.by_label("segments_dropped")
    assert sum(per.values()) == buf.dropped
    assert set(per) <= {"0", "1"}
    # a named buffer attributes to its stream label instead
    metrics.reset()
    buf = DropOldestSegmentBuffer(SlowSource(), capacity=1,
                                  name="t_attr2", stream="beamX")
    deadline = time.time() + 5
    while buf.dropped < 1 and time.time() < deadline:
        time.sleep(0.01)
    list(buf)
    buf.close()
    assert set(buf.dropped_by_stream) == {"beamX"}
    assert metrics.get("segments_dropped",
                       labels={"stream": "beamX"}) == buf.dropped


# --------------------------------------------------- shared plan cache


def test_shared_plan_cache_key_ignores_tenancy(tmp_path):
    bb = _make_bb(tmp_path, "k", 0)
    from srtb_tpu.pipeline.segment import SegmentProcessor
    a = _mkcfg(tmp_path, "a", bb, stream_name="a", stream_priority=1,
               checkpoint_path=os.path.join(str(tmp_path), "a.ck"))
    b = _mkcfg(tmp_path, "b", bb, stream_name="b")
    assert SegmentProcessor.plan_cache_key(a) == \
        SegmentProcessor.plan_cache_key(b)
    c = _mkcfg(tmp_path, "c", bb, spectrum_channel_count=128)
    assert SegmentProcessor.plan_cache_key(a) != \
        SegmentProcessor.plan_cache_key(c)


def test_shared_plan_cache_compiles_once(tmp_path):
    bb = _make_bb(tmp_path, "p", 0)
    cache = SharedPlanCache()
    p1 = cache.get(_mkcfg(tmp_path, "a", bb))
    p2 = cache.get(_mkcfg(tmp_path, "b", bb))
    assert p1 is p2 and cache.compiles == 1 and cache.hits == 1
    assert p1._fleet_shared
    # retire() without force is a no-op on a shared plan
    p1.retire()
    assert p1._jit_process is not None and callable(p1._jit_process)
    # a different family compiles separately
    p3 = cache.get(_mkcfg(tmp_path, "c", bb,
                          spectrum_channel_count=128))
    assert p3 is not p1 and cache.compiles == 2
    cache.invalidate()
    with pytest.raises(RuntimeError, match="retired"):
        p1._jit_process(None)


# ------------------------------------------------------ fleet e2e


def test_fleet_matches_solo_and_shares_plan(tmp_path):
    bbs = {t: _make_bb(tmp_path, t, i)
           for i, t in enumerate(("s0", "s1"))}
    solo = {}
    for t, bb in bbs.items():
        metrics.reset()
        solo[t] = _solo(_mkcfg(tmp_path, t + "solo", bb))
    metrics.reset()
    caps = {t: _Cap() for t in bbs}
    fleet = StreamFleet([
        StreamSpec(name=t, cfg=_mkcfg(tmp_path, t, bb),
                   sinks=[caps[t]])
        for t, bb in bbs.items()])
    res = fleet.run()
    fleet.close()
    assert fleet.plans.compiles == 1 and fleet.plans.hits == 1
    for t in bbs:
        assert res[t].status == "done" and res[t].dropped == 0
        assert res[t].drained == solo[t][0].segments
        _decisions_equal(caps[t].out, solo[t][1])
    # per-stream labeled series materialized
    assert metrics.by_label("segments") == {
        t: float(solo[t][0].segments) for t in bbs}


def test_fleet_victim_oom_isolated(tmp_path):
    bbs = {t: _make_bb(tmp_path, t, i)
           for i, t in enumerate(("s0", "s1", "s2"))}
    solo = {}
    for t, bb in bbs.items():
        metrics.reset()
        solo[t] = _solo(_mkcfg(tmp_path, t + "solo", bb))
    plan = "s1:dispatch:oom@1"
    metrics.reset()
    caps = {t: _Cap() for t in bbs}
    jp = {t: os.path.join(str(tmp_path), f"j_{t}.jsonl") for t in bbs}
    fleet = StreamFleet([
        StreamSpec(name=t,
                   cfg=_mkcfg(tmp_path, t, bb, fault_plan=plan,
                              telemetry_journal_path=jp[t]),
                   sinks=[caps[t]])
        for t, bb in bbs.items()])
    res = fleet.run()
    fleet.close()
    assert all(r.status == "done" for r in res.values())
    # victim demoted; demotion attributed to the victim only
    assert metrics.by_label("plan_demotions") == {"s1": 1.0}
    assert res["s1"].extras["plan"] != res["s0"].extras["plan"]
    # healthy streams bit-identical (time series included)
    for t in ("s0", "s2"):
        _decisions_equal(caps[t].out, solo[t][1])
    # victim: decisions exact (time series may carry the demoted
    # plan's documented tolerance)
    _decisions_equal(caps["s1"].out, solo["s1"][1], ts_exact=False)
    # v7 journals: stream-stamped; per-stream attribution fields
    for t in bbs:
        recs = [json.loads(line) for line in open(jp[t])]
        assert all(r["v"] == 13 and r["stream"] == t for r in recs)
        want = 1 if t == "s1" else 0
        assert recs[-1]["plan_demotions"] == want, t


def test_fleet_device_halt_shared_reinit(tmp_path):
    bbs = {t: _make_bb(tmp_path, t, i)
           for i, t in enumerate(("s0", "s1"))}
    solo = {}
    for t, bb in bbs.items():
        metrics.reset()
        solo[t] = _solo(_mkcfg(tmp_path, t + "solo", bb))
    metrics.reset()
    caps = {t: _Cap() for t in bbs}
    fleet = StreamFleet([
        StreamSpec(name=t,
                   cfg=_mkcfg(tmp_path, t, bb,
                              fault_plan="s1:dispatch:device_halt@2",
                              device_reinit_max=1),
                   sinks=[caps[t]])
        for t, bb in bbs.items()])
    res = fleet.run()
    fleet.close()
    assert all(r.status == "done" for r in res.values())
    # ONE shared reinit, attributed to the faulting stream
    assert metrics.get("device_reinits") == 1
    assert metrics.by_label("device_reinits") == {"s1": 1.0}
    for t in bbs:
        assert res[t].drained == solo[t][0].segments
        _decisions_equal(caps[t].out, solo[t][1], ts_exact=False)


def test_fleet_sink_wedge_sheds_victim_only(tmp_path):
    bb = _make_bb(tmp_path, "h", 1)
    metrics.reset()
    solo_stats, solo_out = _solo(_mkcfg(tmp_path, "hsolo", bb))

    class WedgeSink:
        def __init__(self):
            self.n = 0

        def push(self, work, positive):
            self.n += 1
            if self.n == 2:
                time.sleep(60)

    class SynthSource:
        """Real-time-ish source (no input file): hand-built
        segments, stream-adjacent seq stamps."""

        def __init__(self, data, n_seg):
            self.segs = [SegmentWork(data=data[i * N:(i + 1) * N],
                                     timestamp=i, seq=i)
                         for i in range(n_seg)]

        def __iter__(self):
            return iter(self.segs)

    raw = np.fromfile(bb, dtype=np.uint8)
    metrics.reset()
    hcap = _Cap()
    fleet = StreamFleet([
        StreamSpec(name="victim",
                   cfg=_mkcfg(tmp_path, "victim", "",
                              segment_deadline_s=0.2,
                              baseband_reserve_sample=False,
                              shutdown_join_timeout_s=0.5),
                   source=SynthSource(raw, SEGMENTS),
                   sinks=[WedgeSink()]),
        StreamSpec(name="h", cfg=_mkcfg(tmp_path, "h", bb),
                   sinks=[hcap]),
    ])
    t0 = time.time()
    res = fleet.run()
    elapsed = time.time() - t0
    assert elapsed < 30, f"fleet stalled behind the wedge ({elapsed})"
    # healthy stream untouched, bit-identical
    assert res["h"].status == "done" and res["h"].dropped == 0
    assert res["h"].drained == solo_stats.segments
    _decisions_equal(hcap.out, solo_out)
    # victim: accounted-only loss, attributed per stream
    v = res["victim"]
    assert v.dropped >= 1
    assert v.drained + v.dropped == SEGMENTS
    assert metrics.get("segments_dropped",
                       labels={"stream": "victim"}) == v.dropped
    assert metrics.get("segments_dropped",
                       labels={"stream": "h"}) == 0


def test_fleet_victim_manifest_rollback_isolated(tmp_path):
    from srtb_tpu.io.manifest import RunManifest
    bbs = {t: _make_bb(tmp_path, t, i)
           for i, t in enumerate(("v", "h"))}
    man = {t: os.path.join(str(tmp_path), f"man_{t}.jsonl")
           for t in bbs}

    def cfgs(tag_suffix=""):
        return {t: _mkcfg(tmp_path, t + tag_suffix, bb,
                          run_manifest_path=man[t])
                for t, bb in bbs.items()}

    # seed the victim's manifest namespace with crash debris: an
    # uncommitted intent + its orphaned artifact
    debris = os.path.join(str(tmp_path), "v_debris.npy")
    m = RunManifest.open(man["v"], fsync=False)
    m.intent((0, 0, "0:WriteSignalSink"), debris)
    m.sync()
    m.close()
    with open(debris, "wb") as f:
        f.write(b"orphan")
    metrics.reset()
    caps = {t: _Cap() for t in bbs}
    fleet = StreamFleet([
        StreamSpec(name=t, cfg=cfg, sinks=[caps[t]])
        for t, cfg in cfgs().items()])
    res = fleet.run()
    fleet.close()
    assert all(r.status == "done" for r in res.values())
    # the victim's debris was rolled back in ITS namespace only
    assert metrics.get("rolled_back_intents") == 1
    assert not os.path.exists(debris)
    assert os.path.exists(man["h"])


def test_fleet_admission_reject_and_queue(tmp_path):
    bb = _make_bb(tmp_path, "adm", 0)
    caps = {t: _Cap() for t in ("a", "b", "c")}

    def spec(t, prio):
        return StreamSpec(
            name=t,
            cfg=_mkcfg(tmp_path, t, bb, stream_priority=prio,
                       fleet_max_streams=1, fleet_queue_limit=1),
            sinks=[caps[t]])

    fleet = StreamFleet([spec("a", 0), spec("b", 5), spec("c", 9)])
    res = fleet.run()
    fleet.close()
    # capacity 1: a admitted; b queued then evicted by c (priority)
    assert res["a"].status == "done"
    assert res["c"].status == "done"
    assert res["b"].status == "rejected"
    assert not caps["b"].out
    # queued stream ran only after a slot freed; still one plan family
    assert fleet.plans.compiles == 1


def test_fleet_start_failure_frees_queued_slot(tmp_path):
    """A lane whose constructor fails must hand its capacity slot to
    the queued stream — a start failure with a populated waitlist
    used to leave run() spinning forever with no active lanes."""
    bb = _make_bb(tmp_path, "sf", 0)
    cap = _Cap()
    fleet = StreamFleet([
        # sanitize=True fails at lane start (fleet guardrail)
        StreamSpec(name="broken",
                   cfg=_mkcfg(tmp_path, "broken", bb, sanitize=True,
                              fleet_max_streams=1,
                              fleet_queue_limit=1)),
        StreamSpec(name="queued",
                   cfg=_mkcfg(tmp_path, "queued", bb),
                   sinks=[cap]),
    ])
    t0 = time.time()
    res = fleet.run()
    fleet.close()
    assert time.time() - t0 < 60
    assert res["broken"].status == "failed"
    assert res["queued"].status == "done" and cap.out


def test_fleet_healthz_per_stream(tmp_path):
    telemetry.register_stream("lane_a")
    telemetry.register_stream("lane_b")
    try:
        # startup: admitted streams with NO segment yet are healthy
        # (a lane inside its first cold compile must not 503 a
        # liveness probe), same contract as the solo engine's idle
        h = telemetry.health(stale_after_s=0.001)
        assert h["ok"]
        assert h["streams"]["lane_a"] == {"last_segment_age_s": None,
                                          "ok": True}
        telemetry.mark_segment("lane_a")
        telemetry.mark_segment("lane_b")
        h = telemetry.health(stale_after_s=30.0)
        assert h["ok"] and set(h["streams"]) == {"lane_a", "lane_b"}
        # age one stream past the deadline -> unhealthy with the
        # stale stream named, even though the OTHER stream (and the
        # global stamp) is fresh
        metrics.set(telemetry.LAST_SEGMENT_MONOTONIC,
                    time.monotonic() - 100,
                    labels={"stream": "lane_b"})
        telemetry.mark_segment("lane_a")
        h = telemetry.health(stale_after_s=30.0)
        assert not h["ok"] and h["stale_streams"] == ["lane_b"]
        assert h["streams"]["lane_a"]["ok"]
        # released streams stop counting
        telemetry.release_stream("lane_b")
        assert telemetry.health(stale_after_s=30.0)["ok"]
    finally:
        telemetry.release_stream("lane_a")
        telemetry.release_stream("lane_b")


def test_fleet_prometheus_labels(tmp_path):
    bb = _make_bb(tmp_path, "prom", 0)
    fleet = StreamFleet([
        StreamSpec(name="beam0", cfg=_mkcfg(tmp_path, "beam0", bb),
                   sinks=[_Cap()])])
    fleet.run()
    fleet.close()
    prom = metrics.prometheus()
    assert 'srtb_inflight_depth{stream="beam0"}' in prom
    assert 'srtb_segments{stream="beam0"}' in prom


# ------------------------------------------------- v7 schema + report


def test_span_schema_v7_stream_field():
    from srtb_tpu.utils.telemetry import (SPAN_SCHEMA_VERSION,
                                          segment_span)
    assert SPAN_SCHEMA_VERSION == 13
    rec = segment_span(0, {"ingest": 0.01}, 1, 0, False, 4)
    assert rec["v"] == 13 and "stream" not in rec
    metrics.set("plan_demotions", 7)  # global; must NOT leak into a
    metrics.add("plan_demotions", 2, labels={"stream": "x"})
    rec = segment_span(0, {"ingest": 0.01}, 1, 0, False, 4,
                       stream="x")
    assert rec["stream"] == "x"
    # named spans carry the stream's OWN attribution counters
    assert rec["plan_demotions"] == 2


def test_report_mixed_v5_v6(tmp_path):
    from srtb_tpu.tools import telemetry_report as TR
    path = os.path.join(str(tmp_path), "mixed.jsonl")
    v5 = {"type": "segment_span", "v": 5, "ts": 1.0, "segment": 0,
          "stages_ms": {"ingest": 1.0}, "queue_depth": 1,
          "detections": 2, "dump": True, "samples": 100,
          "segments_dropped": 0, "degrade_level": 0,
          "plan_demotions": 0}
    v6a = dict(v5, v=6, ts=2.0, segment=1, stream="s0",
               plan_demotions=1, segments_dropped=2)
    v6b = dict(v5, v=6, ts=3.0, segment=1, stream="s1")
    with open(path, "w") as f:
        for r in (v5, v6a, v6b):
            f.write(json.dumps(r) + "\n")
    rep = TR.report(path)
    assert rep["records"] == 3
    fl = rep["fleet"]
    # v5 record (no stream) drops out of the fleet section
    assert set(fl) == {"s0", "s1"}
    assert fl["s0"]["plan_demotions"] == 1
    assert fl["s0"]["segments_dropped"] == 2
    assert fl["s1"]["plan_demotions"] == 0
    md = TR._md(rep)
    assert "Fleet (per-stream)" in md and "| s0 |" in md
    # a journal with no v6 spans has no fleet section
    solo_path = os.path.join(str(tmp_path), "solo.jsonl")
    with open(solo_path, "w") as f:
        f.write(json.dumps(v5) + "\n")
    rep = TR.report(solo_path)
    assert rep["fleet"] == {}
    assert "Fleet" not in TR._md(rep)


# --------------------------------------------------------- guardrails


def test_fleet_rejects_sanitize_and_micro_batch(tmp_path):
    bb = _make_bb(tmp_path, "g", 0)
    fleet = StreamFleet([
        StreamSpec(name="s", cfg=_mkcfg(tmp_path, "s", bb,
                                        sanitize=True),
                   sinks=[_Cap()])])
    res = fleet.run()
    assert res["s"].status == "failed"
    assert isinstance(res["s"].error, ValueError)
    # REAL-TIME lanes (no input file) still reject micro-batch
    # loudly: batching a live stream trades bounded latency for
    # throughput silently
    rt_cfg = _mkcfg(tmp_path, "s", bb, micro_batch_segments=2,
                    inflight_segments=2).replace(input_file_path="")
    fleet = StreamFleet([
        StreamSpec(name="s", cfg=rt_cfg, source=iter(()),
                   sinks=[_Cap()])])
    res = fleet.run()
    assert res["s"].status == "failed"
    assert isinstance(res["s"].error, ValueError)
    assert "file-mode" in str(res["s"].error)
    # FILE-mode lanes accept it (the archive-replay shape): B
    # segments per vmapped dispatch inside the fleet
    cap = _Cap()
    fleet = StreamFleet([
        StreamSpec(name="s", cfg=_mkcfg(tmp_path, "smb", bb,
                                        micro_batch_segments=2,
                                        inflight_segments=4),
                   sinks=[cap])])
    res = fleet.run()
    assert res["s"].status == "done"
    assert res["s"].drained == len(cap.out) > 0
    # a batch bigger than the lane window still rejects
    fleet = StreamFleet([
        StreamSpec(name="s", cfg=_mkcfg(tmp_path, "sbig", bb,
                                        micro_batch_segments=4,
                                        inflight_segments=2),
                   sinks=[_Cap()])])
    res = fleet.run()
    assert res["s"].status == "failed"
    assert "exceeds" in str(res["s"].error)


def test_fleet_duplicate_names_rejected(tmp_path):
    bb = _make_bb(tmp_path, "d", 0)
    with pytest.raises(ValueError, match="duplicate"):
        StreamFleet([
            StreamSpec(name="s", cfg=_mkcfg(tmp_path, "s1", bb)),
            StreamSpec(name="s", cfg=_mkcfg(tmp_path, "s2", bb))])


def test_fleet_lane_failure_contained(tmp_path):
    """A FATAL fault in one lane fails that lane only; neighbors
    finish and the failed lane's loss is accounted per stream."""
    bbs = {t: _make_bb(tmp_path, t, i)
           for i, t in enumerate(("bad", "good"))}
    metrics.reset()
    solo_stats, solo_out = _solo(_mkcfg(tmp_path, "gsolo",
                                        bbs["good"]))
    metrics.reset()
    gcap = _Cap()
    fleet = StreamFleet([
        StreamSpec(name="bad",
                   cfg=_mkcfg(tmp_path, "bad", bbs["bad"],
                              fault_plan="bad:dispatch:fatal@1"),
                   sinks=[_Cap()]),
        StreamSpec(name="good",
                   cfg=_mkcfg(tmp_path, "good", bbs["good"]),
                   sinks=[gcap]),
    ])
    res = fleet.run()
    fleet.close()
    assert res["bad"].status == "failed"
    assert res["good"].status == "done"
    _decisions_equal(gcap.out, solo_out)
    # nothing vanished from the failed lane's books: everything it
    # dispatched but never drained is accounted loss
    bad = res["bad"]
    assert bad.drained + bad.dropped == bad.stats.segments


# ------------------------------------- elastic pool: drain + migration


def test_fleet_pool_scoped_halt_drains_victim_only(tmp_path):
    """Satellite 1: with >= 2 pool members, a device HALT is no longer
    the shared domain — the faulted member is drained (its plan cache
    alone force-retired, its lanes live-migrated onto the survivor)
    and the budgeted fleet-wide reinit is NOT spent.  The migrant
    rejoins the survivor's plan family at rung 0: pool-wide compiles
    stay at one per device and every stream stays bit-identical."""
    bbs = {t: _make_bb(tmp_path, t, i)
           for i, t in enumerate(("s0", "s1"))}
    solo = {}
    for t, bb in bbs.items():
        metrics.reset()
        solo[t] = _solo(_mkcfg(tmp_path, t + "solo", bb))
    metrics.reset()
    caps = {t: _Cap() for t in bbs}
    fleet = StreamFleet([
        StreamSpec(name=t,
                   cfg=_mkcfg(tmp_path, t, bb,
                              fleet_devices=2,
                              fault_plan="s1:dispatch:device_halt@2",
                              device_reinit_max=1),
                   sinks=[caps[t]])
        for t, bb in bbs.items()])
    assert len(fleet.pool) == 2
    res = fleet.run()
    pool_compiles = fleet.pool.compiles
    halted = fleet.pool.devices[1].state
    fleet.close()
    assert all(r.status == "done" for r in res.values())
    # the reinit budget was available and must NOT have been spent
    assert metrics.get("device_reinits") == 0
    assert metrics.get("device_drains") == 1
    assert metrics.by_label("migrations") == {"s1": 1.0}
    from srtb_tpu.pipeline.pool import STATE_HALTED
    assert halted == STATE_HALTED  # a member halts at most once
    # deterministic placement: s0 -> dev0, s1 -> dev1; the victim
    # drained onto the survivor
    assert res["s0"].extras["device"] == "dev0"
    assert res["s1"].extras["device"] == "dev0"
    assert res["s1"].extras["migrations"] == 1
    # one compile per device, zero recompiles for the migration (the
    # migrant adopted the survivor's family at rung 0)
    assert pool_compiles == 2
    assert metrics.get("plan_demotions") == 0
    for t in bbs:
        assert res[t].dropped == 0
        assert res[t].drained == solo[t][0].segments
        _decisions_equal(caps[t].out, solo[t][1])


def test_batch_former_membership_revalidated(tmp_path):
    """Satellite 2: a migrated/healed lane can never batch into its
    FORMER device's family — eligibility keys on the lane's CURRENT
    processor identity and its member's health state."""
    from types import SimpleNamespace

    from srtb_tpu.pipeline.fleet import _BatchFormer
    from srtb_tpu.pipeline.pool import (STATE_DRAINING, STATE_OK,
                                        DevicePool)

    pool = DevicePool(2)

    class _Proc:
        _fleet_shared = True
        staged = False

    class _Lane:
        def __init__(self, proc, dev):
            self.pipe = SimpleNamespace(processor=proc)
            self.device = dev

        def _unit(self):
            return 1

    former = _BatchFormer(SimpleNamespace(_tsan=None),
                          batch_max=3, linger_s=1.0)
    proc_a, proc_b = _Proc(), _Proc()
    lane0 = _Lane(proc_a, pool.devices[0])
    lane1 = _Lane(proc_a, pool.devices[0])
    lane2 = _Lane(proc_b, pool.devices[1])
    assert all(former.eligible(ln) for ln in (lane0, lane1, lane2))
    # a draining/halted member's lanes stop offering immediately
    pool.devices[0].set_state(STATE_DRAINING)
    assert not former.eligible(lane0) and not former.eligible(lane1)
    assert former.eligible(lane2)
    pool.devices[0].set_state(STATE_OK)
    # groups key on processor identity: per-device families can never
    # merge, and a migration (which swaps in the TARGET cache's
    # processor) moves the lane to the target's group by construction
    former.offer(lane0, (object(), 0.0, 0), 0)
    former.offer(lane2, (object(), 0.0, 0), 0)
    assert len(former._groups) == 2
    assert {id(proc_a), id(proc_b)} == set(former._groups)
    # after a simulated migration lane1 carries dev1's processor: its
    # next offer joins dev1's family, not dev0's
    lane1.pipe.processor = proc_b
    lane1.device = pool.devices[1]
    former.offer(lane1, (object(), 0.0, 1), 1)
    assert len(former._groups[id(proc_b)][1]) == 2
    assert len(former._groups[id(proc_a)][1]) == 1


def test_fleet_stream_killed_on_a_resumes_on_b(tmp_path):
    """Satellite 3: a stream killed mid-segment on device A resumes
    on device B (pin_device) — final output set bit-identical to an
    uninterrupted solo run, manifest fsck-clean."""
    from srtb_tpu.tools.crash_soak import snapshot_outputs
    from srtb_tpu.tools.fsck import fsck

    bb = _make_bb(tmp_path, "mig", 5)

    def _dcfg(tag, run_dir, **kw):
        # default sinks (the artifact writers), deterministic names,
        # detection relaxed so segments actually commit artifacts
        run_dir.mkdir(exist_ok=True)
        return _mkcfg(
            tmp_path, tag, bb,
            baseband_output_file_prefix=str(run_dir / "out_"),
            checkpoint_path=str(run_dir / "ck.json"),
            run_manifest_path=str(run_dir / "manifest.jsonl"),
            deterministic_timestamps=True,
            mitigate_rfi_average_method_threshold=1000.0,
            mitigate_rfi_spectral_kurtosis_threshold=50.0,
            signal_detect_signal_noise_threshold=2.0,
            signal_detect_max_boxcar_length=8,
            inflight_segments=1, **kw)

    golden_dir = tmp_path / "golden_run"
    metrics.reset()
    with Pipeline(_dcfg("golden", golden_dir)) as pipe:
        pipe.run()
    golden = snapshot_outputs(str(golden_dir))
    assert golden  # the equality gate below must gate something

    # phase 1: the stream dies on dev0 after a segment committed but
    # before its checkpoint landed (THE duplicate window)
    run_dir = tmp_path / "mig_run"
    metrics.reset()
    fleet = StreamFleet([StreamSpec(
        name="mig",
        cfg=_dcfg("p1", run_dir, fleet_devices=2,
                  fault_plan="checkpoint:fatal@1"),
        pin_device=0)])
    res1 = fleet.run()
    fleet.close()
    assert res1["mig"].status == "failed"
    assert res1["mig"].extras["device"] == "dev0"

    # phase 2: resume the SAME run pinned to dev1
    metrics.reset()
    fleet = StreamFleet([StreamSpec(
        name="mig", cfg=_dcfg("p2", run_dir, fleet_devices=2),
        pin_device=1)])
    res2 = fleet.run()
    fleet.close()
    assert res2["mig"].status == "done"
    assert res2["mig"].extras["device"] == "dev1"
    assert res2["mig"].dropped == 0
    # exactly-once across the device move: the union of both phases'
    # outputs equals the uninterrupted golden, byte for byte
    assert snapshot_outputs(str(run_dir)) == golden
    rep = fsck(str(run_dir / "manifest.jsonl"),
               str(run_dir / "ck.json"))
    assert rep["clean"], rep


def test_fleet_rebalance_on_slo_burn(tmp_path, monkeypatch):
    """Driver (b): a burning stream on the loaded member migrates to
    the strictly less-loaded peer (migrate_on_burn), exactly once
    (cooldown), with decisions bit-identical to solo."""
    from srtb_tpu.utils import slo

    class _Burning:
        def evaluate(self):
            # s2 sits on dev0 (load 2) next to s0; dev1 holds s1 only
            return {"s2": {"ok": False}}

        def note_segment(self, *a, **k):
            pass

        note_dropped = note_canary = note_segment

    monkeypatch.setattr(slo, "tracker", _Burning())
    bbs = {t: _make_bb(tmp_path, t, i)
           for i, t in enumerate(("s0", "s1", "s2"))}
    metrics.reset()
    solo = _solo(_mkcfg(tmp_path, "s2solo", bbs["s2"]))
    metrics.reset()
    caps = {t: _Cap() for t in bbs}
    fleet = StreamFleet([
        StreamSpec(name=t,
                   cfg=_mkcfg(tmp_path, t, bb, fleet_devices=2,
                              migrate_on_burn=True),
                   sinks=[caps[t]])
        for t, bb in bbs.items()])
    res = fleet.run()
    fleet.close()
    assert all(r.status == "done" for r in res.values())
    assert res["s2"].extras["device"] == "dev1"
    assert res["s2"].extras["migrations"] == 1
    assert metrics.by_label("migrations") == {"s2": 1.0}
    # the rebalance is a drain-migrate, not a fault: nothing reinits,
    # nothing demotes, nothing drops
    assert metrics.get("device_reinits") == 0
    assert metrics.get("plan_demotions") == 0
    assert res["s2"].dropped == 0
    _decisions_equal(caps["s2"].out, solo[1])


def test_fleet_rolling_restart_drains_one_at_a_time(tmp_path):
    """Driver (c): an operator rolling restart drains every member
    exactly once, lanes live-migrate onto peers and every stream
    finishes bit-identical with zero loss."""
    bbs = {t: _make_bb(tmp_path, t, i)
           for i, t in enumerate(("s0", "s1"))}
    solo = {}
    for t, bb in bbs.items():
        metrics.reset()
        solo[t] = _solo(_mkcfg(tmp_path, t + "solo", bb))
    metrics.reset()
    caps = {t: _Cap() for t in bbs}
    fleet = StreamFleet([
        StreamSpec(name=t,
                   cfg=_mkcfg(tmp_path, t, bb, fleet_devices=2),
                   sinks=[caps[t]])
        for t, bb in bbs.items()])
    fleet.rolling_restart()
    res = fleet.run()
    pool_states = [d.state for d in fleet.pool.devices]
    fleet.close()
    assert all(r.status == "done" for r in res.values())
    assert metrics.get("device_drains") == 2
    assert metrics.get("migrations") >= 2
    assert metrics.get("device_reinits") == 0
    from srtb_tpu.pipeline.pool import STATE_OK
    assert pool_states == [STATE_OK, STATE_OK]  # drained members re-arm
    for t in bbs:
        assert res[t].dropped == 0
        assert res[t].drained == solo[t][0].segments
        _decisions_equal(caps[t].out, solo[t][1])


# ----------------------------------------------------- fleet soak gate


@pytest.mark.slow
def test_fleet_soak_gate():
    from srtb_tpu.tools.fleet_soak import run_soak
    report = run_soak(streams=3, segments=4, log2n=12)
    assert report["ok"]
    assert report["plan_compiles"] == 1
    assert report["plan_cache_hits"] == 2


@pytest.mark.slow
def test_fleet_soak_selftest_sharp():
    from srtb_tpu.tools.fleet_soak import selftest
    assert selftest(log2n=11) == []


@pytest.mark.slow
def test_fleet_migrate_soak_gate():
    from srtb_tpu.tools.fleet_soak import run_migrate
    report = run_migrate(streams=3, segments=6, log2n=12)
    assert report["ok"]
    assert report["device_drains"] == 1
    assert report["migrations"] >= 1
