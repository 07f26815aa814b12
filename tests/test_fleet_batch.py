"""Cross-tenant continuous batching tests (pipeline/fleet._BatchFormer
+ the batch-aware admission/shed policies + the v10 telemetry fields).

The contract under test:
- grouping: only lanes sharing a plan family (the SAME SharedPlanCache
  processor) ever ride one batch; a foreign-family lane stays solo;
- linger deadline: a partial batch flushes once its oldest offer has
  waited ``fleet_batch_linger_ms`` — and a LONE tenant never waits at
  all (the idle scheduler flushes immediately);
- priority fill: when a flush holds more offers than one batch takes,
  high-priority streams ride the first dispatch;
- ragged tail: a leftover single offer goes through the lane's plain
  solo-dispatch path (never a B=1 vmap trace);
- bulkheads: a victim's demotion swaps in an unshared processor, which
  drops it out of the batch group — neighbors keep batching on the
  shared program;
- equality: batched fleet outputs match solo goldens — decisions
  exact, float time series within the documented vmap tolerance;
- no busy-wait: the event-driven scheduler wakeup keeps
  ``fleet_idle_waits`` bounded while a slow sink stalls the fleet.
"""

import json
import os
import time

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.fleet import (StreamFleet, StreamSpec,
                                     _BatchFormer)
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.resilience.admission import AdmissionController
from srtb_tpu.resilience.degrade import FleetShedPolicy
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


def _decisions_match(a, b, ts_exact=True):
    """Decisions exact; time series bitwise or vmap-allclose."""
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        assert np.array_equal(x[0], y[0]), f"signal_counts @ {i}"
        assert np.array_equal(x[1], y[1]), f"zero_count @ {i}"
        if ts_exact:
            assert np.array_equal(x[2], y[2]), f"time_series @ {i}"
        else:
            # the documented vmap tolerance (archive micro-batch
            # precedent): amplitude-relative atol for float32
            # reassociation in the batched plan
            np.testing.assert_allclose(
                x[2], y[2], rtol=1e-5,
                atol=1e-4 * max(float(np.abs(y[2]).max()), 1.0),
                err_msg=f"time_series beyond vmap tolerance @ {i}")
        assert x[3] == y[3], f"positive @ {i}"


def _journal(path):
    return [json.loads(line) for line in open(path)
            if line.strip().startswith("{")]


# ------------------------------------------------ end-to-end equality


def test_batched_fleet_matches_solo_within_vmap_tolerance(tmp_path):
    """3 same-family streams, fleet_batch_max=2: batched AND ragged-
    tail solo dispatches both occur; every stream's decisions match
    its solo golden (float series within the vmap tolerance), the
    plan compiles once, and the journal accounts every batch."""
    tags = ("s0", "s1", "s2")
    bbs = {t: _make_bb(tmp_path, t, i) for i, t in enumerate(tags)}
    solo = {}
    for t, bb in bbs.items():
        metrics.reset()
        solo[t] = _solo(_mkcfg(tmp_path, t + "solo", bb))
    metrics.reset()
    caps = {t: _Cap() for t in bbs}
    jp = {t: os.path.join(str(tmp_path), f"j_{t}.jsonl") for t in bbs}
    fleet = StreamFleet([
        StreamSpec(name=t,
                   cfg=_mkcfg(tmp_path, t, bb, fleet_batch_max=2,
                              telemetry_journal_path=jp[t]),
                   sinks=[caps[t]])
        for t, bb in bbs.items()])
    res = fleet.run()
    fleet.close()
    assert all(r.status == "done" and r.dropped == 0
               for r in res.values())
    assert fleet.plans.compiles == 1 and fleet.plans.hits == 2
    assert metrics.get("batched_dispatches") >= 1
    for t in tags:
        assert res[t].drained == solo[t][0].segments
        _decisions_match(caps[t].out, solo[t][1], ts_exact=False)
    # journal accounting: batched records carry batch_size (== 2 at
    # this batch_max), solo/ragged-tail records omit it entirely
    sizes = []
    for t in tags:
        for r in _journal(jp[t]):
            assert r["v"] == 13 and r["stream"] == t
            if "batch_size" in r:
                sizes.append(r["batch_size"])
                assert r["batch_size"] == 2
                assert r["batch_wait_ms"] >= 0.0
    assert len(sizes) == int(metrics.get("batched_segments"))
    assert len(sizes) == 2 * int(metrics.get("batched_dispatches"))


def test_grouping_by_plan_cache_key(tmp_path):
    """Two same-shape streams + one foreign-family stream (different
    channel count = different plan_cache_key): only the family pair
    ever batches; the loner drains through solo dispatches."""
    bbs = {t: _make_bb(tmp_path, t, i)
           for i, t in enumerate(("a0", "a1", "lone"))}
    metrics.reset()
    caps = {t: _Cap() for t in bbs}
    jp = {t: os.path.join(str(tmp_path), f"j_{t}.jsonl") for t in bbs}

    def cfg_for(t, bb):
        extra = {"spectrum_channel_count": 32} if t == "lone" else {}
        return _mkcfg(tmp_path, t, bb, fleet_batch_max=4,
                      telemetry_journal_path=jp[t], **extra)

    fleet = StreamFleet([
        StreamSpec(name=t, cfg=cfg_for(t, bb), sinks=[caps[t]])
        for t, bb in bbs.items()])
    res = fleet.run()
    fleet.close()
    assert all(r.status == "done" for r in res.values())
    assert metrics.get("batched_dispatches") >= 1
    # the loner's journal never carries batch_size; the family's does
    assert all("batch_size" not in r for r in _journal(jp["lone"]))
    by_stream = metrics.by_label("batched_segments")
    assert "lone" not in by_stream
    assert set(by_stream) <= {"a0", "a1"} and by_stream


def test_lone_tenant_never_waits_out_the_linger(tmp_path):
    """One stream, an hour-long linger, batch never fillable: the
    idle scheduler flushes the partial batch immediately — the run
    completes in seconds, unbatched."""
    bb = _make_bb(tmp_path, "solo1", 0)
    cap = _Cap()
    t0 = time.perf_counter()
    fleet = StreamFleet([StreamSpec(
        name="solo1",
        cfg=_mkcfg(tmp_path, "solo1", bb, fleet_batch_max=4,
                   fleet_batch_linger_ms=3_600_000.0),
        sinks=[cap])])
    res = fleet.run()
    fleet.close()
    elapsed = time.perf_counter() - t0
    assert res["solo1"].status == "done"
    assert res["solo1"].drained == len(cap.out) > 0
    assert elapsed < 60.0, "lone tenant waited on the linger deadline"
    assert metrics.get("batched_dispatches") == 0


# --------------------------------------------- former unit semantics


class _StubLane:
    """Just enough lane surface for _BatchFormer formation policy."""

    def __init__(self, name, priority, proc):
        self.name = name
        self.priority = priority
        self.pipe = type("P", (), {"processor": proc})()


def _former(batch_max, linger_s=0.0):
    f = _BatchFormer.__new__(_BatchFormer)
    _BatchFormer.__init__(f, fleet=None, batch_max=batch_max,
                          linger_s=linger_s)
    return f


def test_former_priority_fill_and_ragged_tail():
    """Flush order: priority desc, offer age asc; a leftover single
    offer routes to the solo-dispatch fallback, never a B=1 batch."""
    proc = object()
    former = _former(batch_max=4)
    shared_calls, solo_calls = [], []
    former._dispatch_shared = \
        lambda p, slots: shared_calls.append((p, list(slots)))
    former._single_fallback = \
        lambda slot, requeue=False: solo_calls.append(slot)
    lanes = [_StubLane("low", 0, proc), _StubLane("high", 9, proc),
             _StubLane("mid", 1, proc)]
    for i, lane in enumerate(lanes):
        former.offer(lane, (object(), 0.0, 0), i)
    assert not shared_calls  # 3 offers < batch_max: still forming
    assert former.flush_all()
    [(got_proc, slots)] = shared_calls
    assert got_proc is proc
    assert [s.lane.name for s in slots] == ["high", "mid", "low"]
    assert not solo_calls

    # 5th offer after an auto-flush at batch_max leaves a tail of one
    shared_calls.clear()
    for i, lane in enumerate(lanes + lanes[:2]):
        former.offer(lane, (object(), 0.0, 10 + i), 10 + i)
    assert len(shared_calls) == 1 and len(shared_calls[0][1]) == 4
    assert former.flush_all()
    assert len(solo_calls) == 1  # the ragged tail went solo


def test_former_linger_deadline_pump():
    """pump() flushes a partial family only once its oldest live
    offer has waited past the linger deadline."""
    former = _former(batch_max=4, linger_s=0.02)
    solo_calls = []
    former._single_fallback = \
        lambda slot, requeue=False: solo_calls.append(slot)
    former.offer(_StubLane("a", 0, object()), (object(), 0.0, 0), 0)
    assert not former.pump()          # deadline not reached
    assert not solo_calls
    time.sleep(0.03)
    assert former.pump()              # oldest offer now past linger
    assert len(solo_calls) == 1
    assert not former.pump()          # nothing left


def test_former_groups_by_processor_identity():
    """Offers from different processors never share a group (the
    plan_cache_key contract: one shared processor per family)."""
    pa, pb = object(), object()
    former = _former(batch_max=2)
    shared_calls = []
    former._dispatch_shared = \
        lambda p, slots: shared_calls.append((p, list(slots)))
    former._single_fallback = lambda slot, requeue=False: None
    former.offer(_StubLane("a0", 0, pa), (object(), 0.0, 0), 0)
    former.offer(_StubLane("b0", 0, pb), (object(), 0.0, 0), 0)
    assert not shared_calls  # one offer per family: nothing fillable
    former.offer(_StubLane("a1", 0, pa), (object(), 0.0, 0), 0)
    assert len(shared_calls) == 1  # family A filled at 2
    assert shared_calls[0][0] is pa
    assert {s.lane.name for s in shared_calls[0][1]} == {"a0", "a1"}


# ------------------------------------------------ bulkhead: demotion


def test_victim_demotion_exits_batch_group(tmp_path):
    """A victim OOM demotes the victim's plan (an UNSHARED processor
    swap): its later segments leave the batch group, neighbors keep
    batching, decisions stay exact, attribution stays per-stream."""
    tags = ("v", "h0", "h1")
    bbs = {t: _make_bb(tmp_path, t, i) for i, t in enumerate(tags)}
    solo = {}
    for t, bb in bbs.items():
        metrics.reset()
        solo[t] = _solo(_mkcfg(tmp_path, t + "solo", bb))
    plan = "v:dispatch:oom@1"
    metrics.reset()
    caps = {t: _Cap() for t in bbs}
    jp = {t: os.path.join(str(tmp_path), f"j_{t}.jsonl") for t in bbs}
    fleet = StreamFleet([
        StreamSpec(name=t,
                   cfg=_mkcfg(tmp_path, t, bb, fleet_batch_max=3,
                              fault_plan=plan,
                              telemetry_journal_path=jp[t]),
                   sinks=[caps[t]])
        for t, bb in bbs.items()])
    res = fleet.run()
    fleet.close()
    assert all(r.status == "done" for r in res.values())
    assert metrics.by_label("plan_demotions") == {"v": 1.0}
    assert res["v"].extras["plan"] != res["h0"].extras["plan"]
    for t in ("h0", "h1"):
        _decisions_match(caps[t].out, solo[t][1], ts_exact=False)
    _decisions_match(caps["v"].out, solo["v"][1], ts_exact=False)
    # the victim's demoted (unshared) processor never batches again:
    # no victim journal record AT or AFTER the fault index carries
    # batch_size
    for r in _journal(jp["v"]):
        if r["segment"] >= 1:
            assert "batch_size" not in r, \
                "demoted victim still riding the shared batch"
    # neighbors kept batching on the shared program
    by_stream = metrics.by_label("batched_segments")
    assert set(by_stream) <= {"h0", "h1", "v"}
    assert "h0" in by_stream or "h1" in by_stream


# -------------------------------------------- scheduler: no busy-wait


def test_event_driven_scheduler_no_busy_wait(tmp_path):
    """A slow sink parks the fleet repeatedly; the condition-variable
    wakeup must wait in O(50 ms) slices, not spin at the old 2 ms
    poll — fleet_idle_waits stays two orders of magnitude below what
    a busy-wait over the same wall time would log."""
    bb = _make_bb(tmp_path, "slow", 0)

    class _SlowCap(_Cap):
        def push(self, work, positive):
            time.sleep(0.25)
            super().push(work, positive)

    cap = _SlowCap()
    t0 = time.perf_counter()
    fleet = StreamFleet([StreamSpec(
        name="slow", cfg=_mkcfg(tmp_path, "slow", bb), sinks=[cap])])
    res = fleet.run()
    fleet.close()
    elapsed = time.perf_counter() - t0
    assert res["slow"].status == "done" and len(cap.out) > 0
    waits = int(metrics.get("fleet_idle_waits"))
    # busy-wait at the old 2 ms sleep over the same stalled wall time
    # would log ~elapsed/0.002 waits; the cond-var waits in >= 50 ms
    # slices (plus real wakeups), so give 4x headroom over elapsed/0.05
    assert waits <= max(40, int(elapsed / 0.05 * 4)), \
        f"{waits} idle waits in {elapsed:.2f}s looks like a busy-wait"


# ------------------------------- batch-aware admission + shed policy


def test_admission_eviction_prefers_loner_family():
    """An outranking request evicts, within the lowest-priority band,
    the newest stream whose plan family has NO co-tenant — kicking a
    batch-group member would cost its whole family the batch density."""
    ac = AdmissionController(max_streams=1, queue_limit=2)
    assert ac.request("run0", priority=0, plan_key="k1") == "admit"
    # queue fills: the LONER (k2) arrives FIRST, the co-tenant (k1)
    # second — pre-batching eviction would take the newest (k1)
    assert ac.request("lone", priority=0, plan_key="k2") == "queue"
    assert ac.request("mate", priority=0, plan_key="k1") == "queue"
    assert ac.request("vip", priority=5, plan_key=None) == "queue"
    assert ac.rejected == ["lone"]
    assert ac.queued == ["vip", "mate"]


def test_admission_eviction_unchanged_without_plan_keys():
    """All-None plan keys reproduce the pre-batching behavior exactly:
    the newest arrival of the lowest band is evicted."""
    ac = AdmissionController(max_streams=1, queue_limit=2)
    assert ac.request("run0", priority=0) == "admit"
    assert ac.request("q0", priority=0) == "queue"
    assert ac.request("q1", priority=0) == "queue"
    assert ac.request("vip", priority=5) == "queue"
    assert ac.rejected == ["q1"]


def test_shed_prefers_unbatched_within_band():
    """Fleet shedding under pressure takes the UNBATCHED lane first
    within a priority band (shedding a batch member degrades its
    whole family); restore order mirrors it."""
    pol = FleetShedPolicy(hold=1)
    lanes = [("bat", 0, True, True), ("solo", 0, True, False)]
    assert pol.observe(1.0, False, lanes) == {"solo"}
    assert pol.observe(1.0, False, lanes) == {"solo", "bat"}
    # relief: the batched member comes back first
    assert pol.observe(0.0, False, lanes) == {"solo"}
    # 3-tuple callers (no batching) still work
    pol2 = FleetShedPolicy(hold=1)
    assert pol2.observe(1.0, False,
                        [("a", 0, True), ("b", 1, True)]) == {"a"}


# ------------------------------------------------- telemetry schema


def test_span_v10_batch_fields_omitted_when_solo():
    assert telemetry.SPAN_SCHEMA_VERSION == 13
    rec = telemetry.segment_span(0, {"dispatch": 0.1}, 0, 0, False,
                                 1024)
    assert "batch_size" not in rec and "batch_wait_ms" not in rec
    rec = telemetry.segment_span(0, {"dispatch": 0.1}, 0, 0, False,
                                 1024, batch_size=3,
                                 batch_wait_ms=1.234)
    assert rec["batch_size"] == 3
    assert rec["batch_wait_ms"] == 1.234


# ------------------------------------------- archive cross-file leg


def test_archive_replay_fleet_batch(tmp_path):
    """Many small files, micro_batch=1, fleet_batch armed: the replay
    report shows cross-file batched dispatches and no failures."""
    from srtb_tpu.pipeline.archive import ArchiveReplay

    files = [_make_bb(tmp_path, f"f{i}", i) for i in range(3)]
    base = _mkcfg(tmp_path, "arch", files[0])
    rep = ArchiveReplay(base, files, str(tmp_path / "arch_out"),
                        lanes=3, micro_batch=1, inflight=2,
                        fleet_batch=3, manifest=False).run()
    assert rep.failed == 0
    assert rep.batched_dispatches >= 1
    assert rep.batched_segments >= 2 * rep.batched_dispatches
