"""Causal segment tracing, the flight recorder, incident bundles, the
Chrome-trace exporter, and SLO burn-rate evaluation (ISSUE 13).

Unit layer: the event hub's ring/shard/merge mechanics and zero-cost
off contract, the SLO burn math under an injected clock, incident
rate/count bounds.  E2E layer: a CPU pipeline run whose every segment
leaves a complete causal chain across the engine/sink thread boundary,
a seeded escalation that produces exactly one incident bundle holding
the injected fault site, its classification, the heal decisions and
the affected segment's manifest disposition, and the exporter's
structural Chrome-trace guarantees."""

import json
import os
import threading

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.utils import events, slo, telemetry
from srtb_tpu.utils.events import EventHub
from srtb_tpu.utils.incidents import IncidentRecorder
from srtb_tpu.utils.metrics import metrics
from srtb_tpu.utils.slo import SloTracker


@pytest.fixture(autouse=True)
def _fresh_observability():
    """Each test gets a fresh hub/registry/SLO world (they are
    process-global by design)."""
    events.configure(False)  # drop any previous test's shards...
    events.configure(True, ring_size=events.DEFAULT_RING_SIZE)
    metrics.reset()
    slo.reset()
    yield
    events.configure(False)
    events.configure(True, ring_size=events.DEFAULT_RING_SIZE)
    metrics.reset()
    slo.reset()


# ------------------------------------------------------------- hub units


def test_ring_bounded_no_growth():
    """The flight recorder is O(ring size): overwriting slots, never
    growing — 10x ring_size emits leave exactly ring_size slots and
    only the newest events."""
    hub = EventHub(ring_size=64)
    for i in range(640):
        hub.emit("stage.ingest", trace=i, seg=i)
    sh = hub._tls.shard
    assert sh.n == 64 and len(sh.slots) == 64
    evs = hub.dump()
    assert len(evs) == 64
    assert [e["trace"] for e in evs] == list(range(576, 640))


def test_shards_merge_across_threads_ordered():
    hub = EventHub(ring_size=128)
    hub.emit("stage.ingest", trace=1)

    def worker():
        hub.emit("stage.sink", trace=1)

    t = threading.Thread(target=worker, name="shard-worker")
    t.start()
    t.join()
    hub.emit("stage.fetch", trace=1)
    evs = hub.dump()
    assert [e["type"] for e in evs] == ["stage.ingest", "stage.sink",
                                       "stage.fetch"]  # by time
    assert {e["thread"] for e in evs} == {
        threading.current_thread().name, "shard-worker"}
    # per-trace filter
    assert hub.dump(trace=2) == []
    assert len(hub.dump(trace=1)) == 3


def test_zero_cost_off_and_configure_keeps_ring():
    events.configure(False)
    assert events.hub is None
    events.emit("stage.ingest", trace=1)  # no-op, no raise
    events.configure(True, ring_size=256)
    events.emit("retry", trace=7, info="x")
    # re-arming with the same ring KEEPS the recorder (a fleet
    # constructing N lanes must not wipe it N times)
    events.configure(True, ring_size=256)
    assert [e["trace"] for e in events.hub.dump()] == [7]
    # a different ring size rebuilds
    events.configure(True, ring_size=128)
    assert events.hub.dump() == []


def test_ambient_context_attribution():
    events.set_current(42, "beamX")
    events.emit("retry", info="dispatch:transient:1")
    events.emit("manifest.intent", trace=3, stream="other")
    evs = events.hub.dump()
    assert evs[0]["trace"] == 42 and evs[0]["stream"] == "beamX"
    assert evs[1]["trace"] == 3 and evs[1]["stream"] == "other"


def test_dump_jsonl_roundtrip(tmp_path):
    events.emit("stage.dispatch", trace=5, seg=2, dur=0.01, info="z")
    path = str(tmp_path / "ev" / "events.jsonl")
    n = events.hub.dump_jsonl(path)
    assert n == 1
    rec = json.loads(open(path).read().strip())
    assert rec["type"] == "stage.dispatch" and rec["trace"] == 5
    assert rec["dur_ms"] == 10.0 and rec["seg"] == 2
    assert "ts" in rec and "thread" in rec


# ------------------------------------------------------ pipeline helpers


def _mk_cfg(tmp_path, tag, n=1 << 14, **kw):
    from srtb_tpu.io.synth import make_dispersed_baseband
    bb = tmp_path / f"{tag}.bin"
    if not bb.exists():
        make_dispersed_baseband(n * 4, 1405.0, 64.0, 0.0,
                                pulse_positions=n // 2, pulse_amp=30.0,
                                nbits=8).tofile(str(bb))
    return Config(baseband_input_count=n, baseband_input_bits=8,
                  baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                  baseband_sample_rate=128e6,
                  input_file_path=str(bb),
                  baseband_output_file_prefix=str(tmp_path / f"{tag}_"),
                  spectrum_channel_count=1 << 6,
                  mitigate_rfi_average_method_threshold=100.0,
                  mitigate_rfi_spectral_kurtosis_threshold=2.0,
                  baseband_reserve_sample=False, writer_thread_count=0,
                  retry_backoff_base_s=0.001,
                  **dict({"inflight_segments": 3}, **kw))


# --------------------------------------------------------- e2e causality


def test_pipeline_causal_chain_across_threads(tmp_path):
    """Every drained segment owns a distinct trace_id whose event
    chain runs ingest -> dispatch -> fetch -> sink in time order, with
    the sink stage on the sink-pipe thread (the boundary the flow
    arrows cross), and the journal span carries the same trace_id."""
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import telemetry_report as TR

    journal = str(tmp_path / "j.jsonl")
    cfg = _mk_cfg(tmp_path, "chain",
                  telemetry_journal_path=journal,
                  events_dump_path=str(tmp_path / "events.jsonl"))
    with Pipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    assert stats.segments >= 3
    evs = events.hub.dump()
    by_trace = {}
    for e in evs:
        if e["type"].startswith("stage."):
            by_trace.setdefault(e["trace"], []).append(e)
    assert len(by_trace) == stats.segments
    assert all(t > 0 for t in by_trace)
    for chain in by_trace.values():
        assert [e["type"] for e in chain] == [
            "stage.ingest", "stage.dispatch", "stage.fetch",
            "stage.sink"]
        assert all(e["dur_ms"] >= 0 for e in chain)
        # the sink stage ran on the sink pipe thread — the causal
        # chain crosses the thread boundary
        assert chain[3]["thread"] != chain[0]["thread"]
        assert chain[3]["thread"].startswith("sink_drain")
    # v8 journal spans join the recorder on trace_id
    recs = TR.load(journal)
    assert [r["v"] for r in recs] == [13] * stats.segments
    assert sorted(r["trace_id"] for r in recs) == sorted(by_trace)
    # the run-end dump landed for the exporter
    assert os.path.exists(str(tmp_path / "events.jsonl"))


def test_events_disabled_run_is_clean(tmp_path):
    """events_enable=0: no trace stamping, no events, spans omit
    trace_id — and the run completes identically."""
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import telemetry_report as TR

    journal = str(tmp_path / "j.jsonl")
    cfg = _mk_cfg(tmp_path, "off", events_enable=False,
                  telemetry_journal_path=journal)
    with Pipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    assert stats.segments >= 3
    assert events.hub is None
    for r in TR.load(journal):
        assert "trace_id" not in r


def test_retry_event_attributed_to_segment(tmp_path):
    """A dispatch-site retry lands on the flight recorder carrying the
    faulted segment's trace id (ambient-context attribution)."""
    from srtb_tpu.pipeline.runtime import Pipeline

    cfg = _mk_cfg(tmp_path, "retry", fault_plan="dispatch:raise@1")
    with Pipeline(cfg, sinks=[]) as pipe:
        pipe.run()
        assert pipe.faults.unfired() == []
    evs = events.hub.dump()
    retries = [e for e in evs if e["type"] == "retry"]
    injected = [e for e in evs if e["type"] == "fault.injected"]
    assert len(retries) == 1 and len(injected) == 1
    assert retries[0]["info"].startswith("dispatch:transient:")
    # both carry segment 1's trace (= the dispatch stage event that
    # eventually succeeded for seg index 1)
    seg1 = [e for e in evs if e["type"] == "stage.dispatch"
            and e["seg"] == 1]
    assert seg1 and retries[0]["trace"] == seg1[0]["trace"] > 0
    assert injected[0]["trace"] == seg1[0]["trace"]


# ------------------------------------------------------ incident bundles


def test_escalation_writes_one_bundle_with_causal_story(tmp_path):
    """The acceptance gate: a seeded device-fault escalation produces
    exactly ONE incident bundle whose causal evidence holds the
    injected fault site, its classification, every heal/demote
    decision, and the affected segment's manifest disposition."""
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.resilience.errors import LadderExhausted

    inc_dir = str(tmp_path / "incidents")
    cfg = _mk_cfg(
        tmp_path, "esc",
        fault_plan="dispatch:oom@1,fetch:oom@2",
        # exactly one real rung: the staged demotion (the base plan
        # resolves unstaged at this size) — the first oom demotes,
        # the second exhausts the ladder.  Serial window: segment 0
        # fully drains (manifest commit + ckpt) BEFORE the faults, so
        # the bundle deterministically holds the WAL's disposition.
        plan_ladder="staged", device_reinit_max=0,
        inflight_segments=1,
        incident_dir=inc_dir,
        checkpoint_path=str(tmp_path / "esc_ck.json"),
        run_manifest_path=str(tmp_path / "esc_manifest.wal"),
        telemetry_journal_path=str(tmp_path / "esc_j.jsonl"))
    with pytest.raises(LadderExhausted), \
            Pipeline(cfg) as pipe:
        pipe.run()
    bundles = [d for d in os.listdir(inc_dir)
               if d.startswith("incident_")]
    assert len(bundles) == 1, bundles
    assert "ladder_exhausted" in bundles[0]
    b = os.path.join(inc_dir, bundles[0])
    names = set(os.listdir(b))
    assert {"incident.json", "events.jsonl", "trace.jsonl",
            "plan.json", "config.json", "metrics.json"} <= names
    meta = json.load(open(os.path.join(b, "incident.json")))
    assert meta["kind"] == "ladder_exhausted"
    offender = meta["trace_id"]
    assert offender > 0
    evs = [json.loads(ln) for ln in open(os.path.join(b,
                                                      "events.jsonl"))]
    types = [e["type"] for e in evs]
    # the injected fault site fired, twice
    fired = [e for e in evs if e["type"] == "fault.injected"]
    assert len(fired) == 2
    assert any("dispatch:oom@1" in e["info"] for e in fired)
    assert any("fetch:oom@2" in e["info"] for e in fired)
    # classification + every heal decision
    assert types.count("fault.device") == 2
    demotes = [e for e in evs if e["type"] == "heal.demote"]
    assert len(demotes) == 1 and demotes[0]["info"].startswith(
        "staged@1")
    # manifest disposition: the WAL's records are on the trace (the
    # run stamps a ckpt consistency point; committed artifacts of
    # earlier segments carry intent/commit/done)
    assert "manifest.ckpt" in types
    # the offending trace's own story is a strict, non-empty subset
    tr = [json.loads(ln) for ln in open(os.path.join(b,
                                                     "trace.jsonl"))]
    assert tr and all(e["trace"] == offender for e in tr)
    assert any(e["type"] == "fault.device" for e in tr)
    # plan identity rode along
    plan = json.load(open(os.path.join(b, "plan.json")))
    assert plan["plan_name"]
    # metrics + config snapshots are JSON objects
    assert json.load(open(os.path.join(b, "metrics.json")))
    assert json.load(open(os.path.join(b, "config.json")))[
        "plan_ladder"] == "staged"
    assert metrics.get("incident_bundles") == 1


def test_incident_rate_limit_and_count_bound(tmp_path):
    rec = IncidentRecorder(str(tmp_path / "inc"), max_bundles=2,
                           min_interval_s=3600.0)
    assert rec.dump("first", reason="a") is not None
    # inside the rate window: suppressed
    assert rec.dump("second", reason="b") is None
    assert metrics.get("incidents_suppressed") == 1
    rec.min_interval_s = 0.0
    assert rec.dump("third", reason="c") is not None
    # count bound: two bundles kept, further dumps suppressed
    assert rec.dump("fourth", reason="d") is None
    assert metrics.get("incident_bundles") == 2
    assert metrics.get("incidents_suppressed") == 2
    names = sorted(os.listdir(str(tmp_path / "inc")))
    assert len(names) == 2
    # sequence numbers monotonic, kinds in the names
    assert names[0].startswith("incident_000_first")
    assert names[1].startswith("incident_001_third")


def test_incident_tmp_swept_on_construction(tmp_path):
    d = tmp_path / "inc"
    d.mkdir()
    stale = d / ("incident_000_x" + ".srtb_tmp")
    stale.mkdir()
    (stale / "partial.json").write_text("{}")
    IncidentRecorder(str(d))
    assert not stale.exists()


# ---------------------------------------------------------- trace export


def test_trace_export_structure_and_flows(tmp_path):
    """Rendered output is valid Chrome-trace JSON; each segment's flow
    chain binds its stage slices across the thread boundary."""
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import trace_export as TE

    dump = str(tmp_path / "events.jsonl")
    cfg = _mk_cfg(tmp_path, "export", events_dump_path=dump)
    with Pipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    doc = TE.render(TE.load_events(dump))
    assert TE.validate(doc) == []
    evs = doc["traceEvents"]
    slices = [e for e in evs if e["ph"] == "X"]
    for stage in ("ingest", "dispatch", "fetch", "sink"):
        assert sum(1 for e in slices if e["name"] == stage) \
            == stats.segments
    # flow chains: one per segment, start on the engine thread's
    # track, finish (bp=e) on the sink thread's track
    starts = [e for e in evs if e["ph"] == "s"]
    finishes = [e for e in evs if e["ph"] == "f"]
    assert len(starts) == len(finishes) == stats.segments
    assert {e["id"] for e in starts} == {e["id"] for e in finishes}
    for s, f in zip(sorted(starts, key=lambda e: e["id"]),
                    sorted(finishes, key=lambda e: e["id"])):
        assert s["tid"] != f["tid"]  # crosses the thread boundary
        assert f["bp"] == "e"
    # CLI: validate mode + file output
    assert TE.main([dump, "--validate"]) == 0
    out = str(tmp_path / "t.json")
    assert TE.main([dump, "--out", out]) == 0
    assert TE.validate(json.load(open(out))) == []


def test_trace_export_one_lane_per_stream(tmp_path):
    """Multi-stream dumps render one trace *process* per stream (the
    fleet view: lanes side by side)."""
    from srtb_tpu.tools import trace_export as TE

    path = str(tmp_path / "ev.jsonl")
    with open(path, "w") as f:
        t = 100.0
        for stream in ("beam0", "beam1"):
            for i, stage in enumerate(("stage.ingest",
                                       "stage.dispatch",
                                       "stage.fetch", "stage.sink")):
                t += 0.001
                f.write(json.dumps({
                    "t": t, "ts": t, "type": stage,
                    "trace": 1 if stream == "beam0" else 2,
                    "stream": stream, "seg": 0, "dur_ms": 0.5,
                    "info": "",
                    "thread": "main" if i < 3 else "sink"}) + "\n")
        f.write(json.dumps({
            "t": t + 1, "ts": t + 1, "type": "heal.demote",
            "trace": 2, "stream": "beam1", "seg": 0, "dur_ms": 0,
            "info": "staged@1", "thread": "main"}) + "\n")
    doc = TE.render(TE.load_events(path))
    assert TE.validate(doc) == []
    procs = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("name") == "process_name"}
    assert procs == {"stream:beam0", "stream:beam1"}
    assert doc["otherData"]["streams"] == ["beam0", "beam1"]
    # decisions render as thread-scoped instants
    inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["name"] == "heal.demote"


def test_trace_export_rejects_garbage(tmp_path):
    from srtb_tpu.tools import trace_export as TE

    empty = tmp_path / "empty.jsonl"
    empty.write_text("not json\n")
    assert TE.main([str(empty), "--validate"]) == 1
    assert TE.validate({"traceEvents": "nope"}) != []
    assert TE.validate({"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0}]}) != []  # no dur
    assert TE.validate({"traceEvents": [
        {"ph": "s", "pid": 1, "tid": 1, "ts": 0.0, "id": 1}]}) != []


# --------------------------------------------------------------- SLO/burn


def _clocked_tracker(**kw):
    t = [0.0]

    def clock():
        return t[0]

    kw.setdefault("fast_window_s", 10.0)
    kw.setdefault("slow_window_s", 100.0)
    return SloTracker(clock=clock, **kw), t


def test_slo_latency_burn_states():
    tr, t = _clocked_tracker(latency_ms=10.0, latency_budget=0.1)
    assert tr.objectives == ("latency",)
    # 100 good segments: ok, burn 0
    for _ in range(100):
        t[0] += 0.1
        tr.note_segment("", 0.005)
    rep = tr.evaluate()["_pipeline"]["latency"]
    assert rep == {"burn_fast": 0.0, "burn_slow": 0.0, "state": "ok"}
    # 5% bad < 10% budget: degraded, burn 0.5
    for i in range(100):
        t[0] += 0.01
        tr.note_segment("", 0.05 if i % 20 == 0 else 0.005)
    rep = tr.evaluate()["_pipeline"]["latency"]
    assert rep["state"] == "degraded"
    assert 0.0 < rep["burn_fast"] < 1.0
    # sustained 100% bad: burning on both windows
    for _ in range(300):
        t[0] += 0.5
        tr.note_segment("", 0.05)
    rep = tr.evaluate()["_pipeline"]["latency"]
    assert rep["state"] == "burning"
    assert rep["burn_fast"] >= 1.0 and rep["burn_slow"] >= 1.0
    # gauges landed (flat stream -> no stream label)
    assert metrics.get("slo_state",
                       labels={"objective": "latency"}) == 2
    assert metrics.get(
        "slo_burn_rate",
        labels={"objective": "latency", "window": "fast"}) >= 1.0


def test_slo_loss_burn_per_stream():
    tr, t = _clocked_tracker(loss_budget=0.01)
    for _ in range(99):
        t[0] += 0.01
        tr.note_segment("beamA", 0.001)
        tr.note_segment("beamB", 0.001)
    tr.note_dropped("beamB", 99)  # 50% loss on B only
    rep = tr.evaluate()
    assert rep["beamA"]["loss"]["state"] == "ok"
    assert rep["beamB"]["loss"]["state"] == "burning"
    assert rep["beamA"]["ok"] and not rep["beamB"]["ok"]
    assert metrics.get("slo_state", labels={
        "objective": "loss", "stream": "beamB"}) == 2
    assert metrics.get("slo_state", labels={
        "objective": "loss", "stream": "beamA"}) == 0


def test_slo_staleness_burn():
    tr, t = _clocked_tracker(staleness_s=5.0, staleness_budget=0.1)
    tr.note_segment("", 0.001)
    t[0] += 4.0  # within the allowed gap
    assert tr.evaluate()["_pipeline"]["staleness"]["state"] == "ok"
    t[0] += 12.0  # 11 s beyond: > 10% of both windows
    rep = tr.evaluate()["_pipeline"]["staleness"]
    assert rep["state"] == "burning" and rep["burn_fast"] > 1.0


def test_slo_state_transition_emits_event():
    tr, t = _clocked_tracker(loss_budget=0.01)
    tr.note_segment("", 0.001)
    tr.evaluate()
    tr.note_dropped("", 10)
    tr.evaluate()
    evs = [e for e in events.hub.dump() if e["type"] == "slo"]
    assert evs and evs[-1]["info"] == "loss:ok->burning"


def test_healthz_carries_slo_section(tmp_path):
    cfg = Config(slo_latency_ms=50.0, slo_loss_budget=0.01)
    tracker = slo.configure(cfg)
    assert tracker is not None and slo.tracker is tracker
    slo.note_segment("", 0.001)
    telemetry.mark_segment()
    h = telemetry.health(stale_after_s=30.0)
    assert h["ok"] and h["slo_ok"]
    assert set(h["slo"]["_pipeline"]) == {"latency", "loss", "ok"}
    # a second configure with identical params keeps the tracker (a
    # fleet's lanes share it)
    assert slo.configure(cfg) is tracker
    # an unarmed config does NOT disarm a live tracker
    assert slo.configure(Config()) is tracker


def test_pipeline_feeds_slo(tmp_path):
    from srtb_tpu.pipeline.runtime import Pipeline

    cfg = _mk_cfg(tmp_path, "slo", slo_latency_ms=1e9,
                  slo_loss_budget=0.5)
    with Pipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    rep = slo.evaluate()
    assert rep is not None
    per = rep["_pipeline"]
    assert per["latency"]["state"] == "ok"
    assert per["loss"]["state"] == "ok"
    assert per["ok"]
    # the latency denominator saw every drained segment
    st = slo.tracker._streams[""]
    assert st.lat[0].total() == stats.segments
