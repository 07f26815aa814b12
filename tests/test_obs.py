"""Fleet control tower (srtb_tpu/obs/): digests, store, aggregator,
cross-device trace join, status + console, /fleet."""

import gzip
import json
import math
import os

import numpy as np
import pytest

from srtb_tpu.obs.digest import QuantileDigest
from srtb_tpu.obs.rollup import Aggregator
from srtb_tpu.obs.store import RollupStore


def _span(ts, seg, stream="s0", device="dev0", plan="p1", **extra):
    rec = {"type": "segment_span", "ts": float(ts), "segment": int(seg),
           "stream": stream, "device": device, "active_plan": plan,
           "samples": 4096,
           "stages_ms": {"ingest": 1.0, "dispatch": 2.0, "sink": 0.5}}
    rec.update(extra)
    return rec


def _write_journal(path, records):
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")


# ------------------------------------------------------------ digest


def test_digest_percentiles_within_documented_error():
    """Any quantile estimate is within ``alpha`` relative error of the
    exact sample at that rank (one order statistic of slack covers the
    interpolation-convention difference vs numpy)."""
    rng = np.random.default_rng(42)
    vals = rng.lognormal(mean=0.0, sigma=1.0, size=20000)
    d = QuantileDigest(alpha=0.01)
    for v in vals:
        d.add(float(v))
    s = np.sort(vals)
    for q in (0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99):
        est = d.quantile(q)
        rank = max(1, math.ceil(q * len(s)))
        neighborhood = s[max(0, rank - 2):rank + 1]
        rel = min(abs(est - x) / x for x in neighborhood)
        assert rel <= d.alpha + 1e-9, (q, est, rel)
    assert d.quantile(0.0) == float(s[0])
    assert d.quantile(1.0) == float(s[-1])


def test_digest_merge_equals_whole():
    """Digesting a stream in three parts then merging equals digesting
    it whole — exactly (same buckets, same counts)."""
    rng = np.random.default_rng(7)
    vals = rng.exponential(scale=3.0, size=3000)
    whole = QuantileDigest()
    parts = [QuantileDigest() for _ in range(3)]
    for i, v in enumerate(vals):
        whole.add(float(v))
        parts[i % 3].add(float(v))
    merged = parts[0]
    merged.merge(parts[1])
    merged.merge(parts[2])
    assert merged.buckets == whole.buckets
    assert merged.count == whole.count
    assert merged.min == whole.min and merged.max == whole.max
    for q in (0.5, 0.95, 0.99):
        assert merged.quantile(q) == whole.quantile(q)


def test_digest_round_trip_and_guards():
    d = QuantileDigest()
    for v in (0.0, 1e-12, 0.5, 100.0):
        d.add(v)
    back = QuantileDigest.from_dict(
        json.loads(json.dumps(d.to_dict(), sort_keys=True)))
    assert back.buckets == d.buckets and back.zeros == d.zeros == 2
    assert back.quantile(0.99) == d.quantile(0.99)
    with pytest.raises(ValueError):
        d.add(-1.0)
    with pytest.raises(ValueError):
        d.add(float("nan"))
    with pytest.raises(ValueError):
        QuantileDigest(alpha=0.01).merge(QuantileDigest(alpha=0.02))
    assert math.isnan(QuantileDigest().quantile(0.5))


# ------------------------------------------------------------- store


def test_store_last_wins_and_compaction_idempotent(tmp_path):
    store = RollupStore(str(tmp_path / "store"))
    store.append_many([
        {"k": "m:1:a", "minute": 1, "segments": 2},
        {"k": "m:1:a", "minute": 1, "segments": 5},  # supersedes
        {"k": "m:2:a", "minute": 2, "segments": 1},
        {"k": "d:stage:x", "digest": {"count": 3}},  # minute-less
    ])
    assert store.latest()["m:1:a"]["segments"] == 5
    r1 = store.compact()
    assert r1["rows"] == 3

    def seg_bytes():
        return {n: (tmp_path / "store" / "segments" / n).read_bytes()
                for n in os.listdir(tmp_path / "store" / "segments")}

    b1 = seg_bytes()
    r2 = store.compact()
    assert r2["rows"] == 3 and seg_bytes() == b1  # byte-identical
    # active arm truncated; state survives in segments
    assert store.latest()["m:1:a"]["segments"] == 5
    # a re-appended duplicate collapses again, not double-counts
    store.append({"k": "m:2:a", "minute": 2, "segments": 1})
    store.compact()
    assert seg_bytes() == b1
    with pytest.raises(ValueError):
        store.append({"minute": 3})  # unkeyed row = programming error


def test_store_retention_drops_old_minutes(tmp_path):
    store = RollupStore(str(tmp_path / "s"), retention_minutes=10)
    store.append_many(
        [{"k": f"m:{m}", "minute": m} for m in (0, 5, 90, 100)]
        + [{"k": "d:meta"}])  # minute-less rows never expire
    rep = store.compact()
    assert rep["dropped"] == 2  # minutes 0 and 5 are > 10 behind 100
    keys = set(store.latest())
    assert keys == {"m:90", "m:100", "d:meta"}


# -------------------------------------------------------- aggregator


def test_aggregator_rollup_counters_and_digests(tmp_path):
    jp = str(tmp_path / "j.jsonl")
    recs = [_span(60.0 + i, i, segments_dropped=(1 if i >= 3 else 0),
                  detections=1, device_ms=2.0, batch_size=2)
            for i in range(5)]
    _write_journal(jp, recs)
    store = RollupStore(str(tmp_path / "store"))
    agg = Aggregator(store, journals=[jp])
    assert agg.poll()["spans"] == 5
    agg.flush()
    state = store.latest()
    row = state["m:1:s0:dev0:p1"]  # ts 60-64 -> minute 1
    assert row["segments"] == 5 and row["detections"] == 5
    # cumulative 0,0,0,1,1 -> one localized loss delta
    assert row["loss_delta"] == 1
    assert row["device_ms_sum"] == pytest.approx(10.0)
    assert row["batch_segments"] == 10
    dig = QuantileDigest.from_dict(
        state["d:stage:dispatch"]["digest"])
    assert dig.count == 5
    assert agg.plans() == ["p1"]


def test_aggregator_resumes_active_journal_by_offset(tmp_path):
    jp = str(tmp_path / "j.jsonl")
    _write_journal(jp, [_span(60.0 + i, i) for i in range(4)])
    store_dir = str(tmp_path / "store")
    agg = Aggregator(RollupStore(store_dir), journals=[jp])
    assert agg.poll()["spans"] == 4
    agg.flush()
    # torn tail (no newline) is left for the next poll
    with open(jp, "a") as f:
        f.write(json.dumps(_span(64.0, 4)) + "\n")
        f.write('{"type": "segment_span", "ts": 65')
    agg2 = Aggregator(RollupStore(store_dir), journals=[jp])
    assert agg2.poll()["spans"] == 1  # only the complete new record
    agg2.flush()
    with open(jp, "a") as f:
        f.write('.0, "segment": 5, "stream": "s0", '
                '"stages_ms": {"ingest": 1.0}}\n')
    agg3 = Aggregator(RollupStore(store_dir), journals=[jp])
    assert agg3.poll()["spans"] == 1  # the completed torn record
    assert agg3.poll()["spans"] == 0  # and nothing twice


def test_aggregator_resumes_from_torn_gz_without_double_count(
        tmp_path):
    """A rotated .gz generation read torn, then complete: only the
    records beyond the first read are ingested (total == exact)."""
    jp = str(tmp_path / "j.jsonl")
    recs = [_span(60.0 + i, i) for i in range(20)]
    payload = "".join(json.dumps(r) + "\n" for r in recs).encode()
    whole = gzip.compress(payload)
    gen = jp + ".1.gz"
    with open(gen, "wb") as f:
        f.write(whole[:len(whole) * 2 // 3])  # torn tail
    _write_journal(jp, [_span(100.0, 20)])  # active arm: 1 span
    store_dir = str(tmp_path / "store")
    agg = Aggregator(RollupStore(store_dir), journals=[jp])
    first = agg.poll()["spans"]
    assert 1 <= first < 21  # readable gz prefix + the active span
    agg.flush()
    with open(gen, "wb") as f:
        f.write(whole)  # rotation completed / repaired
    agg2 = Aggregator(RollupStore(store_dir), journals=[jp])
    second = agg2.poll()["spans"]
    assert first + second == 21  # no span counted twice, none lost
    agg2.flush()
    assert Aggregator(RollupStore(store_dir),
                      journals=[jp]).poll()["spans"] == 0


def test_aggregator_detects_rotation_of_active_arm(tmp_path):
    jp = str(tmp_path / "j.jsonl")
    _write_journal(jp, [_span(60.0 + i, i) for i in range(3)])
    store_dir = str(tmp_path / "store")
    agg = Aggregator(RollupStore(store_dir), journals=[jp])
    assert agg.poll()["spans"] == 3
    agg.flush()
    # rotate: old contents become the .1.gz generation, fresh active
    with open(jp, "rb") as f:
        old = f.read()
    with open(jp + ".1.gz", "wb") as f:
        f.write(gzip.compress(old))
    _write_journal(jp, [_span(120.0 + i, 3 + i) for i in range(2)])
    agg2 = Aggregator(RollupStore(store_dir), journals=[jp])
    # generation re-read is cursor-skipped; fresh active reads from 0
    assert agg2.poll()["spans"] == 2


def test_aggregator_event_dump_dedup(tmp_path):
    ev = str(tmp_path / "events.jsonl")
    rows = [{"t": 1.5, "ts": 61.5, "type": "fleet.migrate",
             "stream": "s0", "seg": 3, "info": "dev0->dev1",
             "thread": "ctl"},
            {"t": 2.5, "ts": 62.5, "type": "stage.sink", "stream": "s0",
             "seg": 3, "thread": "sink"}]  # not a fleet event
    with open(ev, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    store = RollupStore(str(tmp_path / "store"))
    agg = Aggregator(store, journals=[], events_dumps=[ev])
    assert agg.poll()["events"] == 1
    # dumps are full rewrites: re-reading must not re-count
    assert agg.poll()["events"] == 0
    agg.flush()
    evs = [r for r in store.latest().values()
           if r["type"] == "fleet_event"]
    assert len(evs) == 1 and evs[0]["kind"] == "fleet.migrate"
    assert evs[0]["info"] == "dev0->dev1"


# -------------------------------------------------- cross-device join


def test_trace_join_crosses_device_tracks(tmp_path):
    from srtb_tpu.obs import trace_join
    from srtb_tpu.tools.trace_export import validate
    jp = str(tmp_path / "j.jsonl")
    _write_journal(jp, [
        _span(60.0 + i, i, device=("dev0" if i < 3 else "dev1"))
        for i in range(6)])
    ev = str(tmp_path / "events.jsonl")
    with open(ev, "w") as f:
        for i in range(6):
            f.write(json.dumps(
                {"t": 10.0 + i, "ts": 60.0 + i, "type": "stage.dispatch",
                 "trace": i + 1, "stream": "s0", "seg": i,
                 "dur_ms": 2.0, "thread": "eng"}) + "\n")
            f.write(json.dumps(
                {"t": 10.4 + i, "ts": 60.4 + i, "type": "stage.sink",
                 "trace": i + 1, "stream": "s0", "seg": i,
                 "dur_ms": 0.5, "thread": "sink"}) + "\n")
        f.write(json.dumps(
            {"t": 12.5, "ts": 62.5, "type": "fleet.migrate", "trace": 0,
             "stream": "s0", "seg": -1, "info": "dev0->dev1",
             "thread": "ctl"}) + "\n")
    doc = trace_join.join([ev], [jp])
    assert validate(doc) == []  # the same structural gate as CI
    assert doc["otherData"]["devices"] == ["dev0", "dev1"]
    assert doc["otherData"]["stream_devices"]["s0"] == ["dev0", "dev1"]
    # the migration visual: the lane flow chain spans BOTH device pids
    lane = [e for e in doc["traceEvents"] if e.get("cat") == "flow"
            and e["id"] >= trace_join.LANE_FLOW_BASE]
    assert lane and len({e["pid"] for e in lane}) == 2
    # unmapped events would fall to a host track; here all map
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert names == {"device:dev0", "device:dev1"}


def test_trace_join_cli(tmp_path, capsys):
    from srtb_tpu.obs import trace_join
    jp = str(tmp_path / "j.jsonl")
    _write_journal(jp, [_span(60.0, 0)])
    ev = str(tmp_path / "e.jsonl")
    with open(ev, "w") as f:
        f.write(json.dumps({"t": 1.0, "ts": 60.0,
                            "type": "stage.dispatch", "trace": 1,
                            "stream": "s0", "seg": 0, "dur_ms": 1.0,
                            "thread": "eng"}) + "\n")
    out = str(tmp_path / "trace.json")
    assert trace_join.main([ev, "--journals", jp, "--out", out]) == 0
    doc = json.load(open(out))
    assert doc["traceEvents"]


# --------------------------------------- status, console, /fleet


def test_fleet_status_and_console_render(tmp_path):
    from srtb_tpu.obs.status import fleet_status
    from srtb_tpu.tools import console
    from srtb_tpu.utils.metrics import metrics
    metrics.reset()
    try:
        metrics.set("fleet_device_state", 0, labels={"device": "dev0"})
        metrics.set("fleet_device_state", 2, labels={"device": "dev1"})
        metrics.set("fleet_device_lanes", 3, labels={"device": "dev0"})
        metrics.add("migrations", 2)
        metrics.add("migrations", labels={"device": "dev0"}, value=2)
        metrics.add("device_drains", labels={"device": "dev1"})
        metrics.add("batched_dispatches", 4)
        metrics.add("batched_segments", 10)
        # a store with a migration timeline row
        store = RollupStore(str(tmp_path / "store"))
        store.append({"k": "e:1", "type": "fleet_event", "minute": 1,
                      "ts": 61.0, "kind": "fleet.migrate",
                      "stream": "s0", "seg": 3, "info": "dev0->dev1"})
        status = fleet_status(store_dir=str(tmp_path / "store"))
        assert status["devices"]["dev0"]["state"] == "ok"
        assert status["devices"]["dev1"]["state"] == "halted"
        assert status["devices"]["dev0"]["lanes"] == 3
        assert status["pool"]["migrations"] == 2
        assert status["batch"]["occupancy"] == 2.5
        assert status["store"]["timeline"][0]["kind"] == "fleet.migrate"
        text = console.render(status)
        assert "POOL" in text and "dev1" in text and "halted" in text
        assert "fleet.migrate" in text and "dev0->dev1" in text
        assert "occupancy=2.50" in text
    finally:
        metrics.reset()


def test_fleet_endpoint_and_pool_aggregated_metrics(tmp_path):
    import urllib.request
    from srtb_tpu.gui.server import WaterfallHTTPServer
    from srtb_tpu.utils.metrics import metrics
    metrics.reset()
    try:
        metrics.set("fleet_device_state", 0, labels={"device": "dev0"})
        metrics.set("fleet_device_state", 1, labels={"device": "dev1"})
        metrics.add("migrations", labels={"device": "dev1"})
        srv = WaterfallHTTPServer(
            str(tmp_path), port=0,
            fleet_store_dir=str(tmp_path / "store")).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(base + "/fleet",
                                        timeout=10) as r:
                doc = json.loads(r.read().decode())
            assert set(doc["devices"]) == {"dev0", "dev1"}
            assert doc["devices"]["dev1"]["state"] == "draining"
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=10) as r:
                prom = r.read().decode()
            # pool aggregates render as ordinary flat families with
            # their own contiguous HELP/TYPE (strict-expfmt safe)
            assert "srtb_fleet_device_state_pool_max 1" in prom
            assert "srtb_fleet_device_state_pool_sum 1" in prom
            assert "srtb_migrations_pool_sum 1" in prom
            assert ("# HELP srtb_migrations_pool_sum Sum of "
                    "migrations across pool members") in prom
            # snapshot/prometheus parity holds for the new families
            snap = metrics.snapshot()
            assert snap["migrations_pool_sum"] == 1.0
            assert snap["fleet_device_state_pool_max"] == 1.0
            # labeled twins still render (per-device series intact)
            assert 'srtb_migrations{device="dev1"} 1' in prom
        finally:
            srv.stop()
    finally:
        metrics.reset()


def test_console_url_mode_against_server(tmp_path, capsys):
    from srtb_tpu.gui.server import WaterfallHTTPServer
    from srtb_tpu.tools import console
    from srtb_tpu.utils.metrics import metrics
    metrics.reset()
    try:
        metrics.set("fleet_device_state", 0, labels={"device": "dev0"})
        srv = WaterfallHTTPServer(str(tmp_path), port=0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            assert console.main(["--url", base, "--once"]) == 0
            out = capsys.readouterr().out
            assert "POOL" in out and "dev0" in out
            assert console.main(["--url", base, "--once",
                                 "--json"]) == 0
            doc = json.loads(capsys.readouterr().out)
            assert doc["devices"]["dev0"]["state"] == "ok"
        finally:
            srv.stop()
    finally:
        metrics.reset()


# -------------------------------------- telemetry_report fleet devices


def test_telemetry_report_fleet_device_section(tmp_path, capsys):
    from srtb_tpu.tools import telemetry_report as TR
    jp = str(tmp_path / "j.jsonl")
    recs = [
        # v1-era record: no stream/device — must be tolerated, skipped
        {"type": "segment_span", "ts": 59.0, "segment": 0,
         "stages_ms": {"ingest": 1.0}},
        _span(60.0, 0, stream="a", device="dev0", detections=1,
              segments_dropped=0),
        _span(61.0, 1, stream="a", device="dev0", segments_dropped=2),
        _span(62.0, 0, stream="b", device="dev1", detections=3,
              segments_dropped=0),
        # stream a migrates: the delta after the switch bills dev1
        _span(63.0, 2, stream="a", device="dev1", segments_dropped=3),
    ]
    _write_journal(jp, recs)
    fd = TR.fleet_device_stats(TR.load(jp))
    assert set(fd) == {"dev0", "dev1"}
    assert fd["dev0"] == {"spans": 2, "streams": 1, "detections": 1,
                          "segments_dropped": 2, "migrations_in": 0}
    assert fd["dev1"]["spans"] == 2 and fd["dev1"]["streams"] == 2
    assert fd["dev1"]["migrations_in"] == 1
    assert fd["dev1"]["segments_dropped"] == 1  # 3-2, post-migration
    # all-old journal: section simply absent
    assert TR.fleet_device_stats([recs[0]]) == {}
    # rendered report carries the table
    assert TR.main([jp]) == 0
    out = capsys.readouterr().out
    assert "## Fleet devices (per pool member)" in out
    assert "| dev1 | 2 | 2 | 3 | 1 | 1 |" in out
