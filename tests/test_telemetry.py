"""Telemetry-layer tests: histogram percentile math, sliding-window
rates, the JSONL segment-span journal (schema round-trip + rotation),
Prometheus text exposition, /healthz staleness, and the end-to-end
pipeline -> journal -> telemetry_report path on the CPU backend."""

import json
import math
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from srtb_tpu.utils import telemetry
from srtb_tpu.utils.metrics import (Histogram, Metrics, SlidingWindow,
                                    metrics)


# ---------------------------------------------------------------- units


def test_histogram_percentiles_interpolated():
    """Known uniform data over fine buckets: interpolated p50/p95/p99
    land within one bucket width of the exact percentile."""
    h = Histogram("t", buckets=[i / 100 for i in range(1, 101)])
    for i in range(1000):
        h.observe((i + 0.5) / 1000.0)  # uniform on (0, 1)
    assert abs(h.quantile(0.50) - 0.50) < 0.02
    assert abs(h.quantile(0.95) - 0.95) < 0.02
    assert abs(h.quantile(0.99) - 0.99) < 0.02
    p = h.percentiles()
    assert p["p50"] < p["p95"] < p["p99"]
    assert h.count == 1000
    assert abs(h.sum - 500.0) < 1.0


def test_histogram_edge_cases():
    h = Histogram("t", buckets=[1.0, 10.0])
    assert math.isnan(h.quantile(0.5))  # empty
    # everything in the overflow bucket clamps to the top finite edge
    for _ in range(5):
        h.observe(100.0)
    assert h.quantile(0.5) == 10.0
    # cumulative exposition: +Inf bucket equals the total count
    cum = h.cumulative_buckets()
    assert cum[-1] == (math.inf, 5)
    assert cum[0] == (1.0, 0)


def test_histogram_first_bucket_interpolates_from_zero():
    h = Histogram("t", buckets=[10.0, 20.0])
    for _ in range(10):
        h.observe(5.0)
    # rank q*10 inside the first bucket -> linear from 0 to 10
    assert abs(h.quantile(0.5) - 5.0) < 1e-9


def test_sliding_window_rate_and_pruning():
    t = [0.0]
    w = SlidingWindow("x", window_s=10.0, clock=lambda: t[0])
    for _ in range(5):
        w.add(2.0)
    t[0] = 5.0
    assert w.sum() == 10.0
    # young window: rate over elapsed time, not the full window
    assert abs(w.rate() - 10.0 / 5.0) < 1e-9
    # events age out
    t[0] = 10.5
    assert w.sum() == 0.0
    assert w.rate() == 0.0
    w.add(4.0)
    t[0] = 12.0
    assert w.sum() == 4.0
    assert abs(w.rate() - 4.0 / 10.0) < 1e-9  # mature: per window second


def test_metrics_registry_snapshot_and_reset():
    m = Metrics()
    m.add("segments", 3)
    m.histogram("stage_seconds", labels={"stage": "fetch"}).observe(0.02)
    m.window("segments", window_s=10.0).add(3)
    snap = m.snapshot()
    assert snap["segments"] == 3
    assert snap["stage_seconds_fetch_count"] == 1
    assert snap["stage_seconds_fetch_p50"] > 0
    assert snap["segments_per_sec_10s"] > 0
    # same (name, labels) -> same instrument; different labels -> new
    h1 = m.histogram("stage_seconds", labels={"stage": "fetch"})
    h2 = m.histogram("stage_seconds", labels={"stage": "sink"})
    assert h1.count == 1 and h2.count == 0
    m.reset()
    snap = m.snapshot()
    assert "segments" not in snap and "stage_seconds_fetch_count" \
        not in snap


def test_prometheus_exposition_format():
    m = Metrics()
    m.add("segments", 7)
    h = m.histogram("stage_seconds", buckets=[0.01, 0.1, 1.0],
                    labels={"stage": "dispatch"})
    h.observe(0.05)
    h.observe(0.05)
    h.observe(5.0)
    m.window("samples", window_s=10.0).add(100)
    text = m.prometheus()
    lines = text.strip().split("\n")
    assert text.endswith("\n")
    assert "# TYPE srtb_segments gauge" in lines
    assert "srtb_segments 7" in lines
    assert "# TYPE srtb_stage_seconds histogram" in lines
    # cumulative buckets with labels, +Inf bucket == count
    assert ('srtb_stage_seconds_bucket{le="0.01",stage="dispatch"} 0'
            in lines)
    assert ('srtb_stage_seconds_bucket{le="0.1",stage="dispatch"} 2'
            in lines)
    assert ('srtb_stage_seconds_bucket{le="+Inf",stage="dispatch"} 3'
            in lines)
    assert 'srtb_stage_seconds_count{stage="dispatch"} 3' in lines
    assert any(ln.startswith('srtb_stage_seconds_sum{stage="dispatch"}')
               for ln in lines)
    assert any(ln.startswith('srtb_samples_per_sec{window_s="10"}')
               for ln in lines)
    # every non-comment line is "name{labels} value" with a float value
    for ln in lines:
        if ln.startswith("#"):
            continue
        name_part, _, val = ln.rpartition(" ")
        assert name_part and float(val) == float(val)


def test_prometheus_help_type_conformance():
    """Exposition-format conformance: every family carries exactly one
    # HELP and one # TYPE line, HELP first, and all of a family's
    samples stay contiguous after its metadata (strict expfmt
    parsers reject re-opened families and samples before TYPE)."""
    m = Metrics()
    m.add("segments", 7)
    m.add("segments", 2, labels={"stream": "beam0"})
    m.add("custom_thing", 1)  # unknown family: generic HELP fallback
    m.add("only_labeled", 1, labels={"stream": "beam1"})
    m.histogram("stage_seconds", labels={"stage": "fetch"}).observe(0.1)
    m.window("samples", window_s=10.0).add(5)
    lines = m.prometheus().strip().split("\n")
    seen_help: dict[str, int] = {}
    seen_type: dict[str, int] = {}
    current = None
    families_order = []
    for ln in lines:
        if ln.startswith("# HELP "):
            name = ln.split()[2]
            seen_help[name] = seen_help.get(name, 0) + 1
            assert len(ln.split(" ", 3)) == 4 and ln.split(" ", 3)[3]
        elif ln.startswith("# TYPE "):
            name = ln.split()[2]
            seen_type[name] = seen_type.get(name, 0) + 1
            # HELP precedes TYPE for the same family
            assert seen_help.get(name) == seen_type[name]
            current = name
            families_order.append(name)
        else:
            sample = ln.split("{")[0].split(" ")[0]
            # a sample belongs to the most recently opened family
            # (histograms append _bucket/_sum/_count)
            assert sample == current or sample.startswith(
                current + "_"), (sample, current)
    # one HELP + one TYPE per family, no family opened twice
    assert seen_help == seen_type
    assert all(v == 1 for v in seen_type.values())
    assert len(families_order) == len(set(families_order))
    # known families get real help text, unknown the generic fallback
    text = "\n".join(lines)
    assert ("# HELP srtb_segments Segments drained end-to-end "
            "(lifetime)") in text
    assert "# HELP srtb_custom_thing srtb_tpu runtime metric" in text
    assert "# HELP srtb_only_labeled" in text
    assert "# HELP srtb_samples_per_sec" in text
    assert "# HELP srtb_stage_seconds" in text


def test_labeled_series_concurrent_with_scraper():
    """Satellite: fleet lanes hammer add/set(labels=) on one registry
    while a scraper snapshots — no torn reads, no lost updates, and
    the final totals are exact."""
    m = Metrics()
    n_threads, n_iter = 8, 2000
    stop = threading.Event()
    scrape_errors = []

    def scraper():
        while not stop.is_set():
            try:
                snap = m.snapshot()
                text = m.prometheus()
                # every rendered sample parses back as a float; the
                # labeled samples stay contiguous with their family
                for ln in text.strip().split("\n"):
                    if not ln.startswith("#"):
                        float(ln.rpartition(" ")[2])
                assert isinstance(snap, dict)
            except Exception as e:  # noqa: BLE001 - recorded, asserted
                scrape_errors.append(e)
                return

    def lane(i):
        labels = {"stream": f"beam{i % 4}"}
        for k in range(n_iter):
            m.add("segments_dropped", 1, labels=labels)
            m.add("segments_dropped", 1)  # flat twin
            m.set("inflight_depth", k % 5, labels=labels)

    threads = [threading.Thread(target=lane, args=(i,))
               for i in range(n_threads)]
    scr = threading.Thread(target=scraper)
    scr.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    scr.join()
    assert not scrape_errors, scrape_errors
    assert m.get("segments_dropped") == n_threads * n_iter
    per = m.by_label("segments_dropped")
    assert sum(per.values()) == n_threads * n_iter
    # 8 lanes over 4 stream labels: each label saw exactly 2 lanes
    assert set(per) == {f"beam{i}" for i in range(4)}
    assert all(v == 2 * n_iter for v in per.values())


def test_prometheus_includes_derived_series():
    """The derived scalars the JSON snapshot computes (loss rates,
    lifetime Msamples/s, elapsed) are exposed to Prometheus too — an
    alert written against either endpoint sees the other's values."""
    m = Metrics()
    m.add("samples", 2e6)
    m.add("packets_total", 100)
    m.add("packets_lost", 3)
    m.window("packets_total", window_s=60.0).add(100)
    m.window("packets_lost", window_s=60.0).add(3)
    text = m.prometheus()
    vals = {ln.rpartition(" ")[0]: float(ln.rpartition(" ")[2])
            for ln in text.strip().split("\n")
            if not ln.startswith("#")}
    assert abs(vals["srtb_packet_loss_rate"] - 0.03) < 1e-12
    assert abs(vals["srtb_packet_loss_rate_window"] - 0.03) < 1e-12
    assert "srtb_msamples_per_sec" in vals
    assert "srtb_elapsed_s" in vals
    snap = m.snapshot()
    assert abs(snap["packet_loss_rate_window"] - 0.03) < 1e-12


# ------------------------------------------------------------- journal


def test_span_journal_roundtrip_and_rotation(tmp_path):
    from srtb_tpu.tools import telemetry_report as TR
    from srtb_tpu.utils.telemetry import SpanJournal, segment_span

    path = str(tmp_path / "tele" / "journal.jsonl")
    with SpanJournal(path, max_bytes=1 << 20) as j:
        for i in range(3):
            j.write(segment_span(
                segment=i, stages_s={"ingest": 0.001, "dispatch": 0.01,
                                     "fetch": 0.1, "sink": 0.002},
                queue_depth=1, detections=i, dump=bool(i),
                samples=1 << 16, timestamp_ns=123))
    recs = TR.load(path)
    assert len(recs) == 3
    r = recs[-1]
    assert r["type"] == "segment_span" and r["v"] == 13
    assert r["segment"] == 2 and r["detections"] == 2 and r["dump"]
    assert r["samples"] == 1 << 16 and r["timestamp_ns"] == 123
    assert r["queue_depth"] == 1
    assert set(r["stages_ms"]) == {"ingest", "dispatch", "fetch", "sink"}
    assert r["stages_ms"]["fetch"] == 100.0
    assert "ts" in r and "packets_lost" in r
    # v12: the by-stream fields are omitted where the writer gave none,
    # and written as plain integers where it did
    assert "streams" not in r and "detections_by_stream" not in r
    two = segment_span(0, {"sink": 0.001}, 0, 5, True, 1,
                       detections_by_stream=np.array([0, 5]))
    assert two["streams"] == 2 and two["detections_by_stream"] == [0, 5]
    assert json.loads(json.dumps(two))["detections_by_stream"] == [0, 5]

    # rotation: a tiny cap forces the previous generation out — gzip'd
    # to <path>.1.gz by default; load() reads both transparently
    small = str(tmp_path / "rot.jsonl")
    with SpanJournal(small, max_bytes=1400) as j:
        for i in range(10):
            j.write(segment_span(i, {"sink": 0.001}, 0, 0, False, 1))
    rotated = TR.load(small)
    assert (tmp_path / "rot.jsonl.1.gz").exists()
    assert not (tmp_path / "rot.jsonl.1").exists()
    # the active file never exceeds the cap; the newest spans and the
    # previous generation both survive, oldest first
    assert (tmp_path / "rot.jsonl").stat().st_size <= 1400
    segs = [r["segment"] for r in rotated]
    assert segs and segs[-1] == 9 and segs == sorted(segs)

    # legacy plaintext rotation still available (compress=False), and
    # the reader handles it identically
    plain = str(tmp_path / "plain.jsonl")
    with SpanJournal(plain, max_bytes=1400, compress=False) as j:
        for i in range(10):
            j.write(segment_span(i, {"sink": 0.001}, 0, 0, False, 1))
    assert (tmp_path / "plain.jsonl.1").exists()
    segs = [r["segment"] for r in TR.load(plain)]
    assert segs and segs[-1] == 9 and segs == sorted(segs)


def test_span_journal_write_failure_disables_not_raises(tmp_path):
    """Telemetry must never abort the observation: an I/O failure on
    append disables the journal instead of propagating."""
    from srtb_tpu.utils.telemetry import SpanJournal, segment_span

    j = SpanJournal(str(tmp_path / "j.jsonl"), max_bytes=1 << 20)
    j.write(segment_span(0, {"sink": 0.001}, 0, 0, False, 1))

    class _Broken:
        def write(self, _):
            raise OSError(28, "No space left on device")

        def close(self):
            pass

    j._file = _Broken()
    j.write(segment_span(1, {"sink": 0.001}, 0, 0, False, 1))  # no raise
    assert j._file is None
    j.write(segment_span(2, {"sink": 0.001}, 0, 0, False, 1))  # no-op
    j.close()


def test_telemetry_report_stats_and_timeline(tmp_path):
    from srtb_tpu.tools import telemetry_report as TR

    path = tmp_path / "j.jsonl"
    t0 = 1000.0
    with open(path, "w") as f:
        for i in range(100):
            f.write(json.dumps({
                "type": "segment_span", "v": 1, "ts": t0 + i * 0.5,
                "segment": i,
                "stages_ms": {"dispatch": float(i + 1), "sink": 1.0},
                "queue_depth": 1, "detections": 1, "dump": i % 2 == 0,
                "samples": 1 << 20,
                "packets_total": 10.0 * (i + 1),
                "packets_lost": float(i // 50),
            }) + "\n")
    rep = TR.report(str(path), bin_s=10.0)
    assert rep["records"] == 100
    st = rep["stages"]["dispatch"]
    # exact percentiles of 1..100 ms
    assert st["count"] == 100
    assert abs(st["p50_ms"] - 50.5) < 1e-6
    assert abs(st["p99_ms"] - 99.01) < 0.02
    assert st["max_ms"] == 100.0
    assert rep["stages"]["sink"]["p50_ms"] == 1.0
    # synthetic whole-segment stage = sum of the record's stages
    assert rep["stages"]["segment"]["max_ms"] == 101.0
    tl = rep["timeline"]
    assert len(tl) == 5  # 100 records * 0.5 s over 10 s bins
    assert tl[0]["segments"] == 20
    assert abs(tl[0]["segments_per_sec"] - 2.0) < 1e-9
    assert abs(tl[0]["msamples_per_sec"]
               - 20 * (1 << 20) / 10.0 / 1e6) < 1e-3  # rounded to 3dp
    # cumulative counter 0 -> 1 at i=50: one unit of loss localized
    assert sum(b["packets_lost_delta"] for b in tl) == 1
    assert tl[2]["packets_lost_delta"] == 1  # the bin holding i=50
    # the final bin is partial (records end at 49.5 s): its rate uses
    # the covered 9.5 s, not the 10 s width — no phantom slowdown
    assert abs(tl[-1]["segments_per_sec"] - 20 / 9.5) < 1e-3
    # markdown rendering + main() exit codes
    md = TR._md(rep)
    assert "| dispatch |" in md and "Msamples/s" in md
    assert TR.main([str(path)]) == 0
    # an empty (freshly-rotated) journal is a NOTE, not an error: CI
    # artifact stages must not fail a healthy run that simply has not
    # drained a segment yet
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert TR.main([str(empty)]) == 0


def test_report_json_matches_md_sections(tmp_path, capsys):
    """Satellite: --format json is machine-readable with the SAME
    sections the text report renders — CI/dashboards must not scrape
    human tables."""
    from srtb_tpu.tools import telemetry_report as TR
    from srtb_tpu.utils.telemetry import SpanJournal, segment_span

    path = str(tmp_path / "j.jsonl")
    with SpanJournal(path) as j:
        for i in range(4):
            j.write(segment_span(
                i, {"ingest": 0.001, "dispatch": 0.01, "fetch": 0.02,
                    "sink": 0.002}, 1, i % 2, bool(i % 2), 1 << 16,
                overlap_hidden_s=0.005, inflight_depth=2,
                active_plan="four_step+ftail", stream="beam0",
                trace_id=i + 1))
    assert TR.main([path, "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    # every section of the dict report is present in the JSON output
    assert set(doc) == set(TR.report(path))
    assert set(doc) >= {"journal", "records", "stages", "overlap",
                        "resilience", "compute", "durability",
                        "fleet", "timeline"}
    assert doc["records"] == 4
    assert doc["stages"]["dispatch"]["count"] == 4
    assert doc["fleet"]["beam0"]["records"] == 4
    # and the md rendering consumes the identical dict
    md = TR._md(doc)
    assert "## Per-stage wall clock" in md and "| beam0 |" in md


def test_gzip_rotated_generation_reads_transparently(tmp_path):
    """Satellite: a .jsonl.gz previous generation (and a torn gzip
    tail) feed the report exactly like plaintext."""
    import gzip

    from srtb_tpu.tools import telemetry_report as TR

    path = str(tmp_path / "j.jsonl")
    with gzip.open(path + ".1.gz", "wt", compresslevel=1) as f:
        for i in range(3):
            f.write(json.dumps({"type": "segment_span", "v": 7,
                                "ts": 1000.0 + i, "segment": i,
                                "stages_ms": {"sink": 1.0},
                                "samples": 1}) + "\n")
    with open(path, "w") as f:
        f.write(json.dumps({"type": "segment_span", "v": 7,
                            "ts": 1003.0, "segment": 3,
                            "stages_ms": {"sink": 1.0},
                            "samples": 1}) + "\n")
    recs = TR.load(path)
    assert [r["segment"] for r in recs] == [0, 1, 2, 3]
    # torn gzip tail (crash mid-rotation): readable prefix survives
    raw = open(path + ".1.gz", "rb").read()
    open(path + ".1.gz", "wb").write(raw[:len(raw) - 8])
    recs = TR.load(path)
    assert recs and recs[-1]["segment"] == 3


def test_timeline_tail_record_no_rate_spike(tmp_path):
    """A record landing just past a bin boundary must not divide by an
    epsilon window: the mean inter-record gap floors the final bin's
    covered time, so the reported rate stays near the true one."""
    from srtb_tpu.tools import telemetry_report as TR

    path = tmp_path / "j.jsonl"
    with open(path, "w") as f:
        for ts in (1000.0, 1010.01):
            f.write(json.dumps({"type": "segment_span", "v": 1,
                                "ts": ts, "segment": 0,
                                "stages_ms": {"sink": 1.0},
                                "samples": 1}) + "\n")
    tl = TR.timeline(TR.load(str(path)), bin_s=10.0)
    assert len(tl) == 2
    # true rate ~0.1 seg/s; the naive covered-time (0.01 s) would say 100
    assert tl[-1]["segments_per_sec"] < 0.2


# ------------------------------------------------------------- healthz


def test_healthz_staleness(tmp_path):
    from srtb_tpu.gui.server import WaterfallHTTPServer

    metrics.reset()
    srv = WaterfallHTTPServer(str(tmp_path), port=0,
                              health_stale_after_s=5.0).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        # no segment yet: idle but healthy (startup must not page)
        h = json.loads(urllib.request.urlopen(base + "/healthz").read())
        assert h["status"] == "idle" and h["ok"]
        # fresh segment: ok with a small age
        telemetry.mark_segment()
        h = json.loads(urllib.request.urlopen(base + "/healthz").read())
        assert h["status"] == "ok" and h["last_segment_age_s"] < 5.0
        # age the stamp beyond the threshold: 503 + stale
        metrics.set(telemetry.LAST_SEGMENT_MONOTONIC,
                    time.monotonic() - 60.0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "stale"
    finally:
        srv.stop()
        metrics.reset()


def test_metrics_endpoint_serves_histograms(tmp_path):
    """/metrics speaks Prometheus including the per-stage histograms the
    pipeline feeds (acceptance: at least one histogram series with the
    stage names)."""
    from srtb_tpu.gui.server import WaterfallHTTPServer

    metrics.reset()
    metrics.histogram("stage_seconds",
                      labels={"stage": "dispatch"}).observe(0.01)
    srv = WaterfallHTTPServer(str(tmp_path), port=0).start()
    try:
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics").read().decode()
        assert "# TYPE srtb_stage_seconds histogram" in text
        assert 'srtb_stage_seconds_bucket{le="+Inf",stage="dispatch"} 1' \
            in text
    finally:
        srv.stop()
        metrics.reset()


# ---------------------------------------------------- pipeline e2e span


def test_pipeline_writes_segment_spans(tmp_path):
    """A CPU-backend synthetic run produces a journal whose spans carry
    the integrated StageTimer's per-stage wall clock, and the registry
    carries matching stage histograms + sliding-window rates."""
    from srtb_tpu.config import Config
    from srtb_tpu.io.synth import make_dispersed_baseband
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import telemetry_report as TR

    metrics.reset()
    n = 1 << 16
    data = make_dispersed_baseband(n * 2, 1405.0, 64.0, 0.0,
                                   pulse_positions=n // 2, nbits=8)
    path = str(tmp_path / "bb.bin")
    data.tofile(path)
    journal = str(tmp_path / "journal.jsonl")
    cfg = Config(
        baseband_input_count=n,
        baseband_input_bits=8,
        baseband_freq_low=1405.0,
        baseband_bandwidth=64.0,
        baseband_sample_rate=128e6,
        input_file_path=path,
        baseband_output_file_prefix=str(tmp_path / "out_"),
        spectrum_channel_count=1 << 8,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=False,
        writer_thread_count=0,
        telemetry_journal_path=journal,
    )
    with Pipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run(max_segments=2)
    assert stats.segments == 2
    # integrated StageTimer: totals surface on the stats object, with
    # exactly one ingest sample per segment (the terminal failed source
    # read is not recorded)
    assert set(stats.extras["stages"]) >= {"ingest", "dispatch",
                                           "fetch", "sink"}
    assert stats.extras["stages"]["ingest"]["count"] == 2
    recs = TR.load(journal)
    assert len(recs) == 2
    for rec in recs:
        # h2d and enqueue are child stages, timed inside dispatch;
        # ingest_wait is the loop's wait for a reader ahead, inside
        # ingest (0 here: the loop pulled by itself)
        assert set(rec["stages_ms"]) == {"ingest", "ingest_wait",
                                         "dispatch", "h2d", "enqueue",
                                         "fetch", "sink"}
        assert rec["stages_ms"]["ingest_wait"] == 0
        assert rec["ingest_ahead"] == 0
        assert all(v >= 0 for v in rec["stages_ms"].values())
        assert rec["samples"] == n
    assert [r["segment"] for r in recs] == [0, 1]
    # report parses it end to end
    rep = TR.report(journal)
    assert rep["records"] == 2
    assert rep["stages"]["dispatch"]["count"] == 2
    # registry: stage histograms + windowed rates + healthz stamp
    snap = metrics.snapshot()
    assert snap["segments"] == 2
    assert snap["stage_seconds_dispatch_count"] >= 2
    assert snap["segments_per_sec_10s"] > 0
    assert metrics.get(telemetry.LAST_SEGMENT_MONOTONIC) > 0
    prom = metrics.prometheus()
    for stage in ("ingest", "dispatch", "fetch", "sink"):
        assert f'srtb_stage_seconds_count{{stage="{stage}"}}' in prom
    metrics.reset()


def test_file_reader_ingest_gauges(tmp_path):
    """The file ingest path stamps windowed read throughput and pool
    occupancy gauges (the host-side ring-occupancy analog)."""
    from srtb_tpu.config import Config
    from srtb_tpu.io.file_input import BasebandFileReader
    from srtb_tpu.utils.bufferpool import BufferPool

    metrics.reset()
    path = tmp_path / "raw.bin"
    path.write_bytes(bytes(range(256)) * 16)
    cfg = Config(baseband_input_count=1 << 10, baseband_input_bits=8,
                 input_file_path=str(path),
                 baseband_reserve_sample=False)
    reader = BasebandFileReader(cfg, buffer_pool=BufferPool("t"))
    seg = next(reader)
    snap = metrics.snapshot()
    assert snap["file_bytes_read"] == 1 << 10
    assert snap["file_bytes_read_per_sec_10s"] > 0
    assert snap["segment_pool_in_use"] == 1
    assert snap["segment_pool_acquires"] == 1
    assert snap["segment_pool_new_blocks"] == 1
    reader.pool.release(seg.data)
    next(reader)
    snap = metrics.snapshot()
    assert snap["segment_pool_acquires"] == 2
    assert snap["segment_pool_new_blocks"] == 1
    reader.close()
    metrics.reset()
