"""Performance observatory (ISSUE 14): always-on device-time
accounting + live roofline gauges, the perf ledger/trajectory, the
noise-aware regression gate's statistics, and the on-demand
jax.profiler capture hook."""

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.utils import perf_ledger as PL
from srtb_tpu.utils import perf_stats as PS
from srtb_tpu.utils.metrics import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------- stats (satellite)


def test_clear_regression_flagged():
    """A 10% slowdown over a ~4%-noise distribution must be flagged:
    the Mann-Whitney p collapses, the bootstrap CI excludes zero, and
    the effect clears the computed floor."""
    rng = np.random.default_rng(7)
    a = rng.normal(1.00, 0.04, 30)
    b = rng.normal(1.10, 0.044, 30)
    v = PS.compare(a, b)
    assert v["regression"] and not v["improvement"], v
    assert v["p"] < 0.01 and v["ci_low"] > 0.0
    assert 0.05 < v["effect"] < 0.16


def test_small_shift_inside_noise_not_flagged():
    """A 1% shift inside a 4%-noise distribution is indistinguishable
    from sampling noise: the gate must NOT cry regression."""
    rng = np.random.default_rng(11)
    a = rng.normal(1.00, 0.04, 20)
    b = rng.normal(1.01, 0.04, 20)
    v = PS.compare(a, b)
    assert not v["regression"], v
    assert v["effect"] < v["threshold"] or v["p"] >= v["alpha"], v


def test_noise_floor_formalizes_the_4pct_eyeball():
    """With ~4%-sigma samples at the historical rep count (9), the
    computed floor lands in the same territory as PERF.md's hand
    ±4% — the constant was an okay eyeball, now derived."""
    rng = np.random.default_rng(3)
    a = rng.normal(1.0, 0.04, 9)
    b = rng.normal(1.0, 0.04, 9)
    floor = PS.noise_floor(a, b)
    assert 0.01 < floor < 0.10, floor
    # floor shrinks with more reps (sqrt-n), grows with scatter
    big = rng.normal(1.0, 0.04, 100)
    assert PS.noise_floor(big, big) < floor


def test_mann_whitney_identical_and_ties():
    u, p = PS.mann_whitney_u([1.0] * 10, [1.0] * 10)
    assert p == 1.0  # all ties: zero variance path, no false verdict
    _, p2 = PS.mann_whitney_u([1, 2, 3, 4, 5], [1, 2, 3, 4, 5])
    assert p2 > 0.5
    # an unambiguous separation
    _, p3 = PS.mann_whitney_u(list(range(10)), list(range(20, 30)))
    assert p3 < 0.001


def test_bootstrap_ci_deterministic_and_brackets_effect():
    rng = np.random.default_rng(5)
    a = rng.normal(1.0, 0.03, 25)
    b = rng.normal(1.2, 0.03, 25)
    ci1 = PS.bootstrap_effect_ci(a, b, seed=42)
    ci2 = PS.bootstrap_effect_ci(a, b, seed=42)
    assert ci1 == ci2  # seeded: verdicts reproduce
    assert ci1[0] < 0.2 < ci1[1] or abs(0.2 - ci1[1]) < 0.05


def test_improvement_symmetric():
    rng = np.random.default_rng(9)
    a = rng.normal(1.10, 0.03, 25)
    b = rng.normal(1.00, 0.03, 25)
    v = PS.compare(a, b)
    assert v["improvement"] and not v["regression"]


# ------------------------------------------------------- perf ledger


def test_ledger_roundtrip_and_record_fields(tmp_path):
    path = str(tmp_path / "led.jsonl")
    led = PL.PerfLedger(path)
    rec = PL.make_record("bench", 123.4, "Msamples/s", plan="p",
                         plan_signature="sig-blob",
                         shape={"log2n": 20}, platform="cpu",
                         samples_s=[0.1, 0.2],
                         extra={"k": 1})
    assert led.append(rec)
    out = PL.load(path)
    assert len(out) == 1
    r = out[0]
    assert r["value"] == 123.4 and r["source"] == "bench"
    assert r["plan_signature_sha"] == PL.signature_sha("sig-blob")
    assert len(r["plan_signature_sha"]) == 16
    assert r["host_fp"] == PL.host_fingerprint()
    assert r["samples_s"] == [0.1, 0.2]
    # foreign/torn lines tolerated
    with open(path, "a") as f:
        f.write('{"type": "other"}\nnot json\n{"type": "perf_rec')
    assert len(PL.load(path)) == 1


def _write_legacy_bench(tmp_path) -> str:
    """Five legacy-format bench-round files (one JSON object per round:
    n / cmd / rc / tail / parsed), two failed rounds and three measured
    ones — synthetic values, the shape of the retired per-round
    artifacts.  Returns the glob that matches them."""
    cmd = "python bench.py"
    for n, value in enumerate((None, None, 100.0, 150.0, 175.0), 1):
        doc = {"n": n, "cmd": cmd, "rc": 0 if value else 1,
               "tail": "" if value else "Traceback ..."}
        if value:
            doc["parsed"] = {
                "metric": "coherent_dedispersion_pipeline_throughput",
                "value": value, "unit": "Msamples/s/chip",
                "vs_baseline": value / 128.0, "platform": "tpu",
                "log2n": 27, "segment_time_s": 134.2 / value,
                "compile_s": 20.0, "roofline_frac": 0.05,
                "pass": True}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(doc))
    return str(tmp_path / "BENCH_r0*.json")


def test_legacy_bench_import_idempotent(tmp_path):
    """Legacy per-round bench artifacts import into the ledger, failed
    rounds included as value-0 outage records, and a re-import is a
    no-op."""
    from srtb_tpu.tools import perf_ledger as CLI
    path = str(tmp_path / "led.jsonl")
    pat = _write_legacy_bench(tmp_path)
    assert CLI.main([path, "--import", pat]) == 0
    recs = PL.load(path)
    assert len(recs) == len(glob.glob(pat)) == 5
    measured = [r for r in recs if r["value"] > 0]
    failed = [r for r in recs if r["value"] == 0]
    assert measured and failed  # the history holds both kinds
    assert all(r["source"] == "import" for r in recs)
    # provenance honesty: the importer's host/git must not be stamped
    assert all(r["host_fp"] == "" and r["git_sha"] == "" for r in recs)
    assert any(r["extra"].get("roofline_frac") for r in measured)
    # idempotent second import
    assert CLI.main([path, "--import", pat]) == 0
    assert len(PL.load(path)) == len(recs)


def test_perf_report_renders_trajectory(tmp_path, capsys):
    from srtb_tpu.tools import perf_ledger as CLI
    from srtb_tpu.tools import perf_report as PR
    path = str(tmp_path / "led.jsonl")
    CLI.main([path, "--import", _write_legacy_bench(tmp_path)])
    capsys.readouterr()
    assert PR.main([path, "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["records"] >= 4 and doc["groups"]
    # at least one measured group with a best value
    assert any(g["best"] > 0 for g in doc["groups"].values())
    md_rc = PR.main([path])
    md = capsys.readouterr().out
    assert md_rc == 0 and "# Perf trajectory" in md and "| when |" in md
    # empty ledger renders a clear note and exits 0 (dashboards
    # scrape before the first record lands)
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert PR.main([empty]) == 0
    out = capsys.readouterr().out
    assert "no perf records" in out


# ---------------------------------------------------------- the gate


def test_gate_cross_host_calibration():
    """A baseline from another host is rescaled by the calibration
    ratio and gated at the raised smoke-alarm floor."""
    from srtb_tpu.tools import perf_gate as PG
    base = {"samples_s": [1.0] * 16, "calib_s": 0.5, "host_fp": "aaaa"}
    cur = {"samples_s": [2.05] * 8 + [2.1] * 8, "calib_s": 1.0,
           "host_fp": "bbbb"}
    v = PG.gate(base, cur)
    # calib says this host is 2x slower: baseline scales to ~2.0 and
    # the ~3% residual sits far below the cross-host floor
    assert v["cross_host"] and v["calibration_scale"] == 2.0
    assert v["min_effect"] == PG.CROSS_HOST_MIN_EFFECT
    assert not v["regression"], v
    # a genuine 2x regression on top of calibration still fails
    cur2 = {"samples_s": [4.2] * 16, "calib_s": 1.0, "host_fp": "bbbb"}
    assert PG.gate(base, cur2)["regression"]
    # cross-host WITHOUT calibration is incomparable at any floor:
    # flagged, never a (guaranteed-false) verdict
    base_nocal = {"samples_s": [1.0] * 16, "host_fp": "aaaa"}
    v3 = PG.gate(base_nocal, cur2)
    assert v3["uncalibrated_cross_host"]
    assert not v3["regression"] and not v3["improvement"]


def test_stall_plan_uses_fault_machinery():
    from srtb_tpu.resilience.faults import FaultInjector
    from srtb_tpu.tools import perf_gate as PG
    plan = PG.stall_plan(segments=3, warmup=2, stall_s=0.05)
    inj = FaultInjector.from_plan(plan)
    assert inj is not None
    by_index = inj._by_site["dispatch"]
    assert sorted(by_index) == [2, 3, 4]
    assert all(s.action == "stall" and s.arg == 0.05
               for s in by_index.values())


def test_gate_selftest_proves_detection():
    """Acceptance: perf_gate --selftest — the injected dispatch stall
    fails the gate, the clean rerun passes inside the computed
    floor.  Run tiny so it fits the tier-1 budget."""
    from srtb_tpu.tools import perf_gate as PG
    rc = PG.main(["--selftest", "--segments", "10", "--warmup", "3",
                  "--log2n", "12", "--channels", "16"])
    assert rc == 0


def test_gate_baseline_roundtrip(tmp_path, capsys):
    """--write-baseline then --baseline on the same host: same code,
    same machine -> pass; and the capture lands in the ledger."""
    from srtb_tpu.tools import perf_gate as PG
    base = str(tmp_path / "base.json")
    led = str(tmp_path / "led.jsonl")
    args = ["--segments", "8", "--warmup", "2", "--log2n", "12",
            "--channels", "16"]
    assert PG.main(["--write-baseline", base] + args) == 0
    capsys.readouterr()
    rc = PG.main(["--baseline", base, "--ledger", led] + args)
    v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    if rc != 0:
        # clean/clean false-alarms with probability ~alpha/2 on a
        # loaded host — one independent recapture, the same bound the
        # gate's own selftest uses (a real regression fails both)
        rc = PG.main(["--baseline", base, "--ledger", led] + args)
        v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert not v["cross_host"] and v["calibration_scale"] == 1.0
    recs = PL.load(led)
    assert len(recs) >= 1 and recs[0]["source"] == "gate"
    assert len(recs[0]["samples_s"]) == 8


# ------------------------- device-time accounting + roofline gauges


def _obs_cfg(tmp_path, n, **kw):
    from srtb_tpu.io.synth import make_dispersed_baseband
    bb = str(tmp_path / "bb.bin")
    segs = kw.pop("segments", 3)
    make_dispersed_baseband(n * segs, 1405.0, 64.0, 0.0,
                            pulse_positions=n // 2,
                            nbits=8).tofile(bb)
    return Config(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=0.0, input_file_path=bb,
        baseband_output_file_prefix=str(tmp_path / "out_"),
        spectrum_channel_count=kw.pop("spectrum_channel_count", 32),
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=False, writer_thread_count=0, **kw)


@pytest.mark.parametrize("peak_known", [True, False])
def test_device_accounting_v8_spans_and_gauges(tmp_path, monkeypatch,
                                               peak_known):
    """Every drained segment of the async engine journals device_ms +
    achieved_msamps (v8) plus the cumulative compile/cache books, and
    the live gauges + device_seconds histogram land on /metrics — with
    per-stream labeled twins for a named lane.  roofline_frac rides
    along only where the device's kind is in the HBM peak table
    (utils/platform.HBM_PEAK_GBPS): on any other device — this CPU
    included — there is no span field and no gauge, never another
    chip's peak."""
    import jax

    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import telemetry_report as TR
    from srtb_tpu.utils import platform
    peak = 819.0
    kind = jax.devices()[0].device_kind
    assert platform.hbm_peak_gbps(kind) is None  # the CPU has no roof
    if peak_known:
        monkeypatch.setitem(platform.HBM_PEAK_GBPS, kind, peak)
    n = 1 << 13
    journal = str(tmp_path / "j.jsonl")
    cfg = _obs_cfg(tmp_path, n, segments=4, inflight_segments=2,
                   telemetry_journal_path=journal,
                   stream_name="beam7")
    metrics.reset()
    with Pipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    assert stats.segments == 4
    recs = TR.load(journal)
    assert len(recs) == 4
    for r in recs:
        assert r["v"] == 11
        assert r["device_ms"] > 0
        assert r["achieved_msamps"] > 0
        assert ("roofline_frac" in r) == peak_known
        assert r["aot_cache_hits"] == 0 and r["aot_cache_misses"] == 0
    # first dispatch = the run's one (lazy-jit) compile event, and the
    # named span carries the stream's OWN labeled books
    assert recs[-1]["plan_compiles"] == 1
    assert recs[-1]["compile_ms"] > 0
    assert metrics.get("plan_compiles",
                       labels={"stream": "beam7"}) == 1
    # device_ms is concurrent, never inside the host stage sum
    assert "device" not in recs[0]["stages_ms"]
    # live gauges + labeled twins
    for g in ("achieved_msamps", "achieved_gbps"):
        assert metrics.get(g) > 0
        assert metrics.get(g, labels={"stream": "beam7"}) > 0
    prom = metrics.prometheus()
    assert "# TYPE srtb_device_seconds histogram" in prom
    assert 'srtb_plan_compiles{stream="beam7"}' in prom
    assert ('srtb_roofline_frac{stream="beam7"}' in prom) == peak_known
    if peak_known:
        assert metrics.get("roofline_frac") > 0
        # roofline sanity: the gauge equals the plan-floor model over
        # the journaled device wall (lower-bound contract)
        proc = pipe.processor
        model_bytes = proc._segment_bytes + 8.0 * proc.n_spectrum \
            * proc.hbm_passes
        last = recs[-1]
        expect = model_bytes / (last["device_ms"] / 1e3) / 1e9 / peak
        assert abs(last["roofline_frac"] - expect) \
            < 0.05 * expect + 1e-4
    else:
        assert metrics.get("roofline_frac") == 0  # never set
    # report surfaces the device section
    rep = TR.report(journal)
    assert rep["device"]["records"] == 4
    assert rep["device"]["plan_compiles"] == 1
    md = TR._md(rep)
    assert "## Device time (performance observatory)" in md


def test_serial_device_time_is_exact_fetch_wall(tmp_path):
    """inflight_segments=1: device_ms is the dispatch->blocking-fetch
    wall — it must be >= the fetch stage and bounded by the segment's
    host wall + fetch (no queue-wait inflation in serial mode)."""
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import telemetry_report as TR
    n = 1 << 13
    journal = str(tmp_path / "j.jsonl")
    cfg = _obs_cfg(tmp_path, n, segments=3, inflight_segments=1,
                   telemetry_journal_path=journal)
    metrics.reset()
    with Pipeline(cfg, sinks=[]) as pipe:
        pipe.run()
    for r in TR.load(journal):
        assert r["device_ms"] >= r["stages_ms"]["fetch"] * 0.99
        # serial: nothing else runs between dispatch and fetch
        total = sum(r["stages_ms"].values())
        assert r["device_ms"] <= total + 50.0


def test_threaded_pipeline_omits_unmeasured_device_time(tmp_path):
    """ThreadedPipeline does not measure the dispatch->ready wall: its
    spans must OMIT device_ms (never journal a fake 0), while the
    compile/cache books still ride along."""
    from srtb_tpu.pipeline.runtime import ThreadedPipeline
    from srtb_tpu.tools import telemetry_report as TR
    n = 1 << 13
    journal = str(tmp_path / "j.jsonl")
    cfg = _obs_cfg(tmp_path, n, segments=3,
                   telemetry_journal_path=journal)
    metrics.reset()
    with ThreadedPipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    recs = TR.load(journal)
    assert len(recs) == stats.segments >= 2
    for r in recs:
        assert r["v"] == 11
        assert "device_ms" not in r and "roofline_frac" not in r
        assert "compile_ms" in r and "plan_compiles" in r


def test_aot_cache_hit_miss_counters(tmp_path, monkeypatch):
    """The AOT protocol's cache economics are counters now: a cold
    build records misses + exact compile seconds, a warm restart
    records hits and no new compile."""
    from srtb_tpu.pipeline.segment import SegmentProcessor
    monkeypatch.setenv("SRTB_AOT_ALLOW_CPU", "1")
    n = 1 << 12
    cfg = Config(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=0.0,
        spectrum_channel_count=16,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=False, fft_strategy="four_step",
        aot_plan_path=str(tmp_path / "aot"))
    metrics.reset()
    p1 = SegmentProcessor(cfg)
    assert p1.aot_active
    assert metrics.get("aot_cache_misses") >= 1
    assert metrics.get("aot_cache_hits") == 0
    assert metrics.get("compile_seconds") > 0
    compiles0 = metrics.get("plan_compiles")
    # warm restart: loads, compiles nothing
    p2 = SegmentProcessor(cfg)
    assert p2.aot_active
    assert metrics.get("aot_cache_hits") >= 1
    assert metrics.get("plan_compiles") == compiles0
    # an AOT-active first dispatch is NOT a lazy-jit compile event
    rng = np.random.default_rng(0)
    raw = rng.integers(0, 256, size=cfg.segment_bytes(1),
                       dtype=np.uint8)
    p2.process(raw)
    assert metrics.get("plan_compiles") == compiles0


def test_profile_capture_hook(tmp_path):
    """Config.profile_capture_segments records a real jax.profiler
    trace of the first N segments with a capture.json sidecar whose
    trace_ids join the journal spans."""
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import telemetry_report as TR
    n = 1 << 12
    cap = str(tmp_path / "prof")
    journal = str(tmp_path / "j.jsonl")
    cfg = _obs_cfg(tmp_path, n, segments=3, inflight_segments=1,
                   spectrum_channel_count=16,
                   telemetry_journal_path=journal,
                   profile_capture_segments=2,
                   profile_capture_dir=cap)
    metrics.reset()
    with Pipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    assert stats.segments == 3
    side = os.path.join(cap, "capture.json")
    if not os.path.exists(side):
        pytest.skip("jax.profiler unavailable on this backend")
    doc = json.load(open(side))
    assert doc["segments"] == 2
    assert doc["first_segment"] == 0 and doc["last_segment"] == 1
    # the sidecar's trace_ids are the journal's — the join key between
    # the device timeline and the causal-event/journal timeline
    recs = TR.load(journal)
    tids = [r.get("trace_id") for r in recs[:2]]
    assert [doc["first_trace_id"], doc["last_trace_id"]] == tids
    assert metrics.get("profile_captures") == 1
    # the capture wrote actual profiler artifacts next to the sidecar
    files = [f for _, _, fs in os.walk(cap) for f in fs
             if f != "capture.json"]
    assert files, "no profiler trace files written"


def test_steady_state_ledger_never_aborts_the_run(tmp_path):
    """An unwritable ledger path reduces to a warning: the run it was
    supposed to describe still completes and returns stats."""
    from srtb_tpu.pipeline.runtime import Pipeline
    n = 1 << 12
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("file, not a directory")
    cfg = _obs_cfg(tmp_path, n, segments=2, inflight_segments=1,
                   spectrum_channel_count=16,
                   perf_ledger_path=str(blocker / "led.jsonl"))
    metrics.reset()
    with Pipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    assert stats.segments == 2  # the record failed, the run did not


def test_steady_state_ledger_record(tmp_path):
    from srtb_tpu.pipeline.runtime import Pipeline
    n = 1 << 12
    led = str(tmp_path / "led.jsonl")
    cfg = _obs_cfg(tmp_path, n, segments=3, inflight_segments=2,
                   spectrum_channel_count=16, perf_ledger_path=led)
    metrics.reset()
    with Pipeline(cfg, sinks=[]) as pipe:
        stats = pipe.run()
    recs = PL.load(led)
    assert len(recs) == 1
    r = recs[0]
    assert r["source"] == "steady" and r["unit"] == "Msamples/s"
    assert r["extra"]["segments"] == stats.segments == 3
    assert r["shape"]["log2n"] == 12
    assert r["plan"] and r["plan_signature_sha"]


# --------------------------------------------------- bench satellite


def test_bench_uniform_compile_and_cache_fields(tmp_path):
    """Satellite: bench.py emits compile_ms (one semantics across AOT
    and lazy-jit protocols), the cache hit/miss/compile deltas, and
    per-rep samples — and --ledger lands the measurement in the perf
    ledger."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["SRTB_BENCH_LOG2N"] = "13"
    env["SRTB_BENCH_REPS"] = "4"
    led = str(tmp_path / "led.jsonl")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--overlap", "off", "--ledger", led],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads([ln for ln in out.stdout.strip().splitlines()
                      if ln.startswith("{")][-1])
    assert rec["compile_ms"] > 0
    # lazy-jit path on CPU: one first-dispatch compile, no AOT traffic
    assert rec["plan_compiles"] >= 1
    assert rec["aot_cache_hits"] == 0 and rec["aot_cache_misses"] == 0
    assert len(rec["rep_seconds"]) == 4
    assert all(s > 0 for s in rec["rep_seconds"])
    lrecs = PL.load(led)
    assert len(lrecs) == 1 and lrecs[0]["source"] == "bench"
    assert lrecs[0]["samples_s"] == rec["rep_seconds"]
    assert lrecs[0]["extra"]["overlap"] == "off"
