#!/bin/bash
# One-command reproduction of the verification this repo is judged by
# (L8 parity with the reference's CircleCI matrix,
# ref: /root/reference/.circleci/config.yml — there: 2 toolchains x 2
# arches of the SYCL build + ctest; here: native build + static checks +
# the full pytest suite on the virtual 8-device CPU mesh + the
# multichip dryrun smoke).
#
# Usage: ./ci.sh [--fast]   (--fast skips the slowest pytest cases)
set -euo pipefail
cd "$(dirname "$0")"

echo "== [1/21] native build =="
make -C srtb_tpu/native

echo "== [2/21] native sanitizer harness (ASan/UBSan) =="
make -C srtb_tpu/native check

echo "== [3/21] static checks (compile + import) =="
python -m compileall -q srtb_tpu tests __graft_entry__.py
python - <<'EOF'
import importlib, pkgutil
import srtb_tpu
bad = []
for m in pkgutil.walk_packages(srtb_tpu.__path__, "srtb_tpu."):
    try:
        importlib.import_module(m.name)
    except Exception as e:  # noqa: BLE001 - report every import failure
        bad.append((m.name, e))
assert not bad, bad
print(f"all srtb_tpu modules import cleanly")
EOF

echo "== [4/21] srtb-lint (static analysis vs baseline) =="
# fails on findings not in srtb_tpu/analysis/baseline.json; accept an
# intentional finding with --write-baseline + a note, or a pragma.
# The machine-readable run lands next to the other CI artifacts.
mkdir -p artifacts
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.lint srtb_tpu/ \
  --format json > artifacts/lint.json \
  || { cat artifacts/lint.json; exit 1; }

echo "== [5/21] plan audit (compile-time HLO cards vs baseline) =="
# AOT-lowers every plan family and audits the compiled artifacts:
# spectrum-sized HBM sweeps counted, donation proven aliased (not
# silently dropped), no f64/host-callback/collective creep.  Fails on any drift from
# srtb_tpu/analysis/plan_cards.json (accept intentional changes with
# --write-baseline + a note); the selftest then proves the gate still
# catches a dropped donation and an injected extra spectrum pass.
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.plan_audit \
  --out artifacts/plan_cards_audit.json
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.plan_audit --selftest

echo "== [6/21] pytest (8-device CPU mesh) =="
FAST_ARGS=()
if [ "${1:-}" = "--fast" ]; then
  # one source of truth for what "slow" means: the pytest marker
  # (registered in pyproject.toml), not a hardcoded deselect list
  FAST_ARGS=(-m "not slow")
fi
python -m pytest tests/ -q "${FAST_ARGS[@]}"

echo "== [7/21] fused-plan parity (spectrum-pass fusion, Pallas interpret on CPU) =="
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np

from srtb_tpu.config import Config
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.segment import SegmentProcessor, waterfall_to_numpy

n = 1 << 16
base = dict(baseband_input_count=n, baseband_input_bits=2,
            baseband_format_type="simple", baseband_freq_low=1405.0,
            baseband_bandwidth=64.0, baseband_sample_rate=128e6, dm=30.0,
            spectrum_channel_count=8,
            mitigate_rfi_average_method_threshold=25.0,
            mitigate_rfi_spectral_kurtosis_threshold=1.05,
            signal_detect_signal_noise_threshold=5.0,
            signal_detect_max_boxcar_length=8,
            baseband_reserve_sample=False, fft_strategy="four_step")
raw = make_dispersed_baseband(n, 1405.0, 64.0, 30.0,
                              pulse_positions=n // 2, pulse_amp=30.0,
                              nbits=2)

legacy = SegmentProcessor(Config(fused_tail="off", **base))
fused = SegmentProcessor(Config(fused_tail="on", use_pallas=True,
                                use_pallas_sk=True, **base))
assert not legacy.fused_tail and fused.fused_tail
assert fused._skzap and fused.plan_name.endswith("+ftail+skzap")
assert legacy.plan_signature() != fused.plan_signature()
wf_l, res_l = legacy.process(raw)
wf_f, res_f = fused.process(raw)
np.testing.assert_array_equal(np.asarray(res_l.signal_counts),
                              np.asarray(res_f.signal_counts))
np.testing.assert_array_equal(np.asarray(res_l.zero_count),
                              np.asarray(res_f.zero_count))
a, b = waterfall_to_numpy(wf_l), waterfall_to_numpy(wf_f)
scale = np.abs(a).max()
np.testing.assert_allclose(b, a, atol=1e-3 * scale, rtol=0)
print(f"fused-plan parity OK: plan {fused.plan_name} "
      "matches the legacy unfused chain, detections bit-identical")
EOF

echo "== [8/21] ring parity smoke (incremental H2D ring on vs off, Pallas interpret) =="
# The ISSUE-8 acceptance gate: ring-on output is bit-identical to
# ring-off on a Pallas-kernel plan (interpret mode on CPU), and the
# per-segment h2d_bytes counter equals the stride model exactly — the
# full segment on the one cold dispatch, stride_bytes (segment minus
# the reserved overlap tail) on every warm dispatch.  The plan-audit
# stage 5 already proved the carry donation is a real alias for
# every ring-v1 family; this proves the runtime keeps its half of the
# contract.
JAX_PLATFORMS=cpu python - <<'EOF'
import os, tempfile
import numpy as np

from srtb_tpu.config import Config
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.utils.metrics import metrics

tmp = tempfile.mkdtemp(prefix="srtb_ci_ring_")
n = 1 << 14
make_dispersed_baseband(n * 4, 1405.0, 64.0, 0.05, pulse_positions=n,
                        nbits=8).tofile(os.path.join(tmp, "bb.bin"))

class Cap:
    def __init__(self): self.out = []
    def push(self, w, p):
        d = w.detect
        self.out.append((np.asarray(d.signal_counts).copy(),
                         np.asarray(d.zero_count).copy(),
                         np.asarray(d.time_series).copy()))

def run(ring):
    metrics.reset()
    cfg = Config(baseband_input_count=n, baseband_input_bits=8,
                 baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                 baseband_sample_rate=128e6, dm=0.05,
                 input_file_path=os.path.join(tmp, "bb.bin"),
                 baseband_output_file_prefix=os.path.join(tmp, ring + "_"),
                 spectrum_channel_count=64,
                 mitigate_rfi_average_method_threshold=100.0,
                 mitigate_rfi_spectral_kurtosis_threshold=2.0,
                 baseband_reserve_sample=True, writer_thread_count=0,
                 fft_strategy="four_step", use_pallas=True,
                 inflight_segments=3, ingest_ring=ring)
    sink = Cap()
    with Pipeline(cfg, sinks=[sink]) as pipe:
        stats = pipe.run()
    h2d, cold = metrics.get("h2d_bytes"), metrics.get("ring_cold_dispatches")
    metrics.reset()
    return stats, sink, h2d, cold, pipe.processor

s_on, c_on, h_on, cold_on, proc = run("on")
s_off, c_off, h_off, cold_off, _ = run("off")
assert proc.ring and proc.plan_name.endswith("+ring"), proc.plan_name
assert s_on.segments == s_off.segments >= 4
for a, b in zip(c_on.out, c_off.out):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
seg_b, stride = proc._segment_bytes, proc.stride_bytes
assert h_on == seg_b + (s_on.segments - 1) * stride, (h_on, seg_b, stride)
assert h_off == s_off.segments * seg_b, h_off
assert cold_on == 1 and cold_off == 0, (cold_on, cold_off)
print(f"ring parity OK: plan {proc.plan_name}, {s_on.segments} segments "
      f"bit-identical; h2d ring-on {int(h_on)} B == cold {seg_b} + "
      f"{s_on.segments - 1} x stride {stride} (ring-off {int(h_off)} B; "
      f"saved {int(h_off - h_on)} B = reserved fraction "
      f"{proc.reserved_bytes / seg_b:.1%} per warm segment)")
EOF

echo "== [9/21] telemetry + sanitizer smoke (journal + report + /metrics + /healthz + Config.sanitize) =="
JAX_PLATFORMS=cpu python - <<'EOF'
import json, os, tempfile, urllib.request

from srtb_tpu.config import Config
from srtb_tpu.gui.server import WaterfallHTTPServer
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.tools import telemetry_report as TR

tmp = tempfile.mkdtemp(prefix="srtb_ci_tele_")
n = 1 << 16
make_dispersed_baseband(n * 3, 1405.0, 64.0, 0.0, pulse_positions=n,
                        nbits=8).tofile(os.path.join(tmp, "bb.bin"))
journal = os.path.join(tmp, "journal.jsonl")
cfg = Config(baseband_input_count=n, baseband_input_bits=8,
             baseband_freq_low=1405.0, baseband_bandwidth=64.0,
             baseband_sample_rate=128e6,
             input_file_path=os.path.join(tmp, "bb.bin"),
             baseband_output_file_prefix=os.path.join(tmp, "out_"),
             spectrum_channel_count=1 << 8,
             mitigate_rfi_average_method_threshold=100.0,
             mitigate_rfi_spectral_kurtosis_threshold=2.0,
             baseband_reserve_sample=False, writer_thread_count=0,
             inflight_segments=3,  # the async overlap engine
             telemetry_journal_path=journal)
with Pipeline(cfg, sinks=[]) as pipe:
    stats = pipe.run()
assert stats.segments >= 2, stats
# journal non-empty and parseable by telemetry_report
recs = TR.load(journal)
assert recs, "telemetry journal is empty"
# v8 span fields (async engine + resilience + perf observatory) on
# every record: device-time accounting + compile/cache
# books must ride every span, not just /metrics
for rec in recs:
    assert rec["v"] == 11, rec
    assert "overlap_hidden_ms" in rec and rec["inflight_depth"] >= 1, rec
    for key in ("degrade_level", "retries", "requeues", "restarts",
                "device_ms", "compile_ms", "plan_compiles",
                "aot_cache_hits", "aot_cache_misses"):
        assert key in rec, (key, rec)
    assert rec["device_ms"] > 0, rec
# the lazy-jit first dispatch was counted as the run's compile event
assert recs[-1]["plan_compiles"] >= 1 and recs[-1]["compile_ms"] > 0
rep = TR.report(journal)
for stage in ("ingest", "dispatch", "fetch", "sink", "overlap"):
    assert rep["stages"][stage]["count"] == stats.segments, (stage, rep)
assert rep["overlap"]["records"] == stats.segments, rep["overlap"]
assert TR.main([journal, "--format", "json"]) == 0
# live endpoints from a WaterfallHTTPServer
srv = WaterfallHTTPServer(tmp, port=0).start()
try:
    base = f"http://127.0.0.1:{srv.port}"
    prom = urllib.request.urlopen(base + "/metrics").read().decode()
    assert "# TYPE srtb_stage_seconds histogram" in prom, prom[:400]
    assert 'srtb_stage_seconds_bucket{le="+Inf",stage="dispatch"}' in prom
    assert 'srtb_stage_seconds_bucket{le="+Inf",stage="overlap"}' in prom
    assert "srtb_inflight_depth" in prom
    # perf-observatory families (ISSUE 14): device-time histogram,
    # compile/cache counters all scrapeable
    assert "# TYPE srtb_device_seconds histogram" in prom
    for fam in ("srtb_compile_seconds", "srtb_plan_compiles",
                "srtb_aot_cache_hits", "srtb_aot_cache_misses"):
        assert f"\n{fam} " in prom or prom.startswith(f"{fam} "), fam
    h = json.loads(urllib.request.urlopen(base + "/healthz").read())
    assert h["ok"] and h["status"] == "ok", h
finally:
    srv.stop()
print(f"telemetry smoke OK: {stats.segments} segments, "
      f"{len(recs)} v5 spans, overlap stage live, "
      "/metrics + /healthz live")

# one short pipeline with the runtime sanitizer armed: transfer
# tripwire + NaN tripwires + thread checks all live on a real run
import numpy as np
cfg_s = cfg.replace(sanitize=True, inflight_segments=2,
                    telemetry_journal_path="",
                    baseband_output_file_prefix=os.path.join(
                        tmp, "san_"))
with Pipeline(cfg_s, sinks=[]) as pipe:
    stats_s = pipe.run()
assert stats_s.segments == stats.segments, (stats_s, stats)
assert not hasattr(np.asarray, "_srtb_sanitize_orig"), \
    "sanitizer tripwire not restored"
print(f"sanitizer smoke OK: {stats_s.segments} segments with "
      "Config.sanitize on, tripwire restored")
EOF

echo "== [10/21] fault-injection smoke (one transient fault at every site -> recovery + v8 telemetry) =="
JAX_PLATFORMS=cpu python - <<'EOF'
import json, os, tempfile

import numpy as np

from srtb_tpu.config import Config
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.pipeline.segment import SegmentProcessor
from srtb_tpu.tools import telemetry_report as TR
from srtb_tpu.utils.metrics import metrics

tmp = tempfile.mkdtemp(prefix="srtb_ci_fault_")
n = 1 << 14
make_dispersed_baseband(n * 4, 1405.0, 64.0, 0.0, pulse_positions=n,
                        nbits=8).tofile(os.path.join(tmp, "bb.bin"))

def cfg(tag, **kw):
    return Config(baseband_input_count=n, baseband_input_bits=8,
                  baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                  baseband_sample_rate=128e6,
                  input_file_path=os.path.join(tmp, "bb.bin"),
                  baseband_output_file_prefix=os.path.join(tmp, tag),
                  spectrum_channel_count=1 << 8,
                  mitigate_rfi_average_method_threshold=100.0,
                  mitigate_rfi_spectral_kurtosis_threshold=2.0,
                  baseband_reserve_sample=False, writer_thread_count=0,
                  inflight_segments=2, retry_backoff_base_s=0.001, **kw)

class Cap:
    def __init__(self): self.out = []
    def push(self, w, p):
        d = w.detect
        self.out.append((np.asarray(d.signal_counts).copy(),
                         np.asarray(d.zero_count).copy()))

proc = SegmentProcessor(cfg("p_"))
metrics.reset()
clean = Cap()
with Pipeline(cfg("clean_"), sinks=[clean], processor=proc) as pipe:
    st0 = pipe.run()

metrics.reset()
plan = ("ingest:raise@1,h2d:raise@1,dispatch:raise@2,fetch:raise@2,"
        "sink_write:raise@3,checkpoint:raise@3")
faulted = Cap()
journal = os.path.join(tmp, "faults.jsonl")
with Pipeline(cfg("fault_", fault_plan=plan,
                  checkpoint_path=os.path.join(tmp, "ck.json"),
                  telemetry_journal_path=journal),
              sinks=[faulted], processor=proc) as pipe:
    st1 = pipe.run()
    assert pipe.faults.unfired() == [], pipe.faults.unfired()

# recovery: same segment count, bit-identical detections, no loss
assert st1.segments == st0.segments, (st1, st0)
for (a, b), (c, d) in zip(clean.out, faulted.out):
    np.testing.assert_array_equal(a, c)
    np.testing.assert_array_equal(b, d)
assert metrics.get("retries_total") == 6, metrics.get("retries_total")
assert metrics.get("segments_dropped") == 0
prom = metrics.prometheus()
assert "srtb_retries_total 6" in prom, prom[:400]
assert "srtb_faults_injected 6" in prom
# v3 journal fields + report resilience section
recs = TR.load(journal)
assert recs and all(r["v"] == 11 for r in recs)
# the checkpoint-site retry of the last segment lands after that
# segment's journal write: the final record carries 5 of the 6
assert recs[-1]["retries"] == 5 and recs[-1]["requeues"] == 0
rep = TR.report(journal)
assert rep["resilience"]["retries"] == 5, rep["resilience"]
print(f"fault-injection smoke OK: {st1.segments} segments recovered "
      "bit-identical through 6 injected faults, retries accounted in "
      "/metrics + v8 journal")
EOF

echo "== [11/21] chaos smoke (self-healing compute: oom + compile_fail + device_halt in one run) =="
# The ISSUE-9 acceptance gate: a deterministic fault plan injecting all
# three device-fault classes completes with accounted-only loss,
# detection decisions identical to the clean run, and the
# plan_demotions / device_reinits counters matching the injected plan
# EXACTLY; a clean run with the ladder armed is bit-identical to one
# without it (zero-cost off).  The selftest then proves the gate
# catches an unhandled fault class (an injected fatal, and a device
# fault with self-healing disabled).
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.chaos_soak --segments 6 \
  --plan "dispatch:oom@1,fetch:compile_fail@3,h2d:device_halt@5" \
  | tail -1
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.chaos_soak --selftest

echo "== [12/21] crash-soak smoke (SIGKILL exactly-once: manifest recovery + fsck + bit-identical union) =="
# The ISSUE-10 acceptance gate, CI-sized: a deterministic two-kill plan
# — one SIGKILL mid-checkpoint-flush (between sink commit and the
# checkpoint update, the duplicate-on-resume window) and one mid-
# sink-rename (orphan temp + uncommitted intent) — then recovery to
# completion.  Gate: fsck exits clean, the final output set is
# bit-identical (paths + SHA-256) to an uninterrupted golden run, the
# replay-skip and rollback paths both provably fired.  The fsck
# selftest then proves the verifier catches a forged WAL CRC, a
# deleted committed artifact, bit rot and a checkpoint ahead of the
# manifest.
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.crash_soak --segments 5 \
  --kills 2 --kill-plan "ckpt_stall@1,rename@1" --log2n 13 | tail -1
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.fsck --selftest

echo "== [13/21] multichip dryrun (8 virtual devices) =="
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "== [14/21] fleet smoke (multi-tenant bulkheads: 3 streams, 1 victim, shared plan cache) =="
# The ISSUE-11 acceptance gate, CI-sized: 3 seeded streams on one
# device, a stream-selector fault plan injected into stream0 (oom ->
# victim-only demotion, plus a transient sink fault and a fetch
# stall).  Gate: every healthy stream's output set (paths + SHA-256)
# bit-identical to its solo single-stream golden run, the victim's
# loss accounted-only with demotions attributed to its stream id in
# the v8 journal, and the shared AOT plan cache recording exactly ONE
# compile for the shared plan family.  The selftest then proves the
# gate catches cross-stream leakage (an UNSCOPED fault plan arming in
# every lane must FAIL the healthy-journal attribution check).
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.fleet_soak --streams 3 \
  --segments 4 --log2n 12 | tail -1
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.fleet_soak --selftest

echo "== [15/21] fleet-batch smoke (cross-tenant continuous batching: 4 streams, one shared dispatch) =="
# The ISSUE-17 acceptance gate, CI-sized: the round-15 fleet soak
# re-run with the batch former armed (fleet_batch_max=4).  Gate, on
# top of the bulkhead checks above: the v10 journal records batched
# dispatches with batch_size-weighted accounting that matches the
# batched_dispatches/batched_segments counters, the implied device
# dispatch count is <= segments/2 (amortization actually happened),
# the shared plan family still compiles exactly ONCE, outputs match
# the solo goldens (decisions + .bin bitwise, float artifacts within
# the documented vmap tolerance), and the victim exits its batch
# group without retiring its neighbours' programs.
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.fleet_soak --streams 4 \
  --segments 5 --log2n 12 --batch 4 | tail -1

echo "== [16/21] race-soak smoke (seeded schedule perturbation + lockdep, Config.tsan) =="
# The ISSUE-18 acceptance gate, CI-sized.  First the selftest: the
# lockdep layer must TRAP a deliberately injected lock-order inversion
# (and stay quiet on a consistent global order) — a soak that cannot
# catch a planted bug gates nothing.  Then the short deterministic
# soak: 2 streams, batch former armed, one injected fetch stall on the
# victim, with the SchedulePerturber injecting seeded sleeps at every
# instrumented lock acquisition (Config.tsan=1 on the fleet lanes
# only; the solo goldens stay canonical).  Gate: every fleet_soak
# invariant holds under perturbation (bit-identical healthy outputs /
# vmap tolerance, accounted-only victim loss, one shared compile), no
# deadlock within the deadline (on expiry: every live thread's stack
# + creation site), the perturbation journal replays exactly against
# a fresh perturber with the same seed, and no TsanError (order
# cycle / ownership violation) escaped the run.
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.race_soak --selftest
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.race_soak --streams 2 \
  --segments 4 --log2n 12 --batch 2 --seed 0 --deadline 240 | tail -1

echo "== [17/21] archive-replay smoke (full-throughput replay: SIGTERM resume + bit-identical union + micro-batch tolerance) =="
# The ISSUE-12 acceptance gate, CI-sized: a 2-file fleet-fanned replay
# (deterministic timestamps, per-file checkpoint + manifest namespaces)
# killed by a SIGTERM steered into one lane's sink-write window, then
# resumed to completion.  Gate: fsck-clean manifests, no orphan temps,
# the final output set (paths + SHA-256) BIT-IDENTICAL to per-file
# streamed golden runs, and the micro-batched throughput mode
# reproducing identical decisions (same artifact set, raw dumps
# bitwise, float artifacts within the documented vmap tolerance).
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.archive_replay --selftest \
  --segments 4 --log2n 13 | tail -1

echo "== [18/21] trace/incident smoke (causal tracing + flight recorder + bundle + Chrome-trace export) =="
# The ISSUE-13 acceptance gate, CI-sized: a clean traced run proves
# every segment leaves a complete ingest->dispatch->fetch->sink causal
# chain whose export is valid Chrome-trace JSON (schema-checked, flow
# arrows crossing the engine/sink thread boundary — no Perfetto needed
# in CI); then a seeded fault-plan escalation (oom -> one demotion ->
# ladder exhausted) must produce EXACTLY ONE incident bundle whose
# events hold the injected fault site, the device classification, the
# heal decision, the manifest disposition, and the offending trace_id.
JAX_PLATFORMS=cpu python - <<'EOF'
import json, os, tempfile

from srtb_tpu.config import Config
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.tools import trace_export as TE
from srtb_tpu.utils import events

tmp = tempfile.mkdtemp(prefix="srtb_ci_trace_")
n = 1 << 14
make_dispersed_baseband(n * 4, 1405.0, 64.0, 0.0, pulse_positions=n // 2,
                        pulse_amp=30.0, nbits=8).tofile(
    os.path.join(tmp, "bb.bin"))

def cfg(tag, **kw):
    return Config(baseband_input_count=n, baseband_input_bits=8,
                  baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                  baseband_sample_rate=128e6,
                  input_file_path=os.path.join(tmp, "bb.bin"),
                  baseband_output_file_prefix=os.path.join(tmp, tag),
                  spectrum_channel_count=1 << 6,
                  mitigate_rfi_average_method_threshold=100.0,
                  mitigate_rfi_spectral_kurtosis_threshold=2.0,
                  baseband_reserve_sample=False, writer_thread_count=0,
                  retry_backoff_base_s=0.001, **kw)

# leg 1: clean traced run -> valid Chrome-trace export with flows
dump = os.path.join(tmp, "events.jsonl")
with Pipeline(cfg("clean_", inflight_segments=3,
                  events_dump_path=dump), sinks=[]) as pipe:
    stats = pipe.run()
doc = TE.render(TE.load_events(dump))
problems = TE.validate(doc)
assert not problems, problems
slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
for stage in ("ingest", "dispatch", "fetch", "sink"):
    assert sum(1 for e in slices if e["name"] == stage) == stats.segments
starts = [e for e in doc["traceEvents"] if e["ph"] == "s"]
finishes = {e["id"]: e for e in doc["traceEvents"] if e["ph"] == "f"}
assert len(starts) == len(finishes) == stats.segments
assert all(s["tid"] != finishes[s["id"]]["tid"] for s in starts), \
    "flow must cross the engine->sink thread boundary"
assert TE.main([dump, "--validate"]) == 0

# leg 2: seeded escalation -> exactly one bundle, offending trace inside
from srtb_tpu.resilience.errors import LadderExhausted
inc = os.path.join(tmp, "incidents")
try:
    with Pipeline(cfg("esc_", inflight_segments=1,
                      fault_plan="dispatch:oom@1,fetch:oom@2",
                      plan_ladder="staged", device_reinit_max=0,
                      incident_dir=inc,
                      checkpoint_path=os.path.join(tmp, "ck.json"),
                      run_manifest_path=os.path.join(tmp, "m.wal"))) as pipe:
        pipe.run()
    raise AssertionError("escalation did not escalate")
except LadderExhausted:
    pass
bundles = [d for d in os.listdir(inc) if d.startswith("incident_")]
assert len(bundles) == 1, bundles
b = os.path.join(inc, bundles[0])
meta = json.load(open(os.path.join(b, "incident.json")))
assert meta["kind"] == "ladder_exhausted" and meta["trace_id"] > 0
evs = [json.loads(ln) for ln in open(os.path.join(b, "events.jsonl"))]
types = [e["type"] for e in evs]
assert types.count("fault.injected") == 2 and "heal.demote" in types
assert types.count("fault.device") == 2 and "manifest.ckpt" in types
tr = [json.loads(ln) for ln in open(os.path.join(b, "trace.jsonl"))]
assert tr and all(e["trace"] == meta["trace_id"] for e in tr)
# the bundle's recorder tail exports as valid Chrome-trace JSON too
assert TE.main([b, "--validate"]) == 0
print(f"trace/incident smoke OK: {stats.segments} traced segments "
      f"exported with {len(starts)} cross-thread flows; escalation "
      f"produced exactly one bundle ({bundles[0]}) carrying trace "
      f"{meta['trace_id']}")
EOF

echo "== [19/21] canary + quality smoke (pulse-injection sensitivity gate + quality report artifact) =="
# The ISSUE-16 acceptance gate, CI-sized.  Leg 1 (clean): a file-mode
# run with the canary on and the quality epilogue enabled must inject,
# recover, and PASS every sensitivity check (auto-calibrated expected
# S/N), journal v9 quality + canary extras, and keep the science
# outputs silent (canary segments quarantined).  Leg 2 (degraded): the
# same run with 61/64 channels zapped and the clean run's measured S/N
# pinned as the expectation must FAIL the sensitivity check, degrade
# detection health, and drop an incident bundle carrying the canary
# verdict + quality timeline.  The quality report renders both runs
# into the CI artifact set.
JAX_PLATFORMS=cpu python - <<'EOF'
import json, os, tempfile

import numpy as np

from srtb_tpu.config import Config
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.utils import telemetry
from srtb_tpu.utils.metrics import metrics

tmp = tempfile.mkdtemp(prefix="srtb_ci_canary_")
n, segments = 1 << 14, 4
rng = np.random.default_rng(7)
rng.normal(128, 8, n * segments).clip(0, 255).astype("uint8").tofile(
    os.path.join(tmp, "noise.bin"))

def cfg(tag, **kw):
    return Config(baseband_input_count=n, baseband_input_bits=8,
                  baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                  baseband_sample_rate=128e6, dm=0.0,
                  input_file_path=os.path.join(tmp, "noise.bin"),
                  baseband_output_file_prefix=os.path.join(tmp, tag),
                  spectrum_channel_count=1 << 6,
                  mitigate_rfi_average_method_threshold=100.0,
                  mitigate_rfi_spectral_kurtosis_threshold=2.0,
                  baseband_reserve_sample=False, writer_thread_count=0,
                  retry_backoff_base_s=0.001, inflight_segments=3,
                  quality_stats=True, canary_every_segments=2,
                  stream_name="ci",
                  telemetry_journal_path=os.path.join(
                      tmp, f"{tag}.jsonl"), **kw)

# leg 1: clean run -> every canary recovered, science outputs silent
with Pipeline(cfg("clean"), sinks=[]) as pipe:
    stats = pipe.run()
assert stats.segments == segments and stats.signals == 0
checked = metrics.get("canary_checked")
failed = metrics.get("canary_failed")
expected = metrics.get("canary_last_snr")
assert checked == 2 and failed == 0, (checked, failed)
assert expected > 5.0, expected
spans = [json.loads(ln) for ln in open(os.path.join(tmp, "clean.jsonl"))
         if ln.strip().startswith("{")]
spans = [r for r in spans if r.get("type") == "segment_span"]
assert all(r["v"] == 11 and "quality" in r for r in spans)
assert sum(1 for r in spans if "canary" in r) == 2
metrics.reset()

# leg 2: zap 61/64 channels out from under the pulse -> gate FAILS
inc = os.path.join(tmp, "incidents")
with Pipeline(cfg("deg", mitigate_rfi_freq_list="1405-1466",
                  canary_expected_snr=expected, incident_dir=inc,
                  incident_min_interval_s=0.0), sinks=[]) as pipe:
    pipe.run()
assert metrics.get("canary_failed") >= 1
assert metrics.get("detection_health_state") == 1
health = telemetry.health()
assert health["detection"]["state"] == "degraded"
bundles = [d for d in os.listdir(inc) if "canary_sensitivity" in d]
assert bundles, os.listdir(inc)
extra = json.load(open(os.path.join(inc, bundles[0], "extra.json")))
assert extra["canary"]["ok"] is False and extra["quality_timeline"]
with open("artifacts/canary_journal_path.txt", "w") as fh:
    fh.write(os.path.join(tmp, "clean.jsonl"))
print(f"canary smoke OK: clean run recovered S/N {expected:.2f} "
      f"({checked} checks, quarantined); degraded run failed the "
      f"sensitivity gate and produced {bundles[0]}")
EOF
# the science-observatory artifact: render the clean leg's journal
CANARY_JOURNAL=$(cat artifacts/canary_journal_path.txt)
python -m srtb_tpu.tools.quality_report "$CANARY_JOURNAL" \
  --format json > artifacts/quality_report.json
python -m srtb_tpu.tools.quality_report "$CANARY_JOURNAL" \
  > artifacts/quality_report.md
grep -q '"canary"' artifacts/quality_report.json
grep -q '## Canary' artifacts/quality_report.md

echo "== [20/21] migration smoke (elastic pool: scoped device kill + rolling restart, live migration bit-identical) =="
# The ISSUE-19 acceptance gate, CI-sized: 3 seeded streams placed
# across a 2-member VIRTUAL pool (distinct plan caches / halt domains
# on one CPU device).  Kill mode: a scheduled mid-run halt of member
# dev1 — its lanes drain-migrate onto the survivor instead of a
# fleet-wide reinit.  Gate: every victim resumes on the peer with its
# output set (paths + SHA-256) and decision taps bit-identical to the
# solo goldens, loss accounted-only, exactly ONE extra cold ring
# dispatch per migrated lane (ring_cold == streams + migrations),
# pool compiles == pool size (the migrant rejoins the survivor's
# family at rung 0 — zero healthy-lane demotions/recompiles),
# device_reinits == 0, and the v11 journals stamp every span with its
# device (victims end on a different member than they started).
# Rolling mode then drains BOTH members one at a time (the operator
# path), pacing each drain on the previous migrants' resumption.
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.fleet_soak --migrate \
  --streams 3 --segments 6 --log2n 12 --kill-device 1 --kill-at 2 \
  | tail -1
JAX_PLATFORMS=cpu python -m srtb_tpu.tools.fleet_soak --migrate \
  --rolling --streams 3 --segments 6 --log2n 12 --kill-at 2 | tail -1

echo "== [21/21] fleet control tower (aggregator + rollup store + cross-device trace join + console) =="
# The ISSUE-20 acceptance gate, CI-sized: re-run the 2-member virtual
# pool migration soak, then drive its three v11 journals + the flight
# recorder dump through the REAL tower path: aggregator -> rollup
# store (compaction byte-idempotent, cursor resume reads zero) ->
# cross-device Perfetto join (a migrated stream's lane flows span
# BOTH device process-tracks, same validate() gate as trace_export)
# -> /fleet endpoint + pool-aggregated /metrics + operator console.
JAX_PLATFORMS=cpu python - <<'EOF'
import glob, json, os, shutil, sys, urllib.request

OUT = "artifacts/obs"
shutil.rmtree(OUT, ignore_errors=True)
os.makedirs(OUT, exist_ok=True)

from srtb_tpu.tools.fleet_soak import run_migrate
os.makedirs(os.path.join(OUT, "migrate_run"), exist_ok=True)
rep = run_migrate(streams=3, segments=6, log2n=12, kill_device=1,
                  kill_at=2, tmpdir=os.path.join(OUT, "migrate_run"))
print("soak:", json.dumps({k: rep[k] for k in ("migrations", "device_drains")
                           if k in rep}))

from srtb_tpu.utils import events
ev_path = os.path.join(OUT, "events.jsonl")
n_ev = events.hub.dump_jsonl(ev_path)
assert n_ev > 0, "event dump empty"

journals = sorted(glob.glob(os.path.join(OUT, "migrate_run", "journal_*.jsonl")))
assert len(journals) == 3, journals

from srtb_tpu.obs.rollup import Aggregator
from srtb_tpu.obs.store import RollupStore
store_dir = os.path.join(OUT, "store")
store = RollupStore(store_dir)
agg = Aggregator(store, journals=journals, events_dumps=[ev_path])
got = agg.poll()
assert got["spans"] >= 18, got   # 3 streams x 6 segments
assert got["events"] > 0, got
agg.flush()
# idempotent compaction: byte-identical on re-run
store.compact()
def seg_bytes():
    return {n: open(os.path.join(store.segment_dir, n), "rb").read()
            for n in sorted(os.listdir(store.segment_dir))}
b1 = seg_bytes(); store.compact(); b2 = seg_bytes()
assert b1 == b2, "compaction not idempotent"
# resume cursor: a fresh aggregator re-reads nothing
agg2 = Aggregator(RollupStore(store_dir), journals=journals)
assert agg2.poll()["spans"] == 0, "cursor resume double-counted spans"
print(f"store OK: {got['spans']} spans, {got['events']} fleet events, "
      f"compaction idempotent, cursor resume clean")

from srtb_tpu.obs import trace_join
from srtb_tpu.tools.trace_export import validate
doc = trace_join.join([ev_path], journals)
problems = validate(doc)
assert not problems, problems
sd = doc["otherData"]["stream_devices"]
assert any(len(v) >= 2 for v in sd.values()), sd
with open(os.path.join(OUT, "fleet_trace.json"), "w") as f:
    json.dump(doc, f)
print(f"fleet trace OK: {len(doc['traceEvents'])} events, "
      f"stream_devices={json.dumps(sd)}")

from srtb_tpu.gui.server import WaterfallHTTPServer
srv = WaterfallHTTPServer(OUT, port=0, fleet_store_dir=store_dir).start()
try:
    base = f"http://127.0.0.1:{srv.port}"
    with urllib.request.urlopen(base + "/fleet", timeout=10) as r:
        fleet = json.loads(r.read().decode())
    assert fleet["devices"], fleet
    assert fleet["pool"]["migrations"] >= 1, fleet["pool"]
    assert fleet.get("store", {}).get("timeline"), "no migration timeline"
    with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
        prom = r.read().decode()
    assert "srtb_migrations_pool_sum" in prom, "pool aggregate family missing"
    assert "srtb_fleet_device_state_pool_max" in prom
    from srtb_tpu.tools import console
    assert console.main(["--url", base, "--once"]) == 0
finally:
    srv.stop()
print("console + /fleet + pool-aggregated /metrics OK")
EOF

echo "CI OK"
