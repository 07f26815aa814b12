"""Noise-aware perf regression gate (the computed ±4%).

PERF.md's methodology was a hand-run paired A/B judged against an
eyeballed "±4% CPU noise floor".  This tool formalizes it:

- a **calibrated mini-bench** (:func:`capture`): a short serial
  pipeline run over a synthetic baseband file whose per-segment host
  wall clock (from the telemetry journal's span records, warmup
  dropped) yields *per-rep samples*, plus a fixed NumPy calibration
  workload that measures how fast this host is today;
- a **statistical verdict** (utils/perf_stats.py): Mann-Whitney over
  the two sample sets + a bootstrap CI of the median effect + a
  noise floor COMPUTED from the observed scatter — regression only
  when all three agree;
- a **checked-in baseline** protocol: ``--write-baseline`` captures
  samples + calibration on the reference host; ``--baseline`` re-runs
  the identical mini-bench and compares.  On a different host the
  baseline samples are rescaled by the calibration ratio and the
  required effect floor is raised (``CROSS_HOST_MIN_EFFECT``) —
  cross-host comparisons are smoke detection, not precision timing;
- ``--selftest`` proves the gate's teeth: a deterministic slowdown
  injected into the dispatch path via the existing ``Config.fault_plan``
  stall machinery MUST fail the gate, and a clean rerun MUST pass.

Exit codes: 0 pass, 1 regression (or selftest failure), 2 usage/error.

Usage:
  python -m srtb_tpu.tools.perf_gate --selftest
  python -m srtb_tpu.tools.perf_gate --write-baseline PERF_BASELINE.json
  python -m srtb_tpu.tools.perf_gate --baseline PERF_BASELINE.json \
      [--min-effect 0.5] [--ledger LEDGER.jsonl]
  python -m srtb_tpu.tools.perf_gate --a A.json --b B.json
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

from srtb_tpu.utils import perf_ledger as PL
from srtb_tpu.utils import perf_stats as PS

BASELINE_TYPE = "perf_baseline"
BASELINE_VERSION = 1
# a calibrated cross-host comparison carries scheduling/turbo/cache
# noise the within-host floor cannot see: require at least this much
# computed slowdown before failing CI on a different machine
CROSS_HOST_MIN_EFFECT = 0.5


def calibration_workload(reps: int = 5) -> float:
    """Median seconds of a fixed, deterministic NumPy workload (FFT +
    matmul over seeded data) — the "how fast is this host today"
    yardstick used to rescale baseline samples across hosts.  Runs
    the same bytes every time, everywhere."""
    rng = np.random.default_rng(1234)
    x = rng.standard_normal(1 << 16).astype(np.complex64)
    m = rng.standard_normal((256, 256)).astype(np.float32)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        y = np.fft.fft(x)
        z = m @ m
        s = float(np.abs(y).sum() + z.sum())
        times.append(time.perf_counter() - t0)
        assert math.isfinite(s)
    times.sort()
    return times[len(times) // 2]


def _mini_cfg(tmp: str, n: int, channels: int, fault_plan: str = ""):
    from srtb_tpu.config import Config
    journal = os.path.join(tmp, "gate_journal.jsonl")
    return Config(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=0.0,
        input_file_path=os.path.join(tmp, "gate_bb.bin"),
        baseband_output_file_prefix=os.path.join(tmp, "gate_out_"),
        spectrum_channel_count=channels,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=False, writer_thread_count=0,
        fft_strategy="four_step",
        # serial window: each sample is one segment's full host wall
        # clock with no overlap smearing — the honest A/B leg
        inflight_segments=1,
        telemetry_journal_path=journal,
        fault_plan=fault_plan)


def capture(segments: int = 20, warmup: int = 4, log2n: int = 13,
            channels: int = 32, fault_plan: str = "") -> dict:
    """Run the mini-bench once and return its sample set: per-segment
    host seconds (journal span stage sums, first ``warmup`` segments
    dropped — they carry trace/compile), the calibration time, and
    the identity fields a baseline needs."""
    from srtb_tpu.io.synth import make_dispersed_baseband
    from srtb_tpu.pipeline.runtime import Pipeline
    from srtb_tpu.tools import telemetry_report as TR
    from srtb_tpu.utils.telemetry import segment_wall
    from srtb_tpu.utils.metrics import metrics

    n = 1 << log2n
    total = segments + warmup
    with tempfile.TemporaryDirectory(prefix="srtb_perf_gate_") as tmp:
        cfg = _mini_cfg(tmp, n, channels, fault_plan=fault_plan)
        make_dispersed_baseband(
            n * total, 1405.0, 64.0, 0.0, pulse_positions=n // 2,
            nbits=8).tofile(cfg.input_file_path)
        metrics.reset()
        with Pipeline(cfg, sinks=[]) as pipe:
            stats = pipe.run()
            plan = getattr(pipe.processor, "plan_name", "")
            sig = pipe.processor.plan_signature()
        recs = TR.load(cfg.telemetry_journal_path)
    if stats.segments != total or len(recs) < total:
        raise RuntimeError(
            f"mini-bench expected {total} segments, drained "
            f"{stats.segments} with {len(recs)} journal spans")
    samples = [segment_wall(r.get("stages_ms") or {}) / 1e3
               for r in recs[warmup:]]
    return {
        "samples_s": samples,
        "calib_s": calibration_workload(),
        "host_fp": PL.host_fingerprint(),
        "git_sha": PL.git_sha(),
        "plan": plan,
        "plan_signature_sha": PL.signature_sha(sig),
        "shape": {"log2n": log2n, "channels": channels,
                  "segments": segments, "warmup": warmup},
    }


def stall_plan(segments: int, warmup: int, stall_s: float) -> str:
    """A deterministic uniform slowdown: one ``dispatch:stall`` fault
    entry per MEASURED segment (each fires exactly once), riding the
    existing fault-injection machinery — the injected regression
    travels the same guarded dispatch path a real one would."""
    return ",".join(f"dispatch:stall={stall_s:g}@{i}"
                    for i in range(warmup, warmup + segments))


def _load_samples(path: str) -> list[float]:
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, list):
        return [float(x) for x in doc]
    return [float(x) for x in doc["samples_s"]]


def gate(baseline: dict, current: dict, alpha: float = 0.05,
         min_effect: float = 0.0) -> dict:
    """Compare a captured baseline against a current capture.  When
    host fingerprints differ, baseline samples are rescaled by the
    calibration ratio and ``min_effect`` is raised to
    ``CROSS_HOST_MIN_EFFECT`` — the smoke-alarm mode."""
    a = list(baseline["samples_s"])
    cross_host = baseline.get("host_fp") != current.get("host_fp")
    scale = 1.0
    uncalibrated = False
    if cross_host:
        min_effect = max(min_effect, CROSS_HOST_MIN_EFFECT)
        if baseline.get("calib_s") and current.get("calib_s"):
            scale = current["calib_s"] / baseline["calib_s"]
            a = [s * scale for s in a]
        else:
            # raw samples from different-speed hosts are incomparable
            # at ANY floor: a 2x-slower host "regresses" by the host
            # ratio.  Flag it — main() refuses the verdict (exit 2)
            # instead of emitting a guaranteed-false one.
            uncalibrated = True
    verdict = PS.compare(a, current["samples_s"], alpha=alpha,
                         min_effect=min_effect)
    if uncalibrated:
        verdict["uncalibrated_cross_host"] = True
        verdict["regression"] = verdict["improvement"] = False
    verdict.update(cross_host=cross_host,
                   calibration_scale=round(scale, 4),
                   baseline_host=baseline.get("host_fp", ""),
                   current_host=current.get("host_fp", ""),
                   baseline_git=baseline.get("git_sha", ""),
                   current_git=current.get("git_sha", ""),
                   plan=current.get("plan", ""))
    return verdict


def _emit(obj) -> None:
    print(json.dumps(obj, sort_keys=True))
    sys.stdout.flush()


def _ledger_record(ledger_path: str, cap: dict, source: str) -> None:
    if not ledger_path:
        return
    samples = cap["samples_s"]
    med = float(np.median(samples))
    n = 1 << cap["shape"]["log2n"]
    rec = PL.make_record(
        source, n / med / 1e6, "Msamples/s",
        plan=cap["plan"], shape=cap["shape"],
        platform="cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
        else "", samples_s=samples,
        extra={"calib_s": cap["calib_s"]})
    # the capture already hashed the full signature — the ledger keys
    # comparability on it, so it must ride along
    rec["plan_signature_sha"] = cap.get("plan_signature_sha", "")
    PL.PerfLedger(ledger_path).append(rec)


def selftest(args) -> int:
    """Prove the gate has teeth AND doesn't bite clean runs:
    (1) two clean captures compare within the computed floor — pass;
    (2) a capture with a deterministic dispatch stall per measured
    segment (Config.fault_plan) must flag REGRESSION."""
    kw = dict(segments=args.segments, warmup=args.warmup,
              log2n=args.log2n, channels=args.channels)
    clean_a = capture(**kw)
    clean_b = capture(**kw)
    clean = gate(clean_a, clean_b, alpha=args.alpha)
    if clean["regression"]:
        # by construction a clean/clean comparison fails with
        # probability ~alpha/2 (plus real mid-run throttling on shared
        # CI): one independent recapture drops the flake rate to
        # ~(alpha/2)^2 while a GENUINE environment shift still fails
        # both legs
        clean_b = capture(**kw)
        clean = gate(clean_a, clean_b, alpha=args.alpha)
        clean["retried"] = True
    # stall sized from the clean median: unambiguous (~3x) without
    # wasting wall clock on big shapes
    stall_s = max(0.02, 2.0 * float(np.median(clean_a["samples_s"])))
    stalled = capture(fault_plan=stall_plan(args.segments, args.warmup,
                                            stall_s), **kw)
    slow = gate(clean_a, stalled, alpha=args.alpha)
    ok = (not clean["regression"]) and slow["regression"]
    _emit({"selftest": "ok" if ok else "FAILED",
           "clean": {k: clean[k] for k in
                     ("effect", "p", "noise_floor", "regression")},
           "stalled": {k: slow[k] for k in
                       ("effect", "p", "noise_floor", "regression")},
           "stall_s": stall_s,
           "detail": ("injected dispatch stall flagged, clean rerun "
                      "inside the computed floor" if ok else
                      "gate verdicts did not match expectations")})
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--baseline", help="checked-in baseline JSON to "
                                      "gate the current tree against")
    p.add_argument("--write-baseline", metavar="PATH",
                   help="capture the mini-bench and write a baseline")
    p.add_argument("--a", help="sample-set JSON (reference)")
    p.add_argument("--b", help="sample-set JSON (candidate)")
    p.add_argument("--selftest", action="store_true")
    p.add_argument("--alpha", type=float, default=0.05)
    p.add_argument("--min-effect", type=float, default=0.0,
                   help="extra required effect on top of the computed "
                        "noise floor (fractional, e.g. 0.5 = 50%%)")
    p.add_argument("--segments", type=int, default=20)
    p.add_argument("--warmup", type=int, default=4)
    p.add_argument("--log2n", type=int, default=13)
    p.add_argument("--channels", type=int, default=32)
    p.add_argument("--ledger", default="",
                   help="append captures to this perf ledger")
    args = p.parse_args(argv)

    try:
        if args.selftest:
            return selftest(args)
        if args.a and args.b:
            verdict = PS.compare(_load_samples(args.a),
                                 _load_samples(args.b),
                                 alpha=args.alpha,
                                 min_effect=args.min_effect)
            _emit(verdict)
            return 1 if verdict["regression"] else 0
        if args.write_baseline:
            cap = capture(segments=args.segments, warmup=args.warmup,
                          log2n=args.log2n, channels=args.channels)
            doc = {"type": BASELINE_TYPE, "v": BASELINE_VERSION,
                   "ts": time.time(), **cap}
            with open(args.write_baseline, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
                f.write("\n")
            _ledger_record(args.ledger, cap, "gate")
            _emit({"baseline": args.write_baseline,
                   "n_samples": len(cap["samples_s"]),
                   "median_s": float(np.median(cap["samples_s"])),
                   "calib_s": cap["calib_s"],
                   "host_fp": cap["host_fp"]})
            return 0
        if args.baseline:
            with open(args.baseline) as f:
                base = json.load(f)
            shape = base.get("shape") or {}
            cap = capture(
                segments=int(shape.get("segments", args.segments)),
                warmup=int(shape.get("warmup", args.warmup)),
                log2n=int(shape.get("log2n", args.log2n)),
                channels=int(shape.get("channels", args.channels)))
            _ledger_record(args.ledger, cap, "gate")
            verdict = gate(base, cap, alpha=args.alpha,
                           min_effect=args.min_effect)
            _emit(verdict)
            if verdict.get("uncalibrated_cross_host"):
                # a meaningless comparison is an ERROR, not a pass:
                # the baseline lacks calib_s on a different host
                return 2
            return 1 if verdict["regression"] else 0
        p.print_usage(sys.stderr)
        return 2
    except (OSError, ValueError, KeyError, RuntimeError) as e:
        _emit({"error": f"{type(e).__name__}: {e}"})
        return 2


if __name__ == "__main__":
    sys.exit(main())
