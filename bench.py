"""Benchmark: sustained coherent-dedispersion pipeline throughput on one
chip, in the J1644-4559 configuration (2-bit samples, 128 MSa/s, |DM| =
478.80, inverted 64 MHz band — ref: srtb_config_1644-4559.cfg).

Prints ONE JSON line:
  {"metric": ..., "value": Msamples/s, "unit": ..., "vs_baseline": x, ...}
where vs_baseline is the real-time factor against the 128 MSa/s baseband
rate (BASELINE.md target: >= 1x real-time on a single v5e chip).

Runs on whatever platform ``JAX_PLATFORMS`` (or JAX's default) gives:
there is no probe and no fallback.  Every line names the device it ran
on (``platform``, ``device_kind``, ``device_count``); any failure is a
stack trace and a non-zero exit.

Extra emitted fields (roofline model, see PERF.md):
  model_gflops      — FFT-dominated FLOP count of one segment / 1e9
  achieved_gflops_s — model_gflops / measured time
  model_hbm_gb      — modeled HBM bytes moved per segment / 1e9
  achieved_gbps     — model_hbm_gb / measured time
  roofline_frac     — achieved_gbps / the device's HBM peak
                      (utils.platform.HBM_PEAK_GBPS, keyed by device_kind;
                      accelerator runs only, an unknown kind is an error)
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

def emit(obj) -> None:
    print(json.dumps(obj))
    sys.stdout.flush()


def device_facts():
    """(platform, device_kind, device_count, on_accel) as JAX reports
    them — stamped on every emitted line."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    return platform, devs[0].device_kind, len(devs), platform != "cpu"


def rate_unit(on_accel: bool) -> str:
    """A per-chip rate is only claimed on a chip."""
    return "Msamples/s/chip" if on_accel else "Msamples/s"


def roofline_model(n: int, channel_count: int, nbits: int,
                   hbm_passes: int = 7):
    """Static FLOP / HBM-byte model of one segment (documented in PERF.md).

    FFT work (5 m log2 m per length-m complex FFT, m = n/2 packed C2C):
    segment R2C + per-channel backward C2C; elementwise stages modeled at
    ~30 flops/bin.  HBM bytes: the input read plus ``hbm_passes``
    spectrum-sized sweeps — the *plan-dependent* traffic floor, taken
    from ``SegmentProcessor.hbm_passes`` (7 for the legacy chain: R2C
    read+write, RFI+chirp read+write, watfft read+write, SK+detect
    read; <= 4 for the fused plans that fold RFI/chirp into the R2C's
    final pass and SK/detect into the watfft write).  Computing the
    model from the per-plan count keeps ``roofline_frac`` honest: a
    fused plan is measured against its own smaller floor instead of
    being silently flattered by the legacy 7-pass model.
    """
    m = n // 2
    wlen = max(m // channel_count, 1)
    flops = 5.0 * m * math.log2(max(m, 2)) \
        + 5.0 * m * math.log2(max(wlen, 2)) \
        + 30.0 * m
    input_bytes = n * abs(nbits) / 8.0
    spectrum_bytes = 8.0 * m  # complex64
    bytes_moved = input_bytes + spectrum_bytes * hbm_passes
    return flops, bytes_moved


def baseline_pass(on_accel: bool, realtime_factor: float) -> bool:
    """The BASELINE.md gate (>= 1x real-time on one accelerator chip) as
    an explicit artifact field, so a perf regression cannot land looking
    green.  A CPU run is a fail by definition — the target names
    the chip."""
    return bool(on_accel and realtime_factor >= 1.0)


def parse_args(argv=None):
    """--overlap on|off: A/B legs for the async-dispatch overlap win.
    "on" (default, the historical timer semantics) dispatches all reps
    back to back and syncs once — per-dispatch host overhead hides under
    device compute, the way the runtime's in-flight engine streams.
    "off" is the serial reference leg: a blocking host sync after every
    segment, so the per-segment RTT lands in every segment."""
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--overlap", choices=("on", "off"), default="on")
    # fused spectrum tail A/B legs (Config.fused_tail): "on" forces the
    # epilogue-fused plans (requires a non-monolithic strategy, e.g.
    # SRTB_BENCH_FFT_STRATEGY=four_step), "off" the legacy 7-pass chain,
    # "auto" the plan's own resolution.  SRTB_BENCH_FUSED_TAIL is the
    # env spelling the queue scripts use.
    p.add_argument("--fused-tail", choices=("auto", "on", "off"),
                   default=os.environ.get("SRTB_BENCH_FUSED_TAIL", "auto"))
    # front-fused staged megakernel A/B legs (Config.front_fuse, the
    # staged_ffuse family): "on" forces the raw-bytes pass-1 + fused
    # pass-2-epilogue kernels (requires SRTB_BENCH_STAGED=1 +
    # SRTB_STAGED_ROWS_IMPL=pallas2), "off" the classic staged front,
    # "auto" the plan's own resolution.  SRTB_BENCH_FRONT_FUSE is the
    # env spelling the queue scripts use.
    p.add_argument("--front-fuse", choices=("auto", "on", "off"),
                   default=os.environ.get("SRTB_BENCH_FRONT_FUSE",
                                          "auto"))
    # incremental H2D ring A/B legs (Config.ingest_ring).  Both ring
    # legs upload bytes PER REP (the streaming pipeline's real transfer
    # pattern, with overlap-save reserving a tail): "on" re-uploads only
    # the stride through the warm assemble plan, "off" re-uploads the
    # full segment.  The default "none" keeps the historical
    # device-resident-input loop (no per-rep H2D, no reserve) so
    # headline rows stay comparable across rounds.  SRTB_BENCH_RING is
    # the env spelling the queue scripts use.
    p.add_argument("--ring", choices=("on", "off", "none"),
                   default=os.environ.get("SRTB_BENCH_RING", "none"))
    # cross-tenant continuous batching A/B legs (Config.fleet_batch_max,
    # pipeline/fleet._BatchFormer): instead of the solo processor loop,
    # run N same-shape streams through the fleet engine — "on" with the
    # batch former armed (fleet_batch_max=N), "off" with it disabled
    # (every segment its own dispatch).  The delta is the dispatch
    # amortization win.  SRTB_BENCH_FLEET_BATCH is the env spelling the
    # queue scripts use; SRTB_BENCH_FLEET_STREAMS / _FLEET_SEGMENTS
    # size the leg.
    p.add_argument("--fleet-batch", choices=("none", "on", "off"),
                   default=os.environ.get("SRTB_BENCH_FLEET_BATCH",
                                          "none"))
    # perf-ledger output (utils/perf_ledger.py): append this run's
    # measurement — value, per-rep seconds, plan signature hash, host
    # fingerprint, git sha — to the queryable trajectory.
    # SRTB_PERF_LEDGER is the env spelling the queue scripts use.
    p.add_argument("--ledger",
                   default=os.environ.get("SRTB_PERF_LEDGER", ""))
    return p.parse_args(argv)


def run_bench(overlap: str = "on",
              fused_tail: str = "auto", ring: str = "none",
              ledger: str = "", front_fuse: str = "auto"):
    import jax

    # FFTW-wisdom analog: reuse compiled programs across bench runs (the
    # staged 2^30 plan compiles for ~10 min cold, O(seconds) cached)
    from srtb_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from srtb_tpu.config import Config

    platform, device_kind, device_count, on_accel = device_facts()

    # J1644-4559 parameters (ref: srtb_config_1644-4559.cfg) at a segment
    # size that exercises the large-FFT path while fitting one chip.
    # SRTB_BENCH_* env knobs allow A/B runs of specific code paths
    # without changing the headline default.  A CPU run shrinks the
    # segment so a diagnostic line still lands within the driver's budget.
    default_log2n = "27" if on_accel else \
        os.environ.get("SRTB_BENCH_CPU_LOG2N", "21")
    n = 1 << int(os.environ.get("SRTB_BENCH_LOG2N", default_log2n))
    channels = 1 << int(os.environ.get("SRTB_BENCH_LOG2CHAN", "11"))
    cfg = Config(
        baseband_input_count=n,
        baseband_input_bits=2,
        baseband_format_type="simple",
        baseband_freq_low=1405.0 + 32.0,
        baseband_bandwidth=-64.0,
        baseband_sample_rate=128e6,
        # SRTB_BENCH_DM: the reserved fraction scales with |DM|, so the
        # ring legs use it both to fit small CI shapes (the production
        # DM reserves more than a 2^16 segment) and to push the
        # high-reserved-fraction legs where the ring saves the most
        dm=float(os.environ.get("SRTB_BENCH_DM", "-478.80")),
        spectrum_channel_count=channels,
        mitigate_rfi_average_method_threshold=1.5,
        mitigate_rfi_spectral_kurtosis_threshold=1.05,
        signal_detect_signal_noise_threshold=8.0,
        signal_detect_max_boxcar_length=256,
        mitigate_rfi_freq_list="1418-1422",
        # the ring legs measure overlap-save transfer traffic, so they
        # reserve the dedispersion tail (|DM| 478.80 reserves ~16% of a
        # 2^27 segment); the historical headline path keeps reserve off
        baseband_reserve_sample=(ring != "none"),
        ingest_ring=("on" if ring == "on" else "off"),
        fft_strategy=os.environ.get("SRTB_BENCH_FFT_STRATEGY", "auto"),
        use_pallas=bool(int(os.environ.get("SRTB_BENCH_USE_PALLAS", "0"))),
        use_pallas_sk=bool(int(os.environ.get("SRTB_BENCH_USE_PALLAS_SK",
                                              "0"))),
        fused_tail=fused_tail,
        front_fuse=front_fuse,
        # AOT executable cache A/B (utils/aot_cache): run the same
        # config twice with this set — the second run's compile_s is
        # the AOT warm-restart number
        aot_plan_path=os.environ.get("SRTB_BENCH_AOT_DIR", ""),
        # registered search mode (pipeline/registry.py):
        # SRTB_BENCH_SEARCH_MODE=periodicity benches the harmonic-sum
        # + folding plan family (the r8 queue's periodicity legs)
        search_mode=os.environ.get("SRTB_BENCH_SEARCH_MODE",
                                   "single_pulse"),
    )
    # "" = auto (staged at n >= 2^30); "0"/"1" force the plan — the
    # one-program 2^30 experiment (pallas2 has no XLA FFT scratch, so
    # the fused plan may fit where it used to OOM) needs the override
    staged_env = os.environ.get("SRTB_BENCH_STAGED", "")
    # segment bytes + H2D transfer are config-only: do them before any
    # timer so neither compile_s definition counts RNG or transfer time
    rng = np.random.default_rng(0)
    raw = rng.integers(0, 256, size=cfg.segment_bytes(1), dtype=np.uint8)
    raw_dev = jax.device_put(raw)

    # With SRTB_BENCH_AOT_DIR the compile (or the AOT load that replaces
    # it) happens inside SegmentProcessor.__init__, so compile_s must
    # start BEFORE construction for the aot_cold/aot_warm A/B to mean
    # anything.  Without it, keep the historical timer position (first
    # step only) so compile_s rows stay comparable with rounds 2-4 and
    # host-side constant building (chirp banks) isn't miscounted as
    # compile.
    t0 = time.perf_counter()
    # uniform compile accounting (perf observatory): ONE timer started
    # before construction for BOTH protocols — compile_ms covers
    # construction + warmup sync whether the compile happened inside
    # __init__ (AOT load-or-compile) or inside the first dispatch
    # (lazy jit), unlike the legacy compile_s whose start point
    # differs by path (kept below for row comparability with rounds
    # 2+).  The plan/AOT cache counters are metric deltas across the
    # same window.
    from srtb_tpu.utils.metrics import metrics as _metrics
    cache0 = {k: _metrics.get(k) for k in
              ("aot_cache_hits", "aot_cache_misses", "plan_compiles",
               "compile_seconds")}
    t_build = time.perf_counter()
    from srtb_tpu.pipeline import registry
    proc = registry.build_processor(
        cfg, staged=None if staged_env == "" else bool(int(staged_env)))
    # key the timer semantics on AOT actually ENGAGING, not merely being
    # requested: a silently-inactive cache (CPU without the opt-in) must
    # not produce AOT-protocol compile_s rows
    if not getattr(proc, "aot_active", False):
        t0 = time.perf_counter()

    # warmup / compile.  Sync via a host fetch of the (tiny) counts:
    # block_until_ready can return silently on an errored async
    # execution — the error only surfaces at value fetch,
    # and a bench that never fetches would time failures as ~0 s.
    # Ring legs warm BOTH carry-emitting programs (cold + warm assemble)
    # so compile_s covers what the measured loop dispatches.
    if ring == "on":
        (wf, res), carry0 = proc.run_device_cold(raw_dev)
        np.asarray(res.signal_counts)
        del wf, res
        (wf, res), carry0 = proc.run_device_ring(
            carry0, jax.device_put(raw[proc.reserved_bytes:]))
        np.asarray(res.signal_counts)
        del carry0
    else:
        wf, res = proc.run_device(raw_dev)
        np.asarray(res.signal_counts)
    compile_s = time.perf_counter() - t0
    compile_ms = (time.perf_counter() - t_build) * 1e3
    cache_delta = {k: _metrics.get(k) - cache0[k] for k in cache0}
    del wf, res  # a retained 4 GB waterfall would OOM the next 2^30 run

    # optional profiler capture of the steady state (xprof format)
    trace_dir = os.environ.get("SRTB_BENCH_TRACE_DIR", "")
    if trace_dir:
        from srtb_tpu.utils.tracing import device_trace
        with device_trace(trace_dir):
            wf, res = proc.run_device(raw_dev)
            np.asarray(res.signal_counts)
            del wf, res

    # Steady state: dispatch `reps` segments back to back and sync once.
    # This measures streaming throughput the way the runtime actually
    # streams (no host sync between segments); a per-segment host fetch
    # would add the per-dispatch host overhead to every segment.
    # Dropping each
    # waterfall handle right after dispatch lets its 4 GB free as soon
    # as its segment completes (2^30 would OOM otherwise).
    reps = int(os.environ.get("SRTB_BENCH_REPS", "5"))
    # the stride's "new" bytes for warm ring reps (length stride_bytes)
    raw_tail = raw[proc.reserved_bytes:] if ring == "on" else None
    h2d_host_s = 0.0
    h2d_bytes_total = 0
    t0 = time.perf_counter()
    last = None
    carry = None
    rep_seconds = []  # per-rep wall: REAL per-segment samples with
    # overlap off (each rep ends in a blocking sync); dispatch-issue
    # times with overlap on (the device sync lands after the loop) —
    # the regression gate should feed on overlap=off legs
    for _ in range(reps):
        t_rep = time.perf_counter()
        if ring == "none":
            wf, res = proc.run_device(raw_dev)
        elif ring == "on" and carry is not None:
            # warm: only the stride's new bytes cross the link; the
            # staging host time is what the async engine hides under
            # device compute (h2d_hidden_ms)
            th = time.perf_counter()
            new_dev = jax.device_put(raw_tail)
            h2d_host_s += time.perf_counter() - th
            h2d_bytes_total += raw_tail.nbytes
            (wf, res), carry = proc.run_device_ring(carry, new_dev)
        else:
            # ring off (full re-upload per segment, the streaming
            # pipeline's pre-ring transfer pattern) or the cold first
            # ring dispatch
            th = time.perf_counter()
            dev = jax.device_put(raw)
            h2d_host_s += time.perf_counter() - th
            h2d_bytes_total += raw.nbytes
            if ring == "on":
                (wf, res), carry = proc.run_device_cold(dev)
            else:
                wf, res = proc.run_device(dev)
        last = res.signal_counts
        del wf, res
        if overlap == "off":
            # serial reference leg (the runtime's inflight_segments=1
            # A/B twin): a blocking host sync per segment, so the
            # per-dispatch host overhead is paid every time
            np.asarray(last)
        rep_seconds.append(round(time.perf_counter() - t_rep, 5))
    np.asarray(last)
    del carry
    dt = (time.perf_counter() - t0) / reps

    samples_per_sec = n / dt
    msamples = samples_per_sec / 1e6
    realtime_factor = samples_per_sec / cfg.baseband_sample_rate
    flops, bytes_moved = roofline_model(n, channels,
                                        cfg.baseband_input_bits,
                                        hbm_passes=proc.hbm_passes)
    out = {
        "metric": "coherent_dedispersion_pipeline_throughput",
        "value": round(msamples, 2),
        "unit": rate_unit(on_accel),
        "vs_baseline": round(realtime_factor, 3),
        "platform": platform,
        "device_kind": device_kind,
        "device_count": device_count,
        "log2n": int(math.log2(n)),
        "segment_time_s": round(dt, 4),
        "compile_s": round(compile_s, 1),
        # uniform-semantics compile time (construction -> warmup sync,
        # both AOT and lazy-jit protocols) + the cache/compile counter
        # deltas over the same window — every line now says whether
        # its compile was a cache hit, a miss, or a lazy first
        # dispatch, identically across protocols
        "compile_ms": round(compile_ms, 1),
        "aot_cache_hits": int(cache_delta["aot_cache_hits"]),
        "aot_cache_misses": int(cache_delta["aot_cache_misses"]),
        "plan_compiles": int(cache_delta["plan_compiles"]),
        "rep_seconds": rep_seconds,
        "model_gflops": round(flops / 1e9, 1),
        "achieved_gflops_s": round(flops / dt / 1e9, 1),
        "model_hbm_gb": round(bytes_moved / 1e9, 3),
        "achieved_gbps": round(bytes_moved / dt / 1e9, 1),
        "overlap": overlap,
        # per-plan traffic model inputs (spectrum-pass fusion): the plan
        # that actually ran and its modeled spectrum-sweep count, so
        # every artifact line is self-describing about which floor its
        # roofline_frac was computed against
        "plan": proc.plan_name,
        "hbm_passes": proc.hbm_passes,
        "fused_tail": "on" if proc.fused_tail else "off",
        "front_fuse": "on" if getattr(proc, "front_fuse", False)
        else "off",
        "ring": ring,
        "search_mode": proc.MODE,
    }
    if ring != "none":
        # H2D accounting (PERF.md "H2D accounting"): average uploaded
        # bytes per segment (stride model: one cold full segment, then
        # stride_bytes per warm rep) and the host wall time spent
        # staging them — hidden under device compute with overlap on,
        # serialized into every segment with overlap off
        out["h2d_gb"] = round(h2d_bytes_total / reps / 1e9, 4)
        out["h2d_hidden_ms"] = round(h2d_host_s / reps * 1e3, 2)
        out["reserved_frac"] = round(
            proc.reserved_bytes / proc._segment_bytes, 3)
    if int(os.environ.get("SRTB_BENCH_AUDIT", "0")):
        # Roofline cross-check against the compile-time HLO plan
        # auditor (srtb_tpu/analysis/hlo_audit.py): the measured plan's
        # OWN compiled artifacts are re-lowered and their structural
        # spectrum-sized sweeps counted, so the two HBM accountings —
        # model_hbm_gb (the hbm_passes floor model above) and the
        # audited artifact traffic — cite each other in one line.
        # Opt-in (it compiles the plan a second time): ci.sh's bench
        # smoke sets it; big-n TPU headline runs leave it off.
        from srtb_tpu.analysis import hlo_audit as HA
        card = HA.audit_processor(proc)
        spectrum_bytes = 8.0 * proc.n_spectrum
        audited_bytes = raw.nbytes \
            + card["total_spectrum_passes"] * spectrum_bytes
        out["audit_spectrum_passes"] = card["total_spectrum_passes"]
        out["audit_hbm_gb"] = round(audited_bytes / 1e9, 3)
        out["audit_checks_ok"] = not HA.failed_checks({"bench": card})
        # the model is a FLOOR of the artifact's structural traffic: a
        # model claiming >10% more bytes than the audited sweeps means
        # the hbm_passes declaration went stale (e.g. a fusion landed
        # without lowering the declared floor) and achieved_gbps /
        # roofline_frac are being flattered
        if bytes_moved > 1.1 * audited_bytes:
            out["audit_warning"] = (
                f"model_hbm_gb {out['model_hbm_gb']} exceeds audited "
                f"artifact traffic {out['audit_hbm_gb']} by >10% — "
                "hbm_passes floor is stale for this plan")
            print(f"bench: WARNING: {out['audit_warning']}",
                  file=sys.stderr)
    if cfg.aot_plan_path:
        # whether the AOT executable cache actually engaged — the
        # queue's aot_cold/aot_warm verdicts require this to be true
        out["aot_active"] = bool(getattr(proc, "aot_active", False))
    if on_accel:
        # only meaningful against the accelerator's own HBM peak: a CPU
        # run has no roofline, and an accelerator kind that is not in
        # the peak table is an error, never another chip's peak
        from srtb_tpu.utils.platform import hbm_peak_gbps
        peak = hbm_peak_gbps(device_kind)
        if peak is None:
            raise SystemExit(
                f"bench: no HBM peak known for device_kind "
                f"{device_kind!r} (srtb_tpu/utils/platform.py "
                "HBM_PEAK_GBPS) — cannot state a roofline share")
        out["roofline_frac"] = round(bytes_moved / dt / 1e9 / peak, 3)
    out["pass"] = baseline_pass(on_accel, realtime_factor)
    if ledger:
        try:
            from srtb_tpu.utils import perf_ledger as PL
            extra = {k: out[k] for k in
                     ("overlap", "ring", "hbm_passes", "fused_tail",
                      "front_fuse", "compile_s", "compile_ms",
                      "roofline_frac", "achieved_gbps", "vs_baseline",
                      "search_mode")
                     if k in out}
            PL.PerfLedger(ledger).append(PL.make_record(
                "bench", out["value"], out["unit"],
                plan=proc.plan_name,
                plan_signature=proc.plan_signature(),
                shape={"log2n": out["log2n"], "channels": channels,
                       "nbits": cfg.baseband_input_bits},
                platform=platform, samples_s=rep_seconds,
                extra=extra))
        except Exception as e:  # the artifact line must still land
            print(f"bench: WARNING: perf-ledger append failed: {e}",
                  file=sys.stderr)
    emit(out)


def run_fleet_bench(leg: str, ledger: str = ""):
    """The --fleet-batch A/B leg: N same-shape streams through the
    fleet engine, batch former armed ("on", fleet_batch_max=N) or
    disabled ("off").  Emits ONE JSON line with the aggregate
    throughput plus the batching counters (batched_dispatches,
    batched_segments, mean batch_size, implied device dispatches), so
    the on/off delta reads directly as dispatch amortization."""
    import tempfile

    import jax

    from srtb_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from srtb_tpu.config import Config
    from srtb_tpu.pipeline.fleet import StreamFleet, StreamSpec
    from srtb_tpu.utils.metrics import metrics

    platform, device_kind, device_count, on_accel = device_facts()
    default_log2n = "21" if on_accel else \
        os.environ.get("SRTB_BENCH_CPU_LOG2N", "16")
    n = 1 << int(os.environ.get("SRTB_BENCH_LOG2N", default_log2n))
    channels = 1 << int(os.environ.get("SRTB_BENCH_LOG2CHAN", "11"))
    streams = max(2, int(os.environ.get("SRTB_BENCH_FLEET_STREAMS",
                                        "4")))
    segments = max(1, int(os.environ.get("SRTB_BENCH_FLEET_SEGMENTS",
                                         "6")))
    reps = int(os.environ.get("SRTB_BENCH_REPS", "3"))
    batch_max = streams if leg == "on" else 0

    tmp = tempfile.mkdtemp(prefix="srtb_fleet_bench_")
    rng = np.random.default_rng(0)

    def stream_cfg(i: int) -> Config:
        # the J1644 shape (2-bit, inverted band) shared across all
        # streams — one plan family, the batchable case.  Reserve off:
        # the leg measures dispatch amortization, not overlap-save.
        path = os.path.join(tmp, f"bb{i}.bin")
        if not os.path.exists(path):
            rng.integers(0, 256, size=(n * 2 // 8) * segments,
                         dtype=np.uint8).tofile(path)
        return Config(
            baseband_input_count=n,
            baseband_input_bits=2,
            baseband_format_type="simple",
            baseband_freq_low=1405.0 + 32.0,
            baseband_bandwidth=-64.0,
            baseband_sample_rate=128e6,
            dm=float(os.environ.get("SRTB_BENCH_DM", "-478.80")),
            spectrum_channel_count=channels,
            mitigate_rfi_average_method_threshold=1.5,
            mitigate_rfi_spectral_kurtosis_threshold=1.05,
            signal_detect_signal_noise_threshold=8.0,
            signal_detect_max_boxcar_length=256,
            mitigate_rfi_freq_list="1418-1422",
            input_file_path=path,
            stream_name=f"bb{i}",
            fft_strategy=os.environ.get("SRTB_BENCH_FFT_STRATEGY",
                                        "auto"),
            fleet_batch_max=batch_max,
        )

    def one_rep() -> tuple:
        metrics.reset()
        specs = [StreamSpec(name=f"bb{i}", cfg=stream_cfg(i),
                            keep_waterfall=False)
                 for i in range(streams)]
        t0 = time.perf_counter()
        fleet = StreamFleet(specs)
        results = fleet.run()
        fleet.close()
        dt = time.perf_counter() - t0
        drained = sum(r.drained for r in results.values())
        return dt, drained, \
            int(metrics.get("batched_dispatches")), \
            int(metrics.get("batched_segments"))

    # rep 1 pays the (shared) compile; the reported value is the
    # median of all reps, with per-rep seconds in the artifact so a
    # cold first rep is visible, not hidden
    rep_out = [one_rep() for _ in range(reps)]
    rep_seconds = [round(dt, 5) for dt, _, _, _ in rep_out]
    dt, drained, bdisp, bsegs = sorted(rep_out)[len(rep_out) // 2]
    seg_s = drained / dt if dt else 0.0
    msamples = seg_s * n / 1e6
    device_dispatches = drained - bsegs + bdisp
    out = {
        "metric": "fleet_batched_throughput",
        "value": round(msamples, 2),
        "unit": rate_unit(on_accel),
        "vs_baseline": round(seg_s * n / 128e6, 3),
        "platform": platform,
        "device_kind": device_kind,
        "device_count": device_count,
        "fleet_batch": leg,
        "fleet_batch_max": batch_max,
        "streams": streams,
        "segments_per_stream": segments,
        "log2n": int(math.log2(n)),
        "drained": drained,
        "elapsed_s": round(dt, 3),
        "rep_seconds": rep_seconds,
        "batched_dispatches": bdisp,
        "batched_segments": bsegs,
        "batch_size_mean": round(bsegs / bdisp, 2) if bdisp else 0.0,
        "device_dispatches": device_dispatches,
        "pass": True,
    }
    if ledger:
        try:
            from srtb_tpu.utils import perf_ledger as PL
            PL.PerfLedger(ledger).append(PL.make_record(
                "fleet_bench", out["value"], out["unit"],
                plan=f"fleet_batch_{leg}",
                shape={"log2n": out["log2n"], "channels": channels,
                       "nbits": 2, "streams": streams},
                platform=platform, samples_s=rep_seconds,
                extra={k: out[k] for k in
                       ("fleet_batch", "fleet_batch_max",
                        "batched_dispatches", "batched_segments",
                        "batch_size_mean", "device_dispatches",
                        "drained")}))
        except Exception as e:  # the artifact line must still land
            print(f"bench: WARNING: perf-ledger append failed: {e}",
                  file=sys.stderr)
    emit(out)


def main():
    args = parse_args()
    if args.fleet_batch != "none":
        run_fleet_bench(leg=args.fleet_batch, ledger=args.ledger)
    else:
        run_bench(overlap=args.overlap, fused_tail=args.fused_tail,
                  ring=args.ring, ledger=args.ledger,
                  front_fuse=args.front_fuse)


if __name__ == "__main__":
    main()
