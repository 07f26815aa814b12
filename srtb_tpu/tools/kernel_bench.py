"""Per-kernel micro-benchmarks.

The reference publishes exactly one set of kernel timings: its GUI
resample kernel (``resample_spectrum_3``, one work-group per output
pixel) at wg=64 takes ~16.6 ms on an AMD Radeon VII and ~59.9 ms on an
NVIDIA RTX A4000 (ref: spectrum/simplify_spectrum.hpp:449-455).  This
tool times the srtb_tpu equivalents — the resample-as-two-matmuls MXU
formulation plus the other hot kernels — with the same methodology as
bench.py (compile once, min over repeats, block_until_ready).

Usage:
    python -m srtb_tpu.tools.kernel_bench [--log2n 28] [--reps 5]

Prints one JSON line per kernel:
    {"kernel": ..., "ms": ..., "shape": ..., "gsamples_per_s": ...}

Each bench case intentionally builds a fresh jitted lambda: the case IS
the compile+run cycle being measured, and every lambda is jitted once
then timed over repeats — the per-call-recompile hazard srtb-lint
flags does not apply to this harness.
"""
# srtb-lint: disable-file=recompile-hazard (bench harness: one jit per
# case by design, see docstring)

from __future__ import annotations

import argparse
import json
import time

import numpy as np


# Iterations of the on-device timing loop per host sync.  Per-dispatch
# host overhead is enough to bury a sub-millisecond kernel.  The
# timer therefore runs INNER_ITERS executions inside one jitted
# lax.scan, each iteration's input carrying a data dependency on the
# previous output (defeats any client-side pipelining or dedup), and
# pays one host fetch per measurement.
_INNER_ITERS = 16


def _time(fn, *args, reps=5):
    """Best-of-reps mean kernel time over a dependency-chained on-device
    loop.  The chaining adds one read+write copy of args[0] per
    iteration — a known, stated bias (e.g. +~1.3 ms for a 512 MB input
    at HBM speed), far smaller than the ~60 ms per-sync RTT it avoids.
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loop(*args_):
        def body(c, _):
            a = args_[0] + c.astype(args_[0].dtype)  # depend on prev iter
            out = fn(a, *args_[1:])
            leaf = jax.tree_util.tree_leaves(out)[0]
            nxt = jnp.real(jnp.ravel(leaf)[0]).astype(jnp.float32)
            # exactly-zero carry the simplifier cannot prove is zero
            # (x*0 folds for integer kernels and DCEs the whole body)
            zero = nxt - jax.lax.optimization_barrier(nxt)
            return zero, ()

        c, _ = jax.lax.scan(body, jnp.float32(0.0), None,
                            length=_INNER_ITERS)
        return c

    np.asarray(loop(*args))                  # compile + warm + sync
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(loop(*args))
        best = min(best, time.perf_counter() - t0)
    return best / _INNER_ITERS


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--log2n", type=int, default=28,
                   help="segment size driving the kernel shapes")
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--pixmap", type=str, default="1080x1920",
                   help="resample output HxW (reference GUI default)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from srtb_tpu.ops import dedisperse as dd
    from srtb_tpu.ops import detect as det
    from srtb_tpu.ops import rfi
    from srtb_tpu.ops import spectrum as sp
    from srtb_tpu.ops import unpack as U

    n = 1 << args.log2n
    n_spec = n // 2
    nchan = 1 << 11                      # J1644 config: 2**11 channels
    wlen = n_spec // nchan
    out_h, out_w = (int(x) for x in args.pixmap.split("x"))
    reps = args.reps
    rng = np.random.default_rng(0)
    results = []

    def record(kernel, seconds, shape, samples):
        rec = {"kernel": kernel, "ms": round(seconds * 1e3, 3),
               "shape": shape,
               "gsamples_per_s": round(samples / seconds / 1e9, 2)}
        results.append(rec)
        print(json.dumps(rec), flush=True)

    # ---- resample + normalize + colormap (the published-numbers kernel)
    power = jax.device_put(
        rng.random((nchan, wlen), dtype=np.float32))
    w_freq = jax.device_put(sp.freq_area_weights(nchan, out_h))
    w_time = jax.device_put(sp.time_interp_weights(wlen, out_w))

    @jax.jit
    def resample_only(pw, wf, wt):
        return sp.resample_spectrum(pw, wf, wt)

    dt = _time(resample_only, power, w_freq, w_time, reps=reps)
    record("resample_spectrum (2 matmuls, MXU)", dt,
           f"[{nchan},{wlen}]->[{out_h},{out_w}]", nchan * wlen)

    @jax.jit
    def resample_full(pw, wf, wt):
        img = sp.resample_spectrum(pw, wf, wt)
        img = sp.normalize_by_average(img)
        return sp.generate_pixmap(img)

    dt = _time(resample_full, power, w_freq, w_time, reps=reps)
    record("resample+normalize+colormap", dt,
           f"[{nchan},{wlen}]->[{out_h},{out_w}]", nchan * wlen)

    # ---- 2-bit unpack + window (blocked field order) ----
    # The product unpack (ops/unpack.py) interleaves fields into sample
    # order; standalone, XLA materializes its [bytes, 4] intermediate
    # whose minor dim pads 4 -> 128 lanes (16x HBM, OOM at segment
    # sizes).  In the pipeline the interleave always fuses into the FFT
    # feed (proved by the 2^30 runs, where the padded form would be
    # 128 GB), so the honest standalone throughput measurement is the
    # same bit-extract + window traffic in a lane-dense blocked order.
    raw = jax.device_put(rng.integers(0, 256, n // 4, dtype=np.uint8))
    win_b = jax.device_put(
        rng.random(n, dtype=np.float32).reshape(n // 512, 512) + 0.5)

    @jax.jit
    def unpack2_blocked(b, w):
        b2 = b.reshape(-1, 128).astype(jnp.int32)
        fields = [((b2 >> s) & 3).astype(jnp.float32)
                  for s in (6, 4, 2, 0)]
        return jnp.concatenate(fields, axis=-1) * w

    dt = _time(unpack2_blocked, raw, win_b, reps=reps)
    record("unpack 2-bit + window (blocked order)", dt,
           f"[{n // 4}]u8->[{n}]f32", n)

    # ---- front-fused pass 1 (staged_ffuse tentpole): raw bytes ->
    # blocked intermediate in ONE kernel (in-kernel unpack + even/odd
    # pack + column FFT + four-step twiddle) vs the separate
    # unpack-then-pass1 chain it replaces (XLA unpack + pack_even_odd
    # materializing the spectrum-sized z, then the packed pass-1
    # kernel).  Interpret-mode on CPU (functional smoke); real Mosaic
    # on accelerators — THE ffuse probe rows the FFUSE_MOSAIC_OK flag
    # in ops/pallas_fft2 waits on.
    from srtb_tpu.ops import fft as F
    from srtb_tpu.ops import pallas_fft2 as pf2
    m_half = n // 2
    if pf2.ffuse_factor(m_half) is not None:
        interp = jax.default_backend() in ("cpu",)
        ffuse_raw = jax.device_put(
            rng.integers(0, 256, n // 4, dtype=np.uint8))
        fused_front = jax.jit(lambda b: pf2.pass1_front(
            b, m=m_half, streams=1, variant="simple", nbits=2,
            interpret=interp)[0])
        try:
            dt = _time(fused_front, ffuse_raw, reps=reps)
            record("unpack + even/odd + FFT pass 1 (ffuse, 1 kernel)",
                   dt, f"[{n // 4}]u8->[{m_half}]c64-blocked", n)

            fn1, fn2 = pf2.ffuse_factor(m_half)

            def separate(b):
                z = F.pack_even_odd(U.unpack(b, 2, None))
                return pf2.pass1_2d(jnp.real(z).reshape(fn1, fn2),
                                    jnp.imag(z).reshape(fn1, fn2),
                                    interpret=interp)[0]
            dt = _time(jax.jit(separate), ffuse_raw, reps=reps)
            record("unpack -> pack -> FFT pass 1 (separate, z "
                   "materialized)", dt,
                   f"[{n // 4}]u8->[{m_half}]c64-blocked", n)
        except Exception as e:  # pragma: no cover
            print(json.dumps({"kernel": "ffuse pass1", "error": str(e)}))

    # complex arrays are built on device from real transfers (the
    # kernels' own boundary is (re, im) f32)
    spec_re = jax.device_put(rng.standard_normal(n_spec, dtype=np.float32))
    spec_im = jax.device_put(rng.standard_normal(n_spec, dtype=np.float32))
    to_c = jax.jit(jax.lax.complex)
    spec_c = to_c(spec_re, spec_im)

    # ---- chirp multiply (precomputed bank) ----
    f_min, f_c, df = 1405.0, 1437.0, 64.0 / n_spec
    chirp = jnp.asarray(dd.chirp_factor_host_ri(n_spec, f_min, df, f_c,
                                                -478.80))
    mul = jax.jit(lambda s, c: dd.dedisperse(
        s[None], jax.lax.complex(c[0], c[1]))[0])
    dt = _time(mul, spec_c, chirp, reps=reps)
    record("chirp multiply (HBM bank)", dt, f"[{n_spec}]c64", n_spec)

    # ---- df64 on-the-fly chirp (Pallas, TPU only) ----
    if jax.default_backend() not in ("cpu",):
        from srtb_tpu.ops import pallas_kernels as pk
        spec_ri = jnp.stack([spec_re, spec_im])
        pallas_mul = jax.jit(lambda s: pk.dedisperse_df64(
            s, f_min, df, f_c, -478.80))
        try:
            dt = _time(pallas_mul, spec_ri, reps=reps)
            record("chirp multiply (Pallas df64 in-kernel)", dt,
                   f"[{n_spec}]c64", n_spec)
        except Exception as e:  # pragma: no cover
            print(json.dumps({"kernel": "pallas df64", "error": str(e)}))
        # A/B the round-3 anchored-Taylor rewrite against the exact
        # per-element df64 division chains it replaced (save/restore the
        # knob: a user-exported value must survive, and the first chirp
        # record above already honored it)
        import os
        prior = os.environ.get("SRTB_PALLAS_CHIRP_EXACT")
        os.environ["SRTB_PALLAS_CHIRP_EXACT"] = "1"
        try:
            exact_mul = jax.jit(lambda s: pk.dedisperse_df64(
                s, f_min, df, f_c, -478.80))
            dt = _time(exact_mul, spec_ri, reps=reps)
            record("chirp multiply (Pallas df64 exact, pre-anchor)", dt,
                   f"[{n_spec}]c64", n_spec)
        except Exception as e:  # pragma: no cover
            print(json.dumps({"kernel": "pallas df64 exact",
                              "error": str(e)}))
        finally:
            if prior is None:
                del os.environ["SRTB_PALLAS_CHIRP_EXACT"]
            else:
                os.environ["SRTB_PALLAS_CHIRP_EXACT"] = prior

    # ---- fused RFI-s1 + df64 chirp (Pallas, one HBM pass) ----
    if jax.default_backend() not in ("cpu",):
        from srtb_tpu.ops import pallas_kernels as pk
        fused_rfi = jax.jit(lambda s: pk.rfi_s1_dedisperse_df64(
            s, 1.5, 0.125, f_min, df, f_c, -478.80))
        try:
            dt = _time(fused_rfi, spec_ri, reps=reps)
            record("RFI s1 + chirp (Pallas fused)", dt,
                   f"[{n_spec}]c64", n_spec)
        except Exception as e:  # pragma: no cover
            print(json.dumps({"kernel": "pallas rfi+chirp",
                              "error": str(e)}))
        # the jnp sequence it replaces
        seq = jax.jit(lambda s, c: dd.dedisperse(
            rfi.mitigate_rfi_average_and_normalize(
                s[None], 1.5, 0.125),
            jax.lax.complex(c[0], c[1]))[0])
        dt = _time(seq, spec_c, chirp, reps=reps)
        record("RFI s1 + chirp (jnp + bank)", dt, f"[{n_spec}]c64", n_spec)

    # ---- fused spectrum-tail epilogue: Hermitian post + RFI s1 + chirp
    # in ONE write (the spectrum-pass-fusion tentpole) vs the unfused
    # hermitian -> s1 -> chirp sweep sequence.  spec_c stands in for the
    # packed C2C output zf (same size/statistics); runs on any backend —
    # the fusion is XLA-level, not Pallas.
    from srtb_tpu.ops import fft as F

    unfused_tail = jax.jit(lambda zf, c: dd.dedisperse(
        rfi.mitigate_rfi_average_and_normalize(
            F.hermitian_rfft_post(zf, drop_nyquist=True)[None], 1.5,
            0.125),
        jax.lax.complex(c[0], c[1]))[0])
    dt = _time(unfused_tail, spec_c, chirp, reps=reps)
    record("R2C tail: hermitian + RFI s1 + chirp (unfused sweeps)", dt,
           f"[{n_spec}]c64", n_spec)

    cw = jax.jit(lambda c: jnp.stack([
        jnp.real(jax.lax.complex(c[0], c[1])
                 * F._iota_phase(n_spec, 2 * n_spec, -1.0)),
        jnp.imag(jax.lax.complex(c[0], c[1])
                 * F._iota_phase(n_spec, 2 * n_spec, -1.0))]))(chirp)

    def fused_tail(zf, c, cwb):
        epi = lambda z, s: rfi.mitigate_rfi_s1_given_mean(  # noqa: E731
            s, rfi.mean_power_packed(z), 1.5, 0.125)
        return F.hermitian_rfft_post(
            zf, drop_nyquist=True, epilogue=epi,
            premul=(jax.lax.complex(c[0], c[1]),
                    jax.lax.complex(cwb[0], cwb[1])))
    dt = _time(jax.jit(fused_tail), spec_c, chirp, cw, reps=reps)
    record("R2C tail: fused epilogue + chirp-twiddle premul (1 write)",
           dt, f"[{n_spec}]c64", n_spec)

    # ---- spectral kurtosis on the waterfall ----
    wf_re = jax.device_put(
        rng.standard_normal((nchan, wlen)).astype(np.float32))
    wf_im = jax.device_put(
        rng.standard_normal((nchan, wlen)).astype(np.float32))
    wf_c = to_c(wf_re, wf_im)

    # ---- waterfall backward C2C: XLA vs Pallas VMEM rows ----
    # (reuses the wf_re/wf_im pair: each is 256 MB+ at segment sizes)
    from srtb_tpu.ops import pallas_fft as pf
    xla_rows = jax.jit(lambda r, i: jnp.fft.ifft(
        jax.lax.complex(r, i), axis=-1, norm="forward"))
    dt = _time(xla_rows, wf_re, wf_im, reps=reps)
    record("waterfall C2C (XLA ifft)", dt, f"[{nchan},{wlen}]c64", n_spec)
    if jax.default_backend() not in ("cpu",) and pf.supported(wlen, nchan):
        prows = jax.jit(lambda r, i: pf.fft_rows_ri(r, i, inverse=True))
        try:
            dt = _time(prows, wf_re, wf_im, reps=reps)
            record("waterfall C2C (Pallas VMEM rows)", dt,
                   f"[{nchan},{wlen}]c64", n_spec)
        except Exception as e:  # pragma: no cover
            print(json.dumps({"kernel": "pallas fft_rows",
                              "error": str(e)}))
    sk = jax.jit(lambda w: rfi.mitigate_rfi_spectral_kurtosis(w[None], 1.05)[0])
    dt = _time(sk, wf_c, reps=reps)
    record("spectral kurtosis zap", dt, f"[{nchan},{wlen}]c64", n_spec)

    # ---- fused Pallas SK zap + time series (vs sk + detect ts pass) ----
    if jax.default_backend() not in ("cpu",):
        from srtb_tpu.ops import pallas_kernels as pk
        if pk.sk_tiling_ok(nchan, wlen):
            wf_ri = jnp.stack([wf_re, wf_im])
            fused = jax.jit(lambda w: pk.sk_zap_timeseries(w, 1.05))
            try:
                dt = _time(fused, wf_ri, reps=reps)
                record("SK zap + time series (Pallas fused)", dt,
                       f"[{nchan},{wlen}]c64", n_spec)
            except Exception as e:  # pragma: no cover
                print(json.dumps({"kernel": "pallas sk", "error": str(e)}))

    # ---- fully-fused waterfall tail: C2C + dewindow + SK decide + zap
    # + time series in ONE kernel (pf.fft_rows_skzap_ri) vs the 2-kernel
    # chain (fft_rows_stats_ri + sk_apply_timeseries) it supersedes —
    # the "fused SK+ts read" attribution row for the ≤4-pass plans
    if jax.default_backend() not in ("cpu",) and pf.supported(wlen, nchan):
        from srtb_tpu.ops import pallas_kernels as pk
        skzap = jax.jit(lambda r, i: pf.fft_rows_skzap_ri(
            r, i, 1.05, inverse=True))
        try:
            dt = _time(skzap, wf_re, wf_im, reps=reps)
            record("waterfall C2C + SK zap + ts (Pallas skzap, 1 kernel)",
                   dt, f"[{nchan},{wlen}]c64", n_spec)
        except Exception as e:  # pragma: no cover
            print(json.dumps({"kernel": "pallas skzap", "error": str(e)}))

        def two_kernel(r, i):
            yr, yi, s2p, s4p = pf.fft_rows_stats_ri(r, i, inverse=True)
            zap = pk.sk_zap_decision(s2p.sum(-1), s4p.sum(-1),
                                     r.shape[-1], 1.05)
            return pk.sk_apply_timeseries(jnp.stack([yr, yi]), zap)
        try:
            dt = _time(jax.jit(two_kernel), wf_re, wf_im, reps=reps)
            record("waterfall C2C + SK zap + ts (stats + apply, "
                   "2 kernels)", dt, f"[{nchan},{wlen}]c64", n_spec)
        except Exception as e:  # pragma: no cover
            print(json.dumps({"kernel": "pallas stats+apply",
                              "error": str(e)}))

    # ---- detection chain (time series + boxcar ladder) ----
    detect = jax.jit(lambda w: det.detect(w[None], 0, 8.0, 256))
    dt = _time(detect, wf_c, reps=reps)
    record("detect (ts + boxcar ladder 256)", dt, f"[{nchan},{wlen}]c64",
           n_spec)

    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
