"""Isolated Mosaic acceptance + timing probe for the fused two-pass
Pallas C2C (ops/pallas_fft2) at one size.

One JSON line out: block sizes, the plan's VMEM budget, compile time,
steady-state ms, and the f64-oracle relative error.  Run by the
hardware queue per size (2^24..2^29 — the round-3 advisor requires the
padded-footprint block sizing validated at the flagship sizes before
those blocks become defaults), and directly for tuning:

    python -m srtb_tpu.tools.pallas2_probe --log2m 29
    SRTB_PALLAS2_VMEM_MB=48 python -m srtb_tpu.tools.pallas2_probe --log2m 29
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--log2m", type=int, default=24)
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--tol", type=float, default=3e-5)
    p.add_argument("--interpret", action="store_true",
                   help="interpret-mode smoke off-TPU (CI only — cannot "
                        "prove Mosaic acceptance or VMEM fit)")
    args = p.parse_args(argv)

    import numpy as np
    import jax.numpy as jnp
    from srtb_tpu.ops import pallas_fft2 as pf2

    m = 1 << args.log2m
    out = {"probe": "pallas2_mosaic", "log2m": args.log2m}
    try:
        # inside the try: a bad SRTB_PALLAS2_* env value must land as
        # ok:false JSON (the queue's artifact contract), not a traceback
        fac = pf2._factor(m)
        if fac is None:
            out.update(ok=False, error="unsupported size")
            print(json.dumps(out))
            return 1
        n1, n2 = fac
        bb, rb = pf2._block_cols(n1, n2), pf2._block_rows(n2, n1)
        out.update(bb=bb, rb=rb, vmem_mb=pf2._vmem_budget() >> 20)
        rng = np.random.default_rng(0)
        x = (rng.standard_normal(m)
             + 1j * rng.standard_normal(m)).astype(np.complex64)
        xr = jnp.asarray(x.real.copy())
        xi = jnp.asarray(x.imag.copy())
        import jax

        # jit the whole two-pass composition: the timing must rank block
        # plans by kernel time, not per-call eager dispatch overhead
        import functools
        f = jax.jit(functools.partial(pf2.fft2_c2c_ri,
                                      interpret=args.interpret))
        t0 = time.perf_counter()
        yr, yi = f(xr, xi)
        # sync on a tiny slice so compile_s is compile+execute, not the
        # full-size host fetch (2x2 GiB at 2^29) that follows
        np.asarray(yr[..., :8])
        out["compile_s"] = round(time.perf_counter() - t0, 1)
        # split re/im host fetch (the kernel's own boundary is (re, im))
        got = np.asarray(yr) + 1j * np.asarray(yi)
        want = np.fft.fft(x.astype(np.complex128))
        err = float(np.abs(got - want).max() / np.abs(want).max())
        out["rel_err"] = err
        out["ok"] = err < args.tol
        t0 = time.perf_counter()
        for _ in range(args.reps):
            yr, yi = f(xr, xi)
        np.asarray(yr[..., :8])
        out["ms"] = round((time.perf_counter() - t0) / args.reps * 1e3, 2)
    except Exception as e:  # land the failure as data, not a stack trace
        out["ok"] = False
        out["error"] = f"{type(e).__name__}: {e}"[:400]
    print(json.dumps(out))
    return 0 if out.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
