"""FFT benchmark harness: size sweep across strategies.

The analog of the reference's FFT wrapper benchmark procedure
(ref: tests/test-fft_wrappers.cpp:69-78, sweep n = 2^0..2^26 via env
vars).  Prints one JSON line per (size, strategy) with steady-state
timings; use it to tune ops.fft.LARGE_FFT_THRESHOLD / cfg.fft_strategy on
new hardware.

Usage: python -m srtb_tpu.tools.fft_bench [min_log2 [max_log2 [strategies]]]
(strategies: comma list from monolithic,four_step,mxu,pallas,pallas2)
"""
# srtb-lint: disable-file=recompile-hazard (bench harness: each (size,
# strategy) case jits one lambda once, then times steady-state repeats)

from __future__ import annotations

import json
import sys
import time

import numpy as np


def bench_one(n: int, strategy: str, reps: int = 5) -> float | None:
    import jax
    import jax.numpy as jnp

    from srtb_tpu.ops import fft as F

    rng = np.random.default_rng(0)
    x = jax.device_put(rng.standard_normal(n).astype(np.float32))

    fn = jax.jit(lambda v: jnp.abs(F.segment_rfft(v, strategy)))
    try:
        jax.block_until_ready(fn(x))
    except Exception as e:
        print(f"# n=2^{n.bit_length()-1} {strategy}: {e}", file=sys.stderr)
        return None
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        times.append(time.perf_counter() - t0)
    return min(times)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    lo = int(argv[0]) if len(argv) > 0 else 20
    hi = int(argv[1]) if len(argv) > 1 else 27
    strategies = ("monolithic", "four_step", "mxu", "pallas",
                  "pallas2")
    if len(argv) > 2:
        strategies = tuple(argv[2].split(","))
    for log2n in range(lo, hi + 1):
        n = 1 << log2n
        for strategy in strategies:
            dt = bench_one(n, strategy)
            if dt is None:
                continue
            print(json.dumps({
                "n": n, "log2n": log2n, "strategy": strategy,
                "ms": round(dt * 1e3, 3),
                "gsamples_per_s": round(n / dt / 1e9, 3),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
