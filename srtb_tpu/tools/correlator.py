"""Offline 2-file cross-correlator (ref: src/correlator.cpp:35-152).

corr = |iFFT( norm * F1 * conj(F2) )| with norm = input_size^-1.5,
written as raw float32 (byte-compatible with the reference's corr.bin).
"""

from __future__ import annotations

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from srtb_tpu.utils.logging import log


# module-level jit (srtb-lint recompile-hazard caught the old
# per-call jax.jit(nested_fn)(...) spelling, which recompiled the FFT
# pair on every correlate() call); complex_count is static, the norm
# coefficient rides along as a traced scalar
@partial(jax.jit, static_argnums=(2,))
def _corr(a, b, complex_count, norm_coeff):
    fa = jnp.fft.rfft(a.astype(jnp.float32))[:complex_count]
    fb = jnp.fft.rfft(b.astype(jnp.float32))[:complex_count]
    prod = (norm_coeff * fa) * jnp.conj(fb)
    # unnormalized backward C2C, like the reference's BACKWARD plan
    corr = jnp.fft.ifft(prod, norm="forward")
    return jnp.abs(corr)


def correlate(x1: np.ndarray, x2: np.ndarray) -> np.ndarray:
    """Cross-correlation magnitude of two 8-bit sample streams
    (ref: correlator.cpp:109-140).  Returns float32 [n/2]."""
    input_size = min(x1.size, x2.size)
    complex_count = input_size // 2
    real_count = complex_count * 2
    norm_coeff = np.float32(input_size ** -1.5)
    out = _corr(jnp.asarray(x1[:real_count]),
                jnp.asarray(x2[:real_count]),
                complex_count, norm_coeff)
    return jax.device_get(out)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    in_file_1 = argv[0] if len(argv) > 0 else "pol_1.bin"
    in_file_2 = argv[1] if len(argv) > 1 else "pol_2.bin"
    out_file = argv[2] if len(argv) > 2 else "/dev/shm/corr.bin"
    log.info(f"[correlator] reading {os.path.abspath(in_file_1)}")
    log.info(f"[correlator] reading {os.path.abspath(in_file_2)}")
    x1 = np.fromfile(in_file_1, dtype=np.uint8)
    x2 = np.fromfile(in_file_2, dtype=np.uint8)
    out = correlate(x1, x2)
    out.astype("<f4").tofile(out_file)
    log.info(f"[correlator] wrote {out.size} samples to {out_file}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
