"""Double-single ("df64") arithmetic: emulate ~48-bit precision with two f32.

TPU v5e has no fast fp64 ALU, the same constraint as the consumer GPUs the
reference targets; the reference proves two-float arithmetic suffices for
the dedispersion phase (ref: 3rdparty/dsmath/dsmath_sycl.h, used via
coherent_dedispersion.hpp:31-53 when ``use_emulated_fp64``).  This module is
an independent implementation of the classic Dekker/Knuth error-free
transforms as vectorized JAX ops — everything fuses into one XLA kernel.

A df64 value is a pair ``(hi, lo)`` of float32 arrays with ``|lo| <=
ulp(hi)/2`` and value ``hi + lo``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_SPLITTER = np.float32(4097.0)  # 2^12 + 1 for f32 Dekker splitting

# The error-free transforms below only work if the compiler evaluates
# them literally: XLA's simplifier rewrites patterns like (a + b) - a to
# b, which zeroes every lo component and silently degrades df64 to f32
# under jit (verified on CPU: the chirp phase lost ~1 rad at k ~ 8e5).
# optimization_barrier makes the intermediate opaque to the simplifier.
_ob = jax.lax.optimization_barrier


def two_sum(a, b):
    """Error-free sum: a + b = s + e exactly."""
    s = _ob(a + b)
    v = _ob(s - a)
    e = (a - (s - v)) + (b - v)
    return s, e


def quick_two_sum(a, b):
    """Error-free sum assuming |a| >= |b|."""
    s = _ob(a + b)
    e = b - (s - a)
    return s, e


def _split(a):
    """Dekker split of f32 into high/low halves with <=12-bit mantissas."""
    t = _ob(_SPLITTER * a)
    hi = _ob(t - (t - a))
    lo = a - hi
    return hi, lo


def two_prod(a, b):
    """Error-free product: a * b = p + e exactly (no FMA assumed)."""
    p = _ob(a * b)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    e = ((a_hi * b_hi - p) + a_hi * b_lo + a_lo * b_hi) + a_lo * b_lo
    return p, e


def df64(hi, lo=None):
    hi = jnp.asarray(hi, dtype=jnp.float32)
    if lo is None:
        lo = jnp.zeros_like(hi)
    return hi, lo


def from_float64(x) -> tuple[np.ndarray, np.ndarray]:
    """Host-side exact f64 -> (hi, lo) f32 pair (numpy)."""
    x = np.asarray(x, dtype=np.float64)
    hi = x.astype(np.float32)
    lo = (x - hi.astype(np.float64)).astype(np.float32)
    return hi, lo


def to_float64(a) -> np.ndarray:
    hi, lo = a
    return np.asarray(hi, dtype=np.float64) + np.asarray(lo, dtype=np.float64)


def add(a, b):
    a_hi, a_lo = a
    b_hi, b_lo = b
    s, e = two_sum(a_hi, b_hi)
    e = e + a_lo + b_lo
    return quick_two_sum(s, e)


def sub(a, b):
    b_hi, b_lo = b
    return add(a, (-b_hi, -b_lo))


def mul(a, b):
    a_hi, a_lo = a
    b_hi, b_lo = b
    p, e = two_prod(a_hi, b_hi)
    e = e + a_hi * b_lo + a_lo * b_hi
    return quick_two_sum(p, e)


def div(a, b):
    """df64 / df64 via one Newton refinement of the f32 quotient."""
    a_hi, a_lo = a
    b_hi, b_lo = b
    q1 = a_hi / b_hi
    # r = a - q1 * b, computed in df64
    r = sub(a, mul(df64(q1), b))
    q2 = r[0] / b_hi
    return quick_two_sum(q1, q2)


def frac(a):
    """Fractional part (value - round-toward-zero integer part), like
    ``modf`` in the reference phase computation
    (ref: coherent_dedispersion.hpp:142-143, math.hpp:97-154).

    Returns a plain f32 (the fraction fits comfortably in one float once the
    up-to-1e9 integer part is removed).
    """
    hi, lo = a
    int_hi = jnp.trunc(hi)
    # hi - int_hi is exact (both representable), then fold in lo
    f = (hi - int_hi) + lo
    # lo may push the fraction across an integer boundary
    f = f - jnp.trunc(f)
    # match modf semantics: fraction carries the sign of the full value
    # (hi dominates the sign); e.g. 1e9 + 0.6 stored as (1e9+64, -63.4)
    # must yield +0.6, not -0.4
    positive = hi >= 0
    f = jnp.where(positive & (f < 0), f + 1.0, f)
    f = jnp.where((~positive) & (f > 0), f - 1.0, f)
    return f
