"""Unpack raw baseband bytes to float32 samples.

TPU-native re-design of the reference unpack kernels (ref: unpack.hpp):
instead of one work-item per input byte doing scalar bit tricks, the whole
segment is unpacked with vectorized shift/mask lanes — a ``[bytes, k]``
broadcast that XLA lowers to pure VPU code and fuses with the optional
window multiply (the reference fuses its FFT window the same way,
unpack.hpp:32-33).

Bit-width semantics (ref: config.hpp:92-97 + unpack_pipe.hpp:46-136):
positive = unsigned, negative = signed; 1/2/4-bit fields are MSB-first
within each byte (ref: unpack.hpp:43-140); 32/64 are floating point.

Packet-format de-interleave variants:
- ``unpack_interleaved_2pol``   "1212"  (ref: unpack.hpp:214-244)
- ``unpack_naocpsr_snap1``      "1122"  (ref: unpack.hpp:253-283)
- ``unpack_gznupsr_a1``         4-way word-interleave, XOR 0x80
  unsigned->signed trick (ref: unpack.hpp:291-328)
- ``unpack_gznupsr_a1_v2_1``    2-way word-interleave (ref: unpack.hpp:336-369)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from srtb_tpu.ops import scopes as S

SUPPORTED_BITS = (1, 2, 4, 8, -8, 16, -16, 32, 64)


def _unpack_subbyte(data: jnp.ndarray, nbits: int) -> jnp.ndarray:
    """Unpack 1/2/4-bit unsigned fields, MSB-first, to float32.

    in[x] -> out[(8/nbits)*x ...] exactly as unpack.hpp:43-75.
    """
    count = 8 // nbits
    mask = (1 << nbits) - 1
    # shifts are MSB-first: (count-1-i)*nbits
    shifts = jnp.arange(count - 1, -1, -1, dtype=jnp.uint8) * nbits
    fields = (data[:, None] >> shifts[None, :]) & mask
    return fields.reshape(-1).astype(jnp.float32)


@S.scoped(S.UNPACK)
def unpack_subbyte_planes(data: jnp.ndarray, nbits: int) -> jnp.ndarray:
    """Unpack 1/2/4-bit fields to **blocked field planes** ``[count, M]``
    (count = 8/nbits fields per byte, M = byte count): plane k holds field
    k (MSB-first) of every byte, i.e. sample ``count*b + k`` lands at
    ``planes[k, b]``.

    This is the TPU-native form of the unpack: every array keeps the byte
    axis minor and lane-dense.  The sample-order form (`_unpack_subbyte`)
    interleaves count fields per byte, which forces a ``[bytes, count]``
    minor-dim intermediate — on TPU that pads count -> 128 lanes, a 32x
    HBM expansion whenever XLA must materialize it (observed: a 16 GB
    copy at n = 2^27).  Blocked planes never interleave; the consumer
    (ops/fft.rfft_subbyte) folds the blocked->natural permutation into
    the FFT's decimation instead.
    """
    count = 8 // nbits
    mask = (1 << nbits) - 1
    shifts = jnp.arange(count - 1, -1, -1, dtype=jnp.uint8) * nbits
    fields = (data[..., None, :] >> shifts[:, None]) & mask
    return fields.astype(jnp.float32)


@S.scoped(S.UNPACK)
def unpack(data: jnp.ndarray, nbits: int,
           window: jnp.ndarray | None = None) -> jnp.ndarray:
    """Unpack a uint8 byte stream into float32 samples.

    ``window``, if given, is multiplied in (kernel fusion of the FFT window
    into the unpack stage, ref: unpack_pipe.hpp:72-127).
    """
    if nbits not in SUPPORTED_BITS:
        raise ValueError(f"unsupported baseband_input_bits {nbits}")
    data = data.astype(jnp.uint8) if data.dtype != jnp.uint8 else data
    if nbits in (1, 2, 4):
        out = _unpack_subbyte(data, nbits)
    elif nbits == 8:
        out = data.astype(jnp.float32)
    elif nbits == -8:
        out = data.view(jnp.int8).astype(jnp.float32)
    elif nbits == 16:
        out = data.view(jnp.uint16).astype(jnp.float32)
    elif nbits == -16:
        out = data.view(jnp.int16).astype(jnp.float32)
    elif nbits == 32:
        out = data.view(jnp.float32)
    elif nbits == 64:
        # float64 input decoded to f32 from the raw bit pattern — without
        # x64, jnp's .view(float64) silently truncates to a float32 view
        # (doubling the sample count and corrupting every value), so the
        # double is reassembled from its little-endian uint32 halves:
        # sign/exponent/mantissa-high in the high word, mantissa-low in
        # the low word, combined to f32 precision.
        u = data.view(jnp.uint32)
        lo = u[..., 0::2].astype(jnp.float32)
        hi = u[..., 1::2]
        sign = jnp.where((hi >> 31) != 0, jnp.float32(-1.0),
                         jnp.float32(1.0))
        exp = ((hi >> 20) & 0x7FF).astype(jnp.int32)
        frac = ((hi & 0xFFFFF).astype(jnp.float32) * jnp.float32(2.0 ** -20)
                + lo * jnp.float32(2.0 ** -52))
        # exact power of two via the f32 exponent field (jnp.exp2 lowers
        # to exp(x*ln2) and is ~1e-7-relative WRONG for large exponents);
        # clamping the biased exponent to [0, 255] makes out-of-f32-range
        # doubles flush to 0 / +-inf, and f64 subnormals (exp == 0,
        # magnitude < 2^-1021) flush to 0 — all correct truncations
        pw = jax.lax.bitcast_convert_type(
            (jnp.clip(exp - 1023 + 127, 0, 255) << 23).astype(jnp.int32),
            jnp.float32)
        mag = jnp.where(exp == 0, jnp.float32(0.0), (1.0 + frac) * pw)
        out = sign * mag
        out = jnp.where((exp == 0x7FF) & (frac > 0), jnp.float32(jnp.nan),
                        out)
    if window is not None:
        out = out * window
    return out


def samples_per_byte(nbits: int) -> float:
    return 8.0 / abs(nbits)


# ----------------------------------------------------------------
# de-interleave variants (multi-stream packet formats)
# ----------------------------------------------------------------

# Bytes of one row of the "1212" split.  The split reads the segment as
# rows of this many bytes and takes every other byte of a row, so the
# byte axis stays minor and 128 lanes wide on both sides of it; spelled
# ``reshape(-1, 2)[:, k]`` the minor dimension of 2 is padded to 128
# lanes on the chip (a ``u8[2^25, 2]`` tiled to 4.3 GB at 2^27 samples
# a stream: 50.0 ms a segment where this spelling takes 5.6, PERF.md
# section 6, PR 36).  ``x[:, k::2]`` lowers to a gather, which the chip
# runs as one pass; as a strided ``lax.slice`` it read 19.5 ms.
_SPLIT_ROW_BYTES = 1024


@S.scoped(S.UNPACK)
def stream_bytes(data: jnp.ndarray, variant: str) -> tuple:
    """The segment's bytes dealt out to its data streams: one uint8
    array a stream, each holding that stream's own bytes in order
    (:func:`unpack_stream` makes its samples of them).

    - ``interleaved_samples_2`` "1212": stream k owns bytes k, k + 2,
      ...  At 8 / -8 bits that is sample by sample; below 8 bits each
      byte holds 8 / nbits samples of ONE stream, MSB first (cpsr2's
      two-polarisation files at 2 bits); above 8 bits the bytes of a
      sample are dealt out alternately as they always were, which no
      format asks for (ref: unpack.hpp:214-244; dispatch
      unpack_pipe.hpp:146-260).
    - ``naocpsr_snap1`` "1122": pairs of bytes (ref: unpack.hpp:253-283).
    - ``gznupsr_a1``: four streams, four bytes of each in a 16-byte word
      group (ref: unpack.hpp:291-328); ``gznupsr_a1_v2_1``: two
      (ref: unpack.hpp:336-369).
    """
    if variant == "simple":
        return (data,)
    if variant == "interleaved_samples_2":
        row = int(np.gcd(data.shape[-1], _SPLIT_ROW_BYTES))
        x = data.reshape(-1, row)
        return tuple(x[:, k::2].reshape(-1) for k in range(2))
    if variant == "naocpsr_snap1":
        x = data.reshape(-1, 4)
        return tuple(x[:, 2 * k:2 * k + 2].reshape(-1) for k in range(2))
    if variant in ("gznupsr_a1", "gznupsr_a1_v2_1"):
        streams = 4 if variant == "gznupsr_a1" else 2
        x = data.reshape(-1, streams, 4)  # [word, stream, sample-in-word]
        return tuple(x[:, i, :].reshape(-1) for i in range(streams))
    raise ValueError(f"unknown unpack variant {variant!r}")


@S.scoped(S.UNPACK)
def unpack_stream(data: jnp.ndarray, variant: str, nbits: int,
                  window: jnp.ndarray | None = None) -> jnp.ndarray:
    """One stream's own bytes (:func:`stream_bytes`) -> its float32
    samples.  The ``gznupsr_a1`` formats are int8 whatever ``nbits``
    says, the first with the XOR 0x80 unsigned -> signed trick."""
    if variant == "gznupsr_a1":
        return unpack(jnp.bitwise_xor(data, jnp.uint8(0x80)), -8, window)
    if variant == "gznupsr_a1_v2_1":
        return unpack(data, -8, window)
    return unpack(data, nbits, window)


def one_byte_cast(variant: str, nbits: int):
    """Where a sample of this format is one byte, the cast of a stream's
    own bytes to its float32 samples, as :func:`unpack_stream` makes
    them; None for any other width.  Under no scope of its own: the
    caller names the work (the own transform deals the bytes out to
    planes first and casts plane by plane inside the R2C,
    ``pipeline/segment.SegmentProcessor._process_own``)."""
    if variant == "gznupsr_a1":
        return lambda b: jnp.bitwise_xor(b, jnp.uint8(0x80)).view(
            jnp.int8).astype(jnp.float32)
    if variant == "gznupsr_a1_v2_1" or nbits == -8:
        return lambda b: b.view(jnp.int8).astype(jnp.float32)
    if nbits == 8:
        return lambda b: b.astype(jnp.float32)
    return None


def _unpack_streams(data, variant: str, nbits: int, window) -> tuple:
    return tuple(unpack_stream(own, variant, nbits, window)
                 for own in stream_bytes(data, variant))


def unpack_interleaved_2pol(data: jnp.ndarray, nbits: int,
                            window: jnp.ndarray | None = None
                            ) -> jnp.ndarray:
    """"1212" byte-interleaved 2 polarizations -> float32 ``[2, n]``,
    row k = stream k (it still unpacks as a pair)."""
    with jax.named_scope(S.UNPACK):
        return jnp.stack(_unpack_streams(data, "interleaved_samples_2",
                                         nbits, window))


def unpack_naocpsr_snap1(data: jnp.ndarray, nbits: int = -8,
                         window: jnp.ndarray | None = None):
    """"1122" pair-interleaved 2 polarizations -> 2 streams.  Samples
    are int8."""
    return _unpack_streams(data, "naocpsr_snap1", nbits, window)


def unpack_gznupsr_a1(data: jnp.ndarray,
                      window: jnp.ndarray | None = None):
    """4-way word-interleaved, uint8 with XOR 0x80 -> int8."""
    return _unpack_streams(data, "gznupsr_a1", -8, window)


def unpack_gznupsr_a1_v2_1(data: jnp.ndarray,
                           window: jnp.ndarray | None = None):
    """2-way word-interleaved variant, int8 without the XOR trick."""
    return _unpack_streams(data, "gznupsr_a1_v2_1", -8, window)


# ----------------------------------------------------------------
# numpy golden models (used by tests; kept next to the op on purpose)
# ----------------------------------------------------------------

def unpack_oracle(data: np.ndarray, nbits: int) -> np.ndarray:
    """Reference semantics in plain numpy (bit-for-bit vs unpack.hpp)."""
    data = np.asarray(data, dtype=np.uint8)
    if nbits in (1, 2, 4):
        count = 8 // nbits
        mask = (1 << nbits) - 1
        out = np.empty(data.size * count, dtype=np.float32)
        for i in range(count):
            shift = (count - 1 - i) * nbits
            out[i::count] = ((data >> shift) & mask).astype(np.float32)
        return out
    if nbits == 8:
        return data.astype(np.float32)
    if nbits == -8:
        return data.view(np.int8).astype(np.float32)
    if nbits == 16:
        return data.view(np.uint16).astype(np.float32)
    if nbits == -16:
        return data.view(np.int16).astype(np.float32)
    if nbits == 32:
        return data.view(np.float32)
    if nbits == 64:
        return data.view(np.float64).astype(np.float32)
    raise ValueError(nbits)
