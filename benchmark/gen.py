"""The benchmark's baseband: where segments and pulses sit in the replay
file (``Layout``) and the bytes themselves, made on the device from
``--seed`` (``write_file``).

Semantics are ``srtb_tpu/io/synth.py``'s (unit Gaussian noise plus
impulses dispersed by the inverse of the dedispersion chirp, a digitizer
that keeps ~3 sigma in range, MSB-first sub-byte packing, 8-bit samples
unsigned or two's complement) at a speed a
benchmark can pay every run: the noise, the quantizer and the packing run
on the device in one jitted call per block; only one short dispersed-pulse
template is computed on the host, once.  ``selftest/test_gen.py`` holds
the bytes against ``io/synth`` on the same floats.

The digitizer's gain is fixed (unit noise), not re-derived from each
block's sample deviation as ``io/synth.quantize`` does: a block with a
pulse in it must not be scaled differently from its neighbours.

A file may hold several streams (``reference/chain.FORMATS``): each is
independent noise from the seed with the same pulse template added,
and the streams are written interleaved as the format says.  Everything
counted in samples (``n``, ``stride``, ``total``, ``pulse_at``) is per
stream, as the program's ``baseband_input_count`` is (its reader takes
``count * |bits| / 8 * streams`` bytes a segment and ``unpack_streams``
returns ``[streams, count]``); only ``bytes_of`` counts the streams.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.reference import chain

try:
    import scipy.fft as _fft
except ImportError:  # pragma: no cover - scipy is in the image
    _fft = np.fft


def seeded_rng(seed: int, stream: int) -> np.random.Generator:
    """A NumPy generator from any non-negative whole number (the
    driver's seeds pass 2**31); ``stream`` keeps the draws for the pulse
    positions, the pulse shape and the sample apart."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


class Layout:
    """Where the segments and the pulses sit in the file.

    The file holds ``warmup`` segments followed by ``replay`` segments,
    each ``stride`` new samples after the one before (stride = n -
    reserved: consecutive segments overlap by the overlap-save tail),
    plus the last segment's tail.  ``pulsed[k]`` says whether file
    segment k holds a pulse and ``pulse_at[k]`` the sample (from the
    file's start) where its undispersed impulse sits.
    """

    def __init__(self, params: dict, workload: dict, seed: int):
        p = params
        self.n = p["n"]
        self.bits = p["bits"]
        self.streams = p["streams"]
        self.group_bytes = p["group_bytes"]
        self.channels = min(p["channels"], self.n // 2)
        self.reserved = chain.nsamps_reserved(p)
        self.stride = self.n - self.reserved
        warm = list(workload["warmup"]["segments"])
        self.n_warmup = len(warm)
        self.n_replay = int(workload["source"]["file_segments"])
        self.n_segments = self.n_warmup + self.n_replay
        self.total = self.reserved + self.n_segments * self.stride
        pulses = workload["pulses"]
        self.pulse = pulses
        every = int(pulses.get("every", 0))
        phase = int(pulses.get("phase", every // 2 if every else 0))
        self.pulsed = [w == "pulse" for w in warm] + [
            bool(every) and r % every == phase
            for r in range(self.n_replay)]
        self.template_len = 1 << int(pulses["template_log2"])
        sweep = abs(chain.max_delay_time(
            p["freq_low"], p["bandwidth"], float(pulses["dm"]))) \
            * p["sample_rate"]
        if 2 * sweep >= self.template_len:
            raise ValueError(
                f"dispersion sweep of {sweep:.0f} samples does not fit a "
                f"template of {self.template_len}")
        # one time-series sample is 2 * channels raw samples; the pulse
        # sits mid-segment, moved by up to an eighth of a stride from
        # the seed so that the expected bin differs between seeds
        col = 2 * self.channels
        rng = seeded_rng(seed, 7)
        span = max(1, self.stride // 8 // col)
        self.pulse_at = {}
        for k, on in enumerate(self.pulsed):
            if on:
                jitter = int(rng.integers(-span, span + 1)) * col
                self.pulse_at[k] = k * self.stride + self.stride // 2 \
                    // col * col + jitter

    def bytes_of(self, samples: int) -> int:
        """Bytes that ``samples`` samples of every stream take in the
        file."""
        return samples * abs(self.bits) // 8 * self.streams

    @property
    def stride_bytes(self) -> int:
        return self.bytes_of(self.stride)

    @property
    def segment_bytes(self) -> int:
        return self.bytes_of(self.n)

    def draw_sample(self, want: dict, seed: int) -> list:
        """The replay segments compared with the reference: ``count`` of
        the given ``kind`` (``"pulse"``/``"quiet"``), drawn from the seed
        among the first ``within`` replay segments, which every window
        reaches."""
        rng = seeded_rng(seed, 3)
        first, reach = self.n_warmup, min(int(want["within"]), self.n_replay)
        pool = [k for k in range(first, first + reach)
                if self.pulsed[k] == (want["kind"] == "pulse")]
        count = min(int(want["count"]), len(pool))
        return sorted(int(k) for k in rng.choice(pool, size=count,
                                                 replace=False))

    def expected_bin(self, k: int) -> int:
        """Time-series sample of segment k at which its pulse peaks."""
        return (self.pulse_at[k] - k * self.stride) // (2 * self.channels)


def pulse_template(params: dict, lay: Layout, seed: int) -> np.ndarray:
    """float32[template_len]: a white burst of ``width`` samples at the
    template's centre, dispersed by the medium (the inverse chirp) at
    the pulse's DM.  Host, float64 phase, once per run."""
    length = lay.template_len
    pulse = lay.pulse
    rng = seeded_rng(seed, 11)
    x = np.zeros(length, dtype=np.float32)
    width = int(pulse["width"])
    c = length // 2
    x[c:c + width] = float(pulse["amp"]) * rng.standard_normal(width)
    n_spec = length // 2
    spec = _fft.rfft(x)                 # complex64: x is float32
    dm = float(pulse["dm"])

    def disperse(i0, i1):
        spec[i0:i1] *= np.conj(chain.chirp(params, n_spec, dm, i0, i1))
    chain.ranges_on_threads(disperse, n_spec, chain.default_workers())
    return np.asarray(_fft.irfft(spec, length), dtype=np.float32)


def seed_key(seed: int):
    """A JAX key from any non-negative whole number (the driver's seeds
    pass 2**31)."""
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


STREAM_SALT = 0x5354524D     # "STRM": keeps stream keys off block keys


def make_block_fn(bits: int, cols: int, sigma: float = 1.0,
                  streams: int = 1, group_bytes: int = 1):
    """The jitted generator of one block: ``cols`` bytes = ``cols *
    8/|bits|`` samples of every stream.  Sample ``per_byte * b + j`` of a
    stream's block is ``noise[j, b] + planes[j, col0 + b]``: the byte
    axis stays minor (lane-dense on the chip), fields are planes.
    Stream 0 draws from ``fold_in(key, index)`` (a one-stream file is
    what it always was), stream s > 0 from a key of its own; several
    streams leave the block interleaved in groups of ``group_bytes``."""
    import jax
    import jax.numpy as jnp

    width = abs(bits)
    per_byte = 8 // width
    levels = 1 << width
    mid = levels / 2
    gain = np.float32((levels / 2 - 0.5) / 3.0 / sigma)

    def one_stream(key, index, planes, col0):
        k = jax.random.fold_in(key, index)
        sig = jax.random.normal(k, (per_byte, cols), dtype=jnp.float32)
        sig = sig + jax.lax.dynamic_slice(planes, (0, col0),
                                          (per_byte, cols))
        return quantize_pack(sig, bits, gain, mid)

    @jax.jit
    def block(key, index, planes, col0):
        if streams == 1:
            return one_stream(key, index, planes, col0)
        salted = jax.random.fold_in(key, STREAM_SALT)
        rows = [one_stream(key if s == 0 else jax.random.fold_in(salted, s),
                           index, planes, col0) for s in range(streams)]
        out = jnp.stack(rows).reshape(streams, cols // group_bytes,
                                      group_bytes)
        return out.transpose(1, 0, 2).reshape(-1)

    return block


def quantize_pack(sig, bits: int, gain, mid):
    """[per_byte, cols] floats -> uint8[cols], ``io/synth.quantize`` +
    ``pack_subbyte`` with the gain given; ``bits`` -8 writes the same
    level as two's complement (level - 128: the top bit flipped)."""
    import jax.numpy as jnp

    width = abs(bits)
    per_byte = 8 // width
    q = jnp.clip(jnp.round(sig * gain + mid), 0, (1 << width) - 1)
    q = q.astype(jnp.uint8)
    out = q[0] << (8 - width)
    for j in range(1, per_byte):
        out = out | (q[j] << (8 - width * (j + 1)))
    return out ^ jnp.uint8(0x80) if bits < 0 else out


def write_file(path: str, params: dict, lay: Layout, seed: int,
               block_samples: int = 1 << 26) -> dict:
    """Make the file on the device, block by block, and write it.
    Returns the bytes written, the blocks made and the template's
    seconds.  A template that reaches past either end of the file is
    cut there (the sky before the recording began)."""
    import jax
    import jax.numpy as jnp

    bits = lay.bits
    per_byte = 8 // abs(bits)
    group = lay.group_bytes
    blk = min(block_samples, 1 << (lay.total - 1).bit_length())
    at = sorted(lay.pulse_at.values())
    gap = min((b - a for a, b in zip(at, at[1:])), default=None)
    while gap is not None and blk + lay.template_len > gap:
        blk //= 2       # a block meets one pulse template at most
    if blk < per_byte * group:
        raise ValueError("pulses closer together than their template")
    cols = blk // per_byte
    t0 = time.perf_counter()
    tmpl = pulse_template(params, lay, seed) if lay.pulse_at else \
        np.zeros(per_byte, dtype=np.float32)
    template_s = time.perf_counter() - t0
    length = tmpl.size
    # planes[j, c] = template[per_byte * c + j], a block of zeros on
    # either side so that any block offset is one dynamic slice
    planes = np.zeros((per_byte, 2 * cols + length // per_byte),
                      dtype=np.float32)
    planes[:, cols:cols + length // per_byte] = \
        tmpl.reshape(-1, per_byte).T
    planes = jax.device_put(planes)
    starts = sorted(g - length // 2 for g in lay.pulse_at.values())
    for s in starts:
        if s % per_byte:
            raise ValueError("a pulse template starts inside a byte")
    block = make_block_fn(bits, cols, streams=lay.streams,
                          group_bytes=group)
    key = seed_key(seed)
    n_blocks = -(-lay.total // blk)
    total_bytes = lay.bytes_of(lay.total)
    if total_bytes % (lay.streams * group):
        raise ValueError("the file does not end on a whole group")

    def col0_of(b: int) -> int:
        s0 = b * blk
        for s in starts:
            if s < s0 + blk and s + length > s0:
                return (s0 - s) // per_byte + cols
        return 0

    written = 0
    with open(path, "wb") as f:
        pending = None
        for b in range(n_blocks + 1):
            nxt = block(key, b, planes, jnp.int32(col0_of(b))) \
                if b < n_blocks else None
            if pending is not None:
                data = np.asarray(jax.device_get(pending))
                data = data[:total_bytes - written]
                f.write(memoryview(data))
                written += data.size
            pending = nxt
    del planes
    return {"bytes": written, "blocks": n_blocks, "template_s": template_s}
