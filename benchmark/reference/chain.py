"""The plain float64 reference of the served chain.

    unpack -> R2C (Nyquist dropped) -> RFI stage 1 (zap bins over
    threshold x mean power, normalize) -> manual zap list -> coherent
    dedispersion chirp -> waterfall C2C per channel -> spectral-kurtosis
    zap -> power time series -> mean-subtract -> boxcar detection

Formulas are written out from the upstream C++ sources (the file and line
are cited at each step); nothing is imported from ``srtb_tpu`` and nothing
from JAX, so the reference can run in a child process that never touches a
chip.  The configuration arrives as data (``params``: a flat dict, see
``params_from_config``), so a new configuration needs no code here.

Everything is float64 / complex128.  ``low`` selects the *controls*: the
same chain with one step in the nearest lower precision
(``"chirp_f32"``: the chirp phase in plain float32; ``"bf16"``: spectrum
and waterfall rounded to bfloat16).  The benchmark's comparison has to
fail them.

Speed matters (every run of every check pays it), so the segment R2C is a
four-step transform whose legs are batched FFTs (threaded through
``scipy.fft`` where it is installed) and the waterfall stage runs per
block of channels on a thread pool.  ``selftest/test_reference.py`` holds
both against ``numpy.fft`` at a small size.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

try:  # threaded batched FFTs; numpy's pocketfft is the fallback
    import scipy.fft as _sfft
except ImportError:  # pragma: no cover - scipy is in the image
    _sfft = None

# dispersion constant, MHz^2 pc^-1 cm^3 s (ref: coherent_dedispersion.hpp:67)
D = 4.148808e3


def default_workers() -> int:
    return max(1, min(16, (os.cpu_count() or 2) - 1))


def _fft(a, axis, inverse=False, workers=1):
    if _sfft is not None:
        f = _sfft.ifft if inverse else _sfft.fft
        return f(a, axis=axis, workers=workers)
    f = np.fft.ifft if inverse else np.fft.fft
    return f(a, axis=axis)


# ------------------------------------------------------------------ params

# Sample formats (``baseband_format_type``): how many data streams a file
# holds and how many bytes of one stream stand together before the next
# stream's.  The benchmark's own table, written from the upstream
# description (SURVEY.md; ref: unpack.hpp:214-283,
# backend_registry.hpp:36-92), not imported from the program:
#   simple                 one stream
#   interleaved_samples_2  two streams, bytes "1 2 1 2" (cpsr2 files)
#   naocpsr_snap1          two streams, 8-bit samples "1 1 2 2"
# ``gznupsr_a1`` (VDIF header, 4-sample word groups) waits for the PR
# that brings a packet source.
FORMATS = {
    "simple": {"streams": 1, "group_bytes": 1},
    "interleaved_samples_2": {"streams": 2, "group_bytes": 1},
    "naocpsr_snap1": {"streams": 2, "group_bytes": 2},
}
# bits of one sample; negative = two's complement
BITS = (1, 2, 4, 8, -8)


def params_from_config(options: dict) -> dict:
    """The numbers the chain needs, from a configuration file's
    ``options`` (the program's option names; values may be expressions
    such as ``"2 ** 27"`` or ``"1405 + 32"``).  Everything counted in
    samples (``n``, the reserved tail) is per stream."""
    def num(key, default=None):
        v = options.get(key, default)
        if isinstance(v, str):
            v = eval(v, {"__builtins__": {}}, {})  # arithmetic only
        return v

    dm_list = options.get("dm_list")
    if isinstance(dm_list, str):
        dm_list = [float(x) for x in dm_list.split(",") if x.strip()]
    bits = int(num("baseband_input_bits"))
    name = str(options.get("baseband_format_type", "simple"))
    if bits not in BITS or name not in FORMATS:
        raise ValueError(f"the benchmark knows samples of {BITS} bits in "
                         f"the formats {sorted(FORMATS)}, not {bits} bits "
                         f"in {name!r}")
    fmt = FORMATS[name]
    if name == "naocpsr_snap1" and abs(bits) != 8:
        raise ValueError("naocpsr_snap1 interleaves pairs of 8-bit samples")
    return {
        "n": int(num("baseband_input_count")),
        "bits": bits,
        "streams": fmt["streams"],
        "group_bytes": fmt["group_bytes"],
        "freq_low": float(num("baseband_freq_low")),
        "bandwidth": float(num("baseband_bandwidth")),
        "sample_rate": float(num("baseband_sample_rate")),
        "dm": float(num("dm", 0.0)),
        "dm_list": dm_list or [],
        "channels": int(num("spectrum_channel_count")),
        # thresholds are stated in the configuration file, never
        # defaulted here: a default would have to track the program's
        "avg_threshold": float(num("mitigate_rfi_average_method_threshold")),
        "sk_threshold": float(
            num("mitigate_rfi_spectral_kurtosis_threshold")),
        "snr_threshold": float(num("signal_detect_signal_noise_threshold")),
        "max_boxcar": int(num("signal_detect_max_boxcar_length")),
        "rfi_freq_list": str(options.get("mitigate_rfi_freq_list", "")),
        "reserve": bool(int(num("baseband_reserve_sample", 0))),
    }


# ------------------------------------------------------------------ layout


def max_delay_time(freq_low: float, bandwidth: float, dm: float) -> float:
    """Dispersion delay across the band, seconds
    (ref: coherent_dedispersion.hpp:75-85)."""
    f, f_c = freq_low + bandwidth, freq_low
    return -D * dm * (1.0 / (f * f) - 1.0 / (f_c * f_c))


def nsamps_reserved(p: dict) -> int:
    """Samples overlapped between consecutive segments
    (ref: coherent_dedispersion.hpp:103-128): twice the sweep, with the
    rest rounded down to whole waterfall columns."""
    if not p["reserve"]:
        return 0
    minimal = 2 * round(max_delay_time(p["freq_low"], p["bandwidth"],
                                       p["dm"]) * p["sample_rate"])
    per_bin = p["channels"] * 2
    refft_total = (p["n"] - minimal) // per_bin * per_bin
    return p["n"] - refft_total if refft_total > 0 else 0


# ------------------------------------------------------------------ steps


def ranges_on_threads(fn, n: int, workers: int, align: int = 1) -> list:
    """fn(i0, i1) over [0, n) in contiguous pieces, on threads (NumPy
    and the FFTs release the interpreter lock)."""
    pieces = max(1, min(4 * workers, n // max(align, 1)))
    step = -(-n // pieces)
    step = -(-step // align) * align
    ranges = [(i, min(n, i + step)) for i in range(0, n, step)]
    if workers <= 1 or len(ranges) == 1:
        return [fn(a, b) for a, b in ranges]
    with ThreadPoolExecutor(workers) as pool:
        return list(pool.map(lambda r: fn(*r), ranges))


def segment_bytes(p: dict) -> int:
    """Bytes of one segment in the file, all streams."""
    return p["n"] * abs(p["bits"]) // 8 * p["streams"]


def deinterleave(raw: np.ndarray, p: dict) -> list:
    """The bytes of a file's segment -> the bytes of each stream
    (ref: unpack.hpp:214-283): stream s owns bytes ``group_bytes * (S * j
    + s) ...`` of every group j."""
    streams = p["streams"]
    rows = np.asarray(raw, dtype=np.uint8).reshape(-1, streams,
                                                   p["group_bytes"])
    # one stream: a view, nothing is copied
    return [np.ascontiguousarray(rows[:, s, :]).reshape(-1)
            for s in range(streams)]


def stream_tag(file_seg: int, stream: int) -> str:
    """The prefix of a segment's answers in the child's ``.npz``:
    ``s<k>`` for stream 0 (the only one of a one-stream file),
    ``s<k>.p<s>`` for stream s > 0."""
    return f"s{file_seg}" if stream == 0 else f"s{file_seg}.p{stream}"


def unpack(raw: np.ndarray, bits: int, workers: int = 1) -> np.ndarray:
    """uint8 bytes -> float64 samples, one stream (ref: unpack.hpp:43-140):
    1/2/4-bit unsigned fields MSB-first in each byte; 8 unsigned; -8
    two's complement."""
    b = np.asarray(raw, dtype=np.uint8)
    if bits in (1, 2, 4):
        count = 8 // bits
        mask = (1 << bits) - 1
        # every byte's fields, looked up: table[byte, i] = field i
        byte = np.arange(256, dtype=np.uint16)[:, None]
        shifts = (count - 1 - np.arange(count)) * bits
        table = ((byte >> shifts) & mask).astype(np.float64)
        out = np.empty((b.size, count), dtype=np.float64)

        def look_up(i0, i1):
            out[i0:i1] = table[b[i0:i1]]
        ranges_on_threads(look_up, b.size, workers)
        return out.reshape(-1)
    if bits == 8:
        return b.astype(np.float64)
    if bits == -8:
        return b.view(np.int8).astype(np.float64)
    raise ValueError(f"reference unpack: unsupported bits {bits}")


def rfft_drop_nyquist(x: np.ndarray, workers: int = 1,
                      four_step_min: int = 1 << 22) -> np.ndarray:
    """R2C of a real sequence of even length n, bins 0..n/2-1
    (ref: fft_pipe.hpp:44-78).

    Small inputs: ``numpy.fft.rfft``.  Large ones (a 2^27 transform
    takes NumPy 19 s on one core) go through the textbook four-step
    transform of the even/odd-packed half-length complex sequence, whose
    legs are batched FFTs and whose elementwise passes run on threads;
    the selftest holds the two against each other."""
    n = x.size
    if n < four_step_min:
        return np.fft.rfft(x)[:-1]
    m = n // 2
    log2m = m.bit_length() - 1
    if 1 << log2m != m:
        raise ValueError("reference four-step R2C needs a power of two")
    n1 = 1 << (log2m // 2)
    n2 = m // n1
    # z[j] = x[2j] + i x[2j+1] is the same memory read as complex
    a = np.ascontiguousarray(x, dtype=np.float64).view(np.complex128)
    a = a.reshape(n1, n2)
    a = _fft(a, axis=0, workers=workers)                    # [k1, j2]
    j2 = np.arange(n2, dtype=np.float64)[None, :]

    def twiddle(r0, r1):
        ang = (-2.0 * np.pi / m) * (
            np.arange(r0, r1, dtype=np.float64)[:, None] * j2)
        a[r0:r1] *= np.cos(ang) + 1j * np.sin(ang)
    ranges_on_threads(twiddle, n1, workers)
    a = _fft(a, axis=1, workers=workers)                    # [k1, k2]
    zf = np.empty(m, dtype=np.complex128)                   # k = k1 + n1*k2
    zt = zf.reshape(n2, n1)

    def transpose(r0, r1):
        zt[:, r0:r1] = a[r0:r1].T
    ranges_on_threads(transpose, n1, workers)
    del a
    # Hermitian split: X[k] = E[k] + exp(-2 pi i k / n) O[k], with
    # E = (Z[k] + conj Z[m-k]) / 2 and O = (Z[k] - conj Z[m-k]) / 2i
    spec = np.empty(m, dtype=np.complex128)

    def split(k0, k1):
        idx = (m - np.arange(k0, k1)) % m if k0 == 0 else None
        zr = np.conj(zf[idx] if idx is not None
                     else zf[m - k1 + 1:m - k0 + 1][::-1])
        z = zf[k0:k1]
        ang = (-2.0 * np.pi / n) * np.arange(k0, k1, dtype=np.float64)
        w = np.cos(ang) + 1j * np.sin(ang)
        spec[k0:k1] = 0.5 * (z + zr) + w * (-0.5j) * (z - zr)
    ranges_on_threads(split, m, workers)
    return spec


def rfi_stage1(spec: np.ndarray, p: dict, workers: int = 1) -> np.ndarray:
    """Zap bins whose power exceeds threshold x mean power, scale the
    rest by (N^2/channels)^-1/2 evaluated in float32 as the program
    does (ref: rfi_mitigation_pipe.hpp:50-80).  Works in place."""
    n_spec = spec.size
    nf = np.float32(n_spec)
    coeff = float(np.power(nf * nf / np.float32(p["channels"]),
                           np.float32(-0.5)))

    def total(i0, i1):
        s = spec[i0:i1]
        return float(np.sum(s.real ** 2 + s.imag ** 2))
    mean = sum(ranges_on_threads(total, n_spec, workers)) / n_spec
    limit = p["avg_threshold"] * mean

    def zap(i0, i1):
        s = spec[i0:i1]
        over = s.real ** 2 + s.imag ** 2 > limit
        s *= coeff
        s[over] = 0.0
    ranges_on_threads(zap, n_spec, workers)
    return spec


def rfi_manual(spec: np.ndarray, p: dict) -> np.ndarray:
    """In place: zap the listed frequency ranges, "lo-hi, lo-hi" in MHz: bin =
    round((f - f_low) / bw * (N - 1)), both ends included, ends swapped
    for an inverted band (ref: spectrum/rfi_mitigation.hpp:63-143)."""
    text = p["rfi_freq_list"].strip()
    if not text:
        return spec
    n = spec.size
    for part in text.split(","):
        pieces = [s for s in part.split("-") if s.strip()]
        if len(pieces) != 2:
            continue
        lo_f, hi_f = float(pieces[0]), float(pieces[1])
        if np.signbit(hi_f - lo_f) != np.signbit(p["bandwidth"]):
            lo_f, hi_f = hi_f, lo_f
        lo = int(round((lo_f - p["freq_low"]) / p["bandwidth"] * (n - 1)))
        hi = int(round((hi_f - p["freq_low"]) / p["bandwidth"] * (n - 1)))
        if 0 <= lo <= hi < n:
            spec[lo:hi + 1] = 0.0
    return spec


def chirp(p: dict, n_spec: int, dm: float, i0: int, i1: int,
          dtype=np.float64) -> np.ndarray:
    """Dedispersion factors of bins i0..i1-1 (ref:
    coherent_dedispersion.hpp:133-150): k = D*1e6*dm/f*((f-f_c)/f_c)^2
    turns, factor = exp(-2 pi i frac(k)).  ``dtype=float32`` is the
    control: k reaches ~1e7 turns, past a 24-bit mantissa."""
    t = np.dtype(dtype).type
    f_min = t(p["freq_low"])
    f_c = t(p["freq_low"] + p["bandwidth"])
    df = t(p["bandwidth"] / n_spec)
    i = np.arange(i0, i1, dtype=dtype)
    f = f_min + df * i
    d = (f - f_c) / f_c
    k = t(D * 1e6) * t(dm) / f * (d * d)
    phi = t(-2.0 * np.pi) * np.modf(k)[0]
    return (np.cos(phi) + 1j * np.sin(phi)).astype(np.complex128)


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round to bfloat16 (nearest even) and back, real or complex."""
    if np.iscomplexobj(x):
        return to_bf16(x.real) + 1j * to_bf16(x.imag)
    u = np.asarray(x, dtype=np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


def sk_thresholds(m: int, sk_threshold: float):
    """Acceptance bounds of the SK estimator over m samples, evaluated
    in float32 as the program does (ref: rfi_mitigation.hpp:290-341)."""
    hi = max(sk_threshold, 2.0 - sk_threshold)
    lo = min(sk_threshold, 2.0 - sk_threshold)
    scale = (m - 1.0) / (m + 1.0)
    return np.float32(lo * scale + 1.0), np.float32(hi * scale + 1.0)


def boxcar_lengths(max_boxcar: int, t: int) -> list:
    """1, then 2, 4, ... while <= max and < t
    (ref: signal_detect_pipe.hpp:387-389)."""
    out, b = [1], 2
    while b <= max_boxcar and b < t:
        out.append(b)
        b *= 2
    return out


def detect(ts: np.ndarray, p: dict) -> dict:
    """Boxcar ladder on the mean-subtracted series (ref:
    signal_detect_pipe.hpp:347-424, signal_detect.hpp:32-72): per length
    the count over threshold x rms and the peak over rms."""
    t = ts.size
    acc = np.cumsum(ts)
    counts, peaks, bins = [], [], []
    lengths = boxcar_lengths(p["max_boxcar"], t)
    for b in lengths:
        series = ts if b == 1 else acc[b:] - acc[:-b]
        sigma = float(np.sqrt(np.mean(series * series)))
        counts.append(int(np.sum(series > p["snr_threshold"] * sigma)))
        peaks.append(float(series.max() / max(sigma, 1e-30)))
        bins.append(int(series.argmax()))
    return {"boxcar_lengths": lengths, "signal_counts": counts,
            "snr_peaks": peaks, "peak_bins": bins}


# ------------------------------------------------------------------ chain


def cleaned_spectrum(raw: np.ndarray, p: dict, workers: int = 1,
                     low: str = "") -> np.ndarray:
    """Bytes of one segment -> its RFI-cleaned, normalized spectrum."""
    spec = rfft_drop_nyquist(unpack(raw, p["bits"], workers), workers)
    spec = rfi_manual(rfi_stage1(spec, p, workers), p)
    return to_bf16(spec) if low == "bf16" else spec


def trial(spec: np.ndarray, p: dict, dm: float, workers: int = 1,
          low: str = "") -> dict:
    """One DM on a cleaned spectrum: chirp, waterfall, SK zap, series,
    detection.  Returns the mean-subtracted ``time_series`` (trimmed of
    the reserved tail), ``zero_count`` and ``detect``'s lists."""
    n_spec = spec.size
    ch = min(p["channels"], n_spec)
    wlen = n_spec // ch
    reserved_t = nsamps_reserved(p) // ch
    t = wlen - reserved_t if wlen > reserved_t else wlen
    lo, hi = sk_thresholds(wlen, p["sk_threshold"])
    block = max(1, min(ch, (1 << 21) // wlen))   # ~32 MB of complex128
    cdtype = np.float32 if low == "chirp_f32" else np.float64

    def one(c0):
        c1 = min(ch, c0 + block)
        s = spec[c0 * wlen:c1 * wlen] * chirp(p, n_spec, dm, c0 * wlen,
                                              c1 * wlen, cdtype)
        # unnormalized backward C2C per channel (ref: fft_pipe.hpp:285-344)
        wf = _fft(s.reshape(c1 - c0, wlen), axis=-1, inverse=True) * wlen
        if low == "bf16":
            wf = to_bf16(wf)
        pw = wf.real ** 2 + wf.imag ** 2
        s2, s4 = pw.sum(axis=-1), (pw * pw).sum(axis=-1)
        with np.errstate(invalid="ignore", divide="ignore"):
            sk = wlen * s4 / (s2 * s2)
        zap = (sk > hi) | (sk < lo)          # NaN (an empty row) stays
        pw[zap] = 0.0
        return pw[:, :t].sum(axis=0), int(np.sum(pw[:, 0] == 0))

    starts = range(0, ch, block)
    if workers > 1:
        with ThreadPoolExecutor(workers) as pool:
            parts = list(pool.map(one, starts))
    else:
        parts = [one(c0) for c0 in starts]
    ts = np.sum([a for a, _ in parts], axis=0)
    ts = ts - ts.mean()
    out = detect(ts, p)
    out["time_series"] = ts
    out["zero_count"] = sum(z for _, z in parts)
    return out


def segment(raw: np.ndarray, p: dict, dms=None, workers: int = 1,
            low: str = "") -> list:
    """The whole chain on one segment's bytes, for each DM in ``dms``
    (default: the configuration's own)."""
    if dms is None:
        dms = p["dm_list"] or [p["dm"]]
    spec = cleaned_spectrum(raw, p, workers, low)
    return [trial(spec, p, dm, workers, low) for dm in dms]
