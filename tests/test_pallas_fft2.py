"""The repo's own R2C (ops/pallas_fft2: the column-native passes and the
post pass) vs numpy, and the rules that choose it.

CPU CI runs the kernels in interpret mode, the production legs (4096,
8192) only where a test asks for 2^24 points by name; everything else
patches the leg table down (``small_legs``) or hands the legs in.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from srtb_tpu.ops import fft as F
from srtb_tpu.ops import pallas_fft2 as PF2

ON_TPU = jax.default_backend() == "tpu"
INTERPRET = not ON_TPU

M = 1 << 24  # smallest pallas2 size (legs 4096 x 4096)


def _rand_c64(shape, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def test_segment_rfft_pallas2_strategy():
    """End-to-end R2C through the pallas2 strategy (pack + two-pass C2C +
    Hermitian post) against the monolithic rfft at n = 2^25."""
    n = 2 * M
    rng = np.random.default_rng(11)
    x = rng.standard_normal(n).astype(np.float32)
    want = np.fft.rfft(x.astype(np.float64))[:-1]
    got = np.asarray(F.segment_rfft(
        jnp.asarray(x), "pallas2_interpret" if INTERPRET else "pallas2"))
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-5


def test_rfft_subbyte_pallas2_blocked_planes():
    """The blocked-plane sub-byte R2C with pallas2 plane FFTs (the
    production 2^30 ingest composition) against the f64 oracle: 4-bit
    (count=2, one packed plane of length n/2 = 2^24)."""
    from srtb_tpu.ops import unpack as U

    n = 2 * M
    rng = np.random.default_rng(17)
    raw = rng.integers(0, 256, n // 2, dtype=np.uint8)
    x = np.asarray(U.unpack(jnp.asarray(raw), 4, None)).astype(np.float64)
    want = np.fft.rfft(x)[:-1]
    got = np.asarray(F.rfft_subbyte(
        jnp.asarray(raw), 4,
        "pallas2_interpret" if INTERPRET else "pallas2"))
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-5


def test_segment_rfft_pallas2_small_falls_back():
    """Below the pallas2 window the strategy silently takes the
    pallas-legs four-step — tiny configs must not crash."""
    n = 1 << 16
    rng = np.random.default_rng(13)
    x = rng.standard_normal(n).astype(np.float32)
    want = np.fft.rfft(x.astype(np.float64))[:-1]
    got = np.asarray(F.segment_rfft(
        jnp.asarray(x), "pallas2_interpret" if INTERPRET else "pallas2"))
    assert np.abs(got - want).max() / np.abs(want).max() < 5e-6


@pytest.mark.parametrize("m,top", [
    (1 << 26, (1 << 26) - 1),       # pass 1: kc * (R * j2_0) and kr * j2_0
    (1 << 14, (1 << 14) - 1),       # the post pass's row factor, p * n2
    (1 << 29, (1 << 29) - 1),       # the split's own bound
])
def test_phase_split_is_float64_at_the_largest_residues(m, top):
    """The in-kernel hi/lo phase split (`_phase_cos_sin`: pass 1's two
    twiddle factors, the post pass's row factor) at residues far beyond
    float32's 24-bit mantissa, against float64: the top of the range,
    the middle, and both sides of a multiple of the split's 2^15."""
    r = np.array([top, top - 1, m // 2 + 1, (1 << 15) - 1, 1 << 15,
                  min(top, (1 << 15) + 1), 1, 0], np.int64) % m
    for sign in (-1.0, 1.0):
        c, s_ = jax.jit(lambda q: PF2._phase_cos_sin(q, m, sign))(
            jnp.asarray(r, jnp.int32))
        want = np.exp(sign * 2j * np.pi * r.astype(np.float64) / m)
        err = np.abs((np.asarray(c) + 1j * np.asarray(s_)) - want).max()
        assert err < 2e-6, (m, sign, err)


# ------------------------------------------------------------------
# the column-native passes and the post pass (ISSUE 43): what the
# served plan runs on a chip, held to float64 here in interpret mode at
# shapes a CPU finishes in seconds (the leg tables take any power of
# two; production legs are 4096 and 8192)

def test_cols_production_shapes():
    assert PF2.cols_factor(1 << 24) == (4096, 4096)
    assert PF2.cols_factor(1 << 25) == (4096, 8192)
    assert PF2.cols_factor(1 << 26) == (8192, 8192)
    assert PF2.cols_factor(1 << 27) is None     # 1 GSa/s: no leg of 2^14
    assert PF2.cols_factor(1 << 23) is None
    # J1644: 2-bit, two pairs of blocked planes of 2^25 points
    assert F.own_tail_shape(1 << 27, 2) == (2, 4096, 8192)
    # two streams in sample order, and 4-bit: one transform of 2^26
    assert F.own_tail_shape(1 << 27, 8) == (1, 8192, 8192)
    assert F.own_tail_shape(1 << 27, 4) == (1, 8192, 8192)
    assert F.own_tail_shape(1 << 26, 8) == (1, 4096, 8192)
    assert F.own_tail_shape(1 << 25, 8) == (1, 4096, 4096)
    # 1 GSa/s: whole bytes where one packed transform has no leg pair go
    # as two plane pairs of n/4 points, every fourth sample a plane
    assert F.own_tail_shape(1 << 28, 8) == (2, 8192, 8192)
    assert F.own_tail_shape(1 << 28, -8) == (2, 8192, 8192)
    assert F.own_tail_shape(1 << 28, 2) == (2, 8192, 8192)
    assert F.own_tail_shape(1 << 29, 8) is None
    assert F.own_tail_shape(1 << 27, 1) is None  # four pairs: no post


@pytest.mark.parametrize("n1,n2,batch,inverse", [
    (256, 128, 2, False), (128, 256, 1, True), (1024, 512, 1, False),
    # the other direction of each asymmetric pair of legs
    (256, 128, 1, True), (128, 256, 2, False)])
def test_cols_transform_matches_numpy(n1, n2, batch, inverse):
    m = n1 * n2
    x = _rand_c64((batch, m), 11)
    want = (np.fft.ifft(x.astype(np.complex128), norm="forward") if inverse
            else np.fft.fft(x.astype(np.complex128)))
    got = np.asarray(PF2.fft2_cols(jnp.asarray(x), inverse=inverse,
                                   interpret=True, factor=(n1, n2)))
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-6


@pytest.mark.parametrize("log2m,lead,inverse", [
    (14, (), False),            # legs 128 x 128, as 2^24 is 4096 x 4096
    (14, (), True),
    (15, (2,), False),          # 128 x 256, as 2^25 is 4096 x 8192
    (15, (), True),
    (16, (2, 2), False),        # 256 x 256; two leading dimensions
])
def test_cols_transform_finds_its_legs(small_legs, log2m, lead, inverse):
    """`fft2_cols` with no legs handed in (as `_pallas2_or_fallback`
    calls it): the legs of `cols_factor`, any leading dimensions kept,
    both directions unnormalized."""
    m = 1 << log2m
    x = _rand_c64((*lead, m), 19 + log2m)
    want = (np.fft.ifft(x.astype(np.complex128), norm="forward") if inverse
            else np.fft.fft(x.astype(np.complex128)))
    got = np.asarray(PF2.fft2_cols(jnp.asarray(x), inverse=inverse,
                                   interpret=True))
    assert got.shape == x.shape
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-6


@pytest.mark.parametrize("p,n2,n1", [(2, 128, 256), (1, 64, 128)])
def test_post_pass_equals_the_xla_finish_and_post(p, n2, n1):
    """`post_spectrum` against `finish_rfft_subbyte(premul=, epilogue=)`:
    the butterfly, the Hermitian post, both banks, RFI s1's zap and
    normalisation, two manual ranges (one in each half)."""
    from srtb_tpu.ops import rfi
    big_m = n1 * n2
    m = p * big_m
    a = _rand_c64((p, big_m), 5)
    c = np.exp(1j * np.random.default_rng(6).uniform(0, 2 * np.pi, m)
               ).astype(np.complex64)
    c_ri = jnp.stack([jnp.real(c), jnp.imag(c)])
    cw = jnp.asarray(c) * F._iota_phase(m, 2 * m, -1.0)
    cw_ri = jnp.stack([jnp.real(cw), jnp.imag(cw)])
    thr, norm = 1.5, 0.37
    bins = [(5, 40), (m - 300, m - 290)]
    mask = np.zeros(m, bool)
    for lo, hi in bins:
        mask[lo:hi + 1] = True

    def epilogue(zf, spec):
        spec = rfi.mitigate_rfi_s1_given_mean(
            spec, rfi.mean_power_packed(zf), thr, norm)
        return rfi.mitigate_rfi_manual(spec, jnp.asarray(mask))
    want = np.asarray(F.finish_rfft_subbyte(
        jnp.asarray(a), True, epilogue=epilogue,
        premul=(jnp.asarray(c), cw)))
    a3 = jnp.asarray(a).reshape(p, n2, n1)
    s_re, s_im = PF2.post_spectrum(
        jnp.real(a3), jnp.imag(a3), PF2.post_bank(c_ri, cw_ri),
        threshold=thr, norm=norm, bins=bins, interpret=True)
    got = np.asarray(s_re) + 1j * np.asarray(s_im)
    assert ((got == 0) == (want == 0)).all()
    assert (want == 0).sum() > 100 and (want != 0).sum() > m // 2
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-6


V5E = 16_911_433_728     # ``bytes_limit`` of one v5e (PERF.md section 4)


@pytest.mark.parametrize("n,bits,streams,on_tpu,limit,own_plan,want", [
    (1 << 27, 2, 1, True, V5E, True, "pallas2"),        # J1644
    (1 << 27, 2, 2, True, V5E, True, "pallas2"),        # both polarisations
    (1 << 27, 2, 1, False, None, True, "monolithic"),   # not on a TPU
    (1 << 27, 2, 1, False, V5E, True, "monolithic"),
    # shapes the kernels take and no chip has run: whole bytes and 4-bit
    # (one plane pair, legs 8192 x 8192), 2^26 and 2^25 samples
    (1 << 27, 8, 1, True, V5E, True, "monolithic"),
    (1 << 27, 4, 1, True, V5E, True, "monolithic"),
    (1 << 26, 2, 1, True, V5E, True, "monolithic"),
    (1 << 25, 8, 1, True, V5E, True, "monolithic"),
    # 1 GSa/s: two plane pairs of 2^26 points, read faster in PR 48
    (1 << 28, 8, 1, True, V5E, True, "pallas2"),
    (1 << 28, 8, 1, False, V5E, True, "monolithic"),    # not on a TPU
    (1 << 28, 8, 1, True, 8_000_000_000, True, "monolithic"),   # no room
    # a chip that holds XLA's plan (9.76 GB) and not what the own
    # transform's plan read at its peak (12.02 GB) keeps XLA's
    (1 << 28, 8, 1, True, 11_000_000_000, True, "monolithic"),
    (1 << 28, 8, 1, True, 12_500_000_000, True, "pallas2"),
    (1 << 28, 8, 1, True, V5E, False, "monolithic"),    # not the own plan
    # the same shape with other bits or streams: no chip has run it
    (1 << 28, 2, 1, True, V5E, True, "monolithic"),
    (1 << 28, 8, 2, True, V5E, True, "monolithic"),
    (1 << 28, 0, 1, True, V5E, True, "monolithic"),     # wider than a byte
    (1 << 27, 1, 1, True, V5E, True, "monolithic"),     # four plane pairs
    (1 << 27, 2, 1, True, 4_000_000_000, True, "monolithic"),   # no room
    (1 << 27, 2, 2, True, 5_000_000_000, True, "monolithic"),
    (1 << 27, 2, 1, True, 5_000_000_000, True, "pallas2"),
    (1 << 27, 2, 1, True, None, True, "pallas2"),       # no limit reported
    # a plan that would not run the own transform whole (staged, the
    # tail off, use_pallas, under a vmap: ``own_r2c_hostable``)
    (1 << 27, 2, 1, True, V5E, False, "monolithic"),
    (1 << 30, 2, 1, True, V5E, True, "four_step"),      # the staged plan
    (1 << 30, 2, 1, False, None, True, "four_step"),
    (1 << 16, 2, 1, True, V5E, True, "monolithic"),     # below the legs
])
def test_resolve_strategy_table(n, bits, streams, on_tpu, limit, own_plan,
                                want):
    assert F.resolve_strategy(n, "auto", bits=bits, streams=streams,
                              on_tpu=on_tpu, bytes_limit=limit,
                              own_plan=own_plan) == want
    # a strategy asked for by name is nobody's to change
    assert F.resolve_strategy(n, "mxu", bits=bits, on_tpu=on_tpu) == "mxu"
    # and with nothing said of the platform, XLA's or the staged plan's
    assert F.resolve_strategy(n, "auto", bits=bits) in (
        "monolithic", "four_step")


def _j1644(**kw):
    from srtb_tpu.config import Config
    return Config(**{**dict(
        baseband_input_count=1 << 27, baseband_input_bits=2,
        baseband_format_type="simple", baseband_freq_low=1405.0 + 32,
        baseband_bandwidth=-64.0, baseband_sample_rate=128e6, dm=-478.8,
        spectrum_channel_count=1 << 11), **kw})


@pytest.mark.parametrize("kw,staged,want", [
    ({}, False, "pallas2"),
    ({"baseband_format_type": "interleaved_samples_2"}, False, "pallas2"),
    # the plans "auto" must not hand a pallas2 that was never measured:
    # each would run the two passes with XLA's Hermitian post and tail
    ({"use_pallas": True}, False, "monolithic"),
    ({"use_pallas": True, "use_pallas_sk": True}, False, "monolithic"),
    ({"fused_tail": "off"}, False, "monolithic"),
    ({}, True, "monolithic"),                           # forced staged
    ({"micro_batch_segments": 2}, False, "monolithic"),
    ({"fleet_batch_max": 4}, False, "monolithic"),
    ({"baseband_input_bits": 8}, False, "monolithic"),
    ({"baseband_input_bits": 4}, False, "monolithic"),
    # 2^28 samples: the 1 GSa/s segment of one-byte samples and nothing
    # else (2 bits, two streams and 16 bits were never read)
    ({"baseband_input_count": 1 << 28, "baseband_input_bits": 8}, False,
     "pallas2"),
    ({"baseband_input_count": 1 << 28, "baseband_input_bits": -8}, False,
     "pallas2"),
    ({"baseband_input_count": 1 << 28}, False, "monolithic"),
    ({"baseband_input_count": 1 << 28, "baseband_input_bits": 8,
      "baseband_format_type": "interleaved_samples_2"}, False, "monolithic"),
    ({"baseband_input_count": 1 << 28, "baseband_input_bits": 16}, False,
     "monolithic"),
    # by name it is the caller's to choose, whatever the plan
    ({"fft_strategy": "pallas2", "use_pallas": True}, False, "pallas2"),
    ({"fft_strategy": "monolithic"}, False, "monolithic"),
])
def test_segment_strategy_decides_once_on_a_tpu(monkeypatch, kw, staged,
                                                want):
    """On a v5e "auto" names pallas2 only for the plan that runs the
    repo's own transform whole, and `own_tail`'s rule is the same one."""
    from srtb_tpu.pipeline import segment as SG
    from srtb_tpu.utils import platform
    monkeypatch.setattr(platform, "on_accelerator", lambda: True)
    monkeypatch.setattr(platform, "device_bytes_limit", lambda: V5E)
    cfg = _j1644(**kw)
    got = SG.segment_strategy(cfg, staged)
    assert got == want
    if cfg.fft_strategy == "auto":
        assert got != "pallas2" or SG.own_r2c_hostable(cfg, staged)
        if not staged:
            # the fused tail follows: on exactly where the own transform is
            assert SG.fused_tail_resolves(cfg, staged) == (got == "pallas2")
    # off the chip nothing changes plan
    monkeypatch.setattr(platform, "on_accelerator", lambda: False)
    assert SG.segment_strategy(cfg, staged) == (
        want if cfg.fft_strategy != "auto" else "monolithic")


@pytest.mark.parametrize("log2m,on_tpu,strategy,raises", [
    (27, True, "pallas2", True),        # 1 GSa/s by name, on a chip
    (29, True, "pallas2", True),
    (26, True, "pallas2", False),       # the column-native passes
    (27, False, "pallas2", True),       # off the chip: the same error
    (27, True, "pallas2_interpret", True),
])
def test_no_transform_above_the_column_native_lengths(monkeypatch, log2m,
                                                      on_tpu, strategy,
                                                      raises):
    """`fft_strategy pallas2` above 2^26 points: the one spelling of the
    passes that factored 2^27 to 2^29 went in PR 50 (Mosaic refused it
    for a v5e), so the dispatch says so on every backend, interpret
    mode included, and routes nowhere."""
    from srtb_tpu.utils import platform
    monkeypatch.setattr(platform, "on_accelerator", lambda: on_tpu)
    z = jax.ShapeDtypeStruct((1 << log2m,), jnp.complex64)

    def trace():
        return jax.eval_shape(
            lambda a: F._pallas2_or_fallback(a, strategy), z)
    if raises:
        with pytest.raises(ValueError, match="no transform of .* points"):
            trace()
    else:
        assert trace().shape == z.shape


_SMALL_LEGS = (128, 256)


@pytest.fixture
def small_legs(monkeypatch):
    """The column-native passes at CPU sizes: a table of two legs as in
    production, 128 and 256, so 2^14 to 2^16 points have a leg pair and
    2^17 (whole bytes at 2^18 samples) has none."""
    def factor(m):
        for n1 in _SMALL_LEGS:
            if m % n1 == 0 and m // n1 in _SMALL_LEGS:
                return n1, m // n1
        return None
    monkeypatch.setattr(PF2, "cols_factor", factor)


def _plan(n, bits, fmt, strategy, window="rectangle"):
    from srtb_tpu.config import Config
    from srtb_tpu.pipeline.segment import SegmentProcessor
    cfg = Config(
        baseband_input_count=n, baseband_input_bits=bits,
        baseband_format_type=fmt, baseband_freq_low=1405.0 + 32,
        baseband_bandwidth=-64.0, baseband_sample_rate=128e6, dm=-0.3,
        spectrum_channel_count=64,
        mitigate_rfi_average_method_threshold=1.5,
        mitigate_rfi_spectral_kurtosis_threshold=1.05,
        mitigate_rfi_freq_list="1418-1422",
        signal_detect_max_boxcar_length=64, baseband_reserve_sample=True,
        fft_strategy=strategy)
    proc = SegmentProcessor(cfg, window_name=window)
    raw = np.random.default_rng(3).integers(
        0, 256, cfg.segment_bytes(proc.fmt.data_stream_count),
        dtype=np.uint8)
    wf, result = proc.process(raw)
    return proc, np.asarray(wf), result


@pytest.mark.parametrize("n,bits,fmt,window", [
    (1 << 16, 2, "simple", "rectangle"),        # blocked planes, p = 2
    (1 << 15, 8, "simple", "rectangle"),        # sample order, p = 1
    (1 << 16, 2, "interleaved_samples_2", "rectangle"),  # stream by stream
    # a window goes in with the planes, whichever way they are made
    (1 << 16, 2, "simple", "hamming"),
    (1 << 16, 2, "interleaved_samples_2", "hamming"),
    (1 << 15, 8, "interleaved_samples_2", "hamming"),
    # whole bytes whose packed length has no leg pair (2^17 points here,
    # 2^27 on a chip): p = 2, every fourth sample a plane
    (1 << 18, 8, "simple", "rectangle"),
    (1 << 18, 8, "simple", "hamming"),
    (1 << 18, 8, "interleaved_samples_2", "rectangle"),
])
def test_own_tail_plan_equals_the_monolithic_chain(small_legs, n, bits,
                                                   fmt, window):
    """The served plan with the repo's own transform (two passes and the
    post pass that carries RFI s1, the manual zap and the chirp) against
    the unfused monolithic chain (`_spectrum_tail`), to float32."""
    from srtb_tpu.utils.metrics import metrics
    own, wf1, r1 = _plan(n, bits, fmt, "pallas2", window)
    assert own.own_tail and own.plan_name == "fused:pallas2+ftail+ring"
    assert F.own_tail_shape(n, bits)[0] == (
        1 if n == 1 << 15 else 2)
    assert metrics.get("segment_r2c_own") == 1
    mono, wf0, r0 = _plan(n, bits, fmt, "monolithic", window)
    assert not mono.own_tail and mono.plan_name == "fused:monolithic+ring"
    assert metrics.get("segment_r2c_own") == 0
    assert own.plan_signature() != mono.plan_signature()
    assert '"r2c"' not in mono.plan_signature()
    assert ((wf1 == 0) == (wf0 == 0)).all()
    # the waterfall divides the window out again: 1 / 0.087 at a
    # hamming window's edges, on both sides' rounding
    tol = 2e-6 if window == "rectangle" else 2e-5
    assert np.abs(wf1 - wf0).max() / np.abs(wf0).max() < tol
    for f1, f0 in zip(r1, r0):
        if isinstance(f1, jax.Array) and f1.dtype == jnp.float32:
            f1, f0 = np.asarray(f1), np.asarray(f0)
            assert np.abs(f1 - f0).max() <= 1e-5 * max(np.abs(f0).max(), 1)


def test_four_planes_of_a_byte_sequence_give_numpys_rfft():
    """Whole bytes at p = 2: every fourth sample a plane
    (`deal_planes` on the bytes, the cast plane by plane), two passes a
    plane pair and the post pass's join, against NumPy's float64
    `rfft` of the samples in order; a unit chirp, nothing zapped."""
    n1 = n2 = 256
    n = 4 * n1 * n2
    m = n // 2
    raw = np.random.default_rng(23).integers(0, 256, n, dtype=np.uint8)
    planes = jnp.stack([q.view(jnp.int8).astype(jnp.float32)
                        for q in F.deal_planes(jnp.asarray(raw), 4)])
    assert planes.shape == (4, n // 4)
    x = raw.view(np.int8).astype(np.float64)
    assert (np.asarray(planes) == x.reshape(-1, 4).T).all()
    c_ri = jnp.stack([jnp.ones(m), jnp.zeros(m)])
    w = F._iota_phase(m, 2 * m, -1.0)
    got = np.asarray(F.own_spectrum(
        planes, (n1, n2), PF2.post_bank(
            c_ri, jnp.stack([jnp.real(w), jnp.imag(w)])),
        threshold=1e20, norm=1.0, bins=(), interpret=True))
    want = np.fft.rfft(x)[:-1]
    assert got.shape == want.shape
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-6


@pytest.mark.parametrize("fmt,bits,want", [
    ("simple", 8, 8), ("simple", -8, 8), ("interleaved_samples_2", 8, 8),
    ("naocpsr_snap1", -8, 8), ("gznupsr_a1", 8, 8),
    ("gznupsr_a1_v1", 8, 8),
    ("simple", 2, 2), ("interleaved_samples_2", 4, 4),
    # dealt out as floats or not at all: not the own plan's
    ("simple", 16, 0), ("simple", -16, 0), ("simple", 32, 0),
    ("naocpsr_snap1", 2, 0),
])
def test_own_plan_takes_samples_of_a_byte_or_less(small_legs, fmt, bits,
                                                  want):
    """The own transform takes a stream's bytes as they lie: sub-byte
    fields MSB first, or a byte a sample, whose cast (`one_byte_cast`,
    unscoped, so that it runs under the R2C's name) is the one
    `unpack_stream` makes.  Anything else is refused, by name too."""
    from srtb_tpu.io import formats
    from srtb_tpu.ops import unpack as U
    from srtb_tpu.pipeline import segment as SG
    cfg = _j1644(baseband_input_count=1 << 16, baseband_input_bits=bits,
                 baseband_format_type=fmt, fft_strategy="pallas2")
    assert SG._r2c_sample_bits(cfg) == want
    assert SG.own_r2c_hostable(cfg, False) == bool(want)
    variant = formats.resolve(fmt).unpack_variant
    cast = U.one_byte_cast(variant, bits)
    assert (cast is not None) == (want == 8)
    if cast is not None:
        raw = jnp.asarray(np.random.default_rng(5).integers(
            0, 256, 4096, dtype=np.uint8))
        got = cast(raw)
        assert got.dtype == jnp.float32
        assert (np.asarray(got) == np.asarray(
            U.unpack_stream(raw, variant, bits))).all()


def test_fused_tail_through_premul_and_epilogue_equals_unfused():
    """The fused tail's XLA spelling (`premul` + `epilogue`, any
    non-monolithic strategy with a bank) against the unfused chain."""
    fused, wf1, _r1 = _plan(1 << 15, 8, "simple", "four_step")
    assert fused.fused_tail and not fused.own_tail
    _mono, wf0, _r0 = _plan(1 << 15, 8, "simple", "monolithic")
    assert ((wf1 == 0) == (wf0 == 0)).all()
    assert np.abs(wf1 - wf0).max() / np.abs(wf0).max() < 2e-6
