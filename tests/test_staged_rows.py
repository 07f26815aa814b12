"""The plan a 2^30-sample segment resolves to by itself
(``staged:four_step+rows``: three programs a segment, each walking the
canonical boundary ``[2, S, F, T]`` in blocks, the half-size C2C split
F x T), at sizes the CPU holds.

The deployment is ``benchmark/configs/j1644_2p30.json`` (the J1644-4559
recording at the segment its own cfg states: 2-bit, reserve 0, zap
1418-1422 MHz) with the cuts listed at ``CUTS``: the segment 2^30 -> 2^22
and the channels 2^11 -> 2^3, which keeps the deployment's 2^18 time
samples a channel (so a channel's backward C2C is the four-step inside a
block that the deployment's is), and the DM scaled with the segment.
``segment.STAGED_MIN_N`` and ``FUSED_TAIL_DF64_MAX_SPECTRUM`` are patched
down by the tests: no option chooses the plan.

(a) through ``Pipeline`` from a file to its sinks, a pulsed and a quiet
    segment against the benchmark's float64 chain
    (``benchmark/reference/chain.py``) by the benchmark's own numbers
    (``benchmark/check.py``);
(b) the blocked spellings against the whole-plane spellings they stand
    in for: the Hermitian post, the in-place second half of the
    four-step, stage (a) in blocks of rows, and the whole plan.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check
from benchmark.reference import chain
from srtb_tpu.config import Config
from srtb_tpu.io import synth
from srtb_tpu.ops import fft as F
from srtb_tpu.pipeline import segment
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.pipeline.segment import SegmentProcessor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "j1644_2p30.json")) as _f:
    FULL = json.load(_f)["options"]
LOG2N = 22
CUTS = dict(baseband_input_count=f"2 ** {LOG2N}",
            spectrum_channel_count="2 ** 3",
            dm=-478.8 / (1 << (30 - LOG2N)))
OPTIONS = dict(FULL, **CUTS)
N = 1 << LOG2N

# float32 transforms of 2^21 points against float64, in the benchmark's
# units: the series' widest gap in units of its own noise, the boxcars'
# peak S/N relative.  Read here (XLA:CPU): series 1.6e-5 on the pulsed
# segment and 3.5e-6 on the quiet one, S/N 1.4e-7 (the cell's limits at
# 2^30 are set from the chip's readings, PERF.md section 6); the float64
# chain's own controls read 0.21 and 1.0e-3 with the spectrum and the
# waterfall held in bfloat16, 0.72 and 7.0e-3 with the chirp's phase in
# float32 (test_the_lower_precision_controls_fail_here).
SERIES_LIMIT = 2e-4
SNR_LIMIT = 2e-5


@pytest.fixture
def staged_at_this_size(monkeypatch):
    """What a 2^30 segment meets, at 2^22: the staged plan by the size
    rule, its tail unfused by the bankless rule."""
    monkeypatch.setattr(segment, "STAGED_MIN_N", N)
    monkeypatch.setattr(segment, "FUSED_TAIL_DF64_MAX_SPECTRUM", N >> 4)


def _config(**extra) -> Config:
    return Config.from_args([f"--{k}={v}" for k, v in
                             dict(OPTIONS, **extra).items()])


def _file_bytes() -> np.ndarray:
    """Two segments of seeded 2-bit noise, a dispersed pulse in the
    middle of the first."""
    cfg = _config()
    return synth.make_dispersed_baseband(
        2 * N, cfg.baseband_freq_low, cfg.baseband_bandwidth, cfg.dm,
        [N // 2], nbits=2, pulse_amp=12.0, seed=40)


class _Capture:
    def __init__(self):
        self.rows = []

    def push(self, work, has_signal):
        d = work.detect
        self.rows.append({
            "fired": bool(has_signal),
            "series": np.array(d.time_series, np.float32)[0],
            "snr_peaks": np.array(d.snr_peaks, np.float32)[0],
            "zero_count": int(np.asarray(d.zero_count).reshape(-1)[0])})


@pytest.fixture(scope="module")
def reference():
    p = chain.params_from_config(OPTIONS)
    raw = _file_bytes()
    seg = chain.segment_bytes(p)
    return [chain.segment(raw[k * seg:(k + 1) * seg], p, workers=2)[0]
            for k in range(2)]


@pytest.fixture
def served(tmp_path, staged_at_this_size):
    """The two segments through ``Pipeline`` as ``tools/main.py`` builds
    it: the program's reader, its own sinks, one capture sink behind."""
    path = tmp_path / "baseband.bin"
    _file_bytes().tofile(path)
    cfg = _config(input_file_path=path,
                  baseband_output_file_prefix=tmp_path / "out_",
                  telemetry_journal_path=tmp_path / "journal.jsonl")
    pipe = Pipeline(cfg)
    cap = _Capture()
    pipe.sinks.append(cap)
    try:
        plan = pipe.processor.plan_name
        pipe.run()
    finally:
        pipe.close()
    with open(tmp_path / "journal.jsonl") as f:
        spans = [json.loads(ln) for ln in f]
    return plan, cap.rows, [s for s in spans
                            if s.get("type") == "segment_span"]


@pytest.mark.parametrize("k,kind", [(0, "pulse"), (1, "quiet")])
def test_the_plan_of_a_2p30_segment_against_the_float64_chain(
        served, reference, k, kind):
    plan, rows, spans = served
    assert plan == "staged:monolithic+rows"
    assert len(rows) == 2
    got, want = rows[k], reference[k]
    assert got["fired"] == (kind == "pulse")
    assert got["zero_count"] == want["zero_count"]
    assert check.series_gap(got["series"], want["time_series"]) \
        < SERIES_LIMIT
    if kind == "pulse":
        assert check.relative_gap(got["snr_peaks"], want["snr_peaks"]) \
            < SNR_LIMIT
        assert int(np.argmax(got["series"])) == want["peak_bins"][0]
    # the journal tells the three dispatches apart, inside ``enqueue``
    ms = spans[k]["stages_ms"]
    assert {"enqueue_a", "enqueue_b", "enqueue_c"} <= set(ms)
    assert ms["enqueue_a"] + ms["enqueue_b"] + ms["enqueue_c"] \
        <= ms["enqueue"] + 1e-3
    assert spans[k]["active_plan"] == plan


@pytest.mark.parametrize("low", ["bf16", "chirp_f32"])
def test_the_lower_precision_controls_fail_here(reference, low):
    """The float64 chain with its spectrum and waterfall held in
    bfloat16, or its chirp's phase evaluated in float32, reads over both
    limits on the pulsed segment."""
    p = chain.params_from_config(OPTIONS)
    raw = _file_bytes()[:chain.segment_bytes(p)]
    got = chain.segment(raw, p, workers=2, low=low)[0]
    assert check.series_gap(got["time_series"],
                            reference[0]["time_series"]) > 10 * SERIES_LIMIT
    assert check.relative_gap(got["snr_peaks"],
                              reference[0]["snr_peaks"]) > 10 * SNR_LIMIT


# ---------------------------------------------- blocked against whole

@pytest.mark.parametrize("rows,cols,blocks", [(8, 512, 8), (16, 256, 4),
                                              (2, 1024, 2), (64, 128, 32),
                                              (6, 256, 6)])
def test_hermitian_post_over_row_blocks_is_the_whole_plane_post(
        rows, cols, blocks):
    rng = np.random.default_rng(rows * cols)
    m = rows * cols
    z = (rng.standard_normal(m) + 1j * rng.standard_normal(m)).astype(
        np.complex64)
    want = np.asarray(F.hermitian_rfft_post(jnp.asarray(z)[None],
                                            drop_nyquist=True))[0]
    z_ri = jnp.stack([jnp.real(z), jnp.imag(z)]).reshape(2, 1, rows, cols)
    got = np.asarray(jax.jit(F.hermitian_rfft_post_rows,
                             static_argnums=1)(z_ri, blocks))
    got = (got[0] + 1j * got[1]).reshape(-1)
    # the twiddle is a product of two factors where the whole-plane one
    # is a product of two others: float32 rounding apart
    assert np.max(np.abs(got - want)) < 1e-6 * np.max(np.abs(want))
    assert F.block_count(rows, m, pairs=True) == min(rows, 8) \
        - min(rows, 8) % 2


@pytest.mark.parametrize("log2m,rows,blocks", [(15, 64, 4), (16, 8, 8),
                                               (12, 4, 2), (15, 256, 1)])
def test_second_half_over_column_blocks_is_the_whole_plane_transform(
        log2m, rows, blocks):
    rng = np.random.default_rng(log2m)
    m = 1 << log2m
    z = (rng.standard_normal(m) + 1j * rng.standard_normal(m)).astype(
        np.complex64)
    a = F.four_step_stage1_cols(jnp.asarray(z).reshape(1, m // rows, rows))
    want = np.asarray(F.four_step_stage2(a))[0]          # [k], whole
    a_ri = jnp.stack([jnp.real(a), jnp.imag(a)])         # [2, 1, F, T]
    got = np.asarray(jax.jit(F.four_step_stage2_cols,
                             static_argnums=1)(a_ri, blocks))
    got = (got[0] + 1j * got[1]).reshape(-1)
    exact = np.fft.fft(z.astype(np.complex128))
    scale = np.max(np.abs(exact))
    assert np.max(np.abs(got - want)) < 1e-6 * scale
    assert np.max(np.abs(got - exact)) < 1e-6 * scale


def test_the_blocked_plan_is_the_whole_plane_plan(staged_at_this_size,
                                                  monkeypatch):
    """One segment through the plan in blocks (stage (a) by rows, stage
    (b)'s transform by columns and post by row pairs, stage (c) by
    channels) and through the whole-plane spellings they stand in for."""
    raw = _file_bytes()[:N // 4]
    cfg = _config()
    blocked = SegmentProcessor(cfg)
    assert blocked.plan_name == "staged:monolithic+rows"
    assert blocked.staged_rows == 8 and blocked._stage_a_block_rows() == 2
    assert F.block_count(1 << 11, 1 << 29, pairs=True) == 128   # the cell's
    wf_b, det_b = blocked.process(raw)
    a_rows = np.asarray(jax.jit(blocked._stage_a)(jnp.asarray(raw)))
    monkeypatch.setattr(F, "block_count", lambda *a, **kw: 0)
    whole = SegmentProcessor(cfg)
    assert whole.plan_name == "staged:monolithic"
    wf_w, det_w = whole.process(raw)
    wf_b, wf_w = np.asarray(wf_b), np.asarray(wf_w)
    assert wf_b.shape == wf_w.shape == (2, 1, 8, 1 << 18)
    assert np.max(np.abs(wf_b - wf_w)) < 1e-5 * np.max(np.abs(wf_w))
    ts_b = np.asarray(det_b.time_series)
    ts_w = np.asarray(det_w.time_series)
    assert check.series_gap(ts_b, ts_w) < 1e-4
    assert np.array_equal(np.asarray(det_b.zero_count),
                          np.asarray(det_w.zero_count))
    assert np.array_equal(np.asarray(det_b.signal_counts),
                          np.asarray(det_w.signal_counts))
    # stage (a) in blocks of rows IS the whole-plane first half in the
    # F x T split: no arithmetic differs
    monkeypatch.setattr(SegmentProcessor, "_stage_a_block_rows",
                        lambda self: 0)
    a_whole = np.asarray(jax.jit(blocked._stage_a)(jnp.asarray(raw)))
    assert np.array_equal(a_rows, a_whole)


# ------------------------------- every width the blocks take, windowed
# and zapped (the parity matrix of the front-fused staged family, gone in
# PR 50, pointed at the plan two cells run)

SMALL_LOG2N = 16


@pytest.fixture
def staged_at_2p16(monkeypatch):
    monkeypatch.setattr(segment, "STAGED_MIN_N", 1 << SMALL_LOG2N)
    monkeypatch.setattr(segment, "FUSED_TAIL_DF64_MAX_SPECTRUM",
                        1 << (SMALL_LOG2N - 4))


@pytest.mark.parametrize("what", ["plain", "hamming", "zapped"])
@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_blocks_against_whole_planes_at_every_width(bits, what,
                                                    staged_at_2p16,
                                                    monkeypatch):
    """`staged:monolithic+rows` against the whole-plane stages
    (`_stage_a_nat` / `_stage_b_nat` / `_stage_c_nat`) on one pulsed
    segment of 1-, 2-, 4- and 8-bit simple samples: as it comes, under
    a Hamming window (which stage (a) multiplies in block by block and
    stage (c) divides out of each channel), and with two manual zap
    ranges, one at the band's edge (a block compares its own bin
    indices where the whole planes take a mask)."""
    n = 1 << SMALL_LOG2N
    cfg = _config(baseband_input_count=n, baseband_input_bits=bits,
                  spectrum_channel_count="2 ** 3",
                  dm=-478.8 / (1 << (30 - SMALL_LOG2N)) * 64,
                  signal_detect_max_boxcar_length=64).replace(
        mitigate_rfi_freq_list="1436.5-1437, 1418-1422"
        if what == "zapped" else "")
    window = "hamming" if what == "hamming" else "rectangle"
    if what == "hamming":
        # a channel's 2^12 time samples keep the segment-long window's
        # shape, which s2 at the source's 1.05 takes for RFI in every
        # channel: out of the way, so that every channel is compared
        cfg = cfg.replace(mitigate_rfi_spectral_kurtosis_threshold=1e9)
    # (a pulse the source's own thresholds leave channels around: 12
    # sigma in 2^12 time samples a channel is zapped as RFI by s2)
    raw = synth.make_dispersed_baseband(
        n, cfg.baseband_freq_low, cfg.baseband_bandwidth, cfg.dm,
        [n // 2], nbits=bits, pulse_amp=3.0, seed=50 + bits)
    blocked = SegmentProcessor(cfg, window_name=window)
    assert blocked.plan_name == "staged:monolithic+rows"
    assert blocked.staged_rows == 8
    assert blocked._stage_a_block_rows() == {1: 4, 2: 2, 4: 1, 8: 1}[bits]
    assert (blocked.rfi_mask is None) and \
        bool(blocked.rfi_bins) == (what == "zapped")
    wf_b, det_b = blocked.process(raw)
    monkeypatch.setattr(F, "block_count", lambda *a, **kw: 0)
    whole = SegmentProcessor(cfg, window_name=window)
    assert whole.plan_name == "staged:monolithic"
    assert (whole.rfi_mask is not None) == (what == "zapped")
    wf_w, det_w = whole.process(raw)
    wf_b, wf_w = np.asarray(wf_b), np.asarray(wf_w)
    assert wf_b.shape == wf_w.shape == (2, 1, 8, n // 16)
    assert ((wf_b == 0) == (wf_w == 0)).all()
    assert (wf_w == 0).mean() < 1
    # the waterfall divides the window out again: 1 / 0.087 at a
    # Hamming window's edges, on both sides' rounding
    tol = 2e-5 if what == "hamming" else 2e-6
    assert np.max(np.abs(wf_b - wf_w)) < tol * np.max(np.abs(wf_w))
    assert check.series_gap(np.asarray(det_b.time_series),
                            np.asarray(det_w.time_series)) < 1e-4
    assert np.array_equal(np.asarray(det_b.zero_count),
                          np.asarray(det_w.zero_count))
    assert np.array_equal(np.asarray(det_b.signal_counts),
                          np.asarray(det_w.signal_counts))
    if what != "hamming":       # (the window's shape hides 3 sigma)
        assert int(np.asarray(det_w.signal_counts).sum()) > 0
