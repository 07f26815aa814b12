"""The two-polarisation J1644-4559 deployment (``j1644_2pol_2p27``,
ISSUE 36) at a size the CPU holds: 2^16 samples a stream of 2-bit
samples, both polarisations byte-interleaved in one file ("1212",
``baseband_format_type interleaved_samples_2``) and split on the device,
the J1644 band (64 MHz inverted below 1437 MHz, zap 1418-1422 MHz) with
the DM scaled with the segment (-478.8 / 2^11) so that the overlap-save
reserve is the deployment's 17.6 % of every segment, under the 3/11 at
which a mid-stride pulse is still searched.  Cut besides, as the
benchmark's ``tiny_j1644`` cuts them: 2^6 channels, SK threshold
1.05 -> 1.4 (512 time samples a row, not 32768), boxcars 256 -> 16.

The reference is ``oracle_utils``: ``oracle_deinterleave`` +
``oracle_stream_chain``, float64 NumPy re-derived from the upstream
sources, on each de-interleaved stream.

(a) ``Pipeline`` from a file to its sinks on the fused plan, the ring
    (one cold dispatch, then warm ones) and the staged plan: waterfall,
    time series, zapped rows and detections of both streams against the
    oracle, each tolerance beside its reason and beside what a bfloat16
    result would read;
(b) stream 0 of the two-stream file is the one-stream run on the same
    bytes de-interleaved on the host, bit for bit;
(c) a pulse in stream 1 only: ``detections_by_stream`` is ``[0, k]``, the
    candidate's series are named ``.s1.<b>.tim`` and its ``.bin`` holds
    both polarisations;
(d) ring on against ring off: the same bytes reach ``_process``;
    ``ring_carry_bytes`` is the reserve's bytes of BOTH streams;
(e) the split is bit-identical to the oracle's for 1, 2, 4, 8 and -8 bits.
"""

import functools
import glob
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from oracle_utils import oracle_deinterleave, oracle_stream_chain

from srtb_tpu.config import Config
from srtb_tpu.io import synth
from srtb_tpu.ops import dedisperse as dd
from srtb_tpu.ops import detect as det
from srtb_tpu.ops import unpack as U
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.pipeline.segment import SegmentProcessor, waterfall_to_numpy
from srtb_tpu.utils.metrics import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "j1644_2pol_2p27.json")) as _f:
    FULL = json.load(_f)["options"]
# the cuts, listed: size, channels, the DM with the size, and the two
# thresholds tiny_j1644 cuts for the shorter rows
SCALE = 1 << 11
TINY = dict(FULL, baseband_input_count="2 ** 16",
            spectrum_channel_count="2 ** 6", dm=-478.8 / SCALE,
            mitigate_rfi_spectral_kurtosis_threshold=1.4,
            signal_detect_max_boxcar_length=16)
N = 1 << 16
SEGMENTS = 4
PULSED = 1           # the file segment whose stride holds the pulse
FMT = "interleaved_samples_2"

# float32 transforms of 2^16 points against float64, read here over
# both streams of the four segments: the waterfall 2.2e-7 of its largest
# magnitude on the fused plan and the ring, 6.5e-7 staged; the series
# (64 rows' power summed, mean-subtracted) 7.7e-7 and 1.1e-6 of its
# largest excursion.  The limits stand 15x and 28x over the largest
# reading; the oracle's own waterfall merely STORED in bfloat16 reads
# 2.4e-3 and its series 3.8e-4 (test_bfloat16_fails_both_tolerances),
# 240x and 12x over the limits.
WATERFALL_TOL = 1e-5
SERIES_TOL = 3e-5


def _config(options: dict, **extra) -> Config:
    return Config.from_args([f"--{k}={v}" for k, v in
                             dict(options, **extra).items()])


def _bf16(x: np.ndarray) -> np.ndarray:
    """float64 rounded to bfloat16's 8 significant bits and back."""
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


@functools.lru_cache(maxsize=None)
def _file_bytes(seed: int, pulse_streams: tuple) -> np.ndarray:
    """Four overlapped segments of two byte-interleaved 2-bit streams:
    independent seeded noise in each, a dispersed pulse in the middle of
    segment ``PULSED``'s stride in the streams named."""
    cfg = _config(TINY)
    reserved = dd.nsamps_reserved(cfg)
    stride = N - reserved
    total = N + (SEGMENTS - 1) * stride
    streams = []
    for s in range(2):
        at = [PULSED * stride + stride // 2] if s in pulse_streams else []
        streams.append(synth.make_dispersed_baseband(
            total, cfg.baseband_freq_low, cfg.baseband_bandwidth, cfg.dm,
            at, nbits=2, pulse_amp=12.0, seed=seed * 2 + s))
    raw = np.empty(2 * streams[0].size, dtype=np.uint8)
    raw[0::2], raw[1::2] = streams
    raw.setflags(write=False)
    return raw


def _segment_of(raw: np.ndarray, cfg: Config, k: int) -> np.ndarray:
    seg = cfg.segment_bytes(2)
    stride = seg - dd.nsamps_reserved(cfg) * 2 // 8 * 2
    return raw[k * stride:k * stride + seg]


class _Capture:
    """Appended last: what the detector handed the sinks, and what the
    program's own sink before it left on disk."""

    def __init__(self, prefix: str):
        self.prefix, self.rows = prefix, []

    def push(self, work, has_signal):
        d = work.detect
        blobs = {}
        for name in sorted(glob.glob(self.prefix + "*")):
            with open(name, "rb") as f:
                blobs[name[len(self.prefix):].split(".", 1)[1]] = f.read()
            os.remove(name)
        self.rows.append({
            "fired": bool(has_signal),
            "waterfall": waterfall_to_numpy(work.waterfall),
            "series": np.array(d.time_series, np.float32),
            "zero_count": np.array(d.zero_count).reshape(-1),
            "counts": np.array(d.signal_counts),
            "blobs": blobs})


def _run(tmp: str, tag: str, raw: np.ndarray, options: dict = TINY,
         staged=None, **extra) -> dict:
    """The file through ``Pipeline`` with the program's reader and its own
    sinks; ``staged=True`` forces the three-program plan."""
    path = os.path.join(tmp, f"baseband_{tag}.bin")
    raw.tofile(path)
    prefix = os.path.join(tmp, f"out_{tag}_")
    journal = os.path.join(tmp, f"journal_{tag}.jsonl")
    cfg = _config(options, input_file_path=path,
                  baseband_output_file_prefix=prefix,
                  telemetry_journal_path=journal, writer_thread_count=0,
                  **extra)
    processor = None if staged is None \
        else SegmentProcessor(cfg, staged=staged)
    metrics.reset()
    capture = _Capture(prefix)
    with Pipeline(cfg, processor=processor) as pipe:
        pipe.sinks.append(capture)
        pipe.run(max_segments=SEGMENTS)
        plan = pipe.processor.plan_name
        gauge = metrics.get("data_streams")
    metrics.reset()
    with open(journal) as f:
        spans = [json.loads(ln) for ln in f]
    spans = [s for s in spans if s.get("type") == "segment_span"]
    return {"cfg": cfg, "rows": capture.rows, "spans": spans,
            "plan": plan, "data_streams": gauge}


@functools.lru_cache(maxsize=None)
def _oracle(seed: int, pulse_streams: tuple) -> list:
    """Per file segment and stream: the float64 chain's waterfall, series,
    zapped rows (SK, and the rows the zap list empties) and firings."""
    cfg = _config(TINY)
    raw = _file_bytes(seed, pulse_streams)
    out = []
    for k in range(SEGMENTS):
        rows = []
        for x in oracle_deinterleave(_segment_of(raw, cfg, k), FMT, 2):
            wf, ts, _sk_rows = oracle_stream_chain(x, cfg)
            lengths = det.boxcar_lengths(
                cfg.signal_detect_max_boxcar_length, ts.size)
            acc = np.concatenate([[0.0], np.cumsum(ts)])
            counts = []
            for b in lengths:
                # the reference's window sums leave the last one out for
                # b > 1 (ref: signal_detect_pipe.hpp:368-399)
                s = ts if b == 1 else (acc[b:] - acc[:-b])[1:]
                counts.append(int((s > cfg.
                                   signal_detect_signal_noise_threshold
                                   * np.sqrt(np.mean(s * s))).sum()))
            rows.append({"waterfall": wf, "series": ts, "counts": counts,
                         "zero_count": int((np.abs(wf[:, 0]) == 0).sum())})
        out.append(rows)
    return out


def _gap(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def workdir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("j1644_2pol"))


def test_the_reserve_is_the_deployments_share_of_a_segment():
    full, tiny = _config(FULL), _config(TINY)
    assert full.baseband_format_type == tiny.baseband_format_type == FMT
    assert dd.nsamps_reserved(full) == 23494656            # 17.50 %
    assert dd.nsamps_reserved(tiny) == 11520               # 17.58 %
    assert dd.nsamps_reserved(full) / (1 << 27) < 3 / 11
    assert dd.nsamps_reserved(tiny) / N < 3 / 11
    # a stride of both streams' bytes, the carry, and what a cold
    # dispatch sends: the numbers ISSUE 36 gives for the cell
    proc_bytes = full.segment_bytes(2)
    reserved_bytes = dd.nsamps_reserved(full) * 2 // 8 * 2
    assert proc_bytes == 1 << 26
    assert reserved_bytes == 11747328
    assert proc_bytes - reserved_bytes == 55361536


# ---- (a) Pipeline, file to sinks, against the float64 oracle ----------

PLANS = {
    "fused": dict(ingest_ring="off"),
    "ring": dict(),
    "staged": dict(staged=True),
}


@pytest.mark.parametrize("plan", list(PLANS))
def test_both_streams_against_the_float64_oracle(workdir, plan):
    seed = 36
    run = _run(workdir, plan, _file_bytes(seed, (0, 1)), **PLANS[plan])
    want = _oracle(seed, (0, 1))
    assert ("staged" in run["plan"]) == (plan == "staged")
    assert ("+ring" in run["plan"]) == (plan != "fused")
    if plan != "fused":
        # one cold dispatch, then the carry: both kinds were compared
        assert run["spans"][-1]["ring_cold_dispatches"] == 1
    assert run["data_streams"] == 2
    assert len(run["rows"]) == SEGMENTS
    for k, (row, ref) in enumerate(zip(run["rows"], want)):
        assert row["waterfall"].shape == (2, 64, 512)
        # 332 of 512 time samples are searched: the detector trims
        # twice the overlap (PERF.md Open question 11)
        assert row["series"].shape == (2, 332)
        for s in range(2):
            where = f"{plan} segment {k} stream {s}"
            assert _gap(row["waterfall"][s],
                        ref[s]["waterfall"]) < WATERFALL_TOL, where
            assert _gap(row["series"][s],
                        ref[s]["series"]) < SERIES_TOL, where
            # rows the zap list emptied (1418-1422 MHz: 4 of 64) and
            # rows SK zapped: decisions, equal or not
            assert row["zero_count"][s] == ref[s]["zero_count"] >= 4, where
            assert row["counts"][s].tolist() == ref[s]["counts"], where
        # the pulse sits in both streams of one segment's searched part
        assert row["fired"] == (k == PULSED)
        assert (row["counts"].sum(axis=-1) > 0).tolist() \
            == [k == PULSED] * 2
    span = run["spans"][PULSED]
    assert span["v"] == 13 and span["streams"] == 2
    assert span["detections_by_stream"] \
        == run["rows"][PULSED]["counts"].sum(axis=-1).tolist()
    assert span["detections"] == sum(span["detections_by_stream"]) > 0


def test_bfloat16_fails_both_tolerances():
    """The control of (a)'s limits: the oracle's own waterfall kept in
    bfloat16 (the least a bfloat16 transform would do to it) is outside
    both, on every stream."""
    for ref in _oracle(36, (0, 1))[PULSED]:
        wf = ref["waterfall"]
        low = _bf16(wf.real) + 1j * _bf16(wf.imag)
        assert _gap(low, wf) > 100 * WATERFALL_TOL
        t = ref["series"].size
        ts = (low.real ** 2 + low.imag ** 2).sum(axis=0)[:t]
        assert _gap(ts - ts.mean(), ref["series"]) > 10 * SERIES_TOL


# ---- (b) stream 0 is the one-stream run on de-interleaved bytes --------

def test_stream_0_is_the_one_stream_run_bit_for_bit(workdir):
    raw = _file_bytes(36, (0, 1))
    two = _run(workdir, "two", raw, ingest_ring="off")
    # the same plan (fused:monolithic) on the bytes split on the host
    one = _run(workdir, "one", raw[0::2].copy(),
               dict(TINY, baseband_format_type="simple"),
               ingest_ring="off")
    assert one["plan"] == two["plan"] and one["data_streams"] == 1
    for k, (a, b) in enumerate(zip(one["rows"], two["rows"])):
        assert a["waterfall"].shape == (1, 64, 512)
        np.testing.assert_array_equal(a["waterfall"][0], b["waterfall"][0],
                                      err_msg=str(k))
        np.testing.assert_array_equal(a["series"][0], b["series"][0],
                                      err_msg=str(k))
        assert a["counts"][0].tolist() == b["counts"][0].tolist()
        assert a["zero_count"][0] == b["zero_count"][0]
    assert "streams" in one["spans"][0] and one["spans"][0]["streams"] == 1


# ---- (c) a pulse in one polarisation only ------------------------------

# ---- (b2) several streams are one stream several times (ISSUE 38) ------

def _stream_bytes(fmt: str, raw: np.ndarray) -> list:
    """Each stream's own bytes of an interleaved segment."""
    if fmt == "interleaved_samples_2":          # "1212" by bytes
        return [raw[k::2].copy() for k in range(2)]
    pairs = raw.reshape(-1, 4)                  # naocpsr_snap1: "1122"
    return [pairs[:, 2 * k:2 * k + 2].reshape(-1).copy() for k in range(2)]


@pytest.mark.parametrize("quality", [False, True],
                         ids=["plain", "quality_stats"])
@pytest.mark.parametrize("fmt,bits", [("interleaved_samples_2", 2),
                                      ("naocpsr_snap1", 8)])
def test_several_streams_are_one_stream_several_times(fmt, bits, quality):
    """``SegmentProcessor`` runs a segment of S streams as S one-stream
    chains (ISSUE 38), so on the interleaved segment it gives, a stream,
    what a ``simple``-format processor gives on that stream's own bytes:
    the same calls of the same transforms on the same shapes, so the
    waterfall, the series, the counts and the peaks bit for bit.  (The
    boxcars' own rows and the quality vector to the series' tolerance:
    XLA:CPU recomputes the mean-subtracted series inside the fusion
    that pads the rows, in another order in the longer program.)"""
    extra = dict(baseband_input_bits=bits, quality_stats=quality,
                 ingest_ring="off")
    two = SegmentProcessor(_config(TINY, baseband_format_type=fmt, **extra))
    one = SegmentProcessor(_config(TINY, baseband_format_type="simple",
                                   **extra))
    assert two.fmt.data_stream_count == 2 and one.plan_name == two.plan_name
    rng = np.random.default_rng(38)
    raw = rng.integers(0, 256, size=two._segment_bytes, dtype=np.uint8)
    wf2, det2 = two._jit_process(jnp.asarray(raw), two.chirp, two.chirp_w)
    assert wf2.shape == (2, 2, 64, 512)
    assert (det2.quality is not None) == quality
    for s, own in enumerate(_stream_bytes(fmt, raw)):
        wf1, det1 = one._jit_process(jnp.asarray(own), one.chirp,
                                     one.chirp_w)
        np.testing.assert_array_equal(np.asarray(wf1)[:, 0],
                                      np.asarray(wf2)[:, s], err_msg=str(s))
        for field in ("zero_count", "time_series", "signal_counts",
                      "snr_peaks"):
            np.testing.assert_array_equal(
                np.asarray(getattr(det1, field))[0],
                np.asarray(getattr(det2, field))[s], err_msg=field)
        close = ["boxcar_series"] + (["quality"] if quality else [])
        for field in close:
            assert _gap(np.asarray(getattr(det2, field))[s],
                        np.asarray(getattr(det1, field))[0]) < SERIES_TOL, \
                field
    assert tuple(int(b) for b in det2.boxcar_lengths) \
        == tuple(int(b) for b in det1.boxcar_lengths)


# every key of a one-stream plan's signature: ISSUE 38 adds none to it
# (ISSUE 50 took "front_fuse" with the family it told apart)
SIGNATURE_KEYS = {"cfg", "env", "mode", "staged", "interp", "window",
                  "has_chirp", "donate_input", "fused_tail",
                  "skzap", "ingest", "boundary"}


def test_the_plan_signature_names_the_stream_plan():
    """The two-stream programs changed with their avals unchanged
    (ISSUE 38), so their signature says which spelling they are and an
    AOT cache written by the batched one misses cleanly; a one-stream
    plan's signature is what it was, key for key."""
    one = json.loads(SegmentProcessor(
        _config(TINY, baseband_format_type="simple")).plan_signature())
    two = json.loads(SegmentProcessor(_config(TINY)).plan_signature())
    assert set(one) == SIGNATURE_KEYS
    assert set(two) == SIGNATURE_KEYS | {"streams"}
    assert two["streams"] == "looped-v1"
    # the parent's signature of the same plan is this one less the entry
    parents = {k: v for k, v in two.items() if k != "streams"}
    assert json.dumps(parents, sort_keys=True) \
        != json.dumps(two, sort_keys=True)
    assert {k: v for k, v in parents.items() if k != "cfg"} \
        == {k: v for k, v in one.items() if k != "cfg"}
    # the staged plan's stage (c) runs a stream at a time as well
    staged = json.loads(SegmentProcessor(
        _config(TINY), staged=True).plan_signature())
    assert staged["streams"] == "looped-v1" and staged["staged"] is True


def test_a_pulse_in_stream_1_only_names_its_stream(workdir):
    raw = _file_bytes(37, (1,))
    run = _run(workdir, "pol1", raw)
    cfg = run["cfg"]
    assert [r["fired"] for r in run["rows"]] \
        == [k == PULSED for k in range(SEGMENTS)]
    for k, (row, span) in enumerate(zip(run["rows"], run["spans"])):
        by_stream = span["detections_by_stream"]
        assert span["streams"] == 2 and len(by_stream) == 2
        assert span["detections"] == sum(by_stream)
        if k != PULSED:
            assert by_stream == [0, 0] and row["blobs"] == {}
            continue
        assert by_stream[0] == 0 and by_stream[1] > 0
        blobs = row["blobs"]
        tims = sorted(n for n in blobs if n.endswith(".tim"))
        fired = [b for b, c in zip(det.boxcar_lengths(16, 332),
                                   row["counts"][1]) if c > 0]
        # every series carries its stream's index; none is stream 0's
        assert tims == sorted(f"s1.{b}.tim" for b in fired) and fired
        # the .bin holds both polarisations as they lie in the file
        assert blobs["bin"] == _segment_of(raw, cfg, k).tobytes()
        assert len(blobs["bin"]) == cfg.segment_bytes(2) == 2 * N // 4
        # one waterfall a stream
        assert {"0.npy", "1.npy"} <= set(blobs)


# ---- (d) ring on against ring off --------------------------------------

def test_ring_on_and_off_hand_process_the_same_bytes(monkeypatch):
    proc = SegmentProcessor(_config(TINY))
    plain = SegmentProcessor(_config(TINY, ingest_ring="off"))
    assert proc.ring and not plain.ring
    # the carry is the reserve of BOTH streams, byte-interleaved
    assert proc.reserved_bytes == 11520 * 2 // 8 * 2 == 5760
    assert proc._segment_bytes == plain._segment_bytes == 2 * N // 4
    raw = _file_bytes(36, (0, 1))
    first = np.asarray(raw[:proc._segment_bytes])
    second = np.asarray(raw[proc.stride_bytes:
                            proc.stride_bytes + proc._segment_bytes])
    for p in (proc, plain):
        monkeypatch.setattr(p, "_process", lambda raw, *_chirps: raw)
    seen_cold, carry = proc._process_cold(jnp.asarray(first), None)
    seen_warm, next_carry = proc._process_ring(
        carry, jnp.asarray(second[proc.reserved_bytes:]), None)
    assert np.array_equal(np.asarray(seen_cold), first)
    assert np.array_equal(np.asarray(seen_warm), second)
    assert np.array_equal(np.asarray(seen_warm),
                          np.asarray(plain._process(jnp.asarray(second))))
    assert np.array_equal(np.asarray(next_carry),
                          second[proc.stride_bytes:])
    metrics.reset()
    proc.stage_input(second, stride_only=True)
    assert metrics.get("ring_carry_bytes") == proc.reserved_bytes
    assert metrics.get("h2d_bytes") == proc.stride_bytes
    metrics.reset()


def test_ring_carry_bytes_in_the_journal(workdir):
    run = _run(workdir, "carry", _file_bytes(36, (0, 1)))
    spans = run["spans"]
    seg, reserved = 2 * N // 4, 5760
    assert spans[-1]["ring_cold_dispatches"] == 1
    assert spans[-1]["ring_carry_bytes"] == (SEGMENTS - 1) * reserved
    assert spans[-1]["h2d_bytes"] + spans[-1]["ring_carry_bytes"] \
        == SEGMENTS * seg


# ---- (e) the split, bit for bit, at every width -------------------------

@pytest.mark.parametrize("nbits", [1, 2, 4, 8, -8])
@pytest.mark.parametrize("size", [8, 1030, 6144, 1 << 16],
                         ids=lambda n: f"{n}B")
def test_the_split_is_bit_identical_to_the_oracle(nbits, size):
    """Rows of 1024 bytes, of 8, of 2 (1030 = 2 x 515) and whole rows:
    one spelling, the oracle's ``reshape(-1, 2)[:, k]`` to the bit."""
    raw = np.random.default_rng([36, size]).integers(
        0, 256, size=size, dtype=np.uint8)
    got = np.asarray(U.unpack_interleaved_2pol(jnp.asarray(raw), nbits))
    want = oracle_deinterleave(raw, FMT, nbits)
    assert got.dtype == np.float32
    assert got.shape == (2, size // 2 * 8 // abs(nbits))
    for s in range(2):
        np.testing.assert_array_equal(got[s], want[s])
    out1, out2 = U.unpack_interleaved_2pol(jnp.asarray(raw), nbits)
    np.testing.assert_array_equal(np.asarray(out2), want[1])


def test_the_split_multiplies_the_window_into_each_stream():
    raw = np.random.default_rng(36).integers(0, 256, 2048, dtype=np.uint8)
    window = np.random.default_rng(37).random(4096).astype(np.float32)
    got = np.asarray(U.unpack_interleaved_2pol(
        jnp.asarray(raw), 2, jnp.asarray(window)))
    want = oracle_deinterleave(raw, FMT, 2)
    for s in range(2):
        np.testing.assert_array_equal(
            got[s], want[s].astype(np.float32) * window)
