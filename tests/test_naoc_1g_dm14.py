"""The upstream-default 1 GSa/s deployment (``naoc_1g_dm14``, ISSUE 31) at
a size the CPU holds: 2^16 samples and 2^6 channels of 8-bit samples, the
DM scaled with the segment (14.2 / 2^12) so that the overlap-save reserve
is the deployment's 24.4 % of every segment, S/N 6.  Cut besides, as the
benchmark's ``tiny_8bit`` cuts them: SK threshold 1.1 -> 1.4 (512 time
samples, not 4096) and boxcars 1024 -> 16 (262 searched samples).

Through ``Pipeline`` with the program's file reader, its ring and its own
sinks, against the float64 chain of ``benchmark/reference/chain.py``:

(a) every segment's time series, the pulse's per-boxcar S/N and peak bin;
(b) a mid-stride pulse is found in exactly one segment and leaves its
    candidate files, quiet segments leave none;
(c) ``ring_carry_bytes`` (0 on the cold dispatch, ``reserved_bytes`` on
    every warm one) beside ``h2d_bytes``; warm and cold dispatches hand
    ``_process`` the same bytes;
(d) the ring plan's lowered HLO carries ``srtb.ring`` and is otherwise
    what it was;
(e) a reserve that outgrows the detector's series is refused at
    construction (the Crab's DM at 2^28), the deployment's is built;
(f) the cell's files load the way ``benchmark/run.py`` loads them.
"""

import contextlib
import functools
import glob
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import check, gen, spec as spec_mod  # noqa: E402
from benchmark.reference import chain  # noqa: E402
from srtb_tpu.config import Config  # noqa: E402
from srtb_tpu.ops import dedisperse as dd  # noqa: E402
from srtb_tpu.ops import scopes as S  # noqa: E402
from srtb_tpu.pipeline.runtime import Pipeline  # noqa: E402
from srtb_tpu.pipeline.segment import (SegmentProcessor,  # noqa: E402
                                       refuse_overlong_reserve)
from srtb_tpu.utils.metrics import metrics  # noqa: E402

CELL = "naoc_1g_dm14.replay_quiet"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "naoc_1g_dm14.json")) as _f:
    FULL = json.load(_f)["options"]
# the cuts, listed: size, channels, the DM with the size, and the two
# thresholds tiny_8bit cuts for the shorter series
SCALE = 1 << 12
TINY = dict(FULL, baseband_input_count="2 ** 16",
            spectrum_channel_count="2 ** 6", dm=14.2 / SCALE,
            mitigate_rfi_spectral_kurtosis_threshold=1.4,
            signal_detect_max_boxcar_length=16)
CRAB_DM = 56.77
WORKLOAD = {"warmup": {"segments": []}, "source": {"file_segments": 5},
            "pulses": {"every": 5, "phase": 2, "dm": TINY["dm"],
                       "amp": 12.0, "width": 32, "template_log2": 15}}
PULSED = 2
SEEDS = [11, 2147483659, 763666155]
# the tiny 8-bit cell's limits (benchmark/selftest/tiny)
SERIES_GAP = SNR_GAP = 0.01


def _config(options: dict, **extra) -> Config:
    return Config.from_args([f"--{k}={v}" for k, v in
                             dict(options, **extra).items()])


class _Capture:
    """Appended last, as the benchmark's stamp is: what the detector
    handed the sinks, and whether the program's own sink before it left
    a candidate."""

    def __init__(self, prefix: str):
        self.prefix, self.rows = prefix, []

    def push(self, work, has_signal):
        det = work.detect
        files = sorted(glob.glob(self.prefix + "*"))
        self.rows.append({
            "fired": bool(has_signal),
            "series": np.array(det.time_series, np.float32).reshape(-1),
            "snr_peaks": np.array(det.snr_peaks, np.float32).reshape(-1),
            "files": files,
            "sizes": [os.path.getsize(p) for p in files]})
        for path in files:
            os.remove(path)


@functools.lru_cache(maxsize=None)
def _run(seed: int, tmp: str) -> dict:
    """One pass of the five-segment file through ``Pipeline``."""
    p = chain.params_from_config(TINY)
    lay = gen.Layout(p, WORKLOAD, seed)
    path = os.path.join(tmp, f"baseband_{seed}.bin")
    gen.write_file(path, p, lay, seed)
    prefix = os.path.join(tmp, f"out_{seed}_")
    journal = os.path.join(tmp, f"journal_{seed}.jsonl")
    cfg = _config(TINY, input_file_path=path,
                  baseband_output_file_prefix=prefix,
                  telemetry_journal_path=journal, writer_thread_count=0)
    assert int(dd.nsamps_reserved(cfg)) == lay.reserved
    metrics.reset()
    capture = _Capture(prefix)
    with Pipeline(cfg) as pipe:
        pipe.sinks.append(capture)
        assert pipe.processor.ring
        pipe.run(max_segments=lay.n_segments)
        reserved_bytes = pipe.processor.reserved_bytes
    metrics.reset()
    with open(journal) as f:
        spans = [json.loads(ln) for ln in f]
    spans = [s for s in spans if s.get("type") == "segment_span"]
    data = np.fromfile(path, dtype=np.uint8)
    want = [chain.segment(data[k * lay.stride_bytes:
                               k * lay.stride_bytes + lay.segment_bytes],
                          p)[0] for k in range(lay.n_segments)]
    return {"lay": lay, "rows": capture.rows, "spans": spans,
            "want": want, "reserved_bytes": reserved_bytes}


@pytest.fixture(scope="module")
def workdir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("naoc"))


def test_the_reserve_is_the_deployments_share_of_a_segment():
    full = chain.params_from_config(FULL)
    tiny = chain.params_from_config(TINY)
    assert chain.nsamps_reserved(full) == 65470464          # 24.39 %
    assert chain.nsamps_reserved(tiny) == 16000             # 24.41 %
    assert abs(chain.nsamps_reserved(tiny) / tiny["n"]
               - chain.nsamps_reserved(full) / full["n"]) < 5e-4
    # under the 3/11 at which a mid-stride pulse is still searched
    assert chain.nsamps_reserved(full) / full["n"] < 3 / 11


@pytest.mark.parametrize("seed", SEEDS)
def test_series_snr_and_peak_bin_against_the_reference(workdir, seed):
    run = _run(seed, workdir)
    lay, rows, want = run["lay"], run["rows"], run["want"]
    assert len(rows) == lay.n_segments == 5
    for k, (row, ref) in enumerate(zip(rows, want)):
        # 262 of 512 time samples are searched: the detector's trim
        assert row["series"].shape == ref["time_series"].shape == (262,)
        assert check.series_gap(row["series"],
                                ref["time_series"]) < SERIES_GAP, k
    row, ref = rows[PULSED], want[PULSED]
    assert ref["boxcar_lengths"] == [1, 2, 4, 8, 16]
    assert check.relative_gap(row["snr_peaks"],
                              ref["snr_peaks"]) < SNR_GAP
    got_bin = int(np.argmax(row["series"]))
    assert got_bin == ref["peak_bins"][0]
    assert abs(got_bin - lay.expected_bin(PULSED)) <= 8


@pytest.mark.parametrize("seed", SEEDS)
def test_a_mid_stride_pulse_is_found_once_with_its_candidate(workdir,
                                                             seed):
    run = _run(seed, workdir)
    lay, rows = run["lay"], run["rows"]
    assert [r["fired"] for r in rows] == [k == PULSED for k in range(5)]
    for k, row in enumerate(rows):
        if k != PULSED:
            assert row["files"] == [], k      # a quiet segment leaves none
            continue
        bins = [s for p, s in zip(row["files"], row["sizes"])
                if p.endswith(".bin")]
        assert bins == [lay.segment_bytes]
        assert any(p.endswith(".tim") for p in row["files"])
    assert max(run["want"][PULSED]["snr_peaks"]) > 6.0


@pytest.mark.parametrize("seed", SEEDS[:1])
def test_ring_carry_bytes_beside_h2d_bytes_in_the_journal(workdir, seed):
    run = _run(seed, workdir)
    lay, spans, reserved = run["lay"], run["spans"], run["reserved_bytes"]
    assert reserved == lay.bytes_of(lay.reserved) == 16000
    carried = [s["ring_carry_bytes"] for s in spans]
    sent = [s["h2d_bytes"] for s in spans]
    assert len(spans) == 5
    # cumulative at drain, two in flight: by the last span every dispatch
    # is counted: one cold (nothing carried), four warm
    assert carried[-1] == 4 * reserved and carried[0] in (0, reserved)
    assert sent[-1] == lay.segment_bytes + 4 * lay.stride_bytes
    assert sent[-1] + carried[-1] == 5 * lay.segment_bytes
    assert spans[-1]["ring_cold_dispatches"] == 1


def test_warm_and_cold_dispatches_hand_process_the_same_bytes(
        monkeypatch):
    proc = SegmentProcessor(_config(TINY))
    rng = np.random.default_rng(3)
    stream = rng.integers(0, 256, proc.stride_bytes * 2
                          + proc.reserved_bytes, dtype=np.uint8)
    first = stream[:proc._segment_bytes]
    second = stream[proc.stride_bytes:proc.stride_bytes
                    + proc._segment_bytes]
    monkeypatch.setattr(proc, "_process", lambda raw, *_chirps: raw)
    seen_cold, carry = proc._process_cold(jax.numpy.asarray(first), None)
    assert np.array_equal(np.asarray(carry), first[proc.stride_bytes:])
    seen_warm, next_carry = proc._process_ring(
        carry, jax.numpy.asarray(second[proc.reserved_bytes:]), None)
    assert np.array_equal(np.asarray(seen_cold), first)
    assert np.array_equal(np.asarray(seen_warm), second)
    assert np.array_equal(np.asarray(next_carry),
                          second[proc.stride_bytes:])
    # the counter: a cold upload carries nothing, a warm one the reserve
    metrics.reset()
    proc.stage_input(second)
    assert metrics.get("ring_carry_bytes") == 0
    assert metrics.get("h2d_bytes") == proc._segment_bytes
    proc.stage_input(second, stride_only=True)
    assert metrics.get("ring_carry_bytes") == proc.reserved_bytes == 16000
    assert metrics.get("h2d_bytes") == proc._segment_bytes \
        + proc.stride_bytes
    metrics.reset()


def _ring_programs() -> dict:
    proc = SegmentProcessor(_config(TINY))
    out = {}
    for name, fn, avals, _donated in proc.lowerables():
        if name in ("ring", "ring_cold"):
            lowered = fn.lower(*avals)
            out[name] = (lowered.as_text(),
                         lowered.as_text(debug_info=True))
    assert set(out) == {"ring", "ring_cold"}
    return out


@pytest.mark.parametrize("program", ["ring", "ring_cold"])
def test_the_ring_plan_carries_srtb_ring_as_metadata_only(program,
                                                          monkeypatch):
    text, located = _ring_programs()[program]
    scopes = set(re.findall(r"srtb\.[a-z0-9_]+", located))
    # 8-bit: the unpack is a cast inside the R2C, but still named
    assert S.RING in scopes and S.FFT_R2C in scopes
    assert not re.findall(r"srtb\.[a-z0-9_]+", text)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, bare_located = _ring_programs()[program]
    assert not re.findall(r"srtb\.[a-z0-9_]+", bare_located)
    count = len(re.findall(r"^\s+%?\S+ = ", text, flags=re.M))
    assert count == len(re.findall(r"^\s+%?\S+ = ", bare, flags=re.M)) > 10
    assert text == bare


@pytest.mark.parametrize("options, dm, served", [
    (FULL, CRAB_DM, False),             # 97.5 % of a 2^28 segment
    (FULL, 14.2, True),
    (TINY, CRAB_DM / SCALE, False),
    (TINY, 14.2 / SCALE, True),
], ids=["2p28-crab", "2p28-dm14", "tiny-crab", "tiny-dm14"])
def test_an_overlong_reserve_is_refused_at_construction(options, dm,
                                                        served):
    cfg = _config(options, dm=dm)
    if served:
        refuse_overlong_reserve(cfg)          # admits it
        if options is TINY:
            proc = SegmentProcessor(cfg)
            assert proc.ring and proc.time_reserved_count == 250 \
                < proc.watfft_len == 512
        return
    # before the chirp bank: at 2^28 that is 29 s and 25 GB of host memory
    with pytest.raises(ValueError) as e:
        SegmentProcessor(cfg)
    text = str(e.value)
    n = 1 << (28 if options is FULL else 16)
    assert f"dm {dm}" in text and str(n) in text
    # the largest DM this segment serves, and what the detector would trim
    limit = float(re.search(r"\|dm\| up to ([0-9.e-]+) at",
                            text).group(1))
    assert limit == pytest.approx(29.10 if options is FULL
                                  else 29.10 / SCALE, rel=2e-3, abs=5e-3)
    assert abs(dm) > limit
    refuse_overlong_reserve(_config(options, dm=0.999 * limit))


def test_the_cells_files_load_as_run_py_loads_them():
    sp = spec_mod.Spec(spec_mod.HERE, CELL)
    assert sp.chips == 1 and sp.workload["driver"] == "served"
    assert sp.config["options"] == FULL and set(sp.config["reduced"]) \
        == {"gui_enable"}
    p = chain.params_from_config(FULL)
    lay = gen.Layout(p, sp.workload, 2147496017)
    # what the served driver holds the program to before it builds it
    assert int(dd.nsamps_reserved(_config(FULL))) == lay.reserved
    assert lay.stride == 202964992 and lay.n_replay == 8
    assert not any(lay.pulsed[lay.n_warmup:]) and lay.pulsed[0]
    assert lay.expected_bin(0) < 4096 - lay.reserved // (1 << 15)
    names = {m["name"] for m, _reader in sp.metrics("per_layer")}
    assert {"ops.ring_ms_per_seg", "io.ring_carry_mb_per_seg",
            "kernels.hbm_share", "device.idle_share"} <= names
    assert "ops.unpack_ms_per_seg" not in names
    assert {m["name"] for m, _r in sp.metrics("end_to_end")} \
        == {"rt_factor", "setup_s"}


class _CaptureFiles(_Capture):
    """``_Capture`` that keeps every candidate file's bytes, by
    extension, before the files go."""

    def push(self, work, has_signal):
        blobs = {}
        for name in glob.glob(self.prefix + "*"):
            with open(name, "rb") as f:
                blobs[name.split(".", 1)[1]] = f.read()
        super().push(work, has_signal)
        self.rows[-1]["blobs"] = blobs


def _run_from_pool(seed: int, tmp: str, tag: str, pool) -> tuple:
    """The five-segment file through ``Pipeline`` with the reader on
    ``pool``: the capture's rows, and the pulsed segment's bytes as the
    file holds them."""
    from srtb_tpu.io.file_input import BasebandFileReader
    p = chain.params_from_config(TINY)
    lay = gen.Layout(p, WORKLOAD, seed)
    path = os.path.join(tmp, f"baseband_pool_{seed}.bin")
    if not os.path.exists(path):
        gen.write_file(path, p, lay, seed)
    prefix = os.path.join(tmp, f"pool_{tag}_{seed}_")
    cfg = _config(TINY, input_file_path=path,
                  baseband_output_file_prefix=prefix,
                  writer_thread_count=0)
    capture = _CaptureFiles(prefix)
    metrics.reset()
    with Pipeline(cfg, source=BasebandFileReader(
            cfg, buffer_pool=pool)) as pipe:
        pipe.sinks.append(capture)
        pipe.run(max_segments=lay.n_segments)
    metrics.reset()
    data = np.fromfile(path, dtype=np.uint8)
    start = PULSED * lay.stride_bytes
    return capture.rows, data[start:start + lay.segment_bytes].tobytes()


def test_a_poisoned_pool_gives_the_fresh_pools_candidates(workdir):
    """The reader no longer zero-fills its block: from a pool whose
    blocks come back full of 0xFF the detections and the candidate's
    bytes (the warm segment's head is dumped from the host block, never
    uploaded) are those of a fresh pool."""
    from srtb_tpu.utils.bufferpool import BufferPool
    seed = SEEDS[0]
    fresh, segment = _run_from_pool(seed, workdir, "fresh",
                                    BufferPool("fresh"))
    pool = BufferPool("poisoned")
    held = [pool.acquire(len(segment), zero=False) for _ in range(6)]
    for buf in held:
        buf[:] = 0xFF
        pool.release(buf)
    stale, _ = _run_from_pool(seed, workdir, "stale", pool)
    assert pool.stats()["new_blocks"] == 6      # every pull was recycled
    assert [r["fired"] for r in fresh] == [k == PULSED for k in range(5)]
    assert len(stale) == len(fresh) == 5
    for k, (a, b) in enumerate(zip(fresh, stale)):
        assert a["fired"] == b["fired"], k
        np.testing.assert_array_equal(a["series"], b["series"],
                                      err_msg=str(k))
        assert a["blobs"] == b["blobs"], k
    blobs = stale[PULSED]["blobs"]
    assert blobs["bin"] == segment and {"0.npy", "1.tim"} <= set(blobs)
