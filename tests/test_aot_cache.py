"""AOT executable persistence (utils/aot_cache.py, VERDICT r4 #6).

A restarted observation must not pay the XLA compile again when the
persistent compile cache is bypassed: SegmentProcessor.enable_aot
persists the compiled plan executables and a second process-equivalent
build loads them.  CPU backends are opt-in (SRTB_AOT_ALLOW_CPU=1) —
save+load on one host is safe; the default-off policy mirrors
utils/compile_cache.py's host-swap SIGILL rationale.
"""

import glob
import os

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.pipeline.segment import SegmentProcessor


def _cfg(tmp_path, n=1 << 14, **kw):
    return Config(
        baseband_input_count=n,
        baseband_input_bits=2,
        baseband_format_type="simple",
        baseband_freq_low=1405.0,
        baseband_bandwidth=64.0,
        baseband_sample_rate=128e6,
        dm=30.0,
        spectrum_channel_count=1 << 6,
        signal_detect_max_boxcar_length=16,
        mitigate_rfi_average_method_threshold=1e9,
        mitigate_rfi_spectral_kurtosis_threshold=1e9,
        baseband_reserve_sample=False,
        aot_plan_path=str(tmp_path / "aot"),
        **kw,
    )


def _raw(cfg, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=cfg.segment_bytes(1), dtype=np.uint8)


@pytest.mark.parametrize("staged", [False, True],
                         ids=["fused", "staged"])
def test_aot_roundtrip(tmp_path, monkeypatch, staged):
    monkeypatch.setenv("SRTB_AOT_ALLOW_CPU", "1")
    cfg = _cfg(tmp_path)
    raw = _raw(cfg)

    p1 = SegmentProcessor(cfg, staged=staged)
    wf1 = np.asarray(p1.process(raw)[0])
    blobs = glob.glob(str(tmp_path / "aot" / "*.aot"))
    assert len(blobs) == (3 if staged else 1), blobs
    mtimes = {b: os.path.getmtime(b) for b in blobs}

    # "restart": a fresh processor over the same config must LOAD (no
    # blob rewritten) and produce the identical executables' results
    p2 = SegmentProcessor(cfg, staged=staged)
    from jax.stages import Compiled
    progs = ([p2._jit_stage_a, p2._jit_stage_b, p2._jit_stage_c]
             if staged else [p2._jit_process])
    assert all(isinstance(p, Compiled) for p in progs)
    wf2 = np.asarray(p2.process(raw)[0])
    assert {b: os.path.getmtime(b) for b in blobs} == mtimes, \
        "a warm start must not re-save (i.e. must not have recompiled)"
    np.testing.assert_array_equal(wf1, wf2)


def test_aot_signature_miss_recompiles(tmp_path, monkeypatch):
    """A changed plan-shaping knob must miss the cache, not load a
    stale executable for the wrong program."""
    monkeypatch.setenv("SRTB_AOT_ALLOW_CPU", "1")
    cfg = _cfg(tmp_path)
    SegmentProcessor(cfg).process(_raw(cfg))
    n_blobs = len(glob.glob(str(tmp_path / "aot" / "*.aot")))
    cfg2 = cfg.replace(spectrum_channel_count=1 << 5)
    p2 = SegmentProcessor(cfg2)
    p2.process(_raw(cfg2))
    assert len(glob.glob(str(tmp_path / "aot" / "*.aot"))) == 2 * n_blobs


def test_plan_signature_keys_on_trace_shape_only(tmp_path, monkeypatch):
    """The AOT cache key must ignore deployment-local knobs (paths,
    socket buffers) — an operator relocating outputs or tuning IO
    between runs must still hit the cache — while any trace-shaping
    field must miss."""
    monkeypatch.setenv("SRTB_AOT_ALLOW_CPU", "1")
    cfg = _cfg(tmp_path)
    sig = SegmentProcessor(cfg).plan_signature()
    # deployment-local changes: same signature
    same = cfg.replace(baseband_output_file_prefix="/elsewhere/out_",
                       udp_receiver_rcvbuf_bytes=1 << 20,
                       segment_deadline_s=42.0)
    assert SegmentProcessor(same).plan_signature() == sig
    # run-local SRTB_ knobs (watcher logs): same signature
    monkeypatch.setenv("SRTB_WATCH_LOG", "/tmp/w.log")
    assert SegmentProcessor(same).plan_signature() == sig
    # trace-shaping changes: different signature
    assert SegmentProcessor(
        cfg.replace(spectrum_channel_count=1 << 5)).plan_signature() != sig
    assert SegmentProcessor(
        cfg.replace(fft_strategy="four_step")).plan_signature() != sig
    monkeypatch.setenv("SRTB_STAGED_ROWS_IMPL", "pallas")
    assert SegmentProcessor(cfg).plan_signature() != sig


def test_aot_cpu_default_off(tmp_path, monkeypatch):
    """Without the opt-in, CPU backends keep the plain jit wrappers and
    write nothing (the host-swap SIGILL policy)."""
    monkeypatch.delenv("SRTB_AOT_ALLOW_CPU", raising=False)
    import jax
    if jax.default_backend() != "cpu":
        pytest.skip("policy under test is CPU-only")
    cfg = _cfg(tmp_path)
    p = SegmentProcessor(cfg)
    from jax.stages import Compiled
    assert not isinstance(p._jit_process, Compiled)
    assert not glob.glob(str(tmp_path / "aot" / "*.aot"))
