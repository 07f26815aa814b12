"""Ask the chip's compiler without a chip: the served plans and the
Pallas kernels AOT-compiled for a described (not attached) v5e.

Interpret mode and XLA:CPU accept programs the TPU compiler refuses —
a slice off the tiling, too much VMEM, a program that does not fit
16 GB of HBM.  These cases cost no chip time and guard every later PR.
Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (never at
import, never in a skipif/parametrize): only one process may load
libtpu, and under xdist every worker imports every test file.  All the
cases live in this one file so one worker owns the library; the
compiles run in the test's own process with the persistent compilation
cache off (an entry written for a described device cannot be read back
without one).
"""

import json
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from srtb_tpu.config import Config

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _j1644(log2n: int, **over) -> Config:
    """The J1644-4559 served configuration (chip_smoke.py's)."""
    kw = dict(
        baseband_input_count=1 << log2n,
        baseband_input_bits=2,
        baseband_format_type="simple",
        baseband_freq_low=1405.0 + 32.0,
        baseband_bandwidth=-64.0,
        baseband_sample_rate=128e6,
        dm=-478.80 * min(1.0, 2.0 ** (log2n - 27)),
        spectrum_channel_count=1 << 11,
        signal_detect_signal_noise_threshold=8.0,
        mitigate_rfi_spectral_kurtosis_threshold=1.05,
        baseband_reserve_sample=True,
    )
    kw.update(over)
    return Config(**kw)


def _naoc(log2n: int, dm: float) -> Config:
    """The upstream defaults (8-bit 1 GSa/s, 2^15 channels, reserve on:
    ``benchmark/configs/naoc_1g_dm14.json`` / ``naoc_crab_2p30.json``)."""
    return Config(
        baseband_input_count=1 << log2n, baseband_input_bits=8,
        baseband_format_type="simple", baseband_freq_low=1000.0,
        baseband_bandwidth=500.0, baseband_sample_rate=1e9, dm=dm,
        spectrum_channel_count=1 << 15,
        mitigate_rfi_average_method_threshold=10.0,
        mitigate_rfi_spectral_kurtosis_threshold=1.1,
        signal_detect_signal_noise_threshold=6.0,
        signal_detect_max_boxcar_length=1024,
        baseband_reserve_sample=True)


def _compile_all(proc, one_chip, only=None) -> dict:
    """Compile every program of the plan (or those named in ``only``)
    for the described chip; {name: compiled}."""
    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    out = {}
    for name, fn, avals, _donated in proc.lowerables():
        if only is None or name in only:
            out[name] = fn.lower(*jax.tree.map(on_chip, avals)).compile()
    return out


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return m.temp_size_in_bytes + m.argument_size_in_bytes


def test_j1644_default_plan_compiles_and_fits(one_chip):
    """2^27 samples / 2^11 channels, default plan with the overlap-save
    ring: ``fused``, ``ring`` and ``ring_cold`` all compile, each within
    one v5e's 16 GB."""
    from srtb_tpu.pipeline.segment import SegmentProcessor

    proc = SegmentProcessor(_j1644(27), donate_input=True)
    assert proc.ring and not proc.staged
    compiled = _compile_all(proc, one_chip)
    assert set(compiled) == {"fused", "ring", "ring_cold"}
    for name, c in compiled.items():
        assert _device_bytes(c) < V5E_HBM_BYTES, (name,
                                                  c.memory_analysis())


@pytest.fixture(scope="module")
def two_pol_ring(one_chip):
    """The served ``ring`` program of the ``j1644_2pol_2p27`` deployment
    (both polarisations byte-interleaved, ``interleaved_samples_2``) at
    2 x 2^27 samples, compiled for the described chip."""
    from srtb_tpu.pipeline.segment import SegmentProcessor

    proc = SegmentProcessor(
        _j1644(27, baseband_format_type="interleaved_samples_2"),
        donate_input=True)
    assert proc.ring and proc._segment_bytes == 1 << 26
    return _compile_all(proc, one_chip, only={"ring"})["ring"]


def test_j1644_two_polarisation_plan_splits_lane_dense(two_pol_ring):
    """The two-stream program fits a v5e twice over (two segments in
    flight), and the split leaves no byte array with a minor dimension
    of 2, which the chip pads to 128 lanes (``u8[33554432,2]`` tiled to
    4.3 GB was 50 ms a segment, PR 36)."""
    import re

    assert 2 * _device_bytes(two_pol_ring) < V5E_HBM_BYTES, \
        two_pol_ring.memory_analysis()
    text = two_pol_ring.as_text()
    assert "u8[" in text and not re.search(r"u8\[\d+,2\]", text)


def test_j1644_two_polarisation_r2c_takes_one_stream_at_a_time(two_pol_ring):
    """ISSUE 38: handed ``f32[2, 2^27]`` the chip's compiler tiled the
    stream axis into the minor tile (``T(2,128)``), relaid the stack out
    twice (``copy_bitcast_fusion f32[2,134217728]``) and ran every
    fusion of the R2C on ``f32[2,128,128,128,64]``.  The streams now go
    through ONE loop whose body holds the one-stream chain, so none of
    that is left under ``srtb.fft_r2c``, and the 2-bit unpack in the
    body keeps the transform's own tiling: no array of weight has a
    minor dimension of 4 (``f32[33554432,4]`` padded to 16 GB is what
    two transforms traced one behind the other made of it)."""
    import re

    text = two_pol_ring.as_text()
    assert "/srtb.waterfall/while/body/closed_call/srtb.fft_r2c/" in text
    r2c = [ln.strip() for ln in text.splitlines() if "srtb.fft_r2c" in ln]
    assert len(r2c) > 50
    tiled = [ln[:160] for ln in r2c
             if re.search(r"= \(?f32\[2,[^\]]*\]\{[^}]*T\(2,128\)", ln)]
    assert not tiled, tiled[:3]
    assert not [ln[:160] for ln in r2c if re.search(r"= \(?f32\[2,128,", ln)]
    assert "f32[2,134217728]" not in text
    assert not re.search(r"(f32|u8)\[\d{5,},4\]", text)


def test_j1644_two_polarisation_unscoped_work_is_the_compilers(two_pol_ring):
    """What ``ops.unscoped_ms_per_seg`` reads in the two-stream cell is
    nothing the program traced and left unnamed: every instruction of
    weight without an ``srtb.`` scope carries no ``op_name`` at all, it
    is a copy or a fusion the chip's compiler put in for a layout of
    its own.  The heaviest are the copies between the passes of the
    R2C, the same the one-stream program has (its ``copy.252/253/254``).
    Since the loop (ISSUE 38) the program's output, the two streams'
    waterfalls, is no copy any more: the loop writes each stream's
    waterfall into a buffer laid out so that the turn to ``[2, S,
    channels, time]`` is a bitcast, under ``srtb.waterfall``."""
    import re

    lines = two_pol_ring.as_text().splitlines()
    unscoped = []
    for line in lines:
        cycles = re.search(r'"estimated_cycles":"?(\d+)', line)
        name = re.search(r'op_name="([^"]*)"', line)
        if cycles and int(cycles.group(1)) > 100_000 \
                and not (name and "srtb." in name.group(1)):
            unscoped.append((int(cycles.group(1)), line.strip()))
    assert unscoped and all('op_name="' not in ln for _c, ln in unscoped), \
        [ln[:120] for _c, ln in unscoped if 'op_name="' in ln]
    heaviest = max(unscoped)[1]
    assert re.match(r"%\S+ = f32\[128,128,128,64\]\{[^}]*\} copy\(",
                    heaviest), heaviest[:200]
    out = [ln for ln in lines if re.match(
        r"\s+%\S+ = f32\[2,2,2048,32768\]\{3,2,1,0[^}]*\} ", ln)]
    assert len(out) == 1 and " bitcast(%while" in out[0] \
        and "srtb.waterfall" in out[0], [ln[:200] for ln in out]


def test_j1644_pallas_plan_lowers_through_mosaic(one_chip, monkeypatch):
    """The same configuration at 2^24 with the Pallas RFI+chirp,
    row-FFT and SK-zap kernels: steered onto the non-interpret path in
    the test (the program has no option for it), every program must
    carry a Mosaic custom call and compile."""
    from srtb_tpu.pipeline.segment import SegmentProcessor
    from srtb_tpu.utils import platform

    monkeypatch.setattr(platform, "on_accelerator", lambda: True)
    proc = SegmentProcessor(
        _j1644(24, use_pallas=True, use_pallas_sk=True),
        donate_input=True)
    assert proc._pallas_interpret is False
    compiled = _compile_all(proc, one_chip)
    assert set(compiled) == {"fused", "ring", "ring_cold"}
    for name, c in compiled.items():
        assert "tpu_custom_call" in c.as_text(), name
        assert _device_bytes(c) < V5E_HBM_BYTES, name


def test_pallas_row_fft_at_waterfall_shape(one_chip):
    """The Pallas row-FFT at the J1644 2^27 waterfall shape: 2048
    channels x 2^15 time samples."""
    from srtb_tpu.ops import pallas_fft

    plane = jax.ShapeDtypeStruct((2048, 1 << 15), jnp.float32,
                                 sharding=one_chip)
    c = jax.jit(lambda re, im: pallas_fft.fft_rows_ri(
        re, im, inverse=True, interpret=False)).lower(plane,
                                                      plane).compile()
    assert "tpu_custom_call" in c.as_text()
    assert _device_bytes(c) < V5E_HBM_BYTES


def test_staged_plan_three_programs_compile(one_chip):
    """The staged three-program plan (the 2^30 production segment's) at
    a size that compiles in seconds: ``fft_len_cap`` lowered so the
    four-step recursion the big shape takes is the one compiled."""
    from srtb_tpu.pipeline.segment import SegmentProcessor

    proc = SegmentProcessor(
        _j1644(20, spectrum_channel_count=1 << 8, fft_len_cap=1 << 9,
               baseband_reserve_sample=False),
        staged=True, donate_input=True)
    assert proc.staged
    compiled = _compile_all(proc, one_chip)
    assert set(compiled) == {"stage_a", "stage_b", "stage_c"}
    for name, c in compiled.items():
        assert _device_bytes(c) < V5E_HBM_BYTES, name


def test_the_2p30_plan_holds_no_temporary_of_a_whole_plane(one_chip,
                                                           monkeypatch):
    """The plan a 2^30-sample segment resolves to by itself
    (``staged:...+rows``), at 2^22 samples / 2^5 channels with the size
    rules patched down and ``fft_len_cap`` lowered so a row's transform
    is the four-step inside a block that the big shape's is: each of the
    three programs walks the boundary in blocks, so its temporaries stay
    under ONE float32 plane of the spectrum (n/2 x 4 bytes; the
    whole-plane spellings held six and seven of them at 2^30 and were
    refused), stages (b) and (c) alias the donated boundary, and every
    program compiles in seconds (at 2^30 / 2^11: 0.41 / 0.04 / 0.40 GB
    of temporaries against a 2.15 GB plane, 5 / 4 / 9 s;
    ``benchmark/selftest/aot_compile.py j1644_2p30.replay_quiet``)."""
    import time

    from srtb_tpu.pipeline import segment
    from srtb_tpu.pipeline.segment import SegmentProcessor

    log2n = 22
    monkeypatch.setattr(segment, "STAGED_MIN_N", 1 << log2n)
    monkeypatch.setattr(segment, "FUSED_TAIL_DF64_MAX_SPECTRUM", 1 << 10)
    proc = SegmentProcessor(
        _j1644(log2n, spectrum_channel_count=1 << 5, fft_len_cap=1 << 9,
               baseband_reserve_sample=False,
               mitigate_rfi_freq_list="1418-1422"),
        donate_input=True)
    assert proc.plan_name == "staged:monolithic+rows"
    assert proc.staged_rows == 8 and proc.rfi_mask is None
    plane = (1 << (log2n - 1)) * 4
    t0 = time.perf_counter()
    compiled = _compile_all(proc, one_chip)
    assert time.perf_counter() - t0 < 120
    assert set(compiled) == {"stage_a", "stage_b", "stage_c"}
    for name, c in compiled.items():
        m = c.memory_analysis()
        assert m.temp_size_in_bytes < plane, (name, m)
    for name in ("stage_b", "stage_c"):
        m = compiled[name].memory_analysis()
        assert m.alias_size_in_bytes >= 2 * plane, (name, m)



# ---- the plan `auto` picks on a chip (ISSUE 43) -----------------------

V5E_BYTES_LIMIT = 16_911_433_728      # ``memory_stats()["bytes_limit"]``


def _as_on_a_chip(monkeypatch):
    """Steer the program onto its TPU branch in the test (it has no
    option for it): the backend test says "a TPU", the chip reports a
    v5e's ``bytes_limit``, and the chirp bank, which a compile needs the
    shape of and nothing else, is zeros (the float64 phase takes 15-30 s
    of one host thread at these sizes)."""
    import numpy as np

    from srtb_tpu.ops import dedisperse
    from srtb_tpu.utils import platform

    monkeypatch.setattr(platform, "on_accelerator", lambda: True)
    monkeypatch.setattr(platform, "device_bytes_limit",
                        lambda: V5E_BYTES_LIMIT)
    monkeypatch.setattr(
        dedisperse, "chirp_factor_host_ri",
        lambda n, *a, **k: np.zeros((2, n), np.float32))


def _two_in_flight_bytes(compiled) -> int:
    """One program's temporaries and arguments, and the outputs (the
    waterfall, the next carry) of two segments."""
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + 2 * m.output_size_in_bytes)


def test_j1644_under_auto_is_the_own_transform_and_fits_twice(
        one_chip, monkeypatch):
    """The J1644 2^27 served plan as `auto` resolves it on a v5e: the
    repo's own transform with the tail in its post pass.  Its three
    programs go through Mosaic (pass 1, pass 2, the post pass: three
    custom calls each, no XLA FFT left but the waterfall's), and two
    segments in flight fit the chip."""
    from srtb_tpu.pipeline.segment import SegmentProcessor

    _as_on_a_chip(monkeypatch)
    proc = SegmentProcessor(_j1644(27), donate_input=True)
    assert proc.plan_name == "fused:pallas2+ftail+ring"
    assert proc.own_tail and proc._pallas_interpret is False
    assert proc.chirp_w.shape == (4, (1 << 26) // 128, 128)
    compiled = _compile_all(proc, one_chip)
    assert set(compiled) == {"fused", "ring", "ring_cold"}
    for name, c in compiled.items():
        text = c.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 3, name
        assert _two_in_flight_bytes(c) < V5E_BYTES_LIMIT, (
            name, c.memory_analysis())
        # what the fit rule of ops/fft.resolve_strategy reckons with
        assert _two_in_flight_bytes(c) < (24 + 8) * (1 << 27), name


def test_j1644_two_streams_under_auto_run_the_kernels_in_the_loop(
        one_chip, monkeypatch):
    """Both polarisations: the same three kernels, once, inside the loop
    over the streams, fed each stream's own blocked field planes."""
    from srtb_tpu.pipeline.segment import SegmentProcessor

    _as_on_a_chip(monkeypatch)
    proc = SegmentProcessor(
        _j1644(27, baseband_format_type="interleaved_samples_2"),
        donate_input=True)
    assert proc.plan_name == "fused:pallas2+ftail+ring" and proc.own_tail
    c = _compile_all(proc, one_chip, only={"ring"})["ring"]
    assert c.as_text().count('custom_call_target="tpu_custom_call"') == 3
    assert _two_in_flight_bytes(c) < V5E_BYTES_LIMIT, c.memory_analysis()
    assert _two_in_flight_bytes(c) < (24 + 2 * 8) * (1 << 27)


@pytest.mark.parametrize("kw,staged", [
    ({"use_pallas": True, "use_pallas_sk": True}, None),  # chip_smoke's
    ({"fused_tail": "off"}, None),      # the ladder's Mosaic-free rung
    ({}, True),                         # the ladder's staged rung
])
def test_j1644_plans_auto_never_measured_keep_xlas_transform(
        monkeypatch, kw, staged):
    """On a v5e too, a J1644 2^27 plan that would not run the own
    transform whole is the parent's plan under `auto`: its name, its
    signature and the gauge say XLA's R2C, and no bank is precombined."""
    from srtb_tpu.pipeline.segment import SegmentProcessor
    from srtb_tpu.utils.metrics import metrics

    from srtb_tpu.utils import platform

    cfg = _j1644(27, **kw)
    _as_on_a_chip(monkeypatch)
    proc = SegmentProcessor(cfg, staged=staged)
    monkeypatch.setattr(platform, "on_accelerator", lambda: False)
    off_chip = SegmentProcessor(cfg, staged=staged)
    assert proc.strategy == "monolithic" and not proc.own_tail
    assert metrics.get("segment_r2c_own") == 0
    assert "pallas2" not in proc.plan_name and proc._pallas_interpret is False
    assert proc.plan_name == off_chip.plan_name
    # ... but for the kernels' interpret mode, which follows the backend
    sig, sig_off = (json.loads(p.plan_signature()) for p in (proc, off_chip))
    assert sig.pop("interp") is False and sig_off.pop("interp") is True
    assert sig == sig_off
    assert proc.chirp_w is None


def test_naoc_1g_under_auto_is_the_own_transform_and_fits_twice(
        one_chip, monkeypatch):
    """The 1 GSa/s deployment (2^28 samples of 8 bits, 2^15 channels) as
    `auto` resolves it on a v5e since PR 48: a packed transform of 2^27
    points has no leg the column-native passes hold in VMEM (a leg of
    2^14), so the bytes go as TWO plane pairs of 2^26 points, every
    fourth sample a plane, through legs 8192 x 8192, and the post pass
    joins them.  The programs go through Mosaic (three custom calls) and
    the deal-out is lane-dense: no array of weight has a minor dimension
    of 2 or 4, which the chip pads to 128 lanes (every fourth byte of
    rows of 512 as a gather, as the two-stream split takes every other
    one, and the cast in the fusion that writes the four planes, under
    the R2C's name).  Two segments in flight, with the chirp bank the
    plan keeps beside them, hold under what `resolve_strategy` asks of a
    chip's `bytes_limit` at this key (the chip's own peak, 12.02 GB):
    the programs read 9.53 GB, because at 2^15 channels the waterfall's
    C2C holds 18 B a sample of temporaries (4.83 GB, in XLA's plan too)
    where the J1644 shape holds 8.6."""
    from srtb_tpu.ops import fft as F
    from srtb_tpu.pipeline.segment import SegmentProcessor

    _as_on_a_chip(monkeypatch)
    proc = SegmentProcessor(_naoc(28, 14.2), donate_input=True)
    assert proc.plan_name == "fused:pallas2+ftail+ring"
    assert proc.own_tail and proc.fused_tail
    assert proc._pallas_interpret is False
    assert proc.chirp_w.shape == (4, (1 << 27) // 128, 128)
    compiled = _compile_all(proc, one_chip, only={"ring", "ring_cold"})
    assert set(compiled) == {"ring", "ring_cold"}
    for name, c in compiled.items():
        text = c.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 3, name
        # the chirp bank stays on the chip though the programs read the
        # post pass's bank alone
        held = _two_in_flight_bytes(c) + proc.chirp.nbytes
        rule = F.OWN_R2C_READ_FASTER[(8, 1, 2, 8192, 8192)]
        assert 0.85 * rule < held <= rule < V5E_BYTES_LIMIT, (
            name, c.memory_analysis())
        assert c.memory_analysis().temp_size_in_bytes < 18.1 * (1 << 28)
        assert not re.search(r"(f32|u8|s8)\[\d{5,},[24]\]", text), name
        front = [ln for ln in text.splitlines()
                 if re.match(r"\s+%\S+ = f32\[4,8192,8192\]", ln)]
        assert len(front) == 1 and " fusion(" in front[0] \
            and "srtb.fft_r2c" in front[0], [ln[:200] for ln in front]
        # nothing runs under a name this cell's metrics do not read
        assert "srtb.unpack" not in text, name


# ---- the upstream defaults at the Crab's DM (ISSUE 44) -----------------

def test_naoc_crab_2p30_ring_assembles_by_strips_and_fits_twice(one_chip):
    """The deployment of ``benchmark/configs/naoc_crab_2p30.json`` at its
    own size: 2^30 samples of 8 bits, 2^15 channels, DM 56.77, 24.38 % of
    every segment overlapped.  The program picks the staged plan's ring
    by itself; its warm and cold stage (a) take the bytes as rows of
    65536 (3994 of carry, 12390 new) and hold no ``u8`` temporary of a
    whole segment, neither the join of carry and new bytes nor a
    relayout of them (the parent held both, 1.14 GB of temporaries where
    these hold 0.10 / 0.07), the carry aliases its donated twin, and by
    section 4's reckoning (three boundaries, two uploads, two carries,
    the largest program's temporaries) two segments in flight stay under
    16.0 GB with a cold pass among them."""
    from srtb_tpu.pipeline.segment import SegmentProcessor

    proc = SegmentProcessor(_naoc(30, 56.77), donate_input=True)
    assert proc.plan_name == "staged:four_step+rows+ring"
    assert (proc.reserved_bytes, proc.stride_bytes) \
        == (261_750_784, 811_991_040)
    assert proc.time_reserved_count == 7988 and proc.watfft_len == 16384
    assert proc.ring_row_bytes == 65536
    programs = {"stage_a_ring", "stage_a_cold", "stage_b", "stage_c"}
    compiled = _compile_all(proc, one_chip, only=programs)
    assert set(compiled) == programs
    memory = {name: c.memory_analysis() for name, c in compiled.items()}
    boundary = 2 * (1 << 29) * 4
    for name in ("stage_a_ring", "stage_a_cold"):
        text = compiled[name].as_text()
        # what makes an array of a whole segment's bytes: nothing, but
        # the cold program's own argument, read in place by its loop
        made = set(re.findall(
            r"= u8\[(?:1073741824|16384,65536)\]\S* ([a-z-]+)\(", text))
        assert made <= {"parameter", "get-tuple-element"}, made
        assert memory[name].temp_size_in_bytes < 0.5e9, memory[name]
        # the boundary and the next carry (3994 rows tiled as 4000)
        assert 0 <= memory[name].output_size_in_bytes \
            - boundary - proc.reserved_bytes < 1 << 20
    assert 0 <= memory["stage_a_ring"].alias_size_in_bytes \
        - proc.reserved_bytes < 1 << 20
    for name in ("stage_b", "stage_c"):
        assert memory[name].alias_size_in_bytes >= boundary, memory[name]
    in_flight = (3 * boundary + (1 << 30) + proc.stride_bytes
                 + 2 * proc.reserved_bytes
                 + max(m.temp_size_in_bytes for m in memory.values()))
    assert in_flight < 16.0e9 < V5E_BYTES_LIMIT, in_flight
