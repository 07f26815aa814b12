"""The cell PR 40 added, ``j1644_2p30.replay_quiet``: the J1644-4559
recording at the segment its own cfg states (2^30 samples, 2^11 channels,
reserve 0), the only cell the staged plan runs in.  Its files load through
``spec.py`` as ``run.py`` loads them, its configuration is ``j1644_2p27``'s
with the source's two options back (the segment and the reserve), its
layout's bytes are the deployment's (a 256 MiB segment, nothing
overlapped), every limit of its comparison says why, the per-layer
metrics it joins read it and the three ``ops.stage_*`` it brings read a
trace by program (``reducers/programs.py``).

Its tiny relative ``tiny_2p30.replay_quiet`` lives in a root of its own,
``selftest/tiny_staged/`` (``stands_for`` this cell, 2^16 samples,
reserve 0), and runs end to end on the CPU here with
``segment.STAGED_MIN_N`` patched down, as the plan's own tests patch it
(``tests/test_staged_rows.py``): no option chooses the plan.

Compiling the configuration for a described v5e is rehearsal 3 of
README.md: ``python benchmark/selftest/aot_compile.py
j1644_2p30.replay_quiet`` (three programs, none refused).
"""

import json
import os

import pytest
from test_run import run_cell
from test_scopes import US, ld, plane, vi, write_space

from benchmark import counts, gen, spec as spec_mod
from benchmark.reducers import programs
from benchmark.reference import chain

HERE = os.path.dirname(os.path.abspath(__file__))
TINY_STAGED = os.path.join(HERE, "tiny_staged")
CELL = "j1644_2p30.replay_quiet"
TINY_CELL = "tiny_2p30.replay_quiet"
FLAGSHIP = "j1644_2p27.replay_quiet"
STAGES = ("ops.stage_a_ms_per_seg", "ops.stage_b_ms_per_seg",
          "ops.stage_c_ms_per_seg")


def test_the_2p30_cells_files_load_through_spec():
    sp = spec_mod.Spec(spec_mod.HERE, CELL)
    assert sp.chips == 1 and sp.workload["driver"] == "served"
    assert set(sp.config["reduced"]) == {"gui_enable"}
    assert "inflight_segments" not in sp.config["options"]   # 2, the default
    assert "baseband_format_type" in sp.config["assumed"]
    assert sp.config["guarantees"] and "rehearsal" not in sp.config
    entry = next(c for c in sp.bench["configs"] if c["name"] == "j1644_2p30")
    assert entry["reduced"] == ["gui_enable"]
    assert all(len(entry[k]) <= 200 for k in ("source", "why"))
    assert len(sp.cell["why"]) <= 200
    check = sp.workload["check"]
    assert set(check["limits"]) == {"series_gap", "snr_gap", "bin_gap"}
    assert check["limits"]["bin_gap"] == 0
    # every limit says what it was set from
    assert set(check["limits_why"]) == set(check["limits"])
    assert all(len(text) > 40 for text in check["limits_why"].values())
    assert sp.workload["source"] == {"kind": "file_replay",
                                     "file_segments": 8}
    assert sp.workload["pulses"]["every"] == 0
    assert sp.workload["warmup"]["segments"] == ["pulse", "quiet", "quiet"]
    assert sp.workload["trace"] == {"slice_s": 6.0}
    per_layer = {m["name"] for m, _r in sp.metrics("per_layer")}
    assert set(STAGES) <= per_layer
    # a served cell without a ring: every metric of the flagship's but
    # the ring's two, and the three programs
    flagship = {m["name"] for m, _r in spec_mod.Spec(
        spec_mod.HERE, FLAGSHIP).metrics("per_layer")}
    assert per_layer == (flagship - {"ops.ring_ms_per_seg",
                                     "io.ring_carry_mb_per_seg"}) \
        | set(STAGES)
    assert {m["name"] for m, _r in sp.metrics("end_to_end")} \
        == {"rt_factor", "setup_s"}
    # no other cell reports the three programs
    for m in sp.bench["per_layer"]:
        if m["name"] in STAGES:
            assert m["workloads"] == [CELL] and m["moves"] == "rt_factor"


def test_the_configuration_is_the_flagships_with_the_sources_size_back():
    big = spec_mod.Spec(spec_mod.HERE, CELL).config
    flagship = spec_mod.Spec(spec_mod.HERE, FLAGSHIP).config
    assert list(big["options"]) == list(flagship["options"])
    changed = {k for k in flagship["options"]
               if flagship["options"][k] != big["options"][k]}
    assert changed == {"baseband_input_count", "baseband_reserve_sample"}
    assert big["options"]["baseband_input_count"] == "2 ** 30"
    assert big["options"]["baseband_reserve_sample"] == 0
    # the source itself: examples/srtb_config_1644-4559.cfg:6-23
    with open(os.path.join(spec_mod.CHECKOUT, "examples",
                           "srtb_config_1644-4559.cfg")) as f:
        source = dict(ln.split("=", 1) for ln in f
                      if "=" in ln and not ln.lstrip().startswith("#"))
    source = {k.strip(): v.split("#")[0].strip() for k, v in source.items()}
    for key in ("baseband_input_count", "spectrum_channel_count",
                "baseband_reserve_sample", "baseband_input_bits",
                "baseband_freq_low", "baseband_bandwidth",
                "baseband_sample_rate", "mitigate_rfi_freq_list",
                "mitigate_rfi_average_method_threshold",
                "mitigate_rfi_spectral_kurtosis_threshold",
                "signal_detect_signal_noise_threshold",
                "signal_detect_max_boxcar_length"):
        assert str(big["options"][key]) == source[key], key
    assert float(big["options"]["dm"]) == float(source["dm"]) == -478.8
    assert source["gui_enable"] == "1" and big["options"]["gui_enable"] == 0
    # the rehearsal copy PR 39 left stays as it is, but for its note
    with open(os.path.join(HERE, "next", "configs",
                           "j1644_2p30.json")) as f:
        rehearsed = json.load(f)
    assert rehearsed.pop("rehearsal")
    assert rehearsed == big


def test_the_layout_is_the_sources_segment():
    sp = spec_mod.Spec(spec_mod.HERE, CELL)
    p = chain.params_from_config(sp.config["options"])
    assert (p["n"], p["channels"], p["bits"], p["streams"]) \
        == (1 << 30, 1 << 11, 2, 1)
    for seed in (7, 2147500201, 2 ** 31 + 12345):
        lay = gen.Layout(p, sp.workload, seed)
        assert lay.reserved == 0 and lay.stride == 1 << 30
        assert lay.segment_bytes == lay.stride_bytes == 1 << 28
        assert lay.n_warmup == 3 and lay.n_replay == 8
        assert lay.pulsed == [True] + [False] * 10
        assert 0 < lay.expected_bin(0) < 1 << 18
        sampled = lay.draw_sample(sp.workload["check"]["sample"], seed)
        assert len(sampled) == 1 and not lay.pulsed[sampled[0]]
    # twice the sweep fits the 2^25 template, and a segment holds 8.39 s
    assert 2 * abs(chain.max_delay_time(p["freq_low"], p["bandwidth"],
                                        p["dm"])) * p["sample_rate"] \
        < 1 << 25
    assert p["n"] / p["sample_rate"] == pytest.approx(8.388608)
    # eight times the flagship's bytes behind kernels.hbm_share
    flagship = chain.params_from_config(
        spec_mod.Spec(spec_mod.HERE, FLAGSHIP).config["options"])
    assert counts.segment_bytes_per_chip(p) \
        == 8 * counts.segment_bytes_per_chip(flagship)


def test_the_tiny_relative_stands_for_the_cell():
    sp = spec_mod.Spec(TINY_STAGED, TINY_CELL)
    assert sp.workload["stands_for"] == CELL
    assert sp.config["options"]["baseband_reserve_sample"] == 0
    assert sp.config["options"]["baseband_input_count"] == "2 ** 16"
    big = spec_mod.Spec(spec_mod.HERE, CELL)
    assert {m["name"] for m, _r in sp.metrics("per_layer")} \
        == {m["name"] for m, _r in big.metrics("per_layer")}
    assert {m["name"] for m, _r in sp.metrics("end_to_end")} \
        == {m["name"] for m, _r in big.metrics("end_to_end")}


# ------------------------------------------------ the tiny relative, run

@pytest.fixture
def staged_at_2p16(monkeypatch):
    """What a 2^30 segment meets, at 2^16: the staged plan by the size
    rule, its tail unfused by the bankless rule."""
    from srtb_tpu.pipeline import segment

    monkeypatch.setattr(segment, "STAGED_MIN_N", 1 << 16)
    monkeypatch.setattr(segment, "FUSED_TAIL_DF64_MAX_SPECTRUM", 1 << 10)


def run_tiny(capsys, trace=0):
    return run_cell(capsys, workload=TINY_CELL, root=TINY_STAGED,
                    trace=trace)


def test_a_sound_run_of_the_staged_plan_is_correct(capsys, staged_at_2p16):
    rc, out, lines = run_tiny(capsys, trace=1)
    assert rc == 0 and out["correct"] and out["failed"] == 0
    assert any("plan: staged:monolithic+rows" in ln for ln in lines)
    assert out["checks"]["failed"] == []
    for key in ("series_gap", "snr_gap", "bin_gap"):
        value, limit = out["checks"][key]
        assert 0 <= value <= limit
    # the CPU's own profile holds no device plane: the three programs'
    # readers find nothing to read, return nothing and raise nothing
    assert not set(STAGES) & set(out["metrics"])
    assert {"runtime.fetch_ms", "io.h2d_ms_per_seg",
            "runtime.enqueue_ms_per_seg"} <= set(out["metrics"])


def test_without_the_patch_the_tiny_cell_is_the_fused_plan(capsys):
    """The size rule, not the cell's files, chooses the plan."""
    rc, out, lines = run_tiny(capsys)
    assert rc == 0 and out["correct"]
    assert any("plan: fused:monolithic" in ln for ln in lines)


def test_a_broken_stage_makes_correct_false(capsys, monkeypatch,
                                            staged_at_2p16):
    from srtb_tpu.ops import fft as F

    sound = F.hermitian_rfft_post_rows
    monkeypatch.setattr(
        F, "hermitian_rfft_post_rows",
        lambda z_ri, blocks: sound(z_ri, blocks).at[1].multiply(1.001))
    rc, out, _lines = run_tiny(capsys)
    assert rc == 0 and out["correct"] is False
    value, limit = out["checks"]["series_gap"]
    assert value > limit


# --------------------------------------- device time by program, by hand

def staged_plane() -> bytes:
    """A device plane of two segments of a three-program plan: every
    operation names its program at the head of its ``op_name`` but the
    copies the compiler made, which lie inside a launch of the
    ``XLA Modules`` line."""
    ops = {
        1: ("%fusion.1 = f32[8] fusion(...)",
            "jit(_stage_a)/jit(main)/srtb.fft_r2c/while/body/mul:"),
        2: ("%while.2 = f32[8] while(...)",
            "jit(_stage_b)/jit(main)/srtb.fft_r2c/while:"),
        3: ("%fusion.3 = f32[8] fusion(...)",
            "jit(_stage_b)/jit(main)/srtb.fft_r2c/while/body/add:"),
        4: ("%fusion.4 = f32[8] fusion(...)",
            "jit(_stage_c)/jit(main)/srtb.waterfall/while/body/"
            "srtb.chirp/sin:"),
        5: ("%copy.9 = f32[8] copy(...)", None),
    }
    modules = {11: "jit__stage_a(111)", 12: "jit__stage_b(222)",
               13: "jit__stage_c(333)"}
    events, launches = [], []
    for seg in (0, 1):
        t = seg * 100 * US
        events += [(1, t, 10 * US),                     # stage (a)
                   (5, t + 10 * US, 2 * US),            # its copy
                   (2, t + 20 * US, 30 * US),           # stage (b)'s loop
                   (3, t + 25 * US, 10 * US),           # ... and its body
                   (5, t + 50 * US, 3 * US),            # stage (b)'s copy
                   (4, t + 60 * US, 25 * US)]           # stage (c)
        launches += [(11, t, 13 * US), (12, t + 20 * US, 34 * US),
                     (13, t + 60 * US, 26 * US)]
    out = plane("/device:TPU:0", ops, events)
    for meta_id, name in modules.items():
        out += ld(4, vi(1, meta_id) + ld(2, vi(1, meta_id)
                                         + ld(2, name.encode())))
    return out + ld(3, ld(2, b"XLA Modules") + vi(3, 0) + b"".join(
        ld(4, vi(1, m) + vi(2, off) + vi(3, dur))
        for m, off, dur in launches))


def test_device_time_by_program_adds_up_to_the_busy_union(tmp_path):
    path = write_space(tmp_path, staged_plane())
    got = programs.program_seconds(path)
    want = {"_stage_a": 2 * (10 + 2), "_stage_b": 2 * (30 + 3),
            "_stage_c": 2 * 25}
    assert got == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    # cut to a slice that begins inside the first stage (b) and ends
    # inside the second stage (c): what straddles an end keeps its part
    cut = programs.program_seconds(path, (30 * US, 170 * US))
    assert cut == pytest.approx({
        "_stage_a": (10 + 2) * 1e-6, "_stage_b": (20 + 3 + 30 + 3) * 1e-6,
        "_stage_c": (25 + 10) * 1e-6})
    assert sum(cut.values()) == pytest.approx(103e-6)


def test_an_unnamed_trace_and_an_untraced_run_read_as_nothing(tmp_path):
    # operations that name no jit( program, and no module line: {}
    ops = {1: ("%fusion.1 = f32[8] fusion(...)", "srtb.fft_r2c/mul:"),
           2: ("%copy.1 = f32[8] copy(...)", None)}
    bare = write_space(tmp_path, plane("/device:TPU:0", ops,
                                       [(1, 0, US), (2, US, US)]))
    assert programs.program_seconds(bare) == {}

    class Untraced:
        trace = None

    assert programs.program_ms_per_seg(
        Untraced(), {"programs": ["_stage_a"]}) is None
