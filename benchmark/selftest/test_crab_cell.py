"""The cell PR 44 added, ``naoc_crab_2p30.replay_quiet``: the upstream
defaults at the Crab's DM (8-bit 1 GSa/s, 2^15 channels, the overlap on)
over 2^30-sample segments, the only cell the staged plan's ring runs in.
Its configuration is ``naoc_1g_dm14``'s with two options changed (the DM
and the segment that holds its reserve), its files load through
``spec.py`` as ``run.py`` loads them, its layout's numbers are the
deployment's (261 750 784 samples reserved of 2^30, 24.38 %; the detector
trims 7988 of 16384 time samples), each limit of its comparison says why
(the warm-up's first segment holds a giant pulse: every run detects once
and dumps once at this size, and the S/N and the peak's bin are held
beside the series), and the one per-layer metric it brings,
``ops.stage_a_ring_ms_per_seg``, reads a trace by program
(``reducers/programs.py``) and nothing from a program without the staged
ring.

Its source kind is ``file_replay_needs`` (``sources/file_replay_needs.py``):
``file_replay`` behind what the cell says the program must have
(``source.needs``: ``utils/platform.to_host_rows``, the block fetch the
one-copy candidate is made with).  The program before PR 44 holds the
4.29 GB waterfall three times beside the reference's 19.5 GB child, and
the one-chip machine killed it; here it ends the run by itself, exit 1.

Its tiny relative ``tiny_crab.replay_quiet`` lives in a root of its own,
``selftest/tiny_staged_ring/`` (``stands_for`` this cell, 2^16 8-bit
samples, a quarter of every segment overlapped), and runs end to end on
the CPU here with ``segment.STAGED_MIN_N`` patched down, as the plan's
own tests patch it (``tests/test_staged_ring_strips.py``): no option
chooses the plan.

Compiling the configuration for a described v5e is rehearsal 3 of
README.md: ``python benchmark/selftest/aot_compile.py
naoc_crab_2p30.replay_quiet`` (five programs, none refused; the ring's
stage (a) 0.10 GB of temporaries warm, 0.07 cold).
"""

import json
import os

import pytest
from test_2p30_cell import staged_at_2p16  # noqa: F401  (fixture)
from test_naoc_cell import reader
from test_run import run_cell
from test_scopes import US, ld, plane, vi, write_space

from benchmark import counts, gen, spec as spec_mod
from benchmark.reducers import programs
from benchmark.reference import chain

HERE = os.path.dirname(os.path.abspath(__file__))
TINY_ROOT = os.path.join(HERE, "tiny_staged_ring")
CELL = "naoc_crab_2p30.replay_quiet"
TINY_CELL = "tiny_crab.replay_quiet"
SIBLING = "naoc_1g_dm14.replay_quiet"         # the same defaults at DM 14.2
NEW = "ops.stage_a_ring_ms_per_seg"
NEEDS = "srtb_tpu.utils.platform.to_host_rows"
RESERVED, STRIDE = 261_750_784, 811_991_040


def test_the_crab_cells_files_load_through_spec():
    sp = spec_mod.Spec(spec_mod.HERE, CELL)
    assert sp.chips == 1 and sp.workload["driver"] == "served"
    assert set(sp.config["reduced"]) == {"gui_enable"}
    assert "inflight_segments" not in sp.config["options"]   # 2, the default
    assert {"dm", "baseband_input_count"} <= set(sp.config["assumed"])
    assert sp.config["guarantees"] == spec_mod.Spec(
        spec_mod.HERE, SIBLING).config["guarantees"]
    entry = next(c for c in sp.bench["configs"]
                 if c["name"] == "naoc_crab_2p30")
    assert entry["reduced"] == ["gui_enable"]
    assert entry == sp.bench["configs"][-1] and sp.cell == \
        sp.bench["workloads"][-1]                  # appended, not inserted
    assert all(len(entry[k]) <= 200 for k in ("source", "why"))
    assert len(sp.cell["why"]) <= 200
    check = sp.workload["check"]
    # the warm-up pulse is detected and dumped: its S/N and its bin are
    # compared beside the series
    assert check["limits"] == {"series_gap": 1e-3, "snr_gap": 6e-4,
                               "bin_gap": 0}
    assert set(check["limits_why"]) == set(check["limits"])
    assert all(len(text) > 40 for text in check["limits_why"].values())
    assert check["sample"] == {"kind": "quiet", "count": 1, "within": 6}
    # the file replay of ISSUE 44, behind a statement of what the program
    # must have to run it (sources/file_replay_needs.py)
    source = sp.workload["source"]
    assert set(source) == {"kind", "file_segments", "needs", "needs_why"}
    assert (source["kind"], source["file_segments"], source["needs"]) == (
        "file_replay_needs", 6, [NEEDS])
    assert "exit 137" in source["needs_why"]
    assert sp.workload["pulses"] == {"every": 0, "dm": 56.77, "amp": 40.0,
                                     "width": 32, "template_log2": 28}
    assert sp.workload["warmup"]["segments"] == ["pulse", "quiet", "quiet"]
    assert sp.workload["trace"] == {"slice_s": 6.0}
    assert {m["name"] for m, _r in sp.metrics("end_to_end")} \
        == {"rt_factor", "setup_s"}
    # what the 1 GSa/s cell reads, less the bank the staged plan does not
    # hold, plus the three programs (the ring's stage (a), not the plain
    # one's, whose program the ring never runs)
    per_layer = {m["name"] for m, _r in sp.metrics("per_layer")}
    sibling = {m["name"] for m, _r in spec_mod.Spec(
        spec_mod.HERE, SIBLING).metrics("per_layer")}
    # ... and the 8-bit cast, which the staged plan's stage (a) makes an
    # operation of its own under srtb.unpack (the fused plan folds it
    # into the R2C, where no operation carries the name)
    candidate = {n for n in sibling if n.startswith("io.candidate_")}
    assert len(candidate) == 7 and candidate <= per_layer
    assert per_layer == (sibling - {"plan.chirp_bank_s"}) | {
        NEW, "ops.stage_b_ms_per_seg", "ops.stage_c_ms_per_seg",
        "ops.unpack_ms_per_seg"}
    assert "ops.stage_a_ms_per_seg" not in per_layer
    new = next(m for m in sp.bench["per_layer"] if m["name"] == NEW)
    assert new == sp.bench["per_layer"][-1]
    assert new["workloads"] == [CELL] and new["moves"] == "rt_factor"
    # joined at the end of every list it joined
    for m in sp.bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]


def test_the_configuration_is_the_1g_defaults_at_the_crabs_dm():
    crab = spec_mod.Spec(spec_mod.HERE, CELL).config
    sibling = spec_mod.Spec(spec_mod.HERE, SIBLING).config
    assert list(crab["options"]) == list(sibling["options"])
    changed = {k for k in sibling["options"]
               if sibling["options"][k] != crab["options"][k]}
    assert changed == {"baseband_input_count", "dm"}
    assert crab["options"]["baseband_input_count"] == "2 ** 30"
    assert crab["options"]["dm"] == 56.77
    # the rehearsal copy PR 29 left stays as it was: the same defaults at
    # the segment the program refuses
    with open(os.path.join(HERE, "next", "configs",
                           "naoc_crab_1g.json")) as f:
        rehearsed = json.load(f)["options"]
    assert dict(rehearsed, baseband_input_count="2 ** 30") \
        == crab["options"]


def test_the_reserve_is_the_1g_cells_fraction():
    sp = spec_mod.Spec(spec_mod.HERE, CELL)
    p = chain.params_from_config(sp.config["options"])
    assert (p["n"], p["channels"], p["bits"], p["streams"]) \
        == (1 << 30, 1 << 15, 8, 1)
    sweep = chain.max_delay_time(p["freq_low"], p["bandwidth"], p["dm"]) \
        * p["sample_rate"]
    assert 2 * round(sweep) == 261_697_590
    for seed in (7, 2147520011, 2 ** 31 + 12345):
        lay = gen.Layout(p, sp.workload, seed)
        assert (lay.reserved, lay.stride) == (RESERVED, STRIDE)
        assert lay.reserved % (1 << 16) == 0          # whole columns
        assert lay.segment_bytes == 1 << 30
        assert lay.stride_bytes == STRIDE
        assert lay.n_warmup == 3 and lay.n_replay == 6
        assert lay.total == RESERVED + 9 * STRIDE     # 7.57 GB of file
        # the warm-up's first segment alone holds a pulse
        assert lay.pulsed == [True] + [False] * 8
        sampled = lay.draw_sample(sp.workload["check"]["sample"], seed)
        assert len(sampled) == 1 and not lay.pulsed[sampled[0]]
    assert RESERVED // (1 << 15) == 7988 < 16384
    assert RESERVED / (1 << 30) == pytest.approx(0.2437744140625)
    # naoc_1g_dm14's fraction to a hundredth of a percent, under 3/11
    q = chain.params_from_config(
        spec_mod.Spec(spec_mod.HERE, SIBLING).config["options"])
    small = gen.Layout(q, spec_mod.Spec(spec_mod.HERE, SIBLING).workload, 7)
    assert abs(RESERVED / p["n"] - small.reserved / q["n"]) < 2e-4
    assert RESERVED / p["n"] < 3 / 11
    # whole rows of stage (a)'s view: 3994 of carry, 12390 of new bytes
    assert (RESERVED // 65536, STRIDE // 65536) == (3994, 12390)
    # twice the sweep fits the 2^28 template
    assert 2 * sweep < 1 << 28
    assert STRIDE / p["sample_rate"] == pytest.approx(0.81199104)
    # four times the 1 GSa/s cell's bytes behind kernels.hbm_share
    assert counts.segment_bytes_per_chip(p) \
        == 4 * counts.segment_bytes_per_chip(q)


def test_the_tiny_relative_stands_for_the_crab_cell():
    sp = spec_mod.Spec(TINY_ROOT, TINY_CELL)
    assert sp.workload["stands_for"] == CELL
    big_source = spec_mod.Spec(spec_mod.HERE, CELL).workload["source"]
    assert (sp.workload["source"]["kind"], sp.workload["source"]["needs"]) \
        == (big_source["kind"], big_source["needs"])
    options = sp.config["options"]
    assert options["baseband_reserve_sample"] == 1
    assert (options["baseband_input_bits"],
            options["baseband_input_count"]) == (8, "2 ** 16")
    big = spec_mod.Spec(spec_mod.HERE, CELL)
    assert {m["name"] for m, _r in sp.metrics("per_layer")} \
        == {m["name"] for m, _r in big.metrics("per_layer")}
    assert {m["name"] for m, _r in sp.metrics("end_to_end")} \
        == {m["name"] for m, _r in big.metrics("end_to_end")}
    p = chain.params_from_config(options)
    lay = gen.Layout(p, sp.workload, 7)
    assert 0.2 < lay.reserved / lay.n < 3 / 11


# ------------------------------------------------ the tiny relative, run

def run_tiny(capsys, trace=0):
    return run_cell(capsys, workload=TINY_CELL, root=TINY_ROOT,
                    trace=trace)


def test_a_sound_run_of_the_staged_ring_is_correct(capsys, staged_at_2p16):
    rc, out, lines = run_tiny(capsys, trace=1)
    assert rc == 0 and out["correct"] and out["failed"] == 0
    assert any("plan: staged:monolithic+rows+ring" in ln for ln in lines)
    assert out["checks"]["failed"] == []
    assert set(out["checks"]) == {"series_gap", "snr_gap", "bin_gap",
                                  "failed"}
    for key in ("series_gap", "snr_gap", "bin_gap"):
        value, limit = out["checks"][key]
        assert 0 <= value <= limit, key
    # the warm-up's candidate was dumped and its spans read
    assert {"io.candidate_d2h_s", "io.candidate_write_s",
            "io.candidate_mb"} <= set(out["metrics"])
    # the CPU's own profile holds no device plane: the programs' readers
    # find nothing to read, return nothing and raise nothing
    assert not {NEW, "ops.stage_b_ms_per_seg",
                "ops.stage_c_ms_per_seg"} & set(out["metrics"])
    # the carry the device kept a warm dispatch: 127 rows of 128 bytes
    assert out["metrics"]["io.ring_carry_mb_per_seg"]["value"] \
        == pytest.approx(127 * 128 / 1e6)
    assert {"runtime.fetch_ms", "io.h2d_ms_per_seg",
            "runtime.enqueue_ms_per_seg"} <= set(out["metrics"])


def test_a_program_without_what_the_cell_needs_ends_the_run_by_itself(
        capsys, monkeypatch):
    """The program before PR 44 (no ``to_host_rows``: three host copies
    of the candidate's waterfall) is not left to the host's kill: no
    result line, exit code 1, the reason said, and neither the program
    built nor the reference's child started."""
    from benchmark import run
    from benchmark.harness import Run
    from benchmark.sources import file_replay_needs
    from srtb_tpu.pipeline import runtime
    from srtb_tpu.utils import platform

    assert file_replay_needs.missing([NEEDS]) == []
    monkeypatch.delattr(platform, "to_host_rows")
    assert file_replay_needs.missing(
        [NEEDS, "srtb_tpu.no_such_module.x"]) \
        == [NEEDS, "srtb_tpu.no_such_module.x"]
    built = []
    monkeypatch.setattr(runtime.Pipeline, "__init__",
                        lambda self, *a, **k: built.append(self))
    monkeypatch.setattr(Run, "start_reference",
                        lambda self, dms_of: built.append(dms_of))
    rc = run.main(["--root", TINY_ROOT, "--workload", TINY_CELL, "--seed",
                   "11", "--seconds", "1", "--trace", "0", "--allow-cpu"])
    text = capsys.readouterr()
    assert rc == 1 and built == []
    assert "CANNOT RUN THIS CELL" in text.out and NEEDS in text.out
    assert "CannotRunCell" in text.err
    assert not any(ln.startswith("{") for ln in text.out.splitlines())
    assert not os.path.exists(os.path.join(
        spec_mod.CHECKOUT, ".bench_work", TINY_CELL))


def test_the_other_cells_keep_the_plain_file_replay():
    """The guard is this cell's alone: every accepted cell names
    ``file_replay``, and both kinds are in the one table."""
    kinds = spec_mod.load_registry("sources", "KINDS")
    assert set(kinds) == {"file_replay", "file_replay_needs"}
    assert issubclass(kinds["file_replay_needs"], kinds["file_replay"])
    sp = spec_mod.Spec(spec_mod.HERE, CELL)
    for cell in sp.bench["workloads"][:-1]:
        other = spec_mod.Spec(spec_mod.HERE, cell["name"]).workload
        assert other["source"]["kind"] == "file_replay", cell["name"]


def test_without_the_patch_the_tiny_crab_cell_is_the_fused_ring(capsys):
    """The size rule, not the cell's files, chooses the plan."""
    rc, out, lines = run_tiny(capsys)
    assert rc == 0 and out["correct"]
    assert any("plan: fused:monolithic+ring" in ln for ln in lines)


def test_a_strip_read_from_the_wrong_rows_makes_correct_false(
        capsys, monkeypatch, staged_at_2p16):
    """The ring's next carry taken a row early: every warm segment is
    assembled from the wrong overlap."""
    from srtb_tpu.pipeline.segment import SegmentProcessor

    sound = SegmentProcessor._stage_a_with_carry

    def a_row_early(self, *parts):
        a, carry = sound(self, *parts)
        last = parts[-1]
        rows = carry.shape[0]
        return a, last[last.shape[0] - rows - 1:last.shape[0] - 1]

    monkeypatch.setattr(SegmentProcessor, "_stage_a_with_carry",
                        a_row_early)
    rc, out, _lines = run_tiny(capsys)
    assert rc == 0 and out["correct"] is False
    value, limit = out["checks"]["series_gap"]
    assert value > limit


# --------------------------- the ring's stage (a), device time by program

def ring_plane() -> bytes:
    """A device plane of three segments of the staged ring: a cold
    dispatch (``_stage_a_cold``), then two warm ones (``_stage_a_ring``),
    each followed by stages (b) and (c)."""
    ops = {
        1: ("%fusion.1 = f32[8] fusion(...)",
            "jit(_stage_a_cold)/jit(main)/srtb.fft_r2c/while/body/mul:"),
        2: ("%fusion.2 = f32[8] fusion(...)",
            "jit(_stage_a_ring)/jit(main)/srtb.fft_r2c/while/body/mul:"),
        3: ("%slice.3 = u8[8] slice(...)",
            "jit(_stage_a_ring)/jit(main)/srtb.ring/slice:"),
        4: ("%fusion.4 = f32[8] fusion(...)",
            "jit(_stage_b)/jit(main)/srtb.fft_r2c/while/body/add:"),
        5: ("%fusion.5 = f32[8] fusion(...)",
            "jit(_stage_c)/jit(main)/srtb.waterfall/while/body/"
            "srtb.chirp/sin:"),
        6: ("%copy.9 = u8[8] copy(...)", None),
    }
    modules = {11: "jit__stage_a_cold(111)", 12: "jit__stage_a_ring(222)",
               13: "jit__stage_b(333)", 14: "jit__stage_c(444)"}
    events, launches = [], []
    for seg in (0, 1, 2):
        t = seg * 100 * US
        head = (1, 11) if seg == 0 else (2, 12)
        events += [(head[0], t, 12 * US),
                   (6, t + 12 * US, 2 * US),            # its own copy
                   (4, t + 20 * US, 30 * US), (5, t + 60 * US, 25 * US)]
        if seg:
            events.append((3, t + 14 * US, 1 * US))     # the next carry
        launches += [(head[1], t, 16 * US), (13, t + 20 * US, 31 * US),
                     (14, t + 60 * US, 26 * US)]
    out = plane("/device:TPU:0", ops, events)
    for meta_id, name in modules.items():
        out += ld(4, vi(1, meta_id) + ld(2, vi(1, meta_id)
                                         + ld(2, name.encode())))
    return out + ld(3, ld(2, b"XLA Modules") + vi(3, 0) + b"".join(
        ld(4, vi(1, m) + vi(2, off) + vi(3, dur))
        for m, off, dur in launches))


def test_the_rings_stage_a_is_read_by_program(tmp_path):
    assert reader(NEW)["reducer"] == "trace_program_ms_per_seg"
    assert reader(NEW)["args"] == {"programs": ["_stage_a_ring",
                                                "_stage_a_cold"]}
    path = write_space(tmp_path, ring_plane())
    got = programs.program_seconds(path)
    want = {"_stage_a_cold": 12 + 2, "_stage_a_ring": 2 * (12 + 2 + 1),
            "_stage_b": 3 * 30, "_stage_c": 3 * 25}
    assert got == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    # the plain staged plan's reader finds none of its program here, and
    # the ring's none in a plain staged trace
    assert "_stage_a" not in got
    assert set(reader("ops.stage_a_ms_per_seg")["args"]["programs"]) \
        == {"_stage_a"}


def test_a_program_without_the_staged_ring_reads_as_nothing():
    class Untraced:
        trace = None

    assert programs.program_ms_per_seg(Untraced(), reader(NEW)["args"]) \
        is None
