"""The program names its own time (ISSUE 27): stable ``srtb.<stage>``
scopes on the device programs, and host spans opened through one helper
(``utils/tracing.span``) in ``Pipeline``, the candidate writer and the DM
search loop, journaled under the same names.

(a) the scopes are in the lowered HLO and change nothing but metadata;
(b) the served path journals ``h2d`` / ``enqueue`` inside ``dispatch``,
    ``d2h`` / ``write`` / ``publish`` on segments that dump;
(c) the DM search loop records its five stages once a segment, and one
    ``segment_span`` a segment where it has a journal path; the grid's
    processor takes a segment the loop already uploaded;
(d) a span outside a profiler session costs microseconds;
(e) the set-up path is named too (ISSUE 42): ``construct`` with
    ``chirp_bank`` inside it, each program's ``first_dispatch`` booked
    under its family, the candidate's ``write`` by child with the bytes
    handed over; and the window's segments open the spans they opened
    before, no more.
"""

import collections
import contextlib
import glob
import json
import os
import re
import time

import jax
import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.ops import scopes as S
from srtb_tpu.pipeline.runtime import DMSearchPipeline, Pipeline
from srtb_tpu.pipeline.segment import SegmentProcessor
from srtb_tpu.tools import telemetry_report as TR
from srtb_tpu.utils import telemetry
from srtb_tpu.utils.metrics import metrics
from srtb_tpu.utils.tracing import StageTimer, span

N = 1 << 14
SIX = {S.UNPACK, S.FFT_R2C, S.RFI_S1, S.CHIRP, S.WATERFALL, S.DETECT}
# the overlap-save ring's concatenate and carry slice (ISSUE 31)
RING = SIX | {S.RING}


def _cfg(**extra):
    kw = dict(
        baseband_input_count=N, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=0.05,
        spectrum_channel_count=64,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        signal_detect_max_boxcar_length=64,
        baseband_reserve_sample=True, writer_thread_count=0)
    kw.update(extra)
    return Config(**kw)


# 2-bit samples, streams "1212" by whole bytes: the J1644-4559 cpsr2 file
TWO_POL = dict(baseband_format_type="interleaved_samples_2",
               baseband_input_bits=2)


# ------------------------------------------------- (a) scopes in the HLO

def _hlo(fn, avals) -> tuple:
    """(the lowered module as text, the same with its locations: the
    name stack of every operation)."""
    lowered = fn.lower(*avals)
    return lowered.as_text(), lowered.as_text(debug_info=True)


def _scopes_in(text: str) -> set:
    return set(re.findall(r"srtb\.[a-z0-9_]+", text))


def _served_programs(names, **cfg_extra):
    """{program name: (lowered text, with locations)} of a FRESH
    processor (the tracing caches key on the bound methods, so a second
    build traces again)."""
    staged = cfg_extra.pop("staged", False)
    proc = SegmentProcessor(_cfg(**cfg_extra), staged=staged)
    found = {name: _hlo(fn, avals)
             for name, fn, avals, _d in proc.lowerables() if name in names}
    assert set(found) == set(names), (proc.plan_name, sorted(found))
    return found


def _grid_program():
    from srtb_tpu.parallel import mesh as M
    from srtb_tpu.parallel.segment_dist import DistSegmentProcessor

    cfg = _cfg(baseband_reserve_sample=False, dm=30.0,
               use_emulated_fp64=True)
    proc = DistSegmentProcessor(cfg, M.make_mesh(n_dm=4, n_seq=1),
                                [0.0, 10.0, 20.0, 30.0, 40.0, 50.0,
                                 60.0, 70.0])
    raw = jax.ShapeDtypeStruct((cfg.segment_bytes(1),), np.uint8)
    args = [raw, proc.chirp_bank, proc.rfi_mask]
    return {"grid_step": _hlo(proc._step, args)}


# the served plan with the repo's own transform (ISSUE 43): 2-bit blocked
# planes through the two kernel passes and the post pass, here in
# interpret mode with legs of 128 (production legs are 4096 and 8192)
OWN = dict(fft_strategy="pallas2", baseband_input_bits=2,
           baseband_input_count=1 << 16,
           mitigate_rfi_freq_list="1406-1407")


def _own_programs(names, **extra):
    from srtb_tpu.ops import pallas_fft2 as pf2
    production = pf2.cols_factor
    pf2.cols_factor = lambda m: (128, m // 128) \
        if m >= 128 * 128 and not m & (m - 1) else None
    try:
        return _served_programs(names, **OWN, **extra)
    finally:
        pf2.cols_factor = production


def _strip_ring_programs(names, **extra):
    """The plan a 2^30-sample segment with a reserve resolves to by
    itself (ISSUE 44): the plain staged plan's ring, stage (a) taking
    the carry and the new bytes as rows and joining them a strip at a
    time.  The bankless rule is patched down in the test, as
    ``tests/test_staged_rows.py`` patches it: no option chooses the
    plan."""
    from srtb_tpu.pipeline import segment
    rule = segment.FUSED_TAIL_DF64_MAX_SPECTRUM
    segment.FUSED_TAIL_DF64_MAX_SPECTRUM = N >> 4
    try:
        return _served_programs(names, staged=True, **extra)
    finally:
        segment.FUSED_TAIL_DF64_MAX_SPECTRUM = rule


FAMILIES = {
    # the quiet cell's plan: monolithic R2C, fused, overlap-save ring
    "ring": (lambda: _served_programs({"ring"}), RING),
    "ring_cold": (lambda: _served_programs({"ring_cold"}), RING),
    "staged_ring": (lambda: _served_programs(
        {"stage_a_ring", "stage_a_cold"}, staged=True), {S.RING}),
    "staged_ring_strips": (lambda: _strip_ring_programs(
        {"stage_a_ring", "stage_a_cold"}), {S.RING, S.FFT_R2C}),
    "batch_ring": (lambda: _served_programs(
        {"batch_ring", "batch_cold"}, micro_batch_segments=2), RING),
    "staged": (lambda: _served_programs(
        {"stage_a", "stage_b", "stage_c"}, staged=True,
        baseband_reserve_sample=False), SIX),
    "quality": (lambda: _served_programs({"ring"}, quality_stats=True),
                RING | {S.QUALITY}),
    "grid_step": (_grid_program, SIX),
    # two polarisations byte-interleaved in one segment, split on the
    # device (ISSUE 36): the two-stream cell's plan
    "ring_2pol": (lambda: _served_programs({"ring"}, **TWO_POL), RING),
    "ring_own": (lambda: _own_programs({"ring"}), RING),
}
# no reserve, no ring: these hold no ``srtb.ring``
RINGLESS = {"staged", "grid_step"}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_scopes_are_metadata_only(family, monkeypatch):
    build, expected = FAMILIES[family]
    with_scopes = build()
    seen = set().union(*(_scopes_in(loc) for _t, loc in
                         with_scopes.values()))
    assert seen >= expected, f"{family}: missing {expected - seen}"
    assert seen <= RING | {S.QUALITY}, f"{family}: unknown {seen}"
    assert (S.RING in seen) == (family not in RINGLESS), family
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = build()
    for name, (text, _loc) in with_scopes.items():
        bare, bare_loc = without[name]
        assert not _scopes_in(bare_loc), name
        assert text == bare, \
            f"{family}/{name}: the scopes changed more than metadata"


def test_staged_stages_carry_their_own_scopes():
    """Each staged program names what runs in it: the R2C's two halves in
    (a) and (b), the waterfall and the detection in (c)."""
    progs = _served_programs({"stage_a", "stage_b", "stage_c"},
                             staged=True, baseband_reserve_sample=False)
    locs = {name: _scopes_in(loc) for name, (_t, loc) in progs.items()}
    assert {S.UNPACK, S.FFT_R2C} <= locs["stage_a"]
    assert S.FFT_R2C in locs["stage_b"]
    assert {S.WATERFALL, S.DETECT} <= locs["stage_c"]
    assert S.FFT_R2C not in locs["stage_c"]


def test_nothing_of_the_own_transform_reads_as_unscoped():
    """The plan `auto` picks on a chip (ISSUE 43): the two kernel passes
    carry ``srtb.fft_r2c``, the post pass, which applies the chirp, ``srtb.chirp``,
    the mean-power reduction ``srtb.rfi_s1``, the field extraction
    ``srtb.unpack``; no operation on a plane of the segment's size is
    without a scope (on the chip a kernel is ONE operation, named by the
    scope its ``pallas_call`` was traced under)."""
    _text, located = _own_programs({"ring"})["ring"]
    # the program's own function: in interpret mode a kernel's loop
    # bodies are private functions, shared and so without a name stack
    main = located[located.index("func.func public @main"):]
    main = main[:main.index("\n  func.func private")]
    ops, _names = _located_ops(located, within=main)
    plane = (1 << 16) // 4                      # points of one field plane
    def points(types):
        return max([int(np.prod([int(d) for d in dims.split("x") if d]))
                    for dims in re.findall(r"tensor<((?:\d+x)+)(?:f32|ui8)",
                                           types)] or [0])
    big = [(op, types, name) for op, types, name in ops
           if points(types) >= plane]
    assert len(big) > 100
    bare = [(op, types) for op, types, name in big if not _scopes_in(name)]
    assert not bare, bare[:5]
    kernels = [name for _op, _t, name in ops if "pallas_call" in name]
    assert kernels
    innermost = collections.Counter(
        re.findall(r"srtb\.[a-z0-9_]+", name)[-1] for name in kernels)
    assert set(innermost) == {S.FFT_R2C, S.CHIRP}, innermost
    assert innermost[S.FFT_R2C] >= 2 and innermost[S.CHIRP] >= 1
    reductions = [name for op, _t, name in ops
                  if op == "stablehlo.reduce" and S.RFI_S1 in name]
    assert reductions, "RFI s1's mean power is a reduction of its own"
    fields = [name for op, types, name in ops
              if op == "stablehlo.shift_right_logical"]
    assert fields and all(
        re.findall(r"srtb\.[a-z0-9_]+", name)[-1] == S.UNPACK
        for name in fields)


def _located_ops(located: str, within: str = "") -> tuple:
    """([(operation, its types, its name stack)], {loc id: name stack})
    of a lowered module printed with its locations; the operations of
    the part ``within`` only, where one is given."""
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', located,
                            flags=re.M))
    ops = re.findall(r"^\s+%\S+ = \"?((?:stablehlo\.\w+)|(?:call @\w+))"
                     r"\"?.*?: (.*) loc\((#loc\d+)\)$", within or located,
                     flags=re.M)
    return [(op, types, names.get(loc, "")) for op, types, loc in ops], \
        names


@pytest.mark.parametrize("program", ["stage_a_ring", "stage_a_cold"])
def test_the_staged_rings_byte_operations_are_the_rings_or_stage_as(
        program):
    """The staged ring by strips (ISSUE 44): every operation on bytes in
    its warm and cold stage (a) carries ``srtb.ring`` (the join of a
    strip of the carry's rows with a strip of the new bytes', the slice
    that leaves the next carry), ``srtb.unpack`` or ``srtb.fft_r2c``
    (a block's strip read from the rows, its cast), and none makes an
    array of a whole segment's bytes: neither the flat join nor the
    ``[T, bytes a row]`` view of it."""
    _text, located = _strip_ring_programs({program})[program]
    ops, _names = _located_ops(located)
    byte_ops = [(op, types, name) for op, types, name in ops
                if "ui8>" in types and op != "stablehlo.constant"]
    assert byte_ops
    # the loop's body is printed as a function of its own, its names
    # relative to the loop's: ``srtb.fft_r2c/while``, stage (a)'s
    assert re.search(r"srtb\.fft_r2c/while", located)
    innermost = collections.Counter(
        (re.findall(r"srtb\.[a-z0-9_]+", name) or [S.FFT_R2C])[-1]
        for _op, _t, name in byte_ops)
    assert set(innermost) <= {S.RING, S.UNPACK, S.FFT_R2C}, innermost
    assert {op for op, _t, name in byte_ops if not _scopes_in(name)} \
        <= {"stablehlo.dynamic_slice", "stablehlo.reshape"}
    ring = sorted(op for op, _t, name in byte_ops if S.RING in name)
    row, rows = 2 * 64, N // (2 * 64)            # 8-bit samples, 64 channels
    whole = (f"tensor<{N}xui8>", f"tensor<{rows}x{row}xui8>")
    made = [types for _op, types, _n in byte_ops
            if types.split("->")[-1].strip() in whole]
    assert not made, made
    if program == "stage_a_ring":
        # a strip of each part joined inside the loop, the carry sliced
        assert ring == ["stablehlo.concatenate", "stablehlo.slice"]
        joined = [types for op, types, name in byte_ops
                  if op == "stablehlo.concatenate"]
        strip = re.fullmatch(
            rf"\(tensor<(\d+)x(\d+)xui8>, tensor<(\d+)x\2xui8>\) -> "
            rf"tensor<{rows}x\2xui8>", joined[0])
        assert strip and int(strip.group(2)) < row
        assert int(strip.group(1)) + int(strip.group(3)) == rows
    else:
        assert ring == ["stablehlo.slice"]


def test_the_split_is_the_unpacks_and_no_sample_stack_is_traced():
    """Two streams from one segment (ISSUE 36): every operation that
    touches bytes after the ring has assembled them (the rows of 1024,
    every other byte of a row for each stream, the stack of the two
    streams' BYTES that the loop runs over, the fields' shifts and
    masks in the loop's body) carries ``srtb.unpack``; the only other
    byte operations are the ring's own two and the loop's slice of one
    stream's row, which reads as the waterfall's with the rest of the
    loop's own work.  Since ISSUE 38 each stream's samples exist only
    inside its own pass of the loop, as ``[1, n]``: no ``[2, n]`` array
    of samples is traced at all."""
    _text, located = _served_programs({"ring"}, **TWO_POL)["ring"]
    ops, _names = _located_ops(located)
    byte_ops = [(op, types, name) for op, types, name in ops
                if "ui8>" in types and op != "stablehlo.constant"]
    assert sorted(op for op, _t, name in byte_ops if S.RING in name) \
        == ["stablehlo.concatenate", "stablehlo.slice"]
    loop = [(op, name) for op, _t, name in byte_ops
            if S.RING not in name and S.UNPACK not in name]
    assert loop and all(
        _scopes_in(name) == {S.WATERFALL} and "/while/" in name
        and op in ("stablehlo.dynamic_slice", "stablehlo.reshape")
        for op, name in loop), loop
    split = {op for op, _t, name in byte_ops if S.UNPACK in name}
    assert split >= {"stablehlo.reshape", "stablehlo.gather",
                     "stablehlo.concatenate",
                     "stablehlo.shift_right_logical", "stablehlo.and",
                     "stablehlo.convert"}
    # each stream is taken once from rows of 1024 bytes, every other byte
    taken = [types for op, types, _n in ops if op == "stablehlo.gather"]
    assert len(taken) == 2 and all(
        "(tensor<8x1024xui8>" in t and "-> tensor<8x512xui8>" in t
        for t in taken)
    assert "x2xui8>" not in located        # no minor dimension of 2
    stacks = [types for op, types, _n in byte_ops
              if op == "stablehlo.concatenate" and "-> tensor<2x" in types]
    assert stacks == [f"(tensor<1x{N // 4}xui8>, tensor<1x{N // 4}xui8>)"
                      f" -> tensor<2x{N // 4}xui8>"], stacks
    assert f"tensor<2x{N}xf32>" not in located
    assert f"-> tensor<1x{N}xf32>" in located


@pytest.mark.parametrize("program", ["ring", "ring_cold"])
def test_no_transform_takes_the_stream_axis(program):
    """ISSUE 38: a segment of two streams is two one-stream chains one
    after the other inside the program: ONE ``stablehlo.while`` whose
    body is the one-stream program's chain.  No ``stablehlo.fft`` has
    an operand with a leading dimension of 2; the segment R2C is
    called once, on ``[1, n]`` under ``srtb.fft_r2c``, and the
    waterfall's backward C2C once, on ``[channels, time]`` under
    ``srtb.waterfall``, exactly as in the one-stream program, which
    has no loop."""
    channels = 64
    want = {
        f"(tensor<1x{N}xf32>) -> tensor<1x{N // 2 + 1}xcomplex<f32>>":
            S.FFT_R2C,
        f"(tensor<{channels}x{N // 2 // channels}xcomplex<f32>>) -> "
        f"tensor<{channels}x{N // 2 // channels}xcomplex<f32>>":
            S.WATERFALL}
    for streams, extra in ((2, TWO_POL), (1, {})):
        _text, located = _served_programs({program}, **extra)[program]
        assert located.count("stablehlo.while") == streams - 1
        ffts = re.findall(r"stablehlo\.fft .*?: \((tensor<[^>]*>+)\)",
                          located)
        assert ffts and not any(t.startswith("tensor<2x") for t in ffts), \
            ffts
        ops, _names = _located_ops(located)
        calls = [(types, name) for op, types, name in ops
                 if op.startswith("call @fft")]
        assert sorted(t for t, _n in calls) == sorted(want), calls
        for types, name in calls:
            assert re.findall(r"srtb\.[a-z0-9_]+", name)[-1] \
                == want[types], name


def test_the_streams_join_is_the_waterfalls():
    """The streams' outputs are joined once, at the end
    (``SegmentProcessor._stream_after_stream``, ISSUE 38): the loop
    stacks each output along a new leading axis, the waterfalls
    ``[S, 2, 1, channels, time]`` are turned to ``[2, S, channels,
    time]`` and every array of the detect result loses its axis of 1.
    Every value the two-stream program returns (but the ring's carry)
    is made under ``srtb.waterfall`` and nothing else, so
    ``ops.waterfall_ms_per_seg`` reads the join and nothing the
    program traced is ``unscoped``; every operation of the loop that
    carries no stage's scope of its own (its counter, its slices, its
    stacks) carries that one as well.  One stream traces no join at
    all, as before ISSUE 36."""
    channels, time = 64, N // 2 // 64

    def returned(located):
        """(operation, types, name stack) of each array ``main``
        returns (the boxcar lengths are constants)."""
        main = located[located.index("func.func public @main"):]
        main = main[:main.index("\n  }")]
        ids = re.search(r"^\s+return (.*?) :", main, flags=re.M) \
            .group(1).split(", ")
        _ops, names = _located_ops(located)
        out = []
        for i in ids:
            m = re.search(rf"^\s+{re.escape(i)} = \"?(\S+?)\"? .*?: (.*) "
                          rf"loc\((#loc\d*)\)$", main, flags=re.M)
            if m.group(1) != "stablehlo.constant":
                out.append((m.group(1), m.group(2),
                            names.get(m.group(3), "")))
        return out

    _text, located = _served_programs({"ring"}, **TWO_POL)["ring"]
    outs = returned(located)
    joins, carry = outs[:-1], outs[-1]
    assert S.RING in carry[2], carry
    assert len(joins) == 6
    for op, types, name in joins:
        assert op in ("stablehlo.transpose", "stablehlo.reshape"), op
        assert "-> tensor<2x" in types, types
        assert _scopes_in(name) == {S.WATERFALL}, name
    assert joins[0][:2] == (
        "stablehlo.transpose",
        f"(tensor<2x2x{channels}x{time}xf32>) -> "
        f"tensor<2x2x{channels}x{time}xf32>"), joins[0]
    # ``main`` holds the loop; the stages' own functions follow it
    main = located[located.index("func.func public @main"):]
    main = main[:main.index("\n  }")]
    ops, _names = _located_ops(located, within=main)
    unnamed = [(op, name) for op, _t, name in ops
               if op != "stablehlo.constant" and not _scopes_in(name)]
    assert len(ops) > 20 and not unnamed, unnamed[:5]
    _text, located = _served_programs({"ring"})["ring"]
    one = returned(located)
    assert f"-> tensor<2x1x{channels}x{time}xf32>" in one[0][1]
    assert "stablehlo.while" not in located
    # the detect result leaves as the detector made it
    assert all(re.findall(r"srtb\.[a-z0-9_]+", name)[-1] == S.DETECT
               for _op, _t, name in one[1:-1]), one[1:-1]


# ------------------------------------------ (b) the served path's journal

def _baseband(tmp_path, segments, dm, pulse_at=None, amp=25.0):
    data = make_dispersed_baseband(
        N * segments, 1405.0, 64.0, dm,
        pulse_positions=pulse_at if pulse_at is not None else [],
        pulse_amp=amp, nbits=8)
    path = str(tmp_path / "bb.bin")
    data.tofile(path)
    return path


@pytest.fixture()
def served_run(tmp_path):
    """A few ring segments, a pulse in the first: its record dumps."""
    metrics.reset()
    journal = str(tmp_path / "journal.jsonl")
    cfg = _cfg(input_file_path=_baseband(tmp_path, 3, 0.05,
                                         pulse_at=N // 2),
               baseband_output_file_prefix=str(tmp_path / "out_"),
               telemetry_journal_path=journal,
               signal_detect_signal_noise_threshold=8.0,
               inflight_segments=2)
    with Pipeline(cfg) as pipe:
        stats = pipe.run()
        timer = pipe.stage_timer.summary()
    recs = TR.load(journal)
    assert len(recs) == stats.segments >= 3
    yield recs, timer
    metrics.reset()


def test_dispatch_children_are_journaled(served_run):
    recs, timer = served_run
    for rec in recs:
        ms = rec["stages_ms"]
        assert {"ingest", "dispatch", "h2d", "enqueue", "fetch",
                "sink"} <= set(ms)
        assert ms["h2d"] + ms["enqueue"] <= ms["dispatch"] + 1e-3
        assert ms["h2d"] >= 0 and ms["enqueue"] > 0
    for stage in ("h2d", "enqueue", "dispatch"):
        assert timer[stage]["count"] == len(recs)


def test_candidate_stages_only_where_a_segment_dumps(served_run):
    recs, _timer = served_run
    dumped = [r for r in recs if r["dump"]]
    quiet = [r for r in recs if not r["dump"]]
    assert dumped and quiet
    for rec in dumped:
        ms = rec["stages_ms"]
        assert {"d2h", "write", "publish"} <= set(ms)
        assert ms["d2h"] + ms["write"] + ms["publish"] <= ms["sink"] + 1e-3
        assert ms["write"] > 0
    for rec in quiet:
        assert not {"d2h", "write", "publish"} & set(rec["stages_ms"])


def test_segment_wall_leaves_children_out(served_run):
    recs, _timer = served_run
    rec = next(r for r in recs if r["dump"])
    ms = rec["stages_ms"]
    top = ms["ingest"] + ms["dispatch"] + ms["fetch"] + ms["sink"]
    assert telemetry.segment_wall(ms) == pytest.approx(top)
    # flat siblings (the DM search loop has no "dispatch") all count
    flat = {"ingest": 1.0, "h2d": 2.0, "enqueue": 3.0, "fetch": 4.0,
            "record": 5.0}
    assert telemetry.segment_wall(flat) == 15.0
    report = TR.stage_stats(recs)
    assert report["segment"]["max_ms"] <= max(
        telemetry.segment_wall(r["stages_ms"]) for r in recs) + 1e-6


def test_writer_pool_wait_is_the_candidates_write_time(tmp_path):
    """With the asynchronous writer pool the files land after push()
    returns; a later sink that drains (the checkpoint does, so does the
    benchmark's last sink) puts the wait on the same segment's record."""
    metrics.reset()
    journal = str(tmp_path / "journal.jsonl")
    cfg = _cfg(input_file_path=_baseband(tmp_path, 3, 0.05,
                                         pulse_at=N // 2),
               baseband_output_file_prefix=str(tmp_path / "out_"),
               telemetry_journal_path=journal, writer_thread_count=2,
               signal_detect_signal_noise_threshold=8.0)

    with Pipeline(cfg) as pipe:
        pipe.sinks.append(_Drainer(pipe))
        pipe.run()
        assert pipe.stage_timer.summary()["write"]["count"] >= 2
    recs = TR.load(journal)
    dumped = [r for r in recs if r["dump"]]
    assert dumped and all("write" in r["stages_ms"] for r in dumped)
    assert all("write" not in r["stages_ms"]
               for r in recs if not r["dump"])
    metrics.reset()


# ------------------------------------------------ (c) the DM search loop

@pytest.mark.parametrize("depth", [1, 2])
def test_dm_search_loop_spans_and_journal(tmp_path, depth):
    metrics.reset()
    journal = str(tmp_path / "grid.jsonl")
    segments = 3
    cfg = _cfg(
        inflight_segments=depth,
        baseband_reserve_sample=False, dm=30.0, use_emulated_fp64=True,
        dm_list=[0.0, 10.0, 20.0, 30.0, 40.0, 50.0, 60.0, 70.0],
        n_devices=4,
        input_file_path=_baseband(tmp_path, segments, 30.0,
                                  pulse_at=N // 2),
        baseband_output_file_prefix=str(tmp_path / "dm_"),
        signal_detect_signal_noise_threshold=7.0,
        telemetry_journal_path=journal)
    search = DMSearchPipeline(cfg)
    assert dict(search.mesh.shape) == {"dm": 4, "seq": 1}
    try:
        stats = search.run()
    finally:
        search.close()
    assert stats.segments == segments
    timer = search.stage_timer.summary()
    five = ("ingest", "h2d", "enqueue", "fetch", "record")
    assert set(timer) == set(five) | {"construct", "chirp_bank",
                                      "first_dispatch"}
    for stage in five:
        assert timer[stage]["count"] == segments, stage
    recs = TR.load(journal)
    assert [r["segment"] for r in recs] == list(range(segments))
    ids = [r["trace_id"] for r in recs]
    assert len(set(ids)) == segments and all(ids)
    for rec in recs:
        assert rec["type"] == "segment_span"
        assert rec["v"] == telemetry.SPAN_SCHEMA_VERSION
        assert set(rec["stages_ms"]) == set(five)
        assert rec["samples"] == N
        # v12's fields are the served path's: this loop counts by trial
        assert "streams" not in rec and "detections_by_stream" not in rec
    # the segment is replicated over the four dm-rows of the mesh; the
    # counter is read when a record is written, and with a step in
    # flight the next segment is uploaded before that: every record
    # but the last then counts one upload more than its own
    assert [r["h2d_bytes"] for r in recs] == [
        4 * N * min(k + depth, segments) for k in range(segments)]
    assert metrics.get("grid_steps_ahead") == (segments - 1) * (depth - 1)
    with open(search.trials_path) as f:
        trials = [json.loads(ln) for ln in f]
    assert len(trials) == segments and trials[0]["best_dm"] == 30.0
    metrics.reset()


def test_dm_search_loop_without_a_journal(tmp_path):
    cfg = _cfg(
        baseband_reserve_sample=False, dm=30.0,
        dm_list=[0.0, 30.0], n_devices=2,
        input_file_path=_baseband(tmp_path, 2, 30.0),
        baseband_output_file_prefix=str(tmp_path / "dm_"))
    search = DMSearchPipeline(cfg)
    assert search.journal is None
    assert search.run(max_segments=1).segments == 1
    assert search.stage_timer.summary()["record"]["count"] == 1
    search.close()


def test_grid_processor_takes_a_staged_segment():
    """``stage_input`` then ``process(staged)`` is ``process(host
    bytes)``: the loop opens its ``h2d`` and ``enqueue`` spans around
    the two, the processor knows nothing of spans; one upload is
    counted either way."""
    from srtb_tpu.parallel import mesh as M
    from srtb_tpu.parallel.segment_dist import DistSegmentProcessor

    cfg = _cfg(baseband_reserve_sample=False, dm=30.0)
    proc = DistSegmentProcessor(cfg, M.make_mesh(n_dm=2, n_seq=1),
                                [0.0, 30.0])
    raw = make_dispersed_baseband(N, 1405.0, 64.0, 30.0,
                                  pulse_positions=[N // 2], nbits=8)
    metrics.reset()
    whole = proc.process(raw)
    assert metrics.get("h2d_bytes") == 2 * N
    staged = proc.stage_input(raw)
    assert isinstance(staged, jax.Array)
    assert metrics.get("h2d_bytes") == 4 * N
    apart = proc.process(staged)
    assert metrics.get("h2d_bytes") == 4 * N
    np.testing.assert_array_equal(np.asarray(whole.snr_peaks),
                                  np.asarray(apart.snr_peaks))
    metrics.reset()


# ------------------------------------------------------ (d) the helper

def test_span_records_and_cancels():
    timer = StageTimer()
    with span("h2d", timer, trace_id=7) as sp:
        time.sleep(0.002)
    assert sp.seconds >= 0.002
    assert timer.last["h2d"] == sp.seconds and timer.counts["h2d"] == 1
    with span("ingest", timer) as sp:
        sp.cancel()
    assert "ingest" not in timer.counts and sp.seconds >= 0.0
    with span("write"):            # no timer: annotation only
        pass
    with pytest.raises(ValueError):
        with span("fetch", timer):
            raise ValueError("a failing stage is still timed")
    assert timer.counts["fetch"] == 1


def test_span_lands_on_the_profilers_host_plane(tmp_path):
    """Annotation and journal share the stage's name; the trace_id rides
    as an argument, not in the name the benchmark matches on."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("h2d", trace_id=41):
            time.sleep(0.001)
        with span("record"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    paths = sorted(tmp_path.rglob("*.xplane.pb"))
    assert paths
    names = {}
    for plane in ProfileData.from_file(str(paths[-1])).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("srtb:"):
                    names[ev.name] = dict(ev.stats)
    assert set(names) == {"srtb:h2d", "srtb:record"}
    assert int(names["srtb:h2d"]["trace_id"]) == 41


def test_span_is_cheap_outside_a_profiler_session():
    timer = StageTimer()
    with span("warm", timer, trace_id=1):
        pass
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(1000):
            with span("h2d", timer, trace_id=i + 1):
                pass
        best = min(best, (time.perf_counter() - t0) / 1000)
    assert best < 20e-6, f"{best * 1e6:.1f} us a span"


# ------------------------------------------- (e) the set-up path's spans

@pytest.fixture()
def logged(monkeypatch):
    """What the program's logger wrote, as one string when called."""
    import io

    from srtb_tpu.utils.logging import log

    stream = io.StringIO()
    monkeypatch.setattr(log, "stream", stream)
    return stream.getvalue


def _pulsed_cfg(tmp_path, segments=3, **extra):
    return _cfg(input_file_path=_baseband(tmp_path, segments, 0.05,
                                          pulse_at=N // 2),
                baseband_output_file_prefix=str(tmp_path / "out_"),
                telemetry_journal_path=str(tmp_path / "journal.jsonl"),
                signal_detect_signal_noise_threshold=8.0, **extra)


class _Drainer:
    """A last sink that waits for the candidate's files, as the
    checkpoint does and the benchmark's last sink."""

    def __init__(self, pipe):
        self.pipe = pipe

    def push(self, work, positive):
        if positive:
            self.pipe.sinks[0].drain()


WRITE_PATHS = {
    # how the files are written -> (options, the children of ``write``)
    "native_pool": (dict(writer_thread_count=2),
                    {"format", "submit", "drain"}),
    "python_pool": (dict(writer_thread_count=2),
                    {"format", "submit", "drain"}),
    "no_pool": (dict(writer_thread_count=0), {"format", "file"}),
    # the staged transaction: temps first, one barrier, then renames
    "no_pool_manifest": (dict(writer_thread_count=0), {"format", "file"}),
}


@pytest.mark.parametrize("how", sorted(WRITE_PATHS))
def test_the_candidates_write_by_child(tmp_path, monkeypatch, how):
    from srtb_tpu.io import native_writer

    options, children = WRITE_PATHS[how]
    if how == "native_pool" and not native_writer.native_available():
        pytest.skip("libsrtb_writer.so not built")
    if how == "python_pool":
        monkeypatch.setattr(native_writer, "_NATIVE", None)
    if how == "no_pool_manifest":
        options = dict(options,
                       run_manifest_path=str(tmp_path / "manifest.wal"))
    metrics.reset()
    cfg = _pulsed_cfg(tmp_path, **options)
    with Pipeline(cfg) as pipe:
        pool = pipe._owned_writer_pool
        assert (pool is not None) == (options["writer_thread_count"] > 0)
        if pool is not None:
            assert pool.is_native == (how == "native_pool")
            pipe.sinks.append(_Drainer(pipe))
        pipe.run()
        timer = pipe.stage_timer.summary()
        written = list(pipe.sinks[0].written)
        jobs = pool.stats()["jobs_done"] if pool is not None else None
    recs = TR.load(cfg.telemetry_journal_path)
    dumped = [r for r in recs if r["dump"]]
    quiet = [r for r in recs if not r["dump"]]
    assert len(dumped) == len(written) == 1 and quiet
    files = [written[0].bin_path, *written[0].npy_paths,
             *written[0].tim_paths]
    assert len(files) >= 3 and all(os.path.exists(p) for p in files)
    rec = dumped[0]
    ms = rec["stages_ms"]
    new = {"format", "submit", "drain", "file"}
    assert new & set(ms) == children, sorted(ms)
    assert all(ms[k] >= 0 for k in children) and ms["format"] > 0
    assert sum(ms[k] for k in children) <= ms["write"] + 1e-3
    assert telemetry.segment_wall(ms) == pytest.approx(
        ms["ingest"] + ms["dispatch"] + ms["fetch"] + ms["sink"])
    assert rec["candidate_bytes"] == sum(os.path.getsize(p)
                                         for p in files)
    assert rec["v"] == telemetry.SPAN_SCHEMA_VERSION == 13
    for r in quiet:
        assert not new & set(r["stages_ms"])
        assert "candidate_bytes" not in r and "writer_file_ms" not in r
    assert timer["format"]["count"] == len(files)
    if pool is None:
        # the sink's own thread writes: ``file`` is the fourth child
        assert "writer_file_ms" not in rec
        assert timer["file"]["count"] == len(files)
        assert "submit" not in timer and "drain" not in timer
    else:
        assert timer["submit"]["count"] == len(files) == jobs
        assert timer["drain"]["count"] == 1
        assert rec["writer_file_ms"] > 0
        if how == "python_pool":
            # its threads open the span, under the segment's trace id
            assert timer["file"]["count"] == len(files)
            assert rec["writer_file_ms"] == pytest.approx(
                timer["file"]["total_s"] * 1e3, abs=0.01)
        else:
            # the C++ threads open none: stats() gives their seconds
            assert "file" not in timer
    report = TR.report(cfg.telemetry_journal_path)["candidates"]
    assert report["records"] == 1
    assert report["bytes"] == rec["candidate_bytes"]
    metrics.reset()


def test_a_payload_over_the_pools_bound_is_written_by_the_sink(
        tmp_path, monkeypatch):
    """ISSUE 44: the pool bounds the bytes of queued copies (1 GiB); a
    payload over the whole bound (a 2^30-sample segment's 4.29 GB
    waterfall) would be held a second time for nothing, so the sink's
    thread writes it itself under ``file``, and the smaller files still
    go through the pool.  The record's ``writer_file_ms`` covers both."""
    from srtb_tpu.io import native_writer

    # the .bin (N bytes) fits the bound, the waterfall (4 N + header) not
    monkeypatch.setattr(native_writer.AsyncWriterPool,
                        "DEFAULT_MAX_QUEUED_BYTES", 2 * N)
    metrics.reset()
    cfg = _pulsed_cfg(tmp_path, writer_thread_count=2)
    with Pipeline(cfg) as pipe:
        pool = pipe._owned_writer_pool
        assert pool.max_queued_bytes == 2 * N
        pipe.sinks.append(_Drainer(pipe))
        pipe.run()
        written = list(pipe.sinks[0].written)
        jobs = pool.stats()["jobs_done"]
    files = [written[0].bin_path, *written[0].npy_paths,
             *written[0].tim_paths]
    assert len(written) == 1 and len(written[0].npy_paths) == 1
    assert os.path.getsize(written[0].npy_paths[0]) > 2 * N \
        > os.path.getsize(written[0].bin_path)
    assert jobs == len(files) - 1
    wf = np.load(written[0].npy_paths[0])
    assert wf.shape == (64, N // 2 // 64) and wf.dtype == np.complex64
    rec, = [r for r in TR.load(cfg.telemetry_journal_path) if r["dump"]]
    ms = rec["stages_ms"]
    assert {"format", "submit", "drain", "file"} <= set(ms)
    assert sum(ms[k] for k in ("format", "submit", "drain", "file")) \
        <= ms["write"] + 1e-3
    assert rec["writer_file_ms"] >= ms["file"] - 0.01
    assert rec["candidate_bytes"] == sum(os.path.getsize(p) for p in files)
    metrics.reset()


def test_report_tolerates_records_without_the_candidate_fields(tmp_path):
    """A v12 journal (no ``candidate_bytes``) has no candidates section,
    and a mixed one counts the v13 records only."""
    old = {"type": "segment_span", "v": 12, "segment": 0, "ts": 1.0,
           "dump": True, "samples": N,
           "stages_ms": {"ingest": 1.0, "sink": 5.0, "write": 4.0}}
    new = dict(old, v=13, segment=1, candidate_bytes=1000,
               writer_file_ms=2.5,
               stages_ms=dict(old["stages_ms"], format=1.0, submit=1.0,
                              drain=1.5))
    assert TR.candidate_stats([old]) == {}
    got = TR.candidate_stats([old, new])
    assert got["records"] == 1 and got["bytes"] == 1000
    assert got["writer_file_s"] == 0.003 and got["drain_s"] == 0.002
    path = tmp_path / "mixed.jsonl"
    path.write_text(json.dumps(old) + "\n" + json.dumps(new) + "\n")
    assert "## Candidates" in TR._md(TR.report(str(path)))
    assert TR.stage_stats([old, new])["segment"]["max_ms"] == 6.0


def test_construct_holds_the_chirp_bank_once(tmp_path):
    metrics.reset()
    with Pipeline(_pulsed_cfg(tmp_path)) as pipe:
        assert pipe.processor.chirp is not None
        assert pipe.processor.stage_timer is pipe.stage_timer
        before = pipe.stage_timer.summary()
        pipe.run()
        after = pipe.stage_timer.summary()
    assert set(before) == {"construct", "chirp_bank"}
    for timer in (before, after):
        assert timer["construct"]["count"] == 1
        assert timer["chirp_bank"]["count"] == 1
        assert 0 < timer["chirp_bank"]["total_s"] \
            <= timer["construct"]["total_s"]
    # the same family feeds the registry: one reducer reads both loops
    for stage in ("construct", "chirp_bank"):
        h = metrics.histogram("stage_seconds", labels={"stage": stage})
        assert h.count == 1
        assert h.sum == pytest.approx(after[stage]["total_s"], abs=1e-6)
    metrics.reset()


def test_a_plan_that_makes_its_chirp_in_the_step_has_no_bank(
        tmp_path, monkeypatch, logged):
    """The tiny staged plan (what a 2^30 segment resolves to) and the
    grid's in-step arm: ``construct`` and no ``chirp_bank``."""
    from srtb_tpu.parallel import segment_dist
    from srtb_tpu.pipeline import segment

    monkeypatch.setattr(segment, "STAGED_MIN_N", N)
    with Pipeline(_pulsed_cfg(tmp_path)) as pipe:
        assert pipe.processor.staged and pipe.processor.chirp is None
        pipe.run(max_segments=1)
        timer = pipe.stage_timer.summary()
    assert timer["construct"]["count"] == 1 and "chirp_bank" not in timer
    assert "no chirp_bank: the plan makes its chirp in the step" \
        in logged()
    monkeypatch.setattr(segment_dist, "_device_bytes_limit",
                        lambda mesh: 1 << 10)
    cfg = _cfg(baseband_reserve_sample=False, dm=30.0,
               use_emulated_fp64=True, dm_list=[0.0, 30.0], n_devices=2,
               input_file_path=_baseband(tmp_path, 2, 30.0),
               baseband_output_file_prefix=str(tmp_path / "dm_"))
    search = DMSearchPipeline(cfg)
    assert metrics.get("chirp_bank_bytes") == 0
    search.run(max_segments=1)
    search.close()
    timer = search.stage_timer.summary()
    assert timer["construct"]["count"] == 1 and "chirp_bank" not in timer
    assert set(search.processor.first_dispatch_s) == {"grid_step"}
    metrics.reset()


def _by_program(name: str) -> dict:
    return {d["program"]: v for d, v in metrics.labeled_series(name)
            if "program" in d}


def test_first_dispatches_by_program_add_up_served(tmp_path, logged):
    metrics.reset()
    cfg = _pulsed_cfg(tmp_path)
    with Pipeline(cfg) as pipe:
        pipe.run()
        first = dict(pipe.processor.first_dispatch_s)
        timer = pipe.stage_timer.summary()
    by_program = _by_program("compile_seconds")
    assert list(first) == ["ring_cold", "ring"] and by_program == first
    total = metrics.get("compile_seconds")
    assert total > 0 and sum(by_program.values()) == pytest.approx(total)
    assert _by_program("plan_compiles") == {"ring_cold": 1, "ring": 1}
    assert metrics.get("plan_compiles") == 2
    # the span goes to the timer, the journal keeps its cumulative field
    assert timer["first_dispatch"]["count"] == 2
    assert timer["first_dispatch"]["total_s"] == pytest.approx(
        total, abs=1e-5)
    recs = TR.load(cfg.telemetry_journal_path)
    assert all("first_dispatch" not in r["stages_ms"] for r in recs)
    assert recs[-1]["compile_ms"] == pytest.approx(total * 1e3, abs=0.06)
    line = [ln for ln in logged().splitlines()
            if "[setup] construct" in ln]
    assert len(line) == 1 and "(chirp_bank " in line[0]
    said = {k: float(v) for k, v in re.findall(
        r"(\w+) ([0-9.]+) s(?:,|$)", line[0].split("dispatches: ")[1])}
    assert list(said) == ["ring_cold", "ring"]
    assert sum(said.values()) == pytest.approx(total, abs=0.011)
    metrics.reset()


def test_the_staged_ring_names_its_plan_dispatches_and_carry(
        tmp_path, logged, monkeypatch):
    """ISSUE 44: every record of the staged ring says the plan, the
    ``[setup]`` line names its two first dispatches, and the carry the
    device kept reads the reserve's bytes on a warm dispatch and nothing
    on the cold one."""
    from srtb_tpu.pipeline import segment

    monkeypatch.setattr(segment, "STAGED_MIN_N", N)
    monkeypatch.setattr(segment, "FUSED_TAIL_DF64_MAX_SPECTRUM", N >> 4)
    metrics.reset()
    cfg = _pulsed_cfg(tmp_path, segments=4)
    with Pipeline(cfg) as pipe:
        proc = pipe.processor
        assert proc.ring_row_bytes == 2 * 64
        pipe.run()
        first = dict(proc.first_dispatch_s)
    assert list(first) == ["staged_ring_cold", "staged_ring"]
    assert _by_program("compile_seconds") == first
    recs = TR.load(cfg.telemetry_journal_path)
    assert len(recs) >= 4
    assert {r["active_plan"] for r in recs} == {"staged:monolithic+rows+ring"}
    # the counters are the process's, read when a record is written:
    # by the last record every dispatch is counted
    warm = len(recs) - 1
    assert recs[-1]["ring_cold_dispatches"] == 1
    assert recs[-1]["ring_carry_bytes"] == warm * proc.reserved_bytes
    assert recs[-1]["h2d_bytes"] == N + warm * proc.stride_bytes
    line = [ln for ln in logged().splitlines() if "[setup] construct" in ln]
    assert len(line) == 1 and "no chirp_bank" in line[0]
    said = re.findall(r"(\w+) [0-9.]+ s(?:,|$)",
                      line[0].split("dispatches: ")[1])
    assert said == ["staged_ring_cold", "staged_ring"]
    metrics.reset()


def test_first_dispatches_by_program_add_up_grid(tmp_path, logged):
    metrics.reset()
    cfg = _cfg(baseband_reserve_sample=False, dm=30.0,
               use_emulated_fp64=True, dm_list=[0.0, 10.0, 20.0, 30.0],
               n_devices=4, input_file_path=_baseband(tmp_path, 3, 30.0),
               baseband_output_file_prefix=str(tmp_path / "dm_"))
    search = DMSearchPipeline(cfg)
    assert _by_program("compile_seconds").keys() == {"grid_bank"}
    search.run()
    search.run()        # a second run pays and says nothing more
    search.close()
    by_program = _by_program("compile_seconds")
    assert by_program == search.processor.first_dispatch_s
    assert list(by_program) == ["grid_bank", "grid_step"]
    total = metrics.get("compile_seconds")
    assert sum(by_program.values()) == pytest.approx(total)
    assert metrics.get("plan_compiles") == 2
    timer = search.stage_timer.summary()
    assert timer["first_dispatch"]["count"] == 2
    # the bank's program is compiled AND run inside its spans
    assert by_program["grid_bank"] <= timer["chirp_bank"]["total_s"] \
        <= timer["construct"]["total_s"]
    lines = [ln for ln in logged().splitlines()
             if "[setup] construct" in ln]
    assert len(lines) == 1 and "grid_bank" in lines[0] \
        and "grid_step" in lines[0]
    metrics.reset()


@pytest.fixture()
def span_openings(monkeypatch):
    """{span name: times opened} by whoever opens one, on any thread."""
    opened = collections.Counter()
    enter = span.__enter__

    def counting(self):
        opened[self.name] += 1
        return enter(self)

    monkeypatch.setattr(span, "__enter__", counting)
    return opened


# outside any segment: construction, the bank, the two ring programs'
# first dispatches, and the read that finds the source at its end
SETUP_SPANS = {"construct": 1, "chirp_bank": 1, "first_dispatch": 2,
               "ingest": 1}


def test_a_quiet_served_segment_opens_the_parents_six_spans(
        tmp_path, span_openings):
    """THE GUARD ON THE HOT PATH: a quiet segment of the served path
    opens ingest, dispatch, h2d, enqueue, fetch and sink, once each, as
    it did before the set-up path got its spans.  A PR that puts a span
    into the window changes this number and has to say so."""
    cfg = _cfg(input_file_path=_baseband(tmp_path, 5, 0.05),
               baseband_output_file_prefix=str(tmp_path / "out_"),
               writer_thread_count=2, inflight_segments=2)
    with Pipeline(cfg) as pipe:
        assert pipe._owned_writer_pool is not None
        stats = pipe.run()
    # five strides and the overlap they leave: warm ring steps
    segments = stats.segments
    assert segments >= 5 and stats.signals == 0
    assert not glob.glob(str(tmp_path / "out_*"))
    per_segment = span_openings - collections.Counter(SETUP_SPANS)
    assert per_segment == {stage: segments for stage in (
        "ingest", "dispatch", "h2d", "enqueue", "fetch", "sink")}


def test_a_grid_segment_opens_its_five_spans(tmp_path, span_openings):
    segments = 4
    cfg = _cfg(baseband_reserve_sample=False, dm=30.0,
               use_emulated_fp64=True, dm_list=[0.0, 30.0], n_devices=2,
               input_file_path=_baseband(tmp_path, segments, 30.0),
               baseband_output_file_prefix=str(tmp_path / "dm_"))
    search = DMSearchPipeline(cfg)
    assert search.run().segments == segments
    search.close()
    per_segment = span_openings - collections.Counter(SETUP_SPANS)
    assert per_segment == {stage: segments for stage in (
        "ingest", "h2d", "enqueue", "fetch", "record")}
