"""The cell PR 36 added, ``j1644_2pol_2p27.replay_quiet``: its files load
through ``spec.py`` as ``run.py`` loads them, its configuration is
``j1644_2p27``'s with one option changed (the two polarisations stay
byte-interleaved in the file and are split on the device), its layout's
bytes are the deployment's (two streams a segment: a 64 MiB cold upload,
a 55.4 MB stride, an 11.7 MB carry), the per-layer metrics it joins read
it (``ops.unpack_ms_per_seg`` is where the split is read), and the byte
count behind ``kernels.hbm_share`` is twice the one-stream cell's.

Compiling the configuration for a described v5e is rehearsal 3 of
README.md: ``python benchmark/selftest/aot_compile.py
j1644_2pol_2p27.replay_quiet``; the CPU rehearsal of a two-stream run is
``run.py --root benchmark/selftest/tiny --workload tiny_2pol.replay_quiet
--allow-cpu`` (``test_run.py``).
"""

import json
import os

import control_streams
import test_scopes

from benchmark import counts, gen, spec as spec_mod
from benchmark.reference import chain

CELL = "j1644_2pol_2p27.replay_quiet"
ONE_STREAM = "j1644_2p27.replay_quiet"

# ``test_scopes.py`` maps every cell named in a ``workloads`` list of the
# repo's BENCHMARK.json to its tiny copy, and names them in code.  This
# PR may add files only, so the new cell's name is given its tiny
# relative from here, as ``test_naoc_cell.py`` does (the same module
# object pytest collected; run ``pytest benchmark/selftest``, not that
# file alone).
test_scopes.CELLS.setdefault(CELL, "tiny_2pol.replay_quiet")


def test_the_two_stream_cells_files_load_through_spec():
    sp = spec_mod.Spec(spec_mod.HERE, CELL)
    assert sp.chips == 1 and sp.workload["driver"] == "served"
    assert set(sp.config["reduced"]) == {
        "baseband_input_count", "baseband_reserve_sample", "gui_enable"}
    assert "baseband_format_type" in sp.config["assumed"]
    assert sp.config["guarantees"]
    entry = next(c for c in sp.bench["configs"]
                 if c["name"] == "j1644_2pol_2p27")
    assert entry["reduced"] == sorted(sp.config["reduced"])
    assert all(len(entry[k]) <= 200 for k in ("source", "why"))
    assert len(sp.cell["why"]) <= 200
    assert set(sp.workload["check"]["limits"]) == {"series_gap", "snr_gap",
                                                   "bin_gap"}
    assert sp.workload["check"]["limits"]["bin_gap"] == 0
    assert sp.workload["source"] == {"kind": "file_replay",
                                     "file_segments": 32}
    assert sp.workload["pulses"]["every"] == 0
    # the traced slice is the one-stream cell's (ISSUE 36 names it)
    assert sp.workload["trace"] == spec_mod.Spec(
        spec_mod.HERE, ONE_STREAM).workload["trace"] == {"slice_s": 3.0}
    per_layer = {m["name"] for m, _r in sp.metrics("per_layer")}
    # the split is read where the field unpack is; the ring carries two
    # streams' bytes
    assert {"ops.unpack_ms_per_seg", "ops.ring_ms_per_seg",
            "io.ring_carry_mb_per_seg", "io.h2d_mb_per_seg",
            "kernels.hbm_share", "ops.busy_ms_per_seg",
            "device.idle_share", "device.peak_hbm_gb"} <= per_layer
    assert not any("grid" in n or n.startswith("multichip.")
                   for n in per_layer)
    # every metric that reads the one-stream cell reads this one
    one = {m["name"] for m, _r in spec_mod.Spec(
        spec_mod.HERE, ONE_STREAM).metrics("per_layer")}
    assert per_layer == one and len(per_layer) == 21
    assert {m["name"] for m, _r in sp.metrics("end_to_end")} \
        == {"rt_factor", "setup_s"}


def test_the_configuration_is_the_one_stream_one_with_one_option_changed():
    two = spec_mod.Spec(spec_mod.HERE, CELL).config
    one = spec_mod.Spec(spec_mod.HERE, ONE_STREAM).config
    assert list(two["options"]) == list(one["options"])
    changed = {k for k in one["options"]
               if one["options"][k] != two["options"][k]}
    assert changed == {"baseband_format_type"}
    assert two["options"]["baseband_format_type"] == "interleaved_samples_2"
    assert one["options"]["baseband_format_type"] == "simple"


def test_the_layout_is_two_streams_of_the_deployment():
    sp = spec_mod.Spec(spec_mod.HERE, CELL)
    p = chain.params_from_config(sp.config["options"])
    assert (p["n"], p["channels"], p["bits"], p["streams"]) \
        == (1 << 27, 1 << 11, 2, 2)
    for seed in (7, 2147496017, 2 ** 31 + 12345):
        lay = gen.Layout(p, sp.workload, seed)
        # samples are per stream, bytes are of both
        assert lay.reserved == 23494656 and lay.stride == 110723072
        assert lay.reserved / lay.n < 3 / 11
        assert lay.segment_bytes == 1 << 26          # a cold upload
        assert lay.stride_bytes == 55361536          # H2D a warm stride
        assert lay.bytes_of(lay.reserved) == 11747328    # the carry
        assert lay.n_warmup == 3 and lay.n_replay == 32
        assert lay.pulsed == [True] + [False] * 34
        # the pulse peaks inside the 21296 of 32768 time samples searched
        assert 0 < lay.expected_bin(0) < 32768 - lay.reserved // (1 << 11)
        sampled = lay.draw_sample(sp.workload["check"]["sample"], seed)
        assert len(sampled) == 1 and not lay.pulsed[sampled[0]]
    # twice the sweep fits the 2^25 template
    assert 2 * abs(chain.max_delay_time(p["freq_low"], p["bandwidth"],
                                        p["dm"])) * p["sample_rate"] \
        < 1 << 25


def test_the_byte_count_is_twice_the_one_stream_cells():
    two = chain.params_from_config(
        spec_mod.Spec(spec_mod.HERE, CELL).config["options"])
    one = chain.params_from_config(
        spec_mod.Spec(spec_mod.HERE, ONE_STREAM).config["options"])
    assert counts.segment_bytes_per_chip(two) \
        == 2 * counts.segment_bytes_per_chip(one) == 2 * 2717908992


def test_the_controls_are_read_on_each_stream(capsys):
    """``control_streams.py`` on the tiny two-stream cell: each stream's
    readings are its own (the streams are independent noise), both go
    through the cell's limits, and ``bf16`` fails on each.  At 2^27 on
    the chip both controls fail on both streams (PERF.md section 6)."""
    tiny = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
    rc = control_streams.main(["--root", tiny, "--workload",
                               "tiny_2pol.replay_quiet", "--seeds", "1"])
    said = capsys.readouterr().out.splitlines()
    last = json.loads(said[-1])
    assert set(last["control"]) == {"stream0", "stream1"}
    assert last["limits"] == spec_mod.Spec(
        tiny, "tiny_2pol.replay_quiet").workload["check"]["limits"]
    for per in last["control"].values():
        assert set(per) == {"chirp_f32", "bf16"}
        assert per["bf16"]["correct"] is False
        assert per["bf16"]["checks"]["series_gap"][0] \
            > per["bf16"]["checks"]["series_gap"][1]
    a, b = (last["control"][s]["bf16"]["checks"]["series_gap"][0]
            for s in ("stream0", "stream1"))
    assert a != b
    assert any(line.startswith("[stream 1] [control] seed")
               for line in said)
    # the tiny cell's DM is too small for ``chirp_f32`` to fail: the
    # exit code says that not every control failed on every stream
    assert rc == int(any(v["correct"] for per in last["control"].values()
                         for v in per.values()))
