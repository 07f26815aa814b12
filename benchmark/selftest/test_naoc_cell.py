"""The cell PR 31 added, ``naoc_1g_dm14.replay_quiet``: its files load
through ``spec.py`` as ``run.py`` loads them, its layout is the
deployment's (24.4 % of every 2^28 segment overlapped, the warm-up pulse
inside the searched part of the series), its two per-layer metrics read
nothing (and raise nothing) from a program that has neither the
``srtb.ring`` scope nor the ``ring_carry_bytes`` counter, which is what
the parent commit gives, and read a trace and a journal that have them.

Compiling the configuration for a described v5e is rehearsal 3 of
README.md: ``python benchmark/selftest/aot_compile.py
naoc_1g_dm14.replay_quiet``.
"""

import json
import os

import pytest
import test_scopes
from test_scopes import US, known, load_trace, plane, unzip, write_space

from benchmark import gen, spec as spec_mod
from benchmark.reducers import journal, scopes
from benchmark.reference import chain

ROOT = spec_mod.CHECKOUT
CELL = "naoc_1g_dm14.replay_quiet"

# ``test_scopes.py`` maps every cell named in a ``workloads`` list of the
# repo's BENCHMARK.json to its tiny copy, and names them in code.  This
# PR may add files only, so the new cell's name is given its tiny
# relative from here (the same module object pytest collected; run
# ``pytest benchmark/selftest``, not that file alone).
test_scopes.CELLS.setdefault(CELL, "tiny_8bit.replay_quiet")


def reader(metric: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           f"{metric}.json")) as f:
        return json.load(f)


def test_the_cells_files_load_through_spec():
    sp = spec_mod.Spec(spec_mod.HERE, CELL)
    assert sp.chips == 1 and sp.workload["driver"] == "served"
    assert set(sp.config["reduced"]) == {"gui_enable"}
    assert "dm" in sp.config["assumed"] and sp.config["guarantees"]
    entry = next(c for c in sp.bench["configs"]
                 if c["name"] == "naoc_1g_dm14")
    assert entry["reduced"] == ["gui_enable"]
    assert all(len(entry[k]) <= 200 for k in ("source", "why"))
    assert len(sp.cell["why"]) <= 200
    assert set(sp.workload["check"]["limits"]) == {"series_gap", "snr_gap",
                                                   "bin_gap"}
    assert sp.workload["check"]["limits"]["bin_gap"] == 0
    per_layer = {m["name"] for m, _r in sp.metrics("per_layer")}
    assert {"ops.ring_ms_per_seg", "io.ring_carry_mb_per_seg",
            "kernels.hbm_share", "ops.busy_ms_per_seg",
            "device.idle_share", "device.peak_hbm_gb"} <= per_layer
    # no operation carries srtb.unpack at 8 bits; the grid's are the grid's
    assert "ops.unpack_ms_per_seg" not in per_layer
    assert not any("grid" in n or n.startswith("multichip.")
                   for n in per_layer)
    assert {m["name"] for m, _r in sp.metrics("end_to_end")} \
        == {"rt_factor", "setup_s"}


def test_the_layout_is_the_deployments():
    sp = spec_mod.Spec(spec_mod.HERE, CELL)
    p = chain.params_from_config(sp.config["options"])
    assert (p["n"], p["channels"], p["bits"], p["streams"]) \
        == (1 << 28, 1 << 15, 8, 1)
    for seed in (7, 2147496017, 2 ** 31 + 12345):
        lay = gen.Layout(p, sp.workload, seed)
        assert lay.reserved == 65470464 and lay.stride == 202964992
        assert lay.reserved / lay.n < 3 / 11
        assert lay.n_warmup == 3 and lay.n_replay == 8
        assert lay.pulsed == [True] + [False] * 10
        # the pulse peaks inside the 2098 of 4096 time samples searched
        assert 0 < lay.expected_bin(0) < 4096 - lay.reserved // (1 << 15)
        assert len(lay.draw_sample(sp.workload["check"]["sample"],
                                   seed)) == 1
    # twice the sweep fits the 2^26 template
    assert 2 * chain.max_delay_time(p["freq_low"], p["bandwidth"],
                                    p["dm"]) * p["sample_rate"] < 1 << 26


class Rec:
    def __init__(self, trace=None, spans=(), warm_spans=()):
        self.trace, self.spans = trace, list(spans)
        self.warm_spans = list(warm_spans)


def test_the_carry_counter_is_read_where_the_journal_has_it():
    r = reader("io.ring_carry_mb_per_seg")
    fn = journal.REDUCERS[r["reducer"]]
    mb = 65470464
    warm = [{"h2d_bytes": 268435456, "ring_carry_bytes": 0}]
    spans = [{"h2d_bytes": 268435456 + k * 202964992,
              "ring_carry_bytes": k * mb} for k in (1, 2, 3, 4)]
    assert fn(Rec(spans=spans, warm_spans=warm), r["args"]) \
        == pytest.approx(65.470464)
    # the parent's journal has no such counter: nothing, no error
    old = [{"h2d_bytes": s["h2d_bytes"]} for s in spans]
    assert fn(Rec(spans=old, warm_spans=old[:1]), r["args"]) is None
    assert fn(Rec(), r["args"]) is None


def test_the_ring_scope_is_read_where_the_trace_has_it(tmp_path,
                                                       monkeypatch):
    r = reader("ops.ring_ms_per_seg")
    fn = scopes.REDUCERS[r["reducer"]]
    # PR 27's slice: scopes, but no srtb.ring (the parent): nothing read
    info = known("scoped_slice")
    path = unzip(tmp_path, "scoped_slice")
    rec = Rec(load_trace(path, info))
    monkeypatch.setattr(scopes, "slice_path", lambda: path)
    monkeypatch.setattr(scopes, "_CACHE", {})
    assert fn(rec, r["args"]) is None
    assert fn(Rec(None), r["args"]) is None
    # a hand-built plane whose slice carries the name
    ops = {1: ("%slice.7 = u8[65470464] slice(...)",
               "jit(_process_ring)/jit(main)/srtb.ring/slice:"),
           2: ("%fusion.1 = f32[8] fusion(...)",
               "jit(_process_ring)/jit(main)/srtb.fft_r2c/mul:"),
           3: ("%copy.1 = f32[8] copy(...)", None)}
    built = write_space(tmp_path, plane("/device:TPU:0", ops, [
        (1, 0, 3 * US), (2, 3 * US, 20 * US), (3, 30 * US, 7 * US)]))
    monkeypatch.setattr(scopes, "slice_path", lambda: built)
    monkeypatch.setattr(scopes, "_CACHE", {})
    rec.trace.segments = 2
    assert fn(rec, r["args"]) == pytest.approx(3e-3 / 2)
    unscoped = reader("ops.unscoped_ms_per_seg")
    assert fn(rec, unscoped["args"]) == pytest.approx(7e-3 / 2)
