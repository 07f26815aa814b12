"""The metrics PR 42 added, all of which move ``setup_s``: each is held
to its list of cells by a traced run of the tiny relatives on the CPU.
Present and above zero where ``BENCHMARK.json`` lists the cell it stands
for, absent elsewhere, and absent too where a cell is listed but the
program has nothing to say (``plan.chirp_bank_s`` on the staged plan,
which makes its chirp in the step and opens no ``chirp_bank``).

    runtime.construct_s         all five cells
    plan.chirp_bank_s           the four that hold a bank
    plan.grid_first_dispatch_s  the grid
    io.candidate_format_s / _submit_s / _drain_s / _file_s, io.candidate_mb
                                the four served cells

The registry these read is the process's, so every case empties it first
(``reducers/setup.py``).  The grid case wants four devices: ``pytest
benchmark/selftest`` has them (conftest.py), tier-1 runs it in a child.
"""

import json
import os
import shutil

import pytest
from test_2p30_cell import TINY_CELL, TINY_STAGED, staged_at_2p16  # noqa: F401
from test_run import run_cell
from test_scopes import tiny_relatives, tiny_with_new_entries  # noqa: F401

from benchmark import spec as spec_mod
from benchmark.reducers import setup

CANDIDATE = {"io.candidate_format_s", "io.candidate_submit_s",
             "io.candidate_drain_s", "io.candidate_file_s",
             "io.candidate_mb"}
NEW = CANDIDATE | {"runtime.construct_s", "plan.chirp_bank_s",
                   "plan.grid_first_dispatch_s"}
SERVED = ["j1644_2p27.replay_quiet", "naoc_1g_dm14.replay_quiet",
          "j1644_2pol_2p27.replay_quiet", "j1644_2p30.replay_quiet"]
GRID = "j1644_dmgrid8.replay"
STAGED = "j1644_2p30.replay_quiet"


def listed(cell: str) -> set:
    """The new metrics ``BENCHMARK.json`` lists for ``cell``."""
    return {m["name"] for m, _r in spec_mod.Spec(
        spec_mod.HERE, cell).metrics("per_layer")} & NEW


@pytest.fixture(autouse=True)
def empty_registry():
    from srtb_tpu.utils.metrics import metrics

    metrics.reset()
    yield
    metrics.reset()


def test_every_new_metric_is_listed_where_the_issue_lists_it():
    for cell in SERVED:
        want = CANDIDATE | {"runtime.construct_s"}
        if cell != STAGED:
            want = want | {"plan.chirp_bank_s"}
        assert listed(cell) == want, cell
    assert listed(GRID) == {"runtime.construct_s", "plan.chirp_bank_s",
                            "plan.grid_first_dispatch_s"}
    with open(os.path.join(spec_mod.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    # appended, in one block, behind everything the benchmark had
    assert set(names[-len(NEW):]) == NEW
    for m in bench["per_layer"][-len(NEW):]:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["layer"] in ("runtime", "plan", "input/output")
        with open(os.path.join(spec_mod.HERE, "layer_metrics",
                               f"{m['name']}.json")) as f:
            reader = json.load(f)
        assert reader["layer"] == m["layer"] and len(reader["what"]) > 40
    # every served tiny relative stands for a served cell, the grid's for
    # the grid: the runs below cover every list
    assert set(tiny_relatives()) | {STAGED} == set(SERVED) | {GRID}


def values(out: dict) -> dict:
    return {k: v["value"] for k, v in out["metrics"].items() if k in NEW}


@pytest.mark.parametrize("tiny_cell", ["tiny_j1644.replay_quiet",
                                       "tiny_8bit.replay_quiet",
                                       "tiny_2pol.replay_quiet"])
def test_a_served_cell_reports_construction_and_the_writes_children(
        capsys, tiny_with_new_entries, tiny_cell):
    rc, out, lines = run_cell(capsys, workload=tiny_cell,
                              root=tiny_with_new_entries, trace=1)
    assert rc == 0 and out["correct"] and out["failed"] == 0
    m = values(out)
    cell = {v: k for k, v in tiny_relatives().items()}[tiny_cell]
    assert set(m) == listed(cell)
    assert all(v > 0 for v in m.values()), m
    assert m["plan.chirp_bank_s"] <= m["runtime.construct_s"]
    # the children lie inside ``write``, which the accepted metric reads
    # together with ``publish``
    write = out["metrics"]["io.candidate_write_s"]["value"]
    children = sum(m[f"io.candidate_{k}_s"]
                   for k in ("format", "submit", "drain"))
    assert 0 < children <= write + 1e-6
    # what the harness's own stopwatch around the constructor says
    said = [ln for ln in lines if "Pipeline constructed in" in ln]
    assert len(said) == 1
    assert m["runtime.construct_s"] <= float(
        said[0].split("constructed in ")[1].split(" s")[0]) + 0.01


@pytest.fixture()
def staged_root_listing_the_bank(tmp_path):
    """A copy of ``selftest/tiny_staged`` whose ``BENCHMARK.json`` also
    lists the new metrics of the cell it stands for, and
    ``plan.chirp_bank_s`` on top: what the reader says of a plan that has
    no bank."""
    root = str(tmp_path / "tiny_staged")
    shutil.copytree(TINY_STAGED, root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    assert not NEW & {m["name"] for m in bench["per_layer"]}
    with open(os.path.join(spec_mod.CHECKOUT, "BENCHMARK.json")) as f:
        full = json.load(f)
    for m in full["per_layer"]:
        if m["name"] in listed(STAGED) | {"plan.chirp_bank_s"}:
            bench["per_layer"].append(dict(m, workloads=[TINY_CELL]))
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_the_staged_cell_has_no_chirp_bank_to_report(
        capsys, staged_at_2p16, staged_root_listing_the_bank):
    rc, out, lines = run_cell(capsys, workload=TINY_CELL,
                              root=staged_root_listing_the_bank, trace=1)
    assert rc == 0 and out["correct"] and out["failed"] == 0
    assert any("plan: staged:monolithic+rows" in ln for ln in lines)
    m = values(out)
    assert set(m) == listed(STAGED)
    assert "plan.chirp_bank_s" not in m
    assert all(v > 0 for v in m.values()), m


def test_the_grid_reports_construction_bank_and_first_dispatches(
        capsys, tiny_with_new_entries):
    rc, out, _lines = run_cell(capsys, workload="tiny_dmgrid8.replay",
                               root=tiny_with_new_entries, trace=1)
    assert rc == 0 and out["correct"] and out["device"]["count"] == 4
    m = values(out)
    assert set(m) == listed(GRID)
    assert all(v > 0 for v in m.values()), m
    assert m["plan.chirp_bank_s"] <= m["runtime.construct_s"]
    # the bank program is compiled and run inside ``chirp_bank``; the
    # step's first dispatch comes on top
    assert m["plan.grid_first_dispatch_s"] > m["plan.chirp_bank_s"] * 0.5


def test_a_program_without_the_spans_gives_the_readers_nothing():
    """The parent commit, whose registry has no such series and whose
    records no such field: nothing is returned, nothing raised."""

    class Rec:
        warm_spans = [{"dump": True, "stages_ms": {"write": 5.0}}]

    assert setup.stage_total_s(Rec, {"stage": "construct"}) is None
    assert setup.first_dispatch_by_program_s(
        Rec, {"programs": ["grid_bank", "grid_step"]}) is None
    assert setup.warmup_dump_field(
        Rec, {"field": "candidate_bytes", "scale": 1e-6}) is None
    Rec.warm_spans = [{"dump": False, "candidate_bytes": 5},
                      {"dump": True, "candidate_bytes": 2_000_000}]
    assert setup.warmup_dump_field(
        Rec, {"field": "candidate_bytes", "scale": 1e-6}) == 2.0
