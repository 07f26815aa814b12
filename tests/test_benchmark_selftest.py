"""The benchmark's own selftests (``benchmark/selftest/test_*.py``), run
where the driver counts: a PR that renames a span, a counter or a journal
key the benchmark's reducers read fails here, on the CPU, and not on the
chip as ``output_malformed``.  The cases live with the benchmark; this
file imports them under their own names and adds one guard of its own.

The selftest directory goes on ``sys.path`` and its modules are imported
by their top-level names, as ``pytest benchmark/selftest`` imports them:
``test_naoc_cell``, ``test_2pol_cell``, ``test_2p30_cell`` and
``test_crab_cell`` (PR 44) do ``import test_scopes`` (its hand-built profile messages; since PR 39 a tiny cell
names the cell it stands for in its own file and none registers in that
module any more), so all must see one module object.  The two
grid cases want four devices where ``tests/conftest.py`` forces eight;
each runs in a child with the selftests' own ``XLA_FLAGS``.

Three things are repaired here and not in the selftests, which this
file may not edit: ``test_run.run_cell`` hands the program's logger the
test's own ``sys.stderr``, which pytest closes with the test, so the
stream is put back after every case; the record-drain case reads
``drain.lines`` after its write, see below; and the grid cell's case
holds every record's arrival to within 50 ms of the loop's next pull,
which was the serial loop's identity: since PR 41 the pull of segment
k+1 comes a step BEFORE k's record by design (3 ms of this CPU alone,
10-70 ms beside five busy workers), so this file's own
``test_grid_cell_on_four_virtual_devices`` runs the same cell the same
way and asserts what the selftest asserts but for that line, until a
``benchmark`` PR rewords it.  And three cases hold a cell's per-layer
metrics against another cell's (``METRIC_SET_CASES``; the third against
its tiny relative's, whose ``BENCHMARK.json`` a PR that changes the
program may not edit): they hold as written over the metrics they were
written for, and this file says what PR 42's eight add to each cell;
PR 42's own case holds its eight to the end of ``per_layer`` and reads
the file without the ninth that PR 44 appended behind them.  PR 45
appended two more (``PR45``: the served loop's reader, listed for the five
served cells): every one of those cases, and ``test_crab_cell``'s two that
hold PR 44's metric to the end of the list and its cell's set to its tiny
relative's, read the file without them (``_before_pr45``); what the two
read is held by ``tests/test_pipeline.py`` and by this file's
``test_the_slice_is_found_with_the_source_ticked_on_the_reader_thread``.
"""

import itertools
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SELFTEST = os.path.join(ROOT, "benchmark", "selftest")
sys.path[:0] = [ROOT, SELFTEST]

import test_2p30_cell  # noqa: E402
import test_2pol_cell  # noqa: E402
import test_crab_cell  # noqa: E402
import test_gen  # noqa: E402
import test_naoc_cell  # noqa: E402
import test_reference  # noqa: E402
import test_run  # noqa: E402
import test_scopes  # noqa: E402
import test_setup_spans  # noqa: E402
import test_trace  # noqa: E402
from test_2p30_cell import staged_at_2p16  # noqa: E402,F401  (fixture)
from test_reference import raw  # noqa: E402,F401  (fixture)
from test_scopes import tiny_with_new_entries  # noqa: E402,F401  (fixture)
from test_setup_spans import (  # noqa: E402,F401  (fixtures)
    empty_registry, staged_root_listing_the_bank)

# run in a child on four virtual devices
FOUR_DEVICES = ("test_grid_rehearsal_reports_its_five_stages",
                "test_the_grid_reports_construction_bank_and_first_dispatches")

# this file's own spellings of two selftest cases (the docstring)
DRAIN_CASE = "test_a_record_is_stamped_when_it_arrives_not_at_the_next_pull"
GRID_CELL_CASE = "test_grid_cell_on_four_virtual_devices"

LISTED_CASE = "test_every_new_metric_is_listed_where_the_issue_lists_it"

# the cases that pin one cell's set of per-layer metrics to another's
METRIC_SET_CASES = {
    "test_the_two_stream_cells_files_load_through_spec": test_2pol_cell,
    "test_the_2p30_cells_files_load_through_spec": test_2p30_cell,
    "test_the_tiny_relative_stands_for_the_cell": test_2p30_cell,
}
# PR 44's two that hold its metric to the end of ``per_layer`` and its
# cell's set to the tiny relative's
CRAB_SET_CASES = ("test_the_crab_cells_files_load_through_spec",
                  "test_the_tiny_relative_stands_for_the_crab_cell")
# what PR 45 appended to ``per_layer``
PR45 = ("runtime.ingest_wait_ms", "io.ingest_ahead_per_seg")

for _mod in (test_gen, test_reference, test_trace, test_scopes,
             test_naoc_cell, test_2pol_cell, test_2p30_cell, test_crab_cell,
             test_run, test_setup_spans):
    for _name, _obj in vars(_mod).items():
        if _name.startswith("test_") and callable(_obj) \
                and _name not in FOUR_DEVICES + (DRAIN_CASE, LISTED_CASE,
                                                 GRID_CELL_CASE) \
                and _name not in METRIC_SET_CASES \
                and _name not in CRAB_SET_CASES:
            assert _name not in globals(), _name
            globals()[_name] = _obj


@pytest.fixture(autouse=True)
def _logger_stream_put_back():
    from srtb_tpu.utils.logging import log
    stream = log.stream
    yield
    log.stream = stream


def test_a_record_is_stamped_when_it_arrives_not_at_the_next_pull(
        tmp_path, monkeypatch):
    # the case waits for ``drain.lines + 1`` records and reads
    # ``drain.lines`` after its write: where the drain's thread has
    # stamped that record already, it waits for one that never comes
    # (seen under six xdist workers).  Its k-th wait is for k records.
    from benchmark.drivers.dmgrid import RecordDrain

    wait_for, calls = RecordDrain.wait_for, itertools.count(1)
    monkeypatch.setattr(
        RecordDrain, "wait_for",
        lambda self, lines, timeout=10.0: wait_for(self, next(calls),
                                                   timeout))
    getattr(test_run, DRAIN_CASE)(tmp_path)


@pytest.mark.parametrize("case", sorted(METRIC_SET_CASES))
def test_a_cells_metrics_against_another_cells(case, monkeypatch):
    """The selftest's own case over the 28 metrics it was written for
    (it counts 21 for the two-stream cell, and for the 2^30 cell the
    flagship's less the ring's two plus the three programs; the tiny
    staged root lists what the 2^30 cell listed then), then what PR 42
    appended: the two-stream cell gets what the one-stream cell
    gets, the 2^30 cell everything the flagship gets but
    ``plan.chirp_bank_s``, since its plan holds no bank.  PR 44's cell
    and its one metric are left out the same way (``test_crab_cell``
    holds that cell's own set)."""
    from benchmark import spec as spec_mod

    new = test_setup_spans.NEW
    metrics = spec_mod.Spec.metrics
    init = spec_mod.Spec.__init__

    def before_pr44(self, root, workload):
        # PR 44's cell joined ``ops.stage_b_`` / ``_c_ms_per_seg``, which
        # the 2^30 case holds to its own cell, and brought one metric
        init(self, root, workload)
        self.bench = _before_pr44(self.bench)

    with monkeypatch.context() as patch:
        patch.setattr(spec_mod.Spec, "__init__", before_pr44)
        patch.setattr(
            spec_mod.Spec, "metrics",
            lambda self, kind: [(m, r) for m, r in metrics(self, kind)
                                if m["name"] not in new])
        getattr(METRIC_SET_CASES[case], case)()
    flagship, two_pol, big = (test_setup_spans.listed(cell) for cell in (
        "j1644_2p27.replay_quiet", "j1644_2pol_2p27.replay_quiet",
        "j1644_2p30.replay_quiet"))
    assert len(flagship) == 7 and two_pol == flagship
    assert big == flagship - {"plan.chirp_bank_s"}


def _before_pr45(bench: dict) -> dict:
    """A ``BENCHMARK.json`` without the two metrics PR 45 appended."""
    if "per_layer" not in bench:
        return bench
    return dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] not in PR45])


@pytest.mark.parametrize("case", CRAB_SET_CASES)
def test_the_crab_cells_sets_as_pr44_wrote_them(case, monkeypatch):
    from benchmark import spec as spec_mod

    init = spec_mod.Spec.__init__

    def before_pr45(self, root, workload):
        init(self, root, workload)
        self.bench = _before_pr45(self.bench)

    monkeypatch.setattr(spec_mod.Spec, "__init__", before_pr45)
    getattr(test_crab_cell, case)()
    # ... and what PR 45 appended stands at the end, for the served cells
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert tuple(m["name"] for m in bench["per_layer"][-2:]) == PR45
    served = [w["name"] for w in bench["workloads"] if w["chips"] == 1]
    for m in bench["per_layer"][-2:]:
        assert sorted(m["workloads"]) == sorted(served)
        assert m["moves"] == "rt_factor"


def _before_pr44(bench: dict) -> dict:
    """A ``BENCHMARK.json`` without what PR 44 appended to ``per_layer``:
    its one metric, and its cell's name at the end of the lists it
    joined (and without PR 45's two behind it)."""
    bench = _before_pr45(bench)
    if "per_layer" not in bench:
        return bench
    return dict(bench, per_layer=[
        dict(m, workloads=[w for w in m["workloads"]
                           if w != test_crab_cell.CELL])
        if "workloads" in m else m
        for m in bench["per_layer"] if m["name"] != test_crab_cell.NEW])


def test_every_new_metric_is_listed_where_the_issue_lists_it(monkeypatch):
    """PR 42's case holds its eight metrics to the END of ``per_layer``,
    where PR 44 appended a ninth behind them: it reads the file as PR 42
    left it (``test_crab_cell`` holds the ninth to the end)."""
    class Json:
        dumps, loads = json.dumps, json.loads

        @staticmethod
        def load(f):
            return _before_pr44(json.load(f))

    monkeypatch.setattr(test_setup_spans, "json", Json)
    getattr(test_setup_spans, LISTED_CASE)()


def _four_device_env() -> dict:
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=4")


def test_grid_cell_on_four_virtual_devices():
    """``test_run.test_grid_cell_on_four_virtual_devices`` with its last
    assertion brought to the loop as it is (the module's docstring): the
    window's ``run()`` enqueues every step but its first ahead of the
    fetch before, and says so on its closing line."""
    r = subprocess.run(
        [sys.executable, test_run.RUN, "--root", test_run.TINY,
         "--workload", "tiny_dmgrid8.replay", "--seed", "11", "--seconds",
         "1", "--trace", "0", "--allow-cpu"],
        cwd=ROOT, env=_four_device_env(), capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] and out["device"]["count"] == 4
    assert set(out["checks"]) == {"snr_gap", "snr_gap_outer", "failed"}
    # every segment is stamped at its record's arrival, one line says
    # how far from the pull that followed its hand-over
    stamps = [ln for ln in lines if "completion stamps:" in ln]
    assert len(stamps) == 1
    float(stamps[0].rsplit("largest magnitude", 1)[1])   # it parses
    closing = [ln.split("[dm_search] ", 1)[1].split(";")[0]
               for ln in r.stderr.splitlines() if "grid_steps_ahead" in ln]
    # the two warm-up segments, then the window
    assert closing[0] == ("2 segments, 2 of them in this run at window "
                          "2: grid_steps_ahead 1")
    n = out["attempted"]
    assert n > 2 and closing[1:] == [
        f"{n + 2} segments, {n} of them in this run at window 2: "
        f"grid_steps_ahead {n - 1}"]


@pytest.mark.parametrize("case", FOUR_DEVICES)
def test_on_four_virtual_devices(case):
    # the whole directory is collected (test_naoc_cell registers its
    # cell at import), one case of it runs
    env = _four_device_env()
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", SELFTEST, "-k", case],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "1 passed" in r.stdout, \
        r.stdout[-4000:] + r.stderr[-2000:]


# what ``benchmark/reducers/journal*.py`` and ``drivers/served.py`` read
# from a ``segment_span``, beside the stages and counters that
# ``benchmark/layer_metrics/*.json`` name
SPAN_KEYS = ("type", "stages_ms", "device_ms", "compile_ms", "h2d_bytes",
             "ring_carry_bytes", "ring_cold_dispatches", "dump")
SERVED_STAGES = ("ingest", "dispatch", "fetch", "sink")


def test_every_span_key_the_benchmark_reads_is_journalled(capsys):
    """A pin, not a repeat of ``test_run.py``: it fails only where a key
    is missing from the journal of the tiny served cell."""
    cell = "tiny_j1644.replay_quiet"
    work = os.path.join(ROOT, ".bench_work", cell)
    try:
        from benchmark import run
        from srtb_tpu.utils import logging as program_logging

        # as test_run.run_cell: the logger bound another test's stream
        program_logging.log.stream = sys.stderr
        assert run.main(["--root", test_run.TINY, "--workload", cell,
                         "--seed", "11", "--seconds", "1", "--trace", "0",
                         "--allow-cpu", "--keep-work"]) == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["correct"] and out["failed"] == 0
        with open(os.path.join(work, "journal.jsonl")) as f:
            spans = [json.loads(ln) for ln in f]
    finally:
        shutil.rmtree(work, ignore_errors=True)
    spans = [s for s in spans if s.get("type") == "segment_span"]
    assert spans
    counters, stages = set(), set(SERVED_STAGES)
    metrics_dir = os.path.join(ROOT, "benchmark", "layer_metrics")
    for name in sorted(os.listdir(metrics_dir)):
        with open(os.path.join(metrics_dir, name)) as f:
            m = json.load(f)
        if m["reducer"].startswith("journal_"):
            args = m.get("args", {})
            if "counter" in args:
                counters.add(args["counter"])
            if "stage" in args:
                stages.add(args["stage"])
            stages.update(args.get("stages", ()))
    assert {"h2d_bytes", "ring_carry_bytes"} <= counters
    for s in spans:
        missing = (set(SPAN_KEYS) | counters) - set(s)
        assert not missing, (missing, s)
        assert s["v"] == 13 and "plan_compiles" in s
        # the second yardstick's fields do not grow back
        assert not {"roofline_frac", "achieved_msamps"} & set(s), s
    journalled = set().union(*(s["stages_ms"] for s in spans))
    assert stages <= journalled, stages - journalled


def test_the_slice_is_found_with_the_source_ticked_on_the_reader_thread(
        capsys, tmp_path, monkeypatch):
    """With the served loop's reader ahead, the benchmark's source is
    pulled, and its tracer ticked (``start_trace``, the ``bench:slice``
    annotation), on the reader's thread.  The tiny relative of the Crab
    cell, traced, with the rule held on (a tiny pull is microseconds): the
    slice is found, the spans are read, and PR 45's two metrics read the
    reader: every segment of the window taken from it, the loop's wait
    journalled."""
    from benchmark import run
    from srtb_tpu.pipeline import runtime
    from srtb_tpu.utils import logging as program_logging

    root = str(tmp_path / "tiny_staged_ring")
    shutil.copytree(test_crab_cell.TINY_ROOT, root)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        new = [m for m in json.load(f)["per_layer"] if m["name"] in PR45]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] += [dict(m, workloads=[test_crab_cell.TINY_CELL])
                           for m in new]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    pulled_on = set()
    timed_ingest = runtime.Pipeline._timed_ingest

    def spying(self, it, index=0):
        import threading
        pulled_on.add(threading.current_thread().name)
        return timed_ingest(self, it, index)

    monkeypatch.setattr(runtime.Pipeline, "_timed_ingest", spying)
    monkeypatch.setattr(runtime, "_PULL_AHEAD_SHARE", 0.0)
    program_logging.log.stream = sys.stderr
    work = os.path.join(ROOT, ".bench_work", test_crab_cell.TINY_CELL)
    try:
        rc = run.main(["--root", root, "--workload",
                       test_crab_cell.TINY_CELL, "--seed", "2147496019",
                       "--seconds", "1", "--trace", "1", "--allow-cpu"])
    finally:
        shutil.rmtree(work, ignore_errors=True)
    lines = capsys.readouterr().out.strip().splitlines()
    out = json.loads(lines[-1])
    assert rc == 0 and out["correct"] and out["failed"] == 0
    assert pulled_on == {"MainThread", "reader"}
    assert any("trace: slice of" in ln for ln in lines)
    assert out["device"]["window_s"] > 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["io.ingest_ahead_per_seg"] == 1
    assert 0 <= m["runtime.ingest_wait_ms"] < 50
    assert {"io.ingest_ms", "runtime.fetch_ms", "io.h2d_ms_per_seg",
            "runtime.enqueue_ms_per_seg"} <= set(m)
    # the program's spans are read beside the slice (the CPU's profile
    # holds no device plane: the one gap goes to the longest of them)
    assert out["breakdown"]["idle_gaps"][0][0].startswith("srtb:")
