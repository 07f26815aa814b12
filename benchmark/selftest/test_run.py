"""``run.py`` end to end on the CPU at a tiny size: it refuses to run
without a TPU, it runs with ``--allow-cpu`` and prints no device metric,
and ``correct`` comes out false when the timed path is broken underneath
or the schedule is missed."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny")
RUN = os.path.join(ROOT, "benchmark", "run.py")


def run_cell(capsys, workload="tiny_j1644.replay_quiet", root=TINY,
             trace=0, seed=11):
    from benchmark import run
    from srtb_tpu.utils import logging as program_logging

    # the program's logger binds sys.stderr at import: hand it this
    # test's stream, not the closed one of the test that imported it
    program_logging.log.stream = sys.stderr
    rc = run.main(["--root", root, "--workload", workload, "--seed",
                   str(seed), "--seconds", "1", "--trace", str(trace),
                   "--allow-cpu"])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]), lines


def test_without_a_tpu_it_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, RUN, "--root", TINY, "--workload",
         "tiny_j1644.replay_quiet", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert r.returncode != 0
    assert "{" not in r.stdout


@pytest.mark.parametrize("workload", ["tiny_j1644.replay_quiet",
                                      "tiny_8bit.replay_quiet",
                                      "tiny_2pol.replay_quiet"])
def test_sound_run_is_correct_and_cpu_prints_no_device_metric(capsys,
                                                              workload):
    rc, out, lines = run_cell(capsys, workload=workload, trace=1)
    assert rc == 0 and out["correct"] and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    for name in out["metrics"]:
        assert not name.startswith(("ops.", "kernels.", "device.",
                                    "multichip.")), name
    # what was compared, beside its limit, is the line's last key
    assert list(out)[-1] == "checks" and out["checks"]["failed"] == []
    for key in ("series_gap", "snr_gap", "bin_gap"):
        value, limit = out["checks"][key]
        assert 0 <= value <= limit
    # a file of two streams: each stream is compared with its own reference
    assert any(".p1 = " in ln for ln in lines) == ("2pol" in workload)


def test_grid_cell_on_four_virtual_devices(capsys):
    rc, out, lines = run_cell(capsys, workload="tiny_dmgrid8.replay")
    assert rc == 0 and out["correct"] and out["device"]["count"] == 4
    assert set(out["checks"]) == {"snr_gap", "snr_gap_outer", "failed"}
    # every segment is stamped at its record's arrival, and in this
    # synchronous loop that is the next pull to within the thread's wait
    # for the interpreter lock
    stamps = [ln for ln in lines if "completion stamps:" in ln]
    assert len(stamps) == 1
    largest = float(stamps[0].rsplit("largest magnitude", 1)[1])
    assert largest < 50.0


def test_a_record_is_stamped_when_it_arrives_not_at_the_next_pull(tmp_path):
    """A loop that pulled segment k+1 before k's record exists: k is
    stamped at its record."""
    import collections
    import time

    from benchmark.drivers.dmgrid import RecordDrain
    from benchmark.record import SegRec

    handed = collections.deque(
        SegRec(index=i, phase="window", file_seg=i, pulsed=False,
               new_samples=1) for i in range(2))
    first, second = handed
    fifo = str(tmp_path / "out_dm_trials.jsonl")
    real = str(tmp_path / "out_dm_trials.records.jsonl")
    drain = RecordDrain(fifo, real, handed)
    try:
        for rec in (first, second):         # two run() calls of the loop
            with open(fifo, "a") as f:      # as the program opens it
                pulled_next = time.perf_counter()
                time.sleep(0.05)
                f.write('{"segment": 0}\n')
                f.flush()
                written = time.perf_counter()
                drain.wait_for(drain.lines + 1)
            assert pulled_next + 0.04 < rec.done <= written + 0.02
    finally:
        drain.close()
    assert not handed and first.done < second.done
    with open(real) as f:
        assert f.read() == '{"segment": 0}\n' * 2


def test_answer_altered_where_it_is_produced(capsys, monkeypatch):
    from srtb_tpu.pipeline.runtime import Pipeline

    sound = Pipeline._fetch_device

    def broken(self, item, index=0):
        seg, wf, det, off, span = sound(self, item, index)
        det = det._replace(time_series=det.time_series * 1.01)
        return seg, wf, det, off, span

    monkeypatch.setattr(Pipeline, "_fetch_device", broken)
    rc, out, lines = run_cell(capsys)
    assert rc == 0 and out["correct"] is False
    assert any("series_gap" in ln and "FAIL" in ln for ln in lines)
    # the failing number stands beside its limit on the result line
    value, limit = out["checks"]["series_gap"]
    assert value > limit == 0.01
    assert out["checks"]["bin_gap"] == [0.0, 0.0]


def test_outer_shards_returned_in_another_order(capsys, monkeypatch):
    """The grid's outer trials (the first and the last chip's shards)
    come back swapped: the curve still peaks at the injected DM, and
    only the comparison of EVERY trial with the reference sees it."""
    from srtb_tpu.parallel.segment_dist import DistSegmentProcessor

    sound = DistSegmentProcessor.process

    def broken(self, data):
        res = sound(self, data)
        p = res.snr_peaks
        order = [7, 6, 2, 3, 4, 5, 1, 0]
        return res._replace(
            snr_peaks=p.reshape(8, -1)[order, :].reshape(p.shape))

    monkeypatch.setattr(DistSegmentProcessor, "process", broken)
    rc, out, lines = run_cell(capsys, workload="tiny_dmgrid8.replay")
    assert rc == 0 and out["correct"] is False
    assert any("snr_gap_outer" in ln and "FAIL" in ln for ln in lines)
    assert not any("snr_gap." in ln and "FAIL" in ln for ln in lines)
    assert out["checks"]["snr_gap_outer"][0] > out["checks"]["snr_gap_outer"][1]
    assert out["checks"]["snr_gap"][0] <= out["checks"]["snr_gap"][1]


def _tiny_copy(tmp_path, **options):
    """The tiny cells with some of the configuration's options changed."""
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        path = os.path.join(root, "configs", os.path.basename(c["file"]))
        with open(path) as f:
            cfg = json.load(f)
        cfg["options"].update(options)
        with open(path, "w") as f:
            json.dump(cfg, f)
        c["file"] = path
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("threshold, what", [
    (1e9, "holds a pulse, none detected"),
    (2.0, "holds no pulse but fired"),
])
def test_missed_pulse_and_false_alarm(capsys, tmp_path, threshold, what):
    root = _tiny_copy(tmp_path,
                      signal_detect_signal_noise_threshold=threshold)
    rc, out, lines = run_cell(capsys, root=root)
    assert rc == 0 and out["correct"] is False
    assert any(what in ln for ln in lines)
    assert any(what in text for text in out["checks"]["failed"])
    if threshold == 2.0:
        assert out["failed"] > 0
