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


def test_sound_run_is_correct_and_cpu_prints_no_device_metric(capsys):
    rc, out, _ = run_cell(capsys, trace=1)
    assert rc == 0 and out["correct"] and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    for name in out["metrics"]:
        assert not name.startswith(("ops.", "kernels.", "device.",
                                    "multichip.")), name


def test_grid_cell_on_four_virtual_devices(capsys):
    rc, out, _ = run_cell(capsys, workload="tiny_dmgrid8.replay")
    assert rc == 0 and out["correct"] and out["device"]["count"] == 4


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
    if threshold == 2.0:
        assert out["failed"] > 0
