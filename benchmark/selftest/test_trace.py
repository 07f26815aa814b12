"""The trace reductions give known numbers: on intervals made by hand,
and on a small recorded trace of the served cell on one v5e
(``data/quiet_slice.xplane.pb.gz``: a 0.4 s slice of
``j1644_2p27.replay_quiet``; ``data/quiet_slice.json`` holds what was
read from it when it was recorded, and the host-clock length of the
slice)."""

import gzip
import json
import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps_by_hand():
    busy, gaps = trace.union_seconds([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0),
                                      (3.2, 3.4), (6.0, 6.5)])
    assert busy == pytest.approx(3.5)
    assert gaps == [(2.0, 3.0), (4.0, 6.0)]


def test_reductions_by_hand():
    tr = trace.Trace(
        devices={0: [("convolution_add_fusion.1 f32[8]", 0.0, 1.0),
                     ("fusion.4 f32[8]", 1.0, 1.0),
                     ("all-reduce.2 f32[2]", 3.0, 0.5)],
                 1: [("convolution_add_fusion.1 f32[8]", 0.0, 2.0)]},
        host=[("srtb:ingest", 1.9, 0.9), ("srtb:fetch", 2.8, 0.1)],
        window_s=4.0)
    assert tr.busy_s() == pytest.approx((2.5 + 2.0) / 2)
    assert tr.op_seconds("(?i)fft|^convolution") == pytest.approx(1.5)
    assert tr.op_seconds("^all-reduce") == pytest.approx(0.25)
    assert tr.top_ops(1) == [["convolution_add_fusion.1 f32[8]", 1.5]]
    # device 0 idles from 2.0 to 3.0: 0.8 s of it under srtb:ingest
    assert tr.idle_gaps() == [["srtb:ingest", pytest.approx(1.0)]]


def test_short_name():
    name = ("%fusion.43 = (f32[128,64]{1,0:T(8,128)}, f32[2]{0}) "
            "fusion(f32[128,64]{0,1} %a), kind=kOutput")
    assert trace.short_name(name) == "fusion.43 f32[128,64]"


def test_recorded_trace(tmp_path):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "quiet_slice.json")) as f:
        want = json.load(f)
    pb = tmp_path / "quiet_slice.xplane.pb"
    with gzip.open(os.path.join(DATA, "quiet_slice.xplane.pb.gz")) as f:
        pb.write_bytes(f.read())
    tr = trace.Trace.from_profile(ProfileData.from_file(str(pb)),
                                  want["window_s"])
    assert sorted(tr.devices) == [0]
    assert len(tr.devices[0]) == want["device_ops"]
    assert tr.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    assert tr.op_seconds("(?i)fft|^convolution") == pytest.approx(
        want["fft_s"], rel=1e-9)
    assert tr.top_ops(1)[0][0] == want["top_op"]
    assert tr.idle_gaps(1)[0][0] == want["top_gap"]
    assert 0.0 < tr.busy_s() < tr.window_s
