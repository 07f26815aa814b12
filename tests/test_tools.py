"""Tools + GUI service tests: correlator (vs numpy oracle), waterfall PNG
service (test-gui analog: synthetic spectra into the real renderer,
ref: src/test-gui.cpp), main CLI smoke test, filterbank header."""

import os
import struct
import zlib

import numpy as np

from srtb_tpu.config import Config
from srtb_tpu.io.writers import encode_angle_dms, write_filterbank_header
from srtb_tpu.tools.correlator import correlate
from srtb_tpu.gui.waterfall import WaterfallService, write_png


def test_correlator_peak_at_lag():
    """Cross-correlating a shifted copy peaks at the shift
    (ref math: correlator.cpp:109-140)."""
    rng = np.random.default_rng(0)
    n = 1 << 12
    lag = 37
    # zero-mean signed samples; with unsigned offset-binary data the DC bin
    # adds a constant baseline at every lag (same behavior as the reference,
    # which applies no mean removal either)
    base = rng.integers(-50, 50, size=n + lag).astype(np.int8)
    x1 = base[:n]
    x2 = base[lag:lag + n]
    corr = correlate(x1, x2)
    assert corr.shape == (n // 2,)
    # the correlation is computed on the half-spectrum (analytic signal),
    # as in the reference: n/2 output points span n samples, so the peak
    # appears at lag/2 with 2-sample resolution
    assert abs(int(np.argmax(corr)) - lag // 2) <= 1


def test_waterfall_service_png(tmp_path):
    cfg = Config(gui_pixmap_width=64, gui_pixmap_height=48)
    svc = WaterfallService(cfg, in_freq=128, in_time=256,
                           out_dir=str(tmp_path))
    rng = np.random.default_rng(1)
    wf_ri = rng.standard_normal((2, 128, 256)).astype(np.float32)
    svc.push(wf_ri, data_stream_id=0)
    svc.push(wf_ri * 2, data_stream_id=0)  # lossy: replaces frame 1
    path = svc.render_pending()
    assert path is not None and os.path.exists(path)
    assert svc.render_pending() is None  # nothing pending

    with open(path, "rb") as f:
        data = f.read()
    assert data[:8] == b"\x89PNG\r\n\x1a\n"
    w, h = struct.unpack(">II", data[16:24])
    assert (w, h) == (64, 48)
    # decode and spot-check a pixel is valid RGBA
    idat = data[data.index(b"IDAT") + 4:data.index(b"IEND") - 4]
    raw = zlib.decompress(idat)
    assert len(raw) == 48 * (64 * 4 + 1)


def test_write_png_roundtrip(tmp_path):
    argb = np.full((4, 5), 0xFF112233, dtype=np.uint32)
    p = str(tmp_path / "t.png")
    write_png(p, argb)
    with open(p, "rb") as f:
        data = f.read()
    raw = zlib.decompress(data[data.index(b"IDAT") + 4:
                               data.index(b"IEND") - 4])
    row0 = raw[1:21]
    assert row0[:4] == bytes([0x11, 0x22, 0x33, 0xFF])  # RGBA order


def test_filterbank_header(tmp_path):
    p = str(tmp_path / "fb.fil")
    with open(p, "wb") as f:
        write_filterbank_header(f, fch1=1469.0, foff=-0.03125, nchans=2048,
                                tsamp=3.2e-5, source_name="J1644-4559",
                                src_raj=encode_angle_dms(16, 44, 49.3),
                                src_dej=encode_angle_dms(-45, 59, 9.5))
    data = open(p, "rb").read()
    assert data.startswith(struct.pack("<i", 12) + b"HEADER_START")
    assert b"HEADER_END" in data
    assert b"source_name" in data
    # decode fch1
    i = data.index(b"fch1") + 4
    assert struct.unpack("<d", data[i:i + 8])[0] == 1469.0


def test_encode_angle_dms():
    assert encode_angle_dms(16, 44, 49.3) == 164449.3
    assert encode_angle_dms(-45, 59, 9.5) == -455909.5


def test_main_cli_on_file(tmp_path):
    """Smoke-test the main tool end to end on a small synthetic file."""
    from srtb_tpu.tools.main import main
    rng = np.random.default_rng(0)
    n = 1 << 14
    raw = rng.integers(0, 256, size=n, dtype=np.uint8)
    in_path = str(tmp_path / "in.bin")
    raw.tofile(in_path)
    rc = main([
        "--input_file_path", in_path,
        "--baseband_input_count", str(n),
        "--baseband_input_bits", "8",
        "--spectrum_channel_count", "2**6",
        "--signal_detect_max_boxcar_length", "16",
        "--baseband_output_file_prefix", str(tmp_path / "out_"),
        "--baseband_reserve_sample", "0",
        "--gui_enable", "1",
        "--gui_pixmap_width", "32",
        "--gui_pixmap_height", "24",
    ])
    assert rc == 0
    pngs = [f for f in os.listdir(tmp_path) if f.endswith(".png")]
    assert pngs, "gui_enable must produce waterfall PNGs"


def test_waterfall_spectrum_sum_count(tmp_path):
    """spectrum_sum_count: sum N segments' power before drawing
    (ref: config.hpp:196-200)."""
    cfg = Config(gui_pixmap_width=32, gui_pixmap_height=16,
                 spectrum_sum_count=3)
    svc = WaterfallService(cfg, in_freq=64, in_time=64,
                           out_dir=str(tmp_path))
    rng = np.random.default_rng(2)
    wf = rng.standard_normal((2, 64, 64)).astype(np.float32)
    svc.push(wf); assert svc.render_pending() is None
    svc.push(wf); assert svc.render_pending() is None
    svc.push(wf)
    path = svc.render_pending()
    assert path is not None and os.path.exists(path)


def test_waterfall_http_server(tmp_path):
    """Live viewer: index page lists the latest frame per stream and serves
    the PNG bytes."""
    import urllib.request
    from srtb_tpu.gui.server import WaterfallHTTPServer

    cfg = Config(gui_pixmap_width=16, gui_pixmap_height=8)
    svc = WaterfallService(cfg, in_freq=32, in_time=32,
                           out_dir=str(tmp_path))
    svc.push(np.random.default_rng(0)
             .standard_normal((2, 32, 32)).astype(np.float32))
    svc.render_pending()

    srv = WaterfallHTTPServer(str(tmp_path)).start()
    try:
        idx = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/").read().decode()
        assert "waterfall_s0_000000.png" in idx
        png = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/waterfall_s0_000000.png").read()
        assert png[:8] == b"\x89PNG\r\n\x1a\n"
    finally:
        srv.stop()


def test_scrolling_waterfall_and_scheduler():
    """Legacy scrolling provider analog: lines scroll through a persistent
    image; the 3n+1 scheduler grows while a backlog remains and halves
    once caught up (ref: gui/spectrum_image_provider.hpp:79-102)."""
    from srtb_tpu.gui.waterfall import RequestSizeScheduler, ScrollingWaterfall

    s = RequestSizeScheduler()
    assert s.get_next_request_size() == 1
    s.set_last_size_too_few(True)
    assert s.get_next_request_size() == 4      # 3*1+1
    s.set_last_size_too_few(True)
    assert s.get_next_request_size() == 13     # 3*4+1
    s.set_last_size_too_few(False)
    assert s.get_next_request_size() == 6
    for _ in range(5):
        s.set_last_size_too_few(False)
    assert s.get_next_request_size() == 1      # floor at 1

    in_freq, w, h = 64, 32, 16
    sw = ScrollingWaterfall(in_freq, width=w, height=h)
    rng = np.random.default_rng(0)
    for i in range(40):
        spec = np.zeros(in_freq, dtype=np.float32)
        spec[:] = 0.1
        spec[i % in_freq] = float(i + 1)       # marker per line
        sw.push_spectrum(spec)
    consumed = 0
    rounds = 0
    while consumed < 40 and rounds < 50:
        consumed += sw.consume()
        rounds += 1
    assert consumed == 40 and sw.lines_total == 40
    # newest line sits at the TOP of the scroll window (reference scrolls
    # down, painting new lines at y=0)
    assert abs(sw._img[0].max() - 40.1) < 1e-3
    pix = sw.render()
    assert pix.shape == (h, w) and pix.dtype == np.uint32
    # catching up took adaptive batches: fewer rounds than lines
    assert rounds < 40
    # partially-filled window must not paint data as overflow color
    from srtb_tpu.ops.spectrum import COLOR_OVERFLOW
    sw2 = ScrollingWaterfall(in_freq, width=w, height=h)
    sw2.push_spectrum(np.full(in_freq, 0.5, dtype=np.float32))
    sw2.consume()
    pix2 = sw2.render()
    assert not (pix2[0] == np.uint32(COLOR_OVERFLOW)).any()


def test_main_cli_scrolling_gui(tmp_path):
    """gui_scroll_lines selects the legacy scrolling provider through the
    real CLI and produces a scroll image."""
    from srtb_tpu.tools.main import main
    rng = np.random.default_rng(0)
    n = 1 << 14
    rng.integers(0, 256, size=2 * n, dtype=np.uint8).tofile(
        str(tmp_path / "in.bin"))
    rc = main([
        "--input_file_path", str(tmp_path / "in.bin"),
        "--baseband_input_count", str(n),
        "--baseband_input_bits", "8",
        "--spectrum_channel_count", "2**6",
        "--signal_detect_max_boxcar_length", "16",
        "--baseband_output_file_prefix", str(tmp_path / "out_"),
        "--baseband_reserve_sample", "0",
        "--gui_enable", "1",
        "--gui_scroll_lines", "4",
        "--gui_pixmap_width", "32",
        "--gui_pixmap_height", "24",
    ])
    assert rc == 0
    assert os.path.exists(str(tmp_path / "waterfall_s0_scroll.png"))


def test_test_gui_tool(tmp_path):
    """The test-gui analog (ref: src/test-gui.cpp): synthetic spectra
    through both real waterfall providers, PNGs on disk."""
    from srtb_tpu.tools.test_gui import main

    out = str(tmp_path / "gui")
    rc = main(["--out", out, "--frames", "2", "--streams", "1",
               "--freq", "64", "--time", "128", "--scroll-lines", "4"])
    assert rc == 0
    names = sorted(p.name for p in (tmp_path / "gui").iterdir())
    assert "waterfall_s0_000000.png" in names
    assert "waterfall_s0_scroll.png" in names


def test_e2e_live_harness_smoke(tmp_path):
    """The live UDP->device->candidates harness must run end to end on
    loopback: paced sender, segment assembly, threaded pipeline, live
    /metrics over HTTP, one JSON artifact line."""
    import json

    from srtb_tpu.tools import e2e_live

    out = tmp_path / "e2e.jsonl"
    rc = e2e_live.main([
        "--seconds", "1.5", "--rate_x", "0.05", "--log2n", "18",
        "--log2chan", "7", "--port", "42157", "--deadline_s", "60",
        "--gui", "--gui_min_interval_s", "0.2",
        "--prefix", str(tmp_path) + "/out_", "--out", str(out)])
    assert rc == 0
    rec = json.loads(out.read_text().splitlines()[-1])
    assert rec["segments"] >= 1
    assert rec["packets_total"] > 0
    assert rec["metrics_http"]["segments"] == rec["segments"]
    # the HTTP server must list the tap's rendered frames (regression:
    # serving the prefix instead of its directory kept /frames.json
    # empty forever)
    assert rec["gui_frames"] >= 1
    assert rec["gui_frames_served"] >= 1
    # both throughput denominators present and labeled (VERDICT r4 #5)
    assert rec["msamples_per_s_window"] > 0
    assert rec["lifetime_seconds"] >= rec["seconds"]
    # deadline armed for real above (60 s >> per-segment time): reaching
    # the artifact line at all is the no-hit evidence
    assert rec["deadline_s"] == 60


def test_e2e_live_overload_degrades_gracefully(tmp_path):
    """Overload mode (VERDICT r4 #5): offer wire-rate load far above the
    CPU compute rate and require the reference's never-stall-on-loss
    property (ref: io/udp/udp_receiver.hpp:129-164): the pipeline keeps
    draining segments, excess packets fall off the kernel socket buffer
    and surface as *accounted* counter-gap loss, and the run terminates
    cleanly instead of stalling or crashing."""
    import json

    from srtb_tpu.tools import e2e_live

    # The overload is statistical: the OS scheduler occasionally
    # starves the paced sender so thoroughly that the bounded 6-segment
    # run completes before any excess builds up — observed as a clean
    # zero-loss record (all offered packets consumed, no stall), i.e.
    # the HARNESS failed to create overload, not the pipeline failing
    # to account it.  Such inconclusive runs are retried on a fresh
    # port (bounded); a stall/crash/unaccounted-loss run still fails
    # immediately on its own assertions.
    for attempt, port in enumerate((42161, 42261, 42361)):
        out = tmp_path / f"e2e_overload_{attempt}.jsonl"
        rc = e2e_live.main([
            # rate_x 2.0 = twice the 128 MSa/s wire pace; single-core
            # CPU compute at 2^18 is far slower, so overload is
            # structural, and the 32 KB rcvbuf (= half of one
            # 16-packet block) makes the overflow near-deterministic
            # even when the OS scheduler starves the sender (observed
            # flaky at 256 KB on a 1-core host).  --seconds only paces
            # the sender; --max_segments bounds the run.
            "--seconds", "120", "--rate_x", "2.0", "--log2n", "18",
            "--log2chan", "7", "--port", str(port),
            "--deadline_s", "120",
            "--max_segments", "6", "--rcvbuf_bytes", str(1 << 15),
            "--prefix", str(tmp_path) + f"/out{attempt}_",
            "--out", str(out)])
        assert rc == 0
        rec = json.loads(out.read_text().splitlines()[-1])
        assert rec["segments"] == 6
        # the offered load genuinely exceeded what was drained...
        assert rec["vs_realtime_window"] < rec["rate_x"]
        dropped = rec["metrics_http"].get("segments_dropped", 0)
        if rec["packets_lost"] > 0 or dropped > 0:
            break  # overload materialized and was accounted
    else:
        raise AssertionError(
            f"no accounted loss in {attempt + 1} overload runs: {rec}")
    # the excess is visible as ACCOUNTED loss, not a stall.  Two
    # sanctioned loss channels exist: kernel-buffer overflow surfacing
    # as udp counter-gap loss (packets_lost), or — when the ingest
    # thread keeps draining the socket faster than compute (the
    # Python-receiver fallback on recvmmsg-less sandboxes does) — the
    # overlap engine's DropOldestSegmentBuffer (segments_dropped).
    if rec["packets_lost"]:
        assert 0 < rec["loss_rate"] < 1
        assert rec["packets_total"] > rec["packets_lost"]


def test_plot_dm_curve(tmp_path):
    """The DM-search acceptance plot renders from a trials record."""
    import json

    from srtb_tpu.tools import plot_dm_curve as PD

    rec = {"segment": 0, "timestamp": 0, "best_dm": -478.8,
           "best_snr": 60.0, "dm_list": [-400.0, -478.8, -550.0],
           "peak_snr": [5.0, 60.0, 6.0], "signal_counts": [0, 9, 0],
           "zero_counts": [0, 0, 0]}
    trials = tmp_path / "out_dm_trials.jsonl"
    trials.write_text(json.dumps(rec) + "\n")
    out = PD.plot(str(trials))
    data = open(out, "rb").read()
    assert data[:8] == b"\x89PNG\r\n\x1a\n"


def test_waterfall_service_per_receiver_stream_id(tmp_path):
    """data_stream_id names the PANE for per-receiver (S=1) segments —
    it must not be used as an S index (found live: MultiUdpSource
    receiver 1 crashed the GUI tap on an S=1 waterfall)."""
    cfg = Config(gui_pixmap_width=16, gui_pixmap_height=8)
    svc = WaterfallService(cfg, in_freq=32, in_time=32,
                           out_dir=str(tmp_path))
    wf = np.random.default_rng(3).standard_normal(
        (2, 1, 32, 32)).astype(np.float32)   # [2, S=1, F, T]
    svc.push(wf, data_stream_id=1)           # receiver 1's segment
    path = svc.render_pending()
    assert path is not None and path.endswith("waterfall_s1_000000.png")
    # interleaved formats (S>1) still index by stream
    wf2 = np.random.default_rng(4).standard_normal(
        (2, 2, 32, 32)).astype(np.float32)
    svc.push(wf2, data_stream_id=1)
    assert svc.render_pending().endswith("waterfall_s1_000001.png")
