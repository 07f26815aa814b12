"""Live UDP -> device -> candidates end-to-end harness.

The reference runs its whole graph off live packets in one process
(ref: src/main.cpp:261-271 composes udp_receiver_pipe -> unpack -> fft
-> rfi -> dedisperse -> ... -> write_signal_pipe; README.md:320-322
documents the production deployment).  Ingest soak (udp_soak) and
file-fed compute (benchmark/run.py) each prove half of that; this harness
proves the composition: a paced loopback sender streams dispersed-pulse
baseband packets at a multiple of the real-time wire rate, a
UdpReceiverSource assembles segments, the ThreadedPipeline overlaps
device dispatch with drain, candidates land on disk, and /metrics is
live-served over HTTP throughout.

Emits ONE JSON line (append with --out E2E_LIVE.jsonl).  Throughput is
reported under TWO explicitly-labeled denominators (they differ, and an
ambiguous single number invites the wrong comparison):

  window   -- the offered-load window only: samples drained / wall time
              between "compile done, senders released" and pipeline
              completion.  This is the keep-up-with-the-wire claim and
              the number to compare against rate_x.
  lifetime -- samples / process elapsed since metrics.reset() at harness
              start, i.e. including jit compile and warmup.  This is
              what an operator computing "bytes on disk / wall clock of
              the observation" would see.

  {"harness": "e2e_live", "seconds": window wall, "rate_x": sender pace,
   "segments": N, "msamples_per_s_window": ..., "vs_realtime_window": ...,
   "lifetime_seconds": ..., "msamples_per_s_lifetime": ...,
   "vs_realtime_lifetime": ..., "packets_total": ..., "packets_lost": ...,
   "loss_rate": ..., "signals": ..., "deadline_hits": 0,
   "metrics_http": {...}}

Zero loss + vs_realtime_window >= rate_x means the process kept up with the
offered load end to end; deadline_hits is 0 by construction when the
line is emitted at all (a tripped segment_deadline_s aborts loudly,
the reference's fail-fast philosophy).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import struct
import sys
import threading
import time

from srtb_tpu.config import Config
from srtb_tpu.io import formats
from srtb_tpu.utils.logging import log


def _sender(port: int, fmt, payload_segment: bytes, pace_pps: float,
            started: threading.Event, stop: threading.Event):
    """Stream ``payload_segment`` cyclically as counter-sequential packets
    at ``pace_pps``, then trail off slowly so the receiver's in-progress
    block completes (same flush trick as udp_soak)."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.connect(("127.0.0.1", port))
    payload = fmt.payload_bytes
    n_slices = len(payload_segment) // payload
    header_size = fmt.packet_header_size

    def send(c):
        head = struct.pack("<Q", c) + b"\x00" * (header_size - 8) \
            if header_size >= 8 else b""
        off = (c % n_slices) * payload
        try:
            sock.send(head + payload_segment[off:off + payload])
        except OSError:
            pass  # kernel buffer overflow surfaces as counter-gap loss

    started.wait()
    chunk = 32
    t0 = time.perf_counter()
    c = 0
    while not stop.is_set():
        send(c)
        c += 1
        if c % chunk == 0:
            lag = c / pace_pps - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)
    for _ in range(4 * 64):  # flush any partially-assembled block
        send(c)
        c += 1
        time.sleep(0.0005)
    sock.close()


def run(args) -> dict:
    import numpy as np

    from srtb_tpu.gui.server import WaterfallHTTPServer
    from srtb_tpu.io.synth import make_dispersed_baseband
    from srtb_tpu.io.udp import UdpReceiverSource
    from srtb_tpu.pipeline.runtime import ThreadedPipeline
    from srtb_tpu.utils.metrics import metrics

    n = 1 << args.log2n
    ports = [args.port + i for i in range(args.receivers)]
    cfg = Config(
        baseband_input_count=n,
        baseband_input_bits=2,
        baseband_format_type="fastmb_roach2",
        baseband_freq_low=1405.0 + 32.0,
        baseband_bandwidth=-64.0,
        baseband_sample_rate=128e6,
        dm=-478.80,
        spectrum_channel_count=1 << args.log2chan,
        signal_detect_signal_noise_threshold=8.0,
        signal_detect_max_boxcar_length=64,
        mitigate_rfi_spectral_kurtosis_threshold=1.05,
        baseband_reserve_sample=False,
        baseband_output_file_prefix=args.prefix,
        udp_receiver_address=["127.0.0.1"] * len(ports),
        udp_receiver_port=ports,
        udp_packet_provider=args.provider,
        udp_receiver_rcvbuf_bytes=args.rcvbuf_bytes,
        segment_deadline_s=args.deadline_s,
        fft_strategy=args.fft_strategy,
    )
    fmt = formats.resolve(cfg.baseband_format_type)
    metrics.reset()

    # one segment of J1644-parameter baseband with a centered dispersed
    # pulse, streamed cyclically -> every assembled segment carries a
    # detectable pulse wherever the cycle boundary lands... conservative:
    # pulses at 1/4 and 3/4 so any rotation keeps one intact
    seg_bytes = cfg.segment_bytes(1)
    payload_segment = make_dispersed_baseband(
        n, cfg.baseband_freq_low, cfg.baseband_bandwidth, cfg.dm,
        pulse_positions=[n // 4, 3 * n // 4], pulse_amp=40.0,
        nbits=2, seed=5).tobytes()
    assert len(payload_segment) == seg_bytes

    real_time_bps = cfg.baseband_sample_rate * 2 / 8  # 2-bit payload
    pace_pps = args.rate_x * real_time_bps / fmt.payload_bytes
    if args.max_segments > 0:
        # explicit cap (overload runs: the auto formula assumes the
        # pipeline keeps up, which is exactly what an overload test
        # disproves — the run must still terminate)
        expected_segments = args.max_segments
    else:
        expected_segments = max(1, int(
            args.seconds * args.rate_x * cfg.baseband_sample_rate / n)) \
            * len(ports)  # each receiver contributes its own stream

    started = threading.Event()
    stop = threading.Event()
    senders = [threading.Thread(
        target=_sender, args=(port, fmt, payload_segment, pace_pps,
                              started, stop),
        name=f"e2e-live-sender-{port}", daemon=True) for port in ports]
    for s in senders:
        s.start()

    # serve the directory the WaterfallService writes frames into, not
    # the file prefix itself (with the default prefix /tmp/e2e_live/out_
    # that "directory" doesn't exist and /frames.json stays empty)
    http_srv = WaterfallHTTPServer(os.path.dirname(args.prefix) or ".",
                                   port=args.http_port).start()
    if len(ports) > 1:
        # the reference's production shape: one udp_receiver_pipe per
        # polarization (ref: main.cpp:261-271) -> MultiUdpSource
        from srtb_tpu.io.udp import MultiUdpSource
        src = MultiUdpSource(cfg)
    else:
        src = UdpReceiverSource(cfg)
    # lossy waterfall tap (the reference streams its QML waterfall from
    # the same live pipeline, ref: main.cpp + spectrum_image_provider):
    # keep the device handle, but fetch + render at most every
    # --gui_min_interval_s so a slow render can never backpressure the
    # wire-rate drain — frames in between are simply dropped
    waterfall_service = None
    gui_frames = [0]
    if args.gui:
        import glob

        from srtb_tpu.gui.waterfall import WaterfallService
        # clear stale frames from a prior run of the same prefix: the
        # served-frames self-check below must count THIS run's renders,
        # not last run's leftovers
        for old in glob.glob(os.path.join(
                os.path.dirname(args.prefix) or ".",
                "waterfall_s*_*.png")):
            try:
                os.remove(old)
            except OSError:
                pass
        n_spec = n // 2
        nchan = min(cfg.spectrum_channel_count, n_spec)
        waterfall_service = WaterfallService(
            cfg, in_freq=nchan, in_time=n_spec // nchan,
            out_dir=os.path.dirname(args.prefix) or ".")
    # keep_waterfall stays False: only the tap (wants_waterfall) sees
    # the handle — the candidate writer must NOT start dumping a
    # full waterfall .npy per positive segment during a rate benchmark
    pipe = ThreadedPipeline(cfg, source=src, keep_waterfall=False)
    if waterfall_service is not None:
        last_render = [0.0]

        class _LossyTap:
            wants_waterfall = True

            def push(self, work, has_signal):
                now = time.perf_counter()
                if (work.waterfall is None
                        or now - last_render[0] < args.gui_min_interval_s):
                    return
                last_render[0] = now
                waterfall_service.push(work.waterfall,
                                       work.segment.data_stream_id)
                waterfall_service.render_pending()
                gui_frames[0] += 1
        pipe.sinks.append(_LossyTap())
    try:
        # compile BEFORE offering load: the first jit of the segment
        # program takes seconds (CPU) to minutes (TPU, cold), during
        # which nothing drains and the kernel socket buffer overflows —
        # measured 2.9% startup loss at even 0.05x rate without this
        warm = np.frombuffer(payload_segment, dtype=np.uint8)
        wf, det = pipe.processor.process(warm)
        np.asarray(det.signal_counts)
        del wf, det
        log.info("[e2e_live] pipeline compiled; starting offered load")
        started.set()
        t0 = time.perf_counter()
        stats = pipe.run(max_segments=expected_segments)
        wall = time.perf_counter() - t0
    finally:
        stop.set()
        for s in senders:
            s.join(timeout=5)
        src.close()
        pipe.close()

    # live /metrics snapshot over real HTTP, part of what this proves
    import urllib.request
    with urllib.request.urlopen(
            f"http://127.0.0.1:{http_srv.port}/metrics.json",
            timeout=10) as r:
        metrics_http = json.loads(r.read().decode())
    gui_frames_served = None
    if args.gui:
        # self-verifying: the server must actually list the frames the
        # tap rendered (regression guard for serving the wrong
        # directory, where /frames.json stayed empty forever)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{http_srv.port}/frames.json",
                timeout=10) as r:
            streams = json.loads(r.read().decode()).get("streams", {})
        gui_frames_served = sum(len(v) for v in streams.values())
    http_srv.stop()

    total = metrics_http.get("packets_total", 0.0)
    lost = metrics_http.get("packets_lost", 0.0)
    # window: the offered-load window (post-compile); lifetime: metrics
    # clock since reset() at harness start, incl. compile/warmup.  Both
    # labeled — see module docstring for which claim each supports.
    window_msps = stats.samples / wall / 1e6 if wall else 0.0
    lifetime_s = metrics_http.get("elapsed_s", 0.0)
    lifetime_msps = metrics_http.get("msamples_per_sec", 0.0)
    out = {
        "harness": "e2e_live",
        "seconds": round(wall, 1),
        "rate_x": args.rate_x,
        "log2n": args.log2n,
        "receivers": len(ports),
        "provider": args.provider,
        "segments": stats.segments,
        "msamples_per_s_window": round(window_msps, 1),
        "vs_realtime_window": round(window_msps * 1e6
                                    / cfg.baseband_sample_rate, 3),
        "lifetime_seconds": round(lifetime_s, 1),
        "msamples_per_s_lifetime": round(lifetime_msps, 1),
        "vs_realtime_lifetime": round(lifetime_msps * 1e6
                                      / cfg.baseband_sample_rate, 3),
        "packets_total": int(total),
        "packets_lost": int(lost),
        "loss_rate": round(lost / total, 6) if total else None,
        "signals": stats.signals,
        "deadline_s": args.deadline_s,
        "deadline_hits": 0,  # a hit aborts before this line is reached
        "gui_frames": gui_frames[0] if waterfall_service else None,
        "gui_frames_served": gui_frames_served,
        "metrics_http": metrics_http,
    }
    try:
        import jax
        out["platform"] = jax.default_backend()
    except Exception:  # pragma: no cover
        pass
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seconds", type=float, default=60.0,
                   help="offered-load duration (sender keeps this pace)")
    p.add_argument("--rate_x", type=float, default=2.0,
                   help="sender pace as a multiple of the 128 MSa/s "
                        "real-time wire rate")
    p.add_argument("--log2n", type=int, default=24)
    p.add_argument("--log2chan", type=int, default=11)
    p.add_argument("--port", type=int, default=42150)
    p.add_argument("--receivers", type=int, default=1,
                   help="N receivers on ports port..port+N-1 "
                        "(MultiUdpSource, the reference's per-pol shape)")
    p.add_argument("--http_port", type=int, default=0)
    p.add_argument("--provider", default="recvmmsg",
                   choices=["recvmmsg", "packet_ring", "recvfrom",
                            "asyncio"])
    p.add_argument("--deadline_s", type=float, default=0.0)
    p.add_argument("--rcvbuf_bytes", type=int, default=1 << 28,
                   help="SO_RCVBUF request for the receiver sockets "
                        "(small values make overload surface as prompt "
                        "accounted loss)")
    p.add_argument("--max_segments", type=int, default=0,
                   help="stop after this many drained segments "
                        "(0 = derive from --seconds and --rate_x; "
                        "required for overload runs, where the offered "
                        "load exceeds the compute rate by design)")
    p.add_argument("--fft_strategy", default="auto")
    p.add_argument("--gui", action="store_true",
                   help="lossy waterfall tap + renderer during the run")
    p.add_argument("--gui_min_interval_s", type=float, default=0.5)
    p.add_argument("--prefix", default="/tmp/e2e_live/out_")
    p.add_argument("--out", default="",
                   help="append the JSON line to this file too")
    args = p.parse_args(argv)

    import os
    os.makedirs(os.path.dirname(args.prefix) or ".", exist_ok=True)
    result = run(args)
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps({
                "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                **result}) + "\n")
    log.info(f"[e2e_live] {result['segments']} segments, "
             f"{result['vs_realtime_window']}x real-time (window), "
             f"{result['vs_realtime_lifetime']}x (lifetime), "
             f"loss {result['loss_rate']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
