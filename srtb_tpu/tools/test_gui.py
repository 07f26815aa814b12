"""GUI smoke test: synthetic spectra through the real waterfall service.

The analog of the reference's ``test-gui`` binary
(ref: src/test-gui.cpp:1-128), which pumps generated spectra into the
real image provider to exercise the GUI path without a telescope: this
tool synthesizes dynamic spectra (drifting tones + noise, plus a
dispersed-sweep frame), pushes them through :class:`WaterfallService` in
both provider modes (simple per-segment frames and the legacy scrolling
provider), writes the PNGs, and can briefly serve them over the HTTP
viewer.

Usage:
  python -m srtb_tpu.tools.test_gui [--out DIR] [--frames N]
         [--streams S] [--scroll-lines K] [--http-port P] [--serve-s SEC]
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np

from srtb_tpu.config import Config
from srtb_tpu.utils.logging import log


def synthetic_frame(n_freq: int, n_time: int, seed: int,
                    kind: str = "tones") -> np.ndarray:
    """One synthetic [2, F, T] (re, im) dynamic spectrum.

    ``tones``: noise + a few drifting carriers (test-gui.cpp's moving
    peak); ``sweep``: a quadratic frequency sweep, the shape of a
    dispersed pulse after imperfect dedispersion.
    """
    rng = np.random.default_rng(seed)
    wf = rng.standard_normal((2, n_freq, n_time)).astype(np.float32)
    f = np.arange(n_freq, dtype=np.float32)[:, None]
    t = np.arange(n_time, dtype=np.float32)[None, :]
    if kind == "tones":
        for i in range(3):
            center = (0.2 + 0.3 * i) * n_freq + \
                (n_freq / 8.0) * np.sin(2 * np.pi * (t / n_time + i / 3.0))
            wf[0] += 8.0 * np.exp(-0.5 * ((f - center) / 1.5) ** 2)
    else:
        center = n_freq * (0.9 - 0.8 * (t / n_time) ** 2)
        wf[0] += 10.0 * np.exp(-0.5 * ((f - center) / 2.0) ** 2)
    return wf


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", default="test_gui_out")
    p.add_argument("--frames", type=int, default=8)
    p.add_argument("--streams", type=int, default=2)
    p.add_argument("--freq", type=int, default=256)
    p.add_argument("--time", type=int, default=512)
    p.add_argument("--scroll-lines", type=int, default=16,
                   help="lines per frame for the scrolling provider pass "
                        "(0 disables it)")
    p.add_argument("--http-port", type=int, default=0)
    p.add_argument("--serve-s", type=float, default=2.0)
    args = p.parse_args(argv)

    from srtb_tpu.gui.waterfall import WaterfallService

    os.makedirs(args.out, exist_ok=True)
    base = dict(baseband_input_count=1 << 12, baseband_input_bits=8,
                baseband_reserve_sample=False,
                gui_pixmap_width=640, gui_pixmap_height=360)

    written = []
    # pass 1: simple per-segment provider (SimpleSpectrumImageProvider)
    svc = WaterfallService(Config(**base), args.freq, args.time,
                           out_dir=args.out)
    for i in range(args.frames):
        for s in range(args.streams):
            kind = "sweep" if (i + s) % 3 == 2 else "tones"
            svc.push(synthetic_frame(args.freq, args.time, 97 * i + s,
                                     kind), data_stream_id=s)
            path = svc.render_pending()
            if path:
                written.append(path)

    # pass 2: legacy scrolling provider with the 3n+1 scheduler
    if args.scroll_lines > 0:
        svc2 = WaterfallService(Config(gui_scroll_lines=args.scroll_lines,
                                       **base),
                                args.freq, args.time, out_dir=args.out)
        for i in range(args.frames):
            for s in range(args.streams):
                svc2.push(synthetic_frame(args.freq, args.time,
                                          31 * i + s), data_stream_id=s)
            path = svc2.render_pending()
            if path:
                written.append(path)

    uniq = sorted(set(written))
    log.info(f"[test_gui] wrote {len(uniq)} image file(s) under "
             f"{args.out}: {[os.path.basename(u) for u in uniq]}")
    if not uniq:
        log.error("[test_gui] no frames rendered")
        return 1

    if args.http_port:
        from srtb_tpu.gui.server import WaterfallHTTPServer
        server = WaterfallHTTPServer(args.out, port=args.http_port).start()
        log.info(f"[test_gui] serving {args.out} on port "
                 f"{server.port} for {args.serve_s:.0f}s")
        time.sleep(args.serve_s)
        server.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
