"""Live waterfall HTTP server.

The reference shows live Qt/QML waterfall windows, one per data stream
(ref: gui/gui.hpp:34-67, spectrum_image_provider.hpp, src/main.qml:14-28),
with the event loop repainting as SpectrumImageProvider appends lines.
The headless TPU equivalent: the WaterfallService writes PNG frames and
this stdlib HTTP server serves an *interactive* live view — per-stream
panes that poll ``/frames.json`` and swap the image in place (no page
reload), pause/resume, a history scrubber over the retained frames,
zoom, brightness/contrast (client-side CSS filters over the same
pre-colormapped pixels the reference pushes to its QImage), and a live
metrics bar fed from ``/metrics.json``.  Same interactivity surface as
the QML window — pause, look back, lean in — with no GUI toolkit on
the host.
"""

from __future__ import annotations

import html
import http.server
import json
import os
import re
import threading

from srtb_tpu.utils import termination
from srtb_tpu.utils.logging import log

_INDEX_TEMPLATE = """<!DOCTYPE html>
<html><head><title>srtb_tpu waterfall</title>
<style>
body{{background:#111;color:#eee;font-family:monospace;margin:12px}}
img{{image-rendering:pixelated;border:1px solid #444;display:block}}
.pane{{margin-bottom:14px}}
.bar{{margin:4px 0}}
button{{background:#222;color:#eee;border:1px solid #555;margin-right:4px}}
input[type=range]{{vertical-align:middle}}
#metrics{{color:#8c8;margin-bottom:10px}}
</style></head>
<body><h2>srtb_tpu spectrum waterfall</h2>
<div id="metrics">metrics: …</div>
<div id="panes">{body}</div>
<script>
"use strict";
const panes = {{}};   // stream -> {{paused, pos, frames, img, slider, label}}
// server-rendered pane markup with __S__ placeholders, so a stream that
// starts publishing only after page load still gets a pane (round-3
// advisor catch: tick() used to skip unknown streams forever)
const PANE_HTML = {pane_js};
function addPane(s) {{
  const host = document.createElement("div");
  host.innerHTML = PANE_HTML.replaceAll("__S__", s);
  // no frame name yet: drop the placeholder src (setFrame fills it on
  // the same tick) rather than fetching "/" into the <img>
  host.querySelector("img").removeAttribute("src");
  document.getElementById("panes").appendChild(host.firstElementChild);
  wire(s);
}}
function setFrame(s) {{
  const p = panes[s];
  if (!p.frames.length) return;
  const i = Math.min(p.pos, p.frames.length - 1);
  p.img.src = "/" + p.frames[i];
  p.label.textContent = p.frames[i] +
    (p.paused ? "  [paused]" : "  [live]");
  p.slider.max = p.frames.length - 1;
  p.slider.value = i;
}}
function wire(s) {{
  const el = document.getElementById("pane" + s);
  const p = panes[s] = {{
    paused: false, pos: 0, frames: [],
    img: el.querySelector("img"),
    slider: el.querySelector("input[type=range]"),
    label: el.querySelector(".fname"),
  }};
  el.querySelector(".pause").onclick = (e) => {{
    p.paused = !p.paused;
    e.target.textContent = p.paused ? "resume" : "pause";
    if (!p.paused) p.pos = Math.max(0, p.frames.length - 1);
    setFrame(s);
  }};
  p.slider.oninput = () => {{
    p.paused = true;
    el.querySelector(".pause").textContent = "resume";
    p.pos = +p.slider.value;
    setFrame(s);
  }};
  let zoom = 1;
  el.querySelector(".zin").onclick = () => {{
    zoom = Math.min(8, zoom * 2); p.img.style.width =
      (p.img.naturalWidth * zoom) + "px";
  }};
  el.querySelector(".zout").onclick = () => {{
    zoom = Math.max(0.25, zoom / 2); p.img.style.width =
      (p.img.naturalWidth * zoom) + "px";
  }};
  const bright = el.querySelector(".bright"),
        contrast = el.querySelector(".contrast");
  const filt = () => {{
    p.img.style.filter =
      `brightness(${{bright.value}}%) contrast(${{contrast.value}}%)`;
  }};
  bright.oninput = filt; contrast.oninput = filt;
}}
async function tick() {{
  try {{
    const r = await fetch("/frames.json");
    const data = await r.json();
    for (const s in data.streams) {{
      if (!(s in panes)) addPane(s);
      const p = panes[s];
      p.frames = data.streams[s];
      if (!p.paused) p.pos = Math.max(0, p.frames.length - 1);
      setFrame(s);
    }}
  }} catch (e) {{}}
  try {{
    const m = await (await fetch("/metrics.json")).json();
    const keys = ["segments", "samples", "segments_dropped",
                  "udp_lost_packets", "elapsed_s"];
    document.getElementById("metrics").textContent = "metrics: " +
      keys.filter(k => k in m).map(k => `${{k}}=${{m[k]}}`).join("  ");
  }} catch (e) {{}}
}}
document.querySelectorAll(".pane").forEach(
  el => wire(+el.dataset.stream));
tick(); setInterval(tick, 1000);
</script>
</body></html>
"""

_PANE_TEMPLATE = """<div class="pane" id="pane{s}" data-stream="{s}">
<div>stream {s}: <span class="fname">{name}</span></div>
<div class="bar">
<button class="pause">pause</button>
<button class="zin">zoom+</button>
<button class="zout">zoom-</button>
history <input type="range" min="0" max="0" value="0">
bright <input class="bright" type="range" min="20" max="300"
 value="100">
contrast <input class="contrast" type="range" min="20" max="300"
 value="100">
</div>
<img src="/{name}"></div>
"""


class _Handler(http.server.BaseHTTPRequestHandler):
    directory = "."
    health_stale_after_s = 30.0
    fleet_store_dir = ""  # rollup store surfaced via /fleet

    def log_message(self, *args):  # quiet
        pass

    def _all_frames(self):
        """stream -> frame names sorted by index (the retained history
        the scrubber moves over)."""
        pat = re.compile(r"waterfall_s(\d+)_(\d+)\.png$")
        frames: dict[int, list[tuple[int, str]]] = {}
        try:
            names = os.listdir(self.directory)
        except OSError:
            names = []
        for name in names:
            m = pat.match(name)
            if m:
                frames.setdefault(int(m.group(1)), []).append(
                    (int(m.group(2)), name))
        return {s: [name for _, name in sorted(v)]
                for s, v in frames.items()}

    def _latest_frames(self):
        return {s: v[-1] for s, v in self._all_frames().items() if v}

    def do_GET(self):
        try:
            self._do_get()
        except ConnectionError:
            # browsers abort in-flight <img> loads on every index refresh
            pass

    def _do_get(self):
        if self.path in ("/metrics", "/metrics.json"):
            # live observability beyond the reference's log-only story
            # (SURVEY.md §5.5): JSON snapshot or Prometheus text
            # exposition (counters/gauges, sliding-window rates, and
            # the per-stage wall-clock histograms)
            from srtb_tpu.utils import slo
            from srtb_tpu.utils.metrics import metrics

            # refresh the SLO burn-rate gauges right before the
            # scrape (no-op when no objective is armed), so
            # slo_burn_rate / slo_state are current however long ago
            # the last segment (or /healthz hit) was
            slo.evaluate()
            if self.path == "/metrics.json":
                data = (json.dumps(metrics.snapshot(), sort_keys=True)
                        + "\n").encode()
                ctype = "application/json"
            else:
                data = metrics.prometheus().encode()
                ctype = "text/plain; version=0.0.4"
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
            return
        if self.path == "/healthz":
            # last-segment-age staleness: 503 while the pipeline is
            # wedged (no cooperation needed from the stuck thread),
            # 200 when segments flow or before the first one (startup).
            # Multi-tenant fleet: the payload carries a per-stream
            # breakdown ("streams": {name: {last_segment_age_s, ok}})
            # for every ADMITTED stream, and the endpoint goes 503
            # when ANY of them is stale — one wedged tenant must flip
            # health even while its neighbors keep the global last-
            # segment stamp fresh (utils/telemetry.health).
            from srtb_tpu.utils.telemetry import health

            h = health(stale_after_s=self.health_stale_after_s)
            data = (json.dumps(h, sort_keys=True) + "\n").encode()
            self.send_response(200 if h["ok"] else 503)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
            return
        if self.path == "/fleet":
            # the control tower's status snapshot (obs/status.py):
            # pool member states, per-stream SLO burn,
            # batch occupancy, drift — plus the rollup-store tail
            # when the server was started with fleet_store_dir
            from srtb_tpu.obs.status import fleet_status

            status = fleet_status(store_dir=self.fleet_store_dir)
            data = (json.dumps(status, sort_keys=True)
                    + "\n").encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
            return
        if self.path == "/frames.json":
            data = (json.dumps(
                {"streams": self._all_frames()}) + "\n").encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
            return
        if self.path in ("/", "/index.html"):
            frames = self._latest_frames()
            if frames:
                body = "".join(
                    _PANE_TEMPLATE.format(s=s, name=html.escape(name))
                    for s, name in sorted(frames.items()))
            else:
                body = ('<p>no frames yet (panes appear on first '
                        'refresh with data)</p>'
                        '<meta http-equiv="refresh" content="2">')
            pane_js = json.dumps(
                _PANE_TEMPLATE.format(s="__S__", name=""))
            data = _INDEX_TEMPLATE.format(body=body,
                                          pane_js=pane_js).encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/html")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
            return
        name = os.path.basename(self.path)
        path = os.path.join(self.directory, name)
        if name.endswith(".png") and os.path.exists(path):
            with open(path, "rb") as f:
                data = f.read()
            self.send_response(200)
            self.send_header("Content-Type", "image/png")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
            return
        self.send_response(404)
        self.end_headers()


class WaterfallHTTPServer:
    """Serve the waterfall PNG directory on a background thread.

    The serve thread is supervised (resilience/supervisor.py): if
    ``serve_forever`` dies — a momentary OS-level failure of the
    accept loop — it is restarted with a bounded budget instead of
    silently leaving the observation without its live view.  The GUI
    is best-effort, so the supervisor restarts regardless of the
    error's classification; an exhausted budget logs and gives up
    (never takes the pipeline down)."""

    def __init__(self, directory: str, port: int = 0,
                 address: str = "127.0.0.1",
                 health_stale_after_s: float = 30.0,
                 supervisor=None, fleet_store_dir: str = ""):
        handler = type("Handler", (_Handler,), {
            "directory": directory,
            "health_stale_after_s": health_stale_after_s,
            "fleet_store_dir": fleet_store_dir})
        self._httpd = http.server.ThreadingHTTPServer((address, port),
                                                      handler)
        self.port = self._httpd.server_address[1]
        if supervisor is None:
            from srtb_tpu.resilience.supervisor import Supervisor
            supervisor = Supervisor("gui_server", max_restarts=3,
                                    restart_fatal=True)
        self._supervisor = supervisor
        self._stopping = False
        self._thread = threading.Thread(target=self._serve,
                                        name="srtb-gui-server",
                                        daemon=True)
        termination.tag_thread(self._thread)

    def _serve(self):
        while True:
            try:
                self._httpd.serve_forever()
                return  # shutdown() was called: clean exit
            except Exception as e:  # noqa: BLE001 - supervised restart
                if self._stopping or \
                        not self._supervisor.should_restart(e):
                    log.error(f"[gui] server thread giving up: {e!r}")
                    return

    def start(self) -> "WaterfallHTTPServer":
        self._thread.start()
        log.info(f"[gui] waterfall at http://127.0.0.1:{self.port}/")
        return self

    def stop(self):
        self._stopping = True
        self._httpd.shutdown()
        self._httpd.server_close()
        # join the serve_forever thread: shutdown() only signals it,
        # and an unjoined (if daemon) thread is exactly the leak the
        # sanitizer's thread check exists to catch
        if self._thread.is_alive():
            self._thread.join(timeout=5)
